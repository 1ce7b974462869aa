"""BeaconNodeHttpClient hardening: Retry-After parsing bounds, the
429/503 rate-limit mapping, per-phase timeout classification (connect /
read / stalled body), and the stale-pooled-socket retry-once rule."""

import math
import socket
import threading

import pytest

from lighthouse_tpu.api.client import (
    HTTP_CLIENT_CONNECTIONS,
    HTTP_CLIENT_TIMEOUTS,
    RETRY_AFTER_CAP,
    RETRY_AFTER_DEFAULT,
    BeaconNodeHttpClient,
    _http_error,
    parse_retry_after,
)
from lighthouse_tpu.validator.beacon_node import (
    BeaconNodeError,
    NodeRateLimited,
    NodeTimeout,
)


# ---------------------------------------------------- Retry-After parsing


@pytest.mark.parametrize(
    ("raw", "want"),
    [
        ("2.5", 2.5),
        ("0", 0.0),
        ("30", 30.0),
        # absent / unparsable fall back to the default, never crash
        (None, RETRY_AFTER_DEFAULT),
        ("", RETRY_AFTER_DEFAULT),
        ("abc", RETRY_AFTER_DEFAULT),
        ("Fri, 07 Aug 2026 12:00:00 GMT", RETRY_AFTER_DEFAULT),
        # non-finite floats parse but must not poison backoff arithmetic
        ("nan", RETRY_AFTER_DEFAULT),
        ("inf", RETRY_AFTER_DEFAULT),
        ("-inf", RETRY_AFTER_DEFAULT),
        # negatives clamp up to zero, absurd values clamp to the cap
        ("-5", 0.0),
        ("10000", RETRY_AFTER_CAP),
        ("1e300", RETRY_AFTER_CAP),
    ],
)
def test_parse_retry_after_matrix(raw, want):
    got = parse_retry_after(raw)
    assert math.isfinite(got)
    assert got == want


def test_http_error_rate_limit_mapping():
    e = _http_error("GET", "/x", 429, {"Retry-After": "7"}, b"")
    assert isinstance(e, NodeRateLimited)
    assert e.retry_after == 7.0
    # a 503 that names a Retry-After is the server shedding load — same
    # backoff contract as a 429
    e = _http_error("GET", "/x", 503, {"Retry-After": "1"}, b"")
    assert isinstance(e, NodeRateLimited)
    assert e.retry_after == 1.0
    # a bare 503 (or any other status) stays a hard error
    e = _http_error("GET", "/x", 503, {}, b"down")
    assert isinstance(e, BeaconNodeError)
    assert not isinstance(e, NodeRateLimited)
    assert isinstance(_http_error("GET", "/x", 500, {}, b""),
                      BeaconNodeError)


# --------------------------------------------------- raw-socket fixtures


class RawServer:
    """Scripted one-thread server: each accepted connection runs the
    user-provided handler(sock). For forcing the exact socket behaviours
    (no response, stalled body, close-after-response) a real handler
    never produces."""

    def __init__(self, handler):
        self.handler = handler
        self.listener = socket.socket()
        self.listener.bind(("127.0.0.1", 0))
        self.listener.listen(8)
        self.port = self.listener.getsockname()[1]
        self._stop = False
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def _loop(self):
        while not self._stop:
            try:
                sock, _ = self.listener.accept()
            except OSError:
                return
            try:
                self.handler(sock)
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self.listener.close()
        except OSError:
            pass


def _read_request(sock):
    sock.settimeout(5.0)
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            return buf
        buf += chunk
    return buf


# ------------------------------------------------ timeout classification


def test_read_timeout_classified(chain=None):
    def never_respond(sock):
        _read_request(sock)
        import time

        time.sleep(0.5)
        sock.close()

    srv = RawServer(never_respond)
    base = HTTP_CLIENT_TIMEOUTS.labels("read").value
    c = BeaconNodeHttpClient(f"http://127.0.0.1:{srv.port}", timeout=0.1)
    try:
        with pytest.raises(NodeTimeout, match="response timed out"):
            c._get("/eth/v1/node/version")
        assert HTTP_CLIENT_TIMEOUTS.labels("read").value == base + 1
    finally:
        c.close()
        srv.close()


def test_stalled_body_timeout_classified():
    def stall_body(sock):
        _read_request(sock)
        sock.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: 4096\r\n\r\nab")
        import time

        time.sleep(0.5)
        sock.close()

    srv = RawServer(stall_body)
    base = HTTP_CLIENT_TIMEOUTS.labels("body").value
    c = BeaconNodeHttpClient(f"http://127.0.0.1:{srv.port}", timeout=0.1)
    try:
        with pytest.raises(NodeTimeout, match="body stalled"):
            c._get("/eth/v1/node/version")
        assert HTTP_CLIENT_TIMEOUTS.labels("body").value == base + 1
    finally:
        c.close()
        srv.close()


def test_connection_refused_is_hard_error_not_timeout():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()   # nobody listening here now
    c = BeaconNodeHttpClient(f"http://127.0.0.1:{port}", timeout=0.3)
    try:
        with pytest.raises(BeaconNodeError) as exc:
            c._get("/eth/v1/node/version")
        assert not isinstance(exc.value, NodeTimeout)
    finally:
        c.close()


# --------------------------------------------------- stale-socket retry


def test_stale_pooled_socket_retries_once():
    served = []

    def one_then_close(sock):
        _read_request(sock)
        # counted before the response goes out: the client asserts on the
        # count as soon as it has the response, in another thread
        served.append(1)
        body = b'{"data": {"version": "raw/1"}}'
        sock.sendall(
            b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body
        )
        # keep-alive implied (HTTP/1.1, no Connection: close), but the
        # server hangs up right after — the pooled socket goes stale
        sock.close()

    srv = RawServer(one_then_close)
    base = HTTP_CLIENT_CONNECTIONS.labels("stale_retry").value
    c = BeaconNodeHttpClient(f"http://127.0.0.1:{srv.port}", timeout=2.0)
    try:
        assert c._get("/eth/v1/node/version")["data"]["version"] == "raw/1"
        # second request rides the stale pooled socket, hits the
        # disconnect, and silently retries ONCE on a fresh connection
        assert c._get("/eth/v1/node/version")["data"]["version"] == "raw/1"
        assert HTTP_CLIENT_CONNECTIONS.labels("stale_retry").value \
            == base + 1
        # the stale attempt touched no new server connection — only the
        # first request and the fresh-retry reached the handler
        assert len(served) == 2
    finally:
        c.close()
        srv.close()


def test_fresh_socket_disconnect_does_not_retry():
    def slam(sock):
        _read_request(sock)
        sock.close()   # no response at all, on a FRESH connection

    srv = RawServer(slam)
    c = BeaconNodeHttpClient(f"http://127.0.0.1:{srv.port}", timeout=2.0)
    try:
        with pytest.raises(BeaconNodeError):
            c._get("/eth/v1/node/version")
    finally:
        c.close()
        srv.close()
