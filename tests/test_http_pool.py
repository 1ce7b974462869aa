"""WorkerPoolHTTPServer hardening: bounded workers behind an admission
gate, per-request read deadlines, saturation shedding with a live health
lane, keep-alive parking, graceful FIN shutdown with no thread leak, and
wire-context propagation over the real socket."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from lighthouse_tpu.api.http_api import (
    _ERRORS_TOTAL,
    _SHED_TOTAL,
    _TIMEOUTS_TOTAL,
    BeaconApiHandler,
    resolve_http_request_timeout,
    resolve_http_threads,
    serve,
)
from lighthouse_tpu.chain.beacon_chain import BeaconChain
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.testing.harness import StateHarness, clone_state
from lighthouse_tpu.types.spec import minimal_spec

VALIDATORS = 16


def _chain():
    bls.set_backend("fake")
    spec = minimal_spec()
    harness = StateHarness.new(spec, VALIDATORS)
    return BeaconChain(spec, clone_state(harness.state, spec))


@pytest.fixture(scope="module")
def chain():
    return _chain()


def _raw_get(port, path, extra_headers=(), timeout=5.0):
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        req = f"GET {path} HTTP/1.1\r\nHost: t\r\nConnection: close\r\n"
        for h in extra_headers:
            req += h + "\r\n"
        s.sendall(req.encode() + b"\r\n")
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
        return buf
    finally:
        s.close()


def _read_one_response(s):
    """Read exactly one HTTP response (headers + Content-Length body) off
    a keep-alive socket, leaving the connection open."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = s.recv(65536)
        if not chunk:
            return buf
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    length = 0
    for line in head.split(b"\r\n"):
        if line.lower().startswith(b"content-length:"):
            length = int(line.split(b":", 1)[1])
    while len(rest) < length:
        chunk = s.recv(65536)
        if not chunk:
            break
        rest += chunk
    return head + b"\r\n\r\n" + rest


def _http_threads_alive():
    return [t for t in threading.enumerate()
            if t.name.startswith(("http-worker", "http-shedder",
                                  "http-parker"))]


# ------------------------------------------------------------- resolvers


def test_http_knob_resolution(monkeypatch):
    assert resolve_http_threads(3) == 3
    assert resolve_http_threads(0) == 1          # floor
    monkeypatch.setenv("LIGHTHOUSE_TPU_HTTP_THREADS", "5")
    assert resolve_http_threads() == 5
    assert resolve_http_threads(2) == 2          # explicit beats env
    monkeypatch.delenv("LIGHTHOUSE_TPU_HTTP_THREADS")
    assert resolve_http_threads() == 8
    monkeypatch.setenv("LIGHTHOUSE_TPU_HTTP_REQUEST_TIMEOUT", "3.5")
    assert resolve_http_request_timeout() == 3.5
    assert resolve_http_request_timeout(1.25) == 1.25
    monkeypatch.delenv("LIGHTHOUSE_TPU_HTTP_REQUEST_TIMEOUT")
    assert resolve_http_request_timeout() == 10.0


# ---------------------------------------------------------- bounded pool


def test_pool_is_bounded_and_keepalive_parks(chain):
    before = len(_http_threads_alive())
    server, thread, port = serve(chain, http_threads=2,
                                 request_timeout=1.0)
    try:
        # exactly N workers + shedder + parker, regardless of traffic
        assert len(_http_threads_alive()) - before == 2 + 2
        from lighthouse_tpu.api.client import BeaconNodeHttpClient

        c = BeaconNodeHttpClient(f"http://127.0.0.1:{port}")
        for _ in range(5):
            c._get("/eth/v1/node/version")
        c.close()
        assert len(_http_threads_alive()) - before == 2 + 2
        # one TCP connection served all five requests: the keep-alive
        # socket parked between requests and re-admitted through the gate
        assert server.stats["accepted"] == 1
        # a worker counts a request after it has answered it, so the fifth
        # may still be on its way to the counter when its response is here
        deadline = time.monotonic() + 3.0
        while server.stats["handled"] < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.stats["handled"] == 5
        assert server.stats["requeued"] == 4
    finally:
        server.shutdown()
    assert len(_http_threads_alive()) == before


def test_shutdown_leaks_no_threads_across_cycles(chain):
    before = len(_http_threads_alive())
    for _ in range(3):
        server, thread, port = serve(chain, http_threads=3,
                                     request_timeout=0.5)
        _raw_get(port, "/eth/v1/node/version")
        server.shutdown()
        thread.join(timeout=5.0)
    assert len(_http_threads_alive()) == before


# ------------------------------------------------------- read deadlines


def test_slow_loris_header_deadline(chain):
    server, thread, port = serve(chain, http_threads=1,
                                 request_timeout=0.3)
    try:
        base = _TIMEOUTS_TOTAL.labels("header").value
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        s.sendall(b"GET /eth/v1/node/version HTTP/1.1\r\nX-Drip: ")
        s.settimeout(3.0)
        # the worker's read deadline fires and the server closes on us —
        # the worker is NOT pinned forever
        assert s.recv(4096) == b""
        s.close()
        deadline = time.monotonic() + 3.0
        while (_TIMEOUTS_TOTAL.labels("header").value <= base
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert _TIMEOUTS_TOTAL.labels("header").value > base
        # and the pool still serves the next request
        assert b"200 OK" in _raw_get(port, "/eth/v1/node/version")
    finally:
        server.shutdown()


def test_stalled_body_deadline_408(chain):
    server, thread, port = serve(chain, http_threads=1,
                                 request_timeout=0.3)
    try:
        base = _TIMEOUTS_TOTAL.labels("body").value
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        s.sendall(b"POST /eth/v1/beacon/pool/attestations HTTP/1.1\r\n"
                  b"Host: t\r\nContent-Type: application/json\r\n"
                  b"Content-Length: 512\r\n\r\n[{")
        s.settimeout(3.0)
        buf = b""
        try:
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        except TimeoutError:
            pass
        s.close()
        assert b"408" in buf.split(b"\r\n", 1)[0]
        assert _TIMEOUTS_TOTAL.labels("body").value > base
    finally:
        server.shutdown()


# ------------------------------------------------------------- shedding


def test_saturated_pool_sheds_503_but_health_answers(chain):
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    RECORDER.reset()
    # long request timeout so the single worker stays pinned on the loris
    # connection for the whole test — the queue never drains
    server, thread, port = serve(chain, http_threads=1,
                                 request_timeout=5.0)
    loris = []
    idle = []
    try:
        # pin the single worker with a half-sent request...
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        s.sendall(b"GET /x HTTP/1.1\r\nX-Drip: ")
        loris.append(s)
        time.sleep(0.1)
        # ...fill the bounded admission queue EXACTLY with idle
        # connections (none spill to the shed lane, so the shedder stays
        # free to answer instantly)...
        for _ in range(server._queue.maxsize):
            c = socket.create_connection(("127.0.0.1", port), timeout=5.0)
            idle.append(c)
        time.sleep(0.1)
        base_shed = server.stats["shed"]
        # ...now real requests land on the shed lane: 503 + Retry-After
        resp = _raw_get(port, "/eth/v1/node/syncing", timeout=5.0)
        head, _, body = resp.partition(b"\r\n\r\n")
        assert b"503" in head.split(b"\r\n", 1)[0]
        assert b"Retry-After:" in head
        assert json.loads(body)["code"] == 503
        assert server.stats["shed"] > base_shed
        # the health-exempt route answers INLINE off the shed lane while
        # the pool is saturated — liveness probes see the node alive
        hresp = _raw_get(port, "/eth/v1/node/health", timeout=5.0)
        assert hresp.split(b"\r\n", 1)[0].split()[1] in (b"200", b"206")
        assert server.stats["health_shed_path"] >= 1
        # the saturation edge left a flight-recorder event
        kinds = [e["kind"] for e in RECORDER.events(last=64)]
        assert "http_api_saturated" in kinds
    finally:
        for s in loris + idle:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()


def test_shed_total_counts_by_reason(chain):
    shed_before = {
        r: _SHED_TOTAL.labels(r).value
        for r in ("saturated", "overflow", "shutdown")
    }
    server, thread, port = serve(chain, http_threads=1,
                                 request_timeout=0.5)
    socks = []
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        s.sendall(b"GET /x HTTP/1.1\r\nX-Drip: ")
        socks.append(s)
        time.sleep(0.05)
        for _ in range(server._queue.maxsize
                       + server._shed_queue.maxsize + 6):
            c = socket.create_connection(("127.0.0.1", port), timeout=5.0)
            c.sendall(b"GET /eth/v1/node/version HTTP/1.1\r\nHost: t\r\n"
                      b"Connection: close\r\n\r\n")
            socks.append(c)
        deadline = time.monotonic() + 4.0
        while (time.monotonic() < deadline
               and _SHED_TOTAL.labels("saturated").value
               <= shed_before["saturated"]):
            time.sleep(0.05)
        assert (_SHED_TOTAL.labels("saturated").value
                > shed_before["saturated"])
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()


# ----------------------------------------------------- graceful shutdown


def test_shutdown_completes_in_flight_and_fins_parked(chain):
    server, thread, port = serve(chain, http_threads=2,
                                 request_timeout=1.0)
    # a parked keep-alive connection (request 1 done, socket held open)
    ka = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    ka.sendall(b"GET /eth/v1/node/version HTTP/1.1\r\nHost: t\r\n\r\n")
    ka.settimeout(5.0)
    first = _read_one_response(ka)
    assert b"200 OK" in first

    # an in-flight request racing shutdown: a handler that takes a beat
    import lighthouse_tpu.api.http_api as http_api

    idx = next(i for i, (_p, _m, fn) in enumerate(http_api._ROUTES)
               if fn.__name__ == "get_version")
    real = http_api._ROUTES[idx]

    def get_version(self):
        time.sleep(0.3)
        return real[2](self)

    http_api._ROUTES[idx] = (real[0], real[1], get_version)
    results = {}

    def fire():
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/eth/v1/node/version", timeout=5.0
            ) as r:
                results["status"] = r.status
                results["body"] = r.read()
        except Exception as e:  # noqa: BLE001
            results["error"] = repr(e)

    t = threading.Thread(target=fire)
    t.start()
    time.sleep(0.1)   # let the request reach the worker
    try:
        server.shutdown()
        t.join(timeout=5.0)
        # the in-flight request completed across the shutdown
        assert results.get("status") == 200, results
        # the parked connection was closed with FIN, not RST: EOF, no
        # ECONNRESET
        assert ka.recv(4096) == b""
    finally:
        http_api._ROUTES[idx] = real
        ka.close()


def test_late_arrival_during_shutdown_is_clean(chain):
    server, thread, port = serve(chain, http_threads=1,
                                 request_timeout=0.5)
    server._stop.set()   # shutdown has begun; accept loop still alive
    resp = _raw_get(port, "/eth/v1/node/syncing", timeout=5.0)
    assert b"503" in resp.split(b"\r\n", 1)[0]
    server.shutdown()


# --------------------------------------------- wire context + 500 stages


def test_trace_ctx_header_adopted_and_echoed(chain):
    from lighthouse_tpu.observability.propagation import (
        WireTraceContext,
        decode_ctx,
        encode_ctx,
    )
    from lighthouse_tpu.observability.trace import Tracer

    tracer = Tracer(ring_size=64)
    server, thread, port = serve(chain, tracer=tracer)
    try:
        ctx = WireTraceContext(origin="producer@test", trace_id=7,
                               slot=3, seq=9, sent_at=1.5)
        raw = _raw_get(
            port, "/eth/v1/node/version",
            extra_headers=(f"X-LH-Trace-Ctx: {encode_ctx(ctx).hex()}",),
        )
        head = raw.split(b"\r\n\r\n", 1)[0].decode()
        echoed = None
        for line in head.split("\r\n"):
            if line.lower().startswith("x-lh-trace-ctx:"):
                echoed = line.split(":", 1)[1].strip()
        assert echoed, "response must echo the wire context"
        back = decode_ctx(bytes.fromhex(echoed))
        assert back.causal_id() == ctx.causal_id()
        # the serve-side trace adopted the producer's context
        traces = [tr for tr in tracer.snapshot_ring()
                  if tr.kind == "http_serve"]
        assert traces
        assert traces[-1].meta.get("origin") == "producer@test"
        # garbage context must never fail the request it rode in on
        raw = _raw_get(port, "/eth/v1/node/version",
                       extra_headers=("X-LH-Trace-Ctx: zz-not-hex",))
        assert b"200 OK" in raw
    finally:
        server.shutdown()


def test_handler_fault_500_envelope_and_stage_counter(chain):
    import lighthouse_tpu.api.http_api as http_api

    base = _ERRORS_TOTAL.labels("handler").value

    def get_syncing(self):  # name keeps the route label stable
        raise RuntimeError("wedged backend")

    # the route table binds handler functions directly — swap the entry
    idx = next(i for i, (_p, _m, fn) in enumerate(http_api._ROUTES)
               if fn.__name__ == "get_syncing")
    real = http_api._ROUTES[idx]
    http_api._ROUTES[idx] = (real[0], real[1], get_syncing)
    server, thread, port = serve(chain)
    try:
        raw = _raw_get(port, "/eth/v1/node/syncing")
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"500" in head.split(b"\r\n", 1)[0]
        env = json.loads(body)
        # the error envelope shape: code + message, and the counter
        # attributes the fault to the handler stage
        assert env["code"] == 500
        assert "wedged backend" in env["message"]
        assert _ERRORS_TOTAL.labels("handler").value == base + 1
    finally:
        http_api._ROUTES[idx] = real
        server.shutdown()


def test_undecodable_publish_counts_decode_stage(chain):
    base = _ERRORS_TOTAL.labels("block_ssz_decode").value
    server, thread, port = serve(chain)
    try:
        body = json.dumps({"ssz": "0xdeadbeef"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.server_address[1]}"
            "/eth/v2/beacon/blocks",
            data=body, headers={"Content-Type": "application/json"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc.value.code == 400
        env = json.loads(exc.value.read())
        assert env["code"] == 400
        assert _ERRORS_TOTAL.labels("block_ssz_decode").value == base + 1
    finally:
        server.shutdown()


def test_rejected_slashing_counts_verify_stage(chain):
    from lighthouse_tpu.state_transition.slot import types_for_slot

    base = _ERRORS_TOTAL.labels("proposer_slashing_verify").value
    server, thread, port = serve(chain)
    try:
        types = types_for_slot(chain.spec, chain.current_slot)
        # structurally-valid SSZ (decodes fine) that fails pool
        # verification: two identical zeroed headers are not slashable
        raw = types.ProposerSlashing.serialize(
            types.ProposerSlashing.default()
        )
        body = json.dumps({"ssz": "0x" + raw.hex()}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/eth/v1/beacon/pool/proposer_slashings",
            data=body, headers={"Content-Type": "application/json"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc.value.code == 400
        env = json.loads(exc.value.read())
        assert "invalid proposer slashing" in env["message"]
        assert (_ERRORS_TOTAL.labels("proposer_slashing_verify").value
                == base + 1)
    finally:
        server.shutdown()
