"""Pipelined dispatch executor (crypto/jaxbls/pipeline.py) — host-only.

Everything here runs on stub handles and the pure-python BLS backend:
no jax compiles, no device. Covered: FIFO ordering/continuation
correctness at depth 4 under out-of-order device resolves, the
backpressure window (admit blocks by resolving the oldest), donation
safety (no use-after-donate on the retry / breaker-open fallback
paths), the urgent lane's bypass of the batch window, knob resolution
precedence, and the labeled jaxbls_pipeline_* metric families."""

import threading

import pytest

from lighthouse_tpu.crypto.jaxbls import pipeline as pl
from lighthouse_tpu.utils.metrics import REGISTRY


class StubHandle:
    """Fake device handle: records the order result() fires in."""

    resolved: list = []   # class-level log, reset per test via fixture

    def __init__(self, tag, value=True, error=None):
        self.tag = tag
        self.value = value
        self.error = error

    def result(self):
        StubHandle.resolved.append(self.tag)
        if self.error is not None:
            raise self.error
        return self.value


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    StubHandle.resolved = []
    monkeypatch.delenv("LIGHTHOUSE_TPU_PIPELINE_DEPTH", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_DONATE", raising=False)
    from lighthouse_tpu.autotune import runtime

    runtime.clear()
    yield
    runtime.clear()


def _dispatcher(depth):
    return pl.PipelinedDispatcher(depth=depth)


# ------------------------------------------------------- ordering & depth


def test_depth4_fifo_continuations_under_out_of_order_resolves():
    """Six batches through a depth-4 window; the CALLER resolves the
    newest ticket first (device batches can materialize out of
    order). Continuations must still run in submission order,
    and the window must never exceed depth 4."""
    d = _dispatcher(4)
    done = []
    tickets = []
    for i in range(6):
        tickets.append(
            d.submit(
                lambda i=i: StubHandle(i),
                continuation=lambda v, i=i: done.append(i),
            )
        )
    # submits 4 and 5 admitted by resolving the two oldest
    assert StubHandle.resolved == [0, 1]
    assert done == [0, 1]
    assert d.inflight() == 4

    # newest-first caller order: FIFO drains 2,3,4 before 5 resolves
    assert tickets[5].result() is True
    assert StubHandle.resolved == [0, 1, 2, 3, 4, 5]
    assert done == [0, 1, 2, 3, 4, 5]
    assert d.inflight() == 0
    # idempotent re-read, in any order
    assert tickets[2].result() is True
    assert StubHandle.resolved == [0, 1, 2, 3, 4, 5]


def test_admit_blocks_exactly_at_depth():
    d = _dispatcher(2)
    d.submit(lambda: StubHandle("a"))
    d.submit(lambda: StubHandle("b"))
    assert StubHandle.resolved == []          # window holds both, no waits
    d.submit(lambda: StubHandle("c"))
    assert StubHandle.resolved == ["a"]       # oldest resolved to admit c
    assert d.drain() == 2
    assert StubHandle.resolved == ["a", "b", "c"]


def test_depth4_fifo_under_concurrent_resolvers():
    """Multiple worker threads resolving arbitrary tickets concurrently
    (the beacon-processor pump shape) must still produce exactly one
    continuation per ticket, in submission order."""
    d = _dispatcher(4)
    done = []
    lock = threading.Lock()

    def cont(v, i):
        with lock:
            done.append(i)

    tickets = [
        d.submit(lambda i=i: StubHandle(i),
                 continuation=lambda v, i=i: cont(v, i))
        for i in range(4)
    ]
    threads = [
        threading.Thread(target=t.result)
        for t in reversed(tickets)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert done == [0, 1, 2, 3]
    assert StubHandle.resolved == [0, 1, 2, 3]


def test_concurrent_submitters_never_exceed_depth():
    """Racing batch-lane submitters must not overfill the window between
    the admission check and the append: admission claims a slot
    atomically (len(window) + reserved <= depth)."""
    import time

    d = _dispatcher(2)
    peak = []

    def slow_dispatch(i):
        def dispatch():
            with d._lock:
                peak.append(len(d._window) + d._reserved)
            time.sleep(0.005)   # widen the dispatch window for the race
            return StubHandle(i)

        return dispatch

    threads = [
        threading.Thread(target=lambda i=i: d.submit(slow_dispatch(i)))
        for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert max(peak) <= 2, peak
    d.drain()
    assert sorted(StubHandle.resolved) == list(range(8))


# ------------------------------------------------------------ urgent lane


def test_urgent_lane_bypasses_full_batch_window():
    """With the batch window FULL of unresolved work, an urgent submit
    must dispatch and resolve immediately — it neither waits for a slot
    nor resolves anyone else's batch (the coalesce-window bypass)."""
    d = _dispatcher(2)
    d.submit(lambda: StubHandle("batch0"))
    d.submit(lambda: StubHandle("batch1"))
    t = d.submit(lambda: StubHandle("urgent"), urgent=True)
    assert t.result() is True
    # ONLY the urgent handle resolved; the window is still full
    assert StubHandle.resolved == ["urgent"]
    assert d.inflight() == 2
    assert d.drain() == 2
    assert StubHandle.resolved == ["urgent", "batch0", "batch1"]


# -------------------------------------------------------- donation safety


class DonatedBuffer:
    """Models a device input buffer consumed by donate_argnums: any read
    after the dispatch that donated it is a use-after-donate."""

    def __init__(self):
        self.donated = False

    def read(self):
        if self.donated:
            raise AssertionError("use-after-donate: buffer read after "
                                 "the dispatch consumed it")
        return b"limbs"


def test_error_ticket_does_not_poison_window_and_retry_never_reuses_donated():
    """The breaker-open / device-error fallback path: a failed batch
    re-verifies from HOST data (fresh marshal), never from the donated
    device buffers, and an errored ticket neither blocks nor corrupts
    later tickets."""
    d = _dispatcher(2)
    buf = DonatedBuffer()

    def dispatch_failing():
        buf.read()            # marshal reads the buffer ONCE (legal)
        buf.donated = True    # the jit call consumed it
        return StubHandle("bad", error=RuntimeError("device lost"))

    t_bad = d.submit(dispatch_failing)
    t_ok = d.submit(lambda: StubHandle("good"))

    with pytest.raises(RuntimeError, match="device lost"):
        t_bad.result()
    # the error is sticky and re-raised, not retried against the buffer
    with pytest.raises(RuntimeError, match="device lost"):
        t_bad.result()

    # the retry path marshals FRESH host data: a correct caller never
    # touches the donated buffer again — and the window stays healthy
    fresh = DonatedBuffer()

    def dispatch_retry():
        fresh.read()
        fresh.donated = True
        return StubHandle("retry")

    assert d.submit(dispatch_retry).result() is True
    assert t_ok.result() is True


def test_failing_oldest_batch_never_poisons_an_admitting_submitter():
    """Backpressure resolves the OLDEST batch to admit a new one; if that
    oldest batch errored, the failure belongs to ITS owner (re-raised at
    their result() call) — the unrelated new submission must succeed."""
    d = _dispatcher(1)
    t_bad = d.submit(lambda: StubHandle("bad", error=RuntimeError("boom")))
    t_ok = d.submit(lambda: StubHandle("ok"))   # admission resolves t_bad
    assert t_ok.result() is True
    with pytest.raises(RuntimeError, match="boom"):
        t_bad.result()


def test_hybrid_device_error_falls_back_to_host_sets():
    """End-to-end donation-safety shape at the policy layer: the hybrid
    router's device-error fallback re-verifies from the original host
    SignatureSet objects (a fresh marshal), so a donated device buffer
    is never an input to the retry."""
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.crypto.bls.hybrid import HybridBackend, _dummy_sets

    calls = {"urgent": 0, "host": 0}

    class ExplodingDevice:
        def verify_signature_sets_urgent(self, sets, rands):
            calls["urgent"] += 1
            raise RuntimeError("device died mid-dispatch")

        def verify_signature_sets(self, sets, rands):  # pragma: no cover
            raise RuntimeError("device died mid-dispatch")

    class HostSpy:
        def verify_signature_sets(self, sets, rands):
            calls["host"] += 1
            # host receives the ORIGINAL SignatureSet objects
            assert all(hasattr(s, "signing_keys") for s in sets)
            return True

    b = HybridBackend(probe_startup_wait_secs=0.1, probe_retry_secs=3600)
    b._probe_started.set()
    b._probe_done.set()
    b._state = "up"
    b._device = ExplodingDevice()
    sets = _dummy_sets(1, 1)
    b._warm_buckets.add(b._bucket(sets))
    prev = bls_api._BACKENDS["python"]
    bls_api._BACKENDS["python"] = HostSpy()
    try:
        assert b.verify_signature_sets(sets, [1]) is True
    finally:
        bls_api._BACKENDS["python"] = prev
    assert calls == {"urgent": 1, "host": 1}


def test_hybrid_routes_small_batches_through_urgent_lane():
    """Warm small batches take the device's urgent submitters; batches
    over the urgent threshold take the plain batch path."""
    from lighthouse_tpu.crypto.bls.hybrid import HybridBackend, _dummy_sets

    lanes = []

    class LaneSpy:
        def verify_signature_sets(self, sets, rands):
            lanes.append(("batch", len(sets)))
            return True

        def verify_signature_sets_urgent(self, sets, rands):
            lanes.append(("urgent", len(sets)))
            return True

    b = HybridBackend(probe_startup_wait_secs=0.1, probe_retry_secs=3600,
                      urgent_max_sets=4)
    b._probe_started.set()
    b._probe_done.set()
    b._state = "up"
    b._device = LaneSpy()
    small = _dummy_sets(2, 1)
    big = _dummy_sets(8, 1)
    b._warm_buckets.update({b._bucket(small), b._bucket(big)})
    assert b.verify_signature_sets(small, [1, 1])
    assert b.verify_signature_sets(big, [1] * 8)
    assert lanes == [("urgent", 2), ("batch", 8)]


# -------------------------------------------------- resolution precedence


def test_depth_resolution_precedence(monkeypatch):
    assert pl.resolve_depth() == (4, "default")
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_DEPTH", "9")
    assert pl.resolve_depth() == (9, "env")
    assert pl.resolve_depth(explicit=3) == (3, "explicit")
    # malformed env falls through; clamping applies everywhere
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_DEPTH", "nope")
    assert pl.resolve_depth() == (4, "default")
    assert pl.resolve_depth(explicit=99) == (16, "explicit")
    assert pl.resolve_depth(explicit=0) == (1, "explicit")


def test_donation_resolution(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_DONATE", "0")
    assert pl.donation_enabled() == (False, "env")
    monkeypatch.setenv("LIGHTHOUSE_TPU_DONATE", "1")
    assert pl.donation_enabled() == (True, "env")
    assert pl.donation_enabled(explicit=False) == (False, "explicit")
    monkeypatch.delenv("LIGHTHOUSE_TPU_DONATE")
    enabled, source = pl.donation_enabled()
    assert source == "platform"
    # tier-1 runs on JAX_PLATFORMS=cpu where donation is a warning-noise
    # no-op: the platform default must keep it off there
    import jax

    if jax.default_backend() == "cpu":
        assert enabled is False


# --------------------------------------------------------------- metrics


def test_pipeline_metric_families_are_labeled():
    d = _dispatcher(2)
    d.submit(lambda: StubHandle("m1"))
    d.submit(lambda: StubHandle("m2"), urgent=True).result()
    d.drain()
    text = REGISTRY.expose_text()
    assert 'jaxbls_pipeline_depth{source="explicit"}' in text
    assert 'jaxbls_pipeline_inflight{lane="batch"}' in text
    assert 'jaxbls_pipeline_submitted_total{lane="urgent"}' in text
    assert ('jaxbls_pipeline_resolved_total{lane="batch",outcome="ok"}'
            in text)
    assert 'jaxbls_pipeline_admit_wait_seconds_count{lane="batch"}' in text
    # the lint gate enforces the labeling convention on these families
    import sys

    sys.path.insert(0, "scripts")
    try:
        from lint_metrics import lint_registry

        assert not [
            e for e in lint_registry(REGISTRY) if "jaxbls_pipeline" in e
        ]
    finally:
        sys.path.remove("scripts")
