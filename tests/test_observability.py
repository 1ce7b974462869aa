"""Observability subsystem: tracer span lifecycle, Perfetto export schema,
processor pipeline instrumentation, /metrics + /lighthouse_tpu/pipeline
end-to-end scrapes, and the bn --trace-out export."""

import json
import subprocess
import sys
import urllib.request
from time import perf_counter
from types import SimpleNamespace

from lighthouse_tpu.observability import (
    PIPELINE_STAGES,
    TRACER,
    Tracer,
    chrome_trace_events,
    snapshot,
)
from lighthouse_tpu.observability.trace import Trace


# ---------------------------------------------------------------- tracer


def test_trace_span_lifecycle():
    tracer = Tracer(ring_size=4)
    tr = tracer.begin("gossip_attestation", n_items=32)
    tr.add_span("enqueue", 1.0, 1.5)
    tr.add_span("marshal", 1.5, 1.75, bytes=4096)
    tr.annotate(bucket="64x1")
    tracer.finish(tr)
    assert tracer.completed == 1
    (got,) = tracer.snapshot_ring()
    assert got.kind == "gossip_attestation" and got.n_items == 32
    assert got.duration() == 0.75
    assert got.meta == {"bucket": "64x1"}
    # finishing None (no trace carried) is a no-op, not a crash
    tracer.finish(None)
    assert tracer.completed == 1


def test_trace_ring_is_bounded():
    tracer = Tracer(ring_size=3)
    for i in range(10):
        tr = tracer.begin("k")
        tr.add_span("enqueue", float(i), float(i) + 0.1)
        tracer.finish(tr)
    assert tracer.completed == 10
    ring = tracer.snapshot_ring()
    assert len(ring) == 3
    assert ring[-1].spans[0][1] == 9.0  # newest kept, oldest evicted


def test_chrome_trace_event_schema():
    """Export rows follow the Chrome trace-event JSON schema Perfetto
    loads: complete events ("ph": "X"), µs timestamps rebased to the
    oldest span, pid/tid ints, args stringified."""
    t1 = Trace("gossip_attestation", 8)
    t1.add_span("enqueue", 10.0, 10.5)
    t1.add_span("device", 10.5, 11.0, bucket="64x1")
    t2 = Trace("gossip_aggregate", 2)
    t2.add_span("marshal", 10.2, 10.3)
    events = chrome_trace_events([t1, t2])
    assert len(events) == 3
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["cat"] in ("gossip_attestation", "gossip_aggregate")
    # rebased: the oldest span sits at ts=0; a span 0.2s later at 2e5 µs
    assert min(ev["ts"] for ev in events) == 0
    marshal = next(ev for ev in events if ev["name"] == "marshal")
    assert abs(marshal["ts"] - 2e5) < 1
    device = next(ev for ev in events if ev["name"] == "device")
    assert device["args"]["bucket"] == "64x1"
    json.dumps(events)  # schema must be JSON-serializable as-is
    assert chrome_trace_events([]) == []


def test_tracer_write_chrome_trace(tmp_path):
    tracer = Tracer()
    tr = tracer.begin("k")
    tr.add_span("enqueue", 0.0, 1.0)
    tracer.finish(tr)
    out = tmp_path / "trace.json"
    assert tracer.write_chrome_trace(str(out)) == 1
    doc = json.loads(out.read_text())
    assert doc["traceEvents"][0]["name"] == "enqueue"
    assert doc["displayTimeUnit"] == "ms"


# ------------------------------------------------- device attribution


def test_device_attribution_records_split_and_spans():
    """run_stage with attribution on: the first timed resolve per
    (stage, bucket) classifies as residual compile, later ones as
    steady-state execute, and each adds a device:<stage> sub-span to the
    carried trace. Pure host — no device, no jax backend needed."""
    from lighthouse_tpu.observability import device as obsdev

    obsdev.reset_seen()
    bucket = (16, 2)  # distinctive: no other test dispatches at it
    tr = Trace("gossip_attestation", 4)
    with obsdev.attributed():
        attr = obsdev.begin(bucket, trace=tr)
        assert attr is not None
        assert obsdev.run_stage(attr, "prepare", lambda a, b: a + b, 1, 2) == 3
        assert obsdev.run_stage(attr, "prepare", lambda a, b: a + b, 3, 4) == 7
        obsdev.run_stage(attr, "pairing", lambda: None)
    # attribution off outside the scope: begin() is None, run_stage is a
    # plain annotated pass-through that records nothing
    assert obsdev.begin(bucket) is None
    assert obsdev.run_stage(None, "prepare", lambda: 5) == 5

    timed = [s for s in tr.spans if s[0].startswith("device:")]
    names = [s[0] for s in timed]
    assert names == ["device:prepare", "device:prepare", "device:pairing"]
    phases = [s[3]["phase"] for s in timed]
    assert phases == ["compile", "execute", "compile"]
    # the jit call's own span is the timed interval's child on that trace
    calls = [s for s in tr.spans if s[0].startswith("jaxbls:")]
    assert [(s[0], s[4]) for s in calls] == [
        ("jaxbls:prepare", "device:prepare"),
        ("jaxbls:prepare", "device:prepare"),
        ("jaxbls:pairing", "device:pairing")]
    assert all(s[4] is None for s in timed)
    assert obsdev.STAGE_COMPILE_SECONDS.labels("prepare", 16, 2).value > 0
    assert obsdev.STAGE_DEVICE_SECONDS.labels("prepare", 16, 2).n == 1
    assert obsdev.STAGE_DEVICE_SECONDS.labels("pairing", 16, 2).n == 0
    snap = obsdev.snapshot_stages()
    assert snap["16x2"]["prepare"]["count"] == 1
    assert "compile_s" in snap["16x2"]["pairing"]


def test_merged_export_puts_device_spans_on_distinct_lanes():
    """Acceptance: one trace-event file holds host pipeline spans and
    per-stage device spans on DISTINCT lanes — host spans on the trace's
    pipeline tid, device:<stage> spans each on a dedicated named lane."""
    from lighthouse_tpu.observability.trace import DEVICE_LANE_BASE

    tr = Trace("gossip_attestation", 8)
    tr.add_span("enqueue", 1.0, 1.1)
    tr.add_span("marshal", 1.1, 1.3)
    tr.add_span("device:prepare", 1.3, 1.5, phase="execute")
    tr.add_span("device:h2c", 1.5, 1.8, phase="execute")
    tr.add_span("device", 1.3, 1.9)
    events = chrome_trace_events([tr])
    json.dumps(events)  # must be loadable as-is
    by_name = {}
    for ev in events:
        if ev["ph"] == "X":
            by_name[ev["name"]] = ev["tid"]
    host_tids = {by_name["enqueue"], by_name["marshal"], by_name["device"]}
    assert host_tids == {0}  # one pipeline lane for the host spans
    assert by_name["device:prepare"] >= DEVICE_LANE_BASE
    assert by_name["device:h2c"] >= DEVICE_LANE_BASE
    assert by_name["device:prepare"] != by_name["device:h2c"]
    # each device lane is named via thread_name metadata
    meta = {
        ev["tid"]: ev["args"]["name"]
        for ev in events
        if ev["ph"] == "M" and ev["name"] == "thread_name"
    }
    assert meta[by_name["device:prepare"]] == "device:prepare"
    assert meta[by_name["device:h2c"]] == "device:h2c"


def test_counter_samples_export_as_counter_events(tmp_path):
    """Tracer counter samples (per-WorkKind queue depths) export as
    "ph": "C" rows next to the spans, rebased on the same clock."""
    tracer = Tracer()
    tr = tracer.begin("gossip_attestation")
    tr.add_span("enqueue", 10.0, 10.5)
    tracer.finish(tr)
    tracer.counter_ring.append((10.25, "queue_depth", {"gossip_attestation": 3.0}))
    out = tmp_path / "trace.json"
    tracer.write_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    counters = [ev for ev in doc["traceEvents"] if ev["ph"] == "C"]
    (c,) = counters
    assert c["name"] == "queue_depth"
    assert c["args"] == {"gossip_attestation": 3.0}
    assert abs(c["ts"] - 0.25e6) < 1
    # meta annotations still ride the span args (satellite invariant)
    span = next(ev for ev in doc["traceEvents"] if ev["ph"] == "X")
    assert span["name"] == "enqueue"


def test_processor_samples_queue_depth_counters():
    """Every batch formation samples the per-WorkKind queue-depth gauges
    into the tracer's counter ring."""
    before = TRACER.snapshot_counters()
    _drain_probe()
    samples = TRACER.snapshot_counters()
    # the ring is bounded, and full in a worker that ran many files before
    # this one: a new sample shows as a newer last entry, not as more entries
    assert samples and (not before or samples[-1][0] > before[-1][0])
    t, name, values = samples[-1]
    assert name == "queue_depth"
    assert "gossip_attestation" in values


def test_program_analytics_capture_to_gauges_profile_and_snapshot():
    """perf.capture_program on a compiled function: flops/bytes/HBM land
    in the labeled xla_program_* gauges, the autotune profiler's bucket
    recorder (and from there the persisted profile schema), and the
    snapshot bench.py embeds in artifacts."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.autotune import profile as ap
    from lighthouse_tpu.autotune import profiler as apf
    from lighthouse_tpu.observability import perf
    from lighthouse_tpu.utils.metrics import REGISTRY

    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((8, 8), jnp.float32)
    f(x)  # normal call path compiles; capture re-traces, never re-compiles

    assert not perf.analytics_enabled()
    prev = perf.set_analytics(True)
    try:
        stats = perf.maybe_capture_program("h2c", f, (x,), (32, 4))
        again = perf.maybe_capture_program("h2c", f, (x,), (32, 4))
    finally:
        perf.set_analytics(prev)
    assert stats is not None and again == stats  # second call is a cache hit
    assert stats["flops"] > 0 and stats["bytes_accessed"] > 0
    assert stats["argument_bytes"] == 8 * 8 * 4

    text = REGISTRY.expose_text()
    assert 'xla_program_flops{stage="h2c",n_sets="32",n_pks="4"}' in text
    assert ('xla_program_hbm_bytes{stage="h2c",n_sets="32",n_pks="4",'
            'region="argument"} 256') in text

    # the bucket recorder carries the program, and it round-trips through
    # the versioned profile schema
    bp = apf.snapshot_buckets()[(32, 4)]
    assert bp.programs["h2c"]["flops"] == stats["flops"]
    prof = ap.DeviceProfile(
        key={"platform": "cpu", "backend_revision": ap.BACKEND_REVISION},
        buckets={(32, 4): bp}, source="test",
    )
    rt = ap.DeviceProfile.from_json(prof.to_json())
    assert rt.buckets[(32, 4)].programs == bp.programs

    assert perf.program_snapshot()["32x4"]["h2c"] == stats


# ------------------------------------------------------------- processor


def _drain_probe():
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import pipeline

    bls.set_backend("fake")
    return pipeline.run_probe(n_items=8)


def test_signature_batch_records_span_and_block_families():
    """SignatureBatch.verify() on the pure-Python backend: one histogram
    observation of real seconds, the set counter by the batch's size, and
    a `block:signature_batch` span on the carried trace with the set count
    and the widest key count. An empty batch records nothing."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import trace as obstrace
    from lighthouse_tpu.state_transition import block as blk
    from lighthouse_tpu.utils.metrics import REGISTRY

    bls.set_backend("python")
    msgs = [bytes([0xC0 + i]) * 32 for i in range(2)]
    pairs = [bls.interop_keypair(i) for i in range(3)]
    one = bls.SignatureSet(bls.sign(pairs[0].sk, msgs[0]), [pairs[0].pk],
                           msgs[0])
    two = bls.SignatureSet(
        bls.AggregateSignature.aggregate(
            [bls.sign(kp.sk, msgs[1]) for kp in pairs[1:]]),
        [kp.pk for kp in pairs[1:]], msgs[1])
    batch = blk.SignatureBatch()
    batch.add([one, two])

    n0, total0 = blk._BATCH_SECONDS.n, blk._BATCH_SECONDS.total
    sets0 = blk._BATCH_SETS.value
    tr = Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        assert blk.SignatureBatch().verify() is True     # empty: no record
        assert batch.verify() is True
    finally:
        obstrace.set_current_trace(None)

    assert blk._BATCH_SECONDS.n == n0 + 1
    assert blk._BATCH_SETS.value == sets0 + 2
    (span,) = [sp for sp in tr.spans if sp[0] == blk.BATCH_SPAN]
    name, t0, t1, args, parent = span
    assert name == "block:signature_batch" and parent is None
    assert args == {"sets": 2, "widest_keys": 2}
    assert 0 < t1 - t0 == blk._BATCH_SECONDS.total - total0
    names = {m.name for m in REGISTRY.all_metrics()}
    assert {"block_signature_batch_seconds",
            "block_signature_batch_sets_total"} <= names


def test_processor_traces_every_stage():
    """A batch through a real BeaconProcessor produces one trace holding
    every canonical pipeline stage, and feeds the labeled stage family."""
    from lighthouse_tpu.observability.trace import STAGE_SECONDS

    before = TRACER.completed
    _drain_probe()
    assert TRACER.completed > before
    tr = TRACER.snapshot_ring()[-1]
    assert tr.kind == "gossip_attestation" and tr.n_items == 8
    stages = [s[0] for s in tr.spans if s[4] is None]
    assert stages == ["enqueue", "coalesce", "exec_lock_wait", "marshal",
                      "device", "continuation"]
    assert [s for s in stages if s != "exec_lock_wait"] == list(PIPELINE_STAGES)
    for stage in PIPELINE_STAGES:
        child = STAGE_SECONDS.labels(stage, "gossip_attestation")
        assert child.n > 0, f"stage {stage} never observed"


def test_processor_queue_metrics_and_snapshot():
    from lighthouse_tpu.chain.beacon_processor import (
        _DROPPED,
        _PROCESSED,
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )

    proc = BeaconProcessor()
    proc.max_lengths[WorkKind.gossip_block] = 1
    dropped0 = _DROPPED.labels("gossip_block").value
    processed0 = _PROCESSED.labels("gossip_block").value
    assert proc.submit(WorkItem(WorkKind.gossip_block, run=lambda: None))
    assert not proc.submit(WorkItem(WorkKind.gossip_block, run=lambda: None))
    assert _DROPPED.labels("gossip_block").value == dropped0 + 1
    assert proc.stats()["queued"] == {"gossip_block": 1}
    proc.run_until_idle()
    assert _PROCESSED.labels("gossip_block").value == processed0 + 1
    st = proc.stats()
    assert st["queued"] == {} and st["processed"]["gossip_block"] == 1
    assert st["dropped"]["gossip_block"] == 1

    # the registered processor appears in the pipeline snapshot
    snap = snapshot()
    assert any(
        p.get("dropped", {}).get("gossip_block") == 1 for p in snap["processors"]
    )


def test_processor_device_failure_counted_and_logged():
    """A handle.result() raising must not kill the pump; it increments the
    labeled error counter and emits a structured log record instead of a
    bare traceback."""
    from lighthouse_tpu.chain.beacon_processor import (
        _ERRORS,
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.utils.logging import RECENT

    class BoomHandle:
        def result(self):
            raise RuntimeError("device lost")

    proc = BeaconProcessor()
    errors0 = _ERRORS.labels("device").value
    proc.submit(
        WorkItem(
            kind=WorkKind.gossip_attestation, payload=0,
            run_batch=lambda p: (BoomHandle(), lambda ok: None),
        )
    )
    proc.run_until_idle()
    assert _ERRORS.labels("device").value == errors0 + 1
    rec = [r for r in RECENT if r[2] == "beacon_processor"][-1]
    assert rec[1] == "ERROR" and "device batch failed" in rec[3]
    assert "device lost" in rec[4]["error"]

    # continuation failures are tracked under their own stage label
    cont0 = _ERRORS.labels("continuation").value
    proc.submit(
        WorkItem(
            kind=WorkKind.gossip_attestation, payload=0,
            run_batch=lambda p: (
                SimpleNamespace(result=lambda: True),
                lambda ok: (_ for _ in ()).throw(ValueError("bad cont")),
            ),
        )
    )
    proc.run_until_idle()
    assert _ERRORS.labels("continuation").value == cont0 + 1


# ------------------------------------------- the span primitive and joins


def _tick(seconds: float = 0.002) -> None:
    import time

    time.sleep(seconds)


def test_span_records_parent_and_self_seconds():
    """`span` records a closed span with the span open around it on that
    trace as its parent; `self_seconds` is a span's duration less what its
    children cover: children tile, and never exceed, their parent. With no
    trace at all the seconds go to the `direct` series at once."""
    from lighthouse_tpu.observability import trace as obstrace
    from lighthouse_tpu.observability.trace import STAGE_SECONDS, span

    tr = Trace("gossip_block", 1)
    other = Trace("gossip_attestation", 1)
    obstrace.set_current_trace(tr)
    try:
        with span("marshal") as top:
            _tick()
            with span("block:signature_batch", sets=3) as mid:
                with span("jaxbls:marshal.pubkeys") as leaf:
                    _tick()
                    leaf.args["hit"] = 0
                with span("jaxbls:marshal.pubkeys"):   # the name repeats
                    _tick()
                # a span of ANOTHER trace opened here is nobody's child
                with span("jaxbls:device_wait", other):
                    pass
                tr.add_span("stamped", mid.t0, mid.t0 + 1e-4)
            _tick()
    finally:
        obstrace.set_current_trace(None)
    assert [(s[0], s[4]) for s in tr.spans] == [
        ("jaxbls:marshal.pubkeys", "block:signature_batch"),
        ("jaxbls:marshal.pubkeys", "block:signature_batch"),
        ("stamped", "block:signature_batch"),
        ("block:signature_batch", "marshal"),
        ("marshal", None)]
    assert tr.spans[0][3] == {"hit": 0} and tr.spans[3][3] == {"sets": 3}
    assert other.spans == [("jaxbls:device_wait", other.spans[0][1],
                            other.spans[0][2], None, None)]
    selfs = dict(zip(("leaf1", "leaf2", "stamped", "mid", "top"),
                     tr.self_seconds()))
    durs = [s[2] - s[1] for s in tr.spans]
    assert selfs["leaf1"] == durs[0] and selfs["leaf2"] == durs[1]
    # the stamped span overlaps the first leaf: the union is what is covered
    assert 0 <= selfs["mid"] <= durs[3] - durs[0] - durs[1] + 1e-9
    assert abs(selfs["top"] - (durs[4] - durs[3])) < 1e-9
    assert selfs["top"] >= 0.003                   # the two ticks outside mid
    assert (top.t0, top.t1) == tr.spans[4][1:3]

    direct = STAGE_SECONDS.labels("jaxbls:marshal.h2f", "direct")
    n0 = direct.n
    with span("jaxbls:marshal.h2f", bytes=1):
        pass
    assert direct.n == n0 + 1 and obstrace.current_trace() is None


class _StubArray:
    """What a handle waits on: `__array__` sleeps, as a device array's
    does until it is ready, then gives the verdict."""

    def __init__(self, value, seconds=0.0):
        self._value, self._seconds = value, seconds

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        _tick(self._seconds)
        return np.asarray(self._value)


def _stub_dispatch(dispatcher, seconds=0.005, urgent=False):
    """A ticket of `dispatcher` whose handle is a real VerifyHandle over
    stub arrays: the trace is the thread's current one, as in the backend."""
    from lighthouse_tpu.crypto.jaxbls.backend import VerifyHandle
    from lighthouse_tpu.observability import trace as obstrace

    tr = obstrace.current_trace()
    return dispatcher.submit(
        lambda: VerifyHandle(_StubArray(True, seconds), _StubArray(False),
                             trace=tr),
        urgent=urgent)


def test_handle_resolved_on_another_thread_joins_its_trace():
    """The unit is dispatched on this thread and resolved on another, where
    no trace is current: `jaxbls:device_wait` and the processor's
    `device` and `continuation` land on the trace that
    dispatched it, and the dispatcher counts the dispatch's device time."""
    import threading

    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl
    from lighthouse_tpu.observability import trace as obstrace

    dispatcher = pl.PipelinedDispatcher(depth=2)
    device = pl._DISPATCH_DEVICE.labels("batch")
    n0, total0 = device.n, device.total
    verdicts = []
    proc = BeaconProcessor()
    proc.submit(WorkItem(
        WorkKind.gossip_attestation, payload=0,
        run_batch=lambda p: (_stub_dispatch(dispatcher), verdicts.append)))
    single, batch, tr = proc._next_work(force=True)
    proc._execute(single, batch, tr)
    assert [s[0] for s in tr.spans] == [
        "enqueue", "coalesce", "exec_lock_wait", "jaxbls:admit",
        "jaxbls:enqueue", "marshal"]
    seen = []

    def resolve():
        seen.append(obstrace.current_trace())
        proc._resolve_oldest()
        seen.append(obstrace.current_trace())

    t = threading.Thread(target=resolve)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and seen == [None, None] and verdicts == [True]
    by_name = {s[0]: s for s in tr.spans}
    assert by_name["jaxbls:device_wait"][4] == "device"
    assert by_name["jaxbls:admit"][4] == by_name["jaxbls:enqueue"][4] == "marshal"
    assert by_name["device"][4] is None and by_name["continuation"][4] is None
    wait = by_name["jaxbls:device_wait"]
    assert wait[2] - wait[1] >= 0.004
    assert TRACER.snapshot_ring()[-1] is tr
    # ready less the first stage's enqueue: at least the stub's wait
    assert device.n == n0 + 1 and device.total - total0 >= 0.004


def test_top_level_spans_tile_submit_to_continuation():
    """The top-level spans of a processed unit run from its submit to the
    end of its continuation, in order, never overlapping; between them is
    only the processor's own bookkeeping: no gap of a millisecond in ANY
    of five units. The machine that runs the tests is shared and may take
    a thread off its core inside a gap, so a round of five that holds a
    wider gap is run again, three rounds at most; the order, the overlap
    and the parents are asserted on every unit of every round."""
    import gc

    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl

    dispatcher = pl.PipelinedDispatcher(depth=2)
    proc = BeaconProcessor()
    done = []

    def widest_gap_of_one_unit():
        item = WorkItem(
            WorkKind.gossip_attestation, payload=0,
            run_batch=lambda p: (_stub_dispatch(dispatcher, 0.002),
                                 lambda ok: done.append(perf_counter())))
        proc.submit(item)
        proc.run_until_idle()
        tr = TRACER.snapshot_ring()[-1]
        top = [s for s in tr.spans if s[4] is None]
        assert [s[0] for s in top] == [
            "enqueue", "coalesce", "exec_lock_wait", "marshal", "device",
            "continuation"]
        assert top[0][1] == item.t_enq
        assert top[-1][1] <= done[-1] <= top[-1][2]
        gaps = [b[1] - a[2] for a, b in zip(top, top[1:])]
        assert min(gaps) >= 0, gaps                  # no overlap, in order
        # every other span hangs under a top-level one
        names = {s[0] for s in tr.spans}
        assert all(s[4] in names for s in tr.spans if s[4] is not None)
        return max(gaps)

    rounds = []
    gc.disable()                     # a collection inside a gap is not a gap
    try:
        for _ in range(3):
            rounds.append([widest_gap_of_one_unit() for _ in range(5)])
            if max(rounds[-1]) < 1e-3:
                break
    finally:
        gc.enable()
    assert max(rounds[-1]) < 1e-3, rounds


def test_synchronous_runner_marshal_holds_the_device_wait():
    """A runner that resolves its own handle (SignatureBatch.verify() in a
    gossip_block item) has no `device` span: `jaxbls:device_wait` is a
    descendant of `marshal`, under the entry batch's span, and marshal's
    self time leaves it out."""
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl
    from lighthouse_tpu.observability.trace import span

    dispatcher = pl.PipelinedDispatcher(depth=2)

    def run():
        with span("block:signature_batch", sets=1):
            assert _stub_dispatch(dispatcher, 0.02).result() is True

    proc = BeaconProcessor()
    proc.submit(WorkItem(WorkKind.gossip_block, run=run))
    proc.run_until_idle()
    tr = TRACER.snapshot_ring()[-1]
    by_name = {s[0]: s for s in tr.spans}
    assert "device" not in by_name and "continuation" not in by_name
    chain, name = [], "jaxbls:device_wait"
    while name is not None:
        chain.append(name)
        name = by_name[name][4]
    assert chain == ["jaxbls:device_wait", "block:signature_batch", "marshal"]
    selfs = dict(zip([s[0] for s in tr.spans], tr.self_seconds()))
    marshal = by_name["marshal"]
    assert selfs["jaxbls:device_wait"] >= 0.019
    assert marshal[2] - marshal[1] >= 0.019
    assert selfs["marshal"] < 0.01 and selfs["block:signature_batch"] < 0.01


def test_new_layer_metrics_read_spans_the_program_emits():
    """Every `pipeline_stage_seconds` stage a layer metric of
    benchmarks/layer_metrics reads is a span name the program emits (the
    real-dispatch tests of test_jaxbls_backend, test_jaxbls_registry,
    test_kzg and test_jaxhash see each emitted), and the two other families this PR's metrics read
    are registered."""
    import glob
    import os

    from lighthouse_tpu.crypto.jaxbls import pipeline as pl  # noqa: F401
    from lighthouse_tpu.utils.metrics import REGISTRY

    root = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "layer_metrics")
    read = {}
    for path in glob.glob(os.path.join(root, "*.json")):
        with open(path) as f:
            spec = json.load(f)
        src = spec["source"]
        # a span's seconds over the device backend's dispatches: a ratio
        # that reads nothing where no dispatch reached that backend
        per_dispatch = src.get("reduce") == "ratio" and src["den"] == {
            "family": "jaxbls_dispatch_device_seconds", "reduce": "count"}
        if per_dispatch:
            assert src["scale"] == 1000 and src["num"]["reduce"] == "sum"
            src = src["num"]
        if src.get("family") == "pipeline_stage_seconds":
            assert per_dispatch or src["reduce"] == "mean_ms"
            assert spec["origin"] == "host_clock"
            read[spec["name"]] = src["labels"]["stage"]
        elif per_dispatch:
            read[spec["name"]] = src["family"]
    assert read == {
        "marshal_pubkeys_ms": "jaxbls:marshal.pubkeys",
        "marshal_pubkeys_upload_ms": "jaxbls:marshal.pubkeys_upload",
        "marshal_sigs_ms": "jaxbls:marshal.sigs",
        "marshal_h2f_ms": "jaxbls:marshal.h2f",
        "marshal_upload_ms": "jaxbls:marshal.upload",
        "kzg_pack_ms": "kzg:pack",
        "continuation_ms": "continuation",
        "exec_lock_wait_ms": "beacon_processor_exec_lock_wait_seconds",
        "tree_upload_ms": "jaxhash:upload",
        "tree_readback_ms": "jaxhash:readback",
        # the index marshal of the registry path (test_jaxbls_registry's
        # test_block_by_index_through_signature_batch_parity sees it), and
        # the twins that name the cell block_import_electra
        "el_marshal_indices_ms": "jaxbls:marshal.indices",
        "el_marshal_sigs_ms": "jaxbls:marshal.sigs",
        "el_marshal_h2f_ms": "jaxbls:marshal.h2f",
        "el_marshal_upload_ms": "jaxbls:marshal.upload",
        "el_exec_lock_wait_ms": "beacon_processor_exec_lock_wait_seconds",
        # the twins that name the cell subnet_flood_1key (a dispatch of
        # single-key sets by index: test_jaxbls_registry's
        # test_single_key_sets_with_shared_messages_by_index)
        "sn_marshal_indices_ms": "jaxbls:marshal.indices",
        "sn_marshal_sigs_ms": "jaxbls:marshal.sigs",
        "sn_marshal_h2f_ms": "jaxbls:marshal.h2f",
        "sn_marshal_upload_ms": "jaxbls:marshal.upload",
    }
    from lighthouse_tpu.crypto.jaxbls import registry  # noqa: F401

    families = {m.name for m in REGISTRY.all_metrics()}
    assert {"jaxbls_dispatch_device_seconds",
            "beacon_processor_exec_lock_wait_seconds",
            # what el_registry_key_share and el_prepare_key_bytes_share
            # read, and the table's three other families
            "jaxbls_registry_keys_total", "jaxbls_registry_refused_total",
            "jaxbls_registry_rows", "jaxbls_registry_bytes",
            # what sn_batch_verify_ms reads (the processor observes it)
            "bls_batch_verify_seconds"} <= families
    assert not {"jaxbls_dispatch_enqueue_seconds",
                "jaxbls_device_wait_seconds"} & families


# ------------------------------------------------------------ monitoring


def test_monitoring_reports_real_slasher_state():
    from lighthouse_tpu.utils.monitoring import MonitoringService

    def mk_chain(slasher):
        return SimpleNamespace(
            fork_choice=SimpleNamespace(
                store=SimpleNamespace(
                    justified_checkpoint=(3, b"\x00"),
                    finalized_checkpoint=(2, b"\x00"),
                )
            ),
            head_state=lambda: SimpleNamespace(slot=7),
            slasher=slasher,
        )

    posted = []
    svc = MonitoringService("http://unused.invalid", chain=mk_chain(None),
                            post_fn=posted.append)
    assert svc.tick()
    bn = next(p for p in posted[0] if p["process"] == "beaconnode")
    assert bn["slasher_active"] is False

    svc2 = MonitoringService("http://unused.invalid",
                             chain=mk_chain(object()), post_fn=posted.append)
    svc2.tick()
    bn2 = next(p for p in posted[-1] if p["process"] == "beaconnode")
    assert bn2["slasher_active"] is True

    # sent/errors are read-only views over the registry-backed counts
    assert svc.sent == 1 and svc.errors == 0
    from lighthouse_tpu.utils.metrics import REGISTRY

    assert 'monitoring_posts_total{result="ok"}' in REGISTRY.expose_text()


# ---------------------------------------------------------------- scrapes


def test_metrics_and_pipeline_scrape_over_running_node():
    """End to end over HTTP: a served chain + the Prometheus endpoint.
    After pipeline traffic, /metrics exposes the labeled per-kind queue /
    drop / wait series and /lighthouse_tpu/pipeline returns the
    stage-timing snapshot."""
    from lighthouse_tpu.api.http_api import serve
    from lighthouse_tpu.chain.beacon_chain import BeaconChain
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.testing.harness import StateHarness, clone_state
    from lighthouse_tpu.types.spec import minimal_spec
    from lighthouse_tpu.utils.metrics import metrics_http_server

    bls.set_backend("fake")
    spec = minimal_spec()
    harness = StateHarness.new(spec, 16)
    chain = BeaconChain(spec, clone_state(harness.state, spec))
    _drain_probe()  # pipeline traffic: enqueue->...->continuation

    server, _t, port = serve(chain)
    mserver, mport = metrics_http_server()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/metrics", timeout=5
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        # labeled per-kind processor series
        assert 'beacon_processor_processed_total{kind="gossip_attestation"}' in text
        assert 'beacon_processor_queue_depth{kind="gossip_attestation"}' in text
        assert ('beacon_processor_queue_wait_seconds_count'
                '{kind="gossip_attestation"}') in text
        assert 'beacon_processor_dropped_total{kind="gossip_block"}' in text
        # per-stage pipeline series + exactly one TYPE block per family
        assert 'pipeline_stage_seconds_bucket{stage="device"' in text
        assert text.count("# TYPE beacon_processor_processed_total counter") == 1

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/lighthouse_tpu/pipeline", timeout=5
        ) as r:
            doc = json.loads(r.read().decode())["data"]
        assert set(PIPELINE_STAGES) <= set(doc["stage_timings"])
        assert doc["traces_completed"] >= 1
        assert doc["recent_traces"][-1]["spans"][0]["stage"] == "enqueue"
        # the request itself lands in the route-family latency series (the
        # handler's observe runs just after the response flushes: retry)
        import time

        want = ('http_api_request_seconds_count'
                '{route="get_lh_pipeline",method="GET"}')
        for _ in range(50):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=5
            ) as r:
                text2 = r.read().decode()
            if want in text2:
                break
            time.sleep(0.05)
        assert want in text2
    finally:
        server.shutdown()
        mserver.shutdown()


def test_bn_trace_out_end_to_end(tmp_path):
    """Acceptance path: a node run with --trace-out writes valid Chrome
    trace-event JSON containing spans for every pipeline stage."""
    out = tmp_path / "trace.json"
    r = subprocess.run(
        [sys.executable, "-m", "lighthouse_tpu", "bn", "--spec", "minimal",
         "--interop-validators", "4", "--bls-backend", "fake",
         "--disable-p2p", "--zero-ports", "--shutdown-after-sync",
         "--trace-out", str(out)],
        capture_output=True, text=True, timeout=300, cwd="/root/repo",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "pipeline trace probe complete" in (r.stdout + r.stderr)
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert {ev["name"] for ev in events} >= set(PIPELINE_STAGES)
    spans = [ev for ev in events if ev["ph"] == "X"]
    for ev in spans:
        assert ev["ts"] >= 0 and ev["dur"] >= 0
    # the probe's batch formations also sampled queue depths -> counter rows
    counters = [ev for ev in events if ev["ph"] == "C"]
    assert counters and counters[0]["name"] == "queue_depth"
    assert all(ev["ph"] in ("X", "C", "M") for ev in events)
