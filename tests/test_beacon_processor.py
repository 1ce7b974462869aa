"""BeaconProcessor scheduler tests: priority order, batch coalescing,
bounded queues, threaded pump."""

import threading
import time

from lighthouse_tpu.chain.beacon_processor import (
    BeaconProcessor,
    BeaconProcessorConfig,
    WorkItem,
    WorkKind,
)


def test_priority_order():
    bp = BeaconProcessor()
    order = []
    bp.submit(WorkItem(WorkKind.gossip_attestation, payload=1, run_batch=lambda xs: order.append(("att", xs))))
    bp.submit(WorkItem(WorkKind.gossip_block, run=lambda: order.append(("block", None))))
    bp.submit(WorkItem(WorkKind.chain_segment, run=lambda: order.append(("segment", None))))
    bp.run_until_idle()
    assert [x[0] for x in order] == ["block", "att", "segment"]


def test_attestation_batch_coalescing():
    bp = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=10))
    got = []
    for i in range(25):
        bp.submit(WorkItem(WorkKind.gossip_attestation, payload=i, run_batch=lambda xs: got.append(list(xs))))
    bp.run_until_idle()
    assert [len(b) for b in got] == [10, 10, 5]
    assert sorted(x for b in got for x in b) == list(range(25))
    assert bp.batches_formed >= 2


def test_bounded_queue_drops():
    bp = BeaconProcessor()
    bp.max_lengths[WorkKind.gossip_block] = 2
    assert bp.submit(WorkItem(WorkKind.gossip_block, run=lambda: None))
    assert bp.submit(WorkItem(WorkKind.gossip_block, run=lambda: None))
    assert not bp.submit(WorkItem(WorkKind.gossip_block, run=lambda: None))
    assert bp.dropped[WorkKind.gossip_block] == 1


def test_threaded_pump():
    bp = BeaconProcessor(BeaconProcessorConfig(num_workers=2, max_attestation_batch=8))
    done = threading.Event()
    count = [0]
    lock = threading.Lock()

    def on_batch(xs):
        with lock:
            count[0] += len(xs)
            if count[0] >= 100:
                done.set()

    bp.start()
    try:
        for i in range(100):
            bp.submit(WorkItem(WorkKind.gossip_attestation, payload=i, run_batch=on_batch))
        assert done.wait(timeout=5)
    finally:
        bp.stop()
    assert count[0] == 100


def test_pipelined_batch_continuations():
    """A runner returning (handle, continuation) keeps the pump pulling new
    work while the batch is 'in flight'; continuations all resolve by idle."""
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        BeaconProcessorConfig,
        WorkItem,
        WorkKind,
    )

    order = []

    class SlowHandle:
        def __init__(self, tag):
            self.tag = tag

        def result(self):
            order.append(("resolve", self.tag))
            return True

    proc = BeaconProcessor(BeaconProcessorConfig(max_inflight=2, max_attestation_batch=1))
    done = []

    def mk_runner(tag):
        def run_batch(payloads):
            order.append(("submit", tag))
            return SlowHandle(tag), lambda ok: done.append((tag, ok))

        return run_batch

    for i in range(5):
        proc.submit(
            WorkItem(kind=WorkKind.gossip_attestation, payload=i, run_batch=mk_runner(i))
        )
    proc.run_until_idle()
    assert sorted(done) == [(i, True) for i in range(5)]
    # pipelining: at least one later submit happened before an earlier resolve
    first_resolve = order.index(("resolve", 0))
    assert ("submit", 1) in order[:first_resolve]
    assert proc.pipelined_batches == 5


def test_chain_submit_attestation_batch_pipelined():
    """End-to-end: chain.submit_attestation_batch returns a continuation the
    processor resolves, applying fork-choice votes."""
    import pytest
    from lighthouse_tpu.chain.beacon_chain import BeaconChain
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_transition.slot import types_for_slot
    from lighthouse_tpu.testing.harness import StateHarness, clone_state
    from lighthouse_tpu.types.spec import minimal_spec

    bls.set_backend("fake")
    spec = minimal_spec()
    harness = StateHarness.new(spec, 64)
    chain = BeaconChain(spec, clone_state(harness.state, spec))
    slot = 1
    signed, _ = harness.produce_block(slot, attestations=[], full_sync=False)
    harness.apply_block(signed)
    chain.slot_clock.set_slot(slot)
    chain.per_slot_task()
    chain.process_block(signed)
    types = types_for_slot(spec, slot)
    head_root = types.BeaconBlock.hash_tree_root(signed.message)
    aggs = harness.build_attestations(clone_state(harness.state, spec), slot, head_root)
    # split into single-bit attestations
    singles = []
    for agg in aggs:
        n = len(agg.aggregation_bits)
        for pos in range(n):
            if agg.aggregation_bits[pos]:
                bits = [p == pos for p in range(n)]
                singles.append(
                    types.Attestation.make(
                        aggregation_bits=bits, data=agg.data, signature=agg.signature
                    )
                )
    got = []
    proc = BeaconProcessor()
    proc.submit(
        WorkItem(
            kind=WorkKind.gossip_attestation,
            payload=None,
            run_batch=lambda _p: chain.submit_attestation_batch(
                singles, on_done=got.extend
            ),
        )
    )
    proc.run_until_idle()
    assert len(got) == len(singles)


def test_blob_sidecars_run_directly_behind_the_block():
    """gossip_blob_sidecar sits where the reference's GossipBlobSidecar
    does, and the kinds that were there keep their order."""
    names = [k.name for k in WorkKind]
    assert names[:4] == ["chain_reprocess", "gossip_block",
                         "gossip_blob_sidecar", "api_request_p0"]
    assert names[4:] == [
        "gossip_aggregate", "gossip_attestation", "gossip_sync_contribution",
        "gossip_sync_signature", "rpc_block", "chain_segment",
        "api_request_p1", "gossip_voluntary_exit", "gossip_proposer_slashing",
        "gossip_attester_slashing", "gossip_bls_change", "backfill_segment"]
    bp = BeaconProcessor()
    order = []
    bp.submit(WorkItem(WorkKind.gossip_aggregate, payload=1, run_batch=lambda xs: order.append("agg")))
    bp.submit(WorkItem(WorkKind.api_request_p0, run=lambda: order.append("api")))
    bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload=1, run_batch=lambda xs: order.append("blob")))
    bp.submit(WorkItem(WorkKind.gossip_block, run=lambda: order.append("block")))
    bp.run_until_idle()
    assert order == ["block", "blob", "api", "agg"]


def test_six_queued_blob_sidecars_are_one_batch():
    """A block's six queued sidecars coalesce into ONE batch (the preset's
    MAX_BLOBS_PER_BLOCK, scheduler.FIXED_CAPS); a seventh starts the next."""
    from lighthouse_tpu.chain.scheduler import FIXED_CAPS

    assert FIXED_CAPS == {"gossip_blob_sidecar": 6}
    bp = BeaconProcessor()
    got = []
    for i in range(6):
        bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload=i, run_batch=lambda xs: got.append(list(xs))))
    bp.run_until_idle()
    assert got == [[0, 1, 2, 3, 4, 5]]
    assert bp.processed[WorkKind.gossip_blob_sidecar] == 6
    got.clear()
    for i in range(7):
        bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload=i, run_batch=lambda xs: got.append(list(xs))))
    bp.run_until_idle()
    assert got == [[0, 1, 2, 3, 4, 5], [6]]
    # a node on a fork with larger blocks rebases its scheduler's copy
    bp.scheduler.fixed_caps["gossip_blob_sidecar"] = 9
    got.clear()
    for i in range(9):
        bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload=i, run_batch=lambda xs: got.append(list(xs))))
    bp.run_until_idle()
    assert [len(b) for b in got] == [9]


def test_a_lone_blob_sidecar_is_a_batch_of_one_and_pipelines():
    """One queued sidecar reaches run_batch as a batch of one; its
    (handle, continuation) rides the in-flight window like a signature
    batch's, and its device time stays out of the signature cost model."""
    bp = BeaconProcessor()
    log = []

    class Handle:
        def result(self):
            log.append("resolved")
            return "verdicts"

    def run_batch(xs):
        log.append(("batch", list(xs)))
        return Handle(), lambda res: log.append(("cont", res))

    bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload="sc", run_batch=run_batch))
    bp.run_until_idle()
    assert log == [("batch", ["sc"]), "resolved", ("cont", "verdicts")]
    assert bp.pipelined_batches == 1
    assert bp.scheduler.model()["samples"] == 0


def test_an_explicit_batch_of_1024_coalesces_to_1024_and_not_beyond():
    """The cell subnet_flood_1key's width: `max_attestation_batch=1024`
    given explicitly is pinned - a plan installed later (here one that says
    64, the reference's default) re-bases nothing - and 2,500 queued
    `gossip_attestation` items leave as 1,024 + 1,024 + 452, in order."""
    import dataclasses

    from lighthouse_tpu.autotune import planner, runtime

    bp = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=1024,
                                               num_workers=1))
    assert bp.config.max_attestation_batch_explicit
    assert bp.scheduler.pinned["gossip_attestation"]
    try:
        runtime.install_runtime_plan(dataclasses.replace(
            planner.DEFAULT_PLAN, max_attestation_batch=64, source="test"))
        assert bp.scheduler.caps["gossip_attestation"] == 1024
        got = []
        for i in range(2500):
            assert bp.submit(WorkItem(WorkKind.gossip_attestation, payload=i,
                                      run_batch=got.append))
        bp.run_until_idle()
    finally:
        runtime.clear()
    assert [len(b) for b in got] == [1024, 1024, 452]
    assert [x for b in got for x in b] == list(range(2500))
    assert bp.dropped[WorkKind.gossip_attestation] == 0


def test_a_pipelined_signature_batch_records_its_verify_time_and_width():
    """`bls_batch_verify_seconds` / `bls_batch_verify_sets`: one observation
    a pipelined batch, runner entered -> verdict read (the benchmark's
    `sn_batch_verify_ms`); a runner that returns no handle records none,
    nor does a blob-sidecar batch (not a signature batch)."""
    from lighthouse_tpu.utils.metrics import (
        SIGNATURE_BATCH_SIZE,
        SIGNATURE_VERIFY_TIME,
    )

    class Handle:
        def result(self):
            time.sleep(0.02)
            return True

    def pipelined(payloads):
        time.sleep(0.01)
        return Handle(), lambda ok: None

    bp = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=8,
                                               num_workers=1))
    n0, t0 = SIGNATURE_VERIFY_TIME.n, SIGNATURE_VERIFY_TIME.total
    w0, s0 = SIGNATURE_BATCH_SIZE.n, SIGNATURE_BATCH_SIZE.total
    for i in range(8):
        bp.submit(WorkItem(WorkKind.gossip_attestation, payload=i,
                           run_batch=pipelined))
    bp.submit(WorkItem(WorkKind.gossip_attestation, payload=9,
                       run_batch=lambda xs: None))
    for i in range(2):
        bp.submit(WorkItem(WorkKind.gossip_blob_sidecar, payload=i,
                           run_batch=pipelined))
    bp.run_until_idle()
    assert SIGNATURE_VERIFY_TIME.n - n0 == 1
    assert SIGNATURE_VERIFY_TIME.total - t0 >= 0.03     # marshal + the wait
    assert SIGNATURE_BATCH_SIZE.n - w0 == 1
    assert SIGNATURE_BATCH_SIZE.total - s0 == 8
