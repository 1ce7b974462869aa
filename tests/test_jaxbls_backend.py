"""End-to-end parity: the jax TPU backend vs the pure-Python backend on the
generic BLS API — the same dual-backend strategy the reference uses for
blst vs fake_crypto (/root/reference/crypto/bls/tests/tests.rs).

This is the module that drives the real JaxBackend through its four
stages over the mesh and on the urgent lane's one chip (test_multichip.py's
sharded runs joined it at PR 47; test_jaxbls_registry.py drives the batch
lane without a mesh). It compiles its programs once, in one module-scoped
warm-up (tests/jaxbls_warm.py): a new test of the staged backend joins it
instead of opening a file, and keeps to the builds and key-count buckets
the module warms."""

import json
import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R


rng = random.Random(0xBAC)


def _mk_set(n_pks: int, msg: bytes, valid=True):
    sks = [bls.SecretKey(rng.randrange(1, R)) for _ in range(n_pks)]
    pks = [sk.public_key() for sk in sks]
    agg = sum(sk.scalar for sk in sks) % R
    h = bls_api.hash_to_g2_point(msg)
    if not valid:
        agg = (agg + 1) % R
    sig = bls.Signature(cv.g2_mul(h, agg))
    return bls.SignatureSet(sig, pks, msg)


@pytest.fixture(scope="module", autouse=True)
def _warm_stages_parallel():
    """Cold-compile, in PARALLEL THREADS, the two builds of the four stage
    programs that the tests below dispatch, before they run — XLA releases
    the GIL while compiling, so the wall-clock cost of a cold module is
    about one build's instead of the sum of every stage.

    conftest gives the process 8 virtual devices, so the backend's batch
    lane dispatches over the 8-device `sets` mesh (sets padded to 8) and
    only its urgent lane and the host-side callers (verify, the h2c and
    pairing of aggregate_verify) run the unsharded 4-set programs. Key
    counts below are 1 (single / attribution / aggregate_verify / the
    dispatcher test) and 2-4 (the batches, the aggregate): two key-count
    buckets on the mesh, m = 1 and m = 4, and m = 1 on the urgent lane."""
    from jaxbls_warm import warm_builds

    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    live = parallel.get_mesh()
    assert live is not None and int(live.devices.size) == 8
    warm_builds((8, (1, 4), live), (4, (1,), None))


@pytest.fixture(autouse=True)
def _restore_backend():
    yield
    bls_api.set_backend("python")


def test_verify_signature_sets_parity():
    backend = bls_api.set_backend("jax")
    sets = [_mk_set(3, b"\x11" * 32), _mk_set(1, b"\x22" * 32), _mk_set(4, b"\x33" * 32)]
    rands = [1, 0xDEADBEEF12345677, 0x42]
    assert backend.verify_signature_sets(sets, rands)

    # one invalid set poisons the batch
    bad_sets = sets[:2] + [_mk_set(2, b"\x44" * 32, valid=False)]
    assert not backend.verify_signature_sets(bad_sets, rands)

    # wrong message fails
    tampered = [bls.SignatureSet(sets[0].signature, sets[0].signing_keys, b"\x55" * 32)] + sets[1:]
    assert not backend.verify_signature_sets(tampered, rands)


def test_stage_attribution_on_real_dispatch():
    """Acceptance: a dispatch through the jax backend with attribution on
    records per-stage device seconds with a compile/execute split per
    padding bucket, and the carried trace grows device:<stage> sub-spans
    alongside the host spans (merged-export lanes are covered in
    test_observability). Stages are already warm (module fixture), so the
    two attributed verifies only pay event-timed resolves."""
    from lighthouse_tpu.observability import device as obsdev
    from lighthouse_tpu.observability import trace as obstrace

    backend = bls_api.set_backend("jax")
    sets = [_mk_set(1, b"\xab" * 32)]
    tr = obstrace.Trace("gossip_attestation", 1)
    obstrace.set_current_trace(tr)
    try:
        with obsdev.attributed():
            assert backend.verify_signature_sets(sets, [1])
            assert backend.verify_signature_sets(sets, [1])
    finally:
        obstrace.set_current_trace(None)

    import lighthouse_tpu.crypto.jaxbls.backend as be

    n, m = be.padding_bucket(1, 1)
    for stage in obsdev.STAGES:
        # split per bucket: first resolve -> compile gauge, second ->
        # steady-state histogram
        assert obsdev.STAGE_COMPILE_SECONDS.labels(stage, n, m).value > 0, stage
        assert obsdev.STAGE_DEVICE_SECONDS.labels(stage, n, m).n >= 1, stage
    device_spans = [s[0] for s in tr.spans if s[0].startswith("device:")]
    assert device_spans == [f"device:{s}" for s in obsdev.STAGES] * 2


def test_pairing_stage_is_one_observation_a_dispatch():
    """One chip's stage 4 is a _PairingPrograms under the ONE stage name:
    the Miller loop and the final exponentiation enqueued back to back,
    here as on a TPU (on the CPU the urgent bucket's 5 pairs keep one
    accumulator inside the Miller program; a row of them from 33 pairs on
    and at every count on a TPU — the plan's business, which test_mesh
    drives with stand-ins). A dispatch with attribution on records one
    `pairing` resolve, no stage label is new, and the verdicts are
    right."""
    from lighthouse_tpu.observability import device as obsdev
    from lighthouse_tpu.observability import trace as obstrace

    import lighthouse_tpu.crypto.jaxbls.backend as be

    assert obsdev.STAGES == ("prepare", "h2c", "pairs", "pairing")
    pairing = be._get_stages()[3]
    assert isinstance(pairing, be._PairingPrograms)

    backend = bls_api.set_backend("jax")
    good, bad = _mk_set(1, b"\xcd" * 32), _mk_set(1, b"\xce" * 32, valid=False)
    n, m = be.padding_bucket(1, 1, single_chip=True)
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        with obsdev.attributed():
            # the first resolve of a (stage, bucket) counts as its compile
            assert backend.verify_signature_sets_urgent([good], [1])
            seen = obsdev.STAGE_DEVICE_SECONDS.labels("pairing", n, m).n
            assert backend.verify_signature_sets_urgent([good], [3])
            assert not backend.verify_signature_sets_urgent([bad], [1])
    finally:
        obstrace.set_current_trace(None)
    assert obsdev.STAGE_DEVICE_SECONDS.labels("pairing", n, m).n == seen + 2
    assert [s[0] for s in tr.spans if s[0].startswith("device:")] == [
        f"device:{s}" for s in obsdev.STAGES] * 3
    for family in (obsdev.STAGE_DEVICE_SECONDS, obsdev.STAGE_COMPILE_SECONDS):
        assert {key[0] for key, _ in family.children()} <= set(obsdev.STAGES)


@pytest.mark.parametrize("signature", ["of_order_r", "of_order_13"])
def test_a_signature_outside_the_subgroup_is_refused_by_the_chain(signature):
    """Four one-key sets on the urgent lane (the warmed 4 x 1 program). With
    every signature in G2 stage 1's code is zero and the verdict True. With
    one replaced by a point of order 13 on the twist (`Signature` holds what
    `deserialize(subgroup_check=False)` would hand it) under a coefficient
    that brings the chain to T + T, the addition it leaves out, the code
    says chain_exception, the counter takes it and the verdict is False:
    the pure-Python backend's."""
    from jaxbls_warm import order_13_twist_point

    import lighthouse_tpu.crypto.jaxbls.backend as be

    backend = bls_api.set_backend("jax")
    sets = [_mk_set(1, bytes([0xD0 + i]) * 32) for i in range(4)]
    rands = [0xDEADBEEF12345677, 0b1111, 3, (1 << 64) - 1]
    if signature == "of_order_13":
        sets[1] = bls.SignatureSet(bls.Signature(order_13_twist_point()),
                                   sets[1].signing_keys, sets[1].message)
    refused = be._PREPARE_REFUSED.labels("chain_exception")
    before = refused.value
    want = bls_api._BACKENDS["python"].verify_signature_sets(sets, rands)
    assert want is (signature == "of_order_r")
    assert backend.verify_signature_sets_urgent(sets, rands) is want
    assert refused.value - before == (0 if want else 1)


def test_the_handle_refuses_on_any_code_and_counts_each_reason_once():
    """`VerifyHandle.result()`: stage 1's `bad` code, one read with the
    verdict — any bit refuses, each bit is one count of its reason at the
    first resolve and none at a second."""
    import time

    import numpy as np

    import lighthouse_tpu.crypto.jaxbls.backend as be

    counts = {why: be._PREPARE_REFUSED.labels(why)
              for why in be.PREPARE_REFUSED.values()}
    before = {why: c.value for why, c in counts.items()}
    verdicts = []
    for code in (0, 1, 2, 3):
        handle = be.VerifyHandle(np.bool_(True), np.uint32(code),
                                 bucket=(4, 1), t0=time.perf_counter(),
                                 n_real=1)
        verdicts.append((handle.result(), handle.result()))
    assert verdicts == [(True, True)] + [(False, False)] * 3
    assert {why: c.value - before[why] for why, c in counts.items()} == {
        "identity_aggpk": 2, "chain_exception": 2}


def test_single_verify_parity():
    bls_api.set_backend("jax")
    sk = bls.SecretKey(rng.randrange(1, R))
    msg = b"\x66" * 32
    sig = bls_api.sign(sk, msg)
    assert bls_api.verify(sk.public_key(), msg, sig)
    assert not bls_api.verify(sk.public_key(), b"\x67" * 32, sig)


def test_fast_aggregate_verify_parity():
    bls_api.set_backend("jax")
    msg = b"\x77" * 32
    sks = [bls.SecretKey(rng.randrange(1, R)) for _ in range(4)]
    pks = [sk.public_key() for sk in sks]
    h = bls_api.hash_to_g2_point(msg)
    agg_sig = bls.Signature(cv.g2_mul(h, sum(sk.scalar for sk in sks) % R))
    assert bls_api.fast_aggregate_verify(pks, msg, agg_sig)
    assert not bls_api.fast_aggregate_verify(pks[:3], msg, agg_sig)


def test_aggregate_verify_distinct_messages_parity():
    bls_api.set_backend("jax")
    sks = [bls.SecretKey(rng.randrange(1, R)) for _ in range(3)]
    msgs = [bytes([i]) * 32 for i in range(3)]
    sig_pt = None
    for sk, m in zip(sks, msgs):
        s = cv.g2_mul(bls_api.hash_to_g2_point(m), sk.scalar)
        sig_pt = cv.g2_add(sig_pt, s)
    agg = bls.Signature(sig_pt)
    pks = [sk.public_key() for sk in sks]
    assert bls_api.aggregate_verify(pks, msgs, agg)
    assert not bls_api.aggregate_verify(pks, list(reversed(msgs)), agg)


# ----------------------------------------------------- e2e sharded dispatch
# (from test_mesh.py, which compiles nothing: these need the builds above)


def _mk_set_from(rng, n_pks, msg, valid=True):
    sks = [rng.randrange(1, R) for _ in range(n_pks)]
    pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
    h = bls_api.hash_to_g2_point(msg)
    agg = sum(sks) % R
    if not valid:
        agg = (agg + 1) % R
    return bls.SignatureSet(bls.Signature(cv.g2_mul(h, agg)), pks, msg)


def test_e2e_sharded_dispatch_through_pipelined_dispatcher():
    """The tier-1 multichip acceptance: the REAL JaxBackend over the REAL
    8-virtual-device mesh, batches riding the REAL PipelinedDispatcher —
    FIFO resolution, the urgent single-chip bypass, correct verdicts, and
    the mesh dispatch-lane accounting all survive sharding. Stage shapes
    ((8,1) sharded, (4,1) single-chip) are among those this module's
    warm-up compiles, so here it is seconds; alone in a cold process it
    is the cold compile of both builds (tier-1 never reads a compile
    cache), which cost it 623-757 s while it lived in test_mesh.py."""
    from lighthouse_tpu import parallel
    from lighthouse_tpu.parallel.mesh import MESH_DISPATCH

    mesh = parallel.get_mesh()
    assert mesh is not None and int(mesh.devices.size) == 8

    backend = bls_api.set_backend("jax")
    try:
        rng = random.Random(0xE2E)
        batches = [
            [_mk_set_from(rng, 1, bytes([b * 8 + i]) * 32) for i in range(8)]
            for b in range(3)
        ]
        sharded0 = MESH_DISPATCH.labels("sharded").value
        urgent0 = MESH_DISPATCH.labels("urgent").value

        tickets = [
            backend.verify_signature_sets_async(sets, [1] * 8)
            for sets in batches
        ]
        assert backend.dispatcher.inflight() >= 1
        # the urgent bypass: resolves without draining the batch window
        urgent_set = _mk_set_from(rng, 1, b"\xfe" * 32)
        assert backend.verify_signature_sets_urgent([urgent_set], [1]) is True
        # FIFO: resolving the LAST ticket first drains earlier ones first
        assert tickets[-1].result() is True
        assert all(t.done for t in tickets)
        assert all(t.result() is True for t in tickets)
        assert backend.dispatcher.inflight() == 0

        # a tampered sharded batch still rejects through the collectives
        bad = [_mk_set_from(rng, 1, bytes([0x40 + i]) * 32) for i in range(7)]
        bad.append(_mk_set_from(rng, 1, b"\x66" * 32, valid=False))
        assert backend.verify_signature_sets(bad, [1] * 8) is False

        # lane accounting: 4 sharded batches, 1 urgent bypass
        assert MESH_DISPATCH.labels("sharded").value == sharded0 + 4
        assert MESH_DISPATCH.labels("urgent").value == urgent0 + 1
    finally:
        bls_api.set_backend("python")


# ------------------------------------------------------ a block-shaped batch
# A block's sets as one ragged batch: two one-key sets (proposal, RANDAO),
# four two-key "attestations", one four-key "sync aggregate" — 7 sets, 14
# keys, in the (8, 4) bucket this module warms (8 set slots, 32 key slots).

_BLOCK_WIDTHS = (1, 1, 2, 2, 2, 2, 4)


@pytest.mark.parametrize(
    "damaged", [None, 0, 3, 6], ids=["valid", "one_key", "two_key", "widest"]
)
def test_block_shaped_batch_through_signature_batch_parity(damaged):
    """`SignatureBatch.verify()` on the jax backend against the pure-Python
    backend on the same operands: a valid block is True on both, one bad
    set in any width class makes it False on both, and the bucket-fill and
    lane-addition counters move by exactly what was sent over what the
    bucket holds, what tree_sum_plan says the key-axis sum does and what
    miller_lane_plan says the Miller loop carries."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu.state_transition.block import SignatureBatch

    rng = random.Random(0xB10C)
    sets = [
        _mk_set_from(rng, w, bytes([0xA0 + i]) * 32, valid=(i != damaged))
        for i, w in enumerate(_BLOCK_WIDTHS)
    ]
    batch = SignatureBatch()
    batch.add(sets[0])
    batch.add(sets[1])
    batch.add(sets[2:6])          # a list, as process_operations hands them
    batch.add(None)               # an absent set is skipped
    batch.add(sets[6])
    assert batch.sets == sets

    slots = {
        (axis, kind): be._BUCKET_SLOTS.labels(axis, kind)
        for axis in ("sets", "keys") for kind in ("real", "padded")
    }
    # the key-axis sum's lane-additions, done (the plan's) against needed
    slots.update(
        {("adds", kind): be._TREE_SUM_LANE_ADDS.labels(kind)
         for kind in ("done", "needed")}
    )
    # the Miller loop's plan for the bucket's 8 + 1 pair lanes
    slots.update(
        {("miller", kind): be._MILLER_PLAN.labels(kind)
         for kind in ("dispatches", "accumulators", "in_step_levels")}
    )
    before = {k: c.value for k, c in slots.items()}
    bls_api.set_backend("jax")
    on_jax = batch.verify()
    moved = {k: c.value - before[k] for k, c in slots.items()}
    bls_api.set_backend("python")
    on_python = batch.verify()

    assert on_python is (damaged is None)
    assert on_jax is on_python
    assert be.padding_bucket(7, 4) == (8, 4)
    # the slots LAID, which over this module's eight-device mesh are the one
    # (8, 4) grid's 32: a mesh keeps it. One chip's batch lane would lay
    # these widths as 1 x 4 + 8 x 2 = 20 slots and add 3 + 8 times
    # (tests/test_jaxbls_registry.py drives that lane)
    assert be.key_grid_plan(list(_BLOCK_WIDTHS), 8, 4)[0] == ((1, 4), (8, 2))
    # (8, 4): four keys a set sum unrolled, 3 adds on each of 8 set lanes;
    # 14 real keys in 7 sets need 7
    assert be.co.tree_sum_plan(4, 8) == (4, 0, 2, 24)
    # 9 pairs: one accumulator, both levels of the 4-lane tree in the step
    assert be.po.miller_lane_plan(9) == (1, 2, 0)
    assert moved == {("sets", "real"): 7, ("sets", "padded"): 8,
                     ("keys", "real"): 14, ("keys", "padded"): 32,
                     ("adds", "done"): 24, ("adds", "needed"): 7,
                     ("miller", "dispatches"): 1,
                     ("miller", "accumulators"): 1,
                     ("miller", "in_step_levels"): 2}
    # the pure-Python verify went nowhere near the device counters
    assert {k: c.value - before[k] for k, c in slots.items()} == moved


# ------------------------------------------------ an aggregate-shaped batch
# Two SignedAggregateAndProof of one four-member committee as one batch:
# per aggregate a one-key selection proof (both on the slot's message), a
# one-key aggregator signature (a message each) and the aggregate (both on
# the committee's AttestationData root, four and three of its keys) — 6
# sets, widths 1, 1, 4, 1, 1, 3, four distinct messages, in the (8, 4)
# bucket this module warms; so is a trio verified alone (3 sets).


def _signed_by(sks, msg, valid=True):
    agg = sum(sks) % R
    if not valid:
        agg = (agg + 1) % R
    pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
    sig = bls.Signature(cv.g2_mul(bls_api.hash_to_g2_point(msg), agg))
    return bls.SignatureSet(sig, pks, msg)


@pytest.mark.parametrize(
    "victim,role", [(None, None), (1, 0), (0, 1), (1, 2)],
    ids=["valid", "selection_proof", "aggregator_signature", "aggregate"],
)
def test_aggregate_shaped_batch_through_aggregate_batch_parity(victim, role):
    """`AggregateBatch.submit()` on the jax backend against the pure-Python
    backend on the same operands: two valid aggregates are [True, True] on
    both; one bad set in any role makes the batch False, and the trio
    fallback gives exactly that aggregate False and the other True on
    both. The dispatch counts 6 messages sent and 4 distinct, and the span
    and the batch's families are recorded."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu.chain import aggregate_batch as ab
    from lighthouse_tpu.observability import trace as obstrace

    rng = random.Random(0xA66)
    committee = [rng.randrange(1, R) for _ in range(4)]
    slot_msg, att_msg = b"\x51" * 32, b"\x41" * 32
    batch = ab.AggregateBatch()
    for i, attesting in enumerate((committee, committee[:3])):
        def sound(r):
            return (i, r) != (victim, role)

        batch.add(
            _signed_by([committee[i]], slot_msg, sound(0)),
            _signed_by([committee[i]], bytes([0x61 + i]) * 32, sound(1)),
            _signed_by(attesting, att_msg, sound(2)),
        )
    want = [i != victim for i in range(2)]

    sent = be._DISPATCH_MESSAGES.labels("sent")
    distinct = be._DISPATCH_MESSAGES.labels("distinct")
    fallback0 = ab._BATCH_FALLBACK.value
    aggregates0, seconds0 = ab._BATCH_AGGREGATES.value, ab._BATCH_SECONDS.n
    sent0, distinct0 = sent.value, distinct.value

    bls_api.set_backend("jax")
    assert be.padding_bucket(6, 4) == be.padding_bucket(3, 4) == (8, 4)
    tr = obstrace.Trace("gossip_aggregate", 2)
    obstrace.set_current_trace(tr)
    try:
        handle, continuation = batch.submit()
    finally:
        obstrace.set_current_trace(None)
    ok = handle.result()
    # the batch's own dispatch, before any trio goes down alone
    assert (sent.value - sent0, distinct.value - distinct0) == (6, 4)
    assert ok is (victim is None)
    on_jax = continuation(ok)
    # a False batch sends both trios down again, each alone
    assert ab._BATCH_FALLBACK.value - fallback0 == (0 if victim is None else 2)
    bls_api.set_backend("python")
    on_python = batch.verify()

    assert on_python == want
    assert on_jax == on_python
    assert ab._BATCH_FALLBACK.value - fallback0 == (0 if victim is None else 4)
    assert ab._BATCH_AGGREGATES.value - aggregates0 == 4
    assert ab._BATCH_SECONDS.n - seconds0 == 2
    args = dict(aggregates=2, sets=6, distinct_messages=4, widest_keys=4)
    assert [s[3] for s in tr.spans if s[0] == ab.BATCH_SPAN] == [args]
    assert tr.meta["distinct_messages"] == 4 and tr.meta["bucket"] == "8x4"


#: every span of a dispatch below the processor's own, by the lane it took;
#: benchmarks/layer_metrics reads the jaxbls:marshal.* ones
#: (test_observability pins which file reads which)
_DISPATCH_SPANS = [
    "jaxbls:marshal.pubkeys", "jaxbls:marshal.pubkeys_upload",
    "jaxbls:marshal.sigs", "jaxbls:marshal.h2f", "jaxbls:marshal.upload",
    "jaxbls:marshal", "jaxbls:admit", "jaxbls:prepare", "jaxbls:h2c", "jaxbls:pairs",
    "jaxbls:pairing", "jaxbls:enqueue",
]


#: the per-layer metrics PR 37 reads off a BLS dispatch (benchmarks/
#: layer_metrics/<name>.json, evaluated by benchmarks/layer_reader.py)
_BLS_LAYER_METRICS = [
    "marshal_pubkeys_ms", "marshal_pubkeys_upload_ms", "marshal_sigs_ms",
    "marshal_h2f_ms", "marshal_upload_ms", "dispatch_device_ms",
    "exec_lock_wait_ms", "continuation_ms",
]


def _bench_file(*parts):
    import os

    return os.path.join(os.path.dirname(__file__), "..", "benchmarks", *parts)


def _layer_reader():
    """benchmarks/layer_reader.py, loaded by its path: the benchmark is a
    directory of scripts, not a package."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_layer_reader", _bench_file("layer_reader.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("entry", ["batch", "urgent", "signature_batch"])
def test_real_dispatch_emits_the_span_tree(entry):
    """One real dispatch a lane through a BeaconProcessor work item, no
    attribution: the unit's trace holds the marshal and its five parts, the
    dispatcher's admit (batch lane only) and enqueue with the four stage
    calls under it, the handle's wait under `device` — or,
    where the runner resolves its own handle (SignatureBatch.verify()),
    under the entry batch's span inside `marshal`, whose self time leaves
    the wait out — and the processor's six at the top."""
    import lighthouse_tpu.crypto.jaxbls.pipeline as pl
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.observability import TRACER
    from lighthouse_tpu.state_transition.block import SignatureBatch
    from lighthouse_tpu.utils.metrics import REGISTRY

    backend = bls_api.set_backend("jax")
    sets = [_mk_set(1, bytes([0xD0 + len(entry)]) * 32)]
    verdicts = []

    def run():
        if entry == "signature_batch":
            batch = SignatureBatch()
            batch.add(sets)
            verdicts.append(batch.verify())
            return None
        return (backend.verify_signature_sets_async(
            sets, [1], urgent=entry == "urgent"), verdicts.append)

    lane = "urgent" if entry == "urgent" else "batch"
    device = pl._DISPATCH_DEVICE.labels(lane)
    n0 = device.n
    reader = _layer_reader()
    before = reader.snapshot(REGISTRY)
    proc = BeaconProcessor()
    proc.submit(WorkItem(WorkKind.gossip_block, run=run))
    proc.run_until_idle()
    assert verdicts == [True] and device.n == n0 + 1
    # the benchmark's reader finds every one of this PR's BLS metrics in
    # the window of this one dispatch (a synchronous runner has no
    # continuation: block_import_131 is off that metric's list)
    after = reader.snapshot(REGISTRY)
    for metric in _BLS_LAYER_METRICS:
        with open(_bench_file("layer_metrics", metric + ".json")) as f:
            source = json.load(f)["source"]
        value = reader.evaluate(source, before, after, {}, {})
        if metric == "continuation_ms" and entry == "signature_batch":
            assert not value, (metric, value)
        else:
            assert value is not None and value > 0, (metric, value)
    tr = TRACER.snapshot_ring()[-1]
    names = [s[0] for s in tr.spans]
    parent = {s[0]: s[4] for s in tr.spans}
    assert len(set(names)) == len(names)
    inner = [n for n in _DISPATCH_SPANS if lane == "batch" or n != "jaxbls:admit"]
    waits = ["jaxbls:device_wait"]
    if entry == "signature_batch":
        assert names == (["enqueue", "coalesce", "exec_lock_wait"] + inner
                         + waits + ["block:signature_batch", "marshal"])
        under = "block:signature_batch"
        assert parent[under] == "marshal"
        assert {parent[n] for n in waits} == {under}
        selfs = dict(zip(names, tr.self_seconds()))
        spans = {s[0]: s[2] - s[1] for s in tr.spans}
        assert selfs["marshal"] <= spans["marshal"] - spans[under] + 1e-9
        assert selfs[under] <= spans[under] - spans["jaxbls:device_wait"]
    else:
        assert names == (["enqueue", "coalesce", "exec_lock_wait"] + inner
                         + ["marshal"] + waits + ["device", "continuation"])
        under = "marshal"
        assert {parent[n] for n in waits} == {"device"}
    for n in inner:
        want = ("jaxbls:enqueue" if n in (
            "jaxbls:prepare", "jaxbls:h2c", "jaxbls:pairs", "jaxbls:pairing")
            else "jaxbls:marshal" if n.startswith("jaxbls:marshal.")
            else under)
        assert parent[n] == want, n
    args = {s[0]: s[3] for s in tr.spans}
    assert args["jaxbls:marshal.pubkeys"] == {"hit": 0, "bytes": args[
        "jaxbls:marshal.pubkeys_upload"]["bytes"]}
    assert args["jaxbls:enqueue"] == {"lane": lane}
    assert tr.meta["real_sets"] == 1


@pytest.mark.slow
def test_shard_map_pairing_fallback_real_collective():
    """The REAL shard_map pair product: force the explicit-sharding jit to
    fail and verify valid/tampered batches through the all_gather + Fq12
    partial-product collective. Slow: the fallback pairing program is a
    fresh XLA compile (~minutes cold on CPU)."""
    from lighthouse_tpu import parallel
    from lighthouse_tpu.crypto.jaxbls import backend as be

    mesh = parallel.get_mesh()
    backend = bls_api.set_backend("jax")
    try:
        stages = be._get_stages(mesh=mesh)
        pd = stages[3]
        assert isinstance(pd, be._PairingDispatch)
        old = (pd._jit, pd._use_fallback, pd._fallback, pd._jit_served)

        class _Boom:
            def __call__(self, *a):
                raise RuntimeError("forced propagation failure")

        # a stage that has served nothing yet: behind this module's other
        # tests the jit build has, and a failure would be a runtime one
        pd._jit, pd._use_fallback, pd._fallback, pd._jit_served = (
            _Boom(), False, None, False)
        try:
            rng = random.Random(0x5AFE)
            sets = [_mk_set_from(rng, 1, bytes([i]) * 32) for i in range(8)]
            assert backend.verify_signature_sets(sets, [1] * 8) is True
            assert pd._use_fallback is True
            bad = sets[:-1] + [_mk_set_from(rng, 1, b"\x99" * 32, valid=False)]
            assert backend.verify_signature_sets(bad, [1] * 8) is False
        finally:
            pd._jit, pd._use_fallback, pd._fallback, pd._jit_served = old
    finally:
        bls_api.set_backend("python")


# ------------------------------------------- the staged programs, sharded
# (test_multichip.py until PR 47: it compiled the mesh's build a second
# time, at a key count of its own, and an unsharded build of 8 sets beside
# it. Here its sharded runs ride this module's (8, 4) programs; the two
# tests that need the 8-set build on one device compile it themselves and
# are `slow`, each made up for by a case below that compiles nothing.)
#
# The framework's scaling story (SURVEY.md S5): signature sets are
# data-parallel over a `sets` mesh axis; the cross-set pair-product and
# signature tree-sum become XLA collectives. These tests prove the sharded
# programs (a) compile and run over 8 devices, (b) give the pure-Python
# curve's points stage by stage and the one-device programs' verdict, and
# (c) agree with the pure-Python backend on valid AND invalid batches.

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < N_DEV:
        pytest.skip(f"needs {N_DEV} virtual devices, got {len(devices)}")
    return Mesh(np.array(devices[:N_DEV]), ("sets",))


def _build_sets(n_sets: int, n_pks: int, seed: int, tamper: int | None = None):
    """n_sets aggregate sets; if tamper is an index, that set's signature is
    signed over a different message (invalid)."""
    rng = random.Random(seed)
    sets = []
    for i in range(n_sets):
        sks = [rng.randrange(1, R) for _ in range(n_pks)]
        pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
        msg = i.to_bytes(32, "big")
        signed = (i + 1).to_bytes(32, "big") if tamper == i else msg
        h = bls_api.hash_to_g2_point(signed)
        sig = bls.Signature(cv.g2_mul(h, sum(sks) % R))
        sets.append(bls.SignatureSet(sig, pks, msg))
    rands = [1] + [rng.getrandbits(64) | 1 for _ in range(n_sets - 1)]
    return sets, rands


def _marshal(backend, sets, rands):
    """Reuse the backend's own wire-format marshalling, returning host arrays."""
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import limbs as lb, curve_ops as co, h2c_ops as h2

    n_real = len(sets)
    n = max(be.MIN_SETS, 1 << (n_real - 1).bit_length())
    m = max(len(s.signing_keys) for s in sets)
    m = max(be.MIN_PKS, 1 << (m - 1).bit_length())

    pk_x = np.zeros((n, m, lb.NL), np.uint32)
    pk_y = np.zeros((n, m, lb.NL), np.uint32)
    pk_mask = np.zeros((n, m), np.uint32)
    sig_x = np.zeros((n, 2, lb.NL), np.uint32)
    sig_y = np.zeros((n, 2, lb.NL), np.uint32)
    z_digits = np.zeros((n, be.Z_BITS), np.uint32)
    set_mask = np.zeros((n,), np.uint32)
    us = np.zeros((n, 2, 2, lb.NL), np.uint32)

    for i, s in enumerate(sets):
        keys = s.signing_keys
        pk_x[i, : len(keys)] = be.pack_ints_vec([pk.point[0] for pk in keys])
        pk_y[i, : len(keys)] = be.pack_ints_vec([pk.point[1] for pk in keys])
        pk_mask[i, : len(keys)] = 1
        sp = s.signature.point
        sig_x[i, 0] = be.pack_ints_vec([sp[0][0]])[0]
        sig_x[i, 1] = be.pack_ints_vec([sp[0][1]])[0]
        sig_y[i, 0] = be.pack_ints_vec([sp[1][0]])[0]
        sig_y[i, 1] = be.pack_ints_vec([sp[1][1]])[0]
    zmask = (1 << 64) - 1
    z_digits[:n_real] = co.scalars_to_bits(
        [z & zmask for z in rands], be.Z_BITS)
    set_mask[:n_real] = 1
    us[:n_real] = h2.hash_to_field_batch([s.message for s in sets], backend.dst)
    return (pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask)

@pytest.fixture(scope="module")
def jax_backend():
    return bls_api.set_backend("jax")


def _staged(args, mesh=None):
    """The production staged pipeline, every stage's outputs; with a mesh,
    every input is sharded along the sets axis (collectives cross shards in
    the reductions)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as Pspec

    from lighthouse_tpu.crypto.jaxbls import backend as be

    be._init_consts()
    pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = args
    if mesh is not None:
        def shard(a):
            return jax.device_put(
                a, NamedSharding(mesh, Pspec("sets", *([None] * (a.ndim - 1))))
            )
        pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = (
            shard(a) for a in (pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask)
        )
    # the stages a dispatch over this mesh runs (`in_shardings` over the
    # `sets` axis, stage 4 the one program), not plain jits that would
    # compile a third stage 4 by propagation from the inputs' shardings
    prepare, h2c_stage, pairs_stage, pairing_stage = be._get_stages(mesh=mesh)
    z_pk, sig_acc, bad = prepare(
        pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask
    )
    h_jac = h2c_stage(us)
    px, py, qxx, qyy, pair_mask = pairs_stage(z_pk, h_jac, sig_acc, set_mask)
    ok = pairing_stage(px, py, qxx, qyy, pair_mask)
    return z_pk, sig_acc, bad, h_jac, ok


def _run_staged(args, mesh=None):
    *_, bad, _h_jac, ok = _staged(args, mesh)
    return bool(np.asarray(ok)) and not bool(np.asarray(bad))


def _run_sharded(mesh, args):
    return _run_staged(args, mesh=mesh)


def _warm_eight_sets_on_one_device():
    """The unsharded build at 8 sets of 4 keys, for the two `slow` tests
    below: minutes, and a third build in this process (past conftest's
    mapping mark: run them by name)."""
    from jaxbls_warm import warm_builds

    warm_builds((8, (4,), None))


def test_sharded_valid_batch_verifies(mesh, jax_backend):
    sets, rands = _build_sets(8, 4, seed=0x51)
    args = _marshal(jax_backend, sets, rands)
    assert _run_sharded(mesh, args) is True
    # python ground truth agrees
    py = bls_api._BACKENDS["python"]
    assert py.verify_signature_sets(sets, rands) is True


def test_sharded_invalid_batch_rejects(mesh, jax_backend):
    sets, rands = _build_sets(8, 4, seed=0x52, tamper=5)
    args = _marshal(jax_backend, sets, rands)
    assert _run_sharded(mesh, args) is False
    py = bls_api._BACKENDS["python"]
    assert py.verify_signature_sets(sets, rands) is False


@pytest.mark.slow
def test_sharded_matches_unsharded_bit_identical(mesh, jax_backend):
    _warm_eight_sets_on_one_device()
    sets, rands = _build_sets(8, 4, seed=0x53)
    args = _marshal(jax_backend, sets, rands)

    unsharded = _run_staged(args, mesh=None)
    sharded = _run_sharded(mesh, args)
    assert sharded == unsharded == True  # noqa: E712


def _affine_g1(jac):
    """[(x, y) or None] of a batch of Jacobian G1 points in Montgomery
    limbs, by Python integers."""
    from lighthouse_tpu.crypto.bls381.constants import P
    from lighthouse_tpu.crypto.jaxbls import tower as tw

    out = []
    for x, y, z in zip(*(tw.fq_batch_from_device(c) for c in jac)):
        zi = pow(z, -1, P) if z else 0
        out.append((x * zi * zi % P, y * zi * zi * zi % P) if z else None)
    return out


def _affine_g2(jac):
    """The same for G2: a batch of lanes, or one point."""
    from lighthouse_tpu.crypto.bls381 import fields as pyf
    from lighthouse_tpu.crypto.jaxbls import tower as tw

    coords = [np.asarray(c) for c in jac]
    if coords[0].ndim == 2:
        coords = [c[None] for c in coords]
    out = []
    for lane in zip(*coords):
        x, y, z = (tw.fq2_from_device(c) for c in lane)
        if z == (0, 0):
            out.append(None)
            continue
        zi = pyf.fq2_inv(z)
        zi2 = pyf.fq2_sqr(zi)
        out.append((pyf.fq2_mul(x, zi2), pyf.fq2_mul(y, pyf.fq2_mul(zi2, zi))))
    return out


@pytest.mark.parametrize("tamper", [None, 5], ids=["valid", "tampered"])
def test_sharded_stages_give_the_reference_curves_points(mesh, jax_backend,
                                                         tamper):
    """Made up for test_sharded_matches_unsharded_bit_identical (`slow`
    since PR 47, with the 8-set build on one device it alone needed): what
    that test held the sharded programs to by a verdict, these hold them to
    point by point against the pure-Python curve — z_i * aggpk_i of every
    set, the sum of z_i * sig_i across the shards, H(m_i) of every lane —
    on a sound batch and on one whose set 5 signed another message, and
    the verdict beside them. A sharded program that computes anything but
    what one device computes fails here, with no build beside the mesh's."""
    sets, rands = _build_sets(8, 4, seed=0x57, tamper=tamper)
    z_pk, sig_acc, bad, h_jac, ok = _staged(
        _marshal(jax_backend, sets, rands), mesh=mesh)
    sig_sum, z_aggpk = None, []
    for s, z in zip(sets, rands):
        aggpk = None
        for pk in s.signing_keys:
            aggpk = cv.g1_add(aggpk, pk.point)
        z_aggpk.append(cv.g1_mul(aggpk, z))
        sig_sum = cv.g2_add(sig_sum, cv.g2_mul(s.signature.point, z))
    assert _affine_g1(z_pk) == z_aggpk
    assert _affine_g2(sig_acc) == [sig_sum]
    assert _affine_g2(h_jac) == [
        bls_api.hash_to_g2_point(s.message) for s in sets]
    assert not bool(np.asarray(bad))
    assert bool(np.asarray(ok)) is (tamper is None)


# --------------------------------------------------------- backend path
# The production JaxBackend discovers the mesh itself (parallel/mesh.py):
# verify_signature_sets(_async) is the SAME call sites the chain uses.


def test_backend_dispatch_uses_mesh(jax_backend):
    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    m = parallel.get_mesh()
    assert m is not None and m.devices.size == N_DEV

    sets, rands = _build_sets(8, 4, seed=0x54)
    assert jax_backend.verify_signature_sets(sets, rands) is True
    bad, bad_rands = _build_sets(8, 4, seed=0x55, tamper=3)
    assert jax_backend.verify_signature_sets(bad, bad_rands) is False
    # async path too (what the beacon processor drives)
    h = jax_backend.verify_signature_sets_async(sets, rands)
    assert h.result() is True


@pytest.mark.slow
def test_backend_mesh_agrees_with_single_device(jax_backend, monkeypatch):
    from lighthouse_tpu import parallel

    _warm_eight_sets_on_one_device()
    sets, rands = _build_sets(8, 4, seed=0x56)
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "0")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is None
    single = jax_backend.verify_signature_sets(sets, rands)
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "1")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is not None
    meshed = jax_backend.verify_signature_sets(sets, rands)
    parallel.reset_mesh_cache()
    assert single == meshed == True  # noqa: E712


def test_the_mesh_switch_moves_a_dispatch_between_the_lanes(jax_backend,
                                                           monkeypatch):
    """Made up for test_backend_mesh_agrees_with_single_device (`slow`
    since PR 47): the same batch dispatched with LIGHTHOUSE_TPU_MESH=0 and
    =1, the stages recording stand-ins. Without a mesh the backend asks for
    the one-device stages, places every argument whole on one device and
    counts a `single_device` dispatch; with it, the mesh's stages, every
    argument spread over the eight devices, a `sharded` dispatch. (That the
    one-device programs then give the verdict the mesh's give is the slow
    test's; at 4 sets the urgent lane's tests hold them to the reference.)"""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu import parallel
    from lighthouse_tpu.parallel.mesh import MESH_DISPATCH

    asked = []

    def stand_ins(mesh=None):
        def prepare(*args):
            asked.append((mesh, [len(a.sharding.device_set) for a in args]))
            return "z_pk", "sig_acc", np.uint32(0)

        return (prepare, lambda us: "h_jac",
                lambda *a: ("px", "py", "qxx", "qyy", "pair_mask"),
                lambda *a: np.bool_(True))

    monkeypatch.setattr(be, "_get_stages", stand_ins)
    sets, rands = _build_sets(8, 4, seed=0x56)
    lanes = {k: MESH_DISPATCH.labels(k) for k in ("single_device", "sharded")}
    before = {k: c.value for k, c in lanes.items()}
    try:
        monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "0")
        parallel.reset_mesh_cache()
        assert parallel.get_mesh() is None
        assert jax_backend.verify_signature_sets(sets, rands) is True
        monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "1")
        parallel.reset_mesh_cache()
        live = parallel.get_mesh()
        assert live is not None and live.devices.size == N_DEV
        assert jax_backend.verify_signature_sets(sets, rands) is True
    finally:
        monkeypatch.undo()
        parallel.reset_mesh_cache()
    assert asked == [(None, [1] * 7), (live, [N_DEV] * 7)]
    assert {k: c.value - before[k] for k, c in lanes.items()} == {
        "single_device": 1, "sharded": 1}


def test_hash_to_g2_matches_python():
    """(test_jaxbls_h2c.py until PR 47, where it compiled hash-to-G2 at
    three lanes for itself.) The urgent lane's program, four lanes: each
    message's point is the pure-Python `hash_to_g2`'s."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
    from lighthouse_tpu.crypto.bls381.constants import DST_POP
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2, limbs as lb

    msgs = [b"lighthouse-tpu %d" % i for i in range(3)]
    us = np.zeros((4, 2, 2, lb.NL), np.uint32)
    us[:3] = h2.hash_to_field_batch(msgs, DST_POP)
    h2c_stage = be._get_stages()[1]
    compiled = h2c_stage._cache_size()
    pts = _affine_g2(h2c_stage(us))
    assert h2c_stage._cache_size() == compiled      # the module's program
    for i, msg in enumerate(msgs):
        assert pts[i] == ph2c.hash_to_g2(msg, DST_POP)


def test_sharded_runs_leave_the_module_under_the_mapping_mark():
    """(test_multichip.py's own mark test until PR 47.) The sharded runs
    above added no program to this module's two builds."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK


def test_module_stays_under_the_mapping_mark():
    """Last on purpose: with every build of this module compiled and kept,
    the process must be under conftest's mark — past it conftest drops the
    executables between tests and each later test recompiles for minutes.
    A module that outgrows the mark is split, not left to thrash."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK
