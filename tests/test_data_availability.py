"""Blob/data-availability pipeline: inclusion proofs, the DA checker join,
and an end-to-end deneb import gated on gossip blob sidecars with real KZG
proofs (small dev trusted setup; blob width shrunk via a preset override).

Reference behavior being mirrored: blob_verification.rs gossip checks,
data_availability_checker.rs block/blob joining, import gating."""

import dataclasses

import pytest

from lighthouse_tpu.chain.beacon_chain import BeaconChain, BlockError
from lighthouse_tpu.chain.data_availability import (
    AvailabilityPendingError,
    BlobError,
    BlobIgnoreError,
    DataAvailabilityChecker,
    build_sidecars,
    commitment_inclusion_proof,
    verify_blob_sidecar_for_gossip,
    verify_commitment_inclusion,
)
from lighthouse_tpu.crypto import bls, kzg
from lighthouse_tpu.state_transition.slot import types_for_slot
from lighthouse_tpu.testing.harness import StateHarness, clone_state
from lighthouse_tpu.types.spec import MINIMAL_PRESET, minimal_spec

VALIDATORS = 64
N_FE = 8  # field elements per blob (shrunk so the dev trusted setup is fast)


@pytest.fixture(scope="module")
def env():
    bls.set_backend("python")
    spec = minimal_spec(
        preset=dataclasses.replace(MINIMAL_PRESET, FIELD_ELEMENTS_PER_BLOB=N_FE)
    )
    setup = kzg.TrustedSetup.insecure_dev_setup(N_FE)
    harness = StateHarness.new(spec, VALIDATORS)
    chain = BeaconChain(spec, clone_state(harness.state, spec), kzg_setup=setup)
    return harness, chain, setup


def _mk_blob(i: int) -> bytes:
    return b"".join((j + i + 1).to_bytes(32, "big") for j in range(N_FE))


def _blob_block(harness, chain, setup, n_blobs: int):
    """Produce + sign a block carrying n_blobs commitments, plus sidecars."""
    spec = harness.spec
    slot = harness.state.slot + 1
    types = types_for_slot(spec, slot)
    from lighthouse_tpu.crypto.bls381 import serde

    blobs = [_mk_blob(i) for i in range(n_blobs)]
    commitments = [
        serde.g1_compress(kzg.blob_to_kzg_commitment(b, setup)) for b in blobs
    ]
    proofs = [
        serde.g1_compress(kzg.compute_blob_kzg_proof(b, c, setup))
        for b, c in zip(blobs, commitments)
    ]
    state = clone_state(harness.state, spec)
    from lighthouse_tpu.state_transition.slot import process_slots

    if state.slot < slot:
        process_slots(state, spec, slot)
    import lighthouse_tpu.state_transition.accessors as acc

    proposer = acc.get_beacon_proposer_index(state, spec)
    epoch = slot // spec.preset.SLOTS_PER_EPOCH
    reveal = harness.randao_reveal(state, proposer, epoch)

    chain.slot_clock.set_slot(slot)
    chain.per_slot_task()
    block = chain.produce_block(slot, reveal, blobs_bundle=(blobs, commitments, proofs))
    signed = harness.sign_block(block, types)
    sidecars = build_sidecars(types, spec, signed, blobs, proofs)
    return signed, sidecars


def test_inclusion_proof_roundtrip(env):
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 2)
    spec = harness.spec
    types = types_for_slot(spec, signed.message.slot)
    for sc in sidecars:
        assert verify_commitment_inclusion(types, spec, sc)
    # tampering with the commitment breaks the proof
    bad = sidecars[0].copy_with(kzg_commitment=b"\x01" * 48)
    assert not verify_commitment_inclusion(types, spec, bad)
    # wrong index breaks the proof
    bad2 = sidecars[0].copy_with(index=1)
    assert not verify_commitment_inclusion(types, spec, bad2)


def test_gossip_blob_then_block_imports(env):
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 2)
    types = types_for_slot(harness.spec, signed.message.slot)
    root = types.BeaconBlock.hash_tree_root(signed.message)

    # blobs arrive over gossip first; block import is then immediate
    for sc in sidecars:
        assert chain.process_gossip_blob(sc) is None
    got = chain.process_block(signed)
    assert got == root
    assert chain.head_root == root
    # stored sidecars round-trip
    stored = chain.get_blobs(root)
    assert [bytes(s.blob) for s in stored] == [bytes(s.blob) for s in sidecars]
    harness.apply_block(signed)


def test_block_held_until_blobs_arrive(env):
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 2)
    types = types_for_slot(harness.spec, signed.message.slot)
    root = types.BeaconBlock.hash_tree_root(signed.message)

    with pytest.raises(AvailabilityPendingError) as ei:
        chain.process_block(signed)
    assert ei.value.block_root == root
    assert ei.value.missing == [0, 1]

    assert chain.process_gossip_blob(sidecars[0]) is None
    # last blob joins the held block and triggers the import
    assert chain.process_gossip_blob(sidecars[1]) == root
    assert chain.head_root == root
    harness.apply_block(signed)


def test_gossip_blob_rejections(env):
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 1)
    sc = sidecars[0]

    # bad KZG proof
    bad = sc.copy_with(kzg_proof=bytes(sc.kzg_commitment))
    with pytest.raises(BlobError, match="KZG"):
        verify_blob_sidecar_for_gossip(chain, bad)

    # out-of-range index
    bad = sc.copy_with(index=100)
    with pytest.raises(BlobError, match="index"):
        verify_blob_sidecar_for_gossip(chain, bad)

    # tampered header signature
    bad_hdr = sc.signed_block_header.copy_with(signature=b"\x11" * 96)
    bad = sc.copy_with(signed_block_header=bad_hdr)
    with pytest.raises(BlobError):
        verify_blob_sidecar_for_gossip(chain, bad)

    # accept + dedup (duplicates are IGNOREd, not penalized)
    assert verify_blob_sidecar_for_gossip(chain, sc)
    with pytest.raises(BlobIgnoreError, match="seen"):
        verify_blob_sidecar_for_gossip(chain, sc)


def test_mismatched_sidecars_rejected(env):
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 1)
    wrong = sidecars[0].copy_with(kzg_commitment=b"\x02" * 48)
    with pytest.raises(BlockError, match="match"):
        chain.process_block(signed, blobs=[wrong])


def test_da_checker_spills_to_disk_under_blob_spam(env):
    """overflow_lru_cache.rs semantics: pending entries past the memory cap
    spill to the blobs column; in-memory count stays bounded at 10x the cap
    while every spilled entry remains joinable."""
    from lighthouse_tpu.store.hot_cold import HotColdDB

    harness, chain, setup = env
    spec = harness.spec
    signed, sidecars = _blob_block(harness, chain, setup, 1)
    store = HotColdDB(spec)
    cap = 4
    da = DataAvailabilityChecker(spec, setup, capacity=cap, store=store)

    roots = [bytes([i + 1]) + b"\x00" * 31 for i in range(10 * cap)]
    for r in roots:
        assert da.put_blob(r, sidecars[0]) is None
        assert len(da._pending) <= cap          # memory bounded
    assert da.pending_count() == 10 * cap       # nothing lost
    assert da.spilled >= 10 * cap - cap         # the rest went to disk

    # the OLDEST (long-spilled) entry still joins when its block arrives
    types = types_for_slot(spec, signed.message.slot)
    got = da.put_block(roots[0], signed, types)
    assert got is not None
    block, scs = got
    assert [int(s.index) for s in scs] == [0]
    assert bytes(scs[0].kzg_commitment) == bytes(sidecars[0].kzg_commitment)
    # faulting it back removed the disk copy
    assert roots[0] not in da._on_disk
    assert da.pending_count() == 10 * cap - 1


def test_da_checker_spill_preserves_block_side(env):
    """A pending BLOCK (not just blobs) survives the spill round-trip."""
    from lighthouse_tpu.store.hot_cold import HotColdDB

    harness, chain, setup = env
    spec = harness.spec
    signed, sidecars = _blob_block(harness, chain, setup, 2)
    store = HotColdDB(spec)
    da = DataAvailabilityChecker(spec, setup, capacity=1, store=store)
    types = types_for_slot(spec, signed.message.slot)
    root = b"\x77" * 32
    assert da.put_block(root, signed, types) is None     # awaiting 2 blobs
    da.put_blob(b"\x78" * 32, sidecars[0])               # evicts root to disk
    assert root in da._on_disk
    assert da.missing_indices(root) == [0, 1]            # read-only peek
    assert root in da._on_disk                           # ...didn't fault in
    assert da.put_blob(root, sidecars[0]) is None
    got = da.put_blob(root, sidecars[1])
    assert got is not None and got[0] == signed


def test_da_checker_spill_survives_restart_and_prunes_at_finalization(env):
    """Spilled entries are re-indexed by a NEW checker on the same store
    (no orphaned disk junk after restart) and dropped once finalized."""
    from lighthouse_tpu.store.hot_cold import HotColdDB

    harness, chain, setup = env
    spec = harness.spec
    signed, sidecars = _blob_block(harness, chain, setup, 1)
    store = HotColdDB(spec)
    da = DataAvailabilityChecker(spec, setup, capacity=2, store=store)
    roots = [bytes([i + 1]) + b"\x11" * 31 for i in range(6)]
    for r in roots:
        da.put_blob(r, sidecars[0])
    assert len(da._on_disk) == 4

    # "restart": fresh checker over the same store recovers the index
    da2 = DataAvailabilityChecker(spec, setup, capacity=2, store=store)
    assert set(da2._on_disk) == set(da._on_disk)
    # recovered entries are still joinable
    types = types_for_slot(spec, signed.message.slot)
    spilled_root = next(iter(da2._on_disk))
    assert da2.put_block(spilled_root, signed, types) is not None

    # finalization at/after the sidecar slot prunes everything pending
    sc_slot = int(sidecars[0].signed_block_header.message.slot)
    dropped = da2.prune_finalized(sc_slot)
    assert dropped > 0
    assert da2.pending_count() == 0
    assert da2._on_disk == {}
    from lighthouse_tpu.store.kv import Column

    leftovers = list(store.blobs_db.iter_column(Column.da_spill))
    assert leftovers == []


def test_da_checker_lru_bounds():
    spec = minimal_spec()
    da = DataAvailabilityChecker(spec, None, capacity=2)

    class FakeSC:
        def __init__(self, index):
            self.index = index

    da.put_blob(b"\x01" * 32, FakeSC(0))
    da.put_blob(b"\x02" * 32, FakeSC(0))
    da.put_blob(b"\x03" * 32, FakeSC(0))
    assert len(da._pending) == 2
    assert b"\x01" * 32 not in da._pending


# ------------------------------------------- the pipelined KZG batch


class _Sc:
    """What of a sidecar the KZG check reads."""

    def __init__(self, blob, commitment, proof):
        self.blob, self.kzg_commitment, self.kzg_proof = blob, commitment, proof


@pytest.fixture(scope="module")
def six_sidecars(env):
    from lighthouse_tpu.crypto.bls381 import serde

    _harness, _chain, setup = env
    out = []
    for i in range(6):
        blob = _mk_blob(10 + i)
        c = serde.g1_compress(kzg.blob_to_kzg_commitment(blob, setup))
        p = serde.g1_compress(kzg.compute_blob_kzg_proof(blob, c, setup))
        out.append(_Sc(blob, c, p))
    return out


def _counters():
    from lighthouse_tpu.utils.metrics import REGISTRY

    want = ("kzg_batches_total", "kzg_batch_sidecars_total",
            "kzg_batch_fallback_total", "kzg_blobs_evaluated_total",
            "kzg_points_validated_total")
    by_name = {m.name: m for m in REGISTRY.all_metrics()}
    return {n: by_name[n].value for n in want}


def _moved(before):
    after = _counters()
    return {k.removeprefix("kzg_").removesuffix("_total"): after[k] - before[k]
            for k in after}


def _off_subgroup_commitment(seed: int) -> bytes:
    """A compressed point on the curve and outside G1's subgroup."""
    from lighthouse_tpu.crypto.bls381 import curve as cv, serde
    from lighthouse_tpu.crypto.bls381.constants import P

    x = seed
    while True:
        x += 1
        y = pow((x ** 3 + 4) % P, (P + 1) // 4, P)
        if y * y % P == (x ** 3 + 4) % P and not cv.g1_in_subgroup((x, y)):
            return serde.g1_compress((x, y))


def test_kzg_batch_true_gives_every_sidecar_true(env, six_sidecars):
    _harness, chain, _setup = env
    da = chain.data_availability
    before = _counters()
    handle, verdicts_of = da.submit_kzg_batch(six_sidecars)
    assert verdicts_of(handle.result()) == [True] * 6
    assert _moved(before) == {
        "batches": 1, "batch_sidecars": 6, "batch_fallback": 0,
        "blobs_evaluated": 6, "points_validated": 12}
    assert da.verify_kzg_proofs(six_sidecars) is True
    assert da.verify_kzg_proofs([]) is True


def test_kzg_batch_false_is_resolved_sidecar_by_sidecar(env, six_sidecars):
    """One sidecar carries another's proof: the batch is False, the fallback
    verifies each alone, five True and that one False."""
    _harness, chain, _setup = env
    da = chain.data_availability
    bad = list(six_sidecars)
    bad[2] = _Sc(bad[2].blob, bad[2].kzg_commitment, bad[4].kzg_proof)
    before = _counters()
    handle, verdicts_of = da.submit_kzg_batch(bad)
    assert verdicts_of(handle.result()) == [True, True, False, True, True, True]
    # the batch's six, then six batches of one that are no `submit_kzg_batch`
    assert _moved(before) == {
        "batches": 1, "batch_sidecars": 6, "batch_fallback": 1,
        "blobs_evaluated": 12, "points_validated": 24}
    assert da.verify_kzg_proofs(bad) is False
    # a batch of one that is False needs no second verification
    before = _counters()
    handle, verdicts_of = da.submit_kzg_batch([bad[2]])
    assert verdicts_of(handle.result()) == [False]
    assert _moved(before)["batch_fallback"] == 0


def test_kzg_batch_malformed_input_is_its_own_sidecars_false(env, six_sidecars):
    """A point outside the subgroup, a point off the curve, a field element
    >= r, a wrong length: each is that sidecar's False and no one else's."""
    from lighthouse_tpu.crypto.bls381.constants import R

    _harness, chain, _setup = env
    da = chain.data_availability
    a, b, c, d, e, f = six_sidecars
    # x = 0 is on no point of the curve with this flag byte: 4 is a square,
    # so take an x whose x^3 + 4 is none
    off_curve = None
    x = 1
    from lighthouse_tpu.crypto.bls381.constants import P
    while off_curve is None:
        x += 1
        if pow((x ** 3 + 4) % P, (P - 1) // 2, P) != 1:
            raw = bytearray(x.to_bytes(48, "big"))
            raw[0] |= 0x80
            off_curve = bytes(raw)
    big = bytearray(c.blob)
    big[32:64] = R.to_bytes(32, "big")
    batch = [
        _Sc(a.blob, _off_subgroup_commitment(7), a.kzg_proof),
        _Sc(b.blob, b.kzg_commitment, off_curve),
        _Sc(bytes(big), c.kzg_commitment, c.kzg_proof),
        _Sc(d.blob[:-1], d.kzg_commitment, d.kzg_proof),
        e, f,
    ]
    before = _counters()
    handle, verdicts_of = da.submit_kzg_batch(batch)
    assert verdicts_of(handle.result()) == [False, False, False, False, True, True]
    moved = _moved(before)
    # three never joined the batch; the bad point spoiled its sums, so the
    # two sound ones were verified again alone
    assert moved["batch_fallback"] == 1 and moved["batch_sidecars"] == 6
    assert moved["blobs_evaluated"] == 3 + 2
    assert da.verify_kzg_proofs(batch) is False
    assert da.verify_kzg_proofs([e, f]) is True
    # without the bad point nothing is verified twice
    before = _counters()
    handle, verdicts_of = da.submit_kzg_batch(batch[1:])
    assert verdicts_of(handle.result()) == [False, False, False, True, True]
    assert _moved(before) == {
        "batches": 1, "batch_sidecars": 5, "batch_fallback": 0,
        "blobs_evaluated": 2, "points_validated": 4}


def test_gossip_blob_batch_imports_the_block_it_completes(env):
    """chain.submit_gossip_blob_batch: the gossip checks a sidecar, ONE KZG
    batch, and every sidecar its own outcome."""
    harness, chain, setup = env
    signed, sidecars = _blob_block(harness, chain, setup, 2)
    types = types_for_slot(harness.spec, signed.message.slot)
    root = types.BeaconBlock.hash_tree_root(signed.message)
    with pytest.raises(AvailabilityPendingError):
        chain.process_block(signed)
    bad = sidecars[1].copy_with(kzg_proof=bytes(sidecars[0].kzg_proof))
    stale = sidecars[0].copy_with(index=100)
    done = []
    before = _counters()
    handle, cont = chain.submit_gossip_blob_batch(
        [sidecars[0], bad, stale], on_done=done.append)
    out = cont(handle.result())
    assert done == [out]
    assert out[0] is None                      # verified, block still short
    assert isinstance(out[1], BlobError) and "KZG" in str(out[1])
    assert isinstance(out[2], BlobError) and "index" in str(out[2])
    moved = _moved(before)
    assert moved["batches"] == 1 and moved["batch_sidecars"] == 2
    assert moved["batch_fallback"] == 1
    # the true second sidecar completes the block; a replay is ignored
    handle, cont = chain.submit_gossip_blob_batch([sidecars[1], sidecars[0]])
    out = cont(handle.result())
    assert out[0] == root and chain.head_root == root
    assert isinstance(out[1], BlobIgnoreError)
    # nothing reaches the KZG check: no handle, on_done all the same
    done.clear()
    assert chain.submit_gossip_blob_batch([stale], on_done=done.append) is None
    assert len(done) == 1 and isinstance(done[0][0], BlobError)
    harness.apply_block(signed)
