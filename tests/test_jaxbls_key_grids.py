"""The two key grids of the jax backend's batch lane on one device
(backend.key_grid_plan, PR 42), packed: a batch of unequal widths lies as a
wide and a narrow grid and stage 1 sums each by the one tree_sum. Widths
(1, 2, 2, 4) in the (4, 4) bucket, wide 1 x 4 and narrow 4 x 2
(`_PAIR_GRIDS`: 12 slots of 16, the edge of the 3/4 rule), against the one
grid, the pure-Python backend and, for the aggregate keys, the pure-Python
curve. The plan as a pure function and the two grids by index are in
test_jaxbls_registry.py, where these tests were until PR 45: that file's
nine programs with the 4-bit coefficient chains passed conftest's
memory-mapping mark. This one compiles the unsharded four stages at 4 sets
and the one two-grid prepare, once, in a module fixture."""

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls.backend import one_key_grid
from lighthouse_tpu.observability import trace as obstrace

from jaxbls_warm import PREPARE_ZS, prepare_rest

ONE_GRID = one_key_grid(4, 4)     # the (4, 4) bucket's keys as one grid
_PAIR_GRIDS = ((1, 4), (4, 2))


@pytest.fixture(scope="module")
def _one_device_programs():
    """The unsharded four stages at 4 sets with the packed prepare at
    m = 4, and beside them the packed two-grid prepare over `_PAIR_GRIDS`."""
    import functools

    from jaxbls_warm import run_in_threads, warm_build, warm_one_chip_prepares

    run_in_threads(
        functools.partial(warm_build, 4, (4,), None),
        functools.partial(warm_one_chip_prepares,
                          ("prepare_grids", _PAIR_GRIDS)))


@pytest.fixture()
def one_chip_backend(monkeypatch, _one_device_programs):
    """The jax backend on the batch lane of ONE device (the mesh taken
    away: under a mesh the one grid stays)."""
    from lighthouse_tpu import parallel

    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "1")
    parallel.reset_mesh_cache()
    try:
        yield bls_api.set_backend("jax")
    finally:
        bls_api.set_backend("python")
        monkeypatch.undo()
        parallel.reset_mesh_cache()


def _keys_taken():
    import lighthouse_tpu.crypto.jaxbls.backend as be

    return {s: be._REGISTRY_KEYS.labels(s).value for s in ("table", "packed")}


def _set_of(sks, msg, valid=True):
    """A set of the keys of `sks` signed by all of them; where they sum to
    zero the signature is some point that is not the identity (no
    signature verifies against the identity key)."""
    agg = (sum(sks) + (0 if valid else 1)) % R or 7
    return bls.SignatureSet(
        bls.Signature(cv.g2_mul(bls_api.hash_to_g2_point(msg), agg)),
        [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks], msg)


def _pair_batch(case):
    """Four sets of 1, 2, 2 and 4 keys; `case` damages one."""
    rng = random.Random(0x261D)
    sks = [[rng.randrange(1, R) for _ in range(w)] for w in (1, 2, 2, 4)]
    if case in ("identity_narrow", "identities"):
        sks[1][1] = R - sks[1][0]
    if case in ("identity_wide", "identities"):
        sks[3][1], sks[3][3] = R - sks[3][0], R - sks[3][2]
    return [_set_of(ks, bytes([0xC0 + i]) * 32, valid=(case, i) != ("tampered", 2))
            for i, ks in enumerate(sks)]


@pytest.mark.parametrize("case", [
    "valid", "tampered", "identity_narrow", "identity_wide"])
def test_a_mixed_batch_on_two_grids_gives_the_reference_verdict(
        one_chip_backend, case):
    """`bls.verify_signature_sets` on a packed batch of unequal widths:
    the dispatch lays two grids (its trace says which, its bucket is
    still (4, 4)), counts the slots it lays and the lane-additions of
    both sums, and its verdict is the pure-Python backend's — True when
    sound, False with one bad signature, False with a set whose keys sum
    to the identity in the narrow grid or in the wide one."""
    import lighthouse_tpu.crypto.jaxbls.backend as be

    sets = _pair_batch(case)
    assert be.key_grid_plan([1, 2, 2, 4], 4, 4)[0] == _PAIR_GRIDS
    program = be._get_one_chip_variant("prepare_grids")
    compiled = program._cache_size()
    padded = be._BUCKET_SLOTS.labels("keys", "padded")
    adds = be._TREE_SUM_LANE_ADDS.labels("done")
    padded0, adds0, taken = padded.value, adds.value, _keys_taken()
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        on_jax = bls.verify_signature_sets(sets)
    finally:
        obstrace.set_current_trace(None)
    assert program._cache_size() == compiled
    assert tr.meta["bucket"] == "4x4" and tr.meta["key_grids"] == "1x4+4x2"
    assert padded.value - padded0 == 1 * 4 + 4 * 2
    # tree_sum_plan(4, 1) + tree_sum_plan(2, 4): 3 adds, and 1 on 4 lanes
    assert adds.value - adds0 == 3 + 4
    assert _keys_taken() == {"table": taken["table"],
                             "packed": taken["packed"] + 9}
    bls_api.set_backend("python")
    assert bls.verify_signature_sets(sets) is (case == "valid")
    assert on_jax is (case == "valid")


def _affine_points(jac):
    """[(x, y) or None] of a batch of Jacobian G1 points in Montgomery
    limbs, by Python integers."""
    from lighthouse_tpu.crypto.bls381.constants import P
    from lighthouse_tpu.crypto.jaxbls import tower as tw

    out = []
    for x, y, z in zip(*(tw.fq_batch_from_device(c) for c in jac)):
        zi = pow(z, -1, P) if z else 0
        out.append((x * zi * zi % P, y * zi * zi * zi % P) if z else None)
    return out


@pytest.mark.parametrize("case,n_real", [("identities", 4), ("valid", 3)],
                         ids=["identities", "padded_slot"])
def test_two_grids_sum_to_the_one_grids_aggregate_keys(one_chip_backend,
                                                       case, n_real):
    """The two-grid prepare against the one-grid prepare on the same sets:
    every z_i * aggpk_i the same AFFINE point (the sums associate
    differently, so the Jacobian limbs differ) and the pure-Python
    curve's, the signatures' sum limb for limb, `bad_aggpk` alike — set
    where a real set's keys sum to the identity, in the narrow grid and in
    the wide one. A padded set slot reads the identity entry behind the
    grids' sums, as the one grid's all-masked row sums to it (three sets,
    the wide grid left empty by a hand-laid `where`)."""
    import lighthouse_tpu.crypto.jaxbls.backend as be

    backend = one_chip_backend
    sets = _pair_batch(case)[:n_real]
    plan = be.key_grid_plan([1, 2, 2, 4], 4, 4)
    if n_real == 3:
        plan = (plan[0], np.array([1, 2, 3, 5], np.int32))
    assert plan[0] == _PAIR_GRIDS and plan[1].tolist()[:3] == [1, 2, 3]
    rest = prepare_rest(sets, n_real)
    grids = backend._marshal_pubkeys(sets, plan, single_chip=True)
    one = backend._marshal_pubkeys(sets, ONE_GRID, single_chip=True)
    assert [g.shape for g in grids] == [
        (1, 4, 24), (1, 4, 24), (1, 4), (4, 2, 24), (4, 2, 24), (4, 2), (4,)]
    assert sum(int(np.asarray(m).sum()) for m in (grids[2], grids[5])) == (
        int(np.asarray(one[2]).sum())) == sum(len(s.signing_keys) for s in sets)
    got = be._get_one_chip_variant("prepare_grids")(*grids, *rest)
    want = be._get_stages(mesh=None)[0](*one, *rest)

    def aggregate(s, k):
        total = None
        for pk in s.signing_keys:
            total = cv.g1_add(total, pk.point)
        return cv.g1_mul(total, k)

    reference = [aggregate(s, k) for s, k in zip(sets, PREPARE_ZS)] + [None] * (4 - n_real)
    assert _affine_points(got[0]) == _affine_points(want[0]) == reference
    assert reference.count(None) == {"valid": 1, "identities": 2}[case]
    for a, b in zip(got[1], want[1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert bool(np.asarray(got[2])) is bool(np.asarray(want[2])) is (
        case == "identities")


def test_module_stays_under_the_mapping_mark(one_chip_backend):
    """Last on purpose, as in the other modules that drive the staged
    backend: with this module's five programs compiled and kept, the
    process must be under conftest's mark."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK
