"""The message axis of the jax backend's batch lane (backend.message_lanes,
PR 44): the rule as a pure function, the segmented sum of the sets' z * pk
by message against the pure-Python curve code, the folded stage 3 against
the stage 3 every dispatch ran before it, and the marshal's part (each
distinct message hashed once, the fold's index, the counters) with the
stage programs stubbed out. Nothing here builds hash-to-G2, the Miller
loop or a prepare: the fold is driven at n = 16 sets on k = 4 message
lanes, two small programs. The folded stage 3 in front of a real pairing
program, against `crypto/bls381` verdicts, is in test_jaxbls_pairing.py,
which has a four-lane product check compiled."""

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
from lighthouse_tpu.crypto.jaxbls import tower as tw
from lighthouse_tpu.observability import trace as obstrace

N, K = 16, 4
rng = random.Random(0xF01D)
POINTS = [cv.g1_mul(cv.G1_GEN, rng.randrange(1, R)) for _ in range(N)]


# ------------------------------------------------------------- the rule

ROW = po.MILLER_LANES


@pytest.mark.parametrize("distinct,n,k", [
    (82, 1024, 128),       # subnet_flood_1key: ~82 messages of 1,024 sets
    (74, 1024, 128), (90, 1024, 128), (128, 1024, 128),   # the wobble
    (1, 1024, 128),        # every set on one message: still one row
    (129, 1024, 256), (256, 1024, 256), (300, 1024, 512), (512, 1024, 512),
    (513, 1024, 1024), (1024, 1024, 1024),                # a lane a set
    (106, 256, 128),       # aggregate_flood: ~106 messages of 192 sets
    (100, 256, 128), (112, 256, 128), (128, 256, 128),
    (129, 256, 256),       # 64 aggregates on 64 distinct attestations
    (131, 256, 256),       # block_import_131: every set a message
    (64, 64, 64),          # gossip_flood: never narrower than its sets
    (3, 64, 64), (1, 128, 128), (11, 16, 16), (1, 4, 4),
    (3, 512, 128), (200, 512, 256), (257, 512, 512),
])
def test_message_lanes(distinct, n, k):
    assert be.message_lanes(distinct, n) == k
    folds = k < n
    # the floor of one row, a power of two, room for every message
    assert k >= min(ROW, n) and k & (k - 1) == 0 and k >= min(distinct, n)
    assert folds == (max(ROW, be._next_pow2(distinct)) < n)
    if folds:
        # one sparse line an accumulator at k = 128; a few shapes a bucket
        w, _, _ = po.miller_lane_plan(k + 1, platform="tpu")
        assert w == ROW and po._lines_per_accumulator(k + 1, w) == k // ROW
        assert k in {128, 256, 512}


def test_a_bucket_meets_few_message_shapes():
    for n, shapes in ((256, {128, 256}), (1024, {128, 256, 512, 1024}),
                      (128, {128}), (64, {64})):
        assert {be.message_lanes(d, n) for d in range(1, n + 1)} == shapes


# ------------------------------------------------------ the segmented sum


def _fold_index(lanes):
    """What the marshal sends for sets on message lanes `lanes`."""
    return be.message_fold_index(lanes, N, K)


@pytest.fixture(scope="module")
def fold():
    import jax

    return jax.jit(lambda z_pk, index: be._fold_by_message(z_pk, index, K))


def _host_sums(points, lanes, k=K):
    want = [None] * k
    for pt, j in zip(points, lanes):
        want[j] = cv.g1_add(want[j], pt)
    return want


_NEG3 = cv.g1_neg(POINTS[3])

FOLD_CASES = {
    # shares of every size, the sets' order not the lanes'
    "mixed": (POINTS[:12], [0, 1, 0, 2, 1, 0, 0, 3, 2, 2, 1, 0]),
    "full_bucket": (POINTS, [3, 0, 1, 2] * 4),
    "every_set_one_message": (POINTS, [0] * N),
    "every_message_distinct": (POINTS[:4], [0, 1, 2, 3]),
    "one_set": (POINTS[:1], [0]),
    # two sets with the same key, message and z: the sum doubles
    "doubling": ([POINTS[0], POINTS[1], POINTS[0]], [0, 1, 0]),
    "doubling_far_apart": ([POINTS[0]] + POINTS[1:8] + [POINTS[0]],
                           [1, 0, 0, 0, 0, 0, 0, 0, 1]),
    # a group that sums to the identity, beside one that does not
    "cancels": ([POINTS[3], POINTS[5], _NEG3], [0, 1, 0]),
    "cancels_among_more": ([POINTS[3], POINTS[5], _NEG3, POINTS[5], POINTS[6]],
                           [2, 1, 2, 1, 0]),
    # an identity among the sets of a message (an aggregate key that is one)
    "identity_member": ([POINTS[0], None, POINTS[2]], [0, 0, 1]),
}


@pytest.mark.parametrize("case", list(FOLD_CASES))
def test_fold_by_message_equals_the_host_sums(fold, case):
    points, lanes = FOLD_CASES[case]
    padded = list(points) + [None] * (N - len(points))
    sums, mask = fold(co.g1_batch_to_device(padded), _fold_index(lanes))
    want = _host_sums(points, lanes)
    assert [bool(b) for b in np.asarray(mask)] == [
        j in set(lanes) for j in range(K)]
    for j in set(lanes):
        assert co.g1_from_device(tuple(c[j] for c in sums)) == want[j]
    if case.startswith("cancels"):
        assert want[lanes[0]] is None       # the identity, held as one
    if case.startswith("doubling"):
        assert want[lanes[0]] == cv.g1_mul(POINTS[0], 2)


def test_the_folds_shape_is_the_buckets_alone(fold):
    """One compiled program for every distribution of sets over messages."""
    for points, lanes in FOLD_CASES.values():
        padded = list(points) + [None] * (N - len(points))
        fold(co.g1_batch_to_device(padded), _fold_index(lanes))
    assert fold._cache_size() == 1


# ----------------------------------------------------- the folded stage 3


def _affine_ints(px, py, qxx, qyy, pair_mask):
    return ([(tw.fq_from_device(x), tw.fq_from_device(y))
             for x, y in zip(px, py)],
            [(tw.fq2_from_device(x), tw.fq2_from_device(y))
             for x, y in zip(qxx, qyy)],
            [bool(b) for b in np.asarray(pair_mask)])


@pytest.mark.parametrize("case", ["mixed", "every_set_one_message",
                                  "cancels_among_more", "doubling"])
def test_folded_stage_3_lays_the_pairs_of_the_sums(case):
    """`_stage_pairs_folded` on n sets against `_stage_pairs` on the k
    per-message sums the host made: the same affine pairs, limb for limb,
    the same mask (a lane without a message, or whose sets cancel, out)."""
    import jax

    be._init_consts()
    points, lanes = FOLD_CASES[case]
    padded = list(points) + [None] * (N - len(points))
    h = [cv.g2_mul(cv.G2_GEN, 5 + j) for j in range(K)]
    sig = cv.g2_mul(cv.G2_GEN, 77)
    h_jac = co.g2_batch_to_device(h)
    sig_acc = co.g2_to_device(sig)
    got = jax.jit(be._stage_pairs_folded)(
        co.g1_batch_to_device(padded), h_jac, sig_acc, _fold_index(lanes))
    sums = _host_sums(points, lanes)
    lane_mask = np.array([j in set(lanes) for j in range(K)], np.uint32)
    want = jax.jit(be._stage_pairs)(
        co.g1_batch_to_device(sums), h_jac, sig_acc, lane_mask)
    g1, g2, mask = _affine_ints(*got)
    w1, w2, wmask = _affine_ints(*want)
    assert mask == wmask
    assert mask == [s is not None for s in sums] + [True]
    assert got[0].shape[0] == K + 1                      # k + 1 pairs
    for j, live in enumerate(mask):
        if live:
            assert (g1[j], g2[j]) == (w1[j], w2[j])
    assert g1[K] == cv.g1_neg(cv.G1_GEN) and g2[K] == sig
    assert [g1[j] for j in range(K) if mask[j]] == [
        s for s in sums if s is not None]


# ------------------------------------------------------------ the marshal

_SK = bls.SecretKey(0x5EED)
_PK = _SK.public_key()
_SIG = bls.Signature(cv.g2_mul(cv.G2_GEN, 0xABCD))   # never verified here


def _sets(messages):
    return [bls.SignatureSet(_SIG, [_PK], m) for m in messages]


def _msg(i: int) -> bytes:
    return i.to_bytes(4, "big") * 8


class _Stub:
    """Stage programs that compile nothing and keep what they were given."""

    def __init__(self, monkeypatch):
        self.calls = {}
        self.hashed = []
        real = be.h2.hash_to_field_batch
        self.hash = lambda messages: real(messages, be.DST_POP)  # unrecorded

        def hash_to_field_batch(messages, dst):
            self.hashed.append(list(messages))
            return real(messages, dst)

        def stage(name, out):
            def run(*args):
                self.calls[name] = args
                return out
            return run

        stages = (stage("prepare", ("z_pk", "sig_acc", np.bool_(False))),
                  stage("h2c", "h_jac"),
                  stage("pairs", ("px", "py", "qxx", "qyy", "pair_mask")),
                  stage("pairing", np.bool_(True)))
        monkeypatch.setattr(be.h2, "hash_to_field_batch", hash_to_field_batch)
        monkeypatch.setattr(be, "_get_stages", lambda mesh=None: stages)
        monkeypatch.setattr(
            be, "_get_one_chip_variant",
            lambda name: stage(name, ("px", "py", "qxx", "qyy", "pair_mask")))


@pytest.fixture
def one_chip(monkeypatch):
    """The batch lane of a process without a mesh, its stages stubbed."""
    from lighthouse_tpu import parallel

    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "1")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is None
    yield _Stub(monkeypatch)
    monkeypatch.undo()
    parallel.reset_mesh_cache()


def _counted():
    return {kind: be._DISPATCH_MESSAGES.labels(kind).value
            for kind in ("sent", "distinct", "lanes", "folded_sets")}


def _dispatch(sets, urgent=False):
    backend = be.JaxBackend()
    before = _counted()
    plan0 = {k: be._MILLER_PLAN.labels(k).value
             for k in ("dispatches", "lines_per_accumulator")}
    tr = obstrace.Trace("test", 1)
    obstrace.set_current_trace(tr)
    try:
        ok = backend.verify_signature_sets_async(
            sets, [3 + i for i in range(len(sets))], urgent=urgent).result()
    finally:
        obstrace.set_current_trace(None)
    assert ok is True                              # the stub's verdict
    counted = {k: v - before[k] for k, v in _counted().items()}
    lines = (be._MILLER_PLAN.labels("lines_per_accumulator").value
             - plan0["lines_per_accumulator"])
    assert be._MILLER_PLAN.labels("dispatches").value == plan0["dispatches"] + 1
    return tr, counted, lines


def test_the_marshal_hashes_each_distinct_message_once_and_folds(one_chip):
    """192 one-key sets on 70 messages, shares of unequal size, in the
    (256, 1) bucket: hash-to-field over the 70, `us` on 128 lanes in the
    order the messages first come, the fold's index in place of the set
    mask, the sets' own arrays in the caller's order."""
    n_real, distinct = 192, 70
    lanes = [0] * 64 + [1 + (i * 7) % (distinct - 1) for i in range(128)]
    assert len(set(lanes)) == distinct
    messages = [_msg(j) for j in lanes]
    tr, counted, lines = _dispatch(_sets(messages))
    first_seen = list(dict.fromkeys(messages))
    assert one_chip.hashed == [first_seen]               # once each, in order
    assert counted == {"sent": n_real, "distinct": distinct, "lanes": 128,
                       "folded_sets": n_real}
    assert lines == po._lines_per_accumulator(
        129, po.miller_lane_plan(129)[0])                # fed k + 1 pairs
    assert set(one_chip.calls) == {"prepare", "h2c", "pairs_folded", "pairing"}
    (us,) = one_chip.calls["h2c"]
    assert us.shape == (128, 2, 2, be.lb.NL)
    want_us = np.zeros(us.shape, np.uint32)
    want_us[:distinct] = one_chip.hash(first_seen)
    assert np.array_equal(np.asarray(us), want_us)
    z_pk, h_jac, sig_acc, index = one_chip.calls["pairs_folded"]
    assert (z_pk, h_jac, sig_acc) == ("z_pk", "h_jac", "sig_acc")
    index = np.asarray(index)
    assert index.shape == (2, 256) and index.dtype == np.int32
    lane_of = {m: j for j, m in enumerate(first_seen)}
    lane = np.array([lane_of[m] for m in messages] + [128] * 64)
    assert sorted(index[0]) == list(range(256))          # a permutation
    assert np.array_equal(index[1], lane[index[0]])
    assert (np.diff(index[1]) >= 0).all()
    # stable: the sets of a message stand in the caller's order
    for j in (0, 1, 69):
        assert list(index[0][index[1] == j]) == [
            i for i in range(n_real) if lane[i] == j]
    # the sets are not permuted: prepare's arrays are per set, in order
    set_mask = np.asarray(one_chip.calls["prepare"][-1])
    assert set_mask.tolist() == [1] * n_real + [0] * 64
    assert np.asarray(one_chip.calls["prepare"][-2]).shape == (256, be.Z_BITS)
    # the bucket is still the dispatch's name, the lanes beside it
    assert tr.meta["bucket"] == "256x1" and tr.meta["message_lanes"] == 128
    assert tr.meta["z_window"] == be.Z_WINDOW == 4
    assert tr.meta["distinct_messages"] == distinct
    assert (256, 1) in be._seen_exec_buckets
    h2f = next(s for s in tr.spans if s[0] == "jaxbls:marshal.h2f")
    assert h2f[3] == {"messages": distinct}


def test_a_flipped_message_byte_gets_a_lane_of_its_own(one_chip):
    messages = [_msg(0)] * 100 + [_msg(1)] * 100
    _, counted, _ = _dispatch(_sets(messages))
    assert counted["distinct"] == 2
    flipped = list(messages)
    flipped[7] = bytes([flipped[7][0] ^ 1]) + flipped[7][1:]
    one_chip.hashed.clear()
    _, counted, _ = _dispatch(_sets(flipped))
    assert counted["distinct"] == 3 and counted["lanes"] == 128
    assert one_chip.hashed == [[_msg(0), flipped[7], _msg(1)]]
    index = np.asarray(one_chip.calls["pairs_folded"][3])
    assert list(index[0][index[1] == 1]) == [7]         # alone on its lane


@pytest.mark.parametrize("n_real,distinct,bucket,why", [
    (192, 131, (256, 1), "k = 256 = n"),
    (64, 64, (64, 1), "every set a message"),
    (64, 3, (64, 1), "a bucket of one row or less never folds"),
    (11, 4, (16, 1), "a block's few sets"),
    (128, 5, (128, 1), "k = 128 = n"),
])
def test_a_dispatch_that_does_not_fold_lays_a_lane_a_set(
        one_chip, n_real, distinct, bucket, why):
    messages = [_msg(i % distinct) for i in range(n_real)]
    tr, counted, lines = _dispatch(_sets(messages))
    n = bucket[0]
    assert counted == {"sent": n_real, "distinct": distinct, "lanes": n,
                       "folded_sets": 0}
    assert set(one_chip.calls) == {"prepare", "h2c", "pairs", "pairing"}
    # each distinct message hashed once here too, then laid a set a lane
    assert one_chip.hashed == [[_msg(i) for i in range(distinct)]]
    (us,) = one_chip.calls["h2c"]
    want = np.zeros((n, 2, 2, be.lb.NL), np.uint32)
    want[:n_real] = one_chip.hash(messages)
    assert np.array_equal(np.asarray(us), want)
    *_, set_mask = one_chip.calls["pairs"]
    assert np.asarray(set_mask).tolist() == [1] * n_real + [0] * (n - n_real)
    assert lines == po._lines_per_accumulator(
        n + 1, po.miller_lane_plan(n + 1)[0])
    assert tr.meta["bucket"] == f"{n}x1" and tr.meta["message_lanes"] == n


def test_the_urgent_lane_keeps_a_lane_a_set(one_chip):
    messages = [_msg(i % 9) for i in range(200)]
    tr, counted, _ = _dispatch(_sets(messages), urgent=True)
    assert counted == {"sent": 200, "distinct": 9, "lanes": 256,
                       "folded_sets": 0}
    assert "pairs" in one_chip.calls and "pairs_folded" not in one_chip.calls
    assert tr.meta["bucket"] == "256x1" and tr.meta["message_lanes"] == 256


def test_a_mesh_keeps_a_lane_a_set(monkeypatch):
    """Over the eight-device mesh of this process the same 200 sets on 9
    messages keep today's path, whatever the rule would say on one chip."""
    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert mesh is not None
    stub = _Stub(monkeypatch)
    messages = [_msg(i % 9) for i in range(200)]
    tr, counted, _ = _dispatch(_sets(messages))
    n = be.padding_bucket(200, 1, mesh=mesh)[0]
    assert be.message_lanes(9, n) == 128 < n             # it would fold
    assert counted == {"sent": 200, "distinct": 9, "lanes": n,
                       "folded_sets": 0}
    assert "pairs" in stub.calls and "pairs_folded" not in stub.calls
    assert tr.meta["message_lanes"] == n


@pytest.mark.parametrize("n_sets,single_chip,folded_too", [
    (1024, False, True), (192, False, True), (128, False, False),
    (64, False, False), (200, True, False)])
def test_warm_stages_warms_the_row_of_message_lanes(
        one_chip, monkeypatch, n_sets, single_chip, folded_too):
    """Beside prepare and hash-to-G2 at the bucket, a one-chip batch-lane
    bucket wider than a row warms hash-to-G2 at 128 lanes,
    `_stage_pairs_folded` from its n sets and stage 4 on what that gives;
    the urgent lane and a bucket of a row or less warm what they did."""
    seen = []

    def stage(name, out):
        def run(*args):
            seen.append((name, [np.shape(a) for a in args]))
            return out
        return run

    pairs = tuple(np.zeros((129, 1), np.uint32) for _ in range(5))
    monkeypatch.setattr(be, "_get_stages", lambda mesh=None: (
        stage("prepare", 0), stage("h2c", 0), stage("pairs", 0),
        stage("pairing", 0)))
    monkeypatch.setattr(be, "_get_one_chip_variant",
                        lambda name: stage(name, pairs))
    be.warm_stages(n_sets, 1, single_chip=single_chip)
    n = be.padding_bucket(n_sets, 1, single_chip=True)[0]
    h2c = sorted(shapes[0][0] for name, shapes in seen if name == "h2c")
    assert h2c == ([128, n] if folded_too else [n])
    names = {name for name, _ in seen}
    assert names == {"prepare", "h2c"} | (
        {"pairs_folded", "pairing"} if folded_too else set())
    if folded_too:
        (shapes,) = [sh for name, sh in seen if name == "pairs_folded"]
        assert shapes[3] == (2, n)                            # the index
        (shapes,) = [sh for name, sh in seen if name == "pairing"]
        assert shapes == [(129, 1)] * 5
