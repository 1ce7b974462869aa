"""Differential tests: jaxbls pairing vs pure-Python bls381.pairing.

The device pairing uses unit-scaled lines, so raw Miller values differ from
the ground truth by Fq2 units — equality is checked after final
exponentiation (the only form consensus code ever uses)."""

import functools
import hashlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381 import fields as pyf
from lighthouse_tpu.crypto.bls381 import pairing as pp
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
from lighthouse_tpu.crypto.jaxbls import tower as tw

rng = random.Random(0xE7)


def _device_pairs(pairs, pad_to):
    """Host affine pairs [(g1, g2), ...] -> batched device arrays + mask."""
    n = len(pairs)
    mask = np.zeros(pad_to, bool)
    mask[:n] = True
    g1s = [p for p, _ in pairs] + [None] * (pad_to - n)
    g2s = [q for _, q in pairs] + [None] * (pad_to - n)
    xp = tw.fq_batch_to_device([p[0] if p else 0 for p in g1s])
    yp = tw.fq_batch_to_device([p[1] if p else 0 for p in g1s])
    xq = tw.fq2_batch_to_device([q[0] if q else (0, 0) for q in g2s])
    yq = tw.fq2_batch_to_device([q[1] if q else (0, 0) for q in g2s])
    return (xp, yp), (xq, yq), jnp.asarray(mask)


# Final exponentiation is compiled ONCE in this module (it was five times: fused
# behind a Miller loop in `_full_pairing` and, at two and at four lanes, in
# `pairing_product_is_one`, and alone twice): every test takes its Miller value
# from the module's one-accumulator `_stage_miller` at nine lanes ("w1" of
# `miller_loops`; fewer pairs are padded with masked lanes, which
# test_padded_lanes_contribute_one shows neutral) and hands it to the one
# program below, as one chip's stage 4 hands it from program to program.
_compiled: dict = {}          # name -> executable, filled by `_programs`
_digests: dict = {}           # name -> sha256 of the program's lowered text
_final_exp_eqns: list = []    # the traced equations of `_final_exp_and_verdict`


def _final_exp_and_verdict(f):
    """The element after final exponentiation and, from the same program,
    `be._stage_final_exp`'s verdict on it (that stage's body:
    test_the_modules_final_exponentiation_is_the_stages_program)."""
    e = po.final_exponentiation(f)
    return e, tw.fq12_eq_one(e)


def _final_exp(f):
    return _compiled["final_exp"](f)[0]


def _stage_final_exp(f):
    return _compiled["final_exp"](f)[1]


def _nine(p, q, mask):
    """(xp, yp), (xq, yq) and the mask of up to nine pair lanes as
    `_stage_miller`'s five arguments at nine, the lanes added masked and
    zero, as the backend pads."""
    def pad(a):
        a = np.asarray(a)
        return np.pad(a, [(0, 9 - a.shape[0])] + [(0, 0)] * (a.ndim - 1))

    return (*map(pad, p), *map(pad, q), pad(mask).astype(bool))


def _full_pairing(p, q, mask):
    return _final_exp(_compiled["w1"](*_nine(p, q, mask)))


def _product_check(p, q, mask):
    return _stage_final_exp(_compiled["w1"](*_nine(p, q, mask)))


@pytest.fixture(scope="module", autouse=True)
def _no_cache_writes_for_this_module():
    """Serializing this module's product-check executable reproducibly
    segfaults the XLA:CPU cache writer when it follows the full suite's
    compile sequence (5/5 warming passes died at the same line). Disable
    persistent-cache WRITES for the module; its programs recompile each
    cold run instead of crashing the process."""
    import jax as _jax

    prev = _jax.config.jax_persistent_cache_min_compile_time_secs
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 10**9)
    yield
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)


def test_single_pairing_matches_python():
    a = rng.randrange(1, R)
    b = rng.randrange(1, R)
    p = pc.g1_mul(pc.G1_GEN, a)
    q = pc.g2_mul(pc.G2_GEN, b)
    dp, dq, mask = _device_pairs([(p, q)], 1)
    got = tw.fq12_from_device(_full_pairing(dp, dq, mask))
    assert got == pp.pairing(p, q)


def test_bilinearity_product_check():
    # e(aG1, bG2) * e(-abG1, G2) == 1
    a = rng.randrange(1, R)
    b = rng.randrange(1, R)
    p1 = pc.g1_mul(pc.G1_GEN, a)
    q1 = pc.g2_mul(pc.G2_GEN, b)
    p2 = pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * b % R))
    q2 = pc.G2_GEN
    dp, dq, mask = _device_pairs([(p1, q1), (p2, q2)], 2)
    assert bool(_product_check(dp, dq, mask))


def test_product_check_rejects_wrong():
    a = rng.randrange(1, R)
    p1 = pc.g1_mul(pc.G1_GEN, a)
    q1 = pc.g2_mul(pc.G2_GEN, 7)
    p2 = pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * 8 % R))  # wrong scalar
    dp, dq, mask = _device_pairs([(p1, q1), (p2, pc.G2_GEN)], 2)
    assert not bool(_product_check(dp, dq, mask))


def test_padded_lanes_contribute_one():
    # Same bilinearity check but padded to 4 lanes with garbage-identity pads.
    a = rng.randrange(1, R)
    b = rng.randrange(1, R)
    p1 = pc.g1_mul(pc.G1_GEN, a)
    q1 = pc.g2_mul(pc.G2_GEN, b)
    p2 = pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * b % R))
    dp, dq, mask = _device_pairs([(p1, q1), (p2, pc.G2_GEN)], 4)
    assert bool(_product_check(dp, dq, mask))


# Stage 3 of a dispatch that folds its sets by message (backend.message_lanes,
# PR 44) in front of this module's four-lane product check: six single-key
# sets in a bucket of eight on up to three message lanes, k + 1 = 4 pairs.
# Stage 1's outputs are
# the host's (z * pk a set, the sum of z * sig; 64-bit coefficients), the
# messages' points the pure-Python hash-to-G2's; `_stage_pairs_folded` from 8
# sets onto 3 lanes is the one program compiled here (small). The verdict is
# the pure-Python backend's on the same sets.
_FOLDED = ((3, b"\xA1" * 32), (9, b"\xA1" * 32), (0, b"\xB2" * 32),
           (7, b"\xA1" * 32), (3, b"\xB2" * 32), (2, b"\xA1" * 32))
_pairs_folded = jax.jit(be._stage_pairs_folded)


@functools.lru_cache(maxsize=None)
def _folded_signature(sk: int, msg: bytes):
    """sk H(msg) by the pure-Python curve code, once a (key, message)."""
    from lighthouse_tpu.crypto.bls import api as bls_api

    return pc.g2_mul(bls_api.hash_to_g2_point(msg), sk)


@pytest.mark.parametrize("damage", [
    None, "swapped_among_one_message", "flipped_message", "replaced_signer",
    "one_message"], ids=lambda d: d or "valid")
def test_folded_stage_3_then_the_product_check_give_the_reference_verdict(
        damage):
    """Sound (two messages, four and two sets, one validator under both;
    the third lane holds no message and is masked), with the signatures of
    two sets of ONE message exchanged, with one byte of one set's shared
    message flipped (a lane of its own, the third), with a signer replaced,
    and with every set on one message."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import api as bls_api

    be._init_consts()
    r = random.Random(0xF01D)
    sks = [r.randrange(1, R) for _ in range(12)]
    pks = [bls.PublicKey(pc.g1_mul(pc.G1_GEN, sk)) for sk in sks]

    def signed(i, msg, signer=None):
        sk = sks[i if signer is None else signer]
        return bls.SignatureSet(
            bls.Signature(_folded_signature(sk, msg)), [pks[i]], msg)

    folded = _FOLDED
    if damage == "one_message":
        folded = tuple((i, b"\xA1" * 32) for i, _ in _FOLDED[:4]) + (
            (10, b"\xA1" * 32), (2, b"\xA1" * 32))
    sets = [signed(i, msg) for i, msg in folded]
    if damage == "swapped_among_one_message":
        a, b = sets[0], sets[3]                   # both sign \xA1...
        assert a.message == b.message
        sets[0] = bls.SignatureSet(b.signature, a.signing_keys, a.message)
        sets[3] = bls.SignatureSet(a.signature, b.signing_keys, b.message)
    elif damage == "flipped_message":
        victim = sets[1]
        sets[1] = bls.SignatureSet(victim.signature, victim.signing_keys,
                                   b"\xA0" + victim.message[1:])
    elif damage == "replaced_signer":
        sets[5] = signed(4, sets[5].message, signer=2)
    bls_api.set_backend("python")
    want = bls.verify_signature_sets(sets)
    assert want is (damage in (None, "one_message"))

    n, k = 8, 3
    lane_of: dict = {}
    for s in sets:
        lane_of.setdefault(s.message, len(lane_of))
    assert len(lane_of) == {"flipped_message": 3, "one_message": 1}.get(
        damage, 2)
    zs = [r.randrange(1, 1 << 64) for _ in sets]
    z_pk = co.g1_batch_to_device(
        [pc.g1_mul(s.signing_keys[0].point, z) for s, z in zip(sets, zs)]
        + [None] * (n - len(sets)))                   # padded set slots
    sig_sum = None
    for s, z in zip(sets, zs):
        sig_sum = pc.g2_add(sig_sum, pc.g2_mul(s.signature.point, z))
    h_jac = co.g2_batch_to_device(
        [bls_api.hash_to_g2_point(m) for m in lane_of]
        + [None] * (k - len(lane_of)))
    px, py, qxx, qyy, pair_mask = _pairs_folded(
        z_pk, h_jac, co.g2_to_device(sig_sum),
        be.message_fold_index([lane_of[s.message] for s in sets], n, k))
    assert px.shape[0] == k + 1
    assert [bool(b) for b in np.asarray(pair_mask)] == [
        j < len(lane_of) for j in range(k)] + [True]
    assert bool(_product_check((px, py), (qxx, qyy), pair_mask)) is want
    assert _pairs_folded._cache_size() == 1   # one shape, every distribution


def test_the_modules_final_exponentiation_is_the_stages_program():
    """This module's one final exponentiation traced to
    `be._stage_final_exp`'s own equations, one after the other: the stage's
    program, with the element kept beside the verdict. (The stage itself is
    compiled and held to the pure-Python verdicts in
    test_jaxbls_backend.py's urgent lane.)"""
    f = jax.ShapeDtypeStruct(tw.FQ12_ONE.shape, tw.FQ12_ONE.dtype)
    stage = jax.make_jaxpr(be._stage_final_exp)(f)
    assert [str(e) for e in stage.eqns] == _final_exp_eqns
    assert len(stage.eqns) > 10


def test_final_exp_matches_python_on_random_miller_output():
    # Feed the same Miller value through both final exps.
    p = pc.g1_mul(pc.G1_GEN, rng.randrange(1, R))
    q = pc.g2_mul(pc.G2_GEN, rng.randrange(1, R))
    m = pp.miller_loop([(p, q)])
    dm = tw.fq12_to_device(m)
    got = tw.fq12_from_device(_final_exp(dm))
    assert got == pp.final_exponentiation(m)


# ------------------------------------------- the Miller loop's lane plan

# (platform the program is built for, pair lanes, plan): W accumulators,
# dense tree levels left in each step, levels of the one tree after the
# loop. 5 / 65 / 257 are the served buckets (4x128, 64x128, 256x512), 4 the
# KZG check's lanes; 17 and 65 are also a chip's share of the gossip and
# block buckets' pairs (65 -> 68, 257 -> 260) on a four-chip `sets` mesh. On
# a TPU a row of lanes costs what one lane costs and every count takes the
# row; on the CPU, where the tests run, a lane costs a lane.
_CPU_PLANS = [
    (1, (1, 0, 0)), (2, (1, 0, 0)), (3, (1, 0, 0)), (5, (1, 1, 0)),
    (9, (1, 2, 0)), (17, (1, 3, 0)), (33, (128, 0, 7)), (65, (128, 0, 7)),
    (129, (128, 0, 7)), (257, (128, 0, 7)), (258, (128, 1, 7)),
    # a 1,024-set bucket's pairs: eight lines an accumulator, the line
    # pairs and two dense levels in the step (subnet_flood_1key)
    (1025, (128, 2, 7)),
]
_PLAN_ROWS = [("cpu", n, plan) for n, plan in _CPU_PLANS] + [
    ("tpu", n, plan if n >= 33 else (128, 0, 7)) for n, plan in _CPU_PLANS]


@pytest.mark.parametrize("platform,n_pairs,plan", _PLAN_ROWS,
                         ids=["%s-%d" % row[:2] for row in _PLAN_ROWS])
def test_miller_lane_plan(platform, n_pairs, plan):
    w, in_step, after = po.miller_lane_plan(n_pairs, platform)
    assert (w, in_step, after) == plan
    if platform == jax.default_backend():
        assert po.miller_lane_plan(n_pairs) == plan     # None = this process
    if w == 1:
        # the loop of a platform without the row: the whole tree over the
        # line pairs is in the step
        assert n_pairs < po.MILLER_WIDE_FROM[platform] and after == 0
        return
    # a full row of accumulators, each taking g lines a step: g the
    # smallest power of two that seats every pair but the one over
    g = po._lines_per_accumulator(n_pairs, w)
    assert w == po.MILLER_LANES and g & (g - 1) == 0
    assert w * g >= n_pairs - 1 and (g == 1 or w * g // 2 < n_pairs - 1)
    # a sparse line or a line pair needs no dense level; each doubling of
    # g beyond that leaves one in the step; the rest is one tree, after
    assert in_step == max(g.bit_length() - 2, 0)
    assert after == w.bit_length() - 1


@pytest.fixture(scope="module", autouse=True)
def _programs(_no_cache_writes_for_this_module):
    """Every program of the module, compiled side by side (one thread a
    program: XLA releases the GIL) into `_compiled`.

    The backend's `_stage_miller` (miller_loop_product over the stage's
    five flat arguments) at nine pair lanes under three plans: "w1" one
    accumulator (the CPU's loop), "pairs" four accumulators of a line pair
    each + the pair over (the block bucket's form), "padded" sixteen
    accumulators of one sparse line, seven of them padding (the form of
    the gossip bucket and, on a TPU, of the urgent bucket and the KZG
    check); and at seventeen pair lanes "eights": two accumulators of
    EIGHT lines each + the pair over, so line pairs and two dense levels
    inside the step, the form of a 1,024-set bucket's 1,025 pairs on the
    chip's row of 128. The module's four Miller-loop compiles; beside them
    the one final exponentiation and `_stage_pairs_folded` from eight sets
    onto three lanes, at the operands of its test."""
    from jaxbls_warm import run_in_threads

    be._init_consts()
    shipped = po.MILLER_LANES, po.MILLER_WIDE_FROM
    plans = {"w1": (128, 1 << 30, (1, 2, 0), 8, 9),
             "pairs": (4, 1, (4, 0, 2), 2, 9),
             "padded": (16, 1, (16, 0, 4), 1, 9),
             "eights": (2, 1, (2, 2, 1), 8, 17)}
    traced = jax.jit(_final_exp_and_verdict).trace(
        jax.ShapeDtypeStruct(tw.FQ12_ONE.shape, tw.FQ12_ONE.dtype))
    _final_exp_eqns[:] = [str(e) for e in traced.jaxpr.eqns]
    lowered = {"final_exp": traced.lower()}
    try:
        for name, (lanes, wide_from, plan, g, n_pairs) in plans.items():
            # the one entry every platform falls back to: whatever this
            # process runs on takes it
            po.MILLER_LANES, po.MILLER_WIDE_FROM = lanes, {"cpu": wide_from}
            assert po.miller_lane_plan(n_pairs) == plan
            assert po._lines_per_accumulator(n_pairs, plan[0]) == g
            # traced now, while the patched plan is in force, and as a
            # function of its own: jit keeps a function's trace by its
            # argument shapes, whatever plan is in force, and handed the
            # three nine-lane plans the first one's program from PR 32 on
            def stage(*args):
                return be._stage_miller(*args)

            stage.__name__ = "_stage_miller_" + name
            dp, dq, mask = _device_pairs([], n_pairs)
            lowered[name] = jax.jit(stage).lower(*dp, *dq, mask)
    finally:
        po.MILLER_LANES, po.MILLER_WIDE_FROM = shipped

    def compile_(name):
        _digests[name] = hashlib.sha256(
            lowered[name].as_text().encode()).hexdigest()
        _compiled[name] = lowered[name].compile()

    def folded():
        jax.block_until_ready(_pairs_folded(
            co.g1_batch_to_device([pc.G1_GEN] * 7 + [None]),
            co.g2_batch_to_device([pc.G2_GEN] * 2 + [None]),
            co.g2_to_device(pc.G2_GEN), be.message_fold_index([0], 8, 3)))

    run_in_threads(folded, *(functools.partial(compile_, n) for n in lowered))
    yield
    _compiled.clear()
    _digests.clear()
    _final_exp_eqns.clear()


@pytest.fixture(scope="module")
def miller_loops():
    """The four `_stage_miller` plans of `_programs`, by name."""
    return _compiled


def test_every_plan_is_a_program_of_its_own(miller_loops):
    """The four plans lowered to four different programs: what the tests
    below compare are different Miller loops, not one loop with itself."""
    plans = ("w1", "pairs", "padded", "eights")
    assert set(plans) < set(miller_loops)
    assert len({_digests[name] for name in plans}) == 4


@functools.cache
def _eight_pairs():
    """(a_i, b_i) and the pairs (a_i G1, b_i G2), i < 8."""
    r = random.Random(0x9A1)
    ab = [(r.randrange(1, R), r.randrange(1, R)) for _ in range(8)]
    return ab, [(pc.g1_mul(pc.G1_GEN, a), pc.g2_mul(pc.G2_GEN, b))
                for a, b in ab]


def _nine_lanes(masked, tamper=False, filler=None):
    """Device operands of nine pair lanes. Lanes 0-7 hold e(a_i G1, b_i G2);
    those in `masked` are padding (zeroed as the backend pads, or holding
    the real pair `filler`); lane 8 closes the product of the live ones to
    1 with e(-sum(a_i b_i) G1, G2) — off by one G1 when `tamper`."""
    ab, pairs = _eight_pairs()
    total = sum(a * b for i, (a, b) in enumerate(ab) if i not in masked)
    lanes = list(pairs)
    lanes.append((pc.g1_neg(pc.g1_mul(pc.G1_GEN, (total + tamper) % R)),
                  pc.G2_GEN))
    for i in masked:
        lanes[i] = filler or (None, None)
    dp, dq, _ = _device_pairs(lanes, 9)
    mask = np.ones(9, bool)
    mask[list(masked)] = False
    return (*dp, *dq, jnp.asarray(mask))      # _stage_miller's arguments


@pytest.mark.parametrize("tamper", [False, True], ids=["valid", "tampered"])
@pytest.mark.parametrize("masked", [{3, 6}, {2, 3, 4, 5, 6, 7}],
                         ids=["two_masked", "kzg_shape"])
def test_wide_accumulators_give_the_one_accumulator_miller_value(
        miller_loops, masked, tamper):
    """Nine pairs, some lanes masked — two (3 and 6), or all but two and
    the closing pair, what the KZG check's two pairs are in a padded row:
    the Miller value of four and of sixteen accumulators equals one
    accumulator's limb for limb, BEFORE final exponentiation, and the
    verdict after it is the product's truth."""
    lanes = _nine_lanes(masked, tamper)
    narrow = np.asarray(miller_loops["w1"](*lanes))
    for name in ("pairs", "padded"):
        wide = miller_loops[name](*lanes)
        assert np.array_equal(np.asarray(wide), narrow), name
        assert bool(tw.fq12_eq_one(_final_exp(wide))) is (not tamper)


@pytest.mark.parametrize("tamper", [False, True], ids=["valid", "tampered"])
@pytest.mark.parametrize("form", ["w1", "padded"])
def test_stage_four_as_two_programs_gives_the_product_checks_verdict(
        miller_loops, form, tamper):
    """Stage 4 as one chip serves a wide bucket,
    `_stage_final_exp(_stage_miller(...))`, the Miller value handed from
    one program to the other: the verdict is pairing_product_is_one's —
    that function's own two steps over the same Miller value — and the
    product's truth, at a padded row of accumulators (the served form) and
    at one accumulator."""
    f = miller_loops[form](*_nine_lanes({3, 6}, tamper))
    assert f.shape == tw.FQ12_ONE.shape
    ok = _stage_final_exp(f)
    assert ok.shape == () and ok.dtype == jnp.bool_
    assert bool(ok) is bool(tw.fq12_eq_one(_final_exp(f))) is (not tamper)


@pytest.mark.parametrize("tamper", [False, True], ids=["valid", "tampered"])
def test_eight_lines_an_accumulator_give_the_product_of_the_loops(
        miller_loops, tamper):
    """Seventeen pair lanes on two accumulators: each takes EIGHT lines a
    step (four line pairs, two dense in-step levels: miller_lane_plan's
    (W, 2, .), what 1,025 pairs are on a row of 128), the pair over folded
    into lane 0. Its Miller value equals, limb for limb, the product of
    one-accumulator loops over the same pairs (lanes 0-8 and lanes 9-16 as
    two calls of the nine-lane loop, multiplied), and the verdict after
    final exponentiation is the product's truth."""
    ab, pairs = _eight_pairs()
    lanes = pairs + [(pc.g1_mul(pc.G1_GEN, b), pc.g2_mul(pc.G2_GEN, a))
                     for a, b in ab]                  # sixteen pairs
    total = 2 * sum(a * b for a, b in ab)
    lanes.append((pc.g1_neg(pc.g1_mul(pc.G1_GEN, (total + tamper) % R)),
                  pc.G2_GEN))
    dp, dq, _ = _device_pairs(lanes, 17)
    wide = miller_loops["eights"](*dp, *dq, jnp.ones(17, bool))

    def nine(chunk):
        p, q, _ = _device_pairs(chunk + [(None, None)] * (9 - len(chunk)), 9)
        mask = np.arange(9) < len(chunk)
        return miller_loops["w1"](*p, *q, jnp.asarray(mask))

    narrow = tw.fq12_mul(nine(lanes[:9]), nine(lanes[9:]))
    assert np.array_equal(np.asarray(wide), np.asarray(narrow))
    assert bool(tw.fq12_eq_one(_final_exp(wide))) is (not tamper)


def test_all_masked_accumulator_lane_leaves_the_product_unchanged(
        miller_loops):
    """With four accumulators over nine lanes, accumulator 1 holds lanes 1
    and 5. Masked both, it stays 1 whatever the padding holds: zeros and a
    real pair give the same Miller value, which is one accumulator's and
    still verifies."""
    zeros = _nine_lanes({1, 5})
    junk = _nine_lanes({1, 5}, filler=(pc.G1_GEN, pc.G2_GEN))
    wide = miller_loops["pairs"](*zeros)
    assert np.array_equal(np.asarray(wide),
                          np.asarray(miller_loops["pairs"](*junk)))
    assert np.array_equal(np.asarray(wide),
                          np.asarray(miller_loops["w1"](*zeros)))
    assert bool(tw.fq12_eq_one(_final_exp(wide)))
