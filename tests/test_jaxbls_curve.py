"""Differential tests: jaxbls curve ops vs pure-Python bls381.curve."""

import random

import jax
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381.constants import P, R
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import tower as tw

rng = random.Random(0xC1)


def rand_g1():
    return pc.g1_mul(pc.G1_GEN, rng.randrange(1, R))


def rand_g2():
    return pc.g2_mul(pc.G2_GEN, rng.randrange(1, R))


def test_g1_add_double_roundtrip():
    p, q = rand_g1(), rand_g1()
    dp, dq = co.g1_to_device(p), co.g1_to_device(q)
    add = jax.jit(lambda a, b: co.jac_add(a, b, co.FQ_OPS))
    dbl = jax.jit(lambda a: co.jac_double(a, co.FQ_OPS))
    assert co.g1_from_device(add(dp, dq)) == pc.g1_add(p, q)
    assert co.g1_from_device(dbl(dp)) == pc.g1_add(p, p)
    # identity cases
    ident = co.identity(co.FQ_OPS)
    assert co.g1_from_device(add(dp, ident)) == p
    assert co.g1_from_device(add(ident, dp)) == p
    # p + p via add must route to double
    assert co.g1_from_device(add(dp, dp)) == pc.g1_add(p, p)
    # p + (-p) = identity
    neg = (dp[0], co.FQ_OPS.neg(dp[1]), dp[2])
    assert co.g1_from_device(add(dp, neg)) is None


def test_g2_add_double():
    p, q = rand_g2(), rand_g2()
    dp, dq = co.g2_to_device(p), co.g2_to_device(q)
    add = jax.jit(lambda a, b: co.jac_add(a, b, co.FQ2_OPS))
    assert co.g2_from_device(add(dp, dq)) == pc.g2_add(p, q)
    assert co.g2_from_device(add(dp, dp)) == pc.g2_add(p, p)


def test_g1_scalar_mul_dynamic_bits():
    p = rand_g1()
    zs = [rng.randrange(1, 1 << 64) for _ in range(4)]
    dp = co.g1_batch_to_device([p] * 4)
    bits = jax.numpy.asarray(co.scalars_to_bits(zs, 64))
    mul = jax.jit(lambda pt, b: co.scalar_mul_bits(pt, b, co.FQ_OPS))
    res = mul(dp, bits)
    for i, z in enumerate(zs):
        got = co.g1_from_device(jax.tree_util.tree_map(lambda x: x[i], res))
        assert got == pc.g1_mul(p, z)


def test_g2_scalar_mul_static():
    p = rand_g2()
    k = rng.randrange(1, R)
    dp = co.g2_to_device(p)
    mul = jax.jit(lambda pt: co.scalar_mul_static(pt, k, co.FQ2_OPS))
    assert co.g2_from_device(mul(dp)) == pc.g2_mul(p, k)


def test_subgroup_order_annihilates():
    p = rand_g1()
    dp = co.g1_to_device(p)
    res = jax.jit(lambda pt: co.scalar_mul_static(pt, R, co.FQ_OPS))(dp)
    assert co.g1_from_device(res) is None


def test_tree_sum_masked():
    pts = [rand_g1() for _ in range(5)]
    padded = pts + [None, None, None]
    mask = np.array([1, 1, 1, 1, 1, 0, 0, 0])
    dp = co.g1_batch_to_device(padded)
    s = jax.jit(lambda pt, m: co.masked_tree_sum(pt, m, co.FQ_OPS))(dp, mask)
    expected = None
    for pt in pts:
        expected = pc.g1_add(expected, pt)
    assert co.g1_from_device(s) == expected


# (m entries, rest lanes) -> the (c, fold_steps, finish_rounds) it must plan
_TREE_SUM_SHAPES = {
    "fold_and_finish": ((16, co.TREE_SUM_L0 // 4), (4, 3, 2)),
    "fold_alone": ((8, co.TREE_SUM_L0), (1, 7, 0)),
    "loop_alone": ((8, 2), (8, 0, 3)),       # c = m: the fixed-shape loop
    "unrolled": ((4, 2), (4, 0, 2)),         # m <= 4
}


def _same_point(jac, affine):
    """Host: Jacobian (X, Y, Z) bigints against affine (x, y) or None —
    X = x Z^2, Y = y Z^3, identity = Z 0. No inversion on 2,048 lanes."""
    X, Y, Z = jac
    if affine is None or Z == 0:
        return affine is None and Z == 0
    return X == affine[0] * Z * Z % P and Y == affine[1] * Z * Z * Z % P


@pytest.mark.parametrize("branch", list(_TREE_SUM_SHAPES))
def test_tree_sum_matches_host_sum(branch):
    """tree_sum over axis 0 of an (m, rest) grid against the bigint sum of
    every column, with identity lanes, P + P and P - P meeting inside the
    fold (neighbouring entries) and inside the finish (entries half the
    axis apart), and an all-identity column."""
    (m, rest), plan = _TREE_SUM_SHAPES[branch]
    assert co.tree_sum_plan(m, rest)[:3] == plan

    r = random.Random(0x5EED + m * rest)
    pool = [None, None] + [pc.g1_mul(pc.G1_GEN, r.randrange(1, R)) for _ in range(5)]
    grid = [[r.choice(pool) for _ in range(rest)] for _ in range(m)]
    p, q = pool[2], pool[3]
    col0 = [p, p] + [None] * (m - 2)                      # P + P, first add
    col1 = [q, pc.g1_neg(q), p] + [None] * (m - 3)        # P - P, then 0 + P
    for j in range(m):
        grid[j][0], grid[j][1] = col0[j], col1[j]
    if rest >= 8:
        for j in range(m):
            grid[j][2] = grid[j][3] = grid[j][4] = None   # [4] stays identity
        grid[0][2], grid[m // 2][2] = p, p                # P + P half apart
        grid[0][3], grid[m // 2][3] = q, pc.g1_neg(q)     # P - P half apart

    flat = co.g1_batch_to_device([pt for row in grid for pt in row])
    dev = jax.tree_util.tree_map(
        lambda x: x.reshape((m, rest) + x.shape[1:]), flat
    )
    got = zip(*(
        tw.fq_batch_from_device(v)
        for v in jax.jit(lambda pts: co.tree_sum(pts, co.FQ_OPS))(dev)
    ))
    want = [None] * rest
    for row in grid:
        want = [pc.g1_add(w, pt) for w, pt in zip(want, row)]
    wrong = [k for k, (g, w) in enumerate(zip(got, want)) if not _same_point(g, w)]
    assert not wrong, (branch, wrong[:8])
    assert want[0] == pc.g1_add(p, p) and want[1] == p
    if rest >= 8:
        assert want[2:5] == [pc.g1_add(p, p), None, None]


@pytest.mark.parametrize(
    "m, rest, plan",
    [
        (512, 256, (8, 63, 3, 135_168)),     # block pubkeys, bucket 256x512
        (128, 64, (32, 3, 5, 16_384)),       # gossip pubkeys, bucket 64x128
        (128, 4, (128, 0, 7, 3_584)),        # urgent pubkeys: c = m
        (256, 1, (256, 0, 8, 2_048)),        # sig_acc over 256 sets: c = m
        (64, 1, (64, 0, 6, 384)),
        (8, 4_096, (1, 7, 0, 28_672)),       # rest >= L0: the fold alone
        (4, 8, (4, 0, 2, 24)),               # unrolled: m - 1 adds a lane
        (1, 8, (1, 0, 0, 0)),
    ],
)
def test_tree_sum_plan_table(m, rest, plan):
    """No jit: (c, fold_steps, finish_rounds, lane_additions) by shape, at
    the measured TREE_SUM_L0; c * rest reaches L0 or c is capped at m."""
    assert co.TREE_SUM_L0 == 2048
    assert co.tree_sum_plan(m, rest) == plan
    c = plan[0]
    assert c == m or (c * rest >= co.TREE_SUM_L0 > (c // 2) * rest)


@pytest.mark.parametrize("m", [8, 128, 512, 4096])
def test_tree_sum_add_instances_do_not_grow_with_m(m, monkeypatch):
    """Tracing only: tree_sum calls jac_add at most twice whatever m is
    (fold + finish), once where the plan is the loop alone — the unrolled
    tree's log2(m) instances were the prepare-stage compile whale."""
    calls = []
    real = co.jac_add
    monkeypatch.setattr(
        co, "jac_add", lambda a, b, ops: calls.append(1) or real(a, b, ops)
    )
    for rest, shape in ((256, (m, 256, 24)), (1, (m, 24))):
        _, fold_steps, rounds, _ = co.tree_sum_plan(m, rest)
        fq = jax.ShapeDtypeStruct(shape, np.uint32)
        calls.clear()
        jax.eval_shape(lambda pts: co.tree_sum(pts, co.FQ_OPS), (fq, fq, fq))
        assert len(calls) == (fold_steps > 0) + (rounds > 0) <= 2


def test_batch_affine_roundtrip():
    pts = [rand_g1() for _ in range(3)] + [None]
    dp = co.g1_batch_to_device(pts)
    x, y, inf = jax.jit(lambda p: co.jac_to_affine(p, co.FQ_OPS))(dp)
    xs = tw.fq_batch_from_device(x)
    ys = tw.fq_batch_from_device(y)
    infs = np.asarray(inf)
    for i, pt in enumerate(pts):
        if pt is None:
            assert infs[i]
        else:
            assert not infs[i]
            assert (xs[i], ys[i]) == pt


# ---- the batch-verification coefficient's chain (co.scalar_mul_z) ---------

_Z_CASES = {
    "one": 1,
    "two": 2,
    "top_bit_alone": 1 << 63,
    "all_ones": (1 << 64) - 1,
    "twenty_leading_zeros": (1 << 43) + 0x5A5A5A5A5,
    "random": 0xC0FFEE0DDBA11AD5,
    "identity_base": 0x1234567,          # the lane whose base is the identity
    "masked_slot": 0,                    # a padded set slot: zero bits too
}
@pytest.fixture(scope="module")
def z_chains():
    """ONE compiled program a group, eight lanes, the window stage 1 runs
    (backend.Z_WINDOW), shared by every case below: fn(group)(base lanes...,
    bits) -> (product, met)."""
    import functools

    @functools.lru_cache(maxsize=None)
    def fn(group):
        if group == "g1":
            return jax.jit(lambda p, b: co.scalar_mul_z(
                p, b, co.FQ_OPS, window=be.Z_WINDOW))
        return jax.jit(lambda x, y, inf, b: co.scalar_mul_z(
            (x, y), b, co.FQ2_OPS, p_inf=inf, window=be.Z_WINDOW))

    return fn


def _z_chain_lanes(group, points, zs):
    bits = co.scalars_to_bits(zs, 64)
    if group == "g1":
        # a Z of its own a lane, as tree_sum leaves an aggregate key
        jac = []
        for i, pt in enumerate(points):
            s = 3 + i
            jac.append(None if pt is None else
                       (pt[0] * s * s % P, pt[1] * s ** 3 % P))
        x, y, z = (np.asarray(c) for c in co.g1_batch_to_device(jac))
        scale = np.asarray(tw.fq_batch_to_device(
            [3 + i for i in range(len(points))]))
        z = np.where((z != 0).any(-1, keepdims=True), scale, z)
        return ((x, y, z), bits)
    x, y, _ = co.g2_batch_to_device(points)
    return (x, y, np.array([pt is None for pt in points]), bits)


def _lane_is(group, prod, i, affine):
    """Lane i of a Jacobian product against the host's affine point (None
    the identity): X = x Z^2, Y = y Z^3 in host integers — no inversion on
    the device, which op by op would compile for longer than the chains."""
    if group == "g1":
        return _same_point(
            tuple(tw.fq_batch_from_device(c[i:i + 1])[0] for c in prod), affine)
    from lighthouse_tpu.crypto.bls381 import fields as f

    X, Y, Z = (tuple(tw.fq_batch_from_device(c[i])) for c in prod)
    if affine is None or Z == (0, 0):
        return affine is None and Z == (0, 0)
    zz = f.fq2_sqr(Z)
    return (X == f.fq2_mul(affine[0], zz)
            and Y == f.fq2_mul(affine[1], f.fq2_mul(zz, Z)))


@pytest.fixture(scope="module")
def z_chain_products(z_chains):
    """{group: (bases, products, met)} over the lanes of `_Z_CASES`, each
    program run once."""
    r = random.Random(0x45)
    out = {}
    for group, gen, mul in (("g1", pc.G1_GEN, pc.g1_mul),
                            ("g2", pc.G2_GEN, pc.g2_mul)):
        bases = [mul(gen, r.randrange(1, R)) for _ in _Z_CASES]
        bases[list(_Z_CASES).index("identity_base")] = None
        bases[list(_Z_CASES).index("masked_slot")] = None
        prod, met = z_chains(group)(
            *_z_chain_lanes(group, bases, list(_Z_CASES.values())))
        out[group] = (bases, prod, np.asarray(met))
    return out


@pytest.mark.parametrize("group", ["g1", "g2"])
@pytest.mark.parametrize("case", list(_Z_CASES))
def test_z_chain_matches_the_host_product(z_chain_products, case, group):
    """z * P by the coefficient chain against the pure-Python curve's, as a
    group element (another formula gives another Z): the corner
    coefficients, an identity base, a masked slot; `met` stays down."""
    bases, prod, met = z_chain_products[group]
    i = list(_Z_CASES).index(case)
    mul = pc.g1_mul if group == "g1" else pc.g2_mul
    z = _Z_CASES[case]
    want = mul(bases[i], z) if bases[i] is not None and z else None
    assert _lane_is(group, prod, i, want)
    assert not met[i]


# coefficients under which the bit chain would meet T + T and -T + T for a
# point T of order 13 (14 T = T, 12 T = -T); building the window's table
# meets 14 T + T on every lane of such a point, whatever its digits
_STEERED = (0b1111, 0b1101)


def test_z_chain_reports_the_addition_it_leaves_out(z_chains):
    """A point of order 13 on the twist: the chain meets T + T (the case the
    shorter addition gets wrong) and `met` rises on its lanes — at the least
    on the first — while the same coefficients on points of order r leave
    it down and give the host's products."""
    from jaxbls_warm import order_13_twist_point

    t = order_13_twist_point()
    r = random.Random(0x13)
    good = [pc.g2_mul(pc.G2_GEN, r.randrange(1, R)) for _ in range(2)]
    zs = list(_STEERED) * 2 + [5, 5, 1, 1]
    points = [t, t] + good + [t, good[0], t, None]
    prod, met = z_chains("g2")(*_z_chain_lanes("g2", points, zs))
    met = np.asarray(met)
    assert met[0], "T + T went unreported"
    assert met[1] or _lane_is("g2", prod, 1, pc.mul_raw(t, zs[1], pc.FQ2_OPS))
    assert not met[2] and not met[3] and not met[5] and not met[7]
    for i in (2, 3, 5):
        assert _lane_is("g2", prod, i, pc.g2_mul(points[i], zs[i]))
    # a lane the chain did not steer wrong is either exact or reported
    for i in (4, 6):
        assert met[i] or _lane_is("g2", prod, i,
                                  pc.mul_raw(t, zs[i], pc.FQ2_OPS))
