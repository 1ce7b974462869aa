"""Batched G1/G2 group ops on TPU: Jacobian coordinates over jaxbls.tower.

Points are pytrees (X, Y, Z) with the identity encoded as Z == 0; coordinates
are Fq limb arrays (G1) or Fq2 pairs (G2) in Montgomery form. All ops
broadcast over leading batch dims and are branch-free (selects), so they
vmap/scan cleanly inside jit — the TPU-native counterpart of blst's G1/G2
point arithmetic used by /root/reference/crypto/bls/src/impls/blst.rs.

Ground truth for differential tests: lighthouse_tpu/crypto/bls381/curve.py.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..bls381.constants import P, R
from ..bls381 import curve as pc
from . import limbs as lb
from . import tower as tw


class _Ops:
    """Field-generic namespace so G1 (Fq) and G2 (Fq2) share point formulas.

    `zero`/`one` are PROPERTIES: each use materializes them fresh from a
    shape and a host array, never from a module-level device constant (see
    the constants note in limbs.py)."""

    __slots__ = (
        "add", "sub", "mul", "sqr", "neg", "small", "select", "inv",
        "is_zero", "eq", "_zero_shape", "_one_np",
    )

    def __init__(self, *, zero_shape, one_np, **kw):
        for k, v in kw.items():
            setattr(self, k, v)
        self._zero_shape = zero_shape
        self._one_np = one_np

    @property
    def zero(self):
        return jnp.zeros(self._zero_shape, jnp.uint32)

    @property
    def one(self):
        return jnp.asarray(self._one_np)


def _fq_select(cond, a, b):
    # 32-bit reshape, then compare (i1 minor-dim inserts don't lower)
    return jnp.where(lb.b2u(cond)[..., None] == 1, a, b)


FQ_OPS = _Ops(
    add=lb.add_mod, sub=lb.sub_mod, mul=lb.mont_mul, sqr=lb.mont_sqr,
    neg=lb.neg_mod, small=lb.mul_small, select=_fq_select, inv=lb.mont_inv,
    is_zero=lb.is_zero, eq=lb.eq,
    zero_shape=(lb.NL,), one_np=tw._mont_const(1),
)

FQ2_OPS = _Ops(
    add=lb.add_mod, sub=lb.sub_mod, mul=tw.fq2_mul, sqr=tw.fq2_sqr,
    neg=lb.neg_mod, small=lb.mul_small, select=tw.fq2_select, inv=tw.fq2_inv,
    is_zero=tw.fq2_is_zero, eq=tw.fq2_eq,
    zero_shape=(2, lb.NL), one_np=tw.FQ2_ONE,
)


def identity(ops, batch=()):
    z = jax.tree_util.tree_map(lambda c: jnp.broadcast_to(c, batch + c.shape), ops.zero)
    o = jax.tree_util.tree_map(lambda c: jnp.broadcast_to(c, batch + c.shape), ops.one)
    return (o, o, z)


def pt_select(ops, cond, a, b):
    return tuple(ops.select(cond, x, y) for x, y in zip(a, b))


def is_identity(ops, p):
    return ops.is_zero(p[2])


def _stk(ops, *els):
    """Stack field elements along a new lane axis just above the element
    dims (Fq: (..., NL) -> (..., k, NL); Fq2: (..., 2, NL) -> (..., k, 2, NL)).
    Lane stacking is THE compile-time lever: each ops.mul call costs a fixed
    ~400 HLO ops regardless of lane count, so point formulas gather their
    independent products into few wide calls (the same trick the tower
    uses for fq6/fq12)."""
    axis = -1 if ops is FQ_OPS else -2
    axis -= 1
    return jnp.stack(els, axis=axis)


def _lanes(ops, stacked, k):
    # static integer indexing (a squeeze-slice) instead of jnp.take, which
    # lowers through gather
    tail = (slice(None),) * (1 if ops is FQ_OPS else 2)
    return tuple(stacked[(Ellipsis, i) + tail] for i in range(k))


def jac_double(p, ops):
    """Identity-safe Jacobian doubling (Z=0 stays Z=0; no y=0 points in the
    prime-order subgroups of BLS12-381). 8 field products in 3 stacked
    multiply calls."""
    X, Y, Z = p
    # round 1: A = X^2, B = Y^2, YZ = Y*Z                (one call, 3 lanes)
    r1 = ops.mul(_stk(ops, X, Y, Y), _stk(ops, X, Y, Z))
    A, B, YZ = _lanes(ops, r1, 3)
    # round 2: C = B^2, t = (X+B)^2, F = (3A)^2          (one call, 3 lanes)
    E = ops.small(A, 3)
    XB = ops.add(X, B)
    r2 = ops.mul(_stk(ops, B, XB, E), _stk(ops, B, XB, E))
    C, t, F = _lanes(ops, r2, 3)
    D = ops.small(ops.sub(ops.sub(t, A), C), 2)
    X3 = ops.sub(F, ops.small(D, 2))
    # round 3: E*(D - X3)                                 (one call, 1 lane)
    Y3 = ops.sub(ops.mul(E, ops.sub(D, X3)), ops.small(C, 8))
    Z3 = ops.small(YZ, 2)
    return (X3, Y3, Z3)


def jac_add(p1, p2, ops):
    """Complete Jacobian addition via selects (handles identity/equal/
    negation). The general case and the embedded doubling (for P == Q)
    share stacked multiply calls — ~6 wide multiplies total instead of ~20
    narrow ones, which is what keeps chained adds compilable."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    # round 1: Z1Z1, Z2Z2, Y1Z2, Y2Z1, Y1^2(dbl B), Y1Z1(dbl YZ)
    r1 = ops.mul(
        _stk(ops, Z1, Z2, Y1, Y2, Y1, Y1),
        _stk(ops, Z1, Z2, Z2, Z1, Y1, Z1),
    )
    Z1Z1, Z2Z2, Y1Z2, Y2Z1, dB, dYZ = _lanes(ops, r1, 6)
    # round 2: U1, U2, S1, S2 + dbl lanes: A = X1^2, C = dB^2, t = (X1+dB)^2
    dXB = ops.add(X1, dB)
    r2 = ops.mul(
        _stk(ops, X1, X2, Y1Z2, Y2Z1, X1, dB, dXB),
        _stk(ops, Z2Z2, Z1Z1, Z2Z2, Z1Z1, X1, dB, dXB),
    )
    U1, U2, S1, S2, dA, dC, dt = _lanes(ops, r2, 7)
    H = ops.sub(U2, U1)
    r = ops.sub(S2, S1)
    dE = ops.small(dA, 3)
    # round 3: HH = H^2, rr = r^2, Z1Z2 = Z1*Z2, dF = dE^2
    r3 = ops.mul(_stk(ops, H, r, Z1, dE), _stk(ops, H, r, Z2, dE))
    HH, rr, Z1Z2, dF = _lanes(ops, r3, 4)
    dD = ops.small(ops.sub(ops.sub(dt, dA), dC), 2)
    dX3 = ops.sub(dF, ops.small(dD, 2))
    # round 4: HHH = H*HH, V = U1*HH, Z3 = Z1Z2*H, dY3a = dE*(dD - dX3)
    r4 = ops.mul(
        _stk(ops, H, U1, Z1Z2, dE),
        _stk(ops, HH, HH, H, ops.sub(dD, dX3)),
    )
    HHH, V, Z3, dY3a = _lanes(ops, r4, 4)
    X3 = ops.sub(ops.sub(rr, HHH), ops.small(V, 2))
    # round 5: r*(V - X3), S1*HHH
    r5 = ops.mul(_stk(ops, r, S1), _stk(ops, ops.sub(V, X3), HHH))
    rVX3, S1HHH = _lanes(ops, r5, 2)
    Y3 = ops.sub(rVX3, S1HHH)
    general = (X3, Y3, Z3)

    dY3 = ops.sub(dY3a, ops.small(dC, 8))
    dZ3 = ops.small(dYZ, 2)
    doubled = (dX3, dY3, dZ3)

    h_zero = ops.is_zero(H)
    r_zero = ops.is_zero(r)
    p1_inf = ops.is_zero(Z1)
    p2_inf = ops.is_zero(Z2)

    out = pt_select(ops, jnp.logical_and(h_zero, r_zero), doubled, general)
    inf = jax.tree_util.tree_map(lambda c, g: jnp.broadcast_to(c, g.shape), identity(ops), general)
    out = pt_select(ops, jnp.logical_and(h_zero, jnp.logical_not(r_zero)), inf, out)
    out = pt_select(ops, p1_inf, p2, out)
    out = pt_select(ops, p2_inf, p1, out)
    return out


def affine_to_jac(ops, aff, inf_mask=None):
    """(x, y) affine -> Jacobian. inf_mask (...,) bool marks identity entries."""
    x, y = aff
    batch = np.shape(ops.is_zero(x))

    def bcast(c):
        return jnp.broadcast_to(c, batch + c.shape)

    one = jax.tree_util.tree_map(bcast, ops.one)
    if inf_mask is None:
        Z = one
    else:
        zero = jax.tree_util.tree_map(bcast, ops.zero)
        Z = ops.select(inf_mask, zero, one)
    return (x, y, Z)


def jac_to_affine(p, ops):
    """Jacobian -> affine (x, y, inf_mask). One Fermat inversion per element
    (batched under the hood: the pow scan runs over the whole batch at once)."""
    X, Y, Z = p
    inf = ops.is_zero(Z)
    safe_z = ops.select(inf, jnp.broadcast_to(ops.one, Z.shape), Z)
    zinv = ops.inv(safe_z)
    zinv2 = ops.sqr(zinv)
    zinv3 = ops.mul(zinv2, zinv)
    return (ops.mul(X, zinv2), ops.mul(Y, zinv3), inf)


def scalar_mul_bits(p_jac, bits, ops):
    """p * k where bits is a (..., nbits) uint32 array, MSB first (dynamic
    scalars, e.g. the 64-bit batch-verification coefficients)."""

    def body(acc, bit):
        acc = jac_double(acc, ops)
        added = jac_add(acc, p_jac, ops)
        return pt_select(ops, bit == 1, added, acc), None

    batch = bits.shape[:-1]
    init = identity(ops)
    init = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), init, p_jac
    )
    moved = jnp.moveaxis(bits, -1, 0)
    acc, _ = jax.lax.scan(body, init, moved)
    return acc


# ---- the batch-verification coefficient's chain ---------------------------
#
# z * P for a 64-bit z and a P of prime order r ~ 2^255: every accumulator is
# k * P with 0 <= k < 2^64, so the chain never adds a point to itself or to
# its negative, and the complete jac_add's embedded doubling (7 of its 23
# products) is dead work. The formulas below leave it out and REPORT the one
# comparison that says they were wrong (H == 0 with both points finite), so a
# caller whose input is not of order r refuses it instead of trusting the sum.


def _products(ops, muls=(), sqrs=()):
    """One stacked multiply round: the products a * b of `muls` and the
    squares of `sqrs`, all in ONE lb.mont_mul call. Over Fq a square is a
    product; over Fq2 a product is three Fq lanes (Karatsuba, as
    tower.fq2_mul) and a square two ((a0 + a1)(a0 - a1), a0 a1, as
    tower.fq2_sqr). Returns (products, squares) as tuples."""
    n_m, n_s = len(muls), len(sqrs)
    if ops is FQ_OPS:
        t = lb.mont_mul(
            jnp.stack([a for a, _ in muls] + list(sqrs), axis=-2),
            jnp.stack([b for _, b in muls] + list(sqrs), axis=-2),
        )
        out = tuple(t[..., i, :] for i in range(n_m + n_s))
        return out[:n_m], out[n_m:]

    def parts(els):
        s = jnp.stack(els, axis=-3)                    # (..., k, 2, NL)
        return s[..., 0, :], s[..., 1, :]

    lhs, rhs = [], []        # the Fq lanes of the one mont_mul, by group
    if n_m:
        a0, a1 = parts([a for a, _ in muls])
        b0, b1 = parts([b for _, b in muls])
    if n_s:
        s0, s1 = parts(list(sqrs))
    # every operand sum in one add: a0 + a1, b0 + b1, s0 + s1
    sums = lb.add_mod(
        jnp.concatenate(([a0, b0] if n_m else []) + ([s0] if n_s else []), axis=-2),
        jnp.concatenate(([a1, b1] if n_m else []) + ([s1] if n_s else []), axis=-2),
    )
    if n_m:
        lhs += [a0, a1, sums[..., :n_m, :]]
        rhs += [b0, b1, sums[..., n_m:2 * n_m, :]]
    if n_s:
        lhs += [sums[..., 2 * n_m:, :], s0]
        rhs += [lb.sub_mod(s0, s1), s1]
    t = lb.mont_mul(jnp.concatenate(lhs, axis=-2), jnp.concatenate(rhs, axis=-2))
    t0, t1, t2 = (t[..., i * n_m:(i + 1) * n_m, :] for i in range(3))
    c0, h = t[..., 3 * n_m:3 * n_m + n_s, :], t[..., 3 * n_m + n_s:, :]
    # t0 + t1 (a product's cross term needs it) and 2 a0 a1 in one add
    adds = lb.add_mod(jnp.concatenate([t0, h], axis=-2),
                      jnp.concatenate([t1, h], axis=-2))
    prods = sqs = ()
    if n_m:
        res = lb.sub_mod(jnp.concatenate([t0, t2], axis=-2),
                         jnp.concatenate([t1, adds[..., :n_m, :]], axis=-2))
        prods = tuple(jnp.stack([res[..., i, :], res[..., n_m + i, :]], axis=-2)
                      for i in range(n_m))
    if n_s:
        sqs = tuple(jnp.stack([c0[..., i, :], adds[..., n_m + i, :]], axis=-2)
                    for i in range(n_s))
    return prods, sqs


def _z_double(p, ops):
    """jac_double with its five squares as squares: 2 products + 5 squares
    in the same three rounds."""
    X, Y, Z = p
    (YZ,), (A, B) = _products(ops, [(Y, Z)], [X, Y])
    E = ops.small(A, 3)
    _, (C, t, F) = _products(ops, sqrs=[B, ops.add(X, B), E])
    D = ops.small(ops.sub(ops.sub(t, A), C), 2)
    X3 = ops.sub(F, ops.small(D, 2))
    (EDX,), _ = _products(ops, [(E, ops.sub(D, X3))])
    return (X3, ops.sub(EDX, ops.small(C, 8)), ops.small(YZ, 2))


def _z_add(p1, p2, ops):
    """p1 + p2 for Jacobian p1 and p2 either Jacobian (X2, Y2, Z2) or affine
    (x2, y2) — a mixed addition, Z2 known to be one: 11 products where the
    general sum takes 16, in the same five rounds. No doubling inside and no
    identity handling: right wherever both points are finite and H != 0.
    Returns (sum, H == 0); the caller selects around the identities and owns
    what an H == 0 between finite points means."""
    X1, Y1, Z1 = p1
    if len(p2) == 2:
        (X2, Y2), Z2 = p2, None
        (Y2Z1,), (Z1Z1,) = _products(ops, [(Y2, Z1)], [Z1])
        (U2, S2), _ = _products(ops, [(X2, Z1Z1), (Y2Z1, Z1Z1)])
        U1, S1 = X1, Y1
    else:
        X2, Y2, Z2 = p2
        (Y1Z2, Y2Z1), (Z1Z1, Z2Z2) = _products(
            ops, [(Y1, Z2), (Y2, Z1)], [Z1, Z2])
        (U1, U2, S1, S2), _ = _products(
            ops, [(X1, Z2Z2), (X2, Z1Z1), (Y1Z2, Z2Z2), (Y2Z1, Z1Z1)])
    H = ops.sub(U2, U1)
    r = ops.sub(S2, S1)
    if Z2 is None:
        _, (HH, rr) = _products(ops, sqrs=[H, r])
        (HHH, V, Z3), _ = _products(ops, [(H, HH), (U1, HH), (Z1, H)])
    else:
        (Z1Z2,), (HH, rr) = _products(ops, [(Z1, Z2)], [H, r])
        (HHH, V, Z3), _ = _products(ops, [(H, HH), (U1, HH), (Z1Z2, H)])
    X3 = ops.sub(ops.sub(rr, HHH), ops.small(V, 2))
    (rVX3, S1HHH), _ = _products(ops, [(r, ops.sub(V, X3)), (S1, HHH)])
    return (X3, ops.sub(rVX3, S1HHH), Z3), ops.is_zero(H)


def _z_add_finite(acc, p, p_jac, p_inf, ops):
    """acc + p by `_z_add`, selected around the identities: (the sum — p
    where acc is the identity, acc where p is, by the mask `p_inf` —, the
    lanes where two finite points met H == 0: acc = +-p, the case `_z_add`
    gets wrong). `p` is `p_jac` itself or its affine (x, y)."""
    added, h_zero = _z_add(acc, p, ops)
    acc_inf = ops.is_zero(acc[2])
    out = pt_select(ops, p_inf, acc, pt_select(ops, acc_inf, p_jac, added))
    met = jnp.logical_and(
        h_zero, jnp.logical_not(jnp.logical_or(acc_inf, p_inf)))
    return out, met


def scalar_mul_z(p, bits, ops, p_inf=None, window: int = 4):
    """bits * p, the chain of the batch-verification coefficients: `bits`
    (..., 64) uint32, MSB first; p Jacobian (X, Y, Z), or affine (x, y) with
    `p_inf` (...,) bool marking the identity lanes (then every addition of p
    is a mixed one). Returns (product, met): `met` (...,) bool is where some
    step added two finite points with H == 0 — the accumulator was +-p,
    which a 64-bit coefficient times a point of order r never is; a lane
    that reports it holds no trustworthy product and its caller refuses it
    (scalar_mul_bits, whose every step carries a doubling for that case,
    stays for the scalars that can meet it: msm.py).

    The table [0 .. 2^window - 1] * p is built once (one doubling, then a
    scan of `previous + p`); then per `window` bits as many doublings and
    ONE addition of the table entry the digit names (a one-hot masked sum,
    as scalar_mul_windowed reads its table). `window` divides 64 and is at
    least 2."""
    if len(p) == 2:
        p_jac = affine_to_jac(ops, p, inf_mask=p_inf)
    else:
        p_jac, p_inf = p, ops.is_zero(p[2])
    none = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), identity(ops), p_jac)
    met0 = jnp.zeros(p_inf.shape, bool)
    moved = jnp.moveaxis(bits, -1, 0)

    nt = 1 << window
    two = _z_double(p_jac, ops)

    def next_entry(carry, _):
        prev, met = carry
        nxt, now = _z_add_finite(prev, p, p_jac, p_inf, ops)
        return (nxt, jnp.logical_or(met, now)), nxt

    (_, met), rest = jax.lax.scan(next_entry, (two, met0), None, length=nt - 3)
    table = tuple(
        jnp.concatenate([jnp.stack([z, x, d]), more])
        for z, x, d, more in zip(none, p_jac, two, rest))
    weights = jnp.asarray(1 << np.arange(window - 1, -1, -1), jnp.uint32)
    digits = jnp.sum(
        moved.reshape((-1, window) + moved.shape[1:])
        * weights.reshape((1, window) + (1,) * (moved.ndim - 1)), axis=1)
    nt_range = jnp.arange(nt, dtype=jnp.uint32)

    def digit_step(carry, digit):
        acc, met = carry
        acc = jax.lax.fori_loop(
            0, window, lambda _, a: _z_double(a, ops), acc)
        q = _table_entry(table, digit, nt_range)
        acc, now = _z_add_finite(acc, q, q, ops.is_zero(q[2]), ops)
        return (acc, jnp.logical_or(met, now)), None

    (acc, met), _ = jax.lax.scan(digit_step, (none, met), digits)
    return acc, met


def scalar_mul_static(p_jac, k: int, ops):
    """p * k for a static Python int k (e.g. cofactors, subgroup order)."""
    if k < 0:
        X, Y, Z = p_jac
        p_jac = (X, ops.neg(Y), Z)
        k = -k
    bits = jnp.asarray(np.array([int(b) for b in bin(k)[2:]], np.uint32))

    def body(acc, bit):
        acc = jac_double(acc, ops)
        # static scalar -> scalar predicate: only the taken branch runs
        acc = jax.lax.cond(bit == 1, lambda a: jac_add(a, p_jac, ops), lambda a: a, acc)
        return acc, None

    init = jax.tree_util.tree_map(lambda c, x: jnp.broadcast_to(c, x.shape), identity(ops), p_jac)
    acc, _ = jax.lax.scan(body, init, bits)
    return acc


def _table_entry(table_arr, digit, nt_range):
    """Entry `digit` (...,) of a per-lane table — a tuple of coordinates,
    each (nt,) + batch + element dims, `nt_range` = arange(nt) made outside
    the caller's loop — by a one-hot masked sum (nt elementwise mult-adds).
    A take_along_axis gather here made XLA:TPU compile times explode with
    batch size; the mask-select form lowers to plain VPU ops."""
    def g(coord):
        # coord: (nt, ...batch, *elem)
        oh = digit[None, ...] == nt_range[(slice(None),) + (None,) * digit.ndim]
        oh = oh[(...,) + (None,) * (coord.ndim - 1 - digit.ndim)]
        return jnp.sum(coord * jnp.asarray(oh, coord.dtype), axis=0)

    return tuple(g(c) for c in table_arr)


def scalar_mul_windowed(p_jac, digits, ops, window: int = 4):
    """p * k for dynamic scalars given as base-2^w digit arrays (MSB first).

    digits: (..., ndigits) uint32 in [0, 2^w). Builds a runtime table of
    [0..2^w-1]*P per lane (identity-safe complete adds), then scans the
    digits with w doublings + one table-gather add per step. For the 64-bit
    batch-verification coefficients this does 16 adds + 16*(4 dbl + 1 add)
    instead of 64 dbl + 64 select-adds."""
    nt = 1 << window
    table = [identity(ops), p_jac]
    table[0] = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), table[0], p_jac
    )
    # Build [2..nt-1]*P in log rounds of ONE stacked jac_add each
    # (j*P = (j//2)*P + (j - j//2)*P, both halves < len(table)): 4 add
    # instances for w=4 instead of a 14-long sequential chain — the chain
    # dominated kernel compile time.
    while len(table) < nt:
        m = len(table)
        idx = list(range(m, min(2 * (m - 1), nt - 1) + 1))
        A = tuple(jnp.stack([table[j // 2][ci] for j in idx]) for ci in range(3))
        B = tuple(jnp.stack([table[j - j // 2][ci] for j in idx]) for ci in range(3))
        S = jac_add(A, B, ops)
        for k, _j in enumerate(idx):
            table.append(tuple(S[ci][k] for ci in range(3)))
    # stack: tuple of coords, each (nt,) + batch + elem shape
    table_arr = tuple(jnp.stack([t[i] for t in table]) for i in range(3))

    nt_range = jnp.arange(nt, dtype=jnp.uint32)

    def gather(digit):
        return _table_entry(table_arr, digit, nt_range)

    moved = jnp.moveaxis(digits, -1, 0)

    def body(acc, digit):
        for _ in range(window):
            acc = jac_double(acc, ops)
        acc = jac_add(acc, gather(digit), ops)
        return acc, None

    init = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), identity(ops), p_jac
    )
    acc, _ = jax.lax.scan(body, init, moved)
    return acc


def scalars_to_digits(zs, nbits: int, window: int = 4) -> np.ndarray:
    """Host: list of ints -> (n, nbits//window) uint32 digit array, MSB first."""
    nd = (nbits + window - 1) // window
    out = np.zeros((len(zs), nd), np.uint32)
    for i, z in enumerate(zs):
        for j in range(nd):
            out[i, nd - 1 - j] = (z >> (j * window)) & ((1 << window) - 1)
    return out


# psi endomorphism + fast G2 cofactor clearing ---------------------------

_PSI_CONSTS: dict = {}


def _psi_consts():
    # cache NUMPY arrays and convert per use: caching a jnp array built
    # lazily INSIDE a traced call leaks that trace's constant-tracer into
    # every later trace (UnexpectedTracerError once another jit reuses it)
    if not _PSI_CONSTS:
        _PSI_CONSTS["cx"] = np.asarray(tw._fq2_const_np(pc.PSI_CX))
        _PSI_CONSTS["cy"] = np.asarray(tw._fq2_const_np(pc.PSI_CY))
    return (
        jnp.asarray(_PSI_CONSTS["cx"]),
        jnp.asarray(_PSI_CONSTS["cy"]),
    )


def psi_jac(p):
    """Untwist-Frobenius-twist endomorphism on Jacobian G2 points.

    x = X/Z^2 -> c_x*conj(x) gives (c_x*conj(X), c_y*conj(Y), conj(Z))."""
    cx, cy = _psi_consts()
    X, Y, Z = p
    return (
        tw.fq2_mul(tw.fq2_conj(X), cx),
        tw.fq2_mul(tw.fq2_conj(Y), cy),
        tw.fq2_conj(Z),
    )


def _neg_pt(p, ops):
    X, Y, Z = p
    return (X, ops.neg(Y), Z)


def clear_cofactor_g2(p):
    """h_eff * P via the psi trick (ground truth: bls381.curve.
    g2_clear_cofactor_fast, itself pinned against the 636-bit h_eff scalar
    multiplication): [x^2-x-1]P + [x-1]psi(P) + psi^2(2P)."""
    from ..bls381.constants import X_ABS
    ops = FQ2_OPS

    def xmul(q):
        return _neg_pt(scalar_mul_static(q, X_ABS, ops), ops)

    t1 = xmul(p)                                       # x P
    t2 = psi_jac(p)
    t3 = psi_jac(psi_jac(jac_double(p, ops)))          # psi^2(2P)
    t3 = jac_add(t3, _neg_pt(t2, ops), ops)
    t2 = xmul(jac_add(t1, t2, ops))                    # x^2 P + x psi(P)
    t3 = jac_add(t3, t2, ops)
    t3 = jac_add(t3, _neg_pt(t1, ops), ops)
    return jac_add(t3, _neg_pt(p, ops), ops)


def scalars_to_bits(zs, nbits: int) -> np.ndarray:
    """Host: list of ints -> (n, nbits) uint32 bit array, MSB first."""
    out = np.zeros((len(zs), nbits), np.uint32)
    for i, z in enumerate(zs):
        for j in range(nbits):
            out[i, nbits - 1 - j] = (z >> j) & 1
    return out


# Lanes (entries kept x lanes behind axis 0) that tree_sum folds down to
# before it starts halving. Measured on a v5e (PR 28, one G1 jac_add of L
# flat lanes inside a fori_loop, scripts/measure_tree_sum_l0.py), us a lane:
# 1.07 at 256, 0.99 at 512, 0.91 at 1,024, 0.855 at 2,048, 0.852 at 4,096,
# 0.98 at 8,192, 1.16 at 16,384, 1.33 at 32,768, 1.59 at 131,072. The add
# never becomes bound by its count of small operations in that range (0.27 ms
# at 256 lanes); 2,048 is the smallest width at the lowest cost a lane. With
# it the (512, 256) key grid sums in 112 ms (107-123 for L0 from 256 to
# 4,096; 1,766 unfolded) and the (128, 64) grid in 14.7 ms (8.1 at 256, 26.1
# at 4,096; 56.6 unfolded). One constant for every caller, no knob: a smaller
# value would buy the gossip bucket ~6 ms of a 590 ms batch and route the
# narrow urgent bucket (128 x 4) through the fold as well.
TREE_SUM_L0 = 2048


def tree_sum_plan(m: int, rest: int) -> tuple:
    """What tree_sum does with m entries on axis 0 and `rest` lanes behind
    each: (c, fold_steps, finish_rounds, lane_additions). Pure, no jit —
    tree_sum follows it, the backend's lane-addition counter reads it.

    c is the smallest power of two with c * rest >= TREE_SUM_L0, capped at
    m: the fold adds m/c - 1 chunks of c entries into the first, the finish
    halves the c survivors in log2(c) fixed-shape rounds. c == m is the
    fixed-shape loop alone; m <= 4 is the unrolled halving (m - 1 adds)."""
    assert m >= 1 and m & (m - 1) == 0, "tree_sum needs power-of-two length"
    if m <= 4:
        return m, 0, m.bit_length() - 1, (m - 1) * rest
    c = 1
    while c < m and c * rest < TREE_SUM_L0:
        c *= 2
    fold_steps = m // c - 1
    finish_rounds = c.bit_length() - 1
    return c, fold_steps, finish_rounds, (fold_steps + finish_rounds) * c * rest


def _halving_rounds(p_jac, ops, view, axis, rounds):
    """`rounds` fixed-shape halving rounds over axis `axis` of `view`, the
    batch dims of p_jac seen as that shape: round r adds the entry
    half-a-stride away (dynamic roll) and keeps the sum in the low entries
    via select. One jac_add instance for all rounds; after log2(view[axis])
    rounds entry 0 holds the sum."""
    batch = np.shape(ops.is_zero(p_jac[2]))
    # select conds index ALL batch dims (everything but the field-element
    # dims): the entry index over the full batch, not just the one axis
    entry = jax.lax.broadcasted_iota(jnp.int32, view, axis).reshape(batch)

    def body(r, acc):
        half = jnp.int32(view[axis]) >> (r + 1)
        shifted = jax.tree_util.tree_map(
            lambda x: jnp.roll(
                x.reshape(view + x.shape[len(batch):]), -half, axis=axis
            ).reshape(x.shape),
            acc,
        )
        added = jac_add(acc, shifted, ops)
        # entries >= half hold garbage sums; keep previous values there
        # (only entries < the next round's stride are ever read again)
        return pt_select(ops, entry < half, added, acc)

    return jax.lax.fori_loop(0, rounds, body, p_jac)


def tree_sum(p_jac, ops):
    """Sum points along the FIRST batch axis.

    Input axis length must be a power of two (pad with identity).

    tree_sum_plan gives the sizes from the shape (m entries, `rest` lanes
    behind each):
      * c == m: the fixed-shape loop alone (_halving_rounds over axis 0),
        m lanes of add in each of log2(m) rounds — what this function was
        before it folded, and still is for every narrow caller.
      * c < m, fold: axis 0 viewed as (m/c, c); a fori_loop adds chunk j
        into chunk 0 for j = 1 .. m/c - 1 on ONE lane axis of rest * c
        (lane i*c + k = lane i of entry k). Flat because the chip's vector
        unit is 128 lanes wide and XLA puts one batch dim on them: a
        (32, 64) batch ran the same add 3.7x slower than 2,048 flat lanes
        (6.5 against 1.75 ms). Rest-major because that is the order the
        caller's (rest, m) grid lies in, and because a sharded `rest` (the
        mesh's set axis) stays contiguous in it: the fold and its finish
        need no collective.
      * finish: _halving_rounds over the c survivors of every lane group.
    Two jac_add instances compile whatever m is (one when c == m). An
    unrolled halving tree reaches a few lane-additions fewer but
    instantiates log2(m) separate adds, which dominated the prepare-stage
    XLA compile (the r4 multichip gate timed out in exactly that compile).

    Why fold: the loop alone does m * log2(m) lane-additions where m - 1
    are needed, and on the v5e that is not noise: a G1 jac_add costs in
    proportion to its lanes from 256 lanes up (0.85-1.07 us a lane to
    8,192, 1.6 us at 131,072), so the key-axis sum of the 256x512 block
    bucket, 9 rounds x 131,072 lanes, was 1.46 s of a 2.56 s block and is
    0.11 s folded (PERF.md S6, PR 28; scripts/measure_tree_sum_l0.py).
    TREE_SUM_L0 = 2,048 is where the per-lane cost is lowest.

    The association order differs from a halving tree's; jac_add is
    complete (identity lanes, P + P, P - P by its selects), so the result
    is another Jacobian representative of the same point.

    Unrolled halving is kept for m <= 4, where the loop machinery
    outweighs two adds."""
    n = jax.tree_util.tree_leaves(p_jac)[0].shape[0]
    assert n & (n - 1) == 0, "tree_sum needs power-of-two length"
    if n <= 4:
        while n > 1:
            half = n // 2
            a = jax.tree_util.tree_map(lambda x: x[:half], p_jac)
            b = jax.tree_util.tree_map(lambda x: x[half:n], p_jac)
            p_jac = jac_add(a, b, ops)
            n = half
        return jax.tree_util.tree_map(lambda x: x[0], p_jac)

    batch = np.shape(ops.is_zero(p_jac[2]))
    rest = int(np.prod(batch[1:]))
    c, fold_steps, rounds, _ = tree_sum_plan(n, rest)
    if not fold_steps:
        acc = _halving_rounds(p_jac, ops, batch, 0, rounds)
        return jax.tree_util.tree_map(lambda x: x[0], acc)

    def chunk(j):
        def lanes(x):
            field = x.shape[len(batch):]
            ch = jax.lax.dynamic_index_in_dim(
                x.reshape((n // c, c, rest) + field), j, 0, keepdims=False
            )
            return jnp.swapaxes(ch, 0, 1).reshape((rest * c,) + field)

        return jax.tree_util.tree_map(lanes, p_jac)

    acc = jax.lax.fori_loop(
        1, n // c, lambda j, a: jac_add(a, chunk(j), ops), chunk(0)
    )
    if rounds:
        acc = _halving_rounds(acc, ops, (rest, c), 1, rounds)
    return jax.tree_util.tree_map(
        lambda x, x0: x.reshape((rest, c) + x0.shape[len(batch):])[:, 0]
        .reshape(x0.shape[1:]),
        acc, p_jac,
    )


def masked_tree_sum(p_jac, mask, ops):
    """Sum of points where mask==1 along the first axis (mask: (n,) bool/int).

    Masked-out entries are replaced by the identity before reduction."""
    inf = jax.tree_util.tree_map(lambda c, x: jnp.broadcast_to(c, x.shape), identity(ops), p_jac)
    masked = pt_select(ops, jnp.asarray(mask, bool), p_jac, inf)
    return tree_sum(masked, ops)


# ------------------------------------------------ host <-> device conversion


def g1_to_device(pt):
    """Host affine G1 (int pair) or None -> device Jacobian (batchless)."""
    if pt is None:
        return identity(FQ_OPS)
    return (tw.fq_to_device(pt[0]), tw.fq_to_device(pt[1]), tw.FQ_ONE)


def g1_from_device(p_jac):
    x, y, inf = jac_to_affine(p_jac, FQ_OPS)
    if bool(np.asarray(inf)):
        return None
    return (tw.fq_from_device(x), tw.fq_from_device(y))


def g2_to_device(pt):
    if pt is None:
        return identity(FQ2_OPS)
    return (tw.fq2_to_device(pt[0]), tw.fq2_to_device(pt[1]), tw.FQ2_ONE)


def g2_from_device(p_jac):
    x, y, inf = jac_to_affine(p_jac, FQ2_OPS)
    if bool(np.asarray(inf)):
        return None
    return (tw.fq2_from_device(x), tw.fq2_from_device(y))


def g1_batch_to_device(pts):
    """List of host affine G1 points (None allowed) -> batched Jacobian."""
    xs = tw.fq_batch_to_device([pt[0] if pt else 0 for pt in pts])
    ys = tw.fq_batch_to_device([pt[1] if pt else 1 for pt in pts])
    zs = tw.fq_batch_to_device([0 if pt is None else 1 for pt in pts])
    return (xs, ys, zs)


def g2_batch_to_device(pts):
    """List of host affine G2 points (None allowed) -> batched Jacobian
    with stacked Fq2 coords (n, 2, NL)."""
    xs = tw.fq2_batch_to_device([pt[0] if pt else (0, 0) for pt in pts])
    ys = tw.fq2_batch_to_device([pt[1] if pt else (1, 0) for pt in pts])
    zs = tw.fq2_batch_to_device([(0, 0) if pt is None else (1, 0) for pt in pts])
    return (xs, ys, zs)
