"""Batched BLS12-381 field tower (Fq2/Fq6/Fq12) on TPU, Montgomery domain.

STACKED representation (the key compile-time/runtime design decision):
  Fq   : (..., NL)          uint32 16-bit limbs, Montgomery form
  Fq2  : (..., 2, NL)       c0 + c1*u,           u^2 = -1
  Fq6  : (..., 3, 2, NL)    a0 + a1*v + a2*v^2,  v^3 = xi = u + 1
  Fq12 : (..., 2, 3, 2, NL) b0 + b1*w,           w^2 = v

Every tower multiplication gathers its independent Montgomery products into a
single batched mont_mul call over a stacked lane axis (e.g. fq12_mul = ONE
mont_mul over 54 lanes) instead of emitting one XLA subgraph per product.
That keeps compile time near-constant per op and hands the TPU large batched
matmuls (limbs._poly_mul lowers to dot_general). Component layout matches the
pure-Python ground truth (bls381/fields.py) positionally, so conversion is
mechanical and differential tests are direct.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..bls381 import fields as pyf
from ..bls381.constants import P
from . import limbs as lb

NL = lb.NL


def _mont_const(x: int) -> np.ndarray:
    return lb.pack(x * lb.R_MONT % P)


FQ_ZERO = np.zeros((NL,), np.uint32)
FQ_ONE = np.asarray(_mont_const(1))

FQ2_ZERO = np.zeros((2, NL), np.uint32)
FQ2_ONE = np.stack([_mont_const(1), np.zeros(NL, np.uint32)])
FQ6_ZERO = np.zeros((3, 2, NL), np.uint32)
FQ6_ONE = np.stack([FQ2_ONE, FQ2_ZERO, FQ2_ZERO])
# numpy, not jnp: module-level device arrays initialize the backend at
# import (see limbs.py constants note)
FQ12_ONE = np.stack([FQ6_ONE, FQ6_ZERO])


# ----------------------------------------------------------------- Fq2
# add/sub/neg are plain limb ops (they broadcast over the component axis).

fq2_add = lb.add_mod
fq2_sub = lb.sub_mod
fq2_neg = lb.neg_mod


def fq2_conj(a):
    return jnp.stack([a[..., 0, :], lb.neg_mod(a[..., 1, :])], axis=-2)


def fq2_mul(a, b):
    a, b = jnp.broadcast_arrays(a, b)
    a0, a1 = a[..., 0, :], a[..., 1, :]
    b0, b1 = b[..., 0, :], b[..., 1, :]
    # One add for both operand sums (stacked), one mont_mul for all 3 products.
    sums = lb.add_mod(jnp.stack([a0, b0], axis=-2), jnp.stack([a1, b1], axis=-2))
    sa, sb = sums[..., 0, :], sums[..., 1, :]
    t = lb.mont_mul(jnp.stack([a0, a1, sa], axis=-2), jnp.stack([b0, b1, sb], axis=-2))
    t0, t1, t2 = t[..., 0, :], t[..., 1, :], t[..., 2, :]
    t01 = lb.add_mod(t0, t1)
    res = lb.sub_mod(jnp.stack([t0, t2], axis=-2), jnp.stack([t1, t01], axis=-2))
    return res


def fq2_sqr(a):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    s = lb.add_mod(a0, a1)
    d = lb.sub_mod(a0, a1)
    t = lb.mont_mul(jnp.stack([s, a0], axis=-2), jnp.stack([d, a1], axis=-2))
    c0, t1 = t[..., 0, :], t[..., 1, :]
    c1 = lb.add_mod(t1, t1)
    return jnp.stack([c0, c1], axis=-2)


def fq2_mul_fq(a, k):
    """Multiply Fq2 by Fq (k: (..., NL), Montgomery)."""
    return lb.mont_mul(a, k[..., None, :])


def fq2_mul_small(a, k: int):
    return lb.mul_small(a, k)


def fq2_mul_by_xi(a):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    return jnp.stack([lb.sub_mod(a0, a1), lb.add_mod(a0, a1)], axis=-2)


def fq2_inv(a):
    a0, a1 = a[..., 0, :], a[..., 1, :]
    sq = lb.mont_mul(a, a)                      # (a0^2, a1^2) in one call
    norm = lb.add_mod(sq[..., 0, :], sq[..., 1, :])
    ninv = lb.mont_inv(norm)
    out = lb.mont_mul(jnp.stack([a0, lb.neg_mod(a1)], axis=-2), ninv[..., None, :])
    return out


def fq2_is_zero(a):
    # chained single-axis reductions, not one all() over both trailing
    # axes: this is the form the served programs contain; fusing the two is
    # a change to them, to be measured
    return jnp.all(jnp.all(a == 0, axis=-1), axis=-1)


def fq2_eq(a, b):
    return jnp.all(jnp.all(a == b, axis=-1), axis=-1)


def fq2_select(cond, a, b):
    # reshape the condition in 32-bit, compare last (i1 minor-dim inserts
    # are rejected by the chip compiler)
    return jnp.where(lb.b2u(cond)[..., None, None] == 1, a, b)


# ----------------------------------------------------------------- Fq6

fq6_add = lb.add_mod
fq6_sub = lb.sub_mod
fq6_neg = lb.neg_mod


def _sel3(x, i, j, k):
    """Static permutation x[..., [i, j, k], :, :] as slices + stack: list
    indexing creates an i32[3] gather where three static slices do."""
    return jnp.stack([x[..., i, :, :], x[..., j, :, :], x[..., k, :, :]], axis=-3)


def fq6_mul(a, b):
    """Devegili Karatsuba: 6 fq2 products in one batched fq2_mul call."""
    a, b = jnp.broadcast_arrays(a, b)
    # Operand sums for the three cross terms, a and b together: one add.
    sums = lb.add_mod(
        jnp.concatenate([_sel3(a, 1, 0, 0), _sel3(b, 1, 0, 0)], axis=-3),
        jnp.concatenate([_sel3(a, 2, 1, 2), _sel3(b, 2, 1, 2)], axis=-3),
    )
    A = jnp.concatenate([a, sums[..., :3, :, :]], axis=-3)   # (..., 6, 2, NL)
    B = jnp.concatenate([b, sums[..., 3:, :, :]], axis=-3)
    t = fq2_mul(A, B)                                        # ONE mont_mul, 18 lanes
    t0, t1, t2 = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    m12, m01, m02 = t[..., 3, :, :], t[..., 4, :, :], t[..., 5, :, :]

    # pair sums (t1+t2, t0+t1, t0+t2) in one add, cross-minus in one sub
    ps = lb.add_mod(_sel3(t, 1, 0, 0), _sel3(t, 2, 1, 2))
    um = lb.sub_mod(jnp.stack([m12, m01, m02], axis=-3), ps)
    u, v, w = um[..., 0, :, :], um[..., 1, :, :], um[..., 2, :, :]
    # xi-mults for u and t2 in one stacked call
    xis = fq2_mul_by_xi(jnp.stack([u, t2], axis=-3))
    c = lb.add_mod(
        jnp.stack([t0, v, w], axis=-3),
        jnp.stack([xis[..., 0, :, :], xis[..., 1, :, :], t1], axis=-3),
    )
    return c


def fq6_sqr(a):
    return fq6_mul(a, a)


def fq6_mul_by_v(a):
    return jnp.concatenate([fq2_mul_by_xi(a[..., 2:3, :, :]), a[..., 0:2, :, :]], axis=-3)


def fq6_mul_fq2(a, k):
    """Multiply Fq6 by Fq2 (k: (..., 2, NL)): 3 fq2 muls in one call."""
    return fq2_mul(a, k[..., None, :, :])


def fq6_inv(a):
    a0, a1, a2 = a[..., 0, :, :], a[..., 1, :, :], a[..., 2, :, :]
    sq = fq2_sqr(a)                                           # a0^2, a1^2, a2^2
    pr = fq2_mul(a, a[..., [1, 2, 0], :, :])                  # a0a1, a1a2, a2a0
    c0 = fq2_sub(sq[..., 0, :, :], fq2_mul_by_xi(pr[..., 1, :, :]))
    c1 = fq2_sub(fq2_mul_by_xi(sq[..., 2, :, :]), pr[..., 0, :, :])
    c2 = fq2_sub(sq[..., 1, :, :], pr[..., 2, :, :])
    cs = jnp.stack([c0, c1, c2], axis=-3)
    # t = a0*c0 + xi*(a1*c2 + a2*c1)
    acs = fq2_mul(a, cs[..., [0, 2, 1], :, :])                # a0c0, a1c2, a2c1
    t = fq2_add(
        acs[..., 0, :, :],
        fq2_mul_by_xi(fq2_add(acs[..., 1, :, :], acs[..., 2, :, :])),
    )
    tinv = fq2_inv(t)
    return fq6_mul_fq2(cs, tinv)


# ----------------------------------------------------------------- Fq12


def fq12_mul(a, b):
    a, b = jnp.broadcast_arrays(a, b)
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    b0, b1 = b[..., 0, :, :, :], b[..., 1, :, :, :]
    sums = lb.add_mod(jnp.stack([a0, b0], axis=-4), jnp.stack([a1, b1], axis=-4))
    A = jnp.concatenate([a, sums[..., 0:1, :, :, :]], axis=-4)   # (..., 3, 3, 2, NL)
    B = jnp.concatenate([b, sums[..., 1:2, :, :, :]], axis=-4)
    t = fq6_mul(A, B)                                            # ONE mont_mul, 54 lanes
    t0, t1, tx = t[..., 0, :, :, :], t[..., 1, :, :, :], t[..., 2, :, :, :]
    c0 = fq6_add(t0, fq6_mul_by_v(t1))
    c1 = fq6_sub(tx, fq6_add(t0, t1))
    return jnp.stack([c0, c1], axis=-4)


def fq12_mul_by_014(a, l0, l1, l2):
    """Sparse multiplication a * (l0 + l1*v + l2*v*w) — the Miller-loop line
    shape (components 0, 1 of the first Fq6 and component 1 of the second).

    13 Fq2 products (vs 18 for the dense fq12_mul), all gathered into ONE
    batched fq2_mul call. l0/l1/l2: (..., 2, NL)."""
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]   # Fq6 halves (..., 3, 2, NL)
    f0, f1, f2 = a0[..., 0, :, :], a0[..., 1, :, :], a0[..., 2, :, :]
    g0, g1, g2 = a1[..., 0, :, :], a1[..., 1, :, :], a1[..., 2, :, :]

    l01 = fq2_add(l0, l1)
    l12 = fq2_add(l1, l2)
    # (f0+f1), (g0+g1), ... sums for the Karatsuba cross terms; c = f + g
    c0, c1, c2 = fq2_add(f0, g0), fq2_add(f1, g1), fq2_add(f2, g2)
    f01 = fq2_add(f0, f1)
    c01 = fq2_add(c0, c1)
    l0_12 = fq2_add(l0, l12)

    # 13 products in one stacked fq2_mul:
    #  t-part: f0*l0, f1*l1, (f0+f1)*(l0+l1), f2*l0, f2*l1       (a0 * [l0,l1])
    #  q-part: g0*l2, g1*l2, g2*l2                               (a1 * [l2])
    #  r-part: c0*l0, c1*l12, (c0+c1)*(l0+l12), c2*l0, c2*l12    ((a0+a1)*[l0,l1+l2])
    A = jnp.stack([f0, f1, f01, f2, f2, g0, g1, g2, c0, c1, c01, c2, c2], axis=-3)
    B = jnp.stack(
        [l0, l1, l01, l0, l1, l2, l2, l2, l0, l12, l0_12, l0, l12], axis=-3
    )
    t = fq2_mul(A, B)
    p1, p2, p3, p4, p5 = (t[..., i, :, :] for i in range(5))
    q1, q2, q3 = (t[..., i, :, :] for i in range(5, 8))
    r1, r2, r3, r4, r5 = (t[..., i, :, :] for i in range(8, 13))

    # t0 = a0 * (l0 + l1 v):   (p1 + xi*p5, p3 - p1 - p2, p2 + p4)
    t0_0 = fq2_add(p1, fq2_mul_by_xi(p5))
    t0_1 = fq2_sub(fq2_sub(p3, p1), p2)
    t0_2 = fq2_add(p2, p4)
    # t1 = a1 * (l2 v):        (xi*q3, q1, q2)
    t1_0 = fq2_mul_by_xi(q3)
    t1_1 = q1
    t1_2 = q2
    # t2 = (a0+a1) * (l0 + l12 v): (r1 + xi*r5, r3 - r1 - r2, r2 + r4)
    t2_0 = fq2_add(r1, fq2_mul_by_xi(r5))
    t2_1 = fq2_sub(fq2_sub(r3, r1), r2)
    t2_2 = fq2_add(r2, r4)

    # out0 = t0 + v * t1 = (t0_0 + xi*t1_2, t0_1 + t1_0, t0_2 + t1_1)
    out0 = jnp.stack(
        [
            fq2_add(t0_0, fq2_mul_by_xi(t1_2)),
            fq2_add(t0_1, t1_0),
            fq2_add(t0_2, t1_1),
        ],
        axis=-3,
    )
    # out1 = t2 - t0 - t1 componentwise
    out1 = jnp.stack(
        [
            fq2_sub(fq2_sub(t2_0, t0_0), t1_0),
            fq2_sub(fq2_sub(t2_1, t0_1), t1_1),
            fq2_sub(fq2_sub(t2_2, t0_2), t1_2),
        ],
        axis=-3,
    )
    return jnp.stack([out0, out1], axis=-4)


def fq12_sqr(a):
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    # Complex squaring: t = a0*a1; s = (a0+a1)(a0 + v*a1);
    # c0 = s - t - v*t ; c1 = 2t.  The two fq6 muls share one call.
    s1 = fq6_add(a0, a1)
    s2 = fq6_add(a0, fq6_mul_by_v(a1))
    t_pair = fq6_mul(jnp.stack([a0, s1], axis=-4), jnp.stack([a1, s2], axis=-4))
    t, s = t_pair[..., 0, :, :, :], t_pair[..., 1, :, :, :]
    c0 = fq6_sub(fq6_sub(s, t), fq6_mul_by_v(t))
    c1 = fq6_add(t, t)
    return jnp.stack([c0, c1], axis=-4)


def fq12_conj(a):
    return jnp.stack([a[..., 0, :, :, :], fq6_neg(a[..., 1, :, :, :])], axis=-4)


def fq12_inv(a):
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    sq = fq6_sqr(jnp.stack([a0, a1], axis=-4))
    t = fq6_sub(sq[..., 0, :, :, :], fq6_mul_by_v(sq[..., 1, :, :, :]))
    tinv = fq6_inv(t)
    out = fq6_mul(jnp.stack([a0, fq6_neg(a1)], axis=-4), tinv[..., None, :, :, :])
    return out


def fq12_eq_one(a):
    one = jnp.broadcast_to(FQ12_ONE, a.shape)
    eqs = a == one
    for _ in range(4):                       # chained single-axis alls
        eqs = jnp.all(eqs, axis=-1)
    return eqs


def fq12_select(cond, a, b):
    return jnp.where(cond[..., None, None, None, None], a, b)


# ------------------------------------------------ cyclotomic square


def fq12_cyclotomic_sqr(a):
    """Granger-Scott squaring (valid in the cyclotomic subgroup).

    Components g0..g5 (Fq2): a0 = (g0, g1, g2), a1 = (g3, g4, g5); the three
    Fq4 squarings (pairs (g0,g4), (g3,g2), (g1,g5)) run in one batched
    fq2_sqr and one batched fq2_mul-free combine."""
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    g0, g1, g2 = a0[..., 0, :, :], a0[..., 1, :, :], a0[..., 2, :, :]
    g3, g4, g5 = a1[..., 0, :, :], a1[..., 1, :, :], a1[..., 2, :, :]

    C0 = jnp.stack([g0, g3, g1], axis=-3)
    C1 = jnp.stack([g4, g2, g5], axis=-3)
    # fq4_sqr batched: t0 = C0^2, t1 = C1^2, ts = (C0+C1)^2  — one fq2_sqr, 9 lanes
    S = fq2_sqr(jnp.concatenate([C0, C1, lb.add_mod(C0, C1)], axis=-3))
    t0 = S[..., 0:3, :, :]
    t1 = S[..., 3:6, :, :]
    ts = S[..., 6:9, :, :]
    r0 = lb.add_mod(t0, fq2_mul_by_xi(t1))                 # fq4 c0 parts
    r1 = lb.sub_mod(lb.sub_mod(ts, t0), t1)                # fq4 c1 parts

    # Fq4 outputs per pair: (cA0,cA1)=fp4sq(g0,g4), (cB0,cB1)=fp4sq(g3,g2),
    # (cC0,cC1)=fp4sq(g1,g5). Wiring verified against fq12_sqr ground truth:
    #   a0' = (3cA0 - 2g0, 3cB0 - 2g1, 3cC0 - 2g2)
    #   a1' = (3*xi*cC1 + 2g3, 3cA1 + 2g4, 3cB1 + 2g5)
    cC1 = r1[..., 2, :, :]
    lo_g = jnp.stack([g0, g1, g2], axis=-3)
    d = lb.sub_mod(r0, lo_g)
    lo = lb.add_mod(r0, lb.add_mod(d, d))

    hi_t = jnp.concatenate(
        [fq2_mul_by_xi(cC1)[..., None, :, :], r1[..., 0:2, :, :]], axis=-3
    )
    hi_g = jnp.stack([g3, g4, g5], axis=-3)
    s = lb.add_mod(hi_t, hi_g)
    hi = lb.add_mod(hi_t, lb.add_mod(s, s))
    return jnp.stack([lo, hi], axis=-4)


# ------------------------------------------------ Frobenius

# Device constants from the verified pure-Python tables, Montgomery form.


def _fq2_const_np(c) -> np.ndarray:
    return np.stack([_mont_const(c[0]), _mont_const(c[1])])


# (12, 2, NL), (6, 2, NL), (6, 2, NL)
_FROB12_C1 = np.stack([_fq2_const_np(c) for c in pyf.FROB_FQ12_C1])
_FROB6_C1 = np.stack([_fq2_const_np(c) for c in pyf.FROB_FQ6_C1])
_FROB6_C2 = np.stack([_fq2_const_np(c) for c in pyf.FROB_FQ6_C2])


def fq6_frobenius(a, power=1):
    conj = a if power % 2 == 0 else fq2_conj(a)
    # coefficients for components (1, a1, a2): (one, C1[p], C2[p])
    coeff = jnp.asarray(
        np.stack([np.asarray(FQ2_ONE), _FROB6_C1[power % 6], _FROB6_C2[power % 6]])
    )
    return fq2_mul(conj, coeff)


_FROB12_COEFF_NP: dict = {}


def _frob12_coeff_np(power: int) -> np.ndarray:
    """(2, 3, 2, NL) Frobenius coefficient block for fq12_frobenius, cached
    per power mod 12 (host np, see the constants note in limbs.py)."""
    key = power % 12
    if key not in _FROB12_COEFF_NP:
        g = _FROB12_C1[key]
        coeff0 = np.stack([FQ2_ONE, _FROB6_C1[key % 6], _FROB6_C2[key % 6]])
        coeff1 = np.stack(
            [
                np.asarray(_fq2_mul_np(g, FQ2_ONE)),
                _fq2_mul_np(_FROB6_C1[key % 6], g),
                _fq2_mul_np(_FROB6_C2[key % 6], g),
            ]
        )
        _FROB12_COEFF_NP[key] = np.stack([coeff0, coeff1])
    return _FROB12_COEFF_NP[key]


def fq12_frobenius(a, power=1):
    a0, a1 = a[..., 0, :, :, :], a[..., 1, :, :, :]
    conj0 = a0 if power % 2 == 0 else fq2_conj(a0)
    conj1 = a1 if power % 2 == 0 else fq2_conj(a1)
    stacked = jnp.stack([conj0, conj1], axis=-4)
    coeff = jnp.asarray(_frob12_coeff_np(power))
    return fq2_mul(stacked, coeff)


def _fq2_mul_np(a_mont: np.ndarray, b_mont: np.ndarray) -> np.ndarray:
    """Host-side fq2 mul of two Montgomery constant arrays (via Python ints)."""

    def to_int(x):
        v = sum(int(l) << (16 * i) for i, l in enumerate(np.asarray(x, np.uint64)))
        return v * pow(lb.R_MONT, -1, P) % P

    a = (to_int(a_mont[0]), to_int(a_mont[1]))
    b = (to_int(b_mont[0]), to_int(b_mont[1]))
    c = pyf.fq2_mul(a, b)
    return _fq2_const_np(c)


# ------------------------------------------------ host <-> device conversion


def fq_to_device(x: int):
    return jnp.asarray(_mont_const(x))


def fq_from_device(a) -> int:
    return lb.unpack(np.asarray(lb.from_mont_jit(a)))


def fq2_to_device(x):
    return jnp.asarray(_fq2_const_np(x))


def fq2_from_device(a):
    std = np.asarray(lb.from_mont_jit(a))
    return (lb.unpack(std[..., 0, :]), lb.unpack(std[..., 1, :]))


def fq6_to_device(x):
    return jnp.asarray(np.stack([_fq2_const_np(c) for c in x]))


def fq6_from_device(a):
    return tuple(fq2_from_device(a[..., i, :, :]) for i in range(3))


def fq12_to_device(x):
    return jnp.stack([fq6_to_device(x[0]), fq6_to_device(x[1])])


def fq12_from_device(a):
    return tuple(fq6_from_device(a[..., i, :, :, :]) for i in range(2))


def fq_batch_to_device(xs):
    return jnp.asarray(lb.pack_batch([x * lb.R_MONT % P for x in xs]))


def fq_batch_from_device(a) -> list[int]:
    return lb.unpack_batch(np.asarray(lb.from_mont_jit(a)))


def fq2_batch_to_device(xs):
    """List of (c0, c1) -> (n, 2, NL)."""
    return jnp.asarray(np.stack([_fq2_const_np(x) for x in xs]))
