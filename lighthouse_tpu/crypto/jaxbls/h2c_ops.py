"""Batched hash-to-G2 on TPU (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_).

Split host/device at the hashing boundary (SURVEY.md §7 step 1):
  host   — expand_message_xmd with SHA-256 (hashlib; sequential, tiny) and
           hash_to_field reduction to Fq2 elements (Python bigints).
  device — everything algebraic and batch-parallel: simplified SWU with a
           single-exponentiation sqrt_ratio (branch-free candidate selects)
           whose 758-bit exponent is split over the Frobenius into one
           joint chain of 381 squarings (fq2_pow_frobenius), 3-isogeny in
           projective form (no inversions), Jacobian point add and
           psi-based cofactor clearing.

Ground truth: lighthouse_tpu/crypto/bls381/hash_to_curve.py (itself pinned by
the RFC 9380 J.10.1 vector). The device path is differentially tested against
it in tests/test_jaxbls_h2c.py.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..bls381 import fields as pyf
from ..bls381 import hash_to_curve as ph2c
from ..bls381.constants import P, H_EFF_G2
from . import limbs as lb
from . import tower as tw
from . import curve_ops as co

Q = P * P  # order of Fq2

# ------------------------------------------------------------ constants
# Host np arrays (see the constants note in limbs.py); a trace embeds them
# as ordinary constants.

_ISO_A_NP = np.asarray(tw._fq2_const_np(ph2c.ISO_A))
_ISO_B_NP = np.asarray(tw._fq2_const_np(ph2c.ISO_B))
_ISO_Z_NP = np.asarray(tw._fq2_const_np(ph2c.ISO_Z))
_NEG_A_NP = np.asarray(tw._fq2_const_np(pyf.fq2_neg(ph2c.ISO_A)))
_ZA_NP = np.asarray(tw._fq2_const_np(pyf.fq2_mul(ph2c.ISO_Z, ph2c.ISO_A)))

# sqrt_ratio exponent: s = u * v^7 * (u * v^15)^E with E = (q-9)/16 gives
# s^2 = omega * u/v for an 8th root of unity omega.
_E = (Q - 9) // 16
# Fq2's Frobenius is conjugation, so with E = e1*p + e0 the one power is
# a^E = (a^p)^e1 * a^e0 = conj(a)^e1 * a^e0: two powers of 377 and 381 bits
# that share their squarings, half the chain of the 758-bit one.
_E1, _E0 = divmod(_E, P)
assert _E1 < P and _E0 < P and _E1 * P + _E0 == _E

# Bits of e1 and of e0 a joint digit of fq2_pow_frobenius holds. A measured
# constant (scripts/measure_h2c_chain.py on a v5e, PERF.md S6, PR 38), ms a
# call, median of 5; "old" is the one-base 4-bit chain over all 758 bits:
#
#                           old      2 + 2    3 + 3
#   chain alone,  8 lanes   51.38    33.23    29.17
#               128 lanes   46.11    31.04    29.58
#               512 lanes   39.73    27.19    26.96
#   hash_to_g2_jacobian,
#     4 sets (urgent)      126.17   105.74   103.92
#    64 sets (gossip)      115.17    97.88    99.06
#   256 sets (block)       141.50   115.40   112.61
#
# A scan step costs about one field operation more than it holds (~42 us
# beside ~40 us an operation at 128 lanes), so 3 + 3's 126 steps of 4 beat
# 2 + 2's 190 steps of 3 by less than the count says, and its table of 64
# (one stacked multiply over 64 x lanes) takes part of that back: ahead by
# 1.8 and 2.8 ms in the whole program at 4 and 256 sets, behind by 1.2 at 64.
SQRT_WINDOW = 3

# Candidate correction constants: y = s*c with c^2 = 1/omega (QR cases,
# omega in the 4th roots of unity) or c^2 = Z/omega (non-QR cases, omega a
# primitive 8th root). All computed with the verified pure-Python tower.
_I = (0, 1)                      # sqrt(-1) in Fq2 = Fq[u]/(u^2+1)
_RHO = pyf.fq2_sqrt(_I)          # primitive 8th root of unity


def _py_inv(a):
    return pyf.fq2_inv(a)


_QR_OMEGAS = [(1, 0), ((-1) % P, 0), _I, (0, (-1) % P)]
_NQR_OMEGAS = [_RHO, pyf.fq2_mul(_RHO, _I), pyf.fq2_neg(_RHO), pyf.fq2_mul(_RHO, (0, (-1) % P))]

_CANDS = []
for w in _QR_OMEGAS:
    c = pyf.fq2_sqrt(_py_inv(w))
    assert c is not None
    _CANDS.append(c)
for w in _NQR_OMEGAS:
    c = pyf.fq2_sqrt(pyf.fq2_mul(ph2c.ISO_Z, _py_inv(w)))
    assert c is not None, "Z/omega must be square for primitive 8th roots"
    _CANDS.append(c)
_CAND_CONSTS_NP = np.stack([np.asarray(tw._fq2_const_np(c)) for c in _CANDS])

# Isogeny coefficient matrix: 4 polynomials x 4 coefficients (padded), in the
# shared monomial basis [xd^3, xn*xd^2, xn^2*xd, xn^3].
def _poly4(coeffs):
    cs = list(coeffs) + [(0, 0)] * (4 - len(coeffs))
    return np.stack([np.asarray(tw._fq2_const_np(c)) for c in cs])


_ISO_K_NP = np.stack(
    [
        _poly4(ph2c.X_NUM),
        _poly4(ph2c.X_DEN),
        _poly4(ph2c.Y_NUM),
        _poly4(ph2c.Y_DEN),
    ]
)  # (4 polys, 4 coeffs, 2, NL)


# ------------------------------------------------------------ device pieces


def fq2_pow_frobenius(a, e1: int, e0: int):
    """conj(a)^e1 * a^e0 for static e1, e0: a^(e1*p + e0), since the
    Frobenius of Fq2 is conjugation.

    One joint fixed-window chain (Straus): a runtime table of
    conj(a)^i * a^j for i, j < 2^w, then one scan over the joint base-2^w
    digits of (e1, e0), MSB first: w squarings + one table multiply a step.
    The digits are static and the same for every lane."""
    w = SQRT_WINDOW
    nt = 1 << w
    steps = max(-(-max(e1.bit_length(), e0.bit_length()) // w), 1)
    digits = [
        (e1 >> w * k & (nt - 1)) << w | (e0 >> w * k & (nt - 1))
        for k in reversed(range(steps))
    ]

    # powers of a in log rounds (a^j = a^(j//2) * a^(j-j//2)), their
    # conjugates, then every product in one stacked multiply
    pows = [jnp.broadcast_to(tw.FQ2_ONE, a.shape), a]
    while len(pows) < nt:
        m = len(pows)
        idx = list(range(m, min(2 * (m - 1), nt - 1) + 1))
        prod = tw.fq2_mul(
            jnp.stack([pows[j // 2] for j in idx]),
            jnp.stack([pows[j - j // 2] for j in idx]),
        )
        pows.extend(prod[k] for k in range(len(idx)))
    pows = jnp.stack(pows)
    table = tw.fq2_mul(tw.fq2_conj(pows)[:, None], pows[None, :])
    table = table.reshape((nt * nt,) + a.shape)

    def body(acc, digit):
        for _ in range(w):
            acc = tw.fq2_sqr(acc)
        acc = tw.fq2_mul(acc, lax.dynamic_index_in_dim(table, digit, 0, keepdims=False))
        return acc, None

    acc, _ = lax.scan(
        body, table[digits[0]], jnp.asarray(np.array(digits[1:], np.uint32))
    )
    return acc


def fq2_sgn0(a):
    """RFC 9380 sgn0 for Fq2 on device (needs standard form for parity)."""
    std = lb.from_mont(a)
    s0 = std[..., 0, 0] & 1
    z0 = jnp.all(std[..., 0, :] == 0, axis=-1)
    s1 = std[..., 1, 0] & 1
    return s0 | (lb.b2u(z0) & s1)


def fq2_sqrt_ratio(u, v):
    """RFC 9380-style sqrt_ratio for Fq2 (q = p^2 ≡ 9 mod 16).

    Returns (is_qr, y): y^2 * v == u if is_qr else y^2 * v == Z * u.
    Single static exponentiation + 8 constant-multiple candidates."""
    v2 = tw.fq2_sqr(v)
    v4 = tw.fq2_sqr(v2)
    v8 = tw.fq2_sqr(v4)
    v7 = tw.fq2_mul(v4, tw.fq2_mul(v2, v))
    v15 = tw.fq2_mul(v8, v7)
    uv15 = tw.fq2_mul(u, v15)
    s = tw.fq2_mul(tw.fq2_mul(u, v7), fq2_pow_frobenius(uv15, _E1, _E0))

    ys = tw.fq2_mul(                                          # (..., 8, 2, NL)
        s[..., None, :, :], jnp.asarray(_CAND_CONSTS_NP)
    )
    checks = tw.fq2_mul(tw.fq2_sqr(ys), v[..., None, :, :])   # y^2 * v
    zu = tw.fq2_mul(jnp.broadcast_to(jnp.asarray(_ISO_Z_NP), u.shape), u)
    ok_qr = tw.fq2_eq(checks[..., :4, :, :], u[..., None, :, :])
    ok_nqr = tw.fq2_eq(checks[..., 4:, :, :], zu[..., None, :, :])
    is_qr = jnp.any(ok_qr, axis=-1)

    # first matching candidate via 8 unrolled masked selects (argmax +
    # take_along_axis lowers to a gather); the candidate flags concat as
    # u32, the form the served program contains
    ok = jnp.concatenate([lb.b2u(ok_qr), lb.b2u(ok_nqr)], axis=-1)  # (..., 8)
    y = jnp.zeros_like(u)
    found = jnp.zeros(ok.shape[:-1], bool)
    for i in range(8):
        ok_i = ok[..., i] == 1
        sel = jnp.logical_and(ok_i, jnp.logical_not(found))
        y = tw.fq2_select(sel, ys[..., i, :, :], y)
        found = jnp.logical_or(found, ok_i)
    return is_qr, y


def sswu_projective(u):
    """Simplified SWU map to E2' (branch-free). u: (..., 2, NL) Montgomery.

    Returns (xn, xd, y): affine x = xn/xd on E2', y affine."""
    shape = u.shape
    Z = jnp.broadcast_to(jnp.asarray(_ISO_Z_NP), shape)
    A = jnp.broadcast_to(jnp.asarray(_ISO_A_NP), shape)
    B = jnp.broadcast_to(jnp.asarray(_ISO_B_NP), shape)

    u2 = tw.fq2_sqr(u)
    tv1 = tw.fq2_mul(Z, u2)
    tv2 = tw.fq2_add(tw.fq2_sqr(tv1), tv1)
    one = jnp.broadcast_to(jnp.asarray(tw.FQ2_ONE), shape)
    x1n = tw.fq2_mul(B, tw.fq2_add(tv2, one))
    xd = tw.fq2_mul(jnp.broadcast_to(jnp.asarray(_NEG_A_NP), shape), tv2)
    xd = tw.fq2_select(
        tw.fq2_is_zero(xd), jnp.broadcast_to(jnp.asarray(_ZA_NP), shape), xd
    )

    xd2 = tw.fq2_sqr(xd)
    xd3 = tw.fq2_mul(xd2, xd)
    gx1 = tw.fq2_mul(tw.fq2_add(tw.fq2_sqr(x1n), tw.fq2_mul(A, xd2)), x1n)
    gx1 = tw.fq2_add(gx1, tw.fq2_mul(B, xd3))                 # gx1 numerator
    is_qr, y1 = fq2_sqrt_ratio(gx1, xd3)

    x2n = tw.fq2_mul(tv1, x1n)
    u3 = tw.fq2_mul(u2, u)
    y2 = tw.fq2_mul(tw.fq2_mul(Z, u3), y1)
    xn = tw.fq2_select(is_qr, x1n, x2n)
    y = tw.fq2_select(is_qr, y1, y2)

    # sign: sgn0(y) == sgn0(u)
    flip = fq2_sgn0(y) != fq2_sgn0(u)
    y = tw.fq2_select(flip, tw.fq2_neg(y), y)
    return xn, xd, y


def iso_map_jacobian(xn, xd, y):
    """3-isogeny E2' -> E2 evaluated on x = xn/xd, output Jacobian (X, Y, Z).

    All four isogeny polynomials are evaluated in one batched fq2_mul against
    the shared monomial vector [xd^3, xn*xd^2, xn^2*xd, xn^3]."""
    xd2 = tw.fq2_sqr(xd)
    xn2 = tw.fq2_sqr(xn)
    m = jnp.stack(
        [
            tw.fq2_mul(xd2, xd),
            tw.fq2_mul(xn, xd2),
            tw.fq2_mul(xn2, xd),
            tw.fq2_mul(xn2, xn),
        ],
        axis=-3,
    )  # (..., 4, 2, NL)
    terms = tw.fq2_mul(jnp.asarray(_ISO_K_NP), m[..., None, :, :, :])  # (..., 4, 4, 2, NL)
    sums = lb.add_mod(
        lb.add_mod(terms[..., 0, :, :], terms[..., 1, :, :]),
        lb.add_mod(terms[..., 2, :, :], terms[..., 3, :, :]),
    )  # (..., 4, 2, NL): x_num, x_den, y_num, y_den (all * xd^3)
    xo_n = sums[..., 0, :, :]
    xo_d = sums[..., 1, :, :]
    yo_n = tw.fq2_mul(y, sums[..., 2, :, :])
    yo_d = sums[..., 3, :, :]

    # Jacobian with Zj = xo_d * yo_d:
    Zj = tw.fq2_mul(xo_d, yo_d)
    X = tw.fq2_mul(tw.fq2_mul(xo_n, xo_d), tw.fq2_sqr(yo_d))
    Y = tw.fq2_mul(tw.fq2_mul(yo_n, tw.fq2_sqr(xo_d)), tw.fq2_mul(xo_d, tw.fq2_sqr(yo_d)))
    return (X, Y, Zj)


def map_to_g2(u0, u1):
    """Device: two field elements per message -> Jacobian point in G2
    (SSWU + isogeny on both, add, clear cofactor). u0/u1: (..., 2, NL)."""
    us = jnp.stack([u0, u1], axis=0)          # map both in one batched pass
    xn, xd, y = sswu_projective(us)
    q = iso_map_jacobian(xn, xd, y)
    q0 = jax.tree_util.tree_map(lambda c: c[0], q)
    q1 = jax.tree_util.tree_map(lambda c: c[1], q)
    r = co.jac_add(q0, q1, co.FQ2_OPS)
    # psi-based clearing: 2 |x|-multiplications instead of the 636-bit h_eff
    # double-and-add (bls381.curve.g2_clear_cofactor_fast is the ground truth)
    return co.clear_cofactor_g2(r)


# ------------------------------------------------------------ host pipeline


def hash_to_field_batch(messages, dst: bytes) -> np.ndarray:
    """Host: messages -> (n, 2, 2, NL) STANDARD-form limb array of u-values
    (the kernel converts to Montgomery on device — one batched mont_mul,
    keeping all per-element bigint work off the host)."""
    out = np.zeros((len(messages), 2, 2, lb.NL), np.uint32)
    for i, msg in enumerate(messages):
        u0, u1 = ph2c.hash_to_field_fq2(msg, 2, dst)
        for j, u in enumerate((u0, u1)):
            out[i, j, 0] = lb.pack(u[0])
            out[i, j, 1] = lb.pack(u[1])
    return out


def hash_to_g2_jacobian(us):
    """Device: (n, 2, 2, NL) STANDARD-form u-values -> batched Jacobian G2
    points (converts to Montgomery on device first)."""
    us = lb.to_mont(us)
    return map_to_g2(us[:, 0], us[:, 1])
