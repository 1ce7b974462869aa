"""Batched optimal ate pairing on TPU.

Strategy (differs from the pure-Python ground truth only in schedule, not
semantics): the Miller loop runs vmapped over the pair axis — each pair keeps
its own running f_i — then the product over pairs is one tree reduction and a
single shared final exponentiation checks prod_i e(P_i, Q_i) == 1. That keeps
every step embarrassingly batch-parallel (the TPU win) while doing the one
expensive final exp only once, the same trick blst's
verify_multiple_aggregate_signatures uses on CPU
(/root/reference/crypto/bls/src/impls/blst.rs:35-117).

Line evaluations use inversion-free Jacobian steps; every line is scaled by
the Fq2 unit 2YZ^3 (doubling) or Z3 (addition), which the final
exponentiation annihilates (its easy part contains the factor p^2 - 1).
The static low-hamming-weight loop parameter X_ABS is walked with lax.scan
over zero-runs + unrolled add steps, so the compiled graph stays small while
doing no wasted conditional adds.

Like the ground truth (bls381/pairing.py) this computes the CUBED pairing —
the HHT final-exp chain — which is still non-degenerate and bilinear, and
all consensus uses only compare pairing products to 1.

Padded/invalid lanes (identity points) run on garbage deterministically and
are replaced by 1 before the product (mask select), mirroring how the Python
miller_loop skips None pairs.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..bls381.constants import X_ABS
from . import tower as tw
from . import curve_ops as co

# Bits of X_ABS after the implicit leading 1, MSB first (static, 63 bits).
_X_BITS = bin(X_ABS)[3:]


def _dbl_step(r, xp, yp):
    """Jacobian doubling of R (G2/Fq2) + line through the tangent evaluated
    at P=(xp, yp) (G1/Fq, Montgomery). Line scaled by the Fq2 unit 2YZ^3.

    Returns (R2, line) with line = (l0, l1, l2) sparse Fq12 coefficients:
    l(P) = l0 + l1*v + l2*v*w, l0,l1,l2 in Fq2."""
    X, Y, Z = r
    A = tw.fq2_sqr(X)
    B = tw.fq2_sqr(Y)
    C = tw.fq2_sqr(B)
    t = tw.fq2_sqr(tw.fq2_add(X, B))
    D = tw.fq2_mul_small(tw.fq2_sub(tw.fq2_sub(t, A), C), 2)
    E = tw.fq2_mul_small(A, 3)
    F = tw.fq2_sqr(E)
    X3 = tw.fq2_sub(F, tw.fq2_mul_small(D, 2))
    Y3 = tw.fq2_sub(tw.fq2_mul(E, tw.fq2_sub(D, X3)), tw.fq2_mul_small(C, 8))
    ZZ = tw.fq2_sqr(Z)
    Z3 = tw.fq2_mul_small(tw.fq2_mul(Y, Z), 2)

    # l0 = 3X^3 - 2Y^2 ; l1 = -3 X^2 Z^2 * xp ; l2 = Z3 * Z^2 * yp
    l0 = tw.fq2_sub(tw.fq2_mul(E, X), tw.fq2_mul_small(B, 2))
    l1 = tw.fq2_mul_fq(tw.fq2_neg(tw.fq2_mul(E, ZZ)), xp)
    l2 = tw.fq2_mul_fq(tw.fq2_mul(Z3, ZZ), yp)
    return (X3, Y3, Z3), (l0, l1, l2)


def _add_step(r, q_aff, xp, yp):
    """Mixed Jacobian+affine addition R+Q + line through R, Q evaluated at P.
    Line scaled by the Fq2 unit Z3 = Z1*H."""
    X1, Y1, Z1 = r
    xq, yq = q_aff
    Z1Z1 = tw.fq2_sqr(Z1)
    U2 = tw.fq2_mul(xq, Z1Z1)
    S2 = tw.fq2_mul(tw.fq2_mul(yq, Z1), Z1Z1)
    H = tw.fq2_sub(U2, X1)
    rr = tw.fq2_sub(S2, Y1)
    HH = tw.fq2_sqr(H)
    HHH = tw.fq2_mul(H, HH)
    V = tw.fq2_mul(X1, HH)
    X3 = tw.fq2_sub(tw.fq2_sub(tw.fq2_sqr(rr), HHH), tw.fq2_mul_small(V, 2))
    Y3 = tw.fq2_sub(tw.fq2_mul(rr, tw.fq2_sub(V, X3)), tw.fq2_mul(Y1, HHH))
    Z3 = tw.fq2_mul(Z1, H)

    l0 = tw.fq2_sub(tw.fq2_mul(rr, xq), tw.fq2_mul(yq, Z3))
    l1 = tw.fq2_mul_fq(tw.fq2_neg(rr), xp)
    l2 = tw.fq2_mul_fq(Z3, yp)
    return (X3, Y3, Z3), (l0, l1, l2)


def _line_to_fq12(line):
    l0, l1, l2 = line
    z = jnp.zeros_like(l0)
    c0 = jnp.stack([l0, l1, z], axis=-3)
    c1 = jnp.stack([z, l2, z], axis=-3)
    return jnp.stack([c0, c1], axis=-4)


def _mul_by_line(f, line):
    """f * line via the sparse mul_by_014 (13 Fq2 products vs 18 dense)."""
    l0, l1, l2 = line
    return tw.fq12_mul_by_014(f, l0, l1, l2)


def _line_mul_line(la, lb_):
    """Product of two sparse 014 lines -> dense Fq12 (c1[0] stays zero).

    6 Fq2 products (one batched fq2_mul) via Karatsuba cross terms."""
    l0, l1, l2 = la
    m0, m1, m2 = lb_
    A = jnp.stack(
        [l0, l1, l2, tw.fq2_add(l0, l1), tw.fq2_add(l0, l2), tw.fq2_add(l1, l2)],
        axis=-3,
    )
    B = jnp.stack(
        [m0, m1, m2, tw.fq2_add(m0, m1), tw.fq2_add(m0, m2), tw.fq2_add(m1, m2)],
        axis=-3,
    )
    t = tw.fq2_mul(A, B)
    p00, p11, p22 = t[..., 0, :, :], t[..., 1, :, :], t[..., 2, :, :]
    s01, s02, s12 = t[..., 3, :, :], t[..., 4, :, :], t[..., 5, :, :]
    c00 = tw.fq2_add(p00, tw.fq2_mul_by_xi(p22))
    c01 = tw.fq2_sub(tw.fq2_sub(s01, p00), p11)
    c02 = p11
    c10 = jnp.zeros_like(p00)
    c11 = tw.fq2_sub(tw.fq2_sub(s02, p00), p22)
    c12 = tw.fq2_sub(tw.fq2_sub(s12, p11), p22)
    lo = jnp.stack([c00, c01, c02], axis=-3)
    hi = jnp.stack([c10, c11, c12], axis=-3)
    return jnp.stack([lo, hi], axis=-4)


def _set_lane0(fs, folded):
    """fs with lane 0 replaced by `folded` (unit leading axis).

    Keeps tree reductions concat-free: instead of carrying an odd leftover
    lane to the next level through a leading-axis concatenate, the
    straggler is multiplied into lane 0 and planted via an iota select.
    Field products are exact mod P, so the association change is
    bit-invisible."""
    idx = lax.broadcasted_iota(jnp.uint32, fs.shape, 0)
    return jnp.where(idx == 0, folded, fs)


def fq12_product_any(fs):
    """Tree product over the first axis, any length >= 1 (odd stragglers are
    folded into lane 0 — no shape-changing concat)."""
    n = fs.shape[0]
    while n > 1:
        half = n // 2
        prod = tw.fq12_mul(fs[:half], fs[half : 2 * half])
        if n % 2:
            prod = _set_lane0(prod, tw.fq12_mul(prod[0:1], fs[2 * half : n]))
        fs = prod
        n = half
    return fs[0]


def _mask_lines(line, valid_mask):
    """Replace invalid lanes with the identity line (1, 0, 0)."""
    l0, l1, l2 = line
    m = jnp.asarray(valid_mask, bool)
    one = jnp.broadcast_to(jnp.asarray(tw.FQ2_ONE), l0.shape)
    zero = jnp.zeros_like(l0)
    return (
        tw.fq2_select(m, l0, one),
        tw.fq2_select(m, l1, zero),
        tw.fq2_select(m, l2, zero),
    )


def _combine_lines(line, valid_mask):
    """All n masked lines -> ONE dense Fq12: pair the lines sparsely
    (6 Fq2 muls per pair) then tree-reduce the halved batch."""
    l0, l1, l2 = _mask_lines(line, valid_mask)
    n = l0.shape[0]
    if n == 1:
        return _line_to_fq12((l0, l1, l2))[0]
    half = n // 2
    fs = _line_mul_line(
        (l0[:half], l1[:half], l2[:half]),
        (l0[half : 2 * half], l1[half : 2 * half], l2[half : 2 * half]),
    )
    if n % 2:
        # odd straggler: sparse-fold its line into lane 0 (cheaper than the
        # old identity-line pad, and concat-free)
        folded = tw.fq12_mul_by_014(
            fs[0:1], l0[n - 1 : n], l1[n - 1 : n], l2[n - 1 : n]
        )
        fs = _set_lane0(fs, folded)
    return fq12_product_any(fs)


def miller_loop_product(p_aff, q_aff, valid_mask):
    """Multi-pairing Miller loop with ONE shared accumulator f.

    Per bit: a single fq12_sqr (instead of one per pair), each pair's line
    folded in through a sparse line-pair product tree. Returns the Miller
    value prod_i f_i as one Fq12 (conjugated for x < 0)."""
    xp, yp = p_aff
    xq, yq = q_aff
    r = co.affine_to_jac(co.FQ2_OPS, (xq, yq))
    f = tw.FQ12_ONE
    bits_arr = jnp.asarray(np.array([int(b) for b in _X_BITS], np.uint32))

    def step(carry, bit):
        f, r = carry
        f = tw.fq12_sqr(f)
        r, line = _dbl_step(r, xp, yp)
        f = tw.fq12_mul(f, _combine_lines(line, valid_mask))

        def with_add(op):
            f_, r_ = op
            r2, line2 = _add_step(r_, (xq, yq), xp, yp)
            return (tw.fq12_mul(f_, _combine_lines(line2, valid_mask)), r2)

        f, r = lax.cond(bit == 1, with_add, lambda op: op, (f, r))
        return (f, r), None

    (f, r), _ = lax.scan(step, (f, r), bits_arr)
    return tw.fq12_conj(f)          # x < 0: conjugate the Miller value


def miller_loop_batch(p_aff, q_aff, valid_mask):
    """Per-pair Miller loop, batched over the leading axis.

    p_aff: (xp, yp) G1 affine Fq limbs, shape (n, NL) each, Montgomery.
    q_aff: (xq, yq) G2 affine Fq2 pairs, each component (n, NL).
    valid_mask: (n,) bool; invalid lanes yield f = 1.
    Returns per-pair f_i (Fq12 batched)."""
    xp, yp = p_aff
    xq, yq = q_aff
    n = xp.shape[0]
    f = jnp.broadcast_to(tw.FQ12_ONE, (n,) + tw.FQ12_ONE.shape)
    r = co.affine_to_jac(co.FQ2_OPS, (xq, yq))

    # ONE scan instance over the static bit pattern; the (rare) add step
    # hides behind lax.cond with a scalar predicate, so only the taken
    # branch runs at runtime and only one loop body is compiled — compile
    # time stays flat in the bit length.
    bits_arr = jnp.asarray(np.array([int(b) for b in _X_BITS], np.uint32))

    def step(carry, bit):
        f, r = carry
        f = tw.fq12_sqr(f)
        r, line = _dbl_step(r, xp, yp)
        f = _mul_by_line(f, line)

        def with_add(op):
            f_, r_ = op
            r2, line2 = _add_step(r_, (xq, yq), xp, yp)
            return (_mul_by_line(f_, line2), r2)

        f, r = lax.cond(bit == 1, with_add, lambda op: op, (f, r))
        return (f, r), None

    (f, r), _ = lax.scan(step, (f, r), bits_arr)
    # x < 0: conjugate the Miller value.
    f = tw.fq12_conj(f)
    one = jnp.broadcast_to(tw.FQ12_ONE, (n,) + tw.FQ12_ONE.shape)
    return tw.fq12_select(jnp.asarray(valid_mask, bool), f, one)


def fq12_product(fs):
    """Tree product over the first axis (length must be power of two)."""
    n = fs.shape[0]
    assert n & (n - 1) == 0
    while n > 1:
        half = n // 2
        fs = tw.fq12_mul(fs[:half], fs[half:n])
        n = half
    return fs[0]


def _cyc_exp_abs_x(a):
    """a^|x| for cyclotomic a: one scan of Granger-Scott squarings with the
    multiply for one-bits behind lax.cond (scalar predicate -> single
    compiled body, no wasted multiplies at runtime)."""
    bits_arr = jnp.asarray(np.array([int(b) for b in bin(X_ABS)[3:]], np.uint32))

    def step(acc, bit):
        acc = tw.fq12_cyclotomic_sqr(acc)
        acc = lax.cond(bit == 1, lambda x: tw.fq12_mul(x, a), lambda x: x, acc)
        return acc, None

    acc, _ = lax.scan(step, a, bits_arr)
    return acc


def _exp_neg_x(a):
    return tw.fq12_conj(_cyc_exp_abs_x(a))


def final_exponentiation(m):
    """m^(3 (p^12 - 1) / r), matching bls381.pairing.final_exponentiation."""
    t = tw.fq12_mul(tw.fq12_conj(m), tw.fq12_inv(m))      # m^(p^6 - 1)
    t = tw.fq12_mul(tw.fq12_frobenius(t, 2), t)           # ^(p^2 + 1)

    y0 = tw.fq12_mul(_exp_neg_x(t), tw.fq12_conj(t))
    y1 = tw.fq12_mul(_exp_neg_x(y0), tw.fq12_conj(y0))
    y2 = tw.fq12_mul(_exp_neg_x(y1), tw.fq12_frobenius(y1, 1))
    y3 = tw.fq12_mul(
        tw.fq12_mul(_exp_neg_x(_exp_neg_x(y2)), tw.fq12_frobenius(y2, 2)),
        tw.fq12_conj(y2),
    )
    t3 = tw.fq12_mul(tw.fq12_mul(t, t), t)
    return tw.fq12_mul(y3, t3)


def pairing_product_is_one(p_aff, q_aff, valid_mask):
    """prod_{i valid} e(P_i, Q_i) == 1: shared-accumulator Miller loop
    (any pair count) + one final exponentiation."""
    f = miller_loop_product(p_aff, q_aff, valid_mask)
    f = final_exponentiation(f)
    return tw.fq12_eq_one(f)
