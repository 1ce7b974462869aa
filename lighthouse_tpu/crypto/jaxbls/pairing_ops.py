"""Batched optimal ate pairing on TPU.

Strategy (differs from the pure-Python ground truth only in schedule, not
semantics): ONE Miller loop serves all n pairs of a dispatch. It carries W
running accumulators f[0..W), each the Miller value of a fixed, disjoint
subset of the pairs. Each of the 63 doubling steps (and 5 addition steps)
squares the W accumulators once, runs the point step over all pair lanes,
and multiplies every pair's sparse line into its accumulator: directly
(one line an accumulator), or as a sparse line-pair product, a dense Fq12,
when an accumulator takes two lines or more. After the loop ONE tree
product narrows the W accumulators to the Miller value of all pairs, and a
single shared final exponentiation checks prod_i e(P_i, Q_i) == 1, the
same trick blst's verify_multiple_aggregate_signatures uses on CPU
(/root/reference/crypto/bls/src/impls/blst.rs:35-117).

W is read from the pair count (`miller_lane_plan`). On this chip a field
operation on exactly 128 pair lanes costs what it costs on one lane and
less than on 2 to 64, so narrowing a step's lines to ONE value inside every
step (log2(n/2) dense products, each on fewer lanes than the last) costs
more than everything else in the step. So the loop carries W = 128
accumulators, one full row of vector lanes, and pads the pair axis to whole
rows with masked lanes: at every pair count in a program built for a TPU,
from 33 pairs on elsewhere, where a lane costs a lane and a few pairs keep
W = 1, the whole product tree inside the step. Squaring distributes over a
product and every field operation ends canonical (< P), so the product of
the W finals is limb for limb the W = 1 value.

Line evaluations use inversion-free Jacobian steps; every line is scaled by
the Fq2 unit 2YZ^3 (doubling) or Z3 (addition), which the final
exponentiation annihilates (its easy part contains the factor p^2 - 1).
The static low-hamming-weight loop parameter X_ABS is walked with ONE
lax.scan over its bits; the (rare) addition step hides behind lax.cond with
a scalar predicate, so the compiled graph holds one loop body and runs no
wasted conditional adds.

Like the ground truth (bls381/pairing.py) this computes the CUBED pairing —
the HHT final-exp chain — which is still non-degenerate and bilinear, and
all consensus uses only compare pairing products to 1.

Padded/invalid lanes (identity points) run on garbage deterministically;
their lines are replaced by the identity line (mask select) before any
product, mirroring how the Python miller_loop skips None pairs, so an
accumulator whose pairs are all padding stays 1.
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


def _fq12_product_to(fs, w: int):
    """Tree product over the first axis, halved until `w` lanes remain (odd
    stragglers are folded into lane 0 — no shape-changing concat). `w` is
    on the halving ladder of the length: len >> k."""
    n = fs.shape[0]
    while n > w:
        half = n // 2
        prod = tw.fq12_mul(fs[:half], fs[half : 2 * half])
        if n % 2:
            prod = _set_lane0(prod, tw.fq12_mul(prod[0:1], fs[2 * half : n]))
        fs = prod
        n = half
    return fs


def fq12_product_any(fs):
    """Tree product over the first axis, any length >= 1."""
    return _fq12_product_to(fs, 1)[0]


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


def _combine_lines(line, valid_mask, w: int):
    """All n masked lines -> `w` dense Fq12 lanes: pair the lines sparsely
    (6 Fq2 muls per pair), then tree-reduce the halved batch down to w
    (not at all when w == n // 2). Lane j holds the lines of a fixed subset
    of the pairs, the same at every step of the loop."""
    l0, l1, l2 = _mask_lines(line, valid_mask)
    n = l0.shape[0]
    if n == 1:
        return _line_to_fq12((l0, l1, l2))
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
    return _fq12_product_to(fs, w)


# How many accumulators the Miller loop carries, read from the pair count.
# Measured on one TPU v5e (scripts/measure_miller_lanes.py, PR 30), ms a call
# inside a fori_loop, by pair lanes L (0 = no batch axis):
#
#   L                 0     1     2     4     8    16    32    64   128   256   257
#   fq12_mul        .299  .301  .505  .884  .608  .679  .807  .839  .351  .647  .952
#   fq12_sqr        .235  .234  .369  .600  .413  .469  .546  .562  .225  .399  .573
#   fq12_mul_by_014 .257  .151  .204  .306  .305  .527  .834 1.249  .272  .495  .678
#   _line_mul_line    -   .138  .188  .278  .239  .265  .310  .315  .167  .259  .353
#   _dbl_step         -   .288  .370  .577  .503  .377  .532  .678  .263  .378  .500
#
# A call on exactly 128 lanes costs what it costs on ONE and less than on 2
# to 64: the compiler lays the pair axis on the 128 vector lanes, and only
# whole rows are cheap (256 = two rows, 257 pays for three). So the wide loop
# always carries one full row of accumulators, padding the pair axis to it.
# miller_loop_product, ms a call (W accumulators; every value limb for limb
# W = 1's):
#
#   257 pairs   W=1 365.1   W=32 271.4   W=64 178.1   W=128  78.9
#    65 pairs   W=1 270.5   W=16 175.5   W=32 140.4   W=64 157.0 (no padding)
#                                                     W=128  42.2 (padded)
#     5 pairs   W=1 110.9   W=2  118.1                W=128  42.0 (padded)
#     4 pairs   W=1 103.7                             W=128  43.4 (padded; PR 35)
#
# Stage 4 on the padded row (--stage, PR 35; ms a call: the one program
# _stage_pairing / _stage_miller / _stage_final_exp / the two back to back):
#
#     5 pairs   116.4 / 42.1 / 48.0 / 89.1     (W = 1, PR 32: 153.3 / 111.0 / 48.0 / 157.8)
#     4 pairs   116.4 / 42.1 / 48.0 / 89.2
#
# the 65-pair row's numbers (116.3 / 42.1 / 47.9 / 89.3): a row is a row,
# whatever it holds, and the two programs beat the one wherever it is carried.
#
# XLA:CPU (sandbox, jax.jit(miller_loop_product), s a call, compile aside):
#
#     5 pairs   W=1   2.42                            W=128 46.25 (padded)
#
# The two platforms differ in what a lane is. The chip runs 128 lanes in one
# instruction, so a padded row costs what its one live lane costs and saves
# the in-step tree; a CPU core walks the lanes, so 128 lanes cost 128 lanes
# and the row is worth it only from a few dozen pairs on. MILLER_WIDE_FROM is
# therefore read by the platform the program is built for: on a TPU every pair
# count takes the row (the urgent bucket's 5 pairs, KZG's 4, a chip's share of
# a meshed bucket); elsewhere fewer than 33 pair lanes keep ONE accumulator,
# the loop PR 29 served, byte for byte.
MILLER_LANES = 128          # accumulators of the wide loop: one row of vector lanes
#: fewest pair lanes that take the row, by platform (one not named: "cpu"'s)
MILLER_WIDE_FROM = {"tpu": 1, "cpu": 33}


def _lines_per_accumulator(n_pairs: int, w: int) -> int:
    """Lines one of w accumulators takes a step: the smallest power of two
    g with w * g >= n_pairs - 1 (the one pair over — the signature pair of
    a full bucket — is folded into lane 0 on its own)."""
    g = 1
    while w * g < n_pairs - 1:
        g *= 2
    return g


def miller_lane_plan(n_pairs: int, platform: str | None = None) -> tuple:
    """What miller_loop_product does with n_pairs pair lanes in a program
    built for `platform`: (W, in_step_levels, after_loop_levels). Pure, no
    jit — the loop follows it, the backend's plan counter reads it.
    `platform` None is the process's own, jax.default_backend(), read here
    and nowhere else; a process that lowers for a chip it does not run on
    names it.

    From the platform's MILLER_WIDE_FROM pair lanes on (every count on a
    TPU), W = MILLER_LANES accumulators: the pair axis is padded with
    masked lanes to W * g (g a power of two, + the one pair over), each
    accumulator takes g lines a step — one sparse line (g = 1), a line pair
    (g = 2), or line pairs and in_step_levels = log2(g) - 1 dense tree
    levels — and one product tree of after_loop_levels levels narrows the W
    accumulators after the loop. Below that, W = 1 and the whole tree over
    the n // 2 line pairs stays in the step."""
    assert n_pairs >= 1
    if platform is None:
        platform = jax.default_backend()
    if n_pairs < MILLER_WIDE_FROM.get(platform, MILLER_WIDE_FROM["cpu"]):
        return 1, max(n_pairs // 2, 1).bit_length() - 1, 0
    w = MILLER_LANES
    g = _lines_per_accumulator(n_pairs, w)
    return w, max(g.bit_length() - 2, 0), w.bit_length() - 1


def miller_loop_product(p_aff, q_aff, valid_mask, platform: str | None = None):
    """Multi-pairing Miller loop over W shared accumulators (W from
    miller_lane_plan for `platform`; W = 1 is one shared f).

    Per bit: one fq12_sqr of the W accumulators (instead of one per pair),
    each pair's line folded into its accumulator — sparsely, or through
    the sparse line-pair product and the in-step levels of the product
    tree. Returns the Miller value prod_i f_i as one Fq12 (conjugated for
    x < 0)."""
    xp, yp = p_aff
    xq, yq = q_aff
    n = xp.shape[0]
    w = miller_lane_plan(n, platform)[0]
    g = _lines_per_accumulator(n, w)
    f = tw.FQ12_ONE
    if w > 1:
        f = jnp.broadcast_to(f, (w,) + f.shape)
        pad = max(w * g - n, 0)
        if pad:
            # masked lanes up to whole rows: they run on zeros, their lines
            # are the identity line
            def z(a):
                return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

            xp, yp, xq, yq = z(xp), z(yp), z(xq), z(yq)
            valid_mask = z(jnp.asarray(valid_mask, bool))
    r = co.affine_to_jac(co.FQ2_OPS, (xq, yq))
    bits_arr = jnp.asarray(np.array([int(b) for b in _X_BITS], np.uint32))

    def times_lines(f, line):
        if w > 1 and g == 1:
            # one sparse line an accumulator; the pair over goes into lane 0
            l0, l1, l2 = _mask_lines(line, valid_mask)
            f = tw.fq12_mul_by_014(f, l0[:w], l1[:w], l2[:w])
            if l0.shape[0] > w:
                f = _set_lane0(
                    f, tw.fq12_mul_by_014(f[0:1], l0[w:], l1[w:], l2[w:])
                )
            return f
        fs = _combine_lines(line, valid_mask, w)
        return tw.fq12_mul(f, fs if w > 1 else fs[0])

    def step(carry, bit):
        f, r = carry
        f = tw.fq12_sqr(f)
        r, line = _dbl_step(r, xp, yp)
        f = times_lines(f, line)

        def with_add(op):
            f_, r_ = op
            r2, line2 = _add_step(r_, (xq, yq), xp, yp)
            return (times_lines(f_, line2), r2)

        f, r = lax.cond(bit == 1, with_add, lambda op: op, (f, r))
        return (f, r), None

    (f, r), _ = lax.scan(step, (f, r), bits_arr)
    if w > 1:
        f = fq12_product_any(f)
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
