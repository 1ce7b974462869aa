"""Batched multi-precision Montgomery arithmetic for Fq (BLS12-381 base field)
on TPU.

Representation: radix 2^16, 24 limbs, least-significant first, stored as
uint32 with values < 2^16 (canonical form). All ops broadcast over arbitrary
leading batch dimensions; the limb axis is last.

Why 16-bit limbs in uint32: TPU has native 32-bit integer multiply (low half).
16x16 products fit exactly; column sums of 48 such halves stay < 2^22, so a
full 24x24 schoolbook product plus interleaved Montgomery reduction (radix-
2^16 REDC) runs with NO per-step carry chains — one lax.scan carry
normalization per multiplication. This avoids uint64 emulation entirely
(SURVEY.md §7 "hard parts" (a): limbed modular multiplication throughput is
the whole game).

Montgomery domain: R_mont = 2^384. mont_mul(a, b) = a * b * R_mont^-1 mod P.
Differentially tested against Python bigints in tests/test_jaxbls_limbs.py.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..bls381.constants import P

NL = 24            # number of limbs
LB = 16            # bits per limb
MASK = (1 << LB) - 1
U32 = jnp.uint32


def pack(x: int) -> np.ndarray:
    """Host: int -> (NL,) uint32 limb array (little-endian 16-bit limbs)."""
    if not 0 <= x < (1 << (NL * LB)):
        raise ValueError("value out of limb range")
    return np.array([(x >> (LB * i)) & MASK for i in range(NL)], dtype=np.uint32)


def unpack(arr) -> int:
    """Host: limb array (last axis NL) -> int (single element only)."""
    a = np.asarray(arr, dtype=np.uint64).reshape(-1)
    return sum(int(v) << (LB * i) for i, v in enumerate(a))


def pack_batch(xs) -> np.ndarray:
    """Host: list of ints -> (len, NL) uint32."""
    return np.stack([pack(x) for x in xs])


def unpack_batch(arr) -> list[int]:
    a = np.asarray(arr)
    flat = a.reshape(-1, a.shape[-1])
    return [sum(int(v) << (LB * i) for i, v in enumerate(row)) for row in flat]


# ----------------------------------------------------------------- constants

R_MONT = pow(2, NL * LB, P)
R2_INT = R_MONT * R_MONT % P
N0P = (-pow(P, -1, 1 << LB)) % (1 << LB)   # -P^-1 mod 2^16

N_HOST = pack(P)
N_EXT_HOST = np.concatenate([N_HOST, np.zeros(1, np.uint32)])
# HOST (numpy) constants on purpose: a module-level jnp array would
# initialize the default JAX backend at IMPORT time — and the chain's
# pubkey cache imports this module, so a beacon node booting while the
# device does not answer would hang before serving anything. jnp ops convert numpy operands at
# trace time, so consumers are unaffected.
R2 = pack(R2_INT)
ZERO = np.zeros((NL,), np.uint32)
ONE_MONT = pack(R_MONT)


# --------------------------------------------------------------------------
# Two interchangeable sets of carry/borrow internals:
#
#   * FAST (prefix form, DEFAULT) — Kogge-Stone carry-lookahead, all
#     straight-line value code: ~log2(NL) wide vector steps, no lax.scan.
#     A mont_mul then lowers to a handful of fusible elementwise/einsum HLO
#     ops instead of three nested while-loops — the large kernels (pairing,
#     hash-to-curve, windowed scalar mults) contain thousands of mont_muls,
#     and nested scans made XLA compile times explode (>10 min for the
#     verify kernel) and added per-iteration dispatch overhead at runtime.
#     The same straight-line form is what Pallas kernel bodies need (Mosaic
#     cannot lower while-loops efficiently).
#   * SCAN (legacy form) — lax.scan per limb; kept as a differential-testing
#     reference (scan_mode context manager).
# --------------------------------------------------------------------------

_FAST = True

# Kogge-Stone carry form for Pallas kernel bodies: Mosaic has no reliable
# lowering for cumsum/cummax (the closed-form prefix), but handles the
# log2(n) rounds of static lane shifts + logicals fine — and inside a fused
# kernel the extra instruction count stays in VMEM/registers instead of
# round-tripping HBM, so the XLA-compile-time argument against Kogge-Stone
# does not apply there. Thread-local because kernel warming traces several
# programs from parallel threads and the Pallas routing must not leak into
# a concurrently-traced XLA program.
import threading

_TLS = threading.local()


def _pallas_tracing() -> bool:
    return getattr(_TLS, "pallas", False)


def kernel_impl(name):
    """Kernel-body implementation overrides (same mechanism as
    kernel_const, for CODE): long pow/scalar-mul loops need their bit
    patterns as SMEM refs inside Pallas kernels, so wrappers plant
    ref-reading loop implementations that the shared tower/curve code
    dispatches to while tracing a kernel body. Returns None outside."""
    tab = getattr(_TLS, "impl_tab", None)
    if tab is None:
        return None
    return tab.get(name)


def kernel_const(name: str, default_np):
    """Field constants inside Pallas kernel bodies.

    Pallas rejects kernels that close over array constants ("captures
    constants ... pass them as inputs"), and every mont_mul trace references
    the modulus constants — so kernel wrappers pass them as real inputs and
    plant the loaded values in a thread-local table (via `pallas_mode`);
    this accessor is what the arithmetic consults. Outside kernel tracing it
    materializes the ordinary jnp constant."""
    tab = getattr(_TLS, "const_tab", None)
    if tab is not None and name in tab:
        return tab[name]
    return jnp.asarray(default_np)


class pallas_mode:
    """Context manager active while TRACING Pallas kernel bodies: routes
    limb products through the shift-accumulate form (`_poly_mul_shift` —
    Mosaic lowers static lane shifts well, gathers/one-hot matmuls poorly)
    and carries through the Kogge-Stone prefix (no cumsum/cummax). An
    optional constants table redirects `kernel_const` lookups to values the
    kernel received as inputs."""

    def __init__(self, const_tab=None, impl_tab=None):
        self._tab = const_tab
        self._impls = impl_tab

    def __enter__(self):
        self._prev = (
            getattr(_TLS, "pallas", False),
            getattr(_TLS, "const_tab", None),
            getattr(_TLS, "impl_tab", None),
        )
        _TLS.pallas = True
        _TLS.const_tab = self._tab
        _TLS.impl_tab = self._impls

    def __exit__(self, *exc):
        _TLS.pallas, _TLS.const_tab, _TLS.impl_tab = self._prev


class fast_mode:
    """Context manager: route mont_mul/add/sub internals through the
    prefix-carry straight-line forms (now the default; kept for API compat)."""

    def __enter__(self):
        global _FAST
        self._prev = _FAST
        _FAST = True

    def __exit__(self, *exc):
        global _FAST
        _FAST = self._prev


class scan_mode:
    """Context manager: route carry/borrow internals through the legacy
    lax.scan forms (differential-testing reference)."""

    def __enter__(self):
        global _FAST
        self._prev = _FAST
        _FAST = False

    def __exit__(self, *exc):
        global _FAST
        _FAST = self._prev


def _scan_last(f, init, xs):
    """lax.scan over the LAST axis of xs (any leading batch dims)."""
    moved = jnp.moveaxis(xs, -1, 0)
    carry, ys = lax.scan(f, init, moved)
    return carry, jnp.moveaxis(ys, 0, -1)


def _shiftd(x, d: int, fill=0):
    """Shift limbs toward higher indices by d positions along the last axis."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return _concat_last([pad, x[..., :-d]])


def b2u(b):
    """bool -> u32 {0,1} via SELECT, never a cast: the TPU backend refuses
    to bitcast i1 vregs to i32 (`tpu.bitcast_vreg ... Invalid vector
    register cast`, observed compiling mont_mul on a v5e), while select on
    an i1 predicate is native. Use this for every bool->int conversion
    reachable from a Pallas kernel body."""
    return jnp.where(b, jnp.uint32(1), jnp.uint32(0))


def _canon(x):
    """Force an offset-{0,0} vreg layout (Pallas kernel bodies only).

    tpu.concatenate requires operand layouts to AGREE on non-concat
    dimensions, and upstream component slices (a[..., 1, :], shift slices)
    leave residual sublane/lane offsets — every carry-column append then
    dies with "offset mismatch on non-concat dimension" (observed on a
    v5e for add_mod/_shiftd inside the fused kernels while the same code
    compiled standalone). An always-true iota-predicate select is one the
    compiler keeps, and its result inherits the iota's zero-offset layout;
    verified on-chip: the canonicalized form compiles and runs bit-exact
    where the raw concat is rejected (scripts/repro in docs/PERF_NOTES.md
    round-5 notes)."""
    if not _pallas_tracing():
        return x
    idx = lax.broadcasted_iota(jnp.uint32, x.shape, x.ndim - 1)
    return jnp.where(idx < jnp.uint32(x.shape[-1]), x, jnp.zeros_like(x))


def _concat_last(pieces):
    """Minor-axis concatenate with canonicalized operand layouts. Bool
    pieces concat as u32 (an i1 vector concat is a vreg re-layout the chip
    compiler refuses) and convert back."""
    if not _pallas_tracing():
        return jnp.concatenate(pieces, axis=-1)
    isbool = pieces[0].dtype == jnp.bool_
    if isbool:
        pieces = [b2u(p) for p in pieces]
    out = jnp.concatenate([_canon(p) for p in pieces], axis=-1)
    return out != 0 if isbool else out


def _select_assemble(units, ax: int):
    """Assemble unit-extent slabs along axis `ax` via broadcast + iota-
    compare selects. units: arrays all of extent 1 along ax, identical
    elsewhere. Every op here (expand of a unit dim on u32, broadcast,
    iota, select) has a clean Mosaic lowering — unlike tpu.concatenate,
    which rejects operands whose vreg offsets differ on non-concat
    dimensions (observed on a v5e: the tower's minor-dim component stacks,
    vector<1x4x1x24xi32> x7 -> vector<1x4x7x24xi32>, "result/input offset
    mismatch on non-concat dimension")."""
    k = len(units)
    u0 = units[0]
    out_shape = u0.shape[:ax] + (k,) + u0.shape[ax + 1 :]
    isbool = u0.dtype == jnp.bool_
    if isbool:
        units = [b2u(u) for u in units]
    idx = lax.broadcasted_iota(jnp.uint32, out_shape, ax)
    acc = jnp.broadcast_to(units[0], out_shape)
    for i in range(1, k):
        acc = jnp.where(idx == jnp.uint32(i), units[i], acc)
    return acc != 0 if isbool else acc


def kstack(arrays, axis=0):
    """jnp.stack that also lowers inside Pallas kernel bodies.

    Outside pallas tracing this IS jnp.stack. Inside, non-minor-axis
    stacks become select assemblies (see _select_assemble); minor-axis
    (lane-dim) concatenation lowers fine and keeps the jnp form."""
    arrays = [jnp.asarray(a) for a in arrays]
    if not _pallas_tracing():
        return jnp.stack(arrays, axis=axis)
    nd = arrays[0].ndim + 1
    ax = axis % nd
    units = [jnp.expand_dims(a, ax) for a in arrays]
    if ax == nd - 1:
        return _concat_last(units)
    return _select_assemble(units, ax)


def kconcat(arrays, axis=0):
    """jnp.concatenate that also lowers inside Pallas kernel bodies.

    Non-minor-axis concats are decomposed into unit-extent static slices
    and select-assembled. Callers keep pieces small along the concat axis
    (the verify kernels concat 2-9 components); a wide piece would unroll
    one select per slab."""
    arrays = [jnp.asarray(a) for a in arrays]
    nd = arrays[0].ndim
    ax = axis % nd
    if not _pallas_tracing():
        return jnp.concatenate(arrays, axis=axis)
    if ax == nd - 1:
        return _concat_last(arrays)
    units = []
    for a in arrays:
        for i in range(a.shape[ax]):
            units.append(lax.slice_in_dim(a, i, i + 1, axis=ax))
    return _select_assemble(units, ax)


def _prefix_carry(g, p):
    """Carry-lookahead over generate/propagate bit arrays, closed form.

    g[k] = limb k generates a carry (borrow) on its own; p[k] = limb k
    propagates an incoming one. Returns G[k] = carry out of window [0..k]
    with zero carry-in.

    G[k] = OR_{j<=k} (g[j] AND p[j+1..k] all set). Expressed arithmetically
    in f32 (exact: all quantities are sums of powers of two below 2^31):
      S[k]   = cumsum over log-p, log-p = 0 if p else -2^20
      best[k]= cummax of (0 if g else -2^30) - S
      G[k]   = S[k] + best[k] == 0
    TWO scan primitives + elementwise — replaces the Kogge-Stone form whose
    log2(NL) shift rounds emitted ~10x the HLO (slices/concats dominated
    kernel compile time on both CPU and TPU). Inside Pallas bodies the
    Kogge-Stone form is used instead (`pallas_mode`)."""
    if _pallas_tracing():
        return _prefix_carry_ks(g, p)
    import jax

    PBIG = jnp.float32(1 << 20)
    GBIG = jnp.float32(1 << 30)
    logp = jnp.where(p, jnp.float32(0), -PBIG)
    logg = jnp.where(g, jnp.float32(0), -GBIG)
    axis = logp.ndim - 1
    S = jnp.cumsum(logp, axis=axis)               # S[k] = sum_{i<=k} logp[i]
    best = jax.lax.cummax(logg - S, axis=axis)    # max_{j<=k} logg[j] - S[j]
    # term(j,k) = logg[j] + (S[k] - S[j]) == 0 iff g[j] and p[(j,k]] all set
    return (S + best) == 0


def _prefix_carry_ks(g, p):
    """Kogge-Stone (g, p) prefix: log2(n) rounds of static limb shifts.

    Same contract as `_prefix_carry`; used inside Pallas kernel bodies
    (see `pallas_mode`). Composition law per round with doubling span d:
      g'[k] = g[k] | (p[k] & g[k-d]) ;  p'[k] = p[k] & p[k-d]
    with out-of-range lanes contributing no generate and no propagate."""
    g = b2u(g)
    p = b2u(p)
    n = g.shape[-1]
    d = 1
    while d < n:
        g = g | (p & _shiftd(g, d))
        p = p & _shiftd(p, d)
        d *= 2
    return g != 0


def carry_normalize_fast(t):
    """Prefix-carry normalization: redundant u32 limbs (each < 2^31) ->
    canonical 16-bit limbs. Returns (normalized, final carry).

    One folding pass bounds every limb by 2^16 + 2^15 - 1, so at most one
    carry unit remains per limb; the residual ripple is a carry-lookahead
    prefix (generate/propagate can never both be set at that bound)."""
    lo = t & MASK
    hi = t >> LB                                     # < 2^15
    s = lo + _shiftd(hi, 1)                          # < 2^16 + 2^15 - 1
    g = s >> LB                                      # in {0, 1}
    p = (s & MASK) == MASK                           # g and p never both set
    G = _prefix_carry(g != 0, p)
    Gu = b2u(G)
    carry_in = _shiftd(Gu, 1)
    out = (s + carry_in) & MASK
    # positive last-lane index: a NEGATIVE int index lowers via
    # lax.dynamic_slice, which Mosaic rejects (and convert-then-index keeps
    # the squeezed lane 32-bit — bool lanes can't be squeezed to scalars)
    last = t.shape[-1] - 1
    final = Gu[..., last] + hi[..., last]
    return out, final


def _carry_normalize_scan(t):
    def body(c, limb):
        v = limb + c
        return v >> LB, v & MASK

    zero_c = jnp.zeros(t.shape[:-1], U32)
    carry, limbs = _scan_last(body, zero_c, t)
    return limbs, carry


def carry_normalize(t):
    """Propagate carries: redundant u32 limbs -> canonical 16-bit limbs.

    Returns (normalized array same shape, final carry)."""
    if _FAST:
        return carry_normalize_fast(t)
    return _carry_normalize_scan(t)


def _sub_with_borrow_fast(a, b):
    g = a < b
    p = a == b
    Bu = b2u(_prefix_carry(g, p))
    borrow_in = _shiftd(Bu, 1)
    diff = (a - b - borrow_in) & MASK                # u32 wraparound is mod 2^16
    return diff, Bu[..., Bu.shape[-1] - 1]           # nonneg index: static slice


def _sub_with_borrow(a, b):
    """a - b limbwise (canonical 16-bit limbs). Returns (diff, borrow in {0,1})."""
    if _FAST:
        return _sub_with_borrow_fast(a, b)
    return _sub_with_borrow_scan(a, b)


def _sub_with_borrow_scan(a, b):

    def body(borrow, ab):
        ai, bi = ab
        v = ai + (MASK + 1) - bi - borrow
        return 1 - (v >> LB), v & MASK

    zero_b = jnp.zeros(a.shape[:-1], U32)
    moved = (jnp.moveaxis(a, -1, 0), jnp.moveaxis(b, -1, 0))
    borrow, diff = lax.scan(lambda c, ab: body(c, ab), zero_b, moved)
    return jnp.moveaxis(diff, 0, -1), borrow


def _cond_sub_n(t):
    """Reduce t (NL+1 canonical limbs, value < 2N) to t mod N (NL limbs)."""
    n_ext = kernel_const("NEXT", N_EXT_HOST)
    n_b = jnp.broadcast_to(n_ext, t.shape)
    diff, borrow = _sub_with_borrow(t, n_b)
    # reshape the u32 borrow, then compare: reshaping a BOOL (i1) vector
    # with a new unit minor dim is rejected by the chip compiler
    # ("Insertion of minor dim that is not a no-op only supported for
    # 32-bit types"), while the compare emits the i1 in its final layout
    out = jnp.where(borrow[..., None] == 1, t, diff)
    return out[..., :NL]


def _shift_up_one(v):
    """v shifted one lane toward the high end (lane 0 becomes zero, the top
    lane drops): the carry-column shift in the poly products. A pad+slice —
    NOT `.at[1:].add`, whose scatter-add Mosaic cannot lower."""
    return _shiftd(v, 1)


def _poly_mul_shift(a, b, ncols: int):
    """Shift-accumulate schoolbook limb product (FAST form, Pallas bodies):
    na statically-shifted scaled copies of b, summed as straight-line value
    code — no banded-matrix materialization, no gather, lowers cleanly in
    Mosaic. 8-bit split of `a` keeps every partial sum < 2^31."""
    na = a.shape[-1]
    nb = b.shape[-1]
    b = _canon(b)            # pad slices below concat against fresh zeros
    a_lo = a & 0xFF
    a_hi = a >> 8
    zero = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]) + (ncols,), U32)
    c_lo = zero
    c_hi = zero
    pad_cfg = [(0, 0)] * (b.ndim - 1)
    for j in range(min(na, ncols)):
        w = min(nb, ncols - j)
        bj = jnp.pad(b[..., :w], pad_cfg + [(j, ncols - j - w)])
        c_lo = c_lo + a_lo[..., j : j + 1] * bj
        c_hi = c_hi + a_hi[..., j : j + 1] * bj
    col = c_lo + ((c_hi & 0xFF) << 8)
    col = col + _shift_up_one(c_hi >> 8)
    return col                                          # each < 2^31


def _banded(b, na: int, ncols: int):
    """Banded convolution matrix B[..., j, k] = b[k - j] (0 <= k-j < nb):
    polynomial multiplication as the batched matvec
    einsum('...j,...jk->...k', a, B). Compact HLO, keeps XLA compile times
    linear — the DEFAULT form for the plain XLA path."""
    nb = b.shape[-1]
    j = np.arange(na)[:, None]
    k = np.arange(ncols)[None, :]
    idx = k - j                                        # (na, ncols) static
    valid = jnp.asarray((idx >= 0) & (idx < nb))
    idx_c = np.clip(idx, 0, nb - 1)
    return jnp.where(valid, b[..., idx_c], 0)


_POLY_SHIFT = False  # flipped only while tracing Pallas bodies (Mosaic
                     # lowers shift-accumulate; gathers/einsum poorly)

# static anti-diagonal scatter matrices M[j*nb + l, k] = (j + l == k),
# cached per (na, nb, ncols)
_ANTIDIAG: dict = {}


def _antidiag(na: int, nb: int, ncols: int):
    key = (na, nb, ncols)
    got = _ANTIDIAG.get(key)
    if got is None:
        m = np.zeros((na * nb, ncols), np.uint32)
        for j in range(na):
            for l in range(nb):
                if j + l < ncols:
                    m[j * nb + l, j + l] = 1
        _ANTIDIAG[key] = m
        got = m
    return jnp.asarray(got)


def _poly_mul(a, b, ncols: int):
    """Carry-free limb product: a (..., na) * b (..., nb) -> (..., ncols)
    column sums, as ONE outer product + ONE matmul against a static 0/1
    anti-diagonal matrix (dot_general maps onto the MXU; the banded-gather
    einsum it replaces lowered to gathers that bloated both compile time
    and runtime). The 8-bit split of `a` keeps every partial sum < 2^31."""
    if _POLY_SHIFT or _pallas_tracing():
        return _poly_mul_shift(a, b, ncols)
    na = a.shape[-1]
    nb = b.shape[-1]
    M = _antidiag(na, nb, ncols)
    a_lo = (a & 0xFF)[..., :, None]
    a_hi = (a >> 8)[..., :, None]
    bb = b[..., None, :]
    z_lo = (a_lo * bb).reshape(a.shape[:-1] + (na * nb,))   # each < 2^24
    z_hi = (a_hi * bb).reshape(a.shape[:-1] + (na * nb,))
    c_lo = z_lo @ M                                          # columns < 2^29
    c_hi = z_hi @ M
    col = c_lo + ((c_hi & 0xFF) << 8)
    col = col + _shift_up_one(c_hi >> 8)
    return col                                               # each < 2^30


# -P^-1 mod 2^384, full-width Montgomery constant for non-interleaved REDC.
NPRIME_HOST = pack((-pow(P, -1, 1 << (NL * LB))) % (1 << (NL * LB)))


def mont_mul(a, b):
    """Montgomery product a*b*R^-1 mod P. a, b: (..., NL) canonical limbs.

    Non-interleaved REDC with all three limb products as banded
    convolutions:
      T = a*b ; m = (T mod R) * N' mod R ; res = (T + m*N) / R ; cond-sub.
    T itself stays in REDUNDANT column form for the final sum (columns of
    both T and m*N are < 2^30, so T + mN fits u32) — only T's low NL
    columns are normalized, because the m product needs canonical 16-bit
    inputs. One fewer full carry chain per multiply."""
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, batch + (NL,))
    b = jnp.broadcast_to(b, batch + (NL,))

    t = _poly_mul(a, b, 2 * NL + 1)                    # columns < 2^30
    # T mod R needs only the low NL columns canonicalized (the carry past
    # 2^384 is dropped by the mod)
    t_low, _ = carry_normalize(t[..., :NL])
    m = _poly_mul(t_low, kernel_const("NPRIME", NPRIME_HOST), NL)
    m, _ = carry_normalize(m)                          # mod 2^384 via truncation
    mn = _poly_mul(m, kernel_const("N", N_HOST), 2 * NL + 1)
    s = t + mn                                         # columns < 2^31
    s, _ = carry_normalize(s)
    res = s[..., NL:]                                  # (..., NL+1), value < 2N
    return _cond_sub_n(res)


def mont_sqr(a):
    return mont_mul(a, a)


def add_mod(a, b):
    s = a + b                                          # ≤ 2^17 per limb
    s = _concat_last([s, jnp.zeros(s.shape[:-1] + (1,), U32)])
    s, _ = carry_normalize(s)
    return _cond_sub_n(s)


def sub_mod(a, b):
    diff, borrow = _sub_with_borrow(a, b)
    n_arr = jnp.broadcast_to(kernel_const("N", N_HOST), diff.shape)
    fixed = diff + n_arr                               # ≤ 2^17 per limb
    fixed = _concat_last([fixed, jnp.zeros(fixed.shape[:-1] + (1,), U32)])
    fixed, _ = carry_normalize(fixed)
    fixed = fixed[..., :NL]
    return jnp.where(borrow[..., None] == 1, fixed, diff)  # u32 reshape, then i1


def neg_mod(a):
    """-a mod P (0 maps to 0)."""
    n_arr = jnp.broadcast_to(kernel_const("N", N_HOST), a.shape)
    diff, _ = _sub_with_borrow(n_arr, a)
    nonzero = jnp.any(a != 0, axis=-1, keepdims=True)
    return jnp.where(nonzero, diff, a)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def _cond_sub_n_ext(t):
    """One conditional subtract of N on an (NL+1)-limb value; keeps NL+1 limbs."""
    n_ext = jnp.broadcast_to(kernel_const("NEXT", N_EXT_HOST), t.shape)
    diff, borrow = _sub_with_borrow(t, n_ext)
    return jnp.where(borrow[..., None] == 1, t, diff)  # u32 reshape, then i1


def mul_small(a, k: int):
    """a * k mod P for small static int k (callers use k in {2, 3, 8, 12})."""
    assert 0 < k < (1 << 15)
    p = a * np.uint32(k)                               # ≤ 2^31
    lo = p & MASK
    hi = p >> LB
    acc = _concat_last([lo, jnp.zeros(lo.shape[:-1] + (1,), U32)])
    acc = acc + _concat_last([jnp.zeros(hi.shape[:-1] + (1,), U32), hi])
    acc, _ = carry_normalize(acc)                      # value < k*P, NL+1 limbs
    for _ in range(k - 1):
        acc = _cond_sub_n_ext(acc)
    return acc[..., :NL]


R2_HOST = pack(R2_INT)
ONE_STD_HOST = pack(1)


def to_mont(a_std):
    return mont_mul(a_std, jnp.broadcast_to(kernel_const("R2", R2_HOST), a_std.shape))


def from_mont(a_mont):
    return mont_mul(a_mont, jnp.broadcast_to(kernel_const("ONE_STD", ONE_STD_HOST), a_mont.shape))


def mont_pow_static(a, exponent: int, window: int = 4):
    """a^exponent in Montgomery domain, exponent a static Python int.

    Fixed-window exponentiation: a runtime table of a^0..a^(2^w - 1) then one
    scan over the exponent's base-2^w digits (MSB first), each step = w
    squarings + one table multiply. For 381-bit exponents this does ~490
    Montgomery products instead of 762 for bit-at-a-time square-and-select."""
    if exponent == 0:
        return jnp.broadcast_to(ONE_MONT, a.shape)
    digits = []
    e = exponent
    while e:
        digits.append(e & ((1 << window) - 1))
        e >>= window
    digits.reverse()

    # table[i] = a^i in log rounds of ONE stacked multiply each
    # (a^j = a^(j//2) * a^(j-j//2)) — sequential chains dominate compile
    nt = 1 << window
    table = [jnp.broadcast_to(ONE_MONT, a.shape), a]
    while len(table) < nt:
        m = len(table)
        idx = list(range(m, min(2 * (m - 1), nt - 1) + 1))
        prod = mont_mul(
            jnp.stack([table[j // 2] for j in idx]),
            jnp.stack([table[j - j // 2] for j in idx]),
        )
        for k in range(len(idx)):
            table.append(prod[k])
    table_arr = jnp.stack(table)                     # (2^w, ..., NL)

    acc = table_arr[digits[0]]
    rest = jnp.asarray(np.array(digits[1:], np.uint32))
    if rest.size == 0:
        return acc

    def body(acc, digit):
        for _ in range(window):
            acc = mont_sqr(acc)
        acc = mont_mul(acc, lax.dynamic_index_in_dim(table_arr, digit, 0, keepdims=False))
        return acc, None

    acc, _ = lax.scan(body, acc, rest)
    return acc


def mont_inv(a):
    """a^-1 in Montgomery domain (Fermat: a^(P-2)).

    Pallas kernel bodies plant a ref-reading square-and-multiply loop
    ("POW_PM2" — the windowed scan below needs a dynamic table gather that
    Mosaic rejects); the XLA path keeps the windowed form."""
    impl = kernel_impl("POW_PM2")
    if impl is not None:
        return impl(a)
    return mont_pow_static(a, P - 2)


# Jitted entry points for eager/test use. Inside larger jitted programs the
# un-jitted Python functions compose and fuse; these wrappers make standalone
# calls cache their compilation per input shape instead of re-tracing scans.
mont_mul_jit = jax.jit(mont_mul)
mont_sqr_jit = jax.jit(mont_sqr)
add_mod_jit = jax.jit(add_mod)
sub_mod_jit = jax.jit(sub_mod)
neg_mod_jit = jax.jit(neg_mod)
mul_small_jit = jax.jit(mul_small, static_argnums=1)
to_mont_jit = jax.jit(to_mont)
from_mont_jit = jax.jit(from_mont)
mont_pow_static_jit = jax.jit(mont_pow_static, static_argnums=1)
mont_inv_jit = jax.jit(mont_inv)
