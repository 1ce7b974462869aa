"""Batched multi-precision Montgomery arithmetic for Fq (BLS12-381 base field)
on TPU.

Representation: radix 2^16, 24 limbs, least-significant first, stored as
uint32 with values < 2^16 (canonical form). All ops broadcast over arbitrary
leading batch dimensions; the limb axis is last.

Why 16-bit limbs in uint32: TPU has native 32-bit integer multiply (low half).
16x16 products fit exactly; column sums of 48 such halves stay < 2^22, so a
full 24x24 schoolbook product plus interleaved Montgomery reduction (radix-
2^16 REDC) runs with NO per-step carry chains — carries are resolved by a
closed-form lookahead (`carry_normalize`), never limb by limb. This avoids uint64 emulation entirely
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
# Carry/borrow internals: ONE form, carry-lookahead over generate/propagate
# bits in closed form (`_prefix_carry`: one cumsum, one cummax, elementwise
# around them). All straight-line value code, no lax.scan per limb: a
# mont_mul then lowers to a handful of fusible elementwise/dot HLO ops
# instead of three nested while-loops — the large programs (pairing,
# hash-to-curve, scalar mults) contain thousands of mont_muls, and nested
# scans made XLA compile times explode (>10 min for the verify kernel) and
# added per-iteration dispatch overhead at runtime.
# --------------------------------------------------------------------------


def _shiftd(x, d: int, fill=0):
    """Shift limbs toward higher indices by d positions along the last axis."""
    pad = jnp.full(x.shape[:-1] + (d,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def b2u(b):
    """bool -> u32 {0,1} via SELECT, not a cast: select on an i1 predicate
    is native on the TPU, and it is the form every served program contains
    (a convert in its place is a change to them, to be measured)."""
    return jnp.where(b, jnp.uint32(1), jnp.uint32(0))


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
    TWO scan primitives + elementwise; a Kogge-Stone prefix in its place
    takes log2(NL) shift rounds and emitted ~10x the HLO (slices/concats
    dominated kernel compile time on both CPU and TPU)."""
    PBIG = jnp.float32(1 << 20)
    GBIG = jnp.float32(1 << 30)
    logp = jnp.where(p, jnp.float32(0), -PBIG)
    logg = jnp.where(g, jnp.float32(0), -GBIG)
    axis = logp.ndim - 1
    S = jnp.cumsum(logp, axis=axis)               # S[k] = sum_{i<=k} logp[i]
    best = lax.cummax(logg - S, axis=axis)        # max_{j<=k} logg[j] - S[j]
    # term(j,k) = logg[j] + (S[k] - S[j]) == 0 iff g[j] and p[(j,k]] all set
    return (S + best) == 0


def carry_normalize(t):
    """Prefix-carry normalization: redundant u32 limbs (each < 2^31) ->
    canonical 16-bit limbs. Returns (normalized same shape, final carry).

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
    # positive last-lane index: a static slice, where a NEGATIVE int index
    # lowers via lax.dynamic_slice; indexed after b2u, so the squeezed lane
    # is 32-bit
    last = t.shape[-1] - 1
    final = Gu[..., last] + hi[..., last]
    return out, final


def _sub_with_borrow(a, b):
    """a - b limbwise (canonical 16-bit limbs). Returns (diff, borrow in {0,1})."""
    g = a < b
    p = a == b
    Bu = b2u(_prefix_carry(g, p))
    borrow_in = _shiftd(Bu, 1)
    diff = (a - b - borrow_in) & MASK                # u32 wraparound is mod 2^16
    return diff, Bu[..., Bu.shape[-1] - 1]           # nonneg index: static slice


def _cond_sub_n(t):
    """Reduce t (NL+1 canonical limbs, value < 2N) to t mod N (NL limbs)."""
    n_ext = jnp.asarray(N_EXT_HOST)
    n_b = jnp.broadcast_to(n_ext, t.shape)
    diff, borrow = _sub_with_borrow(t, n_b)
    # reshape the u32 borrow, then compare: the compare emits the i1 in its
    # final layout (the served programs' form; reshaping the bool instead is
    # a change to them, to be measured)
    out = jnp.where(borrow[..., None] == 1, t, diff)
    return out[..., :NL]


def _shift_up_one(v):
    """v shifted one lane toward the high end (lane 0 becomes zero, the top
    lane drops): the carry-column shift in the poly products. A pad+slice,
    not an `.at[1:].add` scatter-add."""
    return _shiftd(v, 1)


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
    anti-diagonal matrix (dot_general maps onto the MXU; a banded-gather
    einsum in its place lowered to gathers that bloated both compile time
    and runtime). The 8-bit split of `a` keeps every partial sum < 2^31."""
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

    Non-interleaved REDC with all three limb products as `_poly_mul`
    column sums:
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
    m = _poly_mul(t_low, jnp.asarray(NPRIME_HOST), NL)
    m, _ = carry_normalize(m)                          # mod 2^384 via truncation
    mn = _poly_mul(m, jnp.asarray(N_HOST), 2 * NL + 1)
    s = t + mn                                         # columns < 2^31
    s, _ = carry_normalize(s)
    res = s[..., NL:]                                  # (..., NL+1), value < 2N
    return _cond_sub_n(res)


def mont_sqr(a):
    return mont_mul(a, a)


def add_mod(a, b):
    s = a + b                                          # ≤ 2^17 per limb
    s = jnp.concatenate([s, jnp.zeros(s.shape[:-1] + (1,), U32)], axis=-1)
    s, _ = carry_normalize(s)
    return _cond_sub_n(s)


def sub_mod(a, b):
    diff, borrow = _sub_with_borrow(a, b)
    n_arr = jnp.broadcast_to(jnp.asarray(N_HOST), diff.shape)
    fixed = diff + n_arr                               # ≤ 2^17 per limb
    fixed = jnp.concatenate(
        [fixed, jnp.zeros(fixed.shape[:-1] + (1,), U32)], axis=-1
    )
    fixed, _ = carry_normalize(fixed)
    fixed = fixed[..., :NL]
    return jnp.where(borrow[..., None] == 1, fixed, diff)  # u32 reshape, then i1


def neg_mod(a):
    """-a mod P (0 maps to 0)."""
    n_arr = jnp.broadcast_to(jnp.asarray(N_HOST), a.shape)
    diff, _ = _sub_with_borrow(n_arr, a)
    nonzero = jnp.any(a != 0, axis=-1, keepdims=True)
    return jnp.where(nonzero, diff, a)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def _cond_sub_n_ext(t):
    """One conditional subtract of N on an (NL+1)-limb value; keeps NL+1 limbs."""
    n_ext = jnp.broadcast_to(jnp.asarray(N_EXT_HOST), t.shape)
    diff, borrow = _sub_with_borrow(t, n_ext)
    return jnp.where(borrow[..., None] == 1, t, diff)  # u32 reshape, then i1


def mul_small(a, k: int):
    """a * k mod P for small static int k (callers use k in {2, 3, 8, 12})."""
    assert 0 < k < (1 << 15)
    p = a * np.uint32(k)                               # ≤ 2^31
    lo = p & MASK
    hi = p >> LB
    acc = jnp.concatenate([lo, jnp.zeros(lo.shape[:-1] + (1,), U32)], axis=-1)
    acc = acc + jnp.concatenate(
        [jnp.zeros(hi.shape[:-1] + (1,), U32), hi], axis=-1
    )
    acc, _ = carry_normalize(acc)                      # value < k*P, NL+1 limbs
    for _ in range(k - 1):
        acc = _cond_sub_n_ext(acc)
    return acc[..., :NL]


R2_HOST = pack(R2_INT)
ONE_STD_HOST = pack(1)


def to_mont(a_std):
    return mont_mul(a_std, jnp.broadcast_to(jnp.asarray(R2_HOST), a_std.shape))


def from_mont(a_mont):
    return mont_mul(
        a_mont, jnp.broadcast_to(jnp.asarray(ONE_STD_HOST), a_mont.shape)
    )


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
    """a^-1 in Montgomery domain (Fermat: a^(P-2))."""
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
