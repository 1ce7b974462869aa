"""Fixed-base comb MSM over G1 — the TPU-shaped answer to Pippenger.

SURVEY §7.1 calls for Pippenger MSM; the classic bucket method wins by
REDUCING TOTAL POINT-ADDS at the cost of data-dependent scatter/gather,
which is exactly what a TPU is bad at (and XLA cannot express without
sorts). What a TPU has instead is near-free vector WIDTH and expensive
sequential depth. The dominant MSM workload is fixed-base — KZG blob
commitments and proofs reuse the SAME 4096 Lagrange points every call
(/root/reference/crypto/kzg/src/lib.rs:47-81, c-kzg's precomputed tables) —
so this module trades a one-time precompute for a 16x cut in sequential
depth on every subsequent MSM:

  precompute (once per setup):  T[j][i] = 2^(16 j) * P_i   (j = 0..15)
  every MSM:   sum_i s_i P_i = sum_{i,j} c_{ij} * T[j][i]
               where s_i = sum_j c_{ij} 2^(16 j), c_{ij} 16-bit chunks

i.e. one batch double-and-add over 16*n lanes of 16-BIT scalars + one tree
reduction: sequential depth ~ 2*16 + log2(16 n) ≈ 48 vs ~512 for 256-bit
double-and-add, with the same total lane-ops — all width, no depth.

Differential ground truth: lighthouse_tpu/crypto/bls381/curve.py (tests/
test_jaxbls_msm.py).
"""

from __future__ import annotations

import os

import numpy as np

from . import curve_ops as co
from . import limbs as lb

CHUNK_BITS = 16
N_CHUNKS = 256 // CHUNK_BITS      # 16 comb rows cover the 256-bit scalar

#: window widths the autotune sweep measures and a profile may persist
#: (`autotune calibrate` — the winner lands in DeviceProfile.msm_window)
ALLOWED_WINDOWS = (2, 4, 5, 6)


def msm_window() -> int:
    """Varying-base MSM window width; 0 selects the bit double-and-add
    form. A width-w window runs ceil(256/w) digit steps of (w doublings +
    one table add) instead of 256 (double + cond-add) — less sequential
    depth for the latency-bound KZG linear combinations — but its runtime
    table build (2^w entries) compiles and executes wider, so the best w
    is a device property: `autotune calibrate` sweeps ALLOWED_WINDOWS and
    persists the winner per device kind.

    Resolution (the autotune precedence contract):
      LIGHTHOUSE_TPU_MSM_WINDOW=<0|2|4|5|6>         explicit width
      LIGHTHOUSE_TPU_MSM_WINDOWED=0/1 (legacy)      bit form / w=4
      installed plan's msm_window                   calibrated winner
      platform default                              w=4 accel, bits on CPU
                                                    (the windowed table
                                                    build compiles ~4x
                                                    slower on XLA:CPU and
                                                    CPU runs are tests)"""
    raw = os.environ.get("LIGHTHOUSE_TPU_MSM_WINDOW", "").strip()
    if raw:
        try:
            w = int(raw)
            if w == 0 or w in ALLOWED_WINDOWS:
                return w
        except ValueError:
            pass  # malformed env falls through to the next layer
    legacy = os.environ.get("LIGHTHOUSE_TPU_MSM_WINDOWED", "").strip().lower()
    if legacy:
        return 0 if legacy in ("0", "no", "off", "false") else 4
    try:
        from ...autotune import runtime as _at_runtime

        plan = _at_runtime.active_plan()
    except Exception:
        plan = None
    w = getattr(plan, "msm_window", None) if plan is not None else None
    # 0 is a measured verdict (the bit form won the calibration sweep on
    # this device) — honor it; None means unmeasured -> platform default
    if w == 0 or w in ALLOWED_WINDOWS:
        return int(w)
    import jax

    return 0 if jax.default_backend() == "cpu" else 4


def msm_digits(scalars, window: int) -> np.ndarray:
    """Host packing for `varying_base_msm_kernel`: ints mod r ->
    (n, ceil(256/w)) MSB-first digit array at width `window` (the bit
    form, window=0, consumes base-16 digits and expands them in-kernel —
    one calling convention per width)."""
    from ..bls381.constants import R

    return co.scalars_to_digits(
        [s % R for s in scalars], 256, window or 4
    )


def varying_base_msm_kernel(px, py, mask, digits, window: int = 4):
    """G1 multi-scalar multiplication over per-call (varying) bases:
    batched per-point scalar mults + masked tree reduction — the device
    path for KZG commitments and batch proof combination. `digits` from
    `msm_digits` at the same width; window=0 expands base-16 digits to
    bits in-kernel (the compile-cheap, depth-heavy CPU form)."""
    import jax.numpy as jnp

    r2x = jnp.broadcast_to(lb.R2, px.shape)
    pxm = lb.mont_mul(px, r2x)
    pym = lb.mont_mul(py, r2x)
    valid = jnp.asarray(mask, bool)
    jac = co.affine_to_jac(
        co.FQ_OPS, (pxm, pym), inf_mask=jnp.logical_not(valid)
    )
    if window:
        prod = co.scalar_mul_windowed(jac, digits, co.FQ_OPS, window=window)
    else:
        # base-16 digits -> bits inside the kernel (cheap, data-parallel)
        weights = jnp.asarray(np.array([8, 4, 2, 1], np.uint32))
        bits = (digits[..., :, None] // weights[None, None, :]) % 2
        bits = bits.reshape(digits.shape[0], -1)
        prod = co.scalar_mul_bits(jac, bits, co.FQ_OPS)
    acc = co.masked_tree_sum(prod, mask, co.FQ_OPS)
    x, y, inf = co.jac_to_affine(acc, co.FQ_OPS)
    return lb.from_mont(x), lb.from_mont(y), inf


# --- the KZG batch check's lane pass (crypto/kzg.py, backend.verify_kzg_batch_async)
#
# ONE program whatever the batch holds: KZG_BLOB_SLOTS x KZG_ROWS = 128 lanes,
# one row of the chip's vector lanes, laid out blob-major (lane = blob *
# KZG_ROWS + row). Measured on a v5e (PR 33, scripts/measure_kzg_lanes.py,
# ms a call, median of 5): 8 lanes 55.2, 64 lanes 92.3, 128 lanes 50.7 — as
# for the Miller loop (pairing_ops.MILLER_LANES), a field operation costs a
# full row what it costs one lane and a part row up to twice that, so a
# batch of six (36 real lanes) is served at 128 and not at 64, and a batch of
# one by the same program. On XLA:CPU 128 lanes cost 128 lanes (~50 s a
# call): tier-1 patches KZG_BLOB_SLOTS down, the kernel reads its shape.
KZG_ROWS = 8
KZG_BLOB_SLOTS = 16         # also the most blobs a batch holds
#: rows of a blob: the spec's terms of C' = sum r^i (C_i - y_i G1 + z_i W_i),
#: its term of W' = sum r^i W_i, then C_i and W_i times the group order;
#: two rows of padding keep the lane count a power of two
(KZG_ROW_C, KZG_ROW_G1, KZG_ROW_ZW, KZG_ROW_W,
 KZG_ROW_ORDER_C, KZG_ROW_ORDER_W) = range(6)
KZG_SCALAR_BITS = 255       # r < 2^255, and so is every scalar mod r
KZG_PAIR_LANES = 4          # the two-pair check's lanes: the BLS stage 4 at its
                            # smallest bucket (on a TPU it pads them to its row)


def kzg_lincomb_kernel(px, py, live, bits):
    """Validation and linear combinations of one KZG blob batch.

    px, py: (slots * KZG_ROWS, NL) standard-form affine G1 coordinates;
    live: (lanes,) 1 = a point, 0 = the identity (padding, or a commitment
    / proof that IS the point at infinity); bits: (lanes, 255) each lane's
    scalar, MSB first, never truncated. One double-and-add pass multiplies
    every lane; the rows of the lincombs are then summed over the blobs.
    Returns the G1 side of the two-pair check as the pairing stage takes
    it — lanes (C', -W', pad, pad) in Montgomery affine form with their
    mask: an identity side contributes 1 — and in_subgroup (slots, 2):
    [order] * C_i and [order] * W_i are the identity. Exact for every
    point of the curve: jac_add is complete, and E(Fp) has odd cofactor,
    so no point has y = 0."""
    import jax
    import jax.numpy as jnp

    r2 = jnp.broadcast_to(lb.R2, px.shape)
    jac = co.affine_to_jac(
        co.FQ_OPS, (lb.mont_mul(px, r2), lb.mont_mul(py, r2)),
        inf_mask=jnp.logical_not(jnp.asarray(live, bool)),
    )
    prod = co.scalar_mul_bits(jac, bits, co.FQ_OPS)
    slots = px.shape[0] // KZG_ROWS
    grid = tuple(c.reshape((slots, KZG_ROWS) + c.shape[1:]) for c in prod)
    rows = co.tree_sum(grid, co.FQ_OPS)                  # (KZG_ROWS,) sums

    def row(i):
        return tuple(c[i] for c in rows)

    c_prime = co.jac_add(
        co.jac_add(row(KZG_ROW_C), row(KZG_ROW_G1), co.FQ_OPS),
        row(KZG_ROW_ZW), co.FQ_OPS,
    )
    wx, wy, wz = row(KZG_ROW_W)
    sides = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]), c_prime, (wx, co.FQ_OPS.neg(wy), wz)
    )
    x, y, inf = co.jac_to_affine(sides, co.FQ_OPS)
    pad = jnp.zeros((KZG_PAIR_LANES - 2,) + x.shape[1:], x.dtype)
    pair_mask = jnp.concatenate(
        [jnp.logical_not(inf), jnp.zeros((KZG_PAIR_LANES - 2,), bool)]
    )
    at_identity = co.FQ_OPS.is_zero(prod[2]).reshape(slots, KZG_ROWS)
    in_subgroup = at_identity[:, KZG_ROW_ORDER_C:KZG_ROW_ORDER_W + 1]
    return (jnp.concatenate([x, pad]), jnp.concatenate([y, pad]), pair_mask,
            in_subgroup)


def kzg_verdict_kernel(ok, in_subgroup):
    """The batch's verdict and its points' validity flags as ONE array, so
    the host reads the device once a batch: [ok, flags of blob 0 (C, W),
    blob 1, ...] as uint32."""
    import jax.numpy as jnp

    return jnp.concatenate([
        jnp.asarray(ok, jnp.uint32).reshape(1),
        jnp.asarray(in_subgroup, jnp.uint32).reshape(-1),
    ])


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _precompute_kernel(px, py, inf_mask):
    """(n,) standard-form affine points -> flattened (N_CHUNKS * n,) Jacobian
    comb tables in Montgomery form. Row j holds 2^(16 j) * P_i."""
    import jax
    import jax.numpy as jnp

    r2 = jnp.broadcast_to(lb.R2, px.shape)
    pxm = lb.mont_mul(px, r2)
    pym = lb.mont_mul(py, r2)
    jac = co.affine_to_jac(co.FQ_OPS, (pxm, pym), inf_mask=inf_mask)

    def step(carry, _):
        def dbl(_k, p):
            return co.jac_double(p, co.FQ_OPS)

        nxt = jax.lax.fori_loop(0, CHUNK_BITS, dbl, carry)
        return nxt, carry          # emit BEFORE doubling: ys[j] = 2^(16j) P

    _, rows = jax.lax.scan(step, jac, None, length=N_CHUNKS)
    # (N_CHUNKS, n, ...) -> (N_CHUNKS * n, ...)
    return tuple(jnp.reshape(c, (-1,) + c.shape[2:]) for c in rows)


def _msm_kernel(tx, ty, tz, bits):
    """tables (J*n,) Jacobian + per-lane 16-bit scalars (J*n, 16 bits,
    MSB first) -> affine sum (standard form) + inf flag."""
    prod = co.scalar_mul_bits((tx, ty, tz), bits, co.FQ_OPS)
    acc = co.tree_sum(prod, co.FQ_OPS)
    x, y, inf = co.jac_to_affine(acc, co.FQ_OPS)
    return lb.from_mont(x), lb.from_mont(y), inf


_jit_cache: dict = {}


def _jits():
    import jax

    if not _jit_cache:
        from ...utils.jaxcfg import setup_compilation_cache

        setup_compilation_cache()
        _jit_cache["pre"] = jax.jit(_precompute_kernel)
        _jit_cache["msm"] = jax.jit(_msm_kernel)
    return _jit_cache["pre"], _jit_cache["msm"]


class FixedBaseMSM:
    """Device-resident comb tables for one fixed point set."""

    def __init__(self, points):
        from .backend import pack_ints_vec

        self.n_real = len(points)
        n = max(4, _next_pow2(self.n_real))
        px = np.zeros((n, lb.NL), np.uint32)
        py = np.zeros((n, lb.NL), np.uint32)
        inf = np.ones((n,), bool)
        live = [(i, p) for i, p in enumerate(points) if p is not None]
        if live:
            idx = [i for i, _ in live]
            px[idx] = pack_ints_vec([p[0] for _, p in live])
            py[idx] = pack_ints_vec([p[1] for _, p in live])
            inf[idx] = False
        self._n = n
        pre, _ = _jits()
        self._tables = pre(px, py, inf)   # device-resident, reused per call

    def _bits(self, scalars) -> np.ndarray:
        """host: n_real ints mod r -> (J*n, 16) uint32 bit array, MSB first,
        lane (j, i) holding chunk c_ij of scalar i (vectorized byte view)."""
        from ..bls381.constants import R

        buf = b"".join(int(s % R).to_bytes(32, "little") for s in scalars)
        chunks = np.frombuffer(buf, np.uint8).reshape(self.n_real, 32)
        c16 = chunks[:, 0::2].astype(np.uint32) | (
            chunks[:, 1::2].astype(np.uint32) << 8
        )                                          # (n_real, J) LE chunks
        full = np.zeros((self._n, N_CHUNKS), np.uint32)
        full[: self.n_real] = c16
        ct = full.T                                # (J, n)
        shifts = np.arange(CHUNK_BITS - 1, -1, -1, dtype=np.uint32)
        bits = (ct[..., None] >> shifts) & 1       # (J, n, 16) MSB first
        return bits.reshape(-1, CHUNK_BITS)

    def msm(self, scalars):
        """sum_i scalars[i] * P_i -> host affine int pair or None."""
        assert len(scalars) == self.n_real, (
            f"expected {self.n_real} scalars, got {len(scalars)}"
        )
        _, kmsm = _jits()
        x, y, inf = kmsm(*self._tables, self._bits(scalars))
        if bool(np.asarray(inf)):
            return None
        return (lb.unpack(np.asarray(x)), lb.unpack(np.asarray(y)))
