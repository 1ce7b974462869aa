"""Pallas-fused pairing kernels: the whole Miller loop (and the final-exp
hard part) as single TPU kernels.

Why: the XLA path builds the pairing out of ~50 small elementwise HLO ops per
Montgomery multiply; XLA fuses runs of them, but every fusion boundary is an
HBM round trip and a dispatch, and the Miller loop is a 63-iteration
sequential scan of such chains over tiny (<1 MB) operands — the stage is
latency-bound, not FLOP-bound (docs/PERF_NOTES.md). Fusing each loop into ONE
`pl.pallas_call` keeps f, R and the line tree resident in VMEM for the whole
loop: per-iteration cost collapses from dozens of kernel launches to straight
VPU work.

Kernel design notes:
  * loop bit patterns (the BLS12-381 x parameter, MSB-first) are passed as
    int32 SMEM inputs and read per-iteration with a scalar load inside
    `lax.fori_loop` — Mosaic handles SMEM scalar indexing; closing over a
    constant array and gathering from it does not lower well;
  * Pallas rejects kernels that capture array constants, and the field
    arithmetic references the modulus constants in every multiply — so the
    wrappers pass one constants bundle (modulus forms, tower ones, Frobenius
    coefficients) as real inputs and `limbs.pallas_mode` plants the loaded
    values where `limbs.kernel_const` finds them;
  * kernel bodies trace the SAME tower/curve code as the XLA path
    (tower.py / pairing_ops.py), with `limbs.pallas_mode` routing the two
    Mosaic-hostile internals to kernel-friendly forms: limb products via
    shift-accumulate (`_poly_mul_shift`, static lane shifts) and carries via
    Kogge-Stone prefix (no cumsum/cummax). Differential tests in
    tests/test_jaxbls_pallas.py pin both routings bit-exact to the XLA path;
  * the final exponentiation's easy part stays in XLA: it contains the one
    Fq12 Fermat inversion (a 381-bit windowed pow), which is a dynamic-gather
    scan that Mosaic would force us to restructure for little gain — the hard
    part (5 chains of 63 cyclotomic squarings, ~85% of final-exp work) is the
    fused kernel;
  * everything is single-program (grid=()): the whole multi-pairing working
    set for a 64-set batch is ~200 KB, far under one core's VMEM.

Reference workload this accelerates: multi-set verification exactly as in
/root/reference/crypto/bls/src/impls/blst.rs:35-117 (SURVEY.md §6 north star).

Mode selection (LIGHTHOUSE_TPU_PALLAS) — never decided by a file, a
platform string or a caught exception:
  "auto" (default), "off"/"0" — the plain XLA staged programs.
  "on"/"1"         — the fused kernels, compiled; whatever Mosaic raises
                     is raised.
  "interpret"      — fused kernels in Pallas interpreter mode (CPU tests).
Whether a fused kernel earns a place on the default path is decided on a
benchmark (ROADMAP D3), not here.
"""

from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..bls381.constants import P, X_ABS
from . import limbs as lb
from . import tower as tw
from . import pairing_ops as po

# x-parameter bits after the implicit leading 1, MSB first (63 entries).
_X_BITS_ARR = np.array([int(b) for b in bin(X_ABS)[3:]], np.int32)


def mode() -> str | None:
    """Resolve the Pallas routing mode. Returns "compile", "interpret" or
    None (use the plain XLA path). The decision reads the environment
    switch alone, so every stage and shape takes the same path, and an
    unknown value raises instead of picking one."""
    env = os.environ.get("LIGHTHOUSE_TPU_PALLAS", "auto").lower()
    if env in ("auto", "off", "0", "no"):
        return None
    if env == "interpret":
        return "interpret"
    if env in ("on", "1", "yes", "force"):
        return "compile"
    raise ValueError(
        f"LIGHTHOUSE_TPU_PALLAS={env!r}: expected auto, off, on or interpret"
    )


def _pl():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


# -------------------------------------------------------- constants bundle

_CONSTS_CACHE: list = []


def _consts():
    """(name, np_array) pairs for every constant any kernel body reads via
    limbs.kernel_const. One shared bundle keeps the wrapper plumbing
    uniform; Mosaic drops the entries a given kernel does not touch."""
    if not _CONSTS_CACHE:
        from . import h2c_ops as h2
        from ..bls381 import curve as pc

        _CONSTS_CACHE.append(
            [
                ("N", lb.N_HOST),
                ("NEXT", lb.N_EXT_HOST),
                ("NPRIME", lb.NPRIME_HOST),
                ("R2", lb.R2_HOST),
                ("ONE_STD", lb.ONE_STD_HOST),
                ("FQ_ONE", tw._mont_const(1)),
                ("FQ2_ONE", tw._FQ2_ONE_NP),
                ("FQ12_ONE", tw._FQ12_ONE_NP),
                ("FROB12C_1", tw._frob12_coeff_np(1)),
                ("FROB12C_2", tw._frob12_coeff_np(2)),
                ("PSI_CX", np.asarray(tw._fq2_const_np(pc.PSI_CX))),
                ("PSI_CY", np.asarray(tw._fq2_const_np(pc.PSI_CY))),
                ("ISO_A", h2._ISO_A_NP),
                ("ISO_B", h2._ISO_B_NP),
                ("ISO_Z", h2._ISO_Z_NP),
                ("ISO_NEG_A", h2._NEG_A_NP),
                ("ISO_ZA", h2._ZA_NP),
                ("H2C_CANDS", h2._CAND_CONSTS_NP),
                ("ISO_K", h2._ISO_K_NP),
                ("NEG_G1X", tw._mont_const(pc.g1_neg(pc.G1_GEN)[0])),
                ("NEG_G1Y", tw._mont_const(pc.g1_neg(pc.G1_GEN)[1])),
            ]
        )
    return _CONSTS_CACHE[0]


def _const_inputs():
    """The constants every kernel receives (1-D entries get a leading unit
    axis — Mosaic prefers >=2-D vector operands)."""
    return tuple(
        jnp.asarray(a[None] if a.ndim == 1 else a) for _n, a in _consts()
    )


def _const_tab(refs):
    """Load the bundle inside a kernel body -> {name: value} for
    limbs.kernel_const, dropping the unit axis added by _const_inputs."""
    tab = {}
    for (name, arr), ref in zip(_consts(), refs):
        v = ref[...]
        tab[name] = v[0] if arr.ndim == 1 else v
    return tab


def _n_consts():
    return len(_consts())


def _const_specs(pl, pltpu):
    return [pl.BlockSpec(memory_space=pltpu.VMEM)] * _n_consts()


# ------------------------------------------------------------ Miller loop


def _miller_kernel(bits_ref, *refs):
    """Shared-accumulator multi-Miller loop, one kernel launch.

    Same schedule as pairing_ops.miller_loop_product: per bit one shared
    fq12_sqr, every pair's line folded in through the sparse line-pair
    product tree; conditional add steps behind a scalar-predicate cond."""
    consts = refs[: _n_consts()]
    px_ref, py_ref, qx_ref, qy_ref, mask_ref, f_ref = refs[_n_consts() :]
    tab = _const_tab(consts)
    with lb.pallas_mode(tab):
        xp = px_ref[...]
        yp = py_ref[...]
        xq = qx_ref[...]
        yq = qy_ref[...]
        mask = mask_ref[...][:, 0] != 0                  # (n, 1) -> (n,)

        # R = (xq, yq, 1) in Jacobian (inline: affine_to_jac would close
        # over the ops-namespace ONE constant)
        r = (xq, yq, jnp.broadcast_to(tab["FQ2_ONE"], xq.shape))
        f = tab["FQ12_ONE"]

        def dbl(fr):
            f, r = fr
            f = tw.fq12_sqr(f)
            r, line = po._dbl_step(r, xp, yp)
            f = tw.fq12_mul(f, po._combine_lines(line, mask))
            return f, r

        def add(fr):
            f, r = fr
            r, line = po._add_step(r, (xq, yq), xp, yp)
            f = tw.fq12_mul(f, po._combine_lines(line, mask))
            return f, r

        def step(i, fr):
            fr = dbl(fr)
            return lax.cond(bits_ref[i] == 1, add, lambda x: x, fr)

        f, _r = lax.fori_loop(0, _X_BITS_ARR.shape[0], step, (f, r))
        f_ref[...] = tw.fq12_conj(f)                     # x < 0: conjugate


def miller_loop_product_fused(p_aff, q_aff, valid_mask, *, interpret=False):
    """Drop-in for pairing_ops.miller_loop_product via the fused kernel."""
    pl, pltpu = _pl()
    xp, yp = p_aff
    xq, yq = q_aff
    n = xp.shape[0]
    mask2d = jnp.asarray(valid_mask, jnp.uint32).reshape(n, 1)
    return pl.pallas_call(
        _miller_kernel,
        out_shape=jax.ShapeDtypeStruct(tw.FQ12_ONE.shape, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + _const_specs(pl, pltpu)
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(jnp.asarray(_X_BITS_ARR), *_const_inputs(), xp, yp, xq, yq, mask2d)


# ------------------------------------------------- final exponentiation


def _hard_part_kernel(bits_ref, *refs):
    """The final exponentiation's hard part (input already raised to
    (p^6 - 1)(p^2 + 1)): five |x|-exponentiation chains of Granger-Scott
    cyclotomic squarings + the frobenius/conjugate wiring, fused."""
    consts = refs[: _n_consts()]
    t_ref, out_ref = refs[_n_consts() :]
    tab = _const_tab(consts)
    with lb.pallas_mode(tab):
        t = t_ref[...]

        def exp_neg_x(a):
            def step(i, acc):
                acc = tw.fq12_cyclotomic_sqr(acc)
                return lax.cond(
                    bits_ref[i] == 1, lambda x: tw.fq12_mul(x, a), lambda x: x, acc
                )

            acc = lax.fori_loop(0, _X_BITS_ARR.shape[0], step, a)
            return tw.fq12_conj(acc)                     # x < 0

        y0 = tw.fq12_mul(exp_neg_x(t), tw.fq12_conj(t))
        y1 = tw.fq12_mul(exp_neg_x(y0), tw.fq12_conj(y0))
        y2 = tw.fq12_mul(exp_neg_x(y1), tw.fq12_frobenius(y1, 1))
        y3 = tw.fq12_mul(
            tw.fq12_mul(exp_neg_x(exp_neg_x(y2)), tw.fq12_frobenius(y2, 2)),
            tw.fq12_conj(y2),
        )
        t3 = tw.fq12_mul(tw.fq12_mul(t, t), t)
        out_ref[...] = tw.fq12_mul(y3, t3)


def final_exp_hard_part_fused(t, *, interpret=False):
    pl, pltpu = _pl()
    return pl.pallas_call(
        _hard_part_kernel,
        out_shape=jax.ShapeDtypeStruct(tw.FQ12_ONE.shape, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + _const_specs(pl, pltpu)
        + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(jnp.asarray(_X_BITS_ARR), *_const_inputs(), t)


def final_exponentiation_fused(m, *, interpret=False):
    """Matches pairing_ops.final_exponentiation (the cubed-pairing HHT
    chain): easy part in XLA (contains the Fq12 Fermat inversion), hard
    part fused."""
    t = tw.fq12_mul(tw.fq12_conj(m), tw.fq12_inv(m))     # m^(p^6 - 1)
    t = tw.fq12_mul(tw.fq12_frobenius(t, 2), t)          # ^(p^2 + 1)
    return final_exp_hard_part_fused(t, interpret=interpret)


def pairing_product_is_one_fused(p_aff, q_aff, valid_mask, *, interpret=False):
    with jax.named_scope("jaxbls/pairing_fused"):
        f = miller_loop_product_fused(
            p_aff, q_aff, valid_mask, interpret=interpret
        )
        f = final_exponentiation_fused(f, interpret=interpret)
        return tw.fq12_eq_one(f)


# ---------------------------------------------------------- hash-to-G2

# Full bit patterns (leading 1 included), MSB first, for in-kernel loops.
_XABS_BITS_FULL = np.array([int(b) for b in bin(X_ABS)[2:]], np.int32)


def _e_bits_full():
    from . import h2c_ops as h2

    return np.asarray(h2._E_BITS, np.int32)


def _fq2_pow_ref(a, bits_ref):
    """a^e inside a kernel body: MSB-first square-and-multiply over an SMEM
    bit array (leading bit must be 1 — acc starts at a)."""

    def step(i, acc):
        acc = tw.fq2_sqr(acc)
        return lax.cond(bits_ref[i] == 1, lambda x: tw.fq2_mul(x, a), lambda x: x, acc)

    return lax.fori_loop(1, bits_ref.shape[0], step, a)


def _scalar_mul_ref(p_jac, ops, bits_ref):
    """Jacobian double-and-add over an SMEM bit array inside a kernel
    body (same schedule as curve_ops.scalar_mul_static)."""
    from . import curve_ops as co

    init = jax.tree_util.tree_map(
        lambda c, x: jnp.broadcast_to(c, x.shape), co.identity(ops), p_jac
    )

    def step(i, acc):
        acc = co.jac_double(acc, ops)
        return lax.cond(
            bits_ref[i] == 1, lambda a: co.jac_add(a, p_jac, ops), lambda a: a, acc
        )

    return lax.fori_loop(0, bits_ref.shape[0], step, init)


# ------------------------------------------- prepare / pairs stages

_PM2_BITS = np.array([int(b) for b in bin(P - 2)[2:]], np.int32)


def _mont_pow_ref(a, bits_ref):
    """Fq square-and-multiply over an SMEM bit array (leading bit 1)."""

    def step(i, acc):
        acc = lb.mont_sqr(acc)
        return lax.cond(bits_ref[i] == 1, lambda x: lb.mont_mul(x, a), lambda x: x, acc)

    return lax.fori_loop(1, bits_ref.shape[0], step, a)


def _prepare_kernel(pbits_ref, *refs):
    """Fused stage 1: Montgomery conversion, per-set pubkey tree
    aggregation, the 64-bit random-coefficient double-and-add for aggregate
    pubkeys AND signatures in ONE loop, and the signature tree-sum."""
    from . import curve_ops as co

    consts = refs[: _n_consts()]
    (pkx_ref, pky_ref, pkm_ref, sigx_ref, sigy_ref, zd_ref, sm_ref,
     zx_ref, zy_ref, zz_ref, sx_ref, sy_ref, sz_ref, bad_ref) = refs[_n_consts():]
    tab = _const_tab(consts)
    impls = {"POW_PM2": lambda a: _mont_pow_ref(a, pbits_ref)}
    with lb.pallas_mode(tab, impls):
        # pk arrays arrive PRE-TRANSPOSED (m, n, NL) from the wrapper — the
        # (n, m) -> (m, n) moveaxis is a tiled-dim transpose Mosaic would
        # have to re-layout; XLA does it outside the kernel for free
        pk_x = lb.to_mont(pkx_ref[...])
        pk_y = lb.to_mont(pky_ref[...])
        sig_x = lb.to_mont(sigx_ref[...])
        sig_y = lb.to_mont(sigy_ref[...])
        pk_mask = pkm_ref[...]
        set_mask = sm_ref[...][:, 0]
        zd = zd_ref[...]

        pk_jac_t = co.affine_to_jac(
            co.FQ_OPS, (pk_x, pk_y), inf_mask=jnp.logical_not(pk_mask)
        )
        m = pk_x.shape[0]
        agg = pk_jac_t
        while m > 1:
            half = m // 2
            a = tuple(c[:half] for c in agg)
            b = tuple(c[half:m] for c in agg)
            agg = co.jac_add(a, b, co.FQ_OPS)
            m = half
        aggpk = tuple(c[0] for c in agg)
        aggpk_inf = co.FQ_OPS.is_zero(aggpk[2])
        bad = jnp.any(jnp.logical_and(aggpk_inf, set_mask != 0))

        sig_jac = co.affine_to_jac(
            co.FQ2_OPS, (sig_x, sig_y), inf_mask=jnp.logical_not(set_mask)
        )

        # ONE fused double-and-add loop for both scalings (z is 64 bits).
        # The bit stream rides a SHIFT REGISTER carried through the loop:
        # Mosaic cannot lower a dynamic lane index into the loaded zd value
        # (dynamic_slice — the first on-chip lowering failure), but static
        # slices, shifts and the pad-based lane bump are all fine. Pack the
        # 64 MSB-first bits into 4 16-bit limbs (little-endian limb order,
        # bit 0 of the stream at the MSB of the top limb), then each round
        # reads the top bit and shifts left by one.
        nbits = zd.shape[1]
        assert nbits % lb.LB == 0, (
            "shift-register packer needs LB-aligned bit counts (a partial "
            "top limb would be consumed as leading zero padding)"
        )
        nwz = nbits // lb.LB
        reg = None
        for j in range(nwz):                       # static unrolled pack
            base = nbits - (j + 1) * lb.LB
            limb = jnp.zeros(zd.shape[:1], jnp.uint32)
            for t in range(lb.LB):
                limb = limb + (zd[:, base + t] << (lb.LB - 1 - t))
            limb = limb[:, None]
            reg = limb if reg is None else lb.kconcat([reg, limb], axis=1)
        # reg: (n, nwz), limb nwz-1 holds the first bits to consume

        acc_pk = jax.tree_util.tree_map(
            lambda c, x: jnp.broadcast_to(c, x.shape), co.identity(co.FQ_OPS), aggpk
        )
        acc_sig = jax.tree_util.tree_map(
            lambda c, x: jnp.broadcast_to(c, x.shape), co.identity(co.FQ2_OPS), sig_jac
        )

        def step(_i, carry):
            reg, acc_pk, acc_sig = carry
            bit = (reg[:, nwz - 1] >> (lb.LB - 1)) == 1
            reg = ((reg << 1) & lb.MASK) + lb._shift_up_one(reg >> (lb.LB - 1))
            acc_pk = co.jac_double(acc_pk, co.FQ_OPS)
            acc_pk = co.pt_select(
                co.FQ_OPS, bit, co.jac_add(acc_pk, aggpk, co.FQ_OPS), acc_pk
            )
            acc_sig = co.jac_double(acc_sig, co.FQ2_OPS)
            acc_sig = co.pt_select(
                co.FQ2_OPS, bit, co.jac_add(acc_sig, sig_jac, co.FQ2_OPS), acc_sig
            )
            return reg, acc_pk, acc_sig

        _reg, z_pk, z_sig = lax.fori_loop(
            0, nbits, step, (reg, acc_pk, acc_sig)
        )

        z_sig = co.pt_select(
            co.FQ2_OPS,
            set_mask != 0,
            z_sig,
            tuple(
                jnp.broadcast_to(c, x.shape)
                for c, x in zip(co.identity(co.FQ2_OPS), z_sig)
            ),
        )
        sig_acc = co.tree_sum(z_sig, co.FQ2_OPS)

        zx_ref[...], zy_ref[...], zz_ref[...] = z_pk
        sx_ref[...], sy_ref[...], sz_ref[...] = sig_acc
        bad_ref[...] = lb.b2u(bad).reshape(1, 1)


def stage_prepare_fused(pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask,
                        *, interpret=False):
    """Drop-in for backend._stage_prepare via the fused kernel."""
    with jax.named_scope("jaxbls/prepare_fused"):
        return _stage_prepare_fused(
            pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask,
            interpret=interpret,
        )


def _stage_prepare_fused(pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask,
                         *, interpret=False):
    pl, pltpu = _pl()
    n = pk_x.shape[0]
    fq = jax.ShapeDtypeStruct((n, lb.NL), jnp.uint32)
    fq2 = jax.ShapeDtypeStruct((2, lb.NL), jnp.uint32)
    outs = (fq, fq, fq, fq2, fq2, fq2, jax.ShapeDtypeStruct((1, 1), jnp.uint32))
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    zx, zy, zz, sx, sy, sz, bad = pl.pallas_call(
        _prepare_kernel,
        out_shape=outs,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + _const_specs(pl, pltpu)
        + [vm] * 7,
        out_specs=(vm,) * 7,
        interpret=interpret,
    )(
        jnp.asarray(_PM2_BITS),
        *_const_inputs(),
        jnp.moveaxis(jnp.asarray(pk_x), 1, 0),      # (m, n, NL): see kernel
        jnp.moveaxis(jnp.asarray(pk_y), 1, 0),
        jnp.moveaxis(jnp.asarray(pk_mask, jnp.uint32), 1, 0),
        jnp.asarray(sig_x),
        jnp.asarray(sig_y),
        jnp.asarray(z_digits, jnp.uint32),
        jnp.asarray(set_mask, jnp.uint32).reshape(-1, 1),
    )
    return (zx, zy, zz), (sx, sy, sz), bad[0, 0] != 0


def _pairs_kernel(pbits_ref, *refs):
    """Fused stage 3: ONE batched Fermat inversion for every
    Jacobian->affine conversion. The generator/signature row appends happen
    in the WRAPPER (plain XLA): a ragged leading-axis concatenate is a vreg
    re-layout Mosaic rejects, and the appends are pure data movement."""
    from . import backend as be

    consts = refs[: _n_consts()]
    (zx_ref, zy_ref, zz_ref, hx_ref, hy_ref, hz_ref, sx_ref, sy_ref, sz_ref,
     sm_ref, px_ref, py_ref, qx_ref, qy_ref, pm_ref, sxo_ref, syo_ref,
     sinf_ref) = refs[_n_consts():]
    tab = _const_tab(consts)
    impls = {"POW_PM2": lambda a: _mont_pow_ref(a, pbits_ref)}
    with lb.pallas_mode(tab, impls):
        z_pk = (zx_ref[...], zy_ref[...], zz_ref[...])
        h_jac = (hx_ref[...], hy_ref[...], hz_ref[...])
        sig_acc = (sx_ref[...], sy_ref[...], sz_ref[...])
        set_mask = sm_ref[...][:, 0]

        (p1x, p1y, p1inf), (qx, qy, qinf), (sx, sy, sinf) = be._batched_affine(
            z_pk, h_jac, sig_acc
        )
        pair_mask = jnp.logical_and(
            set_mask != 0, jnp.logical_not(jnp.logical_or(p1inf, qinf))
        )
        px_ref[...] = p1x
        py_ref[...] = p1y
        qx_ref[...] = qx
        qy_ref[...] = qy
        pm_ref[...] = lb.b2u(pair_mask)[:, None]
        sxo_ref[...] = sx
        syo_ref[...] = sy
        sinf_ref[...] = lb.b2u(sinf).reshape(1, 1)


def _const_np(name: str):
    for n, a in _consts():
        if n == name:
            return a
    raise KeyError(name)


def stage_pairs_fused(z_pk, h_jac, sig_acc, set_mask, *, interpret=False):
    """Drop-in for backend._stage_pairs via the fused kernel."""
    with jax.named_scope("jaxbls/pairs_fused"):
        return _stage_pairs_fused(
            z_pk, h_jac, sig_acc, set_mask, interpret=interpret
        )


def _stage_pairs_fused(z_pk, h_jac, sig_acc, set_mask, *, interpret=False):
    pl, pltpu = _pl()
    n = z_pk[0].shape[0]
    fq1 = jax.ShapeDtypeStruct((n, lb.NL), jnp.uint32)
    fq2 = jax.ShapeDtypeStruct((n, 2, lb.NL), jnp.uint32)
    msk = jax.ShapeDtypeStruct((n, 1), jnp.uint32)
    sfq2 = jax.ShapeDtypeStruct((2, lb.NL), jnp.uint32)
    one = jax.ShapeDtypeStruct((1, 1), jnp.uint32)
    vm = pl.BlockSpec(memory_space=pltpu.VMEM)
    p1x, p1y, qx, qy, pm, sx, sy, sinf = pl.pallas_call(
        _pairs_kernel,
        out_shape=(fq1, fq1, fq2, fq2, msk, sfq2, sfq2, one),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + _const_specs(pl, pltpu)
        + [vm] * 10,
        out_specs=(vm,) * 8,
        interpret=interpret,
    )(
        jnp.asarray(_PM2_BITS),
        *_const_inputs(),
        *z_pk,
        *h_jac,
        *sig_acc,
        jnp.asarray(set_mask, jnp.uint32).reshape(-1, 1),
    )
    # row appends in XLA land (outside the kernel)
    px = jnp.concatenate([p1x, jnp.asarray(_const_np("NEG_G1X"))[None]])
    py = jnp.concatenate([p1y, jnp.asarray(_const_np("NEG_G1Y"))[None]])
    qxx = jnp.concatenate([qx, sx[None]])
    qyy = jnp.concatenate([qy, sy[None]])
    pair_mask = jnp.concatenate([pm[:, 0] != 0, sinf[0] == 0])
    return px, py, qxx, qyy, pair_mask


def _h2c_kernel(ebits_ref, xbits_ref, pbits_ref, *refs):
    """Fused hash-to-G2: Montgomery conversion, SSWU (incl. the 758-bit
    sqrt_ratio exponentiation), 3-isogeny, point add and psi cofactor
    clearing — one kernel launch for the whole batch."""
    from . import h2c_ops as h2

    consts = refs[: _n_consts()]
    us_ref, x_ref, y_ref, z_ref = refs[_n_consts() :]
    tab = _const_tab(consts)
    impls = {
        "POW_E": lambda a: _fq2_pow_ref(a, ebits_ref),
        ("scalar_mul_static", X_ABS): lambda p, ops: _scalar_mul_ref(p, ops, xbits_ref),
        # any inversion inside the map (mont_inv rides Fermat) must use the
        # SMEM-bits loop — the windowed fallback's table gather cannot lower
        "POW_PM2": lambda a: _mont_pow_ref(a, pbits_ref),
    }
    with lb.pallas_mode(tab, impls):
        us = lb.to_mont(us_ref[...])
        X, Y, Z = h2.map_to_g2(us[:, 0], us[:, 1])
        x_ref[...] = X
        y_ref[...] = Y
        z_ref[...] = Z


_H2C_BLOCK = 4          # sets per grid step (every bucket size is a
                        # multiple: MIN_SETS == 4, buckets are pow2)


def hash_to_g2_fused(us, *, interpret=False):
    """Drop-in for h2c_ops.hash_to_g2_jacobian via the fused kernel.
    us: (n, 2, 2, NL) standard-form u-values.

    Gridded over the set axis in _H2C_BLOCK chunks with a raised VMEM
    budget: the fused map's scoped-stack peak was measured at 31.8 MB for
    4 sets on a v5e against the 16 MB default limit (the 758-bit
    sqrt_ratio chain keeps many live Fq2 temporaries), so one big block
    would both OOM the stack and scale with n."""
    with jax.named_scope("jaxbls/h2c_fused"):
        return _hash_to_g2_fused(us, interpret=interpret)


def _hash_to_g2_fused(us, *, interpret=False):
    import math

    pl, pltpu = _pl()
    n = us.shape[0]
    blk = math.gcd(n, _H2C_BLOCK)   # any n works; pow2 buckets get 4
    out = jax.ShapeDtypeStruct((n, 2, lb.NL), jnp.uint32)
    out_spec = pl.BlockSpec((blk, 2, lb.NL), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _h2c_kernel,
        grid=(n // blk,),
        out_shape=(out, out, out),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
        + _const_specs(pl, pltpu)
        + [pl.BlockSpec((blk, 2, 2, lb.NL), lambda i: (i, 0, 0, 0))],
        out_specs=(out_spec, out_spec, out_spec),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        jnp.asarray(_e_bits_full()),
        jnp.asarray(_XABS_BITS_FULL),
        jnp.asarray(_PM2_BITS),
        *_const_inputs(),
        jnp.asarray(us),
    )
