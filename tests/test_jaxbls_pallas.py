"""Differential tests: Pallas-fused pairing kernels vs the plain XLA path.

Runs the fused kernels in Pallas interpreter mode on CPU (Mosaic compilation
needs the real chip; the interpreter executes the identical kernel trace), so
these tests pin the FUSED path — including the kernel-only internals routed
by limbs.pallas_mode (Kogge-Stone carries, shift-accumulate limb products) —
bit-exact to the XLA implementation that is itself pinned to the pure-Python
ground truth in test_jaxbls_pairing.py.

The Pallas path is off by default (LIGHTHOUSE_TPU_PALLAS=auto resolves to
plain XLA, and no benchmark cell turns it on), and one fused kernel takes
XLA:CPU 5-25 minutes to compile in interpreter mode. Tier-1 therefore
keeps the per-kernel pins of the pairing — the Miller loop here, the final
exponentiation in test_jaxbls_pallas_final_exp.py (a file each: they share
no program, so two workers share them) — and the routing of all four
stages, traced but not compiled, in test_jaxbls_pallas_stages.py. The
other four differential tests are `slow`.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381 import pairing as pp
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls import limbs as lb
from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
from lighthouse_tpu.crypto.jaxbls import pallas_ops as plo
from lighthouse_tpu.crypto.jaxbls import tower as tw

rng = random.Random(0x9A11A5)


def _rand_fq():
    from lighthouse_tpu.crypto.bls381.constants import P

    return rng.randrange(P)


def test_pallas_mode_mont_internals_bit_exact():
    """The kernel-body routings (Kogge-Stone carry, shift-accumulate poly
    mul) must agree with the default forms on random operands — checked
    directly, without Pallas plumbing."""
    from lighthouse_tpu.crypto.bls381.constants import P

    a_int = [_rand_fq() for _ in range(8)] + [0, P - 1, 1]
    b_int = [_rand_fq() for _ in range(8)] + [P - 1, P - 1, 1]
    a = jnp.asarray(lb.pack_batch(a_int))
    b = jnp.asarray(lb.pack_batch(b_int))

    base_mul = np.asarray(lb.mont_mul(a, b))
    base_add = np.asarray(lb.add_mod(a, b))
    base_sub = np.asarray(lb.sub_mod(a, b))
    with lb.pallas_mode():
        ks_mul = np.asarray(lb.mont_mul(a, b))
        ks_add = np.asarray(lb.add_mod(a, b))
        ks_sub = np.asarray(lb.sub_mod(a, b))
    assert (base_mul == ks_mul).all()
    assert (base_add == ks_add).all()
    assert (base_sub == ks_sub).all()


def _device_pairs(pairs, pad_to):
    n = len(pairs)
    mask = np.zeros(pad_to, bool)
    mask[:n] = True
    g1s = [p for p, _ in pairs] + [None] * (pad_to - n)
    g2s = [q for _, q in pairs] + [None] * (pad_to - n)
    xp = tw.fq_batch_to_device([p[0] if p else 0 for p in g1s])
    yp = tw.fq_batch_to_device([p[1] if p else 0 for p in g1s])
    xq = tw.fq2_batch_to_device([q[0] if q else (0, 0) for q in g2s])
    yq = tw.fq2_batch_to_device([q[1] if q else (0, 0) for q in g2s])
    return (xp, yp), (xq, yq), jnp.asarray(mask)


def _bilinear_pairs(pad_to):
    a = rng.randrange(1, R)
    b = rng.randrange(1, R)
    p1 = pc.g1_mul(pc.G1_GEN, a)
    q1 = pc.g2_mul(pc.G2_GEN, b)
    p2 = pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * b % R))
    return _device_pairs([(p1, q1), (p2, pc.G2_GEN)], pad_to)


@pytest.fixture(scope="module")
def fused_miller_2_pairs():
    """The fused Miller product, jitted and compiled here at the 2-pair
    shape: the compile alone is ~10 minutes under a loaded tier-1 run, and
    conftest's per-test limit is for tests that wait, not for compiles."""
    fused = jax.jit(
        lambda p, q, m: plo.miller_loop_product_fused(p, q, m, interpret=True)
    )
    jax.block_until_ready(fused(*_bilinear_pairs(2)))
    return fused


def test_fused_miller_loop_matches_xla(fused_miller_2_pairs):
    dp, dq, mask = _bilinear_pairs(2)
    want = np.asarray(jax.jit(po.miller_loop_product)(dp, dq, mask))
    got = np.asarray(fused_miller_2_pairs(dp, dq, mask))
    assert (want == got).all()


@pytest.mark.slow  # > 1600 s alone in a process (PR 25); see test_jaxbls_pallas_stages.py
def test_fused_hash_to_g2_matches_xla():
    """Fused SSWU/isogeny/cofactor kernel vs the plain XLA map, bit-exact
    Jacobian output on a 2-message batch."""
    from lighthouse_tpu.crypto.bls381.constants import DST_POP
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2

    us = h2.hash_to_field_batch([b"pallas-h2c-0", b"pallas-h2c-1"], DST_POP)

    def xla_path(u):
        return h2.map_to_g2(*(lambda m: (m[:, 0], m[:, 1]))(lb.to_mont(u)))

    want = jax.jit(xla_path)(us)
    got = jax.jit(lambda u: plo.hash_to_g2_fused(u, interpret=True))(us)
    for w, g in zip(want, got):
        assert (np.asarray(w) == np.asarray(g)).all()


@pytest.mark.slow  # 393 s alone in a process (PR 25); see test_jaxbls_pallas_stages.py
def test_all_fused_stages_end_to_end():
    """The COMPLETE staged verify pipeline (prepare, hash-to-G2, pairs,
    pairing — all four as Pallas kernels in interpreter mode) must agree
    with the XLA path through the public backend API, on valid and
    tampered batches.

    As written it does not reach the fused path (PR 25's compile log: four
    programs compiled, not eight): clearing `_kernel_cache` makes new jit
    objects, but jax caches a TRACE by the stage function's identity and
    avals, so the "interpret" pass reuses the "off" trace and this compares
    XLA with XLA. The routing it meant to cover is traced in
    test_jaxbls_pallas_stages.py; a fresh trace here (`jax.clear_caches()`
    between the modes) costs a second, fused build that nobody has timed."""
    import os

    from lighthouse_tpu.crypto import bls
    import lighthouse_tpu.crypto.jaxbls.backend as jb

    sks = [bls.SecretKey(1000 + i) for i in range(4)]
    pks = [sk.public_key() for sk in sks]
    m0 = b"\x11" * 32
    m1 = b"\x22" * 32
    agg0 = bls.AggregateSignature.aggregate([bls.sign(sks[0], m0), bls.sign(sks[1], m0)])
    agg1 = bls.AggregateSignature.aggregate([bls.sign(sks[2], m1), bls.sign(sks[3], m1)])
    sets = [
        bls.SignatureSet(agg0, pks[0:2], m0),
        bls.SignatureSet(agg1, pks[2:4], m1),
    ]
    bad_sets = [bls.SignatureSet(agg0, pks[0:2], m1), sets[1]]  # wrong message
    rands = [1, (0x9E3779B9 << 1) | 1]

    backend = bls.set_backend("jax")
    prev = os.environ.get("LIGHTHOUSE_TPU_PALLAS")
    results = {}
    try:
        for pl_mode in ("off", "interpret"):
            os.environ["LIGHTHOUSE_TPU_PALLAS"] = pl_mode
            jb._kernel_cache.clear()          # force a fresh trace per mode
            results[pl_mode] = (
                backend.verify_signature_sets(sets, rands),
                backend.verify_signature_sets(bad_sets, rands),
            )
    finally:
        if prev is None:
            os.environ.pop("LIGHTHOUSE_TPU_PALLAS", None)
        else:
            os.environ["LIGHTHOUSE_TPU_PALLAS"] = prev
        jb._kernel_cache.clear()

    assert results["off"] == (True, False), f"XLA path wrong: {results['off']}"
    assert results["interpret"] == (True, False), (
        f"fused path wrong: {results['interpret']}"
    )


@pytest.mark.slow  # 1123 s alone in a process (PR 25); see test_jaxbls_pallas_stages.py
def test_fused_product_check_accepts_and_rejects():
    check = jax.jit(
        lambda p, q, m: plo.pairing_product_is_one_fused(p, q, m, interpret=True)
    )
    dp, dq, mask = _bilinear_pairs(4)        # padded lanes must contribute 1
    assert bool(check(dp, dq, mask))

    a = rng.randrange(1, R)
    p1 = pc.g1_mul(pc.G1_GEN, a)
    q1 = pc.g2_mul(pc.G2_GEN, 7)
    p2 = pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * 8 % R))    # wrong scalar
    dp, dq, mask = _device_pairs([(p1, q1), (p2, pc.G2_GEN)], 4)
    assert not bool(check(dp, dq, mask))


@pytest.mark.slow  # > 1500 s alone in a process (PR 25); see test_jaxbls_pallas_stages.py
def test_fused_miller_odd_pair_count():
    """Odd pair counts exercise the line-combine tree's odd-padding and
    fq12_product_any's carry lane — masked and unmasked."""
    a = rng.randrange(1, R)
    b = rng.randrange(1, R)
    pairs = [
        (pc.g1_mul(pc.G1_GEN, a), pc.g2_mul(pc.G2_GEN, b)),
        (pc.g1_neg(pc.g1_mul(pc.G1_GEN, a * b % R)), pc.G2_GEN),
        (pc.g1_mul(pc.G1_GEN, 7), pc.g2_mul(pc.G2_GEN, 9)),
    ]
    xp = tw.fq_batch_to_device([p[0] for p, _ in pairs])
    yp = tw.fq_batch_to_device([p[1] for p, _ in pairs])
    xq = tw.fq2_batch_to_device([q[0] for _, q in pairs])
    yq = tw.fq2_batch_to_device([q[1] for _, q in pairs])
    for mask in ([True, True, False], [True, True, True]):
        m = jnp.asarray(np.array(mask))
        want = np.asarray(jax.jit(po.miller_loop_product)((xp, yp), (xq, yq), m))
        got = np.asarray(
            jax.jit(
                lambda p, q, mm: plo.miller_loop_product_fused(p, q, mm, interpret=True)
            )((xp, yp), (xq, yq), m)
        )
        assert (want == got).all(), f"odd-pair mismatch mask={mask}"
