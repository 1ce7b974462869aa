"""Differential tests: the parts of device hash-to-G2 vs pure-Python ground
truth (which is itself pinned by the RFC 9380 J.10.1 vector). The whole
program against `hash_to_g2`, message by message, is
test_jaxbls_backend.py::test_hash_to_g2_matches_python, on the urgent
lane's compiled four lanes (here it compiled three of its own until PR 47)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381 import fields as pyf
from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
from lighthouse_tpu.crypto.bls381.constants import P
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.jaxbls import tower as tw


def test_sqrt_exponent_splits_over_the_frobenius():
    """Host only: E = e1*p + e0 with both halves below p, so that
    a^E = conj(a)^e1 * a^e0 is the same element of Fq2."""
    assert h2._E1 * P + h2._E0 == (P * P - 9) // 16 == h2._E
    assert 0 < h2._E1 < P and 0 < h2._E0 < P
    a = (0x1234567, 0x89ABCDE)
    conj_a = (a[0], (-a[1]) % P)
    assert pyf.fq2_pow(a, P) == conj_a
    assert pyf.fq2_pow(a, h2._E) == pyf.fq2_mul(
        pyf.fq2_pow(conj_a, h2._E1), pyf.fq2_pow(a, h2._E0))


def _joint_power_inputs():
    import random

    rng = random.Random(0xE1E0)
    return {
        "random0": (rng.randrange(P), rng.randrange(P)),
        "random1": (rng.randrange(P), rng.randrange(P)),
        "zero": (0, 0),
        "one": (1, 0),
        "real": (rng.randrange(2, P), 0),           # conj(a) == a
        "imaginary": (0, rng.randrange(2, P)),      # conj(a) == -a
    }


@pytest.fixture(scope="module")
def joint_power():
    return jax.jit(lambda a: h2.fq2_pow_frobenius(a, h2._E1, h2._E0))


@pytest.mark.parametrize("a", list(_joint_power_inputs().values()),
                         ids=list(_joint_power_inputs()))
def test_joint_power_matches_python(joint_power, a):
    """The square root's exponentiation, conj(a)^e1 * a^e0 in one joint
    chain, against the pure-Python tower's a^E: a conjugate on the wrong
    factor hides on a real element and shows on every other."""
    got = tw.fq2_from_device(joint_power(tw.fq2_to_device(a)))
    assert got == pyf.fq2_pow(a, h2._E)


def test_sqrt_ratio_qr_and_nqr():
    import random

    rng = random.Random(0x5157)
    sq = jax.jit(h2.fq2_sqrt_ratio)
    for _ in range(2):
        u = (rng.randrange(P), rng.randrange(P))
        v = (rng.randrange(1, P), rng.randrange(P))
        du, dv = tw.fq2_to_device(u), tw.fq2_to_device(v)
        is_qr, y = sq(du, dv)
        yy = pyf.fq2_sqr(tw.fq2_from_device(y))
        ratio = pyf.fq2_mul(u, pyf.fq2_inv(v))
        if bool(is_qr):
            assert yy == ratio
        else:
            assert yy == pyf.fq2_mul(ph2c.ISO_Z, ratio)


def test_sswu_matches_python():
    import random

    rng = random.Random(0x55)
    us = [(rng.randrange(P), rng.randrange(P)) for _ in range(4)]
    dus = jnp.asarray(np.stack([np.asarray(tw.fq2_to_device(u)) for u in us]))
    xn, xd, y = jax.jit(h2.sswu_projective)(dus)
    for i, u in enumerate(us):
        exp_x, exp_y = ph2c.sswu(u)
        got_xn = tw.fq2_from_device(xn[i])
        got_xd = tw.fq2_from_device(xd[i])
        got_y = tw.fq2_from_device(y[i])
        assert pyf.fq2_mul(got_xn, pyf.fq2_inv(got_xd)) == exp_x
        assert got_y == exp_y
