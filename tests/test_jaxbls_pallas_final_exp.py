"""Differential test: the Pallas-fused final exponentiation vs pure Python.

One of the two per-kernel pins of the fused pairing that tier-1 keeps (the
other, the Miller loop, is test_jaxbls_pallas.py; see there for the rest of
the Pallas lane). A file of its own: the two share no program, and each
compile is minutes of XLA:CPU in interpreter mode, so two workers share
them.
"""

import random

import jax

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.bls381 import pairing as pp
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls import pallas_ops as plo
from lighthouse_tpu.crypto.jaxbls import tower as tw

rng = random.Random(0x9A11A5)


def test_fused_final_exp_matches_python():
    p = pc.g1_mul(pc.G1_GEN, rng.randrange(1, R))
    q = pc.g2_mul(pc.G2_GEN, rng.randrange(1, R))
    m = pp.miller_loop([(p, q)])
    dm = tw.fq12_to_device(m)
    got = tw.fq12_from_device(
        jax.jit(lambda x: plo.final_exponentiation_fused(x, interpret=True))(dm)
    )
    assert got == pp.final_exponentiation(m)
