"""The Pallas routing of the four staged programs, traced but not compiled.

LIGHTHOUSE_TPU_PALLAS is read at TRACE time inside each stage
(pallas_ops.mode()), so a broken routing, a fused stage whose outputs no
longer chain into the next stage, or a kernel body that no longer traces
shows here first — in about a minute a stage, where compiling the same
kernels in interpreter mode takes XLA:CPU 5-25 minutes each. These cases
stand in tier-1 for the four `slow` differential tests of
test_jaxbls_pallas.py (end-to-end, fused hash-to-G2, product check, odd
pair counts — the pairing stage has n + 1 = 5 pairs, an odd count); the
numbers are pinned per kernel there and in test_jaxbls_pallas_final_exp.py.
"""

import jax
import numpy as np
import pytest

from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.jaxbls import limbs as lb

N, M = 4, 2  # the smallest set bucket; 2 keys a set, as the end-to-end test has


def _limbs(*shape):
    return jax.ShapeDtypeStruct(shape + (lb.NL,), np.uint32)


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, np.uint32)


_G1 = (_limbs(N), _limbs(N), _limbs(N))            # (n,) jacobian G1
_G2 = (_limbs(N, 2), _limbs(N, 2), _limbs(N, 2))   # (n,) jacobian G2
_STAGES = {
    "prepare": (
        be._stage_prepare,
        (_limbs(N, M), _limbs(N, M), _u32(N, M), _limbs(N, 2), _limbs(N, 2),
         _u32(N, be.Z_DIGITS), _u32(N)),
    ),
    "h2c": (h2.hash_to_g2_jacobian, (_limbs(N, 2, 2),)),
    "pairs": (
        be._stage_pairs,
        (_G1, _G2, (_limbs(2), _limbs(2), _limbs(2)), _u32(N)),
    ),
    "pairing": (
        be._stage_pairing,
        (_limbs(N + 1), _limbs(N + 1), _limbs(N + 1, 2), _limbs(N + 1, 2),
         jax.ShapeDtypeStruct((N + 1,), np.bool_)),
    ),
}


def _primitives(jaxpr):
    """Names of every primitive in `jaxpr`, nested jaxprs included (a
    pallas_call's own kernel body excepted)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("stage", list(_STAGES))
def test_fused_stage_traces_to_the_xla_stage_avals(stage, monkeypatch):
    fn, args = _STAGES[stage]
    be._init_consts()
    traced = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("LIGHTHOUSE_TPU_PALLAS", mode)
        # a fresh function per mode: jax caches a trace by the identity of
        # the function and its avals, and knows nothing of the environment
        traced[mode] = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    assert "pallas_call" not in set(_primitives(traced["off"].jaxpr))
    assert "pallas_call" in set(_primitives(traced["interpret"].jaxpr))
    assert traced["interpret"].out_avals == traced["off"].out_avals
