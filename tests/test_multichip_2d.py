"""The 2-D (sets, pks) mesh on the virtual 8-device CPU platform (conftest).

The 1-D `sets` mesh is test_multichip.py's; the 2-D mesh shares no
compiled program with it, so it has this file (and a worker) to itself.
"""

import random

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R

from test_multichip import N_DEV, _build_sets, jax_backend  # noqa: F401


@pytest.fixture(scope="module", autouse=True)
def _warm_stages_parallel():
    """The one build the test dispatches — 4 sets, 8 keys, over the
    (sets 4, pks 2) mesh — compiled with prepare and hash-to-G2 side by
    side (tests/jaxbls_warm.py)."""
    from jaxbls_warm import warm_builds

    from lighthouse_tpu import parallel

    mp = pytest.MonkeyPatch()
    mp.setenv("LIGHTHOUSE_TPU_PK_SHARDS", "2")
    parallel.reset_mesh_cache()
    try:
        warm_builds((4, (8,), parallel.get_mesh()))
    finally:
        mp.undo()
        parallel.reset_mesh_cache()


def test_backend_2d_mesh_wide_aggregation(jax_backend, monkeypatch):
    """2-D (sets, pks) mesh: WITHIN-SET parallelism — the pubkey axis of a
    wide aggregation (the 512-pk sync-committee shape, scaled down) is
    sharded too, so the per-set point tree spreads across chips and its
    reduction lowers to collectives over the pks axis (SURVEY §5's
    bucket-parallel-within-a-set requirement). This lane owns the 2-D
    coverage: the driver's dryrun_multichip gate runs the 1-D production
    path only (the 2-D re-trace doubled cold-compile wall and timed out
    the r4 gate)."""
    from lighthouse_tpu import parallel

    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", "2")
    parallel.reset_mesh_cache()
    try:
        mesh2 = parallel.get_mesh()
        assert mesh2 is not None and parallel.mesh.PK_AXIS in mesh2.axis_names
        assert dict(mesh2.shape) == {"sets": N_DEV // 2, "pks": 2}

        rng = random.Random(0x2D)
        big_sks = [rng.randrange(1, R) for _ in range(8)]
        big_pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in big_sks]
        msg = b"\x2d" * 32
        h = bls_api.hash_to_g2_point(msg)
        big_sig = bls.Signature(cv.g2_mul(h, sum(big_sks) % R))
        small_sets, rands = _build_sets(3, 2, seed=0x57)
        big_sets = [bls.SignatureSet(big_sig, big_pks, msg)] + small_sets
        big_rands = [1] + rands
        assert jax_backend.verify_signature_sets(big_sets, big_rands) is True
        # a tampered wide set must reject through the same 2-D path
        wrong = bls.Signature(cv.g2_mul(h, (sum(big_sks) + 1) % R))
        bad_sets = [bls.SignatureSet(wrong, big_pks, msg)] + small_sets
        assert jax_backend.verify_signature_sets(bad_sets, big_rands) is False
        py = bls_api._BACKENDS["python"]
        assert py.verify_signature_sets(big_sets, big_rands) is True
        assert py.verify_signature_sets(bad_sets, big_rands) is False
    finally:
        parallel.reset_mesh_cache()
