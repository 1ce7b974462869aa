"""Multi-chip sharding tests on the virtual 8-device CPU mesh (conftest).

The framework's scaling story (SURVEY.md §5): signature sets are
data-parallel over a `sets` mesh axis; the cross-set pair-product and
signature tree-sum become XLA collectives. These tests prove the sharded
program (a) compiles and runs over 8 devices, (b) agrees bit-for-bit with
the unsharded single-device program, and (c) agrees with the pure-Python
backend on valid AND invalid batches.

This is one of the two modules that drive the real JaxBackend through its
four staged programs (the other is test_jaxbls_backend.py). Each compiles
its programs once, in one module-scoped warm-up (tests/jaxbls_warm.py): a
new test of the staged backend joins one of the two instead of opening a
file, and keeps to the builds and key-count buckets its module warms.
"""

import random

import numpy as np
import jax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as Pspec

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import R


N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    if len(devices) < N_DEV:
        pytest.skip(f"needs {N_DEV} virtual devices, got {len(devices)}")
    return Mesh(np.array(devices[:N_DEV]), ("sets",))


def _build_sets(n_sets: int, n_pks: int, seed: int, tamper: int | None = None):
    """n_sets aggregate sets; if tamper is an index, that set's signature is
    signed over a different message (invalid)."""
    rng = random.Random(seed)
    sets = []
    for i in range(n_sets):
        sks = [rng.randrange(1, R) for _ in range(n_pks)]
        pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
        msg = i.to_bytes(32, "big")
        signed = (i + 1).to_bytes(32, "big") if tamper == i else msg
        h = bls_api.hash_to_g2_point(signed)
        sig = bls.Signature(cv.g2_mul(h, sum(sks) % R))
        sets.append(bls.SignatureSet(sig, pks, msg))
    rands = [1] + [rng.getrandbits(64) | 1 for _ in range(n_sets - 1)]
    return sets, rands


def _marshal(backend, sets, rands):
    """Reuse the backend's own wire-format marshalling, returning host arrays."""
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import limbs as lb, curve_ops as co, h2c_ops as h2

    n_real = len(sets)
    n = max(be.MIN_SETS, 1 << (n_real - 1).bit_length())
    m = max(len(s.signing_keys) for s in sets)
    m = max(be.MIN_PKS, 1 << (m - 1).bit_length())

    pk_x = np.zeros((n, m, lb.NL), np.uint32)
    pk_y = np.zeros((n, m, lb.NL), np.uint32)
    pk_mask = np.zeros((n, m), np.uint32)
    sig_x = np.zeros((n, 2, lb.NL), np.uint32)
    sig_y = np.zeros((n, 2, lb.NL), np.uint32)
    z_digits = np.zeros((n, be.Z_BITS), np.uint32)
    set_mask = np.zeros((n,), np.uint32)
    us = np.zeros((n, 2, 2, lb.NL), np.uint32)

    for i, s in enumerate(sets):
        keys = s.signing_keys
        pk_x[i, : len(keys)] = be.pack_ints_vec([pk.point[0] for pk in keys])
        pk_y[i, : len(keys)] = be.pack_ints_vec([pk.point[1] for pk in keys])
        pk_mask[i, : len(keys)] = 1
        sp = s.signature.point
        sig_x[i, 0] = be.pack_ints_vec([sp[0][0]])[0]
        sig_x[i, 1] = be.pack_ints_vec([sp[0][1]])[0]
        sig_y[i, 0] = be.pack_ints_vec([sp[1][0]])[0]
        sig_y[i, 1] = be.pack_ints_vec([sp[1][1]])[0]
    zmask = (1 << 64) - 1
    z_digits[:n_real] = co.scalars_to_bits(
        [z & zmask for z in rands], be.Z_BITS)
    set_mask[:n_real] = 1
    us[:n_real] = h2.hash_to_field_batch([s.message for s in sets], backend.dst)
    return (pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask)


@pytest.fixture(scope="module")
def jax_backend():
    return bls_api.set_backend("jax")


@pytest.fixture(scope="module", autouse=True)
def _warm_stages_parallel():
    """Cold-compile the module's two builds side by side before the tests
    run (tests/jaxbls_warm.py): 8 sets of 2 keys (one key-count bucket,
    m = 2) over the 8-device mesh, and the same unsharded — what the
    sharded result is compared with. Every test after it is seconds."""
    from jaxbls_warm import warm_builds

    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    live = parallel.get_mesh()
    assert live is not None and int(live.devices.size) == N_DEV
    warm_builds((8, (2,), live), (8, (2,), None))


def _run_staged(args, mesh=None):
    """The production staged pipeline; with a mesh, every input is sharded
    along the sets axis (collectives cross shards in the reductions)."""
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2

    be._init_consts()
    pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = args
    if mesh is not None:
        def shard(a):
            return jax.device_put(
                a, NamedSharding(mesh, Pspec("sets", *([None] * (a.ndim - 1))))
            )
        pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask = (
            shard(a) for a in (pk_x, pk_y, pk_mask, sig_x, sig_y, us, z_digits, set_mask)
        )
    prepare, h2c_stage, pairs_stage, pairing_stage = be._get_stages()
    z_pk, sig_acc, bad = prepare(
        pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask
    )
    h_jac = h2c_stage(us)
    px, py, qxx, qyy, pair_mask = pairs_stage(z_pk, h_jac, sig_acc, set_mask)
    ok = pairing_stage(px, py, qxx, qyy, pair_mask)
    return bool(np.asarray(ok)) and not bool(np.asarray(bad))


def _run_sharded(mesh, args):
    return _run_staged(args, mesh=mesh)


def test_sharded_valid_batch_verifies(mesh, jax_backend):
    sets, rands = _build_sets(8, 2, seed=0x51)
    args = _marshal(jax_backend, sets, rands)
    assert _run_sharded(mesh, args) is True
    # python ground truth agrees
    py = bls_api._BACKENDS["python"]
    assert py.verify_signature_sets(sets, rands) is True


def test_sharded_invalid_batch_rejects(mesh, jax_backend):
    sets, rands = _build_sets(8, 2, seed=0x52, tamper=5)
    args = _marshal(jax_backend, sets, rands)
    assert _run_sharded(mesh, args) is False
    py = bls_api._BACKENDS["python"]
    assert py.verify_signature_sets(sets, rands) is False


def test_sharded_matches_unsharded_bit_identical(mesh, jax_backend):
    sets, rands = _build_sets(8, 2, seed=0x53)
    args = _marshal(jax_backend, sets, rands)

    unsharded = _run_staged(args, mesh=None)
    sharded = _run_sharded(mesh, args)
    assert sharded == unsharded == True  # noqa: E712


# --------------------------------------------------------- backend path
# The production JaxBackend discovers the mesh itself (parallel/mesh.py):
# verify_signature_sets(_async) is the SAME call sites the chain uses.


def test_backend_dispatch_uses_mesh(jax_backend):
    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    m = parallel.get_mesh()
    assert m is not None and m.devices.size == N_DEV

    sets, rands = _build_sets(8, 2, seed=0x54)
    assert jax_backend.verify_signature_sets(sets, rands) is True
    bad, bad_rands = _build_sets(8, 2, seed=0x55, tamper=3)
    assert jax_backend.verify_signature_sets(bad, bad_rands) is False
    # async path too (what the beacon processor drives)
    h = jax_backend.verify_signature_sets_async(sets, rands)
    assert h.result() is True


def test_backend_mesh_agrees_with_single_device(jax_backend, monkeypatch):
    from lighthouse_tpu import parallel

    sets, rands = _build_sets(8, 2, seed=0x56)
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "0")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is None
    single = jax_backend.verify_signature_sets(sets, rands)
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "1")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is not None
    meshed = jax_backend.verify_signature_sets(sets, rands)
    parallel.reset_mesh_cache()
    assert single == meshed == True  # noqa: E712


def test_module_stays_under_the_mapping_mark():
    """Last on purpose: with every build of this module compiled and kept,
    the process must be under conftest's mark — past it conftest drops the
    executables between tests and each later test recompiles for minutes.
    A module that outgrows the mark is split, not left to thrash."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK
