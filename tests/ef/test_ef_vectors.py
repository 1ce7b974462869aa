"""EF consensus-spec-tests vector suite.

Runs every case under tests/ef/vectors through the handler-per-runner
dispatcher (testing/ef_runner.py — handler.rs analog), then asserts no
vector file was silently skipped (check_all_files_accessed.py analog).
Vectors are regression vectors generated by scripts/gen_ef_vectors.py (no
egress to fetch official tarballs); official vectors unpacked into the same
root run unchanged."""

from pathlib import Path

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.testing.ef_runner import VectorAccess, discover_cases, run_case

VECTOR_ROOT = Path(__file__).parent / "vectors"

CASES = list(discover_cases(VECTOR_ROOT)) if VECTOR_ROOT.exists() else []

_va = VectorAccess(VECTOR_ROOT)


@pytest.fixture(scope="module", autouse=True)
def _backend():
    """The pure-Python backend, with `hash_to_g2` memoised by (message, DST)
    while this module runs: the cases sign the same chains over and over
    (854 calls on 507 distinct messages at PR 47, a third of a second each,
    most of it the cofactor's 636-bit multiplication), and a point is a
    tuple of integers, safe to share. Keys and signatures already keep
    their decompressed points by their bytes (crypto/bls)."""
    import functools

    from lighthouse_tpu.crypto.bls381 import hash_to_curve as h2c

    bls.set_backend("python")
    real = h2c.hash_to_g2
    memo = functools.lru_cache(maxsize=None)(real)
    h2c.hash_to_g2 = lambda msg, dst: memo(bytes(msg), dst)
    yield
    h2c.hash_to_g2 = real


@pytest.mark.skipif(not CASES, reason="no vectors generated")
@pytest.mark.parametrize(
    "config,fork,runner,handler,case_dir",
    CASES,
    ids=[f"{c}/{f}/{r}/{h}/{d.name}" for c, f, r, h, d in CASES],
)
def test_ef_case(config, fork, runner, handler, case_dir):
    run_case(_va, config, fork, runner, handler, case_dir)


@pytest.mark.skipif(not CASES, reason="no vectors generated")
def test_all_vector_files_accessed():
    """Must run last (pytest preserves definition order within a module)."""
    _va.assert_all_files_accessed()
