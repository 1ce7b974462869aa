#!/usr/bin/env python3
"""Makes the sidecar pools of the cell `kzg_6_blobs` (run by hand; the npz
files are committed so that no later PR changes the yardstick's data):

    python benchmarks/data/gen_blob_pool.py            # both pools

  data/blob_pool_6.npz      48 sidecars of 4,096 field elements (mainnet)
  data/blob_pool_smoke.npz  12 sidecars of 64 (the rehearsal on the CPU)

A pool holds no blob: `meta` has the blobs' generator seed, and sidecar i's
blob is `make_blob(blob_seed, i, n)` of the driver, regenerated to bytes at
set-up (131,072 bytes each at mainnet size; 48 of them would be 6 MB of
noise in git). What it holds is what costs: each sidecar's 48-byte
commitment and 48-byte proof.

They are minted with tau, which the insecure dev setup makes public
(`TrustedSetup.dev_setup_scalars`): with p the blob's polynomial,
commitment = p(tau) G1, and with z the Fiat-Shamir challenge and y = p(z),
proof = ((p(tau) - y) / (tau - z)) G1 — two host scalar multiplications a
blob where the Lagrange-basis MSMs of a real prover are 4,096 terms each.
The bytes are the same the MSMs would give: the commitment and proof of a
polynomial are unique. The generator checks that on the smoke pool, against
`blob_to_kzg_commitment` / `compute_blob_kzg_proof` on the full dev setup.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np

DATA_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(DATA_DIR)
REPO_ROOT = os.path.dirname(BENCH_DIR)
for _p in (REPO_ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

POOLS = (
    # file, field elements a blob, sidecars, the blobs' generator seed
    ("blob_pool_6.npz", 4096, 48, 33_004_096),
    ("blob_pool_smoke.npz", 64, 12, 33_000_064),
)


def _driver():
    path = os.path.join(BENCH_DIR, "drivers", "kzg_blob_loop.py")
    spec = importlib.util.spec_from_file_location("kzg_blob_loop", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mint(blob: bytes, setup, tau: int) -> tuple:
    """(commitment bytes, proof bytes) of `blob` from the known tau."""
    from lighthouse_tpu.crypto import kzg
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381 import serde
    from lighthouse_tpu.crypto.bls381.constants import R

    poly = kzg.blob_to_polynomial(blob, setup)
    p_tau = kzg._evaluate_polynomial_in_evaluation_form(poly, tau, setup)
    commitment = serde.g1_compress(cv.g1_mul(cv.G1_GEN, p_tau))
    z = kzg.compute_challenge(blob, commitment, setup)
    y = kzg._evaluate_polynomial_in_evaluation_form(poly, z, setup)
    q_tau = (p_tau - y) * pow(tau - z, -1, R) % R
    return commitment, serde.g1_compress(cv.g1_mul(cv.G1_GEN, q_tau))


def make_pool(name: str, n: int, sidecars: int, blob_seed: int) -> None:
    from lighthouse_tpu.crypto import kzg

    make_blob = _driver().make_blob
    setup = kzg.TrustedSetup.dev_verifier_setup(n)
    _lis, tau = kzg.TrustedSetup.dev_setup_scalars(1)
    pairs = [mint(make_blob(blob_seed, i, n), setup, tau)
             for i in range(sidecars)]
    if n <= 64:
        full = kzg.TrustedSetup.insecure_dev_setup(n)
        from lighthouse_tpu.crypto.bls381 import serde

        blob = make_blob(blob_seed, 0, n)
        c = serde.g1_compress(kzg.blob_to_kzg_commitment(blob, full))
        p = serde.g1_compress(kzg.compute_blob_kzg_proof(blob, c, full))
        assert (c, p) == pairs[0], "minting disagrees with the prover's MSMs"
    meta = {"field_elements_per_blob": n, "sidecars": sidecars,
            "blob_seed": blob_seed,
            "setup": "TrustedSetup.dev_setup_scalars (insecure: tau is public)",
            "made_by": "benchmarks/data/gen_blob_pool.py"}
    np.savez(
        os.path.join(DATA_DIR, name),
        meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
        commitments=np.frombuffer(b"".join(c for c, _ in pairs),
                                  np.uint8).reshape(sidecars, 48),
        proofs=np.frombuffer(b"".join(p for _, p in pairs),
                             np.uint8).reshape(sidecars, 48),
    )
    print(f"{name}: {sidecars} sidecars of {n} field elements")


if __name__ == "__main__":
    for pool in POOLS:
        make_pool(*pool)
