#!/usr/bin/env python
"""Generate persisted bench fixtures: bench_fixtures.npz (+ _smoke variant).

Run OFFLINE, once, on any platform (local CPU is fine) — bench.py only
LOADS the npz at measurement time. Generating them on the device (pubkey gen + signature-gen compile) once
spent a whole device session before the verify pipeline ever warmed;
persisting the fixtures means zero fixture kernels compile inside a chip
call and the measured region starts minutes earlier.

Contents (all big-endian 48-byte field elements, uint8 arrays):
  att:   128 DISTINCT attestation-style sets, 128 pubkeys each, distinct
         messages (fixes the r4 att_sets_alt double-count — same-keys+
         same-messages sets let the pubkey marshal cache and repeated
         hash-to-field inputs make config 2 easier than a real block)
  small: 2 single-pubkey sets (the proposal + RANDAO roles in config 2)
  sync:  1 set x 512 pubkeys (config 3, the Altair sync aggregate)
  kzg:   4096-entry insecure dev setup, 6 blobs + commitments + proofs
         (config 4) — reference workload /root/reference/crypto/kzg/src/lib.rs:81

Validation at gen time: every BLS set and the KZG batch verify through the
pure-Python backend — fully independent of the jax kernels (which bench.py
re-asserts on-device at measurement time, with negative controls); one
tampered set must reject.
"""

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

SEED = 0xF1C7


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _be48(x: int) -> bytes:
    return int(x).to_bytes(48, "big")


def _g1_arr(points) -> np.ndarray:
    """[(x, y)] -> (n, 2, 48) uint8."""
    return np.frombuffer(
        b"".join(_be48(p[0]) + _be48(p[1]) for p in points), np.uint8
    ).reshape(len(points), 2, 48)


def _g2_arr(points) -> np.ndarray:
    """[((x0,x1),(y0,y1))] -> (n, 2, 2, 48) uint8."""
    return np.frombuffer(
        b"".join(
            _be48(p[0][0]) + _be48(p[0][1]) + _be48(p[1][0]) + _be48(p[1][1])
            for p in points
        ),
        np.uint8,
    ).reshape(len(points), 2, 2, 48)


# ------------------------------------------------------- host fast builders
# Generation-time only. The single-core build box makes device batch
# kernels the SLOW path for one-off generation (each 4096-point device MSM
# costs ~30-40 min of XLA:CPU runtime); host math with a fixed-base window
# table for G generates the SAME group elements in minutes. None of this
# affects what the bench measures — verification kernels are data-
# independent (constant shapes, constant-time limb math), so how the
# fixture points were produced cannot change their verification cost.


def _g1_gen_tables(window: int = 8):
    """tables[j][v] = (v << (window*j)) * G as host affine points: any
    256-bit fixed-base mul becomes <= 32 point additions."""
    from lighthouse_tpu.crypto.bls381 import curve as cv

    tables = []
    base = cv.G1_GEN
    for _j in range(256 // window):
        row = [None] * (1 << window)
        acc = None
        for v in range(1, 1 << window):
            acc = cv.g1_add(acc, base)
            row[v] = acc
        tables.append(row)
        base = cv.g1_mul(base, 1 << window)
    return tables


def _g1_fixed_mul(tables, k: int, window: int = 8):
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381.constants import R

    k %= R
    acc = None
    j = 0
    while k:
        v = k & ((1 << window) - 1)
        if v:
            acc = cv.g1_add(acc, tables[j][v])
        k >>= window
        j += 1
    return acc


def host_base_muls(scalars):
    """scalars -> affine G1 points via the window table (~2 ms each)."""
    tables = _g1_gen_tables()
    return [_g1_fixed_mul(tables, s) for s in scalars]


def _msg(i, tag=0):
    return bytes([tag]) + i.to_bytes(31, "big")


def build_groups(rng, groups):
    """groups: [(n_pks, message)] -> (keys_per_group, sig_points, messages).

    Valid aggregate signatures over distinct keys, generated host-side via
    the fixed-base window table (see the note above — the point VALUES
    don't influence the verification kernels' cost)."""
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
    from lighthouse_tpu.crypto.bls381.constants import DST_POP, R

    n_keys = sum(g[0] for g in groups)
    sks = [rng.randrange(1, R) for _ in range(n_keys)]
    t0 = time.time()
    pts = host_base_muls(sks)
    log(f"  pubkey gen x{n_keys} (host window table): {time.time()-t0:.1f}s")

    t0 = time.time()
    agg_sks, hs = [], []
    off = 0
    for n_pks, msg in groups:
        agg_sks.append(sum(sks[off : off + n_pks]) % R)
        hs.append(ph2c.hash_to_g2(msg, DST_POP))
        off += n_pks
    log(f"  hash-to-g2 x{len(groups)} (host): {time.time()-t0:.1f}s")

    t0 = time.time()
    sig_pts = [cv.g2_mul(h_pt, sk) for h_pt, sk in zip(hs, agg_sks)]
    log(f"  signature gen x{len(groups)} (host): {time.time()-t0:.1f}s")

    keys, off = [], 0
    for n_pks, _msg_ in groups:
        keys.append(pts[off : off + n_pks])
        off += n_pks
    return keys, sig_pts, [g[1] for g in groups]


def gen_kzg(rng, n, n_blobs):
    """KZG fixture via the dev setup's KNOWN tau: commit(p) = p(tau)*G and
    proof(q) = q(tau)*G are single fixed-base muls producing EXACTLY the
    group elements the Lagrange-basis MSM would (commitment math is linear
    in the basis) — generation drops from hours of single-core MSM runtime
    to seconds, and the batch verifier (real pairing + challenge math)
    still checks the result below. NEVER valid for production (tau secret);
    the dev setup is already marked insecure for the same reason."""
    from lighthouse_tpu.crypto import kzg
    from lighthouse_tpu.crypto.bls381 import curve as cv, serde
    from lighthouse_tpu.crypto.bls381.constants import R

    t0 = time.time()
    lis, tau = kzg.TrustedSetup.dev_setup_scalars(n)
    g1 = host_base_muls(lis)
    g2m = [cv.G2_GEN, cv.g2_mul(cv.G2_GEN, tau)]
    setup = kzg.TrustedSetup(
        g1_lagrange=g1, g2_monomial=g2m, roots=kzg._fr_roots_of_unity(n)
    )
    log(f"  kzg setup build (n={n}, host): {time.time()-t0:.1f}s")

    t0 = time.time()
    tables = _g1_gen_tables()
    blobs, cbs, pbs = [], [], []
    for _ in range(n_blobs):
        blob = b"".join(rng.randrange(R).to_bytes(32, "big") for _ in range(n))
        poly = kzg.blob_to_polynomial(blob, setup)
        p_tau = kzg._evaluate_polynomial_in_evaluation_form(poly, tau, setup)
        c = _g1_fixed_mul(tables, p_tau)
        cb = serde.g1_compress(c)
        # the blob proof's challenge point, then q(tau) = (p(tau)-y)/(tau-z)
        z = kzg.compute_challenge(blob, cb, setup)
        y = kzg._evaluate_polynomial_in_evaluation_form(poly, z, setup)
        q_tau = (p_tau - y) * pow((tau - z) % R, R - 2, R) % R
        proof = _g1_fixed_mul(tables, q_tau)
        blobs.append(blob)
        cbs.append(cb)
        pbs.append(serde.g1_compress(proof))
    log(f"  kzg blob/proof fixture x{n_blobs} (host, tau form): "
        f"{time.time()-t0:.1f}s")
    assert kzg.verify_blob_kzg_proof_batch(blobs, cbs, pbs, setup), (
        "kzg fixture failed to verify"
    )
    return g1, g2m, blobs, cbs, pbs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes variant")
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--n-att", type=int, default=512,
        help="distinct attestation-style sets (headline batches all of "
        "them; config 2 always takes the first 128 for its 131-set block)",
    )
    args = ap.parse_args()

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import api as bls_api

    if args.smoke:
        n_att, n_pks, sync_pks, kzg_n, kzg_blobs = 4, 4, 8, 8, 2
        out = args.out or "bench_fixtures_smoke.npz"
    else:
        n_att, n_pks, sync_pks, kzg_n, kzg_blobs = args.n_att, 128, 512, 4096, 6
        out = args.out or "bench_fixtures.npz"

    rng = random.Random(SEED)
    # generation AND validation are host-side: the pure-Python backend is
    # independent of every jax kernel and fast at these sizes
    bls_api.set_backend("python")

    groups = (
        [(n_pks, _msg(i)) for i in range(n_att)]
        + [(1, _msg(0, tag=1)), (1, _msg(1, tag=1))]
        + [(sync_pks, _msg(0, tag=3))]
    )
    log(f"building {len(groups)} signature groups "
        f"({sum(g[0] for g in groups)} keys)")
    keys, sigs, msgs = build_groups(rng, groups)

    # EVERY set verifies through the pure-Python backend — independent of
    # all jax kernels (bench.py re-asserts on-device verification, with a
    # negative control, at measurement time); a tampered set must reject
    sets = [
        bls.SignatureSet(bls.Signature(sp), [bls.PublicKey(p) for p in ks], m)
        for ks, sp, m in zip(keys, sigs, msgs)
    ]
    py = bls_api.set_backend("python")
    t0 = time.time()
    rands = [1] + [rng.getrandbits(64) | 1 for _ in sets[1:]]
    assert py.verify_signature_sets(sets, rands), "python backend disagrees"
    bad = bls.SignatureSet(sets[1].signature, sets[0].signing_keys, sets[0].message)
    assert not py.verify_signature_sets([bad], [1]), "tampered set accepted"
    log(f"  python-backend verification of ALL {len(sets)} sets: "
        f"{time.time()-t0:.1f}s")

    kzg_g1, kzg_g2m, blobs, cbs, pbs = gen_kzg(rng, kzg_n, kzg_blobs)

    arrays = {
        "att_keys": np.stack([_g1_arr(k) for k in keys[:n_att]]),
        "att_sigs": _g2_arr(sigs[:n_att]),
        "att_msgs": np.frombuffer(b"".join(msgs[:n_att]), np.uint8).reshape(-1, 32),
        "small_keys": np.stack([_g1_arr(k) for k in keys[n_att : n_att + 2]]),
        "small_sigs": _g2_arr(sigs[n_att : n_att + 2]),
        "small_msgs": np.frombuffer(
            b"".join(msgs[n_att : n_att + 2]), np.uint8
        ).reshape(-1, 32),
        "sync_keys": _g1_arr(keys[n_att + 2]),
        "sync_sigs": _g2_arr([sigs[n_att + 2]]),
        "sync_msgs": np.frombuffer(msgs[n_att + 2], np.uint8).reshape(1, 32),
        "kzg_setup_g1": _g1_arr(kzg_g1),
        "kzg_g2_monomial": _g2_arr(kzg_g2m),
        "kzg_blobs": np.frombuffer(b"".join(blobs), np.uint8).reshape(kzg_blobs, -1),
        "kzg_commitments": np.frombuffer(b"".join(cbs), np.uint8).reshape(-1, 48),
        "kzg_proofs": np.frombuffer(b"".join(pbs), np.uint8).reshape(-1, 48),
        "meta": np.frombuffer(
            json.dumps(
                {
                    "seed": SEED,
                    "n_att": n_att,
                    "n_pks": n_pks,
                    "sync_pks": sync_pks,
                    "kzg_n": kzg_n,
                    "kzg_blobs": kzg_blobs,
                }
            ).encode(),
            np.uint8,
        ),
    }
    path = os.path.join(os.path.dirname(__file__), "..", out)
    np.savez_compressed(path, **arrays)
    log(f"wrote {os.path.abspath(path)} ({os.path.getsize(path) / 1e6:.1f} MB)")


if __name__ == "__main__":
    main()
