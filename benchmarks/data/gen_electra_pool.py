#!/usr/bin/env python3
"""Generate the Electra block pools: `electra_pool_8.npz` (+ `--smoke`:
`electra_pool_smoke.npz`), one epoch's whole-slot attestations and a block's
three other signature sets over a registry that is DERIVED, never stored.

    python benchmarks/data/gen_electra_pool.py [--smoke]

Run OFFLINE, once, on the CPU: the benchmark only LOADS the npz, and the
committed file is the yardstick's data — regenerating it is a `benchmark`
PR. The registry's 1,048,576 keys would be 100 MB, too much for the tree;
so validator i's secret key is a + i d (mod r) with a and d from
`registry_seed` (drivers/bls_registry_block_loop.py `registry_secrets`), its
public key is (a + i d) G, which the driver derives at set-up by chained
additions, and the signature of ANY set of validators on a message is ONE
G2 multiplication of the message's hash by the sum of their secrets: the
same group element the aggregation of their signatures gives.

The epoch's `slots` committees-by-slot partition the registry
(`slot_partition`: seeded, re-derived by the driver); per slot a seeded
number of its validators within `signers` signs one AttestationData root.
The proposer signs the proposal and the RANDAO reveal; `sync_committee_size`
seeded validators sign the sync aggregate, all of them.

Arrays (big-endian 48-byte field elements, uint8):
  att_mask      (slots, validators / slots / 8)  np.packbits over the slot's
                committee in ascending validator index: who signed
  att_sigs      (slots, 2, 2, 48);  att_msgs   (slots, 32)
  small_sigs    (2, 2, 2, 48);      small_msgs (2, 32)   proposal, RANDAO
  sync_indices  (sync_committee_size,) int32, ascending
  sync_sig      (2, 2, 48);         sync_msg   (32,)
  meta          JSON: registry_seed, validators, slots, sync_committee_size,
                proposer_index, signers [fewest, most a slot],
                attestation_keys [min, max as drawn]

Every number here is the plain reference's (reference/bls_registry_spec.py:
its hash-to-G2, its curve arithmetic, its compression), none the program's.
Before the file is written EVERY set of the pool goes through the
reference's verification once, as one batch on keys it decompresses from
the derived registry's bytes (a square root a validator: minutes at the
full size), and again with one attestation's signature swapped for its
neighbour's; the benchmark verifies every set on the chip in every run.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO_ROOT = os.path.dirname(BENCH_DIR)
for _p in (REPO_ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SIZES = {
    # name: validators, slots, sync committee, signers a slot (fewest,
    # most: 97.9 % to 99.97 % of 32,768 at the full size), registry seed
    "electra_pool_8.npz": (1_048_576, 32, 512, (32_093, 32_759), 41),
    "electra_pool_smoke.npz": (64, 4, 8, (12, 16), 4141),
}


def _load(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _be48(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(48, "big"), np.uint8)


def _g2_array(point) -> np.ndarray:
    (x0, x1), (y0, y1) = point
    return np.stack([np.stack([_be48(x0), _be48(x1)]),
                     np.stack([_be48(y0), _be48(y1)])])


def generate(name: str) -> str:
    drv = _load("drivers", "bls_registry_block_loop")
    ref = _load("reference", "bls_registry_spec")
    n, slots, sync_size, (lo, hi), seed = SIZES[name]
    width = n // slots
    a, d = drv.registry_secrets(seed)
    rng = np.random.default_rng(seed)
    committees = drv.slot_partition(n, slots, seed)

    def message(tag: str) -> bytes:
        return hashlib.sha256(f"electra-pool:{seed}:{tag}".encode()).digest()

    def sign(indices, msg: bytes):
        return ref.g2_mul(ref.hash_to_g2(msg), drv.secret_sum(a, d, indices))

    t0 = time.time()
    masks, att_sigs, att_msgs, signers = [], [], [], []
    for j in range(slots):
        k = int(rng.integers(lo, hi + 1))
        signed = np.zeros(width, bool)
        signed[rng.choice(width, size=k, replace=False)] = True
        signers.append(committees[j][signed])
        att_msgs.append(message(f"att:{j}"))
        att_sigs.append(sign(signers[j], att_msgs[j]))
        masks.append(np.packbits(signed))
    proposer = int(rng.integers(n))
    small_msgs = [message("proposal"), message("randao")]
    small_sigs = [sign([proposer], m) for m in small_msgs]
    sync_indices = np.sort(rng.choice(n, size=sync_size, replace=False))
    sync_msg = message("sync")
    sync_sig = sign(sync_indices, sync_msg)
    print(f"minted {slots + 3} signatures in {time.time() - t0:.1f} s")

    # the reference's verdict on EVERY set, on keys it decompresses itself
    t0 = time.time()
    points = drv.derive_registry(n, a, d)
    key_bytes = [ref.compress_key(p) for p in points]
    assert ref.keys_not_of(key_bytes, points) == 0
    print(f"derived and compressed {n} keys in {time.time() - t0:.1f} s")

    def keyed(indices, msg, sig):
        return (sig, [key_bytes[int(i)] for i in indices], msg)

    pool = ([keyed([proposer], m, s) for m, s in zip(small_msgs, small_sigs)]
            + [keyed(signers[j], att_msgs[j], att_sigs[j])
               for j in range(slots)]
            + [keyed(sync_indices, sync_msg, sync_sig)])
    zs = [int(z) for z in rng.integers(1, 1 << 63, size=len(pool))]
    t0 = time.time()
    decompressed: dict = {}
    assert ref.verify_signature_sets(pool, zs, decompressed) is True
    at = dict(zip(key_bytes, points))
    assert all(at[b] == p for b, p in decompressed.items())      # who signed
    j = int(rng.integers(slots))
    pool[2 + j] = keyed(signers[j], att_msgs[j], att_sigs[(j + 1) % slots])
    assert ref.verify_signature_sets(pool, zs, decompressed) is False
    print(f"reference: all {len(pool)} sets valid as one batch, attestation "
          f"{j} swapped False ({time.time() - t0:.1f} s)")

    widths = [len(s) for s in signers]
    meta = {"registry_seed": seed, "validators": n, "slots": slots,
            "sync_committee_size": sync_size, "proposer_index": proposer,
            "signers": [lo, hi],
            "attestation_keys": [min(widths), max(widths)],
            "secrets": "validator i: (a + i d) mod r, (a, d) = "
                       "registry_secrets(registry_seed)"}
    path = os.path.join(HERE, name)
    np.savez_compressed(
        path,
        att_mask=np.stack(masks),
        att_sigs=np.stack([_g2_array(s) for s in att_sigs]),
        att_msgs=np.stack([np.frombuffer(m, np.uint8) for m in att_msgs]),
        small_sigs=np.stack([_g2_array(s) for s in small_sigs]),
        small_msgs=np.stack([np.frombuffer(m, np.uint8) for m in small_msgs]),
        sync_indices=sync_indices.astype(np.int32),
        sync_sig=_g2_array(sync_sig),
        sync_msg=np.frombuffer(sync_msg, np.uint8),
        meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    print(f"wrote {path}: {os.path.getsize(path)} bytes, {meta}")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the rehearsal pool: 64 validators, 4 slots")
    args = ap.parse_args(argv)
    generate("electra_pool_smoke.npz" if args.smoke else "electra_pool_8.npz")
    return 0


if __name__ == "__main__":
    sys.exit(main())
