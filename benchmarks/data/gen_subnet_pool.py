#!/usr/bin/env python3
"""Generate the subnet attestation pools: `subnet_pool_1key.npz` (+
`--smoke`: `subnet_pool_smoke.npz`), ONE slot's unaggregated attestations
over a registry that is DERIVED, never stored.

    python benchmarks/data/gen_subnet_pool.py [--smoke] [--jobs 4]

Run OFFLINE, once, on the CPU: the benchmark only LOADS the npz, and the
committed file is the yardstick's data - regenerating it is a `benchmark`
PR. The registry is `mainnet-electra-block-8`'s (validator i's secret key
a + i d mod r, (a, d) = `registry_secrets(registry_seed)` of
drivers/bls_registry_block_loop.py; the same `registry_seed`, so the same
1,048,576 keys). One of the epoch's slots (`slot_partition`'s row `slot`)
is split into `committees` committees of equal size by a seeded rank
(`committee_partition` of drivers/bls_subnet_flood.py); every member signs
ONE AttestationData root: its committee's head message, or - a seeded
`late_share` of the members - the committee's late message (a vote for the
parent's root). So the slot's 32,768 single-key attestations share 128
messages.

What is STORED, and what the driver DERIVES:

  stored   head_msgs, late_msgs   (committees, 32)  the messages
           head_points, late_points  (committees, 2, 2, 2, 48)  per message
                        A = a H(M) and D = d H(M), affine G2, big-endian
                        48-byte field elements (x0, x1), (y0, y1)
           late_mask    (committees, size / 8)  np.packbits over a
                        committee's members in ascending validator index:
                        who votes late
           meta         JSON: registry_seed, pool_seed, validators, slots,
                        slot, committees, committee_size, late_share,
                        late_voters [fewest, most a committee], attestations
  derived  the registry's keys; the slot's validators and their committees;
           every member's signature A + i D of its message, by
           reference/bls_subnet_spec.py `mint_members` (two chains of
           additions a message, one addition a member) - 6.3 MB of
           signatures that the file therefore does not hold

Every number here is the plain reference's (reference/bls_subnet_spec.py on
reference/bls_registry_spec.py: its hash-to-G2, its curve arithmetic, its
compression), none the program's. Before the file is written EVERY
attestation of the pool goes through the reference's batch verification
once, in dispatches of `check_batch` drawn by a seeded permutation, on keys
the reference decompresses from the registry's bytes; every one of those
dispatches is verified again with ONE seeded member's signature exchanged
for its neighbour's in the permutation and must come out False; no two of
the pool's signatures are the same point (so a neighbour's is never a
set's own); and `spot_checks` seeded attestations are verified ALONE, with
their own and with the neighbour's signature (the smoke pool: every one).
The benchmark verifies every attestation on the chip in every run.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import multiprocessing
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
    # name: validators, slots, the slot, committees, late share, registry
    # seed (mainnet-electra-block-8's: the same keys), pool seed, sets a
    # checked dispatch, attestations verified alone
    "subnet_pool_1key.npz": (1_048_576, 32, 17, 64, 0.02, 41, 43, 1024, 64),
    "subnet_pool_smoke.npz": (256, 4, 1, 4, 0.125, 4141, 4343, 16, 64),
}


def _load(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: an affine G2 point as (2, 2, 48) big-endian bytes: the Electra pool's
_g2_array = _load("data", "gen_electra_pool")._g2_array


def _check_dispatch(job) -> tuple:
    """One checked dispatch in a worker: (valid verdict, verdict with one
    member's signature exchanged for its neighbour's)."""
    sets, zs, victim = job
    ref = _load("reference", "bls_subnet_spec")
    keys, hashed = {}, {}
    good = ref.verify_batch(sets, zs, keys, hashed)
    swapped = list(sets)
    donor = sets[(victim + 1) % len(sets)]
    swapped[victim] = (donor[0],) + sets[victim][1:]
    return good, ref.verify_batch(swapped, zs, keys, hashed)


def generate(name: str, jobs: int) -> str:
    drv = _load("drivers", "bls_subnet_flood")
    ref = _load("reference", "bls_subnet_spec")
    (n, slots, slot, committees, late_share, registry_seed, pool_seed,
     check_batch, spot_checks) = SIZES[name]
    a, d = drv.registry.registry_secrets(registry_seed)
    rng = np.random.default_rng(pool_seed)
    members = drv.committee_partition(
        drv.registry.slot_partition(n, slots, registry_seed)[slot],
        committees, pool_seed)
    size = members.shape[1]

    def message(tag: str) -> bytes:
        return hashlib.sha256(f"subnet-pool:{pool_seed}:{tag}".encode()).digest()

    t0 = time.time()
    head_msgs = [message(f"head:{c}") for c in range(committees)]
    late_msgs = [message(f"late:{c}") for c in range(committees)]
    head_points = [ref.message_points(m, a, d) for m in head_msgs]
    late_points = [ref.message_points(m, a, d) for m in late_msgs]
    late = rng.random((committees, size)) < late_share
    pool = {"meta": {"validators": n}, "members": members, "late": late,
            "head_msgs": head_msgs, "late_msgs": late_msgs,
            "head_points": head_points, "late_points": late_points}
    atts = drv.mint_slot(pool, ref)           # [(index, message, signature)]
    print(f"minted {len(atts)} signatures on {2 * committees} messages in "
          f"{time.time() - t0:.1f} s")
    assert len(atts) == committees * size
    assert len({sig for _, _, sig in atts}) == len(atts)   # no two the same

    # the reference's verdict on EVERY attestation, on keys it decompresses
    t0 = time.time()
    points = drv.registry.derive_registry(n, a, d)
    key_bytes = {i: ref.base.compress_key(points[i]) for i, _, _ in atts}
    assert ref.base.keys_not_of(
        [key_bytes[i] for i, _, _ in atts],
        [points[i] for i, _, _ in atts]) == 0
    del points
    print(f"derived the registry, compressed {len(key_bytes)} keys in "
          f"{time.time() - t0:.1f} s")
    order = rng.permutation(len(atts))
    work = []
    for at in range(0, len(order), check_batch):
        chunk = [atts[int(j)] for j in order[at:at + check_batch]]
        sets = [(sig, key_bytes[i], msg) for i, msg, sig in chunk]
        zs = [int(z) for z in rng.integers(1, 1 << 63, size=len(sets))]
        work.append((sets, zs, int(rng.integers(len(sets)))))
    t0 = time.time()
    if jobs > 1:
        with multiprocessing.get_context("spawn").Pool(jobs) as workers:
            verdicts = workers.map(_check_dispatch, work)
    else:
        verdicts = [_check_dispatch(w) for w in work]
    assert all(v == (True, False) for v in verdicts), verdicts
    print(f"reference: all {len(atts)} attestations valid in {len(work)} "
          f"dispatches of {check_batch}; each dispatch False with one "
          f"neighbour's signature ({time.time() - t0:.1f} s)")
    t0 = time.time()
    alone = (range(len(atts)) if spot_checks >= len(atts)
             else rng.choice(len(atts), size=spot_checks, replace=False))
    keys, hashed = {}, {}
    for j in alone:
        i, msg, sig = atts[int(j)]
        other = atts[(int(j) + 1) % len(atts)][2]
        assert ref.verify_one(sig, key_bytes[i], msg, keys, hashed) is True
        assert ref.verify_one(other, key_bytes[i], msg, keys, hashed) is False
    print(f"reference: {len(alone)} attestations alone, valid, and False "
          f"with the neighbour's signature ({time.time() - t0:.1f} s)")

    late_counts = late.sum(axis=1)
    meta = {"registry_seed": registry_seed, "pool_seed": pool_seed,
            "validators": n, "slots": slots, "slot": slot,
            "committees": committees, "committee_size": size,
            "late_share": late_share,
            "late_voters": [int(late_counts.min()), int(late_counts.max())],
            "attestations": len(atts),
            "stored": "per committee its head and its late message, each "
                      "message's A = a H(M) and D = d H(M), and who votes "
                      "late (late_mask over the members in ascending index)",
            "derived": "the registry (validator i: (a + i d) mod r, (a, d) = "
                       "registry_secrets(registry_seed)); the slot's "
                       "validators slot_partition(validators, slots, "
                       "registry_seed)[slot] and their committees "
                       "committee_partition(., committees, pool_seed); "
                       "member i's signature A + i D of its message"}
    path = os.path.join(HERE, name)
    np.savez_compressed(
        path,
        head_msgs=np.stack([np.frombuffer(m, np.uint8) for m in head_msgs]),
        late_msgs=np.stack([np.frombuffer(m, np.uint8) for m in late_msgs]),
        head_points=np.stack([np.stack([_g2_array(A), _g2_array(D)])
                              for A, D in head_points]),
        late_points=np.stack([np.stack([_g2_array(A), _g2_array(D)])
                              for A, D in late_points]),
        late_mask=np.packbits(late, axis=1),
        meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    print(f"wrote {path}: {os.path.getsize(path)} bytes, {meta}")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the rehearsal pool: 256 validators, 4 committees "
                    "of 16")
    ap.add_argument("--jobs", type=int, default=4,
                    help="worker processes of the reference's check")
    args = ap.parse_args(argv)
    generate("subnet_pool_smoke.npz" if args.smoke
             else "subnet_pool_1key.npz", args.jobs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
