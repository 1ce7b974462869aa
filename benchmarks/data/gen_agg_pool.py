#!/usr/bin/env python3
"""Generate the aggregate pools: `agg_pool_512.npz` (+ `--smoke`:
`agg_pool_smoke.npz`), one slot's `SignedAggregateAndProof` signature sets.

    python benchmarks/data/gen_agg_pool.py [--smoke] [--jobs N]

Run OFFLINE, once, on the CPU: the benchmark only LOADS the npz, and the
committed file is the yardstick's data — regenerating it is a `benchmark`
PR. Signed by `scripts/gen_bench_fixtures.py`'s method: secret keys are
drawn from the seed, public keys come from its fixed-base window table, and
an aggregate's signature is ONE G2 multiplication of the message's hash by
the sum of its attesters' secret keys (the same group element the
aggregation of their signatures gives).

A slot has `committees` committees of `committee_size` validators; each has
`aggregators` aggregators, members of the committee, and each aggregator
sends one aggregate = three signature sets:

  selection proof       1 key   (the aggregator's)  the slot message, the
                                                    same for every aggregate
  aggregator signature  1 key   (the aggregator's)  its own AggregateAndProof
                                                    message, distinct
  the aggregate         `min_attesting`..`committee_size` keys drawn by the
                        seed, the aggregator's among them; the committee's
                        message, shared by its `aggregators` aggregates

Arrays (big-endian 48-byte field elements, uint8; aggregate i belongs to
committee `agg_committee[i]` = i // aggregators):
  keys            (committees * committee_size, 2, 48)  committee c's members
                  are rows c*committee_size .. (c+1)*committee_size
  agg_committee   (n,) uint16
  agg_index       (n,) uint16   the aggregator's position in its committee
  agg_mask        (n, committee_size / 8) uint8   np.packbits of who attests
  sel_sigs, aggor_sigs, att_sigs   (n, 2, 2, 48)
  aggor_msgs, att_msgs             (n, 32)   att_msgs[i] is its committee's
  committee_msgs  (committees, 32);  slot_msg  (32,)
  meta            JSON: seed and the sizes above

A seeded sample of aggregates, and one of them damaged, go through the
pure-Python backend before the file is written; the benchmark verifies the
whole pool on the chip in every run.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import numpy as np  # noqa: E402

SEED = 0xA66512
CHECKED_AGGREGATES = 8


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _fixtures():
    """scripts/gen_bench_fixtures.py: the window table and the npz wire
    format are its own."""
    path = os.path.join(REPO_ROOT, "scripts", "gen_bench_fixtures.py")
    spec = importlib.util.spec_from_file_location("gen_bench_fixtures", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _msg(tag: int, i: int) -> bytes:
    return bytes([tag]) + i.to_bytes(31, "big")


def _hash(msg: bytes):
    from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
    from lighthouse_tpu.crypto.bls381.constants import DST_POP

    return ph2c.hash_to_g2(msg, DST_POP)


def _sign(job):
    from lighthouse_tpu.crypto.bls381 import curve as cv

    h_pt, sk = job
    return cv.g2_mul(h_pt, sk)


def _base_muls(sks):
    return _fixtures().host_base_muls(sks)


def _chunks(xs, n):
    size = -(-len(xs) // n)
    return [xs[i:i + size] for i in range(0, len(xs), size)]


def build(rng, pool, jobs, committees, aggregators, size, min_attesting):
    from lighthouse_tpu.crypto.bls381.constants import R

    fx = _fixtures()
    t0 = time.time()
    sks = [rng.randrange(1, R) for _ in range(committees * size)]
    keys = [p for part in pool.map(_base_muls, _chunks(sks, jobs))
            for p in part]
    log(f"  {len(keys)} public keys: {time.time() - t0:.1f}s")

    n = committees * aggregators
    agg_committee, agg_index, masks = [], [], []
    aggor_sks, att_sks = [], []
    for c in range(committees):
        for pos in rng.sample(range(size), aggregators):
            attesting = set(rng.sample(range(size),
                                       rng.randint(min_attesting, size)))
            attesting.add(pos)
            agg_committee.append(c)
            agg_index.append(pos)
            masks.append([i in attesting for i in range(size)])
            aggor_sks.append(sks[c * size + pos])
            att_sks.append(sum(sks[c * size + i] for i in attesting) % R)
    slot_msg = _msg(0x51, 0)
    committee_msgs = [_msg(0x41, c) for c in range(committees)]
    aggor_msgs = [_msg(0x61, i) for i in range(n)]

    t0 = time.time()
    hashes = list(pool.map(_hash, [slot_msg] + committee_msgs + aggor_msgs,
                           chunksize=8))
    h_slot, h_committee, h_aggor = (hashes[0], hashes[1:1 + committees],
                                    hashes[1 + committees:])
    log(f"  {len(hashes)} hash-to-G2: {time.time() - t0:.1f}s")
    t0 = time.time()
    sigs = list(pool.map(
        _sign,
        [(h_slot, sk) for sk in aggor_sks]
        + list(zip(h_aggor, aggor_sks))
        + [(h_committee[c], sk) for c, sk in zip(agg_committee, att_sks)],
        chunksize=8))
    log(f"  {len(sigs)} signatures: {time.time() - t0:.1f}s")

    def msgs(ms):
        return np.frombuffer(b"".join(ms), np.uint8).reshape(-1, 32)

    return {
        "keys": fx._g1_arr(keys),
        "agg_committee": np.array(agg_committee, np.uint16),
        "agg_index": np.array(agg_index, np.uint16),
        "agg_mask": np.packbits(np.array(masks, bool), axis=1),
        "sel_sigs": fx._g2_arr(sigs[:n]),
        "aggor_sigs": fx._g2_arr(sigs[n:2 * n]),
        "att_sigs": fx._g2_arr(sigs[2 * n:]),
        "aggor_msgs": msgs(aggor_msgs),
        "att_msgs": msgs([committee_msgs[c] for c in agg_committee]),
        "committee_msgs": msgs(committee_msgs),
        "slot_msg": np.frombuffer(slot_msg, np.uint8),
    }


def check(path: str, rng) -> None:
    """A seeded sample through the pure-Python backend, as the benchmark's
    driver loads it; one damaged aggregate must be refused."""
    from lighthouse_tpu.crypto import bls

    spec = importlib.util.spec_from_file_location(
        "bls_aggregate_flood",
        os.path.join(os.path.dirname(HERE), "drivers",
                     "bls_aggregate_flood.py"))
    driver = importlib.util.module_from_spec(spec)
    sys.path.insert(0, os.path.dirname(HERE))     # layer_reader, common
    spec.loader.exec_module(driver)
    pool, _meta = driver.load_pool(path)
    t0 = time.time()
    bls.set_backend("python")
    sample = rng.sample(range(len(pool)), min(CHECKED_AGGREGATES, len(pool)))
    sets = [s for i in sample for s in pool[i].trio]
    assert bls.verify_signature_sets(sets), "python backend disagrees"
    donor = next(i for i in range(len(pool))
                 if pool[i].committee != pool[sample[0]].committee)
    for role in range(3):
        bad = list(sets)
        bad[role] = driver.tampered(pool, sample[0], donor, role,
                                    "swap_signature")
        assert not bls.verify_signature_sets(bad), f"damaged role {role} accepted"
    log(f"  python backend on {len(sample)} aggregates, valid and damaged: "
        f"{time.time() - t0:.1f}s")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true", help="rehearsal sizes")
    ap.add_argument("--jobs", type=int, default=max(1, (os.cpu_count() or 2) - 2))
    args = ap.parse_args()
    if args.smoke:
        shape = dict(committees=2, aggregators=2, size=4, min_attesting=3)
        out = "agg_pool_smoke.npz"
    else:
        shape = dict(committees=64, aggregators=16, size=512,
                     min_attesting=448)
        out = "agg_pool_512.npz"
    rng = random.Random(SEED)
    with ProcessPoolExecutor(args.jobs, mp_context=get_context("spawn")) as pool:
        arrays = build(rng, pool, args.jobs, **shape)
    arrays["meta"] = np.frombuffer(json.dumps(dict(
        seed=SEED, committees=shape["committees"],
        aggregators_per_committee=shape["aggregators"],
        committee_size=shape["size"], min_attesting=shape["min_attesting"],
        n_aggregates=shape["committees"] * shape["aggregators"],
    )).encode(), np.uint8)
    path = os.path.join(HERE, out)
    np.savez_compressed(path, **arrays)
    log(f"wrote {path} ({os.path.getsize(path) / 1e6:.2f} MB)")
    check(path, rng)


if __name__ == "__main__":
    main()
