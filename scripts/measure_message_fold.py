#!/usr/bin/env python3
"""Times, on the chip, what a dispatch that folds its sets by message runs
in place of a lane a set: the table behind `backend.message_lanes` and
PERF.md S6, PR 44.

Each program jitted as the backend jits it on the chip (its inputs
donated), alone, ms a call, median of 5:

  h2c            `hash_to_g2_jacobian` at k = 128 message lanes;
  miller         `_stage_miller` at k + 1 = 129 pairs (one sparse line an
                 accumulator, the pair over into lane 0), and the
                 `_stage_final_exp` every bucket shares;
  jac_add        ONE G1 `jac_add` at 256 and at 1,024 lanes: a round of the
                 fold;
  fold           `_fold_by_message` alone at (n, k) = (256, 128) and
                 (1024, 128): log2(n) such rounds, the gather before them
                 and the k first lanes read after — every lane's sum
                 checked against the host's (the pure-Python curve code);
  pairs          `_stage_pairs` at 128 lanes (the batched inversion over
                 257 Fq2 lanes and the pair assembly: what stage 3 costs
                 at k lanes without the fold);
  pairs_folded   `_stage_pairs_folded` at (256, 128) and (1024, 128): the
                 program a folding dispatch runs as stage 3.

    chiprun -- python3 scripts/measure_message_fold.py
    python3 scripts/measure_message_fold.py --rehearse        # CPU dry run

One process. Prints one JSON object and writes it, as it grows, to
chiprun_out/message_fold.json. Without a TPU (and without --rehearse, which
runs n = 16, k = 4 and skips hash-to-G2 and the Miller loop) it exits 2.
Not part of the benchmark; rerun it when the curve arithmetic or the chip
changes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381.constants import P
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.jaxbls import limbs as lb
from lighthouse_tpu.utils import jaxcfg

REPS = 5
OUT = "chiprun_out/message_fold.json"


def timed(fn, host_args, reps: int):
    """(first call s, [ms a call], last result): the arguments placed anew
    before each call's clock starts, as a donating program needs."""
    def placed():
        return jax.block_until_ready(jax.device_put(host_args))

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*placed()))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        args = placed()
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return first, ms, out


def donating(stage: str, fn=None):
    return jax.jit(fn or be._ONE_CHIP_VARIANTS[stage],
                   donate_argnums=be.STAGE_DONATE_ARGNUMS[stage])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    d0 = jax.devices()[0]
    if d0.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"no TPU: platform {d0.platform!r}"}))
        return 2
    jaxcfg.setup_compilation_cache()
    be._init_consts()
    reps = 1 if args.rehearse else REPS
    k = 4 if args.rehearse else 128
    buckets = (16,) if args.rehearse else (256, 1024)
    out = {"device": {"platform": d0.platform, "kind": d0.device_kind},
           "message_lanes": k, "rows": []}

    def save():
        os.makedirs("chiprun_out", exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)

    def record(first, ms, **keys):
        out["rows"].append({**keys, "first_call_s": round(first, 2),
                            "ms_median": statistics.median(ms),
                            "ms_all": [round(t, 3) for t in ms]})
        print(json.dumps(out["rows"][-1]), flush=True)
        save()

    rng = np.random.default_rng(44)

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    # -- stage 2 and stage 4 at the row
    us = lb.pack_batch(
        [int.from_bytes(rng.bytes(48), "big") % P for _ in range(4 * k)]
    ).reshape(k, 2, 2, lb.NL)
    if args.rehearse:
        h_jac = host(co.g2_batch_to_device(
            [cv.g2_mul(cv.G2_GEN, j + 2) for j in range(k)]))
    else:
        first, ms, h_jac = timed(donating("h2c", h2.hash_to_g2_jacobian),
                                 (us,), reps)
        h_jac = host(h_jac)
        record(first, ms, program="h2c", lanes=k)

    # n points the host can sum: (i + 1) G by repeated addition
    n_max = max(buckets)
    points, p = [], None
    for _ in range(n_max):
        p = cv.g1_add(p, cv.G1_GEN)
        points.append(p)
    sig_acc = tuple(c[0] for c in h_jac)
    ok = True

    for lanes in () if args.rehearse else (256, 1024):
        a = host(co.g1_batch_to_device(points[:lanes]))
        b = host(co.g1_batch_to_device(points[lanes - 1::-1]))
        first, ms, _ = timed(
            jax.jit(lambda a, b: co.jac_add(a, b, co.FQ_OPS)), (a, b), reps)
        record(first, ms, program="jac_add_g1", lanes=lanes)

    for n in buckets:
        # what the cells send: shares of every size, one message held by a
        # quarter of the sets, some held once, some lanes without a message
        held = max(k * 5 // 8, 2)
        lanes = np.concatenate([
            np.zeros(n // 4, np.int64),
            rng.integers(1, held, n - n // 4 - n // 8),
        ])
        rng.shuffle(lanes)
        fold = be.message_fold_index(lanes, n, k)
        z_pk = host(co.g1_batch_to_device(
            points[:len(lanes)] + [None] * (n - len(lanes))))
        first, ms, (sums, mask) = timed(
            jax.jit(lambda z, f: be._fold_by_message(z, f, k)),
            (z_pk, fold), reps)
        want = [None] * k
        for pt, j in zip(points, lanes):
            want[j] = cv.g1_add(want[j], pt)
        same = all(
            bool(mask[j]) == (want[j] is not None) and (
                want[j] is None
                or co.g1_from_device(tuple(c[j] for c in sums)) == want[j])
            for j in range(k))
        ok = ok and same
        record(first, ms, program="fold", sets=n, lanes=k,
               equals_host_sums=same)

        first, ms, _ = timed(donating("pairs_folded"),
                             (z_pk, h_jac, sig_acc, fold), reps)
        record(first, ms, program="pairs_folded", sets=n, lanes=k)

    z_k = host(co.g1_batch_to_device(points[:k]))
    first, ms, pairs = timed(donating("pairs", be._stage_pairs),
                             (z_k, h_jac, sig_acc, np.ones((k,), np.uint32)),
                             reps)
    record(first, ms, program="pairs", lanes=k)

    if not args.rehearse:
        first, ms, f = timed(donating("miller", be._stage_miller),
                             host(pairs), reps)
        record(first, ms, program="miller", pairs=k + 1)
        first, ms, _ = timed(donating("final_exp", be._stage_final_exp),
                             (host(f),), reps)
        record(first, ms, program="final_exp")

    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    save()
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
