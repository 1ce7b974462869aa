#!/usr/bin/env python3
"""Where does a G1 `jac_add` stop getting cheaper with fewer lanes?

Measures, on the device JAX gives (a TPU, or it says so), what
`curve_ops.TREE_SUM_L0` is set from:

  1. one `jac_add` of L lanes inside a `fori_loop` (as tree_sum runs it), for
     L = 256 .. 131,072: seconds an add, and per lane;
  2. `tree_sum` itself on the two key grids the benchmark's buckets give it,
     (512, 256) and (128, 64), with `TREE_SUM_L0` patched over a ladder of
     values (the last one larger than the grid: the fixed-shape loop alone),
     every result checked against the fixed-shape loop's as an affine point.

  3. `tree_sum` at the shipped `TREE_SUM_L0` on the key grids a dispatch lays
     since PR 42 (`backend.key_grid_plan`: per cell a wide and a narrow grid,
     `LAID_GRIDS`) beside the one grids they replace, every row's sum checked
     against the host's (the pool is small multiples of the generator, so a
     row sums to the sum of its multiples times the generator): the table a
     writer sizes the key axis from.

    chiprun --chips 1 -- python3 scripts/measure_tree_sum_l0.py [--tree-only|--laid-only]

`--tree-only` skips part 1, `--laid-only` parts 1 and 2; `--rehearse` runs toy
sizes (a CPU dry run of the script, not a measurement). Prints one JSON object and writes it to
chiprun_out/tree_sum_l0.json. Not part of the benchmark; rerun it when
jac_add or the chip changes.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.jaxbls import curve_ops as co
from lighthouse_tpu.crypto.jaxbls import tower as tw

ADD_LANES = (256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 131072)
ADDS_PER_CALL = 16
GRIDS = ((512, 256), (128, 64))
L0_LADDER = (256, 512, 1024, 2048, 4096, 8192, 16384, 1 << 30)
# (m keys, rest rows) with the cell whose dispatch lays it; "one grid" rows
# are what the same dispatch laid before the plan
LAID_GRIDS = (
    (32768, 16, "electra block, one grid"),
    (32768, 8, "electra block, wide"),
    (512, 4, "electra block, narrow"),
    (512, 256, "deneb block / aggregates, one grid"),
    (512, 1, "deneb block, wide"),
    (128, 256, "deneb block, narrow"),
    (512, 64, "aggregates, wide"),
    (1, 128, "aggregates, narrow"),
    (128, 64, "gossip batch, one grid"),
)
REPS = 5


def _timed(fn, *args):
    jax.block_until_ready(fn(*args))          # compile + warm
    out = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out), min(out)


def _grid(pool_dev, n_pool, shape, seed):
    idx = np.random.default_rng(seed).integers(0, n_pool, shape)
    return jax.tree_util.tree_map(lambda x: x[idx], pool_dev)


def main() -> int:
    dev = jax.devices()[0]
    small = "--rehearse" in sys.argv
    add_lanes = (64, 128) if small else ADD_LANES
    grids = ((16, 8),) if small else GRIDS
    if "--laid-only" in sys.argv:
        grids = ()
    laid = ((16, 8, "toy"), (8, 1, "toy"), (1, 4, "toy")) if small else LAID_GRIDS
    ladder = (16, 32, 1 << 30) if small else L0_LADDER

    # 63 small multiples of the generator and the identity: real points, so
    # another association order must give the same affine sum
    pool, p = [None], None
    for _ in range(63):
        p = pc.g1_add(p, pc.G1_GEN)
        pool.append(p)
    pool_dev = co.g1_batch_to_device(pool)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "jac_add": [], "tree_sum": [], "laid_grids": []}

    @jax.jit
    def adds(acc, x):
        return jax.lax.fori_loop(
            0, ADDS_PER_CALL, lambda _, a: co.jac_add(a, x, co.FQ_OPS), acc
        )

    if "--tree-only" in sys.argv or "--laid-only" in sys.argv:
        add_lanes = ()
    for lanes in add_lanes:
        a = _grid(pool_dev, len(pool), (lanes,), 1)
        b = _grid(pool_dev, len(pool), (lanes,), 2)
        med, low = _timed(adds, a, b)
        out["jac_add"].append({
            "lanes": lanes, "s_per_add": med / ADDS_PER_CALL,
            "min_s_per_add": low / ADDS_PER_CALL,
            "us_per_lane_add": med / ADDS_PER_CALL / lanes * 1e6,
        })
        print(json.dumps(out["jac_add"][-1]), flush=True)

    to_affine = jax.jit(lambda q: co.jac_to_affine(q, co.FQ_OPS))
    shipped = co.TREE_SUM_L0
    for m, rest in grids:
        g = _grid(pool_dev, len(pool), (m, rest), 3)
        seen, want = set(), None
        for l0 in reversed(ladder):           # the fixed-shape loop first
            co.TREE_SUM_L0 = l0
            plan = co.tree_sum_plan(m, rest)
            if plan in seen:
                continue
            seen.add(plan)
            fn = jax.jit(lambda q: co.tree_sum(q, co.FQ_OPS))
            t0 = time.perf_counter()
            med, low = _timed(fn, g)
            first = time.perf_counter() - t0 - med * REPS
            got = [np.asarray(v) for v in to_affine(fn(g))]
            if want is None:
                want = got
            same = all(np.array_equal(x, y) for x, y in zip(got, want))
            out["tree_sum"].append({
                "m": m, "rest": rest, "l0": l0, "c": plan[0],
                "fold_steps": plan[1], "finish_rounds": plan[2],
                "lane_additions": plan[3], "s": med, "min_s": low,
                "first_call_s": first, "same_point_as_loop": same,
            })
            print(json.dumps(out["tree_sum"][-1]), flush=True)
    co.TREE_SUM_L0 = shipped

    fn = jax.jit(lambda q: co.tree_sum(q, co.FQ_OPS))
    for m, rest, what in laid:
        idx = np.random.default_rng(4).integers(0, len(pool), (m, rest))
        g = jax.tree_util.tree_map(lambda x: x[idx], pool_dev)
        plan = co.tree_sum_plan(m, rest)
        t0 = time.perf_counter()
        med, low = _timed(fn, g)
        first = time.perf_counter() - t0 - med * REPS
        x, y, inf = to_affine(fn(g))
        got = [None if gone else tuple(xy) for gone, *xy in zip(
            np.asarray(inf).reshape(-1), tw.fq_batch_from_device(x),
            tw.fq_batch_from_device(y))]
        same = got == [pc.g1_mul(pc.G1_GEN, int(k)) if k else None
                       for k in idx.sum(axis=0)]
        out["laid_grids"].append({
            "m": m, "rest": rest, "what": what, "c": plan[0],
            "fold_steps": plan[1], "finish_rounds": plan[2],
            "lane_additions": plan[3], "s": med, "min_s": low,
            "us_per_lane_add": med / plan[3] * 1e6 if plan[3] else None,
            "first_call_s": first, "same_point_as_host": bool(same),
        })
        print(json.dumps(out["laid_grids"][-1]), flush=True)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/tree_sum_l0.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if (all(r["same_point_as_loop"] for r in out["tree_sum"])
                 and all(r["same_point_as_host"] for r in out["laid_grids"])) else 1


if __name__ == "__main__":
    sys.exit(main())
