#!/usr/bin/env python3
"""What does the Miller loop pay for, a lane or a call?

Measures, on the device JAX gives (a TPU, or it says so), what
`pairing_ops.miller_lane_plan`'s constants (`MILLER_LANES`, and
`MILLER_WIDE_FROM`'s line for the platform) are set from:

  1. the field operations of one Miller step, each on L pair lanes inside a
     `fori_loop` (as the scan runs them): `fq12_mul`, `fq12_sqr`,
     `_line_mul_line`, `fq12_mul_by_014` and `_dbl_step`, for L = 1 .. 257
     (L = 0: no batch axis at all, the shape of the W = 1 accumulator);
     seconds a call;
  2. `miller_loop_product` itself at the served pair counts (4 = the KZG
     check, 5, 65, 257) with `MILLER_LANES` and `MILLER_WIDE_FROM` patched
     over a ladder of W (W = 1: the one-accumulator loop), every Miller
     value checked equal, limb for limb, to W = 1's;
  3. one traced call of `_stage_pairing` at 65 pairs with W = 1 (the
     program every PR up to 29 served): device seconds by op name, and the
     compiled HLO beside it, to say which loop `%while.29` is;
  4. (`--stage`, alone) stage 4 as one program and as the two that serve it,
     at the served pair counts with the shipped plan (on a TPU: the row at
     every count): ms a call of `_stage_pairing`, of `_stage_miller`, of
     `_stage_final_exp`, and of the two enqueued back to back with one
     `block_until_ready`, the verdicts checked equal. The table behind
     `backend._PairingPrograms`, which serves the two on one chip.

    chiprun --chips 1 -- python3 scripts/measure_miller_lanes.py \
        [--budget-s N] [--jobs 65:1,65:128,...] [--stage [--pairs 5,4]]

Part 2 starts no further compile once N seconds (default 1500) have passed;
`--jobs` runs part 2 alone, on the listed pairs:W (W = 1 first for each
pair count: it is the reference); `--stage` runs part 4 alone, `--pairs`
names its pair counts;
`--rehearse` runs toy sizes (a CPU dry run of the script, not a
measurement). Prints one JSON object and writes it, as it grows, to
chiprun_out/miller_lanes.json, part 4 to chiprun_out/stage_split.json (a
rehearsal's beside it, under another name). Not part of the benchmark;
rerun it when the tower arithmetic or the chip changes.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from lighthouse_tpu.crypto.bls381 import curve as pc
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
from lighthouse_tpu.crypto.jaxbls import tower as tw

OP_LANES = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 257)
CALLS_PER_LOOP = 16
# (pair lanes, W), in the order they are worth their compile: each served
# pair count at W = 1 (the reference value) and at a full row of lanes,
# then narrower rows
MILLER_JOBS = (
    (257, 1), (257, 128), (65, 1), (65, 128), (5, 1), (5, 128),
    (4, 1), (4, 128),
    (65, 64), (65, 32), (257, 64), (65, 16), (257, 32), (5, 2),
    (257, 16), (65, 8), (257, 8),
)
TRACED_PAIRS = 65
REPS = 5
OUT = "chiprun_out/miller_lanes.json"
STAGE_OUT = "chiprun_out/stage_split.json"
STAGE_PAIRS = (5, 4, 65, 257)


def _timed(fn, *args, fresh=False):
    """(median, least) seconds a call over REPS calls after a warm one.
    `fresh`: `args` are host arrays, placed anew before each call's clock
    starts — for a program that donates its inputs."""
    def placed():
        return jax.block_until_ready(jax.device_put(args)) if fresh else args

    jax.block_until_ready(fn(*placed()))      # compile + warm
    out = []
    for _ in range(REPS):
        a = placed()
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        out.append(time.perf_counter() - t0)
    return statistics.median(out), min(out)


def _pairs(n: int, seed: int):
    """n real affine pairs drawn from 16 small multiples of the generators,
    three lanes masked out: ((xp, yp), (xq, yq), mask) on the device."""
    g1, g2, p, q = [], [], None, None
    for _ in range(16):
        p, q = pc.g1_add(p, pc.G1_GEN), pc.g2_add(q, pc.G2_GEN)
        g1.append(p)
        g2.append(q)
    rng = np.random.default_rng(seed)
    i1, i2 = rng.integers(0, 16, n), rng.integers(0, 16, n)
    xp = tw.fq_batch_to_device([g1[i][0] for i in i1])
    yp = tw.fq_batch_to_device([g1[i][1] for i in i1])
    xq = tw.fq2_batch_to_device([g2[i][0] for i in i2])
    yq = tw.fq2_batch_to_device([g2[i][1] for i in i2])
    mask = np.ones(n, bool)
    if n >= 4:                                # tiny shapes stay whole
        mask[rng.integers(0, n, 3)] = False
    return (xp, yp), (xq, yq), jnp.asarray(mask)


def _op_loops():
    """name -> (jitted loop of CALLS_PER_LOOP dependent calls, its inputs
    from (f, g: Fq12; r: G2 jacobian; xp, yp: Fq)). Every call's whole
    result feeds the next, so nothing is dead code."""

    def loop(body):
        return jax.jit(lambda c: jax.lax.fori_loop(
            0, CALLS_PER_LOOP, lambda _, x: body(x), c))

    def line_of(f):                           # three Fq2 of a dense Fq12
        return f[..., 0, 0, :, :], f[..., 0, 1, :, :], f[..., 1, 1, :, :]

    def mul(c):
        return tw.fq12_mul(c[0], c[1]), c[1]

    def sqr(c):
        return (tw.fq12_sqr(c[0]),)

    def line_pair(c):
        return po._line_mul_line(line_of(c[0]), line_of(c[1])), c[1]

    def by_014(c):
        return tw.fq12_mul_by_014(c[0], *line_of(c[1])), c[1]

    def dbl(c):
        r, xp, yp = c
        r, (l0, l1, l2) = po._dbl_step(r, xp, yp)
        return ((r[0], r[1], tw.fq2_add(r[2], l0)),
                l1[..., 0, :], l2[..., 1, :])

    return {
        "fq12_mul": (loop(mul), lambda f, g, r, xp, yp: (f, g)),
        "fq12_sqr": (loop(sqr), lambda f, g, r, xp, yp: (f,)),
        "_line_mul_line": (loop(line_pair), lambda f, g, r, xp, yp: (f, g)),
        "fq12_mul_by_014": (loop(by_014), lambda f, g, r, xp, yp: (f, g)),
        "_dbl_step": (loop(dbl), lambda f, g, r, xp, yp: (r, xp, yp)),
    }


def _op_inputs(lanes: int, seed: int):
    rng = np.random.default_rng(seed)
    batch = (lanes,) if lanes else ()

    def fq(*shape):                           # canonical limbs < 2^16, top
        a = rng.integers(0, 1 << 16, batch + shape + (tw.NL,), np.uint32)
        a[..., -1] &= 0x0FFF                  # limb small enough for < P
        return jnp.asarray(a)

    f, g = fq(2, 3, 2), fq(2, 3, 2)
    r = (fq(2), fq(2), fq(2))
    return f, g, r, fq(), fq()


def _save(out, path=OUT):
    # a rehearsal's numbers are the CPU's: never under the measurement's name
    if out["device"]["platform"] != "tpu":
        path += ".rehearsal"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)


def _wide_from(lanes: int) -> dict:
    """`MILLER_WIDE_FROM` that gives every pair count W = `MILLER_LANES`
    (lanes > 1) or W = 1, on whatever platform this runs: the one entry
    every platform falls back to."""
    return {"cpu": 1 if lanes > 1 else 1 << 30}


def _traced_stage(out, n_pairs: int):
    """One profiled window of `_stage_pairing` at W = 1: device seconds by
    op name (the benchmark's own reduction of names), HLO kept beside."""
    from benchmarks import trace_reduce

    po.MILLER_WIDE_FROM = _wide_from(1)
    p, q, mask = _pairs(n_pairs, 11)
    compiled = jax.jit(be._stage_pairing).lower(*p, *q, mask).compile()
    with gzip.open("chiprun_out/stage_pairing_w1_%d.hlo.txt.gz" % n_pairs,
                   "wt") as fh:
        fh.write(compiled.as_text())
    jax.block_until_ready(compiled(*p, *q, mask))
    trace_dir = "chiprun_out/.miller_trace"
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(compiled(*p, *q, mask))
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    by_name: dict = {}
    path = trace_reduce.find_xplane(trace_dir)
    for plane in trace_reduce.load(path)["planes"] if path else ():
        if not plane["name"].startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            for name, _, dur in line["events"]:
                by_name[name] = by_name.get(name, 0.0) + dur / 1e9
    shutil.rmtree(trace_dir, ignore_errors=True)   # tens of MB; reduced above
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:16]
    out["traced_stage_pairing"] = {
        "pairs": n_pairs, "accumulators": 1, "calls": 3, "wall_s": wall,
        "device_s_by_op": [{"op": k, "s": v} for k, v in top],
    }


def _stage_split(out, pair_counts) -> bool:
    """Part 4: stage 4 as one program against two. The two are the jits
    the backend holds (donation as the platform has it): on the chip they
    are the served executables and share their cache entries. The one
    program is the mesh's; it is jitted here, under the same donation, for
    its column."""
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl

    served = be._get_stages()[3]
    one = jax.jit(be._stage_pairing, **(
        dict(donate_argnums=be.STAGE_DONATE_ARGNUMS["pairing"])
        if pl.donation_enabled()[0] else {}))

    def back_to_back(*args):                  # no sync between the two
        return served.final_exp(served.miller(*args))

    same = True
    for n in pair_counts:
        p, q, mask = _pairs(n, n)
        args = tuple(np.asarray(a) for a in (*p, *q, mask))
        row = {"pairs": n, "accumulators": po.miller_lane_plan(n)[0]}
        for name, fn in (("stage_pairing", one), ("stage_miller", served.miller),
                         ("stage_final_exp", served.final_exp),
                         ("back_to_back", back_to_back)):
            a = args
            if fn is served.final_exp:        # the Miller value, from the host
                a = (np.asarray(served.miller(*jax.device_put(args))),)
            t0 = time.perf_counter()
            med, low = _timed(fn, *a, fresh=True)
            row[name] = {"s": med, "min_s": low,
                         "first_call_s": time.perf_counter() - t0 - med * REPS}
        row["same_verdict"] = (bool(one(*jax.device_put(args)))
                               == bool(back_to_back(*jax.device_put(args))))
        same = same and row["same_verdict"]
        out["stage_split"].append(row)
        print(json.dumps(row), flush=True)
        _save(out, STAGE_OUT)
    return same


def main() -> int:
    dev = jax.devices()[0]
    def option(name, default=None):
        return (sys.argv[sys.argv.index(name) + 1] if name in sys.argv
                else default)

    small = "--rehearse" in sys.argv
    budget = float(option("--budget-s", 1500))
    started = time.perf_counter()
    op_lanes = (0, 1, 2, 5) if small else OP_LANES
    jobs = ((5, 1), (5, 2), (5, 8)) if small else MILLER_JOBS
    jobs_alone = option("--jobs")
    if jobs_alone:
        jobs = tuple(tuple(map(int, j.split(":")))
                     for j in jobs_alone.split(","))
        op_lanes = ()
    be._init_consts()

    if "--stage" in sys.argv:
        out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
               "stage_split": []}
        pairs = option("--pairs")
        same = _stage_split(out, tuple(map(int, pairs.split(","))) if pairs
                            else (2, 5) if small else STAGE_PAIRS)
        print(json.dumps(out))
        return 0 if same else 1

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "ops": [], "miller_loop_product": []}

    loops = _op_loops()
    for lanes in op_lanes:
        inputs = _op_inputs(lanes, lanes)
        for name, (fn, pick) in loops.items():
            if lanes == 0 and name in ("_line_mul_line", "_dbl_step"):
                continue                      # never run without a pair axis
            med, low = _timed(fn, pick(*inputs))
            out["ops"].append({
                "op": name, "lanes": lanes,
                "s_per_call": med / CALLS_PER_LOOP,
                "min_s_per_call": low / CALLS_PER_LOOP,
            })
            print(json.dumps(out["ops"][-1]), flush=True)
        _save(out)

    shipped = po.MILLER_LANES, po.MILLER_WIDE_FROM
    operands, want = {}, {}
    for n, lanes in jobs:
        if time.perf_counter() - started > budget:
            out["miller_loop_product"].append(
                {"pairs": n, "accumulators": lanes, "skipped": "budget"})
            continue
        if n not in operands:
            operands[n] = _pairs(n, n)
        p, q, mask = operands[n]
        po.MILLER_LANES, po.MILLER_WIDE_FROM = lanes, _wide_from(lanes)
        w, in_step, after = po.miller_lane_plan(n)
        assert w == lanes, (n, lanes, w)
        fn = jax.jit(lambda p, q, m: po.miller_loop_product(p, q, m))
        t0 = time.perf_counter()
        med, low = _timed(fn, p, q, mask)
        first = time.perf_counter() - t0 - med * REPS
        got = np.asarray(fn(p, q, mask))
        want.setdefault(n, got)               # W = 1 leads each pair count
        out["miller_loop_product"].append({
            "pairs": n, "accumulators": w,
            "lines_per_accumulator": po._lines_per_accumulator(n, w),
            "in_step_levels": in_step, "after_loop_levels": after,
            "s": med, "min_s": low,
            "first_call_s": first,
            "same_limbs_as_w1": bool(np.array_equal(got, want[n])),
        })
        print(json.dumps(out["miller_loop_product"][-1]), flush=True)
        _save(out)

    if not jobs_alone:
        _traced_stage(out, 5 if small else TRACED_PAIRS)
    po.MILLER_LANES, po.MILLER_WIDE_FROM = shipped

    _save(out)
    print(json.dumps(out))
    ran = [r for r in out["miller_loop_product"] if "s" in r]
    return 0 if all(r["same_limbs_as_w1"] for r in ran) else 1


if __name__ == "__main__":
    sys.exit(main())
