#!/usr/bin/env python3
"""The output check of one cell, in ONE process on ONE set-up: sound runs
over many seeds must all come out `correct`, and every damaged operand the
cell's driver names in its `CONTROLS` (the control) must turn `correct` false.

    python benchmarks/check_outputs.py --workload <cell> [--seeds 12]
        [--seconds 5] [--control-seeds 3] [--tamper a,b]

For this integer system the analogue of "a lower precision the check has to
fail" is a damaged operand at the cell's own size and load: a swapped
signature or a flipped message byte in one batch of the window, one leaf
flipped after the expected root was taken. The first run compiles; every
later one finds the programs in the process. Needs the TPU, as run.py does.
Last line: {"ok": ..., "sound": [...], "control": [...]}; exit 0 only if ok.
"""

from __future__ import annotations

import argparse
import sys
import time

import run as bench_run
from common import emit

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_500_000)
    ap.add_argument("--tamper", default=None,
                    help="comma-separated controls; default: the driver's")
    args = ap.parse_args(argv)

    devices = bench_run.require_tpu()
    driver = bench_run.load_driver(
        bench_run.load_json("workloads", args.workload)["driver"])
    kinds = (args.tamper.split(",") if args.tamper
             else list(getattr(driver, "CONTROLS", ())))
    sound, control = [], []
    seed = args.first_seed
    for _ in range(args.seeds):
        res = bench_run.measure(args.workload, seed, args.seconds, False,
                                devices, t_process=time.perf_counter())
        sound.append({"seed": seed, "correct": res["correct"],
                      "attempted": res["attempted"], "failed": res["failed"]})
        seed += 1
    for kind in kinds:
        for _ in range(args.control_seeds):
            res = bench_run.measure(
                args.workload, seed, args.seconds, False, devices,
                t_process=time.perf_counter(),
                param_overrides={"tamper_window": kind})
            control.append({"seed": seed, "tamper": kind,
                            "correct": res["correct"],
                            "attempted": res["attempted"],
                            "failed": res["failed"]})
            seed += 1
    ok = (all(r["correct"] for r in sound)
          and all(not r["correct"] for r in control)
          and len(control) > 0)
    emit(ok=ok, workload=args.workload, sound=sound, control=control)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
