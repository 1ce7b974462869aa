#!/usr/bin/env python
"""Time the real jitted verify stages on the attached device, per stage.

Thin CLI over lighthouse_tpu/observability/device.profile_stages — the ONE
owner of per-stage timing (the same attribution path `bn --device-trace`
and bench.py use), so script-measured and runtime-measured stage numbers
can never diverge. Each run also feeds the jaxbls_stage_device_seconds /
jaxbls_stage_compile_seconds families and (unless --no-analytics) captures
the compiled programs' flops/bytes/HBM into the xla_program_* gauges and
the autotune profile snapshot, printing roofline utilization against the
device's ESTIMATED peak.

Usage: python scripts/profile_components.py [--sets N] [--pks M] [--reps R]
       [--msm] [--no-analytics]

--msm appends the variable-base vs fixed-base comb MSM comparison at KZG
scale (the one measurement here that is not stage timing).
"""

import argparse
import json
import sys

sys.path.insert(0, ".")


def run_msm_comparison(reps: int) -> None:
    """Variable-base double-and-add vs the fixed-base comb (msm.py) — the
    VERDICT r4 #4 "≥4x at 4096 points" measurement, runnable on the real
    chip when a window opens."""
    import random as _random
    import time as _time

    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381.constants import R

    n_msm = 1024  # keep host point generation tolerable; scale on chip
    _rng = _random.Random(9)
    base = [cv.g1_mul(cv.G1_GEN, _rng.randrange(1, R)) for _ in range(64)]
    pts = [base[i % 64] for i in range(n_msm)]  # repeated points: fine for timing
    scalars = [_rng.randrange(0, R) for _ in range(n_msm)]
    backend = bls_api.set_backend("jax")

    t0 = _time.time()
    r_var = backend.g1_msm(pts, scalars)
    print(f"g1_msm variable-base ({n_msm} pts) warm+run: "
          f"{_time.time()-t0:.2f}s", file=sys.stderr)
    for tag in ("cold (incl. table build)", "warm"):
        t0 = _time.time()
        r_fix = backend.g1_msm_fixed(pts, scalars)
        print(f"g1_msm_fixed ({n_msm} pts) {tag}: "
              f"{_time.time()-t0:.2f}s", file=sys.stderr)
    assert r_var == r_fix, "MSM paths disagree"
    for _ in range(reps):
        t0 = _time.time()
        backend.g1_msm(pts, scalars)
        tv = _time.time() - t0
        t0 = _time.time()
        backend.g1_msm_fixed(pts, scalars)
        tf = _time.time() - t0
        print(f"msm steady: variable {tv:.3f}s fixed {tf:.3f}s "
              f"({tv/max(tf,1e-9):.1f}x)", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, default=64)
    ap.add_argument("--pks", type=int, default=128)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--msm", action="store_true",
                    help="also run the variable- vs fixed-base MSM comparison")
    ap.add_argument("--no-analytics", action="store_true",
                    help="skip compiled-program cost/memory capture")
    args = ap.parse_args()

    from lighthouse_tpu.utils.jaxcfg import setup_compilation_cache

    setup_compilation_cache()

    import jax

    print(f"devices: {jax.devices()}", file=sys.stderr)

    from lighthouse_tpu.observability import device as obs_device

    report = obs_device.profile_stages(
        args.sets, args.pks, reps=args.reps, analytics=not args.no_analytics
    )
    n, m = report["bucket"]
    print(f"bucket {n}x{m} on {report['device_kind']} "
          f"({args.reps} timed reps/stage; first rep = residual compile):",
          file=sys.stderr)
    for stage in obs_device.STAGES:
        st = report["stages"].get(stage)
        if not st:
            continue
        roof = st.get("roofline") or {}
        util = (
            f"   flops-util {roof['flops_utilization']:.4%}"
            f"  hbm-util {roof['hbm_utilization']:.4%}"
            f"  bound={roof['bound']} (vs ESTIMATED peak)"
            if "flops_utilization" in roof else ""
        )
        print(f"{stage:10s} {st['mean_ms']:9.1f} ms"
              f"   (compile {st.get('compile_s', 0.0):6.1f}s){util}")
    print(json.dumps(report, indent=1))

    if args.msm:
        run_msm_comparison(args.reps)


if __name__ == "__main__":
    main()
