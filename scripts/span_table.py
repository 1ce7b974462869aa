#!/usr/bin/env python3
"""Where a work unit's time went, span by span, from the tracer's ring.

    from scripts.span_table import render
    print(render(TRACER.snapshot_ring()))

For every work kind in the ring: the median over the kind's units of each
span's SELF seconds (its duration less what its children cover,
`Trace.self_seconds`), beside the spans of the unit of median duration
and of the slowest unit, with each span's parent. The last column is the
slowest unit's self time less the median over units: the phase that
carries a long unit's excess is the row where that column holds it.

As a command it runs one cell of the benchmark in this process, untraced,
keeps the units that finished inside the measured window, and prints the
benchmark's result line, then the table (and the numbers as JSON with
`--json`):

    chiprun -- python3 scripts/span_table.py --workload block_import_131 \\
        --seed 7 --seconds 50 --json chiprun_out/spans_block_7.json

No benchmark file imports this one; it imports `benchmarks/run.py` only
under `main()`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from collections import deque

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def unit_rows(trace) -> list:
    """[(depth, name, parent, t0, seconds, self_seconds, args)] of one
    unit, parents before their children, in order of start."""
    spans = trace.spans
    selfs = trace.self_seconds()
    rows = []

    def walk(parent, lo, hi, depth):
        kids = [i for i, s in enumerate(spans)
                if s[4] == parent and lo <= s[1] and s[2] <= hi]
        for i in sorted(kids, key=lambda i: spans[i][1]):
            name, t0, t1, args, _ = spans[i]
            rows.append((depth, name, parent, t0, t1 - t0, selfs[i], args))
            walk(name, t0, t1, depth + 1)

    walk(None, float("-inf"), float("inf"), 0)
    return rows


def summarize(traces) -> dict:
    """{kind: {"units", "seconds" and "self_s_by_unit" (every unit, in
    order of duration), "median_self_s": {span: s}, "median_unit",
    "slowest_unit"}}; a unit is {"trace_id", "seconds", "rows"}."""
    by_kind: dict = {}
    for tr in traces:
        if tr.spans:
            by_kind.setdefault(tr.kind, []).append(tr)
    out = {}
    for kind, units in by_kind.items():
        units.sort(key=lambda tr: tr.duration())
        per_span: dict = {}     # span -> its self seconds in each unit
        per_unit = []           # the same by unit, in order of duration
        for tr in units:
            mine: dict = {}
            for (name, *_), s in zip(tr.spans, tr.self_seconds()):
                mine[name] = mine.get(name, 0.0) + s
            for name, s in mine.items():
                per_span.setdefault(name, []).append(s)
            per_unit.append(mine)

        def unit(tr):
            return {"trace_id": tr.trace_id, "seconds": tr.duration(),
                    "meta": {k: str(v) for k, v in tr.meta.items()},
                    "rows": unit_rows(tr)}

        out[kind] = {
            "units": len(units),
            "seconds": [tr.duration() for tr in units],
            "self_s_by_unit": per_unit,
            "median_self_s": {n: statistics.median(v)
                              for n, v in per_span.items()},
            "share_of_units": {n: len(v) / len(units)
                               for n, v in per_span.items()},
            "median_unit": unit(units[(len(units) - 1) // 2]),
            "slowest_unit": unit(units[-1]),
        }
    return out


def render(traces) -> str:
    lines = []
    for kind, k in sorted(summarize(traces).items()):
        med, slow = k["median_unit"], k["slowest_unit"]
        lines.append(
            f"== {kind}: {k['units']} units; median unit "
            f"{med['seconds'] * 1e3:.3f} ms (trace {med['trace_id']}), "
            f"slowest {slow['seconds'] * 1e3:.3f} ms "
            f"(trace {slow['trace_id']}); ms")
        lines.append(
            f"{'span':44s} {'parent':24s} {'med.self':>9s} "
            f"{'median unit':>19s} {'slowest unit':>19s} {'excess':>9s}")
        med_rows = {r[1]: r for r in med["rows"]}
        seen = set()
        for depth, name, parent, _t0, secs, self_s, _args in slow["rows"]:
            seen.add(name)
            m = med_rows.get(name)
            over = k["median_self_s"].get(name, 0.0)
            lines.append(
                f"{'  ' * depth + name:44s} {str(parent):24s} "
                f"{over * 1e3:9.3f} "
                + (f"{m[4] * 1e3:9.3f} {m[5] * 1e3:9.3f} " if m
                   else f"{'-':>9s} {'-':>9s} ")
                + f"{secs * 1e3:9.3f} {self_s * 1e3:9.3f} "
                f"{(self_s - over) * 1e3:9.3f}")
        for depth, name, parent, _t0, secs, self_s, _args in med["rows"]:
            if name not in seen:
                lines.append(
                    f"{'  ' * depth + name:44s} {str(parent):24s} "
                    f"{k['median_self_s'].get(name, 0.0) * 1e3:9.3f} "
                    f"{secs * 1e3:9.3f} {self_s * 1e3:9.3f} "
                    f"{'-':>9s} {'-':>9s} {'-':>9s}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--json", help="also write the numbers here")
    args = ap.parse_args(argv)

    for p in (REPO_ROOT, os.path.join(REPO_ROOT, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench  # benchmarks/run.py

    from lighthouse_tpu.observability import TRACER

    # the ring holds 256 units; a window of this cell may hold more
    TRACER.ring = deque(maxlen=16384)
    window: dict = {}
    open_window, close_window = (bench.Harness.open_window,
                                 bench.Harness.close_window)

    def opened(h):
        window["t_open"] = open_window(h)
        return window["t_open"]

    def closed(h):
        t = close_window(h)
        window["units"] = [tr for tr in TRACER.snapshot_ring()
                           if tr.spans and tr.spans[0][1] >= window["t_open"]]
        # the window's means of the families no span carries, untraced
        window["mean_ms"] = {
            family: bench.layer_reader.evaluate(
                {"family": family, "reduce": "mean_ms"}, h.before, h.after,
                {}, {})
            for family in ("jaxbls_dispatch_device_seconds",
                           "jaxbls_marshal_seconds",
                           "beacon_processor_exec_lock_wait_seconds")}
        return t

    bench.Harness.open_window, bench.Harness.close_window = opened, closed
    result = bench.measure(args.workload, args.seed, args.seconds, False,
                           bench.require_tpu())
    bench.emit(**result)
    bench.emit(step="window_mean_ms", **window["mean_ms"])
    print(render(window["units"]))
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "result": result, "window_mean_ms": window["mean_ms"],
                       "kinds": summarize(window["units"])},
                      f)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
