"""Compiled-program analytics, roofline attribution, and the bench trend
gate.

Three jobs, one module, zero jax at import time:

1. **Program analytics** — at stage compile time (warm_stages, or the
   first attributed dispatch per bucket) capture the compiled XLA
   program's `cost_analysis()` / `memory_analysis()`: flops, bytes
   accessed, and the HBM footprint split (argument/output/temp/generated
   code). Each capture lands as labeled `xla_program_*` gauges, in the
   autotune profiler's per-bucket recorders (so the persisted device
   profile carries the program shape next to the measured timings —
   autotune/profile.py `programs`), and in the in-memory snapshot
   bench.py writes into BENCH artifacts. The `.lower().compile()` pair
   rides the persistent XLA compilation cache (utils/jaxcfg.py), so a
   stage that already compiled via the normal call path re-traces but
   never re-compiles.

2. **Roofline** — `roofline(stats, secs, device_kind)` turns a program's
   flops/bytes plus a measured stage time into achieved-FLOP/s and
   achieved-bytes/s against an ESTIMATED peak for the device kind
   (`PEAK_ESTIMATES`, keyed by the exact `device_kind`; an unknown
   kind gets no roofline row). The verdict decomposes "0.143x est
   blst" into per-stage utilization: a stage at 2% of peak flops and 60%
   of HBM bandwidth is memory-bound and wants layout work, not math.
   Peaks are estimates — every roofline dict says so.

3. **Bench trend** — `trend_report()` parses the checked-in
   `BENCH_r*.json` / `MULTICHIP_r*.json` round series plus the current
   `BENCH_MATRIX.json`, renders carried-forward rounds distinctly
   (a round whose record is skipped — `"skipped": true`, a zero value,
   or an UNAVAILABLE marker — inherits the latest fresh value,
   flagged, so a stale number is never read as a fresh measurement),
   computes fresh-to-fresh deltas, and flags >threshold regressions.
   `check()` is the gate: nonzero on regression. `bn perf report` and
   `scripts/perf_trend.py` are thin CLIs over `run_report()`; the
   aggregate also surfaces on `/lighthouse_tpu/pipeline` via
   `trend_summary()`. All stdlib — runs on CPU with no device attached.
"""

from __future__ import annotations

import glob
import json
import os
import re
import threading

from ..utils.metrics import REGISTRY

# ------------------------------------------------------------------ metrics

XLA_PROGRAM_FLOPS = REGISTRY.gauge_vec(
    "xla_program_flops",
    "XLA cost_analysis flop count of the compiled stage program, by jit "
    "stage and padding bucket",
    ("stage", "n_sets", "n_pks"),
)
XLA_PROGRAM_BYTES_ACCESSED = REGISTRY.gauge_vec(
    "xla_program_bytes_accessed",
    "XLA cost_analysis bytes-accessed estimate of the compiled stage "
    "program, by jit stage and padding bucket",
    ("stage", "n_sets", "n_pks"),
)
XLA_PROGRAM_HBM_BYTES = REGISTRY.gauge_vec(
    "xla_program_hbm_bytes",
    "compiled-program memory footprint from XLA memory_analysis, by jit "
    "stage, padding bucket and region (argument/output/temp/generated_code)",
    ("stage", "n_sets", "n_pks", "region"),
)

_lock = threading.Lock()
_programs: dict = {}       # (stage, (n, m)) -> stats dict
_analytics_override: bool | None = None

#: published peak (bf16 flops/s, HBM bytes/s) keyed by the EXACT
#: `device_kind` JAX reports (Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 819 GB/s HBM; a v5e chip reports "TPU v5 lite").
#: A kind that is not here has no roofline: an error, never a borrowed
#: peak.
PEAK_ESTIMATES = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}
_unknown_kinds_logged: set = set()


def set_analytics(on: bool | None) -> bool | None:
    """Force program-analytics capture on/off; returns the previous
    override so scoped callers can restore it."""
    global _analytics_override
    prev = _analytics_override
    _analytics_override = None if on is None else bool(on)
    return prev


def analytics_enabled() -> bool:
    if _analytics_override is not None:
        return _analytics_override
    env = os.environ.get("LIGHTHOUSE_TPU_PROGRAM_ANALYTICS", "").lower()
    return env in ("1", "on", "yes", "true")


def maybe_capture_program(stage: str, jitted_fn, args, bucket: tuple):
    """capture_program once per (stage, bucket); later calls are free."""
    key = (stage, (int(bucket[0]), int(bucket[1])))
    with _lock:
        if key in _programs:
            return _programs[key]
    return capture_program(stage, jitted_fn, args, bucket)


def capture_program(stage: str, jitted_fn, args, bucket: tuple) -> dict | None:
    """Lower+compile one jit stage at concrete args and record its cost/
    memory analysis. A stage served as several programs run one after the
    other (stage 4 on one chip, `_PairingPrograms`: `.lower` gives a tuple;
    every other stage gives one lowering) records their sum of flops, bytes
    accessed and generated code, and of each memory region the largest —
    the programs never hold the device together. Best-effort: any failure
    returns None and records nothing (a node on an exotic backend must
    not lose the verify path to a diagnostics call)."""
    n, m = int(bucket[0]), int(bucket[1])
    try:
        lowered = jitted_fn.lower(*args)
        stats = {"flops": 0.0, "bytes_accessed": 0.0}
        for low in lowered if isinstance(lowered, tuple) else (lowered,):
            compiled = low.compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            ca = ca or {}
            stats["flops"] += float(ca.get("flops", 0.0))
            stats["bytes_accessed"] += float(ca.get("bytes accessed", 0.0))
            ma = compiled.memory_analysis()
            if ma is None:
                continue
            for region in ("argument", "output", "temp"):
                stats[f"{region}_bytes"] = max(
                    stats.get(f"{region}_bytes", 0),
                    int(getattr(ma, f"{region}_size_in_bytes", 0)),
                )
            stats["generated_code_bytes"] = stats.get(
                "generated_code_bytes", 0
            ) + int(getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception:
        return None
    record_program(stage, bucket, stats)
    return stats


def record_program(stage: str, bucket: tuple, stats: dict) -> None:
    """Publish one program's stats: gauges, snapshot, autotune recorder."""
    n, m = int(bucket[0]), int(bucket[1])
    XLA_PROGRAM_FLOPS.labels(stage, n, m).set(stats.get("flops", 0.0))
    XLA_PROGRAM_BYTES_ACCESSED.labels(stage, n, m).set(
        stats.get("bytes_accessed", 0.0)
    )
    for region in ("argument", "output", "temp", "generated_code"):
        v = stats.get(f"{region}_bytes")
        if v is not None:
            XLA_PROGRAM_HBM_BYTES.labels(stage, n, m, region).set(v)
    with _lock:
        _programs[(stage, (n, m))] = dict(stats)
    try:
        from ..autotune import profiler

        profiler.observe_program(n, m, stage, stats)
    except Exception:
        pass  # diagnostics must never raise into the dispatch path


def program_stats(stage: str, bucket: tuple) -> dict | None:
    with _lock:
        st = _programs.get((stage, (int(bucket[0]), int(bucket[1]))))
    return dict(st) if st else None


def program_snapshot() -> dict:
    """{"<n>x<m>": {stage: stats}} for everything captured so far."""
    with _lock:
        items = list(_programs.items())
    out: dict = {}
    for (stage, (n, m)), stats in items:
        out.setdefault(f"{n}x{m}", {})[stage] = dict(stats)
    return out


def reset_programs() -> None:
    """Drop captured program stats (tests)."""
    with _lock:
        _programs.clear()


# ----------------------------------------------------------------- roofline


def peak_for(device_kind: str | None) -> tuple | None:
    """(peak flops/s, peak HBM bytes/s) of a device kind in the table;
    None — with one logged error per kind — for any other."""
    peaks = PEAK_ESTIMATES.get(device_kind or "")
    if peaks is None and device_kind not in _unknown_kinds_logged:
        _unknown_kinds_logged.add(device_kind)
        from ..utils.logging import get_logger

        get_logger("perf").error(
            "no published peaks for this device kind; no roofline rows "
            "will be produced (add it to PEAK_ESTIMATES with its source)",
            device_kind=device_kind,
        )
    return peaks


def roofline(stats: dict, secs: float, device_kind: str | None) -> dict | None:
    """Achieved vs estimated-peak throughput for one stage execution.

    `stats` is a capture_program dict; `secs` a measured wall time for
    one execution of that program. Returns achieved flops/s + bytes/s,
    their fractions of the device kind's published peaks, and which wall
    the stage is closer to ("compute" vs "memory") — or None where the
    time is not positive or the kind has no peaks on record."""
    if not secs or secs <= 0:
        return None
    peaks = peak_for(device_kind)
    if peaks is None:
        return None
    pf, pb = peaks
    flops = float(stats.get("flops") or 0.0)
    byts = float(stats.get("bytes_accessed") or 0.0)
    fu = flops / secs / pf
    bu = byts / secs / pb
    return {
        "seconds": round(secs, 6),
        "achieved_gflops_per_sec": round(flops / secs / 1e9, 3),
        "achieved_gbytes_per_sec": round(byts / secs / 1e9, 3),
        "peak_note": "peaks are the published bf16/HBM figures "
                     "(PEAK_ESTIMATES), not measurements",
        "flops_utilization": round(fu, 6),
        "hbm_utilization": round(bu, 6),
        "bound": "memory" if bu > fu else "compute",
        "device_kind": device_kind,
    }


# ------------------------------------------------------------- bench trend

#: every vs_est_* denominator in bench.py is an estimate; the report
#: header must say so (BASELINE.md / bench.py baseline_note)
EST_CAVEAT = (
    "vs_est_*/vs_baseline ratios divide by ESTIMATED single-core "
    "blst/c-kzg throughputs (EST_* constants in bench.py) — "
    "estimated, not measured"
)

DEFAULT_REGRESSION_THRESHOLD = 0.10


def default_root() -> str:
    """Repo root (where the BENCH_r*/MULTICHIP_r* artifacts live)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def _round_files(root: str, pattern: str) -> list:
    out = []
    for path in glob.glob(os.path.join(root, pattern)):
        m = re.search(r"_r(\d+)\.json$", path)
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def load_bench_rounds(root: str | None = None) -> list:
    """BENCH_r*.json -> round dicts, oldest first, with skipped rounds
    carrying forward the latest fresh value (flagged, never silently)."""
    root = root or default_root()
    rounds = []
    for n, path in _round_files(root, "BENCH_r*.json"):
        name = os.path.basename(path)
        try:
            with open(path) as f:
                parsed = (json.load(f) or {}).get("parsed") or {}
        except (OSError, json.JSONDecodeError, AttributeError):
            parsed = {}
        metric = str(parsed.get("metric", ""))
        try:
            value = float(parsed.get("value") or 0.0)
        except (TypeError, ValueError):
            value = 0.0
        vs_est = parsed.get("vs_baseline")
        # a round is FRESH only when it measured something: an explicit
        # skipped flag, a zero value, or a device-outage marker in the
        # metric string all mean "no measurement this run"
        skipped = (
            bool(parsed.get("skipped"))
            or value <= 0.0
            or "UNAVAILABLE" in metric.upper()
            or "SKIPPED" in metric.upper()
        )
        try:
            c1_p50 = float(parsed.get("config1_p50_ms") or 0.0) or None
        except (TypeError, ValueError):
            c1_p50 = None
        rounds.append(
            {
                "round": n,
                "source": name,
                "fresh": not skipped and bool(parsed),
                "value": value if not skipped else (value or None),
                "vs_est": vs_est if not skipped else None,
                "raw_vs_est": vs_est,
                "note": parsed.get("note"),
                # the urgent-path latency series (bench.py config 1,
                # recorded in the headline JSON since r8): only a FRESH
                # round's p50 may enter the latency trend
                "config1_p50_ms": c1_p50 if not skipped else None,
                # executor config of the run (depth/donation/msm window)
                "pipeline": parsed.get("pipeline"),
            }
        )
    last_fresh = None
    for r in rounds:
        if r["fresh"]:
            last_fresh = r
            r["carried"] = False
            continue
        if r["value"]:
            # the artifact itself carried a value forward (bench.py did,
            # r5 to PR 21): keep its value AND vs ratio, and
            # name the source round it cites (falling back to the latest
            # fresh round we saw)
            r["carried"] = True
            m = re.search(r"BENCH_r\d+\.json", r.get("note") or "")
            r["carried_from"] = m.group(0) if m else (
                last_fresh["source"] if last_fresh else "artifact carry-forward"
            )
            if r["vs_est"] is None:
                r["vs_est"] = r["raw_vs_est"]
        elif last_fresh is not None:
            r["carried"] = True
            r["carried_from"] = last_fresh["source"]
            r["value"] = last_fresh["value"]
            r["vs_est"] = last_fresh["vs_est"]
        else:
            r["carried"] = False
    for r in rounds:
        r.pop("raw_vs_est", None)
    return rounds


def load_multichip_rounds(root: str | None = None) -> list:
    root = root or default_root()
    rounds = []
    for n, path in _round_files(root, "MULTICHIP_r*.json"):
        try:
            with open(path) as f:
                d = json.load(f) or {}
        except (OSError, json.JSONDecodeError):
            d = {}
        rounds.append(
            {
                "round": n,
                "source": os.path.basename(path),
                "skipped": bool(d.get("skipped")),
                "ok": bool(d.get("ok")),
                "n_devices": d.get("n_devices"),
            }
        )
    return rounds


_RATE_KEYS = (
    "sets_per_sec", "verifies_per_sec", "blocks_per_sec", "blobs_per_sec",
    "roots_per_sec", "epochs_per_sec",
)

#: key families write_loadtest_rows accepts: loadtest_* rows come from
#: `bn loadtest` snapshots; state_root / epoch_transition rows from
#: scripts/bench_state_root.py --bench-matrix — the second workload's
#: bench rows beside the BLS configs
WORKLOAD_ROW_PREFIXES = ("loadtest_", "state_root", "epoch_transition")

#: bounded per-row measurement history (the state-root p50 trend series
#: reads it — every appended entry is a fresh measurement by construction)
MAX_ROW_HISTORY = 12


def write_loadtest_rows(rows: dict, smoke: bool = True,
                        root: str | None = None) -> str:
    """Merge measured workload rows into the BENCH_MATRIX schema — the
    device-free bench seam: `bn loadtest` (flood / the --mesh-devices
    sweep, and any future on-TPU soak) snapshots its measured sets/s +
    p50 here, and `bench_state_root.py --bench-matrix` lands the
    state_root / epoch_transition rows of the second device workload the
    same way — so any soak or host-provable bench doubles as a bench
    round and the trend gate reads the rows as FRESH measurements.
    Read-merge-write: bench.py's configs are preserved; only
    WORKLOAD_ROW_PREFIXES keys are touched, and rows carrying a p50
    accumulate a bounded `history` of fresh entries (the fresh-to-fresh
    series the state-root p50 trend gate checks). Smoke runs land in the
    gitignored-by-convention *_SMOKE variant, same rule as bench.py — a
    CPU harness must never clobber the on-chip artifact of record."""
    root = root or default_root()
    name = "BENCH_MATRIX_SMOKE.json" if smoke else "BENCH_MATRIX.json"
    path = os.path.join(root, name)
    try:
        with open(path) as f:
            matrix = json.load(f) or {}
    except (OSError, json.JSONDecodeError):
        matrix = {}
    for key, row in rows.items():
        key = str(key)
        if not key.startswith(WORKLOAD_ROW_PREFIXES):
            raise ValueError(
                "workload matrix rows must be keyed "
                f"{'/'.join(WORKLOAD_ROW_PREFIXES)}*: {key!r}"
            )
        row = dict(row, source=row.get("source", "loadtest"))
        if (
            row.get("p50_ms") is not None
            or row.get("scheduler_ratio") is not None
        ):
            prev = matrix.get(key)
            history = list(prev.get("history") or []) if isinstance(
                prev, dict
            ) else []
            entry = {
                "measured_unix": row.get("measured_unix"),
                "fresh": True,
            }
            if row.get("p50_ms") is not None:
                entry["p50_ms"] = row["p50_ms"]
            if row.get("scheduler_ratio") is not None:
                # the capacity-control proof's controller-vs-static-optimal
                # ratio (loadgen/capacity.py): the capacity_ratio trend
                # series reads this history fresh-to-fresh
                entry["scheduler_ratio"] = row["scheduler_ratio"]
            # measurement config rides each entry so the trend gate only
            # compares like with like — a host-vs-device (or resized)
            # re-measurement, or a different harness (bench_state_root vs
            # a loadtest soak), is a configuration change, not a regression
            for k in ("hash_backend", "validators", "source", "scenario"):
                if row.get(k) is not None:
                    entry[k] = row[k]
            history.append(entry)
            row["history"] = history[-MAX_ROW_HISTORY:]
        matrix[key] = row
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(matrix, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_matrix(root: str | None = None, name: str = "BENCH_MATRIX.json") -> dict:
    """Per-config summary of the current measurement matrix, with
    config*_skipped / config*_error flags kept distinct from measured
    configs (a skipped config must never read as a measured one).
    loadtest_* rows (write_loadtest_rows) parse like configs and carry
    their `source: loadtest` tag through — they are fresh by
    construction (the writer stamps them at measurement time)."""
    root = root or default_root()
    try:
        with open(os.path.join(root, name)) as f:
            matrix = json.load(f) or {}
    except (OSError, json.JSONDecodeError):
        return {}
    out: dict = {}
    for key, val in matrix.items():
        m = re.match(
            r"^(config\d+|loadtest_\w+|state_root\w*|epoch_transition\w*)"
            r"(?:_(skipped|error))?$",
            key,
        )
        if not m:
            m = re.match(r"^(config\d+)(?:_(skipped|error))?", key)
        if not m:
            continue
        config, flag = m.group(1), m.group(2)
        entry = out.setdefault(config, {})
        if flag:
            entry[flag] = val
            continue
        if not isinstance(val, dict):
            continue
        entry["name"] = key
        for rk in _RATE_KEYS:
            # a null rate (hand-edited or legacy artifact) must degrade to
            # "no measurement", not crash every later trend read
            if val.get(rk) is not None:
                entry["rate"] = float(val[rk])
                entry["rate_unit"] = rk
                break
        for k in ("p50_ms", "p99_ms"):
            if k in val:
                entry[k] = val[k]
        for k in ("source", "n_devices", "measured_unix", "history",
                  "scheduler_ratio"):
            if k in val:
                entry[k] = val[k]
        for k, v in val.items():
            if k.startswith("vs_est"):
                entry["vs_est"] = v
                entry["vs_est_key"] = k
    return out


def trend_report(
    root: str | None = None,
    threshold: float = DEFAULT_REGRESSION_THRESHOLD,
) -> dict:
    """The full per-config trend + regression verdict over the checked-in
    artifacts. Regressions compare FRESH values only — carried-forward
    rounds can neither cause nor mask one."""
    root = root or default_root()
    bench = load_bench_rounds(root)
    multichip = load_multichip_rounds(root)
    matrix = load_matrix(root)
    regressions = []

    fresh = [r for r in bench if r["fresh"]]
    deltas = []
    for prev, cur in zip(fresh, fresh[1:]):
        delta = (cur["value"] - prev["value"]) / prev["value"]
        deltas.append(
            {
                "config": "headline",
                "from": prev["source"],
                "to": cur["source"],
                "delta_pct": round(delta * 100.0, 2),
            }
        )
        if delta < -threshold:
            regressions.append(
                {
                    "config": "headline",
                    "prev": prev["value"],
                    "cur": cur["value"],
                    "from": prev["source"],
                    "to": cur["source"],
                    "delta_pct": round(delta * 100.0, 2),
                }
            )

    # config1 urgent-path p50 (ms, LOWER is better): a fresh-to-fresh
    # latency increase past the threshold gates CI exactly like a
    # throughput drop — raw speed regressions on the urgent lane must
    # not hide behind a healthy headline
    lat_fresh = [r for r in fresh if r.get("config1_p50_ms")]
    lat_deltas = []
    for prev, cur in zip(lat_fresh, lat_fresh[1:]):
        delta = (
            cur["config1_p50_ms"] - prev["config1_p50_ms"]
        ) / prev["config1_p50_ms"]
        lat_deltas.append(
            {
                "config": "config1_p50",
                "from": prev["source"],
                "to": cur["source"],
                "delta_pct": round(delta * 100.0, 2),
            }
        )
        if delta > threshold:
            regressions.append(
                {
                    "config": "config1_p50",
                    "prev": prev["config1_p50_ms"],
                    "cur": cur["config1_p50_ms"],
                    "from": prev["source"],
                    "to": cur["source"],
                    "delta_pct": round(delta * 100.0, 2),
                }
            )

    # state-root p50 (ms, LOWER is better) — the second workload's trend
    # series, read from the bounded histories of EVERY state_root* row
    # (the 16k row keeps the historic unsuffixed key; scale variants like
    # state_root_1m land beside it — same-config gating below already
    # separates them by validator count). Every entry written by
    # bench_state_root.py --bench-matrix is a fresh measurement; entries
    # marked fresh=false — a hand-carried or legacy value — render as
    # carried and can neither cause nor mask a regression, the
    # config1_p50 contract.
    # row histories are append-ordered (write_loadtest_rows), which IS the
    # chronology within a row; rows never share a config key (validators
    # differ), so concatenation order across rows cannot create a
    # cross-row pair below — no re-sort by measured_unix (tests use it as
    # an opaque stamp, not a clock)
    sr_entries = [
        e
        for key in sorted(matrix)
        if key == "state_root" or key.startswith("state_root_")
        for e in ((matrix.get(key) or {}).get("history") or [])
        if isinstance(e, dict)
    ]
    sr_fresh = [
        e for e in sr_entries if e.get("fresh", True) and e.get("p50_ms")
    ]
    sr_deltas = []
    # each fresh entry compares against the MOST RECENT prior fresh entry
    # of the SAME measurement config (backend/validators/harness) — a
    # config flip (host->device, resized run, bench vs loadtest) is not a
    # regression, and an interleaved flip must not mask the next
    # same-config comparison either
    _last_by_config: dict = {}
    for cur in sr_fresh:
        cfg = tuple(
            cur.get(k) for k in ("hash_backend", "validators", "source")
        )
        prev = _last_by_config.get(cfg)
        _last_by_config[cfg] = cur
        if prev is None:
            continue
        delta = (cur["p50_ms"] - prev["p50_ms"]) / prev["p50_ms"]
        sr_deltas.append(
            {"config": "state_root_p50", "delta_pct": round(delta * 100.0, 2)}
        )
        if delta > threshold:
            regressions.append(
                {
                    "config": "state_root_p50",
                    "prev": prev["p50_ms"],
                    "cur": cur["p50_ms"],
                    "from": f"history@{prev.get('measured_unix')}",
                    "to": f"history@{cur.get('measured_unix')}",
                    "delta_pct": round(delta * 100.0, 2),
                }
            )

    # capacity controller-vs-static-optimal ratio (HIGHER is better) — the
    # closed-loop scheduler's trend series, read from the loadtest_* rows'
    # histories (loadgen/driver.py _drive_capacity writes them). A
    # fresh-to-fresh DROP past the threshold gates CI: a scheduler change
    # that loses ground against the same static-optimal reference is a
    # controller regression even while the absolute gate still passes.
    # Same-config comparison only (scenario/validators/source stamped per
    # entry), the state_root_p50 contract.
    cap_entries = []
    cap_deltas = []
    for cfg_key in sorted(matrix):
        if not cfg_key.startswith("loadtest_"):
            continue
        hist = [
            e for e in (matrix[cfg_key].get("history") or [])
            if isinstance(e, dict) and e.get("scheduler_ratio") is not None
        ]
        if not hist:
            continue
        cap_entries.extend(dict(e, row=cfg_key) for e in hist)
        _last: dict = {}
        for cur in hist:
            if not cur.get("fresh", True):
                continue
            cfg = tuple(
                cur.get(k) for k in ("scenario", "validators", "source")
            )
            prev = _last.get(cfg)
            _last[cfg] = cur
            if prev is None or not prev.get("scheduler_ratio"):
                continue
            delta = (
                cur["scheduler_ratio"] - prev["scheduler_ratio"]
            ) / prev["scheduler_ratio"]
            cap_deltas.append(
                {
                    "config": "capacity_ratio",
                    "row": cfg_key,
                    "delta_pct": round(delta * 100.0, 2),
                }
            )
            if delta < -threshold:
                regressions.append(
                    {
                        "config": "capacity_ratio",
                        "prev": prev["scheduler_ratio"],
                        "cur": cur["scheduler_ratio"],
                        "from": f"{cfg_key}@{prev.get('measured_unix')}",
                        "to": f"{cfg_key}@{cur.get('measured_unix')}",
                        "delta_pct": round(delta * 100.0, 2),
                    }
                )

    mc_fresh = [r for r in multichip if not r["skipped"]]
    if mc_fresh and not mc_fresh[-1]["ok"] and any(r["ok"] for r in mc_fresh[:-1]):
        last_ok = [r for r in mc_fresh[:-1] if r["ok"]][-1]
        regressions.append(
            {
                "config": "multichip",
                "prev": "ok",
                "cur": "failed",
                "from": last_ok["source"],
                "to": mc_fresh[-1]["source"],
                "delta_pct": None,
            }
        )

    return {
        "caveat": EST_CAVEAT,
        "threshold_pct": round(threshold * 100.0, 1),
        "headline": {"rounds": bench, "deltas": deltas},
        "config1_p50": {
            "rounds": [
                {
                    "round": r["round"],
                    "source": r["source"],
                    "p50_ms": r["config1_p50_ms"],
                }
                for r in lat_fresh
            ],
            "deltas": lat_deltas,
        },
        "state_root_p50": {"entries": sr_entries, "deltas": sr_deltas},
        "capacity_ratio": {"entries": cap_entries, "deltas": cap_deltas},
        "multichip": {"rounds": multichip},
        "matrix": matrix,
        "regressions": regressions,
        "ok": not regressions,
    }


def check(
    root: str | None = None,
    threshold: float = DEFAULT_REGRESSION_THRESHOLD,
) -> tuple:
    """(exit_code, report): nonzero when any fresh-to-fresh delta drops
    more than `threshold` (the CI gate behind scripts/perf_trend.py
    --check and the lint gate)."""
    report = trend_report(root, threshold)
    return (0 if report["ok"] else 1), report


_trend_cache: dict = {}  # root -> (monotonic deadline, summary)


def trend_summary(root: str | None = None) -> dict | None:
    """Small cached aggregate for /lighthouse_tpu/pipeline: the latest
    headline round (with its carried-forward flag), the regression
    verdict, and the estimate caveat. None when no artifacts exist."""
    import time

    root = root or default_root()
    now = time.monotonic()
    hit = _trend_cache.get(root)
    if hit is not None and hit[0] > now:
        return hit[1]
    try:
        report = trend_report(root)
    except Exception:
        return None
    rounds = report["headline"]["rounds"]
    if not rounds and not report["matrix"]:
        return None
    latest = rounds[-1] if rounds else None
    summary = {
        "caveat": report["caveat"],
        "regressions": len(report["regressions"]),
        "ok": report["ok"],
    }
    if latest is not None:
        summary["headline_latest"] = {
            "source": latest["source"],
            "value_sets_per_sec": latest["value"],
            "vs_est_blst": latest["vs_est"],
            "fresh": latest["fresh"],
            **(
                {"carried_from": latest.get("carried_from")}
                if latest.get("carried")
                else {}
            ),
        }
    _trend_cache[root] = (now + 30.0, summary)
    return summary


# ------------------------------------------------------------ report render


def render_report(report: dict) -> str:
    """Human-readable trend report (bn perf report / scripts/perf_trend.py).
    Carried-forward rounds and skipped matrix configs render unmistakably
    distinct from fresh measurements."""
    lines = [
        "lighthouse-tpu perf trend",
        f"  CAVEAT: {report['caveat']}",
        "",
        "headline (BENCH_r*.json, sets/s):",
    ]
    for r in report["headline"]["rounds"]:
        val = f"{r['value']:.2f}" if r["value"] else "—"
        vs = f"  vs_est_blst={r['vs_est']}" if r.get("vs_est") is not None else ""
        if r["fresh"]:
            tag = "fresh"
        elif r.get("carried"):
            tag = (
                f"CARRIED FORWARD from {r['carried_from']} — "
                "not a fresh measurement"
            )
        else:
            tag = "SKIPPED (no measurement, nothing to carry)"
        lines.append(f"  r{r['round']:02d}  {val:>10s}{vs}  [{tag}]")
    for d in report["headline"]["deltas"]:
        lines.append(
            f"  delta {d['from']} -> {d['to']}: {d['delta_pct']:+.2f}%"
        )
    lat = report.get("config1_p50") or {}
    if lat.get("rounds"):
        lines.append("")
        lines.append(
            "config1 urgent-path p50 (ms, lower is better; fresh rounds "
            "only):"
        )
        for r in lat["rounds"]:
            lines.append(f"  r{r['round']:02d}  {r['p50_ms']:>10.2f}")
        for d in lat["deltas"]:
            lines.append(
                f"  delta {d['from']} -> {d['to']}: {d['delta_pct']:+.2f}%"
            )
    sr = report.get("state_root_p50") or {}
    if sr.get("entries"):
        lines.append("")
        lines.append(
            "state_root p50 (ms, lower is better; BENCH_MATRIX "
            "state_root row history):"
        )
        for e in sr["entries"]:
            if e.get("fresh", True) and e.get("p50_ms"):
                tag = "fresh"
            else:
                tag = "CARRIED FORWARD — not a fresh measurement"
            val = f"{e['p50_ms']:.2f}" if e.get("p50_ms") else "—"
            lines.append(
                f"  @{e.get('measured_unix')}  {val:>10s}  [{tag}]"
            )
        for d in sr["deltas"]:
            lines.append(f"  delta: {d['delta_pct']:+.2f}%")
    cap = report.get("capacity_ratio") or {}
    if cap.get("entries"):
        lines.append("")
        lines.append(
            "capacity controller vs static-optimal (ratio, higher is "
            "better; loadtest_* row histories):"
        )
        for e in cap["entries"]:
            tag = "fresh" if e.get("fresh", True) else (
                "CARRIED FORWARD — not a fresh measurement"
            )
            lines.append(
                f"  {e.get('row')}@{e.get('measured_unix')}  "
                f"{e.get('scheduler_ratio')}  [{tag}]"
            )
        for d in cap["deltas"]:
            lines.append(
                f"  delta ({d['row']}): {d['delta_pct']:+.2f}%"
            )
    lines.append("")
    lines.append("multichip (MULTICHIP_r*.json):")
    for r in report["multichip"]["rounds"]:
        if r["skipped"]:
            tag = "SKIPPED"
        else:
            tag = "ok" if r["ok"] else "FAILED"
        lines.append(
            f"  r{r['round']:02d}  {tag}  (n_devices={r['n_devices']})"
        )
    if report["matrix"]:
        lines.append("")
        lines.append("current matrix (BENCH_MATRIX.json):")
        for config in sorted(report["matrix"]):
            e = report["matrix"][config]
            if "skipped" in e:
                lines.append(
                    f"  {config}: SKIPPED ({e['skipped']}) — no measurement"
                )
                continue
            if "error" in e and "rate" not in e:
                lines.append(f"  {config}: ERROR ({e['error']})")
                continue
            bits = []
            if "rate" in e:
                bits.append(f"{e['rate']} {e['rate_unit']}")
            if "p50_ms" in e:
                bits.append(f"p50={e['p50_ms']}ms")
            if e.get("vs_est") is not None:
                bits.append(f"{e['vs_est_key']}={e['vs_est']} (estimated)")
            if e.get("source") == "loadtest":
                nd = e.get("n_devices")
                bits.append(
                    "source=loadtest (fresh soak snapshot"
                    + (f", {nd} device(s))" if nd else ")")
                )
            lines.append(f"  {config}: " + ", ".join(bits))
    lines.append("")
    if report["regressions"]:
        lines.append(
            f"REGRESSION: {len(report['regressions'])} config(s) dropped "
            f">{report['threshold_pct']}% between fresh rounds:"
        )
        for r in report["regressions"]:
            lines.append(
                f"  {r['config']}: {r['prev']} -> {r['cur']} "
                f"({r['from']} -> {r['to']}, {r['delta_pct']}%)"
            )
    else:
        lines.append(
            f"verdict: OK — no fresh-to-fresh drop exceeds "
            f"{report['threshold_pct']}%"
        )
    return "\n".join(lines)


def run_report(
    root: str | None = None,
    check_mode: bool = False,
    threshold: float = DEFAULT_REGRESSION_THRESHOLD,
    as_json: bool = False,
) -> int:
    """Shared driver behind `bn perf report` and scripts/perf_trend.py."""
    rc, report = check(root, threshold)
    if as_json:
        print(json.dumps(report, indent=1))
    else:
        print(render_report(report))
    return rc if check_mode else 0
