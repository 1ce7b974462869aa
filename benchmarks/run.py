#!/usr/bin/env python3
"""One cell of the benchmark, once, on the TPU — or a non-zero exit.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It imports JAX itself, sets no platform and starts no child:
`jax.devices()[0].platform` must be "tpu", otherwise it says why and exits
non-zero without a result line. Everything that belongs to one cell is data
found by name, never listed here:

    workloads/<cell>.json        the traffic mix: config, driver, parameters
    configs/<config>.json        the deployment's shapes and guarantees
    drivers/<driver>.py          traffic generator + correctness check,
                                 `run(config, params, seed, seconds, trace, h)`
    layer_metrics/<metric>.json  one per-layer metric, read by layer_reader.py

Stdout is one JSON object per line. The LAST line is the result:
`{"correct", "attempted", "failed", "metrics", "device"}`, in a traced
run `"breakdown"`, and last `"compared"`: each number `correct` compared
beside its limit, which are also the last lines of standard error.
`--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics (attribution and the profiler are on, so
its end-to-end numbers go to an earlier line only).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()   # set-up is counted from here

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for _p in (REPO_ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import layer_reader  # noqa: E402  (benchmarks/layer_reader.py)
from common import BenchFailure, check, emit  # noqa: E402,F401
import trace_reduce  # noqa: E402  (benchmarks/trace_reduce.py)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SCOPE = "bench:trace_window"


def load_json(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    check(os.path.isfile(path), f"no {kind}/{name}.json under {bench_dir}")
    with open(path) as f:
        return json.load(f)


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "drivers", f"{name}.py")
    check(os.path.isfile(path), f"no drivers/{name}.py under {bench_dir}")
    spec = importlib.util.spec_from_file_location(f"bench_driver_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layer_metrics(bench_dir: str = BENCH_DIR) -> dict:
    """{metric name: its file's contents}, every file of the directory."""
    d = os.path.join(bench_dir, "layer_metrics")
    out = {}
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            with open(os.path.join(d, fn)) as f:
                out[fn[:-5]] = json.load(f)
    return out


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    check(device_kind in table,
          f"device kind {device_kind!r} is not in peaks.json; add its "
          "published peaks with their source, there is no default")
    return table[device_kind]


class CompileLog:
    """Every XLA compile request of the process, labelled by the step the
    run was in: (label, jitted function, seconds) from JAX's own monitoring
    events, plus persistent-cache hits and misses. One per process."""

    _instance = None

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax.monitoring as mon

        self.label = "setup"
        self.compiles: list = []
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append((self.label, kw.get("fun_name"), secs))

    def _on_event(self, event, **kw):
        key = event.rsplit("/", 1)[-1]
        if key in self.cache:
            self.cache[key] += 1

    def during(self, label: str) -> list:
        return [c for c in self.compiles if c[0] == label]

    def seconds_by_function(self) -> dict:
        out: dict = {}
        for _, fn, secs in self.compiles:
            out[str(fn)] = round(out.get(str(fn), 0.0) + secs, 3)
        return out


class GcLog:
    """Python's garbage collections as they happen, watched and not
    steered: (start, generation, seconds) of each, from `gc.callbacks`. A
    full collection over what tracing left alive stops every thread for
    some tenths of a second; one that falls inside the window is the
    program's own and stays in its numbers. The run's lines say where."""

    def __init__(self):
        self.collections: list = []
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.collections.append(
                (self._t0, info["generation"], time.perf_counter() - self._t0))

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def between(self, t0: float, t1: float) -> dict:
        mine = [c for c in self.collections if t0 <= c[0] < t1]
        full = [c for c in mine if c[1] == 2]
        return {"collections": len(mine),
                "seconds": sum(c[2] for c in mine),
                "full": [[round(c[0] - t0, 3), round(c[2], 4)] for c in full]}


def require_tpu():
    """jax.devices() on a TPU, or SystemExit(2) with the reason and no
    result line."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        emit(error="no TPU: jax.devices()[0].platform is "
             f"{d0.platform!r}; the benchmark sets no platform and measures "
             "nowhere else", platform=d0.platform, kind=d0.device_kind,
             count=len(devices))
        raise SystemExit(2)
    return devices


class Harness:
    """What a driver gets from the harness: the window's edges (registry
    snapshots, the set-up clock, the compile label), the profiler window of
    a traced run, and a place to leave what it measured about itself."""

    def __init__(self, devices, chips: int, trace: bool, bench_dir: str,
                 workload: str, t_process: float):
        self.devices = list(devices[:chips])
        self.trace = trace
        self.bench_dir = bench_dir
        self.workload = workload
        self.log = CompileLog.get()
        self.log.label = "setup"
        self.gc_log = GcLog()
        self._n_compiles_at_start = len(self.log.compiles)
        self._t_process = t_process
        self.reference_seconds = 0.0   # the plain reference: not set-up
        self.notes: dict = {}
        self.before = self.after = None
        self.t_open = self.t_close = None
        self.setup_s = None
        self.trace_dir = os.path.join(bench_dir, ".trace", workload)
        self._trace_scope = None
        self._t_trace = None
        self.trace_wall_s = None

    # -- the measured window

    def open_window(self) -> float:
        """End of set-up. Returns the window's opening time."""
        from lighthouse_tpu.utils.metrics import REGISTRY

        self.before = layer_reader.snapshot(REGISTRY)
        self.log.label = "window"
        self.t_open = time.perf_counter()
        self.setup_s = (self.t_open - self._t_process) - self.reference_seconds
        return self.t_open

    def close_window(self) -> float:
        from lighthouse_tpu.utils.metrics import REGISTRY

        self.t_close = time.perf_counter()
        self.after = layer_reader.snapshot(REGISTRY)
        self.log.label = "after"
        return self.t_close

    def note(self, key: str, value) -> None:
        self.notes[key] = value

    def annotate(self, name: str):
        """A host scope in the profiler's trace (free when none runs)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- the profiler window of a traced run (after the measured window,
    #    while the driver keeps the same loop going)

    def trace_begin(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # scopes, not every Python call
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._t_trace = time.perf_counter()
        self._trace_scope = jax.profiler.TraceAnnotation(TRACE_SCOPE)
        self._trace_scope.__enter__()

    def trace_end(self) -> None:
        import jax

        self._trace_scope.__exit__(None, None, None)
        self.trace_wall_s = time.perf_counter() - self._t_trace
        jax.profiler.stop_trace()

    # -- after the driver returns

    def setup_values(self) -> dict:
        """What the harness itself knows about set-up."""
        mine = self.log.compiles[self._n_compiles_at_start:]
        out = {"setup_compile_s": sum(
            c[2] for c in mine if c[0] not in ("window", "after"))}
        warm = self.notes.get("warmup_s")
        if warm is not None:
            out["setup_trace_lower_s"] = max(
                0.0, warm - sum(c[2] for c in mine if c[0] == "warmup"))
        return out

    def memory_peak_bytes(self):
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in self.devices
        ]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def print_compared(compared: list) -> dict:
    """Each number compared beside its limit: the last lines of standard
    error, and the object that comes last on the result line."""
    out = {c["name"]: {"value": c["value"], "limit": c["limit"]}
           for c in compared}
    for name, c in out.items():
        print(f"compared {name}: value {json.dumps(c['value'])} "
              f"limit {json.dumps(c['limit'])}", file=sys.stderr, flush=True)
    return out


def cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(
        1 for n in os.listdir(cache_dir)
        if not n.endswith("-atime") and not n.startswith(".")
        and os.path.isfile(os.path.join(cache_dir, n))
    )


def measure(workload: str, seed: int, seconds: float, trace: bool, devices,
            bench_dir: str = BENCH_DIR, t_process: float | None = None,
            param_overrides: dict | None = None) -> dict:
    """Everything of a run below the look for a chip: returns the result
    object (the last line). `param_overrides` is for check_outputs.py and
    the tests (tampered operands, rehearsal sizes); the command has no
    option that reaches it."""
    import jax

    from lighthouse_tpu.utils import jaxcfg

    wl = load_json("workloads", workload, bench_dir)
    config = load_json("configs", wl["config"], bench_dir)
    driver = load_driver(wl["driver"], bench_dir)
    params = dict(wl.get("params", {}))
    params.update(param_overrides or {})
    chips = int(wl.get("chips", 1))
    check(len(devices) >= chips,
          f"cell {workload} asks for {chips} chip(s), JAX sees {len(devices)}")
    # the seam parallel/mesh.py reads before it resolves its mesh: on a
    # host with more chips a one-chip cell still uses the first alone
    os.environ["LIGHTHOUSE_TPU_MESH_DEVICES"] = str(chips)

    jaxcfg.setup_compilation_cache()
    cache_dir = jaxcfg.cache_base_dir()
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          f"cache dir in force {jax.config.jax_compilation_cache_dir!r} "
          f"is not {cache_dir!r}")
    d0 = devices[0]
    emit(step="device", platform=d0.platform, kind=d0.device_kind,
         devices_visible=len(devices), devices_used=chips,
         jax=jax.__version__, cache_dir=cache_dir,
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         cache_entries_at_start=cache_entries(cache_dir),
         workload=workload, config=wl["config"], driver=wl["driver"],
         seed=seed, seconds=seconds, trace=int(trace))

    h = Harness(devices, chips, trace, bench_dir, workload,
                _T_PROCESS if t_process is None else t_process)
    h.note("before_driver_s", time.perf_counter() - h._t_process)
    try:
        res = driver.run(config, params, seed, seconds, trace, h)
    finally:
        h.gc_log.close()
    check(h.before is not None and h.after is not None,
          f"driver {wl['driver']} never opened and closed the window")

    log = h.log
    in_window = log.during("window")
    emit(step="compile", compile_secs_by_function=log.seconds_by_function(),
         persistent_cache=dict(log.cache), compiles_in_window=len(in_window),
         setup_s=h.setup_s, reference_s=round(h.reference_seconds, 3),
         window_s=h.t_close - h.t_open,
         before_driver_s=h.notes["before_driver_s"], **h.setup_values())
    check(not in_window, f"compiled inside the window: {in_window}")
    emit(step="gc", setup=h.gc_log.between(h._t_process, h.t_open),
         window=h.gc_log.between(h.t_open, h.t_close))

    end_to_end = dict(res["end_to_end"])
    end_to_end["setup_s"] = {"value": h.setup_s, "unit": "s"}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": h.memory_peak_bytes()}
    result = {"correct": bool(res["correct"]),
              "attempted": int(res["attempted"]),
              "failed": int(res["failed"])}
    if not trace:
        result["metrics"] = end_to_end
        result["device"] = device
        result["compared"] = print_compared(res["compared"])
        return result

    # traced run: the per-layer metrics; its own end-to-end numbers (taken
    # with attribution on) only on an earlier line, to set beside a
    # --trace 0 run's: the difference is what the attribution costs
    emit(step="end_to_end_under_trace", metrics=end_to_end)
    reduced = None
    if h.trace_wall_s is not None:
        t0 = time.perf_counter()
        path = trace_reduce.find_xplane(h.trace_dir)
        check(path is not None, f"the profiler left no trace in {h.trace_dir}")
        raw = trace_reduce.load(path)
        emit(step="trace_structure", file_bytes=os.path.getsize(path),
             **trace_reduce.structure(raw))
        reduced = trace_reduce.reduce(raw, window_scope=TRACE_SCOPE,
                                      n_devices=chips)
        shutil.rmtree(h.trace_dir, ignore_errors=True)   # hundreds of MB
        emit(step="trace_reduced", secs=round(time.perf_counter() - t0, 2),
             trace_wall_s=h.trace_wall_s,
             **{k: v for k, v in reduced.items() if k != "breakdown"})
    peaks = load_peaks(d0.device_kind, bench_dir)
    values = {"harness": h.setup_values(),
              "trace": {} if reduced is None else reduced["values"]}
    metrics = {}
    for name, spec in load_layer_metrics(bench_dir).items():
        cells = spec.get("cells")
        if cells is not None and workload not in cells:
            continue
        v = layer_reader.evaluate(spec["source"], h.before, h.after,
                                  peaks, values)
        if v is not None:
            metrics[name] = {"value": v, "unit": spec["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["compared"] = print_compared(res["compared"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    devices = require_tpu()
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         bool(args.trace), devices)
    except Exception as e:  # the boundary: report, exit non-zero, no result
        traceback.print_exc()
        emit(error=f"{type(e).__name__}: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
