"""From a profiler trace (`*.xplane.pb`) to device busy time, idle share and
the breakdown of a traced run. Kept with the benchmark so every PR computes
the same number the same way; `tests/test_trace_reduce.py` checks it on a
synthetic trace.

`load()` turns the file into plain data,
    {"planes": [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns), ...]}]}]}
and `reduce()` works on that alone, so a test needs no profiler.

On a TPU the device planes are named `/device:TPU:<n>`; their `XLA Ops`
line holds one event per executed operation and `XLA Modules` one per
executed program. Busy is the union of the `XLA Ops` intervals inside the
traced window; the window is the harness's own `bench:trace_window` scope
on the host plane (host and device lines share the trace's clock), ended at
the device lines' last event where that comes first (a long window's device
line stops early: the profiler keeps a few million events, and the seconds
it never wrote are not idle; `window_source` then says
`host_scope_to_device_line_end` and `window_cut_s` how much went), or the
extent of the device events where that scope cannot be placed.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SMALL_GAP_NS = 20_000     # gaps under this are the ops' own spacing
MAX_ATTRIBUTED_GAPS = 4000


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            # a device op's name is its whole HLO line: keep the result name
            events = [(e.name.split(" = ", 1)[0][:80], float(e.start_ns),
                       float(e.duration_ns)) for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def structure(raw: dict) -> dict:
    """Planes, their lines and event counts: what to look at by hand."""
    return {"planes": {
        p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]}
        for p in raw["planes"] if p["lines"]
    }}


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted (k, 2) array of [start, end) intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new block starts where a start lies beyond every earlier end
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.flatnonzero(new)
    starts = iv[idx, 0]
    block_ends = np.append(ends[idx[1:] - 1], ends[-1])
    return np.stack([starts, block_ends], axis=1)


def _clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    iv = np.stack([np.maximum(intervals[:, 0], lo),
                   np.minimum(intervals[:, 1], hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def _line(plane: dict, name: str):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def _as_intervals(events) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.array([(s, s + d) for _, s, d in events], dtype=np.float64)
    return a[a[:, 1] > a[:, 0]]


def _top(totals: dict, k: int) -> list:
    return [[n, s] for n, s in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def reduce(raw: dict, window_scope: str = "bench:trace_window",
           n_devices: int = 1) -> dict:
    """{"busy_s", "window_s", "window_source", "window_cut_s", "values": {...},
    "breakdown": {"device_ops", "idle_gaps"}} — raises ValueError when no
    operation ran on a device inside the window."""
    device_planes = [p for p in raw["planes"]
                     if p["name"].startswith(DEVICE_PREFIX)
                     and any(ln["events"] for ln in p["lines"])]
    if not device_planes:
        raise ValueError("the trace holds no device plane with events "
                         f"(planes: {[p['name'] for p in raw['planes']]})")
    device_planes.sort(key=lambda p: p["name"])

    def ops_events(plane):
        ln = _line(plane, OPS_LINE)
        if ln is not None:
            return ln["events"]
        return [e for ln in plane["lines"]
                if ln["name"] not in (MODULES_LINE, "Steps")
                for e in ln["events"]]

    host_events = [e for p in raw["planes"]
                   if p["name"].startswith("/host:")
                   for ln in p["lines"] for e in ln["events"]]
    all_ops = np.concatenate([_as_intervals(ops_events(p))
                              for p in device_planes])
    if len(all_ops) == 0:
        raise ValueError("no operation ran on a device in the trace")
    dev_lo, dev_hi = float(all_ops[:, 0].min()), float(all_ops[:, 1].max())

    window = None
    for name, s, d in host_events:
        if name == window_scope and d > 0:
            window = (s, s + d)
    source = "host_scope"
    cut_ns = 0.0
    if window is None or window[1] <= dev_lo or window[0] >= dev_hi:
        window, source = (dev_lo, dev_hi), "device_extent"
    elif dev_hi < window[1]:
        # the profiler stops writing a device line at a few million events:
        # what follows its last event is not in the trace, and is not idle
        cut_ns = window[1] - dev_hi
        window, source = (window[0], dev_hi), "host_scope_to_device_line_end"
    lo, hi = window
    window_ns = hi - lo

    busy_ns = []
    merged0 = None
    op_totals: dict = {}
    for plane in device_planes:
        merged = _clip(_union(_as_intervals(ops_events(plane))), lo, hi)
        busy_ns.append(float((merged[:, 1] - merged[:, 0]).sum()))
        if merged0 is None:
            merged0 = merged
        mods = _line(plane, MODULES_LINE)
        for name, s, d in (mods["events"] if mods else []):
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                key = f"program {name}"
                op_totals[key] = op_totals.get(key, 0.0) + part / 1e9
    n_programs = len(op_totals)
    plain: dict = {}
    for plane in device_planes:
        for name, s, d in ops_events(plane):
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                plain[name] = plain.get(name, 0.0) + part / 1e9
    device_ops = (_top(op_totals, min(4, n_programs))
                  + _top(plain, 10 - min(4, n_programs)))
    busy_s = sum(busy_ns) / 1e9 / max(n_devices, len(device_planes))
    if busy_s <= 0:
        raise ValueError("no operation ran on a device inside the window")

    # idle gaps of the first device, by what the host was doing
    edges = np.concatenate([[lo], merged0.reshape(-1), [hi]])
    gaps = edges.reshape(-1, 2)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    length = gaps[:, 1] - gaps[:, 0]
    gap_totals: dict = {}
    small = length < SMALL_GAP_NS
    if small.any():
        gap_totals[f"(gaps under {SMALL_GAP_NS // 1000} us between ops)"] = (
            float(length[small].sum()) / 1e9)
    big = gaps[~small]
    big = big[np.argsort(-(big[:, 1] - big[:, 0]), kind="stable")]
    if len(big) > MAX_ATTRIBUTED_GAPS:
        rest = big[MAX_ATTRIBUTED_GAPS:]
        gap_totals["(further short gaps, not attributed)"] = (
            float((rest[:, 1] - rest[:, 0]).sum()) / 1e9)
        big = big[:MAX_ATTRIBUTED_GAPS]
    # each gap goes to the innermost host scope that covers most of it
    scopes = [(n, s, s + d) for n, s, d in host_events
              if d > 0 and n != window_scope]
    if scopes and len(big):
        s_arr = np.array([g[1] for g in scopes])
        e_arr = np.array([g[2] for g in scopes])
        span = e_arr - s_arr
        for g0, g1 in big:
            over = np.minimum(e_arr, g1) - np.maximum(s_arr, g0)
            covering = over >= 0.5 * (g1 - g0)
            if covering.any():
                i = int(np.argmin(np.where(covering, span, np.inf)))
            else:
                i = int(np.argmax(over))
            key = (f"host in {scopes[i][0]}" if over[i] > 0
                   else "(no host scope)")
            gap_totals[key] = gap_totals.get(key, 0.0) + (g1 - g0) / 1e9
    elif len(big):
        gap_totals["(no host scope)"] = (
            float((big[:, 1] - big[:, 0]).sum()) / 1e9)

    window_s = window_ns / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "window_source": source,
        "window_cut_s": cut_ns / 1e9,
        "device_planes": [p["name"] for p in device_planes],
        "values": {"device_idle_share": 100.0 * (1.0 - busy_s / window_s)},
        "breakdown": {"device_ops": device_ops,
                      "idle_gaps": _top(gap_totals, 10)},
    }
