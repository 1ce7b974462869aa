"""Driver `tree_root_loop`: one caller asks for the root of a registry-sized
list of 32-byte leaves, over and over.

Copied from `chip_smoke.py` `phase_jaxhash` (the tree part) and
`hashlib_root` (PR 22); what changed is the loop and the timing. The hash
backend is `device`; every call goes through `ROUTER.maybe_tree_root`, is
timed from the call to the returned bytes (the host->device copy of the
leaves included), and is compared with the root a plain hashlib ladder gave
for that plane.

Parameters (the workload file's `params`):
  planes          seeded planes of leaves the loop cycles through
  trace_window_s  profiler window of a traced run, after the window
  tamper_window   null; or "flip_leaf": flip one bit of one seeded leaf of
                  plane 0 AFTER its expected root was taken — the control
                  check_outputs.py runs, `correct` must come out false
"""

from __future__ import annotations

import hashlib
import time

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import check, emit, runtime_call, verdict  # benchmarks/common.py

#: the damaged operand check_outputs.py puts into the window as control
CONTROLS = ("flip_leaf",)


def hashlib_root(leaves: bytes) -> bytes:
    """The plain reference: a ladder of hashlib.sha256 over pairs."""
    level = [leaves[i:i + 32] for i in range(0, len(leaves), 32)]
    while len(level) > 1:
        level = [
            hashlib.sha256(level[i] + level[i + 1]).digest()
            for i in range(0, len(level), 2)
        ]
    return level[0]


def run(config, params, seed, seconds, trace, h) -> dict:
    from lighthouse_tpu import jaxhash

    n = int(config["leaves"])
    depth = int(config["depth"])
    if n != 1 << depth:
        raise ValueError(f"{n} leaves is not 2**{depth}")
    n_planes = int(params["planes"])
    rng = np.random.default_rng(seed)
    jaxhash.set_hash_backend("device")

    t0 = time.perf_counter()
    planes = [rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
              for _ in range(n_planes)]
    t_data = time.perf_counter() - t0

    # --- the plain reference, before the program's state is made; its
    # time is not set-up
    t0 = time.perf_counter()
    want = [hashlib_root(p.tobytes()) for p in planes]
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref

    tamper = params.get("tamper_window")
    if tamper == "flip_leaf":
        planes[0][int(rng.integers(n)), int(rng.integers(32))] ^= 0x01
    elif tamper:
        raise ValueError(f"unknown tampering {tamper!r}")

    def root_of(i: int):
        with h.annotate("bench:tree_root"):
            return jaxhash.ROUTER.maybe_tree_root(planes[i % n_planes], depth)

    # --- warm the one shape: plane 1's root twice (the first compiles)
    h.log.label = "warmup"
    t0 = time.perf_counter()
    first = root_of(1)
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    t0 = time.perf_counter()
    second = root_of(1)
    t_second = time.perf_counter() - t0
    check(first is not None and second is not None,
          "the device tree hash returned None (the host ladder would serve)")
    # which of the two levels this process drew: a root costs ~2 ms more
    # for the life of a process whose runtime calls are dear (PERF.md S2)
    runtime_call()

    # --- the window: a closed loop of one caller; it closes with the call
    # during which the time ran out
    results: list = []      # (plane, seconds, root)

    def loop(until: float, start: int) -> int:
        i = start
        while True:
            t0 = time.perf_counter()
            root = root_of(i)
            t1 = time.perf_counter()
            results.append((i % n_planes, t1 - t0, root))
            i += 1
            if t1 >= until:
                return i

    t_open = h.open_window()
    i_next = loop(t_open + seconds, 0)
    h.close_window()
    n_window = len(results)
    if trace:
        h.trace_begin()
        loop(time.perf_counter() + float(params["trace_window_s"]), i_next)
        h.trace_end()

    # --- after the window: one flipped bit must move the root
    flipped = planes[1 % n_planes].copy()
    flipped[int(rng.integers(n)), int(rng.integers(32))] ^= 0x80
    moved = jaxhash.ROUTER.maybe_tree_root(flipped, depth)

    win = results[:n_window]
    lat_ms = np.sort(np.array([s for _, s, _ in win]) * 1e3)
    n_roots = len(win)
    wrong = sum(1 for p, _, r in win if r is None or bytes(r) != want[p])
    p95 = float(lat_ms[int(np.ceil(0.95 * n_roots)) - 1])
    window_s = h.t_close - h.t_open

    def in_window(family: str, labels: dict, reduce: str) -> float:
        return layer_reader.evaluate(
            {"family": family, "labels": labels, "reduce": reduce},
            h.before, h.after, {}, {}) or 0.0

    host_routes = in_window("tree_hash_route_total", {"path": "host"}, "sum")
    dispatches = in_window("jaxhash_device_seconds", {"op": "tree_levels"},
                           "count")

    emit(step="tree_root_loop", leaves=n, depth=depth, planes=n_planes,
         data_secs=round(t_data, 3), reference_secs=round(t_ref, 3),
         warmup_s=h.notes["warmup_s"], second_call_s=t_second,
         window_s=window_s, roots_in_window=n_roots,
         roots_per_s=n_roots / window_s, latency_ms={
             "n": n_roots, "median": float(np.median(lat_ms)), "p95": p95,
             "max": float(lat_ms[-1])},
         device_dispatches_in_window=dispatches,
         host_routes_in_window=host_routes,
         generator="closed loop, no schedule: lateness does not apply",
         tamper_window=tamper,
         call_ms=[round(s * 1e3, 1) for _, s, _ in win])

    # the run's own conditions: a breach is no result at all
    check(host_routes == 0,
          f"the host ladder served {host_routes} root(s)")
    check(dispatches == n_roots,
          f"{dispatches} device dispatches for {n_roots} roots: a result "
          "was served from somewhere else")

    # --- correct: each number compared, beside its limit (all exact)
    warm_ok = bytes(first) == want[1 % n_planes] == bytes(second)
    moved_ok = moved is not None and bytes(moved) != want[1 % n_planes]
    compared = [
        {"name": "warmup_roots", "what": "warm-up roots equal to hashlib's",
         "value": warm_ok,
         "limit": True},
        {"name": "window_wrong",
         "what": "roots of the window that differ from hashlib's",
         "value": wrong, "limit": 0},
        {"name": "after_window_flipped_bit",
         "what": "one flipped bit after the window moves the root",
         "value": moved_ok, "limit": True},
    ]
    return {
        "correct": verdict(compared),
        "compared": compared,
        "attempted": n_roots,
        "failed": wrong,
        "end_to_end": {
            "tree_root_p95_ms": {"value": p95, "unit": "ms"},
        },
    }
