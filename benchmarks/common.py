"""What run.py, check_outputs.py and the drivers share: lines and checks."""

from __future__ import annotations

import json
import time


class BenchFailure(Exception):
    """The run broke one of its own conditions: no result line."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def runtime_call(n: int = 64) -> None:
    """What a call into JAX's runtime costs this process: the median, in
    microseconds, of `n` round trips of a 4-byte `jax.device_put` +
    `block_until_ready`, printed on a line of its own. Set-up, ~0.1 s; no
    metric and no bound reads it. On the chip it reads ~0.52-0.64 ms in
    most processes and ~1.2-1.7 ms in others (8 of 30, PERF.md S2, PR 40):
    beside two runs' medians it tells a process that paid more for every
    runtime call from a program that got slower. In `registry_root_1m`
    the dear processes are the ones whose every root takes ~2 ms (3.9 %)
    longer (2 of 10, PERF.md S2, PR 40)."""
    import jax
    import numpy as np

    x = np.zeros(1, np.int32)
    jax.device_put(x).block_until_ready()
    us = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.device_put(x).block_until_ready()
        us.append((time.perf_counter() - t0) * 1e6)
    us.sort()
    emit(step="runtime_call", n=n, median_us=float(np.median(us)),
         min_us=us[0], p90_us=us[int(0.9 * n)], max_us=us[-1])


def verdict(compared: list) -> bool:
    """`correct`: every number compared equals its limit (all exact). The
    entries go out twice: as a line here, and through the driver's return
    to the result line and standard error (run.py)."""
    emit(step="compared", compared=compared)
    return all(c["value"] == c["limit"] for c in compared)
