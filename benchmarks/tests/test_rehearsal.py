"""Both drivers at rehearsal size on the CPU, through `run.measure` — the
part of a run below the harness's look for a chip. What is checked here is
control flow, counts and `correct`; no number read here is a device metric.
"""

import json
import os
import subprocess
import sys

import jax
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT, write_json

E2E = {"tiny_flood": {"bls_verified_sets_per_s", "bls_verify_p95_ms",
                      "setup_s"},
       "tiny_root": {"tree_root_p95_ms", "setup_s"}}


def measure(bench_dir, cell, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(cell, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


@pytest.mark.parametrize("cell", ["tiny_flood", "tiny_root"])
def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        rehearsal_dir, cell):
    res = measure(rehearsal_dir, cell, seed=2**31 + 11)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == E2E[cell]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]       # compared comes last
    assert all(c["value"] == c["limit"] for c in res["compared"].values())
    json.dumps(res)     # plain numbers only


@pytest.mark.parametrize("cell", ["tiny_flood", "tiny_root"])
def test_a_run_says_which_level_its_process_drew(rehearsal_dir, cell, capsys):
    measure(rehearsal_dir, cell, seconds=0.3)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"step": "runtime_call"')]
    assert len(lines) == 1 and lines[0]["n"] == 64
    assert 0 < lines[0]["min_us"] <= lines[0]["median_us"]


@pytest.mark.parametrize("cell,tamper", [
    ("tiny_flood", "swap_signature"),
    ("tiny_flood", "flip_message"),
    ("tiny_root", "flip_leaf"),
])
def test_a_damaged_operand_in_the_window_turns_correct_false(
        rehearsal_dir, cell, tamper):
    res = measure(rehearsal_dir, cell, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] > 0


def test_a_verifier_that_always_says_true_is_not_correct(
        rehearsal_dir, monkeypatch):
    """The timed path broken underneath: every verdict True."""
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_signature_sets
    calls = []

    def broken(sets, rands):
        calls.append(len(sets))
        if len(calls) <= 2:          # the plain reference stays sound
            return real(sets, rands)
        return True

    monkeypatch.setattr(backend, "verify_signature_sets", broken)
    res = measure(rehearsal_dir, "tiny_flood")
    assert res["correct"] is False


def test_a_ladder_that_returns_a_stale_root_is_not_correct(
        rehearsal_dir, monkeypatch):
    """The timed path broken underneath: the first root, for ever."""
    from lighthouse_tpu.jaxhash import engine

    real = engine.device_build_levels
    first = []

    def broken(leaves, depth, root_only=False, **kw):
        out = real(leaves, depth, root_only=root_only, **kw)
        first.append(out)
        return first[0]

    monkeypatch.setattr(engine, "device_build_levels", broken)
    res = measure(rehearsal_dir, "tiny_root")
    assert res["correct"] is False
    assert res["failed"] > 0


@pytest.mark.parametrize("cell", ["tiny_flood", "tiny_root"])
def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(
        rehearsal_dir, cell, monkeypatch):
    # XLA:CPU's operations sit on the host plane: stand it in for a device
    # plane to rehearse the path. The share it gives is not a device number.
    monkeypatch.setattr(trace_reduce, "DEVICE_PREFIX", "/host:CPU")
    res = measure(rehearsal_dir, cell, trace=True)
    assert res["correct"] is True
    assert {"setup_compile_s", "setup_trace_lower_s"} <= set(res["metrics"])
    assert not set(res["metrics"]) & (E2E[cell] - {"setup_s"})
    if cell == "tiny_root":
        assert {"tree_device_ms", "tree_upload_share",
                "device_idle_share.tree"} <= set(res["metrics"])
        assert "device_idle_share.bls" not in res["metrics"]
    else:
        assert {"processor_queue_wait_ms", "batch_width_mean",
                "device_idle_share.bls"} <= set(res["metrics"])
        assert res["metrics"]["batch_width_mean"]["value"] == 2
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_new_files_are_found_by_name_with_no_edit(rehearsal_dir):
    """A later PR's cell: a workload, a config and a layer metric dropped
    in as new files; nothing that was there is touched."""
    before = {}
    for root, _, files in os.walk(rehearsal_dir):
        for fn in files:
            p = os.path.join(root, fn)
            before[p] = os.path.getmtime(p)
    write_json(os.path.join(rehearsal_dir, "configs", "later-8k.json"),
               {"leaves": 8192, "depth": 13})
    write_json(os.path.join(rehearsal_dir, "workloads", "later_root.json"),
               {"config": "later-8k", "driver": "tree_root_loop", "chips": 1,
                "params": {"planes": 1, "trace_window_s": 0.2}})
    write_json(os.path.join(rehearsal_dir, "layer_metrics",
                            "later_dispatches.json"),
               {"layer": "tree hash", "unit": "1", "moves": "tree_root_p95_ms",
                "cells": ["later_root"],
                "source": {"family": "jaxhash_dispatch_total",
                           "labels": {"lane": "single_device"},
                           "reduce": "sum"}})
    import unittest.mock as mock

    with mock.patch.object(trace_reduce, "DEVICE_PREFIX", "/host:CPU"):
        res = measure(rehearsal_dir, "later_root", trace=True, seconds=0.5)
    assert res["correct"] is True
    assert res["metrics"]["later_dispatches"]["value"] == res["attempted"]
    assert all(os.path.getmtime(p) == t for p, t in before.items())


def test_an_unknown_cell_or_device_kind_is_an_error(rehearsal_dir):
    with pytest.raises(bench_run.BenchFailure):
        measure(rehearsal_dir, "no_such_cell")
    with pytest.raises(bench_run.BenchFailure):
        bench_run.load_peaks("TPU v9 imaginary", rehearsal_dir)


def test_the_collector_is_watched_and_not_steered():
    import gc
    import time

    was = (gc.isenabled(), gc.get_threshold(), gc.get_freeze_count())
    log = bench_run.GcLog()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    log.close()
    gc.collect()            # after close(): not seen
    seen = log.between(t0, t1)
    assert seen["collections"] == 1 and len(seen["full"]) == 1
    assert 0 < seen["seconds"] <= t1 - t0
    assert log.between(t1, time.perf_counter())["collections"] == 0
    assert (gc.isenabled(), gc.get_threshold(), gc.get_freeze_count()) == was


@pytest.mark.parametrize("cell", ["gossip_flood", "registry_root_1m"])
def test_without_a_tpu_the_command_measures_nothing(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert "metrics" not in last and "correct" not in last
    assert "no TPU" in last["error"]
