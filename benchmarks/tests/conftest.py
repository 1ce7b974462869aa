"""The benchmark's own tests: run by hand (`python -m pytest benchmarks/tests
-q`), on the CPU, at rehearsal sizes. Not part of the repo's tier-1 suite.
No time, rate or share read here is a device number."""

import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# read the persistent cache, never write XLA:CPU entries into it
jax.config.update("jax_persistent_cache_min_compile_time_secs", 10**9)


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


#: committed cell -> its rehearsal twin, by driver
TWINS = {"gossip_flood": "tiny_flood", "registry_root_1m": "tiny_root",
         "block_import_131": "tiny_block", "urgent_verify": "tiny_urgent"}

REQUEST_LOOP = {"backend": "python", "work_kind": "gossip_block",
                "trace_window_s": 0.3, "tamper_window": None}


@pytest.fixture
def rehearsal_dir(tmp_path):
    """A benchmark directory of rehearsal-size cells: the committed drivers,
    layer metrics and peaks untouched, plus NEW workload and config files —
    which is also how a later PR adds a cell."""
    d = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(BENCH_DIR, "drivers"), d / "drivers")
    src = os.path.join(BENCH_DIR, "layer_metrics")
    for fn in os.listdir(src):
        with open(os.path.join(src, fn)) as f:
            spec = json.load(f)
        if spec.get("cells") is not None:
            # a cell with no twin here (its own test file builds it) drops out
            spec["cells"] = [TWINS[c] for c in spec["cells"] if c in TWINS]
        write_json(d / "layer_metrics" / fn, spec)
    os.makedirs(d / "data")
    for fn in ("att_pool_smoke.npz", "block_pool_smoke.npz"):
        shutil.copy(os.path.join(BENCH_DIR, "data", fn), d / "data" / fn)
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peaks["device_kinds"][kind] = {"hbm_bytes_per_s": 1e9}   # tests only
    write_json(d / "peaks.json", peaks)
    write_json(d / "configs" / "tiny-att-4.json",
               {"keys_per_set": 4, "sets_per_dispatch": 2})
    write_json(d / "configs" / "tiny-registry-4k.json",
               {"leaves": 4096, "depth": 12})
    write_json(d / "configs" / "tiny-block-5.json",
               {"sets_per_request": 5, "keys_per_request": 18})
    write_json(d / "workloads" / "tiny_flood.json", {
        "config": "tiny-att-4", "driver": "bls_flood", "chips": 1,
        "params": {"backend": "python", "pool": "data/att_pool_smoke.npz",
                   "batch_sets": 2, "backlog_sets": 2, "bucket": [4, 4],
                   "preroll_batches": 1, "reference_sets": 2,
                   "trace_window_s": 0.3, "tamper_window": None}})
    write_json(d / "workloads" / "tiny_root.json", {
        "config": "tiny-registry-4k", "driver": "tree_root_loop", "chips": 1,
        "params": {"planes": 2, "trace_window_s": 0.3,
                   "tamper_window": None}})
    # a block in small: proposal, RANDAO, 2 of 4 four-key attestations, one
    # eight-key sync aggregate = 5 sets, 18 keys
    write_json(d / "workloads" / "tiny_block.json", {
        "config": "tiny-block-5", "driver": "bls_request_loop", "chips": 1,
        "params": dict(REQUEST_LOOP, pool="data/block_pool_smoke.npz",
                       request=[["small", 2], ["att", 2], ["sync", 1]],
                       entry="signature_batch", bucket=[8, 8],
                       preroll_requests=1,
                       reference_request=[["small", 2], ["att", 1],
                                          ["sync", 1]])})
    write_json(d / "workloads" / "tiny_urgent.json", {
        "config": "tiny-att-4", "driver": "bls_request_loop", "chips": 1,
        "params": dict(REQUEST_LOOP, pool="data/att_pool_smoke.npz",
                       request=[["att", 1]], entry="urgent", bucket=[4, 4],
                       preroll_requests=2, reference_request=[["att", 1]])})
    return str(d)
