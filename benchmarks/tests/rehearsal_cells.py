"""The rehearsal-size benchmark directory the tests run in: every committed
cell's twin at a size the CPU verifies in seconds.

`conftest.py`'s `rehearsal_dir` builds the same directory, but maps each
layer metric's `cells` through a table of the two cells PR 24 had and raises
KeyError on any other, so a layer-metric file that names a new cell breaks
every test that uses the fixture. That file may not be edited by a PR that
adds a cell; `benchmarks/conftest.py` hands pytest this builder in its place.
A later `benchmark` PR folds this file into `conftest.py` and deletes both.
"""

import json
import os
import shutil

import jax

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: committed cell -> its rehearsal twin, by driver
TWINS = {"gossip_flood": "tiny_flood", "registry_root_1m": "tiny_root",
         "block_import_131": "tiny_block", "urgent_verify": "tiny_urgent"}

REQUEST_LOOP = {"backend": "python", "work_kind": "gossip_block",
                "trace_window_s": 0.3, "tamper_window": None}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def build(tmp_path) -> str:
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
