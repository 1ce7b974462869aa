"""Driver `bls_registry_block_loop` at rehearsal size on the CPU, through
`run.measure`: a registry of 64 derived validators, an epoch of 4 slots of
16, blocks of proposal + RANDAO + 2 whole-slot attestations + an 8-key sync
aggregate from `electra_pool_smoke.npz`, on the pure-Python backend (which
reads the sets' keys and ignores their indices; the table is built and
compared all the same). What is checked here is control flow, counts and
`correct`; no number read here is a device metric. The indexed path itself
against the packed one is tier-1's (tests/test_jaxbls_registry.py)."""

import json
import os
import shutil
import sys

import jax
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT, write_json

CELL = "tiny_electra"
REAL = "block_import_electra"
E2E = {"bls_verify_p95_ms", "setup_s"}
MINE = {"el_stage_prepare_ms", "el_stage_h2c_ms", "el_stage_pairs_ms",
        "el_stage_pairing_ms", "el_marshal_ms", "el_marshal_indices_ms",
        "el_dispatch_device_ms", "el_block_batch_verify_ms",
        "el_bucket_key_fill_share", "el_bucket_set_fill_share",
        "el_registry_key_share", "el_prepare_key_bytes_share",
        "device_idle_share.electra", "el_marshal_sigs_ms",
        "el_marshal_h2f_ms", "el_marshal_upload_ms", "el_exec_lock_wait_ms",
        "el_miller_accumulators_mean"}
SETS = 5       # proposal, RANDAO, 2 attestations, the sync aggregate


@pytest.fixture
def electra_dir(tmp_path):
    """A benchmark directory holding the rehearsal twin of
    `block_import_electra` alone: the committed driver, reference, layer
    metrics and peaks, the smoke pool, and new workload and config files."""
    d = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(BENCH_DIR, "drivers"), d / "drivers")
    shutil.copytree(os.path.join(BENCH_DIR, "reference"), d / "reference")
    for name, spec in bench_run.load_layer_metrics().items():
        if spec.get("cells") is not None:
            spec["cells"] = [CELL for c in spec["cells"] if c == REAL]
        write_json(d / "layer_metrics" / f"{name}.json", spec)
    os.makedirs(d / "data")
    shutil.copy(os.path.join(BENCH_DIR, "data", "electra_pool_smoke.npz"),
                d / "data" / "electra_pool_smoke.npz")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peaks["device_kinds"][kind] = {"hbm_bytes_per_s": 1e9}   # tests only
    write_json(d / "peaks.json", peaks)
    write_json(d / "configs" / "tiny-electra-2.json",
               {"validators": 64, "slots_per_epoch": 4,
                "attesters_per_slot": 16, "attestations_per_block": 2,
                "sync_committee_size": 8, "sets_per_request": SETS})
    write_json(d / "workloads" / f"{CELL}.json", {
        "config": "tiny-electra-2", "driver": "bls_registry_block_loop",
        "chips": 1,
        "params": {"backend": "python",
                   "pool": "data/electra_pool_smoke.npz",
                   "attestations_per_block": 2, "work_kind": "gossip_block",
                   "bucket": [8, 16], "preroll_blocks": 1,
                   "table_sample_rows": 16, "trace_window_s": 0.3,
                   "tamper_window": None}})
    return str(d)


def measure(bench_dir, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(CELL, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        electra_dir):
    res = measure(electra_dir, seed=2**31 + 41)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % SETS == 0        # whole blocks
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    compared = res["compared"]
    assert list(compared)[:4] == ["registry_bytes", "table_digest",
                                  "table_rows", "table_spare"]
    assert all(c["value"] == c["limit"] for c in compared.values())
    assert compared["refused_past_the_table"]["value"] is None   # no device
    json.dumps(res)     # plain numbers only


@pytest.mark.parametrize("tamper", ["swap_signature", "drop_signer",
                                    "replace_signer"])
def test_a_damaged_attestation_in_the_window_turns_correct_false(
        electra_dir, tamper):
    # what check_outputs.py will run
    assert tamper in bench_run.load_driver("bls_registry_block_loop").CONTROLS
    res = measure(electra_dir, seed=8, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] == SETS
    assert res["compared"]["window_wrong"] == {"value": SETS, "limit": 0}


def test_a_table_that_differs_from_the_registry_is_not_correct(
        electra_dir, monkeypatch):
    """One limb of one row changed on its way to the device: the digest
    over all rows says so, whether or not the seeded rows meet it."""
    from lighthouse_tpu.crypto.jaxbls import registry

    real = registry.PubkeyTable.append

    def append(self, keys):
        real(self, keys)
        self.x = self.x.at[3, 0].set(self.x[3, 0] ^ 1)

    monkeypatch.setattr(registry.PubkeyTable, "append", append)
    res = measure(electra_dir)
    assert res["correct"] is False
    assert res["compared"]["table_digest"] == {"value": 1, "limit": 0}
    assert res["failed"] == 0            # the Python verdicts read the keys


def test_without_the_registry_module_the_driver_fails_at_once(
        electra_dir, monkeypatch, capsys):
    """A tree that lacks crypto/jaxbls/registry.py (the parent of the PR
    that added the cell): `BenchFailure` naming the cell, before any data
    is made."""
    import numpy as np
    from common import BenchFailure

    import lighthouse_tpu.crypto.jaxbls as pkg

    monkeypatch.setitem(sys.modules, "lighthouse_tpu.crypto.jaxbls.registry",
                        None)        # importing it raises ImportError
    monkeypatch.delattr(pkg, "registry", raising=False)
    monkeypatch.setattr(np, "load", lambda *_, **__: pytest.fail(
        "the pool was loaded before the registry module was looked for"))
    with pytest.raises(BenchFailure, match="keeps no registry table.*"
                       + CELL):
        measure(electra_dir)
    out = capsys.readouterr().out
    assert '"step": "device"' in out and "bls_registry_block_loop\"," in out
    assert '"step": "bls_registry_block_loop"' not in out


def test_a_block_is_one_batch_of_one_work_item(electra_dir, monkeypatch):
    """Every block reaches the backend as ONE verify_signature_sets of its
    five sets in block order, each carrying its validator indices, from one
    gossip_block work item."""
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_signature_sets
    seen = []

    def watching(sets, rands):
        seen.append([(len(s.signing_keys), s.signing_indices is not None
                      and len(s.signing_indices)) for s in sets])
        return real(sets, rands)

    monkeypatch.setattr(backend, "verify_signature_sets", watching,
                        raising=False)
    kinds = set()
    real_submit = bp.BeaconProcessor.submit

    def submit(self, item):
        kinds.add((item.kind.name, item.run_batch is not None))
        return real_submit(self, item)

    monkeypatch.setattr(bp.BeaconProcessor, "submit", submit)
    res = measure(electra_dir, seconds=0.5)
    assert res["correct"] is True
    assert kinds == {("gossip_block", False)}
    for block in seen:
        assert len(block) == SETS
        assert [w for w, _ in block[:2]] == [1, 1] and block[-1] == (8, 8)
        assert all(12 <= w <= 16 for w, _ in block[2:4]) or any(
            w == 11 for w, _ in block[2:4])           # a dropped signer
        assert all(w == n for w, n in block)          # one index a key


def test_a_traced_run_reports_its_per_layer_metrics(electra_dir, monkeypatch):
    # XLA:CPU's operations sit on the host plane: stand it in for a device
    # plane to rehearse the path. The share it gives is not a device number.
    monkeypatch.setattr(trace_reduce, "DEVICE_PREFIX", "/host:CPU")
    real_begin = bench_run.Harness.trace_begin

    def begin_and_touch(self):
        real_begin(self)
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(64)))

    monkeypatch.setattr(bench_run.Harness, "trace_begin", begin_and_touch)
    res = measure(electra_dir, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert "bls_verify_p95_ms" not in got
    # the pure-Python backend moves no jaxbls family: the readers of those
    # find nothing and leave their metrics out, as on a parent commit
    assert got == {"el_block_batch_verify_ms", "device_idle_share.electra",
                   "setup_compile_s", "setup_trace_lower_s"}
    assert res["metrics"]["el_block_batch_verify_ms"]["value"] > 0


def test_the_registry_is_derived_and_the_reference_reads_its_bytes():
    """`derive_registry` against one scalar multiplication a key, the
    partition a partition, and the reference (keys from bytes) against the
    pure-Python backend on the smoke pool's sets, valid and swapped."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381 import serde

    drv = bench_run.load_driver("bls_registry_block_loop")
    ref = drv.load_reference(BENCH_DIR)
    pool = drv.load_pool(os.path.join(BENCH_DIR, "data",
                                      "electra_pool_smoke.npz"))
    meta = pool["meta"]
    n = meta["validators"]
    a, d = drv.registry_secrets(meta["registry_seed"])
    points = drv.derive_registry(n, a, d)
    assert len(points) == n == 64
    for i in (0, 1, 2, 31, 32, 63):
        assert points[i] == cv.g1_mul(cv.G1_GEN, (a + i * d) % drv._R)
    assert drv.derive_registry(37, a, d) == points[:37]    # not a power of 2
    part = drv.slot_partition(n, meta["slots"], meta["registry_seed"])
    assert sorted(part.ravel().tolist()) == list(range(n))
    assert (part[:, 1:] > part[:, :-1]).all()
    key_bytes = [serde.g1_compress(p) for p in points]
    assert ref.registry_digest([ref.decompress_key(b) for b in key_bytes]) \
        == ref.registry_digest(points)
    sets = pool["small"] + pool["att"][:2] + [pool["sync"]]
    swapped = list(sets)
    swapped[2] = (sets[2][0], sets[2][1], sets[3][2])
    bls.set_backend("python")
    for block, want in ((sets, True), (swapped, False)):
        as_ref = [(sig, [key_bytes[i] for i in ind.tolist()], msg)
                  for ind, msg, sig in block]
        assert ref.verify_signature_sets(as_ref, [3, 5, 7, 11, 13]) is want
        as_sets = [bls.SignatureSet(
            bls.Signature(sig), [bls.PublicKey(points[i]) for i in ind], msg)
            for ind, msg, sig in block]
        assert bls.verify_signature_sets(as_sets) is want
    # a sum the reference takes the long way round: P + P, and P - P
    p = points[5]
    assert ref.sum_keys([p, p]) == cv.g1_add(p, p)
    assert ref.sum_keys([p, cv.g1_neg(p)]) is None
    assert ref.sum_keys([p, cv.g1_neg(p), points[6]]) == points[6]


def test_the_new_files_are_found_by_name_and_match_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[REAL]
    wl = bench_run.load_json("workloads", REAL)
    assert wl["driver"] == "bls_registry_block_loop" and wl["chips"] == 1
    assert cell["chips"] == 1
    assert cell["config"] == wl["config"] == "mainnet-electra-block-8"
    assert cell["traffic"] == wl["traffic"] == "one_block_outstanding"
    assert cell["why"] == wl["why"] and wl["who"]
    assert "16x32768" in wl["why"] and "3rd slowest" in wl["why"]
    assert wl["params"] == {
        "backend": "jax", "pool": "data/electra_pool_8.npz",
        "attestations_per_block": 8, "work_kind": "gossip_block",
        "bucket": [16, 32768], "preroll_blocks": 2,
        "table_sample_rows": 4096, "trace_window_s": 3.0,
        "tamper_window": None}
    drv = bench_run.load_driver(wl["driver"])
    cfg = bench_run.load_json("configs", wl["config"])
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert len(cfg["source"]) <= 200
    for word in ("MAX_ATTESTATIONS_ELECTRA=8", "MAX_COMMITTEES_PER_SLOT=64",
                 "SLOTS_PER_EPOCH=32", "SYNC_COMMITTEE_SIZE=512"):
        assert word in cfg["source"]
    assert cfg["assumed"] and len(cfg["guarantees"]) == 6
    assert cfg["validators"] == 1_048_576 and cfg["coefficient_bits"] == 64
    assert cfg["attestations_per_block"] == 8 and cfg["sets_per_request"] == 11
    assert cfg["validators"] // cfg["slots_per_epoch"] == cfg[
        "attesters_per_slot"] == wl["params"]["bucket"][1]
    table = cfg["registry_table"]
    assert table["capacity"] * 192 == table["bytes"] == 213_909_504
    # the committed pool is the deployment the configuration states
    pool = drv.load_pool(os.path.join(BENCH_DIR, wl["params"]["pool"]))
    meta = pool["meta"]
    assert (meta["validators"], meta["slots"], meta["sync_committee_size"]) \
        == (cfg["validators"], cfg["slots_per_epoch"],
            cfg["sync_committee_size"])
    widths = [len(ind) for ind, _, _ in pool["att"]]
    assert [min(widths), max(widths)] == meta["attestation_keys"]
    assert meta["signers"] == [32_093, 32_759]          # the issue's range
    assert 32_093 <= min(widths) and max(widths) <= 32_759
    assert len({msg for _, msg, _ in pool["att"]}) == 32
    keys_a_block = 2 + 8 * sum(widths) / 32 + 512
    assert 259_000 < keys_a_block < 262_000
    mine = {k for k, v in bench_run.load_layer_metrics().items()
            if REAL in (v.get("cells") or ())}
    assert mine >= MINE       # among them: later PRs may name this cell too
    listed = {m["name"] for m in bench["per_layer"]
              if REAL in m.get("workloads", ())}
    assert listed == mine
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["bls_verify_p95_ms"]["workloads"]
    assert REAL not in e2e["request_p50_ms"]["workloads"]
    for name in MINE:
        spec = bench_run.load_json("layer_metrics", name)
        assert spec["cells"] == [REAL] and spec["moves"] == "bls_verify_p95_ms"
