"""Driver `bls_subnet_flood` at rehearsal size on the CPU, through
`run.measure`: a registry of 256 derived validators, one slot of 64
attesters in 4 committees of 16 from `subnet_pool_smoke.npz`, 4 single-key
sets a dispatch, one dispatch outstanding, on the pure-Python backend
(which reads the sets' keys and ignores their indices; the table is built
and compared all the same). What is checked here is control flow, counts
and `correct`; no number read here is a device metric. The indexed path
itself at one key a set against the pure-Python backend is tier-1's
(tests/test_jaxbls_registry.py)."""

import json
import os
import shutil

import jax
import numpy as np
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT, write_json

CELL = "tiny_subnet"
REAL = "subnet_flood_1key"
E2E = {"bls_verified_sets_per_s", "bls_verify_p95_ms", "setup_s"}
MINE = {"sn_stage_prepare_ms", "sn_stage_h2c_ms", "sn_stage_pairs_ms",
        "sn_stage_pairing_ms", "sn_marshal_ms", "sn_marshal_sigs_ms",
        "sn_marshal_h2f_ms", "sn_marshal_indices_ms", "sn_marshal_upload_ms",
        "sn_dispatch_device_ms", "sn_batch_verify_ms", "sn_batch_width_mean",
        "sn_distinct_message_share", "sn_registry_key_share",
        "sn_bucket_set_fill_share", "sn_miller_lines_per_accumulator",
        "device_idle_share.subnet"}
B = 4          # sets a dispatch at rehearsal size
SMOKE = os.path.join(BENCH_DIR, "data", "subnet_pool_smoke.npz")


@pytest.fixture
def subnet_dir(tmp_path):
    """A benchmark directory holding the rehearsal twin of
    `subnet_flood_1key` alone: the committed drivers, references, layer
    metrics and peaks, the smoke pool, and new workload and config files."""
    d = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(BENCH_DIR, "drivers"), d / "drivers")
    shutil.copytree(os.path.join(BENCH_DIR, "reference"), d / "reference")
    for name, spec in bench_run.load_layer_metrics().items():
        if spec.get("cells") is not None:
            spec["cells"] = [CELL for c in spec["cells"] if c == REAL]
        write_json(d / "layer_metrics" / f"{name}.json", spec)
    os.makedirs(d / "data")
    shutil.copy(SMOKE, d / "data" / "subnet_pool_smoke.npz")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peaks["device_kinds"][kind] = {"hbm_bytes_per_s": 1e9}   # tests only
    write_json(d / "peaks.json", peaks)
    write_json(d / "configs" / "tiny-subnet-16.json",
               {"validators": 256, "slots_per_epoch": 4,
                "committees_per_slot": 4, "committee_size": 16,
                "attestations_per_slot": 64, "keys_per_set": 1,
                "sets_per_dispatch": B})
    write_json(d / "workloads" / f"{CELL}.json", {
        "config": "tiny-subnet-16", "driver": "bls_subnet_flood", "chips": 1,
        "params": {"backend": "python", "pool": "data/subnet_pool_smoke.npz",
                   "batch_sets": B, "backlog_sets": B, "bucket": [B, 1],
                   "preroll_batches": 1, "table_sample_rows": 16,
                   "reference_sets": 2, "trace_window_s": 0.3,
                   "tamper_window": None}})
    return str(d)


def measure(bench_dir, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(CELL, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        subnet_dir, capsys):
    res = measure(subnet_dir, seed=2**31 + 43)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % B == 0             # whole batches
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    compared = res["compared"]
    assert list(compared) == [
        "registry_bytes", "table_digest", "table_rows", "table_spare",
        "reference_python", "reference_spec", "setup",
        "timed_against_references", "window_wrong", "window_missing",
        "after_window_damaged"]
    assert all(c["value"] == c["limit"] for c in compared.values())
    assert compared["reference_spec"]["value"] == [True, False]
    json.dumps(res)     # plain numbers only
    line = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                if '"step": "bls_subnet_flood"' in ln)
    assert line["widths_seen"] == [B] and line["attestations"] == 64
    assert line["reference_verdicts"] == {"python": [True, False],
                                          "spec": [True, False]}
    shared = line["distinct_messages_a_batch"]
    assert 1 <= shared["min"] <= shared["max"] <= B


@pytest.mark.parametrize("tamper", ["swap_signature", "flip_message",
                                    "replace_signer"])
def test_a_damaged_set_in_the_window_turns_correct_false(subnet_dir, tamper):
    # what check_outputs.py will run
    assert tamper in bench_run.load_driver("bls_subnet_flood").CONTROLS
    res = measure(subnet_dir, seed=8, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] == B
    assert res["compared"]["window_wrong"] == {"value": B, "limit": 0}


def test_a_batch_narrower_than_the_cell_says_breaks_the_run_off(
        subnet_dir, monkeypatch):
    """The processor coalescing to another width than `batch_sets` (here: a
    cap the scheduler lowered) is no result at all."""
    from common import BenchFailure

    from lighthouse_tpu.chain import scheduler

    real = scheduler.CapacityScheduler.decide

    def narrower(self, kind, depth, **kw):
        d = real(self, kind, depth, **kw)
        return scheduler.Decision(d.dispatch, min(d.cap, B // 2), d.reason)

    monkeypatch.setattr(scheduler.CapacityScheduler, "decide", narrower)
    with pytest.raises(BenchFailure, match="batch widths"):
        measure(subnet_dir, seconds=0.3)


def test_a_table_that_differs_from_the_registry_is_not_correct(
        subnet_dir, monkeypatch):
    from lighthouse_tpu.crypto.jaxbls import registry

    real = registry.PubkeyTable.append

    def append(self, keys):
        real(self, keys)
        self.x = self.x.at[3, 0].set(self.x[3, 0] ^ 1)

    monkeypatch.setattr(registry.PubkeyTable, "append", append)
    res = measure(subnet_dir, seconds=0.3)
    assert res["correct"] is False
    assert res["compared"]["table_digest"] == {"value": 1, "limit": 0}
    assert res["failed"] == 0            # the Python verdicts read the keys


def test_without_the_registry_module_the_driver_fails_at_once(
        subnet_dir, monkeypatch, capsys):
    """A tree that lacks crypto/jaxbls/registry.py: `BenchFailure` naming
    the cell, before any data is made."""
    import sys

    from common import BenchFailure

    import lighthouse_tpu.crypto.jaxbls as pkg

    monkeypatch.setitem(sys.modules, "lighthouse_tpu.crypto.jaxbls.registry",
                        None)        # importing it raises ImportError
    monkeypatch.delattr(pkg, "registry", raising=False)
    monkeypatch.setattr(np, "load", lambda *_, **__: pytest.fail(
        "the pool was loaded before the registry module was looked for"))
    with pytest.raises(BenchFailure, match="keeps no registry table.*"
                       + CELL):
        measure(subnet_dir)
    assert '"step": "bls_subnet_flood"' not in capsys.readouterr().out


def test_every_set_is_one_indexed_key_in_a_gossip_attestation_item(
        subnet_dir, monkeypatch):
    """Every dispatch reaches the backend as ONE verify_signature_sets of
    `batch_sets` single-key sets, each naming its validator's row of the
    cache's table, from gossip_attestation work items with a run_batch."""
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_signature_sets
    seen = []

    def watching(sets, rands):
        seen.append([(len(s.signing_keys), s.signing_indices.tolist(),
                      s.signing_registry is not None) for s in sets])
        return real(sets, rands)

    monkeypatch.setattr(backend, "verify_signature_sets", watching,
                        raising=False)
    kinds, caps = set(), set()
    real_submit = bp.BeaconProcessor.submit

    def submit(self, item):
        kinds.add((item.kind.name, item.run_batch is not None))
        caps.add((self.config.max_attestation_batch,
                  self.config.max_attestation_batch_explicit,
                  self.config.num_workers))
        return real_submit(self, item)

    monkeypatch.setattr(bp.BeaconProcessor, "submit", submit)
    res = measure(subnet_dir, seconds=0.5)
    assert res["correct"] is True
    assert kinds == {("gossip_attestation", True)}
    assert caps == {(B, True, 1)}
    drv = bench_run.load_driver("bls_subnet_flood")
    slot = set(drv.load_pool(SMOKE)["members"].ravel().tolist())
    batches = [b for b in seen if len(b) == B]      # the references' are 2
    assert len(batches) >= 4
    for batch in batches:
        assert all(w == 1 and len(ind) == 1 and has_table
                   for w, ind, has_table in batch)
        assert {ind[0] for _, ind, _ in batch} <= slot


def test_a_traced_run_reports_its_per_layer_metrics(subnet_dir, monkeypatch):
    # XLA:CPU's operations sit on the host plane: stand it in for a device
    # plane to rehearse the path. The share it gives is not a device number.
    monkeypatch.setattr(trace_reduce, "DEVICE_PREFIX", "/host:CPU")
    real_begin = bench_run.Harness.trace_begin

    def begin_and_touch(self):
        real_begin(self)
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(64)))

    monkeypatch.setattr(bench_run.Harness, "trace_begin", begin_and_touch)
    res = measure(subnet_dir, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert not got & (E2E - {"setup_s"})
    # the pure-Python backend returns a ready handle and moves no jaxbls
    # family: the readers of those find nothing and leave their metrics
    # out, as on a parent commit
    assert got == {"sn_batch_verify_ms", "sn_batch_width_mean",
                   "device_idle_share.subnet", "setup_compile_s",
                   "setup_trace_lower_s"}
    assert res["metrics"]["sn_batch_width_mean"]["value"] == B
    assert res["metrics"]["sn_batch_verify_ms"]["value"] > 0


def test_the_pool_is_what_its_meta_says_and_the_driver_derives_the_rest():
    """The smoke pool: what is stored against what is derived, the
    reference's minting against one multiplication a member and against the
    program's own signing, and `bls_subnet_spec.py` against
    `bls_registry_spec.py` and the pure-Python backend on the same sets."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls381 import curve as cv

    drv = bench_run.load_driver("bls_subnet_flood")
    ref = drv._spec
    pool = drv.load_pool(SMOKE)
    meta = pool["meta"]
    n, size = meta["validators"], meta["committee_size"]
    assert set(np.load(SMOKE).files) == {
        "head_msgs", "late_msgs", "head_points", "late_points", "late_mask",
        "meta"}                                   # no key, no signature
    assert "A = a H(M)" in meta["stored"] and "A + i D" in meta["derived"]
    members = pool["members"]
    assert members.shape == (meta["committees"], size)
    slot = drv.registry.slot_partition(n, meta["slots"],
                                       meta["registry_seed"])[meta["slot"]]
    assert sorted(members.ravel().tolist()) == slot.tolist()   # a partition
    assert (members[:, 1:] > members[:, :-1]).all()
    late = pool["late"].sum(axis=1)
    assert [int(late.min()), int(late.max())] == meta["late_voters"]
    a, d = drv.registry.registry_secrets(meta["registry_seed"])
    for c in (0, meta["committees"] - 1):
        for msgs, points in ((pool["head_msgs"], pool["head_points"]),
                             (pool["late_msgs"], pool["late_points"])):
            assert points[c] == ref.message_points(msgs[c], a, d)
    atts = drv.mint_slot(pool)
    assert len(atts) == meta["attestations"] == members.size
    assert [i for i, _, _ in atts] == members.ravel().tolist()
    assert len({msg for _, msg, _ in atts}) <= 2 * meta["committees"]
    assert sum(msg in pool["late_msgs"] for _, msg, _ in atts) == late.sum()
    # the chains against the long way round, and against the program's own
    # hash-to-G2 and multiplication (sk H(M))
    A, D = pool["head_points"][0]
    some = members[0][:5].tolist()
    assert ref.mint_members(A, D, members[0], n)[:5] == [
        ref.sign_member(A, D, i) for i in some]
    from lighthouse_tpu.crypto.bls381 import hash_to_curve as h2c
    from lighthouse_tpu.crypto.bls381.constants import DST_POP

    h = h2c.hash_to_g2(pool["head_msgs"][0], DST_POP)
    head0 = {i: sig for i, msg, sig in atts if msg == pool["head_msgs"][0]}
    i = next(iter(head0))
    assert cv.g2_mul(h, (a + i * d) % ref.R) == head0[i]
    # the three verifiers on the same sets, valid and with a swap
    points = drv.registry.derive_registry(n, a, d)
    key_bytes = [ref.base.compress_key(p) for p in points]
    sets = atts[:3] + atts[-2:]                   # two committees
    swapped = list(sets)
    swapped[1] = (sets[1][0], sets[1][1], sets[2][2])
    zs = [3, 5, 7, 11, 13]
    bls.set_backend("python")
    for batch, want in ((sets, True), (swapped, False)):
        assert ref.verify_batch(
            [(sig, key_bytes[i], msg) for i, msg, sig in batch], zs) is want
        assert ref.base.verify_signature_sets(
            [(sig, [key_bytes[i]], msg) for i, msg, sig in batch], zs) is want
        assert bls.verify_signature_sets([bls.SignatureSet(
            bls.Signature(sig), [bls.PublicKey(points[i])], msg)
            for i, msg, sig in batch]) is want
    i, msg, sig = sets[0]
    assert ref.verify_one(sig, key_bytes[i], msg) is True
    assert ref.verify_one(sets[1][2], key_bytes[i], msg) is False


def test_the_new_files_are_found_by_name_and_match_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[REAL]
    wl = bench_run.load_json("workloads", REAL)
    assert wl["driver"] == "bls_subnet_flood" and wl["chips"] == 1
    assert cell["chips"] == 1
    assert cell["config"] == wl["config"] == "mainnet-subnet-att-1key"
    assert cell["traffic"] == wl["traffic"] == "subnet_flood"
    assert cell["why"] == wl["why"] and 0 < len(wl["who"]) <= 200
    assert "1024x1" in wl["why"] and "--subscribe-all-subnets" in wl["who"]
    assert wl["params"] == {
        "backend": "jax", "pool": "data/subnet_pool_1key.npz",
        "batch_sets": 1024, "backlog_sets": 4096, "bucket": [1024, 1],
        "preroll_batches": 2, "table_sample_rows": 4096,
        "reference_sets": 8, "trace_window_s": 3.0, "tamper_window": None}
    drv = bench_run.load_driver(wl["driver"])
    assert drv.CONTROLS == ("swap_signature", "flip_message",
                            "replace_signer")
    cfg = bench_run.load_json("configs", wl["config"])
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert len(cfg["source"]) <= 200
    for word in ("beacon_attestation_{subnet_id}",
                 "compute_subnet_for_attestation",
                 "MAX_COMMITTEES_PER_SLOT=64", "batch.rs:139-225"):
        assert word in cfg["source"]
    assert set(cfg["assumed"]) >= {"sets_per_dispatch", "late_votes",
                                   "registry"}
    att = bench_run.load_json("configs", "mainnet-att-128")
    assert cfg["guarantees"][:3] == att["guarantees"]
    assert cfg["validators"] == 1_048_576 and cfg["coefficient_bits"] == 64
    assert cfg["keys_per_set"] == 1 == wl["params"]["bucket"][1]
    assert cfg["sets_per_dispatch"] == 1024 == wl["params"]["batch_sets"]
    assert (cfg["validators"] // cfg["slots_per_epoch"]
            == cfg["attestations_per_slot"]
            == cfg["committees_per_slot"] * cfg["committee_size"] == 32_768)
    table = cfg["registry_table"]
    assert table["capacity"] * 192 == table["bytes"] == 213_909_504
    # the committed pool is the deployment the configuration states
    pool = drv.load_pool(os.path.join(BENCH_DIR, wl["params"]["pool"]))
    meta = pool["meta"]
    assert (meta["validators"], meta["slots"], meta["committees"],
            meta["committee_size"], meta["attestations"]) == (
        cfg["validators"], cfg["slots_per_epoch"],
        cfg["committees_per_slot"], cfg["committee_size"],
        cfg["attestations_per_slot"])
    assert meta["registry_seed"] == 41             # the Electra block's keys
    assert meta["late_share"] == 0.02
    late = int(pool["late"].sum())
    assert 0.015 < late / meta["attestations"] < 0.025
    assert len(set(pool["head_msgs"]) | set(pool["late_msgs"])) == 128
    # a dispatch's distinct messages, as the issue states them: 6-10 %
    rng = np.random.default_rng(0)
    msg_of = np.where(pool["late"], np.arange(64)[:, None] + 64,
                      np.arange(64)[:, None]).ravel()
    shares = [len(set(msg_of[rng.permutation(32_768)[:1024]])) / 1024
              for _ in range(32)]
    assert 0.06 < min(shares) and max(shares) < 0.10
    mine = {k for k, v in bench_run.load_layer_metrics().items()
            if REAL in (v.get("cells") or ())}
    assert mine >= MINE       # among them: later PRs may name this cell too
    listed = {m["name"] for m in bench["per_layer"]
              if REAL in m.get("workloads", ())}
    assert listed == mine
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["bls_verify_p95_ms"]["workloads"]
    assert REAL in e2e["bls_verified_sets_per_s"]["workloads"]
    assert REAL not in e2e["request_p50_ms"]["workloads"]
    for name in MINE:
        spec = bench_run.load_json("layer_metrics", name)
        assert spec["cells"] == [REAL]
        assert spec["moves"] == "bls_verified_sets_per_s"
