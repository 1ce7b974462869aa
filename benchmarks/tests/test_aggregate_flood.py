"""Driver `bls_aggregate_flood` at rehearsal size on the CPU, through
`run.measure`: two committees of four validators, two aggregators each,
two aggregates (6 sets, bucket (8, 4) were it the jax backend) a dispatch,
on the pure-Python backend. What is checked here is control flow, counts
and `correct`; no number read here is a device metric."""

import json
import os
import shutil

import jax
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT, write_json

CELL = "tiny_agg"
E2E = {"bls_verified_sets_per_s", "bls_verify_p95_ms", "setup_s"}
MINE = {"agg_stage_prepare_ms", "agg_stage_h2c_ms", "agg_stage_pairs_ms",
        "agg_stage_pairing_ms", "agg_marshal_ms", "agg_batch_verify_ms",
        "agg_distinct_message_share", "agg_bucket_key_fill_share",
        "agg_bucket_set_fill_share", "agg_pubkey_cache_miss_share",
        "device_idle_share.agg"}


@pytest.fixture
def agg_dir(tmp_path):
    """A benchmark directory holding the rehearsal twin of
    `aggregate_flood` alone: the committed driver, layer metrics and peaks,
    the smoke pool, and new workload and config files."""
    d = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(BENCH_DIR, "drivers"), d / "drivers")
    for name, spec in bench_run.load_layer_metrics().items():
        if spec.get("cells") is not None:
            spec["cells"] = [CELL for c in spec["cells"]
                             if c == "aggregate_flood"]
        write_json(d / "layer_metrics" / f"{name}.json", spec)
    os.makedirs(d / "data")
    shutil.copy(os.path.join(BENCH_DIR, "data", "agg_pool_smoke.npz"),
                d / "data" / "agg_pool_smoke.npz")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peaks["device_kinds"][kind] = {"hbm_bytes_per_s": 1e9}   # tests only
    write_json(d / "peaks.json", peaks)
    write_json(d / "configs" / "tiny-agg-4.json",
               {"aggregates_per_dispatch": 2, "committee_size": 4,
                "committees": 2, "aggregators_per_committee": 2})
    write_json(d / "workloads" / f"{CELL}.json", {
        "config": "tiny-agg-4", "driver": "bls_aggregate_flood", "chips": 1,
        "params": {"backend": "python", "pool": "data/agg_pool_smoke.npz",
                   "batch_aggregates": 2, "backlog_aggregates": 2,
                   "bucket": [8, 4], "preroll_batches": 1,
                   "reference_aggregates": 2, "trace_window_s": 0.3,
                   "tamper_window": None}})
    return str(d)


def measure(bench_dir, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(CELL, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(agg_dir):
    res = measure(agg_dir, seed=2**31 + 31)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % 6 == 0          # whole batches of 2 x 3 sets
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)     # plain numbers only


@pytest.mark.parametrize("tamper", ["swap_signature", "flip_message"])
@pytest.mark.parametrize("seed", [7, 8, 9])    # the damaged role is seeded
def test_a_damaged_set_in_the_window_turns_correct_false(agg_dir, tamper,
                                                         seed):
    # what check_outputs.py will run
    assert tamper in bench_run.load_driver("bls_aggregate_flood").CONTROLS
    res = measure(agg_dir, seed=seed, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] > 0


def test_a_verifier_that_always_says_true_is_not_correct(agg_dir,
                                                         monkeypatch):
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
    assert measure(agg_dir)["correct"] is False


def test_a_dispatch_is_whole_aggregates_in_arrival_order(agg_dir,
                                                         monkeypatch):
    """Every dispatch reaches the backend as ONE call of 6 sets — widths
    1, 1, 3-4 twice, both selection proofs on one message — from coalesced
    gossip_aggregate work items, and the trio fallback never runs: the
    False batches of set-up and of the end are False for each aggregate."""
    from lighthouse_tpu.chain import aggregate_batch as ab
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_signature_sets
    seen = []

    def watching(sets, rands):
        seen.append([(len(s.signing_keys), s.message) for s in sets])
        return real(sets, rands)

    monkeypatch.setattr(backend, "verify_signature_sets", watching)
    kinds = set()
    real_submit = bp.BeaconProcessor.submit

    def submit(self, item):
        kinds.add((item.kind.name, item.run_batch is not None))
        return real_submit(self, item)

    monkeypatch.setattr(bp.BeaconProcessor, "submit", submit)
    fallback0 = ab._BATCH_FALLBACK.value
    res = measure(agg_dir, seconds=0.5)
    assert res["correct"] is True
    assert kinds == {("gossip_aggregate", True)}
    assert ab._BATCH_FALLBACK.value == fallback0
    for call in seen:
        widths = [w for w, _ in call]
        assert widths[0::3] == [1, 1] and widths[1::3] == [1, 1]
        assert all(3 <= w <= 4 for w in widths[2::3])
    # the flood's dispatches (after the reference's two and set-up's three,
    # before the damaged one at the end): one slot message
    for call in seen[5:-1]:
        assert call[0][1] == call[3][1]
        assert 4 <= len({m for _, m in call}) <= 5


def test_a_traced_run_reports_its_per_layer_metrics(agg_dir, monkeypatch):
    # XLA:CPU's operations sit on the host plane: stand it in for a device
    # plane to rehearse the path. The share it gives is not a device number.
    # The pure-Python backend drives no device: one small jit runs in the
    # profiler window so that the plane exists.
    monkeypatch.setattr(trace_reduce, "DEVICE_PREFIX", "/host:CPU")
    real_begin = bench_run.Harness.trace_begin

    def begin_and_touch(self):
        real_begin(self)
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(64)))

    monkeypatch.setattr(bench_run.Harness, "trace_begin", begin_and_touch)
    res = measure(agg_dir, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert {"setup_compile_s", "setup_trace_lower_s",
            "device_idle_share.agg", "agg_batch_verify_ms"} <= got
    assert "bls_verify_p95_ms" not in got
    # the pure-Python backend moves no jaxbls family, so the readers of
    # those find nothing and leave their metrics out, as on a parent commit
    # that lacks a family; no other cell's metric is read here
    assert got <= MINE | {"setup_compile_s", "setup_trace_lower_s"}
    assert not {m for m in got if m.startswith(("agg_stage", "agg_bucket"))}
    assert res["metrics"]["agg_batch_verify_ms"]["value"] > 0


def test_the_new_files_are_found_by_name_and_match_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["aggregate_flood"]
    wl = bench_run.load_json("workloads", "aggregate_flood")
    assert wl["driver"] == "bls_aggregate_flood" and wl["chips"] == 1
    assert cell["config"] == wl["config"] == "mainnet-agg-512"
    assert wl["params"] == {
        "backend": "jax", "pool": "data/agg_pool_512.npz",
        "batch_aggregates": 64, "backlog_aggregates": 256,
        "bucket": [256, 512], "preroll_batches": 4,
        "reference_aggregates": 2, "trace_window_s": 3.0,
        "tamper_window": None}
    bench_run.load_driver(wl["driver"])
    cfg = bench_run.load_json("configs", wl["config"])
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert len(cfg["source"]) <= 200 and cfg["assumed"]
    assert (cfg["sets_per_aggregate"] * cfg["aggregates_per_dispatch"]
            == cfg["sets_per_dispatch"] == 192)
    assert (cfg["committees"] * cfg["aggregators_per_committee"]
            == cfg["aggregates_per_slot"] == 1024)
    mine = {k for k, v in bench_run.load_layer_metrics().items()
            if "aggregate_flood" in (v.get("cells") or ())}
    assert mine >= MINE       # among them: later PRs name this cell too
    for m in bench["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == ["aggregate_flood"]
            assert m["moves"] == "bls_verified_sets_per_s"


def test_the_pools_hold_what_the_configuration_says():
    driver = bench_run.load_driver("bls_aggregate_flood")
    cfg = bench_run.load_json("configs", "mainnet-agg-512")
    pool, meta = driver.load_pool(
        os.path.join(BENCH_DIR, "data", "agg_pool_512.npz"))
    assert len(pool) == meta["n_aggregates"] == cfg["aggregates_per_slot"]
    for key in ("committee_size", "committees", "aggregators_per_committee"):
        assert meta[key] == cfg[key]
    widths = [[len(s.signing_keys) for s in a.trio] for a in pool]
    assert all(w[:2] == [1, 1] and 448 <= w[2] <= 512 for w in widths)
    assert len({a.trio[0].message for a in pool}) == 1
    assert len({a.trio[1].message for a in pool}) == 1024
    assert len({a.trio[2].message for a in pool}) == 64
    for a in pool:
        # the aggregator is one validator, a member of its own aggregate
        assert a.trio[0].signing_keys[0] is a.trio[1].signing_keys[0]
        assert any(pk is a.trio[0].signing_keys[0]
                   for pk in a.trio[2].signing_keys)
    # 16 aggregates of a committee share its message, with other key sets
    first = [a for a in pool if a.committee == 0]
    assert len(first) == 16
    assert len({tuple(map(id, a.trio[2].signing_keys)) for a in first}) == 16
    keys = sum(sum(w) for w in widths) / len(pool) * 64
    assert 29_000 < keys < 33_000          # ~31 k keys a dispatch of 64


def test_the_message_share_reads_the_new_counter_and_nothing_on_a_parent():
    """`agg_distinct_message_share` over a registry that has the family
    (106 of 192 = 55.2 %) and over one that lacks it (None: the metric is
    left out, it does not raise)."""
    import layer_reader
    from lighthouse_tpu.utils.metrics import Registry

    reg = Registry()
    before = layer_reader.snapshot(reg)
    fam = reg.counter_vec("jaxbls_dispatch_messages_total", "m", ("kind",))
    fam.labels("sent").inc(192)
    fam.labels("distinct").inc(106)
    after = layer_reader.snapshot(reg)
    metrics = bench_run.load_layer_metrics()
    share = layer_reader.evaluate(
        metrics["agg_distinct_message_share"]["source"], before, after, {}, {})
    assert round(share, 1) == 55.2
    for name in MINE - {"device_idle_share.agg"}:
        assert layer_reader.evaluate(metrics[name]["source"], before, before,
                                     {}, {}) is None
