"""Driver `bls_request_loop` at rehearsal size on the CPU, through
`run.measure`: a block in small through SignatureBatch, and one set at a
time down the urgent entry, both on the pure-Python backend. What is checked
here is control flow, counts and `correct`; no number read here is a device
metric."""

import json
import os

import jax
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT

CELLS = ["tiny_block", "tiny_urgent"]
E2E = {"bls_verify_p95_ms", "request_p50_ms", "setup_s"}


def measure(bench_dir, cell, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(cell, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        rehearsal_dir, cell):
    res = measure(rehearsal_dir, cell, seed=2**31 + 27)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)     # plain numbers only


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("tamper", ["swap_signature", "flip_message"])
def test_a_damaged_operand_in_the_window_turns_correct_false(
        rehearsal_dir, cell, tamper):
    # what check_outputs.py will run
    assert tamper in bench_run.load_driver("bls_request_loop").CONTROLS
    res = measure(rehearsal_dir, cell, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_verifier_that_always_says_true_is_not_correct(
        rehearsal_dir, cell, monkeypatch):
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
    res = measure(rehearsal_dir, cell)
    assert res["correct"] is False


@pytest.mark.parametrize("cell,sets", [("tiny_block", 5), ("tiny_urgent", 1)])
def test_the_median_is_numpys_over_one_latency_a_request(
        rehearsal_dir, cell, sets, capsys, monkeypatch):
    """`request_p50_ms` is `np.median` of the window's request latencies,
    one a request and not one a set, the number the driver's own line has
    always printed; the p95 over sets is still reported beside it."""
    import numpy as np

    seen = []
    real = np.median

    def watching(a, *args, **kw):
        seen.append((len(a), float(real(a, *args, **kw))))
        return seen[-1][1]

    monkeypatch.setattr(np, "median", watching)
    res = measure(rehearsal_dir, cell, seed=2**31 + 40)
    monkeypatch.undo()
    line = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                if ln.startswith('{"step": "bls_request_loop"'))
    lat = line["request_latency_ms"]
    p50, p95 = (res["metrics"][k] for k in ("request_p50_ms",
                                            "bls_verify_p95_ms"))
    assert p50["unit"] == p95["unit"] == "ms"
    assert (lat["n"], p50["value"]) in seen       # numpy's, over requests
    assert lat["n"] * sets == res["attempted"]
    assert p50["value"] == lat["median"]
    assert p95["value"] == lat["p95_over_sets"]
    assert lat["min"] <= p50["value"] <= p95["value"] <= lat["max"]


def test_every_run_says_which_level_its_process_drew(rehearsal_dir, capsys):
    measure(rehearsal_dir, "tiny_urgent", seconds=0.3)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"step": "runtime_call"')]
    assert len(lines) == 1 and lines[0]["n"] == 64
    assert 0 < lines[0]["min_us"] <= lines[0]["median_us"] <= lines[0]["max_us"]


def test_a_block_goes_down_as_one_batch_in_block_order(
        rehearsal_dir, monkeypatch):
    """Every request of the block cell reaches the backend as ONE call of
    five sets, widths 1, 1, 4, 4, 8, from a gossip_block work item."""
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_signature_sets
    seen = []

    def watching(sets, rands):
        seen.append([len(s.signing_keys) for s in sets])
        return real(sets, rands)

    monkeypatch.setattr(backend, "verify_signature_sets", watching)
    kinds = []
    real_submit = bp.BeaconProcessor.submit

    def submit(self, item):
        kinds.append((item.kind.name, item.run is not None))
        return real_submit(self, item)

    monkeypatch.setattr(bp.BeaconProcessor, "submit", submit)
    res = measure(rehearsal_dir, "tiny_block", seconds=0.5)
    assert res["correct"] is True
    assert seen[0] == [1, 1, 4, 8]               # the reference's sample
    assert all(w == [1, 1, 4, 4, 8] for w in seen[2:])
    assert set(kinds) == {("gossip_block", True)}
    assert res["attempted"] % 5 == 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_the_new_per_layer_metrics(
        rehearsal_dir, cell, monkeypatch):
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
    res = measure(rehearsal_dir, cell, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert {"setup_compile_s", "setup_trace_lower_s"} <= got
    assert "bls_verify_p95_ms" not in got
    mine = "block" if cell == "tiny_block" else "urgent"
    other = "urgent" if cell == "tiny_block" else "block"
    assert f"device_idle_share.{mine}" in got
    assert f"device_idle_share.{other}" not in got
    assert "device_idle_share.bls" not in got
    # the block's own family is read where a block is verified, and only
    # there; the pure-Python backend moves no jaxbls family, so the readers
    # of those find nothing and leave their metrics out, as on a parent
    # commit that lacks the family
    assert ("block_batch_verify_ms" in got) == (cell == "tiny_block")
    assert not {m for m in got if m.startswith(("req_", "bucket_"))}


def test_the_new_files_are_found_by_name_and_match_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, entry, bucket in (("block_import_131", "signature_batch",
                                 [256, 512]),
                                ("urgent_verify", "urgent", [4, 128])):
        wl = bench_run.load_json("workloads", name)
        assert wl["driver"] == "bls_request_loop" and wl["chips"] == 1
        assert wl["params"]["entry"] == entry
        assert wl["params"]["bucket"] == bucket
        assert wl["params"]["backend"] == "jax"
        assert cells[name]["config"] == wl["config"]
        bench_run.load_json("configs", wl["config"])
        bench_run.load_driver(wl["driver"])
        assert os.path.isfile(os.path.join(BENCH_DIR, wl["params"]["pool"]))
    cfg = bench_run.load_json("configs", "mainnet-block-131")
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert sum(s["count"] for s in cfg["sets"]) == cfg["sets_per_request"]
    assert sum(s["count"] * s["keys_per_set"]
               for s in cfg["sets"]) == cfg["keys_per_request"] == 16898
    n, m = cfg["padding_bucket"]["n_sets"], cfg["padding_bucket"]["n_pks"]
    assert round(100 * 16898 / (n * m), 1) == 12.9
    assert round(100 * 131 / n, 1) == 51.2
    metrics = bench_run.load_layer_metrics()
    mine = {k for k, v in metrics.items()
            if set(v.get("cells") or ()) & {"block_import_131",
                                            "urgent_verify"}}
    # the cells' own metrics are among those that name them: a later PR
    # may add a metric that names these cells too
    assert mine >= {"req_stage_prepare_ms", "req_stage_h2c_ms",
                    "req_stage_pairs_ms", "req_stage_pairing_ms",
                    "req_marshal_ms", "bucket_key_fill_share",
                    "bucket_set_fill_share", "block_batch_verify_ms",
                    "device_idle_share.block", "device_idle_share.urgent"}


def test_the_fill_shares_read_the_new_counter_and_nothing_on_a_parent():
    """The ratio source of the two fill shares over a registry that has
    the family (12.9 % and 51.2 % for one block) and over one that lacks
    it (None: the metric is left out, it does not raise)."""
    import layer_reader
    from lighthouse_tpu.utils.metrics import Registry

    reg = Registry()
    before = layer_reader.snapshot(reg)
    fam = reg.counter_vec("jaxbls_bucket_slots_total", "slots",
                          ("axis", "kind"))
    fam.labels("sets", "real").inc(131)
    fam.labels("sets", "padded").inc(256)
    fam.labels("keys", "real").inc(16898)
    fam.labels("keys", "padded").inc(256 * 512)
    after = layer_reader.snapshot(reg)
    metrics = bench_run.load_layer_metrics()
    key = layer_reader.evaluate(metrics["bucket_key_fill_share"]["source"],
                                before, after, {}, {})
    sets = layer_reader.evaluate(metrics["bucket_set_fill_share"]["source"],
                                 before, after, {}, {})
    assert round(key, 1) == 12.9 and round(sets, 1) == 51.2
    for name in ("bucket_key_fill_share", "bucket_set_fill_share",
                 "block_batch_verify_ms", "req_stage_prepare_ms"):
        assert layer_reader.evaluate(metrics[name]["source"], before, before,
                                     {}, {}) is None
