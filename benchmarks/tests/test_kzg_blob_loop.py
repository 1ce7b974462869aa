"""Driver `kzg_blob_loop` at rehearsal size on the CPU, through
`run.measure`: blocks of two sidecars of 64 field elements from
`blob_pool_smoke.npz`, on the pure-Python backend. What is checked here is
control flow, counts and `correct`; no number read here is a device metric."""

import json
import os
import shutil

import jax
import pytest
import run as bench_run
import trace_reduce

from conftest import BENCH_DIR, REPO_ROOT, write_json

CELL = "tiny_blobs"
E2E = {"bls_verify_p95_ms", "setup_s"}
MINE = {"kzg_batch_verify_ms", "kzg_batch_width_mean", "kzg_fallback_share",
        "kzg_host_field_ms", "kzg_host_points_ms", "kzg_stage_lincomb_ms",
        "kzg_stage_pairing_ms", "kzg_lane_fill_share",
        "device_idle_share.kzg"}
B = 2          # sidecars a block here


@pytest.fixture
def blob_dir(tmp_path):
    """A benchmark directory holding the rehearsal twin of `kzg_6_blobs`
    alone: the committed driver, reference, layer metrics and peaks, the
    smoke pool, and new workload and config files."""
    d = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(BENCH_DIR, "drivers"), d / "drivers")
    shutil.copytree(os.path.join(BENCH_DIR, "reference"), d / "reference")
    for name, spec in bench_run.load_layer_metrics().items():
        if spec.get("cells") is not None:
            spec["cells"] = [CELL for c in spec["cells"] if c == "kzg_6_blobs"]
        write_json(d / "layer_metrics" / f"{name}.json", spec)
    os.makedirs(d / "data")
    shutil.copy(os.path.join(BENCH_DIR, "data", "blob_pool_smoke.npz"),
                d / "data" / "blob_pool_smoke.npz")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peaks["device_kinds"][kind] = {"hbm_bytes_per_s": 1e9}   # tests only
    write_json(d / "peaks.json", peaks)
    write_json(d / "configs" / "tiny-blobs-2.json",
               {"sidecars_per_block": B, "field_elements_per_blob": 64})
    write_json(d / "workloads" / f"{CELL}.json", {
        "config": "tiny-blobs-2", "driver": "kzg_blob_loop", "chips": 1,
        "params": {"backend": "python", "pool": "data/blob_pool_smoke.npz",
                   "preroll_blocks": 1, "reference_blocks": 1,
                   "trace_window_s": 0.3, "tamper_window": None}})
    return str(d)


def measure(bench_dir, seed=7, seconds=1.0, trace=False, **over):
    return bench_run.measure(CELL, seed, seconds, trace, jax.devices(),
                             bench_dir=bench_dir, param_overrides=over)


def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(blob_dir):
    res = measure(blob_dir, seed=2**31 + 33)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["attempted"] % B == 0           # whole blocks
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)     # plain numbers only


@pytest.mark.parametrize("tamper", ["swap_proof", "flip_blob_byte"])
def test_a_damaged_sidecar_in_the_window_turns_correct_false(blob_dir, tamper):
    # what check_outputs.py will run
    assert tamper in bench_run.load_driver("kzg_blob_loop").CONTROLS
    res = measure(blob_dir, seed=8, tamper_window=tamper)
    assert res["correct"] is False
    assert res["failed"] > 0


def test_a_verifier_that_always_says_true_is_not_correct(blob_dir,
                                                         monkeypatch):
    """The timed path broken underneath: every batch True, every point in
    the subgroup. The window cannot tell; the damaged blocks after it do."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import api

    bls.set_backend("python")
    monkeypatch.setattr(
        bls.get_backend(), "verify_kzg_batch_async",
        lambda commitments, *_: api._ReadyHandle(
            (True, [(True, True)] * len(commitments))),
        raising=False)
    assert measure(blob_dir)["correct"] is False


def test_a_block_is_one_batch_of_coalesced_work_items(blob_dir, monkeypatch):
    """Every block of the loop reaches the backend as ONE submission of its
    two sidecars, from coalesced gossip_blob_sidecar work items; the single
    re-verifications happen only for the damaged blocks after the window."""
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.crypto import bls

    bls.set_backend("python")
    backend = bls.get_backend()
    real = backend.verify_kzg_batch_async
    seen = []

    def watching(commitments, *rest):
        seen.append(len(commitments))
        return real(commitments, *rest)

    monkeypatch.setattr(backend, "verify_kzg_batch_async", watching,
                        raising=False)
    kinds = set()
    real_submit = bp.BeaconProcessor.submit

    def submit(self, item):
        kinds.add((item.kind.name, item.run_batch is not None))
        return real_submit(self, item)

    monkeypatch.setattr(bp.BeaconProcessor, "submit", submit)
    res = measure(blob_dir, seconds=0.5)
    assert res["correct"] is True
    assert kinds == {("gossip_blob_sidecar", True)}
    blocks = res["attempted"] // B
    # set-up's block, the pre-roll, the window, perhaps one more in flight
    # at its close: each ONE submission of B
    loop = seen[:1 + 1 + blocks]
    assert loop == [B] * len(loop)
    # after the window: the swapped proof (batch, then both alone), the
    # commitment outside the subgroup (batch, the other sidecar alone), the
    # field element >= r (a batch of the one well-formed sidecar)
    assert seen[-6:] == [B, 1, 1, B, 1, 1]


def test_a_traced_run_reports_its_per_layer_metrics(blob_dir, monkeypatch):
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
    res = measure(blob_dir, trace=True)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert "bls_verify_p95_ms" not in got
    # the pure-Python backend moves no jaxbls or lane family, so the readers
    # of those find nothing and leave their metrics out, as on a parent
    # commit that lacks a family; no other cell's metric is read here
    assert got == (MINE | {"setup_compile_s", "setup_trace_lower_s"}) - {
        "kzg_stage_lincomb_ms", "kzg_stage_pairing_ms", "kzg_lane_fill_share"}
    m = res["metrics"]
    assert m["kzg_batch_width_mean"]["value"] == B
    assert m["kzg_fallback_share"]["value"] == 0
    assert m["kzg_batch_verify_ms"]["value"] > m["kzg_host_field_ms"]["value"] > 0
    assert m["kzg_host_points_ms"]["value"] > 0


def test_the_reference_and_the_program_agree_on_the_smoke_pool():
    """`reference/kzg_spec.py` against `crypto/kzg.py` (python backend) on a
    block of the smoke pool: valid, a swapped proof, each sidecar alone."""
    from lighthouse_tpu.crypto import bls, kzg

    drv = bench_run.load_driver("kzg_blob_loop")
    ref = drv.load_reference(BENCH_DIR)
    pool, meta = drv.load_pool(
        os.path.join(BENCH_DIR, "data", "blob_pool_smoke.npz"))
    n = meta["field_elements_per_blob"]
    setup = kzg.TrustedSetup.dev_verifier_setup(n)
    ref_setup = ref.Setup(n, setup.g2_monomial[1])
    bls.set_backend("python")
    block = pool[:3]
    swapped = [drv.Sidecar(block[0].blob, block[0].kzg_commitment,
                           block[1].kzg_proof)] + block[1:]
    for sidecars, want in ((block, True), (swapped, False)):
        args = ([s.blob for s in sidecars],
                [s.kzg_commitment for s in sidecars],
                [s.kzg_proof for s in sidecars])
        assert ref.verify_blob_kzg_proof_batch(*args, ref_setup) is want
        assert kzg.verify_blob_kzg_proof_batch(*args, setup) is want
    each = [ref.verify_blob_kzg_proof(s.blob, s.kzg_commitment, s.kzg_proof,
                                      ref_setup) for s in swapped]
    assert each == [False, True, True]


def test_the_new_files_are_found_by_name_and_match_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["kzg_6_blobs"]
    wl = bench_run.load_json("workloads", "kzg_6_blobs")
    assert wl["driver"] == "kzg_blob_loop" and wl["chips"] == 1
    assert cell["config"] == wl["config"] == "mainnet-blobs-6"
    assert cell["traffic"] == wl["traffic"] == "six_sidecars_outstanding"
    assert cell["why"] == wl["why"] and wl["who"]
    assert wl["params"] == {
        "backend": "jax", "pool": "data/blob_pool_6.npz",
        "preroll_blocks": 2, "reference_blocks": 1, "trace_window_s": 2.0,
        "tamper_window": None}
    bench_run.load_driver(wl["driver"])
    cfg = bench_run.load_json("configs", wl["config"])
    entry = {c["name"]: c for c in bench["configs"]}["mainnet-blobs-6"]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert len(cfg["source"]) <= 200
    assert cfg["assumed"] and cfg["departures"] and cfg["guarantees"]
    assert cfg["sidecars_per_block"] == 6
    assert (cfg["field_elements_per_blob"] * cfg["bytes_per_field_element"]
            == cfg["bytes_per_blob"] == 131072)
    assert cfg["blob_bytes_per_block"] == 6 * 131072
    mine = {k for k, v in bench_run.load_layer_metrics().items()
            if "kzg_6_blobs" in (v.get("cells") or ())}
    assert mine >= MINE       # among them: later PRs name this cell too
    listed = {m["name"] for m in bench["per_layer"]
              if "kzg_6_blobs" in m.get("workloads", ())}
    assert listed == mine
    p95 = {m["name"]: m for m in bench["end_to_end"]}["bls_verify_p95_ms"]
    assert "kzg_6_blobs" in p95["workloads"]
    z = bench_run.load_driver("kzg_blob_loop").load_pool  # the pools load
    for pool, n, count in (("blob_pool_smoke.npz", 64, 12),):
        sidecars, meta = z(os.path.join(BENCH_DIR, "data", pool))
        assert meta["field_elements_per_blob"] == n and len(sidecars) == count
        assert all(len(s.blob) == 32 * n for s in sidecars)
