"""Autotune subsystem (lighthouse_tpu/autotune): profile JSON round-trip,
planner determinism, the knob-precedence contract (profile < env var <
explicit arg), the consumers (BeaconProcessor caps, HybridBackend budget,
warmup plan), and the CPU smoke calibration end-to-end.

Everything here is host-side: the hybrid backend is constructed with the
probe short-circuited and the smoke calibration measures through the
pure-python BLS backend (a cold XLA:CPU compile of the verify pipeline
takes minutes — tests/README.md — so the device path stays the jaxbls
suites' job)."""

import json

import pytest

from lighthouse_tpu.autotune import calibrate, planner, profile, profiler, runtime
from lighthouse_tpu.utils.metrics import REGISTRY


@pytest.fixture(autouse=True)
def _clean_autotune_state():
    runtime.clear()
    profiler.reset()
    yield
    runtime.clear()
    profiler.reset()


def synthetic_profile() -> profile.DeviceProfile:
    """A fixed v5e-shaped profile; the pinned plan assertions below encode
    the planner's derivation rules against these numbers. Keyed to the
    CURRENT backend revision — runtime.install refuses stale ones (see
    test_install_rejects_stale_backend_revision)."""
    p = profile.DeviceProfile(
        key={
            "platform": "tpu", "device_kind": "TPU v5e", "num_devices": 1,
            "jax_version": "0.9.0",
            "backend_revision": profile.BACKEND_REVISION,
            "bls_backend": "jax",
        },
        source="calibrate",
    )
    rows = [
        # n_sets, n_pks, sets/s, p50_ms, p99_ms, compile_s
        (4, 128, 7.5, 529.0, 560.0, 60.0),
        (64, 128, 100.0, 640.0, 700.0, 616.0),
        (256, 128, 240.0, 1060.0, 1100.0, 900.0),
        (512, 128, 250.0, 2050.0, 2100.0, 1200.0),
    ]
    for n, m, rate, p50, p99, comp in rows:
        p.buckets[(n, m)] = profile.BucketProfile(
            n_sets=n, n_pks=m, samples=8, compile_secs=comp,
            p50_ms=p50, p99_ms=p99, sets_per_sec=rate,
        )
    p.host = {"single_set_ms": 577.0}
    # r7 tuning fields (profile round-trip + plan pass-through pinned)
    p.msm_window = 4
    p.pipeline_depth = 6
    p.warmup_small_buckets = ((4, 128),)
    return p


# ------------------------------------------------------------------ schema


def test_profile_json_round_trip_yields_identical_plan(tmp_path):
    p = synthetic_profile()
    path = profile.save(p, str(tmp_path / "prof.json"))
    loaded = profile.load(path)
    assert loaded.key == p.key
    assert set(loaded.buckets) == set(p.buckets)
    assert planner.plan_from_profile(loaded) == planner.plan_from_profile(p)
    # and a second serialize is byte-stable (sorted keys, sorted buckets)
    path2 = profile.save(loaded, str(tmp_path / "prof2.json"))
    a, b = open(path).read(), open(path2).read()
    assert json.loads(a)["buckets"] == json.loads(b)["buckets"]


def test_profile_rejects_unknown_schema_version():
    doc = synthetic_profile().to_json()
    doc["schema_version"] = 999
    with pytest.raises(ValueError, match="schema_version"):
        profile.DeviceProfile.from_json(doc)


# ----------------------------------------------------------------- planner


def test_planner_is_deterministic_and_pinned():
    p = synthetic_profile()
    plan1 = planner.plan_from_profile(p)
    plan2 = planner.plan_from_profile(synthetic_profile())
    assert plan1 == plan2
    # knee rule: peak 250 sets/s at n=512; smallest bucket within 10% is 256
    assert plan1.max_attestation_batch == 256
    assert plan1.max_aggregate_batch == 128
    # budget: 2x the smallest bucket's p99 (560 ms)
    assert plan1.p99_budget_ms == 1120.0
    # host single set (577 ms) never beats the device p50 at any bucket
    assert plan1.urgent_max_sets == 1
    # warmup: best throughput first
    assert plan1.warmup_buckets == ((512, 128), (256, 128), (64, 128), (4, 128))
    # r7 tuning fields pass through (clamped/validated)
    assert plan1.pipeline_depth == 6
    assert plan1.msm_window == 4
    assert plan1.source.startswith("profile:")


def test_planner_defaults_match_hardcoded_constants():
    """An empty profile derives exactly the historical constants — the
    no-profile node and the empty-profile node behave identically."""
    from lighthouse_tpu.chain import beacon_processor as bp

    empty = profile.DeviceProfile(key={"platform": "cpu"})
    plan = planner.plan_from_profile(empty)
    assert plan.max_attestation_batch == bp.DEFAULT_MAX_ATTESTATION_BATCH
    assert plan.max_aggregate_batch == bp.DEFAULT_MAX_AGGREGATE_BATCH
    assert plan.p99_budget_ms == 500.0
    assert plan.urgent_max_sets == 4
    assert plan.warmup_buckets == planner.DEFAULT_WARMUP_BUCKETS
    assert plan.pipeline_depth == planner.DEFAULT_PIPELINE_DEPTH == 4
    assert plan.msm_window is None


def test_planner_never_lowers_cap_on_a_rising_sweep():
    """A knee sitting at the sweep's largest bucket means throughput was
    still rising when measurement stopped — the cap must not drop below
    the default on that (absent) evidence."""
    p = profile.DeviceProfile(key={"platform": "tpu"})
    for n, rate in [(64, 100.0), (256, 249.0), (512, 308.0)]:  # r5 numbers
        p.buckets[(n, 128)] = profile.BucketProfile(
            n_sets=n, n_pks=128, samples=8, p50_ms=1000.0, p99_ms=1100.0,
            sets_per_sec=rate,
        )
    plan = planner.plan_from_profile(p)
    assert plan.max_attestation_batch == planner.DEFAULT_MAX_ATTESTATION_BATCH
    assert plan.max_aggregate_batch == planner.DEFAULT_MAX_AGGREGATE_BATCH


def test_profile_rejects_malformed_bucket_entry():
    doc = synthetic_profile().to_json()
    del doc["buckets"][0]["n_sets"]
    with pytest.raises(ValueError, match="malformed autotune profile bucket"):
        profile.DeviceProfile.from_json(doc)


# --------------------------------------------- r7 schema migration fields


def test_profile_round_trips_r7_tuning_fields(tmp_path):
    p = synthetic_profile()
    path = profile.save(p, str(tmp_path / "p.json"))
    loaded = profile.load(path)
    assert loaded.msm_window == 4
    assert loaded.pipeline_depth == 6
    assert loaded.warmup_small_buckets == ((4, 128),)
    # pre-r7 documents (no tuning fields) still parse: consumers fall
    # back to the planner defaults, the file is not rejected for SHAPE
    doc = p.to_json()
    for key in ("msm_window", "pipeline_depth", "warmup_small_buckets"):
        del doc[key]
    old = profile.DeviceProfile.from_json(doc)
    assert old.msm_window is None and old.pipeline_depth is None
    plan = planner.plan_from_profile(old)
    assert plan.pipeline_depth == planner.DEFAULT_PIPELINE_DEPTH
    assert plan.msm_window is None


def test_profile_rejects_invalid_msm_window_and_depth():
    doc = synthetic_profile().to_json()
    doc["msm_window"] = 3          # not in the sweep's search space
    with pytest.raises(ValueError, match="msm_window"):
        profile.DeviceProfile.from_json(doc)
    # 0 is a VALID measured verdict: the bit form won the device sweep
    doc["msm_window"] = 0
    assert profile.DeviceProfile.from_json(doc).msm_window == 0
    doc = synthetic_profile().to_json()
    doc["pipeline_depth"] = 0
    with pytest.raises(ValueError, match="pipeline_depth"):
        profile.DeviceProfile.from_json(doc)
    doc = synthetic_profile().to_json()
    doc["warmup_small_buckets"] = ["not-a-pair"]
    with pytest.raises(ValueError, match="warmup_small_buckets"):
        profile.DeviceProfile.from_json(doc)


def test_install_rejects_stale_backend_revision():
    """A profile measured under an older jaxbls BACKEND_REVISION (pre-
    donation kernel structure) must NOT become the knob source: install
    refuses it cleanly and consumers keep their defaults. The explicit
    operator override (allow_stale, the --autotune-profile path) still
    installs, loudly."""
    stale = synthetic_profile()
    stale.key["backend_revision"] = "r5"
    assert stale.is_stale()
    assert runtime.install_profile(stale) is None
    assert runtime.active_plan() is None

    plan = runtime.install_profile(stale, allow_stale=True)
    assert plan is not None and plan.max_attestation_batch == 256


def test_planner_warmup_always_includes_small_buckets():
    """Five wide buckets out-throughput the small one, filling the top-4
    warmup list — the profile's small/urgent shapes must be APPENDED so
    bring-up still precompiles the urgent fast path's bucket."""
    p = synthetic_profile()
    p.buckets[(1024, 128)] = profile.BucketProfile(
        n_sets=1024, n_pks=128, samples=8, p50_ms=4000.0, p99_ms=4100.0,
        sets_per_sec=260.0,
    )
    plan = planner.plan_from_profile(p)
    assert plan.warmup_buckets[:4] == (
        (1024, 128), (512, 128), (256, 128), (64, 128)
    )
    assert (4, 128) in plan.warmup_buckets  # appended, not dropped

    # without an explicit small list the smallest measured bucket is used
    p2 = synthetic_profile()
    p2.warmup_small_buckets = None
    p2.buckets[(1024, 128)] = profile.BucketProfile(
        n_sets=1024, n_pks=128, samples=8, p50_ms=4000.0, p99_ms=4100.0,
        sets_per_sec=260.0,
    )
    assert (4, 128) in planner.plan_from_profile(p2).warmup_buckets


def test_planner_urgent_threshold_uses_host_reference():
    p = synthetic_profile()
    # a 100x faster host: sequential host verifies beat the device p50 up
    # to the 64-set bucket (64 * 5.77 = 369 ms <= 640 ms) but not 256
    p.host = {"single_set_ms": 5.77}
    assert planner.plan_from_profile(p).urgent_max_sets == 64


# --------------------------------------------------------------- consumers


def test_beacon_processor_caps_follow_installed_profile():
    from lighthouse_tpu.chain.beacon_processor import (
        DEFAULT_MAX_AGGREGATE_BATCH,
        DEFAULT_MAX_ATTESTATION_BATCH,
        BeaconProcessorConfig,
    )

    cfg = BeaconProcessorConfig()
    assert cfg.max_attestation_batch == DEFAULT_MAX_ATTESTATION_BATCH
    assert cfg.max_aggregate_batch == DEFAULT_MAX_AGGREGATE_BATCH

    runtime.install_profile(synthetic_profile())
    tuned = BeaconProcessorConfig()
    assert tuned.max_attestation_batch == 256
    assert tuned.max_aggregate_batch == 128
    # the in-flight window follows the plan's measured pipeline depth
    assert tuned.max_inflight == 6
    # explicit values (CLI flags) still win over the plan
    explicit = BeaconProcessorConfig(max_attestation_batch=7, max_inflight=2)
    assert explicit.max_attestation_batch == 7
    assert explicit.max_inflight == 2

    runtime.clear()
    again = BeaconProcessorConfig()
    assert again.max_attestation_batch == DEFAULT_MAX_ATTESTATION_BATCH
    assert again.max_inflight == 4


def _make_hybrid(**kw):
    from lighthouse_tpu.crypto.bls.hybrid import HybridBackend

    return HybridBackend(
        probe_startup_wait_secs=0.1, probe_retry_secs=3600, **kw
    )


def test_hybrid_defaults_without_profile(monkeypatch):
    monkeypatch.delenv("LIGHTHOUSE_TPU_URGENT_MAX_SETS", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", raising=False)
    b = _make_hybrid()
    assert (b.urgent_max_sets, b.p99_budget_ms) == (4, 500.0)
    assert b.knob_sources == {
        "urgent_max_sets": "default", "p99_budget_ms": "default",
    }


def test_hybrid_knob_precedence(monkeypatch):
    """profile-derived < env var < explicit constructor arg."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_URGENT_MAX_SETS", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", raising=False)
    runtime.install_profile(synthetic_profile())

    b = _make_hybrid()
    assert (b.urgent_max_sets, b.p99_budget_ms) == (1, 1120.0)
    assert b.knob_sources["p99_budget_ms"] == "profile"

    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", "123")
    b = _make_hybrid()
    assert b.p99_budget_ms == 123.0
    assert b.knob_sources == {
        "urgent_max_sets": "profile", "p99_budget_ms": "env",
    }

    b = _make_hybrid(p99_budget_ms=42.0, urgent_max_sets=9)
    assert (b.urgent_max_sets, b.p99_budget_ms) == (9, 42.0)
    assert b.knob_sources == {
        "urgent_max_sets": "constructor", "p99_budget_ms": "constructor",
    }

    # malformed env falls through to the profile layer, not to a crash
    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", "not-a-float")
    b = _make_hybrid()
    assert b.p99_budget_ms == 1120.0
    assert b.knob_sources["p99_budget_ms"] == "profile"


def test_hybrid_reresolves_budgets_on_runtime_install(monkeypatch):
    """The mid-run retune fix: installing a profile AFTER the router was
    constructed re-derives the p99 budget and urgent threshold
    immediately (pre-r8 they were resolved once at construction, so an
    `autotune calibrate` + install mid-run served stale budgets until
    restart). Clearing reverts; env-pinned knobs never move."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_URGENT_MAX_SETS", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", raising=False)

    b = _make_hybrid()
    assert (b.urgent_max_sets, b.p99_budget_ms) == (4, 500.0)
    # stall budget tracks the resolved p99 budget (4x) unless pinned
    assert b._stall_budget_secs == pytest.approx(2.0)

    runtime.install_profile(synthetic_profile())
    assert (b.urgent_max_sets, b.p99_budget_ms) == (1, 1120.0)
    assert b.knob_sources["p99_budget_ms"] == "profile"
    assert b._stall_budget_secs == pytest.approx(4.48)

    runtime.clear()
    assert (b.urgent_max_sets, b.p99_budget_ms) == (4, 500.0)
    assert b.knob_sources["p99_budget_ms"] == "default"

    # an env-pinned knob stays pinned across installs (precedence holds)
    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_P99_BUDGET_MS", "123")
    b2 = _make_hybrid()
    runtime.install_profile(synthetic_profile())
    assert b2.p99_budget_ms == 123.0
    assert b2.knob_sources["p99_budget_ms"] == "env"
    assert b2.urgent_max_sets == 1  # un-pinned knob still retunes


def test_msm_window_resolution_honors_measured_bit_form(monkeypatch):
    """A profile whose sweep measured the bit form as the winner
    (msm_window=0) must serve the bit form — the accelerator default
    (w=4) only applies when the window is UNMEASURED (None)."""
    from lighthouse_tpu.crypto.jaxbls.msm import msm_window

    monkeypatch.delenv("LIGHTHOUSE_TPU_MSM_WINDOW", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_MSM_WINDOWED", raising=False)
    p = synthetic_profile()
    p.msm_window = 0
    runtime.install_profile(p)
    assert msm_window() == 0
    p2 = synthetic_profile()
    p2.msm_window = 5
    runtime.install_profile(p2)
    assert msm_window() == 5
    # env override still beats the plan
    monkeypatch.setenv("LIGHTHOUSE_TPU_MSM_WINDOW", "2")
    assert msm_window() == 2


def test_jaxbls_dispatcher_depth_follows_runtime_install():
    """The jaxbls pipeline depth resolution consults the installed plan
    (env > plan > default) — the depth the backend's dispatcher and the
    processor's in-flight window both derive from."""
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl

    assert pl.resolve_depth() == (4, "default")
    runtime.install_profile(synthetic_profile())
    assert pl.resolve_depth() == (6, "profile")
    assert pl.resolve_depth(explicit=2) == (2, "explicit")
    runtime.clear()
    assert pl.resolve_depth() == (4, "default")


# ---------------------------------------------------------------- profiler


def test_profiler_records_and_exposes_per_bucket_metrics():
    # first dispatch at a cold bucket is classified as its compile
    profiler.observe_dispatch(8, 4, 30.0, 8)
    profiler.observe_dispatch(8, 4, 0.5, 8)
    profiler.observe_dispatch(8, 4, 0.3, 6)
    profiler.observe_compile(16, 4, 12.0)

    buckets = profiler.snapshot_buckets()
    b = buckets[(8, 4)]
    assert b.compile_secs == 30.0
    assert b.samples == 2
    assert b.sets_per_sec == pytest.approx(14 / 0.8, rel=1e-6)
    assert buckets[(16, 4)].compile_secs == 12.0

    text = REGISTRY.expose_text()
    # labeled per-bucket families (the name-mangled autotune_*_n{n}_m{m}
    # series were migrated to labels in the observability PR)
    assert ('autotune_dispatch_seconds_bucket'
            '{n_sets="8",n_pks="4",le="0.5"}') in text
    assert 'autotune_sets_per_sec{n_sets="8",n_pks="4"}' in text
    assert 'autotune_compile_seconds{n_sets="16",n_pks="4"}' in text
    assert "autotune_dispatches_total" in text


def test_profiler_first_dispatch_after_warm_still_counts_as_compile():
    """warm_stages only covers stages 1-2, so the first real dispatch at a
    warmed bucket still pays the stage-3/4 compile — it must fold into the
    compile record (max), never into the latency window."""
    profiler.observe_compile(4, 1, 99.0)
    profiler.observe_dispatch(4, 1, 120.0, 4)  # residual stage-3/4 compile
    profiler.observe_dispatch(4, 1, 0.25, 4)   # first real sample
    b = profiler.snapshot_buckets()[(4, 1)]
    assert b.compile_secs == 120.0
    assert b.samples == 1
    assert b.p50_ms == 250.0


def test_hybrid_warm_bucket_marks_routing_warm():
    """The startup warmup path: warm_bucket runs a full dummy verify on
    the device AND marks the bucket warm for routing, so the next small
    verify at that shape rides the device instead of the cold-bucket host
    detour."""
    from lighthouse_tpu.crypto.bls.hybrid import _dummy_sets

    class Stub:
        def __init__(self):
            self.calls = 0

        def verify_signature_sets(self, sets, rands):
            self.calls += 1
            return True

    dev = Stub()
    b = _make_hybrid()
    b._probe_started.set()
    b._probe_done.set()
    b._state = "up"
    b._device = dev

    assert b.warm_bucket(1, 1) is True
    assert dev.calls == 1
    assert b._warm_buckets, "bucket not marked warm for routing"
    assert not b._lats, "warmup compile time must not enter the p99 window"

    sets = _dummy_sets(1, 1)
    assert b.verify_signature_sets(sets, [1]) is True
    assert dev.calls == 2  # device path — no device_cold host detour

    # an in-flight warm of the same shape is not duplicated
    b._warm_buckets.clear()
    b._warming.add(b._bucket(sets))
    assert b.warm_bucket(1, 1) is False
    assert dev.calls == 2  # no second compile launched

    down = _make_hybrid()
    down._probe_started.set()
    down._probe_done.set()
    down._state = "down"
    assert down.warm_bucket(1, 1) is False  # degrades, never raises


# ----------------------------------------------------------------- runtime


def test_warmup_plan_fallback_and_ordering():
    assert runtime.warmup_buckets() == planner.DEFAULT_WARMUP_BUCKETS
    runtime.install_profile(synthetic_profile())
    assert runtime.warmup_buckets() == (
        (512, 128), (256, 128), (64, 128), (4, 128)
    )

    warmed = []
    t = runtime.start_warmup(warm_fn=lambda n, m: warmed.append((n, m)))
    t.join(timeout=10)
    assert warmed == [(512, 128), (256, 128), (64, 128), (4, 128)]


def test_warmup_failure_never_propagates():
    def boom(n, m):
        raise RuntimeError("device lost")

    t = runtime.start_warmup(buckets=((4, 1),), warm_fn=boom)
    t.join(timeout=10)  # the thread swallows the failure and exits


def test_autoload_explicit_path_and_kill_switch(tmp_path, monkeypatch):
    path = profile.save(synthetic_profile(), str(tmp_path / "p.json"))
    monkeypatch.setenv("LIGHTHOUSE_TPU_AUTOTUNE_PROFILE", path)
    plan = runtime.autoload()
    assert plan is not None and plan.max_attestation_batch == 256
    assert runtime.active_plan() == plan

    runtime.clear()
    monkeypatch.setenv("LIGHTHOUSE_TPU_AUTOTUNE", "0")
    assert runtime.autoload() is None
    assert runtime.active_plan() is None


def test_autoload_resolves_current_device_profile(tmp_path, monkeypatch):
    """With no explicit path, autoload detects the device key and loads
    the canonical per-device file (CPU platform: detection is instant)."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_AUTOTUNE_DIR", str(tmp_path))
    monkeypatch.delenv("LIGHTHOUSE_TPU_AUTOTUNE_PROFILE", raising=False)
    key = profile.current_device_key()
    p = synthetic_profile()
    p.key = key
    profile.save(p)  # lands at default_path(key) under tmp_path
    plan = runtime.autoload(wait_secs=30.0)
    assert plan is not None and plan.max_attestation_batch == 256


def test_autoload_corrupt_profile_degrades_to_defaults(tmp_path, monkeypatch):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("LIGHTHOUSE_TPU_AUTOTUNE_PROFILE", str(bad))
    assert runtime.autoload() is None
    assert runtime.active_plan() is None


# ------------------------------------------------- smoke calibration (e2e)


def test_smoke_calibration_end_to_end(tmp_path, capsys):
    """scripts/autotune_calibrate.py --smoke on CPU: tiny fixtures, python
    measurement backend, valid profile JSON out, autotune series in the
    Prometheus exposition — the acceptance-criteria path."""
    out = tmp_path / "smoke_profile.json"
    rc = calibrate.cli_main(["--smoke", "--out", str(out)])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["profile"] == str(out)
    assert summary["autotune_metric_series"] > 0

    prof = profile.load(str(out))
    assert prof.source == "calibrate-smoke"
    assert prof.buckets, "smoke sweep measured no buckets"
    assert prof.host and prof.host["single_set_ms"] > 0
    for b in prof.buckets.values():
        assert b.samples >= 1 and b.sets_per_sec > 0

    # the profile round-trips into a usable plan and installs
    plan = runtime.install_profile(prof)
    assert plan.max_attestation_batch >= 4
    assert plan.warmup_buckets

    text = REGISTRY.expose_text()
    n, m = next(iter(prof.buckets))
    assert f'autotune_dispatch_seconds_count{{n_sets="{n}",n_pks="{m}"}}' in text


def test_cli_autotune_show(tmp_path, capsys):
    from lighthouse_tpu.cli import main as cli_main

    path = profile.save(synthetic_profile(), str(tmp_path / "p.json"))
    rc = cli_main(["autotune", "show", "--profile", path])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plan"]["max_attestation_batch"] == 256
    assert doc["profile"]["schema_version"] == profile.SCHEMA_VERSION
    # the r7 tuning fields render in both the profile and the plan
    assert doc["profile"]["msm_window"] == 4
    assert doc["profile"]["pipeline_depth"] == 6
    assert doc["profile"]["warmup_small_buckets"] == [[4, 128]]
    assert doc["plan"]["pipeline_depth"] == 6
    assert doc["plan"]["msm_window"] == 4


# ------------------------------------------------------------ mesh (r8)


def mesh_profile(mesh_shape="sets8") -> profile.DeviceProfile:
    """synthetic_profile measured on an 8-chip sets-mesh: buckets are
    mesh-multiples and the key carries the topology."""
    p = synthetic_profile()
    p.key["mesh_shape"] = mesh_shape
    p.key["num_devices"] = 8
    return p


def test_profile_mesh_shape_round_trip_and_key_string(tmp_path):
    p = mesh_profile()
    assert p.mesh_shape == "sets8"
    assert "sets8" in p.key_string()
    path = profile.save(p, str(tmp_path / "m.json"))
    again = profile.load(path)
    assert again.mesh_shape == "sets8"
    assert again.key_string() == p.key_string()
    # pre-r8 profiles have no mesh_shape: unknowable, never flags
    legacy = synthetic_profile()
    assert legacy.mesh_shape is None
    assert legacy.mesh_mismatch("sets8") is False
    # distinct topologies must land in distinct canonical files
    assert profile.default_path(p.key) != profile.default_path(legacy.key)


def test_install_refuses_mesh_mismatched_profile():
    """A profile calibrated on one topology is refused on another — the
    same contract as the stale-revision refusal — and the refusal lands
    in the flight recorder (reason mesh_mismatch). The explicit operator
    override still installs, loudly."""
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    p = mesh_profile("sets8")
    # no live topology known -> no check possible -> installs
    assert runtime.install_profile(p) is not None
    runtime.clear()
    # matching topology installs
    assert runtime.install_profile(p, live_mesh_shape="sets8") is not None
    runtime.clear()
    # mismatch refuses + records
    before = RECORDER.events_recorded
    assert runtime.install_profile(p, live_mesh_shape="single") is None
    assert runtime.active_plan() is None
    ev = [e for e in RECORDER.events(16)
          if e["kind"] == "autotune_profile_refused"]
    assert ev and ev[-1]["reason"] == "mesh_mismatch"
    assert ev[-1]["profile_mesh"] == "sets8"
    assert ev[-1]["live_mesh"] == "single"
    assert RECORDER.events_recorded > before
    # operator override: installs with the warning
    plan = runtime.install_profile(p, live_mesh_shape="single",
                                   allow_stale=True)
    assert plan is not None


def test_install_stale_refusal_lands_in_flight_recorder():
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    stale = synthetic_profile()
    stale.key["backend_revision"] = "r5"
    assert runtime.install_profile(stale) is None
    ev = [e for e in RECORDER.events(16)
          if e["kind"] == "autotune_profile_refused"]
    assert ev and ev[-1]["reason"] == "stale_revision"


def test_planner_mesh_derivations():
    """Pinned r8 derivation rules: caps round up to mesh multiples,
    per-chip caps are the even split, the p99 budget carries the
    collective slack (1 + 0.05*log2(D)), and the stall budget is 4x the
    widened p99 — all None/neutral on a single-chip profile."""
    plan1 = planner.plan_from_profile(synthetic_profile())
    assert plan1.mesh_devices == 1
    assert plan1.per_chip_attestation_batch == plan1.max_attestation_batch
    assert plan1.p99_budget_ms == 1120.0          # 2 x 560, no slack
    assert plan1.stall_budget_ms == 4480.0

    plan8 = planner.plan_from_profile(mesh_profile("sets8"))
    assert plan8.mesh_devices == 8
    # knee at 256 already divides 8; per-chip split is exact
    assert plan8.max_attestation_batch == 256
    assert plan8.per_chip_attestation_batch == 32
    assert plan8.per_chip_aggregate_batch == 16
    # collective slack: 2 x 560 x (1 + 0.05*3) = 1288
    assert plan8.p99_budget_ms == 1288.0
    assert plan8.stall_budget_ms == 5152.0

    # a knee that does NOT divide the mesh rounds UP to a multiple
    p = mesh_profile("sets8")
    p.buckets.clear()
    rows = [(4, 1, 10.0), (20, 1, 100.0), (64, 1, 101.0)]
    for n, m, rate in rows:
        p.buckets[(n, m)] = profile.BucketProfile(
            n_sets=n, n_pks=m, samples=4, p50_ms=100.0, p99_ms=120.0,
            sets_per_sec=rate,
        )
    plan = planner.plan_from_profile(p)
    assert plan.max_attestation_batch == 24       # knee 20 -> next mult of 8
    assert plan.max_attestation_batch % 8 == 0

    # 2-D topology: total chips = product of the axes
    plan2d = planner.plan_from_profile(mesh_profile("sets4-pks2"))
    assert plan2d.mesh_devices == 8
    assert plan2d.per_chip_attestation_batch == 64  # split over sets axis


def test_hybrid_stall_budget_follows_plan(monkeypatch):
    """The hybrid router's stall verdict (the QoS breaker's failure
    signal) re-resolves from the plan's collective-aware stall budget on
    a runtime install; env still wins."""
    from lighthouse_tpu.crypto.bls.hybrid import HybridBackend

    hb = HybridBackend()
    # default: 4x the default 500ms budget
    assert hb._stall_budget_secs == pytest.approx(2.0)
    runtime.install_profile(mesh_profile("sets8"), live_mesh_shape="sets8")
    # plan: stall 5152 ms
    assert hb._stall_budget_secs == pytest.approx(5.152)
    runtime.clear()
    assert hb._stall_budget_secs == pytest.approx(2.0)

    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_STALL_BUDGET_MS", "750")
    hb2 = HybridBackend()
    runtime.install_profile(mesh_profile("sets8"), live_mesh_shape="sets8")
    assert hb2._stall_budget_secs == pytest.approx(0.75)  # env wins


def test_processor_max_inflight_retunes_on_install(monkeypatch):
    """BeaconProcessorConfig.max_inflight consumes the plan through the
    live listener (the same contract as the jaxbls dispatcher's depth);
    an explicit --max-inflight-batches value stays pinned."""
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor, BeaconProcessorConfig,
    )

    monkeypatch.delenv("LIGHTHOUSE_TPU_PIPELINE_DEPTH", raising=False)
    proc = BeaconProcessor(BeaconProcessorConfig())
    try:
        assert proc.config.max_inflight == 4      # default depth
        p = mesh_profile("sets8")
        p.pipeline_depth = 7
        runtime.install_profile(p, live_mesh_shape="sets8")
        assert proc.config.max_inflight == 7      # retuned live
        runtime.clear()
        assert proc.config.max_inflight == 4

        # explicitness is self-describing: passing a number to the
        # constructor pins it without a second flag
        pinned = BeaconProcessor(BeaconProcessorConfig(max_inflight=2))
        assert pinned.config.max_inflight_explicit is True
        try:
            runtime.install_profile(p, live_mesh_shape="sets8")
            assert pinned.config.max_inflight == 2  # operator pin holds
        finally:
            pinned.shutdown() if hasattr(pinned, "shutdown") else None
    finally:
        proc.shutdown() if hasattr(proc, "shutdown") else None


# ------------------------------------------------------- tree hashing (r9)


def test_profile_tree_hash_buckets_round_trip(tmp_path):
    """r9: tree_hash_buckets persist, validate, and round-trip; a
    malformed/negative bucket list is refused at parse time."""
    p = synthetic_profile()
    p.tree_hash_buckets = (16384, 65536)
    path = profile.save(p, str(tmp_path / "p.json"))
    again = profile.load(path)
    assert again.tree_hash_buckets == (16384, 65536)
    # absent -> None (pre-r9 docs parse)
    doc = json.loads(open(path).read())
    doc.pop("tree_hash_buckets")
    (tmp_path / "legacy.json").write_text(json.dumps(doc))
    assert profile.load(str(tmp_path / "legacy.json")).tree_hash_buckets is None
    # invalid values refuse loudly
    doc["tree_hash_buckets"] = [0]
    (tmp_path / "bad.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError):
        profile.load(str(tmp_path / "bad.json"))
    doc["tree_hash_buckets"] = ["x"]
    (tmp_path / "bad2.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError):
        profile.load(str(tmp_path / "bad2.json"))


def test_plan_tree_hash_warmup_derivation():
    """Planner pass-through: measured buckets clamp to the sane range and
    deduplicate in order; unmeasured profiles get the registry-scale
    default (the jaxhash warmup consumes plan.tree_hash_warmup)."""
    p = synthetic_profile()
    assert planner.plan_from_profile(p).tree_hash_warmup == \
        planner.DEFAULT_TREE_HASH_WARMUP
    p.tree_hash_buckets = (4, 16384, 16384, 1 << 40)
    plan = planner.plan_from_profile(p)
    assert plan.tree_hash_warmup == (
        planner.TREE_HASH_BUCKET_CLAMP[0], 16384,
        planner.TREE_HASH_BUCKET_CLAMP[1],
    )
    # COUNT cap (the BLS MAX_WARMUP_BUCKETS contract): a 60-entry profile
    # must not compile 60 ladders at bring-up
    p.tree_hash_buckets = tuple(64 * 2**i for i in range(10))
    capped = planner.plan_from_profile(p).tree_hash_warmup
    assert len(capped) == planner.MAX_TREE_HASH_WARMUP
    # and the installed plan surfaces it to consumers
    runtime.install_profile(p)
    assert runtime.active_plan().tree_hash_warmup == capped


# ------------------------------------------------- compile cache placement


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_dir_is_placed_from_outside(tmp_path, placed):
    """utils/jaxcfg.py: with JAX_COMPILATION_CACHE_DIR set JAX's own
    setting stands (the module sets no directory); unset, the cache is
    <repo>/.jax_cache. The autotune profile dir follows whichever is in
    force. A fresh process: the variable is read when jax is imported."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "LIGHTHOUSE_TPU_AUTOTUNE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(repo, ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    code = (
        "import jax\n"
        "from lighthouse_tpu.utils import jaxcfg\n"
        "from lighthouse_tpu.autotune.profile import profile_dir\n"
        "jaxcfg.setup_compilation_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jaxcfg.cache_base_dir())\n"
        "print(profile_dir())\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want, os.path.join(want, "autotune")]

