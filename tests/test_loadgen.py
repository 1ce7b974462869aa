"""Loadgen: deterministic scenarios, fault injection, and the smoke
entry points (`bn loadtest --smoke`, `scripts/loadgen.py --smoke`)."""

import json
import subprocess
import sys

import pytest

from lighthouse_tpu.loadgen import (
    SCENARIOS,
    DeviceStallError,
    FaultInjector,
    StallingBackend,
    get_scenario,
    run_scenario,
    traffic_schedule,
)


def test_traffic_schedule_deterministic_and_seed_sensitive():
    sc = get_scenario("smoke")
    a = traffic_schedule(sc)
    b = traffic_schedule(sc)
    assert a == b
    assert len(a) == sc.slots
    c = traffic_schedule(get_scenario("smoke", seed=sc.seed + 1))
    assert a != c
    # flood multiplies the shape
    base = traffic_schedule(get_scenario("flood", flood_factor=1.0))
    flood = traffic_schedule(get_scenario("flood", flood_factor=4.0))
    assert sum(t.attestations + t.stale_attestations for t in flood) > (
        3 * sum(t.attestations + t.stale_attestations for t in base)
    )


def test_get_scenario_overrides_and_unknown():
    sc = get_scenario("steady", slots=3, seed=7)
    assert sc.slots == 3 and sc.seed == 7
    assert SCENARIOS["steady"].slots != 3      # base untouched
    with pytest.raises(KeyError):
        get_scenario("nope")


def test_stalling_backend_and_injector():
    dev = StallingBackend(wait_secs=0.01)
    assert dev.verify_signature_sets([None], [1]) is True
    dev.stall()
    with pytest.raises(DeviceStallError):
        dev.verify_signature_sets([None], [1])
    handle = dev.verify_signature_sets_async([None], [1])
    with pytest.raises(DeviceStallError):
        handle.result()
    dev.release()
    assert dev.verify_signature_sets([None], [1]) is True
    assert dev.stall_hits == 2

    fired = []
    inj = FaultInjector()
    inj.at(2, lambda: fired.append("a")).at(4, lambda: fired.append("b"))
    assert inj.on_slot(0) == 0
    assert inj.on_slot(3) == 1 and fired == ["a"]
    assert inj.on_slot(3) == 0                 # each action fires once
    # registering after some actions fired must not remap what already ran
    inj.at(1, lambda: fired.append("late"))
    assert inj.on_slot(3) == 1 and fired == ["a", "late"]
    assert inj.on_slot(10) == 1 and fired == ["a", "late", "b"]


def test_smoke_scenario_exercises_every_qos_path():
    report = run_scenario(get_scenario("smoke"))
    # identical rerun: the report is a pure function of (scenario, seed)
    report2 = run_scenario(get_scenario("smoke"))
    for key in ("published", "processed", "dropped", "expired",
                "verified_sets", "batches", "breaker_transitions"):
        assert report[key] == report2[key], key

    pub, proc = report["published"], report["processed"]
    # conservation: every attestation is processed, shed, or expired
    lost = report["dropped"].get("gossip_attestation", 0)
    expired = report["expired"].get("gossip_attestation", 0)
    assert (
        pub["attestations"] + pub["stale_attestations"]
        == proc["gossip_attestation"] + lost + expired
    )
    assert lost > 0, "smoke flood should shed oldest-first"
    assert expired > 0, "stale replays should expire at pop"
    assert proc["gossip_block"] == pub["blocks"]
    assert report["blocks_processed_in_slot"]
    # the device stall drove the full breaker cycle
    tr = report["breaker_transitions"]
    assert tr[0] == "closed" and "open" in tr and "half_open" in tr
    assert tr[-1] == "closed"
    assert report["batches"]["device_stalls"] > 0
    assert report["batches"]["host"] > 0       # host served during the stall
    # every shed/expired item resolved its gossip bookkeeping callback
    assert report["shed_callbacks"] == lost + expired
    json.dumps(report)                         # machine-readable end to end


def test_steady_scenario_sheds_nothing():
    report = run_scenario(get_scenario("steady", slots=4))
    assert report["dropped"] == {} and report["expired"] == {}
    assert report["breaker_transitions"] == ["closed"]
    assert report["batches"]["host"] == 0      # healthy device took it all
    # a healthy run's SLO block: perfect deadline ratio, no incidents
    assert report["deadline_hit_ratio"] == 1.0
    assert report["slo"]["incidents"] == []
    assert report["slo"]["windows"]["slot_5"]["burn_rate"] == 0.0


def test_device_stall_slo_degradation_and_incident(tmp_path):
    """The acceptance surface: device_stall at smoke scale shows the
    per-slot deadline-hit ratio DEGRADING through the stall window and
    RECOVERING after, and the breaker/burn triggers leave >=1 schema-valid
    incident dump in <datadir>/incidents that `bn debug-bundle` packages."""
    import tarfile

    from lighthouse_tpu.loadgen import smoke_variant
    from lighthouse_tpu.observability.debug_bundle import build_bundle
    from lighthouse_tpu.observability.flight_recorder import validate_incident

    sc = smoke_variant(get_scenario("device_stall"))
    datadir = tmp_path / "dd"
    report = run_scenario(sc, datadir=str(datadir))
    stall_start, stall_end = sc.stall_slots
    by_slot = {s["slot"]: s for s in report["slo"]["per_slot"]}
    # healthy before the stall, degraded inside it, recovered after
    for slot in range(stall_start):
        assert by_slot[slot]["deadline_hit_ratio"] == 1.0, slot
    stall_ratios = [
        by_slot[s]["deadline_hit_ratio"] for s in range(stall_start, stall_end)
    ]
    assert min(stall_ratios) < 0.5, stall_ratios
    assert by_slot[sc.slots - 1]["deadline_hit_ratio"] == 1.0
    assert report["deadline_hit_ratio"] < 1.0
    # route share flipped to the host fallback during the stall
    assert by_slot[stall_start]["routes"].get("host", 0) > 0
    assert by_slot[0]["routes"] == {"device": by_slot[0]["routes"]["device"]}
    # deterministic rerun: the SLO accounting is a function of (scenario,
    # seed) like every other count
    report2 = run_scenario(sc, datadir=str(tmp_path / "dd2"))
    assert report2["slo"]["per_slot"] == report["slo"]["per_slot"]
    assert report2["slo"]["incidents"] == report["slo"]["incidents"]
    # >=1 incident dump landed and validates
    incidents = report["slo"]["incidents"]
    assert incidents, "a device stall must leave a durable incident trail"
    assert any("breaker_open" in n for n in incidents)
    for name in incidents:
        with open(datadir / "incidents" / name) as f:
            doc = json.load(f)
        assert validate_incident(doc) == []
    # the breaker-open dump carries THIS run's SLO windows + the event ring
    (breaker_dump,) = [n for n in incidents if "breaker_open" in n]
    with open(datadir / "incidents" / breaker_dump) as f:
        doc = json.load(f)
    assert doc["slo"]["windows"]["slot_5"]["slots"] >= 1
    assert any(e["kind"] == "breaker_transition" for e in doc["events"])
    # ...and `bn debug-bundle --datadir` packages every dump
    out = tmp_path / "bundle.tar.gz"
    manifest = build_bundle(str(out), datadir=str(datadir))
    assert sorted(manifest["incidents"]) == sorted(incidents)
    with tarfile.open(out) as tar:
        for name in incidents:
            assert f"incidents/{name}" in tar.getnames()


def _run_cli(args, timeout=300):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, cwd="/root/repo",
    )


def test_bn_loadtest_smoke_cli(tmp_path):
    out = tmp_path / "report.json"
    r = _run_cli(["-m", "lighthouse_tpu", "bn", "loadtest", "--smoke",
                  "--quiet", "--out", str(out)])
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["scenario"] == "smoke"
    assert summary["blocks_processed_in_slot"] is True
    assert summary["breaker_transitions"][-1] == "closed"
    # the one-line summary carries the SLO headline (smoke has a stall +
    # flood, so the ratio is degraded and the stall left an incident)
    assert summary["slo"]["deadline_hit_ratio"] < 1.0
    assert summary["slo"]["incidents"]
    report = json.loads(out.read_text())
    assert report["qos_totals"]["shed"] > 0
    assert report["slo"]["per_slot"]
    assert report["elapsed_secs"] < 30


def test_bn_loadtest_crash_restart_smoke_cli(tmp_path):
    """The acceptance path: `bn loadtest --scenario crash_restart --smoke`
    crashes the node mid-load via an injected storage fault, restarts it
    from the same datadir, resumes from the persisted head, and the
    extended conservation invariant holds."""
    out = tmp_path / "report.json"
    r = _run_cli(["-m", "lighthouse_tpu", "bn", "loadtest",
                  "--scenario", "crash_restart", "--smoke", "--quiet",
                  "--out", str(out), "--datadir", str(tmp_path / "dd")])
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["scenario"] == "crash_restart"
    assert summary["crash"]["resumed_from_persisted_head"] is True
    assert summary["conservation"]["ok"] is True
    assert summary["conservation"]["lost_to_crash"] > 0
    report = json.loads(out.read_text())
    assert "torn write" in report["crash"]["fault"]
    assert report["crash"]["recovered_head_slot"] == (
        report["crash"]["slot"] - 1
    )
    # the deadline-hit ratio rides next to the conservation invariant
    assert "deadline_hit_ratio" in report["conservation"]
    assert report["slo"]["windows"]["epoch_32"]["slots"] > 0
    assert report["elapsed_secs"] < 30


def test_smoke_modifier_shrinks_named_scenarios():
    """--smoke + --scenario X runs X at smoke scale: same shape (faults,
    mix), clamped size, faults still inside the run."""
    from lighthouse_tpu.loadgen import smoke_variant

    big = get_scenario("steady")
    small = smoke_variant(big)
    assert small.n_validators <= 4096 and small.slots <= 8
    assert small.name == "steady" and small.faults == big.faults
    crash = smoke_variant(get_scenario("crash_restart", slots=3))
    assert crash.crash_slot is not None
    assert 1 <= crash.crash_slot <= crash.slots - 2


def test_scripts_loadgen_smoke(tmp_path):
    out = tmp_path / "report.json"
    r = _run_cli(["scripts/loadgen.py", "--smoke", "--quiet",
                  "--out", str(out)])
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["report"] == str(out)
    report = json.loads(out.read_text())
    assert report["scenario"] == "smoke"
    assert report["qos_totals"]["expired"] > 0


# --------------------------------------------------------------- mesh (r8)


def test_mesh_backend_collective_cost_model():
    """Per-chip sharding: the same batch costs ~1/D the device time on a
    D-chip mesh, and one stalled chip stalls the WHOLE sharded batch (the
    collective semantics) while the urgent lane — pinned to chip 0 —
    keeps serving through a chip-1 stall."""
    from lighthouse_tpu.loadgen.meshsim import MeshShardedBackend

    one = MeshShardedBackend(1, base_ms=0.0, per_set_ms=0.05)
    eight = MeshShardedBackend(8, base_ms=0.0, per_set_ms=0.05)
    import time as _t

    def best_of_three(backend):
        # the quickest of three: beside five other xdist workers a thread
        # can wait longer for a core than the 3.2 ms being compared
        times = []
        for _ in range(3):
            t0 = _t.perf_counter()
            assert backend.verify_signature_sets([None] * 64, [1] * 64) is True
            times.append(_t.perf_counter() - t0)
        return min(times)

    assert best_of_three(eight) < best_of_three(one)  # 8*0.05ms + overhead vs 64*0.05ms
    # occupancy ledger: every chip busy, balanced
    occ = eight.occupancy()
    assert occ["devices"] == 8 and len(occ["chip_busy_secs"]) == 8
    assert occ["busy_balance"] == 1.0

    # collective stall: chip 1 wedged -> sharded batches raise, the
    # urgent lane (chip 0) still serves
    eight.stall_chip(1)
    assert eight.stalled and eight.stalled_chips == (1,)
    with pytest.raises(DeviceStallError):
        eight.verify_signature_sets([None] * 8, [1] * 8)
    assert eight.verify_signature_sets_urgent([None], [1]) is True
    # chip 0 wedged too -> urgent stalls as well
    eight.stall_chip(0)
    with pytest.raises(DeviceStallError):
        eight.verify_signature_sets_urgent([None], [1])
    eight.release_chip(None)
    assert eight.verify_signature_sets([None] * 8, [1] * 8) is True
    assert eight.occupancy()["stall_hits"] == 2


def test_mesh_stall_scenario_breaker_mediated_degradation(tmp_path):
    """The mesh_stall acceptance, in process: one chip's shard wedges ->
    the breaker opens (incident dumped), the deadline-hit ratio dips and
    RECOVERS after the heal, the urgent lane never stalls (chip 1 is the
    wedged one), and the pipeline window never wedges (the run
    completes + conservation holds)."""
    from lighthouse_tpu.loadgen.driver import drive

    out = tmp_path / "mesh_stall.json"
    rc = drive(scenario="mesh_stall", smoke=True, quiet=True,
               out=str(out), datadir=str(tmp_path / "dd"))
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["mesh"]["devices"] == 8          # the virtual CPU mesh
    assert report["mesh"]["stall_hits"] > 0
    assert report["mesh"]["urgent_stalled"] == 0   # chip 0 never wedged
    assert report["mesh"]["urgent_served"] == report["published"]["blocks"]
    ratios = [s["deadline_hit_ratio"] for s in report["slo"]["per_slot"]
              if s["deadline_hit_ratio"] is not None]
    assert min(ratios) < 1.0                       # the dip
    assert ratios[-1] > min(ratios)                # the recovery
    assert report["slo"]["incidents"]
    assert "open" in report["breaker_transitions"]
    assert report["breaker_transitions"][-1] == "closed"
    # per-chip stall attribution reached the flight-recorder ring
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    kinds = [e["kind"] for e in RECORDER.events(256)]
    assert "mesh_chip_stall" in kinds and "mesh_chip_release" in kinds


def test_mesh_sweep_scales_and_writes_matrix_rows(tmp_path):
    """The --mesh-devices sweep in process: flood at 1 and 8 chips, the
    8-chip point must out-serve the 1-chip point, and both land as
    source:loadtest BENCH_MATRIX rows the perf layer parses as fresh."""
    import io

    from lighthouse_tpu.loadgen.driver import drive
    from lighthouse_tpu.observability import perf

    stdout = io.StringIO()
    rc = drive(scenario="flood", smoke=True, quiet=True,
               mesh_devices=[1, 8], out=str(tmp_path / "sweep.json"),
               bench_root=str(tmp_path), stdout=stdout)
    assert rc == 0
    sweep = json.loads(stdout.getvalue().strip().splitlines()[-1])
    r1 = sweep["mesh_sweep"]["1"]["sets_per_sec"]
    r8 = sweep["mesh_sweep"]["8"]["sets_per_sec"]
    assert r8 > r1
    assert sweep["scaling"]["speedup"] > 1.0
    rows = perf.load_matrix(root=str(tmp_path),
                            name="BENCH_MATRIX_SMOKE.json")
    assert rows["loadtest_flood_mesh1"]["source"] == "loadtest"
    assert rows["loadtest_flood_mesh8"]["rate"] == r8
    assert rows["loadtest_flood_mesh8"]["n_devices"] == 8
    # the full sweep report carries both points' complete reports
    full = json.loads((tmp_path / "sweep.json").read_text())
    assert set(full["points"]) == {"1", "8"}


def test_mesh_sweep_fails_when_scaling_absent(monkeypatch, tmp_path):
    """A sweep whose biggest mesh does NOT out-serve the smallest exits
    nonzero — the near-linear-scaling assertion is the acceptance, not a
    log line."""
    import io

    from lighthouse_tpu.loadgen import driver as drv

    def fake_run_scenario(sc, out_path=None, datadir=None, log_fn=None):
        return {
            "scenario": sc.name, "faults": [],
            "mesh": {"devices": sc.mesh_devices, "sets_per_sec": 100.0,
                     "verify_p50_ms": 1.0, "device_batches": 1,
                     "chip_busy_secs": [], "busy_balance": None,
                     "stall_hits": 0, "stalled_chips": [],
                     "urgent_served": 0, "urgent_stalled": 0},
            "slo": {"deadline_hit_ratio": 1.0, "incidents": [],
                    "per_slot": [], "windows": {}},
        }

    monkeypatch.setattr(
        "lighthouse_tpu.loadgen.runner.run_scenario", fake_run_scenario
    )
    stderr = io.StringIO()
    rc = drv.drive(scenario="flood", smoke=True, quiet=True,
                   mesh_devices=[1, 8], out=str(tmp_path / "s.json"),
                   bench_root=str(tmp_path), stderr=stderr)
    assert rc == 1
    assert "did not scale" in stderr.getvalue()


def test_bn_loadtest_mesh_sweep_cli(tmp_path):
    """The acceptance command end to end: under the forced-host-device
    harness, `bn loadtest --scenario flood --smoke --mesh-devices 1,8`
    exits 0, reports sets/s for both points with the 8-device point
    strictly higher, and writes fresh BENCH_MATRIX rows."""
    out = tmp_path / "sweep.json"
    r = _run_cli(["-m", "lighthouse_tpu", "bn", "loadtest",
                  "--scenario", "flood", "--smoke", "--quiet",
                  "--mesh-devices", "1,8", "--out", str(out),
                  "--bench-root", str(tmp_path)])
    assert r.returncode == 0, r.stderr
    sweep = json.loads(r.stdout.strip().splitlines()[-1])
    assert sweep["mesh_sweep"]["8"]["sets_per_sec"] > (
        sweep["mesh_sweep"]["1"]["sets_per_sec"]
    )
    assert (tmp_path / "BENCH_MATRIX_SMOKE.json").exists()
