"""Bench trend harness (observability/perf.py + scripts/perf_trend.py +
`bn perf report`): round parsing over a five-round BENCH_r* series built
in a tmp root beside the checked-in BENCH_MATRIX / MULTICHIP_r* artifacts, carried-forward rendering, regression detection,
the roofline helper, and the CLI exit codes. Host-only — no jax, no
device."""

import json
import os
import subprocess
import sys

import pytest

from lighthouse_tpu.observability import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------- the five-round record series


@pytest.fixture()
def record_root(tmp_path):
    """The series the repo carried as BENCH_r01–r05 until PR 22 deleted
    the records: one fresh headline (21.11 sets/s), then a run with no
    parsed line and three zero-valued UNAVAILABLE records — built here
    with the file's own writers beside the BENCH_MATRIX.json and
    MULTICHIP_r*.json that are still checked in."""
    import glob
    import shutil

    root = str(tmp_path)
    _write_round(root, 1, 21.11)
    with open(os.path.join(root, "BENCH_r02.json"), "w") as f:
        json.dump({"n": 2, "rc": 1, "parsed": None}, f)
    for n in (3, 4, 5):
        _write_round(root, n, 0.0,
                     metric="BLS signature-sets verified/sec "
                            "[TPU UNAVAILABLE at bench time]")
    for path in [os.path.join(REPO, "BENCH_MATRIX.json")] + glob.glob(
            os.path.join(REPO, "MULTICHIP_r*.json")):
        shutil.copy(path, root)
    return root


def test_record_rounds_parse_with_carry_forward(record_root):
    """r01 is the only fresh headline; r02–r05 (missing parse / outage
    records) carry r01's value forward and are flagged as such — a stale
    value never reads fresh."""
    rounds = {r["round"]: r for r in perf.load_bench_rounds(record_root)}
    assert rounds[1]["fresh"] and rounds[1]["value"] == 21.11
    for n in (2, 3, 4, 5):
        r = rounds[n]
        assert not r["fresh"]
        assert r["carried"] and r["carried_from"] == "BENCH_r01.json"
        assert r["value"] == 21.11  # inherited, flagged


def test_record_report_verdict_and_matrix_flags(record_root):
    rc, report = perf.check(record_root)
    assert rc == 0 and report["ok"] and not report["regressions"]
    # the estimate caveat heads the report (vs_est_* is not a measurement)
    assert "ESTIMATED" in report["caveat"]
    # config4 was skipped on time budget in BENCH_MATRIX.json — it must
    # surface as skipped, distinct from a measured config
    assert report["matrix"]["config4"] == {"skipped": "time budget"}
    assert report["matrix"]["config5"]["rate"] == 99.85
    assert report["matrix"]["config5"]["vs_est"] == 0.143
    # multichip rounds parse; latest fresh round is ok -> no regression
    mc = report["multichip"]["rounds"]
    assert [r["ok"] for r in mc] == [False, True, True, False, True]


def test_render_report_marks_carried_and_skipped(record_root):
    _rc, report = perf.check(record_root)
    text = perf.render_report(report)
    assert "ESTIMATED" in text.splitlines()[1]  # caveat in the header
    assert "CARRIED FORWARD from BENCH_r01.json" in text
    assert "config4: SKIPPED" in text
    assert "verdict: OK" in text


def test_checked_in_tree_renders_an_empty_series():
    """The repo itself holds no BENCH_r*.json any more: the report over
    the checkout renders an empty headline series without error."""
    assert perf.load_bench_rounds(REPO) == []
    rc, report = perf.check(REPO)
    assert rc == 0 and report["ok"]
    assert report["headline"]["rounds"] == []
    assert "verdict: OK" in perf.render_report(report)


def test_smoke_matrix_carries_program_analytics_schema():
    """BENCH_MATRIX_SMOKE.json (the gitignored CPU dry-run artifact of
    `LIGHTHOUSE_BENCH_SMOKE=1 python bench.py`) smoke-validates the
    artifact schema: compiled-bucket flops/bytes/HBM from
    cost_analysis()/memory_analysis() under "xla_programs" plus the
    attributed per-stage timings under "stage_attribution"."""
    path = os.path.join(REPO, "BENCH_MATRIX_SMOKE.json")
    if not os.path.exists(path):
        pytest.skip("no smoke bench artifact on this checkout "
                    "(run LIGHTHOUSE_BENCH_SMOKE=1 python bench.py)")
    with open(path) as f:
        matrix = json.load(f)
    programs = matrix["xla_programs"]
    assert programs, "smoke bench recorded no compiled programs"
    bucket, stages = next(iter(programs.items()))
    assert "x" in bucket  # "<n_sets>x<n_pks>"
    stage, stats = next(iter(stages.items()))
    assert stage in ("prepare", "h2c", "pairs", "pairing")
    for key in ("flops", "bytes_accessed", "argument_bytes", "output_bytes"):
        assert key in stats, f"{key} missing from xla_programs[{bucket}][{stage}]"
    assert "stage_attribution" in matrix


# ------------------------------------------------------ synthetic series


def _write_round(root, n, value, *, skipped=False, carried_value=None,
                 config1_p50=None, pipeline=None,
                 metric="BLS signature-sets verified/sec (synthetic)"):
    parsed = {
        "metric": metric,
        "unit": "sets/s",
        "value": value,
        "vs_baseline": round(value / 700.0, 3),
    }
    if config1_p50 is not None:
        parsed["config1_p50_ms"] = config1_p50
    if pipeline is not None:
        parsed["pipeline"] = pipeline
    if skipped:
        parsed["skipped"] = True
        parsed["value"] = carried_value or 0.0
        parsed["vs_baseline"] = round((carried_value or 0.0) / 700.0, 3)
        parsed["note"] = "no measurement this run; value carried forward"
    with open(os.path.join(root, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "parsed": parsed}, f)


def test_regression_detected_and_exits_nonzero(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0)
    _write_round(root, 2, 80.0)  # -20% fresh-to-fresh
    rc, report = perf.check(root)
    assert rc == 1 and not report["ok"]
    (reg,) = report["regressions"]
    assert reg["config"] == "headline" and reg["delta_pct"] == -20.0
    # the script gate (the CI entry point) exits nonzero on the same series
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_trend.py"),
         "--check", "--root", root],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout
    # without --check the report prints but exits 0
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_trend.py"),
         "--root", root],
        capture_output=True, text=True, timeout=60,
    )
    assert r2.returncode == 0


def test_carried_forward_rounds_never_trigger_or_mask_regression(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0)
    # r02: outage, artifact carries 100.0 forward — must not read fresh
    _write_round(root, 2, 0.0, skipped=True, carried_value=100.0)
    _write_round(root, 3, 95.0)  # -5% vs r01: inside the 10% threshold
    rc, report = perf.check(root)
    assert rc == 0, report["regressions"]
    rounds = {r["round"]: r for r in report["headline"]["rounds"]}
    assert rounds[2]["carried"] and not rounds[2]["fresh"]
    # an artifact-carried round keeps its vs ratio and names a round
    # source (the note has no filename -> the latest fresh round)
    assert rounds[2]["vs_est"] == round(100.0 / 700.0, 3)
    assert rounds[2]["carried_from"] == "BENCH_r01.json"
    # the only delta is fresh r01 -> fresh r03
    (delta,) = report["headline"]["deltas"]
    assert delta["from"] == "BENCH_r01.json" and delta["to"] == "BENCH_r03.json"
    assert delta["delta_pct"] == -5.0
    # tighter threshold: the same drop becomes a regression
    rc2, _ = perf.check(root, threshold=0.04)
    assert rc2 == 1


def test_config1_p50_latency_regression_gates(tmp_path):
    """The urgent-path latency series: a fresh-to-fresh config1 p50
    INCREASE past the threshold fails the gate exactly like a headline
    throughput drop — and a healthy headline cannot mask it."""
    root = str(tmp_path)
    _write_round(root, 1, 100.0, config1_p50=90.0,
                 pipeline={"depth": 4, "donated_inputs": True})
    _write_round(root, 2, 110.0, config1_p50=150.0)  # +67% latency
    rc, report = perf.check(root)
    assert rc == 1 and not report["ok"]
    (reg,) = report["regressions"]
    assert reg["config"] == "config1_p50"
    assert reg["prev"] == 90.0 and reg["cur"] == 150.0
    text = perf.render_report(report)
    assert "config1 urgent-path p50" in text
    assert "REGRESSION" in text
    # the CI entry point exits nonzero on the same series
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_trend.py"),
         "--check", "--root", root],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1, r.stdout + r.stderr


def test_config1_p50_improvement_and_missing_rounds_pass(tmp_path):
    """Latency improving (or rounds without the series — every pre-r8
    artifact) must not trip the gate; a skipped round's p50 never enters
    the fresh series."""
    root = str(tmp_path)
    _write_round(root, 1, 100.0, config1_p50=529.0)
    _write_round(root, 2, 0.0, skipped=True, carried_value=100.0,
                 config1_p50=529.0)          # outage: must not read fresh
    _write_round(root, 3, 101.0, config1_p50=95.0)   # big improvement
    _write_round(root, 4, 102.0)                     # series absent: ok
    rc, report = perf.check(root)
    assert rc == 0, report["regressions"]
    lat_rounds = report["config1_p50"]["rounds"]
    assert [r["round"] for r in lat_rounds] == [1, 3]
    (delta,) = report["config1_p50"]["deltas"]
    assert delta["delta_pct"] < 0  # improvement, negative latency delta


def test_multichip_regression_flagged(tmp_path):
    root = str(tmp_path)
    _write_round(root, 1, 100.0)
    for n, ok in ((1, True), (2, False)):
        with open(os.path.join(root, f"MULTICHIP_r{n:02d}.json"), "w") as f:
            json.dump({"n_devices": 8, "ok": ok, "skipped": False}, f)
    rc, report = perf.check(root)
    assert rc == 1
    assert any(r["config"] == "multichip" for r in report["regressions"])


def test_bn_perf_report_cli_runs_host_only(record_root):
    """Acceptance: `bn perf report` on CPU with no device, over the
    five-round record series — per-config trend, regression verdict, r05
    flagged carried-forward."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lighthouse_tpu", "bn", "perf", "report",
         "--check", "--root", record_root],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "r05" in r.stdout and "CARRIED FORWARD" in r.stdout
    assert "verdict: OK" in r.stdout
    assert "ESTIMATED" in r.stdout


# ------------------------------------------------------------- roofline


def test_roofline_against_published_peaks():
    stats = {"flops": 1e9, "bytes_accessed": 4e8}
    rl = perf.roofline(stats, secs=0.01, device_kind="TPU v5 lite")
    assert rl["achieved_gflops_per_sec"] == 100.0
    assert 0 < rl["flops_utilization"] < 1
    assert rl["bound"] in ("compute", "memory")
    assert "not measurements" in rl["peak_note"]
    assert perf.roofline(stats, secs=0.0, device_kind="TPU v5 lite") is None


@pytest.mark.parametrize("kind", ["weird-accelerator", "cpu",
                                  "TPU v5 lite0", None])
def test_unknown_device_kind_has_no_roofline(kind, capsys):
    """A device kind that is not in the table (the exact string JAX
    reports — no prefix match, no `cpu` row, no environment override)
    gets no roofline row and ONE logged error, never a borrowed peak."""
    stats = {"flops": 1e9, "bytes_accessed": 4e8}
    perf._unknown_kinds_logged.discard(kind)
    assert perf.roofline(stats, secs=0.01, device_kind=kind) is None
    assert perf.roofline(stats, secs=0.01, device_kind=kind) is None
    logged = capsys.readouterr()
    assert (logged.out + logged.err).count("no published peaks") == 1


def test_pipeline_snapshot_surfaces_perf_trend(record_root, monkeypatch):
    from lighthouse_tpu.observability import pipeline

    monkeypatch.setattr(perf, "default_root", lambda: record_root)
    snap = pipeline.snapshot()
    trend = snap["perf_trend"]
    assert trend["ok"] is True and trend["regressions"] == 0
    assert "ESTIMATED" in trend["caveat"]
    latest = trend["headline_latest"]
    assert latest["source"] == "BENCH_r05.json"
    assert latest["fresh"] is False
    assert latest["carried_from"] == "BENCH_r01.json"


# ----------------------------------------------------- loadtest rows (r8)


def test_write_loadtest_rows_merge_and_parse(tmp_path):
    """write_loadtest_rows read-merge-writes the BENCH_MATRIX schema:
    bench.py's configs survive, loadtest_* rows parse like configs with
    their source tag (fresh by construction), and non-loadtest keys are
    refused."""
    import json

    from lighthouse_tpu.observability import perf

    (tmp_path / "BENCH_MATRIX_SMOKE.json").write_text(json.dumps({
        "config5_firehose": {"sets_per_sec": 99.85, "vs_est_blst": 0.143},
        "elapsed_secs": 1.0,
    }))
    path = perf.write_loadtest_rows(
        {"loadtest_flood_mesh8": {
            "sets_per_sec": 1234.5, "p50_ms": 2.0, "n_devices": 8,
            "measured_unix": 1.0,
        }},
        smoke=True, root=str(tmp_path),
    )
    doc = json.loads(open(path).read())
    assert doc["config5_firehose"]["sets_per_sec"] == 99.85  # preserved
    assert doc["loadtest_flood_mesh8"]["source"] == "loadtest"

    parsed = perf.load_matrix(root=str(tmp_path),
                              name="BENCH_MATRIX_SMOKE.json")
    assert parsed["config5"]["rate"] == 99.85
    row = parsed["loadtest_flood_mesh8"]
    assert row["rate"] == 1234.5 and row["rate_unit"] == "sets_per_sec"
    assert row["source"] == "loadtest" and row["n_devices"] == 8

    with pytest.raises(ValueError):
        perf.write_loadtest_rows({"config9": {}}, smoke=True,
                                 root=str(tmp_path))


def test_render_report_marks_loadtest_rows_fresh(tmp_path):
    """Rendered trend output labels loadtest rows as fresh soak snapshots
    (never skipped/carried), and the check() gate stays clean with them
    present."""
    import json

    from lighthouse_tpu.observability import perf

    (tmp_path / "BENCH_MATRIX.json").write_text(json.dumps({
        "loadtest_flood_mesh8": {
            "sets_per_sec": 500.0, "p50_ms": 3.1, "n_devices": 8,
            "source": "loadtest", "measured_unix": 2.0,
        },
    }))
    rc, report = perf.check(root=str(tmp_path))
    assert rc == 0
    text = perf.render_report(report)
    assert "loadtest_flood_mesh8" in text
    assert "source=loadtest (fresh soak snapshot, 8 device(s))" in text
    assert "SKIPPED" not in text.split("loadtest_flood_mesh8")[1].split("\n")[0]


# -------------------------------------------------- state-root series (r9)


def _write_state_root(root, p50, smoke=False, backend="host",
                      validators=16384):
    from lighthouse_tpu.observability import perf

    return perf.write_loadtest_rows(
        {"state_root": {
            "p50_ms": p50, "roots_per_sec": round(1000.0 / p50, 2),
            "source": "bench_state_root", "measured_unix": float(p50),
            "hash_backend": backend, "validators": validators,
        }},
        smoke=smoke, root=root,
    )


def test_state_root_rows_accumulate_history(tmp_path):
    """bench_state_root rows merge like loadtest rows and accumulate a
    bounded fresh-measurement history; epoch_transition keys are accepted
    too and both parse through load_matrix."""
    root = str(tmp_path)
    _write_state_root(root, 100.0)
    _write_state_root(root, 98.0)
    from lighthouse_tpu.observability import perf

    perf.write_loadtest_rows(
        {"epoch_transition": {"p50_ms": 50.0, "epochs_per_sec": 20.0,
                              "source": "bench_state_root",
                              "measured_unix": 3.0}},
        smoke=False, root=root,
    )
    parsed = perf.load_matrix(root=root)
    assert parsed["state_root"]["p50_ms"] == 98.0
    assert [e["p50_ms"] for e in parsed["state_root"]["history"]] == [
        100.0, 98.0,
    ]
    assert parsed["epoch_transition"]["rate"] == 20.0
    assert parsed["epoch_transition"]["rate_unit"] == "epochs_per_sec"
    # history is bounded
    for i in range(perf.MAX_ROW_HISTORY + 4):
        _write_state_root(root, 98.0 + i * 0.01)
    parsed = perf.load_matrix(root=root)
    assert len(parsed["state_root"]["history"]) == perf.MAX_ROW_HISTORY


def test_state_root_p50_regression_gates(tmp_path):
    """A fresh-to-fresh state-root p50 INCREASE past the threshold fails
    the gate exactly like config1_p50 (lower is better)."""
    root = str(tmp_path)
    _write_state_root(root, 100.0)
    _write_state_root(root, 125.0)  # +25% latency
    from lighthouse_tpu.observability import perf

    rc, report = perf.check(root)
    assert rc == 1
    reg = [r for r in report["regressions"]
           if r["config"] == "state_root_p50"]
    assert reg and reg[0]["delta_pct"] == 25.0
    text = perf.render_report(report)
    assert "state_root p50" in text
    # the script CLI rides the same verdict
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_trend.py"),
         "--root", root, "--check"],
        capture_output=True, text=True,
    )
    assert r.returncode == 1


def test_state_root_p50_improvement_and_carried_pass(tmp_path):
    """Improvements pass; an entry marked fresh=false (a hand-carried
    value) is EXCLUDED from deltas and renders as carried — it can
    neither cause nor mask a regression."""
    import json

    root = str(tmp_path)
    _write_state_root(root, 100.0)
    # inject a non-fresh entry between two fresh ones
    path = os.path.join(root, "BENCH_MATRIX.json")
    doc = json.loads(open(path).read())
    doc["state_root"]["history"].append(
        {"measured_unix": 2.0, "p50_ms": 500.0, "fresh": False}
    )
    with open(path, "w") as f:
        json.dump(doc, f)
    _write_state_root(root, 92.0)  # fresh improvement vs 100.0
    from lighthouse_tpu.observability import perf

    rc, report = perf.check(root)
    assert rc == 0, report["regressions"]
    deltas = report["state_root_p50"]["deltas"]
    assert len(deltas) == 1 and deltas[0]["delta_pct"] == -8.0
    text = perf.render_report(report)
    assert "CARRIED FORWARD" in text


def test_state_root_p50_config_change_not_a_regression(tmp_path):
    """A host->device (or resized) re-measurement is a CONFIGURATION
    change: the pair must not gate, and the next same-config pair must
    compare — so a backend flip can neither fail CI nor mask a real
    same-config regression."""
    from lighthouse_tpu.observability import perf

    root = str(tmp_path)
    _write_state_root(root, 20.0, backend="device")
    _write_state_root(root, 100.0, backend="host")   # +400%: config change
    rc, report = perf.check(root)
    assert rc == 0, report["regressions"]
    assert report["state_root_p50"]["deltas"] == []
    # same-config regression after the flip still gates
    _write_state_root(root, 125.0, backend="host")   # +25% host-to-host
    rc, report = perf.check(root)
    assert rc == 1
    assert [r["config"] for r in report["regressions"]] == ["state_root_p50"]


def test_state_root_p50_interleaved_config_cannot_mask(tmp_path):
    """An interleaved config-change entry must not break the same-config
    chain: host 100 -> device 20 -> host 125 still gates the host-to-host
    +25% (entries compare against the most recent SAME-config entry, not
    the adjacent one)."""
    from lighthouse_tpu.observability import perf

    root = str(tmp_path)
    _write_state_root(root, 100.0, backend="host")
    _write_state_root(root, 20.0, backend="device")
    _write_state_root(root, 125.0, backend="host")
    rc, report = perf.check(root)
    assert rc == 1, report["state_root_p50"]
    reg = [r for r in report["regressions"]
           if r["config"] == "state_root_p50"]
    assert reg and reg[0]["delta_pct"] == 25.0
