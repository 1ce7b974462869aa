"""BENCHMARK.json against the files the harness finds by name, and against
the limits of the benchmark's contract that can be checked without a run."""

import json
import os
import re

import pytest

from conftest import BENCH_DIR, REPO_ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind, name):
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def test_keys_names_units_and_lengths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["name"] != "setup_s" or m["bound"] == 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_has_its_files_and_they_agree(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        wl = _load("workloads", w["name"])
        assert wl["config"] == w["config"] and w["config"] in configs
        assert wl["chips"] == w["chips"] and wl["traffic"] == w["traffic"]
        assert wl["why"] == w["why"]
        assert os.path.isfile(os.path.join(BENCH_DIR, "drivers",
                                           wl["driver"] + ".py"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    for c in bench["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        cfg = _load("configs", c["name"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_every_per_layer_metric_is_a_file_and_moves_a_reported_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    files = {fn[:-5] for fn in os.listdir(os.path.join(BENCH_DIR,
                                                       "layer_metrics"))}
    assert files == {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        spec = _load("layer_metrics", m["name"])
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] and spec["origin"] == m["source"]
        assert spec.get("cells") == m.get("workloads")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in cells:     # besides setup_s, one end-to-end and one per-layer
        assert any(cell in m.get("workloads", cells)
                   for m in bench["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])



def test_a_cell_is_named_by_an_end_to_end_metric_of_its_own(bench):
    """Besides `setup_s`, which every cell reports, at least one end-to-end
    metric lists the cell under `workloads`: no cell rides on a default."""
    for w in bench["workloads"]:
        assert any(w["name"] in m["workloads"] for m in bench["end_to_end"]
                   if "workloads" in m), w["name"]


def test_the_request_loop_cells_report_their_median_beside_the_tail(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    p50, p95 = e2e["request_p50_ms"], e2e["bls_verify_p95_ms"]
    assert p50["workloads"] == ["block_import_131", "urgent_verify"]
    assert (p50["unit"], p50["better"], p50["source"]) == (
        "ms", "lower", "host_clock")
    assert set(p50["workloads"]) < set(p95["workloads"])   # the tail stays
    # whole percents; the tail's bound is wider than the body's
    for m in (p50, p95):
        assert round(m["bound"] * 100, 9) == round(m["bound"] * 100)
    assert 0.01 <= p50["bound"] <= 0.03 <= p95["bound"] <= 0.05
    assert all(w["chips"] == 1 for w in bench["workloads"])
