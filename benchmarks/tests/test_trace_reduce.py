"""trace_reduce.py on a synthetic trace: no profiler needed."""

import pytest
import trace_reduce as tr

MS = 1e6   # ns


def _trace():
    ops = [("fusion.1", 10 * MS, 20 * MS),       # 10..30
           ("fusion.2", 25 * MS, 15 * MS),       # 25..40 overlaps
           ("copy.3", 60 * MS, 10 * MS),         # 60..70
           ("fusion.1", 90 * MS, 30 * MS)]       # 90..120, cut at 100
    modules = [("jit_ladder", 10 * MS, 30 * MS), ("jit_ladder", 60 * MS, 10 * MS)]
    host = [("bench:trace_window", 0.0, 100 * MS),
            ("bench:marshal_dispatch", 40 * MS, 19 * MS),
            ("bench:continuation", 70 * MS, 5 * MS),
            ("SomethingElse", 75 * MS, 15 * MS)]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1", "lines": []},
    ]}


def test_busy_is_the_union_inside_the_window():
    out = tr.reduce(_trace(), n_devices=1)
    assert out["window_source"] == "host_scope"
    assert out["window_s"] == pytest.approx(0.100)
    # 10..40, 60..70, 90..100
    assert out["busy_s"] == pytest.approx(0.050)
    assert out["values"]["device_idle_share"] == pytest.approx(50.0)


def test_breakdown_names_ops_and_what_the_host_did_in_the_gaps():
    out = tr.reduce(_trace(), n_devices=1)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["program jit_ladder"] == pytest.approx(0.040)
    assert ops["fusion.1"] == pytest.approx(0.030)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 0..10 nothing; 40..60 under marshal; 70..90: SomethingElse covers most
    assert gaps["host in bench:marshal_dispatch"] == pytest.approx(0.020)
    assert gaps["host in SomethingElse"] == pytest.approx(0.020)
    assert gaps["(no host scope)"] == pytest.approx(0.010)
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_a_device_line_that_stops_early_ends_the_window():
    """The profiler wrote device events up to 70 ms of a 100 ms scope: the
    last 30 ms are not in the trace, so they are neither window nor idle,
    and no gap is named after what the host did in them."""
    raw = _trace()
    raw["planes"][1]["lines"][1]["events"] = raw["planes"][1]["lines"][1][
        "events"][:3]                                  # ends at 70
    out = tr.reduce(raw, n_devices=1)
    assert out["window_source"] == "host_scope_to_device_line_end"
    assert out["window_s"] == pytest.approx(0.070)
    assert out["window_cut_s"] == pytest.approx(0.030)
    assert out["busy_s"] == pytest.approx(0.040)       # 10..40, 60..70
    assert out["values"]["device_idle_share"] == pytest.approx(
        100 * 3 / 7)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert "host in SomethingElse" not in gaps         # 75..90: cut off
    assert gaps["host in bench:marshal_dispatch"] == pytest.approx(0.020)


def test_a_device_line_that_reaches_the_end_leaves_the_window_whole():
    out = tr.reduce(_trace(), n_devices=1)             # last op 90..120
    assert out["window_source"] == "host_scope"
    assert out["window_cut_s"] == 0.0
    assert out["window_s"] == pytest.approx(0.100)


def test_without_the_scope_the_window_is_the_device_extent():
    raw = _trace()
    raw["planes"][0]["lines"][0]["events"] = []
    out = tr.reduce(raw, n_devices=1)
    assert out["window_source"] == "device_extent"
    assert out["window_s"] == pytest.approx(0.110)        # 10..120


def test_no_device_operation_is_refused():
    raw = _trace()
    raw["planes"][1]["lines"] = []
    with pytest.raises(ValueError):
        tr.reduce(raw, n_devices=1)


def test_structure_lists_planes_and_lines():
    s = tr.structure(_trace())
    assert s["planes"]["/device:TPU:0"] == {"XLA Modules": 2, "XLA Ops": 4}
