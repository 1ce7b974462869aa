"""The generic layer-metric reader on a registry of its own."""

import layer_reader
from lighthouse_tpu.utils.metrics import Registry

PEAKS = {"hbm_bytes_per_s": 100.0}


def _registry():
    r = Registry()
    h = r.histogram_vec("stage_seconds", "", ("stage", "n"))
    c = r.counter_vec("cache_total", "", ("result",))
    plain = r.histogram("marshal_seconds", "")
    b = r.counter("bytes_total", "")
    return r, h, c, plain, b


def test_deltas_over_the_window_only():
    r, h, c, plain, b = _registry()
    h.labels("pairing", 64).observe(9.0)        # before the window
    c.labels("hit").inc(5)
    before = layer_reader.snapshot(r)
    h.labels("pairing", 64).observe(0.25)
    h.labels("pairing", 64).observe(0.75)
    h.labels("prepare", 64).observe(0.1)
    c.labels("miss").inc(3)
    c.labels("hit").inc(1)
    plain.observe(0.002)
    b.inc(500)
    after = layer_reader.snapshot(r)

    def ev(src):
        return layer_reader.evaluate(src, before, after, PEAKS, {})

    assert ev({"family": "stage_seconds", "labels": {"stage": "pairing"},
               "reduce": "mean_ms"}) == 500.0
    assert ev({"family": "stage_seconds", "labels": {"stage": "pairing"},
               "reduce": "count"}) == 2
    assert ev({"family": "stage_seconds", "reduce": "sum"}) == 1.1
    assert ev({"family": "marshal_seconds", "reduce": "mean_ms"}) == 2.0
    assert ev({"family": "cache_total", "labels": {"result": "miss"},
               "reduce": "sum"}) == 3
    assert ev({"reduce": "ratio", "scale": 100,
               "num": {"family": "cache_total",
                       "labels": {"result": "miss"}, "reduce": "sum"},
               "den": {"family": "cache_total", "reduce": "sum"}}) == 75.0
    # bytes at the peak, as a share of the seconds spent
    assert ev({"reduce": "ratio", "scale": 100,
               "num": {"family": "bytes_total", "reduce": "sum",
                       "per_peak": "hbm_bytes_per_s"},
               "den": {"family": "stage_seconds", "reduce": "sum"}}
              ) == 100 * 5.0 / 1.1


def test_nothing_to_read_gives_none():
    r, h, c, plain, b = _registry()
    h.labels("pairing", 64).observe(1.0)
    before = layer_reader.snapshot(r)
    after = layer_reader.snapshot(r)

    def ev(src):
        return layer_reader.evaluate(src, before, after, PEAKS, {})

    assert ev({"family": "no_such_family", "reduce": "sum"}) is None
    assert ev({"family": "stage_seconds", "labels": {"stage": "h2c"},
               "reduce": "mean_ms"}) is None          # no such child
    assert ev({"family": "stage_seconds", "labels": {"lane": "x"},
               "reduce": "mean_ms"}) is None          # no such label
    assert ev({"family": "stage_seconds", "labels": {"stage": "pairing"},
               "reduce": "mean_ms"}) is None          # nothing in the window
    assert ev({"reduce": "ratio",
               "num": {"family": "cache_total", "reduce": "sum"},
               "den": {"family": "cache_total", "reduce": "sum"}}) is None


def test_trace_and_harness_sources():
    values = {"trace": {"device_idle_share": 12.5},
              "harness": {"setup_compile_s": 3.0}}
    assert layer_reader.evaluate({"trace": "device_idle_share"}, {}, {},
                                 PEAKS, values) == 12.5
    assert layer_reader.evaluate({"harness": "setup_compile_s"}, {}, {},
                                 PEAKS, values) == 3.0
    assert layer_reader.evaluate({"trace": "absent"}, {}, {}, PEAKS,
                                 values) is None
