"""The generic reader of per-layer metrics.

A per-layer metric is one JSON file in `layer_metrics/`; its `source` is
evaluated here and nowhere else, so a later PR that adds a counter to the
program adds one such file and no code. Sources:

  {"family": F, "labels": {...}, "reduce": R}
      over the program's metrics registry, as the DELTA between the
      snapshot at the window's opening and the one at its close. Children
      whose labels contain `labels` are summed. R is
        "mean_ms"  histogram: delta of the sum / delta of the count, x1000
        "sum"      counter or gauge: delta of the value; histogram: of the sum
        "count"    histogram: delta of the count; counter: delta of the value
      optional "per_peak": K divides the number by peaks.json's entry K of
      this device kind (bytes -> seconds at the memory peak, and so on).
  {"reduce": "ratio", "num": <source>, "den": <source>, "scale": S}
      num / den * S (S defaults to 1), both sources as above.
  {"trace": K}    the number the trace reduction gives under K.
  {"harness": K}  a number the harness takes itself (set-up's parts).

A source that finds nothing to read (no such family, no matching child, a
mean over nothing observed in the window, a zero denominator) gives None,
and the harness leaves the metric out of the line.
"""

from __future__ import annotations


def snapshot(registry) -> dict:
    """{family: {"labelnames": (...), "children": {labelvalues: reading}}}
    with reading = ("scalar", value) or ("hist", sum, count)."""
    out = {}
    for m in registry.all_metrics():
        if hasattr(m, "children"):
            names = tuple(m.labelnames)
            kids = {k: _reading(c) for k, c in m.children()}
        else:
            names = ()
            kids = {(): _reading(m)}
        out[m.name] = {"labelnames": names, "children": kids}
    return out


def _reading(metric):
    if hasattr(metric, "counts"):
        return ("hist", float(metric.total), int(metric.n))
    return ("scalar", float(metric.value))


def _delta(family: str, labels: dict, before: dict, after: dict):
    """Summed (d_sum_or_value, d_count, is_hist) over the children of
    `family` whose labels contain `labels`; None when nothing matches."""
    fam = after.get(family)
    if fam is None:
        return None
    names = fam["labelnames"]
    if any(k not in names for k in labels):
        return None
    want = {names.index(k): str(v) for k, v in labels.items()}
    old = before.get(family, {"children": {}})["children"]
    d_val = 0.0
    d_n = 0
    is_hist = False
    matched = False
    for key, now in fam["children"].items():
        if any(key[i] != v for i, v in want.items()):
            continue
        matched = True
        was = old.get(key)
        if now[0] == "hist":
            is_hist = True
            d_val += now[1] - (was[1] if was else 0.0)
            d_n += now[2] - (was[2] if was else 0)
        else:
            d_val += now[1] - (was[1] if was else 0.0)
    if not matched:
        return None
    return d_val, d_n, is_hist


def evaluate(source: dict, before: dict, after: dict, peaks: dict,
             values: dict):
    """The number a `source` reads, or None."""
    if "trace" in source:
        return values.get("trace", {}).get(source["trace"])
    if "harness" in source:
        return values.get("harness", {}).get(source["harness"])
    reduce = source.get("reduce")
    if reduce == "ratio":
        num = evaluate(source["num"], before, after, peaks, values)
        den = evaluate(source["den"], before, after, peaks, values)
        if num is None or not den:
            return None
        return num / den * source.get("scale", 1)
    d = _delta(source["family"], source.get("labels", {}), before, after)
    if d is None:
        return None
    d_val, d_n, is_hist = d
    if reduce == "mean_ms":
        if not is_hist or d_n <= 0:
            return None
        out = d_val / d_n * 1e3
    elif reduce == "sum":
        out = d_val
    elif reduce == "count":
        out = float(d_n) if is_hist else d_val
    else:
        raise ValueError(f"unknown reduce {reduce!r} in {source}")
    if "per_peak" in source:
        out = out / peaks[source["per_peak"]]
    return out
