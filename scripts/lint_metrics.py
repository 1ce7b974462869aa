#!/usr/bin/env python
"""Prometheus naming lint over the process-global metrics registry.

Imports every module that registers metrics (so the registry is fully
populated), then walks it and fails on naming-convention violations:

  - metric names must match the Prometheus identifier grammar
  - counters must end in `_total`; non-counters must NOT
  - base names must not collide with the exposition's reserved histogram
    suffixes (`_bucket`/`_sum`/`_count`)
  - labeled families need valid label names (`le` is rejected at
    registration time; `__`-prefixed names are reserved by Prometheus)
  - every metric carries HELP text (scrapes without it are unreadable)
  - no base-name collisions between a plain series and a family's
    generated series (e.g. a gauge `x_sum` next to a histogram `x`)

Duplicate registration with a different kind/shape raises inside
Registry._register itself; the lint additionally catches cross-metric
collisions the registry cannot see. Run standalone
(`python scripts/lint_metrics.py`) or from the tier-1 gate
(tests/test_metrics.py::test_lint_global_registry).
"""

from __future__ import annotations

import importlib
import os
import re
import sys

# standalone invocation from anywhere: the repo root is the import root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: every module that registers series on the global REGISTRY at import time
METRIC_MODULES = (
    "lighthouse_tpu.utils.metrics",
    "lighthouse_tpu.utils.monitoring",
    "lighthouse_tpu.utils.supervisor",
    "lighthouse_tpu.network.node",
    "lighthouse_tpu.network.gossipsub",
    "lighthouse_tpu.network.sync",
    "lighthouse_tpu.observability.propagation",
    "lighthouse_tpu.chain.beacon_chain",
    "lighthouse_tpu.chain.aggregate_batch",
    "lighthouse_tpu.chain.data_availability",
    "lighthouse_tpu.crypto.kzg",
    "lighthouse_tpu.state_transition.block",
    "lighthouse_tpu.loadgen.netfaults",
    "lighthouse_tpu.loadgen.meshsim",
    "lighthouse_tpu.loadgen.fleet",
    "lighthouse_tpu.validator.beacon_node",
    "lighthouse_tpu.validator.services",
    "lighthouse_tpu.parallel.mesh",
    "lighthouse_tpu.chain.beacon_processor",
    "lighthouse_tpu.chain.scheduler",
    "lighthouse_tpu.loadgen.capacity",
    "lighthouse_tpu.chain.validator_monitor",
    "lighthouse_tpu.crypto.bls.hybrid",
    "lighthouse_tpu.crypto.jaxbls.pipeline",
    "lighthouse_tpu.jaxhash",
    "lighthouse_tpu.jaxhash.engine",
    "lighthouse_tpu.ssz.tree_cache",
    "lighthouse_tpu.ssz.cow",
    "lighthouse_tpu.autotune.profiler",
    "lighthouse_tpu.observability",
    "lighthouse_tpu.observability.device",
    "lighthouse_tpu.observability.perf",
    "lighthouse_tpu.observability.slo",
    "lighthouse_tpu.observability.device_ledger",
    "lighthouse_tpu.observability.flight_recorder",
    "lighthouse_tpu.api.http_api",
    "lighthouse_tpu.api.client",
    "lighthouse_tpu.qos",
)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_RESERVED_SUFFIXES = ("_bucket", "_sum", "_count")


def populate_registry():
    for mod in METRIC_MODULES:
        importlib.import_module(mod)
    from lighthouse_tpu.utils.metrics import REGISTRY

    return REGISTRY


def lint_registry(registry=None) -> list[str]:
    """Return a list of violations (empty = clean)."""
    if registry is None:
        registry = populate_registry()
    errors: list[str] = []
    metrics = registry.all_metrics()
    names = {m.name for m in metrics}
    for m in metrics:
        where = f"{m.kind} {m.name!r}"
        if not _NAME_RE.match(m.name):
            errors.append(f"{where}: invalid metric name")
        if m.kind == "counter" and not m.name.endswith("_total"):
            errors.append(f"{where}: counter names must end in _total")
        if m.kind != "counter" and m.name.endswith("_total"):
            errors.append(f"{where}: only counters may end in _total")
        for suf in _RESERVED_SUFFIXES:
            if m.name.endswith(suf):
                errors.append(
                    f"{where}: base name ends in reserved suffix {suf}"
                )
        if not m.help:
            errors.append(f"{where}: missing HELP text")
        for ln in getattr(m, "labelnames", ()):
            if not _LABEL_RE.match(ln) or ln.startswith("__"):
                errors.append(f"{where}: invalid label name {ln!r}")
        if m.name.startswith("qos_"):
            # QoS accounting series are only useful broken down (shed by
            # kind+reason, refusals by scope, transitions by breaker+state):
            # an unlabeled qos_ aggregate cannot answer "what was lost and
            # why", so the convention is enforced here
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: qos_* metrics must be labeled families"
                )
        if m.name.startswith(("slo_", "flight_recorder_")):
            # the SLO engine's series answer "which window / which outcome
            # / which route" and the flight recorder's "which event kind /
            # which trigger" — an unlabeled aggregate answers none of
            # them, so the convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: slo_*/flight_recorder_* metrics must be "
                    "labeled families"
                )
        if m.name.startswith("jaxbls_pipeline_"):
            # the pipelined executor's series answer "which lane, decided
            # by which config layer" — an unlabeled aggregate over the
            # urgent and batch lanes (or over config sources) hides
            # exactly the routing the executor exists to provide, so the
            # convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: jaxbls_pipeline_* metrics must be labeled "
                    "families (lane / config source)"
                )
        if m.name.startswith(("net_", "gossipsub_")):
            # propagation SLIs and gossipsub mesh health are only readable
            # broken down (which topic stalled, which quantile of the
            # score distribution sank, which context event) — an unlabeled
            # aggregate cannot localize a propagation problem to a topic
            # or a mesh, so the convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: net_*/gossipsub_* metrics must be labeled "
                    "families (topic / role / event / quantile)"
                )
        if m.name.startswith(("sync_", "netfault_")):
            # sync failures and injected network faults are only
            # actionable broken down (which stage failed, which fault
            # fired, which scope ate the message) — an unlabeled
            # aggregate cannot answer "why did the range stall", so the
            # convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: sync_*/netfault_* metrics must be labeled "
                    "families (stage / outcome / fault / scope)"
                )
        if m.name.startswith("mesh_"):
            # the mesh layer's series answer "which axis / which chip /
            # which lane" (axis sizes, per-chip occupancy and stalls,
            # sharded-vs-single-chip dispatch) — an aggregate over chips
            # hides exactly the straggler a mesh_stall incident needs to
            # localize, so the convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: mesh_* metrics must be labeled families "
                    "(axis / chip / lane / outcome)"
                )
        if m.name.startswith(("jaxhash_", "tree_hash_route_")):
            # the tree-hash engine's series answer "which lane / which op
            # / which path served and why" — an unlabeled aggregate over
            # the sharded and single-chip lanes (or over route reasons)
            # hides exactly the second workload's routing, so the
            # convention is enforced like bls_hybrid_route/mesh_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: jaxhash_*/tree_hash_route_* metrics must "
                    "be labeled families (lane / op / path+reason)"
                )
        if m.name.startswith(("tree_cache_", "state_cow_")):
            # the state layer's series answer "HOW was this root served
            # (hit/update/build), WHICH field's chunks copied or re-hashed,
            # which cache kind holds the bytes" — an unlabeled aggregate
            # over fields or outcomes cannot prove the O(changed-chunks)
            # contract the CoW layer exists for, so the convention is
            # enforced like jaxhash_*/tree_hash_route_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: tree_cache_*/state_cow_* metrics must be "
                    "labeled families (outcome / field / kind)"
                )
        if m.name.startswith(("vc_", "fleet_")):
            # the validator duty path's series answer "which duty / which
            # method / which outcome / which node" — an unlabeled
            # aggregate cannot say WHAT was missed or WHY a fallback
            # failed over, so the convention is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: vc_*/fleet_* metrics must be labeled "
                    "families (duty+result / method+result / node / kind)"
                )
        if m.name.startswith("scheduler_"):
            # the capacity scheduler's series answer "which kind's cap,
            # which decision reason, which knob moved which way" — an
            # unlabeled scheduler_* aggregate cannot explain a single
            # control-loop action, so the convention is enforced like
            # qos_* (chain/scheduler.py)
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: scheduler_* metrics must be labeled "
                    "families (kind / reason / knob+direction / class)"
                )
        if m.name.startswith(("jaxbls_stage_", "xla_program_")):
            # per-stage attribution and compiled-program analytics exist
            # to LOCALIZE cost — an aggregate over stages or padding
            # buckets answers nothing, so these families must carry the
            # stage + bucket labels (observability/device.py, perf.py)
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: jaxbls_stage_*/xla_program_* metrics must "
                    "be labeled families (stage + padding bucket)"
                )
        if m.name.startswith("device_ledger_"):
            # the device ledger exists to ATTRIBUTE chip-seconds — which
            # workload burned them, which lane, which victim waited on
            # which occupant, which chip's books they land on. An
            # unlabeled device_ledger_* aggregate is exactly the
            # un-attributed number the ledger replaces, so the convention
            # is enforced like qos_*
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: device_ledger_* metrics must be labeled "
                    "families (workload / lane / victim+occupant / chip)"
                )
        if m.name.startswith(("http_api_", "http_client_")):
            # the HTTP seam's series answer "which route's latency, which
            # shed reason, which read stage timed out, which handler
            # stage failed, which client phase stalled" — an unlabeled
            # http_* aggregate cannot distinguish a saturation shed from
            # a shutdown drain or a connect timeout from a stalled body,
            # so the convention is enforced like qos_* (api/http_api.py,
            # api/client.py)
            if not getattr(m, "labelnames", ()):
                errors.append(
                    f"{where}: http_api_*/http_client_* metrics must be "
                    "labeled families (route+method / reason / stage / "
                    "phase / event / kind)"
                )
        if m.kind == "histogram":
            # a histogram's exposition series must not shadow other metrics
            for suf in _RESERVED_SUFFIXES:
                if m.name + suf in names:
                    errors.append(
                        f"{where}: exposition series {m.name + suf!r} "
                        "collides with another registered metric"
                    )
    return errors


def main() -> int:
    errors = lint_registry()
    registry = populate_registry()
    n = len(registry.all_metrics())
    if errors:
        for e in errors:
            print(f"LINT: {e}", file=sys.stderr)
        print(f"{len(errors)} violation(s) across {n} metrics", file=sys.stderr)
        return 1
    print(f"{n} metrics/families clean")
    # the bench trend gate rides the same CI entry point: host-only,
    # sub-second, fails the lint run on a >10% fresh-to-fresh regression
    # in the checked-in BENCH_r*/MULTICHIP_r* series
    from lighthouse_tpu.observability import perf

    rc, report = perf.check()
    if rc:
        for r in report["regressions"]:
            print(
                f"PERF: {r['config']} regressed {r['delta_pct']}% "
                f"({r['from']} -> {r['to']})",
                file=sys.stderr,
            )
        return rc
    print("perf trend gate clean (no fresh-to-fresh regression)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
