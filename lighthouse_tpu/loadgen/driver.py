"""Shared loadtest driver behind `bn loadtest` and scripts/loadgen.py.

One implementation of the flag set, scenario resolution, report-path
defaulting and the one-line stdout summary, so the two entry points cannot
drift. Default report paths resolve against the repository root (where
.gitignore covers LOADGEN_SMOKE.json / loadgen_report.json), not the
caller's cwd.

This module is a LEAF import: the CLI parser loads it on every invocation
for `add_loadtest_args`, so the runner (and its chain/network import
graph) is only imported inside `drive()`.
"""

from __future__ import annotations

import json
import os
import sys

# lighthouse_tpu/loadgen/driver.py -> repo root
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def default_report_path(smoke: bool) -> str:
    name = "LOADGEN_SMOKE.json" if smoke else "loadgen_report.json"
    return os.path.join(_ROOT, name)


def drive(*, scenario=None, smoke=False, slots=None, validators=None,
          seed=None, flood_factor=None, out=None, quiet=False,
          datadir=None, mesh_devices=None, bench_matrix=False,
          bench_root=None, hash_backend=None, trace_out=None, stdout=None,
          stderr=None) -> int:
    """Run one scenario and print the one-line JSON summary. Returns a
    process exit code. `--smoke` alone runs the 'smoke' scenario; combined
    with an explicit --scenario it is a SIZE modifier — the named scenario
    shrunk to smoke scale (same faults and mix, clamped validators/slots),
    e.g. `bn loadtest --scenario crash_restart --smoke`.

    `--mesh-devices 1,8` turns the run into a mesh SWEEP: the scenario
    runs once per chip count over the mesh-sharded device harness
    (loadgen/meshsim.py), the summary reports sets/s + p50 per point,
    the run FAILS unless the largest point out-serves the smallest, and
    every point lands as a `source: loadtest` BENCH_MATRIX row.
    `--bench-matrix` opts a single (non-sweep) run into the same row
    write; `--bench-root` redirects where the matrix lives (tests)."""
    from .runner import run_scenario
    from .scenarios import get_scenario, is_multinode, smoke_variant

    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr
    name = "smoke" if smoke and scenario is None else (scenario or "smoke")
    if trace_out:
        from .scenarios import (
            is_fleet as _isf,
            is_mixed_duty as _ismd,
            is_multinode as _ism,
        )

        if not (_isf(name) or _ism(name) or _ismd(name)) or mesh_devices:
            # the merged cluster timeline is a multi-node artifact (and
            # mixed_duty's is the device-ledger timeline); a
            # single-process scenario's spans already export via
            # `bn --trace-out` — warn BEFORE any scenario branch so the
            # flag is never dropped silently
            print("warning: --trace-out only applies to multi-node/fleet/"
                  "mixed_duty scenarios; ignored", file=stderr)
            trace_out = None
    if mesh_devices:
        return _drive_mesh_sweep(
            name, mesh_devices, smoke=smoke, slots=slots,
            validators=validators, seed=seed, flood_factor=flood_factor,
            out=out, quiet=quiet, datadir=datadir, bench_root=bench_root,
            stdout=stdout, stderr=stderr,
        )
    from .scenarios import is_capacity

    if is_capacity(name):
        return _drive_capacity(
            name, smoke=smoke, slots=slots, validators=validators,
            seed=seed, out=out, quiet=quiet, datadir=datadir,
            bench_matrix=bench_matrix, bench_root=bench_root,
            stdout=stdout, stderr=stderr,
        )
    from .scenarios import is_mixed_duty

    if is_mixed_duty(name):
        return _drive_mixed_duty(
            name, smoke=smoke, slots=slots, validators=validators,
            seed=seed, out=out, quiet=quiet, datadir=datadir,
            bench_matrix=bench_matrix, bench_root=bench_root,
            trace_out=trace_out, stdout=stdout, stderr=stderr,
        )
    from .scenarios import is_state_root

    if is_state_root(name):
        return _drive_state_root(
            name, smoke=smoke, slots=slots, validators=validators,
            seed=seed, out=out, quiet=quiet,
            bench_matrix=bench_matrix, bench_root=bench_root,
            hash_backend=hash_backend, stdout=stdout, stderr=stderr,
        )
    from .scenarios import is_fleet

    if is_fleet(name):
        return _drive_fleet(
            name, smoke=smoke, slots=slots, validators=validators,
            seed=seed, out=out, quiet=quiet, datadir=datadir,
            trace_out=trace_out, stdout=stdout, stderr=stderr,
        )
    if is_multinode(name):
        return _drive_multinode(
            name, smoke=smoke, slots=slots, validators=validators,
            seed=seed, out=out, quiet=quiet, datadir=datadir,
            trace_out=trace_out, stdout=stdout, stderr=stderr,
        )
    try:
        sc = get_scenario(name, slots=slots, n_validators=validators,
                          seed=seed, flood_factor=flood_factor)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=stderr)
        return 1
    if smoke and sc.name != "smoke":
        sc = smoke_variant(sc)
    out = out or default_report_path(smoke or sc.name == "smoke")
    report = run_scenario(
        sc, out_path=out, datadir=datadir,
        log_fn=None if quiet else (
            lambda m: print(m, file=stderr, flush=True)
        ),
    )
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "published": report["published"],
        "qos_totals": report["qos_totals"],
        "breaker_transitions": report["breaker_transitions"],
        "blocks_processed_in_slot": report["blocks_processed_in_slot"],
        "slo": {
            "deadline_hit_ratio": report["slo"]["deadline_hit_ratio"],
            "windows": report["slo"]["windows"],
            "incidents": report["slo"]["incidents"],
        },
        "elapsed_secs": report["elapsed_secs"],
    }
    if "crash" in report:
        summary["crash"] = report["crash"]
        summary["conservation"] = report["conservation"]
    if "mesh" in report:
        summary["mesh"] = {
            k: report["mesh"][k]
            for k in ("devices", "sets_per_sec", "verify_p50_ms",
                      "stall_hits", "urgent_served", "urgent_stalled")
            if k in report["mesh"]
        }
    print(json.dumps(summary), file=stdout)
    if bench_matrix:
        _write_matrix_rows(name, {None: report}, smoke=smoke,
                           bench_root=bench_root, stderr=stderr)
    if "crash" in report and not (
        report["crash"]["resumed_from_persisted_head"]
        and report["conservation"]["ok"]
    ):
        print("error: crash-restart invariants violated (see report)",
              file=stderr)
        return 1
    if "device_stall" in report.get("faults", ()) and not (
        report["slo"]["incidents"]
    ):
        # a device stall MUST leave a durable incident trail: the breaker
        # opening is the canonical trigger, and a run where it produced no
        # dump means the black box is broken — fail loudly
        print("error: device_stall produced no incident dump "
              "(see report slo block)", file=stderr)
        return 1
    if "mesh_stall" in report.get("faults", ()):
        rc = _check_mesh_stall(report, stderr)
        if rc:
            return rc
    return 0


def _check_mesh_stall(report, stderr) -> int:
    """mesh_stall acceptance: the stalled chip must produce breaker-
    mediated DEGRADATION (deadline-hit ratio dips while the collective is
    wedged) followed by RECOVERY (the healed slots serve on time again),
    with at least one schema-valid incident dumped — never a silently
    wedged pipeline window."""
    if not report["slo"]["incidents"]:
        print("error: mesh_stall produced no incident dump "
              "(see report slo block)", file=stderr)
        return 1
    ratios = [
        s["deadline_hit_ratio"] for s in report["slo"]["per_slot"]
        if s["deadline_hit_ratio"] is not None
    ]
    if not ratios or min(ratios) >= 1.0:
        print("error: mesh_stall produced no deadline-hit-ratio dip "
              "(the stalled shard was never felt)", file=stderr)
        return 1
    if ratios[-1] <= min(ratios):
        print("error: mesh_stall never recovered after the heal "
              f"(per-slot ratios: {ratios})", file=stderr)
        return 1
    return 0


def _write_matrix_rows(name, reports_by_point, *, smoke, bench_root,
                       stderr) -> dict:
    """Snapshot measured sets/s + p50 into the BENCH_MATRIX schema with a
    `source: loadtest` tag (observability/perf.write_loadtest_rows) — the
    device-free bench seam: any soak through `bn loadtest` doubles as a
    bench round, and the trend gate reads the rows as fresh."""
    import time as _time

    from ..observability import perf as _perf

    rows = {}
    stamp = round(_time.time(), 3)
    for point, report in reports_by_point.items():
        mesh = report.get("mesh") or {}
        obs = mesh or report.get("verify_observations") or {}
        key = f"loadtest_{name}" if point is None else (
            f"loadtest_{name}_mesh{point}"
        )
        row = {
            "source": "loadtest",
            "scenario": report["scenario"],
            "measured_unix": stamp,
            "n_devices": mesh.get("devices", 1),
            "deadline_hit_ratio": report["slo"]["deadline_hit_ratio"],
        }
        # only measured values enter the matrix: a null rate row would
        # read as a measurement (and trip every later matrix parse) when
        # it really means "this run had no device-timed batches"
        if obs.get("sets_per_sec") is not None:
            row["sets_per_sec"] = obs["sets_per_sec"]
        if obs.get("verify_p50_ms") is not None:
            row["p50_ms"] = obs["verify_p50_ms"]
        rows[key] = row
    try:
        path = _perf.write_loadtest_rows(rows, smoke=smoke, root=bench_root)
        print(f"bench matrix rows -> {path}", file=stderr)
    except Exception as e:  # a bench snapshot must never fail the run
        print(f"warning: bench matrix write failed: {e}", file=stderr)
    return rows


def _drive_mesh_sweep(name, points, *, smoke, slots, validators, seed,
                      flood_factor, out, quiet, datadir, bench_root,
                      stdout, stderr) -> int:
    """The --mesh-devices sweep: one run per chip count over the
    mesh-sharded harness; asserts the biggest mesh out-serves the
    smallest (near-linear scaling is the whole point of sharding the
    dispatcher) and snapshots every point into BENCH_MATRIX rows."""
    from dataclasses import replace

    from .runner import run_scenario
    from .scenarios import get_scenario, is_multinode, smoke_variant

    from .scenarios import (
        is_capacity,
        is_fleet,
        is_mixed_duty,
        is_state_root,
    )

    if (is_multinode(name) or is_state_root(name) or is_fleet(name)
            or is_capacity(name) or is_mixed_duty(name)):
        print(f"error: --mesh-devices does not apply to scenario "
              f"{name!r} (multi-node, fleet, state_root, capacity and "
              "mixed_duty scenarios drive surfaces the mesh sweep does "
              "not)", file=stderr)
        return 1
    try:
        points = sorted({int(p) for p in points})
    except (TypeError, ValueError):
        print(f"error: bad --mesh-devices list {points!r}", file=stderr)
        return 1
    try:
        base = get_scenario(name, slots=slots, n_validators=validators,
                            seed=seed, flood_factor=flood_factor)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=stderr)
        return 1
    if smoke and base.name != "smoke":
        base = smoke_variant(base)
    incompatible = {"device_stall", "storage_crash", "mesh_stall"} & set(
        base.faults
    )
    if incompatible:
        # device_stall/storage_crash drive surfaces the mesh harness does
        # not have; mesh_stall's acceptance (urgent lane unaffected, dip +
        # recovery) is ill-defined at the sweep's 1-chip point, where the
        # wedged chip IS the urgent lane's — run it standalone, where the
        # driver enforces its gate. Refuse cleanly instead of tracebacking
        # (or silently skipping a gate) mid-sweep.
        print(
            f"error: --mesh-devices cannot sweep scenario {name!r} "
            f"(fault(s) {sorted(incompatible)} don't compose with a "
            "chip-count sweep); use flood/steady/slow_host, and run "
            "mesh_stall standalone",
            file=stderr,
        )
        return 1
    out = out or default_report_path(smoke)
    reports = {}
    prev_env = os.environ.get("LIGHTHOUSE_TPU_MESH_DEVICES")

    def _reset_mesh():
        try:
            from ..parallel import reset_mesh_cache

            reset_mesh_cache()
        except Exception:
            pass

    try:
        for d in points:
            sc = replace(base, mesh=True, mesh_devices=d)
            # flip the REAL mesh seam too, so a harness with virtual
            # devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
            # exercises production mesh bring-up at every sweep point
            os.environ["LIGHTHOUSE_TPU_MESH_DEVICES"] = str(d)
            _reset_mesh()
            reports[d] = run_scenario(
                sc, out_path=None, datadir=datadir,
                log_fn=None if quiet else (
                    lambda m, _d=d: print(f"[mesh={_d}] {m}", file=stderr,
                                          flush=True)
                ),
            )
    finally:
        # restore (never destroy) an operator-set seam and re-resolve the
        # process-wide mesh so nothing after the sweep serves on the last
        # point's topology
        if prev_env is None:
            os.environ.pop("LIGHTHOUSE_TPU_MESH_DEVICES", None)
        else:
            os.environ["LIGHTHOUSE_TPU_MESH_DEVICES"] = prev_env
        _reset_mesh()
    rows = _write_matrix_rows(name, reports, smoke=smoke,
                              bench_root=bench_root, stderr=stderr)
    sweep = {
        "scenario": name,
        "report": out,
        "mesh_sweep": {
            str(d): {
                "sets_per_sec": r["mesh"]["sets_per_sec"],
                "verify_p50_ms": r["mesh"]["verify_p50_ms"],
                "deadline_hit_ratio": r["slo"]["deadline_hit_ratio"],
                "device_batches": r["mesh"]["device_batches"],
            }
            for d, r in reports.items()
        },
        "matrix_rows": sorted(rows),
    }
    lo, hi = points[0], points[-1]
    lo_rate = reports[lo]["mesh"]["sets_per_sec"] or 0.0
    hi_rate = reports[hi]["mesh"]["sets_per_sec"] or 0.0
    if len(points) > 1:
        sweep["scaling"] = {
            "from_devices": lo, "to_devices": hi,
            "speedup": round(hi_rate / lo_rate, 3) if lo_rate else None,
        }
    if out:
        with open(out, "w") as f:
            json.dump({"sweep": sweep, "points": {
                str(d): r for d, r in reports.items()
            }}, f, indent=1)
    print(json.dumps(sweep), file=stdout)
    if len(points) > 1 and not hi_rate > lo_rate:
        print(
            f"error: mesh sweep did not scale: {hi}-device point "
            f"({hi_rate} sets/s) is not above the {lo}-device point "
            f"({lo_rate} sets/s)", file=stderr,
        )
        return 1
    return 0


def _drive_capacity(name, *, smoke, slots, validators, seed, out, quiet,
                    datadir, bench_matrix, bench_root, stdout, stderr) -> int:
    """The closed-loop capacity-control proof (loadgen/capacity.py): the
    controller leg (NO pre-installed profile, scheduler retuning live)
    against the static-optimal fixed-cap reference. Exit code is the
    acceptance gate — nonzero unless the controller's deadline-credited
    throughput lands within the scenario's gate_ratio (default 10%) of
    the best static plan, with conservation intact. The measured
    controller-vs-static ratio lands as a `source: loadtest` BENCH_MATRIX
    row with a fresh-entry history, so the perf trend gate catches a
    controller regression fresh-to-fresh."""
    from .capacity import run_capacity_scenario
    from .scenarios import capacity_smoke_variant, get_capacity_scenario

    sc = get_capacity_scenario(name, slots=slots, n_validators=validators,
                               seed=seed)
    if smoke:
        sc = capacity_smoke_variant(sc)
    out = out or default_report_path(smoke)
    report = run_capacity_scenario(
        sc, out_path=out, datadir=datadir,
        log_fn=None if quiet else (
            lambda m: print(m, file=stderr, flush=True)
        ),
    )
    det = report["deterministic"]
    gate = report["gate"]
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "gate": gate,
        "scheduler": {
            "caps": det["scheduler"]["caps"],
            "retune_count": det["scheduler"]["retune_count"],
            "last_retune_slot": det["scheduler"]["last_retune_slot"],
            "urgent_max_sets": det["scheduler"]["urgent_max_sets"],
            "watermarks": det["scheduler"]["watermarks"],
        },
        "lane_efficiency": det["device"]["lane_efficiency"],
        "bulk_refused": det["bulk"]["refused"],
        "incidents": report["slo"]["incidents"],
        "elapsed_secs": report["elapsed_secs"],
    }
    print(json.dumps(summary), file=stdout)
    if bench_matrix:
        import time as _time

        from ..observability import perf as _perf

        row = {
            "source": "loadtest",
            "scenario": report["scenario"],
            "measured_unix": round(_time.time(), 3),
            "validators": report["n_validators"],
            "scheduler_ratio": gate["ratio"],
            "controller_hits": gate["controller_hits"],
            "static_optimal_hits": gate["static_optimal_hits"],
            "lane_efficiency": det["device"]["lane_efficiency"],
        }
        try:
            path = _perf.write_loadtest_rows(
                {f"loadtest_{name}": row}, smoke=smoke, root=bench_root
            )
            print(f"bench matrix rows -> {path}", file=stderr)
        except Exception as e:  # a bench snapshot must never fail the run
            print(f"warning: bench matrix write failed: {e}", file=stderr)
    if not gate["ok"]:
        print(
            f"error: capacity controller missed the static-optimal gate "
            f"(ratio={gate['ratio']}, need >= {gate['gate_ratio']}, "
            f"conservation_ok="
            f"{det['conservation']['ok']})", file=stderr,
        )
        return 1
    return 0


def _drive_mixed_duty(name, *, smoke, slots, validators, seed, out, quiet,
                      datadir, bench_matrix, bench_root, trace_out=None,
                      stdout=None, stderr=None) -> int:
    """The one-device-many-tenants proof (loadgen/mixed_duty.py): BLS,
    state-root and epoch work share one logical device while the global
    device ledger attributes every chip-second. Exit code is the
    acceptance gate — nonzero unless per-chip conservation holds
    (busy + idle + contention-wait == wall), every tenant lands a
    per-workload SLO block, the injected mid-run stall produces >= 1
    schema-valid device_contention incident naming victim + occupant,
    and a full rerun is BIT-IDENTICAL in the deterministic core.
    `--bench-matrix` snapshots one `loadtest_mixed_duty_<workload>` row
    per tenant. `--trace-out` renders the ledger's merged per-workload
    device timeline (occupancy tracks + waiting markers)."""
    import tempfile as _tempfile

    from .mixed_duty import run_mixed_duty_scenario
    from .scenarios import get_mixed_duty_scenario, mixed_duty_smoke_variant

    sc = get_mixed_duty_scenario(name, slots=slots, n_validators=validators,
                                 seed=seed)
    if smoke:
        sc = mixed_duty_smoke_variant(sc)
    out = out or default_report_path(smoke)
    report = run_mixed_duty_scenario(
        sc, out_path=out, datadir=datadir, trace_out=trace_out,
        log_fn=None if quiet else (
            lambda m: print(m, file=stderr, flush=True)
        ),
    )
    # the determinism gate is a REAL rerun, not a pinky promise: same
    # scenario, fresh datadir, then byte-compare the deterministic cores
    rerun = run_mixed_duty_scenario(
        sc, out_path=None, log_fn=None,
        datadir=_tempfile.mkdtemp(prefix="loadgen-mixed-duty-rerun-"),
    )
    identical = (
        json.dumps(report["deterministic"], sort_keys=True)
        == json.dumps(rerun["deterministic"], sort_keys=True)
    )
    det = report["deterministic"]
    gate = dict(report["gate"])
    gate["rerun_identical"] = identical
    gate["ok"] = gate["ok"] and identical
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "gate": gate,
        "workloads": det["workloads"],
        "conservation": {
            "ok": det["device_ledger"]["conservation"]["ok"],
            "wall": det["device_ledger"]["conservation"]["wall"],
        },
        "contention_seconds": det["device_ledger"]["contention_seconds"],
        "contention_incidents": det["contention_incidents"],
        "incidents": report["slo"]["incidents"],
        "elapsed_secs": report["elapsed_secs"],
    }
    if trace_out:
        summary["trace_out"] = trace_out
    print(json.dumps(summary), file=stdout)
    if bench_matrix:
        import time as _time

        from ..observability import perf as _perf

        stamp = round(_time.time(), 3)
        rows = {}
        for w, blk in det["workloads"].items():
            rows[f"loadtest_{name}_{w}"] = {
                "source": "loadtest",
                "scenario": report["scenario"],
                "workload": w,
                "measured_unix": stamp,
                "n_chips": det["device_ledger"]["n_chips"],
                "deadline_hit_ratio": blk["hit_ratio"],
                "busy_seconds": blk["busy_seconds"],
                "contention_victim_seconds": round(sum(
                    s for k, s in
                    det["device_ledger"]["contention_seconds"].items()
                    if k.split("|")[0] == w
                ), 9),
            }
        try:
            path = _perf.write_loadtest_rows(rows, smoke=smoke,
                                             root=bench_root)
            print(f"bench matrix rows -> {path}", file=stderr)
        except Exception as e:  # a bench snapshot must never fail the run
            print(f"warning: bench matrix write failed: {e}", file=stderr)
    if not gate["ok"]:
        if not gate["conservation_ok"]:
            print("error: mixed_duty device-ledger conservation violated "
                  "(busy + idle + contention-wait != wall; see report)",
                  file=stderr)
        if not gate["workload_blocks_ok"]:
            print("error: mixed_duty run is missing a per-workload SLO "
                  "block for at least one tenant (see report)",
                  file=stderr)
        if not gate["contention_incident_ok"]:
            print("error: mixed_duty stall produced no schema-valid "
                  "device_contention incident naming victim + occupant",
                  file=stderr)
        if not identical:
            print("error: mixed_duty rerun was not bit-identical in the "
                  "deterministic core", file=stderr)
        return 1
    return 0


def _drive_state_root(name, *, smoke, slots, validators, seed, out, quiet,
                      bench_matrix, bench_root, hash_backend=None,
                      stdout=None, stderr=None) -> int:
    """The second-workload soak (loadgen/state_root.py): seeded
    mutate-and-reroot churn at validator scale through the active hash
    backend. Exit code is the conservation verdict — nonzero when the
    balance ledger breaks or the final root diverges from the cache-free
    ground truth. `--bench-matrix` snapshots the measured reroot p50 as
    a `state_root` BENCH_MATRIX row (the bench_state_root.py schema)."""
    from .scenarios import get_state_root_scenario, state_root_smoke_variant
    from .state_root import run_state_root_scenario

    sc = get_state_root_scenario(name, slots=slots, n_validators=validators,
                                 seed=seed, hash_backend=hash_backend)
    if smoke:
        sc = state_root_smoke_variant(sc)
    out = out or default_report_path(smoke)
    report = run_state_root_scenario(
        sc, out_path=out,
        log_fn=None if quiet else (
            lambda m: print(m, file=stderr, flush=True)
        ),
    )
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "hash_backend": report["hash_backend"],
        "published": report["published"],
        "roots": report["roots"],
        "reroot_p50_ms": report["reroot_p50_ms"],
        "conservation": report["conservation"],
        "tree_hash_routes": report["tree_hash_routes"],
        "elapsed_secs": report["elapsed_secs"],
    }
    print(json.dumps(summary), file=stdout)
    if not report["conservation"]["ok"]:
        # verdict BEFORE the matrix write: a run serving wrong roots must
        # never land a fresh p50 entry in the artifact of record
        print("error: state_root conservation violated (see report)",
              file=stderr)
        return 1
    if bench_matrix:
        import time as _time

        from ..observability import perf as _perf

        row = {
            "source": "loadtest",
            "scenario": report["scenario"],
            "measured_unix": round(_time.time(), 3),
            "validators": report["n_validators"],
            "hash_backend": report["hash_backend"],
            "p50_ms": report["reroot_p50_ms"],
            "roots_per_sec": report["roots_per_sec"],
        }
        try:
            path = _perf.write_loadtest_rows(
                {"state_root": row}, smoke=smoke, root=bench_root
            )
            print(f"bench matrix rows -> {path}", file=stderr)
        except Exception as e:  # a bench snapshot must never fail the run
            print(f"warning: bench matrix write failed: {e}", file=stderr)
    return 0


def _drive_fleet(name, *, smoke, slots, validators, seed, out, quiet,
                 datadir, trace_out=None, stdout=None, stderr=None) -> int:
    """Validator-fleet soak leg (loadgen/fleet.py): real VC stacks drive
    every duty through rate-limited node surfaces under composed faults.
    Exit code is the scenario verdict — nonzero on a broken invariant:
    duty conservation, zero slashable signatures (post-hoc replay),
    convergence within K of heal, or burn not recovering under 1x."""
    from .fleet import run_fleet_scenario
    from .scenarios import fleet_smoke_variant, get_fleet_scenario

    sc = get_fleet_scenario(name, slots=slots, n_validators=validators,
                            seed=seed)
    if smoke:
        sc = fleet_smoke_variant(sc)
    out = out or default_report_path(smoke)
    report = run_fleet_scenario(
        sc, out_path=out, datadir=datadir, trace_out=trace_out,
        log_fn=None if quiet else (
            lambda m: print(m, file=stderr, flush=True)
        ),
    )
    det = report["deterministic"]
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "ok": report["ok"],
        "n_vcs": report["n_vcs"],
        "cluster": det["cluster"],
        "duty_conservation": {
            k: det["duty_conservation"][k]
            for k in ("scheduled", "performed", "missed",
                      "performed_ratio", "ok")
        },
        "slashable": {
            "signed_blocks": det["slashable_replay"]["signed_blocks"],
            "signed_attestations":
                det["slashable_replay"]["signed_attestations"],
            "ok": det["slashable_replay"]["ok"],
        },
        "convergence": det["convergence"],
        "burn_final": report["burn_final"],
        "incidents": report["slo"]["incidents"],
        "elapsed_secs": report["elapsed_secs"],
    }
    if "trace" in report:
        summary["trace_out"] = report["trace"]["path"]
    print(json.dumps(summary), file=stdout)
    if not report["ok"]:
        for reason in report["failures"]:
            print(f"error: {reason}", file=stderr)
        return 1
    return 0


def _drive_multinode(name, *, smoke, slots, validators, seed, out, quiet,
                     datadir, trace_out=None, stdout=None,
                     stderr=None) -> int:
    """Multi-node scenario leg: N full nodes over real TCP under a network
    fault plan (loadgen/multinode.py). Exit code is the scenario verdict —
    nonzero on divergence, broken conservation, or an un-exercised fault."""
    from .multinode import run_multinode_scenario
    from .scenarios import get_multinode_scenario, multinode_smoke_variant

    sc = get_multinode_scenario(name, slots=slots, n_validators=validators,
                                seed=seed)
    if smoke:
        sc = multinode_smoke_variant(sc)
    out = out or default_report_path(smoke)
    try:
        report = run_multinode_scenario(
            sc, out_path=out, datadir=datadir, trace_out=trace_out,
            log_fn=None if quiet else (
                lambda m: print(m, file=stderr, flush=True)
            ),
        )
    except ValueError as e:
        # e.g. a --validators override that no longer matches the
        # scenario's fixed validator_split
        print(f"error: {e}", file=stderr)
        return 1
    det = report["deterministic"]
    summary = {
        "scenario": report["scenario"],
        "report": out,
        "ok": report["ok"],
        "convergence": det["convergence"],
        "blocks": det["blocks"],
        "orphaned_blocks": det["orphaned_blocks"],
        "netfault_events": len(det["netfault_events"]),
        "cluster": det["cluster"],
        "incidents": report["slo"]["incidents"],
        "elapsed_secs": report["elapsed_secs"],
    }
    if "trace" in report:
        summary["trace_out"] = report["trace"]["path"]
    if det["sync"] is not None:
        summary["sync"] = {
            "reached_head": det["sync"]["reached_head"],
            "imported_blocks": det["sync"]["imported_blocks"],
            "failovers": det["sync"]["stats"]["failovers"],
            "batch_retries": det["sync"]["stats"]["batch_retries"],
        }
    if det["equivocation"]["injected"]:
        summary["equivocation"] = {
            "injected": det["equivocation"]["injected"],
            "detections": sum(
                det["equivocation"]["detections_by_node"].values()
            ),
            "slashed": det["equivocation"]["slashed_in_final_state"],
        }
    print(json.dumps(summary), file=stdout)
    if not report["ok"]:
        for reason in report["failures"]:
            print(f"error: {reason}", file=stderr)
        return 1
    return 0


def add_loadtest_args(parser) -> None:
    """The flag set shared by both entry points."""
    parser.add_argument("--scenario", default=None,
                        help="named scenario: smoke, steady, flood, "
                             "device_stall, mesh_stall, slow_host, "
                             "crash_restart, state_root (mutate-and-reroot "
                             "churn through the active hash backend), a "
                             "capacity-control proof: diurnal_ramp, "
                             "flash_crowd (closed-loop scheduler vs the "
                             "static-optimal plan; nonzero exit outside "
                             "the gate), a multi-node family: "
                             "partition_heal, fork_reorg, sync_catchup, "
                             "equivocation_storm, or a validator-fleet "
                             "family: fleet_steady, fleet_partition, "
                             "fleet_crash, combined_chaos, fleet_capacity, "
                             "or mixed_duty (BLS + state-root + epoch "
                             "tenants on one device over the global "
                             "device ledger; nonzero exit unless per-chip "
                             "conservation, per-workload SLO blocks, a "
                             "contention incident and a bit-identical "
                             "rerun all hold) (default: smoke)")
    parser.add_argument("--smoke", action="store_true",
                        help="alone: run the ~5s CPU-only smoke scenario; "
                             "with --scenario: run that scenario shrunk to "
                             "smoke scale. Report lands in the gitignored "
                             "LOADGEN_SMOKE.json")
    parser.add_argument("--slots", type=int, default=None,
                        help="override the scenario's slot count")
    parser.add_argument("--validators", type=int, default=None,
                        help="override the scenario's validator count")
    parser.add_argument("--seed", type=int, default=None,
                        help="override the scenario's RNG seed")
    parser.add_argument("--flood-factor", type=float, default=None,
                        help="override the open-loop traffic multiplier")
    parser.add_argument("--out", default=None,
                        help="report path (default: LOADGEN_SMOKE.json for "
                             "smoke, loadgen_report.json otherwise, under "
                             "the repo root)")
    parser.add_argument("--quiet", action="store_true",
                        help="suppress per-slot progress on stderr")
    parser.add_argument("--datadir", default=None,
                        help="datadir for store-backed scenarios "
                             "(crash_restart); default: a fresh tmp dir")
    parser.add_argument("--mesh-devices", default=None,
                        help="comma list of chip counts (e.g. 1,8): run "
                             "the scenario once per count over the "
                             "mesh-sharded device harness, assert the "
                             "largest mesh out-serves the smallest, and "
                             "write each point as a source:loadtest "
                             "BENCH_MATRIX row")
    parser.add_argument("--bench-matrix", action="store_true",
                        help="snapshot this run's measured sets/s + p50 "
                             "into the BENCH_MATRIX schema (source: "
                             "loadtest); sweeps always do")
    parser.add_argument("--bench-root", default=None,
                        help="directory for the BENCH_MATRIX write "
                             "(default: the repo root)")
    parser.add_argument("--hash-backend", default=None,
                        choices=["host", "device", "hybrid"],
                        help="tree-hash backend the state_root scenario "
                             "re-roots through (default: "
                             "LIGHTHOUSE_TPU_HASH_BACKEND or host; other "
                             "scenarios ignore it)")
    parser.add_argument("--trace-out", default=None,
                        help="multi-node/fleet scenarios: merge every "
                             "node's span ring into ONE Perfetto trace "
                             "file — per-node process groups, cross-node "
                             "flow links from each publish span to its "
                             "remote import spans; mixed_duty: render the "
                             "device ledger's merged per-workload device "
                             "timeline (occupancy tracks + waiting "
                             "markers)")


def drive_from_args(args) -> int:
    mesh_devices = None
    if getattr(args, "mesh_devices", None):
        mesh_devices = [p for p in str(args.mesh_devices).split(",") if p]
    return drive(
        scenario=args.scenario, smoke=args.smoke, slots=args.slots,
        validators=args.validators, seed=args.seed,
        flood_factor=args.flood_factor, out=args.out, quiet=args.quiet,
        datadir=args.datadir, mesh_devices=mesh_devices,
        bench_matrix=args.bench_matrix, bench_root=args.bench_root,
        hash_backend=getattr(args, "hash_backend", None),
        trace_out=getattr(args, "trace_out", None),
    )
