#!/usr/bin/env python
"""Headline benchmark + the full BASELINE.md measurement matrix on one chip.

Headline (stdout, ONE JSON line): BASELINE.md config 5, the "mainnet gossip
firehose" — batches of 64 attestation-style signature sets, each an
aggregate over 128 pubkeys with a distinct 32-byte message, verified by the
TPU backend (pipelined through the async submission API, every result
checked). vs_baseline compares against an ESTIMATED single-host blst
throughput for the same workload (~700 sets/s; the reference publishes no
absolute numbers and blst is not present in this image — SURVEY.md §6,
BASELINE.md). Every vs_* ratio in this file divides by an estimate, never
a measurement; the JSON labels say so.

Run design:
  - the bench needs a TPU: without one (and without LIGHTHOUSE_BENCH_SMOKE=1,
    the CPU dry run of the plumbing) it exits non-zero and prints no value;
  - ALL fixtures are persisted in bench_fixtures.npz (committed, built
    offline by scripts/gen_bench_fixtures.py) — zero fixture kernels
    compile before the verify pipeline warms;
  - the headline updates incrementally: after the warm batch (rate incl.
    compile), after one synchronous timed batch, then the pipelined
    measurement — a failure mid-run reports the latest landed number with
    the error in its note;
  - a negative control (tampered signature on the warmed bucket) guards
    against measuring a vacuous accept.

The rest of the matrix (BASELINE.md configs 1-4 + the p99 per-block verify
latency probe) is measured after the headline and written to
BENCH_MATRIX.json / stderr:
  1. fast_aggregate_verify, single 128-pubkey attestation (urgent-path
     latency: p50/p99 over repeated single-set verifies, depth 1)
  2. full-block multi-set: 1 proposal + 1 RANDAO + 128 DISTINCT
     attestations(128 pk) + 1 sync aggregate(512 pk) in ONE batch;
     p50/p99 block verify latency
  3. Altair sync-committee aggregate: 1 set x 512 pubkeys
  4. Deneb KZG batch blob-proof verify (6 blobs, 4096-element setup) on the
     shared device pairing kernel + device MSM
  5. the headline above
"""

import json
import os
import sys
import time

# LIGHTHOUSE_BENCH_SMOKE=1 loads the tiny fixture variant and shrinks every
# config: a CPU dry-run of all code paths (fixture loader, matrix, JSON
# plumbing) so chip time is never spent discovering a Python-level bug.
_SMOKE = os.environ.get("LIGHTHOUSE_BENCH_SMOKE") == "1"

BATCHES = 2 if _SMOKE else 8   # timed batches (headline)
DEPTH = 2 if _SMOKE else 4     # max batches in flight
FULL_BLOCK_REPS = 2 if _SMOKE else 8
LAT_REPS = 4 if _SMOKE else 30

# Estimated single-host blst throughputs (one modern core, see BASELINE.md:
# the reference publishes no absolute numbers). Derivations:
#   firehose set (128-pk aggregate + hash-to-curve + share of multi-pairing)
#     ~1.4ms -> ~700 sets/s
#   single fast_aggregate_verify: same work without batch amortization of
#     the final exp: ~2ms -> 500/s
#   full block (131 sets incl. 512-pk sync aggregate): ~1.4ms * 131 + final
#     exp ~ 190ms -> ~5.3 blocks/s
#   sync aggregate alone (512-pk aggregation + 2 pairings): ~2.5ms -> 400/s
#   c-kzg verify_blob_kzg_proof_batch: ~2.5ms/blob -> 400 blobs/s
EST_BLST_SETS_PER_SEC = 700.0
EST_BLST_SINGLE_FAV_PER_SEC = 500.0
EST_BLST_BLOCKS_PER_SEC = 5.3
EST_BLST_SYNC_AGG_PER_SEC = 400.0
EST_CKZG_BLOBS_PER_SEC = 400.0

BUDGET_SECS = 40 * 60  # matrix configs are skipped once this is spent
_T0 = time.time()
_HEADLINE = {"value": 0.0, "note": "not reached", "shape": (64, 128)}
_MATRIX: dict = {}
_ROOT = os.path.dirname(os.path.abspath(__file__))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _elapsed():
    return time.time() - _T0


def _remaining():
    return BUDGET_SECS - _elapsed()


def _headline_json():
    v = _HEADLINE["value"]
    n_sets, n_pks = _HEADLINE["shape"]
    metric = (
        f"BLS signature-sets verified/sec ({n_sets} sets x {n_pks} pubkeys, "
        f"TPU backend, pipelined depth {DEPTH}; baseline is an ESTIMATED "
        f"blst throughput)"
    )
    if _HEADLINE["note"]:
        metric += f" [{_HEADLINE['note']}]"
    out = {
        "metric": metric,
        "value": round(v, 2),
        "unit": "sets/s",
        "vs_baseline": round(v / EST_BLST_SETS_PER_SEC, 3),
    }
    # executor configuration + the config1 latency series: BENCH_r*.json
    # carries these so `bn perf report` / perf_trend.py can trend the
    # urgent-path p50 (a latency regression gates CI like a throughput
    # drop) and depth/donation next to every headline
    if _MATRIX.get("pipeline"):
        out["pipeline"] = _MATRIX["pipeline"]
    c1 = _MATRIX.get("config1_single_fast_aggregate_verify") or {}
    if c1.get("p50_ms"):
        out["config1_p50_ms"] = c1["p50_ms"]
    return json.dumps(out)


def _set_headline(value, note):
    _HEADLINE["value"] = value
    _HEADLINE["note"] = note
    log(f"  headline -> {value:.1f} sets/s ({note or 'final'})")


def _write_matrix():
    try:
        # compiled-program analytics captured during the run (flops /
        # bytes accessed / HBM regions per jit stage per padding bucket —
        # observability/perf.py); best-effort, absent when nothing compiled
        from lighthouse_tpu.observability import perf as _obs_perf

        programs = _obs_perf.program_snapshot()
        if programs:
            _MATRIX["xla_programs"] = programs
    except Exception as e:  # pragma: no cover - best effort
        log(f"program analytics snapshot failed: {e}")
    try:
        _MATRIX["elapsed_secs"] = round(_elapsed(), 1)
        _MATRIX["baseline_note"] = (
            "all vs_est_* ratios divide by ESTIMATED single-core blst/c-kzg "
            "throughputs (EST_* constants in bench.py) — not measurements"
        )
        # smoke/dry runs must never clobber the on-chip artifact of record
        name = "BENCH_MATRIX_SMOKE.json" if _SMOKE else "BENCH_MATRIX.json"
        with open(os.path.join(_ROOT, name), "w") as f:
            json.dump(_MATRIX, f, indent=1)
    except Exception as e:  # pragma: no cover - best effort
        log(f"matrix write failed: {e}")


_DEVICE_KEY: dict = {}  # captured eagerly once jax.devices() succeeds


def _write_autotune_profile():
    """Every dispatch above already landed in the autotune profiler (the
    jaxbls VerifyHandle hook), so the bench doubles as a calibration run:
    snapshot the per-bucket timings in device-profile format. Smoke runs
    write the gitignored *_SMOKE variant — same rule as the matrix — and
    never the per-device canonical path (an on-chip profile must not be
    overwritten by a CPU dry-run).

    Uses the device key main() captured right after jax.devices()
    succeeded — no key yet means skip."""
    if not _DEVICE_KEY:
        return
    try:
        from lighthouse_tpu.autotune import profile as ap
        from lighthouse_tpu.autotune import profiler as apf

        prof = apf.build_profile(
            _DEVICE_KEY,
            source="bench-smoke" if _SMOKE else "bench",
        )
        if not prof.buckets:
            return
        name = "AUTOTUNE_PROFILE_SMOKE.json" if _SMOKE else "AUTOTUNE_PROFILE.json"
        path = ap.save(prof, os.path.join(_ROOT, name))
        _MATRIX["autotune_profile"] = name
        log(f"autotune profile ({len(prof.buckets)} buckets) -> {path}")
    except Exception as e:  # pragma: no cover - best effort
        log(f"autotune profile write failed: {e}")


# ----------------------------------------------------------------- fixtures


def _load_fixtures():
    """Rebuild SignatureSets (+ the KZG fixture) from the committed npz —
    no device work, no compiles, ~a second of host int conversion. The
    npz wire-format decoders are shared with the autotune calibrator
    (lighthouse_tpu/autotune/calibrate.py), the other consumer of these
    fixture files."""
    from lighthouse_tpu.autotune.calibrate import load_fixture_groups

    name = "bench_fixtures_smoke.npz" if _SMOKE else "bench_fixtures.npz"
    path = os.path.join(_ROOT, name)

    t0 = time.time()
    fx = load_fixture_groups(path, include_small=True, include_kzg=True)
    meta = fx["meta"]
    log(f"fixtures loaded from {name} in {time.time()-t0:.1f}s "
        f"({meta['n_att']} att sets x {meta['n_pks']} pks)")
    return fx


def _rands(rng, n):
    return [1] + [rng.getrandbits(64) | 1 for _ in range(n - 1)]


def _warm_batch(backend, sets, rands):
    """First verify at the bucket (pays the compile). Returns its wall time."""
    t0 = time.time()
    ok = backend.verify_signature_sets(sets, rands)
    dt = time.time() - t0
    log(f"  warmup/compile: {dt:.1f}s ok={ok}")
    assert ok, "warm batch failed to verify"
    return dt


def _latency_stats(samples):
    xs = sorted(samples)
    n = len(xs)
    return {
        "p50_ms": round(xs[n // 2] * 1e3, 2),
        "p99_ms": round(xs[min(n - 1, int(n * 0.99))] * 1e3, 2),
        "mean_ms": round(sum(xs) / n * 1e3, 2),
        "n": n,
    }


# ----------------------------------------------------------------- configs


def run_headline(backend, fx, rng):
    from lighthouse_tpu.crypto import bls

    n_att, n_pks = fx["meta"]["n_att"], fx["meta"]["n_pks"]
    # batch the full fixture width: per-batch wall time is nearly batch-
    # size-invariant (one fq12_sqr per x-bit and one final exp per BATCH,
    # sequential chains are in bits not sets), so throughput scales with
    # width — measured on the v5e: 64->100, 128->187, 256->249, 512->308
    # sets/s (docs/PERF_NOTES.md batch-size scaling)
    n_sets = n_att
    _HEADLINE["shape"] = (n_sets, n_pks)
    log(f"[config 5] gossip firehose {n_sets}x{n_pks}")
    sets = fx["att"][:n_sets]
    rands = _rands(rng, n_sets)

    warm_dt = _warm_batch(backend, sets, rands)
    # first landed number: pessimistic (includes the compile) but nonzero —
    # a failure after this point no longer reports 0.0
    _set_headline(n_sets / warm_dt, "warm batch only, incl. compile")

    # negative control on the warmed bucket: swapped signature must reject
    bad = list(sets)
    bad[1] = bls.SignatureSet(sets[0].signature, sets[1].signing_keys, sets[1].message)
    assert not backend.verify_signature_sets(bad, rands), (
        "negative control FAILED: tampered batch verified"
    )
    log("  negative control: tampered batch rejected")

    # one synchronous timed batch -> provisional steady-state rate
    t0 = time.time()
    assert backend.verify_signature_sets(sets, rands)
    dt1 = time.time() - t0
    _set_headline(n_sets / dt1, "single steady-state batch")

    # the real measurement: pipelined batches, every result checked
    t0 = time.time()
    inflight = []
    for i in range(BATCHES):
        inflight.append(backend.verify_signature_sets_async(sets, rands))
        if len(inflight) >= DEPTH:
            assert inflight.pop(0).result()
    while inflight:
        assert inflight.pop(0).result()
    dt = time.time() - t0
    sets_per_sec = n_sets * BATCHES / dt
    log(f"  {BATCHES} batches in {dt:.2f}s (depth {DEPTH}) -> {sets_per_sec:.1f} sets/s")
    _set_headline(sets_per_sec, "")
    _MATRIX["config5_firehose"] = {
        "sets_per_sec": round(sets_per_sec, 2),
        "single_batch_sets_per_sec": round(n_sets / dt1, 2),
        "warm_batch_secs": round(warm_dt, 1),
        "vs_est_blst": round(sets_per_sec / EST_BLST_SETS_PER_SEC, 3),
    }
    return sets, rands


def run_single_fav(backend, fx, rng):
    """Config 1 + urgent-path latency: one 128-pk set through the jaxbls
    urgent fast lane (bypasses the pipelined batch window — the exact
    path a gossip block's proposer signature takes on a loaded node).
    Target: p50 under one slot-fraction (<100 ms)."""
    n_pks = fx["meta"]["n_pks"]
    submit = getattr(backend, "verify_signature_sets_urgent", None)
    lane = "urgent" if submit is not None else "batch"
    submit = submit or backend.verify_signature_sets
    log(f"[config 1] single fast_aggregate_verify ({n_pks} pks), "
        f"{lane} lane")
    one = [fx["att"][0]]
    rands = [1]
    assert submit(one, rands)  # compile bucket
    samples = []
    for _ in range(LAT_REPS):
        t0 = time.time()
        assert submit(one, rands)
        samples.append(time.time() - t0)
    st = _latency_stats(samples)
    per_sec = 1.0 / (st["mean_ms"] / 1e3)
    log(f"  {st}")
    _MATRIX["config1_single_fast_aggregate_verify"] = {
        **st,
        "lane": lane,
        "verifies_per_sec": round(per_sec, 2),
        "vs_est_blst": round(per_sec / EST_BLST_SINGLE_FAV_PER_SEC, 3),
    }


def run_sync_aggregate(backend, fx, rng):
    log("[config 3] sync-committee aggregate "
        f"({fx['meta']['sync_pks']} pks)")
    sets = fx["sync"]
    rands = [1]
    assert backend.verify_signature_sets(sets, rands)
    samples = []
    for _ in range(max(4, LAT_REPS // 3)):
        t0 = time.time()
        assert backend.verify_signature_sets(sets, rands)
        samples.append(time.time() - t0)
    st = _latency_stats(samples)
    per_sec = 1.0 / (st["mean_ms"] / 1e3)
    log(f"  {st}")
    _MATRIX["config3_sync_aggregate_512"] = {
        **st,
        "verifies_per_sec": round(per_sec, 2),
        "vs_est_blst": round(per_sec / EST_BLST_SYNC_AGG_PER_SEC, 3),
    }


def run_full_block(backend, fx, rng):
    """Config 2 + p99 per-block verify latency: proposer + RANDAO + 128
    DISTINCT attestations + sync aggregate as ONE multi-set batch (the r4
    fixture double-counted 64 sets twice; these are 128 independent key
    groups with distinct messages — scripts/gen_bench_fixtures.py)."""
    log("[config 2] full-block multi-set + p99 block latency")
    # a full block carries 128 attestations — always the FIRST 128 fixture
    # sets, independent of how wide the headline fixture is
    assert _SMOKE or len(fx["att"]) >= 128, (
        "config 2 needs >= 128 fixture sets (gen_bench_fixtures --n-att)"
    )
    block_sets = fx["small"] + fx["att"][:128] + fx["sync"]
    rands = _rands(rng, len(block_sets))
    assert backend.verify_signature_sets(block_sets, rands)
    samples = []
    for _ in range(FULL_BLOCK_REPS):
        t0 = time.time()
        assert backend.verify_signature_sets(block_sets, rands)
        samples.append(time.time() - t0)
    st = _latency_stats(samples)
    per_sec = 1.0 / (st["mean_ms"] / 1e3)
    log(f"  {st} ({len(block_sets)} sets)")
    _MATRIX["config2_full_block_verify"] = {
        **st,
        "sets_in_block": len(block_sets),
        "blocks_per_sec": round(per_sec, 2),
        "vs_est_blst": round(per_sec / EST_BLST_BLOCKS_PER_SEC, 3),
    }


def run_stage_attribution(backend, fx, rng):
    """Per-stage device attribution on the warmed headline bucket: two
    attributed verifies (first timed resolve per stage classifies as the
    stage's residual compile, the second as steady state), written as
    stage -> {mean_ms, compile_s, roofline} so "0.143x est blst"
    decomposes into per-stage utilization (observability/device.py)."""
    from lighthouse_tpu.observability import device as obs_dev

    log("[stage attribution] per-stage device seconds on the warmed bucket")
    # full fixture width: the SAME padding bucket the headline warmed —
    # a narrower batch would cold-compile a second bucket
    sets = fx["att"]
    rands = _rands(rng, len(sets))
    with obs_dev.attributed():
        assert backend.verify_signature_sets(sets, rands)
        assert backend.verify_signature_sets(sets, rands)
    snap = obs_dev.snapshot_stages(
        device_kind=_DEVICE_KEY.get("device_kind")
    )
    if snap:
        _MATRIX["stage_attribution"] = snap
        for bucket, stages in snap.items():
            for stage, st in stages.items():
                log(f"  {bucket} {stage}: {st.get('mean_ms', '—')} ms "
                    f"(compile {st.get('compile_s', 0.0)}s)")


def run_kzg(fx):
    log("[config 4] KZG batch blob-proof verify")
    from lighthouse_tpu.crypto import kzg

    k = fx["kzg"]
    n = len(k["g1_lagrange"])
    setup = kzg.TrustedSetup(
        g1_lagrange=k["g1_lagrange"],
        g2_monomial=k["g2_monomial"],
        roots=kzg._fr_roots_of_unity(n),
    )
    blobs, cbs, pbs = k["blobs"], k["commitments"], k["proofs"]
    n_blobs = len(blobs)

    assert kzg.verify_blob_kzg_proof_batch(blobs, cbs, pbs, setup)
    # negative control: a bit-flipped blob must reject
    bad = [bytes([blobs[0][0] ^ 1]) + blobs[0][1:]] + list(blobs[1:])
    assert not kzg.verify_blob_kzg_proof_batch(bad, cbs, pbs, setup), (
        "KZG negative control FAILED"
    )
    samples = []
    for _ in range(3 if _SMOKE else 5):
        t0 = time.time()
        assert kzg.verify_blob_kzg_proof_batch(blobs, cbs, pbs, setup)
        samples.append(time.time() - t0)
    st = _latency_stats(samples)
    blobs_per_sec = float(n_blobs) / (st["mean_ms"] / 1e3)
    log(f"  {st} -> {blobs_per_sec:.1f} blobs/s")
    _MATRIX["config4_kzg_batch_verify"] = {
        **st,
        "blobs": n_blobs,
        "blobs_per_sec": round(blobs_per_sec, 2),
        "vs_est_ckzg": round(blobs_per_sec / EST_CKZG_BLOBS_PER_SEC, 3),
    }


def main():
    import jax

    if _SMOKE:
        # smoke mode dry-runs the whole bench on CPU, whatever platform
        # the environment selects
        jax.config.update("jax_platforms", "cpu")
    from lighthouse_tpu.utils.jaxcfg import setup_compilation_cache

    setup_compilation_cache()
    import random

    devices = jax.devices()  # no backend at all: raises, exit non-zero
    if not _SMOKE and devices[0].platform != "tpu":
        # a device metric measured on another platform is not that metric:
        # no value is printed under its name
        log(f"bench.py needs a TPU; jax.devices() = {devices}. "
            f"LIGHTHOUSE_BENCH_SMOKE=1 dry-runs the plumbing on CPU.")
        sys.exit(1)

    log(f"devices: {devices}")
    _MATRIX["devices"] = str(devices)
    try:
        # the serving topology: batch dispatches shard over this mesh
        # (parallel/mesh.py), so the matrix must say what topology its
        # numbers were measured on — the same key autotune profiles carry
        from lighthouse_tpu.parallel import get_mesh, mesh_shape_key

        mesh = get_mesh()
        _MATRIX["mesh"] = {
            "shape": mesh_shape_key(mesh),
            "devices": int(mesh.devices.size) if mesh is not None else 1,
        }
        log(f"mesh: {_MATRIX['mesh']}")
    except Exception as e:
        log(f"mesh resolution failed (serving single-chip): {e}")
    try:
        from lighthouse_tpu.autotune.profile import current_device_key

        _DEVICE_KEY.update(current_device_key())
    except Exception as e:
        log(f"autotune device key capture failed: {e}")
    from lighthouse_tpu.crypto.bls import api as bls_api

    # capture compiled-program cost/memory analytics for every bucket the
    # run compiles (rides the XLA compile cache: re-trace, never re-compile)
    from lighthouse_tpu.observability import perf as _obs_perf

    _obs_perf.set_analytics(True)

    backend = bls_api.set_backend("jax")
    rng = random.Random(0xBE7C)

    # pipelined-executor configuration of THIS run, recorded in the
    # artifact so `bn perf report` trends depth/donation/MSM-window next
    # to the numbers they produced. The headline loop drives the measured
    # depth; smoke stays shallow (DEPTH=2) regardless of resolution.
    global DEPTH
    from lighthouse_tpu.crypto.jaxbls import pipeline as _pl
    from lighthouse_tpu.crypto.jaxbls.msm import msm_window as _msm_window

    depth, depth_src = _pl.resolve_depth()
    if not _SMOKE:
        DEPTH = depth
    donate, donate_src = _pl.donation_enabled()
    w = _msm_window()
    _MATRIX["pipeline"] = {
        "depth": DEPTH,
        "depth_source": depth_src,
        "donated_inputs": bool(donate),
        "donation_source": donate_src,
        "msm_window": w if w else "bits",
    }
    log(f"pipeline config: depth {DEPTH} ({depth_src}), "
        f"donation {'on' if donate else 'off'} ({donate_src}), "
        f"msm window {w or 'bits'}")

    try:
        try:
            fx = _load_fixtures()   # host-only, but any failure must still
                                    # emit the headline JSON (finally below)
        except Exception as e:
            _HEADLINE["note"] = f"fixture load FAILED: {type(e).__name__}: {e}"
            log(_HEADLINE["note"])
            return
        try:
            run_headline(backend, fx, rng)
        except Exception as e:
            # keep whatever headline already landed (warm batch / single
            # batch) — a device error mid-measurement is a note on the
            # landed number, not a zero
            _HEADLINE["note"] = (
                (_HEADLINE["note"] or "")
                + f"; died mid-run: {type(e).__name__}: {e}"
            ).lstrip("; ")
            log(f"[headline] FAILED: {type(e).__name__}: {e}")
            _MATRIX["config5_error"] = f"{type(e).__name__}: {e}"

        def attempt(name, need_secs, fn):
            """Best-effort matrix config under the time budget."""
            if _remaining() < need_secs:
                log(f"[{name}] skipped: {int(_remaining())}s left < {need_secs}s budget")
                _MATRIX[f"{name}_skipped"] = "time budget"
                return
            try:
                fn()
            except Exception as e:
                log(f"[{name}] FAILED: {type(e).__name__}: {e}")
                _MATRIX[f"{name}_error"] = f"{type(e).__name__}: {e}"

        attempt("stage_attr", 240,
                lambda: run_stage_attribution(backend, fx, rng))
        attempt("config1", 300, lambda: run_single_fav(backend, fx, rng))
        attempt("config3", 420, lambda: run_sync_aggregate(backend, fx, rng))
        attempt("config2", 600, lambda: run_full_block(backend, fx, rng))
        attempt("config4", 600, lambda: run_kzg(fx))
    finally:
        _write_autotune_profile()
        _write_matrix()
        print(_headline_json(), flush=True)


if __name__ == "__main__":
    main()
