"""Per-stage device-time attribution for the jaxbls verify pipeline.

The dispatch path runs four stages (prepare, hash-to-G2, pairs, pairing —
`crypto/jaxbls/backend.py`) asynchronously: the host enqueues all four and
blocks once, on the final result. Four stages, five jitted programs a
bucket on one chip, on every platform: stage 4 is the Miller loop
(`_stage_miller`, a program a bucket, over the accumulators
`pairing_ops.miller_lane_plan` gives it) and the final exponentiation
(`_stage_final_exp`, one program for every bucket), enqueued back to back
under the one stage name `pairing` — compiled as one program the wide Miller
scan takes twice its time (PERF.md S6, PR 32 and PR 35); the one program
`_stage_pairing` is what a mesh compiles. A profiler capture shows the
two apart (`jaxbls:pairing.miller`, `jaxbls:pairing.final_exp`, the
programs' own names); everything timed or counted here keeps the four
stage names. That is the right shape for throughput, and it leaves the device's own time as one number a
dispatch: the pipeline tracer (observability/trace.py) shows the host's
phases around it — `jaxbls:enqueue` with the stage scopes as children,
`jaxbls:device_wait` — and `jaxbls_dispatch_device_seconds{lane}` the time
from the first stage's enqueue (or the previous dispatch's end, whichever
is later) to the verdict being read; WHICH stage takes what share needs the attribution
below, or the device line of a profiler capture.

This module is the one owner of per-stage device timing:

  - `run_stage(attr, stage, fn, *args)` wraps every stage dispatch. In
    the default (attribution OFF) mode it only records the tracer's
    `jaxbls:<stage>` span: the host's time inside the jit call, and a
    `jax.profiler.TraceAnnotation` scope of that name — nanoseconds when
    no profiler session is active, and the stage shows up named in an
    `xprof`/Perfetto device capture when one is. Dispatch stays fully
    async.
  - With attribution ON (`bn --device-trace`, bench, the calibrator,
    `scripts/profile_components.py`, env
    `LIGHTHOUSE_TPU_DEVICE_ATTRIBUTION=1`), each stage dispatch is
    followed by an event-timed resolve (`jax.block_until_ready`), which
    SERIALIZES the pipeline — attribution is a diagnostic mode, not a
    serving mode. Each timed resolve lands in
    `jaxbls_stage_device_seconds{stage,n_sets,n_pks}`; the FIRST timed
    resolve of a (stage, bucket) in a process is classified as the
    stage's residual compile and lands in
    `jaxbls_stage_compile_seconds{stage,n_sets,n_pks}` instead (the same
    first-dispatch convention as the autotune profiler), giving the
    compile/execute split per padding bucket. The timed interval is a
    `device:<stage>` span of the dispatch's pipeline Trace (enqueue +
    resolve, the `jaxbls:<stage>` span its child), so the
    Chrome/Perfetto export renders host lanes AND a device lane per
    stage in one timeline (observability/trace.py routes `device:*`
    spans onto dedicated tracks).
  - When program analytics are also enabled (observability/perf.py),
    the first attributed dispatch per (stage, bucket) captures the
    compiled program's cost/memory analysis into the `xla_program_*`
    gauges, the autotune profile snapshot, and the bench artifacts.

Everything here is import-light: jax is imported lazily, so `bn perf
report` and the metrics lint run with no device attached.
"""

from __future__ import annotations

import os
import threading

from ..utils.metrics import REGISTRY
from . import perf as _perf
from . import trace as _trace

#: canonical jit-stage order of the multi-set verify pipeline
#: (`_get_stages` in crypto/jaxbls/backend.py)
STAGES = ("prepare", "h2c", "pairs", "pairing")

#: the KZG blob batch's first stage (validation + linear combinations,
#: `crypto/jaxbls/msm.kzg_lincomb_kernel`); its second is `pairing`
KZG_LINCOMB_STAGE = "kzg_lincomb"

#: Trace span-name prefix that routes a span onto a device lane in the
#: Chrome trace-event export (observability/trace.py)
DEVICE_SPAN_PREFIX = "device:"

# stage resolves span sub-ms (CPU toy buckets) to ~minutes (a cold
# residual compile folded into the first timed resolve)
_STAGE_BUCKETS = (
    0.0005, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0, 30.0, 60.0, 300.0,
)

STAGE_DEVICE_SECONDS = REGISTRY.histogram_vec(
    "jaxbls_stage_device_seconds",
    "attributed per-stage device wall time (dispatch -> event-timed "
    "resolve), by jit stage and padding bucket; steady-state resolves "
    "only — the first resolve per (stage, bucket) lands in "
    "jaxbls_stage_compile_seconds",
    ("stage", "n_sets", "n_pks"),
    buckets=_STAGE_BUCKETS,
)
STAGE_COMPILE_SECONDS = REGISTRY.gauge_vec(
    "jaxbls_stage_compile_seconds",
    "first attributed resolve per (stage, padding bucket): the stage's "
    "residual XLA compile + one execution (autotune first-dispatch "
    "convention)",
    ("stage", "n_sets", "n_pks"),
)

_lock = threading.Lock()
_seen: set = set()          # (stage, bucket) pairs that resolved timed once
_enabled_override: bool | None = None


def set_enabled(on: bool | None) -> None:
    """Force attribution on/off for this process (None = back to env)."""
    global _enabled_override
    _enabled_override = None if on is None else bool(on)


def enabled() -> bool:
    if _enabled_override is not None:
        return _enabled_override
    env = os.environ.get("LIGHTHOUSE_TPU_DEVICE_ATTRIBUTION", "").lower()
    return env in ("1", "on", "yes", "true")


class attributed:
    """`with device.attributed():` — attribution on for a scope (bench,
    scripts, tests); restores the previous override on exit."""

    def __enter__(self):
        global _enabled_override
        self._prev = _enabled_override
        _enabled_override = True
        return self

    def __exit__(self, *exc):
        global _enabled_override
        _enabled_override = self._prev
        return False


class DispatchAttribution:
    """Per-dispatch carrier: the padding bucket plus the pipeline Trace
    (if any) that device sub-spans should land in."""

    __slots__ = ("bucket", "trace")

    def __init__(self, bucket: tuple, trace=None):
        self.bucket = (int(bucket[0]), int(bucket[1]))
        self.trace = trace


def begin(bucket: tuple, trace=None) -> DispatchAttribution | None:
    """Attribution handle for one dispatch, or None when disabled (the
    hot-path default: stages stay async, only named annotation scopes)."""
    if not enabled():
        return None
    if trace is None:
        trace = _trace.current_trace()
    return DispatchAttribution(bucket, trace)


def run_stage(attr: DispatchAttribution | None, stage: str, fn, *args):
    """Dispatch one jit stage under the tracer's `jaxbls:<stage>` span;
    with an attribution handle, also event-time the resolve and record
    it, the whole a `device:<stage>` span of the dispatch's trace."""
    if attr is None:
        with _trace.span(f"jaxbls:{stage}"):
            return fn(*args)
    n, m = attr.bucket
    key = (stage, attr.bucket)
    with _lock:
        first = key not in _seen
        _seen.add(key)
    with _trace.span(
        f"{DEVICE_SPAN_PREFIX}{stage}", trace=attr.trace,
        phase="compile" if first else "execute",
    ) as sp:
        with _trace.span(f"jaxbls:{stage}", trace=attr.trace):
            out = fn(*args)
        try:
            import jax

            jax.block_until_ready(out)
        except ImportError:  # pragma: no cover - jax is baked into the image
            pass
    if first:
        # residual compile (whatever XLA work this stage still owed at
        # this bucket) — keep it out of the steady-state distribution
        STAGE_COMPILE_SECONDS.labels(stage, n, m).set(sp.t1 - sp.t0)
    else:
        STAGE_DEVICE_SECONDS.labels(stage, n, m).observe(sp.t1 - sp.t0)
    if _perf.analytics_enabled():
        _perf.maybe_capture_program(stage, fn, args, attr.bucket)
    return out


def reset_seen() -> None:
    """Forget compile/execute classification state (tests)."""
    with _lock:
        _seen.clear()


# --------------------------------------------------------------- snapshots


def snapshot_stages(device_kind: str | None = None) -> dict:
    """Per-bucket, per-stage timing summary from the attributed series,
    with roofline numbers where program analytics exist for the bucket.

    Shape: {"<n>x<m>": {stage: {count, mean_ms, total_s, compile_s?,
    roofline?}}} — the bench artifact and profile_components surface."""
    out: dict = {}
    for (stage, n, m), child in STAGE_DEVICE_SECONDS.children():
        if child.n == 0:
            continue
        mean_s = child.total / child.n
        entry = {
            "count": child.n,
            "mean_ms": round(mean_s * 1e3, 3),
            "total_s": round(child.total, 4),
        }
        stats = _perf.program_stats(stage, (int(n), int(m)))
        if stats is not None:
            rl = _perf.roofline(stats, mean_s, device_kind)
            if rl is not None:
                entry["roofline"] = rl
        out.setdefault(f"{n}x{m}", {})[stage] = entry
    for (stage, n, m), child in STAGE_COMPILE_SECONDS.children():
        if child.value:
            out.setdefault(f"{n}x{m}", {}).setdefault(stage, {})[
                "compile_s"
            ] = round(child.value, 6)
    return out


# ------------------------------------------------- standalone stage profiler


def profile_stages(
    n_sets: int, n_pks: int, reps: int = 3, seed: int = 7,
    analytics: bool = True,
) -> dict:
    """Time the four real stages standalone at one padding bucket:
    warm (first rep = residual compile), then `reps` timed resolves each,
    chaining real intermediates (prepare/h2c outputs feed pairs, pairs
    feeds pairing). THE stage-timing owner — scripts/profile_components.py
    is a thin CLI over this, and every observation also lands in the
    jaxbls_stage_* metric families and (with analytics) the xla_program_*
    gauges + autotune profile snapshot.

    Initializes the jax backend; only call where that is acceptable."""
    import numpy as np

    from ..crypto.jaxbls import backend as be
    from ..crypto.jaxbls import limbs as lb
    from ..parallel import get_mesh, put_pk_grid, put_sets

    # profile the programs the SERVING path runs: on a meshed process the
    # batch lane compiles the mesh-variant stages over mesh-padded
    # buckets with sharded placement — timing fresh unsharded variants at
    # those shapes would attribute cost to programs nothing executes
    mesh = get_mesh()
    prepare, h2c_stage, pairs_stage, pairing_stage = be._get_stages(mesh=mesh)
    n, m = be.padding_bucket(n_sets, n_pks, mesh=mesh)
    rng = np.random.default_rng(seed)

    def rl(shape):
        # random < 2^16 per limb, top limb zero: valid field-element range
        a = rng.integers(0, 1 << 16, size=shape + (lb.NL,), dtype=np.uint32)
        a[..., -1] = 0
        return a

    # host masters; per-batch inputs are RE-PLACED every rep because with
    # donation on (accelerator default) the stages CONSUME them — reusing
    # a donated array on rep 2 would raise 'Array has been deleted'. The
    # pubkey grids are never donated, so they place once (like the
    # serving path's device-resident pubkey cache).
    h_pk_x, h_pk_y = rl((n, m)), rl((n, m))
    h_sig_x, h_sig_y = rl((n, 2)), rl((n, 2))
    h_z = np.ones((n, be.Z_BITS), np.uint32)
    h_mask = np.ones((n,), np.uint32)
    h_us = rl((n, 2, 2))
    pk_x, pk_y = put_pk_grid(h_pk_x), put_pk_grid(h_pk_y)
    pk_mask = put_pk_grid(np.ones((n, m), np.uint32))

    prev_analytics = _perf.set_analytics(analytics)
    try:
        with attributed():
            for _ in range(reps + 1):  # +1: first rep eats residual compile
                sig_x, sig_y = put_sets(h_sig_x), put_sets(h_sig_y)
                z_digits, set_mask = put_sets(h_z), put_sets(h_mask)
                us = put_sets(h_us)
                attr = begin((n, m))
                z_pk, sig_acc, _bad = run_stage(
                    attr, "prepare", prepare,
                    pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask,
                )
                h_jac = run_stage(attr, "h2c", h2c_stage, us)
                pairs_out = run_stage(
                    attr, "pairs", pairs_stage, z_pk, h_jac, sig_acc, set_mask
                )
                run_stage(attr, "pairing", pairing_stage, *pairs_out)
    finally:
        _perf.set_analytics(prev_analytics)

    kind = None
    try:
        import jax

        devices = jax.devices()
        kind = devices[0].device_kind if devices else None
    except Exception:
        pass
    snap = snapshot_stages(device_kind=kind)
    return {
        "bucket": [n, m],
        "device_kind": kind,
        "reps": reps,
        "stages": snap.get(f"{n}x{m}", {}),
        "programs": _perf.program_snapshot().get(f"{n}x{m}", {}),
    }
