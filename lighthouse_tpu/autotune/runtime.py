"""Process-global autotune state: the installed profile/plan + warmup.

`install_profile` makes a profile (and its derived Plan) the process-wide
source of serving knobs; `active_plan` is what the consumers —
BeaconProcessorConfig's default caps and HybridBackend's knob resolution —
consult. With nothing installed both fall back to their historical
hard-coded defaults, byte-identical to the pre-autotune behavior.

`autoload` restores a persisted profile for the current device at node
bring-up. Device identity requires `jax.devices()`, which can block for
minutes on a device that does not answer (the exact failure hybrid.py's probe
exists for), so detection runs in a daemon thread with a bounded wait —
a node started during a device outage just serves on defaults.

`start_warmup` is the node-side consumer of the plan's warmup buckets: a
daemon thread that precompiles each planned (n_sets, n_pks) shape through
jaxbls `warm_stages` so the first real batches skip the multi-minute cold
compile. Before this existed `warm_stages` was dead code from the node's
perspective (only bench/tests called it).
"""

from __future__ import annotations

import os
import threading
import weakref

from ..utils.logging import get_logger
from .planner import DEFAULT_WARMUP_BUCKETS, Plan, plan_from_profile
from .profile import BACKEND_REVISION, DeviceProfile

_lock = threading.Lock()
_state: dict = {"profile": None, "plan": None}
# plan-change listeners (weak refs — consumers are long-lived singletons
# on the live node, but tests construct many HybridBackends and a dead
# listener must not pin one). Called OUTSIDE _lock with the new Plan (or
# None on clear) so a listener may read active_plan()/take its own locks.
_listeners: list = []


def add_plan_listener(fn) -> None:
    """Register `fn(plan_or_none)` to run whenever a profile is installed
    or cleared at runtime — the mechanism consumers (the hybrid router's
    budgets, the jaxbls dispatcher's depth) use to re-resolve
    profile-derived knobs WITHOUT a restart. Bound methods are held via
    WeakMethod: a garbage-collected owner silently unsubscribes."""
    try:
        ref = weakref.WeakMethod(fn)
    except TypeError:
        ref = weakref.ref(fn)
    with _lock:
        _listeners.append(ref)


def _notify_listeners(plan) -> None:
    with _lock:
        refs = list(_listeners)
    for ref in refs:
        fn = ref()
        if fn is None:
            with _lock:
                try:
                    _listeners.remove(ref)
                except ValueError:
                    pass
            continue
        try:
            fn(plan)
        except Exception as e:  # a listener must never break install
            get_logger("autotune").warn(
                "plan listener failed", error=f"{type(e).__name__}: {e}"
            )


def _record_refusal(reason: str, profile: DeviceProfile, path, **fields):
    """Profile refusals are bring-up facts an incident dump should carry
    (an operator wondering why the node serves on defaults reads the
    flight recorder, not the startup scroll)."""
    try:
        from ..observability.flight_recorder import RECORDER

        RECORDER.record(
            "autotune_profile_refused", severity="warn", reason=reason,
            path=str(path or ""), **fields,
        )
    except Exception:
        pass  # diagnostics must never break install


def install_profile(profile: DeviceProfile, path: str | None = None,
                    allow_stale: bool = False,
                    live_mesh_shape: str | None = None) -> Plan | None:
    """Make `profile` the process-wide knob source; returns its Plan.

    A STALE profile — measured under a different jaxbls BACKEND_REVISION,
    i.e. on kernels that no longer exist — is refused (returns None, the
    consumers keep their current knobs): budgets and caps derived from a
    dead kernel structure misroute the live one. The same contract covers
    TOPOLOGY: when the caller knows the live mesh shape (`live_mesh_shape`
    — autoload passes the detected key's, parallel.mesh_shape_key format),
    a profile calibrated on a different topology is refused too — its
    padding buckets, per-chip caps and collective budgets describe a mesh
    this process is not serving on. `allow_stale=True` is the explicit
    operator override (`--autotune-profile PATH` names a file on
    purpose) for BOTH refusals; the rejection is still logged loudly and
    lands in the flight recorder either way."""
    if profile.is_stale():
        log = get_logger("autotune")
        if not allow_stale:
            log.warn(
                "STALE autotune profile refused (backend revision "
                "mismatch); run `autotune calibrate` on this build",
                profile_revision=str(profile.key.get("backend_revision")),
                current_revision=BACKEND_REVISION,
                path=path or "",
            )
            _record_refusal(
                "stale_revision", profile, path,
                profile_revision=str(profile.key.get("backend_revision")),
                current_revision=BACKEND_REVISION,
            )
            return None
        log.warn(
            "installing STALE autotune profile (operator override); its "
            "numbers were measured on a different kernel structure",
            profile_revision=str(profile.key.get("backend_revision")),
            current_revision=BACKEND_REVISION,
        )
    if profile.mesh_mismatch(live_mesh_shape):
        log = get_logger("autotune")
        if not allow_stale:
            log.warn(
                "autotune profile refused (mesh topology mismatch); run "
                "`autotune calibrate` on this topology",
                profile_mesh=str(profile.mesh_shape),
                live_mesh=str(live_mesh_shape),
                path=path or "",
            )
            _record_refusal(
                "mesh_mismatch", profile, path,
                profile_mesh=str(profile.mesh_shape),
                live_mesh=str(live_mesh_shape),
            )
            return None
        log.warn(
            "installing MESH-MISMATCHED autotune profile (operator "
            "override); its buckets/budgets were measured on a different "
            "topology",
            profile_mesh=str(profile.mesh_shape),
            live_mesh=str(live_mesh_shape),
        )
    plan = plan_from_profile(profile)
    measured_backend = profile.key.get("bls_backend")
    if measured_backend not in (None, "jax"):
        # e.g. a gitignored CPU smoke profile pinned via --autotune-profile:
        # install it (the operator asked), but say loudly that its numbers
        # were not measured on the device path the node will serve with
        get_logger("autotune").warn(
            "installed profile was measured on a non-device backend; its "
            "derived knobs may not fit the jax serving path",
            measured_backend=measured_backend,
        )
    with _lock:
        _state["profile"] = profile
        _state["plan"] = plan
    get_logger("autotune").info(
        "autotune profile installed",
        source=plan.source,
        path=path or "",
        max_attestation_batch=plan.max_attestation_batch,
        max_aggregate_batch=plan.max_aggregate_batch,
        p99_budget_ms=plan.p99_budget_ms,
        urgent_max_sets=plan.urgent_max_sets,
        pipeline_depth=plan.pipeline_depth,
        msm_window=plan.msm_window,
        warmup_buckets=str(list(plan.warmup_buckets)),
    )
    _notify_listeners(plan)
    return plan


def install_runtime_plan(plan: Plan) -> Plan:
    """Make a RUNTIME-derived plan (the capacity scheduler's retunes,
    chain/scheduler.py) the process-wide knob source and notify the plan
    listeners — the same actuation path a profile install uses, so the
    hybrid router, the jaxbls dispatcher and the processor's max_inflight
    listener all pick the change up live with their env/CLI precedence
    layers untouched. The installed PROFILE is untouched: a later real
    `install_profile` replaces this plan wholesale (and the scheduler
    re-bases from it via its own listener). The plan's `source` should
    name the producer (the scheduler uses "scheduler:<n>") so consumers
    and logs can tell a control-loop retune from a calibration."""
    with _lock:
        _state["plan"] = plan
    _notify_listeners(plan)
    return plan


def active_plan() -> Plan | None:
    with _lock:
        return _state["plan"]


def active_profile() -> DeviceProfile | None:
    with _lock:
        return _state["profile"]


def clear() -> None:
    """Uninstall (tests): consumers return to the hard-coded defaults."""
    with _lock:
        _state["profile"] = None
        _state["plan"] = None
    _notify_listeners(None)


# ---------------------------------------------------------------- autoload


def detect_device_key(wait_secs: float = 5.0) -> dict | None:
    """Resolve the current device key in a daemon thread bounded by
    `wait_secs` (jax.devices() can block for minutes on a device that
    does not answer). Returns None on timeout or any detection failure."""
    from . import profile as prof

    result: list = []
    done = threading.Event()

    def detect():
        try:
            result.append(prof.current_device_key())
        except Exception as e:  # no device / import failure
            result.append(e)
        done.set()

    threading.Thread(target=detect, daemon=True,
                     name="autotune-device-detect").start()
    if not done.wait(wait_secs):
        return None
    if not result or isinstance(result[0], Exception):
        return None
    return result[0]


def autoload(wait_secs: float | None = None,
             path: str | None = None) -> Plan | None:
    """Load + install a persisted profile for the current device, if any.

    Resolution order: LIGHTHOUSE_TPU_AUTOTUNE=0 disables everything; an
    explicit `path` (or LIGHTHOUSE_TPU_AUTOTUNE_PROFILE) is loaded without
    device detection; otherwise the device key is resolved in a daemon
    thread bounded by `wait_secs` (LIGHTHOUSE_TPU_AUTOTUNE_WAIT_SECS,
    default 5 s) and the canonical per-device file is tried. Returns the
    installed Plan, or None (no profile / disabled / detection timeout) —
    never raises, never blocks unboundedly."""
    log = get_logger("autotune")
    if os.environ.get("LIGHTHOUSE_TPU_AUTOTUNE", "1") in ("0", "off", "no"):
        return None
    from . import profile as prof

    if wait_secs is None:
        try:
            wait_secs = float(
                os.environ.get("LIGHTHOUSE_TPU_AUTOTUNE_WAIT_SECS", 5.0)
            )
        except ValueError:
            wait_secs = 5.0

    path = path or os.environ.get("LIGHTHOUSE_TPU_AUTOTUNE_PROFILE")
    if path:
        try:
            loaded = prof.load(path)
            # an explicitly named profile is an operator override: a
            # stale revision or mesh mismatch installs WITH a loud
            # warning instead of being refused (the canonical-path
            # branch below stays strict — its filename embeds the
            # revision AND the topology). The mismatch warning still
            # needs the LIVE topology: detect it with the same bounded
            # wait (detection failure -> None -> unknowable, no check —
            # the override installs either way, so this never blocks a
            # device-outage start beyond wait_secs).
            live = None
            if loaded.mesh_shape is not None:
                key = detect_device_key(wait_secs)
                live = key.get("mesh_shape") if key else None
            return install_profile(loaded, path=path, allow_stale=True,
                                   live_mesh_shape=live)
        except Exception as e:
            log.warn("autotune profile load failed; serving on defaults",
                     path=path, error=f"{type(e).__name__}: {e}")
            return None

    key = detect_device_key(wait_secs)
    if key is None:
        log.warn("autotune device detection failed or timed out; serving "
                 "on defaults", wait_secs=wait_secs)
        return None
    candidate = prof.default_path(key)
    if not os.path.isfile(candidate):
        log.info("no autotune profile for this device; serving on defaults",
                 expected_path=candidate)
        return None
    try:
        # belt and braces: the canonical filename embeds the topology, but
        # the key INSIDE the file is what install checks against the
        # detected live mesh (a renamed/copied file must still be refused)
        return install_profile(prof.load(candidate), path=candidate,
                               live_mesh_shape=key.get("mesh_shape"))
    except Exception as e:
        log.warn("autotune profile load failed; serving on defaults",
                 path=candidate, error=f"{type(e).__name__}: {e}")
        return None


# ----------------------------------------------------------------- warmup


def warmup_buckets() -> tuple:
    """The active plan's warmup buckets, or the default pair."""
    plan = active_plan()
    return plan.warmup_buckets if plan is not None else DEFAULT_WARMUP_BUCKETS


def start_warmup(buckets=None, warm_fn=None,
                 supervisor=None) -> threading.Thread:
    """Precompile the warmup buckets in a background daemon thread.

    Called from node bring-up (cli.cmd_bn) when the device-backed BLS
    backends are selected. On the hybrid backend the buckets warm through
    `HybridBackend.warm_bucket` — a full-pipeline dummy verify that also
    marks the bucket warm for ROUTING (its own probe bounds the device
    wait); on the plain jax backend they warm through jaxbls
    `warm_stages` after confirming a device is reachable (jax.devices()
    — safe to block HERE, it is a daemon thread). Compile times land in
    the profiler either way. Any failure degrades to cold-compile-on-
    first-dispatch, never to a crashed node."""
    log = get_logger("autotune")
    plan_buckets = tuple(buckets) if buckets is not None else warmup_buckets()

    def attempt():
        # raises on failure — the CALLER owns the retry policy (see below)
        single_chip_too = False
        backend = None
        if warm_fn is not None:
            fn = warm_fn
        else:
            from ..crypto.bls import api as bls_api

            backend = bls_api.get_backend()
            if hasattr(backend, "warm_bucket"):
                # hybrid: full-pipeline warm — small buckets ride the
                # urgent lane inside the router, so the single-chip
                # variant warms by construction
                fn = backend.warm_bucket
            else:
                import jax

                jax.devices()  # may block on a dead device: daemon thread
                from ..crypto.jaxbls.backend import warm_stages as fn
                from ..parallel import get_mesh

                # only a MESHED node has a distinct single-chip urgent
                # variant; warming it twice on one device would just skew
                # the profiler's compile stats with a duplicate ~0s entry
                single_chip_too = get_mesh() is not None
        import time as _time

        plan = active_plan()
        urgent_max = plan.urgent_max_sets if plan is not None else 4
        for n_sets, n_pks in plan_buckets:
            t0 = _time.time()
            ok = fn(n_sets, n_pks)
            if ok is False:  # warm_bucket: device down/failed (None =
                log.warn(    # warm_stages, which raises on failure)
                    "warmup bucket skipped (device unavailable or "
                    "warm failed)", n_sets=n_sets, n_pks=n_pks,
                )
            else:
                log.info("warmup bucket done", n_sets=n_sets,
                         n_pks=n_pks, secs=round(_time.time() - t0, 1))
            if single_chip_too and n_sets <= urgent_max:
                # the urgent bypass lane is PINNED single-chip with its
                # own (unsharded, plain-pow2) programs: warm those too or
                # the first urgent verify on a meshed node pays the cold
                # compile the warmup list exists to hide
                t0 = _time.time()
                fn(n_sets, n_pks, single_chip=True)
                log.info("urgent single-chip bucket done", n_sets=n_sets,
                         n_pks=n_pks, secs=round(_time.time() - t0, 1))
        # one chip, and the chain (built while the buckets above compiled)
        # keeps its registry on the device: every set its builders make
        # names rows of that table, so the batch lane serves the INDEXED
        # prepare at these buckets. A chain not built yet, or a table that
        # grows later, pays that compile at its first dispatch.
        table = getattr(backend, "registry", None)
        if table is not None and len(table) and not single_chip_too:
            from ..crypto.jaxbls.backend import warm_prepare_indexed

            for n_sets, n_pks in plan_buckets:
                t0 = _time.time()
                warm_prepare_indexed(n_sets, n_pks, table)
                log.info("indexed prepare done", n_sets=n_sets, n_pks=n_pks,
                         rows=len(table), secs=round(_time.time() - t0, 1))

    if supervisor is not None:
        # node bring-up path: a warmup crash (device error mid-compile)
        # retries with backoff instead of degrading straight to
        # cold-compile-on-first-dispatch (utils/supervisor.py)
        return supervisor.spawn(attempt, "autotune_warmup")

    def run():
        try:
            attempt()
        except Exception as e:
            log.warn("startup warmup abandoned (first dispatches will "
                     "pay the compile)", error=f"{type(e).__name__}: {e}")

    t = threading.Thread(target=run, daemon=True, name="autotune-warmup")
    t.start()
    return t
