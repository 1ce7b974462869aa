"""Device-mesh layer: production multi-chip sharding of signature batches.

The framework's scaling unit is the signature-set axis (SURVEY.md §5
"distributed communication backend"): every tensor in the verification
pipeline carries the set index as its leading axis, so data-parallel
sharding over a 1-D `sets` mesh makes the per-set stages embarrassingly
parallel while the two cross-set reductions — the signature tree-sum in
stage 1 and the shared-accumulator Fq12 pair product in stage 4 — become
XLA collectives over ICI. This module owns mesh discovery and input
placement; `crypto/jaxbls/backend.py` consults it on every dispatch, so
`verify_signature_sets` transparently uses however many chips are attached
(the analog of blst scaling across cores, except the "cores" are chips).

Resolution seams (all consumed by the forced-host-device harness,
`XLA_FLAGS=--xla_force_host_platform_device_count=8`):

  LIGHTHOUSE_TPU_MESH=0          disable the mesh entirely (single chip)
  LIGHTHOUSE_TPU_MESH_DEVICES=k  use only the first k attached devices —
                                 the `bn loadtest --mesh-devices` sweep's
                                 way of comparing 1-vs-8-chip serving in
                                 one process (k=1 means no mesh)

The mesh has the one axis. A wide key axis is spread on the chip, by the
key grids a dispatch lays (`backend.key_grid_plan`), not across chips.

`reset_mesh_cache()` re-runs discovery after any of these change — the
test seam the harness flips between sweep points.
"""

from __future__ import annotations

import os

from ..utils.metrics import REGISTRY

SET_AXIS = "sets"

# ------------------------------------------------------------------ metrics
# mesh_* series are labeled families (scripts/lint_metrics.py enforces it):
# the axis breakdown answers "what topology is this node actually serving
# on", the dispatch family answers "which lane is sharding work"

_MESH_AXIS_SIZE = REGISTRY.gauge_vec(
    "mesh_axis_size",
    "devices along the one axis (`sets`) of the resolved device mesh; "
    "absent until a mesh resolves",
    ("axis",),
)
MESH_DISPATCH = REGISTRY.counter_vec(
    "mesh_sharded_dispatch_total",
    "jaxbls batch dispatches by placement lane: `sharded` over the mesh, "
    "`urgent` (the bypass lane, pinned to one chip), or `single_device` "
    "(ordinary batches on a mesh-less node)",
    ("lane",),
)

_cached: list = []  # [mesh_or_None] once resolved


def _record_bringup(mesh) -> None:
    """Flight-recorder + metrics + one structured log line for a resolved
    mesh: topology changes are exactly the bring-up facts an incident dump
    should carry next to breaker/route events. The axis gauge is
    (re)written every time — a re-resolution to no mesh at all must not
    leave a stale size on /metrics."""
    from ..utils.logging import get_logger

    shape = dict(mesh.shape) if mesh is not None else {}
    _MESH_AXIS_SIZE.labels(SET_AXIS).set(shape.get(SET_AXIS, 0))
    if mesh is None:
        return
    get_logger("mesh").info(
        "device mesh resolved", shape=str(shape),
        devices=int(mesh.devices.size),
    )
    try:
        from ..observability.flight_recorder import RECORDER

        RECORDER.record(
            "mesh_bringup", devices=int(mesh.devices.size),
            **{f"axis_{a}": int(s) for a, s in shape.items()},
        )
    except Exception:
        pass  # diagnostics must never break mesh discovery


def get_mesh():
    """The process-wide device mesh, or None when only one device is
    attached (or LIGHTHOUSE_TPU_MESH=0, or LIGHTHOUSE_TPU_MESH_DEVICES=1).
    Resolved once — device topology does not change within a process;
    harnesses that flip the env seams call `reset_mesh_cache` after.

    The shape is 1-D over the `sets` axis: signature sets are
    data-parallel, and every key of a set stays on its set's chip."""
    if _cached:
        return _cached[0]
    mesh = None
    if os.environ.get("LIGHTHOUSE_TPU_MESH", "1") != "0":
        import jax

        devices = jax.devices()
        raw_cap = os.environ.get("LIGHTHOUSE_TPU_MESH_DEVICES", "").strip()
        if raw_cap:
            try:
                cap = int(raw_cap)
            except ValueError:
                cap = None
            if cap is None or cap < 1:
                # unparseable OR non-positive: every invalid value is
                # rejected loudly — silent fallback is how a typo'd knob
                # serves the wrong topology for weeks
                from ..utils.logging import get_logger

                get_logger("mesh").warn(
                    "ignoring invalid LIGHTHOUSE_TPU_MESH_DEVICES "
                    "(must be an integer >= 1); using all devices",
                    value=raw_cap,
                )
            else:
                devices = devices[:cap]
        # the kernels' tree reductions (and pad_sets' pow2-multiple rule)
        # require a power-of-two set axis: a 3- or 6-device slice would
        # send the first dispatch into an unsatisfiable padding search.
        # Serve on the largest pow2 prefix and say so.
        if len(devices) > 1 and len(devices) & (len(devices) - 1):
            usable = 1 << (len(devices).bit_length() - 1)
            from ..utils.logging import get_logger

            get_logger("mesh").warn(
                "device count is not a power of two; meshing the first "
                "pow2 devices (the tree reductions are pow2-structured)",
                devices=len(devices), usable=usable,
            )
            devices = devices[:usable]
        if len(devices) > 1:
            import numpy as np
            from jax.sharding import Mesh

            mesh = Mesh(np.array(devices), (SET_AXIS,))
    _record_bringup(mesh)  # also clears stale gauges when mesh is None
    _cached.append(mesh)
    return mesh


def reset_mesh_cache() -> None:
    """Test/harness seam: force re-discovery. The forced-host-device
    harness (and the `--mesh-devices` sweep) flips LIGHTHOUSE_TPU_MESH /
    LIGHTHOUSE_TPU_MESH_DEVICES and calls this so the next `get_mesh()`
    re-reads them; the jaxbls stage cache is keyed by the mesh signature,
    so a re-resolved mesh picks up fresh compiled variants without
    clearing anything else."""
    _cached.clear()


def mesh_shape_key(mesh=_cached) -> str:
    """Canonical topology string for autotune profile keys: "single" for
    no mesh, else the axis and its size, "sets8". Pass an
    explicit mesh (or None) to stringify a known topology without
    resolving the live one."""
    if mesh is _cached:
        mesh = get_mesh()
    if mesh is None:
        return "single"
    return "-".join(f"{axis}{size}" for axis, size in dict(mesh.shape).items())


def parse_mesh_shape(key: str | None) -> dict:
    """Inverse of mesh_shape_key: {"sets": 8}, an axis a "-"-joined
    segment (a profile on disk may name axes this build has not); {} for
    None/"single"/unparseable (treated as single-chip)."""
    import re

    if not key or key == "single":
        return {}
    out = {}
    for part in str(key).split("-"):
        m = re.fullmatch(r"([a-z_]+)(\d+)", part)
        if not m:
            return {}
        out[m.group(1)] = int(m.group(2))
    return out


def sets_sharding(mesh, ndim: int):
    """NamedSharding partitioning the leading (set) axis, replicating the
    rest."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(SET_AXIS, *([None] * (ndim - 1))))


def replicated_sharding(mesh):
    """NamedSharding replicating an array on every mesh device (the
    cross-set accumulators and scalar verdicts)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def put_sets(a, mesh=None):
    """Place an array with its leading axis sharded over the mesh; plain
    device_put when no mesh. The leading dimension must divide the mesh
    size (callers pad the set axis with masked entries — see pad_sets)."""
    import jax

    if mesh is None:
        mesh = get_mesh()
    if mesh is None:
        return jax.device_put(a)
    import numpy as np

    return jax.device_put(a, sets_sharding(mesh, np.ndim(a)))


def put_pk_grid(a, mesh=None):
    """Place an (n_sets, n_pks, ...) key grid: set axis sharded, key axis
    whole, so a set's key sum never leaves its chip."""
    return put_sets(a, mesh)


def put_single(a):
    """Place an array whole on the default (first) device — the urgent
    bypass lane's placement: a ~ms single-set verify must never pay mesh
    resharding or collective latency (docs/PERF_NOTES.md "Multichip
    serving"). Deliberately UNCOMMITTED (no explicit device): the default
    device is chip 0, and uncommitted placement lowers identically to the
    host-numpy inputs the warmup paths feed, so both hit one compiled
    program."""
    import jax

    return jax.device_put(a)


def _pad_pow2_multiple(n: int, size: int) -> int:
    """Smallest power of two >= n that is also a multiple of `size` — the
    kernels' tree reductions are pow2-structured AND sharded axes must
    divide the mesh axis, so both constraints apply together. `size` must
    itself be a power of two (get_mesh guarantees it); a non-pow2 size
    has NO pow2 multiple, so raise instead of searching forever."""
    if size > 1 and size & (size - 1):
        raise ValueError(
            f"mesh axis size {size} is not a power of two — no pow2 "
            "padding exists (get_mesh should have rejected this topology)"
        )
    p = 1
    while p < max(n, 1):
        p *= 2
    while p % size:
        p *= 2
    return p


def pad_sets(n: int, mesh=None) -> int:
    """Round a set count up so it divides evenly across the mesh (and stays
    a power of two for the signature tree-sum). Pass an explicit mesh to
    pad for a topology other than the live one (the padding/bucket rule is
    mesh-shape-keyed — crypto/jaxbls/backend.padding_bucket)."""
    if mesh is None:
        mesh = get_mesh()
    if mesh is None:
        return n
    return _pad_pow2_multiple(n, mesh.shape[SET_AXIS])
