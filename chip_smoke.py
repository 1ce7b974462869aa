#!/usr/bin/env python3
"""Chip smoke: the node's verify path, once, on the TPU — or a non-zero exit.

One process. It imports JAX itself, sets no platform and starts no child:
`jax.devices()[0].platform` must be "tpu", otherwise it says why and exits
non-zero. Every phase raises on what is wrong; nothing here catches a
failure and lets the run end with 0.

Default phases (one chip — on a host that shows more, the first alone):
  device   platform, device kind, count, jax version, the compile cache
           directory in force and how many entries it held at start
  bls      committees of 128 keys in batches of 64 sets (ONE padding
           bucket, 64x128) through BeaconProcessor -> bls "jax" backend ->
           PipelinedDispatcher -> device -> continuation: four batches
           whose continuations must see True, True, True, False; the
           pure-Python backend gives the reference verdicts
  jaxhash  a 1,048,576-leaf tree through the `device` hash backend against
           a hashlib ladder, and the epoch deltas at n = 1,048,576 through
           the device lane against the host vector lane

`--chips 4` runs the bls phase alone over the live four-device `sets` mesh.

Stdout is one JSON object per line; the LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`
(`"ok": false` and a non-zero exit on any failure).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))

# the served shape: spec TARGET_COMMITTEE_SIZE keys per set, the reference's
# max_gossip_attestation_batch_size sets per batch (BASELINE.md)
FIXTURE = "bench_fixtures.npz"
BATCH_SETS = 64
COMMITTEE = 128
BUCKET = (64, 128)
TREE_LEAVES = 1 << 20
EPOCH_VALIDATORS = 1 << 20

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A phase found something wrong."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def metric(name: str):
    from lighthouse_tpu.utils.metrics import REGISTRY

    for m in REGISTRY.all_metrics():
        if m.name == name:
            return m
    return None


def family_values(name: str) -> dict:
    """{"label/label": value} of a counter/gauge family ({} when the
    family was never registered)."""
    fam = metric(name)
    if fam is None:
        return {}
    return {"/".join(map(str, k)): c.value for k, c in fam.children()}


class CompileLog:
    """Every XLA compile request of the process, labelled by the step the
    script was in: (label, jitted function, seconds) from JAX's own
    monitoring events, plus persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.label = "setup"
        self.compiles: list = []
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append((self.label, kw.get("fun_name"), secs))

    def _on_event(self, event, **kw):
        key = event.rsplit("/", 1)[-1]
        if key in self.cache:
            self.cache[key] += 1

    def during(self, label: str) -> list:
        return [c for c in self.compiles if c[0] == label]

    def seconds_by_function(self, prefix: str = "") -> dict:
        out: dict = {}
        for label, fn, secs in self.compiles:
            if label.startswith(prefix):
                out[str(fn)] = round(out.get(str(fn), 0.0) + secs, 3)
        return out


# ----------------------------------------------------------------- device


def require_tpu():
    """jax.devices() on a TPU, or SystemExit(2) with the reason."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        emit(phase="device", error="no TPU: jax.devices()[0].platform is "
             f"{d0.platform!r}; this script sets no platform and runs "
             "nowhere else")
        emit(ok=False, device={"platform": d0.platform,
                               "kind": d0.device_kind, "count": len(devices)})
        raise SystemExit(2)
    return devices


def phase_device(devices, chips: int) -> None:
    import jax

    from lighthouse_tpu.utils import jaxcfg

    jaxcfg.setup_compilation_cache()
    cache_dir = jaxcfg.cache_base_dir()
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          f"cache dir in force {jax.config.jax_compilation_cache_dir!r} "
          f"is not {cache_dir!r}")
    entries = 0
    if os.path.isdir(cache_dir):
        entries = sum(
            1 for n in os.listdir(cache_dir)
            if not n.endswith("-atime") and not n.startswith(".")
            and os.path.isfile(os.path.join(cache_dir, n))
        )
    d0 = devices[0]
    emit(phase="device", platform=d0.platform, kind=d0.device_kind,
         devices_visible=len(devices), devices_used=chips,
         jax=jax.__version__, cache_dir=cache_dir,
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         cache_entries_at_start=entries)


# -------------------------------------------------------------------- bls


def load_sets(n: int) -> list:
    import numpy as np

    from lighthouse_tpu.autotune.calibrate import signature_set

    z = np.load(os.path.join(_ROOT, FIXTURE))
    meta = json.loads(bytes(z["meta"]))
    check(meta["n_att"] >= n and meta["n_pks"] == COMMITTEE,
          f"{FIXTURE} holds {meta['n_att']} sets x {meta['n_pks']} keys; "
          f"need {n} x {COMMITTEE}")
    return [
        signature_set(z["att_keys"][i], z["att_sigs"][i], z["att_msgs"][i])
        for i in range(n)
    ]


def phase_bls(devices, chips: int, log: CompileLog) -> None:
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        BeaconProcessorConfig,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.jaxbls import backend as jb
    from lighthouse_tpu.observability import device as obs_device
    from lighthouse_tpu.parallel import get_mesh

    t0 = time.perf_counter()
    n = BATCH_SETS
    sets = load_sets(2 * n)
    # payload -> set: the fixture's index, plus one more entry — set 1
    # carrying set 0's signature (the negative control of bench.py)
    SWAPPED = 2 * n
    table = dict(enumerate(sets))
    table[SWAPPED] = bls.SignatureSet(
        sets[0].signature, sets[1].signing_keys, sets[1].message
    )
    batches = [
        list(range(0, n)),
        list(range(n, 2 * n)),
        list(range(0, n)),
        [0, SWAPPED] + list(range(2, n)),
    ]
    expected = [True, True, True, False]
    t_load = time.perf_counter() - t0

    # the plain reference: the pure-Python backend on the first four sets,
    # and on those four with the swap
    t0 = time.perf_counter()
    bls.set_backend("python")
    ref = [
        bls.verify_signature_sets([table[i] for i in batches[0][:4]]),
        bls.verify_signature_sets([table[i] for i in batches[3][:4]]),
    ]
    emit(phase="bls", step="reference", backend="python", verdicts=ref,
         fixture_load_secs=round(t_load, 2),
         secs=round(time.perf_counter() - t0, 2))
    check(ref == [True, False], f"pure-Python reference gave {ref}")

    backend = bls.set_backend("jax")
    check(backend.name == "jax", f"backend is {backend.name!r}, not jax")
    mesh = get_mesh()
    if chips == 1:
        check(mesh is None, "one-chip run resolved a mesh")
    else:
        check(mesh is not None and int(mesh.devices.size) == chips,
              f"expected a {chips}-device mesh, got {mesh}")
    # per-stage seconds come from the repo's own attribution families;
    # attribution event-times every stage resolve, so the four stages of
    # a batch run one after the other
    obs_device.set_enabled(True)

    proc = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=n))
    verdicts: list = []
    widths: list = []
    inflight_seen: list = []
    placements: list = []

    def run_batch(payloads):
        k = len(widths)
        widths.append(len(payloads))
        inflight_seen.append(len(proc._inflight))
        log.label = f"batch{k + 1}"
        t = time.perf_counter()
        ticket = bls.verify_signature_sets_async([table[p] for p in payloads])
        secs = time.perf_counter() - t
        log.label = "between"
        out = ticket.handle._ok          # stage 4's output, still in flight
        placements.append(set(out.devices()))
        emit(phase="bls", step="submitted", batch=k + 1, sets=len(payloads),
             dispatch_secs=round(secs, 3),
             compiles=len(log.during(f"batch{k + 1}")))
        return ticket, verdicts.append

    def feed(payloads):
        for p in payloads:
            check(proc.submit(WorkItem(kind=WorkKind.gossip_attestation,
                                       payload=p, run_batch=run_batch)),
                  "processor refused a work item")

    t0 = time.perf_counter()
    for b in batches[:3]:
        feed(b)
    proc.run_until_idle()
    feed(batches[3])
    proc.run_until_idle()
    wall = time.perf_counter() - t0

    stage_first = family_values("jaxbls_stage_compile_seconds")
    later = metric("jaxbls_stage_device_seconds")
    stage_later = {
        "/".join(map(str, k)): {"n": c.n, "mean_secs": round(c.total / c.n, 4)}
        for k, c in later.children() if c.n
    }
    used = set().union(*placements)
    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    emit(phase="bls", step="resolved", verdicts=verdicts, widths=widths,
         inflight_at_submit=inflight_seen, wall_secs=round(wall, 2),
         stage_path={s: "xla" for s in obs_device.STAGES},
         stage_first_resolve_secs={k: round(v, 3)
                                   for k, v in stage_first.items()},
         stage_later_resolves=stage_later,
         compile_secs_by_function=log.seconds_by_function("batch"),
         persistent_cache=dict(log.cache),
         buckets_seen=sorted(jb._seen_exec_buckets),
         pubkey_cache=family_values("jaxbls_pubkey_cache_total"),
         output_devices=sorted(map(str, used)),
         peak_bytes_in_use=peak, processor_errors=errors,
         hybrid_routes=hybrid)

    check(verdicts == expected,
          f"continuations saw {verdicts}, expected {expected}")
    check(widths == [n] * 4, f"batch widths {widths}, expected four of {n}")
    check(max(inflight_seen[:3]) >= 2,
          f"never more than one batch in flight: {inflight_seen}")
    check(jb._seen_exec_buckets == {BUCKET},
          f"backend compiled buckets {sorted(jb._seen_exec_buckets)}, "
          f"expected only {BUCKET}")
    check(not log.during("batch3"),
          f"the third batch compiled: {log.during('batch3')}")
    check(not any(errors.values()), "the processor swallowed an error")
    check(not any(hybrid.values()),
          "the hybrid router served a verification")
    # a stage output lives on the accelerator this run was given (the
    # devices require_tpu() admitted): chip 0 alone, or within the mesh
    check(used and used <= set(devices[:chips]),
          f"stage outputs live on {sorted(map(str, used))}, not on "
          f"{[str(d) for d in devices[:chips]]}")
    if chips > 1:
        # a stage INPUT (the cached pubkey grid) is laid over all chips,
        # and the pairing stage still rides its first build
        grid = next(iter(backend._pk_cache.values()))[0]
        laid = sorted(str(d) for d in grid.sharding.device_set)
        pairing = jb._get_stages(mesh=mesh)[3]
        emit(phase="bls", step="mesh", mesh=dict(mesh.shape),
             input_devices=laid, pairing_flipped=pairing._use_fallback)
        check(len(laid) == chips,
              f"stage input laid over {laid}, expected {chips} devices")
        check(not pairing._use_fallback,
              "the sharded pairing stage flipped to its shard_map build")


# ---------------------------------------------------------------- jaxhash


def hashlib_root(leaves: bytes) -> bytes:
    level = [leaves[i:i + 32] for i in range(0, len(leaves), 32)]
    while len(level) > 1:
        level = [
            hashlib.sha256(level[i] + level[i + 1]).digest()
            for i in range(0, len(level), 2)
        ]
    return level[0]


def phase_jaxhash(seed: int, log: CompileLog) -> None:
    import numpy as np

    from lighthouse_tpu import jaxhash
    from lighthouse_tpu.jaxhash import epoch_vectors as ev
    from lighthouse_tpu.state_transition import accessors as acc

    rng = np.random.default_rng(seed)
    jaxhash.set_hash_backend("device")

    # --- the tree: one plane of 32-byte leaves, root against hashlib
    n = TREE_LEAVES
    leaves = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    log.label = "tree_hash"
    t0 = time.perf_counter()
    root = jaxhash.ROUTER.maybe_tree_root(leaves, n.bit_length() - 1)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = jaxhash.ROUTER.maybe_tree_root(leaves, n.bit_length() - 1)
    t_second = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = hashlib_root(leaves.tobytes())
    t_ref = time.perf_counter() - t0
    emit(phase="jaxhash", step="tree_root", leaves=n,
         first_secs=round(t_first, 3), second_secs=round(t_second, 3),
         hashlib_secs=round(t_ref, 3),
         root=None if root is None else bytes(root).hex(),
         compile_secs_by_function=log.seconds_by_function("tree_hash"))
    check(root is not None and again is not None,
          "the device tree hash returned None (host ladder would serve)")
    check(bytes(root) == want and bytes(again) == want,
          f"device root {bytes(root).hex()} != hashlib {want.hex()}")

    # --- the epoch deltas: device lane against the host vector lane
    n = EPOCH_VALIDATORS
    incr = 10**9
    eff = rng.integers(16, 33, size=n).astype(np.uint64) * np.uint64(incr)
    part = [rng.random(n) < p for p in (0.97, 0.95, 0.9)]
    eligible = rng.random(n) < 0.99
    scores = rng.integers(0, 64, size=n).astype(np.uint64)
    total_active = int(eff.sum())
    base_per_incr = incr * 64 // acc._integer_squareroot(total_active)
    flag_incrs = [int(eff[m].sum()) // incr for m in part]
    total_incr = total_active // incr
    denom = 4 * 2**24              # score bias x bellatrix penalty quotient
    target = part[acc.TIMELY_TARGET_FLAG_INDEX]

    log.label = "epoch"
    t0 = time.perf_counter()
    got = ev._device_altair_deltas(
        n, eff, part, eligible, target, scores, base_per_incr, incr,
        flag_incrs, total_incr, denom, False,
    )
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    rew, pen = [], []
    for f, weight in enumerate(acc.PARTICIPATION_FLAG_WEIGHTS):
        r, p = ev.flag_deltas_vec(
            np, eff, part[f], eligible, base_per_incr, incr, weight,
            flag_incrs[f], total_incr, False,
            f == acc.TIMELY_HEAD_FLAG_INDEX,
        )
        rew.append(r)
        pen.append(p)
    inact = ev.inactivity_deltas_vec(np, eff, scores, target, eligible, denom)
    t_host = time.perf_counter() - t0
    log.label = "after"
    from lighthouse_tpu.jaxhash.router import route_totals

    routes = route_totals()
    emit(phase="jaxhash", step="epoch_deltas", validators=n,
         device_secs=round(t_dev, 3), host_vector_secs=round(t_host, 3),
         device_returned=got is not None, routes=routes,
         reward_sum=int(sum(int(r.sum()) for r in rew)),
         compile_secs_by_function=log.seconds_by_function("epoch"))
    check(got is not None,
          "the device epoch lane returned None (host lane would serve)")
    d_rew, d_pen, d_inact = got
    check(all(np.array_equal(a, b) for a, b in zip(d_rew, rew))
          and all(np.array_equal(a, b) for a, b in zip(d_pen, pen))
          and np.array_equal(d_inact, inact),
          "device epoch deltas differ from the host vector lane")
    check(int(inact.sum()) > 0 and int(rew[0].sum()) > 0,
          "the epoch inputs produced all-zero deltas (vacuous comparison)")
    check(not routes.get("host/device_error"),
          f"a device error was answered by the host: {routes}")
    check(routes.get("device/ok", 0) >= 2, f"tree hash routes: {routes}")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the bls phase alone over the live four-chip "
                         "sets mesh")
    ap.add_argument("--seed", type=int, default=22,
                    help="seed of the jaxhash phase's data")
    args = ap.parse_args(argv)

    devices = require_tpu()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": args.chips}
    try:
        check(len(devices) >= args.chips,
              f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
        # the seam parallel/mesh.py reads before it resolves its mesh: on
        # a host with more chips the default run still uses the first alone
        os.environ["LIGHTHOUSE_TPU_MESH_DEVICES"] = str(args.chips)
        t0 = time.perf_counter()
        log = CompileLog()
        phase_device(devices, args.chips)
        phase_bls(devices, args.chips, log)
        if args.chips == 1:
            phase_jaxhash(args.seed, log)
        emit(phase="done", wall_secs=round(time.perf_counter() - t0, 1),
             compile_secs_total=round(sum(c[2] for c in log.compiles), 1),
             compile_requests=len(log.compiles),
             persistent_cache=dict(log.cache))
    except Exception as e:  # the boundary: report, then exit non-zero
        traceback.print_exc()
        emit(phase="failed", error=f"{type(e).__name__}: {e}")
        emit(ok=False, device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
