"""Driver `kzg_blob_loop`: a blob-carrying block's sidecars as gossip
delivers them, one block outstanding.

A block at the preset's maximum brings `sidecars_per_block` BlobSidecars on
as many gossip subnets within tens of milliseconds. Here they are that many
`WorkKind.gossip_blob_sidecar` work items submitted back to back to a
one-worker `BeaconProcessor`, each with its own continuation; the processor
coalesces what is queued together, and `run_batch` hands the batch to
`DataAvailabilityChecker.submit_kzg_batch` and returns its `(handle,
continuation)`, as `BeaconChain.submit_gossip_blob_batch` does after its
other gossip checks. The next block's sidecars go in when the last verdict
of this one has been delivered. Every block is a seeded choice and order of
the pool's sidecars without replacement.

A sidecar here is its blob, its 48-byte commitment and its 48-byte proof,
all untrusted bytes; its inclusion proof and header signature are outside
this deployment (the configuration's `assumed`).

Latency is per sidecar: `proc.submit` of its work item -> its continuation
ran with its verdict. With a block's sidecars submitted together its 95th
percentile is the time until the block's data is available.

Parameters (the workload file's `params`):
  backend            bls backend of the timed path ("jax")
  pool               npz of commitments and proofs (data/gen_blob_pool.py),
                     relative to benchmarks/
  preroll_blocks     blocks delivered before the window opens (set-up)
  reference_blocks   blocks of the set-up the plain reference verifies too
  trace_window_s     profiler window of a traced run, after the window
  tamper_window      null; or "swap_proof" / "flip_blob_byte": damage one
                     seeded sidecar of one window block and still expect
                     True — the control check_outputs.py runs, `correct`
                     must be false
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import (  # benchmarks/common.py
    check, emit, runtime_call, verdict)

#: the damaged operands check_outputs.py puts into the window as controls
CONTROLS = ("swap_proof", "flip_blob_byte")

_R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
_P = int(
    "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f624"
    "1eabfffeb153ffffb9feffffffffaaab", 16)


def make_blob(blob_seed: int, index: int, n: int) -> bytes:
    """Sidecar `index`'s blob: n seeded, uniformly random canonical field
    elements (32 random bytes, the top bit cleared, kept if below r)."""
    rng = np.random.default_rng([blob_seed, index])
    mask = (1 << 255) - 1
    out: list = []
    while len(out) < n:
        raw = rng.bytes(32 * (n - len(out) + 16))
        for k in range(0, len(raw), 32):
            v = int.from_bytes(raw[k:k + 32], "big") & mask
            if v < _R:
                out.append(v.to_bytes(32, "big"))
    return b"".join(out[:n])


class Sidecar:
    """What of a BlobSidecar the KZG check reads."""

    __slots__ = ("blob", "kzg_commitment", "kzg_proof")

    def __init__(self, blob: bytes, commitment: bytes, proof: bytes):
        self.blob = blob
        self.kzg_commitment = commitment
        self.kzg_proof = proof


def load_pool(path: str):
    """(the pool's Sidecars with their blobs regenerated, the npz's meta)."""
    z = np.load(path)
    meta = json.loads(bytes(z["meta"]))
    n = meta["field_elements_per_blob"]
    pool = [Sidecar(make_blob(meta["blob_seed"], i, n),
                    bytes(z["commitments"][i]), bytes(z["proofs"][i]))
            for i in range(meta["sidecars"])]
    return pool, meta


def off_subgroup_commitment(rng) -> bytes:
    """A compressed G1 point ON the curve and OUTSIDE the subgroup: a seeded
    x with x^3 + 4 a square; the cofactor is ~2^126, so a point of the curve
    taken this way lies outside but for a chance of 2^-126, and the
    multiplication by the group order below makes sure."""
    from lighthouse_tpu.crypto.bls381 import curve as cv
    from lighthouse_tpu.crypto.bls381 import serde

    while True:
        x = int.from_bytes(rng.bytes(47), "big")
        y = pow((x * x * x + 4) % _P, (_P + 1) // 4, _P)
        if y * y % _P == (x * x * x + 4) % _P and not cv.g1_in_subgroup((x, y)):
            return serde.g1_compress((x, y))


def load_reference(bench_dir: str):
    path = os.path.join(bench_dir, "reference", "kzg_spec.py")
    spec = importlib.util.spec_from_file_location("kzg_spec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Item:
    """One work item's payload: the sidecar, its block, its continuation."""

    __slots__ = ("sidecar", "block", "t_submit", "on_verdict")

    def __init__(self, sidecar, block: int, on_verdict):
        self.sidecar = sidecar
        self.block = block
        self.t_submit = 0.0
        self.on_verdict = on_verdict


def family_values(name: str) -> dict:
    from lighthouse_tpu.utils.metrics import REGISTRY

    for m in REGISTRY.all_metrics():
        if m.name == name:
            if not hasattr(m, "children"):
                return {"": m.value}
            return {"/".join(map(str, k)): c.value for k, c in m.children()}
    return {}


def run(config, params, seed, seconds, trace, h) -> dict:
    # the entry points this cell needs, first thing: a tree without them
    # fails here, within seconds, before a pool is built or a program warmed
    from lighthouse_tpu.chain import beacon_processor as bp
    from lighthouse_tpu.chain.data_availability import DataAvailabilityChecker
    from lighthouse_tpu.crypto import kzg

    check(hasattr(bp.WorkKind, "gossip_blob_sidecar"),
          "this tree's BeaconProcessor has no WorkKind.gossip_blob_sidecar: "
          "it cannot run the cell kzg_6_blobs")
    check(hasattr(DataAvailabilityChecker, "submit_kzg_batch")
          and hasattr(kzg, "BlobBatch")
          and hasattr(kzg.TrustedSetup, "dev_verifier_setup"),
          "this tree has no pipelined KZG batch "
          "(DataAvailabilityChecker.submit_kzg_batch, crypto.kzg.BlobBatch): "
          "it cannot run the cell kzg_6_blobs")

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import device as obs_device

    B = int(config["sidecars_per_block"])
    n_fe = int(config["field_elements_per_blob"])
    k_ref = int(params["reference_blocks"])
    preroll = int(params["preroll_blocks"])
    tamper = params.get("tamper_window")
    if k_ref < 1 or preroll < 1:
        raise ValueError("reference_blocks and preroll_blocks must be >= 1")
    if tamper is not None and tamper not in CONTROLS:
        raise ValueError(f"unknown tampering {tamper!r}")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    pool, meta = load_pool(os.path.join(h.bench_dir, params["pool"]))
    if meta["field_elements_per_blob"] != n_fe:
        raise ValueError(f"{params['pool']} has blobs of "
                         f"{meta['field_elements_per_blob']} field elements, "
                         f"the configuration says {n_fe}")
    if len(pool) < 2 * B:
        raise ValueError(f"pool of {len(pool)} sidecars is too small for "
                         f"blocks of {B}")
    setup = kzg.TrustedSetup.dev_verifier_setup(n_fe)
    t_load = time.perf_counter() - t0

    def draw_block() -> list:
        """A block: a seeded choice and order of B of the pool's sidecars."""
        return [pool[int(i)] for i in rng.choice(len(pool), B, replace=False)]

    def with_swapped_proof(block: list) -> tuple:
        v, d = (int(i) for i in rng.choice(B, 2, replace=False))
        out = list(block)
        out[v] = Sidecar(block[v].blob, block[v].kzg_commitment,
                         block[d].kzg_proof)
        return out, v

    def with_flipped_blob_byte(block: list) -> tuple:
        v = int(rng.integers(B))
        blob = bytearray(block[v].blob)
        blob[32 * int(rng.integers(n_fe)) + 31] ^= 0x01
        out = list(block)
        out[v] = Sidecar(bytes(blob), block[v].kzg_commitment,
                         block[v].kzg_proof)
        return out, v

    # --- the plain reference on the set-up's first blocks. Not set-up time.
    ref = load_reference(h.bench_dir)
    ref_setup = ref.Setup(n_fe, setup.g2_monomial[1])
    setup_blocks = [draw_block() for _ in range(k_ref)]

    def ref_batch(block) -> bool:
        return ref.verdict_of(
            ref.verify_blob_kzg_proof_batch, [s.blob for s in block],
            [s.kzg_commitment for s in block], [s.kzg_proof for s in block],
            ref_setup)

    def ref_each(block) -> list:
        return [ref.verdict_of(ref.verify_blob_kzg_proof, s.blob,
                               s.kzg_commitment, s.kzg_proof, ref_setup)
                for s in block]

    t0 = time.perf_counter()
    ref_setup_verdicts = [ref_batch(b) for b in setup_blocks]
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref

    backend = bls.set_backend(params["backend"])
    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve: a traced run only
    obs_device.set_enabled(bool(trace))
    checker = DataAvailabilityChecker(None, setup=setup)
    proc = bp.BeaconProcessor(bp.BeaconProcessorConfig(num_workers=1))

    lock = threading.Lock()
    delivered: list = []     # (t, item, verdict) per sidecar, in order
    blocks_done: list = []   # (t, block id, [verdicts]) per block
    widths: list = []
    pending: dict = {}       # block id -> verdicts still to come
    verdicts_of_block: dict = {}
    state = {"phase": "setup", "feeding": False, "t_open": None,
             "t_close": None, "loop_blocks": 0, "submitted": 0,
             "next_block": 0}
    window_closed = threading.Event()

    def feed(block: list) -> int:
        """Submit a block's sidecars back to back; returns its id."""
        bid = state["next_block"]
        state["next_block"] += 1
        pending[bid] = len(block)
        verdicts_of_block[bid] = [None] * len(block)
        for k, sc in enumerate(block):
            def on_verdict(ok, bid=bid, k=k):
                verdicts_of_block[bid][k] = ok
            it = _Item(sc, bid, on_verdict)
            it.t_submit = time.perf_counter()
            ok = proc.submit(bp.WorkItem(kind=bp.WorkKind.gossip_blob_sidecar,
                                         payload=it, run_batch=run_batch))
            if not ok:
                raise RuntimeError("the processor refused a work item")
        state["submitted"] += len(block)
        return bid

    def run_batch(items):
        widths.append(len(items))
        with h.annotate("bench:marshal_dispatch"):
            ticket, verdicts_of = checker.submit_kzg_batch(
                [it.sidecar for it in items])

        def continuation(result):
            with h.annotate("bench:continuation"):
                verdicts = verdicts_of(result)
                done = []
                for it, ok in zip(items, verdicts):
                    it.on_verdict(bool(ok))
                    t = time.perf_counter()
                    with lock:
                        delivered.append((t, it, bool(ok)))
                        pending[it.block] -= 1
                        if pending[it.block] == 0:
                            done.append(it.block)
                for bid in done:
                    on_block_done(bid)

        return ticket, continuation

    def on_block_done(bid: int) -> None:
        t = time.perf_counter()
        with lock:
            blocks_done.append((t, bid, list(verdicts_of_block[bid])))
            if state["phase"] != "loop":
                return
            state["loop_blocks"] += 1
            if state["loop_blocks"] == preroll:
                # the window's edges are block deliveries
                state["t_open"] = h.open_window()
                state["i_open"] = len(delivered)
                state["b_open"] = len(blocks_done)
            elif (state["t_open"] is not None and state["t_close"] is None
                  and t >= state["t_open"] + seconds):
                state["t_close"] = h.close_window()
                state["i_close"] = len(delivered)
                state["b_close"] = len(blocks_done)
            # fed under the lock the main thread ends the feeding under: a
            # block decided on here is queued before the queues are looked
            # at, never after the processor stopped (where the next block,
            # fed after the window, would share its batch)
            if state["feeding"]:
                block = draw_block()
                if tamper and state["t_open"] is not None and not state.get(
                        "tampered"):
                    state["tampered"] = True
                    damage = (with_swapped_proof if tamper == "swap_proof"
                              else with_flipped_blob_byte)
                    block, _v = damage(block)
                feed(block)
        if state["t_close"] is not None:
            # only now, with the next block queued: the main thread stops
            # the feeding and waits for the queues to drain
            window_closed.set()

    # --- set-up on the timed path: the reference's blocks first (the first
    # compiles), so both sides give their verdicts on the same operands
    h.log.label = "warmup"
    t0 = time.perf_counter()
    warm_ids = [feed(setup_blocks[0])]
    proc.run_until_idle()
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    for b in setup_blocks[1:]:
        warm_ids.append(feed(b))
        proc.run_until_idle()
    setup_verdicts = [all(verdicts_of_block[i]) for i in warm_ids]
    runtime_call()

    # --- the loop: one block outstanding; one worker pumps as the node's
    # does; the delivery of a block's last verdict feeds the next block
    with lock:
        state["phase"] = "loop"
        state["feeding"] = True
    feed(draw_block())
    proc.start()
    try:
        limit = seconds + 120
        if not window_closed.wait(timeout=limit):
            raise RuntimeError(f"the window did not close in {limit} s")
        if trace:
            h.trace_begin()
            time.sleep(float(params["trace_window_s"]))
            h.trace_end()
        with lock:
            state["feeding"] = False
        t_end = time.perf_counter() + 60
        while not proc.queues_empty():
            if time.perf_counter() > t_end:
                raise RuntimeError("the processor did not drain")
            time.sleep(0.005)
    finally:
        proc.stop()

    # --- after the window, on the same set-up and the same path: three
    # damaged blocks, each beside the reference's verdicts a sidecar
    with lock:
        state["phase"] = "after"

    def fallbacks() -> float:
        return sum(family_values("kzg_batch_fallback_total").values())

    def after(block: list) -> tuple:
        """(verdicts a sidecar, fallbacks it cost) of one more block."""
        f0 = fallbacks()
        bid = feed(block)
        proc.run_until_idle()
        return list(verdicts_of_block[bid]), fallbacks() - f0

    after_checks = []
    t_ref_after = 0.0
    block, v = with_swapped_proof(draw_block())
    got, fell = after(block)
    t0 = time.perf_counter()
    want = ref_each(block)
    t_ref_after += time.perf_counter() - t0
    expected = [k != v for k in range(B)]
    after_checks.append({
        "name": "after_swapped_proof",
        "what": "after the window: a block in which one sidecar carries "
        "another's proof: [verdicts, the reference's a sidecar, fallbacks] "
        "against [that one False and the others True, the same, 1]",
        "value": [got, want, fell], "limit": [expected, expected, 1]})
    block = draw_block()
    v = int(rng.integers(B))
    block[v] = Sidecar(block[v].blob, off_subgroup_commitment(rng),
                       block[v].kzg_proof)
    got, _fell = after(block)
    t0 = time.perf_counter()
    want_v = ref.verdict_of(ref.verify_blob_kzg_proof, block[v].blob,
                            block[v].kzg_commitment, block[v].kzg_proof,
                            ref_setup)
    t_ref_after += time.perf_counter() - t0
    after_checks.append({
        "name": "after_off_subgroup",
        "what": "after the window: a commitment on the curve outside the "
        "subgroup: [its sidecar's verdict, the others all True] against "
        "[the reference's, True]",
        "value": [got[v], all(got[:v] + got[v + 1:])],
        "limit": [want_v, True]})
    after_checks.append({
        "name": "reference_off_subgroup",
        "what": "the reference on that commitment", "value": want_v,
        "limit": False})
    block = draw_block()
    v = int(rng.integers(B))
    blob = bytearray(block[v].blob)
    k = 32 * int(rng.integers(n_fe))
    blob[k:k + 32] = _R.to_bytes(32, "big")        # the smallest value >= r
    block[v] = Sidecar(bytes(blob), block[v].kzg_commitment,
                       block[v].kzg_proof)
    got, fell = after(block)
    t0 = time.perf_counter()
    want_v = ref.verdict_of(ref.verify_blob_kzg_proof, block[v].blob,
                            block[v].kzg_commitment, block[v].kzg_proof,
                            ref_setup)
    t_ref_after += time.perf_counter() - t0
    after_checks.append({
        "name": "after_element_over_r",
        "what": "after the window: a blob with one field element >= r: [its "
        "sidecar's verdict, the others all True, fallbacks] against [the "
        "reference's, True, 0]",
        "value": [got[v], all(got[:v] + got[v + 1:]), fell],
        "limit": [want_v, True, 0]})
    after_checks.append({
        "name": "reference_element_over_r",
        "what": "the reference on that blob", "value": want_v,
        "limit": False})

    # --- the window's numbers
    win = delivered[state["i_open"]:state["i_close"]]
    win_blocks = blocks_done[state["b_open"]:state["b_close"]]
    lat_ms = np.array([(t - it.t_submit) * 1e3 for t, it, _ in win])
    n_sidecars = int(len(lat_ms))
    n_blocks = len(win_blocks)
    wrong = sum(1 for _, _, ok in win if not ok)
    missing = state["submitted"] - len(delivered)
    window_s = state["t_close"] - state["t_open"]
    lat_sorted = np.sort(lat_ms)
    p95 = float(lat_sorted[int(np.ceil(0.95 * n_sidecars)) - 1])

    def in_window(family: str, labels: dict | None = None) -> float:
        return layer_reader.evaluate(
            {"family": family, "labels": labels or {}, "reduce": "sum"},
            h.before, h.after, {}, {}) or 0.0

    counted = {
        "blobs_evaluated": in_window("kzg_blobs_evaluated_total"),
        "points_validated": in_window("kzg_points_validated_total"),
        "batches": in_window("kzg_batches_total"),
        "sidecars": in_window("kzg_batch_sidecars_total"),
        "fallbacks": in_window("kzg_batch_fallback_total"),
    }
    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    emit(step="kzg_blob_loop", backend=backend.name, pool_sidecars=len(pool),
         pool_load_secs=round(t_load, 2), reference_secs=round(t_ref, 2),
         reference_after_secs=round(t_ref_after, 2),
         reference_setup_verdicts=ref_setup_verdicts,
         warmup_s=h.notes["warmup_s"], setup_verdicts=setup_verdicts,
         window_s=window_s, blocks_in_window=n_blocks,
         sidecars_in_window=n_sidecars,
         blocks_per_s=n_blocks / window_s, latency_ms={
             "n": n_sidecars, "median": float(np.median(lat_ms)), "p95": p95,
             "max": float(lat_sorted[-1])},
         widths_seen=sorted(set(widths)), counted_in_window=counted,
         processor_errors=errors, hybrid_routes=hybrid,
         dropped=sum(proc.dropped.values()),
         generator="closed loop, no schedule: lateness does not apply",
         tamper_window=tamper)

    # the run's own conditions: a breach is no result at all
    check(not any(errors.values()), f"the processor swallowed an error: "
          f"{errors}")
    check(not any(hybrid.values()), "the hybrid router served a verification")
    check(sum(proc.dropped.values()) == 0, "the processor dropped work")
    check(n_sidecars == B * n_blocks, f"{n_sidecars} sidecars delivered in "
          f"the window for {n_blocks} blocks of {B}")
    check(set(widths) == {B}, f"batch widths {sorted(set(widths))}: a block's "
          f"{B} sidecars were not one batch")

    # --- correct: each number compared, beside its limit (all exact)
    compared = [
        {"name": "reference", "what": "the reference on the set-up's blocks",
         "value": ref_setup_verdicts, "limit": [True] * k_ref},
        {"name": "timed_against_reference",
         "what": "the timed backend on the same blocks, against the "
         "reference's verdicts",
         "value": setup_verdicts, "limit": ref_setup_verdicts},
        {"name": "window_wrong", "what": "sidecars of the window with a "
         "False verdict",
         "value": wrong, "limit": 0},
        {"name": "window_missing", "what": "sidecars submitted whose "
         "verdict never came",
         "value": missing, "limit": 0},
        {"name": "window_counters",
         "what": "the window's counters [blobs evaluated, points validated, "
         "batches, sidecars, fallbacks] against [B, 2 B, 1, B, 0] a block",
         "value": [counted[k] for k in ("blobs_evaluated", "points_validated",
                                        "batches", "sidecars", "fallbacks")],
         "limit": [B * n_blocks, 2 * B * n_blocks, n_blocks, B * n_blocks, 0]},
        *after_checks,
    ]
    return {
        "correct": verdict(compared),
        "compared": compared,
        "attempted": n_sidecars + missing,
        "failed": wrong + missing,
        "end_to_end": {
            "bls_verify_p95_ms": {"value": p95, "unit": "ms"},
        },
    }
