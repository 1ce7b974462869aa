"""Driver `bls_flood`: a gossip flood of attestation signature sets that
never lets the verify queue empty.

Copied from `chip_smoke.py` `phase_bls` (PR 22): the same `run_batch` /
`feed` through a real BeaconProcessor into the bls backend; what changed is
the feeding loop and the timing. A closed loop keeps `backlog_sets` work
items outstanding at all times (each continuation submits one batch's worth
more), one worker pumps as the live node's does, and every batch is a fresh
seeded grouping of the pool, so the pubkey marshal is paid on every batch as
real gossip pays it.

Parameters (the workload file's `params`):
  backend           bls backend of the timed path ("jax")
  pool              npz of the signature-set pool, relative to benchmarks/
  batch_sets        sets per dispatch (the processor's max_attestation_batch)
  backlog_sets      work items outstanding at all times
  bucket            [n_sets, n_pks]: the only padding bucket allowed (jax)
  preroll_batches   batches delivered before the window opens (set-up)
  reference_sets    sets the pure-Python reference verifies, with and
                    without a swapped signature
  trace_window_s    profiler window of a traced run, after the window
  tamper_window     null; or "swap_signature" / "flip_message": damage one
                    seeded set of one window batch and still expect True —
                    the control check_outputs.py runs, `correct` must be false
"""

from __future__ import annotations

import json
import os
import threading
import time

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import (  # benchmarks/common.py
    check, emit, runtime_call, verdict)

#: the damaged operands check_outputs.py puts into the window as controls
CONTROLS = ("swap_signature", "flip_message")


def _fq(a) -> int:
    return int.from_bytes(bytes(a), "big")


def load_pool(path: str, keys_per_set: int) -> list:
    """The pool of SignatureSets (big-endian affine coordinates, the npz
    wire format of scripts/gen_bench_fixtures.py)."""
    from lighthouse_tpu.crypto import bls

    z = np.load(path)
    meta = json.loads(bytes(z["meta"]))
    if meta["n_pks"] != keys_per_set:
        raise ValueError(f"{path} holds {meta['n_pks']} keys a set, the "
                         f"configuration says {keys_per_set}")
    keys, sigs, msgs = z["att_keys"], z["att_sigs"], z["att_msgs"]
    pool = []
    for i in range(meta["n_att"]):
        sig = ((_fq(sigs[i, 0, 0]), _fq(sigs[i, 0, 1])),
               (_fq(sigs[i, 1, 0]), _fq(sigs[i, 1, 1])))
        pks = [bls.PublicKey((_fq(k[0]), _fq(k[1]))) for k in keys[i]]
        pool.append(bls.SignatureSet(bls.Signature(sig), pks,
                                     bytes(msgs[i])))
    return pool


def tampered(pool: list, victim: int, donor: int, how: str):
    """`pool[victim]` damaged: another set's signature, or one message
    byte flipped."""
    from lighthouse_tpu.crypto import bls

    s = pool[victim]
    if how == "swap_signature":
        return bls.SignatureSet(pool[donor].signature, s.signing_keys,
                                s.message)
    if how == "flip_message":
        msg = bytearray(s.message)
        msg[donor % 32] ^= 0x01
        return bls.SignatureSet(s.signature, s.signing_keys, bytes(msg))
    raise ValueError(f"unknown tampering {how!r}")


class _Item:
    """One work item's payload: the set and when it was submitted."""

    __slots__ = ("sset", "t_submit")

    def __init__(self, sset):
        self.sset = sset
        self.t_submit = 0.0


def family_values(name: str) -> dict:
    from lighthouse_tpu.utils.metrics import REGISTRY

    for m in REGISTRY.all_metrics():
        if m.name == name:
            return {"/".join(map(str, k)): c.value for k, c in m.children()}
    return {}


def run(config, params, seed, seconds, trace, h) -> dict:
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        BeaconProcessorConfig,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import device as obs_device

    B = int(params["batch_sets"])
    backlog = int(params["backlog_sets"])
    if backlog % B:
        raise ValueError("backlog_sets must be whole batches")
    if not 2 <= int(params["reference_sets"]) <= B:
        raise ValueError("reference_sets must be 2 to batch_sets")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    pool = load_pool(os.path.join(h.bench_dir, params["pool"]),
                     int(config["keys_per_set"]))
    n_pool = len(pool)
    if n_pool < backlog + B:
        raise ValueError(f"pool of {n_pool} sets is too small for a backlog "
                         f"of {backlog}")
    t_load = time.perf_counter() - t0

    # --- the plain reference: the pure-Python backend on a seeded sample,
    # and on the sample with a seeded swap. Its time is not set-up.
    t0 = time.perf_counter()
    k = int(params["reference_sets"])
    sample = [int(i) for i in rng.choice(n_pool, size=k, replace=False)]
    bls.set_backend("python")
    swapped = [pool[i] for i in sample]
    swapped[1] = tampered(pool, sample[1], sample[0], "swap_signature")
    ref = [bls.verify_signature_sets([pool[i] for i in sample]),
           bls.verify_signature_sets(swapped)]
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref

    backend = bls.set_backend(params["backend"])
    on_device = backend.name == "jax"
    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve and so run a batch's four stages one
    # after the other: a traced run only
    obs_device.set_enabled(bool(trace))

    proc = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=B,
                                                 num_workers=1))
    lock = threading.Lock()
    delivered: list = []      # (t, items, verdict) per batch, in order
    widths: list = []
    state = {"phase": "setup", "feeding": False, "t_open": None,
             "t_close": None, "flood_batches": 0, "submitted": 0}
    window_closed = threading.Event()
    tamper = params.get("tamper_window")

    def stream():
        """Set indices: one seeded permutation of the pool after another."""
        while True:
            yield from (int(i) for i in rng.permutation(n_pool))

    indices = stream()

    def next_batch() -> list:
        return [_Item(pool[next(indices)]) for _ in range(B)]

    def damaged_batch(how: str) -> list:
        """A batch with one seeded set damaged."""
        batch = next_batch()
        v, d = (int(x) for x in rng.choice(n_pool, 2, replace=False))
        batch[v % B] = _Item(tampered(pool, v, d, how))
        return batch

    def feed(items) -> None:
        for it in items:
            it.t_submit = time.perf_counter()
            ok = proc.submit(WorkItem(kind=WorkKind.gossip_attestation,
                                      payload=it, run_batch=run_batch))
            if not ok:
                raise RuntimeError("the processor refused a work item")
        state["submitted"] += len(items)

    def run_batch(items):
        widths.append(len(items))
        with h.annotate("bench:marshal_dispatch"):
            ticket = bls.verify_signature_sets_async(
                [it.sset for it in items])

        def continuation(verdict):
            with h.annotate("bench:continuation"):
                on_delivered(items, verdict)

        return ticket, continuation

    def on_delivered(items, verdict) -> None:
        t = time.perf_counter()
        with lock:
            delivered.append((t, items, bool(verdict)))
            if state["phase"] != "flood":
                return
            state["flood_batches"] += 1
            n = state["flood_batches"]
            if n == int(params["preroll_batches"]):
                # the window's edges are deliveries, so its rate is over
                # whole batches and not cut mid-batch
                state["t_open"] = h.open_window()
                state["i_open"] = len(delivered)
            elif (state["t_open"] is not None and state["t_close"] is None
                  and t >= state["t_open"] + seconds):
                state["t_close"] = h.close_window()
                state["i_close"] = len(delivered)
                window_closed.set()
            feeding = state["feeding"]
        if feeding:
            if tamper and state["t_open"] is not None and not state.get(
                    "tampered"):
                state["tampered"] = True
                feed(damaged_batch(tamper))
            else:
                feed(next_batch())

    # --- set-up on the timed path's own bucket: the reference's sample
    # filled up to one batch (compiles), one more valid batch, and the
    # reference's swapped sample with the same fill, so both backends give
    # their verdicts on the same operands
    fill = next_batch()[k:]
    h.log.label = "warmup"
    t0 = time.perf_counter()
    feed([_Item(pool[i]) for i in sample] + fill)
    proc.run_until_idle()
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    feed(next_batch())
    feed([_Item(s) for s in swapped] + [_Item(it.sset) for it in fill])
    proc.run_until_idle()
    setup_verdicts = [x[2] for x in delivered]
    runtime_call()

    # --- the flood: the backlog first, then one worker pumps as the node's
    # does; each delivery feeds one batch more
    with lock:
        state["phase"] = "flood"
        state["feeding"] = True
    for _ in range(backlog // B):
        feed(next_batch())
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
    n_flood = len(delivered)

    # --- after the window, on the same path: a damaged batch is still False
    with lock:
        state["phase"] = "after"
    feed(damaged_batch("flip_message"))
    proc.run_until_idle()
    after_verdict = delivered[-1][2] if len(delivered) > n_flood else None

    # --- the window's numbers
    win = delivered[state["i_open"]:state["i_close"]]
    lat_ms = np.array([(t - it.t_submit) * 1e3 for t, items, _ in win
                       for it in items])
    n_sets = int(len(lat_ms))
    wrong = sum(len(items) for _, items, ok in win if not ok)
    missing = state["submitted"] - sum(len(x[1]) for x in delivered)
    window_s = state["t_close"] - state["t_open"]
    rate = n_sets / window_s
    lat_sorted = np.sort(lat_ms)
    p95 = float(lat_sorted[int(np.ceil(0.95 * n_sets)) - 1])

    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    pk = {r: layer_reader.evaluate(
        {"family": "jaxbls_pubkey_cache_total", "labels": {"result": r},
         "reduce": "sum"}, h.before, h.after, {}, {}) or 0.0
        for r in ("miss", "hit")}
    buckets = None
    if on_device:
        from lighthouse_tpu.crypto.jaxbls import backend as jb

        buckets = sorted(jb._seen_exec_buckets)
    emit(step="bls_flood", backend=backend.name, pool_sets=n_pool,
         pool_load_secs=round(t_load, 2), reference_secs=round(t_ref, 2),
         reference_sample=sample, reference_verdicts=ref,
         warmup_s=h.notes["warmup_s"], setup_verdicts=setup_verdicts,
         after_window_tampered_verdict=after_verdict,
         window_s=window_s, batches_in_window=len(win), sets_in_window=n_sets,
         sets_per_s=rate, latency_ms={
             "n": n_sets, "median": float(np.median(lat_ms)), "p95": p95,
             "max": float(lat_sorted[-1])},
         widths_seen=sorted(set(widths)), batches_total=len(delivered),
         pubkey_cache_in_window=pk, buckets_seen=buckets,
         processor_errors=errors, hybrid_routes=hybrid,
         dropped=sum(proc.dropped.values()),
         generator="closed loop, no schedule: lateness does not apply",
         tamper_window=tamper)

    # the run's own conditions: a breach is no result at all
    check(set(widths) == {B}, f"batch widths {sorted(set(widths))}, "
          f"expected only {B}")
    check(not any(errors.values()), f"the processor swallowed an error: "
          f"{errors}")
    check(not any(hybrid.values()), "the hybrid router served a verification")
    check(sum(proc.dropped.values()) == 0, "the processor dropped work")
    if on_device:
        want = tuple(params["bucket"])
        check(buckets == [want], f"backend ran buckets {buckets}, expected "
              f"only {want}")
        check(pk["miss"] == len(win) and pk["hit"] == 0,
              f"pubkey cache in the window {pk}: expected one miss a batch "
              f"({len(win)}) and no hit")

    # --- correct: each number compared, beside its limit (all exact)
    compared = [
        {"name": "reference", "what": "reference verdicts (valid sample, "
         "sample with a swap)",
         "value": ref, "limit": [True, False]},
        {"name": "setup", "what": "set-up verdicts (valid, valid, one "
         "swapped signature)",
         "value": setup_verdicts, "limit": [True, True, False]},
        {"name": "timed_against_reference",
         "what": "the timed backend on the reference's own operands in a "
         "full batch (sample, sample with the swap), against the "
         "pure-Python backend's verdicts",
         "value": setup_verdicts[0::2], "limit": ref},
        {"name": "window_wrong", "what": "sets of the window with a wrong "
         "verdict",
         "value": wrong, "limit": 0},
        {"name": "window_missing", "what": "sets submitted whose verdict "
         "never came",
         "value": missing, "limit": 0},
        {"name": "after_window_damaged", "what": "verdict of the damaged "
         "batch after the window",
         "value": after_verdict, "limit": False},
    ]
    return {
        "correct": verdict(compared),
        "compared": compared,
        "attempted": n_sets + missing,
        "failed": wrong + missing,
        "end_to_end": {
            "bls_verified_sets_per_s": {"value": rate, "unit": "sets/s"},
            "bls_verify_p95_ms": {"value": p95, "unit": "ms"},
        },
    }
