"""Driver `bls_aggregate_flood`: a gossip flood of `SignedAggregateAndProof`
messages that never lets the verify queue empty.

Built from `bls_flood.py`'s parts (copied, not imported: that file may not
change under this one). What differs: a work item is ONE aggregate
(`WorkKind.gossip_aggregate`) whose payload is its trio of signature sets —
selection proof, aggregator signature, the aggregate — the processor
coalesces `batch_aggregates` of them, and `run_batch` fills a
`lighthouse_tpu.chain.aggregate_batch.AggregateBatch` in arrival order and
returns its `(handle, continuation)`, as `BeaconChain.submit_aggregate_batch`
does. A closed loop keeps `backlog_aggregates` work items outstanding (each
delivery submits one batch's worth more), one worker pumps as the live
node's does, and every batch is a fresh run of a seeded permutation of the
slot's aggregates, so the pubkey marshal is paid on every batch.

Latency is per signature set: `proc.submit` of its aggregate -> the
continuation delivered that aggregate's verdict; the three sets of an
aggregate share it. The rate is sets delivered over the window, which opens
and closes on a delivery.

A False batch is False for each of its aggregates here. Giving each its own
verdict by verifying every trio again is the chain's job
(`AggregateBatch`'s continuation, tested in tier-1); it would run 64 small
dispatches in another padding bucket, and a run in whose window
`aggregate_batch_fallback_total` moved gives no result.

Parameters (the workload file's `params`):
  backend              bls backend of the timed path ("jax")
  pool                 npz of the slot's aggregates (data/gen_agg_pool.py),
                       relative to benchmarks/
  batch_aggregates     aggregates per dispatch (max_aggregate_batch)
  backlog_aggregates   work items outstanding at all times
  bucket               [n_sets, n_pks]: the only padding bucket allowed (jax)
  preroll_batches      batches delivered before the window opens (set-up)
  reference_aggregates aggregates the pure-Python reference verifies, with
                       and without a swapped signature
  trace_window_s       profiler window of a traced run, after the window
  tamper_window        null; or "swap_signature" / "flip_message": damage one
                       seeded set (aggregate and role by the seed) of one
                       window batch and still expect True — the control
                       check_outputs.py runs, `correct` must be false
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
ROLES = ("selection_proof", "aggregator_signature", "aggregate")


def _fq(a) -> int:
    return int.from_bytes(bytes(a), "big")


def _sig(a):
    from lighthouse_tpu.crypto import bls

    return bls.Signature(((_fq(a[0, 0]), _fq(a[0, 1])),
                          (_fq(a[1, 0]), _fq(a[1, 1]))))


class Aggregate:
    """One SignedAggregateAndProof of the pool: its committee and its three
    signature sets, in the order the chain builds them."""

    __slots__ = ("committee", "trio")

    def __init__(self, committee: int, trio: tuple):
        self.committee = committee
        self.trio = trio


def load_pool(path: str):
    """(the slot's Aggregates, the npz's meta). One PublicKey object a
    validator, shared by every set it signs in, as the node's
    ValidatorPubkeyCache hands them out."""
    from lighthouse_tpu.crypto import bls

    z = np.load(path)
    meta = json.loads(bytes(z["meta"]))
    size = meta["committee_size"]
    keys = [bls.PublicKey((_fq(k[0]), _fq(k[1]))) for k in z["keys"]]
    masks = np.unpackbits(z["agg_mask"], axis=1)[:, :size].astype(bool)
    slot_msg = bytes(z["slot_msg"])
    pool = []
    for i in range(meta["n_aggregates"]):
        c = int(z["agg_committee"][i])
        members = keys[c * size:(c + 1) * size]
        aggregator = (members[int(z["agg_index"][i])],)
        attesting = [members[j] for j in np.flatnonzero(masks[i])]
        pool.append(Aggregate(c, (
            bls.SignatureSet(_sig(z["sel_sigs"][i]), aggregator, slot_msg),
            bls.SignatureSet(_sig(z["aggor_sigs"][i]), aggregator,
                             bytes(z["aggor_msgs"][i])),
            bls.SignatureSet(_sig(z["att_sigs"][i]), attesting,
                             bytes(z["att_msgs"][i])),
        )))
    return pool, meta


def tampered(pool: list, victim: int, donor: int, role: int, how: str):
    """Set `role` of `pool[victim]` damaged: the same role's signature of
    `pool[donor]` (an aggregate of another committee, so it differs), or
    one message byte flipped."""
    from lighthouse_tpu.crypto import bls

    s = pool[victim].trio[role]
    if how == "swap_signature":
        if pool[donor].committee == pool[victim].committee:
            raise ValueError("the donor must be of another committee")
        return bls.SignatureSet(pool[donor].trio[role].signature,
                                s.signing_keys, s.message)
    if how == "flip_message":
        msg = bytearray(s.message)
        msg[donor % 32] ^= 0x01
        return bls.SignatureSet(s.signature, s.signing_keys, bytes(msg))
    raise ValueError(f"unknown tampering {how!r}")


class _Item:
    """One work item's payload: the aggregate's trio and when it was
    submitted."""

    __slots__ = ("trio", "t_submit")

    def __init__(self, trio):
        self.trio = trio
        self.t_submit = 0.0


def family_values(name: str) -> dict:
    from lighthouse_tpu.utils.metrics import REGISTRY

    for m in REGISTRY.all_metrics():
        if m.name == name:
            return {"/".join(map(str, k)): c.value for k, c in m.children()}
    return {}


def run(config, params, seed, seconds, trace, h) -> dict:
    from lighthouse_tpu.chain.aggregate_batch import AggregateBatch
    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        BeaconProcessorConfig,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import device as obs_device

    B = int(params["batch_aggregates"])
    backlog = int(params["backlog_aggregates"])
    k = int(params["reference_aggregates"])
    if backlog % B:
        raise ValueError("backlog_aggregates must be whole batches")
    if not 2 <= k <= B:
        raise ValueError("reference_aggregates must be 2 to batch_aggregates")
    if B != int(config["aggregates_per_dispatch"]):
        raise ValueError(f"batch_aggregates {B} is not the configuration's "
                         f"{config['aggregates_per_dispatch']}")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    pool, meta = load_pool(os.path.join(h.bench_dir, params["pool"]))
    n_pool = len(pool)
    for key in ("committee_size", "committees", "aggregators_per_committee"):
        if meta[key] != config[key]:
            raise ValueError(f"{params['pool']} has {key} {meta[key]}, the "
                             f"configuration says {config[key]}")
    if n_pool < backlog + B:
        raise ValueError(f"pool of {n_pool} aggregates is too small for a "
                         f"backlog of {backlog}")
    t_load = time.perf_counter() - t0

    def damaged(victim: int, role: int, how: str):
        """pool[victim]'s trio with set `role` damaged; the donor is a
        seeded aggregate of another committee."""
        others = [i for i in range(n_pool)
                  if pool[i].committee != pool[victim].committee]
        trio = list(pool[victim].trio)
        trio[role] = tampered(pool, victim, int(rng.choice(others)), role, how)
        return tuple(trio)

    # --- the plain reference: the pure-Python backend on a seeded sample of
    # aggregates, and on the sample with one seeded set's signature swapped.
    # Its time is not set-up.
    t0 = time.perf_counter()
    sample = [int(i) for i in rng.choice(n_pool, size=k, replace=False)]
    swapped_role = int(rng.integers(3))
    valid_trios = [pool[i].trio for i in sample]
    swapped_trios = list(valid_trios)
    swapped_trios[1] = damaged(sample[1], swapped_role, "swap_signature")
    bls.set_backend("python")
    ref = [bls.verify_signature_sets([s for t in valid_trios for s in t]),
           bls.verify_signature_sets([s for t in swapped_trios for s in t])]
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref

    backend = bls.set_backend(params["backend"])
    on_device = backend.name == "jax"
    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve and so run a batch's four stages one
    # after the other: a traced run only
    obs_device.set_enabled(bool(trace))

    proc = BeaconProcessor(BeaconProcessorConfig(max_aggregate_batch=B,
                                                 num_workers=1))
    lock = threading.Lock()
    delivered: list = []      # (t, items, verdict) per batch, in order
    widths: list = []
    state = {"phase": "setup", "feeding": False, "t_open": None,
             "t_close": None, "flood_batches": 0, "submitted": 0}
    window_closed = threading.Event()
    tamper = params.get("tamper_window")

    def stream():
        """Aggregate indices: one seeded permutation of the slot's
        aggregates after another."""
        while True:
            yield from (int(i) for i in rng.permutation(n_pool))

    indices = stream()

    def next_batch() -> list:
        return [_Item(pool[next(indices)].trio) for _ in range(B)]

    def damaged_batch(how: str, role=None) -> list:
        """A batch with one seeded set damaged: aggregate and, unless
        given, role by the seed."""
        batch = next_batch()
        v = int(rng.integers(n_pool))
        if role is None:
            role = int(rng.integers(3))
        batch[v % B] = _Item(damaged(v, role, how))
        return batch

    def feed(items) -> None:
        for it in items:
            it.t_submit = time.perf_counter()
            ok = proc.submit(WorkItem(kind=WorkKind.gossip_aggregate,
                                      payload=it, run_batch=run_batch))
            if not ok:
                raise RuntimeError("the processor refused a work item")
        state["submitted"] += len(items)

    def run_batch(items):
        widths.append(len(items))
        batch = AggregateBatch()
        with h.annotate("bench:marshal_dispatch"):
            for it in items:
                batch.add(*it.trio)
            ticket, verdicts_of = batch.submit()

        def continuation(ok):
            with h.annotate("bench:continuation"):
                # a False batch: see the module's docstring
                verdicts = verdicts_of(ok) if ok else [False] * len(items)
                on_delivered(items, len(verdicts) == len(items)
                             and all(verdicts))

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
    feed([_Item(t) for t in valid_trios] + fill)
    proc.run_until_idle()
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    feed(next_batch())
    feed([_Item(t) for t in swapped_trios] + [_Item(it.trio) for it in fill])
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

    # --- after the window, on the same path: a batch in which one
    # selection proof's message (the one every aggregate shares) has a
    # flipped byte is still False
    with lock:
        state["phase"] = "after"
    feed(damaged_batch("flip_message", role=0))
    proc.run_until_idle()
    after_verdict = delivered[-1][2] if len(delivered) > n_flood else None

    # --- the window's numbers: three sets an aggregate, all with its latency
    win = delivered[state["i_open"]:state["i_close"]]
    lat_ms = np.repeat([(t - it.t_submit) * 1e3 for t, items, _ in win
                        for it in items], 3)
    n_sets = int(len(lat_ms))
    wrong = sum(3 * len(items) for _, items, ok in win if not ok)
    missing = 3 * (state["submitted"] - sum(len(x[1]) for x in delivered))
    window_s = state["t_close"] - state["t_open"]
    rate = n_sets / window_s
    lat_sorted = np.sort(lat_ms)
    p95 = float(lat_sorted[int(np.ceil(0.95 * n_sets)) - 1])

    def in_window(family: str, labels: dict) -> float:
        return layer_reader.evaluate(
            {"family": family, "labels": labels, "reduce": "sum"},
            h.before, h.after, {}, {}) or 0.0

    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    pk = {r: in_window("jaxbls_pubkey_cache_total", {"result": r})
          for r in ("miss", "hit")}
    messages = {kind: in_window("jaxbls_dispatch_messages_total",
                                {"kind": kind})
                for kind in ("sent", "distinct")}
    fallback = in_window("aggregate_batch_fallback_total", {})
    buckets = None
    if on_device:
        from lighthouse_tpu.crypto.jaxbls import backend as jb

        buckets = sorted(jb._seen_exec_buckets)
    emit(step="bls_aggregate_flood", backend=backend.name,
         pool_aggregates=n_pool, pool_load_secs=round(t_load, 2),
         reference_secs=round(t_ref, 2), reference_sample=sample,
         reference_swapped_role=ROLES[swapped_role], reference_verdicts=ref,
         warmup_s=h.notes["warmup_s"], setup_verdicts=setup_verdicts,
         after_window_tampered_verdict=after_verdict,
         window_s=window_s, batches_in_window=len(win),
         aggregates_in_window=n_sets // 3, sets_in_window=n_sets,
         sets_per_s=rate, latency_ms={
             "n": n_sets, "median": float(np.median(lat_ms)), "p95": p95,
             "max": float(lat_sorted[-1])},
         widths_seen=sorted(set(widths)), batches_total=len(delivered),
         pubkey_cache_in_window=pk, messages_in_window=messages,
         fallback_trios_in_window=fallback, buckets_seen=buckets,
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
    check(fallback == 0, f"{fallback} trios were verified again alone inside "
          "the window")
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
        {"name": "after_window_damaged",
         "what": "verdict of the batch with a damaged selection-proof "
         "message after the window",
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
