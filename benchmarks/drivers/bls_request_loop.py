"""Driver `bls_request_loop`: ONE verification request outstanding at a time
— submit, wait for the verdict, submit the next — through a real
BeaconProcessor with one worker.

Built from `bls_flood.py`'s parts (copied, not imported: that file may not
change). What differs is the shape of a request and what is timed. A request
is an ordered list of signature sets of unequal widths, given by `request`
as groups of the pool and counts, and it goes down one of two entries:

  signature_batch   every set of the request is added, in order, to a
                    `state_transition.block.SignatureBatch`, and its
                    `verify()` runs inside one `WorkItem(kind, run=...)`:
                    the seam `per_block_processing(VERIFY_BULK)` uses
                    (bls.verify_signature_sets -> the backend's batch lane).
                    `verify()` is synchronous, so the verdict is back when
                    `run` returns.
  urgent            the request's sets go to the backend's
                    `verify_signature_sets_urgent_async` (the call
                    `crypto/bls/hybrid.py` makes for a warm small batch) with
                    the API's own default coefficients; `run` returns
                    `(ticket, continuation)` and the request is timed to the
                    continuation. A backend with no urgent lane (the
                    pure-Python one, in the rehearsal tests) verifies in
                    place.

Latency per set = `proc.submit` of its request -> the verdict is back in the
work item; the window opens and closes on a delivery. The driver imports
nothing of the program that the parent of the PR that added it lacks.

Parameters (the workload file's `params`):
  backend            bls backend of the timed path ("jax")
  pool               npz of signature sets by group, relative to benchmarks/
                     (`<group>_keys`, `<group>_sigs`, `<group>_msgs`)
  request            [[group, count], ...] in the request's order; a group
                     taken whole keeps the pool's order, any other is a
                     fresh seeded choice and order for every request
  entry              "signature_batch" | "urgent"
  work_kind          the WorkKind of the work item ("gossip_block")
  bucket             [n_sets, n_pks]: the only padding bucket allowed (jax)
  preroll_requests   requests delivered before the window opens (set-up)
  reference_request  [[group, count], ...]: the sample the pure-Python
                     reference verifies, with and without a swapped
                     signature; no group may ask for more than `request`
  trace_window_s     profiler window of a traced run, after the window
  tamper_window      null; or "swap_signature" / "flip_message": damage one
                     seeded set of one window request and still expect True
                     — the control check_outputs.py runs
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import (  # benchmarks/common.py
    check, emit, runtime_call, verdict)

#: the damaged operands check_outputs.py puts into the window as controls
CONTROLS = ("swap_signature", "flip_message")

#: the backend keeps the key grids of its last 8 groupings; a request that
#: repeats one of the last 8 would hit that cache instead of marshalling
RECENT_REQUESTS = 8


def _fq(a) -> int:
    return int.from_bytes(bytes(a), "big")


def load_pool(path: str, groups) -> dict:
    """{group: [SignatureSet, ...]} for the groups asked for (big-endian
    affine coordinates, the npz wire format of scripts/gen_bench_fixtures.py;
    a group of one set may come without its leading axis)."""
    from lighthouse_tpu.crypto import bls

    z = np.load(path)
    pool = {}
    for g in groups:
        if f"{g}_keys" not in z.files:
            raise ValueError(f"{path} holds no group {g!r}")
        keys, sigs, msgs = z[f"{g}_keys"], z[f"{g}_sigs"], z[f"{g}_msgs"]
        if keys.ndim == 3:
            keys = keys[None]
        sets = []
        for i in range(len(msgs)):
            sig = ((_fq(sigs[i, 0, 0]), _fq(sigs[i, 0, 1])),
                   (_fq(sigs[i, 1, 0]), _fq(sigs[i, 1, 1])))
            pks = [bls.PublicKey((_fq(k[0]), _fq(k[1]))) for k in keys[i]]
            sets.append(bls.SignatureSet(bls.Signature(sig), pks,
                                         bytes(msgs[i])))
        pool[g] = sets
    return pool


def tampered(sset, donor, how: str, byte: int = 0):
    """`sset` damaged: `donor`'s signature, or one message byte flipped."""
    from lighthouse_tpu.crypto import bls

    if how == "swap_signature":
        return bls.SignatureSet(donor.signature, sset.signing_keys,
                                sset.message)
    if how == "flip_message":
        msg = bytearray(sset.message)
        msg[byte % len(msg)] ^= 0x01
        return bls.SignatureSet(sset.signature, sset.signing_keys, bytes(msg))
    raise ValueError(f"unknown tampering {how!r}")


class _Request:
    """One request: its sets in order, when it was submitted, its verdict."""

    __slots__ = ("sets", "t_submit", "t_done", "verdict")

    def __init__(self, sets):
        self.sets = sets
        self.t_submit = 0.0
        self.t_done = None
        self.verdict = None


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
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.observability import device as obs_device
    from lighthouse_tpu.state_transition.block import SignatureBatch

    shape = [(str(g), int(c)) for g, c in params["request"]]
    ref_shape = [(str(g), int(c)) for g, c in params["reference_request"]]
    entry = params["entry"]
    if entry not in ("signature_batch", "urgent"):
        raise ValueError(f"unknown entry {entry!r}")
    kind = WorkKind[params["work_kind"]]
    n_preroll = int(params["preroll_requests"])
    if n_preroll < 1:
        raise ValueError("preroll_requests must be at least 1")
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    pool = load_pool(os.path.join(h.bench_dir, params["pool"]),
                     {g for g, _ in shape + ref_shape})
    t_load = time.perf_counter() - t0
    asked = dict(shape)
    if len(asked) != len(shape) or len(dict(ref_shape)) != len(ref_shape):
        raise ValueError("a group appears once in a request")
    for g, c in shape:
        if not 1 <= c <= len(pool[g]):
            raise ValueError(f"request asks for {c} of group {g!r}, the "
                             f"pool holds {len(pool[g])}")
    for g, c in ref_shape:
        if not 1 <= c <= asked.get(g, 0):
            raise ValueError(f"reference_request asks for {c} of group "
                             f"{g!r}, a request holds {asked.get(g, 0)}")
    n_sets = sum(c for _, c in shape)
    # the configuration states the deployment's shape; the traffic keeps it
    width = {g: len(pool[g][0].signing_keys) for g in pool}
    stated = {"sets_per_request": n_sets,
              "keys_per_request": sum(c * width[g] for g, c in shape)}
    if "att" in asked:
        stated["keys_per_set"] = width["att"]
    for key, got in stated.items():
        if key in config and int(config[key]) != got:
            raise ValueError(f"the configuration states {key} = "
                             f"{config[key]}, the request gives {got}")

    # --- drawing requests: a group taken whole keeps the pool's order (the
    # proposal, then the RANDAO reveal), any other is a fresh seeded choice
    # and order; no request repeats one of the last few, so the backend
    # marshals the keys of every request
    distinct = math.prod(math.comb(len(pool[g]), c) for g, c in shape)
    recent: deque = deque(maxlen=min(RECENT_REQUESTS, distinct - 1))

    def draw(groups, exclude=None) -> list:
        """[(group, index), ...] in the request's order."""
        picks = []
        for g, c in groups:
            free = [i for i in range(len(pool[g]))
                    if exclude is None or (g, i) not in exclude]
            if c == len(free):
                picks += [(g, i) for i in free]
            else:
                picks += [(g, free[int(j)]) for j in
                          rng.choice(len(free), size=c, replace=False)]
        return picks

    def next_request() -> _Request:
        while True:
            picks = tuple(draw(shape))
            if picks not in recent:
                recent.append(picks)
                return _Request([pool[g][i] for g, i in picks])

    def damaged_request(how: str, victim: int | None = None) -> _Request:
        """A request with one set damaged: a seeded one, or `victim`."""
        req = next_request()
        if victim is None:
            victim = int(rng.integers(n_sets))
        req.sets[victim] = tampered(req.sets[victim], donor_for(
            req.sets[victim]), how, byte=int(rng.integers(32)))
        return req

    everything = [s for g in sorted(pool) for s in pool[g]]

    def donor_for(sset):
        """A seeded set of the pool other than `sset`."""
        while True:
            d = everything[int(rng.integers(len(everything)))]
            if d is not sset:
                return d

    # --- the plain reference: the pure-Python backend on a seeded sample of
    # the request's shape, and on the sample with a seeded swap. Its time is
    # not set-up.
    t0 = time.perf_counter()
    sample_picks = draw(ref_shape)
    sample = [pool[g][i] for g, i in sample_picks]
    v = int(rng.integers(len(sample)))
    swapped = list(sample)
    swapped[v] = tampered(sample[v], donor_for(sample[v]), "swap_signature")
    bls.set_backend("python")
    ref = [bls.verify_signature_sets(sample),
           bls.verify_signature_sets(swapped)]
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref

    backend = bls.set_backend(params["backend"])
    on_device = backend.name == "jax"
    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve: a traced run only
    obs_device.set_enabled(bool(trace))

    proc = BeaconProcessor(BeaconProcessorConfig(num_workers=1))
    cond = threading.Condition()
    delivered: list = []      # requests in the order their verdicts came
    state = {"phase": "setup", "feeding": False, "t_open": None,
             "t_close": None, "loop_requests": 0, "submitted": 0}
    tamper = params.get("tamper_window")

    def verify_urgent(sets):
        """What crypto/bls/hybrid.py does with a warm small batch."""
        rands = bls_api._default_rands(len(sets))
        submit = getattr(backend, "verify_signature_sets_urgent_async", None)
        if submit is None:
            return bls_api._ReadyHandle(
                backend.verify_signature_sets(sets, rands))
        return submit(sets, rands)

    def feed(req: _Request, claimed: bool = False) -> None:
        """Submit one request; `claimed` when on_delivered counted it
        already, under the lock that the end of the loop waits on."""

        def run_item():
            if entry == "signature_batch":
                with h.annotate("bench:signature_batch"):
                    batch = SignatureBatch()
                    for s in req.sets:
                        batch.add(s)
                    verdict = batch.verify()
                on_delivered(req, verdict)
                return None
            with h.annotate("bench:urgent_dispatch"):
                ticket = verify_urgent(req.sets)

            def continuation(verdict):
                with h.annotate("bench:continuation"):
                    on_delivered(req, verdict)

            return ticket, continuation

        if not claimed:
            with cond:
                state["submitted"] += 1
        req.t_submit = time.perf_counter()
        if not proc.submit(WorkItem(kind=kind, run=run_item)):
            raise RuntimeError("the processor refused a work item")

    def on_delivered(req: _Request, verdict) -> None:
        req.t_done = time.perf_counter()
        req.verdict = bool(verdict)
        with cond:
            delivered.append(req)
            cond.notify_all()
            if state["phase"] != "loop":
                return
            state["loop_requests"] += 1
            if state["loop_requests"] == n_preroll:
                # the window's edges are deliveries: whole requests only
                state["t_open"] = h.open_window()
                state["i_open"] = len(delivered)
            elif (state["t_open"] is not None and state["t_close"] is None
                  and req.t_done >= state["t_open"] + seconds):
                state["t_close"] = h.close_window()
                state["i_close"] = len(delivered)
            feeding = state["feeding"]
            if feeding:
                state["submitted"] += 1
        if feeding:
            if tamper and state["t_open"] is not None and not state.get(
                    "tampered"):
                state["tampered"] = True
                feed(damaged_request(tamper), claimed=True)
            else:
                feed(next_request(), claimed=True)

    def wait_for(done, limit: float, what: str) -> None:
        with cond:
            if not cond.wait_for(done, timeout=limit):
                raise RuntimeError(f"{what} within {limit} s")

    # --- set-up on the timed path's own bucket: the reference's sample
    # filled up to one whole request (compiles), one more valid request, and
    # the reference's swapped sample with the same fill, so both backends
    # give their verdicts on the same operands
    sampled = dict(ref_shape)
    fill = draw([(g, c - sampled.get(g, 0)) for g, c in shape
                 if c > sampled.get(g, 0)], exclude=set(sample_picks))

    def filled(sample_sets) -> _Request:
        """The sample's sets first in each group, then the group's fill."""
        by_group: dict = {g: [] for g, _ in shape}
        for (g, _), s in zip(sample_picks, sample_sets):
            by_group[g].append(s)
        for g, i in fill:
            by_group[g].append(pool[g][i])
        return _Request([s for g, _ in shape for s in by_group[g]])

    # its keys stay in the backend's cache for a few requests, as any other's
    recent.append(tuple(p for g, _ in shape
                        for p in sample_picks + fill if p[0] == g))

    h.log.label = "warmup"
    t0 = time.perf_counter()
    feed(filled(sample))
    proc.run_until_idle()
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    feed(next_request())
    feed(filled(swapped))
    proc.run_until_idle()
    setup_verdicts = [r.verdict for r in delivered]
    runtime_call()

    # --- the loop: one request, then one worker pumps as the node's does;
    # each delivery submits the next
    with cond:
        state["phase"] = "loop"
        state["feeding"] = True
    feed(next_request())
    proc.start()
    try:
        limit = seconds + 300
        wait_for(lambda: state["t_close"] is not None, limit,
                 "the window did not close")
        if trace:
            h.trace_begin()
            time.sleep(float(params["trace_window_s"]))
            h.trace_end()
        with cond:
            state["feeding"] = False
        wait_for(lambda: len(delivered) == state["submitted"], 120,
                 "the last request did not come back")
    finally:
        proc.stop()
    n_loop = len(delivered)

    # --- after the window, on the same path: a request whose LAST set (a
    # block's sync aggregate) has one flipped message byte is still False
    with cond:
        state["phase"] = "after"
    feed(damaged_request("flip_message", victim=n_sets - 1))
    proc.run_until_idle()
    after_verdict = delivered[-1].verdict if len(delivered) > n_loop else None

    # --- the window's numbers
    win = delivered[state["i_open"]:state["i_close"]]
    req_ms = np.array([(r.t_done - r.t_submit) * 1e3 for r in win])
    lat_ms = np.repeat(req_ms, [len(r.sets) for r in win])
    n_win_sets = int(len(lat_ms))
    wrong = sum(len(r.sets) for r in win if not r.verdict)
    missing = (state["submitted"] - len(delivered)) * n_sets
    window_s = state["t_close"] - state["t_open"]
    lat_sorted = np.sort(lat_ms)
    p95 = float(lat_sorted[int(np.ceil(0.95 * n_win_sets)) - 1])
    p50 = float(np.median(req_ms))     # over requests, not over their sets

    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    pk = {r: layer_reader.evaluate(
        {"family": "jaxbls_pubkey_cache_total", "labels": {"result": r},
         "reduce": "sum"}, h.before, h.after, {}, {}) or 0.0
        for r in ("miss", "hit")}
    lanes = {ln: layer_reader.evaluate(
        {"family": "jaxbls_pipeline_submitted_total", "labels": {"lane": ln},
         "reduce": "sum"}, h.before, h.after, {}, {}) or 0.0
        for ln in ("batch", "urgent")}
    buckets = None
    if on_device:
        from lighthouse_tpu.crypto.jaxbls import backend as jb

        buckets = sorted(jb._seen_exec_buckets)
    emit(step="bls_request_loop", backend=backend.name, entry=entry,
         work_kind=kind.name, pool_load_secs=round(t_load, 2),
         request=shape, sets_per_request=n_sets,
         keys_per_request=stated["keys_per_request"],
         reference_secs=round(t_ref, 2), reference_sample=sample_picks,
         reference_swapped=v, reference_verdicts=ref,
         warmup_s=h.notes["warmup_s"], setup_verdicts=setup_verdicts,
         after_window_tampered_verdict=after_verdict, window_s=window_s,
         requests_in_window=len(win), sets_in_window=n_win_sets,
         requests_per_s=len(win) / window_s,
         sets_per_s=n_win_sets / window_s,
         request_latency_ms={
             "n": len(win), "median": p50,
             "min": float(req_ms.min()), "max": float(req_ms.max()),
             "p95_over_sets": p95},
         requests_total=len(delivered), pubkey_cache_in_window=pk,
         lanes_in_window=lanes, buckets_seen=buckets,
         processor_errors=errors, hybrid_routes=hybrid,
         dropped=sum(proc.dropped.values()),
         generator="closed loop, one outstanding: lateness does not apply",
         tamper_window=tamper)

    # the run's own conditions: a breach is no result at all
    check(not any(errors.values()), f"the processor swallowed an error: "
          f"{errors}")
    check(not any(hybrid.values()), "the hybrid router served a verification")
    check(sum(proc.dropped.values()) == 0, "the processor dropped work")
    if on_device:
        want = tuple(params["bucket"])
        check(buckets == [want], f"backend ran buckets {buckets}, expected "
              f"only {want}")
        check(pk["miss"] == len(win) and pk["hit"] == 0,
              f"pubkey cache in the window {pk}: expected one miss a "
              f"request ({len(win)}) and no hit")
        lane = "urgent" if entry == "urgent" else "batch"
        check(lanes[lane] == len(win) and sum(lanes.values()) == len(win),
              f"dispatcher lanes in the window {lanes}: expected "
              f"{len(win)} on {lane} alone")

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
         "whole request (sample, sample with the swap), against the "
         "pure-Python backend's verdicts",
         "value": setup_verdicts[0::2], "limit": ref},
        {"name": "window_wrong", "what": "sets of the window with a wrong "
         "verdict",
         "value": wrong, "limit": 0},
        {"name": "window_missing", "what": "sets submitted whose verdict "
         "never came",
         "value": missing, "limit": 0},
        {"name": "after_window_damaged",
         "what": "verdict of the request with a flipped byte in its last "
         "set's message, after the window",
         "value": after_verdict, "limit": False},
    ]
    return {
        "correct": verdict(compared),
        "compared": compared,
        "attempted": n_win_sets + missing,
        "failed": wrong + missing,
        "end_to_end": {
            "bls_verify_p95_ms": {"value": p95, "unit": "ms"},
            "request_p50_ms": {"value": p50, "unit": "ms"},
        },
    }
