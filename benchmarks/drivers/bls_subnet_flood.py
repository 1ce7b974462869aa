"""Driver `bls_subnet_flood`: one slot's unaggregated attestations as an
all-subnets node is offered them - single-key signature sets on messages
that whole committees share - in a flood that never lets the verify queue
empty, coalesced at the program's own batch width.

An unaggregated attestation is ONE validator's signature on its
committee's AttestationData root. `BeaconChain.prepare_unaggregated_
attestations` (chain/beacon_chain.py) builds, for each, the set
`indexed_attestation_set` makes over that ONE attesting index: the pubkey
cache's key object, its validator index and the cache's table
(`signing_indices`, `signing_registry`). So do the sets here. They travel
as `gossip_attestation` `WorkItem`s through a one-worker `BeaconProcessor`
whose `max_attestation_batch` is `batch_sets` (explicit, so no autotune plan
resizes it), and reach `bls.verify_signature_sets_async` -> the backend's
batch lane -> `PipelinedDispatcher` as `bls_flood`'s do: its closed loop
(`backlog_sets` outstanding at all times, each delivery feeds one batch
more) and its window whose edges are deliveries. The registry's keys reach
the device as `bls_registry_block_loop`'s do: derived on the plain
reference's arithmetic (that driver's `derive_registry`), compressed, taken
in by `ValidatorPubkeyCache.import_new_pubkeys` -> `crypto/jaxbls/
registry.py` `PubkeyTable`, once, at set-up, and compared with the
registry's bytes.

The pool (data/gen_subnet_pool.py) holds one slot: `committees` committees
of one size, a head message and a late message a committee with A = a H(M)
and D = d H(M) each, and who votes late. Member i's signature A + i D is
derived at set-up by the reference (reference/bls_subnet_spec.py
`mint_members`). Each dispatch is the next `batch_sets` of a `--seed`ed
permutation of the slot's attestations, reshuffled every pass, so every
dispatch draws from all committees, as the interleaved subnets of an
all-subnets node do. `--seed` also draws the reference's sample and every
damage.

Latency of a set = `proc.submit` -> its batch's verdict delivered. The
driver's first act is the import of the registry module: a tree without it
fails at once, before any data is made or program compiled.

Parameters (the workload file's `params`):
  backend           bls backend of the timed path ("jax")
  pool              npz of data/gen_subnet_pool.py, relative to benchmarks/
  batch_sets        sets per dispatch (the processor's max_attestation_batch)
  backlog_sets      work items outstanding at all times
  bucket            [n_sets, n_pks]: the only padding bucket allowed (jax)
  preroll_batches   batches delivered before the window opens (set-up)
  table_sample_rows rows of the table compared coordinate by coordinate
                    with keys the reference decompressed
  reference_sets    sets the references verify (the pure-Python backend
                    AND reference/bls_subnet_spec.py), with and without a
                    swapped signature
  trace_window_s    profiler window of a traced run, after the window
  tamper_window     null; or one of CONTROLS: damage one seeded set of one
                    window batch and still expect True - the control
                    check_outputs.py runs, `correct` must be false
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import threading
import time

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import (  # benchmarks/common.py
    BenchFailure, check, emit, runtime_call, verdict)

#: the damaged operands check_outputs.py puts into the window as controls
CONTROLS = ("swap_signature", "flip_message", "replace_signer")

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind: str, name: str):
    path = os.path.join(_BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the Electra block's driver: the derived registry (`registry_secrets`,
#: `derive_registry`, `slot_partition`) and what of a state the pubkey
#: cache reads exist once, there
registry = _load("drivers", "bls_registry_block_loop")
#: the plain reference beside this directory: no signature and no key here
#: is the work of the program under test
_spec = _load("reference", "bls_subnet_spec")


# ---------------------------------------------------------------- the pool


def committee_partition(slot_validators, committees: int,
                        pool_seed: int) -> np.ndarray:
    """(committees, size) validator indices: the slot's validators split
    into committees, each row ascending. Plain modular arithmetic and a
    sort, as `slot_partition` is: validator v's rank is the order of
    (v A + B) mod a prime."""
    vals = np.asarray(slot_validators, np.int64)
    if len(vals) % committees:
        raise ValueError("the slot divides into whole committees")
    raw = hashlib.sha512(b"subnet-pool:committees"
                         + pool_seed.to_bytes(8, "big")).digest()
    prime = registry._SHUFFLE_PRIME
    mult = int.from_bytes(raw[:8], "big") % (prime - 1) + 1
    shift = int.from_bytes(raw[8:16], "big") % prime
    key = (vals.astype(np.uint64) * np.uint64(mult)
           + np.uint64(shift)) % np.uint64(prime)
    order = vals[np.argsort(key, kind="stable")]
    return np.sort(order.reshape(committees, len(vals) // committees), axis=1)


def load_pool(path: str) -> dict:
    """The pool of data/gen_subnet_pool.py: meta, the committees (derived
    here), who votes late, and per message its bytes and (A, D). No key and
    no signature: both are derived."""
    z = np.load(path)
    meta = json.loads(bytes(z["meta"]))
    members = committee_partition(
        registry.slot_partition(meta["validators"], meta["slots"],
                                meta["registry_seed"])[meta["slot"]],
        meta["committees"], meta["pool_seed"])
    size = members.shape[1]
    return {
        "meta": meta,
        "members": members,
        "late": np.unpackbits(z["late_mask"], axis=1)[:, :size].astype(bool),
        "head_msgs": [bytes(m) for m in z["head_msgs"]],
        "late_msgs": [bytes(m) for m in z["late_msgs"]],
        "head_points": [(registry._g2(p[0]), registry._g2(p[1]))
                        for p in z["head_points"]],
        "late_points": [(registry._g2(p[0]), registry._g2(p[1]))
                        for p in z["late_points"]],
    }


def mint_slot(pool: dict, ref=_spec) -> list:
    """[(validator index, message, signature as an affine G2 point)] of the
    slot's attestations, committee by committee, members ascending: each
    member's signature on its committee's head message, or on the late one
    where the pool says so, by the reference's `mint_members`."""
    n = pool["meta"]["validators"]
    out = []
    for c, members in enumerate(pool["members"]):
        late = pool["late"][c]
        sigs = {}
        for which, points, msgs in ((~late, pool["head_points"],
                                     pool["head_msgs"]),
                                    (late, pool["late_points"],
                                     pool["late_msgs"])):
            voters = members[which]
            minted = ref.mint_members(*points[c], voters, n)
            sigs.update((int(v), (msgs[c], s)) for v, s in zip(voters, minted))
        out += [(int(v),) + sigs[int(v)] for v in members]
    return out


class _Item:
    """One work item's payload: the set and when it was submitted."""

    __slots__ = ("sset", "t_submit")

    def __init__(self, sset):
        self.sset = sset
        self.t_submit = 0.0


def run(config, params, seed, seconds, trace, h) -> dict:
    try:
        from lighthouse_tpu.crypto.jaxbls import registry as jax_registry
    except ImportError as e:
        raise BenchFailure(
            "this program keeps no registry table on the device "
            f"({type(e).__name__}: {e}): the cell {h.workload} cannot run "
            "on it") from e

    from lighthouse_tpu.chain.beacon_processor import (
        BeaconProcessor,
        BeaconProcessorConfig,
        WorkItem,
        WorkKind,
    )
    from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.observability import device as obs_device

    B = int(params["batch_sets"])
    backlog = int(params["backlog_sets"])
    k = int(params["reference_sets"])
    if backlog % B:
        raise ValueError("backlog_sets must be whole batches")
    if not 2 <= k <= B:
        raise ValueError("reference_sets must be 2 to batch_sets")
    rng = np.random.default_rng(seed)
    ref = _spec

    # --- the pool, and what the configuration states about it
    t0 = time.perf_counter()
    pool = load_pool(os.path.join(h.bench_dir, params["pool"]))
    t_load = time.perf_counter() - t0
    meta = pool["meta"]
    n_val = meta["validators"]
    n_pool = meta["attestations"]
    stated = {"validators": n_val, "slots_per_epoch": meta["slots"],
              "committees_per_slot": meta["committees"],
              "committee_size": meta["committee_size"],
              "attestations_per_slot": n_pool, "keys_per_set": 1,
              "sets_per_dispatch": B}
    for key, got in stated.items():
        if key in config and int(config[key]) != got:
            raise ValueError(f"the configuration states {key} = "
                             f"{config[key]}, the cell runs {got}")
    if n_pool < backlog + B:
        raise ValueError(f"a slot of {n_pool} attestations is too small for "
                         f"a backlog of {backlog}")

    # --- the registry: keys derived, compressed, taken in by the chain's
    # pubkey cache, which feeds the backend's table on the device
    t0 = time.perf_counter()
    a, d = registry.registry_secrets(meta["registry_seed"])
    points = registry.derive_registry(n_val, a, d)
    t_keys = time.perf_counter() - t0
    backend = bls.set_backend(params["backend"])
    on_device = backend.name == "jax"
    t0 = time.perf_counter()
    state = registry._State(
        [registry._Validator(bls.PublicKey(p).serialize()) for p in points])
    table = (backend.install_registry() if on_device
             else jax_registry.PubkeyTable())
    cache = ValidatorPubkeyCache(table=table)
    cache.import_new_pubkeys(state)
    t_cache = time.perf_counter() - t0
    check(len(cache) == len(table) == n_val,
          f"the cache holds {len(cache)} keys, the table {len(table)}, the "
          f"registry {n_val}")

    # --- the slot's attestations: signatures by the reference, sets as
    # `indexed_attestation_set` builds them over one attesting index
    t0 = time.perf_counter()
    minted = mint_slot(pool, ref)
    t_mint = time.perf_counter() - t0
    check(len(minted) == n_pool, f"minted {len(minted)} attestations, the "
          f"pool's meta says {n_pool}")

    def make_set(index: int, message: bytes, sig):
        return bls.SignatureSet(bls.Signature(sig), (cache.pubkeys[index],),
                                message, signing_indices=[index],
                                signing_registry=table)

    t0 = time.perf_counter()
    atts = [make_set(*m) for m in minted]
    t_sets = time.perf_counter() - t0

    def damaged(how: str, victim: int):
        """Attestation `victim` damaged: another's signature, one byte of
        its message flipped, or its signer exchanged for another
        validator (key and index together, as a set built for the wrong
        committee member would carry them)."""
        s = atts[victim]
        index = int(s.signing_indices[0])
        other = int(rng.integers(n_pool - 1))
        other += other >= victim
        if how == "swap_signature":
            return make_set(index, s.message, atts[other].signature.point)
        if how == "flip_message":
            msg = bytearray(s.message)
            msg[other % 32] ^= 0x01
            return make_set(index, bytes(msg), s.signature.point)
        if how == "replace_signer":
            return make_set(int(atts[other].signing_indices[0]), s.message,
                            s.signature.point)
        raise ValueError(f"unknown damage {how!r}")

    # --- the plain references, on a seeded sample and on the sample with a
    # seeded swap: the pure-Python backend on the sets themselves, and
    # bls_subnet_spec on keys it decompresses from the registry's bytes.
    # Their time is not set-up.
    t0 = time.perf_counter()
    sample = [int(i) for i in rng.choice(n_pool, size=k, replace=False)]
    swapped = [atts[i] for i in sample]
    swapped[1] = make_set(int(atts[sample[1]].signing_indices[0]),
                          atts[sample[1]].message,
                          atts[sample[0]].signature.point)
    bls.set_backend("python")
    ref_python = [bls.verify_signature_sets([atts[i] for i in sample]),
                  bls.verify_signature_sets(swapped)]
    bls.set_backend(params["backend"])

    def for_reference(sset) -> tuple:
        return (sset.signature.point,
                cache.pubkey_bytes[int(sset.signing_indices[0])],
                sset.message)

    zs = [int(z) for z in rng.integers(1, 1 << 63, size=k)]
    ref_keys: dict = {}
    ref_hashed: dict = {}
    ref_spec = [ref.verify_batch([for_reference(s) for s in sets], zs,
                                 ref_keys, ref_hashed)
                for sets in ([atts[i] for i in sample], swapped)]
    # the table on the device against the registry, as the Electra block's
    # driver compares it: every key as derived is what the registry's bytes
    # compress, a digest over all rows, seeded rows coordinate by coordinate
    # against keys the reference decompressed
    n_rows = min(int(params["table_sample_rows"]), n_val)
    rows = np.sort(rng.choice(n_val, size=n_rows, replace=False))
    want_rows = [ref.base.decompress_key(cache.pubkey_bytes[i]) for i in rows]
    bytes_wrong = ref.base.keys_not_of(cache.pubkey_bytes, points)
    want_digest = ref.base.registry_digest(points)
    t_ref = time.perf_counter() - t0
    h.reference_seconds += t_ref
    t0 = time.perf_counter()
    got_rows = table.rows(rows)
    table_checks = {
        "bytes": bytes_wrong,
        "digest": int(table.digest() != want_digest),
        "rows": sum(1 for g, w in zip(got_rows, want_rows) if g != w),
        "spare": table.spare_nonzero(),
    }
    t_table = time.perf_counter() - t0
    del points, state

    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve and so run a batch's stages one after
    # the other: a traced run only
    obs_device.set_enabled(bool(trace))

    proc = BeaconProcessor(BeaconProcessorConfig(max_attestation_batch=B,
                                                 num_workers=1))
    lock = threading.Lock()
    delivered: list = []      # (t, items, verdict) per batch, in order
    widths: list = []
    st = {"phase": "setup", "feeding": False, "t_open": None,
          "t_close": None, "flood_batches": 0, "submitted": 0}
    window_closed = threading.Event()
    tamper = params.get("tamper_window")

    def stream():
        """Attestations: one seeded permutation of the slot after another."""
        while True:
            yield from (int(i) for i in rng.permutation(n_pool))

    indices = stream()

    def next_batch() -> list:
        return [_Item(atts[next(indices)]) for _ in range(B)]

    def damaged_batch(how: str) -> list:
        """A batch with one seeded set damaged."""
        batch = next_batch()
        victim = int(rng.integers(n_pool))
        batch[victim % B] = _Item(damaged(how, victim))
        return batch

    def feed(items) -> None:
        for it in items:
            it.t_submit = time.perf_counter()
            ok = proc.submit(WorkItem(kind=WorkKind.gossip_attestation,
                                      payload=it, run_batch=run_batch))
            if not ok:
                raise RuntimeError("the processor refused a work item")
        st["submitted"] += len(items)

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
            if st["phase"] != "flood":
                return
            st["flood_batches"] += 1
            if st["flood_batches"] == int(params["preroll_batches"]):
                # the window's edges are deliveries, so its rate is over
                # whole batches and not cut mid-batch
                st["t_open"] = h.open_window()
                st["i_open"] = len(delivered)
            elif (st["t_open"] is not None and st["t_close"] is None
                  and t >= st["t_open"] + seconds):
                st["t_close"] = h.close_window()
                st["i_close"] = len(delivered)
                window_closed.set()
            feeding = st["feeding"]
        if feeding:
            if tamper and st["t_open"] is not None and not st.get("tampered"):
                st["tampered"] = True
                feed(damaged_batch(tamper))
            else:
                feed(next_batch())

    # --- set-up on the timed path's own bucket: the references' sample
    # filled up to one batch with valid sets (compiles), one more valid
    # batch, and the references' swapped sample with the same fill, so all
    # three give their verdicts on the same operands
    fill = next_batch()[k:]
    h.log.label = "warmup"
    t0 = time.perf_counter()
    feed([_Item(atts[i]) for i in sample] + fill)
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
        st["phase"] = "flood"
        st["feeding"] = True
    for _ in range(backlog // B):
        feed(next_batch())
    proc.start()
    try:
        limit = seconds + 300
        if not window_closed.wait(timeout=limit):
            raise RuntimeError(f"the window did not close in {limit} s")
        if trace:
            h.trace_begin()
            time.sleep(float(params["trace_window_s"]))
            h.trace_end()
        with lock:
            st["feeding"] = False
        t_end = time.perf_counter() + 120
        while not proc.queues_empty():
            if time.perf_counter() > t_end:
                raise RuntimeError("the processor did not drain")
            time.sleep(0.005)
    finally:
        proc.stop()
    n_flood = len(delivered)

    # --- after the window, on the same path: a batch in which one byte of
    # ONE set's message (which its committee shares) is flipped is False
    with lock:
        st["phase"] = "after"
    feed(damaged_batch("flip_message"))
    proc.run_until_idle()
    after_verdict = delivered[-1][2] if len(delivered) > n_flood else None

    # --- the window's numbers
    win = delivered[st["i_open"]:st["i_close"]]
    lat_ms = np.array([(t - it.t_submit) * 1e3 for t, items, _ in win
                       for it in items])
    n_sets = int(len(lat_ms))
    wrong = sum(len(items) for _, items, ok in win if not ok)
    missing = st["submitted"] - sum(len(x[1]) for x in delivered)
    window_s = st["t_close"] - st["t_open"]
    rate = n_sets / window_s
    lat_sorted = np.sort(lat_ms)
    p95 = float(lat_sorted[int(np.ceil(0.95 * n_sets)) - 1])
    distinct = [len({it.sset.message for it in items}) for _, items, _ in win]

    def in_window(family: str, **labels) -> float:
        return layer_reader.evaluate(
            {"family": family, "labels": labels, "reduce": "sum"},
            h.before, h.after, {}, {}) or 0.0

    errors = registry.family_values("beacon_processor_errors_total")
    hybrid = registry.family_values("bls_hybrid_route_total")
    key_slots = {src: in_window("jaxbls_registry_keys_total", source=src)
                 for src in ("table", "packed")}
    lanes = {ln: in_window("jaxbls_pipeline_submitted_total", lane=ln)
             for ln in ("batch", "urgent")}
    failed_dispatches = in_window("jaxbls_pipeline_resolved_total",
                                  outcome="error")
    buckets = None
    if on_device:
        from lighthouse_tpu.crypto.jaxbls import backend as jb

        buckets = sorted(jb._seen_exec_buckets)
    emit(step="bls_subnet_flood", backend=backend.name,
         pool_load_secs=round(t_load, 2), registry_keys_secs=round(t_keys, 2),
         cache_secs=round(t_cache, 2), mint_secs=round(t_mint, 2),
         pool_sets_secs=round(t_sets, 2), table_check_secs=round(t_table, 2),
         validators=n_val, table_rows=len(table),
         table_capacity=table.capacity, table_bytes=table.nbytes,
         attestations=n_pool, committees=meta["committees"],
         late_voters=int(pool["late"].sum()),
         reference_secs=round(t_ref, 2), reference_sample=sample,
         reference_verdicts={"python": ref_python, "spec": ref_spec},
         warmup_s=h.notes["warmup_s"], setup_verdicts=setup_verdicts,
         after_window_tampered_verdict=after_verdict,
         window_s=window_s, batches_in_window=len(win), sets_in_window=n_sets,
         sets_per_s=rate, latency_ms={
             "n": n_sets, "median": float(np.median(lat_ms)), "p95": p95,
             "max": float(lat_sorted[-1])},
         distinct_messages_a_batch={
             "min": min(distinct), "mean": float(np.mean(distinct)),
             "max": max(distinct)},
         widths_seen=sorted(set(widths)), batches_total=len(delivered),
         key_slots_in_window=key_slots, lanes_in_window=lanes,
         buckets_seen=buckets, processor_errors=errors, hybrid_routes=hybrid,
         failed_dispatches_in_window=failed_dispatches,
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
        check(lanes["urgent"] == 0 and failed_dispatches == 0,
              f"dispatcher lanes in the window {lanes}, "
              f"{failed_dispatches} failed dispatches: expected the batch "
              "lane alone and none failed")
        check(key_slots["packed"] == 0 and key_slots["table"] > 0,
              f"keys of the window by source {key_slots}: a set took the "
              "packed prepare")

    # --- correct: each number compared, beside its limit (all exact)
    compared = [
        {"name": "registry_bytes", "what": "keys as derived that are not "
         "what the registry's 48 bytes compress, by the reference's curve "
         "equation and sign rule, over all validators",
         "value": table_checks["bytes"], "limit": 0},
        {"name": "table_digest", "what": "table on the device against the "
         "registry's keys as derived: digests over all rows differ (0 = "
         "equal)",
         "value": table_checks["digest"], "limit": 0},
        {"name": "table_rows", "what": f"rows of {n_rows} seeded ones whose "
         "coordinates differ from the keys the reference decompressed",
         "value": table_checks["rows"], "limit": 0},
        {"name": "table_spare", "what": "nonzero limbs in the table's spare "
         "rows",
         "value": table_checks["spare"], "limit": 0},
        {"name": "reference_python", "what": "the pure-Python backend's "
         "verdicts (valid sample, sample with a swap)",
         "value": ref_python, "limit": [True, False]},
        {"name": "reference_spec", "what": "reference/bls_subnet_spec.py's "
         "verdicts on the same sample, keys decompressed from the "
         "registry's bytes",
         "value": ref_spec, "limit": [True, False]},
        {"name": "setup", "what": "set-up verdicts (valid, valid, one "
         "swapped signature)",
         "value": setup_verdicts, "limit": [True, True, False]},
        {"name": "timed_against_references",
         "what": "the timed backend on the references' own operands in a "
         "full batch at the timed bucket (sample, sample with the swap), "
         "against both references' verdicts",
         "value": [setup_verdicts[0::2]] * 2, "limit": [ref_python, ref_spec]},
        {"name": "window_wrong", "what": "sets of the window with a wrong "
         "verdict",
         "value": wrong, "limit": 0},
        {"name": "window_missing", "what": "sets submitted whose verdict "
         "never came",
         "value": missing, "limit": 0},
        {"name": "after_window_damaged", "what": "verdict of the batch "
         "after the window in which one byte of one set's shared message "
         "was flipped",
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
