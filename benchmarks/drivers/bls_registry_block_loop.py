"""Driver `bls_registry_block_loop`: an Electra block's signature sets as ONE
batch, one block outstanding, the signers' keys gathered by validator index
from the registry table the backend keeps on the device.

A block here is what `verify_entire_block` collects from a well-packed
Electra block (EIP-7549): the proposal and the RANDAO reveal (one key each),
`attestations_per_block` on-chain attestations that each aggregate a WHOLE
slot's committees (~32,400 keys at 1,048,576 validators), and the sync
aggregate (512 keys): 11 sets, ~260,000 keys. The sets are added in block
order to a `state_transition.block.SignatureBatch` whose `verify()` runs
inside one `gossip_block` `WorkItem` of a one-worker `BeaconProcessor`
(bls.verify_signature_sets -> the backend's batch lane): the seam
`per_block_processing(VERIFY_BULK)` uses. Every set carries its validator
indices beside its keys, as the set builders of
`state_transition/signature_sets.py` make them, and the registry's keys
reach the device through `ValidatorPubkeyCache.import_new_pubkeys` ->
`crypto/jaxbls/registry.py` `PubkeyTable`, once, at set-up.

The registry is not a file. Validator i's secret key is a + i d (mod r), a
and d from the pool's `registry_seed`, so its public key is P0 + i D and the
whole registry comes from chained additions (one batch inversion a level,
on the plain reference's own curve arithmetic: reference/
bls_registry_spec.py, which imports nothing of the program);
an aggregate over any set of validators is ONE G2 multiplication by the sum
of their secrets, which is how `data/gen_electra_pool.py` minted the pool:
the epoch's `slots` whole-slot attestations (a seeded partition of the
registry, participation drawn per slot), the proposal, the RANDAO reveal
and the sync aggregate. `--seed` draws every block's choice and order of
attestations, the reference's sample and every damage.

Latency per set = `proc.submit` of its block -> the block's verdict is back
in the work item, so the 95th percentile is over whole blocks. The driver's
first act is the import of the registry module: a tree without it fails at
once, before any data is made or program compiled.

Parameters (the workload file's `params`):
  backend                 bls backend of the timed path ("jax")
  pool                    npz of data/gen_electra_pool.py, relative to
                          benchmarks/
  attestations_per_block  whole-slot attestations a block carries (8)
  work_kind               the WorkKind of the work item ("gossip_block")
  bucket                  [n_sets, n_pks]: the only padding bucket allowed
  preroll_blocks          blocks delivered before the window opens (set-up)
  table_sample_rows       rows of the table compared coordinate by
                          coordinate with keys the reference decompressed
  trace_window_s          profiler window of a traced run, after the window
  tamper_window           null; or one of CONTROLS: damage one seeded
                          attestation of one window block and still expect
                          True - the control check_outputs.py runs
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import operator
import os
import threading
import time
from collections import deque

import layer_reader  # benchmarks/layer_reader.py
import numpy as np
from common import (  # benchmarks/common.py
    BenchFailure, check, emit, runtime_call, verdict)

#: the damaged operands check_outputs.py puts into the window as controls
CONTROLS = ("swap_signature", "drop_signer", "replace_signer")

#: no block repeats the choice and order of one of the last few
RECENT_BLOCKS = 8

#: the partition's multiplier field: a prime over any registry size here
_SHUFFLE_PRIME = (1 << 31) - 1


def load_reference(bench_dir: str):
    path = os.path.join(bench_dir, "reference", "bls_registry_spec.py")
    spec = importlib.util.spec_from_file_location("bls_registry_spec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the plain reference beside this directory: the registry is derived with
#: ITS curve arithmetic, so no key here is the work of the program under test
_spec = load_reference(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_P, _R = _spec.P, _spec.R


# ------------------------------------------------------------ the registry


def registry_secrets(registry_seed: int) -> tuple:
    """(a, d): validator i's secret key is (a + i d) mod r."""
    def scalar(tag: bytes) -> int:
        raw = hashlib.sha512(tag + registry_seed.to_bytes(8, "big")).digest()
        return int.from_bytes(raw, "big") % (_R - 1) + 1

    return scalar(b"electra-pool:a"), scalar(b"electra-pool:d")


def secret_sum(a: int, d: int, indices) -> int:
    """Sum of the secret keys of validators `indices`, mod r."""
    idx = np.asarray(indices, np.int64)
    return (len(idx) * a + int(idx.sum()) * d) % _R


def _add_to_all(points, q) -> list:
    """[p + q for p in points], affine, with ONE modular inversion."""
    qx, qy = q
    acc, prefix = 1, []
    for x, _ in points:
        prefix.append(acc)
        acc = acc * (qx - x) % _P
    if acc == 0:
        raise ValueError("two registry keys share an x coordinate")
    inv = pow(acc, -1, _P)
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        x, y = points[i]
        lam = (qy - y) * (inv * prefix[i] % _P) % _P
        inv = inv * (qx - x) % _P
        x3 = (lam * lam - x - qx) % _P
        out[i] = (x3, (lam * (x - x3) - y) % _P)
    return out


def derive_registry(n: int, a: int, d: int) -> list:
    """Validator i's public key (a + i d) G as an affine point, i < n:
    level by level, keys k .. 2k - 1 are keys 0 .. k - 1 plus k D."""
    points = [_spec.g1_mul(_spec.G1, a)]
    step = _spec.g1_mul(_spec.G1, d)          # len(points) * D
    while len(points) < n:
        points += _add_to_all(points[:n - len(points)], step)
        step = _spec.g1_add(step, step)
    return points


def slot_partition(n: int, slots: int, registry_seed: int) -> np.ndarray:
    """(slots, n / slots) validator indices: the epoch's committees by
    slot, a partition of the registry, each row ascending. Plain modular
    arithmetic and a sort, so that it never depends on a library's random
    stream: validator i's rank is the order of (i A + B) mod a prime."""
    raw = hashlib.sha512(b"electra-pool:shuffle"
                         + registry_seed.to_bytes(8, "big")).digest()
    mult = int.from_bytes(raw[:8], "big") % (_SHUFFLE_PRIME - 1) + 1
    shift = int.from_bytes(raw[8:16], "big") % _SHUFFLE_PRIME
    if n >= _SHUFFLE_PRIME or n % slots:
        raise ValueError("the registry divides into whole slots")
    key = (np.arange(n, dtype=np.uint64) * np.uint64(mult)
           + np.uint64(shift)) % np.uint64(_SHUFFLE_PRIME)
    order = np.argsort(key, kind="stable").astype(np.int64)
    return np.sort(order.reshape(slots, n // slots), axis=1)


def _fq(a) -> int:
    return int.from_bytes(bytes(a), "big")


def _g2(a) -> tuple:
    return ((_fq(a[0, 0]), _fq(a[0, 1])), (_fq(a[1, 0]), _fq(a[1, 1])))


def load_pool(path: str) -> dict:
    """The pool of data/gen_electra_pool.py: meta, the partition, and per
    set its signers' indices, its message and its signature (an affine G2
    point). No key: the registry is derived."""
    z = np.load(path)
    meta = json.loads(bytes(z["meta"]))
    n, slots = meta["validators"], meta["slots"]
    committees = slot_partition(n, slots, meta["registry_seed"])
    width = n // slots
    signed = np.unpackbits(z["att_mask"], axis=1)[:, :width].astype(bool)
    return {
        "meta": meta,
        "att": [(committees[j][signed[j]], bytes(z["att_msgs"][j]),
                 _g2(z["att_sigs"][j])) for j in range(slots)],
        "small": [(np.array([meta["proposer_index"]], np.int64),
                   bytes(z["small_msgs"][k]), _g2(z["small_sigs"][k]))
                  for k in range(2)],
        "sync": (z["sync_indices"].astype(np.int64), bytes(z["sync_msg"]),
                 _g2(z["sync_sig"])),
    }


class _Validator:
    """What of a Validator record the pubkey cache reads."""

    __slots__ = ("pubkey",)

    def __init__(self, pubkey: bytes):
        self.pubkey = pubkey


class _State:
    __slots__ = ("validators",)

    def __init__(self, validators):
        self.validators = validators


class _Block:
    """One block: its sets in order, when it was submitted, its verdict."""

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
            if not hasattr(m, "children"):
                return {"": m.value}
            return {"/".join(map(str, k)): c.value for k, c in m.children()}
    return {}


def run(config, params, seed, seconds, trace, h) -> dict:
    try:
        from lighthouse_tpu.crypto.jaxbls import registry
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
    from lighthouse_tpu.state_transition.block import SignatureBatch

    kind = WorkKind[params["work_kind"]]
    per_block = int(params["attestations_per_block"])
    n_preroll = int(params["preroll_blocks"])
    if n_preroll < 1:
        raise ValueError("preroll_blocks must be at least 1")
    rng = np.random.default_rng(seed)
    ref = _spec

    # --- the pool, and what the configuration states about it
    t0 = time.perf_counter()
    pool = load_pool(os.path.join(h.bench_dir, params["pool"]))
    t_load = time.perf_counter() - t0
    meta = pool["meta"]
    n_val, n_slots = meta["validators"], meta["slots"]
    if not 1 <= per_block < n_slots:
        raise ValueError(f"a block takes 1 to {n_slots - 1} of the pool's "
                         f"{n_slots} attestations, not {per_block}")
    n_sets = 2 + per_block + 1
    att_keys = [len(ind) for ind, _, _ in pool["att"]]
    stated = {"validators": n_val, "slots_per_epoch": n_slots,
              "attestations_per_block": per_block,
              "sets_per_request": n_sets,
              "sync_committee_size": len(pool["sync"][0]),
              "attesters_per_slot": n_val // n_slots}
    for key, got in stated.items():
        if key in config and int(config[key]) != got:
            raise ValueError(f"the configuration states {key} = "
                             f"{config[key]}, the pool gives {got}")

    # --- the registry: keys derived, compressed, taken in by the chain's
    # pubkey cache, which feeds the backend's table on the device
    t0 = time.perf_counter()
    points = derive_registry(n_val, *registry_secrets(meta["registry_seed"]))
    t_keys = time.perf_counter() - t0
    backend = bls.set_backend(params["backend"])
    on_device = backend.name == "jax"
    t0 = time.perf_counter()
    # decompressed and validated keys are interned by their bytes, as a
    # node's are after its first look at a deposit: the cache's
    # `PublicKey.deserialize` then finds each
    state = _State([_Validator(bls.PublicKey(p).serialize()) for p in points])
    table = backend.install_registry() if on_device else registry.PubkeyTable()
    cache = ValidatorPubkeyCache(table=table)
    cache.import_new_pubkeys(state)
    t_cache = time.perf_counter() - t0
    check(len(cache) == len(table) == n_val,
          f"the cache holds {len(cache)} keys, the table {len(table)}, the "
          f"registry {n_val}")

    # --- the pool's sets, as the set builders make them: the cache's key
    # objects, their validator indices and the cache's table
    t0 = time.perf_counter()

    def make_set(indices, message, sig, keys=None):
        if keys is None:
            keys = (operator.itemgetter(*indices.tolist())(cache.pubkeys)
                    if len(indices) > 1 else (cache.pubkeys[int(indices[0])],))
        return bls.SignatureSet(bls.Signature(sig), keys, message,
                                signing_indices=indices,
                                signing_registry=table)

    atts = [make_set(*a) for a in pool["att"]]
    small = [make_set(*s) for s in pool["small"]]
    sync = make_set(*pool["sync"])
    t_sets = time.perf_counter() - t0

    # --- drawing blocks: proposal, RANDAO, a fresh seeded choice and order
    # of the pool's attestations, the sync aggregate
    recent: deque = deque(maxlen=RECENT_BLOCKS)

    def draw_picks(first=None) -> tuple:
        while True:
            rest = [j for j in range(n_slots) if j != first]
            picks = tuple(int(j) for j in rng.choice(
                rest, size=per_block - (first is not None), replace=False))
            if first is not None:
                picks = (first,) + picks
            if picks not in recent:
                recent.append(picks)
                return picks

    def block_of(picks, replace=None) -> _Block:
        """`replace`: {position among the attestations: another set}."""
        chosen = [atts[j] for j in picks]
        for at, other in (replace or {}).items():
            chosen[at] = other
        return _Block(small + chosen + [sync])

    def damaged(how: str, sset, slot: int):
        """Attestation `sset` of slot `slot` damaged, its signature kept or
        swapped: what a block with it must NOT verify as."""
        ind = sset.signing_indices
        if how == "swap_signature":
            donor = atts[(slot + 1 + int(rng.integers(n_slots - 1)))
                         % n_slots]
            return bls.SignatureSet(donor.signature, sset.signing_keys,
                                    sset.message, signing_indices=ind,
                                    signing_registry=table)
        at = int(rng.integers(len(ind)))
        if how == "drop_signer":
            keep = np.delete(ind, at)
        elif how == "replace_signer":
            # a validator of another slot, who signed something else
            other = pool["att"][(slot + 1) % n_slots][0]
            keep = ind.copy()
            keep[at] = int(other[int(rng.integers(len(other)))])
        else:
            raise ValueError(f"unknown damage {how!r}")
        return make_set(keep, sset.message, sset.signature.point)

    def damaged_block(how: str) -> _Block:
        picks = draw_picks()
        at = int(rng.integers(per_block))
        return block_of(picks, {at: damaged(how, atts[picks[at]], picks[at])})

    # --- the plain reference: proposal + RANDAO + ONE whole-slot attestation
    # at full width + the sync aggregate, on keys it decompresses itself
    # from the registry's bytes, and the same with that attestation's
    # signature swapped. Its time is not set-up.
    t0 = time.perf_counter()
    ref_slot = int(rng.integers(n_slots))
    swapped = damaged("swap_signature", atts[ref_slot], ref_slot)

    def for_reference(sset) -> tuple:
        return (sset.signature.point,
                [cache.pubkey_bytes[i] for i in sset.signing_indices.tolist()],
                sset.message)

    zs = [int(z) for z in rng.integers(1, 1 << 63, size=4)]
    ref_keys: dict = {}       # the reference's own decompressions, kept
    ref_verdicts = [
        ref.verify_signature_sets(
            [for_reference(s) for s in small + [att, sync]], zs, ref_keys)
        for att in (atts[ref_slot], swapped)]
    # the table on the device against the registry: every key as derived
    # is what the registry's bytes compress (the reference's curve equation
    # and sign rule, no square root), a digest over all rows against those
    # keys (integers, never limbs), and seeded rows coordinate by coordinate
    # against keys the reference decompressed, square root and all
    n_sample = min(int(params["table_sample_rows"]), n_val)
    rows = np.sort(rng.choice(n_val, size=n_sample, replace=False))
    want_rows = [ref.decompress_key(cache.pubkey_bytes[i]) for i in rows]
    bytes_wrong = ref.keys_not_of(cache.pubkey_bytes, points)
    want_digest = ref.registry_digest(points)
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
    del points

    # per-stage seconds come from the program's attribution families, which
    # event-time every stage resolve: a traced run only
    obs_device.set_enabled(bool(trace))

    proc = BeaconProcessor(BeaconProcessorConfig(num_workers=1))
    cond = threading.Condition()
    delivered: list = []      # blocks in the order their verdicts came
    st = {"phase": "setup", "feeding": False, "t_open": None,
          "t_close": None, "loop_blocks": 0, "submitted": 0}
    tamper = params.get("tamper_window")

    def feed(blk: _Block, claimed: bool = False) -> None:
        """Submit one block; `claimed` when on_delivered counted it
        already, under the lock that the end of the loop waits on."""

        def run_item():
            with h.annotate("bench:signature_batch"):
                batch = SignatureBatch()
                for s in blk.sets:
                    batch.add(s)
                ok = batch.verify()
            on_delivered(blk, ok)

        if not claimed:
            with cond:
                st["submitted"] += 1
        blk.t_submit = time.perf_counter()
        if not proc.submit(WorkItem(kind=kind, run=run_item)):
            raise RuntimeError("the processor refused a work item")

    def on_delivered(blk: _Block, ok) -> None:
        blk.t_done = time.perf_counter()
        blk.verdict = bool(ok)
        with cond:
            delivered.append(blk)
            cond.notify_all()
            if st["phase"] != "loop":
                return
            st["loop_blocks"] += 1
            if st["loop_blocks"] == n_preroll:
                # the window's edges are deliveries: whole blocks only
                st["t_open"] = h.open_window()
                st["i_open"] = len(delivered)
            elif (st["t_open"] is not None and st["t_close"] is None
                  and blk.t_done >= st["t_open"] + seconds):
                st["t_close"] = h.close_window()
                st["i_close"] = len(delivered)
            feeding = st["feeding"]
            if feeding:
                st["submitted"] += 1
        if feeding:
            if tamper and st["t_open"] is not None and not st.get("tampered"):
                st["tampered"] = True
                feed(damaged_block(tamper), claimed=True)
            else:
                feed(block_of(draw_picks()), claimed=True)

    def wait_for(done, limit: float, what: str) -> None:
        with cond:
            if not cond.wait_for(done, timeout=limit):
                raise RuntimeError(f"{what} within {limit} s")

    # --- set-up on the timed path's own bucket: a whole block around the
    # reference's attestation (compiles), one more valid block, and the
    # first again with the reference's swap, so both sides give their
    # verdicts on the same operands
    ref_picks = draw_picks(first=ref_slot)
    h.log.label = "warmup"
    t0 = time.perf_counter()
    feed(block_of(ref_picks))
    proc.run_until_idle()
    h.note("warmup_s", time.perf_counter() - t0)
    h.log.label = "setup"
    feed(block_of(draw_picks()))
    feed(block_of(ref_picks, {0: swapped}))
    proc.run_until_idle()
    setup_verdicts = [b.verdict for b in delivered]
    runtime_call()

    # --- the loop: one block, then one worker pumps as the node's does;
    # each delivery submits the next
    with cond:
        st["phase"] = "loop"
        st["feeding"] = True
    feed(block_of(draw_picks()))
    proc.start()
    try:
        wait_for(lambda: st["t_close"] is not None, seconds + 300,
                 "the window did not close")
        if trace:
            h.trace_begin()
            time.sleep(float(params["trace_window_s"]))
            h.trace_end()
        with cond:
            st["feeding"] = False
        wait_for(lambda: len(delivered) == st["submitted"], 120,
                 "the last block did not come back")
    finally:
        proc.stop()
    n_loop = len(delivered)

    # --- after the window, on the same path: three damaged blocks, and a
    # block whose first attestation names the row past the table's last
    with cond:
        st["phase"] = "after"
    refused_before = family_values("jaxbls_registry_refused_total")
    for how in CONTROLS:
        feed(damaged_block(how))
    picks = draw_picks()
    victim = atts[picks[0]]
    past = victim.signing_indices.copy()
    past[-1] = len(table)
    feed(block_of(picks, {0: make_set(past, victim.message,
                                      victim.signature.point,
                                      keys=victim.signing_keys)}))
    proc.run_until_idle()
    after = [b.verdict for b in delivered[n_loop:]]
    after_verdicts = dict(zip(CONTROLS, after))
    refused = None
    if on_device:
        refused = (len(after) == len(CONTROLS) + 1 and after[-1] is False
                   and family_values("jaxbls_registry_refused_total")[""]
                   == refused_before[""] + 1)

    # --- the window's numbers
    win = delivered[st["i_open"]:st["i_close"]]
    req_ms = np.array([(b.t_done - b.t_submit) * 1e3 for b in win])
    lat_ms = np.repeat(req_ms, [len(b.sets) for b in win])
    n_win_sets = int(len(lat_ms))
    keys_in_window = sum(len(s.signing_keys) for b in win for s in b.sets)
    wrong = sum(len(b.sets) for b in win if not b.verdict)
    missing = (st["submitted"] - len(delivered)) * n_sets
    window_s = st["t_close"] - st["t_open"]
    p95 = float(np.sort(lat_ms)[int(np.ceil(0.95 * n_win_sets)) - 1])

    def in_window(family: str, **labels) -> float:
        return layer_reader.evaluate(
            {"family": family, "labels": labels, "reduce": "sum"},
            h.before, h.after, {}, {}) or 0.0

    errors = family_values("beacon_processor_errors_total")
    hybrid = family_values("bls_hybrid_route_total")
    key_slots = {src: in_window("jaxbls_registry_keys_total", source=src)
                 for src in ("table", "packed")}
    lanes = {ln: in_window("jaxbls_pipeline_submitted_total", lane=ln)
             for ln in ("batch", "urgent")}
    buckets = None
    if on_device:
        from lighthouse_tpu.crypto.jaxbls import backend as jb

        buckets = sorted(jb._seen_exec_buckets)
    emit(step="bls_registry_block_loop", backend=backend.name,
         work_kind=kind.name, pool_load_secs=round(t_load, 2),
         registry_keys_secs=round(t_keys, 2), cache_secs=round(t_cache, 2),
         pool_sets_secs=round(t_sets, 2), table_check_secs=round(t_table, 2),
         validators=n_val, table_rows=len(table),
         table_capacity=table.capacity, table_bytes=table.nbytes,
         sets_per_request=n_sets,
         attestation_keys=[min(att_keys), max(att_keys)],
         reference_secs=round(t_ref, 2), reference_attestation=ref_slot,
         reference_verdicts=ref_verdicts, warmup_s=h.notes["warmup_s"],
         setup_verdicts=setup_verdicts, after_window_verdicts=after_verdicts,
         refused_past_the_table=refused, window_s=window_s,
         requests_in_window=len(win), sets_in_window=n_win_sets,
         keys_in_window=keys_in_window,
         keys_per_request=keys_in_window / len(win),
         requests_per_s=len(win) / window_s,
         request_latency_ms={
             "n": len(win), "median": float(np.median(req_ms)),
             "min": float(req_ms.min()), "max": float(req_ms.max()),
             "p95_over_sets": p95},
         requests_total=len(delivered), key_slots_in_window=key_slots,
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
        check(lanes["batch"] == len(win) and lanes["urgent"] == 0,
              f"dispatcher lanes in the window {lanes}: expected "
              f"{len(win)} on batch alone")
        check(key_slots["table"] == keys_in_window,
              f"keys of the window by source {key_slots}: the window's "
              f"blocks held {keys_in_window}")

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
        {"name": "table_rows", "what": f"rows of {n_sample} seeded ones "
         "whose coordinates differ from the keys the reference decompressed",
         "value": table_checks["rows"], "limit": 0},
        {"name": "table_spare", "what": "nonzero limbs in the table's spare "
         "rows",
         "value": table_checks["spare"], "limit": 0},
        {"name": "reference", "what": "reference verdicts (proposal + RANDAO "
         "+ one whole-slot attestation + sync aggregate; the same with the "
         "attestation's signature swapped)",
         "value": ref_verdicts, "limit": [True, False]},
        {"name": "setup", "what": "set-up verdicts (valid, valid, one "
         "swapped signature)",
         "value": setup_verdicts, "limit": [True, True, False]},
        {"name": "timed_against_reference",
         "what": "the timed backend on the reference's own operands inside "
         "a whole block (valid, the attestation's signature swapped), "
         "against the reference's verdicts",
         "value": setup_verdicts[0::2], "limit": ref_verdicts},
        {"name": "window_wrong", "what": "sets of the window with a wrong "
         "verdict",
         "value": wrong, "limit": 0},
        {"name": "window_missing", "what": "sets submitted whose verdict "
         "never came",
         "value": missing, "limit": 0},
        {"name": "window_packed", "what": "keys of the window's dispatches "
         "that were packed on the host instead of gathered from the table",
         "value": int(key_slots["packed"]), "limit": 0},
        {"name": "after_window_damaged",
         "what": "verdicts after the window: a block with one attestation's "
         "signature swapped, one with a signer's index dropped, one with a "
         "signer replaced by a validator of another slot",
         "value": [after_verdicts.get(c) for c in CONTROLS],
         "limit": [False] * len(CONTROLS)},
        {"name": "refused_past_the_table",
         "what": "a set naming the row past the table's last was refused on "
         "the host (the block False) and counted once (null off the device)",
         "value": refused, "limit": True if on_device else None},
    ]
    return {
        "correct": verdict(compared),
        "compared": compared,
        "attempted": n_win_sets + missing,
        "failed": wrong + missing,
        "end_to_end": {
            "bls_verify_p95_ms": {"value": p95, "unit": "ms"},
        },
    }
