"""The TPU BLS backend: batched multi-set signature verification on device.

This is the north-star component (BASELINE.json): the plugin that slots into
the generic backend registry (crypto/bls/api.py) exactly where blst slots
into /root/reference/crypto/bls/src/impls/ — but instead of per-core
assembly, `verify_signature_sets` marshals whole batches of SignatureSets to
one jitted XLA program:

    1. masked tree-sum of each set's pubkeys (G1, Jacobian, batched)
    2. z_i * aggpk_i with the 64-bit random coefficients (windowed, w=4)
    3. hash-to-G2 of each message (host sha256 -> device SSWU/isogeny and
       psi-endomorphism cofactor clearing)
    4. sum_i z_i * sig_i (windowed scalar mul + tree reduce)
    5. ONE batched Montgomery-domain inversion for every Jacobian->affine
       conversion (all Z coordinates inverted in a single Fermat chain)
    6. one multi-pairing product check with a single final exponentiation

Shapes are padded to power-of-two buckets (pad lanes masked out) so XLA
compiles one program per bucket, cached persistently (utils/jaxcfg.py) —
the bucketing policy answers SURVEY.md §7 hard part (c). Under a bucket's
(n, m) a one-chip batch of unequal widths lays its keys as two grids, a
wide and a narrow one, chosen from the widths it holds (`key_grid_plan`):
the key axis is where padding costs what data costs.

Throughput design (r2, rebuilt r8): a device round trip is pure
latency the host can hide, so every
batch rides the pipelined executor (crypto/jaxbls/pipeline.py): an async
submission API (`verify_signature_sets_async`) keeps up to `depth` batches
in flight (depth from the autotune plan; `jaxbls_pipeline_*` metrics),
per-batch input buffers are DONATED to the staged jit programs on
accelerators (donate_argnums — intermediates reuse their HBM instead of
fresh allocations), and urgent single-set verifies take a bypass lane
that never waits behind the batch window. Host marshalling is vectorized
numpy (no per-element Python bigint work) and pubkey limb arrays are
cached on device keyed by the identity of the key objects, mirroring the
reference's decompressed ValidatorPubkeyCache
(validator_pubkey_cache.rs:17) feeding blst — which is also why the
pubkey grids are the one input family donation never touches. Where a
chain keeps its registry's keys on the device (`install_registry`,
registry.py) and a batch's sets carry validator indices, the marshal sends
an index grid and stage 1 gathers the rows: no key is packed at all.
"""

from __future__ import annotations

import weakref

import numpy as np

from ...observability import device as _obs_dev
from ...observability import perf as _obs_perf
from ...observability import trace as _obs
from ...utils.metrics import REGISTRY
from ..bls381.constants import P, R, DST_POP
from ..bls381 import curve as pc
from . import limbs as lb
from . import tower as tw
from . import curve_ops as co
from . import h2c_ops as h2
from . import pairing_ops as po

# ------------------------------------------------------------------ metrics
# the marshal as one number (the benchmark's marshal_ms reads it); its
# parts, the enqueue and the device wait are spans of the dispatch's
# pipeline Trace (`jaxbls:marshal.*`, `jaxbls:enqueue`,
# `jaxbls:device_wait`: observability/trace.py), the device's time a
# dispatch is the dispatcher's jaxbls_dispatch_device_seconds
_MARSHAL_SECONDS = REGISTRY.histogram(
    "jaxbls_marshal_seconds",
    "host-side batch marshalling time (packing + device placement)",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
)
_PK_CACHE = REGISTRY.counter_vec(
    "jaxbls_pubkey_cache_total",
    "device-resident pubkey marshalling cache outcomes",
    ("result",),
)
_BUCKET_SLOTS = REGISTRY.counter_vec(
    "jaxbls_bucket_slots_total",
    "slots a dispatch lays, by axis (sets: the padding bucket's n; keys: "
    "every slot of the key grids key_grid_plan chose, n*m where it is the "
    "one grid) and kind: real = what the caller sent, padded = what is "
    "laid; real over padded is the fill",
    ("axis", "kind"),
)
_DISPATCH_MESSAGES = REGISTRY.counter_vec(
    "jaxbls_dispatch_messages_total",
    "messages of the real sets per dispatch: sent = one a set, distinct = "
    "different byte strings among them, lanes = what is laid for stages 2-4 "
    "(one hash-to-G2 map and one Miller pair a lane: message_lanes' k where "
    "the dispatch folds its sets by message, the set bucket's n where it "
    "does not), folded_sets = the real sets of the dispatches that folded; "
    "folded_sets over sent is the share of sets verified on the message axis",
    ("kind",),
)
_TREE_SUM_LANE_ADDS = REGISTRY.counter_vec(
    "jaxbls_tree_sum_lane_additions_total",
    "point additions of the key-axis sum in prepare, per dispatch: done = "
    "what curve_ops.tree_sum_plan gives, summed over the key grids the "
    "dispatch lays (key_grid_plan: one (n, m) grid, or a wide and a narrow "
    "one), needed = real keys - real sets; done over needed is what "
    "padding and the reduction's shape still cost",
    ("kind",),
)
_MILLER_PLAN = REGISTRY.counter_vec(
    "jaxbls_miller_plan_total",
    "what pairing_ops.miller_lane_plan gives the pairing stage, per "
    "dispatch (BLS and KZG alike), for the pair lanes one Miller loop "
    "sees: dispatches, "
    "accumulators = W carried through the loop, in_step_levels = dense "
    "Fq12 tree levels left inside each of its steps; in_step_levels over "
    "dispatches is 0 where the lines are narrowed only after the loop; "
    "lines_per_accumulator = g, the lines one accumulator takes a step (1 "
    "a sparse line, 2 a line pair, 8 at a 1,024-set bucket's 1,025 pairs)",
    ("kind",),
)
_KZG_LANES = REGISTRY.counter_vec(
    "kzg_lanes_total",
    "lanes of the KZG batch check's scalar-multiplication pass, per "
    "dispatch: real = six a blob (its three terms of C', its term of W', "
    "its commitment and its proof times the group order), padded = the "
    "program's one row of lanes; real over padded is the pass's fill",
    ("kind",),
)
_REGISTRY_KEYS = REGISTRY.counter_vec(
    "jaxbls_registry_keys_total",
    "signing keys of the real sets per dispatch, by where stage 1 took "
    "them from: table = gathered by validator index from the registry "
    "table on the device (registry.py), packed = coordinates packed on the "
    "host into the (n, m) limb grid",
    ("source",),
)
_PREPARE_REFUSED = REGISTRY.counter_vec(
    "jaxbls_prepare_refused_total",
    "dispatches whose verdict is False by stage 1's `bad` code, read with "
    "the verdict: identity_aggpk = a real set's keys sum to the identity, "
    "chain_exception = a coefficient chain (curve_ops.scalar_mul_z) of a "
    "real set met accumulator = +-base, which no point of order r gives: a "
    "key or signature outside the subgroup; 0 on honest traffic",
    ("why",),
)
# buckets that have resolved at least once: the benchmark's drivers and
# chip_smoke.py check that a run compiled the one bucket it meant to
_seen_exec_buckets: set = set()

MIN_SETS = 4          # smallest bucket (pairs axis = sets + 1 rounded up)
MIN_PKS = 1
Z_BITS = 64           # a coefficient as the marshal uploads it: its bits, MSB first

_LIVE_MESH = object()  # sentinel: "resolve parallel.get_mesh() lazily"


def _count_miller_plan(miller_pairs: int) -> None:
    """One dispatch into jaxbls_miller_plan_total: the plan of the pair
    lanes ONE Miller loop of its pairing stage sees."""
    w, in_step_levels, _ = po.miller_lane_plan(miller_pairs)
    _MILLER_PLAN.labels("dispatches").inc()
    _MILLER_PLAN.labels("accumulators").inc(w)
    _MILLER_PLAN.labels("in_step_levels").inc(in_step_levels)
    _MILLER_PLAN.labels("lines_per_accumulator").inc(
        po._lines_per_accumulator(miller_pairs, w))


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def padding_bucket(n_sets: int, n_pks: int, mesh=_LIVE_MESH,
                   single_chip: bool = False) -> tuple:
    """THE (n, m) compile-bucket rounding rule of the dispatch path, for a
    workload of n_sets sets whose widest set has n_pks pubkeys. Single
    owner — the hybrid router's bucket tracking and the autotune
    calibrator classify by calling this, so their keys can never desync
    from what actually compiles.

    Mesh-shape-keyed: the set axis rounds up to a multiple of the mesh's
    one axis so every dispatched batch shards evenly (the key width is a
    power of two on every topology); pass an explicit `mesh` to bucket
    for a topology other than the live one (the --mesh-devices sweep), or
    `single_chip=True` for the urgent bypass lane's plain pow2 buckets
    (urgent verifies are pinned to one chip and never pay mesh padding)."""
    n = max(MIN_SETS, _next_pow2(n_sets))
    m = max(MIN_PKS, _next_pow2(n_pks))
    if single_chip:
        return n, m
    from ...parallel import pad_sets

    if mesh is _LIVE_MESH:
        return pad_sets(n), m
    return pad_sets(n, mesh=mesh), m


def key_grid_plan(widths, n: int, m: int) -> tuple:
    """How the keys of a one-chip dispatch lie, from the key counts of its
    sets alone: (grids, where). `n`, `m` are the dispatch's
    `padding_bucket`, which stays its NAME whatever is laid. Pure and
    single owner, as tree_sum_plan is for the sum: the marshal fills what
    this gives, prepare sums it, the slot and lane-addition counters read
    it.

    One grid — `one_key_grid(n, m)`, set i in row i: today's layout —
    unless two lay at most three quarters of its slots. Two grids, `grids`
    = (wide, narrow), each (rows, width): for each power of two t < m the
    narrow grid takes the sets of at most t keys and the wide one the
    rest, each a power of two of rows by a power of two of slots (the wide
    grid m wide, the narrow one its widest set rounded up), and the
    cheapest t wins (the smallest of equals: they lay the same grids).
    `where` is then int32[n]: set i's sum is entry where[i] of the wide
    grid's row sums followed by the narrow grid's and one identity, which
    every padded set slot reads. Sets keep their order inside a grid. So a
    block whose one sync aggregate made every 128-key attestation 512 wide
    lays 1 x 512 + 256 x 128, and a batch of near-equal widths keeps the
    program it has."""
    best = None
    t = 1
    while t < m:
        narrow = [w for w in widths if w <= t]
        if narrow and len(narrow) < len(widths):
            grids = ((_next_pow2(len(widths) - len(narrow)), m),
                     (_next_pow2(len(narrow)), _next_pow2(max(narrow))))
            slots = sum(rows * width for rows, width in grids)
            if best is None or slots < best[0]:
                best = (slots, t, grids)
        t *= 2
    if best is None or 4 * best[0] > 3 * n * m:
        return one_key_grid(n, m)
    _, t, grids = best
    (wide_rows, _), (narrow_rows, _) = grids
    where = np.full((n,), wide_rows + narrow_rows, np.int32)
    rows = [0, wide_rows]                  # the next free entry of each grid
    for i, w in enumerate(widths):
        where[i] = rows[w <= t]
        rows[w <= t] += 1
    return grids, where


def one_key_grid(n: int, m: int) -> tuple:
    """The key_grid_plan of ONE (n, m) grid, set i in row i."""
    return ((n, m),), None


def message_lanes(distinct: int, n: int) -> int:
    """How the messages of a one-chip batch-lane dispatch lie, from the
    count of distinct message byte strings among its sets and its set
    bucket `n` (`padding_bucket`'s, which stays the dispatch's NAME): the
    lanes k laid for hash-to-G2, the batched inversion and the Miller loop
    (k + 1 pairs). Pure and single owner, as key_grid_plan is for the keys:
    the marshal hashes and indexes by it, stage 3 folds to it, the lane and
    Miller-plan counters read it.

    k = max(one row of Miller accumulators, distinct rounded up to a power
    of two), and the dispatch FOLDS — sums its sets' z * pk by message
    before stage 3, e(a, H) e(b, H) = e(a + b, H) — iff k < n; else k = n,
    a lane a set, the programs every dispatch ran before the rule. Never
    narrower than the row: on the chip no hash-to-G2 or Miller program is
    cheaper below it (PERF.md S6, PR 30 and PR 44), and k then takes few
    values (n = 256: 128; n = 1,024: 128, 256, 512), so a dispatch whose
    count wobbles by a few meets no shape that has not compiled. The urgent
    lane and a mesh do not ask: they lay a lane a set, as they lay the one
    key grid."""
    k = max(po.MILLER_LANES, _next_pow2(distinct))
    return k if k < n else n


# The digit width of stage 1's two coefficient chains (co.scalar_mul_z:
# z_i * aggpk_i and z_i * sig_i), one constant for every lane count, named on
# the dispatch's trace (`z_window`). From scripts/measure_z_chain.py's table on
# a v5e (PERF.md S6, PR 45; each chain alone, ms, median of 5, at 4 / 16 / 64 /
# 128 / 256 / 512 / 1,024 lanes; parent = scalar_mul_bits): G2 parent 75.0 /
# 60.7 / 68.6 / 28.6 / 55.8 / 118.4 / 236.2, one bit a step 34.8 / 35.3 / 45.7
# / 15.0 / 28.3 / 58.3 / 114.6, two bits 27.6 / - / 48.3 / - / 25.6 / - /
# 100.2, four 25.9 / 24.1 / 38.2 / 11.6 / 22.0 / 44.1 / 87.0; G1 parent 17.1 /
# 15.8 / 22.2 / 10.8 / 18.8 / 36.6 / 72.7, one bit 16.0 / 11.9 / 16.8 / 8.6 /
# 14.3 / 27.9 / 54.7, four 9.1 / 8.3 / 12.2 / 6.3 / 9.8 / 18.5 / 36.0: four
# bits a digit is the cheapest form at EVERY width on both groups, and a chain
# costs in proportion to its lanes from 128 on, so the lanes are not walked in
# chunks. A FULL row of 128 lanes is the cheapest width of all, under half of
# 4-64 lanes: padding narrow dispatches to it is the next lever, not taken
# here.
Z_WINDOW = 4


def message_fold_index(lanes, n: int, k: int) -> np.ndarray:
    """What a folding dispatch sends stage 3 beside its k lanes of `us`,
    from the message lane of each real set (the caller's order): int32[2, n],
    row 0 the set slots ordered by lane (a stable sort: the sets of a
    message keep their order), row 1 the lane of the slot that stands
    there, ascending; a padded slot's lane is k, behind every message."""
    lane = np.full((n,), k, np.int32)
    lane[:len(lanes)] = lanes
    order = np.argsort(lane, kind="stable").astype(np.int32)
    return np.stack([order, lane[order]])


def _grid_rows(plan, n_sets: int) -> list:
    """[(grid, row)] of the first n_sets sets under a key_grid_plan: grid 0
    the wide (or only) one, grid 1 the narrow one."""
    grids, where = plan
    if where is None:
        return [(0, i) for i in range(n_sets)]
    wide_rows = grids[0][0]
    return [(0, int(at)) if at < wide_rows else (1, int(at) - wide_rows)
            for at in where[:n_sets]]


# ------------------------------------------------------------ host marshalling


def pack_ints_vec(xs) -> np.ndarray:
    """Vectorized host packing: list of ints < 2^384 -> (n, NL) u32 standard-
    form limbs. int.to_bytes + one frombuffer instead of per-limb Python."""
    buf = b"".join(x.to_bytes(48, "little") for x in xs)
    b8 = np.frombuffer(buf, np.uint8).reshape(len(xs), 48)
    return b8[:, 0::2].astype(np.uint32) | (b8[:, 1::2].astype(np.uint32) << 8)


def _to_mont_dev(arr):
    """Device: standard-form limbs (..., NL) -> Montgomery form."""
    import jax.numpy as jnp

    return lb.mont_mul(arr, jnp.broadcast_to(lb.R2, arr.shape))


# ------------------------------------------------------------ device kernel


def _batched_affine(z_pk, h_jac, sig_acc):
    """Jacobian->affine for all three pairing inputs with ONE inversion.

    Z coordinates (n Fq + n Fq2 + 1 Fq2) are stacked into a single Fq2 batch
    (Fq embedded with zero imaginary part) and inverted in one Fermat chain;
    identity lanes (Z == 0) invert to 0 and stay flagged."""
    import jax.numpy as jnp

    Xp, Yp, Zp = z_pk          # G1: (n, NL)
    Xh, Yh, Zh = h_jac         # G2: (n, 2, NL)
    Xs, Ys, Zs = sig_acc       # G2: (2, NL)
    n = Zp.shape[0]

    def embed(fq):             # (n, NL) -> (n, 2, NL)
        return jnp.stack([fq, jnp.zeros_like(fq)], axis=-2)

    zs = jnp.concatenate([embed(Zp), Zh, Zs[None]], axis=0)  # (2n+1, 2, NL)
    zinv = tw.fq2_inv(zs)
    zinv2 = tw.fq2_sqr(zinv)
    zinv3 = tw.fq2_mul(zinv2, zinv)

    pk_i2, pk_i3 = zinv2[:n, 0, :], zinv3[:n, 0, :]         # Fq lanes
    h_i2, h_i3 = zinv2[n : 2 * n], zinv3[n : 2 * n]
    s_i2, s_i3 = zinv2[2 * n], zinv3[2 * n]

    px = lb.mont_mul(Xp, pk_i2)
    py = lb.mont_mul(Yp, pk_i3)
    p_inf = lb.is_zero(Zp)
    qx = tw.fq2_mul(Xh, h_i2)
    qy = tw.fq2_mul(Yh, h_i3)
    q_inf = tw.fq2_is_zero(Zh)
    sx = tw.fq2_mul(Xs, s_i2)
    sy = tw.fq2_mul(Ys, s_i3)
    s_inf = tw.fq2_is_zero(Zs)
    return (px, py, p_inf), (qx, qy, q_inf), (sx, sy, s_inf)


def _sum_key_grid(pk_x, pk_y, pk_mask):
    """Stage 1, the key side: one key grid, coordinates in Montgomery form
    (rows, width, NL) and its mask, to one Jacobian sum a row."""
    import jax.numpy as jnp

    # (rows, width) -> (rows,). tree_sum folds the key axis down to about
    # co.TREE_SUM_L0 lanes and halves the rest: two add instances whatever
    # the width is (the unrolled tree was the compile whale here), about
    # width*rows lane-additions instead of width*rows*log2(width) — which on
    # the v5e was 1.46 s of a 2.56 s block at 256x512 (PERF.md S6, PR 27-28)
    pk_jac = co.affine_to_jac(co.FQ_OPS, (pk_x, pk_y), inf_mask=jnp.logical_not(pk_mask))
    pk_jac_t = tuple(jnp.moveaxis(c, 1, 0) for c in pk_jac)
    return co.tree_sum(pk_jac_t, co.FQ_OPS)                # (rows,) jacobian G1


#: the `bad` output of stage 1 is a code, one bit a reason (VerifyHandle
#: refuses on any, and counts each in jaxbls_prepare_refused_total{why})
PREPARE_REFUSED = {1: "identity_aggpk", 2: "chain_exception"}


def _prepare_from_sums(aggpk, sig_x, sig_y, z_digits, set_mask):
    """Stage 1 after the key side: the sets' aggregate keys (n,) and the
    signatures in Montgomery form to (z_pk, sig_acc, bad). `bad` is a
    uint32 code, zero for a dispatch stage 1 has nothing against:
    PREPARE_REFUSED's 1 where a real set's keys sum to the identity, 2 where
    a coefficient chain of a real set met the case its additions leave out
    (co.scalar_mul_z: the accumulator +-the base, which no point of order r
    gives — a signature or key outside the subgroup steered it there, and
    the spec refuses such a point)."""
    import jax.numpy as jnp

    real = jnp.asarray(set_mask, bool)
    aggpk_inf = co.FQ_OPS.is_zero(aggpk[2])
    bad_aggpk = jnp.any(jnp.logical_and(aggpk_inf, real))

    # z_i * aggpk_i, and z_i * sig_i with a padded set's signature the
    # identity by its mask: its product then stays the identity
    z_pk, met_pk = co.scalar_mul_z(aggpk, z_digits, co.FQ_OPS, window=Z_WINDOW)
    z_sig, met_sig = co.scalar_mul_z(
        (sig_x, sig_y), z_digits, co.FQ2_OPS,
        p_inf=jnp.logical_not(real), window=Z_WINDOW)
    met = jnp.logical_or(met_pk, met_sig)
    sig_acc = co.tree_sum(z_sig, co.FQ2_OPS)               # single jacobian G2
    bad = (bad_aggpk.astype(jnp.uint32)
           | (jnp.any(jnp.logical_and(met, real)).astype(jnp.uint32) << 1))
    return z_pk, sig_acc, bad


def _stage_prepare(pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask):
    """Stage 1: mont conversion, pubkey tree-aggregation, z-scaling of
    aggregate pubkeys and signatures, signature tree-sum. The keys as ONE
    (n, m) grid, set i in row i: what a batch of near-equal widths, the
    urgent lane and a mesh run (key_grid_plan).

    Shapes, here and for hash-to-G2's `us` beside it:
      pk_x/pk_y: (n, m, NL)  padded pubkey affine coords, STANDARD form
      pk_mask:   (n, m)      1 = real pubkey
      sig_x/sig_y: (n, 2, NL) signature affine G2 coords, standard form
                   (infinity rejected host-side per blst semantics)
      us:        (n, 2, 2, NL) hash_to_field outputs per message (standard)
      z_digits:  (n, 64)     coefficient bits, MSB first
      set_mask:  (n,)        1 = real set
    Returns (z_pk, sig_acc, bad): `bad` the stage's code, zero for nothing
    against (PREPARE_REFUSED)."""
    pk_x = _to_mont_dev(pk_x)
    pk_y = _to_mont_dev(pk_y)
    sig_x = _to_mont_dev(sig_x)
    sig_y = _to_mont_dev(sig_y)
    aggpk = _sum_key_grid(pk_x, pk_y, pk_mask)             # (n,) jacobian G1
    return _prepare_from_sums(aggpk, sig_x, sig_y, z_digits, set_mask)


def _stage_prepare_grids(wide_x, wide_y, wide_mask, narrow_x, narrow_y,
                         narrow_mask, where, sig_x, sig_y, z_digits, set_mask):
    """Stage 1 with the keys as the two grids of key_grid_plan: each grid
    summed along its own key axis by the one `_sum_key_grid`, the row sums
    of both and one identity laid end to end, set i's aggregate key read
    from entry `where[i]` (int32[n]; a padded set slot reads the identity,
    as its all-masked row of the one grid sums to), then the rest of
    `_stage_prepare` as it is. Every key is still converted, masked and
    added by the same arithmetic; the slots that hold none are fewer."""
    import jax.numpy as jnp

    sums = [
        _sum_key_grid(_to_mont_dev(x), _to_mont_dev(y), mask)
        for x, y, mask in ((wide_x, wide_y, wide_mask),
                           (narrow_x, narrow_y, narrow_mask))
    ]
    aggpk = tuple(
        jnp.concatenate([w, nr, jnp.asarray(one)[None]])[where]
        for w, nr, one in zip(*sums, co.identity(co.FQ_OPS))
    )
    return _prepare_from_sums(aggpk, _to_mont_dev(sig_x), _to_mont_dev(sig_y),
                              z_digits, set_mask)


def _gather_rows(table_x, table_y, pk_idx):
    """The table's rows named by an index grid. The host has refused every
    index outside the table (registry.index_grid), so the gather promises
    the compiler what is true."""
    return (table_x.at[pk_idx].get(mode="promise_in_bounds"),
            table_y.at[pk_idx].get(mode="promise_in_bounds"))


def _stage_prepare_indexed(table_x, table_y, pk_idx, pk_mask,
                           sig_x, sig_y, z_digits, set_mask):
    """Stage 1 with the keys gathered by validator index from the
    registry table on the device (registry.py: `table_x` / `table_y`
    uint32[capacity, NL], `pk_idx` int32[n, m]), then `_stage_prepare`
    itself: the arithmetic exists once. A masked slot gathers row 0 and is
    the identity by its mask, as a zero slot of the packed grid is. One
    chip: there is no meshed build of this program, a mesh keeps to the
    packed grid."""
    return _stage_prepare(*_gather_rows(table_x, table_y, pk_idx), pk_mask,
                          sig_x, sig_y, z_digits, set_mask)


def _stage_prepare_indexed_grids(table_x, table_y, wide_idx, wide_mask,
                                 narrow_idx, narrow_mask, where,
                                 sig_x, sig_y, z_digits, set_mask):
    """`_stage_prepare_grids` with each grid's rows gathered from the
    registry table, as `_stage_prepare_indexed` is to `_stage_prepare`."""
    return _stage_prepare_grids(
        *_gather_rows(table_x, table_y, wide_idx), wide_mask,
        *_gather_rows(table_x, table_y, narrow_idx), narrow_mask, where,
        sig_x, sig_y, z_digits, set_mask)


def _stage_pairs(z_pk, h_jac, sig_acc, set_mask):
    """Stage 3: batched affine conversion + pair-array assembly."""
    import jax.numpy as jnp

    (p1x, p1y, p1inf), (qx, qy, qinf), (sx, sy, sinf) = _batched_affine(
        z_pk, h_jac, sig_acc
    )
    # pairs: n set-pairs + 1 signature pair (exact count — the shared-f
    # Miller loop takes any pair count, no pow2 padding needed)
    neg_g1x = jnp.broadcast_to(_NEG_G1_GEN[0], (1,) + _NEG_G1_GEN[0].shape)
    neg_g1y = jnp.broadcast_to(_NEG_G1_GEN[1], (1,) + _NEG_G1_GEN[1].shape)
    px = jnp.concatenate([p1x, neg_g1x])
    py = jnp.concatenate([p1y, neg_g1y])
    qxx = jnp.concatenate([qx, sx[None]])
    qyy = jnp.concatenate([qy, sy[None]])
    pair_mask = jnp.concatenate([jnp.asarray(set_mask, bool), jnp.asarray([True])])
    # a set-pair with an identity side contributes 1 (mask it out); the
    # signature accumulator can legitimately be identity (all-zero z*sig)
    side_inf = jnp.concatenate([jnp.logical_or(p1inf, qinf), sinf[None]])
    pair_mask = jnp.logical_and(pair_mask, jnp.logical_not(side_inf))
    return px, py, qxx, qyy, pair_mask


def _fold_by_message(z_pk, fold, k: int):
    """The sets' z * pk (n Jacobian G1 lanes, the caller's order) summed
    by message: (the k lanes' sums, the k lanes' mask). `fold` is the
    marshal's `message_fold_index`, int32[2, n]: the set slots ordered by
    message lane, and their lanes, k for a padded slot (whose z * pk is
    the identity: its coefficient is zero). The shape is (n, k)'s alone,
    whatever the multiplicities: log2(n) rounds of ONE jac_add instance on
    n lanes, round r adding to every lane the lane 2^r further on where
    that still holds the same message — after them the first lane of a
    message holds its sum. jac_add is complete: two sets of one key, message
    and coefficient double, a group that cancels is the identity (its pair
    then contributes 1, as an identity aggregate's does)."""
    import jax
    import jax.numpy as jnp

    order, lane = fold[0], fold[1]
    n = order.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    none = tuple(jnp.broadcast_to(c, x.shape)
                 for c, x in zip(co.identity(co.FQ_OPS), z_pk))

    def round_(r, acc):
        ahead = jnp.int32(1) << r
        same = jnp.logical_and(jnp.roll(lane, -ahead) == lane, at + ahead < n)
        partner = tuple(jnp.roll(x, -ahead, axis=0) for x in acc)
        return co.jac_add(
            acc, co.pt_select(co.FQ_OPS, same, partner, none), co.FQ_OPS)

    acc = jax.lax.fori_loop(0, n.bit_length() - 1, round_,
                            tuple(x[order] for x in z_pk))
    lanes = jnp.arange(k, dtype=jnp.int32)
    # a message's first lane: as many stand before it as hold a lower one
    first = jnp.minimum(
        jnp.sum(lane[None, :] < lanes[:, None], axis=1, dtype=jnp.int32),
        n - 1)
    return tuple(x[first] for x in acc), lane[first] == lanes


def _stage_pairs_folded(z_pk, h_jac, sig_acc, fold):
    """Stage 3 of a dispatch that folds (message_lanes' k < n): the sets'
    z * pk summed by message into the k lanes hash-to-G2 ran on, then
    `_stage_pairs` itself at k + 1 pairs, a lane without a message masked
    as a padded set is."""
    folded, lane_mask = _fold_by_message(z_pk, fold, h_jac[2].shape[0])
    return _stage_pairs(folded, h_jac, sig_acc, lane_mask)


def _stage_pairing(px, py, qxx, qyy, pair_mask):
    """Stage 4 as ONE program: shared-accumulator multi-Miller loop + final
    exponentiation. What the meshed jit build compiles (_PairingDispatch,
    until ROADMAP M4 decides the mesh's stage 4) and nothing else: one
    chip runs the two programs below on every platform."""
    return po.pairing_product_is_one((px, py), (qxx, qyy), pair_mask)


def _stage_miller(px, py, qxx, qyy, pair_mask):
    """Stage 4 on one chip, first program: the shared-accumulator
    multi-Miller loop over miller_lane_plan's W accumulators (a row of
    them, or the one), one Fq12 out. Compiled per bucket (n + 1 pairs)."""
    return po.miller_loop_product((px, py), (qxx, qyy), pair_mask)


def _stage_final_exp(f):
    """Stage 4 on one chip, second program: final exponentiation of the
    Miller value and the comparison with one. No pair axis: one program
    for every bucket, the KZG check included."""
    return tw.fq12_eq_one(po.final_exponentiation(f))


_NEG_G1_GEN = None
_kernel_cache: dict = {}

#: per-stage donate_argnums of the staged jits when donation is on (the
#: policy and its reasons: _get_stages' docstring)
STAGE_DONATE_ARGNUMS = dict(
    prepare=(3, 4, 5), h2c=(0,), pairs=(0, 1, 2, 3), pairing=(0, 1, 2, 3, 4),
    miller=(0, 1, 2, 3, 4), final_exp=(0,),
    # the packed prepare's three, one place on: never the table
    prepare_indexed=(4, 5, 6),
    # and behind two grids and `where`: never a key grid nor the table
    prepare_grids=(7, 8, 9), prepare_indexed_grids=(7, 8, 9),
    # stage 3 of a dispatch that folds: the intermediates and the index
    pairs_folded=(0, 1, 2, 3),
)


def _init_consts():
    global _NEG_G1_GEN
    if _NEG_G1_GEN is None:
        gx, gy = pc.g1_neg(pc.G1_GEN)
        _NEG_G1_GEN = (tw.fq_to_device(gx), tw.fq_to_device(gy))


def _build_shard_map_pairing(mesh):
    """Stage-4 pair product as an EXPLICIT collective (the fallback when
    sharding propagation through the jit build fails): each shard runs the
    shared-accumulator Miller loop over its LOCAL pairs, its accumulator
    count read from that local width — partial products
    over disjoint pair subsets multiply to the full Miller value, and
    conjugation (x < 0) distributes over the product — then one all_gather
    over the sets axis, an Fq12 product of the per-shard partials, and a
    replicated final exponentiation. The pair axis (n_sets + 1, never
    mesh-divisible) is padded with masked identity lanes first."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map as _shard_map

    from ...parallel.mesh import SET_AXIS

    d = int(mesh.shape[SET_AXIS])

    def local_product(px, py, qxx, qyy, pair_mask):
        f = po.miller_loop_product((px, py), (qxx, qyy), pair_mask)
        fs = jax.lax.all_gather(f, SET_AXIS)       # (d, ...) partials
        f = po.fq12_product_any(fs)                # replicated compute
        f = po.final_exponentiation(f)
        return tw.fq12_eq_one(f)

    sharded = _shard_map(
        local_product, mesh=mesh,
        in_specs=(
            P(SET_AXIS, None), P(SET_AXIS, None),
            P(SET_AXIS, None, None), P(SET_AXIS, None, None),
            P(SET_AXIS),
        ),
        out_specs=P(),
        check_vma=False,  # the gathered product IS replicated; the
    )                     # checker cannot see through all_gather

    def pairing(px, py, qxx, qyy, pair_mask):
        pad = (-px.shape[0]) % d
        if pad:
            def z(a):
                return jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
                )

            px, py, qxx, qyy = z(px), z(py), z(qxx), z(qyy)
            pair_mask = jnp.concatenate(
                [pair_mask, jnp.zeros((pad,), pair_mask.dtype)]
            )
        return sharded(px, py, qxx, qyy, pair_mask)

    return jax.jit(pairing)


class _PairingPrograms:
    """Stage 4 on one chip, under the one stage name: TWO jitted programs
    on every platform, `_stage_miller` then `_stage_final_exp`, enqueued
    back to back — the Miller value stays on the device, the host waits
    for neither. How many accumulators the loop carries is
    miller_lane_plan's choice and the Miller program's alone (a row on a
    TPU at every pair count, the urgent bucket's 5 pairs and KZG's 4
    padded to it; elsewhere the one below 33 pairs); final exponentiation
    has no pair axis, so a process compiles it once. They share no
    compilation on purpose: beside final exponentiation's one-lane scans
    the compiler lays the wide scan's point carry limb-minor and the stage
    takes 226 ms where the two take 127 at 257 pairs, 116 where they take
    89 at 65, and the same 116.4 against 89.1 / 89.2 at 5 and 4 pairs on
    the row (a v5e, scripts/measure_miller_lanes.py --stage, PERF.md S6,
    PR 32 and PR 35). The one program `_stage_pairing` is the mesh's
    (_PairingDispatch). Callable like a jitted stage; `.lower` gives the
    two lowerings, so program capture sees the whole stage."""

    def __init__(self, miller, final_exp):
        self.miller = miller
        self.final_exp = final_exp

    def __call__(self, px, py, qxx, qyy, pair_mask):
        with _obs.annotation_scope("jaxbls:pairing.miller"):
            f = self.miller(px, py, qxx, qyy, pair_mask)
        with _obs.annotation_scope("jaxbls:pairing.final_exp"):
            return self.final_exp(f)

    def lower(self, *args):
        miller = self.miller.lower(*args)
        return miller, self.final_exp.lower(miller.out_info)


class _PairingDispatch:
    """Stage-4 dispatcher for the meshed pipeline: the explicit-sharding
    jit build first; if its compile fails (XLA sharding propagation can
    reject the uneven n+1 pair axis on some topologies), ONE structured
    warn and a permanent flip to the shard_map build. Callable like the
    plain jitted stage; `.lower` delegates so program-analytics capture
    keeps working on whichever build serves."""

    def __init__(self, mesh, jitted, donate: bool = False):
        self._mesh = mesh
        self._jit = jitted
        self._donate = donate
        self._fallback = None
        self._use_fallback = False
        self._jit_served = False  # the explicit build compiled + ran once

    def _get_fallback(self):
        if self._fallback is None:
            self._fallback = _build_shard_map_pairing(self._mesh)
        return self._fallback

    def __call__(self, *args):
        if not self._use_fallback:
            try:
                out = self._jit(*args)
                self._jit_served = True
                return out
            except Exception as e:
                if self._jit_served:
                    # the explicit build has compiled and served before:
                    # this is a RUNTIME failure (device OOM, device lost),
                    # not sharding propagation — surface it. Flipping here
                    # would also retry with already-donated buffers.
                    raise
                from ...utils.logging import get_logger

                self._use_fallback = True
                get_logger("jaxbls").warn(
                    "sharded pairing stage failed on first dispatch; "
                    "future pairing dispatches take the shard_map "
                    "pair-product collective",
                    error=f"{type(e).__name__}: {e}",
                )
                if self._donate:
                    # the failed attempt may have CONSUMED the donated
                    # inputs — an in-line retry would mask the real error
                    # with 'Array has been deleted'. Surface this failure
                    # (the hybrid router serves it from the host); the
                    # NEXT dispatch rides the fallback with fresh buffers.
                    raise
        return self._get_fallback()(*args)

    def lower(self, *args):
        fn = self._get_fallback() if self._use_fallback else self._jit
        return fn.lower(*args)

    def miller_pairs(self, n_pairs: int) -> int:
        """Pair lanes ONE Miller loop of the serving build runs: all of
        them in the jit build, a chip's share in the shard_map build."""
        if not self._use_fallback:
            return n_pairs
        from ...parallel.mesh import SET_AXIS

        return -(-n_pairs // int(self._mesh.shape[SET_AXIS]))


def _get_stages(mesh=None):
    """The four stage callables (each program cached separately on disk).

    Four stages, five programs a bucket on one chip: with `mesh=None` (the
    urgent single-chip lane, host-side callers like aggregate_verify, and
    single-device processes) stages 1-3 are plain jits — input placement
    decides the executable — and stage 4 is a _PairingPrograms: the Miller
    loop and the final exponentiation as two jits under the one stage
    name, on every platform (compiled together a wide Miller scan runs at
    half its speed, and alone the final exponentiation is one program for
    every bucket). With a mesh, the stages compile under that mesh's
    contract: explicit `in_shardings` over the `sets` axis for every
    host-marshalled input — exactly the NamedShardings `put_sets`/
    `put_pk_grid` commit, so the lowered programs (and their
    persistent-cache keys) are identical to what propagation produced,
    but a mis-placed input now fails loudly instead of silently
    resharding. Stage-OUTPUT inputs (z_pk/h_jac/sig_acc) keep `None`
    entries — their shardings are XLA's choice — and output shardings
    stay XLA's too (pinning them forks the compile cache for zero layout
    change; docs/PERF_NOTES.md "Multichip serving"). The mesh's stage 4
    is the one program `_stage_pairing`, with a shard_map fallback, via
    _PairingDispatch.

    With buffer donation on (pipeline.donation_enabled — default on
    accelerators, env/flag overridable) the per-batch inputs are marked
    `donate_argnums` so XLA may reuse their HBM for same-shaped
    intermediates instead of fresh allocations:

      prepare: sig_x/sig_y/z_digits (their Montgomery conversions are
               shape-identical), NEVER pk_x/pk_y/pk_mask (the
               device-resident pubkey cache outlives the batch) and
               NEVER set_mask (stage 3 reads it again);
      h2c:     us (consumed into the SSWU map);
      pairs:   the stage-1/2 intermediates (z_pk, h_jac, sig_acc) and
               set_mask — all dead after pair assembly;
      pairing: everything (the output is one scalar) — of the two
               programs, the Miller loop its five inputs, the final
               exponentiation its one.

    Cached per (donation mode, mesh signature) — tests flip
    LIGHTHOUSE_TPU_DONATE and the mesh seams within one process and both
    decisions are baked into the jit."""
    import jax

    from . import pipeline as pl

    _init_consts()
    donate = pl.donation_enabled()[0]
    if mesh is None:
        key = f"stages_d{int(donate)}"
    else:
        from ...parallel import mesh_shape_key

        key = f"stages_d{int(donate)}_{mesh_shape_key(mesh)}"
    if key not in _kernel_cache:
        from ...utils.jaxcfg import setup_compilation_cache

        setup_compilation_cache()
        donate_kw = {
            stage: dict(donate_argnums=argnums) if donate else {}
            for stage, argnums in STAGE_DONATE_ARGNUMS.items()
        }
        if mesh is None:
            _kernel_cache[key] = (
                jax.jit(_stage_prepare, **donate_kw["prepare"]),
                jax.jit(h2.hash_to_g2_jacobian, **donate_kw["h2c"]),
                jax.jit(_stage_pairs, **donate_kw["pairs"]),
                _PairingPrograms(
                    jax.jit(_stage_miller, **donate_kw["miller"]),
                    jax.jit(_stage_final_exp, **donate_kw["final_exp"]),
                ),
            )
        else:
            from ...parallel import mesh as pm

            def sets_s(ndim):
                return pm.sets_sharding(mesh, ndim)

            prepare_in = (
                sets_s(3), sets_s(3), sets_s(2),               # pk_x/y/mask
                sets_s(3), sets_s(3),                          # sig_x/sig_y
                sets_s(2), sets_s(1),                          # z_digits/mask
            )
            pairs_in = (None, None, None, sets_s(1))  # stage outputs + mask
            _kernel_cache[key] = (
                jax.jit(_stage_prepare, in_shardings=prepare_in,
                        **donate_kw["prepare"]),
                jax.jit(h2.hash_to_g2_jacobian, in_shardings=(sets_s(4),),
                        **donate_kw["h2c"]),
                jax.jit(_stage_pairs, in_shardings=pairs_in,
                        **donate_kw["pairs"]),
                _PairingDispatch(
                    mesh, jax.jit(_stage_pairing, **donate_kw["pairing"]),
                    donate=donate,
                ),
            )
    return _kernel_cache[key]


#: what one chip's batch lane serves beside `_get_stages()`, by the name
#: its donation goes under in STAGE_DONATE_ARGNUMS: stage 1 three ways, and
#: stage 3 of a dispatch that folds its sets by message
_ONE_CHIP_VARIANTS = dict(
    prepare_indexed=_stage_prepare_indexed,
    prepare_grids=_stage_prepare_grids,
    prepare_indexed_grids=_stage_prepare_indexed_grids,
    pairs_folded=_stage_pairs_folded,
)


def _get_one_chip_variant(stage: str):
    """One of `_ONE_CHIP_VARIANTS`, jitted for one chip (the batch lane of
    a process without a mesh), under the donation mode of `_get_stages`.
    Stage 1: the keys by index from the registry table, the keys as the two
    grids of key_grid_plan, or both — with the packed one-grid prepare of
    `_get_stages` the four stage-1 programs a node may serve at a bucket;
    the dispatch's own keys say which. Stage 3: `_stage_pairs_folded`,
    where the dispatch's own messages say so (message_lanes)."""
    import jax

    from . import pipeline as pl

    _init_consts()
    donate = pl.donation_enabled()[0]
    key = f"{stage}_d{int(donate)}"
    if key not in _kernel_cache:
        from ...utils.jaxcfg import setup_compilation_cache

        setup_compilation_cache()
        _kernel_cache[key] = jax.jit(
            _ONE_CHIP_VARIANTS[stage],
            **(dict(donate_argnums=STAGE_DONATE_ARGNUMS[stage])
               if donate else {}),
        )
    return _kernel_cache[key]


def warm_stages(n_sets: int, n_pks: int, single_chip: bool = False) -> None:
    """Pre-compile the prepare and hash-to-G2 stages for one bucket shape,
    CONCURRENTLY. Their input layouts are fully determined by the marshal
    (leading set axis sharded over the mesh — or whole on one chip for the
    urgent lane with `single_chip=True`), so dummy zero inputs placed the
    same way hit the same jit-cache entries the real dispatch will use,
    and compiling both in threads makes the wall cost ~max of the two
    largest programs instead of their sum (the r4 multichip dryrun timed
    out in sequential XLA:CPU stage compiles — ~3 min for prepare alone).
    Stages 3/4 take stage OUTPUTS as inputs (shardings chosen by XLA), so
    they still compile on first real dispatch. Prepare is warmed over the
    ONE (n, m) key grid, which a batch of near-equal widths, the urgent
    lane and a mesh run; a one-chip batch of unequal widths (a block, a
    dispatch of aggregates) runs the two-grid prepare of its own
    key_grid_plan and compiles it at its first dispatch. On one chip's
    batch lane a bucket wider than one row of message lanes also warms
    what a dispatch that folds to that row runs (message_lanes' k = 128,
    what a mainnet slot's attestation messages give): hash-to-G2 at k
    lanes, `_stage_pairs_folded` and stage 4 at k + 1 pairs, the last two
    on zero inputs shaped as stage outputs are; another k compiles at its
    first dispatch.

    Callers: the node's startup warmup thread walks the autotune plan's
    bucket list through here (autotune/runtime.start_warmup — which also
    warms the single-chip variant of the plan's urgent shapes); tests and
    bench warm ad-hoc shapes. The wall time is recorded as the bucket's
    compile cost in the autotune profiler."""
    import threading
    import time

    import jax

    from ...autotune import profiler
    from ...parallel import get_mesh, put_pk_grid, put_single, put_sets

    mesh = None if single_chip else get_mesh()
    prepare, h2c_stage, _, pairing_stage = _get_stages(mesh=mesh)
    n, m = padding_bucket(n_sets, n_pks, mesh=mesh, single_chip=single_chip)
    t0 = time.time()

    if single_chip:
        put_pk_grid = put_sets = put_single  # noqa: F811 — one placement
    pk_x = put_pk_grid(np.zeros((n, m, lb.NL), np.uint32))
    pk_y = put_pk_grid(np.zeros((n, m, lb.NL), np.uint32))
    pk_mask = put_pk_grid(np.ones((n, m), np.uint32))
    sig_x = put_sets(np.zeros((n, 2, lb.NL), np.uint32))
    sig_y = put_sets(np.zeros((n, 2, lb.NL), np.uint32))
    z_digits = put_sets(np.ones((n, Z_BITS), np.uint32))
    set_mask = put_sets(np.ones((n,), np.uint32))
    us = put_sets(np.zeros((n, 2, 2, lb.NL), np.uint32))

    def _warm(fn, *args):
        jax.block_until_ready(fn(*args))

    def _warm_folded(k):
        def zeros(*shape):
            return put_single(np.zeros(shape + (lb.NL,), np.uint32))

        _warm(pairing_stage, *_get_one_chip_variant("pairs_folded")(
            tuple(zeros(n) for _ in range(3)),
            tuple(zeros(k, 2) for _ in range(3)),
            tuple(zeros(2) for _ in range(3)),
            put_single(np.zeros((2, n), np.int32)),
        ))

    threads = [
        threading.Thread(
            target=_warm,
            args=(prepare, pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask),
        ),
        threading.Thread(target=_warm, args=(h2c_stage, us)),
    ]
    k = message_lanes(po.MILLER_LANES, n)      # a slot's 128 messages
    if mesh is None and not single_chip and k < n:
        threads += [
            threading.Thread(target=_warm, args=(
                h2c_stage, put_single(np.zeros((k, 2, 2, lb.NL), np.uint32)))),
            threading.Thread(target=_warm_folded, args=(k,)),
        ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    profiler.observe_compile(n, m, time.time() - t0)
    if _obs_perf.analytics_enabled():
        # the executables are hot in the XLA compile cache now, so the
        # lower+compile pair only re-traces: capture the compiled
        # programs' flops/bytes/HBM for this bucket (stages 3/4 are
        # captured at their first attributed dispatch instead — their
        # inputs are stage outputs). With donation on, the warm executes
        # above CONSUMED the per-batch dummies — re-place fresh zeros so
        # the capture never touches a donated buffer.
        from . import pipeline as _pl

        if _pl.donation_enabled()[0]:
            sig_x = put_sets(np.zeros((n, 2, lb.NL), np.uint32))
            sig_y = put_sets(np.zeros((n, 2, lb.NL), np.uint32))
            z_digits = put_sets(np.ones((n, Z_BITS), np.uint32))
            us = put_sets(np.zeros((n, 2, 2, lb.NL), np.uint32))
        _obs_perf.maybe_capture_program(
            "prepare", prepare,
            (pk_x, pk_y, pk_mask, sig_x, sig_y, z_digits, set_mask), (n, m),
        )
        _obs_perf.maybe_capture_program("h2c", h2c_stage, (us,), (n, m))


def warm_prepare_indexed(n_sets: int, n_pks: int, table) -> None:
    """Pre-compile stage 1 of the indexed path (`_stage_prepare_indexed`)
    for one bucket shape against `table`, on zero inputs placed as
    `_marshal_indices` and the marshal place the real ones. For a node on
    ONE chip whose chain keeps its registry on the device: there every set
    the chain's builders make names rows of the table, so gossip batches
    and blocks alike take this program at their own buckets, not the packed
    prepare `warm_stages` compiles — over one (n, m) grid here as there;
    a block's two grids compile at its first dispatch. The table's capacity
    is part of the program's shape: a registry that outgrows it (a
    `registry.ROW_CHUNK` of deposits) compiles anew at its next dispatch."""
    import jax

    from ...parallel import put_single

    n, m = padding_bucket(n_sets, n_pks, single_chip=True)
    table_x, table_y, _ = table.snapshot()
    jax.block_until_ready(_get_one_chip_variant("prepare_indexed")(
        table_x, table_y,
        put_single(np.zeros((n, m), np.int32)),
        put_single(np.ones((n, m), np.uint32)),
        put_single(np.zeros((n, 2, lb.NL), np.uint32)),
        put_single(np.zeros((n, 2, lb.NL), np.uint32)),
        put_single(np.ones((n, Z_BITS), np.uint32)),
        put_single(np.ones((n,), np.uint32)),
    ))


class VerifyHandle:
    """In-flight verification: resolves to bool on .result().

    Keeps references to the dispatched device values so the work proceeds
    asynchronously; result() blocks on the device and applies the host-side
    semantic (stage 1's `bad` code nonzero => False, each reason counted in
    jaxbls_prepare_refused_total at the first resolve). Dispatch-timed handles carry
    their padding bucket and submit time so resolving feeds the autotune
    profiler (first resolve only — result() is idempotent). The handle
    keeps the pipeline Trace current at its dispatch: `jaxbls:device_wait`
    (result() entered -> the verdict read; `t_ready` is its end, which
    the dispatcher reads) lands on the unit that owns the dispatch,
    whichever thread resolves it."""

    __slots__ = ("_ok", "_bad", "_hostfail", "_bucket", "_t0", "_n_real",
                 "_trace", "t_ready")

    def __init__(self, ok=None, bad=None, hostfail=False,
                 bucket=None, t0=None, n_real=0, trace=None):
        self._ok = ok
        self._bad = bad
        self._hostfail = hostfail
        self._bucket = bucket
        self._t0 = t0
        self._n_real = n_real
        self._trace = trace
        self.t_ready = None

    def result(self) -> bool:
        if self._hostfail:
            return False
        with _obs.span("jaxbls:device_wait", self._trace) as waited:
            ok, bad = bool(np.asarray(self._ok)), int(np.asarray(self._bad))
        self.t_ready = waited.t1
        if self._t0 is not None and self._bucket is not None:
            from ...autotune import profiler

            dt, self._t0 = self.t_ready - self._t0, None
            profiler.observe_dispatch(*self._bucket, dt, self._n_real)
            _seen_exec_buckets.add(self._bucket)
            for bit, why in PREPARE_REFUSED.items():
                if bad & bit:
                    _PREPARE_REFUSED.labels(why).inc()
        return ok and not bad


class JaxBackend:
    """Batched TPU verification backend (registered as "jax" in bls.api)."""

    name = "jax"
    # dispatches feed the autotune profiler from inside VerifyHandle, so
    # external measurement loops (autotune/calibrate.py) must not record
    # the same verify a second time
    autotune_self_recording = True

    def __init__(self, dst: bytes = DST_POP):
        from . import pipeline as pl

        self.dst = dst
        # device-resident pubkey marshalling cache:
        #   fingerprint(tuple of id(pk)) -> (pk_x_dev, pk_y_dev, mask, keepalive)
        self._pk_cache: dict = {}
        self._pk_cache_order: list = []
        # the pipelined executor: depth-bounded double-buffering window +
        # the urgent bypass lane (crypto/jaxbls/pipeline.py). Depth and
        # donation resolve env > autotune plan > default at construction;
        # a profile installed later re-resolves through the plan listener
        # (autotune/runtime.add_plan_listener).
        self.dispatcher = pl.PipelinedDispatcher(workload="bls")
        # blob batches (verify_kzg_batch_async) are the device ledger's
        # `kzg` tenant: a window of their own, so a blob-carrying block's
        # sidecars never queue behind four attestation batches
        self.kzg_dispatcher = pl.PipelinedDispatcher(workload="kzg")
        self._registry = None    # see `registry`
        try:
            from ...autotune import runtime as _at_runtime

            _at_runtime.add_plan_listener(self._on_plan_installed)
        except Exception:
            pass  # autotune broken must never take down the backend

    def _on_plan_installed(self, _plan) -> None:
        """A new autotune profile was installed mid-run: re-resolve the
        dispatch depth unless an explicit env/flag pinned it (the same
        live-retune contract as the hybrid router's budgets)."""
        from . import pipeline as pl

        for dispatcher in (self.dispatcher, self.kzg_dispatcher):
            if dispatcher.depth_source in ("profile", "default"):
                dispatcher.set_depth(*pl.resolve_depth())

    @property
    def registry(self):
        """The registry table this backend gathers keys from
        (registry.py), or None: every dispatch then packs its keys. Held
        weakly: the table lives as long as the pubkey cache that feeds it,
        and a chain that goes away leaves the place to the next."""
        return None if self._registry is None else self._registry()

    @registry.setter
    def registry(self, table) -> None:
        self._registry = None if table is None else weakref.ref(table)

    def install_registry(self):
        """Keep the validator registry's keys on the device: installs a
        fresh, empty `registry.PubkeyTable` in place of any other and
        returns it, for a `ValidatorPubkeyCache` to feed and to keep. From
        then on a batch-lane dispatch whose sets all name rows of THIS
        table gathers its keys from it (`_marshal_indices`). One table a
        backend: the sets of a second registry in the process (another
        chain) name another table or none, and keep to the packed grid."""
        from .registry import PubkeyTable

        table = PubkeyTable()
        self.registry = table
        return table

    # -- the multi-set hot path ------------------------------------------

    def _marshal_indices(self, sets, plan, real_keys: int):
        """The key side of a dispatch as registry rows: (table_x, table_y,
        then per grid of `plan` (key_grid_plan) idx int32[rows, width] and
        its mask, then `where` if there are two) on the device, in place of
        `_marshal_pubkeys`' limb grids (2 x 2 MB where one limb grid is
        100 MB at 16 x 32,768). Chosen by the data alone: None — the batch
        packs its keys — unless a table is installed and EVERY set carries
        indices into that very table (`signing_registry`: a set built
        against another registry, or against none, does not, whatever its
        indices are). False when a set of this registry names a row outside
        the table: refused and counted, the dispatch is a host failure.
        Rows are taken with the `len` of one snapshot, so a key appended
        before this call is visible to it."""
        table = self.registry
        if table is None or any(s.signing_registry is not table for s in sets):
            return None
        table_x, table_y, rows = table.snapshot()
        from ...parallel import put_single

        with _obs.span("jaxbls:marshal.indices", keys=real_keys) as packed:
            grids = table.index_grid(sets, plan, rows)
            if grids is None:
                return False
            packed.args["bytes"] = sum(g.nbytes for g in grids)
            if plan[1] is not None:
                grids += (plan[1],)
            grids = tuple(put_single(g) for g in grids)
        return (table_x, table_y) + grids

    def _marshal_pubkeys(self, sets, plan, single_chip: bool = False):
        """Standard-form limb arrays for all signing keys, as the grids of
        `plan` (key_grid_plan; `one_key_grid(n, m)` for the (n, m) grid):
        per grid x and y (rows, width, NL) and the mask (rows, width), then
        `where` if there are two — stage 1's key arguments, in its order.

        Cached on device keyed by the identity of the pubkey objects — the
        steady-state path (gossip firehose over a known validator registry)
        re-verifies the same PublicKey objects every slot, so after the
        first batch the pubkey upload cost disappears (the analog of the
        reference keeping decompressed keys in ValidatorPubkeyCache). The
        placement lane is part of the key — single-chip by name, meshed
        by TOPOLOGY: a grid sharded for one mesh must never feed the
        urgent single-chip program or a re-resolved mesh of another
        shape (the --mesh-devices sweep flips topologies mid-process)."""
        with _obs.span("jaxbls:marshal.pubkeys") as packed:
            if single_chip:
                lane = "single"
            else:
                from ...parallel import mesh_shape_key

                lane = mesh_shape_key()
            # fingerprint covers the set grouping, not just the flat key
            # sequence: the same keys split differently must not reuse
            # another layout's aggregation mask — nor the urgent lane's one
            # grid serve the batch lane's two (the grids are in the key)
            fp = (
                lane, plan[0],
                tuple(len(s.signing_keys) for s in sets),
                tuple(id(pk) for s in sets for pk in s.signing_keys),
            )
            hit = self._pk_cache.get(fp)
            packed.args["hit"] = int(hit is not None)
            if hit is not None:
                _PK_CACHE.labels("hit").inc()
                return hit[:-1]
            _PK_CACHE.labels("miss").inc()

            grids = [
                (np.zeros(g + (lb.NL,), np.uint32),
                 np.zeros(g + (lb.NL,), np.uint32), np.zeros(g, np.uint32))
                for g in plan[0]
            ]
            for (g, row), s in zip(_grid_rows(plan, len(sets)), sets):
                keys = s.signing_keys
                pk_x, pk_y, pk_mask = grids[g]
                pk_x[row, : len(keys)] = pack_ints_vec([pk.point[0] for pk in keys])
                pk_y[row, : len(keys)] = pack_ints_vec([pk.point[1] for pk in keys])
                pk_mask[row, : len(keys)] = 1
            arrays = [a for grid in grids for a in grid]
            nbytes = sum(a.nbytes for a in arrays)
            packed.args["bytes"] = nbytes
            if plan[1] is not None:
                arrays.append(plan[1])
        from ...parallel import put_pk_grid, put_single

        # (rows, width, ...) pubkey arrays: set axis sharded, key axis
        # whole. Urgent single-chip batches place whole on one device
        # instead, and so do the two grids (never laid over a mesh).
        put = put_single if single_chip else put_pk_grid
        with _obs.span("jaxbls:marshal.pubkeys_upload", bytes=nbytes):
            placed = tuple(put(a) for a in arrays)
            # keep strong refs to the key objects so ids stay valid while
            # cached; the oldest grid leaves the cache, and the device, here
            keepalive = (fp, [pk for s in sets for pk in s.signing_keys])
            self._pk_cache[fp] = placed + (keepalive,)
            self._pk_cache_order.append(fp)
            if len(self._pk_cache_order) > 8:
                old = self._pk_cache_order.pop(0)
                self._pk_cache.pop(old, None)
        return placed

    def verify_signature_sets_async(self, sets, rands, urgent: bool = False):
        """Marshal + submit one batch through the pipelined executor.

        Host marshalling runs HERE (it overlaps whatever the device is
        executing); the staged device dispatch runs inside the
        dispatcher's submit, which blocks first when `depth` batches are
        already in flight (resolving the oldest — the double-buffering
        backpressure). `urgent=True` takes the bypass lane: no window
        wait, no window slot — the low-latency path for single-set
        verifies, PINNED SINGLE-CHIP (plain pow2 bucket, whole-array
        placement on one device, the unsharded stage programs) so
        sharding never taxes the ~ms path with mesh padding or
        collective latency. Returns a ticket with .result() -> bool."""
        import time

        from ...parallel import get_mesh, put_single, put_sets
        from ...parallel.mesh import MESH_DISPATCH

        # the whole marshal is one span (the benchmark's marshal_ms reads
        # its seconds off the histogram), its five parts the children and
        # the bucket's choice and the dispatch's counters its self time:
        # an idle gap of the device that covers several parts is named by
        # this one in a profiler capture, not by the caller's scope
        with _obs.span("jaxbls:marshal") as marshalled:
            mesh = None if urgent else get_mesh()
            single_chip = mesh is None
            prepare, h2c_stage, pairs_stage, pairing_stage = _get_stages(mesh=mesh)
            n_real = len(sets)
            # pad the set axis to the compile bucket AND to a multiple of the
            # device mesh (multi-chip: sets are data-parallel over the mesh,
            # the cross-set reductions become collectives — parallel/mesh.py);
            # the urgent lane keeps plain pow2 buckets on one chip
            widths = [len(s.signing_keys) for s in sets]
            n, m = padding_bucket(
                n_real, max(widths), mesh=mesh, single_chip=single_chip,
            )
            # the bucket is the dispatch's NAME (the handle's, the trace's,
            # the autotune keys'); how its keys lie is the plan's: one
            # (n, m) grid on the urgent lane, over a mesh and for near-equal
            # widths, else a wide grid and a narrow one
            plan = (one_key_grid(n, m) if urgent or mesh is not None
                    else key_grid_plan(widths, n, m))
            grids = plan[0]
            # three truthful lanes: urgent bypass (pinned to one chip), meshed
            # batch, and ordinary batch on a mesh-less node — a dashboard must
            # never read single-device batch traffic as urgent-path activity
            MESH_DISPATCH.labels(
                "urgent" if urgent else ("sharded" if mesh is not None
                                         else "single_device")
            ).inc()
            real_keys = sum(widths)
            _BUCKET_SLOTS.labels("sets", "real").inc(n_real)
            _BUCKET_SLOTS.labels("sets", "padded").inc(n)
            _BUCKET_SLOTS.labels("keys", "real").inc(real_keys)
            _BUCKET_SLOTS.labels("keys", "padded").inc(
                sum(rows * width for rows, width in grids))
            # the messages: each distinct byte string once, in the order it
            # first comes (a set whose message differs in one byte has a
            # lane of its own), and how they lie — a lane a set, or on one
            # chip's batch lane message_lanes' k < n lanes, the sets folded
            # onto them before stage 3
            lane_of: dict = {}
            for s in sets:
                lane_of.setdefault(s.message, len(lane_of))
            distinct_messages = len(lane_of)
            k = (n if urgent or mesh is not None
                 else message_lanes(distinct_messages, n))
            folds = k < n
            _DISPATCH_MESSAGES.labels("sent").inc(n_real)
            _DISPATCH_MESSAGES.labels("distinct").inc(distinct_messages)
            _DISPATCH_MESSAGES.labels("lanes").inc(k)
            _DISPATCH_MESSAGES.labels("folded_sets").inc(n_real if folds else 0)
            if folds:
                pairs_stage = _get_one_chip_variant("pairs_folded")
            _TREE_SUM_LANE_ADDS.labels("done").inc(
                sum(co.tree_sum_plan(width, rows)[3] for rows, width in grids))
            _TREE_SUM_LANE_ADDS.labels("needed").inc(real_keys - n_real)
            miller_pairs = k + 1
            if isinstance(pairing_stage, _PairingDispatch):
                miller_pairs = pairing_stage.miller_pairs(miller_pairs)
            _count_miller_plan(miller_pairs)

            # the keys: registry rows gathered on the device where the data
            # allows it (one chip's batch lane; a mesh keeps the packed
            # grid), else their coordinates packed into the plan's grids
            indexed = (None if urgent or mesh is not None
                       else self._marshal_indices(sets, plan, real_keys))
            if indexed is False:
                return VerifyHandle(hostfail=True)  # a row the table lacks
            if indexed is None:
                _REGISTRY_KEYS.labels("packed").inc(real_keys)
                keys_in = self._marshal_pubkeys(
                    sets, plan, single_chip=single_chip
                )
            else:
                _REGISTRY_KEYS.labels("table").inc(real_keys)
                keys_in = indexed
            # stage 1 by what was laid: `prepare` stays the stages' own for
            # one packed grid, the program every lane ran before the plan
            variant = ("prepare" + ("" if indexed is None else "_indexed")
                       + ("_grids" if len(grids) == 2 else ""))
            if variant != "prepare":
                prepare = _get_one_chip_variant(variant)

            with _obs.span("jaxbls:marshal.sigs"):
                sig_x = np.zeros((n, 2, lb.NL), np.uint32)
                sig_y = np.zeros((n, 2, lb.NL), np.uint32)
                z_digits = np.zeros((n, Z_BITS), np.uint32)
                set_mask = np.zeros((n,), np.uint32)

                sig_ints = []
                for s in sets:
                    sp = s.signature.point
                    if sp is None:
                        return VerifyHandle(hostfail=True)  # infinity signature fails
                    sig_ints.append(sp)
                sig_x[:n_real, 0] = pack_ints_vec([sp[0][0] for sp in sig_ints])
                sig_x[:n_real, 1] = pack_ints_vec([sp[0][1] for sp in sig_ints])
                sig_y[:n_real, 0] = pack_ints_vec([sp[1][0] for sp in sig_ints])
                sig_y[:n_real, 1] = pack_ints_vec([sp[1][1] for sp in sig_ints])

                zmask = (1 << 64) - 1
                z_digits[:n_real] = co.scalars_to_bits(
                    [z & zmask for z in rands], Z_BITS)
                set_mask[:n_real] = 1

            with _obs.span("jaxbls:marshal.h2f", messages=distinct_messages):
                # each distinct message hashed once, whatever is laid; the
                # sets are never permuted: a folding dispatch sends, beside
                # its k lanes of `us`, where each set's z * pk goes
                us = np.zeros((k, 2, 2, lb.NL), np.uint32)
                hashed = h2.hash_to_field_batch(list(lane_of), self.dst)
                lanes = [lane_of[s.message] for s in sets]
                if folds:
                    us[:distinct_messages] = hashed
                    fold = message_fold_index(lanes, n, k)
                else:
                    us[:n_real] = hashed[lanes]

            nbytes = (sig_x.nbytes + sig_y.nbytes + z_digits.nbytes
                      + set_mask.nbytes + us.nbytes
                      + (fold.nbytes if folds else 0))
            # staged dispatch: intermediates stay on device between jit calls,
            # inputs placed with the set axis sharded over the mesh (urgent:
            # whole on one chip; also the no-mesh single-device case)
            put = put_single if single_chip else put_sets
            with _obs.span("jaxbls:marshal.upload", bytes=nbytes):
                sig_x, sig_y, z_digits, set_mask, us = (
                    put(sig_x), put(sig_y), put(z_digits), put(set_mask), put(us),
                )
                # stage 3's last argument: the fold's index, or the set mask
                pairs_by = put(fold) if folds else set_mask
        _MARSHAL_SECONDS.observe(marshalled.t1 - marshalled.t0)
        tr = _obs.current_trace()
        if tr is not None:
            tr.annotate(bucket=f"{n}x{m}",
                        key_grids="+".join(f"{r}x{w}" for r, w in grids),
                        real_sets=n_real,
                        real_keys=real_keys,
                        distinct_messages=distinct_messages,
                        message_lanes=k, z_window=Z_WINDOW)

        def dispatch():
            # the dispatcher's `jaxbls:enqueue` span is open around this
            # call and each stage's jit call a `jaxbls:<stage>` child of
            # it; with device attribution on (bn --device-trace, bench,
            # calibrator) run_stage also event-times each resolve into
            # the per-stage jaxbls_stage_* families and device:<stage>
            # spans — which SERIALIZES the stages (diagnostic mode; the
            # default path stays fully async)
            t0 = time.perf_counter()
            attr = _obs_dev.begin((n, m), trace=tr)
            z_pk, sig_acc, bad = _obs_dev.run_stage(
                attr, "prepare", prepare,
                *keys_in, sig_x, sig_y, z_digits, set_mask,
            )
            h_jac = _obs_dev.run_stage(attr, "h2c", h2c_stage, us)
            px, py, qxx, qyy, pair_mask = _obs_dev.run_stage(
                attr, "pairs", pairs_stage, z_pk, h_jac, sig_acc, pairs_by
            )
            ok = _obs_dev.run_stage(
                attr, "pairing", pairing_stage, px, py, qxx, qyy, pair_mask
            )
            return VerifyHandle(ok, bad, bucket=(n, m), t0=t0, n_real=n_real,
                                trace=tr)

        return self.dispatcher.submit(dispatch, urgent=urgent)

    def verify_signature_sets(self, sets, rands) -> bool:
        return self.verify_signature_sets_async(sets, rands).result()

    # -- the urgent fast path --------------------------------------------
    # single-set / small urgent verifies (a gossip block's proposer sig,
    # the hybrid router's warm small batches) ride the dispatcher's
    # bypass lane: they never wait behind the depth window of coalesced
    # firehose batches. Exposed as separate methods so policy layers
    # (crypto/bls/hybrid.py) can probe with getattr and stay compatible
    # with backends that have no lane concept.

    def verify_signature_sets_urgent_async(self, sets, rands):
        return self.verify_signature_sets_async(sets, rands, urgent=True)

    def verify_signature_sets_urgent(self, sets, rands) -> bool:
        return self.verify_signature_sets_async(sets, rands, urgent=True).result()

    # -- single-set paths reuse the same kernel ---------------------------

    def verify_single(self, pk, message: bytes, sig) -> bool:
        if sig.is_infinity():
            return False
        from .. import bls

        s = bls.SignatureSet(sig, (pk,), message)
        # a lone verify is urgent by definition: bypass the batch window
        return self.verify_signature_sets_urgent([s], [1])

    def aggregate_verify(self, pks, messages, sig) -> bool:
        """Distinct-message AggregateVerify:
        prod_i e(pk_i, H(m_i)) * e(-g1, sig) == 1 — a plain pairing product
        (no random coefficients), so it gets its own small kernel."""
        if len(pks) == 0 or sig.point is None:
            return False
        kernel = _get_aggregate_kernel()
        n_real = len(pks)
        n = max(MIN_SETS, _next_pow2(n_real))

        pk_x = np.zeros((n, lb.NL), np.uint32)
        pk_y = np.zeros((n, lb.NL), np.uint32)
        mask = np.zeros((n,), np.uint32)
        pk_x[:n_real] = pack_ints_vec([pk.point[0] for pk in pks])
        pk_y[:n_real] = pack_ints_vec([pk.point[1] for pk in pks])
        mask[:n_real] = 1

        sp = sig.point
        sig_xy = np.zeros((2, 2, lb.NL), np.uint32)
        sig_xy[0, 0] = pack_ints_vec([sp[0][0]])[0]
        sig_xy[0, 1] = pack_ints_vec([sp[0][1]])[0]
        sig_xy[1, 0] = pack_ints_vec([sp[1][0]])[0]
        sig_xy[1, 1] = pack_ints_vec([sp[1][1]])[0]

        us = np.zeros((n, 2, 2, lb.NL), np.uint32)
        us[:n_real] = h2.hash_to_field_batch(list(messages), self.dst)
        _, h2c_stage, _, pairing_stage = _get_stages()
        h_jac = h2c_stage(us)
        px, py, qxx, qyy, pair_mask = kernel(pk_x, pk_y, mask, sig_xy, h_jac)
        ok = pairing_stage(px, py, qxx, qyy, pair_mask)
        return bool(np.asarray(ok))

    # -- accelerated primitives exposed to KZG ----------------------------

    def g1_msm(self, points, scalars):
        """sum_i scalars[i] * points[i] over G1.

        points: host affine int pairs (None = identity); scalars: ints mod r.
        Returns a host affine int pair or None. Batched double-and-add on
        device + masked tree reduce — the MSM feeding KZG commitments and
        the batch verifier's linear combinations (crypto/kzg.py)."""
        pts = list(points)
        scs = list(scalars)
        n_real = len(pts)
        if n_real == 0:
            return None
        n = max(MIN_SETS, _next_pow2(n_real))

        from . import msm as _msm

        kernel, w = _get_msm_kernel()
        px = np.zeros((n, lb.NL), np.uint32)
        py = np.zeros((n, lb.NL), np.uint32)
        mask = np.zeros((n,), np.uint32)
        px[:n_real] = pack_ints_vec([p[0] if p else 0 for p in pts])
        py[:n_real] = pack_ints_vec([p[1] if p else 0 for p in pts])
        mask[:n_real] = [0 if p is None else 1 for p in pts]
        real_digits = _msm.msm_digits(scs, w)
        digits = np.zeros((n, real_digits.shape[1]), np.uint32)
        digits[:n_real] = real_digits

        x, y, inf = kernel(px, py, mask, digits)
        if bool(np.asarray(inf)):
            return None
        return (lb.unpack(np.asarray(x)), lb.unpack(np.asarray(y)))

    def g1_msm_fixed(self, points, scalars):
        """Fixed-base MSM with per-point-set comb tables cached on device
        (msm.py): the KZG commitment/proof path reuses the SAME Lagrange
        points every call, so the one-time table build amortizes to a ~16x
        sequential-depth cut per MSM (the TPU-shaped Pippenger — SURVEY
        §7.1; c-kzg's precomputed-table analog)."""
        cache = self.__dict__.setdefault("_fixed_msm_cache", {})
        order = self.__dict__.setdefault("_fixed_msm_order", [])
        fp = id(points)
        hit = cache.get(fp)
        if hit is None or hit[1] is not points:
            from .msm import FixedBaseMSM

            hit = (FixedBaseMSM(points), points)   # points ref keeps id valid
            cache[fp] = hit
            if fp in order:          # id reuse after GC: don't double-track
                order.remove(fp)
            order.append(fp)
            if len(order) > 4:
                cache.pop(order.pop(0), None)
        return hit[0].msm(scalars)

    # -- the KZG blob batch ----------------------------------------------

    def verify_kzg_batch_async(self, commitments, proofs, r_pows,
                               y_scalars, z_scalars, tau_g2):
        """The group side of `verify_blob_kzg_proof_batch` for up to
        msm.KZG_BLOB_SLOTS blobs (one program, one row of lanes, whatever
        the batch holds), as ONE pipelined dispatch on the `kzg` tenant's
        batch lane: every commitment C_i and proof W_i times the
        group order (the subgroup checks), the spec's two linear
        combinations C' = sum r_pows[i] C_i + y_scalars[i] G1 + z_scalars[i]
        W_i and W' = sum r_pows[i] W_i in the same double-and-add pass
        (msm.kzg_lincomb_kernel), then e(C', H) e(-W', tau H) == 1 on the
        BLS stage 4 (`_get_stages()[3]`: on a TPU its four pair lanes padded
        to the Miller loop's row, then the final exponentiation every
        bucket shares) — C' and W' never leave the device. Points are host
        affine pairs on the curve (None = infinity), scalars ints mod r.
        Returns a ticket; `.result()` reads the device ONCE: (ok, [(C_i in
        the subgroup, W_i in it), ...]). `ok` means nothing unless every
        flag is True."""
        from ...parallel import put_single
        from . import msm as _msm

        n_real = len(commitments)
        slots, rows = _msm.KZG_BLOB_SLOTS, _msm.KZG_ROWS
        if not 1 <= n_real <= slots:
            raise ValueError(f"a KZG batch holds 1 to {slots} blobs, "
                             f"got {n_real}")
        lanes = slots * rows
        lincomb, verdict = _get_kzg_kernels()
        pairing_stage = _get_stages()[3]
        with _obs.span("kzg:pack", blobs=n_real, lanes=lanes):
            points, scalars, index = [], [], []
            for i, (c, w) in enumerate(zip(commitments, proofs)):
                for row, point, scalar in (
                    (_msm.KZG_ROW_C, c, r_pows[i]),
                    (_msm.KZG_ROW_G1, pc.G1_GEN, y_scalars[i]),
                    (_msm.KZG_ROW_ZW, w, z_scalars[i]),
                    (_msm.KZG_ROW_W, w, r_pows[i]),
                    (_msm.KZG_ROW_ORDER_C, c, R),
                    (_msm.KZG_ROW_ORDER_W, w, R),
                ):
                    if point is not None:
                        points.append(point)
                        scalars.append(scalar)
                        index.append(i * rows + row)
            px = np.zeros((lanes, lb.NL), np.uint32)
            py = np.zeros((lanes, lb.NL), np.uint32)
            live = np.zeros((lanes,), np.uint32)
            bits = np.zeros((lanes, _msm.KZG_SCALAR_BITS), np.uint32)
            px[index] = pack_ints_vec([p[0] for p in points])
            py[index] = pack_ints_vec([p[1] for p in points])
            live[index] = 1
            raw = np.frombuffer(
                b"".join(k.to_bytes(32, "big") for k in scalars), np.uint8
            ).reshape(len(scalars), 32)
            bits[index] = np.unpackbits(
                raw, axis=1)[:, 256 - _msm.KZG_SCALAR_BITS:]
            qx, qy = _kzg_g2_side(tau_g2)
            _KZG_LANES.labels("real").inc(6 * n_real)
            _KZG_LANES.labels("padded").inc(lanes)
            _count_miller_plan(_msm.KZG_PAIR_LANES)
        tr = _obs.current_trace()

        def dispatch():
            # the stage programs' outputs feed each other on the device;
            # the G2 side goes up every dispatch because the pairing
            # program is built to consume (donate) its inputs
            attr = _obs_dev.begin((slots, rows), trace=tr)
            gx, gy, pair_mask, in_subgroup = _obs_dev.run_stage(
                attr, _obs_dev.KZG_LINCOMB_STAGE, lincomb,
                put_single(px), put_single(py), put_single(live),
                put_single(bits),
            )
            ok = _obs_dev.run_stage(
                attr, "pairing", pairing_stage,
                gx, gy, put_single(qx), put_single(qy), pair_mask,
            )
            return KzgHandle(verdict(ok, in_subgroup), n_real, trace=tr)

        return self.kzg_dispatcher.submit(dispatch, bucket=(slots, rows))


class KzgHandle:
    """In-flight KZG batch: `.result()` blocks on the device and reads it
    once: (ok, [(commitment in the subgroup, proof in it), ...] a blob).
    Carries its dispatch's pipeline Trace, as VerifyHandle does."""

    __slots__ = ("_packed", "_n", "_trace", "t_ready")

    def __init__(self, packed, n: int, trace=None):
        self._packed = packed
        self._n = n
        self._trace = trace
        self.t_ready = None

    def result(self) -> tuple:
        with _obs.span("jaxbls:device_wait", self._trace) as waited:
            out = np.asarray(self._packed)
        self.t_ready = waited.t1
        flags = out[1 : 1 + 2 * self._n].reshape(self._n, 2)
        return bool(out[0]), [(bool(c), bool(w)) for c, w in flags]


def _get_kzg_kernels():
    """(the jitted lane pass, the jitted verdict packer)."""
    import jax

    from . import msm as _msm

    _init_consts()
    if "kzg" not in _kernel_cache:
        from ...utils.jaxcfg import setup_compilation_cache

        setup_compilation_cache()
        _kernel_cache["kzg"] = (
            jax.jit(_msm.kzg_lincomb_kernel),
            jax.jit(_msm.kzg_verdict_kernel),
        )
    return _kernel_cache["kzg"]


_kzg_g2_cache: dict = {}


def _kzg_g2_side(tau_g2) -> tuple:
    """Host arrays (x, y), each (KZG_PAIR_LANES, 2, NL) Montgomery limbs, of
    the two-pair check's G2 side: (H, tau H, pad, pad). A constant of the
    trusted setup, so packed once a setup."""
    from . import msm as _msm

    key = (tuple(tau_g2[0]), tuple(tau_g2[1]))
    hit = _kzg_g2_cache.get(key)
    if hit is None:
        pad = [(0, 0)] * (_msm.KZG_PAIR_LANES - 2)
        hit = tuple(
            np.stack([tw._fq2_const_np(c) for c in
                      [pc.G2_GEN[k], tau_g2[k]] + pad])
            for k in (0, 1)
        )
        _kzg_g2_cache.clear()       # one setup a process is the rule
        _kzg_g2_cache[key] = hit
    return hit


def _get_msm_kernel():
    """(jitted varying-base MSM kernel, window width) at the currently
    resolved width (msm.msm_window: env > autotune plan > platform).
    Cached per WIDTH: the form is baked into the trace, and tests flip
    the env overrides within one process."""
    import functools

    import jax

    from . import msm as _msm

    _init_consts()
    w = _msm.msm_window()
    key = f"msm_w{w}"
    if key not in _kernel_cache:
        from ...utils.jaxcfg import setup_compilation_cache

        setup_compilation_cache()
        _kernel_cache[key] = jax.jit(
            functools.partial(_msm.varying_base_msm_kernel, window=w)
        )
    return _kernel_cache[key], w


def _aggregate_kernel(pk_x, pk_y, mask, sig_xy, h_jac):
    """Pair assembly for distinct-message AggregateVerify (h2c + pairing run
    as the shared stages)."""
    import jax.numpy as jnp

    pk_x = _to_mont_dev(pk_x)
    pk_y = _to_mont_dev(pk_y)
    sig_xy = _to_mont_dev(sig_xy)
    qx, qy, qinf = co.jac_to_affine(h_jac, co.FQ2_OPS)

    neg_g1x = _NEG_G1_GEN[0][None]
    neg_g1y = _NEG_G1_GEN[1][None]
    px = jnp.concatenate([pk_x, neg_g1x])
    py = jnp.concatenate([pk_y, neg_g1y])
    qxx = jnp.concatenate([qx, sig_xy[None, 0]])
    qyy = jnp.concatenate([qy, sig_xy[None, 1]])
    pair_mask = jnp.concatenate(
        [jnp.logical_and(jnp.asarray(mask, bool), jnp.logical_not(qinf)),
         jnp.asarray([True])]
    )
    return px, py, qxx, qyy, pair_mask


def _get_aggregate_kernel():
    import jax

    _get_stages()  # ensures constants + cache initialized
    if "agg" not in _kernel_cache:
        _kernel_cache["agg"] = jax.jit(_aggregate_kernel)
    return _kernel_cache["agg"]
