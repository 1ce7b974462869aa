"""The device tree-hash kernel: level-by-level SHA-256 ladders on jnp.

One compiled program per padding bucket (the jaxbls convention —
crypto/jaxbls/backend.py): the leaf count rounds up to a power of two
(`hash_bucket`, mesh-shape-keyed like `padding_bucket`), the whole ladder
of levels compiles as ONE staged jit whose input buffer is DONATED on
accelerators (each level's input is dead once its parents exist — XLA
reuses the HBM), and every dispatch rides the shared
`PipelinedDispatcher` so concurrent tree hashes double-buffer behind the
device exactly like BLS batches do.

Mesh layout (parallel/mesh.py): the leaf axis is sharded over the 1-D
`sets` axis — sibling pairs stay shard-local while the level width
exceeds the mesh, so the ladder stops at `width == mesh size` (each chip
has reduced its local subtree to one node) and the top log2(D) levels +
the virtual zero-hash depth finish on the host (~a handful of hashlib
calls). Small trees are pinned single-chip (`LIGHTHOUSE_TPU_HASH_MESH_MIN`
leaves, default 8192): below that, mesh padding and resharding would cost
more than the hash work.

Leaves upload in the HOST'S NATIVE byte order: a full-bucket C-contiguous
uint8 plane goes up as a zero-copy `view(np.uint32)` of the caller's own
memory (no packing, no temporary), anything else through one zero-filled
buffer, and the ladder's first operation swaps the words to SHA-256's
big-endian order on the device (`be_words`; skipped on a big-endian host).

The compression schedule itself is ssz/sha256_batch.compress — the ONE
definition shared with the numpy host lane, traced here over jnp uint32
lanes. Bit-exactness vs hashlib is pinned for both lanes in
tests/test_sha256_batch.py; ladder/level parity vs the host tree builder
in tests/test_jaxhash.py.
"""

from __future__ import annotations

import sys

import numpy as np

from ..ssz.core import next_pow2
from ..ssz.sha256_batch import (
    PAIR_PAD_WORDS,
    SHA256_H0,
    SHA256_K,
    bytes_from_words,
    pad_blocks,
    round_step,
    schedule_word,
    sha256_pairs,
    words_from_bytes,
)
from ..observability.trace import span
from ..utils.metrics import REGISTRY

# ------------------------------------------------------------------ metrics
# jaxhash_* series are labeled families (scripts/lint_metrics.py enforces
# it): the dispatch family answers "which lane is hashing", the timing
# family "which op cost what", bytes "what got uploaded"

JAXHASH_DISPATCH = REGISTRY.counter_vec(
    "jaxhash_dispatch_total",
    "device tree-hash dispatches by placement lane: `sharded` over the "
    "mesh, or `single_device` (small trees are pinned single-chip; a "
    "mesh-less node is always single_device)",
    ("lane",),
)
_DEVICE_SECONDS = REGISTRY.histogram_vec(
    "jaxhash_device_seconds",
    "wall time of one device hash dispatch (submit through resolve), by "
    "op (tree_levels = the merkle ladder, epoch_deltas = the vectorized "
    "epoch stage); first dispatch at a bucket includes XLA compilation",
    ("op",),
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0),
)
_MARSHALLED = REGISTRY.counter_vec(
    "jaxhash_marshalled_bytes_total",
    "bytes packed for device upload by the tree-hash engine, by array "
    "family",
    ("array",),
)
_LEAF_MARSHAL = REGISTRY.counter_vec(
    "jaxhash_leaf_marshal_total",
    "tree-hash leaf uploads by host path: `view` (a full-bucket contiguous "
    "uint8 plane uploaded as a zero-copy uint32 view of the caller's "
    "memory) or `copy` (ragged, strided or non-uint8 leaves copied once "
    "into a zero-filled bucket buffer)",
    ("path",),
)

#: smallest compile bucket (leaf axis) — below the router threshold the
#: host serves anyway, this only bounds the bucket count
MIN_LEAVES = 64

#: trees whose padded bucket is smaller than this stay single-chip even on
#: a meshed node: the mesh tax (padding to a mesh multiple + resharding)
#: exceeds the hash work of a small ladder. Env-overridable for harnesses.
DEFAULT_MESH_MIN_LEAVES = 8192

_kernel_cache: dict = {}
_dispatcher = None


def _get_dispatcher():
    """The engine's PipelinedDispatcher (lazy: pipeline resolves depth and
    donation from env/plan at construction)."""
    global _dispatcher
    if _dispatcher is None:
        from ..crypto.jaxbls.pipeline import PipelinedDispatcher

        _dispatcher = PipelinedDispatcher(workload="tree_hash")
    return _dispatcher


def mesh_min_leaves() -> int:
    import os

    raw = os.environ.get("LIGHTHOUSE_TPU_HASH_MESH_MIN", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass  # malformed env falls through to the default
    return DEFAULT_MESH_MIN_LEAVES


def _mesh_for(n_bucket: int):
    """The mesh a bucket of this width serves on: None below the
    single-chip pin threshold (and on mesh-less nodes)."""
    if n_bucket < mesh_min_leaves():
        return None
    from ..parallel import get_mesh

    return get_mesh()


def hash_bucket(n_leaves: int, mesh=None) -> int:
    """THE leaf-axis compile-bucket rounding rule: pow2 (the ladder is a
    halving tree), floored at MIN_LEAVES, rounded to a mesh multiple when
    a mesh serves the bucket (a pow2 >= the pow2 mesh size already is
    one, so this is a no-op for every realistic topology)."""
    n = max(MIN_LEAVES, next_pow2(max(1, n_leaves)))
    if mesh is None:
        return n
    from ..parallel import pad_sets

    return pad_sets(n, mesh=mesh)


def compress_rolled(state, w16, k):
    """The ROLLED device driver over the shared schedule_word/round_step
    bodies (ssz/sha256_batch.py): lax.fori_loop builds the 64-word
    message schedule, lax.scan runs the 64 rounds — per-level trace size
    drops ~20x vs the straight-line driver (a 10-level ladder's CPU
    compile fell from ~60 s to seconds), output bit-identical."""
    import jax
    import jax.numpy as jnp

    w = jnp.zeros((64,) + w16.shape[1:], jnp.uint32)
    w = jax.lax.dynamic_update_slice_in_dim(w, w16, 0, axis=0)

    def fill(t, w):
        return w.at[t].set(
            schedule_word(w[t - 16], w[t - 15], w[t - 7], w[t - 2])
        )

    w = jax.lax.fori_loop(16, 64, fill, w)

    def one_round(v, kw):
        kt, wt = kw
        return round_step(v, kt, wt), None

    v, _ = jax.lax.scan(one_round, tuple(state[i] for i in range(8)), (k, w))
    return jnp.stack(v) + state


def be_words(native):
    """uint32 words read from bytes in the host's native order -> the
    big-endian words SHA-256 is defined over (== words_from_bytes of the
    same bytes). Operators only, so numpy and traced jnp lanes both take
    it; the host's byte order is a fact of the process, decided here."""
    if sys.byteorder == "big":
        return native
    return (
        ((native & 0xFF) << 24)
        | ((native & 0xFF00) << 8)
        | ((native >> 8) & 0xFF00)
        | (native >> 24)
    )


def _native_words(leaves: np.ndarray, nb: int):
    """((nb, 8) uint32 words of `leaves` in native byte order, path): the
    caller's own memory as a `view` when it already is the whole bucket,
    else one zero-filled buffer that receives the leaves by a single
    slice assignment (`copy`). A view needs no writable flag."""
    if (
        leaves.shape == (nb, 32)
        and leaves.dtype == np.uint8
        and leaves.flags.c_contiguous
    ):
        return leaves.view(np.uint32), "view"
    words = np.zeros((nb, 8), np.uint32)
    words.view(np.uint8)[: leaves.shape[0]] = leaves
    return words, "copy"


def _make_ladder(n_bucket: int, stop: int, donate: bool, mesh):
    """Jitted level ladder for one bucket: (n_bucket, 8) uint32 leaf words
    IN NATIVE BYTE ORDER (swapped to big-endian here, fused into level
    0) -> tuple of level word arrays (n/2, 8) ... (stop, 8). Levels
    are unrolled in the trace (their shapes halve — static per level),
    the compression inside each is rolled; the whole ladder is one
    program per bucket and intermediates never leave the device."""
    import jax
    import jax.numpy as jnp

    from ..utils.jaxcfg import setup_compilation_cache

    setup_compilation_cache()
    k = jnp.asarray(np.array(SHA256_K, np.uint32))
    h0 = jnp.asarray(np.array(SHA256_H0, np.uint32))
    pad = jnp.asarray(np.array(PAIR_PAD_WORDS, np.uint32))
    n_levels = (n_bucket // stop).bit_length() - 1

    def hash_pairs(cur):
        m2 = cur.shape[0] // 2
        w16 = jnp.concatenate([cur[0::2], cur[1::2]], axis=1).T  # (16, m2)
        state = jnp.broadcast_to(h0[:, None], (8, m2))
        state = compress_rolled(state, w16, k)
        state = compress_rolled(
            state, jnp.broadcast_to(pad[:, None], (16, m2)), k
        )
        return state.T

    def ladder(words):
        out = []
        cur = be_words(words)
        for _ in range(n_levels):
            cur = hash_pairs(cur)
            out.append(cur)
        return tuple(out)

    kwargs = {}
    if donate:
        # the leaves buffer is dead once level 0 exists; levels reuse HBM
        kwargs["donate_argnums"] = (0,)
    if mesh is not None:
        from ..parallel import sets_sharding

        kwargs["in_shardings"] = (sets_sharding(mesh, 2),)
    return jax.jit(ladder, **kwargs)


def _get_ladder(n_bucket: int, mesh):
    """(jitted ladder, stop width) cached per (bucket, donation, mesh
    signature) — the jaxbls stage-cache convention: both decisions are
    baked into the jit, and harnesses flip them within one process."""
    from ..crypto.jaxbls.pipeline import donation_enabled

    donate = donation_enabled()[0]
    if mesh is None:
        stop, key = 1, f"ladder_{n_bucket}_d{int(donate)}"
    else:
        from ..parallel import mesh_shape_key
        from ..parallel.mesh import SET_AXIS

        stop = int(mesh.shape[SET_AXIS])
        if stop >= n_bucket:  # degenerate: nothing left to shard
            return _get_ladder(n_bucket, None)
        key = f"ladder_{n_bucket}_d{int(donate)}_{mesh_shape_key(mesh)}"
    if key not in _kernel_cache:
        _kernel_cache[key] = (_make_ladder(n_bucket, stop, donate, mesh), stop)
    return _kernel_cache[key]


class _LevelsHandle:
    """In-flight ladder dispatch: resolves to host word arrays. With
    `last_only` just the final device level transfers — the root-only
    path (ssz merkleize) must not pay ~2x the leaf bytes of device->host
    copies for levels it immediately discards. `first` skips the
    transfers below that level index (None placeholders keep positions);
    the final device level always materializes — the host tail hashes
    upward from it."""

    __slots__ = ("_levels", "_last_only", "_first")

    def __init__(self, levels, last_only=False, first=0):
        self._levels = levels
        self._last_only = last_only
        self._first = first

    def result(self):
        levels = self._levels
        if self._last_only:
            out = [np.asarray(levels[-1])]
        else:
            last = len(levels) - 1
            out = [
                np.asarray(lvl) if i >= self._first or i == last else None
                for i, lvl in enumerate(levels)
            ]
        self._levels = None  # drop device refs once materialized
        return out


def device_build_levels(leaves: np.ndarray, depth: int,
                        root_only: bool = False, min_level: int = 0):
    """(levels, root) for `leaves` ((n, 32) uint8, n >= 1) padded to
    2**depth — bit-identical to ssz/tree_cache._build: level d is the
    (ceil(n/2^(d+1)), 32) parent array, the list is `depth` long (virtual
    zero-hash levels included), the root is the top node. With
    `root_only=True` levels is None and only the top device level
    transfers to host (the merkleize root path). With `min_level` the
    device levels below that index skip the device->host transfer and
    come back as None (best-effort: host-tail levels above the mesh stop
    are computed regardless, they're a handful of tiny arrays) — the CoW
    spine build at 1M leaves drops ~32 MB of copies this way.

    The device computes the padded pow2 ladder (zero-chunk padding IS the
    SSZ zero-hash folding, so trimmed prefixes match the host builder
    exactly); the mesh-stop tail and the virtual depth finish on host.

    Leaves upload in native byte order and the ladder swaps them on the
    device: a C-contiguous uint8 plane that fills its bucket goes up as a
    uint32 view of the caller's memory (read-only is fine; nothing is
    written through it), anything else is copied once into a zero-filled
    bucket buffer. The call returns after the root is on the host, so the
    caller may reuse its leaves as soon as it has the result.
    Raises on device failure — the router owns the fallback."""
    import time

    from ..parallel import put_sets, put_single
    from ..ssz.core import ZERO_HASHES

    n_real = int(leaves.shape[0])
    nb = hash_bucket(n_real)
    mesh = _mesh_for(nb)
    if mesh is not None:
        nb = hash_bucket(n_real, mesh=mesh)
    real_depth = nb.bit_length() - 1
    if depth < real_depth:
        raise ValueError(
            f"virtual depth {depth} below padded bucket depth {real_depth}"
        )
    t0 = time.perf_counter()
    ladder, stop = _get_ladder(nb, mesh)
    with span("jaxhash:marshal"):
        words, path = _native_words(leaves, nb)
    _LEAF_MARSHAL.labels(path).inc()
    _MARSHALLED.labels("leaves").inc(words.nbytes)
    JAXHASH_DISPATCH.labels(
        "sharded" if mesh is not None else "single_device"
    ).inc()
    put = put_single if mesh is None else (lambda a: put_sets(a, mesh=mesh))
    with span("jaxhash:upload"):
        placed = put(words)
    with span("jaxhash:ladder"):
        ticket = _get_dispatcher().submit(
            lambda: _LevelsHandle(ladder(placed), last_only=root_only,
                                  first=min_level)
        )
    with span("jaxhash:readback"):
        dev_levels = ticket.result()

    import hashlib

    if root_only:
        full = bytes_from_words(dev_levels[0])  # the stop-width level
        while full.shape[0] > 1:
            full = sha256_pairs(full[0::2], full[1::2])
        node = full[0].tobytes()
        for d in range(real_depth, depth):
            node = hashlib.sha256(node + ZERO_HASHES[d]).digest()
        _DEVICE_SECONDS.labels("tree_levels").observe(
            time.perf_counter() - t0
        )
        return None, node

    levels = []
    cur_w = n_real
    full = None
    for lvl_words in dev_levels:  # widths nb/2 ... stop
        cur_w = (cur_w + 1) // 2
        if lvl_words is None:  # skipped transfer (below min_level)
            levels.append(None)
            continue
        full = bytes_from_words(lvl_words)
        levels.append(full[:cur_w].copy())
    # host tail: the remaining real levels below the mesh stop width ...
    while full.shape[0] > 1:
        full = sha256_pairs(full[0::2], full[1::2])
        cur_w = (cur_w + 1) // 2
        levels.append(full[:cur_w].copy())
    # ... and the virtual zero-hash depth (1-element levels, like _build)
    node = levels[-1][0].tobytes()
    for d in range(real_depth, depth):
        node = hashlib.sha256(node + ZERO_HASHES[d]).digest()
        levels.append(np.frombuffer(node, np.uint8).reshape(1, 32).copy())
    _DEVICE_SECONDS.labels("tree_levels").observe(time.perf_counter() - t0)
    root = levels[-1][0].tobytes() if depth else leaves[0].tobytes()
    return levels, root


def warm_tree_bucket(n_leaves: int) -> float:
    """Precompile the ladder for one leaf-count bucket (dummy zero leaves
    through the full dispatch path); returns the wall seconds. The
    autotune plan's tree-hash buckets warm through here at bring-up
    (router.start_warmup) so the first real state root at a planned shape
    skips the cold compile."""
    import time

    t0 = time.time()
    nb = hash_bucket(max(1, n_leaves))
    leaves = np.zeros((min(n_leaves, nb), 32), np.uint8)
    # root_only: the compiled program is identical, and warmup must not
    # pay ~2x the leaf bytes of device->host level transfers it discards
    device_build_levels(leaves, nb.bit_length() - 1, root_only=True)
    return time.time() - t0


# ---------------------------------------------------- device sha256 (tests)


def sha256_msgs_device(msgs: np.ndarray) -> np.ndarray:
    """Device-lane analog of ssz/sha256_batch.sha256_msgs: the SAME
    shared schedule traced over jnp, for the host/device hashlib-parity
    test matrix (multi-block messages included). Not a serving path —
    the serving kernels are the bucketed ladders above."""
    import jax
    import jax.numpy as jnp

    n, length = msgs.shape
    suffix = np.frombuffer(pad_blocks(length), np.uint8)
    padded = np.concatenate(
        [msgs, np.broadcast_to(suffix, (n, suffix.shape[0]))], axis=1
    )
    words = words_from_bytes(padded)  # (n, 16*blocks)
    key = f"msgs_{words.shape[1] // 16}blk"
    if key not in _kernel_cache:
        k = jnp.asarray(np.array(SHA256_K, np.uint32))
        h0 = jnp.asarray(np.array(SHA256_H0, np.uint32))
        blocks = words.shape[1] // 16

        def digest(w):
            state = jnp.broadcast_to(h0[:, None], (8, w.shape[0]))
            for blk in range(blocks):
                state = compress_rolled(
                    state, w[:, 16 * blk : 16 * blk + 16].T, k
                )
            return state.T

        _kernel_cache[key] = jax.jit(digest)
    out_words = np.asarray(_kernel_cache[key](words))
    return bytes_from_words(out_words)
