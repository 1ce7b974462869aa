"""The validator registry's public keys as a table that lives on the device.

A block's signing keys are registry rows: the sets a block brings name
validators by index, and the registry only ever grows. So instead of
packing n x m Python integers into a limb grid for every new grouping and
uploading it (`JaxBackend._marshal_pubkeys`: ~0.24 s of Python and a 100 MB
grid for an Electra block's ~260,000 keys), the backend keeps ONE pair of
device arrays

    x, y : uint32[capacity, NL]   standard-form limbs, as `pack_ints_vec`
                                  makes them; row i = validator i's
                                  decompressed key, rows >= `len` zero

and a dispatch uploads an int32 index grid and its mask for each key grid
it lays (`backend.key_grid_plan`: one (n, m) grid, or a wide and a narrow
one); stage 1 gathers the rows (`backend._stage_prepare_indexed`,
`_stage_prepare_indexed_grids`).

Fed by `chain/pubkey_cache.py` `ValidatorPubkeyCache`: every key the cache
takes in is appended here before `import_new_pubkeys` returns. A host
mirror of the limbs is packed once a key, never per dispatch; an append
uploads only the new rows. Capacity is the registry rounded up to a whole
`ROW_CHUNK` with one more chunk of room for deposits (1,048,576 validators:
1,114,112 rows, 213.9 MB); a registry that outgrows it is placed anew.

What the table promises, and who holds it to that:

  * a row is visible to every dispatch submitted after `append` returned
    (`snapshot` and `append` share one lock; a dispatch marshals against
    the arrays and the `len` it took together);
  * an index outside [0, len) is refused on the host and counted
    (`index_grid` returns None, `jaxbls_registry_refused_total`): never
    clamped, wrapped, or gathered from a spare row;
  * the table is no authority of its own: its rows are the registry's
    keys, and `digest` / `rows` / `spare_nonzero` are what a check reads
    to compare them with keys decompressed from the registry's bytes.

One chip: the arrays are placed whole on the first device, and the backend
takes the indexed path only where it dispatches without a mesh. One
registry: a set says whose rows its indices are (`SignatureSet.
signing_registry`, the table object itself), and only a set of THIS table
is gathered from it or refused by it; the backend holds the table weakly,
so it lives and goes with the pubkey cache that feeds it.
"""

from __future__ import annotations

import hashlib
import threading

import numpy as np

from ...observability import trace as _obs
from ...utils.metrics import REGISTRY
from . import limbs as lb
from .backend import _grid_rows, _next_pow2, pack_ints_vec

#: rows the capacity is rounded to, and the room kept above the registry
ROW_CHUNK = 65_536

_ROWS = REGISTRY.gauge(
    "jaxbls_registry_rows",
    "validator keys the device's registry table holds (its `len`)",
)
_BYTES = REGISTRY.gauge(
    "jaxbls_registry_bytes",
    "bytes of the registry table on the device: capacity x 2 coordinates "
    "x NL limbs x 4",
)
REFUSED = REGISTRY.counter(
    "jaxbls_registry_refused_total",
    "validator indices a dispatch named that the registry table does not "
    "hold (negative, or >= its len): the batch is refused on the host",
)


def _write_rows(table, rows, start):
    import jax

    return jax.lax.dynamic_update_slice(table, rows, (start, 0))


class PubkeyTable:
    """The registry's keys on the device, append-only."""

    def __init__(self):
        self._lock = threading.Lock()
        self._write = None           # the jitted row writer, on first use
        self._len = 0                # rows that hold a key
        self._hx = np.zeros((0, lb.NL), np.uint32)   # the host mirror
        self._hy = np.zeros((0, lb.NL), np.uint32)
        self.x = self.y = None       # the device arrays, once a key is in

    def __len__(self) -> int:
        return self._len

    @property
    def capacity(self) -> int:
        return self._hx.shape[0]

    @property
    def nbytes(self) -> int:
        return self._hx.nbytes + self._hy.nbytes

    @staticmethod
    def _capacity_for(rows: int) -> int:
        return -(-rows // ROW_CHUNK) * ROW_CHUNK + ROW_CHUNK

    def append(self, keys) -> None:
        """Rows len .. len + k - 1 become `keys` (PublicKey objects, in
        registry order). Packs the new keys once, uploads the new rows
        alone (padded to a power of two with the zero rows that follow
        them), or the whole mirror when the capacity had to grow."""
        keys = list(keys)
        if not keys:
            return
        import jax

        with self._lock, _obs.span("jaxbls:registry.append",
                                   rows=len(keys)) as sp:
            start, k = self._len, len(keys)
            k_up = _next_pow2(k)
            grown = start + k_up > self.capacity
            if grown:
                cap = self._capacity_for(start + k)
                hx = np.zeros((cap, lb.NL), np.uint32)
                hy = np.zeros((cap, lb.NL), np.uint32)
                hx[:start], hy[:start] = self._hx[:start], self._hy[:start]
                self._hx, self._hy = hx, hy
            self._hx[start:start + k] = pack_ints_vec([pk.point[0] for pk in keys])
            self._hy[start:start + k] = pack_ints_vec([pk.point[1] for pk in keys])
            if grown:
                x, y = jax.device_put(self._hx), jax.device_put(self._hy)
                sp.args["bytes"] = self.nbytes
            else:
                if self._write is None:
                    self._write = jax.jit(_write_rows)
                at = np.int32(start)
                x = self._write(self.x, self._hx[start:start + k_up], at)
                y = self._write(self.y, self._hy[start:start + k_up], at)
                sp.args["bytes"] = 2 * k_up * lb.NL * 4
            # on the device before any dispatch can name the rows
            jax.block_until_ready((x, y))
            self.x, self.y = x, y
            self._len = start + k
            _ROWS.set(self._len)
            _BYTES.set(self.nbytes)

    def snapshot(self) -> tuple:
        """(x, y, len) as one dispatch sees them."""
        with self._lock:
            return self.x, self.y, self._len

    # ------------------------------------------------------ the marshal

    def index_grid(self, sets, plan, rows: int):
        """Per grid of `plan` (backend.key_grid_plan; `one_key_grid(n, m)`
        for the (n, m) grid) idx int32[rows, width] and mask uint32[rows,
        width] of the sets' signing indices against a table of `rows`
        rows, as one flat tuple in stage 1's order — or None, refused and
        counted, when one of them names a row outside [0, rows)."""
        grids = [(np.zeros(g, np.int32), np.zeros(g, np.uint32))
                 for g in plan[0]]
        for (g, row), s in zip(_grid_rows(plan, len(sets)), sets):
            ind = s.signing_indices
            if int(ind.min()) < 0 or int(ind.max()) >= rows:
                REFUSED.inc(int(np.count_nonzero((ind < 0) | (ind >= rows))))
                return None
            idx, mask = grids[g]
            idx[row, :len(ind)] = ind
            mask[row, :len(ind)] = 1
        return tuple(a for grid in grids for a in grid)

    # ------------------------------------------------------- the checks

    def rows(self, indices) -> list:
        """[(x, y), ...] as integers, read back from the DEVICE."""
        import jax.numpy as jnp

        at = jnp.asarray(np.asarray(indices, np.int32))
        xs = np.asarray(jnp.take(self.x, at, axis=0))
        ys = np.asarray(jnp.take(self.y, at, axis=0))
        return list(zip(lb.unpack_batch(xs), lb.unpack_batch(ys)))

    def digest(self) -> str:
        """SHA-256 over rows 0 .. len - 1 as the DEVICE holds them, each
        row x then y as 48-byte little-endian integers: what
        `b"".join(x.to_bytes(48, "little") + y.to_bytes(48, "little"))`
        over the registry's keys hashes to. A limb over 16 bits, which no
        such integer has, changes the digest."""
        x, y, rows = self.snapshot()
        h = hashlib.sha256()
        if rows:
            both = np.stack([np.asarray(x)[:rows], np.asarray(y)[:rows]],
                            axis=1)
            if int(both.max()) >> lb.LB:
                h.update(b"limb over 16 bits")
            h.update(both.astype("<u2").tobytes())
        return h.hexdigest()

    def spare_nonzero(self) -> int:
        """Nonzero limbs in rows len .. capacity - 1 on the DEVICE."""
        import jax.numpy as jnp

        x, y, rows = self.snapshot()
        if x is None:
            return 0
        return int(jnp.count_nonzero(x[rows:]) + jnp.count_nonzero(y[rows:]))
