"""ValidatorPubkeyCache — index -> decompressed pubkey, store-backed.

Parity surface: /root/reference/beacon_node/beacon_chain/src/
validator_pubkey_cache.rs:17-146. This cache is THE feed for batch
verification: signature-set constructors resolve indices through it
(`pubkey_getter`), and the sets they build carry the decompressed keys,
their validator indices and this cache's table. What feeds the device: given a `table`
(crypto/jaxbls/registry.py `PubkeyTable`, the jax backend's
`install_registry()`), every key the cache takes in is appended to it, row
= validator index, before `import_new_pubkeys` returns, so a dispatch
submitted after that gathers the key on the device by its index. Without a
table the backend packs the keys' coordinates per dispatch, as before."""

from __future__ import annotations

from ..crypto import bls
from ..store.kv import Column, KeyValueOp


class ValidatorPubkeyCache:
    def __init__(self, store=None, table=None):
        self.store = store
        self.table = table
        self.pubkeys: list[bls.PublicKey] = []
        self.pubkey_bytes: list[bytes] = []
        self.index_by_bytes: dict[bytes, int] = {}
        if store is not None:
            self._load()

    def _load(self):
        items = sorted(self.store.hot.iter_column(Column.pubkey_cache))
        for key, value in items:
            index = int.from_bytes(key, "little")
            assert index == len(self.pubkeys), "pubkey cache gap"
            pk = bls.PublicKey.deserialize(value)
            self._push(pk, value)
        self._feed_table(0)

    def _push(self, pk: bls.PublicKey, pk_bytes: bytes):
        self.index_by_bytes[bytes(pk_bytes)] = len(self.pubkeys)
        self.pubkeys.append(pk)
        self.pubkey_bytes.append(bytes(pk_bytes))

    def _feed_table(self, first: int) -> None:
        """Rows `first`.. of the device's table, from the keys just pushed."""
        if self.table is not None:
            self.table.append(self.pubkeys[first:])

    def import_new_pubkeys(self, state) -> None:
        """Add any validators beyond the cache length (import_new_pubkeys
        analog; called on state advance/import)."""
        if len(state.validators) <= len(self.pubkeys):
            return
        ops = []
        first = len(self.pubkeys)
        for i in range(first, len(state.validators)):
            pkb = bytes(state.validators[i].pubkey)
            pk = bls.PublicKey.deserialize(pkb)
            self._push(pk, pkb)
            if self.store is not None:
                ops.append(
                    KeyValueOp.put(Column.pubkey_cache, i.to_bytes(8, "little"), pkb)
                )
        if ops:
            self.store.hot.do_atomically(ops)
        self._feed_table(first)

    def get(self, index: int) -> bls.PublicKey:
        return self.pubkeys[index]

    def get_index(self, pubkey_bytes: bytes) -> int | None:
        return self.index_by_bytes.get(bytes(pubkey_bytes))

    def __len__(self):
        return len(self.pubkeys)

    def pubkey_getter(self):
        """A get_pubkey callable for signature_sets with by-bytes support."""

        def get_pubkey(index: int) -> bls.PublicKey:
            return self.pubkeys[index]

        def by_bytes(pkb: bytes) -> bls.PublicKey:
            idx = self.index_by_bytes.get(bytes(pkb))
            if idx is not None:
                return self.pubkeys[idx]
            return bls.PublicKey.deserialize(bytes(pkb))

        get_pubkey.by_bytes = by_bytes
        get_pubkey.index_by_bytes = self.get_index
        # whose rows the indices are: travels with the sets built from here
        get_pubkey.registry = self.table
        return get_pubkey
