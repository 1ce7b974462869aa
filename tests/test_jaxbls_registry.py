"""The registry table on the device (crypto/jaxbls/registry.py), the indices
a SignatureSet carries, what feeds both, and the indexed path of the jax
backend's batch lane against the packed one and against the pure-Python
backend; and the key grids that lane lays (backend.key_grid_plan) as a pure
function, by index and packed. The first half compiles no stage program; the
second (from "keys by validator index" on) drives the staged backend on ONE
device at the (4, 4) bucket, the unsharded programs compiled once by a module
fixture: nine programs, stage 1's four forms among them, which are the
module's subject, with stage 2 served from the host (test_jaxbls_backend.py
is at its memory-mapping mark with its eight-device builds, whose mesh keeps
the one grid, so these tests have a file of their own; the packed two-grid
batch had a third from PR 45 to PR 47). The reference is the pure-Python
curve code on keys decompressed from their 48-byte form."""

import functools
import hashlib
import random
from types import SimpleNamespace

import numpy as np
import pytest

from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api as bls_api
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.bls381 import serde
from lighthouse_tpu.crypto.bls381.constants import R
from lighthouse_tpu.crypto.jaxbls import registry as reg
from lighthouse_tpu.crypto.jaxbls.backend import one_key_grid
from lighthouse_tpu.observability import trace as obstrace
from lighthouse_tpu.utils.metrics import REGISTRY

from jaxbls_warm import PREPARE_ZS, prepare_rest

rng = random.Random(0x7AB1E)
KEYS = [bls.SecretKey(rng.randrange(1, R)).public_key() for _ in range(16)]
BYTES = [pk.serialize() for pk in KEYS]
ONE_GRID = one_key_grid(4, 4)     # the (4, 4) bucket's keys as one grid


def _reference_points(key_bytes):
    """The registry's keys as the reference reads them: decompressed from
    their bytes by the pure-Python curve code, no cache, no limbs."""
    return [serde.g1_decompress(b, subgroup_check=True) for b in key_bytes]


def _reference_digest(points) -> str:
    return hashlib.sha256(b"".join(
        x.to_bytes(48, "little") + y.to_bytes(48, "little")
        for x, y in points)).hexdigest()


def _value(name, *labels):
    for m in REGISTRY.all_metrics():
        if m.name == name:
            return m.labels(*labels).value if labels else m.value
    raise KeyError(name)


def _state(n):
    return SimpleNamespace(
        validators=[SimpleNamespace(pubkey=b) for b in BYTES[:n]])


@pytest.fixture(autouse=True)
def _rows_by_eights(monkeypatch):
    """Tables of a few rows: the capacity rounds to 8 rows, not 65,536."""
    monkeypatch.setattr(reg, "ROW_CHUNK", 8)


@pytest.fixture()
def one_chip_backend(monkeypatch):
    """The jax backend with its batch lane on one device (the tests' eight
    virtual devices otherwise make it a mesh, which keeps to the packed
    grid), and no table left behind for the files that follow."""
    from lighthouse_tpu import parallel

    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "1")
    parallel.reset_mesh_cache()
    backend = bls_api.set_backend("jax")
    try:
        yield backend
    finally:
        backend.registry = None
        bls_api.set_backend("python")
        monkeypatch.undo()
        parallel.reset_mesh_cache()


# ------------------------------------------------------- the key grids
# backend.key_grid_plan: how a one-chip dispatch's keys lie, from the key
# counts of its sets. Pure; the cells' width lists are BENCHMARK.json's.

_ELECTRA = [1, 1, 32_093, 32_752, 32_400, 32_511, 32_601, 32_333, 32_700,
            32_204, 512]
_DENEB = [1, 1] + [128] * 128 + [512]
_AGGREGATES = [1] * 64 + [1] * 64 + [448 + i for i in range(64)]


@pytest.mark.parametrize("widths,bucket,wide,narrow,slots,lane_adds", [
    (_ELECTRA, (16, 32_768), (8, 32_768), (4, 512), 264_192, 294_912),
    # whatever the participation: the same grids, so the same program
    ([1, 1] + [16_385] * 8 + [512], (16, 32_768), (8, 32_768), (4, 512),
     264_192, 294_912),
    (_DENEB, (256, 512), (1, 512), (256, 128), 33_280, 41_472),
    (_AGGREGATES, (256, 512), (64, 512), (128, 1), 32_896, 40_960),
    ([128] * 64, (64, 128), (64, 128), None, 8_192, 16_384),     # gossip
    ([128], (4, 128), (4, 128), None, 512, 3_584),               # urgent
    ([1] * 7, (8, 1), (8, 1), None, 8, 0),                       # m = 1
    # a dispatch of single-key attestations at the program's own width:
    # all-ones widths keep the one grid (n, 1), and tree_sum_plan takes
    # m = 1 with nothing to add (subnet_flood_1key)
    ([1] * 1024, (1024, 1), (1024, 1), None, 1024, 0),
    ([3, 4, 4, 3], (4, 4), (4, 4), None, 16, 12),                # uniform
    # the 3/4 rule, both sides of its edge in the (4, 4) bucket of 16
    ([2, 2, 2, 4], (4, 4), (1, 4), (4, 2), 12, 7),               # 12 of 16
    ([1, 4, 4, 4], (4, 4), (4, 4), None, 16, 12),                # 17 > 12
    ([1, 1, 4, 3], (4, 4), (2, 4), (2, 1), 10, 6),
    # the cheapest t of several that split: t = 4 (32), not t = 1 (34)
    ([1, 1, 3, 16], (4, 16), (1, 16), (4, 4), 32, 76),
], ids=["electra", "electra_thin", "deneb", "aggregates", "gossip", "urgent",
        "one_key_sets", "subnet_1024", "uniform", "edge_at", "edge_over", "block_by_index",
        "cheapest"])
def test_key_grid_plan(widths, bucket, wide, narrow, slots, lane_adds):
    from lighthouse_tpu.crypto.jaxbls import backend as be

    n, m = bucket
    assert be.padding_bucket(len(widths), max(widths), single_chip=True) == bucket
    grids, where = be.key_grid_plan(widths, n, m)
    assert grids == ((wide,) if narrow is None else (wide, narrow))
    assert sum(rows * width for rows, width in grids) == slots
    assert sum(be.co.tree_sum_plan(width, rows)[3]
               for rows, width in grids) == lane_adds
    if narrow is None:
        assert where is None and wide == bucket
        assert be.key_grid_plan(widths, n, m) == be.one_key_grid(n, m)
        assert be._grid_rows(be.one_key_grid(n, m), len(widths)) == [
            (0, i) for i in range(len(widths))]
        return
    # every set has one row of a grid wide enough for it, rows are used
    # once and in the sets' order; padded set slots read the identity,
    # the entry behind both grids' sums (n_w + n_n may exceed n: Deneb)
    assert where.dtype == np.int32 and where.shape == (n,)
    placed = be._grid_rows((grids, where), len(widths))
    assert len(set(placed)) == len(widths)
    for g in (0, 1):
        rows = [row for grid, row in placed if grid == g]
        assert rows == list(range(len(rows))) and len(rows) <= grids[g][0]
    assert all(w <= grids[g][1] for w, (g, _) in zip(widths, placed))
    assert narrow[1] < wide[1] == m
    assert (where[len(widths):] == wide[0] + narrow[0]).all()
    assert 4 * slots <= 3 * n * m


def test_rows_equal_the_reference_after_build_append_and_growth():
    table = reg.PubkeyTable()
    ref = _reference_points(BYTES)
    assert len(table) == 0 and table.digest() == _reference_digest([])
    # build (grows 0 -> 16 rows), append in place, append past the capacity
    for upto, capacity in ((5, 16), (7, 16), (16, 24)):
        table.append(KEYS[len(table):upto])
        assert (len(table), table.capacity) == (upto, capacity)
        assert table.rows(range(upto)) == ref[:upto]
        assert table.digest() == _reference_digest(ref[:upto])
        assert table.digest() != _reference_digest(ref[:upto - 1])
        assert table.spare_nonzero() == 0
        assert table.x.shape == table.y.shape == (capacity, 24)
        assert _value("jaxbls_registry_rows") == upto
        assert _value("jaxbls_registry_bytes") == capacity * 2 * 24 * 4
    # a spare row that is not zero is seen
    import jax.numpy as jnp

    table.x = table.x.at[20, 3].set(jnp.uint32(9))
    assert table.spare_nonzero() == 1


def test_default_capacity_is_the_registry_rounded_up_with_room(monkeypatch):
    monkeypatch.setattr(reg, "ROW_CHUNK", 65_536)     # the module's own
    table = reg.PubkeyTable()
    assert table._capacity_for(1_048_576) == 1_114_112
    assert table._capacity_for(1_048_577) == 1_048_576 + 2 * 65_536
    assert 1_114_112 * 2 * 24 * 4 == 213_909_504


def test_append_is_a_span_with_rows_and_bytes():
    table = reg.PubkeyTable()
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        table.append(KEYS[:3])          # grows: the whole mirror goes up
        table.append(KEYS[3:6])         # 3 rows padded to 4, in place
    finally:
        obstrace.set_current_trace(None)
    spans = [s for s in tr.spans if s[0] == "jaxbls:registry.append"]
    assert [s[3] for s in spans] == [
        {"rows": 3, "bytes": 16 * 2 * 24 * 4},
        {"rows": 3, "bytes": 4 * 2 * 24 * 4}]


@pytest.mark.parametrize("bad", [8, 9, -1, 2**31])
def test_an_index_outside_the_table_is_refused_and_counted(bad):
    table = reg.PubkeyTable()
    table.append(KEYS[:8])
    sig = bls.Signature(bls_api.hash_to_g2_point(b"\x01" * 32))
    ok = bls.SignatureSet(sig, KEYS[:3], b"\x01" * 32, signing_indices=[0, 1, 7])
    idx, mask = table.index_grid([ok], ONE_GRID, 8)
    assert idx.dtype == np.int32 and idx.tolist()[0] == [0, 1, 7, 0]
    assert mask.tolist()[0] == [1, 1, 1, 0] and not mask[1:].any()
    before = _value("jaxbls_registry_refused_total")
    named = bls.SignatureSet(sig, KEYS[:2], b"\x01" * 32,
                             signing_indices=[2, bad])
    assert table.index_grid([ok, named], ONE_GRID, 8) is None
    assert _value("jaxbls_registry_refused_total") == before + 1
    # the capacity is not the limit: a spare row is refused like any other
    assert table.capacity == 16


def test_a_refused_batch_is_false_before_any_program_runs(one_chip_backend):
    backend = one_chip_backend
    cache = ValidatorPubkeyCache(table=backend.install_registry())
    cache.import_new_pubkeys(_state(8))
    sig = bls.Signature(bls_api.hash_to_g2_point(b"\x02" * 32))
    past = bls.SignatureSet(sig, [cache.get(0), KEYS[8]], b"\x02" * 32,
                            signing_indices=[0, 8],
                            signing_registry=cache.table)
    refused = _value("jaxbls_registry_refused_total")
    taken = {s: _value("jaxbls_registry_keys_total", s)
             for s in ("table", "packed")}
    assert backend.verify_signature_sets([past], [3]) is False
    assert _value("jaxbls_registry_refused_total") == refused + 1
    assert taken == {s: _value("jaxbls_registry_keys_total", s)
                     for s in ("table", "packed")}      # no key went anywhere


def test_the_path_is_chosen_by_the_data(one_chip_backend):
    """`_marshal_indices`: None (the batch packs its keys) with no table,
    with one set lacking indices, or with a set whose indices are rows of
    another table or of none; the grid on the device otherwise."""
    backend = one_chip_backend
    sig = bls.Signature(bls_api.hash_to_g2_point(b"\x03" * 32))
    assert backend.registry is None

    def named(cache, rows=(1, 5, 2)):
        return bls.SignatureSet(sig, [cache.get(i) for i in rows],
                                b"\x03" * 32, signing_indices=rows,
                                signing_registry=cache.table)

    elsewhere = ValidatorPubkeyCache(table=reg.PubkeyTable())
    elsewhere.import_new_pubkeys(_state(8))
    assert backend._marshal_indices([named(elsewhere)], ONE_GRID, 3) is None
    table = backend.install_registry()
    assert backend.registry is table and len(table) == 0
    cache = ValidatorPubkeyCache(table=table)
    cache.import_new_pubkeys(_state(8))
    mine = named(cache)
    bare = bls.SignatureSet(sig, [cache.get(3)], b"\x03" * 32)
    # the same validators, the very same key objects, another chain's rows
    theirs = named(elsewhere)
    assert theirs.signing_keys == mine.signing_keys
    unowned = bls.SignatureSet(sig, mine.signing_keys, b"\x03" * 32,
                               signing_indices=[1, 5, 2])
    for other in (bare, theirs, unowned):
        assert backend._marshal_indices([mine, other], ONE_GRID, 6) is None
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        tx, ty, idx, mask = backend._marshal_indices([mine, mine], ONE_GRID, 6)
    finally:
        obstrace.set_current_trace(None)
    assert tx is table.x and ty is table.y
    assert np.asarray(idx).tolist() == [[1, 5, 2, 0]] * 2 + [[0] * 4] * 2
    assert np.asarray(mask).sum() == 6
    (span,) = [s for s in tr.spans if s[0] == "jaxbls:marshal.indices"]
    assert span[3] == {"keys": 6, "bytes": 2 * 4 * 4 * 4}


def test_the_table_goes_with_the_cache_that_feeds_it(one_chip_backend):
    """The backend holds its table weakly: the pubkey cache (the chain)
    keeps it, and when that goes the next chain finds the place free."""
    import gc

    backend = one_chip_backend
    cache = ValidatorPubkeyCache(table=backend.install_registry())
    cache.import_new_pubkeys(_state(3))
    assert backend.registry is cache.table and len(backend.registry) == 3
    del cache
    gc.collect()
    assert backend.registry is None


def test_signing_indices_are_one_a_key_read_only_and_outside_equality():
    sig = bls.Signature(bls_api.hash_to_g2_point(b"\x04" * 32))
    plain = bls.SignatureSet(sig, KEYS[:2], b"\x04" * 32)
    assert plain.signing_indices is None
    s = bls.SignatureSet(sig, KEYS[:2], b"\x04" * 32, signing_indices=(7, 3))
    assert s.signing_indices.dtype == np.int64
    assert s.signing_indices.tolist() == [7, 3]          # the keys' order
    with pytest.raises(ValueError):
        s.signing_indices[0] = 1
    with pytest.raises(ValueError):
        bls.SignatureSet(sig, KEYS[:2], b"\x04" * 32, signing_indices=[7])
    # whose rows they are: any object, read by identity; none without rows
    assert s.signing_registry is None
    rows_of = object()
    owned = bls.SignatureSet(sig, KEYS[:2], b"\x04" * 32,
                             signing_indices=(7, 3), signing_registry=rows_of)
    assert owned.signing_registry is rows_of and owned == s
    with pytest.raises(ValueError):
        bls.SignatureSet(sig, KEYS[:2], b"\x04" * 32, signing_registry=rows_of)
    assert s == plain and hash(s) == hash(plain)         # the record's keys
    # the other backends read the keys alone
    bls_api.set_backend("fake")
    try:
        assert bls.verify_signature_sets([s])
    finally:
        bls_api.set_backend("python")


def test_import_new_pubkeys_feeds_the_table_before_it_returns():
    table = reg.PubkeyTable()
    cache = ValidatorPubkeyCache(table=table)
    cache.import_new_pubkeys(_state(5))
    assert len(table) == len(cache) == 5
    cache.import_new_pubkeys(_state(5))                 # nothing new
    cache.import_new_pubkeys(_state(9))
    ref = _reference_points(BYTES[:9])
    assert table.rows(range(9)) == ref
    assert table.digest() == _reference_digest(ref)
    get_pubkey = cache.pubkey_getter()
    assert get_pubkey.index_by_bytes(BYTES[8]) == 8
    assert get_pubkey.index_by_bytes(BYTES[9]) is None
    assert get_pubkey.registry is table
    assert not hasattr(cache, "mont_coords")            # the old device feed
    # a cache without a table is the cache of before
    plain = ValidatorPubkeyCache()
    plain.import_new_pubkeys(_state(3))
    assert plain.table is None and len(plain) == 3
    assert plain.pubkey_getter().registry is None


def test_the_first_chain_of_a_process_feeds_the_backends_table(monkeypatch):
    """`BeaconChain` installs a table on a backend that offers one and
    hands it to its pubkey cache; a second chain (another registry) gets
    none. A backend without the offer (python, fake, hybrid) is as before."""
    from lighthouse_tpu.chain.beacon_chain import BeaconChain
    from lighthouse_tpu.testing.harness import StateHarness, clone_state
    from lighthouse_tpu.types.spec import minimal_spec

    class Offering:
        registry = None

        def install_registry(self):
            self.registry = reg.PubkeyTable()
            return self.registry

    bls.set_backend("fake")
    try:
        spec = minimal_spec()
        hs = StateHarness.new(spec, 16)
        plain = BeaconChain(spec, clone_state(hs.state, spec))
        assert plain.pubkey_cache.table is None
        backend = Offering()
        monkeypatch.setattr(bls, "get_backend", lambda: backend)
        first = BeaconChain(spec, clone_state(hs.state, spec))
        second = BeaconChain(spec, clone_state(hs.state, spec))
    finally:
        bls.set_backend("python")
    table = first.pubkey_cache.table
    assert table is backend.registry and len(table) == 16
    assert table.rows(range(16)) == [
        first.pubkey_cache.get(i).point for i in range(16)]
    assert second.pubkey_cache.table is None and len(second.pubkey_cache) == 16


def test_an_electra_block_fills_the_batch_with_indices(monkeypatch):
    """`per_block_processing(VERIFY_BULK)` on an Electra block at the
    minimal preset: proposal and RANDAO name the proposer, every attestation
    set carries `get_attesting_indices_electra`'s indices in order beside
    the keys of those validators, the sync aggregate its signers'."""
    from lighthouse_tpu.state_transition import accessors as acc
    from lighthouse_tpu.state_transition import block as blk
    from lighthouse_tpu.state_transition.slot import (
        process_slots, state_transition, types_for_slot)
    from lighthouse_tpu.testing.harness import StateHarness, clone_state
    from lighthouse_tpu.types.spec import ForkName, minimal_spec

    bls.set_backend("fake")
    try:
        spec = minimal_spec(electra_fork_epoch=0)
        hs = StateHarness.new(spec, 64)
        (head,) = hs.extend_chain(1)
        slot = hs.state.slot + 1
        types = types_for_slot(spec, slot)
        atts = hs.build_attestations(
            clone_state(hs.state, spec), hs.state.slot,
            types.BeaconBlock.hash_tree_root(head.message))
        signed, _ = hs.produce_block(slot, attestations=atts)
        assert spec.fork_name_at_slot(slot) == ForkName.electra
        assert len(signed.message.body.attestations) >= 1

        cache = ValidatorPubkeyCache(table=reg.PubkeyTable())
        cache.import_new_pubkeys(hs.state)
        seen = []
        monkeypatch.setattr(blk.SignatureBatch, "verify",
                            lambda self: seen.append(list(self.sets)) or True)
        state = clone_state(hs.state, spec)
        state_transition(state, signed, spec,
                         get_pubkey=cache.pubkey_getter())
    finally:
        bls.set_backend("python")
    (sets,) = seen
    body = signed.message.body
    proposer = signed.message.proposer_index
    assert len(sets) == 2 + len(body.attestations) + 1
    for s in sets:
        assert s.signing_indices is not None
        assert s.signing_registry is cache.table
        assert [cache.get(i) for i in s.signing_indices.tolist()] == list(
            s.signing_keys)
    assert sets[0].signing_indices.tolist() == [proposer]
    assert sets[1].signing_indices.tolist() == [proposer]
    pre = clone_state(hs.state, spec)
    process_slots(pre, spec, slot)
    for att, s in zip(body.attestations, sets[2:]):
        want = acc.get_attesting_indices_electra(pre, spec, att)
        assert s.signing_indices.tolist() == want and len(want) > 1
    signers = [cache.get_index(bytes(pk)) for pk, bit in zip(
        pre.current_sync_committee.pubkeys,
        body.sync_aggregate.sync_committee_bits) if bit]
    assert sets[-1].signing_indices.tolist() == signers and signers
    # without the cache's resolver the sync set comes without indices, and
    # the batch as a whole then packs its keys
    seen.clear()
    bls.set_backend("fake")
    try:
        state_transition(clone_state(hs.state, spec), signed, spec)
    finally:
        bls.set_backend("python")
    assert seen[0][-1].signing_indices is None
    assert seen[0][0].signing_indices.tolist() == [proposer]
    assert all(s.signing_registry is None for s in seen[0])


def test_start_up_warms_the_indexed_prepare_where_a_table_is_fed(
        one_chip_backend, monkeypatch):
    """`autotune.runtime.start_warmup` on one chip: the packed stages per
    bucket as before, then — the chain's table holding keys — the indexed
    prepare per bucket against that table; without keys, or under a mesh,
    the packed stages alone."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu import parallel
    from lighthouse_tpu.autotune import runtime

    backend = one_chip_backend
    calls = []
    monkeypatch.setattr(be, "warm_stages",
                        lambda n, m, **kw: calls.append(("packed", n, m)))
    monkeypatch.setattr(be, "warm_prepare_indexed",
                        lambda n, m, table: calls.append(("indexed", n, m, table)))
    buckets = ((64, 128), (4, 128))

    def warm():
        calls.clear()
        runtime.start_warmup(buckets=buckets).join(timeout=30)
        return list(calls)

    packed = [("packed", n, m) for n, m in buckets]
    assert warm() == packed                               # no table
    cache = ValidatorPubkeyCache(table=backend.install_registry())
    assert warm() == packed                               # no key in it yet
    cache.import_new_pubkeys(_state(3))
    assert warm() == packed + [("indexed", n, m, cache.table)
                               for n, m in buckets]
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "8")
    parallel.reset_mesh_cache()
    assert [c[0] for c in warm()].count("indexed") == 0   # a mesh packs


def _benchmark_reference():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference",
        "bls_registry_spec.py")
    spec = importlib.util.spec_from_file_location("bls_registry_spec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("what", ["imports", "keys", "hash_to_g2",
                                  "pairing", "verdicts"])
def test_the_benchmarks_reference_is_its_own_and_agrees(what):
    """benchmarks/reference/bls_registry_spec.py, the plain reference that
    decides `correct` in `block_import_electra`: a BLS12-381 of its own
    (no module of this program) that agrees with the pure-Python backend on
    seeded keys, messages and sets."""
    ref = _benchmark_reference()
    if what == "imports":
        import ast

        tree = ast.parse(open(ref.__file__).read())
        imported = {n.module if isinstance(n, ast.ImportFrom) else a.name
                    for n in ast.walk(tree)
                    if isinstance(n, (ast.Import, ast.ImportFrom))
                    for a in n.names}
        assert imported == {"__future__", "hashlib"}
    elif what == "keys":
        for pk, b in zip(KEYS, BYTES):
            assert ref.decompress_key(b) == pk.point
            assert ref.compress_key(pk.point) == b
        points = [pk.point for pk in KEYS]
        assert ref.keys_not_of(BYTES, points) == 0
        x, y = points[3]
        points[3] = (x, ref.P - y)
        points[5] = cv.g1_add(points[5], points[5])
        assert ref.keys_not_of(BYTES, points) == 2
        total = None
        for pk in KEYS:
            total = cv.g1_add(total, pk.point)
        assert ref.sum_keys([pk.point for pk in KEYS]) == total
        off = next(x for x in range(1, 50) if ref.fp_sqrt(x ** 3 + 4) is None)
        with pytest.raises(ValueError):
            ref.decompress_key((off | 4 << 381).to_bytes(48, "big"))
    elif what == "hash_to_g2":
        for msg in (b"", b"\x00" * 32, bytes(range(32)), b"abc"):
            assert ref.hash_to_g2(msg) == bls_api.hash_to_g2_point(msg)
    elif what == "pairing":
        a, b = rng.randrange(1, R), rng.randrange(1, R)
        g2 = ref.hash_to_g2(b"a point of order r")
        pa, qb = ref.g1_mul(ref.G1, a), ref.g2_mul(g2, b)
        assert pa == cv.g1_mul(cv.G1_GEN, a) and qb == cv.g2_mul(g2, b)
        assert ref.pairing_product_is_one(
            [(pa, qb), (ref.g1_neg(ref.g1_mul(ref.G1, a * b % R)), g2)])
        assert not ref.pairing_product_is_one(
            [(pa, qb), (ref.g1_neg(ref.g1_mul(ref.G1, (a * b + 1) % R)), g2)])
    else:
        sks = [rng.randrange(1, R) for _ in range(6)]
        keys = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
        m1, m2 = b"\xA1" * 32, b"\xA2" * 32

        def signed(who, msg):
            return cv.g2_mul(bls_api.hash_to_g2_point(msg),
                             sum(sks[i] for i in who) % R)

        good = [(signed((0, 1, 2, 3), m1), (0, 1, 2, 3), m1),
                (signed((4,), m2), (4,), m2)]
        swapped = [(good[1][0], good[0][1], m1), good[1]]
        replaced = [(good[0][0], (0, 1, 5, 3), m1), good[1]]
        bls_api.set_backend("python")
        for block, want in ((good, True), (swapped, False),
                            (replaced, False)):
            assert ref.verify_signature_sets(
                [(sig, [keys[i].serialize() for i in who], msg)
                 for sig, who, msg in block], [2**63 + 11, 7]) is want
            assert bls.verify_signature_sets([bls.SignatureSet(
                bls.Signature(sig), [keys[i] for i in who], msg)
                for sig, who, msg in block]) is want


# ------------------------------------------------ keys by validator index
# A block of four sets (proposal and RANDAO of one key, an attestation of
# four, a sync aggregate of three) whose sets carry validator indices, on
# the batch lane of ONE device (the mesh taken away: under a mesh the
# packed grid stays), against a 12-validator registry whose keys the
# chain's pubkey cache has put into the table on the device; bucket (4, 4).
# The reference is the pure-Python backend on the sets' own keys.

_TABLE_ROWS = 24      # 12 validators, headroom 8: the table's capacity


#: the key grids the block by index, widths (1, 1, 4, 3), lays in the
#: (4, 4) bucket (backend.key_grid_plan)
_BLOCK_GRIDS = ((2, 4), (2, 1))


@functools.lru_cache(maxsize=None)
def _host_map_to_g2(lane: bytes):
    """What `hash_to_g2_jacobian` does to one lane, by the pure-Python curve
    code: the lane's two standard-form u-values (the bytes of its (2, 2, NL)
    limbs) through SSWU, the isogeny and the cofactor, an affine point."""
    from lighthouse_tpu.crypto.bls381 import hash_to_curve as ph2c
    from lighthouse_tpu.crypto.jaxbls import limbs as lb

    u0, u1 = (tuple(lb.unpack(c) for c in u)
              for u in np.frombuffer(lane, np.uint32).reshape(2, 2, lb.NL))
    return cv.g2_clear_cofactor(cv.g2_add(
        ph2c.iso_map(ph2c.sswu(u0)), ph2c.iso_map(ph2c.sswu(u1))))


def _host_hash_to_g2(us):
    """Stage 2 from the host, a stand-in of the kind test_mesh.py and
    test_graft_entry.py use for stage 4: this module's subject is stage 1's
    four forms, and hash-to-G2 at four lanes, the dearest program of a
    build, is compiled and held to this same reference in
    test_jaxbls_backend.py's urgent lane and test_jaxbls_h2c.py."""
    from lighthouse_tpu.crypto.jaxbls import curve_ops as co

    return co.g2_batch_to_device(
        [_host_map_to_g2(lane.tobytes()) for lane in np.asarray(us)])


@pytest.fixture(scope="module")
def _one_device_programs():
    """The nine programs the tests below dispatch, compiled side by side in
    four threads: the unsharded stages at 4 sets with the packed prepare at
    m = 4 — stages 1, 3 and the two of 4; stage 2 is `_host_hash_to_g2`
    from here to the end of the module —; the indexed prepares at (4, 4)
    over the 24-row table, over one grid and over the block's two; the
    packed two-grid prepare over the block's grids and over `_PAIR_GRIDS`;
    and the indexed prepare at (4, 1), single-key sets (no key axis to
    sum: the smallest of them)."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from jaxbls_warm import run_in_threads, warm_build, warm_one_chip_prepares

    stages = be._get_stages(mesh=None)
    key = next(k for k, v in be._kernel_cache.items() if v is stages)
    be._kernel_cache[key] = (
        stages[0], _host_hash_to_g2, stages[2], stages[3])
    warm = functools.partial(warm_one_chip_prepares, table_rows=_TABLE_ROWS)
    try:
        run_in_threads(
            functools.partial(warm_build, 4, (4,), None),
            functools.partial(warm, ("prepare_indexed", ((4, 4),)),
                              ("prepare_indexed_grids", _BLOCK_GRIDS)),
            functools.partial(warm, ("prepare_grids", _BLOCK_GRIDS),
                              ("prepare_indexed", ((4, 1),))),
            functools.partial(warm, ("prepare_grids", _PAIR_GRIDS)))
        yield
    finally:
        be._kernel_cache[key] = stages


@pytest.mark.parametrize("msg", [b"\xE0" * 32, b"", b"lighthouse-tpu"],
                         ids=["block_root", "empty", "text"])
def test_the_host_stand_in_of_stage_2_is_the_reference_hash_to_g2(msg):
    """`_host_hash_to_g2` on the marshal's u-values of a message is
    `hash_to_g2` of that message (RFC 9380's J.10.1 vector pins it in
    test_bls381_core.py), in the limbs stage 3 reads, beside a padded
    lane's zeros."""
    from lighthouse_tpu.crypto.bls381.constants import DST_POP
    from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2, tower as tw

    us = np.zeros((2, 2, 2, 24), np.uint32)
    us[0] = h2.hash_to_field_batch([msg], DST_POP)[0]
    jac = _host_hash_to_g2(us)
    assert [a.shape for a in jac] == [(2, 2, 24)] * 3
    x, y, z = (tw.fq2_from_device(c[0]) for c in jac)
    assert (x, y) == bls_api.hash_to_g2_point(msg) and z == (1, 0)


@pytest.fixture()
def registry_chain(monkeypatch, _one_device_programs):
    """(backend, cache, secret keys): the jax backend with a table fed by a
    ValidatorPubkeyCache of 12 seeded validators."""
    from types import SimpleNamespace

    from lighthouse_tpu import parallel
    from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
    rng = random.Random(0x2E6)
    sks = [rng.randrange(1, R) for _ in range(14)]
    pks = [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks]
    state = SimpleNamespace(validators=[
        SimpleNamespace(pubkey=pk.serialize()) for pk in pks[:12]])
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "1")
    parallel.reset_mesh_cache()
    backend = bls_api.set_backend("jax")
    cache = ValidatorPubkeyCache(table=backend.install_registry())
    cache.import_new_pubkeys(state)
    assert backend.registry.capacity == _TABLE_ROWS
    cache.state, cache.all_keys = state, pks      # for the append test
    try:
        yield backend, cache, sks
    finally:
        backend.registry = None
        bls_api.set_backend("python")
        monkeypatch.undo()
        parallel.reset_mesh_cache()


def _set_by_index(cache, sks, indices, msg, signers=None):
    """The set the builders make: the cache's key objects and their
    indices; signed by `signers` (the same validators by default)."""
    h = bls_api.hash_to_g2_point(msg)
    agg = sum(sks[i] for i in (indices if signers is None else signers)) % R
    return bls.SignatureSet(
        bls.Signature(cv.g2_mul(h, agg)), [cache.get(i) for i in indices],
        msg, signing_indices=indices, signing_registry=cache.table)


_BLOCK_BY_INDEX = ((5,), (5,), (0, 3, 7, 11), (2, 9, 4))


def _block_by_index(cache, sks, damage=None):
    sets = [_set_by_index(cache, sks, list(ix), bytes([0xE0 + i]) * 32)
            for i, ix in enumerate(_BLOCK_BY_INDEX)]
    att = sets[2]
    if damage == "swapped_signature":
        sets[2] = bls.SignatureSet(sets[3].signature, att.signing_keys,
                                   att.message, signing_indices=[0, 3, 7, 11],
                                   signing_registry=cache.table)
    elif damage == "dropped_signer":
        # the signature is all four's, the set names three of them
        sets[2] = _set_by_index(cache, sks, [0, 3, 11], att.message,
                                signers=[0, 3, 7, 11])
    elif damage == "replaced_signer":
        # validator 7 replaced by validator 8, who did not sign
        sets[2] = _set_by_index(cache, sks, [0, 3, 8, 11], att.message,
                                signers=[0, 3, 7, 11])
    return sets


def _keys_taken():
    import lighthouse_tpu.crypto.jaxbls.backend as be

    return {s: be._REGISTRY_KEYS.labels(s).value for s in ("table", "packed")}


def test_indexed_prepare_is_bit_equal_to_the_packed_prepare(registry_chain):
    """`_stage_prepare_indexed` over the table and `_stage_prepare` over
    the packed grid of the same keys: z_pk, sig_acc and bad_aggpk equal
    limb for limb (the arithmetic exists once; a masked slot is the
    identity whatever row it gathered)."""
    import numpy as np

    import lighthouse_tpu.crypto.jaxbls.backend as be

    backend, cache, sks = registry_chain
    sets = _block_by_index(cache, sks)
    idx, mask = backend.registry.index_grid(sets, ONE_GRID, 12)
    pk_x, pk_y, pk_mask = backend._marshal_pubkeys(sets, ONE_GRID,
                                                   single_chip=True)
    assert np.array_equal(np.asarray(pk_mask), mask)
    rest = prepare_rest(sets)
    table = backend.registry
    got = be._get_one_chip_variant("prepare_indexed")(table.x, table.y, idx, mask, *rest)
    want = be._get_stages(mesh=None)[0](pk_x, pk_y, pk_mask, *rest)
    import jax

    flat_got = jax.tree_util.tree_leaves(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 7
    for a, b in zip(flat_got, flat_want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not bool(np.asarray(got[2]))


@pytest.mark.parametrize("damage", [
    None, "swapped_signature", "dropped_signer", "replaced_signer"],
    ids=lambda d: d or "valid")
def test_block_by_index_through_signature_batch_parity(registry_chain,
                                                       damage):
    """`SignatureBatch.verify()` -> `bls.verify_signature_sets` -> the batch
    lane takes the indexed path, no other entry: every key of the dispatch
    comes from the table, the marshal's key part is `jaxbls:marshal.indices`
    (no grid is packed), the bucket's slots are counted as the packed path
    counts them — the slots the dispatch LAYS: this block's widths (1, 1,
    4, 3) lie as a wide grid of 2 x 4 and a narrow one of 2 x 1, 10 slots
    where the (4, 4) bucket that still names the dispatch has 16 — and the
    verdict is the pure-Python backend's."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu.observability import trace as obstrace
    from lighthouse_tpu.state_transition.block import SignatureBatch

    backend, cache, sks = registry_chain
    batch = SignatureBatch()
    batch.add(_block_by_index(cache, sks, damage))
    # the start-up warm-up compiles the one-grid program, which a batch of
    # near-equal widths runs; this block runs the two-grid one, compiled at
    # its first dispatch (here: by the module's fixture)
    one_grid = be._get_one_chip_variant("prepare_indexed")
    be.warm_prepare_indexed(3, 4, backend.registry)       # rounds to (4, 4)
    program = be._get_one_chip_variant("prepare_indexed_grids")
    compiled = program._cache_size(), one_grid._cache_size()
    adds = be._TREE_SUM_LANE_ADDS.labels("done")
    adds0 = adds.value
    slots = {k: be._BUCKET_SLOTS.labels("keys", k) for k in ("real", "padded")}
    before = {k: c.value for k, c in slots.items()}
    taken = _keys_taken()
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        on_jax = batch.verify()
    finally:
        obstrace.set_current_trace(None)
    n_keys = sum(len(s.signing_keys) for s in batch.sets)
    assert (program._cache_size(), one_grid._cache_size()) == compiled
    assert _keys_taken() == {"table": taken["table"] + n_keys,
                             "packed": taken["packed"]}
    assert be.key_grid_plan([len(s.signing_keys) for s in batch.sets],
                            4, 4)[0] == _BLOCK_GRIDS
    assert {k: c.value - before[k] for k, c in slots.items()} == {
        "real": n_keys, "padded": 2 * 4 + 2 * 1}
    # tree_sum_plan(4, 2) + tree_sum_plan(1, 2): 3 adds on 2 lanes, none
    assert adds.value - adds0 == 6
    spans = {s[0]: s for s in tr.spans}
    assert "jaxbls:marshal.pubkeys" not in spans
    assert "jaxbls:marshal.pubkeys_upload" not in spans
    assert spans["jaxbls:marshal.indices"][4] == "jaxbls:marshal"
    assert spans["jaxbls:marshal.indices"][3] == {
        "keys": n_keys, "bytes": 2 * (2 * 4 + 2 * 1) * 4}
    assert spans["jaxbls:prepare"][4] == "jaxbls:enqueue"   # stage 1's name
    # the bucket is still the dispatch's name, the grids beside it
    assert tr.meta["bucket"] == "4x4" and tr.meta["real_keys"] == n_keys
    assert tr.meta["key_grids"] == "2x4+2x1"
    assert (4, 4) in be._seen_exec_buckets
    bls_api.set_backend("python")
    on_python = batch.verify()
    assert on_python is (damage is None)
    assert on_jax is on_python


_SUBNET = ((3, b"\xA1" * 32), (9, b"\xA1" * 32), (0, b"\xA1" * 32),
           (7, b"\xB2" * 32))


@pytest.mark.parametrize("damage", [
    None, "swapped_signature", "flipped_message", "replaced_signer"],
    ids=lambda d: d or "valid")
def test_single_key_sets_with_shared_messages_by_index(registry_chain,
                                                       damage):
    """Four unaggregated attestations as `prepare_unaggregated_attestations`
    builds them - ONE attesting index a set, three of them on one shared
    message - through `bls.verify_signature_sets` on the batch lane: the
    bucket is (4, 1), its keys ONE grid (all-ones widths never split), every
    key gathered from the table, the Miller plan counted with its lines an
    accumulator, and the verdict the pure-Python backend's - valid, with
    one set's signature swapped for another's, with one byte of one set's
    (shared) message flipped, with one set's signer exchanged for another
    validator."""
    import lighthouse_tpu.crypto.jaxbls.backend as be
    from lighthouse_tpu.crypto.jaxbls import pairing_ops as po

    backend, cache, sks = registry_chain
    sets = [_set_by_index(cache, sks, [i], msg) for i, msg in _SUBNET]
    victim = sets[1]
    if damage == "swapped_signature":
        sets[1] = bls.SignatureSet(sets[0].signature, victim.signing_keys,
                                   victim.message, signing_indices=[9],
                                   signing_registry=cache.table)
    elif damage == "flipped_message":
        msg = bytes([victim.message[0] ^ 1]) + victim.message[1:]
        sets[1] = bls.SignatureSet(victim.signature, victim.signing_keys,
                                   msg, signing_indices=[9],
                                   signing_registry=cache.table)
    elif damage == "replaced_signer":
        sets[1] = _set_by_index(cache, sks, [4], victim.message, signers=[9])
    assert be.key_grid_plan([1] * 4, 4, 1) == be.one_key_grid(4, 1)
    bls_api.set_backend("python")
    want = bls.verify_signature_sets(sets)
    assert want is (damage is None)
    bls_api.set_backend("jax")
    before = _keys_taken()
    plan0 = {k: be._MILLER_PLAN.labels(k).value
             for k in ("dispatches", "lines_per_accumulator")}
    tr = obstrace.Trace("test", 1)
    obstrace.set_current_trace(tr)
    try:
        got = bls.verify_signature_sets(sets)
    finally:
        obstrace.set_current_trace(None)
    assert got is want
    after = _keys_taken()
    assert after["table"] - before["table"] == 4
    assert after["packed"] == before["packed"]
    assert tr.meta["bucket"] == "4x1" and tr.meta["key_grids"] == "4x1"
    assert tr.meta["real_keys"] == 4
    assert tr.meta["distinct_messages"] == (3 if damage == "flipped_message"
                                            else 2)
    names = [s[0] for s in tr.spans]
    assert "jaxbls:marshal.indices" in names
    assert "jaxbls:marshal.pubkeys" not in names
    assert (4, 1) in be._seen_exec_buckets
    w = po.miller_lane_plan(5)[0]
    assert be._MILLER_PLAN.labels("dispatches").value == plan0[
        "dispatches"] + 1
    assert (be._MILLER_PLAN.labels("lines_per_accumulator").value
            - plan0["lines_per_accumulator"]
            == po._lines_per_accumulator(5, w))


def test_a_set_without_indices_sends_the_batch_down_the_packed_path(
        registry_chain):
    backend, cache, sks = registry_chain
    sets = _block_by_index(cache, sks, "replaced_signer")
    sets[1] = bls.SignatureSet(sets[1].signature, sets[1].signing_keys,
                               sets[1].message)              # no indices
    taken = _keys_taken()
    n_keys = sum(len(s.signing_keys) for s in sets)
    assert bls.verify_signature_sets(sets) is False
    assert _keys_taken() == {"table": taken["table"],
                             "packed": taken["packed"] + n_keys}


def test_an_appended_key_verifies_in_the_next_dispatch(registry_chain):
    """Validator 12 before `import_new_pubkeys` brought it: the row past
    `len` is refused and counted, not read as a spare (zero) row. After:
    the next dispatch gathers it and verifies."""
    from types import SimpleNamespace

    from lighthouse_tpu.crypto.jaxbls import registry as reg

    backend, cache, sks = registry_chain
    msg = b"\xEE" * 32
    h = bls_api.hash_to_g2_point(msg)
    sig = bls.Signature(cv.g2_mul(h, (sks[12] + sks[1]) % R))
    early = bls.SignatureSet(sig, [cache.all_keys[12], cache.get(1)], msg,
                             signing_indices=[12, 1],
                             signing_registry=cache.table)
    block = _block_by_index(cache, sks)
    refused = reg.REFUSED.value
    assert bls.verify_signature_sets(block[:3] + [early]) is False
    assert reg.REFUSED.value == refused + 1
    cache.state.validators += [SimpleNamespace(pubkey=pk.serialize())
                               for pk in cache.all_keys[12:]]
    cache.import_new_pubkeys(cache.state)
    table = backend.registry
    assert len(table) == 14 and table.capacity == _TABLE_ROWS
    assert table.spare_nonzero() == 0
    late = bls.SignatureSet(sig, [cache.get(12), cache.get(1)], msg,
                            signing_indices=[12, 1],
                            signing_registry=cache.table)
    taken = _keys_taken()
    assert bls.verify_signature_sets(block[:3] + [late]) is True
    assert _keys_taken()["table"] == taken["table"] + 8
    assert reg.REFUSED.value == refused + 1


def test_another_chains_sets_pack_their_keys_whatever_their_indices(
        registry_chain):
    """A second chain in the process: no table of its own, a LONGER
    registry of other keys. Its sets name its own rows — some past the
    first chain's `len`, some inside it — and none is this table's
    business: the batch packs the sets' keys, nothing is refused, and the
    verdict is the keys' own (a set past the first chain's `len` among
    them: under the table it would have been refused, or read a spare row)."""
    from types import SimpleNamespace

    from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
    from lighthouse_tpu.crypto.jaxbls import registry as reg

    backend, cache, first_sks = registry_chain
    rng = random.Random(0x0DD)
    sks = [rng.randrange(1, R) for _ in range(15)]
    second = ValidatorPubkeyCache()
    second.import_new_pubkeys(SimpleNamespace(validators=[
        SimpleNamespace(pubkey=bls.PublicKey(
            cv.g1_mul(cv.G1_GEN, sk)).serialize()) for sk in sks]))
    assert len(second) == 15 > len(backend.registry) == 12
    assert second.table is None
    sets = [_set_by_index(second, sks, list(ix), bytes([0xD0 + i]) * 32)
            for i, ix in enumerate(((13,), (14, 0), (2, 9, 4), (1, 12, 3)))]
    assert all(s.signing_registry is None for s in sets)
    # beside the first chain's own sets: one foreign set and the batch packs
    # (widths 1, 1, 3, 3: the block's two grids, packed)
    mixed = _block_by_index(cache, first_sks)[:2] + [sets[2], sets[3]]
    assert [s.signing_registry for s in mixed] == [cache.table] * 2 + [None] * 2
    refused, taken = reg.REFUSED.value, _keys_taken()
    assert bls.verify_signature_sets(mixed) is True
    assert reg.REFUSED.value == refused
    assert _keys_taken() == {"table": taken["table"],
                             "packed": taken["packed"] + 8}
    assert backend._marshal_indices(sets, ONE_GRID, 9) is None


def test_indexed_grids_prepare_is_bit_equal_to_the_packed_grids_prepare(
        registry_chain):
    """Over the block's two grids as over the one: the rows gathered from
    the table and the keys packed on the host give stage 1's outputs limb
    for limb, and `index_grid` lays indices where `_marshal_pubkeys` lays
    keys."""
    import jax

    import lighthouse_tpu.crypto.jaxbls.backend as be

    backend, cache, sks = registry_chain
    sets = _block_by_index(cache, sks)
    plan = be.key_grid_plan([len(s.signing_keys) for s in sets], 4, 4)
    assert plan[0] == _BLOCK_GRIDS and plan[1].tolist() == [2, 3, 0, 1]
    table = backend.registry
    wide_idx, wide_mask, narrow_idx, narrow_mask = table.index_grid(
        sets, plan, 12)
    assert wide_idx.tolist() == [[0, 3, 7, 11], [2, 9, 4, 0]]
    assert narrow_idx.tolist() == [[5], [5]]
    packed = backend._marshal_pubkeys(sets, plan, single_chip=True)
    assert np.array_equal(np.asarray(packed[2]), wide_mask)
    assert np.array_equal(np.asarray(packed[5]), narrow_mask)
    assert np.asarray(packed[6]).tolist() == plan[1].tolist()
    rest = prepare_rest(sets)
    got = be._get_one_chip_variant("prepare_indexed_grids")(
        table.x, table.y, wide_idx, wide_mask, narrow_idx, narrow_mask,
        plan[1], *rest)
    want = be._get_one_chip_variant("prepare_grids")(*packed, *rest)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not bool(np.asarray(got[2]))


# ----------------------------------------------- two key grids, packed
# (test_jaxbls_key_grids.py from PR 45 to PR 47: back here since the module's
# stage 2 is the host's, which left the mapping mark room for the ninth
# program.) A batch of unequal widths lies as a wide and a narrow grid and
# stage 1 sums each by the one tree_sum. Widths (1, 2, 2, 4) in the (4, 4)
# bucket, wide 1 x 4 and narrow 4 x 2 (`_PAIR_GRIDS`: 12 slots of 16, the
# edge of the 3/4 rule), against the one grid, the pure-Python backend and,
# for the aggregate keys, the pure-Python curve.

_PAIR_GRIDS = ((1, 4), (4, 2))


@pytest.fixture()
def two_grid_backend(_one_device_programs, one_chip_backend):
    """`one_chip_backend` behind the module's compiled programs."""
    return one_chip_backend


def _set_of(sks, msg, valid=True):
    """A set of the keys of `sks` signed by all of them; where they sum to
    zero the signature is some point that is not the identity (no
    signature verifies against the identity key)."""
    agg = (sum(sks) + (0 if valid else 1)) % R or 7
    return bls.SignatureSet(
        bls.Signature(cv.g2_mul(bls_api.hash_to_g2_point(msg), agg)),
        [bls.PublicKey(cv.g1_mul(cv.G1_GEN, sk)) for sk in sks], msg)


def _pair_batch(case):
    """Four sets of 1, 2, 2 and 4 keys; `case` damages one."""
    rng = random.Random(0x261D)
    sks = [[rng.randrange(1, R) for _ in range(w)] for w in (1, 2, 2, 4)]
    if case in ("identity_narrow", "identities"):
        sks[1][1] = R - sks[1][0]
    if case in ("identity_wide", "identities"):
        sks[3][1], sks[3][3] = R - sks[3][0], R - sks[3][2]
    return [_set_of(ks, bytes([0xC0 + i]) * 32, valid=(case, i) != ("tampered", 2))
            for i, ks in enumerate(sks)]


@pytest.mark.parametrize("case", [
    "valid", "tampered", "identity_narrow", "identity_wide"])
def test_a_mixed_batch_on_two_grids_gives_the_reference_verdict(
        two_grid_backend, case):
    """`bls.verify_signature_sets` on a packed batch of unequal widths:
    the dispatch lays two grids (its trace says which, its bucket is
    still (4, 4)), counts the slots it lays and the lane-additions of
    both sums, and its verdict is the pure-Python backend's — True when
    sound, False with one bad signature, False with a set whose keys sum
    to the identity in the narrow grid or in the wide one."""
    import lighthouse_tpu.crypto.jaxbls.backend as be

    sets = _pair_batch(case)
    assert be.key_grid_plan([1, 2, 2, 4], 4, 4)[0] == _PAIR_GRIDS
    program = be._get_one_chip_variant("prepare_grids")
    compiled = program._cache_size()
    padded = be._BUCKET_SLOTS.labels("keys", "padded")
    adds = be._TREE_SUM_LANE_ADDS.labels("done")
    padded0, adds0, taken = padded.value, adds.value, _keys_taken()
    tr = obstrace.Trace("gossip_block", 1)
    obstrace.set_current_trace(tr)
    try:
        on_jax = bls.verify_signature_sets(sets)
    finally:
        obstrace.set_current_trace(None)
    assert program._cache_size() == compiled
    assert tr.meta["bucket"] == "4x4" and tr.meta["key_grids"] == "1x4+4x2"
    assert padded.value - padded0 == 1 * 4 + 4 * 2
    # tree_sum_plan(4, 1) + tree_sum_plan(2, 4): 3 adds, and 1 on 4 lanes
    assert adds.value - adds0 == 3 + 4
    assert _keys_taken() == {"table": taken["table"],
                             "packed": taken["packed"] + 9}
    bls_api.set_backend("python")
    assert bls.verify_signature_sets(sets) is (case == "valid")
    assert on_jax is (case == "valid")


def _affine_points(jac):
    """[(x, y) or None] of a batch of Jacobian G1 points in Montgomery
    limbs, by Python integers."""
    from lighthouse_tpu.crypto.bls381.constants import P
    from lighthouse_tpu.crypto.jaxbls import tower as tw

    out = []
    for x, y, z in zip(*(tw.fq_batch_from_device(c) for c in jac)):
        zi = pow(z, -1, P) if z else 0
        out.append((x * zi * zi % P, y * zi * zi * zi % P) if z else None)
    return out


@pytest.mark.parametrize("case,n_real", [("identities", 4), ("valid", 3)],
                         ids=["identities", "padded_slot"])
def test_two_grids_sum_to_the_one_grids_aggregate_keys(two_grid_backend,
                                                       case, n_real):
    """The two-grid prepare against the one-grid prepare on the same sets:
    every z_i * aggpk_i the same AFFINE point (the sums associate
    differently, so the Jacobian limbs differ) and the pure-Python
    curve's, the signatures' sum limb for limb, `bad_aggpk` alike — set
    where a real set's keys sum to the identity, in the narrow grid and in
    the wide one. A padded set slot reads the identity entry behind the
    grids' sums, as the one grid's all-masked row sums to it (three sets,
    the wide grid left empty by a hand-laid `where`)."""
    import lighthouse_tpu.crypto.jaxbls.backend as be

    backend = two_grid_backend
    sets = _pair_batch(case)[:n_real]
    plan = be.key_grid_plan([1, 2, 2, 4], 4, 4)
    if n_real == 3:
        plan = (plan[0], np.array([1, 2, 3, 5], np.int32))
    assert plan[0] == _PAIR_GRIDS and plan[1].tolist()[:3] == [1, 2, 3]
    rest = prepare_rest(sets, n_real)
    grids = backend._marshal_pubkeys(sets, plan, single_chip=True)
    one = backend._marshal_pubkeys(sets, ONE_GRID, single_chip=True)
    assert [g.shape for g in grids] == [
        (1, 4, 24), (1, 4, 24), (1, 4), (4, 2, 24), (4, 2, 24), (4, 2), (4,)]
    assert sum(int(np.asarray(m).sum()) for m in (grids[2], grids[5])) == (
        int(np.asarray(one[2]).sum())) == sum(len(s.signing_keys) for s in sets)
    got = be._get_one_chip_variant("prepare_grids")(*grids, *rest)
    want = be._get_stages(mesh=None)[0](*one, *rest)

    def aggregate(s, k):
        total = None
        for pk in s.signing_keys:
            total = cv.g1_add(total, pk.point)
        return cv.g1_mul(total, k)

    reference = [aggregate(s, k) for s, k in zip(sets, PREPARE_ZS)] + [None] * (4 - n_real)
    assert _affine_points(got[0]) == _affine_points(want[0]) == reference
    assert reference.count(None) == {"valid": 1, "identities": 2}[case]
    for a, b in zip(got[1], want[1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert bool(np.asarray(got[2])) is bool(np.asarray(want[2])) is (
        case == "identities")


def test_two_grid_programs_stay_under_the_mapping_mark(two_grid_backend):
    """As in the other modules that drive the staged backend: with this
    module's nine programs compiled and kept, the process must be under
    conftest's mark."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK


def test_module_stays_under_the_mapping_mark(registry_chain):
    """Last on purpose, as in the other modules that drive the staged
    backend: with this module's eight programs compiled and kept (one
    unsharded build and four more prepares), the process must be under
    conftest's mark — past it conftest drops the executables between tests
    and each later test recompiles for minutes."""
    from conftest import _MAP_COUNT_HIGH_MARK, _n_memory_mappings

    assert _n_memory_mappings() < _MAP_COUNT_HIGH_MARK
