"""Device tree-hash engine (lighthouse_tpu/jaxhash): ladder/level parity
vs the host builder, the hybrid router's reasons and breaker, the
vectorized epoch stage's bit-exactness vs the pure-Python spec path, and
the state_root workload surfaces (loadtest scenario, bench matrix rows).

Everything runs on CPU jax (the engine is bit-exactly provable against
hashlib without TPU access — the point of the subsystem); ladder buckets
are kept small so each distinct compile stays in the seconds range."""

import json
import subprocess
import sys

import numpy as np
import pytest

import lighthouse_tpu.ssz.tree_cache as tc
from lighthouse_tpu.jaxhash import engine, router
from lighthouse_tpu.jaxhash import epoch_vectors as ev
from lighthouse_tpu.jaxhash.router import (
    ROUTER,
    TreeHashRouter,
    hash_backend,
    set_hash_backend,
)


@pytest.fixture(autouse=True)
def _host_backend_default(monkeypatch):
    """Every test starts (and ends) on the host default with env seams
    clear; tests opt into device routing explicitly."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_HASH_BACKEND", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_HASH_MIN_LEAVES", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_HASH_MESH_MIN", raising=False)
    set_hash_backend(None)
    yield
    set_hash_backend(None)


def _rand_leaves(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, 32), dtype=np.uint8)


# ----------------------------------------------------------------- engine


@pytest.mark.parametrize("n,depth", [(100, 12), (257, 40)])
def test_device_levels_match_host_builder(n, depth):
    """Level arrays AND root bit-identical to tree_cache._build —
    including non-pow2 leaf counts (odd-tail zero-hash folding) and deep
    virtual depth."""
    from lighthouse_tpu.observability.trace import STAGE_SECONDS

    # the engine's four phases are spans; with no trace current they feed
    # the `direct` series at once (the benchmark's tree cell reads two)
    phases = [STAGE_SECONDS.labels(f"jaxhash:{p}", "direct")
              for p in ("marshal", "upload", "ladder", "readback")]
    seen = [c.n for c in phases]
    leaves = _rand_leaves(n, seed=n)
    lv_d, root_d = engine.device_build_levels(leaves, depth)
    assert [c.n for c in phases] == [k + 1 for k in seen]
    lv_h, root_h = tc._build(leaves, depth)
    assert root_d == root_h
    assert len(lv_d) == len(lv_h) == depth
    for a, b in zip(lv_d, lv_h):
        assert a.shape == b.shape
        assert np.array_equal(a, b)


def _aligned_leaves(n, seed):
    """A plane whose first byte sits on a 64-byte boundary: the CPU
    backend's device_put aliases such memory instead of copying it."""
    raw = np.zeros(n * 32 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    plane = raw[off:off + n * 32].reshape(n, 32)
    plane[:] = _rand_leaves(n, seed)
    return plane


def _readonly_leaves(n, seed):
    return np.frombuffer(_rand_leaves(n, seed).tobytes(), np.uint8).reshape(n, 32)


#: kind -> (maker of the caller's array, the host path it must take); all
#: land in the 2**10 bucket so two ladders (donation off/on) serve every case
_LEAF_KINDS = {
    "full": (lambda: _rand_leaves(1024, 1), "view"),
    "aligned": (lambda: _aligned_leaves(1024, 2), "view"),
    "readonly": (lambda: _readonly_leaves(1024, 3), "view"),
    "ragged": (lambda: _rand_leaves(700, 4), "copy"),
    "strided": (lambda: _rand_leaves(2048, 5)[::2], "copy"),
    "int64": (lambda: _rand_leaves(1024, 6).astype(np.int64), "copy"),
}


@pytest.mark.parametrize("donate", ["0", "1"], ids=["keep", "donate"])
@pytest.mark.parametrize("root_only", [False, True], ids=["levels", "root"])
@pytest.mark.parametrize("kind", list(_LEAF_KINDS))
def test_device_build_levels_leaf_paths(monkeypatch, kind, root_only, donate):
    """Every kind of caller array gives the host builder's levels and
    root, takes the expected host path exactly once, counts the bucket's
    bytes, and is byte-identical afterwards — with the ladder's input
    donated or not (a viewed plane is the CALLER'S memory)."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_DONATE", donate)
    make, want_path = _LEAF_KINDS[kind]
    leaves = make()
    keep = leaves.tobytes()
    assert leaves.flags.writeable == (kind != "readonly")
    depth = 12
    lv_h, root_h = tc._build(np.ascontiguousarray(leaves, np.uint8), depth)

    def counted():
        return np.array(
            [engine._LEAF_MARSHAL.labels(p).value for p in ("view", "copy")]
            + [engine._MARSHALLED.labels("leaves").value]
        )

    before = counted()
    lv_d, root_d = engine.device_build_levels(leaves, depth,
                                              root_only=root_only)
    assert list(counted() - before) == [
        want_path == "view", want_path == "copy", 1024 * 32
    ]

    assert root_d == root_h
    if root_only:
        assert lv_d is None
    else:
        assert len(lv_d) == len(lv_h) == depth
        for a, b in zip(lv_d, lv_h):
            assert np.array_equal(a, b)
    assert leaves.tobytes() == keep


def test_be_words_on_device_equals_words_from_bytes():
    """The swap the ladder starts with, traced over jnp, applied to the
    native uint32 view of random bytes == the host's big-endian packing."""
    import jax

    from lighthouse_tpu.ssz.sha256_batch import words_from_bytes

    data = np.random.default_rng(26).integers(0, 256, (4096, 64), np.uint8)
    got = np.asarray(jax.jit(engine.be_words)(data.view(np.uint32)))
    assert got.dtype == np.uint32
    assert np.array_equal(got, words_from_bytes(data))
    assert np.array_equal(engine.be_words(data.view(np.uint32)), got)


@pytest.mark.parametrize(
    "n,path,limit", [(1 << 16, "view", 0.25), ((1 << 16) - 3, "copy", 1.25)]
)
def test_leaf_marshal_allocates_no_temporaries(monkeypatch, n, path, limit):
    """Guard against the packing temporaries coming back unseen: the host
    memory allocated inside device_build_levels up to the device_put
    stays under a quarter of the leaves' bytes on the view path (the old
    packing peaked at eight times them) and at the one bucket buffer on
    the copy path."""
    import tracemalloc

    import lighthouse_tpu.parallel as par

    monkeypatch.setenv("LIGHTHOUSE_TPU_HASH_MESH_MIN", str(1 << 20))
    leaves = _rand_leaves(n, seed=n)
    engine.device_build_levels(leaves, 16, root_only=True)  # compile
    peaks = []
    real_put = par.put_single

    def put_and_note(a):
        peaks.append(tracemalloc.get_traced_memory()[1])
        return real_put(a)

    monkeypatch.setattr(par, "put_single", put_and_note)
    before = engine._LEAF_MARSHAL.labels(path).value
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        _, root = engine.device_build_levels(leaves, 16, root_only=True)
    finally:
        tracemalloc.stop()
    assert engine._LEAF_MARSHAL.labels(path).value - before == 1
    assert root == tc._build(leaves, 16)[1]
    assert len(peaks) == 1
    assert peaks[0] - base < limit * (1 << 16) * 32


@pytest.mark.parametrize("n", [200, 256], ids=["ragged", "full_view"])
def test_device_levels_mesh_sharded(monkeypatch, n):
    """With the single-chip pin threshold lowered, the ladder shards the
    leaf axis over the virtual 8-device mesh (each chip reduces its local
    subtree; host finishes the top) — output still bit-identical, and the
    dispatch is counted on the `sharded` lane. A full bucket goes up as a
    view of the caller's plane, split over the chips as it lies."""
    from lighthouse_tpu.parallel import get_mesh, reset_mesh_cache

    monkeypatch.delenv("LIGHTHOUSE_TPU_MESH", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_MESH_DEVICES", raising=False)
    reset_mesh_cache()
    try:
        if get_mesh() is None:
            pytest.skip("no multi-device mesh in this environment")
        monkeypatch.setenv("LIGHTHOUSE_TPU_HASH_MESH_MIN", "64")
        before = {
            k: c.value for k, c in engine.JAXHASH_DISPATCH.children()
        }
        leaves = _rand_leaves(n, seed=8)
        keep = leaves.copy()
        lv_d, root_d = engine.device_build_levels(leaves, 12)
        lv_h, root_h = tc._build(leaves, 12)
        assert root_d == root_h
        assert np.array_equal(leaves, keep)
        for a, b in zip(lv_d, lv_h):
            assert np.array_equal(a, b)
        sharded = {
            k: c.value for k, c in engine.JAXHASH_DISPATCH.children()
        }.get(("sharded",), 0)
        assert sharded > before.get(("sharded",), 0)
    finally:
        reset_mesh_cache()


def test_warm_tree_bucket_and_plan_warmup():
    secs = engine.warm_tree_bucket(100)
    assert secs >= 0.0
    t = router.start_warmup(buckets=(100,))
    t.join(timeout=60)
    assert not t.is_alive()


def test_calibrate_tree_hash_sweep_measures_buckets():
    """The r9 producer: the calibrator's tree-hash sweep compiles + times
    each requested ladder and returns the bucket tuple it persists."""
    from lighthouse_tpu.autotune.calibrate import tree_hash_sweep

    assert tree_hash_sweep([100], reps=1) == (100,)


# ----------------------------------------------------------------- router


def test_router_reasons_and_threshold(monkeypatch):
    r = TreeHashRouter(min_leaves=64)
    leaves = _rand_leaves(16)
    # host default: no device routing at all
    assert r.maybe_build_levels(leaves, 12) is None
    # below threshold with a device backend: host, reason small
    set_hash_backend("hybrid")
    assert r.maybe_build_levels(leaves, 12) is None
    # above threshold: the device serves, bit-exact
    big = _rand_leaves(100, seed=3)
    routed = r.maybe_build_levels(big, 12)
    assert routed is not None
    _, root = routed
    assert root == tc._build(big, 12)[1]
    totals = router.route_totals()
    assert totals.get("host/backend_host")
    assert totals.get("host/small")
    assert totals.get("device/ok")


def test_router_breaker_and_device_error(monkeypatch):
    set_hash_backend("hybrid")
    r = TreeHashRouter(min_leaves=4)
    calls = {"n": 0}

    def boom(leaves, depth, root_only=False):
        calls["n"] += 1
        raise RuntimeError("device wedged")

    monkeypatch.setattr(engine, "device_build_levels", boom)
    leaves = _rand_leaves(64, seed=4)
    # three consecutive failures -> host served each time, breaker opens
    for _ in range(3):
        assert r.maybe_build_levels(leaves, 12) is None
    assert calls["n"] == 3
    # OPEN circuit: hybrid refuses O(1) without touching the device
    assert r.maybe_build_levels(leaves, 12) is None
    assert calls["n"] == 3
    # backend "device" skips the open-circuit refusal: every attempt rides
    set_hash_backend("device")
    assert r.maybe_build_levels(leaves, 12) is None
    assert calls["n"] == 4


def test_set_hash_backend_validates():
    with pytest.raises(ValueError):
        set_hash_backend("gpu")
    assert hash_backend() == "host"  # default untouched


# ------------------------------------------------------------ ssz routing


def test_merkleize_routes_device(monkeypatch):
    from lighthouse_tpu.ssz.core import merkleize

    rng = np.random.default_rng(5)
    chunks = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
              for _ in range(300)]
    want = merkleize(chunks, 1024)  # host default
    set_hash_backend("device")
    monkeypatch.setattr(ROUTER, "min_leaves", 64)
    before = router.route_totals().get("device/ok", 0)
    got = merkleize(chunks, 1024)
    assert got == want
    assert router.route_totals().get("device/ok", 0) == before + 1


def test_state_root_device_equals_host(monkeypatch):
    """BeaconState.hash_tree_root at (small) validator scale: device and
    host backends produce the same root, through the real ssz descriptor
    stack + tree cache."""
    from lighthouse_tpu.testing.state_fixtures import (
        build_synthetic_state,
        uncached_state_root,
    )

    _spec, types, state = build_synthetic_state(300, participation_seed=1)
    monkeypatch.setattr(ROUTER, "min_leaves", 64)
    set_hash_backend("device")
    root_dev = types.BeaconState.hash_tree_root(state)
    assert root_dev == uncached_state_root(types, state)


# ---------------------------------------------------------- epoch vectors


def _epoch_state(n=300, seed=42, leak=False):
    import random

    from lighthouse_tpu.state_transition.slot import types_for_slot
    from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH, minimal_spec

    spec = minimal_spec()
    types = types_for_slot(spec, 0)
    rng = random.Random(seed)
    vals = []
    for i in range(n):
        slashed = rng.random() < 0.05
        exited = rng.random() < 0.05
        vals.append(types.Validator.make(
            pubkey=i.to_bytes(48, "big"),
            withdrawal_credentials=i.to_bytes(32, "big"),
            effective_balance=rng.choice([0, 16, 31, 32, 32]) * 10**9,
            slashed=slashed,
            activation_eligibility_epoch=0,
            activation_epoch=0 if rng.random() < 0.95 else FAR_FUTURE_EPOCH,
            exit_epoch=2 if exited else FAR_FUTURE_EPOCH,
            withdrawable_epoch=6 if slashed else FAR_FUTURE_EPOCH,
        ))
    state = types.BeaconState.default()
    state.validators = vals
    state.balances = [rng.randrange(0, 40 * 10**9) for _ in range(n)]
    state.previous_epoch_participation = [rng.randrange(0, 8) for _ in range(n)]
    state.current_epoch_participation = [rng.randrange(0, 8) for _ in range(n)]
    state.inactivity_scores = [rng.randrange(0, 50) for _ in range(n)]
    spe = spec.preset.SLOTS_PER_EPOCH
    state.slot = (20 if leak else 3) * spe - 1
    return spec, types, state


@pytest.mark.parametrize("leak", [False, True], ids=["steady", "leak"])
def test_altair_deltas_bit_exact(monkeypatch, leak):
    """The vectorized delta sets (device lane, host-numpy fallback under
    it) match the pure-Python spec loops element for element — slashed /
    exited / zero-balance validators and the inactivity leak included."""
    from lighthouse_tpu.state_transition import epoch as ep
    from lighthouse_tpu.types.spec import ForkName

    spec, _types, state = _epoch_state(leak=leak)
    fork = ForkName.deneb
    eligible = ep._eligible_validator_indices(state, spec)
    want = [
        ep.get_flag_index_deltas(state, spec, f, fork, eligible=eligible)
        for f in range(3)
    ]
    want.append(
        ep.get_inactivity_penalty_deltas(state, spec, fork, eligible=eligible)
    )
    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    set_hash_backend("device")
    got = ev.altair_deltas(state, spec, fork, eligible)
    assert got is not None
    for f in range(4):
        assert got[f][0] == want[f][0], f"rewards diverged, delta set {f}"
        assert got[f][1] == want[f][1], f"penalties diverged, delta set {f}"


def test_altair_deltas_host_lane_bit_exact(monkeypatch):
    """The host-numpy lane (the device-failure fallback) is bit-exact
    too — forced by wedging the device leg."""
    from lighthouse_tpu.state_transition import epoch as ep
    from lighthouse_tpu.types.spec import ForkName

    spec, _types, state = _epoch_state(seed=7)
    fork = ForkName.deneb
    eligible = ep._eligible_validator_indices(state, spec)
    want = [
        ep.get_flag_index_deltas(state, spec, f, fork, eligible=eligible)
        for f in range(3)
    ]
    want.append(
        ep.get_inactivity_penalty_deltas(state, spec, fork, eligible=eligible)
    )
    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    monkeypatch.setattr(ev, "_device_altair_deltas",
                        lambda *a, **k: None)
    set_hash_backend("device")
    got = ev.altair_deltas(state, spec, fork, eligible)
    assert got is not None
    for f in range(4):
        assert (got[f][0], got[f][1]) == want[f], f


def test_device_epoch_lane_itself_serves(monkeypatch):
    """The DEVICE lane returns a result and reports success to the shared
    breaker. `altair_deltas` answers from the host vector lane whenever
    the device leg returns None, so a JAX name removed by an upgrade (the
    `enable_x64` import, jax 0.9.0) hid behind bit-exact host answers in
    every other test here; this one wedges the host lane's way out."""
    from lighthouse_tpu.state_transition import epoch as ep
    from lighthouse_tpu.types.spec import ForkName

    spec, _types, state = _epoch_state(seed=5)
    eligible = ep._eligible_validator_indices(state, spec)
    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    set_hash_backend("device")
    served, recorded = [], []
    device_lane = ev._device_altair_deltas

    def spy(*a, **k):
        served.append(device_lane(*a, **k))
        return served[-1]

    monkeypatch.setattr(ev, "_device_altair_deltas", spy)
    monkeypatch.setattr(ROUTER, "record_device", recorded.append)
    assert ev.altair_deltas(state, spec, ForkName.deneb, eligible) is not None
    assert len(served) == 1 and served[0] is not None, (
        "the device epoch lane returned None: the host vector lane served"
    )
    assert recorded == [True]


def test_epoch_vectors_honor_shared_breaker(monkeypatch):
    """In hybrid mode an OPEN tree-hash breaker refuses the epoch-vector
    device path O(1) (pure-Python serves) — the router.py contract holds
    for the second consumer of the same device too."""
    from lighthouse_tpu.qos.breaker import CircuitBreaker
    from lighthouse_tpu.state_transition import epoch as ep
    from lighthouse_tpu.types.spec import ForkName

    spec, _types, state = _epoch_state(seed=13)
    eligible = ep._eligible_validator_indices(state, spec)
    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    monkeypatch.setattr(
        ROUTER, "_breaker", CircuitBreaker("tree_hash_device_test")
    )
    set_hash_backend("hybrid")
    for _ in range(3):
        ROUTER.record_device(False)
    assert ev.altair_deltas(state, spec, ForkName.deneb, eligible) is None
    # backend "device" keeps attempting (and a success closes the loop)
    set_hash_backend("device")
    assert ev.altair_deltas(state, spec, ForkName.deneb, eligible) is not None


def test_altair_deltas_overflow_falls_back(monkeypatch):
    """A state whose inactivity math would wrap uint64 refuses to
    vectorize (pure-Python bigints serve) instead of silently wrapping."""
    from lighthouse_tpu.state_transition import epoch as ep
    from lighthouse_tpu.types.spec import ForkName

    spec, _types, state = _epoch_state(seed=9)
    state.inactivity_scores[3] = 2**62
    eligible = ep._eligible_validator_indices(state, spec)
    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    set_hash_backend("device")
    assert ev.altair_deltas(state, spec, ForkName.deneb, eligible) is None


def test_process_epoch_end_to_end_device_equals_host(monkeypatch):
    """Full process_epoch: balances and effective balances identical with
    the vectorized stage routed vs the pure-Python default."""
    import copy

    from lighthouse_tpu.state_transition.epoch import process_epoch
    from lighthouse_tpu.state_transition.slot import types_for_slot

    spec, _types, state = _epoch_state(seed=11)
    fork = spec.fork_name_at_slot(state.slot)
    types = types_for_slot(spec, state.slot)
    st_host = copy.deepcopy(state)
    process_epoch(st_host, spec, types, fork)

    monkeypatch.setenv("LIGHTHOUSE_TPU_EPOCH_VEC_MIN", "1")
    set_hash_backend("device")
    st_dev = copy.deepcopy(state)
    process_epoch(st_dev, spec, types, fork)
    assert list(st_host.balances) == list(st_dev.balances)
    assert (
        [v.effective_balance for v in st_host.validators]
        == [v.effective_balance for v in st_dev.validators]
    )


# ------------------------------------------------------ workload surfaces


def test_loadtest_state_root_scenario_device(monkeypatch, tmp_path):
    """The state_root churn scenario through the device backend: routes
    show device/ok, conservation holds, exit 0."""
    from lighthouse_tpu.loadgen.driver import drive

    monkeypatch.setenv("LIGHTHOUSE_TPU_HASH_MIN_LEAVES", "64")
    monkeypatch.setattr(ROUTER, "min_leaves", 64)
    out = tmp_path / "sr.json"
    # the scenario's own --hash-backend plumbing selects the device path
    rc = drive(scenario="state_root", smoke=True, out=str(out), quiet=True,
               validators=512, slots=3, hash_backend="device")
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["conservation"]["ok"]
    assert report["tree_hash_routes"].get("device/ok")


def test_loadtest_state_root_cli_e2e(tmp_path):
    """`bn loadtest --scenario state_root --smoke` end to end (host
    backend: the default node path, no device compiles in the
    subprocess)."""
    out = tmp_path / "report.json"
    r = subprocess.run(
        [sys.executable, "-m", "lighthouse_tpu", "bn", "loadtest",
         "--scenario", "state_root", "--smoke", "--quiet",
         "--hash-backend", "host",
         "--out", str(out), "--validators", "512", "--slots", "2"],
        capture_output=True, text=True, timeout=240,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["scenario"] == "state_root"
    assert summary["conservation"]["ok"]
    report = json.loads(out.read_text())
    assert report["roots"] == report["slots"] + 1


def test_bench_state_root_cli_bench_matrix(tmp_path):
    """bench_state_root.py --smoke --bench-matrix: a fresh state_root row
    (with config-stamped history) lands in the smoke matrix schema; the
    gate verdict is NOT claimed for smoke rows (they land in the ungated
    *_SMOKE artifact)."""
    r = subprocess.run(
        [sys.executable, "scripts/bench_state_root.py", "--smoke",
         "--validators", "512", "--reps", "2", "--bench-matrix",
         "--bench-root", str(tmp_path)],
        capture_output=True, text=True, timeout=240,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    matrix = json.loads((tmp_path / "BENCH_MATRIX_SMOKE.json").read_text())
    assert matrix["state_root"]["p50_ms"] > 0
    entry = matrix["state_root"]["history"][0]
    assert entry["fresh"] is True
    assert entry["hash_backend"] == "host"
    assert entry["source"] == "bench_state_root"
    assert matrix["epoch_transition"]["p50_ms"] > 0
    assert "trend gate not evaluated" in r.stdout
    # the non-smoke leg against a fresh root IS gated (and green)
    r2 = subprocess.run(
        [sys.executable, "scripts/bench_state_root.py",
         "--validators", "512", "--reps", "2", "--skip-epoch",
         "--bench-matrix", "--bench-root", str(tmp_path)],
        capture_output=True, text=True, timeout=240,
    )
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    assert "perf trend gate clean" in r2.stdout
    matrix = json.loads((tmp_path / "BENCH_MATRIX.json").read_text())
    assert matrix["state_root"]["history"][0]["validators"] == 512


def test_plan_carries_tree_hash_warmup():
    """The r9 plan surface: profile tree_hash_buckets pass through
    (clamped, deduplicated); unmeasured profiles get the default."""
    from lighthouse_tpu.autotune import planner

    assert planner.DEFAULT_PLAN.tree_hash_warmup == (16384,)
