"""The mesh layer itself (parallel/mesh.py) + mesh-aware dispatch.

Runs on the forced-host-device harness (tests/conftest.py pins
XLA_FLAGS=--xla_force_host_platform_device_count=8): mesh resolution
seams, the 2-D acceptance/rejection matrix, placement, mesh-keyed
padding, the stage-cache keying and the shard_map fallback's flip
mechanism (with a stub). Nothing here compiles a staged program: the
end-to-end 8-virtual-device dispatch through the REAL
`PipelinedDispatcher`, and the `slow`-marked real shard_map collective,
live in test_jaxbls_backend.py, beside the programs they need.
"""

import numpy as np
import pytest

from lighthouse_tpu import parallel
from lighthouse_tpu.parallel import mesh as pm


@pytest.fixture(autouse=True)
def _fresh_mesh(monkeypatch):
    """Every test re-resolves the mesh from a clean seam state and leaves
    the process-wide cache re-resolved for the next test file."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_MESH_DEVICES", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_PK_SHARDS", raising=False)
    monkeypatch.delenv("LIGHTHOUSE_TPU_MESH", raising=False)
    parallel.reset_mesh_cache()
    yield
    monkeypatch.undo()
    parallel.reset_mesh_cache()


# ------------------------------------------------------------- resolution


def test_get_mesh_resolves_8_devices_and_records_bringup():
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    before = RECORDER.events_recorded
    mesh = parallel.get_mesh()
    assert mesh is not None and int(mesh.devices.size) == 8
    assert dict(mesh.shape) == {"sets": 8}
    assert parallel.mesh_shape_key() == "sets8"
    # bring-up is a flight-recorder fact + a per-axis gauge
    assert RECORDER.events_recorded > before
    kinds = [e["kind"] for e in RECORDER.events(16)]
    assert "mesh_bringup" in kinds
    assert pm._MESH_AXIS_SIZE.labels("sets").value == 8


def test_mesh_devices_env_seam(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "2")
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert mesh is not None and dict(mesh.shape) == {"sets": 2}
    assert parallel.mesh_shape_key() == "sets2"

    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "1")
    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is None
    assert parallel.mesh_shape_key() == "single"

    # junk cap: warned, ignored, full mesh serves
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "zebra")
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert mesh is not None and dict(mesh.shape) == {"sets": 8}


def test_mesh_shape_key_parse_round_trip():
    assert parallel.parse_mesh_shape("sets8") == {"sets": 8}
    assert parallel.parse_mesh_shape("sets4-pks2") == {"sets": 4, "pks": 2}
    assert parallel.parse_mesh_shape("single") == {}
    assert parallel.parse_mesh_shape(None) == {}
    assert parallel.parse_mesh_shape("garbage!!") == {}


# ------------------------------------------ 2-D acceptance/rejection matrix


@pytest.mark.parametrize("raw,expected_shape", [
    ("2", {"sets": 4, "pks": 2}),
    ("4", {"sets": 2, "pks": 4}),
    ("8", {"sets": 1, "pks": 8}),
])
def test_pk_shards_accepted(monkeypatch, raw, expected_shape):
    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", raw)
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert dict(mesh.shape) == expected_shape
    assert pm.PK_AXIS in mesh.axis_names


@pytest.mark.parametrize("raw,reason", [
    ("3", "not_pow2"),          # not a power of two
    ("6", "not_pow2"),
    ("16", "not_dividing"),     # pow2 but exceeds/doesn't divide 8
    ("abc", "unparseable"),     # the pre-r10 SILENT branch: must warn now
    ("", None),                 # empty string parses to... rejected loudly
    ("0", "non_positive"),      # zero/negative: also previously silent
    ("-4", "non_positive"),
])
def test_pk_shards_rejected_loudly(monkeypatch, raw, reason):
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", raw)
    parallel.reset_mesh_cache()
    before = RECORDER.events_recorded
    mesh = parallel.get_mesh()
    # every invalid value falls back to the 1-D sets mesh...
    assert dict(mesh.shape) == {"sets": 8}
    # ...and leaves a structured trace naming the rejected value
    events = [e for e in RECORDER.events(16)
              if e["kind"] == "mesh_config_rejected"]
    assert events, f"no rejection event for {raw!r}"
    assert events[-1]["pk_shards"] == raw
    if reason is not None:
        assert events[-1]["reason"] == reason
    assert RECORDER.events_recorded > before


def test_pk_shards_one_means_1d_quietly(monkeypatch):
    from lighthouse_tpu.observability.flight_recorder import RECORDER

    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", "1")
    parallel.reset_mesh_cache()
    n_rejections = len([
        e for e in RECORDER.events(64)
        if e["kind"] == "mesh_config_rejected"
    ])
    mesh = parallel.get_mesh()
    assert dict(mesh.shape) == {"sets": 8}
    after = len([
        e for e in RECORDER.events(64)
        if e["kind"] == "mesh_config_rejected"
    ])
    assert after == n_rejections  # an explicit 1 is not a config error


def test_mesh_devices_zero_rejected_loudly(monkeypatch, capsys):
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "0")
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert dict(mesh.shape) == {"sets": 8}  # ignored, full mesh serves


def test_non_pow2_device_count_clamps_to_pow2(monkeypatch):
    """A 3- or 6-chip slice must never reach pad_sets (a pow2 multiple of
    3 does not exist — the search would never terminate): the mesh serves
    on the largest pow2 prefix, loudly."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "3")
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    assert dict(mesh.shape) == {"sets": 2}
    assert parallel.pad_sets(3) == 4      # terminates, pow2 multiple of 2

    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", "6")
    parallel.reset_mesh_cache()
    assert dict(parallel.get_mesh().shape) == {"sets": 4}

    # defense in depth: the padding helper itself refuses a non-pow2 axis
    with pytest.raises(ValueError):
        pm._pad_pow2_multiple(4, 3)


def test_mesh_sweep_rejects_mesh_stall(tmp_path):
    """mesh_stall's acceptance gate is ill-defined at the sweep's 1-chip
    point (the wedged chip IS the urgent lane's): the sweep refuses it
    cleanly; it runs standalone where the driver enforces the gate."""
    import io

    from lighthouse_tpu.loadgen.driver import drive

    stderr = io.StringIO()
    rc = drive(scenario="mesh_stall", smoke=True, quiet=True,
               mesh_devices=[1, 8], out=str(tmp_path / "s.json"),
               bench_root=str(tmp_path), stderr=stderr)
    assert rc == 1
    assert "cannot sweep" in stderr.getvalue()


# -------------------------------------------------------------- placement


def test_put_sets_shards_leading_axis():
    mesh = parallel.get_mesh()
    a = parallel.put_sets(np.zeros((8, 3), np.uint32))
    spec = a.sharding.spec
    assert tuple(spec) == ("sets", None)
    assert len(a.sharding.device_set) == 8
    # every shard holds exactly one row
    assert all(s.data.shape == (1, 3) for s in a.addressable_shards)
    assert mesh is not None


def test_put_pk_grid_2d_mesh_shards_pk_axis(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", "2")
    parallel.reset_mesh_cache()
    a = parallel.put_pk_grid(np.zeros((4, 2, 5), np.uint32))
    assert tuple(a.sharding.spec) == ("sets", "pks", None)
    b = parallel.put_sets(np.zeros((4, 5), np.uint32))
    assert tuple(b.sharding.spec) == ("sets", None)


def test_put_single_keeps_array_whole():
    a = parallel.put_single(np.zeros((4, 3), np.uint32))
    assert len(a.sharding.device_set) == 1


# ------------------------------------------------------- mesh-keyed padding


def test_pad_sets_mesh_keyed():
    # live 8-device mesh: pow2 AND multiple of 8
    assert parallel.pad_sets(3) == 8
    assert parallel.pad_sets(8) == 8
    assert parallel.pad_sets(9) == 16
    # explicit topology overrides the live one (the sweep's seam)
    import jax
    from jax.sharding import Mesh

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("sets",))
    assert parallel.pad_sets(3, mesh=mesh2) == 4
    assert parallel.pad_sets(5, mesh=mesh2) == 8


def test_pad_pks_follows_pks_axis(monkeypatch):
    assert parallel.pad_pks(3) == 4          # 1-D mesh: pow2 only
    monkeypatch.setenv("LIGHTHOUSE_TPU_PK_SHARDS", "2")
    parallel.reset_mesh_cache()
    assert parallel.pad_pks(1) == 2          # must cover the pks axis


def test_padding_bucket_mesh_vs_single_chip():
    from lighthouse_tpu.crypto.jaxbls.backend import padding_bucket

    # mesh rule: sets round to a multiple of the 8-chip sets axis
    assert padding_bucket(1, 1) == (8, 1)
    assert padding_bucket(9, 1) == (16, 1)
    # the urgent lane's single-chip rule: plain pow2, no mesh padding
    assert padding_bucket(1, 1, single_chip=True) == (4, 1)
    assert padding_bucket(9, 3, single_chip=True) == (16, 4)
    # explicit-mesh keying (the sweep's second topology in one process)
    import jax
    from jax.sharding import Mesh

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("sets",))
    assert padding_bucket(1, 1, mesh=mesh2) == (4, 1)


# ---------------------------------------------------- stage-cache keying


def test_stage_cache_keyed_by_mesh_and_donation(monkeypatch):
    """_get_stages forks its cache per (donation, mesh signature) WITHOUT
    compiling anything — flipping the mesh seams mid-process (the sweep)
    or the donation env (tests) picks distinct jit builds."""
    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import pipeline as pl

    mesh = parallel.get_mesh()
    be._get_stages()                  # plain (urgent/single-chip) variant
    be._get_stages(mesh=mesh)         # the live 8-chip variant
    assert "stages_d0" in be._kernel_cache
    assert "stages_d0_sets8" in be._kernel_cache
    # donation forks the key too (constructing jits compiles nothing)
    monkeypatch.setattr(pl, "donation_enabled", lambda explicit=None: (True, "env"))
    be._get_stages(mesh=mesh)
    assert "stages_d1_sets8" in be._kernel_cache
    # the meshed variant's stage 4 is the fallback-capable dispatcher
    assert isinstance(
        be._kernel_cache["stages_d0_sets8"][3], be._PairingDispatch
    )
    # one chip's holds the one program and the two that serve wide
    # buckets, each jitted under its own name
    pairing = be._kernel_cache["stages_d0"][3]
    assert isinstance(pairing, be._PairingPrograms)
    assert pairing.one.__wrapped__ is be._stage_pairing
    assert pairing.miller.__wrapped__ is be._stage_miller
    assert pairing.final_exp.__wrapped__ is be._stage_final_exp


@pytest.mark.parametrize("lanes,platform,programs", [
    (5, "cpu", ["@jit_one"]), (32, "cpu", ["@jit_one"]),
    (33, "cpu", ["@jit_miller", "@jit_final_exp"]),
    (257, "cpu", ["@jit_miller", "@jit_final_exp"]),
    (5, "tpu", ["@jit_miller", "@jit_final_exp"]),
    (4, "tpu", ["@jit_miller", "@jit_final_exp"]),
], ids=["5-one", "32-one", "33-two", "257-two", "5-two-on-a-tpu",
        "4-two-on-a-tpu"])
def test_pairing_programs_by_pair_lanes(lanes, platform, programs,
                                        monkeypatch):
    """The one-chip stage-4 callable with stand-in programs (nothing here
    compiles a Miller loop). What serves follows the Miller loop's plan:
    one accumulator -> the one program, a row of them (33 pairs on here,
    every pair count — the urgent bucket's 5, KZG's 4 — where the process
    runs on a TPU, which the plan reads from jax.default_backend()) -> two
    programs chained on the device. `.lower` gives the lowerings of what
    serves — the second at the first's output shape — program capture
    records their sum under the one stage, an attributed dispatch is ONE
    `pairing` resolve, and with donation on each dispatch consumes its own
    inputs and holds nothing over to the next."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
    from lighthouse_tpu.observability import device as obsdev
    from lighthouse_tpu.observability import perf

    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert (po.miller_lane_plan(lanes)[0] > 1) == (len(programs) == 2)

    def miller(px, py, qxx, qyy, pair_mask):
        return jnp.where(pair_mask, px + py + qxx + qyy, 0)

    def final_exp(f):
        return jnp.sum(f * f) == 100.0 * (lanes - 1)

    def one(px, py, qxx, qyy, pair_mask):
        return final_exp(miller(px, py, qxx, qyy, pair_mask))

    donate = be.STAGE_DONATE_ARGNUMS
    pairing = be._PairingPrograms(
        jax.jit(one, donate_argnums=donate["pairing"]),
        jax.jit(miller, donate_argnums=donate["miller"]),
        jax.jit(final_exp, donate_argnums=donate["final_exp"]),
    )

    def inputs():
        return tuple(jnp.full((lanes,), v, jnp.float32)
                     for v in (1, 2, 3, 4)) + (jnp.arange(lanes) < lanes - 1,)

    lowered = pairing.lower(*inputs())
    as_tuple = lowered if isinstance(lowered, tuple) else (lowered,)
    assert [low.as_text().split()[1] for low in as_tuple] == programs
    if len(programs) == 2:
        assert lowered[0].out_info.shape == (lanes,)
        assert lowered[1].in_avals[0][0].shape == (lanes,)

    first = inputs()
    assert bool(pairing(*first)) is True
    if len(programs) == 2:
        # the stand-in Miller value can live in a donated input (the one
        # program's scalar cannot, and XLA:CPU then keeps the inputs)
        assert first[0].is_deleted()
        with pytest.raises((RuntimeError, ValueError), match="deleted"):
            pairing(*first)
    resolves = obsdev.STAGE_DEVICE_SECONDS.labels("pairing", lanes - 1, 1)
    with obsdev.attributed():
        attr = obsdev.begin((lanes - 1, 1))
        obsdev.run_stage(attr, "pairing", pairing, *inputs())  # "compile"
        seen = resolves.n
        assert bool(obsdev.run_stage(attr, "pairing", pairing, *inputs()))
    assert resolves.n == seen + 1                    # fresh buffers: served

    perf.reset_programs()
    try:
        served = perf.capture_program("pairing", pairing, inputs(), (4, 1))
        alone = [perf.capture_program("pairing", fn, a, (4, 1)) for fn, a in (
            (pairing.miller, inputs()),
            (pairing.final_exp, (jnp.ones(lanes, jnp.float32),)),
            (pairing.one, inputs()))]
    finally:
        perf.reset_programs()
    if len(programs) == 1:
        assert served == alone[2]
        return
    for summed in ("flops", "bytes_accessed", "generated_code_bytes"):
        assert served[summed] == alone[0][summed] + alone[1][summed]
    assert alone[0]["flops"] > 0 and alone[1]["flops"] > 0
    for largest in ("argument_bytes", "output_bytes", "temp_bytes"):
        assert served[largest] == max(alone[0][largest], alone[1][largest])


def test_pairing_dispatch_flips_to_fallback_once(monkeypatch):
    """The shard_map fallback MECHANISM: a failing explicit-sharding jit
    flips the dispatcher permanently to the fallback build (stubbed here;
    the real collective compile is covered by the slow-marked e2e)."""
    from lighthouse_tpu.crypto.jaxbls import backend as be

    mesh = parallel.get_mesh()
    calls = []

    class _Boom:
        def __call__(self, *a):
            raise RuntimeError("forced sharding-propagation failure")

    def fake_build(m):
        assert m is mesh
        calls.append("built")
        return lambda *a: "fallback-result"

    monkeypatch.setattr(be, "_build_shard_map_pairing", fake_build)
    pd = be._PairingDispatch(mesh, _Boom())
    assert pd(1, 2, 3, 4, 5) == "fallback-result"
    assert pd._use_fallback is True
    assert pd(1, 2, 3, 4, 5) == "fallback-result"
    assert calls == ["built"]  # built once, flip is sticky
