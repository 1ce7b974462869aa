"""The main path's device programs, compiled for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with the image
compiles for a topology that is described, not present
(`jax.experimental.topologies`). What it refuses here the chip refuses too
— a kernel slice off the tiling, a program over the chip's 16 GB — so
these cases guard every later PR at no chip time. A compile that passes is
not a chip run: `chip_smoke.py` is.

The topology is described inside a module-scoped fixture of THIS file
(never at import, never in conftest, never autouse) because the process
that loads the TPU library holds its lock until it exits; the compiles run
in the test's own process, with the persistent cache off around them (an
entry written for a described chip cannot be read back without one).

Unmarked: the compiles of a few seconds (`pairs` at each served bucket's
set count) and the KZG lane pass at its one served size (~half a minute). `slow`: the minute-long stage compiles at the served 64x128
bucket (prepare ~1 min, hash-to-G2 ~3 min, of stage 4's two programs the
Miller loop ~2 min and the final exponentiation ~1.25 min on eight host
cores), the Miller loop of the urgent 4x128 bucket, whose 5 pairs a
program built for a TPU pads to one row of 128 lanes, and the indexed
prepare of the Electra block's 16x32768 bucket over the 1,114,112-row
registry table (`_stage_prepare_indexed`, minutes), and the two-grid
prepares (`backend.key_grid_plan`) that the three cells of unequal widths
serve since PR 42: the Electra block's 8x32768 + 4x512 by index, the Deneb
block's 1x512 + 256x128 and the aggregates' 64x512 + 128x1 packed; and
since PR 43 the 1024x1 bucket of `subnet_flood_1key` (`pairs` unmarked,
~45 s; `slow`: the indexed prepare ~75 s, hash-to-G2 at 1,024 lanes ~3.5
min and 1.9 GB of temporaries, the Miller loop at 1,025 pairs ~3.5 min);
since PR 44 `_stage_pairs_folded`, stage 3 of a dispatch that folds its
sets by message onto a row of 128 lanes (`backend.message_lanes`): from
256 sets unmarked (~45 s), from 1,024 `slow` (~1 min). The programs beside
it at 128 lanes and 129 pairs are hash-to-G2 and the Miller loop as the
64x128 cases compile them, at twice the lanes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.crypto.jaxbls import h2c_ops as h2
from lighthouse_tpu.crypto.jaxbls import limbs as lb
from lighthouse_tpu.crypto.jaxbls import pairing_ops as po

V5E_HBM_BYTES = 16 * 1024**3
N_SETS, N_PKS = 64, 128   # the served gossip bucket (chip_smoke.py)
#: the served buckets: urgent, gossip, block, Electra block, a dispatch of
#: single-key subnet attestations (BENCHMARK.json's BLS cells)
SERVED_BUCKETS = ((4, 128), (64, 128), (256, 512), (16, 32768), (1024, 1))
#: the registry table of 1,048,576 validators with its room for deposits
TABLE_ROWS = 1_114_112


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _stage_args(n: int, m: int, sharding) -> dict:
    """Argument shapes of the five programs of the four stages at bucket
    (n, m), as the marshal and the previous programs produce them."""
    NL = lb.NL

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)

    g1 = tuple(u32(n, NL) for _ in range(3))          # z_pk, jacobian G1
    g2 = tuple(u32(n, 2, NL) for _ in range(3))       # h_jac, jacobian G2
    acc = tuple(u32(2, NL) for _ in range(3))         # sig_acc, one G2 point
    mask = jax.ShapeDtypeStruct((n + 1,), jnp.bool_, sharding=sharding)
    return {
        "prepare": (u32(n, m, NL), u32(n, m, NL), u32(n, m),
                    u32(n, 2, NL), u32(n, 2, NL), u32(n, be.Z_BITS), u32(n)),
        # the table's two coordinates, the index grid, then as `prepare`
        "prepare_indexed": (
            u32(TABLE_ROWS, NL), u32(TABLE_ROWS, NL),
            jax.ShapeDtypeStruct((n, m), jnp.int32, sharding=sharding),
            u32(n, m), u32(n, 2, NL), u32(n, 2, NL), u32(n, be.Z_BITS),
            u32(n)),
        "h2c": (u32(n, 2, 2, NL),),
        "pairs": (g1, g2, acc, u32(n)),
        "miller": (u32(n + 1, NL), u32(n + 1, NL),
                   u32(n + 1, 2, NL), u32(n + 1, 2, NL), mask),
        "final_exp": (u32(2, 3, 2, NL),),             # the Miller value
    }


#: the served dispatches of unequal widths (BENCHMARK.json's three such
#: cells): bucket, key counts, and the stage-1 program that serves them
MIXED_DISPATCHES = {
    "electra_block": ((16, 32768), [1, 1] + [32_400] * 8 + [512],
                      "prepare_indexed_grids"),
    "deneb_block": ((256, 512), [1, 1] + [128] * 128 + [512],
                    "prepare_grids"),
    "aggregates": ((256, 512), [1] * 128 + [480] * 64, "prepare_grids"),
}


def _grids_args(stage: str, n: int, m: int, widths, sharding) -> tuple:
    """Argument shapes of a two-grid prepare for a dispatch of `widths`,
    laid as `backend.key_grid_plan` lays it."""
    NL = lb.NL

    def arr(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    grids, where = be.key_grid_plan(widths, n, m)
    if stage == "prepare_indexed_grids":
        keys = (arr((TABLE_ROWS, NL)), arr((TABLE_ROWS, NL))) + tuple(
            a for g in grids for a in (arr(g, jnp.int32), arr(g)))
    else:
        keys = tuple(a for g in grids
                     for a in (arr(g + (NL,)), arr(g + (NL,)), arr(g)))
    return keys + (arr(where.shape, jnp.int32), arr((n, 2, NL)),
                   arr((n, 2, NL)), arr((n, be.Z_BITS)), arr((n,)))


def _stage_miller_for_a_tpu(px, py, qxx, qyy, pair_mask):
    """`backend._stage_miller` as a process on the chip lowers it. The
    Miller loop's lane plan reads the platform off the process, which is
    the CPU here, so this one names the platform it compiles for."""
    return po.miller_loop_product((px, py), (qxx, qyy), pair_mask,
                                  platform="tpu")


_STAGE_FNS = {
    "prepare": be._stage_prepare,
    "h2c": h2.hash_to_g2_jacobian,
    "pairs": be._stage_pairs,
    "miller": _stage_miller_for_a_tpu,
    "final_exp": be._stage_final_exp,
    "prepare_indexed": be._stage_prepare_indexed,
}


def _compile_stage(stage: str, n: int, m: int, sharding):
    """The stage as the TPU node jits it: donation on."""
    be._init_consts()
    fn = jax.jit(_STAGE_FNS[stage],
                 donate_argnums=be.STAGE_DONATE_ARGNUMS[stage])
    return fn.lower(*_stage_args(n, m, sharding)[stage]).compile()


def _assert_fits_hbm(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)


@pytest.mark.parametrize("stage,bucket", [
    *[("pairs", b) for b in SERVED_BUCKETS],
    pytest.param("prepare", (N_SETS, N_PKS), marks=pytest.mark.slow),
    pytest.param("h2c", (N_SETS, N_PKS), marks=pytest.mark.slow),
    pytest.param("miller", (N_SETS, N_PKS), marks=pytest.mark.slow),
    pytest.param("miller", SERVED_BUCKETS[0], marks=pytest.mark.slow),
    pytest.param("final_exp", (N_SETS, N_PKS), marks=pytest.mark.slow),
    pytest.param("prepare_indexed", SERVED_BUCKETS[3],
                 marks=pytest.mark.slow),
    # subnet_flood_1key's bucket: no key axis, 1,024 lanes in stages 1-2,
    # 1,025 pairs = eight lines an accumulator in the Miller loop
    pytest.param("prepare_indexed", SERVED_BUCKETS[4],
                 marks=pytest.mark.slow),
    pytest.param("h2c", SERVED_BUCKETS[4], marks=pytest.mark.slow),
    pytest.param("miller", SERVED_BUCKETS[4], marks=pytest.mark.slow),
], ids=lambda v: v if isinstance(v, str) else "%dx%d" % v)
def test_stage_compiles_for_v5e(stage, bucket, one_chip,
                                no_persistent_cache):
    n, m = bucket
    compiled = _compile_stage(stage, n, m, one_chip)
    _assert_fits_hbm(compiled)
    # the next stage's argument shapes in _stage_args are this stage's
    # outputs: a drifted hand-written shape must fail here, not broadcast
    args = _stage_args(n, m, one_chip)
    out = _shapes(jax.eval_shape(_STAGE_FNS[stage], *args[stage]))
    if stage in ("prepare", "prepare_indexed"):
        assert out[:2] == _shapes((args["pairs"][0], args["pairs"][2]))
    elif stage == "h2c":
        assert out == _shapes(args["pairs"][1])
    elif stage == "pairs":
        assert out == _shapes(args["miller"])
    elif stage == "miller":
        assert out == _shapes(args["final_exp"][0])


@pytest.mark.slow
@pytest.mark.parametrize("dispatch", list(MIXED_DISPATCHES))
def test_two_grid_prepare_compiles_for_v5e(dispatch, one_chip,
                                           no_persistent_cache):
    """Stage 1 over a wide and a narrow key grid, as the TPU node jits it
    for each served dispatch of unequal widths: it compiles, fits the
    chip, and hands stage 3 what the one-grid prepare hands it."""
    (n, m), widths, stage = MIXED_DISPATCHES[dispatch]
    be._init_consts()
    fn = be._ONE_CHIP_VARIANTS[stage]
    args = _grids_args(stage, n, m, widths, one_chip)
    compiled = jax.jit(
        fn, donate_argnums=be.STAGE_DONATE_ARGNUMS[stage]
    ).lower(*args).compile()
    _assert_fits_hbm(compiled)
    pairs = _stage_args(n, m, one_chip)["pairs"]
    assert _shapes(jax.eval_shape(fn, *args))[:2] == _shapes(
        (pairs[0], pairs[2]))


@pytest.mark.parametrize("n", [
    256, pytest.param(1024, marks=pytest.mark.slow)])
def test_folded_stage_3_compiles_for_v5e(n, one_chip, no_persistent_cache):
    """`_stage_pairs_folded` as the TPU node jits it, from the n sets of a
    served bucket onto one row of message lanes: it compiles, fits the
    chip, and hands the Miller loop k + 1 = 129 pairs, whatever n is."""
    k = be.message_lanes(82, n)
    assert k == po.MILLER_LANES < n
    be._init_consts()
    z_pk, _, sig_acc, _ = _stage_args(n, 1, one_chip)["pairs"]
    _, h_jac, _, _ = _stage_args(k, 1, one_chip)["pairs"]
    args = (z_pk, h_jac, sig_acc,
            jax.ShapeDtypeStruct((2, n), jnp.int32, sharding=one_chip))
    fn = be._ONE_CHIP_VARIANTS["pairs_folded"]
    compiled = jax.jit(
        fn, donate_argnums=be.STAGE_DONATE_ARGNUMS["pairs_folded"]
    ).lower(*args).compile()
    _assert_fits_hbm(compiled)
    assert _shapes(jax.eval_shape(fn, *args)) == _shapes(
        _stage_args(k, 1, one_chip)["miller"])


def test_tree_hash_ladder_compiles_for_v5e(one_chip, no_persistent_cache):
    """The jaxhash ladder at its 1,048,576-leaf bucket fits the chip."""
    from lighthouse_tpu.jaxhash import engine

    n = 1 << 20
    ladder = engine._make_ladder(n, 1, True, None)
    words = jax.ShapeDtypeStruct((n, 8), np.uint32, sharding=one_chip)
    _assert_fits_hbm(ladder.lower(words).compile())


def test_kzg_lane_pass_compiles_for_v5e_at_its_served_row(one_chip,
                                                          no_persistent_cache):
    """`msm.kzg_lincomb_kernel` at the ONE size it is served at — 16 blob
    slots of 8 lanes, a full row of 128 — which tier-1 never executes
    (XLA:CPU pays it lane by lane; tests/test_kzg.py patches the slots
    down). Its outputs are the pairing stage's inputs at 4 pair lanes. About half a minute."""
    from lighthouse_tpu.crypto.jaxbls import msm

    lanes = msm.KZG_BLOB_SLOTS * msm.KZG_ROWS
    assert lanes == 128

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    args = (u32(lanes, lb.NL), u32(lanes, lb.NL), u32(lanes),
            u32(lanes, msm.KZG_SCALAR_BITS))
    _assert_fits_hbm(jax.jit(msm.kzg_lincomb_kernel).lower(*args).compile())
    gx, gy, pair_mask, in_subgroup = _shapes(
        jax.eval_shape(msm.kzg_lincomb_kernel, *args))
    pairs = msm.KZG_PAIR_LANES
    assert gx == gy == ((pairs, lb.NL), jnp.uint32)
    assert pair_mask == ((pairs,), jnp.bool_)
    assert in_subgroup == ((msm.KZG_BLOB_SLOTS, 2), jnp.bool_)
