"""Compile the staged BLS programs of a test module once, in threads.

Not a test file. The programs of the four stages (prepare, hash-to-G2,
pairs, pairing) are large at any shape: on XLA:CPU one build of them costs
minutes, and `tests/conftest.py` drops compiled executables at every file
boundary, so each module that drives the real `JaxBackend` pays for its
builds itself. The module that does over the mesh (`test_jaxbls_backend.py`)
calls `warm_builds` from one module-scoped fixture with exactly the builds
its tests dispatch, a thread a build: XLA releases the GIL while it compiles,
so the wall cost is about one build's, not the sum. No more threads than
that: in the six-worker tier-1 run every core is taken already, and PR 25
measured three threads a build (prepare, hash-to-G2 and pairing side by
side) at the same seconds for the module (589-650 against 604-608) and no
fewer for the files beside it. A new test that drives the staged backend
joins that module (and, if it needs a new bucket, adds it to the module's
warm-up) instead of opening a file of its own.

Also here: `warm_one_chip_prepares` and `prepare_rest` for the file that
drives stage 1 of the batch lane without a mesh (test_jaxbls_registry.py),
`run_in_threads` for any module fixture that compiles several programs, and
for the tests of the coefficient chain's refusal in both the curve's and the
backend's file `order_13_twist_point`, a point of E'(Fq2) outside G2.
"""

import functools
import threading

import numpy as np


def run_in_threads(*jobs):
    """Run the callables concurrently; re-raise the first failure."""
    errors = []

    def guarded(job):
        try:
            job()
        except BaseException as e:  # surfaced below, in the caller's thread
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(j,)) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def warm_build(n, ms, mesh=None):
    """One build: the four stages at `n` sets, prepare at every key-count
    bucket in `ms`, placed as the dispatch path places them — over `mesh`,
    or whole on one device with `mesh=None` (the urgent lane, and every
    lane of a process without a mesh). One stage after the other, each on
    the real outputs of those before it: the shardings of stage outputs
    are XLA's choice, so only a real chain compiles what a dispatch runs."""
    import jax

    from lighthouse_tpu import parallel
    from lighthouse_tpu.crypto.jaxbls import backend as be, limbs as lb

    prepare, h2c_stage, pairs_stage, pairing_stage = be._get_stages(mesh=mesh)
    if mesh is None:
        put_pk = put = parallel.put_single
    else:
        put_pk = functools.partial(parallel.put_pk_grid, mesh=mesh)
        put = functools.partial(parallel.put_sets, mesh=mesh)

    def limbs(*shape):
        return np.zeros(shape + (lb.NL,), np.uint32)

    for m in ms:
        z_pk, sig_acc, _bad = prepare(
            put_pk(limbs(n, m)), put_pk(limbs(n, m)),
            put_pk(np.ones((n, m), np.uint32)),
            put(limbs(n, 2)), put(limbs(n, 2)),
            put(np.ones((n, be.Z_BITS), np.uint32)),
            put(np.ones((n,), np.uint32)),
        )
    h_jac = h2c_stage(put(limbs(n, 2, 2)))
    pairs = pairs_stage(z_pk, h_jac, sig_acc, put(np.ones((n,), np.uint32)))
    jax.block_until_ready(pairing_stage(*pairs))


def warm_builds(*builds):
    """Each build is (n, ms, mesh); one thread a build."""
    run_in_threads(*(functools.partial(warm_build, *b) for b in builds))


def warm_one_chip_prepares(*programs, table_rows=None):
    """Compile stage-1 programs of the batch lane without a mesh at 4 sets:
    each of `programs` is (`_get_one_chip_variant` name, its key grids),
    the arguments placed as the marshal places them (an indexed program's
    table, `table_rows` rows of zeros, as `PubkeyTable.append` does)."""
    import jax

    from lighthouse_tpu.crypto.jaxbls import backend as be, limbs as lb
    from lighthouse_tpu.parallel import put_single

    def limbs(*shape):
        return np.zeros(shape + (lb.NL,), np.uint32)

    def ones(*shape):
        return put_single(np.ones(shape, np.uint32))

    for stage, grids in programs:
        if "indexed" in stage:
            keys = (jax.device_put(limbs(table_rows)),
                    jax.device_put(limbs(table_rows))) + tuple(
                a for g in grids
                for a in (put_single(np.zeros(g, np.int32)), ones(*g)))
        else:
            keys = tuple(
                put_single(a) for g in grids
                for a in (limbs(*g), limbs(*g), np.ones(g, np.uint32)))
        if len(grids) == 2:
            keys += (put_single(np.zeros((4,), np.int32)),)
        jax.block_until_ready(be._get_one_chip_variant(stage)(
            *keys, put_single(limbs(4, 2)), put_single(limbs(4, 2)),
            ones(4, be.Z_BITS), ones(4)))


#: the coefficients of `prepare_rest`'s four sets
PREPARE_ZS = [3, 0xDEADBEEF12345677, 0x42, 2**63 + 9]


def prepare_rest(sets, n_real=4):
    """Stage 1's arguments behind the keys for up to four sets at n = 4:
    (sig_x, sig_y, z_digits, set_mask), the coefficients `PREPARE_ZS`."""
    from lighthouse_tpu.crypto.jaxbls import backend as be, curve_ops as co

    sig_x = np.zeros((4, 2, 24), np.uint32)
    sig_y = np.zeros((4, 2, 24), np.uint32)
    for i, s in enumerate(sets):
        (x0, x1), (y0, y1) = s.signature.point
        sig_x[i] = be.pack_ints_vec([x0, x1])
        sig_y[i] = be.pack_ints_vec([y0, y1])
    set_mask = np.array([1] * n_real + [0] * (4 - n_real), np.uint32)
    return sig_x, sig_y, co.scalars_to_bits(PREPARE_ZS, be.Z_BITS), set_mask


@functools.lru_cache(maxsize=None)
def order_13_twist_point():
    """A point of order 13 on the twist E'(Fq2), affine: outside G2, which
    the cofactor (it holds 13^2) times r annihilates. The first x = k + u
    whose curve point survives that cofactor over 169."""
    from lighthouse_tpu.crypto.bls381 import curve as pc
    from lighthouse_tpu.crypto.bls381 import fields as f
    from lighthouse_tpu.crypto.bls381.constants import B_G2, H_G2, R

    assert H_G2 % 169 == 0
    k = 0
    while True:
        k += 1
        x = (k, 1)
        y2 = f.fq2_add(f.fq2_mul(f.fq2_sqr(x), x), B_G2)
        if not f.fq2_legendre_is_square(y2):
            continue
        t = pc.mul_raw((x, f.fq2_sqrt(y2)), H_G2 * R // 169, pc.FQ2_OPS)
        if t is not None and pc.mul_raw(t, 13, pc.FQ2_OPS) is not None:
            t = pc.mul_raw(t, 13, pc.FQ2_OPS)      # order 169 -> 13
        if t is not None:
            assert pc.mul_raw(t, 13, pc.FQ2_OPS) is None
            assert pc.is_on_curve(t, pc.FQ2_OPS)
            return t
