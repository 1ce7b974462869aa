"""Compile the staged BLS programs of a test module once, in threads.

Not a test file. The programs of the four stages (prepare, hash-to-G2,
pairs, pairing) are large at any shape: on XLA:CPU one build of them costs
minutes, and `tests/conftest.py` drops compiled executables at every file
boundary, so each module that drives the real `JaxBackend` pays for its
builds itself. The two modules that do (`test_jaxbls_backend.py`,
`test_multichip.py`; the 2-D mesh's `test_multichip_2d.py` is a third,
with a build nothing shares) call `warm_builds` from one module-scoped
fixture with exactly the builds their tests dispatch, a thread a build: XLA
releases the GIL while it compiles, so the wall cost is about one build's,
not the sum. No more threads than that: in the six-worker tier-1 run every
core is taken already, and PR 25 measured three threads a build (prepare,
hash-to-G2 and pairing side by side) at the same seconds for the module
(589-650 against 604-608) and no fewer for the files beside it.
A new test that drives the staged backend joins one of those modules
(and, if it needs a new bucket, adds it to that module's warm-up) instead
of opening a file of its own.
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
            put(np.ones((n, be.Z_DIGITS), np.uint32)),
            put(np.ones((n,), np.uint32)),
        )
    h_jac = h2c_stage(put(limbs(n, 2, 2)))
    pairs = pairs_stage(z_pk, h_jac, sig_acc, put(np.ones((n,), np.uint32)))
    jax.block_until_ready(pairing_stage(*pairs))


def warm_builds(*builds):
    """Each build is (n, ms, mesh); one thread a build."""
    run_in_threads(*(functools.partial(warm_build, *b) for b in builds))
