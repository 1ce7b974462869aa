"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Whatever platform the environment selects, the tests run on the CPU: the
device-count flag and jax.config are both set before any backend is
initialized. Sharding/multi-chip tests then run on 8 virtual CPU devices
anywhere; the chip itself is reached through chip_smoke.py (README
"Running").
"""

import faulthandler
import os
import signal

import pytest

# Must be set before the CPU backend initializes (no backend is initialized
# yet at conftest import time).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

from lighthouse_tpu.utils.jaxcfg import setup_compilation_cache

setup_compilation_cache()

# Under pytest the persistent cache is READ-ONLY by default: XLA:CPU's
# executable serializer intermittently segfaults when writing cache entries
# late in a long multi-program process (observed at jax 0.9.0 in
# compilation_cache.put_executable_and_time after ~150 compiled programs;
# standalone compiles of the same programs never crash). Warming runs opt
# back in with LIGHTHOUSE_TPU_CACHE_WRITE=1 (scripts/warm_test_cache.sh) —
# re-run until green; each pass extends the cache, normal runs only read.
if os.environ.get("LIGHTHOUSE_TPU_CACHE_WRITE") != "1":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 10**9)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-node simulations, and compiles of many minutes"
    )
    # xdist's loadfile scheduler hands files out by their number of tests,
    # most first, unless told otherwise — which puts the compile-heavy
    # files (5-7 tests each) at the very end of the run. Hand out in
    # collection order instead: _longest_first below decides it.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


# The order in which `--dist loadfile` hands the heavy files to the six
# workers, with the seconds each took on its worker (and its process's CPU
# seconds) in a whole tier-1 run on the 8-core sandbox (PR 47's tree: 973 s in
# all; tests/README.md has the runs). Their items move to the front of the
# collection, the ~1000 light tests fill in behind, and the run is bounded by
# about max(longest file, total / workers). First the file the run waits
# for, then five files of ONE busy thread each: the backend module's warm-up
# traces in Python (the GIL) and compiles in two threads, and beside two more
# warm-ups of four and six threads on these eight cores it took 703 s where
# it takes 387 alone. The other compile modules start when the first of the
# five ends, early enough to end before the backend module does. A file that
# needs more than 300 s on its worker belongs here; none but the first, whose
# seconds are the run's, may need more than 750 s. xdist hands a worker its
# next file two tests before the end: no one- or two-test file in the first six.
_LONGEST_FIRST = (
    "test_jaxbls_backend.py",           # 959 (816 alone; CPU 2008: two builds)
    "test_ef_vectors.py",               # 608 (434 alone; CPU 336: pure Python)
    "test_kzg.py",                      # 442 (CPU 456)
    "test_beacon_chain.py",             # 276 (CPU 195)
    "test_fleet.py",                    # 193 (CPU 90)
    "test_jaxbls_curve.py",             # 196 (CPU 298)
    "test_jaxbls_registry.py",          # 619 (559 alone; CPU 1172: nine programs)
    "test_jaxbls_pairing.py",           # 517 (385 alone; CPU 669: six programs)
    "test_chip_compile.py",             # 379 (CPU 722: the TPU compiler's threads)
    "test_jaxbls_msm.py",               # 194
)


def _longest_first(items, names=_LONGEST_FIRST):
    """`items` with those of the files in `names` first, in the order of
    `names`; order inside a file, and of every other item, is kept."""
    rank = {name: i for i, name in enumerate(names)}

    def key(item):
        file_name = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
        return rank.get(file_name, len(rank))

    return sorted(items, key=key)  # stable


def pytest_collection_modifyitems(config, items):
    items[:] = _longest_first(items)


# Every XLA:CPU executable a process keeps costs memory mappings, and the
# kernel's vm.max_map_count is 65,530 here. One jaxbls file alone takes a
# fresh process to 50,000 (its eight-device programs), so a worker of the
# six-worker tier-1 run that enters such a file with the executables of the
# files before it reaches the limit: mmap fails inside the next compile and
# the worker dies of SIGSEGV or SIGABRT in backend_compile_and_load (PR 22
# watched workers die at 64,037 and 64,352 and survive at 63,968; which
# test it hits depends on which files happened to share a worker). So every
# file hands its worker on without compiled executables (jit recompiles on
# demand), and inside a file a process past the mark below drops them too:
# recompiling is cheaper than a dead worker.
_MAP_COUNT_HIGH_MARK = 50_000


def _release_executables() -> None:
    import gc

    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module", autouse=True)
def _release_executables_between_files():
    yield
    _release_executables()


def _n_memory_mappings() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to measure
        return 0


@pytest.fixture(autouse=True)
def _release_executables_near_map_limit():
    yield
    if _n_memory_mappings() > _MAP_COUNT_HIGH_MARK:
        _release_executables()


# No test of tier-1 needs minutes once its module's programs are compiled,
# so a test that is still running after this long is waiting on something
# that will not come; without a limit it keeps its worker until the
# driver's outer clock cuts the whole run. One limit, no marker raises it.
_TEST_LIMIT_S = 600


@pytest.fixture(autouse=True)
def _per_test_time_limit(request):
    def _on_alarm(signum, frame):
        pytest.fail(
            f"{request.node.nodeid} ran past {_TEST_LIMIT_S} s", pytrace=False
        )

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    # every thread's stack, a moment before the failure is raised
    faulthandler.dump_traceback_later(_TEST_LIMIT_S - 1, exit=False)
    signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, prev)
