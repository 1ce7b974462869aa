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


# Files that need the most seconds on their worker, longest first, with the
# seconds each took on its worker in a six-worker tier-1 run on the 8-core
# sandbox beside it (PR 46's final tree: 1,381 s in all, on a night when the
# sandbox ran PR 45's tree in 1,453 s and the driver's machine had run it in
# 1,132). Their items move to the front of the collection so
# `--dist loadfile` hands them out at t = 0 and the ~1000 light tests fill
# in behind: the run is then bounded by about max(longest file, total /
# workers). A file that needs more than 300 s on its worker belongs here.
# xdist hands a worker its next file while the last two tests of its current
# one are still pending, so the worker of a one- or two-test file among the
# first six takes the seventh file on at t = 0 and runs it after minutes of
# compiling: keep a short file in seventh place.
_LONGEST_FIRST = (
    "test_jaxbls_backend.py",           # 1091 (574 alone)
    "test_jaxbls_pairing.py",           # 932
    "test_ef_vectors.py",               # 882
    "test_multichip.py",                # 856
    "test_jaxbls_registry.py",          # 826 (486 alone: eight one-device programs)
    "test_kzg.py",                      # 644 beside the five above from t = 0 (394 alone)
    "test_fleet.py",                    # 208 (seventh: the short one)
    "test_jaxbls_key_grids.py",         # 455 (293 alone: five one-device programs)
    "test_beacon_chain.py",             # 325
    "test_jaxbls_h2c.py",               # 248
    "test_jaxbls_msm.py",               # 171
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
