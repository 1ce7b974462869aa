"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Whatever platform the environment selects, the tests run on the CPU: the
device-count flag and jax.config are both set before any backend is
initialized. Sharding/multi-chip tests then run on 8 virtual CPU devices
anywhere; the chip itself is reached through chip_smoke.py (README
"Running").
"""

import os

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
    config.addinivalue_line("markers", "slow: long multi-node simulations")


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


@pytest.fixture(autouse=True)
def _release_executables_near_map_limit():
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to measure
        return
    if n_maps > _MAP_COUNT_HIGH_MARK:
        _release_executables()
