#!/bin/bash
# Populate the jax compile cache for the test suite ($JAX_COMPILATION_CACHE_DIR
# when set, else <repo>/.jax_cache — lighthouse_tpu/utils/jaxcfg.py).
#
# pytest runs are cache-READ-ONLY by default (see tests/conftest.py: the
# XLA:CPU executable serializer can segfault when writing entries late in a
# long run). This script enables writes and loops until the suite survives
# a full pass — each attempt extends the cache, so it converges quickly;
# afterwards normal `pytest tests/` runs are fast and crash-free.
set -u
cd "$(dirname "$0")/.."
for attempt in 1 2 3 4 5; do
  echo "=== warming pass $attempt ==="
  LIGHTHOUSE_TPU_CACHE_WRITE=1 python -m pytest tests/ -q
  rc=$?
  if [ $rc -eq 0 ]; then
    echo "suite green with warm cache after $attempt pass(es)"
    exit 0
  fi
  echo "pass $attempt exited rc=$rc (cache extended; retrying)"
done
exit 1
