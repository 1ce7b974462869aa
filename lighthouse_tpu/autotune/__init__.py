"""Autotune: device profiler + adaptive batch planner for the BLS pipeline.

The repo's serving knobs were guessed once and hard-coded: the beacon
processor's batch caps (chain/beacon_processor.py), the hybrid router's
p99 budget and urgent-set threshold (crypto/bls/hybrid.py), and the jaxbls
padding buckets (crypto/jaxbls/backend.py). Those numbers are valid for
exactly one device. This subsystem closes the measure -> plan -> act loop:

  - `profiler`  — lightweight per-bucket timing hooks around the jaxbls
    dispatch (compile time, dispatch latency, achieved sets/sec), exported
    through the process metrics registry AND kept in memory;
  - `profile`   — a versioned JSON device profile (keyed by device kind +
    jax version + backend revision) persisted next to the jit cache so a
    restarted node skips re-learning;
  - `calibrate` — the offline sweep that measures each padding bucket and
    writes the profile (scripts/autotune_calibrate.py, `autotune
    calibrate` CLI);
  - `planner`   — pure, deterministic derivation of the serving knobs and
    a startup warmup plan from a profile;
  - `runtime`   — process-global installed profile/plan, disk autoload,
    and the background warmup thread that precompiles the planned buckets
    via jaxbls `warm_stages` at node bring-up.

Import cost: this package and its submodules import only the stdlib and
`utils.metrics`; jax / numpy / fixtures are imported lazily inside the
functions that need them, so consulting the planner from hot paths
(BeaconProcessorConfig defaults, HybridBackend construction) is cheap and
can never block on a device.
"""

from . import planner, profile, profiler, runtime  # noqa: F401

__all__ = ["calibrate", "planner", "profile", "profiler", "runtime"]
