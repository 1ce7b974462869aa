"""QoS: admission control, overload shedding, rate limiting, circuit breaking.

PR 1 taught the node to size batches per device and PR 2 made every stage
observable; this package is the layer that *protects* the pipeline when the
measured numbers go bad. The reference client treats overload as a design
concern — a priority order of work kinds, oldest-first shedding on the
batchable gossip queues (LIFO-queue semantics in
beacon_processor/src/lib.rs:301-372), and explicit backfill rate limiting —
and this package gives the TPU port the same spine:

  - `admission`: per-WorkKind priority classes consulted by
    `BeaconProcessor.submit`, slot-deadline stamping so an attestation that
    can no longer be attested is shed at pop time (counted `expired`, not
    `dropped`), and the `qos_shed_total{kind,reason}` family that accounts
    for every lost work item.
  - `ratelimit`: deterministic token buckets wrapping the HTTP API (429 +
    Retry-After instead of unbounded queued work) and gossip ingest.
  - `breaker`: a closed/open/half-open circuit breaker formalizing the
    hybrid BLS router's device-health handling; a stalled device degrades
    to the host path within one budget window, and recovery is probe-driven
    (`bls_device_circuit_state`).

The companion `lighthouse_tpu/loadgen` package proves all of it under
synthetic mainnet-shaped floods and injected faults.

Importing this package imports every submodule so the global metrics
registry is fully populated (scripts/lint_metrics.py relies on that).
"""

from .admission import (  # noqa: F401
    ATTESTATION_PROPAGATION_SLOT_RANGE,
    AdmissionController,
    PriorityClass,
    SHED_TOTAL,
    count_shed,
)
from .breaker import CLOSED, HALF_OPEN, OPEN, CircuitBreaker  # noqa: F401
from .ratelimit import RateLimiter, TokenBucket  # noqa: F401
