"""Observability: pipeline span tracing + stage-timing snapshots.

The verification dataflow (gossip -> BeaconProcessor queues -> coalesced
batch -> host marshal -> device dispatch -> device wait -> continuation) is
the system's hot path; this package makes it legible from the outside:

  - `trace`: a lightweight span tracer. Every executed work unit carries a
    Trace through the pipeline stages, and every layer below the processor
    adds its phases to it through the one primitive `span` (parent, trace
    id, a profiler scope of the same name); completed traces land in a
    bounded ring and feed per-stage Prometheus histograms, and the ring
    exports as Chrome trace-event (Perfetto) JSON (`bn --trace-out
    trace.json`).
  - `pipeline`: the stage-timing snapshot behind the
    `/lighthouse_tpu/pipeline` ops endpoint.
  - `device`: per-stage device-time attribution for the jaxbls dispatch
    (named annotation scopes always; event-timed per-stage resolves +
    `device:<stage>` trace lanes under `bn --device-trace`).
  - `perf`: compiled-program analytics (`xla_program_*` gauges from XLA
    cost/memory analysis), roofline derivation, and the BENCH_r*/
    MULTICHIP_r* trend + regression gate (`bn perf report`,
    scripts/perf_trend.py).
  - `slo`: the slot-level service-level accountant — one SlotReport per
    slot-clock boundary (admitted/processed/shed per kind, deadline-hit
    ratio for TIMELY work, route share, wait/latency quantiles), rolling
    5-slot and 32-slot windows with burn-rate, `slo_*` families, the
    `/lighthouse_tpu/slo` ops endpoint and the health degraded signal.
  - `flight_recorder`: the always-on black box — a bounded ring of
    structured events (breaker transitions, shed bursts, deadline misses,
    supervisor restarts, route flips, WARN+ log records) with incident
    triggers that dump diagnosis snapshots to `datadir/incidents/` and
    render as instant markers in the Perfetto export.
  - `propagation`: cross-node causality — the wire trace context every
    gossip publish / Req-Resp request carries, per-node propagation SLIs
    (`net_propagation_seconds{topic}`, time-to-head), the
    propagation-stall incident trigger, and the deterministic cluster
    rollup the multinode/fleet reports embed.
  - `debug_bundle`: `bn debug-bundle` — one tarball of everything above
    plus `bn doctor` output and bench metadata, for offline diagnosis.

Always-on by design: recording a trace is appending a few floats to a
deque, so there is no enabled/disabled bifurcation to test — `--trace-out`
only controls whether the ring is written to disk at shutdown.
"""

from .trace import (  # noqa: F401
    PIPELINE_STAGES,
    TRACER,
    Trace,
    Tracer,
    chrome_trace_events,
    current_trace,
    set_current_trace,
    span,
)
from .pipeline import register_processor, snapshot  # noqa: F401
from . import device, perf  # noqa: F401  (registers the device/xla families)
from . import flight_recorder, slo  # noqa: F401  (registers slo_*/flight_recorder_* families + the log sink)
from . import propagation  # noqa: F401  (registers the net_* families)
from .flight_recorder import RECORDER  # noqa: F401
from .slo import ACCOUNTANT  # noqa: F401
