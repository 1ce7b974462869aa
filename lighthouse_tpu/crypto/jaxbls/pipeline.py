"""Pipelined dispatch executor for the jaxbls device path.

The latency levers this module owns (docs/PERF_NOTES.md "Pipelined
dispatch & buffer donation"):

  - **depth-bounded double-buffering**: up to `depth` batches ride the
    device queue while the host marshals the next one. bench.py proved
    "pipelined depth 4" by hand since round 2; the `PipelinedDispatcher`
    makes it the serving path — every `verify_signature_sets_async`
    submission passes through the backend's dispatcher, which blocks a
    NEW batch submission (resolving the oldest in-flight batch) only
    when the window is full. Depth resolves explicit arg > env
    (LIGHTHOUSE_TPU_PIPELINE_DEPTH) > autotune plan (`pipeline_depth`,
    measured by scripts/bench_batch_scaling.py --depths) > default 4,
    the same precedence contract as every other autotuned knob.
  - **FIFO continuation ordering**: tickets resolve in submission order
    regardless of which ticket's `.result()` is called first — device
    batches can materialize out of order (multi-stage async
    dispatch), but chain-mutating continuations must not.
  - **an urgent lane**: single-set / urgent verifies bypass the depth
    window entirely — they never wait behind queued firehose batches
    and never occupy a window slot, so a gossip block's proposer check
    is not taxed by 4 x 512-set batches in flight (the config1 p50
    lever, target < 100 ms = one slot-fraction). On a multi-chip mesh
    the lane is additionally PINNED SINGLE-CHIP (backend.py r10): plain
    pow2 buckets, whole-array placement on one device, the unsharded
    stage programs — mesh padding and collective latency never tax the
    ~ms path (`mesh_sharded_dispatch_total{lane}` counts both lanes).
  - **input-buffer donation policy**: whether the staged jit programs
    (four stages; five programs for a batch bucket on one chip) are
    built with `donate_argnums` (crypto/jaxbls/backend.py
    `_get_stages`). Donated per-batch inputs (sig/z/us/stage
    intermediates — never the cached pubkey grids) let XLA reuse their
    HBM for same-shaped intermediates instead of fresh allocations.
    Resolution: explicit > env (LIGHTHOUSE_TPU_DONATE) > platform
    default (on for accelerators, off on CPU where XLA ignores
    donation and warns).

Host-only by construction: nothing here imports jax at module level, so
the dispatcher is testable with stub handles on the python backend
(tests/test_jaxbls_pipeline.py) and `resolve_depth` is safe to call
from import-time default factories (BeaconProcessorConfig).
"""

from __future__ import annotations

import os
import threading
from collections import deque
from time import perf_counter

from ...observability import trace as _obs
from ...observability.device_ledger import LEDGER
from ...utils.metrics import REGISTRY

# ------------------------------------------------------------------ metrics
# all jaxbls_pipeline_* series are labeled families (scripts/lint_metrics.py
# enforces it): depth/donation answer "configured how, by which layer",
# inflight/submitted/resolved answer "which lane is doing the work"

_DEPTH_GAUGE = REGISTRY.gauge_vec(
    "jaxbls_pipeline_depth",
    "configured double-buffering depth of the jaxbls dispatch window, by "
    "the layer that decided it (explicit/env/profile/default)",
    ("source",),
)
_DONATE_GAUGE = REGISTRY.gauge_vec(
    "jaxbls_pipeline_donated_inputs",
    "1 = staged jit programs built with donate_argnums (per-batch input "
    "buffers reusable by XLA), by the layer that decided it",
    ("source",),
)
_INFLIGHT = REGISTRY.gauge_vec(
    "jaxbls_pipeline_inflight",
    "device batches currently in flight through the dispatcher, by lane",
    ("lane",),
)
_SUBMITTED = REGISTRY.counter_vec(
    "jaxbls_pipeline_submitted_total",
    "batches submitted through the pipelined dispatcher, by lane",
    ("lane",),
)
_RESOLVED = REGISTRY.counter_vec(
    "jaxbls_pipeline_resolved_total",
    "batches resolved by the pipelined dispatcher, by lane and outcome",
    ("lane", "outcome"),
)
_ADMIT_WAIT = REGISTRY.histogram_vec(
    "jaxbls_pipeline_admit_wait_seconds",
    "time a submission waited for a window slot (resolving the oldest "
    "in-flight batch) before dispatching, by lane — the urgent lane "
    "never waits",
    ("lane",),
    buckets=(0.0001, 0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0),
)

_DISPATCH_DEVICE = REGISTRY.histogram_vec(
    "jaxbls_dispatch_device_seconds",
    "the device's time for one dispatch as served, by lane: its verdict "
    "on the host (the end of result()'s one read, no sync of its own) "
    "less the later of its first stage's enqueue and the previous "
    "dispatch of this dispatcher read; with stage attribution on it is the serialized time, and the "
    "difference is what attribution costs",
    ("lane",),
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 30.0,
             120.0, 600.0),
)

DEFAULT_DEPTH = 4
DEPTH_CLAMP = (1, 16)


def _clamp_depth(d: int) -> int:
    lo, hi = DEPTH_CLAMP
    return max(lo, min(hi, int(d)))


def _plan():
    """The installed autotune plan, or None — never raises and never
    initializes a device (autotune/runtime.py is jax-free)."""
    try:
        from ...autotune import runtime

        return runtime.active_plan()
    except Exception:
        return None


def resolve_depth(explicit=None) -> tuple:
    """(depth, source) with the autotune precedence contract:
    explicit arg > LIGHTHOUSE_TPU_PIPELINE_DEPTH > plan.pipeline_depth >
    DEFAULT_DEPTH. Clamped to DEPTH_CLAMP at every layer."""
    if explicit is not None:
        return _clamp_depth(explicit), "explicit"
    raw = os.environ.get("LIGHTHOUSE_TPU_PIPELINE_DEPTH", "").strip()
    if raw:
        try:
            return _clamp_depth(int(raw)), "env"
        except ValueError:
            pass  # malformed env falls through to the next layer
    plan = _plan()
    depth = getattr(plan, "pipeline_depth", None) if plan is not None else None
    if depth:
        return _clamp_depth(depth), "profile"
    return DEFAULT_DEPTH, "default"


def donation_enabled(explicit=None) -> tuple:
    """(enabled, source): explicit arg > LIGHTHOUSE_TPU_DONATE env >
    platform default (accelerators donate, CPU keeps plain jits — XLA:CPU
    ignores donation and warns on every call)."""
    if explicit is not None:
        return bool(explicit), "explicit"
    env = os.environ.get("LIGHTHOUSE_TPU_DONATE", "").strip().lower()
    if env:
        return env not in ("0", "no", "off", "false"), "env"
    try:
        import jax

        return jax.default_backend() != "cpu", "platform"
    except Exception:
        return False, "platform"


# --------------------------------------------------------------- dispatcher


class PipelineTicket:
    """One submitted batch: resolves to its handle's result() value.

    `result()` preserves FIFO semantics for the batch lane — resolving
    ticket k first resolves every earlier unresolved batch-lane ticket
    (continuations included) in submission order. Urgent tickets resolve
    independently; they were never in the window. A handle/continuation
    exception is captured once and re-raised to EVERY result() caller —
    it never poisons later tickets. The ticket keeps when its first stage
    was enqueued, for the dispatch's device time."""

    __slots__ = ("_dispatcher", "lane", "handle", "continuation",
                 "done", "value", "error", "claimed", "_ev", "interval",
                 "t_enqueued")

    def __init__(self, dispatcher, lane, handle, continuation, interval=None,
                 t_enqueued=None):
        self._dispatcher = dispatcher
        self.lane = lane
        self.handle = handle
        self.continuation = continuation
        self.interval = interval       # device-ledger interval, or None
        self.t_enqueued = t_enqueued
        self.done = False
        self.value = None
        self.error = None
        self.claimed = False           # a thread owns this ticket's finish
        self._ev = threading.Event()   # set when done (cross-thread waits)

    def result(self):
        return self._dispatcher.resolve(self)


class PipelinedDispatcher:
    """Depth-bounded in-flight window over async device handles.

    submit(dispatch) runs `dispatch()` (the marshal already happened in
    the caller — host work that overlaps the device) after admitting the
    batch into the window: when `depth` batches are already in flight the
    OLDEST is resolved first, which is exactly the backpressure that
    keeps host marshal of batch k+1 overlapped with device execution of
    batch k instead of letting submissions pile up the device queue.
    Urgent submissions skip both the wait and the window."""

    def __init__(self, depth=None, donate=None, workload=None):
        self.depth, self.depth_source = resolve_depth(depth)
        self.donate, self.donate_source = donation_enabled(donate)
        # tenant identity in the process-wide device ledger: named
        # dispatchers attribute every submission's device time to their
        # workload; anonymous ones (ad-hoc tests) stay off the books
        self.workload = None if workload is None else str(workload)
        if self.workload is not None:
            LEDGER.register(self.workload, self)
        # state lock (window bookkeeping, cheap) + a reentrant resolution
        # lock serializing FIFO drains: a continuation may legally submit
        # or resolve (the processor's continuation path does both)
        self._lock = threading.Lock()
        self._resolve_lock = threading.RLock()
        self._window: deque = deque()      # batch-lane tickets, FIFO
        # admission slots claimed by submitters still inside dispatch():
        # len(window) + reserved <= depth is the invariant, so concurrent
        # batch-lane submitters can never overfill the window between the
        # admission check and the append (the condition shares _lock and
        # is notified whenever a ticket leaves the window or a
        # reservation is released)
        self._reserved = 0
        self._slot_free = threading.Condition(self._lock)
        self._urgent_inflight = 0
        self._last_ready = 0.0         # when the previous dispatch was ready
        _DEPTH_GAUGE.labels(self.depth_source).set(self.depth)
        _DONATE_GAUGE.labels(self.donate_source).set(int(self.donate))

    def set_depth(self, depth: int, source: str) -> None:
        """Live depth retune (autotune plan installed mid-run)."""
        self.depth = _clamp_depth(depth)
        self.depth_source = source
        _DEPTH_GAUGE.labels(source).set(self.depth)

    # -- submission ------------------------------------------------------

    def submit(self, dispatch, continuation=None, urgent=False,
               bucket=None, est_cost=None) -> PipelineTicket:
        """Admit + dispatch one batch. `dispatch` is a thunk performing
        the device submission and returning a handle with .result();
        `continuation(value)` (optional) runs when the ticket resolves,
        in submission order for the batch lane. `bucket`/`est_cost`
        (optional) annotate the device-ledger interval with the padding
        bucket and the cost model's estimate for this batch."""
        lane = "urgent" if urgent else "batch"
        interval = None
        if self.workload is not None:
            interval = LEDGER.open(
                self.workload, lane=lane, bucket=bucket, est_cost=est_cost
            )
        waited = 0.0
        if not urgent:
            # claim a window slot ATOMICALLY (len(window) + reserved <
            # depth) so concurrent submitters can never overfill the
            # window between this check and the post-dispatch append
            with _obs.span("jaxbls:admit") as admit:
                self._claim_slot()
            waited = admit.t1 - admit.t0
        _ADMIT_WAIT.labels(lane).observe(waited)
        if interval is not None:
            interval.start()           # admit wait over: device dispatch
        try:
            with _obs.span("jaxbls:enqueue", lane=lane) as enqueue:
                handle = dispatch()
        except BaseException:
            if interval is not None:
                interval.close("error")
            if not urgent:
                with self._slot_free:
                    self._reserved -= 1
                    self._slot_free.notify_all()
            raise
        ticket = PipelineTicket(self, lane, handle, continuation, interval,
                                t_enqueued=enqueue.t0)
        with self._lock:
            if urgent:
                self._urgent_inflight += 1
                _INFLIGHT.labels("urgent").set(self._urgent_inflight)
            else:
                self._reserved -= 1
                self._window.append(ticket)
                _INFLIGHT.labels("batch").set(len(self._window))
        _SUBMITTED.labels(lane).inc()
        return ticket

    def _claim_slot(self) -> None:
        """Block until the window has a free slot and reserve it,
        resolving the oldest in-flight batch while it is full."""
        while True:
            with self._lock:
                if len(self._window) + self._reserved < self.depth:
                    self._reserved += 1
                    return
                oldest = self._window[0] if self._window else None
            if oldest is not None:
                try:
                    self.resolve(oldest)  # blocking wait: backpressure
                except Exception:
                    # the failure belongs to the OLDEST batch and stays
                    # recorded on its ticket (its owner re-raises at
                    # result()); it must not surface into this unrelated
                    # submission
                    pass
            else:
                # every slot is a reservation held by a submitter still
                # inside dispatch(): wait for one to land
                with self._slot_free:
                    self._slot_free.wait(timeout=0.05)

    # -- resolution ------------------------------------------------------

    def resolve(self, ticket: PipelineTicket):
        """Resolve `ticket` (and, for the batch lane, every earlier
        batch-lane ticket first — FIFO). Returns the stored value or
        re-raises the stored error; idempotent."""
        if ticket.done:
            return self._outcome(ticket)
        if ticket.lane == "urgent":
            with self._lock:
                already_claimed, ticket.claimed = ticket.claimed, True
            if already_claimed:
                ticket._ev.wait()      # another thread owns the finish
                return self._outcome(ticket)
            self._finish(ticket)
            with self._lock:
                self._urgent_inflight = max(0, self._urgent_inflight - 1)
                _INFLIGHT.labels("urgent").set(self._urgent_inflight)
            return self._outcome(ticket)
        with self._resolve_lock:
            while not ticket.done:
                with self._slot_free:
                    head = self._window.popleft() if self._window else None
                    _INFLIGHT.labels("batch").set(len(self._window))
                    if head is not None:
                        self._slot_free.notify_all()
                if head is None:
                    # the ticket left the window on another thread's drain
                    # mid-check; loop re-reads done
                    if not ticket.done:  # pragma: no cover - defensive
                        self._finish(ticket)
                    break
                self._finish(head)
        return self._outcome(ticket)

    def drain(self) -> int:
        """Resolve every in-flight batch-lane ticket (shutdown/tests).
        Per-ticket errors stay on their tickets; the drain completes."""
        n = 0
        while True:
            with self._lock:
                ticket = self._window[0] if self._window else None
            if ticket is None:
                return n
            try:
                self.resolve(ticket)
            except Exception:
                pass  # recorded on the ticket; owner re-raises at result()
            n += 1

    def inflight(self) -> int:
        with self._lock:
            return len(self._window) + self._urgent_inflight

    def _finish(self, ticket: PipelineTicket) -> None:
        if ticket.done:
            return
        try:
            value = ticket.handle.result()
            # a handle that kept when its read ended (the jaxbls ones)
            # gives the dispatch its device time, no sync added
            t_ready = getattr(ticket.handle, "t_ready", None)
            if t_ready is not None:
                _DISPATCH_DEVICE.labels(ticket.lane).observe(
                    t_ready - max(ticket.t_enqueued, self._last_ready))
                self._last_ready = t_ready
            if ticket.continuation is not None:
                ticket.continuation(value)
            ticket.value = value
            outcome = "ok"
        except Exception as e:
            ticket.error = e
            outcome = "error"
        ticket.done = True
        # drop the handle/continuation refs: a resolved ticket must not
        # keep device buffers (or captured marshal inputs) alive
        ticket.handle = None
        ticket.continuation = None
        if ticket.interval is not None:
            ticket.interval.close(outcome)
            ticket.interval = None
        ticket._ev.set()
        _RESOLVED.labels(ticket.lane, outcome).inc()

    @staticmethod
    def _outcome(ticket: PipelineTicket):
        if ticket.error is not None:
            raise ticket.error
        return ticket.value
