"""BeaconProcessor — priority work scheduler with gossip batch coalescing.

Parity surface: /root/reference/beacon_node/beacon_processor/src/lib.rs —
the Work queue kinds (:549-658), bounded FIFO/LIFO queues per kind
(:301-372), explicit priority order (:955-1090), and the dynamic coalescing
of queued gossip attestations/aggregates into batch work items
(:970-1087) — and, unlike the reference, of a block's queued blob sidecars
into one KZG batch (a dispatch here costs the same whatever it carries).
That coalescing is the upstream feeder for the TPU backend:
the reference caps batches at 64 because CPU batch verification saturates;
here the default batch caps are sized for chip occupancy instead
(DEFAULT_MAX_*_BATCH), and the scheduler drains widest-first.

Threading model: unlike the reference's tokio worker pool, this scheduler
is a synchronous priority queue pumped by a small thread pool — Python's
GIL makes many workers pointless, but the heavy work (device batches,
native store IO, sha256) all releases the GIL or runs on device, so a few
workers suffice. Determinism-first: `run_until_idle` drains synchronously
for tests (manual time), `start`/`stop` run the pump in threads.

Observability: every queue is a labeled Prometheus series (the reference's
beacon_processor_*_queue_total idiom) and every executed work unit carries
a Trace through the pipeline stages — enqueue (submit -> pop), coalesce
(batch formation), marshal (runner execution, which for device batches is
host marshal + async dispatch), device (handle wait), continuation (chain
mutation). See lighthouse_tpu/observability. The per-batch overhead is a
few dict lookups + histogram observes; nothing here blocks on a scrape.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from dataclasses import dataclass, field
from enum import IntEnum
from time import perf_counter
from typing import Callable

from ..observability import slo as obs_slo
from ..observability import trace as obs
from ..qos.admission import count_shed
from ..utils.logging import get_logger
from ..utils.metrics import (
    REGISTRY,
    SIGNATURE_BATCH_SIZE,
    SIGNATURE_VERIFY_TIME,
)

log = get_logger("beacon_processor")


class WorkKind(IntEnum):
    """Priority order, highest first (lib.rs:955-1090 ordering)."""

    chain_reprocess = 0
    gossip_block = 1
    # directly behind the block, as the reference's GossipBlobSidecar: the
    # block cannot import before its sidecars' proofs have verified
    gossip_blob_sidecar = 2
    api_request_p0 = 3
    gossip_aggregate = 4
    gossip_attestation = 5
    gossip_sync_contribution = 6
    gossip_sync_signature = 7
    rpc_block = 8
    chain_segment = 9
    api_request_p1 = 10
    gossip_voluntary_exit = 11
    gossip_proposer_slashing = 12
    gossip_attester_slashing = 13
    gossip_bls_change = 14
    backfill_segment = 15


DEFAULT_MAX_ATTESTATION_BATCH = 1024   # reference default 64; sized for TPU
DEFAULT_MAX_AGGREGATE_BATCH = 512

# ------------------------------------------------------------------ metrics
# labeled per-kind families (beacon_processor/src/metrics.rs analog: the
# reference exports one gauge per queue; here one family with a kind label)

_QUEUE_DEPTH = REGISTRY.gauge_vec(
    "beacon_processor_queue_depth",
    "work items currently queued, by work kind",
    ("kind",),
)
_DROPPED = REGISTRY.counter_vec(
    "beacon_processor_dropped_total",
    "work items dropped because their queue was full, by work kind",
    ("kind",),
)
_PROCESSED = REGISTRY.counter_vec(
    "beacon_processor_processed_total",
    "work items executed, by work kind",
    ("kind",),
)
_QUEUE_WAIT = REGISTRY.histogram_vec(
    "beacon_processor_queue_wait_seconds",
    "submit-to-pop latency of the oldest item in each executed work unit",
    ("kind",),
    buckets=(0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0, 30.0),
)
_EXEC_LOCK_WAIT = REGISTRY.histogram(
    "beacon_processor_exec_lock_wait_seconds",
    "time spent waiting for the chain-mutation exec lock",
    buckets=(0.00001, 0.0001, 0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
)
_INFLIGHT = REGISTRY.gauge(
    "beacon_processor_inflight_batches",
    "device verification batches currently in flight",
)
_BATCHES_FORMED = REGISTRY.counter(
    "beacon_processor_batches_formed_total",
    "coalesced multi-item batches formed by the scheduler",
)
_ERRORS = REGISTRY.counter_vec(
    "beacon_processor_errors_total",
    "work unit failures swallowed by the pump, by pipeline stage",
    ("stage",),
)


def _planned(attr: str, default: int) -> int:
    """Batch cap from the installed autotune plan, else the hard-coded
    default — with no profile installed the config is byte-identical to
    the pre-autotune constants (lighthouse_tpu/autotune/planner.py)."""
    try:
        from ..autotune import runtime

        plan = runtime.active_plan()
        if plan is not None:
            return int(getattr(plan, attr))
    except Exception:
        pass
    return default


def _pipeline_depth() -> int:
    """Default in-flight window: the jaxbls pipeline depth resolution
    (env LIGHTHOUSE_TPU_PIPELINE_DEPTH > autotune plan > 4). jax-free —
    crypto/jaxbls/pipeline.py imports nothing device-side at module
    level — and never raises into config construction."""
    try:
        from ..crypto.jaxbls.pipeline import resolve_depth

        return int(resolve_depth()[0])
    except Exception:
        return 4
DEFAULT_QUEUE_LENGTHS = {
    WorkKind.gossip_attestation: 16384,
    WorkKind.gossip_aggregate: 4096,
    WorkKind.gossip_block: 1024,
    WorkKind.rpc_block: 1024,
    WorkKind.chain_segment: 64,
    WorkKind.backfill_segment: 64,
}
DEFAULT_QUEUE_LEN = 1024


@dataclass
class WorkItem:
    kind: WorkKind
    run: Callable[[], None] | None = None
    # batchable items carry a payload + a batch runner instead
    payload: object = None
    run_batch: Callable[[list], None] | None = None
    # stamped by submit(): feeds the queue-wait histogram + enqueue span
    t_enq: float = 0.0
    # QoS (lighthouse_tpu/qos): last slot at which this work still matters;
    # checked at pop time against the admission controller's slot clock
    deadline_slot: int | None = None
    # called with the shed reason ("queue_full" / "expired" / "admission")
    # when the item is lost — the gossip layer resolves its deferred
    # validation slot here so shed work never strands a PENDING entry
    on_shed: Callable[[str], None] | None = None


@dataclass
class BeaconProcessorConfig:
    # default caps consult the installed autotune plan (device-measured
    # throughput knee) and fall back to the guessed constants; an explicit
    # value (CLI --max-*-batch) always wins over both — AND pins the cap
    # against the capacity scheduler's runtime retuning (None auto-resolves
    # and stays retunable, a number self-describes as explicit, the same
    # contract max_inflight established in r8)
    max_attestation_batch: int | None = None
    max_aggregate_batch: int | None = None
    max_attestation_batch_explicit: bool = False
    max_aggregate_batch_explicit: bool = False
    # cores-wide like the reference's pool (beacon_processor/src/lib.rs:732
    # sizes by num_cpus); capped — beyond a few workers the Python-side
    # share of each task stops scaling under the GIL
    num_workers: int = field(
        default_factory=lambda: max(2, min(8, os.cpu_count() or 2))
    )
    # max device batches in flight before the pump blocks on the oldest —
    # the double-buffering depth (SURVEY §7 step 2: host marshals batch N+1
    # while the device verifies batch N). None (the default) auto-resolves
    # through the jaxbls dispatcher's depth resolution (env > autotune
    # plan > default 4) so the processor window and the backend window
    # agree, AND keeps re-resolving on runtime profile installs via the
    # processor's plan listener. Passing a NUMBER pins it: explicitness
    # is self-describing (__post_init__ flips max_inflight_explicit), so
    # a caller constructing BeaconProcessorConfig(max_inflight=2) is
    # never clobbered by a later plan install.
    max_inflight: int | None = None
    max_inflight_explicit: bool = False
    # the capacity scheduler (chain/scheduler.py) publishes its retuned
    # knobs process-wide through the autotune plan-listener contract only
    # when this is set (the live bn node path; in-process harnesses with
    # several processors keep actuation per-instance)
    scheduler_publish_plan: bool = False

    def __post_init__(self):
        if self.max_inflight is None:
            self.max_inflight = _pipeline_depth()
        else:
            self.max_inflight_explicit = True
        if self.max_attestation_batch is None:
            self.max_attestation_batch = _planned(
                "max_attestation_batch", DEFAULT_MAX_ATTESTATION_BATCH
            )
        else:
            self.max_attestation_batch_explicit = True
        if self.max_aggregate_batch is None:
            self.max_aggregate_batch = _planned(
                "max_aggregate_batch", DEFAULT_MAX_AGGREGATE_BATCH
            )
        else:
            self.max_aggregate_batch_explicit = True


class BeaconProcessor:
    # blob sidecars coalesce to at most a block's worth, a cap the
    # scheduler holds fixed (scheduler.FIXED_CAPS); the other two retune
    BATCHABLE = (WorkKind.gossip_blob_sidecar, WorkKind.gossip_attestation,
                 WorkKind.gossip_aggregate)

    def __init__(self, config: BeaconProcessorConfig | None = None,
                 admission=None):
        self.config = config or BeaconProcessorConfig()
        # QoS admission controller (lighthouse_tpu/qos/admission.py) — when
        # None, submit/pop behave exactly like the pre-QoS processor except
        # for the oldest-first shed on full batchable queues
        self.admission = admission
        self.queues: dict[WorkKind, deque] = {k: deque() for k in WorkKind}
        self.max_lengths = {
            k: DEFAULT_QUEUE_LENGTHS.get(k, DEFAULT_QUEUE_LEN) for k in WorkKind
        }
        self.dropped: dict[WorkKind, int] = {k: 0 for k in WorkKind}
        self.expired: dict[WorkKind, int] = {k: 0 for k in WorkKind}
        self.shed_admission: dict[WorkKind, int] = {k: 0 for k in WorkKind}
        self.processed: dict[WorkKind, int] = {k: 0 for k in WorkKind}
        self.batches_formed = 0
        self.pipelined_batches = 0
        # per-kind metric children resolved ONCE: the hot path pays a plain
        # dict lookup per event, never a family lock
        self._m_depth = {k: _QUEUE_DEPTH.labels(k.name) for k in WorkKind}
        self._m_dropped = {k: _DROPPED.labels(k.name) for k in WorkKind}
        self._m_processed = {k: _PROCESSED.labels(k.name) for k in WorkKind}
        self._m_wait = {k: _QUEUE_WAIT.labels(k.name) for k in WorkKind}
        # in-flight device submissions: (handle, continuation, trace) FIFO
        self._inflight: deque = deque()
        self._lock = threading.Lock()
        # Serializes chain-mutating execution (runners + continuations)
        # across workers: without it two workers could concurrently mutate
        # observed-* caches / naive pools / fork-choice votes that the
        # gossip path otherwise serializes. Device waits (handle.result())
        # deliberately happen OUTSIDE this lock so workers still overlap
        # host marshalling with device verification.
        self._exec_lock = threading.RLock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # capacity scheduler (chain/scheduler.py): owns batch formation —
        # _pop_locked delegates the dispatch-vs-coalesce verdict and the
        # live batch caps to it — and closes the control loop by retuning
        # caps/watermarks/urgent threshold from the SLO slot reports
        from .scheduler import CapacityScheduler

        self.scheduler = CapacityScheduler(
            self.config, admission=self.admission,
            publish_plan=self.config.scheduler_publish_plan,
        )
        # slot-level SLO accountant (observability/slo.py): every admit /
        # shed / processed / queue-wait lands in the current slot's report.
        # Defaults to the node's global accountant; loadgen swaps in a
        # private instance so scenario reports stay seed-deterministic.
        # (Property setter: the scheduler's control loop follows the swap.)
        self.slo = obs_slo.ACCOUNTANT
        from ..observability import register_processor

        register_processor(self)
        # live retune (r8): a mesh-aware autotune profile installed
        # mid-run re-resolves the in-flight window through the same plan
        # listener contract the jaxbls dispatcher and the hybrid router
        # use — unless the operator pinned --max-inflight-batches. A
        # broken autotune import must never take down the processor, but
        # it must be LOUD (the PR 9 no-silent-except rule): a node whose
        # plan listener silently failed to register would serve stale
        # knobs forever with nothing to show for it.
        try:
            from ..autotune import runtime as _at_runtime

            _at_runtime.add_plan_listener(self._on_plan_installed)
            _at_runtime.add_plan_listener(self.scheduler.on_plan_installed)
        except Exception as e:
            _ERRORS.labels("plan_listener").inc()
            log.warn(
                "autotune plan-listener registration failed; runtime "
                "retunes disabled for this processor",
                error=f"{type(e).__name__}: {e}",
            )

    @property
    def slo(self):
        return self._slo

    @slo.setter
    def slo(self, accountant) -> None:
        """Swapping the accountant (loadgen's private per-run instance)
        re-binds the scheduler's control-loop tick to the new one."""
        self._slo = accountant
        self.scheduler.bind_slo(accountant)

    def _on_plan_installed(self, _plan) -> None:
        if self.config.max_inflight_explicit:
            return
        self.config.max_inflight = _pipeline_depth()

    # ------------------------------------------------------------- submit

    def submit(self, item: WorkItem) -> bool:
        """Enqueue; returns False if the item was refused (already past its
        slot deadline, admission class over its watermark, or a full
        non-batchable queue). A full BATCHABLE
        queue sheds its OLDEST entry instead and admits the incoming item —
        the reference's LIFO-queue semantics for gossip attestations
        (beacon_processor/src/lib.rs:301-372): under flood, fresher work has
        strictly more propagation value than work already going stale. The
        `dropped` counter stays accurate either way: one item is lost per
        over-full submit, it is just not always the incoming one."""
        item.t_enq = perf_counter()
        kind = item.kind
        shed = None           # (item, reason) resolved outside the lock
        accepted = False
        with self._lock:
            q = self.queues[kind]
            cap = self.max_lengths[kind]
            if self.admission is not None and self.admission.is_expired(item):
                # dead on arrival (stale replay past its window): shed the
                # INCOMING item as expired — it must never take a queue
                # slot, and above all never displace live work via the
                # oldest-first branch below
                self.expired[kind] += 1
                shed = (item, "expired")
            elif self.admission is not None and not self.admission.admit(
                kind, len(q), cap
            ):
                self.shed_admission[kind] += 1
                shed = (item, "admission")
            elif len(q) >= cap:
                self.dropped[kind] += 1
                self._m_dropped[kind].inc()
                if kind in self.BATCHABLE and q:
                    shed = (q.popleft(), "queue_full")
                    q.append(item)
                    accepted = True
                else:
                    shed = (item, "queue_full")
            else:
                q.append(item)
                accepted = True
            self._m_depth[kind].set(len(q))
        # shed bookkeeping outside self._lock: on_shed re-enters the gossip
        # layer (report_validation_result takes the gossipsub lock)
        if shed is not None:
            self._notify_shed(shed[0], shed[1])
        if accepted:
            self.slo.record_admitted(kind.name)
            self._wake.set()
        return accepted

    def _notify_shed(self, item: WorkItem, reason: str) -> None:
        count_shed(item.kind.name, reason)
        self.slo.record_shed(item.kind.name, reason)
        if item.on_shed is not None:
            try:
                item.on_shed(reason)
            except Exception as e:  # shed callbacks must never kill a caller
                _ERRORS.labels("shed_callback").inc()
                log.error("on_shed callback failed", kind=item.kind.name,
                          error=f"{type(e).__name__}: {e}")

    # ------------------------------------------------------------- drain

    def _next_work(self, force: bool = False):
        """Pop the highest-priority work; coalesce batchable kinds.
        Returns (single, batch, trace) — the trace carries the enqueue and
        coalesce spans of whatever was popped. Items whose slot deadline
        has passed are shed HERE, counted `expired` (they already paid
        their queue residency; running them now would burn a device batch
        slot on unactionable work). Batch FORMATION is the capacity
        scheduler's call (chain/scheduler.py): a batchable queue may be
        HELD to coalesce wider; `force=True` (run_until_idle, drain, the
        worker's post-wait pass) overrides coalesce-holds so held work is
        never starved — only a harness budget gate outlasts force."""
        expired: list[WorkItem] = []
        try:
            with self._lock:
                return self._pop_locked(expired, force)
        finally:
            # self.expired was bumped under the lock (workers race here);
            # only the metric + callback run outside it
            for it in expired:
                self._notify_shed(it, "expired")

    def _pop_locked(self, expired: list, force: bool = False):
        adm = self.admission
        for kind in WorkKind:
            q = self.queues[kind]
            if not q:
                continue
            t_pop = perf_counter()
            if kind in self.BATCHABLE:
                decision = self.scheduler.decide(
                    kind, len(q),
                    inflight=len(self._inflight),
                    max_inflight=self.config.max_inflight,
                    force=force,
                )
                if not decision.dispatch:
                    continue   # held to coalesce; lower priorities may run
                cap = decision.cap
                items = []
                while q and len(items) < cap:
                    it = q.popleft()
                    if adm is not None and adm.is_expired(it):
                        self.expired[kind] += 1
                        expired.append(it)
                        continue
                    items.append(it)
                self._m_depth[kind].set(len(q))
                if not items:
                    continue   # whole queue had expired; try the next kind
                trace = self._begin_trace(kind, items[0], len(items), t_pop)
                if len(items) == 1:
                    return items[0], None, trace
                self.batches_formed += 1
                _BATCHES_FORMED.inc()
                return None, items, trace
            item = None
            while q:
                it = q.popleft()
                if adm is not None and adm.is_expired(it):
                    self.expired[kind] += 1
                    expired.append(it)
                    continue
                item = it
                break
            self._m_depth[kind].set(len(q))
            if item is None:
                continue       # whole queue had expired; try the next kind
            trace = self._begin_trace(kind, item, 1, t_pop)
            return item, None, trace
        return None, None, None

    def _begin_trace(self, kind, oldest: WorkItem, n: int, t_pop: float):
        """Trace for one popped work unit: the enqueue span covers the
        OLDEST item's queue residency (== the max wait in the unit), the
        coalesce span the pop/batch-form step itself."""
        self._m_wait[kind].observe(t_pop - oldest.t_enq)
        self.slo.record_queue_wait(kind.name, t_pop - oldest.t_enq)
        # sample the per-kind queue-depth gauges into the tracer's counter
        # ring: the Chrome trace export renders them as counter rows
        # ("ph": "C") so backlog is visible next to the pipeline spans
        obs.TRACER.sample_counters(
            "queue_depth",
            {k.name: g.value for k, g in self._m_depth.items()},
        )
        trace = obs.TRACER.begin(kind.name, n)
        trace.add_span("enqueue", oldest.t_enq, t_pop)
        trace.add_span("coalesce", t_pop, perf_counter(), items=n)
        return trace

    def _execute(self, single, batch, trace=None) -> None:
        obs.set_current_trace(trace)
        with obs.span("exec_lock_wait", trace) as waited:
            self._exec_lock.acquire()
        _EXEC_LOCK_WAIT.observe(waited.t1 - waited.t0)
        try:
            with obs.span("marshal", trace) as marshalled:
                if batch is not None:
                    kind = batch[0].kind
                    runner = batch[0].run_batch
                    payloads = [it.payload for it in batch]
                    result = runner(payloads)
                elif single is not None:
                    kind = single.kind
                    if single.run is not None:
                        result = single.run()
                    elif single.run_batch is not None:
                        result = single.run_batch([single.payload])
                    else:
                        result = None
                else:
                    return
        finally:
            obs.set_current_trace(None)
            self._exec_lock.release()
        n = len(batch) if batch is not None else 1
        self.processed[kind] += n
        self._m_processed[kind].inc(n)
        self.slo.record_processed(kind.name, n)
        self._handle_result(result, trace, kind, n, marshalled.t0)

    def _handle_result(self, result, trace=None, kind=None, n=1,
                       t_run=None) -> None:
        """A runner may return (handle, continuation): the device batch is
        in flight and the continuation runs when it resolves. The pump keeps
        pulling (and marshalling) new work while up to max_inflight device
        batches verify — the host/device overlap the reference gets from
        its worker pool (beacon_processor/src/lib.rs:732-1100). `t_run`
        is when the runner was entered: the batch's verify time counts
        from there to the verdict (bls_batch_verify_seconds)."""
        if (
            isinstance(result, tuple)
            and len(result) == 2
            and hasattr(result[0], "result")
            and callable(result[1])
        ):
            with self._lock:
                self._inflight.append(
                    (result[0], result[1], trace, kind, n, t_run))
                self.pipelined_batches += 1
                _INFLIGHT.set(len(self._inflight))
                over = len(self._inflight) > self.config.max_inflight
            if over:
                self._resolve_oldest()
        else:
            # no device leg: the work completed inside the marshal span
            obs.TRACER.finish(trace)

    def _resolve_oldest(self) -> bool:
        with self._lock:
            if not self._inflight:
                return False
            handle, cont, trace, kind, n, t_run = self._inflight.popleft()
            _INFLIGHT.set(len(self._inflight))
        # the unit's trace is current again while its handle resolves and
        # its continuation runs, on whichever worker got here: what they
        # call below (a fallback verify) joins the unit's spans
        outer = obs.current_trace()
        obs.set_current_trace(trace)
        try:
            return self._resolve(handle, cont, trace, kind, n, t_run)
        finally:
            obs.set_current_trace(outer)

    def _resolve(self, handle, cont, trace, kind, n, t_run=None) -> bool:
        # a device failure mid-batch (device lost) must never kill the pump
        # worker: the batch is lost (its deferred gossip validations expire
        # as ignores) but the node keeps verifying
        try:
            with obs.span("device", trace) as waited:
                res = handle.result()  # device wait: outside the exec lock
        except Exception as e:
            _ERRORS.labels("device").inc()
            log.error(
                "device batch failed; batch dropped",
                error=f"{type(e).__name__}: {e}",
            )
            obs.TRACER.finish(trace)
            return True
        dev_secs = waited.t1 - waited.t0
        self.slo.record_verify_latency(dev_secs)
        if t_run is not None and kind is not WorkKind.gossip_blob_sidecar:
            # runner entered (marshal, dispatch, the wait behind batches in
            # flight) -> the verdict read: what one signature batch took
            SIGNATURE_VERIFY_TIME.observe(waited.t1 - t_run)
            SIGNATURE_BATCH_SIZE.observe(n)
        if kind is not None and kind in self.BATCHABLE:
            # the scheduler's batch cost model learns from DEVICE resolves
            # only (host-path wall time must not steer device batch sizing)
            self.scheduler.observe_verify(kind.name, n, dev_secs)
        try:
            with obs.span("continuation", trace), self._exec_lock:
                cont(res)              # chain mutation: serialized
        except Exception as e:
            _ERRORS.labels("continuation").inc()
            log.error(
                "batch continuation failed",
                error=f"{type(e).__name__}: {e}",
            )
        obs.TRACER.finish(trace)
        return True

    def drain_inflight(self) -> int:
        n = 0
        while self._resolve_oldest():
            n += 1
        return n

    def run_until_idle(self) -> int:
        """Synchronously drain everything (test/deterministic mode).
        Forced passes override the scheduler's coalesce-holds — only a
        harness budget gate (loadgen/capacity.py) outlasts force, and a
        gate-held queue counts as idle here (run_available is the pump
        that respects it)."""
        n = 0
        while True:
            single, batch, trace = self._next_work(force=True)
            if single is None and batch is None:
                n += self.drain_inflight()
                if self.queues_empty() or self._only_gated():
                    return n
                continue
            self._execute(single, batch, trace)
            n += 1

    def _only_gated(self) -> bool:
        """True when everything still queued is held by a scheduler
        budget gate: a forced pump must return instead of spinning."""
        if self.scheduler._budget_gate is None:
            return False
        with self._lock:
            if self._inflight:
                return False
            return all(
                (not q) or k in self.BATCHABLE
                for k, q in self.queues.items()
            ) and any(q for q in self.queues.values())

    def run_available(self) -> int:
        """Pump only what the scheduler releases (no force): held batches
        stay queued to coalesce — the capacity harness's per-slot drive,
        where a device-time budget gate carries backlog across slots."""
        n = 0
        while True:
            single, batch, trace = self._next_work()
            if single is None and batch is None:
                self.drain_inflight()
                single, batch, trace = self._next_work()
                if single is None and batch is None:
                    return n
            self._execute(single, batch, trace)
            n += 1

    def drain(self, timeout: float = 5.0) -> bool:
        """Graceful-shutdown drain: finish queued + in-flight work within
        `timeout` seconds. With the worker pool running it waits for the
        pump to empty the queues; without (synchronous/test mode) it pumps
        inline. Returns True when everything drained — False means the
        deadline hit with work still queued (the caller sheds it by
        stopping; queued gossip items resolve via on_shed at GC, and the
        deadline bounds how long SIGTERM can hang)."""
        import time as _time

        deadline = perf_counter() + max(0.0, timeout)
        if self._threads:
            self._wake.set()
            while perf_counter() < deadline:
                if self.queues_empty():
                    return True
                _time.sleep(0.005)
            return self.queues_empty()
        while perf_counter() < deadline:
            single, batch, trace = self._next_work(force=True)
            if single is None and batch is None:
                self.drain_inflight()
                if self.queues_empty():
                    return True
                continue
            self._execute(single, batch, trace)
        return self.queues_empty()

    def queues_empty(self) -> bool:
        with self._lock:
            return all(not q for q in self.queues.values()) and not self._inflight

    def stats(self) -> dict:
        """Live scheduler state for /lighthouse_tpu/pipeline snapshots."""
        with self._lock:
            queued = {
                k.name: len(q) for k, q in self.queues.items() if q
            }
            inflight = len(self._inflight)
        return {
            "queued": queued,
            "inflight_batches": inflight,
            "max_inflight": self.config.max_inflight,
            "batches_formed": self.batches_formed,
            "pipelined_batches": self.pipelined_batches,
            "processed": {
                k.name: v for k, v in self.processed.items() if v
            },
            "dropped": {k.name: v for k, v in self.dropped.items() if v},
            "expired": {k.name: v for k, v in self.expired.items() if v},
            "shed_admission": {
                k.name: v for k, v in self.shed_admission.items() if v
            },
            "workers": len(self._threads),
            "scheduler": self.scheduler.stats(),
        }

    def qos_totals(self) -> dict:
        """Aggregate loss counts for remote monitoring (utils/monitoring.py
        puts these in its POST body). "shed" matches the Prometheus
        `qos_shed_total` family's total — EVERY lost item across all
        reasons (queue_full + admission + expired) — so a dashboard can
        cross-check the two; "expired" is the deadline subset of it."""
        expired = sum(self.expired.values())
        return {
            "shed": sum(self.dropped.values())
            + sum(self.shed_admission.values()) + expired,
            "expired": expired,
        }

    # ------------------------------------------------------------- threads

    def start(self) -> None:
        self._stop.clear()
        for i in range(self.config.num_workers):
            t = threading.Thread(target=self._worker, name=f"beacon-proc-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    def _worker(self) -> None:
        force_next = False
        while not self._stop.is_set():
            single, batch, trace = self._next_work(force=force_next)
            force_next = False
            if single is None and batch is None:
                if self._resolve_oldest():
                    continue
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                # the wait bounds how long a coalesce-hold can starve a
                # small batch on the live path: the next pass dispatches
                # whatever the scheduler was still widening
                force_next = True
                continue
            try:
                self._execute(single, batch, trace)
            except Exception as e:  # worker never dies on bad work
                _ERRORS.labels("execute").inc()
                log.error(
                    "work unit failed; pump continues",
                    kind=(single or batch[0]).kind.name,
                    error=f"{type(e).__name__}: {e}",
                )
                # the failed unit's enqueue/coalesce spans still belong in
                # the ring — failing work is exactly what an operator pulls
                # a trace for
                obs.TRACER.finish(trace)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        for t in self._threads:
            t.join(timeout=2)
        self._threads.clear()
