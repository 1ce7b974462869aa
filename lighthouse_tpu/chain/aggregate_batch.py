"""AggregateBatch — gossip aggregates verified as ONE pipelined batch.

The aggregate twin of `state_transition.block.SignatureBatch`. A
`SignedAggregateAndProof` is three signature sets (selection proof,
aggregator signature, the aggregate itself) and a dispatch's aggregates are
verified together, 3·k sets in arrival order
(/root/reference/beacon_node/beacon_chain/src/attestation_verification/
batch.rs:31-135 `batch_verify_aggregated_attestations`). Unlike a block's
batch the submission is asynchronous: `submit()` marshals and dispatches on
the caller's thread and hands back `(handle, continuation)`, so the beacon
processor's pump marshals the next batch while the device verifies this one
— the split `BeaconChain.submit_attestation_batch` has had for unaggregated
attestations.

A batch says only "all valid" or "something is not": on False every trio is
verified again alone, so each aggregate gets its own exact verdict
(batch.rs:213-221).
"""

from __future__ import annotations

from time import perf_counter

from ..crypto import bls
from ..observability import trace as _obs
from ..utils.metrics import REGISTRY

_BATCH_SECONDS = REGISTRY.histogram(
    "aggregate_batch_seconds",
    "one dispatch's gossip aggregates (3 signature sets each) verified as "
    "one batch: AggregateBatch.submit() to one verdict an aggregate "
    "delivered, the trio fallback after a False batch included",
    buckets=(0.001, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             120.0, 600.0),
)
_BATCH_AGGREGATES = REGISTRY.counter(
    "aggregate_batch_aggregates_total",
    "aggregates whose verdict an AggregateBatch delivered",
)
_BATCH_FALLBACK = REGISTRY.counter(
    "aggregate_batch_fallback_total",
    "trios verified again alone after their batch came back False",
)
BATCH_SPAN = "gossip:aggregate_batch"


class AggregateBatch:
    """Accumulates the trios of a dispatch's aggregates, then one backend
    batch verify of all their sets and one verdict an aggregate."""

    def __init__(self):
        self.trios: list[tuple] = []

    def add(self, selection_set, aggregator_set, attestation_set) -> None:
        self.trios.append((selection_set, aggregator_set, attestation_set))

    def submit(self):
        """Marshal and dispatch every set, in arrival order. Returns
        `(handle, continuation)`: `continuation(handle.result())` gives
        `list[bool]`, one verdict an aggregate."""
        trios = list(self.trios)
        sets = [s for trio in trios for s in trio]
        args = dict(
            aggregates=len(trios), sets=len(sets),
            distinct_messages=len({s.message for s in sets}),
            widest_keys=max((len(s.signing_keys) for s in sets), default=0),
        )
        with _obs.span(BATCH_SPAN, **args) as sp:
            handle = bls.verify_signature_sets_async(sets)

        def continuation(ok: bool) -> list:
            if ok:
                verdicts = [True] * len(trios)
            else:
                _BATCH_FALLBACK.inc(len(trios))
                verdicts = [bls.verify_signature_sets(trio) for trio in trios]
            _BATCH_SECONDS.observe(perf_counter() - sp.t0)
            _BATCH_AGGREGATES.inc(len(trios))
            return verdicts

        return handle, continuation

    def verify(self) -> list:
        """The synchronous form of `submit()`."""
        handle, continuation = self.submit()
        return continuation(handle.result())
