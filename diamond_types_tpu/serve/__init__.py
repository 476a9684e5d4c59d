"""Sharded multi-document merge scheduling over the device mesh.

The single-chip device tier peaks well below one host core (VERDICT r5:
VMEM de-amortization past ~8 docs/call plus the ~60 s per-program kill
bound), so production scale goes through the multi-chip path. This
package turns many independent documents into continuously fed,
shape-bucketed, per-shard batches:

  * `router`     — deterministic doc-id -> shard assignment
                   (rendezvous hashing, explicit rebalance)
  * `admission`  — shape-bucketed pending-merge queues with a
                   size-or-deadline flush trigger and bounded depth +
                   backpressure (JIT dynamic batching, arxiv 1904.07421)
  * `bank`       — per-shard FusedDocSession bank with LRU eviction
                   and device-slot capacity accounting
  * `metrics`    — JSON-exportable counters for serve-bench / soak tools
  * `scheduler`  — the composition: DocStore-facing submit/pump/drain
  * `driver`     — trace-replay bench driver (cli serve-bench) with a
                   byte-parity gate against the single-engine merge
"""

from .admission import AdmissionQueue, Backpressure, shape_bucket
from .bank import SessionBank
from .metrics import ServeMetrics
from .router import ShardRouter
from .scheduler import MergeScheduler

__all__ = [
    "AdmissionQueue", "Backpressure", "MergeScheduler", "ServeMetrics",
    "SessionBank", "ShardRouter", "shape_bucket",
]
