"""raft_tpu.serve — online ANN query serving.

The offline library answers "given a batch, search"; this package answers
the online question: single-query requests arriving over time, against
indexes that change while being served.  Five pieces:

- :mod:`~raft_tpu.serve.batcher` — dynamic micro-batching into a padded
  power-of-two bucket ladder, so every request hits an already-compiled
  executable (zero recompiles after warmup).
- :mod:`~raft_tpu.serve.mutation` — ``MutableIndex``: tombstone deletes
  (filtered inside the backend searches) + a brute-force side buffer for
  upserts, merged through one ``select_k``.
- :mod:`~raft_tpu.serve.registry` — named, versioned indexes with atomic
  hot-swap and snapshot/restore.
- :mod:`~raft_tpu.serve.metrics` — QPS / p50 / p99 / batch-fill, the
  queue/pad/dispatch/device stage breakdown, and a *real* recompile
  counter (jax.monitoring backend-compile events); every instance also
  reports into the process-wide :mod:`raft_tpu.obs` registry.
- :mod:`~raft_tpu.serve.replica` — query-sharded multi-chip dispatch over
  a replicated index (comms/ mesh).
- :mod:`~raft_tpu.serve.compactor` — online compaction: a background
  worker that folds tombstones + side buffer back into the main
  structure via memory-budgeted shadow rebuilds, recall-gated atomic
  promotion, and zero post-swap recompiles.
- :mod:`~raft_tpu.serve.ragged` — continuous ragged batching: one packed
  dispatch per capacity bucket for heterogeneous requests; per-request
  ``k`` and registered filter ids ride as descriptor *data* instead of
  executable shapes, retiring the per-(bucket × k × filter) variant
  lattice (``SearchService(ragged=True)`` / ``RAFT_TPU_RAGGED=1``).
- :mod:`~raft_tpu.serve.shard` — ``ShardedIndex``: the index itself
  partitioned across the mesh axis (brute-force rows / IVF lists), each
  shard running the existing local search with one cross-shard tie-stable
  ``select_k`` merge — capacity ≈ N× one chip instead of throughput ≈ N×.
- :mod:`~raft_tpu.serve.overload` — overload-safe serving: priority
  classes and deadlines on every request, an ``AdmissionController``
  shedding lowest-priority-first under pressure (typed ``Shed`` /
  ``DeadlineExceeded`` rejections, never silent), a
  ``DegradedModeManager`` stepping search effort down with hysteresis,
  and ``HedgedDispatcher`` racing replica members for p0 tail latency
  (``SearchService(overload=True)`` / ``RAFT_TPU_OVERLOAD=1``).

``SearchService`` (:mod:`~raft_tpu.serve.service`) assembles them, and
carries the obs v2 hooks: attach a :class:`raft_tpu.obs.QualityAuditor`
for online recall auditing off the hot path, read ``healthz()`` /
``readyz()`` for OK / DEGRADED / UNHEALTHY verdicts.  See
``docs/serving.md`` for the guided tour.
"""

from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.build import build_sharded, knn_graph_sharded
from raft_tpu.serve.compactor import CompactionPolicy, Compactor
from raft_tpu.serve.effort import EffortArbiter
from raft_tpu.serve.metrics import (
    ServingMetrics,
    compile_count,
    install_compile_listener,
)
from raft_tpu.serve.mutation import MutableIndex
from raft_tpu.serve.overload import (
    AdmissionController,
    DeadlineExceeded,
    DegradedModeManager,
    HedgedDispatcher,
    OverloadConfig,
    Shed,
)
from raft_tpu.serve.ragged import FilterRegistry, RaggedSearcher, RaggedSpec
from raft_tpu.serve.registry import IndexRegistry
from raft_tpu.serve.replica import (
    ReplicaGroup,
    make_replicated_search,
    replicated_search,
)
from raft_tpu.serve.service import SearchService
from raft_tpu.serve.shard import ShardedIndex, shard_index

__all__ = [
    "AdmissionController",
    "CompactionPolicy",
    "Compactor",
    "DeadlineExceeded",
    "DegradedModeManager",
    "EffortArbiter",
    "FilterRegistry",
    "HedgedDispatcher",
    "IndexRegistry",
    "MicroBatcher",
    "MutableIndex",
    "OverloadConfig",
    "RaggedSearcher",
    "RaggedSpec",
    "ReplicaGroup",
    "SearchService",
    "ServingMetrics",
    "Shed",
    "ShardedIndex",
    "build_sharded",
    "compile_count",
    "knn_graph_sharded",
    "install_compile_listener",
    "make_replicated_search",
    "replicated_search",
    "shard_index",
]
