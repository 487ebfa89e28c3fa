"""Multi-chip serving: replicated index, query-sharded dispatch.

Query serving scales differently from index building: the index fits on
one chip (or is already sharded by comms/), and the scarce resource is
*query throughput*.  The serving answer is data parallelism over the
query stream — the index is replicated across the mesh axis, a batch of
queries shards ``P(axis, None)``, every device runs the full search on
its slice, and the per-shard results all-gather back replicated (the
same shape the single-device search returns, so the batcher cannot tell
the difference).  N devices ≈ N× the batch throughput at identical
per-query results.

This composes with the rest of the serve stack: ``ReplicaGroup`` wraps an
:class:`~raft_tpu.serve.registry.IndexRegistry`, so hot-swap and
mutations behave exactly as in the single-chip path (the snapshot a
search closes over is replicated at trace time).  It also composes with
pipelined dispatch: the returned searcher *enqueues* the replicated
executable and returns unmaterialized device arrays — the batcher's
completion thread is the only place that blocks — so at
``pipeline_depth`` > 1 the host shards/pads the next batch while the
mesh still computes the previous ones, with the same bounded in-flight
window as the single-chip path.

Shape discipline: query shards are ``bucket/size`` rows, so warming the
bucket ladder warms the replicated executables too — one compile per
bucket, independent of device count.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu.comms.comms import Comms, local_comms
from raft_tpu.core.trace import trace_range
from raft_tpu.serve.registry import IndexRegistry


def make_replicated_search(comms: Comms, search_fn):
    """Build a reusable ``(queries, k) -> (distances, ids)`` replicated
    searcher around ``search_fn(queries_shard, k)``.

    ``search_fn`` must be traceable given a [q_shard, dim] query array
    (all index state enters as closure constants — every backend search
    and ``MutableIndex.search`` qualify).  Queries are padded to a
    multiple of the axis size; padded rows are dropped from the result.

    The returned callable owns its executables: the shard_map body is
    wrapped in a persistent ``jax.jit`` per k, so repeated calls at the
    same (k, padded batch) shape reuse one compile — the zero-recompile
    contract the batcher's warmup ladder relies on.  Build it ONCE per
    index state (the serve path keys it on registry version + mutation
    generation) and call it many times.
    """
    mesh, axis = comms.mesh, comms.axis
    size = comms.get_size()
    # the per-shard search runs under jit, not bare in the shard_map body:
    # older jax's ShardMapTracer lacks the eager operator surface (bitwise
    # ops on closure constants fail), while nested-jit tracers are complete
    jitted = jax.jit(search_fn, static_argnums=1)
    sharded = {}  # k -> jitted shard_map wrapper

    def _sharded(k: int):
        f = sharded.get(k)
        if f is None:

            def local(q_shard):
                v, i = jitted(q_shard, k)
                vg = lax.all_gather(v, axis, axis=0, tiled=True)
                ig = lax.all_gather(i, axis, axis=0, tiled=True)
                return vg, ig

            f = jax.jit(
                shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(P(axis, None),),
                    out_specs=(P(None, None), P(None, None)),
                    check_vma=False,
                )
            )
            sharded[k] = f
        return f

    query_spec = NamedSharding(mesh, P(axis, None))

    def _pre_sharded(queries) -> bool:
        # the batcher's staging buffers (or a caller that device_put its
        # own shards) may hand us queries already laid out P(axis, None);
        # a fresh device_put then shows up as a pointless copy_out/shard
        # stage in the flight recorder — detect and skip it
        if not isinstance(queries, jax.Array) or queries.ndim != 2:
            return False
        if queries.dtype != jnp.float32 or queries.shape[0] % size != 0:
            return False
        try:
            return queries.sharding.is_equivalent_to(query_spec, queries.ndim)
        except Exception:
            return False

    def run(queries, k: int) -> Tuple[jax.Array, jax.Array]:
        if _pre_sharded(queries):
            q = queries.shape[0]
            t0 = time.perf_counter()
            qs = queries
        else:
            queries = jnp.asarray(queries, jnp.float32)
            q = queries.shape[0]
            q_pad = -(-q // size) * size
            if q_pad != q:
                queries = jnp.pad(queries, ((0, q_pad - q), (0, 0)))
            t0 = time.perf_counter()
            qs = jax.device_put(queries, query_spec)
        with trace_range("serve.replicated_search") as sp:
            t1 = time.perf_counter()
            v, i = _sharded(k)(qs)
            t2 = time.perf_counter()
            if sp is not None:
                # shard: host-side pad + device_put of the query shards;
                # dispatch: tracing/enqueue of the replicated executable
                # (device wait lands in the caller's block_until_ready)
                sp.add_stage("shard", t1 - t0)
                sp.add_stage("dispatch", t2 - t1)
        return v[:q], i[:q]

    return run


def replicated_search(
    comms: Comms,
    search_fn,
    queries: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """One-shot convenience over :func:`make_replicated_search`.

    Compiles fresh every call — for repeated serving use
    ``make_replicated_search`` (or :class:`ReplicaGroup`, which caches).
    Returns replicated (distances [q, k], ids [q, k]).
    """
    return make_replicated_search(comms, search_fn)(queries, k)


class ReplicaGroup:
    """A registry served data-parallel across the local mesh.

    Resolves names through the registry *per call* (so hot-swaps apply to
    the next batch) and runs the resolved index's merged mutable search
    replicated over the comms axis.  Drop-in as a batcher ``search_fn``
    via :meth:`searcher`.

    Two scaling modes share this front end:

    - ``shard_index=False`` (default): query sharding — every device holds
      the full index, queries split ``P(axis, None)``.  N devices ≈ N×
      throughput; capacity capped by one chip's HBM.
    - ``shard_index=True``: index sharding — registry indexes are
      partitioned across the axis via
      :class:`~raft_tpu.serve.shard.ShardedIndex` (capacity ≈ N× one
      chip), queries replicate, and one cross-shard merge produces the
      global top-k.  An index that is *already* a ``ShardedIndex`` is
      dispatched directly in either mode.
    """

    def __init__(
        self,
        registry: IndexRegistry,
        comms: Optional[Comms] = None,
        *,
        n_devices: Optional[int] = None,
        shard_index: bool = False,
    ):
        self.registry = registry
        self.comms = comms if comms is not None else local_comms(n_devices)
        self.shard_index = shard_index
        # per-name replicated searcher, keyed on (version, generation) so
        # hot-swaps and mutations retrace while steady-state traffic reuses
        # the warmed executables (zero hot-path recompiles)
        self._searchers = {}

    @property
    def n_replicas(self) -> int:
        return self.comms.get_size()

    def search(
        self, name: str, queries, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        from raft_tpu.serve.shard import ShardedIndex

        index, version = self.registry.get_versioned(name)
        key = (version, getattr(index, "generation", 0))
        cached = self._searchers.get(name)
        if cached is None or cached[0] != key:
            if isinstance(index, ShardedIndex):
                # already partitioned (and pinned to its own mesh) — the
                # cross-shard merge is baked into its search
                run = index.search
            elif self.shard_index:
                run = ShardedIndex.from_index(
                    index, self.comms, label=name
                ).search
            else:
                run = make_replicated_search(
                    self.comms, lambda q_shard, kk: index.search(q_shard, kk)
                )
            self._searchers[name] = cached = (key, run)
            # every rebuild retraces the replicated executables on next
            # dispatch — a counter climbing on the hot path is the
            # "swap/mutation churn is eating compiles" capacity signal
            obs.default_registry().counter(
                "raft_tpu_replica_searcher_builds_total",
                help="replicated searcher (re)builds, one per index "
                "version/generation change",
            ).inc(index=name)
            # a point event in the flight ring: an incident dump shows the
            # rebuild (and its retrace cost) next to the batches it delayed
            obs.flight.record_event(
                "replica_rebuild", index=name,
                version=key[0], generation=key[1],
            )
        return cached[1](queries, k)

    def searcher(self, name: str, k: int):
        """A ``queries -> (distances, ids)`` callable for MicroBatcher."""

        def search_fn(queries):
            return self.search(name, queries, k)

        return search_fn

    def member_searchers(self, name: str, k: int):
        """Two independently-dispatched searchers for hedged dispatch
        (:class:`~raft_tpu.serve.overload.HedgedDispatcher`): the
        replicated mesh search as the primary, and a direct single-chip
        search resolved against the same registry as the hedge.  The two
        run genuinely different executables — if the collective path
        stalls (a straggling replica, a slow all-gather), the local
        member still answers from one chip.  On a multi-host deployment
        the hedge member would instead target a second replica group on
        another slice; the host-side contract (same signature, distinct
        dispatch) is identical.
        """

        def local_fn(queries):
            index, _version = self.registry.get_versioned(name)
            return index.search(queries, k)

        return (self.searcher(name, k), local_fn)
