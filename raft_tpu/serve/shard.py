"""Pod-scale index sharding: partition one index across a named mesh axis.

``ReplicaGroup`` scales *throughput* by replicating the whole index and
sharding queries; capacity stays capped by a single chip's HBM.  This
module scales *capacity*: :class:`ShardedIndex` partitions the index
itself — brute-force rows, IVF lists (ivf_flat + ivf_pq; CAGRA falls back
to row-partitioned brute refine over its dataset) — across the devices of
a :class:`~raft_tpu.comms.comms.Comms` mesh axis via ``NamedSharding``,
so each device holds ~1/N of the index.

Search is the blocking scheme of "Large Scale Distributed Linear Algebra
With TPUs" (PAPERS.md) applied to ANN: every shard runs the *existing*
local search (the same dispatch the single-device path uses, including
the Pallas IVF scan legs) over its partition under ``shard_map``, then the
global answer is produced by one cross-shard merge — an all-gather of the
per-shard top-k candidates followed by a single tie-stable
:func:`~raft_tpu.ops.matrix.select_k_stable`.  The merge collective moves
``n_shards · k`` candidates per query (tiny next to the index), and an
optional bf16 cast on the gathered distances (EQuARX-style,
``RAFT_TPU_SHARD_MERGE_DTYPE=bfloat16``) halves even that — candidate
distances tolerate low precision before any final refine.

Semantics vs the single-device backends:

- ``brute_force`` / ``cagra`` fallback: exact — the per-shard candidate
  union always contains the global top-k, and the id-tie-stable merge
  returns identical (ids, distances).
- ``ivf_flat`` / ``ivf_pq``: each shard probes up to ``n_probes`` of *its
  own* lists, so the probed set is a superset of the single-device probed
  set — recall is ≥ the unsharded search at equal ``n_probes`` (exactly
  equal when probing is exhaustive, ``n_probes >= n_lists``).  This
  mirrors how multi-GPU IVF deployments shard (per-partition probing).

Tombstones from a :class:`~raft_tpu.serve.mutation.MutableIndex` are
folded in at shard time (the global pass bitset is tiny and rides along
replicated); live side-buffer rows are rejected — compact/rebuild before
sharding.  A sharded index is an immutable serving layout: mutate the
source and hot-swap a fresh :meth:`ShardedIndex.from_index` through the
registry.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu import kernels as _kernels
from raft_tpu.comms.comms import Comms, local_comms
from raft_tpu.core import env as _env
from raft_tpu.core.bitset import Bitset, RowFilter, WORD_BITS
from raft_tpu.core.trace import trace_range
from raft_tpu.distance.pairwise import DISTANCE_TYPES
from raft_tpu.ops import matrix
from raft_tpu.serve.mutation import MutableIndex

#: env knob for the merge all-gather's distance dtype (EQuARX-style
#: quantized collective): "float32" (default, exact) or "bfloat16"
MERGE_DTYPE_ENV = "RAFT_TPU_SHARD_MERGE_DTYPE"

_MERGE_DTYPES = {
    "float32": None,  # no cast — gather full-precision distances
    "f32": None,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
}


def merge_dtype_from_env() -> Optional[jnp.dtype]:
    """Resolve ``RAFT_TPU_SHARD_MERGE_DTYPE`` to a cast dtype (or None)."""
    name = _env.env_str(MERGE_DTYPE_ENV, "float32").strip().lower()
    if name not in _MERGE_DTYPES:
        raise ValueError(
            f"{MERGE_DTYPE_ENV}={name!r} not understood; expected one of "
            f"{sorted(_MERGE_DTYPES)}"
        )
    return _MERGE_DTYPES[name]


def _pack_pass_words(mask: np.ndarray) -> np.ndarray:
    """Pack a boolean pass mask into Bitset-layout uint32 words (host)."""
    n = mask.shape[0]
    nw = (n + WORD_BITS - 1) // WORD_BITS
    padded = np.zeros((nw * WORD_BITS,), np.uint32)
    padded[:n] = mask.astype(np.uint32)
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    return np.sum(
        padded.reshape(nw, WORD_BITS) << shifts[None, :], axis=1, dtype=np.uint32
    )


#: env knob for how sharded CAGRA serves: "brute" (row-partitioned brute
#: refine, exact) or "graph" (partitioned graph traversal, graph_shard.py)
CAGRA_MODE_ENV = "RAFT_TPU_SHARD_CAGRA"


def _resolve_cagra_mode(mode: str) -> str:
    if mode == "env":
        mode = (_env.env_str(CAGRA_MODE_ENV, "brute") or "brute")
    mode = mode.strip().lower()
    if mode not in ("brute", "graph"):
        raise ValueError(
            f"cagra shard mode {mode!r} not understood; expected 'brute', "
            f"'graph' or 'env' ({CAGRA_MODE_ENV})"
        )
    return mode


def _round_robin(n_items: int, n_shards: int) -> list:
    """Per-shard item indices, round-robin (balances size-sorted skew)."""
    return [np.arange(s, n_items, n_shards) for s in range(n_shards)]


class ShardedIndex:
    """One logical index partitioned across a mesh axis.

    Build via :meth:`from_index`; call :meth:`search` like any backend.
    Quacks enough like :class:`MutableIndex` (``kind``/``dim``/``size``/
    ``generation``/``pending_mutations``/``device_bytes``/``search``) to be
    registered and hot-swapped through ``IndexRegistry``/``SearchService``
    and served by ``ReplicaGroup``/``MicroBatcher``.
    """

    #: True on the partitioned-graph CAGRA subclass
    #: (:class:`raft_tpu.serve.graph_shard.GraphShardedIndex`) — consumers
    #: (kernel-path stamps, explain) read it duck-typed via ``getattr``
    graph_mode = False

    def __init__(
        self,
        comms: Comms,
        kind: str,
        metric: str,
        dim: int,
        size: int,
        parts: Dict[str, jax.Array],
        specs: Dict[str, P],
        *,
        search_params=None,
        merge_dtype=None,
        label: str = "",
        shard_stats: Optional[Dict[str, list]] = None,
    ):
        self.comms = comms
        self.kind = kind
        self.metric = metric
        self.dim = int(dim)
        self.size = int(size)
        self.search_params = search_params
        self.label = label or kind
        self.merge_dtype = merge_dtype
        canonical = DISTANCE_TYPES[metric]
        self.select_min = canonical != "inner_product"
        self._names = tuple(parts)
        self._parts = parts
        self._specs = specs
        self._searchers: Dict[Tuple[int, ...], object] = {}
        # MutableIndex-compatible serving surface: a sharded layout is
        # immutable — mutate the source index and hot-swap a re-shard
        self.generation = 0
        self._shard_stats = shard_stats or {}
        self._publish_shard_gauges()

    # -- construction --------------------------------------------------------
    @classmethod
    def from_index(
        cls,
        index,
        comms: Optional[Comms] = None,
        *,
        n_devices: Optional[int] = None,
        search_params=None,
        merge_dtype="env",
        label: str = "",
        cagra_mode: str = "env",
    ) -> "ShardedIndex":
        """Partition a built index (or a compacted ``MutableIndex``) across
        ``comms``'s axis.

        ``merge_dtype`` defaults to the ``RAFT_TPU_SHARD_MERGE_DTYPE`` env
        knob; pass ``None`` (exact f32 merge) or ``jnp.bfloat16`` to
        override.  A ``MutableIndex`` may carry tombstones (folded into the
        sharded filter) but not live side-buffer rows.

        ``cagra_mode`` selects how a CAGRA index is served: ``"brute"``
        (default; row-partitioned brute refine — exact, the correctness
        control arm), ``"graph"`` (partitioned graph traversal with halo
        frontiers, :mod:`raft_tpu.serve.graph_shard`), or ``"env"`` to
        consult ``RAFT_TPU_SHARD_CAGRA``.
        """
        comms = comms if comms is not None else local_comms(n_devices)
        if merge_dtype == "env":
            merge_dtype = merge_dtype_from_env()
        deleted = None
        if isinstance(index, MutableIndex):
            if index.refine_rows is not None:
                raise ValueError(
                    "cannot shard a MutableIndex with exact refine: the "
                    "sharded search has no refine leg"
                )
            with index._lock:
                if int(index._side_live.sum()) > 0:
                    raise ValueError(
                        "cannot shard a MutableIndex with live side-buffer "
                        "rows; rebuild/compact the index first"
                    )
                if index._main_ids is not None:
                    # the sharded layouts carry global ids as row positions
                    # (arange rows / list_index); a compacted id map would
                    # silently serve wrong ids through them
                    raise ValueError(
                        "cannot shard a MutableIndex with a remapped id "
                        "space (a compacted index); rebuild it with dense "
                        "ids from live_vectors() first"
                    )
                if index._n_deleted:
                    deleted = index._deleted.copy()
            if search_params is None:
                search_params = index.search_params
            kind, inner = index.kind, index.index
        else:
            kind, inner = _infer_kind(index), index
        if kind == "cagra" and _resolve_cagra_mode(cagra_mode) == "graph":
            from raft_tpu.serve.graph_shard import GraphShardedIndex

            return GraphShardedIndex._shard_graph(
                comms, inner, deleted, search_params, merge_dtype, label
            )
        if kind in ("brute_force", "cagra"):
            # CAGRA's graph is a per-shard traversal structure with global
            # fan-out; the default CAGRA mode therefore serves the capacity
            # win by sharding the rows — row-partitioned brute refine over
            # its dataset (exact; the graph mode's correctness control arm)
            return cls._shard_rows(
                comms, kind, inner, deleted, merge_dtype, label
            )
        if kind == "ivf_flat":
            return cls._shard_ivf_flat(
                comms, inner, deleted, search_params, merge_dtype, label
            )
        if kind == "ivf_pq":
            return cls._shard_ivf_pq(
                comms, inner, deleted, search_params, merge_dtype, label
            )
        raise ValueError(f"unsupported index kind for sharding: {kind!r}")

    @classmethod
    def _shard_rows(cls, comms, kind, inner, deleted, merge_dtype, label):
        data = np.asarray(inner.dataset)
        n, d = data.shape
        s_count = comms.get_size()
        r = -(-n // s_count)
        rows = np.zeros((s_count, r, d), data.dtype)
        ids = np.full((s_count, r), -1, np.int32)
        words = np.zeros(
            (s_count, (r + WORD_BITS - 1) // WORD_BITS), np.uint32
        )
        row_counts = []
        for s in range(s_count):
            lo, hi = s * r, min((s + 1) * r, n)
            m = hi - lo
            if m > 0:
                rows[s, :m] = data[lo:hi]
                ids[s, :m] = np.arange(lo, hi, dtype=np.int32)
            passes = np.zeros((r,), bool)
            passes[:m] = True
            if deleted is not None and m > 0:
                passes[:m] &= ~deleted[lo:hi]
            words[s] = _pack_pass_words(passes)
            row_counts.append(int(passes.sum()))
        parts, specs = _place(
            comms,
            sharded={"rows": rows, "ids": ids, "pass_words": words},
            replicated={},
        )
        live = n if deleted is None else n - int(deleted.sum())
        return cls(
            comms, kind, inner.metric, d, live, parts, specs,
            merge_dtype=merge_dtype, label=label,
            shard_stats={"rows": row_counts},
        )

    @classmethod
    def _shard_ivf_flat(cls, comms, inner, deleted, params, merge_dtype, label):
        from raft_tpu.neighbors import ivf_flat

        params = params if params is not None else ivf_flat.SearchParams()
        arrays = {
            "centers": np.asarray(inner.centers),
            "list_data": np.asarray(inner.list_data),
            "list_index": np.asarray(inner.list_index),
            "list_sizes": np.asarray(inner.list_sizes),
            "list_norms": np.asarray(inner.list_norms),
        }
        fills = {"list_index": -1, "list_sizes": 0, "list_norms": np.inf}
        sharded, stats = _partition_lists(arrays, fills, comms.get_size())
        n_main = int(arrays["list_sizes"].sum())
        replicated = _global_pass_filter(deleted, n_main)
        parts, specs = _place(comms, sharded=sharded, replicated=replicated)
        live = n_main if deleted is None else n_main - int(deleted.sum())
        return cls(
            comms, "ivf_flat", inner.metric, int(inner.dim), live, parts,
            specs, search_params=params, merge_dtype=merge_dtype, label=label,
            shard_stats=stats,
        )

    @classmethod
    def _shard_ivf_pq(cls, comms, inner, deleted, params, merge_dtype, label):
        from raft_tpu.neighbors import ivf_pq

        params = params if params is not None else ivf_pq.SearchParams()
        arrays = {
            "centers": np.asarray(inner.centers),
            "centers_rot": np.asarray(inner.centers_rot),
            "list_codes": np.asarray(inner.list_codes),
            "list_index": np.asarray(inner.list_index),
            "list_sizes": np.asarray(inner.list_sizes),
            "list_data": np.asarray(inner.list_data),
            "list_y2": np.asarray(inner.list_y2),
        }
        fills = {"list_index": -1, "list_sizes": 0, "list_y2": np.inf}
        replicated = {"rotation": np.asarray(inner.rotation)}
        if inner.codebook_kind == "per_cluster":
            arrays["codebook"] = np.asarray(inner.codebook)
        else:
            replicated["codebook"] = np.asarray(inner.codebook)
        sharded, stats = _partition_lists(arrays, fills, comms.get_size())
        n_main = int(arrays["list_sizes"].sum())
        replicated.update(_global_pass_filter(deleted, n_main))
        parts, specs = _place(comms, sharded=sharded, replicated=replicated)
        live = n_main if deleted is None else n_main - int(deleted.sum())
        self = cls(
            comms, "ivf_pq", inner.metric, int(inner.dim), live, parts,
            specs, search_params=params, merge_dtype=merge_dtype, label=label,
            shard_stats=stats,
        )
        self._pq_meta = (
            inner.codebook_kind, int(inner.pq_bits), float(inner.scan_scale),
        )
        return self

    # -- search --------------------------------------------------------------
    def search(
        self, queries, k: int, *, sample_filter=None
    ) -> Tuple[jax.Array, jax.Array]:
        """Global (distances [q, k], ids [q, k]) over all shards.

        One SPMD dispatch: per-shard local search + the single cross-shard
        merge collective.  Executables are cached per k (and per query
        batch shape via jit), preserving the batcher's zero-recompile
        contract once the bucket ladder is warm.

        ``sample_filter`` is an optional per-query
        :class:`~raft_tpu.core.bitset.RowFilter` over **global** ids (the
        ragged path's packed predicate words, replicated to every shard):
        the IVF legs pass it straight into the local search (their
        ``list_index`` ids are global), the row-partitioned legs re-base
        the global bits onto each shard's local rows.  The filtered
        executable is cached separately — serving a filter-free stream
        never pays the gather.
        """
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise ValueError(
                f"queries shape {queries.shape} vs index dim {self.dim}"
            )
        fargs = ()
        filter_bits = None
        if sample_filter is not None:
            if not isinstance(sample_filter, RowFilter):
                raise TypeError(
                    "ShardedIndex.search expects a per-query RowFilter "
                    f"over global ids, got {type(sample_filter).__name__}"
                )
            filter_bits = int(sample_filter.n_bits)
            fargs = (jnp.asarray(sample_filter.words, jnp.uint32),)
        f = self._searcher(int(k), filter_bits)
        t0 = time.perf_counter()
        with trace_range("serve.sharded_search") as sp:
            v, i = f(queries, *fargs, *(self._parts[n] for n in self._names))
            dt = time.perf_counter() - t0
            if sp is not None:
                # dispatch: tracing/enqueue of the sharded executable (the
                # device wait lands in the caller's block_until_ready)
                sp.add_stage("dispatch", dt)
        # kernel-path attribution (consumed by the batcher on this same
        # thread): stamped AFTER the dispatch so a first-call trace of the
        # per-shard core cannot overwrite it with its inner leg's stamp.
        # Graph-mode CAGRA serves filtered traffic through its exact
        # brute-refine core, hence the filter term.
        graph_walk = self.graph_mode and filter_bits is None
        _kernels.stamp_kernel_path(
            "sharded_graph" if graph_walk else "sharded"
        )
        obs.default_registry().histogram(
            "raft_tpu_sharded_search_seconds",
            help="host-side dispatch latency of index-sharded searches "
            "(the slowest shard paces the whole SPMD step)",
        ).observe(dt, index=self.label, shards=str(self.n_shards))
        return v, i

    @property
    def n_shards(self) -> int:
        return self.comms.get_size()

    def _searcher(self, k: int, filter_bits: Optional[int] = None):
        key = (k, filter_bits)
        f = self._searchers.get(key)
        if f is None:
            f = self._build_searcher(k, filter_bits)
            self._searchers[key] = f
        return f

    def _local_pool(self) -> Tuple[int, int]:
        """(n_probes_local, candidate pool per shard) from static shapes."""
        if self.kind in ("brute_force", "cagra"):
            return 0, int(self._parts["rows"].shape[1])
        l_local = int(self._parts["list_index"].shape[1])
        cap = int(self._parts["list_index"].shape[2])
        npb = min(int(self.search_params.n_probes), l_local)
        return npb, npb * cap

    def _build_searcher(self, k: int, filter_bits: Optional[int] = None):
        mesh, axis = self.comms.mesh, self.comms.axis
        npb, pool = self._local_pool()
        kk = min(k, pool)
        if kk * self.n_shards < k:
            raise ValueError(
                f"k={k} exceeds the sharded candidate pool "
                f"{self.n_shards}x{kk}; raise n_probes or lower k"
            )
        local = self._make_local(k, kk, npb, filter_bits)
        filter_specs = () if filter_bits is None else (P(None, None),)
        in_specs = (P(None, None),) + filter_specs + tuple(
            self._specs[n] for n in self._names
        )
        return jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=(P(None, None), P(None, None)),
                check_vma=False,
            )
        )

    def _make_local(self, k: int, kk: int, npb: int,
                    filter_bits: Optional[int] = None):
        # the per-shard search and the merge selection both run under
        # nested jit, not bare in the shard_map body: older jax's
        # ShardMapTracer lacks the eager operator surface, while
        # nested-jit tracers are complete (same split as replica.py) —
        # only the all-gather collectives live in the bare body
        core = jax.jit(self._make_shard_search(kk, npb, filter_bits))
        select_min = self.select_min

        def _select(vg, ig):
            # ONE cross-shard selection; ties resolve to the smallest
            # global id regardless of shard layout (select_k_stable —
            # which routes to the fused kernels/select_k.py VMEM path at
            # merge widths, replacing the two-key full-row sort in HBM)
            return matrix.select_k_stable(
                vg.astype(jnp.float32), k,
                select_min=select_min, input_indices=ig,
            )

        sel = jax.jit(_select)

        if filter_bits is None:
            def local(q, *args):
                v, gi = core(q, *args)
                vg = self.comms.allgather(v, axis=1)
                ig = self.comms.allgather(gi, axis=1)
                return sel(vg, ig)
        else:
            def local(q, words, *args):
                v, gi = core(q, words, *args)
                vg = self.comms.allgather(v, axis=1)
                ig = self.comms.allgather(gi, axis=1)
                return sel(vg, ig)

        return local

    def _make_shard_search(self, kk: int, npb: int,
                           filter_bits: Optional[int] = None):
        """Per-shard ``(queries[, filter words], *parts) -> (dists [q,kk],
        global ids)``, squeezing the leading shard axis off every
        partitioned block and re-assembling the backend Index so the
        *existing* local search (Pallas scan legs included) runs unchanged
        over the partition.  The optional EQuARX-style bf16 cast of the
        candidate distances happens here, before the merge all-gather
        moves them.

        When ``filter_bits`` is set the core takes the replicated
        per-query global-id filter words as its second operand: IVF legs
        AND them with the tombstone bitset and pass the RowFilter through
        (list ids are global); row legs re-base the global bits onto
        local row positions before the local knn."""
        names = self._names
        merge_dtype = self.merge_dtype

        def _cast(v):
            if merge_dtype is not None and v.dtype != merge_dtype:
                return v.astype(merge_dtype)
            return v

        def _global_filter(p, words):
            """Tombstone bitset, per-query RowFilter, or their AND —
            all over the global id space the IVF list ids live in."""
            filt = _replicated_filter(p)
            if words is None:
                return filt
            if filt is not None:
                nw = min(int(filt.words.shape[0]), int(words.shape[1]))
                words = words.at[:, :nw].set(
                    words[:, :nw] & filt.words[:nw][None, :]
                )
            return RowFilter(words, filter_bits)

        if self.kind in ("brute_force", "cagra"):
            from raft_tpu.neighbors import brute_force

            def core(q, *args, words=None):
                p = dict(zip(names, args))
                rows, ids = p["rows"][0], p["ids"][0]
                if words is None:
                    filt = Bitset(p["pass_words"][0], rows.shape[0])
                else:
                    # re-base the global per-query bits onto this shard's
                    # local row positions (ids are the global row ids),
                    # folding the local pass bitset in
                    safe = jnp.clip(ids, 0, None).astype(jnp.uint32)
                    w = words[:, safe // WORD_BITS]           # [q, r]
                    bit = (w >> (safe % WORD_BITS)) & jnp.uint32(1)
                    mask = (bit == 1) & (ids >= 0)[None, :]
                    local_words = (
                        RowFilter.from_mask_rows(mask).words
                        & p["pass_words"][0][None, :]
                    )
                    filt = RowFilter(local_words, rows.shape[0])
                v, li = brute_force.knn(
                    rows, q, kk, metric=self.metric, sample_filter=filt
                )
                safe = jnp.clip(li, 0, rows.shape[0] - 1)
                gi = jnp.where(li >= 0, ids[safe], jnp.int32(-1))
                return _cast(v), gi

        elif self.kind == "ivf_flat":
            from raft_tpu.neighbors import ivf_flat

            sp = dataclasses.replace(self.search_params, n_probes=npb)

            def core(q, *args, words=None):
                p = dict(zip(names, args))
                sub = ivf_flat.Index(
                    self.metric, p["centers"][0], p["list_data"][0],
                    p["list_index"][0], p["list_sizes"][0], p["list_norms"][0],
                )
                filt = _global_filter(p, words)
                v, gi = ivf_flat.search(sp, sub, q, kk, sample_filter=filt)
                return _cast(v), gi

        else:
            from raft_tpu.neighbors import ivf_pq

            codebook_kind, pq_bits, scan_scale = self._pq_meta
            sp = dataclasses.replace(self.search_params, n_probes=npb)

            def core(q, *args, words=None):
                p = dict(zip(names, args))
                codebook = (
                    p["codebook"][0] if codebook_kind == "per_cluster"
                    else p["codebook"]
                )
                sub = ivf_pq.Index(
                    self.metric, codebook_kind, pq_bits, p["centers"][0],
                    p["centers_rot"][0], p["rotation"], codebook,
                    p["list_codes"][0], p["list_index"][0], p["list_sizes"][0],
                    p["list_data"][0], p["list_y2"][0], scan_scale=scan_scale,
                )
                filt = _global_filter(p, words)
                v, gi = ivf_pq.search(sp, sub, q, kk, sample_filter=filt)
                return _cast(v), gi

        if filter_bits is None:
            return core

        def filtered(q, words, *args):
            return core(q, *args, words=words)

        return filtered

    # -- MutableIndex-compatible serving surface ----------------------------
    def pending_mutations(self) -> Tuple[int, int]:
        """(0, 0): a sharded layout is immutable; mutate the source index
        and hot-swap a re-shard through the registry."""
        return 0, 0

    def upsert(self, vectors, ids=None):
        """Loud failure for writes forwarded after a sharded rebuild
        (a retired MutableIndex forwards mutations to its successor)."""
        raise NotImplementedError(
            "ShardedIndex is immutable: rebuild through "
            "serve.build.build_sharded (or Compactor.rebuild_sharded) and "
            "hot-swap the result"
        )

    def delete(self, ids):
        raise NotImplementedError(
            "ShardedIndex is immutable: rebuild through "
            "serve.build.build_sharded (or Compactor.rebuild_sharded) and "
            "hot-swap the result"
        )

    def device_bytes(self) -> int:
        """Total bytes across all shards (feeds the per-version live-buffer
        gauges, comparable with the unsharded index's footprint)."""
        return sum(int(a.nbytes) for a in self._parts.values())

    def per_shard_bytes(self) -> list:
        """Bytes resident on each device: sharded arrays contribute 1/N,
        replicated ones (rotation, shared codebook, filter) in full."""
        s_count = self.n_shards
        shard_b = repl_b = 0
        for name, arr in self._parts.items():
            if self._specs[name] and self._specs[name][0] is not None:
                shard_b += int(arr.nbytes) // s_count
            else:
                repl_b += int(arr.nbytes)
        return [shard_b + repl_b] * s_count

    def save(self, path: str) -> None:
        raise NotImplementedError(
            "ShardedIndex is a serving-time layout; snapshot the source "
            "index and re-shard on restore"
        )

    # -- observability -------------------------------------------------------
    def explain_contributions(self, ids) -> Dict[str, object]:
        """Per-shard counts of merged result ids — which shards the
        answer actually came from.  Deep-explain only: the ids are an
        already-copied host result, so there is no extra sync and the
        call never runs on the hot path.  Row-partitioned kinds own
        contiguous id ranges (``id // rows_per_shard``); the IVF kinds
        consult a lazily-built id→owner map from the partitioned
        ``list_index``."""
        try:
            flat = np.asarray(ids).reshape(-1)
            flat = flat[flat >= 0]
            s_count = self.n_shards
            if self.kind in ("brute_force", "cagra"):
                r = int(self._parts["rows"].shape[1])
                owner = flat // r
            else:
                owner_map = self._id_owner()
                flat = flat[flat < owner_map.shape[0]]
                owner = owner_map[flat]
            counts = np.bincount(
                owner[(owner >= 0) & (owner < s_count)], minlength=s_count
            )
            return {
                "available": True,
                "n_shards": s_count,
                "per_shard": [int(c) for c in counts[:s_count]],
            }
        except Exception as exc:  # never let explain break serving
            return {"available": False, "error": repr(exc)}

    def _id_owner(self) -> np.ndarray:
        """Cached global-id → owning-shard map for the IVF layouts
        (built once, deep-explain only)."""
        owner = getattr(self, "_owner_map", None)
        if owner is None:
            li = np.asarray(self._parts["list_index"])  # raft-tpu: ignore[HOSTSYNC] deep-explain only: one-time owner-map pull, never on the hot path
            top = int(li.max()) + 1 if li.size else 0
            owner = np.full(max(top, 0), -1, np.int32)
            for s in range(li.shape[0]):
                sid = li[s].reshape(-1)
                sid = sid[sid >= 0]
                owner[sid] = s
            self._owner_map = owner
        return owner

    def _publish_shard_gauges(self) -> None:
        """Per-shard row/list/byte gauges — the imbalance dashboard."""
        reg = obs.default_registry()
        per_bytes = self.per_shard_bytes()
        rows = self._shard_stats.get("rows")
        lists = self._shard_stats.get("lists")
        halo = self._shard_stats.get("halo")
        for s in range(self.n_shards):
            labels = {"index": self.label, "shard": str(s)}
            if rows is not None:
                reg.gauge(
                    "raft_tpu_shard_rows",
                    help="live vectors owned by each index shard",
                ).set(float(rows[s]), **labels)
            if lists is not None:
                reg.gauge(
                    "raft_tpu_shard_lists",
                    help="IVF lists owned by each index shard",
                ).set(float(lists[s]), **labels)
            if halo is not None:
                reg.gauge(
                    "raft_tpu_shard_halo_rows",
                    help="replicated halo rows held by each graph-mode "
                    "CAGRA shard (cross-cut neighbors kept so local hops "
                    "never dead-end at the partition boundary)",
                ).set(float(halo[s]), **labels)
            reg.gauge(
                "raft_tpu_shard_live_bytes",
                help="per-device bytes held by each index shard "
                "(sharded arrays at 1/N + replicated sidecars)",
            ).set(float(per_bytes[s]), **labels)

    def measure_shard_skew(self, queries, k: int) -> Dict[str, object]:
        """Per-shard device-time probe — straggler detection.

        The production :meth:`search` is ONE shard_map dispatch: the
        slowest shard paces every other, and per-shard time is invisible
        from the host.  This probe runs the *same* per-shard core search
        (Pallas legs included) over each shard's partition individually —
        warmed, then timed — and publishes
        ``raft_tpu_shard_device_seconds{index,shard}`` plus the max/mean
        straggler factor ``raft_tpu_shard_device_skew{index}``.  A skew
        near 1.0 means the round-robin partitioning is balanced; a high
        skew names the shard throttling the whole SPMD step.

        Deliberately off the hot path (operator / bench entry): compiles
        and syncs spent here never touch the batcher's zero-recompile
        contract or the serve-stage timers.
        """
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise ValueError(
                f"queries shape {queries.shape} vs index dim {self.dim}"
            )
        npb, pool = self._local_pool()
        kk = min(int(k), pool)
        core = jax.jit(self._make_shard_search(kk, npb))
        times = []
        with trace_range("serve.shard_skew"):
            for s in range(self.n_shards):
                # sharded parts contribute this shard's slice (leading
                # axis kept — the core squeezes it, exactly as the
                # shard_map body would); replicated parts ride whole
                args = tuple(
                    self._parts[n][s : s + 1]
                    if self._specs[n] and self._specs[n][0] is not None
                    else self._parts[n]
                    for n in self._names
                )
                out = core(queries, *args)
                jax.block_until_ready(out)  # raft-tpu: ignore[HOSTSYNC] probe warmup barrier
                t0 = time.perf_counter()
                out = core(queries, *args)
                jax.block_until_ready(out)  # raft-tpu: ignore[HOSTSYNC] probe timing barrier
                times.append(time.perf_counter() - t0)
        reg = obs.default_registry()
        for s, dt in enumerate(times):
            reg.gauge(
                "raft_tpu_shard_device_seconds",
                help="measured per-shard seconds for one probe search, "
                "dispatched individually outside the SPMD step",
            ).set(float(dt), index=self.label, shard=str(s))
        mean = sum(times) / len(times)
        skew = (max(times) / mean) if mean > 0.0 else 1.0
        reg.gauge(
            "raft_tpu_shard_device_skew",
            help="max/mean of the per-shard probe times — the straggler "
            "factor pacing the real sharded dispatch",
        ).set(float(skew), index=self.label)
        return {"per_shard_s": times, "skew": skew}


def _infer_kind(index) -> str:
    mod = type(index).__module__.rsplit(".", 1)[-1]
    if mod not in ("brute_force", "ivf_flat", "ivf_pq", "cagra"):
        raise ValueError(
            f"cannot infer index kind from {type(index)!r}; pass a built "
            "brute_force/ivf_flat/ivf_pq/cagra index or a MutableIndex"
        )
    return mod


def _partition_lists(arrays, fills, s_count):
    """Round-robin the leading (list) axis of every array into [S, Lp, ...]
    stacks, padding with empty lists (sizes 0, ids −1, norms inf)."""
    l_total = arrays["list_index"].shape[0]
    groups = _round_robin(l_total, s_count)
    lp = max(len(g) for g in groups)
    out = {}
    for name, arr in arrays.items():
        fill = fills.get(name, 0)
        stack = np.full((s_count, lp) + arr.shape[1:], fill, arr.dtype)
        for s, g in enumerate(groups):
            if len(g):
                stack[s, : len(g)] = arr[g]
                if name == "centers" and len(g) < lp:
                    # padded slots re-use a real center: they may attract
                    # probes (wasting one) but their lists are empty, so
                    # every candidate they yield is (−1, worst) — harmless
                    stack[s, len(g):] = arr[g[0]]
        out[name] = stack
    sizes = arrays["list_sizes"]
    stats = {
        "lists": [len(g) for g in groups],
        "rows": [int(sizes[g].sum()) for g in groups],
    }
    return out, stats


def _global_pass_filter(deleted, n_main):
    """Replicated global-id pass bitset words (IVF ids are global)."""
    if deleted is None:
        return {}
    return {"pass_words": _pack_pass_words(~deleted[:n_main])}


def _replicated_filter(parts):
    words = parts.get("pass_words")
    if words is None:
        return None
    return Bitset(words, int(words.shape[0]) * WORD_BITS)


def _place(comms, *, sharded, replicated):
    """device_put every array with its NamedSharding: sharded stacks split
    on the leading (shard) axis, sidecars replicated on every device."""
    mesh, axis = comms.mesh, comms.axis
    parts, specs = {}, {}
    for name, arr in sharded.items():
        spec = P(axis, *([None] * (arr.ndim - 1)))
        parts[name] = jax.device_put(arr, NamedSharding(mesh, spec))
        specs[name] = spec
    for name, arr in replicated.items():
        spec = P(*([None] * arr.ndim))
        parts[name] = jax.device_put(arr, NamedSharding(mesh, spec))
        specs[name] = spec
    return parts, specs


def shard_index(index, comms: Optional[Comms] = None, **kwargs) -> ShardedIndex:
    """Convenience alias for :meth:`ShardedIndex.from_index`."""
    return ShardedIndex.from_index(index, comms, **kwargs)
