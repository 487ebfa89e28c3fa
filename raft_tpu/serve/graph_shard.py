"""Partitioned CAGRA: true sharded graph traversal with halo frontiers.

:class:`~raft_tpu.serve.shard.ShardedIndex` serves CAGRA by
row-partitioned brute refine — exact, but O(rows/shard) device work per
query, which forfeits CAGRA's algorithmic win exactly where sharding is
supposed to deliver it.  This module restores the sublinear walk at pod
scale:

* **Cluster cut** — the graph is partitioned with the existing balanced
  k-means coarse clustering (:mod:`raft_tpu.cluster.kmeans_balanced`,
  ``C = n_shards``): each shard owns the rows of its cluster, so the cut
  follows the data's own geometry and most graph edges stay internal.
* **Halo nodes** — each shard replicates a bounded set of cross-cut
  neighbors (ranked by in-degree from owned rows, capped by
  ``RAFT_TPU_SHARD_CAGRA_HALO``) so local hops never dead-end at a
  partition boundary.  Halo rows route the walk but never appear in
  results (the per-shard pass bitset covers owned live rows only, so the
  merged id set is duplicate-free).
* **Shard-local traversal** — each shard runs the PR 13 fused Pallas hop
  (or its XLA twin off-TPU) over its *local-id* subgraph
  (:func:`raft_tpu.neighbors.cagra.traverse_steps`); the local↔global id
  translation is one gather (local→global, via the shard's ``ids`` row)
  and one binary search (global→local, via a sorted gid table).
* **Halo frontier exchange** — every ``RAFT_TPU_SHARD_CAGRA_SYNC_STEPS``
  local hops the shards exchange their current best candidates (global
  ids + traversal-space distances, optionally bf16-quantized like the
  shard merge, EQuARX-style) through the same all-gather the brute merge
  uses; each shard folds the arrivals it can resolve locally back into
  its buffer as unexplored candidates.  The cadence is fixed at trace
  time, so the number of collectives per query is static and the
  batcher's zero-recompile contract holds.

The brute-refine path stays the default (``RAFT_TPU_SHARD_CAGRA=brute``)
and the correctness control arm; ``tests/test_graph_shard.py`` holds
graph mode to the single-host walk's recall.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import env as _env
from raft_tpu.core.bitset import WORD_BITS
from raft_tpu.distance.pairwise import DISTANCE_TYPES
from raft_tpu.neighbors import cagra
from raft_tpu.neighbors._common import sorted_id_dedup
from raft_tpu.ops.matrix import select_k
from raft_tpu.serve.shard import ShardedIndex, _pack_pass_words, _place

__all__ = ["GraphShardedIndex", "partition_cagra_graph"]

#: per-shard cap on replicated halo rows (unset = keep every cross-cut
#: neighbor; 0 = no halo — local hops dead-end at the cut)
HALO_ENV = "RAFT_TPU_SHARD_CAGRA_HALO"

#: local hops between cross-shard frontier exchanges (static cadence)
SYNC_STEPS_ENV = "RAFT_TPU_SHARD_CAGRA_SYNC_STEPS"

#: sorted-gid-table padding sentinel: sorts past every real int32 id
_GID_PAD = np.int32(np.iinfo(np.int32).max)


def sync_steps_from_env() -> int:
    """Resolve ``RAFT_TPU_SHARD_CAGRA_SYNC_STEPS`` (floor 1)."""
    return max(1, int(_env.env_int(SYNC_STEPS_ENV, 4)))


def halo_cap_from_env() -> Optional[int]:
    """Resolve ``RAFT_TPU_SHARD_CAGRA_HALO`` (None = unlimited)."""
    cap = _env.env_int(HALO_ENV)
    return None if cap is None else max(0, int(cap))


def cut_labels(data: np.ndarray, n_shards: int, metric: str,
               seed: int = 0) -> np.ndarray:
    """Cluster-cut shard assignment: one balanced k-means with
    ``C = n_shards`` over (a subsample of) the dataset, then a full
    predict pass.  The same coarse clustering the IVF builds and the
    CAGRA entry-point table already use — the cut follows data geometry,
    so most graph edges stay shard-internal."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.neighbors._common import subsample_trainset

    canonical = DISTANCE_TYPES[metric]
    kb_metric = (
        "inner_product" if canonical == "inner_product" else "sqeuclidean"
    )
    n = data.shape[0]
    n_train = min(n, max(n_shards * 1024, 8192))
    train = (
        subsample_trainset(data, n_train, seed) if n_train < n
        else jnp.asarray(data)
    ).astype(jnp.float32)
    kb = kmeans_balanced.KMeansBalancedParams(
        n_iters=10, metric=kb_metric, seed=seed
    )
    centers = kmeans_balanced.fit(kb, train, n_shards)
    labels = kmeans_balanced.predict(
        centers, jnp.asarray(data, jnp.float32), metric=kb_metric
    )
    return np.asarray(labels, np.int64)


def partition_cagra_graph(
    data: np.ndarray,
    graph: np.ndarray,
    labels: np.ndarray,
    n_shards: int,
    *,
    halo_cap: Optional[int] = None,
    deleted: Optional[np.ndarray] = None,
    entry_ids: Optional[np.ndarray] = None,
) -> Tuple[Dict[str, np.ndarray], np.ndarray, Dict[str, list]]:
    """Materialize per-shard subgraphs with halo replicas (host numpy).

    Each shard owns the rows with its label; its halo is the distinct
    cross-cut neighbors of owned rows, ranked by in-degree from owned
    rows (high-traffic boundary nodes replicate first) and capped at
    ``halo_cap``.  The local id space is owned rows then halo rows, every
    shard padded to a uniform length (rows zero, ids −1, graph −1, pass
    bits clear — the traversal masks all of them).

    Returns ``(sharded-part stacks, halo_start [S], shard stats)``; the
    parts are ``rows``/``ids``/``pass_words``/``graph``/``sort_gid``/
    ``sort_lid`` (+ ``entry_lids`` when ``entry_ids`` is given).
    """
    n, d = data.shape
    deg = graph.shape[1]
    owned = [
        np.flatnonzero(labels == s).astype(np.int64) for s in range(n_shards)
    ]
    halos = []
    for s in range(n_shards):
        o = owned[s]
        ext = np.empty(0, np.int64)
        if o.size:
            nb = graph[o].ravel()
            nb = nb[(nb >= 0) & (nb < n)]
            ext = nb[labels[nb] != s]
        if ext.size:
            uniq, counts = np.unique(ext, return_counts=True)
            order = np.argsort(-counts, kind="stable")  # ties: gid asc
            h = uniq[order]
        else:
            h = np.empty(0, np.int64)
        if halo_cap is not None:
            h = h[:halo_cap]
        halos.append(np.sort(h))

    rl = max(1, max(len(o) + len(h) for o, h in zip(owned, halos)))
    rows = np.zeros((n_shards, rl, d), data.dtype)
    ids = np.full((n_shards, rl), -1, np.int32)
    lgraph = np.full((n_shards, rl, deg), -1, np.int32)
    words = np.zeros(
        (n_shards, (rl + WORD_BITS - 1) // WORD_BITS), np.uint32
    )
    sort_gid = np.full((n_shards, rl), _GID_PAD, np.int32)
    sort_lid = np.zeros((n_shards, rl), np.int32)
    halo_start = np.zeros((n_shards,), np.int32)
    elids = (
        None if entry_ids is None
        else np.full((n_shards, len(entry_ids)), -1, np.int32)
    )
    live_rows, halo_rows = [], []
    g2l = np.full((n,), -1, np.int32)
    for s in range(n_shards):
        loc = np.concatenate([owned[s], halos[s]])
        m = loc.size
        halo_start[s] = owned[s].size
        if m:
            rows[s, :m] = data[loc]
            ids[s, :m] = loc
            g2l[:] = -1
            g2l[loc] = np.arange(m, dtype=np.int32)
            sub = graph[loc]
            lgraph[s, :m] = np.where(
                (sub >= 0) & (sub < n), g2l[np.clip(sub, 0, n - 1)], -1
            )
            order = np.argsort(loc, kind="stable")
            sort_gid[s, :m] = loc[order]
            sort_lid[s, :m] = order
            if elids is not None:
                elids[s] = g2l[np.clip(entry_ids, 0, n - 1)]
        passes = np.zeros((rl,), bool)
        passes[: owned[s].size] = True
        if deleted is not None and owned[s].size:
            passes[: owned[s].size] &= ~np.asarray(deleted)[owned[s]]
        words[s] = _pack_pass_words(passes)
        live_rows.append(int(passes.sum()))
        halo_rows.append(int(halos[s].size))

    sharded = {
        "rows": rows, "ids": ids, "pass_words": words, "graph": lgraph,
        "sort_gid": sort_gid, "sort_lid": sort_lid,
    }
    if elids is not None:
        sharded["entry_lids"] = elids
    return sharded, halo_start, {"rows": live_rows, "halo": halo_rows}


class GraphShardedIndex(ShardedIndex):
    """Sharded CAGRA served by partitioned graph traversal.

    Construct through :meth:`ShardedIndex.from_index` with
    ``cagra_mode="graph"`` (or ``RAFT_TPU_SHARD_CAGRA=graph``), or through
    ``serve.build.build_sharded(kind="cagra", cagra_mode="graph")`` which
    emits the partitioned layout directly from the ring-kNN graph.

    Unfiltered searches run the halo-frontier traversal; filtered
    searches (and anything the walk cannot serve) ride the inherited
    exact brute-refine core over the same ``rows``/``ids``/``pass_words``
    parts — one layout, two engines.
    """

    graph_mode = True

    def __init__(self, comms, metric, dim, size, parts, specs, *,
                 search_params=None, merge_dtype=None, label="",
                 shard_stats=None, halo_start=None, sync_steps=4,
                 has_entries=False):
        self._halo_start = (
            np.zeros((comms.get_size(),), np.int32)
            if halo_start is None else np.asarray(halo_start, np.int32)
        )
        self._sync_steps = max(1, int(sync_steps))
        self._has_entries = bool(has_entries)
        if search_params is None:
            search_params = cagra.SearchParams()
        super().__init__(
            comms, "cagra", metric, dim, size, parts, specs,
            search_params=search_params, merge_dtype=merge_dtype,
            label=label, shard_stats=shard_stats,
        )

    # -- construction --------------------------------------------------------
    @classmethod
    def _shard_graph(cls, comms, inner, deleted, search_params,
                     merge_dtype, label) -> "GraphShardedIndex":
        """Partition a built :class:`raft_tpu.neighbors.cagra.Index`."""
        if getattr(inner, "paged", None) is not None:
            raise NotImplementedError(
                "graph-mode sharded CAGRA cannot serve a paged dataset: "
                "per-shard halo subgraphs re-index rows into local id "
                "spaces, and the paged per-DMA translation tables are "
                "keyed by global row id — halo rows would read wrong "
                "pages.  Serve paged CAGRA unsharded, or shard with "
                "RAFT_TPU_SHARD_CAGRA=brute (row-partitioned brute "
                "refine)."
            )
        if not isinstance(inner.dataset, (jax.Array, np.ndarray)):
            raise NotImplementedError(
                "graph-mode sharded CAGRA needs a dense dataset; "
                "VPQ-compressed indexes keep RAFT_TPU_SHARD_CAGRA=brute"
            )
        data = np.asarray(inner.dataset)
        graph = np.asarray(inner.graph, np.int32)
        n, d = data.shape
        s_count = comms.get_size()
        labels = cut_labels(data, s_count, inner.metric)
        entry_ids = (
            None if inner.entry_centers is None
            else np.asarray(inner.entry_ids, np.int64)
        )
        sharded, halo_start, stats = partition_cagra_graph(
            data, graph, labels, s_count,
            halo_cap=halo_cap_from_env(),
            deleted=None if deleted is None else np.asarray(deleted),
            entry_ids=entry_ids,
        )
        replicated = {}
        if entry_ids is not None:
            replicated["entry_centers"] = np.asarray(
                inner.entry_centers, np.float32
            )
        parts, specs = _place(comms, sharded=sharded, replicated=replicated)
        live = n if deleted is None else n - int(np.asarray(deleted).sum())
        return cls(
            comms, inner.metric, d, live, parts, specs,
            search_params=search_params, merge_dtype=merge_dtype,
            label=label, shard_stats=stats, halo_start=halo_start,
            sync_steps=sync_steps_from_env(),
            has_entries=entry_ids is not None,
        )

    # -- traversal configuration --------------------------------------------
    def _traverse_config(self, kk: int) -> Dict[str, object]:
        """Static per-searcher traversal plan: buffer width, hop budget,
        exchange cadence, and the fused-kernel gate — all resolved
        host-side once, so the SPMD body traces with a fixed collective
        count."""
        from raft_tpu import kernels as _kernels
        from raft_tpu.kernels.cagra_traverse import traverse_supported

        rl = int(self._parts["rows"].shape[1])
        params = self.search_params
        metric = DISTANCE_TYPES[self.metric]
        itopk = min(max(int(params.itopk_size), int(kk)), rl)
        width = max(1, int(params.search_width))
        if params.max_iterations:
            max_iter = int(params.max_iterations)
        elif self._has_entries:
            # entry-seeded walks start next to the answer (cagra.search's
            # auto budget)
            max_iter = max(8, (itopk + width - 1) // width)
        else:
            max_iter = max(16, (itopk + width - 1) // width * 2)
        sync = self._sync_steps
        rounds = max(1, -(-max_iter // sync))
        # same routing gate as cagra.search: the fused Pallas hop serves
        # dense f32/bf16 subgraphs at fold-friendly widths on TPU
        fused = (
            _kernels.use_pallas()
            and _kernels.cagra_fused_enabled()
            and traverse_supported(self._parts["rows"], itopk)
        )
        return {
            "itopk": itopk, "width": width, "metric": metric,
            "sync": sync, "rounds": rounds, "fused": fused,
            # frontier-exchange width per shard: enough to re-seed a
            # remote walk without bloating the collective
            "ex_w": min(itopk, 32),
        }

    def modeled_device_work(self, kk: int) -> Dict[str, int]:
        """Analytic per-query-per-shard distance-computation count for the
        traversal plan ``_traverse_config(kk)`` resolves: seed scoring at
        init plus ``width·deg`` candidate scores per hop.  The brute-refine
        control arm scores every resident row (``rows_len``), so
        ``rows_len / total`` is the modeled per-device work ratio."""
        cfg = self._traverse_config(kk)
        rl = int(self._parts["rows"].shape[1])
        deg = int(self._parts["graph"].shape[2])
        params = self.search_params
        n_samplings = max(1, int(params.num_random_samplings))
        if self._has_entries:
            n_centers = int(self._parts["entry_centers"].shape[0])
            s = min(max(int(params.num_entry_centers), 0), n_centers)
            seeds = s + min(rl, max(cfg["itopk"], 32) * n_samplings)
        else:
            seeds = min(rl, max(2 * cfg["itopk"], 128) * n_samplings)
        hops = int(cfg["rounds"]) * int(cfg["sync"])
        per_hop = int(cfg["width"]) * deg
        return {
            "seeds": int(seeds),
            "hops": hops,
            "per_hop": per_hop,
            "distances": int(seeds) + hops * per_hop,
            "rows_len": rl,
        }

    # -- serving -------------------------------------------------------------
    def _make_init(self, cfg):
        """Per-shard buffer init: top entry centers mapped to local ids
        (−1 where this shard holds neither the row nor a halo copy of it)
        plus a random local top-up — same seeding discipline as
        ``cagra.make_seed_ids``, in local id space."""
        names = self._names
        params = self.search_params
        has_entries = self._has_entries
        itopk, metric = cfg["itopk"], cfg["metric"]

        def init(q, *args):
            p = dict(zip(names, args))
            rows, ids = p["rows"][0], p["ids"][0]
            rl = rows.shape[0]
            nq = q.shape[0]
            seeds = []
            if has_entries:
                centers = p["entry_centers"].astype(jnp.float32)
                s = int(min(
                    max(int(params.num_entry_centers), 0), centers.shape[0]
                ))
                if s > 0:
                    seeds.append(cagra._entry_seeds(
                        q, centers, p["entry_lids"][0], s, metric
                    ))
                n_rand = min(
                    rl,
                    max(itopk, 32) * max(1, int(params.num_random_samplings)),
                )
            else:
                n_rand = min(
                    rl,
                    max(2 * itopk, 128)
                    * max(1, int(params.num_random_samplings)),
                )
            key = jax.random.PRNGKey(int(params.rand_xor_mask) & 0x7FFFFFFF)
            # the same local ids on every shard name DIFFERENT global
            # rows, so the pooled random seeds are distinct cross-shard
            # without any coordination
            seeds.append(jax.random.randint(key, (nq, n_rand), 0, rl,
                                            jnp.int32))
            lids = (
                jnp.concatenate(seeds, axis=1) if len(seeds) > 1
                else seeds[0]
            )
            # demote padding rows (id −1) and absent entry rows before
            # they can seed the buffer
            safe = jnp.clip(lids, 0, rl - 1)
            lids = jnp.where((lids >= 0) & (ids[safe] >= 0), lids, -1)
            return cagra.traverse_init(rows, q, lids, itopk, metric)

        return init

    def _make_extract(self, cfg):
        """Frontier-exchange payload: this shard's current best ``ex_w``
        candidates as (traversal-space distance, GLOBAL id), optionally
        quantized like the final merge (EQuARX-style)."""
        ex_w = cfg["ex_w"]
        merge_dtype = self.merge_dtype

        def extract(buf_d, buf_i, ids):
            rl = ids.shape[0]
            d, lid = select_k(buf_d, ex_w, select_min=True,
                              input_indices=buf_i)
            gid = jnp.where(
                lid >= 0, ids[jnp.clip(lid, 0, rl - 1)], jnp.int32(-1)
            )
            d = jnp.where(gid >= 0, d, jnp.inf)
            if merge_dtype is not None and d.dtype != merge_dtype:
                d = d.astype(merge_dtype)
            return d, gid

        return extract

    def _make_fold(self, cfg):
        """Fold the gathered cross-shard frontier back into the local
        buffer: binary-search each global id in the sorted local gid
        table, keep the ones this shard can resolve (owned or halo),
        dedup, and merge as UNEXPLORED candidates — the next super-step's
        hops expand them.  Arrivals reuse the distance computed on their
        source shard (same row, same query, same metric)."""
        itopk = cfg["itopk"]

        def fold(buf_d, buf_i, explored, gd, gg, sort_gid, sort_lid):
            rl = sort_gid.shape[0]
            pos = jnp.clip(jnp.searchsorted(sort_gid, gg), 0, rl - 1)
            present = (sort_gid[pos] == gg) & (gg >= 0)
            lid = jnp.where(present, sort_lid[pos], jnp.int32(-1))
            d = jnp.where(lid >= 0, gd.astype(jnp.float32), jnp.inf)
            # the same row can arrive from several shards (halo copies):
            # keep one
            order, dup = sorted_id_dedup(lid)
            lid_s = jnp.take_along_axis(lid, order, axis=1)
            d_s = jnp.where(
                dup, jnp.inf, jnp.take_along_axis(d, order, axis=1)
            )
            # resident buffer entries win — they carry explored flags
            in_buf = jnp.any(
                lid_s[:, :, None] == buf_i[:, None, :], axis=2
            )
            d_s = jnp.where(in_buf, jnp.inf, d_s)
            all_d = jnp.concatenate([buf_d, d_s], axis=1)
            all_i = jnp.concatenate([buf_i, lid_s], axis=1)
            all_e = jnp.concatenate(
                [explored, jnp.zeros(d_s.shape, bool)], axis=1
            )
            buf_d, mpos = select_k(all_d, itopk, select_min=True)
            buf_i = jnp.take_along_axis(all_i, mpos, axis=1)
            buf_i = jnp.where(jnp.isfinite(buf_d), buf_i, -1)
            explored = jnp.take_along_axis(all_e, mpos, axis=1)
            explored = explored | ~jnp.isfinite(buf_d)
            return buf_d, buf_i, explored

        return fold

    def _make_finalize(self, cfg, kk: int):
        """Per-shard answer: mask the buffer to owned live rows (the pass
        bitset), select the best ``kk``, translate to global ids, and
        apply the final metric transforms — the cross-shard merge's
        tie-stable select expects final-space values."""
        metric = cfg["metric"]
        merge_dtype = self.merge_dtype

        def finalize(buf_d, buf_i, ids, pass_words):
            rl = ids.shape[0]
            safe = jnp.clip(buf_i, 0, rl - 1).astype(jnp.uint32)
            word = pass_words[safe // WORD_BITS]
            bit = (word >> (safe % WORD_BITS)) & jnp.uint32(1)
            d = jnp.where((bit == 1) & (buf_i >= 0), buf_d, jnp.inf)
            gid = jnp.where(
                buf_i >= 0, ids[jnp.clip(buf_i, 0, rl - 1)], jnp.int32(-1)
            )
            v, gi = select_k(d, kk, select_min=True, input_indices=gid)
            gi = jnp.where(jnp.isfinite(v), gi, -1)
            if metric == "inner_product":
                v = -v
            elif metric == "euclidean":
                v = jnp.sqrt(jnp.maximum(v, 0.0))
            if merge_dtype is not None and v.dtype != merge_dtype:
                v = v.astype(merge_dtype)
            return v, gi

        return finalize

    def _make_local(self, k: int, kk: int, npb: int,
                    filter_bits: Optional[int] = None):
        """Graph-mode SPMD body: init → (SYNC_STEPS local hops → frontier
        all-gather → fold) × rounds → finalize → the one cross-shard
        merge.  The round loop unrolls at trace time, so the collective
        count is static — ``2·(rounds−1)`` frontier gathers plus the two
        merge gathers, every dispatch.  Filtered traffic keeps the
        inherited exact brute-refine body (the walk has no filtered leg;
        the parts serve both)."""
        if filter_bits is not None:
            return super()._make_local(k, kk, npb, filter_bits)
        cfg = self._traverse_config(kk)
        names = self._names
        comms = self.comms
        select_min = self.select_min
        # nested jit for everything but the collectives: older jax's
        # ShardMapTracer lacks the eager operator surface (same split as
        # ShardedIndex._make_local / replica.py)
        init = jax.jit(self._make_init(cfg))
        extract = jax.jit(self._make_extract(cfg))
        fold = jax.jit(self._make_fold(cfg))
        finalize = jax.jit(self._make_finalize(cfg, kk))
        steps = functools.partial(
            cagra.traverse_steps, steps=cfg["sync"], width=cfg["width"],
            metric=cfg["metric"], fused=cfg["fused"],
        )

        def _select(vg, ig):
            from raft_tpu.ops import matrix

            return matrix.select_k_stable(
                vg.astype(jnp.float32), k,
                select_min=select_min, input_indices=ig,
            )

        sel = jax.jit(_select)
        rounds = cfg["rounds"]

        def local(q, *args):
            p = dict(zip(names, args))
            rows, graph = p["rows"][0], p["graph"][0]
            state = init(q, *args)
            for r in range(rounds):
                buf_d, buf_i, explored = state
                state = steps(rows, graph, q, buf_d, buf_i, explored)
                if r + 1 < rounds:
                    buf_d, buf_i, explored = state
                    fd, fg = extract(buf_d, buf_i, p["ids"][0])
                    fdg = comms.allgather(fd, axis=1)
                    fgg = comms.allgather(fg, axis=1)
                    state = fold(
                        buf_d, buf_i, explored, fdg, fgg,
                        p["sort_gid"][0], p["sort_lid"][0],
                    )
            buf_d, buf_i, _ = state
            v, gi = finalize(buf_d, buf_i, p["ids"][0], p["pass_words"][0])
            vg = comms.allgather(v, axis=1)
            ig = comms.allgather(gi, axis=1)
            return sel(vg, ig)

        return local

    def _make_shard_search(self, kk: int, npb: int,
                           filter_bits: Optional[int] = None):
        """Exchange-free per-shard core — the full hop budget run locally
        with no collectives, same signature as the inherited brute core.
        This is what :meth:`measure_shard_skew` and the explain probe
        dispatch per shard (a collective inside would deadlock a
        single-shard replay); filtered requests return the inherited
        exact brute-refine core."""
        if filter_bits is not None:
            return super()._make_shard_search(kk, npb, filter_bits)
        cfg = self._traverse_config(kk)
        names = self._names
        init = self._make_init(cfg)
        finalize = self._make_finalize(cfg, kk)
        total = cfg["rounds"] * cfg["sync"]

        def core(q, *args):
            p = dict(zip(names, args))
            rows, graph = p["rows"][0], p["graph"][0]
            buf_d, buf_i, explored = init(q, *args)
            buf_d, buf_i, explored = cagra.traverse_steps(
                rows, graph, q, buf_d, buf_i, explored,
                steps=total, width=cfg["width"], metric=cfg["metric"],
                fused=cfg["fused"],
            )
            return finalize(
                buf_d, buf_i, p["ids"][0], p["pass_words"][0]
            )

        return core

    # -- observability -------------------------------------------------------
    def explain_contributions(self, ids) -> Dict[str, object]:
        """Per-shard counts of merged result ids under the CLUSTER cut
        (the base class's contiguous ``id // rows_per_shard`` rule does
        not apply), plus the graph-mode layout facts."""
        try:
            flat = np.asarray(ids).reshape(-1)
            flat = flat[flat >= 0]
            owner_map = self._graph_owner()
            flat = flat[flat < owner_map.shape[0]]
            owner = owner_map[flat]
            s_count = self.n_shards
            counts = np.bincount(
                owner[(owner >= 0) & (owner < s_count)], minlength=s_count
            )
            return {
                "available": True,
                "mode": "graph",
                "n_shards": s_count,
                "per_shard": [int(c) for c in counts[:s_count]],
                "owned_rows": list(self._shard_stats.get("rows", [])),
                "halo_rows": list(self._shard_stats.get("halo", [])),
                "sync_steps": int(self._sync_steps),
            }
        except Exception as exc:  # never let explain break serving
            return {"available": False, "error": repr(exc)}

    def _graph_owner(self) -> np.ndarray:
        """Cached global-id → owning-shard map from the owned prefixes of
        each shard's id row (built once, deep-explain only)."""
        owner = getattr(self, "_owner_map", None)
        if owner is None:
            ids = np.asarray(self._parts["ids"])  # raft-tpu: ignore[HOSTSYNC] deep-explain only: one-time owner-map pull, never on the hot path
            top = int(ids.max()) + 1 if ids.size else 0
            owner = np.full(max(top, 0), -1, np.int32)
            for s in range(ids.shape[0]):
                own = ids[s, : int(self._halo_start[s])]
                own = own[own >= 0]
                owner[own] = s
            self._owner_map = owner
        return owner

    def explain_traversal(self, queries, k: int = 10) -> Dict[str, object]:
        """Deep-explain traversal probe: per-shard hop budget, frontier
        exchange rounds, and halo-hit counts for one query batch.

        Replays the exchange-free per-shard core (the same hop budget the
        SPMD dispatch runs) shard by shard and counts how many of each
        shard's surviving buffer candidates are halo rows — how hard each
        query leaned on the replicated boundary.  Off the hot path by
        construction (operator / deep-explain entry); compiles and syncs
        here never touch the serving executables."""
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise ValueError(
                f"queries shape {queries.shape} vs index dim {self.dim}"
            )
        rl = int(self._parts["rows"].shape[1])
        kk = min(max(1, int(k)), rl)
        cfg = self._traverse_config(kk)
        names = self._names
        init = jax.jit(self._make_init(cfg))
        halo_hits, buffer_live = [], []
        for s in range(self.n_shards):
            args = tuple(
                self._parts[n][s : s + 1]
                if self._specs[n] and self._specs[n][0] is not None
                else self._parts[n]
                for n in names
            )
            p = dict(zip(names, args))
            buf_d, buf_i, explored = init(queries, *args)
            buf_d, buf_i, _ = cagra.traverse_steps(
                p["rows"][0], p["graph"][0], queries, buf_d, buf_i,
                explored, steps=cfg["rounds"] * cfg["sync"],
                width=cfg["width"], metric=cfg["metric"],
                fused=cfg["fused"],
            )
            lids = np.asarray(buf_i)  # raft-tpu: ignore[HOSTSYNC] deep-explain probe pull, never on the hot path
            fin = np.isfinite(np.asarray(buf_d))  # raft-tpu: ignore[HOSTSYNC] deep-explain probe pull, never on the hot path
            hs = int(self._halo_start[s])
            halo_hits.append(int(((lids >= hs) & fin).sum()))
            buffer_live.append(int(fin.sum()))
        return {
            "available": True,
            "hops": int(cfg["rounds"] * cfg["sync"]),
            "sync_steps": int(cfg["sync"]),
            "exchange_rounds": int(cfg["rounds"] - 1),
            "itopk": int(cfg["itopk"]),
            "halo_hits": halo_hits,
            "buffer_candidates": buffer_live,
        }
