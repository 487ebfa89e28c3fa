"""CAGRA: graph-based ANN index (build + batched beam search).

Reference surface: ``neighbors/cagra.cuh`` / ``cagra_types.hpp:57-142`` —
build = kNN graph via IVF-PQ-search+refine or NN-descent
(``graph_build_algo`` cagra_types.hpp:50-63), then ``sort_knn_graph`` +
2-hop detour-counting ``optimize``/prune (detail/cagra/graph_core.cuh:
130-235,322); search = beam search with a visited filter and per-query
persistent CTA kernels (detail/cagra/search_single_cta_kernel-inl.cuh:55-592,
search_multi_kernel.cuh; plan/tuning search_plan.cuh:81-164 — ``itopk_size``,
``search_width``, hashmap sizing; Python ref: pylibraft neighbors/cagra).

TPU re-design
-------------
* **Build** is batched dense ops end to end: the kNN graph comes from
  IVF-PQ search + exact refine (cagra_build.cuh:47-201), NN-descent
  (our static-shape formulation, nn_descent.py), or exact brute force for
  small sets. ``optimize`` — the detour-count prune — is a per-row
  [K, K, K] membership tensor contraction, tiled with ``lax.scan``; the
  reverse-edge pass is one sort-based scatter. No irregularity anywhere.
* **Search** replaces the per-query persistent CTA + hash-set with a
  *query-batched* beam search: state is a static [tile, itopk] candidate
  buffer with explored flags; one iteration = select_k unexplored parents
  (search_width), one gather of graph rows, one MXU distance batch, and a
  broadcast-membership dedup merge back into the buffer (plays the role
  of the reference's visited hashmap, detail/cagra/hashmap.hpp — no sorts
  in the hot loop). The whole search is one ``lax.while_loop`` inside jit
  — SURVEY §7 strategy (a).
* **Low-precision datasets** halve the search's HBM gather traffic (its
  dominant cost): pass ``dataset.astype(jnp.bfloat16)`` to ``build`` —
  the index keeps the input dtype and ``_gather_rows`` casts only the
  gathered tile to f32 (the reference's half/int8 dataset templates,
  cagra_types.hpp:142) — or ``compress()`` to VPQ for 8–16×.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core import serialize as ser
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC
from raft_tpu.neighbors import brute_force, ivf_pq, nn_descent
from raft_tpu.neighbors._common import sorted_id_dedup
from raft_tpu.neighbors.refine import refine
from raft_tpu.ops.matrix import select_k
from raft_tpu.core.trace import host_range, traced
from raft_tpu.core.logger import logger as _log

_SERIALIZATION_VERSION = 1


@dataclass
class IndexParams:
    """(ref: cagra_types.hpp:57-121 index_params)

    ``entry_points`` — size of the coarse entry-point table (a TPU-first
    addition, not in the reference's CAGRA): a small kmeans codebook whose
    nearest dataset row per centroid seeds the beam search, replacing most
    of the random-restart iterations with one MXU matmul. The walk starts
    next to the answer instead of navigating to it, which is what makes
    the query-batched formulation competitive — random-seeded beams spend
    the bulk of their iterations crossing clusters (measured round 4:
    2-3× the iterations for the same recall). ``None`` → auto
    (≈4·√n, power of two, clamped to [64, 4096]); ``0`` disables."""

    metric: str = "sqeuclidean"
    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    #: auto | ivf_pq | nn_descent | nn_descent_batch | brute_force
    #: (nn_descent_batch = out-of-core clustered graph build,
    #: ref nn_descent_batch.cuh)
    build_algo: str = "auto"
    nn_descent_niter: int = 20
    seed: int = 0
    entry_points: Optional[int] = None


@dataclass
class SearchParams:
    """(ref: cagra_types.hpp search_params / search_plan.cuh:81-164)

    ``num_entry_centers`` — how many coarse entry points seed each query's
    beam when the index carries an entry-point table (see
    IndexParams.entry_points); 0 falls back to pure random seeding.

    ``search_width`` defaults to 1 (the reference's default is 4): on the
    batched-TPU formulation every extra parent multiplies the
    per-iteration gather/score work across the whole query tile, and the
    round-4 sweeps measured width 1 strictly pareto-better at equal
    recall on both 20k and 100k workloads (wider beams pay off only on
    weakly-connected graphs — raise it together with
    num_random_samplings there)."""

    max_queries: int = 0          # 0 → auto query tile
    itopk_size: int = 64
    max_iterations: int = 0       # 0 → auto
    search_width: int = 1
    min_iterations: int = 0
    rand_xor_mask: int = 0x128394  # seed for random init candidates
    num_random_samplings: int = 1
    num_entry_centers: int = 16


@dataclass(frozen=True)
class EffortSpec:
    """Typed search-effort knobs for CAGRA (see ivf_flat.EffortSpec for
    the contract): beam size ``itopk_size`` and parent count
    ``search_width``.  The degrade ladder moves only ``itopk_size`` —
    width 1 measured pareto-better at equal recall on this formulation
    (see SearchParams.search_width), so the ladder never widens and the
    warmed variant set stays one executable per (bucket, level)."""

    itopk_size: int = 64
    search_width: int = 1

    backend: ClassVar[str] = "cagra"

    @classmethod
    def from_params(cls, params: Optional[SearchParams] = None,
                    **extra) -> "EffortSpec":
        base = params if params is not None else SearchParams()
        return cls(itopk_size=int(base.itopk_size),
                   search_width=int(base.search_width))

    def apply(self, params: Optional[SearchParams] = None) -> SearchParams:
        base = params if params is not None else SearchParams()
        return dc_replace(base, itopk_size=int(self.itopk_size),
                          search_width=int(self.search_width))

    def degraded(self, level: int) -> "EffortSpec":
        if level <= 0:
            return self
        return EffortSpec(
            itopk_size=max(32, int(self.itopk_size) >> int(level)),
            search_width=int(self.search_width),
        )

    def knobs(self):
        return {"itopk_size": int(self.itopk_size),
                "search_width": int(self.search_width)}


class Index:
    """CAGRA index: dataset + fixed-degree directed graph
    (ref: cagra_types.hpp:142 index{dataset, graph}). ``dataset`` is either
    a dense [n, d] array or a ``vpq_dataset.VpqDataset`` (the reference's
    compressed-dataset option, dataset.hpp:37-259)."""

    def __init__(self, metric: str, dataset, graph: jax.Array,
                 entry_centers: Optional[jax.Array] = None,
                 entry_ids: Optional[jax.Array] = None):
        self.metric = metric
        self.dataset = dataset
        self.graph = graph
        #: optional coarse entry-point table: [c, d] centroids + [c] id of
        #: the dataset row nearest each centroid (beam-search seeds)
        self.entry_centers = entry_centers
        self.entry_ids = entry_ids

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]


@traced("cagra.compress")
def compress(index: Index, params=None, *, res: Optional[Resources] = None) -> Index:
    """Replace the dense dataset with a VPQ-compressed one; search then
    decodes candidates on the fly and distances become approximate
    (ref: cagra index_params.compression + compute_distance_vpq.cuh)."""
    from raft_tpu.neighbors import vpq_dataset

    if not isinstance(index.dataset, jax.Array):
        raise ValueError("index dataset is already compressed")
    params = params or vpq_dataset.VpqParams()
    ds = vpq_dataset.build(params, index.dataset, res=res)
    return Index(index.metric, ds, index.graph,
                 index.entry_centers, index.entry_ids)


# --------------------------------------------------------------------------
# graph optimization (ref: detail/cagra/graph_core.cuh)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("out_degree", "tile"))
def _prune_detourable(graph: jax.Array, out_degree: int, tile: int) -> jax.Array:
    """Detour-count prune (ref: graph_core.cuh kern_prune:130-187).

    Edge (u → v=g[u,j]) is detourable through w=g[u,i] (i<j, so w is closer
    to u than v) when v also appears in w's neighbor list. Edges are ranked
    by (detour_count, original rank) and the best ``out_degree`` kept.
    """
    n, K = graph.shape

    def body(_, row0):
        rows = jnp.clip(row0 + jnp.arange(tile), 0, n - 1)
        g = graph[rows]                                   # [t, K]
        safe = jnp.clip(g, 0, n - 1)
        hop2 = graph[safe]                                # [t, K(i), K(l)]
        # match[t,i,j] = g[t,j] ∈ hop2[t,i,:]
        match = jnp.any(
            hop2[:, :, :, None] == g[:, None, None, :], axis=2
        )                                                 # [t, i, j]
        lower = jnp.tril(jnp.ones((K, K), bool), k=-1)    # i < j mask (i rows)
        detour = jnp.sum(match & lower.T[None], axis=1)   # [t, j]
        detour = jnp.where(g < 0, K + 1, detour)
        # lexicographic (detour, rank): stable sort by detour keeps rank order
        order = jnp.argsort(detour, axis=1, stable=True)
        kept = jnp.take_along_axis(g, order[:, :out_degree], axis=1)
        return _, (rows, kept)

    n_tiles = (n + tile - 1) // tile
    starts = jnp.arange(n_tiles) * tile
    _, (rows, kept) = lax.scan(body, None, starts)
    out = jnp.zeros((n, out_degree), jnp.int32)
    return out.at[rows.reshape(-1)].set(kept.reshape(-1, out_degree))


@functools.partial(jax.jit, static_argnames=("rev_cap",))
def _reverse_graph(graph: jax.Array, rev_cap: int) -> jax.Array:
    """Reverse-edge lists via one sort-based scatter
    (ref: graph_core.cuh optimize reverse pass :322)."""
    n, D = graph.shape
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, D)).ravel()
    tgt = graph.ravel()
    order = jnp.argsort(tgt, stable=True)
    tgt_s, src_s = tgt[order], src[order]
    # position within each target group = index − first index of the group
    first = jnp.searchsorted(tgt_s, tgt_s, side="left")
    pos = jnp.arange(n * D) - first
    valid = (tgt_s >= 0) & (pos < rev_cap)
    rev = jnp.full((n, rev_cap), -1, jnp.int32)
    rev = rev.at[jnp.where(valid, tgt_s, n), jnp.where(valid, pos, 0)].set(
        jnp.where(valid, src_s, -1), mode="drop"
    )
    return rev


@jax.jit
def _merge_forward_reverse(forward: jax.Array, reverse: jax.Array) -> jax.Array:
    """Final edge list: protect the best forward half, then prefer reverse
    edges over weak forward edges, order-preserving dedupe
    (ref: graph_core.cuh optimize merge, num_protected_edges = degree/2)."""
    n, D = forward.shape
    prot = (D + 1) // 2
    cand = jnp.concatenate([forward[:, :prot], reverse, forward[:, prot:]], axis=1)
    m = cand.shape[1]
    # first-occurrence flags, mapped back to the original (unsorted) layout
    order, dup_s = sorted_id_dedup(cand)
    dup = jnp.zeros((n, m), bool).at[
        jnp.arange(n)[:, None], order
    ].set(dup_s)
    bad = dup | (cand < 0)
    # stable order with dups pushed past the end
    prio = jnp.where(bad, m + jnp.arange(m)[None, :], jnp.arange(m)[None, :])
    keep = jnp.argsort(prio, axis=1, stable=True)[:, :D]
    out = jnp.take_along_axis(cand, keep, axis=1)
    # rows with < D unique candidates: backfill from forward (always unique)
    out = jnp.where(out < 0, forward, out)
    return out


@traced("cagra.optimize")
def optimize(
    knn_graph: jax.Array,
    out_degree: int,
    *,
    res: Optional[Resources] = None,
) -> jax.Array:
    """Prune an intermediate kNN graph (rows sorted by distance) to a
    fixed-degree CAGRA search graph (ref: graph_core.cuh optimize)."""
    res = ensure(res)
    knn_graph = jnp.asarray(knn_graph, jnp.int32)
    n, K = knn_graph.shape
    if out_degree > K:
        raise ValueError(f"out_degree {out_degree} > input degree {K}")
    # [t, K, K, K] bool membership tensor bounds the tile
    tile = max(1, min(n, res.workspace_rows(K * K * K, cap=256)))
    pruned = _prune_detourable(knn_graph, out_degree, tile)
    rev = _reverse_graph(pruned, out_degree)
    return _merge_forward_reverse(pruned, rev)


# --------------------------------------------------------------------------
# build (ref: detail/cagra/cagra_build.cuh)
# --------------------------------------------------------------------------

def _build_entry_points(dataset, n_entries: int, metric: str, seed: int, res):
    """Coarse entry-point table: a small balanced-kmeans codebook plus the
    id of the dataset row nearest each centroid (the beam-search seeds).
    One trainset-subsample kmeans + one brute-force 1-NN pass — O(n·c)
    MXU work at build time that removes the random-restart navigation
    iterations from every future query."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.neighbors._common import subsample_trainset

    n = dataset.shape[0]
    kb_metric = (
        "inner_product" if metric == "inner_product" else "sqeuclidean"
    )
    n_train = min(n, max(n_entries * 8, 8192))
    train = (
        subsample_trainset(dataset, n_train, seed)
        if n_train < n else jnp.asarray(dataset)
    ).astype(jnp.float32)
    kb = kmeans_balanced.KMeansBalancedParams(
        n_iters=10, metric=kb_metric, seed=seed
    )
    centers = kmeans_balanced.fit(kb, train, n_entries, res=res)
    _, ids = brute_force.knn(dataset, centers, 1, metric=metric, res=res)
    return centers, ids[:, 0].astype(jnp.int32)


def _auto_entry_points(n: int) -> int:
    """≈ 4·√n rounded up to a power of two, clamped to [64, 4096]."""
    raw = max(2.0, 4.0 * float(np.sqrt(max(n, 1))))
    return int(np.clip(1 << int(np.ceil(np.log2(raw))), 64, 4096))

def _graph_build_ivf_pq_params(params: IndexParams, n: int, d: int):
    """The internal IVF-PQ config for the knn-graph source.

    Mirrors the reference's shape (`ivf_pq::index_params::from_dataset`
    ivf_pq_types.hpp:123-136: n_lists=sqrt(n), trainset 0.1;
    cagra_build.cuh:92: n_probes=min(2*d, n_lists)) but re-tuned for the
    decoded-cache scan: our per-row scan cost is full-dimension cache
    bytes, not a 4x-compressed LUT walk, so the scanned *fraction*
    (n_probes/n_lists) is the build-time knob.  sqrt-law lists keep that
    fraction shrinking as n grows while each probe still sees enough rows
    to feed gpu_top_k candidates."""
    inter = min(params.intermediate_graph_degree, n - 1)
    n_lists = 4 if n < 10_000 else max(32, int(n**0.5))
    ip = ivf_pq.IndexParams(
        n_lists=n_lists,
        metric=params.metric,
        kmeans_trainset_fraction=1.0 if n < 10_000 else max(
            0.1, min(1.0, 128.0 * n_lists / n)),
        seed=params.seed,
    )
    # scanned fraction ~n_probes/n_lists: 32/316 at 100k (10%), 32/1000 at
    # 1M (3.2%) — graph recall is rescued by the generous candidate pool +
    # exact refine, and the n<10k brute-force path never reaches here
    sp = ivf_pq.SearchParams(n_probes=max(8, min(n_lists, 32)))
    gpu_top_k = min(n, 2 * (inter + 1))
    return ip, sp, gpu_top_k


def _graph_source_candidates(idx, sp, dataset, k: int, res) -> jax.Array:
    """[n, k] ids: every row's ``k`` nearest by the graph source's IVF-PQ
    search.  The rows are searched list by list: a tile of neighbouring
    rows probes few lists, so the probe-major scan fills its buckets and
    skips the rest of its static bucket bound.  Each row's candidates are
    the same in any order."""
    n, d = dataset.shape
    order = _list_order(idx.list_index)
    qtile = _graph_build_qtile(res, n, d)
    parts = []
    for s in range(0, n, qtile):
        rows = order[s : s + qtile]
        parts.append(ivf_pq.search(sp, idx, dataset[rows], k, res=res)[1])
    listed = jnp.concatenate(parts)
    del parts
    at = np.empty(n, np.int32)
    at[order] = np.arange(n, dtype=np.int32)   # row -> its place in order
    return listed[at]


def _list_order(list_index: jax.Array) -> np.ndarray:
    """The row ids in the order the IVF lists hold them (each row sits in
    exactly one list)."""
    ids = np.asarray(list_index).reshape(-1)
    return ids[ids >= 0].astype(np.int32)


def _graph_build_qtile(res, n: int, d: int) -> int:
    """Row-query tile for the search-all-rows graph stage (bounded by the
    per-query candidate workspace)."""
    return max(1, res.workspace_rows(4 * n // 64 + 4 * d, cap=8192))


@traced("cagra.build")
def build(
    params: IndexParams,
    dataset: jax.Array,
    *,
    res: Optional[Resources] = None,
) -> Index:
    """(ref: cagra_build.cuh build: build_knn_graph → sort → optimize)"""
    res = ensure(res)
    # keep the dataset in its input dtype (f32/bf16/int8/uint8 — ref CAGRA
    # dtype templates cagra_types.hpp:142); search casts gathered rows
    # only. A host numpy dataset stays host-side until after the graph
    # build so the out-of-core path (nn_descent_batch) never uploads it
    # wholesale; the final index upload happens once, below.
    if not isinstance(dataset, np.ndarray):
        dataset = jnp.asarray(dataset)
    n, d = dataset.shape
    metric = DISTANCE_TYPES[params.metric]
    if metric not in ("sqeuclidean", "euclidean", "inner_product"):
        raise ValueError(f"cagra supports L2/IP metrics, got {params.metric}")
    inter = min(params.intermediate_graph_degree, n - 1)
    degree = min(params.graph_degree, inter)

    algo = params.build_algo
    if algo == "auto":
        # TPU-first threshold (round-5 CAGRA build-time work, VERDICT r4
        # next #4): an exact tiled kNN graph at n=100k, d=96 is ~2 TFLOP
        # of pure MXU work — cheaper than the ivf_pq build+search+refine
        # pipeline it replaces (measured 80% of the 196 s on-chip build)
        # and yields an exact graph.  On host backends the crossover
        # stays at 8k (a single-core 100k brute scan is minutes).
        brute_cap = 131_072 if jax.default_backend() == "tpu" else 8192
        algo = "brute_force" if n <= brute_cap else "ivf_pq"

    if algo == "brute_force":
        g = nn_descent.build_exact(dataset, inter, metric=params.metric, res=res)
        knn_graph = g.graph
    elif algo in ("nn_descent", "nn_descent_batch"):
        nnd = nn_descent.IndexParams(
            graph_degree=inter,
            intermediate_graph_degree=min(n - 1, max(inter + inter // 2, inter + 8)),
            max_iterations=params.nn_descent_niter,
            metric=params.metric,
            seed=params.seed,
        )
        if algo == "nn_descent_batch":
            # out-of-core graph build: clustered per-batch GNND + merge
            # (ref: nn_descent_batch.cuh — datasets beyond device memory)
            knn_graph = nn_descent.build_batch(
                nnd, np.asarray(dataset), res=res
            ).graph
        else:
            knn_graph = nn_descent.build(nnd, dataset, res=res).graph
    elif algo == "ivf_pq":
        # ref cagra_build.cuh:47-201: ivf_pq build → per-row search with
        # gpu_top_k = degree * refine_rate → exact refine → drop self
        ip, sp, gpu_top_k = _graph_build_ivf_pq_params(params, n, d)
        idx = ivf_pq.build(ip, dataset, res=res)
        cands = _graph_source_candidates(idx, sp, dataset, gpu_top_k, res)
        _, knn_graph = refine(
            dataset, dataset, cands, inter + 1, metric=params.metric, res=res
        )
        # drop the self column wherever it landed
        self_col = knn_graph == jnp.arange(n, dtype=knn_graph.dtype)[:, None]
        order = jnp.argsort(self_col, axis=1, stable=True)
        knn_graph = jnp.take_along_axis(knn_graph, order, axis=1)[:, :inter]
    else:
        raise ValueError(f"unknown build_algo {params.build_algo}")

    return finalize_index(params, dataset, knn_graph, res=res)


def finalize_index(params: IndexParams, dataset, knn_graph,
                   *, res: Optional[Resources] = None) -> Index:
    """Shared index finalization (single-device ``build`` AND the MNMG
    ``comms.distributed.sharded_cagra_build``): optimize the kNN graph to
    the output degree, upload the dataset ONCE in its input dtype, build
    the coarse entry-point table."""
    res = ensure(res)
    n = dataset.shape[0]
    metric = DISTANCE_TYPES[params.metric]
    inter = min(params.intermediate_graph_degree, n - 1)
    degree = min(params.graph_degree, inter)
    graph = optimize(jnp.asarray(knn_graph, jnp.int32), degree, res=res)
    # the index itself is device-resident (search gathers from it); a
    # host build input uploads exactly once, here
    dataset = jnp.asarray(dataset)
    n_entries = params.entry_points
    if n_entries is None:
        n_entries = _auto_entry_points(n)
    n_entries = min(n_entries, n)
    entry_centers = entry_ids = None
    if n_entries:
        entry_centers, entry_ids = _build_entry_points(
            dataset, n_entries, metric, params.seed, res
        )
    _log.debug(
        "cagra.finalize: n=%d degree=%d dtype=%s entries=%d",
        n, graph.shape[1], dataset.dtype, n_entries,
    )
    return Index(params.metric, dataset, graph, entry_centers, entry_ids)


def from_graph(metric: str, dataset: jax.Array, graph: jax.Array,
               entry_centers: Optional[jax.Array] = None,
               entry_ids: Optional[jax.Array] = None) -> Index:
    """Construct an index from a prebuilt graph (ref: cagra index ctor from
    existing dataset+graph mdspans, cagra_types.hpp:142)."""
    return Index(
        metric, jnp.asarray(dataset), jnp.asarray(graph, jnp.int32),
        None if entry_centers is None else jnp.asarray(entry_centers),
        None if entry_ids is None else jnp.asarray(entry_ids, jnp.int32),
    )


# --------------------------------------------------------------------------
# search (ref: detail/cagra/search_single_cta_kernel-inl.cuh, TPU-batched)
# --------------------------------------------------------------------------

def make_seed_ids(params: SearchParams, index: Index, queries: jax.Array,
                  k: int, itopk: Optional[int] = None) -> jax.Array:
    """Init candidates for a query batch ([q, s] dataset row ids): the
    coarse entry points (when the index carries them) + a random top-up
    (the rescue knob for weakly-connected graphs, scaled by
    num_random_samplings). Factored out of :func:`search` so the sharded
    search can seed the FULL batch once and split it with the queries —
    per-query results then don't depend on how the batch was sharded.

    This function OWNS the base itopk formula (``itopk`` overrides it for
    callers that widen the buffer, e.g. filtered search) — one owner, so
    the sharded and single-device seed pools cannot drift."""
    if itopk is None:
        itopk = min(max(params.itopk_size, k), index.size)
    n = index.size
    metric = DISTANCE_TYPES[index.metric]
    q = queries.shape[0]
    use_entries = (
        index.entry_centers is not None and params.num_entry_centers > 0
    )
    if use_entries:
        s = int(min(params.num_entry_centers, index.entry_centers.shape[0]))
        entry = _entry_seeds(
            jnp.asarray(queries, jnp.float32),
            index.entry_centers.astype(jnp.float32),
            index.entry_ids, s, metric,
        )
        n_rand = min(
            n, max(itopk, 32) * max(1, params.num_random_samplings)
        )
    else:
        entry = None
        n_rand = min(n, max(2 * itopk, 128) * max(1, params.num_random_samplings))
    key = jax.random.PRNGKey(params.rand_xor_mask & 0x7FFFFFFF)
    seed_ids = jax.random.randint(key, (q, n_rand), 0, n, jnp.int32)
    if entry is not None:
        seed_ids = jnp.concatenate([entry, seed_ids], axis=1)
    return seed_ids


@functools.partial(jax.jit, static_argnames=("s", "metric"))
def _entry_seeds(queries, centers, entry_ids, s: int, metric: str):
    """Top-``s`` coarse entry points per query — one MXU matmul + select_k
    (the IVF coarse-selection shape). Returns seed ids [q, s]."""
    if metric == "inner_product":
        sc = -jnp.matmul(queries, centers.T, precision=_PREC)
    else:
        c2 = jnp.sum(centers * centers, axis=1)
        sc = c2[None, :] - 2.0 * jnp.matmul(queries, centers.T, precision=_PREC)
    _, top = select_k(sc, s, select_min=True)
    return entry_ids[top]


def _query_distance(qs: jax.Array, vecs: jax.Array, metric: str) -> jax.Array:
    """dist(qs[i], vecs[i, j]) — [t, d] vs [t, c, d]."""
    ip = jnp.einsum("td,tcd->tc", qs, vecs, precision=_PREC)
    if metric == "inner_product":
        return -ip
    v2 = jnp.sum(vecs * vecs, axis=2)
    q2 = jnp.sum(qs * qs, axis=1)
    return jnp.maximum(q2[:, None] + v2 - 2.0 * ip, 0.0)


def _gather_rows(dataset, ids):
    """Candidate-row gather: dense take or VPQ decode-on-gather
    (ref: compute_distance_vpq.cuh decodes codes inside the kernel).
    Returns f32 — the cast runs on the gathered tile only, so a
    low-precision dataset is never copied whole to fp32."""
    if isinstance(dataset, jax.Array):
        return dataset[jnp.clip(ids, 0, dataset.shape[0] - 1)].astype(jnp.float32)
    return dataset.decode(ids)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "itopk", "width", "max_iter", "min_iter", "metric", "tile",
        "fused",
    ),
)
def _search_jit(
    dataset, graph, queries, filter_words, seed_ids,
    k: int, itopk: int, width: int, max_iter: int, min_iter: int,
    metric: str, tile: int, fused: bool = False,
):
    n, d = dataset.shape
    deg = graph.shape[1]
    q = queries.shape[0]
    n_tiles = (q + tile - 1) // tile
    pad = n_tiles * tile - q
    qt = jnp.pad(queries, ((0, pad), (0, 0))).reshape(n_tiles, tile, d)
    st = jnp.pad(seed_ids, ((0, pad), (0, 0))).reshape(n_tiles, tile, -1)
    # per-row filters (ragged batches) tile alongside the queries; ndim is
    # static in trace so the branch costs nothing at runtime
    per_row = filter_words is not None and filter_words.ndim == 2
    if per_row:
        ft = jnp.pad(filter_words, ((0, pad), (0, 0))).reshape(
            n_tiles, tile, -1
        )
    else:
        ft = jnp.zeros((n_tiles, 1, 1), jnp.uint32)  # unused carrier

    def one_tile(args):
        qs, seeds, fw_t = args                            # [t, d], [t, s]

        def filt_inf(ids, dists):
            if filter_words is None:
                return dists
            safe = jnp.clip(ids, 0, None)
            if per_row:
                word = jnp.take_along_axis(fw_t, safe // 32, axis=1)
            else:
                word = filter_words[safe // 32]
            bit = (word >> (safe % 32).astype(jnp.uint32)) & 1
            return jnp.where(bit == 0, jnp.inf, dists)

        # ---- random init (ref: random_samplings init of itopk candidates)
        vecs = _gather_rows(dataset, seeds)
        dists = _query_distance(qs, vecs, metric)
        dists = jnp.where(seeds < 0, jnp.inf, dists)
        # dedupe seeds, take itopk best
        order, dup = sorted_id_dedup(seeds)
        s_ids = jnp.take_along_axis(seeds, order, axis=1)
        s_d = jnp.where(dup, jnp.inf, jnp.take_along_axis(dists, order, axis=1))
        buf_d, buf_i = select_k(s_d, itopk, select_min=True, input_indices=s_ids)
        # inf slots must not retain a real id: it would shadow (dedup-demote)
        # a later finite copy of the same node forever
        buf_i = jnp.where(jnp.isfinite(buf_d), buf_i, -1)
        explored = jnp.zeros((tile, itopk), bool)
        # result buffer: best-k *filter-passing* candidates seen so far.
        # Traversal itself stays unfiltered — filtered-out nodes still route
        # the walk (ref: CAGRA filtering excludes hits from the result list,
        # not from graph navigation).
        res_d, res_i = select_k(
            filt_inf(buf_i, buf_d), k, select_min=True, input_indices=buf_i
        )
        res_i = jnp.where(jnp.isfinite(res_d), res_i, -1)

        def cond(state):
            it, buf_i, buf_d, explored, res_i, res_d, _ = state
            frontier = ~explored & jnp.isfinite(buf_d)
            return (it < min_iter) | ((it < max_iter) & jnp.any(frontier))

        # strict-upper-triangular mask: earlier[i, j] ⇔ i < j (used to
        # demote later copies of an id within one candidate batch)
        c_w = width * deg
        earlier = jnp.triu(jnp.ones((c_w, c_w), bool), k=1)

        def body(state):
            it, buf_i, buf_d, explored, res_i, res_d, expanded = state
            # ---- pick search_width best unexplored parents
            # (ref: pickup_next_parents search_single_cta_kernel-inl.cuh:55)
            front_d = jnp.where(explored | ~jnp.isfinite(buf_d), jnp.inf, buf_d)
            _, ppos = select_k(front_d, width, select_min=True)
            parent_ok = jnp.take_along_axis(front_d, ppos, axis=1) < jnp.inf
            parents = jnp.take_along_axis(buf_i, ppos, axis=1)    # [t, w]
            explored = explored.at[
                jnp.arange(tile)[:, None], ppos
            ].set(True)
            # the walk's work: (query, parent) pairs with a real parent
            expanded = expanded + jnp.sum(parent_ok, axis=1, dtype=jnp.int32)
            if fused:
                # ---- fused hop: expand + score + dedup + merge ride one
                # Pallas kernel (kernels/cagra_traverse.py). Only the tiny
                # [t, w] neighbor-id gather stays in XLA (it doubles as the
                # kernel's scalar-prefetch operand); the [t, w·deg, d] row
                # gather, the O(c²) dedup, and the itopk merge sort never
                # materialize in HBM. The gate in search() keeps filtered
                # traffic on the XLA body (res-buffer side-merge below).
                from raft_tpu.kernels import interpret_mode
                from raft_tpu.kernels.cagra_traverse import cagra_fused_hop

                parents_m = jnp.where(parent_ok, parents, -1)
                buf_d, buf_i, explored = cagra_fused_hop(
                    dataset, graph, qs, parents_m, buf_d, buf_i, explored,
                    metric=metric, interpret=interpret_mode(),
                )
                return (it + 1, buf_i, buf_d, explored, res_i, res_d,
                        expanded)
            # ---- expand: gather graph rows (the data-dependent gather)
            nbrs = graph[jnp.clip(parents, 0, n - 1)]             # [t, w, deg]
            nbrs = jnp.where(parent_ok[:, :, None], nbrs, -1)
            cand = nbrs.reshape(tile, width * deg)
            vecs = _gather_rows(dataset, cand)                    # [t, w*deg, d]
            cd = _query_distance(qs, vecs, metric)
            cd = jnp.where(cand < 0, jnp.inf, cd)
            # ---- dedup by broadcast membership instead of sort: the hot
            # loop's visited-hashmap role (detail/cagra/hashmap.hpp) is two
            # O(c²)/O(c·itopk) VPU compares — cheap, fused, and free of the
            # multi-pass bitonic sorts the sorted-id dedup cost per
            # iteration. A candidate is demoted to inf if (a) an earlier
            # slot in this batch carries the same id, or (b) the id already
            # sits in the buffer (whose copy keeps its explored flag).
            dup_in_batch = jnp.any(
                (cand[:, :, None] == cand[:, None, :]) & earlier[None], axis=1
            )                                                     # [t, c]
            in_buf = jnp.any(cand[:, :, None] == buf_i[:, None, :], axis=2)
            cd = jnp.where(dup_in_batch | in_buf, jnp.inf, cd)
            # ---- fold filter-passing candidates into the result buffer.
            # Any node already in buf was offered to the result buffer when
            # first encountered, so the mask above cannot lose hits.
            if filter_words is not None:
                # res can hold ids long evicted from buf → its own
                # membership mask keeps the result buffer duplicate-free
                in_res = jnp.any(
                    cand[:, :, None] == res_i[:, None, :], axis=2
                )
                m_i = jnp.concatenate([res_i, cand], axis=1)
                m_d = jnp.concatenate(
                    [res_d, jnp.where(in_res, jnp.inf, filt_inf(cand, cd))],
                    axis=1,
                )
                res_d, res_i = select_k(
                    m_d, k, select_min=True, input_indices=m_i
                )
                res_i = jnp.where(jnp.isfinite(res_d), res_i, -1)
            # ---- merge into the candidate buffer (ids are now unique)
            all_i = jnp.concatenate([buf_i, cand], axis=1)
            all_d = jnp.concatenate([buf_d, cd], axis=1)
            all_e = jnp.concatenate(
                [explored, jnp.zeros((tile, width * deg), bool)], axis=1
            )
            buf_d, pos = select_k(all_d, itopk, select_min=True)
            buf_i = jnp.take_along_axis(all_i, pos, axis=1)
            buf_i = jnp.where(jnp.isfinite(buf_d), buf_i, -1)
            explored = jnp.take_along_axis(all_e, pos, axis=1)
            explored = explored | ~jnp.isfinite(buf_d)
            return (it + 1, buf_i, buf_d, explored, res_i, res_d,
                    expanded)

        it, buf_i, buf_d, _, res_i, res_d, expanded = lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), buf_i, buf_d, explored, res_i, res_d,
             jnp.zeros((tile,), jnp.int32)),
        )
        if filter_words is None:
            v, i = select_k(buf_d, k, select_min=True, input_indices=buf_i)
        else:
            # result buffer may hold duplicate ids past the frontier (see
            # body); one final dedup pass cleans them
            order, dup = sorted_id_dedup(res_i)
            s_i = jnp.take_along_axis(res_i, order, axis=1)
            s_d = jnp.where(dup, jnp.inf, jnp.take_along_axis(res_d, order, axis=1))
            v, i = select_k(s_d, k, select_min=True, input_indices=s_i)
        i = jnp.where(jnp.isfinite(v), i, -1)
        if metric == "inner_product":
            v = -v
        elif metric == "euclidean":
            v = jnp.sqrt(jnp.maximum(v, 0.0))
        return v, i, expanded, it

    vals, idx, expanded, trips = lax.map(one_tile, (qt, st, ft))
    # work counts ride out beside the answers: each query's expanded
    # parents, then each tile's loop trips (kernels.stamp_search_work)
    work = jnp.concatenate([expanded.reshape(-1)[:q], trips])
    return vals.reshape(-1, k)[:q], idx.reshape(-1, k)[:q], work


# --------------------------------------------------------------------------
# fixed-step traversal pieces (raft_tpu.serve.graph_shard: sharded graph
# mode drives the hop loop itself, pausing every SYNC_STEPS hops for a
# cross-shard frontier exchange)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("itopk", "metric"))
def traverse_init(dataset, queries, seed_ids, itopk: int, metric: str):
    """Candidate-buffer init from seed ids — the seed half of
    ``_search_jit``, factored out for callers that own the hop loop.
    Returns ``(buf_d, buf_i, explored)`` holding the buffer invariant
    (``buf_i == -1`` wherever ``buf_d == +inf``; nothing explored)."""
    vecs = _gather_rows(dataset, seed_ids)
    dists = _query_distance(queries, vecs, metric)
    dists = jnp.where(seed_ids < 0, jnp.inf, dists)
    order, dup = sorted_id_dedup(seed_ids)
    s_ids = jnp.take_along_axis(seed_ids, order, axis=1)
    s_d = jnp.where(dup, jnp.inf, jnp.take_along_axis(dists, order, axis=1))
    buf_d, buf_i = select_k(s_d, itopk, select_min=True, input_indices=s_ids)
    buf_i = jnp.where(jnp.isfinite(buf_d), buf_i, -1)
    explored = jnp.zeros(buf_d.shape, bool)
    return buf_d, buf_i, explored


@functools.partial(
    jax.jit, static_argnames=("steps", "width", "metric", "fused")
)
def traverse_steps(dataset, graph, queries, buf_d, buf_i, explored,
                   steps: int, width: int, metric: str, fused: bool = False):
    """``steps`` unfiltered beam-search hops — ``_search_jit``'s loop body
    as a standalone fixed-trip loop over ``(buf_d, buf_i, explored)``.

    An exhausted frontier makes remaining hops no-ops (every parent slot
    reads +inf, parents mask to −1, candidate scores stay +inf), so the
    fixed trip count is always safe; that is what keeps the sharded graph
    traversal's per-query collective count static and recompile-free.
    ``graph`` may contain −1 entries (missing halo neighbors): both the
    XLA body and the fused Pallas hop mask negative candidate ids.
    ``fused`` must only be set when the caller verified
    ``traverse_supported(dataset, itopk)`` — same gate as :func:`search`.
    """
    n = dataset.shape[0]
    deg = graph.shape[1]
    tile, itopk = buf_d.shape
    c_w = width * deg
    earlier = jnp.triu(jnp.ones((c_w, c_w), bool), k=1)

    def body(_, state):
        buf_i, buf_d, explored = state
        front_d = jnp.where(explored | ~jnp.isfinite(buf_d), jnp.inf, buf_d)
        _, ppos = select_k(front_d, width, select_min=True)
        parent_ok = jnp.take_along_axis(front_d, ppos, axis=1) < jnp.inf
        parents = jnp.take_along_axis(buf_i, ppos, axis=1)
        explored = explored.at[jnp.arange(tile)[:, None], ppos].set(True)
        if fused:
            from raft_tpu.kernels import interpret_mode
            from raft_tpu.kernels.cagra_traverse import cagra_fused_hop

            parents_m = jnp.where(parent_ok, parents, -1)
            buf_d, buf_i, explored = cagra_fused_hop(
                dataset, graph, queries, parents_m, buf_d, buf_i, explored,
                metric=metric, interpret=interpret_mode(),
            )
            return buf_i, buf_d, explored
        nbrs = graph[jnp.clip(parents, 0, n - 1)]
        nbrs = jnp.where(parent_ok[:, :, None], nbrs, -1)
        cand = nbrs.reshape(tile, c_w)
        vecs = _gather_rows(dataset, cand)
        cd = _query_distance(queries, vecs, metric)
        cd = jnp.where(cand < 0, jnp.inf, cd)
        dup_in_batch = jnp.any(
            (cand[:, :, None] == cand[:, None, :]) & earlier[None], axis=1
        )
        in_buf = jnp.any(cand[:, :, None] == buf_i[:, None, :], axis=2)
        cd = jnp.where(dup_in_batch | in_buf, jnp.inf, cd)
        all_i = jnp.concatenate([buf_i, cand], axis=1)
        all_d = jnp.concatenate([buf_d, cd], axis=1)
        all_e = jnp.concatenate(
            [explored, jnp.zeros((tile, c_w), bool)], axis=1
        )
        buf_d, pos = select_k(all_d, itopk, select_min=True)
        buf_i = jnp.take_along_axis(all_i, pos, axis=1)
        buf_i = jnp.where(jnp.isfinite(buf_d), buf_i, -1)
        explored = jnp.take_along_axis(all_e, pos, axis=1)
        explored = explored | ~jnp.isfinite(buf_d)
        return buf_i, buf_d, explored

    buf_i, buf_d, explored = lax.fori_loop(
        0, steps, body, (buf_i, buf_d, explored)
    )
    return buf_d, buf_i, explored


@traced("cagra.search")
def search(
    params: SearchParams,
    index: Index,
    queries: jax.Array,
    k: int,
    *,
    sample_filter: Optional[Bitset] = None,
    deleted_mask: Optional[Bitset] = None,
    res: Optional[Resources] = None,
    seed_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Batched beam search (ref: cagra_search.cuh → single-CTA kernel,
    re-expressed as query-batched iterations). Returns
    (distances [q, k], indices [q, k]).

    ``seed_ids`` overrides init-candidate generation ([q, s] dataset row
    ids) — the seam the sharded search uses so per-query results are
    bit-identical regardless of how the query batch is split.

    ``deleted_mask`` excludes set bits (tombstones, raft_tpu.serve) and
    composes with ``sample_filter`` (pass-bits kept).

    The walk's work — each query's expanded (query, parent) pairs and
    each query tile's loop trips — comes out of the same program and is
    stamped with ``kernels.stamp_search_work`` for the serving batcher,
    which reads it in its copy-out."""
    res = ensure(res)
    from raft_tpu.neighbors._common import resolve_pass_filter

    sample_filter = resolve_pass_filter(sample_filter, deleted_mask)
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != index.dim:
        raise ValueError(f"queries shape {queries.shape} vs index dim {index.dim}")
    n = index.size
    metric = DISTANCE_TYPES[index.metric]
    itopk = min(max(params.itopk_size, k), n)
    if sample_filter is not None:
        # widen the internal buffer by the filter's inverse pass rate so the
        # beam still meets ~itopk allowed nodes (the reference ecosystem's
        # filtered search similarly grows its workload; heavy filters
        # otherwise starve the result list). Rounded to a power of two to
        # bound recompilation to O(log n) shape buckets.
        passing = max(1, int(sample_filter.count()))
        scale = min(32.0, max(1.0, n / passing))
        widened = min(n, int(itopk * scale))
        itopk = 1 << (widened - 1).bit_length()
        itopk = min(itopk, n)
    width = params.search_width
    deg = index.graph_degree
    q = queries.shape[0]
    use_entries = (
        index.entry_centers is not None and params.num_entry_centers > 0
    )
    # ref search_plan.cuh: auto max_iterations scales with itopk/width.
    # Entry-seeded walks start next to the answer and need roughly half
    # the navigation budget of random-restart walks (round-4 sweep).
    if params.max_iterations:
        max_iter = params.max_iterations
    elif use_entries:
        max_iter = max(8, (itopk + width - 1) // width)
    else:
        max_iter = max(16, (itopk + width - 1) // width * 2)
    min_iter = min(params.min_iterations, max_iter)

    # init candidates: coarse entry points when the index carries them
    # (one MXU matmul replaces most of the random-restart navigation),
    # topped up with random seeds for graphs/queries the coarse table
    # mis-covers (ref rand_xor_mask seeds + num_random_samplings).
    if seed_ids is None:
        with host_range("cagra.seed"):
            seed_ids = make_seed_ids(params, index, queries, k, itopk=itopk)
    else:
        seed_ids = jnp.asarray(seed_ids, jnp.int32)

    per_q = 4 * (width * deg) * (index.dim + 4) + 16 * itopk
    tile = params.max_queries or max(1, min(max(q, 1), res.workspace_rows(per_q, cap=512)))
    fw = sample_filter.words if sample_filter is not None else None
    if fw is not None and fw.ndim == 2 and fw.shape[0] != q:
        raise ValueError(
            f"row filter has {fw.shape[0]} rows for {q} queries"
        )
    # fused-hop gate: filtered traffic keeps the XLA body (the res-buffer
    # side-merge has no kernel leg), as do compressed datasets and
    # out-of-envelope itopk.  RAFT_TPU_PALLAS_CAGRA=0 reverts just this
    # kernel without losing the rest of the Pallas fleet.
    from raft_tpu import kernels as _kernels
    from raft_tpu.kernels.cagra_traverse import traverse_supported

    # paged index: beam-search gathers are graph-hop-dependent, so no
    # probe-keyed prefetch exists — the whole dataset must sit in the hot
    # pool (identity-pinned once; BudgetExceeded from pin_identity
    # otherwise — raise the budget, or serve over-HBM payloads from the
    # IVF backends whose working set is probe-bounded)
    paged = getattr(index, "paged", None)
    if paged is not None:
        from raft_tpu.store.paged import PagedRows

        paged.pin_identity()
        pool, page_slot = paged.view()
        dataset = PagedRows(pool, page_slot, index.size)
    else:
        dataset = index.dataset

    fused = (
        fw is None
        and _kernels.use_pallas()
        and _kernels.cagra_fused_enabled()
        and traverse_supported(dataset, itopk)
    )
    _kernels.stamp_kernel_path("pallas" if fused else "xla")
    v, i, work = _search_jit(
        dataset, index.graph, queries, fw, seed_ids,
        int(k), int(itopk), int(width), int(max_iter), int(min_iter),
        metric, int(tile), fused=fused,
    )
    _kernels.stamp_search_work(work, q)
    return v, i


# --------------------------------------------------------------------------
# serialization (ref: detail/cagra/cagra_serialize.cuh)
# --------------------------------------------------------------------------

@traced("cagra.save")
def save(filename: str, index: Index, *, include_dataset: bool = True) -> None:
    from raft_tpu.neighbors.vpq_dataset import VpqDataset

    arrays = {"graph": index.graph}
    if index.entry_centers is not None:
        arrays["entry_centers"] = index.entry_centers
        arrays["entry_ids"] = index.entry_ids
    kind = "none"
    if include_dataset:
        if isinstance(index.dataset, VpqDataset):
            kind = "vpq"
            arrays.update(
                vq_centers=index.dataset.vq_centers,
                pq_codebook=index.dataset.pq_codebook,
                vq_codes=index.dataset.vq_codes,
                pq_codes=index.dataset.pq_codes,
            )
        else:
            kind = "dense"
            arrays["dataset"] = index.dataset
    ser.save_tree(
        filename, "cagra", _SERIALIZATION_VERSION,
        {
            "metric": index.metric,
            "dataset_kind": kind,
            "dim": int(index.dim),
            # kept for format compatibility with earlier files
            "include_dataset": int(include_dataset),
        },
        arrays,
    )


@traced("cagra.load")
def load(filename: str, *, dataset: Optional[jax.Array] = None) -> Index:
    from raft_tpu.neighbors.vpq_dataset import VpqDataset

    scalars, arrays = ser.load_tree(filename, "cagra", _SERIALIZATION_VERSION)
    kind = scalars.get("dataset_kind", "dense" if scalars["include_dataset"] else "none")
    if kind == "dense":
        ds = jnp.asarray(arrays["dataset"])
    elif kind == "vpq":
        ds = VpqDataset(
            jnp.asarray(arrays["vq_centers"]),
            jnp.asarray(arrays["pq_codebook"]),
            jnp.asarray(arrays["vq_codes"]),
            jnp.asarray(arrays["pq_codes"]),
            int(scalars["dim"]),
        )
    elif dataset is not None:
        ds = jnp.asarray(dataset, jnp.float32)
    else:
        raise ValueError("index was saved without dataset; pass dataset=")
    ec = arrays.get("entry_centers")
    ei = arrays.get("entry_ids")
    return Index(
        scalars["metric"], ds, jnp.asarray(arrays["graph"]),
        None if ec is None else jnp.asarray(ec),
        None if ei is None else jnp.asarray(ei, jnp.int32),
    )
