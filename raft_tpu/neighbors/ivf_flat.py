"""IVF-Flat: inverted-file index with uncompressed vectors.

Reference: balanced-kmeans coarse quantizer + per-list vector storage,
build/extend/search/serialize (ref: cpp/include/raft/neighbors/ivf_flat_types.hpp:47-284
— params ``n_lists=1024``, ``kmeans_n_iters=20``, ``kmeans_trainset_fraction``,
``adaptive_centers``; build pipeline neighbors/detail/ivf_flat_build.cuh:344;
search = coarse select then fused interleaved scan then select_k,
neighbors/detail/ivf_flat_search-inl.cuh:40-271; Python ref:
pylibraft.neighbors.ivf_flat).

TPU re-design of the storage layout: the reference interleaves each list in
groups of 32 vectors × veclen for warp-coalesced scans
(ivf_flat_build.cuh:88-154). On TPU the equivalent is a *dense padded tensor*
``list_data [n_lists, list_cap, dim]`` — every list padded to one static
capacity so the probe scan is a single gather + batched contraction with a
validity mask, fully static-shaped for XLA. Balanced kmeans keeps
``list_cap`` within a small factor of the mean list size, bounding the
padding waste; capacity rounds up to the TPU sublane multiple (8).

Search: (1) coarse: queries×centersᵀ matmul + top-n_probes (pure MXU);
(2) gather probed lists and compute per-candidate distances with the same
Gram decomposition used everywhere (‖y‖² precomputed per stored vector);
(3) masked select_k over [n_probes × list_cap] candidates.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core import serialize as ser
from raft_tpu.core import validation
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC
from raft_tpu.neighbors._common import (
    allocate_append_slots,
    centroid_group_inverse,
    compute_list_layout,
    subsample_trainset,
    coarse_select,
    invalid_mask,
    invalid_mask_rows,
    default_max_cap,
    merge_split_lists,
    pallas_scan_enabled,
    run_probe_major,
    run_query_tiled,
    select_scan_strategy,
    unpack_lists,
)
from raft_tpu.kernels import stamp_kernel_path as _stamp_kernel_path
from raft_tpu.ops.matrix import select_k
from raft_tpu.store.paged import gather_lists as _gather_lists
from raft_tpu.core.trace import traced
from raft_tpu.core.logger import logger as _log

_SERIALIZATION_VERSION = 1


@dataclass
class IndexParams:
    """(ref: ivf_flat_types.hpp:47 index_params)"""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    add_data_on_build: bool = True
    conservative_memory_allocation: bool = False  # ref ivf_flat_types.hpp
    seed: int = 0


@dataclass
class SearchParams:
    """(ref: ivf_flat_types.hpp search_params — n_probes). ``strategy``
    selects the scan schedule — see ivf_pq.SearchParams.strategy (shared
    probe-major machinery, _common.invert_probes)."""

    n_probes: int = 20
    strategy: str = "auto"  # auto | query_major | probe_major


@dataclass(frozen=True)
class EffortSpec:
    """Typed search-effort knobs for IVF-Flat — the values an actuator
    (overload ladder, SLO autotuner) may move at serve time.

    Every knob is a host Python value that selects among *already
    compiled* executables: the serving warmup ladder precompiles one
    variant per (bucket, effort level), so stepping effort re-dispatches
    a warmed executable and never appears as a new static jit argument
    (the RECOMPILE rule enforces this).  ``refine_ratio`` is an offline
    sweep knob — the bench harness searches ``k × ratio`` candidates and
    exact-refines; online actuation maps only the SearchParams fields.
    """

    n_probes: int = 20
    refine_ratio: int = 1

    backend: ClassVar[str] = "ivf_flat"

    @classmethod
    def from_params(cls, params: Optional[SearchParams] = None,
                    **extra) -> "EffortSpec":
        base = params if params is not None else SearchParams()
        return cls(n_probes=int(base.n_probes),
                   refine_ratio=int(extra.get("refine_ratio", 1)))

    def apply(self, params: Optional[SearchParams] = None) -> SearchParams:
        """SearchParams carrying this spec's online knobs (non-effort
        fields inherited from ``params``)."""
        base = params if params is not None else SearchParams()
        return dc_replace(base, n_probes=int(self.n_probes))

    def degraded(self, level: int) -> "EffortSpec":
        """This spec stepped down ``level`` notches of the serving effort
        ladder: halve ``n_probes`` per level (floor 1), drop refine."""
        if level <= 0:
            return self
        return EffortSpec(
            n_probes=max(1, int(self.n_probes) >> int(level)),
            refine_ratio=1,
        )

    def knobs(self):
        return {"n_probes": int(self.n_probes),
                "refine_ratio": int(self.refine_ratio)}


class Index:
    """Padded-list IVF-Flat index.

    Fields (all jnp arrays, jit-traversable):
      centers     [n_lists, dim]     — coarse centroids
      list_data   [n_lists, cap, dim]— padded vectors (zeros past size)
      list_index  [n_lists, cap]     — source ids (-1 past size)
      list_sizes  [n_lists]
      list_norms  [n_lists, cap]     — ‖vector‖² (inf past size, so padded
                                       slots lose every select_min)
    """

    def __init__(self, metric, centers, list_data, list_index, list_sizes,
                 list_norms, headroom: bool = True):
        self.metric = metric
        self.centers = centers
        self.list_data = list_data
        self.list_index = list_index
        self.list_sizes = list_sizes
        self.list_norms = list_norms
        # list growth headroom policy (False under
        # conservative_memory_allocation; serialized like the reference's
        # conservative_memory_allocation flag, ivf_flat_serialize.cuh:66)
        self.headroom = headroom
        # cached centroid→group map for repeated fast appends (derived)
        self._group_inverse = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))

    @property
    def list_cap(self) -> int:
        return self.list_data.shape[1]


def _pack_lists(
    dataset: np.ndarray, ids: np.ndarray, labels: np.ndarray, n_lists: int,
    metric: str, headroom: bool = True, max_cap="default",
):
    """Streamed pack into the padded [n_lists', cap, dim] device layout +
    per-slot norms: (list, slot) metadata host-side
    (_common.compute_list_layout, no padded host payload copies), then
    row chunks scatter into donated device buffers — same 10⁸-row-safe
    scheme as ivf_pq._assemble_lists (ref: the reference's batched
    device-side list fill, ivf_flat_build.cuh:163).

    Oversized lists are split with duplicated centroids (skew-bounded cap;
    see _common.split_oversized_lists) — returns center_map so the caller
    expands its centroid rows."""
    n = dataset.shape[0]
    d = dataset.shape[1]
    # max_cap=None disables skew splitting — the sharded build's
    # shard-major relabel needs list ids to stay stable (serve.build)
    lst, slot, sizes, center_map, cap = compute_list_layout(
        labels, n_lists,
        max_cap=default_max_cap(n, n_lists) if max_cap == "default" else max_cap,
        headroom=headroom,
    )
    L = len(center_map)
    itemsize = np.dtype(dataset.dtype).itemsize
    chunk = int(np.clip((256 << 20) // max(d * (itemsize + 8), 1), 8, max(n, 8)))

    l_data = jnp.zeros((L, cap, d), dataset.dtype)
    l_index = jnp.full((L, cap), -1, jnp.int32)
    l_norms = jnp.full((L, cap), jnp.inf, jnp.float32)
    ids = np.asarray(ids, np.int32)
    lst32 = np.asarray(lst, np.int32)
    slot32 = np.asarray(slot, np.int32)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        pad = chunk - (e - s)
        rows = dataset[s:e]
        i_c, l_c, s_c = ids[s:e], lst32[s:e], slot32[s:e]
        if pad:
            rows = np.concatenate(
                [np.asarray(rows), np.zeros((pad, d), dataset.dtype)]
            )
            i_c = np.concatenate([i_c, np.zeros(pad, np.int32)])
            l_c = np.concatenate([l_c, np.full(pad, L, np.int32)])  # drop
            s_c = np.concatenate([s_c, np.zeros(pad, np.int32)])
        l_data, l_index, l_norms = _scatter_rows_chunk(
            l_data, l_index, l_norms,
            jnp.asarray(rows), jnp.asarray(i_c), jnp.asarray(l_c),
            jnp.asarray(s_c),
        )
    return l_data, l_index, jnp.asarray(sizes), l_norms, center_map


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _scatter_rows_chunk(l_data, l_index, l_norms, rows, ids, lst, slot):
    """Donated chunk scatter for the streamed pack (padding rows carry
    lst == n_lists → mode="drop")."""
    rows32 = rows.astype(jnp.float32)
    return (
        l_data.at[lst, slot].set(rows, mode="drop"),
        l_index.at[lst, slot].set(ids, mode="drop"),
        l_norms.at[lst, slot].set(jnp.sum(rows32 * rows32, axis=-1), mode="drop"),
    )


@traced("ivf_flat.build")
def build(
    params: IndexParams,
    dataset: jax.Array,
    *,
    res: Optional[Resources] = None,
) -> Index:
    """(ref: ivf_flat build pipeline, detail/ivf_flat_build.cuh:344 —
    subsample trainset → kmeans_balanced::fit → predict → pack lists)

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import ivf_flat
    >>> x = np.random.default_rng(0).random((2000, 16), dtype=np.float32)
    >>> idx = ivf_flat.build(
    ...     ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=3), x
    ... )
    >>> d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, x[:4], 3)
    >>> bool((np.asarray(i)[:, 0] == np.arange(4)).all())  # exact: self is 1-NN
    True
    """
    res = ensure(res)
    # host numpy/memmap datasets stay host-resident — the trainset gather
    # and extend's per-tile stream are the only uploads (see ivf_pq.build)
    if not isinstance(dataset, np.ndarray):
        dataset = jnp.asarray(dataset)
    n, d = dataset.shape
    canonical = DISTANCE_TYPES[params.metric]
    if canonical not in ("sqeuclidean", "euclidean", "inner_product", "cosine"):
        raise ValueError(f"ivf_flat supports L2/IP/cosine metrics, got {params.metric}")

    # train the coarse quantizer under the index metric so list membership
    # agrees with the probe ranking at search time (ref: ivf_flat build uses
    # index.metric for kmeans_balanced — detail/ivf_flat_build.cuh:360)
    kb_metric = canonical if canonical in ("cosine", "inner_product") else "sqeuclidean"
    kb = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=kb_metric, seed=params.seed
    )
    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    trainset = (
        subsample_trainset(dataset, n_train, params.seed)
        if n_train < n
        else jnp.asarray(dataset)
    )
    centers = kmeans_balanced.fit(kb, trainset.astype(jnp.float32), params.n_lists, res=res)

    index = Index(
        params.metric,
        centers,
        jnp.zeros((params.n_lists, 8, d), dataset.dtype),
        jnp.full((params.n_lists, 8), -1, jnp.int32),
        jnp.zeros((params.n_lists,), jnp.int32),
        jnp.full((params.n_lists, 8), jnp.inf, jnp.float32),
        headroom=not params.conservative_memory_allocation,
    )
    if params.add_data_on_build:
        index = extend(index, dataset, jnp.arange(n, dtype=jnp.int32), res=res)
    _log.debug(
        "ivf_flat.build: n=%d dim=%d n_lists=%d (requested %d) cap=%d dtype=%s",
        n, d, index.n_lists, params.n_lists, index.list_cap,
        index.list_data.dtype,
    )
    return index


@traced("ivf_flat.extend")
def extend(
    index: Index,
    new_vectors: jax.Array,
    new_indices: Optional[jax.Array] = None,
    *,
    res: Optional[Resources] = None,
) -> Index:
    """Add vectors (ref: ivf_flat extend, detail/ivf_flat_build.cuh:163).

    Capacity changes re-pack the padded layout host-side; search recompiles
    only when ``list_cap`` crosses its next padded tier — the explicit
    recompile-tier strategy for XLA static shapes (SURVEY §7 hard part 4).
    """
    res = ensure(res)
    if getattr(index, "paged", None) is not None:
        raise ValueError(
            "extend() on a paged index is unsupported — paged serving "
            "routes growth through MutableIndex side buffers and "
            "re-paginates at compaction (see docs/paged_storage.md)"
        )
    x = (
        new_vectors
        if isinstance(new_vectors, np.ndarray)
        else jnp.asarray(new_vectors, index.list_data.dtype)
    )
    canonical = DISTANCE_TYPES[index.metric]
    kb_metric = (
        canonical if canonical in ("cosine", "inner_product") else "sqeuclidean"
    )
    n_new = x.shape[0]
    if isinstance(x, np.ndarray):
        # tiled predict: a host numpy/memmap input stays host-resident and
        # only tiles cross to the device (the ivf_pq.extend scheme)
        tile = max(1, res.workspace_rows(8 * x.shape[1], cap=1 << 18))
        label_parts = []
        for s in range(0, n_new, tile):
            xt = jnp.asarray(x[s : s + tile]).astype(jnp.float32)
            label_parts.append(
                np.asarray(kmeans_balanced.predict(index.centers, xt, metric=kb_metric, res=res))
            )
        labels = (
            np.concatenate(label_parts) if label_parts else np.zeros(0, np.int64)
        )
    else:
        # device input: one fused predict, one device→host transfer (no
        # per-tile host round trips)
        labels = np.asarray(
            kmeans_balanced.predict(
                index.centers, x.astype(jnp.float32), metric=kb_metric, res=res
            )
        )
    new_vectors = x
    old_n = index.size
    if new_indices is None:
        new_indices = jnp.arange(old_n, old_n + n_new, dtype=jnp.int32)

    # fast path: append into spare capacity with device scatters, no repack
    # (the TPU answer to the reference's device-side list growth,
    # detail/ivf_flat_build.cuh:163; shard-aware — see allocate_append_slots)
    if new_vectors.shape[0] and old_n:
        if index._group_inverse is None:
            index._group_inverse = centroid_group_inverse(index.centers)
        alloc = allocate_append_slots(
            index.centers, index.list_sizes, index.list_cap,
            np.asarray(labels), group_inverse=index._group_inverse,
        )
        if alloc is not None:
            slab, slots, counts_new = alloc
            lj, sj = jnp.asarray(slab), jnp.asarray(slots)
            rows_dev = jnp.asarray(new_vectors, index.list_data.dtype)
            rows32 = rows_dev.astype(jnp.float32)
            new = Index(
                index.metric,
                index.centers,
                index.list_data.at[lj, sj].set(rows_dev),
                index.list_index.at[lj, sj].set(
                    jnp.asarray(new_indices, jnp.int32)
                ),
                index.list_sizes + jnp.asarray(counts_new, jnp.int32),
                index.list_norms.at[lj, sj].set(
                    jnp.sum(rows32 * rows32, axis=-1)
                ),
                headroom=index.headroom,
            )
            new._group_inverse = index._group_inverse
            return new

    # merge with existing content host-side, then re-pack; split shards from
    # a previous pack are first merged back to their parent list so repeated
    # extend() calls cannot inflate n_lists
    old_rows, old_ids, old_labels = unpack_lists(
        np.asarray(index.list_data), np.asarray(index.list_index)
    )
    if old_rows.shape[0] == 0:
        # initial fill (build): skip the concatenate so the host never
        # holds a second copy of a huge dataset
        all_rows = np.asarray(new_vectors).astype(old_rows.dtype, copy=False)
        all_ids = np.asarray(new_indices, np.int32)
        all_labels = np.asarray(labels)
    else:
        all_rows = np.concatenate(
            [old_rows, np.asarray(new_vectors).astype(old_rows.dtype, copy=False)]
        )
        all_ids = np.concatenate([old_ids, np.asarray(new_indices, np.int32)])
        all_labels = np.concatenate([old_labels, np.asarray(labels)])
    uniq, all_labels = merge_split_lists(np.asarray(index.centers), all_labels)
    base_centers = index.centers[jnp.asarray(uniq)]
    list_data, list_index, list_sizes, list_norms, center_map = _pack_lists(
        all_rows, all_ids, all_labels, len(uniq), index.metric,
        headroom=index.headroom,
    )
    centers = base_centers[jnp.asarray(center_map)]
    return Index(
        index.metric, centers, list_data, list_index, list_sizes, list_norms,
        headroom=index.headroom,
    )


@functools.partial(jax.jit, static_argnames=("n_probes", "k", "metric", "query_tile"))
def _search_jit(
    queries,      # [q, d] f32
    centers,      # [L, d] f32
    list_data,    # [L, cap, d]
    list_index,   # [L, cap] int32
    list_norms,   # [L, cap] f32 (inf at padding)
    filter_words, # [W] uint32 or None-like all-ones
    n_probes: int,
    k: int,
    metric: str,
    query_tile: int,
):
    q, d = queries.shape
    cap = list_data.shape[1]
    select_min = metric != "inner_product"

    # ---- coarse: select n_probes lists (ref: ivf_flat_search-inl.cuh:40)
    probes = coarse_select(queries, centers, metric, n_probes)  # [q, p]

    n_tiles = (q + query_tile - 1) // query_tile
    pad_q = n_tiles * query_tile - q
    qt = jnp.pad(queries, ((0, pad_q), (0, 0))).reshape(n_tiles, query_tile, d)
    pt = jnp.pad(probes, ((0, pad_q), (0, 0))).reshape(n_tiles, query_tile, n_probes)
    # per-row filters (ragged batches) tile alongside the queries; ndim is
    # static in trace so the branch costs nothing at runtime
    per_row = filter_words is not None and filter_words.ndim == 2
    if per_row:
        ft = jnp.pad(filter_words, ((0, pad_q), (0, 0))).reshape(
            n_tiles, query_tile, -1
        )
    else:
        ft = jnp.zeros((n_tiles, 1, 1), jnp.uint32)  # unused carrier

    def tile(args):
        qq, pp, fw_t = args  # [t, d], [t, p], [t, W]
        # [t, p, cap, d] gather (page-table indirected when paged)
        data = _gather_lists(list_data, pp).astype(jnp.float32)
        ids = list_index[pp]                          # [t, p, cap]
        norms = list_norms[pp]                        # [t, p, cap]
        # distance epilogue per metric
        ip = jnp.einsum("td,tpcd->tpc", qq, data, precision=_PREC)
        if metric == "inner_product":
            dist = -ip
        elif metric == "cosine":
            qn = jnp.maximum(jnp.linalg.norm(qq, axis=1), 1e-12)  # [t]
            vn = jnp.sqrt(jnp.maximum(norms, 1e-24))
            dist = 1.0 - ip / (qn[:, None, None] * vn)
        else:  # sqeuclidean/euclidean: ‖y‖² − 2x·y (+‖x‖² later, rank-stable)
            dist = norms - 2.0 * ip
        if per_row:
            invalid = invalid_mask_rows(ids, fw_t)
        else:
            invalid = invalid_mask(ids, filter_words)
        dist = jnp.where(invalid, jnp.inf, dist)
        # filtered-out candidates must surface as id −1, never their real id
        ids = jnp.where(invalid, -1, ids)
        flat_d = dist.reshape(query_tile, n_probes * cap)
        flat_i = ids.reshape(query_tile, n_probes * cap)
        v, i = select_k(flat_d, k, select_min=True, input_indices=flat_i)
        if metric == "inner_product":
            v = -v
        elif metric == "euclidean":
            qq2 = jnp.sum(qq * qq, axis=1)
            v = jnp.sqrt(jnp.maximum(v + qq2[:, None], 0.0))
        elif metric == "sqeuclidean":
            qq2 = jnp.sum(qq * qq, axis=1)
            v = v + qq2[:, None]
        return v, i

    vals, idx = lax.map(tile, (qt, pt, ft))
    return (
        vals.reshape(n_tiles * query_tile, k)[:q],
        idx.reshape(n_tiles * query_tile, k)[:q],
    )


@functools.partial(
    jax.jit, static_argnames=("n_probes", "k", "metric", "bucket", "bb")
)
def _search_probe_major_jit(
    queries,      # [q, d] f32
    centers,      # [L, d] f32
    list_data,    # [L, cap, d]
    list_index,   # [L, cap] int32
    list_norms,   # [L, cap] f32 (inf at padding)
    filter_words,
    n_probes: int,
    k: int,
    metric: str,
    bucket: int,
    bb: int,
):
    """Probe-major scan schedule (shared machinery with ivf_pq —
    _common.invert_probes / merge_probe_major_partials): each list's rows
    stream from HBM once per bucket instead of once per probing query
    (the TPU answer to the reference's per-list interleaved_scan
    scheduling, ivf_flat_interleaved_scan-inl.cuh)."""
    q, d = queries.shape
    L, cap, _ = list_data.shape
    G = bucket
    kk = min(k, cap)

    probes = coarse_select(queries, centers, metric, n_probes)
    q2 = jnp.sum(queries * queries, axis=1)
    qn = jnp.maximum(jnp.sqrt(q2), 1e-12)

    def score_fn(bl, bq):
        data = _gather_lists(list_data, bl).astype(jnp.float32)    # [bb, cap, d]
        ids = list_index[bl]
        norms = list_norms[bl]
        qq = queries[jnp.clip(bq, 0)]                              # [bb, G, d]
        # precision must match the query-major einsum (_PREC = HIGHEST):
        # default precision runs f32 matmuls as bf16 passes on TPU and the
        # two schedules would disagree on close-neighbor ranks
        ip = lax.dot_general(
            qq, data, (((2,), (2,)), ((0,), (0,))),
            precision=_PREC,
            preferred_element_type=jnp.float32,
        )                                                          # [bb, G, cap]
        if metric == "inner_product":
            dist = -ip
        elif metric == "cosine":
            vn = jnp.sqrt(jnp.maximum(norms, 1e-24))
            dist = 1.0 - ip / (qn[jnp.clip(bq, 0)][:, :, None] * vn[:, None, :])
        else:  # (sq)euclidean: ‖y‖² − 2x·y (+‖x‖² later, rank-stable)
            dist = norms[:, None, :] - 2.0 * ip
        invalid = invalid_mask(ids, filter_words)                  # [bb, cap]
        dist = jnp.where(invalid[:, None, :], jnp.inf, dist)
        dist = jnp.where(bq[:, :, None] < 0, jnp.inf, dist)
        ids_m = jnp.where(invalid, -1, ids)
        return select_k(
            dist.reshape(bb * G, cap), kk, select_min=True,
            input_indices=jnp.broadcast_to(
                ids_m[:, None, :], (bb, G, cap)
            ).reshape(bb * G, cap),
        )

    v, i = run_probe_major(probes, L, G, bb, kk, k, score_fn)
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v + q2[:, None], 0.0))
    elif metric == "sqeuclidean":
        v = v + q2[:, None]
    return v, i


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "k", "metric", "bucket", "interpret"),
)
def _search_probe_major_pallas(
    queries, centers, list_data, list_index, list_norms, list_filter,
    n_probes: int, k: int, metric: str, bucket: int, interpret: bool,
):
    """Probe-major schedule with the fused Pallas scan (kernels/
    ivf_scan.py — payload-agnostic: here y² = the stored row norms and
    queries are unrotated; inner product rides the kernel's −ip leg and
    ``list_filter`` is the pre-packed per-list word table, packed once in
    :func:`search`). Scores + per-query top-k stay in VMEM."""
    from raft_tpu.kernels.ivf_scan import ivf_scan_probe_major
    from raft_tpu.neighbors._common import (
        invert_probes as _invert,
        merge_probe_major_partials as _merge,
    )

    q, d = queries.shape
    L, cap, _ = list_data.shape
    G = bucket
    kk = min(k, cap)
    probes = coarse_select(queries, centers, metric, n_probes)
    q2 = jnp.sum(queries * queries, axis=1)
    bucket_list, bucket_query, bucket_pair, B = _invert(probes, L, G)
    qg = queries[jnp.clip(bucket_query, 0)]                  # [B, G, d]
    q2g = jnp.where(bucket_query >= 0, q2[jnp.clip(bucket_query, 0)], jnp.inf)
    # padding slots carry inf norms; the kernel masks by ids < 0, so zero
    # them to keep inf out of the MXU product path
    norms = jnp.where(list_index >= 0, list_norms, 0.0)
    vals, ids = ivf_scan_probe_major(
        bucket_list, qg, q2g, list_data, norms, list_index, kk,
        metric=metric, list_filter=list_filter, interpret=interpret,
    )
    v, i = _merge(
        vals.reshape(B * G, kk), ids.reshape(B * G, kk),
        bucket_pair, q, n_probes, kk, k,
    )
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@functools.partial(
    jax.jit, static_argnames=("n_probes", "k", "metric", "interpret")
)
def _search_query_major_pallas(
    queries, centers, list_data, list_index, list_norms, list_filter,
    n_probes: int, k: int, metric: str, interpret: bool, query_fid=None,
):
    """Query-major schedule with the fused Pallas scan (payload-agnostic
    kernels/ivf_scan.ivf_scan_query_major — here y² = stored row norms
    and queries ride unrotated): probed lists stream straight into VMEM;
    the XLA leg's [t, p, cap, d] gather copy and score tensor never
    exist. Queries pad to the kernel group width with q2=+inf rows.

    ``query_fid`` (ragged descriptor leg) selects each query's filter row
    from a pre-packed [n_filters, L, cap_w] ``list_filter`` table; padding
    rows ride fid 0 — their q2=+inf already voids the result."""
    from raft_tpu.kernels.ivf_scan import _QM_GROUP, ivf_scan_query_major

    q, d = queries.shape
    probes = coarse_select(queries, centers, metric, n_probes)
    q2 = jnp.sum(queries * queries, axis=1)
    # padding slots carry inf norms; the kernel masks by ids < 0, so zero
    # them to keep inf out of the MXU product path
    norms = jnp.where(list_index >= 0, list_norms, 0.0)
    pad = (-q) % _QM_GROUP
    if pad:
        probes = jnp.pad(probes, ((0, pad), (0, 0)))
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
        q2 = jnp.pad(q2, (0, pad), constant_values=jnp.inf)
        if query_fid is not None:
            query_fid = jnp.pad(query_fid, (0, pad))
    v, i = ivf_scan_query_major(
        probes, queries, q2, list_data, norms, list_index, int(k),
        metric=metric, scan_dtype="highest", list_filter=list_filter,
        query_fid=query_fid, interpret=interpret,
    )
    v, i = v[:q], i[:q]
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        # kernel folds +‖q‖² into the L2 score, so only the root remains
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@traced("ivf_flat.search")
def search(
    params: SearchParams,
    index: Index,
    queries: jax.Array,
    k: int,
    *,
    sample_filter: Optional[Bitset] = None,
    deleted_mask: Optional[Bitset] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (distances [q, k], indices [q, k]); indices −1 never appear
    unless a list underfills k (then distance is +inf).

    ``deleted_mask`` excludes set bits (tombstones, raft_tpu.serve) and
    composes with ``sample_filter`` (pass-bits kept)."""
    res = ensure(res)
    from raft_tpu.neighbors._common import resolve_pass_filter

    sample_filter = resolve_pass_filter(sample_filter, deleted_mask)
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != index.dim:
        raise ValueError(f"queries shape {queries.shape} vs index dim {index.dim}")
    n_probes = min(params.n_probes, index.n_lists)
    if k > n_probes * index.list_cap:
        raise ValueError(
            f"k={k} exceeds the candidate pool n_probes*list_cap="
            f"{n_probes}*{index.list_cap}; raise n_probes"
        )
    canonical = DISTANCE_TYPES[index.metric]
    fw = sample_filter.words if sample_filter is not None else None
    validation.check_in(
        params.strategy, ("auto", "query_major", "probe_major"), "strategy"
    )
    per_row = fw is not None and fw.ndim == 2
    req_strategy = params.strategy
    if per_row:
        validation.expects(
            fw.shape[0] == queries.shape[0],
            f"row filter has {fw.shape[0]} rows for "
            f"{queries.shape[0]} queries",
        )
        # probe-major tiles score whole lists against query *buckets*; a
        # per-query filter has no per-list formulation there, so ragged
        # batches always take the query-major schedule
        req_strategy = "query_major"
    strategy, bucket, bb, q_tile = select_scan_strategy(
        req_strategy, queries.shape[0], n_probes, index.n_lists,
        index.list_cap, index.dim, res.workspace_limit_bytes, k=int(k),
    )
    # paged storage: run the coarse pass up front, admit the probed
    # lists' pages, then scan through the page-table device view — the
    # search executables below are the ones the monolithic arm compiles
    paged = getattr(index, "paged", None)
    if paged is not None:
        from raft_tpu.neighbors._common import paged_lists_for_search

        list_data = paged_lists_for_search(index, queries, canonical, n_probes)
    else:
        list_data = index.list_data
    if strategy == "probe_major":
        use_pallas = pallas_scan_enabled(canonical, list_data.dtype)
        if paged is not None and use_pallas:
            from raft_tpu.kernels.ivf_scan import paged_scan_supported

            use_pallas = paged_scan_supported(
                list_data, min(int(k), index.list_cap), fw is not None
            )
        if use_pallas:
            from raft_tpu.kernels import interpret_mode
            from raft_tpu.kernels.ivf_scan import pack_list_filter

            # pack the filter ONCE per call (query-independent)
            lf = (
                None if fw is None
                else pack_list_filter(index.list_index, fw)
            )
            _stamp_kernel_path("pallas")

            def run_pm(qt):
                return _search_probe_major_pallas(
                    qt, index.centers, list_data, index.list_index,
                    index.list_norms, lf, n_probes, int(k), canonical,
                    bucket, interpret_mode(),
                )
        else:
            _stamp_kernel_path("xla")

            def run_pm(qt):
                return _search_probe_major_jit(
                    qt,
                    index.centers,
                    list_data,
                    index.list_index,
                    index.list_norms,
                    fw,
                    n_probes,
                    int(k),
                    canonical,
                    bucket,
                    bb,
                )

        # host-level query batching bounds the merge buffers (see
        # select_scan_strategy)
        return run_query_tiled(run_pm, queries, q_tile)
    from raft_tpu.kernels import ivf_scan as _scan_mod

    has_descriptor = per_row and getattr(sample_filter, "table", None) is not None
    if (
        paged is None  # query-major kernel streams whole monolithic lists
        and pallas_scan_enabled(canonical, list_data.dtype)
        and (not per_row or has_descriptor)
        and _scan_mod.qm_scratch_bytes(n_probes, index.list_cap)
        <= _scan_mod.QM_VMEM_BUDGET
    ):
        from raft_tpu.kernels import interpret_mode

        if has_descriptor:
            # ragged descriptor leg: pack every registered filter's per-list
            # word table once; each query's fid prefetches its own block
            lf = _scan_mod.pack_list_filter_table(
                index.list_index, sample_filter.table
            )
            fid = jnp.asarray(sample_filter.fid, jnp.int32)
            _stamp_kernel_path("pallas")

            def run_qm(qt, ft):
                return _search_query_major_pallas(
                    qt, index.centers, index.list_data, index.list_index,
                    index.list_norms, lf, n_probes, int(k), canonical,
                    interpret_mode(), query_fid=ft,
                )

            return run_query_tiled(
                run_qm, queries, _scan_mod.qm_query_tile(n_probes),
                extras=(fid,),
            )

        lf = (
            None if fw is None
            else _scan_mod.pack_list_filter(index.list_index, fw)
        )
        _stamp_kernel_path("pallas")

        def run_qm(qt):
            return _search_query_major_pallas(
                qt, index.centers, index.list_data, index.list_index,
                index.list_norms, lf, n_probes, int(k), canonical,
                interpret_mode(),
            )

        return run_query_tiled(
            run_qm, queries, _scan_mod.qm_query_tile(n_probes)
        )
    # tile queries so the [t, p, cap, d] gather respects the workspace budget
    per_q = 4 * n_probes * index.list_cap * (index.dim + 2)
    query_tile = int(min(max(queries.shape[0], 1), max(1, res.workspace_rows(per_q, cap=256))))
    # per-row filters land here only when the fused descriptor leg was
    # unavailable — stamp the fallback distinctly in the kernel_paths tally
    _stamp_kernel_path("xla_filter_fallback" if per_row else "xla")
    return _search_jit(
        queries,
        index.centers,
        list_data,
        index.list_index,
        index.list_norms,
        fw,
        n_probes,
        int(k),
        canonical,
        query_tile,
    )


@traced("ivf_flat.save")
def save(filename: str, index: Index) -> None:
    ser.save_tree(
        filename,
        "ivf_flat",
        _SERIALIZATION_VERSION,
        # ref serializes conservative_memory_allocation
        # (ivf_flat_serialize.cuh:66); headroom == not conservative
        {"metric": index.metric, "headroom": int(index.headroom)},
        {
            "centers": index.centers,
            "list_data": index.list_data,
            "list_index": index.list_index,
            "list_sizes": index.list_sizes,
            "list_norms": index.list_norms,
        },
    )


@traced("ivf_flat.load")
def load(filename: str) -> Index:
    scalars, arrays = ser.load_tree(filename, "ivf_flat", _SERIALIZATION_VERSION)
    return Index(
        scalars["metric"],
        jnp.asarray(arrays["centers"]),
        jnp.asarray(arrays["list_data"]),
        jnp.asarray(arrays["list_index"]),
        jnp.asarray(arrays["list_sizes"]),
        jnp.asarray(arrays["list_norms"]),
        headroom=bool(scalars.get("headroom", 1)),
    )
