"""Exact (brute-force) k-nearest-neighbor search.

Reference: tiled pairwise-distance + per-tile select_k + cross-tile merge
(ref: cpp/include/raft/neighbors/detail/knn_brute_force.cuh:60-300
``tiled_brute_force_knn``; select_k at :240,:282; merge via
knn_merge_parts.cuh; index type neighbors/brute_force_types.hpp:49;
Python ref: pylibraft.neighbors.brute_force.knn).

TPU design: the dataset-tile loop is a ``lax.scan`` carrying the running
top-k per query (concat + top_k merge — the knn_merge_parts equivalent);
query tiles go through ``lax.map``. Distance tiles ride the MXU for
expanded metrics. All shapes static; tile sizes picked from the workspace
budget like the reference sizes tiles against its workspace resource.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core import serialize as ser
from raft_tpu.core import validation
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, distance_matrix_tile
from raft_tpu.ops.matrix import select_k
from raft_tpu.core.trace import traced

_SERIALIZATION_VERSION = 1


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tile_cols", "query_tile", "select_min")
)
def _tiled_knn(
    queries: jax.Array,
    dataset: jax.Array,
    k: int,
    metric: str,
    p: float,
    tile_cols: int,
    query_tile: int,
    select_min: bool,
    filter_words: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    n_q, d = queries.shape
    n, _ = dataset.shape

    n_col_tiles = (n + tile_cols - 1) // tile_cols
    pad_n = n_col_tiles * tile_cols - n
    # pad dataset rows; padded distances forced to worst value via index mask
    ds = jnp.pad(dataset, ((0, pad_n), (0, 0)))
    ds_tiles = ds.reshape(n_col_tiles, tile_cols, d)
    worst = jnp.inf if select_min else -jnp.inf

    n_q_tiles = (n_q + query_tile - 1) // query_tile
    pad_q = n_q_tiles * query_tile - n_q
    q_tiles = jnp.pad(queries, ((0, pad_q), (0, 0))).reshape(n_q_tiles, query_tile, d)
    # per-row filters (ragged batches) tile alongside the queries so each
    # query row is masked by its own word set; ndim is static in trace
    per_row = filter_words is not None and filter_words.ndim == 2
    if per_row:
        fw_tiles = jnp.pad(filter_words, ((0, pad_q), (0, 0))).reshape(
            n_q_tiles, query_tile, -1
        )
    else:
        fw_tiles = jnp.zeros((n_q_tiles, 1, 1), jnp.uint32)  # unused carrier

    def per_query_tile(args):
        q, fw_t = args

        def scan_tile(carry, inp):
            best_v, best_i = carry
            tile, tile_idx = inp
            dist = distance_matrix_tile(q, tile, metric, p)
            col_ids = tile_idx * tile_cols + jnp.arange(tile_cols, dtype=jnp.int32)
            dist = jnp.where((col_ids < n)[None, :], dist, worst)
            sel_ids = jnp.broadcast_to(col_ids[None, :], dist.shape)
            if filter_words is not None:
                # post-filter (tombstones / sample filter): excluded rows
                # take the worst distance and surface as id −1, matching
                # the IVF family's filtered-candidate contract
                if per_row:
                    word = fw_t[:, jnp.clip(col_ids, 0) // 32]
                else:
                    word = filter_words[jnp.clip(col_ids, 0) // 32][None, :]
                passing = (
                    (word >> (col_ids % 32).astype(jnp.uint32)[None, :]) & 1
                ).astype(bool) & (col_ids < n)[None, :]
                dist = jnp.where(passing, dist, worst)
                sel_ids = jnp.where(passing, sel_ids, -1)
            tv, ti = select_k(
                dist, min(k, tile_cols), select_min=select_min,
                input_indices=sel_ids,
            )
            merged = jnp.concatenate([best_v, tv], axis=1)
            merged_i = jnp.concatenate([best_i, ti], axis=1)
            nv, ni = select_k(merged, k, select_min=select_min, input_indices=merged_i)
            return (nv, ni), None

        init_v = jnp.full((query_tile, k), worst, jnp.float32)
        init_i = jnp.zeros((query_tile, k), jnp.int32)
        (vals, idx), _ = lax.scan(
            scan_tile,
            (init_v, init_i),
            (ds_tiles, jnp.arange(n_col_tiles, dtype=jnp.int32)),
        )
        return vals, idx

    vals, idx = lax.map(per_query_tile, (q_tiles, fw_tiles))
    vals = vals.reshape(n_q_tiles * query_tile, k)[:n_q]
    idx = idx.reshape(n_q_tiles * query_tile, k)[:n_q]
    return vals, idx


@traced("brute_force.knn")
def knn(
    dataset: jax.Array,
    queries: jax.Array,
    k: int,
    *,
    metric: str = "sqeuclidean",
    p: float = 2.0,
    sample_filter=None,
    deleted_mask=None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN: (distances [n_q, k], indices [n_q, k]).

    (Python ref: pylibraft.neighbors.brute_force.knn — same order of
    returns.) ``inner_product`` selects largest, all distances smallest,
    matching the reference's select-direction logic.

    ``sample_filter`` (pass-bits kept) and ``deleted_mask`` (set bits
    excluded — the serve layer's tombstone convention) post-filter the
    candidate set; excluded rows surface as id −1 at the worst distance.

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import brute_force
    >>> x = np.random.default_rng(0).random((1000, 16), dtype=np.float32)
    >>> dists, ids = brute_force.knn(x, x[:5], 3)
    >>> ids.shape
    (5, 3)
    >>> bool((np.asarray(ids)[:, 0] == np.arange(5)).all())  # self is 1-NN
    True
    """
    res = ensure(res)
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    validation.check_in(metric, DISTANCE_TYPES, "metric")
    validation.check_matrix(dataset, "dataset")
    validation.check_matrix(queries, "queries")
    validation.check_same_cols(dataset, queries, "dataset", "queries")
    validation.check_positive(k, "k")
    validation.expects(
        k <= dataset.shape[0],
        f"k={k} larger than dataset size {dataset.shape[0]}",
    )
    canonical = DISTANCE_TYPES[metric]
    select_min = canonical != "inner_product"
    n, d = dataset.shape

    # kernel-path attribution: the tiled XLA matmul path unless the fused
    # Pallas leg below takes the call
    from raft_tpu.kernels import stamp_kernel_path

    stamp_kernel_path("xla")

    from raft_tpu.neighbors._common import resolve_pass_filter

    pass_filter = resolve_pass_filter(sample_filter, deleted_mask)
    if pass_filter is not None and pass_filter.n_bits < n:
        raise ValueError(
            f"filter covers {pass_filter.n_bits} ids but dataset has {n} rows"
        )
    filter_words = None if pass_filter is None else pass_filter.words
    if filter_words is not None and filter_words.ndim == 2:
        validation.expects(
            filter_words.shape[0] == queries.shape[0],
            f"row filter has {filter_words.shape[0]} rows for "
            f"{queries.shape[0]} queries",
        )

    # Pallas fused distance+topk path (ref: the fusedL2Knn fast path,
    # spatial/knn/detail/fused_l2_knn-inl.cuh — fuses the distance tile and
    # selection so the [n_q, n] score matrix never reaches HBM). Same gate
    # as every kernel (kernels.use_pallas); interpret mode keeps it
    # testable on CPU.
    from raft_tpu.kernels import use_pallas

    canonical_f32 = dataset.dtype == jnp.float32 and queries.dtype == jnp.float32
    if (
        use_pallas()
        and canonical in ("sqeuclidean", "euclidean", "inner_product")
        and k <= 128
        and canonical_f32
        and filter_words is None  # the fused kernel has no post-filter leg
    ):
        from raft_tpu.kernels import interpret_mode
        from raft_tpu.kernels.fused_knn import fused_l2_topk

        stamp_kernel_path("pallas")
        if canonical == "inner_product":
            vals, idx = fused_l2_topk(
                queries, dataset, jnp.zeros(n), int(k), mode="ip",
                interpret=interpret_mode(),
            )
            return -vals, idx
        xx = jnp.sum(dataset * dataset, axis=1)
        vals, idx = fused_l2_topk(
            queries, dataset, xx, int(k), interpret=interpret_mode()
        )
        q2 = jnp.sum(queries * queries, axis=1)
        vals = jnp.maximum(vals + q2[:, None], 0.0)
        if canonical == "euclidean":
            vals = jnp.sqrt(vals)
        return vals, idx

    # tile sizing against workspace (ref: knn_brute_force.cuh tile sizing).
    # Expanded metrics materialize [query_tile, tile_cols]; unexpanded ones
    # materialize the [query_tile, tile_cols, d] broadcast, so the per-column
    # cost includes both factors.
    from raft_tpu.distance.pairwise import _EXPANDED

    query_tile = int(min(max(queries.shape[0], 1), 1024))
    if canonical in _EXPANDED or canonical == "haversine":
        elem = 4 * max(d, query_tile)
    else:
        elem = 4 * d * query_tile
    tile_cols = int(min(n, max(512, res.workspace_rows(elem, cap=1 << 14))))
    # keep the dataset in its input dtype (int8/uint8/bf16/f32 — ref
    # low-precision dataset templates, ivf_flat_types.hpp:47): tiles are
    # cast (or int8-MXU dotted) inside distance_matrix_tile, so HBM holds
    # no fp32 copy of the dataset. Integer queries against an integer
    # dataset take the exact int-Gram path; mixed cases fall back to f32
    # queries with per-tile dataset casts.
    both_int = jnp.issubdtype(dataset.dtype, jnp.integer) and jnp.issubdtype(
        queries.dtype, jnp.integer
    )
    if not both_int and queries.dtype != jnp.float32:
        queries = queries.astype(jnp.float32)
    vals, idx = _tiled_knn(
        queries,
        dataset,
        int(k),
        canonical,
        p,
        tile_cols,
        query_tile,
        select_min,
        filter_words,
    )
    return vals, idx


@dataclass(frozen=True)
class EffortSpec:
    """Identity effort spec: exact search has no recall/throughput knob,
    so every actuator level maps to the same (full) effort.  Exists so
    the effort arbiter and frontier sweep treat all four backends
    uniformly (see ivf_flat.EffortSpec for the contract)."""

    backend: ClassVar[str] = "brute_force"

    @classmethod
    def from_params(cls, params=None, **extra) -> "EffortSpec":
        return cls()

    def apply(self, params=None):
        return params

    def degraded(self, level: int) -> "EffortSpec":
        return self

    def knobs(self):
        return {}


class Index:
    """Brute-force index: dataset + precomputed norms
    (ref: neighbors/brute_force_types.hpp:49)."""

    def __init__(self, dataset: jax.Array, metric: str = "sqeuclidean"):
        self.dataset = jnp.asarray(dataset)
        self.metric = metric

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]


@traced("brute_force.build")
def build(dataset: jax.Array, *, metric: str = "sqeuclidean", res=None) -> Index:
    """(ref: neighbors/brute_force.cuh build)"""
    return Index(dataset, metric)


@traced("brute_force.search")
def search(
    index: Index,
    queries: jax.Array,
    k: int,
    *,
    sample_filter=None,
    deleted_mask=None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    # paged index: every row is scanned each dispatch, so the whole
    # dataset must sit in the hot pool — identity-pin it once (single
    # host→HBM transfer; BudgetExceeded if the pool is short) and hand
    # the flat pool view to the unchanged knn (bitwise-identical rows)
    paged = getattr(index, "paged", None)
    if paged is not None:
        paged.pin_identity()
        pool, _ = paged.view()
        dataset = pool.reshape((-1,) + pool.shape[2:])[: index.size]
    else:
        dataset = index.dataset
    return knn(
        dataset, queries, k, metric=index.metric,
        sample_filter=sample_filter, deleted_mask=deleted_mask, res=res,
    )


class Batch:
    """One batch of a :class:`BatchKQuery`: neighbors
    ``[offset, offset+size)`` for every query, sorted by distance."""

    def __init__(self, distances: jax.Array, indices: jax.Array, offset: int):
        self._distances = distances
        self._indices = indices
        self.offset = offset

    def distances(self) -> jax.Array:
        return self._distances

    def indices(self) -> jax.Array:
        return self._indices

    @property
    def size(self) -> int:
        return self._indices.shape[1]


class BatchKQuery:
    """Incremental-k queries over a brute-force index: iterate each
    query's neighbor list in batches of ``batch_size`` — batch 0 is the
    nearest ``batch_size`` neighbors, batch 1 the next ``batch_size``,
    and so on, without deciding a final k up front.

    (ref: neighbors/brute_force.cuh:31-70 ``make_batch_k_query`` +
    detail/knn_brute_force_batch_k_query.cuh ``gpu_batch_k_query``.)
    The reference caches a device result matrix and grows the searched k
    exponentially when iteration passes the cached range; here the cached
    state is the jitted tiled-kNN result at the grown k, so stepping
    through b batches costs O(log b) searches, each a cache-hit
    compile.  Batches past the cached k re-search with
    ``k = max(2*cached, offset+size)`` — the reference's doubling rule
    (knn_brute_force_batch_k_query.cuh load_batch).
    """

    def __init__(self, index: Index, queries: jax.Array, batch_size: int,
                 *, res: Optional[Resources] = None):
        validation.check_positive(batch_size, "batch_size")
        self.index = index
        self.queries = jnp.asarray(queries)
        self.batch_size = int(batch_size)
        self._res = res
        self._cached_k = 0
        self._vals: Optional[jax.Array] = None
        self._ids: Optional[jax.Array] = None

    def _ensure(self, upto: int) -> None:
        upto = min(upto, self.index.size)
        if upto <= self._cached_k:
            return
        want = min(
            self.index.size,
            max(upto, 2 * self._cached_k, 2 * self.batch_size),
        )
        self._vals, self._ids = search(
            self.index, self.queries, want, res=self._res
        )
        self._cached_k = want

    def batch(self, offset: int, size: int) -> Batch:
        """Neighbors ``[offset, offset+size)`` for every query (clamped at
        the index size)."""
        validation.expects(offset >= 0, f"offset must be >= 0, got {offset}")
        size = max(0, min(size, self.index.size - offset))
        if size == 0:  # beyond the index (or size<=0): empty batch, no
            n_q = self.queries.shape[0]  # search and no None deref
            return Batch(jnp.zeros((n_q, 0), jnp.float32),
                         jnp.zeros((n_q, 0), jnp.int32), offset)
        self._ensure(offset + size)
        return Batch(
            self._vals[:, offset:offset + size],
            self._ids[:, offset:offset + size],
            offset,
        )

    def __iter__(self):
        offset = 0
        while offset < self.index.size:
            b = self.batch(offset, self.batch_size)
            yield b
            offset += b.size


def make_batch_k_query(
    index: Index,
    queries: jax.Array,
    batch_size: int,
    *,
    res: Optional[Resources] = None,
) -> BatchKQuery:
    """(ref: neighbors/brute_force.cuh:70 ``make_batch_k_query``)"""
    return BatchKQuery(index, queries, batch_size, res=res)


@traced("brute_force.save")
def save(filename: str, index: Index) -> None:
    """(ref: brute_force serialize — version-stamped, SURVEY §5 checkpoint)"""
    ser.save_tree(
        filename,
        "brute_force",
        _SERIALIZATION_VERSION,
        {"metric": index.metric},
        {"dataset": index.dataset},
    )


@traced("brute_force.load")
def load(filename: str) -> Index:
    scalars, arrays = ser.load_tree(filename, "brute_force", _SERIALIZATION_VERSION)
    return Index(jnp.asarray(arrays["dataset"]), scalars["metric"])
