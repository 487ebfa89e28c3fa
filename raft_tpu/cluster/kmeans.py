"""Lloyd's k-means with kmeans++ init (ref: cpp/include/raft/cluster/
kmeans.cuh, detail/kmeans.cuh (1,255 LoC), kmeans_types.hpp;
Python ref: pylibraft.cluster.kmeans).

TPU shape: the assignment step is the fused distance+argmin (one MXU matmul
per tile, SURVEY §2.7), the update step is ``segment_sum`` (sorted
scatter-add). The whole Lloyd loop runs on-device inside ``lax.while_loop``
with a convergence test, so there is exactly one dispatch per ``fit``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import distance_matrix_tile
from raft_tpu.core.trace import traced


@dataclass
class KMeansParams:
    """(ref: cluster/kmeans_types.hpp KMeansParams)"""

    n_clusters: int = 8
    max_iter: int = 300
    tol: float = 1e-4
    init: str = "kmeans++"  # kmeans++ | random | array
    n_init: int = 1
    seed: int = 0
    metric: str = "sqeuclidean"  # sqeuclidean | cosine (spherical k-means)
    batch_samples: int = 1 << 15  # assignment row-tile (bounds the [tile, k] matrix)


def _normalize_rows(x: jax.Array) -> jax.Array:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _assign(
    x: jax.Array, centers: jax.Array, tile: int = 0
) -> Tuple[jax.Array, jax.Array]:
    """(min_dist², label) per row — fused distance+argmin, row-tiled so the
    [tile, k] distance matrix (not [n, k]) bounds the workspace."""

    def one(t):
        d2 = distance_matrix_tile(t, centers, "sqeuclidean")
        return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1).astype(jnp.int32)

    n = x.shape[0]
    if tile <= 0 or n <= tile:
        return one(x)
    n_tiles = (n + tile - 1) // tile
    pad = n_tiles * tile - n
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_tiles, tile, x.shape[1])
    best, labels = lax.map(one, xp)
    return best.reshape(-1)[:n], labels.reshape(-1)[:n]


@traced("kmeans.plus_plus_init")
def kmeans_plus_plus_init(
    key: jax.Array, x: jax.Array, n_clusters: int, weights: Optional[jax.Array] = None
) -> jax.Array:
    """kmeans++ seeding (ref: detail/kmeans.cuh kmeansPlusPlus).

    Iteratively sample the next center ∝ weighted min-distance²; the
    incremental min-d² update keeps each step a single [n, d]·[d] pass.
    """
    n, d = x.shape
    w = jnp.ones((n,), x.dtype) if weights is None else weights
    k0, key = jax.random.split(key)
    first = jax.random.choice(k0, n, p=w / jnp.sum(w))
    centers0 = jnp.zeros((n_clusters, d), x.dtype).at[0].set(x[first])
    min_d2_0 = jnp.sum((x - x[first][None, :]) ** 2, axis=1)

    def body(i, carry):
        centers, min_d2, key = carry
        key, sub = jax.random.split(key)
        probs = w * min_d2
        probs = probs / jnp.maximum(jnp.sum(probs), 1e-30)
        nxt = jax.random.choice(sub, n, p=probs)
        c = x[nxt]
        centers = centers.at[i].set(c)
        min_d2 = jnp.minimum(min_d2, jnp.sum((x - c[None, :]) ** 2, axis=1))
        return centers, min_d2, key

    centers, _, _ = lax.fori_loop(1, n_clusters, body, (centers0, min_d2_0, key))
    return centers


@traced("kmeans.compute_new_centroids")
def compute_new_centroids(
    x: jax.Array,
    centroids: jax.Array,
    labels: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """One centroid-update step (Python ref:
    pylibraft.cluster.kmeans.compute_new_centroids)."""
    n_clusters = centroids.shape[0]
    if labels is None:
        _, labels = _assign(x, centroids)
    w = jnp.ones((x.shape[0],), x.dtype) if weights is None else weights
    sums = jax.ops.segment_sum(x * w[:, None], labels, num_segments=n_clusters)
    counts = jax.ops.segment_sum(w, labels, num_segments=n_clusters)
    return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centroids)


@functools.partial(jax.jit, static_argnames=("max_iter", "metric", "tile"))
def _lloyd(x, centers0, weights, max_iter: int, tol: float, metric: str, tile: int):
    n_clusters = centers0.shape[0]
    spherical = metric == "cosine"

    def cond(carry):
        _, it, prev, cur = carry
        # relative-change of the assignment inertia between iterations;
        # prev/cur start at +inf so the loop always takes ≥2 iterations
        # before the test can trigger
        return (it < max_iter) & ~(jnp.abs(prev - cur) <= tol * jnp.maximum(cur, 1e-30))

    def body(carry):
        centers, it, _, prev_inertia = carry
        best, labels = _assign(x, centers, tile)
        inertia = jnp.sum(weights * best)  # inertia of THIS assignment
        sums = jax.ops.segment_sum(x * weights[:, None], labels, num_segments=n_clusters)
        counts = jax.ops.segment_sum(weights, labels, num_segments=n_clusters)
        centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centers
        )
        if spherical:
            # spherical k-means: centers live on the unit sphere, so the
            # sqeuclidean argmin stays rank-equivalent to cosine
            centers = _normalize_rows(centers)
        return centers, it + 1, prev_inertia, inertia

    centers, n_iter, _, _ = lax.while_loop(
        cond, body, (centers0, jnp.int32(0), jnp.inf, jnp.inf)
    )
    # final inertia measured against the final centers
    best, _ = _assign(x, centers, tile)
    return centers, jnp.sum(weights * best), n_iter


@traced("kmeans.fit")
def fit(
    params: KMeansParams,
    x: jax.Array,
    sample_weights: Optional[jax.Array] = None,
    *,
    init_centers: Optional[jax.Array] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fit k-means; returns (centroids, inertia, n_iter)
    (Python ref: pylibraft.cluster.kmeans.fit — same return triple).

    ``n_init`` restarts keep the best inertia, like the reference.

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.cluster import kmeans
    >>> x = np.concatenate(
    ...     [np.zeros((50, 2)), np.ones((50, 2))]
    ... ).astype(np.float32)
    >>> c, inertia, n_iter = kmeans.fit(
    ...     kmeans.KMeansParams(n_clusters=2, seed=0), x
    ... )
    >>> c.shape
    (2, 2)
    >>> bool(inertia < 1e-3)  # two exact point-clusters
    True
    """
    res = ensure(res)
    if params.metric not in ("sqeuclidean", "euclidean", "l2", "cosine"):
        raise ValueError(f"kmeans supports sqeuclidean/cosine, got {params.metric}")
    metric = "cosine" if params.metric == "cosine" else "sqeuclidean"
    x = jnp.asarray(x, jnp.float32)
    if metric == "cosine":
        x = _normalize_rows(x)
    w = (
        jnp.ones((x.shape[0],), jnp.float32)
        if sample_weights is None
        else jnp.asarray(sample_weights, jnp.float32)
    )
    key = jax.random.fold_in(jax.random.PRNGKey(params.seed), 0)
    if params.init == "array" and init_centers is None:
        raise ValueError("init='array' requires init_centers")

    # deterministic restarts are identical — an explicit init runs once
    n_init = 1 if init_centers is not None else max(params.n_init, 1)
    best = None
    for trial in range(n_init):
        kt = jax.random.fold_in(key, trial)
        if init_centers is not None:
            c0 = jnp.asarray(init_centers, jnp.float32)
            if metric == "cosine":
                c0 = _normalize_rows(c0)
        elif params.init == "random":
            idx = jax.random.choice(kt, x.shape[0], shape=(params.n_clusters,), replace=False)
            c0 = x[idx]
        else:
            c0 = kmeans_plus_plus_init(kt, x, params.n_clusters, w)
        centers, inertia, n_iter = _lloyd(
            x, c0, w, params.max_iter, params.tol, metric, params.batch_samples
        )
        if best is None or float(inertia) < float(best[1]):
            best = (centers, inertia, n_iter)
    return best


@functools.lru_cache(maxsize=32)
def _lloyd_sharded_program(
    mesh, axis: str, max_iter: int, tol: float, metric: str, tile: int,
    reduce_dtype: str,
):
    """Build (and cache) the compiled sharded Lloyd loop per (mesh, axis,
    statics) — a fresh shard_map closure per fit would defeat jit's trace
    cache and re-trace the while_loop every call."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from raft_tpu.comms.quantized import quantized_psum

    def local(x, w, c0):
        x = x.astype(jnp.float32)
        if metric == "cosine":
            x = _normalize_rows(x)
        w = w.astype(jnp.float32)
        n_clusters, d = c0.shape
        spherical = metric == "cosine"

        def cond(carry):
            _, it, prev, cur = carry
            return (it < max_iter) & ~(
                jnp.abs(prev - cur) <= tol * jnp.maximum(cur, 1e-30)
            )

        def body(carry):
            centers, it, _, prev_inertia = carry
            best, labels = _assign(x, centers, tile)
            local_inertia = jnp.sum(w * best)
            sums = jax.ops.segment_sum(
                x * w[:, None], labels, num_segments=n_clusters
            )
            counts = jax.ops.segment_sum(w, labels, num_segments=n_clusters)
            # ONE collective per iteration: the [k, d] partial sums, the
            # counts column, and the inertia scalar ride a single packed
            # (optionally quantized) psum — the build loop's only
            # cross-device traffic
            side = jnp.zeros((n_clusters, 2), jnp.float32)
            side = side.at[:, 0].set(counts).at[0, 1].set(local_inertia)
            packed = quantized_psum(
                jnp.concatenate([sums, side], axis=1), axis, reduce_dtype
            )
            g_sums, g_counts = packed[:, :d], packed[:, d]
            inertia = packed[0, d + 1]
            centers = jnp.where(
                g_counts[:, None] > 0,
                g_sums / jnp.maximum(g_counts[:, None], 1e-30),
                centers,
            )
            if spherical:
                centers = _normalize_rows(centers)
            return centers, it + 1, prev_inertia, inertia

        centers, n_iter, _, _ = lax.while_loop(
            cond, body, (c0, jnp.int32(0), jnp.inf, jnp.inf)
        )
        # final inertia measured against the final centers (matches _lloyd)
        best, _ = _assign(x, centers, tile)
        inertia = lax.psum(jnp.sum(w * best), axis)
        return centers, inertia, n_iter

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(None, None)),
            out_specs=(P(None, None), P(), P()),
            check_vma=False,
        )
    )


@traced("kmeans.fit_sharded")
def fit_sharded(
    comms,
    params: KMeansParams,
    data_sharded: jax.Array,
    sample_weights: Optional[jax.Array] = None,
    *,
    init_centers: Optional[jax.Array] = None,
    reduce_dtype: Optional[str] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`fit` over data row-sharded across ``comms``' mesh axis.

    Semantically :func:`fit`'s Lloyd loop, distributed: each shard
    assigns its rows and computes partial centroid sums/counts; the
    partials merge in ONE packed ``psum`` per iteration (optionally
    bf16/int8-quantized via ``reduce_dtype`` /
    ``RAFT_TPU_BUILD_REDUCE_DTYPE``).  The training rows never funnel
    through one host — only [k, d+2] statistics travel.

    ``data_sharded`` is the global [n, d] array (sharded or shardable on
    the comms axis; n must divide the axis size — pad with zero-weight
    rows otherwise).  ``sample_weights`` shards alongside the rows.
    Init is on a replicated weight-aware subsample (rows travel once);
    ``init_centers`` bypasses it, giving runs that are comparable
    1:1 against a single-host :func:`fit` with the same init.

    Returns replicated (centroids, inertia, n_iter) like :func:`fit`.
    """
    res = ensure(res)
    if params.metric not in ("sqeuclidean", "euclidean", "l2", "cosine"):
        raise ValueError(
            f"kmeans supports sqeuclidean/cosine, got {params.metric}"
        )
    metric = "cosine" if params.metric == "cosine" else "sqeuclidean"
    n, _ = data_sharded.shape
    size = comms.get_size()
    if n % size != 0:
        raise ValueError(
            f"n={n} rows do not divide the {size}-way mesh axis; pad the "
            "shard with zero-weight rows (serve.build does this)"
        )
    if reduce_dtype is None:
        from raft_tpu.comms.quantized import reduce_dtype_from_env

        reduce_dtype = reduce_dtype_from_env()
    w = (
        jnp.ones((n,), jnp.float32)
        if sample_weights is None
        else jnp.asarray(sample_weights, jnp.float32)
    )
    key = jax.random.fold_in(jax.random.PRNGKey(params.seed), 0)
    if params.init == "array" and init_centers is None:
        raise ValueError("init='array' requires init_centers")

    run = _lloyd_sharded_program(
        comms.mesh, comms.axis, params.max_iter, float(params.tol), metric,
        params.batch_samples, reduce_dtype,
    )

    subsample = w_sub = None
    if init_centers is None:
        # replicated init subsample: rows travel once at init.  A
        # with-replacement draw is O(n_sub) — no full-n permutation of
        # the sharded dataset; collisions in an init sample are harmless
        k_sub, key = jax.random.split(key)
        n_sub = min(n, max(4 * params.n_clusters, 4096))
        idx = jax.random.randint(k_sub, (n_sub,), 0, n)
        subsample = jnp.asarray(data_sharded[idx], jnp.float32)
        if metric == "cosine":
            subsample = _normalize_rows(subsample)
        w_sub = w[idx]  # zero-weight padding rows are never seeds

    n_init = 1 if init_centers is not None else max(params.n_init, 1)
    best = None
    for trial in range(n_init):
        kt = jax.random.fold_in(key, trial)
        if init_centers is not None:
            c0 = jnp.asarray(init_centers, jnp.float32)
            if metric == "cosine":
                c0 = _normalize_rows(c0)
        elif params.init == "random":
            idx2 = jax.random.choice(
                kt, subsample.shape[0], shape=(params.n_clusters,),
                replace=subsample.shape[0] < params.n_clusters,
                p=w_sub / jnp.maximum(jnp.sum(w_sub), 1e-12),
            )
            c0 = subsample[idx2]
        else:
            c0 = kmeans_plus_plus_init(kt, subsample, params.n_clusters, w_sub)
        centers, inertia, n_iter = run(data_sharded, w, c0)
        if best is None or float(inertia) < float(best[1]):
            best = (centers, inertia, n_iter)
    return best


@traced("kmeans.predict")
def predict(
    centroids: jax.Array,
    x: jax.Array,
    *,
    metric: str = "sqeuclidean",
    batch_samples: int = 1 << 15,
    res: Optional[Resources] = None,
) -> jax.Array:
    """Nearest-centroid labels (Python ref: pylibraft kmeans predict path)."""
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(centroids, jnp.float32)
    if metric == "cosine":
        x, c = _normalize_rows(x), _normalize_rows(c)
    _, labels = _assign(x, c, batch_samples)
    return labels


@traced("kmeans.fit_predict")
def fit_predict(
    params: KMeansParams,
    x: jax.Array,
    sample_weights: Optional[jax.Array] = None,
    *,
    res: Optional[Resources] = None,
):
    centroids, inertia, n_iter = fit(params, x, sample_weights, res=res)
    labels = predict(
        centroids, x, metric=params.metric, batch_samples=params.batch_samples, res=res
    )
    return centroids, labels, inertia, n_iter


@traced("kmeans.transform")
def transform(centroids: jax.Array, x: jax.Array) -> jax.Array:
    """Distances to every centroid (ref: kmeans.cuh kmeans_transform)."""
    return distance_matrix_tile(
        jnp.asarray(x, jnp.float32), jnp.asarray(centroids, jnp.float32), "sqeuclidean"
    )


@traced("kmeans.cluster_cost")
def cluster_cost(
    x: jax.Array,
    centroids: jax.Array,
    *,
    batch_samples: int = 1 << 15,
    res: Optional[Resources] = None,
) -> jax.Array:
    """Total inertia (Python ref: pylibraft.cluster.kmeans.cluster_cost)."""
    best, _ = _assign(
        jnp.asarray(x, jnp.float32), jnp.asarray(centroids, jnp.float32), batch_samples
    )
    return jnp.sum(best)
