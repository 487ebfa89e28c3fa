"""Hierarchical *balanced* k-means — the coarse quantizer trainer used by all
IVF index builds.

Reference: ``cluster/detail/kmeans_balanced.cuh`` (1,089 LoC) —
``build_hierarchical`` (:952) trains ~√k mesoclusters, partitions the
trainset, trains fine clusters per mesocluster sized proportionally
(``build_fine_clusters`` :839), then runs balancing iterations where
``adjust_centers`` (:521) re-seeds under-populated clusters from populous
ones. The inner loop is fused-L2-argmin predict + reduce_rows_by_key update
(:83-164). Public API: ``fit/predict/fit_predict``
(cluster/kmeans_balanced.cuh:76-).

TPU shape: predict is an MXU matmul tile + argmin; update is segment_sum;
``adjust_centers`` is expressed as a jit-friendly masked teleport (small
clusters jump to a random point of an over-populated cluster). The
per-mesocluster fine fits share one compiled function over a padded member
buffer (weight-0 padding), so hierarchy costs one compile.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import argmin_tile_rows, tiled_argmin
from raft_tpu.core.trace import traced


@dataclass
class KMeansBalancedParams:
    """(ref: cluster/kmeans_balanced.cuh kmeans_balanced_params — n_iters is
    the reference's `kmeans_n_iters`, default 20 in ivf types)"""

    n_iters: int = 20
    metric: str = "sqeuclidean"  # sqeuclidean | cosine (spherical) | inner_product
    mesocluster_threshold: int = 256  # hierarchy kicks in above this many clusters
    seed: int = 0


def _maybe_normalize(x: jax.Array, metric: str) -> jax.Array:
    if metric == "cosine":
        return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x


@functools.partial(jax.jit, static_argnames=("metric", "tile_rows"))
def _predict_jit(centers, x, metric: str, tile_rows: int):
    """Normalize + delegate to the shared workspace-tiled fused
    distance+argmin (pairwise.tiled_argmin — see its DEEP-scale memory
    rationale; the reference likewise batches predict,
    cluster/detail/kmeans_balanced.cuh predict's minibatch loop)."""
    x = _maybe_normalize(x.astype(jnp.float32), metric)
    c = _maybe_normalize(centers.astype(jnp.float32), metric)
    inner = "inner_product" if metric == "inner_product" else "sqeuclidean"
    return tiled_argmin(x, c, inner, tile_rows)


@traced("kmeans_balanced.predict")
def predict(
    centers: jax.Array,
    x: jax.Array,
    *,
    metric: str = "sqeuclidean",
    res: Optional[Resources] = None,
) -> jax.Array:
    """Labels via fused distance-argmin (ref: kmeans_balanced.cuh predict →
    predict_core :83-164, which uses fusedL2NNMinReduce for L2 and
    pairwise_distance+argmin for other metrics — the metric MUST match the
    one used at build so list membership and probe ranking agree)."""
    res = ensure(res)
    centers = jnp.asarray(centers)
    return _predict_jit(
        centers, jnp.asarray(x), metric,
        argmin_tile_rows(centers.shape[0], res),
    )


@functools.partial(
    jax.jit, static_argnames=("n_iters", "n_clusters", "metric", "tile_rows")
)
def _balanced_iterations(
    key: jax.Array,
    x: jax.Array,
    centers0: jax.Array,
    weights: jax.Array,
    n_iters: int,
    n_clusters: int,
    metric: str = "sqeuclidean",
    tile_rows: int = 1 << 16,
):
    """n_iters × (assign → update → adjust_centers).

    adjust_centers (ref: kmeans_balanced.cuh:521): clusters with
    count < average/ratio are re-seeded to a random trainset point drawn
    from the data mass (points in big clusters are proportionally more
    likely), keeping cluster sizes balanced — essential for IVF list
    uniformity.
    """
    n = x.shape[0]
    spherical = metric == "cosine"
    inner = "inner_product" if metric == "inner_product" else "sqeuclidean"

    def assign(centers):
        # shared workspace-tiled fused distance+argmin (pairwise.tiled_argmin)
        return tiled_argmin(x, centers, inner, tile_rows)

    def body(carry, key_i):
        centers = carry
        labels = assign(centers)
        sums = jax.ops.segment_sum(x * weights[:, None], labels, num_segments=n_clusters)
        counts = jax.ops.segment_sum(weights, labels, num_segments=n_clusters)
        centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centers
        )
        if spherical:
            centers = _maybe_normalize(centers, "cosine")
        # --- adjust: teleport starved clusters onto random data points,
        # uniform over positive-weight rows (weight-0 padding never chosen).
        # Inverse-CDF draw, NOT jax.random.categorical: categorical over n
        # logits with shape=(n_clusters,) materializes an [n_clusters, n]
        # gumbel tensor — ~1 GB/iteration at a 250k trainset and ~50 GB at
        # DEEP-scale (measured via compile memory_analysis; it was the
        # build pipeline's peak-memory term)
        total = jnp.sum(weights)
        avg = total / n_clusters
        starved = counts < avg / 8.0  # ref threshold: average/adjust ratio
        # int32 cumsum: an f32 running sum silently plateaus at 2^24 rows,
        # which would starve everything past ~16.7M of selection probability
        cum = jnp.cumsum((weights > 0).astype(jnp.int32))
        r = jax.random.randint(key_i, (n_clusters,), 1, cum[-1] + 1)
        # first idx with cum[idx] >= r: zero-weight rows own empty intervals
        picks = jnp.clip(jnp.searchsorted(cum, r), 0, n - 1)
        centers = jnp.where(starved[:, None], x[picks], centers)
        return centers, counts

    keys = jax.random.split(key, n_iters)
    centers, counts_hist = lax.scan(body, centers0, keys)
    # final clean update without adjustment
    labels = assign(centers)
    sums = jax.ops.segment_sum(x * weights[:, None], labels, num_segments=n_clusters)
    counts = jax.ops.segment_sum(weights, labels, num_segments=n_clusters)
    centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centers
    )
    if spherical:
        centers = _maybe_normalize(centers, "cosine")
    return centers, labels


@functools.partial(
    jax.jit, static_argnames=("n_clusters", "n_iters", "metric", "tile_rows")
)
def _fit_flat(
    key: jax.Array,
    x: jax.Array,
    n_clusters: int,
    n_iters: int,
    weights: jax.Array,
    metric: str = "sqeuclidean",
    tile_rows: int = 1 << 16,
) -> jax.Array:
    k_init, k_iter = jax.random.split(key)
    n = x.shape[0]
    # init ∝ weight, *without replacement*: distinct seeds, and weight-0
    # padding rows are never chosen while any positive-weight row remains
    idx = jax.random.choice(
        k_init, n, shape=(n_clusters,), replace=n < n_clusters,
        p=weights / jnp.maximum(jnp.sum(weights), 1e-12),
    )
    centers0 = x[idx]
    centers, _ = _balanced_iterations(
        k_iter, x, centers0, weights, n_iters, n_clusters, metric, tile_rows
    )
    return centers


@traced("kmeans_balanced.fit")
def fit(
    params: KMeansBalancedParams,
    x: jax.Array,
    n_clusters: int,
    *,
    res: Optional[Resources] = None,
) -> jax.Array:
    """Train n_clusters balanced centers (ref: kmeans_balanced.cuh fit →
    detail::build_hierarchical :952)."""
    res = ensure(res)
    metric = params.metric
    x = _maybe_normalize(jnp.asarray(x, jnp.float32), metric)
    n, d = x.shape
    key = jax.random.PRNGKey(params.seed)
    ones = jnp.ones((n,), jnp.float32)

    tile_rows = argmin_tile_rows(n_clusters, res)
    if n_clusters <= params.mesocluster_threshold or n < 4 * n_clusters:
        return _fit_flat(
            key, x, n_clusters, params.n_iters, ones, metric, tile_rows
        )

    # ---- hierarchical path (ref: build_hierarchical :952) -----------------
    n_meso = int(math.ceil(math.sqrt(n_clusters)))
    k_meso, k_fine, k_final = jax.random.split(key, 3)
    meso_centers = _fit_flat(
        k_meso, x, n_meso, params.n_iters, ones, metric, tile_rows
    )
    # x is already normalized for cosine (normalizing again is idempotent),
    # so this assignment matches the training metric
    meso_labels = np.asarray(predict(meso_centers, x, metric=metric, res=res))

    # fine cluster budget per mesocluster, proportional to its population;
    # empty mesoclusters get 0 fine clusters (ref: build_fine_clusters :839)
    counts = np.bincount(meso_labels, minlength=n_meso).astype(np.int64)
    fine_k = np.where(
        counts > 0,
        np.maximum(1, np.floor(n_clusters * counts / max(n, 1)).astype(np.int64)),
        0,
    )
    occupied = counts > 0
    while fine_k.sum() != n_clusters:  # fix rounding drift
        if fine_k.sum() < n_clusters:
            load = np.where(occupied, counts / np.maximum(fine_k, 1), -np.inf)
            fine_k[np.argmax(load)] += 1
        else:
            load = np.where(fine_k > 1, counts / np.maximum(fine_k, 1), np.inf)
            fine_k[np.argmin(load)] -= 1

    # one compiled, vmapped fine-fit over a padded member buffer for ALL
    # mesoclusters at once (one dispatch instead of n_meso sequential fits);
    # padding repeats the mesocluster's own members (weight 0) so random
    # seeds/teleports can never land outside the partition
    # bucket the padded shapes to stable sizes (next power of two members,
    # sublane-multiple fine count): the vmapped fine fit is compiled per
    # (max_members, max_fine) signature, and raw data-dependent values force
    # a fresh XLA compile for every dataset (27 s per recompile on the
    # chip, round 4). Extra lanes are weight-0 padding.
    max_members = min(int(counts.max()), n)
    max_members = 1 << max(5, (max_members - 1).bit_length())
    max_fine = int(-(-int(fine_k.max()) // 8) * 8)
    occ = np.nonzero((counts > 0) & (fine_k > 0))[0]
    sel = np.empty((len(occ), max_members), np.int64)
    wts = np.zeros((len(occ), max_members), np.float32)
    for row, m in enumerate(occ):
        members = np.nonzero(meso_labels == m)[0]
        pad = max_members - len(members)
        sel[row, : len(members)] = members
        sel[row, len(members):] = members[np.arange(pad) % len(members)]
        wts[row, : len(members)] = 1.0
    keys = jax.vmap(lambda m: jax.random.fold_in(k_fine, m))(jnp.asarray(occ))
    vfit = jax.vmap(
        lambda kk, sub, w: _fit_flat(
            kk, sub, max_fine, params.n_iters, w, metric, tile_rows
        )
    )
    # chunk the vmap so peak memory stays inside the workspace budget even
    # when one mesocluster holds most of the trainset (member buffer +
    # per-iteration distance tile per vmapped lane)
    per_meso = 4 * max_members * (x.shape[1] + max_fine)
    chunk = int(np.clip(res.workspace_limit_bytes // max(per_meso, 1), 1, len(occ)))
    parts = []
    for s in range(0, len(occ), chunk):
        idx = jnp.asarray(sel[s : s + chunk])
        parts.append(
            np.asarray(
                vfit(keys[s : s + chunk], x[idx], jnp.asarray(wts[s : s + chunk]))
            )
        )
    fine_np = np.concatenate(parts)
    centers = jnp.asarray(
        np.concatenate([fine_np[r, : int(fine_k[m])] for r, m in enumerate(occ)])
    )
    assert centers.shape[0] == n_clusters, (centers.shape, n_clusters)

    # final balancing passes over the full trainset (ref: :1016-1043)
    centers, _ = _balanced_iterations(
        k_final, x, centers, ones, max(2, params.n_iters // 10), n_clusters,
        metric, tile_rows,
    )
    return centers


@functools.lru_cache(maxsize=32)
def _balanced_sharded_program(
    mesh, axis: str, n_iters: int, n_clusters: int, metric: str,
    tile_rows: int, reduce_dtype: str,
):
    """Build (and cache) the compiled sharded balancing loop — the
    distributed counterpart of :func:`_balanced_iterations`.  Each shard
    assigns its rows and computes partial sums/counts; partials merge in
    ONE packed (optionally quantized) psum per iteration.  The starved-
    cluster teleport draws from a replicated weight-mass pool (the init
    subsample) instead of the full trainset — the draw must be identical
    on every shard, and shipping a cross-shard gather into the scan would
    reintroduce per-iteration row traffic."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from raft_tpu.comms.quantized import quantized_psum

    spherical = metric == "cosine"
    inner = "inner_product" if metric == "inner_product" else "sqeuclidean"

    def local(key, x, w, c0, pool, pool_w):
        x = _maybe_normalize(x.astype(jnp.float32), metric)
        w = w.astype(jnp.float32)
        d = c0.shape[1]
        m = pool.shape[0]

        def assign(centers):
            return tiled_argmin(x, centers, inner, tile_rows)

        def update(centers):
            labels = assign(centers)
            sums = jax.ops.segment_sum(
                x * w[:, None], labels, num_segments=n_clusters
            )
            counts = jax.ops.segment_sum(w, labels, num_segments=n_clusters)
            packed = quantized_psum(
                jnp.concatenate([sums, counts[:, None]], axis=1),
                axis, reduce_dtype,
            )
            g_sums, g_counts = packed[:, :d], packed[:, d]
            centers = jnp.where(
                g_counts[:, None] > 0,
                g_sums / jnp.maximum(g_counts[:, None], 1e-30),
                centers,
            )
            if spherical:
                centers = _maybe_normalize(centers, "cosine")
            return centers, labels, g_counts

        def body(carry, key_i):
            centers, _, g_counts = update(carry)
            # teleport starved clusters onto random pool rows (same
            # inverse-CDF weight-mass draw as _balanced_iterations);
            # replicated pool + replicated key → every shard teleports
            # identically, keeping centers replicated without a collective
            avg = jnp.sum(g_counts) / n_clusters
            starved = g_counts < avg / 8.0
            cum = jnp.cumsum((pool_w > 0).astype(jnp.int32))
            r = jax.random.randint(key_i, (n_clusters,), 1, cum[-1] + 1)
            picks = jnp.clip(jnp.searchsorted(cum, r), 0, m - 1)
            centers = jnp.where(starved[:, None], pool[picks], centers)
            return centers, g_counts

        keys = jax.random.split(key, n_iters)
        centers, _ = lax.scan(body, c0, keys)
        # final clean update without adjustment
        centers, labels, _ = update(centers)
        return centers, labels

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(None), P(axis, None), P(axis), P(None, None),
                P(None, None), P(None),
            ),
            out_specs=(P(None, None), P(axis)),
            check_vma=False,
        )
    )


@traced("kmeans_balanced.fit_sharded")
def fit_sharded(
    comms,
    params: KMeansBalancedParams,
    data_sharded: jax.Array,
    n_clusters: int,
    sample_weights: Optional[jax.Array] = None,
    *,
    init_centers: Optional[jax.Array] = None,
    reduce_dtype: Optional[str] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`fit` over data row-sharded across ``comms``' mesh axis.

    Seeding (the hierarchical/flat :func:`fit`) runs on a replicated
    weight-aware subsample — rows travel exactly once, bounded size —
    then the balancing iterations run distributed over the FULL sharded
    trainset: per-shard assign + partial sums, merged with one packed
    (optionally ``reduce_dtype``-quantized, env
    ``RAFT_TPU_BUILD_REDUCE_DTYPE``) psum per iteration.  The starved-
    cluster teleport draws from the replicated subsample (a weight-mass
    draw like the reference's adjust_centers) so all shards stay
    center-replicated without extra collectives.

    ``data_sharded`` is [n, d] with n a multiple of the axis size (pad
    with zero-weight rows otherwise).  Returns (centers [k, d]
    replicated, labels [n] sharded).
    """
    res = ensure(res)
    metric = params.metric
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
    key = jax.random.PRNGKey(params.seed)
    k_sub, k_iter = jax.random.split(key)

    # replicated pool: seeds the hierarchy AND feeds the teleport draws.
    # With-replacement draw — O(n_sub), no full-n permutation; host-side
    # filtering drops zero-weight padding rows so they never seed
    n_sub = min(n, max(8 * n_clusters, 8192))
    idx = np.asarray(
        jax.random.randint(k_sub, (n_sub,), 0, n), dtype=np.int64
    )
    w_np = np.asarray(w)
    idx = idx[w_np[idx] > 0]
    if idx.size == 0:
        raise ValueError("all sample weights are zero; nothing to cluster")
    pool = _maybe_normalize(
        jnp.asarray(data_sharded[jnp.asarray(idx)], jnp.float32), metric
    )
    pool_w = jnp.asarray(w_np[idx])

    if init_centers is None:
        c0 = fit(params, pool, n_clusters, res=res)
    else:
        c0 = _maybe_normalize(jnp.asarray(init_centers, jnp.float32), metric)

    run = _balanced_sharded_program(
        comms.mesh, comms.axis, max(1, params.n_iters), n_clusters, metric,
        argmin_tile_rows(n_clusters, res), reduce_dtype,
    )
    return run(k_iter, data_sharded, w, c0, pool, pool_w)


@traced("kmeans_balanced.fit_predict")
def fit_predict(
    params: KMeansBalancedParams,
    x: jax.Array,
    n_clusters: int,
    *,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    centers = fit(params, x, n_clusters, res=res)
    return centers, predict(centers, x, metric=params.metric, res=res)
