"""Random ball cover (RBC) nearest neighbors.

Reference: ``neighbors/ball_cover.cuh`` + ``spatial/knn/detail/ball_cover/``
— sample √n landmarks, assign every point to its closest landmark, and at
query time prune landmark balls with the triangle inequality
(``registers.cuh`` kernels). Supports haversine/L2 (SURVEY §2.8).

TPU re-design: the index is the same (landmarks from random sampling, then
closest-landmark assignment packed into padded per-landmark lists — the IVF
layout from ``_common.pack_padded_lists``). The query replaces per-thread
triangle pruning with *probe ranking*: rank landmarks by query→landmark
distance and scan the closest ``n_probes`` balls with dense batched
distances + select_k. The triangle inequality shows up as the probe bound:
with all points in their closest ball, scanning the k_landmark-nearest balls
gives the reference's "approximate" mode; n_probes = all landmarks is exact.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC, pairwise_distance
from raft_tpu.neighbors._common import pack_padded_lists, subsample_trainset
from raft_tpu.ops.matrix import select_k
from raft_tpu.core.trace import traced

_SUPPORTED = ("sqeuclidean", "euclidean", "haversine")


def _dist(a: jax.Array, b: jax.Array, metric: str) -> jax.Array:
    """Plain [m, n] distance for the RBC metrics — delegates to the shared
    pairwise kernels (only the fused gathered-rows form in _query_jit needs
    a custom expression)."""
    return pairwise_distance(a, b, metric=metric)


class BallCoverIndex:
    """(ref: neighbors/ball_cover_types.hpp BallCoverIndex)"""

    def __init__(self, metric, landmarks, list_vecs, list_index, list_sizes, radii):
        self.metric = metric
        self.landmarks = landmarks        # [L, d]
        self.list_vecs = list_vecs        # [L, cap, d]
        self.list_index = list_index      # [L, cap]
        self.list_sizes = list_sizes      # [L]
        self.radii = radii                # [L] max dist landmark→member

    @property
    def n_landmarks(self) -> int:
        return self.landmarks.shape[0]

    @property
    def dim(self) -> int:
        return self.landmarks.shape[1]


@traced("ball_cover.build")
def build(
    dataset: jax.Array,
    *,
    metric: str = "sqeuclidean",
    n_landmarks: int = 0,
    seed: int = 0,
    res: Optional[Resources] = None,
) -> BallCoverIndex:
    """(ref: ball_cover.cuh build_index: sample √n landmarks → assign)"""
    res = ensure(res)
    x = jnp.asarray(dataset, jnp.float32)
    n, d = x.shape
    canonical = DISTANCE_TYPES.get(metric, metric)
    if canonical not in _SUPPORTED:
        raise ValueError(f"ball_cover supports {_SUPPORTED}, got {metric}")
    L = n_landmarks or max(1, int(np.sqrt(n)))
    # host-side landmark draw (see _common.subsample_trainset: a device
    # no-replacement choice compiles a full-n sort, ~20 s on the chip)
    landmarks = subsample_trainset(x, L, seed)
    base = "haversine" if canonical == "haversine" else "sqeuclidean"
    dists = _dist(x, landmarks, base)
    labels = jnp.argmin(dists, axis=1).astype(jnp.int32)
    member_d = jnp.take_along_axis(dists, labels[:, None], axis=1)[:, 0]
    list_vecs, list_index, sizes, _ = pack_padded_lists(
        np.asarray(x), np.arange(n, dtype=np.int32), np.asarray(labels), L
    )
    radii = jnp.zeros(L, jnp.float32).at[labels].max(member_d)
    return BallCoverIndex(
        canonical, landmarks, jnp.asarray(list_vecs), jnp.asarray(list_index),
        jnp.asarray(sizes), radii,
    )


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "metric"))
def _query_jit(landmarks, list_vecs, list_index, queries,
               k: int, n_probes: int, metric: str):
    base = "haversine" if metric == "haversine" else "sqeuclidean"
    L, cap, d = list_vecs.shape
    ql = _dist(queries, landmarks, base)                   # [q, L]
    _, probes = select_k(ql, n_probes, select_min=True)    # [q, p]
    vecs = list_vecs[probes]                               # [q, p, cap, d]
    ids = list_index[probes]                               # [q, p, cap]
    ip = jnp.einsum("qd,qpcd->qpc", queries, vecs, precision=_PREC)
    if base == "haversine":
        # haversine is cheap enough to evaluate directly on the gathered rows
        q_e = queries[:, None, None, :]
        sdlat = jnp.sin((vecs[..., 0] - q_e[..., 0]) / 2)
        sdlon = jnp.sin((vecs[..., 1] - q_e[..., 1]) / 2)
        h = sdlat * sdlat + jnp.cos(q_e[..., 0]) * jnp.cos(vecs[..., 0]) * sdlon * sdlon
        dist = 2.0 * jnp.arcsin(jnp.sqrt(jnp.clip(h, 0.0, 1.0)))
    else:
        v2 = jnp.sum(vecs * vecs, axis=3)
        q2 = jnp.sum(queries * queries, axis=1)
        dist = jnp.maximum(q2[:, None, None] + v2 - 2.0 * ip, 0.0)
    dist = jnp.where(ids < 0, jnp.inf, dist)
    flat_d = dist.reshape(queries.shape[0], -1)
    flat_i = ids.reshape(queries.shape[0], -1)
    v, i = select_k(flat_d, k, select_min=True, input_indices=flat_i)
    if metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@traced("ball_cover.knn_query")
def knn_query(
    index: BallCoverIndex,
    queries: jax.Array,
    k: int,
    *,
    n_probes: int = 0,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """kNN via ball probing (ref: ball_cover.cuh knn_query; n_probes=L ⇒
    exact, smaller ⇒ the reference's approximate/perf mode)."""
    res = ensure(res)
    queries = jnp.asarray(queries, jnp.float32)
    L = index.n_landmarks
    p = min(n_probes or max(1, int(np.sqrt(L)) * 4), L)
    return _query_jit(
        index.landmarks, index.list_vecs, index.list_index, queries,
        int(k), int(p), index.metric,
    )


@traced("ball_cover.all_knn_query")
def all_knn_query(
    index: BallCoverIndex, k: int, *, n_probes: int = 0,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """kNN of every indexed point (ref: ball_cover.cuh all_knn_query)."""
    # reconstruct dataset order from the padded lists
    ids = np.asarray(index.list_index)
    vecs = np.asarray(index.list_vecs)
    live = ids >= 0
    order = np.argsort(ids[live])
    data = vecs[live][order]
    return knn_query(index, jnp.asarray(data), k, n_probes=n_probes, res=res)


@traced("ball_cover.eps_nn")
def eps_nn(
    index: BallCoverIndex,
    queries: jax.Array,
    eps: float,
    *,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """ε-ball adjacency via landmark pruning: balls with
    dist(q, landmark) − radius > ε cannot contain matches
    (ref: ball_cover.cuh eps_nn — the triangle-inequality filter)."""
    res = ensure(res)
    queries = jnp.asarray(queries, jnp.float32)
    base = "haversine" if index.metric == "haversine" else "sqeuclidean"
    # eps is expressed in the *index metric*: squared-L2 for sqeuclidean,
    # plain L2 for euclidean, radians for haversine; internal distances are
    # squared for the L2 family, so normalize eps to the internal space
    if index.metric == "euclidean":
        eps_int = float(eps) ** 2
    else:
        eps_int = float(eps)
    ql = _dist(queries, index.landmarks, base)             # [q, L]
    if base == "sqeuclidean":
        # prune in the metric's own space: √dq − √r ≤ √eps_int
        cant = jnp.sqrt(ql) - jnp.sqrt(index.radii)[None, :] > np.sqrt(eps_int)
    else:
        cant = ql - index.radii[None, :] > eps_int
    n = int((np.asarray(index.list_index) >= 0).sum())
    q = queries.shape[0]
    adj = np.zeros((q, n), bool)
    # scan only the balls that survive pruning (host loop over landmarks —
    # ball count is √n; each scan is one batched distance)
    cant = np.asarray(cant)
    for l in range(index.n_landmarks):
        need = ~cant[:, l]
        if not need.any():
            continue
        ids = np.asarray(index.list_index[l])
        live = ids >= 0
        vecs = index.list_vecs[l][jnp.asarray(live)]
        d = np.asarray(_dist(queries, vecs, base))
        hit = d <= eps_int
        adj[:, ids[live]] |= hit & need[:, None]
    return jnp.asarray(adj), jnp.asarray(adj.sum(1).astype(np.int32))
