"""Refine: re-rank ANN candidates with exact distances.

Reference: ``neighbors/refine.cuh`` — takes a dataset, queries, and candidate
neighbor ids (typically from ivf_pq::search with k' > k), recomputes exact
distances for each (query, candidate) pair, and selects the top-k
(device impl ``detail/refine_device.cuh``; host/OpenMP impl
``detail/refine_host-inl.hpp``; used by CAGRA build
``detail/cagra/cagra_build.cuh:146-196``).

TPU shape: candidates are a static [q, k'] id matrix → one batched gather of
candidate vectors + a batched row-vs-row distance (VPU/MXU) + select_k.
There is no irregularity, so this is pure XLA. A ``host=True`` path mirrors
the reference's CPU refine (numpy, useful to overlap with device work).
"""

from __future__ import annotations

from typing import Optional, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC
from raft_tpu.neighbors._common import lane_pad
from raft_tpu.ops.matrix import select_k
from raft_tpu.core.trace import traced


#: per-tile candidate-gather budget: the [tile, k', d] f32 gather (plus
#: XLA's copy of it) must fit HBM next to the dataset — an unbounded
#: gather OOMed the chip at CAGRA-build scale (100k queries × 258
#: candidates × 96 dims → 30.8 GB program; ladder config4, round 4)
_REFINE_TILE_BYTES = 512 * 1024 * 1024


def _refine_query_tile(q: int, kprime: int, d: int) -> int:
    per_row = kprime * d * 4
    tile = max(8, _REFINE_TILE_BYTES // max(1, per_row))
    return min(q, 1 << (tile.bit_length() - 1))


def prepare_rows(dataset) -> jax.Array:
    """The device rows a refine that is called again and again should keep:
    ``dataset`` zero-padded to whole 128-lane tiles (``_common.lane_pad``;
    the array itself when its width already is), in its own dtype.

    The refine's row gather reads the row-major tiling; a resident
    ``[n, 96]`` array is stored transposed instead, and every refine call
    would relayout all of it first.  :func:`refine` takes the padded rows
    with queries of the logical width and returns the same results: it
    drops the zero lanes from each gathered candidate block."""
    return lane_pad(jnp.asarray(dataset))


@functools.partial(jax.jit, static_argnames=("k", "metric", "tile"))
def _refine_jit(dataset, queries, candidates, k: int, metric: str,
                tile: int | None = None):
    q, kprime = candidates.shape
    if tile is not None and tile < q:
        pad = -q % tile
        qs = jnp.pad(queries, ((0, pad), (0, 0))).reshape(
            -1, tile, queries.shape[1]
        )
        cs = jnp.pad(
            candidates, ((0, pad), (0, 0)), constant_values=-1
        ).reshape(-1, tile, kprime)
        v, i = jax.lax.map(
            lambda t: _refine_tile(dataset, t[0], t[1], k, metric), (qs, cs)
        )
        return v.reshape(-1, k)[:q], i.reshape(-1, k)[:q]
    return _refine_tile(dataset, queries, candidates, k, metric)


def _refine_tile(dataset, queries, candidates, k: int, metric: str):
    safe = jnp.clip(candidates, 0, dataset.shape[0] - 1)
    # rows from prepare_rows are wider than the queries: the gather reads
    # them as stored, and their zero lanes are dropped from the block
    cand = dataset[safe][..., : queries.shape[1]].astype(jnp.float32)
    qf = queries.astype(jnp.float32)
    ip = jnp.einsum("qd,qcd->qc", qf, cand, precision=_PREC)
    if metric == "inner_product":
        dist = -ip
    elif metric == "cosine":
        qn = jnp.maximum(jnp.linalg.norm(qf, axis=1), 1e-12)
        cn = jnp.maximum(jnp.linalg.norm(cand, axis=2), 1e-12)
        dist = 1.0 - ip / (qn[:, None] * cn)
    else:
        c2 = jnp.sum(cand * cand, axis=2)
        q2 = jnp.sum(qf * qf, axis=1)
        dist = jnp.maximum(q2[:, None] + c2 - 2.0 * ip, 0.0)
    dist = jnp.where(candidates < 0, jnp.inf, dist)
    v, i = select_k(dist, k, select_min=True, input_indices=candidates)
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@traced("refine.refine")
def refine(
    dataset: jax.Array,
    queries: jax.Array,
    candidates: jax.Array,
    k: int,
    *,
    metric: str = "sqeuclidean",
    host: bool = False,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact re-rank of ``candidates`` [q, k'] → top-k (distances, indices).

    Negative candidate ids are treated as invalid (distance +inf), matching
    the reference's handling of underfull candidate lists.  ``dataset`` may
    be the lane-padded rows of :func:`prepare_rows`.
    """
    res = ensure(res)
    canonical = DISTANCE_TYPES[metric]
    candidates = jnp.asarray(candidates, jnp.int32)
    if k > candidates.shape[1]:
        raise ValueError(f"k={k} > candidate count {candidates.shape[1]}")
    width, d = np.shape(dataset)[1], np.shape(queries)[1]
    if width < d:
        raise ValueError(f"dataset rows are {width} wide, the queries {d}")
    if host:
        # the explicit CPU refine; serving refines on device (host=False)
        qh = np.asarray(queries)  # raft-tpu: ignore[HOSTSYNC] opt-in host refine
        ds = np.asarray(dataset)  # raft-tpu: ignore[HOSTSYNC] opt-in host refine
        if width != d:  # drop the zero lanes of prepare_rows
            ds = np.ascontiguousarray(ds[:, :d])
        return _refine_host(ds, qh, np.asarray(candidates), k, canonical)  # raft-tpu: ignore[HOSTSYNC] opt-in host refine
    tile = _refine_query_tile(
        candidates.shape[0], candidates.shape[1], dataset.shape[1]
    )
    return _refine_jit(
        jnp.asarray(dataset), jnp.asarray(queries), candidates, int(k),
        canonical, tile=tile,
    )


def _refine_host(dataset, queries, candidates, k, metric):
    """CPU refine (ref: detail/refine_host-inl.hpp — OpenMP loop over
    queries). Uses the native threaded C++ entry point when the toolchain
    built it (raft_runtime parity); falls back to vectorized numpy."""
    from raft_tpu.core import native

    if (
        metric in native._METRIC_CODES
        and dataset.dtype == np.float32
        and dataset.flags.c_contiguous  # native path must not copy the dataset
        and native.available()
    ):
        v, i = native.refine_host(dataset, queries, candidates, k, metric)
        return jnp.asarray(v), jnp.asarray(i)
    safe = np.clip(candidates, 0, dataset.shape[0] - 1)
    cand = dataset[safe].astype(np.float32)
    qf = queries.astype(np.float32)
    ip = np.einsum("qd,qcd->qc", qf, cand)
    if metric == "inner_product":
        dist = -ip
    elif metric == "cosine":
        qn = np.maximum(np.linalg.norm(qf, axis=1), 1e-12)
        cn = np.maximum(np.linalg.norm(cand, axis=2), 1e-12)
        dist = 1.0 - ip / (qn[:, None] * cn)
    else:
        c2 = np.sum(cand * cand, axis=2)
        q2 = np.sum(qf * qf, axis=1)
        dist = np.maximum(q2[:, None] + c2 - 2.0 * ip, 0.0)
    dist = np.where(candidates < 0, np.inf, dist)
    order = np.argsort(dist, axis=1, kind="stable")[:, :k]
    v = np.take_along_axis(dist, order, axis=1)
    i = np.take_along_axis(candidates, order, axis=1)
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = np.sqrt(np.maximum(v, 0.0))
    return jnp.asarray(v), jnp.asarray(i)
