"""The plain reference: exact search over the raw seeded rows, in plain
``jnp`` and ``lax.top_k``, and the exact distance of any (query, row) pair.

It imports nothing of the program and takes nothing the program made: the
rows and queries come from ``benchmark.lib.data`` and the seed.  The exact
top-k follows ``chip_smoke.exact_topk`` (PR 21): HIGHEST-precision matmul,
streamed over blocks of base rows, here also tiled over queries so that one
score block stays near a GiB.  The same search computed from bfloat16 rows
and queries at the default precision is the control (``precision="bf16"``).

Distances follow the program's convention: ``inner_product`` reports the
inner product (larger is nearer), ``sqeuclidean`` the squared distance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BASE_BLOCK = 1 << 17   # base rows per scoring block
QUERY_TILE = 2048      # queries per scoring block
PAIR_CHUNK = 1 << 14   # answered rows per exact-distance chunk


def _larger_is_nearer(metric: str) -> bool:
    if metric == "inner_product":
        return True
    if metric == "sqeuclidean":
        return False
    raise ValueError(f"reference supports inner_product and sqeuclidean, "
                     f"not {metric!r}")


@functools.partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _topk_step(best_v, best_i, q, xb, offset, n, *, k, metric, precision):
    if precision == "bf16":
        qc, xc = q.astype(jnp.bfloat16), xb.astype(jnp.bfloat16)
        ip = jnp.matmul(qc, xc.T, preferred_element_type=jnp.float32)
        qf, xf = qc.astype(jnp.float32), xc.astype(jnp.float32)
    else:
        ip = jnp.matmul(q, xb.T, precision=jax.lax.Precision.HIGHEST)
        qf, xf = q, xb
    if metric == "inner_product":
        s = ip
    else:
        d2 = (jnp.sum(qf * qf, axis=1)[:, None] + jnp.sum(xf * xf, axis=1)[None]
              - 2.0 * ip)
        s = -d2
    rows = offset + jnp.arange(xb.shape[0], dtype=jnp.int32)
    s = jnp.where(rows[None] < n, s, -jnp.inf)
    v = jnp.concatenate([best_v, s], axis=1)
    i = jnp.concatenate([best_i, jnp.broadcast_to(rows, s.shape)], axis=1)
    v, j = jax.lax.top_k(v, k)
    return v, jnp.take_along_axis(i, j, axis=1)


def search(base, queries, k: int, metric: str, precision: str = "highest"):
    """Exact top-k of every query over ``base``: (distances, ids) as numpy
    arrays in the program's convention, nearest first."""
    larger = _larger_is_nearer(metric)
    n = base.shape[0]
    block = min(BASE_BLOCK, n)
    out_v, out_i = [], []
    for q0 in range(0, queries.shape[0], QUERY_TILE):
        q = jnp.asarray(queries[q0:q0 + QUERY_TILE], jnp.float32)
        best_v = jnp.full((q.shape[0], k), -jnp.inf, jnp.float32)
        best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
        for s0 in range(0, n, block):
            xb = base[s0:s0 + block]
            if xb.shape[0] < block:  # last block: pad to the one shape
                xb = jnp.pad(xb, ((0, block - xb.shape[0]), (0, 0)))
            best_v, best_i = _topk_step(best_v, best_i, q, xb, s0, n, k=k,
                                        metric=metric, precision=precision)
        out_v.append(np.asarray(best_v))
        out_i.append(np.asarray(best_i))
    v, i = np.concatenate(out_v), np.concatenate(out_i)
    return (v if larger else -v), i


@functools.partial(jax.jit, static_argnames=("metric",))
def _pair_chunk(base, q, ids, *, metric):
    """Exact distance and rounding scale of each (query, id) pair, by
    elementwise f32 arithmetic (no matmul, so no MXU precision choice)."""
    x = base[jnp.clip(ids, 0, base.shape[0] - 1)]          # [r, k, d]
    qq = q[:, None, :]
    if metric == "inner_product":
        d = jnp.sum(qq * x, axis=-1)
        scale = jnp.sqrt(jnp.sum(qq * qq, -1)) * jnp.sqrt(jnp.sum(x * x, -1))
    else:
        d = jnp.sum((qq - x) ** 2, axis=-1)
        scale = jnp.sum(qq * qq, -1) + jnp.sum(x * x, -1)
    return d, scale


def pair_distances(base, queries: np.ndarray, ids: np.ndarray, metric: str):
    """(exact distance, scale) of each answered (query row, id): arrays of
    ``ids.shape``.  ``scale`` bounds the magnitude of the terms a distance
    is computed from, so rounding error is read against it."""
    _larger_is_nearer(metric)
    r = ids.shape[0]
    d_out = np.empty(ids.shape, np.float32)
    s_out = np.empty(ids.shape, np.float32)
    for s0 in range(0, r, PAIR_CHUNK):
        e = min(s0 + PAIR_CHUNK, r)
        q = np.zeros((PAIR_CHUNK, queries.shape[1]), np.float32)
        i = np.zeros((PAIR_CHUNK, ids.shape[1]), np.int32)
        q[: e - s0], i[: e - s0] = queries[s0:e], ids[s0:e]
        d, s = _pair_chunk(base, q, i, metric=metric)
        d_out[s0:e], s_out[s0:e] = np.asarray(d)[: e - s0], np.asarray(s)[: e - s0]
    return d_out, s_out
