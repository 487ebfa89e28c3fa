"""Data of a configuration's geometry, made on the device.

Copied from ``raft_tpu/bench/datasets.synthetic_geometry`` (PR 21's
generator): a mixture of Gaussians with ``max(16, sqrt(n)/4)`` centres
drawn uniformly from [0, 10)^d and unit noise, base rows and queries drawn
from the same mixture.  Here it runs as one jitted call on the device, so
a run's set-up does not pay for a host-side draw and upload of the base.

The base rows are the configuration's dataset, as a published dataset is
one fixed file: they come from the configuration's own ``data_seed``, with
the rows dealt to the centres in equal shares.  ``--seed`` draws the query
pool (each query's centre and noise) and, in ``lib/traffic.py``, the order
the pool is sent in.  A base drawn from ``--seed`` gave the index's list
split a different list count on every seed (PERF.md), so each new seed
compiled part of the build and the search again in its set-up.

Where the configuration sets ``unit_norm``, base rows and queries are
scaled to unit length, so that inner product is the cosine by which the
source (ann-benchmarks' angular sets) ranks its neighbours.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 2**62 (the driver's
    seeds pass 32 signed bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def n_centres(n: int) -> int:
    return max(16, int(math.sqrt(n) / 4))


def _unit(x):
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _mixture(data_key, query_key, n: int, d: int, q: int, c: int, unit: bool):
    k_c, k_l, k_n = jax.random.split(data_key, 3)
    k_ql, k_qn = jax.random.split(query_key)
    centres = jax.random.uniform(k_c, (c, d), jnp.float32) * 10.0
    shares = jax.random.permutation(k_l, jnp.arange(n, dtype=jnp.int32) % c)
    base = centres[shares] + jax.random.normal(k_n, (n, d), jnp.float32)
    queries = centres[jax.random.randint(k_ql, (q,), 0, c)] + jax.random.normal(
        k_qn, (q, d), jnp.float32
    )
    if unit:
        base, queries = _unit(base), _unit(queries)
    return base, queries


def make(geometry: dict, seed: int):
    """(base [n, d] f32, queries [q, d] f32), both on the default device:
    the base from the configuration's ``data_seed``, the queries from
    ``seed``."""
    n, d, q = int(geometry["n"]), int(geometry["d"]), int(geometry["queries"])
    return _mixture(seed_key(geometry["data_seed"]), seed_key(seed), n, d, q,
                    n_centres(n), bool(geometry.get("unit_norm", False)))
