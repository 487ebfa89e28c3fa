"""Reusable Pallas/TPU kernel building blocks — the device-util toolkit.

The reference keeps a kernel toolkit under ``cpp/include/raft/util/``
(warp_primitives.cuh, bitonic_sort.cuh, pow2_utils.cuh, vectorized.cuh,
reduction.cuh — SURVEY §2.2) plus a shared-memory tiling-policy base for
pairwise kernels (``linalg/contractions.cuh``, §2.3). On TPU the warp/SM
machinery has no analog — the compiler owns vectorization — but the same
three needs recur in every hand-written kernel:

1. power-of-two / padding address math        (pow2_utils.cuh analog)
2. a tile-size policy fitting VMEM            (contractions.cuh analog)
3. an in-kernel running top-k maintenance     (bitonic warp-queue analog,
                                               select_warpsort.cuh idea)

They live here so each Pallas kernel composes them instead of re-deriving
them. Everything is a pure jnp function usable both inside ``pallas_call``
kernels and in plain XLA code (and therefore testable on CPU without
interpret mode).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# address math (ref: util/pow2_utils.cuh, util/integer_utils.hpp)

#: TPU native tile quanta: 8 sublanes × 128 lanes (f32).
SUBLANE = 8
LANE = 128


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    return cdiv(x, multiple) * multiple


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def pad_dim(x: jax.Array, axis: int, multiple: int, fill=0) -> jax.Array:
    """Pad one axis up to a multiple (the kernel-edge guard the reference
    handles with per-thread bounds checks; on TPU padding is the idiom)."""
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


# ---------------------------------------------------------------------------
# tile policy (ref: linalg/contractions.cuh Policy4x4 etc.)


@dataclass(frozen=True)
class TilePolicy:
    """Tile shape for an [m, d] × [n, d] pairwise contraction kernel."""

    tile_m: int
    tile_n: int
    grid: Tuple[int, int]
    vmem_bytes: int  # estimated per-step VMEM footprint


def choose_tile_policy(
    m: int,
    n: int,
    d: int,
    *,
    itemsize: int = 4,
    extra_cols: int = 0,
    vmem_budget: int = 8 * 1024 * 1024,
    max_tile_m: int = 512,
    max_tile_n: int = 1024,
) -> TilePolicy:
    """Pick (tile_m, tile_n) so both operand tiles + the score tile fit the
    VMEM budget (the reference solves the same constraint against shared
    memory with hard-coded Policy types, contractions.cuh; here it's a
    closed-form shrink from the largest MXU-aligned tiles).

    ``extra_cols`` accounts for per-kernel extras held per tile_m row
    (e.g. a running top-k of width k_pad).
    """
    d_pad = round_up(max(d, 1), LANE)
    tile_m = min(max_tile_m, round_up(max(m, 1), SUBLANE))
    tile_n = min(max_tile_n, round_up(max(n, 1), LANE))

    def footprint(tm: int, tn: int) -> int:
        # q tile + x tile + f32 score tile + extras
        return (
            (tm + tn) * d_pad * itemsize
            + tm * tn * 4
            + tm * extra_cols * 8
        )

    # halve-then-re-round so tiles always stay on the native quantum (a
    # non-power-of-two start like 160 must not shrink below/for off LANE)
    while footprint(tile_m, tile_n) > vmem_budget and tile_n > LANE:
        tile_n = max(LANE, round_up(tile_n // 2, LANE))
    while footprint(tile_m, tile_n) > vmem_budget and tile_m > SUBLANE:
        tile_m = max(SUBLANE, round_up(tile_m // 2, SUBLANE))
    return TilePolicy(
        tile_m,
        tile_n,
        (cdiv(m, tile_m), cdiv(n, tile_n)),
        footprint(tile_m, tile_n),
    )


# ---------------------------------------------------------------------------
# in-kernel running top-k (ref idea: matrix/detail/select_warpsort.cuh warp
# queues — fold a fresh candidate tile into a resident sorted queue)


def fold_topk(
    run_v: jax.Array,   # [rows, k_pad] current best values (ascending-ish)
    run_i: jax.Array,   # [rows, k_pad] their indices
    cand_v: jax.Array,  # [rows, c] new candidate values
    cand_i: jax.Array,  # [rows, c] their indices
    k: int,
    *,
    worst: float = float("inf"),
) -> Tuple[jax.Array, jax.Array]:
    """Fold a candidate tile into a resident top-k (select-min): k rounds of
    masked min-extraction over the concatenated pool. O(k·(k_pad+c)) VPU work
    with no sort network — the right trade for the k ≤ 128 regime the fused
    kernels serve. Returns ([rows, k_pad] vals, idx) with slots ≥ k = worst.
    """
    pool_v = jnp.concatenate([run_v, cand_v], axis=1)
    pool_i = jnp.concatenate([run_i, cand_i], axis=1)
    return _extract_topk(pool_v, pool_i, k, run_v.shape[1], worst)


def select_topk(
    cand_v: jax.Array,  # [rows, c] candidate values, c >= k
    cand_i: jax.Array,  # [rows, c] their indices
    k: int,
    *,
    worst: float = float("inf"),
) -> Tuple[jax.Array, jax.Array]:
    """The k smallest of each row of one candidate tile, ascending:
    :func:`fold_topk` into an empty resident top-k, without extracting
    over the empty run's k slots. Returns ([rows, k] vals, idx)."""
    return _extract_topk(cand_v, cand_i, k, k, worst)


def _extract_topk(pool_v, pool_i, k: int, k_pad: int, worst: float):
    """k rounds of masked min-extraction over ``pool``; ties go to the
    first position."""
    rows, n_pool = pool_v.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, n_pool), 1)

    def extract(t, carry):
        pool, out_v, out_i = carry
        m = jnp.min(pool, axis=1)
        first = jnp.min(jnp.where(pool == m[:, None], pos, n_pool), axis=1)
        onehot = pos == first[:, None]
        sel_i = jnp.sum(jnp.where(onehot, pool_i, 0), axis=1)
        hole = jax.lax.broadcasted_iota(jnp.int32, (rows, k_pad), 1) == t
        out_v = jnp.where(hole, m[:, None], out_v)
        out_i = jnp.where(hole, sel_i[:, None], out_i)
        return jnp.where(onehot, worst, pool), out_v, out_i

    out_v0 = jnp.full((rows, k_pad), worst, pool_v.dtype)
    out_i0 = jnp.zeros((rows, k_pad), pool_i.dtype)
    _, out_v, out_i = jax.lax.fori_loop(
        0, k, extract, (pool_v, out_v0, out_i0)
    )
    return out_v, out_i


def quantize_queries_i8(q: jax.Array):
    """Per-row symmetric int8 quantization of query rows [.., rot] →
    (q_i8 same shape, scale [.., 1] f32 with a 1e-12 floor). THE one copy
    of the quantized-query recipe — the Pallas int8 scan leg and both XLA
    int8 score paths must stay numerically identical for the kernel-vs-XLA
    parity tests to hold (pure jnp, Pallas-safe)."""
    sq = jnp.maximum(
        jnp.max(jnp.abs(q), axis=-1, keepdims=True) / 127.0, 1e-12
    )
    q_i8 = jnp.clip(jnp.round(q / sq), -127, 127).astype(jnp.int8)
    return q_i8, sq


def int8_scored_ip(qr: jax.Array, dec_i8: jax.Array, dims, scan_scale):
    """q·y inner products against an int8 scan cache: per-row symmetric
    quantization of ``qr`` (:func:`quantize_queries_i8`), int8×int8 MXU
    dot with the given ``dot_general`` dimension numbers, f32 rescale by
    (per-row scale × global ``scan_scale``). THE one copy of the XLA
    int8-score recipe — the single-device query/probe-major scans and the
    sharded scan all call this so they stay numerically identical to each
    other and to the Pallas kernel's quantized leg."""
    from jax import lax

    q_i8, sq = quantize_queries_i8(qr)
    ip_i32 = lax.dot_general(
        q_i8, dec_i8, dims, preferred_element_type=jnp.int32
    )
    # sq is qr.shape[:-1] + (1,); right-pad axes so it broadcasts over the
    # ip result's trailing (…, cap) dims
    extra = ip_i32.ndim - sq.ndim
    if extra:
        sq = sq.reshape(sq.shape[:-1] + (1,) * (extra + 1))
    return ip_i32.astype(jnp.float32) * (sq * scan_scale)


def col_ids_tile(rows: int, tile_n: int, col_base) -> jax.Array:
    """Global column indices of a [rows, tile_n] tile starting at col_base
    (the vectorized-iota every tiled kernel needs)."""
    return col_base + jax.lax.broadcasted_iota(jnp.int32, (rows, tile_n), 1)
