"""Pallas per-row k-selection: VMEM-resident masked-extraction top-k.

``ops.matrix.select_k`` is the single most load-bearing primitive in the
library (ref: matrix/detail/select_radix.cuh, select_warpsort.cuh — the
reference spends two whole CUDA kernel families on it).  Its XLA
formulations materialize a full-width sort in HBM: ``lax.top_k`` lowers to
a sort-based TopK, and the tie-stable merge variant
(``select_k_stable``) is a two-key full-row ``lax.sort``.  At serving
merge widths (a few hundred to a few thousand candidates, k ≤ 128) that
sort dominates the merge legs — the cross-shard gather merge, the tiled
brute-force merges, and the ragged ``mask_row_k`` path all pay it.

This kernel keeps the whole row in VMEM and runs k rounds of masked
min-extraction (the warp-select idea expressed as VPU-wide ops):

  round t:  m      = min over not-yet-removed values
            tiebrk = min tie key among the entries attaining m
            pick   = first position attaining (m, tiebrk)
            out[t] = (m, payload[pick]);  removed |= pick

O(k·n) VPU work with no sort network, one HBM read of the row and one
k-wide write — the same trade ``toolkit.fold_topk`` makes, but with a
*removal mask* instead of overwrite-with-worst so legitimate +inf
candidates (sentinel pads from upstream merges) are never re-extracted.

Both tie-break disciplines ride one kernel body — the wrapper picks the
tie key:

- **positional** (parity with ``lax.top_k``'s lowest-index-wins): tie key
  = column position, payload = ``input_indices`` (or the position);
- **stable** (parity with ``select_k_stable``'s smallest-id-wins): tie
  key = ids with negatives remapped past every real id, payload = ids
  with negatives as −1.

Padding: rows pad to the sublane quantum and columns to the lane quantum
with (+inf, worst tie, −1) slots; a pad can never win a round while a
real candidate remains, and k ≤ n real candidates always remain.
Validated in interpret mode on CPU (exact-match vs both XLA paths) plus a
TPU-gated compile test.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from raft_tpu.kernels.toolkit import LANE, SUBLANE, round_up
from raft_tpu.ops import cost as ops_cost

_INF = float("inf")
_SENTINEL = 2**31 - 1

#: widest row the VMEM-resident select serves — past it matrix.select_k's
#: chunked tournament (narrow sorts) tiles better and the O(k·n) rounds
#: stop paying for themselves
MAX_N = 8192
#: deepest k — matches the serving regime (and fold_topk's k ≤ 128 trade)
MAX_K = 128

_ROW_BLOCK = SUBLANE


def select_k_supported(n: int, k: int, dtype) -> bool:
    """Routing gate for ``ops.matrix.select_k`` / ``select_k_stable``:
    float rows (f32/bf16 — compared in exact f32 upcast) at VMEM-resident
    widths.  Integer rows keep matrix.py's exact argsort/int64 paths."""
    dt = jnp.dtype(dtype)
    return (
        dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
        and 0 < k <= MAX_K
        and k <= n <= MAX_N
    )


def _select_kernel(v_ref, tie_ref, pay_ref, out_v_ref, out_i_ref, *,
                   k: int, n_pad: int):
    """One row block: k masked min-extraction rounds.  The removal mask
    (not overwrite-with-worst) is what makes +inf a legal candidate value:
    a removed entry can never re-win even when the running min reaches
    +inf, so sentinel-padded merge rows select exactly like the XLA sort."""
    v = v_ref[...]
    tie = tie_ref[...]
    pay = pay_ref[...]
    rows = v.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, n_pad), 1)

    def extract(t, carry):
        removed, out_v, out_i = carry
        live = removed == 0
        eff = jnp.where(live, v, _INF)
        m = jnp.min(eff, axis=1)
        # removed entries sit at +inf; exclude them so an all-inf tail
        # round still picks a fresh entry
        is_min = (eff == m[:, None]) & live
        sel_tie = jnp.min(jnp.where(is_min, tie, _SENTINEL), axis=1)
        cand = is_min & (tie == sel_tie[:, None])
        first = jnp.min(jnp.where(cand, pos, n_pad), axis=1)
        pick = pos == first[:, None]
        sel_pay = jnp.sum(jnp.where(pick, pay, 0), axis=1)
        hole = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1) == t
        out_v = jnp.where(hole, m[:, None], out_v)
        out_i = jnp.where(hole, sel_pay[:, None], out_i)
        return removed | pick.astype(jnp.int32), out_v, out_i

    # int32, not bool: Mosaic cannot legalize an scf.for carrying an i1
    # vector, so a bool mask fails to compile for the chip
    removed0 = jnp.zeros((rows, n_pad), jnp.int32)
    out_v0 = jnp.full((rows, k), _INF, jnp.float32)
    out_i0 = jnp.full((rows, k), -1, jnp.int32)
    _, out_v, out_i = jax.lax.fori_loop(
        0, k, extract, (removed0, out_v0, out_i0)
    )
    out_v_ref[...] = out_v
    out_i_ref[...] = out_i


def select_k_pallas(
    scores: jax.Array,
    k: int,
    *,
    select_min: bool = True,
    stable: bool = False,
    input_indices: Optional[jax.Array] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row top-k with the fused VMEM kernel.  ``stable=False`` is
    exact-match with ``matrix.select_k``'s float path (lowest position
    wins ties); ``stable=True`` with ``matrix.select_k_stable`` (smallest
    id wins, negative ids lose every tie and surface as −1).  Output rows
    are sorted (ascending for ``select_min``) by construction — each
    round extracts the global remaining min."""
    rows, n = scores.shape
    if not select_k_supported(n, k, scores.dtype):
        raise ValueError(
            f"select_k_pallas unsupported shape/dtype: n={n} k={k} "
            f"{scores.dtype}"
        )
    v = scores.astype(jnp.float32)
    if not select_min:
        v = -v
    n_pad = round_up(max(n, LANE), LANE)
    r_pad = round_up(max(rows, 1), _ROW_BLOCK)
    v = jnp.pad(
        v, ((0, r_pad - rows), (0, n_pad - n)), constant_values=_INF
    )
    pos = jax.lax.broadcasted_iota(jnp.int32, (r_pad, n_pad), 1)
    ids = None
    if input_indices is not None:
        ids = jnp.broadcast_to(
            input_indices.astype(jnp.int32), (rows, n)
        )
        ids = jnp.pad(
            ids, ((0, r_pad - rows), (0, n_pad - n)), constant_values=-1
        )
    if stable:
        base = ids if ids is not None else jnp.where(pos < n, pos, -1)
        tie = jnp.where(base < 0, _SENTINEL, base)
        pay = jnp.where(base < 0, -1, base)
    else:
        # pad positions exceed every real position, so pads lose the
        # positional tie-break among equal (+inf) values by construction
        tie = pos
        pay = ids if ids is not None else pos

    c = ops_cost.select_k_cost(r_pad, n_pad, k)
    ops_cost.note("select_k", c)
    out_v, out_i = pl.pallas_call(
        functools.partial(_select_kernel, k=k, n_pad=n_pad),
        grid=(r_pad // _ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((_ROW_BLOCK, n_pad), lambda r: (r, 0)),
            pl.BlockSpec((_ROW_BLOCK, n_pad), lambda r: (r, 0)),
            pl.BlockSpec((_ROW_BLOCK, n_pad), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_ROW_BLOCK, k), lambda r: (r, 0)),
            pl.BlockSpec((_ROW_BLOCK, k), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((r_pad, k), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )(v, tie, pay)
    out_v = out_v[:rows]
    out_i = out_i[:rows]
    if not select_min:
        out_v = -out_v
    return out_v.astype(scores.dtype), out_i
