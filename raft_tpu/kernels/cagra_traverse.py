"""Pallas fused CAGRA hop: frontier expansion + scoring + dedup + merge.

``neighbors/cagra.py``'s beam search runs a ``lax.while_loop`` whose body
is a gather-heavy XLA chain: gather the parents' neighbor lists, gather
(and cast) the candidates' dataset rows into a materialized
[tile, width·deg, d] HBM copy, score on the MXU, dedup by broadcast
membership, then a global ``select_k`` merge back into the [tile, itopk]
candidate buffer.  The dataset-row gather copy plus the full-width merge
sort are the hop's dominant HBM traffic — exactly the irregular workload
the "Ragged Paged Attention" line of work (PAPERS.md) shows hand-written
TPU kernels beat dense HLO at.

This kernel fuses one hop over a ``grid=(tile, width)`` schedule:

- the *scalar-prefetched frontier ids* drive a dynamic-BlockSpec DMA of
  each parent's neighbor list into VMEM (the ivf_scan pattern), and the
  candidate ids (a tiny pre-gathered int table riding as a second
  prefetched scalar) drive per-row in-kernel DMAs of the candidates'
  dataset rows — the [tile, width·deg, d] gather copy never exists;
- MXU scoring ([1, d] × [deg, d]ᵀ, f32 accumulate at HIGHEST precision,
  matching the XLA hop's ``_query_distance`` einsum);
- visited-dedup by membership against the VMEM-resident merged buffer
  plus a strict-upper within-step mask (the reference's visited-hashmap
  role, detail/cagra/hashmap.hpp);
- itopk buffer maintenance in VMEM via ``toolkit.fold_topk``, with the
  same resident-wins tie discipline as the XLA merge (buffer entries
  occupy the pool's first positions).

The merged buffer lives in scratch across the ``width`` steps of one
query; the final step recovers explored flags by membership against the
input buffer (buffer ids are unique, so the flag transfers exactly) and
writes the three state planes once.

The hop is bit-equivalent to the XLA body up to value ties at the
buffer's eviction boundary (an evicted-then-reencountered id can displace
an equal-valued different id) — the parity tests therefore gate on
recall equivalence, the same gate the XLA legs hold each other to.
Filtered search keeps the XLA hop (the result-buffer side-merge needs
the raw candidate distances; see docs/kernels.md for the dispatch
matrix).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.kernels.toolkit import fold_topk
from raft_tpu.ops import cost as ops_cost
from raft_tpu.store.paged import PagedRows

_INF = float("inf")

#: widest internal buffer the fused hop serves — filtered searches widen
#: itopk past this (they keep the XLA hop anyway) and the fold's O(itopk²)
#: rounds stop paying past it
MAX_ITOPK = 512

#: name of the hop's op in a device trace (the named scope of its call)
HOP_OP = "cagra_fused_hop"


def traverse_supported(dataset, itopk: int) -> bool:
    """Routing gate for the fused hop: dense float dataset (f32/bf16 —
    rows upcast in VMEM after the DMA) at fold-friendly buffer widths.
    VPQ datasets decode on gather (no raw rows to DMA) and int8 datasets
    lack a dequant scale — both keep the XLA hop.  A paged dataset
    (:class:`~raft_tpu.store.paged.PagedRows`) rides the same per-row DMA
    with one extra prefetched-scalar page-table hop."""
    return (
        (isinstance(dataset, jax.Array) or isinstance(dataset, PagedRows))
        and jnp.dtype(dataset.dtype)
        in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
        and 0 < itopk <= MAX_ITOPK
    )


def _hop_kernel(par_ref, cand_ref, g_blk, q_blk, bd_blk, bi_blk, be_blk,
                dataset_ref, od_blk, oi_blk, oe_blk, rows_s, md_s, mi_s,
                sem, *, metric: str, deg: int, itopk: int, width: int,
                d: int, page_rows=None, ps_ref=None):
    """One (query, parent) step.  Scratch (rows_s, md_s, mi_s) persists
    across the ``width`` steps of a query; w==0 seeds the merged buffer
    from the input planes and w==width−1 writes the merged state once.

    ``page_rows``/``ps_ref`` select the paged leg: ``dataset_ref`` is then
    the HBM page pool ``[slots, page_rows, d]`` and each candidate row DMA
    translates its global id through the prefetched ``page_slot`` table —
    the "one more prefetched indirection" the page table costs."""
    t = pl.program_id(0)
    w = pl.program_id(1)
    pid = par_ref[t * width + w]

    @pl.when(w == 0)
    def _seed():
        md_s[...] = bd_blk[0]
        # invariant from the XLA wrapper: buf_i is −1 wherever buf_d is
        # +inf, so membership below never matches a stale id
        mi_s[...] = bi_blk[0]

    # ---- candidate dataset rows: per-row DMA driven by the prefetched
    # candidate-id table (invalid ids clamp to row 0; scores masked below)
    def load(j, _):
        cid = jnp.maximum(cand_ref[(t * width + w) * deg + j], 0)
        if page_rows is None:
            src = dataset_ref.at[pl.ds(cid, 1), :]
        else:
            pg = cid // page_rows
            slot = jnp.maximum(ps_ref[pg], 0)
            src = dataset_ref.at[slot, pl.ds(cid - pg * page_rows, 1), :]
        cp = pltpu.make_async_copy(src, rows_s.at[pl.ds(j, 1), :], sem)
        cp.start()
        cp.wait()
        return 0

    lax.fori_loop(0, deg, load, 0)

    rows = rows_s[...].astype(jnp.float32)                   # [deg, d]
    q = q_blk[0].astype(jnp.float32)                         # [1, d]
    ip = lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )                                                        # [1, deg]
    if metric == "inner_product":
        cd = -ip
    else:
        # v² via an MXU ones-contraction keeps every vector op 2-D; at
        # HIGHEST like ip: a DEFAULT-precision dot rounds the squares to
        # bf16 (one pass), which put ~4e-4 of the pair's scale into every
        # distance on the chip and scrambled the ranking of near neighbours
        v2 = lax.dot_general(
            jnp.full((1, d), 1.0, jnp.float32), rows * rows,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )                                                    # [1, deg]
        q2 = jnp.sum(q * q, axis=1, keepdims=True)           # [1, 1]
        cd = jnp.maximum(q2 + v2 - 2.0 * ip, 0.0)

    # ---- visited dedup: membership vs the live merged buffer (covers the
    # original buffer AND earlier parents' survivors) + a strict-upper
    # within-step mask for duplicate neighbors in one list
    cand = g_blk[0]                                          # [1, deg]
    m_i = mi_s[...]                                          # [1, itopk]
    in_buf = jnp.any(cand[:, :, None] == m_i[:, None, :], axis=2)
    pi = lax.broadcasted_iota(jnp.int32, (1, deg, deg), 1)
    pj = lax.broadcasted_iota(jnp.int32, (1, deg, deg), 2)
    dup = jnp.any(
        (cand[:, :, None] == cand[:, None, :]) & (pi < pj), axis=1
    )
    bad = (cand < 0) | in_buf | dup | (pid < 0)
    cd = jnp.where(bad, _INF, cd)
    cand = jnp.where(bad, -1, cand)

    # ---- fold into the merged buffer: residents ride the pool's first
    # positions, so fold_topk's first-position tie-break keeps the XLA
    # merge's resident-wins discipline
    v, i = fold_topk(md_s[...], m_i, cd, cand, itopk)
    # the +inf slots a short pool leaves behind must not carry ids (they
    # would shadow later finite copies) — same fixup as the XLA hop
    i = jnp.where(jnp.isfinite(v), i, -1)
    md_s[...] = v
    mi_s[...] = i

    @pl.when(w == width - 1)
    def _finish():
        mv = md_s[...]
        mi = mi_s[...]
        # explored flags transfer by membership against the input buffer
        # (ids unique): new candidates are unexplored, +inf slots explored
        hit = (mi[:, :, None] == bi_blk[0][:, None, :]) & (
            be_blk[0][:, None, :] != 0
        )
        exp = jnp.any(hit, axis=2) | ~jnp.isfinite(mv)
        od_blk[0] = mv
        oi_blk[0] = mi
        oe_blk[0] = exp.astype(jnp.int32)


def _hop_kernel_paged(par_ref, cand_ref, ps_ref, g_blk, q_blk, bd_blk,
                      bi_blk, be_blk, pool_ref, od_blk, oi_blk, oe_blk,
                      rows_s, md_s, mi_s, sem, *, metric: str, deg: int,
                      itopk: int, width: int, d: int, page_rows: int):
    """Paged entry point: same hop body, with the page-slot table riding
    as a third prefetched scalar ahead of the grid operands."""
    _hop_kernel(
        par_ref, cand_ref, g_blk, q_blk, bd_blk, bi_blk, be_blk, pool_ref,
        od_blk, oi_blk, oe_blk, rows_s, md_s, mi_s, sem, metric=metric,
        deg=deg, itopk=itopk, width=width, d=d, page_rows=page_rows,
        ps_ref=ps_ref,
    )


def cagra_fused_hop(
    dataset,                 # [n, d] f32/bf16 jax.Array or PagedRows
    graph: jax.Array,        # [n, deg] int32
    queries: jax.Array,      # [tile, d] f32
    parents: jax.Array,      # [tile, width] int32, −1 = no parent
    buf_d: jax.Array,        # [tile, itopk] f32 (+inf empty slots)
    buf_i: jax.Array,        # [tile, itopk] int32 (−1 at +inf slots)
    explored: jax.Array,     # [tile, itopk] bool (parents pre-marked)
    *,
    metric: str,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused hop; returns the merged (buf_d, buf_i, explored).
    Call from inside the search while-loop — everything here traces into
    the enclosing jit."""
    tile, itopk = buf_d.shape
    width = parents.shape[1]
    paged = isinstance(dataset, PagedRows)
    n, d = dataset.shape
    deg = graph.shape[1]
    # candidate-id table for the DMA scalars: a [tile, width, deg] int32
    # gather — 4 bytes/candidate next to the d·itemsize/candidate row
    # gather the kernel eliminates
    cand = graph[jnp.clip(parents, 0, n - 1)]
    cand = jnp.where(parents[:, :, None] < 0, -1, cand)

    c = ops_cost.cagra_traverse_cost(
        tile, width, deg, d, itopk, itemsize=dataset.dtype.itemsize
    )
    ops_cost.note("cagra_traverse", c)

    # index_maps take *rest so the same lambdas serve 2 (dense) or 3
    # (paged: + page_slot) prefetched scalar operands
    def _nbr_map(t, w, par, *rest):
        return jnp.maximum(par[t * width + w], 0), 0, 0

    def _tile_map(t, w, *rest):
        return t, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if paged else 2,
        grid=(tile, width),
        in_specs=[
            # the parent's neighbor list (dynamic)
            pl.BlockSpec((1, 1, deg), _nbr_map),
            pl.BlockSpec((1, 1, d), _tile_map),
            pl.BlockSpec((1, 1, itopk), _tile_map),
            pl.BlockSpec((1, 1, itopk), _tile_map),
            pl.BlockSpec((1, 1, itopk), _tile_map),
            pl.BlockSpec(memory_space=pltpu.ANY),   # dataset/pool in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, 1, itopk), _tile_map),
            pl.BlockSpec((1, 1, itopk), _tile_map),
            pl.BlockSpec((1, 1, itopk), _tile_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((deg, d), dataset.dtype),    # candidate rows
            pltpu.VMEM((1, itopk), jnp.float32),    # merged values
            pltpu.VMEM((1, itopk), jnp.int32),      # merged ids
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    if paged:
        kern = functools.partial(
            _hop_kernel_paged, metric=metric, deg=deg, itopk=itopk,
            width=width, d=d, page_rows=dataset.page_rows,
        )
        scalars = (
            parents.reshape(-1).astype(jnp.int32),
            cand.reshape(-1).astype(jnp.int32),
            dataset.page_slot.astype(jnp.int32),
        )
        ds_operand = dataset.pool
    else:
        kern = functools.partial(
            _hop_kernel, metric=metric, deg=deg, itopk=itopk,
            width=width, d=d,
        )
        scalars = (
            parents.reshape(-1).astype(jnp.int32),
            cand.reshape(-1).astype(jnp.int32),
        )
        ds_operand = dataset
    hop = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((tile, 1, itopk), jnp.float32),
            jax.ShapeDtypeStruct((tile, 1, itopk), jnp.int32),
            jax.ShapeDtypeStruct((tile, 1, itopk), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )
    # the scope names the custom call: a device trace shows the hop's time
    # under ``%cagra_fused_hop.<n>`` (HOP_OP), wherever the loop calling it
    # sits
    with jax.named_scope(HOP_OP):
        od, oi, oe = hop(
            *scalars,
            graph.reshape(n, 1, deg),
            queries[:, None, :],
            buf_d[:, None, :],
            buf_i[:, None, :],
            explored[:, None, :].astype(jnp.int32),
            ds_operand,
        )
    return od[:, 0, :], oi[:, 0, :], oe[:, 0, :] != 0
