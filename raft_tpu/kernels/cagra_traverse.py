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

This kernel fuses one hop over a query-blocked schedule: each grid step
walks a block of ``qb`` queries of the tile (whole sublane tiles, up to
128, derived from the shapes by :func:`_query_block`; a tile that is not a
multiple of ``qb`` is padded with inert rows whose parent is −1):

- its only view of the graph is the candidate-id table, the parents'
  neighbor rows as XLA gathers them from the graph in whatever layout the
  chip stores it (the kernel takes no graph operand, so nothing forces a
  relayout of the whole graph inside the walk loop).  The table rides
  twice: as prefetched scalars that address the candidates' row DMAs,
  and as a VMEM block the dedup reads;
- every one of the block's ``width·qb·deg`` row copies is started before
  any is waited on, so a step pays about one HBM round trip rather than
  one per row; the [tile, width·deg, d] gather copy never exists;
- MXU scoring, eight queries at a time ([8, d] × [8·deg, d]ᵀ, f32
  accumulate at HIGHEST precision, matching the XLA hop's
  ``_query_distance`` einsum); each query's own ``deg`` columns are taken
  out of the group's product by a masked roll-and-add tree, which adds
  exact zeros;
- visited-dedup by membership against each row's live buffer plus a
  strict-upper within-parent mask (the reference's visited-hashmap role,
  detail/cagra/hashmap.hpp);
- itopk buffer maintenance via ``toolkit.fold_topk`` on [qb, itopk + deg]
  pools, one fold per parent in parent order, with the same resident-wins
  tie discipline as the XLA merge (buffer entries occupy the pool's first
  positions).  Explored flags ride through the fold inside the ids (an
  explored id ``i`` is held as ``−i−2``), so no membership pass has to
  recover them.

Every row's arithmetic is its own: a row's result does not depend on
``qb`` or on which rows share its block.

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


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


#: queries a score group takes: one sublane tile
_GROUP = 8
#: most queries a grid step walks
_MAX_BLOCK = 128
#: VMEM a block's candidate rows, and its dedup compares, may take
_VMEM_BUDGET = 4 << 20


def _query_block(tile: int, width: int, deg_p: int, d: int, itopk: int,
                 itemsize: int) -> int:
    """Queries per grid step: the tile rounded up to whole sublane tiles,
    at most ``_MAX_BLOCK``, and no more than the block's candidate rows
    (``width·deg_p·d`` elements a query) and dedup compares
    (``deg_p·itopk``) fit the VMEM budget.  A step's fold runs its
    ``itopk`` rounds in about the same time for 8 rows as for 128, so the
    block is as tall as that allows."""
    per_query = max(width * deg_p * d * itemsize, deg_p * itopk * 4)
    fit = _VMEM_BUDGET // per_query // _GROUP * _GROUP
    return max(_GROUP, min(_round_up(tile, _GROUP), _MAX_BLOCK, fit))


def _own_columns(x, deg_p: int):
    """[8, 8·deg_p] → [8, deg_p]: row b keeps columns b·deg_p … of its
    group's product (its own query's candidates).  The other columns are
    zeroed and the 8 column groups summed by a roll-and-add tree, so each
    kept value only has exact zeros added to it."""
    nw = _GROUP * deg_p
    row = lax.broadcasted_iota(jnp.int32, (_GROUP, nw), 0)
    col = lax.broadcasted_iota(jnp.int32, (_GROUP, nw), 1)
    x = jnp.where((col >= row * deg_p) & (col < row * deg_p + deg_p), x, 0.0)
    s = nw // 2
    while s >= deg_p:
        x = x + pltpu.roll(x, s, 1)
        s //= 2
    return x[:, :deg_p]


def _score(q, rows, metric: str, deg_p: int):
    """Distances of a group's 8 queries ``q`` [8, d] to their own
    candidates among ``rows`` [8·deg_p, d] (query-major) → [8, deg_p]."""
    ip = lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )                                                        # [8, 8·deg_p]
    if metric == "inner_product":
        cd = -ip
    else:
        # v² via an MXU ones-contraction keeps every vector op 2-D; at
        # HIGHEST like ip: a DEFAULT-precision dot rounds the squares to
        # bf16 (one pass), which put ~4e-4 of the pair's scale into every
        # distance on the chip and scrambled the ranking of near
        # neighbours
        v2 = lax.dot_general(
            jnp.full((1, q.shape[1]), 1.0, jnp.float32), rows * rows,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )                                                    # [1, 8·deg_p]
        q2 = jnp.sum(q * q, axis=1, keepdims=True)           # [8, 1]
        cd = jnp.maximum(q2 + v2 - 2.0 * ip, 0.0)
    return _own_columns(cd, deg_p)


def _decode(code):
    """Buffer ids from the fold's codes (explored id i is held as −i−2)."""
    return jnp.where(code <= -2, -code - 2, code)


def _hop_kernel(cand_ref, c_blk, q_blk, bd_blk, bi_blk, be_blk, dataset_ref,
                od_blk, oi_blk, oe_blk, rows_s, sem, *, metric: str, qb: int,
                deg_p: int, itopk: int, width: int, tile_p: int,
                page_rows=None, ps_ref=None):
    """One block of ``qb`` queries.  ``cand_ref`` is the flat prefetched
    candidate table ([width, tile_p, deg_p], −1 = no candidate); the rows
    scratch holds the block's candidate rows in (parent, query, slot)
    order.

    ``page_rows``/``ps_ref`` select the paged leg: ``dataset_ref`` is then
    the HBM page pool ``[slots, page_rows, d]`` and each candidate row DMA
    translates its global id through the prefetched ``page_slot`` table —
    the "one more prefetched indirection" the page table costs."""
    t0 = pl.program_id(0) * qb

    def row_copy(cid, r):
        if page_rows is None:
            src = dataset_ref.at[pl.ds(cid, 1), :]
        else:
            pg = cid // page_rows
            slot = jnp.maximum(ps_ref[pg], 0)
            src = dataset_ref.at[slot, pl.ds(cid - pg * page_rows, 1), :]
        return pltpu.make_async_copy(src, rows_s.at[pl.ds(r, 1), :], sem)

    def each_candidate(fn):
        # (parent, query, slot) order; ids < 0 start no copy (their
        # scores are masked to +inf below, whatever the scratch holds)
        for w in range(width):
            def per_query(b, _, w=w):
                base = (w * tile_p + t0 + b) * deg_p
                r0 = (w * qb + b) * deg_p

                def per_slot(c, _):
                    cid = cand_ref[base + c]

                    @pl.when(cid >= 0)
                    def _():
                        fn(row_copy(cid, r0 + c))

                    return 0

                lax.fori_loop(0, deg_p, per_slot, 0, unroll=True)
                return 0

            lax.fori_loop(0, qb, per_query, 0)

    # all of the block's copies in flight before the first wait
    each_candidate(lambda cp: cp.start())
    each_candidate(lambda cp: cp.wait())

    q = q_blk[...].astype(jnp.float32)                       # [qb, d]
    pi = lax.broadcasted_iota(jnp.int32, (qb, deg_p, deg_p), 1)
    pj = lax.broadcasted_iota(jnp.int32, (qb, deg_p, deg_p), 2)
    # the fold carries explored flags inside the ids
    md = bd_blk[...]
    code = jnp.where(be_blk[...] != 0, -bi_blk[...] - 2, bi_blk[...])
    gw = _GROUP * deg_p
    for w in range(width):
        # score in groups of 8 queries: the MXU product of a group is
        # [8, 8·deg_p], so the work grows with qb, not with qb²
        r0 = w * qb * deg_p
        cd = jnp.concatenate([
            _score(q[g * _GROUP:(g + 1) * _GROUP],
                   rows_s[r0 + g * gw:r0 + (g + 1) * gw, :].astype(
                       jnp.float32),
                   metric, deg_p)
            for g in range(qb // _GROUP)
        ], axis=0)                                           # [qb, deg_p]

        # ---- visited dedup: membership vs the live buffer (covers the
        # input buffer AND earlier parents' survivors) + a strict-upper
        # within-parent mask for duplicate neighbors in one list
        cand = c_blk[w]                                      # [qb, deg_p]
        live = _decode(code)
        in_buf = jnp.any(cand[:, :, None] == live[:, None, :], axis=2)
        dup = jnp.any(
            (cand[:, :, None] == cand[:, None, :]) & (pi < pj), axis=1
        )
        bad = (cand < 0) | in_buf | dup
        cd = jnp.where(bad, _INF, cd)
        cand = jnp.where(bad, -1, cand)

        # ---- fold into the buffer: residents ride the pool's first
        # positions, so fold_topk's first-position tie-break keeps the XLA
        # merge's resident-wins discipline
        md, code = fold_topk(md, code, cd, cand, itopk)
        # the +inf slots a short pool leaves behind must not carry ids (they
        # would shadow later finite copies) — same fixup as the XLA hop
        code = jnp.where(jnp.isfinite(md), code, -1)

    od_blk[...] = md
    oi_blk[...] = _decode(code)
    # new candidates are unexplored, +inf slots explored
    oe_blk[...] = ((code <= -2) | ~jnp.isfinite(md)).astype(jnp.int32)


def _hop_kernel_paged(cand_ref, ps_ref, c_blk, q_blk, bd_blk, bi_blk,
                      be_blk, pool_ref, od_blk, oi_blk, oe_blk, rows_s, sem,
                      **kw):
    """Paged entry point: same hop body, with the page-slot table riding
    as a second prefetched scalar ahead of the grid operands."""
    _hop_kernel(
        cand_ref, c_blk, q_blk, bd_blk, bi_blk, be_blk, pool_ref, od_blk,
        oi_blk, oe_blk, rows_s, sem, ps_ref=ps_ref, **kw,
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
    # a score group's product (8 · deg_p columns) fills whole 128-lane
    # vregs
    deg_p = _round_up(deg, 16)
    qb = _query_block(tile, width, deg_p, d, itopk,
                      dataset.dtype.itemsize)
    tile_p = _round_up(tile, qb)
    pad = tile_p - tile
    # candidate-id table, the kernel's one view of the graph: a
    # [tile, width, deg] int32 gather — 4 bytes/candidate next to the
    # d·itemsize/candidate row gather the kernel eliminates — padded with
    # −1 (no candidate) to [width, tile_p, deg_p]
    cand = graph[jnp.clip(parents, 0, n - 1)]
    cand = jnp.where(parents[:, :, None] < 0, -1, cand)
    cand = jnp.pad(
        cand, ((0, pad), (0, 0), (0, deg_p - deg)), constant_values=-1
    ).transpose(1, 0, 2)

    c = ops_cost.cagra_traverse_cost(
        tile, width, deg, d, itopk, itemsize=dataset.dtype.itemsize
    )
    ops_cost.note("cagra_traverse", c)

    # index_maps take *_ so the same lambdas serve 1 (dense) or 2 (paged:
    # + page_slot) prefetched scalar operands
    def _blk(i, *_):
        return i, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if paged else 1,
        grid=(tile_p // qb,),
        in_specs=[
            pl.BlockSpec((width, qb, deg_p), lambda i, *_: (0, i, 0)),
            pl.BlockSpec((qb, d), _blk),
            pl.BlockSpec((qb, itopk), _blk),
            pl.BlockSpec((qb, itopk), _blk),
            pl.BlockSpec((qb, itopk), _blk),
            pl.BlockSpec(memory_space=pl.ANY),   # dataset/pool in HBM
        ],
        out_specs=[pl.BlockSpec((qb, itopk), _blk)] * 3,
        scratch_shapes=[
            pltpu.VMEM((width * qb * deg_p, d), dataset.dtype),  # rows
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kw = dict(metric=metric, qb=qb, deg_p=deg_p, itopk=itopk, width=width,
              tile_p=tile_p)
    if paged:
        kern = functools.partial(
            _hop_kernel_paged, page_rows=dataset.page_rows, **kw
        )
        scalars = (cand.reshape(-1), dataset.page_slot.astype(jnp.int32))
        ds_operand = dataset.pool
    else:
        kern = functools.partial(_hop_kernel, **kw)
        scalars = (cand.reshape(-1),)
        ds_operand = dataset
    hop = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((tile_p, itopk), jnp.float32),
            jax.ShapeDtypeStruct((tile_p, itopk), jnp.int32),
            jax.ShapeDtypeStruct((tile_p, itopk), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )
    rows = ((0, pad), (0, 0))
    # the scope names the custom call: a device trace shows the hop's time
    # under ``%cagra_fused_hop.<n>`` (HOP_OP), wherever the loop calling it
    # sits
    with jax.named_scope(HOP_OP):
        od, oi, oe = hop(
            *scalars,
            cand,
            jnp.pad(queries, rows),
            jnp.pad(buf_d, rows, constant_values=_INF),
            jnp.pad(buf_i, rows, constant_values=-1),
            jnp.pad(explored.astype(jnp.int32), rows),
            ds_operand,
        )
    return od[:tile], oi[:tile], oe[:tile] != 0
