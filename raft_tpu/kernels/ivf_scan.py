"""Pallas probe-major IVF scan: per-list MXU scoring + VMEM-resident top-k.

The probe-major schedule (neighbors/_common.run_probe_major) streams each
probed list's rows from HBM once per query bucket.  Its XLA formulation
still materializes the per-step score tensor ([bb, G, cap]) and runs a
sort-based select over it in HBM.  This kernel fuses the two: for each
bucket the list's decoded rows are DMA'd into VMEM via a *dynamic block
index* (scalar-prefetched ``bucket_list`` drives the BlockSpec index_map —
the Pallas answer to data-dependent gathers, SURVEY §7 hard part 2), the
[G, cap] score tile is computed on the MXU, and the per-query top-k is
extracted in VMEM (toolkit.fold_topk) — scores never reach HBM.

Role parity: the reference's per-list ``compute_similarity`` scan kernel
(cpp/include/raft/neighbors/detail/ivf_pq_compute_similarity-inl.cuh) with
its shmem LUT + warp select; here the "LUT" is the decoded scan cache and
the warp queue is the VMEM fold.

Used by the ivf_pq AND ivf_flat scan paths when ``kernels.use_pallas()``
holds (same gate as every kernel).  Coverage
(round 4 widened to match the reference's compute_similarity surface):

- **Metrics**: L2 (sqeuclidean/euclidean), **inner product**, and
  **cosine** (ivf_flat's normalized leg, same rsqrt floors as its XLA
  schedule).
- **Storage**: f32/bf16 rows upcast in VMEM; ivf_pq's **int8 scan cache
  takes the fused quantized-query leg** (per-query symmetric
  quantization, int8×int8 MXU dot, scan_scale rescale — the memory-lean
  DEEP-100M mode).  Raw int8/uint8 ivf_flat datasets stay on the XLA
  schedule (no dequant scale).
- **Filters**: bitset sample filters ride as a *packed per-list word
  table* ([L, ceil(cap/32)] uint32, n/8 bytes total — built by
  ``pack_list_filter`` from the global bitset once per search call).
  Each bucket DMAs its list's words (a few dozen bytes) and expands them
  to a lane mask in VMEM — the global bitset itself never needs to fit
  VMEM, which is what kept this leg XLA-only in round 3.

The kernel is payload-agnostic: ivf_pq feeds decoded reconstructions +
their norms, ivf_flat feeds raw rows + row norms.  Validated in interpret
mode on CPU plus a TPU-gated compile test.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.kernels.toolkit import fold_topk, quantize_queries_i8
from raft_tpu.ops import cost as ops_cost
from raft_tpu.store.paged import PagedLists

_WORST = float("inf")


def pack_list_filter_table(list_index: jax.Array, table: jax.Array):
    """Pack a whole filter registry for the ragged descriptor leg:
    ``table`` [F, W_global] global-bitset rows → [F, L, ceil(cap/32)]
    per-list word tables (``pack_list_filter`` vmapped over the filter
    axis).  Each query's prefetched ``fid`` then selects its own [L, cap_w]
    plane inside the kernel, so a batch mixing F different predicates
    shares one executable."""
    return jax.vmap(lambda fw: pack_list_filter(list_index, fw))(table)


def pack_list_filter(list_index: jax.Array, filter_words: jax.Array):
    """Pack the bitset pass/fail of every (list, slot) into per-list
    uint32 words ([L, ceil(cap/32)]): bit j of word w covers slot
    32·w + j.  One XLA gather over the [L, cap] id table — n/8 bytes of
    output, so a DEEP-100M filter table is ~12 MB next to a ~10 GB scan
    cache.  Padding slots (id < 0) pack as fail."""
    L, cap = list_index.shape
    safe = jnp.clip(list_index, 0)
    word = filter_words[safe // 32]
    bit = (word >> (safe % 32).astype(jnp.uint32)) & 1
    ok = (bit == 1) & (list_index >= 0)                  # [L, cap] bool
    cap_w = -(-cap // 32)
    ok = jnp.pad(ok, ((0, 0), (0, cap_w * 32 - cap)))
    ok = ok.reshape(L, cap_w, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    return jnp.sum(ok << shifts, axis=2).astype(jnp.uint32)


def _score_against_list(dec, qg, q2, y2_row, ids_row, filt_row, scale,
                        *, metric: str, filtered: bool, scan_dtype: str):
    """Score a query block against one list's rows — the shared inner
    piece of both fused schedules. ``dec`` [cap, rot] (any storage dtype),
    ``qg`` [G, rot] f32, ``q2`` [G, 1] f32 (+inf marks padding queries),
    ``y2_row``/``ids_row`` [1, cap], ``filt_row`` [1, cap_w] uint32.
    Returns (scores [G, cap] with invalid slots at +inf, cand_i [G, cap])."""
    G = qg.shape[0]
    cap = dec.shape[0]
    if dec.dtype == jnp.int8:
        q_i8, sq = quantize_queries_i8(qg)               # [G, rot], [G, 1]
        ip_i32 = jax.lax.dot_general(
            q_i8, dec,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )                                                # [G, cap]
        ip = ip_i32.astype(jnp.float32) * (sq * scale)
    else:
        # MXU: [G, rot] × [cap, rot]ᵀ; stored rows upcast in VMEM only.
        # scan_dtype mirrors the caller's XLA schedule so the two legs
        # rank ties the same way: "highest"/"float32" = f32 compute,
        # "bfloat16" = the ivf_pq lut_dtype ladder's bf16 compute
        sd = jnp.bfloat16 if scan_dtype == "bfloat16" else jnp.float32
        # precision parity with the XLA legs, measured on-chip (round 4):
        # Mosaic's DEFAULT f32 dot is a single bf16 pass, while XLA's f32
        # DEFAULT keeps ~f32 fidelity — near-equal candidates then rank
        # differently between the legs (id agreement 0.955 on clustered
        # bf16-storage data).  "float32" pins HIGHEST to match XLA's
        # effective precision; "bfloat16" casts both operands to bf16
        # first, so DEFAULT is already bit-matched to the XLA bf16 dot.
        ip = jax.lax.dot_general(
            qg.astype(sd), dec.astype(sd),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(
                jax.lax.Precision.DEFAULT if scan_dtype == "bfloat16"
                else jax.lax.Precision.HIGHEST
            ),
        )                                                # [G, cap]
    if metric == "inner_product":
        scores = -ip
    elif metric == "cosine":
        # same guards as the XLA leg (ivf_flat score_fn): rsqrt with the
        # floors keeps padding (+inf q2 → rsqrt→0) and zero rows finite
        qn_inv = jax.lax.rsqrt(jnp.maximum(q2, 1e-24))   # [G, 1]
        vn_inv = jax.lax.rsqrt(jnp.maximum(y2_row, 1e-24))  # [1, cap]
        scores = 1.0 - ip * qn_inv * vn_inv
    else:
        scores = y2_row - 2.0 * ip + q2                  # [G, cap]
    invalid = (ids_row < 0) | jnp.isinf(q2)              # [G, cap]
    if filtered:
        cap_w = filt_row.shape[1]
        # lane-oriented expansion: repeat each word across its 32 lanes
        # (broadcast + minormost reshape — the only reshape shape Mosaic
        # lowers cheaply), then shift by lane position % 32
        rep = jnp.broadcast_to(
            filt_row[:, :, None], (1, cap_w, 32)
        ).reshape(1, cap_w * 32)
        shifts = (
            jax.lax.broadcasted_iota(jnp.uint32, (1, cap_w * 32), 1)
            % jnp.uint32(32)
        )
        passing = ((rep >> shifts) & 1)[:, :cap] == 1    # [1, cap]
        invalid = invalid | ~passing
    scores = jnp.where(invalid, _WORST, scores)
    cand_i = jnp.broadcast_to(ids_row, (G, cap))
    return scores, cand_i


def _scan_kernel(bucket_list_ref, dec_ref, y2_ref, ids_ref, filt_ref, qg_ref,
                 q2_ref, scale_ref, vals_ref, out_ids_ref, *, kk: int,
                 metric: str, filtered: bool, scan_dtype: str):
    """One bucket: score its list's rows against its G queries, keep the
    per-query top-kk.  dec/y2/ids/filt blocks were selected by the
    prefetched bucket_list (dynamic index_map); qg/q2 are the bucket's
    pre-gathered rotated queries (+inf q2 marks padding slots).  An int8
    dec block takes the quantized-query path: per-query symmetric
    quantization in VMEM, int8×int8 MXU dot with int32 accumulation,
    rescale by the per-query scale × the cache's frozen scan_scale
    (scale_ref, SMEM) — the memory-lean DEEP-100M mode's scoring, fused.
    ``metric`` picks the score: L2 (y² − 2ip + q²) or inner product
    (−ip); ``filtered`` expands the list's packed filter words to a lane
    mask and demotes failing slots."""
    G = qg_ref.shape[1]
    # Mosaic lowering: every vector op stays 2-D — q2 rides as a [G, 1]
    # column block and y2/ids as [1, cap] rows, so the masks build from
    # plain 2-D broadcasts (1-D reshapes/transposes crash tpu_compile)
    scores, cand_i = _score_against_list(
        dec_ref[0], qg_ref[0], q2_ref[0], y2_ref[0], ids_ref[0],
        filt_ref[0], scale_ref[0, 0],
        metric=metric, filtered=filtered, scan_dtype=scan_dtype,
    )
    run_v = jnp.full((G, kk), _WORST, jnp.float32)
    run_i = jnp.full((G, kk), -1, jnp.int32)
    v, i = fold_topk(run_v, run_i, scores, cand_i, kk)
    i = jnp.where(jnp.isfinite(v), i, -1)
    vals_ref[0] = v
    out_ids_ref[0] = i


def _scan_paged_kernel(bucket_list_ref, page_slot_ref, dec_ref, y2_ref,
                       ids_ref, qg_ref, q2_ref, scale_ref, vals_ref,
                       out_ids_ref, run_v_ref, run_i_ref, *, kk: int,
                       ppl: int, pr: int, metric: str, scan_dtype: str):
    """Paged probe-major step: grid (B, ppl) walks the bucket's list one
    *page* at a time.  The dec block rides the page-table indirection —
    TWO prefetched scalars compose in its index_map
    (``page_slot[bucket_list[b] * ppl + j]``), so the hot pool's slot
    order is invisible to the kernel body.  y2/ids stay monolithic
    [1, cap] blocks (device-resident sidecars) sliced per page in VMEM;
    the per-query top-kk accumulates across pages in scratch and is
    written once on the last page (the qm kernel's accumulate-then-fold
    shape, folded incrementally so no [G, cap] pool materializes)."""
    G = qg_ref.shape[1]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        run_v_ref[...] = jnp.full((G, kk), _WORST, jnp.float32)
        run_i_ref[...] = jnp.full((G, kk), -1, jnp.int32)

    y2_row = jax.lax.dynamic_slice_in_dim(y2_ref[0], j * pr, pr, axis=1)
    ids_row = jax.lax.dynamic_slice_in_dim(ids_ref[0], j * pr, pr, axis=1)
    scores, cand_i = _score_against_list(
        dec_ref[0], qg_ref[0], q2_ref[0], y2_row, ids_row,
        jnp.zeros((1, 1), jnp.uint32), scale_ref[0, 0],
        metric=metric, filtered=False, scan_dtype=scan_dtype,
    )
    v, i = fold_topk(run_v_ref[...], run_i_ref[...], scores, cand_i, kk)
    run_v_ref[...] = v
    run_i_ref[...] = i

    @pl.when(j == ppl - 1)
    def _emit():
        vf = run_v_ref[...]
        vals_ref[0] = vf
        out_ids_ref[0] = jnp.where(jnp.isfinite(vf), run_i_ref[...], -1)


def paged_scan_supported(list_data, kk: int, filtered: bool) -> bool:
    """Routing gate for the paged probe-major leg: the per-page fold
    caps the candidate pool at ``page_rows`` per step (so ``kk`` may not
    exceed it) and filtered searches keep the XLA schedule (the packed
    word table is capacity-indexed, not page-indexed)."""
    if not isinstance(list_data, PagedLists):
        return False
    pr = list_data.page_rows
    return (not filtered) and kk <= pr and pr % 8 == 0


def _ivf_scan_probe_major_paged(
    bucket_list, q_gathered, q2_gathered, list_data: PagedLists, list_y2,
    list_index, kk, *, metric, scan_dtype, scan_scale, interpret,
):
    """Paged body of :func:`ivf_scan_probe_major` (same contract)."""
    B, G, rot = q_gathered.shape
    L, cap = list_data.shape[:2]
    ppl = list_data.pages_per_list
    pr = list_data.page_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, ppl),
        in_specs=[
            pl.BlockSpec(       # dec: page j of the bucket's list, via
                (1, pr, rot),   # the device page table (slot −1 of a
                                # non-probed padding list clamps to 0;
                                # its scores die on the q2=+inf mask)
                lambda b, j, bl, ps: (
                    jnp.maximum(ps[bl[b] * ppl + j], 0), 0, 0
                ),
            ),
            pl.BlockSpec((1, 1, cap), lambda b, j, bl, ps: (bl[b], 0, 0)),
            pl.BlockSpec((1, 1, cap), lambda b, j, bl, ps: (bl[b], 0, 0)),
            pl.BlockSpec((1, G, rot), lambda b, j, bl, ps: (b, 0, 0)),
            pl.BlockSpec((1, G, 1), lambda b, j, bl, ps: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),       # scan_scale
        ],
        out_specs=[
            pl.BlockSpec((1, G, kk), lambda b, j, bl, ps: (b, 0, 0)),
            pl.BlockSpec((1, G, kk), lambda b, j, bl, ps: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, kk), jnp.float32),   # running top-kk values
            pltpu.VMEM((G, kk), jnp.int32),     # running top-kk ids
        ],
    )
    c = ops_cost.ivf_scan_cost(
        B, G, cap, rot, kk, itemsize=list_data.dtype.itemsize
    )
    ops_cost.note("ivf_scan_probe_major_paged", c)
    vals, ids = pl.pallas_call(
        functools.partial(
            _scan_paged_kernel, kk=kk, ppl=ppl, pr=pr, metric=metric,
            scan_dtype=scan_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, G, kk), jnp.float32),
            jax.ShapeDtypeStruct((B, G, kk), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )(
        bucket_list,
        list_data.page_slot,
        list_data.pool,
        list_y2[:, None, :],
        list_index[:, None, :],
        q_gathered,
        q2_gathered[:, :, None],
        jnp.asarray(scan_scale, jnp.float32).reshape(1, 1),
    )
    return vals, ids


@functools.partial(
    jax.jit, static_argnames=("kk", "metric", "scan_dtype", "interpret")
)
def ivf_scan_probe_major(
    bucket_list: jax.Array,   # [B] int32 — list id per bucket
    q_gathered: jax.Array,    # [B, G, rot] f32 — bucket queries (rotated)
    q2_gathered: jax.Array,   # [B, G] f32 — ‖q_rot‖² (+inf at padding)
    list_data: jax.Array,     # [L, cap, rot] f32/bf16/int8 stored rows
    list_y2: jax.Array,       # [L, cap] f32
    list_index: jax.Array,    # [L, cap] int32
    kk: int,
    *,
    metric: str = "sqeuclidean",
    scan_dtype: str = "highest",  # highest | float32 | bfloat16 (float leg)
    list_filter: jax.Array | None = None,  # [L, ceil(cap/32)] uint32
    scan_scale: float = 1.0,  # int8 cache dequant scale (1.0 for floats)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns per-bucket (vals [B, G, kk], ids [B, G, kk]) score partials
    (L2 or −ip per ``metric``) — feed them to
    _common.merge_probe_major_partials.  The caller supplies the
    pre-gathered bucket queries (one [B, G, rot] HBM pass — tiny next to
    the list stream this schedule saves) and, for filtered searches, the
    ``pack_list_filter`` word table.

    A :class:`~raft_tpu.store.paged.PagedLists` ``list_data`` takes the
    paged leg (grid (B, pages_per_list), dec indirected through the
    device page table; gate with :func:`paged_scan_supported`)."""
    if isinstance(list_data, PagedLists):
        assert list_filter is None, "paged pallas leg is unfiltered-only"
        return _ivf_scan_probe_major_paged(
            bucket_list, q_gathered, q2_gathered, list_data, list_y2,
            list_index, kk, metric=metric, scan_dtype=scan_dtype,
            scan_scale=scan_scale, interpret=interpret,
        )
    B, G, rot = q_gathered.shape
    L, cap, _ = list_data.shape
    filtered = list_filter is not None
    if not filtered:
        # single-word dummy rides the same BlockSpec; the kernel skips it
        list_filter = jnp.zeros((L, 1), jnp.uint32)
    cap_w = list_filter.shape[1]

    # 2-D operands indexed by the dynamic list id carry a singleton middle
    # axis: Mosaic requires each block's last two dims to be (8, 128)-
    # divisible OR equal to the array dims, and a (1, cap) block over an
    # [L, cap] array satisfies neither when L is dynamic-selected.  As
    # [L, 1, cap] the block (1, 1, cap) matches the trailing (1, cap)
    # exactly (first real Mosaic compile, round 4).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(       # dec: the bucket's list rows (dynamic)
                (1, cap, rot), lambda b, bl: (bl[b], 0, 0)
            ),
            pl.BlockSpec((1, 1, cap), lambda b, bl: (bl[b], 0, 0)),   # y2
            pl.BlockSpec((1, 1, cap), lambda b, bl: (bl[b], 0, 0)),   # ids
            pl.BlockSpec((1, 1, cap_w), lambda b, bl: (bl[b], 0, 0)),  # filt
            pl.BlockSpec((1, G, rot), lambda b, bl: (b, 0, 0)),  # queries
            pl.BlockSpec((1, G, 1), lambda b, bl: (b, 0, 0)),    # q2 column
            pl.BlockSpec(memory_space=pltpu.SMEM),               # scan_scale
        ],
        out_specs=[
            pl.BlockSpec((1, G, kk), lambda b, bl: (b, 0, 0)),
            pl.BlockSpec((1, G, kk), lambda b, bl: (b, 0, 0)),
        ],
    )
    c = ops_cost.ivf_scan_cost(
        B, G, cap, rot, kk, itemsize=list_data.dtype.itemsize
    )
    ops_cost.note("ivf_scan_probe_major", c)
    vals, ids = pl.pallas_call(
        functools.partial(
            _scan_kernel, kk=kk, metric=metric, filtered=filtered,
            scan_dtype=scan_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, G, kk), jnp.float32),
            jax.ShapeDtypeStruct((B, G, kk), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )(
        bucket_list,
        list_data,
        list_y2[:, None, :],
        list_index[:, None, :],
        list_filter[:, None, :],
        q_gathered,
        q2_gathered[:, :, None],
        jnp.asarray(scan_scale, jnp.float32).reshape(1, 1),
    )
    return vals, ids


def _scan_qm_kernel(probes_ref, dec_ref, y2_ref, ids_ref, filt_ref, q_ref,
                    q2_ref, scale_ref, vals_ref, out_ids_ref, s_v, s_i, *,
                    kk: int, metric: str, filtered: bool, scan_dtype: str,
                    P: int, G: int, cap: int, cap_pad: int):
    """One (query-block, probe, member) step of the fused query-major
    scan: score member ``i``'s probe-``p`` list into the block's VMEM
    score scratch; after the block's last (p, i) step, ONE fold over the
    whole [G, P*cap] pool extracts every member's top-kk.  The G-wide
    fold is the point: a per-query fold would waste 7 of 8 sublanes and
    dominate the kernel (round-4 chip measurement); batching G
    queries' pools through fold_topk amortizes it G-fold."""
    p = pl.program_id(1)
    i = pl.program_id(2)
    scores, cand_i = _score_against_list(
        dec_ref[0], q_ref[0], q2_ref[0], y2_ref[0], ids_ref[0],
        filt_ref[0], scale_ref[0, 0],
        metric=metric, filtered=filtered, scan_dtype=scan_dtype,
    )                                                    # [1, cap] each
    # scratch rows are lane-padded to cap_pad: merging (G, P, cap) to
    # (G, P*cap) is a Mosaic "unsupported shape cast" whenever cap isn't
    # a lane multiple (real indexes: cap=632), so pad slots carry
    # _WORST/-1 and the aligned pool reshapes for ONE G-wide fold
    if cap_pad > cap:
        scores = jnp.concatenate(
            [scores, jnp.full((1, cap_pad - cap), _WORST, scores.dtype)], 1
        )
        cand_i = jnp.concatenate(
            [cand_i, jnp.full((1, cap_pad - cap), -1, cand_i.dtype)], 1
        )
    s_v[i, p, :] = scores[0]
    s_i[i, p, :] = cand_i[0]

    @pl.when((p == P - 1) & (i == G - 1))
    def _fold():
        pool_v = s_v[...].reshape(G, P * cap_pad)
        pool_i = s_i[...].reshape(G, P * cap_pad)
        run_v = jnp.full((G, kk), _WORST, jnp.float32)
        run_i = jnp.full((G, kk), -1, jnp.int32)
        v, o = fold_topk(run_v, run_i, pool_v, pool_i, kk)
        o = jnp.where(jnp.isfinite(v), o, -1)
        vals_ref[0] = v
        out_ids_ref[0] = o


def _scan_qm_kernel_fid(probes_ref, fid_ref, *rest, **kw):
    """Descriptor-leg adapter: with two prefetched scalars (probes, fid)
    the kernel receives an extra leading ref, but fid only drives the filt
    BlockSpec index map — the body is byte-identical to the single-filter
    schedule (the block already arrived selected)."""
    _scan_qm_kernel(probes_ref, *rest, **kw)


#: query-block width of the fused query-major scan — one full sublane set
_QM_GROUP = 8


#: per-block VMEM scratch ceiling for the query-major kernel — the ONE
#: owner both index dispatches gate on; past it the XLA legs tile better.
#: Tune from the on-chip ivf_scan_ab sweep.
QM_VMEM_BUDGET = 6 * 1024 * 1024


def _cap_pad(cap: int) -> int:
    """Lane-padded scratch row width — the ONE owner of the padding rule
    (scratch rows pad to a 128 multiple so the fold's pool reshape is a
    supported Mosaic relayout; see _scan_qm_kernel)."""
    return -(-cap // 128) * 128


def qm_scratch_bytes(n_probes: int, cap: int) -> int:
    """VMEM score+id scratch the query-major kernel allocates per block —
    the dispatch gates on this (one owner for the formula and _QM_GROUP).
    cap counts lane-padded (scratch rows are padded to a 128 multiple)."""
    return 2 * _QM_GROUP * n_probes * _cap_pad(cap) * 4


def qm_query_tile(n_probes: int) -> int:
    """Host-level query tile for the fused query-major dispatch: bounds
    the scalar-prefetch operand (q_tile·n_probes int32 must stay
    SMEM-small), rounded to the kernel group width."""
    return max(_QM_GROUP, min(4096, (32_768 // max(1, n_probes)) // 8 * 8))


@functools.partial(
    jax.jit, static_argnames=("kk", "metric", "scan_dtype", "interpret")
)
def ivf_scan_query_major(
    probes: jax.Array,        # [Q, P] int32 — per-query probed list ids
    q_rot: jax.Array,         # [Q, rot] f32 — rotated queries
    q2: jax.Array,            # [Q] f32 — ‖q_rot‖² (+inf marks padding)
    list_data: jax.Array,     # [L, cap, rot] f32/bf16/int8 stored rows
    list_y2: jax.Array,       # [L, cap] f32
    list_index: jax.Array,    # [L, cap] int32
    kk: int,
    *,
    metric: str = "sqeuclidean",
    scan_dtype: str = "highest",
    list_filter: jax.Array | None = None,  # [L, ceil(cap/32)] uint32
    query_fid: jax.Array | None = None,    # [Q] int32 — ragged filter ids
    scan_scale: float = 1.0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused query-major IVF scan: each query's probed lists stream
    straight from the index into VMEM (the XLA schedule's materialized
    [t, p, cap, rot] gather copy and [t, p, cap] score tensor never
    exist), scores accumulate in a per-block VMEM scratch, and one
    G-wide fold per query block extracts the top-kk.  Returns
    (vals [Q, kk], ids [Q, kk]) raw score partials — same conventions as
    the XLA query-major leg pre-postprocess.  Q must be a multiple of
    the group width (pad with q2=+inf rows; their outputs are -1/inf).

    Ragged descriptor leg: with ``query_fid`` (and ``list_filter`` a
    ``pack_list_filter_table`` [F, L, cap_w] table) each query's filter id
    rides as a second prefetched scalar that only the filt BlockSpec index
    map consumes — query i of step (qb, p) DMAs word block
    ``fid[qb·G+i]·L + probes[...]`` of the flattened [F·L, 1, cap_w]
    table.  The kernel body is unchanged, so heterogeneous-filter batches
    keep the fused path with one executable.

    VMEM budget: the scratch holds 2·G·P·cap_pad·4 bytes (cap lane-padded
    to a 128 multiple; ``qm_scratch_bytes`` is the owner) — callers gate
    on this (see ivf_pq's dispatch) and fall back to XLA past it."""
    Q, P = probes.shape
    L, cap, rot = list_data.shape
    G = _QM_GROUP
    if Q % G:
        raise ValueError(f"Q={Q} must be a multiple of {G} (pad upstream)")
    if query_fid is not None:
        if list_filter is None or list_filter.ndim != 3:
            raise ValueError(
                "query_fid requires a pack_list_filter_table [F, L, cap_w] "
                "list_filter"
            )
        F, _, cap_w = list_filter.shape
        cap_pad = _cap_pad(cap)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Q // G, P, G),
            in_specs=[
                pl.BlockSpec(       # dec: member i's probe-p list (dynamic)
                    (1, cap, rot),
                    lambda qb, p, i, pr, fid: (pr[(qb * G + i) * P + p], 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, cap),
                    lambda qb, p, i, pr, fid: (pr[(qb * G + i) * P + p], 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, cap),
                    lambda qb, p, i, pr, fid: (pr[(qb * G + i) * P + p], 0, 0),
                ),
                pl.BlockSpec(       # filt: the member's OWN filter plane
                    (1, 1, cap_w),
                    lambda qb, p, i, pr, fid: (
                        fid[qb * G + i] * L + pr[(qb * G + i) * P + p],
                        0,
                        0,
                    ),
                ),
                pl.BlockSpec(       # member i's query row
                    (1, 1, rot), lambda qb, p, i, pr, fid: (qb * G + i, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1), lambda qb, p, i, pr, fid: (qb * G + i, 0, 0)
                ),
                pl.BlockSpec(memory_space=pltpu.SMEM),   # scan_scale
            ],
            out_specs=[
                pl.BlockSpec((1, G, kk), lambda qb, p, i, pr, fid: (qb, 0, 0)),
                pl.BlockSpec((1, G, kk), lambda qb, p, i, pr, fid: (qb, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((G, P, cap_pad), jnp.float32),
                pltpu.VMEM((G, P, cap_pad), jnp.int32),
            ],
        )
        c = ops_cost.ivf_scan_cost(
            Q * P, 1, cap, rot, kk, itemsize=list_data.dtype.itemsize
        )
        ops_cost.note("ivf_scan_query_major", c)
        vals, ids = pl.pallas_call(
            functools.partial(
                _scan_qm_kernel_fid, kk=kk, metric=metric, filtered=True,
                scan_dtype=scan_dtype, P=P, G=G, cap=cap, cap_pad=cap_pad,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((Q // G, G, kk), jnp.float32),
                jax.ShapeDtypeStruct((Q // G, G, kk), jnp.int32),
            ],
            cost_estimate=c.as_pallas(),
            interpret=interpret,
        )(
            probes.reshape(-1),
            jnp.asarray(query_fid, jnp.int32).reshape(-1),
            list_data,
            list_y2[:, None, :],
            list_index[:, None, :],
            list_filter.reshape(F * L, 1, cap_w),
            q_rot[:, None, :],
            q2[:, None, None],
            jnp.asarray(scan_scale, jnp.float32).reshape(1, 1),
        )
        return vals.reshape(Q, kk), ids.reshape(Q, kk)
    filtered = list_filter is not None
    if not filtered:
        list_filter = jnp.zeros((L, 1), jnp.uint32)
    cap_w = list_filter.shape[1]
    cap_pad = _cap_pad(cap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q // G, P, G),
        in_specs=[
            pl.BlockSpec(       # dec: member i's probe-p list (dynamic)
                (1, cap, rot),
                lambda qb, p, i, pr: (pr[(qb * G + i) * P + p], 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, cap),
                lambda qb, p, i, pr: (pr[(qb * G + i) * P + p], 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, cap),
                lambda qb, p, i, pr: (pr[(qb * G + i) * P + p], 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, cap_w),
                lambda qb, p, i, pr: (pr[(qb * G + i) * P + p], 0, 0),
            ),
            pl.BlockSpec(       # member i's query row
                (1, 1, rot), lambda qb, p, i, pr: (qb * G + i, 0, 0)
            ),
            pl.BlockSpec((1, 1, 1), lambda qb, p, i, pr: (qb * G + i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),       # scan_scale
        ],
        out_specs=[
            pl.BlockSpec((1, G, kk), lambda qb, p, i, pr: (qb, 0, 0)),
            pl.BlockSpec((1, G, kk), lambda qb, p, i, pr: (qb, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, P, cap_pad), jnp.float32),
            pltpu.VMEM((G, P, cap_pad), jnp.int32),
        ],
    )
    c = ops_cost.ivf_scan_cost(
        Q * P, 1, cap, rot, kk, itemsize=list_data.dtype.itemsize
    )
    ops_cost.note("ivf_scan_query_major", c)
    vals, ids = pl.pallas_call(
        functools.partial(
            _scan_qm_kernel, kk=kk, metric=metric, filtered=filtered,
            scan_dtype=scan_dtype, P=P, G=G, cap=cap, cap_pad=cap_pad,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q // G, G, kk), jnp.float32),
            jax.ShapeDtypeStruct((Q // G, G, kk), jnp.int32),
        ],
        cost_estimate=c.as_pallas(),
        interpret=interpret,
    )(
        probes.reshape(-1),
        list_data,
        list_y2[:, None, :],
        list_index[:, None, :],
        list_filter[:, None, :],
        q_rot[:, None, :],
        q2[:, None, None],
        jnp.asarray(scan_scale, jnp.float32).reshape(1, 1),
    )
    return vals.reshape(Q, kk), ids.reshape(Q, kk)
