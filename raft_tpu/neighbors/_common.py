"""Shared helpers for the IVF index family.

Padded-list packing, coarse cluster selection, and bitset-filter masking are
identical between IVF-Flat and IVF-PQ (ref: the reference shares them via
``neighbors/ivf_list.hpp`` + ``detail/ivf_common.cuh``)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.distance.pairwise import _PREC
from raft_tpu.ops.matrix import select_k


def round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


#: lanes of a TPU vector register: the minor dim of a row-major tile
LANES = 128


def padded_width(d: int) -> int:
    """``d`` rounded up to whole 128-lane tiles."""
    return round_up(d, LANES)


def lane_pad(x, width: Optional[int] = None):
    """Zero-pad the last dim of ``x`` to ``width`` (default
    :func:`padded_width` of it); ``x`` itself when it is already that wide.

    A resident row array whose width is not a lane multiple is stored by
    the TPU runtime in a compact transposed tiling, while the scan kernels
    and row gathers read the row-major one — so XLA relayouts the whole
    array at the entry of every program that reads it.  Stored padded, the
    array's default layout is already row-major.  Zero lanes add exact
    zeros to every dot product against a query padded the same way."""
    d = x.shape[-1]
    pad = (padded_width(d) if width is None else width) - d
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def merge_split_lists(centers: np.ndarray, labels: np.ndarray):
    """Collapse split shards (bit-identical duplicated centroids) back to
    their parent list before a re-pack.

    Without this, repeated extend() calls inflate n_lists without bound:
    predict() ties on duplicated centroids send every new member to the
    first shard, which then re-splits each call. Returns
    (unique_idx [L_unique] — first occurrence of each distinct centroid in
    original order, new_labels mapped onto the unique set)."""
    centers = np.asarray(centers)
    _, first_idx, inverse = np.unique(
        centers, axis=0, return_index=True, return_inverse=True
    )
    # re-order the unique set by first occurrence so stable list ids persist
    order = np.argsort(first_idx)
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    unique_idx = first_idx[order]
    new_labels = rank[inverse[np.asarray(labels, np.int64)]]
    return unique_idx, new_labels.astype(np.int64)


def default_max_cap(n_rows: int, n_lists: int) -> int:
    """Per-list capacity bound: a slack factor over the mean occupancy
    (sublane-rounded).

    Padded storage costs ``slack × n_rows × row_bytes`` regardless of the
    list count, so the slack factor IS the memory multiplier.  2× leaves
    room for mild imbalance without splitting; at DEEP-100M scale that
    doubling breaks the one-chip budget (2 × 9.6 GB int8 > 16 GB HBM), and
    balanced-kmeans lists are even enough that 1.25× plus
    ``split_oversized_lists`` (which relabels overflow into shard lists —
    correctness never depends on the slack) is the right trade."""
    mean = max(1, -(-n_rows // max(1, n_lists)))
    slack_num, slack_den = (5, 4) if n_rows >= 50_000_000 else (2, 1)
    return max(32, round_up(slack_num * mean // slack_den, 8))


def split_oversized_lists(
    labels: np.ndarray, n_lists: int, max_cap: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Bound list skew: relabel members of lists larger than ``max_cap`` into
    split sublists appended after the original lists.

    Returns (new_labels, center_map [n_lists'] int64) where
    ``center_map[l]`` is the original list whose centroid list ``l`` shares.
    Split sublists duplicate their parent's centroid, so coarse selection
    scores them identically and probes every shard of a hot cluster at equal
    rank — scan cost stays proportional to real data instead of global-max
    padding (the TPU answer to the reference's variable-length interleaved
    lists, ivf_flat_build.cuh:88-154; see VERDICT r1 weak #4)."""
    labels = np.asarray(labels, np.int64).copy()
    sizes = np.bincount(labels, minlength=n_lists)
    center_map = list(range(n_lists))
    next_id = n_lists
    for l in np.nonzero(sizes > max_cap)[0]:
        members = np.nonzero(labels == l)[0]
        n_parts = -(-len(members) // max_cap)  # ceil
        for p in range(1, n_parts):
            part = members[p * max_cap : (p + 1) * max_cap]
            labels[part] = next_id
            center_map.append(int(l))
            next_id += 1
    return labels, np.asarray(center_map, np.int64)


def subsample_trainset(dataset, n_train: int, seed: int):
    """Host-side no-replacement row subsample → gathered rows (input dtype).

    The indices are drawn with numpy: a device-side no-replacement
    ``jax.random.choice`` lowers to a full-n sort whose one-off XLA compile
    costs ~20 s on the chip; only the O(n_train) gather runs on
    device. (ref: trainset subsampling, ivf_pq_build.cuh:1706-1766)"""
    import jax.numpy as _jnp

    n = dataset.shape[0]
    idx = np.random.default_rng(seed).choice(n, size=n_train, replace=False)
    if isinstance(dataset, np.ndarray):
        # host dataset (possibly a memmap): gather host-side, upload only
        # the trainset rows
        return _jnp.asarray(dataset[np.sort(idx)])
    return dataset[_jnp.asarray(np.sort(idx))]


def compute_list_layout(
    labels: np.ndarray,
    n_lists: int,
    max_cap: Optional[int] = None,
    headroom: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Per-row (list, slot) placement for the padded list layout — metadata
    only, no payload touched (so callers can stream the payload scatter
    device-side in bounded chunks instead of materializing padded host
    arrays; the 100M-scale path).

    Returns (lst [n], slot [n], sizes [n_lists'], center_map [n_lists'],
    cap). cap is the max list size rounded up to the sublane multiple (8) —
    plus ~12.5% growth headroom when ``headroom`` is set, so even the
    fullest list keeps spare slots and in-place extends
    (allocate_append_slots) don't immediately fall back to a repack. With
    ``max_cap`` set, oversized lists are split (split_oversized_lists) so
    cap ≤ round_up(max_cap, 8) regardless of cluster skew; center_map tells
    the caller how to expand its centroid rows."""
    from raft_tpu.core import native

    def with_headroom(base: int) -> int:
        cap = base + max(8, base // 8) if headroom else base
        cap = max(8, round_up(cap, 8))
        if max_cap is not None:
            cap = min(cap, round_up(max_cap, 8))
        return max(cap, round_up(max(base, 1), 8))  # never below actual max

    labels = np.asarray(labels, np.int64)
    n = labels.shape[0]
    if max_cap is not None and n and native.available():
        # native layout pass (threads/split logic in C++)
        slot, lst, center_map, cap = native.pack_list_layout(
            labels, n_lists, max_cap
        )
        cap = with_headroom(cap)
        sizes = np.bincount(lst, minlength=len(center_map)).astype(np.int32)
        return lst, slot, sizes, center_map, cap

    if max_cap is not None:
        labels, center_map = split_oversized_lists(labels, n_lists, max_cap)
        n_lists = len(center_map)
    else:
        center_map = np.arange(n_lists, dtype=np.int64)
    sizes = np.bincount(labels, minlength=n_lists)
    cap = with_headroom(int(sizes.max()) if n else 8)
    order = np.argsort(labels, kind="stable")
    starts = np.zeros(n_lists + 1, np.int64)
    np.cumsum(sizes, out=starts[1:])
    slot = np.empty(n, np.int64)
    slot[order] = np.arange(n) - starts[labels[order]]
    return labels, slot, sizes.astype(np.int32), center_map, cap


def pack_padded_lists(
    payload: np.ndarray,
    ids: np.ndarray,
    labels: np.ndarray,
    n_lists: int,
    max_cap: Optional[int] = None,
    headroom: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Scatter rows into the padded [n_lists', cap, ...] layout (host-side;
    the analog of the reference's per-list code/vector packing,
    ivf_flat_build.cuh:88-154). Returns (list_payload, list_index, sizes,
    center_map). Layout policy (headroom / skew splitting) lives in
    compute_list_layout; the payload scatter here is numpy fancy indexing —
    use compute_list_layout directly + device scatters for datasets too big
    to duplicate host-side."""
    lst, slot, sizes, center_map, cap = compute_list_layout(
        labels, n_lists, max_cap=max_cap, headroom=headroom
    )
    n_lists = len(center_map)
    list_payload = np.zeros((n_lists, cap) + payload.shape[1:], payload.dtype)
    list_index = np.full((n_lists, cap), -1, np.int32)
    list_payload[lst, slot] = payload
    list_index[lst, slot] = ids
    return list_payload, list_index, sizes, center_map


def unpack_lists(
    list_payload: np.ndarray, list_index: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Inverse of pack_padded_lists → (payload, ids, labels) host arrays."""
    valid = list_index >= 0
    payload = list_payload[valid]
    ids = list_index[valid]
    labels = np.repeat(np.arange(list_index.shape[0]), valid.sum(1)).astype(np.int32)
    return payload, ids, labels


def coarse_select(
    queries: jax.Array, centers: jax.Array, metric: str, n_probes: int
) -> jax.Array:
    """Top-n_probes cluster ids per query: one MXU GEMM + select_k
    (ref: ivf_pq_search.cuh select_clusters:67, ivf_flat_search-inl.cuh:40)."""
    if metric == "cosine":
        qn = queries / jnp.maximum(jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-12)
        cn = centers / jnp.maximum(jnp.linalg.norm(centers, axis=1, keepdims=True), 1e-12)
        coarse = -jnp.matmul(qn, cn.T, precision=_PREC)
    elif metric == "inner_product":
        coarse = -jnp.matmul(queries, centers.T, precision=_PREC)
    else:
        cnorm = jnp.sum(centers * centers, axis=1)
        coarse = cnorm[None, :] - 2.0 * jnp.matmul(queries, centers.T, precision=_PREC)
    _, probes = select_k(coarse, n_probes, select_min=True)
    return probes


def sorted_id_dedup(ids: jax.Array):
    """Shared sorted-id dedup idiom: stable-sort each row by id and flag every
    repeat after the first occurrence (the TPU replacement for visited
    hash-sets / bloom filters — one sort + one adjacent compare).

    Returns (order [n, m] int32 — the stable argsort, dup [n, m] bool in
    *sorted* space). Callers gather their payloads through ``order`` and
    demote slots where ``dup`` (first occurrence in the original layout wins,
    because stable sort preserves it)."""
    order = jnp.argsort(ids, axis=-1, stable=True)
    s = jnp.take_along_axis(ids, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(s[..., :1], bool), s[..., 1:] == s[..., :-1]], axis=-1
    )
    return order, dup


def resolve_pass_filter(sample_filter, deleted_mask):
    """Fold an optional tombstone mask into the pass-filter convention.

    ``sample_filter`` keeps set bits (ref: sample_filter_types.hpp
    bitset_filter); ``deleted_mask`` EXCLUDES set bits (the serving layer's
    tombstone convention, raft_tpu.serve.mutation).  Returns a single
    pass-filter Bitset/RowFilter or None.  Both masks must cover the same
    id space when combined (a RowFilter may cover a superset — ragged
    batches filter in the global id space, which extends past the main
    index rows the tombstones cover; the extra words pass through).
    """
    from raft_tpu.core.bitset import Bitset, RowFilter

    if deleted_mask is None:
        return sample_filter
    if sample_filter is None:
        return Bitset(~deleted_mask.words, deleted_mask.n_bits)
    if isinstance(sample_filter, RowFilter):
        if sample_filter.n_bits < deleted_mask.n_bits:
            raise ValueError(
                f"row filter covers {sample_filter.n_bits} ids but "
                f"deleted_mask covers {deleted_mask.n_bits}"
            )
        nw = deleted_mask.words.shape[0]
        live = ~deleted_mask.words
        words = sample_filter.words.at[:, :nw].set(
            sample_filter.words[:, :nw] & live[None, :]
        )
        table = sample_filter.table
        if table is not None:
            table = table.at[:, :nw].set(table[:, :nw] & live[None, :])
        return RowFilter(
            words,
            sample_filter.n_bits,
            fid=sample_filter.fid,
            table=table,
            pass_count=sample_filter.pass_count,
        )
    if sample_filter.n_bits != deleted_mask.n_bits:
        raise ValueError(
            f"sample_filter covers {sample_filter.n_bits} ids but "
            f"deleted_mask covers {deleted_mask.n_bits}"
        )
    return Bitset(sample_filter.words & ~deleted_mask.words, sample_filter.n_bits)


def invalid_mask(ids: jax.Array, filter_words: Optional[jax.Array]) -> jax.Array:
    """Candidate mask: padding slots plus bitset-filtered ids
    (ref: neighbors/sample_filter_types.hpp bitset_filter)."""
    invalid = ids < 0
    if filter_words is not None:
        word = filter_words[jnp.clip(ids, 0, None) // 32]
        bit = (word >> (jnp.clip(ids, 0, None) % 32).astype(jnp.uint32)) & 1
        invalid = invalid | (bit == 0)
    return invalid


def invalid_mask_rows(ids: jax.Array, row_words: jax.Array) -> jax.Array:
    """Per-row variant of :func:`invalid_mask` for ragged batches: ids
    [rows, ...] tested against row_words [rows, n_words] — query row r is
    filtered by its own word set, so heterogeneous predicates share one
    compiled scan."""
    r = ids.shape[0]
    clipped = jnp.clip(ids, 0, None)
    word = jnp.take_along_axis(
        row_words, (clipped // 32).reshape(r, -1), axis=1
    ).reshape(ids.shape)
    bit = (word >> (clipped % 32).astype(jnp.uint32)) & 1
    return (ids < 0) | (bit == 0)


def centroid_group_inverse(centers) -> np.ndarray:
    """Group id per list, where split shards of one oversized list (which
    duplicate their parent centroid, see split_oversized_lists) share a
    group. O(L·dim) — cache the result on the index for repeated appends."""
    _, inverse = np.unique(np.asarray(centers), axis=0, return_inverse=True)
    return inverse


def invert_probes(probes: jax.Array, n_lists: int, bucket: int):
    """Invert the (query, probe) relation into per-list query buckets — the
    shared front half of the probe-major scan schedule (SURVEY §7 hard
    part 2; used by the IVF-PQ and IVF-Flat probe-major kernels).

    Traced helper; ``bucket`` (G) must be static. Returns
    (bucket_list [B], bucket_query [B, G], bucket_pair [B, G], B) where
    B = q·p//G + n_lists is the static bucket-count bound, bucket_query
    rows are -1-padded, and bucket_pair holds each slot's original
    (query-major) pair index for the scatter-back merge."""
    q, p = probes.shape
    G = bucket
    P = q * p
    pair_list = probes.reshape(P)
    pair_query = jnp.repeat(jnp.arange(q, dtype=jnp.int32), p)
    order = jnp.argsort(pair_list, stable=True)
    sl = pair_list[order]
    sq = pair_query[order]
    first = jnp.searchsorted(sl, sl, side="left")
    pos = jnp.arange(P) - first                                  # rank in list
    counts = jax.ops.segment_sum(
        jnp.ones(P, jnp.int32), sl, num_segments=n_lists
    )
    nb = (counts + G - 1) // G                                   # buckets/list
    bucket_off = jnp.cumsum(nb) - nb                             # [n_lists]
    pair_bucket = bucket_off[sl] + pos // G                      # [P]
    slot = pos % G
    B = P // G + n_lists  # static bound: Σ ceil(c/G) ≤ P/G + #nonzero lists
    bucket_list = jnp.zeros(B, jnp.int32).at[pair_bucket].set(sl)
    bucket_query = jnp.full((B, G), -1, jnp.int32).at[pair_bucket, slot].set(sq)
    bucket_pair = jnp.full((B, G), -1, jnp.int32).at[pair_bucket, slot].set(
        order.astype(jnp.int32)
    )
    return bucket_list, bucket_query, bucket_pair, B


def select_scan_strategy(
    strategy: str,
    q: int,
    n_probes: int,
    n_lists: int,
    list_cap: int,
    row_dim: int,
    workspace_bytes: int,
    k: int = 10,
):
    """Resolve the IVF scan schedule + probe-major sizing — ONE copy of the
    auto rule and the bucket/bb arithmetic for both IVF indexes and the
    sharded scan (tuned from the on-chip ``ivf_scan_ab`` A/B; see
    SearchParams.strategy).

    Returns (strategy, bucket, bb, q_tile); bucket/bb are None for
    query_major. ``q_tile`` bounds the probe-major merge buffers
    (pair partials are O(q·n_probes·k)) — callers batch queries host-side
    at this tile like the query-major path does for its gathers.
    """
    if strategy == "auto":
        # probe-major pays off when the batch reuses lists heavily: every
        # list is then streamed ~once instead of once per probing query
        strategy = (
            "probe_major"
            if q >= 256 and q * n_probes >= 4 * n_lists
            else "query_major"
        )
    if strategy != "probe_major":
        return strategy, None, None, None
    # merge-buffer bound: pair partials + bucket metadata ≈ 24 B per
    # (pair, k-slot); allow 4× the workspace for these transients. The
    # floor is the probe-major minimum batch (256) — NOT a bound override:
    # huge n_probes·k on a small workspace must still tile hard.
    per_q = max(1, n_probes * max(k, 1) * 24)
    q_tile = int(np.clip(4 * workspace_bytes // per_q, 256, max(q, 256)))
    # bucket size comes from the reuse ratio of the ACTUAL per-call batch,
    # min(q, q_tile) — sizing from the full q would leave tiles mostly -1
    # padding (masked MXU slots) whenever q ≫ q_tile
    reuse = max(1.0, (min(q, q_tile) * n_probes) / max(n_lists, 1))
    bucket = int(np.clip(1 << int(np.ceil(np.log2(reuse))), 16, 512))
    # per-step workspace: bb × (list rows + [G, cap] scores/ids + queries)
    per_b = list_cap * (row_dim * 4 + bucket * 8) + bucket * row_dim * 4
    bb = int(np.clip(workspace_bytes // max(per_b, 1), 1, 64))
    return strategy, bucket, bb, q_tile


def merge_probe_major_partials(vs, is_, bucket_pair, q, n_probes, kk, k):
    """Scatter per-(pair) top-kk partials back to (query, probe) order and
    merge per query — the back half of the probe-major schedule. ``vs``/
    ``is_`` are [B_pad·G, kk]; padding slots carry bucket_pair −1 and are
    dropped."""
    P = q * n_probes
    flat_pair = bucket_pair.reshape(-1)
    dest = jnp.where(flat_pair >= 0, flat_pair, P)               # P = drop
    pair_v = jnp.full((P, kk), jnp.inf, jnp.float32).at[dest].set(
        vs, mode="drop"
    )
    pair_i = jnp.full((P, kk), -1, jnp.int32).at[dest].set(is_, mode="drop")
    return select_k(
        pair_v.reshape(q, n_probes * kk), k, select_min=True,
        input_indices=pair_i.reshape(q, n_probes * kk),
    )


def pallas_scan_enabled(
    metric: str, storage_dtype, *, allow_int8: bool = False
) -> bool:
    """ONE copy of the fused-Pallas-scan gate shared by ivf_pq and
    ivf_flat: ``kernels.use_pallas()`` (on TPU unless RAFT_TPU_PALLAS=0;
    RAFT_TPU_PALLAS=1 forces interpret mode off-TPU), L2 + inner-product + cosine,
    float/bf16 storage (the kernel upcasts in VMEM). Filtered searches
    ride the kernel's packed per-list word table (round 4 — see
    kernels/ivf_scan.pack_list_filter). ``allow_int8`` admits the
    quantized scan cache (ivf_pq only — the kernel's int8 leg dequantizes
    by scan_scale, which raw int8/uint8 ivf_flat datasets don't have)."""
    from raft_tpu.kernels import use_pallas

    dtypes = (jnp.float32, jnp.bfloat16) + ((jnp.int8,) if allow_int8 else ())
    return (
        use_pallas()
        and metric in ("sqeuclidean", "euclidean", "inner_product", "cosine")
        and storage_dtype in dtypes
    )


def run_query_tiled(run_fn, queries, q_tile: int, extras=()):
    """Host-level query batching: run ``run_fn(q_tile_block, *extra_blocks)
    → (v, i)`` over fixed-size query tiles (tail zero-padded so every call
    shares one compiled shape) and concatenate. The single tiling
    implementation for every probe-major/sharded search entry. ``extras``
    are per-query arrays (leading dim = n_q, e.g. ragged filter ids) sliced
    and padded alongside the queries."""
    n_q = queries.shape[0]
    if q_tile >= n_q:
        return run_fn(queries, *extras)
    vs, is_ = [], []
    for s in range(0, n_q, q_tile):
        qt = queries[s : s + q_tile]
        ets = [e[s : s + q_tile] for e in extras]
        pad = q_tile - qt.shape[0]
        if pad:
            qt = jnp.pad(qt, ((0, pad), (0, 0)))
            ets = [
                jnp.pad(e, [(0, pad)] + [(0, 0)] * (e.ndim - 1)) for e in ets
            ]
        v, i = run_fn(qt, *ets)
        vs.append(v[: v.shape[0] - pad] if pad else v)
        is_.append(i[: i.shape[0] - pad] if pad else i)
    return jnp.concatenate(vs), jnp.concatenate(is_)


def run_probe_major(probes, n_lists: int, bucket: int, bb: int, kk: int,
                    k: int, score_fn):
    """The full probe-major schedule scaffold shared by the IVF-PQ,
    IVF-Flat, and sharded scans: invert the (query, probe) relation, pad
    buckets to whole steps, run one scan over bucket batches, and merge the
    partials per query.

    ``score_fn(bucket_lists [bb], bucket_queries [bb, G]) →
    (v [bb·G, kk], i [bb·G, kk])`` supplies the index-specific scoring; it
    must mask padding slots (bucket_queries < 0) to +inf itself.
    Traced helper; bucket/bb/kk/k static."""
    q, p = probes.shape
    G = bucket
    bucket_list, bucket_query, bucket_pair, B = invert_probes(
        probes, n_lists, G
    )
    n_steps = -(-B // bb)
    B_pad = n_steps * bb
    bucket_list = jnp.pad(bucket_list, (0, B_pad - B))
    bucket_query = jnp.pad(
        bucket_query, ((0, B_pad - B), (0, 0)), constant_values=-1
    )
    bucket_pair = jnp.pad(
        bucket_pair, ((0, B_pad - B), (0, 0)), constant_values=-1
    )

    def step(start):
        bl = jax.lax.dynamic_slice_in_dim(bucket_list, start, bb)
        bq = jax.lax.dynamic_slice_in_dim(bucket_query, start, bb)
        return score_fn(bl, bq)

    vs, is_ = jax.lax.map(step, jnp.arange(n_steps) * bb)
    return merge_probe_major_partials(
        vs.reshape(B_pad * G, kk), is_.reshape(B_pad * G, kk),
        bucket_pair, q, p, kk, k,
    )


def allocate_append_slots(centers, list_sizes, cap, labels, group_inverse=None):
    """Assign a (list, slot) to each new row for an in-place append, or
    return None when a centroid group is out of spare capacity.

    Split shards of a skewed list duplicate their parent centroid (see
    split_oversized_lists); rows whose predicted shard is full overflow
    into a sibling shard with space — they rank identically at probe
    selection, so placement among siblings is recall-neutral. Shared by the
    IVF-Flat/IVF-PQ fast extend paths (the TPU answer to the reference's
    device-side list growth, ivf_flat_build.cuh:163 / ivf_pq_build.cuh:1501).

    ``group_inverse`` — pass ``centroid_group_inverse(centers)`` cached by
    the caller to skip the O(L·dim) dedupe on every incremental append.

    Returns (lists [n], slots [n], counts_new [L]) — all numpy — or None.
    """
    centers = np.asarray(centers)
    sizes = np.asarray(list_sizes).copy()
    labels = np.asarray(labels, np.int64)
    L = centers.shape[0]
    if labels.size and labels.max() >= L:
        return None

    inverse = (
        group_inverse
        if group_inverse is not None
        else centroid_group_inverse(centers)
    )
    group_members: dict = {}
    for lst, g in enumerate(inverse):
        group_members.setdefault(int(g), []).append(lst)

    out_list = np.empty_like(labels)
    out_slot = np.empty_like(labels)
    for g in np.unique(inverse[labels]):
        rows = np.nonzero(inverse[labels] == g)[0]
        members = group_members[int(g)]
        if sum(cap - sizes[m] for m in members) < len(rows):
            return None  # group out of capacity → caller repacks
        i = 0
        for m in members:
            take = min(cap - sizes[m], len(rows) - i)
            if take <= 0:
                continue
            sel = rows[i : i + take]
            out_list[sel] = m
            out_slot[sel] = sizes[m] + np.arange(take)
            sizes[m] += take
            i += take
            if i == len(rows):
                break
    return out_list, out_slot, sizes - np.asarray(list_sizes)


@functools.partial(jax.jit, static_argnames=("metric", "n_probes"))
def _coarse_probes_jit(queries, centers, metric, n_probes):
    """Standalone coarse pass for the paged prefix (same math the search
    executables re-derive in-trace — deterministic, so both agree)."""
    return coarse_select(queries, centers, metric, n_probes)


def paged_lists_for_search(index, queries, metric: str, n_probes: int):
    """Paged-search prefix shared by ivf_flat/ivf_pq: run the coarse
    pass, key the pager by the probed lists (async prefetch hint, then
    blocking admission), and hand back the :class:`PagedLists` device
    view the unchanged search executables scan through.

    The coarse top-n_probes runs twice (here and inside the search
    executable) — one tiny [q, L] GEMM, cheap next to the list scan, and
    the price of keeping the scan executables byte-identical to the
    monolithic arm."""
    from raft_tpu.obs import explain as _explain
    from raft_tpu.store.paged import PagedLists, pages_for_lists

    tiered = index.paged
    explain_on = _explain.enabled()
    if tiered.slots == tiered.n_pages:
        # fully-resident pool: pin the identity mapping once and skip the
        # per-dispatch coarse/residency bookkeeping entirely — nothing can
        # ever be evicted, so the page table is immutable after the pin.
        # This is what keeps the HBM-resident paged arm within a few
        # percent of the monolithic control.
        tiered.pin_identity()
        pool, page_slot = tiered.view()
        if explain_on:
            _explain.stamp_page_stats({
                "pager": tiered.name, "pinned": True,
                "hits": 0, "misses": 0,
            })
        return PagedLists(pool, page_slot, tiered.pages_per_list)
    probes = _coarse_probes_jit(queries, index.centers, metric, n_probes)
    lists = np.unique(np.asarray(probes))  # raft-tpu: ignore[HOSTSYNC] prefetch keying needs the probed lists on host before dispatch
    pages = pages_for_lists(lists, tiered.pages_per_list)
    h0 = m0 = 0
    if explain_on:
        # bracket the pager calls with the counters this dispatch already
        # maintains — the deltas are THIS batch's page attribution (no
        # extra syncs: `lists` is the host array computed above either way)
        h0, m0, _ = tiered.counters()
    tiered.prefetch(pages)
    tiered.ensure_resident(pages)
    if explain_on:
        h1, m1, resident = tiered.counters()
        _explain.stamp_page_stats({
            "pager": tiered.name, "pinned": False,
            "probed_lists": int(lists.size),
            "pages": int(pages.size),
            "hits": h1 - h0, "misses": m1 - m0,
            "resident": resident,
        })
    pool, page_slot = tiered.view()
    return PagedLists(pool, page_slot, tiered.pages_per_list)
