"""Multi-device (SPMD) algorithms over the comms facade.

The reference reaches multi-GPU through algorithms written against comms_t
(data-parallel kmeans in cuML, distributed ANN; ref:
docs/source/using_raft_comms.rst, SURVEY §2.13.4). Here the same two
workhorses, written once against ``Comms`` and run under shard_map:

- ``sharded_knn``: dataset rows sharded across the mesh axis; each shard
  computes local top-k, then an all-gather + merge — the collective
  equivalent of knn_merge_parts (ref: neighbors/detail/knn_merge_parts.cuh).
  This is this domain's "ring attention": scaling dataset size beyond one
  device (SURVEY §5 long-context note).
- ``kmeans_step``: one Lloyd iteration with row-sharded data; centroid sums
  and counts are psum-ed (allreduce) exactly like cuML's MNMG kmeans.
"""

from __future__ import annotations


import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.comms.comms import Comms
from raft_tpu.distance.pairwise import DISTANCE_TYPES, distance_matrix_tile
from raft_tpu.ops.matrix import select_k


def sharded_knn(
    comms: Comms,
    dataset_sharded: jax.Array,
    queries: jax.Array,
    k: int,
    *,
    metric: str = "sqeuclidean",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over a row-sharded dataset.

    ``dataset_sharded`` is the global [n, d] array (sharded or shardable on
    the comms axis); queries are replicated. Returns replicated
    (distances [q, k], global indices [q, k]).
    """
    if metric not in DISTANCE_TYPES:
        raise ValueError(f"unsupported metric {metric!r}; one of {sorted(DISTANCE_TYPES)}")
    mesh = comms.mesh
    axis = comms.axis
    n = dataset_sharded.shape[0]
    size = comms.get_size()
    shard_rows = n // size
    select_min = DISTANCE_TYPES[metric] != "inner_product"
    k_local = min(k, shard_rows)  # a shard can contribute at most its rows

    def local(ds_shard, q):
        rank = lax.axis_index(axis)
        dist = distance_matrix_tile(q, ds_shard, metric)
        v, i = select_k(dist, k_local, select_min=select_min)
        if k_local < k:  # pad so the merged pool still holds k winners
            worst = jnp.inf if select_min else -jnp.inf
            v = jnp.pad(v, ((0, 0), (0, k - k_local)), constant_values=worst)
            i = jnp.pad(i, ((0, 0), (0, k - k_local)), constant_values=0)
        gi = i + rank * shard_rows  # globalize ids
        # gather all shards' candidates and reselect — merge step
        vg = lax.all_gather(v, axis, axis=1, tiled=True)  # [q, size*k]
        ig = lax.all_gather(gi, axis, axis=1, tiled=True)
        return select_k(vg, k, select_min=select_min, input_indices=ig)

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return f(dataset_sharded, queries)


def shard_ivf_pq_index(comms: Comms, index) -> dict:
    """Shard an IVF-PQ index list-wise across the comms axis.

    The MNMG ANN pattern (ref: SURVEY §5 'distributed communication
    backend' — shard indexes across the mesh, merge per-shard top-k):
    lists (and their decoded scan rows) are distributed over devices; the
    coarse centroids travel with their lists so each shard probes locally.
    Lists are padded to a multiple of the axis size with empty lists whose
    centroids are masked out of coarse selection.
    """
    from jax.sharding import NamedSharding

    size = comms.get_size()
    L = index.n_lists
    L_pad = -(-L // size) * size
    pad = L_pad - L

    def dev_put(arr, spec):
        return jax.device_put(arr, NamedSharding(comms.mesh, spec))

    axis = comms.axis
    centers = jnp.pad(index.centers, ((0, pad), (0, 0)))
    # the int8 memory-lean cache shards AS int8 — each shard keeps its
    # 1/size of the rot_dim-bytes/vector cache and the global scan_scale,
    # and the sharded scan runs the same quantized-query recipe as the
    # single-device kernel (dequantizing here would double every shard's
    # bytes, defeating the mode on exactly the DEEP-100M-on-a-mesh
    # configuration that needs both features)
    scan_scale = (
        float(index.scan_scale)
        if index.list_data.dtype == jnp.int8 else 1.0
    )
    data = jnp.pad(index.list_data, ((0, pad), (0, 0), (0, 0)))
    y2 = jnp.pad(index.list_y2, ((0, pad), (0, 0)))
    ids = jnp.pad(index.list_index, ((0, pad), (0, 0)), constant_values=-1)
    valid = jnp.arange(L_pad) < L
    return {
        "centers": dev_put(centers, P(axis, None)),
        "list_data": dev_put(data, P(axis, None, None)),
        "list_y2": dev_put(y2, P(axis, None)),
        "list_index": dev_put(ids, P(axis, None)),
        "list_valid": dev_put(valid, P(axis)),
        "rotation": dev_put(index.rotation, P(None, None)),
        "metric": index.metric,
        "scan_scale": scan_scale,
    }


def _sharded_scan_plan(
    comms: Comms, sharded: dict, queries: jax.Array, k: int,
    n_probes: int, strategy: str, *, upcast_f32: bool = False,
):
    """Shared pre-scan arithmetic for the sharded IVF searches
    (validation, per-shard probe/k budgets, workspace query tiling,
    scan-strategy resolution) — ONE owner so the PQ and Flat paths
    cannot drift. ``upcast_f32`` accounts for scans that gather the
    stored rows and then copy them to f32 (the flat low-precision path)
    so low-precision storage doesn't overshoot the workspace budget.
    Returns (queries as f32, plan dict)."""
    from raft_tpu.core.resources import ensure as _ensure
    from raft_tpu.neighbors._common import select_scan_strategy

    size = comms.get_size()
    L_shard = sharded["centers"].shape[0] // size
    cap = sharded["list_data"].shape[1]
    row_dim = sharded["list_data"].shape[2]
    p_local = min(n_probes, L_shard)
    k_local = min(k, p_local * cap)
    if size * k_local < k:
        raise ValueError(
            f"k={k} exceeds the global candidate pool "
            f"{size}*{k_local} (shards*probed slots); raise n_probes"
        )
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != sharded["centers"].shape[1]:
        raise ValueError(
            f"queries shape {queries.shape} vs index dim "
            f"{sharded['centers'].shape[1]}"
        )
    if strategy not in ("auto", "query_major", "probe_major"):
        raise ValueError(
            f"strategy must be auto|query_major|probe_major, got {strategy!r}"
        )
    ws = _ensure(None).workspace_limit_bytes
    itemsize = jnp.dtype(sharded["list_data"].dtype).itemsize
    if upcast_f32 and itemsize < 4:
        itemsize += 4  # the gathered block plus its f32 copy both live
    per_q = max(1, p_local * cap * (row_dim * itemsize + 12))
    query_tile = int(min(queries.shape[0], max(1, ws // per_q)))
    local_strategy, bucket, bb, q_tile = select_scan_strategy(
        strategy, queries.shape[0], p_local, L_shard, cap, row_dim, ws,
        k=k_local,
    )
    if local_strategy == "probe_major":
        # per-step scan work is bounded via bb; the merge buffers via the
        # probe-major query tile (host-level batching by the caller)
        query_tile = q_tile
    return queries, {
        "L_shard": L_shard, "cap": cap, "row_dim": row_dim,
        "p_local": p_local, "k_local": k_local,
        "query_tile": max(1, query_tile),
        "strategy": local_strategy, "bucket": bucket, "bb": bb,
    }


def _merge_across_shards(v, i, axis: str, k: int, k_local: int):
    """Pad per-shard top-k_local to k, all-gather, re-select — the
    knn_merge_parts-equivalent collective tail every sharded IVF search
    shares. Runs inside shard_map."""
    if k_local < k:
        v = jnp.pad(v, ((0, 0), (0, k - k_local)), constant_values=jnp.inf)
        i = jnp.pad(i, ((0, 0), (0, k - k_local)), constant_values=-1)
    vg = lax.all_gather(v, axis, axis=1, tiled=True)
    ig = lax.all_gather(i, axis, axis=1, tiled=True)
    return select_k(vg, k, select_min=True, input_indices=ig)


def sharded_ivf_pq_search(
    comms: Comms,
    sharded: dict,
    queries: jax.Array,
    k: int,
    *,
    n_probes: int = 20,
    lut_dtype: str = "float32",
    strategy: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Distributed IVF-PQ search: each shard probes ``n_probes`` of its own
    lists and scans them; per-shard top-k results (global dataset ids) are
    all-gathered and re-selected — the knn_merge_parts-equivalent collective
    (ref: the reference's MNMG search = local search + merge; BASELINE
    config #5 distributed IVF-PQ).

    ``lut_dtype`` mirrors the single-device SearchParams knob: "float32"
    (default) upcasts the stored rows for the scan so sharded distances
    match the single-device search; "bfloat16" halves the scan stream.
    int8 (memory-lean) caches ignore it and run the quantized-query int8
    MXU path with the index's global ``scan_scale`` — numerically identical
    to the single-device int8 scan, at int8 bytes per shard.
    ``strategy`` selects each shard's local scan schedule (see
    ivf_pq.SearchParams.strategy — the probe-major schedule streams each
    local list from HBM once per bucket).

    Returns replicated (distances [q, k], ids [q, k]).
    """
    from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC
    from raft_tpu.neighbors._common import lane_pad, run_probe_major

    metric = DISTANCE_TYPES[sharded["metric"]]
    mesh, axis = comms.mesh, comms.axis
    # the PQ scan never upcasts its gather (bf16 scans as bf16, int8 rides
    # the quantized MXU path) — no upcast allowance in the sizing
    queries, plan = _sharded_scan_plan(
        comms, sharded, queries, k, n_probes, strategy
    )
    L_shard, cap = plan["L_shard"], plan["cap"]
    p_local, k_local = plan["p_local"], plan["k_local"]
    local_strategy, bucket, bb = plan["strategy"], plan["bucket"], plan["bb"]
    query_tile = plan["query_tile"]

    def local(centers_s, valid_s, data_s, y2_s, ids_s, rot, q):
        # coarse over this shard's lists, empty-padding masked out
        if metric == "inner_product":
            coarse = -jnp.matmul(q, centers_s.T, precision=_PREC)
        else:
            c2 = jnp.sum(centers_s * centers_s, axis=1)
            coarse = c2[None, :] - 2.0 * jnp.matmul(q, centers_s.T, precision=_PREC)
        coarse = jnp.where(valid_s[None, :], coarse, jnp.inf)
        _, probes = select_k(coarse, p_local, select_min=True)

        q_rot = jnp.matmul(q, rot.T, precision=_PREC)
        q2 = jnp.sum(q_rot * q_rot, axis=1)               # [q]
        # zero lanes up to the cache's padded width add exact zeros
        q_rot = lane_pad(q_rot, data_s.shape[-1])
        # scan compute dtype per lut_dtype (f32 upcast of the stored rows by
        # default — the single-device kernel's knob); f32 accumulation.
        # int8 caches instead ride the MXU's native int8 path with the
        # SAME quantized-query recipe as the single-device scan
        # (toolkit.quantize_queries_i8 + scan_scale rescale).
        quantized = data_s.dtype == jnp.int8
        scan_scale = sharded.get("scan_scale", 1.0)
        scan_dtype = jnp.bfloat16 if lut_dtype == "bfloat16" else jnp.float32
        n_q = q.shape[0]

        def scored_ip(qr, dec, batch_axes):
            """q·y inner products in the cache's native dtype; int8 caches
            ride the shared quantized-query recipe (toolkit.int8_scored_ip
            — the same helper the single-device scans use)."""
            if quantized:
                from raft_tpu.kernels.toolkit import int8_scored_ip

                return int8_scored_ip(qr, dec, batch_axes, scan_scale)
            return lax.dot_general(
                qr.astype(scan_dtype), dec.astype(scan_dtype), batch_axes,
                preferred_element_type=jnp.float32,
            )

        if local_strategy == "probe_major":
            # per-shard probe-major schedule (shared scaffold
            # _common.run_probe_major): each local list streams once per
            # bucket, partials merge per query
            kk = min(k_local, cap)

            def score_fn(bl, bq):
                dec = data_s[bl]                          # [bb, cap, rot]
                ids_b = ids_s[bl]
                y2_b = y2_s[bl]
                qr = q_rot[jnp.clip(bq, 0)]               # [bb, G, rot]
                ip = scored_ip(qr, dec, (((2,), (2,)), ((0,), (0,))))
                if metric == "inner_product":
                    sc = -ip
                else:
                    qq2 = q2[jnp.clip(bq, 0)]
                    sc = y2_b[:, None, :] - 2.0 * ip + qq2[:, :, None]
                sc = jnp.where(ids_b[:, None, :] < 0, jnp.inf, sc)
                sc = jnp.where(bq[:, :, None] < 0, jnp.inf, sc)
                return select_k(
                    sc.reshape(bb * bucket, cap), kk, select_min=True,
                    input_indices=jnp.broadcast_to(
                        ids_b[:, None, :], (bb, bucket, cap)
                    ).reshape(bb * bucket, cap),
                )

            v, i = run_probe_major(
                probes, L_shard, bucket, bb, kk, k_local, score_fn
            )
        else:
            dec = data_s[probes]                          # [q, p, cap, rot]
            ids = ids_s[probes]                           # [q, p, cap]
            y2 = y2_s[probes]
            ip = scored_ip(q_rot, dec, (((1,), (3,)), ((0,), (0,))))
            if metric == "inner_product":
                scores = -ip
            else:
                scores = y2 - 2.0 * ip + q2[:, None, None]
            # padding slots carry id −1; +inf scores keep them losing
            scores = jnp.where(ids < 0, jnp.inf, scores)
            flat_s = scores.reshape(n_q, p_local * cap)
            flat_i = ids.reshape(n_q, p_local * cap)
            v, i = select_k(
                flat_s, k_local, select_min=True, input_indices=flat_i
            )
        # merge across shards (global ids already)
        v, i = _merge_across_shards(v, i, axis, k, k_local)
        if metric == "inner_product":
            v = -v
        elif metric == "euclidean":
            v = jnp.sqrt(jnp.maximum(v, 0.0))
        return v, i

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(axis, None), P(axis), P(axis, None, None), P(axis, None),
            P(axis, None), P(None, None), P(None, None),
        ),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    from raft_tpu.neighbors._common import run_query_tiled

    def run_tile(qq):
        return f(
            sharded["centers"], sharded["list_valid"], sharded["list_data"],
            sharded["list_y2"], sharded["list_index"], sharded["rotation"], qq,
        )

    return run_query_tiled(run_tile, queries, max(1, query_tile))


def sharded_ivf_pq_build(
    comms: Comms,
    x_sharded: jax.Array,
    params,
    *,
    res=None,
):
    """MNMG IVF-PQ build — the raft-dask pattern (ref:
    python/raft-dask/raft_dask/common/comms.py:172-212: workers share one
    quantizer and index their local rows), TPU-native:

    1. Train the coarse centroids + PQ codebooks ONCE on the trainset
       subsample (the same deterministic kernels as the single-device
       build — same seed → identical quantizers).
    2. Run the O(n) predict+encode shard-locally under shard_map: each
       device encodes its own rows against the replicated quantizer; only
       the compressed stream (pq_dim B/row) leaves the devices.
    3. Assemble the global list layout through the single-device seam
       (``ivf_pq._extend_encoded``) — byte-identical to a single-device
       build of the same rows, so searches are id-faithful.

    ``x_sharded`` is the global [n, d] array, sharded (or shardable) on
    the comms axis. Returns the assembled :class:`ivf_pq.Index`; pass it
    to :func:`shard_ivf_pq_index` for distributed search (the full
    build → search round trip runs in ``dryrun_multichip``).
    """
    from dataclasses import replace

    from raft_tpu.cluster.kmeans_balanced import _predict_jit
    from raft_tpu.core.resources import ensure as _ensure
    from raft_tpu.distance.pairwise import argmin_tile_rows
    from raft_tpu.neighbors import ivf_pq

    mesh, axis = comms.mesh, comms.axis
    size = comms.get_size()
    n, dim = x_sharded.shape
    x_sharded = jnp.asarray(x_sharded)

    # 1) quantizer training (trainset-subsample-sized, like the reference's
    # build — ivf_pq_build.cuh:1706-1766; the O(n) work is steps 2-3)
    skel = ivf_pq.build(
        replace(params, add_data_on_build=False), x_sharded, res=res
    )

    # 2) shard-local encode
    kb_metric = (
        "inner_product"
        if DISTANCE_TYPES[params.metric] == "inner_product"
        else "sqeuclidean"
    )
    tile_rows = argmin_tile_rows(skel.centers.shape[0], _ensure(res))
    n_pad = -(-n // size) * size
    if n_pad != n:
        from jax.sharding import NamedSharding

        x_sharded = jax.device_put(
            jnp.pad(x_sharded, ((0, n_pad - n), (0, 0))),
            NamedSharding(mesh, P(axis, None)),
        )

    def local(xs, centers, centers_rot, rotation, codebook):
        xs = xs.astype(jnp.float32)
        lt = _predict_jit(centers, xs, kb_metric, tile_rows)
        codes = ivf_pq._encode(
            rotation, centers, centers_rot, codebook, xs, lt,
            skel.codebook_kind,
        )
        return codes, lt.astype(jnp.int32)

    rep = P(*([None] * 2))
    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), rep, rep, rep,
                  P(*([None] * skel.codebook.ndim))),
        out_specs=(P(axis, None), P(axis)),
        check_vma=False,
    )
    codes, labels = f(
        x_sharded, skel.centers, skel.centers_rot, skel.rotation,
        skel.codebook,
    )

    # 3) assemble — only the compressed stream crosses to the host.
    # In multi-process SPMD the sharded codes span non-addressable
    # devices; every process needs the full stream for the (replicated)
    # assembly, so gather across hosts — for a single process
    # process_allgather is a plain device→host fetch (caught by the
    # 2-process n=100k suite, round 5).
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils as _mh

        codes_np = _mh.process_allgather(codes, tiled=True)
        labels_np = _mh.process_allgather(labels, tiled=True)
    else:
        codes_np, labels_np = np.asarray(codes), np.asarray(labels)
    return ivf_pq._extend_encoded(
        skel,
        codes_np[:n],
        labels_np[:n],
        jnp.arange(n, dtype=jnp.int32),
    )


def shard_ivf_flat_index(comms: Comms, index) -> dict:
    """Shard an IVF-Flat index list-wise across the comms axis — the flat
    sibling of :func:`shard_ivf_pq_index` (raw rows + norms instead of a
    decoded PQ cache; rows shard in their stored dtype)."""
    from jax.sharding import NamedSharding

    size = comms.get_size()
    L = index.n_lists
    L_pad = -(-L // size) * size
    pad = L_pad - L

    def dev_put(arr, spec):
        return jax.device_put(arr, NamedSharding(comms.mesh, spec))

    axis = comms.axis
    centers = jnp.pad(index.centers, ((0, pad), (0, 0)))
    data = jnp.pad(index.list_data, ((0, pad), (0, 0), (0, 0)))
    # padding slots carry +inf norms in the single-device layout; zero
    # them so inf never enters the MXU product, and mask by id instead
    norms = jnp.pad(
        jnp.where(index.list_index >= 0, index.list_norms, 0.0),
        ((0, pad), (0, 0)),
    )
    ids = jnp.pad(index.list_index, ((0, pad), (0, 0)), constant_values=-1)
    valid = jnp.arange(L_pad) < L
    return {
        "centers": dev_put(centers, P(axis, None)),
        "list_data": dev_put(data, P(axis, None, None)),
        "list_norms": dev_put(norms, P(axis, None)),
        "list_index": dev_put(ids, P(axis, None)),
        "list_valid": dev_put(valid, P(axis)),
        "metric": index.metric,
    }


def sharded_ivf_flat_search(
    comms: Comms,
    sharded: dict,
    queries: jax.Array,
    k: int,
    *,
    n_probes: int = 20,
    strategy: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Distributed IVF-Flat search: per-shard coarse selection over local
    lists, local scan (query-major or probe-major — the same two
    schedules as the single-device search), all-gather + re-select merge.
    Returns replicated (distances [q, k], ids [q, k])."""
    from raft_tpu.distance.pairwise import _PREC
    from raft_tpu.neighbors._common import run_probe_major, run_query_tiled

    metric = DISTANCE_TYPES[sharded["metric"]]
    mesh, axis = comms.mesh, comms.axis
    # upcast_f32: the flat scan copies the gathered low-precision rows to
    # f32 before scoring — the sizing must budget gather + copy
    queries, plan = _sharded_scan_plan(
        comms, sharded, queries, k, n_probes, strategy, upcast_f32=True
    )
    L_shard, cap = plan["L_shard"], plan["cap"]
    p_local, k_local = plan["p_local"], plan["k_local"]
    local_strategy, bucket, bb = plan["strategy"], plan["bucket"], plan["bb"]
    query_tile = plan["query_tile"]

    def local(centers_s, valid_s, data_s, norms_s, ids_s, q):
        q2 = jnp.sum(q * q, axis=1)
        qn = jnp.maximum(jnp.sqrt(q2), 1e-12)
        if metric == "inner_product":
            coarse = -jnp.matmul(q, centers_s.T, precision=_PREC)
        elif metric == "cosine":
            cn = centers_s / jnp.maximum(
                jnp.linalg.norm(centers_s, axis=1, keepdims=True), 1e-12
            )
            coarse = -jnp.matmul(q / qn[:, None], cn.T, precision=_PREC)
        else:
            c2 = jnp.sum(centers_s * centers_s, axis=1)
            coarse = c2[None, :] - 2.0 * jnp.matmul(
                q, centers_s.T, precision=_PREC
            )
        coarse = jnp.where(valid_s[None, :], coarse, jnp.inf)
        _, probes = select_k(coarse, p_local, select_min=True)
        n_q = q.shape[0]

        if local_strategy == "probe_major":
            kk = min(k_local, cap)

            def score_fn(bl, bq):
                data = data_s[bl]                           # [bb, cap, d]
                ids_b = ids_s[bl]
                norms_b = norms_s[bl]
                qq = q[jnp.clip(bq, 0)]                     # [bb, G, d]
                ip = lax.dot_general(
                    qq, data.astype(jnp.float32),
                    (((2,), (2,)), ((0,), (0,))),
                    precision=_PREC, preferred_element_type=jnp.float32,
                )                                           # [bb, G, cap]
                if metric == "inner_product":
                    sc = -ip
                elif metric == "cosine":
                    vn = jnp.sqrt(jnp.maximum(norms_b, 1e-24))
                    sc = 1.0 - ip / (
                        qn[jnp.clip(bq, 0)][:, :, None] * vn[:, None, :]
                    )
                else:   # rank-stable L2: +‖q‖² restored after the merge
                    sc = norms_b[:, None, :] - 2.0 * ip
                sc = jnp.where(ids_b[:, None, :] < 0, jnp.inf, sc)
                sc = jnp.where(bq[:, :, None] < 0, jnp.inf, sc)
                return select_k(
                    sc.reshape(bb * bucket, cap), kk, select_min=True,
                    input_indices=jnp.broadcast_to(
                        ids_b[:, None, :], (bb, bucket, cap)
                    ).reshape(bb * bucket, cap),
                )

            v, i = run_probe_major(
                probes, L_shard, bucket, bb, kk, k_local, score_fn
            )
        else:
            data = data_s[probes]                           # [q, p, cap, d]
            ids = ids_s[probes]
            norms = norms_s[probes]
            ip = lax.dot_general(
                q, data.astype(jnp.float32),
                (((1,), (3,)), ((0,), (0,))),
                precision=_PREC, preferred_element_type=jnp.float32,
            )                                               # [q, p, cap]
            if metric == "inner_product":
                sc = -ip
            elif metric == "cosine":
                vn = jnp.sqrt(jnp.maximum(norms, 1e-24))
                sc = 1.0 - ip / (qn[:, None, None] * vn)
            else:
                sc = norms - 2.0 * ip
            sc = jnp.where(ids < 0, jnp.inf, sc)
            v, i = select_k(
                sc.reshape(n_q, p_local * cap), k_local, select_min=True,
                input_indices=ids.reshape(n_q, p_local * cap),
            )
        v, i = _merge_across_shards(v, i, axis, k, k_local)
        # postprocess (rank-stable parts restored; matches ivf_flat.search)
        if metric == "inner_product":
            v = -v
        elif metric == "euclidean":
            v = jnp.sqrt(jnp.maximum(v + q2[:, None], 0.0))
        elif metric == "sqeuclidean":
            v = v + q2[:, None]
        return v, i

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(axis, None), P(axis), P(axis, None, None), P(axis, None),
            P(axis, None), P(None, None),
        ),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )

    def run_tile(qq):
        return f(
            sharded["centers"], sharded["list_valid"], sharded["list_data"],
            sharded["list_norms"], sharded["list_index"], qq,
        )

    return run_query_tiled(run_tile, queries, max(1, query_tile))


def sharded_cagra_search(
    comms: Comms,
    index,
    queries: jax.Array,
    k: int,
    *,
    params=None,
):
    """Data-parallel CAGRA search: the graph index is REPLICATED (graph
    traversals don't partition — the reference's multi-GPU ANN mode
    likewise replicates the index and splits the query stream), queries
    shard over the comms axis, each device runs the full entry-seeded
    beam search on its shard, and results all-gather back replicated.

    This is the throughput-scaling mode for the flagship index: N devices
    ≈ N× the query throughput at identical per-query results (exactness
    asserted in ``dryrun_multichip``)."""
    from raft_tpu.neighbors import cagra

    params = params or cagra.SearchParams()
    mesh, axis = comms.mesh, comms.axis
    size = comms.get_size()
    queries = jnp.asarray(queries, jnp.float32)
    q = queries.shape[0]
    # seed the FULL batch once (pre-padding, so the draw matches a
    # single-device call on the same queries) and split the seeds with
    # the queries — per-query results are then independent of the split
    seeds = cagra.make_seed_ids(params, index, queries, k)
    q_pad = -(-q // size) * size
    if q_pad != q:
        queries = jnp.pad(queries, ((0, q_pad - q), (0, 0)))
        seeds = jnp.pad(seeds, ((0, q_pad - q), (0, 0)))
    from jax.sharding import NamedSharding

    qs = jax.device_put(queries, NamedSharding(mesh, P(axis, None)))
    ss = jax.device_put(seeds, NamedSharding(mesh, P(axis, None)))

    def local(q_shard, s_shard):
        v, i = cagra.search(params, index, q_shard, k, seed_ids=s_shard)
        vg = lax.all_gather(v, axis, axis=0, tiled=True)
        ig = lax.all_gather(i, axis, axis=0, tiled=True)
        return vg, ig

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    v, i = f(qs, ss)
    return v[:q], i[:q]


def sharded_cagra_build(
    comms: Comms,
    params,
    dataset,
    *,
    max_cluster_rows: int = 65_536,
    res=None,
):
    """MNMG CAGRA build — closes the one index build that was still
    single-device-only. The batch-GNND plan (balanced clustering + top-2
    overlap assignment, nn_descent.plan_batches — the raft-dask MNMG
    pattern of planning once and fanning the O(n) work out) runs
    host-side; the expensive per-batch graph builds run data-parallel
    over the mesh (batches stack [B, pad_m, d] and shard over the comms
    axis; each device ``lax.map``s a fixed-iteration GNND over its local
    batches); local graphs merge host-side exactly as in
    ``nn_descent.build_batch``; optimize + entry-point construction run
    replicated on the merged graph.

    **Split-invariant by design**: each batch's PRNG key folds in its
    GLOBAL batch index, and the GNND runs a fixed iteration count (an
    SPMD worker set cannot take data-dependent early exits divergently)
    — so the built index is bit-identical for ANY device count,
    asserted in ``dryrun_multichip``.
    """
    from jax.sharding import NamedSharding

    from raft_tpu.core.resources import ensure
    from raft_tpu.neighbors import cagra, nn_descent

    res = ensure(res)
    mesh, axis = comms.mesh, comms.axis
    size = comms.get_size()
    # the returned Index keeps the caller's dtype (bf16/int8 datasets stay
    # low-precision, as in cagra.build); only the GNND batch stack is f32
    dataset_orig = dataset if isinstance(dataset, np.ndarray) \
        else jnp.asarray(dataset)
    dataset_np = np.asarray(dataset, np.float32)
    n, d = dataset_np.shape
    inter = min(params.intermediate_graph_degree, n - 1)
    nnd = nn_descent.IndexParams(
        graph_degree=inter,
        intermediate_graph_degree=min(
            n - 1, max(inter + inter // 2, inter + 8)
        ),
        max_iterations=params.nn_descent_niter,
        metric=params.metric,
        seed=params.seed,
    )
    # force=True: a single-batch dataset takes the same SPMD path (and
    # the same split-invariance guarantee) as the multi-batch case;
    # plan_batches also owns the L2-only metric guard (the far sentinel
    # has no IP/cosine analog)
    plan = nn_descent.plan_batches(
        nnd, dataset_np, max_cluster_rows=max_cluster_rows, force=True,
        res=res,
    )
    batches, pad_m, k_out = plan["batches"], plan["pad_m"], plan["k_out"]
    lp = plan["local_params"]
    metric = DISTANCE_TYPES[lp.metric]
    k_inter = min(lp.intermediate_graph_degree, pad_m - 1)
    sample = lp.sample_size or min(k_inter, 16)
    c = sample * k_inter + sample
    tile = max(1, min(pad_m, res.workspace_rows(4 * c * (d + 4), cap=4096)))

    B = len(batches)
    B_pad = -(-B // size) * size
    stack = np.empty((B_pad, pad_m, d), np.float32)
    for b in range(B_pad):
        # tail padding repeats the last batch; its outputs are discarded
        stack[b] = nn_descent.pad_batch(
            dataset_np, batches[min(b, B - 1)], plan
        )
    base = jax.random.PRNGKey(lp.seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(B_pad, dtype=jnp.int32)
    )

    def one(args):
        x1, key1 = args
        gi, gd = nn_descent.gnnd_fixed(
            key1, x1, metric=metric, k=k_inter, sample=sample,
            tile=tile, iters=lp.max_iterations,
        )
        return gi[:, :k_out], gd[:, :k_out]

    def local(xb, kb):
        return lax.map(one, (xb, kb))

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )
    # device_put straight from numpy: each device receives ONLY its shard
    # (an intermediate jnp.asarray would commit the whole ~2x-dataset
    # stack to one device first — the OOM this MNMG build exists to avoid)
    xs = jax.device_put(stack, NamedSharding(mesh, P(axis, None, None)))
    ks = jax.device_put(keys, NamedSharding(mesh, P(axis, None)))
    gi_all, gd_all = f(xs, ks)
    if jax.process_count() > 1:
        # the merged graph is assembled (replicated) on every host; the
        # per-batch local graphs live on non-addressable devices
        from jax.experimental import multihost_utils as _mh

        gi_np = _mh.process_allgather(gi_all, tiled=True)
        gd_np = _mh.process_allgather(gd_all, tiled=True)
    else:
        gi_np, gd_np = np.asarray(gi_all), np.asarray(gd_all)

    g_ids = np.full((n, k_out), -1, np.int32)
    g_dists = np.full((n, k_out), np.inf, np.float32)
    for b, rows in enumerate(batches):
        nn_descent.merge_local_graph(
            g_ids, g_dists, rows, gi_np[b], gd_np[b], plan
        )
    knn = nn_descent.finalize_global_graph(g_ids, g_dists).graph
    # shared finalize (optimize + entry table + one dtype-preserving
    # upload) keeps the MNMG index identical in construction to
    # cagra.build's
    return cagra.finalize_index(params, dataset_orig, knn, res=res)


def kmeans_step(
    comms: Comms,
    data_sharded: jax.Array,
    centroids: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One distributed Lloyd iteration: assign + psum centroid sums/counts.

    Returns (new_centroids [k, d] replicated, inertia scalar replicated).
    The collective pattern of cuML MNMG kmeans over raft comms (allreduce of
    per-worker centroid partial sums).
    """
    mesh = comms.mesh
    axis = comms.axis
    n_clusters = centroids.shape[0]

    def local(x, c):
        d2 = distance_matrix_tile(x, c, "sqeuclidean")
        labels = jnp.argmin(d2, axis=1)
        best = jnp.min(d2, axis=1)
        sums = jax.ops.segment_sum(x, labels, num_segments=n_clusters)
        counts = jax.ops.segment_sum(jnp.ones_like(best), labels, num_segments=n_clusters)
        sums = lax.psum(sums, axis)
        counts = lax.psum(counts, axis)
        inertia = lax.psum(jnp.sum(best), axis)
        newc = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), c)
        return newc, inertia

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(None, None), P()),
        check_vma=False,
    )
    return f(data_sharded, centroids)


def kmeans_fit(
    comms: Comms,
    data_sharded: jax.Array,
    n_clusters: int,
    *,
    n_iters: int = 20,
    tol: float = 1e-4,
    seed: int = 0,
    n_init: int = 3,
) -> Tuple[jax.Array, jax.Array]:
    """Full distributed kmeans fit (BASELINE config #5's distributed
    kMeans; the cuML-over-raft-comms MNMG pattern: every iteration is one
    :func:`kmeans_step` allreduce, the whole loop one compiled program).

    ``data_sharded`` is [n, d] sharded over the comms axis. Init is
    kmeans++ on a replicated global subsample (rows travel once at init —
    random-row seeding collapses clusters on tight blobs, and a collapsed
    cluster never recovers in plain Lloyd). Returns (centroids [k, d]
    replicated, inertia_history [n_iters]); post-convergence iterations
    (shift² < tol·mean-row-norm²) report inf, keeping the scan
    static-shape. ``n_init`` restarts keep the lowest-inertia run (kmeans++
    occasionally double-seeds a tight cluster; same remedy as the
    single-device fit / the reference's n_init).
    """
    from raft_tpu.cluster.kmeans import kmeans_plus_plus_init

    n, _ = data_sharded.shape
    key = jax.random.PRNGKey(seed)
    k_sub, key = jax.random.split(key)
    n_sub = min(n, max(4 * n_clusters, 4096))
    # with-replacement draw: O(n_sub), no full-n permutation of the sharded
    # dataset (collisions in an init subsample are harmless)
    idx = jax.random.randint(k_sub, (n_sub,), 0, n)
    subsample = data_sharded[idx]  # cross-shard gather, replicated result

    scale = jnp.mean(jnp.sum(data_sharded * data_sharded, axis=1))
    run = _kmeans_fit_program(comms.mesh, comms.axis, n_iters, float(tol))
    best = None
    for r in range(max(1, n_init)):
        k_init = jax.random.fold_in(key, r)
        centroids0 = kmeans_plus_plus_init(k_init, subsample, n_clusters)
        c, hist = run(data_sharded, centroids0, scale)
        hist_np = np.asarray(hist)
        finite = hist_np[np.isfinite(hist_np)]
        cost = float(finite[-1]) if finite.size else float("inf")
        if best is None or cost < best[0]:
            best = (cost, c, hist)
    return best[1], best[2]


@functools.lru_cache(maxsize=32)
def _kmeans_fit_program(mesh, axis: str, n_iters: int, tol: float):
    """Build (and cache) the compiled fit loop per (mesh, axis, n_iters,
    tol) — a fresh closure per call would defeat jit's trace cache and
    re-trace the whole scan on every fit."""
    import types

    comms_like = types.SimpleNamespace(mesh=mesh, axis=axis)

    @jax.jit
    def run(x, c0, scale):
        def body(carry, _):
            c, done = carry
            newc, inertia = kmeans_step(comms_like, x, c)
            shift = jnp.sum((newc - c) ** 2)
            # post-convergence iterations report inf (static-shape scan)
            out = jnp.where(done, jnp.inf, inertia)
            done = done | (shift < tol * scale)
            return (jnp.where(done, c, newc), done), out

        (c, _), hist = lax.scan(
            body, (c0, jnp.zeros((), bool)), None, length=n_iters
        )
        return c, hist

    return run
