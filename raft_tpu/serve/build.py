"""Pod-scale distributed index build: train the index where the data lives.

``ShardedIndex.from_index`` scales *serving* — but it still requires a
single-host build first, which caps the buildable index at one host's
memory and one chip's FLOPs.  This module closes that gap:
:func:`build_sharded` trains brute_force / ivf_flat / ivf_pq / cagra
indexes over training data that stays row-sharded across a named mesh
axis, and returns a :class:`~raft_tpu.serve.shard.ShardedIndex` already
in its round-robin serving layout — hot-swappable through
``IndexRegistry`` with zero extra re-shard step.

What runs sharded (the O(n·d·k) legs — all per-iteration compute and
collectives are mesh-resident):

- **Coarse k-means** (ivf_flat/ivf_pq): every Lloyd iteration computes
  local assignments and partial centroid sums/counts on each shard's
  rows, then merges them with ONE packed ``psum`` per iteration
  (:func:`raft_tpu.cluster.kmeans_balanced.fit_sharded`).  The psum
  payload can be quantized EQuARX-style
  (``RAFT_TPU_BUILD_REDUCE_DTYPE=bfloat16|int8`` — see
  :mod:`raft_tpu.comms.quantized`): centroid partial sums tolerate low
  precision because each shard's contribution is renormalized by the
  global counts.
- **PQ codebook fitting** (ivf_pq per_subspace): per-subspace k-means
  over the *sharded* rotated residuals — one packed [pq_dim, k_pq,
  pq_len+1] sums|counts psum per Lloyd iteration, same quantization
  knob.
- **CAGRA kNN graph**: a ring of ``ppermute`` block exchanges.  Each of
  the S steps moves one shard-block of rows one hop around the ring;
  every shard scores its own rows against the visiting block
  (optionally in ``RAFT_TPU_BUILD_KNN_BLOCK_ROWS``-row column tiles to
  bound the distance matrix) and folds the block's top-k into a running
  tie-stable merge (:func:`~raft_tpu.ops.matrix.select_k_stable`), so
  the resulting graph is partition-invariant: identical to the
  single-host exact kNN regardless of how rows were sharded.  Rows
  travel around the ring exactly once; no all-gather of the dataset.

What is host-mediated (one-time layout staging, NOT per-iteration): the
final list assembly moves each row (ivf_flat) or its compressed PQ code
(ivf_pq — ``pq_dim`` bytes/row) to its destination list.  This is the
same host-staged transposition the existing
``comms.distributed.sharded_ivf_pq_build`` and
``ShardedIndex.from_index`` use, standing in for a DCN all-to-all; the
expensive training legs never funnel through it.

Layout: the sharded assembly targets ``ShardedIndex``'s round-robin
list placement *directly* via a shard-major relabel — global list ``l``
lives on shard ``l % S`` at slot ``l // S``, so relabeling
``l' = (l % S)·Lp + (l // S)`` (``Lp = ceil(L/S)``) and packing
``S·Lp`` lists in one pass (list splitting disabled — ``max_cap=None``)
yields, after a ``[S·Lp, ...] → [S, Lp, ...]`` reshape, exactly the
stacks ``_partition_lists`` would have produced from a single-host
index.  Padded slots reuse a real center and carry empty lists, same as
the re-shard path.

Observability: each phase sets the ``raft_tpu_build_phase`` /
``raft_tpu_build_rows_done`` gauges and opens a ``serve.build.<phase>``
span; completion publishes a ``build_complete`` event on the bus.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.comms.comms import Comms, local_comms
from raft_tpu.comms.quantized import quantized_psum, reduce_dtype_from_env
from raft_tpu.core import env as _env
from raft_tpu.core.logger import logger as _log
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.core.trace import trace_range, traced
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC, distance_matrix_tile
from raft_tpu.neighbors._common import padded_width
from raft_tpu.obs import events
from raft_tpu.ops import matrix
from raft_tpu.serve.shard import (
    ShardedIndex,
    _pack_pass_words,
    _place,
    _resolve_cagra_mode,
    merge_dtype_from_env,
)

#: env knob: column-tile rows of the ring kNN exchange (bounds the
#: [my_rows, tile] distance matrix; default = one shard's rows per step)
KNN_BLOCK_ENV = "RAFT_TPU_BUILD_KNN_BLOCK_ROWS"

#: build phases in execution order — the ``raft_tpu_build_phase`` gauge
#: reports the current phase as an index into this tuple
PHASES = (
    "place",      # pad + device_put the training rows across the mesh
    "coarse",     # sharded balanced k-means (ivf_flat / ivf_pq)
    "codebooks",  # sharded per-subspace PQ codebook fit (ivf_pq)
    "encode",     # sharded residual PQ encode (ivf_pq)
    "knn_graph",  # ring-of-ppermute exact kNN graph (cagra)
    "assemble",   # shard-major list assembly into the serving layout
    "finalize",   # graph prune / index construction / placement
)

_BUILD_KINDS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")


@contextlib.contextmanager
def _phase(label: str, name: str):
    """One build phase: gauge + span (``serve.build.<name>``)."""
    obs.default_registry().gauge(
        "raft_tpu_build_phase",
        help="current distributed-build phase (index into serve.build.PHASES)",
    ).set(float(PHASES.index(name)), index=label)
    with trace_range(f"serve.build.{name}"):
        yield


def _rows_done(label: str, n: int) -> None:
    obs.default_registry().gauge(
        "raft_tpu_build_rows_done",
        help="rows the distributed build has processed through its "
        "current phase",
    ).set(float(n), index=label)


def knn_block_rows_from_env(r: int) -> int:
    """Ring-exchange column tile: env override clamped to [8, r]."""
    b = _env.env_int(KNN_BLOCK_ENV)
    if b is None:
        return r
    return int(max(8, min(int(b), r)))


# -- data placement ----------------------------------------------------------

def _place_rows(comms: Comms, data) -> Tuple[np.ndarray, jax.Array, jax.Array, int]:
    """Pad ``data`` to a shard-divisible row count and place it.

    Returns ``(data_np [n_pad, d] host, x_sharded [n_pad, d] P(axis, None),
    weights [n_pad] P(axis) — 1.0 real / 0.0 padding, n_real)``.  Padding
    rows sit at the END of the global id space so every builder can mask
    them with ``gid < n``.
    """
    mesh, axis = comms.mesh, comms.axis
    s_count = comms.get_size()
    data_np = np.asarray(data)
    if data_np.ndim != 2:
        raise ValueError(f"expected [n, dim] training data, got {data_np.shape}")
    n, d = data_np.shape
    if n < s_count:
        raise ValueError(f"need at least one row per shard: n={n} < {s_count}")
    r = -(-n // s_count)
    n_pad = r * s_count
    if n_pad != n:
        data_np = np.concatenate(
            [data_np, np.zeros((n_pad - n, d), data_np.dtype)]
        )
    w = np.zeros((n_pad,), np.float32)
    w[:n] = 1.0
    x_sh = jax.device_put(data_np, NamedSharding(mesh, P(axis, None)))
    w_sh = jax.device_put(w, NamedSharding(mesh, P(axis)))
    return data_np, x_sh, w_sh, n


def _shard_major_relabel(labels: np.ndarray, n_lists: int, s_count: int):
    """Relabel global list ids into the round-robin serving layout.

    Global list ``l`` serves from shard ``l % S``, local slot ``l // S``
    (``_round_robin``); packing labels ``l' = (l % S)·Lp + l // S`` over
    ``S·Lp`` lists makes the flat [S·Lp, ...] assembly reshape directly
    into the per-shard stacks.  Returns ``(relabeled, lp, src)`` where
    ``src[l']`` is the global list backing padded slot ``l'`` (padded
    slots reuse the shard's first real list's center, matching
    ``_partition_lists``).
    """
    lp = -(-n_lists // s_count)
    labels = np.asarray(labels)
    relab = (labels % s_count) * lp + labels // s_count
    flat = np.arange(s_count * lp)
    s_idx, j_idx = flat // lp, flat % lp
    g = s_idx + j_idx * s_count
    src = np.where(g < n_lists, g, s_idx)
    return relab.astype(np.int64), lp, src


def _list_stats(n_lists: int, s_count: int, sizes: np.ndarray):
    """Per-shard (real) list and row counts for ``shard_stats``."""
    lists = [len(range(s, n_lists, s_count)) for s in range(s_count)]
    per_shard = sizes.reshape(s_count, -1)
    return {"lists": lists, "rows": [int(r.sum()) for r in per_shard]}


# -- sharded ring kNN (cagra) ------------------------------------------------

@functools.lru_cache(maxsize=32)
def _ring_knn_program(mesh, axis, s_count: int, n_real: int, k_sel: int,
                      metric: str, block_rows: int):
    """Exact kNN ids over row-sharded data via a ring of ppermute steps.

    Each shard keeps its own rows resident and scores them against the
    visiting block, folding per-tile top-k into a running tie-stable
    merge.  Candidate ids are globalized per visiting block (``owner·r +
    col``), so the merged graph is identical to the single-host exact
    kNN — ties resolve to the smallest global id on every partition
    (partition invariance; tested in test_build_sharded.py).
    """
    select_min = DISTANCE_TYPES[metric] != "inner_product"
    worst = jnp.inf if select_min else -jnp.inf

    def local(x):
        rank = lax.axis_index(axis)
        my = x.astype(jnp.float32)
        r = my.shape[0]
        n_tiles = -(-r // block_rows)
        r_pad = n_tiles * block_rows
        kk = min(k_sel, block_rows)

        vals0 = jnp.full((r, k_sel), worst, jnp.float32)
        gids0 = jnp.full((r, k_sel), -1, jnp.int32)
        blk0 = jnp.pad(my, ((0, r_pad - r), (0, 0)))

        def tile_fold(carry, t, blk, owner):
            vals, gids = carry
            cols = lax.dynamic_slice_in_dim(blk, t * block_rows, block_rows, 0)
            d2 = distance_matrix_tile(my, cols, metric)       # [r, block]
            # mask tile padding (col >= r) and global padding (gi >= n)
            # BEFORE the per-tile select: a zero-padded fake row scores a
            # finite distance and would displace real candidates from the
            # tile's top-k otherwise
            col_all = t * block_rows + jnp.arange(block_rows, dtype=jnp.int32)
            ok_all = (col_all < r) & (owner * r + col_all < n_real)
            d2 = jnp.where(ok_all[None, :], d2, worst)
            v, li = matrix.select_k(d2, kk, select_min=select_min)
            col = t * block_rows + li
            gi = owner * r + col
            ok = (col < r) & (gi < n_real)
            v = jnp.where(ok, v, worst)
            gi = jnp.where(ok, gi, -1)
            return matrix.select_k_stable(
                jnp.concatenate([vals, v], axis=1), k_sel,
                select_min=select_min,
                input_indices=jnp.concatenate([gids, gi], axis=1),
            ), None

        def hop(carry, t):
            vals, gids, blk = carry
            owner = (rank - t) % s_count
            (vals, gids), _ = lax.scan(
                functools.partial(tile_fold, blk=blk, owner=owner),
                (vals, gids), jnp.arange(n_tiles),
            )
            # send my current block one hop around the ring (i -> i+1);
            # after step t every shard holds block (rank - t - 1) % S
            blk = lax.ppermute(
                blk, axis, [(i, (i + 1) % s_count) for i in range(s_count)]
            )
            return (vals, gids, blk), None

        (vals, gids, _), _ = lax.scan(
            hop, (vals0, gids0, blk0), jnp.arange(s_count)
        )
        # drop self (always distance 0 in L2 / max-sim in IP) the same way
        # nn_descent.build_exact does: stable-sort the self column to the
        # end, keep the first k_sel - 1
        myid = rank * r + jnp.arange(r, dtype=jnp.int32)
        self_col = gids == myid[:, None]
        order = jnp.argsort(self_col, axis=1, stable=True)
        gids = jnp.take_along_axis(gids, order, axis=1)[:, : k_sel - 1]
        return gids

    return jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None),),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )


@traced("serve.build.knn_graph")
def knn_graph_sharded(comms: Comms, data, k: int, *, metric: str = "sqeuclidean",
                      block_rows: Optional[int] = None) -> np.ndarray:
    """Exact [n, k] neighbor-id graph (self excluded, rows sorted by
    distance) built with the ring exchange — each row crosses the
    interconnect exactly once."""
    data_np, x_sh, _, n = _place_rows(comms, data)
    r = data_np.shape[0] // comms.get_size()
    if k + 1 > n:
        raise ValueError(f"k={k} needs at least k+1 rows, got n={n}")
    b = block_rows if block_rows is not None else knn_block_rows_from_env(r)
    run = _ring_knn_program(
        comms.mesh, comms.axis, comms.get_size(), n, k + 1, metric, int(b)
    )
    return np.asarray(run(x_sh))[:n]


# -- sharded PQ codebook fit (ivf_pq) ----------------------------------------

@functools.lru_cache(maxsize=32)
def _pq_codebooks_program(mesh, axis, n_iters: int, reduce_dtype: str):
    """Per-subspace Lloyd over sharded rotated residuals: ONE packed
    [pq_dim, k_pq, pq_len+1] sums|counts psum per iteration (optionally
    quantized).  The [r, pq_dim, k_pq] one-hot assignment is bounded by
    the per-shard row count — the point of training sharded."""

    def local(x, labels, w, centers, rotation, cb0):
        x32 = x.astype(jnp.float32)
        resid = jnp.matmul(
            x32 - centers[labels], rotation.T, precision=_PREC
        )
        pq_dim, k_pq, pq_len = cb0.shape
        sub = resid.reshape(resid.shape[0], pq_dim, pq_len)

        def body(cb, _):
            ip = jnp.einsum("njl,jkl->njk", sub, cb, precision=_PREC)
            cb2 = jnp.sum(cb * cb, axis=2)
            codes = jnp.argmin(cb2[None] - 2.0 * ip, axis=2)   # [r, pq_dim]
            hot = jax.nn.one_hot(codes, k_pq, dtype=jnp.float32)
            hot = hot * w[:, None, None]
            sums = jnp.einsum("njk,njl->jkl", hot, sub, precision=_PREC)
            counts = jnp.sum(hot, axis=0)                      # [pq_dim, k_pq]
            packed = jnp.concatenate([sums, counts[..., None]], axis=-1)
            packed = quantized_psum(packed, axis, reduce_dtype)
            g_sums = packed[..., :pq_len]
            g_counts = packed[..., pq_len]
            cb = jnp.where(
                g_counts[..., None] > 0.0,
                g_sums / jnp.maximum(g_counts, 1.0)[..., None],
                cb,
            )
            return cb, None

        cb, _ = lax.scan(body, cb0.astype(jnp.float32), None, length=n_iters)
        return cb

    return jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(
                P(axis, None), P(axis), P(axis),
                P(None, None), P(None, None), P(None, None, None),
            ),
            out_specs=P(None, None, None),
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=32)
def _encode_program(mesh, axis, codebook_kind: str):
    """Shard-local residual PQ encode — rows never leave their shard;
    only the pq_dim-byte codes are staged out for assembly."""
    from raft_tpu.neighbors import ivf_pq

    def local(x, labels, centers, centers_rot, rotation, codebook):
        return ivf_pq._encode(
            rotation, centers, centers_rot, codebook,
            x.astype(jnp.float32), labels, codebook_kind,
        )

    return jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(
                P(axis, None), P(axis), P(None, None), P(None, None),
                P(None, None), P(None, None, None),
            ),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )


def _seed_subsample(key, data_np: np.ndarray, n: int, n_sub: int):
    """Replicated seeding rows: a with-replacement draw from the REAL
    rows (padding excluded by construction — ids < n)."""
    idx = np.asarray(
        jax.random.randint(key, (min(n, n_sub),), 0, n)
    )
    return jnp.asarray(data_np[idx], jnp.float32)


# -- per-kind builders -------------------------------------------------------

def _build_rows_sharded(comms, kind, data_np, x_sh, n, metric, merge_dtype,
                        label, params, res, search_params=None,
                        cagra_mode="env"):
    """brute_force / cagra: the serving layout IS the training layout —
    contiguous row blocks with global arange ids.  cagra additionally
    builds its pruned search graph from the ring kNN graph; with
    ``cagra_mode="graph"`` the build emits the partitioned-graph serving
    layout (:class:`~raft_tpu.serve.graph_shard.GraphShardedIndex`)
    directly from that graph instead of the brute-refine row blocks."""
    s_count = comms.get_size()
    n_pad, d = data_np.shape
    r = n_pad // s_count

    graph = None
    if kind == "cagra":
        from raft_tpu.neighbors import cagra

        params = params if params is not None else cagra.IndexParams()
        metric = params.metric
        inter = min(int(params.intermediate_graph_degree), n - 1)
        with _phase(label, "knn_graph"):
            knn = knn_graph_sharded(comms, data_np[:n], inter, metric=metric)
            _rows_done(label, n)
        with _phase(label, "finalize"):
            degree = min(int(params.graph_degree), inter)
            graph = np.asarray(
                cagra.optimize(jnp.asarray(knn, jnp.int32), degree, res=res)
            )
        if _resolve_cagra_mode(cagra_mode) == "graph":
            from raft_tpu.serve.graph_shard import GraphShardedIndex

            with _phase(label, "assemble"):
                # partitioned-graph serving layout straight from the ring
                # kNN graph: entry-point table + a transient single-host
                # Index shell, cluster-cut and halo'd by _shard_graph
                dataset = jnp.asarray(data_np[:n])
                canonical = DISTANCE_TYPES[metric]
                n_entries = params.entry_points
                if n_entries is None:
                    n_entries = cagra._auto_entry_points(n)
                n_entries = min(n_entries, n)
                entry_centers = entry_ids = None
                if n_entries:
                    entry_centers, entry_ids = cagra._build_entry_points(
                        dataset, n_entries, canonical, params.seed, res
                    )
                tmp = cagra.Index(
                    metric, dataset, jnp.asarray(graph, jnp.int32),
                    entry_centers, entry_ids,
                )
                index = GraphShardedIndex._shard_graph(
                    comms, tmp, None, search_params, merge_dtype, label
                )
                _rows_done(label, n)
            # the pruned graph stays a build artifact for single-device
            # consumers (cagra.from_graph), same as the brute layout below
            index.cagra_graph = graph
            return index

    with _phase(label, "assemble"):
        ids = np.full((s_count, r), -1, np.int32)
        words = np.zeros(
            (s_count, _pack_pass_words(np.ones(r, bool)).shape[0]), np.uint32
        )
        row_counts = []
        for s in range(s_count):
            lo, hi = s * r, min((s + 1) * r, n)
            m = max(hi - lo, 0)
            if m > 0:
                ids[s, :m] = np.arange(lo, hi, dtype=np.int32)
            passes = np.zeros((r,), bool)
            passes[:m] = True
            words[s] = _pack_pass_words(passes)
            row_counts.append(m)
        mesh, axis = comms.mesh, comms.axis
        rows = jax.device_put(
            data_np.reshape(s_count, r, d),
            NamedSharding(mesh, P(axis, None, None)),
        )
        parts, specs = _place(
            comms, sharded={"ids": ids, "pass_words": words}, replicated={}
        )
        parts["rows"] = rows
        specs["rows"] = P(axis, None, None)
        _rows_done(label, n)

    index = ShardedIndex(
        comms, kind, metric, d, n, parts, specs,
        merge_dtype=merge_dtype, label=label,
        shard_stats={"rows": row_counts},
    )
    if graph is not None:
        # the pruned CAGRA search graph: sharded serving runs the
        # row-partitioned brute fallback (same as from_index), but the
        # graph is the build artifact single-device consumers feed to
        # cagra.from_graph
        index.cagra_graph = graph
    return index


def _build_ivf_flat_sharded(comms, data_np, x_sh, w_sh, n, params,
                            search_params, merge_dtype, reduce_dtype, label,
                            res):
    from raft_tpu.neighbors import ivf_flat

    params = params if params is not None else ivf_flat.IndexParams()
    canonical = DISTANCE_TYPES[params.metric]
    if canonical not in ("sqeuclidean", "euclidean", "inner_product", "cosine"):
        raise ValueError(
            f"ivf_flat supports L2/IP/cosine metrics, got {params.metric}"
        )
    s_count = comms.get_size()
    d = data_np.shape[1]

    with _phase(label, "coarse"):
        kb_metric = (
            canonical if canonical in ("cosine", "inner_product")
            else "sqeuclidean"
        )
        kb = kmeans_balanced.KMeansBalancedParams(
            n_iters=params.kmeans_n_iters, metric=kb_metric, seed=params.seed
        )
        centers, labels_sh = kmeans_balanced.fit_sharded(
            comms, kb, x_sh, params.n_lists, sample_weights=w_sh,
            reduce_dtype=reduce_dtype, res=res,
        )
        labels = np.asarray(labels_sh)[:n]
        _rows_done(label, n)

    with _phase(label, "assemble"):
        relab, lp, src = _shard_major_relabel(labels, params.n_lists, s_count)
        l_data, l_index, sizes, l_norms, center_map = ivf_flat._pack_lists(
            data_np[:n], np.arange(n, dtype=np.int32), relab,
            s_count * lp, params.metric,
            headroom=not params.conservative_memory_allocation,
            max_cap=None,
        )
        centers_np = np.asarray(centers)[src]           # [S*Lp, d]
        cap = l_data.shape[1]
        sharded = {
            "centers": centers_np.reshape(s_count, lp, d),
            "list_data": l_data.reshape(s_count, lp, cap, d),
            "list_index": l_index.reshape(s_count, lp, cap),
            "list_sizes": sizes.reshape(s_count, lp),
            "list_norms": l_norms.reshape(s_count, lp, cap),
        }
        stats = _list_stats(params.n_lists, s_count, np.asarray(sizes))
        _rows_done(label, n)

    with _phase(label, "finalize"):
        parts, specs = _place(comms, sharded=sharded, replicated={})
    return ShardedIndex(
        comms, "ivf_flat", params.metric, d, n, parts, specs,
        search_params=(
            search_params if search_params is not None
            else ivf_flat.SearchParams()
        ),
        merge_dtype=merge_dtype, label=label, shard_stats=stats,
    )


def _build_ivf_pq_sharded(comms, data_np, x_sh, w_sh, n, params,
                          search_params, merge_dtype, reduce_dtype, label,
                          res):
    from raft_tpu.neighbors import ivf_pq

    params = params if params is not None else ivf_pq.IndexParams()
    canonical = DISTANCE_TYPES[params.metric]
    if canonical not in ("sqeuclidean", "euclidean", "inner_product"):
        raise ValueError(f"ivf_pq supports L2/IP metrics, got {params.metric}")
    if not (4 <= params.pq_bits <= 8):
        raise ValueError(f"pq_bits must be in [4, 8], got {params.pq_bits}")
    s_count = comms.get_size()
    d = data_np.shape[1]
    pq_dim = params.pq_dim or ivf_pq._auto_pq_dim(d)
    pq_len = max(1, (d + pq_dim - 1) // pq_dim)
    rot_dim = pq_dim * pq_len
    k_pq = 1 << params.pq_bits
    key = jax.random.PRNGKey(params.seed)
    _, k_rot, k_cb = jax.random.split(key, 3)

    with _phase(label, "coarse"):
        kb_metric = (
            "inner_product" if canonical == "inner_product" else "sqeuclidean"
        )
        kb = kmeans_balanced.KMeansBalancedParams(
            n_iters=params.kmeans_n_iters, metric=kb_metric, seed=params.seed
        )
        centers, labels_sh = kmeans_balanced.fit_sharded(
            comms, kb, x_sh, params.n_lists, sample_weights=w_sh,
            reduce_dtype=reduce_dtype, res=res,
        )
        rotation = ivf_pq.make_rotation_matrix(
            k_rot, rot_dim, d, params.force_random_rotation
        )
        centers_rot = jnp.matmul(centers, rotation.T, precision=_PREC)
        _rows_done(label, n)

    with _phase(label, "codebooks"):
        # replicated seeding subsample (rows travel once, ~8·k_pq of them),
        # then the full sharded refine — every iteration one packed psum
        n_sub = min(n, max(8 * k_pq, 4096))
        x_sub = _seed_subsample(jax.random.fold_in(k_cb, 1), data_np, n, n_sub)
        lab_sub = kmeans_balanced.predict(
            centers, x_sub, metric=kb_metric, res=res
        )
        resid_sub = jnp.matmul(
            x_sub - centers[lab_sub], rotation.T, precision=_PREC
        )
        if params.codebook_kind == ivf_pq.CODEBOOK_PER_SUBSPACE:
            sub_t = jnp.transpose(
                resid_sub.reshape(-1, pq_dim, pq_len), (1, 0, 2)
            )
            cb0 = ivf_pq._train_codebooks_lloyd(k_cb, sub_t, k_pq, 2)
            refine = _pq_codebooks_program(
                comms.mesh, comms.axis, 25, reduce_dtype
            )
            codebook = refine(x_sh, labels_sh, w_sh, centers, rotation, cb0)
        elif params.codebook_kind == ivf_pq.CODEBOOK_PER_CLUSTER:
            # per_cluster wants one k-means per LIST — n_lists independent
            # small problems that gain nothing from a cross-shard reduce;
            # train them on the replicated residual subsample (the
            # single-host build subsamples here too)
            codebook = _per_cluster_codebooks(
                k_cb, resid_sub, np.asarray(lab_sub), params.n_lists,
                k_pq, pq_len, pq_dim,
            )
        else:
            raise ValueError(f"unknown codebook_kind {params.codebook_kind}")

    with _phase(label, "encode"):
        enc = _encode_program(comms.mesh, comms.axis, params.codebook_kind)
        codes_sh = enc(x_sh, labels_sh, centers, centers_rot, rotation, codebook)
        # compressed stream off the mesh: pq_dim bytes/row + the labels —
        # the DCN all-to-all stand-in (rows themselves never move)
        codes = np.asarray(codes_sh)[:n]
        labels = np.asarray(labels_sh)[:n]
        _rows_done(label, n)

    with _phase(label, "assemble"):
        relab, lp, src = _shard_major_relabel(labels, params.n_lists, s_count)
        centers_rot_np = np.asarray(centers_rot)[src]
        cb_assemble = codebook
        if params.codebook_kind == ivf_pq.CODEBOOK_PER_CLUSTER:
            cb_assemble = jnp.asarray(np.asarray(codebook)[src])
        dec_dtype = _resolve_decoded_dtype(params, n, rot_dim, pq_dim)
        l_codes, l_index, sizes, l_data, l_y2, _, scale = ivf_pq._assemble_lists(
            codes, np.arange(n, dtype=np.int32), relab, s_count * lp,
            cb_assemble, params.codebook_kind, centers_rot_np, dec_dtype,
            headroom=not params.conservative_memory_allocation,
            max_cap=None,
        )
        cap = l_codes.shape[1]
        sharded = {
            "centers": np.asarray(centers)[src].reshape(s_count, lp, d),
            "centers_rot": centers_rot_np.reshape(s_count, lp, rot_dim),
            "list_codes": l_codes.reshape(s_count, lp, cap, pq_dim),
            "list_index": l_index.reshape(s_count, lp, cap),
            "list_sizes": sizes.reshape(s_count, lp),
            "list_data": l_data.reshape(s_count, lp, cap, l_data.shape[-1]),
            "list_y2": l_y2.reshape(s_count, lp, cap),
        }
        replicated = {"rotation": np.asarray(rotation)}
        if params.codebook_kind == ivf_pq.CODEBOOK_PER_CLUSTER:
            sharded["codebook"] = np.asarray(cb_assemble).reshape(
                s_count, lp, k_pq, pq_len
            )
        else:
            replicated["codebook"] = np.asarray(codebook)
        stats = _list_stats(params.n_lists, s_count, np.asarray(sizes))
        _rows_done(label, n)

    with _phase(label, "finalize"):
        parts, specs = _place(comms, sharded=sharded, replicated=replicated)
    index = ShardedIndex(
        comms, "ivf_pq", params.metric, d, n, parts, specs,
        search_params=(
            search_params if search_params is not None
            else ivf_pq.SearchParams()
        ),
        merge_dtype=merge_dtype, label=label, shard_stats=stats,
    )
    index._pq_meta = (params.codebook_kind, int(params.pq_bits), float(scale))
    return index


def _per_cluster_codebooks(key, resid, labels, n_lists, k_pq, pq_len, pq_dim):
    """Pooled per-cluster codebook training on a replicated residual
    subsample (mirrors ivf_pq.build's counting-sort pooling)."""
    from raft_tpu.neighbors import ivf_pq

    flat = np.asarray(resid).reshape(-1, pq_len)
    lab2 = np.repeat(labels, pq_dim)
    counts = np.bincount(lab2, minlength=n_lists)
    cap = max(int(counts.max()) if counts.size else 1, k_pq)
    cap = min(cap, max(8 * k_pq, 2048))
    order = np.argsort(lab2, kind="stable")
    starts = np.cumsum(counts) - counts
    within = np.arange(len(lab2)) - starts[lab2[order]]
    keep = within < cap
    pooled = np.zeros((n_lists, cap, pq_len), np.float32)
    wts = np.zeros((n_lists, cap), np.float32)
    pooled[lab2[order][keep], within[keep]] = flat[order][keep]
    wts[lab2[order][keep], within[keep]] = 1.0
    return ivf_pq._train_codebooks_lloyd(
        key, jnp.asarray(pooled), k_pq, 25, jnp.asarray(wts)
    )


def _resolve_decoded_dtype(params, n, rot_dim, pq_dim):
    """The single-host build's decoded-dtype ladder, shared verbatim:
    bf16 unless the projected cache exceeds a REAL device limit."""
    from raft_tpu.neighbors import ivf_pq

    decoded = params.decoded_dtype
    if decoded == "auto":
        est_rows = int(n * 1.35) + 8 * params.n_lists
        bf16_bytes = est_rows * (padded_width(rot_dim) * 2 + pq_dim + 8)
        total, limit_is_real = ivf_pq._device_memory_budget()
        budget = int(ivf_pq._AUTO_HBM_FRACTION * total)
        decoded = "int8" if bf16_bytes > budget and limit_is_real else "bfloat16"
    if decoded not in ivf_pq._DECODED_DTYPES:
        raise ValueError(f"unknown decoded_dtype {decoded!r}")
    return ivf_pq._DECODED_DTYPES[decoded]


# -- entry point -------------------------------------------------------------

@traced("serve.build")
def build_sharded(
    kind: str,
    data,
    comms: Optional[Comms] = None,
    *,
    n_devices: Optional[int] = None,
    index_params=None,
    search_params=None,
    metric: str = "sqeuclidean",
    merge_dtype="env",
    reduce_dtype: Optional[str] = None,
    label: str = "",
    cagra_mode: str = "env",
    res: Optional[Resources] = None,
) -> ShardedIndex:
    """Build a :class:`ShardedIndex` of ``kind`` with the training data
    row-sharded across ``comms``'s mesh axis.

    ``data`` may be a host array (placed here, padded to a
    shard-divisible row count with zero-weight rows) or an already
    mesh-sharded ``[n, dim]`` array.  ``index_params`` is the backend's
    ``IndexParams`` (``metric`` is only read for brute_force, which has
    none).  ``reduce_dtype`` quantizes the per-iteration training
    collectives (default: ``RAFT_TPU_BUILD_REDUCE_DTYPE``);
    ``merge_dtype`` is the *serving* merge knob, same as
    ``ShardedIndex.from_index``.

    The result is already in serving layout — register it and hot-swap
    through ``IndexRegistry`` like any re-sharded index; ``Compactor``
    uses it as its distributed rebuild leg
    (:meth:`raft_tpu.serve.compactor.Compactor.rebuild_sharded`).

    ``cagra_mode`` picks the CAGRA serving layout the build emits:
    ``"brute"`` (row-partitioned brute refine — exact), ``"graph"``
    (partitioned graph traversal with halo frontiers, built directly
    from the ring kNN graph), or ``"env"`` (``RAFT_TPU_SHARD_CAGRA``).
    """
    if kind not in _BUILD_KINDS:
        raise ValueError(
            f"unsupported index kind {kind!r}; expected one of {_BUILD_KINDS}"
        )
    comms = comms if comms is not None else local_comms(n_devices)
    if merge_dtype == "env":
        merge_dtype = merge_dtype_from_env()
    if reduce_dtype is None:
        reduce_dtype = reduce_dtype_from_env()
    res = ensure(res)
    lbl = label or f"{kind}-sharded"
    t0 = time.perf_counter()

    with _phase(lbl, "place"):
        data_np, x_sh, w_sh, n = _place_rows(comms, data)

    if kind in ("brute_force", "cagra"):
        index = _build_rows_sharded(
            comms, kind, data_np, x_sh, n, metric, merge_dtype, lbl,
            index_params, res, search_params=search_params,
            cagra_mode=cagra_mode,
        )
    elif kind == "ivf_flat":
        index = _build_ivf_flat_sharded(
            comms, data_np, x_sh, w_sh, n, index_params, search_params,
            merge_dtype, reduce_dtype, lbl, res,
        )
    else:
        index = _build_ivf_pq_sharded(
            comms, data_np, x_sh, w_sh, n, index_params, search_params,
            merge_dtype, reduce_dtype, lbl, res,
        )

    wall = time.perf_counter() - t0
    events.publish(
        "build_complete",
        reason=f"distributed {kind} build",
        index=lbl, index_kind=kind, rows=n, shards=comms.get_size(),
        seconds=round(wall, 4), reduce_dtype=reduce_dtype,
    )
    _log.info(
        "build_sharded: kind=%s n=%d dim=%d shards=%d reduce=%s %.3fs",
        kind, n, data_np.shape[1], comms.get_size(), reduce_dtype, wall,
    )
    return index
