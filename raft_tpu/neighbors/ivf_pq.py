"""IVF-PQ: inverted-file index with product-quantized residual vectors.

Reference surface: build/extend/search/serialize with hierarchical balanced
k-means coarse quantizer, optional random rotation, per-subspace or
per-cluster PQ codebooks (ref: cpp/include/raft/neighbors/ivf_pq_types.hpp:47-172
— ``pq_bits`` 4..8, ``pq_dim``, ``codebook_gen`` :42, ``n_probes``,
``lut_dtype``; build pipeline neighbors/detail/ivf_pq_build.cuh:1681-1836:
trainset subsample → kmeans_balanced::fit → predict → make_rotation_matrix:122
→ set_centers:317 → train_per_subset:395 / train_per_cluster:473 →
extend:1501; search pipeline neighbors/detail/ivf_pq_search.cuh:588-718:
select_clusters = GEMM + select_k, then per-probe LUT build +
compute_similarity scan + select_k; Python ref: pylibraft ivf_pq.pyx:312-748).

TPU re-design
-------------
* **Storage**: the reference packs pq_bits-wide codes into interleaved bit
  fields scanned warp-style (ivf_pq_build.cuh process_and_fill_codes:1323).
  On TPU the natural unit is the int8 VPU lane: codes live *unpacked* one
  byte per sub-quantizer in a dense padded tensor
  ``list_codes [n_lists, cap, pq_dim] uint8`` — every probe scan is then a
  static-shape gather + vectorized LUT lookup, no bit twiddling on the
  critical path. (pq_bits still bounds the codebook size 2**pq_bits, and a
  packed serialization keeps files small for pq_bits<8.)
* **Decoded-reconstruction scoring**: the reference's per-(query,probe) LUT
  gather (compute_similarity's shmem scan,
  ivf_pq_compute_similarity-inl.cuh) is a scalar-gather pattern the TPU
  cannot vectorize — measured 12.4 s of a 12.7 s search on a v5e chip for
  1k queries. Instead the index stores, next to the codes, the *decoded*
  reconstruction of every vector in rotated space
  (``list_data [L, cap, rot_dim]``, bf16 by default):
  ``y = center_rot + concat_j codebook[j, code_j]``. Scoring is then
  ``‖q_rot − y‖² = ‖y‖² − 2·q_rot·y + ‖q_rot‖²`` — one MXU matmul per
  query tile over gathered probe rows, identical scores to the LUT
  formulation (Σ_j ‖res_j − cb_j‖² telescopes to ‖res − dec‖²). Memory:
  2·rot_dim bytes/vector (bf16) vs the reference's fp16 LUT path — the
  same accuracy class, with codes kept packed for serialization parity.
* **Rotation**: random orthonormal (QR of gaussian), padding dim up to
  rot_dim = pq_dim*pq_len like make_rotation_matrix (ivf_pq_build.cuh:122).
* **Codebook training**: per-subspace Lloyd iterations vmapped over all
  pq_dim subspaces at once — one compiled kernel trains every codebook
  (reference loops subspaces on separate streams, train_per_subset:395).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dc_replace
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core import serialize as ser
from raft_tpu.core import validation
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.resources import Resources, ensure
from raft_tpu.distance.pairwise import DISTANCE_TYPES, _PREC
from raft_tpu.neighbors._common import (
    allocate_append_slots,
    centroid_group_inverse,
    compute_list_layout,
    subsample_trainset,
    coarse_select,
    default_max_cap,
    invalid_mask,
    invalid_mask_rows,
    lane_pad,
    merge_split_lists,
    padded_width,
    pallas_scan_enabled,
    run_probe_major,
    run_query_tiled,
    select_scan_strategy,
    unpack_lists,
)
from raft_tpu.kernels import stamp_kernel_path as _stamp_kernel_path
from raft_tpu.kernels.toolkit import int8_scored_ip, quantize_queries_i8
from raft_tpu.ops.matrix import select_k
from raft_tpu.store.paged import gather_lists as _gather_lists
from raft_tpu.core.trace import traced
from raft_tpu.core.logger import logger as _log

_SERIALIZATION_VERSION = 1

CODEBOOK_PER_SUBSPACE = "per_subspace"
CODEBOOK_PER_CLUSTER = "per_cluster"

#: scan-cache storage dtypes (the lut_dtype accuracy ladder analog,
#: ref ivf_pq_types.hpp:139-172): bf16 = HBM-halving default, f32 = exact
#: decode, int8 = memory-lean quantized cache (rot_dim bytes/vector).
_DECODED_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "int8": jnp.int8,
}

#: fraction of device memory the scan cache may claim before "auto"
#: downgrades bf16 → int8 (leaves room for queries, probe gathers, and the
#: decode chunk)
_AUTO_HBM_FRACTION = 0.55



def _device_memory_budget() -> tuple[int, bool]:
    """Bytes of accelerator memory to plan against, and whether that number
    is a *real* reported limit (TPU/GPU ``memory_stats`` or the
    ``RAFT_TPU_HBM_BYTES`` override) as opposed to the 16 GiB (one v5e
    chip) assumption used when the backend reports nothing (e.g. CPU)."""
    from raft_tpu.core import env as _env

    hbm = _env.env_int("RAFT_TPU_HBM_BYTES")
    if hbm is not None:
        return hbm, True
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"]), True
    except Exception:
        pass
    return 16 << 30, False

#: HBM budget for the f32 intermediates of one decode chunk (the decode is
#: chunked over lists so huge indexes — the int8 mode's reason to exist —
#: never materialize a full f32 copy of themselves).
_DECODE_CHUNK_BYTES = 256 << 20


@dataclass
class IndexParams:
    """(ref: ivf_pq_types.hpp:47-139 index_params)"""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8          # 4..8 (ref :55)
    pq_dim: int = 0           # 0 → auto: dim/4 rounded up to 8 (ref :64)
    codebook_kind: str = CODEBOOK_PER_SUBSPACE  # ref codebook_gen :42
    force_random_rotation: bool = False
    add_data_on_build: bool = True
    conservative_memory_allocation: bool = False
    seed: int = 0
    # dtype of the decoded scan cache (the fp16-LUT accuracy-class analog,
    # ref search_params::lut_dtype ivf_pq_types.hpp:139-172): "bfloat16"
    # halves scan HBM traffic; "float32" is exact decode; "int8" is the
    # memory-lean quantized cache (rot_dim B/vector). "auto" (default)
    # picks bf16 unless the projected index footprint exceeds the device
    # memory budget (_device_memory_budget), then drops to int8 — so
    # DEEP-100M-shape builds fit a 16 GB chip without manual tuning.
    decoded_dtype: str = "auto"


@dataclass
class SearchParams:
    """(ref: ivf_pq_types.hpp:139-172 search_params)

    ``strategy`` selects the scan schedule (the analog of the reference's
    compute_similarity kernel-variant choice):

    - ``query_major`` — per query-tile, gather the rows of its probed
      lists and score them (one batched MXU contraction). HBM reads each
      list once per *probing query*.
    - ``probe_major`` — invert the (query, probe) relation: sort pairs by
      list, bucket each list's probing queries, and scan list-by-list, so
      each list's rows stream from HBM once per *bucket* (~once per
      batch) instead of once per query — the SURVEY §7 "probe-major
      batching" answer to data-dependent gathers. Per-list top-k partials
      are scattered back and merged per query.
    - ``auto`` — probe_major when the batch reuses lists heavily
      (q·n_probes ≫ n_lists and q is large), else query_major.
    """

    n_probes: int = 20
    lut_dtype: str = "float32"                 # float32 | bfloat16 (ref fp8/half analog)
    internal_distance_dtype: str = "float32"   # float32 | bfloat16
    strategy: str = "auto"                     # auto | query_major | probe_major


@dataclass(frozen=True)
class EffortSpec:
    """Typed search-effort knobs for IVF-PQ (see ivf_flat.EffortSpec for
    the contract): ``n_probes`` + ``lut_dtype`` actuate online through
    SearchParams; ``refine_ratio`` is the offline sweep's exact-refine
    multiplier.  Knob values select among warmed executables — they never
    ride as static jit arguments."""

    n_probes: int = 20
    refine_ratio: int = 1
    lut_dtype: str = "float32"

    backend: ClassVar[str] = "ivf_pq"

    @classmethod
    def from_params(cls, params: Optional[SearchParams] = None,
                    **extra) -> "EffortSpec":
        base = params if params is not None else SearchParams()
        return cls(n_probes=int(base.n_probes),
                   refine_ratio=int(extra.get("refine_ratio", 1)),
                   lut_dtype=str(base.lut_dtype))

    def apply(self, params: Optional[SearchParams] = None) -> SearchParams:
        base = params if params is not None else SearchParams()
        return dc_replace(base, n_probes=int(self.n_probes),
                          lut_dtype=str(self.lut_dtype))

    def degraded(self, level: int) -> "EffortSpec":
        """Step down ``level`` notches: halve ``n_probes`` per level
        (floor 1), drop the LUT to bf16 at level ≥ 2 (the cheapest-scan
        analog of disabling refine), drop refine."""
        if level <= 0:
            return self
        return EffortSpec(
            n_probes=max(1, int(self.n_probes) >> int(level)),
            refine_ratio=1,
            lut_dtype="bfloat16" if level >= 2 else str(self.lut_dtype),
        )

    def knobs(self):
        return {"n_probes": int(self.n_probes),
                "refine_ratio": int(self.refine_ratio),
                "lut_dtype": str(self.lut_dtype)}


def _auto_pq_dim(dim: int) -> int:
    # ref ivf_pq_types.hpp:123 from_dataset: dim/4 rounded, here rounded up to
    # a multiple of 8 so rot_dim tiles the VPU sublane.
    v = max(1, dim // 4)
    return (v + 7) // 8 * 8 if v > 8 else v


class Index:
    """IVF-PQ index with padded per-list code storage + decoded scan cache.

    Fields:
      centers      [L, dim]  f32        — coarse centroids (original space)
      centers_rot  [L, rot_dim] f32     — rotated centroids
      rotation     [rot_dim, dim] f32   — orthonormal rows
      codebook     per_subspace: [pq_dim, 2**pq_bits, pq_len] f32
                   per_cluster:  [L, 2**pq_bits, pq_len] f32
      list_codes   [L, cap, pq_dim] uint8 — device-resident (streamed
                   assemble + O(appended) fast-extend scatters); not on
                   the scan path but counted in the HBM budget (the
                   "+ pq_dim" term of the auto-dtype projection)
      list_data    [L, cap, padded_width(rot_dim)] bf16/f32/int8 — decoded
                   reconstructions (center_rot + codebook decode), the
                   search scan target; zero lanes past rot_dim make each
                   row whole 128-lane tiles (_common.lane_pad), so the
                   array's stored TPU layout is the one the scans read
      list_y2      [L, cap] f32 — ‖reconstruction‖² (from the stored dtype)
      list_index   [L, cap] int32 (-1 past size)
      list_sizes   [L] int32
    """

    def __init__(
        self, metric, codebook_kind, pq_bits, centers, centers_rot, rotation,
        codebook, list_codes, list_index, list_sizes, list_data, list_y2,
        scan_scale: float = 1.0,
        headroom: bool = True,
    ):
        self.metric = metric
        self.codebook_kind = codebook_kind
        self.pq_bits = pq_bits
        self.centers = centers
        self.centers_rot = centers_rot
        self.rotation = rotation
        self.codebook = codebook
        self.list_codes = list_codes
        self.list_index = list_index
        self.list_sizes = list_sizes
        self.list_data = list_data
        self.list_y2 = list_y2
        # dequantization scale of an int8 scan cache (1.0 for float caches)
        self.scan_scale = scan_scale
        # list growth headroom policy (False under
        # conservative_memory_allocation; serialized like the reference's
        # conservative_memory_allocation flag, ivf_pq_serialize.cuh:64)
        self.headroom = headroom
        # cached centroid→group map for repeated fast appends (derived)
        self._group_inverse = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.list_codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def pq_n_centers(self) -> int:
        return 1 << self.pq_bits

    @property
    def list_cap(self) -> int:
        return self.list_codes.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))


def make_rotation_matrix(
    key: jax.Array, rot_dim: int, dim: int, force_random: bool
) -> jax.Array:
    """Orthonormal [rot_dim, dim]: random QR when forced or when padding is
    needed, else identity (ref: ivf_pq_build.cuh make_rotation_matrix:122)."""
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    if not force_random:
        # norm-preserving zero-padded identity
        return jnp.eye(rot_dim, dim, dtype=jnp.float32)
    if rot_dim <= dim:
        g = jax.random.normal(key, (dim, rot_dim), jnp.float32)
        q, _ = jnp.linalg.qr(g)  # orthonormal columns
        return q.T
    # rot_dim > dim: orthonormal columns of [rot_dim, dim]
    g = jax.random.normal(key, (rot_dim, dim), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q


#: row-chunk budget for one Lloyd distance block across all S subspace
#: problems: [S, chunk, n_centers] f32 stays ≤ this many bytes. Without the
#: chunking the vmapped iteration materializes [S, n, 256] f32 — 24 GB at
#: the 1M build's 500k trainset (measured, benchmarks/rss_trace.py) and
#: ~98 GB at the 10M build's 2M trainset, past any HBM.
_LLOYD_BLOCK_BYTES = 512 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("n_centers", "n_iters"))
def _train_codebooks_lloyd(key, subvecs, n_centers: int, n_iters: int,
                           weights=None):
    """Batched Lloyd over S independent subspace problems.

    subvecs: [S, n, pq_len], weights: optional [S, n] (0 ⇒ row is padding and
    contributes nothing). Returns [S, n_centers, pq_len]. vmapped so all
    pq_dim (or n_lists) codebooks train in one XLA program
    (ref: train_per_subset ivf_pq_build.cuh:395 / train_per_cluster :473,
    which run a kmeans per subspace on residual slices).

    The assignment step is chunked over trainset rows (lax.scan over
    [chunk]-row blocks accumulating weighted sums/counts), bounding the
    distance block at ``_LLOYD_BLOCK_BYTES`` regardless of trainset size —
    DEEP-scale builds train their codebooks without an O(S·n·k) tensor."""
    S, n, L = subvecs.shape
    if weights is None:
        weights = jnp.ones((S, n), subvecs.dtype)

    # weight-proportional seed draw, over the UNPADDED rows so the result
    # is bit-invariant to the chunk size chosen below
    def draw(key, x, w):
        idx = jax.random.choice(
            key, n, shape=(n_centers,), replace=n < n_centers,
            p=w / jnp.maximum(jnp.sum(w), 1e-12),
        )
        return x[idx]

    keys = jax.random.split(key, S)
    centers_init = jax.vmap(draw)(keys, subvecs, weights)

    # pad rows to a chunk multiple with weight-0 rows (weightless rows
    # cannot influence sums/counts)
    chunk = int(np.clip(_LLOYD_BLOCK_BYTES // (4 * S * n_centers), 256, n))
    n_pad = (-n) % chunk
    if n_pad:
        subvecs = jnp.pad(subvecs, ((0, 0), (0, n_pad), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, n_pad)))
    n_chunks = (n + n_pad) // chunk

    def one(centers0, x, w):
        xc = x.reshape(n_chunks, chunk, L)
        wc = w.reshape(n_chunks, chunk)

        def body(centers, _):
            c2 = jnp.sum(centers * centers, 1)[None, :]

            def block(carry, xw):
                sums, counts = carry
                xb, wb = xw
                d2 = c2 - 2.0 * jnp.matmul(xb, centers.T, precision=_PREC)
                labels = jnp.argmin(d2, axis=1)
                sums = sums + jax.ops.segment_sum(
                    xb * wb[:, None], labels, num_segments=n_centers
                )
                counts = counts + jax.ops.segment_sum(wb, labels, n_centers)
                return (sums, counts), None

            (sums, counts), _ = lax.scan(
                block,
                (jnp.zeros((n_centers, L), x.dtype), jnp.zeros((n_centers,), x.dtype)),
                (xc, wc),
            )
            new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-12), centers)
            return new, None

        centers, _ = lax.scan(body, centers0, None, length=n_iters)
        return centers

    return jax.vmap(one)(centers_init, subvecs, weights)


@functools.partial(jax.jit, static_argnames=("codebook_kind",))
def _encode(rotation, centers, centers_rot, codebook, x, labels, codebook_kind):
    """Residual-encode rows → uint8 codes [n, pq_dim]
    (ref: process_and_fill_codes ivf_pq_build.cuh:1323)."""
    rot_dim = rotation.shape[0]
    res = x - centers[labels]                       # [n, dim]
    res_rot = jnp.matmul(res, rotation.T, precision=_PREC)  # [n, rot_dim]
    if codebook_kind == CODEBOOK_PER_SUBSPACE:
        pq_dim, k, pq_len = codebook.shape
        sub = res_rot.reshape(-1, pq_dim, pq_len)   # [n, j, l]
        # ||sub - cb||² argmin over k: −2·ip + ||cb||²  (‖sub‖² is rank-neutral)
        ip = jnp.einsum("njl,jkl->njk", sub, codebook, precision=_PREC)
        cb2 = jnp.sum(codebook * codebook, axis=2)  # [j, k]
        codes = jnp.argmin(cb2[None] - 2.0 * ip, axis=2)
    else:
        n_lists, k, pq_len = codebook.shape
        pq_dim = rot_dim // pq_len
        sub = res_rot.reshape(-1, pq_dim, pq_len)
        cb = codebook[labels]                       # [n, k, l]
        ip = jnp.einsum("njl,nkl->njk", sub, cb, precision=_PREC)
        cb2 = jnp.sum(cb * cb, axis=2)              # [n, k]
        codes = jnp.argmin(cb2[:, None, :] - 2.0 * ip, axis=2)
    return codes.astype(jnp.uint8)


def _decode_lists(
    codebook: np.ndarray,
    codebook_kind: str,
    centers_rot: np.ndarray,
    list_codes: np.ndarray,
    list_index: np.ndarray,
    dtype,
) -> Tuple[jax.Array, jax.Array, float]:
    """Host-side decode of packed lists → (list_data [L, cap,
    padded_width(rot)] dtype, list_y2 [L,cap] f32, scan_scale).
    y = center_rot + concat_j codebook[j, code_j]; padding slots and the
    lanes past rot are zeroed. y2 is computed from the
    *stored* (rounded/quantized) values so scores match what the scan kernel
    sees exactly.

    ``dtype == int8`` selects the memory-lean scan cache (the TPU analog of
    the reference's fp8 LUT accuracy class, ivf_pq_types.hpp lut_dtype):
    reconstructions are symmetrically quantized with one global scale
    (returned; 1.0 for float dtypes) and the scan runs on the MXU's native
    int8 path — rot_dim bytes/vector, so DEEP-100M-shape datasets fit HBM.

    The decode runs on device: only the codes (pq_dim bytes/vector) and the
    small codebook/centroid tables cross host→device; the full decoded
    cache (rot_dim·itemsize bytes/vector) is produced where it lives. It is
    jitted and chunked over the list axis so the f32 decode intermediates
    never exceed a fixed HBM budget — the int8 mode exists precisely for
    indexes whose full f32 decode would not fit."""
    L, cap, pq_dim = list_codes.shape
    codes = jnp.asarray(list_codes)
    cb = jnp.asarray(codebook)
    cr = jnp.asarray(centers_rot)
    valid = jnp.asarray(np.asarray(list_index) >= 0)
    rot_dim = cr.shape[1]
    per_list = max(1, cap * rot_dim * 4)
    chunk = int(np.clip(_DECODE_CHUNK_BYTES // per_list, 1, max(L, 1)))

    per_cluster = codebook_kind == CODEBOOK_PER_CLUSTER

    def chunks(extra=None):
        for s in range(0, L, chunk):
            cb_c = cb[s : s + chunk] if per_cluster else cb
            yield (
                cb_c, cr[s : s + chunk], codes[s : s + chunk],
                valid[s : s + chunk],
            ) + (() if extra is None else (extra,))

    def assemble(part_iter, out_dtype):
        """Write decoded chunks into preallocated (donated) buffers so peak
        HBM is one final cache + one chunk, never 2× (the concatenate of a
        parts list doubles residency exactly on the just-fits indexes the
        int8 mode exists for).  The chunks are rot_dim wide; the buffer's
        lanes past them stay zero."""
        data = jnp.zeros((L, cap, padded_width(rot_dim)), out_dtype)
        y2 = jnp.zeros((L, cap), jnp.float32)
        s = 0
        for part_d, part_y2 in part_iter:
            data = _write_rows(data, part_d, s)
            y2 = _write_rows(y2, part_y2, s)
            s += part_d.shape[0]
        return data, y2

    if dtype == jnp.int8:
        m = 0.0
        for args in chunks():
            m = max(m, float(_decode_chunk_absmax(*args, per_cluster)))
        scale = max(m, 1e-12) / 127.0
        data, y2 = assemble(
            (_decode_chunk_int8(*args, per_cluster) for args in chunks(scale)),
            jnp.int8,
        )
        return data, y2, scale
    name = "bfloat16" if dtype == jnp.bfloat16 else "float32"
    data, y2 = assemble(
        (_decode_chunk_float(*args, per_cluster, name) for args in chunks()),
        dtype,
    )
    return data, y2, 1.0


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(buf, part, start):
    """Donated in-place row-block write (start is traced → one compiled
    program regardless of chunk count); ``part`` may be narrower than
    ``buf`` in its trailing dims, which it then fills from index 0."""
    return lax.dynamic_update_slice_in_dim(buf, part, start, axis=0)


def _decode_y(cb, cr, codes, valid, per_cluster: bool):
    """Decoded f32 reconstructions of one list chunk (traced helper)."""
    idx = codes.astype(jnp.int32)[..., None, None]
    if per_cluster:
        dec = jnp.take_along_axis(cb[:, None, None], idx, axis=3)[..., 0, :]
    else:
        dec = jnp.take_along_axis(cb[None, None], idx, axis=3)[..., 0, :]
    y = dec.reshape(codes.shape[0], codes.shape[1], -1) + cr[:, None, :]
    return jnp.where(valid[..., None], y, 0.0)


@functools.partial(jax.jit, static_argnames=("per_cluster",))
def _decode_chunk_absmax(cb, cr, codes, valid, per_cluster: bool):
    return jnp.max(jnp.abs(_decode_y(cb, cr, codes, valid, per_cluster)))


@functools.partial(jax.jit, static_argnames=("per_cluster",))
def _decode_chunk_int8(cb, cr, codes, valid, scale, per_cluster: bool):
    y = _decode_y(cb, cr, codes, valid, per_cluster)
    y_int = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    y_f32 = y_int.astype(jnp.float32) * scale
    return y_int, jnp.sum(y_f32 * y_f32, axis=-1)


@functools.partial(jax.jit, static_argnames=("per_cluster", "dtype_name"))
def _decode_chunk_float(cb, cr, codes, valid, per_cluster: bool, dtype_name: str):
    y = _decode_y(cb, cr, codes, valid, per_cluster)
    y_stored = y.astype(_DECODED_DTYPES[dtype_name])
    y_f32 = y_stored.astype(jnp.float32)
    return y_stored, jnp.sum(y_f32 * y_f32, axis=-1)


def _rows_y(cb, cr, codes, labels, per_cluster: bool):
    """f32 reconstructions of a row chunk: y = cr[label] + decode(codes).
    Shared by the streamed assemble, the fast-append decode, and absmax
    scans (traced helper; OOB labels clamp-gather — callers mask/drop)."""
    codes_i = codes.astype(jnp.int32)
    if per_cluster:
        b = cb[labels]  # [n, K, l]
        dec = jnp.take_along_axis(b, codes_i[:, :, None], axis=1)
    else:
        dec = jnp.take_along_axis(
            cb[None], codes_i[:, :, None, None], axis=2
        )[:, :, 0, :]
    return dec.reshape(codes.shape[0], -1) + cr[labels]


@functools.partial(jax.jit, static_argnames=("per_cluster",))
def _rows_absmax(cb, cr, codes, labels, valid, per_cluster: bool):
    y = _rows_y(cb, cr, codes, labels, per_cluster)
    return jnp.max(jnp.where(valid[:, None], jnp.abs(y), 0.0))


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2, 3),
    static_argnames=("per_cluster",),
)
def _scatter_chunk(
    l_codes, l_index, l_data, l_y2,  # donated [L, cap, ...] buffers
    cb, cr, codes, ids, lst, slot, scale,
    per_cluster: bool,
):
    """Decode one row chunk and scatter it into the padded device buffers.

    Padding rows in the (fixed-size) last chunk carry lst == n_lists —
    out of bounds, so ``mode="drop"`` discards them; gather clamping on the
    decode side is harmless for dropped rows. Donation keeps peak HBM at
    one index + one chunk (the streamed analog of the reference's batched
    device-side extend, ivf_pq_build.cuh:1374-1460)."""
    y = _rows_y(cb, cr, codes, lst, per_cluster)
    if l_data.dtype == jnp.int8:
        stored = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
        y_f32 = stored.astype(jnp.float32) * scale
    else:
        stored = y.astype(l_data.dtype)
        y_f32 = stored.astype(jnp.float32)
    y2 = jnp.sum(y_f32 * y_f32, axis=-1)
    return (
        l_codes.at[lst, slot].set(codes, mode="drop"),
        l_index.at[lst, slot].set(ids, mode="drop"),
        l_data.at[lst, slot].set(
            lane_pad(stored, l_data.shape[-1]), mode="drop"
        ),
        l_y2.at[lst, slot].set(y2, mode="drop"),
    )


def _assemble_lists(
    codes: np.ndarray,
    ids: np.ndarray,
    labels: np.ndarray,
    n_lists: int,
    codebook: np.ndarray,
    codebook_kind: str,
    centers_rot: np.ndarray,
    dtype,
    headroom: bool = True,
    max_cap="default",
):
    """Streamed device-side list assembly: compute the (list, slot) layout
    host-side (metadata only — O(n) ints, no padded payload copies), then
    decode + scatter row chunks into preallocated, donated device buffers.

    Host residency is bounded by the compressed stream (codes pq_dim B/row
    + labels/ids 8 B/row); device residency by the final index + one
    decode chunk. This replaces the old pack-then-decode path whose padded
    host arrays and full-index transfers could not survive 10⁸ rows
    (ref: batched extend ivf_pq_build.cuh:1374-1501). Oversized lists are
    split with duplicated centroids (skew-bounded cap;
    _common.split_oversized_lists); returns center_map for the caller to
    expand centers/codebooks."""
    n, pq_dim = codes.shape
    # max_cap=None disables skew splitting — the sharded build's
    # shard-major relabel needs list ids to stay stable (serve.build)
    lst, slot, sizes, center_map, cap = compute_list_layout(
        labels, n_lists,
        max_cap=default_max_cap(n, n_lists) if max_cap == "default" else max_cap,
        headroom=headroom,
    )
    L = len(center_map)
    centers_rot = np.asarray(centers_rot)[center_map]
    if codebook_kind == CODEBOOK_PER_CLUSTER:
        codebook = np.asarray(codebook)[center_map]
    per_cluster = codebook_kind == CODEBOOK_PER_CLUSTER
    rot_dim = centers_rot.shape[1]
    cb = jnp.asarray(codebook)
    cr = jnp.asarray(centers_rot)

    # fixed chunk size → every chunk reuses one compiled scatter program;
    # bound the f32 decode intermediates (y, dec, stored) + the per-cluster
    # codebook gather to the decode HBM budget
    per_row = rot_dim * 4 * 4
    if per_cluster:
        per_row += codebook.shape[1] * codebook.shape[2] * 4
    chunk = int(np.clip(_DECODE_CHUNK_BYTES // max(per_row, 1), 8, max(n, 8)))

    codes = np.ascontiguousarray(np.asarray(codes, np.uint8))
    ids = np.asarray(ids, np.int32)
    lst32 = np.asarray(lst, np.int32)
    slot32 = np.asarray(slot, np.int32)

    def chunk_codes(s):
        e = min(s + chunk, n)
        pad = chunk - (e - s)
        c = codes[s:e]
        l = lst32[s:e]
        if pad:
            c = np.concatenate([c, np.zeros((pad, pq_dim), np.uint8)])
            # padding rows point past the last list → scatter mode="drop"
            l = np.concatenate([l, np.full(pad, L, np.int32)])
        return jnp.asarray(c), jnp.asarray(l)

    def chunk_meta(s):
        e = min(s + chunk, n)
        pad = chunk - (e - s)
        i = ids[s:e]
        sl = slot32[s:e]
        if pad:
            i = np.concatenate([i, np.zeros(pad, np.int32)])
            sl = np.concatenate([sl, np.zeros(pad, np.int32)])
        return jnp.asarray(i), jnp.asarray(sl)

    scale = 1.0
    if dtype == jnp.int8:
        # scale pre-pass streams only codes+list ids (ids/slots are not
        # consumed until the scatter pass — keep them off the wire here)
        m = 0.0
        for s in range(0, max(n, 1), chunk):
            c, l = chunk_codes(s)
            m = max(m, float(_rows_absmax(cb, cr, c, l, l < L, per_cluster)))
        scale = max(m, 1e-12) / 127.0

    l_codes = jnp.zeros((L, cap, pq_dim), jnp.uint8)
    l_index = jnp.full((L, cap), -1, jnp.int32)
    l_data = jnp.zeros((L, cap, padded_width(rot_dim)), dtype)
    l_y2 = jnp.zeros((L, cap), jnp.float32)
    for s in range(0, n, chunk):
        c, l = chunk_codes(s)
        i, sl = chunk_meta(s)
        l_codes, l_index, l_data, l_y2 = _scatter_chunk(
            l_codes, l_index, l_data, l_y2,
            cb, cr, c, i, l, sl, jnp.float32(scale), per_cluster,
        )
    return (
        l_codes,
        l_index,
        jnp.asarray(sizes),
        l_data,
        l_y2,
        center_map,
        scale,
    )


@traced("ivf_pq.build")
def build(
    params: IndexParams,
    dataset: jax.Array,
    *,
    res: Optional[Resources] = None,
) -> Index:
    """(ref: build pipeline detail/ivf_pq_build.cuh:1681-1836)

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.neighbors import ivf_pq
    >>> x = np.random.default_rng(0).random((2000, 32), dtype=np.float32)
    >>> idx = ivf_pq.build(
    ...     ivf_pq.IndexParams(n_lists=16, pq_dim=8, kmeans_n_iters=3), x
    ... )
    >>> d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=16), idx, x[:4], 5)
    >>> i.shape
    (4, 5)
    >>> bool((np.asarray(i) >= 0).all())
    True

    ``dataset`` may be a host numpy array (including a memmap): it is never
    uploaded wholesale — the trainset subsample and the per-tile
    predict+encode stream are the only device transfers, so datasets far
    larger than HBM build on one chip (the out-of-core intent of the
    reference's deep-100M/wiki-all configs, docs/source/wiki_all_dataset.md)."""
    res = ensure(res)
    if not isinstance(dataset, np.ndarray):
        dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    canonical = DISTANCE_TYPES[params.metric]
    if canonical not in ("sqeuclidean", "euclidean", "inner_product"):
        raise ValueError(f"ivf_pq supports L2/IP metrics, got {params.metric}")
    if not (4 <= params.pq_bits <= 8):
        raise ValueError(f"pq_bits must be in [4, 8], got {params.pq_bits}")

    pq_dim = params.pq_dim or _auto_pq_dim(dim)
    pq_len = max(1, (dim + pq_dim - 1) // pq_dim)
    rot_dim = pq_dim * pq_len

    key = jax.random.PRNGKey(params.seed)
    _, k_rot, k_cb = jax.random.split(key, 3)

    # --- trainset subsample (ref :1706-1766; host-side index draw — see
    # _common.subsample_trainset for the compile-cost rationale)
    n_train = min(n, max(params.n_lists * 2, int(n * params.kmeans_trainset_fraction)))
    if n_train < n:
        trainset = subsample_trainset(dataset, n_train, params.seed).astype(jnp.float32)
    else:
        trainset = dataset.astype(jnp.float32)

    # --- coarse quantizer (ref :1776-1781 → kmeans_balanced hierarchical
    # fit, trained under the index metric so list membership matches the
    # probe ranking at search time — ref ivf_pq_build.cuh:1780 passes
    # index.metric into kmeans_balanced)
    kb_metric = "inner_product" if canonical == "inner_product" else "sqeuclidean"
    kb = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=kb_metric, seed=params.seed
    )
    centers = kmeans_balanced.fit(kb, trainset, params.n_lists, res=res)
    labels = kmeans_balanced.predict(centers, trainset, metric=kb_metric, res=res)

    # --- rotation + rotated centers (ref make_rotation_matrix:122, set_centers:317)
    rotation = make_rotation_matrix(k_rot, rot_dim, dim, params.force_random_rotation)
    centers_rot = jnp.matmul(centers, rotation.T, precision=_PREC)

    # --- PQ codebooks on rotated residuals (ref train_per_subset:395 / :473)
    resid = jnp.matmul(trainset - centers[labels], rotation.T, precision=_PREC)
    k_pq = 1 << params.pq_bits
    if params.codebook_kind == CODEBOOK_PER_SUBSPACE:
        subvecs = jnp.transpose(resid.reshape(-1, pq_dim, pq_len), (1, 0, 2))
        codebook = _train_codebooks_lloyd(k_cb, subvecs, k_pq, 25)
    elif params.codebook_kind == CODEBOOK_PER_CLUSTER:
        # pool every subspace slice of a cluster's residuals into one training
        # set per cluster, padded to uniform count with weight-0 rows so the
        # padding cannot bias the centroids (one counting-sort scatter, not a
        # python loop over n_lists). The pooled cap is bounded: the [L, cap,
        # pq_len] allocation scales with the most skewed cluster, and a
        # k_pq-center Lloyd gains nothing past a few thousand samples — rows
        # beyond the cap are dropped (uniform within-cluster subsample via
        # the trainset's row order, itself a random draw).
        flat = np.asarray(resid).reshape(-1, pq_len)
        lab2 = np.repeat(np.asarray(labels), pq_dim)
        counts = np.bincount(lab2, minlength=params.n_lists)
        cap = max(int(counts.max()) if counts.size else 1, k_pq)
        cap = min(cap, max(8 * k_pq, 2048))
        order = np.argsort(lab2, kind="stable")
        starts = np.cumsum(counts) - counts
        within = np.arange(len(lab2)) - starts[lab2[order]]
        keep = within < cap
        pooled = np.zeros((params.n_lists, cap, pq_len), np.float32)
        wts = np.zeros((params.n_lists, cap), np.float32)
        pooled[lab2[order][keep], within[keep]] = flat[order][keep]
        wts[lab2[order][keep], within[keep]] = 1.0
        codebook = _train_codebooks_lloyd(
            k_cb, jnp.asarray(pooled), k_pq, 25, jnp.asarray(wts)
        )
    else:
        raise ValueError(f"unknown codebook_kind {params.codebook_kind}")

    decoded_dtype = params.decoded_dtype
    if decoded_dtype == "auto":
        # projected footprint at bf16: padded rows × (scan cache + codes +
        # y2 + ids); 1.35 ≈ split/headroom padding allowance
        est_rows = int(n * 1.35) + 8 * params.n_lists
        bf16_bytes = est_rows * (padded_width(rot_dim) * 2 + pq_dim + 8)
        total, limit_is_real = _device_memory_budget()
        budget = int(_AUTO_HBM_FRACTION * total)
        # int8 is an accuracy-class change: only auto-select it against a
        # REAL reported device limit — the 16 GiB assumption on backends
        # with no bytes_limit (CPU) must not silently degrade recall.
        decoded_dtype = (
            "int8" if bf16_bytes > budget and limit_is_real else "bfloat16"
        )
        if decoded_dtype == "int8":
            _log.warning(
                "ivf_pq.build: projected bf16 cache %.1f GB exceeds %.1f GB "
                "budget — auto-selecting int8 scan cache (accuracy-class "
                "change; pass decoded_dtype explicitly to override)",
                bf16_bytes / 2**30, budget / 2**30,
            )
        elif bf16_bytes > budget:
            _log.warning(
                "ivf_pq.build: projected bf16 cache %.1f GB exceeds the "
                "assumed %.1f GB budget but the backend reports no memory "
                "limit — keeping bfloat16 (set decoded_dtype='int8' or "
                "RAFT_TPU_HBM_BYTES to opt into the quantized cache)",
                bf16_bytes / 2**30, budget / 2**30,
            )
    validation.check_in(decoded_dtype, _DECODED_DTYPES, "decoded_dtype")
    dec_dtype = _DECODED_DTYPES[decoded_dtype]
    index = Index(
        params.metric,
        params.codebook_kind,
        params.pq_bits,
        centers,
        centers_rot,
        rotation,
        codebook,
        np.zeros((params.n_lists, 8, pq_dim), np.uint8),
        jnp.full((params.n_lists, 8), -1, jnp.int32),
        jnp.zeros((params.n_lists,), jnp.int32),
        jnp.zeros((params.n_lists, 8, padded_width(rot_dim)), dec_dtype),
        jnp.zeros((params.n_lists, 8), jnp.float32),
        headroom=not params.conservative_memory_allocation,
    )
    if params.add_data_on_build:
        index = extend(index, dataset, jnp.arange(n, dtype=jnp.int32), res=res)
    _log.debug(
        "ivf_pq.build: n=%d dim=%d n_lists=%d (requested %d) pq_dim=%d "
        "pq_bits=%d cap=%d",
        n, dim, index.n_lists, params.n_lists, pq_dim, params.pq_bits,
        index.list_cap,
    )
    return index


def _decode_rows(index: Index, codes: jax.Array, labels: jax.Array):
    """Decode encoded rows → (stored-dtype rows [n, rot_dim], y2 [n],
    absmax scalar f32) using the index's scan-cache dtype (+frozen int8
    scale). Device-side; the per-row analog of the host _decode_lists pass.
    ``absmax`` is the pre-quantization |y| peak — callers appending into an
    int8 cache must compare it against 127·scan_scale and take the
    repack/rescale path instead when quantizing would clip."""
    y = _rows_y(
        index.codebook, index.centers_rot, codes, labels,
        index.codebook_kind == CODEBOOK_PER_CLUSTER,
    )
    absmax = jnp.max(jnp.abs(y)) if codes.shape[0] else jnp.float32(0.0)
    if index.list_data.dtype == jnp.int8:
        y_int = jnp.clip(
            jnp.round(y / index.scan_scale), -127, 127
        ).astype(jnp.int8)
        y_f32 = y_int.astype(jnp.float32) * index.scan_scale
        return y_int, jnp.sum(y_f32 * y_f32, axis=-1), absmax
    y_stored = y.astype(index.list_data.dtype)
    y_f32 = y_stored.astype(jnp.float32)
    return y_stored, jnp.sum(y_f32 * y_f32, axis=-1), absmax


def _extend_fast(index: Index, codes_np, labels_np, new_ids):
    """In-place append when the target lists still have spare capacity:
    scatter the new rows' codes/ids/decoded-values into the existing padded
    layout (device .at[] scatters for the scan cache — HBM-bandwidth cost,
    not a host re-decode of the whole index; the TPU answer to the
    reference's device-side list growth, ivf_pq_build.cuh:1501).

    Split shards of a skewed list share one centroid; rows whose predicted
    shard is full overflow into a sibling shard with space (they score
    identically at probe selection, see _common.split_oversized_lists).
    Returns None when a centroid group is out of capacity altogether, or
    when an int8 scan cache would clip the new rows at the frozen
    build-time scan_scale (caller falls back to the repack path, which
    recomputes the scale — keeps fast- and slow-path recall identical)."""
    if index._group_inverse is None:
        index._group_inverse = centroid_group_inverse(index.centers)
    alloc = allocate_append_slots(
        index.centers, index.list_sizes, index.list_cap, labels_np,
        group_inverse=index._group_inverse,
    )
    if alloc is None:
        return None
    slab, slots, counts_new = alloc

    lj = jnp.asarray(slab)
    sj = jnp.asarray(slots)
    ids_j = jnp.asarray(np.asarray(new_ids, np.int32))

    dec_rows, y2_rows, absmax = _decode_rows(index, jnp.asarray(codes_np), lj)
    if index.list_data.dtype == jnp.int8 and float(absmax) > 127.0 * float(
        index.scan_scale
    ):
        return None  # would clip at the frozen scale → repack rescales

    # codes stay a device array: the append is an O(appended) .at[] scatter
    # (uint8, same shape discipline as list_data), not a host copy+reupload
    # of the whole code tensor.
    new = Index(
        index.metric, index.codebook_kind, index.pq_bits,
        index.centers, index.centers_rot, index.rotation, index.codebook,
        jnp.asarray(index.list_codes).at[lj, sj].set(jnp.asarray(codes_np)),
        index.list_index.at[lj, sj].set(ids_j),
        index.list_sizes + jnp.asarray(counts_new, jnp.int32),
        index.list_data.at[lj, sj].set(
            lane_pad(dec_rows, index.list_data.shape[-1])
        ),
        index.list_y2.at[lj, sj].set(y2_rows),
        index.scan_scale,
        headroom=index.headroom,
    )
    new._group_inverse = index._group_inverse
    return new


@traced("ivf_pq.extend")
def extend(
    index: Index,
    new_vectors: jax.Array,
    new_indices: Optional[jax.Array] = None,
    *,
    res: Optional[Resources] = None,
) -> Index:
    """Encode + append rows (ref: extend detail/ivf_pq_build.cuh:1501).

    ``new_vectors`` may be any supported dtype (f32/bf16/int8/uint8 — ref
    ivf_pq_build.cuh:1690 dtype templates); rows are cast to f32 one tile
    at a time inside the predict+encode loop, so no full-precision copy of
    the input is ever materialized. A host numpy input (incl. memmap) stays
    host-resident: each tile is uploaded as it is encoded, and only the
    compressed stream (codes pq_dim B/row + labels) is retained — bounded
    host residency for 10⁸-row builds."""
    if getattr(index, "paged", None) is not None:
        raise ValueError(
            "extend() on a paged index is unsupported: paged serving routes "
            "growth through MutableIndex side buffers and re-paginates at "
            "compaction"
        )
    res = ensure(res)
    x = new_vectors if isinstance(new_vectors, np.ndarray) else jnp.asarray(new_vectors)
    canonical = DISTANCE_TYPES[index.metric]
    kb_metric = "inner_product" if canonical == "inner_product" else "sqeuclidean"
    # tile the predict+encode to bound the [tile, rot_dim]+einsum workspace
    n = x.shape[0]
    tile = max(1, res.workspace_rows(4 * (index.rot_dim * 3 + index.pq_dim * index.pq_n_centers), cap=1 << 18))
    codes_parts, label_parts = [], []
    for s in range(0, n, tile):
        xt = jnp.asarray(x[s : s + tile]).astype(jnp.float32)
        lt = kmeans_balanced.predict(index.centers, xt, metric=kb_metric, res=res)
        codes_parts.append(
            np.asarray(
                _encode(
                    index.rotation, index.centers, index.centers_rot, index.codebook,
                    xt, lt, index.codebook_kind,
                )
            )
        )
        label_parts.append(np.asarray(lt))
    codes = np.concatenate(codes_parts) if codes_parts else np.zeros((0, index.pq_dim), np.uint8)
    labels = (
        np.concatenate(label_parts) if label_parts else np.zeros((0,), np.int32)
    )
    return _extend_encoded(index, codes, labels, new_indices)


def _extend_encoded(
    index: Index,
    codes: np.ndarray,
    labels: np.ndarray,
    new_indices: Optional[jax.Array] = None,
) -> Index:
    """Append already-encoded rows (codes [n, pq_dim] uint8 + coarse
    labels [n]) — the assembly half of :func:`extend`. The seam the
    distributed build uses: shards encode their own rows in parallel, the
    compressed streams meet here (pq_dim B/row is all that travels)."""
    n = codes.shape[0]
    old_n = index.size
    if new_indices is None:
        new_indices = jnp.arange(old_n, old_n + n, dtype=jnp.int32)

    # fast path: append into spare capacity without touching existing rows
    if n and old_n:
        fast = _extend_fast(index, codes, labels, np.asarray(new_indices))
        if fast is not None:
            return fast

    old_codes, old_ids, old_labels = unpack_lists(
        np.asarray(index.list_codes), np.asarray(index.list_index)
    )
    if old_codes.shape[0] == 0:
        # initial fill (build): no concatenate — one copy of the code
        # stream on the host, never two
        all_codes, all_ids, all_labels = (
            codes, np.asarray(new_indices, np.int32), np.asarray(labels)
        )
    else:
        all_codes = np.concatenate([old_codes, codes])
        all_ids = np.concatenate([old_ids, np.asarray(new_indices, np.int32)])
        all_labels = np.concatenate([old_labels, np.asarray(labels)])
    # merge split shards back to their parent before re-packing (see
    # _common.merge_split_lists — keeps n_lists stable across extends)
    uniq, all_labels = merge_split_lists(np.asarray(index.centers), all_labels)
    uniq_j = jnp.asarray(uniq)
    base_centers = index.centers[uniq_j]
    base_centers_rot = index.centers_rot[uniq_j]
    base_codebook = (
        index.codebook[uniq_j]
        if index.codebook_kind == CODEBOOK_PER_CLUSTER
        else index.codebook
    )
    (
        list_codes, list_index, list_sizes, list_data, list_y2, cmap,
        scan_scale,
    ) = _assemble_lists(
        all_codes, all_ids, all_labels, len(uniq),
        np.asarray(base_codebook), index.codebook_kind,
        np.asarray(base_centers_rot), index.list_data.dtype,
        headroom=index.headroom,
    )
    cmap_j = jnp.asarray(cmap)
    codebook = (
        base_codebook[cmap_j]
        if index.codebook_kind == CODEBOOK_PER_CLUSTER
        else index.codebook
    )
    return Index(
        index.metric, index.codebook_kind, index.pq_bits,
        base_centers[cmap_j], base_centers_rot[cmap_j], index.rotation,
        codebook, list_codes, list_index, list_sizes, list_data, list_y2,
        scan_scale,
        headroom=index.headroom,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_probes", "k", "metric", "query_tile", "scan_dtype", "acc_dtype",
    ),
)
def _search_jit(
    queries,      # [q, dim] f32
    centers,      # [L, dim]
    rotation,     # [rot_dim, dim]
    list_data,    # [L, cap, padded_width(rot_dim)] — decoded reconstructions
    list_y2,      # [L, cap] f32
    list_index,   # [L, cap] int32
    filter_words,
    scan_scale,   # scalar f32 — int8-cache dequant scale (1.0 otherwise)
    n_probes: int,
    k: int,
    metric: str,
    query_tile: int,
    scan_dtype,
    acc_dtype,
):
    """Probe-gather + MXU-matmul scan over decoded reconstructions.

    The per-code LUT gather of the reference's compute_similarity kernel
    (ivf_pq_compute_similarity-inl.cuh) is replaced by
    ``‖q_rot − y‖² = y² − 2·q_rot·y + q²`` over the decoded rows — the scan
    is one batched dot_general that streams the probed lists through the
    MXU (measured ~1000× faster than take_along_axis on v5e)."""
    q, dim = queries.shape
    rot_dim = rotation.shape[0]
    cap = list_data.shape[1]

    # ---- coarse cluster selection (ref select_clusters ivf_pq_search.cuh:67)
    probes = coarse_select(queries, centers, metric, n_probes)  # [q, p]

    q_rot = jnp.matmul(queries, rotation.T, precision=_PREC)  # [q, rot_dim]

    n_tiles = (q + query_tile - 1) // query_tile
    pad_q = n_tiles * query_tile - q
    qt = jnp.pad(q_rot, ((0, pad_q), (0, 0))).reshape(n_tiles, query_tile, rot_dim)
    pt = jnp.pad(probes, ((0, pad_q), (0, 0))).reshape(n_tiles, query_tile, n_probes)
    # per-row filters (ragged batches) tile alongside the queries; ndim is
    # static in trace so the branch costs nothing at runtime
    per_row = filter_words is not None and filter_words.ndim == 2
    if per_row:
        ft = jnp.pad(filter_words, ((0, pad_q), (0, 0))).reshape(
            n_tiles, query_tile, -1
        )
    else:
        ft = jnp.zeros((n_tiles, 1, 1), jnp.uint32)  # unused carrier

    def tile(args):
        qr, pp, fw_t = args  # [t, rot_dim], [t, p], [t, W]
        dec = _gather_lists(list_data, pp)               # [t, p, cap, rot_w]
        ids = list_index[pp]                             # [t, p, cap]
        y2 = list_y2[pp]                                 # [t, p, cap]
        # zero lanes up to the cache's padded width add exact zeros
        qw = lane_pad(qr, list_data.shape[-1])           # [t, rot_w]
        # ip[t,p,c] = q_rot[t]·y[t,p,c] — batched over t, contracting rot
        # acc_dtype = the reference's internal_distance_dtype knob: the
        # score accumulator precision (ivf_pq_types.hpp:139-172)
        if list_data.dtype == jnp.int8:
            # memory-lean mode: rows are int8 × global scan_scale; quantize
            # the query per-row and ride the MXU's native int8 path, then
            # rescale the int32 accumulator (the fp8-LUT accuracy analog)
            ip = int8_scored_ip(
                qw, dec, (((1,), (3,)), ((0,), (0,))), scan_scale
            )                                            # [t, p, cap]
        else:
            ip = lax.dot_general(
                qw.astype(scan_dtype),
                dec.astype(scan_dtype),
                (((1,), (3,)), ((0,), (0,))),            # contract rot; batch t
                preferred_element_type=acc_dtype,
            )                                            # [t, p, cap]
        if metric == "inner_product":
            scores = (-ip).astype(jnp.float32)           # q·y == q_rot·y_rot
        else:
            q2 = jnp.sum(qr * qr, axis=1).astype(acc_dtype)  # [t]
            scores = (
                y2.astype(acc_dtype) - 2.0 * ip + q2[:, None, None]
            ).astype(jnp.float32)

        if per_row:
            invalid = invalid_mask_rows(ids, fw_t)
        else:
            invalid = invalid_mask(ids, filter_words)
        scores = jnp.where(invalid, jnp.inf, scores)
        # filtered-out candidates must surface as id −1, never their real id
        ids = jnp.where(invalid, -1, ids)
        flat_s = scores.reshape(query_tile, n_probes * cap)
        flat_i = ids.reshape(query_tile, n_probes * cap)
        v, i = select_k(flat_s, k, select_min=True, input_indices=flat_i)
        # ---- postprocess (ref ivf_pq_search.cuh:453-467)
        if metric == "inner_product":
            v = -v
        elif metric == "euclidean":
            v = jnp.sqrt(jnp.maximum(v, 0.0))
        return v, i

    vals, idx = lax.map(tile, (qt, pt, ft))
    return (
        vals.reshape(n_tiles * query_tile, k)[:q],
        idx.reshape(n_tiles * query_tile, k)[:q],
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_probes", "k", "metric", "bucket", "bb", "scan_dtype", "acc_dtype",
    ),
)
def _search_probe_major_jit(
    queries,      # [q, dim] f32
    centers,      # [L, dim]
    rotation,     # [rot_dim, dim]
    list_data,    # [L, cap, padded_width(rot_dim)] bf16/f32/int8
    list_y2,      # [L, cap] f32
    list_index,   # [L, cap] int32
    filter_words,
    scan_scale,
    n_probes: int,
    k: int,
    metric: str,
    bucket: int,  # queries per list-bucket (G)
    bb: int,      # buckets per scan step
    scan_dtype,
    acc_dtype,
):
    """Probe-major scan schedule: sort the (query, probe) pairs by list,
    bucket each list's probing queries, and stream list-by-list so every
    list's rows leave HBM ~once per batch instead of once per probing
    query (SURVEY §7 hard-part-2 "probe-major batching"; plays the role of
    the reference's per-list persistent compute_similarity scheduling,
    ivf_pq_compute_similarity-inl.cuh). Per-(pair) top-k partials are
    scattered back to (query, probe) order and merged with one select_k.
    """
    q, dim = queries.shape
    L, cap, rot_w = list_data.shape
    G = bucket
    kk = min(k, cap)

    probes = coarse_select(queries, centers, metric, n_probes)  # [q, p]
    q_rot = jnp.matmul(queries, rotation.T, precision=_PREC)    # [q, rot]
    q2 = jnp.sum(q_rot * q_rot, axis=1)                         # [q]
    q_rot = lane_pad(q_rot, rot_w)                            # [q, rot_w]

    def score_fn(bl, bq):
        dec = _gather_lists(list_data, bl)                         # [bb, cap, rot_w]
        ids = list_index[bl]                                       # [bb, cap]
        y2 = list_y2[bl]
        qr = q_rot[jnp.clip(bq, 0)]                                # [bb, G, rot]
        if list_data.dtype == jnp.int8:
            ip = int8_scored_ip(
                qr, dec, (((2,), (2,)), ((0,), (0,))), scan_scale
            )                                                      # [bb, G, cap]
        else:
            ip = lax.dot_general(
                qr.astype(scan_dtype), dec.astype(scan_dtype),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=acc_dtype,
            )
        if metric == "inner_product":
            scores = (-ip).astype(jnp.float32)
        else:
            qq = q2[jnp.clip(bq, 0)].astype(acc_dtype)             # [bb, G]
            scores = (
                y2[:, None, :].astype(acc_dtype) - 2.0 * ip + qq[:, :, None]
            ).astype(jnp.float32)
        invalid = invalid_mask(ids, filter_words)                  # [bb, cap]
        scores = jnp.where(invalid[:, None, :], jnp.inf, scores)
        scores = jnp.where(bq[:, :, None] < 0, jnp.inf, scores)
        ids_m = jnp.where(invalid, -1, ids)
        v, i = select_k(
            scores.reshape(bb * G, cap), kk, select_min=True,
            input_indices=jnp.broadcast_to(
                ids_m[:, None, :], (bb, G, cap)
            ).reshape(bb * G, cap),
        )
        return v, i                                                # [bb*G, kk]

    v, i = run_probe_major(probes, L, G, bb, kk, k, score_fn)
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_probes", "k", "metric", "bucket", "scan_dtype", "interpret"
    ),
)
def _search_probe_major_pallas(
    queries, centers, rotation, list_data, list_y2, list_index,
    list_filter, scan_scale, n_probes: int, k: int, metric: str,
    bucket: int, scan_dtype: str, interpret: bool,
):
    """Probe-major schedule with the fused Pallas scan
    (kernels/ivf_scan.py): per-bucket list rows DMA into VMEM via the
    scalar-prefetched bucket table, scores + per-query top-k stay in VMEM —
    the [B, G, cap] score tensor never reaches HBM (the XLA formulation's
    remaining traffic). L2 + inner-product, float or int8 caches (the
    kernel's quantized-query leg handles int8 × scan_scale);
    ``list_filter`` is the pre-packed per-list word table (packed ONCE in
    :func:`search` — it's query-independent, so packing here would redo
    the O(n) pass per query tile)."""
    from raft_tpu.kernels.ivf_scan import ivf_scan_probe_major
    from raft_tpu.neighbors._common import (
        invert_probes as _invert,
        merge_probe_major_partials as _merge,
    )

    q, _ = queries.shape
    L, cap, rot_w = list_data.shape
    G = bucket
    kk = min(k, cap)
    probes = coarse_select(queries, centers, metric, n_probes)
    q_rot = jnp.matmul(queries, rotation.T, precision=_PREC)
    q2 = jnp.sum(q_rot * q_rot, axis=1)
    q_rot = lane_pad(q_rot, rot_w)  # the cache's padded width
    bucket_list, bucket_query, bucket_pair, B = _invert(probes, L, G)
    qg = q_rot[jnp.clip(bucket_query, 0)]                   # [B, G, rot_w]
    q2g = jnp.where(bucket_query >= 0, q2[jnp.clip(bucket_query, 0)], jnp.inf)
    vals, ids = ivf_scan_probe_major(
        bucket_list, qg, q2g, list_data, list_y2, list_index, kk,
        metric=metric, scan_dtype=scan_dtype, list_filter=list_filter,
        scan_scale=scan_scale, interpret=interpret,
    )
    v, i = _merge(
        vals.reshape(B * G, kk), ids.reshape(B * G, kk),
        bucket_pair, q, n_probes, kk, k,
    )
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_probes", "k", "metric", "scan_dtype", "interpret"
    ),
)
def _search_query_major_pallas(
    queries, centers, rotation, list_data, list_y2, list_index,
    list_filter, scan_scale, n_probes: int, k: int, metric: str,
    scan_dtype: str, interpret: bool, query_fid=None,
):
    """Query-major schedule with the fused Pallas scan
    (kernels/ivf_scan.ivf_scan_query_major): probed lists stream from
    the index straight into VMEM — the XLA leg's materialized
    [t, p, cap, rot] gather copy and [t, p, cap] score tensor (2× the
    whole scanned volume in extra HBM traffic) never exist.  Queries pad
    to the kernel's group width with q2=+inf rows (outputs -1, sliced
    off).

    ``query_fid`` (ragged descriptor leg) selects each query's filter
    row from a pre-packed [n_filters, L, cap_w] ``list_filter`` table;
    padding rows ride fid 0 — their q2=+inf already voids the result."""
    from raft_tpu.kernels.ivf_scan import _QM_GROUP, ivf_scan_query_major

    q, _ = queries.shape
    probes = coarse_select(queries, centers, metric, n_probes)
    q_rot = jnp.matmul(queries, rotation.T, precision=_PREC)
    q2 = jnp.sum(q_rot * q_rot, axis=1)
    q_rot = lane_pad(q_rot, list_data.shape[-1])  # the cache's padded width
    pad = (-q) % _QM_GROUP
    if pad:
        probes = jnp.pad(probes, ((0, pad), (0, 0)))
        q_rot = jnp.pad(q_rot, ((0, pad), (0, 0)))
        q2 = jnp.pad(q2, (0, pad), constant_values=jnp.inf)
        if query_fid is not None:
            query_fid = jnp.pad(query_fid, (0, pad))
    v, i = ivf_scan_query_major(
        probes, q_rot, q2, list_data, list_y2, list_index, int(k),
        metric=metric, scan_dtype=scan_dtype, list_filter=list_filter,
        scan_scale=scan_scale, query_fid=query_fid, interpret=interpret,
    )
    v, i = v[:q], i[:q]
    if metric == "inner_product":
        v = -v
    elif metric == "euclidean":
        v = jnp.sqrt(jnp.maximum(v, 0.0))
    return v, i


@traced("ivf_pq.search")
def search(
    params: SearchParams,
    index: Index,
    queries: jax.Array,
    k: int,
    *,
    sample_filter: Optional[Bitset] = None,
    deleted_mask: Optional[Bitset] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (distances [q, k], indices [q, k]). Distances are PQ
    approximations — pipe through ``neighbors.refine`` for exact re-ranking
    (ref: ivf_pq search + refine pattern, cagra_build.cuh:146-196).

    ``deleted_mask`` excludes set bits (tombstones, raft_tpu.serve) and
    composes with ``sample_filter`` (pass-bits kept)."""
    res = ensure(res)
    from raft_tpu.neighbors._common import resolve_pass_filter

    sample_filter = resolve_pass_filter(sample_filter, deleted_mask)
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != index.dim:
        raise ValueError(f"queries shape {queries.shape} vs index dim {index.dim}")
    n_probes = min(params.n_probes, index.n_lists)
    if k > n_probes * index.list_cap:
        raise ValueError(
            f"k={k} exceeds candidate pool n_probes*list_cap="
            f"{n_probes}*{index.list_cap}; raise n_probes"
        )
    canonical = DISTANCE_TYPES[index.metric]
    # scan compute dtype: bf16 halves the HBM stream and uses the MXU's
    # native path; float32 upcasts the stored rows (ref lut_dtype knob)
    scan_dtype = jnp.bfloat16 if params.lut_dtype == "bfloat16" else jnp.float32
    acc_dtype = (
        jnp.bfloat16 if params.internal_distance_dtype == "bfloat16" else jnp.float32
    )
    fw = sample_filter.words if sample_filter is not None else None
    validation.check_in(
        params.strategy, ("auto", "query_major", "probe_major"), "strategy"
    )
    per_row = fw is not None and fw.ndim == 2
    req_strategy = params.strategy
    if per_row:
        validation.expects(
            fw.shape[0] == queries.shape[0],
            f"row filter has {fw.shape[0]} rows for "
            f"{queries.shape[0]} queries",
        )
        # probe-major tiles score whole lists against query *buckets*; a
        # per-query filter has no per-list formulation there, so ragged
        # batches always take the query-major schedule
        req_strategy = "query_major"
    strategy, bucket, bb, q_tile = select_scan_strategy(
        req_strategy, queries.shape[0], n_probes, index.n_lists,
        index.list_cap, index.list_data.shape[-1], res.workspace_limit_bytes,
        k=int(k),
    )
    # paged index: prefetch + pin the probed lists' pages before the scan
    # executables dispatch; ``list_data`` becomes the PagedLists view and
    # the schedules below gather through the page table transparently
    paged = getattr(index, "paged", None)
    if paged is not None:
        from raft_tpu.neighbors._common import paged_lists_for_search

        list_data = paged_lists_for_search(index, queries, canonical, n_probes)
    else:
        list_data = index.list_data
    if strategy == "probe_major":
        use_pallas = pallas_scan_enabled(
            canonical, list_data.dtype, allow_int8=True
        ) and params.internal_distance_dtype == "float32"
        if paged is not None and use_pallas:
            from raft_tpu.kernels.ivf_scan import paged_scan_supported

            use_pallas = paged_scan_supported(
                list_data, min(int(k), index.list_cap), fw is not None
            )
        if use_pallas:
            # the kernel accumulates f32 only; a bf16 internal-distance
            # request must keep the XLA leg (preferred_element_type=
            # acc_dtype) or the two legs rank near-ties differently
            from raft_tpu.kernels import interpret_mode
            from raft_tpu.kernels.ivf_scan import pack_list_filter

            # pack the filter ONCE per call (query-independent)
            lf = (
                None if fw is None
                else pack_list_filter(index.list_index, fw)
            )
            _stamp_kernel_path("pallas")

            def run_pm(qt):
                return _search_probe_major_pallas(
                    qt, index.centers, index.rotation, list_data,
                    index.list_y2, index.list_index, lf,
                    float(index.scan_scale), n_probes, int(k),
                    canonical, bucket, params.lut_dtype, interpret_mode(),
                )
        else:
            _stamp_kernel_path("xla")

            def run_pm(qt):
                return _search_probe_major_jit(
                    qt,
                    index.centers,
                    index.rotation,
                    list_data,
                    index.list_y2,
                    index.list_index,
                    fw,
                    float(index.scan_scale),
                    n_probes,
                    int(k),
                    canonical,
                    bucket,
                    bb,
                    scan_dtype,
                    acc_dtype,
                )

        # host-level query batching bounds the merge buffers (pair
        # partials are O(q·p·k); see select_scan_strategy)
        return run_query_tiled(run_pm, queries, q_tile)
    from raft_tpu.kernels import ivf_scan as _scan_mod

    has_descriptor = per_row and getattr(sample_filter, "table", None) is not None
    if (
        # the fused query-major kernel has no paged leg (dense [L, cap]
        # block specs); paged searches ride the XLA gather below
        paged is None
        and pallas_scan_enabled(canonical, list_data.dtype, allow_int8=True)
        and params.internal_distance_dtype == "float32"
        # per-row filters stay fused when they carry the packed
        # descriptor (RowFilter.from_table); ad-hoc [q, w] word planes
        # still ride the XLA fallback below
        and (not per_row or has_descriptor)
        # the fused kernel's per-block score scratch must fit VMEM
        # comfortably; past that the XLA leg tiles better
        and _scan_mod.qm_scratch_bytes(n_probes, index.list_cap)
        <= _scan_mod.QM_VMEM_BUDGET
    ):
        from raft_tpu.kernels import interpret_mode

        if has_descriptor:
            # ragged descriptor leg: pack every registered filter's
            # per-list word table once; each query's fid prefetches its
            # own block (same leg ivf_flat rides — the rotation only
            # changes the query operand, not the filter plumbing)
            lf = _scan_mod.pack_list_filter_table(
                index.list_index, sample_filter.table
            )
            fid = jnp.asarray(sample_filter.fid, jnp.int32)
            _stamp_kernel_path("pallas")

            def run_qm(qt, ft):
                return _search_query_major_pallas(
                    qt, index.centers, index.rotation, index.list_data,
                    index.list_y2, index.list_index, lf,
                    float(index.scan_scale), n_probes, int(k), canonical,
                    params.lut_dtype, interpret_mode(), query_fid=ft,
                )

            return run_query_tiled(
                run_qm, queries, _scan_mod.qm_query_tile(n_probes),
                extras=(fid,),
            )

        lf = (
            None if fw is None
            else _scan_mod.pack_list_filter(index.list_index, fw)
        )
        _stamp_kernel_path("pallas")

        def run_qm(qt):
            return _search_query_major_pallas(
                qt, index.centers, index.rotation, index.list_data,
                index.list_y2, index.list_index, lf,
                float(index.scan_scale), n_probes, int(k), canonical,
                params.lut_dtype, interpret_mode(),
            )

        return run_query_tiled(
            run_qm, queries, _scan_mod.qm_query_tile(n_probes)
        )
    # per-query workspace: probe gather of decoded rows + scores + ids
    if list_data.dtype == jnp.int8:
        itemsize = 1
    else:
        itemsize = 2 if scan_dtype == jnp.bfloat16 else 4
    per_q = n_probes * index.list_cap * (list_data.shape[-1] * itemsize + 12)
    query_tile = int(min(max(queries.shape[0], 1), max(1, res.workspace_rows(per_q, cap=1024))))
    # per-row filters land here only when the fused descriptor leg was
    # unavailable — stamp the fallback distinctly in the kernel_paths tally
    _stamp_kernel_path("xla_filter_fallback" if per_row else "xla")
    return _search_jit(
        queries,
        index.centers,
        index.rotation,
        list_data,
        index.list_y2,
        index.list_index,
        fw,
        float(index.scan_scale),
        n_probes,
        int(k),
        canonical,
        query_tile,
        scan_dtype,
        acc_dtype,
    )


def _pack_bits(codes: np.ndarray, pq_bits: int) -> np.ndarray:
    """Pack uint8 codes (< 2**pq_bits) into a dense bitstream per row for
    serialization parity with the reference's compressed storage."""
    bits = np.unpackbits(codes[..., None], axis=-1, count=8, bitorder="little")
    bits = bits[..., :pq_bits].reshape(codes.shape[0], -1)
    return np.packbits(bits, axis=-1, bitorder="little")


def _unpack_bits(packed: np.ndarray, pq_dim: int, pq_bits: int) -> np.ndarray:
    bits = np.unpackbits(packed, axis=-1, bitorder="little")[:, : pq_dim * pq_bits]
    bits = bits.reshape(packed.shape[0], pq_dim, pq_bits)
    full = np.zeros((packed.shape[0], pq_dim, 8), np.uint8)
    full[..., :pq_bits] = bits
    return np.packbits(full, axis=-1, bitorder="little")[..., 0]


@traced("ivf_pq.save")
def save(filename: str, index: Index) -> None:
    lc = np.asarray(index.list_codes)
    L, cap, pq_dim = lc.shape
    packed = _pack_bits(lc.reshape(L * cap, pq_dim), index.pq_bits)
    ser.save_tree(
        filename,
        "ivf_pq",
        _SERIALIZATION_VERSION,
        {
            "metric": index.metric,
            "codebook_kind": index.codebook_kind,
            "pq_bits": index.pq_bits,
            "pq_dim": pq_dim,
            "list_cap": cap,
            "decoded_dtype": str(np.dtype(index.list_data.dtype).name)
            if index.list_data.dtype != jnp.bfloat16
            else "bfloat16",
            # ref serializes conservative_memory_allocation
            # (ivf_pq_serialize.cuh:64); headroom == not conservative
            "headroom": int(index.headroom),
        },
        {
            "centers": index.centers,
            "centers_rot": index.centers_rot,
            "rotation": index.rotation,
            "codebook": index.codebook,
            "list_codes_packed": packed,
            "list_index": index.list_index,
            "list_sizes": index.list_sizes,
        },
    )


@traced("ivf_pq.load")
def load(filename: str) -> Index:
    scalars, arrays = ser.load_tree(filename, "ivf_pq", _SERIALIZATION_VERSION)
    L = arrays["centers"].shape[0]
    cap, pq_dim = scalars["list_cap"], scalars["pq_dim"]
    codes = _unpack_bits(arrays["list_codes_packed"], pq_dim, scalars["pq_bits"])
    codes = codes.reshape(L, cap, pq_dim)
    stored_dtype = scalars.get("decoded_dtype", "bfloat16")
    validation.check_in(stored_dtype, _DECODED_DTYPES, "decoded_dtype")
    dec_dtype = _DECODED_DTYPES[stored_dtype]
    list_index = arrays["list_index"]
    # the decoded scan cache (and its int8 scale) is derived state: rebuild
    # it from the codes
    list_data, list_y2, scan_scale = _decode_lists(
        arrays["codebook"], scalars["codebook_kind"], arrays["centers_rot"],
        codes, list_index, dec_dtype,
    )
    return Index(
        scalars["metric"],
        scalars["codebook_kind"],
        scalars["pq_bits"],
        jnp.asarray(arrays["centers"]),
        jnp.asarray(arrays["centers_rot"]),
        jnp.asarray(arrays["rotation"]),
        jnp.asarray(arrays["codebook"]),
        codes,
        jnp.asarray(list_index),
        jnp.asarray(arrays["list_sizes"]),
        list_data,
        list_y2,
        scan_scale,
        headroom=bool(scalars.get("headroom", 1)),
    )
