"""Public IVF list helpers — codepacker parity.

Reference: ``neighbors/ivf_flat_helpers.cuh``, ``neighbors/ivf_pq_helpers.cuh``
and ``neighbors/ivf_flat_codepacker.hpp`` expose raw-list access and code
pack/unpack so downstream libraries can manage list storage directly
(SURVEY §2.8 row "ivf_list / helpers / codepacker").
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.neighbors import ivf_flat as _ivf_flat
from raft_tpu.neighbors import ivf_pq as _ivf_pq


# ---- ivf_flat helpers (ref: ivf_flat_helpers.cuh) -------------------------


def ivf_flat_unpack_list(index: "_ivf_flat.Index", list_id: int):
    """(vectors [size, dim], source ids [size]) of one list."""
    size = int(index.list_sizes[list_id])
    return (
        np.asarray(index.list_data[list_id])[:size],
        np.asarray(index.list_index[list_id])[:size],
    )


# ---- ivf_pq helpers (ref: ivf_pq_helpers.cuh) -----------------------------


def ivf_pq_unpack_list(index: "_ivf_pq.Index", list_id: int):
    """(codes [size, pq_dim] uint8, source ids [size]) of one list — the
    codepacker 'unpack' direction (ref: ivf_flat_codepacker.hpp unpack)."""
    size = int(index.list_sizes[list_id])
    return (
        np.asarray(index.list_codes[list_id])[:size],
        np.asarray(index.list_index[list_id])[:size],
    )


def ivf_pq_pack_codes(codes: np.ndarray, pq_bits: int) -> np.ndarray:
    """Dense bitstream from per-byte codes — the codepacker 'pack'
    direction (ref: ivf_flat_codepacker.hpp pack; serialization layout)."""
    return _ivf_pq._pack_bits(np.asarray(codes, np.uint8), pq_bits)


def ivf_pq_unpack_codes(packed: np.ndarray, pq_dim: int, pq_bits: int) -> np.ndarray:
    return _ivf_pq._unpack_bits(np.asarray(packed, np.uint8), pq_dim, pq_bits)


def ivf_pq_reconstruct_list(
    index: "_ivf_pq.Index", list_id: int
) -> Tuple[jax.Array, np.ndarray]:
    """Approximate original-space vectors of one list
    (ref: ivf_pq_helpers.cuh reconstruct_list_data): decoded rotated
    reconstructions mapped back through the orthonormal rotation."""
    size = int(index.list_sizes[list_id])
    # the cache's zero lanes past rot_dim are not part of the vectors
    y_rot = index.list_data[list_id, :size, : index.rot_dim].astype(
        jnp.float32
    )  # [size, rot]
    if index.list_data.dtype == jnp.int8:
        y_rot = y_rot * index.scan_scale  # dequantize the memory-lean cache
    vecs = jnp.matmul(y_rot, index.rotation)  # R^T maps rotated → original
    ids = np.asarray(index.list_index[list_id])[:size]
    return vecs, ids


def index_memory_footprint(index) -> dict:
    """Per-component byte accounting of an index (HBM capacity planning —
    the analog of the reference's index size reporting in ann-bench,
    cpp/bench/ann/src/common/benchmark.hpp index-size counter).

    Works on any index type here (brute_force/ivf_flat/ivf_pq/cagra):
    every array-valued attribute is counted; returns
    {attr: bytes, ..., "total": bytes}.
    """
    out = {}
    total = 0
    for name, val in vars(index).items():
        nbytes = None
        if isinstance(val, np.ndarray):
            nbytes = int(val.nbytes)
        elif isinstance(val, jax.Array):
            nbytes = int(np.dtype(val.dtype).itemsize * val.size)
        if nbytes is not None:
            out[name] = nbytes
            total += nbytes
    out["total"] = total
    return out
