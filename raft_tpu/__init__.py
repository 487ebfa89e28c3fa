"""raft_tpu — a TPU-native library of ML / data-mining primitives and
vector-search (ANN) algorithms, built on JAX / XLA / Pallas.

This is a ground-up TPU-first re-design with the capabilities of RAFT
(Reusable Accelerated Functions and Tools, reference: /root/reference
README.md:1-45): pairwise distances, k-selection, k-means, brute-force and
approximate nearest-neighbor indexes (IVF-Flat, IVF-PQ, CAGRA), sparse
primitives, graph/spectral algorithms, stats, RNG, and a distributed
communication facade over XLA collectives.

Architecture (bottom → top), mirroring the reference's layer map
(SURVEY.md §1) but re-expressed for TPU:

- ``raft_tpu.core``      — resources/context, serialization, logging, bitset
                           (ref: cpp/include/raft/core/)
- ``raft_tpu.ops``       — dense linalg + matrix primitives incl. select_k
                           (ref: cpp/include/raft/{linalg,matrix}/)
- ``raft_tpu.distance``  — pairwise distances, fused L2 1-NN, Gram kernels
                           (ref: cpp/include/raft/distance/)
- ``raft_tpu.random``    — RNG + dataset generators (ref: cpp/include/raft/random/)
- ``raft_tpu.cluster``   — kmeans, balanced kmeans, single-linkage, spectral
                           (ref: cpp/include/raft/cluster/)
- ``raft_tpu.neighbors`` — brute_force / ivf_flat / ivf_pq / cagra / nn_descent
                           / refine (ref: cpp/include/raft/neighbors/)
- ``raft_tpu.sparse``    — COO/CSR types and sparse primitives
                           (ref: cpp/include/raft/sparse/)
- ``raft_tpu.stats``     — summary stats + model metrics incl. neighborhood_recall
                           (ref: cpp/include/raft/stats/)
- ``raft_tpu.comms``     — comms facade over XLA collectives (psum/all_gather/...)
                           (ref: cpp/include/raft/comms/, core/comms.hpp)
- ``raft_tpu.obs``       — observability: metrics registry, spans, XLA event
                           attribution, Prometheus/JSON export
                           (ref: core/nvtx.hpp + core/logger-inl.hpp, made queryable)
- ``raft_tpu.bench``     — ANN benchmark harness (ref: cpp/bench/ann/, raft-ann-bench)

Everything is functional and jit-friendly: static shapes, `lax` control flow,
sharding via `jax.sharding.Mesh` + shard_map.
"""

__version__ = "0.1.0"

import os as _os


def _enable_persistent_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path.

    Cold-process XLA compiles dominate wall time for index builds, so
    compiled executables are cached across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and this
    does nothing; otherwise the cache is ``<checkout>/.jax_cache``.  The
    path is part of each entry's key, so it must not move between runs,
    and a per-checkout directory never serves another host's entries.
    Opt out with ``RAFT_TPU_NO_COMPILE_CACHE=1``.
    """
    if _os.environ.get("RAFT_TPU_NO_COMPILE_CACHE"):  # raft-tpu: ignore[ENVREG] package-init bootstrap, runs before core.env exists
        return
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_compilation_cache_dir is not None:
        return  # an in-process jax.config setting wins as well
    cache_dir = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )
    try:
        _os.makedirs(cache_dir, exist_ok=True)
    except OSError:  # read-only checkout: run uncached
        return
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_enable_persistent_compile_cache()

from raft_tpu.core.resources import Resources, DeviceResources, default_resources

__all__ = [
    "Resources",
    "DeviceResources",
    "default_resources",
    "__version__",
]
