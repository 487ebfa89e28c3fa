"""Pallas TPU kernels for the hot paths.

The reference spends its hand-written-kernel budget on exactly these spots
(SURVEY §2.2/§2.4/§2.8): k-selection (matrix/detail/select_radix.cuh,
select_warpsort.cuh), fused distance+reduction (distance/fused_l2_nn-inl.cuh,
spatial/knn/detail/fused_l2_knn-inl.cuh), and the IVF-PQ LUT scan
(neighbors/detail/ivf_pq_compute_similarity-inl.cuh).  On TPU the XLA
formulations of these are already strong, so each Pallas kernel here has
an XLA twin that tests hold it to (A/B by ``python -m raft_tpu.bench
prims``).

Dispatch: every kernel call site asks ``use_pallas()``, which consults
RAFT_TPU_PALLAS:
  - "0"    — never (pure XLA paths)
  - "1"    — always (interpret mode off-TPU; for tests)
  - "auto" — (default) on TPU backends only
"""

from __future__ import annotations

import threading

import jax

from raft_tpu.core import env as _env


def _platform() -> str:
    return jax.devices()[0].platform


def use_pallas() -> bool:
    mode = _env.env_str("RAFT_TPU_PALLAS", "auto")
    if mode == "0":
        return False
    if mode == "1":
        return True
    return _platform() == "tpu"


def select_k_enabled() -> bool:
    """Per-kernel revert knob under the master gate: the fused k-selection
    (kernels/select_k.py) routes from ops.matrix only when ``use_pallas()``
    AND this knob hold — so a select_k-specific regression can be rolled
    back without losing the scan kernels."""
    return _env.env_bool("RAFT_TPU_PALLAS_SELECT_K", True)


def cagra_fused_enabled() -> bool:
    """Per-kernel revert knob for the fused CAGRA hop
    (kernels/cagra_traverse.py), same contract as ``select_k_enabled``."""
    return _env.env_bool("RAFT_TPU_PALLAS_CAGRA", True)


# ---------------------------------------------------------------------------
# live kernel-path attribution
#
# The routing decisions above (and their per-leg twins inside
# neighbors/ivf_flat.py, neighbors/ivf_pq.py) happen in host Python on
# every search call, but the *outcome* — which leg actually ran — was
# visible only in frozen bench records.  The serve layer wants it per
# dispatch, so each routing branch stamps the leg it took into a
# thread-local and the batcher consumes the stamp right after the search
# callable returns (same thread, zero locks, zero clock calls).  Values
# are a tiny closed vocabulary: "pallas", "xla", "xla_filter_fallback"
# (the per-row-filter XLA leg), "sharded" (SPMD shard_map dispatch, where
# per-leg stamps would fire at trace time only), "sharded_graph" (the
# partitioned-graph CAGRA SPMD dispatch — separated from "sharded" so
# ledger hotspots and bench records can tell the traversal from the
# brute-refine control arm).

_kernel_path_tls = threading.local()


def stamp_kernel_path(path: str) -> None:
    """Record which kernel leg the current search call routed to."""
    _kernel_path_tls.value = path


def consume_kernel_path(default: str = "unknown") -> str:
    """Pop the stamp left by the last search on this thread (or
    ``default`` when the search ran elsewhere, e.g. on hedge threads)."""
    path = getattr(_kernel_path_tls, "value", None)
    _kernel_path_tls.value = None
    return path if path is not None else default


def interpret_mode() -> bool:
    """Pallas interpret=True off-TPU so kernels are testable on CPU
    (SURVEY §5: sanitizer analog — interpret mode is also the OOB guard)."""
    return _platform() != "tpu"


from raft_tpu.kernels.fused_knn import fused_l2_topk  # noqa: E402
from raft_tpu.kernels.fused_argmin import fused_l2_argmin  # noqa: E402
from raft_tpu.kernels.ivf_scan import ivf_scan_probe_major  # noqa: E402
from raft_tpu.kernels.select_k import select_k_pallas  # noqa: E402
from raft_tpu.kernels.cagra_traverse import cagra_fused_hop  # noqa: E402

__all__ = [
    "use_pallas",
    "select_k_enabled",
    "cagra_fused_enabled",
    "interpret_mode",
    "stamp_kernel_path",
    "consume_kernel_path",
    "fused_l2_topk",
    "fused_l2_argmin",
    "ivf_scan_probe_major",
    "select_k_pallas",
    "cagra_fused_hop",
]
