"""Analytical cost model for the hand-written Pallas kernels.

The roofline attribution in obs/cost.py reads flops / bytes from XLA's
compiled cost analysis.  That works for the XLA legs, but a Pallas
kernel is an opaque custom call on TPU — XLA reports nothing for it, so
its cost report would carry blank flops / bytes / roofline columns.

This module is the one owner of the per-kernel analytical cost formulas:

- each kernel wrapper **notes** its cost at trace time
  (:func:`note` inside a :func:`capture` scope opened by
  ``obs.cost.analyze_callable``), so compiled-cost reports can be
  supplemented exactly where XLA came back empty;
- the same :class:`KernelCost` converts to a ``pl.CostEstimate``
  (:meth:`KernelCost.as_pallas`) handed to ``pallas_call`` so the TPU
  scheduler sees honest numbers too.

Formulas count *algorithmic* work (VPU compare/select ops and MXU
multiply-adds) and *HBM-crossing* bytes — VMEM-resident scratch traffic is
deliberately excluded, matching what XLA's cost analysis counts for the
equivalent HLO and keeping the pallas/XLA roofline columns comparable.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple


@dataclass(frozen=True)
class KernelCost:
    """flops / bytes_accessed / transcendentals of one kernel dispatch."""

    flops: int
    bytes_accessed: int
    transcendentals: int = 0

    def __add__(self, other: "KernelCost") -> "KernelCost":
        return KernelCost(
            self.flops + other.flops,
            self.bytes_accessed + other.bytes_accessed,
            self.transcendentals + other.transcendentals,
        )

    def as_pallas(self):
        """The ``pl.CostEstimate`` handed to ``pallas_call`` (imported
        lazily so this module stays importable without Pallas)."""
        from jax.experimental import pallas as pl

        return pl.CostEstimate(
            flops=int(self.flops),
            bytes_accessed=int(self.bytes_accessed),
            transcendentals=int(self.transcendentals),
        )


# ---------------------------------------------------------------------------
# per-kernel formulas (one owner each; kernels import these, never inline)


def select_k_cost(rows: int, n: int, k: int, *, itemsize: int = 4) -> KernelCost:
    """kernels/select_k.py: k rounds of masked min-extraction over
    [rows, n] — each round is ~6 elementwise compare/select passes (mask,
    min, tie-min, first-position, payload pick, removal)."""
    flops = 6 * rows * n * k
    # in: values + tie keys + payloads; out: k values + k ids per row
    bytes_accessed = rows * n * (itemsize + 8) + rows * k * (itemsize + 4)
    return KernelCost(int(flops), int(bytes_accessed))


def cagra_traverse_cost(
    tile: int, width: int, deg: int, d: int, itopk: int, *, itemsize: int = 4
) -> KernelCost:
    """kernels/cagra_traverse.py: one fused hop — per (query, parent):
    MXU scoring of deg neighbor rows (2·deg·d MACs), dedup membership
    (deg·itopk compares) and a fold_topk merge (itopk rounds over
    itopk+deg candidates)."""
    per_parent = (
        2 * deg * d                      # MXU candidate scoring
        + deg * itopk                    # visited-dedup membership
        + 6 * itopk * (itopk + deg)      # fold_topk extraction rounds
    )
    flops = tile * width * per_parent
    bytes_accessed = tile * width * (
        deg * d * itemsize               # dataset rows DMA'd per parent
        + deg * 4                        # its candidate ids (no graph operand)
    ) + tile * (d * itemsize + 3 * itopk * 4 * 2)  # queries + buffers in/out
    return KernelCost(int(flops), int(bytes_accessed))


def ivf_scan_cost(
    n_blocks: int, g: int, cap: int, rot: int, kk: int, *, itemsize: int = 4
) -> KernelCost:
    """kernels/ivf_scan.py (both schedules): per (block, list) — MXU
    scoring of a [g, cap] tile against [cap, rot] rows plus the VMEM
    fold; ``n_blocks`` counts (bucket) or (query-block · probe) steps."""
    per_block = 2 * g * cap * rot + 6 * kk * (kk + cap) * g
    flops = n_blocks * per_block
    bytes_accessed = n_blocks * (
        cap * rot * itemsize + cap * 8 + g * rot * 4
    ) + n_blocks * g * kk * 8
    return KernelCost(int(flops), int(bytes_accessed))


def fused_knn_cost(
    n_q: int, n: int, d: int, k: int, *, itemsize: int = 4
) -> KernelCost:
    """kernels/fused_knn.py: tiled brute-force distance + per-tile
    fold — 2·d MACs per (query, row) pair plus the running-k merge."""
    flops = n_q * n * (2 * d + 6 * k)
    bytes_accessed = (
        (n_q + n) * d * itemsize     # queries + dataset tiles
        + n * itemsize               # sqnorm row
        + n_q * k * (itemsize + 4)   # (value, id) outputs
    )
    return KernelCost(int(flops), int(bytes_accessed))


def fused_argmin_cost(
    n: int, n_centers: int, d: int, *, itemsize: int = 4
) -> KernelCost:
    """kernels/fused_argmin.py: 1-NN assignment — 2·d MACs per
    (row, center) pair plus the per-tile running argmin."""
    flops = n * n_centers * (2 * d + 3)
    bytes_accessed = (
        (n + n_centers) * d * itemsize
        + n_centers * itemsize
        + n * (itemsize + 4)
    )
    return KernelCost(int(flops), int(bytes_accessed))


# ---------------------------------------------------------------------------
# trace-time capture: kernels note their cost while a lowering is being
# traced; obs.cost.analyze_callable opens the scope and folds the noted
# totals into compiled-cost reports where XLA reported nothing (TPU's
# opaque custom-call case)

_tls = threading.local()


@contextlib.contextmanager
def capture() -> Iterator[List[Tuple[str, KernelCost]]]:
    """Collect every :func:`note` issued while the scope is open (e.g.
    during a ``jax.jit(...).lower(...)`` trace).  Nested scopes shadow —
    the inner scope owns the notes."""
    prev = getattr(_tls, "notes", None)
    _tls.notes = []
    try:
        yield _tls.notes
    finally:
        _tls.notes = prev


def note(name: str, cost: KernelCost) -> None:
    """Record one kernel dispatch's analytical cost (no-op outside a
    :func:`capture` scope — kernels call this unconditionally)."""
    notes = getattr(_tls, "notes", None)
    if notes is not None:
        notes.append((name, cost))


def noted_total(
    notes: List[Tuple[str, KernelCost]]
) -> Optional[KernelCost]:
    """Sum a capture scope's notes (None when nothing was noted)."""
    if not notes:
        return None
    total = KernelCost(0, 0, 0)
    for _, c in notes:
        total = total + c
    return total
