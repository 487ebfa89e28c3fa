"""Continuous ragged batching: one packed dispatch for heterogeneous
requests, retiring the pow2 pad ladder's executable lattice.

The classic :class:`~raft_tpu.serve.batcher.MicroBatcher` fixes *batch*
shapes with the pow2 bucket ladder, but every other request-level degree
of freedom — top-``k``, the sample-filter bitset — still leaks into the
executable universe: a service that wants per-request k and filters in
classic mode runs one batcher variant per (k, filter) pair, warms
(buckets × ks × filters) executables, and still recompiles the first
time a novel combination shows up.

Ragged mode collapses that lattice to **one executable per capacity
bucket** by making ``k`` and the filter *data* instead of shape:

- Every dispatch computes the spec's static ``k_max`` result columns.
  Each request's own ``k`` rides in a ``[cap] int32`` descriptor column;
  :func:`raft_tpu.ops.matrix.mask_row_k` applies it inside the
  executable (positions past a row's k surface as id −1 at the worst
  distance) and the future slices its ``[:k]`` columns host-side after
  copy-out.
- Filters are registered up front in a :class:`FilterRegistry`, which
  packs them as rows of one ``[F, W] uint32`` table; a request carries
  only its ``fid``.  The dispatcher gathers the batch's rows host-side
  (numpy — an eager device gather would trace a fresh executable every
  time ``F`` grows) into a :class:`~raft_tpu.core.bitset.RowFilter`
  whose shape depends on the bucket only.  fid 0 is the reserved
  all-pass row, so unfiltered and filtered requests pack together.
- Tombstones compose unchanged: the mutable search folds the deleted
  mask into the per-row pass words before the backend runs
  (:func:`raft_tpu.neighbors._common.resolve_pass_filter`).

Register filters **before** :meth:`~raft_tpu.serve.service.SearchService.
warmup`.  Registration itself never recompiles the XLA legs (the table
gather is host-side and ``W`` is fixed at construction), but two paths
key on filter-derived *Python* values: cagra widens its internal search
width from the registry's pinned minimum pass count, and the fused
Pallas ivf_flat leg packs the whole table per list (``F`` in its
operand shapes).  A post-warmup registration that changes either costs
one compile per bucket on the next dispatch — surfaced loudly as a
``hot_recompile`` obs event, never silently.

Admission becomes *continuous* with the pipeline enabled: the batcher
worker claims the in-flight window slot before cutting the batch, so
requests keep packing into the forming batch for exactly as long as the
device window is full — batch fill rises (and padding waste falls) when
the device, not arrival, is the bottleneck.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import kernels as _kernels
from raft_tpu.core import env as _env
from raft_tpu.core.bitset import Bitset, RowFilter
from raft_tpu.core.trace import traced
from raft_tpu.distance import DISTANCE_TYPES
from raft_tpu.obs import explain as _explain
from raft_tpu.ops.matrix import mask_row_k
from raft_tpu.serve.mutation import MutableIndex


def _params_info(search_params) -> Optional[dict]:
    """Host-side summary of a SearchParams object for explain stamps —
    only the effort-relevant Python values, never the object itself."""
    if search_params is None:
        return None
    out = {}
    for attr in ("n_probes", "itopk_size", "search_width", "lut_dtype"):
        val = getattr(search_params, attr, None)
        if val is not None:
            out[attr] = str(val) if attr == "lut_dtype" else val
    return out or None


@dataclass(frozen=True)
class RaggedSpec:
    """Ragged-mode configuration for a service (or one batcher).

    ``k_max`` is the static top-k capacity every dispatch computes;
    per-request k may not exceed it.  ``filters`` controls whether the
    per-request filter-id column is wired through (off saves the
    RowFilter gather for services that never register filters).
    """

    k_max: int = 32
    filters: bool = True

    @classmethod
    def from_env(cls) -> "RaggedSpec":
        return cls(
            k_max=_env.env_int("RAFT_TPU_RAGGED_KMAX", 32),
            filters=_env.env_bool("RAFT_TPU_RAGGED_FILTERS", True),
        )


class FilterRegistry:
    """Registered sample filters for one ragged-served index.

    Filters pack as rows of one ``[F, W] uint32`` table over a fixed
    global-id space of ``n_bits`` ids; requests reference them by row
    index (fid).  fid 0 is the reserved all-pass row.  Registration is
    append-only — fids stay stable for the life of the served index.

    Semantics: a filter *allows* exactly the ids whose bit is set.  Ids
    past a registered mask's length are denied (zero-filled), but ids
    past the registry's own ``n_bits`` — e.g. side-buffer rows upserted
    after construction — pass every filter (the serve layer treats
    uncovered ids as unconstrained; see ``MutableIndex._side_passes``).
    """

    def __init__(self, n_bits: int):
        if n_bits < 1:
            raise ValueError(f"n_bits must be >= 1, got {n_bits}")
        self.n_bits = int(n_bits)
        self._n_words = (self.n_bits + 31) // 32
        self._lock = threading.Lock()
        all_pass = np.full((1, self._n_words), 0xFFFFFFFF, dtype=np.uint32)
        tail = self.n_bits % 32
        if tail:
            # mask the tail bits so pass counts (cagra's search-width
            # input) stay exact
            all_pass[0, -1] = np.uint32((1 << tail) - 1)
        self._table = all_pass
        self._pass_counts = [self.n_bits]

    def __len__(self) -> int:
        with self._lock:
            return self._table.shape[0]

    def register(self, mask) -> int:
        """Register one filter; returns its fid.

        ``mask`` is a bool array over global ids (shorter than ``n_bits``
        is zero-extended: uncovered ids are denied) or a
        :class:`~raft_tpu.core.bitset.Bitset`.
        """
        if isinstance(mask, Bitset):
            if mask.n_bits > self.n_bits:
                raise ValueError(
                    f"filter covers {mask.n_bits} ids but the registry "
                    f"was sized for {self.n_bits}"
                )
            src = np.asarray(mask.words, dtype=np.uint32)
            words = np.zeros((self._n_words,), dtype=np.uint32)
            words[: src.shape[0]] = src
            count = int(np.unpackbits(
                words.view(np.uint8), bitorder="little"
            ).sum())
        else:
            mask = np.asarray(mask, dtype=bool).reshape(-1)
            if mask.shape[0] > self.n_bits:
                raise ValueError(
                    f"filter covers {mask.shape[0]} ids but the registry "
                    f"was sized for {self.n_bits}"
                )
            padded = np.zeros((self._n_words * 32,), dtype=np.uint8)
            padded[: mask.shape[0]] = mask
            words = np.packbits(padded, bitorder="little").view(np.uint32)
            count = int(mask.sum())
        with self._lock:
            fid = self._table.shape[0]
            # replace, never mutate: snapshot() hands out the old array
            # without copying and dispatches may still hold it
            self._table = np.concatenate(
                [self._table, words[None, :]], axis=0
            )
            self._pass_counts.append(count)
        return fid

    def contains(self, fid: int) -> bool:
        with self._lock:
            return 0 <= fid < self._table.shape[0]

    def snapshot(self) -> Tuple[np.ndarray, int]:
        """(table [F, W], min pass count) — one consistent view.

        The min pass count is the registry-wide floor, pinned so cagra's
        filter-aware search widening sees the same host int on every
        batch regardless of which fids happen to be present — the value
        changes only on registration, never per dispatch.
        """
        with self._lock:
            return self._table, min(self._pass_counts)


class RaggedSearcher:
    """The batcher-facing search fn for one ragged-served index.

    ``__call__(queries [cap, d], row_k [cap], row_fid [cap])`` resolves
    the registry once per batch (the same hot-swap atomicity boundary as
    the classic path), materializes the batch's per-request
    :class:`~raft_tpu.core.bitset.RowFilter` from the filter table
    (host-side numpy gather), and runs the merged mutable search at the
    bucket's static ``k_max`` with per-row k masking inside the
    executable.  Everything shape-relevant depends only on the bucket:
    zero recompiles after a one-variant-per-bucket warmup.
    """

    def __init__(self, service, name: str, spec: RaggedSpec,
                 filters: Optional[FilterRegistry], degraded=None,
                 effort=None):
        self._service = service
        self._name = name
        self._spec = spec
        self._filters = filters
        # optional serve.overload.DegradedModeManager: under sustained
        # pressure its level prescribes reduced-effort search params
        self._degraded = degraded
        # optional serve.effort.EffortArbiter: when present it is the
        # single source of the effective effort level (overload clamp +
        # autotuner walk) and supersedes the direct degraded lookup
        self._effort = effort

    @property
    def filters(self) -> Optional[FilterRegistry]:
        return self._filters

    @traced("serve.ragged.dispatch")
    def __call__(self, queries: jax.Array, row_k: jax.Array,
                 row_fid: jax.Array):
        # resolve once per BATCH: the whole packed batch is answered
        # by one index version (hot-swap atomicity boundary)
        index, _version = self._service.registry.get_versioned(self._name)
        row_k = jnp.asarray(row_k, jnp.int32)
        sample_filter = None
        if self._filters is not None:
            table, min_pass = self._filters.snapshot()
            # HOST gather (numpy in, numpy indexing): the RowFilter's
            # words depend on the bucket size only, so the table may
            # grow without changing any traced shape
            sample_filter = RowFilter.from_table(
                table, np.asarray(row_fid, np.int32),
                self._filters.n_bits, pass_count=min_pass,
            )
        if not isinstance(index, MutableIndex):
            # ShardedIndex (and anything else duck-typed): run at k_max
            # and mask each row's k after it.  Registered filters ride as
            # a per-query global-id RowFilter — the packed table is tiny
            # and replicates to every shard (ShardedIndex.search rebases
            # it per shard; one extra cached executable per k, never a
            # per-(k × filter) lattice)
            # kernel-path attribution: the SPMD body traces once, so the
            # routing stamp happens here on the host, not inside search
            # (graph-mode CAGRA serves filtered traffic through its exact
            # brute-refine core, so a filtered dispatch stamps "sharded")
            graph_walk = (
                getattr(index, "graph_mode", False) and sample_filter is None
            )
            _kernels.stamp_kernel_path(
                "sharded_graph" if graph_walk else "sharded"
            )
            if _explain.enabled():
                # host-side decision stamp — the batcher consumes it on
                # this same thread right after the call
                _explain.stamp_dispatch({
                    "k_max": self._spec.k_max,
                    "sharded": True,
                    "filters": sample_filter is not None,
                })
            if sample_filter is not None:
                dist, ids = index.search(
                    queries, self._spec.k_max, sample_filter=sample_filter
                )
            else:
                dist, ids = index.search(queries, self._spec.k_max)
            select_min = DISTANCE_TYPES[index.metric] != "inner_product"
            return mask_row_k(dist, ids, row_k, select_min=select_min)
        search_params = None
        if self._effort is not None:
            # arbitrated effort level (overload clamp + autotuner); every
            # (bucket, level) variant was warmed by the batcher's
            # level-pinned warmup
            search_params = self._effort.apply(index)
        elif self._degraded is not None:
            # reduced-effort params under pressure; every (bucket, level)
            # variant was warmed by the batcher's level-pinned warmup
            search_params = self._degraded.params_for(index)
        if _explain.enabled():
            # effective effort params actually handed to the backend —
            # recorded where the decision is made, zero extra derivation
            _explain.stamp_dispatch({
                "k_max": self._spec.k_max,
                "filters": sample_filter is not None,
                "effort_params": _params_info(search_params),
            })
        return index.search(
            queries, self._spec.k_max,
            sample_filter=sample_filter, row_k=row_k,
            search_params=search_params,
        )
