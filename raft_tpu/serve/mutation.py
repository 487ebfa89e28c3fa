"""Streaming mutation for served indexes: upsert + tombstone delete.

ANN structures (IVF lists, CAGRA graphs) are batch-built; rebuilding per
write is not an online option.  The serving answer here is the classic
side-buffer + tombstone design:

* **delete(ids)** flips bits in a tombstone :class:`~raft_tpu.core.bitset.
  Bitset` over the main index's id space.  Every neighbors backend grew a
  ``deleted_mask`` argument for exactly this — tombstoned rows are
  filtered *inside* the main search (surfacing as id −1 at the worst
  distance), so deletes are visible immediately without touching the
  built structure.
* **upsert(vectors)** appends to a host-side growing buffer.  Queries scan
  the side buffer brute-force (it is small by construction — a background
  rebuild folds it into the main index; see :meth:`MutableIndex.rebuild`)
  and the two candidate lists merge through one
  :func:`~raft_tpu.ops.matrix.select_k`.
* Upserting an existing id tombstones the old row first, so an id never
  yields two results.

Shape discipline: the side buffer is padded to a power-of-two capacity
(occupancy tracked host-side, dead slots masked via the same Bitset
filter), so the merged search only ever sees O(log growth) distinct side
shapes — compiles stay off the steady-state hot path.

Thread-safety: mutations and snapshot-taking are guarded by a lock;
searches run on an immutable snapshot taken under that lock, so a search
never observes a half-applied mutation (and a hot-swap never tears a
batch).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.bitset import Bitset, RowFilter
from raft_tpu.core import serialize as ser
from raft_tpu.core.trace import trace_range, traced
from raft_tpu.distance import DISTANCE_TYPES
from raft_tpu.kernels.toolkit import next_pow2
from raft_tpu.ops.matrix import mask_row_k, select_k

KINDS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")

_SERVE_SERIALIZATION_VERSION = 1

_MIN_SIDE_CAP = 8

#: candidates per result row that an index with ``refine_dataset`` re-ranks
#: exactly (the reference recipe's refine ratio)
REFINE_RATIO = 4


def _kind_module(kind: str):
    from raft_tpu import neighbors

    if kind not in KINDS:
        raise ValueError(f"unknown index kind {kind!r}; expected one of {KINDS}")
    return getattr(neighbors, kind)


def _infer_kind(index) -> str:
    mod = type(index).__module__.rsplit(".", 1)[-1]
    if mod not in KINDS:
        raise ValueError(
            f"cannot infer index kind from {type(index)!r}; pass kind="
        )
    return mod


# canonical pow2 helper lives in kernels.toolkit; the private alias stays
# importable (compactor sizes its shadow side buffers through it)
_next_pow2 = next_pow2


def _bitset_from_np(mask: np.ndarray) -> Bitset:
    """Pack a host bool mask into a Bitset with numpy-only packing
    (``Bitset.from_mask`` would run jnp scatter ops for the same job)."""
    n = mask.shape[0]
    nw = (n + 31) // 32
    padded = np.zeros(nw * 32, np.uint8)
    padded[:n] = mask
    words = np.packbits(padded, bitorder="little").view(np.uint32)
    return Bitset(jnp.asarray(words), n)


@dataclass(frozen=True)
class _Snapshot:
    """Immutable view a search runs against (see thread-safety note)."""

    tombstones: Optional[Bitset]     # over main rows, None when no deletes
    side_data: Optional[jax.Array]   # [cap, dim] padded, None when empty
    side_ids: Optional[jax.Array]    # [cap] global ids (-1 on dead slots)
    side_live: Optional[Bitset]      # pass-filter over side slots
    generation: int
    main_ids: Optional[jax.Array] = None  # row → global id, None = identity


class MutableIndex:
    """A served index: main (built) structure + tombstones + side buffer.

    Parameters
    ----------
    index:
        A built ``brute_force``/``ivf_flat``/``ivf_pq``/``cagra`` index.
        Main rows are assumed to carry ids ``0..index.size-1`` (what the
        builders assign).
    kind:
        Backend name; inferred from the index type when omitted.
    search_params:
        Per-kind ``SearchParams`` for the main search (ignored for
        brute_force).  Defaults to the backend's defaults.
    main_ids:
        Optional ``[index.size]`` int array mapping main *row* i to its
        global id.  A compacted shadow rebuild packs surviving rows
        densely (builders assign 0..m-1) but must keep serving the
        original ids — the map is applied after the main search and
        before the side-buffer merge.  Tombstones stay row-indexed
        (the in-search filter tests the backend's stored ids, which are
        rows).  ``None`` (the default, and what direct builds want)
        means identity.
    refine_dataset:
        Exact re-rank of the main search (the reference's IVF-PQ search +
        ``refine`` recipe): when given, the main search returns
        ``k * REFINE_RATIO`` candidates and
        :func:`~raft_tpu.neighbors.refine.refine` re-scores them against
        ``refine_dataset`` (``[main_size, dim]``) to the exact top-k.
        Compressed backends need it for recall their codes cannot reach
        alone.  The index keeps only the lane-padded device copy,
        ``refine_rows`` (:func:`~raft_tpu.neighbors.refine.prepare_rows`);
        the ``refine_dataset`` attribute reads it back at ``[main_size,
        dim]``.
    """

    def __init__(self, index, *, kind: Optional[str] = None, search_params=None,
                 main_ids: Optional[np.ndarray] = None, refine_dataset=None):
        self.kind = kind if kind is not None else _infer_kind(index)
        mod = _kind_module(self.kind)  # validates kind
        self.index = index
        self.metric = index.metric
        self.dim = int(index.dim)
        self.main_size = int(index.size)
        if search_params is None and self.kind != "brute_force":
            search_params = mod.SearchParams()
        self.search_params = search_params
        if refine_dataset is not None and (
            tuple(refine_dataset.shape) != (self.main_size, self.dim)
        ):
            raise ValueError(
                f"refine_dataset has shape {tuple(refine_dataset.shape)}, "
                f"the index needs [{self.main_size}, {self.dim}]"
            )
        # the rows the refine gathers, lane-padded once here so that no
        # refine dispatch relayouts them (neighbors.refine.prepare_rows)
        if refine_dataset is not None:
            from raft_tpu.neighbors.refine import prepare_rows

            refine_dataset = prepare_rows(refine_dataset)
        self.refine_rows = refine_dataset

        if main_ids is not None:
            main_ids = np.asarray(main_ids, dtype=np.int64).reshape(-1)
            if main_ids.shape[0] != self.main_size:
                raise ValueError(
                    f"main_ids has {main_ids.shape[0]} entries for "
                    f"{self.main_size} main rows"
                )
            if np.array_equal(main_ids, np.arange(self.main_size)):
                main_ids = None  # identity: keep the remap off the search

        self._lock = threading.Lock()
        # row → global id map; immutable post-construction like the main
        # structure, so its device copy is built once here (not per snapshot)
        self._main_ids = main_ids
        self._main_ids_dev = (
            jnp.asarray(main_ids.astype(np.int32))
            if main_ids is not None else None
        )
        # main-row tombstones, host-side; packed lazily into a Bitset
        self._deleted = np.zeros((self.main_size,), dtype=bool)
        self._n_deleted = 0
        # rows tombstoned at construction (compaction padding sentinels):
        # part of the filter, but not mutation backlog — pending_mutations
        # subtracts them so a fresh compaction doesn't re-trigger itself
        self._n_structural = 0
        # side buffer, host-side source of truth
        self._side_data = np.zeros((0, self.dim), dtype=np.float32)
        self._side_ids = np.zeros((0,), dtype=np.int64)
        self._side_live = np.zeros((0,), dtype=bool)
        self._side_count = 0          # occupied slots (live or dead)
        self._next_id = (
            self.main_size if main_ids is None
            else (int(main_ids.max()) + 1 if main_ids.size else 0)
        )
        self._generation = 0
        # monotonic stamp of when the mutation backlog last became
        # non-empty; None while empty.  Feeds the freshness SLI: age of
        # the oldest un-compacted mutation, not a per-row watermark.
        self._backlog_since: Optional[float] = None
        # set by a compaction promote: mutations arriving after the
        # hot-swap forward to the replacement so they are never lost
        self._retired_to: Optional["MutableIndex"] = None
        self._snapshot_cache: Optional[_Snapshot] = None
        self._refresh_snapshot_locked()

    # -- introspection -------------------------------------------------------
    @property
    def size(self) -> int:
        """Live vectors (main minus tombstones, plus live side rows)."""
        with self._lock:
            return (
                self.main_size - self._n_deleted + int(self._side_live.sum())
            )

    @property
    def refine_dataset(self):
        """The exact refine rows at ``[main_size, dim]``, or None.  A slice
        of ``refine_rows`` made on each read, for save, compaction and
        sharding; the search reads ``refine_rows`` itself."""
        rows = self.refine_rows
        if rows is None or rows.shape[1] == self.dim:
            return rows
        return rows[:, : self.dim]

    @property
    def generation(self) -> int:
        """Monotonic mutation counter (bumps on every upsert/delete)."""
        with self._lock:
            return self._generation

    def device_bytes(self) -> int:
        """Bytes held by this index's arrays (main structure + serve
        state).  Feeds the per-version live-buffer gauges
        (:func:`raft_tpu.obs.cost.refresh_live_buffer_gauges`): the number
        an operator compares across versions to spot a swapped-out index
        whose arrays never freed."""

        def _nb(x) -> int:
            nb = getattr(x, "nbytes", None)
            return int(nb) if isinstance(nb, (int, np.integer)) else 0

        total = sum(_nb(v) for v in vars(self.index).values())
        total += _nb(self._main_ids) + _nb(self._main_ids_dev)
        total += _nb(self.refine_rows)
        with self._lock:
            total += _nb(self._side_data) + _nb(self._side_ids)
            total += _nb(self._side_live) + _nb(self._deleted)
            snap = self._snapshot_cache
        if snap is not None:
            for arr in (snap.side_data, snap.side_ids):
                total += _nb(arr)
            for bs in (snap.tombstones, snap.side_live):
                if bs is not None:
                    total += _nb(bs.words)
        return total

    def lane_pad_bytes(self) -> int:
        """Bytes of zero lanes the resident arrays hold so that each row
        is whole 128-lane tiles (``neighbors._common.lane_pad``): the
        IVF-PQ scan cache past ``rot_dim`` and the refine rows past
        ``dim``.  0 where the widths are lane multiples already."""
        total = 0
        if self.kind == "ivf_pq":
            ld = self.index.list_data
            extra = ld.shape[-1] - self.index.rot_dim
            total += (
                int(np.prod(ld.shape[:-1])) * extra
                * np.dtype(ld.dtype).itemsize
            )
        rows = self.refine_rows
        if rows is not None:
            total += (
                rows.shape[0] * (rows.shape[1] - self.dim)
                * np.dtype(rows.dtype).itemsize
            )
        return int(total)

    def contains(self, id_: int) -> bool:
        with self._lock:
            if self._retired_to is not None:
                succ = self._retired_to
            else:
                if self._main_ids is None:
                    if 0 <= id_ < self.main_size and not self._deleted[id_]:
                        return True
                else:
                    rows = np.flatnonzero(self._main_ids == id_)
                    if rows.size and not self._deleted[rows[0]]:
                        return True
                hits = (self._side_ids == id_) & self._side_live
                return bool(hits.any())
        return succ.contains(id_)

    # -- mutation ------------------------------------------------------------
    @traced("serve.upsert")
    def upsert(self, vectors, ids=None) -> np.ndarray:
        """Insert (or replace) vectors; returns their global ids.

        Without ``ids`` fresh ids are allocated past the main index's
        range.  With ``ids``, any existing row under the same id (main or
        side) is tombstoned first — upsert semantics.
        """
        vectors = np.atleast_2d(np.asarray(vectors, dtype=np.float32))
        if vectors.ndim != 2 or vectors.shape[1] != self.dim:
            raise ValueError(
                f"expected vectors of dim {self.dim}, got {vectors.shape}"
            )
        m = vectors.shape[0]
        with self._lock:
            if self._retired_to is not None:
                # compaction promoted a successor while the caller held a
                # reference to this version: forward so the write lands in
                # the serving index instead of vanishing with this one
                succ = self._retired_to
            else:
                if ids is None:
                    ids = np.arange(
                        self._next_id, self._next_id + m, dtype=np.int64
                    )
                    self._next_id += m
                else:
                    ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
                    if ids.shape != (m,):
                        raise ValueError(
                            f"ids shape {ids.shape} does not match {m} vectors"
                        )
                    self._delete_locked(ids)
                    self._next_id = max(self._next_id, int(ids.max()) + 1)
                self._reserve_locked(self._side_count + m)
                sl = slice(self._side_count, self._side_count + m)
                self._side_data[sl] = vectors
                self._side_ids[sl] = ids
                self._side_live[sl] = True
                self._side_count += m
                self._bump_locked()
                return ids
        return succ.upsert(vectors, ids)

    @traced("serve.delete")
    def delete(self, ids) -> int:
        """Tombstone ids (main or side); returns how many were live."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        with self._lock:
            if self._retired_to is None:
                n = self._delete_locked(ids)
                self._bump_locked()
                return n
            succ = self._retired_to
        return succ.delete(ids)

    def _delete_locked(self, ids: np.ndarray) -> int:
        n_removed = 0
        if self._main_ids is None:
            rows = ids[(ids >= 0) & (ids < self.main_size)]
        else:
            rows = np.flatnonzero(np.isin(self._main_ids, ids))
        if rows.size:
            was_live = ~self._deleted[rows]
            n_removed += int(np.unique(rows[was_live]).size)
            self._deleted[rows] = True
            self._n_deleted = int(self._deleted.sum())
        if self._side_count:
            hits = np.isin(self._side_ids, ids) & self._side_live
            n_removed += int(hits.sum())
            self._side_live[hits] = False
        return n_removed

    def _reserve_locked(self, n: int) -> None:
        cap = self._side_data.shape[0]
        if n <= cap:
            return
        new_cap = max(_MIN_SIDE_CAP, _next_pow2(n))
        grown = np.zeros((new_cap, self.dim), dtype=np.float32)
        grown[:cap] = self._side_data
        self._side_data = grown
        ids = np.full((new_cap,), -1, dtype=np.int64)
        ids[:cap] = self._side_ids
        self._side_ids = ids
        live = np.zeros((new_cap,), dtype=bool)
        live[:cap] = self._side_live
        self._side_live = live

    def _bump_locked(self) -> None:
        self._generation += 1
        deletes = self._n_deleted - self._n_structural
        side = int(self._side_live.sum()) if self._side_count else 0
        if deletes <= 0 and side <= 0:
            self._backlog_since = None
        elif self._backlog_since is None:
            self._backlog_since = time.monotonic()
        self._refresh_snapshot_locked()

    def _refresh_snapshot_locked(self) -> None:
        """Rebuild the search snapshot NOW, at mutation time.

        Mutations are host-side API calls, so this always runs in an eager
        context — building lazily on first search instead would stage the
        jnp constants as tracers when that search happens inside a
        shard_map/jit trace (the replica path) and leak them through the
        cache."""
        tomb = _bitset_from_np(self._deleted) if self._n_deleted else None
        if self._side_count:
            side_data = jnp.asarray(self._side_data)
            side_ids = jnp.asarray(
                np.where(self._side_live, self._side_ids, -1).astype(np.int32)
            )
            side_live = _bitset_from_np(self._side_live)
        else:
            side_data = side_ids = side_live = None
        self._snapshot_cache = _Snapshot(
            tomb, side_data, side_ids, side_live, self._generation,
            self._main_ids_dev,
        )

    # -- search --------------------------------------------------------------
    def _snapshot(self) -> _Snapshot:
        with self._lock:
            return self._snapshot_cache

    def _main_search(self, queries, k, tombstones, sample_filter=None,
                     search_params=None):
        mod = _kind_module(self.kind)
        if self.kind == "brute_force":
            return mod.search(
                self.index, queries, k,
                deleted_mask=tombstones, sample_filter=sample_filter,
            )
        params = self.search_params if search_params is None \
            else search_params
        if self.refine_rows is None:
            return mod.search(
                params, self.index, queries, k,
                deleted_mask=tombstones, sample_filter=sample_filter,
            )
        from raft_tpu.neighbors.refine import refine

        _, cand = mod.search(
            params, self.index, queries, k * REFINE_RATIO,
            deleted_mask=tombstones, sample_filter=sample_filter,
        )
        return refine(
            self.refine_rows, queries, cand, k, metric=self.metric
        )

    def _side_passes(self, snap: _Snapshot, sample_filter):
        """Slot-space view of ``sample_filter`` for the side-buffer scan.

        The caller's filter is keyed by *global* ids; the side scan tests
        *slot* positions.  Gather each slot's bit through ``side_ids`` and
        AND with slot liveness.  Ids past the filter's bit range pass —
        a filter constrains only ids it covers, and upserted rows get ids
        allocated past any pre-registered filter's range.
        """
        if sample_filter is None:
            return snap.side_live
        live = snap.side_live.to_mask()       # [cap] bool
        sid = jnp.clip(snap.side_ids, 0)      # dead slots (-1) die via live
        in_range = snap.side_ids < jnp.int32(sample_filter.n_bits)
        word_ix = jnp.clip(sid // 32, 0, sample_filter.words.shape[-1] - 1)
        bit_ix = (sid % 32).astype(jnp.uint32)
        if isinstance(sample_filter, RowFilter):
            bit = (
                sample_filter.words[:, word_ix] >> bit_ix[None, :]
            ) & jnp.uint32(1)
            mask = jnp.where(in_range[None, :], bit == 1, True) & live[None, :]
            return RowFilter.from_mask_rows(mask)
        bit = (sample_filter.words[word_ix] >> bit_ix) & jnp.uint32(1)
        return Bitset.from_mask(jnp.where(in_range, bit == 1, True) & live)

    def _main_filter_rows(self, snap: _Snapshot, sample_filter):
        """Row-space view of ``sample_filter`` for a compacted main index.

        After compaction the backend's stored rows are dense (promotion
        renumbered them) while the caller's filter stays keyed by
        *global* ids — the ids results are remapped to and the ids the
        :class:`~raft_tpu.serve.ragged.FilterRegistry` was built over.
        Gather each stored row's bit through the compaction id map
        (``snap.main_ids``), exactly like :meth:`_side_passes` does
        through ``side_ids``.  Uncovered ids pass (a filter constrains
        only ids it covers); padding sentinels (gid −1) also pass here
        but never surface — promotion registered them as structural
        tombstones, which compose via ``deleted_mask``.
        """
        gids = snap.main_ids                  # [rows] int32, -1 = padding
        g = jnp.clip(gids, 0)
        covered = (gids >= 0) & (gids < jnp.int32(sample_filter.n_bits))
        word_ix = jnp.clip(g // 32, 0, sample_filter.words.shape[-1] - 1)
        bit_ix = (g % 32).astype(jnp.uint32)
        if isinstance(sample_filter, RowFilter):
            bit = (
                sample_filter.words[:, word_ix] >> bit_ix[None, :]
            ) & jnp.uint32(1)
            mask = jnp.where(covered[None, :], bit == 1, True)
            return RowFilter.from_mask_rows(mask)
        bit = (sample_filter.words[word_ix] >> bit_ix) & jnp.uint32(1)
        return Bitset.from_mask(jnp.where(covered, bit == 1, True))

    def search(self, queries, k: int, *, sample_filter=None,
               row_k=None, search_params=None
               ) -> Tuple[jax.Array, jax.Array]:
        """Merged top-k over main (tombstone-filtered) + side buffer.

        Returns (distances [q, k], ids [q, k]); pruned/padding slots are
        id −1 at the worst distance, like the backend searches.

        ``sample_filter`` (a :class:`~raft_tpu.core.bitset.Bitset`, or a
        :class:`~raft_tpu.core.bitset.RowFilter` with one pass-row per
        query — the ragged path's form) restricts results by global id;
        it composes with tombstones inside the main search and is remapped
        to slot space for the side scan (and, on a compacted index, to
        dense row space for the main search — filters survive
        compaction).  ``row_k`` (``[q] int32``) caps each row's results
        below ``k`` as *data* — positions past a row's own k surface as
        id −1 at the worst distance, with no new executable per distinct
        k.  ``search_params`` overrides the index's own params for this
        call (the degraded-mode ladder's hook); every distinct params
        value is a distinct jit variant, so overriders must warm what
        they pass.
        """
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise ValueError(
                f"queries shape {queries.shape} vs index dim {self.dim}"
            )
        snap = self._snapshot()
        main_filter = sample_filter
        if sample_filter is not None and snap.main_ids is not None:
            # compacted index: remap the global-id filter through the
            # compaction id map so the dense-row backend tests the right
            # bits.  Costs one [q, main_rows] mask per batch — shaped by
            # the bucket and the fixed id map only, so nothing recompiles.
            main_filter = self._main_filter_rows(snap, sample_filter)
        select_min = DISTANCE_TYPES[self.metric] != "inner_product"
        with trace_range("serve.mutable_search"):
            dist, ids = self._main_search(
                queries, k, snap.tombstones, main_filter, search_params
            )
            if snap.main_ids is not None:
                # compacted index: the backend returned dense row ids;
                # remap to the global ids callers know (-1 stays -1)
                ids = jnp.where(
                    ids >= 0, snap.main_ids[jnp.clip(ids, 0)], -1
                )
            if snap.side_data is None:
                if row_k is not None:
                    dist, ids = mask_row_k(
                        dist, ids, row_k, select_min=select_min
                    )
                return dist, ids
            from raft_tpu.neighbors import brute_force

            cap = snap.side_data.shape[0]
            k_side = min(k, cap)
            s_dist, s_slot = brute_force.knn(
                snap.side_data, queries, k_side,
                metric=self.metric,
                sample_filter=self._side_passes(snap, sample_filter),
            )
            # slot → global id (-1 stays -1)
            s_ids = jnp.where(s_slot >= 0, snap.side_ids[s_slot], -1)
            return select_k(
                jnp.concatenate([dist, s_dist], axis=1),
                k,
                select_min=select_min,
                input_indices=jnp.concatenate(
                    [ids.astype(jnp.int32), s_ids.astype(jnp.int32)], axis=1
                ),
                row_k=row_k,
            )

    # -- maintenance ---------------------------------------------------------
    def pending_mutations(self) -> Tuple[int, int]:
        """(tombstoned main rows, live side rows) — rebuild pressure.

        Construction-time padding sentinels (compacted indexes) are
        excluded: they are filter state, not backlog."""
        with self._lock:
            return (
                self._n_deleted - self._n_structural,
                int(self._side_live.sum()),
            )

    def backlog_age_s(self) -> float:
        """Seconds since the mutation backlog last became non-empty.

        0.0 while the backlog is empty — this is the freshness SLI: how
        long the oldest un-compacted mutation has been waiting for a
        rebuild, the thing the freshness SLO bounds."""
        with self._lock:
            deletes = self._n_deleted - self._n_structural
            side = int(self._side_live.sum()) if self._side_count else 0
            if deletes <= 0 and side <= 0:
                self._backlog_since = None
                return 0.0
            if self._backlog_since is None:
                self._backlog_since = time.monotonic()
            return time.monotonic() - self._backlog_since

    def live_vectors(self) -> Tuple[np.ndarray, np.ndarray]:
        """Materialize (vectors, ids) of every live row — rebuild input.

        Main rows keep their original ids; the caller rebuilding into a
        fresh index typically renumbers (builders assign 0..n-1).
        """
        with self._lock:
            keep = ~self._deleted
            main_rows = np.asarray(self._main_dataset())[keep]
            if self._main_ids is None:
                main_ids = np.nonzero(keep)[0].astype(np.int64)
            else:
                main_ids = self._main_ids[keep]
            side_rows = self._side_data[self._side_live]
            side_ids = self._side_ids[self._side_live]
        return (
            np.concatenate([main_rows, side_rows], axis=0),
            np.concatenate([main_ids, side_ids], axis=0),
        )

    def iter_main_rows(self, chunk_rows: int = 65536):
        """Yield ``(row_indices, rows)`` chunks of the main dataset.

        The memory-bounded path a compaction rebuild uses instead of
        :meth:`live_vectors`: each step materializes at most roughly
        ``chunk_rows`` decoded float32 rows (plus one list-data slab for
        the IVF kinds), never the whole dataset.  The main structure is
        immutable, so iteration needs no lock; row indices are positions
        0..main_size-1 — map through the id map (if any) and the caller's
        captured tombstone mask to get live global ids.
        """
        chunk_rows = max(1, int(chunk_rows))
        if self.kind in ("brute_force", "cagra"):
            data = self.index.dataset
            for a in range(0, self.main_size, chunk_rows):
                b = min(a + chunk_rows, self.main_size)
                yield (
                    np.arange(a, b, dtype=np.int64),
                    np.asarray(data[a:b], dtype=np.float32),
                )
            return
        # IVF kinds: rows live scattered across padded lists — chunk over
        # lists so each step slices a bounded slab of list_data
        list_index = np.asarray(self.index.list_index)
        n_lists, cap = list_index.shape
        lists_per = max(1, chunk_rows // max(cap, 1))
        if self.kind == "ivf_pq":
            rot = np.asarray(self.index.rotation, dtype=np.float32)
            scale = float(self.index.scan_scale)
        for l0 in range(0, n_lists, lists_per):
            l1 = min(l0 + lists_per, n_lists)
            idx = list_index[l0:l1]
            valid = idx >= 0
            if not valid.any():
                continue
            data = np.asarray(self.index.list_data[l0:l1], dtype=np.float32)
            rows = data[valid]
            if self.kind == "ivf_pq":
                # decoded reconstructions live in rotated space (possibly
                # int8 scan cache, hence scan_scale, lane-padded past
                # rot_dim); invert the rotation
                rows = (rows[:, : rot.shape[0]] * scale) @ rot
            yield idx[valid].astype(np.int64), rows

    def _main_dataset(self) -> np.ndarray:
        """Recover the main rows in id order (for rebuild/consistency)."""
        if self.kind in ("brute_force", "cagra"):
            return np.asarray(self.index.dataset)
        # IVF variants: scatter padded lists back by source id
        out = np.zeros((self.main_size, self.dim), dtype=np.float32)
        data = np.asarray(self.index.list_data, dtype=np.float32)
        idx = np.asarray(self.index.list_index)
        valid = idx >= 0
        if self.kind == "ivf_pq":
            # decoded reconstructions live in rotated space (possibly int8
            # scan cache, hence scan_scale); invert the orthonormal rotation
            rot = np.asarray(self.index.rotation, dtype=np.float32)
            rows = data[valid][:, : rot.shape[0]]  # drop the zero lanes
            out[idx[valid]] = (rows * float(self.index.scan_scale)) @ rot
        else:
            out[idx[valid]] = data[valid]
        return out

    # -- persistence ---------------------------------------------------------
    def save(self, path: str) -> None:
        """Snapshot serve state to ``path`` + main index to ``path.main``."""
        mod = _kind_module(self.kind)
        with self._lock:
            scalars = {
                "kind": self.kind,
                "main_size": self.main_size,
                "side_count": self._side_count,
                "next_id": self._next_id,
                "generation": self._generation,
                "n_structural": self._n_structural,
                "dim": self.dim,
            }
            arrays = {
                "deleted": self._deleted,
                "side_data": self._side_data,
                "side_ids": self._side_ids,
                "side_live": self._side_live,
            }
            if self._main_ids is not None:
                # compacted indexes serve remapped ids; dropping the map on
                # restore would silently re-serve dense row ids
                arrays["main_ids"] = self._main_ids
            if self.refine_rows is not None:
                arrays["refine_dataset"] = np.asarray(self.refine_dataset)
            tiered = getattr(self.index, "paged", None)
            if tiered is not None:
                # paged layout survives the roundtrip: load re-paginates at
                # the same page size and re-warms the saved residency set
                # (tier *placement*; slot numbers are allocator-internal)
                scalars["paged"] = 1
                scalars["page_rows"] = int(tiered.store.page_rows)
                scalars["pinned"] = int(bool(tiered.stats()["pinned"]))
                arrays["resident_pages"] = tiered.resident_pages()
            ser.save_tree(
                path, "serve_mutable", _SERVE_SERIALIZATION_VERSION,
                scalars, arrays,
            )
        if self.kind == "cagra":
            mod.save(path + ".main", self.index, include_dataset=True)
        else:
            mod.save(path + ".main", self.index)

    @classmethod
    def load(cls, path: str, *, search_params=None) -> "MutableIndex":
        scalars, arrays = ser.load_tree(
            path, "serve_mutable", _SERVE_SERIALIZATION_VERSION
        )
        mod = _kind_module(scalars["kind"])
        index = mod.load(path + ".main")
        if scalars.get("paged"):
            from raft_tpu.store import paginate_index

            tiered = paginate_index(
                index, page_rows=int(scalars["page_rows"]),
                name=f"load:{scalars['kind']}",
            )
            if int(scalars.get("pinned", 0)):
                tiered.pin_identity()
            else:
                resident = np.asarray(arrays.get("resident_pages", ()))
                if resident.size:
                    tiered.ensure_resident(resident)
        # files written before the id map existed have no "main_ids" key —
        # they were identity-mapped by construction
        out = cls(
            index, kind=scalars["kind"], search_params=search_params,
            main_ids=arrays.get("main_ids"),
            refine_dataset=(
                jnp.asarray(arrays["refine_dataset"])
                if "refine_dataset" in arrays else None
            ),
        )
        with out._lock:
            out._deleted = np.asarray(arrays["deleted"], dtype=bool)
            out._n_deleted = int(out._deleted.sum())
            out._side_data = np.asarray(arrays["side_data"], dtype=np.float32)
            out._side_ids = np.asarray(arrays["side_ids"], dtype=np.int64)
            out._side_live = np.asarray(arrays["side_live"], dtype=bool)
            out._side_count = int(scalars["side_count"])
            out._next_id = int(scalars["next_id"])
            out._generation = int(scalars["generation"])
            # older files predate compaction padding; they had none
            out._n_structural = int(scalars.get("n_structural", 0))
            out._refresh_snapshot_locked()
        return out
