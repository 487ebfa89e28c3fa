"""Dynamic micro-batcher: coalesce single-query requests into padded,
power-of-two-bucketed batches.

The serving problem on TPU is that ``jit`` specializes on shapes: a stream
of requests with 1, 3, 7, 2, ... queries would trigger a fresh XLA compile
per novel shape.  The batcher fixes the shape universe up front — batches
are always padded to a bucket from the ladder ``min_bucket, 2*min_bucket,
..., max_batch`` — and :meth:`MicroBatcher.warmup` runs a dummy batch
through every bucket so each executable exists *before* traffic arrives.
After warmup the hot path performs zero compiles, which
:class:`~raft_tpu.serve.metrics.ServingMetrics` verifies by bracketing
every dispatch with :func:`~raft_tpu.serve.metrics.compile_count`.

Coalescing policy: the worker thread takes whatever is queued the moment
it wakes; if the pending rows are below ``max_batch`` it waits up to
``max_delay_ms`` (measured from the oldest queued request) for stragglers,
then dispatches.  Latency recorded per request is submit→complete, i.e.
queue wait is included — that is the number a caller actually experiences.

Pipelined dispatch (``pipeline_depth`` > 1): the dispatch path splits
into three stages so the host and device overlap instead of taking
turns.  (1) The worker pads the batch into a reusable per-bucket staging
buffer and *enqueues* the warmed executable without blocking on the
result; (2) a semaphore bounds the in-flight window to ``pipeline_depth``
device batches, so live device memory stays bounded and the host stalls
(``inflight_wait`` stage) instead of overrunning the device; (3) a
completion thread blocks on the *oldest* in-flight batch, copies results
out, resolves futures in submission order, and runs the observer /
metrics / slow-log off the dispatch path.  ``pipeline_depth=1`` keeps
the original fully-serial dispatch, byte for byte.  Steady-state QPS at
depth > 1 is bounded by the *max* of the host and device stage times
rather than their sum.

Request identity: every :meth:`MicroBatcher.submit` assigns a
process-wide monotonically increasing request id (returned on the future
as ``fut.request_id``).  Both dispatch paths feed each completed or
failed batch — member request ids plus per-request timelines
reconstructed from the stage stamps above — to the always-on
:mod:`raft_tpu.obs.flight` recorder, and auto-dump it on a hot-path
recompile or batch exception.  The only hot-path additions are the
submit-time id assignment and one dict build per *batch* after futures
resolve.

Staging-buffer safety: completion is strictly FIFO and the semaphore
caps in-flight batches at ``pipeline_depth``, so by the time a bucket's
ring slot (one of ``pipeline_depth`` per bucket) comes around again its
previous occupant has fully completed — including the observer call,
which sees a *copy* of the staged rows precisely because the auditor
holds samples past the batch's lifetime.

Ragged mode (``ragged=`` a :class:`raft_tpu.serve.ragged.RaggedSpec`):
heterogeneous requests — each with its own top-``k`` and registered
filter id — pack into ONE dispatch per capacity bucket.  ``k`` and the
filter become descriptor *data* (``row_k``/``row_fid`` int32 columns
alongside the padded queries) instead of executable shapes, collapsing
the per-(bucket × k × filter) variant lattice the classic mode would
need.  With the pipeline enabled, admission also turns *continuous*:
the worker claims the in-flight window slot before cutting the batch,
so the forming batch keeps admitting submissions for exactly as long as
the device window is full (see :meth:`MicroBatcher._worker`).
"""

from __future__ import annotations

import itertools
import queue as queue_mod
import threading
import time
from collections import deque
from contextlib import nullcontext
from concurrent.futures import Future, TimeoutError as _FutureTimeout
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from raft_tpu.core import env as _env
from raft_tpu.core.trace import host_range, trace_range
from raft_tpu import kernels as _kernels
from raft_tpu.kernels.toolkit import next_pow2
from raft_tpu.obs import events as obs_events
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import flight, slowlog, spans
from raft_tpu.serve.metrics import ServingMetrics, compile_count
from raft_tpu.serve.overload import expire_deadlines, validate_priority

# search_fn: (queries [b, dim] float32) -> (distances [b, k], ids [b, k]).
# In ragged mode the signature grows two descriptor columns:
# (queries [b, dim], row_k [b] int32, row_fid [b] int32) -> same shapes,
# always at the spec's k_max — per-request k is data, not shape.
SearchFn = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]

# observer: (queries [n, dim], distances [n, k], ids [n, k]) -> None, called
# with the REAL (unpadded) rows after each dispatched batch resolves.  Must
# be non-blocking — the quality auditor's sample-and-enqueue qualifies.
Observer = Callable[[np.ndarray, np.ndarray, np.ndarray], None]


# canonical pow2 helper lives in kernels.toolkit; the old private name is
# kept because the ladder math below reads naturally with it
_next_pow2 = next_pow2


class _Request:
    __slots__ = ("rows", "future", "t_submit", "req_id", "k", "fid",
                 "priority", "deadline")

    def __init__(self, rows: np.ndarray, future: Future, t_submit: float,
                 req_id: int, k: int = 0, fid: int = 0,
                 priority: int = 1, deadline: Optional[float] = None):
        self.rows = rows
        self.future = future
        self.t_submit = t_submit
        self.req_id = req_id
        self.k = k        # ragged mode: this request's top-k (<= k_max)
        self.fid = fid    # ragged mode: registered filter id (0 = all-pass)
        self.priority = priority    # 0 interactive … 3 background
        self.deadline = deadline    # absolute perf_counter s, or None


def _read_search_work(stamp, n_real: int) -> Optional[Tuple[int, int]]:
    """(expanded parents over the batch's ``n_real`` real rows, loop trips)
    of a search-work stamp (``kernels.stamp_search_work``), or None.  Read
    in the copy-out: the counts come from the program whose answers the
    barrier already waited for."""
    if stamp is None:
        return None
    work, n_rows = stamp
    w = np.asarray(work)  # raft-tpu: ignore[HOSTSYNC] staged copy-out after the barrier
    return int(w[: min(n_real, n_rows)].sum()), int(w[n_rows:].sum())


class _InFlight:
    """One dispatched-but-not-completed batch, handed from the dispatch
    thread to the completion thread in submission order."""

    __slots__ = (
        "batch", "padded", "n", "bucket", "queue_waits", "t_pad",
        "inflight_wait", "t_dispatch", "t_enqueued", "dist", "ids",
        "compiles", "sp", "done", "seq", "t_pickup", "hedged",
        "kernel_path", "admit_level", "page", "dispatch_info", "coalesce",
        "search_work",
    )

    def __init__(self, batch: List[_Request]):
        self.batch = batch
        self.done = threading.Event()
        self.hedged = False
        self.kernel_path = "unknown"
        self.admit_level = 0
        self.page = None           # explain: page-cache stats stamp
        self.dispatch_info = None  # explain: ragged dispatch params stamp
        self.coalesce = None       # straggler wait before the cut (worker)
        self.search_work = None    # graph walk's work-count stamp


class MicroBatcher:
    """Coalesces query requests into pow2-padded batches for a search fn.

    Parameters
    ----------
    search_fn:
        Callable mapping a ``[b, dim]`` float32 query batch to
        ``(distances [b, k], ids [b, k])``.  It is resolved per *dispatch*,
        so a registry hot-swap behind the callable takes effect without
        restarting the batcher (and without recompiles, as shapes are
        unchanged).
    dim:
        Query dimensionality; padded rows are zeros of this width.
    min_bucket / max_batch:
        Bucket ladder bounds; both are rounded up to powers of two.
    max_delay_ms:
        Max time a request may wait for coalescing before dispatch.
    metrics:
        Optional shared :class:`ServingMetrics`; a private one is created
        otherwise.
    start:
        When True (default) the worker thread starts immediately.  Tests
        use ``start=False`` + :meth:`flush` for deterministic batching.
    observer:
        Optional post-dispatch hook receiving the real rows of every
        resolved batch ``(queries, distances, ids)`` — the quality
        auditor's shadow-sampling entry.  Exceptions are swallowed and
        the call sits after future resolution, so a misbehaving observer
        can delay the *next* batch but never fail or block a result.
    pipeline_depth:
        Bound on device batches in flight (default from
        ``RAFT_TPU_PIPELINE_DEPTH``, else 2).  ``1`` reproduces the
        original serial dispatch exactly: pad, enqueue, block, resolve —
        all on the dispatching thread.  At depth > 1 the host pads and
        enqueues the next batch while up to ``pipeline_depth`` earlier
        batches run on the device; a completion thread resolves futures
        in submission order.  Memory cost: ``pipeline_depth`` staging
        buffers per touched bucket plus the live device buffers of the
        in-flight batches.
    ragged:
        Optional :class:`raft_tpu.serve.ragged.RaggedSpec`.  When set,
        ``search_fn`` takes ``(queries, row_k, row_fid)`` and always
        computes ``k_max`` result columns; :meth:`submit` accepts
        per-request ``k``/``fid`` and each future is sliced to its own
        ``[:k]`` after copy-out.  One executable per capacity bucket —
        the (bucket × k × filter) variant lattice collapses.  At
        ``pipeline_depth`` > 1 admission is continuous (see the worker).
    admission / degraded / hedger:
        Optional overload actuators (:mod:`raft_tpu.serve.overload`).
        ``admission`` (an :class:`~raft_tpu.serve.overload.
        AdmissionController`) runs at every batch cut — it expires
        past-deadline requests and sheds low-priority work under
        pressure, resolving their futures with typed errors before the
        batch reaches the device; its verdict also feeds ``degraded``
        (a :class:`~raft_tpu.serve.overload.DegradedModeManager`),
        whose hysteretic effort level the search fn may consult.
        Without a controller, deadline expiry still runs at every cut.
        ``hedger`` (a :class:`~raft_tpu.serve.overload.
        HedgedDispatcher`) reroutes batches carrying priority-0 traffic
        through a raced two-member dispatch; warmup warms every member.
    """

    def __init__(
        self,
        search_fn: SearchFn,
        dim: int,
        *,
        min_bucket: int = 1,
        max_batch: int = 64,
        max_delay_ms: float = 2.0,
        metrics: Optional[ServingMetrics] = None,
        start: bool = True,
        observer: Optional[Observer] = None,
        pipeline_depth: Optional[int] = None,
        ragged=None,
        admission=None,
        degraded=None,
        effort=None,
        hedger=None,
    ):
        if dim <= 0:
            raise ValueError(f"dim must be positive, got {dim}")
        if min_bucket <= 0 or max_batch <= 0:
            raise ValueError("min_bucket and max_batch must be positive")
        min_bucket = _next_pow2(min_bucket)
        max_batch = _next_pow2(max_batch)
        if min_bucket > max_batch:
            raise ValueError(
                f"min_bucket={min_bucket} exceeds max_batch={max_batch}"
            )
        self._search_fn = search_fn
        self.dim = int(dim)
        self.min_bucket = min_bucket
        self.max_batch = max_batch
        self.max_delay_s = float(max_delay_ms) * 1e-3
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.observer = observer
        if pipeline_depth is None:
            pipeline_depth = _env.env_int("RAFT_TPU_PIPELINE_DEPTH", 2)
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self.pipeline_depth = int(pipeline_depth)
        # ragged mode (a serve.ragged.RaggedSpec, or None for classic):
        # search_fn takes (queries, row_k, row_fid) and always computes
        # k_max columns; per-request k/fid ride as data.  Admission turns
        # continuous at depth > 1: the worker claims the in-flight window
        # slot BEFORE cutting the batch, so requests keep packing into the
        # forming batch while the device window is full.
        self.ragged = ragged
        if ragged is not None and ragged.k_max < 1:
            raise ValueError(f"ragged k_max must be >= 1, got {ragged.k_max}")
        # overload actuators (serve.overload); admission inherits this
        # batcher's metrics so shed/expired requests land in the same
        # error counters the SLO availability spec reads
        self.admission = admission
        self.degraded = degraded
        # optional serve.effort.EffortArbiter: the single effort writer
        # (overload ladder clamp + autotuner walk) — when present its
        # ladder supersedes degraded's for warmup, since the search fn
        # consults the arbiter, not the manager, for effective params
        self.effort = effort
        self.hedger = hedger
        if admission is not None and admission.metrics is None:
            admission.metrics = self.metrics
        if hedger is not None and hedger.metrics is None:
            hedger.metrics = self.metrics
        if hedger is not None and hedger.on_interval is None:
            # mirrored hedge members report their device windows here so
            # device_busy_s() merges the pair instead of double-counting
            hedger.on_interval = self._note_device_interval
        # attribution fallback when the search fn did not stamp a routing
        # choice this dispatch (e.g. hedged members run on pool threads,
        # whose thread-local stamps this thread cannot see)
        self._kpath_default = "pallas" if _kernels.use_pallas() else "xla"
        self._last_kernel_path = self._kpath_default
        self._last_hedged = False
        # explain stamps consumed per dispatch (written/read under
        # _dispatch_lock, like _last_kernel_path) + the last admission
        # verdict level (written by _admit on the same thread that then
        # dispatches the batch)
        self._last_page_stats = None
        self._last_dispatch_info = None
        self._last_admit_level = 0
        # the graph walk's work-count stamp (kernels.stamp_search_work),
        # consumed per dispatch like the kernel path; None off the graph
        # index
        self._last_search_work = None

        self._cond = threading.Condition()
        self._queue: Deque[_Request] = deque()
        self._stopping = False
        # one dispatch *stage* at a time, shared by worker thread and
        # flush(); at depth 1 it additionally covers the device wait (the
        # original serial behavior)
        self._dispatch_lock = threading.Lock()
        self._warm = False
        self._thread: Optional[threading.Thread] = None
        # -- pipelined dispatch state (idle at pipeline_depth == 1) ----------
        self._inflight_sem = threading.Semaphore(self.pipeline_depth)
        self._inflight_q: "queue_mod.Queue[Optional[_InFlight]]" = (
            queue_mod.Queue()
        )
        self._completion_thread: Optional[threading.Thread] = None
        self._inflight_lock = threading.Lock()
        self._inflight = 0
        # per-bucket ring of pipeline_depth reusable staging buffers
        self._staging: Dict[int, List[Optional[np.ndarray]]] = {}
        self._staging_idx: Dict[int, int] = {}
        # union of [enqueue, ready] intervals: device-busy estimate for the
        # bench's idle-fraction figure (completion thread only)
        self._busy_s = 0.0
        self._busy_until = 0.0
        # flight-recorder batch sequence (per batcher; request ids are
        # process-wide, see obs.flight.next_request_id)
        self._batch_seq = itertools.count(1)
        self.metrics.record_pipeline(self.pipeline_depth, 0)
        if start:
            self.start()

    # -- bucket ladder -------------------------------------------------------
    def buckets(self) -> List[int]:
        """The full bucket ladder, ascending."""
        out, b = [], self.min_bucket
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return out

    def bucket_for(self, n_rows: int) -> int:
        """Smallest bucket holding ``n_rows`` (clamped into the ladder)."""
        return min(self.max_batch, max(self.min_bucket, _next_pow2(n_rows)))

    # -- lifecycle -----------------------------------------------------------
    def warmup(self) -> int:
        """Compile every bucket's executable up front; returns compile count.

        Runs a zero-filled batch through each bucket in the ladder and
        blocks on the result.  Compiles spent here are booked as
        ``warmup_compiles`` and the hot-path recompile counter is reset, so
        any later non-zero ``recompiles`` is a genuine shape leak.
        """
        total = 0
        # degraded mode changes search params (host Python values the
        # backends trace on), so every level of the ladder gets its own
        # warmup pass — a pressure-driven level flip must never compile
        # on the hot path
        actuator = self.effort if self.effort is not None else self.degraded
        levels = (None,) if actuator is None else actuator.levels()
        with self._dispatch_lock, trace_range("serve.warmup"):
            for level in levels:
                pin = (nullcontext() if level is None
                       else actuator.pinned(level))
                with pin:
                    for b in self.buckets():
                        dummy = np.zeros((b, self.dim), dtype=np.float32)
                        c0 = compile_count(thread=True)
                        # ragged mode warms ONE variant per bucket — k and
                        # filter are data, so the dummy descriptor columns
                        # cover every later (k, fid) mix
                        dist, ids = self._invoke(dummy, [])
                        jax.block_until_ready((dist, ids))
                        if self.hedger is not None:
                            self.hedger.warm(*self._invoke_args(dummy, []))
                        total += compile_count(thread=True) - c0
        self.metrics.record_warmup(total)
        self.metrics.reset_hot_path()
        self._warm = True
        return total

    def _invoke_args(self, padded: np.ndarray, batch: List[_Request]):
        """The search fn's argument tuple for one padded bucket.

        Ragged mode attaches the per-request descriptor columns: each
        request's rows carry its ``(k, fid)``; padding rows run at
        ``k_max`` / filter 0 (all-pass), so the call is the same trace
        for every batch of this bucket.  Classic mode is the original
        single-argument form, byte for byte.
        """
        if self.ragged is None:
            return (jax.numpy.asarray(padded),)
        bucket = padded.shape[0]
        row_k = np.full((bucket,), self.ragged.k_max, np.int32)
        row_fid = np.zeros((bucket,), np.int32)
        off = 0
        for req in batch:
            m = req.rows.shape[0]
            row_k[off : off + m] = req.k
            row_fid[off : off + m] = req.fid
            off += m
        return (
            jax.numpy.asarray(padded),
            jax.numpy.asarray(row_k),
            jax.numpy.asarray(row_fid),
        )

    def _invoke(self, padded: np.ndarray, batch: List[_Request]):
        """Hand one padded bucket to the search fn (or, for batches
        carrying priority-0 traffic with a hedger installed, to the
        raced two-member dispatch).

        Side channel: records whether this dispatch was hedged and which
        ``kernel_path`` the search fn stamped (``kernels.
        stamp_kernel_path`` in the neighbors routing code) on
        ``self._last_hedged`` / ``self._last_kernel_path`` — safe as
        instance state because every call site holds ``_dispatch_lock``.
        """
        args = self._invoke_args(padded, batch)
        hedger = self.hedger
        hedged = hedger is not None and any(r.priority == 0 for r in batch)
        self._last_hedged = hedged
        _kernels.consume_kernel_path()  # drop any stale stamp first
        _kernels.consume_search_work()
        obs_explain.consume_page_stats()
        obs_explain.consume_dispatch()
        if hedged:
            out = hedger.dispatch(*args)
        else:
            out = self._search_fn(*args)
        self._last_kernel_path = _kernels.consume_kernel_path(
            self._kpath_default
        )
        self._last_search_work = _kernels.consume_search_work()
        # explain stamps ride the same thread-local side channel as the
        # kernel-path stamp; empty (None) unless explain collection is on
        self._last_page_stats = obs_explain.consume_page_stats()
        self._last_dispatch_info = obs_explain.consume_dispatch()
        return out

    def _note_device_interval(self, t_start: float, t_end: float) -> None:
        """Merge one device window ``[t_start, t_end]`` into the busy-time
        union.  This is the hedger's ``on_interval`` sink: each member of
        a mirrored hedge pair reports its own window, and the incremental
        union counts their overlap ONCE — so ``device_busy_s()`` stays an
        upper-bounded union instead of double-counting the race."""
        with self._inflight_lock:
            if t_end > self._busy_until:
                self._busy_s += t_end - max(t_start, self._busy_until)
                self._busy_until = t_end

    def _result_view(self, req: _Request, dist: np.ndarray, ids: np.ndarray,
                     off: int):
        """This request's slice of a completed batch's host arrays.

        Ragged mode also slices the column axis down to the request's own
        ``k`` — the executable computed ``k_max`` columns for everyone."""
        m = req.rows.shape[0]
        d, i = dist[off : off + m], ids[off : off + m]
        if self.ragged is not None and req.k < d.shape[1]:
            d, i = d[:, : req.k], i[:, : req.k]
        return d, i

    @property
    def warm(self) -> bool:
        """True once :meth:`warmup` has compiled the bucket ladder."""
        return self._warm

    def queue_depth(self) -> int:
        """Rows currently waiting for dispatch (health signal)."""
        with self._cond:
            return sum(r.rows.shape[0] for r in self._queue)

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        with self._cond:
            self._stopping = False
        self._thread = threading.Thread(
            target=self._worker, name="raft-tpu-serve-batcher", daemon=True
        )
        self._thread.start()

    def stop(self, drain: bool = True) -> None:
        """Stop the worker thread; with ``drain`` pending requests complete
        first, otherwise they fail with :class:`RuntimeError`.  Batches
        already in flight complete and resolve their futures either way —
        they were dispatched before the stop, and dropping device results
        on the floor would break the delivered-exactly-once contract."""
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if drain:
            self.flush()
        else:
            with self._cond:
                pending, self._queue = self._queue, deque()
            for req in pending:
                req.future.set_exception(
                    RuntimeError("MicroBatcher stopped before dispatch")
                )
        self._shutdown_completion()
        self.metrics.close()

    def _shutdown_completion(self) -> None:
        """Drain the completion thread: in-flight batches finish, then the
        sentinel stops the loop.  Safe to call with nothing in flight."""
        t = self._completion_thread
        if t is not None and t.is_alive():
            self._inflight_q.put(None)
            t.join()
        self._completion_thread = None

    def __enter__(self) -> "MicroBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- submission ----------------------------------------------------------
    def submit(self, queries, *, k: Optional[int] = None,
               fid: Optional[int] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Future:
        """Enqueue one request of shape ``[dim]`` or ``[m, dim]``.

        Returns a future resolving to ``(distances [m, k], ids [m, k])``
        numpy arrays (the leading axis is squeezed away for 1-D input).
        The future carries the request's process-wide monotonically
        increasing id as ``fut.request_id`` — the handle that links a
        caller's latency to its flight-recorder timeline and histogram
        exemplar.

        Ragged mode only: ``k`` picks this request's top-k (default and
        ceiling: the spec's ``k_max``) and ``fid`` a registered filter id
        (default 0, the all-pass row).  Heterogeneous ``(k, fid)`` mixes
        pack into one batch — they are descriptor data, not shapes.

        Any mode: ``priority`` is the request's class (0=interactive,
        1=standard — the default, 2=batch, 3=background) and
        ``deadline_s`` a server-side budget measured from now.  Both are
        host-side request metadata (no effect on executable shapes).  A
        request whose deadline passes before its batch is cut resolves
        with :class:`~raft_tpu.serve.overload.DeadlineExceeded` instead
        of occupying a device slot; under overload an installed
        :class:`~raft_tpu.serve.overload.AdmissionController` sheds the
        lowest priorities first with the typed
        :class:`~raft_tpu.serve.overload.Shed` error.
        """
        if self.ragged is None:
            if k is not None or fid is not None:
                raise ValueError(
                    "per-request k/fid need ragged mode — construct the "
                    "batcher (or SearchService) with ragged="
                )
            k, fid = 0, 0
        else:
            k = self.ragged.k_max if k is None else int(k)
            if not 1 <= k <= self.ragged.k_max:
                raise ValueError(
                    f"k={k} outside [1, k_max={self.ragged.k_max}]"
                )
            fid = 0 if fid is None else int(fid)
            if fid < 0:
                raise ValueError(f"fid must be >= 0, got {fid}")
        rows = np.asarray(queries, dtype=np.float32)
        squeeze = rows.ndim == 1
        if squeeze:
            rows = rows[None, :]
        if rows.ndim != 2 or rows.shape[1] != self.dim:
            raise ValueError(
                f"expected queries of dim {self.dim}, got shape {rows.shape}"
            )
        if rows.shape[0] > self.max_batch:
            raise ValueError(
                f"request of {rows.shape[0]} rows exceeds max_batch="
                f"{self.max_batch}; split it client-side"
            )
        priority = validate_priority(priority)
        if deadline_s is not None and float(deadline_s) <= 0.0:
            raise ValueError(
                f"deadline_s must be positive, got {deadline_s}"
            )
        t_submit = time.perf_counter()
        deadline = None if deadline_s is None else t_submit + float(deadline_s)
        req_id = flight.next_request_id()
        fut: Future = Future()
        fut.request_id = req_id
        if squeeze:
            inner = fut
            fut = Future()
            fut.request_id = req_id
            inner.add_done_callback(
                lambda f, out=fut: _squeeze_result(f, out)
            )
            req = _Request(rows, inner, t_submit, req_id, k, fid,
                           priority, deadline)
        else:
            req = _Request(rows, fut, t_submit, req_id, k, fid,
                           priority, deadline)
        with self._cond:
            if self._stopping and (
                self._thread is None or not self._thread.is_alive()
            ):
                # no worker; caller is expected to flush() manually
                pass
            self._queue.append(req)
            self._cond.notify()
        return fut

    def search(self, queries, timeout: Optional[float] = None, *,
               k: Optional[int] = None, fid: Optional[int] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None):
        """Synchronous convenience wrapper around :meth:`submit`.

        ``timeout`` doubles as the server-side deadline when
        ``deadline_s`` is not given: a caller that stops waiting at
        ``timeout`` must not leave its request occupying a batch slot
        and running on device — the expired work is dropped (typed
        :class:`~raft_tpu.serve.overload.DeadlineExceeded`) at the next
        batch cut instead.
        """
        if deadline_s is None and timeout is not None:
            deadline_s = timeout
        fut = self.submit(queries, k=k, fid=fid, priority=priority,
                          deadline_s=deadline_s)
        if self._thread is None or not self._thread.is_alive():
            self.flush()
        try:
            return fut.result(timeout=timeout)
        except _FutureTimeout:
            # py3.10's futures.TimeoutError is not the builtin; normalize
            # so callers catch one type whether the client-side wait or
            # the server-side deadline expiry (DeadlineExceeded, also a
            # TimeoutError) fired first
            raise TimeoutError(
                f"no result within {timeout}s (request still queued or "
                "in flight; its deadline will expire it at the next cut)"
            ) from None

    # -- batching core -------------------------------------------------------
    def flush(self) -> int:
        """Dispatch everything queued right now; returns batches issued.

        Routes through the same path traffic takes: serial dispatch in
        this thread at depth 1, the pipeline at depth > 1 — so a flush
        racing in-flight batches cannot reorder result delivery (the
        completion thread resolves strictly in submission order) and
        never holds ``_dispatch_lock`` across a device wait it did not
        pay for.  Returns only after every batch it dispatched has
        resolved its futures and recorded its metrics."""
        n_batches = 0
        last: Optional[_InFlight] = None
        while True:
            with self._cond:
                if not self._queue:
                    break
                batch = self._take_batch_locked()
            batch = self._admit(batch)
            if not batch:
                continue
            if self.pipeline_depth == 1:
                self._dispatch(batch)
            else:
                rec = self._dispatch_pipelined(batch)
                if rec is not None:
                    last = rec
            n_batches += 1
        if last is not None:
            # FIFO completion: the last record's done event implies every
            # earlier one dispatched here has fully completed too
            last.done.wait()
        return n_batches

    def _take_batch_locked(self) -> List[_Request]:
        """Pop a prefix of the queue totalling at most max_batch rows."""
        taken, rows = [], 0
        while self._queue:
            nxt = self._queue[0]
            if taken and rows + nxt.rows.shape[0] > self.max_batch:
                break
            taken.append(self._queue.popleft())
            rows += nxt.rows.shape[0]
        return taken

    def _coalesce_locked(self) -> Tuple[List[_Request], float]:
        """Wait (condition held) for stragglers up to the oldest queued
        request's deadline, then pop a batch; [] if the queue emptied
        under us (a racing flush took everything).  Also returns the
        seconds spent waiting (the ``coalesce`` stage)."""
        if not self._queue:
            return [], 0.0
        deadline = self._queue[0].t_submit + self.max_delay_s
        t0 = time.perf_counter()
        with host_range("serve.coalesce"):
            while (
                sum(r.rows.shape[0] for r in self._queue) < self.max_batch
                and not self._stopping
            ):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=remaining)
                if not self._queue:
                    break
        waited = time.perf_counter() - t0
        if not self._queue:
            return [], waited
        return self._take_batch_locked(), waited

    def _admit(self, batch: List[_Request]) -> List[_Request]:
        """Batch-cut admission: expire deadlines and, with a controller
        installed, shed under pressure.  Runs at every cut site, OUTSIDE
        the queue condition — resolving a rejected future runs its done
        callbacks inline.  Returns the requests that may dispatch."""
        if not batch:
            return batch
        with host_range("serve.admit"):
            ctrl = self.admission
            index = self.metrics.name or "default"
            if ctrl is None:
                alive = expire_deadlines(
                    batch, index=index, metrics=self.metrics,
                )
                self._last_admit_level = 0
                if len(alive) != len(batch) and obs_explain.enabled():
                    alive_ids = {id(r) for r in alive}
                    obs_explain.observe_admission(
                        index,
                        expired=[r for r in batch
                                 if id(r) not in alive_ids],
                    )
                return alive
            decision = ctrl.decide(
                batch, queue_rows=self.queue_depth(),
                max_batch=self.max_batch,
            )
            # recorded where the decision is already made (no re-derivation
            # on the completion path); read by the same thread that
            # dispatches
            self._last_admit_level = decision.level
            if self.degraded is not None:
                self.degraded.step(decision.level > 0)
            if ((decision.shed or decision.expired)
                    and obs_explain.enabled()):
                # shed / expired requests never reach a batch record —
                # archive their minimal plans here (futures already carry
                # the typed errors; this only observes)
                obs_explain.observe_admission(
                    index, shed=decision.shed, expired=decision.expired,
                    level=decision.level,
                )
            return list(decision.admitted)

    def _worker(self) -> None:
        # continuous admission (ragged + pipeline): claim the in-flight
        # window slot BEFORE cutting the batch.  While a full window
        # blocks this thread, submit() keeps appending — the eventual
        # batch packs everything that arrived during the stall instead of
        # a fixed pre-window cut, so fill rises (and padding waste falls)
        # exactly when the device is the bottleneck.
        continuous = self.ragged is not None and self.pipeline_depth > 1
        while True:
            with self._cond:
                if not self._queue and not self._stopping:
                    with host_range("serve.idle"):
                        while not self._queue and not self._stopping:
                            self._cond.wait()
                if self._stopping:
                    return
                if not continuous:
                    # coalescing window: wait for stragglers, bounded by
                    # the oldest request's deadline
                    batch, coalesce_s = self._coalesce_locked()
                    if not batch:
                        continue
            if continuous:
                with host_range("serve.inflight_wait"):
                    self._inflight_sem.acquire()
                with self._cond:
                    batch, coalesce_s = self._coalesce_locked()
                batch = self._admit(batch)
                if not batch:
                    self._inflight_sem.release()
                    continue
                self._dispatch_pipelined(batch, sem_held=True,
                                         coalesce_s=coalesce_s)
            else:
                batch = self._admit(batch)
                if not batch:
                    continue
                if self.pipeline_depth > 1:
                    self._dispatch_pipelined(batch, coalesce_s=coalesce_s)
                else:
                    with self._dispatch_lock:
                        self._dispatch_locked(batch, coalesce_s)

    def _dispatch(self, batch: List[_Request]) -> None:
        with self._dispatch_lock:
            self._dispatch_locked(batch)

    def _record_flight(
        self,
        *,
        seq: int,
        batch: List[_Request],
        n: int,
        bucket: int,
        compiles: int,
        t_pickup: float,
        t_done: float,
        stages_s: Dict[str, float],
        waits_s: Dict[str, float],
        error: Optional[str] = None,
        kernel_path: str = "unknown",
        hedged: bool = False,
        admit_level: int = 0,
        page: Optional[Dict[str, object]] = None,
        dispatch_info: Optional[Dict[str, object]] = None,
    ) -> None:
        """Feed one completed (or failed) batch to the flight recorder
        (and, when explain collection is on, to the query archive's tail
        sampler — the same dict, one extra member scan).

        ``stages_s`` holds the post-pickup stage durations in execution
        order (the Chrome-trace builder lays them end to end from
        ``t_pickup``); ``waits_s`` the pre-pickup waits (queue, in-flight
        window).  All values come from stamps the dispatch paths already
        take — this reconstructs, it does not measure.
        """
        if not spans.enabled():
            return
        stages_ms = {k: v * 1e3 for k, v in {**waits_s, **stages_s}.items()}
        explain_on = obs_explain.enabled()
        record = {
            "seq": seq,
            "index": self.metrics.name,
            "bucket": bucket,
            "rows": n,
            "compiles": compiles,
            "request_ids": [req.req_id for req in batch],
            "t_pickup": t_pickup,
            "t_done": t_done,
            "stages_s": stages_s,
            "waits_s": waits_s,
            "kernel_path": kernel_path,
            "hedged": hedged,
            "requests": [
                {
                    "id": req.req_id,
                    "rows": req.rows.shape[0],
                    "submit": req.t_submit,
                    "batched": t_pickup,
                    "resolve": t_done,
                    "queue_ms": (t_pickup - req.t_submit) * 1e3,
                    "latency_ms": (t_done - req.t_submit) * 1e3,
                    "stages_ms": stages_ms,
                    # ragged descriptor: what this request actually asked
                    # for inside the packed dispatch
                    **(
                        {"k": req.k, "fid": req.fid}
                        if self.ragged is not None else {}
                    ),
                    **(
                        {"priority": req.priority} if explain_on else {}
                    ),
                }
                for req in batch
            ],
            "error": error,
        }
        if explain_on:
            # explain enrichment: decisions already made/stamped this
            # dispatch — no clocks, no host syncs, one snapshot read
            record["admission_level"] = admit_level
            record["page"] = page
            record["dispatch"] = dispatch_info
            record["effort"] = (
                self.effort.snapshot() if self.effort is not None else None
            )
        flight.record_batch(record)
        if explain_on:
            obs_explain.observe_batch(record)

    def _dispatch_locked(self, batch: List[_Request],
                         coalesce_s: Optional[float] = None) -> None:
        if not batch:
            return
        seq = next(self._batch_seq)
        t_start = time.perf_counter()
        # queue-wait ends the moment the batch is picked up: submit → here
        queue_waits = [t_start - r.t_submit for r in batch]
        n = sum(r.rows.shape[0] for r in batch)
        bucket = self.bucket_for(n)
        with host_range("serve.pad"):
            padded = np.zeros((bucket, self.dim), dtype=np.float32)
            off = 0
            for req in batch:
                m = req.rows.shape[0]
                padded[off : off + m] = req.rows
                off += m
        t_pad = time.perf_counter() - t_start
        sp = None
        err_stage = "dispatch"
        try:
            c0 = compile_count(thread=True)
            with trace_range("serve.batch") as sp:
                t0 = time.perf_counter()
                # dispatch: host-side tracing + enqueue of the executable
                with host_range("serve.dispatch"):
                    dist, ids = self._invoke(padded, batch)
                t1 = time.perf_counter()
                err_stage = "device"
                # device: waiting for the result to materialize — the serial
                # path's one intended sync (the pipelined path moves it to
                # the completion thread)
                with host_range("serve.device_wait"):
                    jax.block_until_ready((dist, ids))  # raft-tpu: ignore[HOSTSYNC] serial-path batch barrier
                t2 = time.perf_counter()
                if sp is not None:
                    sp.add_stage("queue", max(queue_waits, default=0.0))
                    sp.add_stage("pad", t_pad)
                    sp.add_stage("dispatch", t1 - t0)
                    sp.add_stage("device", t2 - t1)
            compiles = compile_count(thread=True) - c0
            with host_range("serve.copy_out"):
                dist = np.asarray(dist)  # raft-tpu: ignore[HOSTSYNC] staged copy-out after the barrier
                ids = np.asarray(ids)  # raft-tpu: ignore[HOSTSYNC] staged copy-out after the barrier
                work = _read_search_work(self._last_search_work, n)
        except Exception as exc:  # noqa: BLE001 — fail the waiting futures
            self._record_flight(
                seq=seq, batch=batch, n=n, bucket=bucket,
                compiles=compile_count(thread=True) - c0,
                t_pickup=t_start, t_done=time.perf_counter(),
                stages_s={"pad": t_pad},
                waits_s={"queue": max(queue_waits, default=0.0)},
                error=repr(exc),
                admit_level=self._last_admit_level,
            )
            self.metrics.record_error(err_stage, len(batch))
            obs_events.publish(
                "batch_error", "batch_exception",
                index=self.metrics.name, bucket=bucket, cause=err_stage,
                requests=len(batch), error=repr(exc),
            )
            for req in batch:
                req.future.set_exception(exc)
            return
        done = time.perf_counter()
        off = 0
        lats = []
        with host_range("serve.resolve"):
            for req in batch:
                req.future.set_result(self._result_view(req, dist, ids, off))
                off += req.rows.shape[0]
                lats.append(done - req.t_submit)
        t_rec = time.perf_counter()
        with host_range("serve.record"):
            observer = self.observer
            if observer is not None:
                # futures are already resolved; the observer (quality
                # auditor) sees only the real rows and must itself be
                # non-blocking
                try:
                    observer(padded[:n], dist[:n], ids[:n])
                except Exception:  # noqa: BLE001 — auditing never fails serving
                    pass
            self.metrics.record_queue_depth(self.queue_depth())
            stages = {
                "queue": queue_waits,
                "pad": (t_pad,),
                "dispatch": (t1 - t0,),
                "device": (t2 - t1,),
            }
            if coalesce_s is not None:
                stages["coalesce"] = (coalesce_s,)
            self.metrics.record_batch(
                n, bucket, lats, compiles,
                stages=stages,
                request_ids=[r.req_id for r in batch],
                kernel_path=self._last_kernel_path,
            )
            if work is not None:
                self.metrics.record_search_work(n, *work)
            self._record_flight(
                seq=seq, batch=batch, n=n, bucket=bucket, compiles=compiles,
                t_pickup=t_start, t_done=done,
                stages_s={
                    "pad": t_pad,
                    "dispatch": t1 - t0,
                    "device": t2 - t1,
                    "copy_out": done - t2,
                },
                waits_s={"queue": max(queue_waits, default=0.0)},
                kernel_path=self._last_kernel_path,
                hedged=self._last_hedged,
                admit_level=self._last_admit_level,
                page=self._last_page_stats,
                dispatch_info=self._last_dispatch_info,
            )
            if compiles and self._warm:
                # a recompile on the warmed hot path is a shape leak:
                # capture the surrounding traffic while it is still in the
                # ring
                obs_events.publish(
                    "hot_recompile",
                    index=self.metrics.name, bucket=bucket,
                    compiles=compiles,
                )
            if sp is not None:
                slowlog.maybe_record(
                    sp,
                    latency_s=max(lats, default=0.0),
                    detail={
                        "index": self.metrics.name,
                        "requests": len(batch),
                        "bucket": bucket,
                        "compiles": compiles,
                        "request_ids": [r.req_id for r in batch],
                        **self._explain_summary(
                            self._last_kernel_path, self._last_page_stats
                        ),
                    },
                )
        self.metrics.record_stage("record", time.perf_counter() - t_rec)

    def _explain_summary(self, kernel_path: str,
                         page: Optional[Dict[str, object]]):
        """Slow-log enrichment: the explain summary (effort level and its
        source, kernel path, page hit ratio) so slow lines are actionable
        without an archive lookup.  Purely additive keys — the existing
        entry fields stay byte-compatible."""
        return obs_explain.summary_line({
            "kernel_path": kernel_path,
            "effort": (
                self.effort.snapshot() if self.effort is not None else None
            ),
            "page": page,
        })

    # -- pipelined dispatch (pipeline_depth > 1) -----------------------------
    @property
    def inflight(self) -> int:
        """Device batches dispatched but not yet completed."""
        with self._inflight_lock:
            return self._inflight

    def device_busy_s(self) -> float:
        """Seconds the device had at least one batch outstanding.

        Pipelined path: exact union of the [enqueue, ready] intervals
        (FIFO completion keeps the incremental union O(1)).  Serial path:
        the sum of recorded device-stage durations, which is the same
        quantity because nothing overlaps at depth 1.  Benches derive the
        device-idle fraction as ``1 - device_busy_s / wall``."""
        if self.pipeline_depth > 1:
            with self._inflight_lock:
                return self._busy_s
        return self.metrics.stage_totals().get("device", 0.0)

    def _staging_buffer(self, bucket: int) -> np.ndarray:
        """Next slot of the bucket's staging ring (dispatch lock held).

        Safe to reuse without copying: at most ``pipeline_depth`` batches
        are ever in flight and completion is FIFO, so a slot's previous
        occupant — ``pipeline_depth`` same-bucket dispatches ago, hence at
        least ``pipeline_depth`` global dispatches ago — has fully
        completed (semaphore released only after copy-out and observer)
        before the slot comes around again."""
        ring = self._staging.get(bucket)
        if ring is None:
            ring = self._staging[bucket] = [None] * self.pipeline_depth
            self._staging_idx[bucket] = 0
        i = self._staging_idx[bucket]
        self._staging_idx[bucket] = (i + 1) % self.pipeline_depth
        buf = ring[i]
        if buf is None:
            buf = ring[i] = np.empty((bucket, self.dim), dtype=np.float32)
        return buf

    def _ensure_completion_thread(self) -> None:
        # only called under _dispatch_lock, so no start/start race
        t = self._completion_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(
            target=self._completer, name="raft-tpu-serve-completer",
            daemon=True,
        )
        self._completion_thread = t
        t.start()

    def _dispatch_pipelined(self, batch: List[_Request], *,
                            sem_held: bool = False,
                            coalesce_s: Optional[float] = None,
                            ) -> Optional[_InFlight]:
        """Stage 1+2: pad into a staging buffer, enqueue device work, hand
        the record to the completion thread.  Never blocks on the device;
        blocks only on the in-flight window (``inflight_wait``).  Returns
        the in-flight record, or None for an empty batch or a dispatch-
        stage failure (which fails only this batch's futures).

        ``sem_held``: the continuous-admission worker already claimed the
        window slot before forming the batch — its wait overlapped
        admission, so this path records ``inflight_wait`` 0."""
        if not batch:
            if sem_held:
                self._inflight_sem.release()
            return None
        t_arrive = time.perf_counter()
        if not sem_held:
            # acquire the window slot BEFORE the dispatch lock: a full
            # window must stall this dispatcher without also blocking the
            # completion thread's progress (it never takes either)
            with host_range("serve.inflight_wait"):
                self._inflight_sem.acquire()
        t_acquired = time.perf_counter()
        with self._dispatch_lock:
            rec = _InFlight(batch)
            rec.seq = next(self._batch_seq)
            rec.t_pickup = t_acquired
            rec.inflight_wait = t_acquired - t_arrive
            rec.coalesce = coalesce_s
            # queue-wait ends when the batch is picked up for dispatch
            rec.queue_waits = [t_acquired - r.t_submit for r in batch]
            n = sum(r.rows.shape[0] for r in batch)
            bucket = self.bucket_for(n)
            t0 = time.perf_counter()
            with host_range("serve.pad"):
                padded = self._staging_buffer(bucket)
                off = 0
                for req in batch:
                    m = req.rows.shape[0]
                    padded[off : off + m] = req.rows
                    off += m
                if off < bucket:
                    # zero the tail so depth>1 results stay bit-identical
                    # to the serial path's freshly-zeroed pad
                    padded[off:] = 0.0
            rec.n, rec.bucket, rec.padded = n, bucket, padded
            rec.t_pad = time.perf_counter() - t0
            # detached span: opened here, closed by the completion thread
            rec.sp = spans.open_span("serve.batch")
            try:
                c0 = compile_count(thread=True)
                t1 = time.perf_counter()
                with host_range("serve.dispatch"):
                    dist, ids = self._invoke(padded, batch)
                t2 = time.perf_counter()
                rec.t_dispatch = t2 - t1
                # compiles happen synchronously at trace/enqueue time, so
                # the bracket closes here, not after the device wait
                rec.compiles = compile_count(thread=True) - c0
                rec.dist, rec.ids = dist, ids
                rec.hedged = self._last_hedged
                rec.kernel_path = self._last_kernel_path
                rec.search_work = self._last_search_work
                # explain stamps: instance state is only valid on this
                # thread (dispatch lock held) — carry them on the record
                # for the completion thread
                rec.admit_level = self._last_admit_level
                rec.page = self._last_page_stats
                rec.dispatch_info = self._last_dispatch_info
            except Exception as exc:  # noqa: BLE001 — fail only this batch
                spans.finish_span(rec.sp)
                self._inflight_sem.release()
                self._record_flight(
                    seq=rec.seq, batch=batch, n=n, bucket=bucket,
                    compiles=compile_count(thread=True) - c0,
                    t_pickup=t_acquired, t_done=time.perf_counter(),
                    stages_s={"pad": rec.t_pad},
                    waits_s={
                        "queue": max(rec.queue_waits, default=0.0),
                        "inflight_wait": rec.inflight_wait,
                    },
                    error=repr(exc),
                )
                self.metrics.record_error("dispatch", len(batch))
                obs_events.publish(
                    "batch_error", "batch_exception",
                    index=self.metrics.name, bucket=bucket,
                    cause="dispatch", requests=len(batch), error=repr(exc),
                )
                for req in batch:
                    req.future.set_exception(exc)
                return None
            rec.t_enqueued = time.perf_counter()
            self._ensure_completion_thread()
            with self._inflight_lock:
                self._inflight += 1
                inflight = self._inflight
            self.metrics.record_pipeline(self.pipeline_depth, inflight)
            self._inflight_q.put(rec)
        return rec

    def _completer(self) -> None:
        """Stage 3: block on the oldest in-flight batch, copy out, resolve
        futures in submission order, run observer/metrics/slow-log."""
        while True:
            rec = self._inflight_q.get()
            if rec is None:
                return
            try:
                self._complete(rec)
            finally:
                with self._inflight_lock:
                    self._inflight -= 1
                    inflight = self._inflight
                # release AFTER _complete: the staging slot must not be
                # reusable until copy-out and the observer are done with it
                self._inflight_sem.release()
                self.metrics.record_pipeline(self.pipeline_depth, inflight)
                rec.done.set()

    def _complete(self, rec: _InFlight) -> None:
        batch = rec.batch
        t3 = time.perf_counter()
        try:
            # the pipelined path's intended sync point: the completion
            # thread blocks on the oldest in-flight batch off the dispatch
            # path, then copies results out
            with host_range("serve.device_wait"):
                jax.block_until_ready((rec.dist, rec.ids))  # raft-tpu: ignore[HOSTSYNC] completion-thread batch barrier
            t4 = time.perf_counter()
            with host_range("serve.copy_out"):
                dist = np.asarray(rec.dist)  # raft-tpu: ignore[HOSTSYNC] staged copy-out after the barrier
                ids = np.asarray(rec.ids)  # raft-tpu: ignore[HOSTSYNC] staged copy-out after the barrier
                work = _read_search_work(rec.search_work, rec.n)
        except Exception as exc:  # noqa: BLE001 — fail only this batch
            spans.finish_span(rec.sp)
            self._record_flight(
                seq=rec.seq, batch=batch, n=rec.n, bucket=rec.bucket,
                compiles=rec.compiles,
                t_pickup=rec.t_pickup, t_done=time.perf_counter(),
                stages_s={"pad": rec.t_pad, "dispatch": rec.t_dispatch},
                waits_s={
                    "queue": max(rec.queue_waits, default=0.0),
                    "inflight_wait": rec.inflight_wait,
                },
                error=repr(exc),
                kernel_path=rec.kernel_path,
                hedged=rec.hedged,
                admit_level=rec.admit_level,
                page=rec.page,
                dispatch_info=rec.dispatch_info,
            )
            self.metrics.record_error("device", len(batch))
            obs_events.publish(
                "batch_error", "batch_exception",
                index=self.metrics.name, bucket=rec.bucket, cause="device",
                requests=len(batch), error=repr(exc),
            )
            for req in batch:
                req.future.set_exception(exc)
            return
        t_device = t4 - t3
        # device-busy union for the idle-fraction estimate: FIFO completion
        # means intervals arrive ordered by start time.  Hedged batches
        # already reported their members' windows via _note_device_interval
        # — adding [t_enqueued, t4] again would double-count the pair.
        if not rec.hedged:
            with self._inflight_lock:
                if t4 > self._busy_until:
                    self._busy_s += t4 - max(rec.t_enqueued, self._busy_until)
                    self._busy_until = t4
        if rec.sp is not None:
            rec.sp.add_stage("queue", max(rec.queue_waits, default=0.0))
            rec.sp.add_stage("pad", rec.t_pad)
            rec.sp.add_stage("inflight_wait", rec.inflight_wait)
            rec.sp.add_stage("dispatch", rec.t_dispatch)
            rec.sp.add_stage("device", t_device)
        spans.finish_span(rec.sp)
        done = time.perf_counter()
        off = 0
        lats = []
        with host_range("serve.resolve"):
            for req in batch:
                req.future.set_result(self._result_view(req, dist, ids, off))
                off += req.rows.shape[0]
                lats.append(done - req.t_submit)
        t_rec = time.perf_counter()
        with host_range("serve.record"):
            observer = self.observer
            if observer is not None:
                # the staging slot outlives this call only until the
                # semaphore releases, but the auditor holds samples longer —
                # hand it a copy of the real rows (dist/ids are fresh arrays
                # already)
                try:
                    observer(rec.padded[: rec.n].copy(), dist[: rec.n],
                             ids[: rec.n])
                except Exception:  # noqa: BLE001 — auditing never fails serving
                    pass
            self.metrics.record_queue_depth(self.queue_depth())
            stages = {
                "queue": rec.queue_waits,
                "pad": (rec.t_pad,),
                "inflight_wait": (rec.inflight_wait,),
                "dispatch": (rec.t_dispatch,),
                "device": (t_device,),
            }
            if rec.coalesce is not None:
                stages["coalesce"] = (rec.coalesce,)
            self.metrics.record_batch(
                rec.n, rec.bucket, lats, rec.compiles,
                stages=stages,
                request_ids=[r.req_id for r in batch],
                kernel_path=rec.kernel_path,
            )
            if work is not None:
                self.metrics.record_search_work(rec.n, *work)
            self._record_flight(
                seq=rec.seq, batch=batch, n=rec.n, bucket=rec.bucket,
                compiles=rec.compiles,
                t_pickup=rec.t_pickup, t_done=done,
                stages_s={
                    "pad": rec.t_pad,
                    "dispatch": rec.t_dispatch,
                    "completer_wait": max(0.0, t3 - rec.t_enqueued),
                    "device": t_device,
                    "copy_out": done - t4,
                },
                waits_s={
                    "queue": max(rec.queue_waits, default=0.0),
                    "inflight_wait": rec.inflight_wait,
                },
                kernel_path=rec.kernel_path,
                hedged=rec.hedged,
                admit_level=rec.admit_level,
                page=rec.page,
                dispatch_info=rec.dispatch_info,
            )
            if rec.compiles and self._warm:
                # a recompile on the warmed hot path is a shape leak:
                # capture the surrounding traffic while it is still in the
                # ring
                obs_events.publish(
                    "hot_recompile",
                    index=self.metrics.name, bucket=rec.bucket,
                    compiles=rec.compiles,
                )
            if rec.sp is not None:
                slowlog.maybe_record(
                    rec.sp,
                    latency_s=max(lats, default=0.0),
                    detail={
                        "index": self.metrics.name,
                        "requests": len(batch),
                        "bucket": rec.bucket,
                        "compiles": rec.compiles,
                        "request_ids": [r.req_id for r in batch],
                        **self._explain_summary(rec.kernel_path, rec.page),
                    },
                )
        self.metrics.record_stage("record", time.perf_counter() - t_rec)


def _squeeze_result(inner: Future, outer: Future) -> None:
    exc = inner.exception()
    if exc is not None:
        outer.set_exception(exc)
        return
    dist, ids = inner.result()
    outer.set_result((dist[0], ids[0]))
