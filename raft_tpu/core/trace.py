"""Tracing / profiling ranges — nvtx parity for TPU.

The reference wraps every major entry point in an NVTX scoped range with a
dedicated ``raft`` domain (ref: cpp/include/raft/core/nvtx.hpp:49-82, used
at e.g. neighbors/detail/ivf_pq_build.cuh:1687).  The TPU equivalents are

- ``jax.profiler.TraceAnnotation`` — host-side Perfetto trace range, shows
  up in ``jax.profiler.trace`` captures (the "domain" maps to the
  ``raft_tpu.`` prefix); :func:`host_range` is this alone;
- ``jax.named_scope`` — attaches the name to the HLO ops traced under the
  range so device-side work is attributable in the profile;
- a :mod:`raft_tpu.obs` span — the queryable record: every range reports
  wall time into the metrics registry and becomes the attribution point
  for XLA compile/cache/transfer events, with no profiler attached.

:func:`trace_range` is all three.  All are near-zero-cost when nothing is
listening; the obs span adds one histogram record per call (bounded by
``tests/test_obs.py``'s overhead guard).

The module also owns the process-wide garbage-collection hook
(:func:`install_gc_hook`): it counts collections and their pauses, and
while a profiler records it marks each pause as a ``raft_tpu.host.gc``
range on the thread that triggered it.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import threading
import time
from typing import Callable, Dict, List, Optional, TypeVar

import jax

DOMAIN = "raft_tpu"

F = TypeVar("F", bound=Callable)

_spans = None  # lazy: raft_tpu.obs pulls numpy/logger machinery not needed
               # by pure-trace consumers until the first range actually opens


def _obs_spans():
    global _spans
    if _spans is None:
        from raft_tpu.obs import spans

        _spans = spans
    return _spans


def host_range(name: str) -> jax.profiler.TraceAnnotation:
    """Profiler range ``raft_tpu.<name>`` on the host timeline, and nothing
    else: no named scope, no obs span, no histogram.  ``name`` must be a
    static string — a capture keys ranges by their full name."""
    return jax.profiler.TraceAnnotation(f"{DOMAIN}.{name}")


@contextlib.contextmanager
def trace_range(name: str):
    """Scoped profiler range ``raft_tpu.<name>`` (ref: nvtx.hpp range).

    Yields the open :class:`raft_tpu.obs.Span` (or ``None`` when obs is
    disabled) so call sites can attach stage timings::

        with trace_range("serve.batch") as sp:
            ...
            if sp is not None:
                sp.add_stage("dispatch", dt)
    """
    with host_range(name), jax.named_scope(name):
        with _obs_spans().span(name) as sp:
            yield sp


def traced(name: Optional[str] = None) -> Callable[[F], F]:
    """Decorator form of :func:`trace_range` for public API entries.

    The wrapper carries ``__traced__`` (the range label) so static checks
    — ``tests/test_trace_coverage.py`` — can verify every public entry
    point ships observable.
    """

    def deco(fn: F) -> F:
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with trace_range(label):
                return fn(*args, **kwargs)

        wrapper.__traced__ = label  # type: ignore[attr-defined]
        return wrapper  # type: ignore[return-value]

    return deco


# ---- garbage-collection pauses --------------------------------------------

GC_RANGE = "host.gc"

_profiling = jax.profiler.TraceAnnotation.is_enabled
_gc_lock = threading.Lock()
_gc_installed = False
# CPython runs one collection at a time, start and stop on the thread that
# triggered it, so one slot holds the open collection's state
_gc_t0 = 0.0
_gc_range: Optional[jax.profiler.TraceAnnotation] = None
_gc_count: List[int] = [0, 0, 0]
_gc_pause_s: List[float] = [0.0, 0.0, 0.0]


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0, _gc_range
    if phase == "start":
        if _profiling():
            _gc_range = host_range(GC_RANGE)
            _gc_range.__enter__()
        _gc_t0 = time.perf_counter()
        return
    gen = info["generation"]
    _gc_pause_s[gen] += time.perf_counter() - _gc_t0
    _gc_count[gen] += 1
    if _gc_range is not None:
        r, _gc_range = _gc_range, None
        r.__exit__(None, None, None)


def install_gc_hook() -> None:
    """Count collections and time their pauses (idempotent, process-wide).

    With no profiler recording, a collection costs the hook two clock
    reads and two additions; with one recording, each pause is also a
    ``raft_tpu.host.gc`` range on the collecting thread."""
    global _gc_installed
    with _gc_lock:
        if not _gc_installed:
            gc.callbacks.append(_on_gc)
            _gc_installed = True


def gc_stats() -> Dict[str, object]:
    """Collections and pause seconds since :func:`install_gc_hook`, in total
    and by generation (0, 1, 2).  Cumulative: difference two reads to get
    a window's."""
    count, pause = list(_gc_count), list(_gc_pause_s)
    return {
        "gc_count": sum(count),
        "gc_pause_s": sum(pause),
        "gc_count_by_gen": count,
        "gc_pause_s_by_gen": pause,
    }
