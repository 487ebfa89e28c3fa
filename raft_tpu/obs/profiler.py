"""One-line Perfetto captures: ``with obs.profile(dir): ...``.

Wraps ``jax.profiler.trace`` the way the Ragged Paged Attention tooling
wraps its Perfetto captures (arxiv 2604.15464): the capture is bracketed
in a span so registry snapshots record that (and how long) a profiling
session ran, and the ``RAFT_TPU_DISABLE_PROFILER`` escape hatch from
``core.trace`` still applies — CI boxes without a writable trace dir can
no-op the capture without touching call sites.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

from raft_tpu.core import env as _env
from raft_tpu.obs import spans as _spans
from raft_tpu.obs.registry import default_registry


@contextlib.contextmanager
def profile(log_dir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a Perfetto/XPlane trace of the enclosed block to ``log_dir``.

    View with ``xprof`` / TensorBoard's profile plugin, or load the
    ``.trace.json.gz`` into https://ui.perfetto.dev.  Every
    ``trace_range``-wrapped call inside shows as a named host range;
    device ops carry the matching ``jax.named_scope`` labels.
    """
    if _env.env_bool("RAFT_TPU_DISABLE_PROFILER"):
        yield
        return
    import jax

    default_registry().counter(
        "raft_tpu_profile_captures_total",
        help="jax.profiler trace sessions started via obs.profile",
    ).inc()
    with _spans.span("obs.profile"):
        with jax.profiler.trace(log_dir):
            yield
