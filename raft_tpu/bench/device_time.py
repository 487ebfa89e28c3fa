"""Device-side timing for bench counters (ref: the reference's gbench
driver reports GPU time from CUDA events next to wall time,
cpp/bench/ann/src/common/benchmark.hpp:165,330-333).

The JAX analog: capture a ``jax.profiler`` trace around the measured
calls and sum the device-plane event durations from the ``*.xplane.pb``
dump. The dump is a TensorFlow-profiler XSpace protobuf; TF isn't in the
image, so a ~60-line protobuf *wire* parser extracts just what the
counter needs (plane name, line events, event durations) — the schema is
stable and public (tsl/profiler/protobuf/xplane.proto: XSpace.planes=1;
XPlane.name=2,.lines=3; XLine.events=4; XEvent.offset_ps=2,
.duration_ps=3 — field numbers verified against a live dump by
the ``TestDeviceTime`` tests).

On host-only backends (CPU fallback) the profiler emits no ``/device:``
plane and :func:`measure_device_time` returns None — callers report the
counter as null rather than faking it with wall time.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
from typing import Dict, Iterator, Optional, Tuple, Union

_Field = Tuple[int, Union[int, bytes]]


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if not x & 0x80:
            return r, i
        s += 7


def _fields(b: bytes) -> Iterator[_Field]:
    """Iterate (field_number, value) over one protobuf message's wire
    bytes; varints decode to int, length-delimited fields to bytes,
    fixed32/64 skipped (unused by the XSpace subset we read)."""
    i, end = 0, len(b)
    while i < end:
        tag, i = _varint(b, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(b, i)
            yield fn, v
        elif wt == 2:
            ln, i = _varint(b, i)
            yield fn, b[i:i + ln]
            i += ln
        elif wt == 5:
            i += 4
        elif wt == 1:
            i += 8
        else:  # wire types 3/4 (groups) never appear in xplane dumps
            raise ValueError(f"unsupported wire type {wt}")


def _line_busy_ps(line: bytes) -> int:
    """Sum of XEvent.duration_ps over one XLine."""
    busy = 0
    for fn, v in _fields(line):
        if fn == 4 and isinstance(v, bytes):        # XLine.events
            for fe, ve in _fields(v):
                if fe == 3 and isinstance(ve, int):  # XEvent.duration_ps
                    busy += ve
    return busy


def plane_busy_ps(xplane_pb: bytes) -> Dict[str, int]:
    """plane name → busy picoseconds (max over the plane's lines of the
    per-line event-duration sum — the busiest executor lane, which for a
    serially-executing accelerator equals elapsed device time the way
    CUDA events measure it)."""
    out: Dict[str, int] = {}
    for fn, v in _fields(xplane_pb):
        if fn != 1 or not isinstance(v, bytes):      # XSpace.planes
            continue
        name, busiest = "", 0
        for fp, vp in _fields(v):
            if fp == 2 and isinstance(vp, bytes):    # XPlane.name
                name = vp.decode("utf-8", "replace")
            elif fp == 3 and isinstance(vp, bytes):  # XPlane.lines
                busiest = max(busiest, _line_busy_ps(vp))
        out[name] = busiest
    return out


def device_busy_seconds(log_dir: str) -> Optional[float]:
    """Device busy time recorded under a ``jax.profiler.trace`` log dir,
    or None when no device plane exists (host-only backend).  Busiest
    device plane, not the sum: one chip dumps several "/device:" planes
    (compute plus DMA/non-core lanes), and summing them double-counted
    overlap — round-4's on-chip ladder showed device time exceeding wall
    time, which is impossible for a single invocation.

    Multi-device semantics: across several chips the max-over-planes is
    the busiest single chip's busy time — a wall-clock-like QPS
    denominator for SPMD work (all chips run the same program in
    lockstep), NOT aggregate device work.  Do not read it as total busy
    seconds across the fleet; for per-chip accounting group planes by
    device ordinal instead."""
    dumps = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )
    busiest_ps = 0
    seen_device = False
    for path in dumps:
        with open(path, "rb") as fh:
            planes = plane_busy_ps(fh.read())
        for name, ps in planes.items():
            if name.startswith("/device:"):
                seen_device = True
                busiest_ps = max(busiest_ps, ps)
    return busiest_ps / 1e12 if seen_device else None


def measure_device_time(fn, *args) -> Optional[float]:
    """Run ``fn(*args)`` once under a profiler trace and return its device
    busy time in seconds (None on host-only backends or when the profiler
    is unavailable). The call is synchronized before and after so the
    trace contains exactly one invocation."""
    import jax

    tmp = tempfile.mkdtemp(prefix="raft_tpu_devtime_")
    try:
        jax.block_until_ready(args)
        try:
            trace = jax.profiler.trace(tmp)
            trace.__enter__()
        except Exception:
            # profiler unavailable (e.g. a second concurrent trace) — the
            # counter degrades to null; a failure of fn itself must NOT be
            # swallowed into the same null, so only the setup is guarded
            return None
        try:
            jax.block_until_ready(fn(*args))
        finally:
            trace.__exit__(None, None, None)
        return device_busy_seconds(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
