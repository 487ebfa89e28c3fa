"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with
``jax.profiler.ProfileData`` and nothing else: the op events of each TPU
device plane (its ``XLA Ops`` line) and the host spans the benchmark and
the program open (``TraceAnnotation`` names starting ``bench.`` or
``raft_tpu.``).  ``reduce`` works on those plain tuples, so it is tested on
recorded and made-up events alike:

- busy time is the union of a chip's op intervals inside the window,
  averaged over the chips used; the idle share is 1 minus busy over window;
- time by op name, summed, from which a reader takes a kernel's time by
  the ops whose name matches it (``kernel_s``);
- each idle gap is named by the innermost host span open at its middle.

This replaces ``raft_tpu/bench/device_time.py``, which took the largest of
each line's summed durations: no union of intervals, no split by kernel.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "raft_tpu.")
WINDOW_SPAN = "bench.window"

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def op_name(name: str) -> str:
    """A TPU op event carries its whole HLO instruction as its name:
    keep the instruction's own name, e.g. ``%ivf_scan_probe_major.1``."""
    return name.split(" = ", 1)[0]


def options():
    """Profiler options of a traced run: device ops and the host's
    ``TraceAnnotation`` spans, without the Python tracer, which records
    every Python call and slowed the online cell's host several-fold."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    """({device plane: [op events]}, [host span events])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    device.setdefault(plane.name, []).extend(
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(HOST_PREFIXES))
    return device, host


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted ``[(start, end)]`` covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def window_of(host: Sequence[Event]) -> Tuple[float, float]:
    spans = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return max(spans, key=lambda w: w[1] - w[0])


def name_points(host: Sequence[Event], points: Sequence[float]) -> List[str]:
    """For each time in ``points``, the name of the shortest host span that
    covers it ("none" where none does; the window span names nothing)."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name != WINDOW_SPAN)
    order = sorted(range(len(points)), key=lambda j: points[j])
    out = ["none"] * len(points)
    active: List[Tuple[float, float, str]] = []
    i = 0
    for j in order:
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [a for a in active if a[1] >= t]
        if active:
            out[j] = min(active, key=lambda a: a[1] - a[0])[2]
    return out


def reduce(device: Dict[str, List[Event]], host: Sequence[Event],
           window: Tuple[float, float], top: int = 10) -> dict:
    """Busy and idle time, time by op name, and named idle gaps over
    ``window`` (ns).  Times come back in seconds, averaged over chips."""
    lo, hi = window
    chips = sorted(device)
    n = max(1, len(chips))
    busy = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gaps_by_span: Dict[str, float] = defaultdict(float)
    for chip in chips:
        clipped = list(_clip(device[chip], lo, hi))
        merged = union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged)
        for name, a, b in clipped:
            op_time[name] += b - a
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
        names = name_points(host, [(g0 + g1) / 2 for g0, g1 in gaps])
        for (g0, g1), name in zip(gaps, names):
            gaps_by_span[name] += g1 - g0
    ns = 1e-9 / n
    window_s = (hi - lo) * 1e-9
    busy_s = busy * ns
    return {
        "chips": len(chips),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "op_s": {k: v * ns for k, v in op_time.items()},
        "device_ops": sorted(((k, v * ns) for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v * ns) for k, v in gaps_by_span.items()),
                            key=lambda kv: -kv[1])[:top],
        "n_ops": sum(len(v) for v in device.values()),
    }


def kernel_s(reduction: dict, pattern: str) -> float:
    """Summed device seconds of the ops whose name matches ``pattern``."""
    pat = re.compile(pattern)
    return sum(v for k, v in reduction["op_s"].items() if pat.search(k))
