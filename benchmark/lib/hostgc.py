"""Garbage-collection pauses over a window, from the ``host`` counters
that ``SearchService.stats`` returns (cumulative since the process
started).  A program without those counters gives no reading."""

from __future__ import annotations

import sys


def window_s(run) -> float:
    w = run.window
    return max(w.t_last, w.t_close) - w.t0


def pause_ms_per_s(run, label: str):
    h0, h1 = run.stats0.get("host"), run.stats1.get("host")
    span = window_s(run)
    if h0 is None or h1 is None or span <= 0:
        return None
    n = [b - a for a, b in zip(h0["gc_count_by_gen"], h1["gc_count_by_gen"])]
    s = [b - a for a, b in zip(h0["gc_pause_s_by_gen"],
                               h1["gc_pause_s_by_gen"])]
    print(f"{label}: window {span!r} s, collections by generation {n}, "
          f"pause s by generation {s!r}", file=sys.stderr)
    return (h1["gc_pause_s"] - h0["gc_pause_s"]) * 1e3 / span
