"""How ``correct`` is decided.

Every answer the window served is judged by what it says, once the window
has closed and the program's state is freed:

- ``unanswered``: requests sent in the window that never got an answer, or
  got an error.  Limit 0.
- ``bad_rows``: answered rows with an id outside the base, a repeated id,
  or distances out of nearest-first order.  Limit 0.
- ``dist_err``: the widest gap between a distance the program reported and
  the exact f32 distance of that (query, id) pair from the raw rows, as a
  share of the pair's ``scale`` (see ``reference.pair_distances``).  Its
  limit is in the configuration file, set between the program's readings
  and the bf16 control's (PERF.md gives both).
- ``recall_miss``: one less the recall of the served ids, the share of
  the exact top-k (``reference.search``) that the answered rows miss, over
  every answered row.  ``dist_err`` holds each returned distance to its
  id; this holds the ids to the exact answer, so that answers that lose
  recall (fewer probes, a coarser scan cache) come out not correct.  Its
  limit is in the configuration file, set between the program's readings
  and those of the control and planted faults (PERF.md gives them).
- ``window_compiles``: XLA compiles inside the measured window.  Limit 0.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.lib import reference


def answered_rows(requests):
    """(pool rows [R], distances [R, k], ids [R, k]) of every answered
    request's rows, in send order."""
    ok = [r for r in requests if r.ok]
    if not ok:
        return (np.zeros(0, np.int64), np.zeros((0, 1), np.float32),
                np.zeros((0, 1), np.int64))
    rows = np.concatenate([r.rows for r in ok])
    dists = np.concatenate([np.atleast_2d(r.dists) for r in ok])
    ids = np.concatenate([np.atleast_2d(r.ids) for r in ok])
    return rows, dists.astype(np.float32), ids.astype(np.int64)


def bad_rows(dists: np.ndarray, ids: np.ndarray, n: int, metric: str) -> int:
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    step = np.diff(dists, axis=1)
    order = (step > 0) if metric == "inner_product" else (step < 0)
    unsorted = order.any(axis=1) | ~np.isfinite(dists).all(axis=1)
    return int((out_of_range | repeated | unsorted).sum())


def dist_err(base, pool, rows, dists, ids, metric: str) -> float:
    n = base.shape[0]
    valid = (ids >= 0) & (ids < n)
    exact, scale = reference.pair_distances(
        base, pool[rows], np.where(valid, ids, 0).astype(np.int32), metric)
    gap = np.abs(dists.astype(np.float64) - exact) / np.maximum(scale, 1e-30)
    gap = np.where(valid, gap, 0.0)
    return float(gap.max()) if gap.size else 0.0


def recall(ids: np.ndarray, rows: np.ndarray, truth: np.ndarray) -> float:
    """Mean over answered rows of |served ∩ exact top-k| / k."""
    if rows.size == 0:
        return 0.0
    t = truth[rows]
    k = t.shape[1]
    hits = (ids[:, :, None] == t[:, None, :]).any(axis=2).sum(axis=1)
    return float(hits.mean() / k)


def judge(requests, base, pool, truth, metric: str, limits: Dict[str, float],
          window_compiles: int):
    """(checks, correct): ``checks`` maps each compared number's short name
    to ``{"value", "limit"}``; ``truth`` is the exact top-k ids of every
    pool row."""
    rows, dists, ids = answered_rows(requests)
    values = {
        "unanswered": sum(not r.ok for r in requests),
        "bad_rows": bad_rows(dists, ids, base.shape[0], metric),
        "dist_err": dist_err(base, pool, rows, dists, ids, metric),
        "recall_miss": 1.0 - recall(ids, rows, truth),
        "window_compiles": int(window_compiles),
    }
    lim = {"unanswered": 0, "bad_rows": 0, "window_compiles": 0,
           "dist_err": float(limits["dist_err"]),
           "recall_miss": float(limits["recall_miss"])}
    checks = {k: {"value": v, "limit": lim[k]} for k, v in values.items()}
    correct = bool(requests) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return checks, correct


def check_lines(checks) -> List[str]:
    return [f"check {name} {c['value']!r} <= {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
            for name, c in checks.items()]
