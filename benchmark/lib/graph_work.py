"""Operations and bytes of a graph walk's hops, counted from the walk's
own count of expanded parents.

A walk's length depends on the data, so its work cannot come from shapes
(``lib/work.py`` counts the IVF scan that way).  The program counts each
(query, parent) pair it expanded over the real rows of a batch and
reports the sums (``SearchService.stats()["search_work"]``).  Each
expansion scores the parent's ``degree`` neighbours:

- ops = expansions x degree x 2 x width (one multiply and one add per
  element of each candidate row);
- bytes = expansions x degree x (width x element size + 4): each
  candidate's row and its 4-byte id.

Every gather counts: the search defines no reuse between hops or queries.
"""

from __future__ import annotations

from typing import Optional, Tuple


def hop_work(expansions: float, *, degree: int, width: int,
             elem_bytes: int):
    """(ops, bytes) of ``expansions`` parent expansions."""
    ops = expansions * degree * 2 * width
    nbytes = expansions * degree * (width * elem_bytes + 4)
    return float(ops), float(nbytes)


def window_work(run) -> Optional[Tuple[int, int]]:
    """(expanded parents, real rows) the service counted over the window
    (the difference of its ``search_work`` counters), or None where the
    program keeps no such counter.  The counters move when a dispatch is
    copied out, so a window can hold a dispatch more or less than the
    requests it answered; their ratio does not depend on which."""
    w1 = run.stats1.get("search_work")
    if w1 is None:
        return None
    w0 = run.stats0.get("search_work") or {}
    return (int(w1["expansions"]) - int(w0.get("expansions", 0)),
            int(w1["rows"]) - int(w0.get("rows", 0)))


def expansions_per_row(run) -> Optional[float]:
    """Expanded parents per real row over the window, or None."""
    w = window_work(run)
    if w is None or w[1] <= 0:
        return None
    return w[0] / w[1]
