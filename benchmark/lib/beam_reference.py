"""The plain reference of CAGRA search: one query's beam search over a
graph, in numpy float32, with no tiling, no batching and no kernel.

It imports nothing of the program.  It follows the reference release's
single-CTA search (``search_single_cta_kernel-inl.cuh``): a buffer of the
``itopk`` best (distance, id) pairs with an explored flag each; every
iteration takes the ``search_width`` best unexplored entries as parents,
marks them explored, gathers their graph rows as candidates, scores them
against the query, drops the candidates already in the buffer or repeated
earlier in the same batch, merges the rest into the buffer and keeps the
``itopk`` best.  The walk stops once no unexplored entry is left after
``min_iterations``, or at ``max_iterations``.

Departures from the reference, each what the program does too:

- the seed ids are an input.  The program seeds each query from a coarse
  entry-point table (a TPU-first addition) topped up with random rows;
  the reference draws random rows alone.  Seeds are scored, deduplicated
  and cut to the ``itopk`` best, as the reference's random init is;
- "already seen" means "in the buffer now".  The reference's visited
  hash table also remembers nodes evicted from the buffer; here an
  evicted node can come back as a candidate, and is dropped again only if
  it cannot beat the buffer;
- ties in distance keep the buffer's entries ahead of new candidates, and
  earlier candidates ahead of later ones (a stable merge);
- distances are those the program reports: the squared L2 distance
  ``|q|^2 + |x|^2 - 2 q.x`` floored at 0, or the inner product, each
  computed in float32 (numpy accumulates in float32 too, in its own
  order, so the last bits differ from the program's).

Exact kNN ground truth is ``benchmark.lib.reference``; this reference
exists to hold the program's walk to the algorithm, row by row.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def _distances(dataset: np.ndarray, query: np.ndarray, ids: np.ndarray,
               metric: str) -> np.ndarray:
    """float32 distance of ``query`` to each row ``ids`` (smaller is
    nearer: the inner product is negated)."""
    x = dataset[ids].astype(np.float32)
    ip = x @ query
    if metric == "inner_product":
        return (-ip).astype(np.float32)
    if metric != "sqeuclidean":
        raise ValueError(f"beam reference supports sqeuclidean and "
                         f"inner_product, not {metric!r}")
    d = np.float32(query @ query) + np.einsum("ij,ij->i", x, x) - 2.0 * ip
    return np.maximum(d, 0.0).astype(np.float32)


def search(dataset: np.ndarray, graph: np.ndarray, query: np.ndarray,
           seed_ids: np.ndarray, itopk: int, search_width: int,
           max_iterations: int, min_iterations: int, metric: str,
           k: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """(distances [k], ids [k], expanded parents) of one query, nearest
    first, in the program's convention: the inner product is reported as
    is (larger is nearer), the squared L2 distance as is.  Slots the walk
    could not fill read id -1 at +inf (-inf for inner product)."""
    n = dataset.shape[0]
    query = np.asarray(query, np.float32)
    # ---- init: score the seeds, drop repeats and invalid ids, keep the
    # itopk best
    seeds = np.asarray(seed_ids, np.int64)
    seeds = seeds[(seeds >= 0) & (seeds < n)]
    seeds = seeds[np.sort(np.unique(seeds, return_index=True)[1])]
    d = _distances(dataset, query, seeds, metric)
    order = np.argsort(d, kind="stable")[:itopk]
    buf_d, buf_i = d[order], seeds[order]
    explored = np.zeros(buf_i.shape[0], bool)
    expanded = 0
    it = 0
    while it < max_iterations:
        frontier = np.flatnonzero(~explored)
        if frontier.size == 0 and it >= min_iterations:
            break
        # ---- parents: the search_width best unexplored entries (the
        # buffer is kept sorted, so the first ones)
        parents = frontier[:search_width]
        explored[parents] = True
        expanded += parents.size
        it += 1
        if parents.size == 0:
            continue
        # ---- candidates: the parents' graph rows, in parent order
        cand = graph[buf_i[parents]].reshape(-1).astype(np.int64)
        cand = cand[(cand >= 0) & (cand < n)]
        cand = cand[~np.isin(cand, buf_i) & _first_occurrence(cand)]
        if cand.size == 0:
            continue
        cd = _distances(dataset, query, cand, metric)
        # ---- merge: buffer first, so it wins ties; keep the itopk best
        all_d = np.concatenate([buf_d, cd])
        all_i = np.concatenate([buf_i, cand])
        all_e = np.concatenate([explored, np.zeros(cand.shape[0], bool)])
        order = np.argsort(all_d, kind="stable")[:itopk]
        buf_d, buf_i, explored = all_d[order], all_i[order], all_e[order]
    top = min(k, buf_i.shape[0])
    out_d = np.full(k, np.inf, np.float32)
    out_i = np.full(k, -1, np.int64)
    out_d[:top], out_i[:top] = buf_d[:top], buf_i[:top]
    if metric == "inner_product":
        out_d = -out_d
    return out_d, out_i, expanded


def _first_occurrence(ids: np.ndarray) -> np.ndarray:
    """True at the first position of each distinct id."""
    first = np.zeros(ids.shape[0], bool)
    first[np.unique(ids, return_index=True)[1]] = True
    return first


def search_rows(dataset, graph, queries, seed_ids, *, itopk: int,
                search_width: int, max_iterations: int, min_iterations: int,
                metric: str, k: int):
    """:func:`search` of each row of ``queries`` with its row of
    ``seed_ids``: (distances [q, k], ids [q, k], expanded parents [q])."""
    dataset = np.asarray(dataset, np.float32)
    graph = np.asarray(graph)
    q = queries.shape[0]
    out_d = np.empty((q, k), np.float32)
    out_i = np.empty((q, k), np.int64)
    expanded = np.empty(q, np.int64)
    for r in range(q):
        out_d[r], out_i[r], expanded[r] = search(
            dataset, graph, queries[r], seed_ids[r], itopk, search_width,
            max_iterations, min_iterations, metric, k)
    return out_d, out_i, expanded


def auto_max_iterations(itopk: int, search_width: int,
                        entry_seeded: bool) -> int:
    """The iteration cap a search gets when none is given: the reference's
    rule (``search_plan.cuh``) of itopk over width, which the program
    halves from a floor of 16 to a floor of 8 when the seeds come from its
    entry-point table."""
    per = (itopk + search_width - 1) // search_width
    return max(8, per) if entry_seeded else max(16, 2 * per)
