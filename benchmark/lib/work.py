"""Operations and bytes of the IVF list scan, counted from shapes.

The count is of the work the search needs, whatever implements it, so a
later PR that changes the scan does not change its yardstick (the
formulas of ``raft_tpu/ops/cost.py`` live with the program; these follow
the issue's definition instead):

- ops = query rows x probed rows per query x 2 x scanned width;
- bytes = the distinct list rows one dispatch probes x scanned width x the
  element size the configuration declares, summed over dispatches.

Probed rows per query are ``n_probes * rows / n_lists``: the mean list
size, as the build's lists are balanced.  The distinct lists of a dispatch
of ``b`` queries are the expectation ``L * (1 - (1 - p/L) ** b)`` for
queries that each probe ``p`` of ``L`` lists at random.
"""

from __future__ import annotations


def scan_work(dispatches: int, rows_per_dispatch: float, *, n_rows: int,
              n_lists: int, n_probes: int, width: int, elem_bytes: int):
    """(ops, bytes) of ``dispatches`` scans of ``rows_per_dispatch`` queries
    each."""
    per_list = n_rows / n_lists
    probed = n_probes * per_list
    ops = dispatches * rows_per_dispatch * probed * 2 * width
    lists = n_lists * (1.0 - (1.0 - n_probes / n_lists) ** rows_per_dispatch)
    nbytes = dispatches * lists * per_list * width * elem_bytes
    return float(ops), float(nbytes)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   flops_per_s: float, bytes_per_s: float):
    """(share of the roofline in %, the bound: "compute" or "memory")."""
    t_ops, t_bytes = ops / flops_per_s, nbytes / bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
