"""Kernels: the fused CAGRA hop's share of its roofline, %.

Kernel time is the summed device time of the hop's Pallas op, matched by
the name the trace gives it (``HOP_OPS``: the custom call is named after
the scope it is called in, ``%cagra_fused_hop.<n>``).  The work is
counted from the service's count of expanded parents: its expansions
per row over the window times the rows the window answered
(``benchmark.lib.graph_work``), so a dispatch copied out in the window
but run mostly before or after the trace does not count.  The share is
max(ops / peak FLOP/s,
bytes / peak B/s) over the kernel time; which bound it goes to stderr.
A run without the counter or without the op gives no reading.
"""

import sys

from benchmark.lib import graph_work, trace, work

HOP_OPS = r"^%?cagra_fused_hop"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    per_row = graph_work.expansions_per_row(run)
    seconds = trace.kernel_s(run.trace, HOP_OPS)
    if not per_row or not run.rows_answered or seconds <= 0:
        return None
    expansions = per_row * run.rows_answered
    s = run.shapes
    ops, nbytes = graph_work.hop_work(
        expansions, degree=s["graph_degree"], width=s["width"],
        elem_bytes=s["elem_bytes"])
    share, bound = work.roofline_share(
        ops, nbytes, seconds, run.peaks["flops_per_s"],
        run.peaks["bytes_per_s"])
    print(f"kernel.hop_roofline.bulk: {bound} bound, ops={ops!r} "
          f"bytes={nbytes!r} kernel_s={seconds!r} expansions={expansions!r}",
          file=sys.stderr)
    return share
