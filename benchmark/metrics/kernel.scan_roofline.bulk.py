"""Kernels: the IVF list scan's share of its roofline, %.

Kernel time is the summed device time of the scan's Pallas ops, matched by
the name the trace gives them (``SCAN_OPS``: on the chip the custom call
is named after the kernel's wrapper, ``%ivf_scan_probe_major.1``).  The work is counted from
shapes (``benchmark.lib.work.scan_work``) over the window's dispatches.
The share is max(ops / peak FLOP/s, bytes / peak B/s) over the kernel
time; which of the two bounds it goes to stderr, not the result line.
A trace with no scan op gives no reading.
"""

import sys

from benchmark.lib import trace, work

SCAN_OPS = r"^%?ivf_scan_(probe|query)_major"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = trace.kernel_s(run.trace, SCAN_OPS)
    batches = run.stats1["batches"] - run.stats0["batches"]
    if seconds <= 0 or batches <= 0:
        return None
    s = run.shapes
    ops, nbytes = work.scan_work(
        batches, run.rows_answered / batches, n_rows=s["n_rows"],
        n_lists=s["n_lists"], n_probes=s["n_probes"], width=s["width"],
        elem_bytes=s["elem_bytes"])
    share, bound = work.roofline_share(
        ops, nbytes, seconds, run.peaks["flops_per_s"],
        run.peaks["bytes_per_s"])
    print(f"kernel.scan_roofline.bulk: {bound} bound, ops={ops!r} "
          f"bytes={nbytes!r} kernel_s={seconds!r}", file=sys.stderr)
    return share
