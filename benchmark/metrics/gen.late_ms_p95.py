"""Load generator: 95th percentile of how late each open-loop request was
sent after its scheduled time, in ms.  Closed loops have no schedule."""

import numpy as np


def read(run):
    if run.traffic["loop"] != "open" or not run.window.requests:
        return None
    late = [r.t_send - r.t_sched for r in run.window.requests]
    return float(np.percentile(late, 95) * 1e3)
