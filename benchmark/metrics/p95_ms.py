"""95th percentile of request latency over every request of the window,
timed from each request's scheduled send time (host clock).  A request
with no answer counts as slower than every answered one."""

import numpy as np


def read(run):
    lat = np.array([r.latency_s() for r in run.window.requests])
    if lat.size == 0:
        return None
    cap = max(run.window.t_last, run.window.t_close) - run.window.t0
    return float(np.percentile(np.minimum(lat, 2 * cap), 95) * 1e3)
