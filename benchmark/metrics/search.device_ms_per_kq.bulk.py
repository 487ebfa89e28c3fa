"""IVF search + refine: device busy time (the union of device-op intervals
in the traced window) per 1,000 query rows answered in it, in ms."""


def read(run):
    if run.trace is None or not run.trace["busy_s"] or not run.rows_answered:
        return None
    return run.trace["busy_s"] * 1e3 / (run.rows_answered / 1000.0)
