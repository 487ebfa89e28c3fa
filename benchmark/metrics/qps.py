"""Query rows answered in the window over the window's length: from the
first send to the last answer (host clock)."""


def read(run):
    span = run.window.t_last - run.window.t0
    return run.rows_answered / span if span > 0 else None
