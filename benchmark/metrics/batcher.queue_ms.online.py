"""Service / batcher: mean wait of a request from submit to the moment
the batcher picks its batch up, in ms, over the window (the service's
exact stage sums ``stage_sum_s`` / ``stage_n`` of the ``queue`` stage,
differenced across the window)."""


def read(run):
    s0, s1 = run.stats0.get("stage_sum_s"), run.stats1.get("stage_sum_s")
    if s0 is None or s1 is None:
        return None
    n = run.stats1["stage_n"].get("queue", 0) - run.stats0["stage_n"].get(
        "queue", 0)
    if n <= 0:
        return None
    return (s1["queue"] - s0.get("queue", 0.0)) / n * 1e3
