"""Service / batcher: host time per dispatched batch spent in the hooks
that run after its answers are delivered (observer, metrics, flight
recorder, slow-log: the service's ``record`` stage), in ms, over the
window."""


def read(run):
    s0, s1 = run.stats0.get("stage_sum_s"), run.stats1.get("stage_sum_s")
    if s0 is None or s1 is None or "record" not in s1:
        return None
    batches = run.stats1["batches"] - run.stats0["batches"]
    if batches <= 0:
        return None
    return (s1["record"] - s0.get("record", 0.0)) / batches * 1e3
