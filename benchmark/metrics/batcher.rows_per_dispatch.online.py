"""Service / batcher: query rows answered in the window per batch the
batcher dispatched in it (service counter ``batches``)."""


def read(run):
    batches = run.stats1["batches"] - run.stats0["batches"]
    return run.rows_answered / batches if batches > 0 else None
