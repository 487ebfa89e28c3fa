"""Device: share of the traced window with no op running on the chip, %."""


def read(run):
    if run.trace is None or not run.trace["chips"]:
        return None
    return 100.0 * run.trace["idle_share"]
