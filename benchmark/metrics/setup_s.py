"""Seconds from the process's start to the window's open: JAX start, data,
build, warmup and the one second of warming traffic."""


def read(run):
    return run.setup_s
