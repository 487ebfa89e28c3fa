"""Host runtime: milliseconds of garbage-collection pauses in the process
per second of the window (the service's ``host`` counters, differenced
across the window).  The collections by generation go to stderr."""

from benchmark.lib import hostgc


def read(run):
    return hostgc.pause_ms_per_s(run, "host.gc_ms_per_s.online")
