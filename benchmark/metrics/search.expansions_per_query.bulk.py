"""Graph search: parents the walk expanded per query row, over the
window (the service's ``search_work`` counters of expansions and real
rows, differenced across the window).  A run without the counter gives no
reading."""

from benchmark.lib import graph_work


def read(run):
    return graph_work.expansions_per_row(run)
