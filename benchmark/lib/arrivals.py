"""Arrival schedule of the open loop.

A variant of ``bench.py``'s ``poisson_arrivals`` (open loop, latency from
the scheduled time), which draws the exponential gaps at random: here
every seed gets the same set of ``round(rate * seconds)`` gaps (the
distribution's quantiles) in an order the seed draws.  So every seed
offers the same load over the same span and only the order of the gaps
moves, which keeps the seed from changing the work.
"""

from __future__ import annotations

import numpy as np


def fixed_poisson_arrivals(rate_qps: float, seconds: float, seed: int):
    """Offsets (seconds from the stream's start) of ``round(rate*seconds)``
    arrivals whose gaps are the exponential quantiles at ``rate_qps``,
    shuffled by ``seed``.  The first arrival comes one gap after 0."""
    n = max(1, int(round(rate_qps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_qps
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.cumsum(gaps)
