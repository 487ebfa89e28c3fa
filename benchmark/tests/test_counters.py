"""Tests of the readers of the service's window counters (the batcher's
exact stage sums and the process's garbage-collection pauses), on made-up
``stats()`` snapshots.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

NEW = ["batcher.queue_ms.online", "batcher.record_ms_per_batch.online",
       "host.gc_ms_per_s.bulk", "host.gc_ms_per_s.online"]


def _reader(name):
    return bench_run.load_module(
        os.path.join(bench_run.HERE, "metrics", name + ".py"), "m")


def _run(stats0, stats1, t_last=10.0):
    win = SimpleNamespace(t0=0.0, t_close=10.0, t_last=t_last, requests=[])
    return SimpleNamespace(stats0=stats0, stats1=stats1, trace=None,
                           rows_answered=1000, window=win)


S0 = {"batches": 10, "stage_sum_s": {"queue": 1.0, "record": 0.5},
      "stage_n": {"queue": 100, "record": 10},
      "host": {"gc_pause_s": 0.1, "gc_count": 5,
               "gc_count_by_gen": [5, 0, 0],
               "gc_pause_s_by_gen": [0.1, 0.0, 0.0]}}
S1 = {"batches": 30, "stage_sum_s": {"queue": 3.0, "record": 0.7},
      "stage_n": {"queue": 500, "record": 30},
      "host": {"gc_pause_s": 0.35, "gc_count": 9,
               "gc_count_by_gen": [7, 1, 1],
               "gc_pause_s_by_gen": [0.15, 0.05, 0.15]}}


def test_counter_readers_difference_the_window():
    run = _run(S0, S1)
    # 2.0 s of queue wait over 400 requests; 0.2 s of hooks over 20 batches
    assert _reader("batcher.queue_ms.online").read(run) == pytest.approx(5.0)
    assert _reader("batcher.record_ms_per_batch.online").read(run) == \
        pytest.approx(10.0)
    # 0.25 s of pauses over a 10 s window
    for cell in ("bulk", "online"):
        assert _reader(f"host.gc_ms_per_s.{cell}").read(run) == \
            pytest.approx(25.0)


def test_gc_window_runs_to_the_last_answer(capsys):
    run = _run(S0, S1, t_last=12.5)
    assert _reader("host.gc_ms_per_s.bulk").read(run) == pytest.approx(20.0)
    err = capsys.readouterr().err
    assert "collections by generation [2, 1, 1]" in err


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_on_a_program_without_the_counters(name):
    """A program that lacks the new counters (the parent's) gives no
    reading, and no error."""
    assert _reader(name).read(_run({"batches": 1}, {"batches": 2})) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_on_an_empty_window(name):
    assert _reader(name).read(_run(S0, S0)) in (None, 0.0)
