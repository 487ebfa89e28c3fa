"""Tests of the graph walk's work counts and their two readers, on made-up
``stats()`` snapshots and traces, and of a tiny CAGRA cell run whole.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import graph_work  # noqa: E402
from benchmark.lib.peaks import PEAKS  # noqa: E402

NEW = ["kernel.hop_roofline.bulk", "search.expansions_per_query.bulk"]
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SHAPES = {"graph_degree": 32, "width": 128, "elem_bytes": 4}


def _reader(name):
    return bench_run.load_module(
        os.path.join(bench_run.HERE, "metrics", name + ".py"), "m")


def _work(expansions, rows):
    return {"dispatches": 3, "rows": rows, "expansions": expansions,
            "iterations": 192}


def _run(stats0, stats1, op_s=None, rows=2000):
    trace = None if op_s is None else {"op_s": op_s}
    return SimpleNamespace(stats0=stats0, stats1=stats1, trace=trace,
                           rows_answered=rows, shapes=SHAPES,
                           peaks=PEAKS["TPU v5 lite"])


def test_hop_work_counts_every_gather():
    ops, nbytes = graph_work.hop_work(1000, degree=32, width=128,
                                      elem_bytes=4)
    assert ops == 1000 * 32 * 2 * 128
    assert nbytes == 1000 * 32 * (128 * 4 + 4)


def test_readers_difference_the_window(capsys):
    s0 = {"search_work": _work(1_000, 1_000)}
    s1 = {"search_work": _work(129_000, 3_000)}
    run = _run(s0, s1, op_s={"%cagra_fused_hop.3": 0.5,
                             "%matrix.select_k.9": 9.0})
    # 128,000 expansions over 2,000 rows, as many as the window answered
    assert _reader("search.expansions_per_query.bulk").read(run) == 64.0
    # memory bound: 128,000 x 32 x 516 B over 0.5 s of the hop at 819 GB/s
    share = _reader("kernel.hop_roofline.bulk").read(run)
    assert share == pytest.approx(100 * 128_000 * 32 * 516 / 819e9 / 0.5)
    assert "memory bound" in capsys.readouterr().err


def test_counter_from_before_the_window_may_be_absent():
    run = _run({}, {"search_work": _work(6_400, 100)}, rows=100)
    assert _reader("search.expansions_per_query.bulk").read(run) == 64.0


def test_a_dispatch_counted_past_the_answered_rows_does_not_inflate(capsys):
    """The counters move at copy-out: a dispatch sent before the window
    opened can land in it (four dispatches counted, three requests
    answered).  Expansions per row stay the walk's, and the hop's work is
    that of the rows answered."""
    s0 = {"search_work": _work(0, 0)}
    s1 = {"search_work": _work(512_000, 4_000)}
    run = _run(s0, s1, op_s={"%cagra_fused_hop.3": 21.0}, rows=3_000)
    assert _reader("search.expansions_per_query.bulk").read(run) == 128.0
    share = _reader("kernel.hop_roofline.bulk").read(run)
    assert share == pytest.approx(100 * 384_000 * 32 * 516 / 819e9 / 21.0)
    assert "expansions=384000.0" in capsys.readouterr().err


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_on_a_program_without_the_counter(name):
    """A program that lacks the counter (the parent's) gives no reading,
    and no error, even where its trace holds the hop under another name."""
    run = _run({"batches": 1}, {"batches": 2}, op_s={"%body.3": 1.0})
    assert _reader(name).read(run) is None


def test_roofline_is_silent_without_the_hop_op():
    run = _run({"search_work": _work(0, 0)},
               {"search_work": _work(6_400, 100)}, op_s={"%body.3": 1.0})
    assert _reader("kernel.hop_roofline.bulk").read(run) is None
    assert _reader("kernel.hop_roofline.bulk").read(
        _run({}, {"search_work": _work(6_400, 100)})) is None


def test_tiny_cagra_run_is_correct_and_counts_its_walk():
    """The cell cut to a CPU size runs whole through ``run_cell``: correct,
    and its traced run reads the expansion counter (the roofline stays
    silent: a CPU trace has no TPU op)."""
    cell, cfg, traffic, _ = bench_run.cell_inputs(BENCH, "sift1m-cagra.bulk",
                                                  True)
    cfg = json.loads(json.dumps(cfg))
    cfg.update(n=3000, d=16, queries=200)
    cfg["index"]["search"]["itopk_size"] = 32
    traffic = dict(traffic, rows_per_request=100, max_batch=128,
                   min_bucket=128)
    specs = bench_run.cell_inputs(BENCH, "sift1m-cagra.bulk", True)[3]
    result, checks = bench_run.run_cell(cfg, traffic, 1, 2**33 + 9, 1.0, True,
                                        specs, require_tpu=False)
    assert result["correct"], checks
    per_query = result["metrics"]["search.expansions_per_query.bulk"]["value"]
    assert 1 <= per_query <= 32   # at most max_iterations = itopk at width 1
    assert "kernel.hop_roofline.bulk" not in result["metrics"]
