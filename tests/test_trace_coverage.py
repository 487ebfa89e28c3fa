"""Observability-coverage contract, enforced by the TRACED checker.

This file used to introspect the imported packages at runtime
(``__traced__`` markers stamped by ``core.trace.traced``, plus
``inspect.getsource`` greps over the batcher).  That whole contract now
lives in :mod:`raft_tpu.analysis.checkers.traced` as a static check —
exported ``neighbors``/``cluster`` entry points must carry ``@traced``,
the serve online surface must carry exact unique span labels, and the
pipelined dispatch path must keep its detached-span and request-id
plumbing.  This test is the thin wrapper: run the checker over the real
package, assert discovery saw the API surface (not vacuous), and assert
zero findings.  The per-rule behaviour of the checker itself (that it
*fires* on violations and honors suppressions) is covered by
``tests/test_static_analysis.py`` against the seeded fixture package.
"""

import os

import pytest

import raft_tpu
from raft_tpu.analysis import run_analysis
from raft_tpu.analysis.checkers import traced as traced_checker
from raft_tpu.analysis.model import Project


@pytest.fixture(scope="module")
def project():
    return Project(os.path.dirname(raft_tpu.__file__))


@pytest.fixture(scope="module")
def result():
    return run_analysis(rules=["TRACED"])


def test_entry_point_discovery_is_not_vacuous(project):
    names = sorted(traced_checker._api_entry_points(project))
    # the checker must actually see the API surface — a refactor that
    # breaks discovery would otherwise green-light everything
    assert len(names) >= 25, names
    for expected in (
        "raft_tpu.neighbors.brute_force.search",
        "raft_tpu.neighbors.ivf_pq.build",
        "raft_tpu.neighbors.hnsw.search",
        "raft_tpu.cluster.kmeans.fit",
    ):
        assert expected in names, f"{expected} not discovered"


def test_serve_surface_discovery_is_not_vacuous(result):
    # all online entry points (service/mutation/ragged/compactor plus
    # the SLO evaluator, incident ingest, the overload trio, the
    # sharded rebuild, the two module-level build
    # entry points, the page-store pager trio, the deep-explain entry
    # point, the query-archive record/dump pair, and the gateway's
    # request dispatch) checked, against exactly one MicroBatcher
    assert result.stats["traced_serve_entries_checked"] == 27, result.stats
    assert result.stats["traced_batcher_classes"] == 1, result.stats
    assert result.stats["traced_labels"] >= 23, result.stats


def test_trace_coverage_is_clean(result):
    rendered = "\n".join(f.render() for f in result.sorted_findings())
    assert not result.findings, (
        "TRACED contract violations (untraced entry point, wrong/duplicate "
        f"span label, or dropped batcher plumbing):\n{rendered}"
    )
