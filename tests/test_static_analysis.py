"""Tier-1 gate for ``raft_tpu.analysis``.

Two halves:

* the real package must produce **zero unsuppressed findings** — this is
  the enforcement end of the static invariants (recompile hazards, lock
  discipline, host-sync leaks, env/obs registry drift), so a regression
  in any guarded property fails the suite with the analyzer's own
  rendered findings as the message;
* the seeded fixture package (``tests/analysis_fixtures/badpkg``) must
  make **every rule fire** and every ``# raft-tpu: ignore[RULE]``
  comment must be honored — the analyzer itself cannot silently go
  vacuous.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from raft_tpu.analysis import RULES, run_analysis

REPO_ROOT = Path(__file__).resolve().parents[1]
FIXTURE_ROOT = str(
    Path(__file__).resolve().parent / "analysis_fixtures" / "badpkg"
)


@pytest.fixture(scope="module")
def clean_result():
    t0 = time.perf_counter()
    res = run_analysis()
    res.stats["_elapsed_s"] = time.perf_counter() - t0
    return res


@pytest.fixture(scope="module")
def fixture_result():
    return run_analysis(root=FIXTURE_ROOT)


def _by_rule(result, rule):
    return (
        [f for f in result.findings if f.rule == rule],
        [f for f in result.suppressed if f.rule == rule],
    )


# -- the real package is clean ----------------------------------------------

def test_package_has_no_unsuppressed_findings(clean_result):
    rendered = "\n".join(f.render() for f in clean_result.sorted_findings())
    assert not clean_result.findings, (
        "static analysis found unsuppressed invariant violations (fix the "
        "code, or add an inline `# raft-tpu: ignore[RULE]` with a reason "
        f"for an intended exception):\n{rendered}"
    )


def test_analysis_runs_fast(clean_result):
    # CI-budget guard: the whole-package run must stay interactive
    assert clean_result.stats["_elapsed_s"] < 10.0, clean_result.stats


def test_discovery_is_not_vacuous(clean_result):
    """A refactor that breaks model building would green-light everything;
    pin the discovery floors so silence stays meaningful."""
    stats = clean_result.stats
    assert stats["modules"] >= 100, stats
    assert stats["functions"] >= 500, stats
    assert stats["recompile_jit_entries"] >= 20, stats
    assert stats["hostsync_roots"] == 7, stats
    assert stats["hostsync_reachable"] >= 30, stats
    assert stats["lockorder_locks"] >= 10, stats
    assert stats["envreg_known_vars"] >= 30, stats
    assert stats["traced_entry_points"] >= 25, stats
    assert stats["traced_serve_entries_checked"] == 27, stats
    assert stats["traced_batcher_classes"] == 1, stats
    assert stats["recompile_descriptor_entries"] == 4, stats
    # kernel dispatch attribution: every routed leg stamps from the
    # closed vocabulary, every pallas_call carries a cost estimate
    assert stats["traced_kernel_path_stamps"] >= 13, stats
    assert stats["traced_pallas_cost_estimates"] == 8, stats


# -- every rule fires on the seeded fixture ---------------------------------

def test_every_rule_fires_on_fixture(fixture_result):
    fired = {f.rule for f in fixture_result.findings}
    assert fired == set(RULES()), (
        f"rules that failed to fire on the seeded fixture: "
        f"{set(RULES()) - fired}"
    )


def test_recompile_rule(fixture_result):
    findings, suppressed = _by_rule(fixture_result, "RECOMPILE")
    symbols = {f.symbol for f in findings}
    assert "badpkg.jits.gate" in symbols, findings
    assert "badpkg.jits.inner" in symbols, findings  # mutable closure
    # descriptor-path discipline: no @jax.jit on the def, but the ragged
    # row_k column is still held to jit rules by qualname suffix
    assert "badpkg.ops.matrix.mask_row_k" in symbols, findings
    # static_argnames negative control must stay quiet
    assert not any("gate_static" in f.symbol for f in findings), findings
    # effort knobs are operands by contract — marking one static is a
    # finding even without value-dependent control flow
    assert any(
        f.symbol == "badpkg.jits.probe_static"
        and "effort knob" in f.message
        for f in findings
    ), findings
    # `row_k is None` structure test is a laundered negative control
    assert not any(
        f.symbol == "badpkg.ops.matrix.select_k" for f in findings
    ), findings
    assert any(s.symbol == "badpkg.jits.concretize" for s in suppressed), (
        suppressed
    )


def test_effort_knob_vocab_in_sync():
    """The checker is stdlib-only so it mirrors the knob vocabulary;
    drifting from the runtime source of truth would let a new backend's
    knob ride static unflagged."""
    from raft_tpu.analysis.checkers.recompile import EFFORT_KNOB_NAMES
    from raft_tpu.neighbors.effort import EFFORT_KNOBS

    assert EFFORT_KNOB_NAMES == EFFORT_KNOBS


def test_hostsync_rule(fixture_result):
    findings, suppressed = _by_rule(fixture_result, "HOSTSYNC")
    assert any(
        ".item()" in f.message and f.symbol.endswith("._dispatch_locked")
        for f in findings
    ), findings
    assert any(".tolist()" in s.message for s in suppressed), suppressed


def test_lockorder_rule(fixture_result):
    findings, suppressed = _by_rule(fixture_result, "LOCKORDER")
    assert any("lock-acquisition cycle" in f.message for f in findings), (
        findings
    )
    assert any(
        "self._pending" in f.message and f.symbol.endswith(".bump")
        for f in findings
    ), findings
    assert any(s.symbol.endswith(".bump_quietly") for s in suppressed), (
        suppressed
    )


def test_envreg_rule(fixture_result):
    findings, suppressed = _by_rule(fixture_result, "ENVREG")
    assert any(f.symbol == "RAFT_TPU_FIXTURE_CAP" for f in findings), (
        findings
    )
    assert any(s.symbol == "RAFT_TPU_FIXTURE_DIR" for s in suppressed), (
        suppressed
    )


def test_traced_rule(fixture_result):
    findings, suppressed = _by_rule(fixture_result, "TRACED")
    symbols = {f.symbol for f in findings}
    # untraced exported entry point
    assert "badpkg.neighbors.flat.search" in symbols, findings
    # serve label contract: missing decorator and wrong label
    assert "badpkg.serve.service.SearchService.search" in symbols, findings
    assert any("reused" in f.message for f in findings), findings
    # batcher plumbing: detached span + request ids + __slots__
    assert any("open_span" in f.message for f in findings), findings
    assert any("req_id slot" in f.message for f in findings), findings
    assert any(s.symbol == "badpkg.neighbors.flat.build" for s in suppressed)
    assert any(s.symbol.endswith("._complete") for s in suppressed)


def test_suppressions_do_not_leak_into_findings(fixture_result):
    suppressed_ids = {s.id for s in fixture_result.suppressed}
    live_ids = {f.id for f in fixture_result.findings}
    assert not (suppressed_ids & live_ids)
    assert len(fixture_result.suppressed) >= 5  # one control per rule


# -- CLI contract ------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "raft_tpu.analysis", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
    )


def test_cli_exit_codes(tmp_path):
    bad = _cli("--root", FIXTURE_ROOT)
    assert bad.returncode == 1, bad.stdout + bad.stderr

    usage = _cli("--rules", "NOSUCHRULE")
    assert usage.returncode == 2, usage.stdout + usage.stderr

    listing = _cli("--list-rules")
    assert listing.returncode == 0
    assert set(listing.stdout.split()) == set(RULES())


def test_cli_clean_on_repo():
    ok = _cli()
    assert ok.returncode == 0, ok.stdout + ok.stderr


def test_cli_baseline_roundtrip(tmp_path):
    baseline = tmp_path / "baseline.json"
    wrote = _cli("--root", FIXTURE_ROOT, "--write-baseline", str(baseline))
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr

    gated = _cli(
        "--root", FIXTURE_ROOT, "--baseline", str(baseline), "--json"
    )
    assert gated.returncode == 0, gated.stdout + gated.stderr
    payload = json.loads(gated.stdout)
    assert payload["findings"] == []
    assert payload["baselined"], payload
