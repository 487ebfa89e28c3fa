"""Per-query EXPLAIN plans + the tail-sampled query archive
(raft_tpu.obs.explain): a deep explain must run one *real* request
through the normal batched path and come back with every plan section
filled for all four backends (paged and sharded arms included), bit-match
the plain search path, and add zero post-warmup recompiles even with
always-on tail sampling; the tail sampler must be deterministic on a
synthetic clock; shed/deadline-expired requests must still land in the
archive; an incident trigger must dump the archive into exactly one
correlated incident; and remove_index must retire the explain metric
series (the PR 16 stale-series pattern)."""

import json
import os

import numpy as np
import pytest

import jax

from raft_tpu import obs, serve
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.obs import events, explain, incidents, slowlog
from raft_tpu.serve.effort import EffortArbiter
from raft_tpu.serve.metrics import compile_count
from raft_tpu.store import MemoryBudget, paginate_index

KINDS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")

# D=20 keeps this suite's executables out of every other suite's jit
# cache (16/24/28/32/8 are taken) so compile-count deltas stay honest
N, D, Q = 400, 20, 16
K_MAX = 8

SECTIONS = ("request", "outcome", "admission", "effort", "bucket",
            "kernel_path", "probe", "page", "shards", "stages",
            "audit", "results")


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(17)
    x = rng.random((N, D), dtype=np.float32)
    q = rng.random((Q, D), dtype=np.float32)
    return x, q


def _build(kind: str, x: np.ndarray) -> serve.MutableIndex:
    if kind == "brute_force":
        return serve.MutableIndex(brute_force.build(x))
    if kind == "ivf_flat":
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
        return serve.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=16)
        )
    if kind == "ivf_pq":
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=D, pq_bits=8), x
        )
        return serve.MutableIndex(
            idx, search_params=ivf_pq.SearchParams(n_probes=16)
        )
    idx = cagra.build(cagra.IndexParams(graph_degree=32), x)
    return serve.MutableIndex(
        idx, search_params=cagra.SearchParams(itopk_size=128)
    )


def _svc(index) -> serve.SearchService:
    # started worker: explain() blocks on the future, so the max_delay
    # cut must happen without an explicit flush
    svc = serve.SearchService(
        k=5, max_batch=16, start=True,
        ragged=serve.RaggedSpec(k_max=K_MAX),
    )
    svc.add_index("t", index)
    return svc


# ---------------------------------------------------------------------------
# the deep explain: every section, every backend, parity with search


@pytest.mark.parametrize("kind", KINDS)
def test_explain_sections_and_parity(corpus, kind):
    x, q = corpus
    svc = _svc(_build(kind, x))
    try:
        svc.warmup("t")
        d_ref, i_ref = svc.search("t", q[0], timeout=60)
        plan = svc.explain("t", q[0], timeout=60)
        s = plan.sections
        for key in SECTIONS:
            assert key in s, f"{kind}: missing section {key!r}"
        assert s["outcome"]["outcome"] == "ok"
        assert s["outcome"]["sampled_reason"] == "deep"
        assert s["admission"]["admitted"] is True
        assert s["bucket"]["index"] == "t"
        assert s["bucket"]["version"] >= 1
        assert s["kernel_path"] not in (None, "unknown", "none")
        assert s["stages"]["batch_stages_s"]
        assert s["stages"]["request_stages_ms"]
        assert s["request"]["k"] == 5
        # the explained request is a real one: answered by the same
        # executables, so ids/distances match the plain path exactly
        np.testing.assert_array_equal(
            np.asarray(s["results"]["ids"]), np.asarray(i_ref)
        )
        np.testing.assert_allclose(
            np.asarray(s["results"]["distances"]),
            np.asarray(d_ref).reshape(-1), atol=1e-5,
        )
        if kind in ("ivf_flat", "ivf_pq"):
            probe = s["probe"]
            assert probe["n_lists"] == 16
            assert probe["n_probes"] == 16
            assert len(probe["probed_lists"]) == 16
            assert probe["candidates"] > 0
        # both renderings round-trip
        assert json.loads(plan.to_json())["schema"] == "raft_tpu.explain"
        text = plan.to_text()
        assert text.startswith("EXPLAIN request")
        assert "kernel_path" in text
    finally:
        svc.stop()


def test_explain_parity_under_ragged_traffic(corpus):
    """The explained request coalesces with a live mixed-(k, rows)
    stream and still answers identically to a quiet plain search."""
    x, q = corpus
    svc = _svc(_build("ivf_flat", x))
    try:
        svc.warmup("t")
        d_ref, i_ref = svc.search("t", q[1], k=7, timeout=60)
        futs = [
            svc.submit("t", q[2 + (i % 6)], k=(i % K_MAX) + 1)
            for i in range(10)
        ]
        plan = svc.explain("t", q[1], k=7, timeout=60)
        for f in futs:
            f.result(timeout=60)
        s = plan.sections
        assert s["outcome"]["outcome"] == "ok"
        assert s["request"]["k"] == 7
        np.testing.assert_array_equal(
            np.asarray(s["results"]["ids"]), np.asarray(i_ref)
        )
        np.testing.assert_allclose(
            np.asarray(s["results"]["distances"]),
            np.asarray(d_ref).reshape(-1), atol=1e-5,
        )
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# collection discipline: sampling on adds zero post-warmup recompiles


@pytest.mark.parametrize("kind", KINDS)
def test_sampling_on_zero_post_warmup_recompiles(corpus, kind, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_EXPLAIN", "1")
    x, q = corpus
    svc = _svc(_build(kind, x))
    try:
        svc.warmup("t")
        rng = np.random.default_rng(3)
        c0 = compile_count()
        futs = []
        for _ in range(14):
            m = int(rng.integers(1, 9))
            futs.append(
                svc.submit("t", q[:m], k=int(rng.integers(1, K_MAX + 1)))
            )
        for f in futs:
            f.result(timeout=60)
        assert compile_count() - c0 == 0, (
            f"{kind}: explain sampling recompiled post-warmup"
        )
        # and the tail sampler actually archived plans while sampling was on
        archived = explain.plans(index="t")
        assert archived, "tail sampler archived nothing"
        reasons = {e["reason"] for e in archived}
        assert reasons <= {"slow_window", "baseline", "recall_alarm"}
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# paged arm: the page section carries the pager's hit/miss attribution


def test_explain_paged_page_section(corpus):
    x, q = corpus
    # low n_probes: one search touches ~4/16 of the page set, so a
    # partial budget serves it without tripping BudgetExceeded while the
    # probed-list churn across queries still produces misses
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
    mi = serve.MutableIndex(
        idx, search_params=ivf_flat.SearchParams(n_probes=4)
    )
    ld = np.asarray(mi.index.list_data)
    pr = 8
    ppl = -(-ld.shape[1] // pr)
    n_pages = ld.shape[0] * ppl
    page_bytes = pr * int(np.prod(ld.shape[2:], dtype=np.int64)) * ld.itemsize
    # partial budget (~60% of the page set) so the slow prefetch path —
    # the one that bumps the hit/miss counters — actually runs
    slots = max(1, int(0.6 * n_pages))
    tiered = paginate_index(
        mi.index, page_rows=pr,
        budget=MemoryBudget(slots * page_bytes + 4 * n_pages),
        name="explain:paged",
    )
    assert tiered.slots < tiered.n_pages
    svc = _svc(mi)
    try:
        svc.warmup("t")
        plan = svc.explain("t", q[0], timeout=60)
        page = plan.sections["page"]
        assert page["pager"] == "explain:paged"
        assert page["pinned"] is False
        assert page["hits"] + page["misses"] > 0
        assert page["pages"] > 0
        assert page["resident"] <= tiered.slots
        # the slow-log summary derives its hit ratio from these stamps
        line = explain.summary_line({"page": page, "kernel_path": "xla"})
        assert line["page_hit_ratio"] is not None
        assert 0.0 <= line["page_hit_ratio"] <= 1.0
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# sharded arm: per-shard contribution counts


def test_explain_sharded_contributions(corpus):
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    x, q = corpus
    sharded = serve.ShardedIndex.from_index(brute_force.build(x))
    svc = serve.SearchService(
        k=5, max_batch=16, start=True,
        ragged=serve.RaggedSpec(k_max=K_MAX),
    )
    svc.add_index("t", sharded)
    try:
        svc.warmup("t")
        plan = svc.explain("t", q[0], timeout=60)
        s = plan.sections
        assert s["kernel_path"] == "sharded"
        shards = s["shards"]
        assert shards["available"] is True
        assert shards["n_shards"] == sharded.n_shards
        assert len(shards["per_shard"]) == sharded.n_shards
        assert sum(shards["per_shard"]) == 5  # every returned id attributed
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# tail sampler: deterministic on a synthetic clock


def test_tail_sampler_deterministic_on_synthetic_clock():
    def run():
        s = explain.TailSampler(
            per_window=2, window_s=1.0, baseline_stride=5,
            alarm_window_s=2.0,
        )
        out = []
        for lat, now in [(0.010, 10.05), (0.020, 10.10), (0.005, 10.20),
                         (0.030, 10.30), (0.001, 10.40)]:
            out.append(tuple(s.reasons(latency_s=lat, now=now)))
        s.note_alarm(11.0)
        for lat, now in [(0.500, 11.10), (0.004, 11.20), (0.006, 11.30),
                         (0.002, 11.35), (0.007, 11.40)]:
            out.append(tuple(s.reasons(latency_s=lat, now=now)))
        return out

    a, b = run(), run()
    assert a == b, "sampler is not deterministic on identical input"
    # window 10: greedy top-2 by latency; 5th observation is the baseline
    assert a[0] == ("slow_window",)
    assert a[1] == ("slow_window",)
    assert a[2] == ()                       # 5ms < min(kept)=10ms
    assert a[3] == ("slow_window",)         # 30ms evicts 10ms
    assert a[4] == ("baseline",)            # stride 5, not slow
    # window 11: fresh top-2 slate; every completion within 2s of the
    # alarm edge is alarm-correlated; 10th observation is baseline again
    assert a[5] == ("recall_alarm", "slow_window")
    assert a[6] == ("recall_alarm", "slow_window")
    assert a[7] == ("recall_alarm", "slow_window")  # 6ms > min(kept)=4ms
    assert a[8] == ("recall_alarm",)                # 2ms not slow
    assert a[9] == ("recall_alarm", "slow_window", "baseline")


# ---------------------------------------------------------------------------
# shed / deadline-expired requests still produce plans


def test_expired_request_archived_and_explainable(corpus, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_EXPLAIN", "1")
    x, q = corpus
    svc = _svc(_build("brute_force", x))
    try:
        svc.warmup("t")
        # a deadline already in the past expires at the batch cut; the
        # explain must still return a plan saying why it never dispatched
        plan = svc.explain("t", q[0], deadline_s=1e-6, timeout=60)
        s = plan.sections
        assert s["outcome"]["outcome"] == "deadline_expired"
        assert s["admission"]["admitted"] is False
        assert s["kernel_path"] == "none"
        assert "DeadlineExceeded" in s["outcome"]["error"]
        # and the admission hook archived it as interesting tail
        reasons = [e["reason"] for e in explain.plans(index="t")]
        assert "deadline_expired" in reasons
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# incident correlation: one trigger, one incident, the dump linked in


def test_archive_dump_lands_in_exactly_one_incident(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_EXPLAIN", "1")
    bus = events.default_bus()  # installs flight + incidents + explain
    record = {
        "seq": 0, "index": "ti", "bucket": 4, "rows": 1, "compiles": 0,
        "t_done": 1.0, "kernel_path": "xla", "error": None,
        "requests": [{"id": 7, "rows": 1, "latency_ms": 3.0}],
    }
    member = record["requests"][0]
    archive = explain.default_archive()
    archive.record(
        explain.build_plan(record, member, "slow_window"),
        reason="slow_window",
    )

    bus.publish("slo_burn", "slo_burn_budget", index="ti")

    mgr = incidents.default_manager()
    incs = mgr.open_incidents() + mgr.closed_incidents()
    assert len(incs) == 1, "trigger must open exactly one incident"
    inc = incs[0]
    # the archive dump is linked as an artifact *and* a timeline event
    assert inc.archive is not None
    assert os.path.exists(inc.archive["path"])
    kinds = [e["kind"] for e in inc.timeline]
    assert kinds.count("explain_dump") == 1
    with open(inc.archive["path"]) as f:
        payload = json.load(f)
    assert payload["schema"] == "raft_tpu.explain_archive"
    assert payload["reason"] == "slo_burn_budget"
    assert [e["request_id"] for e in payload["entries"]] == [7]
    # the correlation guard: a second trigger inside the window must not
    # write a second dump
    before = archive.last_dump()["path"]
    bus.publish("hot_recompile", "hot_recompile_burst", index="ti")
    assert archive.last_dump()["path"] == before


def test_explain_dump_is_context_not_trigger():
    """Taxonomy pin: explain_dump annotates an open incident's timeline;
    it must never open one itself (that would recurse — dumps triggering
    dumps)."""
    assert "explain_dump" in events.KINDS
    assert "explain_dump" not in events.TRIGGER_KINDS
    with pytest.raises(ValueError):
        events.publish("explain_dumps")  # typos fail loudly


# ---------------------------------------------------------------------------
# slow-log enrichment


def test_slowlog_entries_carry_explain_summary(corpus, monkeypatch):
    x, q = corpus
    monkeypatch.setattr(slowlog, "_threshold_s", 0.0)  # log every query
    svc = _svc(_build("brute_force", x))
    try:
        svc.warmup("t")
        svc.search("t", q[0], timeout=60)
        entry = slowlog.entries()[-1]
        # purely additive keys — present even with sampling off
        for key in ("effort_level", "effort_source", "kernel_path",
                    "page_hit_ratio"):
            assert key in entry, f"slowlog entry missing {key!r}"
        assert entry["kernel_path"] not in (None, "")
        # existing fields stay byte-compatible
        for key in ("unix_time", "latency_ms", "bucket"):
            assert key in entry
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# effort-source attribution (read by the plan's effort section)


def test_effort_snapshot_source_attribution():
    arb = EffortArbiter(None, max_level=3, name="src")
    assert arb.snapshot()["source"] == "full_effort"
    arb.set_autotune_level(2)
    snap = arb.snapshot()
    assert snap["source"] == "autotune"
    assert snap["effective_level"] == 2
    with arb.pinned(1):
        assert arb.snapshot()["source"] == "pinned"
        assert arb.snapshot()["effective_level"] == 1
    assert arb.snapshot()["source"] == "autotune"

    class _Deg:
        level = 3

    arb2 = EffortArbiter(_Deg(), max_level=3, name="src2")
    snap2 = arb2.snapshot()
    assert snap2["source"] == "overload_clamp"
    assert snap2["effective_level"] == 3


# ---------------------------------------------------------------------------
# stale-series retirement (PR 16 pattern, via remove_index)


def test_remove_index_retires_explain_series(corpus, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_EXPLAIN", "1")
    x, q = corpus
    reg = obs.default_registry()
    gauge = reg.gauge("raft_tpu_explain_archive_depth")
    counter = reg.counter("raft_tpu_explain_sampled_total")

    svc = _svc(_build("brute_force", x))
    try:
        svc.warmup("t")
        svc.search("t", q[0], timeout=60)
        assert explain.plans(index="t"), "sampler archived nothing"
        assert any(
            dict(key).get("index") == "t" for key in gauge.collect()
        ), "depth gauge never materialized"
        svc.remove_index("t")
        # retirement assertion: no explain series may survive the index
        for metric in (gauge, counter):
            stale = [
                key for key in metric.collect()
                if dict(key).get("index") == "t"
            ]
            assert not stale, f"stale explain series: {stale}"
        assert explain.plans(index="t") == []
    finally:
        svc.stop()
