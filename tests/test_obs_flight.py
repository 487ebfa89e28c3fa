"""Request-scoped tracing + flight recorder + exemplar-linked histograms.

Covers the obs v3 layer: monotonically increasing request ids threaded
through both MicroBatcher dispatch paths, the always-on bounded flight
recorder (ring, JSON + Chrome-trace dumps, debounced incident triggers),
per-bucket histogram exemplars and the OpenMetrics export mode, plus the
satellites — profiler coverage, slow-log request ids and negative
threshold rejection, and the env-configurable recent-span ring.

Shapes here are deliberately distinct (d=16) from tests/test_serve.py
(d=24), tests/test_obs.py (d=28), tests/test_obs_quality.py (d=32) and
tests/test_serve_pipeline.py (d=8): all suites share one process and one
jit cache, and shape collisions would let one suite's warmup silence
another's compile-count assertions.
"""

import copy
import json
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.neighbors import ivf_flat
from raft_tpu.obs import flight
from raft_tpu.obs import health as obs_health
from raft_tpu.obs import slowlog, spans
from raft_tpu.obs.flight import FlightRecorder, trace_events
from raft_tpu.obs.quality import QualityAuditor
from raft_tpu.obs.registry import MetricsRegistry
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.metrics import ServingMetrics

D = 16  # this suite's own query dimensionality (see module docstring)


def _toy_search_fn(k=3):
    def search_fn(q):
        d = jnp.sum(q * q, axis=1, keepdims=True) * jnp.ones((1, k))
        i = jnp.zeros((q.shape[0], k), dtype=jnp.int32)
        return d, i

    return search_fn


def _run_batcher(pipeline_depth, n_requests=6, **kw):
    mb = MicroBatcher(
        _toy_search_fn(), dim=D, max_batch=8, start=False,
        pipeline_depth=pipeline_depth, **kw
    )
    mb.warmup()
    futs = [
        mb.submit(np.full(D, i, dtype=np.float32)) for i in range(n_requests)
    ]
    mb.flush()
    for f in futs:
        f.result(timeout=30)
    return mb, futs


# ---------------------------------------------------------------------------
# request ids


class TestRequestIds:
    def test_futures_carry_monotonic_request_ids(self):
        mb, futs = _run_batcher(pipeline_depth=1)
        mb.stop()
        ids = [f.request_id for f in futs]
        assert all(isinstance(i, int) for i in ids)
        assert ids == sorted(ids) and len(set(ids)) == len(ids)

    def test_squeezed_and_batched_futures_share_id_semantics(self):
        mb = MicroBatcher(
            _toy_search_fn(), dim=D, max_batch=8, start=False,
            pipeline_depth=1,
        )
        f1 = mb.submit(np.zeros(D, dtype=np.float32))        # 1-D: squeezed
        f2 = mb.submit(np.zeros((2, D), dtype=np.float32))   # 2-D: as-is
        assert f1.request_id < f2.request_id
        mb.flush()
        f1.result(timeout=30), f2.result(timeout=30)
        mb.stop()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_flight_records_carry_submission_ordered_ids(self, depth):
        mb, futs = _run_batcher(pipeline_depth=depth, n_requests=10)
        mb.stop()
        recs = [r for r in flight.records() if "request_ids" in r]
        assert recs, "batcher recorded no flight batches"
        flat = [i for r in recs for i in r["request_ids"]]
        assert flat == sorted(flat)
        assert set(f.request_id for f in futs) <= set(flat)

    def test_per_request_timelines_reconstructed(self):
        mb, futs = _run_batcher(pipeline_depth=2, n_requests=4)
        mb.stop()
        rec = [r for r in flight.records() if "requests" in r][-1]
        for req in rec["requests"]:
            assert req["submit"] <= rec["t_pickup"] == req["batched"]
            assert req["resolve"] == rec["t_done"] >= req["submit"]
            assert req["latency_ms"] >= req["queue_ms"] >= 0.0
            for stage in ("pad", "dispatch", "device", "copy_out",
                          "inflight_wait"):
                assert stage in req["stages_ms"]


# ---------------------------------------------------------------------------
# flight recorder mechanics


class TestFlightRecorder:
    def test_ring_bounded_by_cap(self):
        rec = FlightRecorder(cap=4)
        for i in range(10):
            rec.record_event("tick", i=i)
        kept = rec.records()
        assert len(kept) == 4
        assert [r["i"] for r in kept] == [6, 7, 8, 9]
        assert rec.snapshot()["recorded_total"] == 10

    def test_env_cap_respected_on_reset(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_FLIGHT_CAP", "2")
        flight.reset()
        for i in range(5):
            flight.record_event("tick", i=i)
        assert len(flight.records()) == 2

    def test_dump_writes_parseable_json_and_chrome_trace(self, tmp_path):
        rec = FlightRecorder(cap=8)
        rec.record_event("swap", index="a")
        path = rec.dump(str(tmp_path), reason="unit")
        snap = json.load(open(path))
        assert snap["schema"] == "raft_tpu.flight"
        assert snap["reason"] == "unit"
        trace = json.load(open(path[:-len(".json")] + ".trace.json"))
        evs = trace["traceEvents"]
        assert any(e["ph"] == "M" for e in evs)        # track metadata
        assert any(e["ph"] == "i" and e["name"] == "swap" for e in evs)
        assert rec.last_dump()["path"] == path

    def test_auto_dump_is_debounced(self, tmp_path):
        rec = FlightRecorder(cap=8, debounce_s=3600.0)
        rec.record_event("x")
        first = rec.auto_dump("incident")
        second = rec.auto_dump("incident")
        assert first is not None and os.path.exists(first)
        assert second is None
        dumped = [p for p in os.listdir(os.path.dirname(first))
                  if p.endswith(".json") and "incident" in p]
        assert len(dumped) == 2  # snapshot + trace of the single dump
        assert len([p for p in dumped if not p.endswith(".trace.json")]) == 1

    def test_disabled_obs_makes_recorder_a_noop(self):
        rec = FlightRecorder(cap=8)
        obs.set_enabled(False)
        try:
            rec.record_event("x")
            rec.record_batch({"t_pickup": 0.0, "request_ids": []})
            assert rec.records() == []
            assert rec.auto_dump("incident") is None
        finally:
            obs.set_enabled(True)

    def test_trace_events_lays_stages_sequentially(self):
        recs = [{
            "seq": 1, "bucket": 8, "rows": 3, "compiles": 0,
            "request_ids": [1, 2], "t_pickup": 10.0, "t_done": 10.5,
            "stages_s": {"pad": 0.1, "dispatch": 0.2, "device": 0.1},
            "requests": [
                {"id": 1, "submit": 9.8, "resolve": 10.5},
                {"id": 2, "submit": 9.9, "resolve": 10.5},
            ],
            "error": None,
        }]
        evs = trace_events(recs)
        slices = [e for e in evs if e["ph"] == "X" and e["tid"] == 1]
        batch = [e for e in slices if e["name"].startswith("batch")][0]
        assert batch["ts"] == pytest.approx(10.0 * 1e6)
        assert batch["dur"] == pytest.approx(0.5 * 1e6)
        stages = {e["name"]: e for e in slices if e is not batch}
        assert stages["dispatch"]["ts"] == pytest.approx((10.0 + 0.1) * 1e6)
        reqs = [
            e for e in evs if e.get("tid") == 2 and e["ph"] == "X"
        ]
        assert {e["name"] for e in reqs} == {"req 1", "req 2"}


# ---------------------------------------------------------------------------
# incident triggers


class TestIncidentTriggers:
    @pytest.mark.parametrize("depth", [1, 2])
    def test_batch_exception_records_and_dumps(self, depth):
        def bad_fn(q):
            raise RuntimeError("boom")

        mb = MicroBatcher(
            bad_fn, dim=D, max_batch=8, start=False,
            pipeline_depth=depth,
        )
        fut = mb.submit(np.zeros(D, dtype=np.float32))
        mb.flush()
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=30)
        mb.stop(drain=False)
        rec = flight.records()[-1]
        assert rec["error"] and "boom" in rec["error"]
        assert fut.request_id in rec["request_ids"]
        dump = flight.last_dump()
        assert dump is not None and dump["reason"] == "batch_exception"
        json.load(open(dump["trace_path"]))

    def test_hot_recompile_triggers_auto_dump(self, monkeypatch):
        # fake the compile counter climbing during a warmed dispatch: the
        # batcher must treat that as a shape leak and capture the ring
        fake = {"n": 0}

        def fake_compile_count(thread=False):
            fake["n"] += 1
            return fake["n"]

        monkeypatch.setattr(
            "raft_tpu.serve.batcher.compile_count", fake_compile_count
        )
        mb = MicroBatcher(
            _toy_search_fn(), dim=D, max_batch=8, start=False,
            pipeline_depth=1,
        )
        mb._warm = True  # pretend warmup ran; next compile is "hot"
        fut = mb.submit(np.zeros(D, dtype=np.float32))
        mb.flush()
        fut.result(timeout=30)
        mb.stop()
        dump = flight.last_dump()
        assert dump is not None and dump["reason"] == "hot_recompile"

    def test_health_transition_edge_dumps_once(self):
        flight.record_event("context")
        bad = obs_health.IndexProbe(
            warm=True, recompiles=obs_health.COMPILE_STORM,
            queue_depth=0, max_batch=8,
        )
        reg = MetricsRegistry()
        r1 = obs_health.build_report({"i": bad}, registry=reg)
        assert r1["status"] == obs_health.UNHEALTHY
        assert r1["flight"] is not None
        assert r1["flight"]["reason"] == "health_unhealthy"
        first_path = r1["flight"]["path"]
        # still UNHEALTHY: no new transition, no new dump
        r2 = obs_health.build_report({"i": bad}, registry=reg)
        assert r2["flight"]["path"] == first_path
        # recover, then fail again after the debounce window: edge re-arms
        ok = obs_health.IndexProbe(
            warm=True, recompiles=0, queue_depth=0, max_batch=8
        )
        r3 = obs_health.build_report({"i": ok}, registry=reg)
        assert r3["status"] == obs_health.OK

    def test_quality_alarm_edge_dumps(self):
        class _Idx:
            metric = "sqeuclidean"
            generation = 0

            def live_vectors(self):
                vecs = np.eye(4, D, dtype=np.float32)
                return vecs, np.arange(4)

        auditor = QualityAuditor(
            k=2, sampling=1.0, threshold=0.9, registry=MetricsRegistry()
        )
        try:
            flight.record_event("context")
            q = np.eye(2, D, dtype=np.float32)
            wrong = np.full((2, 2), 3, dtype=np.int64)  # recall 0
            assert auditor.observe("qi", 1, _Idx(), q, wrong)
            assert auditor.flush(timeout=30.0)
            dump = flight.last_dump()
            assert dump is not None and dump["reason"] == "quality_alarm"
        finally:
            auditor.stop()


# ---------------------------------------------------------------------------
# exemplars + OpenMetrics export


class TestExemplars:
    def test_observe_accepts_exemplar_and_snapshots_it(self):
        reg = MetricsRegistry()
        h = reg.histogram("ex_h", help="x")
        h.observe(1e-4, exemplar="req-1", op="a")
        h.observe(1e9, exemplar="req-2", op="a")  # overflow bucket
        series = h.collect()
        (key,) = series.keys()
        ex = series[key]["exemplars"]
        assert set(v[1] for v in ex.values()) == {"req-1", "req-2"}
        snap = reg.snapshot()["histograms"]["ex_h"]["op=a"]
        les = {e["le"] for e in snap["exemplars"]}
        assert "+Inf" in les  # JSON-safe overflow edge
        json.dumps(snap)

    def test_openmetrics_carries_exemplars_and_eof(self):
        reg = MetricsRegistry()
        reg.histogram("om_h", help="x").observe(2e-4, exemplar="req-9")
        om = obs.to_openmetrics(reg)
        assert om.rstrip().endswith("# EOF")
        line = [l for l in om.splitlines() if "# {" in l]
        assert line and 'request_id="req-9"' in line[0]
        assert line[0].split(" # ")[0].startswith("om_h_bucket{le=")

    def test_classic_prometheus_output_is_exemplar_free(self):
        reg = MetricsRegistry()
        reg.histogram("pm_h", help="x").observe(2e-4, exemplar="req-9")
        pm = obs.to_prometheus(reg)
        assert "request_id" not in pm and "# EOF" not in pm
        assert "pm_h_bucket" in pm

    def test_serve_exemplars_resolve_to_ring_request_ids(self):
        mb, futs = _run_batcher(
            pipeline_depth=2, n_requests=8,
            metrics=ServingMetrics(name="flight_ex"),
        )
        mb.stop()
        ring_ids = {
            i for r in flight.records() if "request_ids" in r
            for i in r["request_ids"]
        }
        h = obs.default_registry().histogram("raft_tpu_serve_request_seconds")
        found = []
        for key, data in h.collect().items():
            if ("index", "flight_ex") not in key:
                continue
            for _lo, (value, ex) in data["exemplars"].items():
                assert ex.startswith("req-")
                found.append(int(ex[len("req-"):]))
        assert found, "no exemplars recorded for served latencies"
        assert set(found) <= ring_ids
        # and the scrape document agrees with the ring
        om = obs.to_openmetrics()
        assert any(f'request_id="req-{i}"' in om for i in found)


# ---------------------------------------------------------------------------
# acceptance: corrupted index → UNHEALTHY → exactly one ordered dump


def _clustered(rng, n, n_q):
    centers = (rng.standard_normal((24, D)) * 6.0).astype(np.float32)
    x = (
        centers[rng.integers(0, 24, n)]
        + rng.standard_normal((n, D)).astype(np.float32) * 0.25
    )
    q = (
        centers[rng.integers(0, 24, n_q)]
        + rng.standard_normal((n_q, D)).astype(np.float32) * 0.25
    )
    return x.astype(np.float32), q.astype(np.float32)


def _corrupt(index, rng):
    bad = copy.copy(index)
    perm = rng.permutation(np.asarray(index.centers).shape[0])
    bad.centers = jnp.asarray(np.asarray(index.centers)[perm])
    return bad


def test_unhealthy_transition_produces_one_ordered_flight_dump(tmp_path):
    rng = np.random.default_rng(23)
    x, q = _clustered(rng, 600, 16)
    good = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
    bad = _corrupt(good, rng)
    sp = ivf_flat.SearchParams(n_probes=1)  # corruption bites hardest

    # threshold 1.0: any corrupted recall EWMA below 0.5 reads UNHEALTHY
    auditor = QualityAuditor(
        k=10, sampling=1.0, threshold=1.0, ewma_alpha=0.5,
        registry=MetricsRegistry(),
    )
    svc = serve.SearchService(
        k=10, max_batch=8, max_delay_ms=1.0, auditor=auditor,
        pipeline_depth=2,
    )
    try:
        svc.add_index(
            "fr", serve.MutableIndex(bad, search_params=sp), warmup=True
        )
        for i in range(48):
            svc.search("fr", q[i % len(q)])
        assert auditor.flush(timeout=30.0)
        ewma = auditor.recall_ewma("fr")
        assert ewma is not None and ewma < 0.5, (
            f"corruption did not bite (ewma={ewma}); acceptance "
            "scenario needs recall below half the threshold"
        )

        report = svc.healthz()
        assert report["status"] == obs_health.UNHEALTHY
        assert report["flight"] is not None
        dump_dir = os.path.dirname(report["flight"]["path"])
        # polling healthz again while still UNHEALTHY adds no dump
        svc.healthz()
        snapshots = [
            p for p in os.listdir(dump_dir)
            if p.endswith(".json") and not p.endswith(".trace.json")
        ]
        assert len(snapshots) == 1, snapshots

        trace = json.load(open(report["flight"]["trace_path"]))
        assert trace["traceEvents"], "empty Chrome trace"
        snap = json.load(open(report["flight"]["path"]))
        flat = [
            i for r in snap["records"] if "request_ids" in r
            for i in r["request_ids"]
        ]
        assert flat and flat == sorted(flat), (
            "request timelines not submission-ordered at depth 2"
        )
    finally:
        svc.stop()
        auditor.stop()


# ---------------------------------------------------------------------------
# satellites: slow log, span ring, profiler


class TestSlowLog:
    def test_configure_rejects_negative_threshold(self):
        with pytest.raises(ValueError, match=">= 0"):
            slowlog.configure(-5)

    def test_configure_zero_and_none_still_work(self):
        slowlog.configure(0)
        assert slowlog.threshold_ms() == 0.0
        slowlog.configure(None)
        assert slowlog.threshold_ms() is None

    def test_slow_entries_carry_member_request_ids(self):
        slowlog.configure(0)  # everything is slow
        try:
            slowlog.clear()
            mb, futs = _run_batcher(pipeline_depth=2, n_requests=4)
            mb.stop()
            entries = [
                e for e in slowlog.entries() if "request_ids" in e
            ]
            assert entries, "slow batch entry missing request ids"
            logged = {i for e in entries for i in e["request_ids"]}
            assert {f.request_id for f in futs} <= logged
        finally:
            slowlog.configure(None)
            slowlog.clear()


class TestSpanRing:
    def test_env_capacity_applied(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_SPAN_RING", "3")
        assert spans.set_ring_capacity() == 3
        for i in range(6):
            with spans.span(f"ring_test_{i}"):
                pass
        recent = spans.recent_spans(100)
        assert len([s for s in recent if s["name"].startswith("ring_test")]) <= 3

    def test_explicit_capacity_keeps_newest(self):
        spans.clear_recent()
        spans.set_ring_capacity(16)
        for i in range(4):
            with spans.span(f"keep_{i}"):
                pass
        spans.set_ring_capacity(2)
        names = [s["name"] for s in spans.recent_spans(10)]
        assert names == ["keep_2", "keep_3"]

    def test_invalid_env_falls_back_to_default(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_SPAN_RING", "banana")
        assert spans.set_ring_capacity() == 512


class TestProfiler:
    def test_disable_env_is_a_noop(self, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_DISABLE_PROFILER", "1")
        import jax

        def _explode(*a, **k):
            raise AssertionError("jax.profiler.trace must not be entered")

        monkeypatch.setattr(jax.profiler, "trace", _explode)
        before = obs.default_registry().counter(
            "raft_tpu_profile_captures_total"
        ).value()
        ran = []
        with obs.profile("/nonexistent/should/not/matter"):
            ran.append(True)
        assert ran == [True]
        after = obs.default_registry().counter(
            "raft_tpu_profile_captures_total"
        ).value()
        assert after == before  # no capture counted on the no-op path

    def test_capture_counts_and_brackets_a_span(self, monkeypatch, tmp_path):
        monkeypatch.delenv("RAFT_TPU_DISABLE_PROFILER", raising=False)
        import contextlib
        import jax

        calls = []

        @contextlib.contextmanager
        def fake_trace(log_dir):
            calls.append(log_dir)
            yield

        monkeypatch.setattr(jax.profiler, "trace", fake_trace)
        reg = obs.default_registry()
        before = reg.counter("raft_tpu_profile_captures_total").value()
        spans.clear_recent()
        with obs.profile(str(tmp_path)):
            pass
        assert calls == [str(tmp_path)]
        assert reg.counter(
            "raft_tpu_profile_captures_total"
        ).value() == before + 1
        names = [s["name"] for s in spans.recent_spans(10)]
        assert "obs.profile" in names
