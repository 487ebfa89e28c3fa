"""raft_tpu.obs.autotune + raft_tpu.serve.effort: the closed SLO loop.

Typed effort specs must actuate bidirectionally through the single-writer
EffortArbiter (the overload ladder clamps, it never writes), the
Autotuner must walk the warmed ladder under (recall >= floor, p99 budget
healthy) with hysteresis, every step must publish a taxonomy-pinned
``autotune_step`` event and refresh retirable gauges, the frontier sweep
must emit a loadable schema-versioned model — and none of it may cost a
single post-warmup recompile, on any of the four backends.
"""

import json
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.neighbors import effort as neighbors_effort
from raft_tpu.obs import events
from raft_tpu.obs.autotune import (
    Autotuner,
    FrontierModel,
    FrontierPoint,
    pareto,
)
from raft_tpu.obs.registry import MetricsRegistry
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.effort import EffortArbiter
from raft_tpu.serve.metrics import ServingMetrics, compile_count
from raft_tpu.serve.overload import derive_degraded_params


# ---------------------------------------------------------------------------
# typed effort specs: one uniform bidirectional actuation surface


class TestEffortSpecs:
    def test_spec_for_params_captures_knobs(self):
        spec = neighbors_effort.spec_for_params(
            ivf_flat.SearchParams(n_probes=24))
        assert spec.backend == "ivf_flat" and spec.n_probes == 24
        spec = neighbors_effort.spec_for_params(
            ivf_pq.SearchParams(n_probes=12, lut_dtype="float32"))
        assert spec.backend == "ivf_pq" and spec.n_probes == 12
        spec = neighbors_effort.spec_for_params(
            cagra.SearchParams(itopk_size=128, search_width=2))
        assert spec.backend == "cagra"
        assert spec.itopk_size == 128 and spec.search_width == 2

    @pytest.mark.parametrize("params", [
        ivf_flat.SearchParams(n_probes=32),
        ivf_pq.SearchParams(n_probes=32),
        cagra.SearchParams(itopk_size=256),
    ])
    def test_degraded_ladder_is_the_overload_derivation(self, params):
        # one semantics for both actuators: the overload ladder's derived
        # params ARE spec.degraded(level).apply — no second rule set
        for level in (1, 2, 3):
            spec = neighbors_effort.spec_for_params(params)
            assert derive_degraded_params(params, level) == \
                spec.degraded(level).apply(params)

    def test_effort_strictly_decreases_down_the_ladder(self):
        spec = neighbors_effort.spec_for_params(
            ivf_flat.SearchParams(n_probes=32))
        probes = [spec.degraded(lv).knobs()["n_probes"] for lv in range(4)]
        assert probes == [32, 16, 8, 4]
        spec = neighbors_effort.spec_for_params(
            ivf_pq.SearchParams(n_probes=32, lut_dtype="float32"))
        assert spec.degraded(1).knobs()["lut_dtype"] == "float32"
        assert spec.degraded(2).knobs()["lut_dtype"] == "bfloat16"

    def test_brute_force_is_identity_at_every_level(self):
        spec = brute_force.EffortSpec()
        assert spec.degraded(3) is spec
        assert spec.knobs() == {}
        p = object()
        assert spec.apply(p) is p

    def test_spec_for_index_reads_served_shapes(self):
        idx = SimpleNamespace(
            search_params=ivf_flat.SearchParams(n_probes=8), kind="ivf_flat")
        assert neighbors_effort.backend_for_index(idx) == "ivf_flat"
        assert neighbors_effort.spec_for_index(
            SimpleNamespace(search_params=None, kind="nope")) is None

    def test_knob_names_are_the_recompile_deny_list(self):
        # the analysis RECOMPILE rule keys on this exact set; a new knob
        # must land in both places
        assert neighbors_effort.EFFORT_KNOBS == frozenset({
            "n_probes", "refine_ratio", "lut_dtype",
            "itopk_size", "search_width",
        })


# ---------------------------------------------------------------------------
# the arbiter: one writer, one clamp, one derived-params identity


class _Degraded(SimpleNamespace):
    """Overload-ladder stand-in: just the ``level`` the arbiter reads."""


class TestEffortArbiter:
    def _arb(self, degraded_level=0, max_level=3):
        return EffortArbiter(
            _Degraded(level=degraded_level), max_level=max_level, name="t")

    def test_overload_clamps_but_never_writes(self):
        arb = self._arb(degraded_level=2)
        assert arb.autotune_level == 0
        assert arb.effective_level() == 2       # clamp floors the level
        arb.set_autotune_level(1)
        assert arb.effective_level() == 2       # still the clamp
        arb.set_autotune_level(3)
        assert arb.effective_level() == 3       # writer above the clamp
        arb.degraded.level = 0
        assert arb.effective_level() == 3       # clamp release: writer's
        assert arb.autotune_level == 3          # the clamp never wrote

    def test_writer_is_clamped_to_the_warmed_ladder(self):
        arb = self._arb(max_level=2)
        assert arb.set_autotune_level(7) == 2
        assert arb.set_autotune_level(-3) == 0
        assert arb.levels() == (0, 1, 2)

    def test_pin_overrides_both_actuators(self):
        arb = self._arb(degraded_level=2)
        arb.set_autotune_level(1)
        with arb.pinned(0):
            assert arb.effective_level() == 0
        assert arb.effective_level() == 2

    def test_apply_is_identity_cached_per_level(self):
        idx = SimpleNamespace(
            search_params=ivf_flat.SearchParams(n_probes=16))
        arb = self._arb()
        assert arb.apply(idx) is None           # full effort: caller's own
        arb.set_autotune_level(2)
        a, b = arb.apply(idx), arb.apply(idx)
        assert a is b, "derived params must be identity-stable (jit cache)"
        assert a.n_probes == 4
        assert a == derive_degraded_params(idx.search_params, 2)

    def test_concurrent_ladder_and_autotune_never_tear(self):
        # regression: the overload ladder stepping concurrently with the
        # autotune writer must always resolve to a valid arbitrated level
        # and an identity-cached derived object — no torn reads, no
        # deadlock (the arbiter lock is a leaf)
        idx = SimpleNamespace(
            search_params=ivf_flat.SearchParams(n_probes=16))
        arb = self._arb(max_level=3)
        valid = [derive_degraded_params(idx.search_params, lv)
                 for lv in (1, 2, 3)]
        stop = threading.Event()
        errors = []

        def ladder():
            lv = 0
            while not stop.is_set():
                lv = (lv + 1) % 3
                arb.degraded.level = lv

        def tuner():
            lv = 0
            while not stop.is_set():
                lv = (lv + 1) % 4
                arb.set_autotune_level(lv)

        def reader():
            try:
                while not stop.is_set():
                    eff = arb.effective_level()
                    if not 0 <= eff <= arb.max_level:
                        errors.append(f"effective {eff} out of ladder")
                    p = arb.apply(idx)
                    if p is not None and p not in valid:
                        errors.append(f"derived {p!r} not a ladder point")
            except Exception as exc:  # noqa: BLE001 — reported, not raised
                errors.append(repr(exc))

        threads = [threading.Thread(target=f)
                   for f in (ladder, tuner, reader, reader)]
        for t in threads:
            t.start()
        stop.wait(0.4)
        stop.set()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive(), "arbiter deadlocked"
        assert not errors, errors[:5]


# ---------------------------------------------------------------------------
# the controller policy, under a fake clock and fake taps


class _FakeSlo:
    def __init__(self):
        self.paging_specs = []
        self.alerting_specs = []

    def paging(self):
        return list(self.paging_specs)

    def health(self):
        return {"exhausted": [], "alerting": list(self.alerting_specs)}


class _FakeAuditor:
    def __init__(self, ewma=None):
        self.ewma = ewma

    def recall_ewma(self, name):
        return self.ewma


def _tuner(**kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("eval_s", 3600.0)  # never self-ticks; tests drive step()
    return Autotuner(**kw)


def _watched(tuner, *, ewma=None, max_level=3, floor=None,
             n_probes=32, name="t"):
    arb = EffortArbiter(None, max_level=max_level, name=name)
    slo = _FakeSlo()
    auditor = _FakeAuditor(ewma)
    idx = SimpleNamespace(
        search_params=ivf_flat.SearchParams(n_probes=n_probes),
        kind="ivf_flat")
    tuner.watch_index(name, arb, index=idx, auditor=auditor, slo=slo,
                      floor=floor)
    return arb, slo, auditor


class TestAutotunerPolicy:
    def test_burn_sheds_after_degrade_ticks_only(self):
        tuner = _tuner(recall_floor=0.9, degrade_ticks=2, restore_ticks=3)
        arb, slo, _ = _watched(tuner)
        slo.paging_specs = ["t-latency"]
        assert tuner.step("t", now=1.0) == 0    # one bad tick: hysteresis
        assert tuner.step("t", now=2.0) == 1    # sustained: shed one notch
        assert tuner.step("t", now=3.0) == 1    # counter reset: not yet
        assert tuner.step("t", now=4.0) == 2
        assert arb.autotune_level == 2

    def test_recall_floor_buys_effort_back_immediately(self):
        tuner = _tuner(recall_floor=0.9, degrade_ticks=2, restore_ticks=3)
        arb, slo, auditor = _watched(tuner, ewma=0.95)
        arb.set_autotune_level(2)
        auditor.ewma = 0.85                     # audit says we broke it
        slo.paging_specs = ["t-latency"]        # even while p99 burns
        assert tuner.step("t", now=1.0) == 1    # no hysteresis on the way up
        assert tuner.step("t", now=2.0) == 0

    def test_calm_walks_back_to_full_effort_after_restore_ticks(self):
        tuner = _tuner(recall_floor=0.9, degrade_ticks=1, restore_ticks=3)
        arb, slo, _ = _watched(tuner)
        arb.set_autotune_level(2)
        assert tuner.step("t", now=1.0) == 2
        assert tuner.step("t", now=2.0) == 2
        assert tuner.step("t", now=3.0) == 1    # third calm tick: one notch
        assert tuner.step("t", now=4.0) == 1
        assert tuner.step("t", now=5.0) == 1
        assert tuner.step("t", now=6.0) == 0    # and fully home

    def test_descent_blocked_when_recall_margin_is_thin(self):
        # no frontier loaded: the synthetic ladder model assumes ~0.02
        # recall per level, so an EWMA hugging the floor blocks the shed
        tuner = _tuner(recall_floor=0.9, degrade_ticks=1, restore_ticks=3)
        arb, slo, _ = _watched(tuner, ewma=0.905)
        slo.paging_specs = ["t-latency"]
        for tick in range(5):
            assert tuner.step("t", now=float(tick)) == 0
        assert arb.autotune_level == 0

    def test_page_alerts_drive_the_loop_ticket_latches_do_not(self):
        # a ticket-severity latch holds for its whole (scaled) long
        # window — acting on it would pin effort shed long after the
        # breach ends, so only the page slice counts as "burning"
        tuner = _tuner(recall_floor=0.9, degrade_ticks=1, restore_ticks=3)
        arb, slo, _ = _watched(tuner)
        slo.alerting_specs = ["t-latency"]      # ticket latched, no page
        for tick in range(4):
            assert tuner.step("t", now=float(tick)) == 0
        # engines without the paging() accessor fall back to alerting
        legacy = SimpleNamespace(
            health=lambda: {"exhausted": [], "alerting": ["t2-latency"]})
        tuner2 = _tuner(recall_floor=0.9, degrade_ticks=1)
        arb2 = EffortArbiter(None, max_level=2, name="t2")
        tuner2.watch_index("t2", arb2, slo=legacy)
        assert tuner2.step("t2", now=1.0) == 1

    def test_pinned_at_min_effort_surfaces_in_health(self):
        tuner = _tuner(recall_floor=0.9, degrade_ticks=1, restore_ticks=3)
        arb, slo, _ = _watched(tuner, max_level=2)
        slo.paging_specs = ["t-latency"]
        for tick in range(4):
            tuner.step("t", now=float(tick))
        assert arb.autotune_level == 2
        assert tuner.health() == {"pinned_min_effort": ["t"]}
        slo.paging_specs = []
        tuner.step("t", now=10.0)
        assert tuner.health() == {"pinned_min_effort": []}

    def test_frontier_sets_the_calm_target(self):
        # measured frontier: levels 1-2 clear the floor, level 3 does not
        # → calm walks to level 2 (max QPS s.t. recall >= floor) and stays
        model = FrontierModel(meta={"dataset": "unit"})
        for probes, recall, qps in ((32, 0.98, 100.0), (16, 0.96, 180.0),
                                    (8, 0.93, 300.0), (4, 0.85, 500.0)):
            model.add("ivf_flat", FrontierPoint(
                effort={"n_probes": probes, "refine_ratio": 1},
                qps=qps, recall=recall))
        tuner = _tuner(recall_floor=0.9, degrade_ticks=1, restore_ticks=1,
                       frontier=model)
        arb, _slo, _ = _watched(tuner)
        levels = [tuner.step("t", now=float(i)) for i in range(1, 5)]
        assert levels == [1, 2, 2, 2], (
            "calm ticks must converge on the frontier optimum, not full "
            f"effort: {levels}"
        )

    def test_step_event_is_published_with_reason(self):
        seen = []
        sub = events.subscribe(
            seen.append, kinds=frozenset({"autotune_step"}), name="capture")
        try:
            tuner = _tuner(recall_floor=0.9, degrade_ticks=1)
            arb, slo, _ = _watched(tuner)
            slo.paging_specs = ["t-latency"]
            tuner.step("t", now=1.0)
            assert arb.autotune_level == 1
            assert len(seen) == 1
            ev = seen[0]
            assert ev.fields["index"] == "t"
            assert ev.fields["level"] == 1
            assert ev.fields["step_reason"] == "p99_burn"
            slo.paging_specs = []
            for tick in range(2, 8):
                tuner.step("t", now=float(tick))
            assert arb.autotune_level == 0
            assert seen[-1].recovered, (
                "the climb back to full effort must close the event story"
            )
        finally:
            sub.unsubscribe()

    def test_gauges_publish_and_retire_with_the_index(self):
        reg = MetricsRegistry()
        tuner = _tuner(registry=reg, recall_floor=0.9, degrade_ticks=1)
        _arb, slo, _ = _watched(tuner, ewma=0.97)
        slo.paging_specs = ["t-latency"]
        tuner.step("t", now=1.0)
        level = reg.gauge("raft_tpu_autotune_level").collect()
        assert level[(("index", "t"),)] == 1.0
        margin = reg.gauge("raft_tpu_autotune_recall_floor_margin").collect()
        assert margin[(("index", "t"),)] == pytest.approx(0.07)
        tuner.unwatch_index("t")
        for metric in ("raft_tpu_autotune_level",
                       "raft_tpu_autotune_recall_floor_margin",
                       "raft_tpu_autotune_predicted_qps"):
            assert not reg.gauge(metric).collect(), (
                f"{metric} series must retire with the watched index"
            )

    def test_snapshot_provider_registers_and_unregisters(self):
        reg = MetricsRegistry()
        tuner = _tuner(registry=reg)
        _watched(tuner)
        snap = reg.snapshot()["autotune"]
        assert snap["indexes"]["t"]["level"] == 0
        assert snap["frontier_loaded"] is False
        tuner.stop()
        assert "autotune" not in reg.snapshot()


# ---------------------------------------------------------------------------
# service plumbing: the arbiter exists, the tuner watches, healthz folds


def test_service_wires_autotuner_and_healthz_folds_it():
    from raft_tpu import serve

    rng = np.random.default_rng(3)
    x = rng.random((200, 8), dtype=np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4), x)
    mi = serve.MutableIndex(
        idx, search_params=ivf_flat.SearchParams(n_probes=4))
    tuner = _tuner(recall_floor=0.9)
    svc = serve.SearchService(k=3, min_bucket=1, max_batch=4,
                              autotune=tuner)
    try:
        svc.add_index("t", mi)
        arb = svc.effort_arbiter("t")
        assert arb is not None, "autotune service must arbitrate effort"
        assert tuner.level("t") == 0
        hz = svc.healthz()
        check = hz["indexes"]["t"]["checks"]["autotune"]
        assert check["status"] == "OK"
        # reduced effort is DEGRADED by design (still serving), never
        # UNHEALTHY; pinned at min effort names the exhausted ladder
        arb.set_autotune_level(1)
        hz = svc.healthz()
        check = hz["indexes"]["t"]["checks"]["autotune"]
        assert check["status"] == "DEGRADED"
        tuner._states["t"].pinned_min = True
        hz = svc.healthz()
        check = hz["indexes"]["t"]["checks"]["autotune"]
        assert check["status"] == "DEGRADED"
        assert "minimum effort" in check["detail"]
        assert hz["status"] in ("DEGRADED", "UNHEALTHY")
        st = svc.stats("t")
        assert st["autotune_level"] == 1
        assert st["effective_effort_level"] == 1
        svc.remove_index("t")
        assert tuner.level("t") is None, "remove must unwatch the index"
    finally:
        svc.stop()


def test_service_autotuner_reports_measured_rows_per_device_second():
    # with no frontier loaded the predicted_qps gauge falls back to the
    # index's own ServingMetrics: real rows over device-stage seconds
    from raft_tpu import serve

    rng = np.random.default_rng(4)
    x = rng.random((200, 8), dtype=np.float32)
    q = rng.random((12, 8), dtype=np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4), x)
    mi = serve.MutableIndex(
        idx, search_params=ivf_flat.SearchParams(n_probes=4))
    reg = MetricsRegistry()
    tuner = _tuner(registry=reg, recall_floor=0.9)
    svc = serve.SearchService(k=3, min_bucket=1, max_batch=4, start=False,
                              autotune=tuner)
    try:
        svc.add_index("t", mi, warmup=True)
        futs = [svc.submit("t", q[i]) for i in range(len(q))]
        svc.flush("t")
        for f in futs:
            f.result(timeout=60)
        tuner.step("t", now=1.0)
        device_s = svc.stats("t")["stage_sum_s"]["device"]
        assert device_s > 0
        qps = reg.gauge("raft_tpu_autotune_predicted_qps").collect()
        assert qps[(("index", "t"),)] == pytest.approx(len(q) / device_s)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# event taxonomy: the new kind exists, annotates, never triggers


def test_autotune_event_taxonomy():
    assert "autotune_step" in events.KINDS
    # the step annotates the incident its motivating slo_burn opened —
    # it must never open one itself (the controller responding to an
    # alert is context, not a new story)
    assert "autotune_step" not in events.TRIGGER_KINDS
    assert "slo_burn" in events.TRIGGER_KINDS
    with pytest.raises(ValueError):
        events.publish("autotune_stepp")


# ---------------------------------------------------------------------------
# frontier model: pareto, round-trip, schema guard, nearest-point predict


class TestFrontierModel:
    def _point(self, probes, qps, recall):
        return FrontierPoint(effort={"n_probes": probes}, qps=qps,
                             recall=recall)

    def test_pareto_drops_dominated_points(self):
        pts = [self._point(32, 100.0, 0.98), self._point(16, 80.0, 0.95),
               self._point(8, 300.0, 0.93), self._point(4, 500.0, 0.85)]
        kept = pareto(pts)
        assert [p.effort["n_probes"] for p in kept] == [4, 8, 32], (
            "16 probes is dominated (less recall AND less qps than 32)"
        )

    def test_roundtrip_and_schema_guard(self, tmp_path):
        model = FrontierModel(meta={"dataset": "unit", "k": 10})
        model.add("ivf_flat", self._point(8, 300.0, 0.93))
        path = str(tmp_path / "frontier_model.json")
        model.save(path)
        loaded = FrontierModel.load(path)
        assert loaded.meta["dataset"] == "unit"
        assert loaded.points["ivf_flat"][0].effort == {"n_probes": 8}
        doc = json.load(open(path))
        assert doc["schema"] == "raft_tpu.frontier"
        with pytest.raises(ValueError, match="not a raft_tpu.frontier"):
            FrontierModel.from_dict({"schema": "something.else"})
        doc["schema_version"] = 99
        with pytest.raises(ValueError, match="newer than this reader"):
            FrontierModel.from_dict(doc)

    def test_predict_prefers_exact_then_nearest(self):
        model = FrontierModel()
        for probes in (4, 8, 32):
            model.add("ivf_flat", self._point(probes, 100.0 / probes, 0.9))
        exact = model.predict("ivf_flat", {"n_probes": 8})
        assert exact.effort["n_probes"] == 8
        near = model.predict("ivf_flat", {"n_probes": 28})
        assert near.effort["n_probes"] == 32
        assert model.predict("cagra", {"itopk_size": 64}) is None


# ---------------------------------------------------------------------------
# the frontier sweep itself (tiny, CPU): runnable end to end, loadable


def test_frontier_sweep_smoke(tmp_path):
    from raft_tpu.bench.frontier import frontier_main

    out = str(tmp_path / "model.json")
    sweep_out = str(tmp_path / "sweep.json")
    rc = frontier_main([
        "--n", "1000", "--queries", "8", "--k", "5",
        "--dataset", "unit-smoke", "--dim", "16",
        "--algos", "raft_tpu_brute_force,raft_tpu_ivf_flat",
        "--no-comparators", "--warmup", "0", "--iters", "1",
        "--out", out, "--sweep-out", sweep_out,
    ])
    assert rc == 0
    model = FrontierModel.load(out)
    assert set(model.backends()) == {"brute_force", "ivf_flat"}
    assert model.meta["n"] == 1000 and model.meta["k"] == 5
    for backend in model.backends():
        pts = model.points[backend]
        assert pts, f"{backend} swept no points"
        for p in pts:
            assert 0.0 <= p.recall <= 1.0 and p.qps > 0
    # the sweep artifact keeps the legacy human-readable shape alongside
    doc = json.load(open(sweep_out))
    assert doc["results"] and doc["n"] == 1000


# ---------------------------------------------------------------------------
# zero-recompile contract: shuffled effort traffic on all four backends


def _backend_case(kind, x):
    """(served index stub, search_fn(params, batch)) for one backend."""
    if kind == "ivf_flat":
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x)
        base = ivf_flat.SearchParams(n_probes=8)
        return (SimpleNamespace(search_params=base, kind=kind),
                lambda p, q, k: ivf_flat.search(p, idx, q, k))
    if kind == "ivf_pq":
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=2), x)
        base = ivf_pq.SearchParams(n_probes=8)
        return (SimpleNamespace(search_params=base, kind=kind),
                lambda p, q, k: ivf_pq.search(p, idx, q, k))
    if kind == "cagra":
        idx = cagra.build(
            cagra.IndexParams(graph_degree=8, intermediate_graph_degree=16),
            x)
        base = cagra.SearchParams(itopk_size=64)
        return (SimpleNamespace(search_params=base, kind=kind),
                lambda p, q, k: cagra.search(p, idx, q, k))
    idx = brute_force.build(x)
    return (SimpleNamespace(search_params=None, kind=kind),
            lambda p, q, k: brute_force.search(idx, q, k))


@pytest.mark.parametrize(
    "kind", ["ivf_flat", "ivf_pq", "cagra", "brute_force"])
def test_zero_recompiles_under_shuffled_effort_traffic(kind):
    d = 16
    rng = np.random.default_rng(11)
    x = rng.random((256, d), dtype=np.float32)
    q = rng.random((16, d), dtype=np.float32)
    served, run = _backend_case(kind, x)
    arb = EffortArbiter(None, max_level=3, name=f"fx_{kind}")
    base = served.search_params

    def search_fn(batch):
        # the serving dispatch contract: arbitrated params when reduced,
        # the index's own at full effort — values are host operands
        p = arb.apply(served)
        return run(p if p is not None else base, batch, 4)

    batcher = MicroBatcher(
        search_fn, d, min_bucket=8, max_batch=8,
        metrics=ServingMetrics(name=f"fx_{kind}"), effort=arb)
    try:
        batcher.warmup()
        c0 = compile_count()
        for wave in range(12):
            arb.set_autotune_level(int(rng.integers(0, 4)))
            futs = [batcher.submit(q[int(rng.integers(0, len(q)))])
                    for _ in range(int(rng.integers(1, 9)))]
            batcher.flush()
            for f in futs:
                d_, i_ = f.result(timeout=60)
                assert i_.shape == (4,)
        assert compile_count() - c0 == 0, (
            f"{kind}: effort moves recompiled post-warmup — a knob value "
            "leaked into an executable shape"
        )
        assert batcher.metrics.snapshot()["recompiles"] == 0
    finally:
        batcher.stop()
