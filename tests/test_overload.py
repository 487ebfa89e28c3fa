"""raft_tpu.serve.overload: admission control must shed strictly by
priority (interactive never), deadlines must expire as typed errors at
batch-cut time, degraded mode must be hysteretic under a synthetic
clock, hedged dispatch must fire at most once with the loser discarded,
and none of it may cost a single post-warmup recompile."""

import concurrent.futures
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from raft_tpu import serve
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.obs import events
from raft_tpu.obs.incidents import IncidentManager
from raft_tpu.serve.metrics import compile_count
from raft_tpu.serve.overload import (
    AdmissionController,
    DeadlineExceeded,
    DegradedModeManager,
    HedgedDispatcher,
    N_PRIORITIES,
    OverloadConfig,
    Shed,
    derive_degraded_params,
    expire_deadlines,
    validate_priority,
)

D = 12


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(17)
    x = rng.random((300, D), dtype=np.float32)
    q = rng.random((8, D), dtype=np.float32)
    return x, q


def _req(priority=1, deadline=None, wait_s=0.0, now=1000.0):
    """A fake batcher request: only the fields admission reads."""
    return SimpleNamespace(
        priority=priority, deadline=deadline, t_submit=now - wait_s,
        future=concurrent.futures.Future(),
    )


def _ctrl(**cfg):
    return AdmissionController(OverloadConfig(**cfg), name="t")


# ---------------------------------------------------------------------------
# priority validation


def test_validate_priority():
    assert validate_priority(None) == 1
    for p in range(N_PRIORITIES):
        assert validate_priority(p) == p
    for bad in (-1, N_PRIORITIES, 99):
        with pytest.raises(ValueError):
            validate_priority(bad)


# ---------------------------------------------------------------------------
# admission: strict shed order, burn latch, typed resolution


class TestAdmissionController:
    # (oldest wait as a multiple of admit_wait_s) -> expected level
    LEVELS = [(0.5, 0), (1.1, 1), (2.5, 2), (4.5, 3), (100.0, 3)]

    @pytest.mark.parametrize("mult,level", LEVELS)
    def test_shed_order_is_strict(self, mult, level):
        ctrl = _ctrl(admit_wait_s=0.1)
        try:
            now = 1000.0
            batch = [_req(priority=p, wait_s=0.1 * mult, now=now)
                     for p in range(N_PRIORITIES)]
            d = ctrl.decide(batch, now=now)
            assert d.level == level
            min_shed = N_PRIORITIES - level
            shed_ps = sorted(r.priority for r in d.shed)
            assert shed_ps == [p for p in range(N_PRIORITIES)
                               if level > 0 and p >= min_shed]
            assert all(r.priority < min_shed or level == 0
                       for r in d.admitted)
            # every shed future resolved with the typed error
            for r in d.shed:
                exc = r.future.exception(timeout=1)
                assert isinstance(exc, Shed)
                assert exc.priority == r.priority and exc.level == level
            for r in d.admitted:
                assert not r.future.done()
        finally:
            ctrl.close()

    def test_priority_zero_is_never_shed(self):
        ctrl = _ctrl(admit_wait_s=0.01)
        try:
            events.publish("slo_burn", "slo_burn_p99", index="t")
            now = 1000.0
            batch = [_req(priority=0, wait_s=50.0, now=now)
                     for _ in range(4)]
            d = ctrl.decide(batch, queue_rows=10_000, max_batch=1, now=now)
            assert d.level == 3 and len(d.admitted) == 4 and not d.shed
        finally:
            ctrl.close()

    def test_queue_depth_signal(self):
        ctrl = _ctrl(queue_factor=2.0)
        try:
            lvl = ctrl.pressure_level(
                oldest_wait_s=0.0, queue_rows=4, max_batch=2)
            assert lvl == 1  # 4 rows / (2.0 * 2) = 1.0
            assert ctrl.pressure_level(
                oldest_wait_s=0.0, queue_rows=16, max_batch=2) == 3
        finally:
            ctrl.close()

    def test_slo_burn_latch_raises_and_recovers(self):
        ctrl = _ctrl()
        try:
            assert not ctrl.burning()
            events.publish("slo_burn", "slo_burn_avail", index="t")
            assert ctrl.burning()
            base = ctrl.pressure_level(
                oldest_wait_s=0.0, queue_rows=0, max_batch=1)
            assert base == 1  # calm signals + one burn = level 1
            # an alert for a different index must not latch
            events.publish("slo_burn", "slo_burn_other", index="elsewhere")
            # the recovery edge clears exactly its reason
            events.publish("slo_burn", "slo_burn_avail",
                           recovered=True, index="t")
            assert not ctrl.burning()
            assert ctrl.pressure_level(
                oldest_wait_s=0.0, queue_rows=0, max_batch=1) == 0
        finally:
            ctrl.close()

    def test_deadline_expiry_is_typed_and_counted(self):
        ctrl = _ctrl()
        try:
            now = 1000.0
            dead = _req(priority=0, deadline=now - 0.5, now=now)
            alive = _req(priority=0, deadline=now + 5.0, now=now)
            d = ctrl.decide([dead, alive], now=now)
            assert d.expired == (dead,) and d.admitted == (alive,)
            exc = dead.future.exception(timeout=1)
            assert isinstance(exc, DeadlineExceeded)
            assert isinstance(exc, TimeoutError)  # catchable as timeout
            assert exc.late_s == pytest.approx(0.5)
            assert ctrl.expired_total == 1
        finally:
            ctrl.close()

    def test_shed_publishes_one_event_inside_an_incident(self):
        seen = []
        sub = events.subscribe(
            seen.append, kinds=frozenset({"admission_shed"}), name="capture")
        mgr = IncidentManager(events.default_bus(), window_s=5.0,
                              autoclose_s=60.0)
        ctrl = _ctrl(admit_wait_s=0.1)
        try:
            now = 1000.0
            batch = [_req(priority=3, wait_s=10.0, now=now)
                     for _ in range(3)]
            d = ctrl.decide(batch, now=now)
            assert len(d.shed) == 3
            assert len(seen) == 1, "one event per shedding cut, not per req"
            ev = seen[0]
            assert ev.fields["index"] == "t" and ev.fields["level"] == 3
            assert ev.fields["shed"] == {"3": 3}
            # admission_shed is a trigger kind: the decision lands in a
            # correlated incident timeline
            open_ = mgr.open_incidents()
            assert len(open_) == 1
            assert open_[0].trigger["kind"] == "admission_shed"
            assert any(e["kind"] == "admission_shed"
                       for e in open_[0].timeline)
            assert ctrl.shed_total == 3
        finally:
            ctrl.close()
            sub.unsubscribe()

    def test_expire_deadlines_without_controller(self):
        now = 1000.0
        dead = _req(deadline=now - 1.0, now=now)
        alive = _req(deadline=None, now=now)
        out = expire_deadlines([dead, alive], now=now, index="t")
        assert out == [alive]
        assert isinstance(dead.future.exception(timeout=1),
                          DeadlineExceeded)


# ---------------------------------------------------------------------------
# degraded mode: synthetic-clock hysteresis, param derivation


class TestDegradedMode:
    CFG = dict(degrade_after_s=1.0, restore_after_s=5.0,
               max_degrade_level=2)

    def test_hysteresis_under_synthetic_clock(self):
        seen = []
        sub = events.subscribe(
            seen.append,
            kinds=frozenset({"degraded_enter", "degraded_exit"}),
            name="capture")
        try:
            mgr = DegradedModeManager(OverloadConfig(**self.CFG), name="t")
            assert mgr.step(True, now=0.0) == 0    # arms the clock only
            assert mgr.step(True, now=0.5) == 0    # not sustained yet
            assert mgr.step(True, now=1.0) == 1    # first notch
            assert mgr.step(True, now=1.5) == 1    # re-armed, not yet
            assert mgr.step(True, now=2.0) == 2    # second notch
            assert mgr.step(True, now=9.0) == 2    # capped at max
            assert mgr.step(False, now=9.1) == 2   # calm arms restore
            assert mgr.step(False, now=13.0) == 2  # 3.9s calm < 5s
            assert mgr.step(False, now=14.1) == 1  # first restore
            assert mgr.step(False, now=18.0) == 1
            assert mgr.step(False, now=19.1) == 0  # fully restored
            kinds = [(e.kind, e.fields["level"], e.recovered) for e in seen]
            assert kinds == [
                ("degraded_enter", 1, False), ("degraded_enter", 2, False),
                ("degraded_exit", 1, False), ("degraded_exit", 0, True),
            ]
        finally:
            sub.unsubscribe()

    def test_flapping_load_cannot_flap_effort(self):
        mgr = DegradedModeManager(OverloadConfig(**self.CFG), name="t")
        now = 0.0
        for i in range(40):  # 0.4s of pressure, 0.4s of calm, repeat
            assert mgr.step(i % 2 == 0, now=now) == 0
            now += 0.4

    def test_calm_resets_the_pressure_clock(self):
        mgr = DegradedModeManager(OverloadConfig(**self.CFG), name="t")
        assert mgr.step(True, now=0.0) == 0
        assert mgr.step(False, now=0.9) == 0   # pressure clock wiped
        assert mgr.step(True, now=1.0) == 0    # re-armed from scratch
        assert mgr.step(True, now=1.9) == 0    # only 0.9s sustained
        assert mgr.step(True, now=2.0) == 1

    def test_pinned_restores(self):
        mgr = DegradedModeManager(OverloadConfig(**self.CFG), name="t")
        with mgr.pinned(2):
            assert mgr.level == 2
        assert mgr.level == 0

    def test_derive_degraded_params(self):
        p1 = derive_degraded_params(ivf_flat.SearchParams(n_probes=16), 1)
        assert p1.n_probes == 8
        p2 = derive_degraded_params(
            ivf_pq.SearchParams(n_probes=16, lut_dtype="float32"), 2)
        assert p2.n_probes == 4 and p2.lut_dtype == "bfloat16"
        c1 = derive_degraded_params(cagra.SearchParams(itopk_size=128), 1)
        assert c1.itopk_size == 64
        c9 = derive_degraded_params(cagra.SearchParams(itopk_size=64), 9)
        assert c9.itopk_size == 32  # floored, never degenerate
        assert derive_degraded_params(None, 2) is None
        assert derive_degraded_params("opaque", 2) == "opaque"

    def test_params_for_is_identity_cached(self):
        mgr = DegradedModeManager(OverloadConfig(**self.CFG), name="t")
        mi = SimpleNamespace(
            search_params=ivf_flat.SearchParams(n_probes=32))
        assert mgr.params_for(mi) is None  # full effort
        with mgr.pinned(1):
            a = mgr.params_for(mi)
            b = mgr.params_for(mi)
        assert a is b and a.n_probes == 16


# ---------------------------------------------------------------------------
# hedged dispatch: fires at most once, loser discarded, errors surface


class TestHedgedDispatcher:
    def test_requires_two_members(self):
        with pytest.raises(ValueError):
            HedgedDispatcher([lambda q: q], OverloadConfig())

    def test_hedge_fires_exactly_once_and_wins(self):
        release = threading.Event()
        calls = {"a": 0, "b": 0}

        def slow(*args):
            calls["a"] += 1
            release.wait(timeout=30)
            return "primary"

        def fast(*args):
            calls["b"] += 1
            return "hedge"

        seen = []
        sub = events.subscribe(
            seen.append, kinds=frozenset({"hedge_fired"}), name="capture")
        try:
            h = HedgedDispatcher(
                [slow, fast],
                OverloadConfig(hedge=True, hedge_min_delay_s=0.01),
                name="t")
            out = h.dispatch(None)
            assert out == "hedge"
            assert h.fired_total == 1 and h.hedge_wins == 1
            assert calls == {"a": 1, "b": 1}
            assert len(seen) == 1 and seen[0].fields["index"] == "t"
            release.set()  # loser completes; its result is discarded
        finally:
            release.set()
            sub.unsubscribe()

    def test_fast_primary_never_fires_the_hedge(self):
        calls = {"b": 0}

        def hedge(*args):
            calls["b"] += 1
            return "hedge"

        h = HedgedDispatcher(
            [lambda *a: "primary", hedge],
            OverloadConfig(hedge=True, hedge_min_delay_s=0.2), name="t")
        for _ in range(3):
            assert h.dispatch(None) == "primary"
        assert h.fired_total == 0 and calls["b"] == 0

    def test_all_members_failing_raises_the_primary_error(self):
        def boom(*args):
            raise RuntimeError("primary down")

        def boom2(*args):
            raise RuntimeError("hedge down")

        h = HedgedDispatcher(
            [boom, boom2],
            OverloadConfig(hedge=True, hedge_min_delay_s=0.01), name="t")
        with pytest.raises(RuntimeError, match="down"):
            h.dispatch(None)

    def test_batcher_routes_only_p0_batches_through_the_hedger(self, corpus):
        x, _q = corpus
        mi = serve.MutableIndex(brute_force.build(x))
        dispatches = []

        def primary(queries):
            dispatches.append("primary")
            return mi.search(queries, 4)

        hedger = HedgedDispatcher(
            [primary, lambda q: mi.search(q, 4)],
            OverloadConfig(hedge=True, hedge_min_delay_s=1.0), name="t")
        b = serve.MicroBatcher(lambda q: mi.search(q, 4), D, max_batch=4,
                               start=False, hedger=hedger)
        try:
            b.warmup()
            rng = np.random.default_rng(3)
            q = rng.random((D,), dtype=np.float32)
            n0 = len(dispatches)
            f = b.submit(q, priority=1)
            b.flush()
            f.result(timeout=60)
            assert len(dispatches) == n0  # standard traffic: no hedger
            f = b.submit(q, priority=0)
            b.flush()
            d, i = f.result(timeout=60)
            assert d.shape == (4,)
            assert len(dispatches) == n0 + 1  # p0 rides the hedged path
            assert hedger.fired_total == 0  # fast primary: no hedge fire
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# service level: deadlines at flush, timeout unification, shedding,
# degraded search, zero recompiles


def _overload_service(mi, *, cfg=None, start=False, max_batch=8, **kw):
    svc = serve.SearchService(
        k=4, max_batch=max_batch, start=start,
        overload=cfg if cfg is not None else OverloadConfig(), **kw)
    svc.add_index("t", mi)
    return svc


class TestServiceOverload:
    def test_deadline_expires_at_flush_with_typed_error(self, corpus):
        x, q = corpus
        svc = _overload_service(serve.MutableIndex(brute_force.build(x)))
        try:
            svc.warmup("t")
            fut = svc.submit("t", q[0], deadline_s=1e-9)
            live = svc.submit("t", q[1], deadline_s=60.0)
            time.sleep(0.01)
            svc.flush("t")
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
            d, _i = live.result(timeout=60)
            assert d.shape == (4,)
            assert svc.stats("t")["deadline_expired"] == 1
        finally:
            svc.stop()

    def test_deadlines_expire_even_without_overload(self, corpus):
        # expired work must never occupy a device slot regardless of
        # whether an admission controller is installed
        x, q = corpus
        svc = serve.SearchService(k=4, max_batch=8, start=False,
                                  overload=False)
        try:
            svc.add_index("t", serve.MutableIndex(brute_force.build(x)))
            fut = svc.submit("t", q[0], deadline_s=1e-9)
            time.sleep(0.01)
            svc.flush("t")
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
        finally:
            svc.stop()

    def test_search_timeout_is_a_deadline(self, corpus):
        # search(timeout=) used to be a pure client-side wait; it now
        # also rides as the request deadline so expired work drops at
        # batch cut instead of computing into the void
        x, q = corpus
        svc = _overload_service(serve.MutableIndex(brute_force.build(x)),
                                start=True)
        try:
            svc.warmup("t")
            with pytest.raises(TimeoutError):
                svc.search("t", q[0], timeout=1e-9)
            d, _i = svc.search("t", q[0], timeout=60.0)
            assert d.shape == (4,)
        finally:
            svc.stop()

    def test_service_sheds_background_first_under_queue_pressure(
            self, corpus):
        x, q = corpus
        svc = _overload_service(
            serve.MutableIndex(brute_force.build(x)),
            cfg=OverloadConfig(queue_factor=0.25, admit_wait_s=1e9),
            max_batch=2)
        try:
            svc.warmup("t")
            p0 = [svc.submit("t", q[i % len(q)], priority=0)
                  for i in range(3)]
            p3 = [svc.submit("t", q[i % len(q)], priority=3)
                  for i in range(12)]
            svc.flush("t")
            for f in p0:  # interactive always answers
                d, _i = f.result(timeout=60)
                assert d.shape == (4,)
            outcomes = []
            for f in p3:
                try:
                    f.result(timeout=60)
                    outcomes.append("served")
                except Shed as exc:
                    assert exc.priority == 3 and exc.level >= 1
                    outcomes.append("shed")
            assert "shed" in outcomes, outcomes
            st = svc.stats("t")
            assert st["shed_requests"] >= 1
            assert st["admission_level"] >= 0
        finally:
            svc.stop()

    def test_degraded_search_stays_warm_and_correct(self, corpus):
        x, q = corpus
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x)
        mi = serve.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=8))
        svc = _overload_service(
            mi, ragged=serve.RaggedSpec(k_max=8, filters=False))
        try:
            svc.warmup("t")
            mgr = svc._degraded["t"]
            assert mgr.levels() == (0, 1, 2)
            c0 = compile_count()
            for level in mgr.levels():
                with mgr.pinned(level):
                    fut = svc.submit("t", q[0], k=4)
                    svc.flush("t")
                    d, i = fut.result(timeout=60)
                    assert d.shape == (4,) and i.shape == (4,)
                    assert (np.asarray(i) >= 0).all()
            assert compile_count() - c0 == 0, (
                "degraded level flip recompiled — the level ladder was "
                "not warmed"
            )
            with mgr.pinned(2):
                hz = svc.healthz()
            check = hz["indexes"]["t"]["checks"]["overload"]
            assert check["status"] == "DEGRADED"
        finally:
            svc.stop()

    def test_zero_recompiles_under_shuffled_overload_traffic(self, corpus):
        x, q = corpus
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x)
        mi = serve.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=8))
        svc = _overload_service(
            mi, cfg=OverloadConfig(admit_wait_s=1e9, queue_factor=1e9),
            ragged=serve.RaggedSpec(k_max=8))
        try:
            svc.warmup("t")
            rng = np.random.default_rng(7)
            c0 = compile_count()
            for _ in range(6):
                futs = [
                    svc.submit(
                        "t", q[int(rng.integers(0, len(q)))],
                        k=int(rng.integers(1, 9)),
                        priority=int(rng.integers(0, N_PRIORITIES)),
                        deadline_s=float(rng.uniform(30.0, 60.0)),
                    )
                    for _ in range(int(rng.integers(1, 9)))
                ]
                svc.flush("t")
                for f in futs:
                    f.result(timeout=60)
            assert compile_count() - c0 == 0, (
                "shuffled (k, priority, deadline) traffic recompiled — "
                "overload metadata leaked into executable shapes"
            )
            assert svc.stats("t")["recompiles"] == 0
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# taxonomy: the new kinds exist, unknown kinds still fail loudly


def test_overload_event_taxonomy():
    for kind in ("admission_shed", "degraded_enter", "degraded_exit",
                 "hedge_fired"):
        assert kind in events.KINDS
    # shed + degrade decisions open incidents; exits/hedges annotate
    assert "admission_shed" in events.TRIGGER_KINDS
    assert "degraded_enter" in events.TRIGGER_KINDS
    assert "degraded_exit" not in events.TRIGGER_KINDS
    assert "hedge_fired" not in events.TRIGGER_KINDS
    with pytest.raises(ValueError):
        events.publish("admission_shedd")  # typos fail loudly, not vanish
