"""raft_tpu.obs v2: online recall auditing (degradation alarm on a
corrupted index, hot-path non-blocking contract, p99 budget), XLA cost
accounting graceful degradation, health verdict transitions, live-buffer
gauge retirement, and Prometheus export correctness under concurrent
hot-swap.

Shapes here are deliberately distinct (d=32) from tests/test_serve.py
(d=24) and tests/test_obs.py (d=28): all suites share one process and one
jit cache, and shape collisions would let one suite's warmup silence
another's compile-count assertions.
"""

import copy
import gc
import re
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import obs, serve
from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.obs import cost as obs_cost
from raft_tpu.obs import health as obs_health
from raft_tpu.obs.quality import QualityAuditor, _exact_topk
from raft_tpu.obs.registry import MetricsRegistry
from raft_tpu.stats import (
    rank_displacement,
    recall_at_k,
    tie_aware_recall_at_k,
)

D = 32  # this suite's own query dimensionality (see module docstring)


# ---------------------------------------------------------------------------
# canonical recall (satellite: one implementation, used everywhere)


class TestCanonicalRecall:
    def test_perfect_and_disjoint(self):
        ref = np.arange(12).reshape(3, 4)
        assert recall_at_k(ref, ref) == 1.0
        assert recall_at_k(ref + 100, ref) == 0.0

    def test_order_insensitive_partial(self):
        ref = np.array([[0, 1, 2, 3]])
        served = np.array([[3, 2, 9, 0]])  # 3 of 4, scrambled order
        assert recall_at_k(served, ref) == pytest.approx(0.75)

    def test_negative_ref_ids_leave_denominator(self):
        ref = np.array([[0, 1, -1, -1]])       # only 2 valid truths
        served = np.array([[0, 1, 7, 8]])
        assert recall_at_k(served, ref) == 1.0

    def test_k_truncation(self):
        ref = np.array([[0, 1, 2, 3]])
        served = np.array([[0, 9, 9, 9]])
        assert recall_at_k(served, ref, 1) == 1.0
        assert recall_at_k(served, ref, 4) == pytest.approx(0.25)

    def test_tie_aware_accepts_equal_distances(self):
        ref_d = np.array([[1.0, 2.0, 3.0]])
        # different ids but identical distances must count as recalled
        assert tie_aware_recall_at_k(ref_d, ref_d) == 1.0
        worse = np.array([[1.0, 2.0, 9.0]])
        assert tie_aware_recall_at_k(worse, ref_d) == pytest.approx(2 / 3)

    def test_rank_displacement(self):
        ref = np.array([[0, 1, 2, 3]])
        assert rank_displacement(ref, ref) == 0.0
        swapped = np.array([[1, 0, 2, 3]])     # two items off by one
        assert rank_displacement(swapped, ref) == pytest.approx(0.5)
        missing = np.array([[9, 9, 9, 9]])     # absent = full-k penalty
        assert rank_displacement(missing, ref) == pytest.approx(4.0)

    def test_exact_oracle_matches_brute_force(self):
        rng = np.random.default_rng(5)
        x = rng.random((300, D), dtype=np.float32)
        q = rng.random((7, D), dtype=np.float32)
        idx = brute_force.build(x)
        _, ref_ids = brute_force.search(idx, q, 5)
        _, got_ids = _exact_topk(
            x, np.arange(x.shape[0]), q, 5, "sqeuclidean"
        )
        assert recall_at_k(got_ids, np.asarray(ref_ids)) == 1.0


# ---------------------------------------------------------------------------
# auditor mechanics (unit level, no serve stack)


class _FakeIndex:
    """Minimal stand-in exposing the surface the auditor reads."""

    def __init__(self, vecs, ids, metric="sqeuclidean"):
        self._vecs = np.asarray(vecs, np.float32)
        self._ids = np.asarray(ids, np.int64)
        self.metric = metric
        self.generation = 0

    def live_vectors(self):
        return self._vecs, self._ids


@pytest.fixture()
def fake_corpus():
    rng = np.random.default_rng(11)
    x = rng.random((200, D), dtype=np.float32)
    q = rng.random((6, D), dtype=np.float32)
    _, good_ids = _exact_topk(x, np.arange(200), q, 5, "sqeuclidean")
    return _FakeIndex(x, np.arange(200)), q, good_ids


def test_alarm_is_edge_triggered_and_rearms(fake_corpus):
    index, q, good_ids = fake_corpus
    events = []
    reg = MetricsRegistry()
    aud = QualityAuditor(
        k=5, sampling=1.0, threshold=0.9, ewma_alpha=1.0,
        on_degraded=lambda *a: events.append(a), registry=reg,
    )
    bad_ids = np.full_like(good_ids, 199_999)
    try:
        aud.observe("u", 1, index, q, good_ids)
        assert aud.flush() and events == []
        # two bad batches: one downward crossing -> exactly one alarm
        aud.observe("u", 1, index, q, bad_ids)
        aud.observe("u", 1, index, q, bad_ids)
        assert aud.flush()
        assert len(events) == 1
        name, version, ewma = events[0]
        assert (name, version) == ("u", 1) and ewma < 0.9
        # recovery re-arms; the next excursion fires again
        aud.observe("u", 1, index, q, good_ids)
        aud.observe("u", 1, index, q, bad_ids)
        assert aud.flush()
        assert len(events) == 2
        snap = aud.snapshot()["indexes"]["u"]
        assert snap["alarmed"] and snap["audits"] == 5
    finally:
        aud.stop()


def test_version_change_resets_ewma(fake_corpus):
    index, q, good_ids = fake_corpus
    reg = MetricsRegistry()
    aud = QualityAuditor(
        k=5, sampling=1.0, threshold=0.5, ewma_alpha=0.1, registry=reg
    )
    bad_ids = np.full_like(good_ids, 199_999)
    try:
        for _ in range(3):
            aud.observe("v", 1, index, q, bad_ids)
        assert aud.flush()
        assert aud.recall_ewma("v") == pytest.approx(0.0)
        # the rebuilt (swapped) version starts a fresh EWMA — it must not
        # inherit the broken predecessor's history
        aud.observe("v", 2, index, q, good_ids)
        assert aud.flush()
        assert aud.recall_ewma("v") == pytest.approx(1.0)
        assert reg.gauge("raft_tpu_recall").value(
            index="v", version="2") == pytest.approx(1.0)
    finally:
        aud.stop()


def test_observe_never_blocks_when_worker_is_wedged(fake_corpus):
    """The hot-path contract: a full queue drops, it never waits."""
    index, q, good_ids = fake_corpus
    reg = MetricsRegistry()
    aud = QualityAuditor(k=5, sampling=1.0, queue_cap=1, registry=reg)
    release = threading.Event()
    aud._audit = lambda sample: release.wait(timeout=30)  # wedge the worker
    try:
        for _ in range(20):
            t0 = time.perf_counter()
            aud.observe("w", 1, index, q, good_ids)
            assert time.perf_counter() - t0 < 0.1
        snap = aud.snapshot()
        assert snap["dropped"] > 0
        assert snap["dropped"] + snap["submitted"] == 20
        assert reg.counter(
            "raft_tpu_quality_dropped_total").value(index="w") > 0
    finally:
        release.set()
        aud.stop()


def test_sampling_zero_audits_nothing(fake_corpus):
    index, q, good_ids = fake_corpus
    aud = QualityAuditor(k=5, sampling=0.0, registry=MetricsRegistry())
    try:
        assert not aud.observe("z", 1, index, q, good_ids)
        assert aud.snapshot()["submitted"] == 0
    finally:
        aud.stop()


# ---------------------------------------------------------------------------
# acceptance: corrupted index trips the alarm; auditing stays off the
# hot path (p99 budget)


def _clustered(rng, n, n_q):
    """Clustered corpus: shuffling IVF centroids on data like this sends
    probes to the wrong lists, which is the corruption the auditor must
    catch (iid data would mask it — every list looks alike)."""
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
    """The deliberate failure mode: coarse centroids shuffled (a 'bad
    hot-swap'), lists untouched — fast, plausible, and wrong."""
    bad = copy.copy(index)
    perm = rng.permutation(np.asarray(index.centers).shape[0])
    bad.centers = jnp.asarray(np.asarray(index.centers)[perm])
    return bad


def _serve_p99(svc, name, queries, n_requests):
    for i in range(n_requests):
        svc.search(name, queries[i % len(queries)])
    return svc.stats(name)["p99_ms"]


def test_corrupted_index_fires_alarm_within_one_flush_and_p99_budget():
    rng = np.random.default_rng(17)
    x, q = _clustered(rng, 600, 16)
    good = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
    bad = _corrupt(good, rng)
    sp = ivf_flat.SearchParams(n_probes=2)  # few probes: corruption bites

    events = []
    reg = MetricsRegistry()
    auditor = QualityAuditor(
        k=10, sampling=1.0, threshold=0.9, ewma_alpha=0.5,
        on_degraded=lambda *a: events.append(a), registry=reg,
    )
    n_req = 120
    try:
        # measure interleaved, retrying the pair to ride out CI noise: the
        # contract is that sampling=1.0 auditing costs O(enqueue) on the
        # hot path, so p99 must track the auditor-off service within 10%
        for attempt in range(3):
            svc_off = serve.SearchService(
                k=10, max_batch=8, max_delay_ms=1.0
            )
            svc_on = serve.SearchService(
                k=10, max_batch=8, max_delay_ms=1.0, auditor=auditor
            )
            svc_off.add_index(
                "qoff", serve.MutableIndex(bad, search_params=sp),
                warmup=True,
            )
            svc_on.add_index(
                "qa", serve.MutableIndex(bad, search_params=sp), warmup=True
            )
            p99_off = _serve_p99(svc_off, "qoff", q, n_req)
            p99_on = _serve_p99(svc_on, "qa", q, n_req)
            svc_off.stop()
            if p99_on <= 1.10 * p99_off:
                break
            svc_on.stop()
        else:
            pytest.fail(
                f"auditor on hot path: p99 {p99_on:.3f}ms vs "
                f"auditor-off {p99_off:.3f}ms (3 attempts)"
            )

        # one audit flush is enough for the alarm and the gauges
        assert auditor.flush(timeout=30.0)
        assert events, "degradation callback never fired"
        name, version, ewma = events[0]
        assert name == "qa" and ewma < 0.9
        assert reg.gauge("raft_tpu_recall").value(
            index="qa", version=str(version)) < 0.9
        assert reg.gauge("raft_tpu_recall_ewma").value(
            index="qa", version=str(version)) < 0.9
        assert auditor.snapshot()["indexes"]["qa"]["alarmed"]

        # the service-level verdict sees it too (recall check not OK)
        report = svc_on.healthz()
        assert report["status"] in (obs_health.DEGRADED, obs_health.UNHEALTHY)
        assert report["indexes"]["qa"]["checks"]["recall"]["status"] != (
            obs_health.OK
        )
        svc_on.stop()
    finally:
        auditor.stop()


def test_healthy_index_stays_quiet():
    rng = np.random.default_rng(23)
    x, q = _clustered(rng, 600, 8)
    good = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)

    events = []
    reg = MetricsRegistry()
    auditor = QualityAuditor(
        k=10, sampling=1.0, threshold=0.9, ewma_alpha=0.5,
        on_degraded=lambda *a: events.append(a), registry=reg,
    )
    svc = serve.SearchService(
        k=10, max_batch=8, max_delay_ms=0.5, auditor=auditor
    )
    try:
        svc.add_index(
            "qh",
            serve.MutableIndex(
                good, search_params=ivf_flat.SearchParams(n_probes=16)
            ),
            warmup=True,
        )
        for i in range(20):
            svc.search("qh", q[i % len(q)])
        assert auditor.flush(timeout=30.0)
        assert not events
        assert auditor.recall_ewma("qh") >= 0.9
        assert svc.healthz()["indexes"]["qh"]["status"] == obs_health.OK
    finally:
        svc.stop()
        auditor.stop()


# ---------------------------------------------------------------------------
# cost accounting: graceful degradation + the real thing


class _BrokenCompiled:
    def cost_analysis(self):
        raise RuntimeError("backend will not say")

    def memory_analysis(self):
        raise RuntimeError("backend will not say")


class _NoneCompiled:
    def cost_analysis(self):
        return None

    def memory_analysis(self):
        return None


@pytest.mark.parametrize("compiled", [_BrokenCompiled(), _NoneCompiled()])
def test_cost_analysis_degrades_to_absent_gauges(compiled):
    rep = obs_cost.analyze_compiled(compiled)
    assert rep.flops is None and rep.peak_memory_bytes is None
    reg = MetricsRegistry()
    obs_cost.record_cost(rep, registry=reg, index="deg", bucket="8")
    for gauge_name in (
        "raft_tpu_xla_flops",
        "raft_tpu_xla_bytes_accessed",
        "raft_tpu_peak_memory_bytes",
    ):
        assert reg.gauge(gauge_name).collect() == {}, (
            f"{gauge_name} published from a made-up number"
        )


def test_analyze_callable_failure_returns_none():
    def explodes(x):
        raise ValueError("cannot trace")

    assert obs_cost.analyze_callable(explodes, np.ones((4, 4))) is None
    reg = MetricsRegistry()
    obs_cost.record_cost(None, registry=reg, index="x")  # no-op, no raise
    assert reg.gauge("raft_tpu_xla_flops").collect() == {}


def test_analyze_callable_reports_real_numbers_on_cpu():
    rep = obs_cost.analyze_callable(
        lambda a: a @ a.T, np.ones((16, 16), np.float32)
    )
    assert rep is not None
    # the CPU client answers cost_analysis; whatever it reports must be
    # positive and land as gauges
    assert rep.flops and rep.flops > 0
    reg = MetricsRegistry()
    obs_cost.record_cost(rep, registry=reg, index="mm", bucket="16")
    assert reg.gauge("raft_tpu_xla_flops").value(
        index="mm", bucket="16") > 0


def test_analyze_callable_hoists_closed_over_arrays():
    """A served search closes over its index: the cost program must take
    those arrays as parameters, not embed them as constants."""
    big = jnp.ones((2048, 64), jnp.float32)
    rep = obs_cost.analyze_callable(
        lambda q: q @ big.T, np.ones((4, 64), np.float32)
    )
    assert rep is not None and rep.flops
    assert rep.argument_memory_bytes >= big.nbytes


def test_roofline_utilization_bounds():
    assert obs_cost.roofline_utilization(None, 1.0, 1.0) is None
    assert obs_cost.roofline_utilization(1e9, 1e6, None) is None
    u = obs_cost.roofline_utilization(1e9, 1e9, 1.0, kind="TPU v5 lite")
    assert u is not None and u > 0
    # published v5e peaks; no roofline off-TPU; an unknown TPU is an error
    assert obs_cost.device_peaks("TPU v5 lite") == (197e12, 819e9)
    assert obs_cost.roofline_utilization(1e9, 1e9, 1.0, kind="cpu") is None
    with pytest.raises(KeyError, match="TPU v9"):
        obs_cost.device_peaks("TPU v9")


def test_live_buffer_gauges_retire_collected_versions():
    rng = np.random.default_rng(29)
    x = rng.random((150, D), dtype=np.float32)
    reg_idx = serve.IndexRegistry()
    metrics = MetricsRegistry()
    old = serve.MutableIndex(brute_force.build(x))
    reg_idx.register("lb", old)
    reg_idx.swap("lb", serve.MutableIndex(brute_force.build(x)))

    live = obs_cost.refresh_live_buffer_gauges(reg_idx, metrics)
    gauge = metrics.gauge("raft_tpu_index_live_bytes")
    # both versions alive: the held v1 reference and the current v2
    assert set(live) == {"lb:v1", "lb:v2"}
    assert gauge.value(index="lb", version="1") > 0

    del old
    gc.collect()
    live = obs_cost.refresh_live_buffer_gauges(reg_idx, metrics)
    assert set(live) == {"lb:v2"}, "collected version still reported"
    assert ("index", "lb") not in [
        kv for key in gauge.collect() for kv in key if kv[1] == "1"
    ]
    assert gauge.value(index="lb", version="2") > 0


# ---------------------------------------------------------------------------
# health verdicts


def _probe(**kw):
    base = dict(warm=True, recompiles=0, queue_depth=0, max_batch=8)
    base.update(kw)
    return obs_health.IndexProbe(**base)


def test_health_verdict_transitions():
    assert obs_health.index_health(_probe())["status"] == obs_health.OK
    assert obs_health.index_health(
        _probe(warm=False))["status"] == obs_health.DEGRADED
    assert obs_health.index_health(
        _probe(recompiles=1))["status"] == obs_health.DEGRADED
    assert obs_health.index_health(
        _probe(recompiles=obs_health.COMPILE_STORM)
    )["status"] == obs_health.UNHEALTHY
    assert obs_health.index_health(
        _probe(queue_depth=8 * obs_health.QUEUE_DEGRADED_FACTOR + 1)
    )["status"] == obs_health.DEGRADED
    assert obs_health.index_health(
        _probe(queue_depth=8 * obs_health.QUEUE_UNHEALTHY_FACTOR + 1)
    )["status"] == obs_health.UNHEALTHY
    assert obs_health.index_health(
        _probe(recall_ewma=0.85, recall_threshold=0.9)
    )["status"] == obs_health.DEGRADED
    assert obs_health.index_health(
        _probe(recall_ewma=0.3, recall_threshold=0.9)
    )["status"] == obs_health.UNHEALTHY
    # worst-of folds: an UNHEALTHY check dominates a DEGRADED one
    rep = obs_health.index_health(
        _probe(warm=False, recompiles=obs_health.COMPILE_STORM)
    )
    assert rep["status"] == obs_health.UNHEALTHY
    assert rep["checks"]["warmup"]["status"] == obs_health.DEGRADED


def test_build_report_publishes_health_gauge():
    reg = MetricsRegistry()
    report = obs_health.build_report(
        {"a": _probe(), "b": _probe(recompiles=1)}, registry=reg
    )
    assert report["indexes"]["a"]["status"] == obs_health.OK
    assert report["indexes"]["b"]["status"] == obs_health.DEGRADED
    assert report["status"] in (obs_health.DEGRADED, obs_health.UNHEALTHY)
    g = reg.gauge("raft_tpu_health")
    assert g.value(index="a") == 0.0
    assert g.value(index="b") == 1.0
    assert g.value(index="overall") >= 1.0
    assert "memory" in report


def test_service_healthz_readyz_lifecycle():
    rng = np.random.default_rng(31)
    x = rng.random((150, D), dtype=np.float32)
    svc = serve.SearchService(k=5, max_batch=8, start=False)
    try:
        svc.add_index("hz", serve.MutableIndex(brute_force.build(x)))
        assert not svc.readyz()["ready"]  # not warmed yet
        rep = svc.healthz()
        assert rep["indexes"]["hz"]["status"] == obs_health.DEGRADED
        assert rep["indexes"]["hz"]["checks"]["warmup"]["status"] == (
            obs_health.DEGRADED
        )
        svc.warmup("hz")
        assert svc.readyz() == {"ready": True, "indexes": {"hz": True}}
        assert svc.healthz()["indexes"]["hz"]["status"] == obs_health.OK
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Prometheus export under concurrent hot-swap

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+( \d+)?$"
)


def _assert_well_formed(text):
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"


def test_prometheus_export_correct_under_concurrent_hot_swap():
    rng = np.random.default_rng(37)
    x = rng.random((200, D), dtype=np.float32)
    q = rng.random((8, D), dtype=np.float32)
    svc = serve.SearchService(k=5, max_batch=8, max_delay_ms=0.2)
    svc.add_index("cs", serve.MutableIndex(brute_force.build(x)),
                  warmup=True)
    stop = threading.Event()
    errors = []

    def swapper():
        try:
            while not stop.is_set():
                svc.swap("cs", serve.MutableIndex(brute_force.build(x)))
                time.sleep(0.002)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def searcher():
        try:
            i = 0
            while not stop.is_set():
                svc.search("cs", q[i % len(q)])
                i += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=swapper),
               threading.Thread(target=searcher)]
    for t in threads:
        t.start()
    try:
        prev_requests = 0.0
        for _ in range(10):
            text = svc.prometheus()
            _assert_well_formed(text)
            assert "raft_tpu_health" in text
            assert "raft_tpu_index_live_bytes" in text
            # counters must be monotone across scrapes even mid-swap
            vals = [
                float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith("raft_tpu_serve_requests_total")
                and 'index="cs"' in line
            ]
            if vals:
                assert vals[0] >= prev_requests
                prev_requests = vals[0]
            time.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        svc.stop()
    assert not errors, errors
    assert svc.stats("cs")["recompiles"] == 0  # same-shape swaps stay free
