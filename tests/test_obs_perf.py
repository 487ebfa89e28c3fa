"""Measured perf ledger (raft_tpu.obs.perf) + its serve integration.

Covers the ISSUE-12 acceptance surface:

- ledger accounting under pipelined dispatch (depth 2) with ragged
  traffic: per-key device-second totals reconcile exactly with
  ``ServingMetrics.stage_totals()["device"]`` (the ledger rides the same
  stamps), zero post-warmup recompiles with the ledger enabled, and the
  live ``kernel_path`` attribution flows from the neighbors routing code
  through metrics and the prometheus export;
- hotspot ranking by cumulative device seconds with pad-waste fraction
  and measured roofline utilization in (0, 1];
- the per-key EWMA regression detector: ``perf_regression`` fires
  exactly once per debounce window, auto-triggers one profiler capture,
  and lands inside one correlated incident (capture attached to the
  timeline);
- the hedge busy-union fix: a mirrored hedge pair's overlapping device
  windows merge into ``device_busy_s()`` once, not twice;
- the per-shard device-time skew probe on ``ShardedIndex``.
"""

import time

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.neighbors import brute_force
from raft_tpu.obs import events, health, incidents, perf, profiler
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.metrics import compile_count
from raft_tpu.serve.ragged import RaggedSpec
from raft_tpu.serve.service import SearchService
from raft_tpu.serve.shard import ShardedIndex

DIM = 16


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, DIM), dtype=np.float32)


# ---------------------------------------------------------------------------
# ledger unit surface


def test_ledger_accounting_and_hotspot_ranking():
    led = perf.PerfLedger(min_samples=10_000)  # detector disarmed
    for _ in range(10):
        led.record(index="a", backend="brute_force", bucket=8,
                   kernel_path="xla", version="1",
                   device_s=0.002, rows=6, padded_rows=8)
    for _ in range(3):
        led.record(index="b", backend="ivf_flat", bucket=4,
                   kernel_path="pallas", version="2",
                   device_s=0.001, rows=4, padded_rows=4)
    hs = led.top_hotspots()
    assert len(hs) == 2
    # ranked by cumulative device seconds
    assert hs[0]["index"] == "a" and hs[0]["dispatches"] == 10
    assert hs[0]["device_s"] == pytest.approx(0.02)
    # pad-waste-derived wasted-time fraction: 6 real rows of an 8-bucket
    assert hs[0]["wasted_frac"] == pytest.approx(0.25)
    assert hs[1]["wasted_frac"] == 0.0
    assert hs[1]["kernel_path"] == "pallas" and hs[1]["version"] == "2"
    tot = led.totals()
    assert tot["a"]["device_s"] == pytest.approx(0.02)
    assert tot["a"]["rows"] == 60 and tot["a"]["dispatches"] == 10
    snap = led.snapshot()
    assert snap["keys"] == 2 and snap["dispatches"] == 13
    assert snap["active_regressions"] == []


def test_ledger_measured_roofline(monkeypatch):
    # the peak table is keyed by device_kind: give the CPU test device
    # generous peaks so measured utilization lands strictly inside (0, 1]
    import jax

    from raft_tpu.obs import cost

    monkeypatch.setitem(
        cost.PEAKS, jax.devices()[0].device_kind, (1e18, 1e15)
    )
    led = perf.PerfLedger(min_samples=10_000)
    led.register_cost("a", 8, flops=1e6, bytes_accessed=1e5)
    for _ in range(4):
        led.record(index="a", backend="brute_force", bucket=8,
                   kernel_path="xla", version="1",
                   device_s=0.001, rows=8, padded_rows=8)
    (h,) = led.top_hotspots()
    # ledger-derived achieved rates: flops/bytes per measured device second
    assert h["flops_per_s"] == pytest.approx(4e6 / 0.004)
    assert h["bytes_per_s"] == pytest.approx(4e5 / 0.004)
    util = h["roofline_utilization"]
    assert util is not None and 0.0 < util <= 1.0


def test_ledger_env_gate(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_PERF_LEDGER", "0")
    assert not perf.enabled()
    data = _rows(64, 3)

    def fn(q):
        return brute_force.knn(data, q, 4)

    mb = MicroBatcher(fn, DIM, max_batch=4, start=False,
                      cost_accounting=False, pipeline_depth=1)
    assert mb._perf is None  # sampled once at construction
    mb.warmup()
    mb.submit(_rows(2, 4))
    mb.flush()
    mb.stop()
    assert perf.ledger_snapshot()["keys"] == 0


# ---------------------------------------------------------------------------
# serve integration: pipelined + ragged reconciliation, zero recompiles


def test_ledger_reconciles_pipelined_ragged_traffic():
    data = _rows(256, 0)
    svc = SearchService(k=4, max_batch=8, pipeline_depth=2,
                        ragged=RaggedSpec(k_max=8, filters=False))
    svc.add_index("t", brute_force.build(data), warmup=True)
    try:
        c0 = compile_count()
        q = _rows(40, 1)
        futs = [
            svc.submit("t", q[i : i + 2], k=int(1 + i % 5))
            for i in range(0, 40, 2)
        ]
        svc.flush("t")
        for f in futs:
            f.result(timeout=60)
        # the ledger must not cost the hot path a single recompile
        assert compile_count() - c0 == 0
        assert svc.stats("t")["recompiles"] == 0

        b = svc._batcher("t")
        led = perf.default_ledger()
        tot = led.totals()["t"]
        assert tot["dispatches"] > 0 and tot["rows"] == 40
        # per-key totals reconcile with the metrics device stage: both
        # ride the exact same perf_counter stamps
        assert tot["device_s"] == pytest.approx(
            b.metrics.stage_totals()["device"], abs=1e-9
        )
        # attribution: registry kind/version + the stamped kernel path
        (h,) = [x for x in led.top_hotspots() if x["index"] == "t"]
        assert h["backend"] == "brute_force" and h["version"] == "1"
        assert h["kernel_path"] == "xla"  # brute force has no pallas leg
        # live A/B tally in stats() and the kernel_path histogram label
        kp = svc.stats("t")["kernel_paths"]
        assert sum(kp.values()) == tot["dispatches"] and "xla" in kp
        assert 'kernel_path="xla"' in svc.prometheus()
        # exported through the registry provider too
        assert obs.snapshot()["perf"]["keys"] >= 1
        assert "raft_tpu_perf_device_seconds_total" in obs.to_prometheus()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# regression detector → capture → incident


def test_perf_regression_once_per_window_with_capture_and_incident(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("RAFT_TPU_PERF_CAPTURE_DIR", str(tmp_path))
    monkeypatch.setenv("RAFT_TPU_PERF_CAPTURE_S", "0.2")
    led = perf.PerfLedger(min_samples=4, debounce_s=60.0, regression_x=1.5)
    seen = []
    events.subscribe(
        lambda e: seen.append(e), kinds=frozenset({"perf_regression"})
    )

    def rec(device_s):
        led.record(index="t", backend="brute_force", bucket=8,
                   kernel_path="xla", version="1",
                   device_s=device_s, rows=8, padded_rows=8)

    for _ in range(8):
        rec(0.001)  # stable baseline
    for _ in range(30):
        rec(0.05)   # 50x slowdown: trips on every record once armed
    # debounced: exactly one event despite 30 tripped records
    assert len(seen) == 1
    ev = seen[0]
    assert ev.kind == "perf_regression"
    assert ev.reason == "perf_regression_t"
    assert ev.fields["ratio"] > 1.5
    assert ev.fields["kernel_path"] == "xla"
    # suppressed trips are counted on the key, never silently dropped
    (h,) = led.top_hotspots()
    assert h["regressions"] == 1
    # the debounce window reports as an active regression → DEGRADED
    hs = led.health_slice()
    assert hs["active_regressions"] == ["t/b8/xla"]
    assert health.perf_check(hs)["status"] == "DEGRADED"
    # one auto-triggered profiler capture, reason-linked to the event
    cap = profiler.last_capture()
    assert cap is not None
    assert cap["reason"] == "perf_regression_t"
    assert cap["duration_s"] == pytest.approx(0.2)
    # ... landing inside exactly one correlated incident, capture
    # attached to its timeline like a flight dump
    mgr = incidents.default_manager()
    incs = mgr.open_incidents() + mgr.closed_incidents()
    assert len(incs) == 1
    inc = incs[0].to_dict()
    assert inc["capture"] is not None
    assert inc["capture"]["path"] == cap["path"]
    assert any(
        t.get("kind") == "profile_capture"
        and t.get("path") == cap["path"]
        for t in inc["timeline"]
    )
    # the summary surface (snapshot provider) links the same artifact
    snap = incidents.incidents_snapshot()
    summaries = list(snap["open"]) + list(snap["recent_closed"])
    assert [s["capture"] for s in summaries] == [cap["path"]]


def test_perf_regression_fires_again_after_window():
    led = perf.PerfLedger(min_samples=2, debounce_s=0.2, regression_x=1.5)
    seen = []
    events.subscribe(
        lambda e: seen.append(e), kinds=frozenset({"perf_regression"})
    )

    def rec(device_s, n):
        for _ in range(n):
            led.record(index="t", backend="brute_force", bucket=4,
                       kernel_path="xla", version="1",
                       device_s=device_s, rows=4, padded_rows=4)

    rec(0.001, 6)
    rec(0.05, 10)
    assert len(seen) == 1
    time.sleep(0.25)  # past the debounce window
    rec(0.05, 5)
    assert len(seen) == 2


# ---------------------------------------------------------------------------
# hedge device-interval dedupe (satellite: device_busy_s under hedging)


class _MirrorHedger:
    """Stands in for HedgedDispatcher: runs the search once but reports
    the two mirrored members' (almost fully overlapping) device windows
    through the batcher's interval sink — the double-count scenario."""

    def __init__(self, fn, window_s=0.03):
        self.metrics = None
        self.on_interval = None
        self._fn = fn
        self.window_s = window_s
        self.windows = []

    def warm(self, *args):
        self._fn(*args)

    def dispatch(self, *args):
        t0 = time.perf_counter()
        out = self._fn(*args)
        time.sleep(self.window_s)
        t1 = time.perf_counter()
        sink = self.on_interval
        if sink is not None:
            # mirrored pair: same device window, reported twice
            sink(t0, t1)
            sink(t0 + self.window_s / 10.0, t1)
        self.windows.append((t0, t1))
        return out


def test_hedged_device_busy_stays_union_not_sum():
    data = _rows(64, 7)

    def fn(q):
        return brute_force.knn(data, q, 4)

    hedger = _MirrorHedger(fn)
    mb = MicroBatcher(fn, DIM, max_batch=4, start=False, pipeline_depth=2,
                      cost_accounting=False, hedger=hedger)
    # the batcher wired its union sink into the hedger at construction
    assert hedger.on_interval is not None
    mb.warmup()
    futs = [mb.submit(_rows(1, 10 + i)[0], priority=0) for i in range(3)]
    mb.flush()
    for f in futs:
        f.result(timeout=60)
    mb.stop()
    assert hedger.windows, "hedged dispatch never ran"
    union = sum(t1 - t0 for t0, t1 in hedger.windows)
    busy = mb.device_busy_s()
    # the overlapping mirrored windows must merge: busy ≈ one window per
    # dispatch, bounded well below the double-counted sum (2x union)
    assert busy == pytest.approx(union, rel=0.35)
    assert busy < 1.5 * union


# ---------------------------------------------------------------------------
# per-shard skew probe


def test_shard_skew_probe_publishes_gauges():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    x = _rows(512, 5)
    sh = ShardedIndex.from_index(brute_force.build(x))
    out = sh.measure_shard_skew(_rows(8, 6), k=4)
    assert len(out["per_shard_s"]) == sh.n_shards
    assert all(t > 0.0 for t in out["per_shard_s"])
    assert out["skew"] >= 1.0
    prom = obs.to_prometheus()
    assert "raft_tpu_shard_device_seconds" in prom
    assert "raft_tpu_shard_device_skew" in prom
