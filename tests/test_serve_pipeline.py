"""Pipelined dispatch (pipeline_depth > 1): the correctness contracts the
overlap must not cost.

Every future is delivered exactly once through ``stop(drain=True)`` with
batches still in flight; an exception — at dispatch OR at completion —
fails only its own batch while its neighbors complete; results are
bit-identical to the depth=1 serial path; the zero-recompile contract
holds at depth > 1; and the in-flight window never exceeds
``pipeline_depth`` (asserted through the ``inflight_peak`` metric and the
``raft_tpu_serve_inflight_batches`` gauge under real concurrency).

Device-independence: most tests drive the batcher with a *fake device* —
result objects exposing ``block_until_ready()`` (which
``jax.block_until_ready`` duly calls on non-Array leaves) and
``__array__`` — so in-flight overlap is deterministic on a CPU-only host.
The bit-identical and recompile tests use real indexes and real XLA.
"""

import threading
import time

import numpy as np
import pytest

from raft_tpu import obs, serve
from raft_tpu.neighbors import brute_force
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.metrics import ServingMetrics


DIM = 8


class _FakeResult:
    """A device-array stand-in: readiness gated on an Event (or a delay),
    materializing to a prebuilt numpy array."""

    def __init__(self, value: np.ndarray, gate: threading.Event = None,
                 delay_s: float = 0.0, fail: Exception = None):
        self._value = value
        self._gate = gate
        self._delay_s = delay_s
        self._fail = fail

    def block_until_ready(self):
        if self._gate is not None:
            assert self._gate.wait(timeout=30), "fake device never released"
        if self._delay_s:
            time.sleep(self._delay_s)
        if self._fail is not None:
            raise self._fail
        return self

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a if dtype is None else a.astype(dtype)


def _fake_search(gate=None, delay_s=0.0, fail_on=None, fail_stage="dispatch",
                 k=3, log=None):
    """search_fn returning fake device results; row 0's first feature acts
    as the batch marker.  ``fail_on`` (a marker value) raises at the given
    stage: "dispatch" (inside search_fn, synchronously) or "device"
    (inside block_until_ready, on the completion thread)."""

    def search_fn(batch):
        batch = np.asarray(batch)
        marker = float(batch[0, 0])
        if log is not None:
            log.append(marker)
        if fail_on is not None and marker == fail_on and \
                fail_stage == "dispatch":
            raise RuntimeError(f"dispatch failure for marker {marker}")
        # ids encode (marker, row) so tests can check batch->result routing
        dist = batch[:, :k].copy()
        ids = np.tile(np.arange(batch.shape[0])[:, None], (1, k)) \
            + int(marker) * 1000
        fail = None
        if fail_on is not None and marker == fail_on and \
                fail_stage == "device":
            fail = RuntimeError(f"device failure for marker {marker}")
        return (
            _FakeResult(dist, gate=gate, delay_s=delay_s, fail=fail),
            _FakeResult(ids, gate=gate, delay_s=delay_s),
        )

    return search_fn


def _full_batch(marker: float, rows: int = 4) -> np.ndarray:
    """A request that fills max_batch=4 exactly — one request, one batch,
    so the marker in row 0 identifies the whole dispatched batch."""
    out = np.zeros((rows, DIM), np.float32)
    out[:, 0] = marker
    return out


# ---------------------------------------------------------------------------
# stop(drain=True) with batches still in flight


def test_stop_drain_delivers_every_future_exactly_once():
    gate = threading.Event()
    b = MicroBatcher(
        _fake_search(gate=gate), DIM, max_batch=4, max_delay_ms=0.1,
        pipeline_depth=2, metrics=ServingMetrics(name="drain"),
    )
    futs = [b.submit(_full_batch(m)) for m in (1.0, 2.0, 3.0, 4.0)]
    # let the pipeline fill its window (2 in flight, 2 queued or stalled)
    deadline = time.perf_counter() + 10
    while b.inflight < 2 and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert b.inflight == 2
    # release the device and stop WHILE batches are in flight
    stopper = threading.Thread(target=b.stop, kwargs={"drain": True})
    stopper.start()
    gate.set()
    stopper.join(timeout=30)
    assert not stopper.is_alive(), "stop(drain=True) hung"
    for m, fut in zip((1, 2, 3, 4), futs):
        dist, ids = fut.result(timeout=0)  # already resolved, exactly once
        assert ids[0, 0] == m * 1000
        np.testing.assert_array_equal(dist[:, 0], np.full(4, float(m)))
    assert b.inflight == 0


def test_stop_no_drain_fails_pending_but_completes_inflight():
    gate = threading.Event()
    b = MicroBatcher(
        _fake_search(gate=gate), DIM, max_batch=4, max_delay_ms=0.1,
        pipeline_depth=2, metrics=ServingMetrics(name="nodrain"),
    )
    futs = [b.submit(_full_batch(m)) for m in (1.0, 2.0, 3.0, 4.0)]
    deadline = time.perf_counter() + 10
    while b.inflight < 2 and time.perf_counter() < deadline:
        time.sleep(0.005)
    stopper = threading.Thread(target=b.stop, kwargs={"drain": False})
    stopper.start()
    time.sleep(0.05)
    gate.set()
    stopper.join(timeout=30)
    assert not stopper.is_alive()
    resolved, failed = 0, 0
    for fut in futs:
        try:
            fut.result(timeout=30)
            resolved += 1
        except RuntimeError:
            failed += 1
    # the two in-flight batches were dispatched before the stop and must
    # deliver; anything still queued fails fast
    assert resolved >= 2 and resolved + failed == 4


# ---------------------------------------------------------------------------
# exception isolation: batch N fails, N+1 completes


@pytest.mark.parametrize("fail_stage", ["dispatch", "device"])
def test_exception_fails_only_its_own_batch(fail_stage):
    b = MicroBatcher(
        _fake_search(fail_on=2.0, fail_stage=fail_stage), DIM,
        max_batch=4, max_delay_ms=0.1, pipeline_depth=2,
        metrics=ServingMetrics(name="isolate"), start=False,
    )
    futs = [b.submit(_full_batch(m)) for m in (1.0, 2.0, 3.0)]
    b.flush()
    d1, i1 = futs[0].result(timeout=30)
    assert i1[0, 0] == 1000
    with pytest.raises(RuntimeError, match="marker 2"):
        futs[1].result(timeout=30)
    d3, i3 = futs[2].result(timeout=30)  # N+1 completes despite N failing
    assert i3[0, 0] == 3000
    b.stop()


# ---------------------------------------------------------------------------
# bit-identical results and zero recompiles at depth > 1 (real XLA)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = rng.random((200, DIM), dtype=np.float32)
    q = rng.random((17, DIM), dtype=np.float32)
    return x, q


def test_results_bit_identical_to_depth1(corpus):
    x, q = corpus
    idx = serve.MutableIndex(brute_force.build(x))
    results = {}
    for depth in (1, 2):
        b = MicroBatcher(
            lambda queries: idx.search(queries, 5), DIM,
            min_bucket=1, max_batch=8, start=False, pipeline_depth=depth,
            metrics=ServingMetrics(name=f"bit{depth}"),
        )
        futs = [b.submit(q[i]) for i in range(len(q))]
        b.flush()
        results[depth] = [f.result(timeout=60) for f in futs]
        b.stop()
    for (d1, i1), (d2, i2) in zip(results[1], results[2]):
        assert d1.dtype == d2.dtype and i1.dtype == i2.dtype
        np.testing.assert_array_equal(i1, i2)
        # bit-for-bit, not approx: same executable, same padded input
        assert d1.tobytes() == d2.tobytes()


def test_zero_recompiles_at_depth2(corpus):
    x, q = corpus
    svc = serve.SearchService(
        k=5, min_bucket=1, max_batch=8, pipeline_depth=2
    )
    try:
        svc.add_index("zr2", serve.MutableIndex(brute_force.build(x)),
                      warmup=True)
        for i in range(20):
            d, ids = svc.search("zr2", q[i % len(q)])
            assert ids.shape == (5,)
        st = svc.stats("zr2")
        assert st["requests"] == 20
        assert st["pipeline_depth"] == 2
        assert st["recompiles"] == 0, (
            f"pipelined hot path recompiled {st['recompiles']}x after warmup"
        )
        # healthz folds the window invariant into the pipeline check
        hz = svc.healthz()
        pipe = hz["indexes"]["zr2"]["checks"]["pipeline"]
        assert pipe["status"] == "OK" and "depth 2" in pipe["detail"]
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# the window invariant: in-flight never exceeds pipeline_depth


@pytest.mark.parametrize("depth", [2, 3])
def test_inflight_never_exceeds_pipeline_depth(depth):
    metrics = ServingMetrics(name=f"window{depth}")
    b = MicroBatcher(
        _fake_search(delay_s=0.01), DIM, max_batch=4, max_delay_ms=0.1,
        pipeline_depth=depth, metrics=metrics,
    )
    n_batches = 12
    samples = []
    stop_sampling = threading.Event()

    def sampler():
        while not stop_sampling.is_set():
            samples.append(b.inflight)
            time.sleep(0.001)

    t = threading.Thread(target=sampler)
    t.start()
    futs = [b.submit(_full_batch(float(m))) for m in range(1, n_batches + 1)]
    for f in futs:
        f.result(timeout=60)
    stop_sampling.set()
    t.join()
    snap = metrics.snapshot()
    b.stop()
    assert snap["pipeline_depth"] == depth
    assert 0 < snap["inflight_peak"] <= depth, (
        f"window invariant broken: peak {snap['inflight_peak']} > {depth}"
    )
    assert max(samples, default=0) <= depth
    # the gauge a dashboard scrapes must agree with the snapshot's view
    g = obs.default_registry().gauge("raft_tpu_serve_inflight_batches")
    assert g.value(index=f"window{depth}") <= depth


# ---------------------------------------------------------------------------
# flush() routes through the pipeline


def test_flush_through_pipeline_preserves_order_and_blocks():
    b = MicroBatcher(
        _fake_search(delay_s=0.02), DIM, max_batch=4, max_delay_ms=0.1,
        pipeline_depth=2, metrics=ServingMetrics(name="flush"), start=False,
    )
    futs = [b.submit(_full_batch(float(m))) for m in (1.0, 2.0, 3.0)]
    assert b.flush() == 3
    # flush returns only after every dispatched batch resolved its future
    for m, fut in zip((1, 2, 3), futs):
        assert fut.done(), "flush returned with unresolved futures"
        _, ids = fut.result(timeout=0)
        assert ids[0, 0] == m * 1000
    m = b.metrics.snapshot()
    assert m["batches"] == 3 and m["requests"] == 3
    b.stop()


def test_pipelined_batches_report_spans_and_stage_metrics():
    b = MicroBatcher(
        _fake_search(delay_s=0.005), DIM, max_batch=4, max_delay_ms=0.1,
        pipeline_depth=2, metrics=ServingMetrics(name="spans"), start=False,
    )
    futs = [b.submit(_full_batch(float(m))) for m in (1.0, 2.0)]
    b.flush()
    for f in futs:
        f.result(timeout=30)
    snap = b.metrics.snapshot()
    b.stop()
    stages = snap["stages"]
    # the pipelined path records every stage, including the new
    # inflight_wait, into the same reservoirs the serial path uses
    for stage in ("queue", "pad", "inflight_wait", "dispatch", "device"):
        assert stage in stages, f"stage {stage!r} missing from metrics"
    recorded = [
        sp for sp in obs.spans.recent_spans() if sp.get("name") == "serve.batch"
    ]
    assert recorded, "pipelined dispatch recorded no serve.batch spans"
    assert any("inflight_wait" in sp.get("stages_ms", {}) for sp in recorded)


# ---------------------------------------------------------------------------
# hedge device-interval dedupe: device_busy_s under hedging


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
    rng = np.random.default_rng(7)
    data = rng.standard_normal((64, DIM), dtype=np.float32)

    def fn(q):
        return brute_force.knn(data, q, 4)

    hedger = _MirrorHedger(fn)
    mb = MicroBatcher(fn, DIM, max_batch=4, start=False, pipeline_depth=2,
                      hedger=hedger)
    # the batcher wired its union sink into the hedger at construction
    assert hedger.on_interval is not None
    mb.warmup()
    futs = [
        mb.submit(rng.standard_normal(DIM, dtype=np.float32), priority=0)
        for _ in range(3)
    ]
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
