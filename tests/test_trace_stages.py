"""The serving path's own timeline on the profiler clock.

A ``jax.profiler`` capture of a served index must hold the batcher's stage
ranges (``raft_tpu.serve.*``) on the threads that do the work, under static
names; a garbage collection must show as ``raft_tpu.host.gc`` and count in
``stats()["host"]``; and the service's exact stage sums must equal what
the batcher recorded.

Shape isolation: the served corpus here has dim 12 (test_serve 24,
test_obs 28, test_obs_quality 32, test_serve_pipeline 8, test_explain 20).
"""

import gc
import glob
import os
import re
import time
from collections import defaultdict

import jax
import numpy as np
import pytest

from raft_tpu import serve
from raft_tpu.core import trace as core_trace
from raft_tpu.neighbors import brute_force
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.metrics import ServingMetrics

DIM = 12

WORKER = {"serve.idle", "serve.coalesce", "serve.admit", "serve.pad",
          "serve.dispatch"}
COMPLETER = {"serve.device_wait", "serve.copy_out", "serve.resolve",
             "serve.record"}


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(23)
    x = rng.random((300, DIM), dtype=np.float32)
    q = rng.random((24, DIM), dtype=np.float32)
    return serve.MutableIndex(brute_force.build(x)), q


def _capture(tmp_path, body):
    """Run ``body`` inside a profiler capture; returns the program's host
    ranges as {line index: [(name, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    lines = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("raft_tpu."):
                    lines[(plane.name, i)].append(
                        (e.name[len("raft_tpu."):], e.start_ns,
                         e.start_ns + e.duration_ns))
    return lines


def _line_of(lines, stage):
    owners = {k for k, evs in lines.items()
              if any(n == stage for n, _, _ in evs)}
    assert len(owners) == 1, f"{stage} on {len(owners)} threads"
    return owners.pop()


def test_one_function_owns_the_prefix(tmp_path):
    def body():
        with core_trace.host_range("t.host"):
            with core_trace.trace_range("t.full"):
                pass

    lines = _capture(tmp_path, body)
    names = {n for v in lines.values() for n, _, _ in v}
    assert {"t.host", "t.full"} <= names


@pytest.mark.parametrize("depth", [1, 2])
def test_capture_holds_every_stage_range_on_its_thread(served, depth,
                                                        tmp_path):
    idx, q = served
    b = MicroBatcher(lambda queries: idx.search(queries, 5), DIM,
                     max_batch=8, max_delay_ms=1.0, pipeline_depth=depth,
                     metrics=ServingMetrics(), start=False)
    b.warmup()

    def body():
        b.start()
        time.sleep(0.05)  # the worker waits for its first request
        for _ in range(3):
            futs = [b.submit(q[i]) for i in range(len(q))]
            for f in futs:
                f.result(timeout=60)
        b.stop()

    lines = _capture(tmp_path, body)
    worker = WORKER | ({"serve.inflight_wait"} if depth > 1 else set())
    owner = {s: _line_of(lines, s) for s in worker | COMPLETER}
    assert len({owner[s] for s in worker}) == 1
    assert len({owner[s] for s in COMPLETER}) == 1
    # the serial path does every stage on its one thread; the pipeline
    # hands the device wait onwards to the completer
    same = owner["serve.dispatch"] == owner["serve.device_wait"]
    assert same == (depth == 1)
    # static names: one distinct name per stage, no per-batch arguments
    names = {n for evs in lines.values() for n, _, _ in evs
             if n.startswith("serve.")}
    assert all(re.fullmatch(r"serve\.[a-z_]+", n) for n in names), names
    evs = [e for v in lines.values() for e in v]
    first_dispatch = min(s for n, s, _ in evs if n == "serve.dispatch")
    last_resolve = max(e for n, _, e in evs if n == "serve.resolve")
    waits = [(s, e) for n, s, e in evs if n == "serve.device_wait"]
    assert waits and all(first_dispatch <= s and e <= last_resolve
                         for s, e in waits)


def test_gc_is_a_range_and_a_counter(served, tmp_path):
    idx, _ = served
    svc = serve.SearchService(k=5, max_batch=8)
    try:
        svc.add_index("gc12", idx)
        before = svc.stats("gc12")["host"]
        lines = _capture(tmp_path, gc.collect)
        after = svc.stats("gc12")["host"]
    finally:
        svc.stop()
    assert any(n == core_trace.GC_RANGE for v in lines.values()
               for n, _, _ in v)
    assert after["gc_count_by_gen"][2] > before["gc_count_by_gen"][2]
    assert after["gc_count"] > before["gc_count"]
    assert after["gc_pause_s"] > before["gc_pause_s"]


@pytest.mark.parametrize("depth", [1, 2])
def test_stage_sums_match_what_the_batcher_recorded(served, depth):
    idx, q = served
    metrics = ServingMetrics()
    seen = defaultdict(list)
    record_batch, record_stage = metrics.record_batch, metrics.record_stage

    def spy_batch(*args, stages=None, **kw):
        for s, vals in (stages or {}).items():
            seen[s].extend(float(v) for v in vals)
        return record_batch(*args, stages=stages, **kw)

    def spy_stage(stage, seconds):
        seen[stage].append(float(seconds))
        return record_stage(stage, seconds)

    metrics.record_batch, metrics.record_stage = spy_batch, spy_stage
    b = MicroBatcher(lambda queries: idx.search(queries, 5), DIM,
                     max_batch=8, max_delay_ms=1.0, pipeline_depth=depth,
                     metrics=metrics)
    try:
        futs = [b.submit(q[i]) for i in range(len(q))]
        for f in futs:
            f.result(timeout=60)
        b.flush()
    finally:
        b.stop()
    snap = metrics.snapshot()
    assert set(snap["stage_n"]) == set(seen)
    assert {"queue", "coalesce", "record"} <= set(seen)
    for s, vals in seen.items():
        assert snap["stage_n"][s] == len(vals)
        assert snap["stage_sum_s"][s] == pytest.approx(sum(vals), abs=1e-12)
    assert snap["stage_n"]["queue"] == snap["requests"] == len(q)
    assert snap["stage_n"]["record"] == snap["batches"]
    assert metrics.stage_totals() == snap["stage_sum_s"]
