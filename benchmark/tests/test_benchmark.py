"""Tests of the benchmark's own code, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They cover the trace reduction (on made-up events and on a trace recorded
here), the work counts, loading every configuration, traffic mix and
metric of BENCHMARK.json by name, the arrivals, and the decision of
``correct``: a whole run (without the harness's look for a chip) comes
out correct; the bf16 control, two faults planted in the served path and
fewer probes come out not correct.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import arrivals, check, reference, trace, work  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

TINY = {
    "deep1m-ivfpq": {"n": 6000, "d": 16, "queries": 300,
                     "build": {"n_lists": 12, "pq_dim": 16, "kmeans_n_iters": 4},
                     "search": {"n_probes": 6}, "recall_miss": 0.2},
    "sift1m-ivfflat": {"n": 6000, "d": 16, "queries": 300,
                       "build": {"n_lists": 12, "kmeans_n_iters": 4},
                       "search": {"n_probes": 6}, "recall_miss": 0.02},
}
TINY_TRAFFIC = {
    "bulk": {"rows_per_request": 100, "max_batch": 128, "min_bucket": 128},
    "online": {"rate": 200, "max_batch": 8},
}


def tiny_cell(workload: str):
    """(config, traffic, e2e specs) of ``workload`` cut to a CPU size."""
    cell, cfg, traffic, specs = bench_run.cell_inputs(BENCH, workload, False)
    t = TINY[cfg["name"]]
    cfg = json.loads(json.dumps(cfg))
    cfg.update(n=t["n"], d=t["d"], queries=t["queries"])
    cfg["index"]["build"].update(t["build"])
    cfg["index"]["search"].update(t["search"])
    # the limit at this size: tiny DEEP reads 0.137 at any probe count,
    # tiny SIFT 0.0, and 0.037 at one probe (my CPU runs)
    cfg["correct"]["recall_miss"] = t["recall_miss"]
    traffic = dict(traffic, **TINY_TRAFFIC[cell["traffic"]])
    return cfg, traffic, specs


def run_tiny(workload: str, seed: int = 3, seconds: float = 1.0):
    cfg, traffic, specs = tiny_cell(workload)
    return bench_run.run_cell(cfg, traffic, 1, seed, seconds, False, specs,
                              require_tpu=False)


# -- trace reduction ---------------------------------------------------------

def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


def test_reduce_busy_idle_ops_and_named_gaps():
    ms = 1e6
    device = {"/device:TPU:0": [
        ("fusion.1", 10 * ms, 20 * ms),        # 10-30
        ("_scan_kernel", 20 * ms, 20 * ms),    # 20-40, overlaps fusion.1
        ("fusion.1", 60 * ms, 10 * ms),        # 60-70
        ("early", 0, 5 * ms),                  # before the window
    ]}
    host = [
        ("bench.window", 5 * ms, 95 * ms),     # window 5-100
        ("raft_tpu.serve.batch", 40 * ms, 25 * ms),   # covers gap 40-60
        ("bench.request", 0, 100 * ms),        # outer, loses to the batch
    ]
    r = trace.reduce(device, host, trace.window_of(host))
    assert r["window_s"] == pytest.approx(0.095)
    assert r["busy_s"] == pytest.approx(0.040)          # 10-40 and 60-70
    assert r["idle_share"] == pytest.approx(1 - 40 / 95)
    assert trace.kernel_s(r, r"_scan(_qm)?_kernel") == pytest.approx(0.020)
    assert dict(r["device_ops"])["fusion.1"] == pytest.approx(0.030)
    gaps = dict(r["idle_gaps"])
    assert gaps["raft_tpu.serve.batch"] == pytest.approx(0.020)   # 40-60
    assert gaps["bench.request"] == pytest.approx(0.005 + 0.030)  # 5-10, 70-100


def test_reduce_averages_over_chips():
    device = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]}
    r = trace.reduce(device, [], (0, 100))
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(75e-9)


def test_recorded_cpu_trace_loads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.request"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    device, host = trace.load(trace.latest_xplane(str(tmp_path)))
    assert device == {}  # the CPU has no TPU device plane
    names = {h[0] for h in host}
    assert {trace.WINDOW_SPAN, "bench.request"} <= names
    lo, hi = trace.window_of(host)
    r = trace.reduce(device, host, (lo, hi))
    assert r["busy_s"] == 0 and r["idle_share"] == pytest.approx(1.0)


# -- work counts -------------------------------------------------------------

def test_scan_work_counts_from_shapes():
    ops, nbytes = work.scan_work(2, 1000, n_rows=1_000_000, n_lists=1000,
                                 n_probes=10, width=128, elem_bytes=4)
    assert ops == pytest.approx(2 * 1000 * 10 * 1000 * 2 * 128)
    lists = 1000 * (1 - (1 - 10 / 1000) ** 1000)
    assert nbytes == pytest.approx(2 * lists * 1000 * 128 * 4)
    # one query touches exactly its probed lists
    _, one = work.scan_work(1, 1, n_rows=1000, n_lists=10, n_probes=2,
                            width=4, elem_bytes=2)
    assert one == pytest.approx(2 * 100 * 4 * 2)


def test_roofline_share_names_its_bound():
    share, bound = work.roofline_share(1e9, 1e9, 1.0, 1e12, 1e10)
    assert bound == "memory" and share == pytest.approx(10.0)
    share, bound = work.roofline_share(1e12, 1e6, 2.0, 1e12, 1e10)
    assert bound == "compute" and share == pytest.approx(50.0)


# -- loading by name ---------------------------------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace_on", [False, True])
def test_cells_load_by_name(workload, trace_on):
    cell, cfg, traffic, specs = bench_run.cell_inputs(BENCH, workload, trace_on)
    assert cfg["name"] == cell["config"]
    assert os.path.exists(os.path.join(bench_run.HERE, "algos",
                                       cfg["index"]["kind"] + ".py"))
    assert traffic["loop"] in ("open", "closed")
    for spec in specs:
        mod = bench_run.load_module(
            os.path.join(bench_run.HERE, "metrics", spec["name"] + ".py"), "m")
        assert callable(mod.read)
    if not trace_on:
        names = {s["name"] for s in specs}
        assert "setup_s" in names and len(names) >= 2


def test_fixed_arrivals_same_set_for_every_seed():
    a = arrivals.fixed_poisson_arrivals(500, 4, 1)
    b = arrivals.fixed_poisson_arrivals(500, 4, 2**33 + 5)
    assert len(a) == len(b) == 2000
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    assert a[-1] == pytest.approx(4.0, rel=0.05)


# -- correct -----------------------------------------------------------------

def test_bad_rows_and_recall():
    ids = np.array([[1, 2, 3], [4, 4, 5], [6, 7, -1]])
    d = np.array([[1.0, 2.0, 3.0], [1.0, 2.0, 3.0], [1.0, 2.0, 3.0]])
    assert check.bad_rows(d, ids, 100, "sqeuclidean") == 2
    assert check.bad_rows(d[:1, ::-1], ids[:1], 100, "sqeuclidean") == 1
    assert check.bad_rows(d[:1, ::-1], ids[:1], 100, "inner_product") == 0
    truth = np.array([[1, 2, 9], [4, 5, 6]])
    assert check.recall(np.array([[1, 2, 3]]), np.array([0]), truth) == \
        pytest.approx(2 / 3)


def test_base_is_the_configurations_and_queries_the_seeds():
    from benchmark.lib import data

    cfg, _, _ = tiny_cell("deep1m-ivfpq.bulk")
    b1, q1 = data.make(cfg, 1)
    b2, q2 = data.make(cfg, 2**33 + 1)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert not np.allclose(np.asarray(q1), np.asarray(q2))
    assert np.allclose(np.linalg.norm(np.asarray(b1), axis=1), 1, atol=1e-5)
    assert np.allclose(np.linalg.norm(np.asarray(q1), axis=1), 1, atol=1e-5)
    sift, _, _ = tiny_cell("sift1m-ivfflat.bulk")
    assert np.linalg.norm(np.asarray(data.make(sift, 1)[0]), axis=1).min() > 2


@pytest.mark.parametrize("workload", ["deep1m-ivfpq.bulk",
                                      "sift1m-ivfflat.bulk"])
def test_control_in_bf16_is_not_correct(workload):
    """The reference in bfloat16 put in the program's place comes out not
    correct, by dist_err; in f32 it comes out correct."""
    from benchmark import control

    cfg, traffic, _ = tiny_cell(workload)
    out = control.control(cfg, traffic, 11)
    assert not out["correct"]
    assert out["checks"]["dist_err"]["value"] > cfg["correct"]["dist_err"]
    assert control.control(cfg, traffic, 11, precision="highest")["correct"]


@pytest.mark.parametrize("workload", ["deep1m-ivfpq.bulk",
                                      "deep1m-ivfpq.online",
                                      "sift1m-ivfflat.bulk"])
def test_tiny_run_is_correct(workload):
    result, checks = run_tiny(workload)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    names = set(result["metrics"])
    assert "setup_s" in names and len(names) == 2
    assert set(checks) == {"unanswered", "bad_rows", "dist_err",
                           "recall_miss", "window_compiles"}
    assert list(result)[-1] == "checks"


def test_tiny_traced_run_reads_no_device_metric_off_tpu():
    """A traced run on the CPU ends with a result line; with no TPU plane
    in the trace every device reader stays silent rather than read 0."""
    cfg, traffic, _ = tiny_cell("deep1m-ivfpq.online")
    specs = bench_run.cell_inputs(BENCH, "deep1m-ivfpq.online", True)[3]
    result, _ = bench_run.run_cell(cfg, traffic, 1, 7, 1.0, True, specs,
                                   require_tpu=False)
    assert result["correct"]
    assert "device.idle_share.online" not in result["metrics"]
    assert "batcher.rows_per_dispatch.online" in result["metrics"]
    assert result["device"]["busy_s"] == 0 and "breakdown" in result


def _alter(fault):
    """Wrap MutableIndex.search so the served answers carry ``fault``."""
    import jax.numpy as jnp

    from raft_tpu.serve import mutation

    orig = mutation.MutableIndex.search

    def search(self, queries, k, **kw):
        d, i = orig(self, queries, k, **kw)
        if fault == "answer":      # a wrong id where the answer is made
            i = i.at[:, 0].set((i[:, 0] + 1) % self.size)
        elif fault == "half":      # half of the batch left out
            h = i.shape[0] // 2
            i = i.at[h:].set(-1)
            d = d.at[h:].set(jnp.inf)
        return d, i

    return orig, search


@pytest.mark.parametrize("fault", ["answer", "half"])
def test_faults_in_the_served_path_are_not_correct(fault, monkeypatch):
    from raft_tpu.serve import mutation

    _, search = _alter(fault)
    monkeypatch.setattr(mutation.MutableIndex, "search", search)
    result, checks = run_tiny("deep1m-ivfpq.bulk", seed=5)
    assert not result["correct"]
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert ("dist_err" if fault == "answer" else "bad_rows") in failed


def test_fewer_probes_are_not_correct():
    """Every returned distance still matches its id, but the ids miss the
    exact answer: recall_miss alone catches it."""
    from benchmark import control

    cfg, traffic, specs = tiny_cell("sift1m-ivfflat.bulk")
    cfg["index"]["search"]["n_probes"] = 4
    cfg = control.faulty(cfg, "quarter_probes")
    result, checks = bench_run.run_cell(cfg, traffic, 1, 5, 1.0, False,
                                        specs, require_tpu=False)
    assert not result["correct"]
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert failed == {"recall_miss"}
