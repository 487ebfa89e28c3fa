#!/usr/bin/env python
"""Headline benchmark — prints ONE JSON line to stdout.

Headline (BASELINE config #4, the north star): IVF-PQ search QPS at
recall>=0.95 on a DEEP-shaped synthetic workload (500k x 96 float32 on
the accelerator — RAFT_TPU_BENCH_N overrides — clustered like real
embedding data, the reference's make_blobs test recipe; 10k queries,
k=10).  The operating point is found by sweeping
n_probes (with exact refinement, fused into the search program) until
recall >= 0.95 vs exact ground truth, then QPS is measured at that
point.  ``vs_baseline`` is the speedup over exact tiled brute-force kNN
on the same hardware at recall=1.0 — the compression/indexing win the
reference's IVF-PQ exists to deliver
(ref: cpp/include/raft/neighbors/detail/ivf_pq_search.cuh:588).
Queries run as one large batch.

The headline leg runs in this process on the TPU and exits non-zero when
JAX finds none: a measurement without the chip is not a headline.  The
explicit ``--run-leg cpu`` leg measures the frozen CPU workload.
"""

import json
import os
import sys
import time

#: FROZEN CPU workload (since round 3; do not change). Cross-round
#: comparability of BENCH_r*.json depends on the CPU leg measuring the
#: exact same problem every round — only the accelerator workload may scale
#: (RAFT_TPU_BENCH_N). Matches BENCH_r03.json: n=24k rows, d=96, 400
#: queries, k=10, sqeuclidean, seed 0.
_CPU_FALLBACK = {"n": 24_000, "d": 96, "n_q": 400, "k": 10}
#: the accelerator leg's wall-clock budget for its operating-point sweep
_ACCEL_DEADLINE_S = 1500


def _emit(payload: dict) -> None:
    """Print the one BENCH JSON line and drop the schema-versioned record
    artifact next to it (``RAFT_TPU_BENCH_RECORD`` overrides the path,
    ``-`` suppresses).  The record write is best-effort — the printed line
    is the contract, the artifact is what ``bench.py compare`` diffs."""
    print(json.dumps(payload))
    try:
        from raft_tpu.bench.export import write_bench_record

        path = write_bench_record(payload)
        if path:
            print(f"bench record written to {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — never fail the bench line
        print(f"bench record not written: {e}", file=sys.stderr)


def poisson_arrivals(rate_qps: float, n: int, seed: int = 0):
    """Open-loop Poisson arrival offsets (seconds from stream start).

    Cumulative sum of exponential inter-arrival gaps at ``rate_qps``.
    Reusable by any open-loop leg: unlike closed-loop clients, the
    arrival process does not slow down when the server does — which is
    exactly what makes queue growth (and admission control) observable.
    Latency is measured from the *scheduled* arrival, not the actual
    submit, so coordinated omission cannot flatter the tail.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_qps, size=n))


def timeit(fn, *args, warmup=2, iters=5):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    if "--run-leg" in sys.argv:
        idx = sys.argv.index("--run-leg")
        if idx + 1 >= len(sys.argv):
            print("--run-leg requires a value: accel | cpu", file=sys.stderr)
            sys.exit(2)
        run_leg(sys.argv[idx + 1])
        return
    if "compare" in sys.argv[1:]:
        from raft_tpu.bench.export import compare_main

        idx = sys.argv.index("compare")
        sys.exit(compare_main(sys.argv[idx + 1:]))
    if "serve" in sys.argv[1:]:
        run_serve_leg()
        return
    if "ragged" in sys.argv[1:]:
        run_ragged_leg()
        return
    if "overload" in sys.argv[1:]:
        run_overload_leg()
        return
    if "shard" in sys.argv[1:]:
        run_shard_leg()
        return
    if "shard_cagra" in sys.argv[1:]:
        run_shard_cagra_leg()
        return
    if "build" in sys.argv[1:]:
        run_build_leg()
        return
    if "compact" in sys.argv[1:]:
        run_compact_leg()
        return
    if "obs" in sys.argv[1:]:
        run_obs_leg()
        return
    if "paged" in sys.argv[1:]:
        run_paged_leg()
        return
    if "flight" in sys.argv[1:]:
        run_flight_leg()
        return
    if "slo" in sys.argv[1:]:
        run_slo_leg()
        return
    if "explain" in sys.argv[1:]:
        run_explain_leg()
        return
    if "gateway" in sys.argv[1:]:
        run_gateway_leg()
        return
    if "autotune" in sys.argv[1:]:
        run_autotune_leg()
        return
    if "deep" in sys.argv[1:]:
        run_deep_leg()
        return
    if "kernels" in sys.argv[1:]:
        run_kernels_leg()
        return
    if "perf" in sys.argv[1:]:
        run_perf_leg()
        return
    if "analyze" in sys.argv[1:]:
        run_analyze_leg()
        return
    run_leg("accel")


def run_leg(leg: str) -> None:
    import jax

    if leg == "cpu":
        jax.config.update("jax_platforms", "cpu")
        platform = "cpu"
    else:
        platform = jax.devices()[0].platform
        if platform != "tpu":
            sys.exit(f"bench.py: the headline needs a TPU, JAX found "
                     f"{platform!r} (--run-leg cpu measures the CPU leg)")

    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.core.resources import Resources
    from raft_tpu.neighbors import brute_force, ivf_pq
    from raft_tpu.neighbors.refine import refine as refine_fn

    on_accel = platform != "cpu"
    # baseline sweeps must measure the XLA schedules: the default (auto)
    # gate would route the chip's sweeps through Pallas and turn the A/B
    # below into pallas-vs-pallas
    os.environ["RAFT_TPU_PALLAS"] = "0"
    # DEEP-shaped workload on the accelerator — n large enough that the
    # index's sublinear scan visibly beats exact brute force (VERDICT r2:
    # "the headline workload must grow until that win is visible"); reduced
    # on the CPU leg so the line is still produced in bounded time.
    if on_accel:
        n = int(os.environ.get("RAFT_TPU_BENCH_N", 500_000))
        d, n_q, k = 96, 10_000, 10
    else:
        # the FROZEN CPU workload (see _CPU_FALLBACK) — no env
        # override, no re-tuning: the one job of this leg is to measure
        # the same problem in every round
        n, d, n_q, k = (_CPU_FALLBACK[x] for x in ("n", "d", "n_q", "k"))
    # hard wall-clock budget: emit the best-so-far operating point rather
    # than let a cold-compile sweep run into the caller's time cap; the CPU
    # leg keeps its own (shorter) budget
    deadline_env = (
        "RAFT_TPU_BENCH_DEADLINE_S" if on_accel else "RAFT_TPU_BENCH_CPU_DEADLINE_S"
    )
    deadline = time.monotonic() + float(
        os.environ.get(deadline_env, _ACCEL_DEADLINE_S if on_accel else 600)
    )

    # Clustered synthetic data (mixture of gaussians): real ANN corpora
    # (DEEP/SIFT embeddings) are clustered, and the reference's tests build
    # on make_blobs for the same reason.  iid gaussian data has no structure
    # an IVF index can exploit and benchmarks the pathological worst case.
    rng = np.random.default_rng(0)
    n_blobs = 1024
    blob_centers = rng.standard_normal((n_blobs, d)).astype(np.float32)
    blob_std = 0.35
    asg = rng.integers(0, n_blobs, n)
    dataset = jnp.asarray(
        blob_centers[asg] + rng.standard_normal((n, d)).astype(np.float32) * blob_std
    )
    qasg = rng.integers(0, n_blobs, n_q)
    queries = jnp.asarray(
        blob_centers[qasg] + rng.standard_normal((n_q, d)).astype(np.float32) * blob_std
    )
    res = Resources(workspace_limit_bytes=1 << 30)

    # --- exact ground truth + brute-force baseline timing
    def exact(q):
        return brute_force.knn(dataset, q, k, metric="sqeuclidean", res=res)

    gt_d, gt_i = exact(queries)
    gt_ids = np.asarray(gt_i)
    t_exact = timeit(exact, queries)

    # --- IVF-PQ build (n_lists tracks n so probed rows stay ~constant as
    # the workload grows — the reference's ~n/250 rule of thumb)
    params = ivf_pq.IndexParams(
        n_lists=max(1024, n // 250) if on_accel else max(256, n // 64),
        metric="sqeuclidean",
        pq_dim=d // 2,
        pq_bits=8,
        kmeans_n_iters=10,
        kmeans_trainset_fraction=min(0.5, 200_000 / n),
    )
    t0 = time.perf_counter()
    index = ivf_pq.build(params, dataset, res=res)
    build_s = time.perf_counter() - t0

    # --- find the operating point: smallest n_probes with recall >= 0.95
    # (candidates k*4 then exact refine, the reference's standard recipe).
    # NOT wrapped in an outer jit: that would close over the index arrays
    # and bake them in as XLA constants (compile-time blowup); search and
    # refine are each jitted internally, and two dispatches amortize fine
    # over a 10k-query batch.
    def make_search(n_probes, strategy="query_major"):
        sp = ivf_pq.SearchParams(
            n_probes=n_probes, lut_dtype="bfloat16", strategy=strategy
        )

        def fn(q):
            cd, ci = ivf_pq.search(sp, index, q, k * 4, res=res)
            return refine_fn(dataset, q, ci, k, metric="sqeuclidean", res=res)

        return fn

    chosen = None
    # ladder ends at probe-all so the recall target is always reachable
    # (starts at 2: the r4 on-chip run hit recall 0.992 at the then-lowest
    # rung of 4, leaving headline QPS on the table)
    for n_probes in (2, 3, 4, 6, 8, 16, 32, 64, 128, 256, params.n_lists):
        if n_probes > params.n_lists:
            break
        fn = make_search(n_probes)
        _, ids = fn(queries)
        from raft_tpu.stats import recall_at_k

        hits = recall_at_k(np.asarray(ids), gt_ids)
        if hits >= 0.95:
            chosen = (n_probes, float(hits), fn)
            break
        chosen = (n_probes, float(hits), fn)  # keep best-so-far operating point
        if time.monotonic() > deadline:
            print(f"deadline hit at n_probes={n_probes}", file=sys.stderr)
            break

    n_probes, recall, fn = chosen
    t_ours = timeit(fn, queries)
    strategy = "query_major"
    # A/B the probe-major scan schedule at the chosen operating point and
    # keep whichever measures faster (results are id-identical — verified
    # by TestProbeMajorStrategy — so recall carries over). Requires 240 s
    # of slack so a cold compile here stays inside the deadline.
    if time.monotonic() < deadline - 240:
        try:
            t_pm = timeit(make_search(n_probes, "probe_major"), queries)
            if t_pm < t_ours:
                t_ours, strategy = t_pm, "probe_major"
        except Exception as e:
            print(f"probe_major A/B skipped: {e}", file=sys.stderr)
    # Pallas fused-scan A/B at the chosen operating point (dispatch reads
    # the env per call; both schedules have fused legs whose ids match the
    # XLA schedules — equivalence-tested — so recall carries over).
    # Accel-only: off-TPU the kernels run in interpret mode at minutes
    # per call, which would break the CPU leg's bounded-time invariant.
    pallas_used = False
    if on_accel and time.monotonic() < deadline - 240:
        prev_pallas = os.environ.get("RAFT_TPU_PALLAS")
        try:
            os.environ["RAFT_TPU_PALLAS"] = "1"
            # only claim the flag when the dispatch would actually route
            # to the kernel — its gates (metric/dtype, query-major VMEM
            # scratch budget) silently fall back to the identical XLA
            # program, and noise must not record a phantom Pallas win
            from raft_tpu.kernels.ivf_scan import (
                QM_VMEM_BUDGET, qm_scratch_bytes,
            )
            from raft_tpu.neighbors._common import pallas_scan_enabled

            routed = pallas_scan_enabled(
                "sqeuclidean", index.list_data.dtype, allow_int8=True
            ) and (
                strategy != "query_major"
                or qm_scratch_bytes(n_probes, index.list_cap)
                <= QM_VMEM_BUDGET
            )
            if routed:
                t_p = timeit(make_search(n_probes, strategy), queries)
                if t_p < t_ours:
                    t_ours, pallas_used = t_p, True
        except Exception as e:
            print(f"pallas A/B skipped: {e}", file=sys.stderr)
        finally:
            if prev_pallas is None:
                os.environ.pop("RAFT_TPU_PALLAS", None)
            else:
                os.environ["RAFT_TPU_PALLAS"] = prev_pallas
    qps = n_q / t_ours
    exact_qps = n_q / t_exact

    _emit(
        {
            # keep the r1/r2 metric-name format (q1k etc.) when n_q is
            # a whole number of thousands so history stays comparable;
            # the recall95 suffix is only claimed when the operating
            # point actually reached it (deadline/exhaustion exits
            # keep best-so-far and must not mislabel)
            "metric": (
                f"ivf_pq_qps_deep{n // 1000}k_q"
                + (f"{n_q // 1000}k" if n_q % 1000 == 0 else f"{n_q}")
                + ("_k10_recall95" if recall >= 0.95 else "_k10_bestrecall")
            ),
            "value": round(qps, 1),
            "unit": "queries/s",
            "vs_baseline": round(qps / exact_qps, 3),
            "platform": platform,
            "recall": round(recall, 4),
            "n_probes": n_probes,
            "strategy": strategy,
            "pallas": pallas_used,
            # the attribution field the regression gate reports on — the
            # measured A/B routing, not the env default bench_record
            # would otherwise stamp
            "kernel_path": {"pallas": pallas_used},
            "build_s": round(build_s, 1),
            "exact_qps": round(exact_qps, 1),
            "n": n,
        }
    )


def run_serve_leg() -> None:
    """``python bench.py serve`` — pipelined-dispatch A/B benchmark (CPU).

    Exercises the raft_tpu.serve stack the way traffic does — a warmed
    MicroBatcher fed single-query requests from concurrent client
    threads, micro-batched into pow2 buckets — once per pipeline depth
    (1 = the serial pre-pipeline dispatch, then the overlapped depths;
    ``RAFT_TPU_BENCH_PIPELINE_DEPTHS`` overrides the ladder).

    Device model: every host stage is real (submission, coalescing,
    padding into staging buffers, XLA enqueue, copy-out, future
    resolution, metrics/spans), and the search results come from the
    real ivf_flat index — but result readiness is *paced* to a serial
    device queue with a fixed per-batch service time
    (``RAFT_TPU_BENCH_DEVICE_MS``, default 10).  On a CPU-only host the
    "device" otherwise shares the very cores the host stages run on, so
    a raw-compute A/B measures core contention, not overlap — the thing
    pipelining changes is *when the host waits*, and the paced wait
    (a GIL-releasing sleep, exactly like a TPU RPC) makes that visible:
    at depth=1 the dispatch thread idles through every device interval;
    at depth≥2 it pads and resolves the next batches inside them.

    Emits one BENCH-compatible JSON line whose headline value is the
    depth=2 QPS, with a per-depth table (QPS, p50/p99, batch-fill,
    device-idle fraction) and the depth=2 : depth=1 QPS ratio — the
    number the pipeline exists to move.  Recompiles must read 0 at every
    depth or the line is garbage (the hot path is paying XLA compiles).
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import slowlog
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics

    n, d, k = 8192, 64, 10
    n_requests, n_clients = 4096, 4
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    depths = [
        int(x) for x in os.environ.get(
            "RAFT_TPU_BENCH_PIPELINE_DEPTHS", "1,2,4"
        ).split(",")
    ]
    # open-loop clients flood the queue by design (throughput capture);
    # queue waits of seconds are the workload, not slow queries
    slowlog.configure(None)
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)

    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)

    class _Paced:
        """A search result whose readiness models a serial device queue.

        Wraps the real (asynchronously dispatched) jax array;
        ``block_until_ready`` first waits for the actual compute, then
        sleeps out the remainder of the modeled service interval — the
        sleep releases the GIL, so whatever the host overlaps into it is
        honestly overlapped.
        """

        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_search():
        lock = threading.Lock()
        state = {"free": 0.0}
        params = ivf_flat.SearchParams(n_probes=8)

        def search_fn(batch):
            dist, ids = ivf_flat.search(params, index, batch, k)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def run_at_depth(depth: int) -> dict:
        batcher = MicroBatcher(
            make_paced_search(), d, max_batch=32, max_delay_ms=0.5,
            metrics=ServingMetrics(name="bench"), pipeline_depth=depth,
        )
        batcher.warmup()

        def client(cid: int):
            futs = [
                batcher.submit(queries[i])
                for i in range(cid, n_requests, n_clients)
            ]
            for f in futs:
                f.result(timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st = batcher.metrics.snapshot()
        busy = batcher.device_busy_s()
        batcher.stop()
        return {
            "qps": round(n_requests / wall, 1),
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batch_fill": round(st["batch_fill"], 3)
            if st["batch_fill"] else None,
            "batches": st["batches"],
            "recompiles": st["recompiles"],
            "warmup_compiles": st["warmup_compiles"],
            "inflight_peak": st["inflight_peak"],
            # fraction of the run the device had nothing outstanding —
            # the host-side stall the pipeline exists to hide
            "device_idle_frac": round(max(0.0, 1.0 - busy / wall), 3),
        }

    by_depth = {str(depth): run_at_depth(depth) for depth in depths}
    head = by_depth.get("2") or by_depth[str(depths[-1])]
    base = by_depth.get("1")
    ratio = (
        round(head["qps"] / base["qps"], 3)
        if base and base["qps"] else None
    )
    _emit(
        {
            "metric": f"serve_pipeline_qps_ivf_flat_n{n // 1000}k_k{k}",
            "value": head["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "depths": by_depth,
            "qps_vs_depth1": ratio,
            "p50_ms": head["p50_ms"],
            "p99_ms": head["p99_ms"],
            "batch_fill": head["batch_fill"],
            "recompiles": sum(d["recompiles"] for d in by_depth.values()),
            "warmup_compiles": head["warmup_compiles"],
            "requests": n_requests,
            "n": n,
            # explicit routing attribution: ask the shared pallas gate for
            # the index's (metric, storage dtype) instead of letting the
            # record default to the bare env opt-in
            "kernel_path": _serve_kernel_path(),
        }
    )


def _serve_kernel_path() -> dict:
    """Pallas attribution for the ivf_flat-backed serving legs."""
    import jax.numpy as jnp

    from raft_tpu.bench.export import kernel_path

    return kernel_path("sqeuclidean", jnp.float32)


def run_ragged_leg() -> None:
    """``python bench.py ragged`` — ragged vs pow2-ladder A/B (CPU).

    Workload: single-query requests with heterogeneous per-request
    ``(k, filter)`` drawn from a fixed mix (three ks × unfiltered/two
    registered bitset filters), served closed-loop by many concurrent
    clients against the same ivf_flat MutableIndex, under the same paced
    serial-device model as ``bench.py serve`` (every host stage real,
    result readiness paced to ``RAFT_TPU_BENCH_DEVICE_MS`` per batch).

    Baseline arm is what classic mode forces for this traffic: one warmed
    MicroBatcher **per (k, filter) variant** — requests fragment across
    per-variant queues, each cutting small padded batches against the one
    shared device.  Ragged arm is a single batcher in ragged mode: every
    request packs into the same bucket dispatch with its ``(k, fid)``
    riding as descriptor data, continuous admission packing the forming
    batch while the device window is full.

    Emits one BENCH line whose headline value is the ragged arm's QPS,
    with the ladder arm's figures, the QPS ratio, warmup variant counts
    (one per bucket per batcher — the executable-lattice size), padding
    waste, and recompiles (must be 0 on both arms).
    """
    import threading
    import types

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import slowlog
    from raft_tpu.serve import IndexRegistry, MutableIndex
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics
    from raft_tpu.serve.ragged import (
        FilterRegistry,
        RaggedSearcher,
        RaggedSpec,
    )

    n, d, k_max = 8192, 64, 32
    n_requests, n_clients = 4096, 64
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    slowlog.configure(None)

    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)
    params = ivf_flat.SearchParams(n_probes=8)
    mi = MutableIndex(index, search_params=params)

    even = np.zeros(n, bool)
    even[::2] = True
    band = np.zeros(n, bool)
    band[n // 4 : 3 * n // 4] = True
    masks = {0: None, 1: even, 2: band}

    ks = (2, 10, k_max)
    combos = [(k, f) for k in ks for f in (0, 1, 2)]
    plan = [combos[i] for i in rng.integers(0, len(combos), n_requests)]

    class _Paced:
        """Same modeled serial device as ``run_serve_leg`` (see there)."""

        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_pacer():
        """One serial modeled device per arm, shared by every batcher."""
        lock = threading.Lock()
        state = {"free": 0.0}

        def pace(dist, ids):
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return pace

    def drive(submit) -> float:
        """Closed-loop clients: each submits one request, waits, repeats."""
        def client(cid: int):
            for i in range(cid, n_requests, n_clients):
                submit(i).result(timeout=600)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def arm_stats(metrics, wall, warmup_variants):
        st = metrics.snapshot()
        return {
            "qps": round(n_requests / wall, 1),
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batches": st["batches"],
            "batch_fill": round(st["batch_fill"], 3)
            if st["batch_fill"] else None,
            "pad_waste_rows": st["pad_waste_rows"],
            "recompiles": st["recompiles"],
            "warmup_variants": warmup_variants,
        }

    def run_ladder_arm() -> dict:
        pace = make_pacer()
        metrics = ServingMetrics(name="bench-ladder")
        batchers = {}
        variants = 0
        for k, f in combos:
            bs = None if masks[f] is None else Bitset.from_mask(
                jnp.asarray(masks[f])
            )

            def search_fn(batch, _k=k, _bs=bs):
                return pace(*mi.search(batch, _k, sample_filter=_bs))

            b = MicroBatcher(
                search_fn, d, min_bucket=8, max_batch=32, max_delay_ms=0.5,
                metrics=metrics, pipeline_depth=2, cost_accounting=False,
            )
            b.warmup()
            variants += len(b.buckets())
            batchers[(k, f)] = b
        wall = drive(lambda i: batchers[plan[i]].submit(queries[i]))
        out = arm_stats(metrics, wall, variants)
        for b in batchers.values():
            b.stop()
        return out

    def run_ragged_arm() -> dict:
        pace = make_pacer()
        metrics = ServingMetrics(name="bench-ragged")
        spec = RaggedSpec(k_max=k_max)
        reg = IndexRegistry()
        reg.register("t", mi)
        freg = FilterRegistry(n)
        assert freg.register(even) == 1 and freg.register(band) == 2
        searcher = RaggedSearcher(
            types.SimpleNamespace(registry=reg), "t", spec, freg
        )

        def search_fn(batch, row_k, row_fid):
            return pace(*searcher(batch, row_k, row_fid))

        b = MicroBatcher(
            search_fn, d, min_bucket=8, max_batch=32, max_delay_ms=0.5,
            metrics=metrics, pipeline_depth=2, cost_accounting=False,
            ragged=spec,
        )
        b.warmup()
        variants = len(b.buckets())
        wall = drive(
            lambda i: b.submit(queries[i], k=plan[i][0], fid=plan[i][1])
        )
        out = arm_stats(metrics, wall, variants)
        b.stop()
        return out

    ladder = run_ladder_arm()
    ragged = run_ragged_arm()
    ratio = (
        round(ragged["qps"] / ladder["qps"], 3) if ladder["qps"] else None
    )
    reduction = (
        round(ladder["warmup_variants"] / ragged["warmup_variants"], 2)
        if ragged["warmup_variants"] else None
    )
    _emit(
        {
            "metric": f"serve_ragged_qps_ivf_flat_n{n // 1000}k_kmax{k_max}",
            "value": ragged["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "arms": {"ladder": ladder, "ragged": ragged},
            "qps_vs_ladder": ratio,
            "warmup_variant_reduction": reduction,
            "p50_ms": ragged["p50_ms"],
            "p99_ms": ragged["p99_ms"],
            "batch_fill": ragged["batch_fill"],
            "pad_waste_rows": ragged["pad_waste_rows"],
            "recompiles": ladder["recompiles"] + ragged["recompiles"],
            "requests": n_requests,
            "n": n,
            "kernel_path": _serve_kernel_path(),
        }
    )


def run_overload_leg() -> None:
    """``python bench.py overload`` — admission-control A/B under sustained
    overload (CPU).

    Two arms drive the same warmed MicroBatcher + paced serial device with
    the same *open-loop* Poisson stream at 2x the measured sustainable
    capacity — past what even the fully-degraded effort ladder can absorb,
    so steady-state shedding stays on display — with a uniform 25/25/25/25
    priority mix (0 interactive … 3 background):

    - **controlled**: an :class:`~raft_tpu.serve.overload.AdmissionController`
      sheds lowest-priority-first at batch-cut time and a
      :class:`~raft_tpu.serve.overload.DegradedModeManager` steps search
      effort down under sustained pressure (the modeled device interval
      shrinks with the degrade level, the way fewer probes / smaller itopk
      shrink a real search kernel).
    - **uncontrolled**: same stream, no actuators — the queue has nowhere
      to go but up.

    Each arm first measures its own uncontended p0 p99 (a short low-rate
    p0-only stream), so the headline ratio — overloaded p0 p99 vs
    uncontended — is an apples-to-apples within-arm number.  The leg
    asserts the non-negotiables before emitting: priority 0 is never shed,
    recompiles read 0 in both arms, every shed decision landed on the
    event bus *and* inside a correlated incident timeline.  Collapse
    evidence for the uncontrolled arm is queue growth (rows still queued
    when the stream ends) and the p0 tail, both in the emitted record.

    Deadlines are deliberately absent here: expiry would shed load in the
    uncontrolled arm too and blur the A/B (tests cover deadline expiry;
    this leg isolates the controller).
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import events, slowlog
    from raft_tpu.obs.incidents import IncidentManager
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics
    from raft_tpu.serve.overload import (
        AdmissionController,
        DegradedModeManager,
        OverloadConfig,
        Shed,
    )

    from raft_tpu import obs

    n, d, k = 4096, 32, 10
    n_queries = 2048
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    duration_s = float(os.environ.get("RAFT_TPU_BENCH_OVERLOAD_S", "6"))
    # 2x the measured capacity: enough that even the fully-degraded
    # effort ladder cannot absorb it, so steady-state admission shedding
    # (not just the transient) is on display.  1.5x turned out to sit
    # *below* the level-2 degraded service rate — the ladder swallowed
    # it whole and nothing shed after the onset.
    overload_x = 2.0
    max_batch = 16
    # open-loop overload floods the queue by design; queue waits are the
    # workload under test, not slow queries
    slowlog.configure(None)
    # span recording off: with it on, the first admission_shed event
    # auto-dumps the (phase-1-filled) flight ring to disk from the
    # dispatch thread — a one-time ~300ms stall at overload onset that
    # floods the queue to ~700 rows before the controller has a say, and
    # the level-3 drain of that backlog sheds standard-priority traffic
    # the steady state never would.  The bus events and incident
    # correlation this leg asserts on do not need span recording.
    obs.set_enabled(False)
    rng = np.random.default_rng(7)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_queries, d), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)
    params = ivf_flat.SearchParams(n_probes=8)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_search_fn(degraded):
        """Real ivf_flat search, readiness paced to a serial device.

        The modeled interval is ``device_ms`` for a full ``max_batch``
        dispatch, scaling down with the padded batch (30% launch floor +
        70% linear in rows) — a post-shed dispatch carrying only the
        admitted survivors must cost less device time than the full cut,
        or shedding would *waste* capacity instead of reclaiming it.  The
        interval additionally shrinks 20% per degrade level: the effort
        ladder's whole point is that level-n search does less device
        work."""
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            dist, ids = ivf_flat.search(params, index, batch, k)
            cost = device_ms * 1e-3 * (
                0.3 + 0.7 * batch.shape[0] / max_batch
            )
            if degraded is not None:
                cost *= 1.0 - 0.2 * degraded.level
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + cost
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def calibrate() -> float:
        """Saturated service capacity: flood a plain batcher with a
        burst and measure the drain rate.  Closed-loop clients would
        under-measure it — their arrival rate tracks their own latency,
        so "1.5x closed-loop throughput" can sit *below* the true
        service rate and never overload anything."""
        b = MicroBatcher(
            make_search_fn(None), d, min_bucket=8, max_batch=max_batch,
            max_delay_ms=1.0, metrics=ServingMetrics(name="bench-cal"),
            pipeline_depth=2, cost_accounting=False,
        )
        b.warmup()
        n_cal = 1024
        t0 = time.perf_counter()
        futs = [
            b.submit(queries[i % n_queries]) for i in range(n_cal)
        ]
        for f in futs:
            f.result(timeout=120)
        wall = time.perf_counter() - t0
        b.stop()
        return n_cal / wall

    def run_arm(name: str, capacity: float, controlled: bool) -> dict:
        import gc

        ctrl = mgr = None
        if controlled:
            cfg = OverloadConfig(
                # wait thresholds 1.5/3/6 device intervals; the *depth*
                # signal (1/2/4 x max_batch rows) is the one that holds
                # the equilibrium — head-of-queue age lags queue growth
                # by a full drain, so leaning on it alone lets the queue
                # rebuild hundreds of rows deep between reactions, while
                # depth trips level 1 the moment one full cut is waiting
                admit_wait_s=1.5 * device_ms * 1e-3,
                queue_factor=1.5,
                # engage the effort ladder quickly and do not restore
                # mid-run: a restore under sustained 1.5x offered load
                # just relights the overload sawtooth
                degrade_after_s=0.25,
                restore_after_s=5.0,
                max_degrade_level=2,
            )
            ctrl = AdmissionController(cfg, name=name)
            mgr = DegradedModeManager(cfg, name=name)
        metrics = ServingMetrics(name=f"bench-{name}")
        b = MicroBatcher(
            make_search_fn(mgr), d, min_bucket=8, max_batch=max_batch,
            max_delay_ms=1.0, metrics=metrics, pipeline_depth=2,
            cost_accounting=False, admission=ctrl, degraded=mgr,
        )
        warmup_compiles = b.warmup()

        outcomes: list = []

        def stream(arrivals, priorities, sink) -> float:
            t0 = time.perf_counter()
            for i, (off, pr) in enumerate(zip(arrivals, priorities)):
                rest = t0 + off - time.perf_counter()
                if rest > 0:
                    time.sleep(rest)
                fut = b.submit(queries[i % n_queries], priority=int(pr))

                def done(f, _sched=t0 + off, _pr=int(pr)):
                    exc = f.exception()
                    t_done = time.perf_counter()
                    status = (
                        "ok" if exc is None
                        else "shed" if isinstance(exc, Shed) else "error"
                    )
                    sink.append((_pr, status, t_done - _sched, t_done - t0))

                fut.add_done_callback(done)
            return time.perf_counter() - t0

        def await_all(sink, total):
            deadline = time.perf_counter() + 300
            while len(sink) < total:
                if time.perf_counter() > deadline:
                    raise RuntimeError(
                        f"{name}: {total - len(sink)} requests never "
                        "resolved"
                    )
                time.sleep(0.02)

        # phase 0 — discarded warm stream: first-traffic effects (thread
        # spin-up, first-use registry/metrics paths, allocator warmth)
        # must not bias either arm's uncontended baseline
        n_warm = 128
        stream(
            poisson_arrivals(0.25 * capacity, n_warm, seed=5),
            np.zeros(n_warm, dtype=int), outcomes,
        )
        await_all(outcomes, n_warm)
        outcomes.clear()

        # phase 1 — uncontended p0 tail at ~25% capacity
        unc_rate = 0.25 * capacity
        n_unc = int(unc_rate * 1.2)
        stream(
            poisson_arrivals(unc_rate, n_unc, seed=11),
            np.zeros(n_unc, dtype=int), outcomes,
        )
        await_all(outcomes, n_unc)
        unc_lat = sorted(lat for _, st, lat, _ in outcomes if st == "ok")
        p0_unc_p99 = unc_lat[int(0.99 * (len(unc_lat) - 1))]
        outcomes.clear()

        # phase 2 — sustained overload at 1.5x capacity, 4-class mix.
        # GC off for the measured window: a gen-2 pass holds the GIL for
        # tens of ms, freezing the dispatch thread — which reads as (and,
        # via the shed burst it causes, amplifies) phantom overload
        gc.collect()
        gc.disable()
        rate = overload_x * capacity
        n_req = int(rate * duration_s)
        priorities = np.tile(np.arange(4), (n_req + 3) // 4)[:n_req]
        np.random.default_rng(13).shuffle(priorities)
        sampler_stop = threading.Event()
        sampled = {"max_queue": 0, "max_degraded": 0}

        def sampler():
            while not sampler_stop.is_set():
                sampled["max_queue"] = max(
                    sampled["max_queue"], b.queue_depth()
                )
                if mgr is not None:
                    sampled["max_degraded"] = max(
                        sampled["max_degraded"], mgr.level
                    )
                time.sleep(0.005)

        sampler_thread = threading.Thread(target=sampler, daemon=True)
        sampler_thread.start()
        submit_wall = stream(
            poisson_arrivals(rate, n_req, seed=17), priorities, outcomes
        )
        queue_at_submit_end = b.queue_depth()
        await_all(outcomes, n_req)
        gc.enable()
        sampler_stop.set()
        sampler_thread.join()
        b.stop()
        if ctrl is not None:
            ctrl.close()

        offered_qps = n_req / submit_wall
        ok = [(pr, lat, done) for pr, st, lat, done in outcomes
              if st == "ok"]
        served_wall = max(done for _, _, done in ok)
        shed_by_priority: dict = {}
        steady_shed_by_priority: dict = {}
        for pr, st, lat, done in outcomes:
            if st == "shed":
                key = str(pr)
                shed_by_priority[key] = shed_by_priority.get(key, 0) + 1
                if done - lat >= 1.5:
                    steady_shed_by_priority[key] = (
                        steady_shed_by_priority.get(key, 0) + 1
                    )
        errors = sum(1 for _, st, _, _ in outcomes if st == "error")
        p99_by_priority = {}
        for pr in range(4):
            lats = sorted(lat for p, lat, _ in ok if p == pr)
            p99_by_priority[str(pr)] = (
                round(lats[int(0.99 * (len(lats) - 1))] * 1e3, 1)
                if lats else None
            )
        # steady-state p0 tail: requests scheduled after the controller
        # has worked through the 0 -> 1.5x step transient (admission
        # reacts at the first cut, but the effort ladder needs its
        # hysteresis window, and the backlog built meanwhile must drain).
        # The full-stream tail is reported too — the transient is real,
        # it is just a different property than the held steady state.
        steady = sorted(
            lat for pr, lat, done in ok
            if pr == 0 and (done - lat) >= 1.5
        )
        p0_steady_p99 = (
            round(steady[int(0.99 * (len(steady) - 1))] * 1e3, 1)
            if steady else None
        )
        goodput = len(ok) / served_wall
        st = metrics.snapshot()
        return {
            "offered_qps": round(offered_qps, 1),
            "capacity_x": round(offered_qps / capacity, 2),
            "served": len(ok),
            "shed": sum(shed_by_priority.values()),
            "errors": errors,
            "shed_by_priority": shed_by_priority,
            "steady_shed_by_priority": steady_shed_by_priority,
            "goodput_qps": round(goodput, 1),
            "goodput_vs_capacity": round(goodput / capacity, 3),
            "p99_ms_by_priority": p99_by_priority,
            "p0_p99_ms": p99_by_priority["0"],
            "p0_steady_p99_ms": p0_steady_p99,
            "p0_uncontended_p99_ms": round(p0_unc_p99 * 1e3, 1),
            "p0_p99_vs_uncontended": round(
                (p99_by_priority["0"] or 0.0) / (p0_unc_p99 * 1e3), 2
            ),
            "p0_steady_p99_vs_uncontended": (
                round(p0_steady_p99 / (p0_unc_p99 * 1e3), 2)
                if p0_steady_p99 is not None else None
            ),
            "max_queue_rows": sampled["max_queue"],
            "queue_rows_at_submit_end": queue_at_submit_end,
            "max_degraded_level": sampled["max_degraded"],
            "recompiles": st["recompiles"],
            "warmup_compiles": warmup_compiles,
        }

    import gc

    capacity = calibrate()

    seen_kinds: list = []
    sub = events.default_bus().subscribe(
        lambda e: seen_kinds.append(e.kind),
        kinds=frozenset({"admission_shed", "degraded_enter",
                         "degraded_exit"}),
        name="bench-overload-collector",
    )
    im = IncidentManager(
        events.default_bus(), window_s=10.0, autoclose_s=600.0
    )
    try:
        # controlled arm first, on a freshly collected heap: the
        # uncontrolled arm strands thousands of queued futures, and
        # running in its garbage means multi-10ms GC pauses in the
        # dispatch thread that read as (and trigger) phantom overload
        gc.collect()
        on = run_arm("overload-on", capacity, controlled=True)
        incidents = im.open_incidents() + im.closed_incidents()
    finally:
        sub.unsubscribe()
        if im._sub is not None:
            im._sub.unsubscribe()
    gc.collect()
    off = run_arm("overload-off", capacity, controlled=False)

    shed_event_on_bus = "admission_shed" in seen_kinds
    degraded_event_on_bus = "degraded_enter" in seen_kinds
    shed_in_incident = any(
        any(ev["kind"] == "admission_shed" for ev in inc.timeline)
        for inc in incidents
    )

    # the non-negotiables — a record that fails any of these is garbage
    assert "0" not in on["shed_by_priority"], (
        f"priority 0 must never shed: {on['shed_by_priority']}"
    )
    assert on["errors"] == 0 and off["errors"] == 0, (
        f"unexpected request errors: on={on['errors']} off={off['errors']}"
    )
    assert on["recompiles"] == 0 and off["recompiles"] == 0, (
        "hot path recompiled: "
        f"on={on['recompiles']} off={off['recompiles']}"
    )
    assert shed_event_on_bus, "no admission_shed event reached the bus"
    assert shed_in_incident, (
        "shed decisions never landed in a correlated incident timeline"
    )
    assert off["queue_rows_at_submit_end"] > 4 * max(
        1, on["queue_rows_at_submit_end"]
    ), (
        "uncontrolled arm did not collapse: "
        f"off queue {off['queue_rows_at_submit_end']} rows vs "
        f"on {on['queue_rows_at_submit_end']}"
    )
    assert on["goodput_vs_capacity"] >= 0.9, (
        "controller-on goodput fell below 0.9x capacity: "
        f"{on['goodput_vs_capacity']}"
    )
    assert "0" not in on["steady_shed_by_priority"], (
        f"steady-state shed priority 0: {on['steady_shed_by_priority']}"
    )
    # sanity bound only — the frozen record carries the real number
    # (~1.3-1.5x); a shared-CPU hiccup can nudge it, so the hard gate
    # here is loose and the compare smoke pins the regression tolerance
    assert on["p0_steady_p99_vs_uncontended"] <= 3.0, (
        "controller-on steady p0 p99 not held: "
        f"{on['p0_steady_p99_vs_uncontended']}x uncontended"
    )

    _emit(
        {
            "metric": f"serve_overload_goodput_ivf_flat_n{n // 1000}k"
                      f"_x{overload_x}",
            "value": on["goodput_qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "duration_s": duration_s,
            "capacity_qps": round(capacity, 1),
            "arms": {"controlled": on, "uncontrolled": off},
            "p0_p99_vs_uncontended": on["p0_p99_vs_uncontended"],
            "p0_steady_p99_vs_uncontended":
                on["p0_steady_p99_vs_uncontended"],
            "goodput_vs_capacity": on["goodput_vs_capacity"],
            "off_p0_p99_vs_on": (
                round(off["p0_p99_ms"] / on["p0_p99_ms"], 1)
                if on["p0_p99_ms"] else None
            ),
            "shed_event_on_bus": shed_event_on_bus,
            "degraded_event_on_bus": degraded_event_on_bus,
            "shed_in_incident": shed_in_incident,
            "p50_ms": None,
            "p99_ms": on["p0_p99_ms"],
            "recompiles": on["recompiles"] + off["recompiles"],
            "requests": on["served"] + on["shed"],
            "n": n,
            "kernel_path": _serve_kernel_path(),
        }
    )


def run_shard_leg() -> None:
    """``python bench.py shard`` — index-sharding A/B benchmark (CPU,
    8 forced host devices).

    Three arms over the same ivf_flat index and query batch:

    - ``single``: the plain one-device search (the 1-device baseline);
    - ``replicated``: ReplicaGroup-style query sharding — all 8 devices
      hold the FULL index, queries split across them;
    - ``sharded``: ShardedIndex — each device holds ~1/8 of the lists,
      queries replicate, one cross-shard select_k merges.

    The headline value is the sharded-arm QPS (gated ±rtol vs the frozen
    record like every leg), but the number this leg exists to freeze is
    ``bytes_shrink_x``: per-device index bytes, replicated vs sharded —
    the capacity story.  ``n_probes`` is exhaustive, so all three arms
    return identical ids (recall 1.0 between arms is asserted, not
    measured) and hot-path recompiles must read 0 after warmup.
    """
    # 8 virtual host devices; must land in XLA_FLAGS before jax imports
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.comms.comms import local_comms
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve.metrics import compile_count, install_compile_listener
    from raft_tpu.serve.replica import make_replicated_search
    from raft_tpu.serve.shard import ShardedIndex
    from raft_tpu.stats import recall_at_k

    install_compile_listener()
    n_dev = len(jax.devices())
    n, d, k, n_q = 32_768, 64, 10, 1024
    n_lists = 64
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_q, d), dtype=np.float32)

    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists), dataset)
    # exhaustive probing: every arm sees every list, so ids are identical
    # across arms and the A/B compares pure dispatch/layout cost
    sp = ivf_flat.SearchParams(n_probes=n_lists)

    def single_fn(q):
        return ivf_flat.search(sp, index, q, k)

    replicated_fn = make_replicated_search(
        local_comms(n_dev),
        lambda q_shard, kk: ivf_flat.search(sp, index, q_shard, kk),
    )
    sharded = ShardedIndex.from_index(index, search_params=sp, label="bench")

    full_bytes = sum(
        int(np.asarray(a).nbytes)
        for a in (index.centers, index.list_data, index.list_index,
                  index.list_sizes, index.list_norms)
    )
    per_dev_sharded = sharded.per_shard_bytes()[0]
    shrink = full_bytes / per_dev_sharded if per_dev_sharded else None

    arms = {
        "single": single_fn,
        "replicated": lambda q: replicated_fn(q, k),
        "sharded": lambda q: sharded.search(q, k),
    }
    results, ids_by_arm = {}, {}
    for name, fn in arms.items():
        t = timeit(fn, queries)  # timeit warms up first — compiles land
        c1 = compile_count()     # before this read, recompiles after it
        _, ids = fn(queries)
        ids_by_arm[name] = np.asarray(ids)
        results[name] = {
            "qps": round(n_q / t, 1),
            "latency_ms": round(t * 1e3, 2),
            "recompiles": compile_count() - c1,
        }
    base_ids = ids_by_arm["single"]
    for name in ("replicated", "sharded"):
        r = recall_at_k(ids_by_arm[name], base_ids)
        results[name]["recall_vs_single"] = round(float(r), 4)
    assert results["sharded"]["recall_vs_single"] >= 0.999, (
        "sharded arm diverged from single-device ids at exhaustive probing"
    )

    results["replicated"]["per_device_bytes"] = full_bytes
    results["sharded"]["per_device_bytes"] = per_dev_sharded
    _emit(
        {
            "metric": (
                f"shard_index_qps_ivf_flat_n{n // 1024}k_k{k}_s{n_dev}"
            ),
            "value": results["sharded"]["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "devices": n_dev,
            "arms": results,
            "bytes_shrink_x": round(shrink, 2) if shrink else None,
            "merge_dtype": str(sharded.merge_dtype or "float32"),
            "recall": results["sharded"]["recall_vs_single"],
            "recompiles": sum(a["recompiles"] for a in results.values()),
            "n": n,
            "n_lists": n_lists,
            "queries": n_q,
        }
    )


def run_shard_cagra_leg() -> None:
    """``python bench.py shard_cagra`` — partitioned-graph CAGRA A/B
    (CPU, 8 forced host devices).

    Three arms over the same CAGRA index and query batch at matched
    ``itopk``:

    - ``single``: the one-device CAGRA walk (the recall yardstick);
    - ``graph``: GraphShardedIndex — cluster-cut subgraphs with halo
      nodes, shard-local traversal, halo-frontier exchange every
      ``sync_steps`` hops;
    - ``brute``: ShardedIndex brute-refine — each shard scores every
      resident row (exact; the control arm).

    The headline value is the graph-arm QPS, the gate is recall: the
    sharded walk must reach >= 0.95 of the single-host walk's recall
    against exact ground truth.  The number this leg exists to freeze is
    ``work_ratio_vs_brute`` — modeled per-query-per-shard distance
    computations, brute over graph — the sublinear-device-work story.
    Both sharded arms must show 0 post-warmup recompiles.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # bound the halo replicas so the frozen record's layout is stable
    os.environ.setdefault("RAFT_TPU_SHARD_CAGRA_HALO", "512")

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp

    from raft_tpu.comms.comms import local_comms
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.serve.metrics import compile_count, install_compile_listener
    from raft_tpu.serve.shard import ShardedIndex
    from raft_tpu.stats import recall_at_k

    install_compile_listener()
    n_dev = len(jax.devices())
    n, d, k, n_q = 8192, 32, 10, 256
    rng = np.random.default_rng(0)
    dataset = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((n_q, d)).astype(np.float32)

    index = cagra.build(
        cagra.IndexParams(graph_degree=16, intermediate_graph_degree=32),
        dataset,
    )
    # matched effort across all three arms: same beam, same hop budget
    sp = cagra.SearchParams(itopk_size=32, max_iterations=16)

    _, gt = brute_force.knn(jnp.asarray(dataset), jnp.asarray(queries), k)
    gt = np.asarray(gt)

    graph = ShardedIndex.from_index(
        index, local_comms(n_dev), search_params=sp, cagra_mode="graph",
        label="bench_cagra_graph",
    )
    brute = ShardedIndex.from_index(
        index, local_comms(n_dev), search_params=sp, cagra_mode="brute",
        label="bench_cagra_brute",
    )

    arms = {
        "single": lambda q: cagra.search(sp, index, q, k),
        "graph": lambda q: graph.search(q, k),
        "brute": lambda q: brute.search(q, k),
    }
    results, ids_by_arm = {}, {}
    for name, fn in arms.items():
        t = timeit(fn, queries)  # timeit warms up first — compiles land
        c1 = compile_count()     # before this read, recompiles after it
        _, ids = fn(queries)
        ids_by_arm[name] = np.asarray(ids)
        results[name] = {
            "qps": round(n_q / t, 1),
            "latency_ms": round(t * 1e3, 2),
            "recompiles": compile_count() - c1,
            "recall": round(float(recall_at_k(ids_by_arm[name], gt)), 4),
        }
    assert results["graph"]["recompiles"] == 0, "graph arm recompiled hot"
    assert results["brute"]["recompiles"] == 0, "brute arm recompiled hot"
    recall_ratio = results["graph"]["recall"] / max(
        results["single"]["recall"], 1e-9
    )
    assert recall_ratio >= 0.95, (
        f"sharded graph walk lost recall vs single-host: "
        f"{results['graph']['recall']} vs {results['single']['recall']}"
    )

    # modeled per-query-per-shard distance computations: the graph walk
    # scores seeds + hops*width*deg rows; the brute arm scores every
    # resident row.  This is the sublinear-device-work acceptance number.
    work = graph.modeled_device_work(k)
    brute_rows = int(brute._parts["rows"].shape[1])
    results["graph"]["modeled_distances_per_query"] = work["distances"]
    results["brute"]["modeled_distances_per_query"] = brute_rows
    work_ratio = brute_rows / work["distances"]
    assert work_ratio >= 1.5, (
        f"graph walk is not sublinear vs brute-refine: "
        f"{work['distances']} vs {brute_rows} distances/query/shard"
    )

    _emit(
        {
            "metric": f"shard_cagra_graph_qps_n{n // 1024}k_k{k}_s{n_dev}",
            "value": results["graph"]["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "devices": n_dev,
            "arms": results,
            "recall": results["graph"]["recall"],
            "recall_ratio_vs_single": round(recall_ratio, 4),
            "work_ratio_vs_brute": round(work_ratio, 2),
            "modeled_work": work,
            "halo_cap": int(os.environ["RAFT_TPU_SHARD_CAGRA_HALO"]),
            "halo_rows": [int(h) for h in graph._shard_stats["halo"]],
            "sync_steps": graph._sync_steps,
            "itopk": sp.itopk_size,
            "recompiles": sum(a["recompiles"] for a in results.values()),
            "n": n,
            "queries": n_q,
        }
    )


def run_build_leg() -> None:
    """``python bench.py build`` — distributed index build A/B (CPU,
    8 forced host devices).

    Three arms build the same ivf_flat index over the same rows:

    - ``single``: the plain single-host ``ivf_flat.build`` (the 1-device
      baseline);
    - ``sharded_f32``: ``serve.build.build_sharded`` over the 8-device
      mesh, training collectives at full f32;
    - ``sharded_bf16``: same, with the per-iteration centroid psum
      payload quantized to bf16 (``reduce_dtype``).

    Both arms train on ALL rows (``kmeans_trainset_fraction=1.0``) so
    the A/B compares equal Lloyd work — distribution cost vs
    distribution win, not trainset-size luck.  All 8 "devices" share one
    physical core here, so the sharded wall time is ~the sum of the
    per-shard work; the headline is the **modeled** 8-device throughput
    ``rows / (t_sharded / n_dev)`` and the modeled speedup
    ``t_single / (t_sharded / n_dev)`` — i.e. perfect-overlap scaling of
    the measured per-shard work, which is what a real pod realizes when
    every shard runs on its own chip.  Wall times for every arm are in
    the record; nothing is hidden behind the model.

    Each built index is searched at exhaustive probing against the
    brute-force oracle — build-quality parity (recall) is part of the
    frozen record, so a faster build that trains worse centroids gates
    as a regression.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.comms.comms import local_comms
    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.serve.build import build_sharded
    from raft_tpu.serve.metrics import compile_count, install_compile_listener
    from raft_tpu.stats import recall_at_k

    install_compile_listener()
    n_dev = len(jax.devices())
    n, d, k, n_q = 131_072, 64, 10, 256
    n_lists, n_iters = 64, 10
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_q, d), dtype=np.float32)
    _, gt = brute_force.knn(dataset, queries, k)
    gt = np.asarray(gt)

    params = ivf_flat.IndexParams(
        n_lists=n_lists, kmeans_n_iters=n_iters,
        kmeans_trainset_fraction=1.0,
    )
    sp = ivf_flat.SearchParams(n_probes=n_lists)
    comms = local_comms(n_dev)

    def time_build(fn):
        """(seconds, recall, recompiles): the first build warms every
        cached XLA program so compile time never pollutes the A/B; the
        best of two timed repeats drops scheduler jitter (all 8 virtual
        devices share one core here)."""
        fn()
        c0 = compile_count()
        t = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            index = fn()
            t = min(t, time.perf_counter() - t0)
        comp = compile_count() - c0   # the builds only — the quality
        _, ids = index.search(queries, k)  # search compiles separately
        return t, float(recall_at_k(np.asarray(ids), gt)), comp

    class _SingleServes:
        """Adapter: give the single-host index the same .search surface."""

        def __init__(self, index):
            self.index = index

        def search(self, q, kk):
            return ivf_flat.search(sp, self.index, q, kk)

    t_1, rec_1, comp_1 = time_build(
        lambda: _SingleServes(ivf_flat.build(params, dataset))
    )

    arms = {
        "single": {
            "seconds": round(t_1, 3),
            "rows_per_s": round(n / t_1, 1),
            "recall": round(rec_1, 4),
            "recompiles": comp_1,
        }
    }
    for name, rd in (("sharded_f32", "float32"), ("sharded_bf16", "bfloat16")):
        t_s, rec_s, comp_s = time_build(
            lambda rd=rd: build_sharded(
                "ivf_flat", dataset, comms, index_params=params,
                search_params=sp, reduce_dtype=rd, label=f"bench_{rd}",
            )
        )
        modeled = t_s / n_dev
        # per-iteration psum payload: [k, d+2] sums|counts, 4 vs 2 B/elt
        payload = n_lists * (d + 2) * (4 if rd == "float32" else 2)
        arms[name] = {
            "seconds_wall": round(t_s, 3),
            "seconds_modeled": round(modeled, 3),
            "rows_per_s_modeled": round(n / modeled, 1),
            "speedup_modeled_x": round(t_1 / modeled, 2),
            "recall": round(rec_s, 4),
            "recompiles": comp_s,
            "psum_bytes_per_iter": payload,
        }

    headline = arms["sharded_f32"]
    assert headline["speedup_modeled_x"] >= 4.0, (
        f"modeled {n_dev}-device build speedup "
        f"{headline['speedup_modeled_x']}x < 4x — distribution overhead "
        "ate the parallelism"
    )
    assert arms["sharded_bf16"]["recall"] >= rec_1 - 0.02, (
        "bf16-quantized training collectives degraded build quality"
    )
    _emit(
        {
            "metric": f"build_sharded_rows_per_s_ivf_flat_n{n // 1024}k_s{n_dev}",
            "value": headline["rows_per_s_modeled"],
            "unit": "rows/s",
            "platform": "cpu",
            "devices": n_dev,
            "arms": arms,
            "speedup_modeled_x": headline["speedup_modeled_x"],
            "recall": headline["recall"],
            "recompiles": sum(a["recompiles"] for a in arms.values()),
            "n": n,
            "dim": d,
            "n_lists": n_lists,
            "kmeans_n_iters": n_iters,
            "queries": n_q,
        }
    )


def run_flight_leg() -> None:
    """``python bench.py flight`` — flight-recorder overhead A/B (CPU).

    Same paced-device serve workload as ``run_serve_leg`` (real host
    stages, result readiness modeled as a serial device queue at
    ``RAFT_TPU_BENCH_DEVICE_MS`` per batch), run twice at pipeline depth
    2: once with observability fully disabled (``obs.set_enabled(False)``
    — the runtime form of ``RAFT_TPU_OBS_DISABLED``, which no-ops spans,
    exemplars and the flight recorder's ring appends) and once with the
    always-on recorder recording every batch.  The headline value is the
    recorder-on QPS; ``qps_ratio`` (on/off) is the cost of "always-on" —
    the acceptance bar is within 3% on quiet hardware, and the frozen
    record in ``benchmarks/`` gates regressions via ``bench.py compare``.
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu import obs
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import flight, slowlog
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics

    n, d, k = 8192, 64, 10
    n_requests, n_clients, depth = 2048, 4, 2
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    slowlog.configure(None)  # open-loop flood: queue waits are the workload
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)
    params = ivf_flat.SearchParams(n_probes=8)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_search():
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            dist, ids = ivf_flat.search(params, index, batch, k)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def run_arm(name: str) -> dict:
        flight.reset()
        batcher = MicroBatcher(
            make_paced_search(), d, max_batch=32, max_delay_ms=0.5,
            metrics=ServingMetrics(name=f"bench_flight_{name}"),
            pipeline_depth=depth,
        )
        batcher.warmup()

        def client(cid: int):
            futs = [
                batcher.submit(queries[i])
                for i in range(cid, n_requests, n_clients)
            ]
            for f in futs:
                f.result(timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st = batcher.metrics.snapshot()
        recorded = flight.default_recorder().snapshot()["recorded_total"]
        batcher.stop()
        return {
            "qps": round(n_requests / wall, 1),
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batches": st["batches"],
            "recompiles": st["recompiles"],
            "recorded_batches": recorded,
        }

    run_arm("warm")  # discarded: one-time jit/thread warmth must not bias
    obs.set_enabled(False)
    try:
        off = run_arm("off")
    finally:
        obs.set_enabled(True)
    on = run_arm("on")
    assert on["recorded_batches"] >= on["batches"], (
        "recorder-on arm recorded fewer batches than it dispatched"
    )
    assert off["recorded_batches"] == 0, (
        "recorder-off arm still recorded batches"
    )
    ratio = round(on["qps"] / off["qps"], 4) if off["qps"] else None
    _emit(
        {
            "metric": f"serve_flight_recorder_qps_ivf_flat_n{n // 1000}k_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "pipeline_depth": depth,
            "recorder_on": on,
            "recorder_off": off,
            "qps_ratio": ratio,
            "overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "recompiles": on["recompiles"] + off["recompiles"],
            "requests": n_requests,
            "n": n,
        }
    )


def run_explain_leg() -> None:
    """``python bench.py explain`` — explain tail-sampling overhead A/B
    (CPU).

    Same paced-device serve workload as ``run_flight_leg`` at pipeline
    depth 2, run once with explain collection off (the default:
    ``RAFT_TPU_EXPLAIN`` unset, so the batcher takes no stamps and the
    archive sees nothing) and once with ``RAFT_TPU_EXPLAIN=1`` —
    always-on tail sampling scanning every completed batch and archiving
    the interesting tail.  The headline value is the sampling-on QPS;
    ``qps_ratio`` (on/off) is the cost of "always-on" — the acceptance
    bar is within 2% on quiet hardware with **zero** post-warmup
    recompiles on both arms (the sampler rides host-side stamps, never
    executable outputs), and the frozen record in ``benchmarks/`` gates
    regressions via ``bench.py compare``.
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import explain, flight, slowlog
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics

    n, d, k = 8192, 64, 10
    n_requests, n_clients, depth = 2048, 4, 2
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    slowlog.configure(None)  # open-loop flood: queue waits are the workload
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)
    params = ivf_flat.SearchParams(n_probes=8)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_search():
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            dist, ids = ivf_flat.search(params, index, batch, k)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def run_arm(name: str) -> dict:
        flight.reset()
        explain.reset()  # clears the ring and re-reads RAFT_TPU_EXPLAIN_*
        batcher = MicroBatcher(
            make_paced_search(), d, max_batch=32, max_delay_ms=0.5,
            metrics=ServingMetrics(name=f"bench_explain_{name}"),
            pipeline_depth=depth,
        )
        batcher.warmup()

        def client(cid: int):
            futs = [
                batcher.submit(queries[i])
                for i in range(cid, n_requests, n_clients)
            ]
            for f in futs:
                f.result(timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st = batcher.metrics.snapshot()
        archived = explain.default_archive().snapshot()["archived_total"]
        batcher.stop()
        return {
            "qps": round(n_requests / wall, 1),
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batches": st["batches"],
            "recompiles": st["recompiles"],
            "archived_plans": archived,
        }

    run_arm("warm")  # discarded: one-time jit/thread warmth must not bias
    os.environ.pop("RAFT_TPU_EXPLAIN", None)
    off = run_arm("off")
    os.environ["RAFT_TPU_EXPLAIN"] = "1"
    try:
        on = run_arm("on")
    finally:
        os.environ.pop("RAFT_TPU_EXPLAIN", None)
    assert on["archived_plans"] > 0, (
        "sampling-on arm archived no plans — the tail sampler never ran"
    )
    assert off["archived_plans"] == 0, (
        "sampling-off arm archived plans — the RAFT_TPU_EXPLAIN gate leaks"
    )
    assert on["recompiles"] == 0 and off["recompiles"] == 0, (
        "explain sampling recompiled post-warmup"
    )
    ratio = round(on["qps"] / off["qps"], 4) if off["qps"] else None
    _emit(
        {
            "metric": f"serve_explain_sampling_qps_ivf_flat_n{n // 1000}k_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "pipeline_depth": depth,
            "sampling_on": on,
            "sampling_off": off,
            "qps_ratio": ratio,
            "overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "recompiles": on["recompiles"] + off["recompiles"],
            "requests": n_requests,
            "n": n,
        }
    )


def run_gateway_leg() -> None:
    """``python bench.py gateway`` — scrape-under-load overhead A/B (CPU).

    A live ``SearchService`` (ivf_flat, paced device at pipeline depth
    2) serves an open-loop arrival stream paced below device capacity —
    the steady-state a healthy replica sees, so ``/healthz`` stays green
    instead of (correctly) reporting the self-inflicted overload a
    closed-loop flood creates.  One arm additionally runs the
    operational HTTP gateway with a 1 Hz poller hitting ``/metrics``
    and ``/healthz`` — the Prometheus-scrape + LB-probe duty cycle a
    pod sees in production.  The headline value is the polled arm's
    QPS; ``qps_ratio`` (polled/unpolled) is the cost of being scraped,
    and the acceptance bar is "within noise": the gateway only calls
    the lock-light pull APIs, so a scrape must never stall a dispatch,
    and both arms must finish with **zero** post-warmup recompiles (the
    scrape path touches no shapes).  The frozen record in
    ``benchmarks/`` gates regressions via ``bench.py compare``.
    """
    import threading
    import urllib.request

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import flight, slowlog
    from raft_tpu.obs.gateway import GatewayConfig

    n, d, k = 8192, 64, 10
    n_requests, depth = 1024, 2
    # the pacing chain serializes dispatches device_ms apart, so the
    # worst-case (fill-1) service rate is ~1/(device_ms + CPU search)
    # ≈ 140 batches/s at 5 ms — arrivals must sit BELOW that, not below
    # the full-fill ceiling, or stability depends on fill growth and a
    # single scheduler hiccup on a 1-core CI host snowballs into a
    # stream-long backlog; 60/s leaves >2x fill-1 headroom, so queue
    # waits stay flat and /healthz stays green across the whole stream
    arrival_qps = 60.0
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "5"))
    poll_hz = 1.0
    slowlog.configure(None)  # paced stream: queue waits are workload
    # the paced stream's synthetic latencies can trip the perf-regression
    # auto-capture, whose first jax.profiler.start_trace pays a one-time
    # multi-second TensorFlow import on the serving path — that lands in
    # whichever arm is active and poisons the A/B, so captures are off
    os.environ["RAFT_TPU_PERF_CAPTURE_S"] = "0"
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)
    built = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_index():
        """A served MutableIndex whose search models a busy device: real
        ivf_flat results, completion paced device_ms apart."""
        index = serve.MutableIndex(
            built, search_params=ivf_flat.SearchParams(n_probes=8)
        )
        inner = index.search
        lock = threading.Lock()
        state = {"free": 0.0}

        def paced_search(batch, k, **kw):
            dist, ids = inner(batch, k, **kw)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        index.search = paced_search
        return index

    def poller(url: str, stop: threading.Event, out: dict):
        """The production scrape duty cycle: /metrics + /healthz, 1 Hz.
        HTTP status codes are tallied (a 503 is the gateway *working* —
        reporting an unhealthy verdict); only transport failures count
        as scrape errors."""
        import urllib.error

        while not stop.is_set():
            for path in ("/metrics", "/healthz"):
                try:
                    with urllib.request.urlopen(url + path, timeout=10) as r:
                        r.read()
                        code = r.status
                except urllib.error.HTTPError as err:
                    code = err.code
                except Exception:  # noqa: BLE001 — counted, not fatal
                    out["errors"] += 1
                    continue
                key = str(code)
                out["codes"][key] = out["codes"].get(key, 0) + 1
            out["scrapes"] += 1
            stop.wait(1.0 / poll_hz)

    def run_arm(name: str, polled: bool, limit: int = 0) -> dict:
        n_requests_arm = limit or n_requests
        flight.reset()
        svc = serve.SearchService(
            k=k, max_batch=8, max_delay_ms=0.5, pipeline_depth=depth,
            gateway=GatewayConfig(port=0) if polled else None,
        )
        svc.add_index(name, make_paced_index(), warmup=True)
        stop = threading.Event()
        poll_stats = {"scrapes": 0, "errors": 0, "codes": {}}
        poll_thread = None
        if polled:
            poll_thread = threading.Thread(
                target=poller, args=(svc.gateway.url, stop, poll_stats)
            )
            poll_thread.start()

        # open-loop paced arrivals: submit on a fixed schedule below
        # device capacity, then drain — both arms see the identical
        # stream, so any wall-clock delta is the scrape's cost
        interval = 1.0 / arrival_qps
        futs = []
        t0 = time.perf_counter()
        next_at = t0
        for i in range(n_requests_arm):
            lag = next_at - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            futs.append(svc.submit(name, queries[i]))
            next_at += interval
        for f in futs:
            f.result(timeout=300)
        wall = time.perf_counter() - t0
        stop.set()
        if poll_thread is not None:
            poll_thread.join(timeout=30)
        st = svc.stats(name)
        svc.stop()
        return {
            "qps": round(n_requests_arm / wall, 1),
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "recompiles": st["recompiles"],
            "scrapes": poll_stats["scrapes"],
            "scrape_errors": poll_stats["errors"],
            "scrape_codes": poll_stats["codes"],
        }

    run_arm("warm", polled=False, limit=128)  # discarded: jit warmth
    unpolled = run_arm("off", polled=False)
    polled = run_arm("on", polled=True)
    assert polled["scrapes"] >= 2, (
        f"polled arm saw only {polled['scrapes']} scrape cycles — the "
        "workload finished before the 1 Hz poller exercised anything"
    )
    assert polled["scrape_errors"] == 0, (
        f"{polled['scrape_errors']} scrape(s) failed under serving load"
    )
    assert polled["recompiles"] == 0 and unpolled["recompiles"] == 0, (
        "gateway scraping recompiled the serve hot path"
    )
    ratio = round(polled["qps"] / unpolled["qps"], 4) \
        if unpolled["qps"] else None
    _emit(
        {
            "metric": f"serve_gateway_scrape_qps_ivf_flat_"
                      f"n{n // 1000}k_k{k}",
            "value": polled["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "pipeline_depth": depth,
            "poll_hz": poll_hz,
            "polled": polled,
            "unpolled": unpolled,
            "qps_ratio": ratio,
            "overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "recompiles": polled["recompiles"] + unpolled["recompiles"],
            "requests": n_requests,
            "n": n,
        }
    )


def run_slo_leg() -> None:
    """``python bench.py slo`` — SLO-engine overhead A/B (CPU).

    Same paced-device serve workload as ``run_flight_leg`` at pipeline
    depth 2, run as ``RAFT_TPU_BENCH_SLO_ROUNDS`` (default 3)
    interleaved off/on rounds: each round serves once with no SLO
    engine and once with a :class:`raft_tpu.obs.slo.SloEngine`
    evaluating the availability and latency objectives for the served
    name on a deliberately aggressive 200 ms tick (50x faster than the
    production default; on the single-core CI host each evaluator wake
    preempts the serving core, so the tick rate IS the overhead — 50x
    is the honest worst case that still meets the <2% bar there, and
    multi-core hosts run the evaluator on a spare core for ~0%).  The
    headline ratio pools total requests over total
    wall per arm kind, because on a single-core CI host one off/on pair
    swings +-10% with scheduler noise.  The evaluator reads cumulative
    counters and histogram bucket totals off the hot path (never the
    raw reservoirs — see ``Histogram.bucket_totals``); the acceptance
    bar is <2% QPS overhead, gated by ``bench.py compare`` against the
    frozen record in ``benchmarks/``.
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import slo, slowlog
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics

    n, d, k = 8192, 64, 10
    n_requests, n_clients, depth = 2048, 4, 2
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    slowlog.configure(None)  # open-loop flood: queue waits are the workload
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=64), dataset)
    params = ivf_flat.SearchParams(n_probes=8)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_search():
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            dist, ids = ivf_flat.search(params, index, batch, k)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def _run_slo_arm(served: str, with_engine: bool) -> tuple:
        batcher = MicroBatcher(
            make_paced_search(), d, max_batch=32, max_delay_ms=0.5,
            metrics=ServingMetrics(name=served),
            pipeline_depth=depth,
        )
        batcher.warmup()
        engine = None
        if with_engine:
            engine = slo.SloEngine(
                [
                    slo.SloSpec(f"{served}-availability", served,
                                "availability", 0.999),
                    slo.SloSpec(f"{served}-latency", served, "latency",
                                0.9999, target=0.25),
                ],
                eval_s=0.2, scale=1.0,
            )
            engine.start()

        def client(cid: int):
            futs = [
                batcher.submit(queries[i])
                for i in range(cid, n_requests, n_clients)
            ]
            for f in futs:
                f.result(timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st = batcher.metrics.snapshot()
        out = {
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batches": st["batches"],
            "recompiles": st["recompiles"],
        }
        if engine is not None:
            snap = engine.snapshot()
            out["evals"] = max(
                s["samples"] for s in snap["specs"].values()
            )
            out["budget_remaining"] = round(min(
                s["budget_remaining"] for s in snap["specs"].values()
            ), 6)
            engine.stop()
        batcher.stop()
        return wall, out

    _run_slo_arm("bench_slo_warm", False)  # discarded: jit/thread warmth
    # interleaved rounds, pooled walls: single-core CI hosts schedule
    # the 4-client open-loop flood noisily enough that one off/on pair
    # can swing +-10% either way — the headline ratio comes from total
    # requests over total wall per arm kind across all rounds
    n_rounds = int(os.environ.get("RAFT_TPU_BENCH_SLO_ROUNDS", "3"))
    off_wall = on_wall = 0.0
    off_recompiles = on_recompiles = 0
    off = on = None
    for r in range(n_rounds):
        wall, off = _run_slo_arm(f"bench_slo_off{r}", False)
        off_wall += wall
        off_recompiles += off["recompiles"]
        wall, on = _run_slo_arm(f"bench_slo_on{r}", True)
        on_wall += wall
        on_recompiles += on["recompiles"]
    off["qps"] = round(n_rounds * n_requests / off_wall, 1)
    on["qps"] = round(n_rounds * n_requests / on_wall, 1)
    off["recompiles"], on["recompiles"] = off_recompiles, on_recompiles
    assert on.get("evals", 0) > 0, (
        "SLO evaluator never ticked during the measured arm"
    )
    assert on["budget_remaining"] > 0.0, (
        "error budget burned on an error-free workload"
    )
    ratio = round(on["qps"] / off["qps"], 4) if off["qps"] else None
    _emit(
        {
            "metric": f"serve_slo_engine_qps_ivf_flat_n{n // 1000}k_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "pipeline_depth": depth,
            "slo_on": on,
            "slo_off": off,
            "rounds": n_rounds,
            "qps_ratio": ratio,
            "overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "recompiles": on["recompiles"] + off["recompiles"],
            "requests": n_requests,
            "n": n,
        }
    )


def run_autotune_leg() -> None:
    """``python bench.py autotune`` — closed-loop autotuner A/B (CPU).

    Two arms run the identical paced-device serve workload through
    three phases — healthy, injected p99 breach (the paced device slows
    ``slow_mult``×, the "TPU neighbor got noisy" incident), healthy
    again:

    - ``off``: no controller — the breach persists for the whole slow
      phase (per-tick p99 stays over the latency target);
    - ``on``: an :class:`raft_tpu.obs.autotune.Autotuner` watches the
      index through its :class:`raft_tpu.serve.effort.EffortArbiter`;
      the ``slo_burn`` edge drives an effort descent (fewer probes →
      proportionally less device time) that restores p99 within the
      controller window, the measured recall EWMA holds ≥ the floor the
      whole run, and effort climbs back to full once the slowdown
      lifts.

    The per-level recall feeding the controller is *measured* up front
    (exact groundtruth vs the derived params at every warmed ladder
    level), not assumed.  Both arms assert zero post-warmup recompiles
    (every level was warmed); the on arm additionally asserts a
    correlated incident timeline carrying the ``slo_burn`` →
    ``autotune_step`` chain.  Frozen record:
    ``benchmarks/BENCH_autotune_r18.json``.
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import obs
    from raft_tpu.neighbors import effort as neighbors_effort
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import autotune as obs_autotune
    from raft_tpu.obs import incidents as obs_incidents
    from raft_tpu.obs import slo, slowlog
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.effort import EffortArbiter
    from raft_tpu.serve.metrics import ServingMetrics
    from raft_tpu.stats import recall_at_k

    n, d, k = 8192, 32, 10
    n_lists, base_probes = 64, 32
    reqs_per_tick = 32
    # the paced deadline is a FLOOR under the real jax dispatch (~25-35 ms
    # per full-effort batch on CPU), so the synthetic device pace must
    # dominate it for effort moves to be visible in latency
    device_ms = 40.0      # healthy device-plane ms per batch at full effort
    # the latency SLO counts whole histogram buckets (the evaluator reads
    # bucket totals, never reservoirs), so the target sits just above the
    # 204.8 ms bucket edge: healthy (~45 ms) and one-descent (~170 ms)
    # traffic is good, the injected breach (~320 ms) is not
    target_s = 0.205
    slow_mult = 8.0       # injected slowdown: 320 ms at level 0 breaches,
    #                       160 ms at level 1 clears — one descent suffices
    floor = 0.9
    max_level = 3
    healthy_ticks, slow_ticks, recover_ticks = 8, 12, 12

    obs.install()
    slowlog.configure(None)  # paced batches outlast the slow threshold
    rng = np.random.default_rng(0)
    # clustered corpus (mixture of gaussians): IVF recall stays high at
    # every ladder level, so the floor *gates* descent instead of
    # blocking it — uniform data would put the deep levels under 0.9
    centers = rng.random((n_lists, d), dtype=np.float32) * 10
    lab = rng.integers(0, n_lists, n)
    dataset = (centers[lab]
               + rng.normal(0, 1.0, (n, d))).astype(np.float32)
    qlab = rng.integers(0, n_lists, reqs_per_tick * 4)
    queries = (centers[qlab]
               + rng.normal(0, 1.0, (len(qlab), d))).astype(np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists), dataset)
    base_params = ivf_flat.SearchParams(n_probes=base_probes)

    # measured recall per warmed ladder level (exact numpy groundtruth):
    # the controller's quality input is real, precomputed once
    d2 = (
        (queries**2).sum(1)[:, None]
        + (dataset**2).sum(1)[None, :]
        - 2.0 * queries @ dataset.T
    )
    gt = np.argsort(d2, axis=1)[:, :k].astype(np.int32)
    spec = neighbors_effort.spec_for_params(base_params)
    recall_by_level = {}
    for level in range(max_level + 1):
        p = spec.degraded(level).apply(base_params)
        _, ids = ivf_flat.search(p, index, jnp.asarray(queries), k)
        recall_by_level[level] = float(recall_at_k(np.asarray(ids), gt))

    class _ServedIndex:
        """MutableIndex-shaped view: what the arbiter reads per dispatch."""

        def __init__(self, params):
            self.search_params = params
            self.kind = "ivf_flat"

    served = _ServedIndex(base_params)

    class _LevelRecallTap:
        """Auditor stand-in: reports the measured recall of the level
        the arbiter is actually serving at."""

        def __init__(self, arb):
            self._arb = arb

        def recall_ewma(self, name):
            return recall_by_level[self._arb.effective_level()]

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    slow = {"mult": 1.0}

    def make_paced_search(arb):
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            params = arb.apply(served) if arb is not None else None
            p = params if params is not None else base_params
            dist, ids = ivf_flat.search(p, index, batch, k)
            # device time tracks effort: fewer probes, less device work
            busy = (device_ms * 1e-3 * slow["mult"]
                    * p.n_probes / base_probes)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + busy
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    def run_arm(with_tuner: bool, tag: str) -> dict:
        arb = None
        if with_tuner:
            arb = EffortArbiter(None, max_level=max_level, name=tag)
        batcher = MicroBatcher(
            make_paced_search(arb), d, max_batch=reqs_per_tick,
            # 2 ms cut delay: each tick's 32 submits land in ONE full
            # batch, so per-request latency is the device pace, not a
            # second-batch queue wait straddling a bucket edge
            max_delay_ms=2.0, metrics=ServingMetrics(name=tag),
            pipeline_depth=1, effort=arb,
        )
        batcher.warmup()
        # settle ticks: a fresh batcher's first dispatches pay one-off
        # thread/dispatch cold-start (tens of ms).  They run BEFORE the
        # SLO spec exists — add_spec primes the counter baseline, so
        # cold-start latency never counts against the budget
        for _ in range(2):
            for f in [batcher.submit(queries[i % len(queries)])
                      for i in range(reqs_per_tick)]:
                f.result(timeout=120)
        engine = slo.SloEngine(
            [slo.SloSpec(f"{tag}-latency", tag, "latency",
                         objective=0.99, target=target_s)],
            eval_s=1.0, scale=1.0 / 600.0,
        )
        tuner = None
        tap = None
        if with_tuner:
            tap = _LevelRecallTap(arb)
            tuner = obs_autotune.Autotuner(
                eval_s=3600.0, recall_floor=floor,
                degrade_ticks=2, restore_ticks=6,
            )
            tuner.watch_index(tag, arb, auditor=tap, slo=engine)

        t_syn = 0.0
        ticks = []
        first_burn = None
        t_wall0 = time.perf_counter()
        for phase, n_ticks, mult in (
            ("healthy", healthy_ticks, 1.0),
            ("slow", slow_ticks, slow_mult),
            ("recover", recover_ticks, 1.0),
        ):
            slow["mult"] = mult
            for _ in range(n_ticks):
                t_syn += 1.0
                t0 = time.perf_counter()
                futs = [
                    batcher.submit(queries[i % len(queries)])
                    for i in range(reqs_per_tick)
                ]
                lat = []
                for f in futs:
                    f.result(timeout=120)
                    lat.append(time.perf_counter() - t0)
                engine.evaluate_once(now=t_syn)
                if tuner is not None:
                    tuner.evaluate_once(now=t_syn)
                burning = f"{tag}-latency" in engine.paging()
                if burning and first_burn is None:
                    first_burn = len(ticks)
                lvl = arb.autotune_level if arb is not None else 0
                ticks.append({
                    "phase": phase,
                    "min_ms": round(min(lat) * 1e3, 2),
                    "p99_ms": round(
                        sorted(lat)[max(0, int(0.99 * len(lat)) - 1)]
                        * 1e3, 2),
                    "level": lvl,
                    "burning": burning,
                    "recall": round(
                        recall_by_level[
                            arb.effective_level() if arb is not None
                            else 0], 4),
                })
        wall = time.perf_counter() - t_wall0
        st = batcher.metrics.snapshot()
        engine.stop()
        if tuner is not None:
            tuner.stop()
        batcher.stop()
        n_requests = reqs_per_tick * len(ticks)
        return {
            "qps": round(n_requests / wall, 1),
            "recompiles": st["recompiles"],
            "warmup_compiles": st["warmup_compiles"],
            "first_burn_tick": first_burn,
            "max_level": max(t["level"] for t in ticks),
            "final_level": ticks[-1]["level"],
            "min_recall": min(t["recall"] for t in ticks),
            "ticks": ticks,
        }

    run_arm(False, "bench_tune_warm")  # discarded: jit/thread warmth
    off = run_arm(False, "bench_tune_off")
    on = run_arm(True, "bench_tune_on")
    if os.environ.get("RAFT_TPU_BENCH_DEBUG"):
        for arm_tag, arm in (("off", off), ("on", on)):
            for i, t in enumerate(arm["ticks"]):
                print(f"  {arm_tag}[{i:2d}] {t['phase']:8s} "
                      f"min={t['min_ms']:8.2f} p99={t['p99_ms']:8.2f} "
                      f"level={t['level']} burn={t['burning']}",
                      file=sys.stderr)
            print(f"  {arm_tag} first_burn={arm['first_burn_tick']}",
                  file=sys.stderr)

    target_ms = target_s * 1e3
    slow_off = [t for t in off["ticks"] if t["phase"] == "slow"]
    slow_on = [t for t in on["ticks"] if t["phase"] == "slow"]
    rec_on = [t for t in on["ticks"] if t["phase"] == "recover"]

    # -- the A/B story, asserted before emitting ------------------------
    # off arm: the breach persists — most slow-phase ticks stay over
    # the target (all of them, absent scheduler noise)
    off_over = sum(1 for t in slow_off if t["p99_ms"] > target_ms)
    assert off_over >= len(slow_off) - 1, (
        f"off arm never breached: {off_over}/{len(slow_off)} slow ticks "
        "over target — the injected slowdown is broken"
    )
    # on arm: the controller shed effort...
    assert on["max_level"] > 0, "autotuner never stepped effort down"
    assert on["first_burn_tick"] is not None, "latency SLO never burned"
    # ...which restored p99 within the controller window (degrade_ticks
    # descents after the first burn, plus one tick for the pipeline to
    # drain the pre-descent pace)
    window = 4
    restored = None
    for i, t in enumerate(on["ticks"]):
        if (on["first_burn_tick"] is not None
                and i > on["first_burn_tick"] and t["phase"] == "slow"
                and t["p99_ms"] <= target_ms):
            restored = i - on["first_burn_tick"]
            break
    assert restored is not None and restored <= window, (
        f"on arm p99 not restored within {window} ticks of the burn: "
        f"{[t['p99_ms'] for t in slow_on]}"
    )
    # ...while measured recall held the floor the whole run...
    assert on["min_recall"] >= floor, (
        f"recall EWMA fell below the floor: {on['min_recall']} < {floor}"
    )
    # ...and effort climbed back to full once the slowdown lifted
    assert on["final_level"] == 0, (
        f"effort never climbed back: final level {on['final_level']}, "
        f"recover ticks {[(t['level'], t['p99_ms']) for t in rec_on]}"
    )
    # zero-recompile contract across the whole A/B: every ladder level
    # was warmed, so no effort move may compile on the hot path
    assert off["recompiles"] == 0 and on["recompiles"] == 0, (
        f"hot-path recompiles: off={off['recompiles']} "
        f"on={on['recompiles']}"
    )
    # the correlated incident: ONE incident's story contains both the
    # on-arm slo_burn and the autotune_step it provoked.  (The off arm
    # burns first and opens the incident; the on arm's events land in
    # the same still-fresh timeline — correlation by design, so the
    # chain is searched across trigger + timeline, not just the trigger.)
    chain = None
    mgr = obs_incidents.default_manager()
    for inc in mgr.open_incidents() + mgr.closed_incidents():
        doc = inc.to_dict()
        story = [doc.get("trigger", {})] + list(doc.get("timeline", []))
        burns = [e for e in story
                 if e.get("kind") == "slo_burn" and not e.get("recovered")
                 and e.get("index") == "bench_tune_on"]
        steps = [e for e in story
                 if e.get("kind") == "autotune_step"
                 and e.get("index") == "bench_tune_on"]
        if burns and steps:
            chain = {
                "incident_id": doc.get("id"),
                "trigger": "slo_burn",
                "autotune_steps": len(steps),
                "first_step_reason": steps[0].get("step_reason"),
            }
            break
    assert chain is not None, (
        "no incident correlates the slo_burn with an autotune_step"
    )

    # headline p99: the plateau right after restoration (the controller
    # re-probes full effort later in the slow phase, which is part of the
    # story but not a stable number to regress against)
    post = on["ticks"][on["first_burn_tick"] + restored:
                       on["first_burn_tick"] + restored + 3]
    recovery_p99 = max(t["p99_ms"] for t in post) if post else None
    _emit(
        {
            "metric": f"serve_autotune_closed_loop_ivf_flat_"
                      f"n{n // 1000}k_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "slow_mult": slow_mult,
            "target_ms": target_ms,
            "recall_floor": floor,
            "recall_by_level": {
                str(lv): round(r, 4) for lv, r in recall_by_level.items()
            },
            "restored_within_ticks": restored,
            "p99_ms": recovery_p99,
            "recall": on["min_recall"],
            "recompiles": off["recompiles"] + on["recompiles"],
            "incident_chain": chain,
            "autotune_on": {kk: vv for kk, vv in on.items()
                            if kk != "ticks"},
            "autotune_off": {kk: vv for kk, vv in off.items()
                             if kk != "ticks"},
            "on_levels": [t["level"] for t in on["ticks"]],
            "on_p99_ms": [t["p99_ms"] for t in on["ticks"]],
            "off_p99_ms": [t["p99_ms"] for t in off["ticks"]],
            "phases": {"healthy": healthy_ticks, "slow": slow_ticks,
                       "recover": recover_ticks},
        }
    )


def run_deep_leg() -> None:
    """``python bench.py deep`` — dataset-scale DEEP-geometry frontier.

    Runs the :mod:`raft_tpu.bench.frontier` sweep on the DEEP synthetic
    geometry (96-dim inner product) at ``RAFT_TPU_BENCH_DEEP_N`` rows
    (default 100K; the harness is 100M-capable — the sharded path
    (``RAFT_TPU_BENCH_DEEP_SHARDS``) builds via ``build_sharded`` so
    the corpus never has to fit one device), then emits the best
    serve-backend operating point at recall ≥ 0.9 plus the serialized
    :class:`~raft_tpu.obs.autotune.FrontierModel` the serving autotuner
    loads through ``RAFT_TPU_FRONTIER_PATH``.
    """
    import jax

    if os.environ.get("RAFT_TPU_BENCH_DEEP_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from raft_tpu.bench import frontier as frontier_mod

    n = int(os.environ.get("RAFT_TPU_BENCH_DEEP_N", "100000"))
    shards = int(os.environ.get("RAFT_TPU_BENCH_DEEP_SHARDS", "0"))
    n_queries = int(os.environ.get("RAFT_TPU_BENCH_DEEP_QUERIES", "1000"))
    k = 10
    ds = frontier_mod.make_dataset(
        "deep-image-96-inner", n, n_queries=n_queries, k=k,
    )
    n_rows, dim = int(ds.base.shape[0]), int(ds.base.shape[1])
    if shards:
        results = frontier_mod.sweep_sharded(
            ds, kinds=sorted(frontier_mod.SERVE_BACKENDS), k=k,
            n_devices=shards,
        )
    else:
        grids = frontier_mod.default_grids(
            n_rows, dim, ds.metric, comparators=False)
        results = frontier_mod.sweep(
            ds, grids, k=k,
            checkpoint_path=f"bench_deep_{n_rows}.json.partial",
        )
    model = frontier_mod.frontier_model(
        results, n_queries=n_queries,
        meta={"dataset": ds.name, "n": n_rows, "dim": dim, "k": k,
              "n_queries": n_queries, "metric": ds.metric,
              "sharded": shards,
              "platform": jax.devices()[0].platform},
    )
    out = os.environ.get("RAFT_TPU_BENCH_DEEP_OUT",
                         f"frontier_model_deep_{n_rows}.json")
    model.save(out)
    good = [r for r in results if r.recall >= 0.9] or results
    head = max(good, key=lambda r: r.qps)
    _emit(
        {
            "metric": f"deep_frontier_n{n_rows}_k{k}",
            "value": round(head.qps, 1),
            "unit": "queries/s",
            "platform": jax.devices()[0].platform,
            "recall": round(head.recall, 4),
            "algo": head.algo,
            "search_param": head.search_param,
            "sharded": shards,
            "frontier_path": out,
            "pareto_points": sum(
                len(p) for p in model.points.values()),
            "backends": model.backends(),
        }
    )


def run_compact_leg() -> None:
    """``python bench.py compact`` — online-compaction churn-soak A/B (CPU).

    Two arms run the identical upsert/delete/search churn (same rng
    stream) against a served brute-force index:

    - ``off``: no compactor — the side buffer and tombstones accrete, so
      side rows must grow monotonically (the failure mode the subsystem
      exists to remove);
    - ``on``: the compactor folds mutations back into the main structure
      whenever the side buffer crosses the trigger, so side rows and
      live index bytes stay bounded across every hot-swap.

    The headline value is the on-arm search QPS over the whole soak.  The
    line is garbage unless: on-arm max side rows stay within one trigger
    window, on-arm live bytes stay flat at the first compacted footprint,
    on-arm recall >= off-arm recall (both exact here, so equality), every
    promoted pass kept its projected peak under the memory budget, and
    on-arm hot-path recompiles read 0 after warmup — all asserted before
    emitting.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu import serve
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import slowlog
    from raft_tpu.serve.compactor import CompactionPolicy, Compactor
    from raft_tpu.stats import recall_at_k

    n, d, k = 3800, 32, 10
    cycles, churn_rows = 24, 128
    n_q = 64
    pol = CompactionPolicy(
        max_side_rows=256, max_tombstone_frac=0.25,
        interval_s=3600.0,           # deterministic: scan() driven per cycle
        chunk_rows=4096, gate_queries=64,
    )
    slowlog.configure(None)  # compaction passes outlast the slow threshold
    rng0 = np.random.default_rng(0)
    dataset = rng0.random((n, d), dtype=np.float32)
    queries = rng0.random((n_q, d), dtype=np.float32)

    def run_arm(compact: bool) -> dict:
        rng = np.random.default_rng(7)
        svc = serve.SearchService(k=k, max_batch=n_q, max_delay_ms=0.5,
                                  compaction=False)
        comp = Compactor(svc, pol, start=False) if compact else None
        svc.compactor = comp
        mi = serve.MutableIndex(brute_force.build(dataset))
        svc.add_index("churn", mi, warmup=True)
        live = {int(i): dataset[i] for i in range(n)}

        def churn():
            cur = svc.get("churn")
            rows = rng.random((churn_rows, d), dtype=np.float32)
            ids = [int(i) for i in cur.upsert(rows)]
            # oldest-first deletes: the off arm's deletes then always hit
            # main rows, so its side buffer growth is pure and monotone
            dead = sorted(live)[:churn_rows]
            cur.delete(dead)
            for i in dead:
                del live[i]
            for i, r in zip(ids, rows):
                live[i] = r
            return ids

        # warm phase (not measured): first churn establishes the mutation
        # variants; with the compactor on, the first pass also moves the
        # index to its pow2-padded steady-state shapes and warms them
        churn()
        if comp is not None:
            first = comp.trigger_now("churn")
            assert first["status"] == "promoted", first
        jax.block_until_ready(svc.search("churn", queries))
        svc._batcher("churn").metrics.reset_hot_path()

        side_series, bytes_series, lat = [], [], []
        base_bytes = svc.get("churn").device_bytes()
        for _cycle in range(cycles):
            churn()
            t0 = time.perf_counter()
            for _ in range(4):
                jax.block_until_ready(svc.search("churn", queries))
            lat.append((time.perf_counter() - t0) / 4)
            if comp is not None:
                comp.scan()
            _deletes, side = svc.get("churn").pending_mutations()
            side_series.append(side)
            bytes_series.append(svc.get("churn").device_bytes())

        # exact oracle over the tracked live set scores the final state
        ids_live = np.fromiter(live.keys(), np.int64, len(live))
        rows_live = np.stack([live[int(i)] for i in ids_live])
        _dd, oracle_rows = brute_force.knn(rows_live, queries, k)
        oracle = ids_live[np.asarray(oracle_rows)]
        _dd, got = svc.search("churn", queries)
        recall = float(recall_at_k(np.asarray(got), oracle))

        st = svc.stats("churn")
        snap = comp.snapshot() if comp is not None else {}
        last = snap.get("last_result") or {}
        if comp is not None:
            comp.stop()
        svc.stop()
        total_q = cycles * 4 * n_q
        return {
            "qps": round(total_q / sum(lat), 1),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3 / n_q, 3),
            "recall": round(recall, 4),
            "recompiles": st["recompiles"],
            "compactions": snap.get("compactions", 0),
            "max_side_rows": int(max(side_series)),
            "final_side_rows": int(side_series[-1]),
            "base_live_bytes": int(base_bytes),
            "max_live_bytes": int(max(bytes_series)),
            "side_rows_series": [int(s) for s in side_series],
            "peak_rebuild_bytes": last.get("projected_peak_bytes"),
            "budget_bytes": last.get("budget_bytes"),
        }

    on = run_arm(True)
    off = run_arm(False)

    # the claims the record freezes — fail loudly rather than freeze lies
    assert on["max_side_rows"] <= 2 * pol.max_side_rows, on
    assert on["max_live_bytes"] <= 1.5 * on["base_live_bytes"], on
    assert on["recall"] >= off["recall"], (on["recall"], off["recall"])
    assert on["recompiles"] == 0, on
    assert on["compactions"] >= 3, on
    assert on["peak_rebuild_bytes"] <= on["budget_bytes"], on
    off_side = off["side_rows_series"]
    assert all(b > a for a, b in zip(off_side, off_side[1:])), off_side
    assert off["final_side_rows"] >= cycles * churn_rows, off

    _emit(
        {
            "metric": f"serve_compact_churn_bf_n{n}_c{cycles}_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "arms": {"on": on, "off": off},
            "recall": on["recall"],
            "recompiles": on["recompiles"],
            "compactions": on["compactions"],
            "bounded_side_rows": on["max_side_rows"],
            "unbounded_side_rows": off["final_side_rows"],
            "trigger_side_rows": pol.max_side_rows,
            "headroom_frac": pol.headroom_frac,
            "n": n,
            "cycles": cycles,
            "churn_rows": churn_rows,
            "queries": n_q,
        }
    )


def run_obs_leg() -> None:
    """``python bench.py obs`` — the serve leg with the observability
    registry emitted alongside the QPS numbers (CPU).

    Same workload shape as ``serve`` but smaller, because the payload here
    is the *metrics*, not the throughput: the JSON line carries the
    process registry snapshot — span latency histograms for every traced
    entry point the workload crossed, XLA compiles attributed to the span
    that caused them, executable-cache hits, the queue/pad/dispatch/device
    stage breakdown, and the slow-query log.  One line answers "where did
    the milliseconds go" for a whole serving session.
    """
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu import obs, serve
    from raft_tpu.neighbors import ivf_flat

    obs.install()
    n, d, k = 4096, 64, 10
    n_requests, n_clients = 256, 4
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_requests, d), dtype=np.float32)

    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=32), dataset)
    svc = serve.SearchService(k=k, max_batch=32, max_delay_ms=0.5)
    svc.add_index(
        "bench", serve.MutableIndex(
            index, search_params=ivf_flat.SearchParams(n_probes=8)
        ),
        warmup=True,
    )

    def client(cid: int):
        futs = [
            svc.submit("bench", queries[i])
            for i in range(cid, n_requests, n_clients)
        ]
        for f in futs:
            f.result(timeout=120)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    st = svc.stats("bench")
    snap = svc.metrics()["registry"]
    svc.stop()
    compiles_by_span = snap["counters"].get("raft_tpu_xla_compiles_total", {})
    _emit(
        {
            "metric": f"obs_serve_qps_ivf_flat_n{n // 1000}k_k{k}",
            "value": round(n_requests / wall, 1),
            "unit": "queries/s",
            "platform": "cpu",
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "recompiles": st["recompiles"],
            "stages_ms": {
                s: {q: round(v, 3) for q, v in p.items()}
                for s, p in st["stages"].items()
            },
            "xla_compiles_by_span": compiles_by_span,
            "xla_cache": snap["counters"].get(
                "raft_tpu_xla_executable_cache_total", {}
            ),
            "span_histograms": sorted(
                key.split("=", 1)[1]
                for key in snap["histograms"].get(
                    "raft_tpu_span_seconds", {}
                )
            ),
            "slow_queries": len(snap["slow_queries"]["recent"]),
            "requests": n_requests,
        }
    )


def run_paged_leg() -> None:
    """``python bench.py paged`` — paged-vs-monolithic search A/B (CPU).

    Three arms over the same ivf_flat build, dispatched in identical
    small batches:

    * ``mono`` — the unpaged control (``RAFT_TPU_PAGED`` off is the
      production default, so this arm is the baseline every ratio is
      against);
    * ``paged_resident`` — the index paginated with an unconstrained
      budget, so every page fits the HBM hot pool: this is the ≤10%-
      overhead acceptance arm (page-table gather + per-dispatch
      coarse/residency bookkeeping is the only delta);
    * ``paged_overbudget`` — the hot pool deliberately sized *smaller*
      than the page set (slots < pages), which a monolithic index cannot
      serve at all; the clock pager demand-fetches each batch's probed
      pages, so this arm's QPS carries the host↔device paging tax and
      its eviction counters land in the payload.

    The paged gather is bit-identical to the monolithic gather for
    resident pages, so all three arms must return *identical* ids — that
    is asserted, not measured as recall.  Post-warmup recompiles must
    read 0 on the mono and resident arms: the hot pool is a static shape
    and the search executables never see the pager.  The over-budget arm
    is allowed a tiny straggler count — page-movement scatters are
    pow2-bucketed, so their compiled-shape universe is O(log pages) and
    a bucket the warmup happened not to hit may land in the timed loop —
    but the bound is asserted, so an unbounded retrace still fails.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve.metrics import compile_count, install_compile_listener
    from raft_tpu.store import MemoryBudget, paginate_index

    install_compile_listener()
    n, d, k = 32_768, 64, 10
    n_lists, n_probes = 128, 8
    page_rows = 128
    batch, n_batches = 8, 32  # small batches keep each probed-page union
    n_q = batch * n_batches   # well under the over-budget arm's hot pool
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    queries = rng.random((n_q, d), dtype=np.float32)
    sp = ivf_flat.SearchParams(n_probes=n_probes)

    def build():
        # deterministic seed → every arm's build is structurally identical
        return ivf_flat.build(ivf_flat.IndexParams(n_lists=n_lists), dataset)

    def measure(index, iters=3):
        """(qps, ids, recompiles) over the batched dispatch driver."""
        def one_pass():
            out = [
                ivf_flat.search(
                    sp, index, queries[b * batch:(b + 1) * batch], k
                )[1]
                for b in range(n_batches)
            ]
            jax.block_until_ready(out)
            return np.concatenate([np.asarray(i) for i in out])

        ids = one_pass()  # warmup: compiles + first residency faults land
        # warm until compile-stable: the pager's pow2-bucketed movement
        # scatters compile lazily per padded size, so run passes until a
        # full pass adds no executables (bounded — the bucket set is
        # O(log pages))
        for _ in range(10):
            c = compile_count()
            one_pass()
            if compile_count() == c:
                break
        c0 = compile_count()
        t0 = time.perf_counter()
        for _ in range(iters):
            one_pass()
        t = (time.perf_counter() - t0) / iters
        return round(n_q / t, 1), ids, compile_count() - c0

    arms = {}
    idx_mono = build()
    arms["mono"] = {}
    arms["mono"]["qps"], base_ids, arms["mono"]["recompiles"] = measure(
        idx_mono
    )

    idx_res = build()
    t_res = paginate_index(
        idx_res, page_rows=page_rows, budget=None, name="bench:resident"
    )
    arms["paged_resident"] = {}
    arms["paged_resident"]["qps"], ids_res, arms["paged_resident"][
        "recompiles"
    ] = measure(idx_res)
    assert t_res.slots == t_res.n_pages, t_res.stats()
    assert np.array_equal(ids_res, base_ids), (
        "paged_resident ids diverged from the monolithic control"
    )

    # over-budget: grant the pager ~60% of the page set — the budget
    # formula is the TieredStore admission formula run backwards, so the
    # slot count is exact, not approximate
    idx_over = build()
    ppl = -(-idx_over.list_data.shape[1] // page_rows)
    n_pages = n_lists * ppl
    page_bytes = page_rows * d * 4
    slots = int(0.6 * n_pages)
    budget = MemoryBudget(slots * page_bytes + 4 * n_pages)
    t_over = paginate_index(
        idx_over, page_rows=page_rows, budget=budget, name="bench:overbudget"
    )
    assert t_over.slots == slots < t_over.n_pages, t_over.stats()
    arms["paged_overbudget"] = {}
    arms["paged_overbudget"]["qps"], ids_over, arms["paged_overbudget"][
        "recompiles"
    ] = measure(idx_over)
    assert np.array_equal(ids_over, base_ids), (
        "paged_overbudget ids diverged from the monolithic control"
    )
    st = t_over.stats()
    arms["paged_overbudget"]["slots"] = st["slots"]
    arms["paged_overbudget"]["pages"] = st["n_pages"]
    arms["paged_overbudget"]["evictions"] = st["evictions"]
    arms["paged_overbudget"]["misses"] = st["misses"]
    arms["paged_overbudget"]["hits"] = st["hits"]

    for name, a in arms.items():
        limit = 4 if name == "paged_overbudget" else 0
        assert a["recompiles"] <= limit, (
            f"hot path recompiled after warmup ({name}): {arms}"
        )
    overhead = 100.0 * (
        1.0 - arms["paged_resident"]["qps"] / arms["mono"]["qps"]
    )
    _emit(
        {
            "metric": f"paged_ab_qps_ivf_flat_n{n // 1024}k_k{k}",
            "value": arms["paged_resident"]["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "arms": arms,
            "resident_overhead_pct": round(overhead, 1),
            "ids_identical": True,
            "recompiles": sum(a["recompiles"] for a in arms.values()),
            "page_rows": page_rows,
            "n": n,
            "n_lists": n_lists,
            "n_probes": n_probes,
            "queries": n_q,
        }
    )


def run_kernels_leg() -> None:
    """``python bench.py kernels`` — select_k + CAGRA XLA-vs-Pallas A/B
    (CPU, interpret mode).

    Off-TPU the Pallas kernels run in interpret mode, which lowers the
    kernel *body* through XLA — so this leg is an **algorithmic** A/B:
    the same masked-extraction / fused-hop formulations the TPU runs,
    wall-clocked honestly against their XLA twins on CPU.  Interpret
    mode serializes the grid (one (query, parent) step at a time), so
    the benched shapes sit where the kernels' structural wins dominate
    that serialization tax rather than at TPU-preferred tilings:

    - **select_k (stable)**: the serving-merge discipline — two-key
      smallest-id-wins selection with ``input_indices`` — at a tiled
      brute-force merge shape (32 query rows x 8192 pooled candidates,
      k=32).  The XLA twin pays a full-width two-key ``lax.sort``; the
      kernel pays k masked min-extraction rounds over a VMEM-resident
      row.  Parity is asserted **bitwise** (the kernel's routing
      contract).  The positional variant is not wall-clocked here: on
      CPU ``lax.top_k`` is a fast partial selection, so the interpret
      number would say nothing about the TPU sort-based lowering it
      replaces.
    - **cagra_traverse**: a wide-beam regime (itopk=width=128, deg=64,
      3 hops) where the XLA hop's ``[t, w*deg, d]`` dataset-gather copy
      and its (itopk + w*deg)-wide two-key merge sort dominate — the
      exact HBM traffic the fused hop exists to delete.  Parity is
      asserted as recall equivalence plus row-wise distance agreement
      (the fused hop's contract; ids may swap only across exact ties).

    Both arms of both A/Bs self-assert zero post-warmup recompiles, and
    each arm records the ``kernel_path`` it stamped.  A final serving
    phase drives a CAGRA-backed ``SearchService`` with the kernels
    enabled and asserts the PerfLedger attributes its device seconds to
    a ``kernel_path="pallas"`` hotspot key with a measured roofline —
    the record's top-level ``kernel_path`` stamps ``pallas: true``.
    Gated by ``bench.py compare`` against the frozen record
    (``benchmarks/BENCH_kernels_r15.json``).
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import kernels, obs, serve
    from raft_tpu.bench.export import kernel_path
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.obs import perf
    from raft_tpu.ops import matrix
    from raft_tpu.serve.metrics import compile_count

    obs.install()
    rng = np.random.default_rng(15)
    saved_pallas = os.environ.get("RAFT_TPU_PALLAS")

    def measure(fn, *args, iters=5):
        """(mean_seconds, outputs) with a zero-recompile self-assert:
        warmup compiles, the timed iterations must not."""
        for _ in range(2):
            out = jax.block_until_ready(fn(*args))
        c0 = compile_count()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        assert compile_count() - c0 == 0, "timed iterations recompiled"
        return dt, out

    # -- select_k (stable serving-merge discipline) --------------------------
    rows, n, k = 32, 8192, 32
    s = jnp.asarray(np.round(rng.standard_normal((rows, n)) * 3).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 1_000_000, size=(rows, n)).astype(np.int32))

    def sk_arm(pallas: bool):
        # a fresh jit closure per arm: the routing branch is resolved at
        # trace time from the env, exactly like the serving call sites
        os.environ["RAFT_TPU_PALLAS"] = "1" if pallas else "0"
        fn = jax.jit(lambda sc, si: matrix.select_k_stable(sc, k, input_indices=si))
        dt, (v, i) = measure(fn, s, ids)
        return dt, np.asarray(v), np.asarray(i)

    t_sk_xla, v0, i0 = sk_arm(False)
    t_sk_pal, v1, i1 = sk_arm(True)
    np.testing.assert_array_equal(v0, v1)  # bitwise: values
    np.testing.assert_array_equal(i0, i1)  # bitwise: ids
    sk_speedup = t_sk_xla / t_sk_pal
    assert sk_speedup > 1.0, (
        f"select_k pallas arm did not beat its XLA twin: {sk_speedup:.2f}x"
    )

    # -- cagra_traverse (wide-beam fused hop) --------------------------------
    nd, d, n_q, kq = 8000, 192, 8, 10
    x = rng.normal(size=(nd, d)).astype(np.float32)
    q = x[rng.choice(nd, n_q, replace=False)] + rng.normal(
        0, 0.3, (n_q, d)
    ).astype(np.float32)
    built = cagra.build(
        cagra.IndexParams(
            intermediate_graph_degree=96, graph_degree=64,
            build_algo="brute_force",
        ),
        x,
    )
    _, gt = brute_force.knn(x, q, kq)
    sp = cagra.SearchParams(itopk_size=128, search_width=128, max_iterations=3)

    def cagra_arm(pallas: bool):
        os.environ["RAFT_TPU_PALLAS"] = "1" if pallas else "0"
        dt, (dist, idx) = measure(
            lambda qq: cagra.search(sp, built, qq, kq), q, iters=3
        )
        stamped = kernels.consume_kernel_path()
        assert stamped == ("pallas" if pallas else "xla"), stamped
        return dt, np.asarray(dist), np.asarray(idx), stamped

    t_cg_xla, d0, c0, path0 = cagra_arm(False)
    t_cg_pal, d1, c1, path1 = cagra_arm(True)

    def recall(idx):
        hits = sum(
            len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(idx, np.asarray(gt))
        )
        return hits / gt.size

    r0, r1 = recall(c0), recall(c1)
    assert abs(r0 - r1) <= 0.02, (r0, r1)
    np.testing.assert_allclose(d0, d1, rtol=1e-5, atol=1e-5)
    cg_speedup = t_cg_xla / t_cg_pal
    assert cg_speedup > 1.0, (
        f"cagra pallas arm did not beat its XLA twin: {cg_speedup:.2f}x"
    )

    # -- serving-path attribution: pallas keys in the perf ledger ------------
    os.environ["RAFT_TPU_PALLAS"] = "1"
    svc = serve.SearchService(k=kq, max_batch=8, min_bucket=8, max_delay_ms=0.5)
    svc.add_index("kernels_bench", built, warmup=True)
    futs = [svc.submit("kernels_bench", q[i % n_q : i % n_q + 2]) for i in range(24)]
    svc.flush("kernels_bench")
    for f in futs:
        f.result(timeout=300)
    st = svc.stats("kernels_bench")
    assert st["recompiles"] == 0, st
    mine = [
        h for h in perf.default_ledger().top_hotspots(n=64)
        if h["index"] == "kernels_bench"
    ]
    assert mine, "served cagra executable never showed up as a hotspot"
    pal = [h for h in mine if h["kernel_path"] == "pallas"]
    assert pal, f"no pallas-keyed hotspot rows: {[h['kernel_path'] for h in mine]}"
    assert all(h["backend"] == "cagra" for h in pal), pal
    dev_s = sum(h["device_s"] for h in pal)
    assert dev_s > 0.0, pal
    utils = [
        h["roofline_utilization"] for h in pal
        if h.get("roofline_utilization") is not None
    ]
    assert utils and all(0.0 < u <= 1.0 for u in utils), (
        f"pallas keys missing a measured roofline in (0, 1]: {utils}"
    )
    svc.stop()
    if saved_pallas is None:
        os.environ.pop("RAFT_TPU_PALLAS", None)
    else:
        os.environ["RAFT_TPU_PALLAS"] = saved_pallas

    _emit(
        {
            "metric": f"kernels_cagra_pallas_qps_n{nd // 1000}k_d{d}_w128",
            "value": round(n_q / t_cg_pal, 2),
            "unit": "queries/s",
            "platform": "cpu",
            "recall": round(r1, 4),
            "recompiles": 0,
            "interpret_mode": True,
            "select_k": {
                "rows": rows, "n": n, "k": k,
                "xla": {"ms": round(t_sk_xla * 1e3, 3), "kernel_path": "xla"},
                "pallas": {"ms": round(t_sk_pal * 1e3, 3), "kernel_path": "pallas"},
                "speedup": round(sk_speedup, 3),
                "parity": "bitwise",
            },
            "cagra_traverse": {
                "n": nd, "d": d, "n_q": n_q, "graph_degree": 64,
                "itopk": 128, "search_width": 128, "max_iterations": 3,
                "xla": {"ms": round(t_cg_xla * 1e3, 3), "kernel_path": path0,
                        "recall": round(r0, 4)},
                "pallas": {"ms": round(t_cg_pal * 1e3, 3), "kernel_path": path1,
                           "recall": round(r1, 4)},
                "speedup": round(cg_speedup, 3),
                "parity": "recall+distances",
            },
            "serving": {
                "backend": "cagra",
                "pallas_hotspot_device_s": round(dev_s, 6),
                "roofline_utilization": round(max(utils), 6),
                "recompiles": st["recompiles"],
            },
            "kernel_path": kernel_path(pallas=True),
        }
    )


def run_perf_leg() -> None:
    """``python bench.py perf`` — measured perf-ledger A/B + evidence
    chain (CPU).

    Phase A (overhead): a paced-device serve workload at pipeline depth
    2, run as interleaved ledger-off/ledger-on rounds with pooled walls.
    Unlike the ``slo`` leg this one paces a *tiny* (256-row) search so
    the 10 ms device model dominates the wall: the real ivf_flat compute
    swings 3-5x with CPU co-tenancy on CI hosts, which would drown a 2%
    claim in scheduler noise (measured: identical arms ranged
    0.68-4.7 s).  The ledger's per-dispatch cost is float math plus
    three counter bumps riding the batcher's existing device-stage
    stamps (zero new clock calls), so the acceptance bar is <2% QPS
    overhead, with zero hot-path recompiles in both arms — gated by
    ``bench.py compare`` against the frozen record.

    Phase B (attribution): a real brute-force SearchService whose ledger
    rows must self-report sanely before the record freezes: the served
    executable shows up as a hotspot keyed ``(index, backend, bucket,
    kernel_path, version)`` with ``kernel_path="xla"`` (brute force has
    no Pallas leg), its measured roofline utilization lands in (0, 1],
    its device seconds reconcile with the metrics device-stage totals,
    and ``top_hotspots`` comes back ranked by cumulative device seconds.

    Phase C (regression chain): a served search fn forced ~8x slower
    mid-run by *chaining extra device dispatches* (a host sleep would
    land in the dispatch stage and the detector reads device time).  The
    per-key EWMA detector must publish exactly one debounced
    ``perf_regression``, auto-trigger exactly one profiler capture, and
    land inside exactly one correlated incident carrying the capture on
    its timeline — all asserted before the JSON line is emitted.
    """
    import tempfile
    import threading

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from raft_tpu import obs, serve
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import events, perf, profiler, slowlog
    from raft_tpu.obs import incidents as obs_incidents
    from raft_tpu.serve.batcher import MicroBatcher
    from raft_tpu.serve.metrics import ServingMetrics

    os.environ.setdefault("RAFT_TPU_PERF_CAPTURE_S", "0.2")
    os.environ.setdefault(
        "RAFT_TPU_PERF_CAPTURE_DIR", tempfile.mkdtemp(prefix="raft_perf_")
    )
    obs.install()
    slowlog.configure(None)  # open-loop flood: queue waits are the workload

    n, d, k = 8192, 64, 10
    n_requests, n_clients, depth = 2048, 4, 2
    device_ms = float(os.environ.get("RAFT_TPU_BENCH_DEVICE_MS", "10"))
    rng = np.random.default_rng(0)
    dataset = rng.random((n, d), dtype=np.float32)
    tiny = rng.random((256, d), dtype=np.float32)  # pacing-dominated arm
    queries = rng.random((n_requests, d), dtype=np.float32)

    class _Paced:
        __slots__ = ("arr", "deadline")

        def __init__(self, arr, deadline: float):
            self.arr = arr
            self.deadline = deadline

        def block_until_ready(self):
            jax.block_until_ready(self.arr)
            rest = self.deadline - time.perf_counter()
            if rest > 0:
                time.sleep(rest)  # releases the GIL, like a TPU RPC
            return self

        def __array__(self, dtype=None):
            a = np.asarray(self.arr)
            return a if dtype is None else a.astype(dtype)

    def make_paced_search():
        lock = threading.Lock()
        state = {"free": 0.0}

        def search_fn(batch):
            dist, ids = brute_force.knn(tiny, batch, k)
            with lock:
                start = max(time.perf_counter(), state["free"])
                state["free"] = deadline = start + device_ms * 1e-3
            return _Paced(dist, deadline), _Paced(ids, deadline)

        return search_fn

    # -- Phase A: ledger-on/off overhead A/B ---------------------------------
    def run_overhead_arm(name: str, ledger_on: bool) -> tuple:
        # the batcher samples perf.enabled() ONCE at construction — the
        # off arm holds no ledger reference at all, not a per-call gate
        if ledger_on:
            os.environ.pop("RAFT_TPU_PERF_LEDGER", None)
        else:
            os.environ["RAFT_TPU_PERF_LEDGER"] = "0"
        batcher = MicroBatcher(
            make_paced_search(), d, max_batch=32, max_delay_ms=0.5,
            metrics=ServingMetrics(name=name), pipeline_depth=depth,
        )
        assert (batcher._perf is not None) == ledger_on
        batcher.warmup()

        def client(cid: int):
            futs = [
                batcher.submit(queries[i])
                for i in range(cid, n_requests, n_clients)
            ]
            for f in futs:
                f.result(timeout=300)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st = batcher.metrics.snapshot()
        batcher.stop()
        return wall, {
            "p50_ms": round(st["p50_ms"], 3) if st["p50_ms"] else None,
            "p99_ms": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            "batches": st["batches"],
            "recompiles": st["recompiles"],
        }

    run_overhead_arm("bench_perf_warm", True)  # discarded: jit/thread warmth
    n_rounds = int(os.environ.get("RAFT_TPU_BENCH_PERF_ROUNDS", "3"))
    off_wall = on_wall = 0.0
    off_recompiles = on_recompiles = 0
    off = on = None
    for r in range(n_rounds):
        wall, off = run_overhead_arm(f"bench_perf_off{r}", False)
        off_wall += wall
        off_recompiles += off["recompiles"]
        wall, on = run_overhead_arm(f"bench_perf_on{r}", True)
        on_wall += wall
        on_recompiles += on["recompiles"]
    os.environ.pop("RAFT_TPU_PERF_LEDGER", None)  # ledger on for B and C
    off["qps"] = round(n_rounds * n_requests / off_wall, 1)
    on["qps"] = round(n_rounds * n_requests / on_wall, 1)
    off["recompiles"], on["recompiles"] = off_recompiles, on_recompiles
    assert on["recompiles"] == 0 and off["recompiles"] == 0, (on, off)
    ratio = round(on["qps"] / off["qps"], 4) if off["qps"] else None

    # -- Phase B: live attribution on a real served index --------------------
    svc = serve.SearchService(k=k, max_batch=32, max_delay_ms=0.5,
                              pipeline_depth=depth)
    svc.add_index("perf_bench", brute_force.build(dataset), warmup=True)
    futs = [svc.submit("perf_bench", queries[i : i + 2]) for i in range(128)]
    svc.flush("perf_bench")
    for f in futs:
        f.result(timeout=300)
    st = svc.stats("perf_bench")
    assert st["recompiles"] == 0, st
    led = perf.default_ledger()
    hotspots = led.top_hotspots(n=64)
    ranks = [h["device_s"] for h in hotspots]
    assert ranks == sorted(ranks, reverse=True), "hotspots not ranked"
    mine = [h for h in hotspots if h["index"] == "perf_bench"]
    assert mine, "served executable never showed up as a hotspot"
    assert all(
        h["backend"] == "brute_force" and h["kernel_path"] == "xla"
        and h["version"] == "1" for h in mine
    ), mine
    utils = [
        h["roofline_utilization"] for h in mine
        if h.get("roofline_utilization") is not None
    ]
    assert utils and all(0.0 < u <= 1.0 for u in utils), (
        f"measured roofline out of (0, 1]: {utils}"
    )
    tot = led.totals()["perf_bench"]
    dev_stage = svc._batcher("perf_bench").metrics.stage_totals()["device"]
    assert abs(tot["device_s"] - dev_stage) <= 1e-6 * max(dev_stage, 1e-9), (
        tot, dev_stage,
    )
    svc.stop()

    # -- Phase C: forced slowdown → regression → capture → incident ----------
    fired = []
    events.subscribe(
        lambda e: fired.append(e), kinds=frozenset({"perf_regression"})
    )
    slow_mode = {"on": False}

    def reg_fn(q):
        dist, ids = brute_force.knn(dataset, q, k)
        if slow_mode["on"]:
            for _ in range(7):
                # data dependency chains the dispatches, so the slowdown
                # is device work the batcher's device stage measures
                q = q + dist[:, :1] * 0.0
                dist, ids = brute_force.knn(dataset, q, k)
        return dist, ids

    reg = MicroBatcher(
        reg_fn, d, max_batch=4, start=False,
        metrics=ServingMetrics(name="perf_reg"), pipeline_depth=1,
        perf_meta=lambda: ("brute_force", "1"),
    )
    reg.warmup()

    def drive(count: int):
        for i in range(count):
            fut = reg.submit(queries[i])
            reg.flush()
            fut.result(timeout=300)

    drive(40)              # stable baseline, arms the detector (>=32)
    slow_mode["on"] = True
    drive(20)              # ~8x device time: trips on every record
    reg.stop()
    assert len(fired) == 1, (
        f"expected exactly one debounced perf_regression, got {len(fired)}"
    )
    assert fired[0].reason == "perf_regression_perf_reg"
    cap = profiler.last_capture()
    assert cap is not None and cap["reason"] == "perf_regression_perf_reg"
    mgr = obs_incidents.default_manager()
    # the event lands in exactly ONE correlated incident (it may have
    # joined an incident another trigger opened inside the window rather
    # than opening its own — either way the capture rides its timeline)
    incs = [
        i.to_dict() for i in mgr.open_incidents() + mgr.closed_incidents()
    ]
    hits = [
        inc for inc in incs
        if any(
            t.get("kind") == "perf_regression"
            and t.get("reason") == "perf_regression_perf_reg"
            for t in inc["timeline"]
        )
    ]
    assert len(hits) == 1, [i["reason"] for i in incs]
    inc = hits[0]
    assert any(
        t.get("kind") == "profile_capture" and t.get("path") == cap["path"]
        for t in inc["timeline"]
    ), inc["timeline"]
    time.sleep(0.4)  # let the async capture's stop timer close the trace

    reg_key = [h for h in led.top_hotspots(n=64) if h["index"] == "perf_reg"]
    _emit(
        {
            "metric": f"serve_perf_ledger_qps_bf_n{n // 1000}k_k{k}",
            "value": on["qps"],
            "unit": "queries/s",
            "platform": "cpu",
            "device_ms": device_ms,
            "pipeline_depth": depth,
            "rounds": n_rounds,
            "ledger_on": on,
            "ledger_off": off,
            "qps_ratio": ratio,
            "overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "recompiles": on["recompiles"] + off["recompiles"],
            "hotspot": {
                key: mine[0][key]
                for key in ("index", "backend", "bucket", "kernel_path",
                            "version", "dispatches", "wasted_frac")
            },
            "roofline_utilization": round(max(utils), 6),
            "regression_chain": {
                "events": len(fired),
                "ratio": round(float(fired[0].fields["ratio"]), 2),
                "capture": cap["path"] is not None,
                "incident": True,
                "regressions_on_key": sum(
                    h["regressions"] for h in reg_key
                ),
            },
            "requests": n_requests,
            "n": n,
            "kernel_path": _serve_kernel_path(),
        }
    )


def run_analyze_leg() -> None:
    """``python bench.py analyze`` — static-analysis smoke (host only).

    Runs every :mod:`raft_tpu.analysis` checker over the package and
    records the wall time, so the "analysis stays interactive" budget
    (<10 s on CPU, enforced by tests/test_static_analysis.py) has a
    tracked number per round alongside the perf legs.  Exits nonzero and
    prints the rendered findings to stderr if any invariant is violated
    — the same contract as ``python -m raft_tpu.analysis``.
    """
    from raft_tpu.analysis import run_analysis

    t0 = time.perf_counter()
    result = run_analysis()
    wall = time.perf_counter() - t0
    _emit(
        {
            "metric": "static_analysis_wall_s",
            "value": round(wall, 3),
            "unit": "s",
            "platform": "host",
            "findings": len(result.findings),
            "suppressed": len(result.suppressed),
            "stats": dict(sorted(result.stats.items())),
        }
    )
    if result.findings:
        for f in result.sorted_findings():
            print(f.render(), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
