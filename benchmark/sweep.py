"""One-off sweeps that fix a cell's parameters, kept so a later benchmark
PR can make them again.  One process, one build, many settings:

    python3 benchmark/sweep.py --config sift1m-ivfflat --n-probes 1,5,10,20,50,100
    python3 benchmark/sweep.py --config deep1m-ivfpq --traffic online --rates 500,1000,2000

``--n-probes`` serves every query of the pool through the index's own
search at each ``n_probes``, in 1,000-row batches, and prints recall@10
against the plain reference.  ``--rates`` serves the traffic mix through a
``SearchService`` at each offered rate for ``--seconds`` and prints the
completed rate and the latency tail, and whether the queue grew: the
latency of the last tenth of the requests against the first tenth.
The knee is the highest rate at which the queue does not grow.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(os.path.abspath(__file__))]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402


def emit(rec: dict) -> None:
    print("SWEEP " + json.dumps(rec), flush=True)


def sweep_n_probes(cfg, seed, values):
    import jax
    import numpy as np

    from benchmark.lib import check, data, reference

    base, queries = data.make(cfg, seed)
    pool = np.asarray(queries)
    algo = bench_run.load_module(
        os.path.join(bench_run.HERE, "algos", cfg["index"]["kind"] + ".py"), "algo")
    t0 = time.perf_counter()
    mi, shapes = algo.build(cfg, base)
    emit({"build_s": time.perf_counter() - t0, **shapes})
    truth = reference.search(base, pool, cfg["k"], cfg["metric"])[1]
    kind = cfg["index"]["kind"]
    mod = __import__(f"raft_tpu.neighbors.{kind}", fromlist=["SearchParams"])
    for p in values:
        params = mod.SearchParams(**dict(cfg["index"]["search"], n_probes=p))
        ids = []
        for warm in (True, False):
            t0 = time.perf_counter()
            ids = []
            for s in range(0, pool.shape[0], 1000):
                _, i = mi.search(pool[s:s + 1000], cfg["k"], search_params=params)
                ids.append(np.asarray(jax.block_until_ready(i)))
            dt = time.perf_counter() - t0
        ids = np.concatenate(ids)
        emit({"n_probes": p, "recall_at_10": check.recall(
            ids, np.arange(pool.shape[0]), truth),
            "seconds_10k_queries": dt})


def sweep_rates(cfg, traffic, seed, rates, seconds):
    import numpy as np

    from benchmark.lib import data
    from benchmark.lib import traffic as traffic_lib
    from raft_tpu import serve

    base, queries = data.make(cfg, seed)
    pool = np.asarray(queries)
    algo = bench_run.load_module(
        os.path.join(bench_run.HERE, "algos", cfg["index"]["kind"] + ".py"), "algo")
    mi, _ = algo.build(cfg, base)
    svc = serve.SearchService(
        k=int(cfg["k"]), min_bucket=int(traffic["min_bucket"]),
        max_batch=int(traffic["max_batch"]),
        max_delay_ms=float(traffic["max_delay_ms"]), cost_accounting=False)
    try:
        svc.add_index("cell", mi, warmup=True)

        def submit(rows):
            return svc.submit("cell", rows)

        traffic_lib.warm_buckets(submit, pool, traffic)
        traffic_lib.run(submit, pool, dict(traffic, rate=rates[0]), 1.0, seed)
        counter = bench_run.CompileCounter()
        for rate in rates:
            mark = (counter.count, counter.hits, counter.seconds)
            b0 = svc.stats("cell")["batches"]
            win = traffic_lib.run(submit, pool, dict(traffic, rate=rate),
                                  seconds, seed)
            b1 = svc.stats("cell")["batches"]
            lat = np.array([r.latency_s() for r in win.requests]) * 1e3
            late = np.array([r.t_send - r.t_sched for r in win.requests]) * 1e3
            tenth = max(1, len(lat) // 10)
            ok = sum(r.ok for r in win.requests)
            emit({"rate": rate, "requests": len(lat), "answered": ok,
                  "completed_per_s": ok / (win.t_last - win.t0),
                  "p50_ms": float(np.percentile(lat, 50)),
                  "p95_ms": float(np.percentile(lat, 95)),
                  "p99_ms": float(np.percentile(lat, 99)),
                  "first_tenth_ms": float(np.median(lat[:tenth])),
                  "last_tenth_ms": float(np.median(lat[-tenth:])),
                  "late_p95_ms": float(np.percentile(late, 95)),
                  "rows_per_dispatch": ok / max(1, b1 - b0),
                  **counter.since(mark)[0]})
    finally:
        svc.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--n-probes", default="")
    ap.add_argument("--traffic", default="online")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    bench_run.prepare_env()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bench_run.chips_of(1, True)
    bench = bench_run.load_json(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    entry = bench_run.by_name(bench["configs"], args.config, "config")
    cfg = bench_run.load_json(os.path.join(bench_run.ROOT, entry["file"]))
    if args.n_probes:
        sweep_n_probes(cfg, args.seed, [int(x) for x in args.n_probes.split(",")])
    if args.rates:
        traffic = bench_run.load_json(os.path.join(
            bench_run.HERE, "traffic", args.traffic + ".json"))
        sweep_rates(cfg, traffic, args.seed,
                    [float(x) for x in args.rates.split(",")], args.seconds)


if __name__ == "__main__":
    main()
