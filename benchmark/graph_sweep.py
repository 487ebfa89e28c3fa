"""Readings that fix a graph cell's ``itopk_size`` and its ``correct``
limits, and hold its served rows to the plain beam search, on the chip at
the configuration's size:

    python3 benchmark/graph_sweep.py --workload sift1m-cagra.bulk --seed 7 \
        --itopk 64,128,256,512
    python3 benchmark/graph_sweep.py --workload sift1m-cagra.bulk \
        --seed 2147483659 --compare 1000

- ``--itopk``: the cell run through ``run.run_cell`` at each
  ``itopk_size`` in turn, as ``control.py`` runs its faults, each run's
  served answers judged by ``check.judge``: ``correct``, recall@10 and
  every check beside its limit, and the run's metrics.  The sweep stops
  at the lowest setting whose recall@10 reaches ``--target``, the cell's
  ``itopk_size``; the one before it, half of it, is the planted fault.
- ``--compare m``: the cell's index built by its algorithm, the cell's
  traffic served through a ``SearchService`` for ``--seconds``, the
  window judged as a run's is, and ``m`` answered rows of it held to the
  plain beam search (``lib/beam_reference.py``) given the index's graph
  and the seed ids their dispatch used: rows whose ids agree, rows that
  differ only at distance ties, the reference's expansions and the widest
  distance gap.

Each reading is one ``SWEEP`` line on stdout, with the device it ran on
and the configuration's ``n`` and ``d``.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(os.path.abspath(__file__))]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402

#: a distance gap beyond this share of the pair's scale is a disagreement
#: (the f32 rounding of both sides reads ~3e-7 here; bf16 rows ~1e-4)
DIST_TOL = 1e-5

INDEX_NAME = "graph"


def emit(rec: dict, cfg: dict, device) -> None:
    print("SWEEP " + json.dumps({"device": device.device_kind,
                                 "n": int(cfg["n"]), "d": int(cfg["d"]),
                                 **rec}), flush=True)


def with_itopk(cfg: dict, itopk: int) -> dict:
    """``cfg`` searched at ``itopk_size`` ``itopk``."""
    cfg = copy.deepcopy(cfg)
    cfg["index"]["search"]["itopk_size"] = int(itopk)
    return cfg


def sweep_itopk(cell, cfg, traffic, specs, seed, seconds, values, target,
                device):
    chosen = None
    for itopk in values:
        result, checks = bench_run.run_cell(
            with_itopk(cfg, itopk), traffic, int(cell["chips"]), seed,
            seconds, False, specs)
        recall = 1.0 - checks["recall_miss"]["value"]
        if chosen is None and recall >= target:
            chosen = itopk
        emit({"itopk": itopk, "seed": seed, "recall_at_10": recall,
              "correct": result["correct"], "checks": checks,
              "metrics": result["metrics"]}, cfg, device)
        if chosen is not None:
            break  # the larger settings cost more and are not needed
    emit({"chosen_itopk": chosen, "target": target}, cfg, device)
    return chosen


def serve_window(cfg, traffic, seed, seconds):
    """The cell's index and one window of its traffic through a
    ``SearchService``: (MutableIndex, base rows, query pool, window)."""
    import numpy as np

    from benchmark.lib import data
    from benchmark.lib import traffic as traffic_lib
    from raft_tpu import serve

    base, queries = data.make(cfg, seed)
    pool = np.asarray(queries)
    algo = bench_run.load_module(
        os.path.join(bench_run.HERE, "algos", cfg["index"]["kind"] + ".py"),
        "algo_" + cfg["index"]["kind"])
    t0 = time.perf_counter()
    mi, _ = algo.build(cfg, base)
    build_s = time.perf_counter() - t0
    svc = serve.SearchService(
        k=int(cfg["k"]), min_bucket=int(traffic["min_bucket"]),
        max_batch=int(traffic["max_batch"]),
        max_delay_ms=float(traffic["max_delay_ms"]), cost_accounting=False)
    try:
        svc.add_index(INDEX_NAME, mi, warmup=True)

        def submit(rows):
            return svc.submit(INDEX_NAME, rows)

        traffic_lib.warm_buckets(submit, pool, traffic)
        win = traffic_lib.run(submit, pool, traffic, seconds, seed)
    finally:
        svc.stop()
    return mi, base, pool, win, build_s


def hold_to_reference(cfg, traffic, mi, pool, win, m):
    """Hold ``m`` answered rows of ``win`` to the plain beam search."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import beam_reference, reference
    from raft_tpu.neighbors import cagra

    k = int(cfg["k"])
    bucket = int(traffic["max_batch"])
    if int(traffic["rows_per_request"]) <= bucket // 2:
        raise SystemExit("the comparison needs one request per dispatch")
    # one request per dispatch: its rows sit first in a zero-padded
    # bucket, and its seeds are those the search drew for that bucket
    params, index = mi.search_params, mi.index
    itopk = min(max(params.itopk_size, k), index.size)
    entry_seeded = (index.entry_ids is not None
                    and params.num_entry_centers > 0)
    max_iter = params.max_iterations or beam_reference.auto_max_iterations(
        itopk, params.search_width, entry_seeded)
    data_h = np.asarray(index.dataset, np.float32)
    graph_h = np.asarray(index.graph)
    got_d, got_i, want_d, want_i, want_e, rows = [], [], [], [], [], []
    n_rows = 0
    for r in (r for r in win.requests if r.ok):
        if n_rows >= m:
            break
        q = np.zeros((bucket, pool.shape[1]), np.float32)
        q[:r.rows.size] = pool[r.rows]
        seeds = np.asarray(cagra.make_seed_ids(params, index, jnp.asarray(q),
                                               k, itopk=itopk))
        take = min(m - n_rows, r.rows.size)
        wd, wi, we = beam_reference.search_rows(
            data_h, graph_h, pool[r.rows[:take]], seeds[:take], itopk=itopk,
            search_width=params.search_width, max_iterations=max_iter,
            min_iterations=params.min_iterations, metric=cfg["metric"], k=k)
        got_d.append(np.atleast_2d(r.dists)[:take])
        got_i.append(np.atleast_2d(r.ids)[:take])
        want_d.append(wd)
        want_i.append(wi)
        want_e.append(we)
        rows.append(r.rows[:take])
        n_rows += take
    got_d, got_i = np.concatenate(got_d), np.concatenate(got_i)
    want_d, want_i = np.concatenate(want_d), np.concatenate(want_i)
    want_e, rows = np.concatenate(want_e), np.concatenate(rows)
    same = (got_i == want_i).all(axis=1)
    _, scale = reference.pair_distances(
        data_h, pool[rows], np.clip(got_i, 0, None).astype(np.int32),
        cfg["metric"])
    gap = np.abs(got_d.astype(np.float64) - want_d) / np.maximum(scale, 1e-30)
    # a row whose ids differ but whose k distances agree, to the rounding
    # of the sums, differs only at ties
    tie = ~same & (gap <= DIST_TOL).all(axis=1)
    return {"compare_rows": int(rows.size), "ids_agree": int(same.sum()),
            "ties": int(tie.sum()), "disagree": int((~same & ~tie).sum()),
            "dist_gap_max": float(gap.max()),
            "reference_expansions_per_row": [int(want_e.min()),
                                             int(want_e.max())],
            "reference_expansions": int(want_e.sum()),
            "itopk": itopk, "max_iterations": max_iter}


def compare(cfg, traffic, seed, seconds, m, device):
    from benchmark.lib import check, reference

    mi, base, pool, win, build_s = serve_window(cfg, traffic, seed, seconds)
    truth = reference.search(base, pool, int(cfg["k"]), cfg["metric"])[1]
    checks, correct = check.judge(win.requests, base, pool, truth,
                                  cfg["metric"], cfg["correct"], 0)
    del base
    emit({"seed": seed, "build_s": build_s, "requests": len(win.requests),
          "correct": correct, "checks": checks,
          **hold_to_reference(cfg, traffic, mi, pool, win, m)}, cfg, device)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--itopk", default="")
    ap.add_argument("--target", type=float, default=0.95)
    ap.add_argument("--compare", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench_run.prepare_env()
    bench_run.program_env()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = bench_run.chips_of(1, True)[0]
    bench = bench_run.load_json(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, specs = bench_run.cell_inputs(bench, args.workload,
                                                      False)
    if args.itopk:
        sweep_itopk(cell, cfg, traffic, specs, args.seed, args.seconds,
                    [int(x) for x in args.itopk.split(",")], args.target,
                    device)
    if args.compare:
        compare(cfg, traffic, args.seed, args.seconds, args.compare, device)


if __name__ == "__main__":
    main()
