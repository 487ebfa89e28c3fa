"""Measured QPS–recall frontier sweep → serialized :class:`FrontierModel`.

Run as ``python -m raft_tpu.bench frontier``; also the closed-loop
autotuner's measurement leg:

- sweeps every algorithm's effort grid on a synthetic-or-real
  DEEP-geometry dataset at configurable scale (``--n``), per-algo
  checkpoint/resume included — a 100M sweep survives a mid-run death;
- optionally builds the four serve backends **shard-parallel** via
  :func:`raft_tpu.serve.build.build_sharded` (``--sharded``), the same
  pod-scale path the paged index store feeds, so the frontier can be
  measured at sizes a single device cannot hold;
- pareto-filters each serve backend's points and emits a
  schema-versioned :class:`~raft_tpu.obs.autotune.FrontierModel`
  document — the file ``RAFT_TPU_FRONTIER_PATH`` points the serving
  :class:`~raft_tpu.obs.autotune.Autotuner` at — plus the standard
  enveloped bench record for ``bench compare``.

    python -m raft_tpu.bench frontier --n 100000 --platform cpu

Writes ``benchmarks/frontier_<platform>.json`` (+ ``.png``) for the
human sweep artifact and ``--out`` (default
``benchmarks/frontier_model_<platform>.json``) for the serve-time model.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from raft_tpu.obs.autotune import FrontierModel, FrontierPoint

#: bench-harness algo name → serve backend tag: the FrontierModel key the
#: serving Autotuner resolves through ``EffortSpec.backend``.  Comparator
#: algos (numpy_exact, hnswlib, ...) stay in the sweep artifact but never
#: enter the model — the autotuner can only actuate the serve backends.
SERVE_BACKENDS = {
    "raft_tpu_brute_force": "brute_force",
    "raft_tpu_ivf_flat": "ivf_flat",
    "raft_tpu_ivf_pq": "ivf_pq",
    "raft_tpu_cagra": "cagra",
}


def default_grids(
    n: int, dim: int, metric: str, *, comparators: bool = True
) -> List[Tuple[str, Dict[str, Any], List[Dict[str, Any]]]]:
    """The sweep grid: ``(algo, build_param, effort points)`` per entry.

    The raft_tpu entries sweep exactly the knobs the serve-side
    ``EffortSpec`` actuates (n_probes / refine_ratio / itopk_size /
    search_width), so every measured point is a point the autotuner can
    actually select.
    """
    grids: List[Tuple[str, Dict[str, Any], List[Dict[str, Any]]]] = [
        ("raft_tpu_brute_force", {}, [{}]),
        (
            "raft_tpu_ivf_flat",
            {"n_lists": max(64, n // 500)},
            [{"n_probes": p} for p in (4, 8, 16, 32, 64)],
        ),
        (
            # pq_dim = d/2 (the reference's sift-1M grid region) — the
            # auto d/4 is too coarse past ~64 dims for recall≥0.9 at k=10
            "raft_tpu_ivf_pq",
            {"n_lists": max(64, n // 500), "pq_dim": dim // 2},
            [{"n_probes": p} for p in (4, 8, 16, 32, 64)]
            + [{"n_probes": p, "refine_ratio": r}
               for p in (8, 16, 32) for r in (2, 4)],
        ),
        (
            # deg-64 graph + entry-point-seeded w=1 walks — the winning
            # region from the round-4 chip sweep
            "raft_tpu_cagra",
            {"graph_degree": 64, "intermediate_graph_degree": 128},
            [
                {"itopk_size": t, "search_width": 1, "max_iterations": mi,
                 "num_entry_centers": s}
                for t in (16, 32)
                for mi in (3, 4, 6, 8)
                for s in (8, 16)
            ]
            + [{"itopk_size": 64, "search_width": 1},
               {"itopk_size": 64, "search_width": 4}],
        ),
    ]
    if comparators:
        grids.insert(0, ("numpy_exact", {}, [{}]))
        grids.extend([
            (
                # half-the-gather-bytes CAGRA: bf16 traversal dataset
                "raft_tpu_cagra_bf16",
                {"graph_degree": 64, "intermediate_graph_degree": 128},
                [
                    {"itopk_size": t, "search_width": 1, "max_iterations": mi,
                     "num_entry_centers": 16}
                    for t in (16, 32) for mi in (4, 6, 8)
                ],
            ),
            (
                # memory-lean CAGRA: VPQ-compressed, decode-on-gather
                "raft_tpu_cagra_vpq",
                {"graph_degree": 64, "intermediate_graph_degree": 128},
                [
                    {"itopk_size": t, "search_width": 1, "max_iterations": mi,
                     "num_entry_centers": 16}
                    for t in (16, 32) for mi in (4, 8)
                ],
            ),
            ("hnswlib_format", {"graph_degree": 32},
             [{"ef": e} for e in (32, 64, 128)]),
            # same exported file, searched by the native C++ HNSW engine
            ("hnsw_native", {"graph_degree": 32},
             [{"ef": 64, "n_seeds": 1}, {"ef": 128, "n_seeds": 1},
              {"ef": 128, "n_seeds": 128}, {"ef": 256, "n_seeds": 256}]),
        ])
        if metric != "inner_product":
            # sklearn spatial trees refuse unnormalized MIP
            grids.insert(1, ("sklearn", {"algorithm": "ball_tree"}, [{}]))
    return grids


def make_dataset(name: str, n: int, *, n_queries: int, k: int,
                 dim: int = 0, metric: str = ""):
    """Synthetic-or-registered dataset at ``n`` rows with groundtruth.

    Known names scale the registered geometry (``datasets.synthetic``);
    unknown names fall back to explicit DEEP-like geometry (``--dim`` /
    ``--metric``, defaulting to deep's 96-dim inner product).
    """
    from raft_tpu.bench import datasets
    from raft_tpu.bench.datasets import _SYNTH_SHAPES

    if name in _SYNTH_SHAPES:
        full_n = _SYNTH_SHAPES[name][0]
        ds = datasets.synthetic(name, scale=n / full_n, n_queries=n_queries)
    else:
        ds = datasets.synthetic_geometry(
            name, n, dim or 96, metric or "inner_product",
            n_queries=n_queries,
        )
    return datasets.generate_groundtruth(ds, k=k)


# -- the sweep -----------------------------------------------------------


def sweep(ds, grids, *, k: int, checkpoint_path: str,
          warmup: int = 1, iters: int = 3) -> List[Any]:
    """Run every grid entry with per-algo checkpoint/resume.

    A run cut mid-sweep (a time limit, a lost machine) must not lose the
    completed algos' measurements (a 1M sweep is ~10 min/algo on chip): each finished
    algo appends to ``<checkpoint_path>`` and a restart resumes from it,
    re-running only what's missing.  A backend-unavailable failure keeps
    the algo un-done and aborts (``SystemExit``) so the resume retries
    it instead of failing every remaining algo against a dead chip.
    """
    from raft_tpu.bench import runner

    n = int(ds.base.shape[0])
    done_algos: set = set()
    results: List[Any] = []
    if os.path.exists(checkpoint_path):
        try:
            with open(checkpoint_path) as fh:
                part = json.load(fh)
            # dataset is part of the signature: a leftover partial from a
            # different dataset with matching n/k must not merge stale
            # measurements into this artifact
            if (part.get("n"), part.get("k"),
                    part.get("dataset")) == (n, k, ds.name):
                done_algos = set(part["done_algos"])
                results = [runner.RunResult(**d) for d in part["results"]]
                print(f"resuming from {checkpoint_path}: "
                      f"{sorted(done_algos)} done")
        except Exception as e:
            print(f"ignoring unreadable partial ({e})")

    def checkpoint() -> None:
        with open(checkpoint_path, "w") as fh:
            json.dump(
                {"n": n, "k": k, "dataset": ds.name,
                 "done_algos": sorted(done_algos),
                 "results": [r.to_dict() for r in results]}, fh,
            )

    for name, build_param, search_params in grids:
        if name in done_algos:
            continue
        t0 = time.time()
        try:
            rs = runner.run_case(
                ds, name, build_param, search_params, k=k,
                warmup=warmup, iters=iters,
            )
        except Exception as e:  # record the failure, keep the sweep going
            print(f"{name}: FAILED ({e})")
            if "unavailable" in str(e).lower():
                checkpoint()
                print("backend unavailable — aborting; checkpoint kept")
                raise SystemExit(1)
            done_algos.add(name)
            checkpoint()
            continue
        results.extend(rs)
        done_algos.add(name)
        checkpoint()
        good = [r for r in rs if r.recall >= 0.9] or rs
        best = max(good, key=lambda r: r.qps)
        print(
            f"{name}: {len(rs)} points in {time.time()-t0:.0f}s; "
            f"best{'@recall≥0.9' if good is not rs else ' (no point ≥0.9)'}: "
            f"{best.qps:.0f} qps @ {best.recall:.3f}"
        )
    return results


def sweep_sharded(ds, *, kinds: Sequence[str], k: int,
                  n_devices: Optional[int] = None,
                  warmup: int = 1, iters: int = 3) -> List[Any]:
    """Shard-parallel sweep: build each serve backend once via
    :func:`~raft_tpu.serve.build.build_sharded` (row-sharded training
    over the local mesh — the path a 100M paged-store corpus feeds),
    then sweep the effort knobs the :class:`ShardedIndex` reads per
    dispatch.  Only the serve backends run here; comparators have no
    sharded leg."""
    import dataclasses

    import jax

    from raft_tpu.bench import device_time, runner
    from raft_tpu.serve.build import build_sharded

    queries = np.asarray(ds.queries, np.float32)
    nq = queries.shape[0]
    results: List[Any] = []
    for algo in kinds:
        kind = SERVE_BACKENDS[algo]
        t0 = time.perf_counter()
        sidx = build_sharded(kind, np.asarray(ds.base, np.float32),
                             n_devices=n_devices, metric=ds.metric)
        build_s = time.perf_counter() - t0
        base_sp = sidx.search_params
        if kind == "brute_force":
            grid: List[Dict[str, Any]] = [{}]
        elif kind == "cagra":
            grid = [{"itopk_size": t} for t in (16, 32, 64)]
        else:
            grid = [{"n_probes": p} for p in (4, 8, 16, 32, 64)]
        for effort in grid:
            # the ShardedIndex reads search_params per dispatch (host
            # value), so swapping it between points costs one cached
            # executable per distinct value — exactly the serving shape
            if effort and base_sp is not None:
                sidx.search_params = dataclasses.replace(base_sp, **effort)
            for _ in range(warmup):
                jax.block_until_ready(sidx.search(queries, k))
            t0 = time.perf_counter()
            for _ in range(iters):
                d, i = sidx.search(queries, k)
            jax.block_until_ready((d, i))
            dt = (time.perf_counter() - t0) / iters
            rec = runner.recall_at_k(np.asarray(i), ds.gt_neighbors[:, :k])
            dev_s = device_time.measure_device_time(
                lambda qq: sidx.search(qq, k), queries
            )
            results.append(runner.RunResult(
                algo=algo, dataset=ds.name, k=k,
                build_param={"sharded": sidx.n_shards},
                search_param=dict(effort),
                build_time_s=build_s, qps=nq / dt,
                latency_ms=dt / nq * 1e3, recall=rec, end_to_end_s=dt,
                device_time_s=dev_s,
                device_qps=None if not dev_s else nq / dev_s,
            ))
        if base_sp is not None:
            sidx.search_params = base_sp
        best = max(results[-len(grid):], key=lambda r: r.qps)
        print(f"{algo} (sharded x{sidx.n_shards}): {len(grid)} points; "
              f"best {best.qps:.0f} qps @ {best.recall:.3f}")
    return results


# -- the model -----------------------------------------------------------


def frontier_model(results, *, n_queries: int,
                   meta: Optional[Dict[str, Any]] = None) -> FrontierModel:
    """Fold sweep results into a pareto-filtered :class:`FrontierModel`.

    Only serve-backend points enter (the autotuner can't actuate a
    comparator); ``device_s_per_query`` comes from the measured
    device-plane batch time (None off-accelerator, never faked)."""
    model = FrontierModel(meta=dict(meta or {}))
    for r in results:
        backend = SERVE_BACKENDS.get(r.algo)
        if backend is None:
            continue
        model.add(backend, FrontierPoint(
            effort=dict(r.search_param),
            qps=float(r.qps),
            recall=float(r.recall),
            device_s_per_query=(
                None if not r.device_time_s
                else float(r.device_time_s) / max(1, n_queries)
            ),
        ))
    model.pareto_filter()
    return model


# -- CLI -----------------------------------------------------------------


def frontier_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "raft_tpu.bench frontier",
        description="measured QPS–recall frontier sweep → FrontierModel",
    )
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--dataset", default="deep-image-96-inner",
                    help="synthetic stand-in geometry (see bench.datasets); "
                    "unknown names use --dim/--metric DEEP-like geometry")
    ap.add_argument("--dim", type=int, default=0)
    ap.add_argument("--metric", default="")
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--platform", default="",
                    help="e.g. cpu to force a backend")
    ap.add_argument("--algos", default="",
                    help="comma-filter, e.g. numpy_exact,raft_tpu_ivf_pq")
    ap.add_argument("--no-comparators", action="store_true",
                    help="serve backends only (the autotuner's model leg)")
    ap.add_argument("--sharded", type=int, default=0, metavar="N",
                    help="build the serve backends shard-parallel over N "
                    "devices (0: single-device runner sweep)")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--sweep-out", default="",
                    help="human sweep artifact (default benchmarks/"
                    "frontier_<platform>.json)")
    ap.add_argument("--out", default="",
                    help="FrontierModel path (default benchmarks/"
                    "frontier_model_<platform>.json) — point "
                    "RAFT_TPU_FRONTIER_PATH here")
    args = ap.parse_args(list(argv) if argv is not None else None)

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    platform = jax.devices()[0].platform

    from raft_tpu.bench import export, plot

    ds = make_dataset(args.dataset, args.n, n_queries=args.queries,
                      k=args.k, dim=args.dim, metric=args.metric)
    n, dim = int(ds.base.shape[0]), int(ds.base.shape[1])

    bench_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "benchmarks",
    )
    sweep_out = args.sweep_out or os.path.join(
        bench_dir, f"frontier_{platform}.json")
    model_out = args.out or os.path.join(
        bench_dir, f"frontier_model_{platform}.json")

    if args.sharded:
        kinds = [a for a in SERVE_BACKENDS
                 if not args.algos or a in set(args.algos.split(","))]
        results = sweep_sharded(
            ds, kinds=kinds, k=args.k, n_devices=args.sharded,
            warmup=args.warmup, iters=args.iters,
        )
    else:
        grids = default_grids(
            n, dim, ds.metric, comparators=not args.no_comparators)
        if args.algos:
            keep = set(args.algos.split(","))
            grids = [g for g in grids if g[0] in keep]
        results = sweep(
            ds, grids, k=args.k, checkpoint_path=sweep_out + ".partial",
            warmup=args.warmup, iters=args.iters,
        )

    # per-algo build cost, first-class: build time gates alongside the
    # QPS pareto — search wins don't excuse uncompetitive builds.
    build_seconds: Dict[str, float] = {}
    for r in results:
        build_seconds[r.algo] = max(
            build_seconds.get(r.algo, 0.0), r.build_time_s)
    for a, bs in sorted(build_seconds.items()):
        print(f"build_s {a}: {bs:.1f}")

    doc = {
        "platform": platform,
        "n": n,
        "dim": dim,
        "n_queries": int(ds.queries.shape[0]),
        "k": args.k,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "build_seconds": build_seconds,
        "frontiers": dict(plot.group_frontiers(results)),
        "results": [r.to_dict() for r in results],
    }
    os.makedirs(os.path.dirname(sweep_out) or ".", exist_ok=True)
    with open(sweep_out, "w") as fh:
        json.dump(doc, fh, indent=2)
    part_path = sweep_out + ".partial"
    if os.path.exists(part_path):
        os.remove(part_path)
    print("wrote", sweep_out)

    meta = {
        "dataset": ds.name, "n": n, "dim": dim,
        "n_queries": int(ds.queries.shape[0]), "k": args.k,
        "platform": platform, "metric": ds.metric,
        "sharded": int(args.sharded),
    }
    model = frontier_model(
        results, n_queries=int(ds.queries.shape[0]), meta=meta)
    model.save(model_out)
    print("wrote", model_out,
          f"({sum(len(p) for p in model.points.values())} pareto points "
          f"across {len(model.points)} backends)")

    # the comparable headline for ``bench compare``: best serve-backend
    # QPS at recall ≥ 0.9 (falls back to the overall best when nothing
    # clears it — tiny smoke sweeps)
    serve_pts = [r for r in results if r.algo in SERVE_BACKENDS]
    if serve_pts:
        good = [r for r in serve_pts if r.recall >= 0.9] or serve_pts
        head = max(good, key=lambda r: r.qps)
        export.write_bench_record({
            "metric": f"frontier_{ds.name}_k{args.k}",
            "value": round(head.qps, 1),
            "unit": "queries/s",
            "platform": platform if platform == "cpu" else None,
            "recall": round(head.recall, 4),
            "algo": head.algo,
            "search_param": head.search_param,
            "frontier": model.to_dict(),
        })

    try:
        plot.plot_results(results, sweep_out.replace(".json", ".png"),
                          title=f"recall/QPS frontier ({platform}, n={n})")
        print("wrote", sweep_out.replace(".json", ".png"))
    except Exception as e:
        print("plot skipped:", e)
    return 0


if __name__ == "__main__":
    sys.exit(frontier_main())
