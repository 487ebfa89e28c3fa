"""Primitive-level microbenchmarks — bench/prims parity.

Reference: ``cpp/bench/prims/`` runs Google-Benchmark timings per primitive
(matrix/select_k, distance, linalg, cluster, random). Here: a table of
wall-clock timings for the hot primitives, runnable on any backend:

    python -m raft_tpu.bench.prims [--out results.json] [--filter select_k]

Timings amortize dispatch latency over inner iterations, so a single
call's host overhead does not dominate the per-primitive time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Callable, Dict, List

import numpy as np

# platform override must land before any backend init (same contract as
# raft_tpu.bench.__main__); direct read: core.env would import raft_tpu
# and therefore jax before the platform override lands
if os.environ.get("RAFT_TPU_PLATFORM"):  # raft-tpu: ignore[ENVREG] pre-jax bootstrap
    import jax

    jax.config.update("jax_platforms", os.environ["RAFT_TPU_PLATFORM"])  # raft-tpu: ignore[ENVREG] pre-jax bootstrap

from raft_tpu.core import env as _env  # noqa: E402 — after platform override


def pallas_arm(fn: Callable, pallas: bool) -> Callable:
    """``fn`` with ``RAFT_TPU_PALLAS`` pinned to "1" or "0" around each
    call: the gate is read per search call, so an A/B pins both arms
    (unset means auto, which is Pallas on a TPU)."""

    def arm(*args):
        prev = _env.raw("RAFT_TPU_PALLAS")
        os.environ["RAFT_TPU_PALLAS"] = "1" if pallas else "0"
        try:
            return fn(*args)
        finally:
            if prev is None:
                os.environ.pop("RAFT_TPU_PALLAS", None)
            else:
                os.environ["RAFT_TPU_PALLAS"] = prev

    return arm


def _timeit(fn: Callable, args, warmup: int = 2, iters: int = 5) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _cases() -> List[Dict]:
    import jax
    import jax.numpy as jnp

    from raft_tpu.distance.fused_nn import fused_l2_nn_argmin
    from raft_tpu.distance.pairwise import pairwise_distance
    from raft_tpu.ops.matrix import select_k

    rng = np.random.default_rng(0)
    cases = []

    # NB: operands are passed as call arguments, never closed over — a
    # closed-over array becomes an XLA constant and the whole benchmark gets
    # constant-folded at compile time.

    # select_k (ref: bench/prims/matrix/select_k.cu shapes); the explicit
    # algo cases A/B the wide-top_k vs chunked-tournament paths to tune the
    # auto heuristic (_CHUNKED_MIN_N — the select_k-inl.cuh:47 analog)
    for rows, cols, k in [(1024, 16384, 64), (128, 131072, 256), (4096, 2048, 10)]:
        x = jnp.asarray(rng.standard_normal((rows, cols)).astype(np.float32))
        fn = jax.jit(functools.partial(select_k, k=k, select_min=True))
        cases.append(
            {
                "name": f"select_k/{rows}x{cols}/k{k}",
                "fn": fn,
                "args": (x,),
                "bytes": rows * cols * 4,
                "flops": 0,
            }
        )
    # decision-boundary sweep for the auto heuristic: cols crosses the
    # current _CHUNKED_MIN_N=8192 from both sides at the k values the
    # dispatch branches on (fit with benchmarks/fit_heuristics.py).
    # ONE device array per (rows, cols), shared across the k/algo grid —
    # per-k copies would hold ~3x the HBM for the whole run
    ab_shapes = {(1024, c): (10, 64, 256) for c in
                 (4096, 8192, 16384, 32768, 131072)}
    ab_shapes[(64, 1_000_000)] = (100,)
    ab_shapes[(4096, 8192)] = (16,)
    for (rows, cols), ks in ab_shapes.items():
        x = jnp.asarray(rng.standard_normal((rows, cols)).astype(np.float32))
        for k in ks:
            for algo in ("topk", "chunked"):
                fn = jax.jit(
                    functools.partial(select_k, k=k, select_min=True, algo=algo)
                )
                cases.append(
                    {
                        "name": f"select_k_ab/{rows}x{cols}/k{k}/{algo}",
                        "fn": fn,
                        "args": (x,),
                        "bytes": rows * cols * 4,
                        "flops": 0,
                    }
                )

    # pairwise distance (ref: bench/prims/distance/)
    for m, n, d, metric in [(2048, 2048, 128, "sqeuclidean"), (1024, 1024, 512, "l1")]:
        a = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        fn = jax.jit(functools.partial(pairwise_distance, metric=metric))
        cases.append(
            {
                "name": f"pairwise/{metric}/{m}x{n}x{d}",
                "fn": fn,
                "args": (a, b),
                "bytes": (m + n) * d * 4 + m * n * 4,
                "flops": 2 * m * n * d,
            }
        )

    # IVF-PQ scan-strategy A/B (query-major vs probe-major schedules —
    # tune ivf_pq.SearchParams.strategy's auto rule from the chip numbers;
    # the analog of the reference's compute_similarity kernel-variant
    # selection)
    from raft_tpu.neighbors import ivf_pq as _pq

    # index built lazily on the first (warmup) call so a --filter that
    # skips these cases never pays the 100k build
    _scan_state: Dict = {}

    def _scan_index():
        if "index" not in _scan_state:
            blob_c = rng.standard_normal((512, 96)).astype(np.float32) * 4
            asg = rng.integers(0, 512, 100_000)
            xb = blob_c[asg] + rng.standard_normal((100_000, 96)).astype(np.float32)
            _scan_state["index"] = _pq.build(
                _pq.IndexParams(n_lists=1024, pq_dim=48, kmeans_n_iters=5), xb
            )
        return _scan_state["index"]

    qs = jnp.asarray(rng.standard_normal((4096, 96)).astype(np.float32))
    # logical scan traffic per query-major pass: probed rows × bf16 row
    # bytes at the *mean* occupancy (n/n_lists) — padding excluded, and the
    # probe-major case reads far less physically; gbps here is a
    # schedule-comparable "effective" rate, not measured HBM bandwidth
    scan_bytes = 4096 * 32 * (100_000 // 1024) * 96 * 2
    for strat, pallas in (
        ("query_major", False), ("query_major", True),
        ("probe_major", False), ("probe_major", True),
    ):
        sp = _pq.SearchParams(n_probes=32, strategy=strat)

        def scan(q, _sp=sp):
            return _pq.search(_sp, _scan_index(), q, 10)

        cases.append(
            {
                "name": f"ivf_scan_ab/100kx96/p32/{strat}"
                + ("_pallas" if pallas else ""),
                "fn": pallas_arm(scan, pallas),
                "args": (qs,),
                "bytes": scan_bytes,
                "flops": 0,
            }
        )

    # brute-force kNN A/B: XLA tiled formulation vs the fused Pallas
    # distance+topk kernel — the evidence that keeps fused_knn the TPU
    # default (mirrors ivf_scan_ab)
    from raft_tpu.neighbors import brute_force as _bf

    bx = jnp.asarray(rng.standard_normal((200_000, 96)).astype(np.float32))
    bq = jnp.asarray(rng.standard_normal((4096, 96)).astype(np.float32))

    for pallas in (False, True):
        cases.append(
            {
                "name": "bf_knn_ab/200kx96/q4096/k10"
                + ("/pallas" if pallas else "/xla"),
                "fn": pallas_arm(lambda xx, qq: _bf.knn(xx, qq, 10), pallas),
                "args": (bx, bq),
                "bytes": 200_000 * 96 * 4,
                "flops": 2 * 200_000 * 4096 * 96,
            }
        )

    # fused L2 argmin — the kmeans inner loop (ref: bench/prims/distance/fused_l2_nn.cu)
    m, n, d = 8192, 1024, 128
    a = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    cases.append(
        {
            "name": f"fused_l2_nn/{m}x{n}x{d}",
            "fn": jax.jit(fused_l2_nn_argmin),
            "args": (a, b),
            "bytes": (m + n) * d * 4,
            "flops": 2 * m * n * d,
        }
    )
    return cases


def run(filter_: str = "", out_path: str = "") -> List[Dict]:
    import os

    import jax

    # per-case checkpoint (mirrors bench/frontier.py): an on-chip
    # sweep cut by its time limit resumes from <out>.partial instead
    # of re-timing every completed case
    part = out_path + ".partial" if out_path else ""
    results: List[Dict] = []
    done = set()
    if part and os.path.exists(part):
        try:
            with open(part) as f:
                results = json.load(f)
            done = {r["name"] for r in results}
            print(f"resuming from {part}: {len(done)} cases done")
        except Exception:
            results, done = [], set()
    for case in _cases():
        if filter_ and filter_ not in case["name"]:
            continue
        if case["name"] in done:
            continue
        s = _timeit(case["fn"], case["args"])
        row = {
            "name": case["name"],
            "seconds": round(s, 6),
            "gbps": round(case["bytes"] / s / 1e9, 2),
            "gflops": round(case["flops"] / s / 1e9, 2) if case["flops"] else None,
            "platform": jax.devices()[0].platform,
        }
        results.append(row)
        print(json.dumps(row))
        if part:
            with open(part, "w") as f:
                json.dump(results, f)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        if part and os.path.exists(part):
            os.remove(part)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--filter", default="", help="substring filter on case names")
    ap.add_argument("--out", default="", help="write JSON results here")
    args = ap.parse_args()
    run(args.filter, args.out)


if __name__ == "__main__":
    main()
