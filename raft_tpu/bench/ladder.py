"""BASELINE ladder runner — configs #1–#4 with QPS@recall, GB/s, and MFU.

Reference: the raft-ann-bench harness records QPS/latency/recall as
first-class counters (cpp/bench/ann/src/common/benchmark.hpp:330-379);
BASELINE.md defines the measurable ladder for this repo:

  #1 pairwise L2 1k×128 — correctness vs numpy + bandwidth
  #2 brute-force kNN (SIFT-10k shape) — recall 1.0 + GB/s + GFLOP/s
  #3 IVF-Flat (SIFT-1M shape) — QPS @ recall ≥ 0.95
  #4 IVF-PQ + CAGRA (DEEP/GIST shape) — QPS @ recall ≥ 0.95 (north star)

Usage:
    python -m raft_tpu.bench.ladder [--scale 1.0] [--out benchmarks/...]

Results append to a JSON file (default ``benchmarks/ladder_<platform>.json``)
with one record per config: metric values, operating point, achieved
FLOP/s ÷ the bf16 peak and HBM GB/s ÷ peak bandwidth where the device has
published peaks (``obs.cost.PEAKS``). These are host-clock rates: the
device-time split is ROADMAP Speed item 3.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from raft_tpu.obs.cost import device_peaks

if os.environ.get("RAFT_TPU_PLATFORM"):  # raft-tpu: ignore[ENVREG] pre-jax bootstrap
    import jax

    jax.config.update("jax_platforms", os.environ["RAFT_TPU_PLATFORM"])  # raft-tpu: ignore[ENVREG] pre-jax bootstrap


def _timeit(fn, *args, warmup=2, iters=5):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _dev(fn, *args):
    """Device busy time for one call (None on host-only backends) — the
    reference's CUDA-event GPU-time counter (benchmark.hpp:165,330-333)."""
    from raft_tpu.bench.device_time import measure_device_time

    return measure_device_time(fn, *args)


def _blobs(n, d, n_clusters, seed):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    asg = rng.integers(0, n_clusters, n)
    return centers, (
        centers[asg] + rng.standard_normal((n, d)).astype(np.float32) * 0.35
    )


def _recall(ids, gt):
    from raft_tpu.stats import neighborhood_recall

    return float(neighborhood_recall(np.asarray(ids), np.asarray(gt)))


def config1_pairwise(res, platform):
    import jax.numpy as jnp

    from raft_tpu.distance.pairwise import pairwise_distance

    rng = np.random.default_rng(0)
    x = rng.standard_normal((1000, 128)).astype(np.float32)
    y = rng.standard_normal((1000, 128)).astype(np.float32)
    got = np.asarray(pairwise_distance(jnp.asarray(x), jnp.asarray(y), metric="sqeuclidean"))
    want = ((x[:, None] - y[None]) ** 2).sum(-1)
    max_rel = float(np.max(np.abs(got - want) / np.maximum(want, 1e-6)))
    s = _timeit(
        lambda a, b: pairwise_distance(a, b, metric="sqeuclidean", res=res),
        jnp.asarray(x), jnp.asarray(y),
    )
    bytes_moved = (2 * 1000 * 128 + 1000 * 1000) * 4
    return {
        "config": "1_pairwise_l2_1kx128",
        "max_rel_err_vs_numpy": max_rel,
        "seconds": s,
        "gbs": bytes_moved / s / 1e9,
        "pass": max_rel < 1e-4,
    }


def config2_bruteforce(res, platform, scale):
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force

    n, d, n_q, k = int(10_000 * scale), 128, int(1_000 * scale), 10
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((n_q, d)).astype(np.float32)
    xd, qd = jnp.asarray(x), jnp.asarray(q)
    _, ids = brute_force.knn(xd, qd, k, res=res)
    # exact numpy ground truth
    d2 = ((q[:, None] - x[None]) ** 2).sum(-1) if n * n_q <= 2e7 else None
    if d2 is not None:
        gt = np.argsort(d2, axis=1)[:, :k]
        recall = _recall(ids, gt)
    else:
        recall = None
    s = _timeit(lambda a, b: brute_force.knn(a, b, k, res=res), xd, qd)
    dev_s = _dev(lambda a, b: brute_force.knn(a, b, k, res=res), xd, qd)
    flops = 2.0 * n * n_q * d
    peaks = device_peaks()
    return {
        "config": "2_bruteforce_sift10k",
        "n": n,
        "recall": recall,
        "qps": n_q / s,
        "device_seconds": dev_s,
        "device_qps": n_q / dev_s if dev_s else None,
        "gflops": flops / s / 1e9,
        "mfu_bf16_peak": (flops / s) / peaks[0] if peaks else None,
        "pass": recall is None or recall >= 0.999,
    }


def config3_ivf_flat(res, platform, scale):
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force, ivf_flat

    n, d, n_q, k = int(1_000_000 * scale), 128, int(10_000 * scale), 10
    n = max(n, 20_000)
    n_q = max(n_q, 200)
    n_clusters = max(64, n // 250)  # ~250 rows/cluster at any scale
    c, x = _blobs(n, d, n_clusters, 2)
    rng_q = np.random.default_rng(3)
    q = (
        c[rng_q.integers(0, n_clusters, n_q)]
        + rng_q.standard_normal((n_q, d)).astype(np.float32) * 0.35
    )
    xd, qd = jnp.asarray(x), jnp.asarray(q)
    t0 = time.perf_counter()
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=max(64, int(np.sqrt(n) * 2)), kmeans_n_iters=10),
        xd, res=res,
    )
    build_s = time.perf_counter() - t0
    _, gt = brute_force.knn(xd, qd, k, res=res)
    best = None
    for p in (8, 16, 32, 64, 128):
        if p > index.n_lists:
            break
        sp = ivf_flat.SearchParams(n_probes=p)
        _, ids = ivf_flat.search(sp, index, qd, k, res=res)
        r = _recall(ids, gt)
        s = _timeit(lambda qq: ivf_flat.search(sp, index, qq, k, res=res), qd)
        best = {"n_probes": p, "recall": r, "qps": n_q / s}
        if r >= 0.95:
            break
    dev_s = _dev(lambda qq: ivf_flat.search(sp, index, qq, k, res=res), qd)
    best["device_seconds"] = dev_s
    best["device_qps"] = n_q / dev_s if dev_s else None
    # bandwidth: probed rows streamed per query batch
    row_bytes = d * np.dtype(np.float32).itemsize
    scanned = n_q * best["n_probes"] * index.list_cap * row_bytes
    peaks = device_peaks()
    return {
        "config": "3_ivf_flat_sift1m",
        "n": n,
        "build_s": build_s,
        **best,
        "scan_gbs": scanned * best["qps"] / n_q / 1e9,
        "hbm_frac": (scanned * best["qps"] / n_q) / peaks[1]
        if peaks
        else None,
        "pass": best["recall"] >= 0.9,
    }


def config4_ivf_pq_cagra(res, platform, scale):
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force, cagra, ivf_pq
    from raft_tpu.neighbors.refine import refine

    n, d, n_q, k = int(100_000 * scale), 96, int(10_000 * scale), 10
    n = max(n, 20_000)
    n_q = max(n_q, 200)
    n_clusters = max(64, n // 100)
    c, x = _blobs(n, d, n_clusters, 4)
    rng_q = np.random.default_rng(5)
    q = (
        c[rng_q.integers(0, n_clusters, n_q)]
        + rng_q.standard_normal((n_q, d)).astype(np.float32) * 0.35
    )
    xd, qd = jnp.asarray(x), jnp.asarray(q)
    _, gt = brute_force.knn(xd, qd, k, res=res)

    t0 = time.perf_counter()
    pq = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=1024, pq_dim=d // 2, kmeans_n_iters=10),
        xd, res=res,
    )
    pq_build_s = time.perf_counter() - t0
    pq_best = None
    for p in (8, 16, 32, 64, 128, 256):
        sp = ivf_pq.SearchParams(n_probes=p, lut_dtype="bfloat16")

        def fn(qq):
            _, ci = ivf_pq.search(sp, pq, qq, k * 4, res=res)
            return refine(xd, qq, ci, k, res=res)

        _, ids = fn(qd)
        r = _recall(ids, gt)
        s = _timeit(fn, qd)
        pq_best = {"n_probes": p, "recall": r, "qps": n_q / s}
        if r >= 0.95:
            break
    dev_s = _dev(fn, qd)
    pq_best["device_seconds"] = dev_s
    pq_best["device_qps"] = n_q / dev_s if dev_s else None

    t0 = time.perf_counter()
    cg = cagra.build(cagra.IndexParams(graph_degree=64), xd, res=res)
    cg_build_s = time.perf_counter() - t0
    cg_best = None
    # entry-seeded w=1 ladder: walk max_iterations up until the recall
    # gate clears (the round-4 winning region; itopk rises as a fallback)
    for itopk, mi in ((16, 3), (16, 4), (16, 6), (16, 8), (32, 8),
                      (32, 16), (64, 0)):
        sp = cagra.SearchParams(
            itopk_size=itopk, search_width=1, max_iterations=mi,
            num_entry_centers=16,
        )
        _, ids = cagra.search(sp, cg, qd, k, res=res)
        r = _recall(ids, gt)
        s = _timeit(lambda qq: cagra.search(sp, cg, qq, k, res=res), qd)
        cg_best = {"itopk": itopk, "max_iterations": mi, "recall": r,
                   "qps": n_q / s}
        if r >= 0.95:
            break
    dev_s = _dev(lambda qq: cagra.search(sp, cg, qq, k, res=res), qd)
    cg_best["device_seconds"] = dev_s
    cg_best["device_qps"] = n_q / dev_s if dev_s else None

    return {
        "config": "4_ivf_pq_cagra_deep100k",
        "n": n,
        "ivf_pq": {"build_s": pq_build_s, **pq_best},
        "cagra": {"build_s": cg_build_s, **cg_best},
        "pass": pq_best["recall"] >= 0.9 and cg_best["recall"] >= 0.85,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink factor for CPU smoke runs (e.g. 0.02)")
    ap.add_argument("--out", default="")
    ap.add_argument("--configs", default="1,2,3,4")
    args = ap.parse_args()

    import jax

    from raft_tpu.core.resources import Resources

    platform = jax.devices()[0].platform
    res = Resources(workspace_limit_bytes=1 << 30)
    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "benchmarks", f"ladder_{platform}.json",
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def mark_scaled(rec):
        """A pass at reduced scale is NOT a pass of the BASELINE config:
        stamp it "scaled" and put the effective n in the config name so a
        down-scaled run can never masquerade as the real ladder result."""
        if args.scale < 1.0:
            if "n" in rec:
                rec["config"] = f"{rec['config']}@n{rec['n']}"
            if rec.get("pass") is True and "n" in rec:
                rec["pass"] = "scaled"
        return rec

    wanted = set(args.configs.split(","))
    records = []
    if "1" in wanted:
        records.append(config1_pairwise(res, platform))
        print(json.dumps(records[-1]))
    if "2" in wanted:
        records.append(mark_scaled(config2_bruteforce(res, platform, args.scale)))
        print(json.dumps(records[-1]))
    if "3" in wanted:
        records.append(mark_scaled(config3_ivf_flat(res, platform, args.scale)))
        print(json.dumps(records[-1]))
    if "4" in wanted:
        records.append(mark_scaled(config4_ivf_pq_cagra(res, platform, args.scale)))
        print(json.dumps(records[-1]))

    doc = {"platform": platform, "scale": args.scale,
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"), "records": records}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
