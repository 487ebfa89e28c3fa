#!/usr/bin/env python
"""Distributed consumer example — the multi-device half of the template
project (ref: cpp/template/src/ + raft-dask usage docs,
docs/source/using_raft_comms.rst).

Runs on any device set; with no accelerator it simulates an 8-device mesh
on CPU (exactly what the test suite and the driver's multichip dryrun do):

    python examples/distributed_quickstart.py [--devices 8]
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU mesh size when simulating")
    ap.add_argument("--platform", default="",
                    help="force a backend, e.g. cpu (else autodetect)")
    ap.add_argument("--n", type=int, default=40_000)
    ap.add_argument("--dim", type=int, default=64)
    args = ap.parse_args()

    import jax

    # opt-in CPU simulation, matching ann_quickstart's --platform pattern:
    # an explicit --platform wins; otherwise accelerators autodetect and
    # only a CPU-only environment gets the N-virtual-device mesh
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu":
            jax.config.update("jax_num_cpu_devices", args.devices)
    elif not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_num_cpu_devices", args.devices)

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.comms import Comms, make_mesh
    from raft_tpu.comms.distributed import (
        kmeans_fit,
        shard_ivf_pq_index,
        sharded_cagra_search,
        sharded_ivf_pq_build,
        sharded_ivf_pq_search,
        sharded_knn,
    )
    from raft_tpu.neighbors import brute_force, cagra, ivf_pq, refine
    from raft_tpu.stats import neighborhood_recall

    n_dev = len(jax.devices())
    comms = Comms(make_mesh(n_dev))
    print(f"mesh: {n_dev}×{jax.devices()[0].platform}")

    n = (args.n // n_dev) * n_dev  # row-sharding needs n % n_dev == 0
    if n != args.n:
        print(f"rounding --n {args.n} down to {n} (multiple of {n_dev} devices)")
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((64, args.dim)).astype(np.float32) * 4
    lab = rng.integers(0, 64, n)
    x = centers[lab] + rng.standard_normal((n, args.dim)).astype(np.float32)
    q = x[:256] + 0.01
    xs = jax.device_put(x, NamedSharding(comms.mesh, P(comms.axis, None)))

    # 1. distributed kmeans (psum-allreduced Lloyd, ++init, n_init restarts)
    c, hist = kmeans_fit(comms, xs, 64, n_iters=10)
    finite = np.asarray(hist)[np.isfinite(np.asarray(hist))]
    print(f"kmeans_fit: inertia {finite[0]:.0f} → {finite[-1]:.0f} "
          f"({len(finite)} iters)")

    # 2. distributed exact kNN (local top-k + all-gather merge)
    _, gt = brute_force.knn(x, q, 10)
    dist, ids = sharded_knn(comms, xs, jnp.asarray(q), 10)
    r = float(neighborhood_recall(np.asarray(ids), np.asarray(gt)))
    print(f"sharded_knn: recall vs single-device exact = {r:.4f}")

    # 3. distributed ANN, build AND search: MNMG IVF-PQ build (shard-local
    # encode against the replicated quantizer — byte-identical to a
    # single-device build) → list-sharded search + refine
    index = sharded_ivf_pq_build(
        comms, xs,
        ivf_pq.IndexParams(n_lists=64, pq_dim=args.dim // 2, kmeans_n_iters=5),
    )
    sharded = shard_ivf_pq_index(comms, index)
    _, ci = sharded_ivf_pq_search(comms, sharded, jnp.asarray(q), 40, n_probes=16)
    _, ids2 = refine(x, q, ci, 10)
    r2 = float(neighborhood_recall(np.asarray(ids2), np.asarray(gt)))
    print(f"sharded_ivf_pq_build → sharded search + refine: recall = {r2:.4f}")

    # 4. data-parallel CAGRA: replicated graph index, sharded query stream
    g = cagra.build(
        cagra.IndexParams(graph_degree=32, intermediate_graph_degree=48), x
    )
    _, ids3 = sharded_cagra_search(
        comms, g, q, 10,
        params=cagra.SearchParams(itopk_size=16, max_iterations=6),
    )
    r3 = float(neighborhood_recall(np.asarray(ids3), np.asarray(gt)))
    print(f"sharded_cagra_search: recall = {r3:.4f}")
    print("ok")


if __name__ == "__main__":
    main()
