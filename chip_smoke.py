#!/usr/bin/env python
"""Bring the IVF-PQ serving path up on a TPU, through its user entry points.

One process drives every phase (a chip belongs to one process at a time):
``ivf_pq.build`` -> ``serve.SearchService`` over a ``MutableIndex`` with
exact refine -> single-query traffic from client threads and one
1,000-query batch -> exact ground truth on the chip -> checks, including
the compiled select_k and fused-kNN kernels against plain ``jnp`` +
``lax.top_k`` answers that share no code with the library.  The
deployment is the ann-benchmarks DEEP geometry (``deep-image-96-inner``:
d=96, inner product, 9.99M rows published), generated from ``--seed``.
One chip runs it at a tenth of the rows; the index parameters are the
frontier harness's IVF-PQ grid point (n_lists = n/500, pq_dim = d/2,
n_probes 32, exact refine of 4k candidates).

Every phase prints one line with its seconds.  Any failed check exits
non-zero.  The last stdout line is the one JSON result line; it is printed
only when every check passed on a TPU.
"""

import argparse
import json
import sys
import threading
import time

DATASET = "deep-image-96-inner"
K = 10
N_PROBES = 32
N_SINGLE = 512
N_BATCH = 1000
CLIENTS = 4
ROWS_PER_LIST = 500
MIN_RECALL = 0.90
GT_BLOCK = 1 << 18  # base rows per exact-scoring block


class Checks:
    """Collects every check so one run reports all failures, not the first."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what):
        print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def phase(name, t0, **kv):
    fields = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{name}] {time.perf_counter() - t0:.3f}s {fields}", flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (monitoring
    events), so a phase can report its compile time apart from its run,
    and which functions took it."""

    def __init__(self):
        import collections

        import jax

        self.seconds = 0.0
        self.by_fun = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *args, fun_name="?", **kwargs):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.by_fun[fun_name] += duration

    def report(self, since):
        """Print the functions that took the most compile time since the
        ``by_fun`` snapshot ``since``."""
        top = (self.by_fun - since).most_common(6)
        print("  compile by function: " + ", ".join(
            f"{name}={sec:.1f}s" for name, sec in top), flush=True)


def device_phase():
    t0 = time.perf_counter()
    import jax

    devs = jax.devices()
    d0 = devs[0]
    phase("device", t0, platform=d0.platform, kind=repr(d0.device_kind),
          count=len(devs))
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d0.platform!r}")
    from raft_tpu import kernels

    if kernels.interpret_mode() or not kernels.use_pallas():
        sys.exit("chip_smoke: Pallas kernels would not run compiled on this TPU")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def make_data(seed, scale):
    """Seeded DEEP-geometry base + queries; the base also goes to the
    device (the index's build input and its refine rows)."""
    import jax

    from raft_tpu.bench import datasets

    t0 = time.perf_counter()
    ds = datasets.synthetic(DATASET, scale=scale, n_queries=N_BATCH, seed=seed)
    base = jax.block_until_ready(jax.device_put(ds.base))
    n, d = ds.base.shape
    published = datasets._SYNTH_SHAPES[DATASET][0]
    phase("data", t0, rows=n, dim=d, metric=ds.metric,
          base_bytes=ds.base.nbytes, cut=f"{n}/{published} rows (scale={scale})")
    return ds, base


def exact_topk(base, queries, k):
    """Exact inner-product top-k (values, ids) by plain ``jnp`` matmul and
    ``lax.top_k``, streamed over host row blocks: the reference the
    library's own kernels are held to, sharing no code with them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = base.shape[0]

    @jax.jit
    def step(best_v, best_i, q, xb, offset):
        s = jnp.matmul(q, xb.T, precision=jax.lax.Precision.HIGHEST)
        rows = offset + jnp.arange(xb.shape[0], dtype=jnp.int32)
        s = jnp.where(rows < n, s, -jnp.inf)
        v = jnp.concatenate([best_v, s], axis=1)
        i = jnp.concatenate([best_i, jnp.broadcast_to(rows, s.shape)], axis=1)
        v, j = jax.lax.top_k(v, k)
        return v, jnp.take_along_axis(i, j, axis=1)

    q = jnp.asarray(queries, jnp.float32)
    best_v = jnp.full((q.shape[0], k), -jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    block = min(GT_BLOCK, n)
    for s0 in range(0, n, block):
        xb = np.zeros((block, base.shape[1]), np.float32)
        part = base[s0:s0 + block]
        xb[: part.shape[0]] = part
        best_v, best_i = step(best_v, best_i, q, xb, s0)
    return np.asarray(best_v), np.asarray(best_i)


def kernel_checks(ds, check):
    """The compiled select_k and fused brute-force kNN against plain
    ``lax.top_k`` / :func:`exact_topk` answers on chip-sized inputs."""
    import jax
    import numpy as np

    from raft_tpu.kernels import interpret_mode, select_k_pallas
    from raft_tpu.neighbors import brute_force

    t0 = time.perf_counter()
    scores = jax.random.normal(jax.random.PRNGKey(0), (1024, 8192))
    for k in (10, 32):
        v_p, i_p = select_k_pallas(scores, k, select_min=False,
                                   interpret=interpret_mode())
        v_r, i_r = jax.lax.top_k(scores, k)
        check(np.array_equal(np.asarray(i_p), np.asarray(i_r))
              and np.array_equal(np.asarray(v_p), np.asarray(v_r)),
              f"select_k_pallas 1024x8192 k={k} equals lax.top_k")
    rows = min(GT_BLOCK, ds.base.shape[0])
    sub = ds.base[:rows]
    bf_d, bf_i = brute_force.knn(sub, ds.queries, K, metric=ds.metric)
    ex_d, ex_i = exact_topk(sub, ds.queries, K)
    same, tie = same_results(bf_d, bf_i, ex_d, ex_i, rtol=1e-5)
    phase("kernels", t0, fused_knn_rows_equal=f"{same}/{N_BATCH}",
          fused_knn_rows_tied=tie, fused_knn_base_rows=rows)
    check(same + tie == N_BATCH,
          f"fused brute-force kNN over {rows} rows equals exact top-k")


def index_params(n, d, metric):
    from raft_tpu.neighbors import ivf_pq

    return ivf_pq.IndexParams(
        n_lists=n // ROWS_PER_LIST, metric=metric, pq_dim=d // 2
    )


def build_phase(clock, params, base):
    import jax

    from raft_tpu.neighbors import ivf_pq

    t0 = time.perf_counter()
    c0, by0 = clock.seconds, clock.by_fun.copy()
    index = ivf_pq.build(params, base)
    jax.block_until_ready(index.list_data)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    phase("build", t0, n_lists=index.n_lists, list_cap=index.list_cap,
          decoded=index.list_data.dtype, compile_s=f"{compile_s:.3f}",
          run_s=f"{wall - compile_s:.3f}")
    clock.report(by0)
    return index


def same_results(d_a, i_a, d_b, i_b, rtol=1e-6):
    """(rows with identical ids, rows differing only inside distance ties).
    A row whose ids differ but whose distances match is a tie reordering:
    both answers are the exact top-k."""
    import numpy as np

    d_a, d_b = np.asarray(d_a), np.asarray(d_b)
    same = (np.asarray(i_a) == np.asarray(i_b)).all(axis=1)
    tie = ~same & np.isclose(d_a, d_b, rtol=rtol, atol=0).all(axis=1)
    return int(same.sum()), int(tie.sum())


def settled_stats(svc, name, requests):
    """``svc.stats(name)`` once the batcher has booked ``requests``: it
    books a batch just after resolving the batch's futures."""
    deadline = time.monotonic() + 30
    st = svc.stats(name)
    while st["requests"] < requests and time.monotonic() < deadline:
        time.sleep(0.01)
        st = svc.stats(name)
    return st


def serve_singles(svc, name, queries):
    """``len(queries)`` single-vector requests from CLIENTS threads."""
    import numpy as np

    n = queries.shape[0]
    dist = np.zeros((n, K), np.float32)
    ids = np.zeros((n, K), np.int64)
    errors = []

    def client(c):
        try:
            for i in range(c, n, CLIENTS):
                d, j = svc.search(name, queries[i], timeout=300)
                dist[i], ids[i] = np.asarray(d), np.asarray(j)
        except Exception as e:  # noqa: BLE001 — reported as a failed check
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    hung = sum(t.is_alive() for t in threads)
    if hung:
        errors.append(f"{hung} clients hung")
    return dist, ids, errors


def one_chip(args, check):
    import jax
    import numpy as np

    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.refine import refine
    from raft_tpu.serve.mutation import REFINE_RATIO
    from raft_tpu.stats import recall_at_k

    clock = CompileClock()
    ds, base = make_data(args.seed, args.scale)
    n, d = ds.base.shape
    index = build_phase(clock, index_params(n, d, ds.metric), base)

    t0 = time.perf_counter()
    sp = ivf_pq.SearchParams(n_probes=N_PROBES)
    mi = serve.MutableIndex(index, search_params=sp, refine_dataset=base)
    svc = serve.SearchService(k=K, max_batch=1024)
    try:
        c0, by0 = clock.seconds, clock.by_fun.copy()
        svc.add_index("deep", mi, warmup=True)
        st = svc.stats("deep")
        phase("warmup", t0, warmup_compiles=st["warmup_compiles"],
              compile_s=f"{clock.seconds - c0:.3f}")
        clock.report(by0)

        t0 = time.perf_counter()
        singles = ds.queries[:N_SINGLE]
        s_dist, s_ids, errors = serve_singles(svc, "deep", singles)
        t_single = time.perf_counter() - t0
        t1 = time.perf_counter()
        b_dist, b_ids = svc.search("deep", ds.queries, timeout=600)
        b_dist, b_ids = np.asarray(b_dist), np.asarray(b_ids)
        t_batch = time.perf_counter() - t1
        st = settled_stats(svc, "deep", N_SINGLE + 1)
        phase("serve", t0, singles=N_SINGLE, clients=CLIENTS,
              single_s=f"{t_single:.3f}", batch_queries=N_BATCH,
              batch_s=f"{t_batch:.3f}", requests=st["requests"],
              batches=st["batches"], p50_ms=st["p50_ms"], p99_ms=st["p99_ms"],
              kernel_paths=st["kernel_paths"])
    finally:
        svc.stop()

    t0 = time.perf_counter()
    _, gt = exact_topk(ds.base, ds.queries, K)
    recall = recall_at_k(b_ids, gt)
    recall_single = recall_at_k(s_ids, gt[:N_SINGLE])
    phase("groundtruth", t0, recall_at_10=recall,
          recall_at_10_singles=recall_single)
    kernel_checks(ds, check)

    t0 = time.perf_counter()
    q = jax.device_put(ds.queries)
    _, cand = ivf_pq.search(sp, index, q, K * REFINE_RATIO)
    r_dist, r_ids = refine(base, q, cand, K, metric=ds.metric)
    same_b, tie_b = same_results(b_dist, b_ids, r_dist, r_ids)
    same_s, tie_s = same_results(s_dist, s_ids, np.asarray(r_dist)[:N_SINGLE],
                                 np.asarray(r_ids)[:N_SINGLE])
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    phase("checks", t0, batch_rows_equal=f"{same_b}/{N_BATCH}",
          batch_rows_tied=tie_b, single_rows_equal=f"{same_s}/{N_SINGLE}",
          single_rows_tied=tie_s, peak_bytes_in_use=peak)
    check(recall >= MIN_RECALL, f"recall@10 {recall} >= {MIN_RECALL}")
    check(same_b + tie_b == N_BATCH and same_s + tie_s == N_SINGLE,
          "served ids equal ivf_pq.search + refine of the same queries")
    check(st["recompiles"] == 0, f"recompiles after warmup: {st['recompiles']}")
    check(not st["errors"] and not errors,
          f"batch errors: {st['errors']}, client errors: {errors}")
    check(set(st["kernel_paths"]) == {"pallas"},
          f"scan dispatches on the pallas path: {st['kernel_paths']}")
    check(peak is not None, f"peak_bytes_in_use reported: {peak}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=0.1,
                    help="fraction of the published 9.99M rows")
    args = ap.parse_args(argv)
    device = device_phase()
    check = Checks()
    one_chip(args, check)
    if check.failed:
        sys.exit(f"chip_smoke: {len(check.failed)} check(s) failed")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
