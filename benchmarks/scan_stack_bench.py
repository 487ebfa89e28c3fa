#!/usr/bin/env python
"""Time the probe-major IVF list scan alone at a bulk cell's shapes, for
several stack heights (buckets folded per grid step).

    python benchmarks/scan_stack_bench.py --shape deep --stacks 1,4,8,16
    python benchmarks/scan_stack_bench.py --shape sift --stacks 1,2,4,8

Random lists and queries at the cell's geometry: ``deep`` is
``deep1m-ivfpq`` (2,118 lists of capacity 1,000, bf16 rows 128 lanes wide,
inner product, 1,024 queries x 32 probes, 40 candidates); ``sift`` is
``sift1m-ivfflat`` (1,129 lists of capacity 1,960, f32 rows of 128, L2,
1,024 x 5 probes, k 10).  Buckets of 16 queries come from
``invert_probes`` over uniformly random probes, as the serving path makes
them.  For each height the scan runs at ``kk`` = 1 and at the cell's kk:
the difference over kk - 1 is the cost of one extraction round, the rest
is DMA and scoring.  Every height's partials are checked bit for bit
against height 1.  A height the compiler refuses (its step exceeds the
default scoped VMEM) is reported as refused.  Needs a TPU; prints one
JSON line per (height, kk) and writes them to ``--out``.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {
    "deep": dict(L=2118, cap=1000, fill=472, rot=128, dtype="bfloat16",
                 q=1024, p=32, kk=40, metric="inner_product"),
    "sift": dict(L=1129, cap=1960, fill=886, rot=128, dtype="float32",
                 q=1024, p=5, kk=10, metric="sqeuclidean"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="deep")
    ap.add_argument("--stacks", default="1,4,8,16")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from raft_tpu.kernels import ivf_scan
    from raft_tpu.neighbors._common import invert_probes

    if jax.devices()[0].platform != "tpu":
        sys.exit("scan_stack_bench needs a TPU")
    sh = SHAPES[args.shape]
    L, cap, rot, G = sh["L"], sh["cap"], sh["rot"], 16
    rng = np.random.default_rng(args.seed)
    data = jnp.asarray(rng.standard_normal((L, cap, rot), np.float32),
                       sh["dtype"])
    y2 = jnp.sum(data.astype(jnp.float32) ** 2, axis=-1)
    ids = np.arange(L * cap, dtype=np.int32).reshape(L, cap)
    ids[:, sh["fill"]:] = -1
    ids = jnp.asarray(ids)
    probes = jnp.asarray(np.stack([
        rng.choice(L, sh["p"], replace=False) for _ in range(sh["q"])
    ]).astype(np.int32))
    queries = jnp.asarray(rng.standard_normal((sh["q"], rot), np.float32))
    bl, bq, _, B = invert_probes(probes, L, G)
    qg = queries[jnp.clip(bq, 0)]
    q2 = jnp.where(bq >= 0, jnp.sum(queries ** 2, 1)[jnp.clip(bq, 0)],
                   jnp.inf)
    live = int(jnp.sum(jnp.isfinite(q2[:, 0])))

    def run(stack, kk):
        fn = jax.jit(lambda *xs: ivf_scan._ivf_scan_probe_major_dense(
            *xs, kk, metric=sh["metric"], scan_dtype="float32",
            list_filter=None, scan_scale=1.0, interpret=False, stack=stack))
        out = jax.block_until_ready(fn(bl, qg, q2, data, y2, ids))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(bl, qg, q2, data, y2, ids))
            times.append(time.perf_counter() - t0)
        return statistics.median(times), out

    lines, ref = [], {}
    for stack in [int(s) for s in args.stacks.split(",")]:
        per_kk = {}
        est = ivf_scan._stack_vmem_bytes(
            stack, G, cap, rot, data.dtype.itemsize, sh["kk"], 1)
        for kk in (1, sh["kk"]):
            try:
                t, (v, i) = run(stack, kk)
            except Exception as e:     # the compiler refuses the height
                line = dict(shape=args.shape, stack=stack, kk=kk,
                            vmem_est_mib=round(est / 2**20, 2),
                            refused=str(e).splitlines()[0][:300])
                lines.append(line)
                print(json.dumps(line), flush=True)
                break
            if stack == 1:
                ref[kk] = (np.asarray(v), np.asarray(i))
            equal = bool(
                np.array_equal(np.asarray(v), ref[kk][0])
                and np.array_equal(np.asarray(i), ref[kk][1])
            )
            per_kk[kk] = t
            line = dict(shape=args.shape, stack=stack, kk=kk, buckets=B,
                        live_buckets=live, ms=round(t * 1e3, 4),
                        us_per_live_bucket=round(t * 1e6 / live, 4),
                        vmem_est_mib=round(est / 2**20, 2),
                        equal_to_stack1=equal,
                        device=jax.devices()[0].device_kind)
            lines.append(line)
            print(json.dumps(line), flush=True)
        if len(per_kk) < 2:
            continue
        steps = -(-live // stack)
        kk = sh["kk"]
        round_ns = (per_kk[kk] - per_kk[1]) / (kk - 1) / steps * 1e9
        line = dict(shape=args.shape, stack=stack, rows=stack * G,
                    ns_per_round=round(round_ns, 1),
                    us_per_bucket_rounds=round(
                        (per_kk[kk] - per_kk[1]) * 1e6 / live, 4),
                    us_per_bucket_rest=round(per_kk[1] * 1e6 / live, 4))
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
