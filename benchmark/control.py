"""Readings that set the limits of ``correct``, on the chip at a cell's size.

    python3 benchmark/control.py --workload deep1m-ivfpq.bulk --seeds 11,12,13 \
        --faults control,half_probes,int8_cache --seconds 5

Each reading goes through ``check.judge``, as a run's served answers do,
and prints one line with ``correct`` and every number compared beside its
limit.  The benchmark's own runs never run this.

- ``control``: the plain reference put in the program's place, computed in
  the nearest precision below the configuration's float32 (bfloat16 rows
  and queries at the default matmul precision).  It answers every query of
  the seed's pool, in requests of the cell's size; it has to come out not
  correct.
- ``half_probes``, ``quarter_probes``: the cell run through the served path
  (``run.run_cell``) with ``n_probes`` halved or quartered.
- ``int8_cache``: the IVF-PQ cell with an int8 decoded scan cache in place
  of the configured bfloat16.

The faults are what a later PR could be tempted to trade recall for; their
readings set the upper end of ``recall_miss``'s limit (PERF.md).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(os.path.abspath(__file__))]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402


def control(cfg: dict, traffic: dict, seed: int,
            precision: str = "bf16") -> dict:
    """The reference's answers to the whole pool in ``precision``, judged."""
    import numpy as np

    from benchmark.lib import check, data, reference
    from benchmark.lib.traffic import Request

    base, queries = data.make(cfg, seed)
    pool = np.asarray(queries)
    k, metric = int(cfg["k"]), cfg["metric"]
    truth = reference.search(base, pool, k, metric)[1]
    d, i = reference.search(base, pool, k, metric, precision=precision)
    m = int(traffic["rows_per_request"])
    requests = []
    for s in range(0, pool.shape[0], m):
        rows = np.arange(s, min(s + m, pool.shape[0]))
        requests.append(Request(t_sched=0.0, rows=rows, t_done=0.0,
                                dists=d[rows], ids=i[rows]))
    checks, correct = check.judge(requests, base, pool, truth, metric,
                                  cfg["correct"], 0)
    return {"correct": correct, "attempted": len(requests), "checks": checks}


def faulty(cfg: dict, fault: str) -> dict:
    """``cfg`` with ``fault`` planted."""
    cfg = copy.deepcopy(cfg)
    search = cfg["index"]["search"]
    if fault == "half_probes":
        search["n_probes"] = max(1, search["n_probes"] // 2)
    elif fault == "quarter_probes":
        search["n_probes"] = max(1, search["n_probes"] // 4)
    elif fault == "int8_cache":
        if cfg["index"]["kind"] != "ivf_pq":
            raise SystemExit("int8_cache is a fault of IVF-PQ cells")
        cfg["index"]["build"]["decoded_dtype"] = "int8"
        cfg["index"]["scan_elem_bytes"] = 1
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="control")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench_run.prepare_env()
    bench_run.chips_of(1, True)
    bench = bench_run.load_json(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, specs = bench_run.cell_inputs(bench, args.workload,
                                                      False)
    for fault in args.faults.split(","):
        for s in args.seeds.split(","):
            if fault == "control":
                out = control(cfg, traffic, int(s))
            else:
                result, _ = bench_run.run_cell(
                    faulty(cfg, fault), traffic, int(cell["chips"]), int(s),
                    args.seconds, False, specs)
                out = {k: result[k] for k in ("correct", "attempted",
                                              "failed", "checks")}
            print("READING " + json.dumps({"workload": args.workload,
                                           "fault": fault, "seed": int(s),
                                           **out}), flush=True)


if __name__ == "__main__":
    main()
