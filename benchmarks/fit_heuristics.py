#!/usr/bin/env python
"""Fit dispatch-heuristic constants from a measured prims sweep.

The reference trains its select_k algorithm dispatch offline from GPU
sweeps (cpp/include/raft/matrix/detail/select_k-inl.cuh:47-75, notebooks
cpp/scripts/heuristics/select_k/).  This is the TPU analog: consume
``benchmarks/prims_tpu.json`` (written on-chip by ``raft_tpu.bench.prims``) and
report, per primitive, the measured decision boundary next to the
constant the dispatch currently hard-codes:

- ``select_k_ab/<rows>x<cols>/k<k>/{topk,chunked}`` →
  recommended ``_CHUNKED_MIN_N`` (ops/matrix.py)
- ``ivf_scan_ab/.../{query_major,probe_major[,_pallas]}`` →
  query-vs-probe-major and Pallas-promotion verdicts
  (neighbors/_common.select_scan_strategy / pallas_scan_enabled)

Usage: python benchmarks/fit_heuristics.py [benchmarks/prims_tpu.json]
Prints one JSON document; write the recommendations back into the
constants by hand (each constant carries a comment citing this artifact).
"""

import json
import re
import sys
from collections import defaultdict


def main() -> None:
    path = sys.argv[1] if len(sys.argv) > 1 else "benchmarks/prims_tpu.json"
    rows = json.load(open(path))
    by_name = {r["name"]: r["seconds"] for r in rows}
    platform = rows[0]["platform"] if rows else "?"

    # --- select_k: per (rows, cols, k), which algo wins and by how much
    shapes = defaultdict(dict)
    for name, secs in by_name.items():
        m = re.match(r"select_k_ab/(\d+)x(\d+)/k(\d+)/(topk|chunked)", name)
        if m:
            r, c, k, algo = int(m[1]), int(m[2]), int(m[3]), m[4]
            shapes[(r, c, k)][algo] = secs
    table = []
    for (r, c, k), d in sorted(shapes.items()):
        if {"topk", "chunked"} <= d.keys():
            table.append({
                "rows": r, "cols": c, "k": k,
                "topk_s": d["topk"], "chunked_s": d["chunked"],
                "winner": "chunked" if d["chunked"] < d["topk"] else "topk",
                "speedup": round(max(d.values()) / min(d.values()), 3),
            })
    # smallest cols where chunked wins for every k at that cols AND at
    # every larger swept cols (guards against a noise win at one small
    # shape steering the whole large-n regime to the slower path)
    chunked_min_n = None
    swept = sorted({t["cols"] for t in table})
    for c in swept:
        tail = [
            t for t in table if t["cols"] >= c and t["rows"] == 1024
        ]
        if tail and all(t["winner"] == "chunked" for t in tail):
            chunked_min_n = c
            break

    # --- ivf scan schedules
    scan = {
        name.split("/")[-1]: secs
        for name, secs in by_name.items() if name.startswith("ivf_scan_ab")
    }
    scan_verdict = {}
    if {"query_major", "probe_major"} <= scan.keys():
        scan_verdict["probe_major_vs_query_major"] = round(
            scan["query_major"] / scan["probe_major"], 3
        )
    if {"probe_major", "probe_major_pallas"} <= scan.keys():
        scan_verdict["pallas_vs_xla_probe_major"] = round(
            scan["probe_major"] / scan["probe_major_pallas"], 3
        )
        scan_verdict["promote_pallas_default"] = (
            scan["probe_major_pallas"] < scan["probe_major"]
        )

    print(json.dumps({
        "platform": platform,
        "select_k_table": table,
        "recommended_CHUNKED_MIN_N": chunked_min_n,
        "scan_seconds": scan,
        "scan_verdict": scan_verdict,
    }, indent=2))


if __name__ == "__main__":
    main()
