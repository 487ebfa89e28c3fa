"""Result export: CSV tables, schema-versioned bench records, and the
noise-aware record comparator behind ``bench.py compare``.

CSV side (ref: raft-ann-bench data_export — flattens the per-run JSON
into build/search CSV tables for plotting) is unchanged.  The record side
is the regression gate: every bench leg wraps its one-line JSON payload
in :func:`bench_record` and writes it via :func:`write_bench_record`, so
any two runs — across rounds, machines, or branches — can be diffed with
:func:`compare_records`.  Thresholds are *noise-aware*: throughput and
latency compare relatively (default ±25%, wide enough for shared-CPU CI
jitter, narrow enough to catch a 2x regression), recall compares with an
absolute tolerance, and a hot-path recompile appearing where the
baseline had none is always a failure regardless of timing.
"""

from __future__ import annotations

import csv
import json
import os
from typing import Dict, List, Optional, Tuple

from raft_tpu.bench.runner import RunResult
from raft_tpu.core import env as _env

_FIELDS = [
    "algo", "dataset", "k", "build_param", "search_param",
    "build_time_s", "qps", "latency_ms", "recall", "end_to_end_s",
    "device_time_s", "device_qps",
]

#: bump when the record envelope (not the payload) changes shape
BENCH_SCHEMA_VERSION = 1

#: env var naming the default record path bench legs write to
RECORD_PATH_ENV = "RAFT_TPU_BENCH_RECORD"
DEFAULT_RECORD_PATH = "BENCH_last.json"


def to_csv(results: List[RunResult], path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", newline="") as fh:
        w = csv.DictWriter(fh, fieldnames=_FIELDS)
        w.writeheader()
        for r in results:
            d = r.to_dict()
            d["build_param"] = json.dumps(d["build_param"])
            d["search_param"] = json.dumps(d["search_param"])
            w.writerow(d)


def from_json(path: str) -> List[RunResult]:
    with open(path) as fh:
        return [RunResult(**d) for d in json.load(fh)]


# ---- schema-versioned bench records ----------------------------------------

def kernel_path(
    metric: Optional[str] = None,
    storage_dtype=None,
    *,
    pallas: Optional[bool] = None,
) -> Dict[str, object]:
    """Which kernel implementation a record's numbers are attributable to.

    Every record carries this (stamped by :func:`bench_record` if the leg
    didn't set it), so "pallas won X%" claims are checkable against the
    record instead of against memory.  Pass ``pallas=`` when the leg
    measured the routing itself (the accel A/B leg does); pass
    ``metric``/``storage_dtype`` to ask the shared
    :func:`~raft_tpu.neighbors._common.pallas_scan_enabled` gate; with
    neither, ask ``kernels.use_pallas()`` alone.
    """
    if pallas is None:
        if metric is not None and storage_dtype is not None:
            from raft_tpu.neighbors._common import pallas_scan_enabled

            pallas = pallas_scan_enabled(metric, storage_dtype)
        else:
            from raft_tpu.kernels import use_pallas

            pallas = use_pallas()
    return {"pallas": bool(pallas)}


def bench_record(payload: Dict[str, object]) -> Dict[str, object]:
    """Wrap one bench leg's JSON payload in the versioned envelope.

    Stamps a default :func:`kernel_path` into payloads that lack one —
    additive, so records written before the field existed still load and
    compare (absence is simply not reported).
    """
    if not isinstance(payload, dict) or "metric" not in payload:
        raise ValueError(
            "bench payload must be a dict with a 'metric' key, got "
            f"{type(payload).__name__}"
        )
    rec = dict(payload)
    rec.setdefault("kernel_path", kernel_path())
    return {
        "schema": "raft_tpu.bench",
        "schema_version": BENCH_SCHEMA_VERSION,
        "record": rec,
    }


def write_bench_record(
    payload: Dict[str, object], path: Optional[str] = None
) -> str:
    """Write the enveloped record; returns the path written.

    Default path comes from ``RAFT_TPU_BENCH_RECORD`` (set it to ``-`` or
    empty to suppress the write) falling back to ``BENCH_last.json`` in
    the working directory — every leg leaves a comparable artifact even
    when nobody asked for one.
    """
    if path is None:
        path = _env.env_str(RECORD_PATH_ENV, DEFAULT_RECORD_PATH)
    if not path or path == "-":
        return ""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as fh:
        json.dump(bench_record(payload), fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path


def load_record(path: str) -> Dict[str, object]:
    """Load a bench payload from any of the formats in the wild.

    Accepts the :func:`bench_record` envelope, the driver's historical
    ``BENCH_r0N.json`` wrapper (payload under ``"parsed"``), or a bare
    payload dict (a captured stdout line).  Returns the payload.
    """
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got "
                         f"{type(doc).__name__}")
    if doc.get("schema") == "raft_tpu.bench":
        ver = doc.get("schema_version")
        if ver != BENCH_SCHEMA_VERSION:
            raise ValueError(
                f"{path}: unsupported bench schema_version {ver!r} "
                f"(this build reads {BENCH_SCHEMA_VERSION})"
            )
        payload = doc.get("record")
    elif "parsed" in doc:  # BENCH_r0N.json driver wrapper
        payload = doc["parsed"]
    else:
        payload = doc
    if not isinstance(payload, dict) or "metric" not in payload:
        raise ValueError(f"{path}: no bench payload with a 'metric' key")
    return payload


# ---- noise-aware comparison ------------------------------------------------

#: units where a LARGER primary value is better; everything that looks
#: like a duration (ms / s suffix) is treated as smaller-is-better
_HIGHER_IS_BETTER_UNITS = ("/s", "qps", "ops")


def _higher_is_better(unit: str) -> bool:
    u = (unit or "").lower()
    return any(tok in u for tok in _HIGHER_IS_BETTER_UNITS)


def compare_records(
    baseline: Dict[str, object],
    candidate: Dict[str, object],
    *,
    rtol: float = 0.25,
    recall_atol: float = 0.02,
) -> Tuple[bool, List[str]]:
    """Diff two bench payloads; returns (ok, report_lines).

    ``ok`` is False on any regression: primary value worse than the
    baseline by more than ``rtol`` (direction inferred from ``unit``),
    recall lower by more than ``recall_atol``, or hot-path recompiles
    appearing where the baseline had none.  Improvements and in-tolerance
    drift are reported but pass.  Records for *different* metrics (or
    different platforms) are incomparable — reported as skipped, ok=True —
    so a CI job pointed at a stale baseline degrades to a no-op instead
    of a false alarm.
    """
    lines: List[str] = []
    ok = True

    b_metric, c_metric = baseline.get("metric"), candidate.get("metric")
    if b_metric != c_metric:
        lines.append(
            f"SKIP incomparable metrics: baseline={b_metric!r} "
            f"candidate={c_metric!r}"
        )
        return True, lines
    b_plat, c_plat = baseline.get("platform"), candidate.get("platform")
    if b_plat != c_plat:
        lines.append(
            f"SKIP incomparable platforms: baseline={b_plat!r} "
            f"candidate={c_plat!r}"
        )
        return True, lines
    lines.append(f"metric {b_metric} (platform={b_plat})")

    # primary value, direction by unit
    try:
        bv = float(baseline["value"])
        cv = float(candidate["value"])
    except (KeyError, TypeError, ValueError):
        lines.append("SKIP no comparable 'value' field")
        return True, lines
    unit = str(candidate.get("unit") or baseline.get("unit") or "")
    hib = _higher_is_better(unit)
    ratio = (cv / bv) if bv else float("inf")
    worse = ratio < (1.0 - rtol) if hib else ratio > (1.0 + rtol)
    tag = "REGRESSION" if worse else "ok"
    lines.append(
        f"  value: {bv:g} -> {cv:g} {unit} "
        f"({ratio:.0%} of baseline, {'higher' if hib else 'lower'} is "
        f"better, rtol={rtol:.0%}) {tag}"
    )
    ok &= not worse

    # secondary latency percentiles (always lower-is-better)
    for field in ("p50_ms", "p99_ms", "latency_ms"):
        b, c = baseline.get(field), candidate.get(field)
        if b is None or c is None:
            continue
        b, c = float(b), float(c)
        if b <= 0:
            continue
        r = c / b
        worse = r > (1.0 + rtol)
        tag = "REGRESSION" if worse else "ok"
        lines.append(f"  {field}: {b:g} -> {c:g} ({r:.0%} of baseline) {tag}")
        ok &= not worse

    # recall: absolute tolerance — relative thresholds are meaningless on
    # a [0, 1] quantity pinned near 1
    b, c = baseline.get("recall"), candidate.get("recall")
    if b is not None and c is not None:
        b, c = float(b), float(c)
        worse = c < b - recall_atol
        tag = "REGRESSION" if worse else "ok"
        lines.append(
            f"  recall: {b:.4f} -> {c:.4f} (atol={recall_atol}) {tag}"
        )
        ok &= not worse

    # hot-path recompiles: zero tolerance once the baseline achieved zero
    b, c = baseline.get("recompiles"), candidate.get("recompiles")
    if b is not None and c is not None and int(b) == 0 and int(c) > 0:
        lines.append(
            f"  recompiles: 0 -> {int(c)} REGRESSION (hot-path XLA "
            "compiles reappeared)"
        )
        ok = False

    # kernel path: informational, never a failure — but a value delta
    # measured across a pallas-routing change is not apples-to-apples,
    # so say which kernels produced each side (absent in old records)
    b, c = baseline.get("kernel_path"), candidate.get("kernel_path")
    if (b is not None or c is not None) and b != c:
        lines.append(
            f"  kernel_path: {json.dumps(b)} -> {json.dumps(c)} "
            "(info: sides ran different kernel routings)"
        )

    lines.append("PASS" if ok else "FAIL")
    return ok, lines


def compare_main(argv: Optional[List[str]] = None) -> int:
    """CLI body shared by ``bench.py compare`` and
    ``python -m raft_tpu.bench compare``.  Exit 0 on pass/skip, 1 on
    regression, 2 on usage/IO errors."""
    import argparse

    ap = argparse.ArgumentParser(
        "bench compare",
        description="Diff two bench records with noise-aware thresholds.",
    )
    ap.add_argument("--baseline", required=True,
                    help="baseline record (BENCH_last.json / BENCH_r0N.json)")
    ap.add_argument("--candidate", default="",
                    help="candidate record; default: run the CPU bench leg "
                    "now and compare its record")
    ap.add_argument("--rtol", type=float, default=0.25,
                    help="relative tolerance for value/latency (default .25)")
    ap.add_argument("--recall-atol", type=float, default=0.02,
                    help="absolute tolerance for recall (default .02)")
    args = ap.parse_args(argv)

    try:
        baseline = load_record(args.baseline)
    except (OSError, ValueError) as e:
        print(f"compare: cannot load baseline: {e}")
        return 2
    cand_path = args.candidate
    if not cand_path:
        import subprocess
        import sys
        import tempfile

        cand_path = os.path.join(
            tempfile.mkdtemp(prefix="raft_tpu_bench_"), "candidate.json"
        )
        env = dict(os.environ, **{RECORD_PATH_ENV: cand_path})
        bench_py = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "bench.py")
        print(f"compare: no --candidate; running {bench_py} --run-leg cpu")
        proc = subprocess.run(
            [sys.executable, bench_py, "--run-leg", "cpu"], env=env
        )
        if proc.returncode != 0 or not os.path.exists(cand_path):
            print(f"compare: candidate leg failed (rc={proc.returncode})")
            return 2
    try:
        candidate = load_record(cand_path)
    except (OSError, ValueError) as e:
        print(f"compare: cannot load candidate: {e}")
        return 2
    ok, lines = compare_records(
        baseline, candidate, rtol=args.rtol, recall_atol=args.recall_atol
    )
    print("\n".join(lines))
    return 0 if ok else 1
