"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, in this order: make the configuration's base rows and query
pool on the device from ``--seed``; build the index; register it as a
``MutableIndex`` in a ``SearchService`` and warm the cell's buckets and its
traffic; drive the traffic for ``--seconds`` (profiled with ``--trace 1``);
read device memory; free the program's state; run the plain reference and
judge every served answer; print the check lines on stderr and the one
result line on stdout.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name BENCHMARK.json gives it:
``configs/<config>.json``, ``algos/<index kind>.py``, ``traffic/<mix>.json``
and ``metrics/<metric>.py``.  A run without a TPU, with fewer chips than
the cell asks for, or on a device kind with no published peaks exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from the process's start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")
INDEX_NAME = "cell"


def log(phase: str, t0: float, **kv) -> float:
    """One stderr line per phase, with its seconds; returns the time now."""
    now = time.perf_counter()
    fields = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {now - t0:.3f}s {fields}", file=sys.stderr, flush=True)
    return now


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def prepare_env() -> None:
    """Run-time settings that must precede the first JAX import."""
    # import the checkout's packages, never this directory's files as
    # top-level modules
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    # the AOT cost programs of the warmup measure the implementation's HLO,
    # not the work, and took 20-26 s of every warmup (PERF.md): off
    os.environ["RAFT_TPU_COST_ACCOUNTING"] = "0"
    program_env()


def program_env() -> None:
    """Settings of the program that it reads at run time."""
    # the perf ledger's regression trips start 1 s profiler captures from
    # the serving path, which stalled the online cell for seconds at a time
    # (PERF.md); the benchmark owns the profiler
    os.environ["RAFT_TPU_PERF_CAPTURE_S"] = "0"
    # with captures off, the trips' event chain (flight dump, incident)
    # still stalled serving: 78 ms p95 against 13 ms with the ledger off,
    # same seed (PERF.md); no metric reads the ledger
    os.environ["RAFT_TPU_PERF_LEDGER"] = "0"
    # a fixed path inside the checkout, unless the machine names one
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def cell_inputs(bench: dict, workload: str, trace: bool):
    """(cell, config, traffic, metric specs) of ``workload``."""
    cell = by_name(bench["workloads"], workload, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "config")
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    specs = bench["per_layer" if trace else "end_to_end"]
    specs = [m for m in specs if workload in m.get("workloads", [workload])]
    return cell, cfg, traffic, specs


def read_metrics(specs, run) -> dict:
    out = {}
    for spec in specs:
        reader = load_module(
            os.path.join(HERE, "metrics", spec["name"] + ".py"),
            "metric_" + spec["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


class CompileCounter:
    """Programs JAX had to compile or load (``backend_compile_duration``
    fires for both; ``cache_hits`` for a load from the persistent cache)
    and seconds of JAX's compile events, from jax.monitoring.  ``window``
    counts the programs compiled or loaded while the window is open."""

    def __init__(self):
        import jax

        self.count = 0
        self.hits = 0
        self.seconds = 0.0
        self.window = 0
        self._armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.window += self._armed
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _on_event(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        """Programs compiled or loaded, cache hits and compile seconds since
        ``mark``, and a new mark."""
        now = (self.count, self.hits, self.seconds)
        return {"programs": now[0] - mark[0], "cache_hits": now[1] - mark[1],
                "compile_s": round(now[2] - mark[2], 3)}, now

    def start(self):
        self.window, self._armed = 0, True

    def stop(self) -> int:
        self._armed = False
        return self.window


def chips_of(chips: int, require_tpu: bool):
    import jax

    from benchmark.lib.peaks import PEAKS

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu:
        if d0.platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {d0.platform!r}")
        if len(devs) < chips:
            raise NoChip(f"cell asks for {chips} chips, JAX found {len(devs)}")
        if d0.device_kind not in PEAKS:
            raise NoChip(f"no published peaks for {d0.device_kind!r}")
    return devs


def run_cell(cfg: dict, traffic: dict, chips: int, seed: int, seconds: float,
             trace: bool, specs, *, require_tpu: bool = True):
    """Run one cell; returns (result line dict, checks)."""
    import jax
    import numpy as np

    from benchmark.lib import check, data, reference
    from benchmark.lib import trace as trace_lib
    from benchmark.lib import traffic as traffic_lib
    from benchmark.lib.peaks import PEAKS

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    program_env()
    devs = chips_of(chips, require_tpu)
    used = devs[:chips]
    compiles = CompileCounter()
    mark = (0, 0, 0.0)
    t = log("devices", T_START, platform=devs[0].platform,
            kind=repr(devs[0].device_kind), count=len(devs))

    from raft_tpu import serve

    base, queries = data.make(cfg, seed)
    pool = np.asarray(queries)
    c, mark = compiles.since(mark)
    t = log("data", t, rows=base.shape[0], pool=pool.shape[0], **c)
    algo = load_module(os.path.join(HERE, "algos", cfg["index"]["kind"] + ".py"),
                       "algo_" + cfg["index"]["kind"])
    index, shapes = algo.build(cfg, base)
    c, mark = compiles.since(mark)
    t = log("build", t, **shapes, **c)
    del base, queries  # a refined index keeps its own reference to the rows
    svc = serve.SearchService(
        k=int(cfg["k"]), min_bucket=int(traffic["min_bucket"]),
        max_batch=int(traffic["max_batch"]),
        max_delay_ms=float(traffic["max_delay_ms"]), cost_accounting=False)
    try:
        svc.add_index(INDEX_NAME, index, warmup=True)
        c, mark = compiles.since(mark)
        t = log("warmup", t, **c)

        def submit(rows):
            return svc.submit(INDEX_NAME, rows)

        # real rows through every bucket the traffic can fill, then one
        # second of the cell's own traffic, so that every shape and path
        # the window takes has run once before it opens
        traffic_lib.warm_buckets(submit, pool, traffic)
        traffic_lib.run(submit, pool, traffic, 1.0, seed + 1)
        stats0 = svc.stats(INDEX_NAME)
        c, mark = compiles.since(mark)
        t = log("warm_traffic", t, **c)
        setup_s = time.perf_counter() - T_START
        trace_dir = os.path.join(OUT_DIR, "trace")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_lib.options())
        compiles.start()
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                win = traffic_lib.run(submit, pool, traffic, seconds, seed)
        finally:
            window_compiles = compiles.stop()
            if trace:
                jax.profiler.stop_trace()
        stats1 = svc.stats(INDEX_NAME)
        mem = [d.memory_stats() or {} for d in used]
        resident = max(m.get("bytes_in_use", 0) for m in mem)
        peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        t = log("window", t, requests=len(win.requests),
                batches=stats1["batches"] - stats0["batches"],
                compiles=window_compiles, bytes_in_use=resident,
                peak_bytes_in_use=peak,
                kernel_paths=stats1.get("kernel_paths"))
    finally:
        svc.stop()
    del svc, index
    gc.collect()

    # the reference: its own rows from the seed, after the program's state
    # is freed and memory has been read
    base, _ = data.make(cfg, seed)
    truth = reference.search(base, pool, int(cfg["k"]), cfg["metric"])[1]
    checks, correct = check.judge(win.requests, base, pool, truth,
                                  cfg["metric"], cfg["correct"],
                                  window_compiles)
    del base
    t = log("reference", t)
    reduction = None
    if trace:
        device, host = trace_lib.load(trace_lib.latest_xplane(trace_dir))
        reduction = trace_lib.reduce(device, host, trace_lib.window_of(host))
        t = log("trace", t, ops=reduction["n_ops"], host_spans=len(host))
    run = SimpleNamespace(
        cfg=cfg, traffic=traffic, seed=seed, seconds=seconds, window=win,
        setup_s=setup_s, stats0=stats0, stats1=stats1,
        resident_bytes=resident, peak_bytes=peak,
        rows_answered=int(check.answered_rows(win.requests)[0].size),
        shapes=shapes, trace=reduction,
        peaks=PEAKS.get(devs[0].device_kind))
    result = {
        "correct": correct,
        "attempted": len(win.requests),
        "failed": sum(not r.ok for r in win.requests),
        "metrics": read_metrics(specs, run),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": int(peak)},
    }
    if reduction is not None:
        result["device"].update(busy_s=reduction["busy_s"],
                                window_s=reduction["window_s"])
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduction["device_ops"]],
            "idle_gaps": [list(x) for x in reduction["idle_gaps"]],
        }
    result["checks"] = checks
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_env()
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, specs = cell_inputs(bench, args.workload,
                                            bool(args.trace))
    try:
        result, checks = run_cell(cfg, traffic, int(cell["chips"]), args.seed,
                                  args.seconds, bool(args.trace), specs)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    from benchmark.lib.check import check_lines

    for line in check_lines(checks):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
