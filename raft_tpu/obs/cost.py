"""XLA capacity accounting: what each compiled executable costs the chip.

"Memory Safe Computations with XLA Compiler" (PAPERS.md) makes the case
that memory/compute figures must come from the compiler, not from a
guess: XLA already knows the FLOPs, the bytes each HLO touches, and the
buffer sizes it allocated — this module surfaces those numbers as
queryable gauges, per serving executable, so "are we near the roofline"
and "did the old index version's arrays actually get freed" stop being
profiler questions.

Three layers:

- :func:`analyze_compiled` — tolerant extraction from a ``jax`` AOT
  ``Compiled`` object.  ``cost_analysis()`` returns a list of dicts on
  some backends, a dict on others, and ``None`` (or raises) on the rest;
  ``memory_analysis()`` may lack a peak-memory field entirely (the CPU
  client derives nothing).  Whatever is absent stays absent — no gauge is
  ever published from a made-up number.
- :func:`analyze_callable` + :func:`record_cost` — AOT-compile a callable
  at given arg shapes, time one execution of the already-compiled
  executable, and publish ``raft_tpu_xla_*`` gauges with a roofline
  utilization estimate against the published peaks of the device
  (:data:`PEAKS`, keyed by ``device_kind``).
- :func:`refresh_live_buffer_gauges` — walks an
  :class:`~raft_tpu.serve.registry.IndexRegistry`'s weakly-referenced
  version history and publishes ``raft_tpu_index_live_bytes`` per
  (name, version) still alive on the host; versions the GC has collected
  get their series *removed*, so a stale series IS the leak report.

Everything here runs off the serving hot path (the live-buffer, page and
mutation gauges at scrape time), and every extraction is
exception-tolerant: a backend that cannot answer degrades to absent
gauges, not to a crash.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from raft_tpu.core.logger import child as _child_logger
from raft_tpu.obs.registry import MetricsRegistry, default_registry

_log = _child_logger("obs.cost")

#: (peak bf16 FLOP/s, peak HBM bytes/s) per chip, keyed by JAX's
#: ``device_kind``: the published figures, never a guess.  "TPU v5 lite" is
#: TPU v5e: 197 TFLOP/s bf16, 819 GB/s (Google Cloud documentation,
#: "TPU v5e").  A TPU missing here is an error; other platforms (the CPU
#: test backend) have no roofline.
PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(kind: Optional[str] = None) -> Optional[Tuple[float, float]]:
    """(peak_flops_per_s, peak_bytes_per_s) of the device kind (default: the
    first JAX device), or None off-TPU.  Raises ``KeyError`` for a TPU kind
    with no published peaks in :data:`PEAKS`."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind in PEAKS:
        return PEAKS[kind]
    if kind.startswith("TPU"):
        raise KeyError(
            f"no published peaks for device_kind {kind!r}; add them to "
            f"raft_tpu.obs.cost.PEAKS with their source"
        )
    return None


@dataclass
class CostReport:
    """Everything extractable from one compiled executable (None = the
    backend would not say)."""

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    peak_memory_bytes: Optional[float] = None
    argument_memory_bytes: Optional[float] = None
    output_memory_bytes: Optional[float] = None
    temp_memory_bytes: Optional[float] = None
    generated_code_bytes: Optional[float] = None
    seconds: Optional[float] = None          # one timed post-compile run
    utilization: Optional[float] = None      # achieved / roofline-attainable
    labels: Dict[str, str] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, object]:
        return {k: v for k, v in vars(self).items() if v is not None}


def _cost_props(compiled) -> Dict[str, float]:
    """Flatten cost_analysis() across its per-backend shapes."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if ca is None:
        return {}
    if isinstance(ca, dict):
        ca = [ca]
    out: Dict[str, float] = {}
    try:
        for entry in ca:
            for key, val in dict(entry).items():
                if isinstance(val, (int, float)):
                    out[key] = out.get(key, 0.0) + float(val)
    except Exception:
        return {}
    return out


def analyze_compiled(compiled) -> CostReport:
    """Extract a :class:`CostReport` from a jax AOT ``Compiled`` object.

    Never raises: fields the backend cannot report stay ``None``.
    """
    rep = CostReport()
    props = _cost_props(compiled)
    if "flops" in props:
        rep.flops = props["flops"]
    if "bytes accessed" in props:
        rep.bytes_accessed = props["bytes accessed"]
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        def _grab(*names):
            for n in names:
                v = getattr(mem, n, None)
                if isinstance(v, (int, float)) and v >= 0:
                    return float(v)
            return None

        rep.argument_memory_bytes = _grab("argument_size_in_bytes")
        rep.output_memory_bytes = _grab("output_size_in_bytes")
        rep.temp_memory_bytes = _grab("temp_size_in_bytes")
        rep.generated_code_bytes = _grab("generated_code_size_in_bytes")
        # TPU clients report peak directly; the CPU client doesn't — the
        # arg+output+temp sum is the working-set lower bound XLA admits to
        rep.peak_memory_bytes = _grab("peak_memory_in_bytes")
        if rep.peak_memory_bytes is None:
            parts = [
                p for p in (
                    rep.argument_memory_bytes,
                    rep.output_memory_bytes,
                    rep.temp_memory_bytes,
                )
                if p is not None
            ]
            if parts:
                rep.peak_memory_bytes = float(sum(parts))
    return rep


def roofline_utilization(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    seconds: Optional[float],
    kind: Optional[str] = None,
) -> Optional[float]:
    """Achieved FLOP/s as a fraction of the roofline-attainable rate.

    Attainable = ``min(peak_flops, intensity * peak_bw)`` — the classic
    roofline ceiling at the program's arithmetic intensity.  1.0 means
    the executable runs as fast as this hardware can run *this* program;
    low values point at launch overhead or a mis-scheduled kernel rather
    than "needs a bigger chip".  None when any input is unknown, or the
    device (``kind``, default the first JAX device) has no peaks.
    """
    if not flops or not seconds or seconds <= 0:
        return None
    peaks = device_peaks(kind)
    if peaks is None:
        return None
    peak_flops, peak_bw = peaks
    attainable = peak_flops
    if bytes_accessed and bytes_accessed > 0:
        attainable = min(peak_flops, (flops / bytes_accessed) * peak_bw)
    if attainable <= 0:
        return None
    return float((flops / seconds) / attainable)


def analyze_callable(fn, *args, time_run: bool = True) -> Optional[CostReport]:
    """AOT-compile ``fn`` at ``args``'s shapes and report its cost.

    With ``time_run`` the *compiled* executable is executed once and
    timed, yielding the roofline utilization estimate.  Returns ``None``
    when lowering/compilation itself fails (e.g. a backend without AOT
    support) — callers treat that as "no gauges", not an error.

    Note the compile here is a real XLA compile: call it off the serving
    path, never per request.
    """
    import jax

    from raft_tpu.ops import cost as ops_cost

    flat = jax.tree_util.tree_leaves(args)
    try:
        with ops_cost.capture() as notes:
            # lower with the arrays ``fn`` closes over (a served index's
            # whole payload) as parameters: jit of the closure would embed
            # them as program constants — a GB-sized program per bucket at
            # deployment sizes, host memory and minutes of compile each
            closed = jax.make_jaxpr(fn)(*args)

            def program(consts, *xs):
                return jax.core.eval_jaxpr(closed.jaxpr, consts, *xs)

            compiled = jax.jit(program).lower(closed.consts, *flat).compile()
    except Exception as exc:
        _log.debug("cost analysis unavailable: %r", exc)
        return None
    rep = analyze_compiled(compiled)
    # Mosaic custom-calls are opaque to XLA's cost model on TPU, so a
    # kernel-dominated executable can report no flops/bytes at all.  The
    # Pallas wrappers note their analytic CostEstimates at trace time;
    # use their total ONLY where XLA reported nothing (in interpret mode
    # XLA sees the lowered kernel body — supplementing there would
    # double count).
    noted = ops_cost.noted_total(notes)
    if noted is not None:
        if rep.flops is None and noted.flops:
            rep.flops = float(noted.flops)
        if rep.bytes_accessed is None and noted.bytes_accessed:
            rep.bytes_accessed = float(noted.bytes_accessed)
    if time_run:
        try:
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(closed.consts, *flat))
            rep.seconds = time.perf_counter() - t0
        except Exception:
            rep.seconds = None
    rep.utilization = roofline_utilization(
        rep.flops, rep.bytes_accessed, rep.seconds
    )
    return rep


#: gauge name → CostReport attribute published by record_cost
_GAUGES = (
    ("raft_tpu_xla_flops", "flops",
     "FLOPs per execution of a compiled serving executable"),
    ("raft_tpu_xla_bytes_accessed", "bytes_accessed",
     "bytes each execution moves (XLA cost model)"),
    ("raft_tpu_peak_memory_bytes", "peak_memory_bytes",
     "peak (or derived arg+out+temp) device memory of one executable"),
    ("raft_tpu_xla_argument_memory_bytes", "argument_memory_bytes",
     "argument buffer bytes of one executable"),
    ("raft_tpu_xla_output_memory_bytes", "output_memory_bytes",
     "output buffer bytes of one executable"),
    ("raft_tpu_xla_roofline_utilization", "utilization",
     "achieved FLOP/s over the roofline-attainable rate (0..1)"),
)


def record_cost(
    report: Optional[CostReport],
    registry: Optional[MetricsRegistry] = None,
    **labels: str,
) -> None:
    """Publish a report's known fields as gauges; absent fields publish
    nothing (the acceptance contract for backends that return None)."""
    if report is None:
        return
    reg = registry if registry is not None else default_registry()
    report.labels = {str(k): str(v) for k, v in labels.items()}
    for gauge_name, attr, help_ in _GAUGES:
        val = getattr(report, attr)
        if val is not None:
            reg.gauge(gauge_name, help=help_).set(float(val), **labels)


# ---------------------------------------------------------------------------
# live-buffer accounting per IndexRegistry version

def refresh_live_buffer_gauges(
    index_registry, registry: Optional[MetricsRegistry] = None,
) -> Dict[str, float]:
    """Publish ``raft_tpu_index_live_bytes{index=,version=}`` for every
    index version still alive on the host.

    The serve :class:`~raft_tpu.serve.registry.IndexRegistry` keeps a
    weak reference to every version it has ever held; a hot-swapped-out
    version whose arrays are still reachable (an in-flight batch, a
    caller's stray reference, a leak) keeps its gauge — a version the GC
    collected gets its series removed.  The dashboard view is therefore
    exact: two live series under one name during a swap is normal for
    seconds, and a pathological leak is an old version's series that
    never disappears.

    Pipelined dispatch interacts here by design: at ``pipeline_depth``
    > 1 up to that many batches can each pin the version they resolved,
    so a swapped-out version legitimately stays live for up to
    ``pipeline_depth`` batch completions (bounded by the in-flight
    semaphore) rather than one.  The gauges stay truthful because they
    report reachability, not intent — the leak signal is a series that
    outlives the window, not one that exists during it.
    """
    reg = registry if registry is not None else default_registry()
    gauge = reg.gauge(
        "raft_tpu_index_live_bytes",
        help="host+device bytes held by each still-reachable index version",
    )
    live: Dict[str, float] = {}
    alive_keys = set()
    for (name, version), index in index_registry.live_versions().items():
        if getattr(getattr(index, "index", None), "paged", None) is not None:
            # paged versions report through the page-residency gauges
            # (refresh_page_gauges) — a monolithic live-bytes series for
            # them would double-count the aliased cold tier; any series a
            # version published before pagination retires below
            continue
        try:
            nbytes = float(index.device_bytes())
        except Exception:
            continue
        labels = {"index": name, "version": str(version)}
        gauge.set(nbytes, **labels)
        alive_keys.add((name, str(version)))
        live[f"{name}:v{version}"] = nbytes
    # retire series whose version object is gone
    for key in gauge.series():
        d = dict(key)
        if "index" in d and "version" in d:
            if (d["index"], d["version"]) not in alive_keys:
                gauge.remove(**d)
    return live


def refresh_page_gauges(
    index_registry, registry: Optional[MetricsRegistry] = None,
) -> Dict[str, Dict[str, float]]:
    """Publish page-residency gauges for every still-reachable *paged*
    index version: ``raft_tpu_page_resident{index=,version=}`` (pages in
    the HBM hot pool), ``raft_tpu_page_host`` (cold pages on host only),
    and ``raft_tpu_page_pool_bytes`` (device bytes the hot pool + page
    table reserve from the memory budget).

    Rides the same weak version history as
    :func:`refresh_live_buffer_gauges` and retires series whose version
    object the GC collected — the fetch/eviction *flow* counters
    (``raft_tpu_page_{hits,misses,evictions}_total``) are push-side,
    bumped by :class:`~raft_tpu.store.tiered.TieredStore` itself.
    """
    reg = registry if registry is not None else default_registry()
    g_res = reg.gauge(
        "raft_tpu_page_resident",
        help="HBM-resident pages of each still-reachable paged index version",
    )
    g_host = reg.gauge(
        "raft_tpu_page_host",
        help="host-only (cold) pages of each still-reachable paged index version",
    )
    g_bytes = reg.gauge(
        "raft_tpu_page_pool_bytes",
        help="device bytes reserved by each paged version's hot pool",
    )
    out: Dict[str, Dict[str, float]] = {}
    alive = set()
    for (name, version), index in index_registry.live_versions().items():
        tiered = getattr(getattr(index, "index", None), "paged", None)
        if tiered is None:
            continue
        try:
            st = tiered.stats()
            pool_bytes = float(tiered.nbytes)
        except Exception:
            continue
        labels = {"index": name, "version": str(version)}
        g_res.set(float(st["resident"]), **labels)
        g_host.set(float(st["host_only"]), **labels)
        g_bytes.set(pool_bytes, **labels)
        alive.add((name, str(version)))
        out[f"{name}:v{version}"] = {
            "resident": float(st["resident"]),
            "host": float(st["host_only"]),
            "pool_bytes": pool_bytes,
        }
    for gauge in (g_res, g_host, g_bytes):
        for key in gauge.series():
            d = dict(key)
            if "index" in d and "version" in d:
                if (d["index"], d["version"]) not in alive:
                    gauge.remove(**d)
    return out


def refresh_mutation_gauges(
    index_registry, registry: Optional[MetricsRegistry] = None,
) -> Dict[str, Dict[str, float]]:
    """Publish per-index mutation-pressure gauges from the registry's
    *current* entries: ``raft_tpu_index_pending_deletes``,
    ``raft_tpu_index_side_rows``, and ``raft_tpu_index_tombstone_frac``
    (tombstones over main rows, construction padding excluded).

    These are the compaction trigger inputs — the same numbers
    :class:`~raft_tpu.serve.compactor.Compactor` compares against its
    policy — so compaction pressure is visible in ``prometheus()``
    output, not only via method calls.  Entries that are not
    :class:`~raft_tpu.serve.mutation.MutableIndex` (sharded indexes,
    raw wrappers without a side buffer) are skipped; series for names
    no longer registered are removed, mirroring
    :func:`refresh_live_buffer_gauges`.
    """
    reg = registry if registry is not None else default_registry()
    g_del = reg.gauge(
        "raft_tpu_index_pending_deletes",
        help="tombstoned rows awaiting compaction (padding excluded)",
    )
    g_side = reg.gauge(
        "raft_tpu_index_side_rows",
        help="live upsert rows in the brute-force side buffer",
    )
    g_frac = reg.gauge(
        "raft_tpu_index_tombstone_frac",
        help="pending deletes over main structure rows",
    )
    out: Dict[str, Dict[str, float]] = {}
    alive = set()
    for name in index_registry.names():
        try:
            index = index_registry.get(name)
            deletes, side = index.pending_mutations()
            denom = max(
                index.main_size - getattr(index, "_n_structural", 0), 1
            )
        except (KeyError, AttributeError):
            continue
        except Exception:
            continue
        frac = float(deletes) / float(denom)
        g_del.set(deletes, index=name)
        g_side.set(side, index=name)
        g_frac.set(frac, index=name)
        alive.add(name)
        out[name] = {
            "pending_deletes": float(deletes),
            "side_rows": float(side),
            "tombstone_frac": frac,
        }
    for gauge in (g_del, g_side, g_frac):
        for key in gauge.series():
            d = dict(key)
            if d.get("index") not in alive:
                gauge.remove(**d)
    return out
