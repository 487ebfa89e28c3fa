"""SearchService: the assembled online query-serving front end.

One object wires the serve stack together: an
:class:`~raft_tpu.serve.registry.IndexRegistry` of named
:class:`~raft_tpu.serve.mutation.MutableIndex` es, one
:class:`~raft_tpu.serve.batcher.MicroBatcher` per served name (each with
its own bucket ladder + :class:`~raft_tpu.serve.metrics.ServingMetrics`),
and optionally a :class:`~raft_tpu.serve.replica.ReplicaGroup` for
query-sharded multi-chip dispatch.

The atomicity contract lives here: a batcher's ``search_fn`` resolves the
registry **once per dispatched batch**, so every row of a coalesced batch
is answered by exactly one index version — :meth:`swap` never tears a
batch, and in-flight batches pin the old version by reference until they
complete.  Swapping a same-shaped index costs zero recompiles (compiled
executables key on shapes, not weights); ``tests/test_serve.py`` pins
both properties.

Typical lifecycle::

    svc = SearchService(k=10)
    svc.add_index("wiki", MutableIndex(built), warmup=True)
    dists, ids = svc.search("wiki", query_vec)     # sync
    fut = svc.submit("wiki", query_vec)            # async, coalesced
    svc.get("wiki").upsert(new_rows)               # visible immediately
    svc.swap("wiki", MutableIndex(rebuilt))        # atomic hot-swap
    svc.stats("wiki")                              # qps/p50/p99/recompiles
    svc.stop()
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Union

import numpy as np

from raft_tpu import obs
from raft_tpu.core import env as _env
from raft_tpu.core.trace import gc_stats, install_gc_hook, traced
from raft_tpu.obs import autotune as obs_autotune
from raft_tpu.obs import cost as obs_cost
from raft_tpu.obs import explain as obs_explain
from raft_tpu.obs import gateway as obs_gateway
from raft_tpu.obs import health as obs_health
from raft_tpu.obs import incidents as obs_incidents
from raft_tpu.obs import perf as obs_perf
from raft_tpu.obs import slo as obs_slo
from raft_tpu.obs import spans as obs_spans
from raft_tpu.obs.quality import QualityAuditor
from raft_tpu.serve.batcher import MicroBatcher
from raft_tpu.serve.compactor import CompactionPolicy, Compactor
from raft_tpu.serve.effort import EffortArbiter
from raft_tpu.serve.metrics import ServingMetrics, install_compile_listener
from raft_tpu.serve.mutation import MutableIndex
from raft_tpu.serve.overload import (
    AdmissionController,
    DeadlineExceeded,
    DegradedModeManager,
    HedgedDispatcher,
    OverloadConfig,
    Shed,
)
from raft_tpu.serve.ragged import FilterRegistry, RaggedSearcher, RaggedSpec
from raft_tpu.serve.registry import IndexRegistry
from raft_tpu.serve.replica import ReplicaGroup
from raft_tpu.serve.shard import ShardedIndex


class _AuditorTap:
    """Late-bound recall tap for the autotuner: reads the service's
    *current* auditor per call, so :meth:`SearchService.attach_auditor`
    takes effect on already-watched indexes."""

    def __init__(self, service: "SearchService"):
        self._service = service

    def recall_ewma(self, name: str) -> Optional[float]:
        auditor = self._service.auditor
        return auditor.recall_ewma(name) if auditor is not None else None


class SearchService:
    """Serve named mutable indexes through per-index micro-batchers."""

    def __init__(
        self,
        registry: Optional[IndexRegistry] = None,
        *,
        k: int = 10,
        min_bucket: int = 1,
        max_batch: int = 64,
        max_delay_ms: float = 2.0,
        replicas: Optional[ReplicaGroup] = None,
        start: bool = True,
        auditor: Optional[QualityAuditor] = None,
        cost_accounting: Optional[bool] = None,
        pipeline_depth: Optional[int] = None,
        compaction: Union[None, bool, CompactionPolicy, Compactor] = None,
        slo: Union[
            None, bool, Sequence[obs_slo.SloSpec], obs_slo.SloEngine
        ] = None,
        ragged: Union[None, bool, RaggedSpec] = None,
        overload: Union[None, bool, OverloadConfig] = None,
        autotune: Union[None, bool, obs_autotune.Autotuner] = None,
        gateway: Union[
            None, bool, obs_gateway.GatewayConfig,
            obs_gateway.OperationalGateway,
        ] = None,
    ):
        install_compile_listener()
        install_gc_hook()
        # full pipeline: XLA event attribution + span/slowlog snapshot
        # sections — the service is the component that promises "where did
        # the milliseconds go" has an answer
        obs.install()
        self.registry = registry if registry is not None else IndexRegistry()
        self.k = int(k)
        self.min_bucket = min_bucket
        self.max_batch = max_batch
        self.max_delay_ms = max_delay_ms
        self.replicas = replicas
        self.auditor = auditor
        self.cost_accounting = cost_accounting
        # None defers to the batcher's RAFT_TPU_PIPELINE_DEPTH / default;
        # 1 forces the serial dispatch path for every served index
        self.pipeline_depth = pipeline_depth
        # ragged=None: RAFT_TPU_RAGGED decides.  True: spec from env.
        # A RaggedSpec is used as-is.  When set, every added index serves
        # through one packed heterogeneous dispatch per capacity bucket —
        # per-request k (<= spec.k_max) and registered filter ids ride as
        # descriptor data, not shapes (see raft_tpu.serve.ragged).
        if ragged is None:
            ragged = _env.env_bool("RAFT_TPU_RAGGED", False)
        if ragged is True:
            ragged = RaggedSpec.from_env()
        elif ragged is False:
            ragged = None
        self.ragged: Optional[RaggedSpec] = ragged
        if self.ragged is not None and replicas is not None:
            raise NotImplementedError(
                "ragged mode and replica dispatch are mutually exclusive: "
                "the replica path has no descriptor-column leg yet"
            )
        self._filter_regs: Dict[str, Optional[FilterRegistry]] = {}
        # overload=None: RAFT_TPU_OVERLOAD decides.  True: config from
        # env.  An OverloadConfig is used as-is.  When set, every added
        # index gets an AdmissionController (priority shedding + deadline
        # expiry at batch cut, driven by queue pressure and slo_burn
        # events) and a DegradedModeManager (hysteretic search-effort
        # ladder; local dispatch only — the replica path has no params
        # leg).  Hedged priority-0 dispatch additionally needs replicas
        # and config.hedge.  Deadline-only expiry runs even without this.
        if overload is None:
            overload = _env.env_bool("RAFT_TPU_OVERLOAD", False)
        if overload is True:
            overload = OverloadConfig.from_env()
        elif overload is False:
            overload = None
        self.overload: Optional[OverloadConfig] = overload
        self._admission: Dict[str, AdmissionController] = {}
        self._degraded: Dict[str, DegradedModeManager] = {}
        self._hedgers: Dict[str, HedgedDispatcher] = {}
        # autotune=None: RAFT_TPU_AUTOTUNE decides.  True: controller
        # from env (frontier via RAFT_TPU_FRONTIER_PATH).  A prebuilt
        # Autotuner is adopted as-is (caller owns its start state).
        # Every added index gets an EffortArbiter — the single writer of
        # effective search effort: the autotuner steps its level, the
        # overload ladder clamps it, and the dispatch reads exactly one
        # arbitrated SearchParams (local dispatch only — the replica
        # path has no params leg).
        self.autotuner: Optional[obs_autotune.Autotuner] = None
        if isinstance(autotune, obs_autotune.Autotuner):
            self.autotuner = autotune
        else:
            if autotune is None:
                autotune = _env.env_bool("RAFT_TPU_AUTOTUNE", False)
            if autotune:
                self.autotuner = obs_autotune.Autotuner()
                if start:
                    self.autotuner.start()
        self._effort: Dict[str, EffortArbiter] = {}
        self._start = start
        self._lock = threading.Lock()
        self._batchers: Dict[str, MicroBatcher] = {}
        self._ks: Dict[str, int] = {}  # effective k per served name
        # compaction=None/False: no worker.  True: policy from env.  A
        # CompactionPolicy: worker with that policy.  A prebuilt Compactor
        # is adopted as-is (its own start state respected).
        self.compactor: Optional[Compactor] = None
        if isinstance(compaction, Compactor):
            self.compactor = compaction
        elif isinstance(compaction, CompactionPolicy):
            self.compactor = Compactor(self, compaction, start=start)
        elif compaction:
            self.compactor = Compactor(
                self,
                start=start and not CompactionPolicy.disabled_by_env(),
            )
        # slo=None/False: no engine.  True: default objectives added per
        # served index (watch_index on add_index).  A sequence of SloSpec:
        # engine with exactly those objectives.  A prebuilt SloEngine is
        # adopted as-is (caller owns its start state).
        self.slo_engine: Optional[obs_slo.SloEngine] = None
        self._slo_auto = False  # add default specs on add_index?
        if isinstance(slo, obs_slo.SloEngine):
            self.slo_engine = slo
        elif slo is True:
            self.slo_engine = obs_slo.SloEngine(service=self)
            self._slo_auto = True
            if start:
                self.slo_engine.start()
        elif slo:
            self.slo_engine = obs_slo.SloEngine(tuple(slo), service=self)
            if start:
                self.slo_engine.start()
        # incident timelines carry a service snapshot at open/close —
        # registry versions and queue depths, the facts an operator wants
        # next to "what fired"
        obs_incidents.default_manager().add_context_source(
            "service", self._incident_context
        )
        # gateway=None: RAFT_TPU_GATEWAY decides.  True: bind config from
        # env.  A GatewayConfig binds a fresh server; a prebuilt
        # OperationalGateway is adopted as-is (and pointed at this
        # service if it has none).  The gateway only calls the pull APIs
        # above — owning it here is lifecycle, not coupling.
        self.gateway: Optional[obs_gateway.OperationalGateway] = None
        if isinstance(gateway, obs_gateway.OperationalGateway):
            self.gateway = gateway
            if self.gateway.service is None:
                self.gateway.service = self
        elif isinstance(gateway, obs_gateway.GatewayConfig):
            self.gateway = obs_gateway.OperationalGateway(
                self, config=gateway
            )
        else:
            if gateway is None:
                gateway = _env.env_bool("RAFT_TPU_GATEWAY", False)
            if gateway:
                self.gateway = obs_gateway.OperationalGateway(self)
        if self.gateway is not None and start:
            self.gateway.start()

    # -- index management ----------------------------------------------------
    def add_index(
        self, name: str, index, *, warmup: bool = False, k: Optional[int] = None
    ) -> int:
        """Register ``index`` under ``name`` and start its batcher.

        ``index`` may be a raw built index (wrapped automatically), a
        :class:`MutableIndex`, or a
        :class:`~raft_tpu.serve.shard.ShardedIndex` (served as-is — the
        cross-shard dispatch is baked into its ``search``).  With
        ``warmup`` the whole bucket ladder is compiled before the method
        returns, so the first real query is already on the hot path.
        """
        if not isinstance(index, (MutableIndex, ShardedIndex)):
            index = MutableIndex(index)
        if (
            _env.env_bool("RAFT_TPU_PAGED", False)
            and isinstance(index, MutableIndex)
            and getattr(index.index, "paged", None) is None
        ):
            # opt-in paged serving: move the main payload behind the
            # budget-enforced page store (BudgetExceeded propagates — a
            # misconfigured budget should fail registration loudly, not
            # serve unpaged silently); structurally unpageable indexes
            # (VPQ datasets, unknown kinds) keep the monolithic layout
            from raft_tpu.store import paginate_index

            try:
                paginate_index(index.index, name=name)
            except ValueError:
                pass
        k = self.k if k is None else int(k)
        if self.ragged is not None and k > self.ragged.k_max:
            raise ValueError(
                f"default k={k} exceeds the ragged spec's k_max="
                f"{self.ragged.k_max}"
            )
        version = self.registry.register(name, index)
        admission = degraded = hedger = effort = None
        if self.overload is not None:
            admission = AdmissionController(self.overload, name=name)
            if self.replicas is None:
                # degraded-mode search threads reduced-effort params into
                # the local dispatch; the replica path has no params leg
                degraded = DegradedModeManager(self.overload, name=name)
            if self.overload.hedge and self.replicas is not None:
                hedger = HedgedDispatcher(
                    self.replicas.member_searchers(name, k),
                    self.overload, name=name,
                )
        if self.replicas is None and (
            degraded is not None or self.autotuner is not None
        ):
            # the single effort-arbitration point: the dispatch reads
            # effective params from here (degraded shed level = clamp,
            # autotuner = writer); plain services skip it entirely
            effort = EffortArbiter(degraded, name=name)
        with self._lock:
            self._ks[name] = k
            old = self._batchers.pop(name, None)
            old_admission = self._admission.pop(name, None)
            self._degraded.pop(name, None)
            self._hedgers.pop(name, None)
            self._effort.pop(name, None)
            if admission is not None:
                self._admission[name] = admission
            if degraded is not None:
                self._degraded[name] = degraded
            if hedger is not None:
                self._hedgers[name] = hedger
            if effort is not None:
                self._effort[name] = effort
            if self.ragged is not None:
                freg = None
                if self.ragged.filters and isinstance(index, MutableIndex):
                    # filter id space: the main index's global ids.  Side
                    # rows upserted later get ids past this range and pass
                    # every filter (uncovered = unconstrained).
                    freg = FilterRegistry(max(1, index.main_size))
                elif self.ragged.filters and isinstance(index, ShardedIndex):
                    # sharded layouts carry dense global row ids; the
                    # packed predicate table replicates to every shard and
                    # ShardedIndex.search rebases it per shard
                    freg = FilterRegistry(max(1, index.size))
                self._filter_regs[name] = freg
                search_fn = RaggedSearcher(
                    self, name, self.ragged, freg, degraded=degraded,
                    effort=effort,
                )
            else:
                search_fn = self._make_search_fn(name, k)
            batcher = MicroBatcher(
                search_fn,
                index.dim,
                min_bucket=self.min_bucket,
                max_batch=self.max_batch,
                max_delay_ms=self.max_delay_ms,
                metrics=ServingMetrics(name=name),
                start=self._start,
                observer=self._make_observer(name),
                cost_accounting=self.cost_accounting,
                pipeline_depth=self.pipeline_depth,
                ragged=self.ragged,
                admission=admission,
                degraded=degraded,
                hedger=hedger,
                effort=effort,
                perf_meta=self._make_perf_meta(name),
            )
            self._batchers[name] = batcher
        if old is not None:
            old.stop()
        if old_admission is not None:
            old_admission.close()
        if self.slo_engine is not None and self._slo_auto and old is None:
            self.slo_engine.watch_index(name)
        if self.autotuner is not None and effort is not None:
            self.autotuner.watch_index(
                name, effort, index=index,
                auditor=_AuditorTap(self),
                slo=self.slo_engine,
                perf=obs_perf.default_ledger(),
            )
        if warmup:
            batcher.warmup()
        return version

    def effort_arbiter(self, name: str) -> Optional[EffortArbiter]:
        """The index's effort-arbitration point (None: plain service with
        neither overload degraded mode nor an autotuner)."""
        with self._lock:
            return self._effort.get(name)

    def _make_search_fn(self, name: str, k: int):
        def search_fn(queries):
            # resolve once per BATCH: the whole padded batch is answered
            # by one index version (hot-swap atomicity boundary)
            index, _version = self.registry.get_versioned(name)
            if self.replicas is not None:
                return self.replicas.search(name, queries, k)
            arb = self._effort.get(name)
            if arb is not None and isinstance(index, MutableIndex):
                params = arb.apply(index)
                if params is not None:
                    # arbitrated reduced-effort params (autotuner level
                    # clamped by the overload ladder); warmed per level
                    # by the batcher's level-pinned warmup
                    return index.search(queries, k, search_params=params)
            return index.search(queries, k)

        return search_fn

    def _make_perf_meta(self, name: str):
        """``(backend, version)`` supplier for the perf ledger's
        executable key.  Resolved per dispatch, so a hot-swap
        re-attributes device time to the successor kind/version from its
        first batch — the ledger's A/B story survives swaps."""

        def perf_meta():
            try:
                index, version = self.registry.get_versioned(name)
            except KeyError:  # removed mid-flight
                return ("unknown", "0")
            return (getattr(index, "kind", "unknown") or "unknown",
                    str(version))

        return perf_meta

    def _make_observer(self, name: str):
        """Batcher observer feeding the quality auditor, if any.

        Reads ``self.auditor`` per call so :meth:`attach_auditor` takes
        effect on already-running batchers.  The (index, version) pair is
        resolved here, right after the dispatch — a swap racing between
        the dispatch and the observation can attribute one audited batch
        to the successor version, which the auditor's per-version EWMA
        reset absorbs.
        """

        def observer(queries, dists, ids):
            auditor = self.auditor
            if auditor is None:
                return
            index, version = self.registry.get_versioned(name)
            auditor.observe(name, version, index, queries, ids)

        return observer

    def attach_auditor(self, auditor: Optional[QualityAuditor]) -> None:
        """Install (or remove, with ``None``) the online recall auditor.

        Existing batchers pick it up immediately — their observer closures
        read ``self.auditor`` at call time.
        """
        self.auditor = auditor

    @traced("serve.swap")
    def swap(self, name: str, index) -> int:
        """Atomically replace the index behind ``name`` (see module doc).

        The existing batcher (and its warmed executables) is kept: a
        same-shaped replacement serves its next batch with no recompile.
        A :class:`~raft_tpu.serve.shard.ShardedIndex` swaps in unwrapped —
        replicated → sharded layout changes are atomic the same way.
        """
        if not isinstance(index, (MutableIndex, ShardedIndex)):
            index = MutableIndex(index)
        with self._lock:
            if name not in self._batchers:
                raise KeyError(f"no served index named {name!r}")
            if index.dim != self._batchers[name].dim:
                raise ValueError(
                    f"swap dim mismatch for {name!r}: "
                    f"{index.dim} != {self._batchers[name].dim}"
                )
        return self.registry.swap(name, index)

    def get(self, name: str) -> MutableIndex:
        """The live index (for upsert/delete — visible to the next batch)."""
        return self.registry.get(name)

    def register_filter(self, name: str, mask) -> int:
        """Register a sample filter for ragged serving; returns its fid.

        ``mask`` is a bool array (or :class:`~raft_tpu.core.bitset.Bitset`)
        over ``name``'s global id space; requests pass the returned fid to
        :meth:`submit`/:meth:`search`.  Register before :meth:`warmup` —
        the table gather is host-side so registration never changes an XLA
        trace, but cagra's pinned search width and the fused Pallas leg
        key on filter-derived host values and would spend one compile per
        bucket on the next dispatch (reported as ``hot_recompile``).
        """
        if self.ragged is None:
            raise RuntimeError(
                "register_filter needs SearchService(ragged=...)"
            )
        with self._lock:
            freg = self._filter_regs.get(name)
        if freg is None:
            raise RuntimeError(
                f"no filter registry for {name!r}: the index kind is not "
                "filterable or the spec has filters=False"
            )
        return freg.register(mask)

    def remove_index(self, name: str) -> None:
        with self._lock:
            batcher = self._batchers.pop(name)
            self._ks.pop(name, None)
            self._filter_regs.pop(name, None)
            admission = self._admission.pop(name, None)
            self._degraded.pop(name, None)
            self._hedgers.pop(name, None)
            self._effort.pop(name, None)
        batcher.stop()
        if admission is not None:
            admission.close()
        self.registry.unregister(name)
        if self.slo_engine is not None and self._slo_auto:
            self.slo_engine.unwatch_index(name)
        if self.autotuner is not None:
            self.autotuner.unwatch_index(name)
        # retire the index's archived plans + explain metric series (the
        # same stale-series hygiene the SLO/autotune unwatch paths follow)
        obs_explain.default_archive().unwatch_index(name)

    def names(self):
        return self.registry.names()

    # -- querying ------------------------------------------------------------
    def _batcher(self, name: str) -> MicroBatcher:
        with self._lock:
            return self._batchers[name]

    def _ragged_args(self, name: str, k: Optional[int], fid: Optional[int]):
        """Validate and default the per-request ragged descriptor."""
        if self.ragged is None:
            if k is not None or fid is not None:
                raise ValueError(
                    "per-request k/fid need SearchService(ragged=...)"
                )
            return None, None
        if k is None:
            with self._lock:
                k = self._ks[name]
        if fid is not None and fid != 0:
            with self._lock:
                freg = self._filter_regs.get(name)
            if freg is None or not freg.contains(fid):
                raise ValueError(
                    f"fid {fid} is not registered for {name!r} "
                    "(register_filter returns valid fids)"
                )
        return k, fid

    def submit(self, name: str, queries, *, k: Optional[int] = None,
               fid: Optional[int] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None):
        """Async search; returns a Future of (distances, ids).

        Ragged mode only: ``k`` (defaults to the index's configured k,
        ceiling ``spec.k_max``) and ``fid`` (a :meth:`register_filter`
        handle; 0/None = unfiltered) shape THIS request inside the packed
        batch — heterogeneous mixes coalesce into one dispatch.

        Any mode: ``priority`` (0=interactive … 3=background, default 1)
        and ``deadline_s`` (server-side budget from now) ride as request
        metadata — under overload the admission controller sheds the
        lowest priorities first and expired requests never reach the
        device; their futures resolve with the typed
        :class:`~raft_tpu.serve.overload.Shed` /
        :class:`~raft_tpu.serve.overload.DeadlineExceeded` errors.
        """
        k, fid = self._ragged_args(name, k, fid)
        return self._batcher(name).submit(
            queries, k=k, fid=fid, priority=priority, deadline_s=deadline_s
        )

    @traced("serve.search")
    def search(self, name: str, queries, timeout: Optional[float] = None,
               *, k: Optional[int] = None, fid: Optional[int] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None):
        """Sync search through the batcher (coalesces with live traffic).

        ``timeout`` doubles as the server-side deadline when
        ``deadline_s`` is not given — a request its caller has stopped
        waiting for is dropped at the next batch cut instead of running
        on device."""
        k, fid = self._ragged_args(name, k, fid)
        return self._batcher(name).search(
            queries, timeout=timeout, k=k, fid=fid,
            priority=priority, deadline_s=deadline_s,
        )

    @traced("serve.explain")
    def explain(self, name: str, queries, *, k: Optional[int] = None,
                fid: Optional[int] = None, priority: Optional[int] = None,
                deadline_s: Optional[float] = None,
                timeout: Optional[float] = None) -> obs_explain.ExplainPlan:
        """EXPLAIN ANALYZE one real request; returns its
        :class:`~raft_tpu.obs.explain.ExplainPlan`.

        The request runs through the **normal** batched path — it
        coalesces with live traffic and is answered by the same
        executables, so the plan describes production behaviour, not a
        simulation.  The plan joins the enriched flight-recorder batch
        record (admission pressure, arbitrated effort level and its
        source, capacity bucket, kernel path, page-cache interaction,
        stage timeline) with a few deep-only host-side probes taken
        *after* the dispatch completes: a coarse-probe replay for the
        IVF kinds, per-shard contribution counts for a
        :class:`~raft_tpu.serve.shard.ShardedIndex`, and the recall
        auditor's verdict.  Works without ``RAFT_TPU_EXPLAIN`` — the
        gate is forced open for this request only — but needs the
        observability pipeline on.  A shed or deadline-expired request
        still yields a plan (its admission section says why it never
        reached the device).
        """
        if not obs_spans.enabled():
            raise RuntimeError(
                "SearchService.explain needs the observability pipeline "
                "on (RAFT_TPU_OBS=1 or obs.enable())"
            )
        k, fid = self._ragged_args(name, k, fid)
        batcher = self._batcher(name)
        archive = obs_explain.default_archive()
        outcome, error, result = "ok", None, None
        with obs_explain.deep_scope():
            fut = batcher.submit(
                queries, k=k, fid=fid, priority=priority,
                deadline_s=deadline_s,
            )
            req_id = fut.request_id
            archive.watch(req_id)
            try:
                try:
                    result = fut.result(timeout)
                except Shed as exc:
                    outcome, error = "shed", exc
                except DeadlineExceeded as exc:
                    outcome, error = "deadline_expired", exc
                except Exception as exc:  # noqa: BLE001 — reported in plan
                    outcome, error = "error", exc
                # the archive entry lands on the completion thread right
                # after the future resolves; poll briefly for it
                entry = archive.find(req_id)
                give_up = time.monotonic() + 2.0
                while entry is None and time.monotonic() < give_up:
                    time.sleep(0.001)
                    entry = archive.find(req_id)
            finally:
                archive.unwatch(req_id)
        if entry is None:
            # record never landed (obs raced off mid-flight): degrade to
            # a minimal plan — an operator entry point must not raise here
            sections: Dict[str, object] = {
                "request": {"id": req_id},
                "outcome": {"outcome": outcome, "error": None,
                            "sampled_reason": "deep"},
                "available": False,
            }
        else:
            sections = entry["plan"]
        if outcome != "ok":
            sections["outcome"] = {
                **(sections.get("outcome") or {}),
                "outcome": outcome,
                "error": repr(error),
            }
        self._explain_deep_sections(name, queries, sections, result)
        return obs_explain.ExplainPlan(sections)

    def _explain_deep_sections(self, name, queries, sections, result):
        """Append the deep-only plan sections: coarse-probe replay,
        shard contributions, audit verdict, result payload.  Host-side
        and off the hot path by construction — the dispatch already
        completed, so the host pulls here stall nothing."""
        try:
            index, version = self.registry.get_versioned(name)
        except KeyError:  # removed mid-explain
            return
        sections.setdefault("bucket", {})["version"] = version
        if isinstance(index, MutableIndex) and index.kind in (
            "ivf_flat", "ivf_pq"
        ):
            prev = sections.get("probe")
            try:
                info = self._probe_replay(name, index, queries)
            except Exception as exc:  # noqa: BLE001 — section degrades
                info = {"available": False, "error": repr(exc)}
            if isinstance(prev, dict) and prev.get("params"):
                info.setdefault("params", prev["params"])
            sections["probe"] = info
        from raft_tpu.serve.shard import ShardedIndex as _Sharded

        if isinstance(index, _Sharded) and result is not None:
            info = index.explain_contributions(np.asarray(result[1]))
            if getattr(index, "graph_mode", False):
                # graph-mode CAGRA: per-shard hop/halo accounting from an
                # exchange-free traversal replay of this query batch
                try:
                    info["traversal"] = index.explain_traversal(queries)
                except Exception as exc:  # noqa: BLE001 — section degrades
                    info["traversal"] = {
                        "available": False, "error": repr(exc)
                    }
            sections["shards"] = info
        auditor = self.auditor
        if auditor is not None:
            ewma = auditor.recall_ewma(name)
            threshold = auditor.threshold
            sections["audit"] = {
                "recall_ewma": ewma,
                "threshold": threshold,
                "verdict": (
                    "unaudited" if ewma is None
                    else "ok" if ewma >= threshold else "below_threshold"
                ),
            }
        else:
            sections["audit"] = {"available": False}
        if result is not None:
            dists, ids = result
            sections["results"] = {
                "ids": np.asarray(ids).tolist(),
                "distances": [
                    round(float(v), 6)
                    for v in np.asarray(dists, dtype=np.float64).reshape(-1)
                ],
            }

    def _probe_replay(self, name, index, queries):
        """Re-run the coarse pass host-side for one explained request:
        same math the search executable re-derives in-trace
        (deterministic — both agree), so the probed list ids and their
        candidate counts can be reported without adding outputs to the
        warmed executables (which would change shapes and recompile)."""
        from raft_tpu.neighbors._common import coarse_select

        base = index.index
        params = None
        with self._lock:
            arb = self._effort.get(name)
        if arb is not None:
            # the same arbitrated effort params the dispatch read
            params = arb.apply(index)
        if params is None:
            params = index.search_params
        centers = base.centers
        n_lists = int(centers.shape[0])
        n_probes = int(getattr(params, "n_probes", 0) or 0)
        n_probes = max(1, min(n_probes or n_lists, n_lists))
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        probes = np.asarray(
            coarse_select(q, centers, index.metric, n_probes)
        )
        sizes = np.asarray(base.list_sizes)
        probed = np.unique(probes.reshape(-1))
        total = float(sizes.sum())
        return {
            "n_probes": n_probes,
            "n_lists": n_lists,
            "probed_lists": [int(p) for p in probed],
            "candidates": int(sizes[probes.reshape(-1)].sum()),
            "coverage": round(
                float(sizes[probed].sum()) / total, 4
            ) if total > 0 else None,
        }

    @traced("serve.warmup")
    def warmup(self, name: Optional[str] = None) -> int:
        """Compile the bucket ladder(s); returns total compiles spent."""
        names = [name] if name is not None else self.names()
        return sum(self._batcher(n).warmup() for n in names)

    @traced("serve.flush")
    def flush(self, name: Optional[str] = None) -> int:
        """Dispatch everything queued for ``name`` (or all indexes).

        Routed through each batcher's pipeline: returns only after the
        flushed batches have resolved their futures, and a flush racing
        in-flight traffic cannot reorder result delivery."""
        names = [name] if name is not None else self.names()
        return sum(self._batcher(n).flush() for n in names)

    # -- compaction ----------------------------------------------------------
    def compact_now(self, name: str) -> Dict[str, object]:
        """Run one synchronous compaction pass for ``name``, bypassing the
        policy thresholds and any abort cooldown (operator escape hatch).
        Requires the service to own a compactor (``compaction=`` knob)."""
        if self.compactor is None:
            raise RuntimeError(
                "no compactor attached; construct the service with "
                "compaction=True (or a CompactionPolicy)"
            )
        return self.compactor.trigger_now(name)

    def pause_compaction(self) -> None:
        """Suspend automatic compaction triggering (a running pass
        finishes; :meth:`compact_now` still works)."""
        if self.compactor is not None:
            self.compactor.pause()

    def resume_compaction(self) -> None:
        if self.compactor is not None:
            self.compactor.resume()

    def drain_compaction(self, timeout: Optional[float] = None) -> bool:
        """Block until no compaction pass is in flight; True on success
        (vacuously so when no compactor is attached)."""
        if self.compactor is None:
            return True
        return self.compactor.drain(timeout=timeout)

    # -- observability -------------------------------------------------------
    def stats(self, name: str) -> Dict[str, object]:
        """Metrics snapshot + index version/size for one served name.

        Includes the per-stage latency breakdown under ``stages`` —
        queue-wait / pad / dispatch / device p50+p99 — so a p99 excursion
        decomposes without a profiler capture; the exact cumulative stage
        sums and counts under ``stage_sum_s`` / ``stage_n``; the
        process's garbage-collection counts and pause seconds under
        ``host`` (:func:`raft_tpu.core.trace.gc_stats`); and, for a
        :class:`~raft_tpu.serve.mutation.MutableIndex`, the resident bytes
        of zero lanes under ``lane_pad_bytes`` (its ``lane_pad_bytes()``).  The
        cumulative fields difference over a window: ``stats1 - stats0``.
        """
        index, version = self.registry.get_versioned(name)
        out = self._batcher(name).metrics.snapshot()
        deleted, side = index.pending_mutations()
        out.update(
            name=name,
            version=version,
            kind=index.kind,
            size=index.size,
            pending_deletes=deleted,
            side_rows=side,
            host=gc_stats(),
        )
        lane_pad_bytes = getattr(index, "lane_pad_bytes", None)
        if lane_pad_bytes is not None:
            out["lane_pad_bytes"] = lane_pad_bytes()
        ctrl = self._admission.get(name)
        if ctrl is not None:
            out.update(
                admission_level=ctrl.last_level,
                shed_requests=ctrl.shed_total,
                deadline_expired=ctrl.expired_total,
            )
        mgr = self._degraded.get(name)
        if mgr is not None:
            out["degraded_level"] = mgr.level
        arb = self._effort.get(name)
        if arb is not None:
            out.update(
                autotune_level=arb.autotune_level,
                effective_effort_level=arb.effective_level(),
            )
        hedger = self._hedgers.get(name)
        if hedger is not None:
            out.update(
                hedges_fired=hedger.fired_total,
                hedge_wins=hedger.hedge_wins,
            )
        return out

    def _refresh_capacity_gauges(self) -> None:
        """Re-derive the per-version live-buffer gauges from the registry's
        weak version history.  Gauges are pull-refreshed (not provider-fed)
        because ``to_prometheus()`` does not run providers — every export
        path below calls this first."""
        try:
            obs_cost.refresh_live_buffer_gauges(self.registry)
        except Exception:  # capacity accounting must never break serving
            pass
        try:
            obs_cost.refresh_mutation_gauges(self.registry)
        except Exception:  # mutation pressure gauges likewise
            pass
        try:
            obs_cost.refresh_page_gauges(self.registry)
        except Exception:  # page-residency gauges likewise
            pass
        try:
            # wasted-time fraction + measured roofline utilization per
            # executable key — pull-refreshed on the same scrape path
            obs_perf.default_ledger().refresh_gauges()
        except Exception:  # perf accounting must never break serving
            pass

    def _incident_context(self) -> Dict[str, object]:
        """Snapshot attached to incident timelines at open/close.

        Deliberately lock-light: registry versions and queue depths only —
        no index or compactor internals, so a context capture triggered by
        a publish from inside the serve stack cannot re-enter the lock the
        publisher holds."""
        indexes: Dict[str, object] = {}
        for name in self.registry.names():
            try:
                _index, version = self.registry.get_versioned(name)
            except KeyError:  # removed between names() and here
                continue
            entry: Dict[str, object] = {"version": version}
            try:
                entry["queue_depth"] = self._batcher(name).queue_depth()
            except KeyError:
                pass
            indexes[name] = entry
        ctx: Dict[str, object] = {"indexes": indexes}
        if self.slo_engine is not None:
            ctx["slo"] = self.slo_engine.health()
        if self.autotuner is not None:
            ctx["autotune"] = self.autotuner.health()
        return ctx

    def healthz(self) -> Dict[str, object]:
        """Aggregated health verdict: OK / DEGRADED / UNHEALTHY.

        One :class:`raft_tpu.obs.health.IndexProbe` per served name —
        warmup state, hot-path recompiles, queue depth vs capacity, the
        pipeline's in-flight window occupancy vs its ``pipeline_depth``
        bound (also scrapeable as ``raft_tpu_serve_pipeline_depth`` /
        ``raft_tpu_serve_inflight_batches``), and the auditor's recall
        EWMA when an auditor is attached — folded
        with the device-memory headroom check by
        :func:`raft_tpu.obs.health.build_report`.  Also publishes the
        ``raft_tpu_health`` gauge (0=OK, 1=DEGRADED, 2=UNHEALTHY) so the
        verdict is scrapeable.

        A transition *into* UNHEALTHY auto-dumps the flight recorder
        (debounced), and the report's ``flight`` key carries the latest
        dump's JSON + Chrome-trace paths — the payload that announces the
        incident also says where the evidence landed.

        With an SLO engine attached (``slo=`` knob) the report also folds
        in the error-budget check: an exhausted budget is DEGRADED, and
        the detail names the offending objectives under ``slo``.

        The measured perf ledger folds in the same way: an executable key
        inside its regression-debounce window (a live ``perf_regression``)
        reports DEGRADED under the report's ``perf`` key.
        """
        self._refresh_capacity_gauges()
        auditor = self.auditor
        pinned_min = (
            set(self.autotuner.health().get("pinned_min_effort", ()))
            if self.autotuner is not None else set()
        )
        probes: Dict[str, obs_health.IndexProbe] = {}
        for name in self.names():
            try:
                b = self._batcher(name)
            except KeyError:  # removed between names() and here
                continue
            compaction: Dict[str, object] = {}
            if self.compactor is not None:
                try:
                    compaction = self.compactor.stats(name)
                except Exception:
                    compaction = {}
            last_abort = compaction.get("last_abort")
            ctrl = self._admission.get(name)
            mgr = self._degraded.get(name)
            probes[name] = obs_health.IndexProbe(
                warm=b.warm,
                recompiles=b.metrics.recompiles,
                queue_depth=b.queue_depth(),
                max_batch=b.max_batch,
                pipeline_depth=b.pipeline_depth,
                inflight=b.inflight,
                admission_level=(
                    ctrl.last_level if ctrl is not None else None
                ),
                degraded_level=mgr.level if mgr is not None else None,
                autotune_level=(
                    self._effort[name].autotune_level
                    if self.autotuner is not None and name in self._effort
                    else None
                ),
                autotune_pinned_min=name in pinned_min,
                recall_ewma=(
                    auditor.recall_ewma(name) if auditor is not None else None
                ),
                recall_threshold=(
                    auditor.threshold if auditor is not None else None
                ),
                compaction_backlog=compaction.get("backlog"),
                compaction_trigger=compaction.get("trigger"),
                compaction_last_abort=(
                    str(last_abort.get("reason", "unknown"))
                    if isinstance(last_abort, dict)
                    else None
                ),
            )
        from raft_tpu.store.budget import default_budget

        page_budget = default_budget()
        return obs_health.build_report(
            probes,
            registry=obs.default_registry(),
            slo=(
                self.slo_engine.health()
                if self.slo_engine is not None else None
            ),
            perf=obs_perf.default_ledger().health_slice(),
            budget=(
                page_budget.snapshot() if page_budget is not None else None
            ),
        )

    def readyz(self) -> Dict[str, object]:
        """Readiness: every served index warmed (bucket ladder compiled).

        Unlike :meth:`healthz` this is a gate, not a diagnosis — a load
        balancer should withhold traffic until ``ready`` is true, then
        switch to ``healthz`` for liveness.
        """
        warm = {n: self._batcher(n).warm for n in self.names()}
        return {"ready": bool(warm) and all(warm.values()), "indexes": warm}

    def metrics(self) -> Dict[str, object]:
        """The whole observability picture in one JSON-safe dict.

        ``indexes`` holds each served name's :meth:`stats` (request p50/p99
        + per-stage breakdown); ``registry`` is the process-wide
        :func:`raft_tpu.obs.snapshot` — span histograms, XLA compile events
        attributed to the span that caused them, cache hit/miss counts,
        the slow-query log, and each index's ``serve.<name>`` section;
        ``health`` is the :meth:`healthz` report.
        """
        out = {
            "indexes": {n: self.stats(n) for n in self.names()},
            "health": self.healthz(),
            "registry": obs.snapshot(),
            # measured perf ledger, surfaced at the top level too (it also
            # rides registry["perf"]): hotspot ranking + regression state
            "perf": obs_perf.default_ledger().snapshot(),
        }
        if self.slo_engine is not None:
            out["slo"] = self.slo_engine.snapshot()
        return out

    def prometheus(self) -> str:
        """The process metrics registry in Prometheus text format.

        Refreshes the pull-style gauges first (live-buffer bytes per index
        version, ``raft_tpu_health``) — the exporter itself never runs
        providers, so the refresh has to happen on the scrape path.
        """
        try:
            self.healthz()  # publishes raft_tpu_health + capacity gauges
        except Exception:
            pass
        return obs.to_prometheus()

    def openmetrics(self) -> str:
        """The registry as OpenMetrics text, exemplars included.

        Same refresh path as :meth:`prometheus`; serve this form to
        scrapers that negotiate ``application/openmetrics-text`` — each
        latency bucket's retained request-id exemplar links it to the
        matching flight-recorder timeline (see :meth:`healthz`'s
        ``flight`` key for the latest dump location).
        """
        try:
            self.healthz()
        except Exception:
            pass
        return obs.to_openmetrics()

    # -- lifecycle -----------------------------------------------------------
    def stop(self) -> None:
        # gateway first: stop answering external probes and admin verbs
        # before the subsystems they read start going down (a scrape
        # mid-teardown would race half-stopped state)
        if self.gateway is not None:
            self.gateway.close()
        # autotuner before the SLO engine: its ticks read slo health
        if self.autotuner is not None:
            self.autotuner.stop()
        if self.slo_engine is not None:
            self.slo_engine.stop()
        try:
            obs_incidents.default_manager().remove_context_source("service")
        except Exception:  # bus already reset (test teardown ordering)
            pass
        # compactor first: a pass mid-flight may still submit warmup work
        # through the batchers it is about to go down with
        if self.compactor is not None:
            self.compactor.stop()
        with self._lock:
            batchers = list(self._batchers.values())
            controllers = list(self._admission.values())
        for b in batchers:
            b.stop()
        # after the batchers: a draining batch may still cut through the
        # admission path, which wants its burn latch live
        for ctrl in controllers:
            ctrl.close()

    def __enter__(self) -> "SearchService":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
