"""QueryService: the concurrent serving front end.

Wires the admission scheduler (bounded queue, priority classes, tenant
rate limits, typed shedding) to the request batcher (coalesced device
dispatches) over a DataStore. One dispatch thread drives the device —
the accelerator runs one program at a time, so more dispatch threads
would only interleave launches, not add throughput; concurrency buys
throughput here through COALESCING, not parallel dispatch.

Lifecycle:

    svc = QueryService(store)                 # starts the dispatcher
    fut = svc.knn("gdelt", CQL, qx, qy, k=8)  # -> Future
    dists, idx, batch = fut.result()
    svc.close(drain=True)                     # graceful: finish queue

Degradation ladder (opt-in per request via allow_degraded, master switch
ServeConfig.degrade): as queue occupancy crosses the watermarks the
service first downgrades hints (level 1: loose bbox — skip the exact
residual re-check of the spatial primary; level 2: + 1-in-4 sampling),
then sheds batch-class work, and the bounded queue rejects the rest.
Responses from downgraded queries carry request.degraded = True.

Observability: per-request ServeEvents into the store's audit writer,
queue-wait and end-to-end latency histograms (p50/p95/p99 via the
Prometheus export), dispatch/coalesce/shed counters — all through
`geomesa_tpu.utils.metrics` plus a per-instance `stats()` snapshot.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu.compilecache.stall import STALLS
from geomesa_tpu.plan.audit import ServeEvent
from geomesa_tpu.plan.planner import QueryTimeout
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve.batcher import (
    MIN_KNN_BATCH, compat_key, execute_batch, fail_expired, split_expired)
from geomesa_tpu.serve.scheduler import (
    PRIORITIES, AdmissionQueue, QueryRejected, RateLimiter, ServeRequest)
from geomesa_tpu.telemetry.recorder import RECORDER
from geomesa_tpu.telemetry.trace import TRACER
from geomesa_tpu.utils.padding import next_pow2 as _next_pow2


@dataclasses.dataclass
class ServeConfig:
    max_queue: int = 128        # admission bound (backpressure, not buffer)
    max_batch: int = 64         # coalescing cap per dispatch
    # coalescing window. None (the default): the gather ends as soon as
    # no compatible request is queued and the window's route can start
    # it (a free pipeline slot; a serial window always can), so windows
    # grow only while the device pipeline is behind. A number: wait out
    # that fixed window (the added-latency ceiling), 0 = no wait
    max_wait_ms: Optional[float] = None
    default_timeout_ms: Optional[int] = None  # per-request deadline default
    tenant_rate: Optional[float] = None  # qps per tenant; None = unlimited
    tenant_burst: float = 8.0
    # poison-query quarantine (docs/ROBUSTNESS.md): a fingerprint that
    # crashes `quarantine_after` dispatches within the TTL is rejected
    # at admission with QueryRejected("quarantined") instead of
    # re-entering the dispatcher; 0 disables
    quarantine_after: int = 3
    quarantine_ttl_s: float = 600.0
    degrade: bool = False       # master switch for the degradation ladder
    degrade_watermark: float = 0.75  # queue occupancy -> hint downgrades
    shed_watermark: float = 0.90     # queue occupancy -> shed batch class
    drain_timeout_s: float = 30.0
    # cold-start management (docs/SERVING.md "Cold start"): a manifest
    # path replays BEFORE the dispatcher starts accepting traffic;
    # track_compiles installs a JitTracker over the engine jits so
    # recompiles are counted and ServeEvents carry kernel compile-stall
    # attribution (warmup()/record_warmup() install it on demand too)
    warmup_manifest: Optional[str] = None
    track_compiles: bool = False
    # telemetry (docs/OBSERVABILITY.md): trace=True enables the
    # PROCESS-WIDE span tracer at construction (TRACER is global — one
    # switch per process, like the stall meter); flight_dump sets the
    # flight recorder's crash-dump path for this process
    trace: bool = False
    flight_dump: Optional[str] = None
    # SLO engine (docs/OBSERVABILITY.md "SLOs"): a spec path (.toml/
    # .json), a spec dict, an SloSpec, or a pre-built SloEngine (tests
    # inject one with a fake clock). When set, every resolved request
    # feeds the engine's sliding windows, `slo.*` gauges export at
    # scrape time, /debug/slo renders the burn report, and — with
    # `degrade` on — the ladder takes the engine's burn-rate boost as a
    # second input alongside queue occupancy, so shedding engages on
    # budget exhaustion too
    slo: object = None
    # continuous profiler (telemetry/prof.py): fold every recorded
    # trace into the process-lifetime distributions (requires trace=
    # True to have traces to fold; the fold itself is budgeted and the
    # flag only flips the process-wide PROFILER switch)
    profile: bool = False
    # pipelined dispatch (docs/SERVING.md "Pipelined dispatch"): kNN
    # windows run prepare/transfer/launch on the dispatch thread and
    # defer the device sync to a completer thread, keeping up to
    # `pipeline_depth` windows in flight (transfer overlaps compute —
    # the ROADMAP item-2 host-gap work). pipeline=False restores the
    # fully serial dispatch (chaos determinism runs use it).
    # pipeline_donate: None = auto (donate staged query buffers via the
    # registry serve tier on backends that support donation; CPU does
    # not), True/False forces.
    pipeline: bool = True
    pipeline_depth: int = 2
    pipeline_donate: Optional[bool] = None
    # persistent serve loop (docs/SERVING.md "Persistent serve loop"):
    # eligible kNN window classes dispatch over ONE long-lived ring
    # program (frozen plan/mask/capacity, AOT handle, depth-`ring_depth`
    # ring of donated staging slots) — per window only a slot write +
    # one executable invocation + the completer's harvest read.
    # Ineligible or stale windows fall back typed to the pipeline
    # above; ring=False disables the tier entirely (serial-determinism
    # and chaos runs that already disable the pipeline get it for free)
    ring: bool = True
    ring_depth: int = 4
    # sharded serving (docs/SERVING.md "Sharded serving"): route live
    # traffic through the multi-chip engine. "auto" = single-chip on 1
    # device, sharded over every device when >1 (the `gmtpu serve`
    # default); N = first N devices; None/"off" = single-chip. When a
    # mesh resolves, the store's device cache re-tiers to mesh
    # residency (one NamedSharding upload per manifest snapshot, per-
    # chip tile ownership), coalesced kNN windows dispatch as ONE
    # pjit/shard_map program with psum/all_gather merge, and admission
    # tags each query's shard affinity so single-owner windows run on
    # the chip their tiles live on.
    mesh: object = None
    # standing queries (docs/SERVING.md "Standing queries"): bounds and
    # rate limits for the subscribe/unsubscribe wire verbs; the
    # SubscriptionManager shares this service's per-tenant token
    # buckets, so queries and subscriptions draw one admission budget.
    # subscribe_poll_ms drives the auto-poll pump while subscriptions
    # are active (None = polls happen only on the `poll` verb or when
    # queries fold the topic)
    subscribe_max: int = 256
    subscribe_outbox: int = 1024
    subscribe_rate: Optional[float] = None
    subscribe_poll_ms: Optional[float] = None
    # approximate-answer tier (docs/SERVING.md "Approximate answers"):
    # approx=True lets tolerant queries (hints.tolerance) serve from
    # sketches with typed bounds; while the SLO exactness budget is
    # spent the tolerance hint is STRIPPED at admission (budget
    # exhaustion moves traffic to the exact path, never to silent
    # accuracy loss). approx_degrade_tolerance is the degradation
    # ladder's first rung — BEFORE loose-bbox: an allow_degraded
    # count/density under overload gets a sketch answer with a bound
    # instead of a silently loosened exact scan.
    approx: bool = True
    approx_degrade_tolerance: float = 0.1
    # version-exact result cache: count/execute results keyed on
    # (typeName, canonical CQL, hints, manifest version) — repeated
    # dashboard queries cost a dict lookup, invalidation is exact by
    # construction (a write bumps the version). 0 disables.
    result_cache: int = 256


# the ways a coalescing gather ends (`serve.coalesce.<end>` counters,
# the `coalesce` span's `ended` attribute)
COALESCE_ENDS = ("immediate", "slot_bound", "timed", "cap")


def _quarantine_key(req: ServeRequest):
    """Poison fingerprint: the coalescing key (canonical CQL + kind +
    kernel choice — exactly what would share the crashing dispatch), or
    a coarse (kind, type) key for requests that never coalesce."""
    return compat_key(req) or ("solo", req.kind, req.query.type_name)


class QueryService:
    """In-process serving API over a DataStore (or any store exposing
    get_feature_source). Thread-safe: submit from any thread."""

    def __init__(self, store, config: Optional[ServeConfig] = None,
                 autostart: bool = True):
        self.store = store
        self.config = config or ServeConfig()
        # sharded serving: resolve the mesh spec once and install it on
        # the store — existing sources re-tier their device cache, new
        # sources inherit it (docs/SERVING.md "Sharded serving").
        # None = inherit whatever the store already carries (a store
        # constructed with DataStore(mesh=...) serves sharded
        # regardless of the config spelling); "off" = force single-chip,
        # clearing a previously installed mesh.
        if self.config.mesh is not None:
            from geomesa_tpu.parallel.mesh import serve_mesh

            self.mesh = serve_mesh(self.config.mesh)
            if hasattr(store, "set_mesh"):
                store.set_mesh(self.mesh)
        else:
            self.mesh = getattr(store, "mesh", None)
        self.queue = AdmissionQueue(self.config.max_queue)
        self.limiter = RateLimiter(
            self.config.tenant_rate, self.config.tenant_burst)
        from geomesa_tpu.faults import QuarantineRegistry

        self.quarantine = QuarantineRegistry(
            strikes=max(self.config.quarantine_after, 1),
            ttl_s=self.config.quarantine_ttl_s)
        # version-exact result cache (geomesa_tpu.approx.cache):
        # admission peeks it before queueing, the dispatch loop
        # populates it, and a hit never enters a coalescing window
        self.result_cache = None
        if self.config.result_cache > 0:
            from geomesa_tpu.approx.cache import ResultCache

            self.result_cache = ResultCache(self.config.result_cache)
        self.audit = getattr(store, "audit", None)
        if self.config.trace:
            TRACER.enable()
        if self.config.flight_dump:
            RECORDER.auto_dump_path = self.config.flight_dump
        if self.config.profile:
            from geomesa_tpu.telemetry.prof import PROFILER

            PROFILER.enable()
        # SLO engine: accept a path, dict, SloSpec or a ready engine
        # (tests pass one with a fake clock)
        self.slo = None
        if self.config.slo is not None:
            from geomesa_tpu.telemetry.slo import SloEngine, SloSpec

            spec = self.config.slo
            if isinstance(spec, SloEngine):
                self.slo = spec
            else:
                if isinstance(spec, str):
                    spec = SloSpec.load(spec)
                elif isinstance(spec, dict):
                    spec = SloSpec.from_dict(spec)
                self.slo = SloEngine(spec)
        self._closed = False
        self._stop = threading.Event()
        self._inflight = 0
        self._state_lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        # dispatcher load, always on: cumulative ns the dispatch loop
        # has run, and the part of it spent waiting inside queue.pop.
        # Plain ints written only by the dispatch thread (no lock on
        # the hot path); stats() reads them as they stand
        self._loop_ns = 0
        self._loop_wait_ns = 0
        self._worker: Optional[threading.Thread] = None
        # standing-query manager (geomesa_tpu.subscribe): attached by
        # the wire layer when the first subscribe verb arrives, so
        # stats()/debug endpoints surface subscription state
        self.subscriptions = None
        # columnar-wire push fan-out (docs/SERVING.md "Columnar wire"):
        # ONE PushMux per service, shared by every connection so a
        # subscription's frames can mirror onto attached connections —
        # built lazily by wire_mux() on the first push/attach
        self._push_mux = None
        # the bound /metrics port, when the owner started a
        # MetricsServer for this service (gmtpu serve --metrics-port,
        # fleet replicas). With port=0 the OS picks — N replicas on one
        # host must not collide on a fixed port — so the bound value is
        # reported here and in the startup line, not assumed
        self.metrics_port: Optional[int] = None
        # pipelined dispatch path (serve/pipeline.py): the default for
        # kNN windows; its completer thread starts lazily on the first
        # pipelined window
        self.pipeline = None
        if self.config.pipeline:
            from geomesa_tpu.serve.pipeline import DispatchPipeline

            self.pipeline = DispatchPipeline(
                self, depth=self.config.pipeline_depth,
                donate=self.config.pipeline_donate,
                ring=self.config.ring,
                ring_depth=self.config.ring_depth)
        # compilation management: compiled executables must survive
        # restarts (the cache is idempotent/never-failing to enable)
        try:
            from geomesa_tpu.compilecache.persist import (
                enable_persistent_cache)

            enable_persistent_cache()
        # gt: waive GT14
        # (deliberate degrade: the persistent compile cache is an
        # optimization that must never fail service construction —
        # compilecache/persist.py documents the never-raises contract)
        except Exception:
            pass
        self.tracker = None          # JitTracker over the engine jits
        self._tracker_acquired = False
        self._recorder = None        # WarmupRecorder, when recording
        try:
            if self.config.track_compiles:
                self._ensure_tracker()
            if self.config.warmup_manifest:
                # startup hook: replay before the dispatcher takes
                # traffic
                self.warmup(self.config.warmup_manifest)
        except BaseException:
            # a failed constructor (e.g. missing manifest) must not
            # leak the process-global engine wrappers: close() is
            # unreachable for a never-constructed service
            self._release_tracker()
            raise
        if autostart:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        self._stop.clear()
        self._worker = threading.Thread(
            target=self._loop, name="gmtpu-serve-dispatch", daemon=True)
        self._worker.start()

    def close(self, drain: bool = True,
              timeout_s: Optional[float] = None) -> None:
        """Stop the service. drain=True (graceful): admissions stop with
        QueryRejected(shutting_down) while every already-admitted request
        still executes; drain=False: queued requests are rejected."""
        with self._state_lock:
            self._closed = True
        if not drain:
            for r in self.queue.drain_all():
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(
                        QueryRejected("shutting_down", "service closed"))
        deadline = time.monotonic() + (
            timeout_s if timeout_s is not None
            else self.config.drain_timeout_s)
        while time.monotonic() < deadline:
            with self._state_lock:
                idle = self._inflight == 0
            if idle and len(self.queue) == 0:
                break
            time.sleep(0.005)
        self._stop.set()
        if self._worker is not None:
            self._worker.join(timeout=5.0)
        if self.pipeline is not None:
            # windows already launched still sync (no torn responses);
            # runs after the dispatch thread stopped submitting
            self.pipeline.close()
        with self._state_lock:
            mux = self._push_mux
        if mux is not None:
            mux.close()  # joins the per-sink writer threads
        # restore the bare engine jits (owner only); the tracker object
        # (and its counters) stays readable after close
        self._release_tracker()

    # -- warmup / compile management ---------------------------------------

    def _ensure_tracker(self):
        """Attach the process-wide engine JitTracker to this service
        (the engine jits are module globals — services share ONE tracker
        via refcounted acquisition, and the wrappers come off when the
        LAST service releases; see
        analysis.runtime.acquire_engine_tracker). Counting recompiles is
        also what makes ServeEvent compile-stall attribution see kernel
        compiles."""
        if self.tracker is None:
            from geomesa_tpu.analysis.runtime import acquire_engine_tracker

            self.tracker, _ = acquire_engine_tracker(
                recorder=self._recorder)
            self._tracker_acquired = True
        return self.tracker

    def _release_tracker(self) -> None:
        if self._tracker_acquired and self.tracker is not None:
            from geomesa_tpu.analysis.runtime import release_engine_tracker

            release_engine_tracker(self.tracker)
            self._tracker_acquired = False

    def record_warmup(self):
        """Start recording a warmup manifest from live traffic: every
        compiling kernel signature (via JitTracker) and every dispatched
        query shape lands in the returned WarmupRecorder. Call
        `.manifest().save(path)` on it when the workload is
        representative."""
        from geomesa_tpu.compilecache.manifest import WarmupRecorder

        self._recorder = WarmupRecorder()
        tracker = self._ensure_tracker()
        tracker.recorder = self._recorder
        return self._recorder

    def warmup(self, manifest, check: bool = False):
        """Replay a warmup manifest (path, or a WarmupManifest) through
        the compilecache so every kernel/filter this service will need is
        compiled — and persisted — before traffic. With `check=True` the
        replay is followed by a second pass that must compile NOTHING
        (`report.residual_recompiles == 0`), the programmatic equivalent
        of `gmtpu warmup --check`. Returns the WarmupReport."""
        from geomesa_tpu.compilecache import warmup as _warmup
        from geomesa_tpu.compilecache.manifest import WarmupManifest
        from geomesa_tpu.utils.metrics import metrics

        if isinstance(manifest, str):
            manifest = WarmupManifest.load(manifest)
        self._ensure_tracker()
        t0 = time.monotonic()
        run = _warmup.check if check else _warmup.replay
        report = run(manifest, store=self.store)
        metrics.gauge("serve.warmup.seconds", time.monotonic() - t0)
        metrics.gauge("serve.warmup.ok", 1.0 if report.ok else 0.0)
        self._bump("warmups")
        return report

    # -- submission API ----------------------------------------------------

    def submit(self, req: ServeRequest) -> Future:
        """Admission control, then enqueue. Raises the typed
        QueryRejected (never queues unboundedly) on shed/limit/closed.
        With tracing on, opens the request's Trace (root span "query")
        and the "admit" child span; a rejected request finishes its
        trace here and still lands in the flight recorder — overload
        postmortems need the shed requests, not just the served ones."""
        trace = TRACER.start_trace(
            "query", kind=req.kind, type=req.query.type_name,
            tenant=req.tenant)
        if trace is None:
            try:
                self._admit(req)
                hit, value = self._cache_peek(req)
                if hit:
                    return self._resolve_cached(req, value)
                value = self._approx_peek(req)
                if value is not None:
                    return self._resolve_approx(req, value)
                return self._enqueue(req)
            except QueryRejected:
                self._observe_slo(req, "rejected", 0.0)
                raise
        req.trace = trace
        try:
            # the admit span must CLOSE before the request becomes
            # visible to the dispatcher (queue.put): the span's append
            # happens at __exit__, and a dispatcher racing ahead of it
            # could snapshot/finish the trace admit-less (or leak the
            # admit span into riders' adopted window slice)
            with TRACER.scope(trace):
                with TRACER.span("admit"):
                    self._admit(req)
            hit, value = self._cache_peek(req)
            if hit:
                return self._resolve_cached(req, value)
            with TRACER.scope(trace):
                value = self._approx_peek(req)
            if value is not None:
                return self._resolve_approx(req, value)
            return self._enqueue(req)
        except BaseException as e:
            if isinstance(e, QueryRejected):
                self._observe_slo(req, "rejected", 0.0)
            trace.finish(status="rejected", error=type(e).__name__)
            RECORDER.record(trace)
            raise

    def _admit(self, req: ServeRequest) -> None:
        """Admission checks up to — but excluding — the queue put."""
        self._bump("submitted")
        with self._state_lock:
            closed = self._closed
        if closed:
            self._bump("rejected")
            raise QueryRejected("shutting_down", "service closed")
        if self.config.quarantine_after and not self.quarantine.empty():
            detail = self.quarantine.blocked(_quarantine_key(req))
            if detail is not None:
                self._bump("rejected")
                self._bump("quarantined")
                raise QueryRejected("quarantined", detail)
        try:
            self.limiter.admit(req.tenant)
        except QueryRejected:
            self._bump("rejected")
            raise
        if req.deadline is None and self.config.default_timeout_ms:
            req.deadline = (time.monotonic()
                            + self.config.default_timeout_ms / 1000.0)
        level = self.degrade_level()
        if level >= 2 and req.priority >= PRIORITIES.index("batch"):
            self._bump("rejected")
            self._bump("shed")
            raise QueryRejected(
                "shed", "sustained overload: batch class shed")
        if level >= 1 and self.config.degrade and req.allow_degraded:
            self._degrade(req, level)
        # approximate-answer governor (docs/SERVING.md "Approximate
        # answers"): a spent exactness budget STRIPS the tolerance hint
        # — the request pays the exact path; approximation is a
        # budgeted contract, never silent degradation. Config-disabled
        # approx strips too but counts separately — "budget_exact"
        # must mean the GOVERNOR acted, or a disabled service reads as
        # perpetual budget exhaustion on dashboards.
        if req.query.hints.tolerance is not None and not self._approx_ok():
            req.query = dataclasses.replace(
                req.query, hints=dataclasses.replace(
                    req.query.hints, tolerance=None))
            if not self.config.approx:
                self._bump("approx_disabled")
            else:
                self._bump("approx_budget_exact")
                from geomesa_tpu.utils.metrics import metrics

                metrics.counter("approx.budget_exact")
        if req.kind in ("count", "execute") and self.result_cache is not None:
            # the batcher populates the cache with the version the
            # planner's plan actually pinned (exact-by-construction)
            req.cache = self.result_cache
        if self.mesh is not None:
            # shard-affinity admission (docs/SERVING.md "Sharded
            # serving"): tag the query with the chips owning its tiles
            # — metadata-only; the planner's dispatch seam recomputes
            # the authoritative value and routes single-owner windows
            # to their chip
            from geomesa_tpu.serve.scheduler import shard_affinity
            from geomesa_tpu.utils.metrics import metrics

            try:
                source = self.store.get_feature_source(
                    req.query.type_name)
            except Exception:
                return  # the dispatch path raises the typed error
            shards = shard_affinity(source, req)
            if shards:
                req.shards = ",".join(map(str, shards))
                metrics.counter("serve.affinity.admitted",
                                shards=req.shards)

    def _enqueue(self, req: ServeRequest) -> Future:
        try:
            self.queue.put(req)
        except QueryRejected:
            self._bump("rejected")
            raise
        from geomesa_tpu.utils.metrics import metrics

        metrics.gauge("serve.queue.depth", float(len(self.queue)))
        return req.future

    def query(self, type_name: str, cql: str = "INCLUDE",
              hints=None, **kw) -> Future:
        q = Query(type_name, cql, hints=hints) if hints is not None \
            else Query(type_name, cql)
        return self.submit(self._request("execute", q, **kw))

    def count(self, type_name: str, cql: str = "INCLUDE", **kw) -> Future:
        return self.submit(self._request("count", Query(type_name, cql), **kw))

    def knn(self, type_name: str, cql: str, qx, qy, k: int = 10,
            impl: str = "sparse", **kw) -> Future:
        req = self._request("knn", Query(type_name, cql), **kw)
        req.qx, req.qy, req.k, req.impl = qx, qy, k, impl
        return self.submit(req)

    def _request(self, kind: str, query: Query, tenant: str = "",
                 priority: "int | str" = "normal",
                 timeout_ms: Optional[int] = None,
                 allow_degraded: bool = False) -> ServeRequest:
        if isinstance(priority, str):
            priority = PRIORITIES.index(priority)
        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms else None)
        return ServeRequest(kind=kind, query=query, tenant=tenant,
                            priority=priority, deadline=deadline,
                            allow_degraded=allow_degraded)

    # -- approximate tier + result cache -----------------------------------

    def _approx_ok(self) -> bool:
        """Sketch serving allowed right now? Config master switch AND
        the SLO exactness budget (spent budget routes exact)."""
        if not self.config.approx:
            return False
        if self.slo is None:
            return True
        return not self.slo.exactness_spent()

    def _sketch_rung_ok(self, req: ServeRequest) -> bool:
        """Can the sketch tier plausibly answer this request? The
        ladder's rung choice: an ELIGIBLE filter takes the sketch rung
        (typed bound), an ineligible one keeps the legacy loose-bbox/
        sampling rewrite — the ladder must not lose its shedding lever
        on filters the sketches cannot see. Memoized filter parse, no
        sketch builds, no I/O."""
        try:
            source = self.store.get_feature_source(req.query.type_name)
            eng = source.planner.approx_engine()
            if eng.store is None:
                return False
            eligible = eng._parse_filter(req.query)[0]
            return bool(eligible)
        # gt: waive GT14
        # (deliberate degrade: rung SELECTION is best-effort — any
        # failure here falls back to the legacy degrade rewrite)
        except Exception:
            return False

    def _cache_key(self, req: ServeRequest):
        """The request's result-cache key at the CURRENT committed
        manifest version, or None when uncacheable (knn, tolerant,
        unversioned storage). Recomputed fresh at every peek — a key
        minted before a concurrent write must never serve the old
        version's entry after the write committed."""
        if self.result_cache is None or req.kind == "knn":
            return None
        try:
            source = self.store.get_feature_source(req.query.type_name)
        except Exception:
            return None  # the dispatch path raises the typed error
        storage = getattr(source, "storage", None)
        mv = getattr(storage, "manifest_version", None)
        if not callable(mv):
            return None
        from geomesa_tpu.approx.cache import result_key

        return result_key(req.kind, req.query, mv())

    def _cache_peek(self, req: ServeRequest, count_miss: bool = True):
        """(hit, value) against the version-exact result cache."""
        if self.result_cache is None or req.kind == "knn":
            return False, None
        return self.result_cache.get(self._cache_key(req),
                                     count_miss=count_miss)

    def _approx_peek(self, req: ServeRequest):
        """Admission-time sketch resolution (docs/SERVING.md
        "Approximate answers"): a tolerant COUNT answers on the submit
        thread in microseconds — it never queues, never coalesces, and
        never waits behind an exact device scan. Returns the
        ApproxCount or None (every fallthrough pays the normal queued
        path, where the planner retries the sketch tier with full plan
        context)."""
        if req.kind != "count" or req.query.hints.tolerance is None:
            return None
        try:
            source = self.store.get_feature_source(req.query.type_name)
            planner = getattr(source, "planner", None)
            fn = getattr(planner, "approx_count_result", None)
            if fn is None:
                return None
            qr = fn(req.query)
        # gt: waive GT14
        # (deliberate degrade: the admission peek is an optimization —
        # any failure here falls through to the queued dispatch path,
        # which surfaces the typed error to the right future)
        except Exception:
            return None
        if qr is None:
            return None
        from geomesa_tpu.approx.engine import ApproxCount

        return ApproxCount(int(qr.count), int(qr.bound), qr.confidence)

    def _resolve_approx(self, req: ServeRequest, value) -> Future:
        """Resolve a sketch-served request at admission: full tier
        bookkeeping (metrics, SLO exactness spend, trace, audit), no
        queue, no dispatch."""
        from geomesa_tpu.utils.metrics import metrics

        req.approx = True
        if req.sketch_rung:
            # the ladder's speculative rung actually served: NOW the
            # request is a degraded answer (typed bound) and the
            # exactness budget spend is honest
            req.degraded = True
            self._bump("degraded")
            metrics.counter("serve.degraded")
        self._bump("approx_served")
        self._bump("completed")
        metrics.counter("serve.requests", kind=req.kind, status="ok")
        metrics.counter("serve.tier", tier="sketch")
        metrics.histogram("serve.latency").update(0.0)
        self._observe_slo(req, "ok", 0.0)
        if req.future.set_running_or_notify_cancel():
            req.future.set_result(value)
        if req.trace is not None:
            RECORDER.record(req.trace.finish(status="ok", approx=True))
        if self.audit is not None:
            self.audit.write(ServeEvent(
                trace_id=(req.trace.trace_id
                          if req.trace is not None else ""),
                type_name=req.query.type_name,
                kind=req.kind,
                tenant=req.tenant,
                priority=PRIORITIES[req.priority],
                queue_ms=0.0,
                exec_ms=0.0,
                batch_size=1,
                status="ok",
                degraded=req.degraded,
                approx=True,
            ))
        return req.future

    def _resolve_cached(self, req: ServeRequest, value,
                        queue_ms: float = 0.0) -> Future:
        """Resolve a request straight from the result cache: no queue,
        no coalescing window, no dispatch — full bookkeeping (metrics,
        SLO, trace, audit) still applies so tier shares stay honest."""
        from geomesa_tpu.utils.metrics import metrics

        req.cache_hit = True
        self._bump("cache_hits")
        self._bump("completed")
        metrics.counter("serve.requests", kind=req.kind, status="ok")
        metrics.counter("serve.tier", tier="cached")
        latency_s = queue_ms / 1000.0
        metrics.histogram("serve.latency").update(latency_s)
        self._observe_slo(req, "ok", latency_s)
        if req.future.set_running_or_notify_cancel():
            req.future.set_result(value)
        if req.trace is not None:
            RECORDER.record(req.trace.finish(status="ok", cache_hit=True))
        if self.audit is not None:
            self.audit.write(ServeEvent(
                trace_id=(req.trace.trace_id
                          if req.trace is not None else ""),
                type_name=req.query.type_name,
                kind=req.kind,
                tenant=req.tenant,
                priority=PRIORITIES[req.priority],
                queue_ms=queue_ms,
                exec_ms=0.0,
                batch_size=1,
                status="ok",
                degraded=req.degraded,
                cache_hit=True,
            ))
        return req.future

    # -- columnar wire -----------------------------------------------------

    def wire_mux(self):
        """The service-wide push fan-out (serve/columnar.py PushMux):
        one per service, lazily built — frames encode once and fan to
        every connection sink attached to their subscription."""
        with self._state_lock:
            if self._push_mux is None:
                from geomesa_tpu.serve.columnar import PushMux

                self._push_mux = PushMux(
                    queue_limit=self.config.subscribe_outbox)
            return self._push_mux

    # -- degradation ladder ------------------------------------------------

    def degrade_level(self) -> int:
        """0 = nominal; 1 = hint downgrades; 2 = + shed batch class.
        Two inputs, max wins: queue occupancy (a pure function, so the
        ladder releases the moment the backlog drains) and — when an
        SLO engine is attached — the burn-rate boost
        (docs/OBSERVABILITY.md "SLOs": a degrade-marked objective
        breaching the multi-window burn threshold engages the ladder
        even with an empty queue, because budget exhaustion means the
        served latency itself is the overload signal; the boost
        releases as the breach ages out of the fast window)."""
        if not self.config.degrade:
            return 0
        occ = len(self.queue) / self.config.max_queue
        level = 0
        if occ >= self.config.shed_watermark:
            level = 2
        elif occ >= self.config.degrade_watermark:
            level = 1
        if self.slo is not None and level < 2:
            level = max(level, self.slo.degrade_boost())
        return level

    def _degrade(self, req: ServeRequest, level: int) -> None:
        """Rewrite hints toward cheaper execution. The FIRST rung —
        before loose-bbox — is the sketch tier (docs/SERVING.md
        "Approximate answers"): an eligible count/density gets a
        tolerance hint and serves from sketches WITH a typed bound,
        which beats silently dropping the exact residual check; the
        planner's fallthrough keeps it safe when the bound does not
        fit. Aggregations with shapes a rewrite would corrupt
        (stats/bin/arrow) never degrade."""
        h = req.query.hints
        if h.is_stats or h.is_bin or h.is_arrow:
            return
        sketchable = (req.kind == "count"
                      or (req.kind == "execute" and h.is_density
                          and h.density_weight is None))
        if (sketchable and h.tolerance is None and self._approx_ok()
                and self._sketch_rung_ok(req)):
            # the rung is SPECULATIVE: it injects the tolerance hint
            # and records the level, but degraded/budget accounting
            # happens only where a sketch answer is actually served
            # (_resolve_approx / _finish_window) — a bound that does
            # not fit must not flag an EXACT answer degraded or spend
            # the exactness budget it never used
            if self.config.quarantine_after and req.quarantine_key is None:
                req.quarantine_key = _quarantine_key(req)
            req.query = dataclasses.replace(
                req.query, hints=dataclasses.replace(
                    h, tolerance=self.config.approx_degrade_tolerance))
            req.sketch_rung = level
            return
        if h.is_density:
            return  # loose-bbox/sampling would corrupt the grid
        # stash the PRE-degrade fingerprint: strikes must land on the
        # same key admission checks (see ServeRequest.quarantine_key)
        if self.config.quarantine_after and req.quarantine_key is None:
            req.quarantine_key = _quarantine_key(req)
        changes = {"loose_bbox": True}
        if level >= 2 and h.sampling is None:
            changes["sampling"] = 4
        req.query = dataclasses.replace(
            req.query, hints=dataclasses.replace(h, **changes))
        req.degraded = True
        self._bump("degraded")
        from geomesa_tpu.utils.metrics import metrics

        metrics.counter("serve.degraded")

    def _observe_slo(self, req: ServeRequest, status: str,
                     latency_s: float) -> None:
        """Feed one resolved request into the SLO engine's sliding
        windows (no-op without a spec; a tuple append with one)."""
        if self.slo is not None:
            # a sketch-served answer spends the exactness budget like a
            # ladder-degraded one: approximation is budgeted, and the
            # closed loop (exactness_spent -> tolerance stripped) is
            # what keeps it from becoming silent degradation
            self.slo.observe(req.kind, status, latency_s,
                             degraded=req.degraded or req.approx)

    # -- dispatch loop -----------------------------------------------------

    def _mark_inflight(self, _req: ServeRequest) -> None:
        # runs under the queue lock (pop's on_pop hook): removal and the
        # in-flight mark are one atomic step, so close(drain=True) can
        # never observe "queue empty, nothing in flight" while a popped
        # request is still on its way into _dispatch
        with self._state_lock:
            self._inflight += 1

    def _loop(self) -> None:
        import logging

        pc = time.perf_counter_ns
        start = pc()
        base = self._loop_ns
        while True:
            t = pc()
            req = self.queue.pop(timeout=0.05, on_pop=self._mark_inflight)
            t2 = pc()
            # gt: waive GT07
            # (single writer: only this thread writes the two load
            # counters; stats() reads each int whole under the GIL)
            self._loop_wait_ns += t2 - t
            self._loop_ns = base + t2 - start
            if req is None:
                if self._stop.is_set():
                    return
                continue
            try:
                self._dispatch(req)
            except Exception as e:  # noqa: BLE001 — the dispatcher must live
                # _dispatch resolves member futures before anything that
                # can throw here (audit/metrics); log and keep serving.
                # An un-typed error escaping to here is exactly the
                # postmortem case the flight recorder exists for: dump
                # the last-N-queries window before continuing.
                logging.getLogger(__name__).exception(
                    "serve dispatch loop error")
                RECORDER.crash_dump("serve dispatch loop error", e)
            finally:
                with self._state_lock:
                    self._inflight -= 1

    def _gather(self, first: ServeRequest) -> Tuple[List[ServeRequest], str]:
        """Coalescing window: collect queued requests compatible with
        `first`, up to max_batch. Returns the members and how the
        gather ended (COALESCE_ENDS): `cap` (max_batch filled),
        `timed` (an explicit max_wait_ms ran out), `immediate` (nothing
        compatible queued and the route free: no wait at all) or
        `slot_bound` (every pipeline slot was busy; the window took
        what arrived until one freed)."""
        reqs = [first]
        key = compat_key(first)
        cap = self.config.max_batch
        if cap <= 1:
            return reqs, "cap"
        if key is None:
            return reqs, "immediate"
        if self.config.max_wait_ms is None:
            # only a pipelined window can find its route busy; a serial
            # one runs on this thread, so it can always start now
            pipe = self.pipeline if first.kind == "knn" else None
            ended = "immediate"
            while True:
                reqs.extend(self.queue.drain_compatible(
                    key, compat_key, cap - len(reqs)))
                if len(reqs) >= cap:
                    return reqs, "cap"
                if pipe is None or pipe.slot_free():
                    return reqs, ended
                ended = "slot_bound"
                if not pipe.wait_slot():
                    return reqs, ended
        deadline = time.monotonic() + self.config.max_wait_ms / 1000.0
        while True:
            got = self.queue.drain_compatible(
                key, compat_key, cap - len(reqs))
            reqs.extend(got)
            if len(reqs) >= cap:
                return reqs, "cap"
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return reqs, "timed"
            time.sleep(min(0.0005, remaining))

    def _run_window(self, live: List[ServeRequest]) -> None:
        """The device-facing part of one dispatch: source lookup +
        coalesced execution, futures resolved for every member."""
        try:
            # an unknown type name raises HERE, not in execute_batch's
            # guarded body — it must fail these futures, not the
            # dispatcher thread (one bad request would hang the service)
            source = self.store.get_feature_source(live[0].query.type_name)
        except BaseException as e:  # noqa: BLE001 — fan out like a dispatch
            for r in live:
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(e)
        else:
            execute_batch(source, live)

    def _dispatch(self, first: ServeRequest) -> None:
        from geomesa_tpu.serve.batcher import fused_count_key
        from geomesa_tpu.utils.metrics import metrics

        g0_ns = time.perf_counter_ns()
        reqs, ended = self._gather(first)
        g1_ns = time.perf_counter_ns()
        self._bump("coalesce." + ended)
        metrics.counter("serve.coalesce." + ended)
        live, dead = split_expired(reqs)
        lead = live[0] if live else None
        pipelined = (self.pipeline is not None and lead is not None
                     and lead.kind == "knn")
        counts: List[ServeRequest] = []
        if pipelined:
            # cross-kind fusion: COUNT requests against the same
            # (type, CQL, hints) resolve from this window's filter-mask
            # reduction instead of their own dispatch RTT
            fkey = fused_count_key(lead)
            if fkey is not None:
                got = self.queue.drain_compatible(
                    fkey, compat_key, self.config.max_batch)
                counts, cdead = split_expired(got)
                dead = dead + cdead
        fail_expired(dead)
        for r in dead:
            self._bump("timeout")
            metrics.counter("serve.timeout")
            self._observe_slo(r, "timeout",
                              time.monotonic() - r.enqueued_at)
            if r.trace is not None:
                r.trace.record("queue.wait", r.enqueued_ns, g1_ns)
                RECORDER.record(r.trace.finish(status="timeout"))
        if not live:
            return
        if (lead.kind in ("count", "execute")
                and self.result_cache is not None
                and lead.query.hints.tolerance is None):
            # second-chance peek: a twin that dispatched while this
            # request queued may have populated the cache — resolve
            # the whole window (members share the coalescing key, so
            # one current-version key answers them all) without any
            # device work. The batcher therefore never coalesces a
            # cache-hit. Misses are unmetered here (admission already
            # counted them).
            hit, value = self._cache_peek(lead, count_miss=False)
            if hit:
                t_hit = time.monotonic()
                for r in live:
                    self._resolve_cached(
                        r, value,
                        queue_ms=(t_hit - r.enqueued_at) * 1000.0)
                return
        t0 = time.monotonic()
        now_ns = time.perf_counter_ns()
        for r in live + counts:
            metrics.histogram("serve.queue.wait").update(t0 - r.enqueued_at)
            if r.trace is not None:
                # cross-thread phase: opened (implicitly) at enqueue on
                # the submitting thread, closed here — recorded with
                # explicit stamps rather than a with-block
                r.trace.record("queue.wait", r.enqueued_ns, now_ns)
        # everything recorded into the LEAD trace from here on is the
        # shared dispatch window; riders adopt a copy at completion
        adopt_from = (lead.trace.span_count()
                      if lead.trace is not None else 0)
        if lead.trace is not None:
            lead.trace.record("coalesce", g0_ns, g1_ns,
                              gathered=len(reqs), fused=len(counts),
                              ended=ended)
        if self._recorder is not None:
            self._record_queries(live, counts)
        from geomesa_tpu.faults import RECOVERY

        if pipelined:
            # pipelined route: the source lookup error fans out HERE
            # (the serial path does it inside _run_window)
            try:
                source = self.store.get_feature_source(
                    lead.query.type_name)
            except BaseException as e:  # noqa: BLE001 — fan out typed
                for r in live + counts:
                    if r.future.set_running_or_notify_cancel():
                        r.future.set_exception(e)
                self._finish_window(live, counts, lead, t0,
                                    time.monotonic(), adopt_from,
                                    None, 0, [], [], pipelined=True)
                return
            # the window stays in flight past this method: it owns one
            # inflight token until _window_complete releases it, so
            # close(drain=True) waits for the completer too
            with self._state_lock:
                self._inflight += 1
            try:
                self.pipeline.submit(source, live, counts, lead, t0,
                                     g0_ns, g1_ns, adopt_from)
            except BaseException as e:
                # submit resolves all futures on its internal failure
                # paths; an exception HERE means the window never got a
                # slot (completer dead) — fail whatever is still
                # pending so no client hangs, then let _loop log it
                with self._state_lock:
                    self._inflight -= 1
                for r in live + counts:
                    if not r.future.done() and \
                            r.future.set_running_or_notify_cancel():
                        r.future.set_exception(e)
                raise
            return
        stall_token = STALLS.token()
        rec_token = RECOVERY.token()
        dispatch_span_id = None
        dispatch_end_ns = 0
        if lead.trace is not None:
            with TRACER.scope(lead.trace):
                with TRACER.span("dispatch", batch=len(live)) as dsp:
                    self._run_window(live)
                # read the handle RIGHT after the block closes (the
                # scope's shared handle holds the just-closed span);
                # None if tracing flipped off between admit and here
                dispatch_span_id = getattr(dsp, "span_id", None)
                dispatch_end_ns = getattr(dsp, "end_ns", 0)
        else:
            self._run_window(live)
        t1 = time.monotonic()
        # per-dispatch compile-stall attribution: everything THIS THREAD
        # noted into the stall meter during the window (tracked kernel
        # compiles + filter compiles — the dispatch's own work runs
        # synchronously on the dispatch thread) is charged to the
        # requests that rode the dispatch; scoping by thread keeps the
        # window exact even when other services/planner callers share
        # the process-wide meter
        stalls = STALLS.since(stall_token,
                              thread_ident=threading.get_ident())
        # recovery attribution, same thread-scoped window discipline as
        # the compile stalls: retries/faults noted by this dispatch
        # thread are charged to the requests that rode the dispatch
        # (boundary work on helper threads — the streaming-count decode-
        # ahead — is metered globally but not attributed per-request)
        recovery = RECOVERY.since(rec_token,
                                  thread_ident=threading.get_ident())
        self._finish_window(live, [], lead, t0, t1, adopt_from,
                            dispatch_span_id, dispatch_end_ns, stalls,
                            recovery)

    def _window_complete(self, win, t1: float, end_ns: int) -> None:
        """Pipeline completion callback (completer thread): shared
        finish bookkeeping, then release the window's inflight token."""
        try:
            self._finish_window(
                win.live, win.counts, win.lead, win.t0, t1,
                win.adopt_from, win.wid, end_ns,
                win.stalls, win.recovery, pipelined=True)
        finally:
            with self._state_lock:
                self._inflight -= 1

    def _finish_window(self, live, counts, lead, t0, t1, adopt_from,
                       dispatch_span_id, dispatch_end_ns, stalls,
                       recovery, pipelined: bool = False) -> None:
        """Everything that happens after a window's futures are
        resolved: stall/recovery attribution spans, counters, metrics,
        quarantine accounting, rider trace adoption, audit events.
        Shared verbatim by the serial path (dispatch thread) and the
        pipeline (completer thread)."""
        from geomesa_tpu.faults import BREAKERS, BreakerOpen, classify
        from geomesa_tpu.utils.metrics import metrics

        retries = sum(1 for kind, _ in recovery if kind == "retry")
        faults_seen = sum(1 for kind, _ in recovery if kind == "fault")
        breaker_state = ",".join(
            f"{name}={state}"
            for name, state in sorted(BREAKERS.states().items())
            if state != "closed")
        compile_ms = sum(s for _, _, s in stalls) * 1000.0
        labels = list(dict.fromkeys(lbl for lbl, _, _ in stalls))
        compiled = ",".join(labels[:5])
        if len(labels) > 5:
            compiled += f",+{len(labels) - 5}"
        if lead.trace is not None and dispatch_span_id is not None:
            # stall/recovery attribution as child spans of the dispatch
            # window: each stall at the interval its compiling call
            # took (the meter keeps the start stamp); retry/fault notes
            # only know when they were read, and render as instants at
            # the window end
            for label, start_ns, secs in stalls:
                lead.trace.record(
                    "compile.stall", start_ns,
                    start_ns + int(secs * 1e9),
                    parent_id=dispatch_span_id, label=label)
            for kind, label in recovery:
                lead.trace.record(
                    kind, dispatch_end_ns, dispatch_end_ns,
                    parent_id=dispatch_span_id, label=label)
        if stalls:
            self._bump("compile_stalled_dispatches")
            metrics.counter("serve.compile.stalled")
        self._bump("dispatches")
        members = len(live) + len(counts)
        self._bump("coalesced", members - 1)
        metrics.counter("serve.dispatch")
        if pipelined:
            self._bump("pipelined_windows")
            metrics.counter("serve.pipeline.windows")
        if members > 1:
            metrics.counter("serve.coalesced", members - 1)
        metrics.gauge("serve.queue.depth", float(len(self.queue)))
        struck: set = set()
        adopted: Optional[list] = None
        for r in live + counts:
            if r.future.cancelled():
                # cancelled between queue pop and execute: .exception()
                # would raise CancelledError and kill the dispatcher
                if r.trace is not None:
                    RECORDER.record(r.trace.finish(status="cancelled"))
                continue
            metrics.histogram("serve.latency").update(t1 - r.enqueued_at)
            # labeled series: per-kind/status and per-tenant request
            # counts export as proper Prometheus labels (one
            # serve_requests family), so dashboards slice without
            # name-mangled metric explosions
            status = "ok"
            exc = r.future.exception()
            if exc is not None:
                status = ("timeout" if isinstance(exc, QueryTimeout)
                          else "error")
                self._bump("failed")
                # poison-query accounting: a crash (permanent/OOM after
                # every recovery layer gave up) strikes the request's
                # coalescing fingerprint; shed/timeout/transient and
                # breaker-open rejections say nothing about the QUERY
                # being poisonous — they are load/dependency signals.
                # The OSError family is exempt even when classified
                # permanent (FileNotFoundError from a compaction race,
                # PermissionError): infrastructure answers, not kernel
                # crashes — a healthy hot query must not get itself
                # quarantined by three raced reads.
                if (self.config.quarantine_after
                        and not isinstance(exc, (QueryRejected,
                                                 QueryTimeout,
                                                 BreakerOpen,
                                                 OSError))
                        and classify(exc) != "transient"):
                    # ONE strike per crashing dispatch, not one per
                    # coalesced rider: N riders share the fingerprint
                    # by construction, and striking each would let a
                    # single crash of a >=quarantine_after batch
                    # quarantine the query immediately. Degraded
                    # requests strike their PRE-degrade fingerprint —
                    # the one admission checks.
                    key = (r.quarantine_key
                           if r.quarantine_key is not None
                           else _quarantine_key(r))
                    if key not in struck:
                        struck.add(key)
                        self.quarantine.strike(key)
            else:
                self._bump("completed")
                if r.approx:
                    self._bump("approx_served")
                    if r.sketch_rung and not r.degraded:
                        # rung request sketch-served on the DISPATCH
                        # path (cold sketch built there): degraded
                        # accounting lands with the serve, same as
                        # the admission-resolved case
                        r.degraded = True
                        self._bump("degraded")
                        metrics.counter("serve.degraded")
                metrics.counter(
                    "serve.tier",
                    tier="sketch" if r.approx else "exact")
            # SLO accounting distinguishes rejection from failure even
            # where the wire status does not: a pipelined window failed
            # by shutdown/drain fans QueryRejected out to its members
            # (status "error" on the wire), but rejections must never
            # burn the availability budget (telemetry/slo.py
            # BAD_STATUSES — shedding protects the budget)
            self._observe_slo(
                r, "rejected" if isinstance(exc, QueryRejected)
                else status, t1 - r.enqueued_at)
            metrics.counter("serve.requests", kind=r.kind, status=status)
            if r.tenant:
                metrics.counter("serve.tenant.requests", tenant=r.tenant)
                metrics.histogram(
                    "serve.tenant.latency",
                    tenant=r.tenant).update(t1 - r.enqueued_at)
            if r.trace is not None:
                if r is not lead and lead.trace is not None:
                    # riders adopt a copy of the shared dispatch-window
                    # spans (coalesce + dispatch subtree). Span ids are
                    # preserved so the gap report can dedup the shared
                    # window; the lead's own respond span stays out —
                    # riders record their own via the protocol callback
                    if adopted is None:
                        adopted = [
                            s for s in
                            lead.trace.snapshot_spans()[adopt_from:]
                            if s.name != "respond"]
                    r.trace.adopt(
                        adopted, clamp_start_ns=r.trace.root.start_ns)
                RECORDER.record(r.trace.finish(
                    status=status, batch=members, degraded=r.degraded,
                    approx=r.approx))
            if self.audit is not None:
                self.audit.write(ServeEvent(
                    trace_id=(r.trace.trace_id
                              if r.trace is not None else ""),
                    type_name=r.query.type_name,
                    kind=r.kind,
                    tenant=r.tenant,
                    priority=PRIORITIES[r.priority],
                    queue_ms=(t0 - r.enqueued_at) * 1000.0,
                    exec_ms=(t1 - t0) * 1000.0,
                    batch_size=members,
                    pipelined=pipelined,
                    status=status,
                    degraded=r.degraded,
                    compile_ms=compile_ms,
                    compiled=compiled,
                    retries=retries,
                    fault_injected=faults_seen,
                    breaker_state=breaker_state,
                    # riders share the window's route; the lead carries
                    # the authoritative launch attribution (fused
                    # counts too — they resolved from the same program)
                    mesh_shape=r.mesh_shape or lead.mesh_shape,
                    shards=r.shards or lead.shards,
                    approx=r.approx,
                    cache_hit=r.cache_hit,
                ))

    def _record_queries(self, live: List[ServeRequest],
                        counts: List[ServeRequest] = ()) -> None:
        """Record this dispatch's query shape into the warmup recorder.
        Members share a compat key, so one entry per dispatch; the kNN
        bucket is the PADDED stacked-query axis the batcher will build,
        which is what the kernel actually compiles for. Fused count
        riders record their own count entry — the warmup replay runs
        counts through the real planner, and a count that happened to
        fuse onto a kNN window live must still pre-compile its serial
        program (the fusion is opportunistic, not guaranteed)."""
        lead = live[0]
        try:
            from geomesa_tpu.cql import ast

            cql = ast.to_cql(lead.query.filter_ast)
        except Exception:
            return
        from geomesa_tpu.plan.hints import QueryHints

        # replay runs with default hints: only default-hint queries are
        # recorded faithfully. This guards ALL kinds — a degraded kNN
        # (loose_bbox/sampling rewritten by the ladder) or a hinted
        # aggregation would replay as a DIFFERENT program, pre-compiling
        # something serving never runs while the real one still compiles
        # inline
        if lead.query.hints != QueryHints():
            return
        if lead.kind == "knn":
            total = sum(len(np.asarray(r.qx).ravel()) for r in live)
            padded = max(MIN_KNN_BATCH, _next_pow2(max(total, 1)))
            self._recorder.record_query(
                "knn", lead.query.type_name, cql,
                q=padded, k=lead.k, impl=lead.impl)
        else:
            self._recorder.record_query(
                lead.kind, lead.query.type_name, cql)
        if counts:
            # fused riders share the lead's (type, CQL, hints) by
            # construction, and the fusion key pins default-compatible
            # hints — record the count shape they would run serially
            self._recorder.record_query(
                "count", lead.query.type_name, cql)

    # -- introspection -----------------------------------------------------

    def _bump(self, name: str, n: int = 1) -> None:
        with self._state_lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def stats(self) -> Dict[str, int]:
        with self._state_lock:
            out = dict(self._counters)
        out.setdefault("dispatches", 0)
        out.setdefault("coalesced", 0)
        # how each coalescing gather ended (QueryService._gather)
        out["coalesce"] = {e: out.pop("coalesce." + e, 0)
                           for e in COALESCE_ENDS}
        # dispatcher load: busy share over an interval is
        # 1 - Δloop_wait_ns / Δloop_ns
        out["loop_ns"] = self._loop_ns
        out["loop_wait_ns"] = self._loop_wait_ns
        out["queue_depth"] = len(self.queue)
        out["degrade_level"] = self.degrade_level()
        out["quarantine"] = self.quarantine.stats()
        # serving-tier shares (docs/SERVING.md "Approximate answers"):
        # sketch / cached / exact out of everything completed — the
        # numbers /debug/approx, `gmtpu top` and the fleet router's
        # stats probe read
        sketch = out.get("approx_served", 0)
        cached = out.get("cache_hits", 0)
        completed = out.get("completed", 0)
        out["approx"] = {
            "enabled": self.config.approx,
            "allowed_now": self._approx_ok(),
            "budget_exact": out.get("approx_budget_exact", 0),
            "tiers": {"sketch": sketch, "cached": cached,
                      "exact": max(completed - sketch - cached, 0)},
        }
        if self.result_cache is not None:
            out["cache"] = self.result_cache.stats()
        if self.metrics_port is not None:
            out["metrics_port"] = self.metrics_port
        subs = self.subscriptions  # racing close() may null the attr
        if subs is not None:
            out["subscriptions"] = subs.stats()
        with self._state_lock:
            mux = self._push_mux
        if mux is not None:
            out["wire"] = mux.stats()
        if self.pipeline is not None:
            out["pipeline"] = self.pipeline.stats()
        if self.tracker is not None:
            out["recompiles"] = self.tracker.total_recompiles()
        if self.mesh is not None:
            # topology for /debug/stats consumers (`gmtpu top`): the
            # launch-route counters live in the metrics snapshot; the
            # shape belongs to the service
            out["mesh"] = {
                "shape": list(int(s) for s in self.mesh.devices.shape),
                "devices": int(self.mesh.devices.size),
            }
        if self.slo is not None:
            out["slo"] = self.slo.report()
        return out

    def export_gauges(self) -> None:
        """Push point-in-time gauges (queue depth, degrade level,
        in-flight count, quarantine size, breaker states) into the
        shared metrics registry. The `--metrics-port` endpoint calls
        this before every /metrics render so a scrape sees NOW, not the
        last time a request happened to update a gauge — an idle,
        fully-drained server must scrape as idle."""
        from geomesa_tpu.utils.metrics import metrics

        metrics.gauge("serve.queue.depth", float(len(self.queue)))
        for cls, depth in self.queue.depths().items():
            metrics.gauge("serve.queue.class_depth", float(depth),
                          priority=cls)
        metrics.gauge("serve.degrade.level", float(self.degrade_level()))
        with self._state_lock:
            inflight = self._inflight
        metrics.gauge("serve.inflight", float(inflight))
        if self.slo is not None:
            self.slo.export_gauges()
        if self.pipeline is not None:
            p = self.pipeline.stats()
            metrics.gauge("serve.pipeline.inflight", float(p["inflight"]))
            metrics.gauge("serve.pipeline.max_inflight",
                          float(p["max_inflight"]))
            ring = p.get("ring")
            if ring is not None:
                metrics.gauge("serve.ring.programs",
                              float(ring["programs"]))
        if self.result_cache is not None:
            c = self.result_cache.stats()
            metrics.gauge("serve.cache.entries", float(c["entries"]))
        metrics.gauge("serve.approx.allowed",
                      1.0 if self._approx_ok() else 0.0)
        q = self.quarantine.stats()
        metrics.gauge("fault.quarantine.active", float(q["quarantined"]))
        metrics.gauge("fault.quarantine.striking", float(q["striking"]))
        try:
            from geomesa_tpu.faults import BREAKERS
            from geomesa_tpu.faults.breaker import _STATE_NUM

            for name, state in BREAKERS.states().items():
                metrics.gauge(f"fault.breaker.{name}", _STATE_NUM[state])
        # gt: waive GT14
        # (deliberate degrade: gauge freshness is best-effort — a scrape
        # must render whatever IS fresh rather than 500 because one
        # breaker-registry read raced a reconfigure)
        except Exception:
            pass


def self_check(verbose: bool = True) -> int:
    """`gmtpu serve --self-check`: an end-to-end smoke against a
    throwaway store — coalescing happens (fewer dispatches than
    requests), coalesced kNN results match serial execution, the bounded
    queue sheds with a typed QueryRejected, and latency histograms
    export. Returns 0 on pass, 1 on failure; runs in-process in a few
    seconds on CPU (used by the non-slow test suite)."""
    import tempfile

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore

    def say(msg):
        if verbose:
            print(f"serve self-check: {msg}")

    rng = np.random.default_rng(7)
    n = 512
    sft = SimpleFeatureType.from_spec(
        "selfcheck", "name:String,score:Double,dtg:Date,*geom:Point")
    batch = FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })
    cql = "BBOX(geom, -180, -90, 180, 90)"
    failures = 0
    with tempfile.TemporaryDirectory() as tmp:
        store = DataStore(tmp, use_device_cache=True)
        src = store.create_schema(sft)
        src.write(batch)

        qpts = rng.uniform(-60, 60, (8, 2))
        serial = [src.knn(cql, qpts[i:i + 1, 0], qpts[i:i + 1, 1], k=5)
                  for i in range(8)]

        svc = QueryService(store, ServeConfig(max_wait_ms=20.0),
                           autostart=False)
        futs = [svc.knn("selfcheck", cql, qpts[i:i + 1, 0],
                        qpts[i:i + 1, 1], k=5) for i in range(8)]
        cfuts = [svc.count("selfcheck", cql) for _ in range(3)]
        svc.start()
        results = [f.result(timeout=60) for f in futs]
        counts = [f.result(timeout=60) for f in cfuts]
        svc.close(drain=True)
        st = svc.stats()
        say(f"dispatches={st['dispatches']} for 11 requests "
            f"(coalesced {st['coalesced']})")
        if st["dispatches"] >= 11:
            say("FAIL: no coalescing happened")
            failures += 1
        for i, ((d, ix, _), (sd, six, _)) in enumerate(zip(results, serial)):
            if not (np.allclose(d, sd) and np.array_equal(ix, six)):
                say(f"FAIL: coalesced kNN result {i} != serial")
                failures += 1
        if len(set(counts)) != 1 or counts[0] != n:
            say(f"FAIL: coalesced counts wrong: {counts}")
            failures += 1

        svc2 = QueryService(store, ServeConfig(max_queue=2),
                            autostart=False)
        svc2.count("selfcheck", cql)
        svc2.count("selfcheck", "BBOX(geom, 0, 0, 10, 10)")
        try:
            svc2.count("selfcheck", "BBOX(geom, -10, -10, 0, 0)")
            say("FAIL: bounded queue did not shed")
            failures += 1
        except QueryRejected as e:
            say(f"bounded queue shed with reason={e.reason!r}")
            if e.reason != "queue_full":
                failures += 1
        svc2.start()
        svc2.close(drain=True)

        from geomesa_tpu.utils.metrics import metrics

        prom = metrics.to_prometheus()
        for needle in ("serve_latency_seconds_bucket",
                       "serve_latency_seconds_p99"):
            if needle not in prom:
                say(f"FAIL: {needle} missing from Prometheus export")
                failures += 1
    say("FAIL" if failures else "OK")
    return 1 if failures else 0
