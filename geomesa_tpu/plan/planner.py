"""The query planner and executor.

Parity: geomesa-index-api QueryPlanner / QueryRunner / LocalQueryRunner
[upstream, unverified], restructured for the TPU executor (SURVEY.md §3.1):

  1. normalize filter (parse), merge hints
  2. extract primary bounds (bbox + interval) — FilterHelper semantics
  3. prune partitions (the index-range analog) via the store's scheme
  4. scan pruned partitions with parquet row-group pushdown (covering)
  5. device residual evaluation: compiled predicate mask (the Z3Iterator +
     FilterTransformIterator analog, fused into one XLA program)
  6. aggregation push-down per hints (density / stats / bin) on device
  7. local post-processing: sort, max-features, projection (LocalQueryRunner)

Every phase is timed into the audit record; `explain` narrates the plan.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

from geomesa_tpu.core.columnar import DictColumn, FeatureBatch
from geomesa_tpu.cql import ast, compile_filter, extract_bbox, extract_intervals
from geomesa_tpu.cql.compile import CompiledFilter
from geomesa_tpu.cql.extract import BBox, Interval
from geomesa_tpu.plan.audit import AuditWriter, QueryEvent
from geomesa_tpu.plan.explain import Explainer
from geomesa_tpu.plan.hints import QueryHints
from geomesa_tpu.plan.query import Query
from geomesa_tpu.plan.runner import sample_mask as _sample_mask
from geomesa_tpu.telemetry.trace import TRACER, device_get
from geomesa_tpu.utils.padding import next_pow2 as _next_pow2
from geomesa_tpu.store.fs import FileSystemStorage


class QueryTimeout(TimeoutError):
    """Typed deadline expiry carrying the phase that blew the budget and
    the elapsed wall time. Subclasses TimeoutError so every existing
    caller that catches the bare type keeps working; the serve scheduler
    needs the distinction between deadline expiry, shed load
    (serve.scheduler.QueryRejected), and real errors."""

    def __init__(self, phase: str, elapsed_ms: float, timeout_ms: float):
        super().__init__(
            f"query exceeded timeout={timeout_ms:.0f}ms during {phase} "
            f"(elapsed {elapsed_ms:.0f}ms)"
        )
        self.phase = phase
        self.elapsed_ms = elapsed_ms
        self.timeout_ms = timeout_ms


@dataclasses.dataclass
class QueryPlan:
    query: Query
    filter: ast.Filter
    bbox: BBox
    interval: Interval
    partitions: List[str]
    total_partitions: int
    compiled: Optional[CompiledFilter]
    # plan-time manifest snapshot (partition -> entry list): execution
    # pins residency loads to the same committed write version the
    # pruning saw, so a concurrent batch-atomic write is all-or-nothing
    # for this query (None for storages without snapshot support)
    manifest: Optional[dict] = None


@dataclasses.dataclass
class QueryResult:
    kind: str  # features | density | stats | bin | arrow | count | topk_cells
    features: Optional[FeatureBatch] = None
    grid: Optional[np.ndarray] = None
    stats: object = None
    bin_bytes: Optional[bytes] = None
    arrow_bytes: Optional[bytes] = None
    count: int = 0
    # approximate-answer tier (docs/SERVING.md "Approximate answers"):
    # approx=True means this answer came from sketches and the exact
    # answer is GUARANTEED within +/- `bound` (count units / grid-cell
    # weight) at `confidence` (1.0: deterministic interval)
    approx: bool = False
    bound: float = 0.0
    confidence: float = 1.0
    # the manifest_snapshot() commit version this result was pinned to
    # (None for storages without versioning) — what makes the serve
    # result cache's invalidation exact-by-construction
    version: Optional[int] = None


class QueryPlanner:
    def __init__(
        self,
        storage: FileSystemStorage,
        audit: Optional[AuditWriter] = None,
        mesh=None,
        coord_dtype=None,
        cache=None,  # Optional[store.cache.DeviceCacheManager]
    ):
        self.storage = storage
        self.audit = audit
        self.mesh = mesh
        self.cache = cache
        # QueryInterceptor SPI: callables Query -> Query run before
        # planning; see plan/interceptor.py
        self.interceptors: List = []
        # one planner serves the dispatch thread AND direct callers
        # concurrently (serve makes that the normal mode); this guards
        # the lazily-built shared state: the compiled-filter cache, the
        # kNN capacity cache and the stats-manager singleton (GT12)
        self._mutex = threading.Lock()
        if coord_dtype is None:
            import jax.numpy as jnp

            from geomesa_tpu.utils.config import SystemProperties

            coord_dtype = (
                jnp.float64
                if SystemProperties.COORD_DTYPE.get() == "float64"
                else jnp.float32
            )
        self.coord_dtype = coord_dtype

    def _enable_compile_cache(self) -> None:
        """Library-level persistent compilation cache (compilecache/):
        idempotent and never-failing, so compiled predicate masks and
        kernels survive process restarts for every planner consumer.
        Called from the EXECUTION entry points, not the
        constructor — resolving the per-backend cache subdir initializes
        the jax backend (seconds on TPU), which metadata-only paths like
        `gmtpu explain` must never pay."""
        try:
            from geomesa_tpu.compilecache.persist import (
                enable_persistent_cache)

            enable_persistent_cache()
        except Exception:
            pass

    # -- planning ----------------------------------------------------------

    def plan(self, query: Query, explain: Optional[Explainer] = None) -> QueryPlan:
        # telemetry seam: planning (interceptors, bounds extraction,
        # pruning, residual filter compile closure) as one span — the
        # no-op path costs one attribute read for unscoped callers
        with TRACER.span("plan"):
            return self._plan(query, explain)

    def _plan(self, query: Query, explain: Optional[Explainer] = None) -> QueryPlan:
        from geomesa_tpu.plan.interceptor import run_interceptors

        e = explain or Explainer()
        query = run_interceptors(query, self.interceptors, e)
        sft = self.storage.sft
        f = query.filter_ast
        e.push(f"Planning '{query.type_name}' {ast.to_cql(f)}")
        g = sft.default_geometry
        d = sft.default_dtg
        bbox = extract_bbox(f, g.name) if g else BBox(-180, -90, 180, 90)
        interval = extract_intervals(f, d.name) if d else Interval(None, None)
        e(f"Primary bbox: ({bbox.xmin}, {bbox.ymin}, {bbox.xmax}, {bbox.ymax})")
        e(f"Primary interval: [{interval.start}, {interval.end}]")
        snapshot_fn = getattr(self.storage, "manifest_snapshot", None)
        manifest = snapshot_fn() if snapshot_fn is not None else None
        if manifest is not None:
            partitions = self.storage.prune_partitions(
                bbox, interval, manifest=manifest)
            total = len(manifest)
        else:
            partitions = self.storage.prune_partitions(bbox, interval)
            total = len(self.storage.partitions())
        e(f"Partitions: {len(partitions)} of {total} after pruning")
        est = self._stats_estimate(bbox, interval)
        if est is not None:
            e(f"Estimated matches (stats sketches): ~{est}")
        if query.hints.query_index:
            e(f"Index override requested: {query.hints.query_index!r} "
              "(single-strategy partition store; recorded only)")
        residual = f
        if query.hints.loose_bbox and g is not None:
            residual = _loosen_bbox(residual, g.name)
            e("Loose bbox: default-geometry BBOX predicates dropped from residual")
        compiled = None
        if not isinstance(residual, ast.Include):
            compiled = self._compile_cached(residual, sft)
            e(f"Residual predicate: compiled mask over "
              f"{len(compiled.builders)} param table(s)")
        else:
            e("Residual predicate: none (INCLUDE)")
        if query.hints.is_density:
            e(f"Aggregation: density {query.hints.density_width}x"
              f"{query.hints.density_height} over {query.hints.density_bbox}")
        elif query.hints.is_stats:
            e(f"Aggregation: stats {query.hints.stats_string!r}")
        elif query.hints.is_bin:
            e(f"Aggregation: bin track={query.hints.bin_track}")
        e.pop()
        return QueryPlan(query, f, bbox, interval, partitions, total,
                         compiled, manifest=manifest)

    def _compile_cached(self, residual: ast.Filter, sft) -> CompiledFilter:
        """Reuse CompiledFilter across queries keyed on canonical CQL: a
        fresh compile_filter per query would carry a fresh jax.jit wrapper,
        forcing an XLA recompile of the predicate kernel on EVERY query
        (~0.65s) even for textually identical repeat filters."""
        key = ast.to_cql(residual)
        with self._mutex:
            cached = getattr(self, "_compiled_filters", None)
            if cached is None:
                cached = self._compiled_filters = {}
            got = cached.get(key)
        if got is not None:
            return got
        # compile OUTSIDE the mutex: it costs ~0.65s and the lock also
        # serves _knn_caps / stats-manager lookups — holding it here
        # would stall every concurrent query behind one cache miss. Two
        # threads may compile the same filter once each; setdefault
        # keeps a single winner. (The inline compile-stall metering for
        # ServeEvent attribution lives in CompiledFilter._metered — the
        # XLA compile happens lazily at the first mask()/band() call,
        # not here: compile_filter only builds closures.)
        compiled = compile_filter(residual, sft)
        with self._mutex:
            if len(cached) > 256:  # bound memory on adversarial streams
                cached.clear()
            return cached.setdefault(key, compiled)

    def stats_manager(self):
        with self._mutex:
            if not hasattr(self, "_stats_mgr"):
                from geomesa_tpu.plan.stats_manager import StatsManager

                self._stats_mgr = StatsManager(self.storage)
            return self._stats_mgr

    def _stats_estimate(self, bbox: BBox, interval: Interval):
        """Sketch-based selectivity (StatsBasedEstimator analog); None when
        no stats exist (neither analyzed nor write-path updated)."""
        mgr = self.stats_manager()
        mgr.refresh()
        if not mgr.stats:
            return None
        return mgr.estimate_count(bbox, interval)

    def update_stats(self, batch) -> None:
        """Write-path stats hook (StatUpdater analog): called by
        FeatureSource.write after the storage append."""
        self.stats_manager().update(batch)

    # -- execution ---------------------------------------------------------

    def execute(
        self,
        query: Query,
        explain: Optional[Explainer] = None,
        timeout_ms: Optional[int] = None,
    ) -> QueryResult:
        """Plan and run one query. `timeout_ms` overrides the
        geomesa.query.timeout system property for THIS query — the serve
        scheduler propagates each request's remaining deadline budget here
        so the planner's cooperative checks enforce it (0 = no timeout).
        The deadline also scopes the dependency retry fabric (faults/):
        a storage/Kafka/device retry loop deep in the stack never sleeps
        past this request's remaining budget."""
        from geomesa_tpu.faults import deadline_scope
        from geomesa_tpu.utils.config import SystemProperties

        if timeout_ms is None:
            timeout_ms = int(SystemProperties.QUERY_TIMEOUT_MS.get())
        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms else None)
        with deadline_scope(deadline):
            return self._execute_deadlined(query, explain, timeout_ms)

    def _execute_deadlined(
        self,
        query: Query,
        explain: Optional[Explainer],
        timeout_ms: Optional[int],
    ) -> QueryResult:
        self._enable_compile_cache()
        t0 = time.perf_counter()

        def check_timeout(phase: str) -> None:
            elapsed_ms = (time.perf_counter() - t0) * 1000
            if timeout_ms and elapsed_ms > timeout_ms:
                raise QueryTimeout(phase, elapsed_ms, timeout_ms)

        from geomesa_tpu.utils.profiling import device_trace

        plan = self.plan(query, explain)
        # interceptors may have rewritten hints/projection/limits, not just
        # the filter — the rewritten query is authoritative from here on
        query = plan.query
        t_plan = time.perf_counter()
        check_timeout("planning")

        hints = query.hints
        # approximate-answer tier (docs/SERVING.md "Approximate
        # answers"): a tolerance hint routes count/density (and the
        # sketch-native topk_cells kind) through the sketch engine —
        # microseconds, no device work — IFF the a-priori bound fits;
        # every fallthrough (ineligible / bound_exceeded /
        # stale_sketch) is metered and pays the exact path below
        if hints.topk_cells or (hints.tolerance is not None
                                and (hints.count_only or hints.is_density)):
            result = None
            if hints.tolerance is not None:
                result = self.approx_engine().answer(plan, query)
            if result is None and hints.topk_cells:
                result = self._topk_exact(query, plan, timeout_ms)
            if result is not None:
                t_done = time.perf_counter()
                self._record(query, plan, hints, int(result.count),
                             t0, t_plan, t_plan, t_done)
                return result
        # HBM-resident path: per-partition cached device batches skip the
        # parquet scan entirely (sampling falls back: every-nth is defined
        # over the global match order, not per partition)
        # loose_bbox also falls back: the scan path re-applies the bbox
        # row-exactly via parquet pushdown, which cached whole partitions
        # cannot reproduce once the residual drops the BBOX predicate
        if self.cache is not None and not hints.sampling and not hints.loose_bbox:
            with device_trace("query"):
                result, mask_count, t_scan = self._execute_cached(plan, query)
            t_done = time.perf_counter()
            self._record(query, plan, hints, mask_count,
                         t0, t_plan, t_scan, t_done)
            return self._stamp_version(result, plan)

        with device_trace("query"):
            return self._stamp_version(
                self._execute_scan(
                    query, plan, hints, t0, t_plan, check_timeout
                ),
                plan,
            )

    @staticmethod
    def _stamp_version(result: QueryResult, plan: QueryPlan) -> QueryResult:
        """Pin the result to the plan's committed write version so the
        serve result cache keys it exactly (approx/cache.py)."""
        if result.version is None and plan.manifest is not None:
            result.version = getattr(plan.manifest, "version", None)
        return result

    def approx_engine(self):
        """The lazily-built sketch answer engine (one per planner, like
        the stats manager; geomesa_tpu.approx.engine)."""
        with self._mutex:
            if not hasattr(self, "_approx_engine"):
                from geomesa_tpu.approx.engine import SketchAnswerEngine

                self._approx_engine = SketchAnswerEngine(self)
            return self._approx_engine

    def _topk_exact(self, query: Query, plan: QueryPlan,
                    timeout_ms: Optional[int]) -> QueryResult:
        """Exact topk_cells fallback: one device density scan over the
        sketch-aligned world grid (the filter mask restricts it to
        matching rows), then an exact host top-k — same cell geometry
        as the sketch path, so the two tiers rank the same cells."""
        from geomesa_tpu.approx.sketches import DEFAULT_BINS

        eng = self.approx_engine()
        b = (eng.store.bins_per_dim if eng.store is not None
             else DEFAULT_BINS)
        k = int(query.hints.topk_cells)
        dq = dataclasses.replace(
            query,
            hints=dataclasses.replace(
                query.hints, topk_cells=None, tolerance=None,
                count_only=False, density_bbox=(-180.0, -90.0, 180.0, 90.0),
                density_width=b, density_height=b))
        r = self._execute_deadlined(dq, None, timeout_ms)
        cells: List[dict] = []
        if r.grid is not None:
            grid = np.asarray(r.grid)
            for rr, cc in zip(*np.nonzero(grid)):
                cells.append({
                    "row": int(rr), "col": int(cc),
                    "bbox": [-180.0 + cc * 360.0 / b,
                             -90.0 + rr * 180.0 / b,
                             -180.0 + (cc + 1) * 360.0 / b,
                             -90.0 + (rr + 1) * 180.0 / b],
                    "count": int(round(float(grid[rr, cc]))),
                    "bound": 0,
                })
            cells.sort(key=lambda d: (-d["count"], d["row"], d["col"]))
            cells = cells[:k]
        return QueryResult("topk_cells", stats=cells,
                           count=sum(c["count"] for c in cells),
                           version=r.version)

    def _execute_scan(self, query, plan, hints, t0, t_plan, check_timeout):
        import jax.numpy as jnp

        from geomesa_tpu.engine.device import to_device

        scan_iter = self.storage.scan(
            plan.bbox,
            plan.interval,
            columns=_needed_columns(query, plan, self.storage.sft),
        )
        # cold-path COUNT pipeline: decode the NEXT chunk on a host
        # thread while the device masks the current one (parquet decode ->
        # host -> device -> mask was fully serial in rounds 1-2 and lost
        # 0.39x to a plain pyarrow scan). Per-chunk counts accumulate as
        # device scalars; one sync at the end. Only the simple-count
        # shape streams — band refinement / visibility / sampling /
        # features need the materialized rows.
        can_stream_count = (
            hints.count_only and not hints.sampling
            and plan.compiled is not None
            and getattr(self.storage.sft, "user_data", {}).get(
                "geomesa.vis.attr") is None
        )
        if can_stream_count:
            from concurrent.futures import ThreadPoolExecutor

            # decode-ahead thread hides parquet time behind upload+mask;
            # decoded chunks ACCUMULATE to a large upload unit first —
            # each host->device transfer carries a fixed cost, and
            # per-SCAN_BATCH_SIZE uploads (16 of them at bench scale)
            # tripled the cold wall time
            UPLOAD_ROWS = 1 << 23
            counts = []
            corrections = [0]
            pending = []
            pending_rows = 0

            def flush():
                nonlocal pending, pending_rows
                if not pending:
                    return
                big = (pending[0] if len(pending) == 1
                       else FeatureBatch.concat(pending))
                padded = big.pad_to(_next_pow2(len(big)))
                dev = to_device(padded, coord_dtype=self.coord_dtype)
                m = plan.compiled.mask(dev, padded)
                counts.append(jnp.sum(m, dtype=jnp.int32))
                if plan.compiled.has_band:
                    # f64-exact counts (VERDICT r3 #5): correct this
                    # unit's count for f32 boundary rows — a small sync
                    # per ~8M-row unit, not a full-mask fetch
                    corrections[0] += plan.compiled.band_count_correction(
                        dev, padded, m)
                pending, pending_rows = [], 0

            # one span for the fused pipeline: decode-ahead + upload +
            # mask overlap by design, so finer phases would double-count
            with TRACER.span("scan", streaming=True):
                with ThreadPoolExecutor(max_workers=1) as ex:
                    fut = ex.submit(lambda: next(scan_iter, None))
                    while True:
                        chunk = fut.result()
                        if chunk is None:
                            break
                        fut = ex.submit(lambda: next(scan_iter, None))
                        # flush BEFORE overshooting: a unit that crosses
                        # the bound pow2-pads to DOUBLE the bytes on the
                        # wire
                        if pending_rows and \
                                pending_rows + len(chunk) > UPLOAD_ROWS:
                            flush()
                        pending.append(chunk)
                        pending_rows += len(chunk)
                        if pending_rows >= UPLOAD_ROWS:
                            flush()
                    flush()
            t_scan = time.perf_counter()
            check_timeout("scan")
            mask_count = int(sum(int(c) for c in device_get(counts))) \
                + corrections[0]
            t_done = time.perf_counter()
            self._record(query, plan, hints, mask_count,
                         t0, t_plan, t_scan, t_done)
            return QueryResult("count", count=mask_count)

        with TRACER.span("scan"):
            batches = list(scan_iter)
        t_scan = time.perf_counter()
        check_timeout("scan")

        result: QueryResult
        if not batches:
            result = self._empty_result(hints, query)
            mask_count = 0
        else:
            batch = FeatureBatch.concat(batches)
            # pow2 padding stabilizes jit cache shapes across scans
            padded = batch.pad_to(_next_pow2(len(batch)))
            dev = to_device(padded, coord_dtype=self.coord_dtype)
            with TRACER.span("kernel.dispatch", kernel="filter.mask"):
                dev_mask = (
                    plan.compiled.mask(dev, padded)
                    if plan.compiled is not None
                    else dev["__valid__"]
                )
            from geomesa_tpu.plan.runner import visibility_mask

            has_band = plan.compiled is not None and plan.compiled.has_band
            vm = visibility_mask(self.storage.sft, padded, hints)
            if hints.count_only and not hints.sampling:
                # device reduction: one scalar (plus a small band-row
                # correction for f32-boundary exactness) instead of a
                # full-mask fetch
                m = dev_mask
                if vm is not None:
                    m = m & jnp.asarray(vm)
                mask_count = int(device_get(jnp.sum(m, dtype=jnp.int64)))
                if has_band:
                    mask_count += plan.compiled.band_count_correction(
                        dev, padded, m,
                        extra=(jnp.asarray(vm) if vm is not None else None),
                    )
                t_done = time.perf_counter()
                self._record(query, plan, hints, mask_count,
                             t0, t_plan, t_scan, t_done)
                return QueryResult("count", count=mask_count)
            mask = device_get(dev_mask)
            if has_band:
                # f64 re-check of rows inside the f32 boundary band
                # (SURVEY.md:824-827); density paths keep the device mask —
                # grid quantization dwarfs the ~1e-7 deg band
                mask = plan.compiled.refine(mask, dev, padded)
            if vm is not None:
                # feature-level visibility: rows the auths cannot see are
                # invisible to counts and every aggregation
                mask = mask & vm
            if hints.sampling:
                groups = None
                if hints.sample_by:
                    col = padded.columns[hints.sample_by]
                    groups = (
                        np.asarray(col.codes)
                        if isinstance(col, DictColumn)
                        else np.asarray(col)
                    )
                mask = _sample_mask(mask, hints.sampling, groups)
            mask_count = int(mask.sum())
            with TRACER.span("aggregate"):
                result = self._aggregate(padded, dev, mask, query)
        t_done = time.perf_counter()
        self._record(query, plan, hints, mask_count, t0, t_plan, t_scan, t_done)
        return result

    def _record(self, query, plan, hints, mask_count, t0, t_plan, t_scan, t_done):
        """The planner's per-query record (a `query.audit` span): phase
        timers and the audit QueryEvent."""
        from geomesa_tpu.utils.metrics import metrics

        with TRACER.span("query.audit"):
            metrics.counter("query.count")
            metrics.counter("query.features.matched", mask_count)
            metrics.timer("query.plan").timer.update(t_plan - t0)
            metrics.timer("query.scan").timer.update(t_scan - t_plan)
            metrics.timer("query.compute").timer.update(t_done - t_scan)
            if self.audit is not None:
                self.audit.write(
                    QueryEvent(
                        type_name=query.type_name,
                        filter=ast.to_cql(plan.filter),
                        hints=str(hints),
                        plan_time_ms=(t_plan - t0) * 1000,
                        scan_time_ms=(t_scan - t_plan) * 1000,
                        compute_time_ms=(t_done - t_scan) * 1000,
                        result_count=mask_count,
                        partitions_scanned=len(plan.partitions),
                        partitions_total=plan.total_partitions,
                    )
                )

    def _lanes(self, sb, partitions):
        """Partition pruning over the superbatch: (allowed, lanes), with
        `allowed` the host bool [P] of the plan's resident partition ids
        and `lanes` the device bool [N] `allowed[sb.pids]`, computed by
        ONE `lane_mask` launch (a range compare against the runs of
        allowed ids, no gather). (None, None) when none is resident.
        Each launch adds its run count to `query.lane.runs`: a selection
        that fragments (a date + Z2 scheme, residency holes) shows there."""
        from geomesa_tpu.store.cache import lane_mask, lane_runs
        from geomesa_tpu.utils.metrics import metrics

        with TRACER.span("kernel.dispatch", kernel="lane.mask") as span:
            allowed = np.zeros(max(len(sb.ids), 1), bool)
            for name in partitions:
                i = sb.ids.get(name)
                if i is not None:
                    allowed[i] = True
            runs, r = lane_runs(allowed)
            span.set(runs=r)
            if not r:
                return None, None
            metrics.counter("query.lane.masks")
            metrics.counter("query.lane.runs", r)
            return allowed, lane_mask(sb.pids, runs)

    def _execute_cached(self, plan: QueryPlan, query: Query):
        """HBM-resident execution over the cache's SUPERBATCH: one dense
        kernel over every resident row, with partition pruning applied as a
        lane mask (`_lanes`, computed once per request) instead of
        per-partition dispatches.
        Returns (result, mask_count, t_scan); "scan time" here is the
        cache-ensure (load of any non-resident partition).

        Why dense-over-everything: a per-partition loop costs one kernel
        launch each (and one device round trip each if fetched
        naively); a single memory-bound pass over
        all resident rows is ~2ms per 4M rows. Partition pruning still
        limits what gets LOADED into HBM; once resident, lanes are cheaper
        than launches."""
        import jax.numpy as jnp

        hints = query.hints
        with TRACER.span("residency"):
            self.cache.ensure(plan.partitions, manifest=plan.manifest)
        t_scan = time.perf_counter()

        sb = self.cache.superbatch()
        if sb is None:
            return self._empty_result(hints, query), 0, t_scan
        _, lanes = self._lanes(sb, plan.partitions)
        if lanes is None:
            return self._empty_result(hints, query), 0, t_scan

        with TRACER.span("kernel.dispatch", kernel="filter.mask"):
            dev_mask = (
                plan.compiled.mask(sb.dev, sb.batch)
                if plan.compiled is not None
                else sb.dev["__valid__"]
            )
            dev_mask = dev_mask & lanes
        has_band = plan.compiled is not None and plan.compiled.has_band
        from geomesa_tpu.plan.runner import visibility_mask

        vm = visibility_mask(self.storage.sft, sb.batch, hints)
        if vm is not None:
            dev_mask = dev_mask & jnp.asarray(vm)

        if hints.count_only and not hints.sampling:
            with TRACER.span("kernel.dispatch", kernel="mask.sum"):
                total = jnp.sum(dev_mask, dtype=jnp.int64)
            total = int(device_get(total))
            if has_band:
                extra = lanes if vm is None else lanes & jnp.asarray(vm)
                total += plan.compiled.band_count_correction(
                    sb.dev, sb.batch, dev_mask, extra=extra)
            return QueryResult("count", count=total), total, t_scan

        if hints.is_density:
            from geomesa_tpu.plan.runner import (
                density_device_grid, query_mask_token)

            # partition pruning feeds the mask too: extend the token so a
            # plan scanning different partitions never reuses the calib
            token = query_mask_token(query) + (tuple(sorted(plan.partitions)),)
            grid = density_device_grid(
                self.storage.sft, sb.batch, sb.dev, dev_mask, hints,
                mask_token=token, mesh=getattr(sb, "mesh", None),
            )
            total = int(device_get(jnp.sum(dev_mask, dtype=jnp.int32)))
            if total == 0:
                return self._empty_result(hints, query), 0, t_scan
            return (
                QueryResult("density", grid=device_get(grid), count=total),
                total,
                t_scan,
            )

        # host-mask paths (stats/bin/features): one transfer, then the same
        # single-batch aggregation the scan path uses
        mask = device_get(dev_mask)
        if has_band:
            # refine patches band rows with the pure-filter f64 value, so
            # re-AND the partition-allowed + visibility components it
            # cannot know about
            # non-inplace: refine returns the caller's (possibly read-
            # only numpy-view) mask unchanged when no rows are flagged
            mask = plan.compiled.refine(mask, sb.dev, sb.batch)
            mask = mask & device_get(lanes)
            if vm is not None:
                mask = mask & vm
        total = int(mask.sum())
        if total == 0:
            return self._empty_result(hints, query), 0, t_scan
        with TRACER.span("aggregate"):
            result = self._aggregate(sb.batch, sb.dev, mask, query)
        return result, total, t_scan

    def _knn_mask_setup(self, plan, query):
        """Residency/scan + f64-exact filter mask for one kNN dispatch —
        the shared prelude of `_knn_launch` (per window) and `ring_arm`
        (once per armed ring program). Returns (sb, batch, dev, mask,
        is_empty); `sb` is None on the uncached scan path and `is_empty`
        short-circuits the caller's empty-result contract. The mask here
        is final: band corrections are scattered in (f64-exact at the
        f32 boundary) and visibility is folded, which is what lets both
        the fused count reduction and the ring tier's frozen-mask
        contract hold on every route."""
        import jax.numpy as jnp

        from geomesa_tpu.engine.device import to_device
        from geomesa_tpu.plan.runner import visibility_mask
        from geomesa_tpu.utils.metrics import note_device_op

        sb = None
        if self.cache is not None:
            with TRACER.span("residency"):
                self.cache.ensure(plan.partitions, manifest=plan.manifest)
                sb = self.cache.superbatch()
            if sb is None:
                return None, None, None, None, True
            allowed, lanes = self._lanes(sb, plan.partitions)
            if lanes is None:
                return None, None, None, None, True
            batch, dev = sb.batch, sb.dev
            with TRACER.span("kernel.dispatch", kernel="filter.mask"):
                mask = (
                    plan.compiled.mask(dev, batch)
                    if plan.compiled is not None
                    else dev["__valid__"]
                )
                mask = mask & lanes
            note_device_op()
            if plan.compiled is not None and plan.compiled.has_band:
                # f64 band refinement, device-resident: exact values
                # scatter into the mask at their indices, ANDed with the
                # partition component gathered at just those rows (the
                # old fetch-patch-reupload refine plus the full
                # np.asarray(sb.pids) fetch moved ~3n bytes between host
                # and device per query)
                bidx, bexact = plan.compiled.band_corrections(dev, batch)
                if len(bidx):
                    pid_at = device_get(sb.pids[jnp.asarray(bidx)])
                    note_device_op()
                    # row validity must survive the scatter here exactly
                    # as on the scan branch and in knn_scan: without it
                    # an invalid superbatch row inside the f32 band is
                    # resurrected with its f64 filter value
                    if batch.valid is not None:
                        bexact = bexact & batch.valid[bidx]
                    mask = mask.at[jnp.asarray(bidx)].set(
                        jnp.asarray(bexact & allowed[pid_at]))
        else:
            with TRACER.span("scan"):
                batches = list(
                    self.storage.scan(
                        plan.bbox, plan.interval,
                        columns=_needed_columns(
                            query, plan, self.storage.sft),
                    )
                )
            if not batches:
                return None, None, None, None, True
            batch = FeatureBatch.concat(batches)
            batch = batch.pad_to(_next_pow2(len(batch)))
            dev = to_device(batch, coord_dtype=self.coord_dtype)
            with TRACER.span("kernel.dispatch", kernel="filter.mask"):
                mask = (
                    plan.compiled.mask(dev, batch)
                    if plan.compiled is not None
                    else dev["__valid__"]
                )
                mask = mask & dev["__valid__"]
            note_device_op()
            if plan.compiled is not None and plan.compiled.has_band:
                bidx, bexact = plan.compiled.band_corrections(dev, batch)
                if len(bidx):
                    if batch.valid is not None:
                        bexact = bexact & batch.valid[bidx]
                    mask = mask.at[jnp.asarray(bidx)].set(
                        jnp.asarray(bexact))
        vm = visibility_mask(self.storage.sft, batch, query.hints)
        if vm is not None:
            mask = mask & jnp.asarray(vm)
        return sb, batch, dev, mask, False

    def knn(
        self,
        query: "Query | str",
        qx,
        qy,
        k: int = 10,
        impl: str = "sparse",
        timeout_ms: Optional[int] = None,
    ):
        """Deadline-scoped wrapper over `_knn` (same contract as
        `execute`: the request budget bounds boundary retries too)."""
        from geomesa_tpu.faults import deadline_scope

        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms else None)
        with deadline_scope(deadline):
            return self._knn(query, qx, qy, k=k, impl=impl,
                             timeout_ms=timeout_ms)

    def knn_launch(
        self,
        query: "Query | str",
        qx,
        qy,
        k: int = 10,
        impl: str = "sparse",
        timeout_ms: Optional[int] = None,
        staged=None,
        want_mask_count: bool = False,
        donate: bool = False,
    ) -> "KnnLaunch":
        """Async half of `knn`: plan → prune → mask → kernel DISPATCH,
        returning a `KnnLaunch` handle without reading any result back.
        JAX dispatch is asynchronous, so the kernel executes while the
        caller overlaps the next window's host prep and transfer — the
        serve pipeline's entry point (docs/SERVING.md "Pipelined
        dispatch"). `launch.sync()` completes the contract with the same
        single combined transfer (and overflow fallback) the serial
        path pays, so `knn_launch(...).sync() == knn(...)` bit-for-bit.

        `staged`: pre-staged device (qx, qy) from the pipeline's
        transfer stage (engine.device.QueryStager); `qx`/`qy` must still
        be the HOST copies — the OOM ladder re-stages from them.
        `want_mask_count`: also launch a count reduction over the final
        filter mask (the cross-kind count+kNN fusion); available after
        sync as `launch.mask_count` when `launch.fused_ok`. The mask at
        reduction time is f64-exact — band corrections are scattered in
        and visibility is folded — so the fusion holds for banded and
        band-free filters alike (parity-asserted in
        tests/test_pipeline.py); `fused_ok` stays in the contract so a
        future gate can decline, and callers must handle False by
        dispatching the count serially.
        `donate`: route the kernel through the ExecutableRegistry's
        serve donation tier so the staged query buffers are donated to
        XLA (no-op on backends without donation support, i.e. CPU)."""
        from geomesa_tpu.faults import deadline_scope

        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms else None)
        with deadline_scope(deadline):
            launch = self._knn_launch(
                query, qx, qy, k=k, impl=impl, timeout_ms=timeout_ms,
                staged=staged, want_mask_count=want_mask_count,
                donate=donate)
        launch.deadline = deadline
        return launch

    def _knn(
        self,
        query: "Query | str",
        qx,
        qy,
        k: int = 10,
        impl: str = "sparse",
        timeout_ms: Optional[int] = None,
    ):
        """Serial kNN = launch + sync back to back (the launch/sync
        seam exists for the serve pipeline; composing it here keeps the
        two paths byte-identical by construction)."""
        return self._knn_launch(
            query, qx, qy, k=k, impl=impl, timeout_ms=timeout_ms).sync()

    def _knn_launch(
        self,
        query: "Query | str",
        qx,
        qy,
        k: int = 10,
        impl: str = "sparse",
        timeout_ms: Optional[int] = None,
        staged=None,
        want_mask_count: bool = False,
        donate: bool = False,
    ) -> "KnnLaunch":
        """KNN aggregation push-down over the store scan (SURVEY.md §3.4
        KNN process stack): plan → prune → device predicate mask → fused
        Pallas scan over match-bearing tiles only (engine.knn_scan — the
        kernel the north-star bench runs), with the documented
        overflow→fullscan fallback. No host materialization of candidates:
        on the cached (HBM-resident) path the mask and scan touch only
        device arrays. Returns (dists [Q,k] meters np, indices [Q,k] np
        into `batch` rows, batch) — feature-level visibility folds into
        the mask, so unauthorized rows can never be anyone's neighbor.

        impl: "sparse" | "fullscan" | "auto". Tile capacities are
        calibrated from the live mask once per (filter, k) and cached
        across queries (planner-stats analog); an overflow drops the
        cached value. "auto" (round 5, VERDICT task 6) resolves from the
        write-path stats sketches — the StrategyDecider cost idea
        (SURVEY.md:213-214) applied to kernel choice: an estimated
        selectivity near 1 means nearly every data tile bears a match,
        so the sparse scan's gather adds cost over the dense pass for
        nothing — route straight to fullscan with NO calibration fetch
        or overflow round trip. No stats -> sparse (its own overflow
        fallback keeps that safe)."""
        import jax.numpy as jnp

        from geomesa_tpu.engine.knn_scan import (
            capacity_bucket, count_match_tiles, default_interpret,
            knn_fullscan_tiled, knn_sparse_launch)
        from geomesa_tpu.utils.metrics import metrics, note_device_op

        if isinstance(query, str):
            query = Query(self.storage.sft.name, query)
        self._enable_compile_cache()
        t0 = time.perf_counter()

        def check_timeout(phase: str) -> None:
            # same cooperative deadline contract as execute(): the serve
            # scheduler propagates each request's remaining budget here
            elapsed_ms = (time.perf_counter() - t0) * 1000
            if timeout_ms and elapsed_ms > timeout_ms:
                raise QueryTimeout(phase, elapsed_ms, timeout_ms)

        plan = self.plan(query)
        check_timeout("planning")
        query = plan.query
        g = self.storage.sft.default_geometry
        if g is None or g.type != "Point":
            raise ValueError("planner.knn requires a point default geometry")

        def empty():
            # a real empty batch, not None: callers select() against the
            # returned features (legacy window path guaranteed the same).
            # Returned as an already-synced launch so the serial and
            # pipelined paths share one early-out shape (fused count 0).
            sft = self.storage.sft
            return KnnLaunch.ready(
                self,
                (
                    np.full((len(qx), k), np.inf),
                    np.zeros((len(qx), k), np.int32),
                    FeatureBatch.from_pydict(
                        sft, {a.name: [] for a in sft.attributes}
                    ),
                ),
                fused=want_mask_count,
            )

        sb, batch, dev, mask, is_empty = self._knn_mask_setup(plan, query)
        if is_empty:
            return empty()
        check_timeout("scan")

        x = dev[f"{g.name}__x"]
        y = dev[f"{g.name}__y"]
        kk = min(k, x.shape[0])
        mb = max(64, kk)
        interp = default_interpret()
        if sb is not None and getattr(sb, "mesh", None) is not None:
            # mesh-resident serving route (docs/SERVING.md "Sharded
            # serving"): the coalesced window executes as ONE sharded
            # program across the mesh — or, when every allowed
            # partition's rows live on a single chip (shard affinity),
            # as a single-device kernel on that chip
            return self._knn_launch_mesh(
                plan, sb, qx, qy, k, kk, mb, interp, mask, batch,
                staged=staged, want_mask_count=want_mask_count)
        if staged is not None:
            # pipeline transfer stage already put the (padded, f32)
            # query arrays on device — the values are identical to the
            # serial conversion below (QueryStager casts the same way)
            jqx, jqy = staged
        else:
            jqx = jnp.asarray(np.asarray(qx), jnp.float32)
            jqy = jnp.asarray(np.asarray(qy), jnp.float32)
        count_dev = None
        if want_mask_count:
            # cross-kind fusion: a count against the same (type, CQL,
            # hints) is ONE reduction over the mask this launch already
            # computed — it rides the kernel's result transfer instead
            # of paying its own dispatch RTT. The mask at this point is
            # f64-exact: the band-correction scatter above patched every
            # f32-boundary row with its exact value (the same correction
            # the count paths apply via band_count_correction), and
            # visibility is folded in — parity with planner.count is
            # asserted in tests/test_pipeline.py for banded and
            # band-free filters alike.
            count_dev = jnp.sum(mask, dtype=jnp.int64)
        launch = KnnLaunch(self, k=k, kk=kk, impl=impl, batch=batch,
                           count_dev=count_dev, hq=_host_q(qx, qy))
        if impl == "auto":
            impl = launch.impl = self._knn_impl_from_stats(plan)
        if impl == "sparse":
            # capacity reuse hits on REPEATED identical queries (the
            # steady-state server shape); radius-growth loops re-key per
            # bbox and simply recalibrate — a stale cap is never wrong,
            # only overflow-fallback slow or dead-program wasteful
            key = (ast.to_cql(plan.filter), kk)
            seed_cap = self._caps_seed(key)
            with TRACER.span("kernel.dispatch", kernel="knn_sparse",
                             q=int(jqx.shape[0]), k=kk):
                if seed_cap is None:
                    # calibration: the one (small, scalar) sync a cold
                    # (filter, k) pays at launch; repeats hit the cache
                    seed_cap = capacity_bucket(int(device_get(
                        count_match_tiles(mask))))
                if donate:
                    fd, fi, ov = self._knn_serve_kernel(
                        "knn_scan.knn_sparse_scan", (0, 1),
                        jqx, jqy, x, y, mask,
                        k=kk, tile_capacity=seed_cap, m_blocks=mb,
                        interpret=interp)
                    # the staged jqx/jqy were DONATED to the kernel —
                    # the overflow fallback must never re-read them, so
                    # the handle keeps host copies instead (same f32
                    # values; knn_fullscan converts on entry)
                    fb_qx = np.asarray(qx, np.float32)
                    fb_qy = np.asarray(qy, np.float32)
                    metrics.counter("knn.tiles.inline")
                else:
                    fd, fi, ov, seed_cap = knn_sparse_launch(
                        jqx, jqy, x, y, mask, k=kk,
                        tile_capacity=seed_cap, m_blocks=mb,
                        interpret=interp,
                    )
                    fb_qx, fb_qy = jqx, jqy
            note_device_op()
            launch.arm_sparse(fd, fi, ov, fb_qx, fb_qy, x, y, mask,
                              cap=seed_cap, caps_key=key, mb=mb,
                              interp=interp)
        else:
            with TRACER.span("kernel.dispatch", kernel="knn_fullscan",
                             q=int(jqx.shape[0]), k=kk):
                if donate:
                    fd, fi = self._knn_serve_kernel(
                        "knn_scan.knn_fullscan_tiled", (0, 1),
                        jqx, jqy, x, y, mask,
                        k=kk, m_blocks=mb, interpret=interp)
                else:
                    fd, fi = knn_fullscan_tiled(
                        jqx, jqy, x, y, mask, k=kk, m_blocks=mb,
                        interpret=interp,
                    )
            note_device_op()
            launch.arm_dense(fd, fi)
        return launch

    def _knn_serve_kernel(self, name: str, donate_argnums, *args,
                          **statics):
        """Dispatch a kNN kernel through the ExecutableRegistry's serve
        donation tier (registry.serve_variant): the staged query buffers
        (argnums 0, 1) are serve-owned — nothing re-reads them after the
        launch and the host copies stay on the requests for the OOM
        re-staging fallback — so XLA may reuse their HBM across windows.
        The AOT handle also means a warm serve process never traces
        here. Donation itself is ignored (with a JAX warning) on
        backends without support (CPU); the pipeline gates on backend
        before asking for it."""
        import importlib

        from geomesa_tpu.compilecache.registry import registry

        tail, attr = name.rsplit(".", 1)
        fn = getattr(importlib.import_module(
            f"geomesa_tpu.engine.{tail}"), attr)
        vname = registry.serve_variant(
            name, donate_argnums=donate_argnums, fn=fn,
            static_argnames=tuple(statics))
        handle = registry.compile(vname, *args, **statics)
        return handle.call(*args)

    def _caps_seed(self, key):
        """Lazily create the sparse-capacity cache and return the
        cached seed for `key` (None = cold, calibrate). One policy for
        every dispatch route (serial / whole-mesh / shard-affinity):
        a miss against an oversized cache clears it, bounding memory
        on adversarial query streams — a dropped cap is never wrong,
        only recalibration-slow. Write-back stays with the launches'
        sync paths (same `_mutex`)."""
        with self._mutex:
            caps = getattr(self, "_knn_caps", None)
            if caps is None:
                caps = self._knn_caps = {}
            if key not in caps and len(caps) > 256:
                caps.clear()
            return caps.get(key)

    def _knn_launch_mesh(self, plan, sb, qx, qy, k, kk, mb, interp,
                         mask, batch, staged=None,
                         want_mask_count: bool = False) -> "KnnLaunch":
        """Mesh dispatch seam: one pjit/shard_map program across every
        chip of the superbatch's mesh — per-shard `knn_sparse_scan`,
        all_gather top-k merge, psum'd fused count — AOT-managed under
        a mesh-keyed ExecutableRegistry entry `(kernel, bucket, dtype,
        mesh_shape)` so a warm sharded process compiles nothing.
        Results are bit-identical to the single-chip path: the mesh
        superbatch keeps the serial row layout (store/cache.py), the
        per-pair f32 haversine is the same arithmetic, and the merged
        top-k is the same ascending k-smallest set.

        Shard affinity: when every allowed partition's rows live on ONE
        chip, the window skips the collective program entirely and runs
        the serial sparse kernel against that chip's resident rows —
        the query lands where its tiles live."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from geomesa_tpu.compilecache.registry import registry
        from geomesa_tpu.engine.knn_scan import (
            capacity_bucket, make_knn_fullscan_sharded,
            make_knn_serve_sharded, shard_match_tiles)
        from geomesa_tpu.parallel.mesh import SHARD_AXIS
        from geomesa_tpu.utils.metrics import metrics

        mesh = sb.mesh
        d = int(mesh.devices.size)
        mesh_shape = tuple(int(s) for s in mesh.devices.shape)
        shards = sb.shards_for(plan.partitions)
        if len(shards) == 1:
            return self._knn_launch_local(
                plan, sb, qx, qy, k, kk, mb, interp, mask, batch,
                shards[0], staged=staged,
                want_mask_count=want_mask_count)
        g = self.storage.sft.default_geometry
        x = sb.dev[f"{g.name}__x"]
        y = sb.dev[f"{g.name}__y"]
        rep = NamedSharding(mesh, P())
        row = NamedSharding(mesh, P(SHARD_AXIS))
        if staged is not None:
            # re-pin like the mask below: a no-op when the pipeline
            # staged onto THIS mesh (the normal case), and the guard
            # that keeps a window straddling a set_mesh() from feeding
            # a stale placement to the mesh executable
            jqx = jax.device_put(staged[0], rep)
            jqy = jax.device_put(staged[1], rep)
        else:
            jqx = jax.device_put(
                jnp.asarray(np.asarray(qx), jnp.float32), rep)
            jqy = jax.device_put(
                jnp.asarray(np.asarray(qy), jnp.float32), rep)
        # the mask came out of SPMD elementwise/scatter ops — re-pin the
        # row sharding so the AOT executable's parameter layout always
        # matches (a no-op when XLA already kept it sharded)
        mask = jax.device_put(mask, row)
        key = (ast.to_cql(plan.filter), kk, ("mesh",) + mesh_shape)
        seed_cap = self._caps_seed(key)
        shard_list = ",".join(map(str, shards))
        with TRACER.span("kernel.dispatch", kernel="knn_mesh",
                         q=int(jqx.shape[0]), k=kk, mesh=d,
                         shards=shard_list):
            if seed_cap is None:
                # calibration: MAX per-shard match tiles — one scalar
                # sync on a cold (filter, k, mesh) key, cached after
                # gt: waive GT31
                # (the read is a static compile parameter, not data)
                seed_cap = capacity_bucket(int(device_get(
                    shard_match_tiles(mask, d))))
            vname = registry.mesh_variant(
                "knn_scan.knn_serve_sharded", mesh,
                fn=make_knn_serve_sharded(mesh),
                static_argnames=("k", "tile_capacity", "m_blocks",
                                 "want_count", "interpret"))
            handle = registry.compile(
                vname, jqx, jqy, x, y, mask, k=kk,
                tile_capacity=seed_cap, m_blocks=mb,
                want_count=want_mask_count, interpret=interp)
            out = handle.call(jqx, jqy, x, y, mask)
        fd, fi, ov = out[0], out[1], out[2]
        count_dev = out[3] if want_mask_count else None
        metrics.counter("knn.mesh.dispatches")
        metrics.counter("knn.tiles.inline")
        from geomesa_tpu.utils.metrics import note_device_op

        note_device_op()
        launch = KnnLaunch(self, k=k, kk=kk, impl="mesh", batch=batch,
                           count_dev=count_dev, hq=_host_q(qx, qy))
        launch.mesh_shape = mesh_shape
        launch.shards = shards

        def dense_fallback():
            # overflow contract: the dense sharded fullscan — same
            # per-pair arithmetic and merge as the serial fallback
            dname = registry.mesh_variant(
                "knn_scan.knn_fullscan_sharded", mesh,
                fn=make_knn_fullscan_sharded(mesh),
                static_argnames=("k", "m_blocks", "interpret"))
            h = registry.compile(dname, jqx, jqy, x, y, mask, k=kk,
                                 m_blocks=mb, interpret=interp)
            return h.call(jqx, jqy, x, y, mask)

        launch.arm_mesh(fd, fi, ov, dense_fallback, cap=seed_cap,
                        caps_key=key)
        return launch

    def _knn_launch_local(self, plan, sb, qx, qy, k, kk, mb, interp,
                          mask, batch, shard: int, staged=None,
                          want_mask_count: bool = False) -> "KnnLaunch":
        """Shard-affinity route: all allowed partitions' rows live on
        `shard`, so the window runs the SERIAL sparse kernel against
        that chip's device-local rows — no collectives, and different
        windows occupy different chips. Global indices are
        `local + shard * shard_rows`, which under the mesh layout
        contract equals the serial index bit-for-bit. The fused count
        reduces the local mask: every allowed row lives here, so the
        local sum IS the global sum."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.engine.knn_scan import (
            capacity_bucket, count_match_tiles, knn_sparse_launch)
        from geomesa_tpu.parallel.mesh import shard_view
        from geomesa_tpu.utils.metrics import metrics

        mesh = sb.mesh
        S = sb.shard_rows
        dev_s = mesh.devices.flat[shard]
        g = self.storage.sft.default_geometry
        lx = shard_view(sb.dev[f"{g.name}__x"], shard, S, device=dev_s)
        ly = shard_view(sb.dev[f"{g.name}__y"], shard, S, device=dev_s)
        lm = shard_view(mask, shard, S, device=dev_s)
        if staged is not None:
            # staged pairs are mesh-replicated: take the owning chip's
            # replica (whole array — shard 0 of the query axis)
            sqx, sqy = staged
            jqx = shard_view(sqx, 0, int(sqx.shape[0]), device=dev_s)
            jqy = shard_view(sqy, 0, int(sqy.shape[0]), device=dev_s)
        else:
            jqx = jax.device_put(
                jnp.asarray(np.asarray(qx), jnp.float32), dev_s)
            jqy = jax.device_put(
                jnp.asarray(np.asarray(qy), jnp.float32), dev_s)
        count_dev = None
        if want_mask_count:
            count_dev = jnp.sum(lm, dtype=jnp.int64)
        launch = KnnLaunch(self, k=k, kk=kk, impl="sparse", batch=batch,
                           count_dev=count_dev, hq=_host_q(qx, qy))
        launch.mesh_shape = tuple(int(s) for s in mesh.devices.shape)
        launch.shards = (shard,)
        launch.idx_offset = shard * S
        key = (ast.to_cql(plan.filter), kk, ("shard", shard))
        seed_cap = self._caps_seed(key)
        metrics.counter("knn.mesh.local_dispatches")
        with TRACER.span("kernel.dispatch", kernel="knn_sparse",
                         q=int(jqx.shape[0]), k=kk,
                         shards=str(shard)):
            if seed_cap is None:
                seed_cap = capacity_bucket(int(device_get(
                    count_match_tiles(lm))))
            fd, fi, ov, seed_cap = knn_sparse_launch(
                jqx, jqy, lx, ly, lm, k=kk, tile_capacity=seed_cap,
                m_blocks=mb, interpret=interp)
        from geomesa_tpu.utils.metrics import note_device_op

        note_device_op()
        launch.arm_sparse(fd, fi, ov, jqx, jqy, lx, ly, lm,
                          cap=seed_cap, caps_key=key, mb=mb,
                          interp=interp)
        return launch

    def ring_arm(self, query: "Query | str", q_padded: int, k: int = 10,
                 impl: str = "sparse", donate: bool = False,
                 depth: int = 4) -> "RingProgram":
        """Arm ONE persistent serve program for a (type, canonical CQL,
        hints, k, impl, Q-bucket[, mesh_shape]) window class
        (docs/SERVING.md "Persistent serve loop"): plan → residency →
        the f64-exact filter mask → capacity calibration → AOT handle
        under the registry's ring tier, all exactly ONCE. Per window the
        ring loop then pays a slot write + one executable invocation +
        the completer's harvest read — none of the per-window plan/
        residency/mask work the pipelined route repeats.

        Raises RingIneligible (typed — the caller keeps the PR-7
        pipelined route) when the window class cannot hold the frozen
        contract: configured interceptors (must run per request),
        storage without committed manifest versioning (staleness would
        be undetectable), no device cache / no resident superbatch
        (nothing to pre-bind), a non-point geometry, or a mesh window
        whose tiles live on a single shard (the shard-affinity route is
        already one cheap local dispatch and keeps per-chip
        attribution exact)."""
        from geomesa_tpu.engine.knn_scan import (
            capacity_bucket, count_match_tiles, default_interpret,
            shard_match_tiles)

        import jax.numpy as jnp

        if isinstance(query, str):
            query = Query(self.storage.sft.name, query)
        if self.interceptors:
            raise RingIneligible("interceptors")
        mv_fn = getattr(self.storage, "manifest_version", None)
        if mv_fn is None:
            raise RingIneligible("no_version")
        if self.cache is None:
            raise RingIneligible("no_device_cache")
        self._enable_compile_cache()
        plan = self.plan(query)
        query = plan.query
        g = self.storage.sft.default_geometry
        if g is None or g.type != "Point":
            raise RingIneligible("non_point")
        sb, batch, dev, mask, is_empty = self._knn_mask_setup(plan, query)
        if is_empty or sb is None:
            # nothing resident/matching: the empty window is already
            # one cheap early-out on the pipelined route (with a cache
            # present, sb None only ever co-occurs with is_empty)
            raise RingIneligible("empty")
        x = dev[f"{g.name}__x"]
        y = dev[f"{g.name}__y"]
        kk = min(k, x.shape[0])
        mb = max(64, kk)
        interp = default_interpret()
        if impl == "auto":
            impl = self._knn_impl_from_stats(plan)
        prog = RingProgram(self, plan, sb, batch, k=k, kk=kk, impl=impl,
                           mb=mb, interp=interp, depth=depth,
                           mversion=int(mv_fn()))
        # the fused-count rider precompute: the mask is FROZEN for this
        # program's lifetime (version-checked per window), so the
        # cross-kind count is one arm-time reduction, not a per-window
        # device op — the one deliberate host sync the arm pays
        prog.mask_count = int(device_get(jnp.sum(mask, dtype=jnp.int64)))
        import jax

        from geomesa_tpu.compilecache.registry import registry

        qabs = jax.ShapeDtypeStruct((int(q_padded),), jnp.float32)
        if getattr(sb, "mesh", None) is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from geomesa_tpu.engine.knn_scan import (
                make_knn_fullscan_sharded, make_knn_serve_sharded)
            from geomesa_tpu.parallel.mesh import SHARD_AXIS

            mesh = sb.mesh
            shards = sb.shards_for(plan.partitions)
            if len(shards) <= 1:
                raise RingIneligible("shard_affinity")
            prog.route = "mesh"
            prog.mesh_shape = tuple(int(s) for s in mesh.devices.shape)
            prog.shards = shards
            prog.placement = NamedSharding(mesh, P())
            # pre-pin the frozen mask to the row sharding ONCE (the
            # per-window re-pin the mesh route pays today)
            prog.mask = jax.device_put(
                mask, NamedSharding(mesh, P(SHARD_AXIS)))
            prog.x, prog.y = x, y
            d = int(mesh.devices.size)
            prog.caps_key = (ast.to_cql(plan.filter), kk,
                             ("mesh",) + prog.mesh_shape)
            # same capacity policy as every other route (_caps_seed
            # creates the cache; sync's write-back shares it): reuse a
            # warm seed, calibrate once otherwise
            cap = self._caps_seed(prog.caps_key)
            if cap is None:
                # gt: waive GT31
                # (the read is a static compile parameter, not data)
                cap = capacity_bucket(int(device_get(
                    shard_match_tiles(mask, d))))
            prog.cap = cap
            base = registry.mesh_variant(
                "knn_scan.knn_serve_sharded", mesh,
                fn=make_knn_serve_sharded(mesh),
                static_argnames=("k", "tile_capacity", "m_blocks",
                                 "want_count", "interpret"))
            # mesh ring entries never donate: the overflow fallback
            # re-reads the staged pair, and the collective program's
            # replicated inputs are not serve-owned per chip
            vname = registry.ring_variant(
                base, depth, fn=make_knn_serve_sharded(mesh),
                static_argnames=("k", "tile_capacity", "m_blocks",
                                 "want_count", "interpret"))
            prog.handle = registry.compile(
                vname, qabs, qabs, x, y, prog.mask, k=kk,
                tile_capacity=cap, m_blocks=mb, want_count=False,
                interpret=interp)
            prog.dense_fn = make_knn_fullscan_sharded(mesh)
            prog.mesh = mesh
        else:
            from geomesa_tpu.engine.knn_scan import (
                knn_ring_fullscan, knn_ring_scan, knn_tile_summary)

            prog.x, prog.y, prog.mask = x, y, mask
            donate_argnums = (0, 1) if donate else ()
            if impl == "sparse":
                prog.route = "sparse"
                prog.caps_key = (ast.to_cql(plan.filter), kk)
                cap = self._caps_seed(prog.caps_key)
                if cap is None:
                    # gt: waive GT31
                    # (the read is a static compile parameter, not data)
                    cap = capacity_bucket(int(device_get(
                        count_match_tiles(mask))))
                prog.cap = cap
                # the tile list is a function of the frozen mask: build
                # it once here, not in every window. The f32 mask takes
                # the bool mask's place (the overflow fallback reads it)
                (prog.mask, prog.tile_ids, prog.n_sel,
                 prog.overflow) = knn_tile_summary(mask, tile_capacity=cap)
                vname = registry.ring_variant(
                    "knn_scan.knn_ring_scan", depth, fn=knn_ring_scan,
                    donate_argnums=donate_argnums,
                    static_argnames=("k", "m_blocks", "interpret"))
                prog.handle = registry.compile(
                    vname, qabs, qabs, x, y, *prog.tiles(), k=kk,
                    m_blocks=mb, interpret=interp)
            else:
                prog.route = "fullscan"
                vname = registry.ring_variant(
                    "knn_scan.knn_ring_fullscan", depth,
                    fn=knn_ring_fullscan,
                    donate_argnums=donate_argnums,
                    static_argnames=("k", "m_blocks", "interpret"))
                prog.handle = registry.compile(
                    vname, qabs, qabs, x, y, mask, k=kk, m_blocks=mb,
                    interpret=interp)
        from geomesa_tpu.utils.metrics import metrics

        metrics.counter("serve.ring.armed")
        return prog

    def _knn_impl_from_stats(self, plan: "QueryPlan") -> str:
        """Stats-typed sparse-vs-fullscan decision (VERDICT r4 task 6).

        estimated_selectivity = sketch estimate of matches in the plan's
        bbox+interval over the store count. Above KNN_FULLSCAN_SELECTIVITY
        (default 0.5) the sparse scan cannot prune meaningfully — nearly
        every tile bears a match — so the dense scan wins and no
        calibration fetch or overflow round trip is spent discovering
        that. The Z3 sketch is an UPPER bound, so a high estimate only
        ever forfeits pruning the sparse path might still have had, never
        correctness.

        Two cases must stay sparse regardless of the estimate (review
        findings): (a) no spatial sketch exists — estimate_count's
        fallback is the bbox-blind store count, which would misroute
        every query on sketch-less stores; (b) the filter carries
        attribute predicates the sketches cannot see — 'world bbox AND
        v < tiny' has near-zero true selectivity even though its bbox
        estimate is the whole store, and sparse is the safe default (its
        overflow fallback IS the fullscan)."""
        from geomesa_tpu.utils.config import SystemProperties

        total = getattr(self.storage, "count", 0) or 0
        if total <= 0:
            return "sparse"
        mgr = self.stats_manager()
        mgr.refresh()
        if "z3" not in mgr.stats and "z2" not in mgr.stats:
            return "sparse"
        if self._has_attribute_predicates(plan.filter):
            return "sparse"
        est = mgr.estimate_count(plan.bbox, plan.interval)
        if est is None:
            return "sparse"
        thresh = float(SystemProperties.KNN_FULLSCAN_SELECTIVITY.get())
        return "fullscan" if est >= thresh * total else "sparse"

    def _has_attribute_predicates(self, f) -> bool:
        """True if the filter references anything the spatial/temporal
        sketches cannot estimate: comparisons, IN/LIKE/BETWEEN/IsNull on
        attributes, or spatial/temporal predicates on NON-default columns
        (secondary geometries/dtgs are outside the sketch too)."""
        sft = self.storage.sft
        g = sft.default_geometry
        d = sft.default_dtg
        gname = g.name if g is not None else None
        dname = d.name if d is not None else None
        for node in ast.walk(f):
            if isinstance(node, (ast.SpatialPredicate,
                                 ast.DistancePredicate)):
                if node.prop.name != gname:
                    return True
            elif isinstance(node, ast.TemporalPredicate):
                if node.prop.name != dname:
                    return True
            elif isinstance(node, ast.Comparison):
                # dtg range comparisons are sketch-visible; anything else
                # is an attribute predicate
                names = [e.name for e in (node.left, node.right)
                         if isinstance(e, ast.Property)]
                if any(nm != dname for nm in names):
                    return True
            elif isinstance(node, (ast.Between, ast.Like, ast.In,
                                   ast.IsNull)):
                return True
        return False

    def count(self, query: Query, timeout_ms: Optional[int] = None) -> int:
        """EXACT_COUNT path; with exact_count=False and INCLUDE, serve the
        manifest count (the stats-estimate analog). geomesa.force.count
        makes every count exact regardless of hints. `timeout_ms`
        propagates a serve-layer deadline into the nested execute.
        A sketch-served answer (tolerance hint, docs/SERVING.md
        "Approximate answers") returns as an `ApproxCount` — an int
        subclass carrying `.bound`/`.confidence`, so every existing
        consumer keeps working."""
        r = self.count_result(query, timeout_ms=timeout_ms)
        n = int(r.count)
        if r.approx:
            from geomesa_tpu.approx.engine import ApproxCount

            return ApproxCount(n, int(r.bound), r.confidence)
        return n

    def approx_count_result(self, query: Query) -> Optional[QueryResult]:
        """Admission-time sketch peek (serve/service.py): the
        microsecond count path ONLY — returns None on any fallthrough
        so the caller queues the request for the exact dispatch path.
        Types with configured interceptors decline here (the fast path
        must not run a non-idempotent chain the queued path would run
        again); they still reach the sketch tier via count_result."""
        if query.hints.tolerance is None:
            return None
        if self.interceptors and not query.intercepted:
            return None
        # build=False: a cold/stale partition must not run a parquet
        # rescan on the SUBMIT thread — the queued dispatch path
        # builds (metered) where exact scans already run
        return self.approx_engine().fast_count(query, build=False)

    def count_result(self, query: Query,
                     timeout_ms: Optional[int] = None) -> QueryResult:
        """`count` with provenance: a fresh QueryResult(kind="count")
        carrying the committed manifest version the answer was pinned
        to (the serve result cache's key — approx/cache.py) and any
        approx bound. The serve batcher calls this; `count()` derives
        the plain/ApproxCount int from it."""
        from geomesa_tpu.utils.config import SystemProperties

        from geomesa_tpu.plan.interceptor import run_interceptors

        # the estimate shortcut must see the POST-interceptor query, or a
        # rewrite/guard configured on the type is bypassed for counts; the
        # intercepted marker makes the nested execute() -> plan() pass a
        # no-op, so non-idempotent interceptors apply exactly once
        query = run_interceptors(query, self.interceptors)
        if query.hints.distinct is not None:
            self._validate_distinct(query.hints.distinct)
        if (
            not query.hints.exact_count
            and not SystemProperties.FORCE_COUNT.get()
            and isinstance(query.filter_ast, ast.Include)
            # a manifest row count is NOT a distinct-value count
            and query.hints.distinct is None
            # a manifest count knows nothing about auths: visibility-
            # configured types must count through the masked path
            and not (self.storage.sft.user_data or {}).get("geomesa.vis.attr")
        ):
            snap_fn = getattr(self.storage, "manifest_snapshot", None)
            if snap_fn is not None:
                # one snapshot pins count AND version atomically
                snap = snap_fn()
                n = sum(int(e["count"]) for files in snap.values()
                        for e in files)
                version = getattr(snap, "version", None)
            else:
                n = self.storage.count
                version = None
            if query.max_features is not None:
                n = min(n, query.max_features)
            return QueryResult("count", count=n, version=version)
        if query.hints.tolerance is not None:
            # the microsecond path: memoized sketch merge, no planner
            # pipeline — falls through metered when the bound does not
            # fit or a partition's sketch is stale (approx/engine.py)
            r = self.approx_engine().fast_count(query)
            if r is not None:
                return r
        if query.hints.distinct is not None:
            # the sketch attempt above fell through (or no tolerance
            # was offered): distinct counts pay an exact feature scan
            # plus a host-side unique over the named column
            return self._distinct_exact(query, timeout_ms=timeout_ms)
        # tolerance stripped: fast_count above WAS the sketch attempt —
        # leaving the hint on would re-enter the engine inside execute()
        # (a second full merge and a double-counted fallthrough reason)
        counting = dataclasses.replace(
            query, hints=dataclasses.replace(
                query.hints, count_only=True, tolerance=None)
        )
        r = self.execute(counting, timeout_ms=timeout_ms)
        if r.kind == "features":
            n = len(r.features) if r.features is not None else 0
        else:
            n = r.count
        # GeoTools getCount honors the query limit (the features path caps
        # via finish_features; the count_only short-circuit must match)
        if query.max_features is not None:
            n = min(n, query.max_features)
        return QueryResult("count", count=n, version=r.version,
                           approx=r.approx, bound=r.bound,
                           confidence=r.confidence)

    def _validate_distinct(self, attr: str) -> None:
        """A bad `distinct` hint is the CLIENT's error and must answer
        the request typed — not surface as a KeyError from a scan."""
        from geomesa_tpu.core.sft import GEOMETRY_TYPES

        sft = self.storage.sft
        if attr not in sft:
            raise ValueError(
                f"distinct attribute {attr!r} not in schema "
                f"{sft.name!r}")
        if sft.attribute(attr).type in GEOMETRY_TYPES:
            raise ValueError(
                f"distinct over geometry attribute {attr!r} is not "
                f"supported")

    def _distinct_exact(self, query: Query,
                        timeout_ms: Optional[int] = None) -> QueryResult:
        """Exact COUNT(DISTINCT attr): execute the query as features and
        unique-count the named column on the host. The fallback behind
        the HLL tier (approx/engine.py fast_distinct) — predicated,
        visibility-masked and interceptor-rewritten queries all land
        here, because the row set execute() returns is already the
        exact one."""
        attr = query.hints.distinct
        q = dataclasses.replace(
            query, hints=dataclasses.replace(
                query.hints, tolerance=None, distinct=None,
                count_only=False))
        r = self.execute(q, timeout_ms=timeout_ms)
        feats = r.features
        n = 0
        if feats is not None and len(feats):
            import numpy as np

            from geomesa_tpu.core.columnar import DictColumn

            col = feats.columns[attr]
            if isinstance(col, DictColumn):
                vals = np.asarray(col.decode(), dtype=object)
                vals = vals[vals != None]  # noqa: E711 — elementwise
                n = len(np.unique(vals.astype(str)))
            else:
                n = len(np.unique(np.asarray(col)))
        return QueryResult("count", count=n, version=r.version)

    # -- internals ---------------------------------------------------------

    def _empty_result(
        self, hints: QueryHints, query: Optional[Query] = None
    ) -> QueryResult:
        if hints.is_density:
            import numpy as np

            return QueryResult(
                "density",
                grid=np.zeros((hints.density_height, hints.density_width), np.float32),
            )
        if hints.is_stats:
            from geomesa_tpu.stats import parse_stats

            return QueryResult("stats", stats=parse_stats(hints.stats_string))
        # same hint precedence as runner.aggregate (arrow before bin): the
        # result KIND of a query must not depend on whether it matched rows
        if hints.is_arrow:
            from geomesa_tpu.core.arrow_io import to_ipc_bytes, to_sorted_ipc_bytes
            from geomesa_tpu.plan.runner import apply_fid_policy, finish_features

            sft = self.storage.sft
            # the fid policy + projection make the empty stream's schema
            # identical to non-empty results (client-side shard merges
            # reject mismatched schemas) — sort metadata included, so an
            # all-empty shard still participates in a delta merge
            empty = FeatureBatch.from_pydict(
                sft, {a.name: [] for a in sft.attributes}
            )
            if query is not None:
                empty = finish_features(empty, query)
            empty = apply_fid_policy(empty, hints.arrow_include_fid)
            if hints.arrow_sort_field:
                payload = to_sorted_ipc_bytes(
                    empty, hints.arrow_sort_field, hints.arrow_sort_reverse
                )
            else:
                payload = to_ipc_bytes(empty)
            return QueryResult("arrow", arrow_bytes=payload)
        if hints.is_bin:
            return QueryResult("bin", bin_bytes=b"")
        return QueryResult("features", features=None, count=0)

    def _aggregate(self, batch, dev, mask: np.ndarray, query: Query) -> QueryResult:
        from geomesa_tpu.plan.runner import aggregate

        # the execute paths fold the visibility mask before calling here
        return aggregate(
            self.storage.sft, batch, dev, mask, query, fold_visibility=False
        )

    def _run_stats(self, batch, dev, mask: np.ndarray, expression: str):
        from geomesa_tpu.plan.runner import run_stats

        return run_stats(batch, dev, mask, expression)


def _pad_to_k(dists: np.ndarray, idx: np.ndarray, k: int):
    """Pad a [Q, kk<=k] kNN result to k columns (inf distance, index 0) —
    shared by the planner and process result paths."""
    if dists.shape[1] < k:
        pad = k - dists.shape[1]
        dists = np.pad(dists, ((0, 0), (0, pad)), constant_values=np.inf)
        idx = np.pad(idx, ((0, 0), (0, pad)))
    return dists, idx


def _host_q(qx, qy):
    """Host f64 copies of the window's query points, kept on the launch
    for sync's canonical meter recompute."""
    return (np.asarray(qx, np.float64).ravel(),
            np.asarray(qy, np.float64).ravel())


def _canonical_dists(dists, idx, batch, hq):
    """Canonical final meters (docs/SERVING.md "Sharded serving"): the
    device kernels RANK — their f32 refine picks the neighbor set and
    order — and the reported distances are recomputed here in f64 and
    rounded ONCE to the result dtype. XLA fuses the in-kernel haversine
    differently per compiled program (single-chip jit, the shard_map
    mesh program, different [Q] buckets), so kernel-reported meters can
    drift in final ulps across routes for the SAME neighbor pair. One
    host recompute from one formula (`haversine_m_np`, the test
    oracle's distance) makes every dispatch route — serial, pipelined,
    shard-affinity, whole-mesh — report identical bits whenever the
    neighbor sets agree, which is what makes sharded serving
    bit-identical to the single-chip path (tests/test_mesh_serve.py)."""
    if hq is None or dists.size == 0:
        return dists
    fin = np.isfinite(dists)
    if not fin.any():
        return dists
    from geomesa_tpu.engine.geodesy import haversine_m_np

    g = batch.sft.default_geometry
    col = batch.columns[g.name]
    cx = np.asarray(col.x, np.float64)
    cy = np.asarray(col.y, np.float64)
    qx, qy = hq
    ii = np.clip(idx, 0, len(cx) - 1)
    d64 = haversine_m_np(qx[:, None], qy[:, None], cx[ii], cy[ii])
    return np.where(fin, d64, dists).astype(dists.dtype, copy=False)


class KnnLaunch:
    """One dispatched-but-unsynced kNN window (planner.knn_launch).

    The launch did: plan → residency/scan → filter mask → kernel
    dispatch, all ASYNC from the device's point of view — holding this
    object means device work may still be running. `sync()` performs the
    single combined device read (results + sparse-overflow flag + any
    fused count scalar, ONE transfer — the knn_sparse_auto discipline),
    runs the documented overflow→fullscan fallback, writes the planner's
    capacity cache back, and returns exactly what `planner.knn` returns.
    The serial path IS launch+sync back to back, so the pipelined and
    serial results are bit-identical by construction (regression-tested
    in tests/test_pipeline.py).

    After a fused-count sync, `mask_count` holds the host int (the
    count+kNN cross-kind fusion); `fused_ok` says whether the launch
    accepted the fusion request (it declines under f32 band
    refinement)."""

    __slots__ = ("planner", "k", "kk", "impl", "batch", "deadline",
                 "mask_count", "fused_ok", "_ready", "_fd", "_fi", "_ov",
                 "_cap", "_caps_key", "_jqx", "_jqy", "_x", "_y",
                 "_mask", "_mb", "_interp", "_count_dev", "_dense",
                 "_hq", "idx_offset", "mesh_shape", "shards", "ring")

    def __init__(self, planner, k, kk, impl, batch, count_dev=None,
                 hq=None):
        self.planner = planner
        self.k = k
        self.kk = kk
        self.impl = impl
        self.batch = batch
        self.deadline = None
        self.mask_count = None
        self.fused_ok = count_dev is not None
        self._count_dev = count_dev
        self._ready = None
        self._fd = self._fi = self._ov = None
        self._jqx = self._jqy = self._x = self._y = self._mask = None
        self._cap = self._caps_key = None
        self._mb = self._interp = None
        self._dense = None          # mesh overflow fallback (callable)
        self._hq = hq               # host (qx, qy) f64 — sync's meters
        # mesh attribution (docs/SERVING.md "Sharded serving"): the
        # device topology the window ran on and which shards owned its
        # tiles — ServeEvent.mesh_shape/shards carry these
        self.idx_offset = 0         # shard-affinity global-index base
        self.mesh_shape: tuple = ()
        self.shards: tuple = ()
        # ring-route marker (docs/SERVING.md "Persistent serve loop"):
        # sync stamps its span so the gap report's ring-mode
        # attribution can separate harvest reads from pipeline syncs
        self.ring = False

    @classmethod
    def ready(cls, planner, result, fused: bool = False) -> "KnnLaunch":
        """An already-resolved launch (the empty-store early-out): sync
        returns `result` immediately; a fused count resolves to 0."""
        launch = cls(planner, k=0, kk=0, impl="none", batch=result[2])
        launch._ready = result
        launch.fused_ok = fused
        launch.mask_count = 0 if fused else None
        return launch

    def arm_sparse(self, fd, fi, ov, jqx, jqy, x, y, mask, cap,
                   caps_key, mb, interp) -> None:
        self._fd, self._fi, self._ov = fd, fi, ov
        self._jqx, self._jqy, self._x, self._y = jqx, jqy, x, y
        self._mask = mask
        self._cap, self._caps_key = cap, caps_key
        self._mb, self._interp = mb, interp

    def arm_dense(self, fd, fi) -> None:
        self._fd, self._fi = fd, fi

    def arm_mesh(self, fd, fi, ov, dense_fallback, cap, caps_key) -> None:
        """Arm a mesh-program launch: device-resident merged results +
        the ANY-shard overflow flag; `dense_fallback` dispatches the
        sharded fullscan when sync observes the overflow."""
        self._fd, self._fi, self._ov = fd, fi, ov
        self._dense = dense_fallback
        self._cap, self._caps_key = cap, caps_key

    def sync(self):
        """Block until the window's device work is done and return
        (dists [Q,k] np, idx [Q,k] np, batch). Runs under the request's
        deadline scope when `knn_launch` installed one, so the overflow
        fallback's boundary retries stay budget-bounded."""
        if self.deadline is None:
            return self._sync()
        from geomesa_tpu.faults import deadline_scope

        with deadline_scope(self.deadline):
            return self._sync()

    def _sync(self):
        if self._ready is not None:
            return self._ready
        import functools

        from geomesa_tpu.engine.knn_scan import knn_sparse_finish

        extra = (self._count_dev,) if self._count_dev is not None else ()
        from geomesa_tpu.utils.metrics import note_device_op

        note_device_op()
        attrs = {"shards": ",".join(map(str, self.shards))
                 if self.shards else ""}
        if self.ring:
            attrs["ring"] = True
        # every read below is a `device.sync` span of its own (the
        # read alone); the host work after them is `knn.canonical`
        get = functools.partial(device_get, **attrs)
        if self._dense is not None:
            # mesh program: ONE combined read (results + any-shard
            # overflow + fused count); overflow routes to the sharded
            # fullscan, mirroring the serial contract
            got = get((self._fd, self._fi, self._ov) + extra)
            fd, fi, ov = got[0], got[1], got[2]
            extra_host = tuple(got[3:])
            cap = self._cap
            if bool(np.asarray(ov)):
                fd, fi = get(self._dense())
                cap = -1
        elif self._ov is not None:
            fd, fi, cap, extra_host = knn_sparse_finish(
                self._fd, self._fi, self._ov,
                self._jqx, self._jqy, self._x, self._y, self._mask,
                k=self.kk, tile_capacity=self._cap, m_blocks=self._mb,
                interpret=self._interp, extra=extra, get=get)
        else:
            got = get((self._fd, self._fi) + extra)
            fd, fi, extra_host = got[0], got[1], tuple(got[2:])
            cap = None
        with TRACER.span("knn.canonical"):
            if cap is not None:
                with self.planner._mutex:
                    caps = self.planner._knn_caps
                    if cap > 0:
                        caps[self._caps_key] = cap
                    else:
                        caps.pop(self._caps_key, None)
            fi = np.asarray(fi)
            if self.idx_offset:
                # shard-affinity route: local row ids -> global (the
                # mesh layout keeps serial indices, so this restores
                # bit-identity with the single-chip path)
                fi = fi + np.int32(self.idx_offset)
            dists, idx = _pad_to_k(np.asarray(fd), fi, self.k)
            dists = _canonical_dists(dists, idx, self.batch, self._hq)
        if extra_host:
            self.mask_count = int(extra_host[0])
        # drop the device refs promptly: the pipeline may hold the
        # launch object past completion for bookkeeping, and these
        # buffers are the window's HBM footprint
        self._fd = self._fi = self._ov = self._count_dev = None
        self._jqx = self._jqy = self._x = self._y = self._mask = None
        self._dense = None
        self._ready = (dists, idx, self.batch)
        return self._ready


class RingIneligible(RuntimeError):
    """Typed refusal: this window class cannot take the persistent ring
    route (docs/SERVING.md "Persistent serve loop"). Carries the
    metered reason; the serve loop falls back to the PR-7 pipelined
    dispatch — slower per window, never wrong."""

    def __init__(self, reason: str):
        super().__init__(f"ring-ineligible: {reason}")
        self.reason = reason


class RingProgram:
    """One armed persistent serve program (planner.ring_arm).

    Everything a window would otherwise recompute per dispatch is
    frozen here: the plan's partitions, the resident superbatch, the
    f64-exact filter mask (band corrections + visibility folded; on the
    sparse route its `knn_tile_summary`: the f32 mask, the tile list,
    its count and the overflow flag), the calibrated sparse capacity,
    the fused-count scalar, and the AOT executable under the registry
    ring tier. `launch()` is the whole per-window device interaction:
    ONE executable invocation over the pre-bound feature buffers plus
    the staged slot pair. `fresh()` is the per-window staleness gate —
    a lock-peek plus an int compare, never residency work — and a False
    answer sends the window back to the pipelined route, whose
    plan/ensure pass rebuilds residency and lets the ring loop re-arm
    against the new version.

    Bit-identity holds by construction: the kernel, mask, capacity and
    merge are exactly the serial route's, the staged slot carries the
    same host-f64→f32 cast, and sync runs the same overflow ladder and
    `_canonical_dists` f64 recompute every other route runs."""

    __slots__ = ("planner", "plan", "sb", "batch", "k", "kk", "impl",
                 "mb", "interp", "depth", "mversion", "mask_count",
                 "route", "handle", "x", "y", "mask", "tile_ids", "n_sel",
                 "overflow", "cap", "caps_key", "placement", "mesh",
                 "mesh_shape", "shards", "dense_fn")

    def __init__(self, planner, plan, sb, batch, k, kk, impl, mb,
                 interp, depth, mversion):
        self.planner = planner
        self.plan = plan
        self.sb = sb
        self.batch = batch
        self.k = k
        self.kk = kk
        self.impl = impl
        self.mb = mb
        self.interp = interp
        self.depth = depth
        self.mversion = mversion
        self.mask_count = 0
        self.route = "sparse"
        self.handle = None
        self.x = self.y = self.mask = None
        self.tile_ids = self.n_sel = self.overflow = None
        self.cap = None
        self.caps_key = None
        self.placement = None        # staging placement (mesh: replicated)
        self.mesh = None
        self.mesh_shape: tuple = ()
        self.shards: tuple = ()
        self.dense_fn = None         # mesh overflow program builder

    def tiles(self) -> tuple:
        """The sparse route's frozen scan inputs after x and y:
        (maskf, tile_ids, n_sel, overflow)."""
        return self.mask, self.tile_ids, self.n_sel, self.overflow

    def fresh(self) -> bool:
        """Cheap per-window staleness gate: the superbatch reference
        must still be the cache's CURRENT one (a residency change mints
        a new object) and the storage commit version must be the armed
        one (a write that has not re-tiered residency yet must still
        route to the pipelined path, whose plan/ensure applies it)."""
        cache = self.planner.cache
        if cache is None or cache.superbatch_peek() is not self.sb:
            return False
        try:
            return int(self.planner.storage.manifest_version()) \
                == self.mversion
        except Exception:
            return False

    def launch(self, staged, qx, qy, timeout_ms: Optional[int] = None,
               want_mask_count: bool = False) -> "KnnLaunch":
        """Per-window ring dispatch: one AOT executable invocation on
        the pre-bound buffers + the staged slot. Returns a KnnLaunch
        whose sync is byte-identical to the serial route's (same
        overflow ladder, same `_canonical_dists`). The fused count
        resolves from the arm-time scalar — zero per-window device
        work for count riders."""
        from geomesa_tpu.utils.metrics import metrics, note_device_op

        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms else None)
        jqx, jqy = staged
        launch = KnnLaunch(self.planner, k=self.k, kk=self.kk,
                           impl=self.impl, batch=self.batch,
                           hq=_host_q(qx, qy))
        launch.ring = True
        launch.deadline = deadline
        if want_mask_count:
            launch.fused_ok = True
            launch.mask_count = self.mask_count
        shard_list = ",".join(map(str, self.shards)) \
            if self.shards else ""
        with TRACER.span("kernel.dispatch", kernel="knn_ring",
                         q=int(jqx.shape[0]), k=self.kk,
                         shards=shard_list):
            if self.route == "mesh":
                fd, fi, ov = self.handle.call(
                    jqx, jqy, self.x, self.y, self.mask)
                launch.mesh_shape = self.mesh_shape
                launch.shards = self.shards
                launch.arm_mesh(fd, fi, ov, self._dense_fallback(jqx, jqy),
                                cap=self.cap, caps_key=self.caps_key)
                metrics.counter("knn.mesh.dispatches")
                metrics.counter("knn.tiles.inline")
            elif self.route == "fullscan":
                fd, fi = self.handle.call(
                    jqx, jqy, self.x, self.y, self.mask)
                launch.arm_dense(fd, fi)
            else:
                fd, fi, ov = self.handle.call(
                    jqx, jqy, self.x, self.y, *self.tiles())
                metrics.counter("knn.tiles.armed")
                # the staged slot may be DONATED to the program — the
                # overflow fallback must never re-read it, so the
                # handle keeps host f32 copies (same values; the
                # fullscan converts on entry). Overflow is structurally
                # unreachable here (the capacity was calibrated from
                # THIS frozen mask), but the ladder stays armed.
                launch.arm_sparse(
                    fd, fi, ov,
                    np.asarray(qx, np.float32), np.asarray(qy, np.float32),
                    self.x, self.y, self.mask,
                    cap=self.cap, caps_key=self.caps_key, mb=self.mb,
                    interp=self.interp)
        note_device_op()
        metrics.counter("serve.ring.windows")
        return launch

    def _dense_fallback(self, jqx, jqy):
        """Mesh overflow contract, armed lazily: compiled only if a
        window ever observes the (structurally unreachable) overflow
        flag — the cold path must not tax every arm."""
        def run():
            from geomesa_tpu.compilecache.registry import registry

            dname = registry.mesh_variant(
                "knn_scan.knn_fullscan_sharded", self.mesh,
                fn=self.dense_fn,
                static_argnames=("k", "m_blocks", "interpret"))
            h = registry.compile(dname, jqx, jqy, self.x, self.y,
                                 self.mask, k=self.kk, m_blocks=self.mb,
                                 interpret=self.interp)
            return h.call(jqx, jqy, self.x, self.y, self.mask)

        return run


def _loosen_bbox(f: ast.Filter, geom_name: str) -> ast.Filter:
    """LOOSE_BBOX semantics: drop default-geometry BBOX predicates from the
    residual — the covering index/pushdown result is accepted as-is for the
    spatial primary (attribute/temporal predicates stay exact)."""
    if isinstance(f, ast.SpatialPredicate) and f.op == "BBOX" and f.prop.name == geom_name:
        return ast.Include()
    if isinstance(f, ast.And):
        kids = tuple(_loosen_bbox(c, geom_name) for c in f.children)
        kids = tuple(c for c in kids if not isinstance(c, ast.Include))
        if not kids:
            return ast.Include()
        return kids[0] if len(kids) == 1 else ast.And(kids)
    # do not descend through OR/NOT: dropping a disjunct would change results
    return f


def _needed_columns(query: Query, plan: QueryPlan, sft):
    """Physical column projection for the scan: filter-referenced attributes
    + hint attributes + requested projection (None = all, for full feature
    results)."""
    hints = query.hints
    g = sft.default_geometry
    d = sft.default_dtg
    needed = set()
    # the visibility column must ALWAYS ride the scan when configured —
    # dropping it would silently disable the feature-level auth mask
    vis_attr = (sft.user_data or {}).get("geomesa.vis.attr")
    if vis_attr:
        needed.add(vis_attr)
    for node in ast.walk(plan.filter):
        for field in ("prop", "left", "right"):
            v = getattr(node, field, None)
            if isinstance(v, ast.Property):
                needed.add(v.name)
    if hints.sample_by:
        needed.add(hints.sample_by)
    if hints.arrow_sort_field:
        needed.add(hints.arrow_sort_field)
    if hints.is_density:
        needed.add(g.name)
        if hints.density_weight:
            needed.add(hints.density_weight)
    elif hints.is_bin:
        needed.add(g.name)
        needed.add(hints.bin_track)
        if hints.bin_label:
            needed.add(hints.bin_label)
        if d is not None:
            needed.add(d.name)
    elif hints.is_stats:
        from geomesa_tpu.stats import parse_stats
        from geomesa_tpu.stats.sketches import Z3HistogramStat

        for s in parse_stats(hints.stats_string).stats:
            if isinstance(s, Z3HistogramStat):
                needed.add(s.geom)
                needed.add(s.dtg)
            elif s.attribute:
                needed.add(s.attribute)
    elif query.attributes is None:
        return None  # full feature results: all columns
    else:
        needed.update(query.attributes)
        for attr, _ in query.sort_by or []:
            needed.add(attr)
    return sorted(needed)






