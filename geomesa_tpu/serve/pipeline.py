"""Pipelined dispatch: keep the device busy across coalesced windows.

BENCH r03 measured an 812M pts/s burst but only 523M sustained, with
dispatch RTT (0.101s) exceeding net kernel time (0.066s) — the serve
hot path is dispatch-bound, not FLOP-bound (ROADMAP item 2; PR 6's gap
report shows 32% host gap live even on CPU). The serial dispatch loop
serializes, per window: host stacking → host→device transfer → kernel →
device sync → respond. Every one of those host phases leaves the device
idle.

This module overlaps them. Each coalesced kNN window is split into
stages:

    prepare   host stacking/padding of member query points
              (batcher.stack_queries — byte-identical to serial)
    transfer  host→device staging of the stacked queries through
              engine.device.QueryStager's double-buffered slots
    launch    planner.knn_launch: plan → mask → kernel DISPATCH (JAX
              async dispatch returns before the kernel finishes)
    sync      planner.KnnLaunch.sync on the COMPLETER thread: the one
              combined device read, overflow fallback, result split,
              future resolution, audit

The dispatch thread runs prepare/transfer/launch for window N+1 while
window N's kernel is still executing; the sync is deferred to a
dedicated completer thread and happens exactly when the results are
consumed for the response. In-flight windows are bounded by `depth`
(default 2 — classic double buffering): the dispatch thread blocks on
the window slot semaphore when the pipeline is full, which is the
backpressure that keeps HBM footprint bounded.

Cross-kind fusion rides here too: COUNT requests whose (type, CQL,
hints) match the kNN window (batcher.fused_count_key) resolve from the
window's filter-mask reduction — one fused program instead of a second
dispatch RTT. The reduction runs over the f64-exact mask (band
corrections scattered in), so the planner currently accepts every
fusion request; `KnnLaunch.fused_ok` stays in the contract and riders
a future gate declines re-dispatch serially on the completer.

Failure semantics match the serial path exactly: device OOM runs the
batcher's halving → host-eval ladder (re-staging from the HOST query
copies each request still holds — staged device buffers are never
re-read, which is what makes the registry's serve donation tier safe);
any other error fans out typed to every member. A `device.transfer`
fault mid-pipeline fails ONLY its own window — windows already launched
drain cleanly through the completer (`gmtpu chaos` asserts this).

GT16 (docs/ANALYSIS.md) lint-enforces the stage discipline: no
`block_until_ready` / `future.result()` / `jax.device_get` inside the
prepare/transfer/launch stages — a blocking call there re-serializes
the exact host gap this module exists to remove.
"""

from __future__ import annotations

import threading
import time
from queue import SimpleQueue
from time import perf_counter_ns
from typing import List, Optional

from geomesa_tpu.serve.batcher import (
    _run_group, batch_timeout_ms, stack_queries, split_knn_results)
from geomesa_tpu.serve.scheduler import ServeRequest
from geomesa_tpu.telemetry.trace import TRACER, new_span_id

_STOP = object()


class PipelinedWindow:
    """One coalesced window moving through the pipeline stages."""

    __slots__ = ("source", "live", "counts", "lead", "t0", "g0_ns",
                 "adopt_from", "wid", "running", "running_counts",
                 "qx", "qy", "offsets", "staged", "launch",
                 "stalls", "recovery", "seq", "prep_start_ns", "put_ns")

    def __init__(self, source, live, counts, lead, t0, g0_ns,
                 adopt_from, seq):
        self.source = source
        self.live = live            # every popped member (incl. cancelled)
        self.counts = counts        # fused count riders
        self.lead = lead
        self.t0 = t0                # monotonic at dispatch start
        self.g0_ns = g0_ns          # perf_counter_ns at gather start
        self.adopt_from = adopt_from
        self.seq = seq
        self.wid: Optional[int] = None   # pre-allocated window span id
        self.running: List[ServeRequest] = []
        self.running_counts: List[ServeRequest] = []
        self.staged = None
        self.launch = None
        self.stalls: list = []
        self.recovery: list = []
        self.prep_start_ns = 0
        self.put_ns = 0             # perf_counter_ns at the completer hand-off


class DispatchPipeline:
    """The pipelined execution path behind QueryService._dispatch.

    Owned by one QueryService; `submit` runs on the service's dispatch
    thread, the deferred syncs on this pipeline's completer thread.
    `depth` bounds windows in flight (submit blocks when full)."""

    def __init__(self, service, depth: int = 2,
                 donate: Optional[bool] = None, ring: bool = True,
                 ring_depth: int = 4):
        from geomesa_tpu.engine.device import QueryStager

        self.service = service
        self.depth = max(2, int(depth))
        self._donate = donate       # None = auto (backend supports it)
        self._stager = QueryStager(depth=self.depth)
        # persistent serve loop (serve/ringloop.py): eligible kNN
        # windows dispatch over a long-lived ring program instead of
        # the per-window transfer+launch below; ring-ineligible windows
        # fall back typed to this pipeline unchanged
        self.ring = None
        if ring:
            from geomesa_tpu.serve.ringloop import RingLoop

            self.ring = RingLoop(service,
                                 depth=max(int(ring_depth), self.depth),
                                 donate=donate)
        self._slots = threading.BoundedSemaphore(self.depth)
        self._completions: SimpleQueue = SimpleQueue()
        self._lock = threading.Lock()
        # notified when a window gives its slot back, for a coalescing
        # gather waiting on a full pipeline (QueryService._gather)
        self._slot_freed = threading.Condition(self._lock)
        self._seq = 0
        self._inflight = 0
        self._max_inflight = 0
        self._windows = 0
        self._fused = 0
        self._fused_declined = 0
        self._closed = False
        self._worker: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def _ensure_started(self) -> None:
        with self._lock:
            if self._worker is not None and self._worker.is_alive():
                return
            self._worker = threading.Thread(
                target=self._complete_loop, name="gmtpu-serve-sync",
                daemon=True)
            self._worker.start()

    def close(self, timeout_s: float = 10.0) -> None:
        """Drain remaining completions and stop the completer. Windows
        already launched still sync (no torn responses on shutdown)."""
        from queue import Empty

        with self._lock:
            self._closed = True
            worker = self._worker
        if self.ring is not None:
            self.ring.close()
        if worker is not None and worker.is_alive():
            self._completions.put(_STOP)
            worker.join(timeout=timeout_s)
        # a window enqueued AFTER the _STOP sentinel (the dispatch
        # thread raced shutdown past submit's closed-check) would sit in
        # a queue nobody reads: its member futures must fail typed
        # rather than hang a client forever
        while True:
            try:
                win = self._completions.get_nowait()
            except Empty:
                break
            if win is _STOP:
                continue
            from geomesa_tpu.serve.scheduler import QueryRejected

            exc = QueryRejected(
                "shutting_down",
                "service closed before the pipelined window synced")
            for r in win.running + win.running_counts:
                if not r.future.done():
                    r.future.set_exception(exc)
            self._window_done(win)

    @property
    def donate(self) -> bool:
        if self._donate is None:
            import jax

            # donation is unimplemented on CPU (JAX warns and ignores);
            # resolve once, lazily, so constructing a service does not
            # force backend init
            self._donate = jax.default_backend() != "cpu"
        return self._donate

    # -- dispatch-thread stages --------------------------------------------

    def submit(self, source, live: List[ServeRequest],
               counts: List[ServeRequest], lead, t0: float, g0_ns: int,
               g1_ns: int, adopt_from: int) -> None:
        """Run prepare/transfer/launch for one window and hand it to the
        completer. Blocks while `depth` windows are in flight. All
        failure modes resolve member futures and complete the window's
        bookkeeping before returning — the caller never needs to clean
        up. `g0_ns`/`g1_ns` bound the gather (the `coalesce` span); the
        dispatcher's work from the gather's end to the slot wait
        (expiry split, fused-count drain, queue-wait stamps, source
        lookup) is the window's `window.open` span."""
        from geomesa_tpu.compilecache.stall import STALLS
        from geomesa_tpu.faults import RECOVERY

        self._ensure_started()
        # the slot wait: zero-length when a slot is free at once, else
        # the time the dispatch thread stood behind `depth` windows in
        # flight (recorded below as `pipeline.slot_wait` once the window
        # has its span id)
        wait0_ns = wait1_ns = perf_counter_ns()
        if not self._slots.acquire(blocking=False):
            # bounded-wait acquire: the completer survives every window
            # error by construction, but if it is ever not running
            # (process teardown, BaseException) the dispatch thread must
            # fail loudly instead of wedging on a slot that can never
            # free
            while not self._slots.acquire(timeout=1.0):
                with self._lock:
                    worker = self._worker
                if worker is None or not worker.is_alive():
                    raise RuntimeError(
                        "pipeline completer is not running; window "
                        "slots cannot free")
            wait1_ns = perf_counter_ns()
        with self._lock:
            self._seq += 1
            self._inflight += 1
            self._max_inflight = max(self._max_inflight, self._inflight)
            seq = self._seq
        win = PipelinedWindow(source, live, counts, lead, t0, g0_ns,
                              adopt_from, seq)
        trace = lead.trace
        if trace is not None:
            win.wid = new_span_id()
            trace.record("window.open", g1_ns, wait0_ns,
                         parent_id=win.wid)
            trace.record("pipeline.slot_wait", wait0_ns, wait1_ns,
                         parent_id=win.wid)
        stall_token = STALLS.token()
        rec_token = RECOVERY.token()
        ok = False
        try:
            self._prepare(win)
            if win.running:
                # ring route first (docs/SERVING.md "Persistent serve
                # loop"): slot write + one pre-compiled dispatch; a
                # typed refusal (ineligible/stale) keeps the pipelined
                # transfer+launch, and a feed ERROR lands in the same
                # failure ladder a launch error would
                if self.ring is None or not self.ring.try_feed(win):
                    self._transfer(win)
                    self._launch(win)
            ok = True
        except BaseException as e:  # noqa: BLE001 — serial-path parity
            self._note_meters(win, stall_token, rec_token)
            self._fail(win, e)
            self._window_done(win)
            return
        self._note_meters(win, stall_token, rec_token)
        if not win.running:
            # every kNN member was cancelled between pop and prepare:
            # any fused counts still deserve their (serial) dispatch
            if win.running_counts:
                _run_group(win.source, win.running_counts)
            self._window_done(win)
            return
        with self._lock:
            self._windows += 1
            closed = self._closed
        if closed:
            # shutdown raced this window past the launch: the completer
            # may already have consumed _STOP, so never enqueue — fail
            # typed here (close()'s drain sweep covers the narrower
            # race where the put itself beat the sentinel)
            from geomesa_tpu.serve.scheduler import QueryRejected

            self._fail(win, QueryRejected(
                "shutting_down",
                "service closed before the pipelined window synced"))
            self._window_done(win)
            return
        win.put_ns = perf_counter_ns()
        self._completions.put(win)

    def _note_meters(self, win, stall_token, rec_token) -> None:
        """Dispatch-thread attribution window: compile stalls + recovery
        events this thread noted during prepare/transfer/launch are this
        window's (same thread-scoped discipline as the serial path);
        the completer appends its own sync-side window later."""
        from geomesa_tpu.compilecache.stall import STALLS
        from geomesa_tpu.faults import RECOVERY

        ident = threading.get_ident()
        win.stalls.extend(STALLS.since(stall_token, thread_ident=ident))
        win.recovery.extend(RECOVERY.since(rec_token, thread_ident=ident))

    def _prepare(self, win: PipelinedWindow) -> None:
        """Host stacking/padding (batcher.stack_queries). Marks member
        futures running — a rider cancelled while queued drops out here
        exactly like the serial execute_batch."""
        win.prep_start_ns = perf_counter_ns()
        win.running = [r for r in win.live
                       if r.future.set_running_or_notify_cancel()]
        win.running_counts = [r for r in win.counts
                              if r.future.set_running_or_notify_cancel()]
        if not win.running:
            return
        win.qx, win.qy, win.offsets = stack_queries(win.running)
        trace = win.lead.trace
        if trace is not None and win.wid is not None:
            trace.record("prepare", win.prep_start_ns, perf_counter_ns(),
                         parent_id=win.wid, batch=len(win.running))

    def _transfer(self, win: PipelinedWindow) -> None:
        """Stage the stacked queries into the double-buffered device
        slots (QueryStager): the transfer overlaps the previous window's
        kernel instead of serializing in front of this one's. On a mesh
        service the slots are PER-SHARD-REPLICATED — one NamedSharding
        placement puts the window's queries on every chip (the sharded
        program reads them replicated; the shard-affinity route takes
        the owning chip's replica) — and the placement joins the slot
        key so mesh and single-chip slots never alias."""
        lead = win.lead
        # gate on the SOURCE's residency tier, not ServeConfig.mesh:
        # the planner only takes the mesh route when the superbatch is
        # mesh-resident, and a store the tier cannot shard (extended
        # geometry, --no-device-cache) must stage single-device
        # buffers for the single-chip kernel it will actually run
        cache = getattr(win.source.planner, "cache", None)
        mesh = cache.serving_mesh() if cache is not None else None
        placement = None
        key = (lead.query.type_name, lead.k, lead.impl, len(win.qx))
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            placement = NamedSharding(mesh, P())
            key = key + ("mesh", tuple(int(s) for s in mesh.devices.shape))
        with TRACER.scope(lead.trace, parent_id=win.wid):
            win.staged = self._stager.stage(key, win.qx, win.qy,
                                            device=placement)

    def _launch(self, win: PipelinedWindow) -> None:
        """planner.knn_launch: plan → mask → async kernel dispatch. The
        fused count reduction rides the same launch when requested."""
        lead = win.lead
        timeout_ms = batch_timeout_ms(win.running + win.running_counts)
        with TRACER.scope(lead.trace, parent_id=win.wid):
            win.launch = win.source.planner.knn_launch(
                lead.query, win.qx, win.qy, k=lead.k, impl=lead.impl,
                timeout_ms=timeout_ms, staged=win.staged,
                want_mask_count=bool(win.running_counts),
                donate=self.donate)
        from geomesa_tpu.serve.batcher import note_launch_route

        # routing attribution lands BEFORE the deferred sync, so the
        # completer's ServeEvents carry it even when the window fails
        note_launch_route(win.running + win.running_counts, win.launch)

    # -- completer thread --------------------------------------------------

    def _complete_loop(self) -> None:
        import logging

        from geomesa_tpu.telemetry.recorder import RECORDER

        log = logging.getLogger(__name__)
        while True:
            win = self._completions.get()
            if win is _STOP:
                return
            trace = win.lead.trace
            if trace is not None and win.wid is not None:
                # the window's wait in the queue while the completer
                # synced the windows ahead of it
                trace.record("pipeline.pending", win.put_ns,
                             perf_counter_ns(), parent_id=win.wid)
            try:
                self._sync(win)
            except Exception as e:  # noqa: BLE001 — the completer must live
                log.exception("serve pipeline completer error")
                RECORDER.crash_dump("serve pipeline completer error", e)
            try:
                self._window_done(win)
            except Exception as e:  # noqa: BLE001 — ditto: the window's
                # slot/inflight releases ran in _window_done's finally,
                # so surviving a finish-bookkeeping error (audit I/O,
                # metrics) leaks nothing — it only costs that window's
                # audit record
                log.exception("serve pipeline finish error")
                RECORDER.crash_dump("serve pipeline finish error", e)

    def _sync(self, win: PipelinedWindow) -> None:
        """Deferred device sync: the one combined read, result split,
        fused-count resolution — and the serial path's full failure
        ladder when the window errors."""
        from geomesa_tpu.compilecache.stall import STALLS
        from geomesa_tpu.faults import RECOVERY

        stall_token = STALLS.token()
        rec_token = RECOVERY.token()
        lead = win.lead
        try:
            with TRACER.scope(lead.trace, parent_id=win.wid):
                dists, idx, batch = win.launch.sync()
                split_knn_results(win.running, win.offsets, dists, idx,
                                  batch)
            self._resolve_counts(win)
        except BaseException as e:  # noqa: BLE001 — fan out, serial parity
            self._fail(win, e)
        finally:
            self._note_meters(win, stall_token, rec_token)

    def _resolve_counts(self, win: PipelinedWindow) -> None:
        if not win.running_counts:
            return
        launch = win.launch
        if launch is not None and launch.fused_ok \
                and launch.mask_count is not None:
            with self._lock:
                self._fused += len(win.running_counts)
            from geomesa_tpu.utils.metrics import metrics

            metrics.counter("serve.fused.counts",
                            len(win.running_counts))
            n = launch.mask_count
            for r in win.running_counts:
                r.future.set_result(n)
        else:
            # defensive: the planner currently never declines
            # (fused_ok is always True when requested — the mask is
            # f64-exact), but the contract allows it, and a declined
            # rider gets its own serial dedup'd dispatch — slower,
            # never wrong
            with self._lock:
                self._fused_declined += len(win.running_counts)
            _run_group(win.source, win.running_counts)

    def _fail(self, win: PipelinedWindow, exc: BaseException) -> None:
        """Window failure = the serial path's ladder: OOM runs the
        batcher's halving → host-eval fallback (re-staging from the host
        query copies), everything else fans out typed. Fused counts
        always get a real (serial) count attempt — the count's failure
        story must not depend on the kNN it happened to ride with."""
        from geomesa_tpu.faults import classify
        from geomesa_tpu.serve.batcher import _oom_fallback
        from geomesa_tpu.telemetry.recorder import RECORDER

        # flight-recorder lifecycle event: a pipelined window dying
        # mid-flight is the multi-chip postmortem case — record WHICH
        # shards the window was routed to (note_launch_route stamped
        # the lead before the deferred sync) alongside the error, so a
        # crash dump distinguishes "one chip's windows keep failing"
        # from a fleet-wide fault
        RECORDER.note_event(
            "pipeline", action="window_failed", seq=win.seq,
            members=len(win.running) + len(win.running_counts),
            error=type(exc).__name__,
            shards=win.lead.shards or None,
            mesh_shape=win.lead.mesh_shape or None)
        # done-future guards throughout: a failure AFTER partial
        # resolution (e.g. the kNN split succeeded, then the fused-count
        # path threw) must only fail the still-pending members —
        # set_exception on a resolved future raises InvalidStateError
        pending = [r for r in win.running if not r.future.done()]
        if pending:
            if isinstance(exc, Exception) and classify(exc) == "oom":
                _oom_fallback(win.source, pending, exc)
            else:
                for r in pending:
                    r.future.set_exception(exc)
        pending_counts = [r for r in win.running_counts
                          if not r.future.done()]
        if pending_counts:
            try:
                _run_group(win.source, pending_counts)
            except BaseException as e:  # noqa: BLE001 — never drop a rider
                for r in pending_counts:
                    if not r.future.done():
                        r.future.set_exception(e)

    def _window_done(self, win: PipelinedWindow) -> None:
        """Completion bookkeeping: record the window span (its extent is
        only known now), hand the window to the service's shared finish
        path, free the slot. Called exactly once per submitted window —
        from submit's failure paths or from the completer — so the slot
        frees exactly once per acquire. The finish path itself is the
        lead trace's `window.finish` span (attr `window`: the window's
        span id), a child of the root since the window span has
        already ended; it runs up to the slot release, so the next
        window's slot wait shows what the bookkeeping held back."""
        import logging

        t1 = time.monotonic()
        end_ns = perf_counter_ns()
        trace = win.lead.trace
        if trace is not None and win.wid is not None:
            trace.record(
                "dispatch", win.g0_ns, end_ns, span_id=win.wid,
                batch=len(win.live), pipelined=True, seq=win.seq,
                fused=len(win.counts))
        try:
            try:
                self.service._window_complete(win, t1, end_ns)
            except Exception as e:  # noqa: BLE001 — bookkeeping only:
                # futures are already resolved, and letting this
                # propagate out of submit's failure path would make the
                # service decrement its inflight token a SECOND time
                # (negative inflight wedges close(drain=True) for the
                # whole drain timeout)
                from geomesa_tpu.telemetry.recorder import RECORDER

                logging.getLogger(__name__).exception(
                    "serve pipeline finish error")
                RECORDER.crash_dump("serve pipeline finish error", e)
        finally:
            if trace is not None and win.wid is not None:
                trace.record("window.finish", end_ns, perf_counter_ns(),
                             window=win.wid)
            with self._lock:
                self._inflight -= 1
                self._slot_freed.notify_all()
            self._slots.release()

    # -- slot state, read by the coalescing gather ---------------------------

    def slot_free(self) -> bool:
        """Could a window start now? A peek at the windows in flight;
        the semaphore is left to submit."""
        with self._lock:
            return self._inflight < self.depth

    def wait_slot(self, timeout_s: float = 1.0) -> bool:
        """Block until a window gives its slot back. False when none
        did within `timeout_s` and the completer is not running, so
        none ever will: the caller then goes on to submit, whose own
        guard fails the window loudly."""
        with self._lock:
            while self._inflight >= self.depth:
                if not self._slot_freed.wait(timeout_s):
                    worker = self._worker
                    if worker is None or not worker.is_alive():
                        return False
            return True

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            out = {
                "depth": self.depth,
                "windows": self._windows,
                "inflight": self._inflight,
                "max_inflight": self._max_inflight,
                "fused_counts": self._fused,
                "fused_declined": self._fused_declined,
                "stager": self._stager.stats(),
            }
        if self.ring is not None:
            out["ring"] = self.ring.stats()
        return out
