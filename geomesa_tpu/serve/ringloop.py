"""Persistent on-device serve loop: ring-fed, donated-buffer dispatch.

BENCH r03–r05 proved the serve hot path is dispatch-bound, not
FLOP-bound: per-window dispatch RTT (0.101 s) exceeds kernel time
(0.066 s), capping sustained throughput at ~513–523M pts/s against the
≥700M ROADMAP target. The PR-7 pipeline overlaps the per-window host
work with the previous window's kernel, but every window still PAYS
that host work — plan, residency ensure, filter mask, kernel binding —
before its dispatch. This module amortizes all of it to a one-time
setup cost.

One **ring program** per (type, canonical CQL, hints, k, impl,
Q-bucket[, mesh_shape]) window class (planner.ring_arm): the plan, the
resident superbatch, the f64-exact filter mask (with its sparse tile
list on the single-chip route), the calibrated sparse capacity, the
fused-count scalar and the AOT executable are all frozen at arm time
under the ExecutableRegistry's ring tier (`<kernel>@ring{depth}[+donate]`
— depth and the donation contract key the entry). Query inputs live in a fixed ring of `depth` staging slots
(engine.device.QueryStager generalized to depth R); with donation on
(non-CPU backends) each slot's buffer is consumed by its window's
program and XLA reuses that HBM across the rotation, so the device
drains slot after slot without a host round trip between windows. On
CPU CI the structural form is the same slot-reuse executable: per
window the Python work is ONLY

    slot write     QueryStager.stage into the next ring slot
    dispatch       ONE pre-compiled executable invocation
    harvest        the completer thread's combined sync read

— no plan, no residency walk, no mask recompute (the mesh route still
lists each shard's match-bearing tiles per window), no tracing, no new
executions compiled (zero recompiles asserted via JitTracker in
tests/test_ringloop.py). `dispatches_per_window` (the
`serve.device.ops` delta over a run's windows) meters exactly this: the
ring route is strictly below the pipelined baseline.

Correctness contract:

- **bit-identity** on every route: the ring runs the same kernels over
  the same frozen mask with the same staged f64→f32 cast, and sync is
  the serial route's sync — same overflow ladder, same
  `_canonical_dists` f64 host recompute (asserted ring-vs-serial-vs-
  pipelined in tests/test_ringloop.py).
- **typed fallback**: anything the frozen contract cannot hold —
  interceptors, un-versioned storage, no resident superbatch,
  shard-affinity mesh windows, a stale version — raises/returns typed
  and the window takes the PR-7 pipelined route unchanged (the OOM
  ladder re-stages from host copies exactly as today: a feed failure
  fans out through the pipeline's `_fail`, whose `_oom_fallback` holds
  the host query copies).
- **staleness**: `RingProgram.fresh()` per window is a lock-peek
  (superbatch identity) plus an int compare (storage commit version);
  a write sends the next window down the pipelined route, whose
  plan/ensure rebuilds residency, and the ring re-arms against the new
  version.

GT23 (docs/ANALYSIS.md) lint-enforces the feed discipline: no blocking
host sync (`block_until_ready` / future `.result()` / `device_get`)
and no naked per-window `device_put` inside the feed/slot scope of
this module — the slot write goes through the stager's designated
path, and blocking belongs to the completer's harvest.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from geomesa_tpu.telemetry.trace import TRACER

__all__ = ["RingLoop"]


class RingLoop:
    """The ring-program table + feed seam behind DispatchPipeline.

    Owned by one pipeline; `try_feed` runs on the service's dispatch
    thread (the pipeline calls it in place of transfer+launch), the
    harvest stays on the pipeline's completer thread. Armed programs
    are bounded (`MAX_PROGRAMS`, least-recently-fed eviction) and
    ineligibility is negative-cached per key until the storage version
    moves, so a permanently ineligible window class costs one dict
    probe per window, not one failed arm."""

    MAX_PROGRAMS = 32

    def __init__(self, service, depth: int = 4,
                 donate: Optional[bool] = None):
        from geomesa_tpu.engine.device import QueryStager

        self.service = service
        self.depth = max(2, int(depth))
        self._donate = donate
        # ring slots: a QueryStager at depth R — the slot handed to
        # window N re-offers only after R windows, which the pipeline's
        # depth bound keeps safely past N's sync
        self._stager = QueryStager(depth=self.depth)
        self._lock = threading.Lock()
        self._programs: Dict[tuple, object] = {}   # key -> RingProgram
        self._refused: Dict[tuple, tuple] = {}     # key -> (mv, reason)
        self._windows = 0
        self._armed = 0
        self._fallbacks: Dict[str, int] = {}

    @property
    def donate(self) -> bool:
        if self._donate is None:
            import jax

            # donation is unimplemented on CPU (JAX warns and ignores);
            # resolved lazily like the pipeline's flag
            self._donate = jax.default_backend() != "cpu"
        return self._donate

    # -- feed seam (dispatch thread) ---------------------------------------

    def try_feed(self, win) -> bool:
        """Dispatch one prepared window over its ring program. Returns
        True with `win.launch` armed (the completer harvests it exactly
        like a pipelined launch), or False — the caller runs the
        pipelined transfer+launch path. Raises only what a pipelined
        launch could raise (fault-injected slot transfers included):
        the caller's failure ladder applies unchanged."""
        from geomesa_tpu.serve.batcher import (
            batch_timeout_ms, note_launch_route, ring_key)

        lead = win.lead
        with TRACER.scope(lead.trace, parent_id=win.wid):
            # the window class's key and its armed program (armed here
            # on first use: the arm's plan/mask spans nest under it)
            with TRACER.span("ring.program"):
                key = ring_key(lead, len(win.qx))
                prog = (self._current_program(key, win)
                        if key is not None else None)
            if prog is None:
                return False
            timeout_ms = batch_timeout_ms(win.running + win.running_counts)
            with TRACER.span("ring.slot", q=int(len(win.qx)),
                             depth=self.depth):
                win.staged = self._stager.stage(
                    key, win.qx, win.qy, device=prog.placement)
            win.launch = prog.launch(
                win.staged, win.qx, win.qy, timeout_ms=timeout_ms,
                want_mask_count=bool(win.running_counts))
        note_launch_route(win.running + win.running_counts, win.launch)
        with self._lock:
            self._windows += 1
        return True

    def _current_program(self, key, win):
        """The fresh armed program for `key`, arming on first use —
        or None (typed fallback to the pipeline), with the reason
        metered and negative-cached against the current storage
        version."""
        with self._lock:
            prog = self._programs.pop(key, None)
            if prog is not None:
                self._programs[key] = prog  # re-insert = LRU touch
        if prog is not None:
            if prog.fresh():
                return prog
            # a version move stales EVERY armed program against that
            # storage generation — sweep them all now so idle keys do
            # not pin the previous superbatch's device arrays until LRU
            # eviction happens to reach them
            with self._lock:
                for k in [k for k, p in self._programs.items()
                          if not p.fresh()]:
                    del self._programs[k]
            self._note_fallback("stale")
            # deliberately NOT re-armed inline: the pipelined window
            # this falls back to runs plan/ensure, rebuilding residency
            # so the NEXT window's arm binds the new superbatch
            return None
        return self._arm(key, win)

    def _arm(self, key, win):
        """One-time arm for a window class (the ring's setup cost —
        comparable to a single pipelined window's plan+mask work plus
        one AOT compile, amortized over every window that follows)."""
        from geomesa_tpu.plan.planner import RingIneligible

        lead = win.lead
        planner = win.source.planner
        if not hasattr(planner, "ring_arm"):
            return None
        mv_fn = getattr(planner.storage, "manifest_version", None)
        mv = None
        if mv_fn is not None:
            try:
                mv = int(mv_fn())
            except Exception:
                mv = None
        with self._lock:
            refused = self._refused.get(key)
        if refused is not None and refused[0] == mv:
            # same meter as a fresh refusal: stats AND the exported
            # counter must agree on every fallback, cached or not
            self._note_fallback(refused[1])
            return None
        try:
            prog = planner.ring_arm(
                lead.query, q_padded=len(win.qx), k=lead.k,
                impl=lead.impl, donate=self.donate, depth=self.depth)
        except RingIneligible as e:
            with self._lock:
                self._refused[key] = (mv, e.reason)
                while len(self._refused) > 4 * self.MAX_PROGRAMS:
                    self._refused.pop(next(iter(self._refused)))
            self._note_fallback(e.reason)
            return None
        with self._lock:
            self._refused.pop(key, None)
            self._programs[key] = prog
            self._armed += 1
            while len(self._programs) > self.MAX_PROGRAMS:
                # least-recently-fed program goes first; its device
                # refs free once in-flight windows sync
                self._programs.pop(next(iter(self._programs)))
        return prog

    def _note_fallback(self, reason: str) -> None:
        from geomesa_tpu.utils.metrics import metrics

        with self._lock:
            self._fallbacks[reason] = self._fallbacks.get(reason, 0) + 1
        metrics.counter("serve.ring.fallbacks")

    # -- lifecycle / introspection -----------------------------------------

    def close(self) -> None:
        """Drop every armed program (their device refs free once
        in-flight windows sync — harvesting is the completer's job and
        each window syncs exactly once there)."""
        with self._lock:
            self._programs.clear()
            self._refused.clear()

    def stats(self) -> dict:
        with self._lock:
            return {
                "depth": self.depth,
                "programs": len(self._programs),
                "armed": self._armed,
                "windows": self._windows,
                "fallbacks": dict(sorted(self._fallbacks.items())),
                "stager": self._stager.stats(),
            }
