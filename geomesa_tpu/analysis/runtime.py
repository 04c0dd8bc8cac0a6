"""Opt-in runtime guards: recompile counters + transfer guards.

The static linter catches what the AST shows; these guards catch what
only shows up live — a retrace storm from an unhashable config object,
or a silent host round-trip on the query hot path. Both surface through
`geomesa_tpu.utils.metrics` (gauges `analysis.recompiles.<name>`,
counter `analysis.recompiles`), so the existing JSON/Prometheus
exporters pick them up with no extra wiring.

`JitTracker.wrap` wraps a single jitted callable; `guard_engine` sweeps
the engine modules and wraps every jitted callable in place (reversible
with `.unwrap()`); `transfer_guard` is a thin, version-tolerant wrapper
over `jax.transfer_guard`. All of it is pay-when-used: importing this
module does not import jax.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

# the canonical module list + jit predicate + sweep live in
# compilecache.kernels (stdlib-only import) so this tracker, the
# ExecutableRegistry default sweep and warmup check() can never drift
# apart about what the hot-kernel universe IS
from geomesa_tpu.compilecache.kernels import (  # noqa: F401 (re-export)
    ENGINE_MODULES as _ENGINE_MODULES, is_jitted, iter_jitted)

TRANSFER_MODES = ("allow", "log", "disallow")


class JitTracker:
    """Counts compile-cache growth per wrapped jitted callable.

    Every wrapped call compares `fn._cache_size()` before/after; growth
    means this call traced+compiled instead of hitting the cache. The
    counts publish to the metrics registry on every recompile and are
    queryable via `report()`. `warn_after` (per callable) invokes
    `on_storm` once when a callable exceeds it — the runtime analog of
    lint rule GT01.

    Warmup plumbing (docs/SERVING.md "Cold start"): a compiling call's
    wall time is noted into the process-wide compile-stall meter
    (`compilecache.stall.STALLS`, feeding ServeEvent attribution and the
    `compile.stall` histogram), and when a `recorder`
    (`compilecache.manifest.WarmupRecorder`) is attached, the observed
    (kernel, shapes, dtypes, static-args) tuple is recorded into the
    warmup manifest — the tuples `gmtpu warmup` later replays.
    """

    def __init__(self, registry=None, warn_after: Optional[int] = None,
                 on_storm: Optional[Callable[[str, int], None]] = None,
                 recorder=None):
        if registry is None:
            from geomesa_tpu.utils.metrics import metrics as registry
        self.registry = registry
        self.warn_after = warn_after
        self.on_storm = on_storm
        self.recorder = recorder  # read per call: attachable post-install
        self._lock = threading.Lock()
        self.recompiles: Dict[str, int] = {}
        self.calls: Dict[str, int] = {}
        self._warned: set = set()
        self._installed: List[tuple] = []  # (module, attr, original)

    def total_recompiles(self) -> int:
        with self._lock:
            return sum(self.recompiles.values())

    def wrap(self, fn, name: Optional[str] = None,
             origin: Optional[Tuple[str, str]] = None):
        """`origin` is the (full module name, attr) pair install() saw —
        the manifest needs the importable path, not just the label."""
        if not is_jitted(fn):
            raise TypeError(
                f"JitTracker.wrap expects a jax.jit callable, got {fn!r}")
        label = name or getattr(fn, "__name__", repr(fn))

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            before = fn._cache_size()
            t0 = time.perf_counter_ns()
            out = fn(*args, **kwargs)
            elapsed = (time.perf_counter_ns() - t0) / 1e9
            grew = fn._cache_size() - before
            storm_count = 0
            with self._lock:
                self.calls[label] = self.calls.get(label, 0) + 1
                if grew > 0:
                    n = self.recompiles.get(label, 0) + grew
                    self.recompiles[label] = n
                    self.registry.counter("analysis.recompiles", grew)
                    self.registry.gauge(
                        f"analysis.recompiles.{label}", float(n))
                    if (self.warn_after is not None
                            and n > self.warn_after
                            and label not in self._warned):
                        self._warned.add(label)
                        storm = self.on_storm
                        storm_count = n  # captured under the lock (GT07)
                    else:
                        storm = None
                else:
                    storm = None
                recorder = self.recorder if grew > 0 else None
            if grew > 0:
                # the elapsed wall of a compiling call IS the inline
                # stall a request saw (trace + compile + one execute)
                try:
                    from geomesa_tpu.compilecache.stall import STALLS

                    STALLS.note(label, elapsed, start_ns=t0)
                except Exception:
                    pass
            if recorder is not None and origin is not None:
                try:
                    recorder.record_kernel(
                        origin[0], origin[1], args, kwargs, elapsed)
                except Exception:
                    pass  # recording must never break the live call
            if storm is not None:
                storm(label, storm_count)
            return out

        wrapper._gt_tracked = fn  # type: ignore[attr-defined]
        wrapper._gt_tracker = self  # type: ignore[attr-defined]
        return wrapper

    # -- in-place module instrumentation ----------------------------------

    def install(self, module, names: Optional[List[str]] = None) -> int:
        """Wrap every jitted top-level callable of `module` in place.
        Returns how many were wrapped. Idempotent per module attr."""
        wrapped = 0
        for attr in names or sorted(vars(module)):
            obj = getattr(module, attr, None)
            if not is_jitted(obj) or hasattr(obj, "_gt_tracked"):
                continue
            label = f"{module.__name__.rsplit('.', 1)[-1]}.{attr}"
            setattr(module, attr, self.wrap(
                obj, name=label, origin=(module.__name__, attr)))
            with self._lock:
                self._installed.append((module, attr, obj))
            wrapped += 1
        return wrapped

    def unwrap(self) -> None:
        with self._lock:
            installed, self._installed = self._installed, []
        for module, attr, original in reversed(installed):
            setattr(module, attr, original)

    def is_installed(self) -> bool:
        with self._lock:
            return bool(self._installed)

    def report(self) -> Dict[str, dict]:
        with self._lock:
            return {
                name: {"calls": self.calls.get(name, 0),
                       "recompiles": self.recompiles.get(name, 0)}
                for name in sorted(set(self.calls) | set(self.recompiles))
            }


def guard_engine(registry=None, warn_after: Optional[int] = None,
                 on_storm: Optional[Callable[[str, int], None]] = None,
                 modules=None, recorder=None) -> JitTracker:
    """Wrap every jitted callable across the engine modules with one
    shared tracker (the engine's jit caches, guarded). Call `.unwrap()`
    to restore. `recorder` (a WarmupRecorder) additionally records every
    compiling signature into a warmup manifest. A tracker that actually
    wrapped something claims the process-wide active slot (see
    acquire_engine_tracker), so later sharers can find it."""
    import importlib

    global _active_tracker, _active_refs, _active_owned
    tracker = JitTracker(registry=registry, warn_after=warn_after,
                         on_storm=on_storm, recorder=recorder)
    with _active_lock:
        for modname in modules or _ENGINE_MODULES:
            try:
                mod = importlib.import_module(modname)
            except ImportError:
                continue
            tracker.install(mod)
        if tracker.is_installed():
            # direct callers (gmtpu guard) own their wrappers and unwrap
            # them themselves; acquirers sharing this epoch refcount
            # from zero and never unwrap it (see release_engine_tracker)
            _active_tracker = tracker
            _active_refs = 0
            _active_owned = False
    return tracker


# The engine jits are MODULE GLOBALS, so only one tracker's wrappers can
# be installed at a time — a second guard_engine() finds everything
# already wrapped and silently counts nothing. Long-lived consumers
# (QueryService) therefore acquire the process-wide tracker instead of
# installing their own. Acquisition is REFCOUNTED: every acquire pairs
# with a release, and the wrappers come off only when the last acquirer
# releases — closing the first of two live services must not disable
# tracking for the survivor. The RLock makes check-then-install atomic
# (guard_engine re-enters it), and _find_installed_tracker recovers a
# tracker installed OUTSIDE this protocol (e.g. `gmtpu guard` calling
# guard_engine directly) via the back-pointer every wrapper carries —
# such adopted trackers are shared but never unwrapped by release (their
# installer owns the wrappers).
_active_lock = threading.RLock()
_active_tracker: Optional[JitTracker] = None
_active_refs = 0
_active_owned = False  # True iff acquire's own install put the wrappers on


def _find_installed_tracker(modules=None) -> Optional[JitTracker]:
    """The tracker whose wrappers currently sit on the engine modules
    (every wrapper back-points to its tracker), or None."""
    import importlib

    for modname in modules or _ENGINE_MODULES:
        try:
            mod = importlib.import_module(modname)
        except ImportError:
            continue
        for attr in sorted(vars(mod)):
            tracker = getattr(getattr(mod, attr, None), "_gt_tracker", None)
            if tracker is not None:
                return tracker
    return None


def acquire_engine_tracker(recorder=None, **kwargs
                           ) -> "Tuple[JitTracker, bool]":
    """Returns (tracker, installed_by_me). EVERY acquire must be paired
    with release_engine_tracker(tracker); the wrappers come off when the
    last acquirer releases (and only if an acquire installed them)."""
    global _active_tracker, _active_refs, _active_owned
    with _active_lock:
        active = _active_tracker
        if active is not None and active.is_installed():
            if recorder is not None:
                active.recorder = recorder
            _active_refs += 1
            return active, False
        tracker = guard_engine(recorder=recorder, **kwargs)
        if tracker.is_installed():
            # guard_engine claimed the slot; this epoch is acquire-owned
            _active_refs = 1
            _active_owned = True
            return tracker, True
        # nothing wrapped: either a foreign tracker already owns the
        # modules (adopt + share it, never count-nothing silently) or no
        # engine module is importable (degenerate; the tracker is inert)
        foreign = _find_installed_tracker(kwargs.get("modules"))
        if foreign is not None:
            if recorder is not None:
                foreign.recorder = recorder
            # publish so later acquires skip the install + module scan
            _active_tracker = foreign
            _active_refs += 1
            _active_owned = False  # its installer unwraps it, not us
            return foreign, False
        return tracker, True


def release_engine_tracker(tracker: JitTracker) -> None:
    """Counterpart to acquire: drop one reference; the LAST release of
    an acquire-installed epoch restores the bare engine jits (adopted
    foreign trackers are left for their installer to unwrap). The
    tracker object and its counters remain readable. Unwrap happens
    UNDER the slot lock: restoring module attrs while a concurrent
    acquire installs a fresh tracker would interleave the two setattr
    sweeps and leave some kernels untracked. Lock order is always
    _active_lock -> tracker._lock (install/unwrap take the tracker
    lock; JitTracker never takes the slot lock)."""
    global _active_tracker, _active_refs, _active_owned
    with _active_lock:
        if tracker is not _active_tracker:
            # stale epoch (the slot moved on): restoring is this
            # tracker's own business; unwrap is a no-op if already bare
            tracker.unwrap()
            return
        _active_refs = max(_active_refs - 1, 0)
        if _active_refs == 0:
            if _active_owned:
                tracker.unwrap()
            _active_tracker = None
            _active_owned = False


def clear_engine_jit_caches(modules=None) -> int:
    """Drop every engine jit's dispatch cache (unwrapping any tracker
    wrapper). Returns how many caches were cleared — 0 when this jax
    version has no `clear_cache` on jit products. Used by the warmup
    regression tests to simulate a fresh process without spawning one."""
    cleared = 0
    for _mod, _tail, _attr, obj in iter_jitted(modules):
        if hasattr(obj, "clear_cache"):
            try:
                obj.clear_cache()
                cleared += 1
            except Exception:
                pass
    return cleared


@contextlib.contextmanager
def transfer_guard(mode: str = "disallow"):
    """`jax.transfer_guard` with a version-tolerant fallback: "log"
    logs every implicit host<->device transfer, "disallow" raises on
    them — the runtime teeth behind lint rule GT02."""
    if mode not in TRANSFER_MODES:
        raise ValueError(
            f"transfer mode must be one of {TRANSFER_MODES}, got {mode!r}")
    import jax

    guard = getattr(jax, "transfer_guard", None)
    if guard is None:  # very old jax: guard unavailable, run unguarded
        yield
        return
    with guard(mode):
        yield


def run_guarded(path: str, argv: Optional[List[str]] = None,
                transfer: str = "allow",
                warn_after: Optional[int] = None,
                on_storm: Optional[Callable[[str, int], None]] = None,
                registry=None,
                races: bool = False) -> Tuple[Dict[str, dict], int]:
    """Execute a Python script under the runtime guards (the `gmtpu
    guard` command): engine jit caches tracked, optional transfer
    guard, optional lockset race harness (`races=True`: every lock the
    script CREATES is tracked; lock-order inversions and empty-lockset
    accesses land in the report under "locksets"). Returns (report,
    script exit status) — a script ending in the standard
    `sys.exit(main())` idiom must not swallow the report, so SystemExit
    is caught and surfaced as the status."""
    import runpy
    import sys

    tracker = guard_engine(registry=registry, warn_after=warn_after,
                           on_storm=on_storm)
    old_argv = sys.argv
    sys.argv = [path] + list(argv or ())
    status = 0
    lock_report = None
    try:
        with contextlib.ExitStack() as stack:
            if transfer != "allow":
                stack.enter_context(transfer_guard(transfer))
            watch = None
            if races:
                from geomesa_tpu.analysis.locksets import trace_locks

                watch = stack.enter_context(trace_locks())
            try:
                runpy.run_path(path, run_name="__main__")
            except SystemExit as e:
                code = e.code
                status = code if isinstance(code, int) else (
                    0 if code is None else 1)
            if watch is not None:
                lock_report = watch.report()
    finally:
        sys.argv = old_argv
        tracker.unwrap()
    report = tracker.report()
    if lock_report is not None:
        report["locksets"] = lock_report
    return report, status
