"""Typed transient/permanent error classification for the recovery fabric.

Every dependency boundary (storage, Kafka, device transfer, kvstore,
compile cache) classifies failures into three kinds:

  transient  — worth retrying: I/O hiccups, connection resets, broker
               unavailability. Bounded retry with backoff applies.
  oom        — device memory exhaustion: NOT retried as-is (the same
               program would fail the same way); the serve layer halves
               the coalesced batch bucket and ultimately falls back to
               host evaluation (cql/hosteval.py).
  permanent  — bad input, schema drift, crashes: surfaced immediately,
               never retried, and counted toward poison-query quarantine.

The `FaultInjected` mixin marks exceptions raised by the injection
harness so the chaos checker can distinguish "an injected fault escaped
typed" (a bug) from organic failures. Injected classes subclass the
REAL exception families (OSError, ConnectionError, ...) so production
recovery code never special-cases injection — the fault path exercised
under test is byte-for-byte the path a real failure takes.
"""

from __future__ import annotations


class FaultInjected:
    """Marker mixin: this exception was raised by the fault harness."""


class TransientError(RuntimeError):
    """Explicitly-retryable dependency failure (base for wrappers)."""


class PermanentError(RuntimeError):
    """Explicitly non-retryable failure (bad input, unsupported path)."""


class DeviceOOM(MemoryError):
    """Device memory exhaustion (host->device transfer or kernel alloc).

    Real XLA OOMs surface as jaxlib XlaRuntimeError with a
    RESOURCE_EXHAUSTED status; `classify` maps those here by message so
    the recovery fabric needs no jaxlib import."""


class InjectedIOError(OSError, FaultInjected):
    """Injected storage/file I/O failure (transient)."""


class InjectedUnavailable(ConnectionError, FaultInjected):
    """Injected dependency-unavailable failure (transient)."""


class InjectedOOM(DeviceOOM, FaultInjected):
    """Injected device out-of-memory (oom)."""


class InjectedCrash(RuntimeError, FaultInjected):
    """Injected hard crash (permanent; feeds poison-query quarantine)."""


# FaultPlan `error` keys -> exception classes ("latency" injects delay
# only and maps to None)
ERROR_KINDS = {
    "io": InjectedIOError,
    "unavailable": InjectedUnavailable,
    "oom": InjectedOOM,
    "crash": InjectedCrash,
    "latency": None,
}

TYPED_ERRORS = (TransientError, PermanentError, DeviceOOM, OSError,
                ConnectionError)


def classify(exc: BaseException) -> str:
    """Map an exception to "transient" | "oom" | "permanent".

    Deadline expiry (plan.QueryTimeout subclasses TimeoutError and
    carries .phase) is permanent by definition — retrying past a blown
    deadline is the exact bug the fabric exists to prevent."""
    if isinstance(exc, DeviceOOM):
        return "oom"
    # real XLA OOM without importing jaxlib: status-name match
    name = type(exc).__name__
    if name == "XlaRuntimeError" and "RESOURCE_EXHAUSTED" in str(exc):
        return "oom"
    if isinstance(exc, PermanentError):
        return "permanent"
    if isinstance(exc, TimeoutError) and hasattr(exc, "phase"):
        return "permanent"  # QueryTimeout: the budget is gone
    if isinstance(exc, TransientError):
        return "transient"
    if isinstance(exc, (FileNotFoundError, PermissionError,
                        IsADirectoryError, NotADirectoryError)):
        # definitive filesystem answers, not flakiness: a missing file
        # (e.g. a compaction-raced read against an older manifest
        # snapshot) will be just as missing on attempt 4 — retrying
        # burns the backoff budget AND counts toward opening the
        # storage breaker on a perfectly healthy disk
        return "permanent"
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return "transient"
    if isinstance(exc, OSError):
        return "transient"
    return "permanent"


def is_typed(exc: BaseException) -> bool:
    """True when the exception is part of the serving error contract:
    a client can act on it (retry, back off, fix the query). Used by
    the chaos checker to detect un-typed escapes."""
    if isinstance(exc, TYPED_ERRORS) or isinstance(exc, FaultInjected):
        return True
    # serve-layer typed signals, duck-typed to avoid import cycles
    if hasattr(exc, "reason"):  # QueryRejected / BreakerOpen
        return True
    if isinstance(exc, TimeoutError) and hasattr(exc, "phase"):
        return True  # QueryTimeout
    return False
