"""geomesa_tpu.faults — fault injection + recovery fabric.

Two halves (docs/ROBUSTNESS.md):

1. **Injection harness** (`harness.py`, `plan.py`): named sites threaded
   through every dependency boundary (storage, Kafka, device transfer,
   kvstore, compile cache), driven by a declarative seeded `FaultPlan`
   so failures are a replayable INPUT. Zero-overhead no-op check when
   inactive.
2. **Recovery fabric** (`errors.py`, `retry.py`, `breaker.py`,
   `quarantine.py`, `context.py`): typed transient/permanent/OOM
   classification, bounded deadline-aware retry with full-jitter backoff,
   per-dependency circuit breakers, poison-query quarantine, and the
   RecoveryMeter that attributes retries/faults to ServeEvents.

`chaos.py` (the `gmtpu chaos` CLI) runs a serve workload under a plan
and asserts the recovery invariants hold. `fallback.py` is the device-
OOM host-evaluation escape hatch; both import heavier subsystems and
are loaded lazily — this package root stays import-light so the engine
and storage layers can register sites without cycles.
"""

from geomesa_tpu.faults.breaker import BREAKERS, BreakerOpen, CircuitBreaker
from geomesa_tpu.faults.context import (
    RECOVERY, current_deadline, deadline_scope)
from geomesa_tpu.faults.errors import (
    DeviceOOM, FaultInjected, PermanentError, TransientError, classify,
    is_typed)
from geomesa_tpu.faults.harness import (
    SITES, FaultHarness, FaultSite, active, current, inject, install,
    site, uninstall)
from geomesa_tpu.faults.plan import FaultPlan, FaultRule
from geomesa_tpu.faults.quarantine import QuarantineRegistry
from geomesa_tpu.faults.retry import RetryPolicy, retry_call

__all__ = [
    "BREAKERS", "BreakerOpen", "CircuitBreaker",
    "RECOVERY", "current_deadline", "deadline_scope",
    "DeviceOOM", "FaultInjected", "PermanentError", "TransientError",
    "classify", "is_typed",
    "SITES", "FaultHarness", "FaultSite", "active", "current", "inject",
    "install", "site", "uninstall",
    "FaultPlan", "FaultRule", "QuarantineRegistry",
    "RetryPolicy", "retry_call",
]
