"""Compilation management: persistent cache, AOT registry, warmup.

The north-star metric is points/sec at identical recall, but a serving
process that compiles lazily spends its first minutes — and its p99
under shape churn — inside XLA. This subsystem makes compilation a
managed, observable, ahead-of-time resource (docs/SERVING.md "Cold
start" section):

- `enable_persistent_cache()` (persist.py): the library-level persistent
  XLA compilation cache shared by the planner, `QueryService`,
  `gmtpu serve` and the benchmark — executables survive process restarts.
- `ExecutableRegistry` (registry.py): `jit(...).lower(abstract).compile()`
  AOT handles per (kernel, pow2 shape bucket, dtype, static-args) key,
  with opt-in buffer donation.
- Warmup manifests (manifest.py / warmup.py): JitTracker records what
  compiled; `gmtpu warmup` and the `QueryService` startup hook replay it
  before traffic; `check()` proves a replayed process compiles nothing
  inline.
- `STALLS` (stall.py): per-dispatch compile-stall attribution feeding
  `ServeEvent.compile_ms` and the `compile.stall` histogram.
"""

from geomesa_tpu.compilecache.manifest import (
    KernelEntry, QueryEntry, WarmupManifest, WarmupRecorder)
from geomesa_tpu.compilecache.persist import (
    default_cache_dir, enable_persistent_cache, persistent_cache_dir)
from geomesa_tpu.compilecache.registry import (
    CompiledHandle, ExecutableRegistry, registry)
from geomesa_tpu.compilecache.stall import STALLS, StallMeter
from geomesa_tpu.compilecache.warmup import WarmupReport, check, replay

__all__ = [
    "KernelEntry", "QueryEntry", "WarmupManifest", "WarmupRecorder",
    "default_cache_dir", "enable_persistent_cache",
    "persistent_cache_dir", "CompiledHandle", "ExecutableRegistry",
    "registry", "STALLS", "StallMeter", "WarmupReport", "check",
    "replay",
]
