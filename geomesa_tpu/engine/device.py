"""Device-resident feature batches.

The host FeatureBatch (NumPy + vocab) maps onto a flat dict of device arrays
— a pytree that jitted kernels take as an argument. Naming convention:

  <attr>            numeric / dict-code (int32) / temporal (int64 millis)
  <attr>__x/__y     point coordinates (coord_dtype, default float32)
  <attr>__bbox      [N,4] per-feature envelopes (extended geometries)
  <attr>__verts     [V,2] CSR vertex buffer (extended geometries)
  <attr>__rings     [R+1] ring offsets        <attr>__featr  [N+1] feature->rings
  __valid__         bool validity mask (padding-aware)

Dtype policy (SURVEY.md §7 design stance): f64 on host; f32 coordinates on
device by default (adequate for ~1 m predicate resolution; kernels that need
tighter tolerance, e.g. kNN refinement, upcast selectively). Epoch-millis
stay int64 — int64 compare/add on TPU lowers to cheap s32 pairs, unlike f64
matmuls. geomesa_tpu enables jax x64 so int64 survives; all kernel dtypes
are explicit, so nothing else silently widens.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

import jax

# gt: waive GT25
# (the env-conditioned x64 switch IS per-process divergence bait on a
# pod — a host with a different GEOMESA_TPU_ENABLE_X64 compiles
# different programs and deadlocks the first collective. The static
# finding is real; the mitigation is runtime, where statics can't see
# it: parallel.distributed.assert_uniform_runtime() folds this knob
# into a cross-process fingerprint check right after
# jax.distributed.initialize, so divergence dies loudly at startup
# instead of hanging a pod)
if os.environ.get("GEOMESA_TPU_ENABLE_X64", "1") == "1":
    jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from geomesa_tpu.core.columnar import DictColumn, FeatureBatch, GeometryColumn
from geomesa_tpu.faults import BREAKERS, RetryPolicy, retry_call
from geomesa_tpu.faults import harness as _faults
from geomesa_tpu.telemetry.trace import TRACER

DeviceBatch = Dict[str, jax.Array]

VALID = "__valid__"

# host->device transfers are the device boundary: a transient transfer
# failure surfaces as an I/O-ish error worth a couple of fast retries;
# RESOURCE_EXHAUSTED (OOM) is NOT retried here — the same transfer would
# fail identically, so it propagates for the serve layer's bucket-halving
# + host-eval fallback (faults/fallback.py). The backoff is deliberately
# TINY (worst case ~37ms of sleep total): some callers — the
# DeviceCacheManager residency swaps — invoke to_device under their
# instance lock (the GT09-waived double-buffer uploads), and while the
# multi-second upload itself is the accepted cost there, the retry
# fabric must not add meaningful lock-held sleep on top of it.
_TRANSFER_SITE = _faults.site(
    "device.transfer", "host->device batch transfer (engine.device)")
_DEVICE_RETRY = RetryPolicy(max_attempts=3, base_ms=2.0, cap_ms=25.0)


def to_device(
    batch: FeatureBatch,
    coord_dtype=jnp.float32,
    device=None,
) -> DeviceBatch:
    """Transfer a FeatureBatch to device arrays (see module docstring).
    Runs under the recovery fabric: transient transfer failures retry
    with backoff against the "device" circuit breaker; OOM propagates
    typed (see _TRANSFER_SITE note above)."""
    with TRACER.span("device.transfer", rows=len(batch)):
        return retry_call(
            _to_device_impl, batch, coord_dtype, device,
            policy=_DEVICE_RETRY, label="device",
            breaker=BREAKERS.get("device"))


def _to_device_impl(
    batch: FeatureBatch,
    coord_dtype=jnp.float32,
    device=None,
) -> DeviceBatch:
    _TRANSFER_SITE.fire()
    out: Dict[str, jax.Array] = {}
    put = lambda a: jax.device_put(a, device)
    for attr in batch.sft.attributes:
        col = batch.columns[attr.name]
        if isinstance(col, GeometryColumn):
            out[f"{attr.name}__x"] = put(jnp.asarray(col.x, coord_dtype))
            out[f"{attr.name}__y"] = put(jnp.asarray(col.y, coord_dtype))
            if not col.is_point:
                out[f"{attr.name}__bbox"] = put(jnp.asarray(col.bbox, coord_dtype))
                out[f"{attr.name}__verts"] = put(jnp.asarray(col.vertices, coord_dtype))
                out[f"{attr.name}__rings"] = put(jnp.asarray(col.ring_offsets, jnp.int32))
                out[f"{attr.name}__featr"] = put(jnp.asarray(col.feature_rings, jnp.int32))
                et = col.edge_table()
                out[f"{attr.name}__vfeat"] = put(jnp.asarray(et.vfeat, jnp.int32))
                out[f"{attr.name}__ex1"] = put(jnp.asarray(et.x1, coord_dtype))
                out[f"{attr.name}__ey1"] = put(jnp.asarray(et.y1, coord_dtype))
                out[f"{attr.name}__ex2"] = put(jnp.asarray(et.x2, coord_dtype))
                out[f"{attr.name}__ey2"] = put(jnp.asarray(et.y2, coord_dtype))
                out[f"{attr.name}__efeat"] = put(jnp.asarray(et.efeat, jnp.int32))
        elif isinstance(col, DictColumn):
            out[attr.name] = put(jnp.asarray(col.codes, jnp.int32))
        elif col.dtype == object:
            continue  # Bytes columns stay host-side
        elif attr.is_temporal:
            out[attr.name] = put(jnp.asarray(col, jnp.int64))
        else:
            out[attr.name] = put(jnp.asarray(col))
    valid = (
        batch.valid
        if batch.valid is not None
        else np.ones(len(batch), dtype=bool)
    )
    out[VALID] = put(jnp.asarray(valid))
    return out


# edge tables are built by GeometryColumn.edge_table() (vectorized,
# memoized, ring-orientation-normalized for polygon kinds) — see
# core.columnar.EdgeTable.


# -- double-buffered query staging (serve pipeline) -------------------------


class QueryStager:
    """Double-buffered host→device staging slots for the serve
    pipeline's query streams (docs/SERVING.md "Pipelined dispatch").

    Each pipelined window stages its (padded, f32) stacked query points
    through `stage()` before the kernel launch, so the transfer overlaps
    the PREVIOUS window's kernel instead of serializing in front of this
    window's. Per (kernel, bucket) key the stager keeps `depth` slots
    rotated per window; the slot reference is what bounds live staging
    HBM to `depth` buffers per key and — under the registry's serve
    donation tier — guarantees the pair handed to window N is never the
    pair window N+1 is transferring into (a donated buffer is consumed
    by its window's program; the rotation means the stager re-offers
    that slot only after the depth-bounded pipeline has synced the
    window that consumed it).

    The persistent serve loop (serve/ringloop.py) reuses this exact
    discipline generalized to depth R: its ring of donated slot buffers
    IS a QueryStager at `depth=R`, so the slot handed to window N is
    never the slot window N+1 is transferring into as long as R bounds
    the windows in flight (docs/SERVING.md "Persistent serve loop").

    The dtype discipline matches the serial path exactly
    (`jnp.asarray(np.asarray(qx), jnp.float32)`): host f64 → f32 cast on
    host, then device_put — so pipelined results are bit-identical.
    Transfers run under the same recovery fabric as `to_device`
    (device.transfer fault site, tiny-backoff retries, device breaker).
    Thread-safe, though the serve pipeline calls it from the single
    dispatch thread."""

    # bound on distinct (kernel, bucket) keys: beyond it the
    # least-recently-staged key is evicted so a long-lived multi-tenant
    # service never pins more than MAX_KEYS * depth stale device pairs
    # (an evicted key's buffers free once its in-flight windows sync —
    # the kernels hold their own references)
    MAX_KEYS = 64

    def __init__(self, depth: int = 2):
        if depth < 2:
            raise ValueError("stager depth must be >= 2 (double buffer)")
        self.depth = depth
        self._lock = threading.Lock()
        # key -> [seq, slot0, slot1, ...]; slot = (qx_dev, qy_dev).
        # Insertion-ordered; stage() re-inserts on touch, so iteration
        # order is least-recently-staged first (the eviction order)
        self._slots: Dict[object, list] = {}
        self._staged_total = 0

    def stage(self, key, qx, qy, device=None):
        """Transfer one window's stacked query points; returns the
        device (qx, qy) pair. `qx`/`qy` are host arrays (the caller
        keeps them — the OOM ladder re-stages from host)."""
        qx32 = np.asarray(qx, np.float32)
        qy32 = np.asarray(qy, np.float32)

        def _put():
            _TRANSFER_SITE.fire()
            return (jax.device_put(jnp.asarray(qx32), device),
                    jax.device_put(jnp.asarray(qy32), device))

        from geomesa_tpu.utils.metrics import note_device_op

        note_device_op()
        with TRACER.span("device.transfer", rows=int(qx32.shape[0]),
                         staged=True):
            pair = retry_call(
                _put, policy=_DEVICE_RETRY, label="device",
                breaker=BREAKERS.get("device"))
        with self._lock:
            slot = self._slots.pop(key, None)
            if slot is None:
                slot = [0] + [None] * self.depth
                while len(self._slots) >= self.MAX_KEYS:
                    # least-recently-staged key goes first
                    self._slots.pop(next(iter(self._slots)))
            self._slots[key] = slot  # re-insert = LRU touch
            seq = slot[0]
            slot[1 + seq % self.depth] = pair
            slot[0] = seq + 1
            self._staged_total += 1
        return pair

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"keys": len(self._slots),
                    "staged": self._staged_total}


# -- batch-identity device cache --------------------------------------------
# Repeat analytics over one materialized batch (the KNN process's steady
# state, the SQL engine's table scans) must not re-upload coordinates per
# call — the host->device transfer is the dominant cost at scale.
# Keyed by object identity + dtype; evicted when the batch is collected.
# (FeatureBatch is an eq=True dataclass, hence unhashable — id() keying
# with a weakref.finalize eviction hook instead of a WeakKeyDictionary.)
_BATCH_CACHE: Dict[int, Dict[str, DeviceBatch]] = {}


def to_device_cached(
    batch: FeatureBatch, coord_dtype=jnp.float32, device=None
) -> DeviceBatch:
    """`to_device` memoized on the batch OBJECT (not value): safe because
    FeatureBatch columns are treated as immutable throughout the engine
    (every mutation path builds a new batch via select/concat/pad_to)."""
    import weakref

    key = id(batch)
    slot = _BATCH_CACHE.get(key)
    if slot is None:
        slot = _BATCH_CACHE[key] = {}
        weakref.finalize(batch, _BATCH_CACHE.pop, key, None)
    dkey = f"{jnp.dtype(coord_dtype)}|{device}"
    if dkey not in slot:
        slot[dkey] = to_device(batch, coord_dtype=coord_dtype, device=device)
    return slot[dkey]
