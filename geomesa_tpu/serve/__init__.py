"""Concurrent query serving: admission control, request coalescing,
tail-latency observability (docs/SERVING.md).

No reference-module parity here — upstream GeoMesa delegates concurrency
to GeoServer/the client; a device-resident store needs its own serving
discipline because one accelerator runs one program at a time. The
design borrows from inference serving (Clipper-style adaptive batching
with latency knobs; Orca-style continuous batching — see PAPERS.md):
coalesce compatible requests into shared device dispatches, bound the
queue, shed explicitly — and pipeline the dispatches themselves
(pipeline.py): window N+1's host prep and transfer overlap window N's
kernel, with the device sync deferred to a completer thread
(docs/SERVING.md "Pipelined dispatch").
"""

from geomesa_tpu.serve.scheduler import (
    PRIORITIES, AdmissionQueue, QueryRejected, RateLimiter, ServeRequest,
    TokenBucket)
from geomesa_tpu.serve.batcher import (
    compat_key, execute_batch, fused_count_key)
from geomesa_tpu.serve.pipeline import DispatchPipeline
from geomesa_tpu.serve.service import QueryService, ServeConfig, self_check
from geomesa_tpu.serve.columnar import PushMux, wire_capabilities

__all__ = [
    "PRIORITIES", "AdmissionQueue", "QueryRejected", "RateLimiter",
    "ServeRequest", "TokenBucket", "compat_key", "execute_batch",
    "fused_count_key", "DispatchPipeline",
    "QueryService", "ServeConfig", "self_check",
    "PushMux", "wire_capabilities",
]
