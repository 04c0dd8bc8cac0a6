"""Multi-host distributed initialization (DCN) for the shard mesh.

Parity: the reference's distributed runtime is storage RPC + Zookeeper
coordination (SURVEY.md C27/§5.8); the TPU-native equivalent is
`jax.distributed` over DCN with one global mesh on the same "shard" axis
the single-host kernels already use. Because every kernel in engine/ is
written against the mesh axis name (not a device count), scaling to
multi-host is configuration, not code: collectives ride ICI within a slice
and DCN across slices, routed by XLA.

Usage on each host (same program, standard JAX multi-host SPMD):

    from geomesa_tpu.parallel.distributed import initialize, global_mesh
    initialize(coordinator="host0:1234", num_processes=4, process_id=ID)
    mesh = global_mesh()           # one "shard" axis over ALL devices
    dev = shard_batch_host(local_batch, mesh)   # per-host arrays
    grid = density_sharded(mesh, ...)           # psum crosses hosts

Host-level data feeding follows the reference's storage division: each host
reads its own partitions (FS store over a shared filesystem), mirroring
per-tablet data locality; result merging is the collectives' job.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from geomesa_tpu.parallel.mesh import SHARD_AXIS


def initialize(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """jax.distributed.initialize with env-var fallback
    (GEOMESA_TPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID; on Cloud TPU
    pods all three are auto-detected and may be omitted)."""
    import jax

    coordinator = coordinator or os.environ.get("GEOMESA_TPU_COORDINATOR")
    if num_processes is None and "GEOMESA_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["GEOMESA_TPU_NUM_PROCESSES"])
    if process_id is None and "GEOMESA_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["GEOMESA_TPU_PROCESS_ID"])
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_coordinator() -> bool:
    """True on process 0 — and in every single-process run (the fast
    path: an uninitialized distributed runtime is process 0 of 1, and
    `jax.process_index()` answers without touching the network).

    This is the gate for shared-storage side effects — store metadata,
    device-cache manifests, sketch sidecars, SLO baselines, warmup
    manifests (gmtpu-lint GT27): exactly one host of a pod may perform
    them, or N processes race identical (or worse, divergent) writes
    into one file. Per-partition data writes stay per-host by design
    (`process_partitions`) and are waived, not gated."""
    try:
        import jax

        return int(jax.process_index()) == 0
    except Exception:
        # jax unavailable or backend not yet up: by definition not a
        # multi-process run — behave like the single-process path
        return True


def process_suffix() -> str:
    """'' in single-process runs, '.p<idx>' on a pod — appended to
    per-process debug artifacts (flight dumps) whose value is per-host,
    so hosts never collide on shared storage yet nothing is lost."""
    try:
        import jax

        if int(jax.process_count()) > 1:
            return f".p{int(jax.process_index())}"
    except Exception:
        pass
    return ""


def runtime_fingerprint() -> int:
    """A 31-bit digest of the process-local knobs that reshape every
    compiled program (the GT25 divergence surface): the effective x64
    switch, the env var that selects it, and the jax version. Two
    processes with different fingerprints would compile different
    sharded programs against the same mesh — mismatched collectives, a
    silent pod hang."""
    import hashlib

    import jax

    parts = (
        str(bool(jax.config.jax_enable_x64)),
        os.environ.get("GEOMESA_TPU_ENABLE_X64", "1"),
        jax.__version__,
    )
    digest = hashlib.sha256("|".join(parts).encode("utf-8")).digest()
    return int.from_bytes(digest[:4], "big") & 0x7FFFFFFF


def assert_uniform_runtime(mesh=None) -> None:
    """Collectively verify every process runs the same program-shaping
    configuration before any kernel dispatches: each process contributes
    its `runtime_fingerprint()` on its shard of the global mesh; a
    pmin/pmax pair then proves all contributions equal. The check itself
    is divergence-proof — it runs on fixed int32 whatever the x64 knobs
    say — so it detects exactly the drift it guards against instead of
    hanging on it. Raises RuntimeError on mismatch (the worker should
    die loudly NOW, not deadlock at the first real psum).

    Call it right after `initialize()` (parallel/launch.py does); it is
    a cheap no-op-equivalent on a single process."""
    import functools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map as _shard_map

    mesh = mesh if mesh is not None else global_mesh()
    fp = runtime_fingerprint()
    n = int(mesh.devices.size)
    host = np.full((n,), fp, np.int32)
    spec = NamedSharding(mesh, P(SHARD_AXIS))
    # every process fills only its addressable shards — the standard
    # per-host feeding idiom (launch.smoke_step's `put`)
    vals = jax.make_array_from_callback((n,), spec, lambda idx: host[idx])

    @functools.partial(_shard_map, mesh=mesh, in_specs=(P(SHARD_AXIS),),
                       out_specs=(P(), P()), check_vma=False)
    def minmax(v):
        return (jax.lax.pmin(v[0], SHARD_AXIS),
                jax.lax.pmax(v[0], SHARD_AXIS))

    lo, hi = minmax(vals)
    lo, hi = int(lo), int(hi)
    if lo != hi:
        raise RuntimeError(
            f"divergent runtime configuration across processes: "
            f"fingerprint spread [{lo}, {hi}], local {fp} (process "
            f"{jax.process_index()}/{jax.process_count()}). Check "
            f"GEOMESA_TPU_ENABLE_X64 and jax versions on every host — "
            f"divergent programs deadlock at the first collective."
        )


def global_mesh():
    """One 1-D mesh with the shard axis over every device of every host.

    jax.devices() is globally consistent across processes after
    initialize(), so each host constructs the identical mesh."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (SHARD_AXIS,))


def process_partitions(partitions, process_id=None, num_processes=None):
    """Deterministic partition->host assignment for host-local feeding:
    host i reads partitions[i::P] (the per-tablet locality analog). Same
    list on every host => disjoint, exhaustive coverage."""
    import jax

    pid = process_id if process_id is not None else jax.process_index()
    n = num_processes if num_processes is not None else jax.process_count()
    return sorted(partitions)[pid::n]
