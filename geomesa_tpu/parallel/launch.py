"""Multi-host launch harness: jax.distributed over DCN.

Parity: the reference's distributed batch compute (SURVEY.md C26) runs on
Spark/MapReduce clusters; the TPU-native equivalent is multi-host JAX — one
process per host, `jax.distributed.initialize` over the DCN coordinator,
one global Mesh spanning every host's chips, the SAME shard_map kernels as
single-host (collectives ride ICI within a slice and DCN across hosts;
SURVEY.md §5.8 commits to XLA collectives only, no NCCL/MPI).

Two entry points:

- `python -m geomesa_tpu.parallel.launch --num-processes N` (launcher):
  spawns N local processes wired to a localhost coordinator — the CI-able
  smoke test proving the multi-process path end-to-end on CPU devices
  without TPU hardware (the reference's "mini-cluster in one box" testing
  idea, §4).
- `python -m geomesa_tpu.parallel.launch --process-id I --num-processes N
  --coordinator HOST:PORT` (worker): one per real host in production; on
  TPU pods, `initialize()` with no args picks the coordinator from the
  TPU environment instead.

The smoke step runs a real sharded query step (predicate mask -> density
psum + moments psum over the global mesh) on deterministic synthetic data
and verifies the merged results against a host NumPy oracle in EVERY
process — a wrong collective cannot pass.
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_cpu() -> None:
    """Pin this process to the virtual-CPU platform (before its first
    backend use)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def smoke_step(verbose: bool = True) -> dict:
    """One sharded query step over the GLOBAL mesh; oracle-checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from geomesa_tpu.engine.density import density_sharded
    from geomesa_tpu.engine.stats import masked_moments, stats_sharded
    from geomesa_tpu.parallel.mesh import SHARD_AXIS

    devices = np.asarray(jax.devices())
    mesh = Mesh(devices, (SHARD_AXIS,))
    n = len(devices) * 512
    rng = np.random.default_rng(42)  # same seed in every process
    x = rng.uniform(-60, 60, n).astype(np.float32)
    y = rng.uniform(-45, 45, n).astype(np.float32)
    score = rng.uniform(-10, 10, n).astype(np.float32)

    spec = NamedSharding(mesh, P(SHARD_AXIS))

    def put(arr):
        # every process holds the full (deterministic) array; each
        # contributes only its addressable shards
        return jax.make_array_from_callback(
            arr.shape, spec, lambda idx: arr[idx]
        )

    gx, gy, gs = put(x), put(y), put(score)
    mask_np = (np.abs(x) < 50) & (score > 0)
    gmask = put(mask_np)

    grid = density_sharded(
        mesh, gx, gy, put(np.ones(n, np.float32)), gmask,
        (-60.0, -45.0, 60.0, 45.0), 16, 16,
    )
    c, s, ss = stats_sharded(
        mesh, lambda v, m: masked_moments(v, m), gs, gmask
    )

    # oracle check in EVERY process: psum over DCN must reproduce the
    # single-host NumPy truth
    want_count = int(mask_np.sum())
    got_mass = float(np.asarray(grid).sum())
    got_count = int(np.asarray(c))
    want_sum = float(score[mask_np].sum())
    got_sum = float(np.asarray(s))
    ok = (
        round(got_mass) == want_count
        and got_count == want_count
        and abs(got_sum - want_sum) < 1e-2
    )
    out = {
        "process": jax.process_index(),
        "processes": jax.process_count(),
        "devices": len(devices),
        "count": got_count,
        "grid_mass": got_mass,
        "ok": ok,
    }
    if verbose:
        print(f"multihost-smoke {out}", flush=True)
    if not ok:
        raise AssertionError(f"multi-host collective mismatch: {out}")
    return out


def run_worker(coordinator: str, num_processes: int, process_id: int) -> None:
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    # before any real kernel: prove every process compiled from the same
    # program-shaping config (x64 knobs, jax version) — divergent env
    # across hosts deadlocks at the first psum, invisibly (GT25); this
    # check fails loudly instead
    from geomesa_tpu.parallel.distributed import assert_uniform_runtime

    assert_uniform_runtime()
    smoke_step()


def launch_local(num_processes: int, port: int = 29511) -> int:
    """Spawn N local worker processes over a localhost coordinator (the
    2-process DCN smoke test). Returns the number of failed workers."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # each worker gets ONE cpu device so the global mesh really spans
    # processes (collectives must cross the process boundary)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = []
    for i in range(num_processes):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "geomesa_tpu.parallel.launch",
                    "--coordinator", f"127.0.0.1:{port}",
                    "--num-processes", str(num_processes),
                    "--process-id", str(i),
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    failed = 0
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=300)
        sys.stdout.write(out)
        if p.returncode != 0:
            failed += 1
            print(f"worker {i} FAILED (rc={p.returncode})", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--port", type=int, default=29511)
    args = ap.parse_args(argv)

    if args.process_id is None:
        # launcher mode: spawn the workers locally
        return launch_local(args.num_processes, args.port)
    # worker mode
    _force_cpu()
    run_worker(args.coordinator, args.num_processes, args.process_id)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
