"""The fleet supervisor: spawn N replicas, keep a router over them.

Two spawn modes, one contract:

- `spawn="thread"` — each replica is a `ReplicaServer` on in-process
  threads with its OWN DataStore over the shared catalog (separate
  queues, device caches, counters — process semantics without process
  spin-up cost). This is the CI / chaos / test mode: replica "kill -9"
  is `abort()` (sockets slammed mid-flight), and everything runs on
  CPU in seconds.
- `spawn="process"` — each replica is a separate OS process
  (`python -m geomesa_tpu.fleet.replica`), spawned with the
  `parallel/launch.py` discipline: argv carries ports/ids, the child
  prints ONE machine-readable ready line on stdout
  (`{"event": "replica_listening", "port": ...}`) that the supervisor
  parses for the ephemeral port, and logs to stderr. This is the
  deployment shape — a crash takes down one process, not the fleet.

`rolling_restart()` is the zero-downtime path `gmtpu fleet restart`
drives: one replica at a time, gated on the survivor pool's SLO budget
(a survivor whose burn gates fire pauses the roll — restarting into a
burning fleet converts a maintenance action into an outage), drained
via the admin drain verb (never a process signal), respawned, and held
until the fresh incarnation passes its warmup gate and takes traffic.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional

from geomesa_tpu.fleet.membership import Membership, ReplicaHandle
from geomesa_tpu.fleet.replica import ReplicaServer
from geomesa_tpu.fleet.router import FleetRouter


class ChipPlacementError(ValueError):
    """Process spawn on a TPU host asked for more replicas than the host
    has chips. A chip belongs to one process: an extra replica could
    only hang or fail on its first device use."""


GOOGLE_PCI_VENDOR = "0x1ae0"


def _google_device(sysfs_dir: str) -> bool:
    try:
        with open(os.path.join(sysfs_dir, "vendor")) as f:
            return f.read().strip().lower() == GOOGLE_PCI_VENDOR
    except OSError:
        return False


def local_tpu_chips(dev: str = "/dev", sysfs: str = "/sys") -> int:
    """TPU chips this host's replicas would open, counted from their
    device files without opening them: a supervisor that opened the chip
    would keep it from every replica. A chip is an `accelN` or a VFIO
    group whose PCI vendor is Google; other accelerators and VFIO
    passthrough count for nothing. 0 when JAX_PLATFORMS leaves the TPU
    out, since the replicas then never touch a chip."""
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms and "tpu" not in platforms.split(","):
        return 0
    accel = [p for p in glob.glob(os.path.join(dev, "accel[0-9]*"))
             if _google_device(os.path.join(
                 sysfs, "class", "accel", os.path.basename(p), "device"))]
    if accel:
        return len(accel)
    return sum(
        any(_google_device(d) for d in glob.glob(os.path.join(
            sysfs, "kernel", "iommu_groups", os.path.basename(p),
            "devices", "*")))
        for p in glob.glob(os.path.join(dev, "vfio", "[0-9]*")))


@dataclasses.dataclass
class FleetConfig:
    n_replicas: int = 2
    catalog: Optional[str] = None
    # thread spawn may inject a store factory instead of a catalog
    # (tests hand replicas pre-built stores over one tmp catalog)
    store_factory: Optional[Callable[[], object]] = None
    spawn: str = "thread"            # "thread" | "process"
    host: str = "127.0.0.1"
    router_port: int = 0
    warmup_manifest: Optional[str] = None
    metrics_port: Optional[int] = None   # per-replica; 0 = ephemeral
    serve_config: object = None          # ServeConfig for thread spawn
    probe_interval_s: float = 0.5
    ready_timeout_s: float = 300.0
    # rolling restart: how long to wait for the survivor pool's SLO
    # burn gates to clear before calling the roll off
    slo_gate_timeout_s: float = 30.0
    # False reverts the router to the pre-upgrade shape: subscribe
    # verbs refuse typed and the hello advertises no rehome capability
    rehome: bool = True
    force_cpu_workers: bool = False      # process spawn: pin CPU (CI)

    def __post_init__(self):
        if self.spawn not in ("thread", "process"):
            raise ValueError(
                f"spawn must be 'thread' or 'process', got {self.spawn!r}")
        if self.catalog is None and self.store_factory is None:
            raise ValueError("FleetConfig needs a catalog "
                             "or a store_factory")
        if self.spawn == "process" and self.catalog is None:
            raise ValueError("process spawn needs a catalog path")


class FleetSupervisor:
    """Owns the replica set and the router. `start()` returns the
    router's client port; `close()` drains everything."""

    def __init__(self, config: FleetConfig):
        self.config = config
        self.membership = Membership()
        self.router = FleetRouter(
            self.membership, host=config.host,
            port=config.router_port,
            probe_interval_s=config.probe_interval_s,
            supervisor=self, rehome=config.rehome)
        self._slots = 0
        self._lock = threading.Lock()
        # process replicas on a TPU host get one chip each (slot i ->
        # chip i); 0 = no chips to place (CPU host, CPU-pinned workers
        # or thread spawn)
        self._chips = (
            local_tpu_chips()
            if config.spawn == "process" and not config.force_cpu_workers
            else 0)

    # -- lifecycle ---------------------------------------------------------

    def start(self, wait_ready: bool = True) -> int:
        self._check_chip(self.config.n_replicas - 1)
        port = self.router.start()
        for _ in range(self.config.n_replicas):
            self.spawn_replica()
        if wait_ready:
            self.wait_ready()
        return port

    def close(self) -> None:
        for h in self.membership.all():
            try:
                self._stop_replica(h, graceful=True)
            except Exception:  # noqa: BLE001 — close everything we can
                pass
        self.router.stop()

    # -- spawning ----------------------------------------------------------

    def spawn_replica(self) -> ReplicaHandle:
        """One new replica incarnation: spawn, register, dial."""
        with self._lock:
            slot = self._slots
            self._slots += 1
        return self._spawn_into(slot, incarnation=0)

    def _spawn_into(self, slot: int, incarnation: int) -> ReplicaHandle:
        rid = (f"r{slot}" if incarnation == 0
               else f"r{slot}.{incarnation}")
        if self.config.spawn == "thread":
            handle = self._spawn_thread(rid)
        else:
            handle = self._spawn_process(rid, slot)
        handle.slot = slot
        handle.incarnation = incarnation
        self.membership.add(handle)
        self.router.attach(handle)
        return handle

    def _store_factory(self):
        if self.config.store_factory is not None:
            return self.config.store_factory
        catalog = self.config.catalog

        def make():
            from geomesa_tpu.plan.datastore import DataStore

            return DataStore(catalog, use_device_cache=True)

        return make

    def _spawn_thread(self, rid: str) -> ReplicaHandle:
        server = ReplicaServer(
            self._store_factory(), self.config.serve_config,
            replica_id=rid, host=self.config.host, port=0,
            warmup_manifest=self.config.warmup_manifest,
            metrics_port=self.config.metrics_port)
        port = server.start()
        return ReplicaHandle(
            replica_id=rid, host=self.config.host, port=port,
            spawn="thread", server=server)

    def _check_chip(self, slot: int) -> None:
        if self._chips and slot >= self._chips:
            raise ChipPlacementError(
                f"replica slot {slot} needs chip {slot}, but this host "
                f"has {self._chips} TPU chip(s): run at most "
                f"{self._chips} process replicas here, or spawn threads")

    def _spawn_process(self, rid: str, slot: int) -> ReplicaHandle:
        self._check_chip(slot)
        cmd = [sys.executable, "-m", "geomesa_tpu.fleet.replica",
               "--catalog", self.config.catalog,
               "--replica-id", rid,
               "--host", self.config.host, "--port", "0"]
        if self.config.warmup_manifest:
            cmd += ["--warmup", self.config.warmup_manifest]
        if self.config.metrics_port is not None:
            cmd += ["--metrics-port", str(self.config.metrics_port)]
        if self.config.force_cpu_workers:
            cmd += ["--force-cpu"]
        env = None
        if self._chips:
            # this replica's own chip, as a one-chip slice
            env = dict(os.environ, TPU_VISIBLE_CHIPS=str(slot),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        # spawn contract (parallel/launch.py discipline): the child's
        # FIRST stdout line reports its ephemeral port
        line = proc.stdout.readline()
        try:
            ready = json.loads(line)
            port = int(ready["port"])
        except (ValueError, KeyError, TypeError):
            proc.kill()
            raise RuntimeError(
                f"replica {rid} did not print a ready line "
                f"(got {line!r})")
        return ReplicaHandle(
            replica_id=rid, host=self.config.host, port=port,
            pid=proc.pid, spawn="process", proc=proc,
            metrics_port=ready.get("metrics_port"))

    # -- waiting -----------------------------------------------------------

    def wait_ready(self, timeout_s: Optional[float] = None) -> None:
        """Block until EVERY replica is routable (the warmup gate
        included); raises on timeout or on any replica dying during
        spin-up — a fleet that comes up partial must fail loudly at
        start, not quietly serve a fraction of the requested
        capacity."""
        deadline = time.monotonic() + (
            timeout_s if timeout_s is not None
            else self.config.ready_timeout_s)
        while time.monotonic() < deadline:
            handles = self.membership.all()
            states = [h.state for h in handles]
            if any(s == "dead" for s in states):
                errors = [(h.replica_id,
                           getattr(h.server, "error", None))
                          for h in handles if h.state == "dead"]
                raise RuntimeError(
                    f"replica(s) died during fleet spin-up: {errors}")
            if states and all(s in ("ready", "degraded")
                              for s in states):
                return
            time.sleep(0.02)
        raise TimeoutError(
            f"fleet not ready: "
            f"{[(h.replica_id, h.state) for h in self.membership.all()]}")

    # -- kill / restart ----------------------------------------------------

    def kill_replica(self, replica_id: str,
                     graceful: bool = False) -> None:
        """Scripted replica death. graceful=False is the chaos path:
        kill -9 for process replicas, `abort()` (sockets slammed
        mid-flight) for thread replicas — failover is the router's
        problem, which is what the certification asserts."""
        h = self.membership.get(replica_id)
        if h is None:
            raise KeyError(f"no replica {replica_id!r}")
        self._stop_replica(h, graceful=graceful)

    def _stop_replica(self, h: ReplicaHandle, graceful: bool) -> None:
        if graceful:
            self._drain_via_wire(h)
        if h.spawn == "thread" and h.server is not None:
            if graceful:
                h.server.stop()
            else:
                h.server.abort()
        elif h.proc is not None:
            if graceful:
                try:
                    h.proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    h.proc.kill()
            else:
                h.proc.kill()
                h.proc.wait(timeout=30)
        if h.link is not None:
            h.link.close()
        self.membership.transition(h.replica_id, "dead", "stopped")

    def _drain_via_wire(self, h: ReplicaHandle) -> dict:
        """The admin drain verb over a fresh admin connection — no
        process signals, so thread and process replicas drain through
        the identical code path the protocol tests pin down."""
        from geomesa_tpu.fleet.router import FleetClient

        try:
            cli = FleetClient(h.host, h.port)
        except OSError:
            return {"drained": False, "error": "unreachable"}
        try:
            cli.hello(role="admin")
            return cli.request({"op": "drain"}, timeout_s=60.0)
        except (OSError, TimeoutError) as e:
            return {"drained": False, "error": str(e)}
        finally:
            cli.close()

    def respawn(self, replica_id: str) -> ReplicaHandle:
        """A fresh incarnation in a dead replica's slot (new id, same
        slot) — the dead handle stays in membership as the postmortem
        record."""
        old = self.membership.get(replica_id)
        if old is None:
            raise KeyError(f"no replica {replica_id!r}")
        if old.state != "dead":
            raise RuntimeError(
                f"replica {replica_id} is {old.state}; kill or drain "
                f"it before respawning")
        return self._spawn_into(old.slot, old.incarnation + 1)

    def rolling_restart(self) -> dict:
        """Drain one replica at a time; gate each step on the survivor
        pool's SLO budget; respawn and wait for the warmup gate before
        touching the next. Returns a typed summary (the `gmtpu fleet
        restart` document)."""
        rolled: List[dict] = []
        targets = [h for h in self.membership.all()
                   if h.state in ("ready", "degraded")]
        for h in targets:
            if not self._await_survivor_budget(exclude=h.replica_id):
                return {"ok": False, "rolled": rolled,
                        "error": "survivor pool burning its SLO "
                                 "budget; roll paused — retry when "
                                 "the budget recovers",
                        "blocked_on": h.replica_id}
            # subscription drain BEFORE the query drain: standing
            # queries move to survivors via fresh exported snapshots
            # (strictly fresher than the probe checkpoints), so the
            # restart costs each client at most one state resync
            subs = {"moved": 0, "failed": 0}
            if getattr(self.router, "rehome", False):
                subs = self.router.rehome_replica(h.replica_id)
            self._stop_replica(h, graceful=True)
            fresh = self.respawn(h.replica_id)
            state = self._wait_replica_ready(fresh)
            rolled.append({"old": h.replica_id,
                           "new": fresh.replica_id, "state": state,
                           "subs": subs})
            if state != "ready":
                return {"ok": False, "rolled": rolled,
                        "error": f"fresh replica {fresh.replica_id} "
                                 f"came up {state}; roll stopped "
                                 f"before touching the next survivor"}
        return {"ok": True, "rolled": rolled}

    def _wait_replica_ready(self, h: ReplicaHandle) -> str:
        deadline = time.monotonic() + self.config.ready_timeout_s
        while time.monotonic() < deadline:
            if h.state in ("ready", "degraded", "dead"):
                return h.state
            time.sleep(0.02)
        return h.state

    def _await_survivor_budget(self, exclude: str) -> bool:
        """True once every OTHER routable replica is ready with its
        burn gates quiet (the probes keep `burn_gated` fresh); False
        if the gate never clears within the timeout."""
        deadline = time.monotonic() + self.config.slo_gate_timeout_s
        while time.monotonic() < deadline:
            survivors = [
                h for h in self.membership.all()
                if h.replica_id != exclude
                and h.state in ("ready", "degraded")]
            if survivors and all(
                    h.state == "ready" and not h.burn_gated
                    for h in survivors):
                return True
            time.sleep(self.config.probe_interval_s)
        return False

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        return self.router.stats()
