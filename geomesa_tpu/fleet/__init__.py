"""Replica fleets: a fault-tolerant serving tier over N QueryService
processes (docs/SERVING.md "Replica fleets", docs/ROBUSTNESS.md
"Replica fleets").

The single-process serve stack already has everything a fleet needs —
zero-recompile spin-up (warmup manifests), typed failover semantics
(the fault fabric), per-process SLO burn export. This package composes
them:

- `ReplicaServer` (replica.py): one `QueryService` behind a TCP
  JSON-lines listener with a typed health state machine (starting →
  warming → ready → draining → dead). A fresh replica refuses traffic
  with a typed, retryable rejection until its warmup manifest replays
  with `gmtpu warmup --check` semantics (zero residual recompiles).
- `FleetRouter` (router.py): a thin router speaking the existing wire
  protocol. Per-request routing is shard-affinity (rendezvous hash, so
  a query lands where its compiled shapes and cache lines are warm) →
  least-loaded → SLO-burn-aware (a replica whose fast+slow burn gates
  fire sheds traffic to healthy peers). Replica death triggers
  drain-then-redistribute: in-flight requests fail typed as retryable
  `unavailable` and idempotent ones are retried ONCE on a healthy peer
  within their deadline — never silently dropped.
- `FleetSupervisor` (supervisor.py): spawns the replicas (in-process
  threads for CI/chaos, separate OS processes via the
  `parallel/launch.py` spawn discipline for real deployments), runs
  health probes, and drives `gmtpu fleet restart` — a rolling restart
  draining one replica at a time, gated on the survivor pool's SLO
  budget.
- `Membership` (membership.py): the shared replica table + the
  router-side `fleet.*` gauges (per-replica state, routed/retried/shed
  counters).

Certification: `gmtpu chaos --fleet` (faults/chaos.py) kills a replica
mid-burst and asserts zero un-typed client errors and zero
double-executed work.
"""

from geomesa_tpu.fleet.health import (
    REPLICA_STATES, ReplicaStateError, state_number, validate_transition)
from geomesa_tpu.fleet.membership import Membership, ReplicaHandle
from geomesa_tpu.fleet.replica import ReplicaServer
from geomesa_tpu.fleet.router import FleetClient, FleetRouter
from geomesa_tpu.fleet.supervisor import FleetConfig, FleetSupervisor

__all__ = [
    "REPLICA_STATES", "ReplicaStateError", "state_number",
    "validate_transition", "Membership", "ReplicaHandle",
    "ReplicaServer", "FleetRouter", "FleetClient", "FleetConfig",
    "FleetSupervisor",
]
