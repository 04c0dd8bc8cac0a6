"""`gmtpu chaos`: run a serve workload under a fault plan and prove the
recovery invariants hold.

The runner synthesizes (or opens) a store, starts a QueryService, and
drives a DETERMINISTIC sequential workload — FS counts/kNN/feature
fetches, FS writes, Kafka live-layer writes and polls, a compile-cache
enable — with the given FaultPlan installed. Sequential submission plus
coalescing-off config keeps every site's call sequence reproducible, so
the same plan+seed injects the same faults at the same calls; `--check`
replays the run and diffs the fire logs to prove it.

Invariants asserted (the acceptance contract, docs/ROBUSTNESS.md):

  1. zero un-typed escapes: every request resolves with a result or an
     error the classification recognizes (QueryRejected / QueryTimeout /
     BreakerOpen / OSError-family / FaultInjected ...);
  2. zero torn manifests: after the run, metadata.json parses and every
     entry references an existing data file with a matching row count;
  3. injected coverage: every deterministic rule (nth_call / every) in
     the plan actually fired;
  4. breaker visibility: each dependency the plan names in
     `expect_breakers` shows open AND half-open transitions in metrics
     (the runner shrinks reset timeouts so the full closed -> open ->
     half-open -> closed cycle plays out in-process);
  5. graceful drain still completes and the dispatch thread survives;
  6. disabled-harness overhead: the no-op site check stays sub-µs-ish
     (bounded loosely so CI noise cannot flake it).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from geomesa_tpu.faults import errors as _errors
from geomesa_tpu.faults import harness as _harness
from geomesa_tpu.faults.breaker import BREAKERS
from geomesa_tpu.faults.plan import FaultPlan

# dependencies whose breakers the runner re-configures for fast
# in-process open -> half-open -> close cycles. reset_timeout_s=0 makes
# every open -> half-open transition happen on the NEXT gate instead of
# after a wall-clock wait: the full cycle still exercises all three
# states AND the fire sequence stays independent of run timing (run 1
# pays jit compiles, the replay doesn't — a real timeout would make the
# two runs' probe schedules diverge and break replay determinism)
_DEPS = ("storage", "kafka", "device")
_CHAOS_BREAKER = dict(failure_threshold=3, reset_timeout_s=0.0,
                      half_open_max=1)
_NOOP_CALLS = 200_000
_NOOP_BUDGET_US = 5.0  # per-call bound; a no-op attr check is ~0.1µs


@dataclasses.dataclass
class ChaosReport:
    requests: int = 0
    ok: int = 0
    typed_errors: Dict[str, int] = dataclasses.field(default_factory=dict)
    untyped_errors: List[str] = dataclasses.field(default_factory=list)
    writes_ok: int = 0
    writes_failed: int = 0
    fires: int = 0
    fired_sites: List[str] = dataclasses.field(default_factory=list)
    breaker_counters: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    replay_match: Optional[bool] = None
    noop_us_per_call: float = 0.0
    invariant_failures: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok_overall(self) -> bool:
        return not self.invariant_failures

    def to_json(self) -> dict:
        doc = dataclasses.asdict(self)
        # `ok` in the JSON is the invariant VERDICT (what --check exits
        # on); the per-request success count moves to `requests_ok` so
        # the two never shadow each other
        doc["requests_ok"] = doc.pop("ok")
        doc["ok"] = self.ok_overall
        return doc


def _synth_store(root: str, n: int = 384, seed: int = 5,
                 use_device_cache: bool = False):
    """A small FS store on the SCAN path (no device cache): every query
    re-reads partition files, so storage faults keep biting. The mesh
    phase flips `use_device_cache` on — mesh residency is a device-cache
    tier."""
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore

    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "chaos", "name:String,score:Double,dtg:Date,*geom:Point")
    store = DataStore(root, use_device_cache=use_device_cache)
    src = store.create_schema(sft)
    src.write(_synth_batch(sft, rng, n))
    return store, sft


def _synth_batch(sft, rng, n):
    from geomesa_tpu.core.columnar import FeatureBatch

    # one-day dtg window -> one date partition (a handful of files, not
    # one per day: the workload's read sequence stays small and exact)
    return FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_590_080_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })


def _check_manifest(root: str, type_name: str, failures: List[str]) -> None:
    import pyarrow.parquet as pq

    meta_path = os.path.join(root, type_name, "metadata.json")
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except Exception as e:  # torn / unparseable manifest IS the failure
        failures.append(f"manifest unreadable: {e}")
        return
    for pname, entries in meta.get("manifest", {}).items():
        for entry in entries:
            path = os.path.join(root, type_name, pname, entry["file"])
            if not os.path.exists(path):
                failures.append(
                    f"manifest references missing file {path}")
                continue
            try:
                rows = pq.read_metadata(path).num_rows
            except Exception as e:
                failures.append(f"unreadable data file {path}: {e}")
                continue
            if rows != entry["count"]:
                failures.append(
                    f"manifest count {entry['count']} != file rows "
                    f"{rows} for {path}")


def _run_workload(plan: FaultPlan, root: str, requests: int,
                  report: ChaosReport, say) -> List[tuple]:
    """One seeded pass: build stores, serve the request mix under the
    installed harness, close, validate the manifest. Returns the fire
    log (the replay-determinism artifact)."""
    from geomesa_tpu.compilecache.persist import persistent_cache_dir
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.kafka.store import KafkaDataStore
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    store, sft = _synth_store(os.path.join(root, "cat"))
    live_sft = SimpleFeatureType.from_spec(
        "chaos_live", "name:String,*geom:Point")
    kstore = KafkaDataStore()
    ksrc = kstore.create_schema(live_sft)
    rng = np.random.default_rng(plan.seed + 17)
    qpts = rng.uniform(-60, 60, (requests, 2))
    cql = "BBOX(geom, -170, -80, 170, 80)"
    prior_cache = persistent_cache_dir()

    prior_breakers = {name: BREAKERS.current_config(name)
                      for name in _DEPS}
    svc = None

    def outcome(fn):
        report.requests += 1
        try:
            fn()
            report.ok += 1
        except Exception as e:  # noqa: BLE001 — the classification decides
            if _errors.is_typed(e):
                key = type(e).__name__
                report.typed_errors[key] = (
                    report.typed_errors.get(key, 0) + 1)
            else:
                report.untyped_errors.append(f"{type(e).__name__}: {e}")

    # everything that mutates process-wide state (breaker tuning, the
    # service's dispatch thread, the harness) happens INSIDE this try:
    # a setup failure — e.g. another harness already installed — must
    # not leak chaos breakers or an orphaned dispatcher into the process
    try:
        for name in _DEPS:
            BREAKERS.configure(name, **_CHAOS_BREAKER)
        svc = QueryService(store, ServeConfig(
            max_wait_ms=0.0, max_batch=1, drain_timeout_s=30.0))
        log = _drive(plan, root, requests, report, svc, store, sft,
                     kstore, ksrc, qpts, cql, rng, outcome)
    finally:
        if svc is not None:
            try:
                svc.close(drain=False)
            except Exception:
                pass
        for name in _DEPS:
            # hand back whatever tuning the process had, not the
            # constructor defaults
            BREAKERS.restore_config(name, prior_breakers[name])
        # cache restore runs HERE — after _drive's harness context has
        # exited — so a plan injecting at compilecache.persist cannot
        # swallow the restore (enable degrades to None under injection
        # by contract). prior_cache came from persistent_cache_dir(),
        # which is ALREADY platform-suffixed: per_platform=False, or
        # the restore would point jax at <dir>/<backend>/<backend> and
        # orphan every previously persisted executable.
        from geomesa_tpu.compilecache.persist import (
            disable_persistent_cache, enable_persistent_cache)

        disable_persistent_cache()
        if prior_cache is not None:
            enable_persistent_cache(cache_dir=prior_cache,
                                    per_platform=False, force=True)
    _check_manifest(os.path.join(root, "cat"), "chaos",
                    report.invariant_failures)
    # pipeline-drain phase: a device.transfer fault fired MID-pipeline
    # (other windows in flight) must fail only its own window — typed —
    # while every other in-flight window drains cleanly. Runs in its
    # own harness scope (per-activation site counters keep it
    # deterministic regardless of the legacy phase's call counts); its
    # fires append to the returned log so the replay diff covers it.
    log += _pipeline_burst(plan, root, report, say)
    # standing-query phase: an injected kafka.poll outage must surface
    # TYPED from the poll, and the subscription event streams must show
    # zero missed / zero double-applied events across the outage — the
    # failed window's messages arrive exactly once when the broker
    # heals (offset-pinned fold + retained delta buffer). Own harness
    # scope; fires append to the replay-diffed log.
    log += _subscribe_phase(plan, report, say)
    # sharded-serving phase: a single-shard device.transfer outage
    # during a sharded window fails only that window — typed — while
    # the mesh keeps dispatching ONE-program windows (the breaker/
    # retry fabric is per-dependency, not a per-chip meltdown). Own
    # harness scope; fires append to the replay-diffed log.
    log += _mesh_phase(plan, root, report, say)
    say(f"workload: {report.ok}/{report.requests} ok, "
        f"typed={sum(report.typed_errors.values())}, "
        f"untyped={len(report.untyped_errors)}, "
        f"fires={len(log)}")
    return log


# burst shape: 6 single-request kNN windows through the pipeline
# (max_batch=1 keeps windows singleton => the stager's device.transfer
# fires land at deterministic call indices), with window 3's transfer
# failed through ALL retry attempts (the device RetryPolicy makes 3) —
# calls 5, 6, 7 at the site: windows 1-2 fire stage+scan-upload (2
# calls each), window 3's stage then retries twice more
_BURST_REQUESTS = 6
_BURST_FAULT_CALLS = (5, 6, 7)


def _pipeline_burst(plan: FaultPlan, root: str, report: ChaosReport,
                    say) -> List[tuple]:
    from geomesa_tpu.faults.plan import FaultRule
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    # same row count as the legacy phase's store: the padded batch hits
    # the SAME pow2 kernel bucket, so the burst re-uses warm compiles
    # instead of adding a shape to every seeded run's wall time
    store, sft = _synth_store(os.path.join(root, "burst"), n=384,
                              seed=plan.seed + 29)
    rng = np.random.default_rng(plan.seed + 31)
    qpts = rng.uniform(-60, 60, (_BURST_REQUESTS, 2))
    cql = "BBOX(geom, -170, -80, 170, 80)"
    svc = QueryService(store, ServeConfig(
        max_wait_ms=0.0, max_batch=1, drain_timeout_s=30.0))
    burst_plan = FaultPlan(
        seed=plan.seed + 37,
        rules=[FaultRule(site="device.transfer", error="unavailable",
                         nth_call=c) for c in _BURST_FAULT_CALLS])
    try:
        # warm OUTSIDE the harness: compiles and first-read I/O must not
        # consume injected calls (run 2's warm in-process caches would
        # otherwise shift the fire schedule and break replay)
        svc.knn("chaos", cql, qpts[0:1, 0], qpts[0:1, 1],
                k=5, timeout_ms=60_000).result(120)
        ok = typed = 0
        with _harness.active(burst_plan) as h:
            futs = [svc.knn("chaos", cql, qpts[i:i + 1, 0],
                            qpts[i:i + 1, 1], k=5, timeout_ms=60_000)
                    for i in range(_BURST_REQUESTS)]
            for f in futs:
                report.requests += 1
                try:
                    f.result(timeout=120)
                    ok += 1
                    report.ok += 1
                except Exception as e:  # noqa: BLE001 — classification decides
                    if _errors.is_typed(e):
                        typed += 1
                        key = type(e).__name__
                        report.typed_errors[key] = (
                            report.typed_errors.get(key, 0) + 1)
                    else:
                        report.untyped_errors.append(
                            f"burst: {type(e).__name__}: {e}")
            svc.close(drain=True)
            blog = h.fire_log()
        pstats = (svc.stats().get("pipeline") or {})
        if len(blog) != len(_BURST_FAULT_CALLS):
            report.invariant_failures.append(
                f"pipeline burst: expected {len(_BURST_FAULT_CALLS)} "
                f"device.transfer fires, saw {len(blog)}")
        if typed != 1 or ok != _BURST_REQUESTS - 1:
            report.invariant_failures.append(
                f"pipeline burst: faulted window must fail alone and "
                f"typed (ok={ok}, typed={typed} of {_BURST_REQUESTS})")
        if pstats.get("inflight", 0) != 0:
            report.invariant_failures.append(
                "pipeline burst: windows still in flight after drain")
        if svc._worker is not None and svc._worker.is_alive():
            report.invariant_failures.append(
                "pipeline burst: dispatch thread alive after drain")
        say(f"pipeline burst: {ok} ok / {typed} typed, "
            f"max_inflight={pstats.get('max_inflight')}, "
            f"fires={len(blog)}")
        return blog
    finally:
        try:
            svc.close(drain=False)
        except Exception:
            pass


# standing-query phase shape: 2 subscriptions (a bbox geofence + a tiny
# density window) over a 6-feature moving fleet. The kafka retry policy
# makes 4 attempts, so every=1 + max_fires=4 exhausts the FIRST poll's
# retries (typed error, no fold) and leaves the second poll clean — it
# folds the outage window's messages exactly once.
_SUB_ROWS = 6
_SUB_FAULT_FIRES = 4


def _subscribe_phase(plan: FaultPlan, report: ChaosReport,
                     say) -> List[tuple]:
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.faults.plan import FaultRule
    from geomesa_tpu.kafka.store import KafkaDataStore
    from geomesa_tpu.subscribe import DensityWindow, SubscriptionManager

    sft = SimpleFeatureType.from_spec("chaos_sub", "name:String,*geom:Point")
    store = KafkaDataStore()
    store.create_schema(sft)
    mgr = SubscriptionManager(store)
    bbox = (-20.0, -20.0, 20.0, 20.0)

    def make_batch(i: int) -> FeatureBatch:
        rng = np.random.default_rng(plan.seed + 53 + i)
        return FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], _SUB_ROWS).tolist(),
            "geom": np.stack([rng.uniform(-50, 50, _SUB_ROWS),
                              rng.uniform(-30, 30, _SUB_ROWS)], 1),
        }, fids=[f"v{j}" for j in range(_SUB_ROWS)])

    rows: Dict[str, tuple] = {}  # fid -> (x, y): the host oracle

    def note_rows(batch):
        xs = batch.columns["geom"].x
        ys = batch.columns["geom"].y
        for j, fid in enumerate(batch.fids.decode()):
            rows[str(fid)] = (float(xs[j]), float(ys[j]))

    def oracle_matched():
        return {fid for fid, (x, y) in rows.items()
                if bbox[0] <= x <= bbox[2] and bbox[1] <= y <= bbox[3]}

    frames: List[dict] = []
    geo = mgr.subscribe("chaos_sub", f"BBOX(geom, {bbox[0]}, {bbox[1]}, "
                                     f"{bbox[2]}, {bbox[3]})",
                        initial_state=False)
    mgr.subscribe("chaos_sub",
                  density=DensityWindow((-60.0, -30.0, 60.0, 30.0), 8, 4),
                  initial_state=False)

    def replayed_matched() -> set:
        """Fold the pushed enter/exit stream in seq order — the event
        log must reconstruct the matched set exactly (zero missed /
        duplicate / phantom transitions)."""
        state: set = set()
        for f in sorted((f for f in frames
                         if f.get("subscription") == geo.sub_id
                         and f["event"] in ("enter", "exit")),
                        key=lambda f: f["seq"]):
            fids = set(f["fids"])
            if f["event"] == "enter":
                if fids & state:
                    report.invariant_failures.append(
                        f"subscribe phase: duplicate enter {fids & state}")
                state |= fids
            else:
                if fids - state:
                    report.invariant_failures.append(
                        f"subscribe phase: phantom exit {fids - state}")
                state -= fids
        return state

    # warm fold OUTSIDE the harness (fused-kernel compile must not
    # consume injected calls — replay determinism, as in the burst)
    b0 = make_batch(0)
    store.write("chaos_sub", b0)
    note_rows(b0)
    store.poll("chaos_sub")
    mgr.flush(frames.append)
    if replayed_matched() != oracle_matched():
        report.invariant_failures.append(
            "subscribe phase: warm fold diverged from the host oracle")
    sub_plan = FaultPlan(
        seed=plan.seed + 59,
        rules=[FaultRule(site="kafka.poll", error="unavailable",
                         every=1, max_fires=_SUB_FAULT_FIRES)])
    base_ev = mgr.evaluator.stats()
    # pin the kafka breaker to the chaos tuning for the injected
    # outage (same as the main workload — which RESTORED the
    # process's prior config before this phase runs): an ambient
    # threshold <= the 4 injected failures would open mid-retry,
    # yielding BreakerOpen instead of the expected typed poll error
    # and a fire-count short-fall
    prior_kafka = BREAKERS.current_config("kafka")
    BREAKERS.configure("kafka", **_CHAOS_BREAKER)
    try:
        with _harness.active(sub_plan) as h:
            b1 = make_batch(1)
            store.write("chaos_sub", b1)
            report.requests += 1
            try:
                store.poll("chaos_sub")  # all 4 retry attempts injected
                report.invariant_failures.append(
                    "subscribe phase: injected kafka.poll outage did not "
                    "surface from the poll")
            except Exception as e:  # noqa: BLE001 — the classification decides
                # typed errors are recorded but NOT counted ok — same
                # accounting as outcome() and the pipeline burst
                if _errors.is_typed(e):
                    key = type(e).__name__
                    report.typed_errors[key] = (
                        report.typed_errors.get(key, 0) + 1)
                else:
                    report.untyped_errors.append(
                        f"subscribe poll: {type(e).__name__}: {e}")
            mgr.flush(frames.append)
            if replayed_matched() != oracle_matched():
                # the failed poll must not have half-applied the window
                report.invariant_failures.append(
                    "subscribe phase: failed poll leaked events")
            note_rows(b1)
            b2 = make_batch(2)
            store.write("chaos_sub", b2)
            note_rows(b2)
            store.poll("chaos_sub")  # heals: folds BOTH windows, once
            mgr.flush(frames.append)
            blog = h.fire_log()
    finally:
        BREAKERS.restore_config("kafka", prior_kafka)
        # the injected outage must not outlive the phase
        BREAKERS.reset("kafka")
    ev = mgr.evaluator.stats()
    if replayed_matched() != oracle_matched():
        report.invariant_failures.append(
            "subscribe phase: post-outage matched set diverged "
            "(missed or double-applied events)")
    # one committed fold with one dispatch per evaluation path: the
    # healed poll folds BOTH windows once and dispatches the bbox
    # geofence's lane plus the fused remainder carrying the density
    # window (docs/SERVING.md "Standing queries" lanes) — the faulted
    # poll never folded
    folds = ev["folds"] - base_ev["folds"]
    dispatches = ev["dispatches"] - base_ev["dispatches"]
    lane_disp = (ev.get("lane_dispatches", 0)
                 - base_ev.get("lane_dispatches", 0))
    if folds != 1 or dispatches != 2 or lane_disp != 1:
        report.invariant_failures.append(
            f"subscribe phase: expected 1 in-harness fold with one "
            f"lane + one fused dispatch (the healed poll), saw "
            f"folds={folds} dispatches={dispatches} "
            f"lane_dispatches={lane_disp}")
    if len(blog) != _SUB_FAULT_FIRES:
        report.invariant_failures.append(
            f"subscribe phase: expected {_SUB_FAULT_FIRES} kafka.poll "
            f"fires, saw {len(blog)}")
    mgr.close()
    say(f"subscribe phase: {len(frames)} frames, matched oracle ok, "
        f"fires={len(blog)}")
    return blog


# sharded-serving phase shape (docs/SERVING.md "Sharded serving"): 6
# singleton kNN windows through the pipelined MESH service (auto mesh
# over every local device, mesh residency on). Each window's only
# device.transfer call is its staged query upload, so window 3's
# transfer faulted through all 3 retry attempts = in-harness calls
# 3, 4, 5 at the site — modelling one shard's host->device transfer
# failing mid-window.
_MESH_REQUESTS = 6
_MESH_FAULT_CALLS = (3, 4, 5)


def _mesh_phase(plan: FaultPlan, root: str, report: ChaosReport,
                say) -> List[tuple]:
    """A single-shard device.transfer outage during a SHARDED window
    fails only that window — typed — and the mesh keeps serving: the
    breaker/retry fabric applies per-dependency, never as a per-chip
    meltdown (no degrade to single-chip, no dead dispatcher). Own
    harness scope; fires append to the replay-diffed log."""
    import jax

    from geomesa_tpu.faults.plan import FaultRule
    from geomesa_tpu.serve.service import QueryService, ServeConfig
    from geomesa_tpu.utils.metrics import metrics

    def mesh_dispatch_count() -> float:
        # windows that ran a mesh route: whole-mesh programs plus
        # shard-affinity local windows
        return (metrics.count("knn.mesh.dispatches")
                + metrics.count("knn.mesh.local_dispatches"))

    if len(jax.devices()) < 2:
        say("mesh phase: skipped (single device — no mesh to shard)")
        return []
    store, sft = _synth_store(os.path.join(root, "mesh"), n=384,
                              seed=plan.seed + 41, use_device_cache=True)
    rng = np.random.default_rng(plan.seed + 43)
    qpts = rng.uniform(-60, 60, (_MESH_REQUESTS, 2))
    cql = "BBOX(geom, -170, -80, 170, 80)"
    svc = QueryService(store, ServeConfig(
        max_wait_ms=0.0, max_batch=1, drain_timeout_s=30.0,
        mesh="auto"))
    mesh_d = int(svc.mesh.devices.size) if svc.mesh is not None else 0
    mesh_plan = FaultPlan(
        seed=plan.seed + 47,
        rules=[FaultRule(site="device.transfer", error="unavailable",
                         nth_call=c) for c in _MESH_FAULT_CALLS])

    try:
        # warm OUTSIDE the harness: the mesh program compile, the
        # sharded residency upload, and the stager's first slot must
        # not consume injected calls (replay determinism)
        svc.knn("chaos", cql, qpts[0:1, 0], qpts[0:1, 1],
                k=5, timeout_ms=60_000).result(120)
        base_mesh = mesh_dispatch_count()
        ok = typed = 0
        with _harness.active(mesh_plan) as h:
            futs = [svc.knn("chaos", cql, qpts[i:i + 1, 0],
                            qpts[i:i + 1, 1], k=5, timeout_ms=60_000)
                    for i in range(_MESH_REQUESTS)]
            for f in futs:
                report.requests += 1
                try:
                    f.result(timeout=120)
                    ok += 1
                    report.ok += 1
                except Exception as e:  # noqa: BLE001 — classification decides
                    if _errors.is_typed(e):
                        typed += 1
                        key = type(e).__name__
                        report.typed_errors[key] = (
                            report.typed_errors.get(key, 0) + 1)
                    else:
                        report.untyped_errors.append(
                            f"mesh: {type(e).__name__}: {e}")
            svc.close(drain=True)
            blog = h.fire_log()
        if len(blog) != len(_MESH_FAULT_CALLS):
            report.invariant_failures.append(
                f"mesh phase: expected {len(_MESH_FAULT_CALLS)} "
                f"device.transfer fires, saw {len(blog)}")
        if typed != 1 or ok != _MESH_REQUESTS - 1:
            report.invariant_failures.append(
                f"mesh phase: the faulted sharded window must fail "
                f"alone and typed (ok={ok}, typed={typed} of "
                f"{_MESH_REQUESTS})")
        # no per-chip meltdown: every surviving window still ran the
        # ONE-program mesh route (the outage neither wedged the mesh
        # nor silently degraded the service to single-chip)
        survived = mesh_dispatch_count() - base_mesh
        if survived != _MESH_REQUESTS - 1:
            report.invariant_failures.append(
                f"mesh phase: expected {_MESH_REQUESTS - 1} sharded "
                f"dispatches around the outage, saw {survived:.0f}")
        if svc._worker is not None and svc._worker.is_alive():
            report.invariant_failures.append(
                "mesh phase: dispatch thread alive after drain")
        say(f"mesh phase: {ok} ok / {typed} typed over a {mesh_d}-chip "
            f"mesh, fires={len(blog)}")
        return blog
    finally:
        try:
            svc.close(drain=False)
        except Exception:
            pass


def _drive(plan, root, requests, report, svc, store, sft, kstore, ksrc,
           qpts, cql, rng, outcome) -> List[tuple]:
    """The harness-scoped middle of one chaos pass: enable the compile
    cache under injection, serve the request mix, interleave writers,
    drain; returns the fire log. Cache/breaker restoration is the
    CALLER's job, outside the harness scope."""
    from geomesa_tpu.compilecache.persist import enable_persistent_cache

    with _harness.active(plan) as h:
        try:
            # compile-cache boundary: an injected failure must DEGRADE
            # (enable returns None), never raise
            cache_dir = os.path.join(root, "jaxcache")
            try:
                enable_persistent_cache(cache_dir=cache_dir, force=True)
                enable_persistent_cache(cache_dir=cache_dir, force=True)
            except Exception as e:  # noqa: BLE001 — contract violation
                report.untyped_errors.append(
                    f"compile-cache enable raised: {type(e).__name__}")
            for i in range(requests):
                op = i % 4
                if op == 0:
                    outcome(lambda: svc.count(
                        "chaos", cql, timeout_ms=30_000).result(60))
                elif op == 1:
                    outcome(lambda i=i: svc.knn(
                        "chaos", cql, qpts[i:i + 1, 0], qpts[i:i + 1, 1],
                        k=5, timeout_ms=30_000).result(60))
                elif op == 2:
                    outcome(lambda: svc.query(
                        "chaos", cql, timeout_ms=30_000).result(60))
                else:
                    outcome(lambda: ksrc.get_count("INCLUDE"))
                if i % 5 == 4:
                    # interleaved writers: FS batch-atomic appends and
                    # Kafka produces, both under injection
                    try:
                        store.get_feature_source("chaos").write(
                            _synth_batch(sft, rng, 16))
                        report.writes_ok += 1
                    except Exception as e:  # noqa: BLE001
                        if _errors.is_typed(e):
                            report.writes_failed += 1
                        else:
                            report.untyped_errors.append(
                                f"write: {type(e).__name__}: {e}")
                    try:
                        kstore.write("chaos_live", _synth_batch(
                            ksrc.sft, rng, 4))
                        report.writes_ok += 1
                    except Exception as e:  # noqa: BLE001
                        if _errors.is_typed(e):
                            report.writes_failed += 1
                        else:
                            report.untyped_errors.append(
                                f"kafka write: {type(e).__name__}: {e}")
            svc.close(drain=True)
            if svc._worker is not None and svc._worker.is_alive():
                report.invariant_failures.append(
                    "dispatch thread still alive after drain")
            if len(svc.queue) != 0:
                report.invariant_failures.append(
                    "queue not empty after graceful drain")
        finally:
            try:
                svc.close(drain=False)
            except Exception:
                pass
        return h.fire_log()


def _counter_snapshot() -> Dict[str, float]:
    from geomesa_tpu.utils.metrics import metrics

    with metrics._lock:
        return dict(metrics.counters)


def run_chaos(plan: FaultPlan, requests: int = 32, replay: bool = True,
              out=None) -> ChaosReport:
    """Programmatic `gmtpu chaos`: returns a ChaosReport whose
    `ok_overall` reflects every invariant (the CLI exit code)."""
    out = out if out is not None else sys.stderr

    def say(msg):
        print(f"chaos: {msg}", file=out)

    report = ChaosReport()
    before = _counter_snapshot()
    with tempfile.TemporaryDirectory() as tmp:
        log = _run_workload(plan, os.path.join(tmp, "run1"),
                            requests, report, say)
        if replay:
            replay_report = ChaosReport()
            log2 = _run_workload(plan, os.path.join(tmp, "run2"),
                                 requests, replay_report, say)
            report.replay_match = log == log2
            if not report.replay_match:
                report.invariant_failures.append(
                    f"replay diverged: {len(log)} vs {len(log2)} fires "
                    f"(first diff at "
                    f"{next((i for i, (a, b) in enumerate(zip(log, log2)) if a != b), min(len(log), len(log2)))})")
            report.invariant_failures.extend(
                f"replay: {f}" for f in replay_report.invariant_failures)
            report.untyped_errors.extend(
                f"replay: {u}" for u in replay_report.untyped_errors)
    report.fires = len(log)
    report.fired_sites = sorted({s for s, _, _ in log})

    # invariant 1: zero un-typed escapes
    for u in report.untyped_errors:
        report.invariant_failures.append(f"un-typed escape: {u}")
    # invariant 3: every deterministic rule fired
    import fnmatch

    for rule in plan.rules:
        if rule.nth_call is None and rule.every is None:
            continue  # probabilistic rules may legitimately stay quiet
        hit = any(
            (site == rule.site or fnmatch.fnmatchcase(site, rule.site))
            and err == rule.error
            for site, _, err in log)
        if not hit:
            report.invariant_failures.append(
                f"rule for {rule.site!r} ({rule.error}) never fired — "
                f"the workload does not exercise that site")
    # invariant 4: breaker transitions visible in metrics
    after = _counter_snapshot()
    for name in plan.expect_breakers:
        for phase in ("open", "half_open"):
            key = f"fault.breaker.{name}.{phase}"
            delta = after.get(key, 0.0) - before.get(key, 0.0)
            report.breaker_counters[key] = delta
            if delta < 1:
                report.invariant_failures.append(
                    f"breaker {name!r} never transitioned to {phase} "
                    f"(metrics counter {key} unchanged)")
    # invariant 6: the disabled harness must cost ~nothing
    site = _harness.site("chaos.noop.probe")
    t0 = time.perf_counter()
    for _ in range(_NOOP_CALLS):
        site.fire()
    per_call_us = (time.perf_counter() - t0) / _NOOP_CALLS * 1e6
    report.noop_us_per_call = round(per_call_us, 4)
    if per_call_us > _NOOP_BUDGET_US:
        report.invariant_failures.append(
            f"no-op site check costs {per_call_us:.2f}µs/call "
            f"(budget {_NOOP_BUDGET_US}µs): the inactive fast path "
            "is doing work")
    say("OK" if report.ok_overall else
        f"FAIL: {'; '.join(report.invariant_failures)}")
    return report


# -- fleet chaos (docs/ROBUSTNESS.md "Replica fleets") ----------------------
#
# `gmtpu chaos --fleet`: the replica-kill certification. A 2-replica
# thread fleet (same process semantics as deployment: own stores, own
# queues, the real wire protocol over real sockets) serves five phases:
#
#   1. route   — sequential mixed traffic; every answer ok; both
#                replicas take traffic (rendezvous affinity spreads
#                deterministic keys deterministically);
#   2. faults  — the plan's deterministic rules fire under the harness
#                (sequential submission keeps the site call sequence
#                replayable) and the retry fabric absorbs them: every
#                answer still ok, fire log exact;
#   3. kill    — a burst pipelined on one client connection, replica
#                killed abruptly (abort(): the kill -9 stand-in) while
#                requests are in flight. EVERY request gets exactly one
#                answer: ok, or typed retryable
#                unavailable/rejected/timeout — zero un-typed errors,
#                zero silent drops, zero duplicate responses (the wire
#                has no write verbs and the router retries reads only,
#                so zero double-executed writes by construction);
#   4. warmup  — a FRESH replica with a manifest recorded from phase-1
#                traffic demonstrably refuses traffic (typed,
#                retryable `warming`) until `gmtpu warmup --check`
#                semantics pass, and the router never routes to it
#                before `ready`;
#   5. subscribe-kill — a geofence standing query subscribed THROUGH
#                the router over a shared Kafka live layer, owner
#                replica killed abruptly mid-stream. The router
#                re-homes the subscription onto the survivor from its
#                checkpoint; a host oracle replays the client's frame
#                stream and asserts ZERO missed / duplicate / phantom
#                enter-exit transitions modulo exactly ONE state
#                resync, seq strictly monotonic across the kill, and
#                zero client-side handoff choreography.
#
# The whole sequence runs twice with the same seed; the harness fire
# logs must match exactly (invariant 3's replay discipline).

_FLEET_ROUTE_REQUESTS = 12
_FLEET_FAULT_REQUESTS = 6
_FLEET_KILL_REQUESTS = 20


def default_fleet_plan(seed: int = 23) -> FaultPlan:
    """The built-in replica-kill plan: two deterministic storage
    faults the retry fabric must absorb (fires below the retry
    budget), asserted fired + replay-exact. The kill itself is
    scripted by the runner, not a harness rule — process death is not
    an injection site."""
    from geomesa_tpu.faults.plan import FaultRule

    # the fault phase makes 6 sequential scan-path counts -> one
    # fs.read_partition call each, +1 per injected fire's retry:
    # fires at calls 2 and 5 leave every request recovered (the retry
    # budget absorbs single faults) while both rules provably fire
    return FaultPlan(seed=seed, rules=[
        FaultRule(site="fs.read_partition", error="io", nth_call=2),
        FaultRule(site="fs.read_partition", error="io", nth_call=5),
    ])


def _fleet_request(i: int, qpts, cql: str,
                   rid: Optional[str] = None) -> dict:
    rid = rid if rid is not None else f"q{i}"
    if i % 2 == 0:
        return {"id": rid, "op": "count", "typeName": "chaos",
                "cql": cql, "timeoutMs": 60_000}
    return {"id": rid, "op": "knn", "typeName": "chaos",
            "cql": cql, "x": [float(qpts[i, 0])],
            "y": [float(qpts[i, 1])], "k": 5, "timeoutMs": 60_000}


def _fleet_answer(report: ChaosReport, doc: dict, where: str) -> None:
    report.requests += 1
    if doc.get("ok"):
        report.ok += 1
    elif doc.get("error") in ("unavailable", "rejected", "timeout"):
        key = doc.get("reason") or doc["error"]
        report.typed_errors[key] = report.typed_errors.get(key, 0) + 1
    else:
        report.untyped_errors.append(
            f"{where}: {doc.get('error')}: {doc.get('message')}")


def _run_fleet_pass(plan: FaultPlan, root: str, report: ChaosReport,
                    say) -> List[tuple]:
    import threading

    from geomesa_tpu.fleet import (
        FleetConfig, FleetSupervisor, ReplicaServer)
    from geomesa_tpu.fleet.wire import connect_json
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve.service import ServeConfig

    catalog = os.path.join(root, "cat")
    _synth_store(catalog, n=384, seed=plan.seed)
    rng = np.random.default_rng(plan.seed + 61)
    qpts = rng.uniform(-60, 60, (64, 2))
    cql = "BBOX(geom, -170, -80, 170, 80)"

    # scan-path stores so the plan's storage rules keep biting, and
    # coalescing-off so the fault phase's site sequence is replayable
    def store_factory():
        return DataStore(catalog, use_device_cache=False)

    sup = FleetSupervisor(FleetConfig(
        n_replicas=2, catalog=catalog, store_factory=store_factory,
        serve_config=ServeConfig(max_wait_ms=0.0, max_batch=1),
        probe_interval_s=0.2))
    extra = None
    try:
        port = sup.start()
        # phase-4 prep: record a warmup manifest from live traffic on
        # replica r0 (thread spawn exposes the service)
        recorder = sup.membership.get("r0").server.svc.record_warmup()

        cli = connect_json("127.0.0.1", port)
        # phase 1: route — sequential, every answer ok, both replicas
        # take traffic
        for i in range(_FLEET_ROUTE_REQUESTS):
            cli.send(_fleet_request(i, qpts, cql))
            got = next(cli.docs())
            _fleet_answer(report, got, "route")
            if not got.get("ok"):
                report.invariant_failures.append(
                    f"fleet route phase: request {i} failed "
                    f"{got.get('error')}/{got.get('reason')}")
        routed = {r["replica"]: r["routed"]
                  for r in sup.stats()["replicas"]}
        if sorted(v > 0 for v in routed.values()) != [True, True]:
            report.invariant_failures.append(
                f"fleet route phase: traffic did not spread over both "
                f"replicas ({routed})")

        # phase 2: deterministic faults under the harness, absorbed by
        # the retry fabric; sequential submission keeps the fire
        # schedule exact
        with _harness.active(plan) as h:
            for i in range(_FLEET_FAULT_REQUESTS):
                cli.send(_fleet_request(2 * i, qpts, cql))  # counts
                got = next(cli.docs())
                _fleet_answer(report, got, "fault")
                if not got.get("ok"):
                    report.invariant_failures.append(
                        f"fleet fault phase: retry fabric did not "
                        f"absorb an injected fault "
                        f"({got.get('error')}/{got.get('reason')})")
            log = list(h.fire_log())

        manifest_path = os.path.join(root, "fleet_warmup.json")
        recorder.manifest().save(manifest_path)

        # phase 3: replica kill mid-burst. Pipeline the burst on one
        # connection, kill r1 abruptly while requests are in flight.
        for i in range(_FLEET_KILL_REQUESTS):
            cli.send(_fleet_request(i % 16, qpts, cql, rid=f"k{i}"))
        sup.kill_replica("r1", graceful=False)
        answers: Dict[str, dict] = {}
        stop = threading.Event()
        timer = threading.Timer(120.0, stop.set)
        timer.start()
        for got in cli.docs(stop):
            rid = got.get("id")
            if rid in answers:
                report.invariant_failures.append(
                    f"fleet kill phase: duplicate response for {rid} "
                    f"(double-delivery)")
            answers[rid] = got
            if len(answers) >= _FLEET_KILL_REQUESTS:
                break
        timer.cancel()
        if len(answers) != _FLEET_KILL_REQUESTS:
            report.invariant_failures.append(
                f"fleet kill phase: {_FLEET_KILL_REQUESTS} requests, "
                f"{len(answers)} answers — requests were silently "
                f"dropped")
        for rid, got in answers.items():
            _fleet_answer(report, got, f"kill:{rid}")
        st = sup.stats()["router"]
        say(f"fleet kill phase: {len(answers)} answered, "
            f"retried={st['retried']}, unavailable={st['unavailable']}")

        # phase 4: a fresh replica refuses traffic until its warmup
        # check is green, and the router never routes to it before
        # ready
        hold = threading.Event()
        extra = ReplicaServer(
            store_factory, ServeConfig(max_wait_ms=0.0, max_batch=1),
            replica_id="r2", warmup_manifest=manifest_path,
            warmup_hold=hold)
        eport = extra.start()
        from geomesa_tpu.fleet.membership import ReplicaHandle

        handle = ReplicaHandle(replica_id="r2", host="127.0.0.1",
                               port=eport, spawn="thread", server=extra)
        sup.membership.add(handle)
        sup.router.attach(handle)
        probe = connect_json("127.0.0.1", eport)
        got = probe.request(
            {"id": "w1", "op": "count", "typeName": "chaos",
             "cql": cql}, timeout_s=30.0)
        if got.get("ok") or got.get("reason") != "warming" \
                or not got.get("retryable"):
            report.invariant_failures.append(
                f"fleet warmup phase: warming replica did not refuse "
                f"traffic typed+retryable (got {got})")
        if any(h2.replica_id == "r2"
               for h2 in sup.membership.routable()):
            report.invariant_failures.append(
                "fleet warmup phase: router considers a warming "
                "replica routable")
        hold.set()
        state = extra.wait_state("ready", timeout=120.0)
        if state != "ready" or (extra.warmup_report is not None
                                and not extra.warmup_report.ok):
            report.invariant_failures.append(
                f"fleet warmup phase: fresh replica came up {state} "
                f"({extra.error}) — warmup --check not green")
        else:
            got = probe.request(
                {"id": "w2", "op": "count", "typeName": "chaos",
                 "cql": cql}, timeout_s=60.0)
            report.requests += 1
            if got.get("ok"):
                report.ok += 1
            else:
                report.invariant_failures.append(
                    f"fleet warmup phase: ready replica refused "
                    f"traffic ({got})")
        probe.close()
        cli.close()

        # phase 5: subscribe-kill — fleet-native standing queries
        # survive an abrupt owner death with at most one resync
        _fleet_subscribe_kill_phase(plan, report, say)
        return log
    finally:
        if extra is not None:
            try:
                extra.abort()
            except Exception:
                pass
        sup.close()


_FLEET_SUB_BATCHES = 4          # geofence stream batches (kill after #2)
_FLEET_SUB_FIDS = 24


def _fleet_subscribe_kill_phase(plan: FaultPlan, report: ChaosReport,
                                say) -> None:
    """A geofence stream subscribed through the router across an
    abrupt owner kill. Host-oracle replay of the client's frames
    certifies the re-home contract: zero missed/dup/phantom
    transitions, exactly one state resync, seq monotonic — with the
    client doing nothing but reading its one connection."""
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.fleet import FleetConfig, FleetSupervisor
    from geomesa_tpu.fleet.router import FleetClient
    from geomesa_tpu.kafka.store import KafkaDataStore

    sft = SimpleFeatureType.from_spec(
        "geofence", "name:String,score:Double,dtg:Date,*geom:Point")
    fence = (-20.0, -15.0, 25.0, 20.0)
    cql = f"BBOX(geom, {fence[0]}, {fence[1]}, {fence[2]}, {fence[3]})"
    rng = np.random.default_rng(plan.seed + 97)
    fids = [f"v{i}" for i in range(_FLEET_SUB_FIDS)]

    def batch(k: int) -> FeatureBatch:
        # same fid population every batch: vessels MOVE, so the fence
        # sees enter AND exit transitions each fold
        return FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b", "c"],
                               _FLEET_SUB_FIDS).tolist(),
            "score": rng.uniform(-5, 5, _FLEET_SUB_FIDS),
            "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000,
                                _FLEET_SUB_FIDS),
            "geom": np.stack([rng.uniform(-60, 60, _FLEET_SUB_FIDS),
                              rng.uniform(-30, 30, _FLEET_SUB_FIDS)],
                             1),
        }, fids=list(fids))

    def inside(b: FeatureBatch) -> set:
        g = b.columns[sft.default_geometry.name]
        x = np.asarray(g.x)
        y = np.asarray(g.y)
        keep = ((x >= fence[0]) & (x <= fence[2])
                & (y >= fence[1]) & (y <= fence[3]))
        return {f for f, k in zip(b.fids.decode(), keep) if k}

    store = KafkaDataStore()
    src = store.create_schema(sft)
    sup = FleetSupervisor(FleetConfig(
        n_replicas=2, store_factory=lambda: store,
        probe_interval_s=0.1))
    frames: List[dict] = []
    fail = report.invariant_failures.append
    try:
        port = sup.start()
        cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
        got = cli.request({"op": "subscribe", "typeName": "geofence",
                           "cql": cql}, on_push=frames.append)
        if not got.get("ok"):
            fail(f"fleet subscribe phase: subscribe refused ({got})")
            return
        sid = got["subscription"]
        owner = got["replica"]
        oracle = None
        killed = False
        for k in range(_FLEET_SUB_BATCHES):
            b = batch(k)
            oracle = inside(b)
            src.write(b)
            if k == 2 and not killed:
                # let one checkpoint ride the stats probe, then kill
                # the owner abruptly mid-stream and wait for the
                # router's re-home to land on the survivor
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    row = sup.membership.sub_owner(sid)
                    if row is not None and row.checkpoint is not None:
                        break
                    time.sleep(0.02)
                sup.kill_replica(owner, graceful=False)
                killed = True
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    row = sup.membership.sub_owner(sid)
                    if row is not None and row.replica_id != owner:
                        break
                    time.sleep(0.02)
                row = sup.membership.sub_owner(sid)
                if row is None or row.replica_id == owner:
                    fail("fleet subscribe phase: subscription was not "
                         "re-homed after the owner kill")
                    return
            got = cli.request({"op": "poll"}, on_push=frames.append)
            report.requests += 1
            if got.get("ok"):
                report.ok += 1
            else:
                fail(f"fleet subscribe phase: poll {k} failed ({got})")
        cli.close()

        evs = [f for f in frames if f.get("subscription") == sid]
        seqs = [f.get("seq") for f in evs]
        if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
            fail(f"fleet subscribe phase: client seq not strictly "
                 f"monotonic across the kill ({seqs})")
        resyncs = sum(1 for f in evs[1:] if f.get("event") == "state")
        if resyncs != 1:
            fail(f"fleet subscribe phase: expected exactly one state "
                 f"resync from the kill, saw {resyncs}")
        state: set = set()
        for f in evs:
            ev = f.get("event")
            if ev == "state":
                state = set(f["fids"])
            elif ev == "enter":
                dup = set(f["fids"]) & state
                if dup:
                    fail(f"fleet subscribe phase: duplicate enter "
                         f"transitions for {sorted(dup)}")
                state |= set(f["fids"])
            elif ev == "exit":
                ghost = set(f["fids"]) - state
                if ghost:
                    fail(f"fleet subscribe phase: phantom exit "
                         f"transitions for {sorted(ghost)}")
                state -= set(f["fids"])
        if oracle is not None and state != oracle:
            fail(f"fleet subscribe phase: replayed matched set "
                 f"diverged from the host oracle (missed="
                 f"{sorted(oracle - state)}, extra="
                 f"{sorted(state - oracle)})")
        st = sup.stats()["router"]
        say(f"fleet subscribe phase: {len(evs)} frames, "
            f"1 resync, rehomed={st['rehome_succeeded']}")
    finally:
        sup.close()


def run_fleet_chaos(plan: Optional[FaultPlan] = None,
                    replay: bool = True, out=None) -> ChaosReport:
    """Programmatic `gmtpu chaos --fleet`. Returns a ChaosReport whose
    `ok_overall` is the certification verdict."""
    out = out if out is not None else sys.stderr

    def say(msg):
        print(f"chaos --fleet: {msg}", file=out)

    plan = plan if plan is not None else default_fleet_plan()
    report = ChaosReport()
    with tempfile.TemporaryDirectory() as tmp:
        log = _run_fleet_pass(plan, os.path.join(tmp, "run1"),
                              report, say)
        if replay:
            replay_report = ChaosReport()
            log2 = _run_fleet_pass(plan, os.path.join(tmp, "run2"),
                                   replay_report, say)
            report.replay_match = log == log2
            if not report.replay_match:
                report.invariant_failures.append(
                    f"fleet replay diverged: {len(log)} vs "
                    f"{len(log2)} fires")
            report.invariant_failures.extend(
                f"replay: {f}" for f in replay_report.invariant_failures)
            report.untyped_errors.extend(
                f"replay: {u}" for u in replay_report.untyped_errors)
    report.fires = len(log)
    report.fired_sites = sorted({s for s, _, _ in log})
    for u in report.untyped_errors:
        report.invariant_failures.append(f"un-typed escape: {u}")
    import fnmatch

    for rule in plan.rules:
        if rule.nth_call is None and rule.every is None:
            continue
        hit = any(
            (site == rule.site or fnmatch.fnmatchcase(site, rule.site))
            and err == rule.error
            for site, _, err in log)
        if not hit:
            report.invariant_failures.append(
                f"fleet rule for {rule.site!r} ({rule.error}) never "
                f"fired")
    say("OK" if report.ok_overall else
        f"FAIL: {'; '.join(report.invariant_failures)}")
    return report


def run_cli(args) -> int:
    if getattr(args, "fleet", False):
        plan = (FaultPlan.load(args.plan)
                if getattr(args, "plan", None) else None)
        if plan is not None and getattr(args, "seed", None) is not None:
            plan.seed = args.seed
        report = run_fleet_chaos(
            plan, replay=not getattr(args, "no_replay", False))
        print(json.dumps(report.to_json(), indent=1))
        if args.check:
            return 0 if report.ok_overall else 1
        return 0
    if getattr(args, "list_sites", False):
        # import the boundary modules so their sites register
        import geomesa_tpu.compilecache.manifest  # noqa: F401
        import geomesa_tpu.compilecache.persist  # noqa: F401
        import geomesa_tpu.engine.device  # noqa: F401
        import geomesa_tpu.index.kvstore  # noqa: F401
        import geomesa_tpu.kafka.store  # noqa: F401
        import geomesa_tpu.store.fs  # noqa: F401
        import geomesa_tpu.subscribe.evaluator  # noqa: F401

        for name, doc in sorted(_harness.SITES.items()):
            print(f"{name:<32} {doc}")
        return 0
    plan = FaultPlan.load(args.plan)
    if getattr(args, "seed", None) is not None:
        plan.seed = args.seed
    report = run_chaos(plan, requests=args.requests,
                       replay=not getattr(args, "no_replay", False))
    print(json.dumps(report.to_json(), indent=1))
    if args.check:
        return 0 if report.ok_overall else 1
    return 0
