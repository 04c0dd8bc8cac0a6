#!/usr/bin/env python
"""CI gate: `gmtpu lint --fail-on warn` over geomesa_tpu/ + warmup smoke.

Runs EVERY registered rule — the JAX hazards GT01..GT06, the concurrency
pass GT07..GT12 (lock discipline, lock-order cycles, blocking-under-lock,
per-call locks, callback-under-lock, unguarded shared state), the
serving-hot-path rule GT13 and the robustness rule GT14 (swallowed
errors / unbounded retry loops at the store/kafka/serve boundaries),
the interprocedural SPMD pass GT24..GT27 (unbound collective axes,
process-divergent control flow, sharding-spec drift, ungated process-
local side effects — docs/ANALYSIS.md "Reading an SPMD report"), and
the provenance dataflow pass GT28..GT31 (raw shapes reaching hot-path
dispatches, f32→f64 exactness laundering, unmatchable registry keys,
device→host→device bounces — docs/ANALYSIS.md "Reading a provenance
report") — and exits nonzero on any unwaived finding, printing each
with file:line and rule code. The lint itself runs through the incremental engine
(analysis/incremental.py): warm runs on an unchanged tree replay the
content-hash cache in well under a second, with findings byte-identical
to a cold scan. In text mode a clean lint is
followed by the smokes: the spmd smoke (lint a known-dirty miniature
repo fixture, require all four SPMD rules to fire and the gate verdict
to go nonzero — the pass itself stays honest), the warmup smoke
(`gmtpu warmup --check`
semantics against the committed fixture manifest on CPU, proving the
manifest record→replay→check loop stays green), the chaos smoke
(`gmtpu chaos --check` semantics replaying scripts/chaos_smoke_plan.json
against a tiny serve workload, proving the fault-injection + recovery
fabric invariants — docs/ROBUSTNESS.md), the telemetry smoke (a
traced serve workload whose /metrics scrape must parse and whose
dispatch-gap report must be non-empty — docs/OBSERVABILITY.md), and
the sentinel smoke (record a perf baseline, replay it to an `ok`
verdict, then prove a synthetic 3x phase slowdown exits nonzero —
docs/OBSERVABILITY.md "Sentinel"), and the lane smoke (the vmapped-lane
vs fused-slot standing-query comparison at S=256 with membership churn:
>=10x events/s floor, identical event totals, lane dispatches/poll <=4
— docs/SERVING.md "Standing queries"). Rides the tier-1 pytest run via
tests/test_lint_gate.py and is runnable standalone:

    python scripts/lint_gate.py [--format json|sarif]
        [--no-spmd-smoke] [--no-dataflow-smoke] [--no-warmup-smoke]
        [--no-chaos-smoke] [--no-telemetry-smoke] [--no-sentinel-smoke]
        [--no-fleet-smoke] [--no-rehome-smoke] [--no-approx-smoke]
        [--no-wire-smoke] [--no-ring-smoke] [--no-lane-smoke]

Rule catalog + waiver syntax: docs/ANALYSIS.md.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:  # standalone invocation from anywhere
    sys.path.insert(0, REPO_ROOT)

SMOKE_MANIFEST = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "warmup_smoke_manifest.json")
CHAOS_PLAN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "chaos_smoke_plan.json")


def _pin_cpu() -> None:
    """Pin jax to CPU for the smokes (shared with warmup_smoke; must run
    before the first backend use). Idempotent."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def chaos_smoke(plan_path: str = CHAOS_PLAN) -> int:
    """`gmtpu chaos --check` semantics against the committed smoke plan
    on CPU: faults injected at every registered site class, the serve
    workload survives with typed errors only, breakers cycle visibly,
    and a seeded replay reproduces the exact fire log. Stderr-only like
    the warmup smoke — stdout stays machine-parseable."""
    _pin_cpu()
    from geomesa_tpu.faults.chaos import run_chaos
    from geomesa_tpu.faults.plan import FaultPlan

    report = run_chaos(FaultPlan.load(plan_path), requests=32,
                       replay=True, out=sys.stderr)
    print(
        f"chaos smoke: {report.ok}/{report.requests} ok, "
        f"{sum(report.typed_errors.values())} typed error(s), "
        f"{report.fires} fault(s) fired at "
        f"{len(report.fired_sites)} site(s), replay_match="
        f"{report.replay_match}, noop={report.noop_us_per_call}us",
        file=sys.stderr)
    for f in report.invariant_failures:
        print(f"chaos smoke: FAIL {f}", file=sys.stderr)
    return 0 if report.ok_overall else 1


def telemetry_smoke() -> int:
    """Serve a tiny traced workload, then prove the observability layer
    end to end: the /metrics scrape parses as Prometheus text (and
    carries the serving + breaker families), and the dispatch-gap
    report over the recorded traces is non-empty with sane coverage.
    Stderr-only like the other smokes — stdout stays machine-parseable
    for the lint formats."""
    _pin_cpu()
    import json
    import re
    import tempfile
    import urllib.request

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve.service import QueryService, ServeConfig
    from geomesa_tpu.telemetry import (
        RECORDER, TRACER, MetricsServer, gap_report)

    failures = []
    RECORDER.clear()
    TRACER.enable()
    try:
        rng = np.random.default_rng(5)
        n = 256
        sft = SimpleFeatureType.from_spec(
            "telesmoke", "name:String,dtg:Date,*geom:Point")
        with tempfile.TemporaryDirectory() as tmp:
            store = DataStore(tmp, use_device_cache=True)
            src = store.create_schema(sft)
            src.write(FeatureBatch.from_pydict(sft, {
                "name": rng.choice(["a", "b"], n).tolist(),
                "dtg": rng.integers(
                    1_590_000_000_000, 1_600_000_000_000, n),
                "geom": np.stack([rng.uniform(-170, 170, n),
                                  rng.uniform(-80, 80, n)], 1),
            }))
            cql = "BBOX(geom, -180, -90, 180, 90)"
            svc = QueryService(store, ServeConfig(max_wait_ms=20.0),
                               autostart=False)
            qp = rng.uniform(-60, 60, (6, 2))
            futs = [svc.knn("telesmoke", cql, qp[i:i + 1, 0],
                            qp[i:i + 1, 1], k=4) for i in range(6)]
            futs += [svc.count("telesmoke", cql) for _ in range(2)]
            svc.start()
            for f in futs:
                f.result(timeout=180)
            # drain BEFORE scraping: futures resolve inside the dispatch
            # window, but traces land in the recorder slightly later in
            # the completion loop — close() joins the dispatch thread,
            # so the scrape and the in-process report see the same set
            svc.close(drain=True)
            server = MetricsServer(port=0, stats_fn=svc.stats,
                                   pre_scrape=svc.export_gauges)
            port = server.start()
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=10) as r:
                    body = r.read().decode()
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/debug/gap",
                        timeout=10) as r:
                    http_gap = json.loads(r.read().decode())
            finally:
                server.stop()
    finally:
        TRACER.disable()
    # the scrape must PARSE: every non-comment line is
    # `name[{labels}] <float>`
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$')
    bad = [ln for ln in body.splitlines()
           if ln and not ln.startswith("#") and not sample.match(ln)]
    if bad:
        failures.append(f"unparseable /metrics line(s): {bad[:3]}")
    for needle in ("serve_latency_seconds_bucket", "serve_queue_depth",
                   "fault_breaker_", "fault_quarantine_active"):
        if needle not in body:
            failures.append(f"/metrics missing {needle}")
    rep = gap_report(RECORDER.traces())
    if not rep["phases"] or rep["dispatch_gap"]["windows"] < 1:
        failures.append(f"gap report empty: {rep}")
    elif rep["coverage"] < 0.90:
        failures.append(
            f"gap coverage {rep['coverage']} < 0.90 (un-instrumented "
            f"serve seam?)")
    if http_gap.get("traces") != rep["traces"]:
        failures.append("/debug/gap disagrees with in-process report")
    print(
        f"telemetry smoke: {rep['traces']} trace(s), coverage "
        f"{rep['coverage']}, {rep['dispatch_gap']['windows']} dispatch "
        f"window(s), /metrics {len(body.splitlines())} line(s)",
        file=sys.stderr)
    for f in failures:
        print(f"telemetry smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def sentinel_smoke() -> int:
    """The perf-regression sentinel loop, self-relative (docs/
    OBSERVABILITY.md "Sentinel"): record a baseline from a tiny traced
    serve workload, replay the identical workload, and require the
    comparison to verdict `ok` (no false regression on CI jitter);
    then inject a synthetic 3x slowdown into one phase's samples and
    require `regressed` with a nonzero exit code (a real slowdown
    cannot slip through). Self-relative on purpose — wall-clock
    baselines do not transfer across CI hosts, so the property CI can
    assert anywhere is exactly record -> replay -> verdict. Stderr-only
    like the other smokes."""
    _pin_cpu()
    import tempfile

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve.service import QueryService, ServeConfig
    from geomesa_tpu.telemetry import RECORDER, TRACER, sentinel
    from geomesa_tpu.telemetry.prof import PROFILER

    failures = []
    rng = np.random.default_rng(9)
    n = 256
    sft = SimpleFeatureType.from_spec(
        "sentsmoke", "name:String,dtg:Date,*geom:Point")

    def workload(store):
        # SEQUENTIAL requests on purpose: each one is its own dispatch
        # window, so every per-phase reservoir collects >= min_n
        # samples and the comparison verdicts instead of answering
        # insufficient-data (a single coalesced window would fold one
        # sample per phase). result_cache=0 + 8 exact counts keep the
        # plan/residency/filter.mask families sampled past min_n now
        # that ring-served kNN windows pay them only at arm time
        # (docs/SERVING.md "Persistent serve loop")
        svc = QueryService(store, ServeConfig(max_wait_ms=1.0,
                                              result_cache=0))
        qp = rng.uniform(-60, 60, (10, 2))
        cql = "BBOX(geom, -180, -90, 180, 90)"
        for i in range(10):
            svc.knn("sentsmoke", cql, qp[i:i + 1, 0],
                    qp[i:i + 1, 1], k=4).result(timeout=180)
        for _ in range(8):
            svc.count("sentsmoke", cql).result(timeout=180)
        svc.close(drain=True)

    TRACER.enable()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            store = DataStore(tmp, use_device_cache=True)
            src = store.create_schema(sft)
            src.write(FeatureBatch.from_pydict(sft, {
                "name": rng.choice(["a", "b"], n).tolist(),
                "dtg": rng.integers(
                    1_590_000_000_000, 1_600_000_000_000, n),
                "geom": np.stack([rng.uniform(-170, 170, n),
                                  rng.uniform(-80, 80, n)], 1),
            }))
            workload(store)  # warm pass: compiles stay out of both
            RECORDER.clear()
            PROFILER.reset()
            PROFILER.enable()
            workload(store)
            base = sentinel.baseline_from_profile(
                PROFILER.snapshot(include_samples=True))
            # round-trip through disk exactly like the real workflow
            # (bench-serve --record-baseline -> gmtpu sentinel)
            with tempfile.NamedTemporaryFile(
                    "w", suffix=".json", delete=False) as tf:
                base_path = tf.name
            sentinel.save_baseline(base_path, base)
            base = sentinel.load_baseline(base_path)
            os.unlink(base_path)
            PROFILER.reset()
            workload(store)
            current = sentinel.baseline_from_profile(
                PROFILER.snapshot(include_samples=True))
    finally:
        PROFILER.disable()
        TRACER.disable()
    replay = sentinel.compare(base, current)
    if replay["regressed"] or sentinel.exit_code(replay) != 0:
        failures.append(
            f"identical replay verdicted regressed: "
            f"{[k for k, v in replay['metrics'].items() if v['verdict'] == 'regressed']}")
    if sentinel.exit_code(replay, strict=True) != 0:
        # the identical replay must COMPARE every baseline metric: an
        # insufficient-data verdict here means a phase/kernel family
        # stopped being instrumented (or the workload stopped sampling
        # it), which would silently un-guard that metric in every
        # future sentinel run
        failures.append(
            f"identical replay left metrics uncompared: "
            f"{[k for k, v in replay['metrics'].items() if v['verdict'] == 'insufficient-data']}")
    # synthetic regression: one phase 3x slower, everything else as
    # measured — the sentinel must flag exactly a regression and the
    # exit code must go nonzero
    slowed = {k: dict(v) for k, v in current["metrics"].items()}
    victim = ("phase.dispatch" if "phase.dispatch" in slowed
              else next(iter(slowed)))
    slowed[victim] = {
        "n": current["metrics"][victim]["n"],
        "median_ms": current["metrics"][victim]["median_ms"] * 3.0,
        "samples_ms": [v * 3.0 for v in
                       current["metrics"][victim]["samples_ms"]],
    }
    tripped = sentinel.compare(base, {"metrics": slowed})
    if not tripped["regressed"] or sentinel.exit_code(tripped) == 0:
        failures.append(
            f"synthetic 3x slowdown on {victim} not flagged: "
            f"{tripped['metrics'].get(victim)}")
    elif tripped["metrics"][victim]["verdict"] != "regressed":
        failures.append(
            f"victim verdict {tripped['metrics'][victim]['verdict']}, "
            f"expected regressed")
    print(
        f"sentinel smoke: replay {replay['counts']}, synthetic-3x on "
        f"{victim} -> {tripped['metrics'].get(victim, {}).get('verdict')}"
        f" (exit {sentinel.exit_code(tripped)})", file=sys.stderr)
    for f in failures:
        print(f"sentinel smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def fleet_smoke() -> int:
    """A 2-replica thread fleet on CPU over a tiny store, one scripted
    abrupt replica kill mid-burst (docs/ROBUSTNESS.md "Replica
    fleets"): every request must come back as a result or a typed
    retryable error — zero un-typed, zero dropped, zero duplicate
    responses — and the router's gauges must stay consistent with the
    answers the client actually saw (routed >= answered requests,
    retried reflected in membership). Stderr-only like the other
    smokes."""
    _pin_cpu()
    import json
    import tempfile
    import threading
    import time

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.fleet import FleetConfig, FleetSupervisor
    from geomesa_tpu.fleet.wire import connect_json
    from geomesa_tpu.plan.datastore import DataStore

    failures = []
    rng = np.random.default_rng(7)
    n = 384
    burst = 16
    sft = SimpleFeatureType.from_spec(
        "fleetsmoke", "name:String,score:Double,dtg:Date,*geom:Point")
    with tempfile.TemporaryDirectory() as tmp:
        ds = DataStore(tmp, use_device_cache=True)
        ds.create_schema(sft).write(FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "score": rng.uniform(-10, 10, n),
            "dtg": rng.integers(
                1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1),
        }))
        del ds
        sup = FleetSupervisor(FleetConfig(
            n_replicas=2, catalog=tmp, probe_interval_s=0.2))
        try:
            port = sup.start()
            conn = connect_json("127.0.0.1", port)
            # warm both replica planners outside the measured burst
            conn.request({"id": "w", "op": "count",
                          "typeName": "fleetsmoke", "cql": "INCLUDE"},
                         timeout_s=300.0)
            qp = rng.uniform(-60, 60, (burst, 2))
            for i in range(burst):
                conn.send({"id": f"q{i}", "op": "knn",
                           "typeName": "fleetsmoke", "cql": "INCLUDE",
                           "x": [float(qp[i, 0])],
                           "y": [float(qp[i, 1])], "k": 4,
                           "timeoutMs": 60_000})
            sup.kill_replica("r0", graceful=False)
            answers = {}
            stop = threading.Event()
            timer = threading.Timer(120.0, stop.set)
            timer.start()
            for got in conn.docs(stop):
                rid = got.get("id")
                if rid in answers:
                    failures.append(f"duplicate response for {rid}")
                answers[rid] = got
                if len(answers) >= burst:
                    break
            timer.cancel()
            conn.close()
            if len(answers) != burst:
                failures.append(
                    f"{burst} requests, {len(answers)} answers: "
                    f"requests dropped during failover")
            untyped = [r for r in answers.values()
                       if not r.get("ok")
                       and r.get("error") not in ("unavailable",
                                                  "rejected",
                                                  "timeout")]
            if untyped:
                failures.append(f"un-typed client error(s): "
                                f"{untyped[:3]}")
            snap = sup.stats()
            routed_total = sum(r["routed"] for r in snap["replicas"])
            if routed_total < len(answers):
                failures.append(
                    f"router gauges inconsistent: routed_total="
                    f"{routed_total} < answered={len(answers)}")
            retried_onto = sum(r["retried_onto"]
                               for r in snap["replicas"])
            if snap["router"]["retried"] != retried_onto:
                failures.append(
                    f"router gauges inconsistent: retried="
                    f"{snap['router']['retried']} but membership "
                    f"says {retried_onto}")
            states = {r["replica"]: r["state"]
                      for r in snap["replicas"]}
            if states.get("r0") != "dead" or states.get("r1") != "ready":
                failures.append(f"post-kill states wrong: {states}")
            ok_n = sum(1 for r in answers.values() if r.get("ok"))
            print(
                f"fleet smoke: {len(answers)}/{burst} answered "
                f"({ok_n} ok), retried={snap['router']['retried']}, "
                f"states={states}", file=sys.stderr)
        finally:
            sup.close()
    for f in failures:
        print(f"fleet smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def rehome_smoke() -> int:
    """Fleet-native standing queries (docs/ROBUSTNESS.md "Standing
    queries"): a geofence subscription placed THROUGH the router over
    a shared Kafka live layer must survive an abrupt owner-replica
    kill with zero client choreography — the router re-homes it onto
    the survivor, the client's seq stays strictly monotonic, the frame
    stream replays to the exact matched set with at most ONE state
    resync, and the rehome counters account for the move. Stderr-only
    like the other smokes."""
    _pin_cpu()
    import time

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.fleet import FleetConfig, FleetSupervisor
    from geomesa_tpu.fleet.router import FleetClient
    from geomesa_tpu.kafka.store import KafkaDataStore

    failures = []
    rng = np.random.default_rng(29)
    n = 24
    sft = SimpleFeatureType.from_spec(
        "rehomesmoke", "name:String,score:Double,dtg:Date,*geom:Point")
    fence = (-20.0, -15.0, 25.0, 20.0)
    cql = f"BBOX(geom, {fence[0]}, {fence[1]}, {fence[2]}, {fence[3]})"
    fids = [f"v{i}" for i in range(n)]

    def batch():
        return FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "score": rng.uniform(-5, 5, n),
            "dtg": rng.integers(
                1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-60, 60, n),
                              rng.uniform(-30, 30, n)], 1),
        }, fids=list(fids))

    def inside(b):
        g = b.columns[sft.default_geometry.name]
        x, y = np.asarray(g.x), np.asarray(g.y)
        keep = ((x >= fence[0]) & (x <= fence[2])
                & (y >= fence[1]) & (y <= fence[3]))
        return {f for f, k in zip(b.fids.decode(), keep) if k}

    store = KafkaDataStore()
    src = store.create_schema(sft)
    sup = FleetSupervisor(FleetConfig(
        n_replicas=2, store_factory=lambda: store,
        probe_interval_s=0.1))
    frames = []
    oracle = None
    try:
        port = sup.start()
        cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
        got = cli.request({"op": "subscribe",
                           "typeName": "rehomesmoke", "cql": cql},
                          on_push=frames.append)
        if not got.get("ok"):
            failures.append(f"routed subscribe refused: {got}")
            raise SystemExit
        sid, owner = got["subscription"], got["replica"]
        for k in range(3):
            b = batch()
            oracle = inside(b)
            src.write(b)
            if k == 1:
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    row = sup.membership.sub_owner(sid)
                    if row is not None and row.checkpoint is not None:
                        break
                    time.sleep(0.02)
                sup.kill_replica(owner, graceful=False)
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    row = sup.membership.sub_owner(sid)
                    if row is not None and row.replica_id != owner:
                        break
                    time.sleep(0.02)
                row = sup.membership.sub_owner(sid)
                if row is None or row.replica_id == owner:
                    failures.append("subscription not re-homed after "
                                    "the owner kill")
                    raise SystemExit
            got = cli.request({"op": "poll"}, on_push=frames.append)
            if not got.get("ok"):
                failures.append(f"poll {k} failed: {got}")
        cli.close()
        evs = [f for f in frames if f.get("subscription") == sid]
        seqs = [f.get("seq") for f in evs]
        if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
            failures.append(f"client seq not monotonic: {seqs}")
        resyncs = sum(1 for f in evs[1:] if f.get("event") == "state")
        if resyncs != 1:
            failures.append(f"expected exactly one resync, saw "
                            f"{resyncs}")
        state = set()
        for f in evs:
            ev = f.get("event")
            if ev == "state":
                state = set(f["fids"])
            elif ev == "enter":
                if set(f["fids"]) & state:
                    failures.append("duplicate enter transition")
                state |= set(f["fids"])
            elif ev == "exit":
                if set(f["fids"]) - state:
                    failures.append("phantom exit transition")
                state -= set(f["fids"])
        if oracle is not None and state != oracle:
            failures.append(
                f"replayed matched set diverged from oracle "
                f"(missed={sorted(oracle - state)}, "
                f"extra={sorted(state - oracle)})")
        st = sup.stats()["router"]
        if st["rehome_succeeded"] != 1:
            failures.append(
                f"rehome counters wrong: {st}")
        print(f"rehome smoke: {len(evs)} frames, 1 resync, "
              f"rehomed={st['rehome_succeeded']}", file=sys.stderr)
    except SystemExit:
        pass
    finally:
        sup.close()
    for f in failures:
        print(f"rehome smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def approx_smoke() -> int:
    """The approximate-answer tier loop (docs/SERVING.md "Approximate
    answers"): a tolerant count workload over a tiny store must serve
    from SKETCHES with every reported bound containing the exact
    replayed answer, and a repeated exact query must hit the
    version-exact result cache with a bit-identical result on the
    second pass. Stderr-only like the other smokes."""
    _pin_cpu()
    import tempfile

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.plan.hints import QueryHints
    from geomesa_tpu.plan.query import Query
    from geomesa_tpu.serve.scheduler import ServeRequest
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    failures = []
    rng = np.random.default_rng(17)
    n = 2048
    sft = SimpleFeatureType.from_spec(
        "approxsmoke", "name:String,dtg:Date,*geom:Point")
    cqls = ["BBOX(geom, -180, -90, 180, 90)",
            "BBOX(geom, -60, -30, 60, 30)"]
    with tempfile.TemporaryDirectory() as tmp:
        store = DataStore(tmp, use_device_cache=True)
        src = store.create_schema(sft)
        src.write(FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1),
        }))
        svc = QueryService(store, ServeConfig(max_wait_ms=1.0))
        try:
            served = 0
            for cql in cqls:
                req = ServeRequest(kind="count", query=Query(
                    "approxsmoke", cql,
                    hints=QueryHints(tolerance=0.2)))
                got = svc.submit(req).result(timeout=300)
                exact = svc.count("approxsmoke", cql).result(timeout=300)
                if not getattr(got, "approx", False):
                    failures.append(
                        f"tolerant count {cql!r} not sketch-served")
                    continue
                served += 1
                if abs(int(got) - int(exact)) > got.bound:
                    failures.append(
                        f"bound violated for {cql!r}: approx {int(got)} "
                        f"+/- {got.bound} vs exact replay {int(exact)}")
            # second pass: the exact queries above populated the cache
            for cql in cqls:
                svc.count("approxsmoke", cql).result(timeout=300)
            cache = svc.stats().get("cache", {})
            if cache.get("hits", 0) < len(cqls):
                failures.append(
                    f"repeated exact queries did not hit the result "
                    f"cache: {cache}")
            tiers = svc.stats()["approx"]["tiers"]
        finally:
            svc.close(drain=True)
    print(f"approx smoke: {served} sketch-served (tiers {tiers}), "
          f"cache {cache.get('hits', 0)}h/{cache.get('misses', 0)}m",
          file=sys.stderr)
    for f in failures:
        print(f"approx smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def wire_smoke() -> int:
    """The columnar-wire loop (docs/SERVING.md "Columnar wire"): a
    negotiated columnar session over an in-process stream must answer
    bulk execute/density responses as binary frames whose DECODED
    payloads are bit-identical to a JSON-lines replay of the same
    queries, and a PushMux fan-out to 64 in-process subscribers must
    serialize each frame exactly once (encode-call counter asserted).
    Stderr-only like the other smokes."""
    _pin_cpu()
    import json
    import tempfile

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve import columnar as colwire
    from geomesa_tpu.serve.protocol import serve_connection
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    failures = []
    if not colwire.have_pyarrow():
        # typed skip, same stance as the wire itself: json-only
        # environments downgrade, they do not fail
        print("wire smoke: pyarrow unavailable — columnar capability "
              "off, smoke skipped typed", file=sys.stderr)
        return 0
    rng = np.random.default_rng(13)
    n = 1024
    sft = SimpleFeatureType.from_spec(
        "wiresmoke", "name:String,score:Double,dtg:Date,*geom:Point")
    dens = {"bbox": [-180, -90, 180, 90], "width": 64, "height": 32}
    with tempfile.TemporaryDirectory() as tmp:
        store = DataStore(tmp, use_device_cache=True)
        store.create_schema(sft).write(FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b", "c"], n).tolist(),
            "score": rng.uniform(-10, 10, n),
            "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1),
        }))
        svc = QueryService(store, ServeConfig(max_wait_ms=1.0))
        mem = colwire.MemoryWire()
        mem.add({"id": "h", "op": "hello", "wire": "columnar"})
        mem.add({"id": "qc", "op": "query", "typeName": "wiresmoke",
                 "cql": "INCLUDE", "maxFeatures": n})
        mem.add({"id": "qj", "op": "query", "typeName": "wiresmoke",
                 "cql": "INCLUDE", "maxFeatures": n, "wire": "json"})
        mem.add({"id": "dc", "op": "query", "typeName": "wiresmoke",
                 "cql": "INCLUDE", "density": dens})
        mem.add({"id": "dj", "op": "query", "typeName": "wiresmoke",
                 "cql": "INCLUDE", "density": dens, "wire": "json"})
        out = bytearray()
        try:
            serve_connection(store, svc, mem.lines(),
                             lambda s: out.extend(s.encode()),
                             write_bytes=out.extend,
                             read_bytes=mem.read_exact)
            # push fan-out: 64 in-process subscribers, one encode per
            # frame (the mux's own counter is the assertion)
            mux = svc.wire_mux()
            got = [0] * 64
            sinks = []
            for i in range(64):
                def make(i=i):
                    def w(buf: bytes) -> None:
                        got[i] += len(buf)
                    return w
                sinks.append(mux.register(make(), mode="json",
                                          threaded=False))
            frames = 10
            for k in range(frames):
                mux.publish({"event": "enter", "subscription": "s",
                             "seq": k + 1, "fids": ["a", "b"]}, sinks)
            st = mux.stats()
            if st["encodes"] != frames:
                failures.append(
                    f"fan-out encoded {st['encodes']}x for {frames} "
                    f"frames at 64 sinks (want one encode per frame)")
            if len(set(got)) != 1 or got[0] == 0:
                failures.append(f"sinks saw unequal bytes: {set(got)}")
        finally:
            svc.close(drain=True)
    resp = {d.get("id"): (d, p)
            for d, p in colwire.parse_stream(bytes(out))}
    hello = resp["h"][0]
    if hello.get("wireMode") != "columnar" \
            or "columnar" not in hello.get("wire", ()):
        failures.append(f"hello did not negotiate columnar: {hello}")
    qc, qp = resp["qc"]
    qj = resp["qj"][0]
    if qp is None or qj.get("features") is None:
        failures.append("execute responses missing frame/features")
    elif colwire.decode_execute_payload(qp) != qj["features"]:
        failures.append("columnar execute decode != JSON replay")
    dc, dp = resp["dc"]
    dj = resp["dj"][0]
    if dp is None:
        failures.append("density response missing frame")
    else:
        grid = colwire.decode_density_payload(dc["frame"], dp)
        if (dc["shape"] != dj["shape"] or dc["total"] != dj["total"]
                or float(grid.sum()) != dj["total"]):
            failures.append(
                f"columnar density decode != JSON replay: "
                f"{dc['shape']}/{dc['total']} vs "
                f"{dj['shape']}/{dj['total']}")
    print(
        f"wire smoke: {len(resp)} response(s), execute parity over "
        f"{qc.get('count')} rows, density {dc.get('shape')}, fan-out "
        f"64 sinks x {frames} frames -> {st['encodes']} encode(s)",
        file=sys.stderr)
    for f in failures:
        print(f"wire smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def ring_smoke() -> int:
    """The persistent serve loop end to end (docs/SERVING.md
    "Persistent serve loop"): a small sequential kNN workload through
    the ring path must (a) serve every window past warmup over ONE
    armed ring program, (b) answer bit-identical to a serial-path
    replay of the same queries, and (c) measure dispatches_per_window
    strictly below an identical ring-off (pipelined) run — the
    structural form of the dispatch-amortization claim CPU CI can
    assert. Stderr-only like the other smokes."""
    _pin_cpu()
    import tempfile

    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve.loadgen import device_ops_count
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    failures = []
    rng = np.random.default_rng(23)
    n = 512
    windows = 18
    sft = SimpleFeatureType.from_spec(
        "ringsmoke", "name:String,dtg:Date,*geom:Point")
    cql = "BBOX(geom, -180, -90, 180, 90)"
    with tempfile.TemporaryDirectory() as tmp:
        store = DataStore(tmp, use_device_cache=True)
        src = store.create_schema(sft)
        src.write(FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1),
        }))
        pts = rng.uniform(-60, 60, (windows, 2))
        planner = store.get_feature_source("ringsmoke").planner
        from geomesa_tpu.plan.query import Query

        serial = [planner.knn(Query("ringsmoke", cql), pts[i:i + 1, 0],
                              pts[i:i + 1, 1], k=4)
                  for i in range(windows)]

        def run(cfg):
            svc = QueryService(store, cfg)
            try:
                # warm pass: arm/compile outside the measured loop
                for i in range(2):
                    svc.knn("ringsmoke", cql, pts[i:i + 1, 0],
                            pts[i:i + 1, 1], k=4).result(timeout=300)
                o0 = device_ops_count()
                out = []
                for i in range(windows):
                    out.append(svc.knn(
                        "ringsmoke", cql, pts[i:i + 1, 0],
                        pts[i:i + 1, 1], k=4).result(timeout=300))
                per_window = (device_ops_count() - o0) / windows
                return out, per_window, svc.stats()["pipeline"]
            finally:
                svc.close(drain=True)

        ring_res, ring_pw, ring_stats = run(ServeConfig(max_wait_ms=1.0))
        pipe_res, pipe_pw, _ = run(
            ServeConfig(max_wait_ms=1.0, ring=False))
    for i, ((d, ix, _b), (sd, six, _sb)) in enumerate(
            zip(ring_res, serial)):
        if not (np.array_equal(d, sd) and np.array_equal(ix, six)):
            failures.append(f"ring window {i} != serial replay")
            break
    for i, ((d, ix, _b), (pd, pix, _pb)) in enumerate(
            zip(ring_res, pipe_res)):
        if not (np.array_equal(d, pd) and np.array_equal(ix, pix)):
            failures.append(f"ring window {i} != pipelined replay")
            break
    ring = ring_stats.get("ring") or {}
    if ring.get("windows", 0) < windows:
        failures.append(
            f"only {ring.get('windows')} of {windows} windows rode "
            f"the ring (fallbacks: {ring.get('fallbacks')})")
    if not ring_pw < pipe_pw:
        failures.append(
            f"dispatches_per_window not below the pipelined baseline: "
            f"ring {ring_pw} vs pipelined {pipe_pw}")
    print(
        f"ring smoke: {ring.get('windows')}/{windows} ring window(s) "
        f"over {ring.get('armed')} armed program(s), "
        f"dispatches/window ring={ring_pw:.2f} vs "
        f"pipelined={pipe_pw:.2f}", file=sys.stderr)
    for f in failures:
        print(f"ring smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def lane_smoke() -> int:
    """The vmapped-lane loop (docs/SERVING.md "Standing queries"): the
    lane-vs-fused-slot comparison at S=256 same-class bbox geofences
    with one membership-churn event in both measured windows — the
    lane leg must clear the >=10x events/s floor (the fused leg pays
    an S-proportional trace+compile on the first poll and a full
    rebuild on churn; the lane leg one batched kernel + a parameter-
    row write), lane dispatches-per-poll must stay <=4 (one geofence
    class => one batched dispatch per poll), and both legs must push
    the IDENTICAL event total (the speedup is not bought with dropped
    events). S=256 keeps the fused leg near ~20 s; the S=1024 floor
    itself rides tier-1 via tests/test_subscribe.py. Stderr-only like
    the other smokes."""
    _pin_cpu()
    import numpy as np

    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.kafka.store import KafkaDataStore
    from geomesa_tpu.serve.loadgen import run_subscribe_lanes

    failures = []
    sft = SimpleFeatureType.from_spec(
        "lanesmoke", "name:String,score:Double,dtg:Date,*geom:Point")
    n = 256

    def make_store():
        store = KafkaDataStore()
        store.create_schema(sft)
        return store

    def make_batch(i: int) -> FeatureBatch:
        rng = np.random.default_rng(997 * i + 13)
        return FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b", "c"], n).tolist(),
            "score": rng.uniform(-10, 10, n),
            "dtg": rng.integers(
                1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-60, 60, n),
                              rng.uniform(-30, 30, n)], 1),
        }, fids=[f"v{j}" for j in range(n)])

    rep = run_subscribe_lanes(make_store, "lanesmoke", make_batch,
                              subscriptions=256, batches=2)
    lanes, fused = rep["lanes"], rep["fused"]
    if lanes["events_total"] != fused["events_total"]:
        failures.append(
            f"event totals diverge: lanes {lanes['events_total']} vs "
            f"fused {fused['events_total']}")
    if rep.get("speedup", 0.0) < 10.0:
        failures.append(
            f"lane events/s floor missed: {rep.get('speedup')}x < 10x "
            f"(lanes {lanes['events_per_s']}/s vs fused "
            f"{fused['events_per_s']}/s)")
    if lanes["dispatches_per_poll"] > 4.0:
        failures.append(
            f"lane dispatches-per-poll {lanes['dispatches_per_poll']} "
            f"> 4 for one geofence class")
    if lanes["lane_dispatches"] < lanes["polls"]:
        failures.append(
            f"lane path not exercised: {lanes['lane_dispatches']} lane "
            f"dispatch(es) over {lanes['polls']} poll(s)")
    print(
        f"lane smoke: S=256 speedup {rep.get('speedup')}x "
        f"(lanes first_poll {lanes['first_poll_s']}s churn "
        f"{lanes.get('churn_poll_s')}s vs fused {fused['first_poll_s']}s"
        f"/{fused.get('churn_poll_s')}s), "
        f"{lanes['events_total']} event(s) both legs, lane "
        f"dispatches/poll {lanes['dispatches_per_poll']}",
        file=sys.stderr)
    for f in failures:
        print(f"lane smoke: FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


def spmd_smoke() -> int:
    """Prove the SPMD pass still bites: lint a known-dirty fixture — a
    miniature repo skeleton (pyproject.toml + geomesa_tpu/parallel/
    launch.py, so the multi-process reachability and path scoping are
    exercised for real) seeded with one true positive per rule — and
    require the gate verdict to go nonzero with ALL FOUR rules firing.
    Pure AST analysis: no jax import, runs in milliseconds. Guards
    against the pass silently going blind (a refactor that stops a rule
    matching would otherwise read as a cleaner tree)."""
    import tempfile
    import textwrap

    from geomesa_tpu.analysis.linter import exit_code, lint_paths

    dirty = textwrap.dedent('''\
        import os

        import jax
        import numpy as np
        from jax import lax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


        def merge(x):
            return lax.psum(x, "shard")  # GT24: axis bound nowhere


        def kernel(a):
            return lax.psum(a, "data")


        def run():
            mesh = Mesh(np.array(jax.devices()), ("data",))
            spec = NamedSharding(mesh, P("ghost"))  # GT26: axis drift
            wrapped = shard_map(kernel, mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=P("data"))  # GT26: arity
            if jax.process_index() == 0:  # GT25: divergent programs
                jax.config.update("jax_enable_x64", True)
            return wrapped, spec


        def persist(path, doc):
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                fh.write(doc)
            os.replace(tmp, path)  # GT27: ungated persist
        ''')
    want = {"GT24", "GT25", "GT26", "GT27"}
    with tempfile.TemporaryDirectory() as tmp:
        with open(os.path.join(tmp, "pyproject.toml"), "w") as fh:
            fh.write("[project]\nname = \"spmd-smoke\"\n")
        pkg = os.path.join(tmp, "geomesa_tpu", "parallel")
        os.makedirs(pkg)
        with open(os.path.join(pkg, "launch.py"), "w") as fh:
            fh.write(dirty)
        findings = lint_paths([os.path.join(tmp, "geomesa_tpu")],
                              rules=sorted(want), extra_ref_paths=[])
        fired = {f.rule for f in findings if not f.waived}
        rc = exit_code(findings, "warn")
    missing = sorted(want - fired)
    print(f"spmd smoke: {len(findings)} finding(s) on the dirty "
          f"fixture, rules fired: {sorted(fired)}", file=sys.stderr)
    if rc == 0 or missing:
        print(f"spmd smoke: FAIL the dirty fixture must trip the gate "
              f"(exit {rc}, missing {missing})", file=sys.stderr)
        return 1
    return 0


def dataflow_smoke() -> int:
    """Prove the provenance dataflow pass still bites: lint a known-
    dirty serve-scope fixture seeded with one true positive per rule
    (GT28 raw shape into an AOT dispatch, GT29 f32→f64 laundering
    upcast, GT30 unmatchable registry key, GT31 device→host→device
    bounce) and require ALL FOUR to fire with a nonzero gate verdict;
    then lint the bucketed/registered/device-resident clean twin and
    require silence. The dirty SARIF render must carry the GT29
    provenance chain as relatedLocations — the report format the docs
    teach ("Reading a provenance report") is asserted here, not just
    rendered. Pure AST analysis: no jax import, runs in milliseconds."""
    import json
    import tempfile
    import textwrap

    from geomesa_tpu.analysis.linter import (
        exit_code, lint_paths, render_sarif)

    dirty = textwrap.dedent('''\
        import jax
        import numpy as np

        from geomesa_tpu.compilecache.registry import registry


        def handle(payload):
            qx = np.frombuffer(payload)           # raw wire extent
            handle_ = registry.compile("knn.score@serve", qx)  # GT28+GT30
            out = handle_.call(qx)
            host = jax.device_get(out)
            back = jax.device_put(host)           # GT31: bounce
            small = qx.astype(np.float32)
            exact = small.astype(np.float64)      # GT29: launder
            return back, exact
        ''')
    clean = textwrap.dedent('''\
        import numpy as np

        from geomesa_tpu.compilecache.registry import registry
        from geomesa_tpu.utils.padding import next_pow2


        def score(qx):
            return qx * 2.0


        registry.serve_variant("knn.score", fn=score)


        def pad_to(a, size):
            return np.concatenate([a, np.zeros(size - len(a))])


        def handle(payload):
            raw = np.frombuffer(payload)
            qx = pad_to(raw, next_pow2(max(len(raw), 1)))
            handle_ = registry.compile("knn.score@serve", qx)
            out = handle_.call(qx)
            exact = np.asarray(payload, np.float64)
            return out, exact
        ''')
    want = {"GT28", "GT29", "GT30", "GT31"}

    def run(src):
        with tempfile.TemporaryDirectory() as tmp:
            with open(os.path.join(tmp, "pyproject.toml"), "w") as fh:
                fh.write("[project]\nname = \"dataflow-smoke\"\n")
            pkg = os.path.join(tmp, "geomesa_tpu", "serve")
            os.makedirs(pkg)
            with open(os.path.join(pkg, "handler.py"), "w") as fh:
                fh.write(src)
            return lint_paths([os.path.join(tmp, "geomesa_tpu")],
                              rules=sorted(want), extra_ref_paths=[])

    findings = run(dirty)
    fired = {f.rule for f in findings if not f.waived}
    rc = exit_code(findings, "warn")
    sarif = json.loads(render_sarif(findings))
    chains = [r for r in sarif["runs"][0]["results"]
              if r["ruleId"] == "GT29" and r.get("relatedLocations")]
    leftover = [f.render() for f in run(clean) if not f.waived]
    missing = sorted(want - fired)
    print(f"dataflow smoke: {len(findings)} finding(s) on the dirty "
          f"fixture, rules fired: {sorted(fired)}, clean twin: "
          f"{len(leftover)} finding(s)", file=sys.stderr)
    if rc == 0 or missing:
        print(f"dataflow smoke: FAIL the dirty fixture must trip the "
              f"gate (exit {rc}, missing {missing})", file=sys.stderr)
        return 1
    if not chains:
        print("dataflow smoke: FAIL GT29 SARIF result carries no "
              "relatedLocations provenance chain", file=sys.stderr)
        return 1
    if leftover:
        print(f"dataflow smoke: FAIL clean twin not clean: {leftover}",
              file=sys.stderr)
        return 1
    return 0


def warmup_smoke(manifest_path: str = SMOKE_MANIFEST) -> int:
    """`gmtpu warmup --check` against the fixture manifest, pinned to
    CPU (the fixture records interpret-mode kernels; this gate must run
    on hardware-less CI). Output goes to stderr only — stdout stays
    machine-parseable for the lint formats. Returns 0 on pass."""
    # same backend pinning as bench.py --smoke; the "tpu" factory must
    # stay registered for pallas lowering imports
    _pin_cpu()

    from geomesa_tpu.compilecache.manifest import WarmupManifest
    from geomesa_tpu.compilecache.warmup import check

    report = check(WarmupManifest.load(manifest_path))
    for msg in report.errors:
        print(f"warmup smoke: {msg}", file=sys.stderr)
    print(
        f"warmup smoke: {report.kernels_compiled} compiled, "
        f"{report.kernels_cached} cached, {report.kernels_failed} failed, "
        f"residual recompiles {report.residual_recompiles}",
        file=sys.stderr)
    if report.queries_skipped:
        # same refusal as `gmtpu warmup --check` without a catalog: a
        # skipped query entry was never verified, so a green exit would
        # read as "serving compiles nothing" when the check proved
        # nothing about it — the smoke manifest must stay kernel-only
        print("warmup smoke: manifest contains query entries this "
              "store-less smoke cannot replay; FAIL", file=sys.stderr)
        return 1
    return 0 if report.ok else 1


def main(argv=None) -> int:
    from geomesa_tpu.analysis.incremental import lint_paths_incremental
    from geomesa_tpu.analysis.linter import (
        exit_code, render_json, render_sarif, render_text)

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--format", default="text",
                   choices=["text", "json", "sarif"])
    p.add_argument("--no-spmd-smoke", action="store_true",
                   help="skip the SPMD-pass smoke (known-dirty fixture "
                        "must fire GT24..GT27 and trip the gate; text "
                        "mode only)")
    p.add_argument("--no-dataflow-smoke", action="store_true",
                   help="skip the dataflow-pass smoke (known-dirty "
                        "serve fixture must fire GT28..GT31 with a "
                        "GT29 SARIF provenance chain, clean twin must "
                        "stay silent; text mode only)")
    p.add_argument("--no-warmup-smoke", action="store_true",
                   help="skip the warmup-manifest smoke (it runs only "
                        "in text mode; json/sarif stdout stays pure)")
    p.add_argument("--no-chaos-smoke", action="store_true",
                   help="skip the chaos-plan smoke (text mode only, "
                        "like the warmup smoke)")
    p.add_argument("--no-telemetry-smoke", action="store_true",
                   help="skip the telemetry smoke (traced serve "
                        "workload + /metrics parse + gap report; text "
                        "mode only)")
    p.add_argument("--no-sentinel-smoke", action="store_true",
                   help="skip the perf-regression sentinel smoke "
                        "(record -> replay -> ok; synthetic 3x "
                        "slowdown -> regressed; text mode only)")
    p.add_argument("--no-fleet-smoke", action="store_true",
                   help="skip the replica-fleet smoke (2-replica "
                        "fleet on CPU, one scripted kill, zero "
                        "un-typed errors + consistent router gauges; "
                        "text mode only)")
    p.add_argument("--no-rehome-smoke", action="store_true",
                   help="skip the subscription re-home smoke (a "
                        "routed geofence standing query across an "
                        "abrupt owner kill: zero missed/dup/phantom "
                        "transitions, one state resync, seq monotonic;"
                        " text mode only)")
    p.add_argument("--no-approx-smoke", action="store_true",
                   help="skip the approximate-answer smoke (sketch-"
                        "served tolerant counts with bounds verified "
                        "against exact replay + result-cache hit on "
                        "the second pass; text mode only)")
    p.add_argument("--no-wire-smoke", action="store_true",
                   help="skip the columnar-wire smoke (negotiated "
                        "columnar session with decoded parity vs a "
                        "JSON replay + one-encode push fan-out to 64 "
                        "in-process subscribers; text mode only)")
    p.add_argument("--no-ring-smoke", action="store_true",
                   help="skip the persistent-serve-loop smoke "
                        "(sequential kNN windows over one armed ring "
                        "program: bit-identity vs serial + "
                        "dispatches_per_window strictly below the "
                        "pipelined baseline; text mode only)")
    p.add_argument("--no-lane-smoke", action="store_true",
                   help="skip the vmapped-lane smoke (lane vs fused-"
                        "slot standing-query comparison at S=256 with "
                        "membership churn: >=10x events/s floor, "
                        "identical event totals, lane dispatches/poll "
                        "<=4; text mode only)")
    args = p.parse_args(argv)
    # incremental: a warm cache replays findings byte-identical to a
    # cold scan (asserted by tests/test_analysis_spmd.py), so repeated
    # gate runs — and the json/sarif renders CI takes after a green
    # text run — pay for one analysis, not one per invocation
    findings = lint_paths_incremental(
        [os.path.join(REPO_ROOT, "geomesa_tpu")])
    if args.format == "json":
        print(render_json(findings))
    elif args.format == "sarif":
        print(render_sarif(findings))
    else:
        print(render_text(findings))
    rc = exit_code(findings, "warn")
    if args.format == "text" and not args.no_spmd_smoke and rc == 0:
        rc = spmd_smoke()
    if args.format == "text" and not args.no_dataflow_smoke and rc == 0:
        rc = dataflow_smoke()
    if args.format == "text" and not args.no_warmup_smoke and rc == 0:
        rc = warmup_smoke()
    if args.format == "text" and not args.no_chaos_smoke and rc == 0:
        rc = chaos_smoke()
    if args.format == "text" and not args.no_telemetry_smoke and rc == 0:
        rc = telemetry_smoke()
    if args.format == "text" and not args.no_sentinel_smoke and rc == 0:
        rc = sentinel_smoke()
    if args.format == "text" and not args.no_fleet_smoke and rc == 0:
        rc = fleet_smoke()
    if args.format == "text" and not args.no_rehome_smoke and rc == 0:
        rc = rehome_smoke()
    if args.format == "text" and not args.no_approx_smoke and rc == 0:
        rc = approx_smoke()
    if args.format == "text" and not args.no_wire_smoke and rc == 0:
        rc = wire_smoke()
    if args.format == "text" and not args.no_ring_smoke and rc == 0:
        rc = ring_smoke()
    if args.format == "text" and not args.no_lane_smoke and rc == 0:
        rc = lane_smoke()
    return rc


if __name__ == "__main__":
    sys.exit(main())
