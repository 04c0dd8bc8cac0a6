"""SLO fabric tests (docs/OBSERVABILITY.md): the SLO engine's burn
math on a fake clock, the closed serve loop (injected latency fault ->
budget burn -> degradation ladder engages -> recovery, observable via
/debug/slo), the continuous profiler's fold semantics + overhead
budget, and the scrape-vs-fold races (/debug/gap + /metrics + /debug/prof under
concurrent mesh+pipelined traffic).

Wall-clock discipline (tier-1 budget is near-full): the serve fixtures
reuse the exact store/kernel shapes test_serve.py and
test_mesh_serve.py already compiled (512-row point store with k=5 kNN;
the 4-day/1024-row mesh store under a 4-chip mesh), all SLO window
arithmetic runs on a fake clock, and the single injected-latency fault
adds ~0.4s once.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from geomesa_tpu.telemetry.prof import ContinuousProfiler, render_prof
from geomesa_tpu.telemetry.slo import (SloEngine, SloSpec,
                                       parse_toml_subset, render_slo)

# -- spec parsing -----------------------------------------------------------


class TestSloSpec:
    def test_toml_subset_round_trip(self, tmp_path):
        p = tmp_path / "slo.toml"
        p.write_text("""
# serve objectives
[slo]
fast_window_s = 2.0
slow_window_s = 8.0   # scaled for tests
burn_threshold = 2.0

[objective.knn_p99]
kind = "latency"
threshold_ms = 25.0
goal = 0.9
query_kind = "knn"
degrade = true

[objective.availability]
kind = "availability"
goal = 0.999
""")
        spec = SloSpec.load(str(p))
        assert spec.fast_window_s == 2.0
        assert spec.budget_window_s == 8.0  # defaults to slow
        assert spec.objectives["knn_p99"].threshold_ms == 25.0
        assert spec.objectives["knn_p99"].degrade
        assert spec.objectives["availability"].kind == "availability"

    def test_json_spec(self, tmp_path):
        p = tmp_path / "slo.json"
        p.write_text(json.dumps({
            "slo": {"fast_window_s": 1.0, "slow_window_s": 4.0},
            "objective": {
                "tput": {"kind": "throughput", "min_per_s": 10.0},
            }}))
        spec = SloSpec.load(str(p))
        assert spec.objectives["tput"].min_per_s == 10.0

    def test_validation(self):
        with pytest.raises(ValueError, match="no .objective"):
            SloSpec.from_dict({"slo": {}})
        with pytest.raises(ValueError, match="unknown kind"):
            SloSpec.from_dict(
                {"objective": {"x": {"kind": "nope"}}})
        with pytest.raises(ValueError, match="threshold_ms"):
            SloSpec.from_dict(
                {"objective": {"x": {"kind": "latency"}}})
        with pytest.raises(ValueError, match="unknown key"):
            SloSpec.from_dict(
                {"objective": {"x": {"kind": "availability",
                                     "typo_ms": 3}}})
        with pytest.raises(ValueError, match="fast window"):
            SloSpec.from_dict({
                "slo": {"fast_window_s": 10.0, "slow_window_s": 5.0},
                "objective": {"x": {"kind": "availability"}}})

    def test_toml_parser_errors(self):
        with pytest.raises(ValueError, match="key = value"):
            parse_toml_subset("just words\n")
        with pytest.raises(ValueError, match="cannot parse"):
            parse_toml_subset("x = [1, 2]\n")


# -- burn math on a fake clock ----------------------------------------------


def make_engine(**objective_kw):
    spec = SloSpec.from_dict({
        "slo": {"fast_window_s": 2.0, "slow_window_s": 8.0,
                "burn_threshold": 2.0},
        "objective": {"obj": dict(
            {"kind": "latency", "threshold_ms": 10.0, "goal": 0.9,
             "degrade": True, "min_count": 4}, **objective_kw)},
    })
    now = [1000.0]
    eng = SloEngine(spec, clock=lambda: now[0])
    eng.boost_ttl_s = 0.0  # tests assert step-for-step
    return eng, now


class TestBurnMath:
    def test_clean_traffic_burns_nothing(self):
        eng, now = make_engine()
        for _ in range(20):
            eng.observe("knn", "ok", 0.001)
            now[0] += 0.05
        obj = eng.spec.objectives["obj"]
        rates = eng.burn_rates(obj)
        assert rates["fast"] == 0.0 and rates["slow"] == 0.0
        assert eng.budget_remaining(obj) == 1.0
        assert eng.breaching() == [] and eng.degrade_boost() == 0

    def test_bad_traffic_burns_and_recovers(self):
        eng, now = make_engine()
        obj = eng.spec.objectives["obj"]
        for _ in range(10):
            eng.observe("knn", "ok", 0.5)  # 500ms >> 10ms threshold
            now[0] += 0.05
        rates = eng.burn_rates(obj)
        # all-bad traffic burns at 1/budget = 10x
        assert rates["fast"] == pytest.approx(10.0)
        assert rates["slow"] == pytest.approx(10.0)
        assert eng.budget_remaining(obj) == 0.0
        assert eng.breaching() == ["obj"]
        assert eng.degrade_boost() == 2
        rep = eng.report()
        assert rep["objectives"]["obj"]["state"] == "violated"
        # recovery: the breach ages out of the windows
        now[0] += 10.0
        assert eng.breaching() == [] and eng.degrade_boost() == 0
        assert eng.budget_remaining(obj) == 1.0
        assert render_slo(eng.report())  # renders without data too

    def test_multiwindow_gate_needs_both(self):
        """A burst that clears the fast window while still polluting
        the slow one must NOT breach (and vice versa) — the classic
        multi-window rule."""
        eng, now = make_engine()
        for _ in range(10):
            eng.observe("knn", "ok", 0.5)
            now[0] += 0.05
        assert eng.breaching() == ["obj"]
        # 3s later: out of the 2s fast window, inside the 8s slow one
        now[0] += 3.0
        for _ in range(10):
            eng.observe("knn", "ok", 0.001)  # fast traffic now good
            now[0] += 0.01
        obj = eng.spec.objectives["obj"]
        rates = eng.burn_rates(obj)
        assert rates["fast"] == 0.0 and rates["slow"] > 2.0
        assert eng.breaching() == []

    def test_query_kind_filter(self):
        eng, now = make_engine(query_kind="knn")
        for _ in range(10):
            eng.observe("count", "ok", 0.5)  # wrong kind: ignored
        assert eng.burn_rates(eng.spec.objectives["obj"])["fast"] == 0.0

    def test_availability_counts_typed_errors_not_shedding(self):
        eng, now = make_engine(kind="availability", threshold_ms=0.0)
        obj = eng.spec.objectives["obj"]
        for status in ("ok", "error", "timeout", "rejected"):
            for _ in range(5):
                eng.observe("knn", status, 0.01)
        # 10 bad (error+timeout) of 20 counted (rejected excluded from
        # the bad set but still in the denominator)
        assert eng.burn_rates(obj)["fast"] == pytest.approx(
            (10 / 20) / 0.1)

    def test_exactness_counts_degraded(self):
        eng, now = make_engine(kind="exactness", threshold_ms=0.0)
        for i in range(10):
            eng.observe("knn", "ok", 0.01, degraded=(i % 2 == 0))
        assert eng.burn_rates(
            eng.spec.objectives["obj"])["fast"] == pytest.approx(5.0)

    def test_throughput_floor(self):
        eng, now = make_engine(kind="throughput", threshold_ms=0.0,
                               min_per_s=10.0)
        obj = eng.spec.objectives["obj"]
        # 2s of traffic at 20/s: above the floor
        for _ in range(40):
            eng.observe("knn", "ok", 0.001)
            now[0] += 0.05
        assert eng.burn_rates(obj)["fast"] == 0.0
        # traffic stops; the fast window drains to ~zero rate
        now[0] += 2.0
        assert eng.burn_rates(obj)["fast"] > 2.0

    def test_boost_cache_honors_ttl(self):
        eng, now = make_engine()
        eng.boost_ttl_s = 0.5
        for _ in range(10):
            eng.observe("knn", "ok", 0.5)
            now[0] += 0.01
        assert eng.degrade_boost() == 2
        # breach ages out, but the cache still answers until the TTL
        now[0] += 10.0
        eng.boost_ttl_s = 1e9
        eng._boost_cache = (now[0], 2)
        assert eng.degrade_boost() == 2  # cached
        eng.boost_ttl_s = 0.0
        assert eng.degrade_boost() == 0  # recomputed


# -- the closed serve loop --------------------------------------------------


@pytest.fixture(scope="module")
def slo_store(tmp_path_factory):
    """Same shapes as test_serve/test_telemetry (512-row point store,
    k=5 whole-world kNN) so the kernels are warm by suite order."""
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore

    rng = np.random.default_rng(7)
    n = 512
    sft = SimpleFeatureType.from_spec(
        "sloserve", "name:String,score:Double,dtg:Date,*geom:Point")
    batch = FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })
    tmp = tmp_path_factory.mktemp("sloserve")
    store = DataStore(str(tmp), use_device_cache=True)
    store.create_schema(sft).write(batch)
    return store


CQL = "BBOX(geom, -180, -90, 180, 90)"


class TestServeClosedLoop:
    """The acceptance demo: injected latency fault -> budget burn ->
    burn gauges flip -> ladder engages -> recovery, via /debug/slo."""

    def test_injected_latency_burns_budget_and_degrades(self, slo_store):
        from geomesa_tpu.faults import harness as fharness
        from geomesa_tpu.faults.plan import FaultPlan, FaultRule
        from geomesa_tpu.serve.service import QueryService, ServeConfig
        from geomesa_tpu.serve.scheduler import QueryRejected
        from geomesa_tpu.telemetry.export import MetricsServer
        from geomesa_tpu.utils.metrics import metrics

        # warm pass: residency upload + kernel compiles happen on a
        # throwaway service, so the measured phases see steady-state
        # latencies (the objective threshold is a wall-clock bound)
        rngw = np.random.default_rng(4)
        warm = QueryService(slo_store, ServeConfig(max_wait_ms=5.0))
        warm.knn("sloserve", CQL, rngw.uniform(-60, 60, 1),
                 rngw.uniform(-60, 60, 1), k=5).result(timeout=300)
        warm.close(drain=True)

        now = [5000.0]
        spec = SloSpec.from_dict({
            "slo": {"fast_window_s": 2.0, "slow_window_s": 8.0,
                    "burn_threshold": 2.0},
            "objective": {
                "knn_p99": {"kind": "latency", "threshold_ms": 150.0,
                            "goal": 0.9, "query_kind": "knn",
                            "degrade": True, "min_count": 4},
                "availability": {"kind": "availability", "goal": 0.99,
                                 "min_count": 4},
            }})
        eng = SloEngine(spec, clock=lambda: now[0])
        eng.boost_ttl_s = 0.0
        svc = QueryService(slo_store, ServeConfig(
            max_wait_ms=20.0, degrade=True, slo=eng), autostart=False)
        server = MetricsServer(port=0, stats_fn=svc.stats,
                               pre_scrape=svc.export_gauges,
                               slo_fn=eng.report)
        port = server.start()
        rng = np.random.default_rng(3)
        qp = rng.uniform(-60, 60, (8, 2))

        def burst(count):
            futs = [svc.knn("sloserve", CQL, qp[i:i + 1, 0],
                            qp[i:i + 1, 1], k=5) for i in range(count)]
            for f in futs:
                f.result(timeout=300)

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return r.read().decode()

        try:
            svc.start()
            # phase 1 — healthy traffic: no burn, ladder off
            burst(6)
            now[0] += 0.2
            doc = json.loads(get("/debug/slo"))
            assert doc["enabled"] and doc["breaching"] == []
            assert doc["objectives"]["knn_p99"]["state"] in (
                "ok", "insufficient-data")
            assert svc.degrade_level() == 0

            # phase 2 — inject a 400ms latency fault at the device
            # transfer boundary: every served kNN blows the 150ms
            # objective, the budget burns, the multi-window gate trips
            plan = FaultPlan(rules=[FaultRule(
                site="device.transfer", error="latency",
                probability=1.0, latency_ms=400.0)])
            fharness.install(plan)
            try:
                burst(6)
            finally:
                fharness.uninstall()
            now[0] += 0.2
            doc = json.loads(get("/debug/slo"))
            assert "knn_p99" in doc["breaching"], doc
            assert doc["objectives"]["knn_p99"]["burn_rate"]["fast"] > 2.0
            assert doc["objectives"]["knn_p99"]["budget_remaining"] < 1.0
            assert doc["degrade_boost"] >= 1
            # the ladder is engaged on burn alone — the queue is EMPTY
            assert len(svc.queue) == 0
            level = svc.degrade_level()
            assert level >= 1
            # level 2 (budget exhausted): batch-class work sheds typed
            if level >= 2:
                with pytest.raises(QueryRejected, match="shed"):
                    svc.count("sloserve", CQL, priority="batch")
            # degraded execution: an opted-in request gets the hint
            # rewrite, visible in the service counters
            before = svc.stats().get("degraded", 0)
            f = svc.knn("sloserve", CQL, qp[0:1, 0], qp[0:1, 1], k=5)
            # allow_degraded rides the kwargs path
            f2 = svc.knn("sloserve", CQL, qp[1:2, 0], qp[1:2, 1], k=5,
                         allow_degraded=True)
            f.result(timeout=300)
            f2.result(timeout=300)
            assert svc.stats().get("degraded", 0) == before + 1

            # the burn gauges export at scrape time
            body = get("/metrics")
            assert 'slo_burn_rate{objective="knn_p99",window="fast"}' \
                in body
            assert 'slo_budget_remaining{objective="knn_p99"}' in body

            # phase 3 — recovery: the breach ages out of both windows,
            # healthy traffic resumes, the ladder releases
            now[0] += 10.0
            burst(4)
            now[0] += 0.1
            doc = json.loads(get("/debug/slo"))
            assert doc["breaching"] == [] and doc["degrade_boost"] == 0
            assert doc["objectives"]["knn_p99"]["budget_remaining"] == 1.0
            assert svc.degrade_level() == 0
            # availability never burned: latency was slow, not failing
            assert doc["objectives"]["availability"]["burn_rate"][
                "slow"] == 0.0
            # /debug/stats carries the slo report for gmtpu top
            stats = json.loads(get("/debug/stats"))
            assert stats["serve"]["slo"]["enabled"]
        finally:
            server.stop()
            svc.close(drain=True)

    def test_window_rejection_observed_as_rejected_not_error(
            self, slo_store):
        """A pipelined window failed with QueryRejected (shutdown/
        drain) fans the rejection out to its members through
        _finish_window, where the wire status is 'error' — but the SLO
        observation must stay 'rejected': shedding never burns the
        availability budget (review regression)."""
        import time as _time

        from geomesa_tpu.serve.scheduler import (QueryRejected,
                                                 ServeRequest)
        from geomesa_tpu.serve.service import QueryService, ServeConfig
        from geomesa_tpu.plan.query import Query

        spec = {"slo": {"fast_window_s": 2.0, "slow_window_s": 8.0},
                "objective": {"avail": {"kind": "availability",
                                        "goal": 0.99}}}
        svc = QueryService(slo_store,
                           ServeConfig(max_wait_ms=1.0, slo=spec),
                           autostart=False)
        try:
            req = ServeRequest(kind="count",
                               query=Query("sloserve", CQL))
            req.enqueued_at = _time.monotonic()
            req.future.set_running_or_notify_cancel()
            req.future.set_exception(
                QueryRejected("shutting_down", "service closed"))
            svc._finish_window([req], [], req, req.enqueued_at,
                               _time.monotonic(), 0, None, 0, [], [],
                               pipelined=True)
            obs = list(svc.slo._obs)
            assert obs and obs[-1][2] == "rejected", obs
        finally:
            svc.close(drain=False)

    def test_wire_stats_verb_carries_slo(self, slo_store):
        from geomesa_tpu.serve.protocol import serve_lines
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        spec = {"slo": {"fast_window_s": 2.0, "slow_window_s": 8.0},
                "objective": {"avail": {"kind": "availability",
                                        "goal": 0.99}}}
        svc = QueryService(slo_store,
                           ServeConfig(max_wait_ms=5.0, slo=spec))
        out = []
        serve_lines(
            slo_store,
            [json.dumps({"id": "c1", "op": "count",
                         "typeName": "sloserve", "cql": CQL}),
             json.dumps({"id": "s1", "op": "stats"})],
            out.append, service=svc)
        docs = [json.loads(ln) for ln in out]
        stats = next(d for d in docs if d["id"] == "s1")
        assert stats["ok"] and stats["stats"]["slo"]["enabled"]
        assert "avail" in stats["stats"]["slo"]["objectives"]


# -- continuous profiler ----------------------------------------------------


def synth_trace(i, scale=1.0, proc="aa", shards=None, overlap=False):
    us = 1000
    # overlapping windows share wall time across traces
    t0 = (i * 50 if overlap else i * 200) * us
    attrs = {"kernel": "knn_sparse"}
    if shards:
        attrs["shards"] = shards
    return {
        "trace_id": f"{proc}-{i}", "name": "query",
        "root": {"name": "query", "id": i * 10 + 1, "parent": None,
                 "t0_ns": t0, "t1_ns": t0 + int(100 * us * scale),
                 "thread": 0},
        "spans": [
            {"name": "queue.wait", "id": i * 10 + 2, "parent": i * 10 + 1,
             "t0_ns": t0, "t1_ns": t0 + 40 * us, "thread": 0},
            {"name": "dispatch", "id": i * 10 + 3, "parent": i * 10 + 1,
             "t0_ns": t0 + 40 * us,
             "t1_ns": t0 + int(95 * us * scale), "thread": 0},
            {"name": "kernel.dispatch", "id": i * 10 + 4,
             "parent": i * 10 + 3, "t0_ns": t0 + 50 * us,
             "t1_ns": t0 + int(70 * us * scale), "thread": 0,
             "attrs": attrs},
        ],
    }


class TestProfiler:
    def test_fold_phases_kernels_and_root(self):
        p = ContinuousProfiler()
        p.enable()
        for i in range(20):
            p.fold(synth_trace(i))
        snap = p.snapshot()
        assert snap["traces"] == 20
        assert snap["phases"]["dispatch"]["n"] == 20
        assert snap["phases"]["query"]["n"] == 20  # root fold
        assert snap["phases"]["dispatch"]["p50_ms"] == pytest.approx(
            0.055, rel=0.01)
        k = snap["kernels"]["knn_sparse"]
        assert k["device"]["n"] == 20
        assert k["device"]["p50_ms"] == pytest.approx(0.02, rel=0.01)
        # host gap = window (55) - device (20) = 35µs, all attributed
        # to the only kernel family
        assert k["gap"]["p50_ms"] == pytest.approx(0.035, rel=0.01)
        assert render_prof(snap)

    def test_rider_dedup_by_span_id(self):
        """A rider-adopted copy of the shared window (same proc, same
        span ids) must not double-count the window."""
        p = ContinuousProfiler()
        p.enable()
        t = synth_trace(1)
        p.fold(t)
        rider = dict(synth_trace(1), trace_id="aa-99")
        p.fold(rider)
        snap = p.snapshot()
        assert snap["phases"]["dispatch"]["n"] == 1
        assert snap["traces"] == 2
        # a DIFFERENT process's identical ids are distinct spans
        p.fold(synth_trace(1, proc="bb"))
        assert p.snapshot()["phases"]["dispatch"]["n"] == 2

    def test_shard_lanes_and_imbalance(self):
        p = ContinuousProfiler()
        p.enable()
        for i in range(10):
            p.fold(synth_trace(i, shards="0,1"))
        for i in range(10, 14):
            p.fold(synth_trace(i, scale=3.0, shards="1"))
        snap = p.snapshot()
        lanes = snap["shards"]["lanes"]
        assert set(lanes) == {"0", "1"}
        assert lanes["1"]["device_ms"] > lanes["0"]["device_ms"]
        assert snap["shards"]["imbalance_ratio"] > 1.1

    def test_pipeline_overlap_estimate(self):
        p = ContinuousProfiler()
        p.enable()
        for i in range(10):
            p.fold(synth_trace(i, overlap=True))
        pl = p.snapshot()["pipeline"]
        assert pl["windows_in_flight_max"] >= 2
        assert pl["overlap_ms"] > 0.0
        # pairwise sums are clamped per window: at depth > 2 the share
        # must still read as a fraction of window time, never > 100%
        assert pl["overlap_share"] <= 1.0
        # serial windows report no overlap
        p2 = ContinuousProfiler()
        p2.enable()
        for i in range(10):
            p2.fold(synth_trace(i, overlap=False))
        assert p2.snapshot()["pipeline"]["overlap_ms"] == 0.0
        # depth-4: four identical windows would sum 3x pairwise
        # overlap per window without the clamp
        p3 = ContinuousProfiler()
        p3.enable()
        us = 1000
        for i in range(8):
            p3.fold({"trace_id": f"cc-{i}", "name": "q",
                     "root": {"name": "q", "id": i * 10 + 1,
                              "parent": None, "t0_ns": 0,
                              "t1_ns": 100 * us, "thread": 0},
                     "spans": [{"name": "dispatch", "id": i * 10 + 2,
                                "parent": i * 10 + 1, "t0_ns": 0,
                                "t1_ns": 100 * us, "thread": 0}]})
        deep = p3.snapshot()["pipeline"]
        assert deep["windows_in_flight_max"] >= 4
        assert deep["overlap_share"] <= 1.0, deep

    def test_recorder_hook_and_disable(self):
        from geomesa_tpu.telemetry.prof import PROFILER
        from geomesa_tpu.telemetry.recorder import FlightRecorder

        rec = FlightRecorder(capacity=4)
        PROFILER.reset()
        PROFILER.enable()
        try:
            rec.record(synth_trace(1))
            assert PROFILER.snapshot()["traces"] == 1
        finally:
            PROFILER.disable()
        rec.record(synth_trace(2))
        assert PROFILER.snapshot()["traces"] == 1  # off = no fold
        PROFILER.reset()

    def test_fold_overhead_budget(self):
        """The cost contract: the fold is one pass, 2µs per unit of
        work — a unit per span plus two fixed units (the root fold and
        the window/overlap bookkeeping, which amortize away on real
        ~15-span serve traces but dominate a 3-span synthetic). Same
        same-process relative fallback discipline as the tracer tests:
        a throttled CI host is measured against its own floor loop
        (the minimal possible span walk), and a structural regression
        — an O(n) seen-table sweep per fold, an unbounded window ring —
        blows the 25x-floor ratio on any host."""
        import gc

        p = ContinuousProfiler()
        p.enable()
        traces = [synth_trace(i) for i in range(2000)]
        spans_per = len(traces[0]["spans"])
        fold = floor = float("inf")
        # let the preceding serve tests' dispatcher/completer threads
        # finish dying: a busy sibling core reads as fold overhead
        time.sleep(0.1)
        gc.disable()
        try:
            for _ in range(9):
                p.reset()
                t0 = time.perf_counter_ns()
                for t in traces:
                    p.fold(t)
                fold = min(fold,
                           (time.perf_counter_ns() - t0) / len(traces))
                acc = 0
                t0 = time.perf_counter_ns()
                for t in traces:
                    for s in t["spans"]:
                        acc += s["t1_ns"] - s["t0_ns"]
                floor = min(floor,
                            (time.perf_counter_ns() - t0) / len(traces))
        finally:
            gc.enable()
        budget = 2000.0 * (spans_per + 2)
        assert fold < budget or fold < 25 * floor, (
            f"fold cost {fold:.0f}ns/trace ({spans_per} spans; floor "
            f"{floor:.0f}ns in the same process)")

    def test_disabled_maybe_fold_is_noop_cheap(self):
        p = ContinuousProfiler()
        doc = synth_trace(1)
        n = 20000
        t0 = time.perf_counter_ns()
        for _ in range(n):
            p.maybe_fold(doc)
        per = (time.perf_counter_ns() - t0) / n
        assert p.snapshot()["traces"] == 0
        # one attribute read + branch; generous bound for slow hosts
        assert per < 1000.0, f"disabled maybe_fold {per:.0f}ns"


class TestCliVerbs:
    def test_prof_and_sentinel_from_files(self, tmp_path, capsys):
        import argparse

        from geomesa_tpu.cli.commands import _prof

        p = ContinuousProfiler()
        p.enable()
        for i in range(20):
            p.fold(synth_trace(i))
        prof_doc = p.snapshot(include_samples=True)
        prof_path = tmp_path / "prof.json"
        prof_path.write_text(json.dumps(prof_doc))
        rc = _prof(argparse.Namespace(input=str(prof_path), url=None,
                                      host="", port=0, json=False))
        assert rc == 0
        assert "continuous profile" in capsys.readouterr().out


# -- gmtpu top --------------------------------------------------------------


class TestTopFrame:
    def test_mesh_subscriptions_and_slo_lines(self):
        from geomesa_tpu.cli.commands import _top_frame

        doc = {
            "metrics": {
                "histograms": {"serve.latency": {
                    "count": 40, "p50_s": 0.01, "p95_s": 0.02,
                    "p99_s": 0.03}},
                "counters": {
                    "knn.mesh.dispatches": 7.0,
                    "knn.mesh.local_dispatches": 2.0,
                    'serve.affinity.admitted{shards="0"}': 6.0,
                    'serve.affinity.admitted{shards="1,2"}': 4.0,
                },
                "gauges": {"serve.queue.depth": 1.0},
            },
            "serve": {
                "dispatches": 9, "coalesced": 3,
                "mesh": {"shape": [4], "devices": 4},
                "subscriptions": {"subscriptions": 5, "lagged": 1,
                                  "by_status": {"active": 3,
                                                "quarantined": 1}},
                "slo": {"enabled": True,
                        "objectives": {"p99": {"budget_remaining": 0.4}},
                        "breaching": ["p99"], "degrade_boost": 1},
                "quarantine": {},
            },
            "recorder": {},
            "breakers": {},
        }
        frame = _top_frame(doc, None, None)
        assert "mesh" in frame and "(4 dev)" in frame
        assert "7 mesh / 2 local" in frame
        # lane totals on the first poll: shard 0 = 6, shards 1/2 = 4
        assert "0:6" in frame and "1:4" in frame and "2:4" in frame
        assert "3 active, 1 lagged, 1 quarantined (5 total)" in frame
        assert "BREACHING: p99" in frame and "40.0%" in frame
        # second poll: lanes render as rates from counter deltas
        prev = json.loads(json.dumps(doc))
        doc["metrics"]["counters"][
            'serve.affinity.admitted{shards="0"}'] = 16.0
        frame2 = _top_frame(doc, prev, 2.0)
        assert "0:5.0/s" in frame2

    def test_plain_frame_unchanged_without_new_sections(self):
        from geomesa_tpu.cli.commands import _top_frame

        doc = {"metrics": {"histograms": {}, "counters": {},
                           "gauges": {}},
               "serve": {"quarantine": {}}, "recorder": {},
               "breakers": {}}
        frame = _top_frame(doc, None, None)
        assert "mesh" not in frame and "subs" not in frame
        assert "slo" not in frame


# -- scrape-vs-fold races ---------------------------------------------------


MESH_D = 4
ROWS_PER_DAY = 256
DAYS = ("2020-06-01", "2020-06-02", "2020-06-03", "2020-06-04")
MESH_CQL = "BBOX(geom, -170, -80, 170, 80) AND score > -5"


def _mesh_batch():
    """Identical shapes to test_mesh_serve.make_batch (4 day-partitions
    x 256 rows) so the mesh-keyed AOT executables are already warm when
    the suite runs in order."""
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType

    rng = np.random.default_rng(11)
    n = ROWS_PER_DAY * len(DAYS)
    dtg = np.concatenate([
        int(np.datetime64(day, "ms").astype(np.int64))
        + rng.integers(6 * 3600_000, 18 * 3600_000, ROWS_PER_DAY)
        for day in DAYS
    ])
    sft = SimpleFeatureType.from_spec(
        "meshed", "name:String,score:Double,dtg:Date,*geom:Point")
    return sft, FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": dtg,
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })


class TestScrapeVsFoldRaces:
    """/debug/gap, /metrics (gauge export) and /debug/prof answered
    WHILE mesh+pipelined traffic records traces and folds profiles —
    the scrape-vs-fold interleavings were previously untested. The
    assertions are response integrity (every scrape parses, gap
    coverage sane, no 500s) under genuine concurrency, not timing."""

    def test_concurrent_scrapes_parse_under_mesh_traffic(
            self, tmp_path_factory):
        from geomesa_tpu.plan.datastore import DataStore
        from geomesa_tpu.serve.service import QueryService, ServeConfig
        from geomesa_tpu.telemetry import RECORDER, TRACER
        from geomesa_tpu.telemetry.export import MetricsServer
        from geomesa_tpu.telemetry.prof import PROFILER

        sft, batch = _mesh_batch()
        root = str(tmp_path_factory.mktemp("slo_mesh"))
        store = DataStore(root, use_device_cache=True)
        store.create_schema(sft).write(batch)
        RECORDER.clear()
        PROFILER.reset()
        PROFILER.enable()
        TRACER.enable()
        spec = {"slo": {"fast_window_s": 30.0, "slow_window_s": 60.0},
                "objective": {"p99": {"kind": "latency",
                                      "threshold_ms": 5000.0,
                                      "goal": 0.9}}}
        svc = QueryService(store, ServeConfig(
            mesh=MESH_D, max_wait_ms=10.0, slo=spec), autostart=False)
        server = MetricsServer(port=0, stats_fn=svc.stats,
                               pre_scrape=svc.export_gauges,
                               slo_fn=svc.slo.report)
        port = server.start()
        scrape_errors = []
        gap_docs = []
        stop = threading.Event()

        def scraper():
            import re

            sample = re.compile(
                r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$')
            while not stop.is_set():
                try:
                    for path in ("/debug/gap", "/metrics",
                                 "/debug/prof", "/debug/slo"):
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
                            body = r.read().decode()
                        if path == "/metrics":
                            bad = [ln for ln in body.splitlines()
                                   if ln and not ln.startswith("#")
                                   and not sample.match(ln)]
                            if bad:
                                scrape_errors.append(
                                    f"unparseable: {bad[:2]}")
                        else:
                            doc = json.loads(body)
                            if path == "/debug/gap":
                                gap_docs.append(doc)
                                if doc.get("coverage", 0) > 1.0:
                                    scrape_errors.append(
                                        f"coverage > 1: {doc}")
                except Exception as e:  # noqa: BLE001 — the assertion
                    scrape_errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=scraper, daemon=True)
                   for _ in range(2)]
        rng = np.random.default_rng(23)
        try:
            svc.start()
            for t in threads:
                t.start()
            futs = []
            for i in range(16):
                qp = rng.uniform(-60, 60, (1, 2))
                futs.append(svc.knn("meshed", MESH_CQL, qp[:, 0],
                                    qp[:, 1], k=5))
                if i % 5 == 4:
                    futs.append(svc.count("meshed", MESH_CQL))
            for f in futs:
                f.result(timeout=300)
            # at least one scrape lands while traffic is in flight;
            # give the scrapers one more full round over a non-empty
            # recorder before stopping them
            time.sleep(0.2)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            server.stop()
            svc.close(drain=True)
            TRACER.disable()
            PROFILER.disable()
        assert not scrape_errors, scrape_errors[:5]
        assert gap_docs, "no /debug/gap scrape completed"
        # the final gap view over the drained recorder is coherent
        final = gap_docs[-1]
        assert final["traces"] >= 1
        assert 0.0 <= final["coverage"] <= 1.0
        # the profiler folded the same traffic the recorder holds
        snap = PROFILER.snapshot()
        assert snap["traces"] >= final["traces"]
        assert "dispatch" in snap["phases"]
        PROFILER.reset()
