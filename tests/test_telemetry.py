"""geomesa_tpu.telemetry tests: span core semantics + the hard
per-span overhead budget, trace round-trip through the Perfetto export
under a concurrent serve workload (parent/child + monotonic-nesting
invariants), flight-recorder bounded memory + crash-dump path, labeled
metrics export, the /metrics HTTP endpoint, and the dispatch-gap
report. Everything runs in-process on tiny stores; the serve workload
reuses the shapes test_serve.py already compiled so the suite pays no
new kernel compiles."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from geomesa_tpu.telemetry.export import (MetricsServer, from_perfetto,
                                          to_perfetto, write_jsonl)
from geomesa_tpu.telemetry.gap import gap_report, render_gap
from geomesa_tpu.telemetry.recorder import FlightRecorder
from geomesa_tpu.telemetry.trace import NOOP_SPAN, Trace, Tracer


# -- span core --------------------------------------------------------------


class TestTraceCore:
    def test_disabled_returns_shared_noop(self):
        tr = Tracer()
        s = tr.span("x")
        assert s is NOOP_SPAN
        with s as inner:
            inner.set(a=1)  # no-op, no error
        assert tr.start_trace("q") is None
        assert tr.current_trace() is None

    def test_enabled_but_unscoped_is_noop(self):
        tr = Tracer()
        tr.enable()
        assert tr.span("x") is NOOP_SPAN

    def test_nesting_and_parentage(self):
        tr = Tracer()
        tr.enable()
        trace = tr.start_trace("q", kind="knn")
        with tr.scope(trace):
            with tr.span("outer") as outer:
                with tr.span("inner", k=5) as inner:
                    pass
                # the scope's SHARED handle holds the just-closed span:
                # read ids immediately after each block exits
                inner_id = inner.span_id
            outer_id = outer.span_id
            with tr.span("sibling"):
                pass
        trace.finish(status="ok")
        spans = {s.name: s for s in trace.snapshot_spans()}
        assert spans["outer"].parent_id == trace.root.span_id
        assert spans["inner"].parent_id == outer_id
        assert spans["sibling"].parent_id == trace.root.span_id
        assert spans["inner"].attrs == {"k": 5}
        assert inner_id == spans["inner"].span_id
        assert outer_id == spans["outer"].span_id
        # monotonic nesting
        assert (spans["outer"].start_ns <= spans["inner"].start_ns
                <= spans["inner"].end_ns <= spans["outer"].end_ns)
        assert trace.root.attrs["status"] == "ok"
        assert trace.root.end_ns >= spans["sibling"].end_ns

    def test_exception_marks_error_and_unwinds(self):
        tr = Tracer()
        tr.enable()
        trace = tr.start_trace("q")
        with tr.scope(trace):
            with pytest.raises(ValueError):
                with tr.span("boom"):
                    raise ValueError("x")
            with tr.span("after"):
                pass
        spans = {s.name: s for s in trace.snapshot_spans()}
        assert spans["boom"].attrs["error"] == "ValueError"
        # the stack unwound: "after" is a root child, not boom's child
        assert spans["after"].parent_id == trace.root.span_id

    def test_record_and_finish_idempotent(self):
        tr = Tracer()
        tr.enable()
        trace = tr.start_trace("q")
        t0 = time.perf_counter_ns()
        trace.record("queue.wait", t0, t0 + 1000, waited=True)
        trace.finish(status="ok")
        end1 = trace.root.end_ns
        trace.finish(status="late")
        assert trace.root.end_ns == end1  # first close wins
        assert trace.root.attrs["status"] == "ok"

    def test_adopt_reparents_and_clamps(self):
        tr = Tracer()
        tr.enable()
        lead = tr.start_trace("lead")
        with tr.scope(lead):
            with tr.span("dispatch") as d:
                with tr.span("kernel.dispatch"):
                    pass
        rider = tr.start_trace("rider")
        clamp = rider.root.start_ns
        rider.adopt(lead.snapshot_spans(), clamp_start_ns=clamp)
        spans = {s.name: s for s in rider.snapshot_spans()}
        # the dispatch span re-parented to the rider's root; its child
        # kept its real parent (ids are preserved for gap dedup)
        assert spans["dispatch"].parent_id == rider.root.span_id
        assert spans["kernel.dispatch"].parent_id == d.span_id
        assert all(s.start_ns >= clamp for s in rider.snapshot_spans())


class TestOverheadBudget:
    """The hard budget: <2µs per live span, unmeasurable when off.

    Methodology: min over 9 trials with gc paused and a FRESH trace per
    trial (a shared multi-hundred-k span list would measure list
    growth, not span cost). The shared CI host sometimes throttles a
    whole process ~2.5x — visible as the no-op loop (pure `with`
    machinery, no clock/alloc) costing 3x its quiet-floor; the relative
    fallback (live ≤ 6x no-op, measured in the SAME process) keeps the
    assertion about OUR code's overhead rather than the host's mood. A
    genuinely regressed hot path fails both arms on a quiet host."""

    N = 10_000
    _cached = None  # one measurement serves both assertions (wall-
    # clock budget: the suite sits within ~40s of the tier-1 timeout)

    def _measure(self):
        if TestOverheadBudget._cached is not None:
            return TestOverheadBudget._cached
        import gc

        tr_on = Tracer()
        tr_on.enable()
        tr_off = Tracer()
        live = noop = float("inf")
        gc.disable()
        try:
            for _ in range(7):
                trace = tr_on.start_trace("bench")
                t0 = time.perf_counter_ns()
                with tr_on.scope(trace):
                    for _ in range(self.N):
                        with tr_on.span("s"):
                            pass
                live = min(live,
                           (time.perf_counter_ns() - t0) / self.N)
                t0 = time.perf_counter_ns()
                for _ in range(self.N):
                    with tr_off.span("s"):
                        pass
                noop = min(noop,
                           (time.perf_counter_ns() - t0) / self.N)
        finally:
            gc.enable()
        TestOverheadBudget._cached = (live, noop)
        return live, noop

    def test_live_span_under_2us(self):
        live, noop = self._measure()
        assert live < 2000 or live < 6 * noop, (
            f"live span cost {live:.0f}ns/span "
            f"(no-op floor {noop:.0f}ns in the same process)")

    def test_noop_fast_path_unmeasurable(self):
        live, noop = self._measure()
        # "unmeasurable": no allocation, no clock read — a shared
        # singleton and one tls read, far under the live-span cost
        assert noop < max(500.0, live * 0.5), (
            f"no-op span cost {noop:.0f}ns/span (live {live:.0f}ns)")
        tr = Tracer()
        assert tr.span("s") is tr.span("t")  # shared singleton


# -- serve round-trip -------------------------------------------------------


@pytest.fixture(scope="module")
def traced_workload(tmp_path_factory):
    """One concurrent traced serve workload shared by the round-trip
    assertions: 8 coalescible kNN + 3 dedup counts submitted from 4
    client threads (same store/kernel shapes as test_serve.py, so the
    jit caches are already warm when the suite runs in order)."""
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore
    from geomesa_tpu.serve.service import QueryService, ServeConfig
    from geomesa_tpu.telemetry.recorder import RECORDER
    from geomesa_tpu.telemetry.trace import TRACER

    rng = np.random.default_rng(7)
    n = 512
    sft = SimpleFeatureType.from_spec(
        "teletrip", "name:String,score:Double,dtg:Date,*geom:Point")
    batch = FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })
    cql = "BBOX(geom, -180, -90, 180, 90)"
    tmp = tmp_path_factory.mktemp("teletrip")
    store = DataStore(str(tmp), use_device_cache=True)
    src = store.create_schema(sft)
    src.write(batch)
    RECORDER.clear()
    TRACER.enable()
    try:
        svc = QueryService(store, ServeConfig(max_wait_ms=25.0),
                           autostart=False)
        qp = rng.uniform(-60, 60, (8, 2))
        futs = []
        futs_lock = threading.Lock()

        def client(idxs):
            for i in idxs:
                if i < 8:
                    f = svc.knn("teletrip", cql, qp[i:i + 1, 0],
                                qp[i:i + 1, 1], k=5)
                else:
                    f = svc.count("teletrip", cql)
                with futs_lock:
                    futs.append(f)

        threads = [threading.Thread(target=client, args=(range(c, 11, 4),))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        svc.start()
        for f in futs:
            f.result(timeout=120)
        svc.close(drain=True)
    finally:
        TRACER.disable()
    traces = RECORDER.traces()
    events = store.audit.snapshot()
    return {"traces": traces, "audit": events}


class TestServeRoundTrip:
    def _check_invariants(self, traces):
        assert traces, "no traces recorded"
        for t in traces:
            root = t["root"]
            ids = {root["id"]}
            by_id = {root["id"]: root}
            for s in t["spans"]:
                ids.add(s["id"])
                by_id[s["id"]] = s
            for s in t["spans"]:
                # every parent exists in the same trace
                assert s["parent"] in ids, (t["trace_id"], s)
                assert s["t1_ns"] >= s["t0_ns"]
                # monotonic nesting: a child lies within its parent
                # (root children may start before the root only never —
                # adoption clamps to the rider's root start)
                p = by_id[s["parent"]]
                if p is not root:
                    assert s["t0_ns"] >= p["t0_ns"] - 1, (s, p)
                    assert s["t1_ns"] <= p["t1_ns"] + 1, (s, p)
                else:
                    assert s["t0_ns"] >= root["t0_ns"], (s, root)

    def test_trace_structure_and_phases(self, traced_workload):
        traces = traced_workload["traces"]
        assert len(traces) == 11
        self._check_invariants(traces)
        for t in traces:
            names = {s["name"] for s in t["spans"]}
            assert {"admit", "queue.wait", "dispatch"} <= names, names
            assert t["root"]["attrs"]["status"] == "ok"
        # kNN traces reached the kernel seams
        knn = [t for t in traces if t["root"]["attrs"]["kind"] == "knn"]
        assert knn and all(
            "kernel.dispatch" in {s["name"] for s in t["spans"]}
            for t in knn)

    def test_perfetto_round_trip(self, traced_workload):
        traces = traced_workload["traces"]
        doc = json.loads(json.dumps(to_perfetto(traces)))
        assert all(e["ph"] in ("M", "X") for e in doc["traceEvents"])
        back = from_perfetto(doc)
        assert len(back) == len(traces)
        self._check_invariants(back)
        by_id = {t["trace_id"]: t for t in back}
        for t in traces:
            rt = by_id[t["trace_id"]]
            assert {s["id"] for s in rt["spans"]} == {
                s["id"] for s in t["spans"]}
            assert {(s["name"], s["parent"]) for s in rt["spans"]} == {
                (s["name"], s["parent"]) for s in t["spans"]}

    def test_jsonl_export(self, traced_workload):
        lines = []
        n = write_jsonl(traced_workload["traces"], lines.append)
        assert n == 11 and len(lines) == 11
        assert all(json.loads(ln)["trace_id"] for ln in lines)

    def test_audit_correlation(self, traced_workload):
        """ServeEvent.trace_id joins the audit log to the recorder."""
        from geomesa_tpu.plan.audit import ServeEvent

        events = [e for e in traced_workload["audit"]
                  if isinstance(e, ServeEvent)
                  and e.type_name == "teletrip"]
        assert len(events) == 11
        trace_ids = {t["trace_id"] for t in traced_workload["traces"]}
        assert all(e.trace_id in trace_ids for e in events)
        assert len({e.trace_id for e in events}) == 11

    def test_gap_report_coverage(self, traced_workload):
        traces = traced_workload["traces"]
        rep = gap_report(traces)
        assert rep["traces"] == 11
        assert rep["dispatch_gap"]["windows"] >= 1
        # acceptance bar: per-phase root coverage within 5% of wall
        assert rep["coverage"] >= 0.95, rep
        assert {"admit", "queue.wait", "dispatch"} <= set(rep["phases"])
        g = rep["dispatch_gap"]
        assert 0 <= g["gap_fraction"] <= 1
        assert g["device_ms"] + g["host_gap_ms"] <= g["exec_ms"] * 1.01
        text = render_gap(rep)
        assert "dispatch windows" in text and "coverage" in text

    def test_shared_window_dedup(self, traced_workload):
        """Coalesced riders adopt copies of the lead's window spans with
        ids preserved; the gap report counts each window once."""
        traces = traced_workload["traces"]
        dispatch_ids = [s["id"] for t in traces for s in t["spans"]
                        if s["name"] == "dispatch"]
        rep = gap_report(traces)
        assert rep["dispatch_gap"]["windows"] == len(set(dispatch_ids))
        assert len(dispatch_ids) > len(set(dispatch_ids))  # sharing real


# -- flight recorder --------------------------------------------------------


class TestFlightRecorder:
    def _trace(self, name="q"):
        t = Trace(name)
        return t.finish(status="ok")

    def test_bounded_memory(self):
        rec = FlightRecorder(capacity=4, event_capacity=8)
        for _ in range(10):
            rec.record(self._trace())
        for i in range(20):
            rec.note_event("fault", site=f"s{i}")
        snap = rec.snapshot()
        assert len(snap["traces"]) == 4
        assert len(snap["events"]) == 8
        assert snap["dropped_traces"] == 6
        assert snap["dropped_events"] == 12
        assert snap["events"][-1]["site"] == "s19"  # newest kept

    def test_record_accepts_none_and_dict(self):
        rec = FlightRecorder(capacity=4)
        rec.record(None)
        rec.record({"trace_id": "x", "root": {}, "spans": []})
        assert len(rec.traces()) == 1

    def test_crash_dump(self, tmp_path):
        rec = FlightRecorder(capacity=4)
        rec.auto_dump_path = str(tmp_path / "flight.json")
        rec.record(self._trace())
        path = rec.crash_dump("dispatch loop error",
                              RuntimeError("boom"))
        assert path == rec.auto_dump_path
        doc = json.loads((tmp_path / "flight.json").read_text())
        assert doc["reason"] == "dispatch loop error"
        assert doc["traces"] and doc["events"][-1]["kind"] == "crash"
        assert "RuntimeError: boom" in doc["events"][-1]["error"]

    def test_breaker_transitions_land_in_recorder(self):
        from geomesa_tpu.faults.breaker import CircuitBreaker
        from geomesa_tpu.telemetry.recorder import RECORDER

        before = len(RECORDER.events())
        b = CircuitBreaker("teledep", failure_threshold=1,
                           reset_timeout_s=0.0)
        b.record_failure()   # -> open
        b.allow()            # -> half_open
        b.record_success()   # -> closed
        new = RECORDER.events()[before:]
        got = [(e["dependency"], e["state"]) for e in new
               if e["kind"] == "breaker" and e["dependency"] == "teledep"]
        assert got == [("teledep", "open"), ("teledep", "half_open"),
                       ("teledep", "closed")]

    def test_quarantine_strikes_land_in_recorder(self):
        from geomesa_tpu.faults.quarantine import QuarantineRegistry
        from geomesa_tpu.telemetry.recorder import RECORDER

        before = len(RECORDER.events())
        q = QuarantineRegistry(strikes=2, ttl_s=60.0)
        assert not q.strike(("k",))
        assert q.strike(("k",))
        acts = [e["action"] for e in RECORDER.events()[before:]
                if e["kind"] == "quarantine"]
        assert acts == ["strike", "trip"]


# -- labeled metrics --------------------------------------------------------


class TestMetricsLabels:
    def test_label_series_and_export(self):
        from geomesa_tpu.utils.metrics import MetricsRegistry

        m = MetricsRegistry()
        m.counter("serve.requests")
        m.counter("serve.requests", kind="knn", status="ok")
        m.counter("serve.requests", 2, kind="knn", status="ok")
        m.counter("serve.requests", kind="count", status="error")
        m.gauge("depth", 3, shard="a")
        txt = m.to_prometheus()
        # one TYPE declaration per family, proper label syntax
        assert txt.count("# TYPE serve_requests counter") == 1
        assert 'serve_requests{kind="knn",status="ok"} 3.0' in txt
        assert 'serve_requests{kind="count",status="error"} 1.0' in txt
        assert "serve_requests 1.0" in txt.splitlines()
        assert 'depth{shard="a"} 3.0' in txt

    def test_labeled_histograms_merge_and_export(self):
        from geomesa_tpu.utils.metrics import MetricsRegistry

        m = MetricsRegistry()
        ha = m.histogram("lat", tenant="a")
        hb = m.histogram("lat", tenant="b")
        assert m.histogram("lat", tenant="a") is ha  # stable series
        ha.update(0.01)
        hb.update(0.02)
        ha.merge(hb)  # merge() works across labeled series
        assert ha.count == 2
        txt = m.to_prometheus()
        assert 'lat_seconds_bucket{tenant="a",le="0.016"} 1' in txt
        assert 'lat_seconds_count{tenant="a"} 2' in txt
        assert 'lat_seconds_count{tenant="b"} 1' in txt

    def test_families_render_contiguously(self):
        """The text format requires every sample of a family to be
        contiguous — interleaved insertion across families must not
        interleave the rendered output (strict parsers reject it)."""
        from geomesa_tpu.utils.metrics import MetricsRegistry

        m = MetricsRegistry()
        m.counter("serve.requests", kind="knn")
        m.counter("serve.tenant.requests", tenant="a")
        m.counter("serve.requests", kind="count")
        ha = m.histogram("lat", tenant="a")
        m.histogram("other")
        hb = m.histogram("lat", tenant="b")
        ha.update(0.01)
        hb.update(0.02)
        lines = m.to_prometheus().splitlines()
        idx = [i for i, ln in enumerate(lines)
               if ln.startswith("serve_requests{")]
        assert len(idx) == 2 and idx[1] == idx[0] + 1
        # the lat_seconds family (bucket/sum/count samples of BOTH
        # label sets) must form one contiguous block with no foreign
        # family (other_seconds) inside it
        fam = [i for i, ln in enumerate(lines)
               if ln.startswith(("lat_seconds_bucket{",
                                 "lat_seconds_sum", "lat_seconds_count"))]
        inside = lines[fam[0]:fam[-1] + 1]
        assert not any(ln.startswith("other_seconds") for ln in inside)
        # TYPE declared exactly once per family, before its samples
        assert sum(ln == "# TYPE serve_requests counter"
                   for ln in lines) == 1
        assert lines.index("# TYPE serve_requests counter") < idx[0]

    def test_label_cardinality_bounded(self):
        """Client-controlled label values (per-tenant series) must not
        grow the registry without bound: past the per-family cap, new
        label sets fold into the unlabeled aggregate."""
        from geomesa_tpu.utils.metrics import MetricsRegistry

        m = MetricsRegistry()
        cap = MetricsRegistry.MAX_LABELED_SERIES_PER_FAMILY
        for i in range(cap + 50):
            m.counter("serve.tenant.requests", tenant=f"t{i}")
        labeled = [k for k in m.counters
                   if k.startswith("serve.tenant.requests{")]
        assert len(labeled) == cap
        # the 50 overflow increments landed on the aggregate series
        assert m.counters["serve.tenant.requests"] == 50.0
        # an already-registered series keeps updating past the cap
        m.counter("serve.tenant.requests", tenant="t0")
        assert m.counters['serve.tenant.requests{tenant="t0"}'] == 2.0

    def test_label_escaping(self):
        from geomesa_tpu.utils.metrics import MetricsRegistry

        m = MetricsRegistry()
        m.counter("c", cql='BBOX(geom, "x")\n')
        txt = m.to_prometheus()
        assert 'c{cql="BBOX(geom, \\"x\\")\\n"} 1.0' in txt


# -- metrics server ---------------------------------------------------------


class TestMetricsServer:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()

    def test_endpoints(self):
        rec = FlightRecorder(capacity=4)
        rec.record(Trace("q").finish(status="ok"))
        scraped = []
        server = MetricsServer(
            port=0, stats_fn=lambda: {"dispatches": 3},
            pre_scrape=lambda: scraped.append(1), recorder=rec)
        port = server.start()
        try:
            base = f"http://127.0.0.1:{port}"
            status, body = self._get(f"{base}/metrics")
            assert status == 200 and "# TYPE" in body
            assert scraped  # pre_scrape hook ran
            status, body = self._get(f"{base}/healthz")
            doc = json.loads(body)
            assert status == 200 and doc["ok"]
            assert doc["serve"] == {"dispatches": 3}
            _, body = self._get(f"{base}/debug/traces")
            assert len(from_perfetto(json.loads(body))) == 1
            _, body = self._get(f"{base}/debug/stats")
            doc = json.loads(body)
            assert doc["serve"] == {"dispatches": 3}
            assert doc["recorder"]["traces_held"] == 1
            assert "breakers" in doc
            _, body = self._get(f"{base}/debug/gap")
            assert json.loads(body)["traces"] == 1
            try:
                self._get(f"{base}/nope")
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()


# -- gap report math --------------------------------------------------------


class TestGapReport:
    def test_synthetic_attribution(self):
        us = 1000  # ns per µs keeps the arithmetic readable
        root = {"name": "query", "id": 1, "parent": None,
                "t0_ns": 0, "t1_ns": 100 * us, "thread": 0}
        spans = [
            {"name": "queue.wait", "id": 2, "parent": 1,
             "t0_ns": 0, "t1_ns": 40 * us, "thread": 0},
            {"name": "dispatch", "id": 3, "parent": 1,
             "t0_ns": 40 * us, "t1_ns": 100 * us, "thread": 0},
            {"name": "kernel.dispatch", "id": 4, "parent": 3,
             "t0_ns": 50 * us, "t1_ns": 70 * us, "thread": 0},
            {"name": "plan", "id": 5, "parent": 3,
             "t0_ns": 41 * us, "t1_ns": 49 * us, "thread": 0},
        ]
        rep = gap_report([{"trace_id": "t1", "name": "query",
                           "root": root, "spans": spans}])
        assert rep["wall_ms"] == pytest.approx(0.1)
        assert rep["coverage"] == pytest.approx(1.0)
        g = rep["dispatch_gap"]
        assert g["windows"] == 1
        assert g["exec_ms"] == pytest.approx(0.06)
        assert g["device_ms"] == pytest.approx(0.02)
        assert g["host_gap_ms"] == pytest.approx(0.04)
        assert g["gap_fraction"] == pytest.approx(0.04 / 0.06, abs=1e-3)

    def test_empty_input(self):
        rep = gap_report([])
        assert rep["traces"] == 0 and rep["phases"] == {}
        assert render_gap(rep)

    def test_multi_process_dumps_do_not_collide(self):
        """Span ids are per-process counters; merged replica dumps
        dedup by (process, id) — trace ids are pid-qualified exactly
        so this works."""
        def trace_from(pid, trace_seq):
            us = 1000
            return {
                "trace_id": f"{pid}-{trace_seq}", "name": "query",
                "root": {"name": "query", "id": 1, "parent": None,
                         "t0_ns": 0, "t1_ns": 10 * us, "thread": 0},
                "spans": [{"name": "dispatch", "id": 2, "parent": 1,
                           "t0_ns": 0, "t1_ns": 10 * us, "thread": 0}],
            }

        rep = gap_report([trace_from("aa", 1), trace_from("bb", 1)])
        assert rep["dispatch_gap"]["windows"] == 2
        assert rep["phases"]["dispatch"]["count"] == 2
        # same process, same ids = one shared (adopted) window
        rep = gap_report([trace_from("aa", 1), trace_from("aa", 2)])
        assert rep["dispatch_gap"]["windows"] == 1


# -- one clock with the profiler --------------------------------------------


def _profile(tmp_path, body):
    """Run `body()` under a CPU jax.profiler trace; the host-plane
    events as [(name, start_ns, stats dict)]."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    return [(e.name, int(e.start_ns), dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events]


class TestProfilerMirror:
    def test_live_spans_land_on_the_host_plane(self, tmp_path):
        """While tracing is on, every live span is a profiler TraceMe of
        the same name carrying its span id; the per-span offsets
        between the two clocks agree to well under 50 µs."""
        tr = Tracer()
        tr.enable()
        trace = tr.start_trace("q")

        def body():
            with tr.scope(trace):
                for _ in range(200):
                    with tr.span("mirror.outer"):
                        with tr.span("mirror.inner", k=1):
                            pass

        events = _profile(tmp_path, body)
        spans = {s.span_id: s for s in trace.snapshot_spans()}
        got = [(n, t0, st) for n, t0, st in events
               if n in ("mirror.outer", "mirror.inner")]
        assert len(got) == len(spans) == 400
        offsets = []
        for name, t0, stats in got:
            s = spans[int(stats["sid"])]
            assert s.name == name
            offsets.append(t0 - s.start_ns)
        assert max(offsets) - min(offsets) < 50_000, (
            min(offsets), max(offsets))

    def test_recorded_spans_and_no_profiler_issue_no_traceme(
            self, tmp_path, monkeypatch):
        """Spans stamped after the fact have no TraceMe, and where the
        profiler's type cannot be had (no jax) live spans still record."""
        from geomesa_tpu.telemetry import trace as T

        monkeypatch.setattr(T, "_traceme_type", lambda: None)
        tr = Tracer()
        tr.enable()
        trace = tr.start_trace("q")

        def body():
            with tr.scope(trace):
                with tr.span("mirror.off"):
                    pass
            t0 = time.perf_counter_ns()
            trace.record("mirror.recorded", t0, t0 + 10)

        names = {n for n, _, _ in _profile(tmp_path, body)}
        assert not names & {"mirror.off", "mirror.recorded"}
        assert {s.name for s in trace.snapshot_spans()} == {
            "mirror.off", "mirror.recorded"}

    def test_disabled_span_stays_the_shared_noop(self):
        tr = Tracer()
        tr.enable()
        tr.disable()
        trace = Trace("q")
        with tr.scope(trace):
            assert tr.span("x") is NOOP_SPAN
            assert tr.span("y", a=1) is NOOP_SPAN
        assert trace.span_count() == 0


class TestDeviceGet:
    def test_read_is_one_device_sync_span(self):
        import jax.numpy as jnp

        from geomesa_tpu.telemetry.trace import TRACER, device_get

        x = jnp.arange(8) * 2
        assert device_get(x).tolist() == list(range(0, 16, 2))  # unscoped
        TRACER.enable()
        try:
            trace = TRACER.start_trace("q")
            with TRACER.scope(trace):
                with TRACER.span("outer"):
                    got = device_get((x, x + 1), ring=True)
        finally:
            TRACER.disable()
        assert int(got[1][0]) == 1
        spans = {s.name: s for s in trace.snapshot_spans()}
        sync = spans["device.sync"]
        assert sync.parent_id == spans["outer"].span_id
        assert sync.attrs == {"ring": True}


class TestStallStamps:
    def test_since_carries_each_stall_start(self):
        from geomesa_tpu.compilecache.stall import StallMeter

        m = StallMeter()
        tok = m.token()
        t0 = time.perf_counter_ns()
        m.note("k:a", 0.002, start_ns=t0 - 5_000_000)
        m.note("k:b", 0.001)
        t1 = time.perf_counter_ns()
        (la, sa, da), (lb, sb, db) = m.since(tok)
        assert (la, sa, da) == ("k:a", t0 - 5_000_000, 0.002)
        # no stamp given: the stall ended as it was noted
        assert lb == "k:b" and db == 0.001
        assert t0 - 1_000_000 <= sb <= t1 - 1_000_000
        assert m.since(m.token()) == []

    def test_compile_stall_span_at_its_real_interval(self):
        from types import SimpleNamespace

        from geomesa_tpu.plan.query import Query
        from geomesa_tpu.serve.scheduler import ServeRequest
        from geomesa_tpu.serve.service import QueryService, ServeConfig
        from geomesa_tpu.telemetry.trace import TRACER

        store = SimpleNamespace(get_feature_source=None, audit=None)
        svc = QueryService(store, ServeConfig(), autostart=False)
        TRACER.enable()
        try:
            req = ServeRequest(kind="count", query=Query("t", "INCLUDE"))
            req.trace = TRACER.start_trace("query", kind="count")
        finally:
            TRACER.disable()
        req.future.set_running_or_notify_cancel()
        req.future.set_result(1)
        w0 = time.perf_counter_ns()
        wid = 12345
        stall0 = w0 + 1_000_000
        svc._finish_window([req], [], req, time.monotonic(),
                           time.monotonic(), 0, wid, w0 + 9_000_000,
                           [("filter:mask:x", stall0, 0.003)], [])
        (st,) = [s for s in req.trace.snapshot_spans()
                 if s.name == "compile.stall"]
        assert (st.start_ns, st.end_ns) == (stall0, stall0 + 3_000_000)
        assert st.parent_id == wid
        assert st.attrs == {"label": "filter:mask:x"}
