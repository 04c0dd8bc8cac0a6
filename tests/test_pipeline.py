"""Pipelined dispatch (serve/pipeline.py + planner.knn_launch).

The load-bearing assertions, per the acceptance contract:

- **overlap**: window N+1's transfer/launch happen BEFORE window N's
  deferred sync completes (fake planner with gated syncs — no real
  clocks, no sleeps on the assert path), and the depth bound holds
  (window N+2 must NOT launch while N is unsynced at depth 2);
- **identity**: pipelined results are bit-identical to the serial path
  for the same coalesced window shape, and fused counts equal
  planner.count for banded and band-free filters;
- **gap report**: pipelined runs report windows_in_flight_max >= 2 with
  transfer time overlapping other windows, coverage <= 1.0, and the
  invariants survive a Perfetto export round-trip (the CPU-CI stand-in
  for the TPU sustained-throughput claim).
"""

import threading
import warnings
from types import SimpleNamespace

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve import QueryService, ServeConfig
from geomesa_tpu.telemetry.gap import gap_report

CQL = "BBOX(geom, -170, -80, 170, 80) AND score > -5"
CQL_PLAIN = "BBOX(geom, -120, -60, 120, 60)"


def make_batch(n=600, seed=3):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "served", "name:String,score:Double,dtg:Date,*geom:Point")
    return sft, FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    sft, batch = make_batch()
    ds = DataStore(
        str(tmp_path_factory.mktemp("pipeline")), use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


# -- fake-planner overlap harness ------------------------------------------


class FakeLaunch:
    """A KnnLaunch stand-in whose sync blocks on a per-window gate —
    the test decides exactly when each window's device work 'finishes',
    so the overlap assertions are deterministic and clock-free."""

    fused_ok = False
    mask_count = None
    deadline = None

    def __init__(self, seq, q, k, events, gate, trace_sync=False):
        self.seq = seq
        self.q = q
        self.k = k
        self.events = events
        self.gate = gate
        self.trace_sync = trace_sync

    def sync(self):
        self.events.append(("sync_start", self.seq))
        assert self.gate.wait(timeout=30), "test gate never opened"
        if self.trace_sync:
            from geomesa_tpu.telemetry.trace import TRACER

            with TRACER.span("device.sync"):
                pass
        self.events.append(("sync_done", self.seq))
        return (np.full((self.q, self.k), float(self.seq)),
                np.zeros((self.q, self.k), np.int32), None)


class FakePlanner:
    """Records launch order; per-window latency is injected through the
    FakeLaunch gates (per-stage latency without wall-clock sleeps)."""

    def __init__(self, events, gates, trace_sync=False):
        self.events = events
        self.gates = gates
        self.trace_sync = trace_sync
        self.seq = 0

    def knn_launch(self, query, qx, qy, k=10, impl="sparse",
                   timeout_ms=None, staged=None, want_mask_count=False,
                   donate=False):
        self.seq += 1
        assert staged is not None, "pipeline must stage before launch"
        self.events.append(("launch", self.seq))
        return FakeLaunch(self.seq, len(qx), k, self.events,
                          self.gates[self.seq - 1], self.trace_sync)


def fake_service(events, gates, **cfg):
    planner = FakePlanner(events, gates,
                          trace_sync=cfg.pop("trace_sync", False))
    source = SimpleNamespace(planner=planner)
    store = SimpleNamespace(
        get_feature_source=lambda name: source, audit=None)
    cfg.setdefault("max_wait_ms", 0.0)
    cfg.setdefault("max_batch", 1)
    svc = QueryService(store, ServeConfig(**cfg), autostart=False)
    return svc


class TestPipelineOverlap:
    def test_next_window_launches_before_previous_sync(self):
        """Window 2's transfer+launch proceed while window 1's device
        work is still in flight; window 3 (depth 2) must wait."""
        events: list = []
        gates = [threading.Event() for _ in range(3)]
        svc = fake_service(events, gates, pipeline_depth=2)
        futs = [svc.knn("t", f"BBOX(geom, 0, 0, 1, {i + 1})",
                        np.array([0.0]), np.array([0.0]), k=5)
                for i in range(3)]
        svc.start()

        def wait_for(ev, timeout=10.0):
            import time as _t

            deadline = _t.monotonic() + timeout
            while ev not in events:
                assert _t.monotonic() < deadline, (ev, events)
                _t.sleep(0.002)

        # window 2 launches while window 1 is mid-sync (gate closed)
        wait_for(("launch", 2))
        assert ("sync_done", 1) not in events
        # depth bound: window 3 must NOT have launched yet
        assert ("launch", 3) not in events
        gates[0].set()
        wait_for(("launch", 3))
        gates[1].set()
        gates[2].set()
        for f in futs:
            f.result(timeout=30)
        svc.close(drain=True)
        # transfer precedes launch (staged asserted inside the fake),
        # and launch(2) strictly precedes sync_done(1) in the log
        assert events.index(("launch", 2)) < events.index(
            ("sync_done", 1))
        p = svc.stats()["pipeline"]
        assert p["max_inflight"] >= 2
        assert p["windows"] == 3
        assert p["inflight"] == 0

    def test_results_split_per_window(self):
        events: list = []
        gates = [threading.Event() for _ in range(2)]
        for g in gates:
            g.set()  # no injected latency: plain pass-through
        svc = fake_service(events, gates)
        f1 = svc.knn("t", "BBOX(geom, 0, 0, 1, 1)",
                     np.array([0.0]), np.array([0.0]), k=5)
        f2 = svc.knn("t", "BBOX(geom, 0, 0, 1, 2)",
                     np.array([0.0]), np.array([0.0]), k=5)
        svc.start()
        d1, _, _ = f1.result(timeout=30)
        d2, _, _ = f2.result(timeout=30)
        svc.close(drain=True)
        # each window's rows came from ITS OWN launch (seq-valued)
        assert float(d1[0, 0]) == 1.0
        assert float(d2[0, 0]) == 2.0

    def test_traced_pipeline_gap_invariants(self):
        """The CPU-CI structural invariant: a traced pipelined run's
        gap report shows >=2 windows in flight with transfer overlap,
        coverage <= 1.0 — and survives the Perfetto round-trip."""
        from geomesa_tpu.telemetry import RECORDER, TRACER
        from geomesa_tpu.telemetry.export import from_perfetto, to_perfetto

        events: list = []
        gates = [threading.Event() for _ in range(3)]
        RECORDER.clear()
        TRACER.enable()
        try:
            svc = fake_service(events, gates, pipeline_depth=2,
                               trace_sync=True)
            futs = [svc.knn("t", f"BBOX(geom, 0, 0, 1, {i + 1})",
                            np.array([0.0]), np.array([0.0]), k=5)
                    for i in range(3)]
            svc.start()
            # hold window 1 open until window 2 is launched, so the two
            # window intervals (gather -> sync end) genuinely overlap
            import time as _t

            deadline = _t.monotonic() + 10
            while ("launch", 2) not in events:
                assert _t.monotonic() < deadline, events
                _t.sleep(0.002)
            for g in gates:
                g.set()
            for f in futs:
                f.result(timeout=30)
            svc.close(drain=True)
        finally:
            TRACER.disable()
        traces = RECORDER.traces()
        assert len(traces) >= 3
        for docs in (traces, from_perfetto(to_perfetto(traces))):
            rep = gap_report(docs)
            assert rep["coverage"] <= 1.0
            assert rep["dispatch_gap"]["windows"] >= 3
            p = rep["pipeline"]
            assert p["windows_in_flight_max"] >= 2, rep
            assert p["transfer_overlap_ms"] > 0.0, rep
            assert p["multi_window_ms"] > 0.0, rep


# -- identity against the serial path --------------------------------------


def _run(ds, qpts, config, counts=3):
    svc = QueryService(ds, config, autostart=False)
    futs = [svc.knn("served", CQL, qpts[i:i + 1, 0], qpts[i:i + 1, 1],
                    k=5) for i in range(len(qpts))]
    cfuts = [svc.count("served", CQL) for _ in range(counts)]
    svc.start()
    res = [f.result(timeout=120) for f in futs]
    cnts = [f.result(timeout=120) for f in cfuts]
    svc.close(drain=True)
    return res, cnts, svc.stats()


class TestPipelineIdentity:
    def test_bit_identical_to_serial_and_counts_fused(self, store):
        """Acceptance: the pipelined path produces bit-identical results
        to the serial path for the same coalesced window, and fused
        counts match the serial (dedup'd) planner count while saving a
        whole dispatch."""
        rng = np.random.default_rng(42)
        qpts = rng.uniform(-60, 60, (8, 2))
        res_p, cnt_p, st_p = _run(
            store, qpts, ServeConfig(max_wait_ms=50.0))
        res_s, cnt_s, st_s = _run(
            store, qpts, ServeConfig(max_wait_ms=50.0, pipeline=False))
        for i, ((d, ix, _), (sd, six, _)) in enumerate(zip(res_p, res_s)):
            np.testing.assert_array_equal(d, sd, err_msg=f"knn {i}")
            np.testing.assert_array_equal(ix, six, err_msg=f"knn {i}")
        assert cnt_p == cnt_s
        # the counts rode the kNN window: one dispatch total vs two
        assert st_p["pipeline"]["fused_counts"] == 3
        assert st_p["dispatches"] < st_s["dispatches"]
        assert st_p["pipelined_windows"] >= 1

    def test_fused_count_matches_planner_banded_and_plain(self, store):
        """The fused mask reduction equals planner.count exactly — for
        an f32-band-refined filter (score comparison / bbox band) and a
        plain one; the kNN mask carries the same f64-exact corrections
        the count path applies."""
        src = store.get_feature_source("served")
        rng = np.random.default_rng(7)
        qpts = rng.uniform(-60, 60, (4, 2))
        for cql in (CQL, CQL_PLAIN):
            launch = src.planner.knn_launch(
                Query("served", cql), qpts[:, 0], qpts[:, 1], k=5,
                want_mask_count=True)
            launch.sync()
            assert launch.fused_ok
            assert launch.mask_count == src.planner.count(
                Query("served", cql))

    def test_serial_launch_sync_composition(self, store):
        """planner.knn == planner.knn_launch(...).sync() bit-for-bit
        (the serial path IS the composition)."""
        src = store.get_feature_source("served")
        rng = np.random.default_rng(9)
        qx, qy = rng.uniform(-60, 60, 8), rng.uniform(-60, 60, 8)
        d1, i1, _ = src.planner.knn(Query("served", CQL), qx, qy, k=5)
        d2, i2, _ = src.planner.knn_launch(
            Query("served", CQL), qx, qy, k=5).sync()
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(i1, i2)


# -- gap report on synthetic pipelined spans --------------------------------


def _span(name, sid, parent, t0, t1):
    return {"name": name, "id": sid, "parent": parent,
            "t0_ns": t0, "t1_ns": t1, "thread": 1}


class TestGapPipelineMath:
    def test_overlapping_windows_dedup_and_clamp(self):
        """Two overlapping windows: exec is the interval UNION (not the
        sum), per-stage intervals clamp to their window, coverage <=
        1.0, and transfer overlapping the other window is reported."""
        ms = 1_000_000
        root = _span("query", 1, None, 0, 100 * ms)
        spans = [
            # window A [10, 60]: kernel [12, 20], sync [40, 60]
            _span("dispatch", 10, 1, 10 * ms, 60 * ms),
            _span("kernel.dispatch", 11, 10, 12 * ms, 20 * ms),
            _span("device.sync", 12, 10, 40 * ms, 60 * ms),
            # window B [40, 90]: transfer [42, 50] overlaps window A
            _span("dispatch", 20, 1, 40 * ms, 90 * ms),
            _span("device.transfer", 21, 20, 42 * ms, 50 * ms),
            _span("device.sync", 22, 20, 70 * ms, 90 * ms),
            # child extending past its window: clamps, never inflates
            _span("prepare", 23, 20, 35 * ms, 45 * ms),
        ]
        rep = gap_report([{"trace_id": "p-1", "root": root,
                           "spans": spans}])
        g = rep["dispatch_gap"]
        assert g["windows"] == 2
        # union of [10,60] and [40,90] = 80ms, not 50+50=100
        assert g["exec_ms"] == pytest.approx(80.0)
        assert rep["coverage"] <= 1.0
        p = rep["pipeline"]
        assert p["windows_in_flight_max"] == 2
        assert p["multi_window_ms"] == pytest.approx(20.0)
        # window B's transfer [42, 50] lies inside window A's [10, 60]
        assert p["transfer_overlap_ms"] == pytest.approx(8.0)
        # device time: union across stages AND windows — window B's
        # transfer [42, 50] hides entirely behind window A's sync
        # [40, 60], so that wall time counts ONCE (the pre-fix sum
        # reported 56 and could exceed exec on deeper pipelines)
        assert g["device_ms"] == pytest.approx(8 + 20 + 20)

    def test_serial_run_unchanged(self):
        """Non-overlapping windows: union == sum, no pipeline section
        noise — the pre-pipelining report shape is preserved."""
        ms = 1_000_000
        root = _span("query", 1, None, 0, 100 * ms)
        spans = [
            _span("dispatch", 10, 1, 10 * ms, 40 * ms),
            _span("kernel.dispatch", 11, 10, 12 * ms, 35 * ms),
            _span("dispatch", 20, 1, 50 * ms, 80 * ms),
            _span("kernel.dispatch", 21, 20, 52 * ms, 75 * ms),
        ]
        rep = gap_report([{"trace_id": "p-1", "root": root,
                           "spans": spans}])
        g = rep["dispatch_gap"]
        assert g["exec_ms"] == pytest.approx(60.0)
        assert g["device_ms"] == pytest.approx(46.0)
        assert rep["pipeline"]["windows_in_flight_max"] == 1
        assert rep["pipeline"]["transfer_overlap_ms"] == 0.0


# -- staging + donation tier ------------------------------------------------


class TestStagerAndDonation:
    def test_stager_rotation_and_value_identity(self):
        import jax.numpy as jnp

        from geomesa_tpu.engine.device import QueryStager

        stager = QueryStager(depth=2)
        rng = np.random.default_rng(5)
        qx = rng.uniform(-60, 60, 8)
        qy = rng.uniform(-60, 60, 8)
        pairs = [stager.stage(("t", 5, "sparse", 8), qx, qy)
                 for _ in range(3)]
        # value identity with the serial conversion
        serial = jnp.asarray(np.asarray(qx), jnp.float32)
        for dx, _dy in pairs:
            np.testing.assert_array_equal(np.asarray(dx),
                                          np.asarray(serial))
        st = stager.stats()
        assert st == {"keys": 1, "staged": 3}
        # slots bounded at depth per key (the double buffer)
        slot = stager._slots[("t", 5, "sparse", 8)]
        assert len(slot) - 1 == 2
        # ... and the key table itself is bounded (LRU): a long-lived
        # multi-tenant service must not pin stale pairs per key forever
        for i in range(QueryStager.MAX_KEYS + 5):
            stager.stage(("t2", i, "sparse", 8), qx[:1], qy[:1])
        assert stager.stats()["keys"] <= QueryStager.MAX_KEYS
        assert ("t", 5, "sparse", 8) not in stager._slots  # evicted
        with pytest.raises(ValueError):
            QueryStager(depth=1)

    def test_registry_serve_variant(self):
        """The donation tier: a @serve-keyed AOT variant compiles and
        runs (donation itself is a no-op on CPU — JAX warns and
        ignores — which is exactly why the pipeline gates on backend),
        is idempotent, and never aliases the base registration."""
        import jax.numpy as jnp

        from geomesa_tpu.compilecache.registry import ExecutableRegistry

        reg = ExecutableRegistry()

        def addmul(a, b, scale):
            return a * scale + b

        reg.register("t.addmul", addmul, static_argnames=("scale",))
        vname = reg.serve_variant("t.addmul", donate_argnums=(0,))
        assert vname == "t.addmul@serve"
        assert reg.serve_variant("t.addmul", donate_argnums=(0,)) == vname
        assert vname in reg.names() and "t.addmul" in reg.names()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            h = reg.compile(vname, jnp.ones(8), jnp.ones(8), scale=2.0)
            out = h.call(jnp.ones(8), jnp.ones(8))
        np.testing.assert_allclose(np.asarray(out), 3.0)
        with pytest.raises(KeyError):
            reg.serve_variant("t.missing", donate_argnums=(0,))


# -- the named stretches of a traced window ---------------------------------


def _traced_fake_run(depth=2, windows=3):
    """Three gated windows at depth 2 with tracing on: window 3 must
    wait for a slot while windows 1 and 2 are in flight. Returns the
    live traces of the three leads, in submit order."""
    import time as _t

    from geomesa_tpu.serve.scheduler import ServeRequest
    from geomesa_tpu.telemetry import TRACER

    events: list = []
    gates = [threading.Event() for _ in range(windows)]
    TRACER.enable()
    try:
        svc = fake_service(events, gates, pipeline_depth=depth,
                           trace_sync=True)
        reqs = []
        for i in range(windows):
            r = ServeRequest(kind="knn", query=Query(
                "t", f"BBOX(geom, 0, 0, 1, {i + 1})"))
            r.qx, r.qy, r.k = np.array([0.0]), np.array([0.0]), 5
            reqs.append((r, svc.submit(r)))
        svc.start()
        deadline = _t.monotonic() + 10
        while ("launch", depth) not in events:
            assert _t.monotonic() < deadline, events
            _t.sleep(0.002)
        _t.sleep(0.02)  # window depth+1 now stands at the slot
        for g in gates:
            g.set()
        for _, f in reqs:
            f.result(timeout=30)
        svc.close(drain=True)
    finally:
        TRACER.disable()
    return [r.trace for r, _ in reqs]


class TestWindowStretches:
    def test_slot_wait_positive_behind_depth_windows(self):
        traces = _traced_fake_run()
        waits = []
        for tr in traces:
            (w,) = [s for s in tr.snapshot_spans()
                    if s.name == "pipeline.slot_wait"]
            waits.append(w.duration_ns)
        # windows 1 and 2 found a free slot: zero-length waits; window
        # 3 stood behind them until window 1's slot freed
        assert waits[0] == 0 and waits[1] == 0
        assert waits[2] >= 10_000_000, waits

    def test_pending_open_and_finish_hang_off_the_window(self):
        for tr in _traced_fake_run():
            spans = tr.snapshot_spans()
            (win,) = [s for s in spans if s.name == "dispatch"]
            by = {}
            for s in spans:
                by.setdefault(s.name, []).append(s)
            for name in ("window.open", "pipeline.slot_wait",
                         "pipeline.pending", "prepare", "merge"):
                (s,) = by[name]
                assert s.parent_id == win.span_id, name
                assert win.start_ns <= s.start_ns <= s.end_ns \
                    <= win.end_ns, name
            (fin,) = by["window.finish"]
            # the window span closes first; its finish bookkeeping is a
            # child of the root carrying the window's id
            assert fin.parent_id == tr.root.span_id
            assert fin.attrs == {"window": win.span_id}
            assert fin.start_ns >= win.end_ns


def _banded_store(tmp_path_factory):
    """A 2^21-row store with rows placed ON the edges of BOX, so the
    count's f32 band holds rows to refine in f64. Its windows are long
    enough that the ~0.25 ms of glue between spans per window stays a
    small share on a loaded CPU (a count window's device work, a few
    compares and a sum per row, takes ~4 ms at 2^18 rows)."""
    n = 1 << 21
    sft, batch = make_batch(n=n, seed=11)
    geom = np.asarray(batch.columns["geom"].x).copy(), \
        np.asarray(batch.columns["geom"].y).copy()
    rng = np.random.default_rng(12)
    edge = rng.choice(n, 64, replace=False)
    xs, ys = geom
    xs[edge[:32]] = -120.0 + rng.choice([-1e-9, 0.0, 1e-9], 32)
    ys[edge[32:]] = 60.0 + rng.choice([-1e-9, 0.0, 1e-9], 32)
    batch = FeatureBatch.from_pydict(sft, {
        "name": list(batch.columns["name"].decode()),
        "score": np.asarray(batch.columns["score"]),
        "dtg": np.asarray(batch.columns["dtg"]),
        "geom": np.stack([xs, ys], 1)})
    ds = DataStore(str(tmp_path_factory.mktemp("banded")),
                   use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


BOX = "BBOX(geom, -120, -60, 120, 60) AND score > -5"


@pytest.fixture(scope="module")
def banded(tmp_path_factory):
    return _banded_store(tmp_path_factory)


ROUTES = {
    "count": (dict(max_batch=1, result_cache=0), "count"),
    "pipelined_knn": (dict(max_batch=8, max_wait_ms=1.0, ring=False),
                      "knn"),
    "ring_knn": (dict(max_batch=8, max_wait_ms=1.0), "knn"),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_traced_window_is_named_end_to_end(banded, route, monkeypatch):
    """A traced count with band rows, a pipelined and a ring kNN window:
    every blocking read lies under `device.sync`, the new stretches
    hang off the right parents, and under 5% of window time is left
    unnamed (benchmark/inside.py's arithmetic)."""
    import jax

    from benchmark import inside
    from geomesa_tpu.serve.scheduler import ServeRequest
    from geomesa_tpu.telemetry import TRACER

    cfg, kind = ROUTES[route]
    real_get = jax.device_get
    bare_reads = []

    def watched_get(x):
        ctx = getattr(TRACER._tls, "ctx", None)
        if ctx is not None and not (ctx[2] and ctx[2][-1][0]
                                    == "device.sync"):
            bare_reads.append(ctx[2][-1][0] if ctx[2] else "<none>")
        return real_get(x)

    def request(i):
        r = ServeRequest(kind=kind, query=Query("served", BOX))
        if kind == "knn":
            r.qx = np.array([-60.0 + 4 * i])
            r.qy = np.array([-30.0 + 2 * i])
            r.k = 5
        return r

    svc = QueryService(banded, ServeConfig(trace=True, **cfg))
    try:
        svc.submit(request(0)).result(timeout=300)  # compiles, arms
        monkeypatch.setattr(jax, "device_get", watched_get)
        reqs = []
        for i in range(12):
            r = request(i + 1)
            svc.submit(r).result(timeout=120)
            reqs.append(r)
    finally:
        svc.close(drain=True)
        TRACER.disable()
    assert not bare_reads, bare_reads
    docs = [r.trace.to_json() for r in reqs]
    names = {s["name"] for d in docs for s in d["spans"]}
    assert "device.sync" in names
    if kind == "count":
        assert "band.refine" in names
        refine = [s for d in docs for s in d["spans"]
                  if s["name"] == "band.refine"]
        assert all(s["attrs"]["rows"] > 0 for s in refine)
    else:
        assert {"knn.canonical", "pipeline.slot_wait", "pipeline.pending",
                "window.finish", "window.open", "merge"} <= names
        for d in docs:
            by_id = {s["id"]: s for s in d["spans"]}
            for s in d["spans"]:
                if s["name"] == "knn.canonical":
                    # a sibling of the read, not inside it
                    assert by_id[s["parent"]]["name"] == "dispatch"
        assert ("ring.slot" in names) == (route == "ring_knn")
    share = inside.window_unspanned_pct(docs)
    assert share is not None and share < 5.0, share


class TestDispatcherLoad:
    def test_loop_counters_monotone_and_idle_is_waiting(self):
        import time as _t

        svc = fake_service([], [], pipeline_depth=2)
        st0 = svc.stats()
        assert st0["loop_ns"] == 0 and st0["loop_wait_ns"] == 0
        svc.start()
        _t.sleep(0.15)
        st1 = svc.stats()
        _t.sleep(0.3)
        st2 = svc.stats()
        svc.close(drain=True)
        st3 = svc.stats()
        for a, b in ((st0, st1), (st1, st2), (st2, st3)):
            assert b["loop_ns"] >= a["loop_ns"]
            assert b["loop_wait_ns"] >= a["loop_wait_ns"]
        assert st3["loop_wait_ns"] <= st3["loop_ns"]
        d_loop = st2["loop_ns"] - st1["loop_ns"]
        d_wait = st2["loop_wait_ns"] - st1["loop_wait_ns"]
        # an idle service spends its loop parked in queue.pop
        assert d_loop > 0.2e9 and d_wait / d_loop > 0.95, (d_loop, d_wait)

    def test_loop_counters_stay_cumulative_across_restart(self):
        import time as _t

        svc = fake_service([], [], pipeline_depth=2)
        svc.start()
        _t.sleep(0.1)
        svc.close(drain=True)
        first = svc.stats()
        svc.start()
        _t.sleep(0.1)
        svc.close(drain=True)
        second = svc.stats()
        assert second["loop_ns"] >= first["loop_ns"] + 0.05e9
        assert second["loop_wait_ns"] > first["loop_wait_ns"]

    def test_busy_dispatcher_waits_little(self, monkeypatch):
        """A dispatcher with work queued spends its loop dispatching:
        the wait share drops well below the idle one."""
        import time as _t

        import geomesa_tpu.serve.pipeline as pl
        from benchmark.inside import dispatch_busy_pct

        real = pl.stack_queries

        def slow_stack(reqs):
            _t.sleep(0.05)  # host work on the dispatch thread
            return real(reqs)

        monkeypatch.setattr(pl, "stack_queries", slow_stack)
        gates = [threading.Event() for _ in range(6)]
        for g in gates:
            g.set()
        svc = fake_service([], gates, pipeline_depth=2)
        futs = [svc.knn("t", f"BBOX(geom, 0, 0, 1, {i + 1})",
                        np.array([0.0]), np.array([0.0]), k=5)
                for i in range(6)]
        st0 = svc.stats()
        svc.start()
        for f in futs:
            f.result(timeout=30)
        st1 = svc.stats()
        svc.close(drain=True)
        busy = dispatch_busy_pct(st0, st1)
        assert busy is not None and busy > 50.0, (st0, st1)


class TestCoalesceEnds:
    """The default gather (max_wait_ms None) waits only while every
    pipeline slot is busy; an explicit max_wait_ms keeps its fixed
    window."""

    def test_arrivals_ride_the_window_waiting_for_a_slot(self, store):
        import time as _t

        from geomesa_tpu.utils.metrics import metrics

        rng = np.random.default_rng(7)
        qpts = rng.uniform(-60, 60, (5, 2))
        before = metrics.count("serve.coalesce.slot_bound")
        svc = QueryService(store, ServeConfig(pipeline_depth=2))
        pipe = svc.pipeline
        release = threading.Event()
        real_sync = pipe._sync

        def gated_sync(win):
            assert release.wait(timeout=60), "test gate never opened"
            real_sync(win)

        waiting = threading.Event()
        real_wait = pipe.wait_slot

        def spy_wait(*a, **kw):
            waiting.set()
            return real_wait(*a, **kw)

        pipe._sync = gated_sync
        pipe.wait_slot = spy_wait

        def submit(i):
            return svc.knn("served", CQL, qpts[i:i + 1, 0],
                           qpts[i:i + 1, 1], k=5)

        def until(cond):
            deadline = _t.monotonic() + 60
            while not cond():
                assert _t.monotonic() < deadline
                _t.sleep(0.001)

        try:
            futs = []
            for i in range(2):
                # one window per request: each finds a free slot
                futs.append(submit(i))
                until(lambda: pipe.stats()["inflight"] == i + 1)
            assert not pipe.slot_free()
            futs += [submit(i) for i in range(2, 5)]
            assert waiting.wait(timeout=60)
        finally:
            release.set()
        res = [f.result(timeout=120) for f in futs]
        svc.close(drain=True)
        st = svc.stats()
        assert st["coalesce"] == {
            "immediate": 2, "slot_bound": 1, "timed": 0, "cap": 0}, st
        assert st["dispatches"] == 3 and st["coalesced"] == 2, st
        assert metrics.count("serve.coalesce.slot_bound") == before + 1
        serial, _, _ = _run(store, qpts, ServeConfig(
            max_wait_ms=0.0, max_batch=1, pipeline=False), counts=0)
        for i, ((d, ix, _), (sd, six, _)) in enumerate(zip(res, serial)):
            np.testing.assert_array_equal(d, sd, err_msg=f"knn {i}")
            np.testing.assert_array_equal(ix, six, err_msg=f"knn {i}")

    def test_wait_slot_gives_up_without_a_completer(self):
        svc = fake_service([], [], pipeline_depth=2)
        pipe = svc.pipeline
        assert pipe.slot_free() and pipe.wait_slot(timeout_s=0.01)
        pipe._inflight = pipe.depth  # slots held, no completer running
        assert not pipe.slot_free()
        assert pipe.wait_slot(timeout_s=0.01) is False
        pipe._inflight = 0

    def test_explicit_wait_is_a_fixed_window(self):
        import time as _t

        gates = [threading.Event()]
        gates[0].set()
        svc = fake_service([], gates, max_wait_ms=500.0, max_batch=8)
        svc.start()
        t0 = _t.monotonic()
        first = svc.knn("t", "BBOX(geom, 0, 0, 1, 1)", np.array([0.0]),
                        np.array([0.0]), k=5)
        # the dispatcher has popped `first` and waits out its window
        deadline = t0 + 10
        while len(svc.queue):
            assert _t.monotonic() < deadline
            _t.sleep(0.001)
        _t.sleep(0.02)
        rider = svc.knn("t", "BBOX(geom, 0, 0, 1, 1)", np.array([1.0]),
                        np.array([1.0]), k=5)
        first.result(timeout=30)
        rider.result(timeout=30)
        elapsed = _t.monotonic() - t0
        svc.close(drain=True)
        st = svc.stats()
        assert st["dispatches"] == 1 and st["coalesced"] == 1, st
        assert st["coalesce"]["timed"] == 1, st
        assert elapsed >= 0.5
