"""Serve subsystem: admission control, request coalescing, degradation,
drain, and the latency-histogram observability contract.

The load-bearing test is test_coalescing_fewer_dispatches_same_results:
>= 8 concurrent compatible kNN queries must execute in FEWER device
dispatches than serial execution (dispatch counters + JitTracker over
the engine jit caches) while returning per-query results identical to
serial runs — the whole point of the serving layer.
"""

import json
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.plan.audit import ServeEvent
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.plan.hints import QueryHints
from geomesa_tpu.plan.planner import QueryTimeout
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve import (
    AdmissionQueue, QueryRejected, QueryService, ServeConfig, ServeRequest,
    TokenBucket, compat_key)
from geomesa_tpu.utils.metrics import Histogram, metrics

CQL = "BBOX(geom, -170, -80, 170, 80) AND score > -5"


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
        str(tmp_path_factory.mktemp("serve")), use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


# -- metrics: Histogram ----------------------------------------------------


class TestHistogram:
    def test_counts_sum_quantiles(self):
        h = Histogram()
        for v in [0.001] * 50 + [0.004] * 45 + [0.3] * 5:
            h.update(v)
        assert h.count == 100
        assert h.sum == pytest.approx(0.05 + 0.18 + 1.5)
        assert h.quantile(0.5) <= 0.004
        assert h.quantile(0.99) >= 0.1
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["p50_s"] <= snap["p95_s"] <= snap["p99_s"]

    def test_empty_and_bounds(self):
        h = Histogram()
        assert h.quantile(0.99) == 0.0
        with pytest.raises(ValueError):
            h.quantile(1.5)
        with pytest.raises(ValueError):
            Histogram(buckets=[2.0, 1.0])

    def test_overflow_clamps_to_last_bound(self):
        h = Histogram(buckets=[0.001, 0.01])
        h.update(5.0)  # lands in +Inf bucket
        assert h.quantile(0.99) == 0.01

    def test_merge(self):
        a, b = Histogram(), Histogram()
        for v in [0.001, 0.002]:
            a.update(v)
        for v in [0.004, 0.008, 0.016]:
            b.update(v)
        a.merge(b)
        assert a.count == 5
        assert a.sum == pytest.approx(0.031)
        with pytest.raises(ValueError):
            a.merge(Histogram(buckets=[1.0]))

    def test_thread_safety(self):
        h = Histogram()

        def worker():
            for _ in range(2000):
                h.update(0.001)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == 16000
        assert h.sum == pytest.approx(16.0, rel=1e-6)

    def test_registry_exports(self):
        metrics.histogram("serve.test.latency").update(0.012)
        prom = metrics.to_prometheus()
        assert "# TYPE serve_test_latency_seconds histogram" in prom
        assert 'serve_test_latency_seconds_bucket{le="+Inf"} 1' in prom
        for q in ("p50", "p95", "p99"):
            assert f"serve_test_latency_seconds_{q} " in prom
        doc = json.loads(metrics.to_json())
        assert doc["histograms"]["serve.test.latency"]["count"] == 1


# -- scheduler units -------------------------------------------------------


class TestScheduler:
    def test_token_bucket(self):
        tb = TokenBucket(rate=1000.0, burst=2.0)
        assert tb.try_acquire()
        assert tb.try_acquire()
        assert not tb.try_acquire()
        time.sleep(0.01)  # 1000/s refills ~10 tokens, capped at burst
        assert tb.try_acquire()

    def test_queue_bounded_and_priority_order(self):
        q = AdmissionQueue(max_depth=3)
        batch = ServeRequest(kind="count", query=Query("t"), priority=2)
        normal = ServeRequest(kind="count", query=Query("t"), priority=1)
        inter = ServeRequest(kind="count", query=Query("t"), priority=0)
        q.put(batch)
        q.put(normal)
        q.put(inter)
        with pytest.raises(QueryRejected) as ei:
            q.put(ServeRequest(kind="count", query=Query("t")))
        assert ei.value.reason == "queue_full"
        assert q.pop(0.01) is inter  # priority class beats FIFO age
        assert q.pop(0.01) is normal
        assert q.pop(0.01) is batch
        assert q.pop(0.01) is None

    def test_drain_compatible_keeps_others(self):
        q = AdmissionQueue(max_depth=10)
        a1 = ServeRequest(kind="count", query=Query("t", "score > 0"))
        b = ServeRequest(kind="count", query=Query("t", "score > 1"))
        a2 = ServeRequest(kind="count", query=Query("t", "score>0"))
        for r in (a1, b, a2):
            q.put(r)
        key = compat_key(a1)
        got = q.drain_compatible(key, compat_key, limit=10)
        # textual CQL variants canonicalize to the same key
        assert got == [a1, a2]
        assert q.pop(0.01) is b

    def test_cancelled_requests_skipped(self):
        q = AdmissionQueue(max_depth=4)
        a = ServeRequest(kind="count", query=Query("t"))
        b = ServeRequest(kind="count", query=Query("t"))
        q.put(a)
        q.put(b)
        assert a.cancel()
        assert q.pop(0.01) is b
        assert q.pop(0.01) is None

    def test_compat_keys(self):
        def knn(cql, k=5, hints=None):
            r = ServeRequest(
                kind="knn",
                query=Query("t", cql, hints=hints or QueryHints()))
            r.k = k
            return r

        assert compat_key(knn("score > 0")) == compat_key(knn("score>0"))
        assert compat_key(knn("score > 0")) != compat_key(knn("score > 1"))
        assert compat_key(knn("score > 0", k=5)) != \
            compat_key(knn("score > 0", k=7))
        # auths are part of the hints: different tenants' visibility
        # contexts must never alias into one dispatch
        assert compat_key(knn("score > 0", hints=QueryHints(auths=("A",)))) \
            != compat_key(knn("score > 0"))
        e1 = ServeRequest(kind="execute", query=Query("t", "score > 0"))
        c1 = ServeRequest(kind="count", query=Query("t", "score > 0"))
        assert compat_key(e1) != compat_key(c1)


# -- service integration ---------------------------------------------------


class TestService:
    def test_coalescing_fewer_dispatches_same_results(self, store):
        """Acceptance: >= 8 concurrent compatible kNN queries in fewer
        device dispatches than serial, identical per-query results."""
        import geomesa_tpu.engine.knn_scan as knn_scan_mod

        from geomesa_tpu.analysis.runtime import JitTracker

        src = store.get_feature_source("served")
        rng = np.random.default_rng(42)
        n_req = 10
        qpts = rng.uniform(-60, 60, (n_req, 2))

        tracker = JitTracker()
        tracker.install(knn_scan_mod)
        try:
            # serial baseline: one dispatch per request (warms jit +
            # device caches too, so the comparison isolates dispatches)
            serial = [
                src.knn(CQL, qpts[i:i + 1, 0], qpts[i:i + 1, 1], k=5)
                for i in range(n_req)
            ]
            serial_calls = sum(
                rec["calls"] for rec in tracker.report().values())

            svc = QueryService(
                store, ServeConfig(max_wait_ms=20.0), autostart=False)
            futs = [
                svc.knn("served", CQL, qpts[i:i + 1, 0], qpts[i:i + 1, 1],
                        k=5)
                for i in range(n_req)
            ]
            svc.start()
            results = [f.result(timeout=120) for f in futs]
            svc.close(drain=True)
            coalesced_calls = sum(
                rec["calls"] for rec in tracker.report().values()
            ) - serial_calls
        finally:
            tracker.unwrap()

        st = svc.stats()
        assert st["dispatches"] < n_req, st
        assert st["coalesced"] >= n_req - st["dispatches"]
        # the engine's jit caches saw fewer kernel invocations too
        assert coalesced_calls < serial_calls
        for (d, ix, _), (sd, six, _) in zip(results, serial):
            np.testing.assert_allclose(d, sd, rtol=1e-6)
            np.testing.assert_array_equal(ix, six)

    def test_count_dedup_single_dispatch(self, store):
        svc = QueryService(store, autostart=False)
        futs = [svc.count("served", CQL) for _ in range(6)]
        svc.start()
        counts = [f.result(timeout=120) for f in futs]
        svc.close(drain=True)
        assert len(set(counts)) == 1
        assert svc.stats()["dispatches"] == 1

    def test_overload_bounded_queue_typed_rejection(self, store):
        """Overload never buffers unboundedly: the queue admits exactly
        max_queue requests, rejects the rest with a typed reason, and
        still completes everything it admitted."""
        svc = QueryService(
            store, ServeConfig(max_queue=4), autostart=False)
        admitted = [svc.count("served", f"score > {i}") for i in range(4)]
        rejected = 0
        for i in range(6):
            with pytest.raises(QueryRejected) as ei:
                svc.count("served", f"score > {10 + i}")
            assert ei.value.reason == "queue_full"
            rejected += 1
        assert rejected == 6
        assert len(svc.queue) == 4  # bounded, not grown
        svc.start()
        for f in admitted:
            assert isinstance(f.result(timeout=120), int)
        svc.close(drain=True)
        assert svc.stats()["rejected"] == 6

    def test_deadline_expired_in_queue_raises_query_timeout(self, store):
        svc = QueryService(store, autostart=False)
        fut = svc.count("served", CQL, timeout_ms=1)
        time.sleep(0.05)
        svc.start()
        with pytest.raises(QueryTimeout) as ei:
            fut.result(timeout=60)
        assert ei.value.phase == "queued"
        svc.close(drain=True)

    def test_tenant_rate_limit(self, store):
        svc = QueryService(
            store, ServeConfig(tenant_rate=0.001, tenant_burst=2),
            autostart=False)
        svc.count("served", CQL, tenant="tA")
        svc.count("served", CQL, tenant="tA")
        with pytest.raises(QueryRejected) as ei:
            svc.count("served", CQL, tenant="tA")
        assert ei.value.reason == "rate_limited"
        # other tenants have their own bucket
        svc.count("served", CQL, tenant="tB")
        svc.start()
        svc.close(drain=True)

    def test_degradation_ladder(self, store):
        cfg = ServeConfig(max_queue=4, degrade=True,
                          degrade_watermark=0.5, shed_watermark=0.75)
        svc = QueryService(store, cfg, autostart=False)
        svc.count("served", "score > 1")
        svc.count("served", "score > 2")
        assert svc.degrade_level() == 1
        # level 1: consenting requests get downgraded hints. CQL
        # carries an attribute predicate (`score > -5`) the sketches
        # cannot see, so the ladder keeps the LEGACY loose-bbox rung
        # for it (the sketch rung takes only sketch-eligible filters —
        # docs/SERVING.md "Approximate answers"; tests/test_approx.py
        # covers that branch)
        fut_req = svc._request("count", Query("served", CQL),
                               allow_degraded=True)
        svc.submit(fut_req)
        assert fut_req.degraded and fut_req.query.hints.loose_bbox
        assert fut_req.sketch_rung == 0
        assert svc.degrade_level() == 2
        # level 2: batch class is shed with the typed reason
        with pytest.raises(QueryRejected) as ei:
            svc.count("served", "score > 3", priority="batch")
        assert ei.value.reason == "shed"
        # interactive work still admits (queue permitting)
        svc.count("served", "score > 4", priority="interactive")
        svc.start()
        svc.close(drain=True)
        assert svc.stats()["degraded"] == 1

    def test_graceful_drain_and_shutdown_rejection(self, store):
        svc = QueryService(store, autostart=False)
        futs = [svc.count("served", f"score > {i % 3}") for i in range(5)]
        svc.start()
        svc.close(drain=True)
        for f in futs:
            assert isinstance(f.result(timeout=1), int)  # already done
        with pytest.raises(QueryRejected) as ei:
            svc.count("served", CQL)
        assert ei.value.reason == "shutting_down"

    def test_non_drain_close_rejects_queued(self, store):
        svc = QueryService(store, autostart=False)
        fut = svc.count("served", CQL)
        svc.close(drain=False)
        with pytest.raises(QueryRejected) as ei:
            fut.result(timeout=1)
        assert ei.value.reason == "shutting_down"

    def test_bad_type_name_fails_future_not_dispatcher(self, store):
        """An unknown typeName raises in get_feature_source BEFORE the
        guarded execute_batch; it must fail that request's future and
        leave the dispatch thread alive for everyone else."""
        svc = QueryService(store)
        bad = svc.count("no_such_type", "INCLUDE")
        with pytest.raises(Exception):
            bad.result(timeout=60)
        # dispatcher survived: a valid request still completes
        assert isinstance(
            svc.count("served", "score > 5").result(timeout=120), int)
        svc.close(drain=True)

    def test_cancel_between_pop_and_execute_is_survivable(self, store):
        """A future cancelled while queued resolves as cancelled and the
        post-dispatch accounting skips it instead of raising
        CancelledError into the dispatch loop."""
        svc = QueryService(store, autostart=False)
        req = svc._request("count", Query("served", CQL))
        svc.submit(req)
        assert req.cancel()
        svc.start()
        ok = svc.count("served", "score > 8")
        assert isinstance(ok.result(timeout=120), int)
        svc.close(drain=True)
        assert req.future.cancelled()

    def test_serve_events_audited(self, store):
        base = len(store.audit.events)
        svc = QueryService(store, autostart=False)
        futs = [svc.count("served", "score > 6") for _ in range(3)]
        svc.start()
        for f in futs:
            f.result(timeout=120)
        svc.close(drain=True)
        events = [e for e in store.audit.events[base:]
                  if isinstance(e, ServeEvent)]
        assert len(events) == 3
        assert all(e.status == "ok" and e.batch_size == 3 for e in events)
        assert all(e.queue_ms >= 0 and e.timestamp > 0 for e in events)

    def test_latency_histograms_exported(self, store):
        svc = QueryService(store)
        svc.count("served", "score > 7").result(timeout=120)
        svc.close(drain=True)
        prom = metrics.to_prometheus()
        for family in ("serve_latency_seconds", "serve_queue_wait_seconds"):
            assert f"# TYPE {family} histogram" in prom
            assert f'{family}_bucket{{le="+Inf"}}' in prom
            for q in ("p50", "p95", "p99"):
                assert f"{family}_{q} " in prom


class TestCoalesceEnds:
    """With no max_wait_ms (the default) a gather waits only while the
    window's route cannot take it; the pipelined slot-bound case is in
    tests/test_pipeline.py."""

    def test_lone_knn_dispatches_without_sleeping(self, store,
                                                  monkeypatch):
        import geomesa_tpu.serve.service as service_mod

        real_sleep = time.sleep
        slept = []

        def spy(secs):
            if threading.current_thread().name == "gmtpu-serve-dispatch":
                slept.append(secs)
            real_sleep(secs)

        monkeypatch.setattr(service_mod.time, "sleep", spy)
        before = metrics.count("serve.coalesce.immediate")
        svc = QueryService(store)
        assert svc.config.max_wait_ms is None
        d, ix, _ = svc.knn("served", CQL, np.array([1.0]),
                           np.array([2.0]), k=5).result(timeout=120)
        svc.close(drain=True)
        assert d.shape == (1, 5) and ix.shape == (1, 5)
        assert slept == []
        assert svc.stats()["coalesce"] == {
            "immediate": 1, "slot_bound": 0, "timed": 0, "cap": 0}
        assert metrics.count("serve.coalesce.immediate") == before + 1

    def test_default_serial_count_window_coalesces_twins(self, store):
        before = metrics.count("serve.coalesce.immediate")
        svc = QueryService(store, autostart=False)
        futs = [svc.count("served", "score > 2") for _ in range(5)]
        svc.start()
        counts = [f.result(timeout=120) for f in futs]
        svc.close(drain=True)
        st = svc.stats()
        assert len(set(counts)) == 1
        assert st["dispatches"] == 1 and st["coalesced"] == 4
        assert st["coalesce"]["immediate"] == 1
        assert metrics.count("serve.coalesce.immediate") == before + 1

    def test_max_batch_one_ends_at_cap(self, store):
        svc = QueryService(store, ServeConfig(max_batch=1),
                           autostart=False)
        futs = [svc.count("served", "score > 3") for _ in range(2)]
        svc.start()
        for f in futs:
            f.result(timeout=120)
        svc.close(drain=True)
        assert svc.stats()["coalesce"]["cap"] == 2


# -- JSON-lines protocol + CLI ---------------------------------------------


class TestProtocol:
    def test_serve_lines_round_trip(self, store):
        from geomesa_tpu.serve.protocol import serve_lines

        lines = [
            json.dumps({"id": "c1", "op": "count", "typeName": "served",
                        "cql": CQL}),
            json.dumps({"id": "k1", "op": "knn", "typeName": "served",
                        "cql": CQL, "x": [10.0], "y": [20.0], "k": 3}),
            json.dumps({"id": "q1", "op": "query", "typeName": "served",
                        "cql": "score > 9", "maxFeatures": 5}),
            "not json at all",
            json.dumps({"id": "bad", "op": "nope", "typeName": "served"}),
        ]
        out = []
        n = serve_lines(store, lines, out.append)
        assert n == 5
        docs = {d.get("id"): d for d in map(json.loads, out)}
        assert docs["c1"]["ok"] and docs["c1"]["count"] > 0
        assert docs["k1"]["ok"]
        assert len(docs["k1"]["dists"][0]) == 3
        assert len(docs["k1"]["indices"]) == 1
        assert docs["q1"]["ok"] and docs["q1"]["kind"] == "features"
        assert len(docs["q1"]["features"]) <= 5
        assert not docs["bad"]["ok"] and docs["bad"]["error"] == "error"
        # the malformed line answered under its sequence number
        assert sum(1 for d in docs.values() if not d["ok"]) == 2

    def test_cli_self_check(self):
        from geomesa_tpu.cli.main import main

        assert main(["serve", "--self-check"]) == 0

    def test_cli_serve_requires_catalog(self):
        from geomesa_tpu.cli.main import main

        assert main(["serve"]) == 2


@pytest.mark.slow
class TestLoadSoak:
    def test_open_loop_sheds_over_capacity(self, store):
        """500 kNN requests a second for 2 s against an 8-deep queue:
        over-capacity offered load resolves as served + shed, never as
        an unbounded queue."""
        svc = QueryService(store, ServeConfig(max_queue=8))
        rng = np.random.default_rng(0)
        sent = ok = rejected = failed = 0
        futs = []
        try:
            t_start = time.monotonic()
            for i in range(1000):
                due = t_start + i / 500.0
                time.sleep(max(0.0, due - time.monotonic()))
                req = ServeRequest(kind="knn", query=Query("served", CQL))
                req.qx = rng.uniform(-60, 60, 1)
                req.qy = rng.uniform(-60, 60, 1)
                req.k = 4
                sent += 1
                try:
                    futs.append(svc.submit(req))
                except QueryRejected:
                    rejected += 1
            for f in futs:
                try:
                    f.result(timeout=30)
                    ok += 1
                except QueryRejected:
                    rejected += 1
                except Exception:  # noqa: BLE001 — counted, not raised
                    failed += 1
        finally:
            svc.close(drain=True)
        assert sent == ok + rejected + failed
        assert ok > 0
