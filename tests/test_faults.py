"""Fault-injection harness + recovery fabric (docs/ROBUSTNESS.md).

Covers the PR-5 acceptance surface: the breaker state machine (fake
clock, no sleeps), backoff-with-jitter bounds and deadline awareness
(seeded, fake clock), deterministic plan replay, the device-OOM ->
host-eval fallback returning device-identical results on a small
workload, poison-query quarantine, ServeEvent recovery attribution,
the GT14 lint rule fixtures, the bounded kNN widen loop, and a seeded
chaos regression (the `gmtpu chaos --check` invariants in-process).
"""

import os
import textwrap
from random import Random

import numpy as np
import pytest

from geomesa_tpu import faults
from geomesa_tpu.faults.breaker import BreakerOpen, CircuitBreaker
from geomesa_tpu.faults.errors import (
    DeviceOOM, InjectedCrash, InjectedIOError, PermanentError, classify)
from geomesa_tpu.faults.plan import FaultPlan, FaultRule
from geomesa_tpu.faults.quarantine import QuarantineRegistry
from geomesa_tpu.faults.retry import RetryPolicy, retry_call

CQL = "BBOX(geom, -170, -80, 170, 80)"


def make_store(tmp_path, n=400, seed=9, device_cache=False):
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore

    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "faulty", "name:String,score:Double,dtg:Date,*geom:Point")
    store = DataStore(str(tmp_path), use_device_cache=device_cache)
    store.create_schema(sft).write(FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_590_080_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    }))
    return store


@pytest.fixture(autouse=True)
def _pristine_fabric():
    """Every test starts and ends with no harness installed and closed
    breakers (the fabric is process-global by design)."""
    faults.uninstall()
    faults.BREAKERS.reset()
    yield
    faults.uninstall()
    faults.BREAKERS.reset()


# -- error classification ---------------------------------------------------------


class TestClassification:
    def test_classification(self):
        from geomesa_tpu.plan.planner import QueryTimeout

        assert classify(InjectedIOError("x")) == "transient"
        assert classify(ConnectionResetError("x")) == "transient"
        assert classify(DeviceOOM("x")) == "oom"
        assert classify(InjectedCrash("x")) == "permanent"
        assert classify(PermanentError("x")) == "permanent"
        assert classify(ValueError("x")) == "permanent"
        # definitive filesystem answers must not retry / trip breakers
        # (review finding: a compaction-raced FileNotFoundError burned
        # the whole backoff budget and counted 4 storage-breaker
        # failures on a healthy disk)
        assert classify(FileNotFoundError("gone")) == "permanent"
        assert classify(PermissionError("denied")) == "permanent"
        assert classify(IsADirectoryError("dir")) == "permanent"
        # a blown deadline must NEVER be retried
        assert classify(QueryTimeout("scan", 10.0, 5.0)) == "permanent"

    def test_typed_recognition(self):
        from geomesa_tpu.serve.scheduler import QueryRejected

        assert faults.is_typed(InjectedIOError("x"))
        assert faults.is_typed(QueryRejected("shed"))
        assert faults.is_typed(BreakerOpen("storage", 1.0))
        assert not faults.is_typed(RuntimeError("surprise"))


# -- circuit breaker (fake clock, no sleeps) --------------------------------


class TestBreaker:
    def test_state_machine(self):
        t = [0.0]
        b = CircuitBreaker("dep", failure_threshold=2,
                           reset_timeout_s=10.0, clock=lambda: t[0])
        assert b.state == "closed"
        b.allow(); b.record_failure()
        assert b.state == "closed"  # one failure below threshold
        b.allow(); b.record_failure()
        assert b.state == "open"
        with pytest.raises(BreakerOpen) as ei:
            b.allow()
        assert ei.value.reason == "breaker_open"
        assert 0 < ei.value.retry_after_s <= 10.0
        t[0] = 10.5  # reset timeout elapses -> half-open probe
        b.allow()
        assert b.state == "half_open"
        with pytest.raises(BreakerOpen):
            b.allow()  # probe budget (1) spent
        b.record_success()
        assert b.state == "closed"

    def test_half_open_failure_reopens(self):
        t = [0.0]
        b = CircuitBreaker("dep", failure_threshold=1,
                           reset_timeout_s=5.0, clock=lambda: t[0])
        b.record_failure()
        assert b.state == "open"
        t[0] = 6.0
        b.allow()
        assert b.state == "half_open"
        b.record_failure()
        assert b.state == "open"  # failed probe restarts the clock
        with pytest.raises(BreakerOpen):
            b.allow()

    def test_vanished_probe_does_not_wedge_half_open(self):
        """Review finding: a half-open probe whose failure is
        NON-transient reports neither success nor failure to the
        breaker (retry.py only records dependency-health signals). The
        stale probe slot must free after reset_timeout_s — pre-fix the
        breaker stayed half-open raising BreakerOpen forever."""
        t = [0.0]
        b = CircuitBreaker("dep", failure_threshold=1,
                           reset_timeout_s=5.0, clock=lambda: t[0])
        b.record_failure()
        t[0] = 6.0
        b.allow()  # probe granted... and it vanishes (OOM path)
        with pytest.raises(BreakerOpen):
            b.allow()  # budget spent, probe still fresh
        t[0] = 12.0  # the vanished probe's slot goes stale
        b.allow()  # new probe round instead of a permanent wedge
        b.record_success()
        assert b.state == "closed"

    def test_registry_config_scoped_override_restores(self):
        """Review finding: the chaos runner must hand back the tuning
        the process had, not reset to constructor defaults."""
        from geomesa_tpu.faults.breaker import BreakerRegistry

        reg = BreakerRegistry()
        reg.configure("storage", failure_threshold=10,
                      reset_timeout_s=5.0)
        prior = reg.current_config("storage")
        assert prior == {"failure_threshold": 10, "reset_timeout_s": 5.0}
        reg.configure("storage", failure_threshold=3,
                      reset_timeout_s=0.0)  # chaos-style override
        reg.restore_config("storage", prior)
        b = reg.get("storage")
        assert b.failure_threshold == 10
        assert b.reset_timeout_s == 5.0
        # never-configured dependency restores to defaults (None)
        assert reg.current_config("kafka") is None
        reg.configure("kafka", failure_threshold=1)
        reg.restore_config("kafka", None)
        assert reg.get("kafka").failure_threshold == 5

    def test_transitions_metered(self):
        from geomesa_tpu.utils.metrics import metrics

        t = [0.0]
        b = CircuitBreaker("metered_dep", failure_threshold=1,
                           reset_timeout_s=1.0, clock=lambda: t[0])
        b.record_failure()
        t[0] = 2.0
        b.allow()
        b.record_success()
        with metrics._lock:
            counters = dict(metrics.counters)
        assert counters.get("fault.breaker.metered_dep.open", 0) >= 1
        assert counters.get("fault.breaker.metered_dep.half_open", 0) >= 1
        assert counters.get("fault.breaker.metered_dep.close", 0) >= 1


# -- retry with backoff + jitter (seeded, no real sleeps) -------------------


class TestRetry:
    def test_backoff_bounds(self):
        policy = RetryPolicy(max_attempts=10, base_ms=10.0, cap_ms=500.0)
        rng = Random(42)
        for attempt in range(12):
            for _ in range(50):
                d = policy.backoff_ms(attempt, rng)
                assert 0.0 <= d <= min(500.0, 10.0 * 2 ** attempt)

    def test_transient_retries_then_succeeds(self):
        calls, sleeps = [], []
        policy = RetryPolicy(max_attempts=4, base_ms=10.0, cap_ms=100.0)

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise InjectedIOError("flap")
            return "ok"

        out = retry_call(flaky, policy=policy, label="t",
                         sleep=sleeps.append, rng=Random(1))
        assert out == "ok"
        assert len(calls) == 3
        assert len(sleeps) == 2
        for i, s in enumerate(sleeps):
            assert 0.0 <= s <= min(0.1, 0.01 * 2 ** i)

    def test_permanent_never_retries(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("permanent")

        with pytest.raises(ValueError):
            retry_call(bad, policy=RetryPolicy(max_attempts=5),
                       label="t", sleep=lambda s: None)
        assert len(calls) == 1

    def test_oom_never_retries_nor_trips_breaker(self):
        calls = []
        b = CircuitBreaker("oomdep", failure_threshold=1,
                           reset_timeout_s=60.0)

        def oom():
            calls.append(1)
            raise DeviceOOM("hbm")

        with pytest.raises(DeviceOOM):
            retry_call(oom, policy=RetryPolicy(max_attempts=5),
                       label="t", breaker=b, sleep=lambda s: None)
        assert len(calls) == 1
        # OOM is a program-size signal with its own ladder (halve ->
        # host-eval); it must not open the dependency breaker and
        # fail-fast the requests the ladder exists to save
        assert b.state == "closed"

    def test_exhaustion_raises_last_error(self):
        def always():
            raise InjectedIOError("down")

        with pytest.raises(InjectedIOError):
            retry_call(always, policy=RetryPolicy(max_attempts=3,
                                                  base_ms=0.1),
                       label="t", sleep=lambda s: None)

    def test_deadline_stops_retries(self):
        """The fabric never sleeps past the request deadline: with the
        next backoff crossing the budget, the last error surfaces NOW."""
        calls, sleeps = [], []

        class MaxRng:
            @staticmethod
            def uniform(a, b):
                return b

        def flaky():
            calls.append(1)
            raise InjectedIOError("flap")

        clock = lambda: 100.0  # frozen fake clock
        with faults.deadline_scope(100.005):  # 5ms of budget left
            with pytest.raises(InjectedIOError):
                retry_call(flaky,
                           policy=RetryPolicy(max_attempts=10,
                                              base_ms=10.0),
                           label="t", clock=clock, sleep=sleeps.append,
                           rng=MaxRng())
        assert len(calls) == 1  # 10ms backoff > 5ms budget: no retry
        assert sleeps == []

    def test_nested_deadline_keeps_tighter(self):
        with faults.deadline_scope(50.0):
            with faults.deadline_scope(80.0):
                assert faults.current_deadline() == 50.0
            with faults.deadline_scope(30.0):
                assert faults.current_deadline() == 30.0
        assert faults.current_deadline() is None

    def test_breaker_fail_fast(self):
        b = CircuitBreaker("fastdep", failure_threshold=2,
                           reset_timeout_s=60.0)
        calls = []

        def always():
            calls.append(1)
            raise InjectedIOError("down")

        with pytest.raises(InjectedIOError):
            retry_call(always, policy=RetryPolicy(max_attempts=2,
                                                  base_ms=0.1),
                       label="t", breaker=b, sleep=lambda s: None)
        assert b.state == "open"
        with pytest.raises(BreakerOpen):
            retry_call(always, policy=RetryPolicy(max_attempts=2),
                       label="t", breaker=b, sleep=lambda s: None)
        assert len(calls) == 2  # open breaker: fn never called again


# -- plan + harness determinism --------------------------------------------


class TestHarness:
    def test_plan_json_round_trip(self, tmp_path):
        plan = FaultPlan(
            rules=[FaultRule(site="fs.*", error="io", every=3,
                             max_fires=2, latency_ms=1.0),
                   FaultRule(site="kafka.poll", error="unavailable",
                             nth_call=2)],
            seed=11, expect_breakers=["storage"])
        p = str(tmp_path / "plan.json")
        plan.save(p)
        loaded = FaultPlan.load(p)
        assert loaded == plan

    def test_plan_validation(self):
        with pytest.raises(ValueError):
            FaultRule(site="x", error="nope", every=1)
        with pytest.raises(ValueError):
            FaultRule(site="x", error="io")  # no schedule
        with pytest.raises(ValueError):
            FaultRule(site="x", error="io", probability=1.5)
        with pytest.raises(ValueError):
            FaultRule(site="x", error="io", nth_call=0)

    def test_no_harness_is_noop(self):
        s = faults.site("test.noop.site")
        for _ in range(100):
            s.fire()  # must not raise, must not record anything
        assert faults.current() is None

    def test_schedules_fire_exactly(self):
        plan = FaultPlan(rules=[
            FaultRule(site="test.sched", error="io", every=3,
                      max_fires=2)])
        s = faults.site("test.sched")
        fired = []
        with faults.active(plan) as h:
            for i in range(1, 13):
                try:
                    s.fire()
                except InjectedIOError:
                    fired.append(i)
        assert fired == [3, 6]  # every 3rd call, capped at 2 fires
        assert h.fire_log() == [("test.sched", 3, "io"),
                                ("test.sched", 6, "io")]

    def test_probability_replays_exactly(self):
        plan = FaultPlan(rules=[
            FaultRule(site="test.prob", error="io", probability=0.3)],
            seed=123)
        s = faults.site("test.prob")

        def run():
            fired = []
            with faults.active(plan):
                for i in range(200):
                    try:
                        s.fire()
                    except InjectedIOError:
                        fired.append(i)
            return fired

        a, b = run(), run()
        assert a == b  # seeded per-site stream: exact replay
        assert 20 < len(a) < 100  # ~0.3 of 200, loose bounds

    def test_glob_sites_and_nested_install_rejected(self):
        plan = FaultPlan(rules=[
            FaultRule(site="fsx.*", error="io", nth_call=1)])
        a, b = faults.site("fsx.read"), faults.site("fsx.write")
        with faults.active(plan):
            with pytest.raises(RuntimeError):
                faults.install(plan)  # nested harness must be refused
            with pytest.raises(InjectedIOError):
                a.fire()
            with pytest.raises(InjectedIOError):
                b.fire()  # independent per-site counters: its call #1


# -- poison-query quarantine ------------------------------------------------


class TestQuarantine:
    def test_one_crash_of_coalesced_batch_is_one_strike(self, tmp_path):
        """Review finding: N coalesced riders share the fingerprint by
        construction — one crashing dispatch must count as ONE strike,
        not N (pre-fix a single crash of a 3-rider batch quarantined
        the query immediately)."""
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="crash", every=1)])
        svc = QueryService(store, ServeConfig(
            max_wait_ms=50.0, quarantine_after=3), autostart=False)
        futs = [svc.knn("faulty", CQL, np.array([1.0]),
                        np.array([2.0]), k=3) for _ in range(3)]
        try:
            with faults.active(plan):
                svc.start()
                for f in futs:
                    with pytest.raises(InjectedCrash):
                        f.result(timeout=60)
                # one crashing dispatch = one strike: still admitted
                fut = svc.knn("faulty", CQL, np.array([3.0]),
                              np.array([4.0]), k=3)
                with pytest.raises(InjectedCrash):
                    fut.result(timeout=60)
        finally:
            svc.close(drain=True)
        assert svc.stats().get("quarantined", 0) == 0
        assert svc.quarantine.stats()["quarantined"] == 0

    def test_strikes_then_blocks_then_expires(self):
        t = [0.0]
        q = QuarantineRegistry(strikes=3, ttl_s=100.0,
                               clock=lambda: t[0])
        key = ("knn", "t", "cql")
        assert q.blocked(key) is None
        assert not q.strike(key)
        assert not q.strike(key)
        assert q.strike(key)  # third strike trips
        assert q.blocked(key) is not None
        assert q.blocked(("other",)) is None
        t[0] = 101.0  # TTL elapses: the deploy may have fixed it
        assert q.blocked(key) is None

    def test_full_blocked_table_keeps_striking_state(self):
        """Review finding: with the blocked table full, a threshold
        crossing must neither report tripped nor wipe the key's strike
        history — the key quarantines as soon as capacity frees."""
        t = [0.0]
        q = QuarantineRegistry(strikes=2, ttl_s=10.0, max_entries=1,
                               clock=lambda: t[0])
        q.strike("a"); assert q.strike("a")  # fills the one slot
        t[0] = 5.0
        assert not q.strike("b")
        assert not q.strike("b")  # threshold crossed but table full
        assert q.blocked("b") is None
        t[0] = 10.5  # "a" expires; "b"'s strikes (t=5) still live
        assert q.strike("b")  # history survived: next strike trips
        assert q.blocked("b") is not None

    def test_stale_strikes_expire(self):
        t = [0.0]
        q = QuarantineRegistry(strikes=2, ttl_s=10.0, clock=lambda: t[0])
        q.strike("k")
        t[0] = 11.0
        assert not q.strike("k")  # first strike aged out; count restarts

    def test_infrastructure_oserrors_never_strike(self, tmp_path):
        """Review finding: a compaction-raced FileNotFoundError is
        classified permanent (no futile retries) but it is an
        INFRASTRUCTURE answer — three raced reads must not quarantine a
        healthy hot query."""
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        storage = store.get_feature_source("faulty").storage
        # pull a data file out from under the manifest (the race)
        name, entries = next(iter(storage.manifest_snapshot().items()))
        os.remove(os.path.join(storage.root, name, entries[0]["file"]))
        svc = QueryService(store, ServeConfig(
            max_wait_ms=0.0, quarantine_after=3))
        try:
            for _ in range(4):
                fut = svc.query("faulty", CQL)
                # every attempt fails with the typed FS error — never
                # with QueryRejected("quarantined")
                with pytest.raises(FileNotFoundError):
                    fut.result(timeout=60)
            assert svc.quarantine.stats() == {"quarantined": 0,
                                              "striking": 0}
        finally:
            svc.close(drain=True)

    def test_service_rejects_quarantined_fingerprint(self, tmp_path):
        from geomesa_tpu.serve.scheduler import QueryRejected
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="crash", every=1)])
        svc = QueryService(store, ServeConfig(
            max_wait_ms=0.0, quarantine_after=3))
        try:
            with faults.active(plan):
                for _ in range(3):
                    fut = svc.knn("faulty", CQL, np.array([1.0]),
                                  np.array([2.0]), k=3)
                    with pytest.raises(InjectedCrash):
                        fut.result(timeout=60)
                # fingerprint has three strikes: rejected at ADMISSION
                with pytest.raises(QueryRejected) as ei:
                    svc.knn("faulty", CQL, np.array([5.0]),
                            np.array([5.0]), k=3)
                assert ei.value.reason == "quarantined"
                # different fingerprint (k differs) still admitted
                fut = svc.knn("faulty", CQL, np.array([1.0]),
                              np.array([2.0]), k=4)
                with pytest.raises(InjectedCrash):
                    fut.result(timeout=60)
            assert svc.stats()["quarantined"] >= 1
        finally:
            svc.close(drain=True)


    def test_degraded_request_strikes_admission_fingerprint(
            self, tmp_path):
        """Review finding: the ladder rewrites hints, and the
        fingerprint includes the hint string — strikes must land on the
        PRE-degrade key admission checks, or quarantine silently never
        trips for degraded poison queries."""
        from geomesa_tpu.plan.query import Query
        from geomesa_tpu.serve.service import (
            QueryService, ServeConfig, _quarantine_key)

        store = make_store(tmp_path)
        svc = QueryService(store, ServeConfig(
            max_wait_ms=0.0, degrade=True, quarantine_after=3),
            autostart=False)
        try:
            req = svc._request("count", Query("faulty", CQL),
                               allow_degraded=True)
            pre = _quarantine_key(req)
            svc._degrade(req, 2)
            # a sketch-eligible count takes the SPECULATIVE sketch rung
            # (docs/SERVING.md "Approximate answers"): hints rewritten
            # now, `degraded` marked only if a sketch answer is served —
            # the fingerprint stash happens either way, which is what
            # this test protects
            assert req.sketch_rung == 2 and not req.degraded
            assert req.quarantine_key == pre
            # the post-degrade computed key differs (hints rewritten)…
            assert _quarantine_key(req) != pre
            # …so a strike on the stashed key is what admission sees
            for _ in range(3):
                svc.quarantine.strike(req.quarantine_key)
            fresh = svc._request("count", Query("faulty", CQL))
            assert svc.quarantine.blocked(_quarantine_key(fresh))
        finally:
            svc.close(drain=False)


# -- OOM -> halve -> host-eval fallback ------------------------------------


class TestOOMFallback:
    def test_host_results_match_device(self, tmp_path):
        """Acceptance: with every device transfer OOMing, counts and
        kNN answers equal the healthy device path's on the same store."""
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        qx, qy = np.array([10.0, -40.0]), np.array([20.0, 5.0])

        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            base_count = svc.count("faulty", CQL).result(timeout=60)
            bd, bi, _ = svc.knn("faulty", CQL, qx, qy,
                                k=5).result(timeout=60)
        finally:
            svc.close(drain=True)
        assert base_count > 0

        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="oom", every=1)])
        svc2 = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            with faults.active(plan):
                oom_count = svc2.count("faulty", CQL).result(timeout=60)
                hd, hi, _ = svc2.knn("faulty", CQL, qx, qy,
                                     k=5).result(timeout=60)
        finally:
            svc2.close(drain=True)
        assert oom_count == base_count
        assert np.array_equal(hi, bi)  # identical neighbor sets/order
        assert np.allclose(hd, bd, rtol=1e-3)  # f32 device noise only
        from geomesa_tpu.utils.metrics import metrics

        with metrics._lock:
            assert metrics.counters.get("fault.oom.hosteval", 0) >= 2

    def test_halving_splits_coalesced_batch(self, tmp_path):
        """A coalesced kNN group that OOMs once re-runs as two halves:
        every rider still gets its exact answer."""
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        rng = np.random.default_rng(3)
        pts = rng.uniform(-60, 60, (6, 2))

        svc = QueryService(store, ServeConfig(max_wait_ms=50.0),
                           autostart=False)
        serial = []
        src = store.get_feature_source("faulty")
        for i in range(6):
            serial.append(src.planner.knn(
                CQL, pts[i:i + 1, 0], pts[i:i + 1, 1], k=4))
        # first transfer of the coalesced dispatch OOMs -> halves retry
        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="oom", nth_call=1)])
        futs = [svc.knn("faulty", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1],
                        k=4) for i in range(6)]
        with faults.active(plan):
            svc.start()
            results = [f.result(timeout=120) for f in futs]
            svc.close(drain=True)
        for (d, ix, _), (sd, six, _) in zip(results, serial):
            assert np.array_equal(ix, six)
            assert np.allclose(d, sd, rtol=1e-3)
        from geomesa_tpu.utils.metrics import metrics

        with metrics._lock:
            assert metrics.counters.get("serve.oom.halved", 0) >= 1

    def test_shared_count_group_host_evals_once_without_halving(
            self, tmp_path):
        """Review finding: count/execute groups DEDUP to one planner
        run whose program size is independent of rider count — halving
        them just re-fails the identical allocation. They must go
        straight to ONE host evaluation shared by every rider."""
        from geomesa_tpu.serve.service import QueryService, ServeConfig
        from geomesa_tpu.utils.metrics import metrics

        store = make_store(tmp_path)
        svc = QueryService(store, ServeConfig(max_wait_ms=50.0))
        try:
            base = svc.count("faulty", CQL).result(timeout=60)
        finally:
            svc.close(drain=True)

        with metrics._lock:
            before = dict(metrics.counters)
        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="oom", every=1)])
        svc2 = QueryService(store, ServeConfig(max_wait_ms=50.0),
                            autostart=False)
        futs = [svc2.count("faulty", CQL) for _ in range(4)]
        with faults.active(plan):
            svc2.start()
            counts = [f.result(timeout=120) for f in futs]
            svc2.close(drain=True)
        assert counts == [base] * 4
        with metrics._lock:
            after = dict(metrics.counters)
        assert (after.get("serve.oom.halved", 0)
                == before.get("serve.oom.halved", 0))
        assert (after.get("fault.oom.hosteval", 0)
                - before.get("fault.oom.hosteval", 0)) == 1

    def test_aggregation_hints_surface_typed(self, tmp_path):
        from geomesa_tpu.faults.fallback import host_execute
        from geomesa_tpu.plan.hints import QueryHints
        from geomesa_tpu.plan.query import Query

        store = make_store(tmp_path)
        src = store.get_feature_source("faulty")
        q = Query("faulty", CQL,
                  hints=QueryHints(density_bbox=(-10, -10, 10, 10),
                                   density_width=8, density_height=8))
        with pytest.raises(PermanentError):
            host_execute(src, q)

    def test_host_fallback_respects_interceptor_chain(self, tmp_path):
        """Review finding: the host path must run the planner's
        QueryInterceptor chain exactly like the device path — a
        mandatory rewrite (e.g. tenant isolation) must bind on fallback
        results too."""
        import dataclasses

        from geomesa_tpu.cql import ast, parse_cql
        from geomesa_tpu.faults.fallback import host_count
        from geomesa_tpu.plan.query import Query

        store = make_store(tmp_path)
        src = store.get_feature_source("faulty")
        device_all = src.get_count(Query("faulty", CQL))

        def isolate(query):
            merged = ast.And((query.filter_ast,
                              parse_cql("score > 0")))
            return dataclasses.replace(query, filter=merged)

        src.planner.interceptors.append(isolate)
        device_n = src.get_count(Query("faulty", CQL))
        host_n = host_count(src, Query("faulty", CQL))
        assert host_n == device_n  # identical to the device path…
        assert host_n < device_all  # …and the guard actually bound


# -- storage write atomicity under manifest-commit failure ------------------


class TestManifestCommitRollback:
    def test_failed_commit_rolls_back_memory(self, tmp_path):
        """Review finding: a manifest-persist failure must roll the
        in-memory append back — pre-fix the 'failed' batch kept serving
        from memory, a client retry duplicated every row, and the next
        unrelated write silently committed it to disk."""
        import json as _json
        import os as _os

        store = make_store(tmp_path, n=64)
        src = store.get_feature_source("faulty")
        storage = src.storage
        before = storage.count
        snap_before = {k: list(v)
                       for k, v in storage.manifest_snapshot().items()}

        plan = FaultPlan(rules=[
            FaultRule(site="fs.write_manifest", error="io", nth_call=1)])
        from geomesa_tpu.core.columnar import FeatureBatch
        from geomesa_tpu.core.sft import SimpleFeatureType

        rng = np.random.default_rng(4)
        batch = FeatureBatch.from_pydict(storage.sft, {
            "name": ["x"] * 8,
            "score": rng.uniform(-1, 1, 8),
            "dtg": rng.integers(1_590_000_000_000, 1_590_080_000_000, 8),
            "geom": rng.uniform(-10, 10, (8, 2)),
        })
        with faults.active(plan):
            with pytest.raises(OSError):
                src.write(batch)
        # memory matches disk: the failed batch is NOT visible
        assert storage.count == before
        assert {k: list(v)
                for k, v in storage.manifest_snapshot().items()} \
            == snap_before
        with open(_os.path.join(storage.root, "metadata.json")) as f:
            disk = _json.load(f)["manifest"]
        assert {k: v for k, v in disk.items()} == snap_before
        # a retry succeeds exactly once — no duplicated rows
        src.write(batch)
        assert storage.count == before + 8

    def test_failed_delete_commit_rolls_back_memory(self, tmp_path):
        """Same invariant on the delete path: a failed durable commit
        must not leave a phantom delete visible in memory (a restart
        would resurrect the rows)."""
        store = make_store(tmp_path, n=64)
        src = store.get_feature_source("faulty")
        storage = src.storage
        before = storage.count
        plan = FaultPlan(rules=[
            FaultRule(site="fs.write_manifest", error="io", nth_call=1)])
        with faults.active(plan):
            with pytest.raises(OSError):
                src.delete_features("name = 'a'")
        assert storage.count == before  # memory matches disk
        deleted = src.delete_features("name = 'a'")
        assert deleted > 0
        assert storage.count == before - deleted

    def test_failed_compact_commit_rolls_back_memory(self, tmp_path):
        """compact() too: a failed durable commit keeps the pre-compact
        manifest live in memory and does NOT delete the old files."""
        store = make_store(tmp_path, n=64)
        src = store.get_feature_source("faulty")
        storage = src.storage
        # second file in the same partitions so compact has work
        from geomesa_tpu.core.columnar import FeatureBatch

        rng = np.random.default_rng(6)
        src.write(FeatureBatch.from_pydict(storage.sft, {
            "name": ["y"] * 16,
            "score": rng.uniform(-1, 1, 16),
            "dtg": rng.integers(1_590_000_000_000, 1_590_080_000_000,
                                16),
            "geom": rng.uniform(-10, 10, (16, 2)),
        }))
        before = storage.count
        snap_before = {k: [e["file"] for e in v]
                       for k, v in storage.manifest_snapshot().items()}
        plan = FaultPlan(rules=[
            FaultRule(site="fs.write_manifest", error="io", nth_call=1)])
        with faults.active(plan):
            with pytest.raises(OSError):
                storage.compact()
        assert storage.count == before
        snap_after = {k: [e["file"] for e in v]
                      for k, v in storage.manifest_snapshot().items()}
        assert snap_after == snap_before
        # every pre-compact file survived (rollback skipped removal)
        for name, files in snap_before.items():
            for f in files:
                assert os.path.exists(
                    os.path.join(storage.root, name, f))
        # a retry compacts cleanly
        assert storage.compact() > 0
        assert storage.count == before


# -- ServeEvent recovery attribution ---------------------------------------


class TestServeEventAttribution:
    def test_retries_and_faults_attributed(self, tmp_path):
        from geomesa_tpu.plan.audit import ServeEvent
        from geomesa_tpu.serve.service import QueryService, ServeConfig

        store = make_store(tmp_path)
        plan = FaultPlan(rules=[
            FaultRule(site="fs.read_partition", error="io", nth_call=1)])
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            with faults.active(plan):
                # feature execute: the scan (and so the retry) runs on
                # the dispatch thread itself — the attribution window.
                # (Streaming counts read on the decode-ahead helper
                # thread; those retries are metered globally but not
                # attributed per-request — documented in _dispatch.)
                r = svc.query("faulty", CQL).result(timeout=60)
        finally:
            svc.close(drain=True)
        assert r.count > 0  # the retry absorbed the injected fault
        events = [e for e in store.audit.snapshot()
                  if isinstance(e, ServeEvent)]
        assert events, "serve event missing"
        ev = events[-1]
        assert ev.status == "ok"
        assert ev.retries >= 1
        assert ev.fault_injected >= 1
        assert ev.breaker_state == ""  # one hiccup: breakers closed

    def test_event_fields_default_clean(self, tmp_path):
        from geomesa_tpu.plan.audit import ServeEvent

        ev = ServeEvent(type_name="t", kind="count", tenant="",
                        priority="normal", queue_ms=0.0, exec_ms=0.0,
                        batch_size=1, status="ok")
        doc = ev.to_json()
        assert doc["retries"] == 0
        assert doc["fault_injected"] == 0
        assert doc["breaker_state"] == ""


# -- bounded kNN widen loop -------------------------------------------------


class TestKnnWidenBound:
    def test_partial_recall_instead_of_unbounded_loop(
            self, tmp_path, monkeypatch):
        import geomesa_tpu.process.knn as knn_mod
        from geomesa_tpu.core.columnar import FeatureBatch
        from geomesa_tpu.core.sft import SimpleFeatureType

        monkeypatch.setattr(knn_mod, "MAX_WIDEN_ROUNDS", 4)
        store = make_store(tmp_path, n=2, seed=1)
        src = store.get_feature_source("faulty")
        sft = SimpleFeatureType.from_spec("q", "*geom:Point")
        qpts = FeatureBatch.from_pydict(
            sft, {"geom": np.array([[1.0, 2.0]])})
        proc = knn_mod.KNearestNeighborSearchProcess()
        # 5 neighbors wanted, 2 points exist, infinite search distance:
        # the recall window can NEVER fill — pre-fix this doubled the
        # radius forever; now it returns flagged after the cap
        result = proc.execute(
            qpts, src, num_desired=5, estimated_distance_m=1000.0,
            max_search_distance_m=float("inf"))
        assert result.partial_recall is True
        assert result.distances_m.shape == (1, 5)
        assert np.isfinite(result.distances_m[0]).sum() <= 2

    def test_satisfied_search_not_flagged(self, tmp_path):
        import geomesa_tpu.process.knn as knn_mod
        from geomesa_tpu.core.columnar import FeatureBatch
        from geomesa_tpu.core.sft import SimpleFeatureType

        store = make_store(tmp_path, n=200, seed=2)
        src = store.get_feature_source("faulty")
        sft = SimpleFeatureType.from_spec("q", "*geom:Point")
        qpts = FeatureBatch.from_pydict(
            sft, {"geom": np.array([[1.0, 2.0]])})
        proc = knn_mod.KNearestNeighborSearchProcess()
        result = proc.execute(
            qpts, src, num_desired=3, estimated_distance_m=100_000.0,
            max_search_distance_m=30_000_000.0)
        assert result.partial_recall is False
        assert np.isfinite(result.distances_m).all()


# -- GT14 lint rule ---------------------------------------------------------


def lint_scoped(tmp_path, source, rel="geomesa_tpu/store/mod.py"):
    from geomesa_tpu.analysis import lint_paths

    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return lint_paths([str(tmp_path)], rules=["GT14"],
                      extra_ref_paths=[])


class TestGT14:
    DIRTY = """\
        def read(path):
            try:
                return open(path).read()
            except Exception:
                pass

        def read2(path):
            try:
                return open(path).read()
            except:
                pass

        def poll(broker):
            while True:
                try:
                    broker.consume()
                except Exception:
                    continue
    """

    def test_flags_swallows_and_unbounded_retry(self, tmp_path):
        fs = [f for f in lint_scoped(tmp_path, self.DIRTY)
              if not f.waived]
        got = {(f.rule, f.line) for f in fs}
        assert ("GT14", 4) in got   # except Exception: pass
        assert ("GT14", 10) in got  # bare except: pass
        assert ("GT14", 14) in got  # while True retry without exit
        assert len(fs) == 3

    CLEAN = """\
        import logging

        def read(path):
            try:
                return open(path).read()
            except Exception as e:
                logging.warning("read failed: %s", e)
                return None

        def read_narrow(path):
            try:
                return open(path).read()
            except FileNotFoundError:
                pass  # narrow type: a judgement call, not a swallow

        def poll_bounded(broker):
            for _ in range(3):
                try:
                    return broker.consume()
                except Exception:
                    continue
            raise RuntimeError("exhausted")

        def loop_with_exit(broker):
            while True:
                try:
                    return broker.consume()
                except Exception:
                    raise
    """

    def test_clean_twins_quiet(self, tmp_path):
        fs = [f for f in lint_scoped(tmp_path, self.CLEAN)
              if not f.waived]
        assert fs == []

    NESTED_BREAK = """\
        def poll(broker, backlog):
            while True:
                try:
                    broker.consume()
                except Exception:
                    pass
                for x in backlog:
                    if x:
                        break
    """

    def test_nested_loop_break_is_not_an_exit(self, tmp_path):
        """Review finding: a break belonging to a NESTED for/while
        exits only that inner loop — pre-fix it silenced the outer
        while-True retry-forever report."""
        fs = [f for f in lint_scoped(tmp_path, self.NESTED_BREAK)
              if not f.waived]
        assert ("GT14", 2) in {(f.rule, f.line) for f in fs}

    FOR_ELSE_BREAK = """\
        def poll(broker, attempts):
            while True:
                try:
                    for a in attempts:
                        if broker.consume(a):
                            raise StopIteration
                    else:
                        break
                except OSError:
                    pass
    """

    def test_for_else_break_exits_the_outer_loop(self, tmp_path):
        """Review finding: a break in a nested loop's `else:` clause
        targets the ENCLOSING loop (Python for/else) — flagging this
        bounded loop would force a spurious waiver."""
        fs = [f for f in lint_scoped(tmp_path, self.FOR_ELSE_BREAK)
              if not f.waived and "while True" in f.message]
        assert fs == []

    def test_out_of_scope_paths_ignored(self, tmp_path):
        fs = lint_scoped(tmp_path, self.DIRTY,
                         rel="geomesa_tpu/engine/mod.py")
        assert [f for f in fs if not f.waived] == []

    def test_waivable(self, tmp_path):
        src = """\
            def degrade(path):
                try:
                    return open(path).read()
                # gt: waive GT14
                except Exception:
                    pass
        """
        fs = lint_scoped(tmp_path, src)
        assert all(f.waived for f in fs if f.rule == "GT14")
        assert any(f.rule == "GT14" for f in fs)


# -- seeded chaos regression (gmtpu chaos --check, in-process) --------------


class TestChaosRegression:
    def test_cache_restore_does_not_double_platform_suffix(
            self, tmp_path):
        """Review finding: persistent_cache_dir() is already
        platform-suffixed; restoring it through the default
        per_platform=True re-joined the backend (<dir>/cpu/cpu) and
        silently orphaned every persisted executable."""
        import io

        from geomesa_tpu.compilecache.persist import (
            disable_persistent_cache, enable_persistent_cache,
            persistent_cache_dir)

        prior = enable_persistent_cache(
            cache_dir=str(tmp_path / "cc"), force=True)
        try:
            assert prior is not None and prior.endswith(os.sep + "cpu")
            plan = FaultPlan(rules=[
                FaultRule(site="kafka.poll", error="unavailable",
                          nth_call=1)])
            from geomesa_tpu.faults.chaos import run_chaos

            run_chaos(plan, requests=4, replay=False, out=io.StringIO())
            assert persistent_cache_dir() == prior  # not .../cpu/cpu
        finally:
            disable_persistent_cache()

    def test_setup_failure_leaks_nothing(self):
        """Review finding: a chaos setup failure (here: a harness is
        already installed) must not leak chaos breaker tuning or an
        orphaned dispatch thread into the process."""
        from geomesa_tpu.faults.chaos import run_chaos

        faults.BREAKERS.configure("storage", failure_threshold=10,
                                  reset_timeout_s=7.0)
        plan = FaultPlan(rules=[
            FaultRule(site="fs.read_partition", error="io", nth_call=1)])
        blocker = faults.install(FaultPlan(rules=[
            FaultRule(site="unused.site", error="io", nth_call=1)]))
        assert blocker is not None
        try:
            import io

            with pytest.raises(RuntimeError):
                run_chaos(plan, requests=2, replay=False,
                          out=io.StringIO())
        finally:
            faults.uninstall()
        # prior tuning survived the failed run
        b = faults.BREAKERS.get("storage")
        assert b.failure_threshold == 10
        assert b.reset_timeout_s == 7.0
        faults.BREAKERS.restore_config("storage", None)


    def test_smoke_plan_invariants_and_replay(self):
        import io

        from geomesa_tpu.faults.chaos import run_chaos

        plan_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "chaos_smoke_plan.json")
        plan = FaultPlan.load(plan_path)
        report = run_chaos(plan, requests=16, replay=True,
                           out=io.StringIO())
        assert report.invariant_failures == []
        assert report.ok_overall
        assert report.untyped_errors == []
        assert report.replay_match is True
        assert report.fires > 0
        # every acceptance site CLASS injected: storage read, kafka
        # poll, device transfer, compile-cache write
        fired = set(report.fired_sites)
        assert "fs.read_partition" in fired
        assert "kafka.poll" in fired
        assert "device.transfer" in fired
        assert "compilecache.persist" in fired
        # breaker open AND half-open transitions metered
        assert report.breaker_counters[
            "fault.breaker.storage.open"] >= 1
        assert report.breaker_counters[
            "fault.breaker.storage.half_open"] >= 1
        # the disabled harness stays a no-op check
        assert report.noop_us_per_call < 5.0
