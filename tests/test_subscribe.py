"""geomesa_tpu.subscribe: standing queries over the Kafka live layer.

The load-bearing test is TestIncrementalParity: ≥8 mixed subscriptions
(bbox, dwithin, CQL-attribute, density windows) folded over ≥20 Kafka
batches, where after EVERY batch each subscription's incrementally
maintained matched set equals a fresh one-shot planner query over the
live snapshot (bit-identical fids; density grids allclose), the pushed
enter/exit event stream replays to exactly the diff of consecutive
snapshots (zero missed / duplicate / phantom events), and evaluation is
ONE coalesced device dispatch per poll with zero fused-kernel
recompiles once warm (evaluator dispatch counters + the AOT registry's
miss counter).

Wall-clock discipline (tier-1 budget is effectively full): one Kafka
store per test class, constant fid populations so snapshot shapes stay
in one pow2 bucket, and small density grids.
"""

import json
import time

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.kafka.cache import KafkaFeatureCache
from geomesa_tpu.kafka.store import KafkaDataStore
from geomesa_tpu.plan.hints import QueryHints
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve.scheduler import QueryRejected
from geomesa_tpu.subscribe import (
    DensityWindow, SubscribeConfig, Subscription, SubscriptionManager,
    SubscriptionRegistry)

SFT = SimpleFeatureType.from_spec(
    "live", "name:String,score:Double,dtg:Date,*geom:Point"
)

N_FIDS = 48


def _rows(seed, fids):
    """Deterministic attribute rows for a set of fids."""
    rng = np.random.default_rng(seed)
    n = len(fids)
    return FeatureBatch.from_pydict(SFT, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-5, 5, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack([rng.uniform(-60, 60, n),
                          rng.uniform(-30, 30, n)], 1),
    }, fids=list(fids))


def _density_oracle(window: DensityWindow, batch) -> np.ndarray:
    """Host f64 grid over a snapshot, with the f32 cell binning the
    device kernels use (engine.density.density_grid arithmetic)."""
    grid = np.zeros((window.height, window.width), np.float64)
    if batch is None or len(batch) == 0:
        return grid
    g = SFT.default_geometry.name
    col_g = batch.columns[g]
    x32 = np.asarray(col_g.x, np.float32)
    y32 = np.asarray(col_g.y, np.float32)
    x0, y0, x1, y1 = window.bbox
    dx = np.float32((x1 - x0) / window.width)
    dy = np.float32((y1 - y0) / window.height)
    col = np.floor((x32 - np.float32(x0)) / dx).astype(np.int64)
    row = np.floor((y32 - np.float32(y0)) / dy).astype(np.int64)
    inb = ((col >= 0) & (col < window.width)
           & (row >= 0) & (row < window.height))
    w = (np.ones(len(batch), np.float64) if window.weight_attr is None
         else np.asarray(batch.columns[window.weight_attr], np.float64))
    np.add.at(grid, (row[inb], col[inb]), w[inb])
    return grid


class _EventLog:
    """Collects push frames and replays enter/exit streams per
    subscription, asserting zero duplicate/phantom transitions."""

    def __init__(self):
        self.frames = []

    def push(self, frame):
        self.frames.append(frame)

    def replay_matched(self, sub_id) -> set:
        state = set()
        for f in sorted((f for f in self.frames
                         if f.get("subscription") == sub_id
                         and f.get("event") in ("enter", "exit", "state")),
                        key=lambda f: f["seq"]):
            if f["event"] == "state":
                state = set(f["fids"])
            elif f["event"] == "enter":
                dup = set(f["fids"]) & state
                assert not dup, f"duplicate enter events for {dup}"
                state |= set(f["fids"])
            else:
                ghost = set(f["fids"]) - state
                assert not ghost, f"phantom exit events for {ghost}"
                state -= set(f["fids"])
        return state


class TestIncrementalParity:
    """The acceptance gate: incremental == one-shot, one dispatch per
    poll, event streams are exactly the snapshot diffs."""

    CQLS = [
        "BBOX(geom, -20, -15, 25, 20)",
        "BBOX(geom, -50, -25, -10, 5)",
        "DWITHIN(geom, POINT(10 5), 2000000, meters)",
        "DWITHIN(geom, POINT(-30 -10), 1500000, meters)",
        "name = 'a'",
        "score > 0 AND BBOX(geom, -40, -30, 40, 30)",
    ]
    WINDOWS = [
        DensityWindow((-60.0, -30.0, 60.0, 30.0), 16, 8),
        DensityWindow((-30.0, -20.0, 30.0, 20.0), 12, 10,
                      weight_attr="score"),
    ]

    def test_parity_over_20_batches(self):
        store = KafkaDataStore()
        src = store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        subs = [mgr.subscribe("live", cql) for cql in self.CQLS]
        subs += [mgr.subscribe("live", density=w) for w in self.WINDOWS]
        assert len(subs) == 8
        log = _EventLog()
        from geomesa_tpu.compilecache.registry import registry as aot

        fids = [f"f{i}" for i in range(N_FIDS)]
        base_ev = mgr.evaluator.stats()
        base_misses = aot.stats()["misses"]
        polls_with_delta = 0
        warm_misses = None
        for b in range(20):
            if b == 0:
                store.write("live", _rows(1000, fids))     # seed all
            elif b == 7:
                for fid in fids[:3]:
                    store.delete("live", fid)              # shrink
            elif b == 8:
                store.write("live", _rows(2000 + b, fids[:3]))  # re-add
            elif b == 10:
                store.clear("live")                        # wipe
            elif b == 11:
                store.write("live", _rows(3000, fids))     # re-seed
            else:
                # moving fleet: half the population drifts each batch
                moving = [fids[(b * 7 + j) % N_FIDS] for j in range(24)]
                store.write("live", _rows(4000 + b, moving))
            applied = store.poll("live")
            assert applied > 0
            polls_with_delta += 1
            mgr.flush(log.push)
            snap = store.cache("live").snapshot()
            # one-shot parity: every predicate subscription's matched
            # set is bit-identical to a fresh planner query's fids
            for sub, cql in zip(subs[:6], self.CQLS):
                res = src.get_features(Query("live", cql))
                got = (set() if res.features is None
                       else set(res.features.fids.decode()))
                assert sub.matched == got, (
                    f"batch {b}: {cql!r} incremental != one-shot")
                # and the replayed event stream reconstructs it
                assert log.replay_matched(sub.sub_id) == got
            # density parity: grids allclose against the host oracle
            for sub, window in zip(subs[6:], self.WINDOWS):
                oracle = _density_oracle(window, snap)
                assert np.allclose(sub.grid, oracle, atol=1e-9), (
                    f"batch {b}: density window diverged "
                    f"(max err {np.abs(sub.grid - oracle).max()})")
        ev = mgr.evaluator.stats()
        d_folds = ev["folds"] - base_ev["folds"]
        d_disp = ev["dispatches"] - base_ev["dispatches"]
        # three coalesced device dispatches per poll — the bbox lane,
        # the dwithin lane, and the fused remainder (attribute/compound
        # predicates + both density windows) — independent of how many
        # subscriptions each lane carries; the two windows with no
        # changed rows (b=7 deletes-only, b=10 clear-only) fold
        # set-difference-only and dispatch nothing
        assert d_disp == 3 * (polls_with_delta - 2), (
            ev, polls_with_delta)
        assert d_folds == polls_with_delta
        assert (ev["lane_dispatches"]
                - base_ev.get("lane_dispatches", 0)) == d_disp // 3 * 2
        assert ev["fallbacks"] == base_ev.get("fallbacks", 0)
        # each kernel compiles once per pow2 delta bucket (the 20-batch
        # run sees three: 64-seed, 32-move, 16-readd) — fused remainder
        # plus one per lane class — NEVER per batch or per
        # subscription...
        warm_misses = aot.stats()["misses"]
        assert warm_misses - base_misses <= 9
        # ...and repeated buckets are pure AOT hits: further batches
        # add zero compiles (the zero-recompile steady state)
        for b in range(3):
            moving = [fids[(b * 11 + j) % N_FIDS] for j in range(24)]
            store.write("live", _rows(5000 + b, moving))
            store.poll("live")
        assert aot.stats()["misses"] == warm_misses, (
            "kernel recompiled on a warm pow2 bucket")
        assert (mgr.evaluator.stats()["dispatches"]
                - base_ev["dispatches"]) == d_disp + 9
        mgr.close()


class TestExactlyOnce:
    """Injected kafka.poll outage: typed error from the poll, zero
    missed and zero double-applied events across the outage."""

    def test_poll_fault_then_heal(self):
        from geomesa_tpu.faults import harness as _h
        from geomesa_tpu.faults.plan import FaultPlan, FaultRule

        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        sub = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)")
        log = _EventLog()
        fids = [f"f{i}" for i in range(24)]
        store.write("live", _rows(1, fids))
        store.poll("live")
        mgr.flush(log.push)
        matched_before = set(sub.matched)
        # the kafka retry policy makes 4 attempts: every=1 x 4 fires
        # exhausts the FIRST poll (typed), leaves the second clean
        plan = FaultPlan(seed=3, rules=[FaultRule(
            site="kafka.poll", error="unavailable", every=1, max_fires=4)])
        store.write("live", _rows(2, fids))
        with _h.active(plan):
            with pytest.raises(ConnectionError):
                store.poll("live")
            mgr.flush(log.push)
            # failed poll: no fold, no events, state untouched
            assert sub.matched == matched_before
            assert log.replay_matched(sub.sub_id) == matched_before
            healed = store.poll("live")
        from geomesa_tpu.faults.breaker import BREAKERS

        BREAKERS.reset("kafka")
        assert healed == 24
        mgr.flush(log.push)
        # the outage window folded exactly once: replayed events match
        # a fresh one-shot over the live snapshot
        src = store.get_feature_source("live")
        res = src.get_features(Query("live", sub.cql))
        want = set(res.features.fids.decode()) if res.features is not None else set()
        assert sub.matched == want
        assert log.replay_matched(sub.sub_id) == want
        mgr.close()


class TestSlowConsumer:
    """Bounded outbox: overflow flips lagged mode with a typed
    subscription_lagged frame and a latest-state-only re-sync —
    memory never grows past the bound."""

    def test_outbox_overflow_lagged_resync(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(
            store, SubscribeConfig(outbox_limit=3))
        sub = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)",
                            initial_state=False)
        fids = [f"f{i}" for i in range(16)]
        # no flush between batches: the outbox must overflow its bound
        for b in range(8):
            store.write("live", _rows(100 + b, fids))
            store.poll("live")
        assert sub.lagged
        assert sub.outbox_depth() <= 3
        assert sub.overflows >= 1
        log = _EventLog()
        mgr.flush(log.push)
        kinds = [f["event"] for f in log.frames]
        assert "subscription_lagged" in kinds
        assert kinds[-1] == "state"
        state = [f for f in log.frames if f["event"] == "state"][-1]
        assert set(state["fids"]) == sub.matched
        assert not sub.lagged
        # incremental delivery resumes after the re-sync
        store.write("live", _rows(999, fids))
        store.poll("live")
        mgr.flush(log.push)
        assert log.replay_matched(sub.sub_id) == sub.matched

    def test_terminal_frames_bypass_lagged_drop(self):
        # a lagged subscription still hears that it DIED: expired /
        # quarantined frames are the last thing the client ever gets
        sub = Subscription("live", "INCLUDE", outbox_limit=2)
        sub.offer({"event": "enter", "fids": ["a"]})
        sub.offer({"event": "enter", "fids": ["b"]})
        sub.offer({"event": "enter", "fids": ["c"]})  # overflow -> lagged
        assert sub.lagged
        assert sub.offer({"event": "enter", "fids": ["d"]}) is False
        assert sub.offer({"event": "quarantined", "message": "boom"})
        kinds = [f["event"] for f in sub.drain()]
        assert kinds == ["subscription_lagged", "quarantined"]

    def test_quarantined_subscription_swept_by_ttl(self):
        reg = SubscriptionRegistry()
        now = [0.0]
        sub = Subscription("live", "INCLUDE", clock=lambda: now[0])
        reg.register(sub)
        reg.quarantine(sub.sub_id)
        sub.expires_at = 50.0  # what the evaluator stamps on trip
        assert reg.expire_tick(now=10.0) == []
        assert reg.expire_tick(now=60.0) == [sub]
        assert reg.maybe(sub.sub_id) is None  # no longer pinned/flushed

    def test_rate_limited_drain_backpressures(self):
        sub = Subscription("live", "INCLUDE", rate=2.0, rate_burst=2.0,
                           outbox_limit=64)
        for i in range(6):
            sub.offer({"event": "enter", "fids": [f"f{i}"]})
        got = sub.drain()
        # burst of 2 frames passes; the rest stay queued (backpressure
        # into the bounded outbox, not silent drops)
        assert len(got) == 2
        assert sub.outbox_depth() == 4

    def test_failing_push_sink_loses_no_frames(self):
        # a sink that raises mid-flush must leave the undelivered
        # remainder queued (front of the outbox, seq order preserved),
        # not silently drop drained frames
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        sub = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)",
                            initial_state=False)
        fids = [f"f{i}" for i in range(8)]
        for b in range(3):
            store.write("live", _rows(40 + b, fids))
            store.poll("live")
        assert sub.outbox_depth() >= 2
        delivered = []

        def broken(frame):
            if delivered:
                raise BrokenPipeError("sink gone")
            delivered.append(frame)

        with pytest.raises(BrokenPipeError):
            mgr.flush(broken)
        assert len(delivered) == 1
        log = _EventLog()
        mgr.flush(log.push)
        seqs = [f["seq"] for f in delivered + log.frames]
        # contiguous seqs across both flushes: zero lost, zero dup
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
        mgr.close()


class TestQuarantine:
    """A predicate that crashes evaluation is struck and quarantined —
    not retried forever — while healthy subscriptions keep folding."""

    class _Poison:
        filter_ast = None
        _band_fn = None

        def params(self, batch):
            return {}

        def mask_fn(self):
            def bad(params, dev):
                raise RuntimeError("poisoned predicate")

            return bad

        def mask_refined(self, dev, batch):
            raise RuntimeError("poisoned predicate")

    def test_crashing_predicate_quarantined(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(
            store, SubscribeConfig(quarantine_after=2))
        healthy = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)")
        poisoned = mgr.subscribe("live", "score > 1.5")
        mgr.evaluator._filters[("live", "score > 1.5")] = self._Poison()
        fids = [f"f{i}" for i in range(16)]
        log = _EventLog()
        ev0 = mgr.evaluator.stats()
        for b in range(3):
            store.write("live", _rows(200 + b, fids))
            store.poll("live")
            mgr.flush(log.push)
        ev = mgr.evaluator.stats()
        # the first two crashing folds degrade to the per-subscription
        # fallback and strike; the third runs fused again (poisoned
        # predicate quarantined out of the kernel)
        assert ev["fallbacks"] - ev0.get("fallbacks", 0) == 2
        assert ev["strikes"] == 2
        assert poisoned.status == "quarantined"
        assert any(f["event"] == "quarantined"
                   and f["subscription"] == poisoned.sub_id
                   for f in log.frames)
        # healthy subscription never missed a window
        src = store.get_feature_source("live")
        res = src.get_features(Query("live", healthy.cql))
        assert healthy.matched == set(res.features.fids.decode())
        assert log.replay_matched(healthy.sub_id) == healthy.matched
        # re-registering the same predicate is rejected at admission
        with pytest.raises(QueryRejected) as exc:
            mgr.subscribe("live", "score > 1.5")
        assert exc.value.reason == "quarantined"
        mgr.close()

    def test_apply_phase_crash_strikes_not_stalls(self):
        # a predicate that crashes only in the per-subscription apply
        # phase (host-band refinement, density weights) — AFTER the
        # fused kernel succeeded — must be struck and quarantined like
        # a fused-kernel crash, not retried forever via the
        # buffer-retaining infra path, and must not stall the type
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store,
                                  SubscribeConfig(quarantine_after=2))
        healthy = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)")
        dens = mgr.subscribe("live", density=DensityWindow(
            (-60, -30, 60, 30), 8, 4, weight_attr="score"))

        def boom(d, batch):
            raise RuntimeError("weights crashed")

        mgr.evaluator._weights = boom
        fids = [f"f{i}" for i in range(16)]
        log = _EventLog()
        for b in range(3):
            store.write("live", _rows(500 + b, fids))
            store.poll("live")
            mgr.flush(log.push)
        assert dens.status == "quarantined"
        # the crashing apply never stalled the fold: the buffer was
        # consumed each poll and the healthy subscription kept folding
        assert mgr.evaluator.stats()["folds"] == 3
        src = store.get_feature_source("live")
        res = src.get_features(Query("live", healthy.cql))
        assert healthy.matched == set(res.features.fids.decode())
        mgr.close()

    def test_quarantine_after_zero_disables(self):
        # quarantine_after=0 means DISABLED (the serve layer's
        # contract), not first-strike-kills
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store,
                                  SubscribeConfig(quarantine_after=0))
        sub = mgr.subscribe("live", "score > 1.5")
        mgr.evaluator._filters[("live", "score > 1.5")] = self._Poison()
        fids = [f"f{i}" for i in range(8)]
        for b in range(3):
            store.write("live", _rows(300 + b, fids))
            store.poll("live")
        assert sub.status == "active"
        assert mgr.evaluator.stats().get("strikes", 0) == 0
        mgr.close()

    def test_infra_errors_do_not_strike(self):
        # the serving layer's quarantine exemption applies here too:
        # transient failures and the OSError family are infrastructure
        # answers — an infra blip must not quarantine standing
        # subscriptions (they re-seed from the snapshot instead)
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store,
                                  SubscribeConfig(quarantine_after=2))
        sub = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)")
        ev = mgr.evaluator
        for _ in range(3):
            ev._strike(sub, ConnectionError("broker blip"))
        # OSError family exempt even when classified permanent
        ev._strike(sub, FileNotFoundError("compaction-raced read"))
        st = ev.stats()
        assert st.get("strikes", 0) == 0 and st["eval_errors"] == 4
        assert sub.status == "active" and sub._resync_pending()
        mgr.close()


class TestLifecycle:
    def test_ttl_expiry_and_registry_transitions(self):
        reg = SubscriptionRegistry()
        now = [0.0]
        sub = Subscription("live", "INCLUDE", ttl_s=10.0,
                           clock=lambda: now[0])
        reg.register(sub)
        v0 = reg.version("live")
        assert reg.expire_tick(now=5.0) == []
        assert reg.expire_tick(now=11.0) == [sub]
        assert sub.status == "expired"
        assert reg.maybe(sub.sub_id) is None
        assert reg.version("live") > v0
        assert reg.take_parting() == [sub]

    def test_expired_frame_queued_before_parting_visible(self):
        # the terminal `expired` frame must already be in the outbox
        # when the subscription first becomes visible to take_parting:
        # a flush racing the sweep pops-and-drains parting subs, and a
        # frame offered after that drain is stranded forever
        reg = SubscriptionRegistry()
        now = [0.0]
        sub = Subscription("live", "INCLUDE", ttl_s=5.0,
                           clock=lambda: now[0])
        reg.register(sub)
        now[0] = 10.0
        assert reg.expire_tick() == [sub]
        assert reg.take_parting() == [sub]
        assert [f["event"] for f in sub.drain()] == ["expired"]

    def test_pause_resume_resyncs(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        sub = mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)",
                            initial_state=False)
        fids = [f"f{i}" for i in range(16)]
        store.write("live", _rows(5, fids))
        store.poll("live")
        mgr.pause(sub.sub_id)
        assert mgr.registry.active_for("live") == []
        log = _EventLog()
        mgr.flush(log.push)
        assert log.frames == []  # paused consumers hold their outbox
        # batches folded WHILE paused never reach this subscription's
        # state (no active subs: the evaluator may even drop the
        # window) — resume must re-seed from the live snapshot, not
        # re-announce the pre-pause matched set
        store.write("live", _rows(6, fids))
        store.poll("live")
        mgr.resume(sub.sub_id)
        mgr.flush(log.push)
        # a resumed subscription re-syncs: state frame, then increments
        assert any(f["event"] == "state" for f in log.frames)
        assert log.replay_matched(sub.sub_id) == sub.matched
        src = store.get_feature_source("live")
        res = src.get_features(Query("live", sub.cql))
        oneshot = (set(res.features.fids.decode())
                   if res.features is not None else set())
        assert sub.matched == oneshot  # post-resume state is LIVE state
        mgr.unsubscribe(sub.sub_id)
        assert len(mgr.registry) == 0

    def test_density_jit_cache_is_per_instance(self):
        # the window-geometry → jitted binning executable cache must
        # die with its evaluator (one wire connection), not accrete
        # process-wide across every connection's distinct windows
        store = KafkaDataStore()
        store.create_schema(SFT)
        m1, m2 = SubscriptionManager(store), SubscriptionManager(store)
        try:
            assert m1.evaluator._cells_cache is not m2.evaluator._cells_cache
        finally:
            m1.close()
            m2.close()

    def test_close_detaches_store_hooks(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        mgr.subscribe("live", "BBOX(geom, -20, -15, 25, 20)")
        fids = [f"f{i}" for i in range(8)]
        store.write("live", _rows(1, fids))
        store.poll("live")
        folds = mgr.evaluator.stats()["folds"]
        assert folds == 1
        mgr.close()
        # a closed manager must stop costing polls: no fold hook, no
        # cache listener, no buffered events
        assert store._fold_hooks == []
        store.write("live", _rows(2, fids))
        store.poll("live")
        assert mgr.evaluator.stats()["folds"] == folds
        st = mgr.evaluator._state("live")
        assert st.buffer == [] and not st.listening

    def test_subscribe_validation(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(
            store, SubscribeConfig(max_subscriptions=1))
        with pytest.raises(ValueError):
            mgr.subscribe("live", "nosuch = 3")   # bad attribute
        with pytest.raises(KeyError):
            mgr.subscribe("ghost", "INCLUDE")     # unknown type
        # density weight column validated at admission too — typo'd or
        # non-numeric answers typed HERE, not as the first fold's crash
        with pytest.raises(ValueError):
            mgr.subscribe("live", density=DensityWindow(
                (-60, -30, 60, 30), 8, 4, weight_attr="nosuch"))
        with pytest.raises(ValueError):
            mgr.subscribe("live", density=DensityWindow(
                (-60, -30, 60, 30), 8, 4, weight_attr="name"))
        mgr.subscribe("live", "INCLUDE")
        with pytest.raises(QueryRejected) as exc:
            mgr.subscribe("live", "name = 'a'")
        assert exc.value.reason == "subscription_limit"


class TestExpiryEvents:
    """Satellite regression: expiry-driven removals emit `removed`
    FeatureEvents (geofence EXITs fire when features age out), and a
    concurrently refreshed fid survives the sweep."""

    def test_expire_emits_removed_events(self):
        cache = KafkaFeatureCache(SFT, expiry_ms=1000)
        seen = []
        cache.add_listener(lambda e: seen.append((e.kind, e.fid)))
        from geomesa_tpu.kafka.messages import Change

        t0 = time.time()
        cache.apply(Change("a", {"name": "x"}))
        cache.apply(Change("b", {"name": "y"}))
        seen.clear()
        evicted = cache.expire(now=t0 + 10.0)
        assert evicted == 2
        assert sorted(seen) == [("removed", "a"), ("removed", "b")]
        assert len(cache) == 0
        assert cache.snapshot() is None

    def test_fresh_fid_survives_sweep(self):
        cache = KafkaFeatureCache(SFT, expiry_ms=1000)
        from geomesa_tpu.kafka.messages import Change

        t0 = time.time()
        cache.apply(Change("old", {"name": "x"}))
        cache._stamps["old"] = t0 - 100.0
        cache.apply(Change("fresh", {"name": "y"}))
        assert cache.expire(now=t0 + 0.5) == 1
        assert cache.get("fresh") is not None
        assert cache.get("old") is None

    def test_expiry_drives_geofence_exit(self):
        store = KafkaDataStore(expiry_ms=30)
        store.create_schema(SFT)
        mgr = SubscriptionManager(store)
        sub = mgr.subscribe("live", "BBOX(geom, -180, -90, 180, 90)",
                            initial_state=False)
        store.write("live", _rows(7, ["f0", "f1"]))
        store.poll("live")
        assert len(sub.matched) == 2
        time.sleep(0.06)
        store.poll("live")  # expiry sweep emits removed -> EXIT events
        log = _EventLog()
        mgr.flush(log.push)
        exits = [f for f in log.frames if f["event"] == "exit"]
        assert exits and set(exits[-1]["fids"]) == {"f0", "f1"}
        assert sub.matched == set()
        mgr.close()


class TestWireProtocol:
    """subscribe/unsubscribe/poll verbs + push frames on the JSON-lines
    stream (docs/SERVING.md wire protocol)."""

    def _run(self, lines_iter, store):
        from geomesa_tpu.serve.protocol import serve_lines
        from geomesa_tpu.serve.service import ServeConfig

        out = []
        serve_lines(store, lines_iter, out.append,
                    ServeConfig(pipeline=False))
        return [json.loads(s) for s in out]

    def test_subscribe_poll_unsubscribe_round_trip(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        fids = [f"f{i}" for i in range(12)]
        store.write("live", _rows(1, fids))

        def lines():
            yield json.dumps({
                "id": "s1", "op": "subscribe", "typeName": "live",
                "cql": "BBOX(geom, -20, -15, 25, 20)"})
            yield json.dumps({
                "id": "s2", "op": "subscribe", "typeName": "live",
                "density": {"bbox": [-60, -30, 60, 30],
                            "width": 8, "height": 4}})
            yield json.dumps({"id": "p1", "op": "poll"})
            store.write("live", _rows(2, fids))
            yield json.dumps({"id": "p2", "op": "poll"})
            yield json.dumps({"id": "q1", "op": "count",
                              "typeName": "live"})
            yield json.dumps({"id": "ls", "op": "subscriptions"})
            yield json.dumps({"id": "u1", "op": "unsubscribe",
                              "subscription": "sub-1"})
            yield json.dumps({"id": "bad", "op": "subscribe",
                              "typeName": "live", "cql": "nosuch = 1"})
            yield json.dumps({"id": "u2", "op": "unsubscribe",
                              "subscription": "sub-999"})

        # fresh id space per Subscription module counter is global —
        # resolve the actual id from the response instead of sub-1
        docs = self._run(lines(), store)
        by_id = {d["id"]: d for d in docs if "id" in d}
        events = [d for d in docs if "event" in d]
        sid = by_id["s1"]["subscription"]
        assert by_id["s1"]["ok"] and by_id["s2"]["mode"] == "density"
        assert by_id["p1"]["ok"] and by_id["p1"]["applied"]["live"] == 12
        assert by_id["q1"]["count"] == 12
        assert by_id["ls"]["subscriptions"] == 2
        assert not by_id["bad"]["ok"]
        # unknown id on a LIVE session: typed answer, no leaked KeyError
        assert by_id["u2"]["ok"] is False
        assert by_id["u2"]["message"] == "no such subscription"
        # push frames interleaved: initial state, enters on p1,
        # enter/exit churn on p2, density folds
        kinds = {e["event"] for e in events}
        assert "state" in kinds and "enter" in kinds
        assert any(e["event"] == "density" for e in events)
        log = _EventLog()
        log.frames = [e for e in events if e.get("subscription") == sid]
        assert isinstance(log.replay_matched(sid), set)
        # the registration-time state frame is stamped exactly once —
        # the client's very first frame is seq 1 (offer() re-stamping
        # it to 2 would read as a phantom lost frame under the
        # monotonic-seq contract)
        first = min(log.frames, key=lambda f: f["seq"])
        assert first["event"] == "state" and first["seq"] == 1

    def test_unsubscribe_wrong_store_and_ids(self):
        import tempfile

        from geomesa_tpu.plan.datastore import DataStore

        with tempfile.TemporaryDirectory() as tmp:
            fs_store = DataStore(tmp, use_device_cache=False)

            def lines():
                # poll / introspection verbs answer cheaply without
                # instantiating a manager (works on durable stores too)
                yield json.dumps({"id": "p0", "op": "poll"})
                yield json.dumps({"id": "l0", "op": "subscriptions"})
                yield json.dumps({"id": "u0", "op": "unsubscribe",
                                  "subscription": "sub-999"})
                yield json.dumps({"id": "s1", "op": "subscribe",
                                  "typeName": "x", "cql": "INCLUDE"})

            docs = self._run(lines(), fs_store)
            by_id = {d["id"]: d for d in docs}
            assert by_id["p0"]["ok"] and by_id["p0"]["applied"] == {}
            assert by_id["l0"]["ok"] and by_id["l0"]["subscriptions"] == 0
            assert by_id["u0"]["ok"] is False
            assert by_id["s1"]["ok"] is False  # durable store: typed error


class TestLanes:
    """Vmapped parametric geofence lanes (docs/SERVING.md "Standing
    queries"): same-shape geofence classes evaluate as ONE [S]-batched
    dispatch per class whose compiled program is independent of S;
    membership churn is a parameter-row write, never a rebuild."""

    LANE_CQLS = [
        "BBOX(geom, -20, -15, 25, 20)",
        "BBOX(geom, -50, -25, -10, 5)",
        "DWITHIN(geom, POINT(10 5), 2000000, meters)",
        "DWITHIN(geom, POINT(-30 -10), 1500000, meters)",
        "INTERSECTS(geom, POLYGON((-40 -20, 10 -25, 30 15, -25 22,"
        " -40 -20)))",
        "name = 'a'",  # lane-ineligible: stays on the fused path
    ]
    WINDOW = DensityWindow((-60.0, -30.0, 60.0, 30.0), 16, 8)

    def test_lane_vs_slot_parity_with_mid_run_churn(self):
        """Matched sets and density grids bit-identical between
        lanes=True and lanes=False over 12 batches of moves/deletes/
        re-adds, with a registration AND a cancellation landing
        mid-run, and both modes equal to a fresh one-shot planner
        query after every batch."""
        stores = (KafkaDataStore(), KafkaDataStore())
        for s in stores:
            s.create_schema(SFT)
        mgrs = (SubscriptionManager(stores[0], SubscribeConfig(lanes=True)),
                SubscriptionManager(stores[1],
                                    SubscribeConfig(lanes=False)))
        subs = {m: [m.subscribe("live", cql) for cql in self.LANE_CQLS]
                + [m.subscribe("live", density=self.WINDOW)]
                for m in mgrs}
        fids = [f"f{i}" for i in range(N_FIDS)]
        base = mgrs[0].evaluator.stats()
        src = stores[0].get_feature_source("live")
        for b in range(12):
            if b == 0:
                rows = _rows(1000, fids)
            elif b == 6:
                for store in stores:
                    for fid in fids[:4]:
                        store.delete("live", fid)
                rows = None
            elif b == 7:
                rows = _rows(2000, fids[:4])
            else:
                moving = [fids[(b * 7 + j) % N_FIDS] for j in range(24)]
                rows = _rows(4000 + b, moving)
            if rows is not None:
                for store in stores:
                    store.write("live", rows)
            if b == 4:  # mid-run registration: a parameter-row write
                for m in mgrs:
                    subs[m].append(m.subscribe(
                        "live", "BBOX(geom, -5, -5, 45, 25)"))
            if b == 8:  # mid-run cancellation: a row release
                for m in mgrs:
                    m.unsubscribe(subs[m][0].sub_id)
            for store, m in zip(stores, mgrs):
                store.poll("live")
                m.flush(lambda _f: None)
            live = ([] if b >= 8 else [0]) + list(
                range(1, len(subs[mgrs[0]])))
            for i in live:
                a, c = subs[mgrs[0]][i], subs[mgrs[1]][i]
                if a.density is not None:
                    assert np.array_equal(a.grid, c.grid), (
                        f"batch {b}: lane-mode density grid diverged")
                    continue
                assert a.matched == c.matched, (
                    f"batch {b}: {a.cql!r} lanes != fused slots")
                res = src.get_features(Query("live", a.cql))
                want = (set() if res.features is None
                        else set(res.features.fids.decode()))
                assert a.matched == want, (
                    f"batch {b}: {a.cql!r} lanes != one-shot")
        ev = mgrs[0].evaluator.stats()
        assert ev["lane_dispatches"] > base.get("lane_dispatches", 0)
        lanes = mgrs[0].stats()["lanes"]
        assert lanes["enabled"]
        assert lanes["classes"]["bbox"]["rows"] == 2  # churned 3 -> 2
        assert lanes["classes"]["dwithin"]["rows"] == 2
        assert lanes["classes"]["polygon"]["rows"] == 1
        assert lanes["ineligible"] == {"non_spatial": 1}
        fused = mgrs[1].stats()["lanes"]
        assert not fused["enabled"] and fused["classes"] == {}
        for m in mgrs:
            m.close()

    def test_bucket_growth_compiles_once_then_zero_recompiles(self):
        """JitTracker over engine/lanes.py: the [S]-bucket compiles at
        most once per pow2 capacity; register/cancel churn WITHIN a
        bucket is a row write with ZERO recompiles."""
        from geomesa_tpu.analysis.runtime import (
            acquire_engine_tracker, release_engine_tracker)

        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(store,
                                  SubscribeConfig(max_subscriptions=64))
        # 96 fids -> a 128-row delta bucket no other test compiles, so
        # the per-[S]-bucket compile counts below are exact, not
        # best-effort against a warm process-wide jit cache
        fids = [f"f{i}" for i in range(96)]
        tracker, _ = acquire_engine_tracker(
            modules=["geomesa_tpu.engine.lanes"])
        try:
            def compiles():
                return tracker.recompiles.get("lanes.lane_bbox", 0)

            def boxes(seed, k):
                rng = np.random.default_rng(seed)
                out = []
                for _ in range(k):
                    x0 = float(rng.uniform(-60, 20))
                    y0 = float(rng.uniform(-30, 5))
                    out.append(mgr.subscribe(
                        "live",
                        f"BBOX(geom, {x0}, {y0}, {x0 + 8}, {y0 + 6})"))
                return out

            subs = boxes(1, 8)  # fills the smallest [8]-row bucket
            store.write("live", _rows(1, fids))
            store.poll("live")
            mgr.flush(lambda _f: None)
            assert compiles() == 1, "first [S=8] bucket must compile once"
            # churn WITHIN the bucket: cancel + register recycle rows
            for i in range(5):
                mgr.unsubscribe(subs[i].sub_id)
                subs.append(boxes(100 + i, 1)[0])
                store.write("live", _rows(10 + i, fids))
                store.poll("live")
                mgr.flush(lambda _f: None)
            assert compiles() == 1, (
                "register/cancel churn within an [S] bucket recompiled "
                f"the lane kernel ({tracker.report()})")
            # growth past capacity: exactly one more compile ([16])
            subs += boxes(2, 6)
            store.write("live", _rows(20, fids))
            store.poll("live")
            mgr.flush(lambda _f: None)
            assert compiles() == 2, "bucket growth must compile exactly once"
            for i in range(5, 8):
                mgr.unsubscribe(subs[i].sub_id)
                boxes(200 + i, 1)
                store.write("live", _rows(30 + i, fids))
                store.poll("live")
                mgr.flush(lambda _f: None)
            assert compiles() == 2, (
                "churn within the grown bucket recompiled "
                f"({tracker.report()})")
        finally:
            release_engine_tracker(tracker)
            mgr.close()

    def test_ten_thousand_geofences_bounded_dispatches(self):
        """The acceptance bound: 10^4 same-class registered geofences
        evaluate per poll in <=4 device dispatches (one [S]-batched
        bbox-lane dispatch), with matched sets equal to one-shot
        planner queries on a sample."""
        S = 10_000
        store = KafkaDataStore()
        store.create_schema(SFT)
        mgr = SubscriptionManager(
            store, SubscribeConfig(max_subscriptions=S + 8))
        rng = np.random.default_rng(11)
        subs = []
        for _ in range(S):
            x0 = float(rng.uniform(-60, 26))
            y0 = float(rng.uniform(-30, 8))
            subs.append(mgr.subscribe(
                "live",
                f"BBOX(geom, {x0:.4f}, {y0:.4f}, "
                f"{x0 + 2:.4f}, {y0 + 2:.4f})"))
        fids = [f"f{i}" for i in range(N_FIDS)]
        base = mgr.evaluator.stats()
        store.write("live", _rows(5, fids))
        store.poll("live")
        mgr.flush(lambda _f: None)
        ev = mgr.evaluator.stats()
        assert ev["dispatches"] - base["dispatches"] <= 4, (
            "10^4 same-class geofences must evaluate in <=4 dispatches")
        assert ev["lane_dispatches"] - base.get("lane_dispatches", 0) == 1
        lanes = mgr.stats()["lanes"]
        assert lanes["classes"]["bbox"]["rows"] == S
        assert lanes["ineligible"] == {}
        src = store.get_feature_source("live")
        for sub in [subs[i] for i in (0, 17, 4096, 9999)]:
            res = src.get_features(Query("live", sub.cql))
            want = (set() if res.features is None
                    else set(res.features.fids.decode()))
            assert sub.matched == want, f"{sub.cql!r} lane != one-shot"
        mgr.close()

    def test_lane_floor_at_1024(self):
        """The >=10x events/s acceptance floor at S=1024 on CPU CI:
        both legs run the identical register-before-seed protocol with
        the first (compiling) poll inside the measured window — the
        fused slot path pays an S-proportional trace+compile there,
        the lane path one S-independent batched kernel. Churn is
        excluded to keep the fused leg to a single compile inside the
        tier-1 budget; the zero-recompile churn contract is
        JitTracker-asserted above."""
        S = 1024
        fids = [f"f{i}" for i in range(N_FIDS)]

        def leg(lanes: bool) -> dict:
            # S same-class bbox geofences registered BEFORE the seed
            # batch lands, then the first poll and one steady poll
            store = KafkaDataStore()
            store.create_schema(SFT)
            mgr = SubscriptionManager(store, SubscribeConfig(
                max_subscriptions=S + 8, lanes=lanes))
            rng = np.random.default_rng(5)
            for _ in range(S):
                x0 = float(rng.uniform(-60.0, 28.0))
                y0 = float(rng.uniform(-30.0, 9.0))
                mgr.subscribe(
                    "live", f"BBOX(geom, {x0}, {y0}, {x0 + 2}, {y0 + 2})")
            store.write("live", _rows(600 + 10_001, fids))
            frames = []
            base = mgr.evaluator.stats()
            t0 = time.monotonic()
            mgr.poll_now()
            mgr.flush(frames.append)
            store.write("live", _rows(600, fids))
            mgr.poll_now()
            mgr.flush(frames.append)
            wall = time.monotonic() - t0
            ev = mgr.evaluator.stats()
            mgr.close()
            events = sum(len(f.get("fids", ())) for f in frames
                         if f.get("event") in ("enter", "exit"))
            polls = 2
            return {
                "polls": polls, "events_total": events,
                "events_per_s": events / wall,
                "dispatches_per_poll": (
                    ev.get("dispatches", 0)
                    - base.get("dispatches", 0)) / polls,
                "lane_dispatches": ev.get("lane_dispatches", 0)
                - base.get("lane_dispatches", 0),
            }

        lanes, fused = leg(True), leg(False)
        # the speedup must not be bought with dropped events
        assert lanes["events_total"] == fused["events_total"] > 0
        speedup = lanes["events_per_s"] / fused["events_per_s"]
        assert speedup >= 10.0, (
            f"lane floor missed: {speedup}x "
            f"(lanes {lanes['events_per_s']}/s vs fused "
            f"{fused['events_per_s']}/s)")
        assert lanes["dispatches_per_poll"] <= 4.0
        assert lanes["lane_dispatches"] == lanes["polls"]


class TestHandoff:
    """Matched-set handoff on failover (docs/ROBUSTNESS.md): a standing
    query re-homes onto a survivor replica via handoff_snapshot ->
    subscribe(handoff=...), continuing the client's sequence numbers
    with a state resync frame instead of starting over."""

    CQL = "BBOX(geom, -20, -15, 25, 20)"

    def test_handoff_round_trip(self):
        store = KafkaDataStore()
        store.create_schema(SFT)
        a = SubscriptionManager(store)
        sub = a.subscribe("live", self.CQL)
        log_a = _EventLog()
        fids = [f"f{i}" for i in range(24)]
        store.write("live", _rows(1, fids))
        store.poll("live")
        a.flush(log_a.push)
        matched = set(sub.matched)
        snap = sub.handoff_snapshot()
        assert snap["type"] == "live"
        assert set(snap["matched"]) == matched
        # drained outbox: everything stamped was delivered
        assert snap["watermark"] == snap["seq"]
        # the old replica dies AFTER exporting
        a.close()
        b = SubscriptionManager(store)
        # acceptor validation: the handoff must describe THIS predicate
        with pytest.raises(ValueError):
            b.subscribe("live", "BBOX(geom, 0, 0, 1, 1)", handoff=snap)
        sub2 = b.subscribe("live", self.CQL, handoff=snap)
        log_b = _EventLog()
        b.flush(log_b.push)
        states = [f for f in log_b.frames if f.get("event") == "state"]
        assert states, "handoff acceptance must answer a state resync"
        # sequence numbers CONTINUE from the delivered watermark: the
        # resync frame is the next seq the client sees
        assert states[0]["seq"] == snap["watermark"] + 1
        assert set(states[0]["fids"]) == matched
        assert log_b.replay_matched(sub2.sub_id) == matched
        # and the re-homed query keeps flowing with one-shot parity
        store.write("live", _rows(2, fids))
        store.poll("live")
        b.flush(log_b.push)
        src = store.get_feature_source("live")
        res = src.get_features(Query("live", self.CQL))
        want = (set() if res.features is None
                else set(res.features.fids.decode()))
        assert sub2.matched == want
        assert log_b.replay_matched(sub2.sub_id) == want
        # density grids never hand off: replica-local float state
        with pytest.raises(ValueError):
            b.subscribe("live", density=DensityWindow(
                (-60.0, -30.0, 60.0, 30.0), 8, 4), handoff=snap)
        dens = b.subscribe("live", density=DensityWindow(
            (-60.0, -30.0, 60.0, 30.0), 8, 4))
        with pytest.raises(ValueError):
            dens.handoff_snapshot()
        b.close()

    def test_wire_export_subscription(self):
        """The export_subscription verb round-trips the snapshot over
        the JSON-lines wire and a re-subscribe WITH it answers the
        state resync frame on the new session."""
        from geomesa_tpu.serve.protocol import serve_lines
        from geomesa_tpu.serve.service import ServeConfig

        store = KafkaDataStore()
        store.create_schema(SFT)
        fids = [f"f{i}" for i in range(12)]
        store.write("live", _rows(1, fids))
        out = []
        sid = {}

        def lines_a():
            yield json.dumps({"id": "s1", "op": "subscribe",
                              "typeName": "live", "cql": self.CQL})
            yield json.dumps({"id": "p1", "op": "poll"})
            yield json.dumps({"id": "x1", "op": "export_subscription",
                              "subscription": "PLACEHOLDER"})
            yield json.dumps({"id": "x2", "op": "export_subscription",
                              "subscription": "sub-999999"})

        # two-pass: the export needs the real sub id from the ack
        def lines_resolved():
            for ln in lines_a():
                doc = json.loads(ln)
                if doc.get("subscription") == "PLACEHOLDER":
                    doc["subscription"] = sid["v"]
                    ln = json.dumps(doc)
                yield ln
                if doc["id"] == "s1":
                    got = [json.loads(s) for s in out]
                    sid["v"] = next(d["subscription"] for d in got
                                    if d.get("id") == "s1")

        serve_lines(store, lines_resolved(), out.append,
                    ServeConfig(pipeline=False))
        docs = [json.loads(s) for s in out]
        by_id = {d["id"]: d for d in docs if "id" in d}
        assert by_id["x1"]["ok"], by_id["x1"]
        snap = by_id["x1"]["handoff"]
        assert snap["type"] == "live" and snap["cql"] == self.CQL
        assert snap["matched"] and snap["watermark"] >= 1
        assert by_id["x2"]["ok"] is False
        assert by_id["x2"]["message"] == "no such subscription"
        # the snapshot is pure JSON: accepted verbatim on a NEW session
        out_b = []

        def lines_b():
            yield json.dumps({"id": "s2", "op": "subscribe",
                              "typeName": "live", "cql": self.CQL,
                              "handoff": snap})

        serve_lines(store, lines_b(), out_b.append,
                    ServeConfig(pipeline=False))
        docs_b = [json.loads(s) for s in out_b]
        state = [d for d in docs_b if d.get("event") == "state"]
        assert state and state[0]["seq"] == snap["watermark"] + 1
        assert set(state[0]["fids"]) == set(snap["matched"])
