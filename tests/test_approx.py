"""geomesa_tpu.approx: the approximate-answer tier + result cache.

The load-bearing suite is TestParity: 20 mixed workload batches —
writes between queries included, so version invalidation is OBSERVED —
where every sketch-served answer's reported bound must contain the
exact device answer, repeated exact queries are bit-identical cache
hits, and the sketch path compiles nothing. TestClosedLoopSlo drives
the exactness-budget governor end to end: budget exhaustion measurably
shifts traffic to the exact path (no silent accuracy loss).

Wall-clock discipline (tier-1 budget): one module store, a fixed small
CQL set (filter compiles amortize across tests), pure-numpy bound-math
fuzzing where no device is needed.
"""

import time

import numpy as np
import pytest

from geomesa_tpu.approx import (
    ApproxCount, PartitionSketchStore, ResultCache, entry_token,
    merge_count_bounds, resample_bounds, result_key, topk_cell_bounds)
from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.cql.extract import BBox, Interval
from geomesa_tpu.plan.hints import QueryHints
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve.scheduler import ServeRequest
from geomesa_tpu.serve.service import QueryService, ServeConfig

SFT_SPEC = "name:String,score:Double,dtg:Date,*geom:Point"

CQLS = [
    "BBOX(geom, -180, -90, 180, 90)",
    "BBOX(geom, -60, -30, 60, 30)",
    "BBOX(geom, 0, 0, 90, 45)",
]

T0, T1 = 1_590_000_000_000, 1_600_000_000_000


def _batch(sft, seed, n, narrow_dtg=False):
    rng = np.random.default_rng(seed)
    # narrow_dtg: confine the write to ~one weekly partition so the
    # incremental-write tests pay one partition's sketch rebuild + a
    # small residency delta, not a full-store churn (wall budget)
    dtg = (rng.integers(T0, T0 + 6 * 86_400_000, n) if narrow_dtg
           else rng.integers(T0, T1, n))
    return FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": dtg,
        "geom": np.stack([rng.uniform(-170, 170, n),
                          rng.uniform(-80, 80, n)], 1),
    })


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    from geomesa_tpu.plan.datastore import DataStore

    sft = SimpleFeatureType.from_spec("apx", SFT_SPEC)
    ds = DataStore(str(tmp_path_factory.mktemp("approx")),
                   use_device_cache=True)
    src = ds.create_schema(sft)
    src.write(_batch(sft, 1, 4096))
    return ds


@pytest.fixture(scope="module")
def wide_store(tmp_path_factory):
    """2^16 rows: a warm exact count there does device work beyond its
    fixed per-call host cost (~1 ms on a CPU, which alone is all of an
    exact count over `store`'s 4096 rows)."""
    from geomesa_tpu.plan.datastore import DataStore

    sft = SimpleFeatureType.from_spec("apx", SFT_SPEC)
    ds = DataStore(str(tmp_path_factory.mktemp("approx_wide")),
                   use_device_cache=True)
    ds.create_schema(sft).write(_batch(sft, 1, 1 << 16))
    return ds


# -- pure bound math (no device) --------------------------------------------


class TestBoundMath:
    """Deterministic-interval guarantees fuzzed against brute force:
    the whole tier's honesty rests on these brackets."""

    def _sketch_store(self, tmp_path, n=3000, seed=0):
        from geomesa_tpu.plan.datastore import DataStore

        sft = SimpleFeatureType.from_spec("bm", SFT_SPEC)
        ds = DataStore(str(tmp_path), use_device_cache=False)
        src = ds.create_schema(sft)
        rng = np.random.default_rng(seed)
        xs = rng.uniform(-179, 179, n)
        ys = rng.uniform(-89, 89, n)
        ts = rng.integers(T0, T1, n)
        src.write(FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "score": rng.uniform(-1, 1, n),
            "dtg": ts,
            "geom": np.stack([xs, ys], 1),
        }))
        storage = src.storage
        pstore = PartitionSketchStore(storage)
        snap = storage.manifest_snapshot()
        sketches = [pstore.build(name, snap[name]) for name in snap]
        return xs, ys, ts, sketches

    def test_count_bounds_bracket_brute_force(self, tmp_path):
        xs, ys, ts, sketches = self._sketch_store(tmp_path)
        rng = np.random.default_rng(42)
        for i in range(40):
            x0, x1 = sorted(rng.uniform(-185, 185, 2))
            y0, y1 = sorted(rng.uniform(-95, 95, 2))
            if rng.random() < 0.3:
                interval = Interval(None, None)
            else:
                a, b = sorted(rng.integers(T0, T1, 2))
                interval = Interval(int(a), int(b))
            truth = np.sum(
                (xs >= x0) & (xs <= x1) & (ys >= y0) & (ys <= y1)
                & ((interval.start is None)
                   | (ts >= (interval.start or 0)))
                & ((interval.end is None) | (ts <= (interval.end or 0))))
            lo, hi = merge_count_bounds(
                sketches, BBox(x0, y0, x1, y1), interval)
            assert lo <= truth <= hi, (i, lo, truth, hi)

    def test_resample_bound_holds_per_cell(self, tmp_path):
        xs, ys, ts, sketches = self._sketch_store(tmp_path, seed=3)
        from geomesa_tpu.approx.sketches import merge_region

        sure, maybe, b = merge_region(sketches, Interval(None, None))
        rng = np.random.default_rng(7)
        for _ in range(8):
            x0, x1 = sorted(rng.uniform(-150, 150, 2))
            y0, y1 = sorted(rng.uniform(-70, 70, 2))
            if x1 - x0 < 20 or y1 - y0 < 10:
                continue
            w, h = int(rng.integers(4, 14)), int(rng.integers(3, 9))
            grid, bound = resample_bounds(sure, maybe, (x0, y0, x1, y1),
                                          w, h)
            # brute-force truth grid with the same floor binning
            dx, dy = (x1 - x0) / w, (y1 - y0) / h
            col = np.floor((xs - x0) / dx).astype(int)
            row = np.floor((ys - y0) / dy).astype(int)
            inb = (col >= 0) & (col < w) & (row >= 0) & (row < h)
            truth = np.zeros((h, w))
            np.add.at(truth, (row[inb], col[inb]), 1.0)
            assert np.abs(grid - truth).max() <= bound + 1e-9

    def test_topk_cells_bracket_brute_force(self, tmp_path):
        xs, ys, ts, sketches = self._sketch_store(tmp_path, seed=5)
        from geomesa_tpu.approx.sketches import merge_region

        sure, maybe, b = merge_region(sketches, Interval(None, None))
        bbox = BBox(-60, -30, 60, 30)
        cells = topk_cell_bounds(sure, maybe, bbox, 10)
        assert cells
        sel = (xs >= bbox.xmin) & (xs <= bbox.xmax) \
            & (ys >= bbox.ymin) & (ys <= bbox.ymax)
        cx = np.clip(((xs + 180.0) / 360.0 * b).astype(int), 0, b - 1)
        cy = np.clip(((ys + 90.0) / 180.0 * b).astype(int), 0, b - 1)
        for c in cells:
            truth = int(np.sum(sel & (cx == c["col"]) & (cy == c["row"])))
            assert abs(c["count"] - truth) <= c["bound"], (c, truth)

    def test_entry_token_moves_with_writes(self, store):
        storage = store.get_feature_source("apx").storage
        snap = storage.manifest_snapshot()
        name = next(iter(snap))
        assert entry_token(snap[name]) == entry_token(snap[name])
        assert entry_token(snap[name]) != entry_token(
            snap[name] + [{"file": "x", "count": 1}])


# -- parity over mixed batches (device exact vs sketch) ----------------------


class TestParity:
    def test_bounds_contain_exact_across_20_batches(self, store):
        """20 mixed workload batches, writes between queries: every
        sketch answer's bound contains the exact device answer, the
        version moves are OBSERVED (post-write answers track the new
        data), and the sketch path compiles nothing."""
        from geomesa_tpu.analysis.runtime import (
            acquire_engine_tracker, release_engine_tracker)

        src = store.get_feature_source("apx")
        pl = src.planner
        sft = src.sft
        interval_cql = (
            "BBOX(geom, -90, -45, 90, 45) AND dtg DURING "
            "2020-05-25T00:00:00Z/2020-08-01T00:00:00Z")
        cqls = CQLS + [interval_cql]
        # warm the exact path once (filter compiles + device cache)
        for cql in cqls:
            pl.count(Query("apx", cql))
        tracker, _ = acquire_engine_tracker()
        try:
            base_recompiles = tracker.total_recompiles()
            sketch_served = 0
            verified = 0
            version_changes = 0
            last = {}
            for i in range(20):
                if i % 5 == 4:
                    src.write(_batch(sft, 100 + i, 256,
                                     narrow_dtg=True))
                # the whole tolerant workload serves every batch; the
                # exact device verification rotates (wall budget) —
                # every cql is verified against exact several times,
                # including right after each write
                verify_cql = cqls[i % len(cqls)]
                for cql in cqls:
                    a = pl.count(Query(
                        "apx", cql, hints=QueryHints(tolerance=0.25)))
                    if not isinstance(a, ApproxCount):
                        continue
                    sketch_served += 1
                    assert a.confidence == 1.0
                    if cql == verify_cql:
                        exact = pl.count(Query("apx", cql))
                        verified += 1
                        assert abs(int(a) - exact) <= a.bound, (
                            i, cql, int(a), a.bound, exact)
                        if cql in last and last[cql] != (int(a), exact):
                            version_changes += 1
                        last[cql] = (int(a), exact)
            assert sketch_served >= 40  # the tier actually served
            assert verified >= 12       # bound-vs-exact, incl. post-write
            assert version_changes > 0  # invalidation observed
            # zero-recompile: the sketch path never touches the device
            assert tracker.total_recompiles() == base_recompiles
        finally:
            release_engine_tracker(tracker)

    def test_density_and_topk_parity(self, store):
        pl = store.get_feature_source("apx").planner
        dh = QueryHints(tolerance=0.5,
                        density_bbox=(-60.0, -30.0, 60.0, 30.0),
                        density_width=12, density_height=6)
        r = pl.execute(Query("apx", CQLS[1], hints=dh))
        re_ = pl.execute(Query("apx", CQLS[1], hints=QueryHints(
            density_bbox=(-60.0, -30.0, 60.0, 30.0),
            density_width=12, density_height=6)))
        assert r.approx and not re_.approx
        assert np.abs(np.asarray(r.grid)
                      - np.asarray(re_.grid)).max() <= r.bound + 1e-9
        rt = pl.execute(Query("apx", CQLS[1],
                              hints=QueryHints(tolerance=1.0,
                                               topk_cells=5)))
        rte = pl.execute(Query("apx", CQLS[1],
                               hints=QueryHints(topk_cells=5)))
        assert rt.approx and rt.kind == "topk_cells"
        assert not rte.approx and rte.kind == "topk_cells"
        exact_by_cell = {(c["row"], c["col"]): c["count"]
                         for c in rte.stats}
        for c in rt.stats:
            truth = exact_by_cell.get((c["row"], c["col"]))
            if truth is not None:
                assert abs(c["count"] - truth) <= c["bound"]

    def test_sketch_p50_speedup_over_exact(self, wide_store):
        """The headline: warm tolerant counts vs warm exact device
        counts — asserted at a conservative 25x."""
        pl = wide_store.get_feature_source("apx").planner
        qa = Query("apx", CQLS[1], hints=QueryHints(tolerance=0.25))
        qe = Query("apx", CQLS[1])
        assert isinstance(pl.count(qa), ApproxCount)  # warm + eligible
        pl.count(qe)

        def p50(q, reps=15):
            ts = []
            for _ in range(reps):
                t = time.perf_counter()
                pl.count(q)
                ts.append(time.perf_counter() - t)
            return float(np.percentile(ts, 50))

        a, e = p50(qa), p50(qe)
        assert e / a >= 25.0, f"sketch p50 {a*1e3:.3f}ms vs exact " \
                              f"{e*1e3:.3f}ms = {e/a:.1f}x"

    def test_ineligible_filters_route_exact(self, store):
        pl = store.get_feature_source("apx").planner
        for cql in ("name = 'a'",
                    "BBOX(geom, -60, -30, 60, 30) AND score > 0",
                    "BBOX(geom,-10,-10,10,10) OR BBOX(geom,20,20,30,30)"):
            a = pl.count(Query("apx", cql, hints=QueryHints(tolerance=0.5)))
            assert not isinstance(a, ApproxCount), cql
            assert a == pl.count(Query("apx", cql))


# -- stale-sketch fallthrough (the torn-merge fix) ---------------------------


class TestStaleFallthrough:
    def test_version_mismatch_never_serves(self, store):
        """A sketch built at version V is NEVER merged at V+1: the
        typed StaleSketch fallthrough routes exact (metered) instead
        of a torn merge — the satellite fix for the stats_manager's
        lazy-rebuild race."""
        src = store.get_feature_source("apx")
        pl = src.planner
        eng = pl.approx_engine()
        q = Query("apx", CQLS[1], hints=QueryHints(tolerance=0.25))
        assert isinstance(pl.count(q), ApproxCount)
        src.write(_batch(src.sft, 999, 128, narrow_dtg=True))  # version moves
        storage = src.storage
        snap = storage.manifest_snapshot()
        # every partition the write touched: the cached sketch's token
        # no longer matches -> get() refuses
        stale = [name for name in snap
                 if eng.store.get(name, snap[name]) is None]
        assert stale, "the write must have invalidated some partition"
        # with builds disabled the engine must fall through TYPED
        eng.allow_build = False
        try:
            a = pl.count(q)
            assert not isinstance(a, ApproxCount)
            assert eng.last_reason == "stale_sketch"
            assert a == pl.count(Query("apx", CQLS[1]))  # exact answer
        finally:
            eng.allow_build = True
        # builds re-enabled: version-exact again, bound contains exact
        a2 = pl.count(q)
        assert isinstance(a2, ApproxCount)
        assert abs(int(a2) - pl.count(Query("apx", CQLS[1]))) <= a2.bound


# -- sidecar persistence ------------------------------------------------------


class TestSidecarPersistence:
    """The manifest-versioned sketch sidecar (ROADMAP item 2 remaining
    rung): a second process loads version-exact sketches from disk
    instead of re-scanning partitions; a stale entry is a typed
    skip-and-rebuild, never a torn load."""

    def test_warm_spinup_answers_without_builds(self, tmp_path):
        import os

        from geomesa_tpu.plan.datastore import DataStore

        sft = SimpleFeatureType.from_spec("apx", SFT_SPEC)
        root = str(tmp_path / "cat")
        ds = DataStore(root, use_device_cache=True)
        src = ds.create_schema(sft)
        src.write(_batch(sft, 21, 1024))
        q = Query("apx", CQLS[1], hints=QueryHints(tolerance=0.25))
        pl = src.planner
        a1 = pl.count(q)
        assert isinstance(a1, ApproxCount)
        eng1 = pl.approx_engine()
        assert os.path.exists(eng1.store.sidecar_path)

        # "replica spin-up": a fresh process-equivalent store over the
        # same catalog — the sidecar pre-installs every sketch, so the
        # first tolerant answer runs ZERO partition builds
        ds2 = DataStore(root, use_device_cache=True)
        pl2 = ds2.get_feature_source("apx").planner
        eng2 = pl2.approx_engine()
        st = eng2.store.stats()
        assert st["sidecar_loaded"] >= 1 and st["sidecar_stale"] == 0
        # every partition already serves version-exact from the loaded
        # sidecar — the first tolerant answer needs ZERO builds
        snap = pl2.storage.manifest_snapshot()
        assert all(eng2.store.get(n, snap[n]) is not None for n in snap)
        eng2.allow_build = False  # a build attempt would now raise/route exact
        try:
            a2 = pl2.count(q)
        finally:
            eng2.allow_build = True
        assert isinstance(a2, ApproxCount)
        assert int(a2) == int(a1) and a2.bound == a1.bound

    def test_stale_sidecar_is_typed_rebuild(self, tmp_path):
        from geomesa_tpu.plan.datastore import DataStore

        sft = SimpleFeatureType.from_spec("apx", SFT_SPEC)
        root = str(tmp_path / "cat")
        ds = DataStore(root, use_device_cache=True)
        src = ds.create_schema(sft)
        src.write(_batch(sft, 31, 512, narrow_dtg=True))
        q = Query("apx", CQLS[0], hints=QueryHints(tolerance=0.25))
        pl = src.planner
        assert isinstance(pl.count(q), ApproxCount)
        # the write happens AFTER the sidecar was persisted: its
        # token no longer matches the committed manifest
        src.write(_batch(sft, 32, 256, narrow_dtg=True))

        ds2 = DataStore(root, use_device_cache=True)
        src2 = ds2.get_feature_source("apx")
        eng2 = src2.planner.approx_engine()
        st = eng2.store.stats()
        assert st["sidecar_stale"] >= 1  # never installed torn
        # the stale partition rebuilds from a pinned read on first use;
        # the answer stays bound-correct against the exact count
        a = src2.planner.count(q)
        assert isinstance(a, ApproxCount)
        exact = src2.planner.count(Query("apx", CQLS[0]))
        assert abs(int(a) - int(exact)) <= a.bound


# -- result cache ------------------------------------------------------------


class TestResultCache:
    def test_lru_hit_miss_evict(self):
        c = ResultCache(max_entries=2)
        k1 = ("count", "t", "CQL1", "h", None, 1)
        k2 = ("count", "t", "CQL2", "h", None, 1)
        k3 = ("count", "t", "CQL3", "h", None, 1)
        assert c.get(k1) == (False, None)
        c.put(k1, 11)
        c.put(k2, 22)
        assert c.get(k1) == (True, 11)
        c.put(k3, 33)  # evicts k2 (k1 was touched more recently)
        assert c.get(k2) == (False, None)
        s = c.stats()
        assert s["hits"] == 1 and s["misses"] == 2 and s["evictions"] == 1

    def test_result_key_canonicalizes_and_gates(self):
        qa = Query("t", "BBOX(geom, 0,0, 10, 10)  AND name = 'a'")
        qb = Query("t", "BBOX(geom,0,0,10,10) AND name='a'")
        assert result_key("count", qa, 7) == result_key("count", qb, 7)
        assert result_key("count", qa, 7) != result_key("count", qa, 8)
        assert result_key("knn", qa, 7) is None
        assert result_key("count", qa, None) is None
        qt = Query("t", "INCLUDE", hints=QueryHints(tolerance=0.1))
        assert result_key("count", qt, 7) is None

    def test_serve_cache_bit_identical_and_version_exact(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            r1 = svc.query("apx", CQLS[1]).result(timeout=300)
            r2 = svc.query("apx", CQLS[1]).result(timeout=300)
            assert r2 is r1  # bit-identical by object identity
            st = svc.stats()
            assert st["cache"]["hits"] >= 1
            assert st["approx"]["tiers"]["cached"] >= 1
            # a write bumps the version: the next run recomputes
            src = store.get_feature_source("apx")
            src.write(_batch(src.sft, 500, 64, narrow_dtg=True))
            r3 = svc.query("apx", CQLS[1]).result(timeout=300)
            assert r3 is not r1
        finally:
            svc.close(drain=True)


# -- serve tier + closed-loop SLO governor -----------------------------------


class TestServeTier:
    def test_admission_resolution_and_attribution(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            base = svc.stats()
            req = ServeRequest(kind="count", query=Query(
                "apx", CQLS[1], hints=QueryHints(tolerance=0.25)))
            got = svc.submit(req).result(timeout=300)
            assert isinstance(got, ApproxCount) and req.approx
            st = svc.stats()
            assert st["approx"]["tiers"]["sketch"] >= \
                base["approx"]["tiers"]["sketch"] + 1
            evs = [e for e in store.audit.snapshot()
                   if getattr(e, "approx", False)]
            assert evs and evs[-1].kind == "count"
        finally:
            svc.close(drain=True)

    def test_wire_carries_bound_and_cached(self, store):
        import json as _json

        from geomesa_tpu.serve.protocol import serve_lines

        out = []

        def lines():
            yield _json.dumps({"id": "a1", "op": "count",
                               "typeName": "apx", "cql": CQLS[1],
                               "tolerance": 0.25})
            yield _json.dumps({"id": "e1", "op": "count",
                               "typeName": "apx", "cql": CQLS[1]})
            # e2 is the dashboard's REPEAT: it must arrive after e1
            # resolved (else the batcher dedups them into one window
            # and the cache never comes into play)
            deadline = time.monotonic() + 60
            while not any(_json.loads(d)["id"] == "e1" for d in out):
                assert time.monotonic() < deadline
                time.sleep(0.005)
            yield _json.dumps({"id": "e2", "op": "count",
                               "typeName": "apx", "cql": CQLS[1]})

        serve_lines(store, lines(), out.append,
                    ServeConfig(max_wait_ms=0.0))
        docs = {d["id"]: d for d in map(_json.loads, out)}
        assert docs["a1"]["approx"] is True
        assert docs["a1"]["confidence"] == 1.0
        exact = docs["e1"]["count"]
        assert abs(docs["a1"]["count"] - exact) <= docs["a1"]["bound"]
        assert "approx" not in docs["e1"]
        assert docs["e2"]["count"] == exact
        assert docs["e2"].get("cached") is True

    def test_closed_loop_exactness_budget(self, store):
        """Budget spent => MORE traffic to the exact path: tolerant
        counts serve from sketches (each spends exactness budget)
        until the budget is gone, after which the SAME tolerant
        request is served EXACT — never silently less accurate."""
        from geomesa_tpu.telemetry.slo import SloEngine, SloSpec

        now = [1000.0]
        spec = SloSpec.from_dict({
            "slo": {"fast_window_s": 5.0, "slow_window_s": 10.0,
                    "burn_threshold": 1.5},
            "objective": {
                "exactness": {"kind": "exactness", "goal": 0.9,
                              "degrade": True, "min_count": 4},
            },
        })
        engine = SloEngine(spec, clock=lambda: now[0])
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0,
                                              slo=engine))
        try:
            q = Query("apx", CQLS[1], hints=QueryHints(tolerance=0.25))
            approx_phase = 0
            exact_phase = 0
            for i in range(10):
                req = ServeRequest(kind="count", query=Query(
                    "apx", CQLS[1], hints=QueryHints(tolerance=0.25)))
                got = svc.submit(req).result(timeout=300)
                if isinstance(got, ApproxCount):
                    approx_phase += 1
                else:
                    exact_phase += 1
                now[0] += 0.2
            # the first requests served approx and burned the budget;
            # once spent, tolerance is stripped at admission and the
            # tail of the workload is exact
            assert approx_phase >= 4
            assert exact_phase >= 1
            assert svc.stats()["approx_budget_exact"] >= 1
            assert not svc._approx_ok()
            # recovery: the degraded observations age out of the
            # budget window and sketch serving resumes
            now[0] += 30.0
            req = ServeRequest(kind="count", query=q)
            got = svc.submit(req).result(timeout=300)
            assert isinstance(got, ApproxCount)
        finally:
            svc.close(drain=True)

    def test_degrade_ladder_sketch_rung(self, store):
        # warm the sketches at the CURRENT version first: the
        # admission peek never builds (builds belong to the dispatch
        # thread), so the rung resolves at submit only when warm
        pl = store.get_feature_source("apx").planner
        assert isinstance(
            pl.count(Query("apx", CQLS[1],
                           hints=QueryHints(tolerance=0.5))),
            ApproxCount)
        cfg = ServeConfig(max_queue=4, degrade=True,
                          degrade_watermark=0.25, shed_watermark=0.9,
                          max_wait_ms=0.0,
                          approx_degrade_tolerance=0.5)
        svc = QueryService(store, cfg, autostart=False)
        try:
            svc.count("apx", "score > 1")  # queue occupancy
            req = svc._request("count", Query("apx", CQLS[1]),
                               allow_degraded=True)
            fut = svc.submit(req)
            # sketch-eligible filter: the FIRST rung is the sketch
            # tier, not loose-bbox, and it resolved AT ADMISSION with
            # a typed bound — degraded accounting lands WITH the serve
            assert req.sketch_rung == 1
            assert req.query.hints.tolerance == \
                cfg.approx_degrade_tolerance
            assert not req.query.hints.loose_bbox
            assert fut.done()
            assert isinstance(fut.result(), ApproxCount)
            assert req.degraded
            # an INELIGIBLE filter under the same ladder keeps the
            # legacy loose-bbox rewrite (shedding lever preserved)
            req2 = svc._request("count",
                                Query("apx", "name = 'a'"),
                                allow_degraded=True)
            svc._degrade(req2, 1)
            assert req2.sketch_rung == 0
            assert req2.degraded and req2.query.hints.loose_bbox
        finally:
            svc.start()
            svc.close(drain=True)


# -- approximate density subscriptions ---------------------------------------

class TestApproxDensitySubscribe:
    def test_frames_bound_and_zero_dispatches(self):
        from geomesa_tpu.kafka.store import KafkaDataStore
        from geomesa_tpu.subscribe import (
            DensityWindow, SubscriptionManager)

        sft = SimpleFeatureType.from_spec("alive", SFT_SPEC)
        kstore = KafkaDataStore()
        kstore.create_schema(sft)
        mgr = SubscriptionManager(kstore)
        w = (-60.0, -30.0, 60.0, 30.0)
        sa = mgr.subscribe("alive", density=DensityWindow(
            w, 12, 6, tolerance=0.5))
        se = mgr.subscribe("alive", density=DensityWindow(w, 12, 6))
        assert sa.mode == "approx_density" and se.mode == "density"
        fids = [f"f{i}" for i in range(40)]
        frames = []
        for i in range(6):
            rng = np.random.default_rng(300 + i)
            n = 24 + 2 * i
            kstore.write("alive", FeatureBatch.from_pydict(sft, {
                "name": rng.choice(["a", "b"], n).tolist(),
                "score": rng.uniform(-1, 1, n),
                "dtg": rng.integers(T0, T1, n),
                "geom": np.stack([rng.uniform(-55, 55, n),
                                  rng.uniform(-25, 25, n)], 1),
            }, fids=fids[:n]))
            kstore.poll("alive")
        mgr.flush(frames.append)
        af = [f for f in frames if f.get("event") == "approx_density"]
        assert af, "approx_density frames must flow"
        for f in af:
            assert f["approx"] is True and f["confidence"] == 1.0
            assert "bound" in f and "within_tolerance" in f
        # per-cell parity against the exact incremental grid
        assert np.abs(sa.grid - se.grid).max() <= af[-1]["bound"] + 1e-9

        # an approx-ONLY manager folds with ZERO device dispatches —
        # the thousand-subscriber fan-out stops paying per-poll device
        # work
        kstore2 = KafkaDataStore()
        kstore2.create_schema(sft)
        mgr2 = SubscriptionManager(kstore2)
        for j in range(3):
            mgr2.subscribe("alive", density=DensityWindow(
                w, 8, 4, tolerance=1.0))
        for i in range(4):
            rng = np.random.default_rng(900 + i)
            kstore2.write("alive", FeatureBatch.from_pydict(sft, {
                "name": ["a"] * 16,
                "score": rng.uniform(-1, 1, 16),
                "dtg": rng.integers(T0, T1, 16),
                "geom": np.stack([rng.uniform(-50, 50, 16),
                                  rng.uniform(-20, 20, 16)], 1),
            }, fids=fids[:16]))
            kstore2.poll("alive")
        assert mgr2.evaluator.stats()["dispatches"] == 0
        frames2 = []
        mgr2.flush(frames2.append)
        assert sum(1 for f in frames2
                   if f.get("event") == "approx_density") >= 3
        mgr.close()
        mgr2.close()

    def test_approx_window_rejects_weight_and_decay(self):
        from geomesa_tpu.subscribe import DensityWindow

        with pytest.raises(ValueError):
            DensityWindow((-1.0, -1.0, 1.0, 1.0), 4, 4, tolerance=0.1,
                          weight_attr="score")
        with pytest.raises(ValueError):
            DensityWindow((-1.0, -1.0, 1.0, 1.0), 4, 4, tolerance=0.1,
                          decay=0.5)


class TestDistinct:
    """DISTINCT counts (QueryHints.distinct, docs/SERVING.md
    "Approximate answers"): a tolerance hint resolves at admission from
    per-partition HyperLogLog sketches merged under the manifest
    snapshot with a typed [lo, hi] bound; without one (or with a
    predicate — the HLL path is Include-only) the answer pays an exact
    feature scan + host unique count."""

    @pytest.fixture(scope="class")
    def dstore(self, tmp_path_factory):
        from geomesa_tpu.plan.datastore import DataStore

        sft = SimpleFeatureType.from_spec("dst", SFT_SPEC)
        ds = DataStore(str(tmp_path_factory.mktemp("distinct")),
                       use_device_cache=True)
        src = ds.create_schema(sft)
        rng = np.random.default_rng(3)
        n = 4096
        names = [f"u{int(v)}" for v in rng.integers(0, 1500, n)]
        src.write(FeatureBatch.from_pydict(sft, {
            "name": names,
            "score": rng.uniform(-10, 10, n),
            "dtg": rng.integers(T0, T1, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1),
        }))
        return ds, len(set(names))

    def test_hll_resolve_within_bound(self, dstore):
        ds, truth = dstore
        planner = ds.get_feature_source("dst").planner
        res = planner.count_result(Query(
            "dst", "INCLUDE",
            hints=QueryHints(distinct="name", tolerance=0.1)))
        assert res.approx and res.bound > 0
        assert res.confidence == pytest.approx(0.99)
        assert abs(res.count - truth) <= res.bound, (
            f"HLL estimate {res.count} +/- {res.bound} missed exact "
            f"{truth}")
        # memoized under the manifest version: bit-identical repeat
        again = planner.count_result(Query(
            "dst", "INCLUDE",
            hints=QueryHints(distinct="name", tolerance=0.1)))
        assert (again.count, again.bound) == (res.count, res.bound)

    def test_exact_without_tolerance(self, dstore):
        ds, truth = dstore
        planner = ds.get_feature_source("dst").planner
        res = planner.count_result(Query(
            "dst", "INCLUDE", hints=QueryHints(distinct="name")))
        assert not getattr(res, "approx", False)
        assert res.count == truth

    def test_predicate_routes_exact(self, dstore):
        ds, _truth = dstore
        src = ds.get_feature_source("dst")
        cql = "BBOX(geom, -60, -30, 60, 30)"
        feats = src.get_features(Query("dst", cql)).features
        want = len(set(np.asarray(
            feats.columns["name"].decode(), dtype=object)))
        # tolerance offered, but the HLL tier is Include-only: the
        # filtered distinct must come back exact, never estimated
        res = src.planner.count_result(Query(
            "dst", cql, hints=QueryHints(distinct="name",
                                         tolerance=0.1)))
        assert not getattr(res, "approx", False)
        assert res.count == want

    def test_validation_is_typed(self, dstore):
        ds, _truth = dstore
        planner = ds.get_feature_source("dst").planner
        with pytest.raises(ValueError, match="not in schema"):
            planner.count_result(Query(
                "dst", "INCLUDE", hints=QueryHints(distinct="nosuch")))
        with pytest.raises(ValueError, match="geometry"):
            planner.count_result(Query(
                "dst", "INCLUDE", hints=QueryHints(distinct="geom")))

    def test_wire_carries_lo_hi(self, dstore):
        import json as _json

        from geomesa_tpu.serve.protocol import serve_lines

        ds, truth = dstore
        out = []

        def lines():
            yield _json.dumps({"id": "d1", "op": "count",
                               "typeName": "dst", "cql": "INCLUDE",
                               "distinct": "name", "tolerance": 0.1})
            yield _json.dumps({"id": "d2", "op": "count",
                               "typeName": "dst", "cql": "INCLUDE",
                               "distinct": "name"})

        serve_lines(ds, lines(), out.append, ServeConfig(pipeline=False))
        by_id = {d["id"]: d for d in map(_json.loads, out)}
        d1 = by_id["d1"]
        assert d1["ok"] and d1["approx"]
        # the typed bound rides the wire as a [lo, hi] interval that
        # must contain the exact answer
        assert d1["lo"] <= truth <= d1["hi"]
        assert d1["lo"] == max(0, d1["count"] - d1["bound"])
        assert d1["hi"] == d1["count"] + d1["bound"]
        d2 = by_id["d2"]
        assert d2["ok"] and d2["count"] == truth
        assert not d2.get("approx") and "lo" not in d2
