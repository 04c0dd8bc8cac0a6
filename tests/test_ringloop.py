"""Persistent on-device serve loop (serve/ringloop.py + planner ring
tier).

The load-bearing assertions, per the acceptance contract:

- **bit-identity**: K>=16 consecutive coalesced windows through the
  ring path equal the serial route (and the ring-off pipelined route)
  bit for bit, fused count riders included — same kernels, same frozen
  f64-exact mask, same `_canonical_dists` recompute;
- **zero per-window compiles**: after warmup the ring serves from ONE
  armed AOT program (JitTracker sees no recompiles across the run);
- **dispatch amortization**: `dispatches_per_window` (the
  serve.device.ops delta per window) is strictly below the PR-7
  pipelined baseline on CPU CI — the structural form of the TPU
  dispatch-RTT claim;
- **typed fallback**: a write makes the armed program stale → the next
  window takes the pipelined route (fresh residency) and the ring
  re-arms; a fault-injected slot-write OOM runs the batcher's halving
  ladder from host copies exactly like a pipelined window;
- **drain/close**: every in-flight window is harvested exactly once.

Shapes deliberately mirror tests/test_pipeline.py (600-row store, k=5,
single-point windows padding to the same pow2 bucket) so the kernel jit
caches stay warm across the suite — the ROADMAP wall-time rule.
"""

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.plan.query import Query
from geomesa_tpu.serve import QueryService, ServeConfig

CQL = "BBOX(geom, -170, -80, 170, 80) AND score > -5"
WINDOWS = 18  # >= 16 consecutive ring windows (acceptance floor)


def make_batch(n=600, seed=3, start=0):
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
        str(tmp_path_factory.mktemp("ringloop")), use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


@pytest.fixture(scope="module")
def qpts():
    return np.random.default_rng(42).uniform(-60, 60, (WINDOWS + 4, 2))


@pytest.fixture(scope="module")
def serial_oracle(store, qpts):
    src = store.get_feature_source("served")
    return [src.planner.knn(Query("served", CQL), qpts[i:i + 1, 0],
                            qpts[i:i + 1, 1], k=5)
            for i in range(len(qpts))]


def _counter(name):
    from geomesa_tpu.utils.metrics import metrics

    with metrics._lock:
        return metrics.counters.get(name, 0.0)


def _sequential(store, qpts, config, lo=0, hi=WINDOWS, svc=None):
    """`hi - lo` consecutive single-request windows (each resolves
    before the next submits — the steady-state serve shape the ring
    exists for). Returns (results, pipeline stats)."""
    own = svc is None
    if own:
        svc = QueryService(store, config)
    try:
        out = []
        for i in range(lo, hi):
            out.append(svc.knn("served", CQL, qpts[i:i + 1, 0],
                               qpts[i:i + 1, 1], k=5).result(timeout=300))
        return out, svc.stats()["pipeline"]
    finally:
        if own:
            svc.close(drain=True)


class TestRingIdentity:
    def test_ring_bit_identical_to_serial_and_pipelined(
            self, store, qpts, serial_oracle):
        """Acceptance: K>=16 consecutive windows, ring vs serial vs
        ring-off pipelined — identical bits, every window past warmup
        on ONE armed program with zero fallbacks."""
        armed0 = _counter("knn.tiles.armed")
        ring_res, ring_p = _sequential(
            store, qpts, ServeConfig(max_wait_ms=1.0))
        armed = _counter("knn.tiles.armed") - armed0
        pipe_res, pipe_p = _sequential(
            store, qpts, ServeConfig(max_wait_ms=1.0, ring=False))
        for i in range(WINDOWS):
            d, ix, _ = ring_res[i]
            sd, six, _ = serial_oracle[i]
            np.testing.assert_array_equal(d, sd, err_msg=f"knn {i}")
            np.testing.assert_array_equal(ix, six, err_msg=f"knn {i}")
            pd, pix, _ = pipe_res[i]
            np.testing.assert_array_equal(d, pd, err_msg=f"knn {i}")
            np.testing.assert_array_equal(ix, pix, err_msg=f"knn {i}")
        ring = ring_p["ring"]
        assert ring["windows"] == WINDOWS
        # every ring window scanned the arm-time tile list
        assert armed == ring["windows"]
        assert ring["armed"] == 1 and ring["programs"] == 1
        assert ring["fallbacks"] == {}
        assert "ring" not in pipe_p

    def test_fused_count_rider_resolves_from_armed_scalar(self, store):
        """COUNT riders on a ring window resolve from the arm-time mask
        reduction — equal to planner.count, zero extra dispatches."""
        src = store.get_feature_source("served")
        exact = src.planner.count(Query("served", CQL))
        rng = np.random.default_rng(7)
        pts = rng.uniform(-60, 60, (5, 2))
        svc = QueryService(store, ServeConfig(max_wait_ms=50.0),
                           autostart=False)
        # warm window first so the riders land on a WARM ring program
        warm = svc.knn("served", CQL, pts[0:1, 0], pts[0:1, 1], k=5)
        svc.start()
        warm.result(timeout=300)
        futs = [svc.knn("served", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1],
                        k=5) for i in range(1, 4)]
        cfuts = [svc.count("served", CQL) for _ in range(3)]
        for f in futs:
            f.result(timeout=300)
        counts = [f.result(timeout=300) for f in cfuts]
        st = svc.stats()["pipeline"]
        svc.close(drain=True)
        assert all(c == exact for c in counts)
        assert st["fused_counts"] >= 1
        assert st["ring"]["windows"] >= 1

    def test_zero_recompiles_after_warmup(self, store, qpts):
        """JitTracker across the post-warmup run: the ring path traces
        and compiles NOTHING per window (the AOT handle is armed
        once)."""
        svc = QueryService(store, ServeConfig(max_wait_ms=1.0,
                                              track_compiles=True))
        try:
            _sequential(store, qpts, None, lo=0, hi=2, svc=svc)  # warmup
            base = svc.tracker.total_recompiles()
            _sequential(store, qpts, None, lo=2, hi=WINDOWS, svc=svc)
            assert svc.tracker.total_recompiles() == base
            ring = svc.stats()["pipeline"]["ring"]
            assert ring["windows"] == WINDOWS
            assert ring["armed"] == 1
        finally:
            svc.close(drain=True)

    def test_dispatches_per_window_strictly_below_pipelined(
            self, store, qpts):
        """Acceptance: the measured per-window device-interaction count
        (serve.device.ops delta / windows) on the ring route is
        STRICTLY below the PR-7 pipelined baseline for identical
        work."""
        def measured(config):
            svc = QueryService(store, config)
            try:
                _sequential(store, qpts, None, lo=0, hi=2, svc=svc)
                o0 = _counter("serve.device.ops")
                _sequential(store, qpts, None, lo=2, hi=WINDOWS, svc=svc)
                return (_counter("serve.device.ops") - o0) / (WINDOWS - 2)
            finally:
                svc.close(drain=True)

        ring_pw = measured(ServeConfig(max_wait_ms=1.0))
        pipe_pw = measured(ServeConfig(max_wait_ms=1.0, ring=False))
        assert ring_pw < pipe_pw, (ring_pw, pipe_pw)


class TestRingFallbacks:
    def test_write_goes_stale_then_rearms_fresh(self, tmp_path):
        """A committed write makes the armed program stale: the next
        window takes the pipelined route (fresh residency, new rows
        visible at the batch boundary) and the ring re-arms against
        the new version — results stay exact throughout."""
        sft, batch = make_batch(n=300, seed=11)
        ds = DataStore(str(tmp_path), use_device_cache=True)
        src = ds.create_schema(sft)
        src.write(batch)
        rng = np.random.default_rng(5)
        pts = rng.uniform(-60, 60, (8, 2))
        svc = QueryService(ds, ServeConfig(max_wait_ms=1.0))
        try:
            for i in range(4):
                svc.knn("served", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1],
                        k=5).result(timeout=300)
            st0 = svc.stats()["pipeline"]["ring"]
            assert st0["windows"] >= 3
            # commit more rows: the armed mask/version is now stale
            _, more = make_batch(n=200, seed=13)
            src.write(more)
            results = []
            for i in range(4, 8):
                results.append(svc.knn(
                    "served", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1],
                    k=5).result(timeout=300))
            st1 = svc.stats()["pipeline"]["ring"]
            (prog,) = svc.pipeline.ring._programs.values()
        finally:
            svc.close(drain=True)
        assert st1["fallbacks"].get("stale", 0) >= 1
        assert st1["armed"] >= st0["armed"] + 1  # re-armed post-write
        # exactness against a fresh serial replay over the grown store
        planner = ds.get_feature_source("served").planner
        # the re-armed program's tile list is the new mask's, not the
        # stale one's
        from geomesa_tpu.engine.knn_scan import knn_tile_summary

        plan = planner.plan(Query("served", CQL))
        mask = planner._knn_mask_setup(plan, plan.query)[3]
        maskf, tile_ids, n_sel, _ = knn_tile_summary(
            mask, tile_capacity=prog.cap)
        np.testing.assert_array_equal(np.asarray(prog.mask),
                                      np.asarray(maskf))
        np.testing.assert_array_equal(np.asarray(prog.tile_ids),
                                      np.asarray(tile_ids))
        assert int(prog.n_sel) == int(n_sel)
        for j, i in enumerate(range(4, 8)):
            sd, six, _ = planner.knn(
                Query("served", CQL), pts[i:i + 1, 0], pts[i:i + 1, 1],
                k=5)
            d, ix, _ = results[j]
            np.testing.assert_array_equal(d, sd)
            np.testing.assert_array_equal(ix, six)

    def test_slot_write_oom_runs_the_halving_ladder(self, tmp_path):
        """OOM-ladder parity: an injected OOM on the ring's slot write
        (the device.transfer fault site) halves the coalesced window
        and re-runs from the HOST query copies — every rider exact,
        like the pipelined path."""
        from geomesa_tpu.faults import harness as faults
        from geomesa_tpu.faults.plan import FaultPlan, FaultRule

        sft, batch = make_batch(n=300, seed=17)
        ds = DataStore(str(tmp_path), use_device_cache=True)
        ds.create_schema(sft).write(batch)
        rng = np.random.default_rng(3)
        pts = rng.uniform(-60, 60, (6, 2))
        planner = ds.get_feature_source("served").planner
        serial = [planner.knn(Query("served", CQL), pts[i:i + 1, 0],
                              pts[i:i + 1, 1], k=5) for i in range(6)]
        svc = QueryService(ds, ServeConfig(max_wait_ms=50.0),
                           autostart=False)
        # warm (and arm) with one window OUTSIDE the fault plan
        warm = svc.knn("served", CQL, pts[0:1, 0], pts[0:1, 1], k=5)
        svc.start()
        warm.result(timeout=300)
        futs = [svc.knn("served", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1],
                        k=5) for i in range(6)]
        plan = FaultPlan(rules=[
            FaultRule(site="device.transfer", error="oom", nth_call=1)])
        with faults.active(plan):
            results = [f.result(timeout=300) for f in futs]
        svc.close(drain=True)
        for (d, ix, _), (sd, six, _) in zip(results, serial):
            assert np.array_equal(ix, six)
            assert np.allclose(d, sd, rtol=1e-3)

    def test_drain_close_harvests_every_window_once(self, store, qpts):
        """Submit a burst, close(drain=True) immediately: every future
        resolves exactly once with a real result, nothing is left
        in flight, and the slot accounting balances."""
        svc = QueryService(store, ServeConfig(max_wait_ms=1.0))
        futs = [svc.knn("served", CQL, qpts[i:i + 1, 0],
                        qpts[i:i + 1, 1], k=5) for i in range(8)]
        svc.close(drain=True)
        done = [f for f in futs if f.done()]
        assert len(done) == 8
        for f in futs:
            d, ix, _ = f.result(timeout=1)
            assert d.shape == (1, 5) and ix.shape == (1, 5)
        p = svc.stats()["pipeline"]
        assert p["inflight"] == 0


class TestDensitySlotParity:
    def test_slotted_density_matches_static_kernel(self):
        """The slot-parameterized density variant (engine/density.py,
        ring groundwork) is bit-identical to the static-bbox kernel on
        f32-exact envelopes — the eligibility gate the ring tier would
        apply."""
        import jax.numpy as jnp

        from geomesa_tpu.engine.density import (
            density_grid, density_grid_slotted)

        rng = np.random.default_rng(9)
        n = 512
        x = jnp.asarray(rng.uniform(-170, 170, n), jnp.float32)
        y = jnp.asarray(rng.uniform(-80, 80, n), jnp.float32)
        w = jnp.ones(n, jnp.float32)
        m = jnp.asarray(rng.random(n) > 0.25)
        bbox = (-180.0, -90.0, 180.0, 90.0)  # f32-exact envelope
        static = density_grid(x, y, w, m, bbox, 64, 32)
        slot = jnp.asarray(np.asarray(bbox, np.float32))
        slotted = density_grid_slotted(x, y, w, m, slot, 64, 32)
        np.testing.assert_array_equal(np.asarray(static),
                                      np.asarray(slotted))
