"""Partition-lane pruning of the resident superbatch: `lane_mask` (a
range compare of the partition-id column against the runs of allowed
ids) equals `allowed[pids]` bit for bit, keeps the mesh tier's row
sharding, holds no gather, compiles one program per pow2 run count, and
serves exact counts and kNN through the planner over a fragmented
(date + Z2) partition selection."""

import math

import jax
import numpy as np
import pytest

import reference_engine as oracle
from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.cql import parse_cql
from geomesa_tpu.engine.geodesy import haversine_m_np
from geomesa_tpu.plan import DataStore
from geomesa_tpu.plan.query import Query
from geomesa_tpu.store.cache import lane_mask, lane_runs
from geomesa_tpu.store.partition import (
    CompositeScheme, DateTimeScheme, Z2Scheme)
from geomesa_tpu.telemetry.trace import TRACER
from geomesa_tpu.utils.metrics import metrics
from geomesa_tpu.utils.padding import next_pow2

SPEC = "speed:Double,dtg:Date,*geom:Point"
T0 = int(np.datetime64("2021-03-01T00:00:00", "ms").astype(np.int64))
DAY = 86400_000
SFT = SimpleFeatureType.from_spec("lanes", SPEC)


def _batch(days, seed, lon=(-170, 170), lat=(-80, 80)):
    """Rows over the given day offsets, `days[i]` rows falling on day i
    (rows per day differ, so the partitions' pow2 padding differs)."""
    r = np.random.default_rng(seed)
    dtg = np.concatenate([
        T0 + d * DAY + r.integers(0, DAY, n) for d, n in enumerate(days)])
    n = len(dtg)
    return FeatureBatch.from_pydict(SFT, {
        "speed": r.uniform(0, 30, n),
        "dtg": dtg,
        "geom": np.stack([r.uniform(*lon, n), r.uniform(*lat, n)], 1),
    })


def _resident(tmp_path, scheme, batch):
    """A device-cached store with every partition resident, and its
    superbatch (built by the store's DeviceCacheManager)."""
    ds = DataStore(str(tmp_path / "cat"), use_device_cache=True)
    src = ds.create_schema(SFT, scheme)
    src.write(batch)
    assert src.get_count("speed >= 0") == len(batch)  # loads every lane
    return ds, src


def _allowed_cases(p):
    alt = np.zeros(p, bool)
    alt[::2] = True
    cases = {
        "none": np.zeros(p, bool),
        "single": np.isin(np.arange(p), [p // 2]),
        "first": np.isin(np.arange(p), [0]),
        "last": np.isin(np.arange(p), [p - 1]),
        "one_run": (np.arange(p) >= 3) & (np.arange(p) <= p - 4),
        "alternating": alt,
        "all": np.ones(p, bool),
    }
    for seed in (0, 1, 2):
        cases[f"random{seed}"] = np.random.default_rng(seed).random(p) < 0.5
    return cases


DAYS = [5, 9, 3, 16, 1, 7, 33, 2, 4, 12, 6, 8]  # 12 daily partitions


@pytest.fixture(scope="module")
def daily_sb(tmp_path_factory):
    _, src = _resident(tmp_path_factory.mktemp("lanes"),
                       DateTimeScheme("yyyy/MM/dd"), _batch(DAYS, seed=4))
    sb = src.planner.cache.superbatch()
    assert len(sb.ids) == len(DAYS)
    return sb


@pytest.mark.parametrize("case", sorted(_allowed_cases(len(DAYS))))
def test_lane_mask_equals_allowed_gather(daily_sb, case):
    allowed = _allowed_cases(len(DAYS))[case]
    runs, r = lane_runs(allowed)
    assert runs.shape == (next_pow2(max(r, 1)), 2)
    got = np.asarray(lane_mask(daily_sb.pids, runs))
    want = allowed[np.asarray(daily_sb.pids)]
    assert got.dtype == want.dtype == np.bool_
    np.testing.assert_array_equal(got, want)


def test_lane_runs_are_the_maximal_runs():
    allowed = np.array([1, 1, 0, 1, 0, 0, 1, 1, 1, 0, 1], bool)
    runs, r = lane_runs(allowed)
    assert r == 4
    np.testing.assert_array_equal(runs, [[0, 1], [3, 3], [6, 8], [10, 10]])
    runs, r = lane_runs(np.array([0, 1, 1, 1, 0], bool))
    assert r == 1
    np.testing.assert_array_equal(runs, [[1, 3]])
    runs, r = lane_runs(np.array([1, 0, 1, 0, 1], bool))
    assert r == 3 and runs.shape == (4, 2)
    assert runs[3, 0] > runs[3, 1]  # padding: an empty range


def test_lane_mask_keeps_mesh_sharding_with_pad_rows(tmp_path):
    from geomesa_tpu.parallel.mesh import serve_mesh

    ds = DataStore(str(tmp_path / "cat"), use_device_cache=True)
    src = ds.create_schema(SFT, DateTimeScheme("yyyy/MM/dd"))
    batch = _batch([1, 2, 4], seed=6)  # padded rows 1 + 2 + 4 = 7
    src.write(batch)
    ds.set_mesh(serve_mesh(4))
    assert src.get_count("speed >= 0") == len(batch)
    sb = src.planner.cache.superbatch()
    assert sb.mesh is not None
    assert len(sb.pids) == 8  # one trailing pad row, carrying the last pid
    pids = np.asarray(sb.pids)
    assert pids[-1] == pids[-2] == 2
    for bits in range(8):
        allowed = np.array([(bits >> i) & 1 for i in range(3)], bool)
        out = lane_mask(sb.pids, lane_runs(allowed)[0])
        assert out.sharding == sb.pids.sharding
        np.testing.assert_array_equal(np.asarray(out), allowed[pids])
    # the pad row stays inert: counts match the host path
    q = "BBOX(geom, -100, -60, 100, 60) AND dtg DURING " \
        "2021-03-02T00:00:00Z/2021-03-03T12:00:00Z"
    host = DataStore(str(tmp_path / "cat")).get_feature_source("lanes")
    assert src.get_count(q) == host.get_count(q)


def test_lane_mask_has_no_gather_and_few_programs():
    p = 117
    pids = jax.numpy.asarray(np.repeat(np.arange(p, dtype=np.int32), 3)[:-1])
    runs, _ = lane_runs(np.arange(p) % 2 == 0)
    assert "gather" not in str(jax.make_jaxpr(lane_mask)(pids, runs))
    before = lane_mask._cache_size()
    host_pids = np.asarray(pids)
    for r in range(1, (p + 1) // 2 + 1):
        allowed = np.zeros(p, bool)
        allowed[0:2 * r:2] = True
        runs, got_r = lane_runs(allowed)
        assert got_r == r
        np.testing.assert_array_equal(
            np.asarray(lane_mask(pids, runs)), allowed[host_pids])
    assert lane_mask._cache_size() - before <= math.ceil(math.log2(p)) + 1


# -- through the planner, over a fragmented selection ----------------------

FRAG_CQL = ("BBOX(geom, 10, 10, 40, 40) AND speed > 12 AND dtg DURING "
            "2021-03-02T00:00:00Z/2021-03-05T00:00:00Z")


@pytest.fixture(scope="module")
def composite(tmp_path_factory):
    batch = _batch([400] * 6, seed=8)
    _, src = _resident(
        tmp_path_factory.mktemp("composite"),
        CompositeScheme([DateTimeScheme("yyyy/MM/dd"), Z2Scheme(bits=2)]),
        batch)
    planner = src.planner
    sb = planner.cache.superbatch()
    plan = planner.plan(Query("lanes", FRAG_CQL))
    ids = sorted(sb.ids[p] for p in plan.partitions if p in sb.ids)
    runs = 1 + sum(b - a > 1 for a, b in zip(ids, ids[1:]))
    assert runs > 1  # one Z2 cell per day: the selection is not contiguous
    return src, batch, runs


def _lane_counters():
    return (metrics.counters.get("query.lane.masks", 0),
            metrics.counters.get("query.lane.runs", 0))


def test_planner_count_over_fragmented_lanes(composite):
    src, batch, runs = composite
    want = int(oracle.eval_filter(parse_cql(FRAG_CQL), batch).sum())
    assert want > 0
    masks0, runs0 = _lane_counters()
    was = TRACER.enabled
    TRACER.enable()
    try:
        trace = TRACER.start_trace("count")
        with TRACER.scope(trace):
            got = src.get_count(FRAG_CQL)
    finally:
        if not was:
            TRACER.disable()
    assert got == want
    masks1, runs1 = _lane_counters()
    assert (masks1 - masks0, runs1 - runs0) == (1, runs)
    lane = [s for s in trace.snapshot_spans()
            if s.name == "kernel.dispatch"
            and (s.attrs or {}).get("kernel") == "lane.mask"]
    assert [s.attrs["runs"] for s in lane] == [runs]
    assert not any((s.attrs or {}).get("kernel") == "lane.gather"
                   for s in trace.snapshot_spans())


def test_planner_knn_over_fragmented_lanes(composite):
    src, batch, runs = composite
    mask = oracle.eval_filter(parse_cql(FRAG_CQL), batch)
    g = batch.columns["geom"]
    x, y = np.asarray(g.x), np.asarray(g.y)
    qx, qy = np.array([20.0, 33.0]), np.array([25.0, 15.0])
    masks0, runs0 = _lane_counters()
    d, i, got = src.knn(FRAG_CQL, qx, qy, k=4)
    masks1, runs1 = _lane_counters()
    assert masks1 > masks0
    assert runs1 - runs0 == runs * (masks1 - masks0)
    want = np.empty((len(qx), 4))
    for j in range(len(qx)):
        want[j] = np.sort(haversine_m_np(qx[j], qy[j], x[mask], y[mask]))[:4]
    np.testing.assert_allclose(np.sort(d, 1), want, rtol=1e-4, atol=1.0)
    # every served row is a matching row of the selected lanes
    sel = oracle.eval_filter(parse_cql(FRAG_CQL), got)
    assert sel[i[np.isfinite(d)]].all()
