"""Extended-geometry serving (docs/SERVING.md "Extended geometries &
TubeSelect"): CPU mesh parity for the XZ-sliced residency tier.

The load-bearing claims, proven on a 4-device CPU mesh (conftest forces
an 8-device host platform):

- extended stores (LineStrings here) build MESH residency: the
  superbatch row-shards across chips AND carries per-shard CSR tiles
  (vertex/ring/edge buffers with shard-local offsets), with the same
  partition->shard ownership map the point tier has;
- INTERSECTS/DWITHIN counts, kNN-on-lines and TubeSelect answer
  bit-identically across every route — serial, pipelined, mesh,
  ring-fed mesh — against the host f64 oracle, over >= 16 consecutive
  windows (the ring arms once and stays fresh);
- a coalesced TubeSelect window is ONE dispatch: the service dispatch
  counter, the engine jit caches (JitTracker: zero module-jit calls on
  the mesh route) and the `serve.device.ops` accounting all agree;
- the tube ring retires the blanket non-point refusal: tube windows
  arm and ride ring programs (`serve.ring.windows` moves, fallbacks
  stay empty).

Budget note (tier-1 wall): ONE tiny 4-partition LineString store
(512 rows), every test shares its warm mesh programs.
"""

import json

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.engine.tube import tube_select_host
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.serve import QueryService, ServeConfig
from geomesa_tpu.utils.metrics import metrics

MESH_D = 4
ROWS_PER_DAY = 128
DAYS = ("2021-03-01", "2021-03-02", "2021-03-03", "2021-03-04")
POLY = "POLYGON ((-6 -6, 6 -6, 6 6, -6 6, -6 -6))"
CQL_INTERSECTS = f"INTERSECTS(geom, {POLY})"
CQL_DWITHIN = "DWITHIN(geom, POINT(0 0), 400000, meters)"

RADIUS_M = 150_000.0
HALF_WINDOW_MS = 12 * 3_600_000
T = 17  # pads to 32: one tube ring class for every window below

# these specify ROADMAP 2.1/2.2, which no code implements yet: extended
# mesh residency, kNN over non-point stores, the served tube verb
NOT_BUILT = pytest.mark.xfail(
    strict=True, raises=(AttributeError, ValueError),
    reason="specified, not implemented (ROADMAP 2.1/2.2)")


def _day_millis(day: str) -> int:
    return int(np.datetime64(day, "ms").astype(np.int64))


def make_batch():
    """4 day-partitions x 128 rows of 3-vertex linestrings: each
    partition pow2-pads to exactly 128 rows, so under a 4-chip mesh
    (shard_rows = 512/4 = 128) partition i is owned by shard i alone."""
    rng = np.random.default_rng(23)
    sft = SimpleFeatureType.from_spec(
        "corridors", "name:String,score:Double,dtg:Date,*geom:LineString")
    frames = []
    for d, day in enumerate(DAYS):
        n = ROWS_PER_DAY
        x0 = rng.uniform(-12, 12, n)
        y0 = rng.uniform(-12, 12, n)
        wkts = [
            f"LINESTRING ({x0[i]} {y0[i]}, {x0[i] + 0.08} {y0[i] + 0.05},"
            f" {x0[i] + 0.16} {y0[i] - 0.03})"
            for i in range(n)
        ]
        frames.append({
            "name": [f"f{d}_{i}" for i in range(n)],
            "score": rng.uniform(-10, 10, n),
            "dtg": _day_millis(day)
            + rng.integers(6 * 3600_000, 18 * 3600_000, n),
            "geom": wkts,
        })
    return sft, frames


def track():
    tx = np.linspace(-8.0, 8.0, T)
    ty = np.linspace(-5.0, 5.0, T)
    tt = np.linspace(_day_millis(DAYS[0]),
                     _day_millis(DAYS[-1]) + 86_400_000, T).astype(np.int64)
    return tx, ty, tt


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    sft, frames = make_batch()
    root = str(tmp_path_factory.mktemp("extended_serve"))
    ds = DataStore(root, use_device_cache=True)
    ds.create_schema(sft)
    src = ds.get_feature_source("corridors")
    for data in frames:
        src.write(FeatureBatch.from_pydict(sft, data))
    del ds
    return root


@pytest.fixture(scope="module")
def mesh_store(catalog):
    return DataStore(catalog, use_device_cache=True)


@pytest.fixture(scope="module")
def serial_store(catalog):
    """Independent single-chip store over the same files — the oracle
    the mesh answers must match bit-for-bit."""
    return DataStore(catalog, use_device_cache=True)


@pytest.fixture(scope="module")
def host_batch(serial_store):
    src = serial_store.get_feature_source("corridors")
    return src.get_features("INCLUDE").features


def _counter(name: str) -> float:
    return json.loads(metrics.to_json())["counters"].get(name, 0.0)


def _mesh_service(store, **kw) -> QueryService:
    return QueryService(
        store, ServeConfig(mesh=MESH_D, max_wait_ms=20.0, **kw),
        autostart=False)


def _tube_names(svc, started=False) -> list:
    tx, ty, tt = track()
    fut = svc.tube("corridors", "INCLUDE", tx, ty, tt,
                   RADIUS_M, HALF_WINDOW_MS)
    if not started:
        svc.start()
    r = fut.result(timeout=300)
    return sorted(r.features.columns["name"].decode())


@NOT_BUILT
def test_extended_mesh_residency_csr_tiles(mesh_store):
    """The extended superbatch row-shards across the mesh AND carries
    per-shard CSR tiles with shard-local offsets; the partition
    ownership map mirrors the point tier's."""
    svc = _mesh_service(mesh_store)
    svc.start()
    try:
        svc.count("corridors", CQL_INTERSECTS).result(timeout=300)
    finally:
        svc.close(drain=True)
    src = mesh_store.get_feature_source("corridors")
    sb = src.planner.cache.superbatch()
    assert sb.extended
    assert sb.mesh is not None and sb.shard_rows == ROWS_PER_DAY
    owned = sorted(sb.owners.items())
    assert [o for _, o in owned] == [(0,), (1,), (2,), (3,)], owned
    # CSR tiles: [D, ...] stacked per-shard slices, offsets rewritten
    # shard-local — every shard's feature-offset table spans exactly
    # its shard_rows rows and ends at its own vertex count
    tiles = sb.tiles
    featr = np.asarray(tiles["geom__featr"])
    verts = np.asarray(tiles["geom__verts"])
    assert featr.shape == (MESH_D, ROWS_PER_DAY + 1)
    assert verts.shape[0] == MESH_D and verts.shape[2] == 2
    assert (featr[:, 0] == 0).all()
    # one ring per linestring, offsets rewritten shard-local
    assert (featr[:, -1] == ROWS_PER_DAY).all()
    # vertex-feature ownership stays in-shard: padded entries map to
    # the sentinel row (shard_rows), real ones below it
    vfeat = np.asarray(tiles["geom__vfeat"])
    assert vfeat.max() <= ROWS_PER_DAY
    # upload accounting: the residency walk metered tile rows
    assert src.planner.cache.stats()["upload_tile_rows"] > 0


def test_counts_bit_identical_across_routes(mesh_store, serial_store):
    serial_src = serial_store.get_feature_source("corridors")
    want_int = serial_src.get_count(CQL_INTERSECTS)
    want_dw = serial_src.get_count(CQL_DWITHIN)
    assert want_int > 0 and want_dw > 0
    svc = _mesh_service(mesh_store)
    svc.start()
    try:
        got_int = svc.count("corridors", CQL_INTERSECTS).result(timeout=300)
        got_dw = svc.count("corridors", CQL_DWITHIN).result(timeout=300)
    finally:
        svc.close(drain=True)
    assert got_int == want_int
    assert got_dw == want_dw


@NOT_BUILT
def test_knn_on_lines_bit_identical(mesh_store, serial_store):
    """kNN over an extended store runs on the representative coords —
    mesh route bit-identical to single-chip serial."""
    rng = np.random.default_rng(5)
    qx = rng.uniform(-10, 10, 1)
    qy = rng.uniform(-10, 10, 1)
    serial_src = serial_store.get_feature_source("corridors")
    sd, six, _ = serial_src.knn(CQL_INTERSECTS, qx, qy, k=5)
    svc = _mesh_service(mesh_store)
    svc.start()
    try:
        d, ix, _ = svc.knn("corridors", CQL_INTERSECTS, qx, qy,
                           k=5).result(timeout=300)
    finally:
        svc.close(drain=True)
    np.testing.assert_array_equal(ix, six)
    assert np.array_equal(d, sd), (d, sd)


def tube_oracle(host_batch) -> list:
    tx, ty, tt = track()
    col = host_batch.columns["geom"]
    t = np.asarray(host_batch.columns["dtg"]).astype(
        "datetime64[ms]").astype("int64")
    hits = tube_select_host(np.asarray(col.x), np.asarray(col.y), t,
                            tx, ty, tt, RADIUS_M, HALF_WINDOW_MS)
    names = host_batch.columns["name"].decode()
    return sorted(names[i] for i in np.nonzero(hits)[0])


@NOT_BUILT
def test_tube_parity_16_windows_all_routes(mesh_store, serial_store,
                                           host_batch):
    """TubeSelect bit-identical to the f64 host oracle on every route,
    over >= 16 CONSECUTIVE windows on the ring-fed mesh service (the
    armed program stays fresh; fallbacks stay empty)."""
    want = tube_oracle(host_batch)
    assert want, "oracle matched nothing; bad fixture"

    # serial route (no pipeline, no mesh)
    svc = QueryService(serial_store,
                       ServeConfig(pipeline=False, max_wait_ms=5.0),
                       autostart=False)
    try:
        got = _tube_names(svc)
        assert got == want
    finally:
        svc.close(drain=True)

    # pipelined route (no mesh): same answer
    svc = QueryService(serial_store, ServeConfig(max_wait_ms=5.0),
                       autostart=False)
    try:
        got = _tube_names(svc)
        assert got == want
    finally:
        svc.close(drain=True)

    # mesh + ring: 16 consecutive windows, every one bit-identical;
    # the ring arms on the first and feeds the rest
    svc = _mesh_service(mesh_store)
    svc.start()
    try:
        base_ring = _counter("serve.ring.windows")
        for i in range(16):
            got = _tube_names(svc, started=True)
            assert got == want, f"window {i} diverged"
        stats = svc.stats()
    finally:
        svc.close(drain=True)
    ring = (stats.get("pipeline") or {}).get("ring") or {}
    assert ring.get("windows", 0) >= 15, ring
    assert not ring.get("fallbacks"), ring
    assert _counter("serve.ring.windows") - base_ring >= 15


@NOT_BUILT
def test_tube_coalesced_window_one_dispatch(mesh_store, host_batch):
    """>= 8 identical concurrent TubeSelect requests coalesce (dedup
    key) into ONE window and ONE device dispatch: service counter says
    one dispatch, the engine tube module's jit caches see zero calls
    (mesh route = AOT registry), and serve.device.ops moves by a
    per-window constant, not per-rider."""
    import geomesa_tpu.engine.tube as tube_mod

    from geomesa_tpu.analysis.runtime import JitTracker

    want = tube_oracle(host_batch)
    tx, ty, tt = track()

    # warm the mesh tube route at this T bucket
    svc = _mesh_service(mesh_store)
    f = svc.tube("corridors", "INCLUDE", tx, ty, tt,
                 RADIUS_M, HALF_WINDOW_MS)
    svc.start()
    f.result(timeout=300)
    svc.close(drain=True)

    tracker = JitTracker()
    tracker.install(tube_mod)
    try:
        base_mesh = _counter("tube.mesh.dispatches")
        base_ring = _counter("serve.ring.windows")
        base_ops = _counter("serve.device.ops")
        svc = _mesh_service(mesh_store)
        futs = [svc.tube("corridors", "INCLUDE", tx, ty, tt,
                         RADIUS_M, HALF_WINDOW_MS) for _ in range(8)]
        svc.start()
        results = [f.result(timeout=300) for f in futs]
        svc.close(drain=True)
        jit_calls = sum(rec["calls"] for rec in tracker.report().values())
    finally:
        tracker.unwrap()

    assert svc.stats()["dispatches"] == 1, svc.stats()
    assert jit_calls == 0, tracker.report()
    # one window: exactly one mesh dispatch on whichever route (ring or
    # pipelined launch) took it
    d_mesh = _counter("tube.mesh.dispatches") - base_mesh
    d_ring = _counter("serve.ring.windows") - base_ring
    assert d_mesh == 1, (d_mesh, d_ring)
    # per-window device-op budget: slot/stage transfer + program
    # dispatch + combined sync read (+ nothing per rider)
    assert _counter("serve.device.ops") - base_ops <= 4
    for r in results:
        got = sorted(r.features.columns["name"].decode())
        assert got == want


@NOT_BUILT
def test_tube_ring_retires_non_point_refusal(mesh_store):
    """The extended tier's whole point on the ring: tube windows ARM
    (no `non_point`/`no_geometry` refusal), and the per-reason
    ineligibility meter stays quiet for them."""
    svc = _mesh_service(mesh_store)
    tx, ty, tt = track()
    f = svc.tube("corridors", "score > -100", tx, ty, tt,
                 RADIUS_M, HALF_WINDOW_MS)
    svc.start()
    try:
        f.result(timeout=300)
        # second window of the same class rides the armed program
        svc.tube("corridors", "score > -100", tx, ty, tt,
                 RADIUS_M, HALF_WINDOW_MS).result(timeout=300)
        stats = svc.stats()
    finally:
        svc.close(drain=True)
    ring = (stats.get("pipeline") or {}).get("ring") or {}
    falls = ring.get("fallbacks", {})
    assert "no_geometry" not in falls and "non_point" not in falls, falls
    assert ring.get("armed", 0) >= 1, ring
