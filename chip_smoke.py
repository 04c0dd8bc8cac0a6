"""Chip smoke: the DataStore -> planner -> QueryService path on a TPU.

Drives the main path once through the entry points a user calls, on a
seeded GDELT-shaped store that is HBM-resident at a real size, and checks
every answer against an independent host oracle:

  - `get_count` of the README's BBOX+time+attribute ECQL equals
    `cql.hosteval.eval_filter_host` exactly;
  - the planner's kNN push-down (k=10, 64 query points) matches a NumPy
    f64 haversine over the same filtered rows (the tests' tolerance);
  - a 512x512 density query through the `density_*` hints has a mass
    equal to that count;
  - a started `QueryService` answers the same count and kNN as
    concurrent requests, equal to the direct answers.

The whole set runs twice in this one process: cold, then again after
`jax.clear_caches()`, so the second pass compiles from the persistent
compilation cache and its compile seconds show against the first's.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # mesh-sharded serve vs single-chip

The script refuses to run anywhere but a TPU with the chip count asked
for: it exits non-zero before any phase, naming the platform it found.
Progress goes to stderr; the last line of stdout is one JSON object,
`{"ok": true, "device": {"platform", "kind", "count"}}`. Any failed check
or error exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import threading
import time

import numpy as np

TYPE_NAME = "gdelt"
SPEC = "score:Double,dtg:Date,*geom:Point"
# the README quickstart's BBOX+time+attribute query
CQL = ("BBOX(geom, -10, 20, 30, 60) AND score > 0 "
       "AND dtg DURING 2020-06-13T00:00:00Z/2020-08-21T00:00:00Z")
T0_MS, T1_MS = 1_590_000_000_000, 1_600_000_000_000
K = 10
Q = 64                      # kNN query points
# whole-world grid: every row lies strictly inside it, so the grid's
# mass must equal the filter's count exactly
DENSITY_BBOX = (-180.0, -90.0, 180.0, 90.0)
DENSITY_EDGE = 512
EARTH_RADIUS_M = 6_371_008.8
# the tests' tolerance for a kernel's f32-keyed distances against f64
KNN_RTOL, KNN_ATOL = 1e-4, 1.0
SERVE_REQUESTS = 8

_T_START = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    log(f"check {what}: {'PASS' if ok else 'FAIL'}")
    if not ok:
        raise CheckFailed(what)


def require_chip(chips: int) -> dict:
    """The device the run asked for, or SystemExit naming what was found.
    Never carries on on another platform."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform "
            f"{dev['platform']!r} ({dev['kind']}); refusing to run")
    if dev["count"] != chips:
        raise SystemExit(
            f"chip_smoke: asked for {chips} chip(s), found {dev['count']} "
            f"{dev['kind']} device(s); refusing to run")
    return dev


# -- data and oracle -------------------------------------------------------


def make_columns(n: int, seed: int) -> dict:
    """GDELT-shaped events: two thirds around city-like hotspots, one
    third spread over the globe, uniform scores and timestamps."""
    rng = np.random.default_rng(seed)
    n_hot = (2 * n) // 3
    centers = np.stack([rng.uniform(-150, 150, 256),
                        rng.uniform(-50, 65, 256)], 1)
    which = rng.integers(0, len(centers), n_hot)
    hot = centers[which] + rng.normal(0.0, 2.0, (n_hot, 2))
    flat = np.stack([rng.uniform(-180, 180, n - n_hot),
                     rng.uniform(-90, 90, n - n_hot)], 1)
    geom = np.concatenate([hot, flat])
    geom[:, 0] = np.clip(geom[:, 0], -179.999, 179.999)
    geom[:, 1] = np.clip(geom[:, 1], -89.999, 89.999)
    return {
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(T0_MS, T1_MS, n),
        "geom": geom,
    }


def query_points(seed: int) -> tuple:
    rng = np.random.default_rng(seed + 1)
    return rng.uniform(-10, 30, Q), rng.uniform(20, 60, Q)


def haversine_knn_f64(qx, qy, x, y, k: int) -> np.ndarray:
    """Sorted k smallest great-circle distances (m) per query point."""
    lon, lat = np.radians(x), np.radians(y)
    coslat = np.cos(lat)
    out = np.empty((len(qx), k))
    for i in range(len(qx)):
        qlon, qlat = math.radians(qx[i]), math.radians(qy[i])
        a = (np.sin((lat - qlat) / 2) ** 2
             + math.cos(qlat) * coslat * np.sin((lon - qlon) / 2) ** 2)
        d = 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))
        out[i] = np.sort(np.partition(d, k - 1)[:k])
    return out


def host_oracle(batch, qx, qy) -> dict:
    from geomesa_tpu.cql import parse_cql
    from geomesa_tpu.cql.hosteval import eval_filter_host

    t = time.perf_counter()
    mask = eval_filter_host(parse_cql(CQL), batch)
    g = batch.geometry
    x, y = np.asarray(g.x)[mask], np.asarray(g.y)[mask]
    knn = haversine_knn_f64(qx, qy, x, y, K)
    log(f"host oracle: {int(mask.sum())} matching rows, "
        f"{time.perf_counter() - t:.1f}s")
    return {"count": int(mask.sum()), "knn": knn}


# -- compile accounting ------------------------------------------------------


class CompileClock:
    """Sums JAX's backend-compile durations (which include persistent
    cache reads) and counts persistent-cache hits and misses, from
    whichever thread compiles (the serve dispatcher compiles too)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += secs

    def _event(self, event: str, **kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snap(self) -> tuple:
        with self._lock:
            return self.secs, self.hits, self.misses


def counter(name: str) -> float:
    from geomesa_tpu.utils.metrics import metrics

    return json.loads(metrics.to_json())["counters"].get(name, 0.0)


# -- phases ------------------------------------------------------------------


def timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def direct_phase(src, oracle, qx, qy) -> dict:
    """count, kNN and density through the planner."""
    from geomesa_tpu.plan.hints import QueryHints
    from geomesa_tpu.plan.query import Query

    walls = {}
    n, walls["count"] = timed(lambda: src.get_count(CQL))
    check(n == oracle["count"],
          f"count parity ({n} vs host f64 oracle {oracle['count']})")

    def knn():
        d, ix, _ = src.knn(CQL, qx, qy, k=K)
        return np.asarray(d), np.asarray(ix)

    (d, ix), walls["knn"] = timed(knn)
    err = np.abs(np.sort(d, 1) - oracle["knn"])
    check(bool(np.all(err <= KNN_ATOL + KNN_RTOL * oracle["knn"])),
          f"kNN parity ({len(qx)}x{K}, max |d - f64 haversine| = "
          f"{float(err.max()):.4f} m)")

    hints = QueryHints(density_bbox=DENSITY_BBOX, density_width=DENSITY_EDGE,
                       density_height=DENSITY_EDGE)
    grid, walls["density"] = timed(
        lambda: np.asarray(src.get_features(
            Query(TYPE_NAME, CQL, hints=hints)).grid))
    mass = float(grid.astype(np.float64).sum())
    check(grid.shape == (DENSITY_EDGE, DENSITY_EDGE) and mass == n,
          f"density {grid.shape} mass {mass:.0f} == count {n}")
    return {"count": n, "knn": (d, ix), "grid": grid, "wall_s": walls}


def serve_answers(store, qx, qy, mesh=None) -> dict:
    """kNN, count and density as concurrent requests to a started
    QueryService over `mesh` (None: the store's own placement; "off":
    the single-chip serve path)."""
    from geomesa_tpu.plan.hints import QueryHints
    from geomesa_tpu.serve.service import QueryService, ServeConfig

    hints = QueryHints(density_bbox=DENSITY_BBOX, density_width=DENSITY_EDGE,
                       density_height=DENSITY_EDGE)
    t = time.perf_counter()
    svc = QueryService(store, ServeConfig(max_wait_ms=20.0, mesh=mesh),
                       autostart=False)
    parts = np.array_split(np.arange(len(qx)), SERVE_REQUESTS)
    # all queued before start: the kNN requests coalesce into one window
    knn_futs = [svc.knn(TYPE_NAME, CQL, qx[p], qy[p], k=K) for p in parts]
    cnt_futs = [svc.count(TYPE_NAME, CQL) for _ in range(SERVE_REQUESTS)]
    dens_fut = svc.query(TYPE_NAME, CQL, hints=hints)
    svc.start()
    try:
        knn = [f.result(timeout=600) for f in knn_futs]
        out = {"counts": [f.result(timeout=600) for f in cnt_futs],
               "grid": np.asarray(dens_fut.result(timeout=600).grid)}
    finally:
        svc.close(drain=True)
    out["d"] = np.concatenate([np.asarray(r[0]) for r in knn])
    out["ix"] = np.concatenate([np.asarray(r[1]) for r in knn])
    out["wall_s"] = time.perf_counter() - t
    out["dispatches"] = svc.stats()["dispatches"]
    return out


def serve_phase(store, qx, qy, direct) -> dict:
    """The served answers must equal the direct ones."""
    got = serve_answers(store, qx, qy)
    dd, dix = direct["knn"]
    check(all(c == direct["count"] for c in got["counts"]),
          f"served counts {sorted(set(got['counts']))} == direct "
          f"{direct['count']}")
    check(np.array_equal(got["ix"], dix)
          and np.allclose(got["d"], dd, rtol=1e-6, atol=0),
          f"served kNN == direct ({SERVE_REQUESTS} requests, max |d| "
          f"diff {float(np.abs(got['d'] - dd).max()):.3g} m)")
    mass = float(got["grid"].astype(np.float64).sum())
    check(mass == direct["count"],
          f"served density mass {mass:.0f} == count {direct['count']}")
    return got


def build_store(root: str, n: int, seed: int):
    from geomesa_tpu.core.columnar import FeatureBatch
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.plan.datastore import DataStore

    t = time.perf_counter()
    sft = SimpleFeatureType.from_spec(TYPE_NAME, SPEC)
    batch = FeatureBatch.from_pydict(sft, make_columns(n, seed))
    store = DataStore(root, use_device_cache=True)
    src = store.create_schema(sft)
    src.write(batch)
    log(f"store: {n} rows generated and written in "
        f"{time.perf_counter() - t:.1f}s")
    return store, src, batch


def load(src, n: int) -> dict:
    """Make every partition HBM-resident as one superbatch."""
    import jax

    cache = src.planner.cache
    check(cache is not None, "store has a device cache")
    t = time.perf_counter()
    cache.ensure()
    sb = cache.superbatch()
    jax.block_until_ready(sb.dev)
    log(f"load: {time.perf_counter() - t:.1f}s")
    st = cache.stats()
    mem = jax.devices()[0].memory_stats() or {}
    used = int(mem.get("bytes_in_use", 0))
    log(f"residency: {st['rows']} rows in {st['partitions']} partitions "
        f"({st['padded_rows']} padded), device bytes_in_use={used} "
        f"(limit {mem.get('bytes_limit')})")
    check(st["rows"] == n, f"all {n} rows resident")
    return {"rows": st["rows"], "partitions": st["partitions"],
            "bytes_in_use": used}


def setup() -> str:
    """The compile cache in effect and real (not interpreted) kernels."""
    from geomesa_tpu.compilecache.persist import enable_persistent_cache
    from geomesa_tpu.engine.knn_scan import default_interpret

    cache_dir = enable_persistent_cache()
    check(cache_dir is not None,
          f"persistent compile cache in effect: {cache_dir}")
    check(default_interpret() is False, "Pallas interpret mode is off")
    return cache_dir


def one_chip(args) -> None:
    import jax

    cache_dir = setup()
    clock = CompileClock()
    qx, qy = query_points(args.seed)
    with tempfile.TemporaryDirectory() as tmp:
        store, src, batch = build_store(tmp, args.rows, args.seed)
        oracle = host_oracle(batch, qx, qy)
        del batch
        res = load(src, args.rows)
        # x, y f32 alone are 8 bytes a row
        check(res["bytes_in_use"] >= 8 * args.rows,
              f"HBM bytes_in_use {res['bytes_in_use']} >= 8 B x "
              f"{args.rows} rows")
        passes = []
        for label in ("cold", "warm"):
            if label == "warm":
                jax.clear_caches()
            c0 = clock.snap()
            t = time.perf_counter()
            direct = direct_phase(src, oracle, qx, qy)
            served = serve_phase(store, qx, qy, direct)
            c1 = clock.snap()
            p = {"pass": label, "wall_s": time.perf_counter() - t,
                 "compile_s": c1[0] - c0[0], "cache_hits": c1[1] - c0[1],
                 "cache_misses": c1[2] - c0[2],
                 "direct_wall_s": direct["wall_s"],
                 "serve_wall_s": served["wall_s"],
                 "serve_dispatches": served["dispatches"]}
            log(f"pass {label}: {json.dumps(p)}")
            passes.append(p)
    cold, warm = passes
    log(f"compile seconds: cold {cold['compile_s']:.2f} "
        f"(cache misses {cold['cache_misses']}), warm "
        f"{warm['compile_s']:.2f} (cache hits {warm['cache_hits']}), "
        f"cache dir {cache_dir}")
    if cold["cache_misses"]:
        check(warm["compile_s"] < cold["compile_s"],
              "warm compile time below the cold one")
    else:
        # the machine came with a cache this repo filled earlier: the
        # cold pass already read everything from it
        check(warm["cache_hits"] > 0,
              "cold pass fully served by the persistent cache, warm "
              "pass reads it too")
    for name in ("serve.oom.hosteval", "serve.ring.fallbacks"):
        v = counter(name)
        check(v == 0, f"{name} = {v:g}")
    print(json.dumps({
        "rows": args.rows, "bytes_in_use": res["bytes_in_use"],
        "partitions": res["partitions"], "compile_cache_dir": cache_dir,
        "count": oracle["count"], "passes": passes,
    }))


def four_chips(args) -> None:
    """Mesh-sharded serving against the single-chip serve path on the
    same store: bit-identical answers, one program per coalesced kNN
    window, resident shards on every chip."""
    setup()
    qx, qy = query_points(args.seed)
    with tempfile.TemporaryDirectory() as tmp:
        store, src, batch = build_store(tmp, args.rows, args.seed)
        del batch
        windows0 = counter("knn.mesh.dispatches")
        mesh = serve_answers(store, qx, qy, args.chips)
        windows = counter("knn.mesh.dispatches") - windows0
        sb = src.planner.cache.superbatch_peek()
        check(sb is not None and sb.mesh is not None,
              "store is mesh-resident")
        x = sb.dev[f"{src.sft.default_geometry.name}__x"]
        shard_devs = {s.device for s in x.addressable_shards}
        log(f"resident shards: {len(x.addressable_shards)} on "
            f"{sorted(str(d) for d in shard_devs)}")
        check(len(shard_devs) == args.chips,
              f"resident shards on {len(shard_devs)} distinct devices")
        single = serve_answers(store, qx, qy, "off")
    log(f"mesh serve {mesh['wall_s']:.1f}s, single-chip serve "
        f"{single['wall_s']:.1f}s, mesh kNN windows {windows:g}")
    check(windows == 1, f"one program per kNN window ({windows:g} "
          f"dispatches for one coalesced window)")
    check(np.array_equal(mesh["d"], single["d"])
          and np.array_equal(mesh["ix"], single["ix"]),
          "mesh kNN bit-identical to single-chip")
    check(mesh["counts"] == single["counts"],
          f"mesh counts {sorted(set(mesh['counts']))} == single-chip "
          f"{sorted(set(single['counts']))}")
    check(np.array_equal(mesh["grid"], single["grid"]),
          "mesh density bit-identical to single-chip")
    for name in ("serve.oom.hosteval", "serve.ring.fallbacks"):
        v = counter(name)
        check(v == 0, f"{name} = {v:g}")
    print(json.dumps({"rows": args.rows, "chips": args.chips,
                      "count": mesh["counts"][0], "mesh_windows": windows,
                      "mesh_wall_s": mesh["wall_s"],
                      "single_wall_s": single["wall_s"]}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_chip(args.chips)
    log(f"device: {dev}")
    if args.chips == 1:
        one_chip(args)
    else:
        four_chips(args)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
