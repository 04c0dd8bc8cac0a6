"""CQL tests: parser golden cases, extraction, compiled-mask parity vs oracle."""

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.cql import (
    compile_filter,
    extract_bbox,
    extract_intervals,
    parse_cql,
)
from geomesa_tpu.cql import ast
from geomesa_tpu.engine.device import to_device

import reference_engine as oracle

SPEC = "name:String,age:Integer,score:Double,dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2020-06-01T00:00:00", "ms").astype(np.int64))


def make_batch(n=500, seed=0):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec("t", SPEC)
    names = rng.choice(["alpha", "beta", "gamma", "delta"], n).tolist()
    names = [None if i % 17 == 0 else v for i, v in enumerate(names)]
    return FeatureBatch.from_pydict(
        sft,
        {
            "name": names,
            "age": rng.integers(0, 100, n),
            "score": rng.uniform(-5, 5, n),
            "dtg": rng.integers(T0, T0 + 30 * 86400_000, n),
            "geom": np.stack(
                [rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)], axis=1
            ),
        },
        fids=[f"f{i}" for i in range(n)],
    )


class TestParser:
    def test_simple_comparisons(self):
        f = parse_cql("age > 5")
        assert isinstance(f, ast.Comparison) and f.op == ">"
        f = parse_cql("name = 'it''s'")
        assert f.right.value == "it's"

    def test_precedence(self):
        f = parse_cql("age > 5 AND name = 'x' OR score < 3")
        assert isinstance(f, ast.Or)
        assert isinstance(f.children[0], ast.And)

    def test_not_and_parens(self):
        f = parse_cql("NOT (age > 5 OR age < 1)")
        assert isinstance(f, ast.Not) and isinstance(f.child, ast.Or)

    def test_bbox(self):
        f = parse_cql("BBOX(geom, -10, -20, 30, 40)")
        assert isinstance(f, ast.SpatialPredicate)
        assert f.geometry.bbox == (-10.0, -20.0, 30.0, 40.0)
        f2 = parse_cql("BBOX(geom, -10, -20, 30, 40, 'EPSG:4326')")
        assert f2.geometry.bbox == f.geometry.bbox

    def test_intersects_wkt(self):
        f = parse_cql("INTERSECTS(geom, POLYGON ((0 0, 10 0, 10 10, 0 10, 0 0)))")
        assert f.op == "INTERSECTS" and f.geometry.kind == "Polygon"

    def test_dwithin_units(self):
        f = parse_cql("DWITHIN(geom, POINT (1 2), 3, kilometers)")
        assert f.distance_m == 3000.0
        f = parse_cql("DWITHIN(geom, POINT (1 2), 2, nautical miles)")
        assert f.distance_m == 3704.0

    def test_during(self):
        f = parse_cql("dtg DURING 2020-06-01T00:00:00Z/2020-06-02T00:00:00Z")
        assert f.op == "DURING" and f.end - f.start == 86400_000

    def test_during_tz_offset(self):
        f = parse_cql("dtg AFTER 2020-06-01T02:00:00+02:00")
        assert f.start == T0

    def test_between_like_in_null(self):
        assert isinstance(parse_cql("age BETWEEN 1 AND 10"), ast.Between)
        assert isinstance(parse_cql("name LIKE 'a%'"), ast.Like)
        assert parse_cql("name ILIKE 'A%'").case_insensitive
        assert parse_cql("name NOT IN ('a', 'b')").negate
        assert parse_cql("name IS NOT NULL").negate

    def test_include_exclude_empty(self):
        assert isinstance(parse_cql("INCLUDE"), ast.Include)
        assert isinstance(parse_cql("EXCLUDE"), ast.Exclude)
        assert isinstance(parse_cql(""), ast.Include)

    def test_roundtrip_through_to_cql(self):
        texts = [
            "age > 5",
            "BBOX(geom, -10, -20, 30, 40) AND dtg DURING 2020-06-01T00:00:00Z/2020-06-02T00:00:00Z",
            "name IN ('a', 'b') OR NOT (score <= 1.5)",
        ]
        for t in texts:
            f = parse_cql(t)
            f2 = parse_cql(ast.to_cql(f))
            assert f == f2, t

    def test_errors(self):
        for bad in ["age >", "BBOX(geom, 1, 2)", "name LIKE 5 AND", "((age = 1)"]:
            with pytest.raises(ValueError):
                parse_cql(bad)


class TestExtract:
    def test_bbox_and(self):
        f = parse_cql("BBOX(geom, -10, -20, 30, 40) AND age > 5")
        bb = extract_bbox(f, "geom")
        assert (bb.xmin, bb.ymin, bb.xmax, bb.ymax) == (-10, -20, 30, 40)

    def test_bbox_intersection(self):
        f = parse_cql("BBOX(geom, -10, -10, 10, 10) AND BBOX(geom, 0, 0, 20, 20)")
        bb = extract_bbox(f, "geom")
        assert (bb.xmin, bb.ymin, bb.xmax, bb.ymax) == (0, 0, 10, 10)

    def test_bbox_or_union(self):
        f = parse_cql("BBOX(geom, -10, -10, 0, 0) OR BBOX(geom, 5, 5, 20, 20)")
        bb = extract_bbox(f, "geom")
        assert (bb.xmin, bb.ymin, bb.xmax, bb.ymax) == (-10, -10, 20, 20)

    def test_bbox_or_with_unconstrained(self):
        f = parse_cql("BBOX(geom, -10, -10, 0, 0) OR age > 5")
        assert extract_bbox(f, "geom").is_whole_world

    def test_not_is_unconstrained(self):
        f = parse_cql("NOT (BBOX(geom, -10, -10, 0, 0))")
        assert extract_bbox(f, "geom").is_whole_world

    def test_dwithin_buffered(self):
        f = parse_cql("DWITHIN(geom, POINT (0 0), 111.3, kilometers)")
        bb = extract_bbox(f, "geom")
        assert bb.xmin == pytest.approx(-1.0, abs=0.02)
        assert bb.ymax == pytest.approx(1.0, abs=0.02)

    def test_intervals(self):
        f = parse_cql(
            "dtg DURING 2020-06-01T00:00:00Z/2020-06-02T00:00:00Z AND BBOX(geom, 0, 0, 1, 1)"
        )
        iv = extract_intervals(f, "dtg")
        assert iv.start == T0 and iv.end == T0 + 86400_000

    def test_interval_or_union(self):
        f = parse_cql(
            "dtg BEFORE 2020-06-01T00:00:00Z OR dtg AFTER 2020-06-03T00:00:00Z"
        )
        iv = extract_intervals(f, "dtg")
        assert iv.start is None and iv.end is None

    def test_interval_comparison(self):
        f = parse_cql("dtg >= 2020-06-01T00:00:00Z AND dtg < 2020-06-02T00:00:00Z")
        iv = extract_intervals(f, "dtg")
        assert iv.start == T0 and iv.end == T0 + 86400_000


PARITY_FILTERS = [
    "INCLUDE",
    "EXCLUDE",
    "age > 50",
    "age <= 10 OR age >= 90",
    "score BETWEEN -1.0 AND 1.0",
    "17 < age",
    "name = 'alpha'",
    "name <> 'beta'",
    "name < 'c'",
    "name LIKE 'a%'",
    "name LIKE '%ta'",
    "name ILIKE 'AL%'",
    "name NOT LIKE 'a%'",
    "name IN ('alpha', 'gamma')",
    "name NOT IN ('alpha', 'gamma')",
    "age IN (1, 2, 3, 50)",
    "name IS NULL",
    "name IS NOT NULL",
    "score IS NULL",
    "dtg DURING 2020-06-05T00:00:00Z/2020-06-10T00:00:00Z",
    "dtg BEFORE 2020-06-05T00:00:00Z",
    "dtg AFTER 2020-06-20T12:00:00Z",
    "dtg = 2020-06-05T00:00:00Z",
    "BBOX(geom, -20, -20, 20, 20)",
    "BBOX(geom, -20, -20, 20, 20) AND dtg DURING 2020-06-05T00:00:00Z/2020-06-20T00:00:00Z AND age > 30",
    "INTERSECTS(geom, POLYGON ((-30 -30, 30 -30, 30 30, -30 30, -30 -30)))",
    "WITHIN(geom, POLYGON ((-30 -30, 30 -30, 0 40, -30 30, -30 -30)))",
    "INTERSECTS(geom, POLYGON ((-30 -30, 30 -30, 30 30, -30 30, -30 -30), (-10 -10, 10 -10, 10 10, -10 10, -10 -10)))",
    "DISJOINT(geom, POLYGON ((-30 -30, 30 -30, 30 30, -30 30, -30 -30)))",
    "DWITHIN(geom, POINT (0 0), 2000, kilometers)",
    "BEYOND(geom, POINT (10 10), 1000, kilometers)",
    "DWITHIN(geom, LINESTRING (-40 -40, 40 40), 500, kilometers)",
    "NOT (age > 50 AND name = 'alpha')",
    "(name = 'alpha' OR name = 'beta') AND score > 0 AND BBOX(geom, -50, -50, 50, 50)",
    "name NOT BETWEEN 'a' AND 'c'",
    "age NOT BETWEEN 20 AND 80",
    "TOUCHES(geom, POINT (1 2))",
]


class TestCompileParity:
    @pytest.mark.parametrize("cql", PARITY_FILTERS)
    def test_parity(self, cql):
        import jax.numpy as jnp

        batch = make_batch(500)
        f = parse_cql(cql)
        expected = oracle.eval_filter(f, batch)
        compiled = compile_filter(f, batch.sft)
        dev = to_device(batch, coord_dtype=jnp.float64)
        got = np.asarray(compiled.mask(dev, batch))
        np.testing.assert_array_equal(got, expected, err_msg=cql)

    def test_parity_with_padding(self):
        import jax.numpy as jnp

        batch = make_batch(100).pad_to(128)
        f = parse_cql("age >= 0")  # matches everything valid
        compiled = compile_filter(f, batch.sft)
        dev = to_device(batch, coord_dtype=jnp.float64)
        got = np.asarray(compiled.mask(dev, batch))
        assert got.sum() == 100  # padding never matches

    def test_unknown_attribute_raises(self):
        batch = make_batch(10)
        with pytest.raises(ValueError, match="unknown attribute"):
            compile_filter(parse_cql("bogus = 1"), batch.sft)

    def test_param_reuse_across_batches(self):
        import jax.numpy as jnp

        f = parse_cql("name = 'alpha' AND age > 30")
        b1 = make_batch(200, seed=1)
        compiled = compile_filter(f, b1.sft)
        for seed in (1, 2, 3):
            b = make_batch(200, seed=seed)
            dev = to_device(b, coord_dtype=jnp.float64)
            got = np.asarray(compiled.mask(dev, b))
            np.testing.assert_array_equal(got, oracle.eval_filter(f, b))


POLY_SPEC = "name:String,*geom:Polygon"

POLY_FILTERS = [
    "BBOX(geom, 2, 2, 8, 8)",
    "INTERSECTS(geom, POLYGON ((2 2, 8 2, 8 8, 2 8, 2 2)))",
    "WITHIN(geom, POLYGON ((-1 -1, 11 -1, 11 11, -1 11, -1 -1)))",
    "CONTAINS(geom, POINT (3.5 3.5))",
    "CONTAINS(geom, POLYGON ((3.1 3.1, 3.4 3.1, 3.4 3.4, 3.1 3.4, 3.1 3.1)))",
    "DISJOINT(geom, POLYGON ((20 20, 30 20, 30 30, 20 30, 20 20)))",
    "DWITHIN(geom, POINT (12 5), 300, kilometers)",
]


def make_poly_batch(n=60, seed=3):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec("p", POLY_SPEC)
    wkts = []
    for i in range(n):
        cx, cy = rng.uniform(0, 10, 2)
        w, h = rng.uniform(0.2, 3.0, 2)
        wkts.append(
            f"POLYGON (({cx-w} {cy-h}, {cx+w} {cy-h}, {cx+w} {cy+h}, {cx-w} {cy+h}, {cx-w} {cy-h}))"
        )
    return FeatureBatch.from_pydict(
        sft, {"name": [f"p{i}" for i in range(n)], "geom": wkts}
    )


class TestExtendedGeometryParity:
    @pytest.mark.parametrize("cql", POLY_FILTERS)
    def test_parity(self, cql):
        import jax.numpy as jnp

        batch = make_poly_batch()
        f = parse_cql(cql)
        expected = oracle.eval_filter(f, batch)
        compiled = compile_filter(f, batch.sft)
        dev = to_device(batch, coord_dtype=jnp.float64)
        got = np.asarray(compiled.mask(dev, batch))
        np.testing.assert_array_equal(got, expected, err_msg=cql)

    def test_linestring_data_parity(self):
        import jax.numpy as jnp

        sft = SimpleFeatureType.from_spec("l", "name:String,*geom:LineString")
        batch = FeatureBatch.from_pydict(
            sft,
            {
                "name": ["through", "outside", "inside"],
                "geom": [
                    "LINESTRING (0 0, 10 5)",       # passes through the literal
                    "LINESTRING (20 20, 30 25)",    # far away
                    "LINESTRING (1.2 2.2, 1.8 2.8)",  # wholly inside
                ],
            },
        )
        dev = to_device(batch, coord_dtype=jnp.float64)
        for cql, expect in [
            ("INTERSECTS(geom, POLYGON ((1 2, 6 2, 6 4, 1 4, 1 2)))", [True, False, True]),
            ("WITHIN(geom, POLYGON ((1 2, 6 2, 6 4, 1 4, 1 2)))", [False, False, True]),
            ("DISJOINT(geom, POLYGON ((1 2, 2 2, 2 3, 1 3, 1 2)))", [True, True, False]),
        ]:
            f = parse_cql(cql)
            got = np.asarray(compile_filter(f, sft).mask(dev, batch)).tolist()
            assert got == expect, cql
            np.testing.assert_array_equal(got, oracle.eval_filter(f, batch), err_msg=cql)

    def test_known_answers(self):
        import jax.numpy as jnp

        sft = SimpleFeatureType.from_spec("p", POLY_SPEC)
        batch = FeatureBatch.from_pydict(
            sft,
            {
                "name": ["inside", "straddle", "outside", "surrounds"],
                "geom": [
                    "POLYGON ((1 1, 2 1, 2 2, 1 2, 1 1))",
                    "POLYGON ((4 4, 6 4, 6 6, 4 6, 4 4))",
                    "POLYGON ((20 20, 21 20, 21 21, 20 21, 20 20))",
                    "POLYGON ((-5 -5, 15 -5, 15 15, -5 15, -5 -5))",
                ],
            },
        )
        dev = to_device(batch, coord_dtype=jnp.float64)
        lit = "POLYGON ((0 0, 5 0, 5 5, 0 5, 0 0))"
        got = lambda cql: np.asarray(
            compile_filter(parse_cql(cql), sft).mask(dev, batch)
        ).tolist()
        assert got(f"INTERSECTS(geom, {lit})") == [True, True, False, True]
        assert got(f"WITHIN(geom, {lit})") == [True, False, False, False]
        assert got(f"DISJOINT(geom, {lit})") == [False, False, True, False]
        assert got(f"CONTAINS(geom, POINT (1.5 1.5))") == [True, False, False, True]


class TestBBoxBandExactCount:
    """f64-exact counts under f32 device coords (round 4, VERDICT #5):
    points planted within f32-ulp of bbox edges must count exactly."""

    def _batch(self):
        import numpy as np

        from geomesa_tpu.core.columnar import FeatureBatch
        from geomesa_tpu.core.sft import SimpleFeatureType

        rng = np.random.default_rng(61)
        n = 4096
        sft = SimpleFeatureType.from_spec("t", "score:Double,*geom:Point")
        x = rng.uniform(-170, 170, n)
        y = rng.uniform(-80, 80, n)
        # adversarial: coordinates straddling the bbox edge x=60 closer
        # than f32 can represent (f32(60 +- 2e-6) rounds to 60.000002/
        # 59.999998 unpredictably vs the f64 truth)
        for i in range(64):
            x[i] = 60.0 + rng.uniform(-1, 1) * 2.0e-6
            y[i] = rng.uniform(-20, 20)
        return sft, FeatureBatch.from_pydict(
            sft, {"score": rng.uniform(-1, 1, n),
                  "geom": np.stack([x, y], 1)}), x, y

    def test_count_exact_matches_f64(self):
        import jax.numpy as jnp
        import numpy as np

        from geomesa_tpu.cql import compile_filter, parse_cql
        from geomesa_tpu.engine.device import to_device

        sft, batch, x, y = self._batch()
        f = parse_cql("BBOX(geom, -60, -30, 60, 30)")
        compiled = compile_filter(f, sft)
        assert compiled.has_band  # bbox filters now carry a band
        dev = to_device(batch, coord_dtype=jnp.float32)

        def count_exact(extra=None):
            # the planner's count: device sum + the f64 band correction
            m = compiled.mask(dev, batch)
            if extra is not None:
                m = m & extra
            return (int(jnp.sum(m, dtype=jnp.int64))
                    + compiled.band_count_correction(dev, batch, m, extra))

        got = count_exact()
        exp = int(np.sum((x >= -60) & (x <= 60) & (y >= -30) & (y <= 30)))
        assert got == exp
        # extra mask participates in both count and correction
        extra = jnp.asarray(np.arange(len(batch)) % 2 == 0)
        got_e = count_exact(extra)
        exp_e = int(np.sum((x >= -60) & (x <= 60) & (y >= -30) & (y <= 30)
                           & (np.arange(len(batch)) % 2 == 0)))
        assert got_e == exp_e

    def test_store_count_exact(self, tmp_path):
        import numpy as np

        from geomesa_tpu.plan.datastore import DataStore

        sft, batch, x, y = self._batch()
        for cached in (False, True):
            ds = DataStore(str(tmp_path / ("c" if cached else "p")),
                           use_device_cache=cached)
            src = ds.create_schema(sft)
            src.write(batch)
            got = src.get_count("BBOX(geom, -60, -30, 60, 30)")
            exp = int(np.sum(
                (x >= -60) & (x <= 60) & (y >= -30) & (y <= 30)))
            assert got == exp, ("cached" if cached else "scan")
