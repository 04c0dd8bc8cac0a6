"""SQL pushdown engine + parallel jobs tests."""

import json
import os

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.jobs import export_partitions, ingest_files
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.sql.engine import SqlContext, SqlError

from tests.reference_engine import eval_filter
from geomesa_tpu.cql import parse_cql


def make_store(tmp_path, n=400, seed=21):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "gdelt", "actor:String,score:Double,dtg:Date,*geom:Point"
    )
    batch = FeatureBatch.from_pydict(
        sft,
        {
            "actor": rng.choice(["USA", "FRA", "CHN"], n).tolist(),
            "score": rng.uniform(-10, 10, n),
            "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
            "geom": np.stack(
                [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1
            ),
        },
    )
    ds = DataStore(str(tmp_path / "cat"))
    ds.create_schema(sft).write(batch)
    return sft, batch, ds


class TestSqlEngine:
    def test_select_where_pushdown_parity(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, score FROM gdelt WHERE "
            "st_intersects(geom, st_makeBBOX(-60, -30, 60, 30)) "
            "AND score > 2.5"
        )
        f = parse_cql("BBOX(geom, -60, -30, 60, 30) AND score > 2.5")
        assert r.count == int(eval_filter(f, batch).sum())
        assert list(r.features.sft.attribute_names) == ["actor", "score"]

    def test_count_star(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql("SELECT COUNT(*) FROM gdelt WHERE actor = 'USA'")
        f = parse_cql("actor = 'USA'")
        assert r.kind == "count"
        assert r.count == int(eval_filter(f, batch).sum())

    def test_order_limit(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT score FROM gdelt WHERE score > 0 "
            "ORDER BY score DESC LIMIT 5"
        )
        got = np.asarray(r.features.columns["score"])
        allv = np.asarray(batch.columns["score"])
        exp = np.sort(allv[allv > 0])[::-1][:5]
        np.testing.assert_allclose(got, exp)

    def test_contains_argument_flip(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        wkt = "POLYGON ((-60 -30, 60 -30, 60 30, -60 30, -60 -30))"
        a = ctx.sql(
            f"SELECT COUNT(*) FROM gdelt WHERE st_contains(st_geomFromWKT('{wkt}'), geom)"
        )
        b = ctx.sql(
            f"SELECT COUNT(*) FROM gdelt WHERE st_within(geom, st_geomFromWKT('{wkt}'))"
        )
        assert a.count == b.count > 0

    def test_temporal_between(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT COUNT(*) FROM gdelt WHERE dtg BETWEEN "
            "'2020-06-01T00:00:00Z' AND '2020-08-01T00:00:00Z'"
        )
        t = np.asarray(batch.columns["dtg"])
        f = parse_cql(
            "dtg >= 2020-06-01T00:00:00Z AND dtg <= 2020-08-01T00:00:00Z"
        )
        assert r.count == int(eval_filter(f, batch).sum())

    def test_dwithin_meters(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT COUNT(*) FROM gdelt WHERE "
            "st_dwithin(geom, st_point(0, 0), 2000000)"
        )
        f = parse_cql("DWITHIN(geom, POINT (0 0), 2000000, meters)")
        assert r.count == int(eval_filter(f, batch).sum())

    def test_compute_predicate_local_fallback(self, tmp_path):
        # non-pushable scalar st_* predicates post-filter locally
        # (LocalQueryRunner contract) instead of raising (round-1 weak #7)
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql("SELECT * FROM gdelt WHERE st_area(geom) > 2")
        assert r.features is None or len(r.features) == 0  # points: area 0
        r = ctx.sql(
            "SELECT * FROM gdelt WHERE st_x(geom) > 0 AND score > 0"
        )
        exp = int(
            ((np.asarray(batch.columns["geom"].x) > 0)
             & (np.asarray(batch.column("score")) > 0)).sum()
        )
        assert (0 if r.features is None else len(r.features)) == exp
        # under OR the index part would be unsound -> still raises clearly
        with pytest.raises(SqlError, match="OR over a non-pushable"):
            ctx.sql(
                "SELECT * FROM gdelt WHERE st_x(geom) > 0 OR score > 0"
            )

    def test_in_like_null(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT COUNT(*) FROM gdelt WHERE actor IN ('USA', 'FRA')"
        )
        f = parse_cql("actor IN ('USA', 'FRA')")
        assert r.count == int(eval_filter(f, batch).sum())
        r2 = ctx.sql("SELECT COUNT(*) FROM gdelt WHERE actor LIKE 'U%'")
        assert r2.count == int(
            eval_filter(parse_cql("actor LIKE 'U%'"), batch).sum()
        )


class TestJobs:
    def _csv_files(self, tmp_path, n_files=4, rows=30):
        paths = []
        rng = np.random.default_rng(0)
        for i in range(n_files):
            p = tmp_path / f"in_{i}.csv"
            lines = []
            for j in range(rows):
                lines.append(
                    f"a{i}_{j},{rng.uniform(-10, 10):.3f},"
                    f"2020-06-0{1 + (j % 9)}T00:00:00Z,"
                    f"{rng.uniform(-170, 170):.4f},{rng.uniform(-80, 80):.4f}"
                )
            p.write_text("\n".join(lines) + "\n")
            paths.append(str(p))
        return paths

    def _converter_cfg(self):
        return {
            "type": "delimited-text",
            "format": "CSV",
            "id-field": "$1",
            "fields": [
                {"name": "actor", "transform": "$1::string"},
                {"name": "score", "transform": "$2::double"},
                {"name": "dtg", "transform": "isoDateTime($3)"},
                {"name": "geom", "transform": "point($4::double, $5::double)"},
            ],
        }

    def test_parallel_ingest_and_resume(self, tmp_path):
        from geomesa_tpu.convert import converter_from_config

        sft = SimpleFeatureType.from_spec(
            "t", "actor:String,score:Double,dtg:Date,*geom:Point"
        )
        ds = DataStore(str(tmp_path / "cat"))
        src = ds.create_schema(sft)
        files = self._csv_files(tmp_path)
        cfg = self._converter_cfg()
        factory = lambda: converter_from_config(sft, cfg)
        rep = ingest_files(src, factory, files, workers=3)
        assert not rep.files_failed
        assert rep.features == 4 * 30
        assert src.get_count("INCLUDE") == 120
        # re-run: everything skipped, nothing double-written
        rep2 = ingest_files(src, factory, files, workers=3)
        assert sorted(rep2.skipped) == sorted(files)
        assert rep2.features == 0
        assert src.get_count("INCLUDE") == 120

    def test_ingest_failure_isolation(self, tmp_path):
        from geomesa_tpu.convert import converter_from_config

        sft = SimpleFeatureType.from_spec(
            "t", "actor:String,score:Double,dtg:Date,*geom:Point"
        )
        ds = DataStore(str(tmp_path / "cat"))
        src = ds.create_schema(sft)
        files = self._csv_files(tmp_path, n_files=2)
        missing = str(tmp_path / "nope.csv")
        cfg = self._converter_cfg()
        rep = ingest_files(
            src, lambda: converter_from_config(sft, cfg), files + [missing],
            workers=2,
        )
        assert len(rep.files_ok) == 2
        assert len(rep.files_failed) == 1 and missing in rep.files_failed[0]
        assert src.get_count("INCLUDE") == 60

    def test_export_partitions(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        src = ds.get_feature_source("gdelt")
        out = {}

        def writer(name, b):
            out[name] = len(b)

        names = export_partitions(src, writer, cql="score > 0", workers=3)
        assert names
        f = parse_cql("score > 0")
        assert sum(out.values()) == int(eval_filter(f, batch).sum())


class TestSqlAggregation:
    """GROUP BY / aggregates via device segment reductions (round-1
    missing #3; SURVEY.md:381-383)."""

    def _oracle_groups(self, batch, mask=None):
        actors = np.array(
            ["" if a is None else a for a in batch.columns["actor"].decode()]
        )
        scores = np.asarray(batch.column("score"))
        if mask is not None:
            actors, scores = actors[mask], scores[mask]
        out = {}
        for a in np.unique(actors):
            s = scores[actors == a]
            out[a] = (len(s), s.sum(), s.min(), s.max(), s.mean())
        return out

    def test_group_by_aggregates_parity(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, COUNT(*), SUM(score), MIN(score), MAX(score), "
            "AVG(score) AS mean_score FROM gdelt GROUP BY actor "
            "ORDER BY actor"
        )
        t = r.features
        exp = self._oracle_groups(batch)
        assert len(t) == len(exp)
        actors = t.columns["actor"].decode()
        assert actors == sorted(exp)
        for i, a in enumerate(actors):
            cnt, s, lo, hi, mean = exp[a]
            assert int(np.asarray(t.column("count"))[i]) == cnt
            np.testing.assert_allclose(
                np.asarray(t.column("sum_score"))[i], s, rtol=1e-9)
            np.testing.assert_allclose(
                np.asarray(t.column("min_score"))[i], lo, rtol=1e-9)
            np.testing.assert_allclose(
                np.asarray(t.column("max_score"))[i], hi, rtol=1e-9)
            np.testing.assert_allclose(
                np.asarray(t.column("mean_score"))[i], mean, rtol=1e-9)

    def test_group_by_with_where_and_order_limit(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, COUNT(*) AS n FROM gdelt WHERE score > 0 "
            "GROUP BY actor ORDER BY n DESC LIMIT 2"
        )
        t = r.features
        mask = np.asarray(batch.column("score")) > 0
        exp = self._oracle_groups(batch, mask)
        counts = sorted((c for c, *_ in exp.values()), reverse=True)[:2]
        assert np.asarray(t.column("n")).tolist() == counts

    def test_global_aggregates_single_row(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT COUNT(*) AS n, AVG(score) AS m FROM gdelt"
        )
        t = r.features
        assert len(t) == 1
        assert int(np.asarray(t.column("n"))[0]) == len(batch)
        np.testing.assert_allclose(
            np.asarray(t.column("m"))[0],
            np.asarray(batch.column("score")).mean(),
            rtol=1e-9,
        )

    def test_group_by_multi_key(self, tmp_path):
        sft, batch, ds = make_store(tmp_path, n=300, seed=5)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, COUNT(*) AS n FROM gdelt "
            "WHERE st_intersects(geom, st_makeBBOX(-100, -60, 100, 60)) "
            "GROUP BY actor ORDER BY actor"
        )
        t = r.features
        f = parse_cql("BBOX(geom, -100, -60, 100, 60)")
        mask = eval_filter(f, batch)
        exp = self._oracle_groups(batch, mask)
        got = dict(zip(t.columns["actor"].decode(),
                       np.asarray(t.column("n")).tolist()))
        assert got == {a: c for a, (c, *_) in exp.items()}

    def test_bare_column_outside_group_by_rejected(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="must appear in GROUP BY"):
            ctx.sql("SELECT score, COUNT(*) FROM gdelt GROUP BY actor")

    def test_sum_of_string_rejected(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="cannot aggregate string"):
            ctx.sql("SELECT SUM(actor) FROM gdelt")


class TestStBuffer:
    def test_buffer_in_where_via_pushdown(self, tmp_path):
        # st_buffer literal feeds a pushable spatial predicate
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        from geomesa_tpu.sql.functions import st_buffer, st_point, st_asText

        poly = st_buffer(st_point(0.0, 0.0), 40.0)
        r = ctx.sql(
            "SELECT COUNT(*) FROM gdelt WHERE "
            f"st_within(geom, st_geomFromWKT('{st_asText(poly)}'))"
        )
        from geomesa_tpu.engine.pip import points_in_polygon_np

        g = batch.columns["geom"]
        exp = int(points_in_polygon_np(g.x, g.y, poly).sum())
        assert abs(r.count - exp) <= max(2, exp // 200)

    def test_null_skipping_and_empty_set_semantics(self, tmp_path):
        # SQL NULL semantics: NaN doubles are skipped by SUM/MIN/MAX/AVG,
        # COUNT(col) counts non-null only; empty sets yield NULL (NaN) for
        # MIN/MAX/AVG and 0 for COUNT (round-2 review findings)
        rng = np.random.default_rng(9)
        sft = SimpleFeatureType.from_spec(
            "t", "actor:String,score:Double,*geom:Point"
        )
        scores = np.array([1.0, np.nan, 3.0, np.nan, 5.0])
        batch = FeatureBatch.from_pydict(
            sft,
            {
                "actor": ["a", "a", "a", "b", "b"],
                "score": scores,
                "geom": rng.uniform(-10, 10, (5, 2)),
            },
        )
        ds = DataStore(str(tmp_path / "cat"))
        ds.create_schema(sft).write(batch)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, COUNT(*) AS n, COUNT(score) AS nn, "
            "SUM(score) AS s, MIN(score) AS lo, AVG(score) AS m "
            "FROM t GROUP BY actor ORDER BY actor"
        )
        t = r.features
        assert np.asarray(t.column("n")).tolist() == [3, 2]
        assert np.asarray(t.column("nn")).tolist() == [2, 1]
        np.testing.assert_allclose(np.asarray(t.column("s")), [4.0, 5.0])
        np.testing.assert_allclose(np.asarray(t.column("lo")), [1.0, 5.0])
        np.testing.assert_allclose(np.asarray(t.column("m")), [2.0, 5.0])
        # empty set
        r = ctx.sql(
            "SELECT COUNT(*) AS n, MIN(score) AS lo, AVG(score) AS m "
            "FROM t WHERE score > 1000000000"
        )
        t = r.features
        assert int(np.asarray(t.column("n"))[0]) == 0
        assert np.isnan(np.asarray(t.column("lo"))[0])
        assert np.isnan(np.asarray(t.column("m"))[0])


class TestSqlJoin:
    """Inner equi-join with per-side pushdown (SURVEY.md:381-383 relation
    joins)."""

    def _two_tables(self, tmp_path):
        rng = np.random.default_rng(31)
        events_sft = SimpleFeatureType.from_spec(
            "events", "actor:String,score:Double,*geom:Point"
        )
        n = 200
        actors = rng.choice(["USA", "FRA", "CHN", "XXX"], n)
        events = FeatureBatch.from_pydict(events_sft, {
            "actor": actors.tolist(),
            "score": rng.uniform(-10, 10, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1)})
        countries_sft = SimpleFeatureType.from_spec(
            "countries", "code:String,pop:Double,*geom:Point"
        )
        countries = FeatureBatch.from_pydict(countries_sft, {
            "code": ["USA", "FRA", "CHN", "GBR"],
            "pop": [331.0, 67.0, 1412.0, 67.2],
            "geom": np.array([[-98.0, 39.0], [2.0, 46.0],
                              [104.0, 35.0], [-2.0, 54.0]])})
        ds = DataStore(str(tmp_path / "cat"))
        ds.create_schema(events_sft).write(events)
        ds.create_schema(countries_sft).write(countries)
        return ds, events, countries, actors

    def test_join_parity(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, e.score, c.pop FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "WHERE e.score > 0 AND c.pop > 100"
        )
        t = r.features
        scores = np.asarray(events.column("score"))
        pops = dict(zip(countries.columns["code"].decode(),
                        np.asarray(countries.column("pop"))))
        exp = sum(
            1 for a, s in zip(actors, scores)
            if s > 0 and a in pops and pops[a] > 100
        )
        assert len(t) == exp
        got_pop = np.asarray(t.column("pop"))
        got_actor = t.columns["actor"].decode()
        for a, p in zip(got_actor, got_pop):
            assert pops[a] == p and pops[a] > 100
        # XXX actors (no matching country) never appear
        assert "XXX" not in set(got_actor)

    def test_join_order_limit_and_aliases(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.score AS s, c.code FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "ORDER BY s DESC LIMIT 5"
        )
        t = r.features
        assert len(t) == 5
        s = np.asarray(t.column("s"))
        assert (np.diff(s) <= 0).all()
        scores = np.asarray(events.column("score"))
        joined = scores[np.isin(actors, ["USA", "FRA", "CHN", "GBR"])]
        np.testing.assert_allclose(s, np.sort(joined)[::-1][:5])

    def test_join_errors(self, tmp_path):
        ds, *_ = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="select list"):
            ctx.sql("SELECT * FROM events e JOIN countries c ON e.actor = c.code")
        with pytest.raises(SqlError, match="ambiguous"):
            ctx.sql("SELECT geom FROM events e JOIN countries c ON e.actor = c.code")
        with pytest.raises(SqlError, match="two tables"):
            ctx.sql("SELECT e.actor FROM events e JOIN countries c ON e.actor = e.actor")

    def test_join_spatial_pushdown_per_side(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor FROM events e JOIN countries c "
            "ON e.actor = c.code "
            "WHERE st_intersects(e.geom, st_makeBBOX(-90, -45, 90, 45))"
        )
        g = events.columns["geom"]
        sel = (g.x >= -90) & (g.x <= 90) & (g.y >= -45) & (g.y <= 45)
        exp = sum(
            1 for a, m in zip(actors, sel)
            if m and a in ("USA", "FRA", "CHN", "GBR")
        )
        assert (0 if r.features is None else len(r.features)) == exp

    def test_join_empty_side_and_between(self, tmp_path):
        # (round-2 review) an empty side must yield an empty result, not
        # crash; BETWEEN's AND must not split JOIN WHERE conjuncts
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, c.pop FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "WHERE e.score > 1000000000"
        )
        assert len(r.features) == 0 and r.count == 0
        r = ctx.sql(
            "SELECT e.actor FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "WHERE e.score BETWEEN 0 AND 5 AND c.pop > 100"
        )
        scores = np.asarray(events.column("score"))
        pops = dict(zip(countries.columns["code"].decode(),
                        np.asarray(countries.column("pop"))))
        exp = sum(1 for a, s in zip(actors, scores)
                  if 0 <= s <= 5 and a in pops and pops[a] > 100)
        assert (0 if r.features is None else len(r.features)) == exp

    def test_single_table_alias_binds(self, tmp_path):
        # (round-2 review) a consumed alias must resolve qualified refs
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql("SELECT e.score FROM events e WHERE e.score > 0 "
                    "ORDER BY e.score DESC LIMIT 3")
        scores = np.asarray(events.column("score"))
        np.testing.assert_allclose(
            np.asarray(r.features.column("score")),
            np.sort(scores[scores > 0])[::-1][:3])

    def test_join_parenthesized_between_and_alias_collision(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor FROM events e JOIN countries c "
            "ON e.actor = c.code "
            "WHERE (e.score BETWEEN 0 AND 5) AND c.pop > 100"
        )
        scores = np.asarray(events.column("score"))
        pops = dict(zip(countries.columns["code"].decode(),
                        np.asarray(countries.column("pop"))))
        exp = sum(1 for a, s in zip(actors, scores)
                  if 0 <= s <= 5 and a in pops and pops[a] > 100)
        assert (0 if r.features is None else len(r.features)) == exp
        with pytest.raises(SqlError, match="duplicate output column"):
            ctx.sql("SELECT e.score AS pop, c.pop FROM events e "
                    "JOIN countries c ON e.actor = c.code")

    def test_join_group_by_aggregates(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT c.code, COUNT(*) AS n, AVG(e.score) AS m, SUM(c.pop) "
            "FROM events e JOIN countries c ON e.actor = c.code "
            "WHERE e.score > 0 GROUP BY c.code ORDER BY c.code"
        )
        t = r.features
        scores = np.asarray(events.column("score"))
        pops = dict(zip(countries.columns["code"].decode(),
                        np.asarray(countries.column("pop"))))
        exp = {}
        for a, s in zip(actors, scores):
            if s > 0 and a in pops:
                cnt, tot = exp.get(a, (0, 0.0))
                exp[a] = (cnt + 1, tot + s)
        codes = t.columns["code"].decode()
        assert codes == sorted(exp)
        for i, a in enumerate(codes):
            cnt, tot = exp[a]
            assert int(np.asarray(t.column("n"))[i]) == cnt
            np.testing.assert_allclose(
                np.asarray(t.column("m"))[i], tot / cnt, rtol=1e-9)
            np.testing.assert_allclose(
                np.asarray(t.column("sum_pop"))[i], pops[a] * cnt, rtol=1e-9)

    def test_join_global_aggregate(self, tmp_path):
        ds, events, countries, actors = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT COUNT(*) AS n FROM events e "
            "JOIN countries c ON e.actor = c.code"
        )
        exp = sum(1 for a in actors if a in ("USA", "FRA", "CHN", "GBR"))
        assert int(np.asarray(r.features.column("n"))[0]) == exp

    def test_join_aggregate_duplicate_alias_rejected(self, tmp_path):
        ds, *_ = self._two_tables(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="duplicate output column"):
            ctx.sql("SELECT COUNT(*) AS x, SUM(e.score) AS x FROM events e "
                    "JOIN countries c ON e.actor = c.code")


class TestSqlHaving:
    """HAVING + COUNT(*) LIMIT semantics (round-2 advisor findings)."""

    def test_count_star_limit_not_capped(self, tmp_path):
        # LIMIT applies to the single result row, never to the counted rows
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        full = ctx.sql("SELECT COUNT(*) FROM gdelt WHERE score > 0").count
        assert full > 5
        r = ctx.sql("SELECT COUNT(*) FROM gdelt WHERE score > 0 LIMIT 5")
        assert r.count == full

    def test_having_on_group_by(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT actor, COUNT(*) AS n, AVG(score) AS m FROM gdelt "
            "GROUP BY actor HAVING COUNT(*) > 100 AND m > -5 ORDER BY actor"
        )
        actors = batch.columns["actor"].decode()
        scores = np.asarray(batch.column("score"))
        exp = {}
        for a, s in zip(actors, scores):
            c, t = exp.get(a, (0, 0.0))
            exp[a] = (c + 1, t + s)
        keep = sorted(
            a for a, (c, t) in exp.items() if c > 100 and t / c > -5
        )
        t = r.features
        assert t.columns["actor"].decode() == keep
        for i, a in enumerate(keep):
            assert int(np.asarray(t.column("n"))[i]) == exp[a][0]

    def test_having_agg_not_selected_rejected(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="not in the\n?.*select list|not in the select"):
            ctx.sql(
                "SELECT actor, COUNT(*) FROM gdelt GROUP BY actor "
                "HAVING SUM(score) > 0"
            )

    def test_having_without_aggregates_rejected(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="HAVING requires"):
            ctx.sql("SELECT actor FROM gdelt HAVING actor = 'USA'")

    def test_join_having_qualified_agg(self, tmp_path):
        rng = np.random.default_rng(31)
        events_sft = SimpleFeatureType.from_spec(
            "events", "actor:String,score:Double,*geom:Point"
        )
        n = 200
        actors = rng.choice(["USA", "FRA", "CHN", "XXX"], n)
        events = FeatureBatch.from_pydict(events_sft, {
            "actor": actors.tolist(),
            "score": rng.uniform(-10, 10, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1)})
        countries_sft = SimpleFeatureType.from_spec(
            "countries", "code:String,pop:Double,*geom:Point"
        )
        countries = FeatureBatch.from_pydict(countries_sft, {
            "code": ["USA", "FRA", "CHN", "GBR"],
            "pop": [331.0, 67.0, 1412.0, 67.2],
            "geom": np.array([[-98.0, 39.0], [2.0, 46.0],
                              [104.0, 35.0], [-2.0, 54.0]])})
        ds = DataStore(str(tmp_path / "cat"))
        ds.create_schema(events_sft).write(events)
        ds.create_schema(countries_sft).write(countries)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT c.code, COUNT(*) AS n, SUM(e.score) FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "GROUP BY c.code HAVING SUM(e.score) > 0 ORDER BY c.code"
        )
        scores = np.asarray(events.column("score"))
        exp = {}
        for a, s in zip(actors, scores):
            if a in ("USA", "FRA", "CHN", "GBR"):
                c, t = exp.get(a, (0, 0.0))
                exp[a] = (c + 1, t + s)
        keep = sorted(a for a, (c, t) in exp.items() if t > 0)
        assert r.features.columns["code"].decode() == keep

    def test_join_order_by_unambiguous_bare_name(self, tmp_path):
        # both sides carry 'geom'; 'pop' only exists on countries but was
        # renamed is not the case -- select both sides' score-like columns
        rng = np.random.default_rng(31)
        a_sft = SimpleFeatureType.from_spec("ta", "k:String,v:Double,*geom:Point")
        b_sft = SimpleFeatureType.from_spec("tb", "k:String,w:Double,*geom:Point")
        na = 20
        ka = rng.choice(["p", "q"], na)
        ds = DataStore(str(tmp_path / "cat"))
        ds.create_schema(a_sft).write(FeatureBatch.from_pydict(a_sft, {
            "k": ka.tolist(), "v": rng.uniform(0, 1, na),
            "geom": np.stack([rng.uniform(-10, 10, na),
                              rng.uniform(-10, 10, na)], 1)}))
        ds.create_schema(b_sft).write(FeatureBatch.from_pydict(b_sft, {
            "k": ["p", "q"], "w": [1.0, 2.0],
            "geom": np.array([[0.0, 0.0], [1.0, 1.0]])}))
        ctx = SqlContext(ds)
        # 'k' exists on both sides -> selected a.k is renamed a_k; the bare
        # spelling still resolves because only ONE selected output carries it
        r = ctx.sql(
            "SELECT a.k, a.v FROM ta a JOIN tb b ON a.k = b.k ORDER BY k"
        )
        got = r.features.columns["a_k"].decode()
        assert got == sorted(got)
        # ambiguous bare name in ORDER BY lists valid spellings
        with pytest.raises(SqlError, match="valid spellings"):
            ctx.sql(
                "SELECT a.k AS x, b.k AS yz, a.v FROM ta a "
                "JOIN tb b ON a.k = b.k ORDER BY nosuch"
            )

    def test_having_review_fixes(self, tmp_path):
        # string-vs-number HAVING comparisons error instead of silently
        # stringifying; COUNT(*) LIMIT 0 yields zero rows; qualified group
        # keys resolve in JOIN HAVING
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="string column"):
            ctx.sql("SELECT actor, COUNT(*) FROM gdelt GROUP BY actor "
                    "HAVING actor > 5")
        with pytest.raises(SqlError, match="numeric column"):
            ctx.sql("SELECT actor, COUNT(*) AS n FROM gdelt GROUP BY actor "
                    "HAVING n = 'x'")
        r = ctx.sql("SELECT COUNT(*) FROM gdelt LIMIT 0")
        assert r.features is not None and len(r.features) == 0

    def test_join_having_qualified_group_key(self, tmp_path):
        ds, events, countries, actors = TestSqlJoin()._two_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT c.code, COUNT(*) AS n FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "GROUP BY c.code HAVING c.code <> 'USA' ORDER BY c.code"
        )
        got = r.features.columns["code"].decode()
        assert "USA" not in got and got == sorted(got)

    def test_join_having_raw_column_rejected(self, tmp_path):
        # a raw ungrouped column in JOIN HAVING must error, not silently
        # become its aggregate
        ds, events, countries, actors = TestSqlJoin()._two_tables(tmp_path)
        ctx = SqlContext(ds)
        with pytest.raises(SqlError, match="unknown column"):
            ctx.sql(
                "SELECT c.code, SUM(e.score) FROM events e "
                "JOIN countries c ON e.actor = c.code "
                "GROUP BY c.code HAVING e.score > 0"
            )


class TestSqlJoinVariants:
    """Round-3 surface: multi-table chains, LEFT/RIGHT OUTER,
    DISTINCT."""

    def _three_tables(self, tmp_path):
        rng = np.random.default_rng(37)
        ev_sft = SimpleFeatureType.from_spec(
            "events", "actor:String,score:Double,*geom:Point")
        n = 120
        actors = rng.choice(["USA", "FRA", "CHN", "XXX"], n)
        ds = DataStore(str(tmp_path / "cat"))
        ds.create_schema(ev_sft).write(FeatureBatch.from_pydict(ev_sft, {
            "actor": actors.tolist(),
            "score": rng.uniform(-10, 10, n),
            "geom": np.stack([rng.uniform(-170, 170, n),
                              rng.uniform(-80, 80, n)], 1)}))
        c_sft = SimpleFeatureType.from_spec(
            "countries", "code:String,region:String,pop:Double,*geom:Point")
        ds.create_schema(c_sft).write(FeatureBatch.from_pydict(c_sft, {
            "code": ["USA", "FRA", "CHN", "GBR"],
            "region": ["AM", "EU", "AS", "EU"],
            "pop": [331.0, 67.0, 1412.0, 67.2],
            "geom": np.array([[-98.0, 39.0], [2.0, 46.0],
                              [104.0, 35.0], [-2.0, 54.0]])}))
        r_sft = SimpleFeatureType.from_spec(
            "regions", "rcode:String,rname:String,*geom:Point")
        ds.create_schema(r_sft).write(FeatureBatch.from_pydict(r_sft, {
            "rcode": ["AM", "EU"],
            "rname": ["America", "Europe"],
            "geom": np.array([[-90.0, 40.0], [10.0, 50.0]])}))
        return ds, actors

    def test_three_table_chain(self, tmp_path):
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, c.region, r.rname FROM events e "
            "JOIN countries c ON e.actor = c.code "
            "JOIN regions r ON c.region = r.rcode "
            "ORDER BY e.actor"
        )
        t = r.features
        reg = {"USA": "AM", "FRA": "EU", "CHN": None, "GBR": "EU"}
        exp = sum(1 for a in actors if reg.get(a) in ("AM", "EU"))
        assert len(t) == exp
        names = dict(AM="America", EU="Europe")
        for a, rn in zip(t.columns["actor"].decode(),
                         t.columns["rname"].decode()):
            assert names[reg[a]] == rn

    def test_left_outer_join(self, tmp_path):
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, c.pop FROM events e "
            "LEFT JOIN countries c ON e.actor = c.code"
        )
        t = r.features
        assert len(t) == len(actors)  # every event row survives
        pops = {"USA": 331.0, "FRA": 67.0, "CHN": 1412.0}
        got_pop = np.asarray(t.column("pop"))
        for a, p in zip(t.columns["actor"].decode(), got_pop):
            if a in pops:
                assert p == pops[a]
            else:
                assert np.isnan(p)  # XXX has no country -> NULL

    def test_right_outer_join(self, tmp_path):
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, c.code FROM events e "
            "RIGHT JOIN countries c ON e.actor = c.code"
        )
        t = r.features
        n_matched = sum(1 for a in actors if a in ("USA", "FRA", "CHN"))
        assert len(t) == n_matched + 1  # GBR row survives unmatched
        codes = t.columns["code"].decode()
        assert "GBR" in codes
        i = codes.index("GBR")
        assert t.columns["actor"].decode()[i] is None  # null-extended

    def test_left_join_aggregate_counts_nulls_correctly(self, tmp_path):
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, COUNT(c.pop) AS npop, COUNT(*) AS nrows "
            "FROM events e LEFT JOIN countries c ON e.actor = c.code "
            "GROUP BY e.actor ORDER BY e.actor"
        )
        t = r.features
        for a, np_, nr in zip(t.columns["actor"].decode(),
                              np.asarray(t.column("npop")),
                              np.asarray(t.column("nrows"))):
            exp_rows = int((actors == a).sum())
            assert nr == exp_rows
            assert np_ == (exp_rows if a != "XXX" else 0)  # NULLs skipped

    def test_distinct_single_table(self, tmp_path):
        sft, batch, ds = make_store(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql("SELECT DISTINCT actor FROM gdelt ORDER BY actor")
        got = r.features.columns["actor"].decode()
        assert got == sorted(set(batch.columns["actor"].decode()))
        # DISTINCT + LIMIT: dedup happens before the limit
        r2 = ctx.sql("SELECT DISTINCT actor FROM gdelt LIMIT 2")
        assert len(r2.features) == 2
        assert len(set(r2.features.columns["actor"].decode())) == 2

    def test_distinct_join(self, tmp_path):
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT DISTINCT c.region FROM events e "
            "JOIN countries c ON e.actor = c.code ORDER BY c.region"
        )
        got = r.features.columns["region"].decode()
        present = {a for a in actors if a in ("USA", "FRA", "CHN")}
        exp = sorted({{"USA": "AM", "FRA": "EU", "CHN": "AS"}[a]
                      for a in present})
        assert got == exp

    def test_outer_join_empty_side(self, tmp_path):
        # an outer join whose filtered side is EMPTY must null-extend,
        # not crash (round-3 review finding). NB: WHERE pushes into the
        # SCAN (ON-clause placement; documented in _join) — post-join
        # WHERE semantics would instead collapse the join to inner
        ds, actors = self._three_tables(tmp_path)
        ctx = SqlContext(ds)
        r = ctx.sql(
            "SELECT e.actor, c.pop FROM events e "
            "LEFT JOIN countries c ON e.actor = c.code "
            "WHERE c.pop > 1e9"
        )
        t = r.features
        assert len(t) == len(actors)
        assert np.isnan(np.asarray(t.column("pop"))).all()


def test_join_side_size_guard(tmp_path):
    # round-4 (VERDICT weak #8): a join side exceeding
    # geomesa.sql.join.max.rows must refuse instead of silently
    # materializing; filters that shrink the side below the cap pass
    from geomesa_tpu.utils.config import SystemProperties

    sft, batch, ds = make_store(tmp_path, n=400)
    dim_sft = SimpleFeatureType.from_spec(
        "dim", "actor:String,weight:Double,*geom:Point")
    ds.create_schema(dim_sft).write(FeatureBatch.from_pydict(
        dim_sft,
        {"actor": ["USA", "FRA", "CHN"],
         "weight": [1.0, 2.0, 3.0],
         "geom": np.zeros((3, 2))}))
    ctx = SqlContext(ds)
    q = ("SELECT g.actor AS a, d.weight AS w FROM gdelt g "
         "JOIN dim d ON g.actor = d.actor LIMIT 5")
    SystemProperties.set("geomesa.sql.join.max.rows", 100)
    try:
        with pytest.raises(SqlError, match="join.max.rows"):
            ctx.sql(q)
        # a pushdown filter under the cap goes through
        r = ctx.sql("SELECT g.actor AS a, d.weight AS w FROM gdelt g "
                    "JOIN dim d ON g.actor = d.actor "
                    "WHERE g.score > 9.8 LIMIT 5")
        assert r.kind == "features"
    finally:
        SystemProperties.clear("geomesa.sql.join.max.rows")
    r = ctx.sql(q)  # default cap: fine
    assert r.count == 5
