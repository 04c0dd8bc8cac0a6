"""Tests: converter DSL + framework, visibility security, flags, metrics, CLI."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from geomesa_tpu.convert import (
    DelimitedTextConverter,
    EvalContext,
    JsonConverter,
    compile_expression,
    converter_from_config,
    schemas,
)
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.security import (
    StaticAuthorizationsProvider,
    VisibilityEvaluator,
    allow_mask,
)
from geomesa_tpu.utils.config import SystemProperties
from geomesa_tpu.utils.metrics import MetricsRegistry


class TestTransforms:
    def ctx(self, *pos, **named):
        return EvalContext(list(pos), named, line_no=3)

    def test_refs_and_casts(self):
        assert compile_expression("$1::int")(self.ctx("x", "42")) == 42
        assert compile_expression("$2::double")(self.ctx("x", "1", "2.5")) == 2.5
        assert compile_expression("$name")(self.ctx(named={})) is None

    def test_functions(self):
        assert compile_expression("concat($1, '-', $2)")(self.ctx("", "a", "b")) == "a-b"
        assert compile_expression("lowercase(trim($1))")(self.ctx("", "  AB ")) == "ab"
        assert compile_expression("point($1, $2)")(self.ctx("", "1.5", "2.5")) == (1.5, 2.5)
        assert compile_expression("toInt($1, 7)")(self.ctx("", "bad")) == 7
        assert compile_expression("withDefault($1, 'x')")(self.ctx("", "")) == "x"
        assert compile_expression("lineNo()")(self.ctx("")) == 3
        assert len(compile_expression("md5($1)")(self.ctx("", "v"))) == 32

    def test_dates(self):
        ms = compile_expression("dateParse('yyyyMMdd', $1)")(self.ctx("", "20200601"))
        assert ms == int(np.datetime64("2020-06-01", "ms").astype(np.int64))
        ms = compile_expression("isoDateTime($1)")(self.ctx("", "2020-06-01T12:00:00Z"))
        assert ms == int(np.datetime64("2020-06-01T12:00:00", "ms").astype(np.int64))
        assert compile_expression("secsToDate($1)")(self.ctx("", "100")) == 100_000

    def test_nested(self):
        e = compile_expression("concat(uppercase($1), toString(toInt($2)))")
        assert e(self.ctx("", "ab", "9")) == "AB9"

    def test_errors(self):
        with pytest.raises(ValueError):
            compile_expression("nosuchfn($1)")
        with pytest.raises(ValueError):
            compile_expression("$1::nosuchtype")
        with pytest.raises(ValueError):
            compile_expression("toInt(")


CSV = """id,name,lat,lon,when
1,alpha,51.5,-0.1,2020-06-01T00:00:00Z
2,beta,48.8,2.35,2020-06-02T00:00:00Z
3,,48.8,2.35,2020-06-03T00:00:00Z
bad,gamma,not_a_lat,xx,2020-06-04T00:00:00Z
"""


class TestConverters:
    def make(self):
        sft = SimpleFeatureType.from_spec(
            "t", "name:String,dtg:Date,*geom:Point"
        )
        config = {
            "type": "delimited-text",
            "format": "CSV",
            "options": {"skip-lines": 1},
            "id-field": "$1",
            "fields": [
                {"name": "name", "transform": "withDefault($2, 'unknown')"},
                {"name": "dtg", "transform": "isoDateTime($5)"},
                {"name": "geom", "transform": "point($4, $3)"},
            ],
        }
        return sft, config

    def test_csv(self):
        sft, config = self.make()
        conv = DelimitedTextConverter(sft, config)
        batch = conv.convert(io.StringIO(CSV))
        assert len(batch) == 3  # bad record skipped
        assert conv.failed == 1
        assert batch.fids.decode() == ["1", "2", "3"]
        assert batch.column("name").decode() == ["alpha", "beta", "unknown"]
        np.testing.assert_allclose(batch.geometry.x, [-0.1, 2.35, 2.35])

    def test_skip_keeps_columns_aligned(self):
        # a record failing geometry validation must not leave earlier
        # columns partially appended (silent row misalignment)
        sft = SimpleFeatureType.from_spec("t", "name:String,*geom:Point")
        config = {
            "type": "delimited-text",
            "fields": [
                {"name": "name", "transform": "$1"},
                {"name": "geom", "transform": "point($2, $3)"},
            ],
        }
        conv = DelimitedTextConverter(sft, config)
        batch = conv.convert(io.StringIO("a,1,2\nbad,,\nc,5,6\n"))
        assert conv.failed == 1
        assert batch.column("name").decode() == ["a", "c"]
        np.testing.assert_allclose(batch.geometry.x, [1.0, 5.0])

    def test_json_missing_path_stays_null(self):
        # $0 must be the extracted path value (None when missing), never the
        # whole record object
        sft = SimpleFeatureType.from_spec("t", "name:String,*geom:Point")
        config = {
            "type": "json",
            "fields": [
                {"name": "name", "path": "$.props.name",
                 "transform": "withDefault($0, 'UNKNOWN')"},
                {"name": "lon", "path": "$.loc.0"},
                {"name": "lat", "path": "$.loc.1"},
                {"name": "geom", "transform": "point($lon, $lat)"},
            ],
        }
        conv = converter_from_config(sft, config)
        batch = conv.convert(io.StringIO(json.dumps({"loc": [1.0, 2.0]})))
        assert batch.column("name").decode() == ["UNKNOWN"]

    def test_raise_mode(self):
        sft, config = self.make()
        config["options"]["error-mode"] = "raise-errors"
        conv = DelimitedTextConverter(sft, config)
        with pytest.raises(Exception):
            conv.convert(io.StringIO(CSV))

    def test_json(self):
        sft = SimpleFeatureType.from_spec("t", "name:String,dtg:Date,*geom:Point")
        config = {
            "type": "json",
            "id-field": "$name",
            "fields": [
                {"name": "name", "path": "$.props.name"},
                {"name": "dtg", "path": "$.when", "transform": "isoDateTime($0)"},
                {"name": "lon", "path": "$.loc.0"},
                {"name": "lat", "path": "$.loc.1"},
                {"name": "geom", "transform": "point($lon, $lat)"},
            ],
        }
        lines = "\n".join(
            json.dumps(
                {"props": {"name": f"n{i}"}, "when": "2020-06-01T00:00:00Z",
                 "loc": [i * 1.0, i * 2.0]}
            )
            for i in range(4)
        )
        conv = converter_from_config(sft, config)
        assert isinstance(conv, JsonConverter)
        batch = conv.convert(io.StringIO(lines))
        assert len(batch) == 4
        np.testing.assert_allclose(batch.geometry.x, [0, 1, 2, 3])
        np.testing.assert_allclose(batch.geometry.y, [0, 2, 4, 6])

    def test_gdelt_schema(self):
        sft, config = schemas.WELL_KNOWN["gdelt"]
        cols = [""] * 57
        cols[0] = "e1"
        cols[1] = "20200601"
        cols[6] = "FRANCE"
        cols[26] = "043"
        cols[30] = "2.4"
        cols[31] = "12"
        cols[53] = "48.85"  # ActionGeo_Lat ($54)
        cols[54] = "2.35"   # ActionGeo_Long ($55)
        tsv = "\t".join(cols)
        conv = converter_from_config(sft, config)
        batch = conv.convert(io.StringIO(tsv))
        assert len(batch) == 1
        assert batch.column("Actor1Name").decode() == ["FRANCE"]
        assert batch.column("GoldsteinScale")[0] == pytest.approx(2.4)
        assert batch.geometry.x[0] == pytest.approx(2.35)

    def test_ais_schema(self):
        sft, config = schemas.WELL_KNOWN["ais"]
        csv_text = (
            "MMSI,BaseDateTime,LAT,LON,SOG,COG,Heading,VesselName\n"
            "367000001,2021-03-01T00:00:01,29.9,-90.1,7.5,180.0,181.0,EVER GIVEN\n"
        )
        conv = converter_from_config(sft, config)
        batch = conv.convert(io.StringIO(csv_text))
        assert len(batch) == 1
        assert batch.column("VesselName").decode() == ["EVER GIVEN"]
        assert batch.geometry.y[0] == pytest.approx(29.9)

    def test_osm_schema(self):
        sft, config = schemas.WELL_KNOWN["osm"]
        csv_text = "42,2.35,48.85,mapper,3,2021-05-01T12:00:00,amenity=cafe\n"
        conv = converter_from_config(sft, config)
        batch = conv.convert(io.StringIO(csv_text))
        assert len(batch) == 1
        assert batch.column("osm_id").decode() == ["42"]
        assert batch.column("version")[0] == 3
        assert batch.geometry.x[0] == pytest.approx(2.35)

    def test_twitter_schema(self):
        import json as _json

        sft, config = schemas.WELL_KNOWN["twitter"]
        tweet = {
            "id_str": "123", "text": "hello",
            "user": {"screen_name": "alice"},
            "created_at": "Wed Aug 27 13:08:45 +0000 2008",
            "coordinates": {"type": "Point", "coordinates": [-74.0, 40.7]},
        }
        conv = converter_from_config(sft, config)
        batch = conv.convert(io.StringIO(_json.dumps(tweet)))
        assert len(batch) == 1
        assert batch.column("user_name").decode() == ["alice"]
        assert batch.geometry.y[0] == pytest.approx(40.7)
        assert batch.column("dtg")[0] == 1219842525000


class TestVisibility:
    def test_parse_eval(self):
        ev = VisibilityEvaluator()
        assert ev.can_see("", ["any"])
        assert ev.can_see(None, [])
        assert ev.can_see("admin", ["admin"])
        assert not ev.can_see("admin", ["user"])
        assert ev.can_see("admin&(usa|gbr)", ["admin", "gbr"])
        assert not ev.can_see("admin&(usa|gbr)", ["admin"])
        assert not ev.can_see("admin&(usa|gbr)", ["usa", "gbr"])
        assert ev.can_see("a|b|c", ["c"])
        assert ev.can_see('"weird label"&x', ["weird label", "x"])

    def test_mixing_requires_parens(self):
        ev = VisibilityEvaluator()
        with pytest.raises(ValueError):
            ev.can_see("a&b|c", ["a"])

    def test_allow_mask(self):
        vocab = ["admin", "admin&usa", None, "public|admin"]
        codes = np.array([0, 1, 2, 3, -1, 1], np.int32)
        m = allow_mask(vocab, codes, ["admin"])
        np.testing.assert_array_equal(m, [True, False, True, True, True, False])
        m2 = allow_mask(vocab, codes, ["admin", "usa"])
        assert m2.all()

    def test_provider(self):
        p = StaticAuthorizationsProvider(["a", "b"])
        assert p.get_authorizations() == ["a", "b"]


class TestSystemProperties:
    def test_default_env_override(self, monkeypatch):
        prop = SystemProperties.SCAN_RANGES_TARGET
        assert prop.get() == 2000
        assert prop.provenance == "default"
        monkeypatch.setenv("GEOMESA_TPU_SCAN_RANGES_TARGET", "512")
        assert prop.get() == 512
        assert prop.provenance.startswith("env:")
        SystemProperties.set(prop.name, 64)
        assert prop.get() == 64
        assert prop.provenance == "override"
        SystemProperties.clear(prop.name)
        assert prop.get() == 512

    def test_registry(self):
        assert "geomesa.scan.ranges.target" in SystemProperties.all()


class TestMetrics:
    def test_counters_timers(self):
        m = MetricsRegistry()
        m.counter("ingest.features", 10)
        m.counter("ingest.features", 5)
        m.gauge("cache.bytes", 1024)
        with m.timer("query"):
            pass
        data = json.loads(m.to_json())
        assert data["counters"]["ingest.features"] == 15
        assert data["gauges"]["cache.bytes"] == 1024
        assert data["timers"]["query"]["count"] == 1
        prom = m.to_prometheus()
        assert "ingest_features 15" in prom
        assert "query_seconds_count 1" in prom


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cli_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_cli(args, env):
    return subprocess.run(
        [sys.executable, "-m", "geomesa_tpu.cli.main"] + args,
        capture_output=True, text=True, env=env, timeout=120,
    )


class TestCLI:
    def test_end_to_end(self, tmp_path, cli_env):
        cat = str(tmp_path / "catalog")
        r = run_cli(["create-schema", "-c", cat, "-f", "pois",
                     "-s", "name:String,dtg:Date,*geom:Point"], cli_env)
        assert r.returncode == 0, r.stderr
        r = run_cli(["get-type-names", "-c", cat], cli_env)
        assert r.stdout.strip() == "pois"
        r = run_cli(["describe-schema", "-c", cat, "-f", "pois"], cli_env)
        assert "String" in r.stdout and "*default geometry" in r.stdout

        # ingest via a converter config file
        conv = tmp_path / "conv.json"
        conv.write_text(json.dumps({
            "type": "delimited-text", "format": "CSV",
            "options": {"skip-lines": 1},
            "id-field": "$1",
            "fields": [
                {"name": "name", "transform": "$2::string"},
                {"name": "dtg", "transform": "isoDateTime($3)"},
                {"name": "geom", "transform": "point($4, $5)"},
            ],
        }))
        data = tmp_path / "data.csv"
        data.write_text(
            "id,name,when,lon,lat\n"
            "1,cafe,2020-06-01T00:00:00Z,2.35,48.85\n"
            "2,pub,2020-06-02T00:00:00Z,-0.1,51.5\n"
        )
        r = run_cli(["ingest", "-c", cat, "-f", "pois", "-C", str(conv), str(data)], cli_env)
        assert "ingested 2 features" in r.stdout, r.stderr

        r = run_cli(["stats-count", "-c", cat, "-f", "pois"], cli_env)
        assert r.stdout.strip() == "2"
        r = run_cli(["export", "-c", cat, "-f", "pois", "-q", "name = 'cafe'",
                     "-F", "csv"], cli_env)
        assert "cafe" in r.stdout and "pub" not in r.stdout

        # round 5: export in a projected CRS (explicit EPSG and auto-UTM)
        r = run_cli(["export", "-c", cat, "-f", "pois", "-q",
                     "BBOX(geom, 0, 45, 5, 50)", "-F", "csv",
                     "--crs", "3857"], cli_env)
        assert r.returncode == 0, r.stderr
        assert "261600.80" in r.stdout  # 2.35 deg lon -> 261600.8 m web mercator
        r = run_cli(["export", "-c", cat, "-f", "pois", "-q",
                     "BBOX(geom, 0, 45, 5, 50)", "-F", "csv",
                     "--crs", "utm"], cli_env)
        assert r.returncode == 0, r.stderr
        assert "auto UTM zone: EPSG:32631" in r.stderr  # lon 2.5 -> zone 31
        r = run_cli(["export", "-c", cat, "-f", "pois", "-q", "INCLUDE",
                     "-F", "csv", "--crs", "utm"], cli_env)
        assert r.returncode != 0  # no spatial filter: zone is ambiguous
        # mixed-case prefix parses; garbage gets a clear error, not a
        # traceback; projected CRS is rejected for formats that would
        # silently corrupt (bin stores raw lon/lat, leaflet plots lat/lng)
        r = run_cli(["export", "-c", cat, "-f", "pois", "-q",
                     "BBOX(geom, 0, 45, 5, 50)", "-F", "csv",
                     "--crs", "Epsg:3857"], cli_env)
        assert r.returncode == 0 and "261600.80" in r.stdout, r.stderr
        r = run_cli(["export", "-c", cat, "-f", "pois", "-F", "csv",
                     "--crs", "3857m"], cli_env)
        assert r.returncode != 0 and "EPSG" in r.stderr
        r = run_cli(["export", "-c", cat, "-f", "pois", "-F", "bin",
                     "--crs", "3857"], cli_env)
        assert r.returncode != 0 and "bin" in r.stderr.lower()
        r = run_cli(["export", "-c", cat, "-f", "pois", "-F", "leaflet",
                     "--crs", "3857"], cli_env)
        assert r.returncode != 0 and "leaflet" in r.stderr.lower()
        r = run_cli(["export", "-c", cat, "-f", "pois", "-F", "gml"], cli_env)
        assert r.returncode == 0, r.stderr
        assert "<gml:FeatureCollection" in r.stdout and "gml:pos" in r.stdout
        for fmt in ("parquet", "orc"):
            out = str(tmp_path / f"out.{fmt}")
            r = run_cli(["export", "-c", cat, "-f", "pois", "-F", fmt,
                         "-o", out], cli_env)
            assert r.returncode == 0, r.stderr
            import pyarrow.orc as paorc
            import pyarrow.parquet as papq

            t = (papq if fmt == "parquet" else paorc).read_table(out)
            assert t.num_rows == 2
        r = run_cli(["explain", "-c", cat, "-f", "pois",
                     "-q", "BBOX(geom, 0, 40, 5, 50)"], cli_env)
        assert "Partitions" in r.stdout
        r = run_cli(["stats-analyze", "-c", cat, "-f", "pois"], cli_env)
        assert r.returncode == 0, r.stderr
        r = run_cli(["stats-top-k", "-c", cat, "-f", "pois", "-a", "name"], cli_env)
        assert "cafe\t1" in r.stdout
        r = run_cli(["env"], cli_env)
        assert "geomesa.scan.ranges.target" in r.stdout

    def test_version_and_help(self, cli_env):
        assert run_cli(["version"], cli_env).returncode == 0
        r = run_cli([], cli_env)
        assert r.returncode == 1


class TestCliSql:
    def test_sql_subcommand(self, tmp_path, cli_env):
        cat = str(tmp_path / "catalog")
        r = run_cli(["create-schema", "-c", cat, "-f", "ev",
                     "-s", "actor:String,score:Double,dtg:Date,*geom:Point"],
                    cli_env)
        assert r.returncode == 0, r.stderr
        conv = tmp_path / "conv.json"
        conv.write_text(json.dumps({
            "type": "delimited-text", "format": "CSV",
            "id-field": "$1",
            "fields": [
                {"name": "actor", "transform": "$2::string"},
                {"name": "score", "transform": "$3::double"},
                {"name": "dtg", "transform": "isoDateTime($4)"},
                {"name": "geom", "transform": "point($5, $6)"},
            ],
        }))
        data = tmp_path / "ev.csv"
        rows = [
            "1,USA,2.0,2020-06-01T00:00:00Z,1.0,2.0",
            "2,USA,4.0,2020-06-01T00:00:00Z,3.0,4.0",
            "3,FRA,6.0,2020-06-01T00:00:00Z,5.0,6.0",
        ]
        data.write_text("\n".join(rows) + "\n")
        r = run_cli(["ingest", "-c", cat, "-f", "ev", "-C", str(conv),
                     str(data)], cli_env)
        assert "ingested 3 features" in r.stdout, r.stderr
        r = run_cli(["sql", "-c", cat, "-q",
                     "SELECT actor, COUNT(*) AS n, SUM(score) AS s FROM ev "
                     "GROUP BY actor ORDER BY actor"], cli_env)
        assert r.returncode == 0, r.stderr
        lines = r.stdout.strip().splitlines()
        assert lines[0] == "actor,n,s"
        assert lines[1].startswith("FRA,1,6") and lines[2].startswith("USA,2,6")
        r = run_cli(["sql", "-c", cat, "-F", "json", "-q",
                     "SELECT COUNT(*) FROM ev WHERE score > 3"], cli_env)
        assert r.stdout.strip() == "2"


class TestCLIDeleteFeatures:
    def test_delete_and_age_off(self, tmp_path, cli_env):
        cat = str(tmp_path / "catalog")
        r = run_cli(["create-schema", "-c", cat, "-f", "ev",
                     "-s", "name:String,dtg:Date,*geom:Point"], cli_env)
        assert r.returncode == 0, r.stderr
        csv = tmp_path / "rows.csv"
        csv.write_text(
            "id,name,dtg,lon,lat\n"
            "1,alpha,2020-06-01T00:00:00,10.0,20.0\n"
            "2,beta,2020-06-20T00:00:00,11.0,21.0\n"
            "3,alpha,2020-07-05T00:00:00,12.0,22.0\n"
        )
        conv = tmp_path / "conv.json"
        conv.write_text(json.dumps({
            "type": "delimited-text", "format": "CSV",
            "options": {"skip-lines": 1},
            "id-field": "$1",
            "fields": [
                {"name": "name", "transform": "$2::string"},
                {"name": "dtg", "transform": "isoDateTime($3)"},
                {"name": "geom", "transform": "point($4, $5)"},
            ],
        }))
        r = run_cli(["ingest", "-c", cat, "-f", "ev",
                     "--converter", str(conv), str(csv)], cli_env)
        assert r.returncode == 0, r.stderr
        r = run_cli(["delete-features", "-c", cat, "-f", "ev",
                     "-q", "name = 'beta'"], cli_env)
        assert r.returncode == 0, r.stderr
        assert "deleted 1 features" in r.stdout
        r = run_cli(["age-off", "-c", cat, "-f", "ev",
                     "--older-than", "2020-07-01T00:00:00Z"], cli_env)
        assert "aged off 1 features" in r.stdout
        r = run_cli(["stats-count", "-c", cat, "-f", "ev",
                     "-q", "INCLUDE"], cli_env)
        assert r.returncode == 0, r.stderr
        assert "1" in r.stdout
