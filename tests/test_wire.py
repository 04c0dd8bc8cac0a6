"""Columnar wire (docs/SERVING.md "Columnar wire"): negotiated binary
record-batch framing + the PushMux one-encode fan-out.

The load-bearing suite is round-trip PARITY: columnar-decoded
`execute`/density/topk/push payloads must be bit-identical to the
JSON-lines path for the same queries — the fast path is only a fast
path if nobody can tell the difference after decode. Alongside it:
the hello negotiation + typed pyarrow-absent fallback, the bulk-ingest
path (record-batch buffers in as NumPy views), the one-encode-per-frame
fan-out invariant at 1000 sinks, writer-thread isolation of a dead
mirror, the replica-socket transport, and a CPU throughput floor
(columnar >= 5x JSON rows/s — the acceptance criterion, with ~40x
margin measured).

Wall-clock discipline (tier-1 budget is effectively full): module-
scoped stores reusing test_serve's 600-row shapes (same pow2 kernel
buckets), one 20k-row store for the throughput floor, and in-memory
streams everywhere a socket is not itself under test.
"""

import json
import queue
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.serve import columnar as colwire
from geomesa_tpu.serve.protocol import serve_connection
from geomesa_tpu.serve.service import QueryService, ServeConfig

DENSITY = {"bbox": [-180, -90, 180, 90], "width": 64, "height": 32}


def make_batch(n=600, seed=3, with_nulls=True):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "served", "name:String,score:Double,dtg:Date,*geom:Point")
    names = rng.choice(["a", "b", "c"], n).tolist()
    if with_nulls:
        # null strings must decode identically on both paths
        names = [None if i % 97 == 0 else v for i, v in enumerate(names)]
    return sft, FeatureBatch.from_pydict(sft, {
        "name": names,
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    sft, batch = make_batch()
    ds = DataStore(
        str(tmp_path_factory.mktemp("wire")), use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


def drive(store, svc, requests, payloads=None, timeout_s=60.0):
    """Run one in-memory conversation; returns {id: (doc, payload)}
    plus the ordered response list. Query responses resolve on the
    dispatch thread AFTER serve_connection returns (the shared service
    stays open), so this polls the output stream until every request
    id has answered — a torn mid-write frame parse simply retries."""
    mem = colwire.MemoryWire()
    payloads = payloads or {}
    for doc in requests:
        mem.add(doc, payloads.get(doc.get("id")))
    out = bytearray()
    serve_connection(store, svc, mem.lines(),
                     lambda s: out.extend(s.encode()),
                     write_bytes=out.extend, read_bytes=mem.read_exact)
    want = {d["id"] for d in requests if "id" in d}
    deadline = time.monotonic() + timeout_s
    resp = []
    while time.monotonic() < deadline:
        try:
            resp = colwire.parse_stream(bytes(out))
        except ValueError:
            time.sleep(0.005)  # mid-frame write in flight
            continue
        if want <= {d.get("id") for d, _ in resp}:
            break
        time.sleep(0.005)
    by_id = {d.get("id"): (d, p) for d, p in resp if "id" in d}
    assert want <= set(by_id), (want, sorted(by_id))
    return by_id, resp


class TestNegotiation:
    def test_hello_advertises_and_upgrades(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            by_id, _ = drive(store, svc, [
                {"id": "h", "op": "hello", "wire": "columnar"}])
            hello = by_id["h"][0]
            assert hello["wire"] == ["json", "columnar"]
            assert hello["wireMode"] == "columnar"
        finally:
            svc.close(drain=True)

    def test_no_binary_sink_downgrades_typed(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            mem = colwire.MemoryWire()
            mem.add({"id": "h", "op": "hello", "wire": "columnar"})
            mem.add({"id": "q", "op": "query", "typeName": "served",
                     "cql": "INCLUDE", "maxFeatures": 5,
                     "wire": "columnar"})
            lines_out = []
            # TEXT-ONLY transport: no write_bytes
            serve_connection(store, svc, mem.lines(), lines_out.append)
            deadline = time.monotonic() + 30.0
            while len(lines_out) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            docs = [json.loads(s) for s in list(lines_out)]
            hello, q = docs[0], docs[1]
            assert hello["wireMode"] == "json"
            assert hello["wireFallback"] == "no_binary_sink"
            assert q["wireFallback"] == "no_binary_sink"
            assert len(q["features"]) == 5  # JSON fallback still serves
        finally:
            svc.close(drain=True)

    def test_pyarrow_absent_skips_typed_to_json(self, store,
                                                monkeypatch):
        # simulate a pyarrow-less container: capability drops, every
        # columnar opt-in downgrades typed — never a crash
        monkeypatch.setattr(colwire, "_PA", None)
        monkeypatch.setattr(colwire, "_PA_CHECKED", True)
        assert colwire.wire_capabilities() == ["json"]
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            by_id, _ = drive(store, svc, [
                {"id": "h", "op": "hello", "wire": "columnar"},
                {"id": "q", "op": "query", "typeName": "served",
                 "cql": "INCLUDE", "maxFeatures": 5,
                 "wire": "columnar"}])
            assert by_id["h"][0]["wire"] == ["json"]
            assert by_id["h"][0]["wireFallback"] == "pyarrow_unavailable"
            q, payload = by_id["q"]
            assert payload is None
            assert q["wireFallback"] == "pyarrow_unavailable"
            assert len(q["features"]) == 5
        finally:
            svc.close(drain=True)


class TestParity:
    """Columnar decode == JSON path, bit-identical, per payload kind."""

    def test_execute_rows_bit_identical(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            by_id, _ = drive(store, svc, [
                {"id": "h", "op": "hello", "wire": "columnar"},
                {"id": "c", "op": "query", "typeName": "served",
                 "cql": "BBOX(geom,-170,-80,170,80) AND score > -5",
                 "maxFeatures": 600},
                {"id": "j", "op": "query", "typeName": "served",
                 "cql": "BBOX(geom,-170,-80,170,80) AND score > -5",
                 "maxFeatures": 600, "wire": "json"}])
            cdoc, payload = by_id["c"]
            jdoc, _ = by_id["j"]
            assert payload is not None and "features" not in cdoc
            rows = colwire.decode_execute_payload(payload)
            # the JSON doc round-trips through json.dumps/loads in
            # drive(), so equality here IS wire-level bit-parity
            assert rows == jdoc["features"]
            assert cdoc["count"] == jdoc["count"] == len(rows)
        finally:
            svc.close(drain=True)

    def test_density_grid_single_buffer(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            by_id, _ = drive(store, svc, [
                {"id": "c", "op": "query", "typeName": "served",
                 "cql": "INCLUDE", "density": DENSITY,
                 "wire": "columnar"},
                {"id": "j", "op": "query", "typeName": "served",
                 "cql": "INCLUDE", "density": DENSITY}])
            cdoc, payload = by_id["c"]
            jdoc, _ = by_id["j"]
            assert payload is not None
            grid = colwire.decode_density_payload(cdoc["frame"], payload)
            assert cdoc["shape"] == jdoc["shape"] == list(grid.shape)
            assert cdoc["total"] == jdoc["total"] == float(grid.sum())
            # the columnar response is a SUPERSET: actual cells, one
            # contiguous f64 buffer, no per-cell JSON
            assert grid.dtype == np.float64
            assert len(payload) == grid.size * 8
        finally:
            svc.close(drain=True)

    def test_topk_cells_codec_bit_identical(self):
        cells = [{"row": 3, "col": 7,
                  "bbox": [-180.0, -90.0, -174.375, -87.1875],
                  "count": 41, "bound": 3},
                 {"row": 0, "col": 0,
                  "bbox": [0.0, 0.0, 5.625, 2.8125],
                  "count": 12, "bound": 0}]
        desc, payload = colwire.encode_topk_frame(cells)
        assert colwire.decode_topk_payload(desc, payload) == cells

    def test_push_frame_codec_bit_identical(self):
        # fids are user data off the ingest path: separators and empty
        # strings must round-trip exactly (length-prefixed offsets)
        frame = {"event": "enter", "subscription": "sub-9", "seq": 4,
                 "fids": [f"f{i}" for i in range(57)]
                 + ["has\nnewline", "", "tab\tand spaces"]}
        jbuf = colwire.encode_push(frame, "json")
        assert json.loads(jbuf.decode()) == frame
        cbuf = colwire.encode_push(frame, "columnar")
        (doc, payload), = colwire.parse_stream(cbuf)
        assert colwire.decode_push(doc, payload) == frame
        # scalar frames (density totals, lifecycle) stay JSON lines
        scalar = {"event": "density", "subscription": "s", "seq": 1,
                  "total": 4.0, "cells": 2}
        assert json.loads(colwire.encode_push(
            scalar, "columnar").decode()) == scalar

    def test_knn_binary_staging_parity(self, store):
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            qx = np.array([1.5, -20.25, 33.0])
            qy = np.array([2.5, 10.125, -44.0])
            desc, payload = colwire.knn_sections(qx, qy)
            by_id, _ = drive(store, svc, [
                {"id": "b", "op": "knn", "typeName": "served",
                 "cql": "INCLUDE", "k": 4,
                 "frame": {"sections": desc}},
                {"id": "j", "op": "knn", "typeName": "served",
                 "cql": "INCLUDE", "k": 4, "x": qx.tolist(),
                 "y": qy.tolist()}],
                payloads={"b": payload})
            assert by_id["b"][0]["dists"] == by_id["j"][0]["dists"]
            assert by_id["b"][0]["indices"] == by_id["j"][0]["indices"]
        finally:
            svc.close(drain=True)


class TestIngest:
    def test_wire_ingest_roundtrip(self, store, tmp_path):
        from geomesa_tpu.core.arrow_io import to_ipc_bytes

        sft, batch = make_batch(n=256, seed=9, with_nulls=False)
        ds = DataStore(str(tmp_path / "ingest"), use_device_cache=True)
        ds.create_schema(sft)
        svc = QueryService(ds, ServeConfig(max_wait_ms=0.0))
        try:
            payload = to_ipc_bytes(batch)
            by_id, _ = drive(ds, svc, [
                {"id": "w", "op": "ingest", "typeName": "served",
                 "frame": {"kind": "ingest"}},
                {"id": "n", "op": "count", "typeName": "served",
                 "cql": "INCLUDE"}],
                payloads={"w": payload})
            assert by_id["w"][0] == {"id": "w", "ok": True,
                                     "rows": 256, "batches": 1}
            assert by_id["n"][0]["count"] == 256
        finally:
            svc.close(drain=True)
        # written-through-the-wire rows answer queries identically to
        # the direct write path
        got = ds.get_feature_source("served").get_features("INCLUDE")
        assert sorted(np.asarray(got.features.columns["score"])) \
            == sorted(np.asarray(batch.columns["score"]))

    def test_cli_arrow_ingest_creates_schema_from_metadata(self,
                                                           tmp_path):
        # fresh catalog, no create-schema: the IPC stream's embedded
        # geomesa.sft.spec metadata seeds the schema (typed refusal
        # when absent — never a raw FileNotFoundError traceback)
        from types import SimpleNamespace

        from geomesa_tpu.cli import commands
        from geomesa_tpu.core.arrow_io import write_ipc

        sft, batch = make_batch(n=64, seed=2, with_nulls=False)
        path = str(tmp_path / "d.arrow")
        write_ipc(path, [batch])
        args = SimpleNamespace(
            catalog=str(tmp_path / "cat"), feature_name="served",
            converter=None, arrow=False, files=[path], workers=1,
            no_resume=False)
        assert commands._ingest(args) == 0
        ds = DataStore(str(tmp_path / "cat"))
        assert ds.get_feature_source("served").get_count() == 64

    def test_write_batch_accepts_record_batch_and_ipc(self, tmp_path):
        from geomesa_tpu.core.arrow_io import to_arrow, to_ipc_bytes

        sft, batch = make_batch(n=128, seed=5, with_nulls=False)
        ds = DataStore(str(tmp_path / "wb"), use_device_cache=False)
        ds.create_schema(sft)
        rows, nb = ds.write_batch("served", to_arrow(batch))
        assert (rows, nb) == (128, 1)
        rows, nb = ds.write_batch("served", to_ipc_bytes(batch))
        assert (rows, nb) == (128, 1)
        assert ds.get_feature_source("served").get_count() == 256


class TestPushMux:
    def test_one_encode_per_frame_at_1000_sinks(self):
        mux = colwire.PushMux()
        seen = [0] * 1000
        sinks = []
        for i in range(1000):
            def make(i=i):
                def w(buf):
                    seen[i] += 1
                return w
            sinks.append(mux.register(make(), mode="json",
                                      threaded=False))
        frames = 7
        for k in range(frames):
            n = mux.publish({"event": "enter", "subscription": "s",
                             "seq": k + 1,
                             "fids": [f"f{j}" for j in range(64)]},
                            sinks)
            assert n == 1000
        st = mux.stats()
        # THE acceptance invariant: 1000 subscribers, one encode/frame
        assert st["encodes"] == frames
        assert st["frames"] == frames
        assert st["fanout"] == frames * 1000
        assert set(seen) == {frames}
        mux.close()

    def test_mixed_modes_encode_once_per_mode(self):
        mux = colwire.PushMux()
        bufs = {"json": [], "columnar": []}
        sinks = [mux.register(bufs["json"].append, mode="json",
                              threaded=False),
                 mux.register(bufs["columnar"].append, mode="columnar",
                              threaded=False)]
        frame = {"event": "exit", "subscription": "s", "seq": 1,
                 "fids": ["a", "b"]}
        mux.publish(frame, sinks)
        assert mux.stats()["encodes"] == 2  # one per MODE, not per sink
        assert json.loads(bufs["json"][0].decode()) == frame
        (doc, payload), = colwire.parse_stream(bufs["columnar"][0])
        assert colwire.decode_push(doc, payload) == frame
        mux.close()

    def test_threaded_writer_isolation_and_reap(self):
        mux = colwire.PushMux()
        good = []
        dead_calls = []

        def bad_write(buf):
            dead_calls.append(1)
            raise OSError("peer gone")

        ids = [mux.register(good.append, threaded=True),
               mux.register(bad_write, threaded=True)]
        mux.publish({"event": "enter", "subscription": "s", "seq": 1,
                     "fids": ["x"]}, ids)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if good and mux.stats()["dead"] >= 1:
                break
            time.sleep(0.01)
        st = mux.stats()
        assert len(good) == 1 and dead_calls  # healthy sink delivered
        assert st["dead"] == 1
        # the dead sink is reaped on the next publish; the healthy one
        # keeps receiving
        mux.publish({"event": "enter", "subscription": "s", "seq": 2,
                     "fids": ["y"]}, ids)
        deadline = time.monotonic() + 5.0
        while len(good) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(good) == 2
        assert mux.stats()["sinks"] == 1
        mux.close()

    def test_attach_modes_get_distinct_mirror_sinks(self, store):
        # a second attach asking for a DIFFERENT encoding must not be
        # silently served by the first mode's sink — the response's
        # wireMode states the encoding actually delivered
        from geomesa_tpu.serve.protocol import _WireState

        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        try:
            out = bytearray()
            w = _WireState(svc, lambda s: out.extend(s.encode()),
                           out.extend, threading.Lock())
            a = w.ensure_mirror("json")
            b = w.ensure_mirror("columnar")
            assert a != b
            assert w.ensure_mirror("json") == a  # idempotent per mode
            w.close()
            assert svc.wire_mux().stats()["sinks"] == 0
        finally:
            svc.close(drain=True)

    def test_bounded_queue_drops_counted(self):
        mux = colwire.PushMux(queue_limit=2)
        blocked = threading.Event()
        release = threading.Event()

        def slow_write(buf):
            blocked.set()
            release.wait(10.0)

        sid = mux.register(slow_write, threaded=True)
        for k in range(8):
            mux.publish({"event": "enter", "subscription": "s",
                         "seq": k + 1, "fids": ["a"]}, [sid])
        assert blocked.wait(5.0)
        st = mux.stats()
        assert st["dropped"] >= 1  # bounded: excess dropped, counted
        release.set()
        mux.close()


class TestSubscribeFanout:
    """Push frames through the wire: owner connection + an attached
    mirror connection, one encode, decoded parity vs the dict frames
    the manager flushed."""

    def _kafka_store(self):
        from geomesa_tpu.kafka.store import KafkaDataStore

        sft = SimpleFeatureType.from_spec(
            "live", "name:String,score:Double,dtg:Date,*geom:Point")
        store = KafkaDataStore()
        store.create_schema(sft)
        return store, sft

    def _rows(self, sft, seed, fids):
        rng = np.random.default_rng(seed)
        n = len(fids)
        return FeatureBatch.from_pydict(sft, {
            "name": rng.choice(["a", "b"], n).tolist(),
            "score": rng.uniform(-5, 5, n),
            "dtg": rng.integers(1_590_000_000_000,
                                1_600_000_000_000, n),
            "geom": np.stack([rng.uniform(-60, 60, n),
                              rng.uniform(-30, 30, n)], 1),
        }, fids=list(fids))

    def test_owner_and_mirror_one_encode(self):
        store, sft = self._kafka_store()
        svc = QueryService(store, ServeConfig(max_wait_ms=0.0))
        fids = [f"f{i}" for i in range(12)]
        a_out = bytearray()
        a_lines: "queue.Queue" = queue.Queue()

        def a_iter():
            while True:
                item = a_lines.get()
                if item is None:
                    return
                yield item

        t = threading.Thread(target=serve_connection, args=(
            store, svc, a_iter(), lambda s: a_out.extend(s.encode())),
            kwargs={"write_bytes": a_out.extend}, daemon=True)
        t.start()
        try:
            a_lines.put(json.dumps(
                {"id": "h", "op": "hello", "wire": "columnar"}))
            a_lines.put(json.dumps(
                {"id": "s1", "op": "subscribe", "typeName": "live",
                 "cql": "BBOX(geom,-60,-30,60,30)"}))
            deadline = time.monotonic() + 10.0
            while b'"s1"' not in bytes(a_out):
                assert time.monotonic() < deadline
                time.sleep(0.01)
            ack = next(d for d, _ in
                       colwire.parse_stream(bytes(a_out))
                       if d.get("id") == "s1")
            sub_id = ack["subscription"]
            # mirror connection attaches to A's subscription
            b_out = bytearray()
            mem = colwire.MemoryWire()
            mem.add({"id": "h", "op": "hello"})
            mem.add({"id": "at", "op": "attach", "subscription": sub_id,
                     "wire": "columnar"})
            serve_connection(store, svc, mem.lines(),
                             lambda s: b_out.extend(s.encode()),
                             write_bytes=b_out.extend,
                             read_bytes=mem.read_exact)
            # NOTE: connection B returned (its lines ended) but its
            # mirror sink lives until wire.close() ran — which it did.
            # Re-attach a raw mirror sink to model a LIVE connection.
            c_out = bytearray()
            sid = svc.wire_mux().register(c_out.extend,
                                          mode="columnar",
                                          threaded=True)
            svc.wire_mux().attach(sid, sub_id)
            enc0 = svc.wire_mux().stats()["encodes"]
            store.write("live", self._rows(sft, 1, fids))
            a_lines.put(json.dumps({"id": "p1", "op": "poll"}))
            deadline = time.monotonic() + 10.0
            while b"enter" not in bytes(a_out) \
                    or b"enter" not in bytes(c_out):
                assert time.monotonic() < deadline, (
                    bytes(a_out), bytes(c_out))
                time.sleep(0.01)
            by_b = {d.get("id"): d for d, _ in
                    colwire.parse_stream(bytes(b_out))}
            assert by_b["at"]["ok"] and by_b["at"]["sinks"] >= 1
            a_frames = [colwire.decode_push(d, p) for d, p in
                        colwire.parse_stream(bytes(a_out))
                        if d.get("event")]
            c_frames = [colwire.decode_push(d, p) for d, p in
                        colwire.parse_stream(bytes(c_out))
                        if d.get("event")]
            a_enter = [f for f in a_frames if f["event"] == "enter"]
            c_enter = [f for f in c_frames if f["event"] == "enter"]
            assert a_enter and a_enter == c_enter  # decoded parity
            assert sorted(a_enter[0]["fids"]) == sorted(fids)
            # owner (columnar) + mirror (columnar): ONE encode per
            # frame covers both; stats count one per distinct mode
            encodes = svc.wire_mux().stats()["encodes"] - enc0
            frames_routed = len([f for f in a_frames
                                 if f.get("subscription") == sub_id])
            assert encodes <= frames_routed + 1  # never per-sink
        finally:
            a_lines.put(None)
            t.join(timeout=10.0)
            svc.close(drain=True)


class TestThroughputFloor:
    def test_columnar_5x_json_at_20k_rows(self, tmp_path):
        """One bulk `execute` result encoded both ways over identical
        rows: the columnar frame decodes to the JSON rows and encodes
        >=5x the rows/s; a PushMux fan-out encodes each frame once."""
        from geomesa_tpu.plan.query import Query
        from geomesa_tpu.serve.protocol import _payload

        rows = 20_000
        sft, batch = make_batch(n=rows, seed=7, with_nulls=False)
        ds = DataStore(str(tmp_path / "tp"), use_device_cache=True)
        ds.create_schema(sft).write(batch)
        result = ds.get_feature_source("served").planner.execute(
            Query("served", "INCLUDE", max_features=rows))
        assert len(result.features) == rows

        def one_json():
            t0 = time.monotonic()
            doc = {"id": "b", "ok": True}
            doc.update(_payload("execute", result, rows))
            buf = (json.dumps(doc) + "\n").encode()
            return buf, time.monotonic() - t0

        def one_columnar():
            t0 = time.monotonic()
            fields, payload = colwire.encode_execute_frame(
                result.features, rows)
            doc = {"id": "b", "ok": True, "kind": "features",
                   "count": rows, "frame": fields}
            return colwire.frame_bytes(doc, payload), time.monotonic() - t0

        # parity before timing: a fast encoder that decodes wrong is
        # not a result
        jbuf, _ = one_json()
        cbuf, _ = one_columnar()
        (_cdoc, cpayload), = colwire.parse_stream(cbuf)
        assert (colwire.decode_execute_payload(cpayload)
                == json.loads(jbuf.decode())["features"])
        j_s = np.median([one_json()[1] for _ in range(2)])
        c_s = np.median([one_columnar()[1] for _ in range(4)])
        speedup = j_s / c_s
        # acceptance floor (>=5x)
        assert speedup >= 5.0, speedup

        frames = 10
        mux = colwire.PushMux(queue_limit=frames + 8)
        sinks = [mux.register(lambda _buf: None, mode=colwire.WIRE_JSON,
                              threaded=False) for _ in range(32)]
        fids = [f"f{i}" for i in range(64)]
        for i in range(frames):
            mux.publish({"event": "enter", "subscription": "s",
                         "seq": i + 1, "fids": fids}, sinks)
        encodes = mux.stats()["encodes"]
        mux.close()
        assert encodes == frames


class TestReplicaSocketTransport:
    """The real socket path: a ReplicaServer + JsonLineConn must carry
    frames intact in both directions (and the frame-aware docs()
    attaches payloads)."""

    def test_columnar_over_socket(self, tmp_path):
        from geomesa_tpu.fleet.replica import ReplicaServer
        from geomesa_tpu.fleet.wire import connect_json

        # own store: the ingest leg writes rows, which must not
        # perturb the module fixture other classes count against
        sft, batch = make_batch()
        store = DataStore(str(tmp_path / "sock"),
                          use_device_cache=True)
        store.create_schema(sft).write(batch)
        server = ReplicaServer(store, ServeConfig(max_wait_ms=0.0),
                               replica_id="rw")
        port = server.start()
        assert server.wait_state("ready", timeout=120.0) == "ready"
        conn = connect_json("127.0.0.1", port)
        try:
            hello = conn.request(
                {"id": "h", "op": "hello", "wire": "columnar"})
            assert hello["wireMode"] == "columnar"
            got = conn.request(
                {"id": "q", "op": "query", "typeName": "served",
                 "cql": "INCLUDE", "maxFeatures": 100})
            payload = got.pop("_payload")
            ref = conn.request(
                {"id": "r", "op": "query", "typeName": "served",
                 "cql": "INCLUDE", "maxFeatures": 100, "wire": "json"})
            assert colwire.decode_execute_payload(payload) \
                == ref["features"]
            # inbound binary over the socket: bulk ingest is refused
            # typed on a durable store only when the type is unknown —
            # here it lands
            from geomesa_tpu.core.arrow_io import to_ipc_bytes

            _, extra = make_batch(n=64, seed=21, with_nulls=False)
            conn.send_frame({"id": "w", "op": "ingest",
                             "typeName": "served",
                             "frame": {"kind": "ingest"}},
                            to_ipc_bytes(extra))
            stop = threading.Event()
            timer = threading.Timer(30.0, stop.set)
            timer.start()
            try:
                for doc in conn.docs(stop):
                    if doc.get("id") == "w":
                        assert doc["ok"] and doc["rows"] == 64
                        break
            finally:
                timer.cancel()
        finally:
            conn.close()
            server.stop()
