"""Replica fleet: router failover certification (docs/ROBUSTNESS.md
"Replica fleets").

The load-bearing claims, proven over real sockets on CPU:

- routed answers are BIT-identical to direct single-store execution;
- a replica killed abruptly (abort = the in-process kill -9) mid-burst
  under the fault harness loses nothing: every client answer is either
  a correct result (bit-identical to a single-replica run) or a typed
  retryable error — zero un-typed, zero dropped, zero duplicates;
- the drain verb is admin-gated and graceful (in-flight finishes, new
  traffic refused typed);
- a fresh replica refuses traffic (typed, retryable) until its warmup
  check is green, and the router never routes to it before `ready`;
- rolling restart drains one replica at a time and ends with fresh
  incarnations serving;
- ephemeral metrics ports (port=0) are reported in stats()/debug
  endpoints so N replicas on one host never collide.

Budget note (tier-1 wall): ONE tiny module-scoped catalog with the
same 384-row shape / k=5 kNN buckets the chaos suite (test_faults)
already compiled — the fleet pays sockets and routing, not kernels.
Process-spawn coverage (real `python -m geomesa_tpu.fleet.replica`
workers paying jax import) is marked slow.
"""

import json
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.fleet import (
    FleetConfig, FleetSupervisor, ReplicaServer, ReplicaStateError,
    state_number, validate_transition)
from geomesa_tpu.fleet.health import burn_gates_fired
from geomesa_tpu.fleet.wire import connect_json
from geomesa_tpu.plan.datastore import DataStore

N_ROWS = 384
CQL = "BBOX(geom, -170, -80, 170, 80)"
K = 5


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    rng = np.random.default_rng(5)
    sft = SimpleFeatureType.from_spec(
        "fleeted", "name:String,score:Double,dtg:Date,*geom:Point")
    root = str(tmp_path_factory.mktemp("fleet"))
    ds = DataStore(root, use_device_cache=True)
    ds.create_schema(sft).write(FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], N_ROWS).tolist(),
        "score": rng.uniform(-10, 10, N_ROWS),
        "dtg": rng.integers(
            1_590_000_000_000, 1_590_080_000_000, N_ROWS),
        "geom": np.stack([rng.uniform(-170, 170, N_ROWS),
                          rng.uniform(-80, 80, N_ROWS)], 1),
    }))
    del ds
    return root


@pytest.fixture(scope="module")
def oracle_store(catalog):
    """Direct single-store execution — the bit-identity reference."""
    return DataStore(catalog, use_device_cache=True)


def _fleet(catalog, n=2, **kw):
    return FleetSupervisor(FleetConfig(
        n_replicas=n, catalog=catalog, probe_interval_s=0.2, **kw))


def _qpts(n, seed=3):
    return np.random.default_rng(seed).uniform(-60, 60, (n, 2))


def _knn_doc(rid, x, y, timeout_ms=60_000):
    return {"id": rid, "op": "knn", "typeName": "fleeted", "cql": CQL,
            "x": [float(x)], "y": [float(y)], "k": K,
            "timeoutMs": timeout_ms}


class TestStateMachine:
    def test_legal_and_illegal_transitions(self):
        assert validate_transition("starting", "warming") == "warming"
        assert validate_transition("warming", "ready") == "ready"
        assert validate_transition("ready", "degraded") == "degraded"
        assert validate_transition("degraded", "ready") == "ready"
        assert validate_transition("ready", "draining") == "draining"
        assert validate_transition("draining", "dead") == "dead"
        assert validate_transition("ready", "ready") == "ready"  # no-op
        for bad in (("ready", "warming"), ("dead", "ready"),
                    ("warming", "degraded"), ("draining", "ready")):
            with pytest.raises(ReplicaStateError):
                validate_transition(*bad)
        with pytest.raises(ReplicaStateError):
            validate_transition("ready", "nonsense")
        with pytest.raises(ReplicaStateError):
            state_number("nonsense")

    def test_burn_gate_reading(self):
        assert not burn_gates_fired({})
        assert not burn_gates_fired({"enabled": False})
        assert burn_gates_fired({"enabled": True, "degrade_boost": 1})
        assert burn_gates_fired({
            "enabled": True, "degrade_boost": 0,
            "breaching": ["knn_p99"],
            "objectives": {"knn_p99": {"degrade": True}}})
        # a breaching objective NOT marked degrade does not shed
        assert not burn_gates_fired({
            "enabled": True, "degrade_boost": 0,
            "breaching": ["availability"],
            "objectives": {"availability": {"degrade": False}}})


class TestRouting:
    def test_routed_answers_bit_identical_to_direct(
            self, catalog, oracle_store):
        qp = _qpts(8)
        src = oracle_store.get_feature_source("fleeted")
        oracle = [src.knn(CQL, qp[i:i + 1, 0], qp[i:i + 1, 1], k=K)
                  for i in range(8)]
        want_count = src.get_count(CQL)
        sup = _fleet(catalog)
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            for i in range(8):
                got = cli.request(_knn_doc(f"q{i}", qp[i, 0], qp[i, 1]),
                                  timeout_s=300.0)
                assert got["ok"], got
                d, ix, _ = oracle[i]
                assert got["indices"] == [[int(j) for j in row]
                                          for row in ix]
                assert got["dists"] == [
                    [float(v) for v in row] for row in d]  # bit-exact
            got = cli.request({"id": "c", "op": "count",
                               "typeName": "fleeted", "cql": CQL},
                              timeout_s=300.0)
            assert got["ok"] and got["count"] == want_count
            # stats routes like a query and carries the replica's view
            got = cli.request({"id": "s", "op": "stats"})
            assert got["ok"] and got["stats"]["replica"]["state"] == \
                "ready"
            # the router counts a send after it returns, which can be
            # after the reply already reached this client
            deadline = time.monotonic() + 10.0
            while True:
                snap = sup.stats()
                routed = sum(r["routed"] for r in snap["replicas"])
                if routed >= 10 or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            assert snap["router"]["requests"] >= 10
            assert routed >= 10
            cli.close()
        finally:
            sup.close()

    def test_wire_handoff_ops_refused_typed(self, catalog):
        """attach/detach carry a client-materialized wire handoff the
        router cannot audit for exactly-once replay: refused typed on
        EVERY router, rehome or not."""
        sup = _fleet(catalog, n=1)
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            for op in ("attach", "detach"):
                got = cli.request({"id": f"s-{op}", "op": op,
                                   "subscription": "sub-1"})
                assert not got["ok"] and got["error"] == "rejected"
                assert got["reason"] == "unsupported"
            cli.close()
        finally:
            sup.close()

    def test_rehome_disabled_back_compat(self, catalog):
        """rehome=False restores the pre-upgrade surface exactly: the
        hello advertises NO rehome capability and every subscribe verb
        refuses typed `unsupported` — an old client scripted against
        the refusal keeps working."""
        sup = _fleet(catalog, n=1, rehome=False)
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            hello = cli.request({"id": "h", "op": "hello"})
            assert hello["ok"] and "rehome" not in hello
            for op in ("subscribe", "unsubscribe", "poll",
                       "subscriptions", "export_subscription",
                       "pause", "resume"):
                got = cli.request({"id": f"s-{op}", "op": op,
                                   "typeName": "fleeted", "cql": CQL,
                                   "subscription": "sub-1"})
                assert not got["ok"] and got["error"] == "rejected", got
                assert got["reason"] == "unsupported"
                assert "replica-sticky" in got["message"]
            cli.close()
        finally:
            sup.close()

    def test_rehome_capability_advertised(self, catalog):
        sup = _fleet(catalog, n=1)
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            hello = cli.request({"id": "h", "op": "hello"})
            assert hello["ok"] and hello["rehome"] is True
            cli.close()
        finally:
            sup.close()

    def test_burn_gated_replica_sheds_to_healthy_peer(self, catalog):
        """SLO-burn-aware routing: when the affinity-preferred replica's
        burn gates fire, new traffic goes to a healthy peer (and the
        skip is counted); when EVERY replica is gated, traffic still
        flows."""
        sup = _fleet(catalog)
        try:
            sup.start()
            # find a key whose rendezvous affinity prefers r0
            doc = None
            for i in range(64):
                cand = _knn_doc(f"p{i}", float(i * 7 % 60), 5.0)
                ranked = sorted(
                    sup.membership.routable(),
                    key=lambda h: __import__("zlib").crc32(
                        f"{sup.router._affinity_key(cand)}|"
                        f"{h.replica_id}".encode()),
                    reverse=True)
                if ranked[0].replica_id == "r0":
                    doc = cand
                    break
            assert doc is not None
            sup.membership.get("r0").burn_gated = True
            shed0 = sup.stats()["router"]["shed"]
            picked = sup.router._pick(doc, exclude=())
            assert picked.replica_id == "r1"
            assert sup.stats()["router"]["shed"] == shed0 + 1
            # all gated: traffic still flows (shedding to nowhere is
            # an outage, not protection)
            sup.membership.get("r1").burn_gated = True
            assert sup.router._pick(doc, exclude=()) is not None
        finally:
            sup.close()


class TestFailover:
    def test_kill_mid_burst_every_answer_typed_or_exact(
            self, catalog, oracle_store):
        """The satellite certification: kill -9 a replica mid-burst
        under the fault harness; every client answer is a correct
        (bit-identical) result or a typed retryable error; zero
        dropped, zero duplicates."""
        from geomesa_tpu.faults import harness as _harness
        from geomesa_tpu.faults.plan import FaultPlan, FaultRule

        burst = 16
        qp = _qpts(burst, seed=9)
        src = oracle_store.get_feature_source("fleeted")
        oracle = {
            f"q{i}": src.knn(CQL, qp[i:i + 1, 0], qp[i:i + 1, 1], k=K)
            for i in range(burst)}
        sup = _fleet(catalog)
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            # warm both replicas so the burst measures routing, and so
            # in-flight work is genuinely mid-kernel when the kill lands
            for rep in sup.membership.all():
                w = connect_json(rep.host, rep.port)
                w.request(_knn_doc("w", 1.0, 2.0), timeout_s=300.0)
                w.close()
            # injected device latency keeps several requests in flight
            # across the kill (the harness is the load shaper here; its
            # fires need no replay determinism in this test)
            plan = FaultPlan(seed=13, rules=[FaultRule(
                site="device.transfer", error="latency",
                latency_ms=15.0, every=1)])
            with _harness.active(plan):
                for i in range(burst):
                    cli.send(_knn_doc(f"q{i}", qp[i, 0], qp[i, 1]))
                sup.kill_replica("r0", graceful=False)
                answers = {}
                stop = threading.Event()
                timer = threading.Timer(120.0, stop.set)
                timer.start()
                for got in cli.docs(stop):
                    assert got["id"] not in answers, \
                        f"duplicate response {got['id']}"
                    answers[got["id"]] = got
                    if len(answers) >= burst:
                        break
                timer.cancel()
            assert len(answers) == burst, sorted(answers)
            for rid, got in answers.items():
                if got.get("ok"):
                    d, ix, _ = oracle[rid]
                    assert got["indices"] == [
                        [int(j) for j in row] for row in ix], rid
                    assert got["dists"] == [
                        [float(v) for v in row] for row in d], rid
                else:
                    assert got.get("error") in (
                        "unavailable", "rejected", "timeout"), got
                    assert got.get("retryable", True), got
            snap = sup.stats()
            states = {r["replica"]: r["state"]
                      for r in snap["replicas"]}
            assert states == {"r0": "dead", "r1": "ready"}
            # gauge consistency: retries counted on both surfaces
            assert snap["router"]["retried"] == sum(
                r["retried_onto"] for r in snap["replicas"])
            cli.close()
        finally:
            sup.close()

    def test_drain_verb_admin_gated_and_graceful(self, catalog):
        sup = _fleet(catalog)
        try:
            port = sup.start()
            h0 = sup.membership.get("r0")
            # a plain client may not drain
            direct = connect_json(h0.host, h0.port)
            got = direct.request({"id": "d0", "op": "drain"})
            assert not got["ok"] and got["reason"] == "admin_required"
            # an admin connection drains: hello upgrades the role
            hello = direct.request({"id": "h", "op": "hello",
                                    "role": "admin"})
            assert hello["ok"] and hello["admin"] is True
            assert hello["replica"] == "r0"
            got = direct.request({"id": "d1", "op": "drain"},
                                 timeout_s=120.0)
            assert got["ok"] and got["state"] == "dead", got
            direct.close()
            assert h0.server.state == "dead"
            # the survivor keeps serving through the router
            cli = connect_json("127.0.0.1", port)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                got = cli.request(_knn_doc("a1", 3.0, 4.0),
                                  timeout_s=120.0)
                if got.get("ok"):
                    break
                assert got.get("error") in ("unavailable",), got
            assert got["ok"], got
            cli.close()
        finally:
            sup.close()

    def test_warming_gate_refuses_until_check_green(self, catalog):
        """A fresh replica refuses traffic typed+retryable until its
        warmup manifest replays with --check semantics green — and the
        router never considers it routable before ready."""
        from geomesa_tpu.compilecache.manifest import WarmupManifest
        from geomesa_tpu.fleet.membership import ReplicaHandle

        sup = _fleet(catalog, n=1)
        try:
            sup.start()
            mpath = catalog + "/warm_manifest.json"
            WarmupManifest().save(mpath)
            hold = threading.Event()
            rep = ReplicaServer(
                lambda: DataStore(catalog, use_device_cache=True),
                replica_id="w0", warmup_manifest=mpath,
                warmup_hold=hold)
            port = rep.start()
            handle = ReplicaHandle(replica_id="w0", host="127.0.0.1",
                                   port=port, spawn="thread",
                                   server=rep)
            sup.membership.add(handle)
            sup.router.attach(handle)
            assert rep.wait_state("warming", timeout=60.0) == "warming"
            probe = connect_json("127.0.0.1", port)
            got = probe.request(_knn_doc("w1", 1.0, 2.0))
            assert not got["ok"] and got["reason"] == "warming"
            assert got["retryable"] is True
            # control verbs still answer while warming
            st = probe.request({"id": "s", "op": "stats"})
            assert st["ok"] and st["stats"]["replica"]["state"] == \
                "warming"
            assert not any(h.replica_id == "w0"
                           for h in sup.membership.routable())
            hold.set()
            assert rep.wait_state("ready", timeout=120.0) == "ready"
            assert rep.warmup_report is not None and \
                rep.warmup_report.ok
            got = probe.request(_knn_doc("w2", 1.0, 2.0),
                                timeout_s=120.0)
            assert got["ok"], got
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if any(h.replica_id == "w0"
                       for h in sup.membership.routable()):
                    break
                time.sleep(0.05)
            assert any(h.replica_id == "w0"
                       for h in sup.membership.routable())
            probe.close()
            rep.stop()
        finally:
            sup.close()

    def test_rolling_restart(self, catalog):
        sup = _fleet(catalog)
        try:
            port = sup.start()
            result = sup.rolling_restart()
            assert result["ok"], result
            assert len(result["rolled"]) == 2
            assert all(r["state"] == "ready" for r in result["rolled"])
            snap = sup.stats()
            states = {r["replica"]: r["state"]
                      for r in snap["replicas"]}
            # old incarnations dead, fresh ones (r0.1, r1.1) serving
            assert states["r0"] == "dead" and states["r1"] == "dead"
            assert states["r0.1"] == "ready"
            assert states["r1.1"] == "ready"
            cli = connect_json("127.0.0.1", port)
            got = cli.request(_knn_doc("rr", 5.0, 6.0), timeout_s=120.0)
            assert got["ok"], got
            cli.close()
        finally:
            sup.close()


class TestProtocolDrain:
    def test_standalone_serve_lines_drain(self, catalog):
        """The drain verb without a fleet: `serve_lines` (stdin is the
        process owner's, hence admin) drains in place — in-flight
        work finishes, later requests answer typed shutting_down."""
        from geomesa_tpu.serve.protocol import serve_lines

        store = DataStore(catalog, use_device_cache=True)
        out = []
        lines = [
            json.dumps({"id": "c1", "op": "count",
                        "typeName": "fleeted", "cql": CQL}),
            json.dumps({"id": "d1", "op": "drain"}),
            json.dumps({"id": "c2", "op": "count",
                        "typeName": "fleeted", "cql": CQL}),
        ]
        serve_lines(store, lines, out.append)
        docs = {json.loads(s)["id"]: json.loads(s) for s in out}
        assert docs["c1"]["ok"]
        assert docs["d1"]["ok"] and docs["d1"]["state"] == "drained"
        assert not docs["c2"]["ok"]
        assert docs["c2"]["reason"] == "shutting_down"

    def test_wire_restart_is_admin_gated(self, catalog):
        from geomesa_tpu.fleet import FleetClient

        sup = _fleet(catalog, n=1)
        try:
            port = sup.start()
            cli = FleetClient("127.0.0.1", port)
            got = cli.request({"op": "restart"})
            assert not got["ok"] and got["reason"] == "admin_required"
            cli.close()
        finally:
            sup.close()

    def test_router_never_proxies_drain(self, catalog):
        """The router's replica links are admin-privileged, so
        forwarding a client's drain would launder it past the
        replica-side admin gate: the router must refuse the verb for
        EVERY session and leave the replica serving."""
        from geomesa_tpu.fleet import FleetClient

        sup = _fleet(catalog, n=1)
        try:
            port = sup.start()
            cli = FleetClient("127.0.0.1", port)
            got = cli.request({"op": "drain"})
            assert not got["ok"] and got["reason"] == "admin_required"
            cli.hello(role="admin")
            got = cli.request({"op": "drain"})
            assert not got["ok"] and got["reason"] == "unsupported"
            # the replica is untouched and still serving
            assert sup.membership.get("r0").state == "ready"
            got = cli.request({"id": "q", "op": "count",
                               "typeName": "fleeted", "cql": CQL},
                              timeout_s=120.0)
            assert got["ok"]
            cli.close()
        finally:
            sup.close()


class TestMetricsPort:
    def test_ephemeral_port_reported(self, catalog):
        """Satellite: MetricsServer port=0 + the bound port reported in
        stats() and the debug endpoints — N replicas on one host must
        not collide on a fixed port."""
        import urllib.request

        rep = ReplicaServer(
            lambda: DataStore(catalog, use_device_cache=True),
            replica_id="m0", metrics_port=0)
        rep.start()
        try:
            assert rep.wait_state("ready", timeout=60.0) == "ready"
            assert rep.metrics_port not in (None, 0)
            assert rep.svc.stats()["metrics_port"] == rep.metrics_port
            assert rep.describe()["metrics_port"] == rep.metrics_port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rep.metrics_port}/healthz",
                    timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert doc["endpoint"]["port"] == rep.metrics_port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rep.metrics_port}/debug/stats",
                    timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert doc["endpoint"]["port"] == rep.metrics_port
            assert doc["serve"]["metrics_port"] == rep.metrics_port
        finally:
            rep.stop()

    def test_fleet_snapshot_reports_bound_ports(self, catalog):
        """The {"op": "fleet"} / status document must carry each
        replica's BOUND ephemeral metrics port (thread replicas bind
        theirs asynchronously during init)."""
        sup = _fleet(catalog, metrics_port=0)
        try:
            sup.start()
            ports = [r["metrics_port"]
                     for r in sup.stats()["replicas"]]
            assert all(p not in (None, 0) for p in ports), ports
            assert len(set(ports)) == len(ports), ports
        finally:
            sup.close()

    def test_two_replicas_distinct_ports(self, catalog):
        reps = [ReplicaServer(
            lambda: DataStore(catalog, use_device_cache=True),
            replica_id=f"mp{i}", metrics_port=0) for i in range(2)]
        try:
            for r in reps:
                r.start()
            for r in reps:
                assert r.wait_state("ready", timeout=60.0) == "ready"
            ports = {r.metrics_port for r in reps}
            assert len(ports) == 2 and None not in ports
        finally:
            for r in reps:
                r.stop()


class TestChipPlacement:
    """Process replicas on a TPU host: one chip each, and a typed
    refusal, before anything starts, for replicas beyond the chips."""

    @pytest.fixture()
    def chips(self, monkeypatch):
        from geomesa_tpu.fleet import supervisor

        def stub(n):
            monkeypatch.setattr(supervisor, "local_tpu_chips", lambda: n)

        return stub

    def test_more_replicas_than_chips_refused_at_start(self, catalog,
                                                       chips):
        from geomesa_tpu.fleet.supervisor import ChipPlacementError

        chips(1)
        sup = FleetSupervisor(FleetConfig(
            n_replicas=2, catalog=catalog, spawn="process"))
        with pytest.raises(ChipPlacementError, match="1 TPU chip"):
            sup.start()
        assert sup.membership.all() == []
        assert sup.router._listener is None  # nothing started

    @pytest.mark.parametrize("spawn,force_cpu", [
        ("thread", False), ("process", True)])
    def test_no_chip_placement_off_the_chip(self, catalog, chips, spawn,
                                            force_cpu):
        chips(1)
        sup = FleetSupervisor(FleetConfig(
            n_replicas=2, catalog=catalog, spawn=spawn,
            force_cpu_workers=force_cpu))
        sup._check_chip(1)  # no refusal: these replicas hold no chip

    @pytest.mark.parametrize("devices,platforms,want", [
        ([("accel", "0x1ae0"), ("accel", "0x1ae0")], None, 2),
        ([("accel", "0x1ae0"), ("accel", "0x8086")], "tpu", 1),
        ([("vfio", "0x1ae0")] * 4, None, 4),
        # GPU / NIC passthrough: VFIO groups that are not TPUs
        ([("vfio", "0x10de"), ("vfio", "0x8086")], None, 0),
        ([("vfio", "0x1ae0")] * 4, "cpu", 0),
        ([], None, 0),
    ])
    def test_chip_detection(self, tmp_path, monkeypatch, devices,
                            platforms, want):
        """local_tpu_chips against a fake /dev and /sys tree."""
        from geomesa_tpu.fleet.supervisor import local_tpu_chips

        dev, sysfs = tmp_path / "dev", tmp_path / "sys"
        (dev / "vfio").mkdir(parents=True)
        (dev / "vfio" / "vfio").touch()  # the VFIO control node
        for i, (kind, vendor) in enumerate(devices):
            if kind == "accel":
                (dev / f"accel{i}").touch()
                d = sysfs / "class" / "accel" / f"accel{i}" / "device"
            else:
                (dev / "vfio" / str(i)).touch()
                d = (sysfs / "kernel" / "iommu_groups" / str(i)
                     / "devices" / f"0000:00:0{i}.0")
            d.mkdir(parents=True)
            (d / "vendor").write_text(vendor + "\n")
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        assert local_tpu_chips(str(dev), str(sysfs)) == want


@pytest.mark.slow
class TestProcessSpawn:
    def test_process_fleet_kill_and_failover(self, catalog):
        """Real OS-process replicas (jax import and all): spawn 2,
        serve, kill -9 one, keep serving. The deployment shape."""
        sup = FleetSupervisor(FleetConfig(
            n_replicas=2, catalog=catalog, spawn="process",
            probe_interval_s=0.3, force_cpu_workers=True))
        try:
            port = sup.start()
            cli = connect_json("127.0.0.1", port)
            got = cli.request(_knn_doc("p1", 1.0, 2.0),
                              timeout_s=600.0)
            assert got["ok"], got
            victim = sup.membership.get("r0")
            assert victim.pid is not None
            sup.kill_replica("r0", graceful=False)
            got = cli.request(_knn_doc("p2", 3.0, 4.0),
                              timeout_s=600.0)
            assert got["ok"], got
            states = {r["replica"]: r["state"]
                      for r in sup.stats()["replicas"]}
            assert states["r0"] == "dead" and states["r1"] == "ready"
            cli.close()
        finally:
            sup.close()


# -- fleet-native standing queries (router-side re-homing) -----------------

SUB_SFT = SimpleFeatureType.from_spec(
    "live", "name:String,score:Double,dtg:Date,*geom:Point")
SUB_CQL = "BBOX(geom, -20, -15, 25, 20)"
SUB_FIDS = [f"v{i}" for i in range(24)]


def _sub_rows(seed, fids=SUB_FIDS):
    rng = np.random.default_rng(seed)
    n = len(fids)
    return FeatureBatch.from_pydict(SUB_SFT, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-5, 5, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack([rng.uniform(-60, 60, n),
                          rng.uniform(-30, 30, n)], 1),
    }, fids=list(fids))


def _kafka_fleet(n=2, **kw):
    """A fleet whose replicas share ONE Kafka live layer (fold hooks
    are a store-level list, so every replica's evaluator sees every
    event — the deployment shape for standing queries)."""
    from geomesa_tpu.kafka.store import KafkaDataStore

    store = KafkaDataStore()
    src = store.create_schema(SUB_SFT)
    sup = FleetSupervisor(FleetConfig(
        n_replicas=n, store_factory=lambda: store,
        probe_interval_s=0.1, **kw))
    return store, src, sup


def _replay(frames, sid):
    """Host-oracle replay of a client's frame stream: asserts zero
    duplicate-enter / phantom-exit transitions, returns the final
    matched set. State frames (initial or resync) reset by contract."""
    state = set()
    for f in sorted((f for f in frames
                     if f.get("subscription") == sid
                     and f.get("event") in ("enter", "exit", "state")),
                    key=lambda f: f["seq"]):
        if f["event"] == "state":
            state = set(f["fids"])
        elif f["event"] == "enter":
            dup = set(f["fids"]) & state
            assert not dup, f"duplicate enter for {sorted(dup)}"
            state |= set(f["fids"])
        else:
            ghost = set(f["fids"]) - state
            assert not ghost, f"phantom exit for {sorted(ghost)}"
            state -= set(f["fids"])
    return state


def _assert_seq_monotonic(frames, sid):
    seqs = [f["seq"] for f in frames if f.get("subscription") == sid]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs), seqs


def _wait_rehomed(sup, sid, old_owner, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        row = sup.membership.sub_owner(sid)
        if row is not None and row.replica_id != old_owner:
            return row
        time.sleep(0.02)
    raise AssertionError(
        f"subscription {sid} never re-homed off {old_owner}")


def _wait_checkpoint(sup, sid, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        row = sup.membership.sub_owner(sid)
        if row is not None and row.checkpoint is not None:
            return row
        time.sleep(0.02)
    raise AssertionError(f"no checkpoint piggybacked for {sid}")


class TestRehome:
    """Fleet-native standing queries: the router homes, checkpoints,
    and re-homes subscriptions across replica failover — the client
    reads one connection and sees at most one resync per kill."""

    def test_routed_parity_with_direct_subscription(self):
        """The matched sets a routed subscription replays to are
        bit-identical to a direct single-replica subscription fed the
        same stream — routing adds zero semantic drift."""
        from geomesa_tpu.kafka.store import KafkaDataStore
        from geomesa_tpu.subscribe import SubscriptionManager

        # direct reference: one manager over its own store
        ref_store = KafkaDataStore()
        ref_store.create_schema(SUB_SFT)
        mgr = SubscriptionManager(ref_store)
        ref_sub = mgr.subscribe("live", SUB_CQL)
        ref_frames = []
        mgr.flush(ref_frames.append)

        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid = got["subscription"]
            for k in range(3):
                b = _sub_rows(100 + k)
                src.write(b)
                got = cli.request({"op": "poll"},
                                  on_push=frames.append)
                assert got["ok"], got
                ref_store.write("live", _sub_rows(100 + k))
                ref_store.poll("live")
                mgr.flush(lambda f: ref_frames.append(f))
                # bit-identical matched set after EVERY batch
                assert _replay(frames, sid) == \
                    _replay(ref_frames, ref_sub.sub_id)
            cli.close()
        finally:
            sup.close()
            mgr.close()

    def test_kill_rehomes_single_resync(self):
        """The tentpole certification: abrupt owner death mid-stream →
        the router replays the subscription onto the survivor from the
        piggybacked checkpoint; the client sees exactly ONE resync,
        monotonic seq, and a replay that matches the live oracle —
        with zero client choreography."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            assert sid.startswith("rs")   # the replica id never leaks
            src.write(_sub_rows(1))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            _wait_checkpoint(sup, sid)
            sup.kill_replica(owner, graceful=False)
            row = _wait_rehomed(sup, sid, owner)
            assert row.rehomes == 1
            src.write(_sub_rows(2))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            resyncs = sum(1 for f in evs[1:]
                          if f.get("event") == "state")
            assert resyncs == 1, evs
            # replayed matched set == live snapshot oracle
            matched = _replay(evs, sid)
            h = sup.membership.get(row.replica_id)
            live = h.server.svc.subscriptions.registry.maybe(
                row.replica_sub_id)
            assert matched == live.matched
            # ownership + telemetry surfaces agree
            snap = sup.stats()
            assert snap["subscriptions"] == 1
            assert snap["sub_rehomes"] == 1
            assert snap["router"]["rehome_attempted"] == 1
            assert snap["router"]["rehome_succeeded"] == 1
            assert snap["router"]["rehome_failed"] == 0
            owned = {r["replica"]: r["subs_owned"]
                     for r in snap["replicas"]}
            assert owned[row.replica_id] == 1
            assert owned[owner] == 0
            assert isinstance(
                sup.membership.export_checkpoint_staleness(), dict)
            cli.close()
        finally:
            sup.close()

    def test_double_failover_seq_continuity(self):
        """Kill the owner, then kill the NEW owner: the sequence the
        client sees stays strictly monotonic across both moves — one
        resync per kill, never more."""
        store, src, sup = _kafka_fleet(n=3)
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            for kill_round in (1, 2):
                src.write(_sub_rows(10 + kill_round))
                assert cli.request({"op": "poll"},
                                   on_push=frames.append)["ok"]
                _wait_checkpoint(sup, sid)
                sup.kill_replica(owner, graceful=False)
                row = _wait_rehomed(sup, sid, owner)
                assert row.rehomes == kill_round
                owner = row.replica_id
            src.write(_sub_rows(13))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            resyncs = sum(1 for f in evs[1:]
                          if f.get("event") == "state")
            assert resyncs == 2, evs   # exactly one per kill
            matched = _replay(evs, sid)
            h = sup.membership.get(owner)
            row = sup.membership.sub_owner(sid)
            live = h.server.svc.subscriptions.registry.maybe(
                row.replica_sub_id)
            assert matched == live.matched
            assert sup.stats()["router"]["rehome_succeeded"] == 2
            cli.close()
        finally:
            sup.close()

    def test_lagged_overflow_then_kill_single_resync_each(self):
        """An outbox overflow (typed `subscription_lagged` + its state
        resync) racing a re-home stays coherent: the client sees the
        lagged resync, then ONE re-home resync — replay is exact, seq
        monotonic, nothing double-resynced."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL, "outboxLimit": 2},
                              on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            # fold server-side WITHOUT flushing (direct store.poll
            # skips the replica's drain): three folds queue more than
            # the 2-slot outbox holds -> overflow -> lagged marker
            for k in range(3):
                src.write(_sub_rows(30 + k))
                store.poll("live")
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            assert any(f.get("event") == "subscription_lagged"
                       for f in frames
                       if f.get("subscription") == sid), frames
            _wait_checkpoint(sup, sid)
            sup.kill_replica(owner, graceful=False)
            _wait_rehomed(sup, sid, owner)
            src.write(_sub_rows(35))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            # exactly two resyncs past the initial state: the lagged
            # recovery and the re-home — the race never stacks extras
            resyncs = sum(1 for f in evs[1:]
                          if f.get("event") == "state")
            assert resyncs == 2, evs
            row = sup.membership.sub_owner(sid)
            live = sup.membership.get(
                row.replica_id).server.svc.subscriptions.registry \
                .maybe(row.replica_sub_id)
            assert _replay(evs, sid) == live.matched
            cli.close()
        finally:
            sup.close()

    def test_paused_sub_rehomes_paused_resyncs_on_resume(self):
        """Pause rides the checkpoint: a paused subscription re-homes
        PAUSED (no frames while the client is away) and pays its one
        state resync when resumed."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            src.write(_sub_rows(40))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            got = cli.request({"op": "pause", "subscription": sid},
                              on_push=frames.append)
            assert got["ok"] and got["status"] == "paused", got
            assert got["subscription"] == sid
            # wait for a checkpoint carrying the paused status
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                row = sup.membership.sub_owner(sid)
                if row is not None and row.paused \
                        and row.checkpoint is not None:
                    break
                time.sleep(0.02)
            row = sup.membership.sub_owner(sid)
            assert row.paused and row.checkpoint is not None
            n_before = len([f for f in frames
                            if f.get("subscription") == sid])
            sup.kill_replica(owner, graceful=False)
            row = _wait_rehomed(sup, sid, owner)
            # landed paused on the survivor: no frames delivered
            live = sup.membership.get(
                row.replica_id).server.svc.subscriptions.registry \
                .maybe(row.replica_sub_id)
            assert live.status == "paused"
            src.write(_sub_rows(41))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            assert len(evs) == n_before, "paused sub leaked frames"
            got = cli.request({"op": "resume", "subscription": sid},
                              on_push=frames.append)
            assert got["ok"] and got["status"] == "active", got
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            # the resume's resync covers everything folded while away
            assert evs[-1]["event"] in ("state", "enter", "exit")
            live = sup.membership.get(
                row.replica_id).server.svc.subscriptions.registry \
                .maybe(row.replica_sub_id)
            assert _replay(evs, sid) == live.matched
            cli.close()
        finally:
            sup.close()

    def test_quarantined_sub_not_rehomed(self):
        """A quarantined subscription's stream ends with its terminal
        frame: ownership is dropped at the frame, so the death sweep
        has nothing to replay — a poisoned predicate cannot chase the
        fleet through failovers."""
        from geomesa_tpu.serve.service import ServeConfig

        store, src, sup = _kafka_fleet(
            serve_config=ServeConfig(quarantine_after=2))
        frames = []

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

        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": "score > 1.5"},
                              on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            mgr = sup.membership.get(owner).server.svc.subscriptions
            mgr.evaluator._filters[("live", "score > 1.5")] = _Poison()
            for k in range(3):
                src.write(_sub_rows(50 + k))
                assert cli.request({"op": "poll"},
                                   on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            assert any(f.get("event") == "quarantined"
                       for f in evs), evs
            # ownership died with the terminal frame
            assert sup.membership.sub_owner(sid) is None
            assert sup.stats()["subscriptions"] == 0
            sup.kill_replica(owner, graceful=False)
            time.sleep(0.5)
            st = sup.stats()["router"]
            assert st["rehome_attempted"] == 0
            assert st["rehome_succeeded"] == 0
            cli.close()
        finally:
            sup.close()

    def test_density_window_rehomes_by_reseed(self):
        """Density-window subscriptions have no incremental handoff
        snapshot (registry refuses one by contract) — the re-home path
        re-seeds from the survivor's live snapshot instead, and the
        client still pays exactly one resync."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request(
                {"op": "subscribe", "typeName": "live",
                 "density": {"bbox": [-60.0, -30.0, 60.0, 30.0],
                             "width": 16, "height": 8}},
                on_push=frames.append)
            assert got["ok"], got
            sid, owner = got["subscription"], got["replica"]
            assert got["mode"] == "density"
            src.write(_sub_rows(60))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            sup.kill_replica(owner, graceful=False)
            row = _wait_rehomed(sup, sid, owner)
            assert row.mode == "density"
            src.write(_sub_rows(61))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            # density frames after the kill keep flowing off the
            # survivor's re-seeded window
            assert any(f.get("event") == "density" for f in evs), evs
            assert sup.stats()["router"]["rehome_succeeded"] == 1
            cli.close()
        finally:
            sup.close()

    def test_rolling_restart_drains_subscriptions(self):
        """Zero-downtime roll with live standing queries: every
        subscription is exported fresh, re-homed to a survivor, and
        still delivering after BOTH replicas have been replaced — the
        client reads one connection throughout."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid = got["subscription"]
            src.write(_sub_rows(70))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            result = sup.rolling_restart()
            assert result["ok"], result
            moved = sum(r["subs"]["moved"] for r in result["rolled"])
            failed = sum(r["subs"]["failed"] for r in result["rolled"])
            assert moved >= 1 and failed == 0, result
            # the subscription is live on a fresh incarnation
            row = sup.membership.sub_owner(sid)
            assert row is not None
            assert sup.membership.get(row.replica_id).state == "ready"
            src.write(_sub_rows(71))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            evs = [f for f in frames if f.get("subscription") == sid]
            _assert_seq_monotonic(evs, sid)
            live = sup.membership.get(
                row.replica_id).server.svc.subscriptions.registry \
                .maybe(row.replica_sub_id)
            assert _replay(evs, sid) == live.matched
            cli.close()
        finally:
            sup.close()

    def test_client_disconnect_releases_ownership(self):
        """A hung-up client's subscriptions are cancelled on the owner
        and dropped from the ownership table — no orphan streams, no
        leaked re-homes on a later kill."""
        store, src, sup = _kafka_fleet()
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL})
            assert got["ok"], got
            sid = got["subscription"]
            assert sup.membership.sub_owner(sid) is not None
            cli.close()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sup.membership.sub_owner(sid) is None:
                    break
                time.sleep(0.05)
            assert sup.membership.sub_owner(sid) is None
            assert sup.stats()["subscriptions"] == 0
        finally:
            sup.close()

    def test_export_subscription_renumbered_to_client_seq(self):
        """export_subscription through the router hands out a snapshot
        in CLIENT-visible numbering (watermark = what the client has
        seen), so a wire handoff taken through the fleet endpoint can
        seed a direct replica subscription without seq regression."""
        store, src, sup = _kafka_fleet()
        frames = []
        try:
            from geomesa_tpu.fleet.router import FleetClient

            port = sup.start()
            cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = cli.request({"op": "subscribe", "typeName": "live",
                               "cql": SUB_CQL},
                              on_push=frames.append)
            assert got["ok"], got
            sid = got["subscription"]
            src.write(_sub_rows(80))
            assert cli.request({"op": "poll"},
                               on_push=frames.append)["ok"]
            got = cli.request({"op": "export_subscription",
                               "subscription": sid},
                              on_push=frames.append)
            assert got["ok"], got
            snap = got["handoff"]
            evs = [f for f in frames if f.get("subscription") == sid]
            assert snap["watermark"] == max(f["seq"] for f in evs)
            assert snap["seq"] >= snap["watermark"]
            assert set(snap["matched"]) == _replay(evs, sid)
            cli.close()
        finally:
            sup.close()
