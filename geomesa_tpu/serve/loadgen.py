"""Load generator for the serving layer (`gmtpu bench-serve`).

Two workload shapes, because they answer different questions:

- closed loop: N clients issue back-to-back queries (each waits for its
  response before sending the next). Measures sustainable throughput and
  the latency the system settles into under exactly-N outstanding
  requests. Throughput rises with N until the device saturates.
- open loop: arrivals at a fixed rate regardless of completions — the
  shape real traffic has. Latency here includes queue wait, so an
  offered rate above capacity shows UNBOUNDED latency growth... unless
  admission control sheds, which is precisely what the bounded queue +
  QueryRejected are for. The report separates served from shed.

Reports throughput plus p50/p95/p99/max latency (exact, from raw
samples — the serving histograms are bucket estimates; a bench should
not inherit their quantization), and the service's dispatch/coalesce
counters so a coalesced-vs-serial comparison is one subtraction.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import wait as futures_wait
from typing import Callable, Dict, List, Optional

import numpy as np

from geomesa_tpu.plan.planner import QueryTimeout
from geomesa_tpu.serve.scheduler import QueryRejected, ServeRequest
from geomesa_tpu.serve.service import QueryService


@dataclasses.dataclass
class LoadReport:
    mode: str
    duration_s: float
    sent: int
    ok: int
    rejected: int
    timeouts: int
    errors: int
    throughput_qps: float
    mean_ms: float
    p50_ms: float
    p95_ms: float
    p99_ms: float
    max_ms: float
    dispatches: int
    coalesced: int
    # sustained mode (docs/SERVING.md "Pipelined dispatch"): the
    # headline pts/s (store points scanned x served queries / wall) and
    # how deep the dispatch pipeline actually ran — the numbers the
    # 523M→700M sustained claim is reproduced from
    pts_per_s: float = 0.0
    windows_in_flight_max: int = 0
    pipelined_windows: int = 0
    fused_counts: int = 0
    # persistent serve loop (docs/SERVING.md "Persistent serve loop"):
    # how many windows rode a ring program, how many fell back typed,
    # and the per-window device-interaction count (`serve.device.ops`
    # delta / windows) — the number `bench-serve --mode sustained
    # --ring` compares against the pipelined baseline
    ring_windows: int = 0
    ring_fallbacks: int = 0
    dispatches_per_window: float = 0.0
    # sharded serving (docs/SERVING.md "Sharded serving"): the mesh the
    # service dispatched on (0 = single-chip) and the headline pts/s
    # normalized per shard — the capacity-multiplier number the
    # ROADMAP item-1 claim is judged on
    mesh_devices: int = 0
    per_shard_pts_per_s: float = 0.0
    # subscribe mode (docs/SERVING.md "Standing queries"): N standing
    # subscriptions folded over M kafka batches — throughput is pushed
    # events/s, latency is the per-batch poll->eval->push cycle, and
    # `dispatches` is the evaluator's fused-kernel count (the
    # one-dispatch-per-poll invariant makes it == batches when warm)
    subscriptions: int = 0
    batches: int = 0
    events_total: int = 0
    events_per_s: float = 0.0
    # approx mode (docs/SERVING.md "Approximate answers"): tolerant vs
    # exact client split — the headline is approx_speedup_p50 (sketch
    # tier vs exact device scan at identical bound-respecting
    # accuracy) plus the serving-tier shares
    approx_ok: int = 0
    exact_ok: int = 0
    approx_p50_ms: float = 0.0
    approx_p99_ms: float = 0.0
    exact_p50_ms: float = 0.0
    exact_p99_ms: float = 0.0
    approx_speedup_p50: float = 0.0
    tier_sketch: int = 0
    tier_cached: int = 0
    tier_exact: int = 0
    bound_violations: int = 0
    # wire mode (docs/SERVING.md "Columnar wire"): JSON-lines vs
    # columnar record-batch encode throughput over identical results,
    # plus the push fan-out (frames x sinks through the one-encode
    # PushMux) — the headline is wire_speedup (rows/s ratio) and the
    # one-encode invariant (push_encodes == frames published)
    wire_rows: int = 0
    wire_json_rows_s: float = 0.0
    wire_columnar_rows_s: float = 0.0
    wire_speedup: float = 0.0
    wire_json_bytes: int = 0
    wire_columnar_bytes: int = 0
    wire_json_p50_ms: float = 0.0
    wire_json_p99_ms: float = 0.0
    wire_columnar_p50_ms: float = 0.0
    wire_columnar_p99_ms: float = 0.0
    push_sinks: int = 0
    push_frames: int = 0
    push_encodes: int = 0
    push_events_per_s: float = 0.0
    wire_parity_ok: bool = True

    def to_json(self) -> dict:
        doc = dataclasses.asdict(self)
        if self.mode != "approx":
            for k in ("approx_ok", "exact_ok", "approx_p50_ms",
                      "approx_p99_ms", "exact_p50_ms", "exact_p99_ms",
                      "approx_speedup_p50", "tier_sketch", "tier_cached",
                      "tier_exact", "bound_violations"):
                doc.pop(k, None)
        if self.mode != "wire":
            for k in ("wire_rows", "wire_json_rows_s",
                      "wire_columnar_rows_s", "wire_speedup",
                      "wire_json_bytes", "wire_columnar_bytes",
                      "wire_json_p50_ms", "wire_json_p99_ms",
                      "wire_columnar_p50_ms", "wire_columnar_p99_ms",
                      "push_sinks", "push_frames", "push_encodes",
                      "push_events_per_s", "wire_parity_ok"):
                doc.pop(k, None)
        return doc


def device_ops_count() -> float:
    """Process-lifetime `serve.device.ops` counter: one tick per
    serve-path device interaction (staged transfer, kernel/program
    dispatch, combined sync read — utils.metrics.note_device_op). The
    delta across a measured run over the window count is
    `dispatches_per_window`, the ring-vs-pipeline headline."""
    from geomesa_tpu.utils.metrics import metrics

    with metrics._lock:
        return float(metrics.counters.get("serve.device.ops", 0.0))


def mesh_dispatch_count() -> float:
    """Process-lifetime count of windows that actually ran a mesh
    route (whole-mesh programs + shard-affinity local windows). The
    delta across a measured run is the honest "did the mesh serve
    this?" signal the topology reporting keys on (bench-serve uses it
    too for closed/open modes)."""
    from geomesa_tpu.utils.metrics import metrics

    with metrics._lock:
        c = metrics.counters
        return float(c.get("knn.mesh.dispatches", 0.0)
                     + c.get("knn.mesh.local_dispatches", 0.0))


def _report(mode: str, duration: float, lat_s: List[float], sent: int,
            rejected: int, timeouts: int, errors: int,
            stats: Dict[str, int]) -> LoadReport:
    lat = np.asarray(lat_s, np.float64) * 1000.0
    ok = len(lat)

    def q(p):
        return float(np.percentile(lat, p)) if ok else 0.0

    return LoadReport(
        mode=mode,
        duration_s=duration,
        sent=sent,
        ok=ok,
        rejected=rejected,
        timeouts=timeouts,
        errors=errors,
        throughput_qps=ok / duration if duration > 0 else 0.0,
        mean_ms=float(lat.mean()) if ok else 0.0,
        p50_ms=q(50), p95_ms=q(95), p99_ms=q(99),
        max_ms=float(lat.max()) if ok else 0.0,
        dispatches=stats.get("dispatches", 0),
        coalesced=stats.get("coalesced", 0),
    )


class _Tally:
    def __init__(self):
        self.lock = threading.Lock()
        self.lat_s: List[float] = []
        self.sent = 0
        self.rejected = 0
        self.timeouts = 0
        self.errors = 0

    def outcome(self, t0: float, fut) -> None:
        try:
            fut.result()
            dt = time.monotonic() - t0
            with self.lock:
                self.lat_s.append(dt)
        except QueryTimeout:
            with self.lock:
                self.timeouts += 1
        except QueryRejected:
            with self.lock:
                self.rejected += 1
        except Exception:
            with self.lock:
                self.errors += 1


def run_closed_loop(
    service: QueryService,
    make_request: Callable[[int], ServeRequest],
    concurrency: int = 8,
    duration_s: float = 5.0,
    requests_per_client: Optional[int] = None,
) -> LoadReport:
    """N clients, each submit→wait→repeat until the duration elapses (or
    a fixed per-client request count when given — deterministic mode for
    tests)."""
    tally = _Tally()
    base = service.stats()
    deadline = time.monotonic() + duration_s

    def client(cid: int):
        i = 0
        while True:
            if requests_per_client is not None:
                if i >= requests_per_client:
                    return
            elif time.monotonic() >= deadline:
                return
            with tally.lock:
                tally.sent += 1
            t0 = time.monotonic()
            try:
                fut = service.submit(make_request(cid * 1_000_003 + i))
            except QueryRejected:
                with tally.lock:
                    tally.rejected += 1
                i += 1
                continue
            tally.outcome(t0, fut)
            i += 1

    t_start = time.monotonic()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    stats = service.stats()
    delta = {k: stats.get(k, 0) - base.get(k, 0)
             for k in ("dispatches", "coalesced")}
    return _report("closed", wall, tally.lat_s, tally.sent,
                   tally.rejected, tally.timeouts, tally.errors, delta)


def run_open_loop(
    service: QueryService,
    make_request: Callable[[int], ServeRequest],
    rate_qps: float = 100.0,
    duration_s: float = 5.0,
) -> LoadReport:
    """Fixed-rate arrivals (uniform spacing), submissions never wait for
    completions. Latency = submit→resolve, queue wait included."""
    if rate_qps <= 0:
        raise ValueError("rate_qps must be > 0")
    tally = _Tally()
    base = service.stats()
    interval = 1.0 / rate_qps
    pending: List[tuple] = []
    t_start = time.monotonic()
    deadline = t_start + duration_s
    i = 0
    while True:
        due = t_start + i * interval
        now = time.monotonic()
        if due >= deadline:
            break
        if due > now:
            time.sleep(due - now)
        with tally.lock:
            tally.sent += 1
        t0 = time.monotonic()
        try:
            fut = service.submit(make_request(i))
            pending.append((t0, fut))
        except QueryRejected:
            with tally.lock:
                tally.rejected += 1
        i += 1
    for t0, fut in pending:
        tally.outcome(t0, fut)
    wall = time.monotonic() - t_start
    stats = service.stats()
    delta = {k: stats.get(k, 0) - base.get(k, 0)
             for k in ("dispatches", "coalesced")}
    return _report("open", wall, tally.lat_s, tally.sent,
                   tally.rejected, tally.timeouts, tally.errors, delta)


def run_sustained(
    service: QueryService,
    make_request: Callable[[int], ServeRequest],
    duration_s: float = 5.0,
    max_outstanding: int = 32,
    points_per_query: int = 0,
    requests: Optional[int] = None,
) -> LoadReport:
    """Sustained-throughput mode (`gmtpu bench-serve --mode sustained`):
    a fixed-duration closed loop that keeps exactly `max_outstanding`
    requests in flight — submissions are gated by a semaphore released
    from future callbacks, not by per-client turnarounds — and reports
    points/sec plus the pipeline's windows-in-flight, not just latency
    percentiles. This is the loop that reproduces the BENCH sustained
    pts/s headline from the CLI: `pts_per_s = points_per_query *
    served_qps` (each served query scans the whole resident store).
    `requests` caps total submissions for deterministic test runs."""
    tally = _Tally()
    base = service.stats()
    mesh_base = mesh_dispatch_count()
    ops_base = device_ops_count()
    pipe = getattr(service, "pipeline", None)
    if pipe is not None:
        # the in-flight high-water must be THIS run's, not the service
        # lifetime's (a warmup pass on the same service would otherwise
        # donate its peak)
        pipe.reset_max_inflight()
    gate = threading.Semaphore(max_outstanding)
    deadline = time.monotonic() + duration_s
    inflight = []
    t_start = time.monotonic()

    def on_done(t0):
        # latency stamps at RESOLUTION time (the callback runs on the
        # resolving thread), not when the harvest loop gets around to
        # the future — with K outstanding the two differ by up to the
        # whole run
        def cb(fut):
            dt = time.monotonic() - t0
            try:
                fut.result()
            except QueryTimeout:
                with tally.lock:
                    tally.timeouts += 1
            except QueryRejected:
                with tally.lock:
                    tally.rejected += 1
            except BaseException:  # noqa: BLE001 — tally, never raise
                with tally.lock:
                    tally.errors += 1
            else:
                with tally.lock:
                    tally.lat_s.append(dt)
            gate.release()

        return cb

    i = 0
    while time.monotonic() < deadline:
        if requests is not None and i >= requests:
            break
        if not gate.acquire(timeout=0.1):
            continue
        with tally.lock:
            tally.sent += 1
        t0 = time.monotonic()
        try:
            fut = service.submit(make_request(i))
        except QueryRejected:
            with tally.lock:
                tally.rejected += 1
            gate.release()
            i += 1
            continue
        i += 1
        inflight.append(fut)
        fut.add_done_callback(on_done(t0))
    # completion barrier only — outcomes were tallied in the callbacks
    # (wait() reports, never raises; a straggler past the bound is
    # abandoned rather than blocking the report)
    futures_wait(inflight, timeout=120)
    wall = time.monotonic() - t_start
    stats = service.stats()
    delta = {k: stats.get(k, 0) - base.get(k, 0)
             for k in ("dispatches", "coalesced")}
    rep = _report("sustained", wall, tally.lat_s, tally.sent,
                  tally.rejected, tally.timeouts, tally.errors, delta)
    rep.pts_per_s = rep.throughput_qps * points_per_query
    p = stats.get("pipeline") or {}
    pbase = base.get("pipeline") or {}
    rep.windows_in_flight_max = int(p.get("max_inflight", 0))
    rep.pipelined_windows = (
        stats.get("pipelined_windows", 0)
        - base.get("pipelined_windows", 0))
    # delta against the pre-run snapshot, like dispatches/coalesced —
    # lifetime totals would credit a warmup pass to the measured run
    rep.fused_counts = int(p.get("fused_counts", 0)
                           - pbase.get("fused_counts", 0))
    ring = p.get("ring") or {}
    ring_base = pbase.get("ring") or {}
    rep.ring_windows = int(ring.get("windows", 0)
                           - ring_base.get("windows", 0))
    rep.ring_fallbacks = (
        sum((ring.get("fallbacks") or {}).values())
        - sum((ring_base.get("fallbacks") or {}).values()))
    # per-window device interactions: the measured run's
    # serve.device.ops delta over its window count (pipelined windows
    # when the pipeline ran, dispatch count on the serial stack) — the
    # ring route's claim is this number strictly below the pipelined
    # baseline's on identical work
    windows = rep.pipelined_windows or rep.dispatches
    if windows > 0:
        rep.dispatches_per_window = round(
            (device_ops_count() - ops_base) / windows, 3)
    mesh = getattr(service, "mesh", None)
    if mesh is not None and mesh_dispatch_count() > mesh_base:
        # topology is reported from the LAUNCH route, not the resolved
        # config: a store the residency tier cannot shard (extended
        # geometry, cold/no device cache) serves single-chip even when
        # ServeConfig.mesh names a mesh, and claiming mesh_devices for
        # it would let bench-serve print a mesh_speedup computed from
        # two identical single-chip runs
        rep.mesh_devices = int(mesh.devices.size)
        rep.per_shard_pts_per_s = rep.pts_per_s / rep.mesh_devices
    return rep


def run_subscribe(
    store,
    type_name: str,
    make_batch: Callable[[int], object],
    subscriptions: int = 8,
    batches: int = 20,
    extent=(-60.0, 60.0),
    density_shape=(64, 32),
    seed: int = 0,
    manager=None,
) -> LoadReport:
    """Standing-query load mode (`gmtpu bench-serve --mode subscribe`):
    register N subscriptions (bbox geofences, dwithin geofences and a
    density window, cycling) over a live Kafka store, produce + poll M
    batches from `make_batch(i)`, and report pushed events/s plus the
    per-batch eval+push latency distribution (p99 is the line the
    ISSUE's standing-query workload is judged on). The evaluator's
    one-dispatch-per-poll invariant is visible in the report:
    `dispatches` ≈ `batches` once the fused kernel is warm."""
    from geomesa_tpu.subscribe import DensityWindow, SubscriptionManager

    mgr = manager if manager is not None else SubscriptionManager(store)
    rng = np.random.default_rng(seed)
    geom = store.get_schema(type_name).default_geometry.name
    lo, hi = extent
    registered = []
    for i in range(subscriptions):
        kind = i % 3
        if kind == 0:
            x0 = float(rng.uniform(lo, hi - 30))
            y0 = float(rng.uniform(lo / 2, hi / 2 - 20))
            registered.append(mgr.subscribe(
                type_name,
                f"BBOX({geom}, {x0}, {y0}, {x0 + 30}, {y0 + 20})"))
        elif kind == 1:
            px = float(rng.uniform(lo / 2, hi / 2))
            py = float(rng.uniform(lo / 4, hi / 4))
            registered.append(mgr.subscribe(
                type_name,
                f"DWITHIN({geom}, POINT({px} {py}), 1500000, meters)"))
        else:
            w, h = density_shape
            registered.append(mgr.subscribe(type_name, density=DensityWindow(
                (lo, lo / 2, hi, hi / 2), w, h)))
    # warm fold OUTSIDE the measured window: THIS manager's fused
    # kernel (the AOT key includes the evaluator nonce + version, so a
    # throwaway warm manager would compile a different entry and leave
    # batch 0 paying the trace+compile), plus the registration-time
    # `state` snapshot frames — the benchmark reports INCREMENTAL push
    # throughput, not baseline transfer or compile time
    store.write(type_name, make_batch(batches))
    mgr.poll_now()
    mgr.flush(lambda _f: None)
    frames: List[dict] = []
    lat_s: List[float] = []
    base = mgr.evaluator.stats()
    t_start = time.monotonic()
    for i in range(batches):
        store.write(type_name, make_batch(i))
        t0 = time.monotonic()
        mgr.poll_now()
        mgr.flush(frames.append)
        lat_s.append(time.monotonic() - t0)
    wall = time.monotonic() - t_start
    ev = mgr.evaluator.stats()
    # incremental events only: geofence transitions count per fid,
    # density folds per frame; lifecycle frames (state/lagged/...)
    # are bookkeeping, not workload output
    events = 0
    for f in frames:
        if f.get("event") in ("enter", "exit"):
            events += len(f.get("fids", ()))
        elif f.get("event") == "density":
            events += 1
    rep = _report("subscribe", wall, lat_s, batches, 0, 0, 0,
                  {"dispatches": ev.get("dispatches", 0)
                   - base.get("dispatches", 0), "coalesced": 0})
    rep.subscriptions = subscriptions
    rep.batches = batches
    rep.events_total = events
    rep.events_per_s = events / wall if wall > 0 else 0.0
    if manager is None:
        mgr.close()
    else:
        # caller-owned manager: cancel what THIS call registered, or
        # repeated runs accumulate 8 stale subs each — every
        # intervening poll pays fused evaluation for them until the
        # table bound rejects run ~32 with subscription_limit
        for s in registered:
            try:
                mgr.unsubscribe(s.sub_id)
            except KeyError:
                pass  # TTL-expired mid-run
    return rep


def run_subscribe_lanes(
    make_store,
    type_name: str,
    make_batch: Callable[[int], object],
    subscriptions: int = 1024,
    batches: int = 4,
    extent=(-60.0, 28.0, -30.0, 9.0),
    seed: int = 5,
    fused: bool = True,
    churn: bool = True,
) -> dict:
    """Lane-vs-fused-slot comparison (`gmtpu bench-serve --mode subscribe
    --lanes`, docs/SERVING.md "Standing queries"): register S same-class
    bbox geofences on a FRESH store per mode, then time the identical
    protocol under `SubscribeConfig(lanes=...)` both ways — first poll
    (where the fused path pays an S-proportional trace+compile and the
    lane path a single S-independent batched kernel), `batches` steady
    polls, and optionally one membership-churn event (register + cancel
    + poll: a full S-wide rebuild for fused slots, a parameter-row write
    for lanes). Events are identical across modes by construction, so
    `speedup` is the lane/fused events-per-second ratio over matching
    windows. Subscriptions register BEFORE the seed batch lands: the
    empty-store bootstrap is then a bookkeeping write, keeping the first
    measured poll about evaluation, not baseline transfer.

    `fused=False` skips the fused leg entirely — its compile cost grows
    super-linearly with S (measured ~1 s at S=64, ~11 s at S=256,
    ~120 s at S=1024 on CPU CI), so sweeps cap the fused mode and run
    lane-only beyond the cap rather than silently extrapolating."""
    from geomesa_tpu.subscribe import SubscribeConfig, SubscriptionManager

    x_lo, x_hi, y_lo, y_hi = extent

    def _mode(lanes: bool) -> dict:
        store = make_store()
        mgr = SubscriptionManager(store, SubscribeConfig(
            max_subscriptions=subscriptions + 8, lanes=lanes))
        geom = store.get_schema(type_name).default_geometry.name
        rng = np.random.default_rng(seed)
        registered = []
        for _ in range(subscriptions):
            x0 = float(rng.uniform(x_lo, x_hi))
            y0 = float(rng.uniform(y_lo, y_hi))
            registered.append(mgr.subscribe(
                type_name,
                f"BBOX({geom}, {x0}, {y0}, {x0 + 2}, {y0 + 2})"))
        store.write(type_name, make_batch(10_001))
        frames: List[dict] = []
        base = mgr.evaluator.stats()
        polls = 0
        t_start = time.monotonic()
        mgr.poll_now()
        mgr.flush(frames.append)
        first_poll_s = time.monotonic() - t_start
        polls += 1
        for i in range(batches):
            store.write(type_name, make_batch(i))
            mgr.poll_now()
            mgr.flush(frames.append)
            polls += 1
        churn_poll_s = None
        if churn:
            x0 = float(rng.uniform(x_lo, x_hi))
            y0 = float(rng.uniform(y_lo, y_hi))
            mgr.subscribe(
                type_name,
                f"BBOX({geom}, {x0}, {y0}, {x0 + 2}, {y0 + 2})")
            mgr.unsubscribe(registered[0].sub_id)
            store.write(type_name, make_batch(batches))
            t0 = time.monotonic()
            mgr.poll_now()
            mgr.flush(frames.append)
            churn_poll_s = time.monotonic() - t0
            polls += 1
        wall = time.monotonic() - t_start
        ev = mgr.evaluator.stats()
        # enter/exit transitions only, as run_subscribe counts them —
        # registration-time `state` frames are bookkeeping, and on the
        # register-before-seed protocol they are empty anyway
        events = 0
        for f in frames:
            if f.get("event") in ("enter", "exit"):
                events += len(f.get("fids", ()))
        dispatches = ev.get("dispatches", 0) - base.get("dispatches", 0)
        out = {
            "mode": "lanes" if lanes else "fused",
            "polls": polls,
            "wall_s": round(wall, 3),
            "events_total": events,
            "events_per_s": round(events / wall, 1) if wall > 0 else 0.0,
            "dispatches": dispatches,
            "dispatches_per_poll":
                round(dispatches / polls, 3) if polls else 0.0,
            "lane_dispatches": ev.get("lane_dispatches", 0)
            - base.get("lane_dispatches", 0),
            "first_poll_s": round(first_poll_s, 3),
        }
        if churn_poll_s is not None:
            out["churn_poll_s"] = round(churn_poll_s, 3)
        mgr.close()
        return out

    lanes_rep = _mode(True)
    out = {
        "run": "subscribe_lanes",
        "subscriptions": subscriptions,
        "batches": batches,
        "lanes": lanes_rep,
        "fused": None,
    }
    if fused:
        fused_rep = _mode(False)
        out["fused"] = fused_rep
        if fused_rep["events_per_s"] > 0:
            out["speedup"] = round(
                lanes_rep["events_per_s"] / fused_rep["events_per_s"], 1)
    else:
        out["note"] = ("fused leg skipped: S-proportional compile cost "
                       "exceeds the bench budget at this S")
    return out


def run_wire(
    store,
    type_name: str,
    rows: int = 100_000,
    iters_json: int = 3,
    iters_columnar: int = 10,
    push_sinks: int = 1000,
    push_frames: int = 50,
    push_fids: int = 64,
) -> LoadReport:
    """`bench-serve --mode wire` (docs/SERVING.md "Columnar wire"):
    encode ONE bulk `execute` result both ways — the JSON-lines path
    (per-row dict + json.dumps, exactly what the legacy wire ships)
    vs the columnar Arrow record-batch frame — over identical rows,
    and report rows/s, bytes and encode p50/p99 for each, plus a
    PushMux fan-out run (`push_frames` enter-frames to `push_sinks`
    in-process subscribers) whose one-encode-per-frame invariant is
    part of the verdict. Decoded columnar rows are parity-checked
    against the JSON rows before anything is timed "ok"."""
    import json as _json

    from geomesa_tpu.plan.query import Query
    from geomesa_tpu.serve import columnar as colwire
    from geomesa_tpu.serve.protocol import _payload

    if not colwire.have_pyarrow():
        # same stance as the wire itself: capability absence is typed,
        # never a mid-bench ModuleNotFoundError traceback
        raise RuntimeError(
            "bench-serve --mode wire needs pyarrow (this host's wire "
            "capability is json-only)")
    source = store.get_feature_source(type_name)
    result = source.planner.execute(
        Query(type_name, "INCLUDE", max_features=rows))
    n = len(result.features) if result.features is not None else 0

    def one_json() -> "tuple[bytes, float]":
        t0 = time.monotonic()
        doc = {"id": "b", "ok": True}
        doc.update(_payload("execute", result, rows))
        buf = (_json.dumps(doc) + "\n").encode()
        return buf, time.monotonic() - t0

    def one_columnar() -> "tuple[bytes, float]":
        t0 = time.monotonic()
        fields, payload = colwire.encode_execute_frame(
            result.features, rows)
        doc = {"id": "b", "ok": True, "kind": "features",
               "count": fields["rows"] if "rows" in fields else n}
        doc["frame"] = fields
        buf = colwire.frame_bytes(doc, payload)
        return buf, time.monotonic() - t0

    # parity BEFORE timing: a fast encoder that decodes wrong is not a
    # result (acceptance: decoded columnar == JSON rows, bit-identical)
    jbuf, _ = one_json()
    cbuf, _ = one_columnar()
    jrows = _json.loads(jbuf.decode())["features"]
    (cdoc, cpayload), = colwire.parse_stream(cbuf)
    crows = colwire.decode_execute_payload(cpayload)
    parity_ok = crows == jrows
    j_ms = []
    for _ in range(max(iters_json, 1)):
        jbuf, dt = one_json()
        j_ms.append(dt * 1000.0)
    c_ms = []
    for _ in range(max(iters_columnar, 1)):
        cbuf, dt = one_columnar()
        c_ms.append(dt * 1000.0)
    j_med = float(np.median(j_ms))
    c_med = float(np.median(c_ms))
    json_rows_s = n / (j_med / 1000.0) if j_med > 0 else 0.0
    col_rows_s = n / (c_med / 1000.0) if c_med > 0 else 0.0

    # push fan-out: one frame encoded once, fanned to every sink
    # (unthreaded in-process sinks — the encode counter is the claim
    # under test; threaded writer isolation is tests/test_wire.py's)
    mux = colwire.PushMux(queue_limit=push_frames + 8)
    sunk = [0]

    def sink_write(buf: bytes) -> None:
        sunk[0] += len(buf)

    sinks = [mux.register(sink_write, mode=colwire.WIRE_JSON,
                          threaded=False) for _ in range(push_sinks)]
    fids = [f"bench-f{i}" for i in range(push_fids)]
    t0 = time.monotonic()
    for i in range(push_frames):
        mux.publish({"event": "enter", "subscription": "bench-sub",
                     "seq": i + 1, "fids": fids}, sinks)
    push_wall = max(time.monotonic() - t0, 1e-9)
    mux_stats = mux.stats()
    mux.close()

    rep = _report("wire", sum(j_ms) / 1000.0 + sum(c_ms) / 1000.0,
                  [v / 1000.0 for v in c_ms],
                  iters_json + iters_columnar, 0, 0, 0, {})
    rep.wire_rows = n
    rep.wire_json_rows_s = json_rows_s
    rep.wire_columnar_rows_s = col_rows_s
    rep.wire_speedup = (col_rows_s / json_rows_s
                        if json_rows_s > 0 else 0.0)
    rep.wire_json_bytes = len(jbuf)
    rep.wire_columnar_bytes = len(cbuf)
    rep.wire_json_p50_ms = float(np.percentile(j_ms, 50))
    rep.wire_json_p99_ms = float(np.percentile(j_ms, 99))
    rep.wire_columnar_p50_ms = float(np.percentile(c_ms, 50))
    rep.wire_columnar_p99_ms = float(np.percentile(c_ms, 99))
    rep.push_sinks = push_sinks
    rep.push_frames = push_frames
    rep.push_encodes = mux_stats["encodes"]
    rep.push_events_per_s = push_frames * push_sinks / push_wall
    rep.wire_parity_ok = parity_ok
    return rep


def run_approx(
    service: QueryService,
    type_name: str,
    cqls: List[str],
    duration_s: float = 5.0,
    clients: int = 8,
    tolerance: float = 0.1,
    requests_per_client: Optional[int] = None,
    exact_counts: Optional[Dict[str, int]] = None,
) -> LoadReport:
    """`bench-serve --mode approx`: a closed-loop workload mixing
    TOLERANT count clients (hints.tolerance — eligible for the sketch
    tier) and EXACT clients (the device-scan path) over a cycling CQL
    list, reporting per-tier p50/p99 and the sketch-vs-exact speedup at
    bound-respecting accuracy. `exact_counts` (cql -> exact answer,
    computed outside the measured window) arms per-answer bound
    verification: every approx answer whose interval does not contain
    the exact answer counts as a bound violation (must be zero)."""
    from geomesa_tpu.plan.hints import QueryHints
    from geomesa_tpu.plan.query import Query

    tally = _Tally()
    base = service.stats()
    deadline = time.monotonic() + duration_s
    approx_lat: List[float] = []
    exact_lat: List[float] = []
    violations = [0]
    lock = threading.Lock()

    def client(cid: int):
        tolerant = cid % 2 == 0
        i = 0
        while True:
            if requests_per_client is not None:
                if i >= requests_per_client:
                    return
            elif time.monotonic() >= deadline:
                return
            cql = cqls[(cid + i) % len(cqls)]
            hints = (QueryHints(tolerance=tolerance) if tolerant
                     else QueryHints())
            req = ServeRequest(kind="count",
                               query=Query(type_name, cql, hints=hints))
            with tally.lock:
                tally.sent += 1
            t0 = time.monotonic()
            try:
                fut = service.submit(req)
            except QueryRejected:
                with tally.lock:
                    tally.rejected += 1
                i += 1
                continue
            try:
                value = fut.result()
                dt = time.monotonic() - t0
                with tally.lock:
                    tally.lat_s.append(dt)
                served_approx = getattr(value, "approx", False)
                # classify by the TIER that answered, not the client's
                # intent: a tolerant request whose bound did not fit
                # paid the exact path and belongs in the exact leg —
                # the speedup headline is sketch-tier vs device-scan
                with lock:
                    (approx_lat if served_approx
                     else exact_lat).append(dt)
                if served_approx and exact_counts is not None:
                    exact = exact_counts.get(cql)
                    if exact is not None and \
                            abs(int(value) - exact) > value.bound:
                        with lock:
                            violations[0] += 1
            except QueryTimeout:
                with tally.lock:
                    tally.timeouts += 1
            except QueryRejected:
                with tally.lock:
                    tally.rejected += 1
            except Exception:
                with tally.lock:
                    tally.errors += 1
            i += 1

    t_start = time.monotonic()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    stats = service.stats()
    delta = {k: stats.get(k, 0) - base.get(k, 0)
             for k in ("dispatches", "coalesced")}
    rep = _report("approx", wall, tally.lat_s, tally.sent,
                  tally.rejected, tally.timeouts, tally.errors, delta)

    def q(arr, p):
        return (float(np.percentile(np.asarray(arr) * 1000.0, p))
                if arr else 0.0)

    rep.approx_ok = len(approx_lat)
    rep.exact_ok = len(exact_lat)
    rep.approx_p50_ms = q(approx_lat, 50)
    rep.approx_p99_ms = q(approx_lat, 99)
    rep.exact_p50_ms = q(exact_lat, 50)
    rep.exact_p99_ms = q(exact_lat, 99)
    if rep.approx_p50_ms > 0:
        rep.approx_speedup_p50 = rep.exact_p50_ms / rep.approx_p50_ms
    tiers = (stats.get("approx") or {}).get("tiers", {})
    base_tiers = (base.get("approx") or {}).get("tiers", {})
    rep.tier_sketch = tiers.get("sketch", 0) - base_tiers.get("sketch", 0)
    rep.tier_cached = tiers.get("cached", 0) - base_tiers.get("cached", 0)
    rep.tier_exact = tiers.get("exact", 0) - base_tiers.get("exact", 0)
    rep.bound_violations = violations[0]
    return rep


def run_fleet_bench(
    catalog: str,
    type_name: str,
    n_replicas: int = 2,
    duration_s: float = 5.0,
    clients: int = 8,
    k: int = 8,
    kill: bool = True,
    kill_window_s: float = 2.0,
    seed: int = 0,
    store_factory=None,
    subscribe_rider: bool = False,
) -> dict:
    """`gmtpu bench-serve --fleet N`: closed-loop clients over the
    ROUTER's wire (real sockets, real failover), one replica killed
    abruptly at half-time. The report separates overall latency from
    the p99 DURING the kill window — the number the fleet exists for —
    and asserts the accounting the chaos certification relies on:
    every request answered (zero dropped), zero un-typed errors.
    Thread-spawn replicas: same code path as deployment minus process
    spin-up, so the comparison measures routing + failover, not jax
    import time.

    `subscribe_rider` adds one standing query THROUGH the router for
    the bench's lifetime (needs a live-layer `store_factory` — the
    replicas must share a pollable store): the report gains a `rider`
    block with the frames seen, resyncs paid, and whether the stream
    survived the kill via the router's re-home — continuity measured
    under the same query storm the latency numbers come from."""
    import time as _time

    from geomesa_tpu.fleet import FleetConfig, FleetSupervisor
    from geomesa_tpu.fleet.wire import connect_json

    sup = FleetSupervisor(FleetConfig(
        n_replicas=n_replicas, catalog=catalog,
        store_factory=store_factory,
        probe_interval_s=0.25))
    lock = threading.Lock()
    lat: List[tuple] = []      # (t_done, latency_s, ok)
    counts = {"sent": 0, "ok": 0, "unavailable": 0, "rejected": 0,
              "timeout": 0, "untyped": 0, "answered": 0}
    kill_at = [None]
    try:
        port = sup.start()
        # warm EVERY replica before the measured window: kernel jits
        # are process-wide (thread spawn) but filter-compile and
        # residency caches are per-replica — an unwarmed replica would
        # charge its cold compiles to the measured p99 (and leave the
        # kill window empty of completions on slow CI hosts)
        for h in sup.membership.all():
            wconn = connect_json(h.host, h.port)
            try:
                for wid, wdoc in (
                    ("w1", {"op": "knn", "typeName": type_name,
                            "cql": "BBOX(geom, -180, -90, 180, 90)",
                            "x": [1.5], "y": [2.5], "k": k}),
                    ("w2", {"op": "count", "typeName": type_name,
                            "cql": "BBOX(geom, -180, -90, 180, 90)"}),
                ):
                    wconn.request({"id": wid, **wdoc}, timeout_s=300.0)
            finally:
                wconn.close()
        stop = threading.Event()

        rider_frames: List[dict] = []
        rider_sub = [None]
        rider_cli = None
        if subscribe_rider:
            from geomesa_tpu.fleet.router import FleetClient

            rider_cli = FleetClient("127.0.0.1", port, timeout_s=30.0)
            got = rider_cli.request(
                {"op": "subscribe", "typeName": type_name,
                 "cql": "BBOX(geom, -60, -30, 60, 30)"},
                on_push=rider_frames.append)
            if got.get("ok"):
                rider_sub[0] = got["subscription"]

        def rider_loop():
            # the standing query rides the storm: periodic polls keep
            # the owner folding while the kill + re-home happen
            while not stop.wait(0.2):
                try:
                    rider_cli.request({"op": "poll"},
                                      on_push=rider_frames.append)
                except (OSError, TimeoutError):
                    return

        rider_thread = None
        if rider_sub[0] is not None:
            rider_thread = threading.Thread(target=rider_loop,
                                            daemon=True)
            rider_thread.start()

        def client(cid: int):
            rng = np.random.default_rng(seed * 9973 + cid)
            conn = connect_json("127.0.0.1", port)
            i = 0
            try:
                while not stop.is_set():
                    qx = float(rng.uniform(-60, 60))
                    qy = float(rng.uniform(-60, 60))
                    doc = {"id": f"c{cid}-{i}", "op": "knn",
                           "typeName": type_name,
                           "cql": "BBOX(geom, -180, -90, 180, 90)",
                           "x": [qx], "y": [qy], "k": k,
                           "timeoutMs": 30_000}
                    with lock:
                        counts["sent"] += 1
                    t0 = _time.monotonic()
                    try:
                        got = conn.request(doc, timeout_s=60.0)
                    except (OSError, TimeoutError):
                        with lock:
                            counts["untyped"] += 1
                        return
                    dt = _time.monotonic() - t0
                    with lock:
                        counts["answered"] += 1
                        if got.get("ok"):
                            counts["ok"] += 1
                            lat.append((_time.monotonic(), dt, True))
                        elif got.get("error") in ("unavailable",
                                                  "rejected", "timeout"):
                            counts[got["error"]] += 1
                            lat.append((_time.monotonic(), dt, False))
                        else:
                            counts["untyped"] += 1
                    i += 1
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(c,),
                                    daemon=True)
                   for c in range(clients)]
        t_start = _time.monotonic()
        for t in threads:
            t.start()
        if kill and n_replicas > 1:
            _time.sleep(duration_s / 2.0)
            victim = next(h.replica_id for h in sup.membership.all()
                          if h.state in ("ready", "degraded"))
            kill_at[0] = _time.monotonic()
            sup.kill_replica(victim, graceful=False)
        deadline = t_start + duration_s
        while _time.monotonic() < deadline:
            _time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=90.0)
        if rider_thread is not None:
            rider_thread.join(timeout=30.0)
        if rider_cli is not None:
            try:
                rider_cli.close()
            except OSError:
                pass
        wall = _time.monotonic() - t_start
        router = sup.stats()["router"]
    finally:
        sup.close()

    ok_lat = np.asarray([d for _, d, ok in lat if ok], np.float64) * 1e3

    def q(arr, p):
        return round(float(np.percentile(arr, p)), 3) if len(arr) else 0.0

    doc = {
        "mode": "fleet",
        "replicas": n_replicas,
        "duration_s": round(wall, 3),
        "killed": kill and n_replicas > 1,
        **counts,
        "dropped": counts["sent"] - counts["answered"]
        - counts["untyped"],
        "throughput_qps": round(counts["ok"] / wall, 2) if wall else 0.0,
        "p50_ms": q(ok_lat, 50), "p99_ms": q(ok_lat, 99),
        "retried": router["retried"],
        "shed": router["shed"],
    }
    if kill_at[0] is not None:
        in_window = np.asarray(
            [d for t, d, ok in lat if ok
             and kill_at[0] <= t <= kill_at[0] + kill_window_s],
            np.float64) * 1e3
        doc["p99_during_kill_ms"] = q(in_window, 99)
        doc["served_during_kill"] = int(len(in_window))
    if subscribe_rider:
        evs = [f for f in rider_frames
               if f.get("subscription") == rider_sub[0]]
        seqs = [f.get("seq") for f in evs]
        doc["rider"] = {
            "subscribed": rider_sub[0] is not None,
            "frames": len(evs),
            # resyncs past the initial state frame = failovers paid
            "resyncs": sum(1 for f in evs[1:]
                           if f.get("event") == "state"),
            "seq_monotonic": seqs == sorted(seqs)
            and len(set(seqs)) == len(seqs),
            "rehomed": router.get("rehome_succeeded", 0),
        }
    return doc


# -- request factories -----------------------------------------------------


def knn_request_factory(type_name: str, cql: str, extent=(-60.0, 60.0),
                        k: int = 8, seed: int = 0,
                        **kw) -> Callable[[int], ServeRequest]:
    """Random single-point kNN requests sharing one (filter, k) — the
    maximally-coalescible serving workload. Points derive from the
    request index, so two runs offer identical work."""
    lo, hi = extent

    def make(i: int) -> ServeRequest:
        rng = np.random.default_rng(seed * 7_919 + i)
        from geomesa_tpu.plan.query import Query

        req = ServeRequest(kind="knn", query=Query(type_name, cql), **kw)
        req.qx = rng.uniform(lo, hi, 1)
        req.qy = rng.uniform(lo, hi, 1)
        req.k = k
        return req

    return make


def count_request_factory(type_name: str, cqls: List[str],
                          **kw) -> Callable[[int], ServeRequest]:
    """Counts cycling through a fixed CQL set: coalescing dedups the
    repeats, distinct filters dispatch apart."""
    from geomesa_tpu.plan.query import Query

    def make(i: int) -> ServeRequest:
        return ServeRequest(
            kind="count", query=Query(type_name, cqls[i % len(cqls)]), **kw)

    return make
