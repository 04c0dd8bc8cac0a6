"""CLI subcommands.

Parity: geomesa-tools commands [upstream, unverified]: create-schema,
describe-schema, get-type-names, remove-schema, ingest, export, explain,
stats-analyze/bounds/count/histogram/top-k, delete-features (via
remove-schema), env. All commands take --catalog (the catalog directory,
standing in for the reference's store connection params).
"""

from __future__ import annotations

import argparse
import json
import sys


def register(sub: "argparse._SubParsersAction") -> None:
    def cmd(name, help_, fn, args):
        p = sub.add_parser(name, help=help_)
        for flags, kw in args:
            p.add_argument(*flags, **kw)
        p.set_defaults(func=fn)
        return p

    cat = (["--catalog", "-c"], {"required": True, "help": "catalog directory"})
    feat = (["--feature-name", "-f"], {"required": True, "help": "feature type name"})
    cql = (["--cql", "-q"], {"default": "INCLUDE", "help": "ECQL filter"})

    cmd("version", "print version", _version, [])
    cmd(
        "create-schema", "create a feature type",
        _create_schema,
        [cat, feat,
         (["--spec", "-s"], {"required": True, "help": "SFT spec string"}),
         (["--partition-scheme"], {"default": None,
          "help": "JSON scheme config (default: daily datetime)"}),
         (["--encoding"], {"default": "parquet",
          "choices": ["parquet", "orc"], "help": "file encoding"})],
    )
    cmd("get-type-names", "list feature types", _get_type_names, [cat])
    cmd("describe-schema", "show a feature type", _describe_schema, [cat, feat])
    cmd("remove-schema", "drop a feature type and its data", _remove_schema, [cat, feat])
    # destructive: the filter is REQUIRED (the shared --cql default of
    # INCLUDE would make a forgotten -q silently delete everything —
    # round-4 review); delete-all must be spelled out as -q INCLUDE
    cmd("delete-features", "delete features matching a CQL filter "
        "(explicit -q INCLUDE deletes all)",
        _delete_features,
        [cat, feat,
         (["--cql", "-q"], {"required": True, "help": "ECQL filter "
                            "(INCLUDE = delete every feature)"})])
    cmd("age-off", "delete features older than an ISO instant",
        _age_off,
        [cat, feat,
         (["--older-than"], {"required": True,
                             "help": "ISO-8601 instant (e.g. "
                                     "2020-06-01T00:00:00Z)"})])
    cmd(
        "ingest", "ingest files through a converter (or Arrow IPC "
                  "record-batch files columnar, no converter needed)",
        _ingest,
        [cat, feat,
         (["--converter", "-C"], {"required": False, "default": None,
          "help": "converter config JSON file, or a well-known name "
                  "(gdelt|ais|nyctaxi); optional for .arrow/.ipc "
                  "inputs, which ingest columnar via write_batch"}),
         (["--arrow"], {"action": "store_true",
          "help": "treat every input as an Arrow IPC stream file "
                  "(the columnar bulk-ingest path — record-batch "
                  "buffers flow in as NumPy views, no per-feature "
                  "dicts; docs/SERVING.md \"Columnar wire\")"}),
         (["--workers"], {"type": int, "default": 1,
          "help": "parallel converter threads (distributed-ingest analog)"}),
         (["--no-resume"], {"action": "store_true",
          "help": "ignore the per-file ingest checkpoint"}),
         (["files"], {"nargs": "+", "help": "input files"})],
    )
    cmd(
        "export", "export features",
        _export,
        [cat, feat, cql,
         (["--output", "-o"], {"default": "-", "help": "output path (- = stdout)"}),
         (["--format", "-F"], {"default": "csv",
          "choices": ["csv", "tsv", "json", "gml", "arrow", "bin", "wkt",
                      "shp", "parquet", "orc", "leaflet"]}),
         (["--attributes", "-a"], {"default": None, "help": "comma-sep projection"}),
         (["--max-features", "-m"], {"type": int, "default": None}),
         (["--bin-track"], {"default": None, "help": "track attr for bin format"}),
         (["--crs"], {"default": None,
          "help": "output CRS: an EPSG code (4326, 3857, UTM 326xx/327xx) "
                  "or 'utm' to pick the zone of the query bbox center"})],
    )
    cmd("explain", "print the query plan", _explain, [cat, feat, cql])
    cmd("stats-analyze", "compute and persist stats", _stats_analyze, [cat, feat])
    cmd("stats-bounds", "attribute bounds", _stats_bounds,
        [cat, feat, cql, (["--attributes", "-a"], {"default": None})])
    cmd("stats-count", "feature count", _stats_count,
        [cat, feat, cql, (["--no-exact"], {"action": "store_true"})])
    cmd(
        "stats-histogram", "attribute histogram", _stats_histogram,
        [cat, feat, cql,
         (["--attribute", "-a"], {"required": True}),
         (["--bins"], {"type": int, "default": 10})],
    )
    cmd(
        "stats-top-k", "most frequent values", _stats_topk,
        [cat, feat, cql,
         (["--attribute", "-a"], {"required": True}),
         (["--k"], {"type": int, "default": 10})],
    )
    cmd("manage-partitions", "list partitions and their files",
        _manage_partitions, [cat, feat])
    cmd("compact", "merge each partition's files into one", _compact,
        [cat, feat,
         (["--partition"], {"default": None, "help": "limit to one partition"})])
    cmd("env", "show system properties", _env, [])
    cmd(
        "sql", "run a SQL SELECT against the catalog",
        _sql,
        [cat,
         (["--query", "-q"], {"required": True, "help": "SQL SELECT text"}),
         (["--format", "-F"], {"default": "csv",
          "choices": ["csv", "json"], "help": "output format"})],
    )

    # serve subsystem (docs/SERVING.md): concurrent query serving with
    # admission control + request coalescing
    serve_p = sub.add_parser(
        "serve", help="concurrent query serving: JSON-lines requests on "
                      "stdin (or --input), responses on stdout")
    serve_p.add_argument("--catalog", "-c", default=None,
                         help="catalog directory (required unless "
                              "--self-check)")
    serve_p.add_argument("--input", default="-",
                         help="JSON-lines request file (- = stdin)")
    serve_p.add_argument("--self-check", action="store_true",
                         help="run the end-to-end serving smoke against "
                              "a throwaway store and exit")
    serve_p.add_argument("--max-queue", type=int, default=128,
                         help="admission queue bound (backpressure)")
    serve_p.add_argument("--max-batch", type=int, default=64,
                         help="coalescing cap per device dispatch")
    serve_p.add_argument("--max-wait-ms", type=float, default=None,
                         help="fixed coalescing window (added-latency "
                              "ceiling); default: end the gather when "
                              "nothing compatible is queued and the "
                              "route can start the window")
    serve_p.add_argument("--timeout-ms", type=int, default=None,
                         help="default per-request deadline")
    serve_p.add_argument("--tenant-rate", type=float, default=None,
                         help="per-tenant rate limit in qps")
    serve_p.add_argument("--degrade", action="store_true",
                         help="enable the overload degradation ladder")
    serve_p.add_argument("--no-device-cache", action="store_true",
                         help="serve from the scan path instead of "
                              "HBM-resident partitions")
    serve_p.add_argument("--mesh", default="auto", metavar="auto|N|off",
                         help="sharded serving (docs/SERVING.md): "
                              "route live traffic through the "
                              "multi-chip engine. auto (default) = "
                              "single-chip on 1 device, sharded over "
                              "all devices when >1; N = first N "
                              "devices; off = single-chip")
    serve_p.add_argument("--metrics", action="store_true",
                         help="print Prometheus metrics to stderr on exit")
    serve_p.add_argument("--metrics-port", type=int, default=None,
                         metavar="PORT",
                         help="serve live /metrics + /healthz + "
                              "/debug/traces|stats|gap on this port "
                              "(0 = OS-assigned; docs/OBSERVABILITY.md)")
    serve_p.add_argument("--metrics-interval", type=float, default=None,
                         metavar="SECONDS",
                         help="print a Prometheus snapshot to stderr "
                              "every N seconds (long-running serves "
                              "without --metrics-port aren't blind)")
    serve_p.add_argument("--trace", action="store_true",
                         help="enable per-query span tracing into the "
                              "flight recorder (read via "
                              "/debug/traces or gmtpu trace)")
    serve_p.add_argument("--slo", default=None, metavar="SPEC",
                         help="SLO spec (.toml or .json, docs/"
                              "OBSERVABILITY.md): evaluate declared "
                              "objectives over sliding windows, export "
                              "slo.* burn gauges + /debug/slo, and "
                              "feed the --degrade ladder on budget "
                              "exhaustion")
    serve_p.add_argument("--profile", action="store_true",
                         help="continuous profiler: fold every traced "
                              "query into lifetime per-phase/kernel/"
                              "shard distributions (/debug/prof, "
                              "gmtpu prof; implies --trace)")
    serve_p.add_argument("--flight-dump", default=None, metavar="PATH",
                         help="flight-recorder crash-dump path (default: "
                              "$GEOMESA_TPU_FLIGHT_DUMP or a pid file "
                              "in the temp dir)")
    serve_p.add_argument("--warmup", default=None, metavar="MANIFEST",
                         help="warmup manifest to replay before accepting "
                              "traffic (docs/SERVING.md cold start)")
    serve_p.add_argument("--track-compiles", action="store_true",
                         help="count engine recompiles and attribute "
                              "inline compile stalls in ServeEvents")
    serve_p.add_argument("--live-poll-ms", type=float, default=None,
                         metavar="MS",
                         help="standing queries: auto-poll the live "
                              "store every MS milliseconds while "
                              "subscriptions are active (push frames "
                              "arrive without explicit poll verbs; "
                              "docs/SERVING.md \"Standing queries\")")
    serve_p.add_argument("--max-subscriptions", type=int, default=256,
                         help="standing-query table bound")
    serve_p.set_defaults(func=_serve)

    warm_p = sub.add_parser(
        "warmup", help="replay a warmup manifest: pre-compile every "
                       "recorded kernel/query shape (and persist the "
                       "executables) before serving")
    warm_p.add_argument("--manifest", "-m", required=True,
                        help="warmup manifest JSON "
                             "(recorded via QueryService.record_warmup)")
    warm_p.add_argument("--catalog", "-c", default=None,
                        help="catalog directory for query entries "
                             "(kernel entries replay without one)")
    warm_p.add_argument("--check", action="store_true",
                        help="after replaying, prove a second pass "
                             "compiles NOTHING; exit nonzero if serving "
                             "would still compile anything")
    warm_p.add_argument("--mesh", default="auto", metavar="auto|N|off",
                        help="replay query entries through the sharded "
                             "serving route (docs/SERVING.md): the mesh "
                             "the serving process will use, so the "
                             "mesh-keyed AOT executables (kernel, "
                             "bucket, dtype, mesh_shape) are the ones "
                             "warmed. auto (default) matches `gmtpu "
                             "serve`")
    warm_p.set_defaults(func=_warmup)

    prof_p = sub.add_parser(
        "prof", help="continuous serve profile: lifetime per-phase/"
                     "per-kernel/per-shard distributions from a "
                     "--metrics-port endpoint (/debug/prof) or a "
                     "saved profile JSON")
    prof_p.add_argument("--url", default=None,
                        help="endpoint base URL (default: "
                             "http://HOST:PORT from --host/--port)")
    prof_p.add_argument("--host", default="127.0.0.1")
    prof_p.add_argument("--port", type=int, default=9090)
    prof_p.add_argument("--input", "-i", default=None, metavar="JSON",
                        help="read a saved /debug/prof document "
                             "instead of polling a live endpoint")
    prof_p.add_argument("--json", action="store_true",
                        help="machine output instead of text")
    prof_p.set_defaults(func=_prof)

    # telemetry surface (docs/OBSERVABILITY.md)
    top_p = sub.add_parser(
        "top", help="live serving dashboard: poll a --metrics-port "
                    "endpoint and render qps/p99/queue/breakers to the "
                    "terminal (no curses; plain text refresh)")
    top_p.add_argument("--url", default=None,
                       help="endpoint base URL (default: "
                            "http://HOST:PORT from --host/--port)")
    top_p.add_argument("--host", default="127.0.0.1")
    top_p.add_argument("--port", type=int, default=9090)
    top_p.add_argument("--interval", type=float, default=2.0,
                       help="poll interval seconds")
    top_p.add_argument("--count", type=int, default=None,
                       help="number of polls, then exit (default: "
                            "until interrupted)")
    top_p.add_argument("--no-clear", action="store_true",
                       help="append frames instead of clearing the "
                            "screen (logs, pipes)")
    top_p.set_defaults(func=_top)

    trace_p = sub.add_parser(
        "trace", help="inspect a trace dump (flight-recorder JSON or "
                      "Perfetto trace_event JSON): per-trace summary, "
                      "or the dispatch-gap report with --gap")
    trace_p.add_argument("--input", "-i", required=True,
                         help="trace file: a flight-recorder dump "
                              "(gmtpu serve --flight-dump, /debug/* "
                              "saved to disk) or Perfetto JSON")
    trace_p.add_argument("--gap", action="store_true",
                         help="print the dispatch-gap report (host-gap "
                              "vs kernel-time attribution)")
    trace_p.add_argument("--json", action="store_true",
                         help="machine output instead of text")
    trace_p.add_argument("--perfetto", default=None, metavar="OUT.json",
                         help="also convert to Perfetto trace_event "
                              "JSON at this path")
    trace_p.set_defaults(func=_trace)

    # fault injection + recovery fabric (docs/ROBUSTNESS.md)
    chaos_p = sub.add_parser(
        "chaos", help="run a serve workload under a fault plan and "
                      "check the recovery invariants (no torn "
                      "manifests, typed errors only, breaker cycles "
                      "visible, deterministic replay)")
    chaos_p.add_argument("--plan", "-p", default=None,
                         help="fault plan JSON (see docs/ROBUSTNESS.md; "
                              "required unless --list-sites)")
    chaos_p.add_argument("--requests", type=int, default=32,
                         help="workload length (mixed count/knn/"
                              "features/Kafka, writers interleaved)")
    chaos_p.add_argument("--seed", type=int, default=None,
                         help="override the plan's RNG seed")
    chaos_p.add_argument("--check", action="store_true",
                         help="exit nonzero unless every invariant "
                              "holds (the acceptance gate)")
    chaos_p.add_argument("--no-replay", action="store_true",
                         help="skip the determinism replay "
                              "(second seeded run + fire-log diff)")
    chaos_p.add_argument("--list-sites", action="store_true",
                         help="print the registered fault-site catalog "
                              "and exit")
    chaos_p.add_argument("--fleet", action="store_true",
                         help="replica-kill certification "
                              "(docs/ROBUSTNESS.md \"Replica "
                              "fleets\"): a 2-replica fleet serves "
                              "through an abrupt replica kill — zero "
                              "un-typed client errors, zero dropped "
                              "or double-delivered requests, every "
                              "deterministic rule fired, replay-exact "
                              "fire log, and a fresh replica refuses "
                              "traffic until warmup --check is green. "
                              "--plan overrides the built-in plan")
    chaos_p.set_defaults(func=_chaos)

    # replica fleet (docs/SERVING.md "Replica fleets"): N QueryService
    # replicas behind a fault-tolerant router
    fleet_p = sub.add_parser(
        "fleet", help="replica fleet: spawn N serve replicas behind a "
                      "fault-tolerant router (shard-affinity + "
                      "least-loaded + SLO-burn-aware routing, "
                      "drain-then-redistribute failover)")
    fleet_p.add_argument("action", nargs="?", default="serve",
                         choices=["serve", "status", "restart"],
                         help="serve = run a fleet; status = print a "
                              "running fleet's membership; restart = "
                              "rolling restart (drain one replica at "
                              "a time, gated on the survivors' SLO "
                              "budget)")
    fleet_p.add_argument("--catalog", "-c", default=None,
                         help="catalog directory (serve)")
    fleet_p.add_argument("--replicas", "-n", type=int, default=2,
                         help="replica count (serve)")
    fleet_p.add_argument("--host", default="127.0.0.1")
    fleet_p.add_argument("--port", type=int, default=0,
                         help="router port (serve: 0 = ephemeral, "
                              "printed on startup; status/restart: "
                              "the running router's port)")
    fleet_p.add_argument("--spawn", default="process",
                         choices=["thread", "process"],
                         help="replica isolation: separate OS "
                              "processes (default; a crash takes one "
                              "replica) or in-process threads (CI/"
                              "smoke)")
    fleet_p.add_argument("--warmup", default=None, metavar="MANIFEST",
                         help="warmup manifest every replica must "
                              "replay GREEN (gmtpu warmup --check "
                              "semantics) before taking traffic")
    fleet_p.add_argument("--metrics-port", type=int, default=None,
                         help="per-replica metrics port; use 0 — "
                              "ephemeral, reported per replica — N "
                              "replicas on one host cannot share a "
                              "fixed port")
    fleet_p.add_argument("--force-cpu", action="store_true",
                         help="pin replica workers to CPU (CI)")
    fleet_p.set_defaults(func=_fleet)

    # analysis subsystem (docs/ANALYSIS.md): gmtpu-lint + runtime guards
    from geomesa_tpu.analysis.linter import add_lint_arguments

    lint_p = sub.add_parser(
        "lint", help="JAX-aware static analysis (rules GT01..GT06 + "
                     "concurrency GT07..GT12)")
    add_lint_arguments(lint_p)
    lint_p.set_defaults(func=_lint)
    guard_p = sub.add_parser(
        "guard", help="run a script under runtime guards "
                      "(recompile counters, transfer guard)")
    guard_p.add_argument("script", help="python script to run")
    guard_p.add_argument("script_args", nargs=argparse.REMAINDER,
                         help="arguments passed to the script")
    guard_p.add_argument("--transfer", default="allow",
                         choices=["allow", "log", "disallow"],
                         help="jax.transfer_guard mode while the script "
                              "runs (default: allow)")
    guard_p.add_argument("--recompile-warn", type=int, default=None,
                         help="warn on stderr when one jitted callable "
                              "recompiles more than N times")
    guard_p.add_argument("--races", action="store_true",
                         help="lockset race harness: track every lock "
                              "the script creates; exit nonzero on "
                              "lock-order inversions or empty-lockset "
                              "accesses (docs/ANALYSIS.md)")
    guard_p.set_defaults(func=_guard)


def _serve(args) -> int:
    from geomesa_tpu.serve.service import ServeConfig, self_check

    if args.self_check:
        return self_check()
    if not args.catalog:
        print("error: serve needs --catalog (or --self-check)",
              file=sys.stderr)
        return 2
    from geomesa_tpu.plan import DataStore
    from geomesa_tpu.serve.protocol import serve_lines

    store = DataStore(args.catalog,
                      use_device_cache=not args.no_device_cache)
    profile = getattr(args, "profile", False)
    config = ServeConfig(
        max_queue=args.max_queue,
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        default_timeout_ms=args.timeout_ms,
        tenant_rate=args.tenant_rate,
        degrade=args.degrade,
        warmup_manifest=getattr(args, "warmup", None),
        track_compiles=getattr(args, "track_compiles", False),
        # the profiler folds recorded traces: --profile without
        # --trace would fold nothing, so it implies tracing
        trace=getattr(args, "trace", False) or profile,
        flight_dump=getattr(args, "flight_dump", None),
        subscribe_poll_ms=getattr(args, "live_poll_ms", None),
        subscribe_max=getattr(args, "max_subscriptions", 256),
        mesh=getattr(args, "mesh", "auto"),
        slo=getattr(args, "slo", None),
        profile=profile,
    )
    def write_line(s: str) -> None:
        # flush per response: with stdout piped (the normal programmatic
        # client), block buffering would deadlock a request/response
        # client against the server's blocking read of the next line
        sys.stdout.write(s)
        sys.stdout.flush()

    import threading

    from geomesa_tpu.serve.service import QueryService

    svc = QueryService(store, config)
    server = None
    stop_snap = threading.Event()
    snap_thread = None
    if getattr(args, "metrics_port", None) is not None:
        from geomesa_tpu.telemetry.export import MetricsServer

        server = MetricsServer(
            port=args.metrics_port,
            stats_fn=svc.stats,
            pre_scrape=svc.export_gauges,
            slo_fn=(svc.slo.report if svc.slo is not None else None))
        port = server.start()
        # the BOUND port, not the requested one: --metrics-port 0 asks
        # the OS for an ephemeral port (fleet replicas sharing a host
        # must), and stats()/this line are where it is reported
        svc.metrics_port = port
        print(f"metrics: {server.url}/metrics (also /healthz, "
              f"/debug/traces, /debug/stats, /debug/gap, /debug/slo, "
              f"/debug/prof) — gmtpu top --port {port}",
              file=sys.stderr)
    if getattr(args, "metrics_interval", None):
        from geomesa_tpu.utils.metrics import metrics

        def snapshot_loop():
            # periodic stderr visibility for long-running serves
            # without a scrape endpoint; stops with the serve loop
            while not stop_snap.wait(args.metrics_interval):
                svc.export_gauges()
                print(f"--- metrics snapshot ---\n"
                      f"{metrics.to_prometheus()}", file=sys.stderr)

        snap_thread = threading.Thread(
            target=snapshot_loop, name="gmtpu-metrics-snapshot",
            daemon=True)
        snap_thread.start()
    try:
        if args.input == "-":
            n = serve_lines(store, sys.stdin, write_line, config,
                            service=svc)
        else:
            with open(args.input) as f:
                n = serve_lines(store, f, write_line, config, service=svc)
    finally:
        stop_snap.set()
        if snap_thread is not None:
            snap_thread.join(timeout=5.0)
        if server is not None:
            server.stop()
    print(f"served {n} request(s)", file=sys.stderr)
    if args.metrics:
        from geomesa_tpu.utils.metrics import metrics

        print(metrics.to_prometheus(), file=sys.stderr)
    return 0


def _top(args) -> int:
    """Curses-free polling dashboard over a `--metrics-port` endpoint:
    qps (from completed-request deltas between polls), latency
    quantiles, queue depth, degrade level, breaker states, compile
    stalls, quarantine — the docs/OBSERVABILITY.md terminal view."""
    import time as _time
    import urllib.error
    import urllib.request

    base = args.url or f"http://{args.host}:{args.port}"
    base = base.rstrip("/")
    prev = None
    prev_at = None
    polls = 0
    while True:
        try:
            with urllib.request.urlopen(f"{base}/debug/stats",
                                        timeout=5) as r:
                doc = json.loads(r.read().decode())
        except KeyboardInterrupt:
            # ^C lands in the blocking poll as often as in the sleep —
            # both are a clean exit, not a traceback
            return 0
        except (urllib.error.URLError, OSError, ValueError) as e:
            print(f"gmtpu top: cannot poll {base}/debug/stats: {e}",
                  file=sys.stderr)
            return 1
        now = _time.monotonic()
        frame = _top_frame(doc, prev, now - prev_at if prev_at else None)
        if not args.no_clear:
            sys.stdout.write("\x1b[2J\x1b[H")
        sys.stdout.write(frame + "\n")
        sys.stdout.flush()
        prev, prev_at = doc, now
        polls += 1
        if args.count is not None and polls >= args.count:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def _top_frame(doc: dict, prev, dt) -> str:
    m = doc.get("metrics", {})
    hists = m.get("histograms", {})
    counters = m.get("counters", {})
    gauges = m.get("gauges", {})
    lat = hists.get("serve.latency", {})
    done = lat.get("count", 0)
    qps = None
    if prev is not None and dt:
        prev_done = prev.get("metrics", {}).get(
            "histograms", {}).get("serve.latency", {}).get("count", 0)
        qps = max(done - prev_done, 0) / dt
    serve = doc.get("serve", {})
    rec = doc.get("recorder", {})
    lines = [
        "gmtpu top — serve telemetry",
        f"  qps        {qps:10.1f}" if qps is not None
        else "  qps        (first poll)",
        f"  served     {done:10d}   p50 {lat.get('p50_s', 0) * 1e3:8.2f} ms"
        f"   p95 {lat.get('p95_s', 0) * 1e3:8.2f} ms"
        f"   p99 {lat.get('p99_s', 0) * 1e3:8.2f} ms",
        f"  queue      {gauges.get('serve.queue.depth', 0):10.0f}"
        f"   inflight {gauges.get('serve.inflight', 0):.0f}"
        f"   degrade L{serve.get('degrade_level', 0)}",
        f"  dispatches {serve.get('dispatches', 0):10d}"
        f"   coalesced {serve.get('coalesced', 0)}"
        f"   rejected {serve.get('rejected', 0)}"
        f"   failed {serve.get('failed', 0)}",
        f"  compile    stalls {int(counters.get('compile.stalls', 0)):5d}"
        f"   stalled dispatches "
        f"{serve.get('compile_stalled_dispatches', 0)}",
    ]
    prev_serve = (prev or {}).get("serve", {})
    d_loop = serve.get("loop_ns", 0) - prev_serve.get("loop_ns", 0)
    if prev is not None and d_loop > 0:
        d_wait = (serve.get("loop_wait_ns", 0)
                  - prev_serve.get("loop_wait_ns", 0))
        lines.append(f"  dispatcher busy {100.0 * (1 - d_wait / d_loop):5.1f}%"
                     " (1 - queue wait / loop time, since last poll)")
    breakers = doc.get("breakers", {})
    open_b = {k: v for k, v in sorted(breakers.items()) if v != "closed"}
    lines.append(
        "  breakers   " + (", ".join(f"{k}={v}" for k, v in open_b.items())
                           if open_b else
                           f"all closed ({len(breakers)} deps)"))
    quar = serve.get("quarantine", {})
    lines.append(
        f"  quarantine {quar.get('quarantined', 0)} blocked, "
        f"{quar.get('striking', 0)} striking"
        f"   flightrec {rec.get('traces_held', 0)} trace(s), "
        f"{rec.get('events_held', 0)} event(s)")
    mesh = serve.get("mesh")
    if mesh:
        md = int(counters.get("knn.mesh.dispatches", 0))
        ml = int(counters.get("knn.mesh.local_dispatches", 0))
        lanes = _lane_counts(counters)
        lane_s = ""
        if lanes:
            prev_lanes = _lane_counts(
                (prev or {}).get("metrics", {}).get("counters", {}))
            if prev is not None and dt:
                lane_s = "   lanes " + " ".join(
                    f"{sid}:{max(c - prev_lanes.get(sid, 0), 0) / dt:.1f}/s"
                    for sid, c in sorted(lanes.items()))
            else:
                lane_s = "   lanes " + " ".join(
                    f"{sid}:{int(c)}" for sid, c in sorted(lanes.items()))
        lines.append(
            f"  mesh       shape {tuple(mesh.get('shape', ()))} "
            f"({mesh.get('devices', 0)} dev)"
            f"   windows {md} mesh / {ml} local{lane_s}")
    approx = serve.get("approx")
    if approx:
        tiers = approx.get("tiers", {})
        total = sum(tiers.values())
        cache = serve.get("cache", {})
        shares = ("  ".join(
            f"{k} {v} ({v / total:.0%})" for k, v in tiers.items())
            if total else "no completed requests yet")
        if not approx.get("enabled", True):
            state = "   approx DISABLED (config)"
        elif not approx.get("allowed_now", True):
            state = "   EXACTNESS BUDGET SPENT (serving exact)"
        else:
            state = ""
        lines.append(
            f"  approx     {shares}"
            + (f"   cache {cache.get('hits', 0)}h/"
               f"{cache.get('misses', 0)}m/{cache.get('entries', 0)}e"
               if cache else "")
            + state)
    subs = serve.get("subscriptions")
    if subs:
        by = subs.get("by_status", {})
        lines.append(
            f"  subs       {by.get('active', 0)} active, "
            f"{subs.get('lagged', 0)} lagged, "
            f"{by.get('quarantined', 0)} quarantined "
            f"({subs.get('subscriptions', 0)} total)")
    slo = serve.get("slo")
    if slo and slo.get("enabled"):
        breaching = slo.get("breaching", [])
        budgets = [o.get("budget_remaining", 1.0)
                   for o in slo.get("objectives", {}).values()]
        lines.append(
            f"  slo        {len(slo.get('objectives', {}))} objective(s)"
            f"   min budget {min(budgets) * 100:.1f}%"
            + (f"   BREACHING: {', '.join(breaching)}" if breaching
               else "   all within budget"))
    return "\n".join(lines)


def _lane_counts(counters: dict) -> dict:
    """Per-shard admitted-query counts off the labeled
    `serve.affinity.admitted{shards=...}` counter series (a multi-owner
    window credits each owning shard)."""
    out: dict = {}
    prefix = "serve.affinity.admitted{"
    for key, v in counters.items():
        if not key.startswith(prefix):
            continue
        label = key[len(prefix):-1]
        if label.startswith('shards="') and label.endswith('"'):
            for sid in label[len('shards="'):-1].split(","):
                sid = sid.strip()
                if sid:
                    out[sid] = out.get(sid, 0.0) + v
    return out


def _fetch_json(base: str, path: str):
    import urllib.request

    with urllib.request.urlopen(f"{base.rstrip('/')}{path}",
                                timeout=10) as r:
        return json.loads(r.read().decode())


def _prof(args) -> int:
    """Render a continuous-profiler snapshot (docs/OBSERVABILITY.md
    "Continuous profiling"): from a live /debug/prof endpoint, or from
    a saved snapshot JSON."""
    import urllib.error

    from geomesa_tpu.telemetry.prof import render_prof

    if args.input:
        with open(args.input) as f:
            doc = json.load(f)
    else:
        base = args.url or f"http://{args.host}:{args.port}"
        try:
            doc = _fetch_json(base, "/debug/prof")
        except (urllib.error.URLError, OSError, ValueError) as e:
            print(f"gmtpu prof: cannot poll {base}/debug/prof: {e}",
                  file=sys.stderr)
            return 1
    if not isinstance(doc, dict) or "phases" not in doc:
        print("error: input is not a /debug/prof document",
              file=sys.stderr)
        return 2
    print(json.dumps(doc) if args.json else render_prof(doc))
    return 0


def _trace(args) -> int:
    """Inspect a trace dump: flight-recorder JSON (`{"traces": ...}`),
    a bare trace list, or Perfetto trace_event JSON round-trips back
    through telemetry.export.from_perfetto."""
    from geomesa_tpu.telemetry import (
        from_perfetto, gap_report, render_gap, to_perfetto)

    with open(args.input) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "traceEvents" in doc:
        traces = from_perfetto(doc)
    elif isinstance(doc, dict) and "traces" in doc:
        traces = doc["traces"]
    elif isinstance(doc, list):
        traces = doc
    else:
        print(f"error: {args.input} is neither a flight-recorder dump "
              "nor a Perfetto trace", file=sys.stderr)
        return 2
    if args.perfetto:
        with open(args.perfetto, "w") as f:
            json.dump(to_perfetto(traces), f)
        print(f"wrote {args.perfetto}", file=sys.stderr)
    if args.gap:
        rep = gap_report(traces)
        print(json.dumps(rep) if args.json else render_gap(rep))
        return 0
    if args.json:
        print(json.dumps(traces))
        return 0
    for t in traces:
        root = t.get("root") or {}
        dur_ms = max(root.get("t1_ns", 0) - root.get("t0_ns", 0), 0) / 1e6
        attrs = dict(root.get("attrs") or ())
        status = attrs.get("status", "?")
        print(f"{t.get('trace_id', '?'):<16} {t.get('name', ''):<8} "
              f"{dur_ms:10.2f} ms  status={status:<9} "
              f"spans={len(t.get('spans', ()))} "
              f"kind={attrs.get('kind', '')}")
    print(f"{len(traces)} trace(s)", file=sys.stderr)
    return 0


def _warmup(args) -> int:
    from geomesa_tpu.compilecache import warmup as _w
    from geomesa_tpu.compilecache.manifest import WarmupManifest
    from geomesa_tpu.compilecache.persist import enable_persistent_cache

    enable_persistent_cache()
    manifest = WarmupManifest.load(args.manifest)
    store = None
    if args.catalog:
        from geomesa_tpu.plan import DataStore

        store = DataStore(args.catalog, use_device_cache=True)
        from geomesa_tpu.parallel.mesh import serve_mesh

        mesh = serve_mesh(getattr(args, "mesh", "auto"))
        if mesh is not None:
            # warm the route serving will take: query entries replay
            # through the mesh dispatch seam, registering + AOT-
            # compiling the mesh-keyed executables (docs/SERVING.md
            # "Sharded serving")
            store.set_mesh(mesh)
    run = _w.check if args.check else _w.replay
    report = run(manifest, store=store)
    for msg in report.errors:
        print(f"warmup: {msg}", file=sys.stderr)
    print(json.dumps(report.to_json()))
    if args.check and report.queries_skipped:
        # skipped entries mean the check proved nothing about them: a
        # green exit here would read as "serving compiles nothing" when
        # the query paths were never replayed
        print("warmup --check: query entries present but no --catalog "
              "given; cannot verify the serving path", file=sys.stderr)
        return 1
    return 0 if report.ok else 1


def _chaos(args) -> int:
    from geomesa_tpu.faults.chaos import run_cli

    if (not args.list_sites and not args.plan
            and not getattr(args, "fleet", False)):
        print("error: chaos needs --plan (or --fleet / --list-sites)",
              file=sys.stderr)
        return 2
    return run_cli(args)


def _fleet(args) -> int:
    import time

    if args.action in ("status", "restart"):
        if not args.port:
            print("error: fleet status/restart needs --port "
                  "(the running router's port)", file=sys.stderr)
            return 2
        from geomesa_tpu.fleet import FleetClient

        cli = FleetClient(args.host, args.port)
        try:
            if args.action == "status":
                doc = cli.request({"op": "fleet"})
                print(json.dumps(doc, indent=1))
                return 0 if doc.get("ok") else 1
            cli.hello(role="admin")
            # rolling restart can legitimately take minutes: each
            # replica drains, respawns, and re-proves its warmup gate
            doc = cli.request({"op": "restart"}, timeout_s=1800.0)
            print(json.dumps(doc, indent=1))
            return 0 if doc.get("ok") else 1
        finally:
            cli.close()
    if not args.catalog:
        print("error: fleet serve needs --catalog", file=sys.stderr)
        return 2
    from geomesa_tpu.fleet import FleetConfig, FleetSupervisor

    sup = FleetSupervisor(FleetConfig(
        n_replicas=args.replicas, catalog=args.catalog,
        spawn=args.spawn, host=args.host, router_port=args.port,
        warmup_manifest=args.warmup,
        metrics_port=args.metrics_port,
        force_cpu_workers=getattr(args, "force_cpu", False)))
    try:
        port = sup.start()
        print(json.dumps({"event": "fleet_ready", "host": args.host,
                          "port": port, "replicas": args.replicas,
                          "spawn": args.spawn}), flush=True)
        print(f"fleet: {args.replicas} replica(s) behind "
              f"{args.host}:{port} — gmtpu fleet status --port {port}",
              file=sys.stderr)
        while True:
            time.sleep(1.0)
            states = [h.state for h in sup.membership.all()]
            if all(s == "dead" for s in states):
                print("fleet: every replica dead; exiting",
                      file=sys.stderr)
                return 1
    except KeyboardInterrupt:
        print("fleet: draining...", file=sys.stderr)
        return 0
    finally:
        sup.close()


def _lint(args) -> int:
    from geomesa_tpu.analysis.linter import run_cli

    return run_cli(args)


def _guard(args) -> int:
    from geomesa_tpu.analysis.runtime import run_guarded

    def storm(name, count):
        print(f"gmtpu guard: retrace storm: {name} recompiled "
              f"{count} times", file=sys.stderr)

    report, status = run_guarded(
        args.script, argv=list(args.script_args),
        transfer=args.transfer, warn_after=args.recompile_warn,
        on_storm=storm, races=getattr(args, "races", False))
    locksets = report.pop("locksets", None)
    tracked = {k: v for k, v in report.items() if v.get("calls")}
    print("gmtpu guard report:", file=sys.stderr)
    if not tracked:
        print("  (no tracked engine jit calls)", file=sys.stderr)
    for name, rec in sorted(tracked.items()):
        print(f"  {name}: calls={rec['calls']} "
              f"recompiles={rec['recompiles']}", file=sys.stderr)
    if locksets is not None:
        print(f"  locksets: {locksets['locks_created']} lock(s) tracked, "
              f"{locksets['order_edges']} order edge(s), "
              f"{len(locksets['inversions'])} inversion(s), "
              f"{len(locksets['races'])} race(s)", file=sys.stderr)
        for inv in locksets["inversions"]:
            print(f"    INVERSION {inv['first']} vs {inv['second']}",
                  file=sys.stderr)
        for race in locksets["races"]:
            print(f"    RACE key={race['key']} "
                  f"threads={race['threads']} writes={race['writes']}",
                  file=sys.stderr)
        if locksets["violations"] and status == 0:
            # the harness's whole point: a racy-but-green script must
            # not exit 0 under --races
            status = 1
    return status


def _version(args) -> int:
    import geomesa_tpu

    print(geomesa_tpu.__version__)
    return 0


def _store(args):
    from geomesa_tpu.plan import DataStore

    return DataStore(args.catalog)


def _create_schema(args) -> int:
    from geomesa_tpu.core.sft import SimpleFeatureType
    from geomesa_tpu.store.partition import scheme_from_config

    sft = SimpleFeatureType.from_spec(args.feature_name, args.spec)
    scheme = (
        scheme_from_config(json.loads(args.partition_scheme))
        if args.partition_scheme
        else None
    )
    _store(args).create_schema(sft, scheme, encoding=args.encoding)
    print(f"created schema {args.feature_name}")
    return 0


def _get_type_names(args) -> int:
    for n in _store(args).get_type_names():
        print(n)
    return 0


def _describe_schema(args) -> int:
    sft = _store(args).get_schema(args.feature_name)
    print(f"{sft.name}:")
    for a in sft.attributes:
        marks = []
        if a.default_geom:
            marks.append("*default geometry")
        if a.options:
            marks.append(",".join(f"{k}={v}" for k, v in a.options.items()))
        print(f"  {a.name:<24}{a.type:<16}{' '.join(marks)}")
    if sft.user_data:
        print("user data:")
        for k, v in sft.user_data.items():
            print(f"  {k}={v}")
    return 0


def _remove_schema(args) -> int:
    _store(args).remove_schema(args.feature_name)
    print(f"removed schema {args.feature_name}")
    return 0


def _delete_features(args) -> int:
    src = _store(args).get_feature_source(args.feature_name)
    n = src.delete_features(args.cql)
    print(f"deleted {n} features from {args.feature_name}")
    return 0


def _age_off(args) -> int:
    import datetime as _dt

    try:
        dt = _dt.datetime.fromisoformat(
            args.older_than.replace("Z", "+00:00"))
    except ValueError:
        print(f"error: --older-than {args.older_than!r} is not a valid "
              "ISO-8601 instant", file=sys.stderr)
        return 2
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    cutoff = int(dt.timestamp() * 1000)
    src = _store(args).get_feature_source(args.feature_name)
    n = src.age_off(cutoff)
    print(f"aged off {n} features from {args.feature_name}")
    return 0


def _ingest(args) -> int:
    from geomesa_tpu.convert import converter_from_config, schemas

    ds = _store(args)
    arrow_files = [p for p in args.files
                   if getattr(args, "arrow", False)
                   or p.endswith((".arrow", ".ipc"))]
    if arrow_files:
        # columnar bulk ingest: record batches go straight into the
        # store as NumPy views via DataStore.write_batch — no
        # converter, no per-feature Python dicts (docs/SERVING.md
        # "Columnar wire")
        if set(arrow_files) != set(args.files):
            print("error: cannot mix Arrow IPC and converter inputs "
                  "in one ingest", file=sys.stderr)
            return 2
        if args.feature_name not in ds.get_type_names():
            # the IPC stream embeds the SFT spec in its schema
            # metadata (arrow_io.arrow_schema) — create the schema
            # from it, or refuse TYPED instead of a raw traceback
            import pyarrow as pa

            from geomesa_tpu.core.sft import SimpleFeatureType

            with open(arrow_files[0], "rb") as f:
                meta = pa.ipc.open_stream(f).schema.metadata or {}
            spec = meta.get(b"geomesa.sft.spec")
            if spec is None:
                print(f"error: schema {args.feature_name!r} does not "
                      f"exist and {arrow_files[0]} carries no "
                      f"geomesa.sft.spec metadata — run create-schema "
                      f"first", file=sys.stderr)
                return 2
            ds.create_schema(SimpleFeatureType.from_spec(
                args.feature_name, spec.decode()))
        total = batches = 0
        for path in arrow_files:
            with open(path, "rb") as f:
                rows, nb = ds.write_batch(args.feature_name, f.read())
            total += rows
            batches += nb
        print(f"ingested {total} features ({batches} record batches, "
              f"columnar) into {args.feature_name}")
        return 0
    if not args.converter:
        print("error: --converter is required for non-Arrow inputs",
              file=sys.stderr)
        return 2
    if args.converter in schemas.WELL_KNOWN:
        sft, config = schemas.WELL_KNOWN[args.converter]
        sft = type(sft)(args.feature_name, sft.attributes, sft.user_data)
    else:
        with open(args.converter) as f:
            config = json.load(f)
        sft = ds.get_schema(args.feature_name)
    if args.feature_name in ds.get_type_names():
        src = ds.get_feature_source(args.feature_name)
    else:
        src = ds.create_schema(sft)
    if getattr(args, "workers", 1) > 1:
        from geomesa_tpu.jobs import ingest_files

        rep = ingest_files(
            src,
            lambda: converter_from_config(src.sft, config),
            args.files,
            workers=args.workers,
            resume=not getattr(args, "no_resume", False),
        )
        print(
            f"ingested {rep.features} features into {args.feature_name} "
            f"({len(rep.files_ok)} files ok, {len(rep.files_failed)} failed, "
            f"{rep.records_failed} records failed, "
            f"{len(rep.skipped)} skipped by checkpoint)"
        )
        for line in rep.files_failed:
            print(f"  FAILED {line}", file=sys.stderr)
        return 1 if rep.files_failed else 0
    conv = converter_from_config(src.sft, config)
    total = failed = 0
    for path in args.files:
        batch = conv.convert(path)
        src.write(batch)
        total += len(batch)
        failed += conv.failed
    print(f"ingested {total} features ({failed} failed) into {args.feature_name}")
    return 0


def _export(args) -> int:
    from geomesa_tpu.plan import Query, QueryHints

    ds = _store(args)
    src = ds.get_feature_source(args.feature_name)
    attrs = args.attributes.split(",") if args.attributes else None
    hints = QueryHints()
    binary = args.format in ("arrow", "bin", "parquet", "orc")
    if args.format == "bin":
        track = args.bin_track or next(
            (a.name for a in src.sft.attributes if not a.is_geometry), None
        )
        if track is None:
            raise ValueError("bin export needs --bin-track (no non-geometry attribute)")
        hints = QueryHints(bin_track=track)
    crs = None
    if getattr(args, "crs", None):
        if str(args.crs).lower() == "utm":
            # auto zone from the query's spatial center (reprojection to
            # the local UTM zone — the common analytic output request)
            from geomesa_tpu.core.crs import utm_zone_srid
            from geomesa_tpu.cql import parse_cql
            from geomesa_tpu.cql.extract import extract_bbox

            g = src.sft.default_geometry
            bbox = extract_bbox(parse_cql(args.cql),
                                g.name if g is not None else "")
            if bbox.is_whole_world:
                raise ValueError(
                    "--crs utm needs a spatial filter (the zone is picked "
                    "from the query bbox center); give an EPSG code instead"
                )
            crs = utm_zone_srid((bbox.xmin + bbox.xmax) / 2,
                                (bbox.ymin + bbox.ymax) / 2)
            print(f"auto UTM zone: EPSG:{crs}", file=sys.stderr)
        else:
            s = str(args.crs).lower().removeprefix("epsg:")
            try:
                crs = int(s)
            except ValueError:
                raise ValueError(
                    f"--crs must be 'utm' or an EPSG code (got {args.crs!r})"
                ) from None
    if crs is not None and crs != 4326:
        # bin results bypass finish_features (raw stored lon/lat), and
        # leaflet plots lat/lng — a projected CRS would silently corrupt both
        if args.format == "bin":
            raise ValueError("--crs is not supported for -F bin "
                             "(BIN encodes stored lon/lat)")
        if args.format == "leaflet":
            raise ValueError("--crs is not supported for -F leaflet "
                             "(leaflet maps plot EPSG:4326 lat/lng)")
    q = Query(args.feature_name, args.cql, attributes=attrs,
              max_features=args.max_features, hints=hints, crs=crs)
    r = src.get_features(q)
    if args.format == "shp":
        if args.output == "-":
            raise ValueError("shp export needs --output (writes .shp/.shx/.dbf)")
        from geomesa_tpu.convert.formats import write_shapefile

        if r.features is None or len(r.features) == 0:
            print("no features matched; nothing written", file=sys.stderr)
            return 0
        write_shapefile(args.output, r.features)
        return 0
    if args.format == "leaflet":
        html = _leaflet_html(r.features, args.feature_name)
        if args.output == "-":
            sys.stdout.write(html)
        else:
            with open(args.output, "w") as f:
                f.write(html)
        return 0
    if args.output == "-":
        out = sys.stdout.buffer if binary else sys.stdout
    else:
        out = open(args.output, "wb" if binary else "w")
    try:
        if args.format == "bin":
            out.write(r.bin_bytes or b"")
        elif args.format in ("arrow", "parquet", "orc"):
            out.write(_arrow_bytes(r.features, src.sft, args.format))
        elif args.format == "gml":
            _write_gml(out, r.features, args.feature_name)
        else:
            _write_text(out, r.features, args.format)
    finally:
        if args.output != "-":
            out.close()
    return 0


def _arrow_bytes(batch, sft, fmt: str) -> bytes:
    """Encode features as Arrow IPC / Parquet / ORC bytes. Zero matches
    still yields a VALID schema-only file (a 0-byte parquet/orc is corrupt
    to every reader), built from an empty batch of the feature type."""
    import io

    import pyarrow as pa

    from geomesa_tpu.core.arrow_io import to_arrow
    from geomesa_tpu.core.columnar import FeatureBatch

    if batch is None or len(batch) == 0:
        print("no features matched; writing schema-only output",
              file=sys.stderr)
        batch = FeatureBatch.from_pydict(
            sft, {a.name: [] for a in sft.attributes}
        )
    rb = to_arrow(batch)
    sink = io.BytesIO()
    if fmt == "arrow":
        with pa.ipc.new_stream(sink, rb.schema) as w:
            w.write_batch(rb)
    else:
        table = pa.Table.from_batches([rb])
        if fmt == "parquet":
            import pyarrow.parquet as papq

            papq.write_table(table, sink)
        else:
            import pyarrow.orc as paorc

            # ORC has no dictionary type: decode dict columns
            cols = [
                c.cast(c.type.value_type)
                if pa.types.is_dictionary(c.type) else c
                for c in (table.column(i).combine_chunks()
                          for i in range(table.num_columns))
            ]
            paorc.write_table(pa.table(cols, names=table.column_names), sink)
    return sink.getvalue()


def _gml_geometry(g) -> str:
    """GML 3.1 markup for a host Geometry (gml:pos/posList are lat lon
    order per the spec's EPSG:4326 axis order)."""

    def pos(ring):
        return " ".join(f"{p[1]} {p[0]}" for p in ring)

    def polygon(rings):
        s = (f"<gml:Polygon><gml:exterior><gml:LinearRing><gml:posList>"
             f"{pos(rings[0])}</gml:posList></gml:LinearRing></gml:exterior>")
        for hole in rings[1:]:
            s += (f"<gml:interior><gml:LinearRing><gml:posList>{pos(hole)}"
                  f"</gml:posList></gml:LinearRing></gml:interior>")
        return s + "</gml:Polygon>"

    k = g.kind
    if k == "Point":
        return (f'<gml:Point srsName="EPSG:4326"><gml:pos>{pos(g.rings[0])}'
                f"</gml:pos></gml:Point>")
    if k == "LineString":
        if not g.rings:
            return "<gml:LineString/>"
        return (f"<gml:LineString><gml:posList>{pos(g.rings[0])}"
                f"</gml:posList></gml:LineString>")
    if k == "Polygon":
        return polygon(g.rings) if g.rings else "<gml:Polygon/>"
    if k not in ("MultiPoint", "MultiLineString", "MultiPolygon"):
        # GeometryCollection / mixed columns have no single GML container
        # here — fail loudly rather than mislabel parts (the WKT export
        # formats handle them)
        raise ValueError(f"cannot encode {k} as GML")
    # Multi*: one member per part (parts = ring count per part); empty
    # parts (e.g. MULTIPOLYGON EMPTY) contribute no members
    members = []
    at = 0
    for count in (g.parts or [1] * len(g.rings)):
        rings = g.rings[at:at + count]
        at += count
        if not rings:
            continue
        if k == "MultiPoint":
            members.append(
                f"<gml:pointMember><gml:Point><gml:pos>{pos(rings[0])}"
                f"</gml:pos></gml:Point></gml:pointMember>")
        elif k == "MultiLineString":
            members.append(
                f"<gml:lineStringMember><gml:LineString><gml:posList>"
                f"{pos(rings[0])}</gml:posList></gml:LineString>"
                f"</gml:lineStringMember>")
        else:
            members.append(
                f"<gml:polygonMember>{polygon(rings)}</gml:polygonMember>")
    return f"<gml:{k}>{''.join(members)}</gml:{k}>"


def _write_gml(out, batch, type_name):
    """GML 3.1 FeatureCollection (the reference's GML export format). Point
    members use gml:pos lat-order per the GML spec's EPSG:4326 axis order."""
    from xml.sax.saxutils import escape, quoteattr

    from geomesa_tpu.core.columnar import DictColumn, GeometryColumn

    out.write(
        '<?xml version="1.0" encoding="UTF-8"?>\n'
        '<gml:FeatureCollection xmlns:gml="http://www.opengis.net/gml" '
        'xmlns:geomesa="http://geomesa.org">\n'
    )
    if batch is not None and len(batch):
        names = batch.sft.attribute_names
        # fail BEFORE writing anything: an unsupported geometry column kind
        # raising mid-stream would leave a truncated invalid document
        for n in names:
            col = batch.columns[n]
            if (isinstance(col, GeometryColumn) and not col.is_point
                    and col.kind not in ("LineString", "Polygon", "MultiPoint",
                                         "MultiLineString", "MultiPolygon")):
                raise ValueError(f"cannot encode {col.kind} as GML")
        fids = batch.fids.decode() if batch.fids is not None else range(len(batch))
        # decode()/materialize once per column — per-row decode is O(N^2)
        cols = {}
        for n in names:
            col = batch.columns[n]
            if isinstance(col, GeometryColumn):
                cols[n] = col
            elif isinstance(col, DictColumn):
                cols[n] = col.decode()
            else:
                cols[n] = col
        for i in range(len(batch)):
            out.write(f'  <gml:featureMember>\n    <geomesa:{type_name} '
                      f"gml:id={quoteattr(str(fids[i]))}>\n")
            for n in names:
                col = cols[n]
                if isinstance(col, GeometryColumn):
                    if col.is_point:
                        gml = (f'<gml:Point srsName="EPSG:4326"><gml:pos>'
                               f"{col.y[i]} {col.x[i]}</gml:pos></gml:Point>")
                    else:
                        gml = _gml_geometry(col.geometry(i))
                    out.write(f"      <geomesa:{n}>{gml}</geomesa:{n}>\n")
                else:
                    out.write(
                        f"      <geomesa:{n}>{escape(str(col[i]))}</geomesa:{n}>\n"
                    )
            out.write(f"    </geomesa:{type_name}>\n  </gml:featureMember>\n")
    out.write("</gml:FeatureCollection>\n")


def _write_text(out, batch, fmt):
    import csv

    import numpy as np

    from geomesa_tpu.core.columnar import DictColumn, GeometryColumn
    from geomesa_tpu.core.wkt import to_wkt

    if batch is None or len(batch) == 0:
        return
    names = batch.sft.attribute_names
    geom_attr = batch.sft.default_geometry

    def geom_wkt(col, i):
        return (
            f"POINT ({col.x[i]} {col.y[i]})"
            if col.is_point
            else to_wkt(col.geometry(i))
        )

    if fmt == "wkt":
        col = batch.columns[geom_attr.name]
        for i in range(len(batch)):
            out.write(geom_wkt(col, i) + "\n")
        return
    # materialize each column once (decode()/asarray are O(N) per call)
    materialized = {}
    for name in names:
        col = batch.columns[name]
        if isinstance(col, GeometryColumn):
            materialized[name] = col
        elif isinstance(col, DictColumn):
            materialized[name] = col.decode()
        else:
            materialized[name] = np.asarray(col)
    rows = []
    for i in range(len(batch)):
        row = {}
        for name in names:
            col = batch.columns[name]
            m = materialized[name]
            if isinstance(col, GeometryColumn):
                row[name] = geom_wkt(m, i)
            elif isinstance(col, DictColumn):
                v = m[i]
                row[name] = "" if v is None else v
            else:
                row[name] = m[i].item()
        rows.append(row)
    if fmt == "json":
        for r in rows:
            out.write(json.dumps(r) + "\n")
    else:
        writer = csv.writer(out, delimiter="\t" if fmt == "tsv" else ",")
        writer.writerow(names)
        for r in rows:
            writer.writerow([r[n] for n in names])


def _leaflet_html(batch, title: str) -> str:
    """Self-contained Leaflet HTML preview (geomesa-tools export -F leaflet
    analog): embedded GeoJSON over CDN Leaflet assets."""
    from geomesa_tpu.core.columnar import GeometryColumn

    features = []
    if batch is not None and len(batch):
        geom = batch.geometry
        fids = batch.fids.decode() if batch.fids is not None else None
        for i in range(len(batch)):
            if isinstance(geom, GeometryColumn) and geom.is_point:
                coords = [float(geom.x[i]), float(geom.y[i])]
                gj = {"type": "Point", "coordinates": coords}
            else:
                from geomesa_tpu.core.wkt import to_geojson

                gj = to_geojson(geom.geometry(i))
            features.append({
                "type": "Feature",
                "id": fids[i] if fids else str(i),
                "geometry": gj,
                "properties": {},
            })
    collection = json.dumps({"type": "FeatureCollection", "features": features})
    return f"""<!DOCTYPE html>
<html><head><title>{title}</title>
<link rel="stylesheet" href="https://unpkg.com/leaflet@1.9.4/dist/leaflet.css"/>
<script src="https://unpkg.com/leaflet@1.9.4/dist/leaflet.js"></script>
<style>#map {{ height: 100vh; }}</style></head>
<body><div id="map"></div><script>
var map = L.map('map').setView([0, 0], 2);
L.tileLayer('https://tile.openstreetmap.org/{{z}}/{{x}}/{{y}}.png',
            {{maxZoom: 19}}).addTo(map);
var data = {collection};
var layer = L.geoJSON(data).addTo(map);
if (data.features.length) map.fitBounds(layer.getBounds());
</script></body></html>
"""


def _manage_partitions(args) -> int:
    storage = _store(args).get_feature_source(args.feature_name).storage
    for name in storage.partitions():
        files = storage.manifest.get(name, [])
        count = sum(f["count"] for f in files)
        print(f"{name}\t{len(files)} file(s)\t{count} feature(s)")
    return 0


def _compact(args) -> int:
    storage = _store(args).get_feature_source(args.feature_name).storage
    removed = storage.compact(args.partition)
    print(f"compacted: {removed} file(s) merged")
    return 0


def _explain(args) -> int:
    src = _store(args).get_feature_source(args.feature_name)
    print(src.explain(args.cql))
    return 0


def _stats_analyze(args) -> int:
    from geomesa_tpu.plan.stats_manager import StatsManager

    src = _store(args).get_feature_source(args.feature_name)
    mgr = StatsManager(src.storage)
    summary = mgr.analyze()
    print(json.dumps(summary, indent=1, default=str))
    return 0


def _stats_bounds(args) -> int:
    from geomesa_tpu.plan import Query, QueryHints

    src = _store(args).get_feature_source(args.feature_name)
    attrs = (
        args.attributes.split(",")
        if args.attributes
        else [
            a.name
            for a in src.sft.attributes
            if not a.is_geometry and a.type not in ("String", "UUID", "Bytes")
        ]
    )
    expr = ";".join(f"MinMax({a})" for a in attrs)
    stats = src.get_features(
        Query(args.feature_name, args.cql, hints=QueryHints(stats_string=expr))
    ).stats
    for a, s in zip(attrs, stats.stats):
        print(f"{a}: {s.result()}")
    return 0


def _stats_count(args) -> int:
    from geomesa_tpu.plan import Query, QueryHints

    src = _store(args).get_feature_source(args.feature_name)
    q = Query(args.feature_name, args.cql,
              hints=QueryHints(exact_count=not args.no_exact))
    print(src.get_count(q))
    return 0


def _stats_histogram(args) -> int:
    import numpy as np

    from geomesa_tpu.plan import Query, QueryHints

    src = _store(args).get_feature_source(args.feature_name)
    attr = src.sft.attribute(args.attribute)
    if attr.is_geometry or attr.type in ("String", "UUID", "Bytes"):
        raise ValueError(
            f"stats-histogram requires a numeric or date attribute; "
            f"{args.attribute!r} is {attr.type} (use stats-top-k for strings)"
        )
    # bounds first, then histogram over them
    mm = src.get_features(
        Query(args.feature_name, args.cql,
              hints=QueryHints(stats_string=f"MinMax({args.attribute})"))
    ).stats.stats[0].result()
    lo, hi = mm
    if lo is None:
        print("no data")
        return 0
    hi = hi if hi > lo else lo + 1
    stats = src.get_features(
        Query(args.feature_name, args.cql,
              hints=QueryHints(
                  stats_string=f"Histogram({args.attribute},{args.bins},{lo},{hi})"
              ))
    ).stats
    counts = stats.stats[0].result()
    width = (hi - lo) / args.bins
    for i, c in enumerate(np.asarray(counts)):
        print(f"[{lo + i * width:.4g}, {lo + (i + 1) * width:.4g}) {int(c)}")
    return 0


def _stats_topk(args) -> int:
    from geomesa_tpu.plan import Query, QueryHints

    src = _store(args).get_feature_source(args.feature_name)
    stats = src.get_features(
        Query(args.feature_name, args.cql,
              hints=QueryHints(stats_string=f"TopK({args.attribute},{args.k})"))
    ).stats
    for value, count in stats.stats[0].result():
        print(f"{value}\t{count}")
    return 0


def _sql(args) -> int:
    """SQL surface through the CLI (upstream exposes SQL via Spark; the
    engine here is sql/engine.py — pushdown, GROUP BY, JOIN)."""
    import numpy as np

    from geomesa_tpu.core.columnar import DictColumn, GeometryColumn
    from geomesa_tpu.core.wkt import to_wkt
    from geomesa_tpu.sql.engine import SqlContext

    r = SqlContext(_store(args)).sql(args.query)
    if r.kind == "count":
        print(r.count)
        return 0
    batch = r.features
    if batch is None or not len(batch):
        if args.format == "json":
            print("[]")
        return 0

    def cells(col):
        if isinstance(col, DictColumn):
            return col.decode()
        if isinstance(col, GeometryColumn):
            return [to_wkt(col.geometry(i)) for i in range(len(col))]
        return [v.item() if hasattr(v, "item") else v for v in np.asarray(col)]

    names = [a.name for a in batch.sft.attributes]
    table = {n: cells(batch.columns[n]) for n in names}
    if args.format == "json":
        def jval(v):
            # NaN is the engine's SQL NULL for doubles; bare NaN is not JSON
            if isinstance(v, float) and v != v:
                return None
            return v

        rows = [
            {n: jval(table[n][i]) for n in names} for i in range(len(batch))
        ]
        print(json.dumps(rows, default=str))
        return 0
    import csv as _csv

    w = _csv.writer(sys.stdout)
    w.writerow(names)
    for i in range(len(batch)):
        w.writerow(
            ["" if table[n][i] is None else table[n][i] for n in names]
        )
    return 0


def _env(args) -> int:
    from geomesa_tpu.utils.config import SystemProperties

    for name, prop in sorted(SystemProperties.all().items()):
        print(f"{name} = {prop.get()} ({prop.provenance}) — {prop.description}")
    return 0
