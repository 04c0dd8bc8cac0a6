"""Zero-recompile serving: persistent cache, AOT registry, warmup
manifests.

The load-bearing test is
TestServeWarmup::test_zero_recompiles_after_warmup — a mixed kNN/count
workload recorded into a manifest, engine jit caches dropped (the
in-process stand-in for a fresh process), the manifest replayed through
QueryService.warmup(), and the SAME workload run twice with JitTracker
proving ZERO engine recompiles — the serving cold-start contract of
docs/SERVING.md's "Cold start" section.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from geomesa_tpu.compilecache.manifest import (
    KernelEntry, QueryEntry, UnrecordableArg, WarmupManifest,
    WarmupRecorder, encode_arg)
from geomesa_tpu.compilecache.registry import ExecutableRegistry
from geomesa_tpu.compilecache import warmup as cc_warmup
from geomesa_tpu.core.columnar import FeatureBatch
from geomesa_tpu.core.sft import SimpleFeatureType
from geomesa_tpu.plan.audit import ServeEvent
from geomesa_tpu.plan.datastore import DataStore
from geomesa_tpu.serve.service import QueryService, ServeConfig
from geomesa_tpu.utils.metrics import Histogram

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CQL = "BBOX(geom, -170, -80, 170, 80) AND score > -5"


def make_store(tmp_path_factory, n=600, seed=3):
    rng = np.random.default_rng(seed)
    sft = SimpleFeatureType.from_spec(
        "served", "name:String,score:Double,dtg:Date,*geom:Point")
    batch = FeatureBatch.from_pydict(sft, {
        "name": rng.choice(["a", "b", "c"], n).tolist(),
        "score": rng.uniform(-10, 10, n),
        "dtg": rng.integers(1_590_000_000_000, 1_600_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)], 1),
    })
    ds = DataStore(
        str(tmp_path_factory.mktemp("compilecache")), use_device_cache=True)
    ds.create_schema(sft).write(batch)
    return ds


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    return make_store(tmp_path_factory)


def run_mixed_workload(svc, knn=6, counts=3):
    """The serving workload shape of the regression: compatible kNN
    requests (coalesce into one padded [8] launch) + count dedup."""
    rng = np.random.default_rng(11)
    pts = rng.uniform(-60, 60, (knn, 2))
    futs = [svc.knn("served", CQL, pts[i:i + 1, 0], pts[i:i + 1, 1], k=5)
            for i in range(knn)]
    cfuts = [svc.count("served", CQL) for _ in range(counts)]
    out = [f.result(timeout=120) for f in futs]
    out += [f.result(timeout=120) for f in cfuts]
    return out


# -- persistent cache ------------------------------------------------------


class TestPersistentCache:
    def test_enable_idempotent_and_per_platform(self, tmp_path):
        import jax

        from geomesa_tpu.compilecache import persist

        old_dir = persist._enabled_dir
        old_cfg = jax.config.jax_compilation_cache_dir
        try:
            got = persist.enable_persistent_cache(
                str(tmp_path / "cc"), force=True)
            assert got is not None
            # per-backend subdir: CPU and TPU artifacts never mix
            assert os.path.basename(got) == jax.default_backend()
            assert os.path.isdir(got)
            assert jax.config.jax_compilation_cache_dir == got
            # idempotent: a later default call does not move the cache
            again = persist.enable_persistent_cache()
            assert again == got
            assert persist.persistent_cache_dir() == got
        finally:
            persist._enabled_dir = old_dir
            jax.config.update("jax_compilation_cache_dir", old_cfg)

    @pytest.fixture()
    def unconfigured(self, monkeypatch):
        """No geomesa cache property; the persist module's state and
        jax's cache dir restored afterwards."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from geomesa_tpu.compilecache import persist

        monkeypatch.delenv("GEOMESA_TPU_COMPILE_CACHE_DIR", raising=False)
        old_dir = persist._enabled_dir
        old_cfg = jax.config.jax_compilation_cache_dir
        old_regex = jax.config.jax_hlo_source_file_canonicalization_regex
        yield persist
        persist._enabled_dir = old_dir
        jax.config.update("jax_compilation_cache_dir", old_cfg)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          old_regex)
        compilation_cache.reset_cache()

    def test_jax_env_dir_honoured_as_is(self, unconfigured, tmp_path,
                                        monkeypatch):
        import jax

        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        before = jax.config.jax_compilation_cache_dir
        got = unconfigured.enable_persistent_cache(force=True)
        # no per-backend subdirectory, and jax's own setting untouched
        assert got == placed
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_inside_checkout(self, unconfigured,
                                              monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        want = os.path.join(checkout, ".jax_cache", jax.default_backend())
        got = unconfigured.enable_persistent_cache(force=True)
        assert got == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_installed_package_defaults_to_user_cache(self, unconfigured,
                                                      tmp_path,
                                                      monkeypatch):
        """Outside a source checkout the package's parent directory is
        site-packages: the default is the per-user cache instead."""
        monkeypatch.setattr(unconfigured, "_CHECKOUT",
                            str(tmp_path / "site-packages"))
        monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
        assert unconfigured.default_cache_dir() == str(
            tmp_path / "xdg" / "geomesa_tpu" / "jax_cache")

    def test_failed_enable_is_counted(self, unconfigured, tmp_path):
        from geomesa_tpu.utils.metrics import metrics

        def failed():
            return json.loads(metrics.to_json())["counters"].get(
                "compilecache.persistent.enable_failed", 0.0)

        before = failed()
        blocker = tmp_path / "file"
        blocker.write_text("")  # makedirs under a file fails
        assert unconfigured.enable_persistent_cache(
            str(blocker / "cache"), force=True) is None
        assert failed() == before + 1

    def test_kernel_source_paths_relative_to_checkout(self, unconfigured,
                                                      tmp_path):
        """Pallas kernels embed their source paths in the program: the
        cache key must not depend on where the checkout lives."""
        import re

        import jax

        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          None)
        unconfigured.enable_persistent_cache(str(tmp_path), force=True)
        regex = jax.config.jax_hlo_source_file_canonicalization_regex
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        src = os.path.join(checkout, "geomesa_tpu", "engine", "knn_scan.py")
        assert re.sub(regex, "", src) == os.path.join(
            "geomesa_tpu", "engine", "knn_scan.py")

    def test_disable_token(self):
        from geomesa_tpu.compilecache import persist

        old_dir = persist._enabled_dir
        try:
            assert persist.enable_persistent_cache("off", force=True) is None
        finally:
            persist._enabled_dir = old_dir


# -- metrics: sub-millisecond buckets --------------------------------------


class TestSubMillisecondBuckets:
    def test_sub_ms_timings_resolve(self):
        h = Histogram()
        assert h.bounds[0] < 0.0005  # explicit sub-ms buckets exist
        for _ in range(100):
            h.update(0.00003)  # a 30µs dispatch
        # previously everything below 0.5ms hit the bottom bucket and
        # quantiles reported up to 0.5ms; now they resolve to ~µs scale
        assert h.quantile(0.99) <= 0.0001

    def test_compile_scale_still_fits(self):
        h = Histogram()
        h.update(120.0)  # a slow cold Mosaic compile
        assert h.quantile(0.5) >= 1.0


# -- ExecutableRegistry ----------------------------------------------------


class TestExecutableRegistry:
    def test_aot_compile_hit_miss_and_call(self):
        import jax
        import jax.numpy as jnp

        reg = ExecutableRegistry()
        reg.register("t.add", jax.jit(lambda a, b: a + b))
        sds = jax.ShapeDtypeStruct((4,), jnp.float32)
        h = reg.compile("t.add", sds, sds)
        assert reg.stats()["misses"] == 1
        out = h.call(jnp.ones(4, jnp.float32),
                     jnp.full(4, 2.0, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 3.0)
        # same signature from CONCRETE arrays keys identically: hit
        h2 = reg.compile("t.add", jnp.zeros(4, jnp.float32),
                         jnp.zeros(4, jnp.float32))
        assert h2 is h
        assert reg.stats()["hits"] == 1
        # a different bucket is a different executable
        sds8 = jax.ShapeDtypeStruct((8,), jnp.float32)
        assert reg.compile("t.add", sds8, sds8) is not h
        with pytest.raises(KeyError):
            reg.compile("t.missing", sds)

    def test_static_args_baked_into_executable(self):
        import jax
        import jax.numpy as jnp

        reg = ExecutableRegistry()
        reg.register("t.mul", jax.jit(
            lambda x, n=2: x * n, static_argnames=("n",)))
        h = reg.compile("t.mul", jax.ShapeDtypeStruct((3,), jnp.float32),
                        n=5)
        # AOT contract: statics are baked; call takes only array args
        np.testing.assert_allclose(
            np.asarray(h.call(jnp.ones(3, jnp.float32))), 5.0)

    def test_donation_opt_in(self):
        import jax
        import jax.numpy as jnp

        reg = ExecutableRegistry()
        reg.register("t.don", lambda x: x + 1.0, donate_argnums=(0,))
        h = reg.compile("t.don", jax.ShapeDtypeStruct((3,), jnp.float32))
        np.testing.assert_allclose(
            np.asarray(h.call(jnp.ones(3, jnp.float32))), 2.0)

    def test_install_defaults_covers_hot_kernels(self):
        import jax
        import jax.numpy as jnp

        reg = ExecutableRegistry()
        n = reg.install_defaults()
        assert n > 0
        names = reg.names()
        assert "knn_scan.knn_sparse_scan" in names
        assert "knn_scan.count_match_tiles" in names
        # AOT-compile a real engine kernel per the planner's pow2 bucket
        h = reg.compile(
            "knn_scan.count_match_tiles",
            jax.ShapeDtypeStruct((4096,), jnp.bool_), data_tile=2048)
        assert int(np.asarray(h.call(jnp.zeros(4096, jnp.bool_)))) == 0


# -- manifest record / round-trip ------------------------------------------


class TestManifest:
    def test_encode_args(self):
        import jax.numpy as jnp

        assert encode_arg(jnp.zeros((2, 3), jnp.float32)) == {
            "shape": [2, 3], "dtype": "float32"}
        assert encode_arg(np.zeros(4, bool)) == {
            "shape": [4], "dtype": "bool"}
        assert encode_arg(7) == {"static": 7}
        assert encode_arg(True) == {"static": True}
        with pytest.raises(UnrecordableArg):
            encode_arg({"a": 1})  # pytrees don't record

    def test_recorder_dedups_and_counts(self):
        rec = WarmupRecorder()
        rec.record_kernel("m.x", "f", (np.zeros(4, np.float32),), {}, 1.0)
        rec.record_kernel("m.x", "f", (np.zeros(4, np.float32),), {}, 2.0)
        rec.record_kernel("m.x", "f", (np.zeros(8, np.float32),), {}, 0.5)
        rec.record_query("count", "t", "INCLUDE")
        rec.record_query("count", "t", "INCLUDE")
        m = rec.manifest()
        kernels = {tuple(e.args[0]["shape"]): e for e in m.kernel_entries}
        assert kernels[(4,)].count == 2
        assert kernels[(4,)].compile_s == 2.0  # max observed
        assert kernels[(8,)].count == 1
        assert m.query_entries[0].count == 2

    def test_recorder_skips_unrecordable(self):
        rec = WarmupRecorder()
        rec.record_kernel("m.x", "f", ({"pytree": 1},), {}, 0.0)
        assert rec.skipped == 1
        assert len(rec.manifest()) == 0

    def test_recorder_bounded_on_high_cardinality(self):
        rec = WarmupRecorder(max_entries=4)
        for i in range(10):
            rec.record_query("count", "t", f"score > {i}")
        rec.record_query("count", "t", "score > 0")  # existing key: counts
        m = rec.manifest()
        assert len(m) == 4
        assert rec.skipped == 6
        assert next(e for e in m.query_entries
                    if e.cql == "score > 0").count == 2

    def test_save_load_round_trip(self, tmp_path):
        m = WarmupManifest([
            KernelEntry("geomesa_tpu.engine.knn_scan", "count_match_tiles",
                        [{"shape": [4096], "dtype": "bool"}],
                        {"data_tile": {"static": 2048}}),
            QueryEntry("knn", "served", CQL, q=8, k=5, impl="sparse"),
        ])
        path = str(tmp_path / "m.json")
        m.save(path)
        m2 = WarmupManifest.load(path)
        assert [e.to_json() for e in m2.entries] == [
            e.to_json() for e in m.entries]

    def test_version_gate(self, tmp_path):
        path = str(tmp_path / "bad.json")
        with open(path, "w") as f:
            json.dump({"version": 99, "entries": []}, f)
        with pytest.raises(ValueError):
            WarmupManifest.load(path)


# -- warmup replay / check -------------------------------------------------


FIXTURE = os.path.join(REPO_ROOT, "scripts", "warmup_smoke_manifest.json")


class TestWarmupReplay:
    @pytest.mark.slow  # the tier-1 lint-gate subprocess runs this same
    def test_fixture_manifest_check_passes(self):  # check every CI run
        report = cc_warmup.check(WarmupManifest.load(FIXTURE))
        assert report.kernels_failed == 0
        assert report.residual_recompiles == 0
        assert report.ok

    def test_bad_kernel_entry_fails_soft(self):
        m = WarmupManifest([KernelEntry(
            "geomesa_tpu.engine.knn_scan", "no_such_kernel", [], {})])
        report = cc_warmup.replay(m)
        assert report.kernels_failed == 1
        assert not report.ok
        assert report.errors

    def test_query_entries_without_store_are_skipped(self):
        m = WarmupManifest([QueryEntry("count", "t", "INCLUDE")])
        report = cc_warmup.replay(m)
        assert report.queries_skipped == 1

    @pytest.mark.slow  # compiles the fixture kernels; the lint-gate
    def test_warmup_cli_check(self, capsys):  # smoke covers this in tier-1
        from geomesa_tpu.cli.main import main

        assert main(["warmup", "-m", FIXTURE, "--check"]) == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["residual_recompiles"] == 0

    def test_warmup_cli_check_refuses_unverifiable_queries(
            self, tmp_path, capsys):
        from geomesa_tpu.cli.main import main

        m = WarmupManifest([QueryEntry("count", "t", "INCLUDE")])
        path = str(tmp_path / "q.json")
        m.save(path)
        # query entries with no --catalog: the check proved nothing
        # about the serving path, so a green exit would lie
        assert main(["warmup", "-m", path, "--check"]) == 1


# -- the serving regression ------------------------------------------------


class TestServeWarmup:
    def test_track_compiles_config_installs_tracker(self, store):
        svc = QueryService(store, ServeConfig(track_compiles=True),
                           autostart=False)
        assert svc.tracker is not None
        # the engine jits are module globals: a second service SHARES
        # the installed tracker instead of silently counting nothing
        svc2 = QueryService(store, ServeConfig(track_compiles=True),
                            autostart=False)
        assert svc2.tracker is svc.tracker
        svc2.close()
        # refcounted: closing ONE of two live services must not disable
        # tracking for the survivor
        assert svc.tracker.is_installed()
        svc.close()
        assert not svc.tracker.is_installed()  # last release unwraps
        assert svc.tracker.total_recompiles() >= 0  # readable after close

    def test_acquire_shares_foreign_guard_tracker(self):
        """The gmtpu-guard composition: a tracker installed via bare
        guard_engine() must be SHARED by acquire, never shadowed by a
        dead tracker that wraps (and counts) nothing."""
        import geomesa_tpu.analysis.runtime as rt

        guard = rt.guard_engine()
        try:
            got, owner = rt.acquire_engine_tracker()
            assert got is guard and not owner
            # even with the active slot lost (an installer that predates
            # the slot protocol), the wrapper back-pointers recover it
            with rt._active_lock:
                rt._active_tracker = None
            got2, owner2 = rt.acquire_engine_tracker()
            assert got2 is guard and not owner2
        finally:
            guard.unwrap()
        # after unwrap the modules are bare again: a fresh acquire
        # installs for real
        fresh, owner3 = rt.acquire_engine_tracker()
        try:
            assert owner3 and fresh.is_installed()
        finally:
            rt.release_engine_tracker(fresh)

    def test_failed_constructor_does_not_leak_wrappers(self, store):
        from geomesa_tpu.analysis.runtime import (
            acquire_engine_tracker, release_engine_tracker)

        with pytest.raises(FileNotFoundError):
            QueryService(store, ServeConfig(
                warmup_manifest="no/such/manifest.json",
                track_compiles=True), autostart=False)
        # the failed constructor released the process-global wrappers:
        # a fresh tracker can install (owner=True) and actually wrap
        tracker, owner = acquire_engine_tracker()
        try:
            assert owner and tracker.is_installed()
        finally:
            release_engine_tracker(tracker)

    def test_record_roundtrip_warmup_zero_recompiles(self, store, tmp_path):
        """The whole contract in one lifecycle: a COLD workload records a
        manifest and its dispatches carry compile-stall attribution; the
        manifest survives save/load; after dropping every engine cache
        (fresh-process stand-in) a warmed service runs the same mixed
        workload twice with ZERO JitTracker recompiles and all-zero
        ServeEvent.compile_ms."""
        from geomesa_tpu.analysis.runtime import clear_engine_jit_caches

        # --- record phase (cold caches so the kernel tuples appear) ---
        if clear_engine_jit_caches() == 0:
            pytest.skip("this jax has no jit clear_cache")
        svc1 = QueryService(store, ServeConfig(max_wait_ms=20.0),
                            autostart=False)
        rec = svc1.record_warmup()
        svc1.start()
        audit0 = len(store.audit.snapshot())
        run_mixed_workload(svc1)
        svc1.close(drain=True)
        # the cold kNN dispatch compiled inline: the audit record names
        # the kernel and carries the stall — the p99 forensics contract
        cold = [e for e in store.audit.snapshot()[audit0:]
                if isinstance(e, ServeEvent)]
        stalled = [e for e in cold if e.compile_ms > 0]
        assert stalled, [(e.compiled, e.compile_ms) for e in cold]
        assert any("knn" in e.compiled for e in stalled)
        manifest = rec.manifest()
        assert manifest.kernel_entries, (
            "cold workload must record compiling kernel signatures")
        # the workload dispatched knn + count: both query shapes recorded
        ops = {e.op for e in manifest.query_entries}
        assert {"knn", "count"} <= ops
        knn_entry = next(e for e in manifest.query_entries
                         if e.op == "knn")
        assert knn_entry.q == 8  # padded pow2 stacked-query bucket

        # --- save -> load round trip ----------------------------------
        path = str(tmp_path / "serve_manifest.json")
        manifest.save(path)
        loaded = WarmupManifest.load(path)
        assert [e.to_json() for e in loaded.entries] == [
            e.to_json() for e in manifest.entries]

        # --- fresh "process": drop every engine dispatch cache --------
        assert clear_engine_jit_caches() > 0

        # --- warmup (+check), then the workload compiles NOTHING ------
        svc2 = QueryService(store, ServeConfig(max_wait_ms=20.0),
                            autostart=False)
        from geomesa_tpu.utils.metrics import metrics

        stalls0 = metrics.counters.get("compile.stalls", 0.0)
        report = svc2.warmup(path, check=True)
        # warmup compiles are ahead-of-time by definition: the inline
        # stall counter (what operators alert on) must not move
        assert metrics.counters.get("compile.stalls", 0.0) == stalls0
        assert report.kernels_failed == 0 and report.queries_failed == 0
        assert report.residual_recompiles == 0
        # warmup did the compiling (query-entry replay may warm a kernel
        # before its own kernel entry comes up — either way the tracker
        # saw the compiles happen inside warmup, not under traffic)
        base = svc2.tracker.total_recompiles()
        assert base >= 1
        svc2.start()
        audit1 = len(store.audit.snapshot())
        run_mixed_workload(svc2)
        run_mixed_workload(svc2)
        svc2.close(drain=True)
        assert svc2.tracker.total_recompiles() == base, (
            f"workload recompiled after warmup: {svc2.tracker.report()}")
        assert svc2.stats()["recompiles"] == base
        # and the audit trail agrees: no dispatch carried a kernel
        # compile stall (filter compiles were warmed by the query replay)
        events = [e for e in store.audit.snapshot()[audit1:]
                  if isinstance(e, ServeEvent)]
        assert events
        assert all(e.compile_ms == 0.0 for e in events), (
            [(e.compiled, e.compile_ms) for e in events])


# -- GT13 ------------------------------------------------------------------


class TestGT13:
    def _findings(self, src, relpath):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt13

        mod = ModInfo("/x.py", src, relpath=relpath)
        return list(gt13(mod, None))

    SRC = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + 1\n"
        "g = jax.jit(lambda x: x * 2)\n"
    )

    def test_flags_serve_and_plan_jits(self):
        found = self._findings(self.SRC, "geomesa_tpu/serve/fast.py")
        assert len(found) == 2
        assert all(f.rule == "GT13" for f in found)
        assert self._findings(self.SRC, "geomesa_tpu/plan/hot.py")

    def test_engine_and_elsewhere_out_of_scope(self):
        assert self._findings(self.SRC, "geomesa_tpu/engine/kernel.py") == []
        assert self._findings(self.SRC, "scripts/report.py") == []

    def test_from_import_alias_decorator(self):
        src = ("from jax import jit\n"
               "@jit\n"
               "def f(x):\n"
               "    return x\n")
        assert self._findings(src, "geomesa_tpu/serve/x.py")

    def test_registered_rule_and_shipped_tree_clean(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT13" in RULES and "GT13" in ALL_RULES


# -- lint gate smoke -------------------------------------------------------


@pytest.mark.slow
def test_lint_gate_runs_warmup_smoke():
    """The gate's text mode ends with the warmup smoke; json mode keeps
    stdout machine-pure (test_lint_gate.py parses it)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "lint_gate.py")],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "warmup smoke" in r.stderr
