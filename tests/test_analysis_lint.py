"""gmtpu-lint rule tests: for every rule GT01..GT06 a fixture module
with known violations (asserting exact rule codes and line numbers) and
a clean counterpart, the waiver channels, the two seeded advisor bugs
replayed against faithful pre-fix excerpts, and the self-lint check that
the shipped package is violation-free modulo committed waivers."""

import os
import subprocess
import sys
import textwrap

import pytest

from geomesa_tpu.analysis import lint_paths
from geomesa_tpu.analysis.linter import exit_code, render_json

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_src(tmp_path, source, name="mod.py", rules=None, **kw):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    # extra_ref_paths=[]: fixture universes are self-contained
    return lint_paths([str(tmp_path)], rules=rules,
                      extra_ref_paths=[], **kw)


def active(findings):
    return [f for f in findings if not f.waived]


def codes_lines(findings):
    return {(f.rule, f.line) for f in active(findings)}


# -- GT01 -------------------------------------------------------------------


class TestGT01Retrace:
    def test_loop_var_and_unhashable_static(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def kern(x, k):
                return x * k

            def run(xs):
                out = []
                for i in range(10):
                    out.append(kern(xs, k=i))
                bad = kern(xs, k=[1, 2])
                return out, bad
        """)
        assert ("GT01", 11) in codes_lines(fs)   # loop var into static
        assert ("GT01", 12) in codes_lines(fs)   # unhashable list literal
        assert all(f.rule == "GT01" for f in active(fs))

    def test_clean_constant_static_and_traced_loop_arg(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def kern(x, k):
                return x * k

            def run(xs):
                out = []
                for i in range(10):
                    out.append(kern(xs[i], k=4))
                return out
        """)
        assert not [f for f in active(fs) if f.rule == "GT01"]


# -- GT02 -------------------------------------------------------------------


class TestGT02HostTransfer:
    def test_host_ops_on_tracers(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax
            import numpy as np

            @jax.jit
            def bad(x):
                y = np.asarray(x)
                z = float(x)
                w = x.item()
                for v in x:
                    z = z + 1.0
                return y, z, w
        """)
        got = codes_lines(fs)
        assert ("GT02", 6) in got    # np.asarray on tracer
        assert ("GT02", 7) in got    # float() on tracer
        assert ("GT02", 8) in got    # .item() on tracer
        assert ("GT02", 9) in got    # host for-loop over tracer
        assert len([f for f in active(fs) if f.rule == "GT02"]) == 4

    def test_clean_jnp_and_static_args(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import functools
            import jax
            import jax.numpy as jnp
            import numpy as np

            @functools.partial(jax.jit, static_argnames=("n",))
            def good(x, n):
                consts = np.asarray([1.0, 2.0])
                acc = jnp.asarray(x)
                for i in range(n):
                    acc = acc + consts[0]
                return acc
        """)
        assert not [f for f in active(fs) if f.rule == "GT02"]


# -- GT03 -------------------------------------------------------------------


class TestGT03DtypeDrift:
    def test_f64_in_kernel_and_transitive_helper(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax
            import jax.numpy as jnp

            @jax.jit
            def kernel(x):
                y = x.astype(jnp.float64)
                z = x.astype("float64")
                return helper(y) + z

            def helper(v):
                return v + jnp.float64(1.0)
        """)
        got = codes_lines(fs)
        assert ("GT03", 6) in got    # jnp.float64 attr in kernel
        assert ("GT03", 7) in got    # 'float64' string dtype
        assert ("GT03", 11) in got   # transitively reachable helper

    def test_waiver_comment_suppresses(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax
            import jax.numpy as jnp

            @jax.jit
            def kernel(x):
                y = x.astype(jnp.float64)  # gt: f64-refine
                # gt: f64-refine
                z = x.astype(jnp.float64)
                return y + z
        """)
        gt03 = [f for f in fs if f.rule == "GT03"]
        assert gt03 and all(f.waived for f in gt03)
        assert not [f for f in active(fs) if f.rule == "GT03"]

    def test_f64_outside_kernel_paths_is_clean(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax
            import jax.numpy as jnp
            import numpy as np

            @jax.jit
            def kernel(x):
                return x + 1

            def host_refine(v):
                return np.asarray(v, np.float64)
        """)
        assert not [f for f in active(fs) if f.rule == "GT03"]


# -- GT04 -------------------------------------------------------------------


class TestGT04UnsyncedTiming:
    def test_unsynced_device_call_between_timestamps(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import time
            import jax

            @jax.jit
            def kern(x):
                return x + 1

            def timed(x):
                t0 = time.perf_counter()
                y = kern(x)
                dt = time.perf_counter() - t0
                return y, dt
        """)
        assert ("GT04", 11) in codes_lines(fs)

    def test_block_until_ready_syncs(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import time
            import jax

            @jax.jit
            def kern(x):
                return x + 1

            def timed(x):
                t0 = time.perf_counter()
                y = kern(x)
                y.block_until_ready()
                dt = time.perf_counter() - t0
                return y, dt
        """)
        assert not [f for f in active(fs) if f.rule == "GT04"]

    def test_np_asarray_counts_as_sync(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import time
            import jax
            import numpy as np

            @jax.jit
            def kern(x):
                return x + 1

            def timed(x):
                t0 = time.perf_counter()
                y = np.asarray(kern(x))
                dt = time.perf_counter() - t0
                return y, dt
        """)
        assert not [f for f in active(fs) if f.rule == "GT04"]


# -- GT05 -------------------------------------------------------------------


class TestGT05DeadJit:
    def test_dead_vs_live(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1

            @jax.jit
            def live_kernel(x):
                return x * 2

            def use(x):
                return live_kernel(x)
        """)
        got = codes_lines(fs)
        assert ("GT05", 4) in got
        assert not any(r == "GT05" and ln != 4 for r, ln in got)

    def test_cross_module_reference_keeps_alive(self, tmp_path):
        (tmp_path / "kern.py").write_text(textwrap.dedent("""\
            import jax

            @jax.jit
            def exported_kernel(x):
                return x + 1
        """))
        (tmp_path / "caller.py").write_text(textwrap.dedent("""\
            from kern import exported_kernel

            def go(x):
                return exported_kernel(x)
        """))
        fs = lint_paths([str(tmp_path)], extra_ref_paths=[])
        assert not [f for f in active(fs) if f.rule == "GT05"]


# -- GT06 -------------------------------------------------------------------


class TestGT06MaskPlumbing:
    def test_sibling_sites_disagree(self, tmp_path):
        fs = lint_src(tmp_path, """\
            def scatter(mask, batch, allowed, compiled, dev, cached):
                if cached:
                    bidx, bexact = compiled.band_corrections(dev, batch)
                    mask = mask.at[bidx].set(bexact & allowed[bidx])
                else:
                    bidx, bexact = compiled.band_corrections(dev, batch)
                    bexact = bexact & batch.valid[bidx]
                    mask = mask.at[bidx].set(bexact)
                return mask
        """)
        assert ("GT06", 3) in codes_lines(fs)
        assert len([f for f in active(fs) if f.rule == "GT06"]) == 1

    def test_consistent_siblings_clean(self, tmp_path):
        fs = lint_src(tmp_path, """\
            def scatter(mask, batch, allowed, compiled, dev, cached):
                if cached:
                    bidx, bexact = compiled.band_corrections(dev, batch)
                    bexact = bexact & batch.valid[bidx]
                    mask = mask.at[bidx].set(bexact & allowed[bidx])
                else:
                    bidx, bexact = compiled.band_corrections(dev, batch)
                    bexact = bexact & batch.valid[bidx]
                    mask = mask.at[bidx].set(bexact)
                return mask
        """)
        assert not [f for f in active(fs) if f.rule == "GT06"]


# -- seeded advisor bugs, replayed ------------------------------------------


class TestSeededBugs:
    """Faithful pre-fix excerpts of the two advisor findings this PR
    fixed: the linter must catch both (they are the seed true positives
    for GT05 and GT06)."""

    def test_gt05_catches_dead_cx_nb(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax
            import jax.numpy as jnp

            class CompiledFilter:
                def _ensure_band_jits(self):
                    if hasattr(self, "_cx_nb"):
                        return
                    band_fn = self._band_fn
                    mask_fn = self._fn

                    def _nb(params, dev, extra):
                        b = band_fn(params, dev)
                        if extra is not None:
                            b = b & extra
                        return jnp.sum(b, dtype=jnp.int32)

                    def _gather(params, dev, extra, k):
                        b = band_fn(params, dev)
                        mm = mask_fn(params, dev)
                        return b, mm

                    self._cx_nb = jax.jit(_nb, static_argnames=())
                    self._cx_gather = jax.jit(_gather, static_argnames=("k",))

                def _band_rows(self, params, dev, extra):
                    return jax.device_get(
                        self._cx_gather(params, dev, extra, k=64))
        """)
        gt05 = [f for f in active(fs) if f.rule == "GT05"]
        assert len(gt05) == 1
        assert gt05[0].line == 22
        assert "_cx_nb" in gt05[0].message

    def test_gt06_catches_planner_cache_branch(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax.numpy as jnp

            def knn(self, plan, sb, batch, dev, mask, allowed, use_cache):
                if use_cache:
                    if plan.compiled is not None and plan.compiled.has_band:
                        bidx, bexact = plan.compiled.band_corrections(dev, batch)
                        if len(bidx):
                            import jax as _jax

                            pid_at = _jax.device_get(sb.pids[jnp.asarray(bidx)])
                            mask = mask.at[jnp.asarray(bidx)].set(
                                jnp.asarray(bexact & allowed[pid_at]))
                else:
                    if plan.compiled is not None and plan.compiled.has_band:
                        bidx, bexact = plan.compiled.band_corrections(dev, batch)
                        if len(bidx):
                            if batch.valid is not None:
                                bexact = bexact & batch.valid[bidx]
                            mask = mask.at[jnp.asarray(bidx)].set(
                                jnp.asarray(bexact))
                return mask
        """)
        gt06 = [f for f in active(fs) if f.rule == "GT06"]
        assert len(gt06) == 1
        assert gt06[0].line == 6
        assert "band_corrections" in gt06[0].message


# -- waiver file ------------------------------------------------------------


class TestWaiverFile:
    def test_file_waiver_by_glob_rule_and_line(self, tmp_path):
        src = """\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """
        (tmp_path / "mod.py").write_text(textwrap.dedent(src))
        wf = tmp_path / "waivers.txt"
        wf.write_text("# seed waiver\nmod.py GT05 4\n")
        fs = lint_paths([str(tmp_path)], extra_ref_paths=[],
                        waiver_file=str(wf))
        gt05 = [f for f in fs if f.rule == "GT05"]
        assert gt05 and all(f.waived for f in gt05)
        assert not active(fs)

    def test_stale_line_pin_does_not_waive(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent("""\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """))
        wf = tmp_path / "waivers.txt"
        wf.write_text("mod.py GT05 99\n")
        fs = lint_paths([str(tmp_path)], extra_ref_paths=[],
                        waiver_file=str(wf))
        assert [f for f in active(fs) if f.rule == "GT05"]

    def test_malformed_waiver_file_raises(self, tmp_path):
        (tmp_path / "mod.py").write_text("x = 1\n")
        wf = tmp_path / "waivers.txt"
        wf.write_text("only-one-field\n")
        with pytest.raises(ValueError):
            lint_paths([str(tmp_path)], extra_ref_paths=[],
                       waiver_file=str(wf))


# -- output + exit codes ----------------------------------------------------


class TestOutputs:
    def test_exit_code_thresholds(self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """)
        assert exit_code(fs, "warn") == 1
        assert exit_code(fs, "error") == 0   # warns don't trip error
        assert exit_code(fs, "never") == 0

    def test_json_render_roundtrips(self, tmp_path):
        import json

        fs = lint_src(tmp_path, """\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """)
        doc = json.loads(render_json(fs))
        assert doc["active"] == len(active(fs))
        assert any(f["rule"] == "GT05" for f in doc["findings"])

    def test_cli_fails_on_violation_and_passes_clean(self, tmp_path):
        (tmp_path / "bad.py").write_text(textwrap.dedent("""\
            import jax

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """))
        r = subprocess.run(
            [sys.executable, "-m", "geomesa_tpu.analysis",
             str(tmp_path), "--fail-on", "warn"],
            capture_output=True, text=True, cwd=REPO_ROOT)
        assert r.returncode == 1
        assert "GT05" in r.stdout
        (tmp_path / "bad.py").write_text("x = 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "geomesa_tpu.analysis",
             str(tmp_path), "--fail-on", "warn"],
            capture_output=True, text=True, cwd=REPO_ROOT)
        assert r.returncode == 0

    def test_empty_scan_set_is_an_error_not_a_clean_pass(self, tmp_path):
        # default CWD-relative path from the wrong directory: zero
        # coverage must not read as a green gate
        r = subprocess.run(
            [sys.executable, "-m", "geomesa_tpu.analysis"],
            capture_output=True, text=True, cwd=str(tmp_path),
            env={**os.environ, "PYTHONPATH": REPO_ROOT})
        assert r.returncode == 2
        assert "no .py files" in r.stderr


class TestWaiverCascade:
    def test_directive_cascades_past_plain_comments_and_blanks(
            self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax

            # gt: waive GT05
            # explanation of why this entry point must stay

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """)
        gt05 = [f for f in fs if f.rule == "GT05"]
        assert gt05 and all(f.waived for f in gt05)
        assert not active(fs)

    def test_directive_does_not_leak_past_the_next_code_line(
            self, tmp_path):
        fs = lint_src(tmp_path, """\
            import jax

            # gt: waive GT05
            x = 1

            @jax.jit
            def dead_kernel(x):
                return x + 1
        """)
        assert [f for f in active(fs) if f.rule == "GT05"]


class TestTextOutput:
    def test_summary_discloses_waived_count(self, tmp_path):
        from geomesa_tpu.analysis.linter import render_text

        fs = lint_src(tmp_path, """\
            import jax

            @jax.jit
            def dead_kernel(x):  # gt: waive GT05
                return x + 1
        """)
        out = render_text(fs)
        assert "0 finding(s), 1 waived" in out
        assert "dead_kernel" not in out          # waived line hidden...
        assert "dead_kernel" in render_text(fs, show_waived=True)


# -- GT15 -------------------------------------------------------------------


class TestGT15TelemetryDiscipline:
    """Wall-clock durations + un-scoped spans in serve/engine/telemetry
    (docs/OBSERVABILITY.md): time.time() feeding a subtraction, and a
    tracer .span() opened outside a `with` block."""

    def _findings(self, src, relpath="geomesa_tpu/serve/mod.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt15

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt15(mod, None))

    DIRTY = """
        import time

        def latency():
            t0 = time.time()
            work()
            return time.time() - t0

        def direct():
            return time.time() - started
    """

    def test_duration_measurement_flagged(self):
        found = self._findings(self.DIRTY)
        assert found and all(f.rule == "GT15" for f in found)
        lines = {f.line for f in found}
        assert 5 in lines   # t0 = time.time() later subtracted
        assert 7 in lines   # time.time() as a direct Sub operand
        assert 10 in lines  # direct() body

    def test_clean_counterparts(self):
        clean = """
            import time

            def latency():
                t0 = time.perf_counter()
                work()
                return time.perf_counter() - t0

            def stamp(event):
                event.timestamp = time.time()   # a WHEN, not a duration

            def arithmetic():
                return a - b
        """
        assert self._findings(clean) == []

    def test_bare_time_import_flagged(self):
        src = """
            from time import time

            def f():
                t0 = time()
                return time() - t0
        """
        assert self._findings(src)

    def test_scope_is_path_limited(self):
        # plan/ keeps its perf_counter discipline via other means; the
        # wall-clock audit timestamps there are deliberate
        assert self._findings(self.DIRTY, "geomesa_tpu/plan/mod.py") == []
        assert self._findings(self.DIRTY, "scripts/report.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/engine/mod.py")
        assert self._findings(
            self.DIRTY, "geomesa_tpu/telemetry/mod.py")

    def test_function_bodies_do_not_leak_scopes(self):
        """A timestamp in one function must not pair with an unrelated
        subtraction in another (or at module level): each def is its
        own scope, including defs seeded directly from the module."""
        src = """
            import time

            def stamp(ev):
                t0 = time.time()
                ev.ts = t0

            def width(a, t0):
                return a - t0
        """
        assert self._findings(src) == []

    def test_span_without_with_flagged(self):
        src = """
            def bad(tracer):
                s = tracer.span("phase")
                work()

            def good(tracer):
                with tracer.span("phase"):
                    work()

            def also_good(tracer, stack):
                stack.enter_context(tracer.span("phase"))
        """
        found = self._findings(src)
        assert [(f.rule, f.line) for f in found] == [("GT15", 3)]

    def test_waiver_and_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT15" in RULES and "GT15" in ALL_RULES
        # inline waiver channel, through the full linter (the fixture
        # must live under a geomesa_tpu/serve/ path for GT15 scope)
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            sub = pathlib.Path(td) / "geomesa_tpu" / "serve"
            sub.mkdir(parents=True)
            (sub / "mod.py").write_text(textwrap.dedent("""
                import time

                def f():
                    # gt: waive GT15
                    t0 = time.time()
                    return time.time() - t0
            """))
            fs = lint_paths([td], rules=["GT15"], extra_ref_paths=[])
            flagged = active(fs)
            # the waived assignment is suppressed; the direct operand
            # on the return line still flags
            assert all(f.line != 6 for f in flagged)


# -- GT16 -------------------------------------------------------------------


class TestGT16PipelineStageBlocking:
    """Blocking calls inside serve/pipeline.py prepare/transfer/launch
    stages (docs/SERVING.md "Pipelined dispatch"): a sync there
    silently re-serializes the window overlap."""

    def _findings(self, src,
                  relpath="geomesa_tpu/serve/pipeline.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt16

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt16(mod, None))

    DIRTY = """
        import jax

        def _launch(self, win):
            fd = kernel(win.qx)
            fd.block_until_ready()

        def _transfer(self, win):
            return jax.device_get(win.staged)

        def submit(self, source, live):
            return live[0].future.result()
    """

    def test_blocking_in_stages_flagged(self):
        found = self._findings(self.DIRTY)
        assert sorted((f.rule, f.line) for f in found) == [
            ("GT16", 6), ("GT16", 9), ("GT16", 12)]

    def test_clean_counterparts(self):
        clean = """
            def _launch(self, win):
                win.launch = planner.knn_launch(win.qx)   # async

            def _prepare(self, win):
                win.running = [r for r in win.live
                               if r.future.set_running_or_notify_cancel()]

            def _sync(self, win):
                # the completer's job: blocking is CORRECT here
                win.launch.sync()
                win.fd.block_until_ready()

            def _complete_loop(self):
                fut.result()
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/serve/batcher.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/plan/planner.py") == []

    def test_waiver_and_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT16" in RULES and "GT16" in ALL_RULES
        assert "GT23" in RULES and "GT23" in ALL_RULES
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            sub = pathlib.Path(td) / "geomesa_tpu" / "serve"
            sub.mkdir(parents=True)
            (sub / "pipeline.py").write_text(textwrap.dedent("""
                def _launch(self, win):
                    # gt: waive GT16
                    win.fd.block_until_ready()
            """))
            fs = lint_paths([td], rules=["GT16"], extra_ref_paths=[])
            assert any(f.rule == "GT16" and f.waived for f in fs)
            assert not active([f for f in fs if f.rule == "GT16"])


# -- GT23 -------------------------------------------------------------------


class TestGT23RingFeedBlocking:
    """Blocking host syncs or naked per-window transfers inside the
    ring feed loop scope (docs/SERVING.md "Persistent serve loop"):
    per-window work is ONLY a stager slot write + one pre-compiled
    dispatch."""

    def _findings(self, src,
                  relpath="geomesa_tpu/serve/ringloop.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt23

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt23(mod, None))

    DIRTY = """
        import jax

        def try_feed(self, win):
            fd = self.handle.call(win.qx)
            fd.block_until_ready()

        def _slot_write(self, win):
            return jax.device_put(win.qx)

        def _feed_one(self, win):
            win.staged = to_device(win.batch)
            return win.future.result()
    """

    def test_blocking_and_transfers_in_feed_scope_flagged(self):
        found = self._findings(self.DIRTY)
        assert sorted((f.rule, f.line) for f in found) == [
            ("GT23", 6), ("GT23", 9), ("GT23", 12), ("GT23", 13)]

    def test_clean_counterparts(self):
        clean = """
            def try_feed(self, win):
                # the DESIGNATED slot write: the stager owns the
                # device_put (retry fabric + rotation contract)
                win.staged = self._stager.stage(key, win.qx, win.qy)
                win.launch = prog.launch(win.staged, win.qx, win.qy)
                return True

            def _arm(self, key, win):
                # arm scope is NOT feed scope: the one-time setup may
                # sync (calibration, fused-count precompute)
                return planner.ring_arm(win.lead.query)

            def _sync(self, win):
                win.launch.sync()
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/serve/pipeline.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/plan/planner.py") == []

    def test_waiver(self):
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            sub = pathlib.Path(td) / "geomesa_tpu" / "serve"
            sub.mkdir(parents=True)
            (sub / "ringloop.py").write_text(textwrap.dedent("""
                def try_feed(self, win):
                    # gt: waive GT23
                    win.fd.block_until_ready()
            """))
            fs = lint_paths([td], rules=["GT23"], extra_ref_paths=[])
            assert any(f.rule == "GT23" and f.waived for f in fs)
            assert not active([f for f in fs if f.rule == "GT23"])


# -- GT17 -------------------------------------------------------------------


class TestGT17ListenerBlocking:
    """Blocking calls inside subscription listener/callback bodies
    (docs/SERVING.md "Standing queries"): listeners run inside the
    Kafka fold with the store lock held — they must only buffer."""

    def _findings(self, src,
                  relpath="geomesa_tpu/subscribe/evaluator.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt17

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt17(mod, None))

    DIRTY = """
        import time

        def on_feature_event(event):
            with open("/tmp/log", "a") as f:
                f.write(str(event))

        def my_listener(event):
            return fut.result()

        def install(cache):
            def hook(event):
                time.sleep(0.1)
                dev = to_device(event.batch)
            cache.add_listener(hook)
    """

    def test_blocking_in_listeners_flagged(self):
        found = self._findings(self.DIRTY)
        assert sorted((f.rule, f.line) for f in found) == [
            ("GT17", 5), ("GT17", 9), ("GT17", 13), ("GT17", 14)]

    def test_clean_counterparts(self):
        clean = """
            def on_feature_event(event):
                with buf_lock:
                    buffer.append((event.kind, event.fid))

            def pump(type_name):
                # NOT a listener: the post-fold pump is where device
                # work belongs
                dev = to_device(batch)
                out = jax.device_get(handle.call(dev))

            def install(cache):
                def hook(event):
                    buffer.append(event)
                cache.add_listener(hook)
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/serve/service.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/engine/device.py") == []

    def test_kafka_scope_and_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT17" in RULES and "GT17" in ALL_RULES
        # kafka/ is in scope: cache listener helpers are covered
        found = self._findings(self.DIRTY,
                               "geomesa_tpu/kafka/cache.py")
        assert found

    def test_waiver(self):
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            sub = pathlib.Path(td) / "geomesa_tpu" / "subscribe"
            sub.mkdir(parents=True)
            (sub / "x.py").write_text(textwrap.dedent("""
                def on_event(e):
                    # gt: waive GT17
                    fut.result()
            """))
            fs = lint_paths([td], rules=["GT17"], extra_ref_paths=[])
            assert any(f.rule == "GT17" and f.waived for f in fs)
            assert not active([f for f in fs if f.rule == "GT17"])


class TestGT18PerDevicePlacement:
    """Per-device placement bypassing NamedSharding (docs/SERVING.md
    "Sharded serving"): serve//plan/ place data ONCE via NamedSharding
    over the mesh — per-chip device_put loops and jax.devices()[i]
    indexing break the recorded tile ownership."""

    def _findings(self, src, relpath="geomesa_tpu/serve/batcher.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt18

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt18(mod, None))

    DIRTY = """
        import jax

        def upload(batch):
            out = []
            for d in jax.devices():
                out.append(jax.device_put(batch.slice_for(d), d))
            return out

        def upload_alias(batch):
            devs = jax.devices()
            first = devs[0]
            return jax.device_put(batch, jax.devices()[1])

        def upload_to_device_loop(parts):
            for i, dev in enumerate(parts):
                to_device(parts[i], device=dev)
    """

    def test_loops_and_indexing_flagged(self):
        found = self._findings(self.DIRTY)
        lines = sorted((f.rule, f.line) for f in found)
        # loop device_put (7), alias subscript (12), direct
        # jax.devices()[1] subscript (13), dev-named loop (17)
        assert lines == [("GT18", 7), ("GT18", 12), ("GT18", 13),
                         ("GT18", 17)], lines

    def test_clean_counterparts(self):
        clean = """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def upload(batch, mesh):
                row = NamedSharding(mesh, P("shard"))
                return to_device(batch, device=row)

            def pin(mask, mesh):
                return jax.device_put(mask, NamedSharding(mesh, P()))

            def per_partition(parts):
                # a loop over PARTITIONS with one shared placement is
                # the single-chip residency path, not per-device
                for name in sorted(parts):
                    to_device(parts[name])
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/engine/device.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/parallel/mesh.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/plan/planner.py") != []

    def test_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT18" in RULES and "GT18" in ALL_RULES

    def test_waiver(self):
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            sub = pathlib.Path(td) / "geomesa_tpu" / "serve"
            sub.mkdir(parents=True)
            (sub / "x.py").write_text(textwrap.dedent("""
                import jax

                def pick():
                    # gt: waive GT18
                    return jax.devices()[0]
            """))
            fs = lint_paths([td], rules=["GT18"], extra_ref_paths=[])
            assert any(f.rule == "GT18" and f.waived for f in fs)
            assert not active([f for f in fs if f.rule == "GT18"])


class TestGT19MetricLabelConsistency:
    """One metric family, different label-key sets across call sites
    (docs/OBSERVABILITY.md): the registry keys series by name+labels,
    so a label-schema fork renders one Prometheus family with
    incompatible schemas — strict scrapers reject it, joins drop
    samples silently."""

    def _findings(self, src, relpath="geomesa_tpu/serve/service.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt19

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt19(mod, None))

    DIRTY = """
        from geomesa_tpu.utils.metrics import metrics

        def on_ok(kind, status, tenant):
            metrics.counter("serve.requests", kind=kind, status=status)
            metrics.counter("serve.requests", kind=kind, status=status)

        def on_shed(kind):
            metrics.counter("serve.requests", kind=kind)

        def scrape(depth):
            metrics.gauge("serve.queue.depth", depth, shard="0")

        def refresh(depth):
            metrics.gauge("serve.queue.depth", float(depth))
    """

    def test_minority_sites_flagged(self):
        found = self._findings(self.DIRTY)
        lines = sorted((f.rule, f.line) for f in found)
        # the {kind}-only counter site (9) forks serve.requests away
        # from the majority {kind,status} schema; the two queue.depth
        # gauge sites tie 1-1, so first-in-file-order ({shard}) wins
        # and the unlabeled site (15) is flagged
        assert lines == [("GT19", 9), ("GT19", 15)], lines
        assert "serve.requests" in found[0].message

    def test_clean_counterparts(self):
        clean = """
            from geomesa_tpu.utils.metrics import metrics

            def on_ok(kind, status):
                metrics.counter("serve.requests", kind=kind,
                                status=status)

            def on_shed(kind):
                # same schema everywhere = one family, no fork
                metrics.counter("serve.requests", kind=kind,
                                status="shed")

            def scrape(depth, name):
                metrics.gauge("serve.queue.depth", float(depth))
                # non-literal family names are not comparable: skipped
                metrics.gauge(f"fault.breaker.{name}", 1.0)
                # `inc` is the counter's amount param, not a label
                metrics.counter("serve.coalesced", inc=3)
                metrics.counter("serve.coalesced")
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/subscribe/registry.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/telemetry/slo.py") != []

    def test_cross_module_via_project(self, tmp_path):
        """The real gate path: two serve/ modules disagreeing on one
        family — the minority module's site is flagged."""
        import pathlib

        sub = pathlib.Path(tmp_path) / "geomesa_tpu" / "serve"
        sub.mkdir(parents=True)
        (sub / "a.py").write_text(textwrap.dedent("""
            def f(kind):
                metrics.counter("serve.widgets", kind=kind)
                metrics.counter("serve.widgets", kind=kind)
        """))
        (sub / "b.py").write_text(textwrap.dedent("""
            def g():
                metrics.counter("serve.widgets")
        """))
        fs = lint_paths([str(tmp_path)], rules=["GT19"],
                        extra_ref_paths=[])
        hits = {(f.path.replace("\\", "/"), f.line)
                for f in active(fs)}
        assert {(p.rsplit("geomesa_tpu/", 1)[-1], ln)
                for p, ln in hits} == {("serve/b.py", 3)}, hits

    def test_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT19" in RULES and "GT19" in ALL_RULES

    def test_waiver(self, tmp_path):
        import pathlib

        sub = pathlib.Path(tmp_path) / "geomesa_tpu" / "serve"
        sub.mkdir(parents=True)
        (sub / "x.py").write_text(textwrap.dedent("""
            def f(kind):
                metrics.counter("serve.widgets", kind=kind)
                metrics.counter("serve.widgets", kind=kind)

            def g():
                # gt: waive GT19
                metrics.counter("serve.widgets")
        """))
        fs = lint_paths([str(tmp_path)], rules=["GT19"],
                        extra_ref_paths=[])
        assert any(f.rule == "GT19" and f.waived for f in fs)
        assert not active([f for f in fs if f.rule == "GT19"])


class TestGT20SocketTimeouts:
    """Unbounded socket calls in fleet scope (docs/ANALYSIS.md GT20):
    a connect/recv with no timeout in the router blocks its reader
    thread forever behind one dead peer — the whole fleet's failover
    wedges with it."""

    def _findings(self, src, relpath="geomesa_tpu/fleet/router.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt20

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt20(mod, None))

    DIRTY = """
        import socket

        def dial(host, port):
            s = socket.socket()
            s.connect((host, port))
            return s.recv(4096)

        def dial2(host, port):
            return socket.create_connection((host, port))

        def serve(listener):
            conn, _ = listener.accept()
            return conn
    """

    def test_unbounded_calls_flagged(self):
        found = self._findings(self.DIRTY)
        lines = sorted((f.rule, f.line) for f in found)
        # connect(6), recv(7), create_connection(10), accept(13)
        assert lines == [("GT20", 6), ("GT20", 7),
                         ("GT20", 10), ("GT20", 13)], lines

    def test_clean_counterparts(self):
        clean = """
            import socket

            class Link:
                def __init__(self, host, port):
                    # cross-method: configured here, read elsewhere
                    self.sock = socket.create_connection(
                        (host, port), timeout=5.0)
                    self.sock.settimeout(0.25)

                def read(self):
                    return self.sock.recv(4096)

            def dial(host, port):
                s = socket.socket()
                s.settimeout(2.0)
                s.connect((host, port))
                return s.recv(64)

            def dial_positional(host, port):
                c = socket.create_connection((host, port), 5.0)
                return c

            def serve(listener):
                listener.settimeout(0.25)
                conn, _ = listener.accept()
                return conn
        """
        assert self._findings(clean) == []

    def test_setdefaulttimeout_exempts_module(self):
        src = """
            import socket

            socket.setdefaulttimeout(3.0)

            def dial(host, port):
                s = socket.socket()
                s.connect((host, port))
                return s.recv(64)
        """
        assert self._findings(src) == []

    def test_scope_is_path_limited(self):
        # the engine talks no sockets; other layers are out of scope
        assert self._findings(
            self.DIRTY, "geomesa_tpu/engine/device.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/serve/protocol.py") != []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/fleet/wire.py") != []

    def test_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT20" in RULES and "GT20" in ALL_RULES

    def test_waiver(self, tmp_path):
        import pathlib

        sub = pathlib.Path(tmp_path) / "geomesa_tpu" / "fleet"
        sub.mkdir(parents=True)
        (sub / "x.py").write_text(textwrap.dedent("""
            import socket

            def dial(host, port):
                s = socket.socket()
                # gt: waive GT20
                s.connect((host, port))
                return s
        """))
        fs = lint_paths([str(tmp_path)], rules=["GT20"],
                        extra_ref_paths=[])
        assert any(f.rule == "GT20" and f.waived for f in fs)
        assert not active([f for f in fs if f.rule == "GT20"])


# -- self-lint --------------------------------------------------------------


class TestSelfLint:
    def test_shipped_package_is_clean_modulo_waivers(self):
        fs = lint_paths([os.path.join(REPO_ROOT, "geomesa_tpu")])
        bad = active(fs)
        assert not bad, "\n".join(f.render() for f in bad)
        # the deliberate f64 stats accumulations ride on inline waivers,
        # so the waiver channel itself is exercised by the shipped tree

    def test_subset_scan_sees_callers_outside_the_subset(self):
        # GT05 liveness: linting one engine file alone must not flag
        # kernels whose call sites live elsewhere in the package
        fs = lint_paths(
            [os.path.join(REPO_ROOT, "geomesa_tpu", "engine", "stats.py")])
        gt05 = [f for f in active(fs) if f.rule == "GT05"]
        assert not gt05, "\n".join(f.render() for f in gt05)
        assert any(f.waived and f.rule == "GT03" for f in fs)


class TestGT21RawCqlCacheKeys:
    """Result-cache keys built from raw CQL text (docs/ANALYSIS.md
    GT21): equivalent filter spellings fork the key space — a dashboard
    fleet's repeated queries become a cache-miss storm instead of dict
    hits."""

    def _findings(self, src, relpath="geomesa_tpu/serve/service.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt21

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt21(mod, None))

    DIRTY = """
        from geomesa_tpu.approx.cache import result_key

        def peek(result_cache, req, version):
            key = result_key(req.kind, req.query.cql, version)
            return result_cache.get(key)

        def peek_wire(result_cache, doc, version):
            return result_cache.get(
                ("count", doc["typeName"], doc["cql"], version))

        def put_wire(result_cache, doc, out, version):
            result_cache.put(
                ("count", doc.get("cql"), version), out)
    """

    def test_raw_cql_keys_flagged(self):
        found = self._findings(self.DIRTY)
        # result_key(.cql) line 5, .get(doc["cql"]) line 9, .put(.get("cql")) line 13
        lines = sorted(f.line for f in found)
        assert len(found) == 3, found
        assert all(f.rule == "GT21" for f in found)
        assert lines == [5, 9, 13], lines

    def test_clean_counterparts(self):
        clean = """
            from geomesa_tpu.approx.cache import result_key
            from geomesa_tpu.cql import ast

            def peek(result_cache, req, version):
                # the Query OBJECT canonicalizes inside result_key
                key = result_key(req.kind, req.query, version)
                return result_cache.get(key)

            def peek_explicit(result_cache, query, version):
                cql = ast.to_cql(query.filter_ast)
                return result_cache.get(("count", cql, version))

            def unrelated(sub, filters):
                # .cql reads OUTSIDE cache-key construction never fire
                return filters[(sub.type_name, sub.cql)]
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/subscribe/evaluator.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/approx/cache.py") != []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/plan/planner.py") != []

    def test_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT21" in RULES and "GT21" in ALL_RULES

    def test_waiver(self, tmp_path):
        import pathlib

        sub = pathlib.Path(tmp_path) / "geomesa_tpu" / "serve"
        sub.mkdir(parents=True)
        (sub / "x.py").write_text(textwrap.dedent("""
            def peek(result_cache, doc, version):
                # gt: waive GT21
                return result_cache.get(("count", doc["cql"], version))
        """))
        fs = lint_paths([str(tmp_path)], rules=["GT21"],
                        extra_ref_paths=[])
        assert any(f.rule == "GT21" and f.waived for f in fs)
        assert not active([f for f in fs if f.rule == "GT21"])


class TestGT22PerRowWireEncode:
    """Per-row serialization in a wire-encode loop (docs/ANALYSIS.md
    GT22): the columnar wire removed the per-feature dict +
    per-subscriber json.dumps pattern from the hot path — this rule
    keeps it from creeping back into serve//subscribe/."""

    def _findings(self, src, relpath="geomesa_tpu/serve/protocol.py"):
        from geomesa_tpu.analysis.modinfo import ModInfo
        from geomesa_tpu.analysis.rules import gt22

        mod = ModInfo("/x.py", textwrap.dedent(src), relpath=relpath)
        return list(gt22(mod, None))

    DIRTY = """
        import json

        def flush(subs, frame, write):
            for sub in subs:
                write(json.dumps(frame) + "\\n")

        def rows_json(batch, names):
            out = []
            for i in range(len(batch)):
                out.append({n: batch[n][i] for n in names})
            return out

        def rows_comp(batch, names, n):
            return [{k: batch[k][i] for k in names} for i in range(n)]
    """

    def test_per_row_encode_flagged(self):
        found = self._findings(self.DIRTY)
        lines = sorted(f.line for f in found)
        assert len(found) == 3, found
        assert all(f.rule == "GT22" for f in found)
        # dumps-in-loop line 6, dictcomp-in-loop line 11, dictcomp-in-
        # listcomp line 15
        assert lines == [6, 11, 15], lines

    def test_clean_counterparts(self):
        clean = """
            import json

            def flush_once(subs, frame, offer):
                # ONE encode, the same buffer fans to every sink
                buf = (json.dumps(frame) + "\\n").encode()
                for sub in subs:
                    offer(sub, buf)

            def respond(doc, write):
                # one dumps per CALL is fine even when callers loop
                write(json.dumps(doc) + "\\n")

            def explicit_rows(batch, names, n):
                # the JSON fallback's explicit per-row dict build
                # (protocol._rows_json shape) stays legal: the rule
                # targets comprehension-built row dicts + in-loop dumps
                rows = []
                for i in range(n):
                    row = {}
                    for name in names:
                        row[name] = batch[name][i]
                    rows.append(row)
                return rows

            TOP = {k: v for k, v in [("a", 1)]}
        """
        assert self._findings(clean) == []

    def test_scope_is_path_limited(self):
        assert self._findings(
            self.DIRTY, "geomesa_tpu/plan/planner.py") == []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/subscribe/manager.py") != []
        assert self._findings(
            self.DIRTY, "geomesa_tpu/serve/columnar.py") != []

    def test_registration(self):
        from geomesa_tpu.analysis.model import RULES
        from geomesa_tpu.analysis.rules import ALL_RULES

        assert "GT22" in RULES and "GT22" in ALL_RULES

    def test_waiver(self, tmp_path):
        import pathlib

        sub = pathlib.Path(tmp_path) / "geomesa_tpu" / "serve"
        sub.mkdir(parents=True)
        (sub / "x.py").write_text(textwrap.dedent("""
            import json

            def flush(subs, frame, write):
                for sub in subs:
                    # gt: waive GT22
                    write(json.dumps(frame) + "\\n")
        """))
        fs = lint_paths([str(tmp_path)], rules=["GT22"],
                        extra_ref_paths=[])
        assert any(f.rule == "GT22" and f.waived for f in fs)
        assert not active([f for f in fs if f.rule == "GT22"])
