"""SPMD pass tests (gmtpu-lint GT24..GT27) + the incremental engine.

Per rule: a dirty fixture (exact rule codes + line numbers), a clean
twin for every precision guard (interprocedural binding, parameter
axes, gate recognition, path scoping), and the waiver channel. The
pre-fix shapes of every true positive this pass found on the shipped
tree — the ungated sidecar/manifest/metadata writes, the env-switched
x64 branch, the unbound/misarity drafts of the multi-host uniformity
probe — are replayed as faithful excerpts so a regression that stops a
rule matching its real catch fails here, not in production review.

Fixtures are miniature repo skeletons (pyproject.toml +
geomesa_tpu/<subsystem>/mod.py): GT25's multi-process reachability and
GT27's subsystem scoping key on project-relative paths, so a bare
tmp-file fixture would silently skip both rules.

Also here: the incremental lint engine's contract — warm and partial
runs byte-identical to a cold scan (render_json equality), warm replay
with zero re-analysis, corrupted-cache fallback — and the single-process
runtime behavior of the new parallel.distributed helpers
(is_coordinator / process_suffix / runtime_fingerprint /
assert_uniform_runtime).
"""

import json
import os
import textwrap

import pytest

from geomesa_tpu.analysis.incremental import (
    DEFAULT_CACHE_FILENAME, lint_paths_incremental)
from geomesa_tpu.analysis.linter import exit_code, lint_paths, render_json

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPMD = ["GT24", "GT25", "GT26", "GT27"]


def write_tree(tmp_path, files):
    """Materialize a miniature repo: pyproject.toml marks the root so
    fixture modules get project-relative paths (geomesa_tpu/...)."""
    (tmp_path / "pyproject.toml").write_text(
        "[project]\nname = \"spmd-fixture\"\n")
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def lint_tree(tmp_path, files, rules=SPMD, **kw):
    write_tree(tmp_path, files)
    return lint_paths([str(tmp_path / "geomesa_tpu")], rules=rules,
                      extra_ref_paths=[], **kw)


def active(findings):
    return [f for f in findings if not f.waived]


def codes_lines(findings):
    return {(f.rule, f.line) for f in active(findings)}


# -- GT24: unbound collective axis ------------------------------------------


class TestGT24UnboundCollective:
    def test_unbound_helper_and_module_level(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import jax
            from jax import lax


            def merge(x):
                return lax.psum(x, "shard")


            TOTAL = lax.psum(1, "shard")
        """})
        got = codes_lines(fs)
        assert ("GT24", 6) in got    # helper: axis bound nowhere
        assert ("GT24", 9) in got    # module level: nothing CAN bind it
        assert all(f.rule == "GT24" for f in active(fs))

    def test_clean_decorator_wrap_binds(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import functools

            import jax
            import numpy as np
            from jax import lax
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, PartitionSpec as P

            AXIS = "shard"


            def mesh():
                return Mesh(np.array(jax.devices()), (AXIS,))


            @functools.partial(shard_map, mesh=mesh(), in_specs=(P(AXIS),),
                               out_specs=P(AXIS), check_vma=False)
            def merge(x):
                return lax.psum(x, AXIS)
        """})
        assert not active(fs)

    def test_clean_interprocedural_caller_binding(self, tmp_path):
        # the _shard_merge_topk shape: the collective lives in a helper
        # whose ONLY callers are shard_map-wrapped — bound through the
        # calling context, not lexically
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import functools

            import jax
            import numpy as np
            from jax import lax
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, PartitionSpec as P


            def _merge(x):
                return lax.pmax(x, "shard")


            def run(mesh, v):
                @functools.partial(shard_map, mesh=mesh,
                                   in_specs=(P("shard"),),
                                   out_specs=P())
                def kern(s):
                    return _merge(s)

                return kern(v)
        """})
        assert not active(fs)

    def test_clean_parameter_axis_skipped(self, tmp_path):
        # axis-generic helpers (pcast-wrapper shape) stay silent
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            from jax import lax


            def pcast(x, axis_name):
                return lax.all_gather(x, axis_name)
        """})
        assert not active(fs)

    def test_dirty_caller_does_not_bind(self, tmp_path):
        # a caller exists but nothing in the chain ever binds the axis
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            from jax import lax


            def _merge(x):
                return lax.psum(x, "shard")


            def run(v):
                return _merge(v)
        """})
        assert ("GT24", 5) in codes_lines(fs)


# -- GT25: process-divergent control flow -----------------------------------


class TestGT25ProcessDivergence:
    def test_dirty_process_branch_on_entry_path(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/launch.py": """\
            import jax


            def boot():
                if jax.process_index() == 0:
                    jax.config.update("jax_enable_x64", True)
        """})
        assert ("GT25", 5) in codes_lines(fs)

    def test_dirty_env_branch_divergent_collectives(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/serve/app.py": """\
            import os

            import jax
            from jax import lax


            def step(x):
                if os.environ.get("FAST_PATH") == "1":
                    return lax.psum(x, "shard")
                return lax.pmean(x, "shard")
        """})
        assert any(f.rule == "GT25" and f.line == 8 for f in active(fs))

    def test_clean_identical_arms(self, tmp_path):
        # divergence is about COLLECTIVE-RELEVANT effects, not any
        # branch: logging per process rank is fine
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/launch.py": """\
            import jax
            from jax import lax


            def step(x):
                if jax.process_index() == 0:
                    print("coordinator")
                return lax.psum(x, "shard")
        """}, rules=["GT25"])
        assert not active(fs)

    def test_clean_unreachable_module_scope_twin(self, tmp_path):
        # byte-identical branch in a module no multi-process entry
        # imports: out of scope, no finding
        fs = lint_tree(tmp_path, {"geomesa_tpu/cql/helpers.py": """\
            import jax


            def boot():
                if jax.process_index() == 0:
                    jax.config.update("jax_enable_x64", True)
        """})
        assert not active(fs)

    def test_waiver_twin(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/launch.py": """\
            import jax


            def boot():
                # gt: waive GT25
                if jax.process_index() == 0:
                    jax.config.update("jax_enable_x64", True)
        """})
        assert not active(fs)
        assert any(f.rule == "GT25" and f.waived for f in fs)


# -- GT26: sharding-spec drift ----------------------------------------------


class TestGT26SpecDrift:
    def test_dirty_ghost_axis_and_arity(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import jax
            import numpy as np
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


            def kernel(a):
                return a


            def run():
                mesh = Mesh(np.array(jax.devices()), ("data",))
                spec = NamedSharding(mesh, P("ghost"))
                wrapped = shard_map(kernel, mesh=mesh,
                                    in_specs=(P("data"), P("data")),
                                    out_specs=P("data"))
                return wrapped, spec
        """})
        got = codes_lines(fs)
        assert ("GT26", 13) in got    # ghost not bound by ("data",)
        assert ("GT26", 14) in got    # 2 in_specs, kernel takes 1
        assert all(f.rule == "GT26" for f in active(fs))

    def test_clean_matching_axes_and_arity(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import jax
            import numpy as np
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


            def kernel(a, b):
                return a + b


            def run():
                mesh = Mesh(np.array(jax.devices()), ("data",))
                spec = NamedSharding(mesh, P("data"))
                wrapped = shard_map(kernel, mesh=mesh,
                                    in_specs=(P("data"), P("data")),
                                    out_specs=P("data"))
                return wrapped, spec
        """})
        assert not active(fs)

    def test_clean_vararg_mapped_fn_skipped(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            import jax
            import numpy as np
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, PartitionSpec as P


            def kernel(*args):
                return args


            def run():
                mesh = Mesh(np.array(jax.devices()), ("data",))
                return shard_map(kernel, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=P("data"))
        """})
        assert not active(fs)

    def test_clean_unresolvable_mesh_unknown_axis(self, tmp_path):
        # mesh arrives as a parameter AND no project mesh exists: the
        # axis universe is empty, so the rule stays conservative
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/ops.py": """\
            from jax.sharding import NamedSharding, PartitionSpec as P


            def place(mesh):
                return NamedSharding(mesh, P("anything"))
        """})
        assert not active(fs)


# -- GT27: ungated process-local side effects -------------------------------


class TestGT27UngatedSideEffects:
    def test_dirty_persist_and_bind(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "geomesa_tpu/store/meta.py": """\
                import os


                def save(path, doc):
                    tmp = path + ".tmp"
                    with open(tmp, "w") as fh:
                        fh.write(doc)
                    os.replace(tmp, path)
            """,
            "geomesa_tpu/serve/http.py": """\
                from http.server import ThreadingHTTPServer


                def start(handler, port):
                    return ThreadingHTTPServer(("0.0.0.0", port), handler)
            """,
        })
        got = codes_lines(fs)
        assert ("GT27", 8) in got    # os.replace in store/
        assert ("GT27", 5) in got    # port bind in serve/
        assert all(f.rule == "GT27" for f in active(fs))

    def test_clean_entry_gate(self, tmp_path):
        # the shape every fixed site in this repo uses: coordinator
        # early-return at function entry
        fs = lint_tree(tmp_path, {"geomesa_tpu/store/meta.py": """\
            import os

            from geomesa_tpu.parallel.distributed import is_coordinator


            def save(path, doc):
                if not is_coordinator():
                    return
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                os.replace(tmp, path)
        """})
        assert not active(fs)

    def test_clean_inline_if_gate(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/store/meta.py": """\
            import os

            import jax


            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                if jax.process_index() == 0:
                    os.replace(tmp, path)
        """})
        assert not active(fs)

    def test_clean_path_scope_twin(self, tmp_path):
        # identical persist outside the multi-host subsystems (a CLI
        # report writer, say) is out of scope
        fs = lint_tree(tmp_path, {"geomesa_tpu/cql/report.py": """\
            import os


            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                os.replace(tmp, path)
        """})
        assert not active(fs)

    def test_clean_caller_gated_helper(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/store/meta.py": """\
            import os

            from geomesa_tpu.parallel.distributed import is_coordinator


            def _persist(tmp, path):
                os.replace(tmp, path)


            def save(path, doc):
                if not is_coordinator():
                    return
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                _persist(tmp, path)
        """})
        assert not active(fs)

    def test_waiver_twin(self, tmp_path):
        fs = lint_tree(tmp_path, {"geomesa_tpu/store/meta.py": """\
            import os


            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                # gt: waive GT27
                os.replace(tmp, path)
        """})
        assert not active(fs)
        assert any(f.rule == "GT27" and f.waived for f in fs)


# -- pre-fix replays: the true positives this pass caught --------------------


class TestPreFixReplays:
    """Faithful excerpts of the shipped code BEFORE this PR's fixes.
    Each must still fire; its committed post-fix twin is covered by the
    self-lint test below (the real tree is the clean fixture)."""

    def test_sketch_sidecar_prefix(self, tmp_path):
        # approx/sketches.py save_sidecar before the coordinator gate
        fs = lint_tree(tmp_path, {"geomesa_tpu/approx/sketches.py": """\
            import json
            import os


            def save_sidecar(path, doc):
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as fh:
                    json.dump(doc, fh)
                os.replace(tmp, path)
                return path
        """})
        assert ("GT27", 9) in codes_lines(fs)

    def test_warmup_manifest_prefix(self, tmp_path):
        # compilecache/manifest.py WarmupManifest.save before the gate:
        # the persist lives in a nested retry closure — the rule must
        # see through it
        fs = lint_tree(tmp_path, {"geomesa_tpu/compilecache/manifest.py": """\
            import json
            import os


            class WarmupManifest:
                def save(self, path):
                    def attempt():
                        tmp = f"{path}.tmp.{os.getpid()}"
                        with open(tmp, "w") as fh:
                            json.dump({}, fh)
                        os.replace(tmp, path)

                    attempt()
        """})
        assert ("GT27", 11) in codes_lines(fs)

    def test_store_metadata_prefix(self, tmp_path):
        # store/fs.py _save_metadata before the gate
        fs = lint_tree(tmp_path, {"geomesa_tpu/store/fs.py": """\
            import json
            import os


            def _save_metadata(root, doc):
                path = os.path.join(root, "metadata.json")
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(doc, fh)
                os.replace(tmp, path)
        """})
        assert ("GT27", 10) in codes_lines(fs)

    def test_x64_env_branch_prefix(self, tmp_path):
        # engine/device.py's env-switched x64 config before the waiver +
        # runtime fingerprint check: reachable from the serve layer, one
        # arm reshapes every compiled program
        fs = lint_tree(tmp_path, {
            "geomesa_tpu/serve/service.py": """\
                from geomesa_tpu.engine import device
            """,
            "geomesa_tpu/engine/device.py": """\
                import os

                import jax

                if os.environ.get("GEOMESA_TPU_ENABLE_X64", "1") == "1":
                    jax.config.update("jax_enable_x64", True)
            """,
        })
        assert any(f.rule == "GT25" and f.path.endswith("device.py")
                   for f in active(fs))

    def test_uniform_runtime_probe_draft_unbound(self, tmp_path):
        # the first draft of assert_uniform_runtime ran its pmin/pmax
        # in a bare helper — no wrap, axis bound nowhere (GT24 caught
        # it during this PR's multi-host helper work)
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/distributed.py": """\
            import jax
            from jax import lax

            AXIS = "shard"


            def _minmax(v):
                return (lax.pmin(v, AXIS), lax.pmax(v, AXIS))


            def assert_uniform_runtime(vals):
                lo, hi = _minmax(vals)
                if int(lo) != int(hi):
                    raise RuntimeError("divergent runtime")
        """})
        got = {(f.rule, f.line) for f in active(fs)}
        assert ("GT24", 8) in got

    def test_uniform_runtime_probe_draft_arity(self, tmp_path):
        # the second draft passed two in_specs to a one-argument mapped
        # function (GT26 caught the copy-paste from a two-input kernel)
        fs = lint_tree(tmp_path, {"geomesa_tpu/parallel/distributed.py": """\
            import functools

            import jax
            import numpy as np
            from jax import lax
            from jax.experimental.shard_map import shard_map
            from jax.sharding import Mesh, PartitionSpec as P

            AXIS = "shard"


            def assert_uniform_runtime(vals):
                mesh = Mesh(np.array(jax.devices()), (AXIS,))

                @functools.partial(shard_map, mesh=mesh,
                                   in_specs=(P(AXIS), P(AXIS)),
                                   out_specs=(P(), P()))
                def minmax(v):
                    return (lax.pmin(v[0], AXIS), lax.pmax(v[0], AXIS))

                return minmax(vals)
        """})
        assert any(f.rule == "GT26" for f in active(fs))


# -- self-lint: the shipped tree is the clean fixture ------------------------


class TestSelfLint:
    def test_shipped_tree_spmd_clean(self):
        fs = lint_paths([os.path.join(REPO_ROOT, "geomesa_tpu")],
                        rules=SPMD)
        assert not active(fs), [f.render() for f in active(fs)]
        # the justified waivers are present, not silently lost
        assert any(f.rule == "GT25" and f.waived for f in fs)
        assert any(f.rule == "GT27" and f.waived for f in fs)
        assert exit_code(fs, "warn") == 0


# -- incremental engine ------------------------------------------------------


class TestIncremental:
    FILES = {
        "geomesa_tpu/parallel/ops.py": """\
            import jax
            from jax import lax


            def merge(x):
                return lax.psum(x, "shard")
        """,
        "geomesa_tpu/store/meta.py": """\
            import os


            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    fh.write(doc)
                os.replace(tmp, path)
        """,
        "geomesa_tpu/cql/util.py": """\
            def ident(x):
                return x
        """,
    }

    def test_warm_and_partial_byte_identical(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        scan = [str(tmp_path / "geomesa_tpu")]
        cold = lint_paths(scan)
        inc1 = lint_paths_incremental(scan)   # populates the cache
        assert (tmp_path / DEFAULT_CACHE_FILENAME).exists()
        inc2 = lint_paths_incremental(scan)   # warm replay
        assert render_json(cold) == render_json(inc1) == render_json(inc2)

        # edit: a new violation must surface through the cache, and the
        # rest of the replayed findings must still match a cold scan
        mod = tmp_path / "geomesa_tpu" / "cql" / "util.py"
        mod.write_text(textwrap.dedent("""\
            import jax


            @jax.jit
            def bad(x):
                return float(x)
        """))
        inc3 = lint_paths_incremental(scan)
        cold3 = lint_paths(scan)
        assert render_json(cold3) == render_json(inc3)
        assert any(f.path.endswith("util.py") for f in active(inc3))
        # and the pre-edit findings are still there (replayed, not lost)
        assert codes_lines(inc1) <= codes_lines(inc3)

    def test_warm_replay_does_not_reparse(self, tmp_path, monkeypatch):
        write_tree(tmp_path, self.FILES)
        scan = [str(tmp_path / "geomesa_tpu")]
        lint_paths_incremental(scan)
        import geomesa_tpu.analysis.incremental as inc_mod

        def boom(*a, **k):
            raise AssertionError("warm replay must not build a project")

        monkeypatch.setattr(inc_mod, "build_project", boom)
        warm = lint_paths_incremental(scan)
        assert warm  # the fixture has findings and they replayed

    def test_corrupted_cache_falls_back_cold(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        scan = [str(tmp_path / "geomesa_tpu")]
        cold = lint_paths(scan)
        (tmp_path / DEFAULT_CACHE_FILENAME).write_text("{not json")
        inc = lint_paths_incremental(scan)
        assert render_json(cold) == render_json(inc)
        # and the rewrite repaired the cache: next run replays warm
        doc = json.loads((tmp_path / DEFAULT_CACHE_FILENAME).read_text())
        assert doc["findings"]

    def test_waiver_file_change_invalidates(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        scan = [str(tmp_path / "geomesa_tpu")]
        before = lint_paths_incremental(scan)
        assert any(f.rule == "GT24" and not f.waived for f in before)
        (tmp_path / ".gmtpu-waivers").write_text(
            "# fixture waiver\ngeomesa_tpu/parallel/ops.py GT24\n")
        after = lint_paths_incremental(scan)
        cold = lint_paths(scan)
        assert render_json(cold) == render_json(after)
        assert not [f for f in active(after) if f.rule == "GT24"]


# -- runtime behavior of the new distributed helpers -------------------------


class TestDistributedHelpers:
    def test_is_coordinator_single_process(self):
        from geomesa_tpu.parallel import is_coordinator

        assert is_coordinator() is True

    def test_process_suffix_single_process(self):
        from geomesa_tpu.parallel.distributed import process_suffix

        assert process_suffix() == ""

    def test_runtime_fingerprint_deterministic(self):
        from geomesa_tpu.parallel.distributed import runtime_fingerprint

        a, b = runtime_fingerprint(), runtime_fingerprint()
        assert a == b
        assert 0 <= a < 2 ** 31

    def test_assert_uniform_runtime_single_process(self):
        # one process is trivially uniform; the probe must be a cheap
        # no-op-equivalent, not a crash, on CPU CI
        from geomesa_tpu.parallel.distributed import assert_uniform_runtime

        assert_uniform_runtime()

    def test_flight_dump_path_unsuffixed_single_process(self, tmp_path):
        from geomesa_tpu.telemetry.recorder import FlightRecorder

        r = FlightRecorder()
        r.note_event("unit")
        out = r.dump(path=str(tmp_path / "dump.json"))
        assert out == str(tmp_path / "dump.json")
        assert json.load(open(out))["event_count"] == 1
