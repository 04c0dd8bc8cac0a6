"""gmtpu-lint orchestration: scan -> index -> rules -> report.

`lint_paths` is the programmatic entry point (the CLI, the CI gate, and
the tests all go through it). The scan set is what gets linted; the
*reference universe* for cross-module questions (GT05 liveness, GT04/GT01
jit-name resolution) additionally pulls in every other .py file under the
repo root (pyproject.toml discoverable above the scan path) — a jitted
kernel linted in isolation whose callers live in `plan/`, `tests/` or
`scripts/` is an API, not a corpse.
"""

from __future__ import annotations

import ast
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Set

from geomesa_tpu.analysis.model import RULES, SEVERITIES, Finding
from geomesa_tpu.analysis.modinfo import JitDef, ModInfo
from geomesa_tpu.analysis.rules import ALL_RULES
from geomesa_tpu.analysis.waivers import (
    DEFAULT_WAIVER_FILENAME, apply_file_waivers, check_rule_code,
    load_waiver_file)


class Project:
    """The cross-module context handed to every rule."""

    def __init__(self, modules: List[ModInfo], ref_modules: List[ModInfo]):
        self.modules = modules
        self.ref_modules = ref_modules
        self.jit_by_name: Dict[str, JitDef] = {}
        for m in modules:
            for jd in m.jit_defs:
                self.jit_by_name.setdefault(jd.name, jd)
        names: Set[str] = set(self.jit_by_name)
        for m in modules:
            m._gt_project_jit_names = names  # type: ignore[attr-defined]
        self._refs: Optional[Dict[str, int]] = None

    # -- GT05 reference universe ------------------------------------------

    def reference_count(self, name: str) -> int:
        if self._refs is None:
            self._refs = self._count_references()
        return self._refs.get(name, 0)

    def _count_references(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        wanted = set(self.jit_by_name)
        for m in self.modules + self.ref_modules:
            for n, c in module_reference_counts(m, wanted).items():
                counts[n] = counts.get(n, 0) + c
        return counts


def module_reference_counts(m: ModInfo, wanted: Set[str]) -> Dict[str, int]:
    """Per-module reference counts for `wanted` names (the GT05 liveness
    universe). A module-scoped function (not a Project method) so the
    incremental engine can cache one count dict per file and rebuild the
    project total from cache for unchanged files."""
    counts: Dict[str, int] = {}

    def bump(n: str) -> None:
        if n in wanted:
            counts[n] = counts.get(n, 0) + 1

    for node in ast.walk(m.tree):
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Load):
            bump(node.id)
        elif isinstance(node, ast.Attribute) and isinstance(
                node.ctx, ast.Load):
            bump(node.attr)
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                bump(a.name)
        elif (isinstance(node, ast.Assign)
              and len(node.targets) == 1
              and isinstance(node.targets[0], ast.Name)
              and node.targets[0].id == "__all__"
              and isinstance(node.value, (ast.List, ast.Tuple))):
            for e in node.value.elts:
                if isinstance(e, ast.Constant) and isinstance(
                        e.value, str):
                    bump(e.value)
    # a jitted def's own wrapping (`x = jax.jit(_fn)`) loads `_fn`,
    # not `x`; decorated defs are not Name loads — no self-counts to
    # subtract for the bound names themselves
    return counts


def _iter_py_files(path: str) -> Iterable[str]:
    if os.path.isfile(path):
        if path.endswith(".py"):
            yield path
        return
    for root, dirs, files in os.walk(path):
        dirs[:] = sorted(d for d in dirs
                         if d not in ("__pycache__", ".git"))
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(root, f)


def find_repo_root(start: str) -> Optional[str]:
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        if os.path.exists(os.path.join(cur, "pyproject.toml")):
            return cur
        nxt = os.path.dirname(cur)
        if nxt == cur:
            return None
        cur = nxt


def _load_module(path: str, base: Optional[str]) -> Optional[ModInfo]:
    try:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        rel = os.path.relpath(path, base) if base else path
        return ModInfo(path, src, relpath=rel.replace(os.sep, "/"))
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None


def build_project(paths: List[str],
                  extra_ref_paths: Optional[List[str]] = None,
                  repo_root: Optional[str] = None) -> Project:
    if repo_root is None:
        repo_root = find_repo_root(paths[0]) if paths else None
    modules: List[ModInfo] = []
    seen: Set[str] = set()
    for p in paths:
        for f in _iter_py_files(p):
            af = os.path.abspath(f)
            if af in seen:
                continue
            seen.add(af)
            m = _load_module(f, repo_root)
            if m is not None:
                modules.append(m)
    ref_paths: List[str] = list(extra_ref_paths or ())
    if repo_root and extra_ref_paths is None:
        # the rest of the repo (scan set deduped below via `seen`):
        # subset scans must still see callers outside the subset
        ref_paths.append(repo_root)
    refs: List[ModInfo] = []
    for p in ref_paths:
        for f in _iter_py_files(p):
            af = os.path.abspath(f)
            if af in seen:
                continue
            seen.add(af)
            m = _load_module(f, repo_root)
            if m is not None:
                refs.append(m)
    return Project(modules, refs)


def lint_paths(paths: List[str],
               rules: Optional[List[str]] = None,
               waiver_file: Optional[str] = None,
               extra_ref_paths: Optional[List[str]] = None,
               include_waived: bool = True) -> List[Finding]:
    """Run the linter; returns findings sorted by (path, line, rule).
    Waived findings are included with .waived=True (the gate ignores
    them; --format json surfaces them for audit)."""
    project = build_project(paths, extra_ref_paths=extra_ref_paths)
    if not project.modules:
        # a CWD-relative default path from the wrong directory must not
        # read as a clean pass: zero coverage is an error, not a green
        raise FileNotFoundError(
            f"gmtpu-lint: no .py files found under {paths!r}")
    selected = rules or sorted(ALL_RULES)
    findings: List[Finding] = []
    for mod in project.modules:
        _check_inline_waiver_tokens(mod)
        for code in selected:
            for f in ALL_RULES[code](mod, project):
                if mod.is_waived(f.rule, f.line):
                    f.waived = True
                    f.waived_by = f"inline:{mod.relpath}:{f.line}"
                findings.append(f)
    finalize_findings(findings, paths, waiver_file)
    if not include_waived:
        findings = [f for f in findings if not f.waived]
    return findings


def resolve_waiver_file(paths: List[str],
                        waiver_file: Optional[str]) -> Optional[str]:
    """An explicit waiver file wins; otherwise the repo-root default, if
    present."""
    if waiver_file is None:
        root = find_repo_root(paths[0]) if paths else None
        cand = os.path.join(root, DEFAULT_WAIVER_FILENAME) if root else None
        if cand and os.path.exists(cand):
            waiver_file = cand
    return waiver_file


def finalize_findings(findings: List[Finding], paths: List[str],
                      waiver_file: Optional[str]) -> List[Finding]:
    """The post-merge tail of the pipeline: file waivers, severity
    overrides, canonical sort. In-place; shared by the cold scan and the
    incremental engine so both paths render byte-identically."""
    entries, severities = [], {}
    waiver_file = resolve_waiver_file(paths, waiver_file)
    if waiver_file:
        entries, severities = load_waiver_file(waiver_file)
    apply_file_waivers(findings, entries)
    for f in findings:
        f.severity = severities.get(
            f.rule, RULES[f.rule].severity if f.rule in RULES else f.severity)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def _check_inline_waiver_tokens(mod: ModInfo) -> None:
    """A `# gt: waive GT99` typo must error, not silently never match."""
    for line, tokens in sorted(mod.waivers.items()):
        for tok in tokens:
            if tok.startswith("waive "):
                code = tok.split(None, 1)[1].strip()
                if code == "all":
                    continue
                check_rule_code(code, f"{mod.relpath}:{line}")


def render_text(findings: List[Finding], show_waived: bool = False) -> str:
    """Pass the FULL findings list (waived included): the summary line
    discloses the waived count either way; `show_waived` only controls
    whether the waived findings' own lines print."""
    active = [f for f in findings if not f.waived]
    waived = len(findings) - len(active)
    lines = [f.render() for f in (findings if show_waived else active)]
    lines.append(
        f"gmtpu-lint: {len(active)} finding(s)"
        + (f", {waived} waived" if waived else ""))
    return "\n".join(lines)


def render_json(findings: List[Finding]) -> str:
    return json.dumps({
        "findings": [f.to_dict() for f in findings],
        "active": sum(1 for f in findings if not f.waived),
        "waived": sum(1 for f in findings if f.waived),
    }, indent=2)


_SARIF_LEVEL = {"info": "note", "warn": "warning", "error": "error"}


def render_sarif(findings: List[Finding]) -> str:
    """SARIF 2.1.0 for CI annotation surfaces (GitHub code scanning
    etc.). Waived findings are emitted with an inSource suppression so
    dashboards show the audit trail without failing the run."""
    rules = [
        {
            "id": code,
            "name": code,
            "shortDescription": {"text": RULES[code].title},
            "defaultConfiguration": {
                "level": _SARIF_LEVEL[RULES[code].severity]},
        }
        for code in sorted(RULES)
    ]
    results = []
    for f in findings:
        r = {
            "ruleId": f.rule,
            "level": _SARIF_LEVEL.get(f.severity, "warning"),
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(1, f.line),
                               "startColumn": f.col + 1},
                },
            }],
        }
        chain = (f.extra or {}).get("chain") or []
        if chain:
            # the provenance chain (dataflow rules): CI annotation
            # surfaces walk from the sink to the leak's origin
            r["relatedLocations"] = [{
                "physicalLocation": {
                    "artifactLocation": {
                        "uri": str(step.get("path", f.path))},
                    "region": {
                        "startLine": max(1, int(step.get("line", 1)))},
                },
                "message": {"text": str(step.get("note", ""))},
            } for step in chain if isinstance(step, dict)]
        if f.waived:
            r["suppressions"] = [{
                "kind": "inSource",
                "justification": f.waived_by,
            }]
        results.append(r)
    doc = {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "gmtpu-lint",
                "informationUri":
                    "https://example.invalid/geomesa-tpu/docs/ANALYSIS.md",
                "rules": rules,
            }},
            "results": results,
        }],
    }
    return json.dumps(doc, indent=2)


def exit_code(findings: List[Finding], fail_on: str) -> int:
    if fail_on == "never":
        return 0
    threshold = SEVERITIES.index(fail_on)
    for f in findings:
        if f.waived:
            continue
        if SEVERITIES.index(f.severity) >= threshold:
            return 1
    return 0


def changed_paths(scan_paths: List[str], ref: str) -> List[str]:
    """Resolve `gmtpu lint --changed[=REF]` to the changed .py files
    inside the scan scope: `git diff --name-only REF` plus untracked
    files. The scan SET narrows; the reference universe does not —
    `build_project` still pulls the whole repo in as ref modules, so
    the universe-backed rules (GT05 liveness, GT13/GT30 registration
    keys) keep their full context and a narrowed run never invents a
    false finding from missing context. The caller-graph passes
    (GT24-GT29, GT31) resolve within the scan set — a changed-only run
    is a fast pre-commit filter; the CI gate lints the full tree."""
    import subprocess
    root = find_repo_root(scan_paths[0]) if scan_paths else None
    root = root or os.getcwd()
    # The empty-tree hash: what the default ref degrades to on a repo
    # whose HEAD is unborn (initial commit), so the pre-commit hook
    # sample works on the very first commit instead of dying on
    # `git diff HEAD`. An explicit bad REF still errors.
    _EMPTY_TREE = "4b825dc642cb6eb9a060e54bf8d69288fbee4904"
    try:
        if ref == "HEAD":
            head = subprocess.run(
                ["git", "-C", root, "rev-parse", "--verify", "-q", "HEAD"],
                capture_output=True, text=True, timeout=30)
            if head.returncode != 0:
                ref = _EMPTY_TREE
        diff = subprocess.run(
            ["git", "-C", root, "diff", "--name-only", ref, "--"],
            capture_output=True, text=True, timeout=30)
        untracked = subprocess.run(
            ["git", "-C", root, "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"gmtpu-lint: --changed needs git: {e}")
    if diff.returncode != 0:
        raise RuntimeError(
            f"gmtpu-lint: git diff --name-only {ref} failed: "
            f"{diff.stderr.strip()}")
    scopes = [os.path.abspath(p) for p in scan_paths]
    out: List[str] = []
    names = diff.stdout.splitlines() + untracked.stdout.splitlines()
    for name in sorted(set(names)):
        if not name.endswith(".py"):
            continue
        af = os.path.abspath(os.path.join(root, name))
        if not os.path.exists(af):
            continue  # deleted files have nothing to lint
        if any(af == sc or af.startswith(sc + os.sep) for sc in scopes):
            out.append(af)
    return out


def run_cli(args) -> int:
    """Shared by `gmtpu lint` and `python -m geomesa_tpu.analysis`."""
    rules = None
    if getattr(args, "rules", None):
        rules = sorted({r.strip().upper() for r in args.rules.split(",")})
        unknown = [r for r in rules if r not in ALL_RULES]
        if unknown:
            raise ValueError(f"unknown rule(s): {', '.join(unknown)} "
                             f"(have {', '.join(sorted(ALL_RULES))})")
    if getattr(args, "spmd", False):
        # the SPMD pass subset (docs/ANALYSIS.md "Reading an SPMD
        # report"); composes with --rules as a union
        spmd_codes = [c for c in ("GT24", "GT25", "GT26", "GT27")
                      if c in ALL_RULES]
        rules = sorted(set(rules or []) | set(spmd_codes))
    if getattr(args, "dataflow", False):
        # the provenance dataflow pass subset (docs/ANALYSIS.md
        # "Reading a provenance report"); composes with --rules
        df_codes = [c for c in ("GT28", "GT29", "GT30", "GT31")
                    if c in ALL_RULES]
        rules = sorted(set(rules or []) | set(df_codes))
    paths = list(args.paths) or ["geomesa_tpu"]
    changed_ref = getattr(args, "changed", None)
    if changed_ref is not None:
        try:
            paths = changed_paths(paths, changed_ref)
        except RuntimeError as e:
            print(e, file=sys.stderr)
            return 2
        if not paths:
            print("gmtpu-lint: no changed .py files in scope",
                  file=sys.stderr)
            return 0
    lint_fn = lint_paths
    if getattr(args, "incremental", False):
        from geomesa_tpu.analysis.incremental import lint_paths_incremental
        lint_fn = lint_paths_incremental
    try:
        findings = lint_fn(
            paths,
            rules=rules,
            waiver_file=getattr(args, "waivers", None),
        )
    except (FileNotFoundError, ValueError) as e:
        # ValueError: malformed waiver file or a waiver naming an
        # unknown rule code — configuration errors exit 2, not a
        # silent green (or a traceback)
        print(e, file=sys.stderr)
        return 2
    fmt = getattr(args, "format", "text")
    if fmt == "json":
        print(render_json(findings))
    elif fmt == "sarif":
        print(render_sarif(findings))
    else:
        print(render_text(findings,
                          show_waived=getattr(args, "show_waived", False)))
    return exit_code(findings, getattr(args, "fail_on", "warn"))


def add_lint_arguments(p) -> None:
    p.add_argument("paths", nargs="*", default=["geomesa_tpu"],
                   help="files or directories to lint "
                        "(default: geomesa_tpu)")
    p.add_argument("--fail-on", dest="fail_on", default="warn",
                   choices=["never"] + list(SEVERITIES),
                   help="minimum severity that makes the exit code "
                        "nonzero (default: warn)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule codes to run "
                        "(default: all)")
    p.add_argument("--waivers", default=None,
                   help=f"waiver file (default: {DEFAULT_WAIVER_FILENAME} "
                        f"at the repo root, if present)")
    p.add_argument("--format", default="text",
                   choices=["text", "json", "sarif"],
                   help="output format (sarif: CI annotation surfaces)")
    p.add_argument("--show-waived", action="store_true",
                   help="include waived findings in text output")
    p.add_argument("--incremental", action="store_true",
                   help="use the content-hash lint cache "
                        "(.gmtpu-lintcache at the repo root): an "
                        "unchanged tree replays cached findings without "
                        "re-parsing; findings are byte-identical to a "
                        "cold scan")
    p.add_argument("--spmd", action="store_true",
                   help="run the interprocedural SPMD pass "
                        "(GT24-GT27; union with --rules)")
    p.add_argument("--dataflow", action="store_true",
                   help="run the interprocedural dtype/shape-"
                        "provenance pass (GT28-GT31; union with "
                        "--rules)")
    p.add_argument("--changed", nargs="?", const="HEAD", default=None,
                   metavar="REF",
                   help="lint only files changed vs REF (git diff "
                        "--name-only, default HEAD) plus untracked "
                        "files; cross-file rules keep the whole-repo "
                        "reference universe")
