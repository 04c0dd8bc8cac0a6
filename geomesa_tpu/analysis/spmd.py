"""Interprocedural SPMD mesh-discipline pass: GT24..GT27.

The GT01..GT23 rules answer single-module questions (plus the lockset
harness's cross-module lock graph). The multi-host roadmap item
(jax.distributed + a host-spanning mesh) introduces a bug class none of
them can see: SPMD divergence. A collective issued under an axis name no
enclosing `shard_map`/`pjit` binds fails at trace time *on the path that
runs it* — which on a pod may be a path CPU CI never takes; two
processes branching differently into mismatched collective sequences
deadlock the whole pod silently; every host writing the same manifest
file corrupts shared state that single-process runs never contend on.

This pass builds a per-module *SPMD summary* (collective sites with
resolved axis names, shard_map/pjit wrap sites with their mesh axes and
spec shapes, Mesh constructions, process/env-conditioned branches,
persist-style side effects, and call/import edges), then a project-wide
index with a call graph over the summaries, and checks:

- **GT24** — a collective primitive (`psum`/`all_gather`/`ppermute`/
  `axis_index`/...) whose axis name is bound neither by an enclosing
  `shard_map`/`pjit`/`pmap` wrap nor by every calling context reaching
  the helper. `engine/knn_scan._shard_merge_topk` is the canonical safe
  shape: bare collectives in a module-level helper, every caller inside
  a wrapped body — the calling-context propagation keeps it clean.
- **GT25** — a branch conditioned on `jax.process_index()` /
  `jax.process_count()` / an `os.environ` read whose arms differ in
  collective-relevant effects (collectives issued directly or through
  callees, or `jax.config.update` mutations that change the compiled
  program), in a module reachable from a distributed entry point. The
  static pod-deadlock detector: CPU CI runs one process and can never
  take both sides.
- **GT26** — sharding-spec drift: `in_specs`/`out_specs`/
  `PartitionSpec`/`NamedSharding` naming a mesh axis the constructing
  mesh (or any mesh built in the project) does not define, or a literal
  `in_specs` tuple whose arity disagrees with the mapped function's
  positional parameters.
- **GT27** — a persist-style side effect (the tmp+`os.replace` atomic
  write idiom, port binds) on a multi-process-reachable path without a
  coordinator gate (`parallel.is_coordinator()` / `process_index()==0`):
  on a pod every host performs it against shared storage.

Summaries are plain-dict serializable (`ModuleSummary.to_dict` /
`from_dict`) so the incremental lint cache can persist them per file and
rebuild the cross-file index for unchanged files without re-walking
their ASTs (analysis/incremental.py).

Like every gmtpu-lint rule: pure AST, never imports the code under
analysis, and precision is a requirement — the gate runs --fail-on warn.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Set, Tuple

from geomesa_tpu.analysis.model import Finding
from geomesa_tpu.analysis.modinfo import ModInfo

# bump when the summary shape changes: cached summaries from an older
# engine must not feed the index (analysis/incremental.py keys on this)
SPMD_SCHEMA = 2

# jax.lax collective primitives and the argument position of axis_name
_COLLECTIVES: Dict[str, int] = {
    "psum": 1, "pmax": 1, "pmin": 1, "pmean": 1,
    "all_gather": 1, "ppermute": 1, "pshuffle": 1,
    "all_to_all": 1, "psum_scatter": 1,
    "axis_index": 0,
}

# callables that establish an axis-binding context for the mapped fn
_WRAPPERS = {"shard_map", "_shard_map", "pjit", "pmap"}

# project functions known to construct the default 1-D serving mesh are
# discovered from their own `Mesh(...)` returns; no hardcoded list here.

_PROCESS_READS = {"process_index", "process_count"}

_GT25_ENTRY_FILES = (
    "geomesa_tpu/parallel/launch.py",
    "geomesa_tpu/parallel/distributed.py",
)
_GT25_ENTRY_PREFIXES = ("geomesa_tpu/serve/",)

_GT27_PREFIXES = (
    "geomesa_tpu/parallel/", "geomesa_tpu/store/",
    "geomesa_tpu/compilecache/", "geomesa_tpu/serve/",
    "geomesa_tpu/telemetry/", "geomesa_tpu/approx/",
)

_GATE_TOKENS = {"is_coordinator", "process_index", "process_count"}


# ---------------------------------------------------------------------------
# per-module summary model (dict-serializable for the incremental cache)
# ---------------------------------------------------------------------------


@dataclass
class CollectiveSite:
    line: int
    col: int
    primitive: str
    axis: Optional[str]          # literal value, "ref:<mod>:<name>", None
    fn: str                      # enclosing function qname or "<module>"


@dataclass
class WrapSite:
    line: int
    mapped: Optional[str]        # qname of the mapped function, if known
    axes: Optional[List[str]]    # mesh axis names, None when unresolved
    spec_axes: List[Tuple[int, int, str]] = field(default_factory=list)
    in_arity: Optional[int] = None   # literal in_specs tuple length
    fn: str = "<module>"


@dataclass
class SpecSite:                  # bare NamedSharding(mesh, P(...)) sites
    line: int
    col: int
    axes: List[str]
    mesh_axes: Optional[List[str]]
    fn: str = "<module>"


@dataclass
class BranchSite:
    line: int
    col: int
    fn: str
    kind: str                    # "process" | "env"
    body_tokens: List[str]
    body_calls: List[str]
    orelse_tokens: List[str]
    orelse_calls: List[str]


@dataclass
class EffectSite:
    line: int
    col: int
    fn: str
    kind: str                    # "persist" | "bind"
    detail: str
    gated: bool


@dataclass
class FuncSummary:
    qname: str
    line: int
    params: List[str]
    has_vararg: bool
    bound_axes: List[str]        # axes bound over this function's body
    bound_unknown: bool          # wrapped, but mesh axes unresolvable
    calls: List[Tuple[str, bool]]    # (resolved callee, call-site gated)
    gate_entry: bool             # body opens with a coordinator guard


@dataclass
class ModuleSummary:
    schema: int
    relpath: str
    module: str                  # dotted name
    imports: List[str]           # project-internal dotted modules
    import_names: Dict[str, str]     # local name -> source dotted module
    axis_constants: Dict[str, str]   # NAME -> literal string value
    mesh_axes: List[List[str]]   # axis tuples of Mesh() constructions
    functions: Dict[str, FuncSummary]
    collectives: List[CollectiveSite]
    wraps: List[WrapSite]
    specs: List[SpecSite]
    branches: List[BranchSite]
    effects: List[EffectSite]

    def to_dict(self) -> dict:
        def enc(obj):
            if isinstance(obj, (CollectiveSite, WrapSite, SpecSite,
                                BranchSite, EffectSite, FuncSummary)):
                return {k: enc(v) for k, v in vars(obj).items()}
            if isinstance(obj, (list, tuple)):
                return [enc(v) for v in obj]
            if isinstance(obj, dict):
                return {k: enc(v) for k, v in obj.items()}
            return obj
        return enc(vars(self))

    @classmethod
    def from_dict(cls, d: dict) -> "ModuleSummary":
        d = dict(d)
        d["functions"] = {
            k: FuncSummary(**{**v, "calls": [tuple(c) for c in v["calls"]]})
            for k, v in d["functions"].items()}
        d["collectives"] = [CollectiveSite(**c) for c in d["collectives"]]
        d["wraps"] = [
            WrapSite(**{**w, "spec_axes": [tuple(s) for s in w["spec_axes"]]})
            for w in d["wraps"]]
        d["specs"] = [SpecSite(**s) for s in d["specs"]]
        d["branches"] = [BranchSite(**b) for b in d["branches"]]
        d["effects"] = [EffectSite(**e) for e in d["effects"]]
        return cls(**d)


# ---------------------------------------------------------------------------
# extraction helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """`a.b.c` -> "a.b.c" for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _module_dotted(relpath: str) -> str:
    p = relpath.replace("\\", "/")
    if p.endswith(".py"):
        p = p[:-3]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class _Extractor:
    """One walk over a ModInfo tree -> ModuleSummary."""

    def __init__(self, mod: ModInfo):
        self.mod = mod
        self.module = _module_dotted(mod.relpath)
        self.summary = ModuleSummary(
            schema=SPMD_SCHEMA, relpath=mod.relpath, module=self.module,
            imports=[], import_names={}, axis_constants={}, mesh_axes=[],
            functions={}, collectives=[], wraps=[], specs=[], branches=[],
            effects=[])
        self._qname_of: Dict[ast.AST, str] = {}
        self._class_of: Dict[ast.AST, str] = {}

    # -- name / axis resolution --------------------------------------------

    def _collect_imports(self) -> None:
        s = self.summary
        pkg_root = self.module.split(".")[0]
        for node in ast.walk(self.mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.split(".")[0] == pkg_root:
                        s.imports.append(a.name)
                        s.import_names[a.asname or a.name.split(".")[0]] = \
                            a.name
            elif isinstance(node, ast.ImportFrom):
                src = node.module or ""
                if node.level:
                    base = self.module.split(".")
                    if self.mod.relpath.endswith("__init__.py"):
                        base = base + [""]
                    base = base[: len(base) - node.level]
                    src = ".".join(base + ([src] if src else []))
                if src.split(".")[0] != pkg_root:
                    continue
                s.imports.append(src)
                for a in node.names:
                    s.import_names[a.asname or a.name] = src
                    # `from pkg import mod` pulls in pkg.mod when the
                    # name is a submodule; record the candidate edge —
                    # reachability ignores it if no such module exists
                    s.imports.append(f"{src}.{a.name}")
        s.imports = sorted(set(s.imports))

    def _collect_axis_constants(self) -> None:
        for node in self.mod.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)):
                self.summary.axis_constants[node.targets[0].id] = \
                    node.value.value

    def _axis_value(self, node: ast.AST) -> Optional[str]:
        """A mesh-axis expression -> literal string, a cross-module
        "ref:<module>:<name>" marker, or None (unresolvable)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in self.summary.axis_constants:
                return self.summary.axis_constants[node.id]
            src = self.summary.import_names.get(node.id)
            if src:
                return f"ref:{src}:{node.id}"
        if isinstance(node, ast.Attribute):
            base = _terminal(node.value)
            src = self.summary.import_names.get(base or "")
            if src:
                return f"ref:{src}:{node.attr}"
        return None

    def _axes_tuple(self, node: ast.AST) -> Optional[List[str]]:
        if isinstance(node, (ast.Tuple, ast.List)):
            out = []
            for e in node.elts:
                v = self._axis_value(e)
                if v is None:
                    return None
                out.append(v)
            return out
        v = self._axis_value(node)
        return [v] if v is not None else None

    # -- function table -----------------------------------------------------

    def _collect_functions(self) -> None:
        def visit(node: ast.AST, prefix: str, cls: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    self._qname_of[child] = q
                    if cls:
                        self._class_of[child] = cls
                    a = child.args
                    params = [p.arg for p in a.posonlyargs + a.args]
                    if params and params[0] in ("self", "cls"):
                        params = params[1:]
                    self.summary.functions[q] = FuncSummary(
                        qname=q, line=child.lineno, params=params,
                        has_vararg=a.vararg is not None, bound_axes=[],
                        bound_unknown=False, calls=[], gate_entry=False)
                    visit(child, q + ".", cls)
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{child.name}.", child.name)
                else:
                    visit(child, prefix, cls)
        visit(self.mod.tree, "", None)

    def _enclosing_qname(self, node: ast.AST) -> str:
        for anc in self.mod.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return self._qname_of.get(anc, "<module>")
        return "<module>"

    def _resolve_callee(self, call: ast.Call) -> Optional[str]:
        """A call -> "<relpath-local qname>", "<module>:<name>" for a
        cross-module target, or None. Methods resolve `self.x()` to the
        enclosing class's `Cls.x`."""
        f = call.func
        if isinstance(f, ast.Name):
            # innermost local def shadowing wins; fall back to module fn
            for anc in self.mod.ancestors(call):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = self._qname_of.get(anc)
                    if q and f"{q}.{f.id}" in self.summary.functions:
                        return f"{q}.{f.id}"
            if f.id in self.summary.functions:
                return f.id
            src = self.summary.import_names.get(f.id)
            if src:
                return f"{src}:{f.id}"
            return None
        if isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name):
                if f.value.id == "self":
                    for anc in self.mod.ancestors(call):
                        if isinstance(anc, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                            cls = self._class_of.get(anc)
                            if cls and f"{cls}.{f.attr}" in \
                                    self.summary.functions:
                                return f"{cls}.{f.attr}"
                    return None
                src = self.summary.import_names.get(f.value.id)
                if src:
                    return f"{src}:{f.attr}"
        return None

    # -- binding contexts ---------------------------------------------------

    def _wrapper_call(self, call: ast.Call) -> Optional[ast.Call]:
        """shard_map(f, ...) / partial(shard_map, ...) -> the call whose
        keywords carry mesh/in_specs/out_specs, else None."""
        name = _terminal(call.func)
        if name in _WRAPPERS:
            return call
        if self.mod.is_partial_ref(call.func) and call.args:
            if _terminal(call.args[0]) in _WRAPPERS:
                return call
        return None

    def _mesh_axes_of_expr(self, node: ast.AST,
                           scope: ast.AST) -> Optional[List[str]]:
        """Resolve a mesh expression to its axis-name tuple: a direct
        `Mesh(..., (axes,))` call, a call to a project constructor that
        returns one, or a local `mesh = <either>` assignment in scope."""
        if isinstance(node, ast.Call):
            if _terminal(node.func) == "Mesh":
                axes_arg = None
                if len(node.args) >= 2:
                    axes_arg = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes_arg = kw.value
                if axes_arg is not None:
                    return self._axes_tuple(axes_arg)
                return None
            callee = self._resolve_callee(node)
            if callee:
                return [f"ctor:{callee}"]
            return None
        if isinstance(node, ast.Name):
            for n in ast.walk(scope):
                if (isinstance(n, ast.Assign) and len(n.targets) == 1
                        and isinstance(n.targets[0], ast.Name)
                        and n.targets[0].id == node.id):
                    return self._mesh_axes_of_expr(n.value, scope)
        return None

    def _record_wrap(self, call: ast.Call, mapped: Optional[str],
                     scope: ast.AST, fn_q: str) -> WrapSite:
        axes: Optional[List[str]] = None
        spec_axes: List[Tuple[int, int, str]] = []
        in_arity: Optional[int] = None
        for kw in call.keywords:
            if kw.arg == "mesh":
                axes = self._mesh_axes_of_expr(kw.value, scope)
            elif kw.arg in ("in_specs", "out_specs"):
                node = kw.value
                if kw.arg == "in_specs" and isinstance(
                        node, (ast.Tuple, ast.List)):
                    in_arity = len(node.elts)
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and _terminal(sub.func) in (
                            "P", "PartitionSpec"):
                        for a in sub.args:
                            v = self._axis_value(a)
                            if v is not None:
                                spec_axes.append(
                                    (sub.lineno, sub.col_offset, v))
        # pmap binds via axis_name=
        if _terminal(call.func) == "pmap":
            for kw in call.keywords:
                if kw.arg == "axis_name":
                    v = self._axis_value(kw.value)
                    axes = [v] if v is not None else None
        ws = WrapSite(line=call.lineno, mapped=mapped, axes=axes,
                      spec_axes=spec_axes, in_arity=in_arity, fn=fn_q)
        self.summary.wraps.append(ws)
        return ws

    def _collect_bindings(self) -> None:
        s = self.summary
        for node in ast.walk(self.mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        w = self._wrapper_call(dec)
                        if w is None:
                            continue
                        q = self._qname_of[node]
                        scope = self.mod.parent(node) or self.mod.tree
                        ws = self._record_wrap(w, q, scope, q)
                        self._bind(q, ws)
            elif isinstance(node, ast.Call):
                w = self._wrapper_call(node)
                if w is None or w is not node:
                    continue
                # skip the partial(...) decorator form handled above
                par = self.mod.parent(node)
                if isinstance(par, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and node in par.decorator_list:
                    continue
                # call form: shard_map(fn, mesh=..., ...)
                mapped = None
                args = node.args
                if self.mod.is_partial_ref(node.func):
                    args = node.args[1:]
                if args:
                    cand = _terminal(args[0])
                    if cand:
                        fn_q = self._enclosing_qname(node)
                        base = "" if fn_q == "<module>" else fn_q + "."
                        if f"{base}{cand}" in s.functions:
                            mapped = f"{base}{cand}"
                        elif cand in s.functions:
                            mapped = cand
                scope = (self.mod.enclosing_function(node)
                         or self.mod.tree)
                ws = self._record_wrap(node, mapped, scope,
                                       self._enclosing_qname(node))
                if mapped:
                    self._bind(mapped, ws)

    def _bind(self, qname: str, ws: WrapSite) -> None:
        f = self.summary.functions.get(qname)
        if f is None:
            return
        if ws.axes is None:
            f.bound_unknown = True
        else:
            for a in ws.axes:
                if a not in f.bound_axes:
                    f.bound_axes.append(a)

    # -- collectives, meshes, specs -----------------------------------------

    def _collect_collectives(self) -> None:
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _terminal(node.func)
            if name not in _COLLECTIVES:
                continue
            if isinstance(node.func, ast.Attribute):
                base = _dotted(node.func.value)
                if base is None or base.split(".")[-1] != "lax":
                    continue
            else:  # bare name must come from jax.lax
                src = self.summary.import_names.get(name, "")
                if not src.endswith("lax"):
                    continue
            pos = _COLLECTIVES[name]
            axis_node = None
            if len(node.args) > pos:
                axis_node = node.args[pos]
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis_node = kw.value
            axis = self._axis_value(axis_node) if axis_node is not None \
                else None
            self.summary.collectives.append(CollectiveSite(
                line=node.lineno, col=node.col_offset, primitive=name,
                axis=axis, fn=self._enclosing_qname(node)))

    def _collect_meshes_and_specs(self) -> None:
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _terminal(node.func)
            if name == "Mesh":
                axes_arg = node.args[1] if len(node.args) >= 2 else None
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes_arg = kw.value
                axes = self._axes_tuple(axes_arg) if axes_arg is not None \
                    else None
                if axes:
                    self.summary.mesh_axes.append(axes)
            elif name == "NamedSharding" and len(node.args) >= 2:
                axes: List[str] = []
                for sub in ast.walk(node.args[1]):
                    if isinstance(sub, ast.Call) and _terminal(sub.func) in (
                            "P", "PartitionSpec"):
                        for a in sub.args:
                            v = self._axis_value(a)
                            if v is not None:
                                axes.append(v)
                if axes:
                    scope = (self.mod.enclosing_function(node)
                             or self.mod.tree)
                    self.summary.specs.append(SpecSite(
                        line=node.lineno, col=node.col_offset, axes=axes,
                        mesh_axes=self._mesh_axes_of_expr(
                            node.args[0], scope),
                        fn=self._enclosing_qname(node)))

    # -- process/env branches (GT25) ----------------------------------------

    def _env_tainted(self, scope: ast.AST) -> Set[str]:
        out: Set[str] = set()
        for n in ast.walk(scope):
            if (isinstance(n, ast.Assign) and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                    and self._branch_kind_of_expr(n.value, set())):
                out.add(n.targets[0].id)
        return out

    def _branch_kind_of_expr(self, test: ast.AST,
                             tainted: Set[str]) -> Optional[str]:
        for n in ast.walk(test):
            if isinstance(n, ast.Call):
                t = _terminal(n.func)
                if t in _PROCESS_READS:
                    return "process"
                if t in ("get", "getenv"):
                    d = _dotted(n.func) or ""
                    if "environ" in d or d.endswith("os.getenv") \
                            or d == "getenv":
                        return "env"
            elif isinstance(n, ast.Subscript):
                d = _dotted(n.value) or ""
                if d.split(".")[-1] == "environ":
                    return "env"
            elif isinstance(n, ast.Name) and n.id in tainted:
                return "env"
        return None

    def _arm_signature(self, stmts: List[ast.stmt]) -> Tuple[List[str],
                                                             List[str]]:
        tokens: List[str] = []
        calls: List[str] = []
        for st in stmts:
            for n in ast.walk(st):
                if not isinstance(n, ast.Call):
                    continue
                t = _terminal(n.func)
                if t in _COLLECTIVES:
                    d = _dotted(n.func) or t
                    if "lax" in d.split(".") or \
                            self.summary.import_names.get(
                                t, "").endswith("lax"):
                        pos = _COLLECTIVES[t]
                        axis_node = (n.args[pos]
                                     if len(n.args) > pos else None)
                        for kw in n.keywords:
                            if kw.arg == "axis_name":
                                axis_node = kw.value
                        ax = (self._axis_value(axis_node)
                              if axis_node is not None else None)
                        tokens.append(f"coll:{t}:{ax}")
                        continue
                if t == "update":
                    d = _dotted(n.func) or ""
                    if "config" in d.split("."):
                        tokens.append("config:update")
                        continue
                resolved = self._resolve_callee(n)
                if resolved:
                    calls.append(resolved)
        return sorted(tokens), sorted(calls)

    def _collect_branches(self) -> None:
        taint_cache: Dict[ast.AST, Set[str]] = {}
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.If):
                continue
            scope = self.mod.enclosing_function(node) or self.mod.tree
            if scope not in taint_cache:
                taint_cache[scope] = self._env_tainted(scope)
            kind = self._branch_kind_of_expr(node.test, taint_cache[scope])
            if kind is None:
                continue
            bt, bc = self._arm_signature(node.body)
            ot, oc = self._arm_signature(node.orelse)
            self.summary.branches.append(BranchSite(
                line=node.lineno, col=node.col_offset,
                fn=self._enclosing_qname(node), kind=kind,
                body_tokens=bt, body_calls=bc,
                orelse_tokens=ot, orelse_calls=oc))

    # -- side effects + coordinator gates (GT27) ----------------------------

    def _is_gate_test(self, test: ast.AST) -> bool:
        for n in ast.walk(test):
            if isinstance(n, (ast.Call, ast.Attribute, ast.Name)):
                t = _terminal(n if not isinstance(n, ast.Call) else n.func)
                if t in _GATE_TOKENS:
                    return True
        return False

    def _site_gated(self, node: ast.AST) -> bool:
        for anc in self.mod.ancestors(node):
            if isinstance(anc, ast.If) and self._is_gate_test(anc.test):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if self._fn_gate_entry(anc):
                    return True
        return False

    def _fn_gate_entry(self, fn: ast.AST) -> bool:
        """An opening `if not is_coordinator(): return` guard gates the
        whole body."""
        for st in list(getattr(fn, "body", ()))[:5]:
            if (isinstance(st, ast.If) and self._is_gate_test(st.test)
                    and any(isinstance(x, (ast.Return, ast.Raise))
                            for x in st.body)):
                return True
        return False

    def _collect_effects(self) -> None:
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _terminal(node.func)
            kind = detail = None
            if name in ("replace", "rename") and isinstance(
                    node.func, ast.Attribute):
                base = _dotted(node.func.value) or ""
                if base.split(".")[-1] == "os":
                    kind, detail = "persist", f"os.{name}"
            elif name in ("HTTPServer", "ThreadingHTTPServer",
                          "TCPServer"):
                kind, detail = "bind", name
            elif name == "bind" and len(node.args) == 1 and isinstance(
                    node.args[0], ast.Tuple):
                kind, detail = "bind", "socket.bind"
            if kind is None:
                continue
            self.summary.effects.append(EffectSite(
                line=node.lineno, col=node.col_offset,
                fn=self._enclosing_qname(node), kind=kind, detail=detail,
                gated=self._site_gated(node)))

    # -- call edges ----------------------------------------------------------

    def _collect_calls(self) -> None:
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = self._resolve_callee(node)
            if target is None:
                continue
            fn_q = self._enclosing_qname(node)
            f = self.summary.functions.get(fn_q)
            if f is not None:
                f.calls.append((target, self._site_gated(node)))

    def run(self) -> ModuleSummary:
        self._collect_imports()
        self._collect_axis_constants()
        self._collect_functions()
        for fn_node, q in self._qname_of.items():
            self.summary.functions[q].gate_entry = \
                self._fn_gate_entry(fn_node)
        self._collect_bindings()
        self._collect_collectives()
        self._collect_meshes_and_specs()
        self._collect_branches()
        self._collect_effects()
        self._collect_calls()
        return self.summary


def extract_summary(mod: ModInfo) -> ModuleSummary:
    return _Extractor(mod).run()


# ---------------------------------------------------------------------------
# project index
# ---------------------------------------------------------------------------


class SpmdIndex:
    """Cross-module SPMD context built from per-module summaries. The
    incremental engine feeds cached summaries for unchanged files via
    `project._gt_spmd_summaries`; a cold scan extracts them all."""

    def __init__(self, summaries: List[ModuleSummary]):
        self.by_module: Dict[str, ModuleSummary] = {
            s.module: s for s in summaries}
        self.by_relpath: Dict[str, ModuleSummary] = {
            s.relpath: s for s in summaries}
        # project axis universe (literal axis names from Mesh() sites)
        self.project_axes: Set[str] = set()
        for s in summaries:
            for axes in s.mesh_axes:
                for a in axes:
                    if not a.startswith(("ref:", "ctor:")):
                        self.project_axes.add(a)
        # mesh-constructor functions: qname -> axes (functions whose
        # summary module records a Mesh() construction inside them —
        # approximated per module; precise enough for default_mesh/
        # global_mesh style one-liners)
        self.ctor_axes: Dict[str, List[str]] = {}
        for s in summaries:
            if len(s.mesh_axes) >= 1:
                axes0 = s.mesh_axes[0]
                same = all(m == axes0 for m in s.mesh_axes)
                if same:
                    for q in s.functions:
                        self.ctor_axes[f"{s.module}:{q}"] = axes0
                        self.ctor_axes[q] = axes0
        # reverse call graph over global ids "module:qname"
        self.callers: Dict[str, List[Tuple[str, str, bool]]] = {}
        for s in summaries:
            for q, f in s.functions.items():
                for target, gated in f.calls:
                    gid = self._global_id(s, target)
                    if gid is not None:
                        self.callers.setdefault(gid, []).append(
                            (s.module, q, gated))
        self._bound_memo: Dict[Tuple[str, str], bool] = {}
        self._coll_memo: Dict[str, Optional[Set[str]]] = {}
        self._reachable: Optional[Set[str]] = None

    # -- id & axis helpers ---------------------------------------------------

    def _global_id(self, summary: ModuleSummary,
                   target: str) -> Optional[str]:
        """Resolve a summary-local call target to "module:qname"."""
        if ":" in target:
            mod_name, name = target.rsplit(":", 1)
            dst = self.by_module.get(mod_name)
            if dst is None:
                return None
            if name in dst.functions:
                return f"{dst.module}:{name}"
            # package __init__ re-export: follow one hop
            src2 = dst.import_names.get(name)
            if src2:
                dst2 = self.by_module.get(src2)
                if dst2 and name in dst2.functions:
                    return f"{dst2.module}:{name}"
            return None
        if target in summary.functions:
            return f"{summary.module}:{target}"
        return None

    def resolve_axis(self, axis: Optional[str]) -> Optional[str]:
        """Follow "ref:<module>:<name>" markers to a literal axis."""
        seen = 0
        while axis is not None and axis.startswith("ref:") and seen < 5:
            _, mod_name, name = axis.split(":", 2)
            dst = self.by_module.get(mod_name)
            if dst is None:
                return None
            if name in dst.axis_constants:
                return dst.axis_constants[name]
            src = dst.import_names.get(name)
            if src is None:
                return None
            axis = f"ref:{src}:{name}"
            seen += 1
        if axis is not None and axis.startswith(("ref:", "ctor:")):
            return None
        return axis

    def resolve_mesh_axes(self,
                          axes: Optional[List[str]]) -> Optional[List[str]]:
        if axes is None:
            return None
        out: List[str] = []
        for a in axes:
            if a.startswith("ctor:"):
                ct = self.ctor_axes.get(a[5:])
                if ct is None:
                    return None
                for c in ct:
                    r = self.resolve_axis(c)
                    if r is None:
                        return None
                    out.append(r)
                continue
            r = self.resolve_axis(a)
            if r is None:
                return None
            out.append(r)
        return out

    # -- GT24 context propagation -------------------------------------------

    def func_bound(self, module: str, qname: str, axis: str,
                   _stack: Optional[Set[str]] = None) -> bool:
        """True when `axis` is bound for every path reaching this
        function: an enclosing wrap binds it, or all in-project callers
        are themselves bound. No callers at all -> unbound."""
        gid = f"{module}:{qname}"
        key = (gid, axis)
        if key in self._bound_memo:
            return self._bound_memo[key]
        stack = _stack or set()
        if gid in stack:
            return True  # cycle: optimistic, avoids self-FP
        s = self.by_module.get(module)
        f = s.functions.get(qname) if s else None
        if f is None:
            return False
        if f.bound_unknown:
            self._bound_memo[key] = True
            return True
        resolved = self.resolve_mesh_axes(f.bound_axes)
        if resolved is None and f.bound_axes:
            # a wrap binds this function but its axes can't be resolved
            # (opaque ctor, cross-module miss): optimistic, like
            # bound_unknown — GT24 only flags provably-unbound axes
            self._bound_memo[key] = True
            return True
        bound = set(resolved or ())
        if axis in bound:
            self._bound_memo[key] = True
            return True
        # nested defs inherit the enclosing function's binding (a def
        # inside a wrapped body executes under the wrap)
        if "." in qname:
            outer = qname.rsplit(".", 1)[0]
            if s and outer in s.functions and self.func_bound(
                    module, outer, axis, stack | {gid}):
                self._bound_memo[key] = True
                return True
        callers = self.callers.get(gid, ())
        if not callers:
            self._bound_memo[key] = False
            return False
        ok = all(self.func_bound(cm, cq, axis, stack | {gid})
                 for cm, cq, _ in callers)
        self._bound_memo[key] = ok
        return ok

    # -- GT25 transitive collective effects ----------------------------------

    def collective_tokens(self, gid: str,
                          depth: int = 4) -> Set[str]:
        if gid in self._coll_memo:
            return self._coll_memo[gid] or set()
        self._coll_memo[gid] = None  # cycle guard
        out: Set[str] = set()
        mod_name, qname = gid.split(":", 1)
        s = self.by_module.get(mod_name)
        if s is not None and qname in s.functions:
            for c in s.collectives:
                if c.fn == qname or c.fn.startswith(qname + "."):
                    out.add(f"coll:{c.primitive}:"
                            f"{self.resolve_axis(c.axis)}")
            if depth > 0:
                for target, _ in s.functions[qname].calls:
                    sub = self._global_id(s, target)
                    if sub is not None:
                        out |= self.collective_tokens(sub, depth - 1)
        self._coll_memo[gid] = out
        return out

    def arm_effective(self, summary: ModuleSummary, tokens: List[str],
                      calls: List[str]) -> Set[str]:
        out = set()
        for t in tokens:
            if t.startswith("coll:"):
                parts = t.split(":", 2)
                out.add(f"coll:{parts[1]}:"
                        f"{self.resolve_axis(parts[2]) or parts[2]}")
            else:
                out.add(t)
        for c in calls:
            gid = self._global_id(summary, c)
            if gid is not None:
                out |= self.collective_tokens(gid)
        return out

    # -- GT25/GT27 reachability ----------------------------------------------

    def reachable_modules(self) -> Set[str]:
        """Modules importable (transitively) from the distributed entry
        points — the code that runs inside a multi-process program."""
        if self._reachable is not None:
            return self._reachable
        entries = []
        for s in self.by_relpath.values():
            rel = s.relpath.replace("\\", "/")
            if rel in _GT25_ENTRY_FILES or rel.startswith(
                    _GT25_ENTRY_PREFIXES):
                entries.append(s.module)
        seen: Set[str] = set()
        work = list(entries)
        while work:
            m = work.pop()
            if m in seen:
                continue
            seen.add(m)
            s = self.by_module.get(m)
            if s is None:
                continue
            for imp in s.imports:
                if imp not in seen:
                    work.append(imp)
                # `import geomesa_tpu.parallel.launch` also runs the
                # package __init__ chain
                parts = imp.split(".")
                for i in range(1, len(parts)):
                    pkg = ".".join(parts[:i])
                    if pkg not in seen:
                        work.append(pkg)
        self._reachable = seen
        return seen

    def caller_gated(self, module: str, qname: str,
                     _depth: int = 2) -> bool:
        """All in-project call sites of this function are coordinator-
        gated (one level of interprocedural gate propagation)."""
        gid = f"{module}:{qname}"
        callers = self.callers.get(gid, ())
        if not callers:
            return False
        for cm, cq, gated in callers:
            if gated:
                continue
            cs = self.by_module.get(cm)
            cf = cs.functions.get(cq) if cs else None
            if cf is not None and cf.gate_entry:
                continue
            if _depth > 0 and self.caller_gated(cm, cq, _depth - 1):
                continue
            return False
        return True


def spmd_index(project) -> SpmdIndex:
    idx = getattr(project, "_gt_spmd", None)
    if idx is None:
        cached: Dict[str, ModuleSummary] = getattr(
            project, "_gt_spmd_summaries", None) or {}
        summaries = []
        for m in project.modules:
            s = cached.get(m.relpath)
            if s is None or s.schema != SPMD_SCHEMA:
                s = extract_summary(m)
            summaries.append(s)
        idx = project._gt_spmd = SpmdIndex(summaries)
        project._gt_spmd_summaries = {
            s.relpath: s for s in summaries}
    return idx


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _finding(rule: str, mod: ModInfo, line: int, col: int,
             msg: str) -> Finding:
    return Finding(rule=rule, path=mod.relpath, line=line, col=col,
                   message=msg)


def gt24(mod: ModInfo, project) -> Iterator[Finding]:
    """Collective whose axis name no enclosing or calling-context wrap
    binds. Axis names that do not resolve statically (passed as
    parameters) are skipped — conservative, no false positives on
    axis-generic helpers that wrap jax.lax.pcast."""
    idx = spmd_index(project)
    s = idx.by_relpath.get(mod.relpath)
    if s is None:
        return
    seen: Set[Tuple[int, str]] = set()
    for c in s.collectives:
        axis = idx.resolve_axis(c.axis)
        if axis is None:
            continue
        if c.fn == "<module>":
            yield _finding(
                "GT24", mod, c.line, c.col,
                f"collective jax.lax.{c.primitive} over axis {axis!r} at "
                f"module level: no shard_map/pjit context can bind it")
            continue
        if idx.func_bound(s.module, c.fn, axis):
            continue
        key = (c.line, c.primitive)
        if key in seen:
            continue
        seen.add(key)
        yield _finding(
            "GT24", mod, c.line, c.col,
            f"collective jax.lax.{c.primitive} over axis {axis!r} in "
            f"{c.fn!r} is not bound by any enclosing shard_map/pjit wrap "
            f"or calling context — traces only under a mesh that binds "
            f"{axis!r}; on a pod this fails (or hangs) at first dispatch")


def gt25(mod: ModInfo, project) -> Iterator[Finding]:
    """Process-divergent control flow on a distributed-reachable path:
    the two arms of a process_index()/env branch disagree on collective-
    relevant effects (collectives issued, or jax.config.update calls
    that reshape every compiled program). One process takes each side;
    the collectives stop lining up; the pod deadlocks — silently, since
    single-process CPU CI only ever sees one arm."""
    idx = spmd_index(project)
    s = idx.by_relpath.get(mod.relpath)
    if s is None or s.module not in idx.reachable_modules():
        return
    for b in s.branches:
        body = idx.arm_effective(s, b.body_tokens, b.body_calls)
        orelse = idx.arm_effective(s, b.orelse_tokens, b.orelse_calls)
        if body == orelse:
            continue
        diff = sorted(body.symmetric_difference(orelse))
        src = ("jax.process_index()/process_count()"
               if b.kind == "process" else "an os.environ read")
        yield _finding(
            "GT25", mod, b.line, b.col,
            f"branch conditioned on {src} reaches different collective-"
            f"relevant effects per arm ({', '.join(diff)}): processes "
            f"taking different sides issue mismatched collective "
            f"sequences (or compile divergent programs) — a silent "
            f"multi-host deadlock CPU CI cannot reproduce")


def gt26(mod: ModInfo, project) -> Iterator[Finding]:
    """Sharding-spec drift: a spec axis name the constructing mesh (or,
    when the mesh is not statically resolvable, ANY project mesh) does
    not define, or a literal in_specs tuple whose arity disagrees with
    the mapped function's positional parameter count."""
    idx = spmd_index(project)
    s = idx.by_relpath.get(mod.relpath)
    if s is None:
        return
    for w in s.wraps:
        mesh_axes = idx.resolve_mesh_axes(w.axes)
        for line, col, raw in w.spec_axes:
            axis = idx.resolve_axis(raw)
            if axis is None:
                continue
            if mesh_axes is not None:
                if axis not in mesh_axes:
                    yield _finding(
                        "GT26", mod, line, col,
                        f"spec names axis {axis!r} but the wrap's mesh "
                        f"binds {mesh_axes!r}")
            elif idx.project_axes and axis not in idx.project_axes:
                yield _finding(
                    "GT26", mod, line, col,
                    f"spec names axis {axis!r}; no mesh constructed "
                    f"anywhere in the project defines that axis "
                    f"(project axes: {sorted(idx.project_axes)!r})")
        if w.in_arity is not None and w.mapped is not None:
            f = s.functions.get(w.mapped)
            if f is not None and not f.has_vararg and \
                    len(f.params) != w.in_arity:
                yield _finding(
                    "GT26", mod, w.line, 0,
                    f"in_specs has {w.in_arity} entr"
                    f"{'y' if w.in_arity == 1 else 'ies'} but mapped "
                    f"function {w.mapped!r} takes {len(f.params)} "
                    f"positional parameter(s)")
    for sp in s.specs:
        mesh_axes = idx.resolve_mesh_axes(sp.mesh_axes)
        for raw in sp.axes:
            axis = idx.resolve_axis(raw)
            if axis is None:
                continue
            if mesh_axes is not None:
                if axis not in mesh_axes:
                    yield _finding(
                        "GT26", mod, sp.line, sp.col,
                        f"NamedSharding spec names axis {axis!r} but its "
                        f"mesh binds {mesh_axes!r}")
            elif idx.project_axes and axis not in idx.project_axes:
                yield _finding(
                    "GT26", mod, sp.line, sp.col,
                    f"NamedSharding spec names axis {axis!r}; no project "
                    f"mesh defines it "
                    f"(project axes: {sorted(idx.project_axes)!r})")


def gt27(mod: ModInfo, project) -> Iterator[Finding]:
    """Process-local side effect on a multi-process-reachable path with
    no coordinator gate. Scope: the persist idiom (tmp + os.replace /
    os.rename) and port binds, in the subsystems the multi-host runtime
    actually enters (parallel/, store/, compilecache/, serve/,
    telemetry/, approx/). Fix: gate on parallel.is_coordinator() (a
    single-process no-op), or waive with the reason the write is
    host-local by design (e.g. per-partition ingest under
    process_partitions())."""
    rel = mod.relpath.replace("\\", "/")
    if not rel.startswith(_GT27_PREFIXES):
        return
    idx = spmd_index(project)
    s = idx.by_relpath.get(mod.relpath)
    if s is None:
        return
    for e in s.effects:
        if e.gated:
            continue
        if e.fn != "<module>":
            f = s.functions.get(e.fn)
            if f is not None and f.gate_entry:
                continue
            if idx.caller_gated(s.module, e.fn):
                continue
        what = ("port bind" if e.kind == "bind"
                else f"atomic persist ({e.detail})")
        yield _finding(
            "GT27", mod, e.line, e.col,
            f"{what} in {e.fn!r} has no coordinator gate: every process "
            f"of a multi-host run performs it against shared storage — "
            f"gate with parallel.is_coordinator() (single-process no-op) "
            f"or waive as host-local by design")


SPMD_RULES = {"GT24": gt24, "GT25": gt25, "GT26": gt26, "GT27": gt27}
