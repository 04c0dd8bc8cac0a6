"""GT07..GT12: lock-discipline static analysis for the serving path.

The lockset family of analyses (Eraser, Savage et al. 1997), restricted
to what the AST can answer without running anything: infer which lock
guards each piece of shared state, then flag accesses that break the
inferred invariant. Shared-state inference is class-aware — a class that
owns a `threading.Lock`/`RLock` (or a `Condition`) has declared its
concurrency contract, and a project-wide thread-entry reachability pass
(`threading.Thread(target=...)`, executor `submit`/`map`, the serve
dispatch loop) classifies which lock-FREE classes are still reached from
threaded code.

Rules:

- GT07  unguarded access to a field that is lock-guarded elsewhere in
        the same class (torn read / lost update), plus unguarded
        container mutations in lock-owning classes.
- GT08  lock-order cycle across the project-wide lock acquisition graph
        (deadlock risk).
- GT09  blocking call while holding a lock: file I/O, device dispatch
        (`to_device`, jitted kernels, `block_until_ready`), `sleep`,
        future `.result()`, thread `.join()`, queue get/put, foreign
        condition `.wait()`.
- GT10  lock created per-call (function-local) — it guards nothing.
- GT11  callback / future `set_result` invoked while holding a lock the
        callback's consumer may also take.
- GT12  shared mutable state mutated from thread-reachable code without
        a guard: mutable default arguments, module globals, and
        container fields of lock-free classes.

Precision stance matches the GT01..GT06 rules: name-based, never
imports the analyzed code, tuned so the shipped tree is clean modulo
documented waivers. Guarded-ness is syntactic: a `with` over an
expression whose name contains "lock"/"mutex", a `with self.<lock
attr>`, a method carrying a locking decorator (`@_locked`), or a
private method whose every intra-class call site is guarded (computed
to a fixpoint).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from geomesa_tpu.analysis.model import Finding
from geomesa_tpu.analysis.modinfo import ClassInfo, ModInfo

# container-mutating method names (list/dict/set/deque)
MUTATORS = {
    "append", "appendleft", "add", "update", "extend", "insert", "pop",
    "popleft", "popitem", "clear", "discard", "remove", "setdefault",
}

_BLOCKING_ATTRS = {"block_until_ready", "device_get", "device_put"}
_CALLBACK_MARKERS = ("callback", "listener", "hook")


def _finding(rule: str, mod: ModInfo, node: ast.AST, msg: str) -> Finding:
    return Finding(rule=rule, path=mod.relpath,
                   line=getattr(node, "lineno", 0),
                   col=getattr(node, "col_offset", 0), message=msg)


def _expr_name(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return f"{_expr_name(node.value)}.{node.attr}"
    if isinstance(node, ast.Call):
        return f"{_expr_name(node.func)}()"
    return ""


def _lockish(node: ast.AST) -> bool:
    name = _expr_name(node).lower()
    return "lock" in name or "mutex" in name


def _self_attr(node: ast.AST) -> Optional[str]:
    return ModInfo._self_attr_name(node)


def _mod_base(mod: ModInfo) -> str:
    return mod.relpath


def _enclosing_class(mod: ModInfo, node: ast.AST) -> Optional[ClassInfo]:
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.ClassDef):
            return mod.classes.get(anc.name)
    return None


def _enclosing_method(mod: ModInfo, node: ast.AST,
                      ci: ClassInfo) -> Optional[str]:
    """Name of the ci method whose body holds node (node itself when it
    is the method's def)."""
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        parent = mod.parent(node)
        if isinstance(parent, ast.ClassDef) and parent.name == ci.name:
            return node.name
    for anc in mod.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            parent = mod.parent(anc)
            if isinstance(parent, ast.ClassDef) and parent.name == ci.name:
                return anc.name
    return None


def _lock_id(mod: ModInfo, expr: ast.AST,
             ci: Optional[ClassInfo]) -> Optional[str]:
    """Stable identity for a lock expression, or None if it does not
    look like a lock. Class lock attrs key as "Class.attr" so every
    instance of the class maps to one graph node."""
    attr = _self_attr(expr)
    if attr is not None and ci is not None:
        if attr in ci.cond_attrs:
            return f"{ci.name}.{ci.cond_attrs[attr]}"
        if attr in ci.lock_attrs:
            return f"{ci.name}.{attr}"
    if _lockish(expr):
        name = _expr_name(expr)
        if attr is not None and ci is not None:
            return f"{ci.name}.{attr}"
        return f"{_mod_base(mod)}:{name}"
    return None


def _held_with_locks(mod: ModInfo, node: ast.AST) -> List[str]:
    """Lock ids of every `with <lock>` enclosing node (lexically)."""
    ci = _enclosing_class(mod, node)
    out: List[str] = []
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.With):
            for item in anc.items:
                lid = _lock_id(mod, item.context_expr, ci)
                if lid is not None:
                    out.append(lid)
    return out


# -- per-class discipline ----------------------------------------------------


class _Access:
    __slots__ = ("field", "method", "node", "kind", "guarded")

    def __init__(self, field, method, node, kind, guarded):
        self.field = field
        self.method = method
        self.node = node
        self.kind = kind          # "read" | "write" | "mutate"
        self.guarded = guarded


class _Discipline:
    """Lock discipline of one class: which methods are fully guarded
    (locking decorator), which are only ever called with the lock held
    (fixpoint over intra-class call sites), and every `self.<field>`
    access with its guarded-ness."""

    def __init__(self, mod: ModInfo, ci: ClassInfo):
        self.mod = mod
        self.ci = ci
        self.full_lock: Dict[str, str] = {}     # method -> lock attr
        self.guard_only: Set[str] = set()
        self.init_only: Set[str] = set()
        self.accesses: List[_Access] = []
        self.acquires: Dict[str, Set[str]] = {}  # method -> lock attrs
        self._intra: Dict[str, List[Tuple[str, bool]]] = {}
        self._build()

    def _build(self) -> None:
        mod, ci = self.mod, self.ci
        for name, fn in ci.methods.items():
            for dec in fn.decorator_list:
                if (isinstance(dec, ast.Name)
                        and dec.id in mod.locking_decorators):
                    self.full_lock[name] = mod.locking_decorators[dec.id]
        raw: List[_Access] = []
        for name, fn in ci.methods.items():
            aliases = self._aliases(fn)
            acq: Set[str] = set(
                [self.full_lock[name]] if name in self.full_lock else [])
            for node in ast.walk(fn):
                if isinstance(node, ast.With):
                    for item in node.items:
                        a = _self_attr(item.context_expr)
                        if a in ci.lock_attrs:
                            acq.add(a)
                        elif a in ci.cond_attrs:
                            acq.add(ci.cond_attrs[a])
                for field, kind in self._accesses_of(node, aliases):
                    raw.append(_Access(field, name, node, kind,
                                       self._guarded0(name, node)))
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    callee = _self_attr(node.func)
                    if callee in ci.methods:
                        self._intra.setdefault(callee, []).append(
                            (name, self._guarded0(name, node)))
            self.acquires[name] = acq
        self._fixpoint()
        for a in raw:
            if a.method in self.guard_only:
                a.guarded = True
        self.accesses = raw
        # propagate intra-class acquisitions (apply -> _upsert takes lock)
        changed = True
        while changed:
            changed = False
            for callee, sites in self._intra.items():
                for caller, _g in sites:
                    before = len(self.acquires.setdefault(caller, set()))
                    self.acquires[caller] |= self.acquires.get(callee, set())
                    if len(self.acquires[caller]) != before:
                        changed = True

    def _guarded0(self, method: str, node: ast.AST) -> bool:
        if method in self.full_lock:
            return True
        ci = self.ci
        for anc in self.mod.ancestors(node):
            if isinstance(anc, ast.With):
                for item in anc.items:
                    a = _self_attr(item.context_expr)
                    if a in ci.lock_attrs or a in ci.cond_attrs:
                        return True
                    if _lockish(item.context_expr):
                        return True
            if isinstance(anc, ast.ClassDef):
                break
        return False

    def _aliases(self, fn: ast.FunctionDef) -> Dict[str, str]:
        """Local names bound to self fields (or elements of them):
        `cached = self._compiled_filters`, `st = self._state[name]`,
        `cached = self._compiled_filters = {}`, `x = getattr(self, "f")`.
        """
        out: Dict[str, str] = {}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            field = None
            v = node.value
            if _self_attr(v) is not None:
                field = _self_attr(v)
            elif (isinstance(v, ast.Subscript)
                  and _self_attr(v.value) is not None):
                field = _self_attr(v.value)
            elif (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                  and v.func.id == "getattr" and len(v.args) >= 2
                  and isinstance(v.args[0], ast.Name)
                  and v.args[0].id == "self"
                  and isinstance(v.args[1], ast.Constant)):
                field = str(v.args[1].value)
            for t in node.targets:
                if field is None and _self_attr(t) is not None:
                    field = _self_attr(t)  # chained: x = self.f = {}
            if field is None or field in self.ci.methods:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = field
        return out

    def _ref_field(self, node: ast.AST,
                   aliases: Dict[str, str]) -> Optional[str]:
        """self.F or an alias of it -> F (never a method name)."""
        attr = _self_attr(node)
        if attr is not None:
            return None if attr in self.ci.methods else attr
        if isinstance(node, ast.Name):
            return aliases.get(node.id)
        return None

    def _accesses_of(self, node: ast.AST,
                     aliases: Dict[str, str]):
        """(field, kind) accesses contributed by this single node."""
        if isinstance(node, ast.Attribute):
            attr = _self_attr(node)
            if attr is not None and attr not in self.ci.methods:
                if isinstance(node.ctx, ast.Store):
                    yield attr, "write"
                elif isinstance(node.ctx, ast.Del):
                    yield attr, "mutate"
                else:
                    yield attr, "read"
        elif isinstance(node, ast.Subscript):
            f = self._ref_field(node.value, aliases)
            if f is not None and isinstance(node.ctx, (ast.Store, ast.Del)):
                yield f, "mutate"
        elif isinstance(node, ast.AugAssign):
            # `self.f += 1` and `alias[k] += 1` are field mutations;
            # `alias += 1` on a bare local name only rebinds the local
            t = node.target
            f = None
            if _self_attr(t) is not None:
                f = self._ref_field(t, aliases)
            elif isinstance(t, ast.Subscript):
                f = self._ref_field(t.value, aliases)
            if f is not None:
                yield f, "mutate"
        elif isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in MUTATORS:
                f = self._ref_field(fn.value, aliases)
                # a field holding a project-class instance (self.queue =
                # AdmissionQueue(...)) is an object with its own
                # discipline, not a raw container — .pop()/.put() on it
                # is a method call, not a container mutation
                if f is not None and f not in self.ci.field_types:
                    yield f, "mutate"

    def _fixpoint(self) -> None:
        ci = self.ci
        changed = True
        while changed:
            changed = False
            for m in ci.methods:
                if m == "__init__" or m in self.guard_only:
                    continue
                sites = [(c, g) for c, g in self._intra.get(m, ())
                         if c != "__init__" and c not in self.init_only]
                if not sites or m not in self._intra:
                    continue
                if all(g or c in self.full_lock or c in self.guard_only
                       for c, g in sites):
                    self.guard_only.add(m)
                    changed = True
        changed = True
        while changed:
            changed = False
            for m, sites in self._intra.items():
                if m == "__init__" or m in self.init_only:
                    continue
                if sites and all(c == "__init__" or c in self.init_only
                                 for c, _g in sites):
                    self.init_only.add(m)
                    changed = True

    def effectively_guarded(self, method: str) -> Optional[str]:
        """Lock attr this method runs under, if fully guarded."""
        if method in self.full_lock:
            return self.full_lock[method]
        if method in self.guard_only:
            return next(iter(sorted(self.ci.lock_attrs)), None)
        return None


def _discipline(mod: ModInfo, ci: ClassInfo) -> _Discipline:
    cache = getattr(mod, "_gt_disciplines", None)
    if cache is None:
        cache = mod._gt_disciplines = {}  # type: ignore[attr-defined]
    if ci.name not in cache:
        cache[ci.name] = _Discipline(mod, ci)
    return cache[ci.name]


# -- project-wide concurrency index -----------------------------------------


class ConcurrencyIndex:
    """Thread-entry reachability + attribute-call-site guard map + the
    lock acquisition graph, computed once per lint run over scan AND
    reference modules (a thread started in a script makes package code
    thread-reachable just like one started inside the package)."""

    def __init__(self, modules: List[ModInfo]):
        self.modules = modules
        # every function/method (incl. nested defs), indexed by name
        self.defs: Dict[str, List[Tuple[ModInfo, ast.AST]]] = {}
        for mod in modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.defs.setdefault(node.name, []).append((mod, node))
        self.reached: Set[int] = set()
        self.reached_classes: Set[str] = set()
        self._reach()
        self.call_sites: Dict[str, List[Tuple[ModInfo, ast.Call, bool]]] = {}
        self._index_call_sites()
        self.edges: Dict[Tuple[str, str], Tuple[ModInfo, ast.AST]] = {}
        self._lock_graph()
        self.cyclic_edges: Set[Tuple[str, str]] = self._cycles()
        self._confined: Dict[str, bool] = {}
        # name -> [(mod, Call)] for every Name-called constructor site,
        # built lazily on the first class_confined query: the per-name
        # full-project walk this replaces dominated whole-repo lint
        # wall time (one walk per queried class vs one walk total)
        self._ctor_sites: Optional[
            Dict[str, List[Tuple[ModInfo, ast.Call]]]] = None

    # -- thread-entry reachability ----------------------------------------

    def _entry_defs(self) -> List[Tuple[ModInfo, ast.AST]]:
        out = []
        for mod in self.modules:
            for owner, name in mod.thread_targets:
                if owner is not None:
                    ci = mod.classes.get(owner)
                    if ci is not None and name in ci.methods:
                        out.append((mod, ci.methods[name]))
                        continue
                out.extend(
                    (m, fn) for m, fn in self.defs.get(name, ()) )
        return out

    def _reach(self) -> None:
        work = list(self._entry_defs())
        while work:
            mod, fn = work.pop()
            if id(fn) in self.reached:
                continue
            self.reached.add(id(fn))
            parent = mod.parent(fn)
            if isinstance(parent, ast.ClassDef):
                self.reached_classes.add(parent.name)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = None
                if isinstance(node.func, ast.Name):
                    name = node.func.id
                elif isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                if name is None:
                    continue
                for target in self.defs.get(name, ()):
                    if id(target[1]) not in self.reached:
                        work.append(target)

    def class_reached(self, name: str) -> bool:
        return name in self.reached_classes

    def func_reached(self, fn: ast.AST) -> bool:
        return id(fn) in self.reached

    def class_confined(self, name: str) -> bool:
        """True when every constructor call of `name` in the universe
        binds the instance to a plain local in a function that spawns no
        threads, and that local never escapes (returned, stored onto an
        object/module, passed as an argument, put in a literal): such
        instances live and die inside one call frame — parser/cursor
        classes — and cannot be shared across threads."""
        if name in self._confined:
            return self._confined[name]
        if self._ctor_sites is None:
            self._ctor_sites = {}
            for mod in self.modules:
                for node in ast.walk(mod.tree):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Name)):
                        self._ctor_sites.setdefault(
                            node.func.id, []).append((mod, node))
        sites = self._ctor_sites.get(name, [])
        ok = bool(sites)
        for mod, call in sites:
            if not self._ctor_confined(mod, call):
                ok = False
                break
        self._confined[name] = ok
        return ok

    def _ctor_confined(self, mod: ModInfo, call: ast.Call) -> bool:
        parent = mod.parent(call)
        if isinstance(parent, ast.Attribute):
            # `_Parser(text).parse()`: the temporary instance is consumed
            # by one method call and never bound at all
            return True
        if not (isinstance(parent, ast.Assign)
                and all(isinstance(t, ast.Name) for t in parent.targets)):
            return False
        fn = None
        for anc in mod.ancestors(call):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = anc
                break
        if fn is None:
            return False  # module-level instance: shared by definition
        for n in ast.walk(fn):
            if mod.is_thread_ctor(n) or (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Name)
                    and "Executor" in n.func.id):
                return False
        names = {t.id for t in parent.targets}
        for n in ast.walk(fn):
            if isinstance(n, ast.Return) and n.value is not None:
                if _uses_names(n.value, names):
                    return False
            elif isinstance(n, (ast.Yield, ast.YieldFrom)) \
                    and n.value is not None:
                if _uses_names(n.value, names):
                    return False
            elif isinstance(n, ast.Call) and n is not call:
                args = list(n.args) + [kw.value for kw in n.keywords]
                if any(isinstance(a, ast.Name) and a.id in names
                       for a in args):
                    return False
            elif isinstance(n, ast.Assign) and n is not parent:
                if isinstance(n.value, ast.Name) and n.value.id in names \
                        and any(not isinstance(t, ast.Name)
                                for t in n.targets):
                    return False
            elif isinstance(n, (ast.List, ast.Tuple, ast.Dict, ast.Set)):
                if _uses_names(n, names):
                    return False
        return True

    # -- call-site guard map ------------------------------------------------

    def _site_guarded(self, mod: ModInfo, node: ast.AST) -> bool:
        """Is this node inside any guarded region (with-lock, locking
        decorator, or guard-only method)?"""
        if _held_with_locks(mod, node):
            return True
        ci = _enclosing_class(mod, node)
        if ci is not None:
            m = _enclosing_method(mod, node, ci)
            if m is not None:
                d = _discipline(mod, ci)
                if d.effectively_guarded(m) is not None:
                    return True
        return False

    def _index_call_sites(self) -> None:
        for mod in self.modules:
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    self.call_sites.setdefault(node.func.attr, []).append(
                        (mod, node, self._site_guarded(mod, node)))

    def all_sites_guarded(self, method_name: str) -> bool:
        """True when every attribute call site of `method_name` in the
        whole universe is inside a guarded region (caller-holds-lock
        discipline: the callee does not need its own lock)."""
        sites = self.call_sites.get(method_name)
        if not sites:
            return True  # never called through an attribute: unreachable
        return all(g for _m, _n, g in sites)

    # -- lock acquisition graph (GT08) --------------------------------------

    def _class_index(self) -> Dict[str, Tuple[ModInfo, ClassInfo]]:
        out: Dict[str, Tuple[ModInfo, ClassInfo]] = {}
        for mod in self.modules:
            for name, ci in mod.classes.items():
                out.setdefault(name, (mod, ci))
        return out

    def _local_types(self, fn: ast.AST,
                     classes: Dict[str, Tuple[ModInfo, ClassInfo]],
                     ci: Optional[ClassInfo]) -> Dict[str, str]:
        """Local / field variable -> class name, from annotations
        (`cache: KafkaFeatureCache = ...`), constructor assignments and
        the enclosing class's typed fields."""
        out: Dict[str, str] = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.AnnAssign)
                    and isinstance(node.target, ast.Name)):
                ann = node.annotation
                if isinstance(ann, ast.Name) and ann.id in classes:
                    out[node.target.id] = ann.id
                elif (isinstance(ann, ast.Constant)
                      and isinstance(ann.value, str)
                      and ann.value in classes):
                    out[node.target.id] = ann.value
            elif (isinstance(node, ast.Assign) and len(node.targets) == 1
                  and isinstance(node.targets[0], ast.Name)
                  and isinstance(node.value, ast.Call)
                  and isinstance(node.value.func, ast.Name)
                  and node.value.func.id in classes):
                out[node.targets[0].id] = node.value.func.id
        return out

    def _callee_acquisitions(
        self, mod: ModInfo, call: ast.Call,
        classes: Dict[str, Tuple[ModInfo, ClassInfo]],
        ci: Optional[ClassInfo],
        local_types: Dict[str, str],
    ) -> Set[str]:
        """Lock ids a call may acquire, via typed receivers only."""
        fn = call.func
        if not isinstance(fn, ast.Attribute):
            return set()
        target_cls: Optional[str] = None
        recv = fn.value
        recv_attr = _self_attr(recv)
        if recv_attr is not None and ci is not None:
            if recv_attr in ci.field_types:
                target_cls = ci.field_types[recv_attr]
            elif recv_attr == "self":
                target_cls = ci.name
        elif isinstance(recv, ast.Name):
            if recv.id == "self" and ci is not None:
                target_cls = ci.name
            else:
                target_cls = local_types.get(recv.id)
        if target_cls is None or target_cls not in classes:
            return set()
        tmod, tci = classes[target_cls]
        d = _discipline(tmod, tci)
        return {f"{tci.name}.{a}"
                for a in d.acquires.get(fn.attr, set())}

    def _lock_graph(self) -> None:
        classes = self._class_index()
        for mod in self.modules:
            for fn in _functions(mod):
                ci = _enclosing_class(mod, fn)
                method = (_enclosing_method(mod, fn, ci)
                          if ci is not None else None)
                held_base: List[str] = []
                if ci is not None and method is not None:
                    d = _discipline(mod, ci)
                    lk = d.effectively_guarded(method)
                    if lk is not None:
                        held_base.append(f"{ci.name}.{lk}")
                local_types = self._local_types(fn, classes, ci)
                for node in _own_nodes(fn):
                    held = held_base + _held_with_locks(mod, node)
                    if not held:
                        continue
                    acquired: Set[str] = set()
                    if isinstance(node, ast.With):
                        # held comes from ANCESTOR withs only, so every
                        # lock item of this with is a fresh acquisition
                        for item in node.items:
                            lid = _lock_id(mod, item.context_expr, ci)
                            if lid is not None:
                                acquired.add(lid)
                        for h in held:
                            for a in acquired:
                                if a != h:
                                    self.edges.setdefault(
                                        (h, a), (mod, node))
                        continue
                    if isinstance(node, ast.Call):
                        acquired = self._callee_acquisitions(
                            mod, node, classes, ci, local_types)
                        for h in held:
                            for a in acquired:
                                if a != h:
                                    self.edges.setdefault(
                                        (h, a), (mod, node))

    def _cycles(self) -> Set[Tuple[str, str]]:
        """Edges that participate in a cycle (SCC with >= 2 nodes)."""
        graph: Dict[str, Set[str]] = {}
        for a, b in self.edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on: Set[str] = set()
        stack: List[str] = []
        sccs: List[Set[str]] = []
        counter = [0]

        def strong(v: str) -> None:
            work = [(v, iter(sorted(graph[v])))]
            index[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on.add(v)
            while work:
                node, it = work[-1]
                advanced = False
                for w in it:
                    if w not in index:
                        index[w] = low[w] = counter[0]
                        counter[0] += 1
                        stack.append(w)
                        on.add(w)
                        work.append((w, iter(sorted(graph[w]))))
                        advanced = True
                        break
                    if w in on:
                        low[node] = min(low[node], index[w])
                if advanced:
                    continue
                work.pop()
                if work:
                    parent = work[-1][0]
                    low[parent] = min(low[parent], low[node])
                if low[node] == index[node]:
                    scc = set()
                    while True:
                        w = stack.pop()
                        on.discard(w)
                        scc.add(w)
                        if w == node:
                            break
                    if len(scc) >= 2:
                        sccs.append(scc)

        for v in sorted(graph):
            if v not in index:
                strong(v)
        bad: Set[Tuple[str, str]] = set()
        for scc in sccs:
            for a, b in self.edges:
                if a in scc and b in scc:
                    bad.add((a, b))
        return bad


def _uses_names(node: ast.AST, names: Set[str]) -> bool:
    return any(isinstance(s, ast.Name) and s.id in names
               for s in ast.walk(node))


def _functions(mod: ModInfo):
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _own_nodes(fn: ast.AST):
    """Nodes of fn excluding nested function bodies (each nested def is
    walked on its own by _functions)."""
    skip: Set[int] = set()
    for n in ast.walk(fn):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)) and n is not fn:
            for sub in ast.walk(n):
                if sub is not n:
                    skip.add(id(sub))
    for n in ast.walk(fn):
        if id(n) not in skip and n is not fn:
            yield n


def _concurrency_index(project) -> ConcurrencyIndex:
    idx = getattr(project, "_gt_concurrency", None)
    if idx is None:
        idx = ConcurrencyIndex(project.modules + project.ref_modules)
        project._gt_concurrency = idx
    return idx


# -- GT07: inconsistent lock discipline within a class -----------------------


def gt07(mod: ModInfo, project) -> Iterator[Finding]:
    """In a class that owns a lock: a field guarded by the lock in one
    method but accessed bare in another (torn read / lost update), or a
    container field mutated with no guard at all. Fields written only in
    __init__ are immutable and exempt; private helpers whose every call
    site holds the lock count as guarded (fixpoint)."""
    for ci in mod.classes.values():
        if not ci.lock_attrs and not ci.cond_attrs:
            continue
        d = _discipline(mod, ci)
        by_field: Dict[str, List[_Access]] = {}
        for a in d.accesses:
            by_field.setdefault(a.field, []).append(a)
        for field, accs in sorted(by_field.items()):
            non_init = [a for a in accs
                        if a.method != "__init__"
                        and a.method not in d.init_only]
            writes = [a for a in non_init if a.kind in ("write", "mutate")]
            if not writes:
                continue  # immutable after construction
            guarded = [a for a in non_init if a.guarded]
            unguarded = [a for a in non_init if not a.guarded]
            if not unguarded:
                continue
            lock = sorted(ci.lock_attrs)[0] if ci.lock_attrs else \
                sorted(ci.cond_attrs.values())[0]
            seen: Set[str] = set()
            if guarded:
                for a in unguarded:
                    if a.method in seen:
                        continue
                    seen.add(a.method)
                    yield _finding(
                        "GT07", mod, a.node,
                        f"field '{field}' of {ci.name} is guarded by "
                        f"self.{lock} elsewhere but {_verb(a.kind)} "
                        f"without it in {a.method!r}: torn read / lost "
                        f"update under the serve threads")
            else:
                for a in unguarded:
                    if a.kind != "mutate" or a.method in seen:
                        continue
                    seen.add(a.method)
                    yield _finding(
                        "GT07", mod, a.node,
                        f"container field '{field}' of lock-owning class "
                        f"{ci.name} is mutated in {a.method!r} without "
                        f"self.{lock}: racy against the guarded methods")


def _verb(kind: str) -> str:
    return {"read": "read", "write": "written",
            "mutate": "mutated"}[kind]


# -- GT08: lock-order cycles -------------------------------------------------


def gt08(mod: ModInfo, project) -> Iterator[Finding]:
    """Project-wide lock acquisition graph: `with A: ... with B:` (or a
    call into a lock-taking method of a typed field) adds edge A->B; any
    cycle is a deadlock waiting for the right interleaving. Findings
    anchor at each acquisition edge inside the scanned module."""
    idx = _concurrency_index(project)
    for (a, b) in sorted(idx.cyclic_edges):
        emod, enode = idx.edges[(a, b)]
        if emod is not mod:
            continue
        cycle = _cycle_text(idx, a, b)
        yield _finding(
            "GT08", mod, enode,
            f"lock-order cycle: {a} is held while acquiring {b}, but the "
            f"reverse order also exists ({cycle}): deadlock risk")


def _cycle_text(idx: ConcurrencyIndex, a: str, b: str) -> str:
    rev = [(x, y) for (x, y) in idx.cyclic_edges if x != a or y != b]
    parts = [f"{a} -> {b}"] + [f"{x} -> {y}" for x, y in sorted(rev)]
    return ", ".join(parts[:4])


# -- GT09: blocking call while holding a lock --------------------------------


def gt09(mod: ModInfo, project) -> Iterator[Finding]:
    """Blocking operations inside a guarded region serialize every other
    thread contending for the lock behind device dispatches, file I/O or
    sleeps — the direct throughput killer for the serve dispatch path."""
    jit_names = _project_jit_names(mod)
    for fn in _functions(mod):
        ci = _enclosing_class(mod, fn)
        base_guard = False
        if ci is not None:
            m = _enclosing_method(mod, fn, ci)
            if m is not None:
                base_guard = _discipline(mod, ci).effectively_guarded(m) \
                    is not None
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            if not base_guard and not _held_with_locks(mod, node):
                continue
            hit = _blocking_hit(mod, node, jit_names, ci)
            if hit is not None:
                yield _finding(
                    "GT09", mod, node,
                    f"{hit} while holding a lock: every contending "
                    f"thread stalls behind it (move it outside the "
                    f"guarded region or waive with a justification)")


def _blocking_hit(mod: ModInfo, call: ast.Call, jit_names: Set[str],
                  ci: Optional[ClassInfo]) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
        if f.id == "open":
            return "file I/O (open)"
        if f.id == "to_device":
            return "device upload (to_device)"
        if f.id == "sleep":
            return "sleep"
        if f.id in jit_names:
            return f"device dispatch ({f.id})"
        return None
    if not isinstance(f, ast.Attribute):
        return None
    recv = _expr_name(f.value).lower()
    if f.attr in _BLOCKING_ATTRS:
        return f"device sync ({f.attr})"
    if f.attr == "to_device":
        return "device upload (to_device)"
    if f.attr == "sleep" and isinstance(f.value, ast.Name) \
            and f.value.id in mod.time_aliases:
        return "sleep"
    if f.attr == "result" and "fut" in recv:
        return "future .result()"
    if f.attr == "join" and any(s in recv
                                for s in ("thread", "worker", "proc")):
        return "thread join"
    if f.attr in ("get", "put") and "queue" in recv:
        return f"queue .{f.attr}()"
    if f.attr == "wait":
        attr = _self_attr(f.value)
        if attr is not None and ci is not None \
                and attr in ci.cond_attrs:
            # waiting on a condition releases its own tied lock — only a
            # FOREIGN lock held around the wait blocks
            return None
        return "blocking .wait()"
    if f.attr in jit_names:
        return f"device dispatch ({f.attr})"
    return None


def _project_jit_names(mod: ModInfo) -> Set[str]:
    names = getattr(mod, "_gt_project_jit_names", None)
    if names is not None:
        return names
    return {jd.name for jd in mod.jit_defs}


# -- GT10: per-call lock -----------------------------------------------------


def gt10(mod: ModInfo, project) -> Iterator[Finding]:
    """A lock created as a function local and only used inside that same
    call guards nothing — every caller gets a fresh lock. Orchestrators
    that hand the lock to worker closures/threads are exempt."""
    for fn in _functions(mod):
        spawns = any(
            mod.is_thread_ctor(n)
            or (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                and "Executor" in n.func.id)
            or (isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in ("submit", "map")
                and not isinstance(n.func.value, ast.Constant))
            for n in ast.walk(fn))
        if spawns:
            continue
        locals_: Dict[str, ast.AST] = {}
        for node in _own_nodes(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and mod.is_lock_ctor(node.value)):
                locals_[node.targets[0].id] = node
        if not locals_:
            continue
        escaped: Set[str] = set()
        for name in locals_:
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)) and node is not fn:
                    if any(isinstance(s, ast.Name) and s.id == name
                           for s in ast.walk(node)):
                        escaped.add(name)
                elif isinstance(node, ast.Return) and node.value is not None:
                    if any(isinstance(s, ast.Name) and s.id == name
                           for s in ast.walk(node.value)):
                        escaped.add(name)
                elif isinstance(node, ast.Assign):
                    if (any(not isinstance(t, ast.Name)
                            for t in node.targets)
                            and isinstance(node.value, ast.Name)
                            and node.value.id == name):
                        escaped.add(name)
                elif isinstance(node, ast.Call):
                    if any(isinstance(a, ast.Name) and a.id == name
                           for a in node.args):
                        escaped.add(name)
        for name, node in sorted(locals_.items(),
                                 key=lambda kv: kv[1].lineno):
            if name in escaped:
                continue
            yield _finding(
                "GT10", mod, node,
                f"lock {name!r} is created per-call inside "
                f"{fn.name!r} and never escapes: every caller gets a "
                f"fresh lock, so it guards nothing (make it an instance "
                f"or module attribute)")


# -- GT11: callback / set_result under a lock --------------------------------


def gt11(mod: ModInfo, project) -> Iterator[Finding]:
    """Resolving a future or invoking a caller-supplied callback while
    holding a lock runs unknown consumer code inside the critical
    section: if that consumer takes the same lock (or a lock ordered
    before it), it deadlocks; at best it stretches the hold time."""
    for fn in _functions(mod):
        ci = _enclosing_class(mod, fn)
        base_guard = False
        if ci is not None:
            m = _enclosing_method(mod, fn, ci)
            if m is not None:
                base_guard = _discipline(mod, ci).effectively_guarded(m) \
                    is not None
        params = set()
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        listener_loops = _listener_loop_vars(fn)
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            if not base_guard and not _held_with_locks(mod, node):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in (
                    "set_result", "set_exception"):
                yield _finding(
                    "GT11", mod, node,
                    f"future .{f.attr}() under a lock: done-callbacks "
                    f"and waiters run inside the critical section "
                    f"(resolve futures after releasing the lock)")
            elif isinstance(f, ast.Name) and (
                    (f.id in params and _callbackish(f.id))
                    or f.id in listener_loops):
                yield _finding(
                    "GT11", mod, node,
                    f"callback {f.id!r} invoked while holding a lock: "
                    f"its consumer may take the same lock (deadlock) or "
                    f"stretch the critical section")


def _callbackish(name: str) -> bool:
    low = name.lower()
    return low.startswith("on_") or any(
        s in low for s in _CALLBACK_MARKERS)


def _listener_loop_vars(fn: ast.AST) -> Set[str]:
    """`for cb in self._listeners: cb(...)` — loop vars drawn from
    listener/callback-named fields."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.For):
            continue
        src = _expr_name(node.iter).lower()
        if any(s in src for s in _CALLBACK_MARKERS):
            out |= {n.id for n in ast.walk(node.target)
                    if isinstance(n, ast.Name)}
    return out


# -- GT12: unguarded shared mutable state ------------------------------------


def gt12(mod: ModInfo, project) -> Iterator[Finding]:
    """Three shapes of shared state mutated from thread-reachable code
    with no guard: (a) mutable default arguments that the body mutates,
    (b) module-global containers (or `global` rebinds) mutated outside
    any lock, (c) container fields of LOCK-FREE classes whose mutating
    methods have at least one unguarded call site (classes whose every
    call site holds a caller's lock follow the caller-holds-lock
    discipline and are exempt)."""
    idx = _concurrency_index(project)
    yield from _gt12_defaults(mod)
    yield from _gt12_globals(mod, idx)
    yield from _gt12_classes(mod, idx)


def _gt12_defaults(mod: ModInfo) -> Iterator[Finding]:
    for fn in _functions(mod):
        args = fn.args
        pos = args.posonlyargs + args.args
        defaults = args.defaults
        pairs = list(zip(pos[len(pos) - len(defaults):], defaults))
        pairs += [(a, d) for a, d in zip(args.kwonlyargs, args.kw_defaults)
                  if d is not None]
        for arg, default in pairs:
            if not isinstance(default, (ast.List, ast.Dict, ast.Set)):
                continue
            if _mutates_name(fn, arg.arg):
                yield _finding(
                    "GT12", mod, default,
                    f"mutable default argument {arg.arg!r} of "
                    f"{fn.name!r} is mutated in the body: one shared "
                    f"instance across ALL calls and threads")


def _mutates_name(fn: ast.AST, name: str) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr in MUTATORS:
            if isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == name:
                return True
        elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            if isinstance(node.value, ast.Name) and node.value.id == name:
                return True
        elif isinstance(node, ast.AugAssign):
            t = node.target
            if isinstance(t, ast.Name) and t.id == name:
                return True
            if isinstance(t, ast.Subscript) and isinstance(
                    t.value, ast.Name) and t.value.id == name:
                return True
    return False


def _gt12_globals(mod: ModInfo, idx: ConcurrencyIndex) -> Iterator[Finding]:
    globals_: Set[str] = set()
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            v = node.value
            container = isinstance(v, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                and v.func.id in ("dict", "list", "set", "deque",
                                  "defaultdict", "OrderedDict"))
            if container:
                globals_.add(node.targets[0].id)
    for fn in _functions(mod):
        if not idx.func_reached(fn):
            continue
        declared = {n for s in ast.walk(fn) if isinstance(s, ast.Global)
                    for n in s.names}
        seen: Set[str] = set()
        for node in _own_nodes(fn):
            name = None
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) \
                    and node.func.attr in MUTATORS \
                    and isinstance(node.func.value, ast.Name):
                name = node.func.value.id
            elif isinstance(node, ast.Subscript) and isinstance(
                    node.ctx, (ast.Store, ast.Del)) \
                    and isinstance(node.value, ast.Name):
                name = node.value.id
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id in declared:
                name = node.targets[0].id
            if name is None or name in seen:
                continue
            if name not in globals_ and name not in declared:
                continue
            if _local_shadow(fn, name) and name not in declared:
                continue
            if _held_with_locks(mod, node):
                continue
            seen.add(name)
            yield _finding(
                "GT12", mod, node,
                f"module global {name!r} mutated from thread-reachable "
                f"{fn.name!r} with no lock held: lost updates / torn "
                f"state under concurrent callers")


def _local_shadow(fn: ast.AST, name: str) -> bool:
    """Is `name` rebound as a plain local anywhere in fn (so the
    mutation touches a local, not the module global)?"""
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return True
        elif isinstance(node, (ast.For,)):
            if any(isinstance(n, ast.Name) and n.id == name
                   for n in ast.walk(node.target)):
                return True
    return False


def _gt12_classes(mod: ModInfo, idx: ConcurrencyIndex) -> Iterator[Finding]:
    for ci in mod.classes.values():
        if ci.lock_attrs or ci.cond_attrs:
            continue  # lock-owning classes are GT07's jurisdiction
        if not idx.class_reached(ci.name):
            continue
        if idx.class_confined(ci.name):
            continue  # instances never leave one call frame
        d = _discipline(mod, ci)
        seen: Set[Tuple[str, str]] = set()
        for a in d.accesses:
            if a.kind != "mutate" or a.guarded:
                continue
            if a.method == "__init__" or a.method in d.init_only:
                continue
            if (a.method, a.field) in seen:
                continue
            if idx.all_sites_guarded(a.method):
                continue  # caller-holds-lock discipline
            seen.add((a.method, a.field))
            yield _finding(
                "GT12", mod, a.node,
                f"lock-free class {ci.name} is reached from thread "
                f"entry points but {a.method!r} mutates shared field "
                f"'{a.field}' with no guard: add a lock or confine "
                f"instances to one thread (waive with justification)")


CONCURRENCY_RULES = {
    "GT07": gt07, "GT08": gt08, "GT09": gt09,
    "GT10": gt10, "GT11": gt11, "GT12": gt12,
}
