"""GT01..GT06 rule implementations.

Each rule is a generator ``rule(mod, project) -> Iterator[Finding]``.
Rules never import the code under analysis; everything is answered from
the per-module AST index (`ModInfo`) and the cross-module name universe
(`Project`). Precision notes live next to each rule — the gate runs with
--fail-on warn, so a rule that cries wolf on the shipped tree is a bug
here, not in the tree.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from geomesa_tpu.analysis.model import Finding
from geomesa_tpu.analysis.modinfo import JitDef, ModInfo

_SYNC_ATTRS = {"block_until_ready", "item", "tolist"}
_SYNC_NP_FNS = {"asarray", "array"}
_HOST_CAST_BUILTINS = {"float", "int", "bool"}
_VALID_WORD = "valid"


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _finding(rule: str, mod: ModInfo, node: ast.AST, msg: str) -> Finding:
    return Finding(rule=rule, path=mod.relpath,
                   line=getattr(node, "lineno", 0),
                   col=getattr(node, "col_offset", 0), message=msg)


def _callee_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _map_call_args(call: ast.Call, jd: JitDef):
    """Yield (param_name_or_None, value_node) for each argument."""
    for i, a in enumerate(call.args):
        name = jd.params[i] if i < len(jd.params) else None
        yield name, i, a
    for kw in call.keywords:
        yield kw.arg, None, kw.value


# -- GT01: retrace storms ---------------------------------------------------


def gt01(mod: ModInfo, project) -> Iterator[Finding]:
    """Static jit arguments fed loop-varying or unhashable values.

    (a) a `for` loop variable passed to a static param retraces every
    iteration; (b) a list/set/dict literal passed to a static param is
    unhashable and fails (or, for tuples of arrays, retraces per call).
    `while` grow-loops (pow2 capacity style) are deliberately exempt —
    they bound their own retrace count.
    """
    for call in ast.walk(mod.tree):
        if not isinstance(call, ast.Call):
            continue
        callee = _callee_name(call)
        jd = project.jit_by_name.get(callee) if callee else None
        if jd is None:
            continue
        statics = jd.static_params()
        loop_vars = _enclosing_for_targets(mod, call)
        for pname, pos, value in _map_call_args(call, jd):
            is_static = (pname in statics) or (pos is not None
                                               and pos in jd.static_nums)
            if not is_static:
                continue
            if isinstance(value, (ast.List, ast.Set, ast.Dict)):
                yield _finding(
                    "GT01", mod, value,
                    f"unhashable {type(value).__name__.lower()} literal "
                    f"passed to static argument "
                    f"{pname or pos!r} of jitted {callee!r}")
            elif isinstance(value, ast.Name) and value.id in loop_vars:
                yield _finding(
                    "GT01", mod, call,
                    f"loop variable {value.id!r} passed to static argument "
                    f"{pname or pos!r} of jitted {callee!r}: retraces every "
                    f"iteration")


def _enclosing_for_targets(mod: ModInfo, node: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for anc in mod.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        if isinstance(anc, ast.For):
            out |= {n.id for n in ast.walk(anc.target)
                    if isinstance(n, ast.Name)}
    return out


# -- GT02: implicit host transfers inside jit scope -------------------------


def gt02(mod: ModInfo, project) -> Iterator[Finding]:
    """Host operations on traced values inside a jitted function body:
    `np.asarray`/`np.array` on a tracer, `float()`/`int()`/`bool()`
    or `.item()`/`.tolist()` on a tracer, and Python `for` loops
    iterating a tracer. Static params are excluded (they are Python
    values at trace time)."""
    for jd in mod.jit_defs:
        if jd.func is None:
            continue
        tracers = set(jd.params) - jd.static_params()
        if not tracers:
            continue
        for node in ast.walk(jd.func):
            if isinstance(node, ast.Call):
                hit = _gt02_call_hit(mod, node, tracers)
                if hit:
                    yield _finding("GT02", mod, node,
                                   f"{hit} on traced value inside jitted "
                                   f"{jd.name!r}: forces a device->host "
                                   f"transfer per call")
            elif isinstance(node, ast.For):
                if _names_in(node.iter) & tracers:
                    yield _finding(
                        "GT02", mod, node,
                        f"host `for` loop over traced value in jitted "
                        f"{jd.name!r}: unrolls/transfers instead of "
                        f"staying on device")


def _gt02_call_hit(mod: ModInfo, call: ast.Call,
                   tracers: Set[str]) -> Optional[str]:
    f = call.func
    args_names = set()
    for a in call.args:
        args_names |= _names_in(a)
    if not (args_names & tracers):
        # .item() takes no args; check the receiver instead
        if (isinstance(f, ast.Attribute) and f.attr in ("item", "tolist")
                and _names_in(f.value) & tracers):
            return f".{f.attr}()"
        return None
    if isinstance(f, ast.Attribute) and mod.is_numpy_ref(f.value):
        if f.attr in _SYNC_NP_FNS | {"frombuffer", "copy"}:
            return f"np.{f.attr}()"
    if isinstance(f, ast.Name) and f.id in _HOST_CAST_BUILTINS:
        return f"{f.id}()"
    return None


# -- GT03: dtype drift ------------------------------------------------------


def gt03(mod: ModInfo, project) -> Iterator[Finding]:
    """float64 indicators inside jitted bodies or module-local helpers
    transitively called from them — the f32 kernel paths. An explicit
    `# gt: f64-refine` comment on the line (or the line above) waives
    the deliberate refine arithmetic."""
    kernel_fns = _f32_kernel_functions(mod)
    seen: Set[int] = set()
    for fn in kernel_fns:
        for node in ast.walk(fn):
            hit = _f64_indicator(mod, node)
            if hit is None or node.lineno in seen:
                continue
            seen.add(node.lineno)
            yield _finding(
                "GT03", mod, node,
                f"{hit} reachable from f32 kernel path {fn.name!r} "
                f"(waive deliberate refinement with '# gt: f64-refine')")


def _f32_kernel_functions(mod: ModInfo) -> List[ast.FunctionDef]:
    roots = [jd.func for jd in mod.jit_defs if jd.func is not None]
    out: List[ast.FunctionDef] = []
    seen: Set[str] = set()
    queue = list(roots)
    while queue:
        fn = queue.pop()
        if fn.name in seen:
            continue
        seen.add(fn.name)
        out.append(fn)
        for call in ast.walk(fn):
            if isinstance(call, ast.Call):
                name = _callee_name(call)
                target = mod.functions.get(name) if name else None
                if target is not None and target.name not in seen:
                    queue.append(target)
    return out


def _f64_indicator(mod: ModInfo, node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and node.attr == "float64":
        if isinstance(node.value, ast.Name) and (
                mod.is_numpy_ref(node.value) or mod.is_jnp_ref(node.value)):
            return f"{node.value.id}.float64"
    if (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value == "float64"):
        return "'float64' literal"
    return None


# -- GT04: unsynced timing --------------------------------------------------


def gt04(mod: ModInfo, project) -> Iterator[Finding]:
    """A timestamp pair bracketing a device dispatch with no sync
    (`block_until_ready`, `jax.device_get`, `np.asarray`/`np.array`,
    `.item()`, `float()`/`int()`) between the dispatch and the closing
    timestamp measures dispatch, not compute. Events are collected in
    source order per function; nested defs are separate scopes."""
    order = {"device": 0, "sync": 1, "timer": 2}
    for fn in _all_functions(mod):
        # same-line ordering: a sync wrapping a device call on one line
        # (`np.asarray(kern(x))`) synchronizes it, so device events sort
        # before syncs; timers close the line
        events = sorted(_gt04_events(mod, fn),
                        key=lambda e: (e[1], order[e[0]]))
        saw_timer = False
        pending: Optional[Tuple[int, str]] = None
        for kind, line, detail in events:
            if kind == "timer":
                if saw_timer and pending is not None:
                    yield Finding(
                        rule="GT04", path=mod.relpath, line=line, col=0,
                        message=(f"timestamp at line {line} closes a timing "
                                 f"window over device call {pending[1]!r} "
                                 f"(line {pending[0]}) with no "
                                 f"block_until_ready/sync in between"))
                saw_timer = True
                pending = None
            elif kind == "device" and saw_timer:
                pending = (line, detail)
            elif kind == "sync":
                pending = None


def _all_functions(mod: ModInfo):
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _gt04_events(mod: ModInfo, fn: ast.FunctionDef):
    own_nested = {n for n in ast.walk(fn)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda))
                  and n is not fn}
    skip: Set[int] = set()
    for n in own_nested:
        for sub in ast.walk(n):
            skip.add(id(sub))
    for node in ast.walk(fn):
        if id(node) in skip or not isinstance(node, ast.Call):
            continue
        line = node.lineno
        if mod.is_timer_call(node):
            yield ("timer", line, "")
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in _SYNC_ATTRS or f.attr == "device_get":
                yield ("sync", line, f.attr)
                continue
            if (f.attr in _SYNC_NP_FNS and isinstance(f.value, ast.Name)
                    and mod.is_numpy_ref(f.value)):
                yield ("sync", line, f"np.{f.attr}")
                continue
        if isinstance(f, ast.Name) and f.id in _HOST_CAST_BUILTINS:
            yield ("sync", line, f.id)
            continue
        callee = _callee_name(node)
        if callee is None:
            continue
        if callee.lstrip("_").startswith("sync"):
            # sync()/_sync() wrapper idiom of timing scripts
            yield ("sync", line, callee)
            continue
        resolved = _resolve_local_def(mod, node, callee)
        if resolved is not None:
            def_node, is_jit = resolved
            if is_jit:
                yield ("device", line, callee)
            elif isinstance(def_node, ast.FunctionDef) and _body_syncs(
                    mod, def_node):
                yield ("sync", line, callee)
            # plain local call without syncs: neither device nor sync —
            # that function is linted on its own
            continue
        if callee in project_jit_names(mod):
            yield ("device", line, callee)


def _local_defs(mod: ModInfo):
    """name -> [(def_node, enclosing_function|None, is_jit)] for every
    function def and jit-binding assignment in the module, with the
    scope each lives in. Cached per module."""
    cache = getattr(mod, "_gt_local_defs", None)
    if cache is not None:
        return cache
    defs = {}
    jitted_fn_nodes = {id(jd.func) for jd in mod.jit_defs
                       if jd.kind == "function" and jd.func is not None}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(
                (node, mod.enclosing_function(node),
                 id(node) in jitted_fn_nodes))
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
              and isinstance(node.value, ast.Call)
              and mod._jit_call_parts(node.value) is not None):
            t = node.targets[0]
            name = t.id if isinstance(t, ast.Name) else (
                t.attr if isinstance(t, ast.Attribute) else None)
            if name is not None:
                defs.setdefault(name, []).append(
                    (node, mod.enclosing_function(node), True))
    mod._gt_local_defs = defs  # type: ignore[attr-defined]
    return defs


def _resolve_local_def(mod: ModInfo, call: ast.Call, name: str):
    """Resolve `name` at this call site to the nearest definition in the
    call's lexical scope chain (innermost wins; within a scope, the
    last definition at or before the call line). Returns
    (def_node, is_jit) or None. This is what keeps a nested plain
    `run()` closure distinct from a module-level `run = jax.jit(...)`
    three functions away (a timing script's shape)."""
    cands = _local_defs(mod).get(name)
    if not cands:
        return None
    chain = [a for a in mod.ancestors(call)
             if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))]
    chain.append(None)  # module scope last
    for scope in chain:
        scoped = sorted((c for c in cands if c[1] is scope),
                        key=lambda c: c[0].lineno)
        if not scoped:
            continue
        pick = None
        for c in scoped:
            if c[0].lineno <= call.lineno:
                pick = c
        pick = pick or scoped[-1]
        return pick[0], pick[2]
    return None


def _body_syncs(mod: ModInfo, fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and (
                f.attr in _SYNC_ATTRS or f.attr == "device_get"):
            return True
        if isinstance(f, ast.Attribute) and f.attr in _SYNC_NP_FNS and \
                isinstance(f.value, ast.Name) and mod.is_numpy_ref(f.value):
            return True
        if isinstance(f, ast.Name) and f.id in _HOST_CAST_BUILTINS:
            return True
    return False


def project_jit_names(mod: ModInfo) -> Set[str]:
    # populated by the linter before rules run (project-wide jit names);
    # falling back to the module's own defs keeps ModInfo usable alone
    names = getattr(mod, "_gt_project_jit_names", None)
    if names is not None:
        return names
    return {jd.name for jd in mod.jit_defs}


# -- GT05: dead jit entry points --------------------------------------------


def gt05(mod: ModInfo, project) -> Iterator[Finding]:
    """A jitted definition nobody references is a stale entry point:
    it keeps a compile cache alive and rots silently when signatures
    drift. References are counted across the scan roots plus the repo's
    tests/bench/scripts (name loads, attribute loads, import aliases,
    and __all__ exports)."""
    for jd in mod.jit_defs:
        refs = project.reference_count(jd.name)
        if refs == 0:
            yield Finding(
                rule="GT05", path=mod.relpath, line=jd.line, col=0,
                message=(f"jitted callable {jd.name!r} has no call sites "
                         f"anywhere in the scanned tree: dead entry point"))


# -- GT06: inconsistent mask plumbing ---------------------------------------


def gt06(mod: ModInfo, project) -> Iterator[Finding]:
    """Within one function, sibling call sites of the same callee whose
    results are mask-combined: if one site ANDs a validity token
    (`*.valid`, `valid`, `row_valid`, ...) into its result and another
    does not, an invalid row can be resurrected on the second path —
    the planner cache-branch band-scatter bug, generalized."""
    for fn in _all_functions(mod):
        sites = _gt06_sites(mod, fn)
        by_callee = {}
        for site in sites:
            by_callee.setdefault(site["callee"], []).append(site)
        for callee, group in by_callee.items():
            if len(group) < 2:
                continue
            with_valid = [s for s in group if s["valid"]]
            without = [s for s in group if not s["valid"]]
            if not with_valid or not without:
                continue
            for s in without:
                yield Finding(
                    rule="GT06", path=mod.relpath,
                    line=s["line"], col=s["col"],
                    message=(f"call site of {callee!r} does not AND a "
                             f"validity mask into its result, but its "
                             f"sibling at line {with_valid[0]['line']} "
                             f"does: invalid rows can leak through this "
                             f"branch"))


def _gt06_sites(mod: ModInfo, fn: ast.FunctionDef):
    """Call sites inside `fn` whose results are bound to names: each gets
    a signature `valid` = does any `&`-combination of a bound name, in
    this site's block (the call statement and its following siblings),
    involve a validity token."""
    sites = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or not isinstance(
                node.value, ast.Call):
            continue
        callee = _callee_name(node.value)
        if callee is None:
            continue
        bound: Set[str] = set()
        for t in node.targets:
            if isinstance(t, ast.Name):
                bound.add(t.id)
            elif isinstance(t, ast.Tuple):
                bound |= {e.id for e in t.elts if isinstance(e, ast.Name)}
        if not bound:
            continue
        region = _region_after(mod, fn, node)
        sites.append({
            "callee": callee, "line": node.value.lineno,
            "col": node.value.col_offset,
            "valid": _valid_anded(region, bound),
        })
    return sites


def _region_after(mod: ModInfo, fn: ast.FunctionDef,
                  stmt: ast.stmt) -> List[ast.stmt]:
    """The statement list containing `stmt`, from `stmt` onward — the
    site's block scope (masking applied in an unrelated earlier branch
    must not vouch for this one)."""
    # find the ancestor statement whose parent holds a stmt list with it
    target = stmt
    parent = mod.parent(target)
    while parent is not None and not isinstance(parent, (
            ast.FunctionDef, ast.AsyncFunctionDef, ast.If, ast.For,
            ast.While, ast.With, ast.Try, ast.Module)):
        target = parent
        parent = mod.parent(target)
    if parent is None:
        return [stmt]
    for blockname in ("body", "orelse", "finalbody"):
        block = getattr(parent, blockname, None)
        if isinstance(block, list) and target in block:
            i = block.index(target)
            return block[i:]
    return [stmt]


def _valid_anded(region: List[ast.stmt], bound: Set[str]) -> bool:
    for stmt in region:
        for node in ast.walk(stmt):
            expr = None
            if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                          ast.BitAnd):
                expr = node
            elif isinstance(node, ast.AugAssign) and isinstance(
                    node.op, ast.BitAnd):
                expr = node
            if expr is None:
                continue
            names = _names_in(expr)
            if not (names & bound):
                continue
            if _has_valid_token(expr):
                return True
    return False


def _has_valid_token(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        ident = None
        if isinstance(sub, ast.Name):
            ident = sub.id
        elif isinstance(sub, ast.Attribute):
            ident = sub.attr
        if ident is not None and _VALID_WORD in ident.lower():
            return True
    return False


# GT13 scope: the serving and planning layers — the paths a live request
# rides. Kernel modules (engine/) define their jits once at module level
# where the ExecutableRegistry's default sweep and the warmup manifests
# see them; a jax.jit created inside serve/ or plan/ is invisible to
# both, so its compile happens inline under traffic.
_GT13_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/plan/")


def gt13(mod: ModInfo, project) -> Iterator[Finding]:
    """GT13: jax.jit call sites on the serve/plan hot path.

    Flags every `jax.jit` use (decorator, `functools.partial(jax.jit,
    ...)` decorator, or direct call) in modules under the serve/plan
    prefixes. Precision: the rule is path-scoped, so engine kernels and
    the compilecache's own registry wrapper never fire; deliberate
    sites waive inline (`# gt: waive GT13`) like every other rule."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT13_PREFIXES):
        return
    seen: Set[int] = set()
    for node in ast.walk(mod.tree):
        hit = None
        if isinstance(node, ast.Call) and mod.is_jit_ref(node.func):
            hit = node
        elif (isinstance(node, ast.Call) and mod.is_partial_ref(node.func)
              and node.args and mod.is_jit_ref(node.args[0])):
            hit = node
        elif isinstance(node, ast.Attribute) and mod.is_jit_ref(node):
            hit = node  # @jax.jit decorator / bare jax.jit reference
        elif (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
              and mod.is_jit_ref(node)):
            hit = node  # @jit decorator via a from-import alias
        if hit is None or hit.lineno in seen:
            continue
        seen.add(hit.lineno)
        yield _finding(
            "GT13", mod, hit,
            "jax.jit on the serve/plan hot path bypasses the "
            "compilecache ExecutableRegistry: warmup manifests cannot "
            "pre-compile it, so it stalls a live request. Define the "
            "kernel in engine/ (the registry's default sweep) or "
            "register it explicitly; waive deliberate sites.")


# GT14 scope: the dependency-boundary layers the recovery fabric
# (geomesa_tpu.faults) covers. A swallowed exception there hides a
# failure the retry/breaker/quarantine machinery should have typed; an
# unbounded retry loop is the exact shape faults.retry_call exists to
# replace (bounded attempts, full-jitter backoff, deadline-aware).
_GT14_PREFIXES = ("geomesa_tpu/store/", "geomesa_tpu/kafka/",
                  "geomesa_tpu/serve/")

_GT14_BROAD = {"Exception", "BaseException"}


def _gt14_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler body discards the error: only pass /
    ellipsis statements. A body that logs, responds, returns a value or
    re-raises is handling, not swallowing; `continue` is the retry
    shape — the while-True branch owns that (flagging it here too would
    double-report every retry loop)."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Constant) and stmt.value.value is Ellipsis:
            continue
        return False
    return True


def _gt14_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except:
    names = []
    if isinstance(t, ast.Tuple):
        names = [e for e in t.elts]
    else:
        names = [t]
    for n in names:
        ident = n.id if isinstance(n, ast.Name) else (
            n.attr if isinstance(n, ast.Attribute) else None)
        if ident in _GT14_BROAD:
            return True
    return False


def gt14(mod: ModInfo, project) -> Iterator[Finding]:
    """GT14: silent error swallows + unbounded retry loops at the
    storage/Kafka/serve dependency boundaries.

    (a) `except:` / `except Exception:` whose body only passes —
    the failure vanishes instead of surfacing typed (or feeding the
    breaker/quarantine fabric). (b) a `while True:` loop with NO
    break/return anywhere in its body and no raise on its exception
    paths, wrapping a try whose handler swallows around at least one
    call — the retry-forever shape that ignores deadlines and retries
    permanent errors. Both waivable inline for the documented deliberate
    cases (the shipped tree is clean modulo those)."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT14_PREFIXES):
        return
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ExceptHandler):
            if _gt14_broad(node) and _gt14_swallows(node):
                yield _finding(
                    "GT14", mod, node,
                    "broad except swallows the error (body is only "
                    "pass): failures at a dependency boundary must "
                    "surface typed or feed the recovery fabric; waive "
                    "deliberate degrade paths inline")
        elif isinstance(node, ast.While):
            if not (isinstance(node.test, ast.Constant)
                    and bool(node.test.value)):
                continue
            if _gt14_has_exit(node):
                continue  # the loop has a non-exceptional exit
            body_nodes = list(_gt14_loop_nodes(node))
            for t in (n for n in body_nodes if isinstance(n, ast.Try)):
                has_io_call = any(isinstance(n, ast.Call)
                                  for s in t.body for n in ast.walk(s))
                swallowing = [h for h in t.handlers
                              if not any(isinstance(n, ast.Raise)
                                         for s in h.body
                                         for n in ast.walk(s))]
                if has_io_call and swallowing:
                    yield _finding(
                        "GT14", mod, node,
                        "unbounded `while True` retry loop: no "
                        "break/return and the except path swallows — "
                        "this retries forever past any deadline; use "
                        "faults.retry_call (bounded, jittered, "
                        "deadline-aware)")
                    break


def _gt14_loop_nodes(loop: ast.While):
    """Walk the loop body, not descending into nested function defs
    (their control flow is not the loop's)."""
    stack = list(loop.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        yield n
        for child in ast.iter_child_nodes(n):
            stack.append(child)


def _gt14_has_exit(loop: ast.While) -> bool:
    """True when the loop can exit non-exceptionally: a `return`
    anywhere in its body (returns leave the whole function, nested
    loops included), or a `break` belonging to THIS loop — a break
    inside a nested while/for's BODY exits only that inner loop and
    must not vouch for the outer one, but a break in a nested loop's
    `else:` clause targets the ENCLOSING loop (Python's for/else) and
    counts. Nested function defs are skipped."""
    stack = [(n, False) for n in loop.body]
    while stack:
        n, nested = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, ast.Return):
            return True
        if isinstance(n, ast.Break) and not nested:
            return True
        if isinstance(n, (ast.While, ast.For, ast.AsyncFor)):
            for child in n.body:
                stack.append((child, True))
            for child in n.orelse:  # for/else break targets OUR loop
                stack.append((child, nested))
            continue
        for child in ast.iter_child_nodes(n):
            stack.append((child, nested))
    return False


# GT15 scope: the layers whose timings feed spans, ServeEvents and the
# latency histograms — serve/, engine/ and the telemetry package
# itself. `time.time()` is wall clock: NTP steps it backward and slews
# it, so a duration measured with it can be negative or skewed; every
# span/latency in these layers must use perf_counter/monotonic. The
# second hazard is a tracer span opened without `with`: _LiveSpan only
# records (and pops the parent stack) on __exit__, so a bare
# `TRACER.span(...)` call leaks an unbalanced open span.
_GT15_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/engine/",
                  "geomesa_tpu/telemetry/")


def _gt15_is_time_time(mod: ModInfo, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if (isinstance(f, ast.Attribute) and f.attr == "time"
            and isinstance(f.value, ast.Name) and f.value.id == "time"):
        return True  # time.time()
    if isinstance(f, ast.Name) and f.id == "time":
        # bare time() — only when `from time import time` is in scope
        for imp in ast.walk(mod.tree):
            if isinstance(imp, ast.ImportFrom) and imp.module == "time":
                if any(a.name == "time" for a in imp.names):
                    return True
    return False


def _gt15_scopes(mod: ModInfo):
    """(scope_node, body_nodes) per function plus the module level, each
    excluding nested function bodies — a name assigned in one function
    never aliases the same-named local of another."""
    fns = list(_all_functions(mod))
    own = {id(f) for f in fns}
    for scope in [mod.tree] + fns:
        nodes = []
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            n = stack.pop()
            if id(n) in own:
                # a (possibly directly-seeded) nested/top-level def is
                # its OWN scope: never leak its body into this one — a
                # module-level `t0 = time.time()` timestamp must not
                # pair with an unrelated `x - t0` in some function
                continue
            nodes.append(n)
            for child in ast.iter_child_nodes(n):
                stack.append(child)
        yield scope, nodes


def gt15(mod: ModInfo, project) -> Iterator[Finding]:
    """GT15: wall-clock durations + un-scoped spans (telemetry layers).

    (a) `time.time()` whose result feeds a subtraction — directly
    (`time.time() - t0`) or via a name later used as a `-` operand in
    the same scope. Plain timestamping (`event.ts = time.time()`) is
    fine: the wall clock is the right clock for *when*, never for *how
    long*. (b) a `.span(...)` call that is not the context expression
    of a `with` item (or an `enter_context(...)` argument)."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT15_PREFIXES):
        return
    flagged: Set[int] = set()
    for _scope, nodes in _gt15_scopes(mod):
        timed_names: dict = {}  # name -> time.time() call line
        subs = []
        for n in nodes:
            if (isinstance(n, ast.Assign) and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                    and _gt15_is_time_time(mod, n.value)):
                timed_names[n.targets[0].id] = n.value.lineno
            elif isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub):
                subs.append(n)
        for sub in subs:
            for operand in (sub.left, sub.right):
                for c in ast.walk(operand):
                    if _gt15_is_time_time(mod, c) and \
                            c.lineno not in flagged:
                        flagged.add(c.lineno)
                        yield _finding(
                            "GT15", mod, c,
                            "time.time() used in a subtraction: wall "
                            "clock measures *when*, not *how long* — "
                            "use time.perf_counter()/monotonic() for "
                            "durations")
            names = _names_in(sub)
            for name in sorted(names & set(timed_names)):
                line = timed_names[name]
                if line in flagged:
                    continue
                flagged.add(line)
                yield Finding(
                    rule="GT15", path=mod.relpath, line=line, col=0,
                    message=(f"time.time() result {name!r} measures a "
                             f"duration (subtracted at line "
                             f"{sub.lineno}): wall clock is not "
                             f"monotonic — use perf_counter/monotonic"))
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "span"):
            continue
        parent = mod.parent(node)
        if isinstance(parent, ast.withitem) and parent.context_expr is node:
            continue
        if (isinstance(parent, ast.Call)
                and isinstance(parent.func, ast.Attribute)
                and parent.func.attr == "enter_context"):
            continue
        yield _finding(
            "GT15", mod, node,
            "tracer span opened outside a `with` block: spans record "
            "only on __exit__, so this leaks an unbalanced open span "
            "(wrap in `with TRACER.span(...)`, or waive a deliberate "
            "manual open)")


# GT16 scope: the pipelined dispatch path (serve/pipeline.py). The
# pipeline's whole point is that prepare/transfer/launch return before
# the device finishes — window N+1's host work overlaps window N's
# kernel. A blocking call inside those stages (block_until_ready, a
# future .result(), an explicit jax.device_get host read) re-serializes
# exactly the host gap the pipeline exists to remove, and it does so
# silently: results stay correct, only the overlap dies. Blocking is
# the COMPLETER's job (the sync stage). Waivable inline for documented
# deliberate syncs; the shipped tree is clean.
_GT16_PATH = "geomesa_tpu/serve/pipeline.py"
_GT16_STAGE_MARKERS = ("prepare", "transfer", "launch")
_GT16_STAGE_NAMES = {"submit"}
_GT16_BLOCKING = {
    "block_until_ready": "device sync",
    "result": "future wait",
    "device_get": "host read",
}


def _gt16_stage_functions(mod: ModInfo):
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        name = node.name.lstrip("_")
        if name in _GT16_STAGE_NAMES or any(
                m in name for m in _GT16_STAGE_MARKERS):
            yield node


def gt16(mod: ModInfo, project) -> Iterator[Finding]:
    """GT16: blocking calls inside pipeline prepare/transfer/launch
    stages.

    Flags `.block_until_ready()`, `.result()` (futures; `set_result` is
    a resolve, not a wait, and is not matched) and `jax.device_get` /
    bare `device_get` calls lexically inside the stage functions of
    serve/pipeline.py (names containing prepare/transfer/launch, plus
    `submit`). `np.asarray` on a device array blocks too but is
    indistinguishable statically from legitimate host stacking — use
    the explicit `jax.device_get` spelling for intentional reads so
    this rule can see them (and waive)."""
    path = mod.relpath.replace("\\", "/")
    if _GT16_PATH not in path:
        return
    seen: Set[int] = set()
    for fn in _gt16_stage_functions(mod):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            ident = None
            if isinstance(f, ast.Attribute):
                ident = f.attr
            elif isinstance(f, ast.Name):
                ident = f.id if f.id == "device_get" else None
            what = _GT16_BLOCKING.get(ident or "")
            if what is None or node.lineno in seen:
                continue
            seen.add(node.lineno)
            yield _finding(
                "GT16", mod, node,
                f"blocking call ({ident}: {what}) inside pipeline stage "
                f"{fn.name!r}: prepare/transfer/launch must return "
                f"before the device finishes — window overlap dies "
                f"silently otherwise. Move the wait to the completer's "
                f"sync stage, or waive a documented deliberate sync")


# GT17 scope: subscription listener/callback bodies under subscribe/
# and kafka/. KafkaFeatureCache listeners are invoked during the
# store's poll fold — with the store RLock held — and the subscribe
# evaluator's delta listener runs on EVERY folded message. A blocking
# call there (file I/O, a future .result(), a device sync/transfer, a
# sleep) stalls the fold for every topic consumer behind the lock and
# re-introduces exactly the emit-under-lock hazard the _emit snapshot
# discipline removed. Listeners BUFFER; the post-fold pump (outside
# the lock) evaluates. Two detection axes: functions whose names mark
# them as listener/callback bodies (contains "listener"/"callback",
# or an `on_*` prefix), and local functions passed by name to
# add_listener(...)/add_fold_hook(...).
_GT17_PREFIXES = ("geomesa_tpu/subscribe/", "geomesa_tpu/kafka/")
_GT17_NAME_MARKERS = ("listener", "callback")
_GT17_NAME_PREFIXES = ("on_",)
_GT17_REGISTER_CALLS = {"add_listener", "add_fold_hook"}
_GT17_BLOCKING = {
    "open": "file I/O",
    "result": "future wait",
    "block_until_ready": "device sync",
    "device_get": "host read",
    "device_put": "device transfer",
    "to_device": "device transfer",
    "sleep": "sleep",
    "poll": "broker poll (re-entrant fold)",
}


def _gt17_listener_functions(mod: ModInfo):
    """Functions that are listener/callback bodies: marker-named defs
    plus defs whose NAME is passed to a listener-registration call."""
    registered: Set[str] = set()
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _GT17_REGISTER_CALLS):
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    registered.add(arg.id)
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        name = node.name.lstrip("_")
        if (name in registered or node.name in registered
                or any(m in name for m in _GT17_NAME_MARKERS)
                or any(name.startswith(p) for p in _GT17_NAME_PREFIXES)):
            yield node


def gt17(mod: ModInfo, project) -> Iterator[Finding]:
    """GT17: blocking calls inside subscription listener/callback
    bodies (subscribe//kafka/ scope).

    Flags `open(...)`, `.result()` (future wait), `.block_until_ready()`,
    `jax.device_get`/`device_put`, `to_device(...)`, `.sleep(...)` and
    `.poll(...)` (a listener re-entering the fold) lexically inside
    listener-shaped functions: names containing listener/callback, an
    `on_*` prefix, or local defs registered via `add_listener`/
    `add_fold_hook`. The listener contract is buffer-only — evaluation
    and device work belong in the post-fold pump, which the store runs
    OUTSIDE its lock. Waivable inline (`# gt: waive GT17`) for a
    documented deliberate block."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT17_PREFIXES):
        return
    seen: Set[int] = set()
    for fn in _gt17_listener_functions(mod):
        # the registration-site walk sees nested defs too, so a
        # listener factory's inner closure is covered either way
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                ident = f.attr
            elif isinstance(f, ast.Name):
                ident = f.id
            else:
                continue
            what = _GT17_BLOCKING.get(ident)
            if what is None or node.lineno in seen:
                continue
            seen.add(node.lineno)
            yield _finding(
                "GT17", mod, node,
                f"blocking call ({ident}: {what}) inside subscription "
                f"listener/callback {fn.name!r}: listeners run inside "
                f"the Kafka fold (store lock held) and must only "
                f"buffer — move the work to the post-fold pump "
                f"(subscribe/evaluator.py), or waive a documented "
                f"deliberate block")


# GT18 scope: the serve and plan layers (serve/, plan/). The sharded
# serving contract (docs/SERVING.md "Sharded serving") is that data
# placement happens ONCE, declaratively, via NamedSharding — the mesh
# superbatch upload, the stager's replicated slots, the planner's
# row-sharding re-pins. Hand-rolled per-device placement — a loop
# device_put-ing slices onto each chip, or `jax.devices()[i]` indexing
# to pick a chip ad hoc — bypasses that: XLA can no longer fuse the
# transfer, ownership stops matching the DeviceCacheManager's recorded
# tile map, and the AOT executables' parameter shardings stop matching
# the data (a silent per-dispatch reshard). The shard-affinity route
# picks its chip from the superbatch's OWNERSHIP map (mesh.devices),
# which this rule deliberately does not match. Waivable inline for a
# documented deliberate placement; the shipped tree is clean.
_GT18_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/plan/")
_GT18_DEVICES_FNS = {"devices", "local_devices"}
_GT18_TRANSFER_FNS = {"device_put", "to_device"}


def _gt18_devices_call(node: ast.AST) -> bool:
    """True for `jax.devices()` / `jax.local_devices()` (attribute or
    bare-name call form — `from jax import devices` included)."""
    if not isinstance(node, ast.Call):
        return False
    name = _callee_name(node)
    return name in _GT18_DEVICES_FNS


def gt18(mod: ModInfo, project) -> Iterator[Finding]:
    """GT18: per-device placement that bypasses NamedSharding
    (serve//plan/ scope).

    Two shapes: (a) a `for`/`while` loop over a device list (the
    iterable mentions `jax.devices()`/`local_devices()` or an alias
    assigned from one, or the loop target is named `dev`/`device`)
    whose body calls `device_put`/`to_device` — the per-chip upload
    loop `parallel.mesh.shard_device_batch` exists to replace; and
    (b) subscripting a `jax.devices()`/`local_devices()` call or an
    alias of one (`jax.devices()[0]`, `devs = jax.devices();
    devs[i]`) — ad-hoc chip selection that ignores the mesh and the
    cache's tile-ownership map. Both waivable inline
    (`# gt: waive GT18`) for a documented deliberate placement."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT18_PREFIXES):
        return
    # alias forms: names assigned (anywhere in the module) from a
    # devices() call — `devs = jax.devices()` — tracked by name; the
    # serve/plan modules are small enough that scope-insensitive
    # aliasing stays precise (no false positives on the shipped tree)
    aliases: Set[str] = set()
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Assign)
                and _gt18_devices_call(node.value)):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    aliases.add(t.id)
        elif (isinstance(node, (ast.AnnAssign, ast.NamedExpr))
                and node.value is not None
                and _gt18_devices_call(node.value)):
            t = node.target
            if isinstance(t, ast.Name):
                aliases.add(t.id)

    def mentions_devices(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            if _gt18_devices_call(n):
                return True
            if isinstance(n, ast.Name) and n.id in aliases:
                return True
        return False

    seen: Set[int] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.For):
            targets = _names_in(node.target)
            dev_loop = (mentions_devices(node.iter)
                        or targets & {"dev", "device"})
            if not dev_loop:
                continue
            for inner in ast.walk(node):
                if (isinstance(inner, ast.Call)
                        and _callee_name(inner) in _GT18_TRANSFER_FNS
                        and inner.lineno not in seen):
                    seen.add(inner.lineno)
                    yield _finding(
                        "GT18", mod, inner,
                        f"per-device {_callee_name(inner)} loop: "
                        f"placement belongs to ONE NamedSharding "
                        f"device_put (parallel.mesh.shard_device_batch "
                        f"/ store.cache mesh superbatch) — a per-chip "
                        f"upload loop bypasses the recorded tile "
                        f"ownership and cannot fuse; waive a "
                        f"documented deliberate placement")
        elif isinstance(node, ast.Subscript):
            v = node.value
            # jax.devices()[i] directly, or an alias devs[i]
            direct = _gt18_devices_call(v)
            aliased = isinstance(v, ast.Name) and v.id in aliases
            if (direct or aliased) and node.lineno not in seen:
                seen.add(node.lineno)
                yield _finding(
                    "GT18", mod, node,
                    "jax.devices()[i] indexing: ad-hoc chip selection "
                    "ignores the serving mesh and the device cache's "
                    "tile-ownership map — place data with NamedSharding "
                    "over parallel.mesh (shard-affinity routes read "
                    "ownership from the superbatch); waive a documented "
                    "deliberate selection")


# GT19 scope: the serve and telemetry layers — the modules that emit
# the Prometheus series dashboards and the SLO engine scrape. The
# metrics registry keys series by name + sorted labels, so two call
# sites emitting ONE family with DIFFERENT label-key sets silently
# fork it: `serve.requests{kind,status}` here, `serve.requests{kind}`
# there renders one family with incompatible schemas — strict scrapers
# reject it, PromQL joins on the missing label silently drop samples,
# and the unlabeled twin shadows the labeled series in sum() without
# anyone deciding that. The fix is always to pick ONE label schema per
# family (or a new family name); the rule points at every minority
# call site.
_GT19_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/telemetry/")
_GT19_EMITTERS = {
    # registry method -> keyword params that are NOT labels
    "counter": {"inc"},
    "gauge": {"value"},
    "histogram": set(),
    "timer": set(),
}


def _gt19_sites(mod: ModInfo):
    """(family, label-key frozenset, call node) for every literal-name
    metric emission in `mod`. Dynamic names (f-strings — the per-
    breaker gauges) and **splat label dicts are skipped: their label
    schema is not statically comparable."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in _GT19_EMITTERS
                and isinstance(f.value, ast.Name)
                and f.value.id == "metrics"):
            continue
        if not node.args:
            continue
        name = node.args[0]
        if not (isinstance(name, ast.Constant)
                and isinstance(name.value, str)):
            continue
        non_labels = _GT19_EMITTERS[f.attr]
        if any(kw.arg is None for kw in node.keywords):
            continue  # **labels splat: schema unknowable statically
        labels = frozenset(kw.arg for kw in node.keywords
                           if kw.arg not in non_labels)
        yield name.value, labels, node


def gt19(mod: ModInfo, project) -> Iterator[Finding]:
    """GT19: one metric family, different label-key sets across call
    sites (serve//telemetry/ scope).

    The family index is built once per lint run over every in-scope
    scanned module (cached on the project; fixture runs with
    project=None index just the module under test). For a family whose
    sites disagree, the MAJORITY label set (ties: the first site in
    path/line order) is taken as the schema and every other site is
    flagged. Waivable inline (`# gt: waive GT19`) for a documented
    deliberate fork."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT19_PREFIXES):
        return
    if project is not None:
        index = getattr(project, "_gt19_index", None)
        if index is None:
            index = {}
            for m in project.modules:
                mp = m.relpath.replace("\\", "/")
                if not any(p in mp for p in _GT19_PREFIXES):
                    continue
                for fam, labels, node in _gt19_sites(m):
                    index.setdefault(fam, []).append(
                        (mp, node.lineno, labels))
            project._gt19_index = index  # type: ignore[attr-defined]
    else:
        index = {}
        for fam, labels, node in _gt19_sites(mod):
            index.setdefault(fam, []).append(
                (path, node.lineno, labels))
    for fam, labels, node in _gt19_sites(mod):
        sites = index.get(fam, ())
        schemas = {ls for _, _, ls in sites}
        if len(schemas) <= 1:
            continue
        # majority schema; ties break to the first site in file order
        counts: dict = {}
        for _, _, ls in sites:
            counts[ls] = counts.get(ls, 0) + 1
        best = max(counts.values())
        winners = [ls for ls in counts if counts[ls] == best]
        if len(winners) == 1:
            schema = winners[0]
        else:
            schema = next(ls for _, _, ls in sorted(sites)
                          if ls in winners)
        if labels == schema:
            continue
        others = sorted({f"{p}:{ln}" for p, ln, ls in sites
                         if ls == schema})
        yield _finding(
            "GT19", mod, node,
            f"metric family {fam!r} emitted with labels "
            f"{{{', '.join(sorted(labels)) or 'none'}}} here but "
            f"{{{', '.join(sorted(schema)) or 'none'}}} at "
            f"{', '.join(others[:3])}: the series forks and "
            f"scrapes/joins break — pick one label schema per family "
            f"(or a distinct family name), or waive a documented "
            f"deliberate fork")


# GT20 scope: the fleet tier + the wire protocol it multiplexes. One
# unbounded blocking socket call in the router wedges EVERY client
# behind one dead replica (the reader thread never returns, pendings
# never redistribute); in a replica it wedges drain. The fleet/wire.py
# discipline is: every socket carries a timeout (settimeout, or
# create_connection(timeout=...)), reads poll with a stop event.
_GT20_PREFIXES = ("geomesa_tpu/fleet/", "geomesa_tpu/serve/protocol.py")
_GT20_BLOCKING = {"connect", "recv", "recv_into", "accept"}


def _gt20_recv_name(node: ast.AST) -> Optional[str]:
    """Dotted receiver of an attribute chain (`self._sock` for
    `self._sock.recv(...)`), or None when not statically nameable."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _gt20_create_connection(call: ast.Call) -> bool:
    """True for socket.create_connection(...) / create_connection(...)."""
    f = call.func
    return ((isinstance(f, ast.Attribute)
             and f.attr == "create_connection")
            or (isinstance(f, ast.Name)
                and f.id == "create_connection"))


def gt20(mod: ModInfo, project) -> Iterator[Finding]:
    """GT20: socket connect/recv without a timeout (fleet scope).

    Flags (a) `X.connect(...)` / `X.recv(...)` / `X.recv_into` /
    `X.accept(...)` where no `X.settimeout(...)` appears for the same
    dotted receiver anywhere in the module (cross-method: a socket
    configured in __init__ and read in a loop is fine), and (b)
    `socket.create_connection(addr)` without a timeout (second
    positional or `timeout=` keyword). A module that calls
    `socket.setdefaulttimeout(...)` is exempt wholesale — the global
    default bounds every socket it creates. Waivable inline
    (`# gt: waive GT20`) for a documented deliberate block."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT20_PREFIXES):
        return
    safe: Set[str] = set()
    default_timeout = False
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "settimeout":
            name = _gt20_recv_name(f.value)
            if name is not None:
                safe.add(name)
        elif (isinstance(f, ast.Attribute)
                and f.attr == "setdefaulttimeout"):
            default_timeout = True
    if default_timeout:
        return
    # names bound from a bounded create_connection are safe receivers
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _gt20_create_connection(node.value)
                and (len(node.value.args) >= 2
                     or any(kw.arg == "timeout"
                            for kw in node.value.keywords))):
            for t in node.targets:
                name = _gt20_recv_name(t)
                if name is not None:
                    safe.add(name)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if _gt20_create_connection(node):
            if (len(node.args) < 2
                    and not any(kw.arg == "timeout"
                                for kw in node.keywords)):
                yield _finding(
                    "GT20", mod, node,
                    "socket.create_connection without a timeout: an "
                    "unreachable replica blocks the caller forever — "
                    "pass timeout= (fleet/wire.connect_json does)")
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in _GT20_BLOCKING):
            continue
        name = _gt20_recv_name(f.value)
        if name is not None and name in safe:
            continue
        yield _finding(
            "GT20", mod, node,
            f"socket .{f.attr}() with no settimeout() on "
            f"{name or 'its receiver'} anywhere in this module: an "
            f"unbounded blocking call in the fleet tier wedges the "
            f"whole router behind one dead peer — call settimeout() "
            f"(poll + stop event, see fleet/wire.py), or waive a "
            f"documented deliberate block")


# GT21 scope: the layers that mint or consult result-cache keys. The
# cache contract (geomesa_tpu.approx.cache) keys on the CANONICAL CQL
# (ast.to_cql of the parsed filter); a site keying on raw filter TEXT
# silently forks the key space — equivalent spellings ("a=1 AND b=2" vs
# "a = 1  AND  b = 2") miss each other and a dashboard fleet's repeated
# queries become a cache-miss storm.
_GT21_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/approx/",
                  "geomesa_tpu/plan/")

# receivers that look like a result cache (dotted tail, lowercased)
_GT21_CACHE_NAMES = ("result_cache", "results_cache", "rcache")

_GT21_KEY_FNS = ("result_key", "cache_key")


def _gt21_raw_cql(node: ast.AST) -> Optional[ast.AST]:
    """First subexpression that reads RAW filter text: `<x>.cql`,
    `<x>["cql"]`, or `<x>.get("cql", ...)`."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "cql":
            return sub
        if isinstance(sub, ast.Subscript):
            sl = sub.slice
            if isinstance(sl, ast.Constant) and sl.value == "cql":
                return sub
        if (isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "get" and sub.args
                and isinstance(sub.args[0], ast.Constant)
                and sub.args[0].value == "cql"):
            return sub
    return None


def _gt21_recv_tail(expr: ast.AST) -> str:
    if isinstance(expr, ast.Attribute):
        return expr.attr.lower()
    if isinstance(expr, ast.Name):
        return expr.id.lower()
    return ""


def gt21(mod: ModInfo, project) -> Iterator[Finding]:
    """GT21: result-cache insertion/lookup sites keying on raw CQL
    text instead of the canonical form.

    Flags (a) calls to a cache-key builder (`result_key` /
    `cache_key`, bare or dotted) whose arguments read raw filter text
    (`<x>.cql`, `<x>["cql"]`, `<x>.get("cql")`), and (b) `.get()` /
    `.put()` / `.peek()` on a result-cache-shaped receiver
    (`*result_cache*`, `rcache`) whose key arguments do. The clean
    form passes the Query OBJECT (the builder canonicalizes through
    the AST) or `ast.to_cql(query.filter_ast)`. Waivable inline
    (`# gt: waive GT21`) for a documented deliberate raw-text key."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT21_PREFIXES):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        fname = (f.attr if isinstance(f, ast.Attribute)
                 else f.id if isinstance(f, ast.Name) else "")
        hit = None
        if fname in _GT21_KEY_FNS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                hit = _gt21_raw_cql(arg)
                if hit is not None:
                    break
        elif (fname in ("get", "put", "peek")
                and isinstance(f, ast.Attribute)
                and any(n in _gt21_recv_tail(f.value)
                        for n in _GT21_CACHE_NAMES)):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                hit = _gt21_raw_cql(arg)
                if hit is not None:
                    break
        if hit is None:
            continue
        yield _finding(
            "GT21", mod, node,
            "result-cache key built from RAW CQL text: equivalent "
            "filter spellings fork the key space into a cache-miss "
            "storm. Pass the Query object to approx.cache.result_key "
            "(it canonicalizes via ast.to_cql), or canonicalize "
            "explicitly with ast.to_cql(query.filter_ast); waive a "
            "documented deliberate raw-text key.")


# GT22 scope: the wire-encode layers — where bulk payloads (execute
# results, push frames) are serialized onto connections. The columnar
# wire (serve/columnar.py) exists precisely so the hot path never pays
# a Python dict + json.dumps PER ROW / PER SUBSCRIBER; this rule keeps
# the pattern from creeping back (docs/SERVING.md "Columnar wire").
_GT22_PREFIXES = ("geomesa_tpu/serve/", "geomesa_tpu/subscribe/")


def _gt22_is_dumps(call: ast.Call) -> bool:
    """True for json.dumps(...) / dumps(...)."""
    f = call.func
    return ((isinstance(f, ast.Attribute) and f.attr == "dumps")
            or (isinstance(f, ast.Name) and f.id == "dumps"))


def gt22(mod: ModInfo, project) -> Iterator[Finding]:
    """GT22: per-row serialization in a wire-encode loop.

    Flags, inside `geomesa_tpu/serve/` and `geomesa_tpu/subscribe/`:
    (a) a `json.dumps(...)` call lexically inside a `for`/`while`
    body — serializing row-by-row (or frame-by-frame per subscriber)
    is the N-encodes pattern the PushMux/columnar framing removed:
    encode ONCE outside the loop, or route through
    `serve.columnar.PushMux`; and (b) a dict comprehension nested
    inside a `for`/`while` body or as the element of a list/generator
    comprehension — materializing one Python dict per feature on the
    encode path (the columnar codecs keep rows in column buffers).
    Function/class boundaries reset the loop context: a helper that
    dumps once per CALL is fine even when its callers loop. Waivable
    inline (`# gt: waive GT22`) for a documented deliberate per-row
    encode (e.g. the bounded JSON fallback)."""
    path = mod.relpath.replace("\\", "/")
    if not any(p in path for p in _GT22_PREFIXES):
        return

    findings: List[Finding] = []

    def walk(node: ast.AST, in_loop: bool, in_comp: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                # new lexical scope: its body runs once per CALL, not
                # once per iteration of an enclosing loop
                walk(child, False, False)
                continue
            loop_here = in_loop or isinstance(child, (ast.For,
                                                      ast.While))
            comp_here = in_comp or isinstance(child, (ast.ListComp,
                                                      ast.GeneratorExp,
                                                      ast.SetComp))
            if (isinstance(child, ast.Call) and in_loop
                    and _gt22_is_dumps(child)):
                findings.append(_finding(
                    "GT22", mod, child,
                    "json.dumps inside a loop on the wire-encode "
                    "path: N rows (or N subscribers) pay N encodes — "
                    "encode ONCE outside the loop, ship the bulk "
                    "payload as a columnar frame "
                    "(serve/columnar.py), or fan push frames through "
                    "PushMux; waive a documented deliberate per-row "
                    "encode"))
            elif isinstance(child, ast.DictComp) and (in_loop
                                                      or in_comp):
                findings.append(_finding(
                    "GT22", mod, child,
                    "dict comprehension per loop iteration on the "
                    "wire-encode path: one Python dict per feature "
                    "is the row-materialization the columnar wire "
                    "removes — keep rows in column buffers "
                    "(serve/columnar.py codecs) and build dicts only "
                    "at the decode edge; waive a documented "
                    "deliberate per-row build"))
            walk(child, loop_here, comp_here)

    walk(mod.tree, False, False)
    yield from findings


# GT23 scope: the persistent serve loop's feed seam (serve/ringloop.py).
# The ring's whole point is that per-window Python work is ONLY a slot
# write + one pre-compiled dispatch — the harvest read belongs to the
# completer, and the slot write goes through QueryStager's designated
# staging path (which carries the retry fabric, the transfer fault site
# and the depth-R rotation contract). A blocking host sync inside the
# feed scope re-serializes the loop exactly like GT16's hazard, and a
# NAKED per-window device_put/to_device there bypasses the ring —
# un-rotated, un-metered, un-donated buffers that silently turn the
# ring back into per-window transfers. Same shape as GT16, extended
# with the transfer calls.
_GT23_PATH = "geomesa_tpu/serve/ringloop.py"
_GT23_MARKERS = ("feed", "slot")
_GT23_BLOCKING = {
    "block_until_ready": "device sync",
    "result": "future wait",
    "device_get": "host read",
    "device_put": "per-window device transfer (use the ring stager)",
    "to_device": "per-window device transfer (use the ring stager)",
}


def _gt23_feed_functions(mod: ModInfo):
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        name = node.name.lstrip("_")
        if any(m in name for m in _GT23_MARKERS):
            yield node


def gt23(mod: ModInfo, project) -> Iterator[Finding]:
    """GT23: blocking host sync or naked per-window transfer inside the
    ring feed loop scope.

    Flags `.block_until_ready()`, `.result()` (futures; `set_result`
    is a resolve and is not matched), `jax.device_get` / bare
    `device_get`, and `device_put` / `to_device` calls lexically inside
    the feed-scope functions of serve/ringloop.py (names containing
    feed/slot). The slot write must go through the stager's staging
    path (`.stage(...)` — retry fabric, fault site, depth-R rotation);
    blocking belongs to the completer's harvest. Waivable inline
    (`# gt: waive GT23`) for a documented deliberate call."""
    path = mod.relpath.replace("\\", "/")
    if _GT23_PATH not in path:
        return
    seen: Set[int] = set()
    for fn in _gt23_feed_functions(mod):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            ident = None
            if isinstance(f, ast.Attribute):
                ident = f.attr
            elif isinstance(f, ast.Name):
                ident = f.id if f.id in ("device_get", "device_put",
                                         "to_device") else None
            what = _GT23_BLOCKING.get(ident or "")
            if what is None or node.lineno in seen:
                continue
            seen.add(node.lineno)
            yield _finding(
                "GT23", mod, node,
                f"blocking/transfer call ({ident}: {what}) inside ring "
                f"feed scope {fn.name!r}: per-window work in the "
                f"persistent serve loop is ONLY a slot write through "
                f"the stager + one pre-compiled dispatch — a host sync "
                f"re-serializes the loop and a naked transfer bypasses "
                f"the ring's rotation/donation contract. Move waits to "
                f"the completer's harvest, transfers into the stager, "
                f"or waive a documented deliberate call")


from geomesa_tpu.analysis.concurrency import (  # noqa: E402
    CONCURRENCY_RULES)
from geomesa_tpu.analysis.spmd import SPMD_RULES  # noqa: E402
from geomesa_tpu.analysis.dataflow import DATAFLOW_RULES  # noqa: E402

ALL_RULES = {
    "GT01": gt01, "GT02": gt02, "GT03": gt03,
    "GT04": gt04, "GT05": gt05, "GT06": gt06,
    "GT13": gt13, "GT14": gt14, "GT15": gt15, "GT16": gt16,
    "GT17": gt17, "GT18": gt18, "GT19": gt19, "GT20": gt20,
    "GT21": gt21, "GT22": gt22, "GT23": gt23,
    **CONCURRENCY_RULES,
    **SPMD_RULES,
    **DATAFLOW_RULES,
}
