"""Host-sync-in-hot-path rule (family 2).

A ``.item()``, ``np.asarray``, ``float()``/``int()``/``bool()`` coercion or
``jax.device_get`` on a tracer inside traced code either fails at trace
time or — when it sneaks through on a concrete value — forces a blocking
device→host transfer per iteration. The hot set is computed by
reachability, mirroring how code actually becomes traced in this repo:

roots
  * jit-covered functions (decorator or module-level wrapper assignment);
  * functions passed to ``lax.scan`` / ``fori_loop`` / ``while_loop`` /
    ``cond`` / ``switch`` / ``lax.map`` / ``vmap`` / ``pmap`` /
    ``shard_map`` (through one level of ``functools.partial``);
  * kernel bodies passed to ``pl.pallas_call`` (again through partial);
  * closures handed to ``core.mcmc.make_traced_segment_runner`` (``step``,
    ``tap``, ``exchange``) at any call site;
  * closures RETURNED by ``make_*`` factory functions — the repo's
    convention for building traced callables (make_tap,
    make_sharded_score_fn, make_sharded_planes_fn, ...).

edges
  direct calls by (possibly imported) name, so helpers called from scan
  bodies are hot transitively.

Host-side boundary code (the collector, the run supervisor between
segments, checkpoint I/O) is deliberately NOT reachable from these roots —
``np.asarray`` there is the designed device→host drain, not a bug.
"""
from __future__ import annotations

import ast

from ..astutil import (call_name, import_map, jit_static_names,
                       jitted_functions, is_jit_expr, partial_aliases,
                       qualname)
from ..engine import Finding, Project

RULE = "hostsync-in-hot-path"

_TRACING_WRAPPERS = {
    "jax.lax.scan", "lax.scan", "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.while_loop", "lax.while_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch", "jax.lax.map", "lax.map",
    "jax.vmap", "vmap", "jax.pmap", "pmap", "shard_map",
    "jax.shard_map",
}
_PALLAS = {"pl.pallas_call", "pallas_call", "jax.experimental.pallas.pallas_call"}
_SEGMENT_RUNNER = "make_traced_segment_runner"

_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "jax.device_get", "onp.asarray", "onp.array"}
_SAFE_ATTR_TOKENS = {"shape", "ndim", "size", "dtype", "itemsize"}


class _Fn:
    """One call-graph node: a function def plus its name-resolution scope."""

    def __init__(self, mod, node: ast.AST, qual: str):
        self.mod = mod
        self.node = node
        self.qual = qual
        self.hot = False
        self.hot_via = ""


def _walk_own(node: ast.AST):
    """Walk a function's own body, excluding nested def/lambda subtrees."""
    stack = (list(node.body) if hasattr(node, "body")
             and not isinstance(node, ast.Lambda) else [node.body])
    while stack:
        cur = stack.pop()
        yield cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(cur))


def _collect_graph(project: Project):
    """Build nodes, name scopes, and the static-ish parameter sets."""
    nodes: dict[tuple[str, str], _Fn] = {}
    by_simple: dict[str, list[_Fn]] = {}
    mod_funcs: dict[str, dict[str, _Fn]] = {}

    for mod in project.modules:
        funcs: dict[str, _Fn] = {}
        for n in ast.walk(mod.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = _Fn(mod, n, qualname(n))
                nodes[(mod.relpath, fn.qual)] = fn
                by_simple.setdefault(n.name, []).append(fn)
                funcs.setdefault(n.name, fn)
        mod_funcs[mod.relpath] = funcs
    return nodes, by_simple, mod_funcs


def _callee_names(call: ast.Call, aliases: dict) -> list[str]:
    """Candidate function names referenced by a call argument position:
    plain Names, partial(...) wrappers, and partial-alias Names."""
    out = []
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        if isinstance(arg, ast.Name):
            tgt = aliases.get(arg.id, (arg.id, set()))[0]
            out.append(tgt)
        elif isinstance(arg, ast.Call) and \
                (call_name(arg) or "").endswith("partial") and arg.args \
                and isinstance(arg.args[0], ast.Name):
            out.append(arg.args[0].id)
    return out


def _mark(fn: _Fn, via: str, queue: list) -> None:
    if not fn.hot:
        fn.hot, fn.hot_via = True, via
        queue.append(fn)


def _static_params(fn_node: ast.AST, mod, aliases: dict) -> set[str]:
    """Parameters of ``fn_node`` that are bound as Python values at trace
    time: jit static_argnames, or keywords bound via functools.partial."""
    statics: set[str] = set()
    if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        a = fn_node.args
        for p in (*a.posonlyargs, *a.args, *a.kwonlyargs):
            ann = p.annotation
            if isinstance(ann, ast.Name) and ann.id in {"int", "float",
                                                        "bool", "str"}:
                statics.add(p.arg)       # scalar-annotated: static by contract
        for dec in fn_node.decorator_list:
            if is_jit_expr(dec):
                statics |= set(jit_static_names(dec))
        for name, (wrapped, bound) in aliases.items():
            if wrapped == fn_node.name:
                statics |= bound
        jm = jitted_functions(mod.tree)
        if fn_node.name in jm:
            statics |= set(jm[fn_node.name][1])
    return statics


def check_hostsync(project: Project) -> list[Finding]:
    nodes, by_simple, mod_funcs = _collect_graph(project)
    queue: list[_Fn] = []

    # --- roots
    for mod in project.modules:
        funcs = mod_funcs[mod.relpath]
        aliases = partial_aliases(mod.tree)
        jm = jitted_functions(mod.tree)
        for name, (fn_node, _) in jm.items():
            if fn_node is not None and fn_node.name in funcs:
                _mark(funcs[fn_node.name], "jit", queue)
        for n in ast.walk(mod.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    if is_jit_expr(dec):
                        key = (mod.relpath, qualname(n))
                        if key in nodes:
                            _mark(nodes[key], "jit", queue)
            if not isinstance(n, ast.Call):
                continue
            cn = call_name(n) or ""
            if cn in _TRACING_WRAPPERS or cn in _PALLAS \
                    or cn.rsplit(".", 1)[-1] == _SEGMENT_RUNNER:
                via = cn.rsplit(".", 1)[-1]
                for callee in _callee_names(n, aliases):
                    for cand in _resolve(callee, n, mod, by_simple,
                                         mod_funcs, project):
                        _mark(cand, via, queue)
        # closures returned by make_* factories run traced by convention
        for n in mod.tree.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n.name.startswith("make_"):
                inner = {f.name: f for f in ast.walk(n)
                         if isinstance(f, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))
                         and f is not n}
                for ret in ast.walk(n):
                    if isinstance(ret, ast.Return) \
                            and isinstance(ret.value, ast.Name) \
                            and ret.value.id in inner:
                        key = (mod.relpath, qualname(inner[ret.value.id]))
                        if key in nodes:
                            _mark(nodes[key], f"{n.name} factory", queue)

    # --- propagate over direct-call edges
    while queue:
        fn = queue.pop()
        aliases = partial_aliases(fn.node)
        for n in _walk_own(fn.node):
            if not isinstance(n, ast.Call):
                continue
            cn = call_name(n)
            if not cn:
                continue
            for cand in _resolve(cn, n, fn.mod, by_simple, mod_funcs,
                                 project):
                _mark(cand, f"called from {fn.qual}", queue)
            for callee in _callee_names(n, aliases):
                if cn in _TRACING_WRAPPERS or cn in _PALLAS:
                    for cand in _resolve(callee, n, fn.mod, by_simple,
                                         mod_funcs, project):
                        _mark(cand, f"traced arg in {fn.qual}", queue)

    # --- violations inside hot bodies
    findings = []
    for fn in nodes.values():
        if not fn.hot:
            continue
        mod_aliases = partial_aliases(fn.mod.tree)
        statics = _static_params(fn.node, fn.mod, mod_aliases)
        for _ in range(2):               # locals derived from static values
            for n in _walk_own(fn.node):
                if not isinstance(n, ast.Assign):
                    continue
                if not _safe_cast_arg(n.value, statics):
                    continue
                for tgt in n.targets:
                    elts = (tgt.elts if isinstance(tgt, (ast.Tuple, ast.List))
                            else [tgt])
                    for e in elts:
                        if isinstance(e, ast.Name):
                            statics.add(e.id)
        for n in _walk_own(fn.node):
            if not isinstance(n, ast.Call):
                continue
            cn = call_name(n) or ""
            bad = None
            if isinstance(n.func, ast.Attribute) and n.func.attr == "item" \
                    and not n.args:
                bad = ".item() host sync"
            elif cn in _SYNC_CALLS:
                bad = f"{cn}() device->host transfer"
            elif cn in {"float", "int", "bool"} and len(n.args) == 1 \
                    and not _safe_cast_arg(n.args[0], statics):
                bad = f"{cn}() coercion of a possibly-traced value"
            if bad:
                findings.append(Finding(
                    RULE, fn.mod.relpath, n.lineno,
                    f"{fn.qual}#{cn or 'item'}",
                    f"{bad} inside hot path '{fn.qual}' (hot via "
                    f"{fn.hot_via}): traced code must stay on device — "
                    "move the coercion to the host side of the segment "
                    "boundary or use jnp ops."))
    return findings


def _resolve(name: str, call: ast.Call, mod, by_simple, mod_funcs, project):
    """Resolve a (possibly dotted) callee name to candidate graph nodes."""
    out = []
    simple = name.rsplit(".", 1)[-1]
    if "." not in name:
        if name in mod_funcs.get(mod.relpath, {}):
            return [mod_funcs[mod.relpath][name]]
        imports = import_map(mod.tree, mod.package)
        target = imports.get(name)
        if target:
            rel = "src/" + target.replace(".", "/") + ".py"
            other = project.find(rel)
            if other is not None and other.relpath in mod_funcs:
                f = mod_funcs[other.relpath].get(simple)
                return [f] if f else []
        return []
    # dotted: alias.func — resolve the alias to a project module
    base = name.split(".")[0]
    imports = import_map(mod.tree, mod.package)
    target = imports.get(base)
    if target:
        rel = "src/" + target.replace(".", "/") + ".py"
        other = project.find(rel)
        if other is not None and other.relpath in mod_funcs:
            f = mod_funcs[other.relpath].get(simple)
            if f:
                out.append(f)
    return out


def _safe_cast_arg(arg: ast.AST, statics: set[str]) -> bool:
    """float()/int()/bool() args that are knowably NOT tracers: literals,
    len()/shape/dtype lookups, and trace-time-static parameters."""
    if isinstance(arg, ast.Constant):
        return True
    if isinstance(arg, ast.Name):
        return arg.id in statics
    if isinstance(arg, ast.Attribute):
        toks = set()
        cur: ast.AST = arg
        while isinstance(cur, ast.Attribute):
            toks.add(cur.attr)
            cur = cur.value
        return bool(toks & _SAFE_ATTR_TOKENS)
    if isinstance(arg, ast.Subscript):
        return _safe_cast_arg(arg.value, statics)
    if isinstance(arg, ast.Call):
        full = call_name(arg) or ""
        cn = full.rsplit(".", 1)[-1]
        if cn in {"len", "ord", "round", "abs", "min", "max"}:
            return all(_safe_cast_arg(a, statics) for a in arg.args)
        # host math on knowably-static values: np.log2(cap) where
        # cap = keys.shape[1] — pure Python/numpy arithmetic, no tracer
        if full.split(".")[0] in {"np", "numpy", "onp", "math"}:
            return bool(arg.args) and all(
                _safe_cast_arg(a, statics) for a in arg.args)
        return False
    if isinstance(arg, ast.BinOp):
        return _safe_cast_arg(arg.left, statics) \
            and _safe_cast_arg(arg.right, statics)
    return False


CHECKERS = [check_hostsync]
