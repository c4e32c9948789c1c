"""Static per-kernel VMEM-footprint estimates for every pl.pallas_call site.

TPU cores have ~16 MiB of VMEM; Pallas double-buffers pipelined in/out
blocks, so the resident footprint of a kernel invocation is roughly

    2 * (sum of in-spec block bytes + sum of out-spec block bytes)
      + scratch bytes.

The estimator evaluates each BlockSpec/scratch shape expression
symbolically from the AST: enclosing-function parameter defaults
(``block_s=2048``), one level of local assignments (``bw = block_s //
32``), module constants, and — for dims only known at run time (``n``,
``w``, ``s``, ...) — a documented assumption table. Every assumption used
is recorded in the emitted row, so the numbers are honest estimates, not
measurements: they ride into the BENCH trajectories as ``mode="static"``
rows (``python -m repro.analysis --emit-vmem``) to seed the kernel
autotuning campaign with a cheap, always-current capacity model.
"""
from __future__ import annotations

import ast
import importlib.util
import os

from .astutil import call_name, const_int
from .engine import Project, load_project
from .rules.pallas import PallasSite, iter_pallas_sites

VMEM_BUDGET_BYTES = 16 * 1024 * 1024     # ~16 MiB/core (Pallas TPU guide)

# run-time dims with no static default anywhere: the documented estimate
# basis (n/s/q match the repo's n=64 gate configs, R = n*q the child one-hot
# width there; w the default window)
ASSUMED_DIMS = {"n": 64, "s": 4, "q": 3, "R": 192, "w": 8, "Q": 81,
                "n_planes": 3,
                "D": 128, "P": 3, "C": 256, "W": 8192, "S": 262144,
                "m": 4096, "BH": 8, "Tq": 2048, "Tk": 2048}

_DTYPE_BYTES = {"float64": 8, "int64": 8, "float32": 4, "int32": 4,
                "uint32": 4, "f32": 4, "bfloat16": 2, "float16": 2,
                "int16": 2, "uint16": 2, "int8": 1, "uint8": 1, "bool_": 1}


class _Unresolved(Exception):
    def __init__(self, name: str):
        super().__init__(name)
        self.name = name


class _Env:
    """Name -> int resolution: local assigns, param defaults, module
    constants, then the assumption table (recording what was assumed)."""

    def __init__(self, site: PallasSite):
        self.exprs: dict[str, ast.AST] = {}
        self.assumed: dict[str, int] = {}
        self._stack: set[str] = set()
        mod_tree = site.mod.tree
        for node in mod_tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self.exprs.setdefault(node.targets[0].id, node.value)
        fn = site.fn
        if fn is not None:
            a = fn.args
            pos = list(a.posonlyargs) + list(a.args)
            for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
                self.exprs[p.arg] = d
            for p, d in zip(a.kwonlyargs, a.kw_defaults):
                if d is not None:
                    self.exprs[p.arg] = d
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    self.exprs[node.targets[0].id] = node.value

    def lookup(self, name: str) -> int:
        if name in self._stack:
            raise _Unresolved(name)
        expr = self.exprs.get(name)
        if expr is not None:
            self._stack.add(name)
            try:
                return self.eval(expr)
            except _Unresolved:
                pass
            finally:
                self._stack.discard(name)
        if name in ASSUMED_DIMS:
            self.assumed[name] = ASSUMED_DIMS[name]
            return ASSUMED_DIMS[name]
        raise _Unresolved(name)

    def eval(self, node: ast.AST) -> int:
        v = const_int(node)
        if v is not None:
            return v
        if isinstance(node, ast.Name):
            return self.lookup(node.id)
        if isinstance(node, ast.BinOp):
            lh, rh = self.eval(node.left), self.eval(node.right)
            op = type(node.op)
            table = {ast.Add: lambda: lh + rh, ast.Sub: lambda: lh - rh,
                     ast.Mult: lambda: lh * rh,
                     ast.FloorDiv: lambda: lh // max(rh, 1),
                     ast.Div: lambda: lh // max(rh, 1),
                     ast.Mod: lambda: lh % max(rh, 1),
                     ast.Pow: lambda: lh ** rh}
            if op in table:
                return table[op]()
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -self.eval(node.operand)
        if isinstance(node, ast.Call):
            cn = (call_name(node) or "").rsplit(".", 1)[-1]
            if cn in {"min", "max"} and node.args:
                vals = [self.eval(a) for a in node.args]
                return min(vals) if cn == "min" else max(vals)
        raise _Unresolved(ast.dump(node)[:40])

    def shape_elems(self, shape: ast.AST) -> int:
        if isinstance(shape, (ast.Tuple, ast.List)):
            total = 1
            for e in shape.elts:
                total *= max(self.eval(e), 1)
            return total
        return max(self.eval(shape), 1)


def _dtype_bytes(node: ast.AST | None) -> int:
    name = ""
    if node is not None:
        for sub in ast.walk(node):
            d = sub if isinstance(sub, ast.Attribute) else None
            if d is not None and d.attr in _DTYPE_BYTES:
                name = d.attr
                break
    return _DTYPE_BYTES.get(name, 4)     # operand dtypes default to 4 B


def estimate_site(site: PallasSite) -> dict | None:
    """Static VMEM row for one pallas_call site (None if nothing to sum)."""
    env = _Env(site)

    def block_bytes(specs, dtypes) -> int:
        total = 0
        for spec, dt in zip(specs, dtypes):
            if spec.block is None:
                continue
            try:
                total += env.shape_elems(spec.block) * dt
            except _Unresolved:
                continue
        return total

    in_bytes = block_bytes(site.in_specs, [4] * len(site.in_specs))
    out_dtypes = [_dtype_bytes(s.args[1] if len(s.args) > 1 else
                               next((kw.value for kw in s.keywords
                                     if kw.arg == "dtype"), None))
                  for s in site.out_shapes]
    out_dtypes += [4] * (len(site.out_specs) - len(out_dtypes))
    out_bytes = block_bytes(site.out_specs, out_dtypes)
    scratch_bytes = 0
    for sc in site.scratch_shapes:
        if isinstance(sc, ast.Call) and sc.args:
            try:
                dt = _dtype_bytes(sc.args[1] if len(sc.args) > 1 else
                                  next((kw.value for kw in sc.keywords
                                        if kw.arg == "dtype"), None))
                scratch_bytes += env.shape_elems(sc.args[0]) * dt
            except _Unresolved:
                continue
    if not (in_bytes or out_bytes or scratch_bytes):
        return None
    total = 2 * (in_bytes + out_bytes) + scratch_bytes
    block = None
    for name in ("block_s", "block_m", "block_q", "block"):
        if name in env.exprs:
            try:
                block = env.eval(env.exprs[name])
                break
            except _Unresolved:
                pass
    return {
        "mode": "static",
        "variant": site.kernel_name,
        "block": block,
        "kernel_path": site.mod.relpath,
        "vmem_in_bytes": in_bytes,
        "vmem_out_bytes": out_bytes,
        "vmem_scratch_bytes": scratch_bytes,
        "vmem_bytes": total,
        "vmem_mib": round(total / 2**20, 4),
        "vmem_frac_of_budget": round(total / VMEM_BUDGET_BYTES, 5),
        "double_buffered": True,
        "assumed_dims": dict(sorted(env.assumed.items())),
    }


def estimate_project(project: Project) -> list[dict]:
    rows = []
    for site in iter_pallas_sites(project):
        row = estimate_site(site)
        if row is not None:
            rows.append(row)
    return rows


def _bench_file_for(row: dict) -> str:
    """order_score kernels ride the MCMC trajectory; the count / fused /
    flash kernels are all upstream-of-sampler compute and ride the
    preprocess trajectory."""
    return ("BENCH_mcmc" if "order_score" in row["kernel_path"]
            else "BENCH_preprocess")


def emit_vmem_rows(paths: list[str], root: str | None = None,
                   save=None) -> list[dict]:
    """Estimate every scanned kernel and merge the rows into the BENCH
    trajectories via benchmarks/common.save (config-keyed merge: the static
    rows land BESIDE the measured rows, never on top of them)."""
    project = load_project(paths, root)
    rows = estimate_project(project)
    if save is None:
        common = os.path.join(project.root, "benchmarks", "common.py")
        spec = importlib.util.spec_from_file_location("_bnlint_bench_common",
                                                      common)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        save = mod.save
    by_file: dict[str, list[dict]] = {}
    for row in rows:
        by_file.setdefault(_bench_file_for(row), []).append(row)
    for name, file_rows in sorted(by_file.items()):
        save(name, file_rows)
    return rows
