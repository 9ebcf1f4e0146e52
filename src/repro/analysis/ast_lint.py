"""Layer 3: repo-specific AST rules over ``runtime/`` and ``models/``.

Works on source text only (no imports, no tracing):

* **AST001** — host-transfer calls (``.item()``, ``np.asarray``/
  ``np.array``, ``jax.device_get``/``device_put``,
  ``.block_until_ready()``) — or HOST RNG calls (``np.random.*``, the
  stdlib ``random`` module) — inside a *hot-path body*: any function
  statically reachable from the jitted serving roots
  (contracts.HOT_PATH_ROOTS) through a conservative call graph
  (module-level calls, imported-module calls, ``self.`` method calls).
  Host RNG in a jitted body is the sampling-era twin of a host
  transfer: the draw either bakes in at trace time or forces a
  callback round-trip, where the contract requires the device-side
  ``jax.random`` threefry keyed by (seed, position)
  (models/sampling.py).
* **AST002** — ``@`` / ``dot`` / ``einsum`` / ``dot_general`` inside
  a parity-critical body (contracts.PARITY_BODIES) that must phrase
  its contractions as explicit multiply+``jnp.sum``.
* **AST003** — a ``jax.jit``-ed body (method reference or lambda)
  reading mutable server state through ``self.<attr>``, where mutable
  means "assigned outside ``__init__``" — jit would freeze the value
  at trace time (the seed ``SlotServer`` frozen-``self.pos`` bug).
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis import contracts
from repro.analysis.report import Finding, Report

# attr names whose call is a host transfer when applied to arrays
_TRANSFER_METHODS = {"item", "block_until_ready"}
# numpy constructors that force device->host materialization
_NUMPY_TRANSFERS = {"asarray", "array", "frombuffer", "copyto", "save"}
# jax module-level explicit transfer APIs
_JAX_TRANSFERS = {"device_get", "device_put"}
# contraction entry points forbidden in parity-critical bodies
_DOT_CALLS = {"dot", "matmul", "einsum", "tensordot", "vdot", "inner",
              "dot_general"}


@dataclasses.dataclass
class ModuleInfo:
    name: str                                   # dotted module name
    path: str                                   # filesystem path
    tree: ast.Module
    mod_aliases: Dict[str, str]                 # local alias -> module
    func_imports: Dict[str, Tuple[str, str]]    # name -> (module, func)
    functions: Dict[str, ast.AST]               # qualname -> def node
    classes: Dict[str, ast.ClassDef]


def _module_name(path: str, repo_root: str) -> str:
    rel = os.path.relpath(os.path.abspath(path),
                          os.path.join(os.path.abspath(repo_root), "src"))
    if not rel.startswith(".."):
        return rel[:-3].replace(os.sep, ".")
    return os.path.splitext(os.path.basename(path))[0]


def parse_module(path: str, repo_root: str = ".") -> ModuleInfo:
    with open(path, "r") as fh:
        tree = ast.parse(fh.read(), filename=path)
    mod_aliases: Dict[str, str] = {}
    func_imports: Dict[str, Tuple[str, str]] = {}
    functions: Dict[str, ast.AST] = {}
    classes: Dict[str, ast.ClassDef] = {}
    for node in tree.body:
        if isinstance(node, ast.Import):
            for a in node.names:
                mod_aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                local = a.asname or a.name
                # `from pkg import mod` and `from pkg.mod import fn`
                # are indistinguishable statically; record both views
                # and let resolution try module-first.
                mod_aliases[local] = f"{node.module}.{a.name}"
                func_imports[local] = (node.module, a.name)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            functions[node.name] = node
        elif isinstance(node, ast.ClassDef):
            classes[node.name] = node
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    functions[f"{node.name}.{item.name}"] = item
    return ModuleInfo(_module_name(path, repo_root), path, tree,
                      mod_aliases, func_imports, functions, classes)


def _iter_calls(node: ast.AST) -> Iterable[ast.Call]:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _resolve_call(call: ast.Call, mod: ModuleInfo, cls: Optional[str],
                  modules: Dict[str, ModuleInfo]
                  ) -> Optional[Tuple[str, str]]:
    """(module_name, qualname) of the call target, or None."""
    f = call.func
    if isinstance(f, ast.Name):
        if f.id in mod.func_imports:
            m, fn = mod.func_imports[f.id]
            if m in modules and fn in modules[m].functions:
                return m, fn
        if f.id in mod.functions:
            return mod.name, f.id
        return None
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        base, attr = f.value.id, f.attr
        if base == "self" and cls is not None:
            q = f"{cls}.{attr}"
            if q in mod.functions:
                return mod.name, q
            return None
        target = mod.mod_aliases.get(base)
        if target and target in modules \
                and attr in modules[target].functions:
            return target, attr
    return None


def _reachable(roots: List[Tuple[str, str]],
               modules: Dict[str, ModuleInfo]
               ) -> Set[Tuple[str, str]]:
    seen: Set[Tuple[str, str]] = set()
    frontier = [r for r in roots
                if r[0] in modules and r[1] in modules[r[0]].functions]
    while frontier:
        m, q = frontier.pop()
        if (m, q) in seen:
            continue
        seen.add((m, q))
        mod = modules[m]
        cls = q.split(".")[0] if "." in q else None
        for call in _iter_calls(mod.functions[q]):
            tgt = _resolve_call(call, mod, cls, modules)
            if tgt is not None and tgt not in seen:
                frontier.append(tgt)
    return seen


def _numpy_aliases(mod: ModuleInfo) -> Set[str]:
    return {a for a, m in mod.mod_aliases.items() if m == "numpy"}


def _jax_aliases(mod: ModuleInfo) -> Set[str]:
    return {a for a, m in mod.mod_aliases.items() if m == "jax"}


def _check_transfers(mod: ModuleInfo, qual: str, node: ast.AST,
                     report: Report) -> None:
    np_al, jax_al = _numpy_aliases(mod), _jax_aliases(mod)
    for call in _iter_calls(node):
        f = call.func
        if not isinstance(f, ast.Attribute):
            continue
        hit = None
        if f.attr in _TRANSFER_METHODS:
            hit = f".{f.attr}()"
        elif isinstance(f.value, ast.Name):
            if f.value.id in np_al and f.attr in _NUMPY_TRANSFERS:
                hit = f"{f.value.id}.{f.attr}()"
            elif f.value.id in jax_al and f.attr in _JAX_TRANSFERS:
                hit = f"{f.value.id}.{f.attr}()"
            elif mod.mod_aliases.get(f.value.id) == "random":
                # stdlib random module (`from jax import random`
                # resolves to "jax.random" and stays allowed)
                hit = f"{f.value.id}.{f.attr}() [host RNG]"
        elif isinstance(f.value, ast.Attribute) \
                and isinstance(f.value.value, ast.Name) \
                and f.value.value.id in np_al \
                and f.value.attr == "random":
            # np.random.<anything>: host RNG smuggled into a span —
            # sampling must go through the device-side jax.random
            # threefry keyed by (seed, position)
            hit = f"{f.value.value.id}.random.{f.attr}() [host RNG]"
        if hit:
            report.add(Finding(
                "AST001",
                f"{hit} inside hot-path body {mod.name}.{qual} "
                f"(transfer-free serving contract)",
                path=mod.path, line=call.lineno,
                detail={"function": qual, "call": hit}))


def _check_parity_body(mod: ModuleInfo, qual: str, node: ast.AST,
                       report: Report) -> None:
    for n in ast.walk(node):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.MatMult):
            report.add(Finding(
                "AST002",
                f"matmul operator in parity-critical body "
                f"{mod.name}.{qual}; scores/PV must stay explicit "
                f"multiply+sum",
                path=mod.path, line=n.lineno,
                detail={"function": qual, "op": "@"}))
        elif isinstance(n, ast.Call) \
                and isinstance(n.func, ast.Attribute) \
                and n.func.attr in _DOT_CALLS:
            report.add(Finding(
                "AST002",
                f"{n.func.attr}() in parity-critical body "
                f"{mod.name}.{qual}; scores/PV must stay explicit "
                f"multiply+sum",
                path=mod.path, line=n.lineno,
                detail={"function": qual, "op": n.func.attr}))


def _mutable_attrs(cls_node: ast.ClassDef) -> Set[str]:
    """Attributes assigned through ``self.`` outside __init__."""
    out: Set[str] = set()
    for item in cls_node.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if item.name == "__init__":
            continue
        for n in ast.walk(item):
            targets = []
            if isinstance(n, ast.Assign):
                targets = n.targets
            elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
                targets = [n.target]
            for t in targets:
                for tt in ast.walk(t):
                    if isinstance(tt, ast.Attribute) \
                            and isinstance(tt.value, ast.Name) \
                            and tt.value.id == "self":
                        out.add(tt.attr)
    return out


def _self_reads(node: ast.AST, attrs: Set[str]
                ) -> List[Tuple[str, int]]:
    hits = []
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and isinstance(n.ctx, ast.Load) \
                and isinstance(n.value, ast.Name) \
                and n.value.id == "self" and n.attr in attrs:
            hits.append((n.attr, n.lineno))
    return hits


def _is_jax_jit(call: ast.Call, mod: ModuleInfo) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "jit"
            and isinstance(f.value, ast.Name)
            and f.value.id in _jax_aliases(mod))


def _check_jit_captures(mod: ModuleInfo, report: Report) -> None:
    for cls_name, cls_node in mod.classes.items():
        mutable = _mutable_attrs(cls_node)
        if not mutable:
            continue
        for call in _iter_calls(cls_node):
            if not _is_jax_jit(call, mod) or not call.args:
                continue
            target = call.args[0]
            bodies: List[Tuple[str, ast.AST]] = []
            if isinstance(target, ast.Lambda):
                bodies.append(("<lambda>", target))
            elif isinstance(target, ast.Attribute) \
                    and isinstance(target.value, ast.Name) \
                    and target.value.id == "self":
                # the method plus everything reachable through self.
                seen: Set[str] = set()
                stack = [target.attr]
                while stack:
                    meth = stack.pop()
                    q = f"{cls_name}.{meth}"
                    if meth in seen or q not in mod.functions:
                        continue
                    seen.add(meth)
                    node = mod.functions[q]
                    bodies.append((q, node))
                    for c in _iter_calls(node):
                        if isinstance(c.func, ast.Attribute) \
                                and isinstance(c.func.value, ast.Name) \
                                and c.func.value.id == "self":
                            stack.append(c.func.attr)
            for qual, body in bodies:
                # reads that are method *calls* resolve at trace time
                # and are not frozen state; _self_reads still flags
                # them if the attr is data (methods are never
                # assigned via self.<x> = ..., so they are not in
                # `mutable` to begin with)
                for attr, line in _self_reads(body, mutable):
                    report.add(Finding(
                        "AST003",
                        f"jitted body {mod.name}.{cls_name}.{qual} "
                        f"reads mutable server state self.{attr}; jit "
                        f"freezes it at trace time — pass it as an "
                        f"operand instead",
                        path=mod.path, line=line,
                        detail={"class": cls_name, "body": qual,
                                "attr": attr,
                                "jit_line": call.lineno}))


def collect_paths(repo_root: str = ".") -> List[str]:
    paths: List[str] = []
    for pkg in contracts.AST_SCAN_PACKAGES:
        base = os.path.join(repo_root, pkg)
        for dirpath, _, files in os.walk(base):
            for f in sorted(files):
                if f.endswith(".py"):
                    paths.append(os.path.join(dirpath, f))
    for suffix in contracts.PARITY_BODIES:
        p = os.path.join(repo_root, "src", "repro", suffix)
        if p not in paths and os.path.exists(p):
            paths.append(p)
    return paths


def run(report: Report, *, paths: Optional[List[str]] = None,
        repo_root: str = ".",
        roots: Optional[List[Tuple[str, str]]] = None,
        parity_bodies: Optional[Dict[str, Set[str]]] = None) -> None:
    """Lint `paths` (default: the contracts' scan scope)."""
    paths = collect_paths(repo_root) if paths is None else paths
    roots = contracts.HOT_PATH_ROOTS if roots is None else roots
    parity = (contracts.PARITY_BODIES if parity_bodies is None
              else parity_bodies)
    modules: Dict[str, ModuleInfo] = {}
    for p in paths:
        info = parse_module(p, repo_root)
        modules[info.name] = info

    hot = _reachable(list(roots), modules)
    for m, q in sorted(hot):
        _check_transfers(modules[m], q, modules[m].functions[q], report)

    for mod in modules.values():
        for suffix, fns in parity.items():
            if not mod.path.replace(os.sep, "/").endswith(suffix):
                continue
            for fn in sorted(fns):
                if fn in mod.functions:
                    _check_parity_body(mod, fn, mod.functions[fn],
                                       report)
        _check_jit_captures(mod, report)
