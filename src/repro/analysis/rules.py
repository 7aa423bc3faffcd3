"""stdlib-`ast` lint rules for JAX trace safety and repo hygiene.

The rule catalogue (DESIGN.md §9):

* ``traced-np-call``       — `np.*` / `jax.device_get` call inside a
  traced function: a host round-trip (or a silent constant-folding
  surprise) in code that compiles into the hot loop.
* ``cast-in-trace``        — `float()` / `int()` / `bool()` / `.item()`
  inside a traced function: forces a concrete value out of a tracer
  (ConcretizationError at best, a device sync at worst).
* ``branch-on-tracer``     — Python `if`/`while` whose condition
  mentions a value derived from `jnp`/`lax` ops inside a traced
  function: data-dependent Python control flow cannot trace.
* ``implicit-dtype``       — `jnp.array`/`jnp.asarray`/`jnp.full`
  without an explicit dtype (or an `np.float64`/`jnp.float64`
  literal) in the hot modules (`fabric/`, `core/jax_coordinator`):
  the input's dtype leaks into the f32 slab (the PR-4 drift class).
* ``host-pull-unaccounted``— a device value crossing to host (`np.
  asarray`/`np.array`/`jax.device_get`/`float`/`int`/`bool`,
  including via `tree_map(np.asarray, …)`) in a method of an
  io-counted class (`SessionPool`) that never touches `self.io`, or
  in a `session_*` host entrypoint of `fabric.jax_engine`: every
  warm-path transfer must be io-accounted or explicitly suppressed.
* ``unused-import``        — module-level import never referenced.
* ``unused-variable``      — function-local name assigned and never
  read.

Traced scope is computed per module: seeds are functions decorated
with `jit`/`pmap`/`vmap` (bare, called, or via `functools.partial`)
or passed by name to `lax.scan`/`while_loop`/`cond`/`vmap`/`pmap`/…;
lexically nested defs inherit the scope; the set closes over the
intra-module call graph (a function called from traced code is
traced). Cross-module edges are not followed — each hot module's
traced kernels are reached from a jit seed in the same module.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Set

__all__ = ["Finding", "lint_module", "traced_functions"]


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    msg: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


TRACE_DECORATORS = {"jit", "pmap", "vmap"}
# callables that trace a function passed to them by name
TRACE_CONSUMERS = {"scan", "while_loop", "cond", "switch", "fori_loop",
                   "associative_scan", "vmap", "pmap", "jit", "grad",
                   "value_and_grad", "checkpoint", "remat",
                   "custom_jvp", "custom_vjp"}
# attribute accesses that yield host metadata, not device values —
# they break the host-pull taint walk (reading .shape is not a pull)
_META_ATTRS = {"shape", "dtype", "ndim", "weak_type", "sharding",
               "aval", "nbytes", "itemsize"}
# device attrs / device-returning calls of the io-counted pool class
_POOL_DEVICE_ATTRS = {"_state", "_tb", "_ctl", "_ep_disp", "_ep_stack"}
_POOL_DEVICE_CALLS = {"_dispatch_slab", "gather_rows", "scatter_rows",
                      "session_advance", "session_plan_tick",
                      "scatter", "gather", "plan_tick"}
_ENGINE_DEVICE_CALLS = {"_run_session_block", "_pmapped_session_block"}
_PULL_FUNCS = {"asarray", "array", "device_get"}
_NP_ROOTS = {"np", "numpy"}


def _leaf_name(node) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _root_name(node) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_np_pull(func) -> bool:
    """`np.asarray` / `np.array` / `jax.device_get` as a callee."""
    leaf, root = _leaf_name(func), _root_name(func)
    if leaf == "device_get":
        return True
    return root in _NP_ROOTS and leaf in _PULL_FUNCS


# ---- traced-scope detection ----------------------------------------------

class _Funcs(ast.NodeVisitor):
    """Collect every function with its enclosing-function chain."""

    def __init__(self):
        self.by_name: Dict[str, List[ast.AST]] = {}
        self.parents: Dict[ast.AST, Optional[ast.AST]] = {}
        self._stack: List[ast.AST] = []

    def _visit_def(self, node):
        self.by_name.setdefault(node.name, []).append(node)
        self.parents[node] = self._stack[-1] if self._stack else None
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_def
    visit_AsyncFunctionDef = _visit_def


def _decorator_is_traced(dec) -> bool:
    if _leaf_name(dec) in TRACE_DECORATORS:
        return True
    if isinstance(dec, ast.Call):
        f = _leaf_name(dec.func)
        if f in TRACE_DECORATORS:
            return True
        if f == "partial" and dec.args and \
                _leaf_name(dec.args[0]) in TRACE_DECORATORS:
            return True
    return False


def traced_functions(tree: ast.AST) -> Set[ast.AST]:
    """The set of function nodes whose bodies run under a jax trace."""
    funcs = _Funcs()
    funcs.visit(tree)
    traced: Set[ast.AST] = set()
    for name, nodes in funcs.by_name.items():
        for node in nodes:
            if any(_decorator_is_traced(d) for d in node.decorator_list):
                traced.add(node)
    # functions handed by name to scan/while_loop/vmap/... anywhere
    for call in ast.walk(tree):
        if not (isinstance(call, ast.Call)
                and _leaf_name(call.func) in TRACE_CONSUMERS):
            continue
        handed = list(call.args) + [kw.value for kw in call.keywords]
        for arg in handed:
            if isinstance(arg, ast.Name) and arg.id in funcs.by_name:
                traced.update(funcs.by_name[arg.id])
    # fixpoint: lexical nesting + intra-module call graph
    changed = True
    while changed:
        changed = False
        for name, nodes in funcs.by_name.items():
            for node in nodes:
                if node in traced:
                    continue
                parent = funcs.parents[node]
                if parent is not None and parent in traced:
                    traced.add(node)
                    changed = True
        for node in list(traced):
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                callee = None
                if isinstance(call.func, ast.Name):
                    callee = call.func.id
                elif isinstance(call.func, ast.Attribute) and \
                        _root_name(call.func) in ("self", "cls"):
                    callee = call.func.attr
                if callee in funcs.by_name:
                    for cand in funcs.by_name[callee]:
                        if cand not in traced:
                            traced.add(cand)
                            changed = True
    return traced


def _own_nodes(func: ast.AST):
    """Walk `func`'s body without descending into nested defs."""
    todo = list(ast.iter_child_nodes(func))
    while todo:
        node = todo.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.Lambda)):
            todo.extend(ast.iter_child_nodes(node))


# ---- taint helpers -------------------------------------------------------

def _mentions(node, tainted: Set[str], device_calls: Set[str]) -> bool:
    """Does this expression reference a tainted name / device attr /
    device-returning call? `.shape`-style metadata reads do not count."""
    if isinstance(node, ast.Attribute):
        if node.attr in _META_ATTRS:
            return False
        if _root_name(node) == "self" and \
                f"self.{node.attr}" in tainted:
            return True
    if isinstance(node, ast.Name) and node.id in tainted:
        return True
    if isinstance(node, ast.Call) and \
            _leaf_name(node.func) in device_calls:
        return True
    return any(_mentions(c, tainted, device_calls)
               for c in ast.iter_child_nodes(node))


def _propagate_taint(func, tainted: Set[str],
                     device_calls: Set[str]) -> Set[str]:
    """Close `tainted` over simple assignments inside `func`."""
    for _ in range(4):  # tiny fixpoint; real chains are 1-2 deep
        grew = False
        for node in ast.walk(func):
            if not isinstance(node, ast.Assign):
                continue
            if not _mentions(node.value, tainted, device_calls):
                continue
            targets = []
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    targets.append(tgt.id)
                elif isinstance(tgt, (ast.Tuple, ast.List)):
                    targets.extend(e.id for e in tgt.elts
                                   if isinstance(e, ast.Name))
            for name in targets:
                if name not in tainted:
                    tainted.add(name)
                    grew = True
        if not grew:
            break
    return tainted


def _pull_sites(func, tainted: Set[str],
                device_calls: Set[str]) -> List[ast.Call]:
    """Calls inside `func` that pull a tainted device value to host."""
    sites = []
    for node in ast.walk(func):
        if not isinstance(node, ast.Call):
            continue
        hit = False
        if _is_np_pull(node.func) or (
                isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int", "bool")):
            hit = any(_mentions(a, tainted, device_calls)
                      for a in node.args)
        elif _leaf_name(node.func) == "tree_map" and any(
                _is_np_pull(a) for a in node.args):
            hit = any(_mentions(a, tainted, device_calls)
                      for a in node.args if not _is_np_pull(a))
        if hit:
            sites.append(node)
    return sites


# ---- per-module rules ----------------------------------------------------

def _check_traced_bodies(tree, path, findings) -> None:
    traced = traced_functions(tree)
    for func in traced:
        # taint for branch-on-tracer: names derived from jnp/lax ops
        tainted: Set[str] = set()

        def from_jnp(node) -> bool:
            return any(isinstance(c, ast.Call)
                       and _root_name(c.func) in ("jnp", "lax")
                       for c in ast.walk(node))

        for _ in range(4):
            grew = False
            for node in _own_nodes(func):
                if not isinstance(node, ast.Assign):
                    continue
                if not (from_jnp(node.value)
                        or _mentions(node.value, tainted, set())):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and \
                            tgt.id not in tainted:
                        tainted.add(tgt.id)
                        grew = True
            if not grew:
                break
        for node in _own_nodes(func):
            if isinstance(node, ast.Call):
                leaf, root = _leaf_name(node.func), _root_name(node.func)
                if root in _NP_ROOTS or leaf == "device_get":
                    findings.append(Finding(
                        "traced-np-call", path, node.lineno,
                        f"host call `{root or ''}.{leaf}` inside traced "
                        f"function `{func.name}`"))
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in ("float", "int", "bool") and \
                        node.args:
                    findings.append(Finding(
                        "cast-in-trace", path, node.lineno,
                        f"`{node.func.id}()` concretizes a value inside "
                        f"traced function `{func.name}`"))
                elif leaf == "item" and not node.args:
                    findings.append(Finding(
                        "cast-in-trace", path, node.lineno,
                        f"`.item()` concretizes a value inside traced "
                        f"function `{func.name}`"))
            elif isinstance(node, (ast.If, ast.While)):
                if _mentions(node.test, tainted, set()):
                    kw = "if" if isinstance(node, ast.If) else "while"
                    findings.append(Finding(
                        "branch-on-tracer", path, node.lineno,
                        f"Python `{kw}` on a jnp-derived value inside "
                        f"traced function `{func.name}`"))


_DTYPE_SCOPED = re.compile(r"(/|^)fabric/|(/|^)core/jax_coordinator\.py$")


def _check_implicit_dtype(tree, path, findings) -> None:
    if not _DTYPE_SCOPED.search(path.replace("\\", "/")):
        return
    # f64 literals are flagged only inside TRACED functions — host
    # result paths deliberately reconstruct absolute times in f64
    # (DESIGN.md §3); inside a trace an f64 request either promotes
    # the slab or silently downgrades, both wrong.
    for func in traced_functions(tree):
        for node in ast.walk(func):
            if isinstance(node, ast.Attribute) and \
                    node.attr == "float64" and \
                    _root_name(node) in ("np", "numpy", "jnp"):
                findings.append(Finding(
                    "implicit-dtype", path, node.lineno,
                    "float64 literal inside a traced function of an "
                    "f32 hot module"))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _root_name(node.func) == "jnp"):
            continue
        leaf = _leaf_name(node.func)
        has_dtype_kw = any(kw.arg == "dtype" for kw in node.keywords)
        if leaf in ("array", "asarray") and \
                len(node.args) < 2 and not has_dtype_kw:
            findings.append(Finding(
                "implicit-dtype", path, node.lineno,
                f"`jnp.{leaf}` without an explicit dtype lets the "
                f"input's dtype leak into the f32 slab"))
        elif leaf == "full" and len(node.args) < 3 and not has_dtype_kw:
            findings.append(Finding(
                "implicit-dtype", path, node.lineno,
                "`jnp.full` without an explicit dtype"))


def _check_host_pulls(tree, path, findings) -> None:
    posix = path.replace("\\", "/")
    # (a) methods of io-counted classes (SessionPool): any device pull
    # in a method that never references `self.io` is unaccounted.
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        has_io = any(isinstance(n, ast.Attribute) and n.attr == "io"
                     and _root_name(n) == "self"
                     for n in ast.walk(cls))
        if not has_io:
            continue
        for meth in cls.body:
            if not isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if meth.name == "__init__":
                continue
            accounted = any(
                isinstance(n, ast.Attribute) and n.attr == "io"
                and _root_name(n) == "self"
                for n in ast.walk(meth))
            if accounted:
                continue
            tainted = {f"self.{a}" for a in _POOL_DEVICE_ATTRS}
            tainted = _propagate_taint(meth, tainted,
                                       _POOL_DEVICE_CALLS)
            seen: Set[int] = set()
            for site in _pull_sites(meth, tainted, _POOL_DEVICE_CALLS):
                if site.lineno in seen:
                    continue
                seen.add(site.lineno)
                findings.append(Finding(
                    "host-pull-unaccounted", path, site.lineno,
                    f"device pull in `{cls.name}.{meth.name}` without "
                    f"`self.io` accounting"))
    # (b) the engine's host-side session_* entrypoints: pulls on the
    # jitted block results are the warm serving path's only host syncs
    # and must be suppressed (with a reason) or removed.
    if not posix.endswith("fabric/jax_engine.py"):
        return
    traced = traced_functions(tree)
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if func in traced or not func.name.startswith("session_"):
            continue
        tainted = {a.arg for a in func.args.args if a.arg == "state"}
        tainted = _propagate_taint(func, tainted, _ENGINE_DEVICE_CALLS)
        seen = set()
        for site in _pull_sites(func, tainted, _ENGINE_DEVICE_CALLS):
            if site.lineno in seen:
                continue
            seen.add(site.lineno)
            findings.append(Finding(
                "host-pull-unaccounted", path, site.lineno,
                f"host sync on a device value in session entrypoint "
                f"`{func.name}`"))


def _check_unused_imports(tree, src, path, findings) -> None:
    import_stmts = [n for n in tree.body
                    if isinstance(n, (ast.Import, ast.ImportFrom))]
    if not import_stmts:
        return
    lines = src.splitlines()
    import_lines = set()
    for node in import_stmts:
        end = getattr(node, "end_lineno", node.lineno)
        import_lines.update(range(node.lineno, end + 1))
    rest = "\n".join(line for i, line in enumerate(lines, 1)
                     if i not in import_lines)
    for node in import_stmts:
        if isinstance(node, ast.ImportFrom) and \
                node.module == "__future__":
            continue
        for alias in node.names:
            if alias.name == "*":
                continue
            bound = alias.asname or alias.name.split(".")[0]
            if not re.search(rf"\b{re.escape(bound)}\b", rest):
                findings.append(Finding(
                    "unused-import", path, node.lineno,
                    f"`{bound}` imported but never used"))


def _check_unused_variables(tree, path, findings) -> None:
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        declared: Set[str] = set()
        for node in ast.walk(func):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                declared.update(node.names)
        loads = {n.id for n in ast.walk(func)
                 if isinstance(n, ast.Name)
                 and isinstance(n.ctx, (ast.Load, ast.Del))}
        for node in _own_nodes(func):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            name = node.targets[0].id
            if name.startswith("_") or name in declared or \
                    name in loads:
                continue
            findings.append(Finding(
                "unused-variable", path, node.lineno,
                f"`{name}` assigned in `{func.name}` but never read"))


def lint_module(path: str, src: str) -> List[Finding]:
    """All module-local findings for one source file (unsuppressed —
    `repro.analysis.lint` applies the `# saath: lint-ok` filter)."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as exc:
        return [Finding("syntax-error", path, exc.lineno or 1,
                        str(exc.msg))]
    findings: List[Finding] = []
    _check_traced_bodies(tree, path, findings)
    _check_implicit_dtype(tree, path, findings)
    _check_host_pulls(tree, path, findings)
    _check_unused_imports(tree, src, path, findings)
    _check_unused_variables(tree, path, findings)
    findings.sort(key=lambda f: (f.line, f.rule))
    return findings
