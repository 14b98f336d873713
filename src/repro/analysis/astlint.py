"""sparqlint — AST rules over ``src/repro`` (layer 1 of the analyzer).

Rules (catalog + rationale in docs/static-analysis.md):

* **SPL001** — functions reachable from traced roots (jit-decorated
  functions, pallas kernels, shard_map bodies, the step factories in
  ``launch/steps.py``) must not perform host side effects: ``print``,
  ``time.*``, or obs registry/tracer calls. Instrumentation brackets the
  jitted calls, it never runs inside them (docs/observability.md).
* **SPL002** — host-only modules (``serving/scheduler.py``,
  ``serving/kv_pool.py``, ``obs/``) must not launch device ops
  (``jnp.*``/``jax.lax.*``/``jax.nn.*`` calls). Scheduler and pool
  bookkeeping stays collective-free host work (docs/sharding.md).
* **SPL003** — tracer-leak heuristics inside traced code: ``.item()``,
  ``float()/int()/bool()`` applied to jnp/jax expressions, and Python
  ``if``/``while`` tests calling into jnp/jax — each forces a trace-time
  concretization error or a silent host sync.
* **SPL004** — metric registration discipline: every literal name passed
  to ``.counter()/.gauge()/.histogram()`` must match the registry's
  naming rule, counters must end in ``_total``, and the name must be
  cataloged in docs/observability.md.

The call graph is intentionally lightweight: same-module calls by name,
cross-module calls through ``import``/``from`` aliases, plus any known
function *referenced* as a call argument (covers ``lax.scan(body, ...)``,
``pallas_call(kernel, ...)``, ``jax.shard_map(body, ...)``).
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .findings import Finding

METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

HOST_ONLY = ("serving/scheduler.py", "serving/kv_pool.py", "obs/")

# attribute roots that mark an expression as device-side jax
_JAX_DEVICE_SUBMODULES = {"lax", "nn", "numpy"}
# obs-object names whose method calls are host side effects
_OBS_NAMES = {"obs", "registry", "tracer"}
# method names that are registry mutations wherever they appear
_OBS_METHODS = {"inc", "observe"}
_TIME_FNS = {"time", "perf_counter", "perf_counter_ns", "monotonic",
             "monotonic_ns", "sleep", "process_time"}


@dataclass
class FuncInfo:
    module: str            # dotted module name, e.g. "repro.kernels.ops"
    path: str              # repo-relative file path
    qualname: str          # e.g. "make_engine_decode.body"
    node: ast.FunctionDef
    is_root: bool = False
    calls: Set[Tuple[str, str]] = field(default_factory=set)  # (mod, name)


@dataclass
class ModuleInfo:
    name: str
    path: str
    tree: ast.Module
    # alias -> dotted module ("jnp" -> "jax.numpy")
    mod_aliases: Dict[str, str] = field(default_factory=dict)
    # local name -> (source module, symbol) for `from x import y`
    sym_imports: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    functions: Dict[str, FuncInfo] = field(default_factory=dict)


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """`a.b.c` -> ["a", "b", "c"]; None for non-name-rooted chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


class _Repo:
    """Parsed view of every module under a source root."""

    def __init__(self, src_root: str):
        self.src_root = src_root
        self.modules: Dict[str, ModuleInfo] = {}
        for dirpath, _, names in sorted(os.walk(src_root)):
            if "__pycache__" in dirpath:
                continue
            for fn in sorted(names):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, src_root)
                dotted = rel[:-3].replace(os.sep, ".")
                if dotted.endswith(".__init__"):
                    dotted = dotted[: -len(".__init__")]
                with open(path) as f:
                    tree = ast.parse(f.read(), filename=path)
                self.modules[dotted] = ModuleInfo(dotted, rel, tree)
        for mi in self.modules.values():
            self._index_module(mi)
        for mi in self.modules.values():
            for fi in mi.functions.values():
                self._collect_calls(mi, fi)

    # -- indexing ----------------------------------------------------
    def _index_module(self, mi: ModuleInfo) -> None:
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mi.mod_aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    full = f"{node.module}.{a.name}"
                    if full in self.modules or a.name == "*":
                        mi.mod_aliases[a.asname or a.name] = full
                    else:
                        mi.sym_imports[a.asname or a.name] = \
                            (node.module, a.name)

        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{prefix}.{child.name}" if prefix else child.name
                    mi.functions[q] = FuncInfo(mi.name, mi.path, q, child)
                    visit(child, q)
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{prefix}.{child.name}"
                          if prefix else child.name)
                else:
                    visit(child, prefix)

        visit(mi.tree, "")
        self._mark_roots(mi)

    def _is_jit_decorator(self, mi: ModuleInfo, dec: ast.AST) -> bool:
        chain = _attr_chain(dec.func if isinstance(dec, ast.Call) else dec)
        if chain is None:
            return False
        if chain[-1] == "jit":
            return True
        # functools.partial(jax.jit, ...)
        if isinstance(dec, ast.Call) and chain[-1] == "partial" and dec.args:
            inner = _attr_chain(dec.args[0])
            return inner is not None and inner[-1] == "jit"
        return False

    def _mark_roots(self, mi: ModuleInfo) -> None:
        # (a) jit-decorated functions anywhere
        for fi in mi.functions.values():
            for dec in fi.node.decorator_list:
                if self._is_jit_decorator(mi, dec):
                    fi.is_root = True
        # (b) nested defs inside make_* factories in launch/steps.py —
        # these are the engine step bodies handed to jax.jit/shard_map
        if mi.name.endswith("launch.steps"):
            for q, fi in mi.functions.items():
                parts = q.split(".")
                if len(parts) > 1 and parts[0].startswith("make_"):
                    fi.is_root = True
        # (c) functions passed by name to pallas_call / shard_map*
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain is None or not node.args:
                continue
            if chain[-1] in ("pallas_call", "shard_map"):
                target = node.args[0]
                if isinstance(target, ast.Call):   # partial(kernel, ...)
                    target = target.args[0] if target.args else target
                tchain = _attr_chain(target)
                if tchain and len(tchain) == 1:
                    for q, fi in mi.functions.items():
                        if q.split(".")[-1] == tchain[0]:
                            fi.is_root = True

    def _resolve(self, mi: ModuleInfo, fi: FuncInfo,
                 name: str) -> Optional[Tuple[str, str]]:
        # innermost enclosing scope first: sibling/nested defs, then
        # module-level defs, then from-imports
        parts = fi.qualname.split(".")
        for depth in range(len(parts), -1, -1):
            q = ".".join(parts[:depth] + [name])
            if q in mi.functions:
                return (mi.name, q)
        if name in mi.sym_imports:
            smod, sym = mi.sym_imports[name]
            if smod in self.modules and sym in self.modules[smod].functions:
                return (smod, sym)
        return None

    def _collect_calls(self, mi: ModuleInfo, fi: FuncInfo) -> None:
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            # direct calls: f(...) / mod.f(...)
            if isinstance(node.func, ast.Name):
                tgt = self._resolve(mi, fi, node.func.id)
                if tgt:
                    fi.calls.add(tgt)
            else:
                chain = _attr_chain(node.func)
                if chain and len(chain) == 2 and \
                        chain[0] in mi.mod_aliases:
                    smod = mi.mod_aliases[chain[0]]
                    if smod in self.modules and \
                            chain[1] in self.modules[smod].functions:
                        fi.calls.add((smod, chain[1]))
            # higher-order: any known function referenced as an argument
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Name):
                    tgt = self._resolve(mi, fi, arg.id)
                    if tgt:
                        fi.calls.add(tgt)

    def reachable_from_roots(self) -> Set[Tuple[str, str]]:
        seen: Set[Tuple[str, str]] = set()
        frontier = [(mi.name, q) for mi in self.modules.values()
                    for q, fi in mi.functions.items() if fi.is_root]
        seen.update(frontier)
        while frontier:
            mod, q = frontier.pop()
            fi = self.modules[mod].functions[q]
            for tgt in fi.calls:
                if tgt not in seen:
                    seen.add(tgt)
                    frontier.append(tgt)
        return seen


# ---------------------------------------------------------------- rules

def _is_device_attr_call(mi: ModuleInfo,
                         chain: List[str]) -> bool:
    """True for jnp.foo(...) / jax.lax.foo(...) / jax.nn.foo(...)."""
    root = mi.mod_aliases.get(chain[0], chain[0])
    if root == "jax.numpy":
        return True
    if root == "jax" and len(chain) >= 3 and \
            chain[1] in _JAX_DEVICE_SUBMODULES:
        return True
    return False


def _contains_jax_expr(mi: ModuleInfo, node: ast.AST) -> bool:
    for sub in ast.walk(node):
        chain = _attr_chain(sub) if isinstance(sub, ast.Attribute) else None
        if chain:
            root = mi.mod_aliases.get(chain[0], chain[0])
            if root == "jax.numpy" or root == "jax":
                return True
    return False


def _check_spl001(repo: _Repo, reachable: Set[Tuple[str, str]],
                  out: List[Finding]) -> None:
    for mod, q in sorted(reachable):
        mi = repo.modules[mod]
        fi = mi.functions[q]
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            msg = None
            if isinstance(node.func, ast.Name):
                if node.func.id == "print":
                    msg = "print() call in traced code"
                elif node.func.id in _TIME_FNS and \
                        node.func.id in mi.sym_imports and \
                        mi.sym_imports[node.func.id][0] == "time":
                    msg = f"time.{node.func.id}() call in traced code"
            else:
                chain = _attr_chain(node.func)
                if chain:
                    root = mi.mod_aliases.get(chain[0], chain[0])
                    if root == "time" and chain[-1] in _TIME_FNS:
                        msg = f"time.{chain[-1]}() call in traced code"
                    elif any(p in _OBS_NAMES for p in chain[:-1]):
                        msg = (f"obs call {'.'.join(chain)}() in traced "
                               "code (instrumentation must stay host-side)")
                    elif chain[-1] in _OBS_METHODS:
                        msg = (f"metric mutation .{chain[-1]}() in traced "
                               "code")
            if msg:
                out.append(Finding(
                    "SPL001", f"{fi.path}::{q}",
                    f"{fi.path}:{node.lineno}", f"{msg} (in `{q}`)"))


def _check_spl002(repo: _Repo, out: List[Finding]) -> None:
    for mi in repo.modules.values():
        if not any(mi.path.startswith(p) or f"/{p}" in f"/{mi.path}"
                   for p in HOST_ONLY):
            continue

        def enclosing(lineno: int) -> str:
            best = ""
            for q, fi in mi.functions.items():
                n = fi.node
                if n.lineno <= lineno <= (n.end_lineno or n.lineno) and \
                        len(q) > len(best):
                    best = q
            return best or "<module>"

        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain and _is_device_attr_call(mi, chain):
                fn = enclosing(node.lineno)
                out.append(Finding(
                    "SPL002", f"{mi.path}::{fn}",
                    f"{mi.path}:{node.lineno}",
                    f"device op {'.'.join(chain)}() in host-only module "
                    f"(in `{fn}`)"))


def _check_spl003(repo: _Repo, reachable: Set[Tuple[str, str]],
                  out: List[Finding]) -> None:
    for mod, q in sorted(reachable):
        mi = repo.modules[mod]
        fi = mi.functions[q]
        for node in ast.walk(fi.node):
            msg = None
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    msg = ".item() concretizes a traced value"
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in ("float", "int", "bool") and \
                        node.args and \
                        _contains_jax_expr(mi, node.args[0]):
                    msg = (f"{node.func.id}() on a jnp/jax expression "
                           "concretizes a traced value")
            elif isinstance(node, (ast.If, ast.While)):
                for sub in ast.walk(node.test):
                    if isinstance(sub, ast.Call):
                        chain = _attr_chain(sub.func)
                        if chain and _is_device_attr_call(mi, chain):
                            msg = ("Python control flow on a traced "
                                   f"value ({'.'.join(chain)}(...))")
                            break
            if msg:
                out.append(Finding(
                    "SPL003", f"{fi.path}::{q}",
                    f"{fi.path}:{node.lineno}", f"{msg} (in `{q}`)"))


def _check_spl004(repo: _Repo, docs_path: str,
                  out: List[Finding]) -> None:
    docs = ""
    if os.path.exists(docs_path):
        with open(docs_path) as f:
            docs = f.read()
    for mi in repo.modules.values():
        if mi.name == "repro.obs.metrics" or mi.name.endswith(".obs"):
            # the registry implementation itself (its internal helper
            # calls are not registrations); other obs/ modules
            # (attribution, slo, ...) register real metrics and must
            # catalog them like everyone else
            continue
        for node in ast.walk(mi.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("counter", "gauge", "histogram")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            prov = f"{mi.path}:{node.lineno}"
            key = f"{mi.path}::{name}"
            if not METRIC_NAME_RE.match(name):
                out.append(Finding(
                    "SPL004", key, prov,
                    f"metric name `{name}` violates ^[a-z][a-z0-9_]*$"))
            if node.func.attr == "counter" and \
                    not name.endswith("_total"):
                out.append(Finding(
                    "SPL004", key, prov,
                    f"counter `{name}` should end in `_total`"))
            if docs and f"`{name}`" not in docs:
                out.append(Finding(
                    "SPL004", key, prov,
                    f"metric `{name}` is not cataloged in "
                    "docs/observability.md"))


def run(src_root: str, docs_path: str = "") -> List[Finding]:
    """Run all AST rules over ``src_root`` (a directory containing the
    ``repro`` package or any module tree). Returns raw findings —
    allowlist application happens in the caller."""
    repo = _Repo(src_root)
    reachable = repo.reachable_from_roots()
    out: List[Finding] = []
    _check_spl001(repo, reachable, out)
    _check_spl002(repo, out)
    _check_spl003(repo, reachable, out)
    _check_spl004(repo, docs_path, out)
    return out
