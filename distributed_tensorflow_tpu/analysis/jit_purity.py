"""jit-purity: no host-side effects reachable from compiled programs.

PR 5's contract — instrumentation (obs registry, logging, prints) and
host RNG/clocks never run inside ``jax.jit``-compiled functions; they
would execute once at trace time and silently vanish from every later
call, or (worse) record trace-time values as if they were per-step.

The rule finds every function compiled in a module — ``@jax.jit`` /
``@pjit`` decorations, ``jax.jit(fn)`` / ``jax.jit(self.method)`` /
``jax.jit(functools.partial(fn, ...))`` / ``jax.jit(_named(name, fn, ...))``
call sites, and jitted lambdas —
then BFS-walks the intra-module call graph from those roots (module
functions plus same-class ``self.method()`` calls) and flags:

- calls into host-clock/RNG modules: ``time.*``, stdlib ``random.*``,
  ``numpy.random.*`` (``jax.random`` is of course fine);
- ``print(...)`` and ``logging`` calls (module-level or via a bound
  ``logging.getLogger`` logger);
- obs-registry usage: any call through an attribute chain containing an
  obs-ish instrument handle (``_obs``, ``_obs_registry``, ``_tracer``)
  or canonically resolving into ``distributed_tensorflow_tpu.obs``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from distributed_tensorflow_tpu.analysis.core import (
    Finding,
    ImportMap,
    Module,
    Rule,
    dotted,
)

RULE_ID = "jit-purity"

_JIT_CALLEES = {
    "jax.jit",
    "jax.pjit",
    "jax.experimental.pjit.pjit",
    "pjit",
}

# Canonical dotted-call prefixes that are host-side effects.
_IMPURE_PREFIXES = (
    "time.",
    "random.",
    "numpy.random.",
    "logging.",
    "distributed_tensorflow_tpu.obs.",
)

# self-attribute chain segments that hold obs handles by repo convention.
_OBS_ATTRS = {"_obs", "_obs_registry", "_tracer", "_metrics", "_registry"}


def _is_jit_callee(call: ast.Call, imports: ImportMap) -> bool:
    name = dotted(call.func)
    if name is None:
        return False
    return imports.canonical(name) in _JIT_CALLEES


class _FunctionIndex:
    """Module/class function tables for intra-module call resolution."""

    def __init__(self, module: Module):
        self.module_funcs: Dict[str, ast.AST] = {}
        self.class_methods: Dict[str, Dict[str, ast.AST]] = {}
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.module_funcs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                methods: Dict[str, ast.AST] = {}
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        methods[item.name] = item
                self.class_methods[node.name] = methods
        # Nested defs (e.g. `step` inside `make_step`) resolve by name too.
        self.all_funcs: Dict[str, ast.AST] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.all_funcs.setdefault(node.name, node)

    def owning_class(self, module: Module, node: ast.AST) -> Optional[str]:
        cls = module.enclosing(node, (ast.ClassDef,))
        return cls.name if isinstance(cls, ast.ClassDef) else None


def _jit_roots(module: Module, imports: ImportMap, index: _FunctionIndex
               ) -> List[Tuple[ast.AST, int]]:
    """(function node, report line) pairs for everything handed to jit."""
    roots: List[Tuple[ast.AST, int]] = []

    def resolve(arg: ast.AST, at: ast.AST) -> Optional[ast.AST]:
        # jax.jit(fn) / jax.jit(self.method) / jax.jit(lambda: ...) /
        # jax.jit(functools.partial(self.method, const, ...))
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            return index.all_funcs.get(arg.id)
        if isinstance(arg, ast.Attribute):
            chain = dotted(arg)
            if chain and chain.startswith("self."):
                cls = index.owning_class(module, at)
                if cls:
                    return index.class_methods.get(cls, {}).get(arg.attr)
            return None
        if isinstance(arg, ast.Call):
            name = dotted(arg.func)
            if name and imports.canonical(name) in (
                    "functools.partial", "partial") and arg.args:
                return resolve(arg.args[0], at)
            if name == "_named" and len(arg.args) >= 2:
                # serve.engine._named("program", fn, *bound): a partial
                # under a name of its own.
                return resolve(arg.args[1], at)
        return None

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                callee = dec.func if isinstance(dec, ast.Call) else dec
                name = dotted(callee)
                if name and imports.canonical(name) in _JIT_CALLEES:
                    roots.append((node, node.lineno))
        elif isinstance(node, ast.Call) and _is_jit_callee(node, imports):
            if node.args:
                target = resolve(node.args[0], node)
                if target is not None:
                    roots.append((target, node.lineno))
    return roots


def _logger_names(module: Module, imports: ImportMap) -> Set[str]:
    """Module-level names bound via logging.getLogger(...)."""
    names: Set[str] = set()
    for node in module.tree.body:
        if (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
            callee = dotted(node.value.func)
            if callee and imports.canonical(callee) == "logging.getLogger":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    return names


def _loose_parts(node: ast.AST) -> Optional[List[str]]:
    """Attribute-chain segments, looking through subscripts —
    ``self._obs["steps"].inc`` -> ["self", "_obs", "inc"]."""
    parts: List[str] = []
    while True:
        if isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            return list(reversed(parts))
        else:
            return None


def _impurity(call: ast.Call, imports: ImportMap, loggers: Set[str]
              ) -> Optional[str]:
    """A human-readable reason if ``call`` is host-impure, else None."""
    name = dotted(call.func)
    if name is None:
        # Chains with subscripts (self._obs["x"].inc()) still count as
        # obs instrumentation.
        loose = _loose_parts(call.func)
        if loose and len(loose) >= 2 and any(p in _OBS_ATTRS for p in loose):
            return (f"obs instrumentation `{'.'.join(loose)}` inside a "
                    "compiled function")
        return None
    if name == "print" or name.startswith("print."):
        return "print() inside a compiled function"
    head = name.split(".")[0]
    if head in loggers and "." in name:
        return f"logging call `{name}` inside a compiled function"
    canonical = imports.canonical(name)
    # jax.random / jax.numpy.* must never match the stdlib prefixes.
    if canonical.startswith(("jax.", "flax.")):
        return None
    for prefix in _IMPURE_PREFIXES:
        if canonical.startswith(prefix) or canonical == prefix[:-1]:
            what = prefix[:-1]
            return f"host-side `{canonical}` (module `{what}`) inside a compiled function"
    # Instrument handles: self._obs.counter(...).inc(), self._tracer.span(...)
    parts = name.split(".")
    if len(parts) >= 2 and any(p in _OBS_ATTRS for p in parts):
        return f"obs instrumentation `{name}` inside a compiled function"
    return None


class JitPurityRule(Rule):
    id = RULE_ID
    description = "host-side effects reachable from jax.jit-compiled code"

    def run(self, modules: Sequence[Module]) -> List[Finding]:
        findings: List[Finding] = []
        for module in modules:
            imports = ImportMap(module)
            index = _FunctionIndex(module)
            loggers = _logger_names(module, imports)
            seen: Set[int] = set()
            queue = list(_jit_roots(module, imports, index))
            while queue:
                fn, _root_line = queue.pop()
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                body = fn.body if isinstance(fn.body, list) else [fn.body]
                for node in [n for b in body for n in ast.walk(b)]:
                    if not isinstance(node, ast.Call):
                        continue
                    reason = _impurity(node, imports, loggers)
                    if reason:
                        findings.append(Finding(
                            rule=self.id,
                            path=module.relpath,
                            line=node.lineno,
                            message=reason,
                            symbol=module.symbol_for(node),
                        ))
                        continue
                    # Follow intra-module calls: f(...), self.m(...)
                    name = dotted(node.func)
                    if name is None:
                        continue
                    callee: Optional[ast.AST] = None
                    if "." not in name:
                        callee = index.all_funcs.get(name)
                    elif name.startswith("self.") and name.count(".") == 1:
                        cls = index.owning_class(module, fn)
                        if cls:
                            callee = index.class_methods.get(
                                cls, {}).get(name.split(".")[1])
                    if callee is not None and id(callee) not in seen:
                        queue.append((callee, node.lineno))
        return findings
