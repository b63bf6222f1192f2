"""unfenced-timing — wall-clock timing of jitted work needs a device
fence (ISSUE 13 satellite).

JAX dispatch is asynchronous: ``t0 = perf_counter(); jitted(...);
perf_counter() - t0`` measures the *enqueue*, not the work.  The repo's
one idiom is a fence between the jitted call and the clock read: a
``device_get``/``np.asarray`` of a probe value, or ``block_until_ready``
(``utils/profiler.StepTimer``).  This pass keeps the hand-rolled form
without the fence out of the code that times.

Detection (per function, events in source order):

- **start** — ``t = time.perf_counter()`` (the bare assignment form);
- **jitted call** — a call of a name bound to ``jax.jit(...)`` in this
  module (assignment or decorator, ``partial(jax.jit, ...)``
  included), or a direct ``jax.jit(...)(...)`` invocation;
- **fence** — ``np.asarray`` / ``jax.device_get`` /
  ``.block_until_ready()`` / ``.item()`` / ``StepTimer.stop``;
- **read** — any other ``time.perf_counter()`` call (the
  ``perf_counter() - t0`` form).

A read while a start is armed and the latest jitted call since then
has no fence after it is a finding.  Heuristic by design (the
host-sync stance): timing code in this repo is straight-line
start/call/fence/read, so positional order is the control flow that
matters.  Scope-fixed to the trees that TIME device work as their
product — ``benchmarks/`` and ``flink_ml_tpu/obs`` — where an unfenced
number would be published as a measurement.
"""

from __future__ import annotations

import ast

from typing import List, Optional, Set

from ..core import ModuleInfo, Project
from .base import LintPass

_PARTIAL = {"functools.partial", "partial"}

#: call qualnames / attribute names that fence the dispatch stream
_FENCE_QUALS = {"numpy.asarray", "jax.device_get", "device_get"}
_FENCE_ATTRS = {"block_until_ready", "item", "stop", "fetch"}

_PERF_QUALS = {"time.perf_counter", "perf_counter"}


def _is_jit_expr(mod: ModuleInfo, node) -> bool:
    """``jax.jit(...)`` or ``partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    qual = mod.call_qualname(node)
    if qual in ("jax.jit", "jit"):
        return True
    if qual in _PARTIAL and node.args:
        inner = mod.qualname(node.args[0])
        return inner in ("jax.jit", "jit")
    return False


def _jitted_names(mod: ModuleInfo) -> Set[str]:
    """Names bound to jitted callables anywhere in the module:
    ``x = jax.jit(...)`` (conditional arms included) and defs decorated
    ``@jax.jit`` / ``@partial(jax.jit, ...)``."""
    out: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            value = node.value
            cands = ([value.body, value.orelse]
                     if isinstance(value, ast.IfExp) else [value])
            if any(_is_jit_expr(mod, c) for c in cands):
                out.add(node.targets[0].id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(mod, dec) or mod.qualname(dec) in (
                        "jax.jit", "jit"):
                    out.add(node.name)
    return out


def _own_nodes(fn: ast.AST):
    """The nodes of ``fn``'s OWN body, nested def subtrees pruned — a
    nested helper's timing bracket is its own scope (it would otherwise
    be reported twice, and a jitted call inside a never-called nested
    def would poison the enclosing function's bracket).  Lambdas stay:
    ``jax.jit(lambda ...)(x)`` executes inline."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _event(mod: ModuleInfo, node: ast.AST, jitted: Set[str]
           ) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    qual = mod.call_qualname(node)
    if qual in _PERF_QUALS:
        parent = mod.parent(node)
        if isinstance(parent, ast.Assign) and parent.value is node:
            return "start"
        return "read"
    if qual in _FENCE_QUALS:
        return "fence"
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _FENCE_ATTRS:
        return "fence"
    if isinstance(f, ast.Name) and f.id in jitted:
        return "jit"
    if _is_jit_expr(mod, f):        # direct jax.jit(fn)(args)
        return "jit"
    return None


class UnfencedTimingPass(LintPass):
    id = "unfenced-timing"
    describes = ("perf_counter timing that brackets a jitted call needs "
                 "a device fence (device_get/np.asarray/block_until_ready) "
                 "before the clock is read")
    roots = ("benchmarks", "flink_ml_tpu/obs")
    scope_fixed = True      # the convention applies to the timing trees
    hint = ("fetch a probe of the result with np.asarray/jax.device_get "
            "(or stop a utils/profiler.StepTimer on it) before reading "
            "the clock")

    def check_module(self, mod: ModuleInfo,
                     project: Project) -> List:
        jitted = _jitted_names(mod)
        findings = []
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            events = []
            for node in _own_nodes(fn):
                kind = _event(mod, node, jitted)
                if kind is not None:
                    events.append((node.lineno, node.col_offset,
                                   kind, node))
            events.sort(key=lambda e: (e[0], e[1]))
            armed = False
            unfenced_jit = False
            for _, _, kind, node in events:
                if kind == "start":
                    armed, unfenced_jit = True, False
                elif kind == "jit":
                    if armed:
                        unfenced_jit = True
                elif kind == "fence":
                    unfenced_jit = False
                elif kind == "read" and armed and unfenced_jit:
                    findings.append(mod.finding(
                        self.id, node,
                        "perf_counter read after a jitted call with no "
                        "device fence in between — this times the "
                        "dispatch enqueue, not the device work",
                        hint=self.hint))
                    unfenced_jit = False   # report once per interval
        return findings
