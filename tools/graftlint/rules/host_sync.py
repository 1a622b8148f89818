"""GL001 — host sync reachable inside a jitted function.

The PR 2 bug class: the serving path hid a per-call device→host
round-trip (``resolve_cap`` re-measured the probe cap and ``int()``'d
a device value on EVERY search), costing ~2.9 s/batch of pure fixed
cost until profiling found it.  Inside a traced function the same
shapes are outright errors or silent performance cliffs:

* ``x.item()`` / ``x.tolist()`` / ``float(x)`` / ``int(x)`` /
  ``bool(x)`` on a traced value → ``ConcretizationTypeError`` or, on a
  constant-folded path, a silent host sync baked into every call;
* ``np.asarray(x)`` / ``np.array(x)`` on a traced value → trace-time
  transfer;
* ``jax.device_get`` / ``block_until_ready`` inside jit → the sync the
  AOT plan layer exists to kill.

Scope: functions that are jit/shard_map targets — decorated
(``@jax.jit``, ``@functools.partial(jax.jit, ...)``) or passed by name
to ``jax.jit`` / ``shard_map`` anywhere in the
module — plus their lexically nested functions.  ``float()``/``int()``
are only flagged on values the local static-ness propagation cannot
prove static (constants, ``.shape``/``.ndim``/``len()`` chains and
names assigned from them, and parameters named in ``static_argnames``
stay silent).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.graftlint.core import (FileContext, Finding, Rule,
                                  call_keywords, dotted_name, register,
                                  str_tuple)

# dotted-name suffixes that mean "this call traces its first argument"
JIT_WRAPPERS = ("jit", "shard_map", "pmap")

NP_MODULES = {"np", "numpy", "onp"}
NP_SYNC_FUNCS = {"asarray", "array", "ascontiguousarray", "copy"}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}
CAST_BUILTINS = {"float", "int", "bool", "complex"}
STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "sharding"}


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return bool(name) and name.split(".")[-1] in JIT_WRAPPERS


def _jit_target(node: ast.Call) -> Optional[ast.AST]:
    """The traced callable of a jit/shard_map call, unwrapping nesting
    like ``jax.jit(jax.shard_map(local, ...))``."""
    if not node.args:
        # jax.jit(static_argnames=...)(f) decorator-factory form
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Call) and _is_jit_call(arg):
        return _jit_target(arg)
    return arg


def _static_argnames(call: ast.Call) -> Tuple[str, ...]:
    kw = call_keywords(call)
    return str_tuple(kw.get("static_argnames", ast.Constant(value=None)))


def _decorator_jit_info(fn: ast.AST) -> Optional[Tuple[str, ...]]:
    """→ static_argnames when ``fn`` is jit-decorated, else None."""
    for dec in getattr(fn, "decorator_list", []):
        name = dotted_name(dec)
        if name and name.split(".")[-1] in JIT_WRAPPERS:
            return ()
        if isinstance(dec, ast.Call):
            cname = dotted_name(dec.func) or ""
            tail = cname.split(".")[-1]
            if tail in JIT_WRAPPERS:                 # @jax.jit(...)
                return _static_argnames(dec)
            if tail == "partial" and dec.args:       # @partial(jax.jit,)
                inner = dotted_name(dec.args[0]) or ""
                if inner.split(".")[-1] in JIT_WRAPPERS:
                    return _static_argnames(dec)
    return None


class _StaticNames(ast.NodeVisitor):
    """Best-effort forward propagation of 'statically known at trace
    time' through one function body: shape/len/constant expressions and
    names assigned only from them."""

    def __init__(self, static: Set[str]):
        self.static = set(static)

    def is_static(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in self.static
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return True
            return self.is_static(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_static(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_static(node.left) and self.is_static(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_static(node.operand)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self.is_static(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.is_static(node.body) and self.is_static(node.orelse)
        if isinstance(node, ast.Compare):
            return (self.is_static(node.left)
                    and all(self.is_static(c) for c in node.comparators))
        if isinstance(node, ast.Call):
            # only bare-name BUILTINS — x.max() is a device reduction,
            # not the static builtin max()
            if not isinstance(node.func, ast.Name):
                return False
            if node.func.id == "len":
                return True          # len() of a traced array is static
            if node.func.id in {"min", "max", "abs", "round",
                                "sum"} | CAST_BUILTINS:
                return bool(node.args) and \
                    all(self.is_static(a) for a in node.args)
        return False

    def visit_Assign(self, node: ast.Assign):
        static = self.is_static(node.value)
        for tgt in node.targets:
            names = ([tgt] if isinstance(tgt, ast.Name)
                     else [e for e in getattr(tgt, "elts", [])
                           if isinstance(e, ast.Name)])
            for n in names:
                (self.static.add if static
                 else self.static.discard)(n.id)
        self.generic_visit(node)


@register
class HostSyncInJit(Rule):
    code = "GL001"
    name = "host-sync-in-jit"
    description = ("`.item()`, `float()`/`int()`, `np.asarray`, "
                   "`device_get`/`block_until_ready` inside a "
                   "jit/shard_map-traced function (the PR 2 "
                   "resolve_cap fixed-cost bug class)")
    paths = ("raft_tpu",)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        tree = ctx.tree
        if tree is None:
            return
        # pass 1: which function defs are traced, and with which
        # static argnames
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
        marked: Dict[ast.AST, Tuple[str, ...]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                statics = _decorator_jit_info(node)
                if statics is not None:
                    marked[node] = statics
            elif isinstance(node, ast.Call) and _is_jit_call(node):
                target = _jit_target(node)
                statics = _static_argnames(node)
                if isinstance(target, ast.Name):
                    for fn in defs.get(target.id, []):
                        marked.setdefault(fn, statics)
                elif isinstance(target, ast.Lambda):
                    marked.setdefault(target, statics)
        # pass 2: scan each traced body (incl. lexically nested defs)
        for fn, statics in marked.items():
            yield from self._scan_traced(ctx, fn, statics)

    def _scan_traced(self, ctx: FileContext, fn: ast.AST,
                     statics: Tuple[str, ...]) -> Iterable[Finding]:
        prop = _StaticNames(set(statics))
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        fname = getattr(fn, "name", "<lambda>")
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign):
                    prop.visit_Assign(node)
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    if attr in SYNC_METHODS:
                        yield ctx.finding(
                            self.code, node,
                            f".{attr}() inside jitted `{fname}` forces "
                            f"a device→host sync at trace/run time")
                        continue
                    root = (name or "").split(".")[0]
                    if root in NP_MODULES and attr in NP_SYNC_FUNCS:
                        yield ctx.finding(
                            self.code, node,
                            f"{name}() inside jitted `{fname}` pulls a "
                            f"traced value to the host — use jnp or "
                            f"hoist out of the traced body")
                        continue
                    if name in ("jax.device_get",):
                        yield ctx.finding(
                            self.code, node,
                            f"jax.device_get inside jitted `{fname}` "
                            f"is a per-call host round-trip")
                        continue
                elif isinstance(node.func, ast.Name):
                    if (node.func.id in CAST_BUILTINS
                            and len(node.args) == 1
                            and not node.keywords
                            and not prop.is_static(node.args[0])):
                        yield ctx.finding(
                            self.code, node,
                            f"{node.func.id}() on a (possibly traced) "
                            f"value inside jitted `{fname}` — "
                            f"concretizes/syncs; compute with jnp or "
                            f"hoist to the host caller")
