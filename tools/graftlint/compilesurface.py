"""Compile-surface model: every trace site, statically enumerated.

The single invariant every serving PR since PR 5 re-asserts
dynamically — ZERO steady-state compiles, read back from
``raft.plan.cache.*`` / ``raft.parallel.plan.*`` counters — has a
static shape: the set of programs a process can ever compile is the
product of each trace site's *key dimensions*, and the contract holds
exactly when every dimension reachable from a serving entry point is
drawn from a finite, pre-warmed rung set.  This module makes that set
a first-class object:

* **site discovery** — every ``jax.jit`` call (including the AOT
  ``jit(...).lower(...).compile()`` chain), ``pallas_call``,
  ``shard_map`` wrapper, ``_shmap_plan(key,
  builder)`` cache boundary and ``build_plan`` /
  ``compile_mutate_program`` / ``compile_tail_program`` builder call
  in the program;
* **key-dimension extraction** — ``_shmap_plan`` key-tuple elements,
  builder-call key arguments (:data:`BUILDER_KEY_PARAMS`), decorator
  ``static_argnames``;
* **classification** — a backward interprocedural dataflow over each
  dimension expression: constants and process-level handles (mesh,
  axis, dtypes, metric enums) are FINITE; values declared in a
  module-level :data:`RUNG_DECL_NAME` dict (the rung-set declarations
  threaded through ``serve/ladder.py``, ``neighbors/plan.py``,
  ``mutate/program.py``, ``serve/dist.py``, ``parallel/ivf.py``) are
  FINITE with their rung set attached; loop variables iterating a
  declared grid are FINITE; anything tracing back to runtime data —
  ``queries.shape[0]``, ``len(queries)``, wall-clock reads, an
  undeclared config attribute — is UNBOUNDED.  Parameters propagate
  through resolved call sites (worst classification wins), so
  ``nq = q.shape[0]`` three frames above a builder call still poisons
  the dimension;
* **serving reachability** — BFS from the serving entry points
  (:data:`ENTRY_POINTS`: batcher dispatch, ``FleetRouter.search``,
  ``MutableIndex`` search/mutate, the plan-contract ``search``
  methods) over a lightweight call resolution that, unlike the
  concurrency call graph, also follows function-level imports and the
  builder calls GL008 summarizes as blocking events;
* **pre-warm coverage** — a grid rung set (a declaration whose set
  name differs from its dimension name) counts as warmed when some
  NON-serving-reachable function loops over it (directly, or through
  a helper whose body names it) and transitively reaches a compile.

Known, deliberate imprecision (argue findings against this model):
``X.shape[0]`` is the runtime batch dimension (unbounded when ``X``
is), ``X.shape[i>0]`` is a feature dimension (fixed per index);
slicing classifies by its bounds (``q[:s]`` has shape ``s``); a
zero-argument call is treated as process-constant (env-mode reads);
``# compile-surface: bounded=<reason>`` on a site's first line
asserts boundedness the dataflow cannot see — the reason lands in the
manifest, and GL012/GL013 trust it.

Everything is stdlib-``ast`` only, like the rest of graftlint.
"""

from __future__ import annotations

import ast
import re
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.graftlint.core import call_keywords, dotted_name, str_tuple

__all__ = ["Surface", "build_surface", "get_surface",
           "RUNG_DECL_NAME", "ENTRY_POINTS"]

FINITE = "FINITE"
UNBOUNDED = "UNBOUNDED"

MANIFEST_VERSION = 1

# module-level declaration constant: {dim_name: (set_name, values|None,
# desc)}.  set_name == dim_name declares a per-process constant;
# set_name != dim_name declares a GRID rung set that GL013 requires a
# pre-warm loop for.
RUNG_DECL_NAME = "COMPILE_SURFACE_RUNGS"

# ``# compile-surface: bounded=<reason>`` on the site's first line
BOUNDED_RE = re.compile(r"#\s*compile-surface:\s*bounded=(.+?)\s*$")

# serving entry points: (class glob, method glob) — the dispatch
# surface of the batcher, fleet router, mutable index and every
# plan-contract handle the ladders serve from
ENTRY_POINTS = (
    ("*SearchServer*", "submit"),
    ("*SearchServer*", "search"),
    ("*SearchServer*", "_execute"),
    ("*SearchServer*", "_loop"),
    ("*SearchServer*", "_dispatch"),
    ("*SearchServer*", "_plan_for_batch"),
    ("*SearchServer*", "_plan_after_failure"),
    ("FleetRouter", "submit"),
    ("FleetRouter", "search"),
    ("FleetRouter", "_dispatch"),
    ("Replica", "submit"),
    ("Replica", "search"),
    ("MutableIndex", "search"),
    ("MutableIndex", "upsert"),
    ("MutableIndex", "delete"),
    ("*Plan*", "search"),
    ("*Plan*", "search_batched"),
)

# builder idioms: bare callee name -> the parameter names that key the
# compiled program (DIM_RENAME maps a parameter to its manifest name)
BUILDER_KEY_PARAMS = {
    "build_plan": ("queries", "k", "params"),
    "compile_mutate_program": ("nq", "k", "params", "delta_cap",
                               "tomb_words"),
    "compile_tail_program": ("nq", "k", "dim", "delta_cap",
                             "tomb_words"),
}
DIM_RENAME = {"queries": "nq", "rep_queries": "nq"}

# process-level handles and enums: finitely many per process, fixed at
# server/plan construction
STRUCTURAL_NAMES = frozenset({
    "mesh", "axis", "axis_name", "comms", "kind", "sqrt", "merge",
    "family", "metric", "descending", "dim", "dtype", "d_dtype",
    "i_dtype", "lut_dtype", "internal_dtype", "internal_distance_dtype",
    "per_cluster", "use_pallas", "use_fused", "use_list", "gather",
    "lc", "fused", "interpret", "rescoring", "params", "self", "cls",
})

JIT_NAMES = ("jit", "pmap")
SHMAP_NAMES = ("shard_map",)

_MAX_DEPTH = 16


@dataclass
class RungDecl:
    module: str
    rel: str
    dim: str
    set_name: str
    values: Optional[Tuple] = None
    desc: str = ""

    @property
    def is_grid(self) -> bool:
        return self.set_name != self.dim


@dataclass
class KeyDim:
    name: str
    expr: str
    cls: str                       # FINITE | UNBOUNDED
    source: str                    # why

    def sig(self) -> str:
        return f"{self.name}={self.cls}"


@dataclass
class TraceSite:
    rel: str
    line: int
    func: str                      # enclosing qualname or "<module>"
    kind: str                      # jit | aot | jit-decorator |
    #                                pallas_call | shard_map |
    #                                shmap_plan | plan_build
    cached_by: Optional[str] = None  # shmap_plan | plan-builder |
    #                                  builder-thunk | jit-cache | None
    dims: List[KeyDim] = field(default_factory=list)
    serving_reachable: bool = False
    bounded_pragma: Optional[str] = None

    def unbounded_dims(self) -> List[KeyDim]:
        if self.bounded_pragma is not None:
            return []
        return [d for d in self.dims if d.cls == UNBOUNDED]

    def worst_case_programs(self) -> Optional[int]:
        """Product of known rung-set sizes over this site's dims; None
        when any FINITE dim has no statically known value set."""
        total = 1
        for d in self.dims:
            if d.cls == UNBOUNDED and self.bounded_pragma is None:
                return None
            m = re.search(r"\|(\d+)\|", d.source)
            if m:
                total *= int(m.group(1))
            elif d.cls == FINITE and d.source.startswith("rung:") \
                    and "|" not in d.source:
                return None
        return total

    def signature(self) -> dict:
        return {
            "file": self.rel,
            "function": self.func,
            "kind": self.kind,
            "cached_by": self.cached_by,
            "serving_reachable": self.serving_reachable,
            "dims": [d.sig() for d in self.dims],
            "bounded": self.bounded_pragma is not None,
        }


class Surface:
    """The enumerated compile surface of one program."""

    def __init__(self, sites: List[TraceSite],
                 rungs: Dict[str, RungDecl],
                 warm_sets: Set[str],
                 warm_sites: Dict[str, List[Tuple[str, int, str]]]):
        self.sites = sites
        self.rungs = rungs
        self.warm_sets = warm_sets
        # grid set name -> [(rel, line, func)] of covering warm loops
        self.warm_sites = warm_sites

    def serving_sites(self) -> List[TraceSite]:
        return [s for s in self.sites if s.serving_reachable]

    def to_manifest(self) -> dict:
        sites = []
        for s in self.sites:
            sites.append({
                "file": s.rel, "line": s.line, "function": s.func,
                "kind": s.kind, "cached_by": s.cached_by,
                "serving_reachable": s.serving_reachable,
                "bounded_pragma": s.bounded_pragma,
                "dims": [{"name": d.name, "expr": d.expr,
                          "class": d.cls, "source": d.source}
                         for d in s.dims],
                "worst_case_programs": s.worst_case_programs(),
            })
        serving = self.serving_sites()
        unbounded = [d for s in serving for d in s.unbounded_dims()]
        known = [s.worst_case_programs() for s in serving]
        return {
            "version": MANIFEST_VERSION,
            "sites": sites,
            "rungs": [{"module": r.module, "dim": r.dim,
                       "set": r.set_name,
                       "values": (list(r.values)
                                  if r.values is not None else None),
                       "grid": r.is_grid, "desc": r.desc}
                      for r in sorted(self.rungs.values(),
                                      key=lambda r: (r.module, r.dim))],
            "warm_coverage": {
                name: [{"file": rel, "line": line, "function": fn}
                       for rel, line, fn in sorted(sites_)]
                for name, sites_ in sorted(self.warm_sites.items())},
            "totals": {
                "sites": len(self.sites),
                "serving_reachable": len(serving),
                "serving_unbounded_dims": len(unbounded),
                "worst_case_serving_programs":
                    (None if any(w is None for w in known)
                     else sum(known)),
            },
        }


# --------------------------------------------------------------------------
# collection walk
# --------------------------------------------------------------------------

def _parents(tree: ast.AST) -> dict:
    out = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return "<expr>"


def _is_zero_arg_builder(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    a = fn.args
    return not (a.args or a.posonlyargs or a.kwonlyargs or a.vararg
                or a.kwarg)


def _fn_params(fn: ast.AST) -> List[str]:
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]


def _walk_no_nested(body) -> "list":
    """Statement-level walk of a function body that does NOT descend
    into nested ``def``/``lambda`` bodies — a nested closure runs when
    *called* (for builder thunks: on a cache miss), so its calls are
    not steady-state edges (same stance as the concurrency
    call graph)."""
    out = []
    stack = list(body) if isinstance(body, list) else [body]
    while stack:
        node = stack.pop()
        out.append(node)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda)):
                continue
            stack.append(child)
    return out


class _FuncScope:
    """Per-function facts the classifier and warm detector need."""

    def __init__(self, qual: str, rel: str, module: str,
                 cls_qual: Optional[str], fn: ast.AST):
        self.qual = qual
        self.rel = rel
        self.module = module
        self.cls_qual = cls_qual
        self.fn = fn
        self.params = _fn_params(fn) if fn is not None else []
        # name -> list of assigned value exprs
        self.assigns: Dict[str, List[ast.AST]] = {}
        # name -> the loop iterable it is bound from
        self.loop_iters: Dict[str, ast.AST] = {}
        self.local_imports: Dict[str, str] = {}
        self.loops: List[Tuple[ast.AST, int]] = []   # (iterable, line)

    def record(self) -> None:
        body = self.fn.body if isinstance(self.fn.body, list) \
            else [self.fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign):
                    for tgt in node.targets:
                        for n in ([tgt] if isinstance(tgt, ast.Name)
                                  else [e for e in
                                        getattr(tgt, "elts", [])
                                        if isinstance(e, ast.Name)]):
                            self.assigns.setdefault(n.id, []).append(
                                node.value)
                elif isinstance(node, ast.For):
                    self.loops.append((node.iter, node.lineno))
                    tgts = ([node.target]
                            if isinstance(node.target, ast.Name)
                            else [e for e in
                                  getattr(node.target, "elts", [])
                                  if isinstance(e, ast.Name)])
                    for n in tgts:
                        self.loop_iters[n.id] = node.iter
                elif isinstance(node, (ast.ListComp, ast.SetComp,
                                       ast.DictComp,
                                       ast.GeneratorExp)):
                    for gen in node.generators:
                        self.loops.append((gen.iter, node.lineno))
                        tgts = ([gen.target]
                                if isinstance(gen.target, ast.Name)
                                else [e for e in
                                      getattr(gen.target, "elts", [])
                                      if isinstance(e, ast.Name)])
                        for n in tgts:
                            self.loop_iters[n.id] = gen.iter
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        alias = a.asname or a.name.split(".")[0]
                        self.local_imports[alias] = (
                            a.name if a.asname else a.name.split(".")[0])
                elif isinstance(node, ast.ImportFrom) and node.module:
                    for a in node.names:
                        if a.name == "*":
                            continue
                        alias = a.asname or a.name
                        self.local_imports[alias] = \
                            f"{node.module}.{a.name}"


class _Collector:
    """One pass over every module tree: sites, light call edges,
    function scopes, rung declarations, pragmas."""

    def __init__(self, program):
        self.p = program
        self.sites: List[TraceSite] = []
        self.scopes: Dict[str, _FuncScope] = {}
        # caller qual -> [(callee qual, Call node)]
        self.calls: Dict[str, List[Tuple[str, ast.Call]]] = {}
        # callee qual -> [(caller qual, Call node)]
        self.rcalls: Dict[str, List[Tuple[str, ast.Call]]] = {}
        self.rungs: Dict[str, RungDecl] = {}
        self._def_to_qual: Dict[Tuple[str, int, str], str] = {}
        for qual, fi in program.functions.items():
            self._def_to_qual[(fi.rel, fi.lineno, fi.name)] = qual

    # -- light call resolution --------------------------------------------
    def _resolve(self, scope: _FuncScope,
                 call: ast.Call) -> Optional[str]:
        f = call.func
        p = self.p
        mod = p.modules.get(scope.module)
        if mod is None:
            return None

        def resolve_dotted(d: str) -> Optional[str]:
            head = d.split(".")[0]
            if head in scope.local_imports:
                base = scope.local_imports[head]
                rest = d.split(".")[1:]
                target = ".".join([base] + rest) if rest else base
                if target in p.modules:
                    return None
                if "." in target:
                    bmod, sym = target.rsplit(".", 1)
                    kind, qual = p.resolve_symbol(bmod, sym) \
                        if bmod in p.modules else (None, None)
                    if kind == "func":
                        return qual
            kind, qual = p.resolve_symbol(scope.module, d)
            return qual if kind == "func" else None

        if isinstance(f, ast.Name):
            return resolve_dotted(f.id)
        if isinstance(f, ast.Attribute):
            v = f.value
            if isinstance(v, ast.Name) and v.id in ("self", "cls") \
                    and scope.cls_qual is not None:
                return p.find_method(scope.cls_qual, f.attr)
            if isinstance(v, ast.Attribute) and \
                    isinstance(v.value, ast.Name) and \
                    v.value.id == "self" and scope.cls_qual is not None:
                t = p.class_attr_type(scope.cls_qual, v.attr)
                if t:
                    kind, qual = p.resolve_symbol(scope.module, t)
                    if kind == "class":
                        m = p.find_method(qual, f.attr)
                        if m:
                            return m
            d = dotted_name(f)
            if d is not None:
                got = resolve_dotted(d)
                if got:
                    return got
            return p.unique_method(f.attr)
        return None

    # -- site kinds ---------------------------------------------------------
    @staticmethod
    def _tail_name(call: ast.Call) -> Optional[str]:
        d = dotted_name(call.func)
        return d.split(".")[-1] if d else None

    def _site_kind(self, call: ast.Call, parents: dict
                   ) -> Optional[str]:
        name = self._tail_name(call)
        if name is None:
            return None
        par = parents.get(call)
        if isinstance(par, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and call in par.decorator_list:
            return None           # decorators are jit-decorator sites
        if name in JIT_NAMES:
            # only the outermost of jit(shard_map(...)) is one site
            par = parents.get(call)
            if isinstance(par, ast.Call) and \
                    self._tail_name(par) in JIT_NAMES and \
                    par.args and par.args[0] is call:
                return None
            # jit(...).lower(...).compile() is the AOT idiom
            if isinstance(par, ast.Attribute) and par.attr == "lower":
                return "aot"
            return "jit"
        if name.endswith("pallas_call"):
            return "pallas_call"
        if name in SHMAP_NAMES:
            par = parents.get(call)
            while isinstance(par, ast.Call) or \
                    isinstance(par, ast.Attribute):
                if isinstance(par, ast.Call) and \
                        self._tail_name(par) in JIT_NAMES:
                    return None          # folded into the jit site
                par = parents.get(par)
            return "shard_map"
        if name == "_shmap_plan":
            return "shmap_plan"
        if name in BUILDER_KEY_PARAMS:
            return "plan_build"
        return None

    # -- one module ---------------------------------------------------------
    def collect_module(self, rel: str, tree: ast.AST) -> None:
        module = self.p.rel_to_module.get(rel)
        if module is None:
            return
        parents = _parents(tree)
        src_lines = (self.p.sources.get(rel) or "").splitlines()

        # rung declarations (module level)
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == RUNG_DECL_NAME:
                try:
                    obj = ast.literal_eval(node.value)
                except Exception:
                    continue
                if not isinstance(obj, dict):
                    continue
                for dim, spec in obj.items():
                    if not (isinstance(spec, tuple) and len(spec) == 3):
                        continue
                    set_name, values, desc = spec
                    self.rungs[str(dim)] = RungDecl(
                        module=module, rel=rel, dim=str(dim),
                        set_name=str(set_name),
                        values=(tuple(values)
                                if values is not None else None),
                        desc=str(desc))

        def qual_of(defnode: ast.AST) -> Optional[str]:
            return self._def_to_qual.get(
                (rel, defnode.lineno, defnode.name))

        def enclosing(node: ast.AST):
            """(program qual or None, nested-def chain, def node)."""
            chain = []
            cur = parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    q = None
                    if not isinstance(cur, ast.Lambda):
                        q = qual_of(cur)
                    if q is not None:
                        return q, chain, cur
                    chain.append(cur)
                cur = parents.get(cur)
            return None, chain, None

        # function scopes + call edges
        for qual, fi in self.p.functions.items():
            if fi.rel != rel:
                continue
            body = self.p._bodies.get(qual)
            if body is None:
                continue
            scope = _FuncScope(qual, rel, module, fi.cls, body)
            scope.record()
            self.scopes[qual] = scope
            for node in _walk_no_nested(body.body):
                if isinstance(node, ast.Call):
                    callee = self._resolve(scope, node)
                    if callee is not None and callee != qual:
                        self.calls.setdefault(qual, []).append(
                            (callee, node))
                        self.rcalls.setdefault(callee, []).append(
                            (qual, node))

        # trace sites
        for node in ast.walk(tree):
            decorator_fn = None
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    info = self._decorator_jit(dec)
                    if info is not None:
                        decorator_fn = (node, info)
                        break
                if decorator_fn is not None:
                    fn, statics = decorator_fn
                    q = qual_of(fn) or "<module>"
                    self.sites.append(TraceSite(
                        rel=rel, line=fn.lineno, func=q,
                        kind="jit-decorator", cached_by="jit-cache",
                        dims=[KeyDim(name=s, expr=s, cls="", source="")
                              for s in statics]))
                continue
            if not isinstance(node, ast.Call):
                continue
            kind = self._site_kind(node, parents)
            if kind is None:
                continue
            qual, chain, defnode = enclosing(node)
            cached = None
            if kind in ("jit", "aot", "pallas_call", "shard_map"):
                if any(_is_zero_arg_builder(fn) for fn in chain):
                    cached = "builder-thunk"
                elif qual is not None and \
                        qual.rsplit(".", 1)[-1] in BUILDER_KEY_PARAMS:
                    cached = "plan-builder"
                elif defnode is not None and any(
                        self._decorator_jit(d) is not None
                        for d in defnode.decorator_list):
                    cached = "enclosing-jit"
            elif kind == "shmap_plan":
                cached = "shmap_plan"
            elif kind == "plan_build":
                cached = "plan-cache"
            pragma = None
            if 1 <= node.lineno <= len(src_lines):
                m = BOUNDED_RE.search(src_lines[node.lineno - 1])
                if m:
                    pragma = m.group(1)
            self.sites.append(TraceSite(
                rel=rel, line=node.lineno,
                func=qual or "<module>", kind=kind, cached_by=cached,
                bounded_pragma=pragma,
                dims=self._site_dims(kind, node)))

    @staticmethod
    def _decorator_jit(dec: ast.AST) -> Optional[Tuple[str, ...]]:
        name = dotted_name(dec)
        if name and name.split(".")[-1] in JIT_NAMES:
            return ()
        if isinstance(dec, ast.Call):
            tail = (dotted_name(dec.func) or "").split(".")[-1]
            if tail in JIT_NAMES:
                kw = call_keywords(dec)
                return str_tuple(kw.get("static_argnames",
                                        ast.Constant(value=None)))
            if tail == "partial" and dec.args:
                inner = (dotted_name(dec.args[0]) or "").split(".")[-1]
                if inner in JIT_NAMES:
                    kw = call_keywords(dec)
                    return str_tuple(kw.get("static_argnames",
                                            ast.Constant(value=None)))
        return None

    def _site_dims(self, kind: str, call: ast.Call) -> List[KeyDim]:
        """The unclassified dimension expressions of one site (the
        classifier fills ``cls``/``source`` later)."""
        dims: List[KeyDim] = []
        if kind == "shmap_plan" and call.args:
            key = call.args[0]
            elts = key.elts if isinstance(key, (ast.Tuple, ast.List)) \
                else [key]
            for e in elts:
                dims.append(KeyDim(name=self._dim_name(e),
                                   expr=_unparse(e), cls="", source="",
                                   ))
                dims[-1]._node = e      # type: ignore[attr-defined]
        elif kind == "plan_build":
            name = self._tail_name(call)
            params = BUILDER_KEY_PARAMS.get(name, ())
            bound = self._bind_args(name, call)
            for pname in params:
                expr = bound.get(pname)
                if expr is None:
                    continue
                d = KeyDim(name=DIM_RENAME.get(pname, pname),
                           expr=_unparse(expr), cls="", source="")
                d._node = expr          # type: ignore[attr-defined]
                dims.append(d)
        return dims

    def _bind_args(self, bare_name: str,
                   call: ast.Call) -> Dict[str, ast.AST]:
        """Positional+keyword binding against the resolved callee's
        signature (falls back to any program function of that name)."""
        callee = None
        for qual, fi in self.p.functions.items():
            if fi.name == bare_name:
                callee = self.p._bodies.get(qual)
                if callee is not None:
                    break
        if callee is None:
            return {}
        params = _fn_params(callee)
        if params and params[0] in ("self", "cls"):
            params = params[1:]
        out: Dict[str, ast.AST] = {}
        for i, arg in enumerate(call.args):
            if i < len(params):
                out[params[i]] = arg
        for kw in call.keywords:
            if kw.arg:
                out[kw.arg] = kw.value
        return out

    @staticmethod
    def _dim_name(e: ast.AST) -> str:
        if isinstance(e, ast.Constant):
            return repr(e.value)
        if isinstance(e, ast.Name):
            return e.id
        if isinstance(e, ast.Attribute):
            return e.attr
        if isinstance(e, ast.Call):
            d = dotted_name(e.func)
            if d and d.split(".")[-1] in ("int", "float", "bool",
                                          "str") and e.args:
                return _Collector._dim_name(e.args[0])
            return (d or "call").split(".")[-1]
        if isinstance(e, ast.Subscript):
            return _Collector._dim_name(e.value)
        return _unparse(e)[:32]


# --------------------------------------------------------------------------
# classification (backward dataflow)
# --------------------------------------------------------------------------

class _Classifier:
    def __init__(self, col: _Collector):
        self.col = col
        self._memo: Dict[Tuple[str, str], Tuple[str, str]] = {}

    def _lookup_name(self, name: str) -> Optional[Tuple[str, str]]:
        n = name.lstrip("_")
        decl = self.col.rungs.get(n) or self.col.rungs.get(name)
        if decl is not None:
            size = f"|{len(decl.values)}|" if decl.values is not None \
                else ""
            return (FINITE, f"rung:{decl.set_name}{size}")
        if n in STRUCTURAL_NAMES or name in STRUCTURAL_NAMES:
            return (FINITE, "structural")
        return None

    def _grid_sets_of(self, text: str) -> List[str]:
        # (?<![A-Za-z0-9]) instead of \b: `self._rungs` and
        # `cfg["shapes"]` both name their grid
        out = []
        for decl in self.col.rungs.values():
            if decl.is_grid and decl.set_name not in out and \
                    re.search(r"(?<![A-Za-z0-9])%s\b"
                              % re.escape(decl.set_name), text):
                out.append(decl.set_name)
        return out

    def _grid_set_of(self, text: str) -> Optional[str]:
        sets = self._grid_sets_of(text)
        return sets[0] if sets else None

    def _join(self, results: Sequence[Tuple[str, str]],
              empty: Tuple[str, str]) -> Tuple[str, str]:
        if not results:
            return empty
        worst = None
        best = None
        for r in results:
            if r[0] == UNBOUNDED:
                worst = r if worst is None else worst
            else:
                best = r if best is None or \
                    (best[1] == "structural"
                     and r[1].startswith("rung:")) else best
        if worst is not None:
            return worst
        return best if best is not None else empty

    def classify(self, expr: ast.AST, qual: Optional[str],
                 depth: int = 0,
                 stack: Optional[Set[Tuple[str, str]]] = None
                 ) -> Tuple[str, str]:
        if depth > _MAX_DEPTH:
            return (UNBOUNDED, "resolution depth exceeded")
        stack = stack if stack is not None else set()
        key = (qual or "<module>", _unparse(expr))
        if key in self._memo:
            return self._memo[key]
        if key in stack:
            return (FINITE, "recursive (cycle-bounded)")
        stack.add(key)
        self._memo[key] = (FINITE, "recursive (cycle-bounded)")
        out = self._classify(expr, qual, depth, stack)
        stack.discard(key)
        self._memo[key] = out
        return out

    def _classify(self, expr, qual, depth, stack):
        join = self._join
        cls = lambda e: self.classify(e, qual, depth + 1, stack)  # noqa: E731
        if isinstance(expr, ast.Constant):
            return (FINITE, "constant")
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return join([cls(e) for e in expr.elts],
                        (FINITE, "constant"))
        if isinstance(expr, ast.Starred):
            return cls(expr.value)
        if isinstance(expr, ast.Name):
            return self._classify_name(expr.id, qual, depth, stack)
        if isinstance(expr, ast.Attribute):
            hit = self._lookup_name(expr.attr)
            if hit is not None:
                return hit
            # enum member access (DistanceType.L2SqrtExpanded,
            # CodebookGen.PER_CLUSTER): a CamelCase base names a class
            if isinstance(expr.value, ast.Name) and \
                    expr.value.id[:1].isupper():
                return (FINITE, "enum member")
            if isinstance(expr.value, ast.Name) and \
                    expr.value.id in ("self", "cls"):
                return (UNBOUNDED,
                        f"undeclared attribute `self.{expr.attr}`")
            base = cls(expr.value)
            if base[0] == UNBOUNDED:
                return base
            return (UNBOUNDED, f"undeclared attribute `.{expr.attr}`")
        if isinstance(expr, ast.Subscript):
            # X.shape[0] = runtime batch dim; X.shape[i>0] = feature dim
            if isinstance(expr.value, ast.Attribute) and \
                    expr.value.attr == "shape":
                idx = expr.slice
                if isinstance(idx, ast.Constant) and \
                        isinstance(idx.value, int) and idx.value == 0:
                    base = cls(expr.value.value)
                    if base[0] == UNBOUNDED:
                        return (UNBOUNDED,
                                "runtime batch shape "
                                f"`{_unparse(expr)}`")
                    return (FINITE, "shape of a bounded value")
                return (FINITE, "feature/mesh dimension")
            text = _unparse(expr.value)
            grid = self._grid_set_of(text)
            if grid is not None:
                decl = next(d for d in self.col.rungs.values()
                            if d.set_name == grid)
                size = f"|{len(decl.values)}|" \
                    if decl.values is not None else ""
                return (FINITE, f"rung:{grid}{size}")
            if isinstance(expr.slice, ast.Slice):
                bounds = [b for b in (expr.slice.lower,
                                      expr.slice.upper,
                                      expr.slice.step) if b is not None]
                return join([cls(b) for b in bounds],
                            (FINITE, "constant slice"))
            return join([cls(expr.value), cls(expr.slice)],
                        (FINITE, "constant"))
        if isinstance(expr, ast.Call):
            d = dotted_name(expr.func) or ""
            root, tail = (d.split(".")[0] if d else ""), \
                (d.split(".")[-1] if d else "")
            if root == "time" or tail in ("monotonic", "perf_counter",
                                          "time_ns"):
                return (UNBOUNDED, f"wall-clock `{_unparse(expr)}`")
            if d.startswith("os.environ") or tail == "getenv":
                return (FINITE, "env (process-constant)")
            args = list(expr.args) + [kw.value for kw in expr.keywords]
            return join([cls(a) for a in args],
                        (FINITE, "zero-arg call (process-constant)"))
        if isinstance(expr, (ast.BinOp,)):
            return join([cls(expr.left), cls(expr.right)],
                        (FINITE, "constant"))
        if isinstance(expr, ast.BoolOp):
            return join([cls(v) for v in expr.values],
                        (FINITE, "constant"))
        if isinstance(expr, ast.UnaryOp):
            return cls(expr.operand)
        if isinstance(expr, ast.Compare):
            return join([cls(expr.left)]
                        + [cls(c) for c in expr.comparators],
                        (FINITE, "constant"))
        if isinstance(expr, ast.IfExp):
            return join([cls(expr.test), cls(expr.body),
                         cls(expr.orelse)], (FINITE, "constant"))
        if isinstance(expr, ast.JoinedStr):
            return join([cls(v.value) for v in expr.values
                         if isinstance(v, ast.FormattedValue)],
                        (FINITE, "constant"))
        if isinstance(expr, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            return join([cls(g.iter) for g in expr.generators],
                        (FINITE, "constant"))
        if isinstance(expr, ast.Lambda):
            return (FINITE, "callable")
        return (UNBOUNDED, f"unmodeled expression `{_unparse(expr)}`")

    def _classify_name(self, name: str, qual, depth, stack):
        scope = self.col.scopes.get(qual) if qual else None
        if scope is not None:
            if name in scope.loop_iters:
                it = scope.loop_iters[name]
                text = _unparse(it)
                # `for x in helper():` — the helper's body may name
                # the grid (``_warm_delta_rungs`` over
                # delta_capacities); the helper is the more specific
                # answer when both match
                grid = self._grid_via_helper(it, scope) or \
                    self._grid_set_of(text)
                if grid is not None:
                    decl = next(d for d in self.col.rungs.values()
                                if d.set_name == grid)
                    size = f"|{len(decl.values)}|" \
                        if decl.values is not None else ""
                    return (FINITE, f"rung:{grid}{size}")
                return self.classify(it, qual, depth + 1, stack)
            if name in scope.assigns:
                return self._join(
                    [self.classify(v, qual, depth + 1, stack)
                     for v in scope.assigns[name]],
                    (FINITE, "constant"))
            if name in scope.params:
                callers = self.col.rcalls.get(qual, ())
                results = []
                for caller_qual, call in callers:
                    bound = self._bind_call(qual, call)
                    arg = bound.get(name)
                    if arg is not None:
                        results.append(self.classify(
                            arg, caller_qual, depth + 1, stack))
                if results:
                    return self._join(results, (FINITE, "constant"))
                hit = self._lookup_name(name)
                if hit is not None:
                    return hit
                return (UNBOUNDED,
                        f"undeclared parameter `{name}` (runtime "
                        f"input at an entry point)")
        hit = self._lookup_name(name)
        if hit is not None:
            return hit
        return (UNBOUNDED, f"undeclared `{name}`")

    def _grid_via_helper(self, it: ast.AST,
                         scope: _FuncScope) -> Optional[str]:
        for node in ast.walk(it):
            if not isinstance(node, ast.Call):
                continue
            callee = self.col._resolve(scope, node)
            if callee is None:
                continue
            body = self.col.p._bodies.get(callee)
            if body is None:
                continue
            grid = self._grid_set_of(_unparse(body))
            if grid is not None:
                return grid
        return None

    def _bind_call(self, callee_qual: str,
                   call: ast.Call) -> Dict[str, ast.AST]:
        body = self.col.p._bodies.get(callee_qual)
        if body is None:
            return {}
        params = _fn_params(body)
        fi = self.col.p.functions.get(callee_qual)
        if params and params[0] in ("self", "cls") and \
                fi is not None and fi.cls is not None:
            params = params[1:]
        out: Dict[str, ast.AST] = {}
        for i, arg in enumerate(call.args):
            if i < len(params):
                out[params[i]] = arg
        for kw in call.keywords:
            if kw.arg:
                out[kw.arg] = kw.value
        return out


# --------------------------------------------------------------------------
# reachability + warm coverage + assembly
# --------------------------------------------------------------------------

def _entry_quals(program) -> Set[str]:
    import fnmatch
    out: Set[str] = set()
    for qual, fi in program.functions.items():
        if fi.cls is None:
            continue
        cname = fi.cls.rsplit(".", 1)[-1]
        for cpat, mpat in ENTRY_POINTS:
            if fnmatch.fnmatch(cname, cpat) and \
                    fnmatch.fnmatch(fi.name, mpat):
                out.add(qual)
                break
    return out


def _reachable(col: _Collector, entries: Set[str]) -> Set[str]:
    seen = set(entries)
    work = list(entries)
    while work:
        cur = work.pop()
        for callee, _node in col.calls.get(cur, ()):
            if callee not in seen:
                seen.add(callee)
                work.append(callee)
    return seen


_COMPILE_KINDS = frozenset({"jit", "aot", "pallas_call", "shard_map",
                            "shmap_plan", "plan_build"})


def _warm_coverage(col: _Collector, classifier: _Classifier,
                   reachable: Set[str]
                   ) -> Tuple[Set[str],
                              Dict[str, List[Tuple[str, int, str]]]]:
    """Grid rung sets with at least one pre-warm loop: a loop over the
    set, in a NON-serving-reachable function, that transitively
    reaches a compile."""
    compiles_in: Set[str] = {s.func for s in col.sites
                             if s.kind in _COMPILE_KINDS
                             and s.func != "<module>"}
    reaches_compile: Dict[str, bool] = {}

    def reaches(qual: str, stack: Set[str]) -> bool:
        if qual in reaches_compile:
            return reaches_compile[qual]
        if qual in stack:
            return False
        stack.add(qual)
        ok = qual in compiles_in or any(
            reaches(callee, stack)
            for callee, _n in col.calls.get(qual, ()))
        stack.discard(qual)
        reaches_compile[qual] = ok
        return ok

    covered: Set[str] = set()
    sites: Dict[str, List[Tuple[str, int, str]]] = {}
    for qual, scope in col.scopes.items():
        if qual in reachable:
            continue
        if not reaches(qual, set()):
            continue
        for it, line in scope.loops:
            grids = classifier._grid_sets_of(_unparse(it))
            helper = classifier._grid_via_helper(it, scope)
            if helper is not None and helper not in grids:
                grids.append(helper)
            for grid in grids:
                covered.add(grid)
                sites.setdefault(grid, []).append(
                    (scope.rel, line, qual))
    return covered, sites


def build_surface(program) -> Surface:
    col = _Collector(program)
    for rel in sorted(program.trees):
        col.collect_module(rel, program.trees[rel])
    entries = _entry_quals(program)
    reachable = _reachable(col, entries)
    classifier = _Classifier(col)
    for site in col.sites:
        site.serving_reachable = site.func in reachable
        for d in site.dims:
            node = getattr(d, "_node", None)
            if node is None:
                # jit-decorator static_argnames: the jax jit cache
                # keys them — name-lookup attaches rung info when
                # declared, otherwise they stay FINITE (whether a
                # caller feeds unbounded VALUES is the keyed sites'
                # dataflow question, not the decorator's)
                hit = classifier._lookup_name(d.name)
                d.cls, d.source = hit if hit is not None else (
                    FINITE, "static-argname (jit-cache-keyed)")
                continue
            qual = site.func if site.func != "<module>" else None
            d.cls, d.source = classifier.classify(node, qual)
    warm_sets, warm_sites = _warm_coverage(col, classifier, reachable)
    col.sites.sort(key=lambda s: (s.rel, s.line))
    return Surface(col.sites, col.rungs, warm_sets, warm_sites)


# one Surface per Program (shared by GL012/GL013/GL014 and the
# --compile-surface CLI within a run; programs are cached upstream)
_SURFACES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def get_surface(program) -> Surface:
    surf = _SURFACES.get(program)
    if surf is None:
        surf = build_surface(program)
        _SURFACES[program] = surf
    return surf
