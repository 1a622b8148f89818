"""GL010/GL011 — the metric/span-name taxonomy, as registry rules.

These are the source-mode checks that lived in
``tools/check_metric_names.py`` (PR 1/3), folded behind the graftlint
registry so one gate runs everything; ``check_metric_names.py`` stays
as a thin shim over :func:`check_events` (and keeps its ``--text`` /
``--trace`` CLI modes unchanged — those validate *exported* artifacts,
not source).

* **GL010** — an ``obs.counter/gauge/histogram/timed`` or
  ``obs.span/spans.span/spanned/add_child_span`` call site whose
  literal name violates the ``raft.<module>.<op>`` taxonomy
  (lowercase ``[a-z0-9_]`` segments, dot-separated).
* **GL011** — one metric name registered under conflicting instrument
  kinds anywhere in the tree (``obs.timed(n)`` registers the
  histogram ``n + ".seconds"``; span names are their own plane and
  never kind-conflict with metrics).  Cross-file: the conflict is
  reported at the *later* site, naming the first.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

from tools.graftlint.core import FileContext, Finding, Rule, register

# the same taxonomy contract as raft_tpu.obs.registry.NAME_RE (kept
# literal so the lint has no import-time dependency on the tree it
# checks)
NAME_RE = re.compile(r"^raft\.[a-z0-9_]+(\.[a-z0-9_]+)*$")

CALL_RE = re.compile(
    r"""\b(?:obs|spans)\.(counter|gauge|histogram|timed|span|spanned"""
    r"""|add_child_span)\(\s*(['"])([^'"]+)\2""")
SPAN_KINDS = ("span", "spanned", "add_child_span")

# any full raft.* string literal — names held in module constants (the
# dispatcher's profiler-range phases) are not call sites; used only for
# span-coverage checks, never flagged
LITERAL_RE = re.compile(r"""['"](raft\.[a-z0-9_]+(?:\.[a-z0-9_]+)+)['"]""")

# fixture-heavy / self-referential sources the taxonomy scan skips
EXCLUDES = ("tools/check_metric_names.py", "tools/graftlint")


def check_events(rel: str, text: str,
                 seen: Dict[str, Tuple[str, str]],
                 span_seen: Dict[str, str],
                 literals: Dict[str, str],
                 ) -> List[Tuple[int, str, str]]:
    """Scan one file's instrument call sites against the taxonomy.

    Mutates the cross-file state dicts (``seen``: metric name ->
    (kind, first site); ``span_seen``/``literals``: name -> first
    site/file) and returns ``[(line, code, message)]`` with messages in
    the exact legacy ``check_metric_names`` wording.
    """
    out: List[Tuple[int, str, str]] = []
    for m in CALL_RE.finditer(text):
        kind, name = m.group(1), m.group(3)
        line = text.count("\n", 0, m.start()) + 1
        site = f"{rel}:{line}"
        if not NAME_RE.match(name):
            out.append((line, "GL010",
                        f"{name!r} violates the raft.<module>.<op> "
                        f"taxonomy"))
            continue
        if kind in SPAN_KINDS:
            span_seen.setdefault(name, site)
            continue
        reg_name = name + ".seconds" if kind == "timed" else name
        reg_kind = "histogram" if kind == "timed" else kind
        prev = seen.get(reg_name)
        if prev is None:
            seen[reg_name] = (reg_kind, site)
        elif prev[0] != reg_kind:
            out.append((line, "GL011",
                        f"{reg_name!r} registered as {reg_kind} but "
                        f"already a {prev[0]} at {prev[1]}"))
    for m in LITERAL_RE.finditer(text):
        if NAME_RE.match(m.group(1)):
            literals.setdefault(m.group(1), rel)
    return out


class _TaxonomyBase(Rule):
    paths = ("raft_tpu", "tests", "tools", "bench_suite.py", "bench.py")
    excludes = EXCLUDES
    _emit: str = ""

    def __init__(self):
        self.seen: Dict[str, Tuple[str, str]] = {}
        self.span_seen: Dict[str, str] = {}
        self.literals: Dict[str, str] = {}

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for line, code, msg in check_events(
                ctx.rel, ctx.text, self.seen, self.span_seen,
                self.literals):
            if code == self._emit:
                yield ctx.finding(code, line, msg)


@register
class MetricTaxonomy(_TaxonomyBase):
    code = "GL010"
    name = "metric-name-taxonomy"
    description = ("instrument/span call sites whose literal name "
                   "violates the raft.<module>.<op> taxonomy "
                   "(docs/observability.md)")
    _emit = "GL010"


@register
class MetricKindConflict(_TaxonomyBase):
    code = "GL011"
    name = "metric-kind-conflict"
    description = ("one metric name registered under conflicting "
                   "instrument kinds across the tree (timed implies a "
                   "<name>.seconds histogram)")
    _emit = "GL011"
