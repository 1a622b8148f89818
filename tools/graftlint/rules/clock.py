"""GL005 — wall-clock ``time.time()`` where a monotonic clock belongs.

``time.time()`` steps under NTP slew/adjustment and DST/admin changes;
any *duration* or *expiry* computed from it can jump backwards or
forwards — an NTP step can silently stretch or shrink an expiry
window on the serving path.  The tree's convention is:

* ``time.perf_counter()`` — durations measured within one thread
  (latency histograms, span timing);
* ``time.monotonic()`` — cross-thread timestamps compared against
  each other (queue delays, cooldowns, expiry);
* ``time.time()`` — ONLY for wall-clock *export* (trace timestamps,
  cross-process file ages), always with a suppression stating so.

Every ``time.time()`` call is flagged; genuinely-wall-clock sites
carry ``# graftlint: disable=GL005`` plus a justification.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.graftlint.core import (FileContext, Finding, Rule,
                                  dotted_name, register)


@register
class MonotonicClock(Rule):
    code = "GL005"
    name = "monotonic-clock"
    description = ("time.time() in the library/tooling tree — "
                   "durations and expiry arithmetic must use "
                   "perf_counter/monotonic (NTP steps skew wall "
                   "clock); suppress with a justification where wall "
                   "time is the point")
    paths = ("raft_tpu", "tools", "bench_suite.py", "bench.py")
    excludes = ("tools/graftlint",)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        tree = ctx.tree
        if tree is None:
            return
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and dotted_name(node.func) == "time.time"
                    and not node.args and not node.keywords):
                yield ctx.finding(
                    self.code, node,
                    "time.time() — wall clock steps under NTP; use "
                    "time.monotonic() for expiry/cross-thread "
                    "deadlines or time.perf_counter() for durations "
                    "(suppress with a justification if wall-clock "
                    "export is intended)")
