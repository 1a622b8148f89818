#!/usr/bin/env python
"""Lint the metric/span-name taxonomy (docs/observability.md).

Three modes, one contract — every metric AND span name is
``raft.<module>.<op>...`` (lowercase ``[a-z0-9_]`` segments,
dot-separated) and a metric name is bound to exactly ONE instrument
kind:

* **source mode** (default): a thin shim over the graftlint registry
  rules **GL010/GL011** (``tools/graftlint/rules/metrics.py`` owns the
  scanning since ISSUE 6) plus the REQUIRED_NAMES /
  REQUIRED_SPAN_NAMES coverage checks below — fail on
  - names violating the taxonomy regex (GL010),
  - the same name registered under conflicting kinds (GL011;
    ``obs.timed(n)`` registers the histogram ``n + ".seconds"``, so a
    ``timed`` name also conflicts with a counter/gauge of that derived
    name; span names are a separate plane and never kind-conflict
    with metrics),
  - a contracted serving instrument/span with no call site left.
* **text mode** (``--text FILE``, ``-`` = stdin): parse a Prometheus
  exposition dump (the ``obs.to_prometheus_text()`` output) and fail on
  - family names not matching ``raft_[a-z0-9_]+``,
  - duplicate ``# TYPE`` declarations for one family.
* **trace mode** (``--trace FILE``, ``-`` = stdin): parse an exported
  Chrome-trace JSON (``obs.to_chrome_trace`` / the endpoint's
  ``format=chrome``) and fail on
  - malformed JSON or a missing ``traceEvents`` array,
  - ``X`` events without ``ts``/``dur``/``pid``/``tid``,
  - event names violating the ``raft.<module>.<op>`` taxonomy.

Runs in the tier-1 path via ``tests/test_obs.py::TestMetricNameLint``
+ ``tests/test_obs_spans.py`` (all modes) and standalone::

    python tools/check_metric_names.py            # lint the source tree
    python bench_suite.py ... | python tools/check_metric_names.py --text -
    curl .../debug/requests?format=chrome | \\
        python tools/check_metric_names.py --trace -

Exit code 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                 # standalone / importlib loads
    sys.path.insert(0, REPO)

from tools.graftlint.rules import metrics as _metrics  # noqa: E402

# the taxonomy contract, re-exported from the graftlint rule module so
# the two gates can never diverge
NAME_RE = _metrics.NAME_RE
CALL_RE = _metrics.CALL_RE
SPAN_KINDS = _metrics.SPAN_KINDS
LITERAL_RE = _metrics.LITERAL_RE
PROM_NAME_RE = re.compile(r"^raft_[a-z0-9_]+$")

# trees holding instrumented call sites (bench/tools ride along so a
# future metric added there is linted too)
SCAN_ROOTS = ("raft_tpu", "tests", "tools", "bench_suite.py", "bench.py")

# serving-path instruments the plan layer CONTRACTS to expose (ISSUE 2:
# plan-cache hit/miss + the resolve_cap measurement-sync counter whose
# flatness proves a warmed plan never round-trips). Coverage check:
# a refactor that silently drops one of these names fails the lint —
# dashboards and the zero-sync test depend on them existing.
REQUIRED_NAMES = (
    "raft.plan.cache.hits",
    "raft.plan.cache.misses",
    "raft.plan.build.total",
    "raft.ivf_scan.resolve_cap.syncs",
    "raft.ivf_scan.resolve_cap.cache_hits",
    "raft.ann.batched_search.sub_batches",
    # fused scan+select routing (ISSUE 7): per-family fused-route
    # decisions + query volume, and the coarse-selection cliff counter
    # (n_probes > 256 silently drops to the lax.top_k variadic sort)
    "raft.ivf_scan.fused.total",
    "raft.ivf_scan.fused.queries",
    "raft.ivf_scan.coarse.fallback",
    # flat plans whose list scan completes a partial last bins window
    # in VMEM instead of padding the lists in HBM
    "raft.ivf_scan.ragged_tail.total",
    # share of the stored PQ code rows the code scan decodes (each list
    # up to its last row, in whole chunks), set per PQ plan build
    "raft.ivf_scan.pq.decoded_share",
    # sharded/streaming build instruments (ISSUE 4): per-family sharded
    # build counters and the streaming ingestion counters — the
    # sharded_build_s bench rows and the build dashboards key on these
    "raft.build.sharded.total",
    "raft.build.sharded.rows",
    "raft.build.streaming.chunks",
    "raft.build.streaming.rows",
    # serving-runtime instruments (ISSUE 5): admission/robustness
    # counters the overload tests and /healthz verdict key on, plus the
    # plan-cache eviction counter of the LRU bound the serve ladder
    # made necessary
    "raft.serve.requests.total",
    "raft.serve.shed.total",
    "raft.serve.deadline.total",
    "raft.serve.degrade.steps",
    "raft.serve.queue.depth",
    "raft.serve.batch.rows",
    "raft.plan.cache.evictions",
    # distributed serving tier (ISSUE 8): per-batch dispatch volume,
    # the quantized cross-shard merge wire accounting the
    # merge_bytes_ratio acceptance figure reads, the mesh-size/ratio
    # gauges /healthz folds in, and the per-rank suspect flags the
    # dist health section names shards from
    "raft.serve.dist.batches",
    "raft.serve.dist.queries",
    "raft.serve.dist.merge.bytes_pre",
    "raft.serve.dist.merge.bytes_post",
    "raft.serve.dist.shard.rows",
    "raft.serve.dist.shards",
    "raft.serve.dist.merge.ratio",
    "raft.comms.health.suspect_rank",
    # live mutable indexes (ISSUE 9): mutation volume, the delta-fill /
    # tombstone gauges the /healthz mutate section reads (incl. the
    # stalled-compactor flag that degrades the verdict), and the
    # compaction lifecycle counters the bench keys on
    "raft.mutate.upserts.total",
    "raft.mutate.deletes.total",
    "raft.mutate.delta.fill_frac",
    "raft.mutate.delta.stalled",
    "raft.mutate.tombstone.frac",
    "raft.mutate.epoch",
    "raft.mutate.compact.total",
    "raft.mutate.compact.inflight",
    "raft.mutate.delta.overflow.total",
    # failure handling (ISSUE 10): the retry budget's lifecycle, the
    # watchdog's hang→typed-error conversions, the dispatcher crash
    # guard, the partial-mesh failover engage/recover cycle /healthz
    # folds in, the mutation WAL durability counters the recovery
    # parity test keys on, and the compactor crash-loop guard
    "raft.serve.retry.total",
    "raft.serve.retry.exhausted.total",
    "raft.serve.dispatch.timeouts.total",
    "raft.serve.dispatcher.errors",
    "raft.serve.failover.total",
    "raft.serve.failover.partial.total",
    "raft.serve.failover.engaged",
    "raft.serve.failover.recovered.total",
    "raft.mutate.wal.appends.total",
    "raft.mutate.wal.replayed.total",
    "raft.mutate.wal.truncations.total",
    "raft.mutate.wal.torn.total",
    "raft.mutate.compactor.errors",
    "raft.mutate.compactor.failing",
    # quality observability (ISSUE 11): the live shadow-exact recall
    # window gauges, the online estimator-calibration gap, the
    # epoch-drift trigger ROADMAP item 5's fold→rebuild policy
    # consumes, and the declarative SLO burn/breach gauges /healthz
    # and /debug/slo read
    "raft.obs.quality.recall",
    "raft.obs.quality.samples.total",
    "raft.obs.quality.sampled.total",
    "raft.obs.quality.calibration.gap",
    "raft.obs.quality.drift",
    "raft.obs.quality.drift.total",
    "raft.slo.burn_rate",
    "raft.slo.breach",
    # replica fleet serving (ISSUE 13): the routing decision volume
    # per replica, the fleet-level retry/backpressure counters, the
    # replica lifecycle gauges /healthz's fleet section reads, the
    # bootstrap counter (timed as raft.fleet.bootstrap.seconds), and
    # the replication-lag gauges the freshness story keys on
    "raft.fleet.route.total",
    "raft.fleet.retry.total",
    "raft.fleet.unroutable.total",
    "raft.fleet.replicas.total",
    "raft.fleet.replicas.serving",
    "raft.fleet.suspects",
    "raft.fleet.replica.state",
    "raft.fleet.replica.transitions.total",
    "raft.fleet.bootstrap.total",
    "raft.fleet.replication.applied.total",
    "raft.fleet.replication.lag_records",
    "raft.fleet.replication.lag_seconds",
    "raft.fleet.rolling.total",
    # multi-process fleet (ISSUE 20): the RPC transport's per-route
    # traffic/error counters, the WAL/checkpoint wire volume (each
    # daemon's OWN registry — federate to see the fleet), and the
    # spawner-side process-lifecycle counters the failover drill
    # asserts on
    "raft.fleet.rpc.requests.total",
    "raft.fleet.rpc.errors.total",
    "raft.fleet.rpc.wal.records.total",
    "raft.fleet.rpc.wal.bytes.total",
    "raft.fleet.rpc.checkpoint.bytes.total",
    "raft.fleet.proc.spawned.total",
    "raft.fleet.proc.alive",
    "raft.fleet.proc.killed.total",
    "raft.fleet.proc.promotions.total",
    # resource observability (ISSUE 14): the sampled device/host split
    # counters, the duty-cycle gauge every "is the chip busy" consumer
    # reads, the HBM table + the low-headroom guardrail /healthz
    # degrades on, and the compile-time ledger
    "raft.obs.profile.samples.total",
    "raft.obs.profile.device.seconds",
    "raft.obs.profile.host.seconds",
    "raft.obs.profile.duty_cycle",
    "raft.obs.profile.hbm.bytes_in_use",
    "raft.obs.profile.hbm.peak_bytes",
    "raft.obs.profile.hbm.limit_bytes",
    "raft.obs.profile.hbm.headroom_frac",
    "raft.obs.profile.hbm.low_headroom",
    "raft.obs.profile.compile.seconds",
    # fleet observability plane (ISSUE 16): the metric federator's own
    # plane — per-instance scrape counts/errors/durations plus the
    # membership and staleness gauges a fleet dashboard alarms on
    "raft.obs.fed.scrapes.total",
    "raft.obs.fed.scrape.errors",
    "raft.obs.fed.scrape.seconds",
    "raft.obs.fed.instances",
    "raft.obs.fed.stale",
    # post-mortem observability (ISSUE 18): the metrics-history ring
    # (frames sampled, edge-triggered mean-shift anomalies) and the
    # crash-durable black box (flush/bytes/segment accounting plus the
    # torn-segment recovery counter the kill-9 test pins)
    "raft.obs.history.frames.total",
    "raft.obs.history.anomaly",
    "raft.obs.history.anomaly.total",
    "raft.obs.blackbox.flushes.total",
    "raft.obs.blackbox.bytes.total",
    "raft.obs.blackbox.segments.total",
    "raft.obs.blackbox.torn.total",
    # tiered serving (ISSUE 19): the hot/cold split and transfer
    # economics of the HBM-budgeted tier — probe routing, fetch
    # bytes/seconds and the overlap credit doctor's transfer-bound
    # verdict reads, plus the placement-policy counters and the
    # budget/occupancy gauges /healthz reports
    "raft.tiered.search.total",
    "raft.tiered.probes.hot",
    "raft.tiered.probes.cold",
    "raft.tiered.fetch.bytes",
    "raft.tiered.fetch.seconds",
    "raft.tiered.overlap.seconds",
    "raft.tiered.refresh.total",
    "raft.tiered.promotions.total",
    "raft.tiered.demotions.total",
    "raft.tiered.hit_rate",
    "raft.tiered.overlap.frac",
    "raft.tiered.budget.bytes",
    "raft.tiered.hot.lists",
    "raft.tiered.hot.bytes",
    # per-list probe mass (ISSUE 19 satellite): the hotness signal the
    # tiered placement policy scores from
    "raft.ivf_scan.probes.batches",
    "raft.ivf_scan.probes.mass",
    # host-runtime pauses: garbage collections per generation, counted
    # by the gc.callbacks hook while tracing is enabled
    "raft.runtime.gc.collections",
    "raft.runtime.gc.seconds",
)

# serving-path SPANS the tracing layer contracts to emit (ISSUE 3):
# the request root, the sub-batch split, and the rank-tagged shard
# spans. Checked against every full raft.* string literal in a
# full-tree scan (the dispatcher phases that are profiler ranges only
# live in module constants, not span call sites).
REQUIRED_SPAN_NAMES = (
    "raft.plan.search",
    "raft.plan.search_batched",
    "raft.ann.sub_batch",
    "raft.parallel.ivf.shard",
    "raft.ivf_flat.search",
    # build-scaling roots (ISSUE 4): the sharded list-layout builds and
    # the streaming ingestion path each open one
    "raft.build.sharded",
    "raft.build.streaming",
    # serving-runtime spans (ISSUE 5): the per-request root, its
    # queue-wait/execution children, and the batch root tagged with
    # occupancy
    "raft.serve.request",
    "raft.serve.queue_wait",
    "raft.serve.execute",
    "raft.serve.batch",
    # distributed serving tier (ISSUE 8): the per-batch mesh dispatch
    # root under raft.serve.batch (the rank-tagged
    # raft.parallel.ivf.shard children ride under it)
    "raft.serve.dist.dispatch",
    # live mutable indexes (ISSUE 9): the compaction fold/prewarm/swap
    # lifecycle span (epoch + row/tombstone counts ride as attrs)
    "raft.mutate.compact",
    # failure handling (ISSUE 10): every retry is a span under the
    # batch root (attempt, backoff, error class as attrs) so a traced
    # request shows its failure story, not only its latency
    "raft.serve.retry",
    # quality observability (ISSUE 11): each shadow-exact replay batch
    # opens one span (family, query count) — off the serving path, so
    # it roots its own trace
    "raft.obs.quality.shadow",
    # replica fleet serving (ISSUE 13): every routing decision opens
    # one span (replica, attempt) under the caller's trace — a traced
    # request names which replica answered it and how many re-routes
    # it took
    "raft.fleet.route",
    # resource observability (ISSUE 14): the profiler's sampled-sync
    # child span — a MEASURED device/host split under the request
    "raft.obs.profile.sync",
    # fleet observability plane (ISSUE 16): each federator sweep and
    # each cross-process trace stitch opens one span — the
    # aggregator's own overhead is itself traced
    "raft.obs.fed.scrape",
    "raft.obs.fed.stitch",
    # tiered serving (ISSUE 19): the tiered search root — hot/cold
    # probe split and overlap ride as attrs on every traced request
    "raft.tiered.search",
    # multi-process fleet (ISSUE 20): the daemon-side RPC span,
    # parented by the caller's traceparent header — one routed request
    # stays ONE trace across process boundaries
    "raft.fleet.rpc",
    # the dispatcher thread's phases: every instant of its loop lies
    # under exactly one of them (collect, assemble and scatter are
    # profiler ranges only; the plan's phases and fetch are spans
    # under the raft.serve.batch root), and the GC pause range
    "raft.serve.collect",
    "raft.serve.assemble",
    "raft.plan.enqueue",
    "raft.plan.host_epilogue",
    "raft.plan.device_wait",
    "raft.serve.fetch",
    "raft.serve.scatter",
    "raft.runtime.gc",
)


def iter_source_files() -> List[str]:
    out = []
    for root in SCAN_ROOTS:
        path = os.path.join(REPO, root)
        if os.path.isfile(path):
            out.append(path)
            continue
        for dirpath, _dirnames, filenames in os.walk(path):
            for fn in filenames:
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(out)


def lint_source(files: List[str] = None) -> List[str]:
    """Scan call sites → list of violation strings (the GL010/GL011
    registry checks, legacy message format). The REQUIRED_NAMES
    coverage check only applies to full-tree scans (``files=None``) —
    an explicit file list (unit tests, partial lints) cannot be
    expected to contain the serving instruments."""
    full_scan = files is None
    files = files if files is not None else iter_source_files()
    self_path = os.path.abspath(__file__)
    graft_dir = os.path.join(os.path.dirname(self_path), "graftlint")
    violations: List[str] = []
    seen: dict = {}
    span_seen: dict = {}
    literals: dict = {}
    for path in files:
        apath = os.path.abspath(path)
        if apath == self_path or apath.startswith(graft_dir + os.sep):
            continue  # docstring examples / the rule sources themselves
        rel = os.path.relpath(path, REPO)
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        for line, _code, msg in _metrics.check_events(
                rel, text, seen, span_seen, literals):
            violations.append(f"{rel}:{line}: {msg}")
    if full_scan:
        for name in REQUIRED_NAMES:
            if name not in seen:
                violations.append(
                    f"required serving metric {name!r} has no "
                    f"instrument call site (REQUIRED_NAMES coverage)")
        for name in REQUIRED_SPAN_NAMES:
            if name not in span_seen and name not in literals:
                violations.append(
                    f"required serving span {name!r} has no span call "
                    f"site or literal (REQUIRED_SPAN_NAMES coverage)")
    return violations


def lint_prometheus_text(text: str) -> List[str]:
    """Validate a Prometheus exposition dump."""
    violations: List[str] = []
    typed: dict = {}
    for ln, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4:
                violations.append(f"line {ln}: malformed TYPE line")
                continue
            name, kind = parts[2], parts[3]
            if not PROM_NAME_RE.match(name):
                violations.append(
                    f"line {ln}: family {name!r} not raft_-prefixed")
            if name in typed:
                violations.append(
                    f"line {ln}: duplicate TYPE declaration for {name!r}")
            typed[name] = kind
            continue
        if line.startswith("#"):
            continue
        # sample line: name{labels} value — name must be raft_ prefixed
        sample = re.match(r"^([A-Za-z_:][A-Za-z0-9_:]*)", line)
        if sample and not sample.group(1).startswith("raft_"):
            violations.append(
                f"line {ln}: sample {sample.group(1)!r} not raft_-prefixed")
    return violations


def lint_chrome_trace(text: str) -> List[str]:
    """Validate an exported Chrome-trace JSON: structure + the span
    taxonomy on every event name (metadata ``ph="M"`` events are
    structural and exempt)."""
    import json
    violations: List[str] = []
    try:
        obj = json.loads(text)
    except ValueError as e:
        return [f"trace: not valid JSON ({e})"]
    events = obj.get("traceEvents") if isinstance(obj, dict) else obj
    if not isinstance(events, list):
        return ["trace: no traceEvents array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            violations.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph == "M":
            continue
        name = ev.get("name", "")
        if not NAME_RE.match(name):
            violations.append(
                f"event {i}: name {name!r} violates the "
                f"raft.<module>.<op> taxonomy")
        if ph != "X":
            violations.append(f"event {i}: ph {ph!r} (expected 'X')")
            continue
        for field in ("ts", "dur", "pid", "tid"):
            if not isinstance(ev.get(field), (int, float)):
                violations.append(
                    f"event {i} ({name}): missing/non-numeric "
                    f"{field!r}")
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--text", metavar="FILE", default=None,
                    help="lint a Prometheus exposition dump instead of "
                         "the source tree ('-' = stdin)")
    ap.add_argument("--trace", metavar="FILE", default=None,
                    help="lint an exported Chrome-trace JSON "
                         "(obs.to_chrome_trace output; '-' = stdin)")
    args = ap.parse_args(argv)
    if args.text is not None:
        text = (sys.stdin.read() if args.text == "-"
                else open(args.text, encoding="utf-8").read())
        violations = lint_prometheus_text(text)
    elif args.trace is not None:
        text = (sys.stdin.read() if args.trace == "-"
                else open(args.trace, encoding="utf-8").read())
        violations = lint_chrome_trace(text)
    else:
        violations = lint_source()
    for v in violations:
        print(v)
    if violations:
        print(f"check_metric_names: {len(violations)} violation(s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
