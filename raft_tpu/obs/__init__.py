"""raft_tpu.obs — metrics, request tracing + runtime telemetry.

The observability layer the reference never had (its story is NVTX
ranges + spdlog — our ``core/trace.py`` / ``core/logger.py``), in
three planes sharing ONE ``raft.<module>.<op>`` naming taxonomy:

* **metrics** (:mod:`raft_tpu.obs.registry`) — dependency-free,
  thread-safe counters/gauges/fixed-boundary histograms wired into
  every hot path; ``RAFT_TPU_METRICS=0`` no-ops it.
* **request-scoped spans** (:mod:`raft_tpu.obs.spans`) — per-request
  traces (trace_id/parent links, wall durations, attributes) through
  the serving paths, landing in the always-on **flight recorder**
  (:mod:`raft_tpu.obs.recorder`): the last N request stories, a
  slow-query log, Chrome-trace export. ``RAFT_TPU_TRACE=0`` no-ops it.
* **endpoint** (:mod:`raft_tpu.obs.endpoint`) — ``obs.serve()``, a
  stdlib HTTP server exposing ``/metrics`` (Prometheus text),
  ``/healthz`` (comms health gauges) and ``/debug/requests`` (the
  recorder).

Further planes ride the same taxonomy and load lazily:
:mod:`raft_tpu.obs.quality` (shadow-exact recall, ISSUE 11),
:mod:`raft_tpu.obs.profiler` (sampled device-time attribution, duty
cycle, HBM accounting — ISSUE 14; ``RAFT_TPU_PROFILE_SAMPLE``,
``/debug/profile``), :mod:`raft_tpu.obs.federation` (cross-process
metric federation + fleet rollup — ISSUE 16; ``obs.serve(
federator=...)`` turns the endpoint into the fleet aggregator), and
the post-mortem pair :mod:`raft_tpu.obs.history` +
:mod:`raft_tpu.obs.blackbox` (metrics history ring with mean-shift
anomaly detection at ``/debug/history``, plus the crash-durable
black-box flight data recorder — ISSUE 18;
``RAFT_TPU_BLACKBOX=<dir>`` ambient-attaches both, and
``tools/doctor.py`` reads the dumps).

Quick use::

    from raft_tpu import obs
    obs.counter("raft.myapp.requests", route="search").inc()
    with obs.timed("raft.myapp.handle"):
        ...
    with obs.span("raft.myapp.request", user="abc") as sp:
        ...
    obs.RECORDER.requests(5)          # last 5 request traces
    srv = obs.serve(port=9100)        # scrape/debug endpoint
    print(obs.to_prometheus_text())   # scrape endpoint body
    state = obs.snapshot()            # JSON-ready dict

See docs/observability.md for the taxonomy, the exporters, the span/
recorder knobs and how ``obs.timed`` relates to profiler trace ranges.
"""

from raft_tpu.obs.registry import (
    REGISTRY,
    DEFAULT_BUCKETS,
    SIZE_BUCKETS,
    NAME_RE,
    CardinalityError,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    snapshot_diff,
    to_prometheus_text,
    reset,
    set_enabled,
    enabled,
)
from raft_tpu.obs.runtime import snapshot
from raft_tpu.obs.timing import timed
from raft_tpu.obs.spans import (
    Span,
    span,
    current_span,
    current_trace_id,
    current_traceparent,
    parse_traceparent,
    set_trace_enabled,
    trace_enabled,
    set_trace_sample_rate,
    trace_sample_rate,
)
from raft_tpu.obs.recorder import FlightRecorder, RECORDER, to_chrome_trace
from raft_tpu.obs.endpoint import DebugServer, serve

__all__ = [
    "REGISTRY",
    "DEFAULT_BUCKETS",
    "SIZE_BUCKETS",
    "NAME_RE",
    "CardinalityError",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "snapshot_diff",
    "to_prometheus_text",
    "reset",
    "set_enabled",
    "enabled",
    "timed",
    # spans / recorder / endpoint
    "Span",
    "span",
    "current_span",
    "current_trace_id",
    "current_traceparent",
    "parse_traceparent",
    "set_trace_enabled",
    "trace_enabled",
    "set_trace_sample_rate",
    "trace_sample_rate",
    "FlightRecorder",
    "RECORDER",
    "to_chrome_trace",
    "DebugServer",
    "serve",
]

# -- black-box ambient attach (ISSUE 18) ----------------------------------
# RAFT_TPU_BLACKBOX=<dir> attaches the metrics-history sampler and the
# crash-durable black box at import, exactly like the profiler's
# RAFT_TPU_PROFILE_SAMPLE knob. Unset/0/off leaves BOTH modules
# unimported — the off state is one env read here and `_STATE is None`
# in each module, nothing else (the < 2% overhead gate is structural).
# The attach lives HERE rather than at blackbox-module import so
# tools/doctor.py can import the modules to READ a dump without ever
# starting a recorder into it.
import os as _os

_bb_dir = _os.environ.get("RAFT_TPU_BLACKBOX", "")
if _bb_dir and _bb_dir.lower() not in ("0", "false", "off", "no"):
    from raft_tpu.obs import blackbox as _blackbox
    from raft_tpu.obs import history as _history

    _history.enable_history()
    _blackbox.enable_blackbox(_bb_dir)
del _os, _bb_dir
