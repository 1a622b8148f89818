"""Dependency-free, thread-safe metrics registry.

The quantitative half of the observability story (the qualitative half
is ``core/trace.py`` xprof ranges + ``core/logger.py``): counters,
gauges and fixed-boundary histograms, grouped into labeled families
keyed by frozen label tuples — the Prometheus data model, implemented
on the stdlib only so ``raft_tpu`` gains no dependency.

Design constraints (ISSUE 1 tentpole):

* **taxonomy** — every metric name is ``raft.<module>.<op>[...]``
  (lowercase, dot-separated), the SAME naming scheme ``obs.timed``
  uses for its xprof trace ranges, so a wall-time histogram and its
  profiler annotation are findable under one name.
  ``tools/check_metric_names.py`` lints the taxonomy.
* **hot-path safe** — instrument lookups are two dict hits under one
  registry lock (host-side microseconds; every instrumented site is a
  per-dispatch host path, never per-element device work).
* **no-op toggle** — ``RAFT_TPU_METRICS=0`` (or ``set_enabled(False)``)
  makes every instrument a shared null object: nothing is registered,
  ``snapshot()`` stays empty, overhead is one attribute check.
* **bounded cardinality** — a family refuses to materialize more than
  ``max_series`` children (:class:`CardinalityError`): an unbounded
  label (query id, pointer) must fail loudly, not leak memory forever.
"""

from __future__ import annotations

import bisect
import math
import os
import re
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

__all__ = [
    "CardinalityError",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "DEFAULT_BUCKETS",
    "SIZE_BUCKETS",
    "NAME_RE",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "snapshot_diff",
    "to_prometheus_text",
    "reset",
    "set_enabled",
    "enabled",
]

# the taxonomy contract: raft.<module>.<op>... — lowercase segments of
# [a-z0-9_], dot-separated, first segment literally "raft"
NAME_RE = re.compile(r"^raft\.[a-z0-9_]+(\.[a-z0-9_]+)*$")

# latency-shaped default boundaries (seconds): sub-ms kernel dispatches
# through minutes-long cold compiles. Upper
# bound of each bucket, +Inf implicit (Prometheus ``le`` semantics:
# a value exactly on a boundary counts in that bucket).
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

# count-shaped boundaries (batch sizes, probe counts, iterations):
# powers of two up to 1M
SIZE_BUCKETS: Tuple[float, ...] = tuple(
    float(1 << i) for i in range(0, 21, 2))


class CardinalityError(RuntimeError):
    """A labeled family exceeded its configured series cap."""


def _env_enabled() -> bool:
    return os.environ.get("RAFT_TPU_METRICS", "1").lower() not in (
        "0", "false", "off", "no")


def _labels_key(labels: Dict[str, object]) -> Tuple[Tuple[str, str], ...]:
    """Frozen, order-independent label identity."""
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _series_name(name: str, key: Tuple[Tuple[str, str], ...]) -> str:
    if not key:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in key) + "}"


class Counter:
    """Monotone counter. ``inc`` only accepts non-negative amounts."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("Counter.inc: negative amount")
        with self._lock:
            self.value += amount


class Gauge:
    """Settable point-in-time value."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self.value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class Histogram:
    """Fixed-boundary histogram (Prometheus bucket semantics: boundary
    is the inclusive upper edge ``le``; one implicit +Inf bucket)."""

    __slots__ = ("_lock", "bounds", "bucket_counts", "sum", "count")

    def __init__(self, lock: threading.RLock,
                 bounds: Sequence[float] = DEFAULT_BUCKETS):
        # strip a trailing +Inf if the caller spelled it out; it is
        # always implicit
        bounds = tuple(float(b) for b in bounds if not math.isinf(b))
        if list(bounds) != sorted(set(bounds)):
            raise ValueError("Histogram: bucket bounds must be strictly "
                             "increasing")
        self._lock = lock
        self.bounds = bounds
        self.bucket_counts = [0] * (len(bounds) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        # bisect_left: value == bounds[i] lands in bucket i (le=bounds[i])
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self.bucket_counts[i] += 1
            self.sum += value
            self.count += 1


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class _Family:
    """One named metric family: a kind + its children keyed by frozen
    label tuples."""

    __slots__ = ("name", "kind", "help", "bounds", "children")

    def __init__(self, name: str, kind: str, help: str = "",
                 bounds: Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.kind = kind
        self.help = help
        self.bounds = tuple(bounds)
        self.children: Dict[Tuple[Tuple[str, str], ...], object] = {}


class _Null:
    """Shared no-op instrument for the disabled registry: accepts every
    instrument method and does nothing."""

    __slots__ = ()

    def inc(self, amount: float = 1.0) -> None: ...
    def dec(self, amount: float = 1.0) -> None: ...
    def set(self, value: float) -> None: ...
    def observe(self, value: float) -> None: ...


_NULL = _Null()


class MetricsRegistry:
    """Thread-safe registry of labeled metric families.

    One coarse ``RLock`` guards registration AND value mutation: every
    instrumented site is a host-side per-dispatch path where
    microseconds are invisible next to a device dispatch, and a single
    lock keeps ``snapshot()`` internally consistent.
    """

    def __init__(self, enabled: Optional[bool] = None,
                 max_series: Optional[int] = None):
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = {}
        self._enabled = _env_enabled() if enabled is None else enabled
        if max_series is None:
            max_series = int(os.environ.get(
                "RAFT_TPU_METRICS_MAX_SERIES", "512"))
        self.max_series = max_series

    # -- enable toggle -----------------------------------------------------
    def set_enabled(self, on: bool = True) -> None:
        self._enabled = bool(on)

    def enabled(self) -> bool:
        return self._enabled

    # -- registration ------------------------------------------------------
    def _get(self, name: str, kind: str, help: str,
             bounds: Sequence[float], labels: Dict[str, object]):
        if not self._enabled:
            return _NULL
        if not NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} violates the raft.<module>.<op> "
                f"taxonomy (want {NAME_RE.pattern})")
        key = _labels_key(labels)
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name, kind, help,
                                                     bounds)
            elif fam.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}, "
                    f"cannot re-register as {kind}")
            child = fam.children.get(key)
            if child is None:
                if len(fam.children) >= self.max_series:
                    raise CardinalityError(
                        f"metric family {name!r} exceeded max_series="
                        f"{self.max_series}: an unbounded label value "
                        f"(id, pointer, timestamp) is leaking series")
                if kind == "histogram":
                    child = Histogram(self._lock, fam.bounds)
                else:
                    child = _KINDS[kind](self._lock)
                fam.children[key] = child
            return child

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        return self._get(name, "counter", help, (), labels)

    def gauge(self, name: str, help: str = "", **labels) -> Gauge:
        return self._get(name, "gauge", help, (), labels)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  **labels) -> Histogram:
        return self._get(name, "histogram", help, buckets, labels)

    # -- export ------------------------------------------------------------
    def snapshot(self) -> dict:
        """Point-in-time JSON-ready dict:
        ``{"counters": {series: value}, "gauges": {...},
        "histograms": {series: {"count", "sum", "buckets"}}}``.
        Series keys are ``name`` or ``name{k=v,...}`` with sorted
        labels."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        with self._lock:
            for fam in self._families.values():
                for key, child in fam.children.items():
                    series = _series_name(fam.name, key)
                    if fam.kind == "counter":
                        out["counters"][series] = child.value
                    elif fam.kind == "gauge":
                        out["gauges"][series] = child.value
                    else:
                        buckets = {}
                        for b, c in zip(child.bounds, child.bucket_counts):
                            buckets[repr(b)] = c
                        buckets["+Inf"] = child.bucket_counts[-1]
                        out["histograms"][series] = {
                            "count": child.count,
                            "sum": child.sum,
                            "buckets": buckets,
                        }
        return out

    def to_prometheus_text(self) -> str:
        """Render the Prometheus text exposition format. Dots in the
        taxonomy become underscores (Prometheus name charset); counters
        gain the ``_total`` suffix, histograms emit cumulative
        ``_bucket{le=...}`` plus ``_sum``/``_count``."""
        lines = []
        with self._lock:
            for name in sorted(self._families):
                fam = self._families[name]
                pname = _prom_name(name)
                if fam.kind == "counter":
                    pname += "_total"
                if fam.help:
                    lines.append(f"# HELP {pname} {fam.help}")
                lines.append(f"# TYPE {pname} {fam.kind}")
                for key in sorted(fam.children):
                    child = fam.children[key]
                    lbl = _prom_labels(key)
                    if fam.kind in ("counter", "gauge"):
                        lines.append(f"{pname}{lbl} {_fmt(child.value)}")
                        continue
                    cum = 0
                    for b, c in zip(child.bounds, child.bucket_counts):
                        cum += c
                        lines.append(
                            f"{pname}_bucket{_prom_labels(key, le=_fmt(b))}"
                            f" {cum}")
                    cum += child.bucket_counts[-1]
                    lines.append(
                        f"{pname}_bucket{_prom_labels(key, le='+Inf')}"
                        f" {cum}")
                    lines.append(f"{pname}_sum{lbl} {_fmt(child.sum)}")
                    lines.append(f"{pname}_count{lbl} {child.count}")
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self) -> None:
        """Drop every registered family (tests, bench isolation)."""
        with self._lock:
            self._families.clear()


def _prom_name(name: str) -> str:
    return name.replace(".", "_")


def _prom_labels(key: Tuple[Tuple[str, str], ...], **extra) -> str:
    items = list(key) + sorted(extra.items())
    if not items:
        return ""
    # exposition-format escapes, in spec order (backslash FIRST so the
    # escapes it introduces are not re-escaped): \\ , \" , \n
    body = ",".join(
        '{}="{}"'.format(k, str(v).replace("\\", r"\\")
                         .replace('"', r'\"').replace("\n", r"\n"))
        for k, v in items)
    return "{" + body + "}"


def _fmt(v: float) -> str:
    """Prometheus sample-value rendering. Must be a true inverse of
    ``float()`` over its image (the federation parser round-trips
    exported text byte-stably): ±Inf and NaN use the exposition
    spellings, integral floats drop the ``.0``, everything else uses
    ``repr`` (shortest float round trip)."""
    v = float(v)
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if math.isnan(v):
        return "NaN"
    if v.is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


# the process-wide default registry every instrumented raft_tpu module
# writes to; tests can build private MetricsRegistry instances
REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "", **labels) -> Counter:
    return REGISTRY.counter(name, help, **labels)


def gauge(name: str, help: str = "", **labels) -> Gauge:
    return REGISTRY.gauge(name, help, **labels)


def histogram(name: str, help: str = "",
              buckets: Sequence[float] = DEFAULT_BUCKETS,
              **labels) -> Histogram:
    return REGISTRY.histogram(name, help, buckets=buckets, **labels)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def to_prometheus_text() -> str:
    return REGISTRY.to_prometheus_text()


def reset() -> None:
    REGISTRY.reset()


def set_enabled(on: bool = True) -> None:
    REGISTRY.set_enabled(on)


def enabled() -> bool:
    return REGISTRY.enabled()


def snapshot_diff(before: dict, after: dict) -> dict:
    """Delta between two :func:`snapshot` dicts — what a bounded piece
    of work (one bench case, one request) actually did. Counters and
    histogram counts subtract; gauges report their ``after`` value when
    it changed. Unchanged series are dropped, so the diff is compact
    enough to embed per bench record."""
    out = {"counters": {}, "gauges": {}, "histograms": {}}
    b_c = before.get("counters", {})
    for k, v in after.get("counters", {}).items():
        d = v - b_c.get(k, 0.0)
        if d:
            out["counters"][k] = d
    b_g = before.get("gauges", {})
    for k, v in after.get("gauges", {}).items():
        if k not in b_g or b_g[k] != v:
            out["gauges"][k] = v
    b_h = before.get("histograms", {})
    for k, h in after.get("histograms", {}).items():
        hb = b_h.get(k, {"count": 0, "sum": 0.0, "buckets": {}})
        dc = h["count"] - hb["count"]
        if not dc:
            continue
        bkts = {edge: c - hb["buckets"].get(edge, 0)
                for edge, c in h["buckets"].items()
                if c - hb["buckets"].get(edge, 0)}
        out["histograms"][k] = {"count": dc,
                                "sum": h["sum"] - hb["sum"],
                                "buckets": bkts}
    return out
