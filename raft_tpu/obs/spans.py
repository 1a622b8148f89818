"""Request-scoped spans — the per-request story the aggregates lack.

``raft_tpu.obs`` metrics answer "how often / how slow on average";
``core.trace`` ranges answer "where inside one profiled session".
Neither ties a p99 histogram bucket back to *which* query, plan, cap
decision, or shard caused it after the fact. Spans do: every serving
entry point opens a **root span**, nested scopes (sub-batches, cap
resolution, shard dispatch) attach as **children** sharing one
``trace_id``, and the completed trace — names, parent links, wall
durations, attributes — lands in the always-on flight recorder
(:mod:`raft_tpu.obs.recorder`), exportable as Chrome-trace/Perfetto
JSON and served by the debug endpoint (:mod:`raft_tpu.obs.endpoint`).

Span names use the SAME ``raft.<module>.<op>`` taxonomy as metrics and
trace ranges (linted by ``tools/check_metric_names.py``), and every
span also opens a ``core.trace.range`` of its name, so one name finds
the histogram, the xprof range, and the recorded request.

Quick use::

    from raft_tpu.obs import spans
    with spans.span("raft.myapp.handle", route="search") as sp:
        with spans.span("raft.myapp.stage"):
            ...
        sp.set_attr("cache", "hit")

Semantics and caveats:

* **wall clock** — a span measures host time in its scope: under JAX
  async dispatch that is enqueue time unless the scope synchronizes
  (the same caveat as ``obs.timed``). ``sp.sync(value)`` optionally
  blocks on a device value and records the device-inclusive duration
  in ``attrs["device_ms"]``.
* **device stages** — an AOT plan executes coarse/scan/merge/rescore/
  postprocess as ONE fused program, so no host span can time a stage.
  The program's stages carry ``jax.named_scope("raft.plan.<stage>")``
  instead: the device ops' metadata in an xprof/Perfetto trace names
  the stage each op belongs to (docs/observability.md walkthrough).
* **GC pauses** — while tracing is enabled, :mod:`raft_tpu.obs.runtime`
  keeps a ``gc.callbacks`` hook that puts each collection on the
  profiler clock as the range ``raft.runtime.gc`` and counts it under
  ``raft.runtime.gc.*``; :func:`set_trace_enabled` installs and
  removes it.
* **toggle** — ``RAFT_TPU_TRACE=0`` (mirroring ``RAFT_TPU_METRICS``)
  no-ops the whole layer: ``span()`` returns one shared null object
  (nothing is allocated or recorded), runtime toggle via
  :func:`set_trace_enabled`.
* **sampling** — ``RAFT_TPU_TRACE_SAMPLE`` (0.0–1.0, default 1.0)
  admits only that fraction of REQUESTS into the recorder, keeping the
  flight recorder affordable at high QPS: the decision happens once,
  at the would-be root span; sampled-out requests reuse the shared
  null span (a thread-local veto depth makes their nested ``span()``
  calls share it too — a child can never start an orphan trace).
  Runtime setter :func:`set_trace_sample_rate` (seedable for
  deterministic tests).
* **threads** — the active trace is thread-local; a trace never leaks
  across requests served on different threads.
* **cross-process propagation** (ISSUE 16) — a span can be parented
  across a thread or process boundary: :func:`current_traceparent`
  renders the innermost open span as a W3C-style ``traceparent``
  header value (``00-<trace_id>-<span_id>-01``), and ``span(name,
  remote_parent=hdr)`` roots a NEW local trace that *adopts* the
  remote trace id and records the remote span as its parent — the
  replica-side ``raft.serve.request`` root becomes a child of the
  router's ``raft.fleet.route`` span even when the two run in
  different processes. Each side records its own trace *fragment*;
  :func:`raft_tpu.obs.recorder.stitch_chrome_trace` merges fragments
  sharing one trace id back into ONE Chrome trace. A remote-parented
  root bypasses per-request sampling (the upstream root already made
  the admission decision — a trace must never lose its tail to an
  independent coin flip downstream).
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from raft_tpu.obs import runtime as _runtime
from raft_tpu.obs.registry import NAME_RE

__all__ = [
    "Span",
    "span",
    "spanned",
    "current_span",
    "current_trace_id",
    "current_traceparent",
    "parse_traceparent",
    "add_child_span",
    "set_trace_enabled",
    "trace_enabled",
    "set_trace_sample_rate",
    "trace_sample_rate",
]


def _env_enabled() -> bool:
    return os.environ.get("RAFT_TPU_TRACE", "1").lower() not in (
        "0", "false", "off", "no")


def _env_sample_rate() -> float:
    try:
        v = float(os.environ.get("RAFT_TPU_TRACE_SAMPLE", "1.0"))
    except ValueError:
        return 1.0
    return min(max(v, 0.0), 1.0)


_enabled = _env_enabled()
_sample_rate = _env_sample_rate()
_sample_rng = random.Random()
_tls = threading.local()
# itertools.count is atomic in CPython; ids only need process-local
# uniqueness (the pid prefixes exported traces where it matters)
_ids = itertools.count(1)
if _enabled:
    _runtime.install()


def set_trace_enabled(on: bool = True) -> None:
    """Runtime toggle (initial state from ``RAFT_TPU_TRACE``); also
    installs or removes the GC pause hook (:mod:`raft_tpu.obs.runtime`)."""
    global _enabled
    _enabled = bool(on)
    if _enabled:
        _runtime.install()
    else:
        _runtime.uninstall()


def trace_enabled() -> bool:
    return _enabled


def set_trace_sample_rate(rate: float, seed: Optional[int] = None
                          ) -> None:
    """Runtime per-request sampling rate (initial state from
    ``RAFT_TPU_TRACE_SAMPLE``). ``seed`` re-seeds the admission RNG —
    deterministic tests only."""
    global _sample_rate
    _sample_rate = min(max(float(rate), 0.0), 1.0)
    if seed is not None:
        _sample_rng.seed(seed)


def trace_sample_rate() -> float:
    return _sample_rate


def _new_id() -> str:
    return f"{next(_ids):08x}"


class _TraceState:
    """Per-thread in-flight trace: the stack of open spans plus the
    records of finished ones."""

    __slots__ = ("trace_id", "spans", "stack", "t0", "t0_unix",
                 "remote_parent")

    def __init__(self, trace_id: Optional[str] = None,
                 remote_parent: Optional[str] = None):
        self.trace_id = (trace_id if trace_id is not None
                         else f"{os.getpid():x}-{_new_id()}")
        # span id of the remote parent this trace fragment hangs under
        # (cross-process propagation, ISSUE 16); None for a local root
        self.remote_parent = remote_parent
        self.spans: List[dict] = []
        self.stack: List["Span"] = []
        self.t0 = time.perf_counter()
        # wall-clock on purpose: exported trace timestamps must be
        # correlatable across processes
        self.t0_unix = time.time()  # graftlint: disable=GL005


class Span:
    """One open scope. Use via :func:`span`; context-manager only."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "trace_id",
                 "_t0", "_trace", "_range", "_tid", "_root", "_remote")

    def __init__(self, name: str, attrs: Dict[str, object],
                 remote: Optional[Tuple[str, str]] = None):
        if not NAME_RE.match(name):
            raise ValueError(
                f"span name {name!r} violates the raft.<module>.<op> "
                f"taxonomy (want {NAME_RE.pattern})")
        self.name = name
        self.attrs = attrs
        self.span_id = ""
        self.parent_id = None
        self.trace_id = ""
        self._t0 = 0.0
        self._trace = None
        self._range = None
        self._tid = 0
        self._root = False
        # parsed (trace_id, span_id) of a remote parent — consumed only
        # when this span roots a new trace
        self._remote = remote

    # -- attributes --------------------------------------------------------
    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def set_attrs(self, **kv) -> None:
        self.attrs.update(kv)

    def sync(self, value) -> float:
        """Block until ``value`` (any pytree of jax arrays) is ready and
        record the device-inclusive elapsed time since span start as
        ``attrs["device_ms"]``. Returns the elapsed seconds."""
        import jax
        jax.block_until_ready(value)
        dt = time.perf_counter() - self._t0
        self.attrs["device_ms"] = round(dt * 1e3, 3)
        return dt

    # -- scope -------------------------------------------------------------
    def __enter__(self) -> "Span":
        tr = getattr(_tls, "trace", None)
        if tr is None:
            if self._remote is not None:
                # adopt the remote trace id so every fragment of one
                # routed request shares it; the remote span id becomes
                # this root's parent link
                tr = _TraceState(trace_id=self._remote[0],
                                 remote_parent=self._remote[1])
            else:
                tr = _TraceState()
            _tls.trace = tr
            self._root = True
        self._trace = tr
        self.trace_id = tr.trace_id
        self.span_id = _new_id()
        if tr.stack:
            self.parent_id = tr.stack[-1].span_id
        elif tr.remote_parent is not None:
            self.parent_id = tr.remote_parent
        tr.stack.append(self)
        self._tid = threading.get_ident()
        # the span IS the profiler range (shared taxonomy): cheap no-op
        # without an active profiler session
        from raft_tpu.core import trace
        self._range = trace.range(self.name)
        self._range.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        rng, self._range = self._range, None
        if rng is not None:
            rng.__exit__(exc_type, exc, tb)
        tr = self._trace
        self._trace = None
        if tr is None:
            return False
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        try:
            tr.stack.remove(self)
        except ValueError:
            pass
        rec = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_start_ms": round((self._t0 - tr.t0) * 1e3, 3),
            "duration_ms": round(dur * 1e3, 3),
            "tid": self._tid,
        }
        if self.attrs:
            rec["attrs"] = dict(self.attrs)
        tr.spans.append(rec)
        if self._root:
            _tls.trace = None
            _finalize(tr, self, dur)
        return False


class _NullSpan:
    """Shared no-op span for the disabled layer: accepts every Span
    method, allocates nothing, records nothing."""

    __slots__ = ()
    name = ""
    span_id = ""
    trace_id = ""
    parent_id = None
    attrs: Dict[str, object] = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key: str, value) -> None: ...

    def set_attrs(self, **kv) -> None: ...

    def sync(self, value) -> float:
        return 0.0


_NULL_SPAN = _NullSpan()


class _VetoSpan(_NullSpan):
    """The shared null span of a SAMPLED-OUT request: state-free (all
    bookkeeping lives in a thread-local depth counter), so one shared
    instance serves every suppressed scope. The veto depth keeps every
    nested ``span()`` of the rejected request on this same object —
    without it, a child opened inside a sampled-out root would roll
    its own admission and could record an orphan fragment trace."""

    __slots__ = ()

    def __enter__(self):
        _tls.veto = getattr(_tls, "veto", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.veto = max(0, getattr(_tls, "veto", 1) - 1)
        return False


_VETO_SPAN = _VetoSpan()


def span(name: str, remote_parent: Optional[str] = None,
         **attrs) -> Span:
    """Open a span named under the ``raft.<module>.<op>`` taxonomy.
    Returns the shared null object when tracing is disabled, or when
    this would start a new trace and per-request sampling
    (``RAFT_TPU_TRACE_SAMPLE``) rejects it.

    ``remote_parent`` (a :func:`current_traceparent` value, usually
    carried in an HTTP header or a ``submit(trace_context=...)``
    field) parents the span across a process/thread boundary: when
    this span roots a new trace, the trace adopts the remote trace id
    and the span records the remote span as its parent — and sampling
    is bypassed (the upstream root already admitted the request).
    Ignored when a trace is already open on this thread (a nested span
    has a real local parent) or when the value is malformed
    (propagation must never fail a request)."""
    if not _enabled:
        return _NULL_SPAN
    remote = (parse_traceparent(remote_parent)
              if remote_parent is not None else None)
    if getattr(_tls, "trace", None) is None and remote is None:
        # root-span admission: one Bernoulli draw per request; the
        # veto depth extends a rejection to the whole request
        if getattr(_tls, "veto", 0):
            return _VETO_SPAN
        if _sample_rate < 1.0 and _sample_rng.random() >= _sample_rate:
            return _VETO_SPAN
    return Span(name, attrs, remote=remote)


def spanned(name: str, **attrs):
    """Decorator form: run every call of the wrapped function inside
    ``span(name, **attrs)`` (fresh span per call — re-entrant). The
    body can enrich it via ``current_span().set_attrs(...)``."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, **attrs):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def current_span():
    """The innermost open span on this thread (the null span when
    tracing is off or no span is open) — lets deep call sites attach
    attributes (resolved cap, cache hit/miss) to the request that is
    already in flight without opening a scope of their own."""
    if not _enabled:
        return _NULL_SPAN
    tr = getattr(_tls, "trace", None)
    if tr is not None and tr.stack:
        return tr.stack[-1]
    return _NULL_SPAN


def current_trace_id() -> Optional[str]:
    tr = getattr(_tls, "trace", None)
    return tr.trace_id if tr is not None else None


def current_traceparent() -> Optional[str]:
    """Render the innermost open span as a W3C-style ``traceparent``
    value (``00-<trace_id>-<span_id>-01``) for cross-process
    propagation, or None when no span is open (or tracing is off).
    The flags byte is always ``01`` (sampled): an open span means the
    admission decision already said yes."""
    if not _enabled:
        return None
    tr = getattr(_tls, "trace", None)
    if tr is None or not tr.stack:
        return None
    return f"00-{tr.trace_id}-{tr.stack[-1].span_id}-01"


def parse_traceparent(header: Optional[str]
                      ) -> Optional[Tuple[str, str]]:
    """Parse a ``traceparent`` value into ``(trace_id, span_id)``, or
    None when missing/malformed — propagation must never fail a
    request. Lenient on the trace-id charset because our ids embed a
    dash (``{pid:x}-{counter:08x}``): split the version off the front,
    then the span id + flags off the back, and the middle is the trace
    id verbatim."""
    if not header:
        return None
    try:
        version, rest = header.strip().split("-", 1)
        trace_id, span_id, _flags = rest.rsplit("-", 2)
    except ValueError:
        return None
    if version != "00" or not trace_id or not span_id:
        return None
    if len(_flags) != 2 or not all(c in "0123456789abcdefABCDEF"
                                   for c in _flags):
        return None
    return trace_id, span_id


def add_child_span(name: str, start_s: float, duration_s: float,
                   **attrs) -> None:
    """Record one already-timed child span under the current span
    (``start_s`` on the ``time.perf_counter`` clock). The rank-tagged
    shard spans of ``parallel/ivf.py`` use this: the SPMD dispatch runs
    every rank inside one host call, so the per-rank spans share the
    dispatch interval and are merged host-side into the one trace."""
    if not _enabled:
        return
    tr = getattr(_tls, "trace", None)
    if tr is None or not tr.stack:
        return
    if not NAME_RE.match(name):
        raise ValueError(f"span name {name!r} violates the taxonomy")
    tr.spans.append({
        "name": name,
        "span_id": _new_id(),
        "parent_id": tr.stack[-1].span_id,
        "t_start_ms": round((start_s - tr.t0) * 1e3, 3),
        "duration_ms": round(duration_s * 1e3, 3),
        "tid": threading.get_ident(),
        "attrs": attrs,
    })


def _finalize(tr: _TraceState, root: Span, dur_s: float) -> None:
    trace = {
        "trace_id": tr.trace_id,
        "name": root.name,
        "start_unix": tr.t0_unix,
        "duration_ms": round(dur_s * 1e3, 3),
        "spans": tr.spans,
    }
    if root.attrs:
        trace["attrs"] = dict(root.attrs)
    if tr.remote_parent is not None:
        # marks this trace as a child FRAGMENT of a remote trace; the
        # stitcher uses it to tell router-side roots from replica-side
        trace["remote_parent"] = tr.remote_parent
    # lazy import: recorder depends on registry/logger only, so the
    # dependency between the two obs submodules stays one-way
    from raft_tpu.obs import recorder as _recorder
    _recorder.RECORDER.record(trace)
