"""Host-runtime pauses on the profiler clock: garbage collections.

A full collection stops every Python thread of the process, the
serving dispatcher included, for as long as it takes to walk the heap;
the chip idles behind it. :func:`install` puts one hook in
``gc.callbacks`` that, for every collection:

* opens the profiler range ``raft.runtime.gc`` (argument
  ``generation``) when the collection starts and closes it when it
  stops, so a device-idle gap in an xprof/Perfetto trace can be named
  by the pause under it;
* accumulates the count and the seconds per generation, which
  :func:`flush` moves into the counters
  ``raft.runtime.gc.collections{generation}`` and
  ``raft.runtime.gc.seconds{generation}``.

The hook takes no lock: a collection can start on a thread that holds
the registry's lock or the batcher's condition, so the callback only
updates plain module-level numbers. :func:`flush` (the serving
dispatcher calls it once a batch; :func:`snapshot` calls it first)
takes the registry's lock outside the collection. No recorder trace is
made for a collection: generation-0 collections come every few
milliseconds and would evict the request traces.

The hook lives while tracing is enabled (``RAFT_TPU_TRACE``,
``spans.set_trace_enabled``), which installs and removes it.
"""

from __future__ import annotations

import gc
import threading
import time

from raft_tpu.obs import registry as _registry

__all__ = ["GC_RANGE", "install", "uninstall", "installed", "flush",
           "snapshot"]

GC_RANGE = "raft.runtime.gc"
_GENERATIONS = 3

# written only inside the callback (collections never overlap: the
# interpreter runs one at a time), read by flush()
_counts = [0] * _GENERATIONS
_seconds = [0.0] * _GENERATIONS
_t_start = 0.0
_open = None
# what flush() has already moved into the registry
_flushed_counts = [0] * _GENERATIONS
_flushed_seconds = [0.0] * _GENERATIONS
_flush_lock = threading.Lock()
_trace = None       # raft_tpu.core.trace, resolved by install()


def _on_gc(phase: str, info: dict) -> None:
    global _t_start, _open
    if phase == "start":
        _t_start = time.perf_counter()
        ann = _trace.annotation(GC_RANGE, generation=info["generation"])
        if ann is not None:
            ann.__enter__()
        _open = ann
        return
    dt = time.perf_counter() - _t_start
    ann, _open = _open, None
    if ann is not None:
        ann.__exit__(None, None, None)
    g = info["generation"]
    _counts[g] += 1
    _seconds[g] += dt


def install() -> None:
    """Put the hook in ``gc.callbacks`` (once)."""
    global _trace
    if _trace is None:
        from raft_tpu.core import trace
        _trace = trace
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def uninstall() -> None:
    """Take the hook out of ``gc.callbacks``."""
    while _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def installed() -> bool:
    return _on_gc in gc.callbacks


def flush() -> None:
    """Move the collections counted since the last flush into the
    registry's ``raft.runtime.gc.*`` counters."""
    from raft_tpu import obs
    with _flush_lock:
        for g in range(_GENERATIONS):
            n, s = _counts[g], _seconds[g]
            if n != _flushed_counts[g]:
                obs.counter("raft.runtime.gc.collections",
                            generation=g).inc(n - _flushed_counts[g])
                obs.counter("raft.runtime.gc.seconds",
                            generation=g).inc(s - _flushed_seconds[g])
                _flushed_counts[g], _flushed_seconds[g] = n, s


def snapshot() -> dict:
    """:func:`raft_tpu.obs.registry.snapshot` with the collections
    counted so far flushed into it first."""
    flush()
    return _registry.snapshot()
