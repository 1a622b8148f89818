"""Profiler trace annotations.

TPU-native analogue of the reference's NVTX ranges
(``cpp/include/raft/core/nvtx.hpp:69-110``): RAII ``range`` objects +
``push_range``/``pop_range``, compiled to no-ops when disabled. Here ranges
map to ``jax.profiler`` trace annotations so they show up in xprof/Perfetto
traces, and are gated by ``enable_tracing`` (reference gates on the
``NVTX_ENABLED`` CMake flag, ``cpp/CMakeLists.txt:212``).
"""

from __future__ import annotations

import contextlib
import threading
from typing import List

import jax

_enabled = True
_tls = threading.local()


def _stack() -> List[object]:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def enable_tracing(on: bool = True) -> None:
    global _enabled
    _enabled = on


@contextlib.contextmanager
def range(fmt: str, *args):
    """RAII-style trace range (reference ``common::nvtx::range``)."""
    if not _enabled:
        yield
        return
    name = fmt % args if args else fmt
    with jax.profiler.TraceAnnotation(name):
        yield


def annotation(name: str, **meta):
    """An un-entered trace annotation carrying ``meta`` as its event
    arguments, or None while tracing is disabled. For a range whose
    start and end arrive in separate callbacks (the GC pause range of
    ``raft_tpu.obs.runtime``): the caller enters and exits it."""
    if not _enabled:
        return None
    return jax.profiler.TraceAnnotation(name, **meta)


def push_range(fmt: str, *args) -> None:
    """Toggle-balance contract (pinned by tests/test_core.py
    TestTraceToggleBalance): the enable state at PUSH time decides what
    the matching pop does. disabled→enabled: the None placeholder is
    popped silently (no annotation was ever entered). enabled→disabled:
    the entered annotation is always exited (see :func:`pop_range`).
    Either direction leaves the per-thread stack balanced."""
    if not _enabled:
        # push a placeholder so push/pop pairs stay balanced even if
        # tracing is toggled between them
        _stack().append(None)
        return
    name = fmt % args if args else fmt
    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    _stack().append(ann)


def pop_range() -> None:
    """Pops regardless of the current enable state: an annotation entered
    while tracing was on must always be exited."""
    stack = _stack()
    if not stack:
        return
    ann = stack.pop()
    if ann is not None:
        ann.__exit__(None, None, None)
