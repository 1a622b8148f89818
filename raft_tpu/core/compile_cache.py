"""Persistent XLA compilation cache — the AOT-kernel role.

The reference ships pre-compiled CUDA kernels, so a fresh process pays
zero compile cost. Our analogue under jit is JAX's persistent
compilation cache: executables are cached on disk keyed by (HLO,
compile options, platform) and reloaded by later processes.

``enable()`` is called by the entry points (chip_smoke.py, bench.py,
bench_suite.py, tools/fleetd.py, tools/profile_*.py) before they touch
JAX — not by ``import raft_tpu`` itself, so plain library users keep
JAX's default behavior unless they opt in.

Where the cache lives: where ``JAX_COMPILATION_CACHE_DIR`` says, if it
is set (JAX reads it itself; no directory is set in code), otherwise
the fixed ``<repo>/.jax_cache``. The path is part of what makes a later
process hit, so it never moves.
"""

from __future__ import annotations

import os

from raft_tpu import obs

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False
_events_hooked = False


def _hook_cache_events() -> None:
    """Mirror jax's compilation-cache monitoring events into the obs
    registry (hit/miss counters + retrieval-time histogram) — the
    runtime answer to "did this process actually run warm?". The
    listener API is jax-internal, so best-effort: on any drift the
    cache still works, only the counters go dark."""
    global _events_hooked
    if _events_hooked:
        return
    try:
        from jax._src import monitoring

        def _on_event(event: str, **kw) -> None:
            if "/compilation_cache/" not in event:
                return
            try:
                obs.counter("raft.compile_cache.event",
                            event=event.rsplit("/", 1)[-1]).inc()
            except Exception:
                pass

        def _on_duration(event: str, duration: float, **kw) -> None:
            if "/compilation_cache/" not in event:
                return
            try:
                obs.histogram("raft.compile_cache.duration_seconds",
                              event=event.rsplit("/", 1)[-1]
                              ).observe(duration)
            except Exception:
                pass

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _events_hooked = True
    except Exception:
        pass


def enable() -> bool:
    """Idempotently turn on the persistent compilation cache. Returns
    True if the cache is active after the call."""
    global _enabled
    if _enabled:
        return True
    import jax
    try:
        if not os.environ.get(ENV_DIR):
            os.makedirs(DEFAULT_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # cache everything: the default thresholds skip small/fast
        # compiles, which a cold chip run still pays once per shape
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    except OSError as e:  # unwritable repo dir
        # visible, once: a silently-off cache pays every compile again
        import warnings
        warnings.warn(f"raft_tpu compile cache disabled ({e!r}); cold "
                      f"compiles will not be reused across processes")
        obs.counter("raft.compile_cache.enable", result="error").inc()
        obs.gauge("raft.compile_cache.active").set(0)
        return False
    _enabled = True
    obs.counter("raft.compile_cache.enable", result="ok").inc()
    obs.gauge("raft.compile_cache.active").set(1)
    _hook_cache_events()
    return True
