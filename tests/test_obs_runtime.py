"""The GC pause hook (raft_tpu.obs.runtime): collections counted per
generation, a range on the profiler clock, no lock taken inside the
collection, and present in ``gc.callbacks`` only while tracing is
enabled."""

import gc
import glob
import threading

import jax
import pytest

from raft_tpu import obs
from raft_tpu.obs import runtime, spans


def _csum(snap, name, **labels):
    want = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    key = f"{name}{{{want}}}" if want else name
    return snap["counters"].get(key, 0.0)


@pytest.fixture
def tracing():
    prev = spans.trace_enabled()
    spans.set_trace_enabled(True)
    yield
    spans.set_trace_enabled(prev)


def test_full_collection_is_counted(tracing):
    before = obs.snapshot()
    gc.collect()
    after = obs.snapshot()
    for name in ("raft.runtime.gc.collections", "raft.runtime.gc.seconds"):
        assert _csum(after, name, generation=2) > \
            _csum(before, name, generation=2)
    assert _csum(after, "raft.runtime.gc.collections", generation=2) - \
        _csum(before, "raft.runtime.gc.collections", generation=2) >= 1


def test_collection_under_the_registry_lock_finishes(tracing):
    """The callback takes no lock: a collection runs to its end while
    another thread holds the registry's lock."""
    held, release = threading.Event(), threading.Event()

    def holder():
        with obs.REGISTRY._lock:
            held.set()
            release.wait(30)

    done = threading.Event()

    def collect():
        gc.collect()
        done.set()

    h = threading.Thread(target=holder, daemon=True)
    h.start()
    assert held.wait(10)
    c = threading.Thread(target=collect, daemon=True)
    try:
        c.start()
        assert done.wait(10), "gc.collect() blocked on the registry lock"
    finally:
        release.set()
        h.join(10)
    before = _csum(obs.snapshot(), "raft.runtime.gc.collections",
                   generation=2)
    gc.collect()
    assert _csum(obs.snapshot(), "raft.runtime.gc.collections",
                 generation=2) >= before + 1


def test_hook_follows_the_trace_toggle(tracing):
    assert runtime.installed()
    spans.set_trace_enabled(False)
    assert not runtime.installed()
    assert runtime._on_gc not in gc.callbacks
    spans.set_trace_enabled(True)
    assert gc.callbacks.count(runtime._on_gc) == 1


def test_collection_is_a_range_on_the_profiler_clock(tracing, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    gens = [dict((str(k), v) for k, v in e.stats).get("generation")
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == runtime.GC_RANGE]
    assert 2 in gens


def test_concurrent_flushes_count_every_collection_once(tracing):
    """Threads that allocate (so collections start on all of them) and
    flush through ``obs.snapshot()`` at once: the registry ends up with
    exactly the collections the hook counted."""
    import sys
    snap = obs.snapshot()
    base = [_csum(snap, "raft.runtime.gc.collections", generation=g)
            for g in range(3)]
    counted = list(runtime._counts)

    def work():
        for _ in range(100):
            _ = [[] for _ in range(2000)]
            obs.snapshot()

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(prev)
    snap = obs.snapshot()
    for g in range(3):
        got = _csum(snap, "raft.runtime.gc.collections", generation=g)
        assert got - base[g] == runtime._counts[g] - counted[g]
    assert runtime._counts[0] > counted[0]
