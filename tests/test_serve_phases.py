"""The serving dispatcher's phases: every batch walks collect →
assemble → enqueue → device wait → fetch → scatter with no gap or
overlap between them (ranges captured by patching
``core.trace.range``, which every span opens too); the per-request
traces stay roots with their queue-wait child; every future of a batch
resolves before any of its request traces is recorded."""

import contextlib
import gc
import threading
import time

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.core import trace
from raft_tpu.neighbors import ivf_flat
from raft_tpu.obs import spans
from raft_tpu.random import make_blobs
from raft_tpu.serve import SearchServer, ServeConfig

PHASES = ("raft.serve.collect", "raft.serve.assemble", "raft.plan.enqueue",
          "raft.plan.host_epilogue", "raft.plan.device_wait",
          "raft.serve.fetch", "raft.serve.scatter")
SLACK_S = 1e-3


@pytest.fixture(scope="module")
def data():
    x, _ = make_blobs(n_samples=2000, n_features=16, centers=10,
                      cluster_std=2.0, seed=0)
    q, _ = make_blobs(n_samples=16, n_features=16, centers=10,
                      cluster_std=2.0, seed=1)
    idx = ivf_flat.build(np.asarray(x), ivf_flat.IndexParams(
        n_lists=8, kmeans_n_iters=3))
    return idx, np.asarray(q)


def _server(data, start=True):
    idx, q = data
    return SearchServer.from_index(
        idx, q[:4], 4, params=ivf_flat.SearchParams(n_probes=8),
        config=ServeConfig(batch_sizes=(1, 4), max_wait_ms=1.0),
        start=start)


@pytest.fixture
def tracing():
    prev = spans.trace_enabled()
    spans.set_trace_enabled(True)
    obs.RECORDER.clear()
    yield obs.RECORDER
    obs.RECORDER.clear()
    spans.set_trace_enabled(prev)


@pytest.fixture
def ranges(monkeypatch):
    """(name, thread id, start, end) of every range opened from now."""
    events = []
    real = trace.range

    @contextlib.contextmanager
    def recording(fmt, *args):
        t0 = time.perf_counter()
        try:
            with real(fmt, *args):
                yield
        finally:
            events.append((fmt % args if args else fmt,
                           threading.get_ident(), t0, time.perf_counter()))

    monkeypatch.setattr(trace, "range", recording)
    return events


def _dispatcher_tid(ranges):
    """The dispatcher's thread: the one that opened raft.serve.scatter."""
    tids = {t for n, t, _, _ in ranges if n == "raft.serve.scatter"}
    assert len(tids) == 1
    return tids.pop()


def test_each_batch_walks_the_phases_in_order(tracing, ranges, data):
    srv = _server(data)
    _, q = data
    gc.disable()        # a collection would open a range of its own
    try:
        srv.search(q[:1])           # its collect opened before the patch
        for row in range(1, 7):
            srv.search(q[row:row + 1])
    finally:
        gc.enable()
        srv.close()
    tid = _dispatcher_tid(ranges)
    seq = sorted((a, b, n) for n, t, a, b in ranges
                 if t == tid and n in PHASES)
    batches, cur = [], []
    for a, b, n in seq:
        if n == "raft.serve.collect" and cur:
            batches.append(cur)
            cur = []
        cur.append((a, b, n))
    batches.append(cur)
    whole = [b for b in batches
             if b[0][2] == "raft.serve.collect"
             and b[-1][2] == "raft.serve.scatter"]
    assert len(whole) >= 5
    want = [p for p in PHASES if p != "raft.plan.host_epilogue"]
    for batch in whole:
        assert [n for _, _, n in batch] == want
        for (_, end, _), (start, _, _) in zip(batch, batch[1:]):
            assert abs(start - end) <= SLACK_S


def test_request_traces_stay_roots_with_their_queue_wait(tracing, data):
    srv = _server(data)
    _, q = data
    try:
        futs = [srv.submit(q[i:i + 1]) for i in range(6)]
        for f in futs:
            f.result(60)
    finally:
        srv.close()
    reqs = [t for t in tracing.requests()
            if t["name"] == "raft.serve.request"]
    assert len(reqs) == 6
    for t in reqs:
        root, = [s for s in t["spans"] if s["name"] == "raft.serve.request"]
        assert root["parent_id"] is None
        waits = [s for s in t["spans"]
                 if s["name"] == "raft.serve.queue_wait"]
        assert len(waits) == 1
        assert waits[0]["parent_id"] == root["span_id"]
    # the batch root keeps its phases and no per-request children
    batches = [t for t in tracing.requests()
               if t["name"] == "raft.serve.batch"]
    assert batches
    for t in batches:
        names = {s["name"] for s in t["spans"]}
        assert "raft.serve.queue_wait" not in names
        assert {"raft.plan.enqueue", "raft.plan.device_wait",
                "raft.serve.fetch"} <= names


def test_futures_resolve_before_request_traces_are_recorded(tracing,
                                                            data,
                                                            monkeypatch):
    srv = _server(data, start=False)
    _, q = data
    futs = [srv.submit(q[i:i + 1]) for i in range(3)]
    seen = []
    real = obs.RECORDER.record

    def record(tr):
        if tr["name"] == "raft.serve.request":
            seen.append([f.done() for f in futs])
        return real(tr)

    monkeypatch.setattr(obs.RECORDER, "record", record)
    srv.start()
    try:
        for f in futs:
            f.result(60)
    finally:
        srv.close()
    assert len(seen) == 3
    assert all(all(done) for done in seen)
