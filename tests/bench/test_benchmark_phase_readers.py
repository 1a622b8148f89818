"""The readers of the dispatcher's phase spans, its GC pause ranges and
the GC counters (``batch_host_ms.bulk``, ``idle_host.online``,
``gc_share.online``): the values hand-computed on a small synthetic
trace committed beside this file, and None where the program records
none of what they read."""

import json
import os

import pytest

import loadgen
import manifest
import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("batch_host_ms.bulk", "idle_host.online", "gc_share.online")


def _trace(name):
    with open(os.path.join(DATA, name)) as f:
        return tr.Trace.from_json(json.load(f))


def _ctx(trace=None, counters=None, seconds=30.0):
    return run.Context(cell={}, cfg={}, run=loadgen.Run(0.0, seconds),
                       pool=None, counters=counters or {}, spans=[],
                       trace=trace, peaks={}, layout={}, probe_table=None,
                       host_window=(0.0, 0.0))


def test_batch_host_ms_is_the_median_gap_between_device_waits():
    # waits inside the window end/start at 30/42, 60/80 ms: gaps 12 and
    # 20 ms; the one before the window and the one past its end are out
    ctx = _ctx(_trace("dispatch_phases_trace.json"))
    assert manifest.reader("batch_host_ms.bulk").read(ctx) == \
        pytest.approx(16.0)


def test_idle_host_counts_host_phases_and_gc_over_device_idle():
    # host phases and GC (collect and bench.* left out), clipped to the
    # 0-100 ms window: 30-36, 40-52, 60-70, 95-100; the device is busy
    # 10-30, 50-60, 80-90, so 6 + 10 + 10 + 5 = 31 ms lie over idle
    ctx = _ctx(_trace("dispatch_phases_trace.json"))
    assert manifest.reader("idle_host.online").read(ctx) == \
        pytest.approx(31.0)


def test_gc_share_is_gc_seconds_over_the_window():
    counters = {"raft.runtime.gc.seconds{generation=0}": 0.03,
                "raft.runtime.gc.seconds{generation=2}": 0.27,
                "raft.runtime.gc.collections{generation=0}": 400.0,
                "raft.serve.batch.rows": 10.0}
    ctx = _ctx(counters=counters, seconds=30.0)
    assert manifest.reader("gc_share.online").read(ctx) == \
        pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_what_it_reads(name):
    # the recorded chip trace predates the phase spans and GC ranges;
    # its counters hold no raft.runtime.gc.* series
    ctx = _ctx(_trace("flat2m_bulk_trace.json"),
               counters={"raft.serve.batch.rows": 256.0})
    assert manifest.reader(name).read(ctx) is None
    assert manifest.reader(name).read(_ctx()) is None


def test_new_readers_are_in_the_manifest_and_resolve():
    man = manifest.load()
    names = {m["name"] for m in man["per_layer"]}
    assert set(READERS) <= names
    assert manifest.problems(man) == []
