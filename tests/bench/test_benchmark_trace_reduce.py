"""The trace reduction (bench/trace_reduce.py): union busy time, idle
gaps named by the host span over them, kernel-event sums;
on a hand-made trace, on a small recorded one committed beside this
file, and through ``load`` on a trace the CPU records here."""

import glob
import json
import os

import jax
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def hand_made() -> tr.Trace:
    # window 0..100 ms on one device: ops 10-30 and 20-40 overlap (busy
    # 10-40), 60-70 inside a program 55-75; idle 0-10, 40-60, 70-100
    ops = [("fusion.1", 10 * MS, 20 * MS), ("custom-call.7", 20 * MS, 20 * MS),
           ("copy.2", 60 * MS, 10 * MS),
           ("fusion.9", 95 * MS, 30 * MS)]          # runs past the window
    mods = [("jit_search", 5 * MS, 40 * MS), ("jit_search", 55 * MS, 20 * MS)]
    host = [("bench.drain", 40 * MS, 30 * MS), ("raft.serve.batch", 0, 12 * MS)]
    return tr.Trace(window=(0, 100 * MS),
                    devices={"/device:TPU:0": tr.Device(ops, mods)},
                    host=host)


def test_busy_is_the_union_of_op_intervals():
    t = hand_made()
    assert t.busy_intervals("/device:TPU:0") == [
        (10 * MS, 40 * MS), (60 * MS, 70 * MS), (95 * MS, 100 * MS)]
    assert t.busy_s() == pytest.approx(0.045)
    assert t.idle_share() == pytest.approx(0.55)
    assert t.window_s == pytest.approx(0.1)


def test_idle_gaps_longest_first_named_by_the_host():
    gaps = hand_made().idle_gaps("/device:TPU:0")
    assert gaps[0] == ("idle", pytest.approx(0.025))        # 70-95
    assert gaps[1] == ("bench.drain", pytest.approx(0.020))  # 40-60
    assert gaps[2] == ("raft.serve.batch", pytest.approx(0.010))


def test_kernel_sums_and_programs():
    t = hand_made()
    secs, progs = t.kernel(lambda n: n.startswith("custom-call"))
    assert secs == pytest.approx(0.020) and progs == 1
    top = dict(t.top_ops(10))
    assert top["fusion.1"] == pytest.approx(0.020)
    assert top["fusion.9"] == pytest.approx(0.005)


def test_json_round_trip():
    t = hand_made()
    back = tr.Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert back.busy_s() == t.busy_s()
    assert back.idle_gaps("/device:TPU:0") == t.idle_gaps("/device:TPU:0")


def test_recorded_chip_trace():
    """40 ms of a traced flat2m.bulk window on one TPU v5 lite."""
    with open(os.path.join(DATA, "flat2m_bulk_trace.json")) as f:
        t = tr.Trace.from_json(json.load(f))
    (dev,) = t.devices
    busy = t.busy_intervals(dev)
    assert all(a < b <= c for (a, b), (c, _) in zip(busy, busy[1:]))
    total = sum(d for _, _, d in t.devices[dev].ops)
    assert 0 < t.busy_s() * 1e9 <= total
    assert 0.0 <= t.idle_share() < 1.0
    gaps = t.idle_gaps(dev)
    assert sum(s for _, s in gaps) <= t.window_s * t.idle_share() + 1e-9
    secs, progs = t.kernel(lambda n: True)
    assert secs >= t.busy_s() and progs >= 1


def test_load_reads_the_window_and_host_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            with jax.profiler.TraceAnnotation("bench.submit"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = tr.load(path)
    assert t.window[1] > t.window[0]
    assert any(name == "bench.submit" for name, _, _ in t.host)
    assert t.devices == {}          # the CPU has no device plane
