"""The load generator (bench/loadgen.py) against fake servers that
stall, shed and never answer."""

import threading
import time
from concurrent.futures import Future

import numpy as np

import loadgen

OPEN = {"kind": "open_poisson", "rate_rps": 200.0, "queries_per_request": 1}


class RejectedError(RuntimeError):
    """Named like the server's back-pressure error: counted as shed."""


def answered(rows):
    f = Future()
    f.set_result((np.zeros((len(rows), 3)), np.zeros((len(rows), 3), int)))
    return f


def test_a_stall_shows_in_every_request_behind_it():
    sent = []

    def submit(rows):
        sent.append(rows)
        if len(sent) == 20:          # the 20th send blocks the generator
            time.sleep(0.15)
        return answered(rows)

    run = loadgen.drive(submit, 100, OPEN, seed=3, seconds=1.0, drain_s=1.0)
    lat, late = run.latencies_ms(), run.lateness_ms()
    assert len(run.requests) == 200 and run.failed() == 0
    # requests due while the generator was stuck were sent late, and
    # their latency counts that wait: it is timed from when each was due
    behind = late > 50.0
    assert behind.sum() >= 10
    assert (lat[behind] >= late[behind]).all()
    assert loadgen.percentile(late, 95) > 50.0


def test_shed_and_unanswered_requests_fail():
    n = [0]
    never = []

    def submit(rows):
        n[0] += 1
        if n[0] % 5 == 0:
            f = Future()
            f.set_exception(RejectedError("queue full"))
            return f
        if n[0] % 7 == 0:
            never.append(Future())   # never resolves
            return never[-1]
        return answered(rows)

    run = loadgen.drive(submit, 100, OPEN, seed=4, seconds=0.5, drain_s=0.2)
    outcomes = [r.outcome for r in run.requests]
    assert outcomes.count("shed") == n[0] // 5
    assert run.lost() == outcomes.count(None) > 0
    assert run.failed() == outcomes.count("shed") + outcomes.count(None)
    # a failed request reads as waiting until the drain gave up on it
    lat = run.latencies_ms()
    assert np.isfinite(lat).all()
    failed = np.array([r.outcome != "ok" for r in run.requests])
    assert (lat[failed] >= (run.t_drained - run.t_end) * 1e3).all()


def test_closed_loop_times_from_when_each_client_was_ready():
    def submit(rows):
        f = Future()
        threading.Timer(0.01, lambda: f.set_result(
            (np.zeros((len(rows), 2)), np.zeros((len(rows), 2), int)))
        ).start()
        return f

    traffic = {"kind": "closed", "clients": 3, "queries_per_request": 4}
    run = loadgen.drive(submit, 50, traffic, seed=5, seconds=0.5,
                        drain_s=1.0)
    assert run.failed() == 0 and 60 <= len(run.requests) <= 160
    assert all(len(r.rows) == 4 for r in run.requests)
    # after its first request, each client's next is due when the last
    # one was answered
    done = sorted(r.t_done for r in run.requests)
    later = [r for r in run.requests if r.t_due > run.t0]
    assert later and all(r.t_due in done for r in later)
    assert run.queries_in_window() <= 4 * len(run.requests)


def test_every_seed_gets_the_same_arrivals_in_another_order():
    a = loadgen.arrival_offsets(300.0, 10.0, seed=1)
    b = loadgen.arrival_offsets(300.0, 10.0, seed=2 ** 31 + 7)
    assert len(a) == len(b) == 3000
    # one fixed set of gaps (less the first), in another order
    qs = np.linspace(0.05, 0.95, 19)
    assert np.allclose(np.quantile(np.diff(a), qs),
                       np.quantile(np.diff(b), qs), rtol=1e-2)
    assert not np.allclose(a, b)
    assert a[0] == 0.0 and a[-1] < 10.0


def test_nearest_rank_percentile():
    assert loadgen.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert loadgen.percentile(range(1, 101), 95) == 95.0
