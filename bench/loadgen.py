"""The one load generator every traffic mix runs through.

A traffic mix is a data file, ``bench/traffic/<name>.json``, of these
parameters:

* ``kind``: ``"closed"`` (``clients`` callers, each sending its next
  request when its last one is answered) or ``"open_poisson"``
  (independent arrivals at ``rate_rps`` requests per second, sent on
  schedule whatever the server does);
* ``queries_per_request``: rows each request carries, drawn from the
  query pool with the run's seed.

Copied from ``tools/loadgen.run_open_loop`` with its measurement fixed:
each request is timed from when it was DUE (its arrival time, or the
moment its closed-loop client became ready), not from when the
generator got round to sending it, so a stall shows in every request
behind it; how late the generator ran is kept per request; requests
shed, expired, errored or still unanswered at the drain all count as
failed; and the window is the fixed ``seconds``, not a wall that
includes the drain.

Every seed gets the same work: an open loop's inter-arrival gaps are
one fixed set (drawn from ``GAP_SEED`` and scaled to fill the window
exactly), put in another order by the run's seed, so each run sends
``round(rate_rps * seconds)`` requests.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

GAP_SEED = 20240601
KINDS = ("closed", "open_poisson")


@dataclass
class Request:
    """One request the window sent, and what became of it."""

    rows: np.ndarray             # indices into the query pool
    t_due: float
    t_send: float = 0.0
    t_done: Optional[float] = None
    outcome: Optional[str] = None   # ok | shed | expired | error
    dists: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: str = ""


@dataclass
class Run:
    """The requests of one window, in the order they were sent."""

    t0: float
    seconds: float
    requests: List[Request] = field(default_factory=list)
    t_drained: float = 0.0

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    def answered(self) -> List[Request]:
        return [r for r in self.requests if r.outcome == "ok"]

    def failed(self) -> int:
        return sum(1 for r in self.requests if r.outcome != "ok")

    def lost(self) -> int:
        """Requests that never got an answer or got an error: the ones
        that count against ``correct`` (a shed is honest back-pressure)."""
        return sum(1 for r in self.requests
                   if r.outcome in (None, "error"))

    def queries_in_window(self) -> int:
        return sum(len(r.rows) for r in self.requests
                   if r.outcome == "ok" and r.t_done <= self.t_end)

    def latencies_ms(self) -> np.ndarray:
        """Due-to-answer time of every request sent. A failed request
        misses any limit: it reads as having waited until the run gave
        up on it, the drain's end (finite, so a result can carry it)."""
        return np.array([((r.t_done if r.outcome == "ok" else self.t_drained)
                          - r.t_due) * 1e3 for r in self.requests])

    def lateness_ms(self) -> np.ndarray:
        return np.array([(r.t_send - r.t_due) * 1e3 for r in self.requests])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) over all values."""
    v = np.sort(np.asarray(values, dtype=float))
    if v.size == 0:
        return float("nan")
    rank = int(np.ceil(q / 100.0 * v.size)) - 1
    return float(v[min(max(rank, 0), v.size - 1)])


def validate(traffic: dict) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r}: want one of {KINDS}")
    if int(traffic.get("queries_per_request", 0)) < 1:
        raise ValueError("traffic: queries_per_request must be >= 1")
    if kind == "closed" and int(traffic.get("clients", 0)) < 1:
        raise ValueError("closed traffic: clients must be >= 1")
    if kind == "open_poisson" and not float(traffic.get("rate_rps", 0)) > 0:
        raise ValueError("open_poisson traffic: rate_rps must be > 0")


def arrival_offsets(rate_rps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop: one
    fixed set of exponential gaps, scaled to end at ``seconds``, in the
    order ``seed`` gives. The first arrival falls on the window's start."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def drive(submit: Callable, pool_size: int, traffic: dict, seed: int,
          seconds: float, drain_s: float = 60.0,
          annotate: Optional[Callable] = None,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Run:
    """Send ``traffic`` for ``seconds`` through ``submit(rows) -> Future``
    (the future resolves to ``(dists, ids)``), then wait up to
    ``drain_s`` for what is still open. ``annotate(name)`` wraps the
    sends and the drain in a named host span (the profiler's
    ``TraceAnnotation`` in a traced run)."""
    validate(traffic)
    span = annotate or (lambda name: contextlib.nullcontext())
    nq = int(traffic["queries_per_request"])
    lock = threading.Lock()
    ready: "queue.SimpleQueue" = queue.SimpleQueue()
    closed = traffic["kind"] == "closed"

    def on_done(req: Request, client: Optional[int], fut) -> None:
        t = clock()
        try:
            d, i = fut.result()
        except Exception as e:  # noqa: BLE001 - classified, kept per request
            name = type(e).__name__
            outcome = ("shed" if name == "RejectedError" else
                       "expired" if name == "DeadlineExceeded" else "error")
            with lock:
                req.t_done, req.outcome, req.error = t, outcome, repr(e)
        else:
            with lock:
                req.t_done, req.outcome = t, "ok"
                req.dists, req.ids = np.asarray(d), np.asarray(i)
        if client is not None:
            ready.put((client, t))

    def send(req: Request, client: Optional[int]) -> None:
        with span("bench.submit"):
            req.t_send = clock()
            fut = submit(req.rows)
        run.requests.append(req)
        fut.add_done_callback(lambda f: on_done(req, client, f))

    run = Run(t0=clock(), seconds=seconds)
    if closed:
        n_clients = int(traffic["clients"])
        rngs = [np.random.default_rng([seed, c]) for c in range(n_clients)]
        for c in range(n_clients):
            ready.put((c, run.t0))
        while True:
            left = run.t_end - clock()
            if left <= 0:
                break
            try:
                c, t_ready = ready.get(timeout=left)
            except queue.Empty:
                break
            if clock() >= run.t_end:
                break
            send(Request(rows=rngs[c].integers(0, pool_size, nq),
                         t_due=t_ready), c)
    else:
        due = run.t0 + arrival_offsets(float(traffic["rate_rps"]), seconds,
                                       seed)
        rows = np.random.default_rng([seed, 1]).integers(
            0, pool_size, (len(due), nq))
        for t_due, r in zip(due, rows):
            wait = t_due - clock()
            if wait > 0:
                sleep(wait)
            send(Request(rows=r, t_due=float(t_due)), None)
    with span("bench.drain"):
        deadline = clock() + drain_s
        while clock() < deadline:
            with lock:
                if all(r.outcome is not None for r in run.requests):
                    break
            sleep(0.005)
        run.t_drained = clock()
    return run
