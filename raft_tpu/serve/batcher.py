"""Dynamic micro-batcher: independent requests → saturated plan shapes.

The serving gap this closes: every caller invoking ``plan.search``
alone runs the chip at per-request batch sizes — nq=1 dispatches on
hardware whose fixed per-dispatch cost was measured at ~9 ms
(docs/performance.md). The batcher is the standard TPU-runtime answer
(TPU-KNN, arxiv 2206.14286; continuous batching a la Ragged Paged
Attention, arxiv 2604.15464): a bounded queue, one dispatcher thread
that coalesces whatever is waiting into the largest admissible compiled
shape from the pre-warmed :class:`~raft_tpu.serve.ladder.PlanLadder`,
pads the ragged tail with duplicated REAL rows from the same batch
(pad results discarded — a pad row's neighbors can never leak into
another caller's results), executes the plan, and scatters per-request
slices back to caller futures.

Robustness is part of the contract, not an afterthought:

* **backpressure** — the queue is bounded (``ServeConfig.max_queue``);
  a submission over it fails NOW with :class:`RejectedError`.
* **deadlines** — an expired request completes with
  :class:`DeadlineExceeded` and never occupies a batch slot.
* **graceful degradation** — the :class:`LoadController` steps
  ``n_probes`` down the configured ladder above the queue-delay
  watermark and back up when drained (p99 bounded at slightly reduced
  recall instead of unbounded latency).
* **failure handling** (ISSUE 10, docs/robustness.md) — an optional
  dispatch **watchdog** (``ServeConfig.dispatch_timeout_ms``) abandons
  a hung dispatch (XLA collectives hang, not error, when a participant
  dies) and converts it into a typed :class:`ShardFailedError`; a
  comms-layer ``Status.ABORT``/``ERROR`` returned by a plan is
  converted the same way. Such failures are **retried** with
  exponential backoff under a ``max_retries`` budget, deadline-aware:
  a request whose deadline lands inside the backoff window fails NOW
  with :class:`DeadlineExceeded` rather than being retried past it.
  A **crash guard** around batch processing mirrors the compactor's:
  an unexpected dispatcher exception fails that batch's futures with
  a typed :class:`DispatchError` (counted under
  ``raft.serve.dispatcher.errors``) and the dispatcher keeps serving.

Every decision lands in ``raft.serve.*`` metrics and spans
(docs/serving.md has the taxonomy and a capacity-planning walkthrough).

Threading model: ONE dispatcher thread owns all device work; caller
threads only touch numpy and futures. With the watchdog enabled,
dispatch runs on a single helper thread the dispatcher waits on — an
abandoned (timed-out) helper drains its stuck program and exits, and a
fresh helper takes over, so at most one *live* dispatch exists at any
time (the overlap with a draining orphan mirrors real abort semantics:
a hung collective cannot be cancelled, only orphaned). Future
callbacks run on the dispatcher thread — keep them trivial.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from collections import deque
from typing import Optional, Tuple

import numpy as np

from raft_tpu import obs
from raft_tpu.core import trace
from raft_tpu.core.error import expects
from raft_tpu.core.logger import get_logger
from raft_tpu.obs import profiler, spans
from raft_tpu.obs import runtime as _runtime
from raft_tpu.serve.controller import LoadController
from raft_tpu.serve.ladder import PlanLadder
from raft_tpu.serve.types import (DeadlineExceeded, DispatchError,
                                  RejectedError, SearchResult,
                                  ServeConfig, ShardFailedError,
                                  _Request)
from raft_tpu.testing import faults

__all__ = ["SearchServer", "SERVE_LATENCY_BUCKETS", "OCCUPANCY_BUCKETS"]

# serving latency needs finer edges than the registry default around the
# tens-of-ms watermark region (p99-under-watermark is asserted from
# these buckets in tests/test_serve.py)
SERVE_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2,
    0.25, 0.3, 0.5, 1.0, 2.5, 5.0, 10.0)
OCCUPANCY_BUCKETS = (0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                     0.875, 1.0)

_SHED_RATE_WINDOW_S = 10.0


class _DispatchWorker:
    """The watchdog's helper thread: executes dispatches so the
    dispatcher can time one out and walk away. A timed-out worker is
    *abandoned* — it finishes (or hangs forever on) its stuck call,
    notices the flag, and exits without touching any shared serving
    state; the server spawns a replacement for the next dispatch."""

    def __init__(self, name: str):
        self._q: queue_mod.Queue = queue_mod.Queue()
        self.abandoned = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    def submit(self, fn) -> dict:
        box = {"done": threading.Event(), "out": None, "err": None}
        self._q.put((fn, box))
        return box

    def stop(self) -> None:
        self._q.put(None)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, box = item
            try:
                box["out"] = fn()
            except BaseException as e:  # delivered to the dispatcher
                box["err"] = e
            box["done"].set()
            if self.abandoned.is_set():
                return


class SearchServer:
    """The serving runtime over one index: ``submit() -> Future`` plus
    a blocking ``search()`` convenience. Construct via
    :meth:`from_index` (real plans) or directly from a
    :class:`PlanLadder` (tests inject fakes)."""

    # static race detector contract (tools/graftlint GL003): these
    # fields sit on the caller-thread/dispatcher-thread boundary and
    # must only be touched under `with self._cond` or inside a
    # `_locked`-suffix method
    GUARDED_BY = ("_q", "_rows_queued", "_closed", "_shed_times",
                  "_draining", "_inflight_rows")

    def __init__(self, ladder: PlanLadder,
                 config: Optional[ServeConfig] = None,
                 start: bool = True):
        self._ladder = ladder
        self._cfg = config if config is not None else ServeConfig()
        self._controller = LoadController(len(ladder.rungs), self._cfg)
        self._q: deque = deque()
        self._rows_queued = 0
        self._cond = threading.Condition()
        self._closed = False
        self._draining = False
        self._inflight_rows = 0
        self._thread: Optional[threading.Thread] = None
        self._shed_times: deque = deque()
        # watchdog helper (dispatcher-thread-only state, like the
        # LoadController: no lock because there is no sharing)
        self._worker: Optional[_DispatchWorker] = None
        # quality observability (ISSUE 11): None until enable_quality
        # attaches a monitor — with sampling off the hot path reads
        # exactly this one flag; _quality_src/_quality_meta carry the
        # mutable-epoch / family / metric context from_index learned
        self._quality = None
        self._quality_src = None
        self._quality_meta: dict = {}
        # resource profiler attribution tag (ISSUE 14): the fleet tier
        # names its replicas here so sampled device time folds into
        # router.report() per replica; dispatcher-thread-only read,
        # single plain-attr write at attach — no lock needed
        self._profile_tag = "server"
        obs.gauge("raft.serve.queue.max").set(self._cfg.max_queue)
        obs.gauge("raft.serve.queue.depth").set(0)
        obs.gauge("raft.serve.shed.rate").set(0.0)
        if start:
            self.start()

    @classmethod
    def from_index(cls, index, rep_queries, k: int, params=None,
                   config: Optional[ServeConfig] = None,
                   start: bool = True) -> "SearchServer":
        """Build + pre-warm the (shape × rung) plan ladder for
        ``index`` and start serving. ``rep_queries`` is the
        representative cap-measurement sample (same contract as
        ``plan.build_plan``). A :class:`raft_tpu.mutate.MutableIndex`
        is accepted too: its (shape × rung × delta-rung) grid is
        pre-warmed instead and the server keeps serving through every
        background compaction (the ladder handles re-resolve to the
        live epoch per call)."""
        config = config if config is not None else ServeConfig()
        from raft_tpu.mutate import MutableIndex, build_serve_ladder
        meta = {"metric": getattr(index, "metric", None)}
        if isinstance(index, MutableIndex):
            meta["family"] = index.family
            expects(k == index.k,
                    "serve.from_index: k=%d != MutableIndex k=%d "
                    "(fixed at its construction)", k, index.k)
            expects(params is None,
                    "serve.from_index: a MutableIndex carries its own "
                    "search params (set them at its construction)")
            ladder = build_serve_ladder(
                index, rep_queries, shapes=config.batch_sizes,
                probes_ladder=config.probes_ladder,
                prewarm=config.prewarm)
        else:
            # same resolver PlanLadder.build uses — an unsupported
            # index fails identically either way, so no guard needed
            from raft_tpu.neighbors import plan as plan_mod
            from raft_tpu.neighbors.tiered import TieredIndex
            if isinstance(index, TieredIndex):
                meta["family"] = "tiered_ivf_flat"
            else:
                meta["family"], _ = plan_mod._resolve_builder(index)
            ladder = PlanLadder.build(index, rep_queries, k, params,
                                      shapes=config.batch_sizes,
                                      probes_ladder=config.probes_ladder,
                                      prewarm=config.prewarm)
        srv = cls(ladder, config, start=start)
        srv._quality_meta = meta
        if isinstance(index, MutableIndex):
            srv._quality_src = index
        return srv

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "SearchServer":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="raft-serve-batcher")
            self._thread.start()
        return self

    def close(self) -> None:
        """Stop admitting, fail everything still queued with
        :class:`RejectedError`, and join the dispatcher."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        if self._worker is not None:
            self._worker.stop()
            self._worker = None
        if self._quality is not None:
            self._quality.close()
        # a never-started server still owes its queue explicit errors
        self._drain_closed()

    def __enter__(self) -> "SearchServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def closed(self) -> bool:
        # benign racy read: a bool snapshot for status endpoints; the
        # admission decision re-checks under the lock in submit()
        return self._closed  # graftlint: disable=GL003

    @property
    def ladder(self) -> PlanLadder:
        return self._ladder

    @property
    def config(self) -> ServeConfig:
        return self._cfg

    # -- quality observability (ISSUE 11) ----------------------------------
    @property
    def quality(self):
        """The attached :class:`raft_tpu.obs.quality.QualityMonitor`
        (None while sampling is off)."""
        return self._quality

    def enable_quality(self, corpus, ids=None, metric=None,
                       estimator=None, qconfig=None, family=None):
        """Attach shadow-exact recall estimation: live queries are
        reservoir-sampled at ``ServeConfig.quality_sample_rate`` and
        replayed off the serving path through a pre-warmed exact
        scorer over ``corpus`` (the index's rows — or a representative
        bounded sample; ``raft_tpu.obs.quality`` docstring for the
        sampled-corpus caveat). Returns the monitor, or None when the
        configured rate is 0 (nothing is constructed — the hot path
        stays at one flag read). For a mutable index the compaction
        epoch listener is wired automatically, so recall is tracked
        per epoch and ``raft.obs.quality.drift`` fires on a degrading
        fold."""
        rate = self._cfg.quality_sample_rate
        if rate <= 0:
            get_logger("serve").info(
                "enable_quality: quality_sample_rate=0 — no monitor "
                "attached (set it on ServeConfig to sample)")
            return None
        from raft_tpu.obs import quality as _quality
        metric = metric if metric is not None \
            else self._quality_meta.get("metric")
        kwargs = {} if metric is None else {"metric": metric}
        qcfg = qconfig if qconfig is not None \
            else _quality.QualityConfig()
        scorer = _quality.ExactScorer(
            corpus, ids=ids, kmax=self._ladder.k,
            max_rows=qcfg.max_rows, chunk=qcfg.chunk,
            batch=qcfg.shadow_batch, seed=qcfg.seed, **kwargs)
        monitor = _quality.QualityMonitor(
            scorer, sample_rate=rate, config=qcfg,
            family=(family if family is not None
                    else self._quality_meta.get("family", "index")),
            estimator=estimator)
        return self.attach_quality(monitor)

    def attach_quality(self, monitor):
        """Attach an already-built monitor (tests inject fakes). Wires
        the mutable-epoch listener when the server fronts a
        :class:`~raft_tpu.mutate.MutableIndex`."""
        src = self._quality_src
        if src is not None:
            src.add_epoch_listener(monitor.note_epoch)
        self._quality = monitor
        return monitor

    def set_profile_tag(self, tag: str) -> None:
        """Name this server's sampled dispatches in the resource
        profiler's per-tag ledger (``raft_tpu.obs.profiler`` —
        :class:`~raft_tpu.fleet.Replica` passes its replica name so
        fleet utilization is attributable per replica)."""
        self._profile_tag = str(tag)

    def _quality_epoch(self) -> int:
        src = self._quality_src
        return int(src.epoch) if src is not None else 0

    def _quality_detail(self) -> str:
        """Shard attribution for coverage-flagged samples — the
        distributed tier returns its current exclusion so a degraded
        recall series names the missing shards."""
        return ""

    # -- admission ---------------------------------------------------------
    def submit(self, queries, k: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               trace_context: Optional[str] = None):
        """Enqueue one request → ``Future`` resolving to ``(dists,
        ids)``, each ``(nq, k)`` numpy arrays. Admission is decided NOW:
        a full queue or a closed server fails the future immediately
        with :class:`RejectedError` (explicit backpressure, never
        unbounded growth).

        ``trace_context`` is an optional ``traceparent`` value; when
        omitted it defaults to the caller thread's innermost open span
        (so a submit made under a router's ``raft.fleet.route`` span —
        or any other span — automatically parents this request's
        ``raft.serve.request`` root, which otherwise opens on the
        dispatcher thread with no trace of its own)."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        expects(q.ndim == 2 and q.shape[1] == self._ladder.dim,
                "serve.submit: queries must be (nq, dim=%d), got %s",
                self._ladder.dim, q.shape)
        nq = int(q.shape[0])
        expects(0 < nq <= self._ladder.max_shape,
                "serve.submit: nq=%d exceeds the largest ladder shape "
                "%d — split the request or widen the ladder", nq,
                self._ladder.max_shape)
        k = self._ladder.k if k is None else int(k)
        expects(0 < k <= self._ladder.k,
                "serve.submit: k=%d exceeds the plan k=%d", k,
                self._ladder.k)
        if deadline_ms is None:
            deadline_ms = self._cfg.default_deadline_ms
        now = time.perf_counter()
        if trace_context is None:
            trace_context = spans.current_traceparent()
        req = _Request(queries=q, nq=nq, k=k, t_enq=now,
                       deadline=(now + deadline_ms / 1e3
                                 if deadline_ms and deadline_ms > 0
                                 else None),
                       trace_ctx=trace_context)
        obs.counter("raft.serve.requests.total").inc()
        obs.counter("raft.serve.queries.total").inc(nq)
        with self._cond:
            if self._closed:
                self._shed_locked(req, "closed")
                return req.future
            if self._draining:
                # drain() stopped admission (rolling restart, ISSUE 13):
                # the queue flushes, new work goes to another replica
                self._shed_locked(req, "draining")
                return req.future
            if len(self._q) >= self._cfg.max_queue:
                self._shed_locked(req, "queue_full")
                return req.future
            self._q.append(req)
            self._rows_queued += nq
            obs.gauge("raft.serve.queue.depth").set(len(self._q))
            self._cond.notify()
        return req.future

    def search(self, queries, k: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               timeout: Optional[float] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(queries, k, deadline_ms).result(timeout)

    # -- load / drain (ISSUE 13: the fleet tier's per-replica view) --------
    def load(self) -> dict:
        """Cheap load snapshot for routing decisions (the fleet
        router's power-of-two-choices input) and for /debug surfaces:
        queued requests/rows, rows in the batch currently executing,
        the recent shed rate, and the admission state. One lock
        acquisition, no device work, no allocation beyond the dict."""
        with self._cond:
            self._update_shed_rate_locked()
            return {
                "queue_depth": len(self._q),
                "queued_rows": self._rows_queued,
                "inflight_rows": self._inflight_rows,
                "shed_rate": (len(self._shed_times)
                              / _SHED_RATE_WINDOW_S),
                "draining": self._draining,
                "closed": self._closed,
            }

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Stop admission and flush: new submissions fail NOW with
        :class:`RejectedError` (reason ``draining``) while every
        already-queued request still executes and every outstanding
        future resolves. Returns True once the queue is empty and no
        batch is in flight (False = timed out with work remaining).
        The dispatcher stays alive — :meth:`resume` re-opens admission
        (the rolling-restart rejoin path); :meth:`close` afterwards is
        a clean stop with nothing left to fail."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            while self._q or self._inflight_rows:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=min(remaining, 0.25))
            return True

    def resume(self) -> None:
        """Re-open admission after :meth:`drain` (rolling-restart
        rejoin)."""
        with self._cond:
            self._draining = False
            self._cond.notify_all()

    # -- internals ---------------------------------------------------------
    def _shed_locked(self, req: _Request, reason: str) -> None:
        """Refuse admission (called under the queue lock). Counted AND
        span-attributed — the shed decision must be visible in both
        observability planes."""
        obs.counter("raft.serve.shed.total", reason=reason).inc()
        self._shed_times.append(time.monotonic())
        self._update_shed_rate_locked()
        with spans.span("raft.serve.request",
                        remote_parent=req.trace_ctx,
                        nq=req.nq, k=req.k,
                        outcome="shed", reason=reason):
            pass
        req.future.set_exception(RejectedError(
            f"request rejected ({reason}): queue depth "
            f"{len(self._q)}/{self._cfg.max_queue}"))

    def _update_shed_rate_locked(self) -> None:
        now = time.monotonic()
        while self._shed_times and now - self._shed_times[0] > \
                _SHED_RATE_WINDOW_S:
            self._shed_times.popleft()
        obs.gauge("raft.serve.shed.rate").set(
            len(self._shed_times) / _SHED_RATE_WINDOW_S)

    def _drain_closed(self) -> None:
        with self._cond:
            pending = list(self._q)
            self._q.clear()
            self._rows_queued = 0
            obs.gauge("raft.serve.queue.depth").set(0)
        for r in pending:
            if not r.future.done():
                obs.counter("raft.serve.shed.total", reason="closed").inc()
                r.future.set_exception(
                    RejectedError("server closed while queued"))

    def _fail_deadline(self, req: _Request, now: float) -> None:
        waited_ms = round((now - req.t_enq) * 1e3, 3)
        obs.counter("raft.serve.deadline.total").inc()
        with spans.span("raft.serve.request",
                        remote_parent=req.trace_ctx,
                        nq=req.nq, k=req.k,
                        outcome="deadline", waited_ms=waited_ms):
            spans.add_child_span("raft.serve.queue_wait", req.t_enq,
                                 now - req.t_enq)
        req.future.set_exception(DeadlineExceeded(
            f"deadline expired after {waited_ms} ms in queue"))

    def _take_batch_locked(self):
        """Pop whole requests up to the largest shape, dropping expired
        ones without letting them occupy a slot."""
        now = time.perf_counter()
        max_shape = self._ladder.max_shape
        batch, rows, expired = [], 0, []
        while self._q:
            r = self._q[0]
            if r.deadline is not None and now >= r.deadline:
                self._q.popleft()
                self._rows_queued -= r.nq
                expired.append(r)
                continue
            if batch and rows + r.nq > max_shape:
                break
            self._q.popleft()
            self._rows_queued -= r.nq
            batch.append(r)
            rows += r.nq
        depth = len(self._q)
        obs.gauge("raft.serve.queue.depth").set(depth)
        return batch, rows, expired, depth, now

    def _loop(self) -> None:
        cfg = self._cfg
        idle_s = max(cfg.degrade_cooldown_ms / 1e3, 0.02)
        wait_s = cfg.max_wait_ms / 1e3
        while True:
            # the dispatcher's phases cover its whole loop: collect →
            # assemble → [batch span: enqueue, host_epilogue,
            # device_wait, fetch] → scatter (docs/serving.md). collect,
            # assemble and scatter are profiler ranges, not spans: a
            # span there would root a trace of its own and enclose the
            # request roots
            with trace.range("raft.serve.collect"):
                with self._cond:
                    while not self._q and not self._closed:
                        if not self._cond.wait(timeout=idle_s):
                            # idle tick: the ladder steps back toward
                            # full quality, the overload verdict
                            # clears, the shed-rate window decays
                            self._controller.observe(0.0, 0)
                            self._update_shed_rate_locked()
                    if self._closed:
                        break
                    # batching window: let the head-of-line request
                    # wait up to max_wait_ms for a fuller batch (or
                    # until the largest shape is already covered)
                    head_t = self._q[0].t_enq
                    while (self._rows_queued < self._ladder.max_shape
                           and not self._closed and self._q):
                        remaining = wait_s - (time.perf_counter()
                                              - head_t)
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=remaining)
                    if self._closed:
                        break
                    batch, rows, expired, depth, now = \
                        self._take_batch_locked()
                    self._inflight_rows = rows
                for r in expired:
                    self._fail_deadline(r, now)
            if batch:
                # dispatcher crash guard (mirrors the compactor's,
                # ISSUE 10): one broken batch fails ITS futures with a
                # typed error; the dispatcher thread keeps serving —
                # previously any exception escaping _execute killed the
                # thread and hung every future behind it
                try:
                    self._execute(batch, rows, depth)
                except Exception as e:
                    obs.counter("raft.serve.dispatcher.errors").inc()
                    get_logger("serve").error(
                        "dispatcher: batch failed outside the dispatch "
                        "path (crash guard): %r", e)
                    err = (e if isinstance(e, DispatchError) else
                           DispatchError(f"dispatcher error: {e!r}"))
                    for r in batch:
                        if not r.future.done():
                            r.future.set_exception(err)
            with self._cond:
                # batch finished (or there was none): a drain() waiter
                # watches this reach zero together with an empty queue
                self._inflight_rows = 0
                self._cond.notify_all()
        self._drain_closed()

    # -- dispatch hooks (overridden by the distributed tier) ---------------
    def _plan_for_batch(self, rows: int, level: int):
        """(shape, plan) for a coalesced batch — the failover-aware
        distributed tier reroutes this to the partial-mesh ladder while
        shards are excluded."""
        return self._ladder.plan_for(rows, level)

    def _plan_after_failure(self, shape: int, level: int, err):
        """A replacement plan for the next attempt after a
        :class:`ShardFailedError` (the distributed tier returns its
        pre-warmed partial-mesh plan once suspects are known); None =
        retry the same plan."""
        return None

    def _watchdog_call(self, fn, timeout_s: float):
        if self._worker is None:
            self._worker = _DispatchWorker("raft-serve-watchdog")
        box = self._worker.submit(fn)
        if not box["done"].wait(timeout_s):
            # a hung XLA dispatch cannot be cancelled — orphan the
            # helper (it exits once its stuck program drains) and turn
            # the hang into a typed, retryable failure
            self._worker.abandoned.set()
            self._worker = None
            obs.counter("raft.serve.dispatch.timeouts.total").inc()
            raise ShardFailedError(
                f"dispatch exceeded dispatch_timeout_ms="
                f"{self._cfg.dispatch_timeout_ms:g}")
        if box["err"] is not None:
            raise box["err"]
        return box["out"]

    def _dispatch(self, plan, qb):
        """One plan execution with the failure conversions applied:
        a watchdog timeout and a comms ``ABORT``/``ERROR`` status both
        become :class:`ShardFailedError` — typed and retryable —
        instead of a silent hang or a bare exception that could kill
        the dispatcher thread."""
        def call():
            faults.inject("serve.execute", shape=plan.nq)
            return plan.search(qb, block=True)

        timeout_s = self._cfg.dispatch_timeout_ms / 1e3
        out = (self._watchdog_call(call, timeout_s) if timeout_s > 0
               else call())
        if not (isinstance(out, tuple) and len(out) == 2):
            # a comms-aware plan may surface sync_stream's verdict as a
            # Status instead of results (duck-typed — no comms import
            # on the serving path)
            status = getattr(out, "name", None) or repr(out)
            raise ShardFailedError(
                f"dispatch reported comms status {status}",
                ranks=getattr(out, "ranks", ()))
        return out

    def _execute(self, batch, rows: int, depth: int) -> None:
        with trace.range("raft.serve.assemble"):
            # profiler attribution: tag this dispatcher thread so a
            # sampled dispatch inside plan.search lands in this
            # server's (replica's) per-tag window — one None read when
            # profiling is off
            profiler.tag_dispatch(self._profile_tag)
            t_start = time.perf_counter()
            head_wait = t_start - min(r.t_enq for r in batch)
            level = self._controller.observe(head_wait, depth)
            shape, plan = self._plan_for_batch(rows, level)
            qb = (batch[0].queries if len(batch) == 1
                  else np.concatenate([r.queries for r in batch],
                                      axis=0))
            pad = shape - rows
            if pad:
                # duplicated-REAL-row padding (the pad_partial rule of
                # ann_types.batched_search): repeated real rows stay
                # in-distribution for the measured probe cap; their
                # result rows are sliced off before scatter
                obs.counter("raft.serve.batch.padded_rows").inc(pad)
                reps = -(-pad // rows)
                qb = np.concatenate([qb, np.tile(qb, (reps, 1))[:pad]],
                                    axis=0)
        plan, d, i, err, dead, t_done = self._run_batch(
            batch, rows, shape, plan, qb, level)
        # scatter: slice each request's rows out of the batch results
        # and resolve its future; then record the per-request traces,
        # so recording never delays an answer
        with trace.range("raft.serve.scatter"):
            exec_dur = t_done - t_start
            _runtime.flush()
            obs.counter("raft.serve.batch.total", level=level).inc()
            obs.counter("raft.serve.batch.rows").inc(rows)
            obs.counter("raft.serve.batch.slots").inc(shape)
            obs.histogram("raft.serve.batch.size",
                          buckets=obs.SIZE_BUCKETS).observe(rows)
            obs.histogram("raft.serve.batch.occupancy",
                          buckets=OCCUPANCY_BUCKETS).observe(
                              rows / shape)
            partial = bool(getattr(plan, "partial", False))
            coverage = float(getattr(plan, "coverage", 1.0))
            # quality sampling: ONE flag read per batch — None means
            # sampling is off and nothing below allocates or runs
            qm = self._quality
            if qm is not None and err is None:
                q_epoch = self._quality_epoch()
                q_excl = self._quality_detail() if partial else ""
            off = 0
            answered = []
            for r in batch:
                if id(r) in dead:   # failed with DeadlineExceeded
                    off += r.nq
                    continue
                wait_s = t_start - r.t_enq
                obs.histogram("raft.serve.queue.delay.seconds",
                              buckets=SERVE_LATENCY_BUCKETS).observe(
                                  wait_s)
                if err is not None:
                    obs.counter("raft.serve.errors.total").inc()
                    r.future.set_exception(err)
                    continue
                d_r = d[off:off + r.nq, :r.k].copy()
                i_r = i[off:off + r.nq, :r.k].copy()
                off += r.nq
                lat = t_done - r.t_enq
                obs.histogram("raft.serve.request.seconds",
                              buckets=SERVE_LATENCY_BUCKETS).observe(lat)
                obs.counter("raft.serve.completed.total").inc()
                if partial:
                    obs.counter(
                        "raft.serve.failover.partial.total").inc()
                r.future.set_result(
                    SearchResult(d_r, i_r, partial=True,
                                 coverage=coverage)
                    if partial else (d_r, i_r))
                answered.append((r, wait_s, lat))
                if qm is not None:
                    # shadow-exact sampling: a Bernoulli draw + bounded
                    # copy on this thread; the exact replay happens on
                    # the monitor's background thread, never in a batch
                    # slot
                    qm.offer(r.queries, i_r, r.k, epoch=q_epoch,
                             coverage=coverage, excluded=q_excl)
            # per-request root traces, once every answer is out:
            # queue-wait + (shared) execution children under one
            # raft.serve.request root — the flight recorder shows each
            # caller's story, batch sharing included
            for r, wait_s, lat in answered:
                with spans.span("raft.serve.request",
                                remote_parent=r.trace_ctx,
                                nq=r.nq, k=r.k,
                                outcome="partial" if partial else "ok",
                                level=level, batch_shape=shape,
                                latency_ms=round(lat * 1e3, 3)):
                    spans.add_child_span("raft.serve.queue_wait",
                                         r.t_enq, wait_s)
                    spans.add_child_span("raft.serve.execute", t_start,
                                         exec_dur, shape=shape,
                                         shared=len(batch) > 1)

    def _run_batch(self, batch, rows: int, shape: int, plan, qb,
                   level: int):
        """The ``raft.serve.batch`` root: dispatch with retries, then
        the device→host fetch. Returns ``(plan, d, i, err, dead,
        t_done)``: the plan that ran last, the host results (None after
        a failure), the failure, and the ids of requests already failed
        during a backoff."""
        cfg = self._cfg
        d = i = err = None
        dead: set = set()       # ids of requests failed during backoff
        attempt = 0
        with spans.span("raft.serve.batch", shape=shape, rows=rows,
                        requests=len(batch),
                        occupancy=round(rows / shape, 4),
                        n_probes=plan.n_probes, level=level) as bsp:
            while True:
                with spans.span("raft.serve.execute", shape=shape,
                                n_probes=plan.n_probes,
                                attempt=attempt):
                    try:
                        d, i = self._dispatch(plan, qb)
                        with spans.span("raft.serve.fetch"):
                            d, i = np.asarray(d), np.asarray(i)
                        err = None
                    except ShardFailedError as e:   # retryable
                        err = e
                    except Exception as e:  # scatter as-is, keep serving
                        err = e
                        bsp.set_attr("error", type(e).__name__)
                        break
                if err is None:
                    if attempt:
                        obs.counter("raft.serve.retry.success.total").inc()
                    break
                bsp.set_attr("error", type(err).__name__)
                # the failover-aware tier may hand back a degraded plan
                # for the next attempt (pre-warmed — never compiled on
                # the failure path)
                nxt = self._plan_after_failure(shape, level, err)
                if nxt is not None:
                    plan = nxt
                if attempt >= cfg.max_retries:
                    obs.counter("raft.serve.retry.exhausted.total").inc()
                    break
                attempt += 1
                backoff = (cfg.retry_backoff_ms / 1e3
                           * cfg.retry_backoff_mult ** (attempt - 1))
                # deadline-aware: a request whose deadline lands inside
                # the backoff window fails NOW with DeadlineExceeded —
                # a retry must never resolve after the caller stopped
                # waiting
                now = time.perf_counter()
                for r in batch:
                    if (id(r) not in dead and r.deadline is not None
                            and r.deadline <= now + backoff):
                        dead.add(id(r))
                        self._fail_deadline(r, now)
                if len(dead) == len(batch):
                    break       # nobody left waiting for the retry
                obs.counter("raft.serve.retry.total").inc()
                with spans.span("raft.serve.retry", attempt=attempt,
                                backoff_ms=round(backoff * 1e3, 3),
                                error=type(err).__name__):
                    if backoff > 0:
                        time.sleep(backoff)
            if attempt:
                bsp.set_attr("retries", attempt)
        return plan, d, i, err, dead, time.perf_counter()
