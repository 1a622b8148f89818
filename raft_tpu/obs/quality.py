"""Online quality observability — shadow-exact recall estimation.

The serving stack observes everything about *speed* and *availability*
(``raft.serve.*`` histograms, spans, ``/healthz``) but, before this
module, nothing about *result quality*: recall was measured offline in
``bench_suite`` and the cheap unrescored estimator there drifts 0.13+
from truth (0.7159 estimated vs 0.8612 true for ivf_pq in a CPU run).
This is the always-on quality signal — the "measured signal" half of
the self-driving loop (ROADMAP item 5), the bench yardstick
productionized:

* the batcher **reservoir-samples** live queries at
  ``ServeConfig.quality_sample_rate`` (``SearchServer.enable_quality``
  attaches a :class:`QualityMonitor`);
* a **background shadow thread** replays the sampled queries — off the
  serving path, never occupying a batch slot — through a pre-warmed
  :class:`ExactScorer` (fixed-shape brute force over the corpus, or a
  bounded deterministic sample of it past ``max_rows``) and compares
  the SERVED ids against the exact ids;
* windowed per-query recall lands in
  ``raft.obs.quality.recall{family,epoch}`` gauges; partial-mesh
  failover results are attributed separately
  (``coverage=partial, excluded=<ranks>``) so degraded recall is
  explainable, not mysterious;
* an optional cheap **estimator** (e.g. the unrescored PQ search) runs
  on the same samples and ``raft.obs.quality.calibration.gap`` = shadow
  recall − estimator recall quantifies the 0.13 estimator gap online;
* recall is tracked **per compaction epoch**: when a fold's epoch rolls
  (the :class:`~raft_tpu.mutate.MutableIndex` epoch listener calls
  :meth:`QualityMonitor.note_epoch`), the previous epoch's windowed
  mean becomes the baseline, and ``raft.obs.quality.drift`` fires —
  gauge + ``raft.obs.quality.drift.total`` — the moment the new epoch
  degrades recall PAST ``drift_budget``. This is the trigger ROADMAP
  item 5's fold→rebuild policy consumes.

Zero-overhead contract (the PR 3 discipline): with sampling off the
serving hot path reads exactly one flag (``SearchServer._quality is
None`` — no allocation, no thread); with sampling on, the shadow
replay performs ZERO steady-state compiles — the scorer is one
fixed-shape jitted program per (batch, chunk) compiled at construction
(``warm()``), asserted in tests from ``raft.plan.cache.*`` staying
flat plus jax's own compile cache.

Caveats, stated rather than hidden:

* past ``max_rows`` the scorer scores a deterministic corpus
  **sample**; "exact" ids are then exact over the sample and the
  recall gauge is an estimator (still unbiased enough for drift/SLO
  purposes — the window compares like against like).
* for a mutable corpus the scorer snapshots construction-time rows;
  re-attach (``enable_quality``) after heavy churn, or rebuild on the
  epoch listener, to keep ground truth fresh. Epoch-to-epoch DRIFT is
  still meaningful under churn: both windows score against the same
  snapshot, so a fold that loses candidates moves the gauge.
* the ``epoch`` label is bounded by the registry cardinality cap
  (``RAFT_TPU_METRICS_MAX_SERIES``); a process compacting thousands of
  epochs should raise it or restart the monitor.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from raft_tpu import obs
from raft_tpu.core.error import expects
from raft_tpu.core.logger import get_logger
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.obs import spans
from raft_tpu.obs.registry import CardinalityError

__all__ = ["ExactScorer", "QualityConfig", "QualityMonitor",
           "corpus_from_index"]

# metrics whose ranking the scorer reproduces exactly; everything else
# must go through a custom scorer object (duck-typed .topk)
_L2_KINDS = (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
             DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded)


def _score_chunk(q, rows, norms, kind: str, kmax: int):
    """One (batch, chunk) exact scoring tile → (top-kmax dists, chunk-
    local indices). Ranking-exact: HIGHEST-precision dot products, L2
    via the expanded form with the query norm dropped (rank-invariant
    per query), similarities negated so ascending-best holds for every
    kind. Pad rows carry +inf (masked via ``norms``)."""
    import jax
    import jax.numpy as jnp
    dots = jnp.einsum("qd,cd->qc", q, rows,
                      precision=jax.lax.Precision.HIGHEST)
    if kind == "l2":
        d = norms[None, :] - 2.0 * dots
    else:  # ip / cosine (corpus pre-normalized for cosine)
        d = jnp.where(jnp.isinf(norms)[None, :], jnp.inf, -dots)
    neg_top, idx = jax.lax.top_k(-d, kmax)
    return -neg_top, idx


_score_chunk_jit = None  # built lazily so importing quality stays jax-free


def _get_score_fn():
    global _score_chunk_jit
    if _score_chunk_jit is None:
        import jax
        _score_chunk_jit = jax.jit(_score_chunk,
                                   static_argnames=("kind", "kmax"))
    return _score_chunk_jit


class ExactScorer:
    """Pre-warmed fixed-shape exact brute-force scorer: the shadow
    ground truth. One jitted (batch × chunk) program compiled at
    construction scores ANY corpus size by tiling — the shadow path
    never compiles again (the zero-steady-state-compile contract).

    ``corpus`` is host rows ``(n, dim)``; ``ids`` maps row → global id
    (default ``arange``; pass the real id map for mutable / re-indexed
    corpora). Past ``max_rows`` a seeded deterministic sample is scored
    instead (``self.sampled`` says so; the recall gauge becomes an
    estimator — module docstring)."""

    def __init__(self, corpus, ids=None,
                 metric: DistanceType = DistanceType.L2Expanded,
                 kmax: int = 64, max_rows: int = 1 << 18,
                 chunk: int = 1 << 16, batch: int = 32, seed: int = 0,
                 warm: bool = True):
        import jax.numpy as jnp
        x = np.ascontiguousarray(np.asarray(corpus, np.float32))
        expects(x.ndim == 2 and x.shape[0] > 0,
                "ExactScorer: corpus must be a non-empty (n, dim) "
                "array, got %s", x.shape)
        n, dim = x.shape
        row_ids = (np.arange(n, dtype=np.int64) if ids is None
                   else np.asarray(ids, np.int64))
        expects(row_ids.shape == (n,),
                "ExactScorer: ids must be (n=%d,), got %s", n,
                row_ids.shape)
        self.sampled = n > max_rows
        if self.sampled:
            sel = np.sort(np.random.default_rng(seed).choice(
                n, size=max_rows, replace=False))
            x, row_ids, n = x[sel], row_ids[sel], max_rows
        if metric == DistanceType.CosineExpanded:
            self._kind = "cos"
            nrm = np.linalg.norm(x, axis=1, keepdims=True)
            x = x / np.maximum(nrm, 1e-30)
        elif metric == DistanceType.InnerProduct:
            self._kind = "ip"
        else:
            expects(metric in _L2_KINDS,
                    "ExactScorer: unsupported metric %s (l2 family, ip "
                    "or cosine)", metric)
            self._kind = "l2"
        self.metric = metric
        self.dim = dim
        self.rows = n
        self.batch = int(batch)
        self.kmax = int(min(kmax, n))
        chunk = int(min(chunk, 1 << 20))
        n_chunks = -(-n // chunk)
        chunk = min(chunk, n) if n_chunks == 1 else chunk
        self._k_tile = int(min(self.kmax, chunk))
        pad = n_chunks * chunk - n
        if pad:
            x = np.concatenate([x, np.zeros((pad, dim), np.float32)])
            row_ids = np.concatenate(
                [row_ids, np.full((pad,), -1, np.int64)])
        # per-row scoring norms: ||row||^2 for l2 (query norm dropped —
        # rank-invariant), 0 for similarities; +inf marks pad rows so
        # they can never enter a top-k
        norms = (np.einsum("cd,cd->c", x, x) if self._kind == "l2"
                 else np.zeros((n_chunks * chunk,), np.float32))
        norms = norms.astype(np.float32)
        norms[n:] = np.inf
        self._ids = row_ids.reshape(n_chunks, chunk)
        self._chunks = [jnp.asarray(x[c * chunk:(c + 1) * chunk])
                        for c in range(n_chunks)]
        self._norms = [jnp.asarray(norms[c * chunk:(c + 1) * chunk])
                       for c in range(n_chunks)]
        if warm:
            self.warm()

    def warm(self) -> "ExactScorer":
        """Compile + run the one (batch × chunk) program now, so the
        shadow thread never compiles (every chunk shares the shape)."""
        z = np.zeros((self.batch, self.dim), np.float32)
        self.topk(z, min(2, self.kmax))
        return self

    def topk(self, queries, k: int) -> np.ndarray:
        """Exact top-``k`` global ids for ``queries`` → ``(nq, k)``
        int64. Tiles queries to the fixed ``batch`` shape and the
        corpus to fixed chunks; merges chunk winners host-side."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        expects(q.shape[1] == self.dim,
                "ExactScorer.topk: queries must be (nq, dim=%d), got "
                "%s", self.dim, q.shape)
        k = int(min(k, self.kmax))
        expects(k > 0, "ExactScorer.topk: k must be >= 1")
        if self._kind == "cos":
            q = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-30)
        fn = _get_score_fn()
        nq = q.shape[0]
        out = np.empty((nq, k), np.int64)
        for s in range(0, nq, self.batch):
            qb = q[s:s + self.batch]
            pad = self.batch - qb.shape[0]
            if pad:
                qb = np.concatenate([qb, np.tile(qb[:1], (pad, 1))])
            ds, gs = [], []
            for c, (rows, norms) in enumerate(
                    zip(self._chunks, self._norms)):
                d, i = fn(qb, rows, norms, kind=self._kind,
                          kmax=self._k_tile)
                d, i = np.asarray(d), np.asarray(i)
                ds.append(d)
                gs.append(self._ids[c][i])
            d_all = np.concatenate(ds, axis=1)
            g_all = np.concatenate(gs, axis=1)
            order = np.argsort(d_all, axis=1, kind="stable")[:, :k]
            ids_b = np.take_along_axis(g_all, order, axis=1)
            out[s:s + self.batch - pad] = ids_b[:self.batch - pad]
        return out


def corpus_from_index(index) -> Tuple[np.ndarray, np.ndarray]:
    """Reconstruct ``(rows, ids)`` from an IVF-Flat index's list layout
    (the common enable_quality source when the caller no longer holds
    the build-time corpus). Raw-vector lists only — PQ/BQ corpora
    should pass the original rows (or ``index.raw`` when kept)."""
    data = np.asarray(index.lists_data)
    idx = np.asarray(index.lists_indices)
    valid = idx >= 0
    rows = data[valid].astype(np.float32, copy=False)
    if getattr(index, "scale", None) is not None:
        rows = rows * np.float32(index.scale)
    return rows, idx[valid].astype(np.int64)


@dataclass(frozen=True)
class QualityConfig:
    """Shadow-path knobs of a :class:`QualityMonitor`.

    * ``window`` — per-(epoch, coverage) rolling window of per-query
      recalls behind each gauge; ``min_window`` samples must accumulate
      before the drift comparison speaks (a 3-sample "regression" is
      noise, not signal).
    * ``max_pending`` — the reservoir bound: between shadow drains at
      most this many sampled queries are held; further samples
      reservoir-replace uniformly (``raft.obs.quality.evicted.total``
      counts the overwritten ones) so a hot burst can never grow host
      memory or bias toward its tail.
    * ``shadow_batch`` / ``chunk`` / ``max_rows`` — the
      :class:`ExactScorer` tile shapes (fixed → compiled once).
    * ``drift_budget`` — an epoch whose windowed recall falls MORE than
      this below the previous epoch's baseline fires
      ``raft.obs.quality.drift`` (strictly past the budget: equal-to-
      budget degradation is within contract).
    * ``poll_ms`` — shadow-thread wake cadence when idle.
    """

    window: int = 256
    min_window: int = 16
    max_pending: int = 256
    shadow_batch: int = 32
    chunk: int = 1 << 16
    max_rows: int = 1 << 18
    drift_budget: float = 0.05
    poll_ms: float = 50.0
    seed: int = 0

    def __post_init__(self):
        if self.window < 1 or self.min_window < 1 \
                or self.max_pending < 1:
            raise ValueError("QualityConfig: window, min_window and "
                             "max_pending must be >= 1")
        if not 0.0 < self.drift_budget < 1.0:
            raise ValueError("QualityConfig: drift_budget must be in "
                             "(0, 1)")


class QualityMonitor:
    """The always-on quality signal: reservoir-sampled live queries,
    shadow-scored exactly on a background thread, folded into windowed
    ``raft.obs.quality.*`` gauges. Construct with any scorer exposing
    ``.topk(queries, k) -> (nq, k) ids`` (tests plant fakes); attach to
    a server via :meth:`raft_tpu.serve.SearchServer.enable_quality`.

    ``estimator`` (optional, ``fn(queries, k) -> ids``) is the CHEAP
    recall estimator being calibrated — e.g. the unrescored PQ search;
    it runs on the shadow thread over the same samples and
    ``raft.obs.quality.calibration.gap`` publishes shadow − estimator
    recall, the gap ``bench_suite`` could previously only see offline.
    """

    # static race contract (tools/graftlint GL003): the dispatcher
    # thread (offer), the shadow thread (_loop/_process) and the epoch
    # listener (note_epoch, on the compactor thread) meet on these
    # fields — touch them only under `with self._cond` or in a
    # `_locked`-suffix method
    GUARDED_BY = ("_pending", "_streamed", "_inflight", "_closed",
                  "_windows", "_est_windows", "_epoch", "_baseline",
                  "_alarmed", "_samples_total")

    def __init__(self, scorer, sample_rate: float,
                 config: Optional[QualityConfig] = None,
                 family: str = "index",
                 estimator: Optional[Callable] = None,
                 start: bool = True):
        expects(0.0 < sample_rate <= 1.0,
                "QualityMonitor: sample_rate must be in (0, 1], got "
                "%s (rate 0 means: do not construct a monitor)",
                sample_rate)
        self.cfg = config if config is not None else QualityConfig()
        self.scorer = scorer
        self.rate = float(sample_rate)
        self.family = str(family)
        self._estimator = estimator
        self._rng = random.Random(self.cfg.seed)
        self._cond = threading.Condition()
        self._pending: List[tuple] = []
        self._streamed = 0          # reservoir stream length since drain
        self._inflight = False
        self._closed = False
        self._windows: Dict[tuple, deque] = {}
        self._est_windows: Dict[tuple, deque] = {}
        self._epoch = 0
        self._baseline: Optional[Tuple[int, float]] = None
        self._alarmed: set = set()
        self._card_warned = False
        self._samples_total = 0
        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "QualityMonitor":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="raft-obs-quality")
            self._thread.start()
        return self

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None

    def __enter__(self) -> "QualityMonitor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- sampling (dispatcher thread) --------------------------------------
    def offer(self, queries, ids, k: int, epoch: int = 0,
              coverage: float = 1.0, excluded: str = "") -> None:
        """Sample served queries into the reservoir (called by the
        batcher on its dispatcher thread — per-query Bernoulli draw,
        then a bounded copy; never any device work). ``coverage`` < 1
        flags a partial-mesh failover answer: those samples land in
        coverage-attributed series so degraded recall has a cause
        attached, and never pollute the full-coverage drift baseline."""
        # benign racy read: a sample racing close() is dropped either
        # way; the reservoir insert below re-checks nothing on purpose
        if self._closed:  # graftlint: disable=GL003
            return
        rng, rate = self._rng, self.rate
        q = np.asarray(queries)
        take = [j for j in range(q.shape[0]) if rng.random() < rate]
        if not take:
            return
        served = np.asarray(ids)
        k = int(k)
        obs.counter("raft.obs.quality.sampled.total").inc(len(take))
        cap = self.cfg.max_pending
        with self._cond:
            for j in take:
                rec = (q[j].astype(np.float32, copy=True),
                       served[j, :k].astype(np.int64, copy=True),
                       k, int(epoch), float(coverage), str(excluded))
                self._streamed += 1
                if len(self._pending) < cap:
                    self._pending.append(rec)
                else:
                    # algorithm R: uniform over the whole stream since
                    # the last shadow drain — a burst can neither grow
                    # memory nor bias the reservoir toward its tail
                    j = rng.randrange(self._streamed)
                    if j < cap:
                        self._pending[j] = rec
                    obs.counter("raft.obs.quality.evicted.total").inc()
            self._cond.notify()

    def note_epoch(self, epoch: int) -> None:
        """Roll the drift baseline at a compaction boundary — wired as
        a :meth:`raft_tpu.mutate.MutableIndex.add_epoch_listener`
        callback so the window split lands exactly where the fold did.
        (Samples tagged with a newer epoch roll it implicitly too.)"""
        with self._cond:
            self._roll_epoch_locked(int(epoch))

    # -- results -----------------------------------------------------------
    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block until every pending sample has been shadow-scored
        (tests / bench hooks) → False on timeout."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while self._pending or self._inflight:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
        return True

    def stats(self) -> dict:
        """Current-window summary (the loadgen/bench report row)."""
        with self._cond:
            cur = self._windows.get((self._epoch, "full", ""))
            est = self._est_windows.get((self._epoch, "full", ""))
            out = {
                "epoch": self._epoch,
                "samples": self._samples_total,
                "window": len(cur) if cur else 0,
                "recall": (round(float(np.mean(cur)), 4)
                           if cur else None),
            }
            if est:
                out["estimator_recall"] = round(float(np.mean(est)), 4)
                if cur:
                    out["calibration_gap"] = round(
                        float(np.mean(cur)) - float(np.mean(est)), 4)
            if self._baseline is not None and cur \
                    and len(cur) >= self.cfg.min_window:
                out["drift"] = round(
                    self._baseline[1] - float(np.mean(cur)), 4)
                out["drift_alarm"] = self._epoch in self._alarmed
            return out

    # -- shadow thread -----------------------------------------------------
    def _loop(self) -> None:
        poll = self.cfg.poll_ms / 1e3
        log = get_logger("obs")
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait(timeout=poll)
                if self._closed and not self._pending:
                    return
                batch = self._pending
                self._pending = []
                self._streamed = 0
                self._inflight = True
            try:
                self._process(batch)
            except Exception as e:
                obs.counter("raft.obs.quality.errors.total").inc()
                log.warning("quality: shadow batch failed (%d samples "
                            "dropped): %r", len(batch), e)
            finally:
                with self._cond:
                    self._inflight = False
                    self._cond.notify_all()

    def _process(self, batch: List[tuple]) -> None:
        rows = np.stack([s[0] for s in batch])
        kmax = max(s[2] for s in batch)
        with spans.span("raft.obs.quality.shadow", family=self.family,
                        queries=len(batch), kmax=kmax):
            exact = np.asarray(self.scorer.topk(rows, kmax))
            est = (np.asarray(self._estimator(rows, kmax))
                   if self._estimator is not None else None)
        obs.counter("raft.obs.quality.shadow.total",
                    family=self.family).inc()
        obs.counter("raft.obs.quality.samples.total").inc(len(batch))
        with self._cond:
            for i, (_q, served, k, epoch, coverage, excl) in \
                    enumerate(batch):
                if epoch > self._epoch:
                    self._roll_epoch_locked(epoch)
                ex = set(int(v) for v in exact[i, :k] if v >= 0)
                r = (len(ex.intersection(int(v) for v in served))
                     / max(1, len(ex) if len(ex) < k else k))
                cov = "full" if coverage >= 1.0 else "partial"
                key = (epoch, cov, excl if cov == "partial" else "")
                self._win(self._windows, key).append(r)
                if est is not None:
                    e_ids = set(int(v) for v in est[i, :k] if v >= 0)
                    self._win(self._est_windows, key).append(
                        len(ex & e_ids)
                        / max(1, len(ex) if len(ex) < k else k))
            self._samples_total += len(batch)
            self._update_gauges_locked()

    def _win(self, table: Dict[tuple, deque], key: tuple) -> deque:
        w = table.get(key)
        if w is None:
            w = table[key] = deque(maxlen=self.cfg.window)
        return w

    def _roll_epoch_locked(self, epoch: int) -> None:
        if epoch <= self._epoch:
            return
        prev = self._windows.get((self._epoch, "full", ""))
        if prev is not None and len(prev) >= self.cfg.min_window:
            # the outgoing epoch's settled window becomes the drift
            # baseline; a short-lived epoch keeps the older baseline
            # (comparing against noise would fire false folds)
            self._baseline = (self._epoch, float(np.mean(prev)))
        self._epoch = epoch
        obs.gauge("raft.obs.quality.drift.alarm",
                  family=self.family).set(0.0)

    def _update_gauges_locked(self) -> None:
        try:
            self._publish_locked()
        except CardinalityError:
            # the epoch label is the only unbounded one; past the
            # registry cap new epoch series are dropped, loudly once
            if not self._card_warned:
                self._card_warned = True
                get_logger("obs").warning(
                    "quality: raft.obs.quality.* label cardinality "
                    "cap hit — raise RAFT_TPU_METRICS_MAX_SERIES or "
                    "restart the monitor; further epoch series are "
                    "dropped")

    def _publish_locked(self) -> None:
        for (epoch, cov, excl), win in self._windows.items():
            if not win:
                continue
            labels = {"family": self.family, "epoch": str(epoch)}
            if cov == "partial":
                labels["coverage"] = "partial"
                if excl:
                    labels["excluded"] = excl
            obs.gauge("raft.obs.quality.recall", **labels).set(
                float(np.mean(win)))
        cur = self._windows.get((self._epoch, "full", ""))
        est = self._est_windows.get((self._epoch, "full", ""))
        if est:
            obs.gauge("raft.obs.quality.estimator.recall",
                      family=self.family,
                      epoch=str(self._epoch)).set(float(np.mean(est)))
            if cur:
                obs.gauge("raft.obs.quality.calibration.gap",
                          family=self.family).set(
                    float(np.mean(cur)) - float(np.mean(est)))
        obs.gauge("raft.obs.quality.window.samples",
                  family=self.family).set(len(cur) if cur else 0)
        if self._baseline is None or not cur \
                or len(cur) < self.cfg.min_window:
            return
        drift = self._baseline[1] - float(np.mean(cur))
        obs.gauge("raft.obs.quality.drift", family=self.family).set(
            drift)
        if drift > self.cfg.drift_budget:
            if self._epoch not in self._alarmed:
                self._alarmed.add(self._epoch)
                obs.counter("raft.obs.quality.drift.total",
                            family=self.family).inc()
                get_logger("obs").warning(
                    "quality: epoch %d recall drifted %.4f below the "
                    "epoch-%d baseline (budget %.4f) — fold degraded "
                    "the index past budget", self._epoch, drift,
                    self._baseline[0], self.cfg.drift_budget)
            obs.gauge("raft.obs.quality.drift.alarm",
                      family=self.family).set(1.0)
        elif self._epoch not in self._alarmed:
            obs.gauge("raft.obs.quality.drift.alarm",
                      family=self.family).set(0.0)
