"""Search routes and plans: one scan route per IVF family.

Every IVF search takes its kernel choice from ONE place. :func:`route`
turns (index shapes, nq, k, params, ``pallas_enabled()``) into a
frozen :class:`Route`: list- or probe-major order, Pallas or XLA, the
fused scan+select kernel or not, the IVF-PQ scan mode, bins, chunk,
lc, gather, rescoring and where the raw corpus lives. The one device
program :func:`_program` runs a route, which it takes as a static
argument. The eager ``ivf_flat.search`` / ``ivf_pq.search`` /
``ivf_bq.search`` call it through the process-wide jit cache
(:func:`run`); :func:`build_plan` lowers and compiles the same
function once, AOT, for the serving layer.

Why plans exist: TPU k-NN serving is dispatch-bound unless the whole
query path is one compiled program that the host merely enqueues
(TPU-KNN, arxiv 2206.14286). A :class:`SearchPlan` is the
serving-shape contract made explicit:

* **AOT compile** — the route's program (coarse GEMM + top-k, probe
  inversion, list scan, merge, metric postprocess, and — when the raw
  corpus is device-resident — the exact re-rank) is lowered and
  compiled ONCE at plan-build time via ``jax.jit(...).lower(...)
  .compile()``, keyed by (nq, dim, route). Serving calls hand the
  executable its buffers; no tracing or shape hashing on the hot path.
* **No host syncs** — :func:`warmup` measures the inverted-table cap
  once from representative queries and prefills the index's
  ``cap_cache``, so ``_ivf_scan.resolve_cap`` never round-trips on the
  serving path (counted by ``raft.ivf_scan.resolve_cap.syncs`` — a
  warmed plan must keep that counter flat, asserted in tests).
* **Async pipelined batching** — :meth:`SearchPlan.search_batched`
  enqueues sub-batches back-to-back (donating the padded query buffers
  it creates on backends that support donation) and performs a single
  terminal ``block_until_ready``.

Plans are cached on the index (``index.plan_cache``; hits/misses/
evictions under ``raft.plan.cache.*``, LRU-bounded by
``RAFT_TPU_PLAN_CACHE_MAX`` — the serving shape ladder churns shapes
routinely). See docs/performance.md for the serving guide.
"""

from __future__ import annotations

import dataclasses
import os
import time
import weakref
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.obs import profiler, spans
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.distance_types import DistanceType


# Compile-surface rung declarations (graftlint GL012–GL014): the plan
# key's non-grid dimensions — each fixed per plan/server/index at
# build time, so the compiled-program count stays a finite product.
COMPILE_SURFACE_RUNGS = {
    "k": ("k", None,
          "result depth — fixed per plan/server at construction"),
    "cap": ("cap", None,
            "inverted-table cap — measured ONCE per (shape, params) "
            "at plan build, then cached (cap_cache)"),
    "kk": ("kk", None,
           "rescore over-fetch depth (rescore_factor * k) — fixed "
           "per plan"),
    "bins": ("bins", None,
             "scan binning — derived from (k, n_probes, list cap) at "
             "build"),
    "scan_bins": ("scan_bins", None,
                  "SearchParams.scan_bins — config, fixed per plan"),
    "slack": ("slack", None,
              "tombstone over-fetch slack — config, fixed per index"),
}


def _plan_cache_max() -> int:
    """LRU bound on ``index.plan_cache`` (``RAFT_TPU_PLAN_CACHE_MAX``,
    default 64 plans; <= 0 disables the bound). Read per call so tests
    and operators can move it at runtime. The serving shape ladder
    (``raft_tpu.serve``) makes (nq, k, n_probes, cap) churn routine —
    an unbounded cache would hold every executable ever compiled."""
    try:
        return int(os.environ.get("RAFT_TPU_PLAN_CACHE_MAX", "64"))
    except ValueError:
        return 64


def _donate_ok() -> bool:
    """Buffer donation is a no-op (with a noisy warning) on CPU; only
    request it where the backend honors it."""
    return jax.default_backend() == "tpu"


@dataclass
class SearchPlan:
    """One AOT-compiled serving program for a fixed (index, nq, k,
    params) operating point. Built by :func:`build_plan` /
    :func:`warmup`; never constructed directly."""

    family: str                 # "ivf_flat" | "ivf_pq" | "ivf_bq"
    key: tuple                  # the plan-cache key (shape identity)
    nq: int
    dim: int
    k: int
    n_probes: int
    cap: int
    metric: DistanceType
    _executable: object = field(repr=False)
    _operands: tuple = field(repr=False)
    # host epilogue (d, i, q) -> (d, i), or None when the compiled
    # program already returns final results (the sync-free case)
    _host_epilogue: Optional[Callable] = field(default=None, repr=False)
    _donate: bool = False

    @property
    def sync_free(self) -> bool:
        """True when a serving call performs zero host round-trips
        (no host rescore epilogue)."""
        return self._host_epilogue is None

    def _run(self, q: jax.Array) -> Tuple[jax.Array, jax.Array]:
        d, i = self._executable(q, *self._operands)
        return self._epilogue(d, i, q)

    def _epilogue(self, d, i, q) -> Tuple[jax.Array, jax.Array]:
        if self._host_epilogue is None:
            return d, i
        with spans.span("raft.plan.host_epilogue"):
            return self._host_epilogue(d, i, q)

    def search(self, queries, block: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
        """Serve one batch of exactly ``plan.nq`` queries → (dists,
        ids), both (nq, k). The call only enqueues (async dispatch)
        unless ``block``; donation-compiled plans consume the query
        buffer, so a defensive device copy is made when the caller's
        array would otherwise be invalidated."""
        # resource profiler admission (one None read when off): a
        # sampled BLOCKING call is split into host work (everything up
        # to enqueue-complete, conversions and spans included) vs the
        # device wait — around the sync it was paying anyway
        prof = block and profiler.sampled()
        t_call = time.perf_counter()
        with spans.span("raft.plan.search", family=self.family,
                        nq=self.nq, k=self.k, n_probes=self.n_probes,
                        cap=self.cap, sync_free=self.sync_free,
                        blocked=block) as sp:
            # the host→device copy of the queries and the enqueue of
            # the compiled program (async dispatch: returns before the
            # device is done)
            with spans.span("raft.plan.enqueue"):
                q = as_array(queries).astype(jnp.float32)
                expects(q.shape == (self.nq, self.dim),
                        "plan.search: queries %s != plan shape (%d, %d)"
                        " — build a plan per serving batch shape",
                        q.shape, self.nq, self.dim)
                obs.counter("raft.plan.search.total").inc()
                obs.counter("raft.plan.search.queries").inc(self.nq)
                if self._donate and isinstance(queries, jax.Array):
                    q = jnp.array(q, copy=True)  # caller keeps theirs
                d, i = self._executable(q, *self._operands)
            d, i = self._epilogue(d, i, q)
            t_enq = t_ready = 0.0
            if block:
                if prof:
                    t_enq = time.perf_counter()
                with spans.span("raft.plan.device_wait"):
                    jax.block_until_ready((d, i))
                if prof:
                    t_ready = time.perf_counter()
                    spans.add_child_span(
                        profiler.SYNC_SPAN, t_enq, t_ready - t_enq,
                        program="plan",
                        host_ms=round((t_enq - t_call) * 1e3, 3),
                        device_ms=round((t_ready - t_enq) * 1e3, 3))
            sp.set_attr("plan_key", repr(self.key))
        if prof and block:
            # the span/trace epilogue above is host work too: charge
            # everything outside the device wait to the host half, so
            # host_s + device_s ≈ this call's whole wall
            profiler.record_sample(
                program="plan", family=self.family, rung=self.n_probes,
                host_s=(t_enq - t_call)
                + (time.perf_counter() - t_ready),
                device_s=t_ready - t_enq)
        return d, i

    def search_batched(self, queries, block: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
        """Serve an arbitrary query count through the plan's compiled
        shape: sub-batches are enqueued back-to-back with NO host sync
        between them (the padded tail buffer is plan-owned, so
        donation is always safe), then concatenated and — by default —
        synced once at the end (the single terminal barrier of the
        issue contract)."""
        from raft_tpu.neighbors.ann_types import batched_search
        q = as_array(queries).astype(jnp.float32)
        expects(q.shape[1] == self.dim, "plan.search_batched: dim "
                "mismatch (%d != %d)", q.shape[1], self.dim)
        if q.shape[0] == self.nq:
            # exact plan shape: route through search(), whose
            # defensive copy protects the caller's buffer from a
            # donation-compiled executable
            return self.search(queries, block=block)
        obs.counter("raft.plan.search.queries").inc(q.shape[0])
        # root span for the whole request; batched_search opens one
        # child span per enqueued sub-batch under it
        with spans.span("raft.plan.search_batched", family=self.family,
                        nq=int(q.shape[0]), k=self.k,
                        n_probes=self.n_probes, cap=self.cap,
                        plan_nq=self.nq, blocked=block):
            d, i = batched_search(self._run, q, max_batch=self.nq,
                                  pad_partial=True)
            if block:
                jax.block_until_ready((d, i))
        return d, i


# ---------------------------------------------------------------------------
# the route: every decision that shapes a family's device program
# ---------------------------------------------------------------------------

_SQRT_METRICS = (DistanceType.L2SqrtExpanded,
                 DistanceType.L2SqrtUnexpanded)


@dataclass(frozen=True)
class Route:
    """One IVF search's kernel choice — hashable, so it is the static
    argument of :func:`_program` and the identity of a plan."""

    family: str              # "ivf_flat" | "ivf_pq" | "ivf_bq"
    metric: DistanceType
    kind: str                # "l2" | "ip" scoring core
    n_probes: int
    k: int
    kk: int                  # candidates the scan returns (rescore·k)
    order: str               # "list" (inverted table) | "probe"
    pallas: bool             # Pallas coarse select_k + scan, else XLA
    fused: bool              # scan+select kernel, top-k resident in VMEM
    scan_mode: str = ""      # ivf_pq: "codes" | "reconstruct" | "lut"
    bins: int = 0
    chunk: int = 0           # ivf_bq XLA decode tile, lists per step
    lc: int = 0              # Pallas lists per grid cell, 0 = auto
    gather: str = "rows"
    dev_sqrt: bool = False   # the scan itself takes the sqrt
    rescoring: bool = False
    raw_on_device: bool = False
    per_cluster: bool = False
    lut_dtype: Optional[str] = None       # dtype names: a cheap repr
    internal_dtype: Optional[str] = None  # in every plan's span
    cap: int = 0             # inverted-table width (list order only)

    @property
    def host_tail(self) -> bool:
        """The exact rescore runs on the host (raw corpus off-device):
        the program returns the estimator candidates."""
        return self.rescoring and not self.raw_on_device


def route(index, nq: int, k: int, params) -> Route:
    """The route of ``nq`` queries at ``k`` — its cap still 0 (see
    :func:`resolve_cap`). Rejects the parameters no route serves."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors._ivf_scan import gather_mode
    from raft_tpu.neighbors.ann_types import list_order_auto
    from raft_tpu.neighbors.ivf_bq import resolve_raw_device
    from raft_tpu.neighbors.ivf_flat import _metric_kind
    from raft_tpu.ops.dispatch import pallas_enabled
    from raft_tpu.ops.pallas_ivf_scan import lc_mode

    family, _ = _resolve_builder(index)
    order = getattr(params, "scan_order", "auto")
    expects(order in ("auto", "probe", "list"),
            "%s.search: unknown scan_order %r", family, order)
    factor = getattr(params, "rescore_factor", 0)
    expects(factor >= 0, "%s.search: rescore_factor must be >= 0, got %d",
            family, factor)
    on_device = getattr(params, "rescore_on_device", "auto")
    expects(on_device in ("auto", "always", "never"),
            "%s.search: rescore_on_device: want auto|always|never, got %r",
            family, on_device)
    # a negative value would bypass the auto-bins rule and fail deep in
    # the scan with an opaque reshape error
    expects(family != "ivf_bq" or params.scan_bins >= 0,
            "ivf_bq.search: scan_bins must be >= 0 (0 = auto), got %d",
            params.scan_bins)
    n_probes = min(params.n_probes, index.n_lists)
    kind = _metric_kind(index.metric)
    pallas = pallas_enabled()
    mode, lut_dtype, per_cluster = "", None, False
    if family == "ivf_pq":
        mode = params.scan_mode
        expects(mode in ("auto", "codes", "reconstruct", "lut"),
                "ivf_pq.search: unknown scan_mode %r", mode)
        if mode == "auto":
            mode = "codes" if pallas else "reconstruct"
        lut_dtype = jnp.dtype(params.lut_dtype).name
        expects(lut_dtype in ("float32", "bfloat16", "float8_e4m3fn"),
                "ivf_pq: lut_dtype must be float32|bfloat16|float8_e4m3fn")
        # the fp8 tier only exists on the code-resident scan: reject
        # rather than silently measure the full-precision paths
        expects(lut_dtype != "float8_e4m3fn" or mode == "codes",
                "ivf_pq: lut_dtype=float8_e4m3fn requires scan_mode="
                "'codes' (resolved scan_mode is %r)", mode)
        pallas = mode == "codes"
        per_cluster = index.codebook_kind == ivf_pq.CodebookGen.PER_CLUSTER

    if family == "ivf_bq" or mode == "codes":
        order = "list"
    elif mode == "lut":
        order = "probe"
    else:
        # the XLA list scans have the l2 core only
        listable = kind == "l2" or pallas
        order = ("list" if listable and (order == "list" or (
            order == "auto" and list_order_auto(nq, n_probes,
                                                index.n_lists)))
                 else "probe")
        pallas = pallas and order == "list"

    rescoring = factor > 0 and getattr(index, "raw", None) is not None
    kk = max(factor, 1) * k
    max_list = index.lists_indices.shape[1]
    bins = params.scan_bins if order == "list" else 0
    if bins == 0 and order == "list" and family != "ivf_flat" \
            and (kk > k or family == "ivf_bq"):
        # a 32x-oversampled GLOBAL candidate pool (n_probes·bins ≈
        # 32·kk, floor 128/list) instead of the per-list 4·k rule:
        # scaling bins with kk would blow the merge width (64 probes ×
        # 4·256 bins = 32k-wide select) and the candidate blocks (~0.5
        # GB at the 500k bench point). Safe because bins are STRIDED
        # in every tier — measured 0.920 vs the contiguous
        # formulation's 0.868 recall@10 at 30k×64/128-list
        bins = min(max(128, (32 * kk) // max(n_probes, 1)), max_list)
    elif family == "ivf_bq":
        bins = min(bins, max_list)
    idt = getattr(params, "internal_distance_dtype", None)
    return Route(
        family=family, metric=index.metric, kind=kind, n_probes=n_probes,
        k=k, kk=kk, order=order, pallas=pallas,
        # the resident state keeps kk on sublanes: past the select_k
        # bound the unfused kernels serve
        fused=pallas and kk <= 256, scan_mode=mode, bins=bins,
        lc=lc_mode() if pallas and family != "ivf_pq" else 0,
        gather=gather_mode() if pallas else "rows",
        dev_sqrt=(index.metric in _SQRT_METRICS and kk == k
                  and not rescoring and family != "ivf_bq"),
        rescoring=rescoring,
        raw_on_device=rescoring and resolve_raw_device(
            index, on_device) is not None,
        per_cluster=per_cluster, lut_dtype=lut_dtype,
        internal_dtype=None if idt is None else jnp.dtype(idt).name)


# host read of the PQ list extents behind the decoded-share gauge,
# memoized for the last (lists, route) so a run of same-route eager
# searches stays free of host syncs
_LAST_SHARE: list = [None, None, 0.0]


def resolve_cap(r: Route, index, q, params) -> Route:
    """``r`` with its inverted-table cap (list order: the one
    measurement sync of a cold shape, then ``index.cap_cache``) and,
    for the IVF-BQ XLA scan, its decode chunk; notes the route's
    counters. Both the eager search and the plan build land here."""
    from raft_tpu.neighbors import _ivf_scan
    from raft_tpu.ops.pallas_ivf_scan import pq_decoded_share, ragged_tail
    if r.order == "list":
        cap = _ivf_scan.resolve_cap(index.cap_cache, q, index.centers,
                                    params, r.n_probes, index.n_lists,
                                    kind=r.kind, use_pallas=r.pallas)
        chunk = 0
        if r.family == "ivf_bq" and not r.pallas:
            # both the (chunk, cap, max_list) estimator block and the
            # (chunk, max_list, dim) decode tile stay modest
            max_list = index.lists_indices.shape[1]
            chunk = min(
                _ivf_scan._chunk_size(index.n_lists, cap, max_list),
                _ivf_scan.largest_divisor_at_most(
                    index.n_lists,
                    max(1, (64 << 20) // max(1, max_list * index.dim * 2))))
        r = dataclasses.replace(r, cap=cap, chunk=chunk)
    _ivf_scan.count_coarse_fallback(r.n_probes, r.pallas)
    if r.fused:
        obs.counter("raft.ivf_scan.fused.total", family=r.family).inc()
    # the Pallas list scan reads the lists unpadded and completes a
    # partial last bins window in VMEM
    if r.family == "ivf_flat" and r.pallas and ragged_tail(
            index.lists_data.shape[1], r.bins, r.k):
        obs.counter("raft.ivf_scan.ragged_tail.total",
                    family="ivf_flat").inc()
    if r.scan_mode == "codes":
        # the code scan decodes each list only up to its last row
        lists, last, share = _LAST_SHARE
        if lists is None or lists() is not index.lists_indices \
                or last != r:
            share = pq_decoded_share(
                index.lists_indices, r.kk, r.cap, r.bins, index.pq_dim,
                index.pq_centers.shape[1], index.rot_dim, r.lut_dtype)
            _LAST_SHARE[:] = [weakref.ref(index.lists_indices), r, share]
        obs.gauge("raft.ivf_scan.pq.decoded_share").set(share)
    return r


def _operands(r: Route, index) -> tuple:
    """The index arrays :func:`_program` takes after the queries,
    materializing the route's lazy caches (code norms matched to the
    LUT tier, the reconstruction cache, the device raw corpus)."""
    from raft_tpu.neighbors import ivf_pq
    if r.family == "ivf_flat":
        ops = (index.centers, index.lists_data, index.lists_norms,
               index.lists_indices, jnp.float32(index.scale))
    elif r.family == "ivf_bq":
        ops = (index.centers, index.centers_rot, index.rotation_matrix,
               index.bits, index.norms2, index.scales, index.lists_indices)
    elif r.scan_mode == "reconstruct":
        ivf_pq._ensure_decoded(index, r.per_cluster)
        ops = (index.centers, index.centers_rot, index.rotation_matrix,
               index.decoded, index.decoded_norms, index.lists_indices)
    else:
        norms = ((ivf_pq._ensure_code_norms(index, r.lut_dtype, r.kind),)
                 if r.scan_mode == "codes" else ())
        ops = (index.centers, index.centers_rot, index.rotation_matrix,
               index.pq_centers, index.codes, *norms, index.lists_indices)
    return ops + ((index.raw_dev,) if r.raw_on_device else ())


def _flat_scan(r: Route, q, centers, data, norms, ids, scale):
    from raft_tpu.neighbors import _ivf_scan, ivf_flat
    if r.order == "probe":
        return ivf_flat._search_impl(q, centers, data, ids, norms, scale,
                                     r.k, r.n_probes, r.dev_sqrt,
                                     kind=r.kind)
    return _ivf_scan.fused_list_search(
        q, centers, data, norms, ids, scale, k=r.k, n_probes=r.n_probes,
        cap=r.cap, bins=r.bins, sqrt=r.dev_sqrt, kind=r.kind,
        use_pallas=r.pallas, gather=r.gather,
        internal_dtype=r.internal_dtype, lc=r.lc, fused=r.fused)


def _pq_scan(r: Route, q, centers, centers_rot, rot, *ops):
    from raft_tpu.neighbors import _ivf_scan, ivf_pq
    if r.scan_mode == "codes":
        return ivf_pq._fused_code_search(
            q, centers, centers_rot, rot, *ops, k=r.kk,
            n_probes=r.n_probes, cap=r.cap, bins=r.bins, sqrt=r.dev_sqrt,
            kind=r.kind, lut_dtype=r.lut_dtype,
            internal_dtype=r.internal_dtype, per_cluster=r.per_cluster,
            gather=r.gather, fused=r.fused)
    if r.scan_mode == "lut":
        return ivf_pq._search_impl(q, centers, centers_rot, rot, *ops,
                                   r.kk, r.n_probes, r.dev_sqrt,
                                   kind=r.kind, per_cluster=r.per_cluster)
    if r.order == "list":
        # lists hold decoded rotated residuals: the scan offsets each
        # list's queries by its rotated center
        return _ivf_scan.fused_reconstruct_list_search(
            q, centers, centers_rot, rot, *ops, k=r.kk,
            n_probes=r.n_probes, cap=r.cap, bins=r.bins, sqrt=r.dev_sqrt)
    return ivf_pq._search_impl_reconstruct(
        q, centers, centers_rot, rot, *ops, r.kk, r.n_probes, r.dev_sqrt,
        kind=r.kind)


def _bq_scan(r: Route, q, centers, centers_rot, rot, bits, norms2,
             scales, ids):
    from raft_tpu.neighbors import ivf_bq
    if r.pallas:
        return ivf_bq._fused_bq_search_pallas(
            q, centers, centers_rot, rot, bits, norms2, scales, ids,
            kk=r.kk, bins=r.bins, n_probes=r.n_probes, cap=r.cap,
            gather=r.gather, kind=r.kind, lc=r.lc, fused=r.fused)
    return ivf_bq._fused_bq_search(
        q, centers, centers_rot, rot, bits, norms2, scales, ids, kk=r.kk,
        bins=r.bins, n_probes=r.n_probes, cap=r.cap, chunk=r.chunk,
        dim=q.shape[1], kind=r.kind)


_SCANS = {"ivf_flat": _flat_scan, "ivf_pq": _pq_scan, "ivf_bq": _bq_scan}


def _program(q, *ops, r: Route):
    """The device program of route ``r``: (queries, *index arrays) →
    final (dists, ids) — or the kk estimator candidates when the exact
    rescore runs on the host (``r.host_tail``)."""
    from raft_tpu.neighbors.ivf_bq import _exact_rescore_device
    from raft_tpu.neighbors.ivf_flat import _postprocess
    if r.metric == DistanceType.CosineExpanded:
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True),
                            1e-30)
    if r.raw_on_device:
        *ops, raw = ops
    d, i = _SCANS[r.family](r, q, *ops)
    if r.host_tail:
        return d, i
    if r.raw_on_device or r.kk > r.k:
        with jax.named_scope("raft.plan.rescore"):
            if r.raw_on_device:
                ex, i = _exact_rescore_device(raw, q, i, k=r.k,
                                              kind=r.kind)
                i = jnp.where(jnp.isfinite(ex), i, -1)
                d = jnp.where(jnp.isfinite(ex), ex, jnp.inf)
            else:
                d, i = d[:, :r.k], i[:, :r.k]
    with jax.named_scope("raft.plan.postprocess"):
        if r.metric in _SQRT_METRICS and not r.dev_sqrt:
            d = jnp.sqrt(jnp.maximum(d, 0.0))
        return _postprocess(d, r.metric), i


_PROGRAM = jax.jit(_program, static_argnames=("r",))
# the plan's copy: it consumes the query buffer (a no-op, with a noisy
# warning, where the backend does not donate — see _donate_ok)
_DONATING = jax.jit(_program, static_argnames=("r",), donate_argnums=(0,))


def _host_epilogue(r: Route, index) -> Optional[Callable]:
    """The exact rescore of a ``host_tail`` route against the host raw
    corpus (``ivf_bq.finish_search``), else None."""
    if not r.host_tail:
        return None

    def rescore(d, i, q):
        from raft_tpu.neighbors.ivf_bq import finish_search
        if r.metric == DistanceType.CosineExpanded:
            q = q / jnp.maximum(
                jnp.linalg.norm(q, axis=1, keepdims=True), 1e-30)
        return finish_search(d, i, index.raw, q, r.k, metric=r.metric,
                             rescore=True, raw_dev=None)

    return rescore


def run(index, q, r: Route, params) -> Tuple[jax.Array, jax.Array]:
    """The eager search along ``r``: resolve the cap, run the program
    through the jit cache, then the host rescore if the route has
    one."""
    r = resolve_cap(r, index, q, params)
    if r.fused:
        obs.counter("raft.ivf_scan.fused.queries").inc(q.shape[0])
    d, i = _PROGRAM(q, *_operands(r, index), r=r)
    epilogue = _host_epilogue(r, index)
    return (d, i) if epilogue is None else epilogue(d, i, q)


def _resolve_builder(index):
    """(family name, device program) of an IVF index."""
    from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
    for cls, name in ((ivf_flat.Index, "ivf_flat"), (ivf_pq.Index, "ivf_pq"),
                      (ivf_bq.Index, "ivf_bq")):
        if isinstance(index, cls):
            return name, _program
    expects(False, "plan: unsupported index type %s (want ivf_flat/"
            "ivf_pq/ivf_bq Index)", type(index).__name__)


def _default_params(family: str):
    from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
    return {"ivf_flat": ivf_flat.SearchParams,
            "ivf_pq": ivf_pq.SearchParams,
            "ivf_bq": ivf_bq.SearchParams}[family]()


def build_plan(index, queries, k: int, params=None,
               warm: bool = True) -> SearchPlan:
    """Build (or fetch from ``index.plan_cache``) the AOT-compiled
    serving plan for this (index, nq, k, params) point.

    ``queries`` — a REPRESENTATIVE batch (real shape AND distribution:
    the inverted-table cap is measured from it, exactly like the eager
    search's first call). One host sync happens here, never on the
    serving path. With ``warm`` the compiled program is also executed
    once on the sample batch so device-side warmup (e.g. kernel
    autotuning) is off the serving path too.
    """
    family, _ = _resolve_builder(index)
    if params is None:
        params = _default_params(family)
    q = as_array(queries).astype(jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "plan: queries must be (nq, dim=%d), got %s", index.dim,
            q.shape)
    nq = q.shape[0]
    r = route(index, nq, k, params)
    with spans.span("raft.plan.build", family=family, nq=nq,
                    k=k) as bsp, \
            obs.timed("raft.plan.build", family=family):
        # the ONE measurement round-trip of the plan lifecycle: also
        # prefills index.cap_cache so the eager search (ivf_flat.search
        # et al.) is sync-free at this shape from now on
        r = resolve_cap(r, index, q, params)
        bsp.set_attrs(cap=r.cap, n_probes=r.n_probes)
        key = (nq, index.dim) + dataclasses.astuple(r)
        cached = index.plan_cache.pop(key, None)
        if cached is not None:
            # re-insert at the MRU end: the plain insertion-ordered dict
            # doubles as the LRU order
            index.plan_cache[key] = cached
            obs.counter("raft.plan.cache.hits").inc()
            bsp.set_attr("plan_cache", "hit")
            return cached
        obs.counter("raft.plan.cache.misses").inc()
        obs.counter("raft.plan.build.total").inc()
        bsp.set_attr("plan_cache", "miss")
        donate = _donate_ok()
        operands = _operands(r, index)
        q_struct = jax.ShapeDtypeStruct((nq, index.dim), jnp.float32)
        t_c0 = time.perf_counter()
        executable = (_DONATING if donate else _PROGRAM).lower(
            q_struct, *operands, r=r).compile()
        # compile-time ledger (resource profiler): the seconds the
        # chip sat idle while the host built this program
        profiler.note_compile("plan", time.perf_counter() - t_c0)
        plan = SearchPlan(family=family, key=key, nq=nq, dim=index.dim,
                          k=k, n_probes=r.n_probes, cap=r.cap,
                          metric=index.metric, _executable=executable,
                          _operands=operands,
                          _host_epilogue=_host_epilogue(r, index),
                          _donate=donate)
        index.plan_cache[key] = plan
        cache_max = _plan_cache_max()
        if cache_max > 0:
            while len(index.plan_cache) > cache_max:
                index.plan_cache.pop(next(iter(index.plan_cache)))
                obs.counter("raft.plan.cache.evictions").inc()
    if warm:
        plan.search(q, block=True)
    return plan


def warmup(index, queries, k: int, params=None) -> SearchPlan:
    """Serving warmup: measure the cap, AOT-compile the plan, run it
    once — after this, same-shape serving calls (plan.search OR the
    family's own ``search``) perform zero measurement syncs. Alias of
    ``build_plan(..., warm=True)`` under the name the serving guide
    uses."""
    return build_plan(index, queries, k, params, warm=True)


def cached_plans(index) -> dict:
    """The index's plan cache (key → SearchPlan) — introspection."""
    return dict(index.plan_cache)
