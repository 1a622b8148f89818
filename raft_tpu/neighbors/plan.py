"""Search plans: AOT-compiled, host-sync-free IVF serving.

Why this layer exists: the last green TPU window measured IVF-Flat at
9,769 QPS end-to-end against a 73,781 QPS chained marginal — a ~9 ms
per-batch FIXED cost (host dispatch, cap measurement, tier routing,
Python glue) swallowed 87% of the speedup the index should buy.
TPU-KNN (arxiv 2206.14286) and the serving-kernel literature agree:
TPU k-NN serving is dispatch-bound unless the whole query path is one
compiled program that the host merely enqueues.

A :class:`SearchPlan` is the serving-shape contract made explicit:

* **AOT compile** — the full fused search (coarse GEMM + top-k, probe
  inversion, list scan, merge, metric postprocess, and — when the raw
  corpus is device-resident — the exact re-rank) is lowered and
  compiled ONCE at plan-build time via ``jax.jit(...).lower(...)
  .compile()``, keyed by (index shapes, nq, k, n_probes, cap, dtypes).
  Serving calls hand the executable its buffers; no tracing, no tier
  ladder, no shape hashing on the hot path.
* **No host syncs** — :func:`warmup` measures the inverted-table cap
  once from representative queries and prefills the index's
  ``cap_cache``, so ``_ivf_scan.resolve_cap`` never round-trips on the
  serving path (counted by ``raft.ivf_scan.resolve_cap.syncs`` — a
  warmed plan must keep that counter flat, asserted in tests).
* **Async pipelined batching** — :meth:`SearchPlan.search_batched`
  enqueues sub-batches back-to-back (donating the padded query buffers
  it creates on backends that support donation) and performs a single
  terminal ``block_until_ready``; the dispatch-sync-dispatch loop of
  the cold path disappears.

Plans are cached on the index (``index.plan_cache``; hits/misses/
evictions under ``raft.plan.cache.*``, LRU-bounded by
``RAFT_TPU_PLAN_CACHE_MAX`` — the serving shape ladder churns shapes
routinely). The cold path — ``ivf_flat.search`` etc. — is
unchanged and remains the flexible/debug entry; see
docs/performance.md for the serving guide.
"""

from __future__ import annotations

import os
import time
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
# family builders: each returns (fn, operands, host_epilogue) where
# ``fn(q, *operands) -> (d, i)`` is the pure jittable serving program
# ---------------------------------------------------------------------------


def _flat_builder(index, k: int, params):
    from raft_tpu.neighbors import _ivf_scan
    from raft_tpu.neighbors.ann_types import list_order_auto
    from raft_tpu.neighbors.ivf_flat import (_metric_kind, _postprocess,
                                             _search_impl)
    from raft_tpu.ops.dispatch import pallas_enabled
    from raft_tpu.ops.pallas_ivf_scan import fused_mode, lc_mode, ragged_tail

    n_probes = min(params.n_probes, index.n_lists)
    kind = _metric_kind(index.metric)
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    use_pallas = pallas_enabled()

    def make(nq: int, cap: int):
        use_list = ((use_pallas or kind == "l2")
                    and (params.scan_order == "list"
                         or (params.scan_order == "auto"
                             and list_order_auto(nq, n_probes,
                                                 index.n_lists))))
        gather = _ivf_scan.gather_mode()
        lc = lc_mode()
        # fused scan+select tier (ISSUE 7): the plan compiles the ONE-
        # pallas_call fine phase — zero new steady-state compiles, the
        # ladder machinery rides the same build_plan path unchanged
        use_fused = use_pallas and fused_mode() and k <= 256
        if use_list and use_fused:
            obs.counter("raft.ivf_scan.fused.total",
                        family="ivf_flat").inc()
        # the Pallas list scan reads the lists unpadded and completes a
        # partial last bins window in VMEM
        if use_list and use_pallas and ragged_tail(
                index.lists_data.shape[1], params.scan_bins, k):
            obs.counter("raft.ivf_scan.ragged_tail.total",
                        family="ivf_flat").inc()

        def fn(q, centers, data, norms, ids, scale):
            if index.metric == DistanceType.CosineExpanded:
                q = q / jnp.maximum(
                    jnp.linalg.norm(q, axis=1, keepdims=True), 1e-30)
            if use_list:
                d, i = _ivf_scan.fused_list_search(
                    q, centers, data, norms, ids, scale, k=k,
                    n_probes=n_probes, cap=cap, bins=params.scan_bins,
                    sqrt=sqrt, kind=kind, use_pallas=use_pallas,
                    gather=gather,
                    internal_dtype=params.internal_distance_dtype,
                    lc=lc, fused=use_fused)
            else:
                d, i = _search_impl(q, centers, data, ids, norms, scale,
                                    k, n_probes, sqrt, kind=kind)
            with jax.named_scope("raft.plan.postprocess"):
                return _postprocess(d, index.metric), i

        operands = (index.centers, index.lists_data, index.lists_norms,
                    index.lists_indices, jnp.float32(index.scale))
        key_bits = (use_list, use_pallas, use_fused, gather, lc,
                    params.scan_bins,
                    jnp.dtype(params.internal_distance_dtype).name,
                    index.lists_data.dtype.name)
        return fn, operands, None, key_bits

    return make, n_probes, kind, use_pallas


def _pq_builder(index, k: int, params):
    from raft_tpu.neighbors import _ivf_scan, ivf_pq
    from raft_tpu.neighbors.ann_types import list_order_auto
    from raft_tpu.neighbors.ivf_bq import (finish_search,
                                           resolve_raw_device)
    from raft_tpu.neighbors.ivf_flat import _metric_kind, _postprocess
    from raft_tpu.ops.dispatch import pallas_enabled

    n_probes = min(params.n_probes, index.n_lists)
    kind = _metric_kind(index.metric)
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    per_cluster = index.codebook_kind == ivf_pq.CodebookGen.PER_CLUSTER
    use_pallas = pallas_enabled()
    scan_mode = params.scan_mode
    if scan_mode == "auto":
        scan_mode = "codes" if use_pallas else "reconstruct"
    rescoring = params.rescore_factor > 0 and index.raw is not None
    kk = max(params.rescore_factor, 1) * k
    dev_sqrt = sqrt if (kk == k and not rescoring) else False
    bins = params.scan_bins
    if bins == 0 and kk > k:
        max_list = index.codes.shape[1]
        bins = min(max(128, (32 * kk) // max(n_probes, 1)), max_list)
    raw_dev = (resolve_raw_device(index, params.rescore_on_device)
               if rescoring else None)

    def _device_epilogue(d, i, q, raw):
        """In-jit tail: exact device rescore (when the raw corpus is
        device-resident) or the estimator slice, then the family output
        conventions — mirrors ivf_bq.finish_search's device branch.
        The sqrt applies only when the device phase didn't already
        (``dev_sqrt``: the kk == k no-rescore case sqrt's in-scan)."""
        from raft_tpu.neighbors.ivf_bq import _exact_rescore_device
        with jax.named_scope("raft.plan.rescore"):
            if raw is not None:
                ex, i_out = _exact_rescore_device(raw, q, i, k=k,
                                                  kind=kind)
                i_out = jnp.where(jnp.isfinite(ex), i_out, -1)
                d = jnp.where(jnp.isfinite(ex), ex, jnp.inf)
            else:
                d, i_out = d[:, :k], i[:, :k]
        with jax.named_scope("raft.plan.postprocess"):
            if sqrt and not dev_sqrt:
                d = jnp.sqrt(jnp.maximum(d, 0.0))
            return _postprocess(d, index.metric), i_out

    def make(nq: int, cap: int):
        host_epilogue = None
        if scan_mode == "codes":
            from raft_tpu.ops.pallas_ivf_scan import (fused_mode,
                                                      pq_decoded_share)
            code_norms = ivf_pq._ensure_code_norms(index, params,
                                                   per_cluster, kind)
            gather = _ivf_scan.gather_mode()
            use_fused = fused_mode() and kk <= 256
            if use_fused:
                obs.counter("raft.ivf_scan.fused.total",
                            family="ivf_pq").inc()
            # the code scan decodes each list only up to its last row
            obs.gauge("raft.ivf_scan.pq.decoded_share").set(
                pq_decoded_share(index.lists_indices, kk, cap, bins,
                                 index.pq_dim, index.pq_centers.shape[1],
                                 index.rot_dim, params.lut_dtype))

            def device_phase(q, centers, centers_rot, rot, books, codes,
                             norms, ids):
                return ivf_pq._fused_code_search(
                    q, centers, centers_rot, rot, books, codes, norms,
                    ids, k=kk, n_probes=n_probes, cap=cap, bins=bins,
                    sqrt=dev_sqrt, kind=kind,
                    lut_dtype=params.lut_dtype,
                    internal_dtype=params.internal_distance_dtype,
                    per_cluster=per_cluster, gather=gather,
                    fused=use_fused)

            operands = [index.centers, index.centers_rot,
                        index.rotation_matrix, index.pq_centers,
                        index.codes, code_norms, index.lists_indices]
            key_bits = ("codes", gather, use_fused,
                        jnp.dtype(params.lut_dtype).name,
                        jnp.dtype(params.internal_distance_dtype).name,
                        bins, kk, rescoring, raw_dev is not None)
        else:
            expects(scan_mode == "reconstruct",
                    "plan: ivf_pq scan_mode %r has no serving plan "
                    "(use 'auto', 'codes' or 'reconstruct')", scan_mode)
            ivf_pq._ensure_decoded(index, per_cluster)
            use_list = (kind == "l2"
                        and (params.scan_order == "list"
                             or (params.scan_order == "auto"
                                 and list_order_auto(nq, n_probes,
                                                     index.n_lists))))

            def device_phase(q, centers, centers_rot, rot, decoded,
                             decoded_norms, ids):
                if use_list:
                    return _ivf_scan.fused_reconstruct_list_search(
                        q, centers, centers_rot, rot, decoded,
                        decoded_norms, ids, k=kk, n_probes=n_probes,
                        cap=cap, bins=bins, sqrt=dev_sqrt)
                return ivf_pq._search_impl_reconstruct(
                    q, centers, centers_rot, rot, decoded,
                    decoded_norms, ids, kk, n_probes, dev_sqrt,
                    kind=kind)

            operands = [index.centers, index.centers_rot,
                        index.rotation_matrix, index.decoded,
                        index.decoded_norms, index.lists_indices]
            key_bits = ("reconstruct", use_list, bins, kk, rescoring,
                        raw_dev is not None)

        if rescoring and raw_dev is None:
            # raw corpus exceeds the device budget: the exact re-rank
            # runs host-side per batch — correct, but NOT sync-free
            def host_epilogue(d, i, q):
                return finish_search(d, i, index.raw, q, k,
                                     metric=index.metric, rescore=True,
                                     raw_dev=None)

            fn_tail = None
        else:
            fn_tail = raw_dev

        def fn(q, *ops):
            if fn_tail is not None:
                *core, raw = ops
            else:
                core, raw = ops, None
            d, i = device_phase(q, *core)
            if host_epilogue is not None:
                return d, i   # estimator phase only; host tail follows
            return _device_epilogue(d, i, q, raw)

        if fn_tail is not None:
            operands.append(fn_tail)
        return fn, tuple(operands), host_epilogue, key_bits

    return make, n_probes, kind, (use_pallas and scan_mode == "codes")


def _bq_builder(index, k: int, params):
    from raft_tpu.neighbors import _ivf_scan, ivf_bq
    from raft_tpu.neighbors._ivf_scan import (_chunk_size,
                                              largest_divisor_at_most)
    from raft_tpu.neighbors.ivf_flat import _metric_kind
    from raft_tpu.ops.dispatch import pallas_enabled
    from raft_tpu.ops.pallas_ivf_scan import lc_mode

    n_probes = min(params.n_probes, index.n_lists)
    kind = _metric_kind(index.metric)
    use_pallas = pallas_enabled()
    rescoring = params.rescore_factor > 0 and index.raw is not None
    kk = max(params.rescore_factor, 1) * k
    max_list = index.bits.shape[1]
    raw_dev = (ivf_bq.resolve_raw_device(index, params.rescore_on_device)
               if rescoring else None)

    def make(nq: int, cap: int):
        from raft_tpu.ops.pallas_ivf_scan import fused_mode
        bins = min(params.scan_bins
                   or max(128, (32 * kk) // max(n_probes, 1)), max_list)
        chunk = min(
            _chunk_size(index.n_lists, cap, max_list),
            largest_divisor_at_most(
                index.n_lists,
                max(1, (64 << 20) // max(1, max_list * index.dim * 2))))
        gather = _ivf_scan.gather_mode()
        lc = lc_mode()
        use_fused = use_pallas and fused_mode() and kk <= 256
        if use_fused:
            obs.counter("raft.ivf_scan.fused.total",
                        family="ivf_bq").inc()

        def device_phase(q, centers, centers_rot, rot, bits, norms2,
                         scales, ids):
            if use_pallas:
                return ivf_bq._fused_bq_search_pallas(
                    q, centers, centers_rot, rot, bits, norms2, scales,
                    ids, kk=kk, bins=bins, n_probes=n_probes, cap=cap,
                    gather=gather, kind=kind, lc=lc, fused=use_fused)
            return ivf_bq._fused_bq_search(
                q, centers, centers_rot, rot, bits, norms2, scales,
                ids, kk=kk, bins=bins, n_probes=n_probes, cap=cap,
                chunk=chunk, dim=index.dim, kind=kind)

        operands = [index.centers, index.centers_rot,
                    index.rotation_matrix, index.bits, index.norms2,
                    index.scales, index.lists_indices]
        host_epilogue = None
        if rescoring and raw_dev is None:
            def host_epilogue(d, i, q):
                return ivf_bq.finish_search(d, i, index.raw, q, k,
                                            metric=index.metric,
                                            rescore=True, raw_dev=None)

        def fn(q, *ops):
            if index.metric == DistanceType.CosineExpanded:
                q = q / jnp.maximum(
                    jnp.linalg.norm(q, axis=1, keepdims=True), 1e-30)
            if raw_dev is not None:
                *core, raw = ops
            else:
                core, raw = ops, None
            d, i = device_phase(q, *core)
            if host_epilogue is not None:
                return d, i
            return _bq_device_tail(d, i, q, raw, index.metric, k, kind,
                                   rescoring)

        if raw_dev is not None:
            operands.append(raw_dev)
        key_bits = (use_pallas, use_fused, gather, lc, bins, chunk, kk,
                    rescoring, raw_dev is not None)
        return fn, tuple(operands), host_epilogue, key_bits

    return make, n_probes, kind, use_pallas


def _bq_device_tail(d, i, q, raw, metric, k: int, kind: str,
                    rescoring: bool):
    """In-jit estimator slice / device rescore + output conventions
    (finish_search's jittable branches, shared by the bq and pq plans
    when no host epilogue is needed)."""
    from raft_tpu.neighbors.ivf_bq import _exact_rescore_device
    from raft_tpu.neighbors.ivf_flat import _postprocess
    sqrt = metric in (DistanceType.L2SqrtExpanded,
                      DistanceType.L2SqrtUnexpanded)
    if rescoring and raw is not None:
        ex, i_out = _exact_rescore_device(raw, q, i, k=k, kind=kind)
        i_out = jnp.where(jnp.isfinite(ex), i_out, -1)
        d = jnp.where(jnp.isfinite(ex), ex, jnp.inf)
    else:
        d, i_out = d[:, :k], i[:, :k]
    if sqrt:
        d = jnp.sqrt(jnp.maximum(d, 0.0))
    return _postprocess(d, metric), i_out


_BUILDERS = {}


def _resolve_builder(index):
    from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
    if not _BUILDERS:
        _BUILDERS.update({ivf_flat.Index: ("ivf_flat", _flat_builder),
                          ivf_pq.Index: ("ivf_pq", _pq_builder),
                          ivf_bq.Index: ("ivf_bq", _bq_builder)})
    for cls, (name, builder) in _BUILDERS.items():
        if isinstance(index, cls):
            return name, builder
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
    the inverted-table cap is measured from it, exactly like the cold
    path's first call). One host sync happens here, never on the
    serving path. With ``warm`` the compiled program is also executed
    once on the sample batch so device-side warmup (e.g. kernel
    autotuning) is off the serving path too.
    """
    from raft_tpu.neighbors import _ivf_scan
    family, builder = _resolve_builder(index)
    if params is None:
        params = _default_params(family)
    q = as_array(queries).astype(jnp.float32)
    expects(q.ndim == 2 and q.shape[1] == index.dim,
            "plan: queries must be (nq, dim=%d), got %s", index.dim,
            q.shape)
    nq = q.shape[0]
    make, n_probes, kind, use_pallas_coarse = builder(index, k, params)
    _ivf_scan.count_coarse_fallback(n_probes, use_pallas_coarse)
    with spans.span("raft.plan.build", family=family, nq=nq,
                    k=k) as bsp, \
            obs.timed("raft.plan.build", family=family):
        # the ONE measurement round-trip of the plan lifecycle: also
        # prefills index.cap_cache so the cold path (ivf_flat.search et
        # al.) is sync-free at this shape from now on
        cap = _ivf_scan.resolve_cap(index.cap_cache, q, index.centers,
                                    params, n_probes, index.n_lists,
                                    kind=kind,
                                    use_pallas=use_pallas_coarse)
        bsp.set_attrs(cap=cap, n_probes=n_probes)
        fn, operands, host_epilogue, key_bits = make(nq, cap)
        key = (family, nq, index.dim, k, n_probes, cap, kind) + key_bits
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
        jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
        q_struct = jax.ShapeDtypeStruct((nq, index.dim), jnp.float32)
        t_c0 = time.perf_counter()
        executable = jitted.lower(q_struct, *operands).compile()
        # compile-time ledger (resource profiler): the seconds the
        # chip sat idle while the host built this program
        profiler.note_compile("plan", time.perf_counter() - t_c0)
        plan = SearchPlan(family=family, key=key, nq=nq, dim=index.dim,
                          k=k, n_probes=n_probes, cap=cap,
                          metric=index.metric, _executable=executable,
                          _operands=operands,
                          _host_epilogue=host_epilogue, _donate=donate)
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
