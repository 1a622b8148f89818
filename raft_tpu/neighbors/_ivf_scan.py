"""List-major ("inverted") IVF fine scan, shared by IVF-Flat and IVF-PQ.

The probe-major scan (``ivf_flat._search_impl``) gathers each query's
p-th probed list per step: every (query, probe) pair re-reads its list's
rows from HBM, so a batch of ``nq`` queries × ``n_probes`` streams
``nq·n_probes·(n/n_lists)·dim`` bytes — 64× the index size at the
default 1024-query/64-probe operating point. The reference reduces the
equivalent waste by sorting the probe list by cluster so same-cluster
work shares the L2 (``ivf_pq_search.cuh:1058-1097``, cub radix sort by
label); the TPU-native version inverts the map outright:

  1. invert (query → probes) into (list → probing queries), a padded
     (n_lists, cap) table (static shape; ``cap`` ≥ the observed max is
     computed on host and bucketed to limit recompiles);
  2. scan lists in chunks: per chunk one dense MXU einsum scores each
     list against *all* queries probing it — each list's rows are read
     exactly once per batch;
  3. per-(list, query) top-k candidates are scattered back through the
     inverse map and merged per query with one final ``select_k``.

Worth it when the reuse factor ``nq·n_probes / n_lists`` is high; the
probe-major scan stays the right call for small/online batches (it only
touches probed lists). ``plan.route`` picks the order.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.precision import matmul_precision


def _round_cap(want: int, nq: int) -> int:
    """Shared inverted-table width bucketing: next power of two (so jit
    caches bucket instead of recompiling per batch), ≥ 8, ≤ nq."""
    cap = 8
    while cap < want:
        cap *= 2
    return min(cap, nq)


def probe_cap(probes, n_lists: int) -> int:
    """Smallest safe static width for the inverted table: the max number
    of queries probing any one list, bucketed by ``_round_cap``. The
    count+max runs as one program (``_counts_and_max``) — the measure
    path is a cold-compile site."""
    from raft_tpu.neighbors.ivf_flat import _counts_and_max
    _, m = _counts_and_max(probes.reshape(-1), n_lists)
    return _round_cap(int(jax.device_get(m)), probes.shape[0])


def _invert_probes(probes, n_lists: int, cap: int):
    """(nq, n_probes) → ``qmap`` (n_lists, cap) query ids (-1 pad) and
    ``inv_pos`` (nq, n_probes): each pair's slot within its list's row.

    Slots are assigned in PROBE-RANK priority order: within a list, pairs
    from low probe ranks (a query's most-promising probes) fill first, so
    when ``cap`` is smaller than a hot list's true probe count the
    overflow drops the *least*-promising (high-rank) probes. With the
    drop-free measured cap (``probe_cap``) the ordering is irrelevant;
    with a cached/static cap it bounds the recall cost of overflow.
    Dropped pairs keep ``inv_pos ≥ cap`` — mergers mask them out."""
    nq, n_probes = probes.shape
    flat_list = probes.reshape(-1)
    qid = jnp.broadcast_to(jnp.arange(nq, dtype=jnp.int32)[:, None],
                           (nq, n_probes)).reshape(-1)
    p_rank = jnp.broadcast_to(jnp.arange(n_probes, dtype=jnp.int32)[None],
                              (nq, n_probes)).reshape(-1)
    counts = jax.ops.segment_sum(jnp.ones(nq * n_probes, jnp.int32),
                                 flat_list, num_segments=n_lists)
    # composite key (list, probe rank); n_lists·n_probes stays well under
    # int32 (≤ n_lists² ≤ 2^34 only for n_lists > 2^17-class indexes —
    # far beyond the list counts this layout targets). Unstable sort:
    # equal keys are same-(list, rank) pairs from different queries,
    # and the drop policy only cares about rank classes — which query
    # within a rank class yields at overflow is arbitrary either way
    # (XLA's sort network is still deterministic for a given shape)
    order = jnp.argsort(flat_list * n_probes + p_rank, stable=False)
    sl = flat_list[order]
    starts = jnp.cumsum(jnp.concatenate([jnp.zeros(1, jnp.int32),
                                         counts]))[:-1]
    pos = jnp.arange(nq * n_probes, dtype=jnp.int32) - starts[sl]
    slot = jnp.where(pos < cap, sl * cap + pos, n_lists * cap)
    qmap = jnp.full((n_lists * cap,), -1, jnp.int32)
    qmap = qmap.at[slot].set(qid[order], mode="drop")
    inv_pos = jnp.zeros((nq * n_probes,), jnp.int32)
    inv_pos = inv_pos.at[order].set(pos)
    return qmap.reshape(n_lists, cap), inv_pos.reshape(nq, n_probes)


def largest_divisor_at_most(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``want`` (≥ 1)."""
    c = 1
    for d in range(1, n + 1):
        if n % d == 0 and d <= want:
            c = d
    return c


def _chunk_size(n_lists: int, cap: int, max_list: int,
                budget_elems: int = 1 << 24) -> int:
    """Largest divisor of n_lists whose (chunk, cap, max_list) score
    block stays under ~``budget_elems`` f32 elements (64 MiB default)."""
    want = max(1, budget_elems // max(1, cap * max_list))
    return largest_divisor_at_most(n_lists, want)


def _score_block(qsub, data, norms, scale):
    """(chunk, cap, dim) queries × (chunk, max_list, dim) list rows →
    (chunk, cap, max_list) squared-L2, mirroring the dtype handling of
    ``ivf_flat._score_probe`` (bf16 on the MXU; int8 via folded scale)."""
    qq = jnp.sum(qsub * qsub, axis=2)
    if data.dtype == jnp.bfloat16:
        # one MXU pass on purpose: operands are already bf16
        ip = jnp.einsum("gcd,gld->gcl", qsub.astype(jnp.bfloat16), data,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT)
    elif data.dtype == jnp.int8:
        ip = scale * jnp.einsum("gcd,gld->gcl", qsub,
                                data.astype(jnp.float32),
                                preferred_element_type=jnp.float32,
                                precision=matmul_precision())
    else:
        ip = jnp.einsum("gcd,gld->gcl", qsub, data,
                        preferred_element_type=jnp.float32,
                        precision=matmul_precision())
    return qq[:, :, None] + norms[:, None, :] - 2.0 * ip


def binned_partial_topk(d, lid, bins: int):
    """Binned (min, argmin) along the trailing list axis — the TPU-KNN
    partial top-k shared by the XLA-tier scans. Bins are STRIDED
    (column c → bin c % bins), matching the Pallas kernels: bucketized
    rows follow dataset order, so a query's true neighbors sit in
    ADJACENT columns — contiguous bins collide them (the kernel
    measured 0.87 vs 0.99+ recall on clustered data; the same ~5%
    recall cliff reproduced here on blobs when bins < list length).
    ``d`` (..., cap, ML) scores, ``lid`` (..., ML) global ids (−1 pad)
    → per-bin ``(min (..., cap, bins), min-id)``; of two hits in one
    bin only the nearer survives (ties: smallest id)."""
    *lead, cap, max_list = d.shape
    b = -(-max_list // bins)
    pad = bins * b - max_list
    dp = jnp.pad(d, [(0, 0)] * (d.ndim - 1) + [(0, pad)],
                 constant_values=jnp.inf)
    db_ = dp.reshape(*lead, cap, b, bins)
    cd = jnp.min(db_, axis=-2)
    col = jnp.pad(jnp.broadcast_to(lid[..., None, :], d.shape),
                  [(0, 0)] * (d.ndim - 1) + [(0, pad)],
                  constant_values=-1).reshape(*lead, cap, b, bins)
    big = jnp.iinfo(jnp.int32).max
    gl = jnp.min(jnp.where(db_ == cd[..., None, :], col, big), axis=-2)
    return cd, jnp.where(gl == big, -1, gl)


def merge_candidates(cand_d, cand_i, probes, inv_pos, k: int,
                     sqrt: bool, use_pallas_select: bool = False,
                     cap: Optional[int] = None):
    """Shared tail of both list-major scans: gather each (query, probe)
    pair's candidate row from the (n_lists, cap, kk) blocks and merge to
    the per-query top-k. ``-1`` candidate ids stay ``-1``. ``cap``, when
    given, masks pairs the inversion dropped (``inv_pos ≥ cap`` — a hot
    list overflowed a cached/static table width)."""
    nq = probes.shape[0]
    kept = None
    if cap is not None:
        kept = inv_pos < cap
        inv_pos = jnp.minimum(inv_pos, cap - 1)
    pd = cand_d[probes, inv_pos].reshape(nq, -1)
    pi = cand_i[probes, inv_pos].reshape(nq, -1)
    if kept is not None:
        kk = pd.shape[1] // probes.shape[1]
        keep_f = jnp.repeat(kept, kk, axis=1)
        pd = jnp.where(keep_f, pd, jnp.inf)
        pi = jnp.where(keep_f, pi, -1)
    pd = jnp.where(pi >= 0, pd, jnp.inf)
    if pd.shape[1] < k:  # fewer candidates than k: pad like the carry init
        short = k - pd.shape[1]
        pd = jnp.pad(pd, ((0, 0), (0, short)), constant_values=jnp.inf)
        pi = jnp.pad(pi, ((0, 0), (0, short)), constant_values=-1)
        use_pallas_select = False
    if use_pallas_select:
        from raft_tpu.ops.pallas_select_k import select_k_pallas
        d, sel = select_k_pallas(pd, k)
    else:
        nd, sel = lax.top_k(-pd, k)
        d = -nd
    ids = jnp.take_along_axis(pi, jnp.maximum(sel, 0), axis=1)
    ids = jnp.where(sel >= 0, ids, -1)
    if sqrt:
        d = jnp.sqrt(jnp.maximum(d, 0.0))
    return d, ids


@functools.partial(jax.jit, static_argnames=("n_probes", "kind",
                                             "use_pallas"))
def coarse_probes(queries, centers, n_probes: int, kind: str = "l2",
                  use_pallas: bool = False):
    """Coarse phase (reference select_clusters, ivf_pq_search.cuh:127):
    query×centers GEMM + n_probes-selection. ``kind`` "ip" probes the
    largest-dot-product centers. With ``use_pallas`` the selection runs
    through the exact Pallas ``select_k`` kernel (the warpsort slot) —
    ``lax.top_k`` is a full variadic sort, tens of ms at
    (1000, 1024+)-wide score matrices, and
    inside the fused single-dispatch search it would dominate the
    coarse phase."""
    from raft_tpu.distance.pairwise import _l2_expanded
    if kind == "ip":
        coarse = -jnp.matmul(queries, centers.T,
                             precision=matmul_precision())
    else:
        coarse = _l2_expanded(queries, centers, sqrt=False)
    if use_pallas and n_probes <= 256:
        from raft_tpu.ops.pallas_select_k import select_k_pallas
        return select_k_pallas(coarse, n_probes)[1]
    return lax.top_k(-coarse, n_probes)[1]


def count_coarse_fallback(n_probes: int, use_pallas: bool) -> None:
    """Telemetry for the coarse-selection cliff: ``coarse_probes`` with
    ``use_pallas=True`` but ``n_probes > 256`` silently falls back to
    the full ``lax.top_k`` variadic sort (the Pallas ``select_k``
    kernel's k ≤ 256 bound — tens of ms at serving widths, see
    docs/performance.md "The coarse n_probes cliff"). Host-side only:
    called once per search / plan build from the routing layers, never
    from inside a trace (a traced increment would count per COMPILE,
    not per call)."""
    if use_pallas and n_probes > 256:
        from raft_tpu import obs
        obs.counter("raft.ivf_scan.coarse.fallback").inc()


class ProbeStats:
    """Bounded host-side per-list probe-mass accumulator — the hotness
    signal the tiered placement policy (and any future multi-tenant
    router) reads. One ``np.bincount`` per batch over the
    already-materialized coarse output; never called from inside a
    trace (same host-side-only discipline as
    :func:`count_coarse_fallback`). Memory is bounded: when more than
    ``2 * bound`` lists are tracked, the tail below the top ``bound``
    by mass is dropped (probe mass is heavy-headed by construction —
    that tail is exactly the cold set)."""

    GUARDED_BY = ("_mass", "_batches", "_total")

    def __init__(self, bound: int = 4096):
        self._lock = threading.Lock()
        self._bound = max(1, int(bound))
        self._mass: dict = {}
        self._batches = 0
        self._total = 0

    def note(self, probes_np) -> None:
        """Fold one coarse output (any int array of list ids) in."""
        flat = np.asarray(probes_np).reshape(-1)
        if flat.size == 0:
            return
        counts = np.bincount(flat)
        nz = np.nonzero(counts)[0]
        with self._lock:
            self._batches += 1
            self._total += int(flat.size)
            for lid in nz:
                li = int(lid)
                self._mass[li] = self._mass.get(li, 0) + int(counts[li])
            if len(self._mass) > 2 * self._bound:
                keep = sorted(self._mass.items(),
                              key=lambda kv: (-kv[1], kv[0]))
                self._mass = dict(keep[:self._bound])

    def histogram(self, n: int = 16):
        """Top-``n`` ``(list_id, probe_mass)`` pairs, mass-descending
        (ties by list id for determinism)."""
        with self._lock:
            items = sorted(self._mass.items(),
                           key=lambda kv: (-kv[1], kv[0]))
        return items[:max(0, int(n))]

    def reset(self) -> None:
        with self._lock:
            self._mass = {}
            self._batches = 0
            self._total = 0


_GLOBAL_PROBE_STATS = ProbeStats()


def note_probes(probes_np, stats: Optional[ProbeStats] = None) -> None:
    """Export per-list probe mass from one coarse output, cheaply:
    ``raft.ivf_scan.probes.{batches,mass}`` counters plus the bounded
    top-N tracker behind :func:`probe_histogram`. Host-side only, like
    :func:`count_coarse_fallback` — call with the materialized probes,
    never under a trace."""
    from raft_tpu import obs
    flat = np.asarray(probes_np)
    obs.counter("raft.ivf_scan.probes.batches").inc()
    obs.counter("raft.ivf_scan.probes.mass").inc(int(flat.size))
    _GLOBAL_PROBE_STATS.note(flat)
    if stats is not None:
        stats.note(flat)


def probe_histogram(n: int = 16):
    """Top-``n`` hottest lists by cumulative probe mass, process-wide
    (the ``raft.ivf_scan.probes.*`` tracker)."""
    return _GLOBAL_PROBE_STATS.histogram(n)


@functools.partial(jax.jit,
                   static_argnames=("k", "cap", "chunk", "bins", "sqrt"))
def inverted_scan(queries, data, norms, ids, probes, k: int, cap: int,
                  chunk: int, scale=1.0, center_offset: Optional[jax.Array]
                  = None, bins: int = 0, sqrt: bool = False):
    """Score every (query, probed list) pair list-major and return the
    merged per-query top-k: (dists (nq, k), global ids (nq, k)).

    ``center_offset`` (n_lists, dim), when given, is subtracted from each
    list's probing queries before scoring — the IVF-PQ residual form
    (queries are pre-rotated; lists hold decoded rotated residuals).

    ``bins`` > 0 replaces the exact per-(list, query) top-k with a
    binned (min, argmin) over ``bins`` row-bins — the TPU-KNN partial
    top-k of the fused kNN kernel (``pallas_fused_knn.py``): of two true
    hits in one bin of one list only the nearer survives. Sort-based
    selection dominates the exact path's runtime; bins ≥ 2k makes the
    candidate pass a cheap VPU reduction at small recall cost.
    """
    nq = queries.shape[0]
    n_lists, max_list = ids.shape
    qmap, inv_pos = _invert_probes(probes, n_lists, cap)

    n_chunks = n_lists // chunk
    qmap_c = qmap.reshape(n_chunks, chunk, cap)
    data_c = data.reshape(n_chunks, chunk, max_list, -1)
    norms_c = norms.reshape(n_chunks, chunk, max_list)
    ids_c = ids.reshape(n_chunks, chunk, max_list)
    off_c = (None if center_offset is None
             else center_offset.reshape(n_chunks, chunk, -1))

    kk = min(k, max_list) if bins <= 0 else min(bins, max_list)

    def one_chunk(args):
        qm, dat, nrm, lid, off = args
        qsub = queries[jnp.clip(qm, 0, nq - 1)]          # (chunk, cap, dim)
        if off is not None:
            qsub = qsub - off[:, None, :]
        d = _score_block(qsub, dat, nrm, scale)
        d = jnp.where(lid[:, None, :] >= 0, jnp.maximum(d, 0.0), jnp.inf)
        if bins > 0 and kk < max_list:
            return binned_partial_topk(d, lid, kk)
        flat = d.reshape(chunk * cap, max_list)
        cd, csel = lax.top_k(-flat, kk)
        cd = -cd
        gl = jnp.take_along_axis(
            jnp.broadcast_to(lid[:, None, :], (chunk, cap, max_list))
            .reshape(chunk * cap, max_list), csel, axis=1)
        return (cd.reshape(chunk, cap, kk), gl.reshape(chunk, cap, kk))

    if off_c is None:
        cand_d, cand_i = lax.map(
            lambda a: one_chunk((*a, None)),
            (qmap_c, data_c, norms_c, ids_c))
    else:
        cand_d, cand_i = lax.map(
            one_chunk, (qmap_c, data_c, norms_c, ids_c, off_c))
    cand_d = cand_d.reshape(n_lists, cap, kk)
    cand_i = cand_i.reshape(n_lists, cap, kk)
    return merge_candidates(cand_d, cand_i, probes, inv_pos, k, sqrt,
                            cap=cap)


def resolve_cap(cache: Optional[dict], queries, centers, params,
                n_probes: int, n_lists: int, kind: str = "l2",
                use_pallas: bool = False) -> int:
    """Inverted-table width policy shared by IVF-Flat and IVF-PQ.

    ``params.probe_cap``: 0 (default) measures the drop-free cap once per
    (nq, n_probes) and caches it on the index — every later same-shape
    search is then a SINGLE dispatch (the measurement costs one extra
    device round-trip); -1 re-measures every
    batch (guaranteed drop-free, the round-2 behavior); > 0 pins an
    explicit cap with no sync at all. A later batch that overflows a
    cached/pinned cap sheds its highest-rank probes only
    (``_invert_probes`` priority order) and the merge masks them.

    Measurement (the -1 mode, and the first 0-mode call per shape) runs
    the coarse phase once here and once again inside the fused search —
    the duplication keeps measured and cached searches byte-identical
    through one jit cache entry; -1 is the drop-free debug mode, not the
    serving path, so the extra coarse GEMM is accepted."""
    from raft_tpu import obs
    from raft_tpu.obs import spans
    pc = getattr(params, "probe_cap", 0)
    if pc > 0:
        cap = _round_cap(pc, queries.shape[0])
        spans.current_span().set_attrs(cap=cap, cap_mode="pinned")
        return cap
    # the tier is part of the key: a cap measured under one coarse
    # selection program must not serve the other (a tie resolved
    # differently could push a list past it — see below)
    key = (queries.shape[0], n_probes, use_pallas)
    if pc == 0 and cache is not None and key in cache:
        obs.counter("raft.ivf_scan.resolve_cap.cache_hits").inc()
        spans.current_span().set_attrs(cap=cache[key],
                                       cap_mode="cache_hit")
        return cache[key]
    # measure over the SAME coarse selection the serving search runs
    # (use_pallas must match) — a tie resolved differently between two
    # selection programs could otherwise push a list past the measured
    # cap and silently shed probes in the drop-free modes. The
    # measurement is a device round-trip (probe_cap's device_get) —
    # the serving-path fixed cost the plan layer's warmup() exists to
    # eliminate; the counter proves a warmed path never lands here.
    obs.counter("raft.ivf_scan.resolve_cap.syncs").inc()
    # the measurement is the request's one host round-trip — a child
    # span makes it visible in the per-request trace (and its absence
    # on a warm path equally so)
    with spans.span("raft.ivf_scan.resolve_cap",
                    nq=int(queries.shape[0]), n_probes=n_probes):
        probes = coarse_probes(queries, centers, n_probes, kind=kind,
                               use_pallas=use_pallas)
        cap = probe_cap(probes, n_lists)
    if pc == 0:
        # ceiling on the AUTO-measured width (drop-free -1 mode stays
        # unbounded): clustered query skew can double the drop-free cap
        # (512 observed at the 500k bench point, 2026-08-02), and a big
        # cap is wrong on BOTH axes — the list-major scan's work grows
        # ∝ cap (the overflow it sheds is the least-promising probe
        # ranks), and the Mosaic kernels' compile time explodes past
        # ~256 (two 300 s-budget parks burned a scarce TPU window).
        # Overridable per call via params.probe_cap, per process via
        # the env.
        import os
        cap_max = int(os.environ.get("RAFT_TPU_AUTO_CAP_MAX", "256"))
        if cap_max > 0:
            # round the ceiling DOWN to the cap bucketing grid — a
            # non-power-of-two env value must not round up past the
            # compile-explosion threshold it exists to guard
            floor = 8
            while floor * 2 <= cap_max:
                floor *= 2
            cap = min(cap, floor)
    if pc == 0 and cache is not None:
        cache[key] = cap
    spans.current_span().set_attrs(cap=cap, cap_mode="measured")
    return cap


def gather_mode() -> str:
    """Resolve the RAFT_TPU_GATHER strategy OUTSIDE jit so the A/B knob
    is a static argument of the fused searches, not an env read frozen
    into the first trace."""
    import os
    mode = os.environ.get("RAFT_TPU_GATHER", "rows")
    from raft_tpu.core.error import expects
    expects(mode in ("rows", "onehot"),
            "RAFT_TPU_GATHER=%s: want rows|onehot", mode)
    return mode


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "cap",
                                             "bins", "sqrt", "kind",
                                             "use_pallas", "gather",
                                             "internal_dtype", "lc",
                                             "fused"))
def fused_list_search(queries, centers, data, norms, ids, scale, *,
                      k: int, n_probes: int, cap: int, bins: int,
                      sqrt: bool, kind: str, use_pallas: bool,
                      gather: str = "rows", internal_dtype=None,
                      lc: int = 0, fused: bool = False):
    """Single-dispatch list-major IVF-Flat search: coarse probe GEMM +
    top-k, probe inversion, query gather, the list scan (Pallas kernel or
    XLA tier) and the candidate merge — ONE jitted computation. The
    reference's search is likewise one stream of kernels with no host
    round-trips (``ivf_flat_search.cuh:1057``). ``lc`` (static):
    kernel lists-per-grid-cell, 0 = auto — resolved by callers via
    ``pallas_ivf_scan.lc_mode()`` outside jit so the cache keys on it.
    ``fused`` (static, set by the route for k ≤ 256): route the fine
    phase through the single-pallas_call scan+select kernel — the
    top-k state stays resident in VMEM and the scan → gather →
    select_k chain disappears."""
    with jax.named_scope("raft.plan.coarse"):
        probes = coarse_probes(queries, centers, n_probes, kind=kind,
                               use_pallas=use_pallas)
    with jax.named_scope("raft.plan.scan"):
        if use_pallas:
            from raft_tpu.ops.pallas_ivf_scan import ivf_list_scan_pallas
            return ivf_list_scan_pallas(queries, data, norms, ids, probes,
                                        k, cap, scale=scale, bins=bins,
                                        sqrt=sqrt, metric=kind,
                                        gather=gather,
                                        internal_dtype=internal_dtype,
                                        lc=lc, fused=fused)
        # XLA tier scores the l2 core only; search() gates routing
        chunk = _chunk_size(ids.shape[0], cap, ids.shape[1])
        return inverted_scan(queries, data, norms, ids, probes, k, cap,
                             chunk, scale, bins=bins, sqrt=sqrt)


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "cap",
                                             "bins", "sqrt"))
def fused_reconstruct_list_search(queries, centers, centers_rot, rot,
                                  decoded, decoded_norms, ids, *,
                                  k: int, n_probes: int, cap: int,
                                  bins: int, sqrt: bool):
    """Single-dispatch IVF-PQ reconstruct-cache list search (the XLA
    tier's analogue of ``fused_list_search``): coarse on the unrotated
    centers, query rotation, residual-form inverted scan, merge."""
    probes = coarse_probes(queries, centers, n_probes)
    q_rot = jnp.matmul(queries, rot.T, precision=matmul_precision())
    chunk = _chunk_size(ids.shape[0], cap, ids.shape[1])
    return inverted_scan(q_rot, decoded, decoded_norms, ids, probes, k,
                         cap, chunk, center_offset=centers_rot,
                         bins=bins, sqrt=sqrt)


def gather_query_rows(queries, qmap, mode: str = ""):
    """Build the per-list query blocks (n_lists, cap, dim) from the probe
    inversion table.

    Two strategies, switchable via ``RAFT_TPU_GATHER`` (A/B-able on
    hardware):

    * ``rows`` (default) — plain XLA row gather.
    * ``onehot`` — one-hot × queries on the MXU in list chunks, with a
      bf16x2 (hi + lo) split: rows are near-f32 (~2^-16 relative, the
      kernel tier's accuracy class), NOT bitwise-exact. XLA lowers big
      row gathers through the scalar core, which has repeatedly been the
      slow path on TPU (LUT-gather scans); this trades them
      for matmul FLOPs.
    """
    import os

    from raft_tpu.core.error import expects

    mode = mode or os.environ.get("RAFT_TPU_GATHER", "rows")
    expects(mode in ("rows", "onehot"),
            "RAFT_TPU_GATHER=%s: want rows|onehot", mode)
    # NOTE: jitted callers must resolve the mode via gather_mode() and
    # pass it explicitly — an env read here would freeze into the trace
    nq = queries.shape[0]
    safe = jnp.clip(qmap, 0, nq - 1)
    if mode != "onehot":
        return queries[safe]

    n_lists, cap = qmap.shape
    # chunk so the (chunk, cap, nq) one-hot stays modest
    chunk = largest_divisor_at_most(
        n_lists, max(1, (64 << 20) // max(1, cap * nq * 2)))

    qh = queries.astype(jnp.bfloat16)
    ql = (queries - qh.astype(jnp.float32)).astype(jnp.bfloat16)

    def one_chunk(idx_c):
        oh = jax.nn.one_hot(idx_c, nq, dtype=jnp.bfloat16)  # (c, cap, nq)
        hi = jnp.einsum("lcq,qd->lcd", oh, qh,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT)
        lo = jnp.einsum("lcq,qd->lcd", oh, ql,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT)
        return hi + lo

    out = jax.lax.map(one_chunk, safe.reshape(-1, chunk, cap))
    return out.reshape(n_lists, cap, queries.shape[1])
