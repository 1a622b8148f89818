"""IVF-Flat ANN index.

Reference: ``raft/neighbors/ivf_flat_types.hpp:31-275`` (index: interleaved
groups of 32 vectors for coalesced access), ``spatial/knn/detail/
ivf_flat_build.cuh:228`` (build = balanced-kmeans train + partition,
``extend`` :108) and ``ivf_flat_search.cuh:1057`` (coarse GEMM + top-k →
fused per-probe ``interleaved_scan_kernel`` with in-kernel block_sort).

TPU re-design:
  * list layout: dense padded buckets — (n_lists, max_list, dim) with the
    pad rows carrying +inf distance. The CUDA 32-interleave exists for
    warp-coalescing; the TPU analogue is simply lane-aligned contiguous
    tiles (max_list rounded to 8 sublanes) that the MXU consumes directly.
  * search: coarse = one (nq, n_lists) MXU matmul + top-k; fine = a scan
    over probe ranks — at probe rank p every query gathers its p-th list
    and scores it with one batched matmul, merging into a running top-k.
    Probed-list scoring is thus n_probes batched MXU ops with *no*
    variable-length control flow (SURVEY.md hard part (c): lists are
    bucketed/padded to static shapes).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.distance.pairwise import _l2_expanded
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core.precision import matmul_precision
from raft_tpu.util.host_sample import sample_rows, take_rows


@dataclass
class IndexParams:
    """reference ivf_flat_types.hpp index_params."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    # reference-parity default. 10 measured downstream-recall-neutral
    # for IVF-Flat (Δ < 0.005 at 16/32 probes on random AND clustered
    # 100k×64, 2026-08-01 A/B) and the EM assignment matmuls are the
    # TPU build bottleneck — the bench/build-speed paths pass 10
    # explicitly (docs/tuning.md)
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    # Pallas matmul tier for the balanced-EM trainer ("bf16" = one MXU
    # pass — the build-speed knob; see docs/tuning.md). None = default.
    kmeans_kernel_precision: object = None
    # list storage dtype: "float32" | "bfloat16" | "int8". The reference
    # indexes f32/f16/u8/s8 datasets (ivf_flat_types.hpp index<T>,
    # quantized dtypes via the kDivisor convention, ann_utils.cuh:79);
    # here narrower storage halves/quarters the HBM bytes the probe
    # scans gather — the search bottleneck — at a small recall cost.
    storage_dtype: str = "float32"


@dataclass
class SearchParams:
    """reference ivf_flat_types.hpp search_params.

    ``scan_order``: "probe" gathers each query's p-th list per step
    (touches only probed lists — right for small/online batches);
    "list" inverts the probe map and scores list-major (each list's rows
    read once per batch — the TPU analogue of the reference's
    sort-probes-by-cluster locality trick, ``ivf_pq_search.cuh:1058``);
    "auto" picks by the reuse factor nq·n_probes/n_lists."""

    n_probes: int = 20
    scan_order: str = "auto"
    # list-order candidate selection: 0 = auto (exact per-(list,query)
    # top-k on the XLA path; 4k strided min-bins in the Pallas kernel —
    # the TPU-KNN partial top-k, recall-gated); -1 = exact on every
    # path; >0 = explicitly that many min-bins per list
    scan_bins: int = 0
    # inverted-table width: 0 = measure once per (nq, n_probes), cache
    # on the index (warm searches are ONE dispatch); -1 = re-measure
    # every batch (drop-free); > 0 = explicit static width, never syncs.
    # Overflowing pairs shed highest-rank probes (see _ivf_scan.resolve_cap)
    probe_cap: int = 0
    # candidate score dtype the Pallas list scan carries to the merge
    # (the internal_distance_dtype role, reference ivf_pq_search.cuh:
    # 780-1004, applied to IVF-Flat): bfloat16 halves the candidate-
    # block HBM writeback+readback; final distances are still f32
    internal_distance_dtype: object = jnp.float32


@dataclass
class Index:
    """IVF-Flat index (reference ``ivf_flat::index``): cluster centers +
    padded per-list data/indices/norms. ``lists_data`` may be stored
    narrow (bf16/int8); ``scale`` dequantizes int8 (value ≈ stored *
    scale — the kDivisor convention, reference ann_utils.cuh:79-123)."""

    centers: jax.Array          # (n_lists, dim)
    lists_data: jax.Array       # (n_lists, max_list, dim)
    lists_indices: jax.Array    # (n_lists, max_list) int32, -1 = pad
    lists_norms: jax.Array      # (n_lists, max_list) squared L2 norms
    list_sizes: jax.Array       # (n_lists,) int32
    metric: DistanceType
    size: int
    scale: float = 1.0
    # measured inverted-table widths keyed (nq, n_probes) — see
    # _ivf_scan.resolve_cap (not part of index identity/serialization)
    cap_cache: dict = field(default_factory=dict, repr=False,
                            compare=False)
    # AOT-compiled serving plans keyed by shape identity — see
    # neighbors/plan.py (not index identity; not serialized)
    plan_cache: dict = field(default_factory=dict, repr=False,
                             compare=False)

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]


def _coarse_scores(queries, centers, kind: str):
    """Coarse cluster scores, smaller-is-better (reference
    select_clusters GEMM, ivf_pq_search.cuh:127): expanded L2, or
    negated dot for the ip core."""
    if kind == "ip":
        return -jnp.matmul(queries, centers.T,
                           precision=matmul_precision())
    return _l2_expanded(queries, centers, sqrt=False)


@functools.partial(jax.jit, static_argnames=("n_lists", "max_list",
                                             "compute_norms"))
def _bucketize_static(x, labels, row_ids, n_lists: int, max_list: int,
                      counts=None, compute_norms: bool = True):
    """jit-safe core of :func:`_bucketize`: scatter rows into padded
    per-list buckets of a caller-chosen static width. ``row_ids`` are
    the ids stored for each row (global ids in sharded builds); rows
    whose list position overflows ``max_list`` are dropped (cannot
    happen when max_list ≥ the true max count). ``counts`` may be
    passed by callers that already computed the per-list totals.
    ``compute_norms=False`` (integer bit-payloads — ivf_bq) skips the
    squared-norm pass and returns ``norms=None``: payloads that are
    not real numbers must ride as int32, never as f32 bitcasts whose
    NaN patterns XLA may canonicalize (ADVICE r3 #2)."""
    n, dim = x.shape
    if counts is None:
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), labels,
                                     num_segments=n_lists)
    if row_ids is None:  # default ids 0..n-1, built in-trace (None is a
        row_ids = jnp.arange(n, dtype=jnp.int32)  # static arg structure)
    order = jnp.argsort(labels, stable=True)
    sorted_labels = labels[order]
    # position of each row within its list
    pos = jnp.arange(n, dtype=jnp.int32) - jnp.cumsum(
        jnp.concatenate([jnp.zeros(1, jnp.int32), counts]))[sorted_labels]
    flat_slot = jnp.where(pos < max_list, sorted_labels * max_list + pos,
                          n_lists * max_list)
    data = jnp.zeros((n_lists * max_list + 1, dim), x.dtype)
    data = data.at[flat_slot].set(x[order], mode="drop")
    idx = jnp.full((n_lists * max_list + 1,), -1, jnp.int32)
    idx = idx.at[flat_slot].set(row_ids[order].astype(jnp.int32),
                                mode="drop")
    data = data[:-1].reshape(n_lists, max_list, dim)
    idx = idx[:-1].reshape(n_lists, max_list)
    if not compute_norms:
        return data, idx, None, counts
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    norms = jnp.where(idx >= 0, norms, 0.0)
    return data, idx, norms, counts


def _bucketize(x, labels, n_lists: int, round_to: int = 8,
               row_ids=None, compute_norms: bool = True):
    """Scatter rows into padded per-list buckets — static-shape layout.
    The bucket width is sized from the observed max count (one host
    sync); sharded builds pre-agree a width and call the static core.
    ``row_ids`` defaults to 0..n-1 (fresh builds); extends pass the
    combined global ids."""
    counts, mx = _counts_and_max(labels, n_lists)
    max_list = int(jax.device_get(mx))
    max_list = max(round_to, (max_list + round_to - 1) // round_to * round_to)
    data, idx, norms, counts = _bucketize_static(
        x, labels, row_ids, n_lists, max_list, counts=counts,
        compute_norms=compute_norms)
    return data, idx, norms, counts


@functools.partial(jax.jit, static_argnames=("n_lists",))
def _counts_and_max(labels, n_lists: int):
    """Per-list counts + their max as ONE program (the max is the one
    host sync of the bucketing path; eager this was 4+ tiny
    compiles)."""
    counts = jax.ops.segment_sum(
        jnp.ones(labels.shape, jnp.int32), labels, num_segments=n_lists)
    return counts, jnp.max(counts)


_SIM_METRICS = (DistanceType.InnerProduct, DistanceType.CosineExpanded)


def _metric_kind(metric: DistanceType) -> str:
    """"l2" or "ip" — the two scoring cores (reference
    ivf_flat_search.cuh metric dispatch; cosine rides the ip core after
    row normalization, the processing.cuh preprocessing trick)."""
    return "ip" if metric in _SIM_METRICS else "l2"


def _postprocess(d, metric: DistanceType):
    """Kernel-internal scores are uniformly smaller-is-better (-sim for
    the ip core); map back to the metric's output convention: IP →
    similarities (descending), cosine → 1 − cos (ascending)."""
    if metric == DistanceType.InnerProduct:
        return -d
    if metric == DistanceType.CosineExpanded:
        return 1.0 + d
    return d


def build(dataset, params: IndexParams = IndexParams(), res=None) -> Index:
    """Train + populate (reference ivf_flat_build.cuh:228 build =
    train balanced kmeans then extend with the full dataset). Cosine
    datasets are row-normalized at build (reference processing.cuh) so
    the ip scoring core applies."""
    x = as_array(dataset).astype(jnp.float32)
    n = x.shape[0]
    expects(params.n_lists <= n, "ivf_flat.build: n_lists > n_samples")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct,
                              DistanceType.CosineExpanded),
            "ivf_flat: unsupported metric %s", params.metric)
    obs.counter("raft.ivf_flat.build.total").inc()
    obs.counter("raft.ivf_flat.build.rows").inc(n)
    from raft_tpu.obs import spans
    # RAII scope like the reference's nvtx range in build (nvtx.hpp:69);
    # obs.timed also lands the wall time in raft.ivf_flat.build.seconds,
    # the span puts the build in the flight recorder
    with spans.span("raft.ivf_flat.build", rows=n,
                    n_lists=params.n_lists), \
            obs.timed("raft.ivf_flat.build"):
        if params.metric == DistanceType.CosineExpanded:
            x = x / jnp.maximum(
                jnp.linalg.norm(x, axis=1, keepdims=True), 1e-30)
        n_train = max(params.n_lists,
                      int(n * params.kmeans_trainset_fraction))
        # random trainset subsample — a prefix would bias centers when
        # input rows arrive ordered (reference subsamples too); drawn
        # host-side (util.host_sample): a traced choice(replace=False)
        # is an n-wide sort compile on TPU
        if n_train < n:
            trainset = take_rows(x, sample_rows(n, n_train, 0))
        else:
            trainset = x
        centers = kmeans_balanced.build_hierarchical(
            trainset, params.n_lists, params.kmeans_n_iters,
            kernel_precision=params.kmeans_kernel_precision, res=res)
        labels = kmeans_balanced.predict(x, centers, res=res)
        data, idx, norms, counts = _bucketize(x, labels, params.n_lists)
        data, norms, scale = _quantize_lists(data, norms,
                                             params.storage_dtype)
    return Index(centers=centers, lists_data=data, lists_indices=idx,
                 lists_norms=norms, list_sizes=counts,
                 metric=params.metric, size=n, scale=scale)


def _quantize_lists(data, norms, storage_dtype: str):
    """Narrow the bucketed list storage; for narrow dtypes the norms are
    recomputed over the dequantized values so probe distances stay
    self-consistent (f32 keeps the caller's precomputed norms)."""
    expects(storage_dtype in ("float32", "bfloat16", "int8"),
            "ivf_flat: storage_dtype must be float32|bfloat16|int8")
    if storage_dtype == "float32":
        return data, norms, 1.0
    if storage_dtype == "bfloat16":
        q = data.astype(jnp.bfloat16)
        return (q, jnp.sum(q.astype(jnp.float32) ** 2, axis=2), 1.0)
    # int8: one global scale (the kDivisor convention uses one fixed
    # divisor for the whole dataset)
    max_abs = float(jax.device_get(jnp.max(jnp.abs(data))))
    scale = max(max_abs, 1e-30) / 127.0
    q = jnp.clip(jnp.round(data / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, jnp.sum(deq * deq, axis=2), scale


def extend(index: Index, new_vectors, new_indices=None, res=None) -> Index:
    """Add vectors to an existing index (reference extend :108): assign to
    nearest centers and re-bucket. Centers are kept fixed (the reference's
    default; adaptive_centers handled at build)."""
    x_new = as_array(new_vectors).astype(jnp.float32)
    if index.metric == DistanceType.CosineExpanded:
        # build() stores row-normalized vectors for cosine; extended
        # rows must match or the ip core scores raw dot products
        x_new = x_new / jnp.maximum(
            jnp.linalg.norm(x_new, axis=1, keepdims=True), 1e-30)
    n_lists = index.n_lists
    # reconstruct flat (data, ids) view of current contents, dequantized
    # to f32 (narrow storage is re-applied after re-bucketing)
    valid = index.lists_indices >= 0
    old_data = index.lists_data.reshape(-1, index.dim)[valid.reshape(-1)]
    if old_data.dtype == jnp.int8:
        old_data = old_data.astype(jnp.float32) * index.scale
        storage = "int8"
    elif old_data.dtype == jnp.bfloat16:
        old_data = old_data.astype(jnp.float32)
        storage = "bfloat16"
    else:
        storage = "float32"
    old_ids = index.lists_indices.reshape(-1)[valid.reshape(-1)]
    if new_indices is None:
        new_ids = jnp.arange(index.size, index.size + x_new.shape[0],
                             dtype=jnp.int32)
    else:
        new_ids = as_array(new_indices).astype(jnp.int32)
    all_data = jnp.concatenate([old_data, x_new], axis=0)
    all_ids = jnp.concatenate([old_ids, new_ids])
    labels = kmeans_balanced.predict(all_data, index.centers, res=res)
    data, idx, norms, counts = _bucketize(all_data, labels, n_lists,
                                          row_ids=all_ids)
    data, norms, scale = _quantize_lists(data, norms, storage)
    return Index(centers=index.centers, lists_data=data, lists_indices=idx,
                 lists_norms=norms, list_sizes=counts, metric=index.metric,
                 size=index.size + x_new.shape[0], scale=scale)


def _gather_list_rows(lists_data, list_id):
    """``lists_data[list_id]`` — (nq, max_list, dim) — as a gather of
    rows. A gather of whole list slabs makes the TPU compiler cut the
    operand into 2048-row slabs and copy every one of them: at 1024
    lists × 15576 × 128 f32 that is 7.5 GB of temporaries, more than a
    v5e has free beside the index. A row gather moves only the rows it
    returns."""
    n_lists, ml, dim = lists_data.shape
    rows = (list_id.astype(jnp.int32)[:, None] * ml
            + jnp.arange(ml, dtype=jnp.int32)[None, :])
    return lists_data.reshape(n_lists * ml, dim).at[rows].get(mode="clip")


def _score_probe(queries, qq, lists_data, lists_norms, lists_indices,
                 list_id, scale: float = 1.0, kind: str = "l2"):
    """Score one probe rank: per-query (max_list,) scores + ids — the
    fine-phase GEMM shared by single-chip and sharded searches
    (reference interleaved_scan_kernel, ivf_flat_search.cuh:665).
    Handles narrow list storage: bf16 rides the MXU directly; int8 is
    dequantized by folding ``scale`` into the accumulated product.
    ``kind`` "ip" returns negated similarities (smaller-is-better)."""
    data = _gather_list_rows(lists_data, list_id)   # (nq, max_list, dim)
    ids = lists_indices[list_id]                    # (nq, max_list)
    if data.dtype == jnp.bfloat16:
        # one MXU pass on purpose: operands are already bf16
        ip = jnp.einsum("qd,qld->ql", queries.astype(jnp.bfloat16), data,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT)
    elif data.dtype == jnp.int8:
        ip = scale * jnp.einsum("qd,qld->ql", queries,
                                data.astype(jnp.float32),
                                preferred_element_type=jnp.float32,
                                precision=matmul_precision())
    else:
        ip = jnp.einsum("qd,qld->ql", queries, data,
                        preferred_element_type=jnp.float32,
                        precision=matmul_precision())
    if kind == "ip":
        return jnp.where(ids >= 0, -ip, jnp.inf), ids
    d = qq[:, None] + lists_norms[list_id] - 2.0 * ip
    return jnp.where(ids >= 0, jnp.maximum(d, 0.0), jnp.inf), ids


def _fine_phase(queries, lists_data, lists_norms, lists_indices, probes,
                scale, k: int, sqrt: bool, kind: str):
    """Probe-major fine phase: scan over probe rank, each rank one
    batched GEMM + top-k merge. ``probes`` may hold list ids OR positions
    into a fetched sub-list table (the host-memory path) — the math is
    identical, which is why this is the single shared definition."""
    nq = queries.shape[0]
    n_probes = probes.shape[1]
    qq = jnp.sum(queries * queries, axis=1)

    def probe_step(carry, p):
        best_d, best_i = carry
        d, ids = _score_probe(queries, qq, lists_data, lists_norms,
                              lists_indices, probes[:, p], scale,
                              kind=kind)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        nd, sel = lax.top_k(-cat_d, k)
        return (-nd, jnp.take_along_axis(cat_i, sel, axis=1)), None

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    (d, i), _ = lax.scan(probe_step, init, jnp.arange(n_probes))
    if sqrt:
        d = jnp.sqrt(jnp.maximum(d, 0.0))
    return d, i


@functools.partial(jax.jit,
                   static_argnames=("k", "n_probes", "sqrt", "kind"))
def _search_impl(queries, centers, lists_data, lists_indices, lists_norms,
                 scale, k: int, n_probes: int, sqrt: bool,
                 kind: str = "l2"):
    # ---- coarse phase (reference ivf_flat_search.cuh:1070-1147):
    # query×centers GEMM + top-k probes
    coarse = _coarse_scores(queries, centers, kind)
    _, probes = lax.top_k(-coarse, n_probes)  # (nq, n_probes)
    return _fine_phase(queries, lists_data, lists_norms, lists_indices,
                       probes, scale, k, sqrt, kind)


def search(index: Index, queries, k: int,
           params: SearchParams = SearchParams(), res=None
           ) -> Tuple[jax.Array, jax.Array]:
    """Search → (dists (nq, k), neighbor ids (nq, k)) (reference
    ivf_flat_search.cuh:1210)."""
    from raft_tpu.obs import spans
    # root span of the request (or child when batched/nested): the
    # per-request story next to the aggregate counters below
    with spans.span("raft.ivf_flat.search", k=k) as sp:
        return _search_spanned(index, queries, k, params, res, sp)


def _search_spanned(index: Index, queries, k: int, params, res, sp
                    ) -> Tuple[jax.Array, jax.Array]:
    from raft_tpu.neighbors import plan
    from raft_tpu.neighbors.ann_types import (MAX_QUERY_BATCH,
                                              batched_search,
                                              pin_scan_order)
    q = as_array(queries).astype(jnp.float32)
    sp.set_attr("nq", int(q.shape[0]))
    expects(q.shape[1] == index.dim, "ivf_flat.search: dim mismatch")
    if q.shape[0] > MAX_QUERY_BATCH:
        # reference search batching (ivf_pq_search.cuh:1234 role); pin
        # "auto" choices from the FULL query count first
        pinned = pin_scan_order(params, q.shape[0], index.n_lists)
        return batched_search(
            lambda qb: search(index, qb, k, pinned, res=res), q)
    r = plan.route(index, q.shape[0], k, params)
    sp.set_attrs(n_probes=r.n_probes, order=r.order)
    # per-batch telemetry (the batched path recurses here per
    # sub-batch, so queries sum correctly across the split)
    obs.counter("raft.ivf_flat.search.queries").inc(q.shape[0])
    obs.histogram("raft.ivf_flat.search.batch_size",
                  buckets=obs.SIZE_BUCKETS).observe(q.shape[0])
    obs.histogram("raft.ivf_flat.search.n_probes",
                  buckets=obs.SIZE_BUCKETS).observe(r.n_probes)
    # RAII scope at the public search (the reference's nvtx range slot);
    # obs.timed opens the trace range and the order-labeled latency
    # histogram together
    with obs.timed("raft.ivf_flat.search", order=r.order):
        return plan.run(index, q, r, params)
