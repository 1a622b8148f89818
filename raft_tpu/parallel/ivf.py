"""Distributed (sharded) IVF search — the 100M-vector north star
(SURVEY.md §6-§7: shard the IVF lists across a mesh, per-shard probe
scans, collective top-k merge).

Design: the index's list dimension (``n_lists``) is sharded over the
mesh's data axis; queries are replicated. Each shard runs the standard
coarse→fine search against its local lists (its local centers are a
disjoint subset of the global centers), then shards merge their top-k
with one all_gather + select. Like the reference's multi-part search
(``knn_merge_parts``-over-parts, brute_force.cuh:48 — and cuML's MNMG
ANN), each shard probes ``n_probes`` of *its own* lists, so total probed
lists grow with the mesh: recall at fixed n_probes is ≥ the single-chip
index's.

List indices hold global database row ids from the single build, so no
id translation is needed at merge.
"""

from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu.obs import spans
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.core.precision import matmul_precision
from raft_tpu.comms.comms import build_comms
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.util.host_sample import sample_rows


# ---------------------------------------------------------------------------
# Sharded search-plan cache (the neighbors/plan.py analogue at mesh
# scope). Every distributed search used to build its `local` closure
# and `jax.jit(jax.shard_map(local, ...))` wrapper PER CALL — a fresh
# function identity every time, so jax's jit cache missed and the whole
# shard_map re-traced (and, without a persistent compile cache,
# re-COMPILED) on every serving call. The builders below are keyed by
# everything that shapes the program (mesh, axis, k, n_probes, metric
# core, scalars baked into the closure), so a warm key reuses one
# compiled callable and the serving call is a single cached dispatch.
# ---------------------------------------------------------------------------
# Compile-surface rung declarations (graftlint GL012–GL014): the
# _shmap_plan key dimensions that are per-index/per-process constants
# — everything else in a key must be a grid rung, an enum or a
# structural handle, or GL012 flags the site as a retrace storm.
COMPILE_SURFACE_RUNGS = {
    "n_lists": ("n_lists", None,
                "coarse list count — fixed per index"),
    "scale": ("scale", None, "quantization scale — fixed per index"),
    "size": ("size", None, "corpus row count — fixed per epoch"),
    "ml": ("ml", None, "max list length — fixed per index layout"),
    "width": ("width", None,
              "rows one shard sends one shard — fixed per build"),
    "max_iter": ("max_iter", None, "trainer bound — config"),
    "tol": ("tol", None, "trainer tolerance — config"),
}

_SHMAP_PLANS: dict = {}


def _shmap_plan(key, builder):
    fn = _SHMAP_PLANS.get(key)
    if fn is None:
        obs.counter("raft.parallel.plan.misses").inc()
        spans.current_span().set_attr("shmap_plan", "miss")
        fn = _SHMAP_PLANS[key] = builder()
    else:
        obs.counter("raft.parallel.plan.hits").inc()
        spans.current_span().set_attr("shmap_plan", "hit")
    return fn


# communicator cache (ISSUE 8 satellite): one Comms per (mesh, axis).
# build_comms re-runs its axis/bootstrap checks on every call — cheap
# once, not per serving batch. Ladder-cached serving paths (and every
# distributed search below) reuse ONE frozen handle per mesh axis;
# callers holding a custom handle (split comms, non-default timeouts)
# pass it via the searches' `comms=` parameter instead.
_COMMS_CACHE: dict = {}


def get_comms(mesh: jax.sharding.Mesh, axis: str = "data"):
    """Cached :class:`~raft_tpu.comms.comms.Comms` over ``mesh[axis]``
    (the ``build_comms`` result, built once per mesh axis)."""
    key = (mesh, axis)
    c = _COMMS_CACHE.get(key)
    if c is None:
        c = _COMMS_CACHE[key] = build_comms(mesh, axis)
    return c


def _rank_spans(n_shards: int, t0: float, dt: float) -> None:
    """One rank-tagged child span per mesh shard, merged host-side into
    the current trace. The shard_map dispatch executes every rank
    inside ONE host call (SPMD), so the per-rank spans share the
    dispatch interval — they tag the trace with WHICH ranks served the
    request (EQuARX-style rank-level accounting), not independent
    per-rank walls. In Chrome-trace export the ``rank`` attribute maps
    to the event pid, so ranks render as separate rows."""
    for r in range(n_shards):
        spans.add_child_span("raft.parallel.ivf.shard", t0, dt, rank=r)


def _shard0(arr, mesh, axis):
    """Shard an array's leading (list) dimension over mesh[axis]."""
    spec = P(axis, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def shard_ivf_flat(index, mesh: jax.sharding.Mesh, axis: str = "data"):
    """Reshard an IVF-Flat index's lists over ``mesh[axis]`` (in place on
    a new Index). n_lists must divide evenly."""
    from raft_tpu.neighbors.ivf_flat import Index
    n_shards = mesh.shape[axis]
    expects(index.n_lists % n_shards == 0,
            f"shard_ivf_flat: n_lists={index.n_lists} not divisible by "
            f"{n_shards} shards")
    return Index(
        centers=_shard0(index.centers, mesh, axis),
        lists_data=_shard0(index.lists_data, mesh, axis),
        lists_indices=_shard0(index.lists_indices, mesh, axis),
        lists_norms=_shard0(index.lists_norms, mesh, axis),
        list_sizes=_shard0(index.list_sizes, mesh, axis),
        metric=index.metric, size=index.size, scale=index.scale)


def shard_ivf_pq(index, mesh: jax.sharding.Mesh, axis: str = "data"):
    """Reshard an IVF-PQ index's lists over ``mesh[axis]``. The bf16
    reconstruction cache is decoded first (sharded scans use it)."""
    from raft_tpu.neighbors.ivf_pq import (
        CodebookGen, Index, _code_norms, _code_norms_per_cluster,
        _decode_lists, _decode_lists_per_cluster)
    n_shards = mesh.shape[axis]
    expects(index.n_lists % n_shards == 0,
            f"shard_ivf_pq: n_lists={index.n_lists} not divisible by "
            f"{n_shards} shards")
    # shard the compact payload FIRST, then decode: the bf16 cache is the
    # one array sharding exists to split — it must never materialize on a
    # single device (the 100M north-star constraint)
    codes = _shard0(index.codes, mesh, axis)
    lists_indices = _shard0(index.lists_indices, mesh, axis)
    if index.codebook_kind == CodebookGen.PER_CLUSTER:
        # per-cluster books are list-aligned: shard them WITH the lists
        # and decode shard-locally
        pq_centers = _shard0(index.pq_centers, mesh, axis)
        decoded = _decode_lists_per_cluster(codes, pq_centers,
                                            lists_indices)
        norms_fn = _code_norms_per_cluster
    else:
        pq_centers = jax.device_put(index.pq_centers,
                                    NamedSharding(mesh, P()))
        decoded = _decode_lists(codes, pq_centers, lists_indices)
        norms_fn = _code_norms
    # build already holds the identical exact norms: shard them instead
    # of re-gathering every code slot; recompute only for older indexes
    decoded_norms = (_shard0(index.code_norms, mesh, axis)
                     if index.code_norms is not None
                     else norms_fn(codes, pq_centers, lists_indices))
    return Index(
        centers=_shard0(index.centers, mesh, axis),
        centers_rot=_shard0(index.centers_rot, mesh, axis),
        rotation_matrix=jax.device_put(index.rotation_matrix,
                                       NamedSharding(mesh, P())),
        pq_centers=pq_centers,
        codes=codes,
        lists_indices=lists_indices,
        list_sizes=_shard0(index.list_sizes, mesh, axis),
        metric=index.metric, pq_bits=index.pq_bits, size=index.size,
        codebook_kind=index.codebook_kind,
        decoded=decoded, decoded_norms=decoded_norms)


def _fine_scan(queries, get_probe, k: int, n_probes: int, axis: str):
    """Shared probe-rank scan with a shard-varying carry (plain
    ``_search_impl`` carries an unvarying init that shard_map's
    varying-manual-axes tracking rejects)."""
    nq = queries.shape[0]

    def probe_step(carry, p):
        best_d, best_i = carry
        d, ids = get_probe(p)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        nd, sel = lax.top_k(-cat_d, k)
        return (-nd, jnp.take_along_axis(cat_i, sel, axis=1)), None

    init = (lax.pcast(jnp.full((nq, k), jnp.inf, jnp.float32), (axis,),
                      to="varying"),
            lax.pcast(jnp.full((nq, k), -1, jnp.int32), (axis,),
                      to="varying"))
    (d, i), _ = lax.scan(probe_step, init, jnp.arange(n_probes))
    return d, i


def _global_merge(comms, axis, d, i, k):
    gd = comms.allgather(d)                   # (n_shards, nq, k)
    gi = comms.allgather(i)
    cat_d = jnp.moveaxis(gd, 0, 1).reshape(d.shape[0], -1)
    cat_i = jnp.moveaxis(gi, 0, 1).reshape(d.shape[0], -1)
    nd, sel = lax.top_k(-cat_d, k)
    fd, fi = -nd, jnp.take_along_axis(cat_i, sel, axis=1)
    # identical on every rank; pmax proves replication to shard_map
    return lax.pmax(fd, axis), lax.pmax(fi, axis)


def _merge_topk(comms, axis, d, i, k, merge: str, size: int):
    """Cross-shard top-k merge at the selected wire format: the exact
    f32 allgather, or the int8 two-stage compressed merge
    (``serve/merge.py`` — EQuARX-style quantized collective; the
    ``RAFT_TPU_DIST_MERGE`` story lives there)."""
    if merge == "int8":
        from raft_tpu.serve.merge import compressed_merge
        return compressed_merge(comms, d, i, k, size)
    return _global_merge(comms, axis, d, i, k)


def _resolve_merge(merge):
    """Library-function default for the cross-shard merge wire format:
    exact f32 unless ``RAFT_TPU_DIST_MERGE`` (or the caller) opts into
    the int8 compressed merge. The serving tier (``serve/dist.py``)
    resolves its own default (int8) — see ``serve/merge.merge_mode``."""
    if merge is None:
        from raft_tpu.serve.merge import merge_mode
        merge = merge_mode(default="f32")
    expects(merge in ("f32", "int8"),
            "distributed search: merge must be 'f32' or 'int8', got %r",
            merge)
    return merge


def _flat_list_plan(mesh, axis: str, k: int, n_probes: int, kind: str,
                    sqrt: bool, scale: float, merge: str, size: int,
                    comms):
    """Cached shard_map program for the list-sharded IVF-Flat search —
    shared by :func:`distributed_ivf_flat_search` and the serving
    tier's pre-warmed distributed plan ladder (``serve/dist.py``)."""
    from raft_tpu.neighbors.ivf_flat import _coarse_scores, _score_probe

    def build():
        def local(centers, lists_data, lists_indices, lists_norms,
                  q_rep):
            qq = jnp.sum(q_rep * q_rep, axis=1)
            coarse = _coarse_scores(q_rep, centers, kind)
            _, probes = lax.top_k(-coarse, n_probes)

            def get_probe(p):
                return _score_probe(q_rep, qq, lists_data, lists_norms,
                                    lists_indices, probes[:, p],
                                    scale, kind=kind)

            d, i = _fine_scan(q_rep, get_probe, k, n_probes, axis)
            if sqrt:
                d = jnp.sqrt(jnp.maximum(d, 0.0))
            return _merge_topk(comms, axis, d, i, k, merge, size)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None, None), P(axis, None),
                      P(axis, None), P()),
            out_specs=(P(), P())))

    return _shmap_plan(
        ("flat_list", mesh, axis, k, n_probes, kind, sqrt, scale, merge,
         size, comms), build)


def distributed_ivf_flat_search(
    index, queries, k: int, params=None,
    mesh: jax.sharding.Mesh = None, axis: str = "data",
    comms=None, merge: str = None,
) -> Tuple[jax.Array, jax.Array]:
    """Search a list-sharded IVF-Flat index (see :func:`shard_ivf_flat`).

    ``comms`` — a pre-built communicator handle (default: the cached
    :func:`get_comms` handle, so repeated serving calls never re-run
    the bootstrap checks). ``merge`` — cross-shard merge wire format
    (``f32`` exact | ``int8`` compressed; default f32 unless
    ``RAFT_TPU_DIST_MERGE`` says otherwise)."""
    from raft_tpu.neighbors.ivf_flat import SearchParams
    params = params or SearchParams()
    expects(mesh is not None, "distributed ivf_flat: mesh is required")
    from raft_tpu.neighbors.ivf_flat import _metric_kind, _postprocess
    q = as_array(queries).astype(jnp.float32)
    expects(q.shape[1] == index.dim, "distributed ivf_flat: dim mismatch")
    if index.metric == DistanceType.CosineExpanded:
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True),
                            1e-30)
    n_shards = mesh.shape[axis]
    nl_local = index.n_lists // n_shards
    n_probes = min(params.n_probes, nl_local)
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    kind = _metric_kind(index.metric)
    scale = float(index.scale)
    merge = _resolve_merge(merge)
    comms = comms if comms is not None else get_comms(mesh, axis)

    with spans.span("raft.parallel.ivf.search", family="ivf_flat",
                    nq=int(q.shape[0]), k=k, n_probes=n_probes,
                    axis=axis, n_shards=n_shards, merge=merge):
        shmapped = _flat_list_plan(mesh, axis, k, n_probes, kind, sqrt,
                                   scale, merge, int(index.size), comms)
        q_rep = jax.device_put(q, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        d, i = shmapped(index.centers, index.lists_data,
                        index.lists_indices, index.lists_norms, q_rep)
        _rank_spans(n_shards, t0, time.perf_counter() - t0)
    return _postprocess(d, index.metric), i


def _pq_list_plan(mesh, axis: str, k: int, n_probes: int, kind: str,
                  sqrt: bool, merge: str, size: int, comms):
    """Cached shard_map program for the list-sharded IVF-PQ
    (reconstruction-scan) search — shared by
    :func:`distributed_ivf_pq_search` and the serving tier's ladder."""
    from raft_tpu.neighbors.ivf_flat import _coarse_scores
    from raft_tpu.neighbors.ivf_pq import _score_probe_reconstruct

    def build():
        def local(centers, centers_rot, rot, decoded, decoded_norms,
                  lists_indices, q_rep):
            coarse = _coarse_scores(q_rep, centers, kind)
            _, probes = lax.top_k(-coarse, n_probes)
            q_rot = jnp.matmul(q_rep, rot.T,
                               precision=matmul_precision())

            def get_probe(p):
                return _score_probe_reconstruct(
                    q_rot, centers_rot, decoded, decoded_norms,
                    lists_indices, probes[:, p], kind=kind)

            d, i = _fine_scan(q_rep, get_probe, k, n_probes, axis)
            if sqrt:
                d = jnp.sqrt(jnp.maximum(d, 0.0))
            return _merge_topk(comms, axis, d, i, k, merge, size)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(),
                      P(axis, None, None), P(axis, None), P(axis, None),
                      P()),
            out_specs=(P(), P())))

    return _shmap_plan(
        ("pq_list", mesh, axis, k, n_probes, kind, sqrt, merge, size,
         comms), build)


def distributed_ivf_pq_search(
    index, queries, k: int, params=None,
    mesh: jax.sharding.Mesh = None, axis: str = "data",
    comms=None, merge: str = None,
) -> Tuple[jax.Array, jax.Array]:
    """Search a list-sharded IVF-PQ index (see :func:`shard_ivf_pq`) via
    the bf16 reconstruction scan. ``comms``/``merge`` as in
    :func:`distributed_ivf_flat_search`."""
    from raft_tpu.neighbors.ivf_pq import SearchParams
    params = params or SearchParams()
    expects(mesh is not None, "distributed ivf_pq: mesh is required")
    q = as_array(queries).astype(jnp.float32)
    expects(q.shape[1] == index.dim, "distributed ivf_pq: dim mismatch")
    expects(index.decoded is not None,
            "distributed ivf_pq: index not sharded via shard_ivf_pq")
    from raft_tpu.neighbors.ivf_flat import _metric_kind, _postprocess
    n_shards = mesh.shape[axis]
    nl_local = index.n_lists // n_shards
    n_probes = min(params.n_probes, nl_local)
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    kind = _metric_kind(index.metric)
    merge = _resolve_merge(merge)
    comms = comms if comms is not None else get_comms(mesh, axis)

    with spans.span("raft.parallel.ivf.search", family="ivf_pq",
                    nq=int(q.shape[0]), k=k, n_probes=n_probes,
                    axis=axis, n_shards=n_shards, merge=merge):
        shmapped = _pq_list_plan(mesh, axis, k, n_probes, kind, sqrt,
                                 merge, int(index.size), comms)
        q_rep = jax.device_put(q, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        d, i = shmapped(index.centers, index.centers_rot,
                        index.rotation_matrix, index.decoded,
                        index.decoded_norms, index.lists_indices, q_rep)
        _rank_spans(n_shards, t0, time.perf_counter() - t0)
    return _postprocess(d, index.metric), i


# ---------------------------------------------------------------------------
# Distributed BUILD (VERDICT round-1 item 6 / reference ivf_pq_build.cuh:605
# extend + SURVEY.md §3.3 MNMG note): the dataset stays row-sharded on the
# mesh; coarse centers are trained with the MNMG kmeans; each shard encodes
# and buckets its OWN rows into partial lists with global ids. The global
# index never materializes on one device — it exists only as the collection
# of per-shard parts, the reference's own multi-part layout
# (brute_force.cuh:48 knn over parts + merge). Search probes the SAME
# global centers on every shard, scans the shard's partial lists, and
# merges — the scanned set equals the single-host index's, so results are
# numerically identical at matched probes.
# ---------------------------------------------------------------------------

from dataclasses import dataclass

from raft_tpu.cluster.kmeans_types import KMeansParams


@dataclass
class DistributedIvfFlat:
    """Row-sharded multi-part IVF-Flat index. ``parts_*`` lead with the
    shard axis and live sharded over ``mesh[axis]``; ``centers`` is
    replicated. ``parts_indices`` holds GLOBAL dataset row ids."""

    centers: jax.Array        # (n_lists, dim) replicated
    parts_data: jax.Array     # (n_shards, n_lists, ml, dim) P(axis,...)
    parts_indices: jax.Array  # (n_shards, n_lists, ml) int32, -1 pad
    parts_norms: jax.Array    # (n_shards, n_lists, ml)
    metric: "DistanceType"
    size: int
    mesh: jax.sharding.Mesh
    axis: str

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]


def _shard_rows(x, mesh, axis):
    """Pad + shard rows over mesh[axis]; returns (x_sharded,
    ids_sharded) with pad rows carrying id -1."""
    n = x.shape[0]
    n_shards = mesh.shape[axis]
    pad = (-n) % n_shards
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    ids = jnp.where(jnp.arange(n + pad) < n,
                    jnp.arange(n + pad, dtype=jnp.int32), -1)
    xs = jax.device_put(x, NamedSharding(mesh, P(axis, None)))
    ids_s = jax.device_put(ids, NamedSharding(mesh, P(axis)))
    return xs, ids_s


def _label_and_agree_width(xs, ids_s, centers, mesh, axis, n_lists: int,
                           kind: str):
    """Shared by both distributed builds: per-shard labels + per-list
    counts in one small jit, then one host sync agrees a static bucket
    width every shard uses (pad rows get the overflow label
    ``n_lists``, excluded from the counts)."""
    from raft_tpu.neighbors.ivf_flat import _coarse_scores

    def build():
        def count_local(x_loc, ids_loc, c):
            lbl = jnp.argmin(_coarse_scores(x_loc, c, kind), axis=1)
            lbl = jnp.where(ids_loc >= 0, lbl, n_lists)
            cnt = jax.ops.segment_sum(jnp.ones_like(lbl, jnp.int32), lbl,
                                      num_segments=n_lists + 1)[:n_lists]
            return lbl.astype(jnp.int32), cnt

        return jax.jit(jax.shard_map(
            count_local, mesh=mesh, in_specs=(P(axis, None), P(axis), P()),
            out_specs=(P(axis), P(axis))))

    # keyed on everything the closure bakes in (GL002: a fresh callable
    # per build re-traced the shard_map every call; amortized ≠ free —
    # repeated builds on one mesh now reuse ONE compiled program)
    counted = _shmap_plan(("count_agree", mesh, axis, n_lists, kind),
                          build)
    c_rep = jax.device_put(centers, NamedSharding(mesh, P()))
    labels_s, counts = counted(xs, ids_s, c_rep)
    ml = int(jax.device_get(jnp.max(counts.reshape(
        mesh.shape[axis], n_lists))))
    ml = max(8, -(-ml // 8) * 8)
    return labels_s, ml, c_rep


def distributed_ivf_flat_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
) -> DistributedIvfFlat:
    """Build a row-sharded IVF-Flat index directly on the mesh: MNMG
    kmeans for the coarse centers, then per-shard label + bucketize of
    the shard's own rows (reference build = train + partition,
    ivf_flat_build.cuh:228, distributed per SURVEY.md §3.3)."""
    from raft_tpu.neighbors.ivf_flat import (IndexParams, _bucketize_static,
                                             _coarse_scores, _metric_kind)
    from raft_tpu.parallel.kmeans import distributed_kmeans_fit
    params = params or IndexParams()
    expects(mesh is not None, "distributed build: mesh is required")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct,
                              DistanceType.CosineExpanded),
            "distributed ivf_flat build: unsupported metric %s",
            params.metric)
    expects(params.storage_dtype == "float32",
            "distributed ivf_flat build: narrow list storage (%s) is not "
            "implemented for sharded parts yet; use float32",
            params.storage_dtype)
    x = as_array(x).astype(jnp.float32)
    if params.metric == DistanceType.CosineExpanded:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                            1e-30)
    n, dim = x.shape
    n_lists = params.n_lists
    expects(n_lists <= n, "distributed build: n_lists > n_samples")

    # 1) coarse centers: the MNMG Lloyd loop over the row-sharded data
    centers, _, _ = distributed_kmeans_fit(
        x, KMeansParams(n_clusters=n_lists,
                        max_iter=params.kmeans_n_iters), mesh, axis)

    xs, ids_s = _shard_rows(x, mesh, axis)
    kind = _metric_kind(params.metric)

    # 2) per-shard labels + one host sync agreeing the bucket width
    labels_s, ml, _ = _label_and_agree_width(xs, ids_s, centers, mesh,
                                             axis, n_lists, kind)

    # 3) per-shard bucketize with global ids (static shapes everywhere)
    def build_bucketed():
        def bucket_local(x_loc, lbl_loc, ids_loc):
            # overflow label n_lists went to pads; fold them to list 0
            # with id -1 (dropped by the id mask at search)
            lbl = jnp.where(lbl_loc < n_lists, lbl_loc, 0)
            safe_ids = jnp.where(lbl_loc < n_lists, ids_loc, -1)
            data, idx, norms, _ = _bucketize_static(
                x_loc, lbl, safe_ids, n_lists, ml)
            return data[None], idx[None], norms[None]

        return jax.jit(jax.shard_map(
            bucket_local, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis)),
            out_specs=(P(axis, None, None, None), P(axis, None, None),
                       P(axis, None, None))))

    bucketed = _shmap_plan(("flat_dbucket", mesh, axis, n_lists, ml),
                           build_bucketed)
    pdata, pidx, pnorms = bucketed(xs, labels_s, ids_s)
    return DistributedIvfFlat(
        centers=centers, parts_data=pdata, parts_indices=pidx,
        parts_norms=pnorms, metric=params.metric, size=n, mesh=mesh,
        axis=axis)


def distributed_ivf_flat_search_parts(
    dindex: DistributedIvfFlat, queries, k: int, params=None,
    comms=None,
) -> Tuple[jax.Array, jax.Array]:
    """Search a row-sharded multi-part index: every shard probes the
    same global centers, scans its partial probed lists, and the
    per-shard top-k merge runs over the comm axis. The scanned set
    equals the single-host index's at matched n_probes."""
    from raft_tpu.neighbors.ivf_flat import (SearchParams, _coarse_scores,
                                             _metric_kind, _postprocess,
                                             _score_probe)
    params = params or SearchParams()
    mesh, axis = dindex.mesh, dindex.axis
    q = as_array(queries).astype(jnp.float32)
    expects(q.shape[1] == dindex.dim, "distributed search: dim mismatch")
    if dindex.metric == DistanceType.CosineExpanded:
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True),
                            1e-30)
    kind = _metric_kind(dindex.metric)
    n_probes = min(params.n_probes, dindex.n_lists)
    sqrt = dindex.metric in (DistanceType.L2SqrtExpanded,
                             DistanceType.L2SqrtUnexpanded)

    comms = comms if comms is not None else get_comms(mesh, axis)

    def build():
        def local(centers, pdata, pidx, pnorms, q_rep):
            qq = jnp.sum(q_rep * q_rep, axis=1)
            coarse = _coarse_scores(q_rep, centers, kind)
            _, probes = lax.top_k(-coarse, n_probes)

            def get_probe(p):
                return _score_probe(q_rep, qq, pdata[0], pnorms[0],
                                    pidx[0], probes[:, p], 1.0,
                                    kind=kind)

            d, i = _fine_scan(q_rep, get_probe, k, n_probes, axis)
            if sqrt:
                d = jnp.sqrt(jnp.maximum(d, 0.0))
            return _global_merge(comms, axis, d, i, k)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axis, None, None, None),
                      P(axis, None, None), P(axis, None, None), P()),
            out_specs=(P(), P())))

    n_shards = mesh.shape[axis]
    with spans.span("raft.parallel.ivf.search", family="ivf_flat_parts",
                    nq=int(q.shape[0]), k=k, n_probes=n_probes,
                    axis=axis, n_shards=n_shards):
        shmapped = _shmap_plan(
            ("flat_parts", mesh, axis, k, n_probes, kind, sqrt, comms),
            build)
        q_rep = jax.device_put(q, NamedSharding(mesh, P()))
        centers_rep = jax.device_put(dindex.centers,
                                     NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        d, i = shmapped(centers_rep, dindex.parts_data,
                        dindex.parts_indices, dindex.parts_norms, q_rep)
        _rank_spans(n_shards, t0, time.perf_counter() - t0)
    return _postprocess(d, dindex.metric), i


@dataclass
class DistributedIvfPq:
    """Row-sharded multi-part IVF-PQ index: compressed codes are the
    only per-row payload, sharded over ``mesh[axis]``; centers,
    rotation, and codebooks are replicated (they are O(n_lists·dim),
    not O(n))."""

    centers: jax.Array        # (n_lists, dim) replicated
    centers_rot: jax.Array    # (n_lists, rot_dim) replicated
    rotation_matrix: jax.Array
    pq_centers: jax.Array     # (pq_dim, n_codes, pq_len) replicated
    parts_codes: jax.Array    # (n_shards, n_lists, ml, pq_dim) u8 sharded
    parts_indices: jax.Array  # (n_shards, n_lists, ml) int32 global ids
    parts_norms: jax.Array    # (n_shards, n_lists, ml) exact code norms
    metric: "DistanceType"
    pq_bits: int
    size: int
    mesh: jax.sharding.Mesh
    axis: str

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def pq_dim(self) -> int:
        return self.pq_centers.shape[0]


def distributed_ivf_pq_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
    seed: int = 0,
) -> DistributedIvfPq:
    """Build a row-sharded IVF-PQ index on the mesh (reference
    ivf_pq_build.cuh:908/605 distributed per SURVEY.md §3.3): MNMG
    kmeans coarse centers; rotation + per-subspace codebooks trained on
    a BOUNDED subsample (≤ 2^15 rows — O(1) in the dataset size, the
    reference's own trainset-subsampling strategy); then each shard
    encodes and buckets its own rows. Codes never leave their shard."""
    from raft_tpu.neighbors.ivf_flat import (_bucketize_static,
                                             _coarse_scores, _metric_kind)
    from raft_tpu.neighbors.ivf_pq import (
        IndexParams, _encode, _train_codebooks_per_subspace,
        make_rotation_matrix)
    from raft_tpu.parallel.kmeans import distributed_kmeans_fit
    params = params or IndexParams()
    expects(mesh is not None, "distributed build: mesh is required")
    from raft_tpu.neighbors.ivf_pq import CodebookGen
    expects(params.codebook_kind == CodebookGen.PER_SUBSPACE,
            "distributed_ivf_pq_build: PER_CLUSTER codebooks are not "
            "supported on the distributed path yet — build single-host "
            "or use PER_SUBSPACE")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct),
            "distributed ivf_pq build: L2-family and InnerProduct "
            "metrics are supported (got %s)", params.metric)
    x = as_array(x).astype(jnp.float32)
    n, dim = x.shape
    n_lists = params.n_lists
    expects(n_lists <= n, "distributed build: n_lists > n_samples")
    expects(n >= (1 << params.pq_bits),
            "distributed ivf_pq build: need at least 2^pq_bits (%d) "
            "training rows", 1 << params.pq_bits)
    pq_dim = params.pq_dim if params.pq_dim > 0 else max(1, dim // 4)
    rot_dim = ((dim + pq_dim - 1) // pq_dim) * pq_dim
    pq_len = rot_dim // pq_dim
    n_codes = 1 << params.pq_bits
    kind = _metric_kind(params.metric)

    # 1) coarse centers: MNMG Lloyd over the row-sharded data
    centers, _, _ = distributed_kmeans_fit(
        x, KMeansParams(n_clusters=n_lists,
                        max_iter=params.kmeans_n_iters), mesh, axis)
    rot = make_rotation_matrix(dim, rot_dim,
                               params.force_random_rotation,
                               seed=seed + 1)
    centers_rot = jnp.matmul(centers, rot.T,
                             precision=matmul_precision())

    # 2) codebooks on a bounded subsample (replicated training)
    m = min(n, 1 << 15)
    # host-side draw (util.host_sample): a traced choice(replace=False)
    # is an n-wide sort compile (minutes at 10M+ rows)
    sel = sample_rows(n, m, seed + 3) if m < n else jnp.arange(n)
    xs_cb = x[sel]
    lbl_cb = jnp.argmin(_coarse_scores(xs_cb, centers, kind), axis=1)
    resid_cb = jnp.matmul(xs_cb - centers[lbl_cb], rot.T,
                          precision=matmul_precision())
    pq_centers = _train_codebooks_per_subspace(
        resid_cb, pq_dim, pq_len, n_codes, params.kmeans_n_iters,
        seed + 2, reseed_threshold=params.reseed_threshold)

    xs, ids_s = _shard_rows(x, mesh, axis)

    # 3) per-shard labels + one host sync agreeing the bucket width
    labels_s, ml, c_rep = _label_and_agree_width(xs, ids_s, centers,
                                                 mesh, axis, n_lists,
                                                 kind)

    # 4) per-shard encode + bucketize the CODES (u8) with global ids
    def build_encoded():
        def encode_local(x_loc, lbl_loc, ids_loc, c, r, books):
            from raft_tpu.neighbors.ivf_pq import _code_norms
            lbl = jnp.where(lbl_loc < n_lists, lbl_loc, 0)
            safe_ids = jnp.where(lbl_loc < n_lists, ids_loc, -1)
            resid_rot = jnp.matmul(x_loc - c[lbl], r.T,
                                   precision=matmul_precision())
            codes = _encode(resid_rot, books).astype(jnp.float32)
            data, idx, _, _ = _bucketize_static(codes, lbl, safe_ids,
                                                n_lists, ml)
            codes_b = data.astype(jnp.uint8)
            norms = _code_norms(codes_b, books, idx)
            return codes_b[None], idx[None], norms[None]

        return jax.jit(jax.shard_map(
            encode_local, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis), P(), P(), P()),
            out_specs=(P(axis, None, None, None), P(axis, None, None),
                       P(axis, None, None))))

    encoded = _shmap_plan(("pq_dencode", mesh, axis, n_lists, ml),
                          build_encoded)
    rep = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
    pcodes, pidx, pnorms = encoded(xs, labels_s, ids_s, c_rep,
                                   rep(rot), rep(pq_centers))
    return DistributedIvfPq(
        centers=centers, centers_rot=centers_rot, rotation_matrix=rot,
        pq_centers=pq_centers, parts_codes=pcodes, parts_indices=pidx,
        parts_norms=pnorms, metric=params.metric,
        pq_bits=params.pq_bits, size=n, mesh=mesh, axis=axis)


def distributed_ivf_pq_search_parts(
    dindex: DistributedIvfPq, queries, k: int, params=None,
    comms=None,
) -> Tuple[jax.Array, jax.Array]:
    """Search a row-sharded multi-part IVF-PQ index: per shard, probed
    code blocks decode on the fly (transient, probe-major) and score
    against the rotated query residual; shards merge over the comm
    axis. Codes stay compressed at rest on every shard.

    Decode is one-hot × codebook on the MXU (the ``_pq_scan_kernel``
    trick, probe-major form) — per-lane LUT gathers lower to the TPU
    scalar core and measured ~100× slower in rounds 1-2. The operand
    dtype follows ``params.lut_dtype`` (bf16 one-pass / f32 highest /
    float8_e4m3fn-quantized books computed in bf16)."""
    from raft_tpu.neighbors.ivf_flat import (_coarse_scores, _metric_kind,
                                             _postprocess)
    from raft_tpu.neighbors.ivf_pq import SearchParams
    params = params or SearchParams()
    mesh, axis = dindex.mesh, dindex.axis
    q = as_array(queries).astype(jnp.float32)
    expects(q.shape[1] == dindex.dim, "distributed search: dim mismatch")
    kind = _metric_kind(dindex.metric)
    n_probes = min(params.n_probes, dindex.n_lists)
    sqrt = dindex.metric in (DistanceType.L2SqrtExpanded,
                             DistanceType.L2SqrtUnexpanded)
    comms = comms if comms is not None else get_comms(mesh, axis)
    pq_dim = dindex.pq_dim
    n_codes = 1 << dindex.pq_bits
    lut_dt = jnp.dtype(params.lut_dtype)
    expects(lut_dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                       jnp.dtype(jnp.float8_e4m3fn)),
            "distributed ivf_pq search: lut_dtype must be "
            "float32|bfloat16|float8_e4m3fn")
    f32_lut = lut_dt == jnp.dtype(jnp.float32)
    op_dt = jnp.float32 if f32_lut else jnp.bfloat16
    op_prec = matmul_precision() if f32_lut else None

    def _local(centers, centers_rot, rot, books, pcodes, pidx, pnorms,
               q_rep, comms):
        coarse = _coarse_scores(q_rep, centers, kind)
        _, probes = lax.top_k(-coarse, n_probes)
        q_rot = jnp.matmul(q_rep, rot.T, precision=matmul_precision())
        if lut_dt == jnp.dtype(jnp.float8_e4m3fn):
            # NOTE: pnorms stay exact-over-f32-books here (recomputing
            # over quantized books would decode every shard's codes);
            # the resulting distance error is within the fp8 tier's own
            # quantization class, matching the reference fp8-LUT contract
            books_op = books.astype(jnp.float8_e4m3fn).astype(op_dt)
        else:
            books_op = books.astype(op_dt)

        def get_probe(p):
            list_id = probes[:, p]
            codes_p = pcodes[0][list_id].astype(jnp.int32)  # (nq, ml, s)
            ids = pidx[0][list_id]
            # transient decode of the probed blocks only: per subspace,
            # one-hot (nq, ml, C) × book (C, pl) rides the MXU
            import jax.nn as jnn
            strips = [
                jnp.einsum("qlc,cp->qlp",
                           jnn.one_hot(codes_p[..., s], n_codes,
                                       dtype=op_dt),
                           books_op[s], precision=op_prec,
                           preferred_element_type=jnp.float32)
                for s in range(pq_dim)]
            dec = jnp.concatenate(strips, axis=-1)        # (nq, ml, rot)
            if kind == "ip":
                full = dec + centers_rot[list_id][:, None, :]
                ip = jnp.einsum("qd,qld->ql", q_rot, full,
                                preferred_element_type=jnp.float32)
                return jnp.where(ids >= 0, -ip, jnp.inf), ids
            resid = q_rot - centers_rot[list_id]
            ip = jnp.einsum("qd,qld->ql", resid, dec,
                            preferred_element_type=jnp.float32)
            rr = jnp.sum(resid * resid, axis=1)
            d = rr[:, None] + pnorms[0][list_id] - 2.0 * ip
            return jnp.where(ids >= 0, jnp.maximum(d, 0.0), jnp.inf), ids

        d, i = _fine_scan(q_rep, get_probe, k, n_probes, axis)
        if sqrt:
            d = jnp.sqrt(jnp.maximum(d, 0.0))
        return _global_merge(comms, axis, d, i, k)

    def build():
        local = functools.partial(_local, comms=comms)
        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(axis, None, None, None),
                      P(axis, None, None), P(axis, None, None), P()),
            out_specs=(P(), P())))

    n_shards = mesh.shape[axis]
    with spans.span("raft.parallel.ivf.search", family="ivf_pq_parts",
                    nq=int(q.shape[0]), k=k, n_probes=n_probes,
                    axis=axis, n_shards=n_shards):
        shmapped = _shmap_plan(
            ("pq_parts", mesh, axis, k, n_probes, kind, sqrt, pq_dim,
             n_codes, lut_dt.name, comms), build)
        rep = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        d, i = shmapped(rep(dindex.centers), rep(dindex.centers_rot),
                        rep(dindex.rotation_matrix),
                        rep(dindex.pq_centers), dindex.parts_codes,
                        dindex.parts_indices, dindex.parts_norms,
                        rep(q))
        _rank_spans(n_shards, t0, time.perf_counter() - t0)
    return _postprocess(d, dindex.metric), i


@dataclass
class DistributedIvfBq:
    """Row-sharded multi-part IVF-BQ index (the 1-bit tier of
    ``neighbors/ivf_bq.py``, sharded like :class:`DistributedIvfFlat`).
    ``raw`` optionally holds the FULL dataset host-side for exact
    rescoring after the global estimator merge."""

    centers: jax.Array        # (n_lists, dim) replicated
    centers_rot: jax.Array    # (n_lists, dim) replicated
    rotation_matrix: jax.Array
    parts_bits: jax.Array     # (n_shards, n_lists, ml, w) uint32
    parts_norms2: jax.Array   # (n_shards, n_lists, ml)
    parts_scales: jax.Array   # (n_shards, n_lists, ml)
    parts_indices: jax.Array  # (n_shards, n_lists, ml) global ids
    metric: "DistanceType"
    size: int
    mesh: jax.sharding.Mesh
    axis: str
    raw: "object" = None      # host numpy (n, dim) f32 or None
    # lazy device copy of `raw` (ivf_bq.resolve_raw_device contract);
    # replicated over the mesh by the rescore gather — the "auto" HBM
    # budget is the guard at multi-chip scale
    raw_dev: "object" = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]


def distributed_ivf_bq_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
) -> DistributedIvfBq:
    """Row-sharded IVF-BQ build: MNMG kmeans coarse phase, then each
    shard sign-encodes and bucketizes its own rows — there is no
    codebook, so beyond the coarse phase the build is one shard-local
    jit (the binary tier's build-speed story survives sharding)."""
    from raft_tpu.neighbors.ivf_bq import IndexParams, _pack_bits
    from raft_tpu.neighbors.ivf_flat import _bucketize_static
    from raft_tpu.neighbors.ivf_pq import make_rotation_matrix
    from raft_tpu.parallel.kmeans import distributed_kmeans_fit
    params = params or IndexParams()
    expects(mesh is not None, "distributed build: mesh is required")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded),
            "distributed ivf_bq build: L2 metrics only (got %s)",
            params.metric)
    x = as_array(x).astype(jnp.float32)
    n, dim = x.shape
    n_lists = params.n_lists
    expects(n_lists <= n, "distributed build: n_lists > n_samples")

    centers, _, _ = distributed_kmeans_fit(
        x, KMeansParams(n_clusters=n_lists,
                        max_iter=params.kmeans_n_iters), mesh, axis)
    rot = make_rotation_matrix(dim, dim, force_random=True)

    xs, ids_s = _shard_rows(x, mesh, axis)
    labels_s, ml, c_rep = _label_and_agree_width(
        xs, ids_s, centers, mesh, axis, n_lists, "l2")
    rot_rep = jax.device_put(rot, NamedSharding(mesh, P()))
    w = -(-dim // 32)

    def build_enc():
        def encode_local(x_loc, lbl_loc, ids_loc, c, rt):
            lbl = jnp.where(lbl_loc < n_lists, lbl_loc, 0)
            safe_ids = jnp.where(lbl_loc < n_lists, ids_loc, -1)
            # full-precision rotation, like ivf_bq.build: default-
            # precision TPU matmul flips signs of near-zero rotated
            # components
            r = jnp.matmul(x_loc - c[lbl], rt.T,
                           precision=matmul_precision())
            # int32 payload (see ivf_bq.build): bit words must not ride
            # as f32 bitcasts — NaN-pattern canonicalization hazard
            payload = jnp.concatenate(
                [lax.bitcast_convert_type(_pack_bits(r), jnp.int32),
                 lax.bitcast_convert_type(
                     jnp.sum(r * r, axis=1)[:, None], jnp.int32),
                 lax.bitcast_convert_type(
                     jnp.mean(jnp.abs(r), axis=1)[:, None], jnp.int32)],
                axis=1)
            data, idx, _, _ = _bucketize_static(payload, lbl, safe_ids,
                                                n_lists, ml,
                                                compute_norms=False)
            return data[None], idx[None]

        return jax.jit(jax.shard_map(
            encode_local, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis), P(), P()),
            out_specs=(P(axis, None, None, None), P(axis, None, None))))

    enc = _shmap_plan(("bq_dencode", mesh, axis, n_lists, ml), build_enc)
    payload, pidx = enc(xs, labels_s, ids_s, c_rep, rot_rep)
    bits = lax.bitcast_convert_type(payload[..., :w], jnp.uint32)
    raw = None
    if params.keep_raw:
        import numpy as _np
        raw = _np.asarray(jax.device_get(x))
    return DistributedIvfBq(
        centers=centers, centers_rot=centers @ rot.T,
        rotation_matrix=rot, parts_bits=bits,
        parts_norms2=lax.bitcast_convert_type(payload[..., w],
                                              jnp.float32),
        parts_scales=lax.bitcast_convert_type(payload[..., w + 1],
                                              jnp.float32),
        parts_indices=pidx, metric=params.metric, size=n, mesh=mesh,
        axis=axis, raw=raw)


def distributed_ivf_bq_search_parts(
    dindex: DistributedIvfBq, queries, k: int, params=None,
    comms=None,
) -> Tuple[jax.Array, jax.Array]:
    """Search the row-sharded binary index: every shard scans its
    partial probed lists with the 1-bit estimator, the per-shard
    candidates merge over the comm axis, and (when raw vectors exist)
    the merged survivors are exactly re-ranked host-side."""
    from raft_tpu.neighbors.ivf_bq import SearchParams, _unpack_pm1
    from raft_tpu.neighbors.ivf_flat import _coarse_scores
    params = params or SearchParams()
    mesh, axis = dindex.mesh, dindex.axis
    q = as_array(queries).astype(jnp.float32)
    expects(q.shape[1] == dindex.dim, "distributed search: dim mismatch")
    n_probes = min(params.n_probes, dindex.n_lists)
    rescore = params.rescore_factor > 0 and dindex.raw is not None
    kk = max(params.rescore_factor, 1) * k
    dim = dindex.dim
    comms = comms if comms is not None else get_comms(mesh, axis)

    def build():
        def local(centers, centers_rot, rot, pbits, pn2, psc, pidx,
                  q_rep):
            coarse = _coarse_scores(q_rep, centers, "l2")
            _, probes = lax.top_k(-coarse, n_probes)
            q_rot = q_rep @ rot.T

            def get_probe(p):
                list_id = probes[:, p]                     # (nq,)
                pm1 = _unpack_pm1(pbits[0][list_id], dim)  # (nq, ml, d)
                ql = q_rot - centers_rot[list_id]          # (nq, d)
                ip = jnp.einsum("qld,qd->ql", pm1,
                                ql.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
                qq = jnp.sum(ql * ql, axis=1)[:, None]
                est = qq + pn2[0][list_id] - 2.0 * psc[0][list_id] * ip
                ids = pidx[0][list_id]
                return jnp.where(ids >= 0, est, jnp.inf), ids

            d, i = _fine_scan(q_rep, get_probe, kk, n_probes, axis)
            return _global_merge(comms, axis, d, i, kk)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis, None, None, None),
                      P(axis, None, None), P(axis, None, None),
                      P(axis, None, None), P()),
            out_specs=(P(), P())))

    n_shards = mesh.shape[axis]
    with spans.span("raft.parallel.ivf.search", family="ivf_bq_parts",
                    nq=int(q.shape[0]), k=k, n_probes=n_probes,
                    axis=axis, n_shards=n_shards, rescore=rescore):
        shmapped = _shmap_plan(
            ("bq_parts", mesh, axis, kk, n_probes, dim, comms), build)
        rep = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        d_est, ids = shmapped(rep(dindex.centers),
                              rep(dindex.centers_rot),
                              rep(dindex.rotation_matrix),
                              dindex.parts_bits, dindex.parts_norms2,
                              dindex.parts_scales, dindex.parts_indices,
                              rep(q))
        _rank_spans(n_shards, t0, time.perf_counter() - t0)
        from raft_tpu.neighbors.ivf_bq import (finish_search,
                                               resolve_raw_device)
        raw_dev = (resolve_raw_device(dindex, params.rescore_on_device)
                   if rescore else None)
        return finish_search(d_est, ids, dindex.raw, q, k,
                             metric=dindex.metric, rescore=rescore,
                             raw_dev=raw_dev)


# ---------------------------------------------------------------------------
# Sharded BUILD into the SERVING (list-sharded) layout (ISSUE 4 tentpole):
# the multi-part builds above keep rows where they land (each shard serves
# its own partial lists); these builds go one step further and land the
# index DIRECTLY in the list-sharded layout that `shard_ivf_*` serves from
# (`distributed_ivf_flat_search` / `distributed_ivf_pq_search`). Coarse
# centers train data-parallel (`balanced_kmeans_sharded`: per-shard
# sufficient statistics + psum each EM sweep — the raft::comms MNMG
# pattern); every shard labels and encodes its OWN rows; then ONE
# all_to_all moves each row's encoded payload to the shard that serves
# its list (`_exchange_rows`), where it lands in the final padded list.
# No O(n) array ever materializes on a single device, and the build
# output needs no reshard step before serving.
# ---------------------------------------------------------------------------

import numpy as np


def _train_coarse_sharded(x, params, mesh, axis: str, seed: int):
    """Coarse-center phase shared by the list-layout sharded builds:
    build()'s trainset subsample (host-side draw, same seed policy) fed
    to the data-parallel balanced trainer."""
    from raft_tpu.cluster.kmeans_balanced import balanced_kmeans_sharded
    n = x.shape[0]
    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    if n_train < n:
        from raft_tpu.util.host_sample import take_rows
        trainset = take_rows(x, sample_rows(n, n_train, seed))
    else:
        trainset = x
    with obs.timed("raft.build.sharded.train"):
        if params.n_lists > 16384:
            # beyond the flat-EM compile ceiling the single-device
            # trainer's two-level hierarchy applies; the sharded flat EM
            # would be one giant compile (kmeans_balanced rationale)
            from raft_tpu.cluster.kmeans_balanced import build_hierarchical
            return build_hierarchical(
                trainset, params.n_lists, params.kmeans_n_iters,
                seed=seed,
                kernel_precision=params.kmeans_kernel_precision)
        return balanced_kmeans_sharded(
            trainset, params.n_lists, params.kmeans_n_iters, seed=seed,
            kernel_precision=params.kmeans_kernel_precision,
            mesh=mesh, axis=axis)


def _label_and_widths(xs, ids_s, centers, mesh, axis, n_lists: int,
                      kind: str):
    """`_label_and_agree_width` extended for list-layout builds: ONE
    host sync agrees both widths — ``width`` bounds the rows any shard
    sends any one shard (the exchange's send slot), ``ml_global`` any
    list's TOTAL count (the serving bucket) — and returns the global
    per-list totals (the index's ``list_sizes``)."""
    from raft_tpu.neighbors.ivf_flat import _coarse_scores

    def build():
        def count_local(x_loc, ids_loc, c):
            lbl = jnp.argmin(_coarse_scores(x_loc, c, kind), axis=1)
            lbl = jnp.where(ids_loc >= 0, lbl, n_lists)
            cnt = jax.ops.segment_sum(jnp.ones_like(lbl, jnp.int32), lbl,
                                      num_segments=n_lists + 1)[:n_lists]
            return lbl.astype(jnp.int32), cnt

        return jax.jit(jax.shard_map(
            count_local, mesh=mesh, in_specs=(P(axis, None), P(axis), P()),
            out_specs=(P(axis), P(axis))))

    counted = _shmap_plan(("count_widths", mesh, axis, n_lists, kind),
                          build)
    c_rep = jax.device_put(centers, NamedSharding(mesh, P()))
    labels_s, counts = counted(xs, ids_s, c_rep)
    n_shards = mesh.shape[axis]
    c = np.asarray(jax.device_get(counts)).reshape(n_shards, n_lists)
    # lists are dealt to shards in contiguous blocks: (source, dest) runs
    runs = c.reshape(n_shards, n_shards, n_lists // n_shards).sum(axis=2)
    width = max(8, -(-int(runs.max()) // 8) * 8)
    totals = c.sum(axis=0)
    ml_global = max(8, -(-int(totals.max()) // 8) * 8)
    return labels_s, width, ml_global, totals.astype(np.int32), c_rep


def _exchange_rows(payload, lbl, ids, n_lists: int, n_shards: int,
                   axis: str, width: int, ml_global: int):
    """Inside shard_map: move each of this shard's rows to the shard
    that serves its list and land it in the list-sharded serving layout
    (nl_local, ml_global, ...) with its ids (-1 in empty slots).

    Lists are dealt to shards in contiguous blocks, so a stable sort of
    the rows by list makes each destination's rows one run. The send
    buffer pads each (source, destination) run to ``width`` — about
    rows/shards, whatever the list-size skew — and ONE all_to_all each
    of payload, list and id moves them; only the serving layout pads to
    the largest list. ``lbl`` is n_lists for padding rows (never sent).
    Within a list, rows keep source-shard-major, row order."""
    nl_local = n_lists // n_shards
    order = jnp.argsort(lbl, stable=True)
    s_lbl = lbl[order]
    dest = s_lbl // nl_local                     # n_shards: padding rows
    cnt = jax.ops.segment_sum(jnp.ones_like(dest), dest,
                              num_segments=n_shards + 1)[:n_shards]
    j = jnp.arange(width, dtype=jnp.int32)
    ok = j[None, :] < cnt[:, None]
    at = jnp.where(ok, (jnp.cumsum(cnt) - cnt)[:, None] + j[None, :], 0)
    rows = order[at]                                         # (S, W)
    base = jnp.arange(n_shards, dtype=jnp.int32)[:, None] * nl_local
    send = (payload[rows],
            jnp.where(ok, s_lbl[at] - base, nl_local),
            jnp.where(ok, ids[rows], -1))
    recv, r_lbl, r_ids = (
        lax.all_to_all(a, axis, 0, 0, tiled=False).reshape(
            (n_shards * width,) + a.shape[2:]) for a in send)
    # slot of each received row within its list, in received
    # (source-major) order; the serving layout gathers from it
    order = jnp.argsort(r_lbl, stable=True)
    s_lbl = r_lbl[order]
    cnt = jax.ops.segment_sum(jnp.ones_like(r_lbl), r_lbl,
                              num_segments=nl_local + 1)
    pos = (jnp.arange(n_shards * width, dtype=jnp.int32)
           - (jnp.cumsum(cnt) - cnt)[s_lbl])
    slot = jnp.where((s_lbl < nl_local) & (pos < ml_global),
                     s_lbl * ml_global + pos, nl_local * ml_global)
    src = jnp.full((nl_local * ml_global,), n_shards * width,
                   jnp.int32).at[slot].set(order, mode="drop")
    data = jnp.take(recv, src, axis=0, mode="fill", fill_value=0)
    idx = jnp.take(r_ids, src, axis=0, mode="fill", fill_value=-1)
    return (data.reshape((nl_local, ml_global) + payload.shape[1:]),
            idx.reshape(nl_local, ml_global))


def sharded_ivf_flat_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
    seed: int = 0,
):
    """Build an IVF-Flat index DIRECTLY INTO the list-sharded serving
    layout (the :func:`shard_ivf_flat` layout): data-parallel balanced
    k-means for the coarse centers, per-shard label + bucketize of each
    shard's own rows, then one all_to_all lands every list on the shard
    that serves it — no single-device bucketize bottleneck. Returns a
    standard ``ivf_flat.Index`` whose arrays are sharded over
    ``mesh[axis]``, served as-is by :func:`distributed_ivf_flat_search`
    (or gathered for single-chip serving)."""
    from raft_tpu.neighbors.ivf_flat import Index, IndexParams, _metric_kind
    params = params or IndexParams()
    expects(mesh is not None, "sharded build: mesh is required")
    n_shards = mesh.shape[axis]
    n_lists = params.n_lists
    expects(n_lists % n_shards == 0,
            "sharded_ivf_flat_build: n_lists=%d not divisible by %d "
            "shards", n_lists, n_shards)
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct,
                              DistanceType.CosineExpanded),
            "sharded ivf_flat build: unsupported metric %s",
            params.metric)
    expects(params.storage_dtype == "float32",
            "sharded ivf_flat build: narrow list storage (%s) is not "
            "implemented for sharded lists yet; use float32",
            params.storage_dtype)
    x = as_array(x).astype(jnp.float32)
    if params.metric == DistanceType.CosineExpanded:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                            1e-30)
    n, dim = x.shape
    expects(n_lists <= n, "sharded build: n_lists > n_samples")
    kind = _metric_kind(params.metric)

    with spans.span("raft.build.sharded", family="ivf_flat", rows=n,
                    n_lists=n_lists, n_shards=n_shards):
        obs.counter("raft.build.sharded.total", family="ivf_flat").inc()
        obs.counter("raft.build.sharded.rows", family="ivf_flat").inc(n)
        centers = _train_coarse_sharded(x, params, mesh, axis, seed)
        xs, ids_s = _shard_rows(x, mesh, axis)
        labels_s, width, ml_global, totals, _ = _label_and_widths(
            xs, ids_s, centers, mesh, axis, n_lists, kind)

        def build():
            def local(x_loc, lbl_loc, ids_loc):
                d2, i2 = _exchange_rows(x_loc, lbl_loc, ids_loc, n_lists,
                                        n_shards, axis, width, ml_global)
                norms = jnp.sum(d2 * d2, axis=2)
                return d2, i2, jnp.where(i2 >= 0, norms, 0.0)

            return jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis)),
                out_specs=(P(axis, None, None), P(axis, None),
                           P(axis, None))))

        with obs.timed("raft.build.sharded.encode", family="ivf_flat"):
            fn = _shmap_plan(("flat_lbuild", mesh, axis, n_lists,
                              width, ml_global, dim), build)
            data, idx, norms = fn(xs, labels_s, ids_s)
    return Index(centers=_shard0(centers, mesh, axis), lists_data=data,
                 lists_indices=idx, lists_norms=norms,
                 list_sizes=_shard0(jnp.asarray(totals), mesh, axis),
                 metric=params.metric, size=n, scale=1.0)


def sharded_ivf_pq_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
    seed: int = 0,
):
    """Build an IVF-PQ index directly into the list-sharded serving
    layout (the :func:`shard_ivf_pq` layout, bf16 reconstruction cache
    included): data-parallel coarse centers, replicated rotation +
    codebooks trained on a bounded subsample, per-shard
    label→residual→encode, one all_to_all of the uint8 CODES (the
    compressed payload is the only per-row wire traffic), shard-local
    decode of the reconstruction cache. Served as-is by
    :func:`distributed_ivf_pq_search`."""
    from raft_tpu.neighbors.ivf_flat import _coarse_scores, _metric_kind
    from raft_tpu.neighbors.ivf_pq import (
        CodebookGen, Index, IndexParams, _code_norms, _decode_lists,
        _encode, _train_codebooks_per_subspace, make_rotation_matrix)
    params = params or IndexParams()
    expects(mesh is not None, "sharded build: mesh is required")
    expects(params.codebook_kind == CodebookGen.PER_SUBSPACE,
            "sharded_ivf_pq_build: PER_CLUSTER codebooks are not "
            "supported on the sharded path — build single-host or use "
            "PER_SUBSPACE")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct),
            "sharded ivf_pq build: L2-family and InnerProduct metrics "
            "are supported (got %s)", params.metric)
    n_shards = mesh.shape[axis]
    n_lists = params.n_lists
    expects(n_lists % n_shards == 0,
            "sharded_ivf_pq_build: n_lists=%d not divisible by %d "
            "shards", n_lists, n_shards)
    x = as_array(x).astype(jnp.float32)
    n, dim = x.shape
    expects(n_lists <= n, "sharded build: n_lists > n_samples")
    expects(n >= (1 << params.pq_bits),
            "sharded ivf_pq build: need at least 2^pq_bits (%d) "
            "training rows", 1 << params.pq_bits)
    pq_dim = params.pq_dim if params.pq_dim > 0 else max(1, dim // 4)
    rot_dim = ((dim + pq_dim - 1) // pq_dim) * pq_dim
    pq_len = rot_dim // pq_dim
    n_codes = 1 << params.pq_bits
    kind = _metric_kind(params.metric)

    with spans.span("raft.build.sharded", family="ivf_pq", rows=n,
                    n_lists=n_lists, n_shards=n_shards):
        obs.counter("raft.build.sharded.total", family="ivf_pq").inc()
        obs.counter("raft.build.sharded.rows", family="ivf_pq").inc(n)
        centers = _train_coarse_sharded(x, params, mesh, axis, seed)
        rot = make_rotation_matrix(dim, rot_dim,
                                   params.force_random_rotation,
                                   seed=seed + 1)
        centers_rot = jnp.matmul(centers, rot.T,
                                 precision=matmul_precision())

        # codebooks on a bounded subsample (replicated training, same
        # O(1)-in-n strategy as the multi-part build)
        with obs.timed("raft.build.sharded.codebooks"):
            m = min(n, 1 << 15)
            sel = sample_rows(n, m, seed + 3) if m < n else jnp.arange(n)
            xs_cb = x[sel]
            lbl_cb = jnp.argmin(_coarse_scores(xs_cb, centers, kind),
                                axis=1)
            resid_cb = jnp.matmul(xs_cb - centers[lbl_cb], rot.T,
                                  precision=matmul_precision())
            pq_centers = _train_codebooks_per_subspace(
                resid_cb, pq_dim, pq_len, n_codes,
                params.kmeans_n_iters, seed + 2,
                kernel_precision=params.kmeans_kernel_precision,
                reseed_threshold=params.reseed_threshold)

        xs, ids_s = _shard_rows(x, mesh, axis)
        labels_s, width, ml_global, totals, c_rep = _label_and_widths(
            xs, ids_s, centers, mesh, axis, n_lists, kind)

        def build():
            def local(x_loc, lbl_loc, ids_loc, c, r, books):
                lbl = jnp.where(lbl_loc < n_lists, lbl_loc, 0)
                resid_rot = jnp.matmul(x_loc - c[lbl], r.T,
                                       precision=matmul_precision())
                codes = _encode(resid_rot, books)        # (rows, s) u8
                d2, i2 = _exchange_rows(codes, lbl_loc, ids_loc, n_lists,
                                        n_shards, axis, width, ml_global)
                norms = _code_norms(d2, books, i2)
                dec = _decode_lists(d2, books, i2)
                return d2, i2, norms, dec

            return jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P(), P(),
                          P()),
                out_specs=(P(axis, None, None), P(axis, None),
                           P(axis, None), P(axis, None, None))))

        with obs.timed("raft.build.sharded.encode", family="ivf_pq"):
            fn = _shmap_plan(("pq_lbuild", mesh, axis, n_lists, width,
                              ml_global, pq_dim, n_codes, kind), build)
            rep = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
            codes_b, idx, norms, decoded = fn(xs, labels_s, ids_s, c_rep,
                                              rep(rot), rep(pq_centers))
    return Index(centers=_shard0(centers, mesh, axis),
                 centers_rot=_shard0(centers_rot, mesh, axis),
                 rotation_matrix=jax.device_put(
                     rot, NamedSharding(mesh, P())),
                 pq_centers=jax.device_put(
                     pq_centers, NamedSharding(mesh, P())),
                 codes=codes_b, lists_indices=idx,
                 list_sizes=_shard0(jnp.asarray(totals), mesh, axis),
                 metric=params.metric, pq_bits=params.pq_bits, size=n,
                 codebook_kind=CodebookGen.PER_SUBSPACE,
                 code_norms=norms, decoded=decoded, decoded_norms=norms,
                 raw=(np.asarray(jax.device_get(x))
                      if params.keep_raw else None))


def sharded_ivf_bq_build(
    x, params=None, mesh: jax.sharding.Mesh = None, axis: str = "data",
    seed: int = 0,
):
    """Build an IVF-BQ index into the list-sharded layout: data-parallel
    coarse phase, per-shard sign-encode (no codebook — one subtract +
    sign past the coarse phase), one all_to_all of the int32 bit
    payload. Returns a standard ``ivf_bq.Index``; at the 1-bit tier the
    whole payload usually fits one chip, so callers commonly gather the
    arrays for single-chip serving (the 100M-in-2.8GB story) — the
    sharded build is the BUILD-time scaling, the multi-part search is
    the serving-time one."""
    from raft_tpu.neighbors.ivf_bq import Index, IndexParams, _pack_bits
    from raft_tpu.neighbors.ivf_pq import make_rotation_matrix
    params = params or IndexParams()
    expects(mesh is not None, "sharded build: mesh is required")
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded),
            "sharded ivf_bq build: L2 metrics only (got %s)",
            params.metric)
    n_shards = mesh.shape[axis]
    n_lists = params.n_lists
    expects(n_lists % n_shards == 0,
            "sharded_ivf_bq_build: n_lists=%d not divisible by %d "
            "shards", n_lists, n_shards)
    x = as_array(x).astype(jnp.float32)
    n, dim = x.shape
    expects(n_lists <= n, "sharded build: n_lists > n_samples")
    w = -(-dim // 32)

    with spans.span("raft.build.sharded", family="ivf_bq", rows=n,
                    n_lists=n_lists, n_shards=n_shards):
        obs.counter("raft.build.sharded.total", family="ivf_bq").inc()
        obs.counter("raft.build.sharded.rows", family="ivf_bq").inc(n)
        centers = _train_coarse_sharded(x, params, mesh, axis, seed)
        rot = make_rotation_matrix(dim, dim, force_random=True)
        xs, ids_s = _shard_rows(x, mesh, axis)
        labels_s, width, ml_global, totals, c_rep = _label_and_widths(
            xs, ids_s, centers, mesh, axis, n_lists, "l2")
        rot_rep = jax.device_put(rot, NamedSharding(mesh, P()))

        def build():
            def local(x_loc, lbl_loc, ids_loc, c, rt):
                lbl = jnp.where(lbl_loc < n_lists, lbl_loc, 0)
                # full-precision rotation + int32 bit payload: the
                # ivf_bq.build contracts (sign stability, no f32
                # bitcast canonicalization)
                r = jnp.matmul(x_loc - c[lbl], rt.T,
                               precision=matmul_precision())
                payload = jnp.concatenate(
                    [lax.bitcast_convert_type(_pack_bits(r), jnp.int32),
                     lax.bitcast_convert_type(
                         jnp.sum(r * r, axis=1)[:, None], jnp.int32),
                     lax.bitcast_convert_type(
                         jnp.mean(jnp.abs(r), axis=1)[:, None],
                         jnp.int32)],
                    axis=1)
                return _exchange_rows(payload, lbl_loc, ids_loc, n_lists,
                                      n_shards, axis, width, ml_global)

            return jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(axis, None), P(axis), P(axis), P(), P()),
                out_specs=(P(axis, None, None), P(axis, None))))

        with obs.timed("raft.build.sharded.encode", family="ivf_bq"):
            fn = _shmap_plan(("bq_lbuild", mesh, axis, n_lists, width,
                              ml_global, dim), build)
            payload, idx = fn(xs, labels_s, ids_s, c_rep, rot_rep)
        bits = lax.bitcast_convert_type(payload[..., :w], jnp.uint32)
        norms2 = lax.bitcast_convert_type(payload[..., w], jnp.float32)
        scales = lax.bitcast_convert_type(payload[..., w + 1],
                                          jnp.float32)
        raw = None
        if params.keep_raw:
            raw = np.asarray(jax.device_get(x))
    return Index(centers=_shard0(centers, mesh, axis),
                 centers_rot=_shard0(
                     jnp.matmul(centers, rot.T,
                                precision=matmul_precision()),
                     mesh, axis),
                 rotation_matrix=rot, bits=bits, norms2=norms2,
                 scales=scales, lists_indices=idx,
                 list_sizes=_shard0(jnp.asarray(totals), mesh, axis),
                 metric=params.metric, size=n, raw=raw)
