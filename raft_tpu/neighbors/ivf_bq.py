"""IVF-BQ: binary-quantized inverted-file index (1 bit/dim + per-row
scale), with exact host-side rescoring.

A capability tier beyond the reference's IVF-Flat/IVF-PQ axis
(`spatial/knn/detail/ivf_flat_build.cuh:228`, `ivf_pq_build.cuh:908`
define the build/search structure mirrored here), following the
sign-random-rotation binary-quantization pattern of the IVF-RaBitQ
line of work (PAPERS.md). Why it earns its place on TPU:

* **Memory**: d/8 code bytes + 12 B stats + 4 B id per vector —
  100M×128 ≈ **2.8 GB**, so
  the 100M×128 north-star dataset fits a single v5e chip's HBM with
  room to spare (f32 IVF-Flat needs 51 GB, IVF-PQ codes ≈ 3.2 GB).
* **Build speed**: NO codebook training — beyond the shared coarse
  k-means the encode is one subtract + sign, so build ≈ IVF-Flat's
  coarse phase alone (the reference's PQ `train_per_subset` loop
  disappears entirely).
* **MXU scoring**: the quantized scan is a plain ±1 bf16 matmul —
  decode is shift/mask VPU work and the estimator rides the MXU at
  full tile shapes; no LUT gathers anywhere.

Scoring model (residual form, like IVF-PQ): for query q probing list
l with center c_l, and a stored point x = c_l + r,

    ||q − x||² = ||q_l||² + ||r||² − 2⟨q_l, r⟩,   q_l = q − c_l
    ⟨q_l, r⟩ ≈ s·⟨q_l, sign(r)⟩,                 s = mean(|r|)

(s·sign(r) is the best {±s}^d approximation of r in L2.) Inner
product uses the same decomposition — ``q·x ≈ q·c_l + s·⟨q_rot,
sign(r_rot)⟩`` — and cosine rides the ip core after row
normalization. The estimator ranks candidates; `rescore_factor`·k
survivors are re-ranked with EXACT f32 scores against the raw vectors
kept host-side (the `host_memory` role: device holds bits, host holds
truth), so returned values are exact and recall approaches the probe
ceiling.

Two device tiers, routed by ``ops.dispatch``: the XLA formulation
(chunked decode tiles + einsum) and the Pallas kernel
(``pallas_ivf_scan._bq_scan_kernel``) that unpacks the bits INSIDE
VMEM — the scan then reads 1 bit/dim from HBM instead of 16, the
binary tier's bandwidth headline. Either way the device phase is one
jitted dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.core.precision import matmul_precision
from raft_tpu import obs
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.util.host_sample import sample_rows, take_rows


@dataclass
class IndexParams:
    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 10          # coarse only; there is no codebook
    kmeans_trainset_fraction: float = 0.5
    kmeans_kernel_precision: object = None
    # keep the raw f32 vectors on HOST for exact rescoring (the
    # device never stores them); False = estimator-only index
    keep_raw: bool = True


@dataclass
class SearchParams:
    n_probes: int = 20
    # rescore_factor·k estimator candidates are re-ranked exactly on
    # host; 0 disables rescoring (estimator distances returned). 8 by
    # default: the estimator, not the probe set, is the recall limiter
    # (measured 0.77 → 0.88 recall@10 going 4 → 8 on clustered 50k×64)
    rescore_factor: int = 8
    # inverted-table width policy, as ivf_flat (see _ivf_scan.resolve_cap)
    probe_cap: int = 0
    # per-list candidate bins; 0 = auto (global pool n_probes·bins ≈
    # 32·rescore_factor·k, floor 128/list — see search()); exact scan
    # when ≥ max_list
    scan_bins: int = 0
    # where the exact re-rank runs: "auto" copies the raw corpus to
    # device HBM once (cached on the index) when it fits
    # RAFT_TPU_RESCORE_DEVICE_MB (default 4096) and fuses the rescore
    # into the search dispatch — the host epilogue costs two
    # device↔host round-trips per batch; "never" keeps the
    # host path (the 100M tier, where raw exceeds HBM); "always"
    # forces the device copy regardless of size
    rescore_on_device: str = "auto"


@dataclass
class Index:
    centers: jax.Array          # (n_lists, dim) f32
    centers_rot: jax.Array      # (n_lists, dim) f32 — P @ centers
    rotation_matrix: jax.Array  # (dim, dim) random orthogonal P
    bits: jax.Array             # (n_lists, max_list, words) uint32
    norms2: jax.Array           # (n_lists, max_list) f32  ||r||²
    scales: jax.Array           # (n_lists, max_list) f32  mean|r|
    lists_indices: jax.Array    # (n_lists, max_list) int32, -1 pad
    list_sizes: jax.Array       # (n_lists,) int32
    metric: DistanceType
    size: int
    raw: Optional[np.ndarray] = None   # (n, dim) f32 host copy
    cap_cache: dict = dataclasses.field(default_factory=dict)
    # AOT-compiled serving plans keyed by shape identity — see
    # neighbors/plan.py (not index identity; not serialized)
    plan_cache: dict = dataclasses.field(default_factory=dict,
                                         repr=False, compare=False)
    # lazy device copy of `raw` for the fused rescore tier
    # (SearchParams.rescore_on_device); never serialized
    raw_dev: Optional[jax.Array] = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def words(self) -> int:
        return self.bits.shape[2]


def _pack_bits(r) -> jax.Array:
    """sign bits of (n, d) → (n, ceil(d/32)) uint32, bit i of word w =
    (r[:, 32w+i] >= 0)."""
    n, d = r.shape
    pad = (-d) % 32
    b = (r >= 0).astype(jnp.uint32)
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)))
    b = b.reshape(n, -1, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    return jnp.sum(b << shifts, axis=2, dtype=jnp.uint32)


def _unpack_pm1(words, d: int, dtype=jnp.bfloat16) -> jax.Array:
    """(..., w) uint32 → (..., d) ±1: the decode tile. VPU shift/mask;
    the result feeds the MXU einsum directly."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)[..., :d]
    return (2.0 * flat.astype(dtype) - 1.0).astype(dtype)


_SUPPORTED_METRICS = (DistanceType.L2Expanded,
                      DistanceType.L2SqrtExpanded,
                      DistanceType.InnerProduct,
                      DistanceType.CosineExpanded)


def build(dataset, params: IndexParams = IndexParams(), res=None) -> Index:
    """Coarse k-means + sign-encode residuals (no codebook training —
    the build-speed headline of the binary tier). Cosine datasets are
    row-normalized at build (the ivf_flat/processing.cuh convention) so
    the ip scoring core applies; ``raw`` stores the normalized rows."""
    x = as_array(dataset).astype(jnp.float32)
    n, d = x.shape
    expects(params.n_lists <= n, "ivf_bq.build: n_lists > n_samples")
    expects(params.metric in _SUPPORTED_METRICS,
            "ivf_bq: unsupported metric %s", params.metric)
    if params.metric == DistanceType.CosineExpanded:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                            1e-30)
    obs.counter("raft.ivf_bq.build.total").inc()
    obs.counter("raft.ivf_bq.build.rows").inc(n)
    from raft_tpu.obs import spans
    with spans.span("raft.ivf_bq.build", rows=n,
                    n_lists=params.n_lists), \
            obs.timed("raft.ivf_bq.build"):
        n_train = max(params.n_lists,
                      int(n * params.kmeans_trainset_fraction))
        trainset = (take_rows(x, sample_rows(n, n_train, 0))
                    if n_train < n else x)
        centers = kmeans_balanced.build_hierarchical(
            trainset, params.n_lists, params.kmeans_n_iters,
            kernel_precision=params.kmeans_kernel_precision, res=res)
        labels = kmeans_balanced.predict(x, centers, res=res)
        # random rotation before the sign code (the RaBitQ trick, via
        # the same construction as ivf_pq.make_rotation_matrix):
        # isotropizes residual coordinates so each bit carries ~equal
        # information. Neutral on already-isotropic data (gaussian /
        # post-kmeans blobs measure within noise), load-bearing on
        # anisotropic real features (low-rank/correlated dims would
        # otherwise waste bits); kept unconditional like the reference's
        # PQ rotation
        from raft_tpu.neighbors.ivf_pq import make_rotation_matrix
        rot = make_rotation_matrix(d, d, force_random=True)
        payload, centers_rot = _encode_payload(x, centers, labels, rot)
        from raft_tpu.neighbors.ivf_flat import _bucketize
        bucketed, idx, _, counts = _bucketize(payload, labels,
                                              params.n_lists,
                                              compute_norms=False)
        w = payload.shape[1] - 2
        bits, norms2, scales = _split_payload(bucketed, w)
        raw = np.asarray(jax.device_get(x)) if params.keep_raw else None
    return Index(centers=centers, centers_rot=centers_rot,
                 rotation_matrix=rot, bits=bits, norms2=norms2,
                 scales=scales,
                 lists_indices=idx, list_sizes=counts,
                 metric=params.metric, size=n, raw=raw)


@jax.jit
def _encode_payload(x, centers, labels, rot):
    """Residual rotation + sign-pack + payload assembly as ONE program
    (eagerly this phase was ~20 op-by-op compiles; cold build is
    compile-count-bound).

    Full-precision rotation: the sign code IS the payload, and TPU
    default-precision (single-pass bf16) matmul flips signs of
    near-zero rotated components vs host f32 math — observed on
    hardware 2026-08-02 (bq_roundtrip_check stage 0a).

    The payload is one combined INT32 block (word bit-patterns +
    bitcast norm/scale columns): int32 has no canonicalization hazard,
    unlike f32 whose NaN-patterned bitcasts XLA may rewrite in
    concatenate/gather/scatter (ADVICE r3 #2); the squared-norm pass
    over the payload is skipped outright (compute_norms=False)."""
    r = jnp.matmul(x - centers[labels], rot.T,
                   precision=matmul_precision())
    norms2 = jnp.sum(r * r, axis=1)
    scales = jnp.mean(jnp.abs(r), axis=1)
    words = _pack_bits(r)
    payload = jnp.concatenate(
        [lax.bitcast_convert_type(words, jnp.int32),
         lax.bitcast_convert_type(norms2[:, None], jnp.int32),
         lax.bitcast_convert_type(scales[:, None], jnp.int32)],
        axis=1)
    centers_rot = jnp.matmul(centers, rot.T,
                             precision=matmul_precision())
    return payload, centers_rot


@functools.partial(jax.jit, static_argnames=("w",))
def _split_payload(bucketed, w: int):
    """Bucketed int32 payload → (bits u32, norms2 f32, scales f32)."""
    bits = lax.bitcast_convert_type(bucketed[:, :, :w], jnp.uint32)
    norms2 = lax.bitcast_convert_type(bucketed[:, :, w], jnp.float32)
    scales = lax.bitcast_convert_type(bucketed[:, :, w + 1], jnp.float32)
    return bits, norms2, scales


@functools.partial(jax.jit, static_argnames=("kk", "bins", "n_probes",
                                             "cap", "chunk", "dim",
                                             "kind"))
def _fused_bq_search(queries, centers, centers_rot, rot, bits, norms2,
                     scales, ids, *, kk: int, bins: int, n_probes: int,
                     cap: int, chunk: int, dim: int, kind: str = "l2"):
    """Single-dispatch device phase: coarse GEMM + top-k probes, query
    rotation, probe inversion, chunked decode-tile estimator scan,
    candidate merge. Returns (est dists (nq, kk), global ids (nq, kk))
    — estimator ordering, smaller-is-better (squared-L2 for the l2
    core; NEGATED similarity ``−(q·c_l + s·⟨q_rot, sign(r_rot)⟩)``
    for ip — the x = c_l + r decomposition of q·x)."""
    from raft_tpu.neighbors import _ivf_scan as S
    nq = queries.shape[0]
    n_lists, max_list = ids.shape
    probes = S.coarse_probes(queries, centers, n_probes, kind=kind)
    q_rot = queries @ rot.T      # orthogonal: geometry unchanged
    qmap, inv_pos = S._invert_probes(probes, n_lists, cap)

    n_chunks = n_lists // chunk
    qmap_c = qmap.reshape(n_chunks, chunk, cap)
    bits_c = bits.reshape(n_chunks, chunk, max_list, -1)
    n2_c = norms2.reshape(n_chunks, chunk, max_list)
    sc_c = scales.reshape(n_chunks, chunk, max_list)
    ids_c = ids.reshape(n_chunks, chunk, max_list)
    cent_c = centers_rot.reshape(n_chunks, chunk, dim)

    def one_chunk(args):
        qm, bw, n2, sc, lid, cl = args
        qg = q_rot[jnp.clip(qm, 0, nq - 1)]           # (chunk, cap, d)
        pm1 = _unpack_pm1(bw, dim)                    # (chunk, ML, d) ±1
        if kind == "ip":
            # one-pass bf16 estimator tier on purpose (exact re-rank
            # follows)
            ip = jnp.einsum("gcd,gld->gcl", qg.astype(jnp.bfloat16),
                            pm1, preferred_element_type=jnp.float32,
                            precision=lax.Precision.DEFAULT)
            # q·c_l dominates the estimator: full precision, like the
            # Pallas tier's post-scan correction
            corr = jnp.einsum("gcd,gd->gc", qg, cl,
                              precision=matmul_precision(),
                              preferred_element_type=jnp.float32)
            est = -(corr[:, :, None] + sc[:, None, :] * ip)
        else:
            qsub = qg - cl[:, None, :]
            ip = jnp.einsum("gcd,gld->gcl", qsub.astype(jnp.bfloat16),
                            pm1, preferred_element_type=jnp.float32,
                            precision=lax.Precision.DEFAULT)
            qq = jnp.sum(qsub * qsub, axis=2)         # (chunk, cap)
            est = (qq[:, :, None] + n2[:, None, :]
                   - 2.0 * sc[:, None, :] * ip)       # (chunk, cap, ML)
        est = jnp.where(lid[:, None, :] >= 0, est, jnp.inf)
        return S.binned_partial_topk(est, lid, bins)

    cand_d, cand_i = lax.map(one_chunk,
                             (qmap_c, bits_c, n2_c, sc_c, ids_c, cent_c))
    cand_d = cand_d.reshape(n_lists, cap, -1)
    cand_i = cand_i.reshape(n_lists, cap, -1)
    return S.merge_candidates(cand_d, cand_i, probes, inv_pos, kk,
                              sqrt=False, cap=cap)


def extend(index: Index, new_vectors, new_indices=None, res=None
           ) -> Index:
    """Add vectors to an existing index (the ivf_flat/ivf_pq extend
    contract, reference ``ivf_pq_build.cuh:605``): label against the
    FROZEN centers, sign-encode with the frozen rotation, re-bucketize
    the combined per-row payloads. Per-row payloads are immutable under
    fixed centers+rotation, so old rows are moved, never re-encoded."""
    x = as_array(new_vectors).astype(jnp.float32)
    expects(x.ndim == 2 and x.shape[1] == index.dim,
            "ivf_bq.extend: dim mismatch")
    if index.metric == DistanceType.CosineExpanded:
        # build() stores normalized rows; extended rows must match or
        # the ip core scores raw dot products (ivf_flat.extend ditto)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                            1e-30)
    n_new = x.shape[0]
    new_ids = (jnp.arange(index.size, index.size + n_new,
                          dtype=jnp.int32)
               if new_indices is None
               else as_array(new_indices).astype(jnp.int32))
    expects(new_ids.shape == (n_new,), "ivf_bq.extend: bad new_indices")
    expects(bool((new_ids >= 0).all()),
            "ivf_bq.extend: new_indices must be non-negative")
    # the host rescore indexes `raw` BY global id — custom ids would
    # misalign it; estimator-only (keep_raw=False) indexes are free to
    # use any id scheme
    expects(index.raw is None or new_indices is None,
            "ivf_bq.extend: custom new_indices are only supported on "
            "keep_raw=False indexes (raw rescore rows are id-indexed)")

    n_lists, ml, w = index.bits.shape
    # flat view of current contents; a slot's list id is its label
    valid = (index.lists_indices >= 0).reshape(-1)
    old_labels = jnp.broadcast_to(
        jnp.arange(n_lists, dtype=jnp.int32)[:, None],
        (n_lists, ml)).reshape(-1)[valid]
    # int32 payload end-to-end (see build): bit words never ride as f32
    old_payload = jnp.concatenate(
        [lax.bitcast_convert_type(index.bits, jnp.int32)
         .reshape(-1, w)[valid],
         lax.bitcast_convert_type(
             index.norms2.reshape(-1)[valid][:, None], jnp.int32),
         lax.bitcast_convert_type(
             index.scales.reshape(-1)[valid][:, None], jnp.int32)],
        axis=1)
    old_ids = index.lists_indices.reshape(-1)[valid]

    new_labels = kmeans_balanced.predict(x, index.centers, res=res)
    # full precision like build(): sign stability (see build comment)
    r = jnp.matmul(x - index.centers[new_labels],
                   index.rotation_matrix.T,
                   precision=matmul_precision())
    new_payload = jnp.concatenate(
        [lax.bitcast_convert_type(_pack_bits(r), jnp.int32),
         lax.bitcast_convert_type(
             jnp.sum(r * r, axis=1)[:, None], jnp.int32),
         lax.bitcast_convert_type(
             jnp.mean(jnp.abs(r), axis=1)[:, None], jnp.int32)],
        axis=1)

    from raft_tpu.neighbors.ivf_flat import _bucketize
    payload = jnp.concatenate([old_payload, new_payload], axis=0)
    labels = jnp.concatenate([old_labels, new_labels])
    ids = jnp.concatenate([old_ids, new_ids])
    bucketed, idx, _, counts = _bucketize(payload, labels, n_lists,
                                          row_ids=ids,
                                          compute_norms=False)
    raw = None
    if index.raw is not None:
        raw = np.concatenate([index.raw,
                              np.asarray(jax.device_get(x))], axis=0)
    return Index(
        centers=index.centers, centers_rot=index.centers_rot,
        rotation_matrix=index.rotation_matrix,
        bits=lax.bitcast_convert_type(bucketed[:, :, :w], jnp.uint32),
        norms2=lax.bitcast_convert_type(bucketed[:, :, w], jnp.float32),
        scales=lax.bitcast_convert_type(bucketed[:, :, w + 1],
                                        jnp.float32),
        lists_indices=idx, list_sizes=counts, metric=index.metric,
        size=index.size + n_new, raw=raw)


@functools.partial(jax.jit, static_argnames=("kk", "bins", "n_probes",
                                             "cap", "gather", "kind",
                                             "lc", "fused"))
def _fused_bq_search_pallas(queries, centers, centers_rot, rot, bits,
                            norms2, scales, ids, *, kk: int, bins: int,
                            n_probes: int, cap: int,
                            gather: str = "rows", kind: str = "l2",
                            lc: int = 0, fused: bool = False):
    """Kernel-tier single-dispatch device phase: the in-VMEM unpack
    scan (``pallas_ivf_scan.ivf_bq_scan_pallas``) reads the 1-bit codes
    straight from HBM — 8× less scan bandwidth than the XLA tier's
    materialized decode tiles. ``gather`` is the RAFT_TPU_GATHER
    strategy resolved OUTSIDE jit (the _ivf_scan contract); ``lc``
    likewise (``pallas_ivf_scan.lc_mode``), 0 = auto; ``fused`` routes
    the fine phase through the single-pallas_call scan+select kernel
    (the route takes it for kk ≤ 256)."""
    from raft_tpu.neighbors import _ivf_scan as S
    from raft_tpu.ops.pallas_ivf_scan import ivf_bq_scan_pallas
    probes = S.coarse_probes(queries, centers, n_probes, kind=kind,
                             use_pallas=True)
    q_rot = queries @ rot.T
    return ivf_bq_scan_pallas(q_rot, centers_rot, bits, norms2, scales,
                              ids, probes, kk, cap, bins=bins,
                              gather=gather, metric=kind, lc=lc,
                              fused=fused)


@functools.partial(jax.jit, static_argnames=("k", "kind"))
def _exact_rescore_device(raw_dev, q, ids, *, k: int, kind: str):
    """Exact re-rank of the kk estimator survivors on DEVICE: gather by
    global id + f32 scores + top-k, one fused dispatch. Value-identical
    to the host epilogue (same scores, same ordering rule) but with no
    device↔host round-trip, so the whole search stays jittable."""
    cand = raw_dev[jnp.maximum(ids, 0)]                 # (nq, kk, d)
    qf = q.astype(jnp.float32)
    if kind == "ip":
        ex = -jnp.einsum("qkd,qd->qk", cand, qf,
                         precision=matmul_precision(),
                         preferred_element_type=jnp.float32)
    else:
        diff = cand - qf[:, None, :]
        ex = jnp.sum(diff * diff, axis=2)
    ex = jnp.where(ids >= 0, ex, jnp.inf)
    nd, sel = lax.top_k(-ex, k)
    return -nd, jnp.take_along_axis(ids, sel, axis=1)


_RAW_DEV_LOCK = threading.Lock()


def resolve_raw_device(index, mode: str) -> Optional[jax.Array]:
    """Device copy of ``index.raw`` per the ``rescore_on_device``
    policy ("auto" | "always" | "never"), cached on the index. None
    means: use the host epilogue. "never" also RELEASES a cached copy
    (the reclaim path after an "always" experiment); "auto" falls back
    to host if the device copy fails to materialize (e.g. HBM already
    full) rather than failing the search."""
    expects(mode in ("auto", "always", "never"),
            "rescore_on_device: want auto|always|never, got %r", mode)
    if mode == "never" or index.raw is None:
        index.raw_dev = None
        return None
    if mode == "auto":
        import os
        budget_mb = int(os.environ.get("RAFT_TPU_RESCORE_DEVICE_MB",
                                       "4096"))
        if index.raw.nbytes > budget_mb << 20:
            return None
    with _RAW_DEV_LOCK:
        if (index.raw_dev is None
                or index.raw_dev.shape != index.raw.shape):
            try:
                index.raw_dev = jnp.asarray(index.raw)
            except Exception:
                if mode == "always":
                    raise
                return None    # auto: HBM full → host epilogue
        return index.raw_dev


def finish_search(d_est, ids, raw, q, k: int,
                  metric: DistanceType = DistanceType.L2Expanded,
                  rescore: bool = False, raw_dev=None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Shared epilogue of the single-chip and distributed searches:
    either slice the estimator top-k, or exactly re-rank the kk
    survivors against the host-resident raw vectors. Internal scores
    are uniformly smaller-is-better (−similarity for the ip core);
    the ivf_flat output conventions are applied last (IP →
    similarities, cosine → 1 − cos, L2Sqrt → euclidean)."""
    from raft_tpu.neighbors.ivf_flat import _metric_kind, _postprocess
    kind = _metric_kind(metric)
    # both Sqrt metrics: ivf_pq routes through here too and supports
    # L2SqrtUnexpanded (r4 review finding)
    sqrt = metric in (DistanceType.L2SqrtExpanded,
                      DistanceType.L2SqrtUnexpanded)
    if not rescore:
        d_est, ids = d_est[:, :k], ids[:, :k]
        if sqrt:
            d_est = jnp.sqrt(jnp.maximum(d_est, 0.0))
        return _postprocess(d_est, metric), ids
    if raw_dev is not None:
        ex, i_out = _exact_rescore_device(raw_dev, q, ids,
                                          k=k, kind=kind)
        i_out = jnp.where(jnp.isfinite(ex), i_out, -1)
        d_out = jnp.where(jnp.isfinite(ex), ex, jnp.inf)
        if sqrt:
            d_out = jnp.sqrt(jnp.maximum(d_out, 0.0))
        return _postprocess(d_out, metric), i_out
    ids_h = np.asarray(jax.device_get(ids))
    qh = np.asarray(jax.device_get(q))
    cand = raw[np.maximum(ids_h, 0)]                    # (nq, kk, d)
    if kind == "ip":
        ex = -np.einsum("qkd,qd->qk", cand, qh)         # −similarity
    else:
        diff = cand - qh[:, None, :]
        ex = np.einsum("qkd,qkd->qk", diff, diff)
    ex = np.where(ids_h >= 0, ex, np.inf)
    order = np.argsort(ex, axis=1)[:, :k]
    d_out = np.take_along_axis(ex, order, axis=1)
    i_out = np.take_along_axis(ids_h, order, axis=1)
    i_out = np.where(np.isfinite(d_out), i_out, -1)
    d_out = np.where(np.isfinite(d_out), d_out, np.inf)
    if sqrt:
        d_out = np.sqrt(np.maximum(d_out, 0.0))
    return _postprocess(jnp.asarray(d_out), metric), jnp.asarray(i_out)


def search(index: Index, queries, k: int,
           params: SearchParams = SearchParams(), res=None
           ) -> Tuple[jax.Array, jax.Array]:
    """Estimator scan on device (one dispatch) + exact host rescore.
    When rescoring, returned values are exact and follow the family
    output conventions (ivf_flat._postprocess): squared-L2 ascending
    (euclidean for the Sqrt metric), similarities DESCENDING for
    InnerProduct, 1 − cos ascending for cosine; estimator values in
    the same conventions otherwise."""
    from raft_tpu.obs import spans
    with spans.span("raft.ivf_bq.search", k=k) as sp:
        return _search_spanned(index, queries, k, params, res, sp)


def _search_spanned(index: Index, queries, k: int, params, res, sp
                    ) -> Tuple[jax.Array, jax.Array]:
    from raft_tpu.neighbors import plan
    from raft_tpu.neighbors.ann_types import (MAX_QUERY_BATCH,
                                              batched_search)
    q = as_array(queries).astype(jnp.float32)
    sp.set_attr("nq", int(q.shape[0]))
    expects(q.shape[1] == index.dim, "ivf_bq.search: dim mismatch")
    if q.shape[0] > MAX_QUERY_BATCH:
        # reference batching loop (ivf_pq_search.cuh:1234 role): bounds
        # the inverted-table width (cap ≤ nq) and reuses one compiled
        # shape per batch
        return batched_search(
            lambda qb: search(index, qb, k, params, res=res), q)
    # rescore_factor shapes the DEVICE phase (kk = factor·k candidates)
    # whether or not raw vectors exist — so an estimator-only index runs
    # the same compiled search as the rescored one, returning the
    # estimator top-k
    r = plan.route(index, q.shape[0], k, params)
    # per-batch telemetry (the batched path recurses per sub-batch)
    obs.counter("raft.ivf_bq.search.queries").inc(q.shape[0])
    obs.histogram("raft.ivf_bq.search.batch_size",
                  buckets=obs.SIZE_BUCKETS).observe(q.shape[0])
    obs.histogram("raft.ivf_bq.search.n_probes",
                  buckets=obs.SIZE_BUCKETS).observe(r.n_probes)
    sp.set_attrs(n_probes=r.n_probes, rescore=r.rescoring)
    with obs.timed("raft.ivf_bq.search"):
        return plan.run(index, q, r, params)
