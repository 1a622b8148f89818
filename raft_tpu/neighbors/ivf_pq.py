"""IVF-PQ ANN index.

Reference: ``raft/neighbors/ivf_pq_types.hpp:31-116`` (params: pq_bits,
pq_dim, codebook_gen PER_SUBSPACE|PER_CLUSTER, lut_dtype,
internal_distance_dtype), build ``spatial/knn/detail/ivf_pq_build.cuh``
(:173 make_rotation_matrix, :464 train_per_subset, :532 train_per_cluster,
:605 extend/encode, :908 build) and search ``ivf_pq_search.cuh``
(:127 select_clusters, :593 ivfpq_compute_similarity_kernel — smem LUT +
bit-packed code scan, :1007 search worker, :1251 public search).

TPU re-design:
  * codes are stored one-byte-per-subquantizer in padded list buckets —
    the CUDA bit-packing optimizes smem bytes; on TPU u8 codes feed
    ``take_along_axis`` gathers directly and VMEM holds the (pq_dim, 256)
    LUT comfortably (the "smem LUT" analogue; SURVEY.md hard part (a)).
  * scoring, default ("reconstruct"): random-access LUT gathers are
    hostile to TPU (XLA lowers them to scalar-core gathers — measured
    ~100x slower than the MXU path), so build() decodes the codes once
    into a bf16 reconstruction cache and search scores probes with the
    same residual-vs-list einsum as IVF-Flat — identical asymmetric-PQ
    distances up to bf16 rounding, 2x less memory than f32 IVF-Flat.
    The CUDA-style LUT-gather scan is kept as scan_mode="lut" (exact
    f32 LUT, the reference's smem-LUT analogue) for parity testing and
    small problems.
  * rotation matrix: random orthogonal via QR of a gaussian, exactly the
    reference's make_rotation_matrix trick.
"""

from __future__ import annotations

import enum
import functools
import threading
from dataclasses import dataclass, field as dataclasses_field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import obs
from raft_tpu.obs import spans
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.neighbors.ivf_flat import (_bucketize, _bucketize_static,
                                         _counts_and_max)
from raft_tpu.core.precision import matmul_precision
from raft_tpu.util.host_sample import (sample_rows, sample_rows_np,
                                       take_rows)


class CodebookGen(enum.IntEnum):
    """reference ivf_pq_types.hpp codebook_gen."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


@dataclass
class IndexParams:
    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    # reference-parity default; it feeds BOTH the coarse trainer and the
    # PQ codebook trainers. 10 costs ~0.3% recall on random data but
    # ~1% on clustered (codebook under-convergence, 2026-08-01 A/B) —
    # the speed knob stays at call sites
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8          # 4..8 in the reference
    pq_dim: int = 0           # 0 = dim/4 heuristic (reference default path)
    codebook_kind: CodebookGen = CodebookGen.PER_SUBSPACE
    force_random_rotation: bool = False
    # matmul tier for BOTH kmeans phases (docs/tuning.md): the Pallas
    # balanced-EM coarse trainer takes it verbatim; the grouped PQ
    # codebook trainer maps it onto the equivalent XLA einsum precision
    # (core.precision.xla_precision_for_kernel)
    kmeans_kernel_precision: object = None
    # keep the raw f32 vectors on HOST for exact rescoring
    # (SearchParams.rescore_factor — the refine.cuh role fused into
    # search, the ivf_bq pattern). The device never stores them; an
    # estimator-only index stays pq_dim+8 bytes/vector
    keep_raw: bool = False
    # grouped-codebook-trainer balancing: codewords whose assignment
    # count falls below reseed_threshold·(rows/n_codes) re-seed from
    # the highest-cost rows each EM sweep (the adjust_centers role,
    # reference ivf_pq_build.cuh:436 applied to train_per_subset).
    # 0 disables reseeding; the default matches the coarse trainer's
    # balance_threshold (was a hardcoded 0.25, ADVICE r5)
    reseed_threshold: float = 0.25


@dataclass
class SearchParams:
    n_probes: int = 20
    # the reference's LUT-precision variants (ivf_pq_search.cuh:780-1004)
    # mapped to TPU terms — all live on the "codes" scan path:
    # lut_dtype = decode dtype: bf16 (one MXU pass), f32 (bf16x3 split),
    # or float8_e4m3fn (the fp_8bit tier: books stored fp8 — half the
    # codebook VMEM/HBM — computed in bf16; requires scan_mode "codes");
    # internal_distance_dtype = candidate score dtype carried to the
    # merge (bf16 halves candidate HBM traffic)
    lut_dtype: object = jnp.bfloat16
    internal_distance_dtype: object = jnp.float32
    # "auto" = "codes" when the Pallas tier is live, else "reconstruct";
    # "codes" = fused Pallas scan over the u8 codes with transient
    #           per-chunk decode tiles (pq_dim+8 bytes resident/vector);
    # "reconstruct" = bf16 decoded-cache MXU scan (XLA formulation;
    #           persists an ~8x cache over the codes);
    # "lut" = per-probe f32 LUT + gather scan (the CUDA formulation)
    scan_mode: str = "auto"
    # rescore_factor·k estimator candidates re-ranked EXACTLY against
    # the host-resident raw vectors (requires keep_raw=True at build;
    # the reference's refine.cuh step fused into search, the ivf_bq
    # pattern). PQ distances are estimates — the codebook quantization
    # error, not the probe set, limits recall at high probes — so the
    # ≥0.9-recall operating points run with rescoring. 0 disables
    # (estimator distances returned). Like ivf_bq, a factor > 0 shapes
    # the DEVICE phase (kk = factor·k candidates) even without raw, so
    # benches chain the true serving program.
    rescore_factor: int = 0
    # "probe"/"list"/"auto" — see ivf_flat.SearchParams.scan_order;
    # list-major applies to the reconstruct scan only
    scan_order: str = "auto"
    # see ivf_flat.SearchParams.scan_bins
    scan_bins: int = 0
    # see ivf_flat.SearchParams.probe_cap / _ivf_scan.resolve_cap
    probe_cap: int = 0
    # "auto" | "always" | "never" — see ivf_bq.SearchParams: the exact
    # re-rank runs fused on device when the raw corpus fits the HBM
    # budget, else on host
    rescore_on_device: str = "auto"


@dataclass
class Index:
    centers: jax.Array            # (n_lists, dim) cluster centers
    centers_rot: jax.Array        # (n_lists, rot_dim) rotated centers
    rotation_matrix: jax.Array    # (rot_dim, dim)
    # PER_SUBSPACE: (pq_dim, 2^bits, pq_len) — one codebook per subspace
    # PER_CLUSTER:  (n_lists, 2^bits, pq_len) — one codebook per coarse
    #               cluster, shared across subspaces (reference
    #               ivf_pq_build.cuh:532 train_per_cluster)
    pq_centers: jax.Array
    codes: jax.Array              # (n_lists, max_list, pq_dim) uint8
    lists_indices: jax.Array      # (n_lists, max_list) int32, -1 pad
    list_sizes: jax.Array
    metric: DistanceType
    pq_bits: int
    size: int
    codebook_kind: CodebookGen = CodebookGen.PER_SUBSPACE
    # exact decoded-residual squared norms, (n_lists, max_list) f32:
    # PQ subspaces concatenate orthogonally so the norm is a sum of
    # per-subspace codeword norms — computed once at build. With ids
    # this bounds resident memory at pq_dim+8 bytes/vector.
    code_norms: Optional[jax.Array] = None
    # bf16 reconstruction cache for the non-Pallas MXU scan path
    # (decoded codes, (n_lists, max_list, rot_dim)) + its per-row squared
    # norms. Derived from codes/pq_centers; built lazily, never on the
    # "codes" path.
    decoded: Optional[jax.Array] = None
    decoded_norms: Optional[jax.Array] = None
    # fp8-LUT tier: code norms recomputed over the float8_e4m3fn-
    # quantized books so the L2 epilogue matches what the kernel decodes
    # (lazy, like decoded)
    code_norms_fp8: Optional[jax.Array] = None
    # raw f32 vectors on HOST (keep_raw builds), indexed by global id —
    # the exact-rescore corpus (ivf_bq.Index.raw role)
    raw: Optional["np.ndarray"] = None
    # measured inverted-table widths keyed (nq, n_probes) — see
    # _ivf_scan.resolve_cap (not index identity; not serialized)
    cap_cache: dict = dataclasses_field(default_factory=dict, repr=False,
                                        compare=False)
    # AOT-compiled serving plans keyed by shape identity — see
    # neighbors/plan.py (not index identity; not serialized)
    plan_cache: dict = dataclasses_field(default_factory=dict, repr=False,
                                         compare=False)
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
    def pq_dim(self) -> int:
        # derived from the codes (valid for both codebook kinds; the
        # pq_centers leading dim is n_lists under PER_CLUSTER)
        return self.codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.pq_centers.shape[2]

    @property
    def rot_dim(self) -> int:
        return self.rotation_matrix.shape[0]


@functools.partial(jax.jit, static_argnames=("dim", "rot_dim"))
def _rotation_qr(seed_arr, dim: int, rot_dim: int):
    """jit core of :func:`make_rotation_matrix` — one program instead of
    an eager op per step (every eager op is its own compile; cold-build
    time is compile-count-bound)."""
    g = jax.random.normal(jax.random.wrap_key_data(seed_arr),
                          (max(rot_dim, dim), dim), dtype=jnp.float32)
    q, _ = jnp.linalg.qr(g.T @ g + 1e-4 * jnp.eye(dim))
    full = q.T  # (dim, dim) orthogonal
    if rot_dim <= dim:
        return full[:rot_dim]
    pad = jnp.zeros((rot_dim - dim, dim), jnp.float32)
    return jnp.concatenate([full, pad], axis=0)


def make_rotation_matrix(dim: int, rot_dim: int, force_random: bool = False,
                         seed: int = 7) -> jax.Array:
    """Random orthogonal (rot_dim, dim) via QR of a gaussian (reference
    ivf_pq_build.cuh:173). When rot_dim == dim and not forced, identity is
    allowed — but the reference always rotates when padding is needed."""
    if rot_dim == dim and not force_random:
        # numpy identity + transfer: jnp.eye eagerly compiles ~5 tiny
        # programs (iota/add/equal/convert), one compile each
        return jnp.asarray(np.eye(dim, dtype=np.float32))
    key_data = jax.random.key_data(jax.random.key(seed))
    return _rotation_qr(key_data, dim, rot_dim)


@jax.jit
def _prep_rotated(x, centers, labels, rot):
    """Rotation + residual phase as ONE program: centers_rot, residuals,
    residuals_rot (reference ivf_pq_build.cuh:908 does the same three
    GEMM/gather steps; eagerly they are 4+ separate compiles)."""
    centers_rot = jnp.matmul(centers, rot.T, precision=matmul_precision())
    residuals = x - centers[labels]
    residuals_rot = jnp.matmul(residuals, rot.T,
                               precision=matmul_precision())
    return centers_rot, residuals_rot


@jax.jit
def _labels_and_prep(x, centers, rot):
    """Coarse assignment + rotation/residual phase as ONE program
    (predict's fused-L2-NN argmin is traceable — folding it in saves
    its separate compile — the compile-count collapse)."""
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn
    labels = fused_l2_nn(x, centers, sqrt=False).key
    centers_rot, residuals_rot = _prep_rotated(x, centers, labels, rot)
    return labels, centers_rot, residuals_rot


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_len",
                                             "n_codes", "n_iters",
                                             "chunk", "precision"))
def _train_books_grouped(residuals_rot, cb_idx, valid, init_idx,
                         pq_dim: int, pq_len: int, n_codes: int,
                         n_iters: int, chunk: int,
                         precision=None, reseed_threshold=0.25):
    """All pq_dim subspace codebooks trained in ONE compiled program —
    the balanced-EM semantics of the former per-subspace
    balanced_kmeans loop (assignment + masked mean + small-cluster
    reseed from the globally worst-cost points, reference
    train_per_subset ivf_pq_build.cuh:464 + adjust_centers :436),
    batched over the subspace axis and row-chunked so the (S, B, C)
    distance blocks stay bounded.

    Why one program: a cold build is compile-COUNT-bound, and the
    sequential loop's traced init sampler + glue was ~12 of the ~32
    programs of the 500k PQ build. The earlier revert note
    ("batched was 25% slower on CPU") predates that measurement: the
    few-hundred-ms warm difference is noise against ~10-20 s saved
    per removed compile.

    residuals_rot (n, rot_dim); cb_idx (m_pad,) int32 trainset rows
    (cyclically padded to a chunk multiple); valid (m_pad,) bool marks
    real rows; init_idx (pq_dim, n_codes) int32 init positions INTO
    the trainset. ``precision`` is the XLA tier for the assignment/
    update einsums (static; ``None`` = the process-wide
    matmul_precision default) — ``IndexParams.kmeans_kernel_precision``
    reaches here via ``core.precision.xla_precision_for_kernel``.
    ``reseed_threshold`` (traced scalar — distinct values never
    recompile) gates the small-codeword reseed:
    ``IndexParams.reseed_threshold``. Returns (pq_dim, n_codes, pq_len)
    codebooks."""
    if precision is None:
        precision = matmul_precision()
    m = cb_idx.shape[0]
    tr = residuals_rot[cb_idx]                          # (m, rot_dim)
    sub = tr.reshape(m, pq_dim, pq_len).transpose(1, 0, 2)  # (S, m, l)
    centers0 = jnp.take_along_axis(sub, init_idx[:, :, None], axis=1)
    vf = valid.astype(jnp.float32)
    avg = jnp.sum(vf) / n_codes
    n_chunks = m // chunk
    xs = (sub.reshape(pq_dim, n_chunks, chunk, pq_len)
          .transpose(1, 0, 2, 3))                       # (nc, S, B, l)
    vs = vf.reshape(n_chunks, chunk)
    base = jnp.arange(m, dtype=jnp.int32).reshape(n_chunks, chunk)

    def one_iter(_, centers):
        cc = jnp.sum(centers * centers, axis=2)         # (S, C)

        def chunk_step(carry, inp):
            counts, sums, wd, wi = carry
            xb, vb, ib = inp                            # (S,B,l),(B,),(B,)
            ip = jnp.einsum("sbl,scl->sbc", xb, centers,
                            preferred_element_type=jnp.float32,
                            precision=precision)
            bb = jnp.sum(xb * xb, axis=2)
            d = bb[:, :, None] + cc[:, None, :] - 2.0 * ip
            assign = jnp.argmin(d, axis=2)              # (S, B)
            dmin = jnp.min(d, axis=2)
            oh = jax.nn.one_hot(assign, n_codes, dtype=jnp.float32)
            oh = oh * vb[None, :, None]
            counts = counts + jnp.sum(oh, axis=1)
            sums = sums + jnp.einsum("sbc,sbl->scl", oh, xb,
                                     preferred_element_type=jnp.float32,
                                     precision=precision)
            # running top-C worst-cost rows per subspace (reseed pool);
            # padded rows never qualify
            dmin = jnp.where(vb[None, :] > 0, dmin, -jnp.inf)
            cd = jnp.concatenate([wd, dmin], axis=1)
            cix = jnp.concatenate(
                [wi, jnp.broadcast_to(ib[None, :], dmin.shape)], axis=1)
            nwd, sel = lax.top_k(cd, n_codes)
            nwi = jnp.take_along_axis(cix, sel, axis=1)
            return (counts, sums, nwd, nwi), None

        init = (jnp.zeros((pq_dim, n_codes), jnp.float32),
                jnp.zeros((pq_dim, n_codes, pq_len), jnp.float32),
                jnp.full((pq_dim, n_codes), -jnp.inf, jnp.float32),
                jnp.zeros((pq_dim, n_codes), jnp.int32))
        (counts, sums, wd, wi), _ = lax.scan(chunk_step, init,
                                             (xs, vs, base))
        newc = sums / jnp.maximum(counts, 1.0)[:, :, None]
        newc = jnp.where(counts[:, :, None] > 0, newc, centers)
        small = counts < reseed_threshold * avg
        slot = jnp.cumsum(small.astype(jnp.int32), axis=1) - 1
        seeds = jnp.take_along_axis(sub, wi[:, :, None], axis=1)
        reseed = jnp.take_along_axis(
            seeds, jnp.clip(slot, 0, n_codes - 1)[:, :, None], axis=1)
        return jnp.where(small[:, :, None], reseed, newc)

    return lax.fori_loop(0, n_iters, one_iter, centers0)


def _train_codebooks_per_subspace(residuals_rot, pq_dim: int, pq_len: int,
                                  n_codes: int, n_iters: int, seed: int,
                                  kernel_precision=None, cb_idx=None,
                                  reseed_threshold: float = 0.25):
    """Per-subspace k-means over residual subvectors (reference
    train_per_subset, ivf_pq_build.cuh:464) — host glue around the
    single-program grouped trainer (_train_books_grouped).

    ``cb_idx``: optional HOST int array of trainset rows (the caller's
    subsample); None trains on all rows. ``kernel_precision`` follows
    the Pallas-kernel spellings (None = env default, ``bf16x3``,
    ``bf16``, ``highest``) and is threaded into the grouped trainer's
    assignment/update einsums via
    ``core.precision.xla_precision_for_kernel`` — the public
    ``IndexParams.kmeans_kernel_precision`` knob therefore shapes PQ
    codebook training exactly like the coarse trainer (it used to be
    silently dropped here)."""
    from raft_tpu.core.precision import xla_precision_for_kernel
    precision = xla_precision_for_kernel(kernel_precision)
    n = residuals_rot.shape[0]
    if cb_idx is None:
        cb_idx = np.arange(n, dtype=np.int32)
    m = int(cb_idx.shape[0])
    chunk = min(m, 4096)
    m_pad = -(-m // chunk) * chunk
    pad_idx = np.asarray(cb_idx, np.int32)[np.arange(m_pad) % m]
    valid = np.arange(m_pad) < m
    rng = np.random.default_rng(seed)
    init_idx = np.stack([
        rng.choice(m, n_codes, replace=m < n_codes)
        for _ in range(pq_dim)]).astype(np.int32)
    return _train_books_grouped(
        residuals_rot, jnp.asarray(pad_idx), jnp.asarray(valid),
        jnp.asarray(init_idx), pq_dim, pq_len, n_codes, n_iters, chunk,
        precision=precision, reseed_threshold=reseed_threshold)


def _list_chunk(L: int, per_list_elems: int,
                budget: int = 1 << 26) -> int:
    """Largest divisor of L whose chunk keeps per_list_elems·chunk under
    the element budget (bounds the (chunk, M·pq_dim, C) intermediates)."""
    from raft_tpu.neighbors._ivf_scan import largest_divisor_at_most
    return largest_divisor_at_most(L, max(1, budget // max(1,
                                                           per_list_elems)))


@functools.partial(jax.jit, static_argnames=("n_codes", "n_iters",
                                             "chunk"))
def _batched_masked_kmeans(data, valid, n_codes: int, n_iters: int, key,
                           chunk: int):
    """One k-means per leading batch entry over masked rows — the
    PER_CLUSTER codebook trainer (reference train_per_cluster,
    ivf_pq_build.cuh:532), shape-bucketed (every cluster trains in one
    compiled program) and list-chunked (``lax.map`` over groups of
    ``chunk`` lists bounds the (chunk, M, C) distance blocks).

    data (L, M, D) f32, valid (L, M) bool → (L, n_codes, D) codebooks.
    Empty slots inherit their initial center (valid rows always win the
    masked assignment)."""
    L, M, D = data.shape

    def em_block(args):
        db, vb, kb = args                                # (G, M, D) ...
        score = jax.random.uniform(kb, vb.shape) + \
            jnp.where(vb, 0.0, 2.0)
        first = jnp.argsort(score, axis=1)[:, :n_codes]
        centers0 = jnp.take_along_axis(db, first[:, :, None], axis=1)

        def one_iter(c, _):
            xx = jnp.sum(db * db, axis=2)[:, :, None]
            cc = jnp.sum(c * c, axis=2)[:, None, :]
            ip = jnp.einsum("lmd,lcd->lmc", db, c,
                            preferred_element_type=jnp.float32,
                            precision=matmul_precision())
            d = xx + cc - 2.0 * ip
            assign = jnp.argmin(d, axis=2)
            oh = jax.nn.one_hot(assign, n_codes, dtype=jnp.float32)
            oh = oh * vb[:, :, None]
            counts = jnp.sum(oh, axis=1)
            sums = jnp.einsum("lmc,lmd->lcd", oh, db,
                              preferred_element_type=jnp.float32,
                              precision=matmul_precision())
            newc = sums / jnp.maximum(counts, 1.0)[:, :, None]
            return jnp.where(counts[:, :, None] > 0, newc, c), None

        c, _ = lax.scan(one_iter, centers0, None, length=n_iters)
        return c

    keys = jax.random.split(key, L // chunk)
    out = lax.map(em_block, (data.reshape(-1, chunk, M, D),
                             valid.reshape(-1, chunk, M), keys))
    return out.reshape(L, n_codes, D)


def _nearest_code(sub, books):
    """argmin_j ||sub − books[j]||² over the last axis, batched over any
    leading dims — THE per-cluster encoding equation, shared by build
    and extend so they can never diverge."""
    ip = jnp.einsum("...sl,...cl->...sc", sub, books,
                    preferred_element_type=jnp.float32,
                    precision=matmul_precision())
    bb = jnp.sum(books * books, axis=-1)[..., None, :]
    ss = jnp.sum(sub * sub, axis=-1)[..., :, None]
    return jnp.argmin(ss + bb - 2.0 * ip, axis=-1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _encode_per_cluster(bucketed_resid, books, chunk: int):
    """codes[l, i, s] = argmin_j ||sub(l, i, s) − books[l, j]||² over the
    bucketed rotated residuals (n_lists, max_list, rot_dim), in list
    chunks."""
    L, M, rot_dim = bucketed_resid.shape
    _, n_codes, pq_len = books.shape
    pq_dim = rot_dim // pq_len

    def enc_block(args):
        rb, bb_ = args
        sub = rb.reshape(rb.shape[0], M * pq_dim, pq_len)
        return _nearest_code(sub, bb_).reshape(rb.shape[0], M, pq_dim)

    out = lax.map(enc_block,
                  (bucketed_resid.reshape(-1, chunk, M, rot_dim),
                   books.reshape(-1, chunk, n_codes, pq_len)))
    return out.reshape(L, M, pq_dim)


@jax.jit
def _code_norms_per_cluster(codes_b, books, lists_indices):
    """Exact ||decoded||² per slot for PER_CLUSTER books: subspaces share
    the list's codebook, so the norm is Σ_s ||books_l[c_s]||²."""
    L, M, pq_dim = codes_b.shape
    bb = jnp.sum(books * books, axis=2)                  # (L, n_codes)
    norms = jnp.zeros((L, M), jnp.float32)
    for s in range(pq_dim):
        norms = norms + jnp.take_along_axis(
            bb, codes_b[:, :, s].astype(jnp.int32), axis=1)
    return jnp.where(lists_indices >= 0, norms, 0.0)


@jax.jit
def _encode(residuals_rot, pq_centers):
    """codes[i, s] = argmin_j ||residual_sub(i,s) - pq_centers[s, j]||².

    Row chunks in the grouped trainer's (S, B, C) assignment form. The
    former whole-corpus ``vmap`` over subspaces gave wrong argmins on
    TPU v5e (13% of codes matched the nearest codeword at 128k×128,
    pq_dim 64; exact on CPU)."""
    pq_dim, n_codes, pq_len = pq_centers.shape
    n = residuals_rot.shape[0]
    chunk = min(4096, n)
    pad = (-n) % chunk
    r = jnp.pad(residuals_rot, ((0, pad), (0, 0))) if pad else residuals_rot
    xs = r.reshape(-1, chunk, pq_dim, pq_len).transpose(0, 2, 1, 3)
    cc = jnp.sum(pq_centers * pq_centers, axis=2)      # (S, C)

    def one_chunk(xb):                                  # (S, B, l)
        ip = jnp.einsum("sbl,scl->sbc", xb, pq_centers,
                        preferred_element_type=jnp.float32,
                        precision=matmul_precision())
        d = jnp.sum(xb * xb, axis=2)[:, :, None] + cc[:, None, :] - 2.0 * ip
        return jnp.argmin(d, axis=2).T.astype(jnp.uint8)   # (B, S)

    return lax.map(one_chunk, xs).reshape(-1, pq_dim)[:n]


@functools.partial(jax.jit, static_argnames=("n_lists", "max_list"))
def _bucketize_codes(codes, labels, counts, pq_centers, n_lists: int,
                     max_list: int):
    """Bucket the (n, pq_dim) uint8 codes into the padded list layout
    AND compute the exact decoded norms in ONE program: the codes ride
    as their integer payload end-to-end (no f32 round-trip casts — the
    ivf_bq int32-payload contract) and the ``_code_norms`` pass fuses
    into the same compile instead of being its own dispatch."""
    codes_b, idx, _, counts = _bucketize_static(
        codes, labels, None, n_lists, max_list, counts=counts,
        compute_norms=False)
    return codes_b, idx, counts, _code_norms(codes_b, pq_centers, idx)


@spans.spanned("raft.ivf_pq.build")
@obs.timed("raft.ivf_pq.build")
def build(dataset, params: IndexParams = IndexParams(), seed: int = 0,
          res=None) -> Index:
    """Build (reference ivf_pq_build.cuh:908): balanced-kmeans coarse
    training → rotation → per-subspace codebooks on residuals → encode."""
    x = as_array(dataset).astype(jnp.float32)
    n, dim = x.shape
    expects(params.n_lists <= n, "ivf_pq.build: n_lists > n_samples")
    obs.counter("raft.ivf_pq.build.total").inc()
    obs.counter("raft.ivf_pq.build.rows").inc(n)
    spans.current_span().set_attrs(rows=n, n_lists=params.n_lists,
                                   pq_bits=params.pq_bits)
    pq_dim = params.pq_dim if params.pq_dim > 0 else max(1, dim // 4)
    rot_dim = ((dim + pq_dim - 1) // pq_dim) * pq_dim
    pq_len = rot_dim // pq_dim
    n_codes = 1 << params.pq_bits
    expects(n >= n_codes,
            "ivf_pq.build: need at least 2^pq_bits (%d) training rows", n_codes)
    expects(params.metric in (DistanceType.L2Expanded,
                              DistanceType.L2SqrtExpanded,
                              DistanceType.L2Unexpanded,
                              DistanceType.L2SqrtUnexpanded,
                              DistanceType.InnerProduct),
            "ivf_pq: L2-family and InnerProduct metrics are supported "
            "(got %s)", params.metric)

    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    if n_train < n:
        # host-side draw (util.host_sample): a traced
        # choice(replace=False) is an n-wide sort compile on TPU
        trainset = take_rows(x, sample_rows(n, n_train, seed))
    else:
        trainset = x
    centers = kmeans_balanced.build_hierarchical(
        trainset, params.n_lists, params.kmeans_n_iters,
        kernel_precision=params.kmeans_kernel_precision, res=res)

    rot = make_rotation_matrix(dim, rot_dim, params.force_random_rotation,
                               seed=seed + 1)
    # coarse assignment + rotation/residuals in ONE program
    labels, centers_rot, residuals_rot = _labels_and_prep(x, centers, rot)

    if params.codebook_kind == CodebookGen.PER_CLUSTER:
        # one codebook per coarse cluster (reference train_per_cluster):
        # bucket the rotated residuals, train a batched masked k-means
        # over every list's pooled subvectors, encode in place
        bucketed, idx, _, counts = _bucketize(residuals_rot, labels,
                                              params.n_lists)
        L, M, _ = bucketed.shape
        # per-subvector validity: each row contributes pq_dim subvectors
        valid = jnp.broadcast_to((idx >= 0)[:, :, None],
                                 (L, M, pq_dim)).reshape(L, -1)
        sub_all = bucketed.reshape(L, M * pq_dim, pq_len)
        t_sub = min(M * pq_dim, 4096)  # training subsample per list
        tr_sub, tr_valid = sub_all[:, :t_sub], valid[:, :t_sub]
        if t_sub < n_codes:
            # the trainer seeds n_codes centers from the slice: pad
            # short lists by cyclic repetition (duplicate seeds are
            # harmless — empty codewords keep their init)
            reps = -(-n_codes // t_sub)
            tr_sub = jnp.tile(tr_sub, (1, reps, 1))[:, :n_codes]
            tr_valid = jnp.tile(tr_valid, (1, reps))[:, :n_codes]
        chunk_t = _list_chunk(L, tr_sub.shape[1] * n_codes)
        books = _batched_masked_kmeans(
            tr_sub, tr_valid, n_codes,
            params.kmeans_n_iters, jax.random.key(seed + 2), chunk_t)
        chunk_e = _list_chunk(L, M * pq_dim * n_codes)
        codes_b = _encode_per_cluster(bucketed, books, chunk_e)
        return Index(centers=centers, centers_rot=centers_rot,
                     rotation_matrix=rot, pq_centers=books, codes=codes_b,
                     lists_indices=idx, list_sizes=counts,
                     metric=params.metric, pq_bits=params.pq_bits, size=n,
                     codebook_kind=CodebookGen.PER_CLUSTER,
                     code_norms=_code_norms_per_cluster(codes_b, books,
                                                        idx),
                     raw=(np.asarray(jax.device_get(x))
                          if params.keep_raw else None))

    n_cb_train = min(n, 1 << 16)
    # the trainset subsample stays HOST indices (padding/init glue runs
    # host-side; the gather rides inside the grouped trainer program)
    cb_idx = (sample_rows_np(n, n_cb_train, seed + 3)
              if n_cb_train < n else None)
    pq_centers = _train_codebooks_per_subspace(
        residuals_rot, pq_dim, pq_len, n_codes,
        params.kmeans_n_iters, seed + 2,
        kernel_precision=params.kmeans_kernel_precision, cb_idx=cb_idx,
        reseed_threshold=params.reseed_threshold)

    codes = _encode(residuals_rot, pq_centers)  # (n, pq_dim) u8

    # bucket codes by list using the same static padded layout as
    # IVF-Flat — directly as uint8 (integer payload: no norms pass, no
    # f32 round-trip casts; same contract as the ivf_bq int32 payloads),
    # with the code-norms pass fused into the same program
    counts, mx = _counts_and_max(labels, params.n_lists)
    max_list = int(jax.device_get(mx))
    max_list = max(8, -(-max_list // 8) * 8)
    codes_b, idx, counts, norms = _bucketize_codes(
        codes, labels, counts, pq_centers, params.n_lists, max_list)

    # the bf16 reconstruction cache is decoded lazily at first
    # reconstruct-mode search — codes/LUT-mode users and serialized
    # indexes never pay its ~8x memory over the codes
    return Index(centers=centers, centers_rot=centers_rot,
                 rotation_matrix=rot, pq_centers=pq_centers, codes=codes_b,
                 lists_indices=idx, list_sizes=counts, metric=params.metric,
                 pq_bits=params.pq_bits, size=n,
                 code_norms=norms,
                 raw=(np.asarray(jax.device_get(x))
                      if params.keep_raw else None))


def extend(index: Index, new_vectors, new_indices=None, res=None) -> Index:
    """Add vectors to an existing index (reference ``ivf_pq::extend``,
    ivf_pq_build.cuh:605): label against the trained centers, encode
    residuals with the FROZEN codebooks/rotation, and re-bucket the
    combined code set. Returns a new Index; the reconstruction cache is
    re-derived lazily."""
    x = as_array(new_vectors).astype(jnp.float32)
    expects(x.ndim == 2 and x.shape[1] == index.dim,
            "ivf_pq.extend: dim mismatch")
    n_new = x.shape[0]
    new_ids = (jnp.arange(index.size, index.size + n_new, dtype=jnp.int32)
               if new_indices is None
               else as_array(new_indices).astype(jnp.int32))
    expects(new_ids.shape == (n_new,), "ivf_pq.extend: bad new_indices")
    expects(bool((new_ids >= 0).all()),
            "ivf_pq.extend: new_indices must be non-negative (negative "
            "ids are the padding sentinel)")
    # the host rescore indexes `raw` BY global id — custom ids would
    # misalign it (the ivf_bq.extend contract)
    expects(index.raw is None or new_indices is None,
            "ivf_pq.extend: custom new_indices are only supported on "
            "keep_raw=False indexes (raw rescore rows are id-indexed)")

    labels = kmeans_balanced.predict(x, index.centers, res=res)
    residuals_rot = jnp.matmul(x - index.centers[labels],
                               index.rotation_matrix.T,
                               precision=matmul_precision())
    if index.codebook_kind == CodebookGen.PER_CLUSTER:
        # frozen per-list books: encode each new row through its label's
        # codebook (reference extend with codebook_gen PER_CLUSTER)
        sub = residuals_rot.reshape(x.shape[0], index.pq_dim,
                                    index.pq_len)
        new_codes = _nearest_code(sub, index.pq_centers[labels])
    else:
        new_codes = _encode(residuals_rot, index.pq_centers)

    # flatten existing valid slots back to (n_old, pq_dim) + their ids
    flat_codes = index.codes.reshape(-1, index.pq_dim)
    flat_ids = index.lists_indices.reshape(-1)
    n_lists, max_list = index.lists_indices.shape
    old_list = jnp.repeat(jnp.arange(n_lists, dtype=jnp.int32), max_list)
    valid = flat_ids >= 0  # eager boolean mask, as in ivf_flat.extend
    n_old = int(index.size)
    all_codes = jnp.concatenate([flat_codes[valid], new_codes], axis=0)
    all_labels = jnp.concatenate([old_list[valid], labels], axis=0)
    all_ids = jnp.concatenate([flat_ids[valid], new_ids], axis=0)

    bucketed, idx, _, counts = _bucketize(
        all_codes.astype(jnp.float32), all_labels, n_lists,
        row_ids=all_ids)
    codes_b = bucketed.astype(jnp.uint8)
    norms_fn = (_code_norms_per_cluster
                if index.codebook_kind == CodebookGen.PER_CLUSTER
                else _code_norms)
    return Index(centers=index.centers, centers_rot=index.centers_rot,
                 rotation_matrix=index.rotation_matrix,
                 pq_centers=index.pq_centers,
                 codes=codes_b,
                 lists_indices=idx, list_sizes=counts,
                 metric=index.metric, pq_bits=index.pq_bits,
                 size=n_old + n_new,
                 codebook_kind=index.codebook_kind,
                 code_norms=norms_fn(codes_b, index.pq_centers, idx),
                 raw=(np.concatenate(
                     [index.raw, np.asarray(jax.device_get(x))])
                     if index.raw is not None else None))


@jax.jit
def _code_norms(codes_b, pq_centers, lists_indices):
    """Exact ||decoded||² per bucketed slot from the codebook norm
    table: subspaces are orthogonal coordinate blocks, so the decoded
    squared norm is Σ_s ||book_s[c_s]||². Pad slots → 0."""
    n_lists, max_list, pq_dim = codes_b.shape
    bb = jnp.sum(pq_centers * pq_centers, axis=2)      # (pq_dim, n_codes)
    flat = codes_b.reshape(-1, pq_dim).astype(jnp.int32)
    norms = jnp.zeros((flat.shape[0],), jnp.float32)
    for s in range(pq_dim):
        norms = norms + bb[s][flat[:, s]]
    norms = norms.reshape(n_lists, max_list)
    return jnp.where(lists_indices >= 0, norms, 0.0)


@jax.jit
def _decode_lists_per_cluster(codes_b, books, lists_indices):
    """Decode PER_CLUSTER codes → bf16 reconstruction cache: subspace s
    of row i in list l decodes through list l's codebook."""
    L, M, pq_dim = codes_b.shape
    _, n_codes, pq_len = books.shape

    def one_list(codes_l, book):
        return book[codes_l.astype(jnp.int32)]        # (M, pq_dim, pl)

    dec = jax.vmap(one_list)(codes_b, books)
    dec = dec.reshape(L, M, pq_dim * pq_len)
    valid = (lists_indices >= 0)[:, :, None]
    return jnp.where(valid, dec, 0.0).astype(jnp.bfloat16)


@jax.jit
def _decode_lists(codes_b, pq_centers, lists_indices):
    """Decode bucketed PQ codes → bf16 reconstruction cache
    ((n_lists, max_list, rot_dim) rotated residuals). Its norms are NOT
    recomputed here — ``_code_norms`` already holds the identical exact
    quantity. One row-gather per subquantizer from its (n_codes, pq_len)
    table — a single fancy-gather over the stacked books broadcasts a
    huge (N, pq_dim, n_codes, pq_len) intermediate on TPU and OOMs at
    ~1M rows; the per-subspace loop stays O(N·pq_len) per step."""
    n_lists, max_list, pq_dim = codes_b.shape
    _, n_codes, pq_len = pq_centers.shape
    flat = codes_b.reshape(-1, pq_dim).astype(jnp.int32)   # (N, pq_dim)
    # decoded[i, s, :] = pq_centers[s, flat[i, s], :]
    dec = jnp.stack([pq_centers[s][flat[:, s]] for s in range(pq_dim)],
                    axis=1)                                # (N, s, l)
    dec = dec.reshape(n_lists, max_list, pq_dim * pq_len)
    # padded slots decode to code 0's centroid; zero them so their norms
    # are harmless (scores for pads are masked at search anyway)
    valid = (lists_indices >= 0)[:, :, None]
    dec = jnp.where(valid, dec, 0.0)
    return dec.astype(jnp.bfloat16)


def _score_probe_reconstruct(q_rot, centers_rot, decoded, decoded_norms,
                             lists_indices, list_id, kind: str = "l2"):
    """Score one probe rank via the bf16 reconstruction cache — shared
    by single-chip and sharded searches. ``kind`` "ip" scores
    ``q_rot·(c_l + decoded)`` and returns negated similarities."""
    data = decoded[list_id]                          # (nq, ml, rot_dim)
    ids = lists_indices[list_id]                     # (nq, ml)
    if kind == "ip":
        qb = q_rot.astype(jnp.bfloat16)
        # one MXU pass on purpose: the bf16 reconstruction scan tier
        ip = jnp.einsum("qd,qld->ql", qb, data,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT)
        cq = jnp.sum(q_rot * centers_rot[list_id], axis=1)  # (nq,)
        return jnp.where(ids >= 0, -(ip + cq[:, None]), jnp.inf), ids
    resid = (q_rot - centers_rot[list_id]).astype(jnp.bfloat16)
    ip = jnp.einsum("qd,qld->ql", resid, data,
                    preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT)
    rr = jnp.sum(resid.astype(jnp.float32) ** 2, axis=1)
    d = rr[:, None] + decoded_norms[list_id] - 2.0 * ip
    return jnp.where(ids >= 0, jnp.maximum(d, 0.0), jnp.inf), ids


@functools.partial(jax.jit,
                   static_argnames=("k", "n_probes", "sqrt", "kind"))
def _search_impl_reconstruct(queries, centers, centers_rot, rot, decoded,
                             decoded_norms, lists_indices, k: int,
                             n_probes: int, sqrt: bool,
                             kind: str = "l2"):
    """MXU scan over the bf16 reconstruction cache: per probe rank,
    score = ||resid - decoded||² via the expanded form — the IVF-Flat
    interleaved-scan analogue (ivf_flat_search.cuh:665) with residuals
    in place of raw queries."""
    nq, dim = queries.shape

    from raft_tpu.neighbors.ivf_flat import _coarse_scores
    coarse = _coarse_scores(queries, centers, kind)
    _, probes = lax.top_k(-coarse, n_probes)
    q_rot = jnp.matmul(queries, rot.T, precision=matmul_precision())

    def probe_step(carry, p):
        best_d, best_i = carry
        d, ids = _score_probe_reconstruct(
            q_rot, centers_rot, decoded, decoded_norms, lists_indices,
            probes[:, p], kind=kind)
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
                   static_argnames=("k", "n_probes", "sqrt", "kind",
                                    "per_cluster"))
def _search_impl(queries, centers, centers_rot, rot, pq_centers, codes,
                 lists_indices, k: int, n_probes: int, sqrt: bool,
                 kind: str = "l2", per_cluster: bool = False):
    nq, dim = queries.shape
    n_lists = centers.shape[0]
    pq_dim = codes.shape[2]
    n_codes, pq_len = pq_centers.shape[1], pq_centers.shape[2]

    # coarse: select_clusters (reference :127)
    from raft_tpu.neighbors.ivf_flat import _coarse_scores
    coarse = _coarse_scores(queries, centers, kind)
    _, probes = lax.top_k(-coarse, n_probes)

    q_rot = queries @ rot.T  # (nq, rot_dim) (reference :1360 query rotation)

    bb = jnp.sum(pq_centers * pq_centers, axis=2)  # (pq_dim|L, n_codes)

    # the per-subspace IP LUT is probe-independent (no residual):
    # LUT[q, s, j] = sub_q(q,s)·book[s, j]; the per-probe center term
    # q_rot·c_l is added after the code gather (reference ip distance
    # dispatch). Hoisted out of the scan so it runs once, not n_probes
    # times. PER_CLUSTER books depend on the probed list, so its LUTs
    # are built inside the scan for both metrics.
    ip_lut = None
    if kind == "ip" and not per_cluster:
        ip_lut = jnp.einsum("qsl,sjl->qsj",
                            q_rot.reshape(nq, pq_dim, pq_len), pq_centers,
                            preferred_element_type=jnp.float32,
                            precision=matmul_precision())

    def probe_step(carry, p):
        best_d, best_i = carry
        list_id = probes[:, p]                           # (nq,)
        if per_cluster:
            books_l = pq_centers[list_id]                # (nq, C, pl)
            if kind == "ip":
                sub = q_rot.reshape(nq, pq_dim, pq_len)
                lut = jnp.einsum("qsl,qjl->qsj", sub, books_l,
                                 preferred_element_type=jnp.float32,
                                 precision=matmul_precision())
            else:
                resid = q_rot - centers_rot[list_id]
                sub = resid.reshape(nq, pq_dim, pq_len)
                ip = jnp.einsum("qsl,qjl->qsj", sub, books_l,
                                preferred_element_type=jnp.float32,
                                precision=matmul_precision())
                ss = jnp.sum(sub * sub, axis=2)
                lut = (ss[:, :, None] + bb[list_id][:, None, :]
                       - 2.0 * ip)
        elif kind == "ip":
            lut = ip_lut
        else:
            # per-query LUT from the rotated residual wrt this center
            resid = q_rot - centers_rot[list_id]         # (nq, rot_dim)
            sub = resid.reshape(nq, pq_dim, pq_len)
            # LUT[q, s, j] = ||sub(q,s) - pq_centers[s, j]||²
            ip = jnp.einsum("qsl,sjl->qsj", sub, pq_centers,
                            preferred_element_type=jnp.float32,
                            precision=matmul_precision())
            ss = jnp.sum(sub * sub, axis=2)
            lut = ss[:, :, None] + bb[None, :, :] - 2.0 * ip

        pcodes = codes[list_id].astype(jnp.int32)        # (nq, max_list, pq_dim)
        ids = lists_indices[list_id]                     # (nq, max_list)
        # scores[q, i] = Σ_s lut[q, s, pcodes[q, i, s]]
        gathered = jnp.take_along_axis(
            lut[:, None, :, :],                          # (nq, 1, pq_dim, n_codes)
            pcodes[:, :, :, None],                       # (nq, max_list, pq_dim, 1)
            axis=3)[..., 0]                              # (nq, max_list, pq_dim)
        d = jnp.sum(gathered, axis=2)
        if kind == "ip":
            cq = jnp.sum(q_rot * centers_rot[list_id], axis=1)
            d = jnp.where(ids >= 0, -(d + cq[:, None]), jnp.inf)
        else:
            d = jnp.where(ids >= 0, jnp.maximum(d, 0.0), jnp.inf)
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


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "cap",
                                             "bins", "sqrt", "kind",
                                             "lut_dtype", "internal_dtype",
                                             "per_cluster", "gather",
                                             "fused"))
def _fused_code_search(q, centers, centers_rot, rot, pq_centers, codes,
                       code_norms, lists_indices, *, k: int,
                       n_probes: int, cap: int, bins: int, sqrt: bool,
                       kind: str, lut_dtype, internal_dtype,
                       per_cluster: bool, gather: str = "rows",
                       fused: bool = False):
    """Single-dispatch code-resident search: coarse select_clusters,
    query rotation, the Pallas code scan and the candidate merge in ONE
    jitted computation (the reference search worker is likewise one
    kernel stream, ``ivf_pq_search.cuh:1007``; see
    ``_ivf_scan.fused_list_search`` for why dispatch count was the
    round-3 QPS lever)."""
    from raft_tpu.neighbors import _ivf_scan
    from raft_tpu.ops.pallas_ivf_scan import ivf_pq_code_scan_pallas
    with jax.named_scope("raft.plan.coarse"):
        probes = _ivf_scan.coarse_probes(q, centers, n_probes, kind=kind,
                                         use_pallas=True)
        q_rot = jnp.matmul(q, rot.T, precision=matmul_precision())
    with jax.named_scope("raft.plan.scan"):
        return ivf_pq_code_scan_pallas(
            q_rot, centers_rot, pq_centers, codes, code_norms,
            lists_indices, probes, k, cap, bins=bins, sqrt=sqrt,
            lut_dtype=lut_dtype, internal_distance_dtype=internal_dtype,
            metric=kind, per_cluster=per_cluster, gather=gather,
            fused=fused)


# guards the lazy reconstruction-cache materialization against
# concurrent searches (see _ensure_decoded)
_DECODE_LOCK = threading.Lock()


def _base_code_norms(index: Index):
    """Exact decoded-residual norms, derived once for older indexes
    that predate the build-time pass."""
    if index.code_norms is None:
        fn = (_code_norms_per_cluster
              if index.codebook_kind == CodebookGen.PER_CLUSTER
              else _code_norms)
        index.code_norms = fn(index.codes, index.pq_centers,
                              index.lists_indices)
    return index.code_norms


def _ensure_code_norms(index: Index, lut_dtype, kind: str):
    """Code norms matched to the LUT tier the code scan decodes: the
    fp8 tier's L2 epilogue must use norms of the fp8-QUANTIZED books
    (reference fp_8bit tier — the LUT there carries the same
    quantization in its distance terms); every other tier uses the
    exact build-time norms."""
    if (jnp.dtype(lut_dtype) == jnp.dtype(jnp.float8_e4m3fn)
            and kind == "l2"):
        if index.code_norms_fp8 is None:
            books8 = index.pq_centers.astype(
                jnp.float8_e4m3fn).astype(jnp.float32)
            fn = (_code_norms_per_cluster
                  if index.codebook_kind == CodebookGen.PER_CLUSTER
                  else _code_norms)
            index.code_norms_fp8 = fn(index.codes, books8,
                                      index.lists_indices)
        return index.code_norms_fp8
    return _base_code_norms(index)


def _ensure_decoded(index: Index, per_cluster: bool) -> None:
    """Materialize the bf16 reconstruction cache lazily.

    Lock: concurrent searches on one index must not run an unguarded
    check-then-set — it would materialize the ~8× decoded cache TWICE
    (peak-HBM hazard) and race the index mutation (r4 review finding).
    The decode programs are simple gathers, so holding the lock across
    them is bounded in practice."""
    if index.decoded is not None and index.decoded_norms is not None:
        return
    with _DECODE_LOCK:
        if index.decoded is None:
            dec_fn = (_decode_lists_per_cluster if per_cluster
                      else _decode_lists)
            index.decoded = dec_fn(index.codes, index.pq_centers,
                                   index.lists_indices)
        if index.decoded_norms is None:
            # alias the exact build-time norms — same quantity
            index.decoded_norms = _base_code_norms(index)


def search(index: Index, queries, k: int,
           params: SearchParams = SearchParams(), res=None
           ) -> Tuple[jax.Array, jax.Array]:
    """ANN search → (approx dists, neighbor ids) (reference
    ivf_pq_search.cuh:1251). ``params.scan_mode``: "auto" (default)
    resolves to the code-resident fused Pallas scan ("codes": u8 codes
    + transient decode tiles, pq_dim+8 bytes resident per vector) when
    the kernel tier is live, else the bf16 reconstruction-cache scan
    ("reconstruct", ~8x the codes' memory); "lut" is the CUDA-style
    gather formulation kept for parity testing."""
    with spans.span("raft.ivf_pq.search", k=k) as sp:
        return _search_spanned(index, queries, k, params, res, sp)


def _search_spanned(index: Index, queries, k: int, params, res, sp
                    ) -> Tuple[jax.Array, jax.Array]:
    from raft_tpu.neighbors import plan
    from raft_tpu.neighbors.ann_types import (MAX_QUERY_BATCH,
                                              batched_search,
                                              pin_scan_order)
    q = as_array(queries).astype(jnp.float32)
    sp.set_attr("nq", int(q.shape[0]))
    expects(q.shape[1] == index.dim, "ivf_pq.search: dim mismatch")
    if q.shape[0] > MAX_QUERY_BATCH:
        # reference batching loop (ivf_pq_search.cuh:1251/:1234); pin
        # "auto" choices from the FULL query count first
        import dataclasses
        mode = plan.route(index, q.shape[0], k, params).scan_mode
        pinned = pin_scan_order(dataclasses.replace(params, scan_mode=mode),
                                q.shape[0], index.n_lists)
        return batched_search(
            lambda qb: search(index, qb, k, pinned, res=res), q)
    r = plan.route(index, q.shape[0], k, params)
    sp.set_attrs(n_probes=r.n_probes, mode=r.scan_mode,
                 rescoring=r.rescoring)
    # per-batch telemetry (the batched path recurses here per
    # sub-batch, so queries sum correctly across the split)
    obs.counter("raft.ivf_pq.search.queries").inc(q.shape[0])
    obs.histogram("raft.ivf_pq.search.batch_size",
                  buckets=obs.SIZE_BUCKETS).observe(q.shape[0])
    obs.histogram("raft.ivf_pq.search.n_probes",
                  buckets=obs.SIZE_BUCKETS).observe(r.n_probes)
    # RAII scope (reference nvtx range in search, ivf_pq_search.cuh:
    # 1263), exception-safe; obs.timed opens the trace range AND the
    # wall-time histogram under one taxonomy name
    with obs.timed("raft.ivf_pq.search", mode=r.scan_mode):
        return plan.run(index, q, r, params)
