"""Pallas IVF list-scan kernel (fused fine phase of IVF-Flat search).

Reference: ``spatial/knn/detail/ivf_flat_search.cuh:665`` — the
``interleaved_scan_kernel``: one CUDA block per (query, probe) streams
the probed list's interleaved vectors, accumulates distances with
vectorized ILP, and keeps an in-kernel ``block_sort`` top-k so the
per-list score matrix never reaches global memory.

TPU re-design (list-major, not probe-major): a gather of "this query's
p-th list" per step re-reads every probed list ~nq·n_probes/n_lists
times from HBM. Instead the probe map is inverted (list → its probing
queries, the ``_ivf_scan`` inversion) and ONE kernel pass scans all
lists:

  grid cell = a chunk of ``LC`` lists. Per list ``l``:
    1. MXU matmul: list rows (max_list, dim) × gathered probing queries
       (cap, dim)ᵀ → transposed score block (max_list, cap) in VMEM —
       rows on sublanes, queries on lanes, the fused-kNN geometry.
    2. epilogue: + list-row norms + query norms − 2·ip, pad rows → +inf.
    3. binned partial top-k along sublanes → (B, cap) candidates with
       global db ids (TPU-KNN partial reduce; B ≥ 2k for the recall
       gate, B == max_list ⇒ exact). Row r goes to bin r % B. The
       lists arrive as the index stores them, max_list rounded only to
       8 rows: when B does not divide it, the last partial window is
       completed to B rows in VMEM (+inf, id −1), so no list-axis pad
       copies the lists in HBM.

Each list's rows are read from HBM exactly once per query batch; the
(max_list, cap) score block lives and dies in VMEM — the property the
reference's fused kernel has on GPU. Candidates are gathered back
per (query, probe) and merged with the exact Pallas ``select_k``.

The FUSED kernels (``fused=True``, which the search route takes
whenever k ≤ 256) go one step further: the per-query top-k state
stays resident in VMEM across the list grid (the ``_select_kernel``
output-block-revisiting trick, filtered-merge early-skip included), so
the candidate tensor never reaches HBM and the whole fine phase —
scan, scatter, select — is ONE ``pallas_call`` where the unfused path
needs three dispatches
(scan kernel → XLA gather → select_k kernel). This is the in-kernel
``block_sort`` of the reference's ``interleaved_scan_kernel``
(``ivf_flat_search.cuh:665``) rebuilt for the list-major TPU geometry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.dispatch import pallas_interpret
from raft_tpu.ops._util import (BIG_I32 as _BIG_I32,
                                VMEM_LIMIT as _VMEM_LIMIT,
                                round_up as _round_up, dot_nt_f32)
from raft_tpu.core.precision import kernel_matmul_mode


def _flat_list_candidates(scale, q, y, norms_l, ids, *, bins: int,
                          metric: str, precision):
    """One IVF-Flat list's binned candidates — the shared per-list body
    of the unfused scan kernel (which writes the blocks to HBM for a
    separate merge dispatch) and the fused scan+select kernel (which
    merges them straight into the VMEM-resident top-k state).

    ``q`` (cap, dim) probing queries, ``y`` (ML, dim) list rows,
    ``norms_l``/``ids`` (ML,) → ``(cd (bins, cap), ci (bins, cap))``.
    """
    ml = y.shape[0]
    cap = q.shape[0]
    if y.dtype == jnp.bfloat16:
        ip = jax.lax.dot_general(
            y, q.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    elif y.dtype == jnp.int8:
        # int8 rides the MXU as bf16 (exact for |v| ≤ 127); the
        # kDivisor-style scale folds into the accumulated product
        ip = scale * jax.lax.dot_general(
            y.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        ip = dot_nt_f32(y, q, precision)             # (ML, cap)
    ids_b = jnp.broadcast_to(ids[:, None], (ml, cap))
    if metric == "ip":
        # similarity → negate: smaller-is-better uniformly (the
        # reference's max-heap IP routing, fused_l2_knn.cuh:947)
        d = jnp.where(ids_b >= 0, -ip, jnp.inf)
    else:
        qq = jnp.sum(q.astype(jnp.float32) * q.astype(jnp.float32),
                     axis=1)[None, :]                # (1, cap)
        d = norms_l[:, None] + qq - 2.0 * ip
        d = jnp.where(ids_b >= 0, jnp.maximum(d, 0.0), jnp.inf)

    # STRIDED bins (row r → bin r % B): bucketized rows follow
    # dataset order, so a query's true neighbors sit in adjacent
    # rows — contiguous bins would collide them (measured 0.87 vs
    # 0.99+ recall on clustered data); striding decorrelates free.
    # The index rounds max_list to 8 rows while B is a search-time
    # choice, so the list may end in a partial window of t = ML − w·B
    # rows: it is completed to B rows HERE, in VMEM (+inf, id −1 — what
    # a pad row scores), and folded in as one more window. Every row
    # keeps bin r % B, so the candidates are those of a list padded to
    # a multiple of B, without that padded copy of the lists in HBM.
    w, t = divmod(ml, bins)
    wins_d, wins_i = [], []
    if w:
        wins_d.append(d[:w * bins].reshape(w, bins, cap))
        wins_i.append(ids_b[:w * bins].reshape(w, bins, cap))
    if t:
        wins_d.append(jnp.concatenate(
            [d[w * bins:], jnp.full((bins - t, cap), jnp.inf, d.dtype)],
            axis=0)[None])
        wins_i.append(jnp.concatenate(
            [ids_b[w * bins:], jnp.full((bins - t, cap), -1, jnp.int32)],
            axis=0)[None])
    cd = functools.reduce(jnp.minimum,
                          [jnp.min(db_, axis=0) for db_ in wins_d])
    ci = functools.reduce(jnp.minimum, [
        jnp.min(jnp.where(db_ == cd[None, :, :], rb, _BIG_I32), axis=0)
        for db_, rb in zip(wins_d, wins_i)])            # (B, cap)
    return cd, jnp.where(ci == _BIG_I32, -1, ci)


def _list_scan_kernel(scale_ref, qsub_ref, data_ref, norms_ref, ids_ref,
                      cd_ref, ci_ref, *, lc: int, bins: int, metric: str,
                      precision):
    scale = scale_ref[0, 0]

    def one_list(l):
        cd, ci = _flat_list_candidates(
            scale, qsub_ref[l], data_ref[l], norms_ref[l, 0],
            ids_ref[l, 0], bins=bins, metric=metric, precision=precision)
        cd_ref[l] = cd.astype(cd_ref.dtype)
        ci_ref[l] = ci

    # lc > 1 iterates via fori_loop so the Mosaic program stays ONE
    # list-body regardless of lc — a Python loop here unrolls lc
    # matmul+epilogue copies into the kernel, an unbounded program
    # growth that makes compiles slow. lc == 1 stays loop-free (the
    # structurally simplest tier).
    if lc == 1:
        one_list(0)
    else:
        jax.lax.fori_loop(0, lc, lambda l, c: (one_list(l), c)[1], 0)


@functools.partial(jax.jit, static_argnames=("bins", "lc", "metric",
                                             "out_dtype", "interpret"))
def _list_scan_call(qsub, data, norms, ids, bins: int, lc: int,
                    scale, interpret: bool, metric: str = "l2",
                    out_dtype=jnp.float32):
    n_lists, cap, dim = qsub.shape
    max_list = data.shape[1]
    gc = n_lists // lc
    kern = functools.partial(
        _list_scan_kernel, lc=lc, bins=bins, metric=metric,
        precision=kernel_matmul_mode(interpret))
    # scale rides as a (1,1) traced input: a static arg would recompile
    # the kernel for every distinct int8 index scale
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    # norms/ids ride with a singleton middle axis: Mosaic constrains the
    # LAST TWO block dims (divisible by (8, 128) or equal to the array
    # dim); as 2-D (lc, max_list) blocks the lc slot is constrained and
    # lc < 8 fails to lower — as (lc, 1, max_list) the constrained pair
    # is (1, max_list) == the array dims, legal for every lc
    norms3 = norms[:, None, :]
    ids3 = ids[:, None, :]
    cd, ci = pl.pallas_call(
        kern,
        grid=(gc,),
        in_specs=[pl.BlockSpec((1, 1), lambda g: (0, 0)),
                  pl.BlockSpec((lc, cap, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, max_list, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0))],
        out_specs=[pl.BlockSpec((lc, bins, cap), lambda g: (g, 0, 0)),
                   pl.BlockSpec((lc, bins, cap), lambda g: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_lists, bins, cap), out_dtype),
                   jax.ShapeDtypeStruct((n_lists, bins, cap), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_lists * max_list * cap * dim,
            bytes_accessed=(data.dtype.itemsize * n_lists * max_list * dim
                            + 4 * n_lists * cap * dim
                            + 8 * n_lists * bins * cap),
            transcendentals=0),
        interpret=interpret,
    )(scale_arr, qsub, data, norms3, ids3)
    return cd, ci


def lc_mode() -> int:
    """Resolve the ``RAFT_TPU_IVF_LC`` override OUTSIDE jit (the
    ``gather_mode()`` contract): callers thread the value through the
    fused searches as a static argument, so the jit cache keys on it
    and an in-process env flip takes effect on the next call instead of
    silently re-executing the first-compiled program. 0 = auto."""
    import os
    return int(os.environ.get("RAFT_TPU_IVF_LC", "0"))


def _pick_lc(n_lists: int, max_list: int, cap: int, dim: int,
             itemsize: int, override: int = 0, tail: int = 0) -> int:
    """Lists per grid cell: enough to amortize per-step overhead while
    the (LC·max_list·dim) data block + score blocks stay well under the
    VMEM cap (double-buffered). ``tail``: rows of the completed partial
    bins window (``_flat_list_candidates``), 0 when bins divide
    max_list.

    ``override`` > 0 pins the value (snapped down to a divisor of
    n_lists) — resolved from ``RAFT_TPU_IVF_LC`` by ``lc_mode()`` in
    the search route and threaded here statically. ``1`` =
    grid-per-list: the PQ kernel's structure, loop-free kernel body."""
    if override > 0:
        lc = min(override, n_lists)
        while n_lists % lc:
            lc -= 1
        return lc
    per_list = (max_list * dim * itemsize          # data block
                + cap * dim * 4                    # gathered queries
                + max_list * cap * 4               # score block
                + max_list * (4 + 4)               # norms + ids
                + tail * cap * (4 + 4))            # tail window d + ids
    budget = _VMEM_LIMIT // 3
    # ≤ 8 bounds the grid-step working set; the kernel body itself is
    # lc-independent now (fori_loop), so this is a VMEM/pipelining
    # knob, not a program-size one
    lc = max(1, min(8, budget // max(per_list, 1)))
    while n_lists % lc:
        lc -= 1
    return lc


# ---------------------------------------------------------------------------
# Fused scan + select-k (ISSUE 7): the list scan keeps a running
# per-query top-k state RESIDENT IN VMEM across the list-chunk grid
# dimension — the same output-block-revisiting trick `_select_kernel`
# uses across candidate tiles, including its filtered-merge early-skip —
# so the (n_lists, bins, cap) candidate tensor never reaches HBM and
# the scan → gather → select_k chain collapses from three dispatches
# (two pallas_calls + an XLA gather) to ONE pallas_call.
# ---------------------------------------------------------------------------

# finite stand-in for +inf through the scatter matmul (inf · 0 = NaN
# would poison the one-hot accumulation); far above any real distance
_BIG_F32 = 3.0e38


def _merge_state(od_ref, oi_ref, cd, ci, qm, *, k: int, cap_axis: int):
    """Scatter one list's candidate block onto the per-query running
    top-k state resident in the revisited ``(kp, nqp)`` output block,
    then an exact filtered merge.

    ``cd``/``ci`` carry the probing-slot axis at ``cap_axis`` (flat/bq
    bin-major ``(bins, cap)`` → 1; pq slot-major ``(cap, bins)`` → 0);
    ``qm`` (cap,) holds the list's probing-query ids (−1 pad). The
    scatter rides the MXU as one-hot × candidates: each list's slot →
    query map is injective (a query probes a list at most once), so
    every output lane receives EXACTLY one slot's value and, at
    ``Precision.HIGHEST``, the permutation is exact (products with 1.0,
    single nonzero per accumulation — even the 3×bf16 decomposition
    reconstructs f32 exactly). Ids split into f32-exact halves
    (``id >> 12`` and ``id & 0xFFF`` are both < 2^24 for id < 2^31;
    the −1 sentinel round-trips: (−1)·4096 + 4095 = −1). Lanes no slot
    maps to read ``_BIG_F32``/−1 and lose every merge; callers mask
    id < 0 → +inf after the final grid step.

    The merge is the ``_select_kernel`` filtered merge verbatim: if no
    scattered candidate beats any lane's current k-th best, the list is
    skipped after one vectorized compare; otherwise k rounds of
    (min, argmin-by-row, invalidate) over the concatenated
    [state; candidates] block re-sort the state in place.
    """
    nqp = od_ref.shape[1]
    cap = qm.shape[0]
    iq = jax.lax.broadcasted_iota(jnp.int32, (cap, nqp), 1)
    oh = ((qm[:, None] == iq) & (qm[:, None] >= 0)).astype(jnp.float32)
    mapped = jnp.max(oh, axis=0, keepdims=True) > 0.0    # (1, nqp)
    cn = (((cap_axis,), (0,)), ((), ()))
    hp = jax.lax.Precision.HIGHEST
    cdf = jnp.minimum(cd.astype(jnp.float32), _BIG_F32)
    sd = jax.lax.dot_general(cdf, oh, cn, precision=hp,
                             preferred_element_type=jnp.float32)
    hi = jax.lax.dot_general((ci >> 12).astype(jnp.float32), oh, cn,
                             precision=hp,
                             preferred_element_type=jnp.float32)
    lo = jax.lax.dot_general((ci & 0xFFF).astype(jnp.float32), oh, cn,
                             precision=hp,
                             preferred_element_type=jnp.float32)
    si = hi.astype(jnp.int32) * 4096 + lo.astype(jnp.int32)
    sd = jnp.where(mapped, sd, _BIG_F32)                 # (B, nqp)
    si = jnp.where(mapped, si, -1)
    b = sd.shape[0]

    kth = od_ref[k - 1:k, :]                             # (1, nqp)

    @pl.when(jnp.any(sd < kth))
    def _():
        c_d = jnp.concatenate([od_ref[0:k, :], sd], axis=0)
        c_i = jnp.concatenate([oi_ref[0:k, :], si], axis=0)
        ri = jax.lax.broadcasted_iota(jnp.int32, (k + b, nqp), 0)

        def round_(r, carry):
            cdd, cii = carry
            m_ = jnp.min(cdd, axis=0, keepdims=True)     # (1, nqp)
            first = jnp.min(jnp.where(cdd == m_, ri, _BIG_I32), axis=0,
                            keepdims=True)
            sel = ri == first                            # one-hot/lane
            idx = jnp.sum(jnp.where(sel, cii, 0), axis=0, keepdims=True)
            od_ref[pl.dslice(r, 1), :] = m_
            oi_ref[pl.dslice(r, 1), :] = idx
            return jnp.where(sel, jnp.inf, cdd), cii

        jax.lax.fori_loop(0, k, round_, (c_d, c_i), unroll=False)


def _init_state(od_ref, oi_ref):
    """First-grid-step init of the revisited top-k state block."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        od_ref[...] = jnp.full(od_ref.shape, jnp.inf, od_ref.dtype)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, jnp.int32)


def _finish_fused(od, oi, nq: int, k: int, sqrt: bool):
    """Tail of the fused scan+select calls: slice the resident state
    back to (nq, k) and apply the ``merge_candidates`` output
    conventions (id −1 ⇒ +inf distance, optional sqrt)."""
    with jax.named_scope("raft.plan.merge"):
        d = od[:k, :nq].T
        i = oi[:k, :nq].T
        d = jnp.where(i >= 0, d, jnp.inf)
        if sqrt:
            d = jnp.sqrt(jnp.maximum(d, 0.0))
        return d, i


def _pick_lc_fused(n_lists: int, max_list: int, cap: int, dim: int,
                   itemsize: int, k: int, nq: int, bins: int,
                   override: int = 0, tail: int = 0) -> int:
    """``_pick_lc`` with the fused kernel's extra VMEM residents: the
    (kp, nqp) state blocks (revisited outputs — live the whole grid)
    and the per-list scatter/merge temporaries (one-hot, scattered
    halves, merge concat). The temporaries don't scale with lc (the
    fori body reuses them) but they shrink the per-list budget."""
    if override > 0:
        lc = min(override, n_lists)
        while n_lists % lc:
            lc -= 1
        return lc
    kp = _round_up(k, 8)
    nqp = _round_up(nq, 128)
    fixed = (2 * kp * nqp * 8          # d+id state blocks
             + cap * nqp * 4           # one-hot
             + 3 * bins * nqp * 4      # scattered d / id halves
             + (k + bins) * nqp * 8)   # merge concat block
    per_list = (max_list * dim * itemsize
                + cap * dim * 4
                + max_list * cap * 4
                + max_list * (4 + 4)
                + tail * cap * (4 + 4))
    budget = max((_VMEM_LIMIT // 3) - fixed, 0)
    lc = max(1, min(8, budget // max(per_list, 1)))
    while n_lists % lc:
        lc -= 1
    return lc


def _fused_list_scan_kernel(scale_ref, qsub_ref, data_ref, norms_ref,
                            ids_ref, qmap_ref, od_ref, oi_ref, *,
                            lc: int, bins: int, k: int, metric: str,
                            precision):
    """IVF-Flat fine phase as ONE program: per list, the shared scoring
    + binned-candidate body, merged straight into the resident state."""
    scale = scale_ref[0, 0]
    _init_state(od_ref, oi_ref)

    def one_list(l):
        cd, ci = _flat_list_candidates(
            scale, qsub_ref[l], data_ref[l], norms_ref[l, 0],
            ids_ref[l, 0], bins=bins, metric=metric, precision=precision)
        _merge_state(od_ref, oi_ref, cd, ci, qmap_ref[l, 0], k=k,
                     cap_axis=1)

    if lc == 1:
        one_list(0)
    else:
        jax.lax.fori_loop(0, lc, lambda l, c: (one_list(l), c)[1], 0)


@functools.partial(jax.jit, static_argnames=("bins", "lc", "k", "nq",
                                             "metric", "interpret"))
def _fused_list_scan_call(qsub, data, norms, ids, qmap, bins: int,
                          lc: int, k: int, nq: int, scale,
                          interpret: bool, metric: str = "l2"):
    n_lists, cap, dim = qsub.shape
    max_list = data.shape[1]
    gc = n_lists // lc
    kp = _round_up(k, 8)
    nqp = _round_up(nq, 128)
    kern = functools.partial(
        _fused_list_scan_kernel, lc=lc, bins=bins, k=k, metric=metric,
        precision=kernel_matmul_mode(interpret))
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    norms3 = norms[:, None, :]
    ids3 = ids[:, None, :]
    qmap3 = qmap[:, None, :]
    od, oi = pl.pallas_call(
        kern,
        grid=(gc,),
        in_specs=[pl.BlockSpec((1, 1), lambda g: (0, 0)),
                  pl.BlockSpec((lc, cap, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, max_list, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, cap), lambda g: (g, 0, 0))],
        # the whole (kp, nqp) state is ONE block revisited by every
        # grid step (constant index map) — it stays in VMEM across the
        # list grid and is written back once
        out_specs=[pl.BlockSpec((kp, nqp), lambda g: (0, 0)),
                   pl.BlockSpec((kp, nqp), lambda g: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((kp, nqp), jnp.float32),
                   jax.ShapeDtypeStruct((kp, nqp), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_lists * max_list * cap * dim
            + 6 * n_lists * bins * cap * nqp,
            bytes_accessed=(data.dtype.itemsize * n_lists * max_list * dim
                            + 4 * n_lists * cap * dim + 8 * kp * nqp),
            transcendentals=0),
        interpret=interpret,
    )(scale_arr, qsub, data, norms3, ids3, qmap3)
    return od, oi


def _fused_bq_scan_kernel(qsub_ref, bits_ref, norms2_ref, scales_ref,
                          ids_ref, qmap_ref, cent_ref, od_ref, oi_ref, *,
                          lc: int, bins: int, dim: int, k: int,
                          metric: str):
    _init_state(od_ref, oi_ref)

    def one_list(l):
        cd, ci = _bq_list_candidates(
            qsub_ref[l], bits_ref[l], norms2_ref[l, 0], scales_ref[l, 0],
            ids_ref[l, 0], bins=bins, dim=dim, metric=metric)
        if metric == "ip":
            # the per-(list, slot) center term −q·c_l — the unfused
            # tier's post-scan rank-1 correction applied in-kernel:
            # constant per slot, so it commutes with the binned min
            corr = jnp.sum(qsub_ref[l] * cent_ref[l, 0][None, :], axis=1)
            cd = cd - corr[None, :]
        _merge_state(od_ref, oi_ref, cd, ci, qmap_ref[l, 0], k=k,
                     cap_axis=1)

    if lc == 1:
        one_list(0)
    else:
        jax.lax.fori_loop(0, lc, lambda l, c: (one_list(l), c)[1], 0)


@functools.partial(jax.jit, static_argnames=("bins", "lc", "dim", "k",
                                             "nq", "interpret", "metric"))
def _fused_bq_scan_call(qsub, bits_i32, norms2, scales, ids, qmap,
                        centers_rot, bins: int, lc: int, dim: int,
                        k: int, nq: int, interpret: bool,
                        metric: str = "l2"):
    n_lists, cap, _ = qsub.shape
    max_list = bits_i32.shape[1]
    w = bits_i32.shape[2]
    gc = n_lists // lc
    kp = _round_up(k, 8)
    nqp = _round_up(nq, 128)
    kern = functools.partial(_fused_bq_scan_kernel, lc=lc, bins=bins,
                             dim=dim, k=k, metric=metric)
    norms3 = norms2[:, None, :]
    scales3 = scales[:, None, :]
    ids3 = ids[:, None, :]
    qmap3 = qmap[:, None, :]
    cent3 = centers_rot[:, None, :]
    od, oi = pl.pallas_call(
        kern,
        grid=(gc,),
        in_specs=[pl.BlockSpec((lc, cap, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, max_list, w), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, cap), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, dim), lambda g: (g, 0, 0))],
        out_specs=[pl.BlockSpec((kp, nqp), lambda g: (0, 0)),
                   pl.BlockSpec((kp, nqp), lambda g: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((kp, nqp), jnp.float32),
                   jax.ShapeDtypeStruct((kp, nqp), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_lists * max_list * cap * dim
            + 6 * n_lists * bins * cap * nqp,
            bytes_accessed=(4 * n_lists * max_list * w
                            + 4 * n_lists * cap * dim + 8 * kp * nqp),
            transcendentals=0),
        interpret=interpret,
    )(qsub, bits_i32, norms3, scales3, ids3, qmap3, cent3)
    return od, oi


def _cell_rows(ext_ref, split: int, sub_ml: int):
    """Rows grid cell ``g`` decodes: its sub-list's share of the list's
    extent (``_scan_extents``), read from the scalar-prefetched
    ``ext_ref`` — 0 for a cell past the list's last row, or of a list
    no query probes."""
    g = pl.program_id(0)
    return jnp.clip(ext_ref[g // split] - (g % split) * sub_ml, 0, sub_ml)


def _fused_pq_scan_kernel(ext_ref, qsub_ref, codes_ref, norms_ref, ids_ref,
                          books_ref, qmap_ref, cent_ref, od_ref, oi_ref,
                          *, bins: int, chunk: int, split: int, k: int,
                          metric: str, pq_dim: int, pq_len: int,
                          n_codes: int, lut_dtype, per_cluster: bool):
    _init_state(od_ref, oi_ref)
    n = _cell_rows(ext_ref, split, codes_ref.shape[2])

    # a cell with no stored row, or of an unprobed list, has no
    # candidate to offer: no decode, no score, no merge
    @pl.when(n > 0)
    def _():
        cd, ci = _pq_cell_candidates(
            qsub_ref[0], codes_ref, norms_ref, ids_ref, books_ref, n,
            bins=bins, chunk=chunk, metric=metric, pq_dim=pq_dim,
            pq_len=pq_len, n_codes=n_codes, lut_dtype=lut_dtype,
            per_cluster=per_cluster)
        if metric == "ip":
            # −q·c_l in-kernel (see _fused_bq_scan_kernel); for
            # PER_CLUSTER both operands arrive p-major permuted — the
            # dot is invariant
            corr = jnp.sum(qsub_ref[0] * cent_ref[0, 0][None, :], axis=1)
            cd = cd - corr[:, None]
        _merge_state(od_ref, oi_ref, cd, ci, qmap_ref[0, 0], k=k,
                     cap_axis=0)


@functools.partial(jax.jit, static_argnames=("bins", "chunk", "k", "nq",
                                             "metric", "lut_dtype",
                                             "interpret", "split",
                                             "per_cluster"))
def _fused_pq_scan_call(ext, qsub, codes_t, norms, ids, books, qmap,
                        centers_rot, bins: int, chunk: int, k: int,
                        nq: int, interpret: bool, metric: str, lut_dtype,
                        split: int = 1, per_cluster: bool = False):
    """The fused tail of the code scan: same grid/operands as
    ``_pq_scan_call`` (incl. the list extents, the ``split`` sub-cell
    sharing of a list's query/qmap blocks via ``g // split``) plus the
    qmap and rotated centers, with the candidate blocks replaced by the
    revisited state."""
    n_lists, cap, rot_dim = qsub.shape
    n_cells, pq_dim, max_list = codes_t.shape
    kp = _round_up(k, 8)
    nqp = _round_up(nq, 128)
    books_spec, n_codes, pq_len = _pq_books_spec(books, pq_dim, rot_dim,
                                                 split, per_cluster)
    kern = functools.partial(
        _fused_pq_scan_kernel, bins=bins, chunk=chunk, split=split, k=k,
        metric=metric, pq_dim=pq_dim, pq_len=pq_len, n_codes=n_codes,
        lut_dtype=jnp.dtype(lut_dtype), per_cluster=per_cluster)
    norms3 = norms[:, None, :]
    ids3 = ids[:, None, :]
    qmap3 = qmap[:, None, :]
    cent3 = centers_rot[:, None, :]
    od, oi = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_cells,),
            in_specs=[pl.BlockSpec((1, cap, rot_dim),
                                   lambda g, e: (g // split, 0, 0)),
                      pl.BlockSpec((1, pq_dim, max_list),
                                   lambda g, e: (g, 0, 0)),
                      pl.BlockSpec((1, 1, max_list), lambda g, e: (g, 0, 0)),
                      pl.BlockSpec((1, 1, max_list), lambda g, e: (g, 0, 0)),
                      books_spec,
                      pl.BlockSpec((1, 1, cap),
                                   lambda g, e: (g // split, 0, 0)),
                      pl.BlockSpec((1, 1, rot_dim),
                                   lambda g, e: (g // split, 0, 0))],
            out_specs=[pl.BlockSpec((kp, nqp), lambda g, e: (0, 0)),
                       pl.BlockSpec((kp, nqp), lambda g, e: (0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((kp, nqp), jnp.float32),
                   jax.ShapeDtypeStruct((kp, nqp), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_cells * max_list * rot_dim * pq_dim * n_codes
            + 2 * n_cells * max_list * cap * rot_dim
            + 6 * n_cells * bins * cap * nqp,
            bytes_accessed=(n_cells * max_list * pq_dim
                            + 4 * n_lists * cap * rot_dim + 8 * kp * nqp),
            transcendentals=0),
        interpret=interpret,
    )(ext, qsub, jax.lax.bitcast_convert_type(codes_t, jnp.int8), norms3,
      ids3, books, qmap3, cent3)
    return od, oi


class _Layout:
    """Shared prologue of the list-major scans: bins resolution, probe
    inversion, lane-aligned inverted-table width, and (BQ and PQ only)
    list-axis padding to a bins multiple. The flat scan pads nothing
    in HBM: it reads the lists as the index stores them and completes
    a partial last bins window in VMEM (``_flat_list_candidates``);
    ``mlp`` is the padded length only the BQ and PQ scans read.

    ``bins``: 0 = auto — 4k bins. IVF lists concentrate a query's true
    neighbors far more than brute-force tiles do, so the collision
    budget needs more width than fused_knn's 2k default (recall 0.944 →
    0.97+ at 16/64 probes on clustered data); the merge rides the fast
    select_k, so the wider candidate set costs little. -1 = exact (one
    row per bin); >0 explicit.
    """

    def __init__(self, probes, n_lists: int, max_list: int, cap: int,
                 bins: int, k: int):
        from raft_tpu.neighbors._ivf_scan import _invert_probes
        bins = _Layout.resolve_bins(bins, k, max_list)
        self.qmap, self.inv_pos = _invert_probes(probes, n_lists, cap)
        # the list axis rounded up to a bins multiple (pad rows: id -1
        # → +inf); the flat scan's partial window is max_list % bins
        self.mlp = _round_up(max_list, bins if bins > 0 else 1)
        self.bins = self.mlp if bins < 0 else bins
        self.tail = self.bins if ragged_tail(max_list, bins, k) else 0
        self.cap = cap
        self.capp = _round_up(max(cap, 8), 8)  # lane-aligned table width

    @staticmethod
    def resolve_bins(bins: int, k: int, max_list: int) -> int:
        return min(max(4 * k, 64), max_list) if bins == 0 else bins

    def pad_lists(self, arr, max_list: int, fill=0):
        if self.mlp == max_list:
            return arr
        pad = [(0, 0), (0, self.mlp - max_list)] + [(0, 0)] * (arr.ndim - 2)
        return jnp.pad(arr, pad, constant_values=fill)

    def padded_qmap(self):
        if self.capp == self.cap:
            return self.qmap
        return jnp.pad(self.qmap, ((0, 0), (0, self.capp - self.cap)),
                       constant_values=-1)

    def merge(self, cd, ci, probes, k: int, sqrt: bool):
        cd = jnp.swapaxes(cd, 1, 2)                # (n_lists, cap, B)
        ci = jnp.swapaxes(ci, 1, 2)
        return self.merge_cap_major(cd, ci, probes, k, sqrt)

    def merge_cap_major(self, cd, ci, probes, k: int, sqrt: bool):
        """Merge candidate blocks already in (n_lists, cap, B) layout."""
        from raft_tpu.neighbors._ivf_scan import merge_candidates
        with jax.named_scope("raft.plan.merge"):
            return merge_candidates(
                cd[:, :self.cap].astype(jnp.float32), ci[:, :self.cap],
                probes, self.inv_pos, k, sqrt, use_pallas_select=True,
                cap=self.cap)


def ragged_tail(max_list: int, bins: int, k: int) -> bool:
    """Whether the flat list scan completes a partial last bins window
    in VMEM: the resolved bins (``_Layout``) do not divide
    ``max_list``. Exact bins (-1) never do."""
    b = _Layout.resolve_bins(bins, k, max_list)
    return b > 0 and max_list % b != 0


def ivf_list_scan_pallas(queries, lists_data, lists_norms, lists_indices,
                         probes, k: int, cap: int, scale=1.0,
                         bins: int = 0, sqrt: bool = False,
                         metric: str = "l2", gather: str = "",
                         internal_dtype=None, lc: int = 0,
                         fused: bool = False):
    """Fused list-major IVF-Flat fine scan + merge.

    ``queries`` (nq, dim) f32; ``lists_data`` (n_lists, max_list, dim)
    f32/bf16/int8; ``probes`` (nq, n_probes) int32; ``cap`` the inverted
    table width (``_ivf_scan.probe_cap``). ``bins``: see ``_Layout``.
    ``metric``: "l2" (squared, ``sqrt`` optional) or "ip" (returns
    NEGATED similarities, ascending — callers postprocess). ``lc``:
    lists per grid cell, 0 = auto (callers resolve ``lc_mode()``
    outside jit). ``fused``: keep the top-k state resident in the scan
    kernel (ONE pallas_call — no candidate tensor, no gather, no
    select_k dispatch; the search route takes it for k ≤ 256).
    Returns (dists (nq, k), ids (nq, k)) sorted best-first.
    """
    nq, dim = queries.shape
    n_lists, max_list = lists_indices.shape
    # the lists go to the kernel as stored: a max_list that bins do not
    # divide ends in a partial window, completed in VMEM (no HBM pad)
    lay = _Layout(probes, n_lists, max_list, cap, bins, k)

    # pre-gather: each list's probing queries → (n_lists, cap, dim).
    # ~cap/mean-probes ≤ 2× the query bytes; read once by the kernel.
    # Strategy (row gather vs one-hot MXU) via RAFT_TPU_GATHER; jitted
    # callers pass it resolved (``gather``) so the env isn't trace-frozen
    from raft_tpu.neighbors._ivf_scan import gather_query_rows
    qsub = gather_query_rows(queries, lay.padded_qmap(), mode=gather)
    if fused:
        lc = _pick_lc_fused(n_lists, max_list, lay.capp, dim,
                            lists_data.dtype.itemsize, k, nq, lay.bins,
                            override=lc, tail=lay.tail)
        od, oi = _fused_list_scan_call(
            qsub, lists_data, lists_norms, lists_indices,
            lay.padded_qmap(), lay.bins, lc, k, nq, scale,
            pallas_interpret(), metric=metric)
        return _finish_fused(od, oi, nq, k, sqrt)
    lc = _pick_lc(n_lists, max_list, lay.capp, dim,
                  lists_data.dtype.itemsize, override=lc, tail=lay.tail)
    # internal_dtype: candidate-block dtype carried to the merge (the
    # IVF-PQ internal_distance_dtype role) — bf16 halves the kernel's
    # HBM writeback+readback; the merge re-ranks in f32 either way
    cd, ci = _list_scan_call(qsub, lists_data, lists_norms, lists_indices,
                             lay.bins, lc, scale, pallas_interpret(),
                             metric=metric,
                             out_dtype=internal_dtype or jnp.float32)
    return lay.merge(cd, ci, probes, k, sqrt)


def _bq_list_candidates(q, words, n2_l, sc_l, ids, *, bins: int,
                        dim: int, metric: str):
    """One BQ list's binned estimator candidates (the shared per-list
    body — see ``_flat_list_candidates``). ``q`` (cap, dim) f32 probing
    queries (center-offset for the l2 core), ``words`` (ML, w) int32
    bit payload → ``(cd (bins, cap), ci (bins, cap))``."""
    ml = words.shape[0]
    cap = q.shape[0]
    w = words.shape[1]
    cols = []
    for j in range(w):
        wj = words[:, j:j + 1]                       # (ML, 1)
        sh = jax.lax.broadcasted_iota(jnp.int32, (1, 32), 1)
        # (x >> s) & 1 extracts bit s for any int32 x, arithmetic
        # shift included — only bit 0 of the shifted value is read
        cols.append((jax.lax.shift_right_logical(
            jnp.broadcast_to(wj, (ml, 32)),
            jnp.broadcast_to(sh, (ml, 32))) & 1))
    bits = jnp.concatenate(cols, axis=1)[:, :dim]    # (ML, dim) 0/1
    pm1 = (2 * bits - 1).astype(jnp.bfloat16)        # ±1
    ip = jax.lax.dot_general(
        pm1, q.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (ML, cap)
    qq = jnp.sum(q * q, axis=1)[None, :]             # (1, cap)
    n2 = n2_l[:, None]                               # (ML, 1)
    sc = sc_l[:, None]                               # (ML, 1)
    ids_b = jnp.broadcast_to(ids[:, None], (ml, cap))
    if metric == "ip":
        # estimator core −s·⟨q, dec⟩; the per-(list, query) center
        # term −q·c_l is a rank-1 correction applied to the
        # candidate blocks AFTER the scan (the ivf_pq ip pattern;
        # the fused kernel applies it in-kernel — constant per slot,
        # so it commutes with the binned min)
        d = -(sc * ip)
    else:
        d = n2 + qq - 2.0 * sc * ip
    # NO maximum(d, 0) clamp here: the 1-bit estimator legitimately
    # goes negative when it overshoots near a true neighbor, and
    # clamping would collapse exactly the strongest candidates into
    # id-order ties (unlike the exact-distance kernels, where the
    # clamp only removes fp noise). The XLA tier matches.
    d = jnp.where(ids_b >= 0, d, jnp.inf)
    wb = ml // bins
    db_ = d.reshape(wb, bins, cap)                   # strided bins
    cd = jnp.min(db_, axis=0)
    rb = ids_b.reshape(wb, bins, cap)
    ci = jnp.min(jnp.where(db_ == cd[None, :, :], rb, _BIG_I32),
                 axis=0)
    return cd, jnp.where(ci == _BIG_I32, -1, ci)


def _bq_scan_kernel(qsub_ref, bits_ref, norms2_ref, scales_ref, ids_ref,
                    cd_ref, ci_ref, *, lc: int, bins: int, dim: int,
                    metric: str):
    """Binary-quantized list scan (ivf_bq's fine phase): unpack the
    1-bit sign codes to a transient ±1 bf16 tile IN VMEM — the 8×-HBM
    win over reading bf16 rows — then the same transposed-score
    geometry as ``_list_scan_kernel`` (rows on sublanes, probing
    queries on lanes) and its strided binned partial top-k.

    Estimator: ``est = ||q_l||² + ||r||² − 2·s·⟨q_l, sign(r)⟩``
    (see ivf_bq.py). Shift/mask unpack loops over the w ≤ dim/32 words
    in Python — w is 4 at d=128, so that unroll stays tiny; the list
    loop is a fori_loop like ``_list_scan_kernel``'s (program size
    must not scale with lc).
    """
    def one_list(l):
        cd, ci = _bq_list_candidates(
            qsub_ref[l], bits_ref[l], norms2_ref[l, 0], scales_ref[l, 0],
            ids_ref[l, 0], bins=bins, dim=dim, metric=metric)
        cd_ref[l] = cd.astype(cd_ref.dtype)
        ci_ref[l] = ci

    if lc == 1:
        one_list(0)
    else:
        jax.lax.fori_loop(0, lc, lambda l, c: (one_list(l), c)[1], 0)


@functools.partial(jax.jit, static_argnames=("bins", "lc", "dim",
                                             "interpret", "metric"))
def _bq_scan_call(qsub, bits_i32, norms2, scales, ids, bins: int,
                  lc: int, dim: int, interpret: bool,
                  metric: str = "l2"):
    n_lists, cap, _ = qsub.shape
    max_list = bits_i32.shape[1]
    w = bits_i32.shape[2]
    gc = n_lists // lc
    kern = functools.partial(_bq_scan_kernel, lc=lc, bins=bins, dim=dim,
                             metric=metric)
    norms3 = norms2[:, None, :]
    scales3 = scales[:, None, :]
    ids3 = ids[:, None, :]
    cd, ci = pl.pallas_call(
        kern,
        grid=(gc,),
        in_specs=[pl.BlockSpec((lc, cap, dim), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, max_list, w), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0)),
                  pl.BlockSpec((lc, 1, max_list), lambda g: (g, 0, 0))],
        out_specs=[pl.BlockSpec((lc, bins, cap), lambda g: (g, 0, 0)),
                   pl.BlockSpec((lc, bins, cap), lambda g: (g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_lists, bins, cap),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((n_lists, bins, cap), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_lists * max_list * cap * dim,
            bytes_accessed=(4 * n_lists * max_list * w
                            + 4 * n_lists * cap * dim
                            + 8 * n_lists * bins * cap),
            transcendentals=0),
        interpret=interpret,
    )(qsub, bits_i32, norms3, scales3, ids3)
    return cd, ci


def ivf_bq_scan_pallas(q_rot, centers_rot, bits, norms2, scales,
                       lists_indices, probes, k: int, cap: int,
                       bins: int = 0, sqrt: bool = False,
                       gather: str = "", metric: str = "l2",
                       lc: int = 0, fused: bool = False):
    """Fused Pallas fine phase for ivf_bq: probe inversion + per-list
    query gather (rotated; center-offset for the l2 core) + the in-VMEM
    unpack scan + the shared candidate merge. Mirrors
    ``ivf_list_scan_pallas`` (incl. ``fused`` — the single-pallas_call
    scan+select tier, with the ip center term applied in-kernel);
    unfused ``metric`` "ip" scores negated similarities with the center
    term applied post-scan."""
    nq, dim = q_rot.shape
    n_lists, max_list = lists_indices.shape
    lay = _Layout(probes, n_lists, max_list, cap, bins, k)
    bits_i32 = jax.lax.bitcast_convert_type(bits, jnp.int32)
    bits_i32 = lay.pad_lists(bits_i32, max_list)
    norms2 = lay.pad_lists(norms2, max_list)
    scales = lay.pad_lists(scales, max_list)
    lists_indices = lay.pad_lists(lists_indices, max_list, fill=-1)
    from raft_tpu.neighbors._ivf_scan import gather_query_rows
    qg = gather_query_rows(q_rot, lay.padded_qmap(), mode=gather)
    qsub = qg if metric == "ip" else qg - centers_rot[:, None, :]
    if fused:
        lc = _pick_lc_fused(n_lists, lay.mlp, lay.capp, dim, 2, k, nq,
                            lay.bins, override=lc)
        od, oi = _fused_bq_scan_call(
            qsub, bits_i32, norms2, scales, lists_indices,
            lay.padded_qmap(), centers_rot, lay.bins, lc, dim, k, nq,
            pallas_interpret(), metric=metric)
        return _finish_fused(od, oi, nq, k, sqrt)
    # VMEM: the unpacked (ML, dim) bf16 tile + (ML, cap) scores dominate
    lc = _pick_lc(n_lists, lay.mlp, lay.capp, dim, 2, override=lc)
    cd, ci = _bq_scan_call(qsub, bits_i32, norms2, scales,
                           lists_indices, lay.bins, lc, dim,
                           pallas_interpret(), metric=metric)
    if metric == "ip":
        # kernel scored −s·⟨q, dec⟩; complete −q·x with the center term
        from raft_tpu.core.precision import matmul_precision
        corr = jnp.einsum("lqd,ld->lq", qsub, centers_rot,
                          precision=matmul_precision(),
                          preferred_element_type=jnp.float32)
        cd = cd.astype(jnp.float32) - corr[:, None, :]  # (L, bins, capp)
    return lay.merge(cd, ci, probes, k, sqrt)


def _pq_scan_kernel(ext_ref, qsub_ref, codes_ref, norms_ref, ids_ref,
                    books_ref, cd_ref, ci_ref, *, bins: int, chunk: int,
                    split: int, metric: str, pq_dim: int, pq_len: int,
                    n_codes: int, lut_dtype, per_cluster: bool):
    """One IVF sub-list per grid cell, scored straight from its u8 codes.

    Decode is ONE one-hot × codebook matmul on the MXU, lanes-major
    over list rows: the codes arrive pre-transposed (pq_dim, ML), one
    vectorized compare builds the stacked one-hot
    ``oh[(s, c), m] = (codes_t[s, m] == c)`` of shape (pq_dim·C, ML),
    and the BLOCK-DIAGONAL codebook matrix ``B`` (rot_dim, pq_dim·C) —
    built once outside the kernel, ``B[s·pl:(s+1)·pl, s·C:(s+1)·C] =
    books[s]ᵀ`` — decodes every subspace in a single K = pq_dim·C
    matmul: ``dec_t = B @ oh`` (rot_dim, ML). Each dec row still
    selects exactly ONE codeword entry (the off-block zeros contribute
    nothing), so values are bit-identical to a per-subspace gather; the
    formulation trades the old pq_dim-unrolled strip loop (a Mosaic
    program that GREW with pq_dim — the r3 compile-hazard class, and
    ~3% MXU utilization at M = pq_len) for one fully-utilized matmul.
    The decode tile lives and dies in VMEM (the reference's smem-LUT
    property, ivf_pq_search.cuh:593) and ONE K = rot_dim matmul scores
    all probing queries against it.

    Only the cell's stored rows are decoded: ``ext_ref`` (scalar-
    prefetched, one int32 per list) holds each list's extent, and the
    cell walks ``ceil(n / chunk)`` chunks of its ``n`` rows
    (``_cell_rows``) — padding past a list's last row and lists no
    query probes cost nothing. A cell with no row runs no chunk and
    writes the (+inf, −1) block the padded scan produced for it.

    PER_CLUSTER: the cell's single codebook (C, pl) is shared across
    subspaces, so block-diagonal stacking would need a per-list B.
    Instead the one-hot stacks on the LANE axis — ``oh2`` (C,
    pq_dim·ML) from the flattened codes — and ``bookᵀ @ oh2`` decodes
    all subspaces at once into (pl, pq_dim·ML) ≡ p-major rows
    (pl·pq_dim, ML); the probing queries arrive pre-permuted to the
    matching p-major column order (``_PER_CLUSTER_PERM``), so scoring
    needs no in-kernel transpose.
    """
    cd, ci = _pq_cell_candidates(
        qsub_ref[0], codes_ref, norms_ref, ids_ref, books_ref,
        _cell_rows(ext_ref, split, codes_ref.shape[2]), bins=bins,
        chunk=chunk, metric=metric, pq_dim=pq_dim, pq_len=pq_len,
        n_codes=n_codes, lut_dtype=lut_dtype, per_cluster=per_cluster)
    cd_ref[0] = cd.astype(cd_ref.dtype)
    ci_ref[0] = ci


def _pq_cell_candidates(q, codes_ref, norms_ref, ids_ref, books_ref, n, *,
                        bins: int, chunk: int, metric: str, pq_dim: int,
                        pq_len: int, n_codes: int, lut_dtype,
                        per_cluster: bool):
    """One PQ cell's binned candidates scored straight from its u8
    codes (the shared per-cell body — see ``_flat_list_candidates``;
    ``books_ref`` stays a ref because PER_CLUSTER reads a per-cell
    block while PER_SUBSPACE reads the shared decode matrix).

    Only the cell's first ``n`` rows are decoded, ``chunk`` rows (a
    multiple of ``bins``) at a time; each chunk's strided-bin minimum
    folds into a running ``(cap, bins)`` minimum with the single-pass
    tie rule (smallest id among equal distances), so the candidates are
    those of a scan over every row of the cell: rows past ``n`` hold id
    −1, scored +inf (``n`` 0 gives +inf, id −1 throughout). Returns
    ``(cd (cap, bins), ci (cap, bins))`` — slot-major, unlike the
    flat/bq helpers."""
    cap = q.shape[0]
    # bf16 LUT = single MXU pass (the reference's fp16-LUT speed tier);
    # f32 LUT = HIGHEST-precision passes (its fp32 accuracy tier);
    # fp8 LUT (float8_e4m3fn) = books arrive fp8-quantized — half the
    # codebook VMEM/HBM of bf16 (the reference's fp_8bit tier,
    # ivf_pq_search.cuh:780-1004) — and upcast to bf16 for the MXU
    f32_lut = jnp.dtype(lut_dtype) == jnp.dtype(jnp.float32)
    operand = jnp.float32 if f32_lut else jnp.bfloat16
    prec = jax.lax.Precision.HIGHEST if f32_lut else None
    rr = jnp.sum(q * q, axis=1)[:, None]                 # (cap, 1)
    q_op = q.astype(operand)
    w = chunk // bins

    def one_chunk(c, carry):
        run_d, run_i = carry
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        # codes arrive as i8 bitcast of the u8 store (1 B/code of HBM
        # traffic), pre-transposed; recover 0..255 with a mask
        codes_t = codes_ref[0, :, rows].astype(jnp.int32) & 0xFF
        if per_cluster:
            codes_flat = codes_t.reshape(1, pq_dim * chunk)
            iota = jax.lax.broadcasted_iota(
                jnp.int32, (n_codes, pq_dim * chunk), 0)
            oh2 = (iota == codes_flat).astype(operand)  # (C, pq_dim·ch)
            dec_pm = jax.lax.dot_general(
                books_ref[0].astype(operand), oh2,
                (((0,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)   # (pl, pq_dim·ch)
            dec_t = dec_pm.reshape(pq_len * pq_dim, chunk)  # p-major
        else:
            iota = jax.lax.broadcasted_iota(
                jnp.int32, (pq_dim, n_codes, chunk), 1)
            oh = (iota == codes_t[:, None, :]).astype(operand)
            oh2 = oh.reshape(pq_dim * n_codes, chunk)
            dec_t = jax.lax.dot_general(
                books_ref[...].astype(operand), oh2,
                (((1,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)   # (rot_dim, ch)

        ip = jax.lax.dot_general(
            q_op, dec_t.astype(operand), (((1,), (0,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32)       # (cap, ch)
        ids_b = jnp.broadcast_to(ids_ref[0, :, rows], (cap, chunk))
        if metric == "ip":
            d = jnp.where(ids_b >= 0, -ip, jnp.inf)
        else:
            d = rr + norms_ref[0, :, rows] - 2.0 * ip
            d = jnp.where(ids_b >= 0, jnp.maximum(d, 0.0), jnp.inf)

        # strided bins along the row axis (row r → bin r % B), row-major
        # reshape (cap, w, B): element [., i, b] = row i·B + b; chunks
        # start on bins multiples, so every row keeps its bin
        db_ = d.reshape(cap, w, bins)
        cd = jnp.min(db_, axis=1)                        # (cap, B)
        rb = ids_b.reshape(cap, w, bins)
        ci = jnp.min(jnp.where(db_ == cd[:, None, :], rb, _BIG_I32),
                     axis=1)
        best = jnp.minimum(run_d, cd)
        return best, jnp.minimum(jnp.where(run_d == best, run_i, _BIG_I32),
                                 jnp.where(cd == best, ci, _BIG_I32))

    init = (jnp.full((cap, bins), jnp.inf, jnp.float32),
            jnp.full((cap, bins), _BIG_I32, jnp.int32))
    cd, ci = jax.lax.fori_loop(0, pl.cdiv(n, chunk), one_chunk, init)
    return cd, jnp.where(ci == _BIG_I32, -1, ci)


def _pq_books_spec(books, pq_dim: int, rot_dim: int, split: int,
                   per_cluster: bool):
    """The codebook operand's block and its (n_codes, pq_len):
    PER_CLUSTER — each cell fetches its list's (C, pl) book;
    PER_SUBSPACE — one shared block-diagonal decode matrix."""
    if per_cluster:
        n_codes, pq_len = books.shape[1], books.shape[2]
        return (pl.BlockSpec((1, n_codes, pq_len),
                             lambda g, e: (g // split, 0, 0)),
                n_codes, pq_len)
    return (pl.BlockSpec((rot_dim, books.shape[1]), lambda g, e: (0, 0)),
            books.shape[1] // pq_dim, rot_dim // pq_dim)


@functools.partial(jax.jit, static_argnames=("bins", "chunk", "metric",
                                             "out_dtype", "lut_dtype",
                                             "interpret", "split",
                                             "per_cluster"))
def _pq_scan_call(ext, qsub, codes_t, norms, ids, books, bins: int,
                  chunk: int, interpret: bool, metric: str, lut_dtype,
                  out_dtype=jnp.float32, split: int = 1,
                  per_cluster: bool = False):
    """``ext`` (n_lists,) int32: each list's extent, scalar-prefetched
    (``_scan_extents``). ``split`` > 1: codes/norms/ids carry ``split``
    sub-lists per original list (leading dim n_lists·split); the query
    blocks stay per-ORIGINAL-list and are shared across a list's
    sub-cells via the index map — no duplicated HBM. ``codes_t``
    arrives pre-transposed (n_cells, pq_dim, sub_ml) u8. ``books``:
    PER_SUBSPACE — the block-diagonal decode matrix (rot_dim,
    pq_dim·C), one shared block fetched once; PER_CLUSTER — (n_lists,
    C, pl), each cell fetches its own list's codebook (and ``qsub``
    arrives p-major permuted, see ``_pq_scan_kernel``)."""
    n_lists, cap, rot_dim = qsub.shape
    n_cells, pq_dim, max_list = codes_t.shape
    books_spec, n_codes, pq_len = _pq_books_spec(books, pq_dim, rot_dim,
                                                 split, per_cluster)
    kern = functools.partial(
        _pq_scan_kernel, bins=bins, chunk=chunk, split=split,
        metric=metric, pq_dim=pq_dim, pq_len=pq_len, n_codes=n_codes,
        lut_dtype=jnp.dtype(lut_dtype), per_cluster=per_cluster)
    # norms/ids carry a singleton middle axis (see _list_scan_call): the
    # 2-D (1, max_list) block put 1 in a Mosaic-constrained slot and
    # failed to lower on real TPU (r3 measurement)
    norms3 = norms[:, None, :]
    ids3 = ids[:, None, :]
    cd, ci = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_cells,),
            in_specs=[pl.BlockSpec((1, cap, rot_dim),
                                   lambda g, e: (g // split, 0, 0)),
                      pl.BlockSpec((1, pq_dim, max_list),
                                   lambda g, e: (g, 0, 0)),
                      pl.BlockSpec((1, 1, max_list), lambda g, e: (g, 0, 0)),
                      pl.BlockSpec((1, 1, max_list), lambda g, e: (g, 0, 0)),
                      books_spec],
            out_specs=[pl.BlockSpec((1, cap, bins), lambda g, e: (g, 0, 0)),
                       pl.BlockSpec((1, cap, bins),
                                    lambda g, e: (g, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((n_cells, cap, bins), out_dtype),
                   jax.ShapeDtypeStruct((n_cells, cap, bins), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            # dec = B @ oh (K = pq_dim·C dense — the one-hot formulation
            # pays C× the gather FLOPs to stay on the MXU) + the score
            flops=2 * n_cells * max_list * rot_dim * pq_dim * n_codes
            + 2 * n_cells * max_list * cap * rot_dim,
            bytes_accessed=(n_cells * max_list * pq_dim
                            + 4 * n_lists * cap * rot_dim
                            + 8 * n_cells * cap * bins),
            transcendentals=0),
        interpret=interpret,
    )(ext, qsub, jax.lax.bitcast_convert_type(codes_t, jnp.int8), norms3,
      ids3, books)
    return cd, ci


# rows a code-scan cell decodes per step: a multiple of bins dividing
# the cell's sub-list, at most this many (one bins window when bins
# are wider) — the granularity at which a list's tail is decoded. On a
# v5e at the 2M-row IVF-PQ point (sub-lists of 1024, mean list 977 of
# 3600) 256 beats 512 by 0.4%; 512 wins 2.8% when every sub-list is full
_PQ_CHUNK_ROWS = 256


def _pq_bins(bins: int, k: int, max_list: int) -> int:
    """The code scan's resolved bins: it bins along the lane axis
    ((cap, ML) -> (cap, w, bins)), and Mosaic refuses a reshape that
    splits a 128-lane tile, so bins are whole lane tiles (more bins
    keep a superset of candidates)."""
    bins = _Layout.resolve_bins(bins, k, max_list)
    return _round_up(max_list if bins < 0 else bins, 128)


def _pq_cells(max_list: int, bins: int, cap: int, pq_dim: int,
              n_codes: int, rot_dim: int, lut_dtype):
    """``(split, sub_ml, chunk)``: the code scan's grid over a list of
    ``max_list`` rows padded to a ``bins`` multiple. VMEM bound: per
    grid cell the stacked one-hot (pq_dim·C, sub_ml), decode tile
    (rot_dim, sub_ml) and score block (cap, sub_ml) all scale with the
    list length — oversized lists split into ``split`` sub-lists of
    ``sub_ml`` rows (extra grid cells sharing the list's probing
    queries) so skewed or low-n_lists indexes still compile. A cell
    decodes ``chunk`` rows at a time (``_PQ_CHUNK_ROWS``)."""
    from raft_tpu.neighbors._ivf_scan import largest_divisor_at_most
    op_item = 4 if jnp.dtype(lut_dtype) == jnp.dtype(jnp.float32) else 2
    capp = _round_up(max(cap, 8), 8)
    per_row = (pq_dim * n_codes * op_item + rot_dim * 4 + capp * 4
               + pq_dim * 4)
    row_budget = max(bins, (_VMEM_LIMIT // 3) // per_row)
    mlp = _round_up(max_list, bins)
    split = -(-mlp // _round_up(row_budget, bins))
    sub_ml = _round_up(-(-mlp // split), bins)
    chunk = bins * largest_divisor_at_most(
        sub_ml // bins, max(1, _PQ_CHUNK_ROWS // bins))
    return split, sub_ml, chunk


def _list_extents(lists_indices):
    """(n_lists,) int32: the position after each list's last row with an
    id ≥ 0 — every row past it is padding. Derived from the ids, so it
    holds for any layout a caller hands in (holes, a fold, a prefix)."""
    ml = lists_indices.shape[1]
    pos = jnp.arange(1, ml + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(lists_indices >= 0, pos[None, :], 0), axis=1)


def _scan_extents(lists_indices, qmap):
    """The extents the code scan decodes: a list no query probes (its
    ``qmap`` row all −1) has none."""
    return jnp.where(jnp.any(qmap >= 0, axis=1),
                     _list_extents(lists_indices), 0)


def pq_decoded_share(lists_indices, k: int, cap: int, bins: int,
                     pq_dim: int, n_codes: int, rot_dim: int,
                     lut_dtype) -> float:
    """Share of the code scan's stored rows (``n_cells · sub_ml``) it
    decodes when every list is probed: each list's extent rounded up to
    the scan's chunk. One host read of the (n_lists,) extents."""
    import numpy as np
    n_lists, max_list = lists_indices.shape
    bins = _pq_bins(bins, k, max_list)
    split, sub_ml, chunk = _pq_cells(max_list, bins, cap, pq_dim, n_codes,
                                     rot_dim, lut_dtype)
    ext = np.asarray(_list_extents(lists_indices)).astype(np.int64)
    return float((-(-ext // chunk) * chunk).sum()) / (n_lists * split
                                                      * sub_ml)


def ivf_pq_code_scan_pallas(q_rot, centers_rot, pq_centers, codes,
                            code_norms, lists_indices, probes, k: int,
                            cap: int, bins: int = 0, sqrt: bool = False,
                            lut_dtype=jnp.bfloat16,
                            internal_distance_dtype=jnp.float32,
                            metric: str = "l2",
                            per_cluster: bool = False,
                            gather: str = "", fused: bool = False):
    """IVF-PQ fine scan directly over the compressed codes.

    Reference ``ivf_pq_search.cuh:593`` scans the bit-packed
    ``pq_dataset`` against a smem LUT. Per-lane LUT gathers are hostile
    to the TPU (XLA lowers them to the scalar core), so the TPU
    formulation decodes **inside the kernel** with one-hot × codebook
    MXU matmuls (``_pq_scan_kernel``): the u8 codes (pq_dim B/vector)
    are the only persistent payload; the (rot_dim, max_list) decode tile
    lives and dies in VMEM — the "on-the-fly decode tile that never
    persists". For L2 each list's probing queries are pre-offset by its
    rotated center so the kernel scores ``||(q_rot − c_l) − decoded||²``;
    IP adds the center term to the decode tile instead.

    The reference's LUT-precision variants (``ivf_pq_search.cuh:
    780-1004``, fp32/fp16/fp8 LUT × fp32/fp16 internal) map to
    ``lut_dtype`` — the decode/score operand dtype (bf16 = one MXU pass,
    f32 = highest-precision passes) — and ``internal_distance_dtype`` —
    the candidate score dtype carried to the merge (bf16 halves
    candidate HBM).

    ``code_norms`` are exact: PQ subspaces concatenate orthogonally, so
    ``||decoded_i||² = Σ_s ||book_s[c_is]||²`` is computed once at build
    from the codebook norm table.

    The lists arrive padded to the largest; each list's extent (the
    position after its last row with an id ≥ 0, 0 when no query probes
    it) rides to the kernel as a scalar, and a grid cell decodes only
    the chunks below it — the candidates are those of a scan over every
    row, since rows past the extent hold id −1.
    """
    nq = q_rot.shape[0]
    n_lists, max_list, pq_dim = codes.shape
    _, n_codes, pq_len = pq_centers.shape
    bins = _pq_bins(bins, k, max_list)
    lay = _Layout(probes, n_lists, max_list, cap, bins, k)
    # each list's extent, from its ids (0 for a list no query probes):
    # the kernel decodes only the rows below it
    ext = _scan_extents(lists_indices, lay.qmap)
    codes = lay.pad_lists(codes, max_list)
    code_norms = lay.pad_lists(code_norms, max_list)
    lists_indices = lay.pad_lists(lists_indices, max_list, fill=-1)
    from raft_tpu.neighbors._ivf_scan import gather_query_rows
    qg = gather_query_rows(q_rot, lay.padded_qmap(), mode=gather)
    if metric == "ip":
        # IP decomposes linearly: q·(c_l + dec) = q·c_l + q·dec. The
        # kernel scores plain rotated queries against decoded residuals
        # (-q·dec); the per-(list, query) center term is a rank-1
        # correction applied to the candidate blocks after the scan.
        qsub = qg
    else:
        # per-list probing queries, residual form: (n_lists, cap, rot_dim)
        qsub = qg - centers_rot[:, None, :]

    rot_dim = pq_dim * pq_len
    fp8 = jnp.dtype(lut_dtype) == jnp.dtype(jnp.float8_e4m3fn)
    f32_lut = jnp.dtype(lut_dtype) == jnp.dtype(jnp.float32)
    operand = jnp.float32 if f32_lut else jnp.bfloat16
    if per_cluster:
        # per-list books ride full precision except the fp8 tier
        # (storage quantization; compute upcasts to bf16 in-kernel)
        books_in = (pq_centers.astype(jnp.float8_e4m3fn) if fp8
                    else pq_centers)
    else:
        # PER_SUBSPACE: build the block-diagonal decode matrix ONCE —
        # B[s·pl:(s+1)·pl, s·C:(s+1)·C] = books[s]ᵀ. Every dec row
        # still selects exactly one codeword (off-block zeros), so the
        # kernel's single K = pq_dim·C matmul is value-identical to
        # per-subspace strips; stored in the compute operand dtype
        # (fp8 for the fp8 tier — half the block's VMEM/HBM)
        B = jnp.zeros((rot_dim, pq_dim * n_codes), jnp.float32)
        for s in range(pq_dim):
            B = jax.lax.dynamic_update_slice(
                B, pq_centers[s].T, (s * pq_len, s * n_codes))
        # fp8 tier: codebook STORAGE quantizes (callers pass code_norms
        # computed over the fp8 books — ivf_pq.search caches that
        # table — so the L2 epilogue stays self-consistent)
        books_in = B.astype(jnp.float8_e4m3fn if fp8 else operand)

    split, sub_ml, chunk = _pq_cells(max_list, lay.bins, cap, pq_dim,
                                     n_codes, rot_dim, lut_dtype)
    mlp2 = sub_ml * split
    if mlp2 != lay.mlp:
        pad = [(0, 0), (0, mlp2 - lay.mlp)]
        codes = jnp.pad(codes, pad + [(0, 0)])
        code_norms = jnp.pad(code_norms, pad)
        lists_indices = jnp.pad(lists_indices, pad, constant_values=-1)

    def as_sub(a):
        return a.reshape(n_lists * split, sub_ml, *a.shape[2:])

    if per_cluster:
        # p-major column permutation matching the kernel's PER_CLUSTER
        # decode-row order (see _pq_scan_kernel): column p·pq_dim + s
        # reads the query's s·pl + p coordinate. Applied AFTER the ip
        # correction below is computed from the unpermuted blocks.
        perm = (jnp.arange(rot_dim) % pq_dim) * pq_len \
            + jnp.arange(rot_dim) // pq_dim
        qsub_k = qsub[..., perm]
    else:
        qsub_k = qsub

    codes_t = jnp.swapaxes(as_sub(codes), 1, 2)   # (cells, pq_dim, sub_ml)
    if fused:
        # the single-pallas_call tier replaces merge_cap_major's tail
        # outright: candidates merge into the resident state in-kernel,
        # the split sub-cells sharing their list's qmap/query blocks;
        # the ip center correction moves in-kernel too (constant per
        # slot — commutes with the binned min). PER_CLUSTER permutes
        # the centers like the queries so the in-kernel dot is the
        # same q·c_l (permutation-invariant).
        cent_k = (centers_rot[..., perm]
                  if (per_cluster and metric == "ip") else centers_rot)
        od, oi = _fused_pq_scan_call(
            ext, qsub_k, codes_t, as_sub(code_norms),
            as_sub(lists_indices), books_in, lay.padded_qmap(), cent_k,
            lay.bins, chunk, k, nq, pallas_interpret(), metric=metric,
            lut_dtype=lut_dtype, split=split, per_cluster=per_cluster)
        return _finish_fused(od, oi, nq, k, sqrt)
    cd, ci = _pq_scan_call(ext, qsub_k, codes_t, as_sub(code_norms),
                           as_sub(lists_indices), books_in, lay.bins,
                           chunk, pallas_interpret(), metric=metric,
                           lut_dtype=lut_dtype,
                           out_dtype=internal_distance_dtype, split=split,
                           per_cluster=per_cluster)
    if split > 1:
        # sub-lists of a list are contiguous: fold them back into a
        # wider candidate block per original list
        cd = cd.reshape(n_lists, split, lay.capp, lay.bins) \
               .transpose(0, 2, 1, 3).reshape(n_lists, lay.capp, -1)
        ci = ci.reshape(n_lists, split, lay.capp, lay.bins) \
               .transpose(0, 2, 1, 3).reshape(n_lists, lay.capp, -1)
    if metric == "ip":
        # kernel scored -q·dec; the true negated similarity is
        # -(q·dec + q·c_l): shift each (list, query) candidate row
        from raft_tpu.core.precision import matmul_precision
        corr = jnp.einsum("lqd,ld->lq", qsub, centers_rot,
                          precision=matmul_precision(),
                          preferred_element_type=jnp.float32)
        cd = cd.astype(jnp.float32) - corr[:, :, None]
    return lay.merge_cap_major(cd, ci, probes, k, sqrt)
