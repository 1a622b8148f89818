"""Pallas exact k-selection kernel (the warpsort role).

Reference: ``spatial/knn/detail/topk.cuh:65-83`` dispatches k≤256 to
warp-sort (``topk/warpsort_topk.cuh:99-366``: per-warp sorted queues
merged through registers) and larger k to multi-pass radix
(``topk/radix_topk.cuh``). Neither maps to TPU (no warp shuffles); XLA's
``lax.top_k`` is a full variadic sort (28 ms for 1000×4096 on v5e in
a pre-PR-21 measurement), orders of magnitude off a merge-pass budget.

TPU design — same transposed geometry as the fused kNN kernel
(``pallas_fused_knn.py``): candidates live on sublanes, rows (queries)
on lanes, so cross-candidate reductions are sublane reductions.

  1. The input (m, n) is transposed once by XLA to (n, m) and tiled
     (TN, TM); the kernel keeps a running sorted (k, TM) state resident
     in the output block across the candidate-tile grid dimension.
  2. Per tile, a *filtered* merge (warp_sort_filtered's trick,
     ``warpsort_topk.cuh:136``): if no tile value beats any lane's
     current k-th best, the tile is skipped after one vectorized
     compare.
  3. Merging is EXACT: k rounds of (min, argmin-by-row, invalidate)
     over the concatenated [state; tile] block — O(k·TN/8) sublane
     vector ops per merging tile, ~0.4 ms for 1000×4096 k=32 vs 28 ms
     for the XLA sort. No binning: unlike the recall-gated fused-kNN
     candidate pass, ``select_k`` is a parity primitive and must return
     exactly the k best.

k > 256 falls back to ``lax.top_k`` (the radix side of the reference
dispatch) in ``neighbors/selection.py``.
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
                                round_up as _round_up)


def _select_kernel(v_ref, od_ref, oi_ref, *, tn: int, k: int):
    # pad candidates arrive as +inf (padded before the transpose), so no
    # in-kernel mask is needed: an inf candidate ties the inf init state
    # and loses to its lower concat row (the state's -1 sentinel)
    j = pl.program_id(1)
    d = v_ref[:]                                         # (TN, TM)
    tm = d.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (tn, tm), 0) + j * tn

    @pl.when(j == 0)
    def _():
        od_ref[:] = jnp.full(od_ref.shape, jnp.inf, jnp.float32)
        oi_ref[:] = jnp.full(oi_ref.shape, -1, jnp.int32)

    kth = od_ref[0, k - 1:k, :]                          # (1, TM)
    improves = jnp.any(d < kth)

    @pl.when(improves)
    def _():
        c_d = jnp.concatenate([od_ref[0], d], axis=0)    # (k+TN, TM)
        c_i = jnp.concatenate([oi_ref[0], row], axis=0)
        ri = jax.lax.broadcasted_iota(jnp.int32, (k + tn, tm), 0)

        def round_(r, carry):
            cd, ci = carry
            m_ = jnp.min(cd, axis=0, keepdims=True)      # (1, TM)
            first = jnp.min(jnp.where(cd == m_, ri, _BIG_I32), axis=0,
                            keepdims=True)
            sel = ri == first                            # one-hot per lane
            idx = jnp.sum(jnp.where(sel, ci, 0), axis=0, keepdims=True)
            od_ref[0, pl.dslice(r, 1), :] = m_
            oi_ref[0, pl.dslice(r, 1), :] = idx
            return jnp.where(sel, jnp.inf, cd), ci

        jax.lax.fori_loop(0, k, round_, (c_d, c_i), unroll=False)


@functools.partial(jax.jit, static_argnames=("k", "tm", "tn", "interpret"))
def _select_k_call(v, k: int, tm: int, tn: int, interpret: bool):
    m, n = v.shape
    mp, np_ = _round_up(m, tm), _round_up(n, tn)
    # one XLA transpose: candidates onto sublanes, rows onto lanes
    vt = jnp.pad(v.astype(jnp.float32).T, ((0, np_ - n), (0, mp - m)),
                 constant_values=jnp.inf)
    gm, gn = mp // tm, np_ // tn
    kern = functools.partial(_select_kernel, tn=tn, k=k)
    od, oi = pl.pallas_call(
        kern,
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((tn, tm), lambda i, j: (j, i))],
        out_specs=[pl.BlockSpec((1, k, tm), lambda i, j: (i, 0, 0)),
                   pl.BlockSpec((1, k, tm), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((gm, k, tm), jnp.float32),
                   jax.ShapeDtypeStruct((gm, k, tm), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_,
            bytes_accessed=4 * (mp * np_ + 2 * mp * k),
            transcendentals=0),
        interpret=interpret,
    )(vt)
    od = jnp.moveaxis(od, 1, 2).reshape(gm * tm, k)[:m]
    oi = jnp.moveaxis(oi, 1, 2).reshape(gm * tm, k)[:m]
    return od, oi


def select_k_pallas(values, k: int, select_min: bool = True,
                    tm: int = 0, tn: int = 0):
    """Exact per-row top-k (smallest when ``select_min``) of a dense
    (m, n) matrix → ``(vals (m, k) f32 sorted best-first, idx (m, k)
    int32)``. Values are exact; tie-breaking between equal values is
    deterministic (lowest index within a merge; a tile whose best only
    *ties* the running k-th is skipped, so cross-tile ties keep the
    earlier tile's index). Rows with fewer than k finite candidates get
    ``-1`` ids and ``+inf`` values in the unfilled slots."""
    m, n = values.shape
    if not 1 <= k <= n:
        raise ValueError(f"select_k_pallas: k={k} outside [1, n={n}]")
    if tm <= 0 or tn <= 0:
        # (TN, TM) f32 tile; TN deep enough to amortize the k-round
        # merge, TM wide enough to fill lanes across the grid row
        tm = 256 if m >= 256 else max(128, _round_up(m, 8))
        tn = 2048 if n >= 2048 else _round_up(n, 8)
    tm = min(tm, _round_up(m, 8))
    tn = min(tn, _round_up(n, 8))
    v = values if select_min else -values
    d, i = _select_k_call(v, int(k), tm, tn, pallas_interpret())
    if not select_min:
        d = -d
    return d, i
