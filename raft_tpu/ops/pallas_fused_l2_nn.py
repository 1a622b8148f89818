"""Pallas fused L2 nearest-neighbor (argmin epilogue) kernel.

Reference: ``raft::distance::fusedL2NN`` — CUDA kernel
``distance/detail/fused_l2_nn.cuh:132`` fuses the expanded-L2 GEMM tiles
with a per-row argmin reduction (custom KVP atomics + a mutex buffer) so
the (m, n) distance matrix never reaches global memory.

TPU design: one MXU matmul per (query-tile, db-tile) grid cell with the
argmin epilogue applied in VMEM before anything is written back; the
running (best-dist, best-idx) state lives in the output block, which
Pallas keeps resident in VMEM while the inner (db) grid dimension
iterates. The block is computed *transposed* — rows are database points,
columns are queries — so the reduction runs along the sublane axis and
the per-query results are natural ``(1, TM)`` row vectors (no in-kernel
transpose). No atomics are needed: the TPU grid is sequential, the CUDA
kernel's inter-CTA mutex disappears.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.dispatch import pallas_interpret
from raft_tpu.ops._util import (BIG_I32 as _BIG_I32, VMEM_LIMIT as _VMEM_LIMIT,
                                round_up as _round_up, dot_nt_f32)
from raft_tpu.core.precision import resolve_kernel_mode


def _nn_kernel(x_ref, y_ref, od_ref, oi_ref, *, n: int, tn: int, gn: int,
               sqrt: bool, precision):
    j = pl.program_id(1)
    x = x_ref[:]                                         # (TM, K)
    y = y_ref[:]                                         # (TN, K)
    xx = jnp.sum(x * x, axis=1, keepdims=True).T         # (1, TM)
    yy = jnp.sum(y * y, axis=1, keepdims=True)           # (TN, 1)
    # transposed expanded-L2 block: d[p, q] = ||y_p - x_q||^2
    d = yy + xx - 2.0 * dot_nt_f32(y, x, precision)
    tm = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (tn, tm), 0) + j * tn
    d = jnp.where(row < n, jnp.maximum(d, 0.0), jnp.inf)
    tmin = jnp.min(d, axis=0, keepdims=True)             # (1, TM)
    arg = jnp.min(jnp.where(d == tmin, row, _BIG_I32), axis=0, keepdims=True)

    @pl.when(j == 0)
    def _():
        od_ref[:] = jnp.full(od_ref.shape, jnp.inf, jnp.float32)
        oi_ref[:] = jnp.zeros(oi_ref.shape, jnp.int32)

    take = tmin[None] < od_ref[:]
    oi_ref[:] = jnp.where(take, arg[None], oi_ref[:])
    od_ref[:] = jnp.where(take, tmin[None], od_ref[:])

    if sqrt:
        @pl.when(j == gn - 1)
        def _():
            od_ref[:] = jnp.sqrt(od_ref[:])


@functools.partial(jax.jit,
                   static_argnames=("sqrt", "tm", "tn", "interpret",
                                    "kernel_precision"))
def _fused_l2_nn_call(x, y, sqrt: bool, tm: int, tn: int, interpret: bool,
                      kernel_precision=None):
    m, k = x.shape
    n = y.shape[0]
    mp, np_ = _round_up(m, tm), _round_up(n, tn)
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, 0)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, np_ - n), (0, 0)))
    gm, gn = mp // tm, np_ // tn
    kern = functools.partial(_nn_kernel, n=n, tn=tn, gn=gn, sqrt=sqrt,
                             precision=resolve_kernel_mode(
                                 kernel_precision, interpret))
    # inside shard_map (the sharded balanced k-means: rows sharded,
    # centers replicated) both operands and the outputs carry the union
    # of the inputs' varying mesh axes
    vma = jax.typeof(xp).vma | jax.typeof(yp).vma
    xp, yp = (jax.lax.pcast(a, tuple(vma - jax.typeof(a).vma),
                            to="varying")
              if vma - jax.typeof(a).vma else a for a in (xp, yp))
    od, oi = pl.pallas_call(
        kern,
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((tn, k), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((1, 1, tm), lambda i, j: (i, 0, 0)),
                   pl.BlockSpec((1, 1, tm), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((gm, 1, tm), jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((gm, 1, tm), jnp.int32, vma=vma)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_ * k,
            bytes_accessed=4 * (gm * np_ * k + gn * mp * k + 2 * mp),
            transcendentals=0),
        interpret=interpret,
    )(xp, yp)
    return oi.reshape(-1)[:m], od.reshape(-1)[:m]


def fused_l2_nn_pallas(x, y, sqrt: bool = False, tm: int = 0, tn: int = 0,
                       kernel_precision: str | None = None):
    """For each row of ``x``: (index, distance) of its nearest row of ``y``
    under (squared) L2 — single fused kernel, no (m, n) buffer.

    Returns ``(idx int32 (m,), dist float32 (m,))``. Tile sizes ``tm``
    (queries, lane axis) and ``tn`` (db, sublane axis) default to a
    VMEM-budget heuristic (1024² for small k; shrunk as the feature dim
    grows — the VMEM-capacity analogue of the reference's smem policy
    selection, ``pairwise_distance_base.cuh:76``) and are clamped to the
    padded problem; padded db rows are masked to +inf.
    """
    m, k = x.shape
    if tm <= 0 or tn <= 0:
        if k <= 512:
            tm, tn = 1024, 4096
        elif k <= 2048:
            tm, tn = 512, 1024
        else:
            tm, tn = 256, 512
    tm = min(tm, _round_up(m, 8))
    tn = min(tn, _round_up(y.shape[0], 8))
    return _fused_l2_nn_call(x, y, bool(sqrt), tm, tn, pallas_interpret(),
                             kernel_precision=kernel_precision)
