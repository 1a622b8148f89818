"""Fused L2 nearest-neighbor (argmin epilogue).

Reference: ``raft::distance::fusedL2NN`` / ``fusedL2NNMinReduce``
(``cpp/include/raft/distance/fused_l2_nn.cuh:89,192``; kernel
``distance/detail/fused_l2_nn.cuh:132``) — computes, for each row of ``x``,
the index and distance of its nearest row of ``y`` without materializing
the full (m, n) distance matrix. The CUDA version fuses an argmin epilogue
with custom atomics into the pairwise-distance tile loop; on TPU the same
fusion is expressed as a scan over column-tiles of ``y`` carrying a running
(min-distance, argmin) pair, which XLA keeps entirely in registers/VMEM —
no (m, n) buffer is ever allocated. A Pallas kernel backs the hot path for
large shapes (see raft_tpu/ops/pallas_fused_l2_nn.py); this module is the
reference XLA formulation and the public API.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.kvp import KeyValuePair
from raft_tpu.core.mdarray import as_array
from raft_tpu.core.precision import matmul_precision

# column-tile budget: tile_n such that m * tile_n stays bounded
_TILE_ELEMS = 1 << 22  # 16 MiB f32 block


def _f32(a):
    return a.astype(jnp.float32) if a.dtype != jnp.float32 else a


@functools.partial(jax.jit, static_argnames=("sqrt",))
def _fused_l2_nn(x, y, sqrt: bool):
    m, k = x.shape
    n = y.shape[0]
    tile_n = max(1, min(n, _TILE_ELEMS // max(1, m)))
    if tile_n >= 128:
        tile_n -= tile_n % 128
    pad = (-n) % tile_n
    yf = _f32(y)
    if pad:
        # padded rows get +inf distance so they never win the argmin
        yf = jnp.pad(yf, ((0, pad), (0, 0)))
    n_tiles = (n + pad) // tile_n
    xf = _f32(x)
    xx = jnp.sum(xf * xf, axis=1)  # (m,)

    y_tiles = yf.reshape(n_tiles, tile_n, k)
    yy_tiles = jnp.sum(y_tiles * y_tiles, axis=2)  # (n_tiles, tile_n)
    base = jnp.arange(n_tiles, dtype=jnp.int32) * tile_n

    def step(carry, inp):
        best_d, best_i = carry
        yt, yyt, off = inp
        # (m, tile_n) block of expanded L2
        d = xx[:, None] + yyt[None, :] - 2.0 * lax.dot_general(
            xf, yt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=matmul_precision())
        d = jnp.maximum(d, 0.0)
        col = jnp.arange(tile_n, dtype=jnp.int32)[None, :] + off
        valid = col < n
        d = jnp.where(valid, d, jnp.inf)
        tile_min = jnp.min(d, axis=1)
        tile_arg = off + jnp.argmin(d, axis=1).astype(jnp.int32)
        take = tile_min < best_d
        best_i = jnp.where(take, tile_arg, best_i)
        best_d = jnp.where(take, tile_min, best_d)
        return (best_d, best_i), None

    # *_like(xx) carries x's varying mesh axes, so the carry types match
    # when this runs inside shard_map (the sharded balanced k-means)
    init = (jnp.full_like(xx, jnp.inf),
            jnp.zeros_like(xx, dtype=jnp.int32))
    (best_d, best_i), _ = lax.scan(step, init, (y_tiles, yy_tiles, base))
    if sqrt:
        best_d = jnp.sqrt(best_d)
    return best_i, best_d


def fused_l2_nn(x, y, sqrt: bool = False,
                kernel_precision: str | None = None,
                res=None) -> KeyValuePair:
    """For each row of ``x``, the (index, distance) of the nearest row of
    ``y`` under (squared) L2. Returns a :class:`KeyValuePair` of arrays
    ``(key: int32 (m,), value: float32 (m,))`` — the structural analogue of
    the reference's ``KeyValuePair<IdxT, DataT>`` output
    (``fused_l2_nn.cuh:89``). Routes to the Pallas kernel
    (:mod:`raft_tpu.ops.pallas_fused_l2_nn`) on TPU backends.
    ``kernel_precision`` (Pallas path): ``None`` = env default (bf16x3)
    | ``"bf16"`` (one MXU pass, ~5e-4 — the EM-training speed tier) |
    ``"bf16x3"`` | ``"highest"``."""
    x, y = as_array(x), as_array(y)
    expects(x.ndim == 2 and y.ndim == 2, "fused_l2_nn: inputs must be rank-2")
    expects(x.shape[1] == y.shape[1], "fused_l2_nn: dim mismatch")
    from raft_tpu.ops.dispatch import pallas_enabled
    if (pallas_enabled() and x.shape[1] <= 4096
            and x.shape[0] > 0 and y.shape[0] > 0):
        from raft_tpu.ops.pallas_fused_l2_nn import fused_l2_nn_pallas
        idx, d = fused_l2_nn_pallas(x, y, sqrt=bool(sqrt),
                                    kernel_precision=kernel_precision)
    else:
        idx, d = _fused_l2_nn(x, y, bool(sqrt))
    return KeyValuePair(idx, d)


def fused_l2_nn_argmin(x, y, sqrt: bool = True, res=None) -> jax.Array:
    """Index-only form, mirroring ``pylibraft.distance.fused_l2_nn_argmin``
    (reference ``python/pylibraft/pylibraft/distance/fused_l2_nn.pyx``)."""
    return fused_l2_nn(x, y, sqrt=sqrt, res=res).key
