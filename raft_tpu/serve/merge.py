"""Quantized cross-shard merge codec (ISSUE 8).

The cross-shard top-k merge is the one wire payload distributed serving
moves per batch: every shard's ``(nq, k)`` (distance, id) candidates.
The f32 path (``parallel.ivf._global_merge``) allgathers both tensors
at full precision — 8 bytes per candidate received ``n_shards - 1``
times per rank. EQuARX (arxiv 2506.17615) shows XLA collectives
tolerate blockwise int8 wire formats at negligible quality loss; merge
traffic tolerates it even better than gradients do, because distances
only RANK candidates — exact re-rank (where the raw corpus is
resident) or the 0.005 recall budget absorbs the rounding.

The compressed merge here restructures the collective AND shrinks the
payload (both EQuARX moves):

* **two stages instead of one allgather** — stage A ``all_to_all``s
  each query block's candidates to one owner rank, which dequantizes
  and ``top_k``-merges its ``nq / n_shards`` slice; stage B allgathers
  the merged (re-quantized) slices so every rank holds the full result.
  Per-rank received bytes drop from ``(n-1)·nq·k`` candidates to
  ``2·(n-1)·nq·k / n`` — the 1/n factor does most of the compression.
* **int8 blockwise-scaled distances** — per-query max-abs scale (the
  block = one query's k candidates), distances on the wire as int8.
* **packed int32 words** — when ids fit 24 bits (``size`` <
  ``PACK_ID_SENTINEL``), each (distance, id) pair rides as ONE uint32
  word: biased dist byte high, 24-bit id low. Bigger corpora fall back
  to the split layout (int8 dists + int32 ids), still compressed.

Net wire ratio vs f32 ≈ ``1.03/n`` packed (``1.29/n`` split): 0.13 at
8 shards, measured by ``bench_serve_sharded`` as ``merge_bytes_ratio``
and counted under ``raft.serve.dist.merge.bytes_{pre,post}``.

Everything in this module except :func:`merge_mode` and
:func:`merge_wire_bytes` runs INSIDE ``shard_map`` (device code, no
obs calls — counters are emitted host-side by ``serve/dist.py`` from
the analytic byte accounting).
"""

from __future__ import annotations

import os
from typing import Tuple

import jax.numpy as jnp
from jax import lax

__all__ = [
    "PACK_ID_SENTINEL",
    "compressed_merge",
    "dequantize_rows",
    "merge_mode",
    "merge_wire_bytes",
    "pack_pairs",
    "quantize_rows",
    "unpack_pairs",
]

_QMAX = 127.0
# 24-bit id space; the all-ones pattern is the invalid-slot sentinel
# (id -1), so packed layout requires ids < PACK_ID_SENTINEL
PACK_ID_SENTINEL = (1 << 24) - 1


def merge_mode(default: str = "int8") -> str:
    """Resolve the cross-shard merge wire format from
    ``RAFT_TPU_DIST_MERGE`` (``f32`` | ``int8``), host-side and OUTSIDE
    jit (the ``lc_mode`` pattern). ``default`` differs by caller:
    the serving tier (``serve/dist.py``) compresses by default; the
    library functions (``distributed_ivf_*_search``) default to the
    exact f32 merge so their bit-exactness contracts (dryrun
    exhaustive-probe == exact) hold unless an operator opts in."""
    v = os.environ.get("RAFT_TPU_DIST_MERGE", "").strip().lower()
    if v in ("f32", "int8"):
        return v
    return default


def quantize_rows(d, i) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Blockwise AFFINE int8 quantization, one block per query row:
    ``(nq, k) f32 -> (nq, k) int8 + (nq,) f32 scale + (nq,) f32 zero``.

    Affine (scale + zero-point), not max-abs symmetric: merge distances
    are concentrated far from zero (a query's cross-shard top-k spans a
    narrow band of the distance axis), so spending the 8 bits on
    ``[row_min, row_max]`` instead of ``[-|max|, |max|]`` cuts the
    rounding step to ``range/254`` — measured ~3× better recall at the
    rank-k boundary. Invalid slots (``i < 0`` — their distance is the
    +inf pad) are excluded from the range and quantize to the max code;
    :func:`dequantize_rows` restores their +inf from the id mask, so an
    all-invalid row round-trips."""
    valid = i >= 0
    hi = jnp.max(jnp.where(valid, d, -jnp.inf), axis=1)
    lo = jnp.min(jnp.where(valid, d, jnp.inf), axis=1)
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    scale = jnp.where(hi > lo, (hi - lo) / (2.0 * _QMAX),
                      1.0).astype(jnp.float32)
    zero = lo.astype(jnp.float32)
    q = jnp.clip(jnp.round((d - zero[:, None]) / scale[:, None]) - _QMAX,
                 -_QMAX, _QMAX)
    q = jnp.where(valid, q, _QMAX).astype(jnp.int8)
    return q, scale, zero


def dequantize_rows(q, scale, zero, i):
    """Inverse of :func:`quantize_rows` (``scale``/``zero``
    broadcastable to ``q``): int8 codes back to f32 distances, invalid
    ids back to the +inf pad the merge sort expects."""
    d = (q.astype(jnp.float32) + _QMAX) * scale + zero
    return jnp.where(i >= 0, d, jnp.inf)


def pack_pairs(q, i):
    """One uint32 word per candidate: biased dist byte high, 24-bit id
    low. Invalid ids (< 0) carry :data:`PACK_ID_SENTINEL`."""
    b = (q.astype(jnp.int32) + 128).astype(jnp.uint32)
    idw = jnp.where(i >= 0, i, PACK_ID_SENTINEL).astype(jnp.uint32)
    return (b << 24) | (idw & jnp.uint32(PACK_ID_SENTINEL))


def unpack_pairs(w) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of :func:`pack_pairs` — bit-exact: the dist byte and the
    24-bit id round-trip unchanged, the sentinel maps back to -1."""
    q = ((w >> 24).astype(jnp.int32) - 128).astype(jnp.int8)
    idw = (w & jnp.uint32(PACK_ID_SENTINEL)).astype(jnp.int32)
    return q, jnp.where(idw == PACK_ID_SENTINEL, -1, idw)


def merge_wire_bytes(nq: int, k: int, n_shards: int, mode: str,
                     size: int = 0) -> Tuple[int, int]:
    """Analytic per-rank RECEIVED wire bytes of one cross-shard merge →
    ``(f32_bytes, mode_bytes)``. Host-side accounting for the
    ``raft.serve.dist.merge.bytes_{pre,post}`` counters and the
    ``merge_bytes_ratio`` bench figure (the trace-time
    ``raft.comms.collective.bytes`` counters only fire once per
    compiled program, never per execution)."""
    if n_shards <= 1:
        return 0, 0
    f32 = (n_shards - 1) * nq * k * 8          # allgather of f32 d + i32 i
    if mode == "f32":
        return f32, f32
    blk = -(-nq // n_shards)
    pair = 4 if 0 < size < PACK_ID_SENTINEL else 5   # packed | split
    # + 8 B/row: the f32 (scale, zero) affine metadata
    per_stage = (n_shards - 1) * blk * (k * pair + 8)
    return f32, 2 * per_stage


def compressed_merge(comms, d, i, k: int, size: int):
    """The int8 two-stage cross-shard top-k merge — runs inside
    ``shard_map``; every rank returns the identical full ``(nq, k)``
    result (same contract as ``_global_merge``).

    Per-query independence is a correctness property the serving tier
    leans on: scales are per-row and each query's candidate set is
    exactly the shards' top-k for that row, so a query's merged result
    does not depend on which batch (or padding) it rode in — asserted
    by the pad-row non-leakage test in ``tests/test_serve_dist.py``.
    """
    n = comms.get_size()
    axis = comms.axis_name
    nq = d.shape[0]
    blk = -(-nq // n)
    pad = blk * n - nq
    if pad:
        d = jnp.pad(d, ((0, pad), (0, 0)), constant_values=jnp.inf)
        i = jnp.pad(i, ((0, pad), (0, 0)), constant_values=-1)
    packed = 0 < size < PACK_ID_SENTINEL

    # stage A: ship each query block's candidates to its owner rank
    qz, s, z = quantize_rows(d, i)
    if packed:
        rw = comms.alltoall(pack_pairs(qz, i)).reshape(n, blk, k)
        rq, ri = unpack_pairs(rw)
    else:
        rq = comms.alltoall(qz).reshape(n, blk, k)
        ri = comms.alltoall(i).reshape(n, blk, k)
    meta = comms.alltoall(jnp.stack([s, z], axis=1)).reshape(n, blk, 2)
    rd = dequantize_rows(rq, meta[..., 0:1], meta[..., 1:2], ri)

    # owner-local merge of its nq/n slice: n·k candidates per query
    cat_d = jnp.moveaxis(rd, 0, 1).reshape(blk, n * k)
    cat_i = jnp.moveaxis(ri, 0, 1).reshape(blk, n * k)
    nd, sel = lax.top_k(-cat_d, k)
    md = -nd
    mi = jnp.take_along_axis(cat_i, sel, axis=1)      # (blk, k)

    # stage B: re-quantize the merged slice, allgather, dequantize
    qz2, s2, z2 = quantize_rows(md, mi)
    if packed:
        gq, gi = unpack_pairs(comms.allgather(pack_pairs(qz2, mi)))
    else:
        gq = comms.allgather(qz2)
        gi = comms.allgather(mi)
    gm = comms.allgather(jnp.stack([s2, z2], axis=1))  # (n, blk, 2)
    fd = dequantize_rows(gq, gm[..., 0:1], gm[..., 1:2],
                         gi).reshape(n * blk, k)[:nq]
    fi = gi.reshape(n * blk, k)[:nq]
    # identical on every rank; pmax proves replication to shard_map
    # (the _global_merge convention)
    return lax.pmax(fd, axis), lax.pmax(fi, axis)
