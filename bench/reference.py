"""The plain reference and the comparison that decides ``correct``.

The reference is exact k-NN over the same corpus: squared L2 from the
expanded form at ``Precision.HIGHEST``, top-k by ``lax.top_k``, in
blocks of corpus rows, each device of the mesh over its own rows with
the shards' top-k merged on the host. It imports nothing of the
program and takes nothing the program made; the corpus is the
benchmark's own data.

What is compared, over every answer the window's requests received
(the window's and the drain's alike):

* ``dist_err``: the widest relative gap between a served distance and
  the true squared L2 distance of the id served beside it, the latter
  computed elementwise in f32 (no matmul). It holds the f32 distances
  the configuration states; a scan in a lower precision fails it.
* ``recall_miss``: 1 - recall@10 of the first 10 of the ``k`` served
  ids against the exact top-10. It holds which rows come back: an
  answer altered, or rows of a batch left out, fail it.
* ``bad_ids``: served ids out of range or repeated within an answer.
* ``lost``: requests that got an error or no answer by the drain's end.
"""

from __future__ import annotations

import concurrent.futures
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

QUERY_BLOCK = 1024
ROW_BLOCK_CAP = 65536


def rows_per_block(n: int, cap: int = ROW_BLOCK_CAP) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    for parts in range(1, n + 1):
        if n % parts == 0 and n // parts <= cap:
            return n // parts
    return 1


def _local_topk(xs, q, k: int, low: bool):
    """Exact top-k of ``q`` over the rows ``xs`` (one device's shard,
    inside ``shard_map``): (m, k) distances and row offsets into ``xs``. ``low`` rounds both operands to bfloat16
    (f32 accumulation): the control's lower precision."""
    n, b = xs.shape[0], rows_per_block(xs.shape[0])
    m = q.shape[0]
    if low:
        q = q.astype(jnp.bfloat16).astype(jnp.float32)
    qq = jnp.sum(q * q, axis=1)

    def step(carry, j):
        best_d, best_i = carry
        xb = lax.dynamic_slice_in_dim(xs, j * b, b)
        if low:
            xb = xb.astype(jnp.bfloat16).astype(jnp.float32)
            ip = lax.dot_general(q.astype(jnp.bfloat16),
                                 xb.astype(jnp.bfloat16),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        else:
            ip = lax.dot_general(q, xb, (((1,), (1,)), ((), ())),
                                 precision=lax.Precision.HIGHEST)
        d = qq[:, None] + jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * ip
        nd, ni = lax.top_k(-d, k)
        cd = jnp.concatenate([best_d, -nd], axis=1)
        ci = jnp.concatenate([best_i, ni + j * b], axis=1)
        nd, sel = lax.top_k(-cd, k)
        return (-nd, jnp.take_along_axis(ci, sel, axis=1)), None

    init = lax.pcast((jnp.full((m, k), jnp.inf, jnp.float32),
                      jnp.full((m, k), -1, jnp.int32)), ("data",),
                     to="varying")
    (d, i), _ = lax.scan(step, init, jnp.arange(n // b))
    return d, i


def _blocks(n: int):
    """Query blocks of ``QUERY_BLOCK`` rows: (start, rows, padding)."""
    for a in range(0, n, QUERY_BLOCK):
        rows = min(QUERY_BLOCK, n - a)
        yield a, rows, QUERY_BLOCK - rows


def _pad(a: np.ndarray, pad: int) -> np.ndarray:
    return np.concatenate([a, np.repeat(a[:1], pad, 0)]) if pad else a


class Exact:
    """Exact search over the corpus ``x`` (row-sharded over ``mesh``'s
    ``data`` axis; a one-device mesh for one chip)."""

    def __init__(self, x, mesh, k: int, low: bool = False):
        self.x, self.k = x, k
        self.n = int(x.shape[0])
        per = self.n // mesh.size

        def search(xs, q):
            d, i = _local_topk(xs, q, k, low)
            return d[None], (i + lax.axis_index("data") * per)[None]

        def true_dists(xs, q, ids):
            loc = ids - lax.axis_index("data") * per
            ok = (loc >= 0) & (loc < per)
            g = xs[jnp.clip(loc, 0, per - 1)]
            d = jnp.sum(jnp.square(g - q[:, None, :]), axis=-1)
            return lax.psum(jnp.where(ok, d, 0.0), "data")

        self._search = jax.jit(jax.shard_map(
            search, mesh=mesh, in_specs=(P("data", None), P()),
            out_specs=(P("data"), P("data"))))
        self._true = jax.jit(jax.shard_map(
            true_dists, mesh=mesh, in_specs=(P("data", None), P(), P()),
            out_specs=P()))

    def search(self, q: np.ndarray):
        """``(dists, ids)`` of the exact top-k, numpy, shards merged."""
        d, i = self._search(self.x, np.asarray(q, np.float32))
        d = np.concatenate(list(np.asarray(d)), axis=1)
        i = np.concatenate(list(np.asarray(i)), axis=1)
        order = np.argsort(d, axis=1, kind="stable")[:, :self.k]
        return (np.take_along_axis(d, order, axis=1),
                np.take_along_axis(i, order, axis=1))

    def top_ids(self, q: np.ndarray) -> np.ndarray:
        """Exact top-k ids of every row of ``q``, in query blocks."""
        return np.concatenate([
            self.search(_pad(q[a:a + rows], pad))[1][:rows]
            for a, rows, pad in _blocks(len(q))])

    def true_dists(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Squared L2 of each ``q`` row to the corpus rows ``ids`` (m, k),
        elementwise in f32, in query blocks."""
        return np.concatenate([
            np.asarray(self._true(
                self.x, _pad(q[a:a + rows], pad).astype(np.float32),
                _pad(ids[a:a + rows], pad).astype(np.int32)))[:rows]
            for a, rows, pad in _blocks(len(q))])


def compare(exact: Exact, pool: np.ndarray, run, k: int) -> Dict[str, float]:
    """The numbers ``correct`` is decided by (see the module docstring),
    over every answered request of ``run``, each served ``k`` ids wide;
    recall over the first ``exact.k`` of them."""
    answered = run.answered()
    numbers = {"dist_err": float("inf"), "recall_miss": 1.0,
               "bad_ids": 0, "lost": run.lost()}
    if not answered or min(r.ids.shape[1] for r in answered) < k:
        return numbers
    rows = np.concatenate([r.rows for r in answered])
    ids = np.concatenate([r.ids[:, :k] for r in answered]).astype(np.int64)
    dists = np.concatenate([r.dists[:, :k] for r in answered])
    uniq, inv = np.unique(rows, return_inverse=True)
    truth = exact.top_ids(pool[uniq])[inv]
    top = ids[:, :exact.k]
    hits = 0
    for a in range(0, len(rows), 65536):
        s = slice(a, a + 65536)
        hits += int((top[s, :, None] == truth[s, None, :]).any(-1).sum())
    srt = np.sort(ids, axis=1)
    bad = (ids < 0) | (ids >= exact.n)
    true = exact.true_dists(pool[rows], np.clip(ids, 0, exact.n - 1))
    err = np.abs(dists.astype(np.float64) - true) / np.maximum(true, 1e-12)
    numbers.update(
        dist_err=float(err[~bad].max()) if (~bad).any() else float("inf"),
        recall_miss=1.0 - hits / (exact.k * len(rows)),
        bad_ids=int(bad.sum() + (srt[:, 1:] == srt[:, :-1]).sum()))
    return numbers


class ReferenceServer:
    """The control's stand-in for the server: exact search in bfloat16,
    one request at a time on a worker thread, with the ``submit``
    contract of ``SearchServer``."""

    def __init__(self, x, mesh, k: int, sizes):
        self._exact = Exact(x, mesh, k, low=True)
        self._pool = concurrent.futures.ThreadPoolExecutor(1)
        for s in sizes:      # warm every request shape in set-up
            self._exact.search(np.zeros((s, x.shape[1]), np.float32))

    def submit(self, queries):
        return self._pool.submit(self._exact.search, queries)

    def close(self) -> None:
        self._pool.shutdown(wait=True)
