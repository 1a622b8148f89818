"""K-means clustering.

Reference: ``raft/cluster/kmeans.cuh:51-953`` / ``cluster/detail/kmeans.cuh``:
``initRandom`` (:59), ``kmeansPlusPlus`` (:84), the Lloyd loop
``kmeans_fit_main`` (:262) built on fusedL2NN argmin +
``reduce_rows_by_key`` weighted centroid update, plus publicly exposed
building blocks (sample_centroids, cluster_cost, minClusterDistance,
countSamplesInCluster).

TPU design: the whole Lloyd iteration is one jit region — assignment via
the scanned fused-L2-argmin (no (n, k) matrix in HBM), update via
segment-sum (deterministic, replaces atomics), convergence via
``lax.while_loop`` on centroid movement, exactly the
compiler-friendly-control-flow shape XLA wants. Empty clusters are
re-seeded deterministically from the current highest-cost points (the
reference shuffles in points from large clusters).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.obs import spans
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.fused_l2_nn import fused_l2_nn
from raft_tpu.cluster.kmeans_types import InitMethod, KMeansParams
from raft_tpu.util.host_sample import sample_rows, take_rows


def _weighted_update(x, labels, weights, n_clusters: int):
    """Weighted per-cluster mean via segment-sum (the reference's
    matrix::gather + reduce_rows_by_key path, detail/kmeans.cuh:262+)."""
    wsum = jax.ops.segment_sum(weights, labels, num_segments=n_clusters)
    psum = jax.ops.segment_sum(x * weights[:, None], labels,
                               num_segments=n_clusters)
    centroids = psum / jnp.where(wsum == 0.0, 1.0, wsum)[:, None]
    return centroids, wsum


def _assign(x, centroids):
    """(labels, sq-dists) of each point to its nearest centroid — via the
    public fused_l2_nn (Pallas kernel on TPU)."""
    kv = fused_l2_nn(x, centroids, sqrt=False)
    return kv.key, kv.value


@functools.partial(jax.jit, static_argnames=("n_clusters", "max_iter"))
def _lloyd(x, weights, init_centroids, n_clusters: int, max_iter: int,
           tol: float):
    n = x.shape[0]

    def body(state):
        centroids, _, it, _ = state
        labels, d = _assign(x, centroids)
        new_centroids, wsum = _weighted_update(x, labels, weights, n_clusters)
        # empty clusters: re-seed from the points with highest cost
        # (deterministic analogue of detail/kmeans.cuh empty handling).
        # approx_max_k, not top_k: the reseed is heuristic, and an exact
        # top_k is an n-wide sort whose first TPU compile at bench
        # shapes (500k rows) runs minutes; PartialReduce is the
        # TPU-native selection
        empty = wsum == 0.0
        n_worst = n_clusters  # top-k worst points, one per potential empty
        _, worst = lax.approx_max_k(d, n_worst)
        order = jnp.cumsum(empty.astype(jnp.int32)) - 1  # slot per empty cluster
        seed_pts = x[worst]
        new_centroids = jnp.where(
            empty[:, None], seed_pts[jnp.clip(order, 0, n_worst - 1)],
            new_centroids)
        shift = jnp.sum((new_centroids - centroids) ** 2)
        inertia = jnp.sum(weights * d)
        return new_centroids, inertia, it + 1, shift

    def cond(state):
        _, _, it, shift = state
        return jnp.logical_and(it < max_iter, shift > tol)

    init_state = (init_centroids, jnp.asarray(jnp.inf, jnp.float32),
                  jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, jnp.float32))
    centroids, inertia, n_iter, _ = lax.while_loop(cond, body, init_state)
    # final assignment for the returned inertia (post-update)
    labels, d = _assign(x, centroids)
    inertia = jnp.sum(weights * d)
    return centroids, labels, inertia, n_iter


@functools.partial(jax.jit, static_argnames=("n_clusters",))
def _plus_plus(x, weights, key, n_clusters: int):
    """k-means++ seeding (reference kmeansPlusPlus, detail/kmeans.cuh:84):
    iteratively sample the next center ∝ weighted min-distance², carried
    through a ``lax.scan`` with a categorical (Gumbel) draw per step."""
    n = x.shape[0]
    k0 = jax.random.fold_in(key, 0)
    first = jax.random.randint(k0, (), 0, n)
    centers0 = jnp.zeros((n_clusters, x.shape[1]), x.dtype).at[0].set(x[first])
    d0 = jnp.sum((x - x[first][None, :]) ** 2, axis=1)

    def step(carry, i):
        centers, mind = carry
        cost = jnp.maximum(mind * weights, 0.0)
        logits = jnp.log(jnp.maximum(cost, 1e-37))
        ki = jax.random.fold_in(key, i)
        pick = jax.random.categorical(ki, logits)
        c = x[pick]
        centers = centers.at[i].set(c)
        mind = jnp.minimum(mind, jnp.sum((x - c[None, :]) ** 2, axis=1))
        return (centers, mind), None

    (centers, _), _ = lax.scan(step, (centers0, d0),
                               jnp.arange(1, n_clusters))
    return centers


def init_plus_plus(x, n_clusters: int, sample_weight=None, seed: int = 0,
                   res=None) -> jax.Array:
    """Public k-means++ seeding (reference kmeans.cuh init_plus_plus)."""
    x = as_array(x).astype(jnp.float32)
    w = (jnp.ones(x.shape[0], jnp.float32) if sample_weight is None
         else as_array(sample_weight).astype(jnp.float32))
    return _plus_plus(x, w, jax.random.key(seed), n_clusters)


def sample_centroids(x, n_clusters: int, seed: int = 0, res=None) -> jax.Array:
    """Random distinct-point seeding (reference initRandom /
    sample_centroids)."""
    x = as_array(x)
    # host-side draw (util.host_sample): a traced choice(replace=False)
    # is an n-wide sort compile on TPU
    return take_rows(x, sample_rows(x.shape[0], n_clusters, seed))


@spans.spanned("raft.kmeans.fit")
@obs.timed("raft.kmeans.fit")
def fit(x, params: KMeansParams = KMeansParams(), sample_weight=None,
        init_centroids=None, res=None
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fit k-means → (centroids (k, d), inertia, n_iter). Mirrors
    ``raft::cluster::kmeans::fit`` (kmeans.cuh:51)."""
    x = as_array(x).astype(jnp.float32)
    n = x.shape[0]
    k = params.n_clusters
    expects(k <= n, "kmeans: n_clusters > n_samples")
    w = (jnp.ones(n, jnp.float32) if sample_weight is None
         else as_array(sample_weight).astype(jnp.float32))

    if init_centroids is not None or params.init == InitMethod.Array:
        expects(init_centroids is not None,
                "kmeans: InitMethod.Array requires init_centroids")
        c0 = as_array(init_centroids).astype(jnp.float32)
    elif params.init == InitMethod.Random:
        c0 = sample_centroids(x, k, params.seed, res)
    else:
        c0 = _plus_plus(x, w, jax.random.key(params.seed), k)

    # Array init is deterministic — restarts would just repeat it
    n_trials = 1 if (init_centroids is not None
                     or params.init == InitMethod.Array) else max(1, params.n_init)
    best = None
    inertias = []
    for trial in range(n_trials):
        if trial > 0:
            # re-seed respecting the requested init method
            if params.init == InitMethod.Random:
                c0 = sample_centroids(x, k, params.seed + trial, res)
            else:
                c0 = _plus_plus(x, w, jax.random.key(params.seed + trial), k)
        centroids, labels, inertia, n_iter = _lloyd(
            x, w, c0, k, params.max_iter, params.tol)
        inertias.append(float(inertia))
        if best is None or inertias[-1] < float(best[2]):
            best = (centroids, labels, inertia, n_iter)
    centroids, _, inertia, n_iter = best
    # the values are already host-synced (the best-trial comparison
    # fetched each inertia; n_iter rides the same executed program)
    obs.counter("raft.kmeans.fit.total").inc()
    obs.counter("raft.kmeans.fit.rows").inc(n)
    spans.current_span().set_attrs(rows=n, n_clusters=k,
                                   n_iter=int(n_iter),
                                   inertia=float(inertia))
    obs.histogram("raft.kmeans.fit.iterations",
                  buckets=obs.SIZE_BUCKETS).observe(int(n_iter))
    obs.gauge("raft.kmeans.fit.inertia").set(float(inertia))
    if len(inertias) > 1:
        # multi-restart improvement: first trial vs the kept best —
        # how much the n_init restarts actually bought
        obs.gauge("raft.kmeans.fit.inertia_delta").set(
            inertias[0] - float(inertia))
    return centroids, inertia, n_iter


def predict(x, centroids, sample_weight=None, res=None) -> jax.Array:
    """Nearest-centroid labels (reference kmeans.cuh predict)."""
    x = as_array(x).astype(jnp.float32)
    centroids = as_array(centroids).astype(jnp.float32)
    labels, _ = _assign(x, centroids)
    return labels


def fit_predict(x, params: KMeansParams = KMeansParams(), sample_weight=None,
                res=None) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(labels, centroids, inertia, n_iter)."""
    centroids, inertia, n_iter = fit(x, params, sample_weight, res=res)
    return predict(x, centroids, res=res), centroids, inertia, n_iter


def transform(x, centroids, res=None) -> jax.Array:
    """Distance of every point to every centroid (reference
    kmeans.cuh transform) — L2 (not squared), matching the reference's
    default L2 metric output."""
    from raft_tpu.distance.pairwise import distance
    from raft_tpu.distance.distance_types import DistanceType
    return distance(x, centroids, DistanceType.L2SqrtExpanded, res=res)


def cluster_cost(x, centroids, sample_weight=None, res=None) -> jax.Array:
    """Total within-cluster squared-distance cost (reference
    kmeans.cuh cluster_cost)."""
    x = as_array(x).astype(jnp.float32)
    _, d = _assign(x, as_array(centroids).astype(jnp.float32))
    if sample_weight is not None:
        d = d * as_array(sample_weight)
    return jnp.sum(d)


def min_cluster_distance(x, centroids, res=None) -> jax.Array:
    """Per-point min squared distance to any centroid (reference
    minClusterDistance building block)."""
    x = as_array(x).astype(jnp.float32)
    _, d = _assign(x, as_array(centroids).astype(jnp.float32))
    return d


def count_samples_in_cluster(x, centroids, res=None) -> jax.Array:
    """Per-cluster sample counts (reference countSamplesInCluster)."""
    x = as_array(x).astype(jnp.float32)
    c = as_array(centroids).astype(jnp.float32)
    labels, _ = _assign(x, c)
    return jax.ops.segment_sum(jnp.ones_like(labels, jnp.int32), labels,
                               num_segments=c.shape[0])
