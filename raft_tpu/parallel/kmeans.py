"""MNMG k-means.

The reference keeps kmeans single-GPU and leaves MNMG to cuML, built from
exactly these pieces + ``handle.get_comms()`` allreduce of centroid
sums/counts (SURVEY.md §3.3 note); this framework ships the MNMG loop
itself. Data rows are sharded over the mesh's ``data`` axis (optionally
with features sharded over a ``model`` axis); each Lloyd step computes
local assignments and per-cluster partial sums, then a psum over the mesh
produces identical replicated centroids on every shard — the exact
communication pattern of cuML's MNMG kmeans, expressed as XLA collectives
on ICI.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.core.mdarray import as_array
from raft_tpu.cluster.kmeans_types import InitMethod, KMeansParams
from raft_tpu.cluster.kmeans import _plus_plus, sample_centroids
from raft_tpu.core.precision import matmul_precision


def distributed_kmeans_step(x_shard, centroids, valid, n_clusters: int,
                            axis: str = "data"):
    """One Lloyd step inside shard_map: local assign + segment-sum, psum
    across the data axis, replicated centroid update. ``valid`` masks the
    pad rows introduced by sharding."""
    # local assignment (fused argmin formulation)
    xx = jnp.sum(x_shard * x_shard, axis=1)
    cc = jnp.sum(centroids * centroids, axis=1)
    d = xx[:, None] + cc[None, :] - 2.0 * lax.dot_general(
        x_shard, centroids, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=matmul_precision())
    labels = jnp.argmin(d, axis=1)
    mind = jnp.min(d, axis=1)
    w = valid.astype(jnp.float32)

    local_sums = jax.ops.segment_sum(x_shard * w[:, None], labels,
                                     num_segments=n_clusters)
    local_counts = jax.ops.segment_sum(w, labels, num_segments=n_clusters)
    local_inertia = jnp.sum(jnp.maximum(mind, 0.0) * w)

    sums = lax.psum(local_sums, axis)
    counts = lax.psum(local_counts, axis)
    inertia = lax.psum(local_inertia, axis)
    new_centroids = sums / jnp.where(counts == 0.0, 1.0, counts)[:, None]
    # keep old centroid for empty clusters (replicated-deterministic)
    new_centroids = jnp.where((counts == 0.0)[:, None], centroids,
                              new_centroids)
    return new_centroids, inertia


def distributed_kmeans_fit(
    x,
    params: KMeansParams = KMeansParams(),
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = "data",
    res=None,
) -> Tuple[jax.Array, jax.Array, int]:
    """Fit k-means over a mesh → (centroids, inertia, n_iter). The full
    Lloyd loop runs as ONE jit'd while_loop over the sharded data."""
    x = as_array(x).astype(jnp.float32)
    if mesh is None:
        mesh = (res.mesh if res is not None
                else jax.sharding.Mesh(jax.devices(), ("data",)))
    n_shards = mesh.shape[axis]
    n, dim = x.shape
    k = params.n_clusters
    pad = (-n) % n_shards
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    valid = (jnp.arange(n + pad) < n)

    if params.init == InitMethod.Random:
        c0 = sample_centroids(x[:n], k, params.seed, res)
    else:
        # kmeans++ seeding on the (host-visible) global data — the seeding
        # cost is O(k) scans, negligible next to the Lloyd loop
        c0 = _plus_plus(x[:n], jnp.ones((n,), jnp.float32),
                        jax.random.key(params.seed), k)

    def build():
        def local(x_shard, valid_shard, c_init):
            def body(state):
                c, _, it, shift = state
                new_c, inertia = distributed_kmeans_step(
                    x_shard, c, valid_shard, k, axis)
                shift = jnp.sum((new_c - c) ** 2)
                return new_c, inertia, it + 1, shift

            def cond(state):
                _, _, it, shift = state
                return jnp.logical_and(it < params.max_iter,
                                       shift > params.tol)

            state = (c_init, jnp.asarray(jnp.inf, jnp.float32),
                     jnp.asarray(0, jnp.int32),
                     jnp.asarray(jnp.inf, jnp.float32))
            c, inertia, n_iter, _ = lax.while_loop(cond, body, state)
            return c, inertia, n_iter

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P()),
            out_specs=(P(), P(), P())))

    from raft_tpu.parallel.ivf import _shmap_plan
    shmapped = _shmap_plan(
        ("kmeans_fit", mesh, axis, k, int(params.max_iter),
         float(params.tol)), build)
    xs = jax.device_put(x, NamedSharding(mesh, P(axis, None)))
    vs = jax.device_put(valid, NamedSharding(mesh, P(axis)))
    cr = jax.device_put(c0, NamedSharding(mesh, P()))
    centroids, inertia, n_iter = shmapped(xs, vs, cr)
    return centroids, inertia, int(n_iter)
