"""Balanced hierarchical k-means — the ANN index trainer.

Reference: ``spatial/knn/detail/ann_kmeans_balanced.cuh`` — minibatched EM
(``predict`` :72 = norm-corrected GEMM + argmin), ``adjust_centers`` (:436:
empty/small clusters steal points from big ones), ``balancing_em_iters``
(:628), ``build_hierarchical`` (:848-ish: two-level — √k mesoclusters then
per-meso fine clusters — so training never runs a huge single k).

TPU design: predict is the scanned fused-L2-argmin (pure MXU);
adjust_centers is deterministic — each under-populated cluster re-seeds to
a point drawn from the highest-assignment-cost points, computed with one
top_k; the EM iteration is a jit'd ``lax.fori_loop``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.fused_l2_nn import fused_l2_nn
from raft_tpu.util.host_sample import sample_rows, take_rows


def _nn(x, centers, kernel_precision=None):
    """(labels, dists) of nearest centers via the public fused_l2_nn —
    one dispatch site for the Pallas-vs-XLA routing. Traceable: usable
    inside the jit'd EM loop."""
    kv = fused_l2_nn(x, centers, sqrt=False,
                     kernel_precision=kernel_precision)
    return kv.key, kv.value


def predict(x, centers, res=None) -> jax.Array:
    """Nearest-center labels (reference ann_kmeans_balanced predict :72)."""
    x = as_array(x).astype(jnp.float32)
    centers = as_array(centers).astype(jnp.float32)
    labels, _ = _nn(x, centers)
    return labels


def _em_body(x, centers0, n_clusters: int, n_iters: int,
             balance_threshold: float, kernel_precision=None):
    n = x.shape[0]
    avg = n / n_clusters

    def one_iter(_, centers):
        labels, d = _nn(x, centers, kernel_precision)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), labels,
                                     num_segments=n_clusters)
        sums = jax.ops.segment_sum(x, labels, num_segments=n_clusters)
        new_centers = sums / jnp.where(counts == 0.0, 1.0, counts)[:, None]
        # adjust_centers (reference :436): clusters below threshold·avg
        # re-seed from the globally highest-cost points. approx_max_k:
        # the exact sort over n rows is a giant first-compile on TPU
        # (sort width = n); the PartialReduce op is the TPU-native
        # selection and re-seed candidates are heuristic anyway.
        small = counts < balance_threshold * avg
        _, worst = lax.approx_max_k(d, n_clusters)
        slot = jnp.cumsum(small.astype(jnp.int32)) - 1
        seeds = x[worst]
        new_centers = jnp.where(small[:, None],
                                seeds[jnp.clip(slot, 0, n_clusters - 1)],
                                new_centers)
        return new_centers

    return lax.fori_loop(0, n_iters, one_iter, centers0)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters",
                                             "kernel_precision"))
def _em(x, centers0, n_clusters: int, n_iters: int, balance_threshold: float,
        kernel_precision=None):
    return _em_body(x, centers0, n_clusters, n_iters, balance_threshold,
                    kernel_precision)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters",
                                             "kernel_precision"))
def _em_seeded(x, init_idx, n_clusters: int, n_iters: int,
               balance_threshold: float, kernel_precision=None):
    """_em with the init-center gather folded in: ``centers0 =
    x[init_idx]`` inside the SAME program (eagerly the gather is its
    own take_rows compile per shape — cold-build compile count,
    VERDICT r4 #6). Value-identical to take_rows + _em."""
    return _em_body(x, x[init_idx], n_clusters, n_iters,
                    balance_threshold, kernel_precision)


def balanced_kmeans(x, n_clusters: int, n_iters: int = 20,
                    balance_threshold: float = 0.25, seed: int = 0,
                    kernel_precision: str | None = None,
                    res=None) -> jax.Array:
    """Train ``n_clusters`` balanced centers (reference
    balancing_em_iters :628). Returns (n_clusters, dim) centers.
    ``kernel_precision``: per-call Pallas matmul tier for the EM
    assignment (``"bf16"`` = one MXU pass — the ANN-trainer speed knob;
    cluster assignment tolerates ~5e-4 relative distance error, gate
    any default change on downstream index recall)."""
    x = as_array(x).astype(jnp.float32)
    obs.counter("raft.kmeans_balanced.em_sweeps").inc(n_iters)
    # init indices sampled HOST-side (util.host_sample rationale: a
    # traced choice(replace=False) is an n-wide sort compile); the
    # gather rides inside the EM program (_em_seeded)
    with obs.timed("raft.kmeans_balanced.train"):
        return _em_seeded(x, sample_rows(x.shape[0], n_clusters, seed),
                          n_clusters, n_iters, balance_threshold,
                          kernel_precision=kernel_precision)


# ---------------------------------------------------------------------------
# Data-parallel trainer (ISSUE 4 tentpole): the MNMG form of the balanced
# EM above — RAFT's own MNMG value proposition is exactly this loop built
# from kmeans pieces + a raft::comms allreduce of the centroid sufficient
# statistics (SURVEY.md §3.3); EQuARX shows the statistics exchange is the
# compressible part, and here it is the ONLY per-sweep wire traffic.
# ---------------------------------------------------------------------------

# jitted-callable cache for the sharded EM program (the parallel/ivf
# _shmap_plan pattern at trainer scope): without it every build would
# re-trace + re-compile the whole shard_map'd fori_loop — the exact
# serving-call retrace bug PR 2 fixed for searches, at build scope.
_SHARDED_EM_PLANS: dict = {}


def _sharded_em_plan(key, builder):
    fn = _SHARDED_EM_PLANS.get(key)
    if fn is None:
        obs.counter("raft.kmeans_balanced.sharded.plan_misses").inc()
        fn = _SHARDED_EM_PLANS[key] = builder()
    else:
        obs.counter("raft.kmeans_balanced.sharded.plan_hits").inc()
    return fn


def balanced_kmeans_sharded(x, n_clusters: int, n_iters: int = 20,
                            balance_threshold: float = 0.25, seed: int = 0,
                            kernel_precision: str | None = None,
                            mesh=None, axis: str = "data",
                            res=None) -> jax.Array:
    """Data-parallel :func:`balanced_kmeans` over ``mesh[axis]``.

    Rows are sharded over the mesh's data axis; each EM sweep computes
    per-shard centroid sums/counts and ``psum``s the sufficient
    statistics (the cuML-MNMG/raft::comms pattern), so per-sweep wire
    traffic is O(n_clusters·dim), independent of the shard size. The
    balancing/reseed step runs on the REPLICATED statistics: each shard
    contributes its top-``n_clusters`` highest-assignment-cost rows,
    the candidates are allgathered and re-ranked identically on every
    shard, so the selected seeds — and therefore the centers — stay
    bit-identical across shards. Returns (n_clusters, dim) replicated
    centers.

    Parity with the single-device trainer: the EM update is the same
    math (sums/counts merely reduce in a different order) and the
    reseed pool is the exact global top-k where the single-device path
    uses ``approx_max_k`` — both are heuristic seed choices; centers
    agree within fp tolerance whenever balancing rarely triggers (the
    parity test's regime) and within recall tolerance downstream
    otherwise."""
    import jax.sharding
    from jax.sharding import NamedSharding, PartitionSpec as P
    from raft_tpu.comms.comms import build_comms

    if mesh is None:
        mesh = (res.mesh if res is not None and hasattr(res, "mesh")
                else jax.sharding.Mesh(jax.devices(), (axis,)))
    x = as_array(x).astype(jnp.float32)
    n, dim = x.shape
    n_shards = mesh.shape[axis]
    obs.counter("raft.kmeans_balanced.em_sweeps").inc(n_iters)
    obs.counter("raft.kmeans_balanced.build.total", path="sharded").inc()

    # init centers: the SAME host-side draw as the single-device trainer
    # (seed-for-seed identical inits are what makes parity testable);
    # gathered eagerly — O(n_clusters·dim), replicated
    c0 = take_rows(x, sample_rows(n, n_clusters, seed))

    pad = (-n) % n_shards
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    valid = jnp.arange(n + pad) < n
    m_local = (n + pad) // n_shards
    # per-shard reseed candidates: enough that the global top-n_clusters
    # is exact (each shard contributes up to n_clusters candidates)
    kc = min(n_clusters, m_local)
    avg = n / n_clusters

    def build():
        comms = build_comms(mesh, axis)

        def local(x_sh, valid_sh, c_init):
            w = valid_sh.astype(jnp.float32)

            def one_iter(_, centers):
                labels, d = _nn(x_sh, centers, kernel_precision)
                counts = comms.allreduce(jax.ops.segment_sum(
                    w, labels, num_segments=n_clusters))
                sums = comms.allreduce(jax.ops.segment_sum(
                    x_sh * w[:, None], labels, num_segments=n_clusters))
                new_centers = sums / jnp.where(counts == 0.0, 1.0,
                                               counts)[:, None]
                # adjust_centers on replicated statistics: per-shard
                # top-kc worst-cost REAL rows → allgather → exact global
                # top-n_clusters, identical on every shard (pad rows
                # carry -inf cost and never qualify)
                dm = jnp.where(valid_sh, d, -jnp.inf)
                wd, wi = lax.top_k(dm, kc)
                cand = x_sh[wi]
                gd = comms.allgather(wd).reshape(-1)
                gc = comms.allgather(cand).reshape(-1, dim)
                _, sel = lax.top_k(gd, n_clusters)
                # pmax proves replication of the gathered-selection to
                # shard_map (the _global_merge trick) — identity in value
                seeds = lax.pmax(gc[sel], axis)
                small = counts < balance_threshold * avg
                slot = jnp.cumsum(small.astype(jnp.int32)) - 1
                return jnp.where(
                    small[:, None],
                    seeds[jnp.clip(slot, 0, n_clusters - 1)],
                    new_centers)

            return lax.fori_loop(0, n_iters, one_iter, c_init)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P()),
            out_specs=P()))

    with obs.timed("raft.kmeans_balanced.train", path="sharded"):
        fn = _sharded_em_plan(
            ("balanced_em", mesh, axis, n_clusters, n_iters,
             float(balance_threshold), kernel_precision,
             m_local, dim), build)
        xs = jax.device_put(x, NamedSharding(mesh, P(axis, None)))
        vs = jax.device_put(valid, NamedSharding(mesh, P(axis)))
        cr = jax.device_put(c0, NamedSharding(mesh, P()))
        return fn(xs, vs, cr)


def build_hierarchical(x, n_clusters: int, n_iters: int = 20,
                       max_train_points: int = 1 << 18, seed: int = 0,
                       kernel_precision: str | None = None,
                       res=None) -> jax.Array:
    """Two-level balanced trainer (reference build_hierarchical): train
    √k mesoclusters on a subsample, partition, then train proportional
    fine clusters per mesocluster; finish with balancing iterations over
    the full center set. ``kernel_precision`` reaches every EM sweep
    (see :func:`balanced_kmeans`)."""
    x = as_array(x).astype(jnp.float32)
    n = x.shape[0]

    # subsample trainset (reference ivf builds train on a subset) —
    # host-side draw for the same no-giant-sort-compile reason as in
    # balanced_kmeans
    if n > max_train_points:
        xt = take_rows(x, sample_rows(n, max_train_points, seed))
    else:
        xt = x
    nt = xt.shape[0]

    # TPU-first: up to tens of thousands of centers, flat EM at full k is
    # a single compile of pure MXU work (the fused argmin tiles
    # n_rows × k × dim at ~peak); the reference's two-level hierarchy
    # (built to bound CUDA fusedL2NN cost) only pays for itself beyond
    # that — and naive per-mesocluster shapes would trigger one XLA
    # recompile each (SURVEY.md hard part (c)).
    if n_clusters <= 16384:
        obs.counter("raft.kmeans_balanced.build.total", path="flat").inc()
        return balanced_kmeans(xt, n_clusters, n_iters, seed=seed,
                               kernel_precision=kernel_precision, res=res)
    obs.counter("raft.kmeans_balanced.build.total", path="two_level").inc()

    # two-level path, shape-bucketed so XLA compiles O(log) variants, not
    # O(n_meso): uniform fine allocation (one km for every mesocluster —
    # the trainer is balanced by construction) and per-meso point sets
    # padded to the next power of two by cyclic repetition (preserves the
    # empirical distribution seen by EM).
    n_meso = int(math.isqrt(n_clusters))
    km = -(-n_clusters // n_meso)  # uniform fine centers per meso
    meso_centers = balanced_kmeans(xt, n_meso, n_iters, seed=seed,
                                   kernel_precision=kernel_precision,
                                   res=res)
    meso_labels = predict(xt, meso_centers, res=res)
    meso_np = jax.device_get(meso_labels)

    centers = []
    for m in range(n_meso):
        pts = xt[meso_np == m]
        if pts.shape[0] == 0:
            centers.append(jnp.broadcast_to(meso_centers[m],
                                            (km, x.shape[1])))
            continue
        if pts.shape[0] <= km:
            pad = jnp.broadcast_to(meso_centers[m],
                                   (km - pts.shape[0], x.shape[1]))
            centers.append(jnp.concatenate([pts, pad], axis=0))
            continue
        target = 1 << max(km.bit_length(),
                          (pts.shape[0] - 1).bit_length())
        reps = -(-target // pts.shape[0])
        pts_p = jnp.tile(pts, (reps, 1))[:target]
        centers.append(balanced_kmeans(pts_p, km, max(4, n_iters // 2),
                                       seed=seed + m + 1,
                                       kernel_precision=kernel_precision,
                                       res=res))
    all_centers = jnp.concatenate(centers, axis=0)[:n_clusters]
    # final balancing sweeps over the full center set
    balance_rounds = max(2, n_iters // 4)
    obs.counter("raft.kmeans_balanced.balancing_rounds").inc(balance_rounds)
    return _em(xt, all_centers, n_clusters, balance_rounds, 0.25,
               kernel_precision=kernel_precision)
