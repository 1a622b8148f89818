"""Distributed brute-force k-NN.

Design (SURVEY.md §5 "scale the big dimension"): shard the database rows
across the mesh's data axis; queries are replicated. Each shard computes
its local top-k with the scanned fused kernel, then shards merge — either
one all_gather + select (small k·n_shards) or a ring of
``collective_permute`` merge steps (constant memory, overlaps with ICI),
the sequence-parallel-style pattern this domain calls for. Index ids are
translated by shard offsets exactly like the reference's multi-part
``knn_merge_parts`` path (brute_force.cuh:48).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.core.mdarray import as_array
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors.brute_force import _knn_scan, _db_tile
from raft_tpu.comms.comms import build_comms
from raft_tpu.parallel.ivf import _shmap_plan


def _merge(d_a, i_a, d_b, i_b, k: int):
    cat_d = jnp.concatenate([d_a, d_b], axis=1)
    cat_i = jnp.concatenate([i_a, i_b], axis=1)
    nd, sel = lax.top_k(-cat_d, k)
    return -nd, jnp.take_along_axis(cat_i, sel, axis=1)


def distributed_knn(
    db,
    queries,
    k: int,
    mesh: jax.sharding.Mesh,
    axis: str = "data",
    metric: DistanceType = DistanceType.L2SqrtExpanded,
    merge: str = "ring",
    res=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact k-NN with the database sharded over ``mesh[axis]``.

    ``merge``: "ring" = n-1 collective_permute merge steps (constant
    memory per step); "allgather" = one gather + final select.
    """
    db = as_array(db).astype(jnp.float32)
    q = as_array(queries).astype(jnp.float32)
    n_shards = mesh.shape[axis]
    n = db.shape[0]
    pad = (-n) % n_shards
    if pad:
        db = jnp.pad(db, ((0, pad), (0, 0)))
    rows_per = (n + pad) // n_shards
    tile = _db_tile(q.shape[0], rows_per)

    def build():
        comms = build_comms(mesh, axis)

        def local(db_shard, q_rep):
            # local top-k over this shard's rows — inlined scan (the shared
            # _knn_scan creates an unvarying carry, which shard_map's
            # varying-manual-axes tracking rejects; here the init is cast
            # varying along the comm axis)
            nq = q_rep.shape[0]
            pad_t = (-rows_per) % tile
            dbp = (jnp.pad(db_shard, ((0, pad_t), (0, 0))) if pad_t else db_shard)
            n_tiles = (rows_per + pad_t) // tile
            db_tiles = dbp.reshape(n_tiles, tile, -1)
            offs = jnp.arange(n_tiles, dtype=jnp.int32) * tile

            from raft_tpu.distance.pairwise import _pairwise

            def step(carry, inp):
                best_d, best_i = carry
                dtile, off = inp
                dd = _pairwise(q_rep, dtile, metric, 2.0)
                col = jnp.arange(tile, dtype=jnp.int32)[None, :] + off
                dd = jnp.where(col < rows_per, dd, jnp.inf)
                td, tsel = lax.top_k(-dd, min(k, tile))
                ti = jnp.take_along_axis(jnp.broadcast_to(col, (nq, tile)),
                                         tsel, axis=1)
                return _merge(best_d, best_i, -td, ti, k), None

            init = (lax.pcast(jnp.full((nq, k), jnp.inf, jnp.float32),
                              (axis,), to="varying"),
                    lax.pcast(jnp.full((nq, k), -1, jnp.int32),
                              (axis,), to="varying"))
            (d, i), _ = lax.scan(step, init, (db_tiles, offs))
            # translate to global ids; mask pad rows (global id >= n)
            offset = lax.axis_index(axis) * rows_per
            gi = i + offset.astype(jnp.int32)
            d = jnp.where(gi < n, d, jnp.inf)
            gi = jnp.where(gi < n, gi, -1)

            if merge == "allgather":
                gd = comms.allgather(d)      # (n_shards, nq, k)
                gidx = comms.allgather(gi)
                cat_d = jnp.moveaxis(gd, 0, 1).reshape(q_rep.shape[0], -1)
                cat_i = jnp.moveaxis(gidx, 0, 1).reshape(q_rep.shape[0], -1)
                nd, sel = lax.top_k(-cat_d, k)
                fd, fi = -nd, jnp.take_along_axis(cat_i, sel, axis=1)
                # identical on every rank; a tiny pmax makes that provable to
                # shard_map's replication checker (no varying->invariant cast
                # exists)
                return lax.pmax(fd, axis), lax.pmax(fi, axis)

            # ring merge: circulate each rank's ORIGINAL candidate set around
            # the ring (merging the traveling set would duplicate candidates);
            # after n-1 hops every rank has merged every shard's set exactly
            # once
            def ring_step(carry, _):
                best_d, best_i, trav_d, trav_i = carry
                trav_d = comms.ring_permute(trav_d, 1)
                trav_i = comms.ring_permute(trav_i, 1)
                best_d, best_i = _merge(best_d, best_i, trav_d, trav_i, k)
                return (best_d, best_i, trav_d, trav_i), None

            (fd, fi, _, _), _ = lax.scan(ring_step, (d, gi, d, gi), None,
                                         length=n_shards - 1)
            # identical on every rank after n-1 hops; pmax proves replication
            return lax.pmax(fd, axis), lax.pmax(fi, axis)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=(P(), P())))

    shmapped = _shmap_plan(
        ("bf_knn", mesh, axis, k, int(metric), merge, rows_per, tile, n),
        build)
    db_sharded = jax.device_put(
        db, NamedSharding(mesh, P(axis, None)))
    q_rep = jax.device_put(q, NamedSharding(mesh, P()))
    return shmapped(db_sharded, q_rep)
