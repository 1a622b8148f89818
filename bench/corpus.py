"""The corpus and the query pool, made on the device from the seed.

A copy of ``chip_smoke.py``'s ``make_blobs`` path in plain
``jax.numpy``, so that no change to the program can change the data: a
mixture of ``n_centers`` Gaussian components with unit-scale centres
and unit noise; every row picks its component independently, so rows
arrive in random order.

The mixture, the corpus and the sample the server's ladder is warmed
with come from the configuration's fixed ``data_seed``; the query pool
comes from the run's seed. The program pads every list to the largest
and measures its inverted-table width from the warm-up sample, and
compiles a program per padded shape, so a corpus drawn anew for every
seed would compile anew in every run and change the work itself (up to
40% of ``qps`` between seeds, PR 22's first chip call). Every seed
therefore searches the same index with its own queries. The pool is
held out: fresh draws from the same mixture, never corpus rows.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A JAX key from any non-negative whole number (more than 32 bits)."""
    import jax
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b))


def _blobs(key, n: int, centers):
    import jax
    import jax.numpy as jnp
    k_label, k_noise = jax.random.split(key)
    labels = jax.random.randint(k_label, (n,), 0, centers.shape[0])
    return centers[labels] + jax.random.normal(
        k_noise, (n, centers.shape[1]), jnp.float32)


def make(data_seed: int, seed: int, n: int, dim: int, n_centers: int,
         n_pool: int, n_warm: int, mesh=None):
    """``(x, warm, pool)``: the corpus on the device (row-sharded over
    ``mesh``'s ``data`` axis where one is given, each device making its
    own rows in one program), the ladder's warm-up sample and the query
    pool on the host."""
    import jax
    from jax.sharding import PartitionSpec as P
    key = seed_key(data_seed)

    def gen_centers(key):
        return jax.random.normal(jax.random.fold_in(key, 0), (n_centers, dim))

    if mesh is None:
        x = jax.jit(lambda k: _blobs(jax.random.fold_in(k, 1), n,
                                     gen_centers(k)))(key)
    else:
        per = n // mesh.size
        if per * mesh.size != n:
            raise ValueError(f"corpus rows {n} do not split over "
                             f"{mesh.size} devices")

        def shard_rows(k):
            shard = jax.lax.axis_index("data")
            return _blobs(jax.random.fold_in(k, 10 + shard), per,
                          gen_centers(k))

        x = jax.jit(jax.shard_map(shard_rows, mesh=mesh, in_specs=P(),
                                  out_specs=P("data", None)))(key)
    held_out = jax.jit(lambda k, kq, m: _blobs(kq, m, gen_centers(k)),
                       static_argnums=2)
    warm = held_out(key, jax.random.fold_in(key, 3), n_warm)
    pool = held_out(key, jax.random.fold_in(seed_key(seed), 2), n_pool)
    return jax.block_until_ready(x), np.asarray(warm), np.asarray(pool)
