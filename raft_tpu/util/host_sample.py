"""Host-side distinct-row sampling for trainset/init subsets.

A traced ``jax.random.choice(..., replace=False)`` lowers to a
full-width permutation — an n-wide sort whose first TPU compile takes
minutes at n ≥ ~100k. Every
in-library use of without-replacement sampling is *seeding*: picking a
trainset subsample or initial centroids before any jit region. The
reference does this with host RNG as well (``initRandom`` /
``trainset_fraction`` subsampling are thrust/host draws, e.g.
``cluster/detail/kmeans.cuh`` shuffle-and-gather), so drawing on host
with numpy and shipping only the gathered rows to device is both the
faithful and the TPU-safe design. The public ``raft_tpu.random``
distributions (user-facing RNG parity) keep their traced
implementations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# below this width the traced draw's sort compiles in ordinary time and
# we keep the historical jax.random stream (seed-for-seed identical to
# earlier releases — several quality tests are calibrated to it); above
# it the permutation compile is the hazard described above
_TRACED_MAX_N = 65536


@jax.jit
def take_rows(x, idx):
    """``x[idx]`` as ONE compiled program. Eager fancy indexing expands
    to ~11 tiny op-by-op programs (convert/broadcast/gather/...), each
    its own compile — cold build time is compile-count-bound.

    Precondition: every ``idx`` entry must be in ``[0, len(x))``. The
    gather under jit CLAMPS out-of-bounds indices silently (XLA
    semantics), unlike an eager ``x[idx]`` on some backends — callers
    that compute indices host-side should validate before calling."""
    return x[idx]


def sample_rows_np(n: int, m: int, seed: int) -> np.ndarray:
    """Host-side variant of :func:`sample_rows`'s large-``n`` path:
    ``m`` distinct sorted indices in ``[0, n)`` as a numpy int32 array
    (same rng stream — ``default_rng(seed).choice``), for callers that
    keep the indices on host (padding/glue before a jitted gather)."""
    idx = np.random.default_rng(seed).choice(n, size=m, replace=False)
    idx.sort()
    return idx.astype(np.int32)


def sample_rows(n: int, m: int, seed: int) -> jnp.ndarray:
    """``m`` distinct indices in ``[0, n)``. Small ``n`` draws the
    traced ``jax.random.choice`` stream (identical to prior versions);
    large ``n`` draws host-side with numpy and returns sorted indices
    (sorted gathers are friendlier to HBM prefetch). Returns a device
    int32 array."""
    if n <= _TRACED_MAX_N:
        idx = jax.random.choice(jax.random.key(seed), n, (m,),
                                replace=False)
        return idx.astype(jnp.int32)
    # int32 cast on HOST: jnp.asarray(idx, int32) of an int64 numpy
    # array compiles a convert_element_type program per shape — one
    # compile per call site for a cast numpy does for free
    return jnp.asarray(sample_rows_np(n, m, seed))
