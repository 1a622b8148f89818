"""Staged IVF-Flat/PQ build profile on the real chip: compile vs compute.

Round-2 measured 97 s for a cold 500k×128×1024-list IVF-Flat build and
attributed it to EM arithmetic — but the arithmetic (20 iters of
262k×1024×128 fused-argmin ≈ 1.4 TFLOP at bf16x3) is sub-second-class on
v5e. The plausible dominators are (a) first compiles of the
Pallas fused_l2_nn shapes and
(b) the eager dispatch chain. This profiler separates them: every stage
is timed cold (first call = compile + run) and warm (second call).

Run: python tools/profile_ivf_build.py
Env: PROFILE_PLATFORM=cpu + PROFILE_N/PROFILE_NLISTS for a harness
smoke at toy shapes (the campaign pre-flight).
"""
import os
import time

import jax

if os.environ.get("PROFILE_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["PROFILE_PLATFORM"])
import jax.numpy as jnp

from raft_tpu.core.compile_cache import enable as _enable_cache
_enable_cache()
print(jax.devices())

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.neighbors import ivf_flat, ivf_pq

key = jax.random.key(0)
n = int(os.environ.get("PROFILE_N", 500_000))
nlists = int(os.environ.get("PROFILE_NLISTS", 1024))
d = 128
db = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
jax.block_until_ready(db)


def stage(name, fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    warm = time.perf_counter() - t0
    print(f"{name}: cold {cold:.2f} s, warm {warm:.3f} s")
    return out


# stage 1: trainset subsample — host-side draw + device gather, the
# path the library now takes (util.host_sample; the old traced
# choice(replace=False) was a minutes-long n-wide-sort compile)
from raft_tpu.util.host_sample import sample_rows
trainset = stage("subsample",
                 lambda: db[sample_rows(n, min(n, max(nlists, n // 2)),
                                        0)])

# stage 2: balanced EM on the trainset (the hierarchical trainer's flat
# path at n_lists ≤ 16384)
centers = stage("EM train (20 iters)", lambda: kmeans_balanced.
                build_hierarchical(trainset, nlists, 20))

# stage 2b: the bf16 single-pass tier — candidate trainer default if the
# speedup holds; compare center quality via downstream recall before
# switching
stage("EM train (20 iters, bf16 tier)",
      lambda: kmeans_balanced.balanced_kmeans(trainset, nlists, 20,
                                              kernel_precision="bf16"))

# stage 3: full-dataset predict (a second fused_l2_nn shape → compile)
labels = stage("predict full", lambda: kmeans_balanced.predict(db, centers))

# stage 4: bucketize (argsort + scatter, now one jit)
stage("bucketize", lambda: ivf_flat._bucketize(db, labels, nlists)[0])

# end to end, cold index vs warm kernels
t0 = time.perf_counter()
idx = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists))
jax.block_until_ready(idx.lists_data)
print(f"ivf_flat.build e2e (warm kernels): {time.perf_counter()-t0:.2f} s")

t0 = time.perf_counter()
pq = ivf_pq.build(db, ivf_pq.IndexParams(n_lists=nlists))
jax.block_until_ready(pq.codes)
print(f"ivf_pq.build e2e: {time.perf_counter()-t0:.2f} s")
