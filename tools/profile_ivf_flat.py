"""Staged IVF-Flat profile on the real chip: which phase eats the time?

Run: python tools/profile_ivf_flat.py
"""
import time
import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.compile_cache import enable as _enable_cache
_enable_cache()
print(jax.devices())

from raft_tpu.neighbors import ivf_flat
from raft_tpu.neighbors import _ivf_scan
from raft_tpu.ops import pallas_ivf_scan as pis

key = jax.random.key(0)
n, d, nq, k, nlists, nprobes = 500_000, 128, 1000, 32, 1024, 64
db = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
q = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

t0 = time.perf_counter()
idx = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists,
                                              kmeans_n_iters=10))
jax.block_until_ready(idx.lists_data)
print("build", round(time.perf_counter() - t0, 1), "s; max_list",
      idx.lists_data.shape[1])


def timed(fn, reps=6):
    o = fn()
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    outs = [fn() for _ in range(reps)]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / reps


# end to end
sp = ivf_flat.SearchParams(n_probes=nprobes)
t = timed(lambda: ivf_flat.search(idx, q, k, sp))
print(f"search e2e: {t*1000:.1f} ms -> {nq/t:.0f} QPS")

# stage 1: coarse probes — time the path the serving search runs
# (Pallas select_k on TPU) plus the lax.top_k variant for comparison
from raft_tpu.ops.dispatch import pallas_enabled
up = pallas_enabled()
probes = _ivf_scan.coarse_probes(q, idx.centers, nprobes, use_pallas=up)
t = timed(lambda: _ivf_scan.coarse_probes(q, idx.centers, nprobes,
                                          use_pallas=up))
print(f"coarse[pallas={up}]: {t*1000:.1f} ms")
if up:
    t = timed(lambda: _ivf_scan.coarse_probes(q, idx.centers, nprobes))
    print(f"coarse[top_k]: {t*1000:.1f} ms")
cap = _ivf_scan.probe_cap(probes, nlists)
print("cap:", cap)

max_list = idx.lists_data.shape[1]
lay = pis._Layout(probes, nlists, max_list, cap, 0, k)
data, norms, ids = idx.lists_data, idx.lists_norms, idx.lists_indices
qmap = lay.padded_qmap()

# stage 2: qsub gather — honors RAFT_TPU_GATHER (rows|onehot) so the
# A/B actually measures both strategies
f_gather = jax.jit(lambda qq: _ivf_scan.gather_query_rows(qq, qmap))
t = timed(lambda: f_gather(q))
import os
print(f"qsub gather[{os.environ.get('RAFT_TPU_GATHER', 'rows')}] "
      f"({nlists}x{lay.capp}x{d}): {t*1000:.1f} ms")
qsub = f_gather(q)

# stage 3: kernel
lc = pis._pick_lc(nlists, max_list, lay.capp, d, 4, tail=lay.tail)
print("lc:", lc, "bins:", lay.bins, "max_list:", max_list)
t = timed(lambda: pis._list_scan_call(qsub, data, norms, ids, lay.bins, lc,
                                      1.0, False))
print(f"list-scan kernel: {t*1000:.1f} ms")
cd, ci = pis._list_scan_call(qsub, data, norms, ids, lay.bins, lc, 1.0,
                             False)

# stage 4: merge
t = timed(lambda: lay.merge(cd, ci, probes, k, False))
print(f"merge: {t*1000:.1f} ms")

# full-probe brute force comparison for context
from raft_tpu.neighbors import brute_force
t = timed(lambda: brute_force.brute_force_knn(db, q, k, mode="fused"))
print(f"fused brute force: {t*1000:.1f} ms -> {nq/t:.0f} QPS")
