"""Fixed-cost attribution for the fused IVF-Flat search.

The round-4 window showed search time nearly FLAT across a 10x size
difference — a fixed cost dominates, not the scan (the last green TPU
run: IVF-Flat 9,769 QPS end-to-end vs 73,781 QPS chained marginal, a
~9 ms/batch fixed cost). This tool gives that cost a name, per stage:

* ``coarse``  — coarse GEMM + top-k probes (chained marginal)
* ``cap``     — ``resolve_cap`` measurement round-trip (per call,
  includes the device sync; the stage a warmed plan eliminates)
* ``invert``  — probe inversion (argsort + scatter)
* ``gather``  — query gather through the inverted table
* ``scan_merge`` — fused-search marginal minus the three device
  stages above: the list scan + candidate merge residue
* ``host_dispatch`` — per-call wall minus the in-jit marginal: Python
  routing, dispatch, and transport — the serving fixed cost

Each stage runs under an ``obs.timed`` scope named
``raft.profile.<stage>`` so the walls land in the metrics registry
alongside the trace ranges, and the
whole breakdown is written as a JSON artifact (default
``chiprun_out/ivf_pieces_<platform>.json``, override via
``PROFILE_OUT``) together with a serving comparison:

* cold per-call path (``probe_cap=-1``: re-measure every batch — the
  dispatch-sync-dispatch loop),
* warm cap-cache path (default ``probe_cap=0`` after one search),
* warm AOT plan (``neighbors/plan.py``), and the derived
  ``fixed_cost_ms`` / plan-vs-cold speedup.

Run: python tools/profile_ivf_pieces.py
Env: PROFILE_PLATFORM=cpu for harness smoke; PROFILE_N/NQ/NLISTS/
NPROBES/CHAIN as profile_ivf_fused; PROFILE_OUT for the artifact path.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("PROFILE_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["PROFILE_PLATFORM"])
from raft_tpu.core.compile_cache import enable as _enable_cache
_enable_cache()
print(jax.devices(), flush=True)

from raft_tpu import obs
from raft_tpu.neighbors import ivf_flat
from raft_tpu.neighbors import plan as plan_mod
from raft_tpu.neighbors import _ivf_scan as S
from raft_tpu.ops.dispatch import pallas_enabled

key = jax.random.key(0)
n = int(os.environ.get("PROFILE_N", 500_000))
d, nq = 128, int(os.environ.get("PROFILE_NQ", 1000))
k = 32
nlists = int(os.environ.get("PROFILE_NLISTS", 1024))
nprobes = int(os.environ.get("PROFILE_NPROBES", 64))
CHAIN = int(os.environ.get("PROFILE_CHAIN", 8))
# the BENCH distribution (bench_suite._ann_dataset, clustered): query
# skew is what separates the serving policies — on it the drop-free
# cap the cold (-1) path re-measures every batch runs ~2× the bounded
# serving cap (512 vs 256 observed at this point, 2026-08-02), so the
# cold path scans twice the table width AND pays a sync per call
import bench_suite
db, q0 = bench_suite._ann_dataset(n, d, nq)
qs = jnp.concatenate(
    [q0[None],
     bench_suite._chained_batches(q0, key, CHAIN - 1)], axis=0)
jax.block_until_ready((db, qs))

idx = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists,
                                              kmeans_n_iters=10))
jax.block_until_ready(idx.lists_data)
max_list = idx.lists_data.shape[1]
use_pallas = pallas_enabled()

probes0 = S.coarse_probes(q0, idx.centers, nprobes,
                          use_pallas=use_pallas)
# the SERVING cap (probe_cap=0 policy incl. the RAFT_TPU_AUTO_CAP_MAX
# ceiling), cached on the index so the warm searches below reuse it —
# profiling the unbounded drop-free cap would attribute scan work the
# serving path never does
cap = S.resolve_cap(idx.cap_cache, q0, idx.centers,
                    ivf_flat.SearchParams(n_probes=nprobes), nprobes,
                    nlists, use_pallas=use_pallas)
print(f"n={n} nlists={nlists} nprobes={nprobes} cap={cap} "
      f"max_list={max_list} pallas={use_pallas}", flush=True)

# ---------------------------------------------------------------------------
# serving comparison FIRST, on a fresh process state (measured 2026-08-04:
# the big chained stage programs below perturb later wall measurements
# by ~2× in-process — the comparison must not inherit that): cold
# per-call (probe_cap=-1, re-measure every batch) vs warm cap-cache vs
# warm AOT plan — per-call WALL including dispatch
# ---------------------------------------------------------------------------
sp = ivf_flat.SearchParams(n_probes=nprobes)
sp_cold = ivf_flat.SearchParams(n_probes=nprobes, probe_cap=-1)


def percall(tag, fn):
    fn(qs[0])  # warm/compile
    best = np.inf
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(CHAIN):
            out = fn(qs[i])
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / CHAIN)
    print(f"{tag:24s}: {best*1e3:7.2f} ms/call "
          f"({nq/best:,.0f} QPS)", flush=True)
    return best


t_cold = percall("search cold (cap=-1)",
                 lambda qb: ivf_flat.search(idx, qb, k, sp_cold))
t_warm = percall("search warm cap-cache",
                 lambda qb: ivf_flat.search(idx, qb, k, sp))
pl = plan_mod.warmup(idx, q0, k, sp)
t_plan = percall("plan.search (AOT)", lambda qb: pl.search(qb))

stages_ms = {}


def _best_of(run, *args, reps=3, per=CHAIN):
    jax.block_until_ready(run(*args))
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, (time.perf_counter() - t0) / per)
    return best


def marginal(tag, fn, *captures):
    """Chained marginal of one piece, recorded under
    ``raft.profile.<tag>`` (obs.timed: histogram + trace range)."""
    @jax.jit
    def run(qb, *cap_):
        acc = jnp.zeros((), jnp.float32)
        for i in range(CHAIN):
            out = fn(qb[i], *cap_)
            leaf = jax.tree.leaves(out)[0]
            # full-output sum (scaled to stay finite): consuming one
            # element lets XLA slice the whole piece away (the gather
            # stage measured 0.00 ms through a [0,0] probe on CPU)
            acc += jnp.sum(leaf.astype(jnp.float32)) * 1e-30
        return acc
    stage_name = "raft.profile." + tag  # non-literal: per-stage series
    with obs.timed(stage_name):
        best = _best_of(run, qs, *captures)
    stages_ms[tag] = best * 1e3
    print(f"{tag:24s}: {best*1e3:7.2f} ms/call", flush=True)
    return best


# 1. the whole fused device program as a chained marginal — measured
#    FIRST so the fixed-cost anchor shares the serving section's
#    process state; scan+merge is its residue over the later stages
scale = jnp.float32(idx.scale)
lc = 0
if use_pallas:
    from raft_tpu.ops.pallas_ivf_scan import lc_mode
    lc = lc_mode()


def fused_piece(qb, centers, data, norms, ids):
    return S.fused_list_search(qb, centers, data, norms, ids, scale,
                               k=k, n_probes=nprobes, cap=cap, bins=0,
                               sqrt=False, kind="l2",
                               use_pallas=use_pallas,
                               gather=S.gather_mode(), lc=lc)


t_fused = marginal("fused_total", fused_piece, idx.centers,
                   idx.lists_data, idx.lists_norms, idx.lists_indices)

# 2. coarse GEMM + top-k probes
marginal("coarse",
         lambda qb, c: S.coarse_probes(qb, c, nprobes,
                                       use_pallas=use_pallas),
         idx.centers)

# 3. the resolve_cap measurement round-trip — a PER-CALL stage (its
#    cost is the sync, which a chain cannot amortize); probe_cap=-1
#    forces the re-measure every call, exactly the cold serving path
with obs.timed("raft.profile.cap"):
    t_cap = _best_of(
        lambda: S.resolve_cap(None, q0, idx.centers, sp_cold, nprobes,
                              nlists, use_pallas=use_pallas),
        per=1)
stages_ms["cap"] = t_cap * 1e3
print(f"{'cap':24s}: {t_cap*1e3:7.2f} ms/call", flush=True)

# 4. probe inversion (argsort + scatter) on fixed probes per link
probes_c = jnp.stack([
    S.coarse_probes(qs[i], idx.centers, nprobes, use_pallas=use_pallas)
    for i in range(CHAIN)])
jax.block_until_ready(probes_c)


@jax.jit
def run_inv(pc):
    acc = jnp.zeros((), jnp.float32)
    for i in range(CHAIN):
        qmap, inv_pos = S._invert_probes(pc[i], nlists, cap)
        acc += qmap.reshape(-1)[0].astype(jnp.float32)
        acc += inv_pos.reshape(-1)[0].astype(jnp.float32)
    return acc


with obs.timed("raft.profile.invert"):
    best = _best_of(run_inv, probes_c)
stages_ms["invert"] = best * 1e3
print(f"{'invert':24s}: {best*1e3:7.2f} ms/call", flush=True)

# 5. query gather through the inverted table
qmap0, inv_pos0 = jax.jit(
    lambda p: S._invert_probes(p, nlists, cap))(probes0)
jax.block_until_ready((qmap0, inv_pos0))
marginal("gather",
         lambda qb, qm: S.gather_query_rows(qb, qm), qmap0)

stages_ms["scan_merge"] = max(
    0.0, stages_ms["fused_total"] - stages_ms["coarse"]
    - stages_ms["invert"] - stages_ms["gather"])
print(f"{'scan_merge (residue)':24s}: {stages_ms['scan_merge']:7.2f} "
      f"ms/call", flush=True)

stages_ms["host_dispatch"] = max(0.0,
                                 (t_warm - t_fused) * 1e3)
obs.gauge("raft.profile.host_dispatch_ms").set(stages_ms["host_dispatch"])
print(f"{'host_dispatch (residue)':24s}: "
      f"{stages_ms['host_dispatch']:7.2f} ms/call", flush=True)

serving = {
    "cold_percall_ms": round(t_cold * 1e3, 3),
    "warm_percall_ms": round(t_warm * 1e3, 3),
    "plan_percall_ms": round(t_plan * 1e3, 3),
    "marginal_ms": round(t_fused * 1e3, 3),
    "cold_qps": round(nq / t_cold, 1),
    "warm_qps": round(nq / t_warm, 1),
    "plan_qps": round(nq / t_plan, 1),
    "marginal_qps": round(nq / t_fused, 1),
    # the issue's definition, per batch: 1/qps − 1/marginal_qps
    "fixed_cost_ms": round((t_plan - t_fused) * 1e3, 3),
    "fixed_cost_cold_ms": round((t_cold - t_fused) * 1e3, 3),
    "plan_speedup_vs_cold": round(t_cold / t_plan, 3),
}

artifact = {
    "tool": "profile_ivf_pieces",
    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    "platform": jax.devices()[0].platform,
    "shape": {"n": n, "dim": d, "nq": nq, "k": k, "n_lists": nlists,
              "n_probes": nprobes, "cap": cap, "max_list": max_list,
              "pallas": use_pallas, "chain": CHAIN},
    "stages_ms": {s: round(v, 3) for s, v in stages_ms.items()},
    "serving": serving,
}
here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
out_path = os.environ.get("PROFILE_OUT") or os.path.join(
    here, "chiprun_out",
    f"ivf_pieces_{jax.devices()[0].platform}.json")
os.makedirs(os.path.dirname(out_path), exist_ok=True)
with open(out_path, "w") as f:
    json.dump(artifact, f, indent=1)
print(json.dumps(serving), flush=True)
print(f"artifact -> {out_path}", flush=True)
