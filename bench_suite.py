#!/usr/bin/env python
"""Extended benchmark table mirroring the reference's gbench suite
(SURVEY.md §6: cpp/bench/{distance,neighbors,cluster,linalg,random,
sparse}). Each case reports wall-time (and a domain rate) as a dict;
run as a script to print one JSON line per case.

Sync note: timings fetch a scalar from each result — a host transfer
of every leaf, which waits for the device like ``block_until_ready``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def _sync(tree):
    import jax
    for leaf in jax.tree.leaves(tree):
        np.asarray(leaf.ravel()[:1])


def _time(fn, reps=5):
    _sync(fn())  # warm/compile
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    _sync(out)
    return (time.perf_counter() - t0) / reps


def bench_pairwise_distance(results):
    # cpp/bench/distance/distance_common.cuh:72-87 — 16384² blocks
    import jax
    import jax.numpy as jnp
    from jax import lax
    from raft_tpu.distance.pairwise import _pairwise
    from raft_tpu.distance.distance_types import DistanceType
    key = jax.random.key(0)
    m = n = 8192
    reps = _chain_reps()
    for d in (64, 256):
        x = jax.random.normal(jax.random.fold_in(key, 1), (m, d))
        y = jax.random.normal(jax.random.fold_in(key, 2), (n, d))
        for metric in (DistanceType.L2Expanded, DistanceType.CosineExpanded,
                       DistanceType.L1):
            t = _time(lambda: _pairwise(x, y, metric, 2.0))

            # marginal in-jit time (round-2 verdict: per-call wall on a
            # dispatch-billed transport is not kernel time). The full
            # (m, n) output is consumed by a sum so XLA materializes the
            # whole matrix each rep (the extra reduce pass is ~1% of the
            # matmul cost at these shapes and is part of the accounting)
            @jax.jit
            def chained(xx, yy, met=metric):
                def body(i, acc):
                    dd = _pairwise(xx + 0.0 * acc, yy, met, 2.0)
                    return acc + jnp.sum(dd) * 1e-30
                return lax.fori_loop(0, reps, body, jnp.float32(0))

            t_marg = _time(lambda: chained(x, y), reps=2) / reps
            results.append({
                "metric": f"pairwise_{metric.name}_{m}x{n}x{d}_ms",
                "value": round(t * 1e3, 3), "unit": "ms",
                "rate": round(2 * m * n * d / t / 1e9, 1),
                "rate_unit": "GFLOP/s",
                "marginal_ms": round(t_marg * 1e3, 3),
                "marginal_rate_gflops": round(2 * m * n * d / t_marg / 1e9,
                                              1)})


def bench_fused_l2_nn(results):
    # cpp/bench/neighbors/fused_l2_nn.cu
    import jax
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn
    key = jax.random.key(1)
    m, n, d = 100_000, 1024, 128
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    y = jax.random.normal(jax.random.fold_in(key, 2), (n, d))
    t = _time(lambda: tuple(fused_l2_nn(x, y)))
    results.append({
        "metric": f"fused_l2_nn_{m//1000}kx{n}x{d}_ms",
        "value": round(t * 1e3, 3), "unit": "ms",
        "rate": round(2 * m * n * d / t / 1e9, 1), "rate_unit": "GFLOP/s"})


def bench_select_k(results):
    # cpp/bench/neighbors/selection.cu
    import functools
    import jax
    from jax import lax
    from raft_tpu.neighbors.selection import select_k
    key = jax.random.key(2)
    v = jax.random.normal(key, (1000, 4096))
    for k in (32, 256):
        t = _time(lambda: select_k(v, k))
        # marginal in-jit time: chain dependent selections in ONE
        # dispatch — per-dispatch host latency is not kernel time
        # (same methodology as bench.py's chained search)
        reps = 20

        @functools.partial(jax.jit, static_argnames=("kk",))
        def chained(vv, kk):
            def body(_, carry):
                vv_, acc = carry
                d, _i = select_k(vv_, kk)
                s = d[0, 0]
                return vv_ + 0.0 * s, acc + s
            return lax.fori_loop(0, reps, body, (vv, 0.0))[1]

        t_marg = _time(lambda: chained(v, k), reps=2) / reps
        results.append({
            "metric": f"select_k_1000x4096_k{k}_ms",
            "value": round(t * 1e3, 3), "unit": "ms",
            "marginal_ms": round(t_marg * 1e3, 3)})


def bench_kmeans(results):
    # cpp/bench/cluster/kmeans.cu — 1M points
    import jax
    from raft_tpu.cluster.kmeans import fit as kmeans_fit
    from raft_tpu.cluster.kmeans_types import KMeansParams, InitMethod
    key = jax.random.key(3)
    n, d, k = 500_000, 64, 256
    x = jax.random.normal(key, (n, d))
    params = KMeansParams(n_clusters=k, max_iter=5,
                          init=InitMethod.Random, seed=0)
    t = _time(lambda: tuple(kmeans_fit(x, params)), reps=2)
    results.append({
        "metric": f"kmeans_{n//1000}kx{d}_k{k}_5iter_ms",
        "value": round(t * 1e3, 1), "unit": "ms"})


def _chain_reps() -> int:
    """Chained-measurement length: 8 on real TPU (amortizes dispatch),
    2 elsewhere — an 8×-unrolled search chain is a minutes-long compile
    on the single-core degraded CPU path and could eat the bench child's
    budget for no extra information."""
    import jax
    return 8 if jax.default_backend() == "tpu" else 2


def _recall_vs(i_got, i_exact, k):
    """Recall of ``i_got`` against a given exact id table."""
    f, e = np.asarray(i_got), np.asarray(i_exact)
    return float(np.mean([len(set(f[r][:k]) & set(e[r][:k])) / k
                          for r in range(len(f))]))


def _ivf_recall(i_got, db, q, k):
    """Recall vs the exact scan (reference eval_neighbours role,
    cpp/test/neighbors/ann_utils.cuh:201)."""
    from raft_tpu.neighbors.brute_force import brute_force_knn
    _, i_e = brute_force_knn(db, q, k, mode="exact")
    return _recall_vs(i_got, i_e, k)


def _chained_search_time(search_fn, q_batches, reps, *operands):
    """Marginal in-jit per-search time: ``reps`` searches over distinct
    query batches chained in ONE dispatch (the gbench stream-of-kernels
    methodology; per-dispatch host latency is not kernel time).
    ``operands`` (index arrays etc.) ride as jit arguments so they are
    device parameters, not giant baked-in constants."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(qs, *ops):
        acc = jnp.zeros((), jnp.float32)
        for j in range(reps):
            dj, ij = search_fn(qs[j], *ops)
            acc = acc + dj[0, 0] + ij[0, 0].astype(jnp.float32)
        return acc

    return _time(lambda: chain(q_batches, *operands), reps=2) / reps



def _cached_cap(index, nq: int, n_probes: int) -> int:
    """The probe cap the warm search measured and cached — keyed by the
    active kernel tier (resolve_cap's cache key)."""
    from raft_tpu.ops.dispatch import pallas_enabled
    return index.cap_cache[(nq, n_probes, pallas_enabled())]

def _resource_utilization(dispatch_fn, seconds=0.5, extra_fn=None):
    """Resource-utilization keys for a bench row (ISSUE 14): run
    blocked dispatches in a tight loop for ``seconds`` under the
    resource profiler at sample rate 1.0 and read back the measured
    duty cycle (``device_util`` — the fraction of wall the device was
    actually executing at this operating point; the rest is host
    dispatch/glue) and the peak device memory the pass saw
    (``hbm_peak_mb``; the live-arrays approximation on CPU). The pass
    runs AFTER the row's timed measurements so the profiled loop never
    perturbs the headline figures."""
    from raft_tpu.obs import profiler
    profiler.enable_profiling(
        1.0, profiler.ProfilerConfig(hbm_poll_ms=100.0,
                                     window_s=max(4 * seconds, 5.0)))
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            dispatch_fn()
        time.sleep(0.15)        # let >= 1 HBM sample land
        rep = profiler.report()
        hbm_peak = max((dev.get("peak_bytes", 0) or 0
                        for dev in rep["hbm"].values()), default=0)
        out = {
            "device_util": rep["duty_cycle"],
            "hbm_peak_mb": round(hbm_peak / 2 ** 20, 2),
        }
        if extra_fn is not None:
            # caller-side keys that must be read WHILE the profiler is
            # still attached (e.g. the fleet's per-replica fold)
            out.update(extra_fn())
        return out
    except Exception as e:      # a profiling hiccup must not void a row
        return {"device_util": None, "hbm_peak_mb": None,
                "profile_error": repr(e)[:120]}
    finally:
        profiler.disable_profiling()


def _ann_dataset(n, d, nq, seed=5):
    """Semi-hard clustered ANN bench distribution: a gaussian mixture
    with unit-scale centers AND unit cluster noise (~125 rows/cluster),
    queries drawn from the same mixture.

    Why not plain gaussian noise: IVF recall on UNIFORM high-dim
    random data is ceiling-limited by the partition itself — measured
    2026-08-01, the exact-fine-phase probe ceiling at the bench probe
    ratio (1/16 of lists) is ~0.35–0.5 on uniform 100k–10M×128, and
    even probing 25% of 1024 lists at 10M×128 caps at 0.893. No IVF —
    the reference's included — can beat its partition's ceiling, which
    is why the reference's ANN evidence uses clustered corpora
    (SIFT-class) too. This mixture measures 0.9731 flat ceiling at
    16/256 probes on 100k×128 (center scale 1.0; scale 2.0 is
    trivially separable at 1.000, scale 0.7 drops to 0.77): recall
    curves are meaningful, not saturated."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(seed)
    nc = max(64, min(8192, n // 125))
    centers = jax.random.normal(jax.random.fold_in(key, 1), (nc, d))

    @jax.jit
    def mix(c, lab_c, key_c):
        # fused gather+noise+add: one materialized chunk
        return c[lab_c] + jax.random.normal(key_c,
                                            (lab_c.shape[0], c.shape[1]))

    # chunked so peak transient memory stays ~2× the dataset (the
    # 10M-row call sites would otherwise hold gather+noise+sum at once)
    step = max(1, min(n, 1 << 20))
    lab = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, nc)
    parts = [mix(centers, lab[s:s + step],
                 jax.random.fold_in(key, 100 + s // step))
             for s in range(0, n, step)]
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    del parts
    qlab = jax.random.randint(jax.random.fold_in(key, 4), (nq,), 0, nc)
    q = mix(centers, qlab, jax.random.fold_in(key, 5))
    return x, q


def _chained_batches(q, key, reps):
    """Timing-only chained query batches: jittered copies of the
    measured queries so the chain stays in-distribution (the pinned
    probe_cap came from ``q``; far-out-of-distribution batches would
    shed probes)."""
    import jax
    nq, d = q.shape
    return q[None] + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 9), (reps, nq, d))


# Headline IVF operating points (probes). The flat row's point must
# clear its own 0.90 recall gate: the 64/1024-probe point measured
# 0.882 on TPU (round 4) against a ~0.88 partition ceiling, so the
# flat default moves to 96 — the first rung of the f1b probes sweep
# (96/128), predicted ≥0.90 from the coverage curves. Env-overridable
# so the measurement campaign can move the point the moment the sweep
# says otherwise; gates derive their metric names from the SAME
# constants so a moved point is still gated (never unmeasured).
FLAT_PROBES = int(os.environ.get("BENCH_IVF_PROBES_FLAT", 96))
IVF_PROBES = int(os.environ.get("BENCH_IVF_PROBES", 64))


def bench_ivf_flat(results, n=500_000, nlists=1024, n_probes=None,
                   label=None, storage_dtype="float32"):
    # cpp/bench/neighbors/knn/ivf_flat_*.cu — SEARCH scope (+BUILD:
    # cold = first build incl. compiles; warm = steady-state rebuild,
    # the gbench BUILD-scope iteration analogue)
    import dataclasses
    import jax
    from raft_tpu.neighbors import ivf_flat
    if n_probes is None:
        n_probes = FLAT_PROBES
    key = jax.random.key(4)
    d, nq, k = 128, 1000, 32
    db, q = _ann_dataset(n, d, nq)
    # kmeans_n_iters=10 vs the parity default 20: measured downstream-
    # recall-neutral for IVF-Flat (an earlier A/B) and ~2×
    # build; the row reports its own recall so the trade is visible
    params = ivf_flat.IndexParams(n_lists=nlists, kmeans_n_iters=10,
                                  storage_dtype=storage_dtype)
    t_build0 = time.perf_counter()
    index = ivf_flat.build(db, params)
    _sync(index.centers)
    t_build = time.perf_counter() - t_build0
    t_build0 = time.perf_counter()
    index = ivf_flat.build(db, params)
    _sync(index.centers)
    t_build_warm = time.perf_counter() - t_build0
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    d_f, i_f = ivf_flat.search(index, q, k, sp)  # warm + measure cap
    rec = _ivf_recall(i_f, db, q, k)
    t = _time(lambda: ivf_flat.search(index, q, k, sp), reps=3)
    # chained marginal: pin the measured cap so nothing syncs in-jit
    spp = dataclasses.replace(sp, probe_cap=_cached_cap(index, nq, n_probes))
    reps = _chain_reps()
    qb = _chained_batches(q, key, reps)

    def run1(qq, centers, data, norms, idsarr, sizes):
        idx2 = ivf_flat.Index(
            centers=centers, lists_data=data, lists_indices=idsarr,
            lists_norms=norms, list_sizes=sizes, metric=index.metric,
            size=index.size, scale=index.scale)
        return ivf_flat.search(idx2, qq, k, spp)

    t_marg = _chained_search_time(
        run1, qb, reps, index.centers, index.lists_data,
        index.lists_norms, index.lists_indices, index.list_sizes)
    # warm-plan serving point (neighbors/plan.py): the AOT executable
    # fed per-call — what the fixed cost shrinks to once dispatch is
    # enqueue-only. fixed_cost_ms = per-batch wall minus the chained
    # in-jit marginal: the host/dispatch overhead the plan layer (and
    # the next TPU window) must erase.
    from raft_tpu.neighbors import plan as _plan
    pl = _plan.warmup(index, q, k, sp)
    t_plan = _time(lambda: pl.search(q), reps=3)
    # resource-utilization pass (ISSUE 14): AFTER the timed figures
    util = _resource_utilization(lambda: pl.search(q, block=True))
    results.append({
        "metric": (label or
                   f"ivf_flat_search_{n//1000}kx{d}_q{nq}_k{k}"
                   f"_p{n_probes}_qps"),
        "value": round(nq / t, 1), "unit": "queries/s",
        "recall": round(rec, 4),
        "marginal_qps": round(nq / t_marg, 1),
        "plan_qps": round(nq / t_plan, 1),
        # ROADMAP item 2's gap as a first-class regression signal:
        # marginal QPS / warm-plan QPS (= t_plan / t_marg). 1.0 = the
        # serving path reaches the kernels' full rate; the last green
        # TPU round sat at ~7x. Gated ≤ 2.0 at the flat 100k point
        # (GAP_GATES below).
        "marginal_gap": round(t_plan / t_marg, 3),
        "fixed_cost_ms": round((t - t_marg) * 1e3, 3),
        "build_s": round(t_build, 2),
        "build_warm_s": round(t_build_warm, 2),
        **util})


def bench_ivf_pq(results, n=500_000, nlists=1024, n_probes=None,
                 label=None, pq_bits=8, pq_dim=0):
    import dataclasses
    import jax
    from raft_tpu.neighbors import ivf_pq
    if n_probes is None:
        n_probes = IVF_PROBES
    key = jax.random.key(5)
    d, nq, k = 128, 1000, 32
    db, q = _ann_dataset(n, d, nq)
    # 10 EM iters: ~0.3% recall cost on random data (the bench
    # distribution; ~1% on clustered), recall rides
    # in the row. keep_raw + rescore_factor: the headline row reports
    # the REFINED operating point (VERDICT r3 #4 — an unrescored PQ
    # estimator rides at ~0.5 recall at this bench point, which is not
    # a competitive index); wall QPS includes the host rescore, the
    # chained marginal isolates the jitted device phase (same kk).
    params = ivf_pq.IndexParams(n_lists=nlists, kmeans_n_iters=10,
                                keep_raw=True, pq_bits=pq_bits,
                                pq_dim=pq_dim)
    t_build0 = time.perf_counter()
    index = ivf_pq.build(db, params)
    _sync(index.centers)
    t_build = time.perf_counter() - t_build0
    # factor 8: kk=256 candidates — the merge width is floored at the
    # same 128 bins as factor 4 (the global-pool rule), so the device
    # cost is identical and rescored recall tracks the flat probe
    # ceiling within 1-2% (2026-08-01 CPU A/B: 0.6914 vs 0.7121
    # ceiling at 64/256 probes, 100k x 128)
    sp = ivf_pq.SearchParams(n_probes=n_probes, rescore_factor=8)
    d_f, i_f = ivf_pq.search(index, q, k, sp)  # warm + measure cap
    rec = _ivf_recall(i_f, db, q, k)
    d_e, i_e = ivf_pq.search(  # estimator-only recall, for the record
        index, q, k, dataclasses.replace(sp, rescore_factor=0))
    rec_est = _ivf_recall(i_e, db, q, k)
    # shadow-exact calibration (ISSUE 11 satellite): the SAME exact
    # scorer the online quality monitor replays through produces the
    # ground truth here, so the 0.13+ estimator drift ROADMAP item 5
    # cites is a tracked bench key (recall_estimator_error) instead of
    # folklore — and the scorer itself is cross-validated against the
    # brute-force recall of the row (recall vs recall_shadow_exact)
    from raft_tpu.obs import quality as _quality
    _scorer = _quality.ExactScorer(np.asarray(db), metric=index.metric,
                                   kmax=k, max_rows=n, batch=250)
    i_x = _scorer.topk(np.asarray(q), k)
    rec_shadow = _recall_vs(i_f, i_x, k)
    rec_est_shadow = _recall_vs(i_e, i_x, k)
    t = _time(lambda: ivf_pq.search(index, q, k, sp), reps=3)
    spp = dataclasses.replace(sp, probe_cap=_cached_cap(index, nq, n_probes))
    reps = _chain_reps()
    qb = _chained_batches(q, key, reps)

    # the warm search populated decoded/decoded_norms iff it took the
    # reconstruct path; ride them as operands so the chained trace does
    # NOT fold a whole-database decode into the measured search time
    has_decoded = index.decoded is not None
    extra = ([index.decoded, index.decoded_norms] if has_decoded else [])

    def run1(qq, centers, centers_rot, rot, books, codes, code_norms,
             idsarr, sizes, *dec):
        idx2 = ivf_pq.Index(
            centers=centers, centers_rot=centers_rot,
            rotation_matrix=rot, pq_centers=books, codes=codes,
            lists_indices=idsarr, list_sizes=sizes, metric=index.metric,
            pq_bits=index.pq_bits, size=index.size,
            codebook_kind=index.codebook_kind, code_norms=code_norms,
            decoded=dec[0] if has_decoded else None,
            decoded_norms=dec[1] if has_decoded else None)
        return ivf_pq.search(idx2, qq, k, spp)

    t_marg = _chained_search_time(
        run1, qb, reps, index.centers, index.centers_rot,
        index.rotation_matrix, index.pq_centers, index.codes,
        index.code_norms, index.lists_indices, index.list_sizes, *extra)
    # warm-plan serving point + fixed cost (see bench_ivf_flat)
    from raft_tpu.neighbors import plan as _plan
    pl = _plan.warmup(index, q, k, sp)
    t_plan = _time(lambda: pl.search(q), reps=3)
    # resource-utilization pass (ISSUE 14): AFTER the timed figures
    util = _resource_utilization(lambda: pl.search(q, block=True))
    results.append({
        "metric": (label or
                   f"ivf_pq_search_{n//1000}kx{d}_q{nq}_k{k}"
                   f"_p{n_probes}_qps"),
        "value": round(nq / t, 1), "unit": "queries/s",
        "recall": round(rec, 4),              # rescored (the headline)
        "recall_estimator": round(rec_est, 4),
        "recall_shadow_exact": round(rec_shadow, 4),
        # the calibration key: rescored-vs-estimator recall gap against
        # ONE shared exact ground truth (the online monitor's scorer)
        "recall_estimator_error": round(rec_shadow - rec_est_shadow, 4),
        "rescore_factor": sp.rescore_factor,
        "marginal_qps": round(nq / t_marg, 1),
        "plan_qps": round(nq / t_plan, 1),
        "marginal_gap": round(t_plan / t_marg, 3),  # see bench_ivf_flat
        "fixed_cost_ms": round((t - t_marg) * 1e3, 3),
        "build_s": round(t_build, 2),
        **util})


def bench_ivf_pq4(results, n=500_000, nlists=1024, n_probes=None):
    # the 4-bit tier (reference pq_bits=4..8 axis): C=16 shrinks the
    # one-hot decode matmul's K by 16× — on the block-diagonal
    # formulation that is a direct FLOP/VMEM cut, the expected top-QPS
    # compressed tier on TPU. pq_dim=64 keeps 32 B/vector (same as the
    # 8-bit default at d=128) so the recall comparison is
    # footprint-neutral; rescoring rides as usual.
    if n_probes is None:
        n_probes = IVF_PROBES
    bench_ivf_pq(results, n=n, nlists=nlists, n_probes=n_probes,
                 pq_bits=4, pq_dim=64,
                 label=(f"ivf_pq4_search_{n//1000}kx128_q1000_k32"
                        f"_p{n_probes}_qps"))


def bench_ivf_flat_100k(results, nlists=1024, n_probes=None):
    # the flat 100k point — where profile_ivf_pieces measured the
    # biggest plan-vs-cold ratio (3.17x) and where the marginal_gap
    # gate lives (GAP_GATES): the fused scan+select kernel (ISSUE 7)
    # must hold plan QPS within 2x of the chained marginal here
    if n_probes is None:
        n_probes = FLAT_PROBES
    bench_ivf_flat(
        results, n=100_000, nlists=nlists, n_probes=n_probes,
        label=(f"ivf_flat_search_100kx128_q1000_k32"
               f"_p{n_probes}_qps"))


def bench_ivf_flat_int8(results, n=500_000, nlists=1024, n_probes=None):
    # the reference's int8_t dataset axis (cpp/bench/neighbors/knn/
    # ivf_flat_int8_t_int64_t.cu): narrow list storage quarters the
    # bytes every probe scans; same harness, one knob
    if n_probes is None:
        n_probes = FLAT_PROBES
    bench_ivf_flat(
        results, n=n, nlists=nlists, n_probes=n_probes,
        storage_dtype="int8",
        label=(f"ivf_flat_int8_search_{n//1000}kx128_q1000_k32"
               f"_p{n_probes}_qps"))


def bench_ivf_bq(results, n=500_000, nlists=1024, n_probes=None,
                 label=None):
    # the 1-bit tier (raft_tpu/neighbors/ivf_bq.py): wall QPS includes
    # the host rescore; device_marginal_qps chains the jitted device
    # phase alone (estimator scan), the gbench stream methodology
    import jax
    from raft_tpu.neighbors import ivf_bq
    if n_probes is None:
        n_probes = IVF_PROBES
    key = jax.random.key(12)
    d, nq, k = 128, 1000, 32
    db, q = _ann_dataset(n, d, nq)
    t_build0 = time.perf_counter()
    index = ivf_bq.build(db, ivf_bq.IndexParams(n_lists=nlists,
                                                kmeans_n_iters=10))
    _sync(index.bits)
    t_build = time.perf_counter() - t_build0
    sp = ivf_bq.SearchParams(n_probes=n_probes)
    d_f, i_f = ivf_bq.search(index, q, k, sp)  # warm + measure cap
    rec = _ivf_recall(i_f, db, q, k)
    t = _time(lambda: ivf_bq.search(index, q, k, sp), reps=3)
    # chained device phase: SAME rescore_factor (kk and merge width are
    # shaped by it whether or not raw vectors exist — ivf_bq.search
    # docstring), raw stripped so the chain stays one jitted program,
    # cap pinned so nothing syncs inside the trace
    sp_est = ivf_bq.SearchParams(n_probes=n_probes,
                                 rescore_factor=sp.rescore_factor,
                                 probe_cap=_cached_cap(index, nq, n_probes))
    reps = _chain_reps()
    qb = _chained_batches(q, key, reps)

    def run1(qq, centers, centers_rot, rot, bits, norms2, scales, ids):
        import dataclasses
        idx2 = dataclasses.replace(index, centers=centers,
                                   centers_rot=centers_rot,
                                   rotation_matrix=rot, bits=bits,
                                   norms2=norms2, scales=scales,
                                   lists_indices=ids, raw=None)
        return ivf_bq.search(idx2, qq, k, sp_est)

    t_marg = _chained_search_time(
        run1, qb, reps, index.centers, index.centers_rot,
        index.rotation_matrix, index.bits, index.norms2, index.scales,
        index.lists_indices)
    # warm-plan serving point; the bq fixed cost is wall minus the
    # chained DEVICE marginal, so it includes the rescore epilogue —
    # the plan folds that epilogue on-device when the raw corpus fits
    from raft_tpu.neighbors import plan as _plan
    pl = _plan.warmup(index, q, k, sp)
    t_plan = _time(lambda: pl.search(q), reps=3)
    results.append({
        "metric": (label or
                   f"ivf_bq_search_{n//1000}kx{d}_q{nq}_k{k}"
                   f"_p{n_probes}_qps"),
        "value": round(nq / t, 1), "unit": "queries/s",
        "recall": round(rec, 4),
        "device_marginal_qps": round(nq / t_marg, 1),
        "plan_qps": round(nq / t_plan, 1),
        # bq gap is warm-plan vs chained DEVICE marginal (the rescore
        # epilogue rides in the plan when raw fits on device)
        "marginal_gap": round(t_plan / t_marg, 3),
        "fixed_cost_ms": round((t - t_marg) * 1e3, 3),
        "build_s": round(t_build, 2)})


def bench_sharded_build(results, n=None, nlists=1024):
    """Sharded multi-chip index builds (parallel/ivf sharded_*_build):
    wall seconds per family, built directly into the list-sharded
    serving layout on a data mesh over every local device. On a 1-chip
    host this measures the sharded path's overhead vs ``build_s``; the
    multi-chip TPU rounds are where ``sharded_build_s`` must undercut
    the single-device ``build_s`` (target ≥2x with 4+ chips — ISSUE 4).
    ``BENCH_SHARDED_N`` overrides the row count (the 1M×128 acceptance
    point); ``BENCH_SHARDED_COMPARE=1`` also times the single-device
    build of each family at the same point so the speedup is measured
    same-round, same-process."""
    import time as _time
    import jax
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.parallel import ivf as pivf
    from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
    n = n or int(os.environ.get("BENCH_SHARDED_N", 500_000))
    d = 128
    db, _q = _ann_dataset(n, d, 8)
    mesh = make_mesh()
    n_shards = mesh.shape["data"]
    if nlists % n_shards:
        nlists = max(n_shards, nlists // n_shards * n_shards)
    compare = os.environ.get("BENCH_SHARDED_COMPARE", "") == "1"
    fams = (
        ("ivf_flat",
         lambda: pivf.sharded_ivf_flat_build(
             db, ivf_flat.IndexParams(n_lists=nlists, kmeans_n_iters=10),
             mesh),
         lambda: ivf_flat.build(
             db, ivf_flat.IndexParams(n_lists=nlists, kmeans_n_iters=10)),
         lambda i: i.lists_data),
        ("ivf_pq",
         lambda: pivf.sharded_ivf_pq_build(
             db, ivf_pq.IndexParams(n_lists=nlists, kmeans_n_iters=10),
             mesh),
         lambda: ivf_pq.build(
             db, ivf_pq.IndexParams(n_lists=nlists, kmeans_n_iters=10)),
         lambda i: i.codes),
        ("ivf_bq",
         lambda: pivf.sharded_ivf_bq_build(
             db, ivf_bq.IndexParams(n_lists=nlists, kmeans_n_iters=10,
                                    keep_raw=False),
             mesh),
         lambda: ivf_bq.build(
             db, ivf_bq.IndexParams(n_lists=nlists, kmeans_n_iters=10,
                                    keep_raw=False)),
         lambda i: i.bits),
    )
    for fam, sharded_fn, single_fn, leaf in fams:
        # one try per family (the bench_ivf_* convention): an OOM in one
        # family must not rob the table of the others' rows
        try:
            t0 = _time.perf_counter()
            idx = sharded_fn()
            _sync(leaf(idx))
            t_sh = _time.perf_counter() - t0
            row = {
                "metric": f"{fam}_sharded_build_{n//1000}kx{d}_s",
                "value": round(t_sh, 2), "unit": "s",
                "sharded_build_s": round(t_sh, 2),
                "n_shards": n_shards, "n_lists": nlists,
                "rows_total": int(np.asarray(
                    jax.device_get(idx.list_sizes)).sum()),
            }
            if compare:
                t0 = _time.perf_counter()
                sidx = single_fn()
                _sync(leaf(sidx))
                t_single = _time.perf_counter() - t0
                row["build_s"] = round(t_single, 2)
                row["speedup_vs_single"] = round(t_single / t_sh, 2)
                del sidx
            del idx
            results.append(row)
        except Exception as e:
            results.append({"metric": f"{fam}_sharded_build_{n//1000}kx{d}_s",
                            "error": repr(e)[:200]})


def bench_serve(results, n=500_000, nlists=1024, n_probes=None):
    """Closed-loop serving bench (ISSUE 5): the micro-batching runtime
    (``raft_tpu.serve``) vs per-request ``plan.search`` at the same
    flat operating point. Independent callers each submit ONE query at
    a time; the batcher coalesces them into ladder shapes, so
    ``serve_qps`` must beat ``per_request_qps`` (the acceptance floor
    is 1.5x on the 500k TPU point) at identical recall, with ZERO plan
    compilations in steady state (asserted via the ``raft.plan.cache``
    counters and reported as ``steady_state_compiles``).

    Knobs: ``BENCH_SERVE_CLIENTS`` (closed-loop caller threads, 16),
    ``BENCH_SERVE_SECONDS`` (measure window, 2.0). An open-loop Poisson
    row (``tools/loadgen.py``) rides along at ~70% of the measured
    closed-loop rate — queue-delay/occupancy under an arrival process
    instead of lockstep callers."""
    import threading
    import jax
    from raft_tpu import obs, serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.neighbors import plan as _plan
    if n_probes is None:
        n_probes = FLAT_PROBES
    n_probes = min(n_probes, nlists)
    d, nq_pool, k = 128, 256, 32
    db, q = _ann_dataset(n, d, nq_pool)
    q_np = np.asarray(q)
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists,
                                                    kmeans_n_iters=10))
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 2.0))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 16))

    # per-request baseline: each caller alone on the nq=1 plan — the
    # chip at per-request batch size (what serving looked like before
    # this subsystem)
    p1 = _plan.warmup(index, q_np[:1], k, sp)
    t0 = time.perf_counter()
    done = 0
    while time.perf_counter() - t0 < seconds / 2:
        p1.search(q_np[done % nq_pool:done % nq_pool + 1], block=True)
        done += 1
    per_request_qps = done / (time.perf_counter() - t0)

    cfg = serve.ServeConfig(batch_sizes=(1, 8, 32, 128), max_queue=512,
                            max_wait_ms=2.0)
    server = serve.SearchServer.from_index(index, q_np[:128], k,
                                           params=sp, config=cfg)
    try:
        # recall on the sample set THROUGH the batcher (pad rows and
        # scatter included), vs the per-request plan path
        served_ids = np.concatenate(
            [np.asarray(server.search(q_np[s:s + 1])[1])
             for s in range(nq_pool)])
        rec_serve = _ivf_recall(served_ids, db, q, k)
        rec_plan = _ivf_recall(
            np.concatenate([np.asarray(
                p1.search(q_np[s:s + 1], block=True)[1])
                for s in range(nq_pool)]), db, q, k)

        # closed-loop measurement: `clients` caller threads, one query
        # each, steady state (the warmup above compiled every shape)
        before = obs.snapshot()
        lats, counts = [], []
        stop = time.perf_counter() + seconds
        lock = threading.Lock()

        def client(tid):
            my_lats = []
            i = tid
            while time.perf_counter() < stop:
                t1 = time.perf_counter()
                server.search(q_np[i % nq_pool:i % nq_pool + 1])
                my_lats.append(time.perf_counter() - t1)
                i += clients
            with lock:
                lats.extend(my_lats)
                counts.append(len(my_lats))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})
        compiles = (cnt.get("raft.plan.cache.misses", 0.0)
                    + cnt.get("raft.plan.build.total", 0.0))
        slots = cnt.get("raft.serve.batch.slots", 0.0)
        occupancy = (cnt.get("raft.serve.batch.rows", 0.0) / slots
                     if slots else 0.0)
        serve_qps = sum(counts) / wall
        lats.sort()

        def pct(p):
            return lats[min(len(lats) - 1,
                            int(p / 100 * (len(lats) - 1)))] * 1e3

        # resource-utilization pass (ISSUE 14): the batcher's sampled
        # dispatches split host vs device — was this point host- or
        # device-bound?
        util = _resource_utilization(
            lambda: server.search(q_np[:1]))
        results.append({
            "metric": f"serve_closed_loop_{n//1000}kx{d}_q1_k{k}"
                      f"_p{n_probes}_qps",
            "value": round(serve_qps, 1), "unit": "queries/s",
            "serve_qps": round(serve_qps, 1),
            "per_request_qps": round(per_request_qps, 1),
            "speedup_vs_per_request": round(
                serve_qps / per_request_qps, 2) if per_request_qps
            else None,
            "serve_p50_ms": round(pct(50), 3),
            "serve_p99_ms": round(pct(99), 3),
            "batch_occupancy": round(occupancy, 4),
            "steady_state_compiles": int(compiles),
            "clients": clients,
            "recall": round(rec_serve, 4),
            "recall_per_request": round(rec_plan, 4),
            **util})

        # open-loop row: Poisson arrivals at ~70% of the closed-loop
        # rate (sub-saturation — queue delay, not collapse)
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "raft_loadgen",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "loadgen.py"))
            loadgen = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(loadgen)
            rep = loadgen.run_open_loop(
                server, q_np, rate_qps=max(10.0, 0.7 * serve_qps),
                duration_s=min(seconds, 2.0), nq=1, seed=0)
            results.append({
                "metric": f"serve_open_loop_{n//1000}kx{d}_q1_k{k}"
                          f"_p{n_probes}_qps",
                "value": rep["achieved_qps"], "unit": "queries/s",
                "offered_qps": rep["offered_qps"],
                "serve_p50_ms": rep["p50_ms"],
                "serve_p99_ms": rep["p99_ms"],
                "shed": rep["shed"],
                "deadline_expired": rep["deadline_expired"]})
        except Exception as e:
            results.append({
                "metric": f"serve_open_loop_{n//1000}kx{d}_q1_k{k}"
                          f"_p{n_probes}_qps", "error": repr(e)[:200]})
    finally:
        server.close()


def bench_serve_sharded(results, n=None, nlists=1024, n_probes=None):
    """Distributed serving bench (ISSUE 8): closed-loop clients against
    the mesh-wide ``DistributedSearchServer`` (list-sharded index over
    every local device, int8 quantized cross-shard merge) vs the
    single-device ``SearchServer`` at the same flat operating point —
    the ``dist_serve_qps`` / ``merge_bytes_ratio`` /
    ``steady_state_compiles`` acceptance row, plus an overload row
    (2x the measured rate through the degradation ladder, p99 vs the
    watermark). Knobs: ``BENCH_DIST_N`` (rows, default 500k),
    ``BENCH_SERVE_CLIENTS`` / ``BENCH_SERVE_SECONDS`` as bench_serve.

    On a 1-device host the mesh degenerates to one shard (the merge
    moves no wire bytes; the row still reports, ratio None) — the
    multi-chip TPU rounds and the 8-way CPU test mesh are where the
    compression figure is real."""
    import threading
    from raft_tpu import obs, serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import shard_ivf_flat
    from raft_tpu.parallel import ivf as pivf
    from raft_tpu.parallel.mesh import make_mesh
    n = n or int(os.environ.get("BENCH_DIST_N", 500_000))
    if n_probes is None:
        n_probes = FLAT_PROBES
    mesh = make_mesh()
    n_shards = mesh.shape["data"]
    if nlists % n_shards:
        nlists = max(n_shards, nlists // n_shards * n_shards)
    n_probes = min(n_probes, nlists)
    d, nq_pool, k = 128, 256, 32
    db, q = _ann_dataset(n, d, nq_pool)
    q_np = np.asarray(q)
    index = ivf_flat.build(db, ivf_flat.IndexParams(n_lists=nlists,
                                                    kmeans_n_iters=10))
    sindex = shard_ivf_flat(index, mesh)
    # per-shard probes: each shard probes its own lists, so the ladder
    # scales the SINGLE-device probe budget down by the mesh (total
    # probed lists stay comparable — the parallel/ivf contract)
    p_shard = max(1, min(n_probes // n_shards, nlists // n_shards))
    sp = ivf_flat.SearchParams(n_probes=p_shard)
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 2.0))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 16))
    ladder = tuple(dict.fromkeys(
        (p_shard, max(1, p_shard // 2), max(1, p_shard // 4))))
    cfg = serve.ServeConfig(batch_sizes=(1, 8, 32, 128), max_queue=512,
                            max_wait_ms=2.0, probes_ladder=ladder,
                            degrade_watermark_ms=200.0)

    # single-device baseline server at the matched operating point
    single = serve.SearchServer.from_index(
        index, q_np[:128], k, params=ivf_flat.SearchParams(
            n_probes=min(n_probes, nlists)),
        config=serve.ServeConfig(batch_sizes=(1, 8, 32, 128),
                                 max_queue=512, max_wait_ms=2.0))
    dist = serve.DistributedSearchServer.from_sharded_index(
        sindex, q_np[:128], k, params=sp, mesh=mesh, config=cfg)
    metric = (f"dist_serve_{n//1000}kx{d}_q1_k{k}_p{p_shard}"
              f"x{n_shards}_qps")
    try:
        # recall THROUGH the distributed batcher (pad + scatter + int8
        # merge included) and the f32-merge reference, both vs brute
        dist_ids = np.concatenate(
            [np.asarray(dist.search(q_np[s:s + 1])[1])
             for s in range(nq_pool)])
        rec_dist = _ivf_recall(dist_ids, db, q, k)
        f32_ids = np.asarray(pivf.distributed_ivf_flat_search(
            sindex, q_np, k, sp, mesh=mesh, merge="f32")[1])
        rec_f32 = _ivf_recall(f32_ids, db, q, k)

        def closed_loop(server):
            lats, counts = [], []
            lock = threading.Lock()
            stop = time.perf_counter() + seconds

            def client(tid):
                my = []
                i = tid
                while time.perf_counter() < stop:
                    t1 = time.perf_counter()
                    server.search(q_np[i % nq_pool:i % nq_pool + 1])
                    my.append(time.perf_counter() - t1)
                    i += clients
                with lock:
                    lats.extend(my)
                    counts.append(len(my))

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            lats.sort()

            def pct(p):
                return (lats[min(len(lats) - 1,
                                 int(p / 100 * (len(lats) - 1)))] * 1e3
                        if lats else float("nan"))

            return sum(counts) / wall, pct(50), pct(99)

        single_qps, _, _ = closed_loop(single)
        before = obs.snapshot()
        dist_qps, p50, p99 = closed_loop(dist)
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})

        def csum(name):
            return sum(v for k_, v in cnt.items()
                       if k_ == name or k_.startswith(name + "{"))

        compiles = (csum("raft.parallel.plan.misses")
                    + csum("raft.plan.cache.misses")
                    + csum("raft.plan.build.total"))
        bpre = csum("raft.serve.dist.merge.bytes_pre")
        bpost = csum("raft.serve.dist.merge.bytes_post")
        # resource-utilization pass (ISSUE 14): mesh-wide dispatches
        util = _resource_utilization(lambda: dist.search(q_np[:1]))
        results.append({
            "metric": metric,
            "value": round(dist_qps, 1), "unit": "queries/s",
            "dist_serve_qps": round(dist_qps, 1),
            "single_serve_qps": round(single_qps, 1),
            "speedup_vs_single": (round(dist_qps / single_qps, 2)
                                  if single_qps else None),
            "dist_p50_ms": round(p50, 3),
            "dist_p99_ms": round(p99, 3),
            "merge_bytes_ratio": (round(bpost / bpre, 4) if bpre
                                  else None),
            "steady_state_compiles": int(compiles),
            "n_shards": n_shards,
            "clients": clients,
            "recall": round(rec_dist, 4),
            "recall_f32_merge": round(rec_f32, 4),
            **util})

        # overload row: open-loop Poisson at 2x the measured closed-
        # loop rate — bounded p99 via the inherited degradation ladder
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "raft_loadgen",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "loadgen.py"))
            loadgen = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(loadgen)
            before = obs.snapshot()
            rep = loadgen.run_open_loop(
                dist, q_np, rate_qps=max(10.0, 2.0 * dist_qps),
                duration_s=min(seconds, 2.0), nq=1,
                deadline_ms=2 * cfg.degrade_watermark_ms, seed=0)
            diff2 = obs.snapshot_diff(before, obs.snapshot())
            results.append({
                "metric": f"dist_serve_overload_{n//1000}kx{d}"
                          f"_x{n_shards}_qps",
                "value": rep["achieved_qps"], "unit": "queries/s",
                "offered_qps": rep["offered_qps"],
                "dist_p99_ms": rep["p99_ms"],
                "watermark_ms": cfg.degrade_watermark_ms,
                "p99_under_2x_watermark": (
                    rep["p99_ms"] <= 2 * cfg.degrade_watermark_ms),
                "shed": rep["shed"],
                "deadline_expired": rep["deadline_expired"],
                "merge_bytes_per_rung": loadgen.merge_bytes_by_rung(
                    diff2.get("counters", {}))})
        except Exception as e:
            results.append({
                "metric": f"dist_serve_overload_{n//1000}kx{d}"
                          f"_x{n_shards}_qps", "error": repr(e)[:200]})
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})
    finally:
        dist.close()
        single.close()


def _big_enabled() -> bool:
    """Reference-scale shapes (cpp/bench/neighbors/knn.cuh:380-389:
    2M/10M×128, 10k×8192) — hours on the CPU mesh, so opt-in via
    BENCH_BIG=1."""
    return os.environ.get("BENCH_BIG", "") == "1"


def _bench_brute(results, n, size_tag, key_seed):
    # fused brute-force scan: wall (single dispatch) + chained marginal
    # (the gbench stream methodology)
    import jax
    from raft_tpu.neighbors.brute_force import brute_force_knn
    key = jax.random.key(key_seed)
    d, nq, k = 128, 1000, 32
    db = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    reps = _chain_reps()
    qb = jax.random.normal(jax.random.fold_in(key, 3), (reps, nq, d))
    t_marg = _chained_search_time(
        lambda qq, dbb: brute_force_knn(dbb, qq, k, mode="fused"),
        qb, reps, db)
    t = _time(lambda: brute_force_knn(db, q, k, mode="fused"), reps=3)
    results.append({
        "metric": f"bfknn_fused_{size_tag}x{d}_q{nq}_k{k}_qps",
        "value": round(nq / t, 1), "unit": "queries/s",
        "marginal_qps": round(nq / t_marg, 1)})


def bench_mutate(results, n=None, nlists=1024, n_probes=None):
    """Live mutable index bench (ISSUE 9), two rows at the flat bench
    point:

    1. **recall parity** — ``BENCH_MUTATE_MUTS`` (default 10k)
       interleaved upserts/deletes (3:1) applied through the delta
       segment, then ONE fold compaction; recall of the compacted
       index vs a FROM-SCRATCH rebuild of the identical live corpus,
       both against the exact scan (acceptance: gap within 0.01).
       ``mutate_apply_qps`` (mutation ingest rate) and
       ``compact_s`` ride along.
    2. **serving under a mutation stream** — closed-loop clients
       against ``SearchServer.from_index(MutableIndex)`` while a
       writer thread streams upsert/delete batches: sustained
       ``mutate_serve_qps`` with ``steady_state_compiles`` asserted
       from the plan-cache counters over the no-compaction window,
       then one triggered compaction under load with
       ``failed_requests`` (acceptance: 0 — zero serving downtime).

    Knobs: ``BENCH_MUTATE_N`` (corpus rows, 100k),
    ``BENCH_MUTATE_MUTS`` (mutations, 10k),
    ``BENCH_MUTATE_SECONDS`` (serve window, 2.0),
    ``BENCH_MUTATE_CLIENTS`` (8)."""
    import threading
    from raft_tpu import mutate, obs, serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.neighbors.brute_force import brute_force_knn
    if n is None:
        n = int(os.environ.get("BENCH_MUTATE_N", 100_000))
    n_muts = int(os.environ.get("BENCH_MUTATE_MUTS", 10_000))
    if n_probes is None:
        n_probes = FLAT_PROBES
    n_probes = min(n_probes, nlists)
    d, nq, k = 128, 256, 32
    n_up = (3 * n_muts) // 4              # 3:1 upsert:delete mix
    n_del = n_muts - n_up
    db_all, q = _ann_dataset(n + n_up, d, nq)
    db_all, q = np.asarray(db_all), np.asarray(q)
    db, reserve = db_all[:n], db_all[n:]
    params = ivf_flat.IndexParams(n_lists=nlists, kmeans_n_iters=10)
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    index = ivf_flat.build(db, params)
    top = 1 << max(14, (n_up + 256).bit_length())
    m = mutate.MutableIndex(
        index, k=k, params=sp,
        config=mutate.MutateConfig(delta_capacities=(top // 4, top)))
    m.warmup(q[:nq], shapes=(nq,))

    rng = np.random.default_rng(11)
    del_ids = rng.choice(n, size=n_del, replace=False)
    # interleave in batches: 3 upsert batches per delete batch
    bs = 256
    t0 = time.perf_counter()
    up_off = del_off = 0
    while up_off < n_up or del_off < n_del:
        for _ in range(3):
            if up_off < n_up:
                m.upsert(reserve[up_off:up_off + bs])
                up_off += min(bs, n_up - up_off)
        if del_off < n_del:
            m.delete(del_ids[del_off:del_off + bs])
            del_off += min(bs, n_del - del_off)
    apply_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    m.compact()
    compact_s = time.perf_counter() - t0

    # live corpus ground truth: deleted rows out, upserts appended;
    # mutable ids map positions -> global id space
    keep = np.ones(n, bool)
    keep[del_ids] = False
    live_db = np.concatenate([db[keep], reserve[:n_up]], axis=0)
    live_ids = np.concatenate([np.arange(n)[keep],
                               np.arange(n, n + n_up)]).astype(np.int32)
    _, i_exact = brute_force_knn(live_db, q, k, mode="exact")
    exact_ids = live_ids[np.asarray(i_exact)]

    def _recall(ids_got):
        g = np.asarray(ids_got)
        return float(np.mean([len(set(g[r]) & set(exact_ids[r])) / k
                              for r in range(len(g))]))

    _, i_m = m.search(q, block=True)
    rec_mutate = _recall(i_m)
    rebuilt = ivf_flat.build(live_db, params)
    _, i_r = ivf_flat.search(rebuilt, q, k, sp)
    rec_rebuild = _recall(live_ids[np.asarray(i_r)])
    results.append({
        "metric": f"mutate_recall_{n//1000}kx{d}_m{n_muts}"
                  f"_k{k}_p{n_probes}",
        "value": round(rec_mutate, 4), "unit": "recall",
        "mutate_recall": round(rec_mutate, 4),
        "rebuild_recall": round(rec_rebuild, 4),
        "recall_gap": round(rec_rebuild - rec_mutate, 4),
        "mutations": n_muts,
        "mutate_apply_qps": round(n_muts / apply_s, 1),
        "compact_s": round(compact_s, 3)})

    # -- serving under a concurrent mutation stream ----------------------
    seconds = float(os.environ.get("BENCH_MUTATE_SECONDS", 2.0))
    clients = int(os.environ.get("BENCH_MUTATE_CLIENTS", 8))
    cfg = serve.ServeConfig(batch_sizes=(1, 8, 32, 128), max_queue=512,
                            max_wait_ms=2.0)
    server = serve.SearchServer.from_index(m, q[:128], k, config=cfg)
    comp = mutate.Compactor(m)
    stop_evt = threading.Event()
    mut_counts = [0]

    def writer():
        i = 0
        while not stop_evt.is_set():
            try:
                ids = m.upsert(reserve[(i * 64) % n_up:
                                       (i * 64) % n_up + 64])
                if i % 4 == 3:
                    m.delete(ids[:16])
                mut_counts[0] += 1
            except mutate.DeltaFullError:
                time.sleep(0.01)
            i += 1
            time.sleep(0.002)

    lats, fails = [], [0]
    lock = threading.Lock()

    def client(tid):
        my, i = [], tid
        while time.perf_counter() < stop_at:
            t1 = time.perf_counter()
            try:
                server.search(q[i % nq:i % nq + 1])
                my.append(time.perf_counter() - t1)
            except Exception:
                with lock:
                    fails[0] += 1
            i += clients
        with lock:
            lats.extend(my)

    try:
        before = obs.snapshot()
        wt = threading.Thread(target=writer, daemon=True)
        stop_at = time.perf_counter() + seconds
        t0 = time.perf_counter()
        wt.start()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        # steady window compiles (the compactor may have folded — its
        # prewarm compiles are off the serving path; report them apart)
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})
        compactions = cnt.get("raft.mutate.compact.total", 0.0)
        compiles = (cnt.get("raft.plan.cache.misses", 0.0)
                    + cnt.get("raft.plan.build.total", 0.0))
        # one forced compaction under continuing load: serving must
        # not drop a single request through the swap
        stop_at = time.perf_counter() + min(seconds, 1.0)
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        comp.trigger()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop_evt.set()
        wt.join(timeout=5.0)
        lats.sort()

        def pct(p):
            return (lats[min(len(lats) - 1,
                             int(p / 100 * (len(lats) - 1)))] * 1e3
                    if lats else float("nan"))

        results.append({
            "metric": f"mutate_serve_{n//1000}kx{d}_q1_k{k}"
                      f"_p{n_probes}_qps",
            "value": round(len(lats) / wall, 1), "unit": "queries/s",
            "mutate_serve_qps": round(len(lats) / wall, 1),
            "mutate_serve_p50_ms": round(pct(50), 3),
            "mutate_serve_p99_ms": round(pct(99), 3),
            "mutation_batches": mut_counts[0],
            "compactions_in_window": int(compactions),
            "steady_state_compiles": (0 if compactions else
                                      int(compiles)),
            "failed_requests": fails[0]})
    finally:
        stop_evt.set()
        comp.close()
        server.close()


def bench_chaos(results, n=None, nlists=64):
    """Chaos smoke (ISSUE 10): open-loop traffic against the mesh-wide
    ``DistributedSearchServer`` with the full failure-handling stack on
    (dispatch watchdog, retry budget, pre-warmed partial-mesh failover)
    while ONE shard stalls mid-run via the fault harness
    (``raft_tpu.testing.faults.stall_shard``), then recovers. The
    acceptance row: zero hung requests (every future resolves within
    deadline+grace), availability ≥ 0.999 with partial results
    explicitly flagged, p99 under the degradation watermark, zero
    steady-state compiles through failure AND recovery (asserted from
    the plan-cache counters — the degraded ladder is pre-warmed, never
    compiled on the failure path), and the exclusion cleared at the
    end. Knobs: ``BENCH_CHAOS_N`` (rows, default 100k),
    ``BENCH_CHAOS_SECONDS`` (traffic window, default 6)."""
    import importlib.util
    import threading
    from raft_tpu import obs, serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import shard_ivf_flat
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.testing import faults
    n = n or int(os.environ.get("BENCH_CHAOS_N", 100_000))
    seconds = float(os.environ.get("BENCH_CHAOS_SECONDS", 6.0))
    mesh = make_mesh()
    n_shards = mesh.shape["data"]
    metric = f"chaos_stall_{n//1000}kx128_x{n_shards}"
    if n_shards < 2:
        results.append({"metric": metric,
                        "error": "needs a multi-device mesh (a stalled "
                                 "shard on 1 device is an outage, not "
                                 "a failover)"})
        return
    if nlists % n_shards:
        nlists = max(n_shards, nlists // n_shards * n_shards)
    d, nq_pool, k = 128, 256, 32
    db, q = _ann_dataset(n, d, nq_pool)
    q_np = np.asarray(q)
    index = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=nlists, kmeans_n_iters=10))
    sindex = shard_ivf_flat(index, mesh)
    p_shard = max(1, min(FLAT_PROBES // n_shards, nlists // n_shards))
    watermark_ms = 1000.0
    cfg = serve.ServeConfig(
        batch_sizes=(1, 8, 32), max_queue=512, max_wait_ms=2.0,
        default_deadline_ms=3000.0,
        degrade_watermark_ms=watermark_ms,
        dispatch_timeout_ms=300.0, max_retries=2,
        retry_backoff_ms=20.0, failover=True, failover_probe_ms=300.0)
    srv = serve.DistributedSearchServer.from_sharded_index(
        sindex, q_np[:32], k,
        params=ivf_flat.SearchParams(n_probes=p_shard), mesh=mesh,
        config=cfg)
    spec = importlib.util.spec_from_file_location(
        "raft_loadgen",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    try:
        # modest open-loop rate (half the closed-loop ceiling): the row
        # measures failure handling, not saturation
        sustainable = loadgen.measure_sustainable_qps(
            srv, q_np, seconds=1.0)
        rate = max(20.0, 0.5 * sustainable)
        stall_rank = n_shards - 1
        before = obs.snapshot()
        release = threading.Event()

        def chaos():
            time.sleep(seconds / 3.0)
            with faults.stall_shard(stall_rank, seconds=60.0):
                release.wait(seconds / 3.0)

        t = threading.Thread(target=chaos, daemon=True)
        t.start()
        rep = loadgen.run_open_loop(
            srv, q_np, rate_qps=rate, duration_s=seconds, nq=1,
            deadline_ms=cfg.default_deadline_ms, seed=0)
        release.set()
        t.join(timeout=90.0)
        # recovery: traffic after the fault cleared must re-admit the
        # full mesh (the probe runs on batch arrivals)
        recovered = False
        t_end = time.perf_counter() + 15.0
        while time.perf_counter() < t_end:
            srv.search(q_np[:1])
            if obs.snapshot()["gauges"].get(
                    "raft.serve.failover.engaged", 0.0) == 0:
                recovered = True
                break
            time.sleep(0.2)
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})

        def csum(name):
            return sum(v for k_, v in cnt.items()
                       if k_ == name or k_.startswith(name + "{"))

        compiles = (csum("raft.parallel.plan.misses")
                    + csum("raft.plan.cache.misses")
                    + csum("raft.plan.build.total"))
        hung = rep["offered"] - (rep["completed"] + rep["shed"]
                                 + rep["deadline_expired"]
                                 + rep["errors"])
        results.append({
            "metric": metric,
            "value": rep["availability"], "unit": "availability",
            "chaos_availability": rep["availability"],
            "chaos_availability_ok": rep["availability"] >= 0.999,
            "chaos_partial_fraction": rep["partial_fraction"],
            "chaos_partial": rep["partial"],
            "chaos_hung_requests": int(hung),
            "chaos_p99_ms": rep["p99_ms"],
            "chaos_watermark_ms": watermark_ms,
            "chaos_p99_bounded": rep["p99_ms"] <= watermark_ms,
            "chaos_errors": rep["errors"],
            "chaos_deadline_expired": rep["deadline_expired"],
            "chaos_retries": int(csum("raft.serve.retry.total")),
            "chaos_dispatch_timeouts": int(
                csum("raft.serve.dispatch.timeouts.total")),
            "chaos_failover_engagements": int(
                csum("raft.serve.failover.total")),
            "chaos_recovered": recovered,
            "chaos_steady_state_compiles": int(compiles),
            "offered_qps": rep["offered_qps"],
            "n_shards": n_shards,
            "stalled_rank": stall_rank})
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})
    finally:
        faults.reset()
        srv.close()


def bench_quality(results, n=None, nlists=256, n_probes=None):
    """Online quality observability bench (ISSUE 11 acceptance): a
    closed-loop serving run with shadow-exact sampling ON must report
    a live recall estimate within 0.05 of the offline recall at the
    SAME operating point, with zero steady-state compiles and the
    shed/deadline behavior unchanged — all asserted from ``raft.*``
    counters. An SLO tracker (availability + recall floor) runs over
    the window and its burn verdicts ride in the row.

    Knobs: ``BENCH_QUALITY_N`` (rows, default 100k),
    ``BENCH_QUALITY_SECONDS`` (measure window, 2.0),
    ``BENCH_QUALITY_CLIENTS`` (closed-loop callers, 8)."""
    import threading
    from raft_tpu import obs, serve
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import quality as quality_mod
    from raft_tpu.obs import slo as slo_mod
    n = int(os.environ.get("BENCH_QUALITY_N", n or 100_000))
    if n_probes is None:
        n_probes = min(FLAT_PROBES, nlists)
    d, nq_pool, k = 128, 256, 32
    db, q = _ann_dataset(n, d, nq_pool)
    q_np, db_np = np.asarray(q), np.asarray(db)
    seconds = float(os.environ.get("BENCH_QUALITY_SECONDS", 2.0))
    clients = int(os.environ.get("BENCH_QUALITY_CLIENTS", 8))
    index = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=nlists, kmeans_n_iters=10))
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    cfg = serve.ServeConfig(batch_sizes=(1, 8, 32), max_queue=512,
                            max_wait_ms=2.0, quality_sample_rate=0.5)
    server = serve.SearchServer.from_index(index, q_np[:32], k,
                                           params=sp, config=cfg)
    metric = (f"quality_live_recall_{n//1000}kx{d}_q1_k{k}"
              f"_p{n_probes}")
    tracker = None
    try:
        # max_rows=n: the bench point stays EXACT ground truth (the
        # default bound would sample past 256k and turn the comparison
        # into estimator-vs-estimator); big window so the whole run's
        # samples land in one mean
        mon = server.enable_quality(db_np, qconfig=quality_mod.
                                    QualityConfig(max_rows=n,
                                                  window=8192))
        # offline recall THROUGH the server at the same operating
        # point — the yardstick the live estimate must track
        served = np.concatenate(
            [np.asarray(server.search(q_np[s:s + 1])[1])
             for s in range(nq_pool)])
        offline = _ivf_recall(served, db, q, k)
        mon.drain()
        tracker = slo_mod.SLOTracker(
            [slo_mod.Objective("availability", "availability",
                               target=0.999, windows=(5.0, 15.0)),
             slo_mod.Objective("recall_floor", "recall",
                               target=max(0.05, offline - 0.1),
                               tolerance=0.05, windows=(5.0, 15.0))],
            poll_s=0.25)
        before = obs.snapshot()
        stop = time.perf_counter() + seconds
        counts, lock = [], threading.Lock()

        def client(tid):
            i, done = tid, 0
            while time.perf_counter() < stop:
                server.search(q_np[i % nq_pool:i % nq_pool + 1])
                i += clients
                done += 1
            with lock:
                counts.append(done)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        mon.drain(30.0)
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})

        def csum(name):
            return sum(v for k_, v in cnt.items()
                       if k_ == name or k_.startswith(name + "{"))

        compiles = (csum("raft.plan.cache.misses")
                    + csum("raft.plan.build.total"))
        live = mon.stats()
        slo_rep = tracker.tick()
        gap = (abs(live["recall"] - offline)
               if live["recall"] is not None else None)
        results.append({
            "metric": metric,
            "value": live["recall"], "unit": "recall",
            "live_recall": live["recall"],
            "offline_recall": round(offline, 4),
            "recall_gap": None if gap is None else round(gap, 4),
            "recall_gap_ok": gap is not None and gap <= 0.05,
            "sampled_queries": int(csum(
                "raft.obs.quality.samples.total")),
            "shadow_batches": int(csum(
                "raft.obs.quality.shadow.total")),
            "calibration_gap": live.get("calibration_gap"),
            "steady_state_compiles": int(compiles),
            # shed/deadline behavior unchanged: a closed loop must not
            # shed, and sampling must not make it start
            "shed": int(csum("raft.serve.shed.total")),
            "deadline_expired": int(csum("raft.serve.deadline.total")),
            "serve_qps": round(sum(counts) / wall, 1),
            "slo_recall_burn": slo_rep["recall_floor"]["burn"],
            "slo_breaches": sorted(nm for nm, o in slo_rep.items()
                                   if o["breach"])})
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})
    finally:
        if tracker is not None:
            tracker.close()
        server.close()


def bench_fleet(results, n=None, nlists=64):
    """Fleet-serving bench (ISSUE 13): N single-host replicas behind
    the power-of-two-choices :class:`raft_tpu.fleet.FleetRouter` at
    the flat bench point. Three rows:

    * **scaling** — aggregate closed-loop QPS at 1/2/4 replicas (the
      ~linear-scaling acceptance axis). The ratio gate only ARMS when
      the process sees multiple accelerator devices
      (``fleet_scaling_gated``): on the CPU smoke every replica shares
      one device's cores, so adding replicas adds contention, not
      capacity — the ratios are reported for the record and the
      capacity-scaling property is proven by
      ``tests/test_fleet.py`` with service-time-dominated fake
      replicas instead. One-replica-per-chip/host is the deployment
      shape the hardware round (r6 stage ``fl0``) measures.
    * **availability through a replica kill** — open-loop traffic over
      3 replicas while one is killed (no drain) mid-run and revived:
      availability must stay ≥ 0.999 with zero steady-state compiles
      fleet-wide (``raft.plan.cache.*`` — the revived replica warms
      from the shared plan cache).
    * **rolling restart** — one full rollout under the same open-loop
      load: zero failed requests is the acceptance figure.

    Knobs: ``BENCH_FLEET_N`` (rows, default 60k),
    ``BENCH_FLEET_SECONDS`` (per-phase window, default 2.0),
    ``BENCH_FLEET_CLIENTS`` (closed-loop callers per replica, 4)."""
    import importlib.util
    import threading
    import jax
    from raft_tpu import fleet, obs, serve
    from raft_tpu.neighbors import ivf_flat
    n = n or int(os.environ.get("BENCH_FLEET_N", 60_000))
    seconds = float(os.environ.get("BENCH_FLEET_SECONDS", 2.0))
    per_rep_clients = int(os.environ.get("BENCH_FLEET_CLIENTS", 4))
    d, nq_pool, k = 128, 256, 32
    metric = f"fleet_serve_{n//1000}kx{d}"
    db, q = _ann_dataset(n, d, nq_pool)
    q_np = np.asarray(q)
    index = ivf_flat.build(db, ivf_flat.IndexParams(
        n_lists=nlists, kmeans_n_iters=10))
    n_probes = min(FLAT_PROBES, nlists)
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    cfg = serve.ServeConfig(batch_sizes=(1, 8, 32), max_queue=512,
                            max_wait_ms=2.0,
                            default_deadline_ms=3000.0)

    def build_server():
        return serve.SearchServer.from_index(index, q_np[:32], k,
                                             params=sp, config=cfg)

    def closed_loop_qps(router, clients):
        stop_t = time.perf_counter() + seconds
        counts = []
        lock = threading.Lock()

        def client(tid):
            i, done = tid, 0
            while time.perf_counter() < stop_t:
                router.search(q_np[i % nq_pool:i % nq_pool + 1],
                              timeout=60.0)
                done += 1
                i += clients
            with lock:
                counts.append(done)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    try:
        # -- scaling: aggregate QPS at 1 / 2 / 4 replicas ---------------
        qps = {}
        compiles_by_count = {}
        for n_reps in (1, 2, 4):
            reps = [fleet.Replica(f"r{i}", build_server())
                    for i in range(n_reps)]
            router = fleet.FleetRouter(reps)
            before = obs.snapshot()
            qps[n_reps] = closed_loop_qps(router,
                                          per_rep_clients * n_reps)
            diff = obs.snapshot_diff(before, obs.snapshot())
            cnt = diff.get("counters", {})
            compiles_by_count[n_reps] = int(
                cnt.get("raft.plan.cache.misses", 0.0)
                + cnt.get("raft.plan.build.total", 0.0))
            router.close(drain_timeout_s=10.0)
        x2 = qps[2] / max(qps[1], 1e-9)
        x4 = qps[4] / max(qps[1], 1e-9)
        # the ratio gate arms only with real per-replica capacity
        # (multiple accelerator devices); shared-device smokes report
        # the ratios for the record without failing on contention
        scaling_gated = (jax.device_count() > 1
                         and jax.default_backend() != "cpu")
        scaling_ok = (x2 >= 1.4 and x4 >= 2.0) if scaling_gated \
            else True

        # -- availability through a replica kill ------------------------
        spec = importlib.util.spec_from_file_location(
            "raft_loadgen",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "loadgen.py"))
        loadgen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(loadgen)
        reps = [fleet.Replica(f"k{i}", build_server())
                for i in range(3)]
        router = fleet.FleetRouter(
            reps, fleet.FleetConfig(max_retries=1, suspect_ms=500.0,
                                    default_deadline_ms=3000.0))
        rate = max(30.0, 0.4 * qps[1])
        window = max(3.0, 2 * seconds)
        before = obs.snapshot()
        release = threading.Event()

        def chaos():
            release.wait(window / 3.0)
            reps[1].kill()      # no drain — a crash, not a deploy
            release.wait(window / 3.0)
            reps[1].begin_bootstrap()
            reps[1].set_server(build_server())
            reps[1].mark_serving()

        ct = threading.Thread(target=chaos, daemon=True)
        ct.start()
        rep = loadgen.run_open_loop(router, q_np, rate_qps=rate,
                                    duration_s=window, nq=1,
                                    deadline_ms=3000.0, seed=0)
        release.set()
        ct.join(timeout=60.0)
        diff = obs.snapshot_diff(before, obs.snapshot())
        cnt = diff.get("counters", {})
        kill_compiles = int(cnt.get("raft.plan.cache.misses", 0.0)
                            + cnt.get("raft.plan.build.total", 0.0))
        hung = rep["offered"] - (rep["completed"] + rep["shed"]
                                 + rep["deadline_expired"]
                                 + rep["errors"])

        # -- rolling restart under load ---------------------------------
        def restart(replica):
            replica.set_server(build_server())

        roll_fail = {}

        def rolling():
            roll_fail["report"] = fleet.rolling_restart(
                router, restart, drain_timeout_s=30.0)

        rt = threading.Thread(target=rolling, daemon=True)
        rt.start()
        rep_roll = loadgen.run_open_loop(router, q_np, rate_qps=rate,
                                         duration_s=window, nq=1,
                                         deadline_ms=3000.0, seed=1)
        rt.join(timeout=120.0)
        roll_report = roll_fail.get("report", {"ok": False})
        roll_failed = (rep_roll["shed"] + rep_roll["errors"]
                       + rep_roll["deadline_expired"])

        # resource-utilization pass (ISSUE 14): dispatches through the
        # router land in per-replica profiler tags — the report folds
        # measured utilization next to the p2c routing signal
        util = _resource_utilization(
            lambda: router.search(q_np[:1], timeout=60.0),
            extra_fn=lambda: {"fleet_duty_cycle_per_replica": {
                row["name"]: row.get("duty_cycle")
                for row in router.report()["replicas"]}})

        results.append({
            "metric": metric,
            "value": round(qps[4], 1), "unit": "qps_x4",
            "fleet_qps_x1": round(qps[1], 1),
            "fleet_qps_x2": round(qps[2], 1),
            "fleet_qps_x4": round(qps[4], 1),
            "fleet_scaling_x2": round(x2, 3),
            "fleet_scaling_x4": round(x4, 3),
            "fleet_scaling_gated": scaling_gated,
            "fleet_scaling_ok": scaling_ok,
            "fleet_shared_device": not scaling_gated,
            "fleet_availability": rep["availability"],
            "fleet_availability_ok": rep["availability"] >= 0.999,
            "fleet_hung_requests": int(hung),
            "fleet_kill_retries": int(sum(
                v for k_, v in cnt.items()
                if k_.startswith("raft.fleet.retry.total"))),
            "fleet_steady_state_compiles": int(kill_compiles),
            "fleet_scaling_compiles": compiles_by_count,
            "fleet_rolling_ok": bool(roll_report.get("ok")),
            "fleet_rolling_failed_requests": int(roll_failed),
            "fleet_rolling_availability": rep_roll["availability"],
            "offered_qps": rep["offered_qps"],
            "n_probes": n_probes,
            **util})
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})
    finally:
        try:
            router.close(drain_timeout_s=5.0)
        except Exception:
            pass

    # -- multi-process row (ISSUE 20): real daemons, real processes --
    _bench_fleet_proc(results, seconds=seconds,
                      per_proc_clients=per_rep_clients)


def _bench_fleet_proc(results, seconds=2.0, per_proc_clients=4):
    """The multi-process fleet scaling row (ISSUE 20): aggregate
    closed-loop QPS at 1/2/4 ``tools/fleetd.py`` daemons — separate
    OS processes behind the HTTP RPC transport, routed by the same
    FleetRouter through :class:`raft_tpu.fleet.RemoteReplica` fronts.
    The linear-scaling ratio gate ARMS when the processes own distinct
    accelerator devices (one chip each — the r6 stage ``fp0`` shape);
    on shared-device CPU the processes contend for cores and the
    ratios are reported informationally. Per-process steady-state
    compiles are asserted from each daemon's OWN ``/metrics``
    (``raft.plan.cache.*`` diffed across the measurement window — N
    real registries, no shared-process shortcut).

    Knobs: ``BENCH_FLEET_PROC_N`` (rows per daemon index, default
    20k), ``BENCH_FLEET_PROC_SECONDS``, ``BENCH_FLEET_PROC_CLIENTS``,
    ``BENCH_FLEET_PROC_STARTUP_S`` (per-spawn health timeout)."""
    if any(str(r.get("metric", "")).startswith("fleet_proc_serve_")
           for r in results):
        # already measured this run (bench_fleet tail-calls this and
        # bench_fleet_proc is its own _CASES entry — a full-suite run
        # hits both; spawning 1+2+4 daemons twice doubles the round's
        # slowest stage for an identical row)
        return
    import tempfile
    import threading
    import urllib.request

    import jax
    from raft_tpu import fleet
    n = int(os.environ.get("BENCH_FLEET_PROC_N", 20_000))
    seconds = float(os.environ.get("BENCH_FLEET_PROC_SECONDS",
                                   seconds))
    clients_per = int(os.environ.get("BENCH_FLEET_PROC_CLIENTS",
                                     per_proc_clients))
    startup_s = float(os.environ.get("BENCH_FLEET_PROC_STARTUP_S",
                                     300.0))
    d, k, nlists = 64, 32, 64
    metric = f"fleet_proc_serve_{n//1000}kx{d}"
    platform = jax.default_backend()
    if platform == "tpu":
        # one process per chip: this parent has initialised the TPU and
        # holds the host's chips, so no daemon could open one
        # (ProcessFleet refuses); run the fleet from a JAX-free parent
        results.append({"metric": metric, "platform": platform,
                        "skipped": "parent process holds the TPU"})
        return
    from raft_tpu.random import make_blobs
    x, _ = make_blobs(n_samples=n, n_features=d,
                      centers=max(2, nlists), cluster_std=2.0, seed=0)
    q_np = np.asarray(x[:256], np.float32)

    def scrape_compiles(urls):
        # each daemon's OWN registry: the prometheus family names for
        # raft.plan.cache.misses / raft.plan.build.total
        out = {}
        for name, url in urls.items():
            total = 0.0
            try:
                with urllib.request.urlopen(url + "/metrics",
                                            timeout=10.0) as r:
                    text = r.read().decode("utf-8", "replace")
            except OSError:
                out[name] = None
                continue
            for line in text.splitlines():
                if line.startswith("raft_plan_cache_misses_total") \
                        or line.startswith(
                            "raft_plan_build_total_total"):
                    try:
                        total += float(line.rsplit(" ", 1)[1])
                    except ValueError:
                        pass
            out[name] = total
        return out

    def closed_loop(router, clients):
        stop_t = time.perf_counter() + seconds
        counts, lock = [], threading.Lock()

        def client(tid):
            i, done = tid, 0
            while time.perf_counter() < stop_t:
                router.search(q_np[i % 256:i % 256 + 1], timeout=60.0)
                done += 1
                i += clients
            with lock:
                counts.append(done)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / (time.perf_counter() - t0)

    try:
        qps, steady_compiles = {}, {}
        for n_procs in (1, 2, 4):
            with tempfile.TemporaryDirectory(
                    prefix="bench_fleet_proc_") as td, \
                    fleet.ProcessFleet(
                        td, n_procs=n_procs, n=n, dim=d, seed=0,
                        n_lists=nlists, k=k,
                        n_probes=min(FLAT_PROBES, nlists),
                        platform=platform,
                        startup_timeout_s=startup_s) as pf:
                router = fleet.FleetRouter(pf.replicas())
                # warm every daemon's whole ladder before measuring
                closed_loop(router, clients_per * n_procs)
                before = scrape_compiles(pf.urls())
                qps[n_procs] = closed_loop(router,
                                           clients_per * n_procs)
                after = scrape_compiles(pf.urls())
                steady_compiles[n_procs] = {
                    name: (None if before.get(name) is None
                           or after.get(name) is None
                           else int(after[name] - before[name]))
                    for name in after}
                router.close(drain_timeout_s=10.0)
        x2 = qps[2] / max(qps[1], 1e-9)
        x4 = qps[4] / max(qps[1], 1e-9)
        # distinct-device processes are real capacity — the gate arms;
        # shared-device CPU processes contend for the same cores
        scaling_gated = (platform != "cpu"
                         and jax.device_count() >= 4)
        scaling_ok = (x2 >= 1.4 and x4 >= 2.0) if scaling_gated \
            else True
        compiles_flat = [v for per in steady_compiles.values()
                         for v in per.values() if v is not None]
        results.append({
            "metric": metric,
            "value": round(qps[4], 1), "unit": "qps_x4",
            "fleet_proc_qps_x1": round(qps[1], 1),
            "fleet_proc_qps_x2": round(qps[2], 1),
            "fleet_proc_qps_x4": round(qps[4], 1),
            "fleet_proc_scaling_x2": round(x2, 3),
            "fleet_proc_scaling_x4": round(x4, 3),
            "fleet_proc_scaling_gated": scaling_gated,
            "fleet_proc_scaling_ok": scaling_ok,
            "fleet_proc_shared_device": not scaling_gated,
            "fleet_proc_steady_state_compiles": int(
                sum(compiles_flat)),
            "fleet_proc_compiles_by_process": steady_compiles,
            "platform": platform})
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})


def bench_fleet_proc(results):
    """Standalone CLI entry for the multi-process fleet row (r6 stage
    ``fp0``): ``python bench_suite.py fleet_proc`` measures just the
    daemon scaling row without re-running the whole in-process fleet
    bench. Same dedupe as the :func:`bench_fleet` tail-call — the row
    lands exactly once however the suite is invoked."""
    _bench_fleet_proc(results)


def bench_brute_500k(results):
    # the IVF bench point's brute baseline, default-on so the
    # bfknn_fused_500k gate (wall-QPS floor 35k — see PERF_GATES) has
    # a row every run; the r3 TPU marginal reference is 139.7k QPS
    _bench_brute(results, 500_000, "500k", key_seed=14)


def bench_brute_2m(results):
    if not _big_enabled():
        return
    _bench_brute(results, 2_000_000, "2M", key_seed=10)


def bench_fused_wide(results):
    # the 10k×8192 reference shape (K-staged fused kernel)
    if not _big_enabled():
        return
    import jax
    from raft_tpu.neighbors.brute_force import brute_force_knn
    key = jax.random.key(11)
    n, d, nq, k = 10_000, 8192, 1000, 32
    db = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    t = _time(lambda: brute_force_knn(db, q, k, mode="fused"), reps=3)
    results.append({
        "metric": f"bfknn_fused_{n//1000}kx{d}_q{nq}_k{k}_qps",
        "value": round(nq / t, 1), "unit": "queries/s"})


def bench_ivf_10m(results):
    # 10M×128: f32 lists = 5.1 GB (fits one v5e chip); PQ codes ≈ 320 MB
    if not _big_enabled():
        return
    bench_ivf_flat(results, n=10_000_000, nlists=4096, n_probes=128,
                   label="ivf_flat_search_10Mx128_q1000_k32_p128_qps")
    bench_ivf_pq(results, n=10_000_000, nlists=4096, n_probes=128,
                 label="ivf_pq_search_10Mx128_q1000_k32_p128_qps")


def bench_linalg_random(results):
    # cpp/bench/linalg/*.cu, cpp/bench/random/*.cu
    import jax
    import jax.numpy as jnp
    from raft_tpu.linalg.reduce import reduce as reduce_fn
    from raft_tpu.random.make_blobs import make_blobs
    key = jax.random.key(6)
    x = jax.random.normal(key, (16384, 1024))
    t = _time(lambda: reduce_fn(x, along_rows=True))
    results.append({"metric": "reduce_rows_16384x1024_ms",
                    "value": round(t * 1e3, 3), "unit": "ms"})
    t = _time(lambda: make_blobs(n_samples=1_000_000, n_features=64,
                                 centers=10, seed=0)[0])
    results.append({"metric": "make_blobs_1Mx64_ms",
                    "value": round(t * 1e3, 1), "unit": "ms"})


def bench_ball_cover(results):
    # reference cpp/bench has no rbc case; recall-gated timing mirrors
    # the ANN cases (pruned exact search vs fixed-budget)
    import jax
    from raft_tpu.neighbors import ball_cover
    key = jax.random.key(6)
    n, d, nq, k = 200_000, 16, 1000, 10
    db = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (nq, d))
    t_b0 = time.perf_counter()
    index = ball_cover.build(db)
    _sync(index.landmarks)
    t_b = time.perf_counter() - t_b0
    t = _time(lambda: ball_cover.knn_query(index, q, k), reps=3)
    results.append({
        "metric": f"ball_cover_pruned_{n//1000}kx{d}_q{nq}_k{k}_qps",
        "value": round(nq / t, 1), "unit": "queries/s",
        "build_s": round(t_b, 2)})


def bench_sparse_wide(results):
    # the hash-strategy slot: 100k-dim sparse rows, column-tiled tier
    import numpy as np_
    from raft_tpu.sparse import dense_to_csr
    from raft_tpu.sparse.distance import pairwise_distance as sp_dist
    from raft_tpu.distance.distance_types import DistanceType
    rng = np_.random.default_rng(7)
    m, n, kdim, nnz = 512, 256, 100_000, 64
    def make(rows):
        d = np_.zeros((rows, kdim), np_.float32)
        cols = rng.integers(0, kdim, (rows, nnz))
        d[np_.arange(rows)[:, None], cols] = rng.random((rows, nnz))
        return dense_to_csr(d)
    cx, cy = make(m), make(n)
    t = _time(lambda: sp_dist(cx, cy, DistanceType.L2SqrtExpanded,
                              col_tile=4096), reps=3)
    results.append({
        "metric": f"sparse_wide_l2_{m}x{n}x{kdim//1000}kdim_ms",
        "value": round(t * 1e3, 1), "unit": "ms"})


def bench_host_ivf(results):
    # the host-memory transfer axis (reference knn.cuh host strategies)
    import numpy as np_
    import jax
    from raft_tpu.neighbors import host_memory, ivf_flat
    rng = np_.random.default_rng(8)
    n, d, nq, k = 200_000, 64, 256, 10
    x = rng.standard_normal((n, d), dtype=np_.float32)
    t_b0 = time.perf_counter()
    h = host_memory.build(x, ivf_flat.IndexParams(n_lists=512,
                                                  kmeans_n_iters=10),
                          chunk_rows=1 << 17)
    t_b = time.perf_counter() - t_b0
    q = x[:nq]
    t = _time(lambda: host_memory.search(
        h, q, k, ivf_flat.SearchParams(n_probes=32)), reps=3)
    results.append({
        "metric": f"host_ivf_search_{n//1000}kx{d}_q{nq}_k{k}_p32_qps",
        "value": round(nq / t, 1), "unit": "queries/s",
        "build_s": round(t_b, 2)})


def bench_tiered(results, n=None, nlists=64):
    """Tiered-serving bench (ISSUE 19): QPS + recall at hot_frac ∈
    {1.0, 0.5, 0.25} vs the fully-resident baseline at the SAME
    (nq, k, n_probes) operating point. The acceptance figures ride
    the row: bit-identical ids at every hot fraction
    (``parity_hot_*`` / ``parity_ok``), zero steady-state compiles
    over the measured windows (``steady_state_compiles`` from
    ``raft.plan.cache.*``), overlap fraction > 0 (cold fetches hidden
    under the hot-tier scan) and the servable-rows headline — the
    corpus-to-budget multiplier at the smallest hot fraction. CPU
    smoke gate: a corpus larger than the hot budget must serve at
    ≥ 0.5× the fully-resident QPS (``qps_ratio_ok``).

    Knobs: ``BENCH_TIERED_N`` (rows, default 120k)."""
    from raft_tpu import obs
    from raft_tpu.neighbors import ivf_flat, tiered
    n = int(os.environ.get("BENCH_TIERED_N", n or 120_000))
    d, nq, k = 64, 128, 32
    n_probes = min(16, nlists)
    metric = f"tiered_search_{n//1000}kx{d}_q{nq}_k{k}_p{n_probes}"
    try:
        db, q = _ann_dataset(n, d, nq)
        q_np = np.asarray(q)
        index = ivf_flat.build(db, ivf_flat.IndexParams(
            n_lists=nlists, kmeans_n_iters=10))
        # probe scan order: the order-sensitive top-k tie-break path
        # the tiered merge reproduces — the parity reference AND the
        # QPS yardstick
        sp = ivf_flat.SearchParams(n_probes=n_probes,
                                   scan_order="probe")
        t_res = _time(lambda: ivf_flat.search(index, q, k, sp),
                      reps=3)
        _, i_ref = ivf_flat.search(index, q, k, sp)
        i_ref_np = np.asarray(i_ref)
        qps_res = nq / t_res
        row = {"metric": metric, "unit": "queries/s",
               "resident_qps": round(qps_res, 1),
               "recall": round(_ivf_recall(i_ref_np, db, q, k), 4),
               "n_probes": n_probes}
        parity_all, compiles = True, 0
        overlap_frac = fetch_mb_s = qps_cold = None
        for hot_frac in (1.0, 0.5, 0.25):
            tindex = tiered.from_index(
                index, tiered.TieredConfig(hot_frac=hot_frac))
            plan = tiered.build_plan(tindex, q_np, k, sp)
            _, i_t = plan.search(q_np, block=True)      # settle
            parity = bool(np.array_equal(np.asarray(i_t), i_ref_np))
            parity_all = parity_all and parity
            before = obs.snapshot()
            t = _time(lambda: plan.search(q_np, block=True), reps=3)
            diff = obs.snapshot_diff(before, obs.snapshot())
            cnt = diff.get("counters", {})

            def csum(name):
                return sum(v for k_, v in cnt.items()
                           if k_ == name or k_.startswith(name + "{"))

            compiles += int(csum("raft.plan.cache.misses")
                            + csum("raft.plan.build.total"))
            tag = f"{hot_frac:g}".replace(".", "_")
            row[f"qps_hot_{tag}"] = round(nq / t, 1)
            row[f"parity_hot_{tag}"] = parity
            fetch_s = csum("raft.tiered.fetch.seconds")
            if hot_frac < 1.0 and fetch_s > 0:
                overlap_frac = round(
                    csum("raft.tiered.overlap.seconds") / fetch_s, 4)
                fetch_mb_s = round(csum("raft.tiered.fetch.bytes")
                                   / 2 ** 20 / fetch_s, 1)
            if hot_frac == 0.25:
                qps_cold = nq / t
                total_b = tindex.n_lists * tindex.bytes_per_list
                budget_b = max(1, tindex.budget_bytes)
                # the headline: rows servable per byte of hot budget —
                # a corpus this many times the pinned footprint serves
                # with full parity
                row["servable_rows"] = n
                row["servable_rows_x"] = round(total_b / budget_b, 2)
                row["budget_mb"] = round(budget_b / 2 ** 20, 2)
                row["hot_lists"] = tindex.hot_lists
        ratio = (qps_cold / max(qps_res, 1e-9)
                 if qps_cold is not None else None)
        row.update({
            "value": row.get("qps_hot_0_25"),
            "parity_ok": parity_all,
            "steady_state_compiles": compiles,
            "overlap_frac": overlap_frac,
            "fetch_mb_s": fetch_mb_s,
            "qps_ratio_vs_resident": None if ratio is None
            else round(ratio, 3),
            "qps_ratio_ok": ratio is not None and ratio >= 0.5})
        results.append(row)
    except Exception as e:
        results.append({"metric": metric, "error": repr(e)[:200]})


# Value-first order: with streaming prints, whatever completes is banked
# — so the headline rows come first and the long-compile pairwise
# family last
_CASES = [bench_select_k, bench_brute_500k,
          bench_ivf_flat, bench_ivf_flat_100k, bench_ivf_pq,
          bench_ivf_pq4,
          bench_ivf_bq, bench_serve, bench_serve_sharded,
          bench_mutate, bench_chaos, bench_quality, bench_fleet,
          bench_fleet_proc, bench_tiered, bench_sharded_build,
          bench_fused_l2_nn, bench_pairwise_distance,
          bench_kmeans,
          bench_ivf_flat_int8, bench_linalg_random, bench_ball_cover,
          bench_sparse_wide, bench_host_ivf, bench_brute_2m,
          bench_fused_wide, bench_ivf_10m]


def _suite_meta():
    """Provenance row appended to every table: library version, the
    active kernel-dispatch mode and the full obs snapshot — BENCH_r*.json
    becomes self-describing about which code produced its numbers. The
    row carries no ``value``, so gates and comparisons skip it (schema
    stays backward-compatible: old tables simply lack the row)."""
    import jax
    import raft_tpu
    from raft_tpu import obs
    from raft_tpu.ops.dispatch import pallas_enabled
    return {
        "metric": "_meta",
        "raft_tpu_version": raft_tpu.__version__,
        "backend": jax.default_backend(),
        "dispatch_pallas": pallas_enabled(),
        "pallas_mode": os.environ.get("RAFT_TPU_PALLAS", "auto"),
        "metrics": obs.snapshot(),
    }


def run_all(cases=None, stream=False):
    """Run the selected cases. With ``stream``, print each case's rows
    the moment the case completes (flushed) — a measurement window that
    dies mid-suite still banks every finished case.

    Every row embeds a ``metrics`` diff (obs snapshot before/after its
    case): the record says which code path produced the number —
    dispatch route, scan mode, compile-cache hits — not just the
    number. A final ``_meta`` row carries version + full snapshot."""
    import jax
    if "BENCH_PLATFORM" in os.environ:
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from raft_tpu.core.compile_cache import enable as _enable_cache
    from raft_tpu import obs
    _enable_cache()  # cross-process warm kernels (AOT-kernel role)
    results = []
    selected = _CASES if not cases else [
        c for c in _CASES if c.__name__.removeprefix("bench_") in cases]
    if cases:
        known = {c.__name__.removeprefix("bench_") for c in _CASES}
        bad = [c for c in cases if c not in known]
        if bad:
            # an unknown case name must never yield a silent empty run
            # (a typo'd --gate invocation would exit green having
            # measured nothing)
            raise SystemExit(f"bench_suite: unknown case(s) {bad}; "
                             f"available: {sorted(known)}")
    for case in selected:
        done = len(results)
        before = obs.snapshot()
        try:
            case(results)
        except Exception as e:  # a failing case must not kill the table
            results.append({"metric": case.__name__, "error": repr(e)})
        diff = obs.snapshot_diff(before, obs.snapshot())
        for r in results[done:]:
            r.setdefault("metrics", diff)
        if stream:
            for r in results[done:]:
                print(json.dumps(r), flush=True)
    results.append(_suite_meta())
    if stream:
        print(json.dumps(results[-1]), flush=True)
    return results


# Perf-regression gates (the role of the reference's recall thresholds +
# gbench tracking, SURVEY.md §4/§6): floor/ceiling per metric, checked by
# `python bench_suite.py --gate [cases...]` on real TPU hardware. Values
# are deliberately loose (~2x headroom off numbers measured before the
# current chip, which have not been re-measured) so host jitter never
# trips them; a trip means a real regression. qps = floor, ms = ceiling.
PERF_GATES = {
    "pairwise_L2Expanded_8192x8192x256_ms": 40.0,
    "pairwise_L1_8192x8192x256_ms": 130.0,
    # wall QPS floor, set from pre-PR-21 records that were deleted with
    # the remote-runtime era; not re-measured on the current chip
    "bfknn_fused_500kx128_q1000_k32_qps": 35_000.0,
    f"ivf_flat_search_500kx128_q1000_k32_p{FLAT_PROBES}_qps": 3500.0,
    # ivf_pq / ivf_bq QPS + recall floors land with the first TPU
    # measurement of each row (VERDICT r3 #7); recall gates for the
    # measured rows live in check_gates' recall pass below
}

# recall floors for headline rows that report one (the reference's
# eval_neighbours min_recall gating, ann_utils.cuh:201). Applied by
# check_gates to the "recall" field of a row when the row ran.
RECALL_GATES = {
    f"ivf_flat_search_500kx128_q1000_k32_p{FLAT_PROBES}_qps": 0.90,
    # rescored PQ headline: VERDICT r3 #4 demands ≥0.9 at the bench
    # point (flat's probe ceiling there measured 0.9298; rescoring
    # tracks it within 1-2%)
    f"ivf_pq_search_500kx128_q1000_k32_p{IVF_PROBES}_qps": 0.85,
    f"ivf_pq4_search_500kx128_q1000_k32_p{IVF_PROBES}_qps": 0.80,
    f"ivf_bq_search_500kx128_q1000_k32_p{IVF_PROBES}_qps": 0.60,
}

# marginal-gap ceilings (ROADMAP item 2 / ISSUE 7): marginal_qps /
# plan_qps per row — the serving path must reach at least 1/gate of
# the kernels' chained rate. The flat 100k point is the acceptance
# gate for the fused scan+select kernel; checked like the recall
# gates (a gated row that lost its marginal_gap field is a failure).
GAP_GATES = {
    f"ivf_flat_search_100kx128_q1000_k32_p{FLAT_PROBES}_qps": 2.0,
}


def check_gates(results, require_all=True):
    """Compare a results table against PERF_GATES → list of failures.
    With ``require_all`` (full-suite gate runs), a gated metric that
    produced no value (case errored, name drifted) is itself a failure —
    a gate must never pass by not running. Case-filtered runs set it
    False so unselected gates aren't charged."""
    failures = []
    seen = set()
    seen_recall = set()
    seen_gap = set()
    for r in results:
        rgate = RECALL_GATES.get(r.get("metric"))
        if rgate is not None and "recall" in r:
            seen_recall.add(r["metric"])
            if r["recall"] < rgate:
                failures.append({"metric": r["metric"],
                                 "value": r["recall"], "gate": rgate,
                                 "kind": "recall"})
        ggate = GAP_GATES.get(r.get("metric"))
        if ggate is not None and "marginal_gap" in r:
            seen_gap.add(r["metric"])
            if r["marginal_gap"] > ggate:
                failures.append({"metric": r["metric"],
                                 "value": r["marginal_gap"],
                                 "gate": ggate,
                                 "kind": "marginal_gap"})
        gate = PERF_GATES.get(r.get("metric"))
        if gate is None or "value" not in r:
            continue
        seen.add(r["metric"])
        is_rate = r.get("metric", "").endswith("qps")
        ok = r["value"] >= gate if is_rate else r["value"] <= gate
        if not ok:
            failures.append({"metric": r["metric"], "value": r["value"],
                             "gate": gate,
                             "kind": "floor" if is_rate else "ceiling"})
    if require_all:
        for metric in PERF_GATES:
            if metric not in seen:
                failures.append({"metric": metric, "value": None,
                                 "gate": PERF_GATES[metric],
                                 "kind": "missing"})
        # recall gates must not pass by not running either (a case
        # that errored, or a row that lost its recall field)
        for metric in RECALL_GATES:
            if metric not in seen_recall:
                failures.append({"metric": metric, "value": None,
                                 "gate": RECALL_GATES[metric],
                                 "kind": "missing"})
        for metric in GAP_GATES:
            if metric not in seen_gap:
                failures.append({"metric": metric, "value": None,
                                 "gate": GAP_GATES[metric],
                                 "kind": "missing"})
    return failures


if __name__ == "__main__":
    import sys
    args = sys.argv[1:]
    gate = "--gate" in args
    if gate:
        args = [a for a in args if a != "--gate"]
    results = run_all(args or None, stream=True)
    if gate:
        fails = check_gates(results, require_all=not args)
        for f in fails:
            print(json.dumps({"gate_failure": f}))
        print(json.dumps({"gates_checked": True, "failures": len(fails)}))
        sys.exit(1 if fails else 0)
