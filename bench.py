#!/usr/bin/env python
"""Headline benchmark: fused brute-force L2 k-NN throughput on one chip.

Mirrors the reference's gbench flagship case (``cpp/bench/neighbors/knn.cuh
:380-389``: {1M-2M}×128 fp32 database, 1000 queries, k=32, SEARCH scope),
run through the Pallas fused distance+top-k kernel
(raft_tpu/ops/pallas_fused_knn.py) with a recall gate against the exact
scan — the reference's ANN bench methodology (recall-thresholded speed,
SURVEY.md §4) — then the IVF / serving rows of ``bench_suite``.

Prints JSON lines, the last one the whole artifact, stamped with the
device it ran on (``platform``, ``device_kind``, ``device_count``).

No fallback hides the device: with no TPU the run exits non-zero
(``BENCH_PLATFORM=cpu`` is an explicit rehearsal, stamped as such); a
fused recall miss or a failed phase is recorded and fails the run.

vs_baseline: the reference repo publishes no absolute numbers; the
declared baseline proxy is 40 ms wall for the 1M×128×1000q×k=32 search
on the reference's A100 class hardware. vs_baseline = proxy_ms /
measured_ms (>1 means faster than proxy).
"""

import json
import os
import sys
import time
import traceback

N_DB = int(os.environ.get("BENCH_N_DB", 1_000_000))
N_DIM = int(os.environ.get("BENCH_DIM", 128))
N_QUERIES = int(os.environ.get("BENCH_QUERIES", 1000))
K = int(os.environ.get("BENCH_K", 32))
BASELINE_PROXY_MS = 40.0
MIN_RECALL = 0.95

# serving-tier rows: (bench_suite case, n cap, row key that marks the
# row, {artifact key: row key}) — each row's figures ride the artifact
# under these keys
_ROW_KEYS = [
    ("bench_serve", None, "serve_qps", {
        "serve_qps": "serve_qps",
        "serve_per_request_qps": "per_request_qps",
        "serve_speedup_vs_per_request": "speedup_vs_per_request",
        "serve_p50_ms": "serve_p50_ms", "serve_p99_ms": "serve_p99_ms",
        "serve_batch_occupancy": "batch_occupancy",
        "serve_steady_state_compiles": "steady_state_compiles",
        "serve_recall": "recall", "serve_device_util": "device_util",
        "serve_hbm_peak_mb": "hbm_peak_mb"}),
    ("bench_serve_sharded", None, "dist_serve_qps", {
        "dist_serve_qps": "dist_serve_qps",
        "dist_single_serve_qps": "single_serve_qps",
        "dist_speedup_vs_single": "speedup_vs_single",
        "dist_p99_ms": "dist_p99_ms",
        "dist_merge_bytes_ratio": "merge_bytes_ratio",
        "dist_steady_state_compiles": "steady_state_compiles",
        "dist_n_shards": "n_shards", "dist_recall": "recall",
        "dist_recall_f32_merge": "recall_f32_merge",
        "dist_device_util": "device_util",
        "dist_hbm_peak_mb": "hbm_peak_mb"}),
    ("bench_serve_sharded", None, "p99_under_2x_watermark", {
        "dist_overload_p99_ms": "dist_p99_ms",
        "dist_overload_p99_bounded": "p99_under_2x_watermark"}),
    ("bench_mutate", None, "mutate_recall", {
        "mutate_recall": "mutate_recall",
        "mutate_rebuild_recall": "rebuild_recall",
        "mutate_recall_gap": "recall_gap",
        "mutate_apply_qps": "mutate_apply_qps",
        "mutate_compact_s": "compact_s"}),
    ("bench_mutate", None, "mutate_serve_qps", {
        "mutate_serve_qps": "mutate_serve_qps",
        "mutate_serve_p99_ms": "mutate_serve_p99_ms",
        "mutate_steady_state_compiles": "steady_state_compiles",
        "mutate_failed_requests": "failed_requests",
        "mutate_compactions_in_window": "compactions_in_window"}),
    ("bench_chaos", 100_000, "chaos_availability", {
        k: k for k in (
            "chaos_availability", "chaos_availability_ok",
            "chaos_partial_fraction", "chaos_hung_requests",
            "chaos_p99_ms", "chaos_p99_bounded", "chaos_recovered",
            "chaos_steady_state_compiles")}),
    ("bench_quality", 200_000, "live_recall", {
        "quality_live_recall": "live_recall",
        "quality_offline_recall": "offline_recall",
        "quality_recall_gap": "recall_gap",
        "quality_recall_gap_ok": "recall_gap_ok",
        "quality_sampled_queries": "sampled_queries",
        "quality_steady_state_compiles": "steady_state_compiles",
        "quality_shed": "shed", "quality_slo_breaches": "slo_breaches"}),
    ("bench_fleet", 100_000, "fleet_qps_x1", {
        k: k for k in (
            "fleet_qps_x1", "fleet_qps_x2", "fleet_qps_x4",
            "fleet_scaling_x4", "fleet_scaling_ok", "fleet_availability",
            "fleet_availability_ok", "fleet_hung_requests",
            "fleet_steady_state_compiles", "fleet_rolling_ok",
            "fleet_rolling_failed_requests",
            "fleet_duty_cycle_per_replica")} | {
        "fleet_device_util": "device_util",
        "fleet_hbm_peak_mb": "hbm_peak_mb"}),
    ("bench_fleet", 100_000, "fleet_proc_qps_x1", {
        k: k for k in (
            "fleet_proc_qps_x1", "fleet_proc_qps_x2", "fleet_proc_qps_x4",
            "fleet_proc_scaling_x4", "fleet_proc_scaling_ok",
            "fleet_proc_scaling_gated",
            "fleet_proc_steady_state_compiles")}),
    ("bench_tiered", 120_000, "parity_ok", {
        "tiered_resident_qps": "resident_qps",
        "tiered_qps_hot_1": "qps_hot_1",
        "tiered_qps_hot_0_5": "qps_hot_0_5",
        "tiered_qps_hot_0_25": "qps_hot_0_25",
        "tiered_parity_ok": "parity_ok",
        "tiered_steady_state_compiles": "steady_state_compiles",
        "tiered_overlap_frac": "overlap_frac",
        "tiered_fetch_mb_s": "fetch_mb_s",
        "tiered_servable_rows_x": "servable_rows_x",
        "tiered_qps_ratio_vs_resident": "qps_ratio_vs_resident",
        "tiered_qps_ratio_ok": "qps_ratio_ok"}),
]

_IVF_KEYS = ("qps", "marginal_qps", "device_marginal_qps", "fixed_cost_ms",
             "plan_qps", "marginal_gap", "device_util", "hbm_peak_mb",
             "recall", "recall_estimator", "build_s")


def main():
    import numpy as np
    import jax
    # BENCH_PLATFORM=cpu: an explicit rehearsal off the chip
    rehearsal = os.environ.get("BENCH_PLATFORM")
    if rehearsal:
        jax.config.update("jax_platforms", rehearsal)
    from raft_tpu.core.compile_cache import enable as _enable_cache
    _enable_cache()
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and not rehearsal:
        print(f"bench: no TPU found (JAX reports platform {platform!r}); "
              f"set BENCH_PLATFORM=cpu for a rehearsal", file=sys.stderr)
        return 1
    import jax.numpy as jnp

    import bench_suite
    from bench_suite import _sync as _fetch  # host-transfer barrier
    from raft_tpu.neighbors.brute_force import brute_force_knn
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.ops.dispatch import pallas_enabled

    key = jax.random.key(0)
    kq, kd = jax.random.split(key)
    db = jax.device_put(jax.random.normal(kd, (N_DB, N_DIM),
                                          dtype=jnp.float32))
    q = jax.device_put(jax.random.normal(kq, (N_QUERIES, N_DIM),
                                         dtype=jnp.float32))
    _fetch([db[0, :1], q[0, :1]])

    mode = "fused" if pallas_enabled() else "exact"
    failed = []

    # recall gate vs the exact scan (eval_neighbours analogue,
    # cpp/test/neighbors/ann_utils.cuh:201). Ground-truth indices are
    # computed ONCE and reused by the bf16-tier gate below.
    exact_ids = None

    def _recall_vs_exact(i_got):
        nonlocal exact_ids
        if exact_ids is None:
            _, i_e = brute_force_knn(db, q, K, mode="exact")
            exact_ids = np.asarray(i_e)
        got = np.asarray(i_got)
        return float(np.mean([
            len(set(got[r]) & set(exact_ids[r])) / K
            for r in range(len(got))]))

    d_f, i_f = brute_force_knn(db, q, K, DistanceType.L2Expanded, mode=mode)
    _fetch([d_f[0, 0], i_f[0, 0]])  # compile + warm
    recall = _recall_vs_exact(i_f) if mode == "fused" else 1.0
    if recall < MIN_RECALL:
        failed.append("fused_recall")

    # offline-throughput timing: n_iters independent searches (distinct
    # query batches) chained inside ONE jitted computation, synced once —
    # the gbench methodology (stream-ordered kernel launches + one
    # stream sync)
    n_iters = int(os.environ.get("BENCH_CHAIN", 10))
    q_batches = jax.device_put(jax.random.normal(
        jax.random.fold_in(kq, 7), (n_iters, N_QUERIES, N_DIM),
        dtype=jnp.float32))

    def time_chain(kprec):
        # touch every search's result so none is dead-code eliminated,
        # and reduce to ONE scalar fetched at the end
        @jax.jit
        def run_chain(db_, qs):
            acc = jnp.zeros((), jnp.float32)
            for i in range(n_iters):
                d_, i_ = brute_force_knn(db_, qs[i], K,
                                         DistanceType.L2Expanded,
                                         mode=mode,
                                         kernel_precision=kprec)
                acc += d_[0, 0] + i_[0, 0].astype(jnp.float32)
            return acc

        _fetch(run_chain(db, q_batches))  # compile + warm
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            _fetch(run_chain(db, q_batches))
            walls.append((time.perf_counter() - t0) / n_iters)
        return min(walls)

    wall = time_chain(None)
    out = {
        "metric": (f"bfknn_{mode}_search_{N_DB//1000}kx{N_DIM}"
                   f"_q{N_QUERIES}_k{K}_qps"),
        "value": round(N_QUERIES / wall, 1),
        "unit": "queries/s",
        "vs_baseline": round(BASELINE_PROXY_MS / (wall * 1e3), 3),
        "recall": round(recall, 4),
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(out), flush=True)

    def phase(name, fn):
        """Run one phase; a failure is recorded and fails the run."""
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - reported, run fails
            traceback.print_exc()
            out[f"{name}_error"] = repr(e)[:200]
            failed.append(name)
        print(json.dumps(out), flush=True)

    # recall-gated single-pass-bf16 speed tier (the reference benches
    # fp16 datasets alongside fp32 — knn.cuh kInputs half variants; on
    # TPU the analogue is one bf16 MXU pass instead of the 3-pass
    # bf16x3 split). Headline takes the tier only if its recall holds.
    def bf16_tier():
        d_b, i_b = brute_force_knn(db, q, K, DistanceType.L2Expanded,
                                   mode="fused", kernel_precision="bf16")
        _fetch([d_b[0, 0], i_b[0, 0]])
        rec_b = _recall_vs_exact(i_b)
        wall_b = time_chain("bf16")
        out["bf16_tier_qps"] = round(N_QUERIES / wall_b, 1)
        out["bf16_tier_recall"] = round(rec_b, 4)
        if rec_b >= MIN_RECALL and wall_b < wall:
            out["value"] = round(N_QUERIES / wall_b, 1)
            out["recall"] = round(rec_b, 4)
            out["kernel_precision"] = "bf16"
            out["vs_baseline"] = round(BASELINE_PROXY_MS / (wall_b * 1e3),
                                       3)

    if mode == "fused" and not os.environ.get("BENCH_SKIP_BF16"):
        phase("bf16_tier", bf16_tier)

    if not os.environ.get("BENCH_SKIP_IVF"):
        # the rehearsal shrinks the shapes: index builds at 500k on the
        # CPU would take longer than the rehearsal is worth
        on_chip = platform == "tpu"
        n_ivf = min(N_DB, 500_000 if on_chip else 50_000)
        nlists = 1024 if on_chip else 128

        def ivf_family(fam, case):
            rows = []
            case(rows, n=n_ivf, nlists=nlists)
            r = rows[0]
            if "error" in r:
                raise RuntimeError(r["error"])
            r = dict(r, qps=r.get("value"))
            for k in _IVF_KEYS:
                if r.get(k) is not None:
                    out[f"{fam}_{k}"] = r[k]

        for fam in ("ivf_flat", "ivf_pq", "ivf_pq4", "ivf_bq"):
            phase(fam, lambda fam=fam: ivf_family(
                fam, getattr(bench_suite, f"bench_{fam}")))

        # sharded multi-chip builds: per-family wall seconds for the
        # list-sharded build path, same-run comparable with build_s
        def sharded_build():
            rows = []
            bench_suite.bench_sharded_build(rows, n=n_ivf, nlists=nlists)
            for r in rows:
                fam = r["metric"].split("_sharded_build_")[0]
                if "error" in r:
                    raise RuntimeError(f"{fam}: {r['error']}")
                out[f"{fam}_sharded_build_s"] = r["sharded_build_s"]
                out.setdefault("sharded_build_n_shards", r.get("n_shards"))

        phase("sharded_build", sharded_build)

        rows_by_case = {}

        def serving_rows(case, cap):
            rows = []
            getattr(bench_suite, case)(
                rows, n=min(n_ivf, cap) if cap else n_ivf,
                **({} if cap else {"nlists": nlists}))
            errs = [r["error"] for r in rows if "error" in r]
            if errs:
                raise RuntimeError("; ".join(errs))
            rows_by_case[case] = rows

        for case, cap in dict.fromkeys((c, n) for c, n, _, _ in _ROW_KEYS):
            name = case.removeprefix("bench_")
            if case == "bench_chaos" and jax.device_count() < 2:
                # a stalled shard on one device is an outage, not a
                # failover: the row needs a mesh
                out["chaos_skipped"] = "needs a multi-device mesh"
                continue
            phase(name, lambda case=case, cap=cap: serving_rows(case, cap))
        for case, _, marker, keys in _ROW_KEYS:
            for r in rows_by_case.get(case, []):
                if marker in r:
                    out.update({k: r[rk] for k, rk in keys.items()
                                if r.get(rk) is not None})
        print(json.dumps(out), flush=True)

    if failed:
        print(f"bench: failed phases: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())


def run_suite():
    """Extended bench table (reference cpp/bench parity) — invoked by
    tools, not the driver. Returns a list of result dicts covering
    pairwise distance, fusedL2NN, select_k, kmeans, and ivf searches."""
    import bench_suite
    return bench_suite.run_all()
