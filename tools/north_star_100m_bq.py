"""100M×128 IVF-BQ: BUILD and SEARCH the 1-bit tier at the full
north-star scale — the memory-tier story as real arrays, not
arithmetic: ~3.2 GB of codes+stats for a 51.2 GB corpus, plus
estimator + exact-rescore recall at the coverage-curve operating
point (tools/north_star_100m_curve.py: ceiling@10 = 0.998 at 64/8192
probes).

Platforms (RAFT_TPU_NS_PLATFORM env):
  cpu (default) — the single-host rehearsal: everything on the CPU
      backend, host-resident corpus.
  tpu           — the round-5 north-star run (VERDICT r4 #4): corpus
      stays HOST-resident numpy (51.2 GB >> HBM), each 256 MB row
      chunk is uploaded ONCE and serves both the exact-GT scan and
      the BQ encode, codes+stats live on device, the estimator scan
      is the served device program, and the exact re-rank runs
      against the host corpus (the host_memory tier pattern). Chunk
      size stays at 2^19 rows = 256 MB per upload.

The search phase reports cold (incl. compile) and warm best-of-3
times → QPS at the operating point.

Run: python tools/north_star_100m_bq.py [N_ROWS] [N_LISTS]
Output: tools/measure_out/north_star_100m_bq.json
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

PLATFORM = os.environ.get("RAFT_TPU_NS_PLATFORM", "cpu")
if PLATFORM != "tpu":
    jax.config.update("jax_platforms", "cpu")
from raft_tpu.core.compile_cache import enable as _enable_cache  # noqa: E402

_enable_cache()

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from raft_tpu.core.precision import matmul_precision  # noqa: E402


def log(msg):
    print(f"[100m-bq] {msg}", flush=True)


def _sync(tree):
    for leaf in jax.tree.leaves(tree):
        np.asarray(leaf.ravel()[:1])


def main(n_rows=100_000_000, n_lists=8192):
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.neighbors import brute_force, ivf_bq
    from raft_tpu.neighbors.ivf_bq import _pack_bits
    from raft_tpu.neighbors.ivf_flat import _bucketize_static
    from raft_tpu.neighbors.ivf_pq import make_rotation_matrix
    from raft_tpu.util.host_sample import sample_rows

    d, k = 128, 10
    nq = int(os.environ.get("RAFT_TPU_NS_NQ",
                            1000 if PLATFORM == "tpu" else 100))
    w = d // 32
    out = {"n_rows": n_rows, "dim": d, "n_lists": n_lists, "k": k,
           "nq": nq, "platform": PLATFORM}
    # 2^19 rows × 128 f32 = 256 MB per chunk: one upload serves both
    # the GT scan and the encode, so the corpus is uploaded once
    step = 1 << 19
    n_chunks = -(-n_rows // step)

    # host-side data gen (numpy): the same semi-hard clustered mixture
    # as bench_suite._ann_dataset (~125 rows/cluster, unit centers +
    # unit noise) drawn with host RNG — on the tpu platform a traced
    # mixture would generate ON DEVICE and pay a 51.2 GB fetch
    rng = np.random.default_rng(0)
    nc = max(64, min(8192, n_rows // 125))
    centers_mix = rng.standard_normal((nc, d)).astype(np.float32)
    t0 = time.perf_counter()
    x = np.empty((n_rows, d), np.float32)
    for s in range(0, n_rows, step):
        e = min(s + step, n_rows)
        lab_c = rng.integers(0, nc, e - s)
        x[s:e] = centers_mix[lab_c]
        x[s:e] += rng.standard_normal((e - s, d), dtype=np.float32)
    q_h = (centers_mix[rng.integers(0, nc, nq)]
           + rng.standard_normal((nq, d), dtype=np.float32))
    q = jnp.asarray(q_h)
    _sync(q)
    log(f"data gen {time.perf_counter()-t0:.0f}s "
        f"({x.nbytes/1e9:.1f} GB host-resident)")

    # coarse centers (1M-row subsample, the curve run's budget)
    t0 = time.perf_counter()
    n_train = min(1_000_000, 125 * n_lists)
    tr_idx = np.asarray(sample_rows(n_rows, n_train, 0))
    trainset = jnp.asarray(x[tr_idx])
    centers = kmeans_balanced.build_hierarchical(trainset, n_lists, 10)
    _sync(centers)
    del trainset
    log(f"coarse train {time.perf_counter()-t0:.0f}s")

    rot = make_rotation_matrix(d, d, force_random=True)

    @jax.jit
    def encode_chunk(xc, c, rt):
        # inline nearest-center labels: one plain matmul + argmin.
        # kmeans_balanced.predict routes through the fused_l2_nn
        # XLA fallback, measured ~6× slower than this on CPU at
        # 8192 centers (2026-08-02). Labels can differ from the
        # library build path near Voronoi boundaries (inline argmin
        # vs fused-L2-NN predict) — this driver measures the tier,
        # not bit-identity with ivf_bq.build.
        cc = jnp.sum(c * c, axis=1)
        lab = jnp.argmin(cc[None, :] - 2.0 * (xc @ c.T), axis=1)
        # full-precision rotation like ivf_bq.build (sign stability
        # near zero)
        r = jnp.matmul(xc - c[lab], rt.T,
                       precision=matmul_precision())
        payload = jnp.concatenate(
            [lax.bitcast_convert_type(_pack_bits(r), jnp.int32),
             lax.bitcast_convert_type(
                 jnp.sum(r * r, axis=1)[:, None], jnp.int32),
             lax.bitcast_convert_type(
                 jnp.mean(jnp.abs(r), axis=1)[:, None], jnp.int32)],
            axis=1)
        return lab, payload

    # fused pass: ONE upload per chunk -> exact-GT partial top-k (the
    # tiled _knn_scan — small per-tile top_k widths) + BQ encode. GT
    # merge on host.
    t0 = time.perf_counter()
    best_d = np.full((nq, k), np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    labels = np.empty((n_rows,), np.int32)
    payload = np.empty((n_rows, w + 2), np.int32)
    pad_rows = n_chunks * step - n_rows
    for i, s in enumerate(range(0, n_rows, step)):
        e = min(s + step, n_rows)
        if e - s < step:  # pad the ragged tail: one compiled shape
            xc_h = np.full((step, d), 1e15, np.float32)
            xc_h[:e - s] = x[s:e]
            xc = jnp.asarray(xc_h)
        else:
            xc = jnp.asarray(x[s:e])
        cd, ci = brute_force.brute_force_knn(xc, q, k, mode="exact")
        lab_c, pay_c = encode_chunk(xc, centers, rot)
        cd_h = np.asarray(cd)
        ci_h = np.asarray(ci).astype(np.int64) + s
        keep = ci_h < n_rows  # padded sentinel rows drop out by value
        cd_h = np.where(keep, cd_h, np.inf)
        alld = np.concatenate([best_d, cd_h], axis=1)
        alli = np.concatenate([best_i, np.where(keep, ci_h, -1)], axis=1)
        sel = np.argsort(alld, axis=1)[:, :k]
        best_d = np.take_along_axis(alld, sel, axis=1)
        best_i = np.take_along_axis(alli, sel, axis=1)
        labels[s:e] = np.asarray(lab_c)[:e - s]
        payload[s:e] = np.asarray(pay_c)[:e - s]
        if i % 10 == 0:
            log(f"gt+encode chunk {i+1}/{n_chunks} "
                f"({time.perf_counter()-t0:.0f}s)")
    out["gt_encode_s"] = round(time.perf_counter() - t0, 1)
    log(f"gt+encode {out['gt_encode_s']}s "
        f"(payload {payload.nbytes/1e9:.2f} GB; padded tail "
        f"{pad_rows} rows)")

    t0 = time.perf_counter()
    counts = np.bincount(labels, minlength=n_lists)
    max_list = int(-(-counts.max() // 8) * 8)
    padded_gb = n_lists * max_list * (w + 2 + 1) * 4 / 1e9
    log(f"max_list {max_list} (mean {counts.mean():.0f}) — padded "
        f"codes+stats+ids {padded_gb:.2f} GB")
    if PLATFORM == "tpu" and padded_gb > 9.0:
        out["aborted"] = f"padded index {padded_gb:.1f} GB > 9 GB HBM budget"
        log(out["aborted"])
        _dump(out)
        return
    # payload uploads in 256 MB pieces, concatenated on device
    pay_dev = jnp.concatenate(
        [jnp.asarray(payload[s:min(s + (step << 3), n_rows)])
         for s in range(0, n_rows, step << 3)])
    bucketed, idx, _, _ = _bucketize_static(
        pay_dev, jnp.asarray(labels),
        jnp.arange(n_rows, dtype=jnp.int32), n_lists, max_list,
        compute_norms=False)
    _sync(bucketed)
    del pay_dev
    bits = lax.bitcast_convert_type(bucketed[:, :, :w], jnp.uint32)
    norms2 = lax.bitcast_convert_type(bucketed[:, :, w], jnp.float32)
    scales = lax.bitcast_convert_type(bucketed[:, :, w + 1], jnp.float32)
    index = ivf_bq.Index(
        centers=centers,
        centers_rot=jnp.matmul(centers, rot.T,
                               precision=matmul_precision()),
        rotation_matrix=rot, bits=bits, norms2=norms2, scales=scales,
        lists_indices=idx, list_sizes=jnp.asarray(counts, jnp.int32),
        metric=DistanceType.L2Expanded, size=n_rows, raw=x)
    del bucketed, payload
    code_gb = (bits.size * 4 + norms2.size * 4 + scales.size * 4
               + idx.size * 4) / 1e9
    out["build_bucketize_s"] = round(time.perf_counter() - t0, 1)
    out["max_list"] = max_list
    out["codes_stats_gb"] = round(code_gb, 2)
    log(f"bucketize {out['build_bucketize_s']}s — index codes+stats "
        f"{code_gb:.2f} GB (padded max_list {max_list}) for "
        f"{x.nbytes/1e9:.1f} GB of raw vectors")

    def recall(ids):
        got = np.asarray(ids)[:, :k]
        return float(np.mean([len(set(got[r]) & set(best_i[r])) / k
                              for r in range(nq)]))

    for factor, tag in ((0, "estimator"), (25, "rescored_f25")):
        # kk=250 ≤ the 256 select-kernel ceiling — the widest
        # exact-merge pool
        sp = ivf_bq.SearchParams(n_probes=64, rescore_factor=factor)
        t0 = time.perf_counter()
        bd, bi = ivf_bq.search(index, q, k, sp)
        _sync((bd, bi))
        cold = time.perf_counter() - t0
        rec = recall(bi)
        warm = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            bd, bi = ivf_bq.search(index, q, k, sp)
            _sync((bd, bi))
            warm = min(warm, time.perf_counter() - t0)
        out[f"recall_{tag}"] = rec
        out[f"search_{tag}_cold_s"] = round(cold, 1)
        out[f"search_{tag}_warm_s"] = round(warm, 3)
        out[f"search_{tag}_qps"] = round(nq / warm, 1)
        log(f"search p=64 {tag}: recall@{k}={rec:.4f} "
            f"cold {cold:.1f}s warm {warm*1e3:.0f}ms -> "
            f"{nq/warm:.0f} QPS")
    _dump(out)


def _dump(out):
    os.makedirs("tools/measure_out", exist_ok=True)
    with open("tools/measure_out/north_star_100m_bq.json", "w") as f:
        json.dump(out, f, indent=1)
    log(f"RESULT {json.dumps(out)}")


if __name__ == "__main__":
    a = sys.argv[1:]
    main(int(a[0]) if a else 100_000_000,
         int(a[1]) if len(a) > 1 else 8192)
