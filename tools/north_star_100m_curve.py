"""100M×128 north-star COVERAGE CURVE (the recall-ceiling artifact at
the true north-star scale, CPU-feasible): generate the bench mixture
with a NUMPY-resident corpus (51 GB — device work runs on slices),
compute exact ground truth for a query subset, train coarse centers on
a subsample, and emit the recall ceiling for every n_probes. The
10M runs showed end-to-end searches match these ceilings
digit-for-digit, so the curve IS the flat-recall surface round 5 will
operate on at v5e-64 scale.

Run: python tools/north_star_100m_curve.py [N_ROWS] [N_LISTS]
Output: tools/measure_out/north_star_100m_curve.json
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402


def log(msg):
    print(f"[100m-curve] {msg}", flush=True)


def main(n_rows=100_000_000, n_lists=8192):
    from raft_tpu.cluster import kmeans_balanced

    d, nq, k = 128, 100, 10
    out = {"n_rows": n_rows, "dim": d, "n_lists": n_lists, "k": k,
           "dist": "clustered"}
    key = jax.random.key(0)
    nc = max(64, min(8192, n_rows // 125))
    centers_mix = jax.random.normal(jax.random.fold_in(key, 1), (nc, d))

    @jax.jit
    def mix(c, lab_c, key_c):
        return c[lab_c] + jax.random.normal(
            key_c, (lab_c.shape[0], c.shape[1]))

    t0 = time.perf_counter()
    x = np.empty((n_rows, d), np.float32)   # host-resident corpus
    step = 1 << 21
    for i, s in enumerate(range(0, n_rows, step)):
        e = min(s + step, n_rows)
        lab_c = jax.random.randint(
            jax.random.fold_in(key, 1000 + i), (e - s,), 0, nc)
        x[s:e] = np.asarray(mix(centers_mix, lab_c,
                                jax.random.fold_in(key, 2000 + i)))
    qlab = jax.random.randint(jax.random.fold_in(key, 4), (nq,), 0, nc)
    q = mix(centers_mix, qlab, jax.random.fold_in(key, 5))
    jax.block_until_ready(q)
    log(f"data gen {time.perf_counter()-t0:.0f}s "
        f"({x.nbytes/1e9:.1f} GB host-resident)")

    # exact ground truth, chunked device scan
    t0 = time.perf_counter()
    best_d = np.full((nq, k), np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    qq = np.asarray(jnp.sum(q * q, axis=1))

    @jax.jit
    def chunk_topk(xc, qm):
        dd = (jnp.sum(xc * xc, 1)[None, :] - 2.0 * qm @ xc.T)
        nd, ni = jax.lax.top_k(-dd, k)
        return -nd, ni

    for s in range(0, n_rows, step):
        e = min(s + step, n_rows)
        cd, ci = chunk_topk(jnp.asarray(x[s:e]), q)
        cd = np.asarray(cd) + qq[:, None]
        ci = np.asarray(ci) + s
        alld = np.concatenate([best_d, cd], axis=1)
        alli = np.concatenate([best_i, ci], axis=1)
        sel = np.argsort(alld, axis=1)[:, :k]
        best_d = np.take_along_axis(alld, sel, axis=1)
        best_i = np.take_along_axis(alli, sel, axis=1)
    log(f"exact GT {time.perf_counter()-t0:.0f}s")

    # coarse centers: bench EM count, ~125 rows/center trainset capped
    # at 1M rows for single-core feasibility
    t0 = time.perf_counter()
    n_train = min(1_000_000, 125 * n_lists)
    from raft_tpu.util.host_sample import sample_rows
    trainset = jnp.asarray(x[sample_rows(n_rows, n_train, 0)])
    centers = kmeans_balanced.build_hierarchical(trainset, n_lists, 10)
    jax.block_until_ready(centers)
    log(f"coarse train {time.perf_counter()-t0:.0f}s "
        f"({n_train} trainset rows)")

    t0 = time.perf_counter()
    gt_rows = jnp.asarray(x[best_i.reshape(-1)])
    gt_labels = np.asarray(
        kmeans_balanced.predict(gt_rows, centers)).reshape(nq, k)
    coarse = (jnp.sum(centers * centers, 1)[None, :]
              - 2.0 * q @ centers.T)
    probe_order = np.asarray(jnp.argsort(coarse, axis=1))
    probe_rank = np.empty_like(probe_order)
    np.put_along_axis(probe_rank, probe_order,
                      np.arange(n_lists)[None, :].repeat(nq, 0), axis=1)
    gt_rank = np.take_along_axis(probe_rank, gt_labels, axis=1)
    curve = {}
    for p in (64, 128, 192, 256, 384, 512, 768, 1024):
        if p > n_lists:
            continue
        curve[p] = float(np.mean(gt_rank < p))
    out["ceiling_curve"] = curve
    log(f"coverage curve {time.perf_counter()-t0:.0f}s: " +
        " ".join(f"p{p}={r:.3f}" for p, r in curve.items()))

    # the footprints this scale implies (real dtypes, arithmetic on
    # the actual shapes — the BQ index at this n is ~d/8+12+4 B/row)
    out["flat_f32_gb"] = round(n_rows * d * 4 / 1e9, 1)
    out["pq8_codes_gb"] = round(n_rows * (d // 4 + 8) / 1e9, 2)
    out["bq_bits_gb"] = round(n_rows * (d // 8 + 12 + 4) / 1e9, 2)

    os.makedirs("tools/measure_out", exist_ok=True)
    with open("tools/measure_out/north_star_100m_curve.json", "w") as f:
        json.dump(out, f, indent=1)
    log(f"RESULT {json.dumps(out)}")


if __name__ == "__main__":
    a = sys.argv[1:]
    main(int(a[0]) if a else 100_000_000,
         int(a[1]) if len(a) > 1 else 8192)
