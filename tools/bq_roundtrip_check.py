"""BQ bit-payload roundtrip assertion (ADVICE r3 #2 follow-through):
on the CURRENT platform, build a small ivf_bq index and verify the
packed sign words that come OUT of the bucketize scatter are exactly
the words a direct host-side re-encode produces — i.e. the int32
payload path (pack → concat → scatter → slice → bitcast) is
bit-exact on this backend. Runs in seconds, so a chip call can
include it to certify the path on real TPU hardware.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

# CPU pre-flight knob
if os.environ.get("CHECK_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["CHECK_PLATFORM"])


def main() -> None:
    from raft_tpu.neighbors import ivf_bq

    print(f"[bq-roundtrip] platform: {jax.devices()[0].platform}",
          flush=True)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4000, 64), np.float32)
    idx = ivf_bq.build(x, ivf_bq.IndexParams(n_lists=8,
                                             kmeans_n_iters=4))

    # host re-encode: the index's own centers/rotation, numpy math
    c = np.asarray(idx.centers)
    rot = np.asarray(idx.rotation_matrix)
    lists_idx = np.asarray(idx.lists_indices)
    bits = np.asarray(idx.bits)
    norms2 = np.asarray(idx.norms2)
    scales = np.asarray(idx.scales)
    n_lists, ml, w = bits.shape
    d = x.shape[1]
    # A device bit may legitimately differ from the host re-encode only
    # where the rotated component is within FP rounding of zero (the
    # device matmul runs at matmul_precision(), not numpy's exact f32
    # evaluation order). Everywhere else a mismatch means the payload
    # path corrupted bits. Borderline threshold, relative to the row's
    # mean |r| (the scale the sign code quantizes against): 1e-4 for
    # the near-f32 tiers; 1e-2 when RAFT_TPU_MATMUL_PRECISION=default
    # (single-pass bf16, ~4e-3 relative matmul error).
    import jax.lax as jlax
    from raft_tpu.core.precision import matmul_precision
    rel_tol = (1e-2 if matmul_precision() == jlax.Precision.DEFAULT
               else 1e-4)
    checked = 0
    borderline_bits = 0
    for l in range(n_lists):
        for s in range(ml):
            gid = lists_idx[l, s]
            if gid < 0:
                continue
            r = (x[gid] - c[l]) @ rot.T
            scale = float(np.abs(r).mean())
            # absolute floor so a degenerate row (r ~ 0 → scale ~ 0)
            # can't excuse every bit: components with any real
            # magnitude stay firm
            firm = np.abs(r) > rel_tol * scale + 1e-12
            j = np.arange(d)
            got = (bits[l, s, j // 32] >> (j % 32)) & 1
            want = (r > 0).astype(np.uint32)
            bad = (got != want) & firm
            assert not bad.any(), \
                (l, s, np.nonzero(bad)[0], r[bad])
            borderline_bits += int(((got != want) & ~firm).sum())
            assert np.isclose(norms2[l, s], float(r @ r), rtol=1e-4), \
                (l, s, norms2[l, s], float(r @ r))
            assert np.isclose(scales[l, s], scale, rtol=1e-4), (l, s)
            checked += 1
    assert checked == 4000, checked
    print(f"[bq-roundtrip] {checked} rows bit-exact through "
          f"pack/scatter/bitcast ({borderline_bits} FP-boundary bits "
          "excused): PASS", flush=True)


if __name__ == "__main__":
    main()
