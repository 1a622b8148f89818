"""Where the IVF-PQ code scan's time goes, on the chip.

Builds the served IVF-PQ index of ``bench/configs/ivf_pq.2m.json`` (the
same corpus, 2048 lists, pq_dim 64 at 8 bits), takes one batch of 256
held-out queries at 128 probes and k 256 (k 32 × rescore factor 8, bins
128 — what the serving plan runs), and times the code scan alone:

* ``fused``: decode + score + binned min + the in-kernel top-k merge;
* ``unfused``: the same kernel with the merge compiled out (it writes
  the candidate blocks; the XLA merge after it is a few ms);
* each over the list extents (``ext``, what the scan runs) and over
  every stored row (``all``: extents forced past the padded length,
  the work of a scan that decodes padding), both also at 512-row
  chunks, plus the fused scan with every extent 0 (the grid's fixed
  cost).

Decode ≈ ``unfused``; merge ≈ ``fused − unfused``. Each figure is the
median wall of ``PROFILE_REPS`` blocking calls after a warm-up. Writes
``chiprun_out/pq_scan_profile.json``.

Run: python tools/profile_ivf_pq_scan.py
Env: PROFILE_PLATFORM=cpu with PROFILE_ROWS/PROFILE_LISTS for a small
smoke of the tool itself; PROFILE_REPS (default 5).
"""
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("PROFILE_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["PROFILE_PLATFORM"])
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "bench"))
from raft_tpu.core.compile_cache import enable as _enable_cache  # noqa: E402
_enable_cache()

import corpus  # noqa: E402  (bench/corpus.py: the benchmark's data)
from raft_tpu.neighbors import _ivf_scan, ivf_pq  # noqa: E402
from raft_tpu.ops import pallas_ivf_scan as pis  # noqa: E402

with open(os.path.join(ROOT, "bench", "configs", "ivf_pq.2m.json")) as f:
    CFG = json.load(f)
ROWS = int(os.environ.get("PROFILE_ROWS", CFG["corpus"]["rows"]))
LISTS = int(os.environ.get("PROFILE_LISTS", CFG["index"]["n_lists"]))
REPS = int(os.environ.get("PROFILE_REPS", 5))
NQ, PROBES = 256, min(CFG["search"]["n_probes"], LISTS)
K = CFG["search"]["k"] * CFG["search"]["rescore_factor"]
BINS = max(128, (32 * K) // PROBES)   # the serving plan's bins


def main():
    c = CFG["corpus"]
    x, _, pool = corpus.make(c["data_seed"], 1, ROWS, c["dim"],
                             c["n_centers"], NQ, 8)
    t0 = time.perf_counter()
    idx = ivf_pq.build(x, ivf_pq.IndexParams(
        n_lists=LISTS, pq_dim=CFG["index"]["pq_dim"],
        pq_bits=CFG["index"]["pq_bits"]))
    jax.block_until_ready(idx.codes)
    build_s = time.perf_counter() - t0
    q = jnp.asarray(pool[:NQ])
    probes = _ivf_scan.coarse_probes(q, idx.centers, PROBES,
                                     use_pallas=True)
    cap = _ivf_scan.probe_cap(probes, LISTS)
    q_rot = q @ idx.rotation_matrix.T
    norms = ivf_pq._base_code_norms(idx)
    ops = (q_rot, idx.centers_rot, idx.pq_centers, idx.codes, norms,
           idx.lists_indices, probes)
    max_list = idx.codes.shape[1]
    bins = min(BINS, max_list)
    sizes = np.asarray(idx.list_sizes)
    out = {"device": str(jax.devices()[0].device_kind), "rows": ROWS,
           "lists": LISTS, "max_list": int(max_list),
           "mean_list": float(sizes.mean()), "cap": int(cap),
           "k": K, "bins": bins, "build_s": build_s, "seconds": {}}
    orig_ext, orig_chunk = pis._scan_extents, pis._PQ_CHUNK_ROWS

    def every_row(ids, qmap):
        return jnp.full((ids.shape[0],), 1 << 30, jnp.int32)

    def no_row(ids, qmap):
        return jnp.zeros((ids.shape[0],), jnp.int32)

    variants = [("fused.ext", True, orig_ext, orig_chunk),
                ("unfused.ext", False, orig_ext, orig_chunk),
                ("fused.all", True, every_row, orig_chunk),
                ("unfused.all", False, every_row, orig_chunk),
                ("fused.ext.chunk512", True, orig_ext, 512),
                ("fused.all.chunk512", True, every_row, 512),
                ("fused.zero", True, no_row, orig_chunk)]
    for name, fused, ext_fn, chunk in variants:
        pis._scan_extents, pis._PQ_CHUNK_ROWS = ext_fn, chunk
        fn = jax.jit(lambda *a, fused=fused: pis.ivf_pq_code_scan_pallas(
            *a, K, cap, bins=bins, fused=fused))
        jax.block_until_ready(fn(*ops))
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*ops))
            walls.append(time.perf_counter() - t0)
        out["seconds"][name] = statistics.median(walls)
        print(name, repr(out["seconds"][name]), flush=True)
    pis._scan_extents, pis._PQ_CHUNK_ROWS = orig_ext, orig_chunk
    split, sub_ml, chunk = pis._pq_cells(
        max_list, pis._pq_bins(bins, K, max_list), cap, idx.pq_dim,
        idx.pq_centers.shape[1], idx.rot_dim, jnp.bfloat16)
    out.update(split=split, sub_ml=sub_ml, chunk=chunk,
               decoded_share=pis.pq_decoded_share(
                   idx.lists_indices, K, cap, bins, idx.pq_dim,
                   idx.pq_centers.shape[1], idx.rot_dim, jnp.bfloat16))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "pq_scan_profile.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
