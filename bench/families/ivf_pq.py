"""IVF-PQ on one chip: ``ivf_pq.build`` (raw vectors kept for the exact
rescore) served by ``SearchServer``."""

from __future__ import annotations

from system import System, serve_config


def build(cfg: dict, x, warm, pool, mesh) -> System:
    import jax
    import jax.numpy as jnp
    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_pq
    idx_cfg, search = cfg["index"], cfg["search"]
    index = ivf_pq.build(x, ivf_pq.IndexParams(
        n_lists=idx_cfg["n_lists"], pq_dim=idx_cfg["pq_dim"],
        pq_bits=idx_cfg["pq_bits"], keep_raw=idx_cfg["keep_raw"]))
    jax.block_until_ready(index.codes)
    sc = serve_config(cfg)
    server = serve.SearchServer.from_index(
        index, warm, search["k"],
        params=ivf_pq.SearchParams(
            n_probes=search["n_probes"],
            rescore_factor=search["rescore_factor"],
            lut_dtype=jnp.dtype(search["lut_dtype"])),
        config=sc)
    return System(server=server, pool=pool, index=index,
                  layout={"kind": "pq", "dim": index.dim,
                          "n_probes": search["n_probes"],
                          "pq_dim": idx_cfg["pq_dim"],
                          "bytes_per_row": idx_cfg["pq_dim"]
                          * idx_cfg["pq_bits"] // 8})
