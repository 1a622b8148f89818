"""IVF-Flat on one chip: ``ivf_flat.build`` served by ``SearchServer``."""

from __future__ import annotations

from system import System, serve_config


def build(cfg: dict, x, warm, pool, mesh) -> System:
    import jax
    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_flat
    idx_cfg, search = cfg["index"], cfg["search"]
    index = ivf_flat.build(x, ivf_flat.IndexParams(n_lists=idx_cfg["n_lists"]))
    jax.block_until_ready(index.lists_data)
    sc = serve_config(cfg)
    server = serve.SearchServer.from_index(
        index, warm, search["k"],
        params=ivf_flat.SearchParams(n_probes=search["n_probes"]),
        config=sc)
    return System(server=server, pool=pool, index=index,
                  layout={"kind": "flat", "dim": index.dim,
                          "n_probes": search["n_probes"],
                          "bytes_per_row": index.dim * 4})
