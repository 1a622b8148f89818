"""Multi-node-multi-device algorithms over a Mesh (SURVEY.md §2.12/§5).

The reference builds MNMG algorithms (in cuML/cuGraph) from RAFT pieces +
``handle.get_comms()``; this package ships them in-framework: distributed
brute-force k-NN (sharded DB + ring top-k merge), MNMG k-means (sharded
data + psum'd centroid statistics), and sharded IVF search.
"""

from raft_tpu.parallel.mesh import make_mesh, shard_rows, replicate
from raft_tpu.parallel.knn import distributed_knn
from raft_tpu.parallel.kmeans import distributed_kmeans_fit, distributed_kmeans_step
from raft_tpu.parallel.ivf import (
    get_comms,
    shard_ivf_flat,
    shard_ivf_pq,
    distributed_ivf_flat_search,
    distributed_ivf_pq_search,
    DistributedIvfFlat,
    DistributedIvfPq,
    distributed_ivf_flat_build,
    distributed_ivf_flat_search_parts,
    distributed_ivf_pq_build,
    distributed_ivf_pq_search_parts,
    distributed_ivf_bq_build,
    distributed_ivf_bq_search_parts,
    sharded_ivf_flat_build,
    sharded_ivf_pq_build,
    sharded_ivf_bq_build,
)

__all__ = [
    "make_mesh", "shard_rows", "replicate",
    "get_comms",
    "distributed_knn",
    "distributed_kmeans_fit", "distributed_kmeans_step",
    "shard_ivf_flat", "shard_ivf_pq",
    "distributed_ivf_flat_search", "distributed_ivf_pq_search",
    "DistributedIvfFlat", "DistributedIvfPq",
    "distributed_ivf_flat_build", "distributed_ivf_flat_search_parts",
    "distributed_ivf_pq_build", "distributed_ivf_pq_search_parts",
    "distributed_ivf_bq_build", "distributed_ivf_bq_search_parts",
    "sharded_ivf_flat_build", "sharded_ivf_pq_build",
    "sharded_ivf_bq_build",
]
