"""The main path's Pallas kernels compiled for a described (not
attached) TPU v5e at real widths — what Mosaic refuses here would fail
on the chip (the `on-chip-measurement` guide, section 2). Nothing runs;
each test asserts the kernel survived as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: a
worker that loads the TPU library holds it until it exits, so only the
worker given this file may load it."""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import raft_tpu.ops.pallas_fused_knn as fused_knn
import raft_tpu.ops.pallas_fused_l2_nn as fused_nn
import raft_tpu.ops.pallas_ivf_scan as ivf_scan

# chip_smoke.py phase shapes: 2M x 128 corpus, 1000 queries, 2048
# lists of ~1000 rows (max_list 2048 covers the balanced build's
# spread), serving batches of 256 (the probe count only sizes the XLA
# probe inversion ahead of the kernels, so it is kept small)
N, DIM, NQ = 2_000_000, 128, 1000
N_LISTS, MAX_LIST, BATCH, PROBES, CAP = 2048, 2048, 256, 32, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(one_chip, monkeypatch):
    """Compile for the described chip with the kernels compiled, not
    interpreted (the CPU backend would otherwise pick interpret mode)."""
    for mod in (fused_knn, fused_nn, ivf_scan):
        monkeypatch.setattr(mod, "pallas_interpret", lambda *a, **k: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_text(fn, *shapes):
        args = [sds(*s) for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_text


F32, I32 = jnp.float32, jnp.int32
QUERIES = ((BATCH, DIM), F32)
PROBE_IDS = ((BATCH, PROBES), I32)
LIST_F32 = ((N_LISTS, MAX_LIST), F32)
LIST_IDS = ((N_LISTS, MAX_LIST), I32)
CENTERS = ((N_LISTS, DIM), F32)


def test_fused_knn(compiled):
    text = compiled(lambda x, y: fused_knn.fused_knn_pallas(x, y, 32),
                    ((NQ, DIM), F32), ((N, DIM), F32))
    assert "tpu_custom_call" in text


def test_fused_l2_nn(compiled):
    # the build's coarse assignment: every row against the list centers
    text = compiled(lambda x, y: fused_nn.fused_l2_nn_pallas(x, y),
                    ((N, DIM), F32), CENTERS)
    assert "tpu_custom_call" in text


def test_ivf_flat_fused_scan(compiled):
    text = compiled(
        lambda q, data, norms, ids, probes: ivf_scan.ivf_list_scan_pallas(
            q, data, norms, ids, probes, 10, CAP, fused=True),
        QUERIES, ((N_LISTS, MAX_LIST, DIM), F32), LIST_F32, LIST_IDS,
        PROBE_IDS)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused", [True, False])
def test_ivf_flat_scan_ragged_tail(compiled, fused):
    """max_list 2000 at k 32 (bins 128) ends in an 80-row partial
    window, completed in VMEM; no pad of the lists survives."""
    ml = 2000
    text = compiled(
        lambda q, data, norms, ids, probes: ivf_scan.ivf_list_scan_pallas(
            q, data, norms, ids, probes, 32, CAP, fused=fused),
        QUERIES, ((N_LISTS, ml, DIM), F32), ((N_LISTS, ml), F32),
        ((N_LISTS, ml), I32), PROBE_IDS)
    assert "tpu_custom_call" in text
    assert f"[{N_LISTS},{ml + 48}," not in text


@pytest.mark.parametrize("k", [10, 32])
def test_ivf_pq_fused_code_scan(compiled, k):
    """k=10 put auto bins (4k=40 -> 64) inside one 128-lane tile, a
    reshape Mosaic refuses; PQ bins are now whole lane tiles."""
    text = compiled(
        lambda q, c, books, codes, norms, ids, probes:
            ivf_scan.ivf_pq_code_scan_pallas(
                q, c, books, codes, norms, ids, probes, k, CAP,
                fused=True),
        QUERIES, CENTERS, ((64, 256, DIM // 64), F32),
        ((N_LISTS, MAX_LIST, 64), jnp.uint8), LIST_F32, LIST_IDS,
        PROBE_IDS)
    assert "tpu_custom_call" in text


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs (jit, loops) included."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("fused", [True, False])
def test_ivf_pq_code_scan_extents(compiled, fused):
    """The served IVF-PQ cell's code scan: 2048 lists of max_list 3600,
    pq_dim 64 at 256 codes, k 256 at bins 128, 128 probes. One int32
    extent per list is scalar-prefetched (the custom call's first
    operand), and each cell's chunk loop (a dynamic trip count) reads
    its code, norm and id blocks at dynamic lane offsets."""
    max_list, pq_dim, probes, cap = 3600, 64, 128, 72
    shapes = (QUERIES, CENTERS, ((pq_dim, 256, DIM // pq_dim), F32),
              ((N_LISTS, max_list, pq_dim), jnp.uint8),
              ((N_LISTS, max_list), F32), ((N_LISTS, max_list), I32),
              ((BATCH, probes), I32))

    def scan(q, c, books, codes, norms, ids, pr):
        return ivf_scan.ivf_pq_code_scan_pallas(
            q, c, books, codes, norms, ids, pr, 256, cap, bins=128,
            fused=fused)

    closed = jax.make_jaxpr(scan)(*[jax.ShapeDtypeStruct(*s)
                                    for s in shapes])
    # the code scan is the one kernel with a scalar-prefetched operand
    # (the unfused tier's merge adds a select_k kernel)
    kernels = [e for e in _eqns(closed.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["grid_mapping"].num_index_operands == 1]
    assert len(kernels) == 1
    assert "while" in {e.primitive.name
                       for e in _eqns(kernels[0].params["jaxpr"])}
    text = compiled(scan, *shapes)
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call and f"operand_layout_constraints={{s32[{N_LISTS}]{{0}}, " \
        in call[0]


def test_ivf_bq_fused_scan(compiled):
    text = compiled(
        lambda q, c, bits, n2, sc, ids, probes: ivf_scan.ivf_bq_scan_pallas(
            q, c, bits, n2, sc, ids, probes, 10, CAP, fused=True),
        QUERIES, CENTERS, ((N_LISTS, MAX_LIST, DIM // 32), jnp.uint32),
        LIST_F32, LIST_F32, LIST_IDS, PROBE_IDS)
    assert "tpu_custom_call" in text


def test_sharded_balanced_em_on_four_chips(topo, monkeypatch):
    """The list-sharded build's coarse trainer (``balanced_kmeans_
    sharded``) calls the fused L2-NN kernel inside ``jax.shard_map``;
    its outputs must carry the mesh's varying axes, or the kernel is
    refused before it compiles. Compiled for the 2x2 mesh at 4096
    lists x 128 (rows are a depth, kept small)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import raft_tpu.ops.dispatch as dispatch
    from raft_tpu.cluster import kmeans_balanced

    monkeypatch.setattr(fused_nn, "pallas_interpret", lambda *a, **k: False)
    monkeypatch.setattr(dispatch, "pallas_enabled", lambda *a, **k: True)
    builds = []

    def capture(key, build):
        builds.append(build)
        raise StopIteration  # the program is lowered below, not run

    monkeypatch.setattr(kmeans_balanced, "_sharded_em_plan", capture)
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, lists = 1 << 16, 4096
    with pytest.raises(StopIteration):
        kmeans_balanced.balanced_kmeans_sharded(
            np.zeros((4 * lists * 2, DIM), np.float32), lists, 2, mesh=mesh)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    text = builds[0]().lower(
        sds((n, DIM), F32, P("data", None)), sds((n,), jnp.bool_, P("data")),
        sds((lists, DIM), F32, P())).compile().as_text()
    assert "tpu_custom_call" in text
