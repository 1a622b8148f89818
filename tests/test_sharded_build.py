"""Build-scaling parity tests (sharded multi-chip + streaming builds).

Three contracts, each against the single-device ``build()``:
  * the data-parallel balanced k-means trainer (psum'd sufficient
    statistics, replicated reseed) matches the single-device trainer on
    an 8-way CPU mesh within fp tolerance;
  * the list-sharded builds (``sharded_ivf_{flat,pq,bq}_build``) land
    the same rows in the same lists — identical ``list_sizes`` totals,
    recall within 0.02 — directly in the serving layout;
  * ``build_streaming`` reproduces the in-memory index from host chunks
    with every host→device transfer bounded by the chunk/train size
    (the O(chunk) device-allocation contract, asserted via the
    ``host_memory._fetch`` transfer hook).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.parallel.mesh import make_mesh


def _clustered(n_clusters, d, per, scale=6.0, noise=0.3, seed=0):
    """Well-separated gaussian mixture: the regime where cluster
    assignments are stable, so trainer parity is governed by reduction
    order, not by boundary-point flips."""
    rng = np.random.default_rng(seed)
    cents = rng.standard_normal((n_clusters, d)).astype(np.float32) * scale
    x = (cents[np.repeat(np.arange(n_clusters), per)]
         + noise * rng.standard_normal((n_clusters * per, d)))
    rng.shuffle(x)
    return jnp.asarray(x.astype(np.float32))


def _recall(i_got, i_exact, k):
    a, b = np.asarray(i_got), np.asarray(i_exact)
    return float(np.mean([len(set(a[r]) & set(b[r])) / k
                          for r in range(len(a))]))


def _gather_index(idx):
    """Pull a (possibly sharded) index's arrays onto the default device
    so the single-device search paths serve it."""
    reps = {}
    for f in dataclasses.fields(idx):
        v = getattr(idx, f.name)
        if isinstance(v, jax.Array):
            reps[f.name] = jnp.asarray(np.asarray(jax.device_get(v)))
    return dataclasses.replace(idx, **reps)


class TestShardedBalancedKmeans:
    def test_centers_match_single_device_8way(self, devices):
        from raft_tpu.cluster.kmeans_balanced import (balanced_kmeans,
                                                      balanced_kmeans_sharded)
        mesh = make_mesh(devices=devices)
        assert mesh.shape["data"] == 8
        x = _clustered(16, 16, 128, seed=3)
        c1 = balanced_kmeans(x, 16, n_iters=8, seed=3)
        c2 = balanced_kmeans_sharded(x, 16, n_iters=8, seed=3, mesh=mesh)
        # same host-side init + same EM math → centers agree up to the
        # psum reduction order (assignments are stable on this mixture)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   rtol=1e-3, atol=1e-3)

    def test_deterministic_across_runs(self, devices):
        from raft_tpu.cluster.kmeans_balanced import balanced_kmeans_sharded
        mesh = make_mesh(devices=devices)
        x = _clustered(8, 16, 64, seed=5)
        c1 = balanced_kmeans_sharded(x, 8, n_iters=6, seed=1, mesh=mesh)
        c2 = balanced_kmeans_sharded(x, 8, n_iters=6, seed=1, mesh=mesh)
        # bit-identical: the cached shard_map plan reruns one compiled
        # program, and the reseed step runs on replicated statistics
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))

    def test_quantization_cost_parity(self, devices):
        from raft_tpu.cluster.kmeans_balanced import (_nn, balanced_kmeans,
                                                      balanced_kmeans_sharded)
        mesh = make_mesh(devices=devices)
        # harder mixture (overlapping clusters) — centers may drift
        # between the paths, but the clustering COST must stay on par
        x = _clustered(16, 16, 128, scale=1.5, noise=1.0, seed=7)
        c1 = balanced_kmeans(x, 16, n_iters=10, seed=2)
        c2 = balanced_kmeans_sharded(x, 16, n_iters=10, seed=2, mesh=mesh)
        _, d1 = _nn(x, c1)
        _, d2 = _nn(x, c2)
        cost1 = float(jnp.mean(d1))
        cost2 = float(jnp.mean(d2))
        assert cost2 <= cost1 * 1.05, (cost1, cost2)


class TestShardedIvfFlatBuild:
    def test_parity_with_single_device_build(self, devices):
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.neighbors.brute_force import brute_force_knn
        from raft_tpu.parallel.ivf import sharded_ivf_flat_build
        mesh = make_mesh(devices=devices)
        x = _clustered(16, 32, 128, seed=0)
        n, k = x.shape[0], 10
        params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=8,
                                      kmeans_trainset_fraction=1.0)
        idx1 = ivf_flat.build(x, params)
        idx2 = sharded_ivf_flat_build(x, params, mesh)
        # identical list_sizes totals: every row lands in exactly one list
        assert int(np.asarray(jax.device_get(idx1.list_sizes)).sum()) == n
        assert int(np.asarray(jax.device_get(idx2.list_sizes)).sum()) == n
        q = x[:128]
        sp = ivf_flat.SearchParams(n_probes=4)
        _, ie = brute_force_knn(x, q, k, mode="exact")
        r1 = _recall(ivf_flat.search(idx1, q, k, sp)[1], ie, k)
        r2 = _recall(ivf_flat.search(_gather_index(idx2), q, k, sp)[1],
                     ie, k)
        assert abs(r1 - r2) <= 0.02, (r1, r2)

    def test_lists_sharded_over_mesh(self, devices):
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel.ivf import sharded_ivf_flat_build
        mesh = make_mesh(devices=devices)
        x = _clustered(8, 16, 64, seed=2)
        idx = sharded_ivf_flat_build(
            x, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), mesh)
        # the build lands in serving position: list axis sharded over
        # the data axis, ready for distributed_ivf_flat_search
        assert idx.lists_data.shape[0] == 8
        assert len(idx.lists_data.sharding.device_set) == 8
        # global ids, each exactly once
        ids = np.asarray(jax.device_get(idx.lists_indices))
        real = ids[ids >= 0]
        assert sorted(real.tolist()) == list(range(x.shape[0]))


class TestExchangeRows:
    def test_ragged_exchange_under_list_skew(self, devices):
        """Most rows in one list: each shard sends each shard one run
        padded to the largest (source, destination) run, not every
        list padded to the largest list, and every row lands in its
        list in source-shard-major, row order."""
        from jax.sharding import PartitionSpec as P
        from raft_tpu.parallel.ivf import _exchange_rows
        mesh = make_mesh(devices=devices)
        s, rows, n_lists = len(devices), 96, 4 * len(devices)
        rng = np.random.default_rng(0)
        lbl = np.where(rng.random(s * rows) < 0.6, 1,
                       rng.integers(0, n_lists, s * rows)).astype(np.int32)
        lbl[rng.random(s * rows) < 0.1] = n_lists          # padding rows
        ids = np.where(lbl < n_lists, np.arange(s * rows), -1)
        ids = ids.astype(np.int32)
        payload = np.stack([ids, 2 * ids], 1).astype(np.float32)
        runs = np.zeros((s, s), int)
        for src in range(s):
            part = lbl[src * rows:(src + 1) * rows]
            part = part[part < n_lists]
            np.add.at(runs[src], part // (n_lists // s), 1)
        width = int(runs.max())
        totals = np.bincount(lbl[lbl < n_lists], minlength=n_lists)
        ml = int(totals.max())
        assert width < rows and ml > width  # a skew worth exchanging

        fn = jax.jit(jax.shard_map(
            lambda p, l, i: _exchange_rows(p, l, i, n_lists, s, "data",
                                           width, ml),
            mesh=mesh, in_specs=(P("data", None), P("data"), P("data")),
            out_specs=(P("data", None, None), P("data", None))))
        data, idx = map(np.asarray, fn(payload, lbl, ids))
        assert data.shape == (n_lists, ml, 2) and idx.shape == (n_lists, ml)
        for li in range(n_lists):
            want = np.flatnonzero(lbl == li)     # source-major, row order
            got = idx[li]
            np.testing.assert_array_equal(got[:len(want)], want)
            assert (got[len(want):] == -1).all()
            np.testing.assert_array_equal(data[li, :len(want)],
                                          payload[want])
            assert (data[li, len(want):] == 0).all()


class TestShardedIvfPqBuild:
    def test_selfhit_and_ids(self, devices):
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.parallel.ivf import sharded_ivf_pq_build
        mesh = make_mesh(devices=devices)
        x = _clustered(16, 32, 128, seed=1)
        n = x.shape[0]
        idx = sharded_ivf_pq_build(
            x, ivf_pq.IndexParams(n_lists=8, kmeans_n_iters=4,
                                  pq_bits=4, pq_dim=8), mesh)
        assert int(np.asarray(jax.device_get(idx.list_sizes)).sum()) == n
        assert idx.decoded is not None  # serving cache built shard-local
        q = x[:64]
        _, iq = ivf_pq.search(_gather_index(idx), q, 10,
                              ivf_pq.SearchParams(n_probes=8))
        iqn = np.asarray(iq)
        assert ((iqn >= -1) & (iqn < n)).all()
        self_hit = np.mean([int(r in iqn[j]) for j, r in
                            enumerate(range(len(q)))])
        assert self_hit >= 0.7, self_hit


class TestShardedIvfBqBuild:
    def test_selfhit_and_exact_rescore(self, devices):
        from raft_tpu.neighbors import ivf_bq
        from raft_tpu.parallel.ivf import sharded_ivf_bq_build
        mesh = make_mesh(devices=devices)
        x = _clustered(16, 32, 128, seed=4)
        n = x.shape[0]
        idx = sharded_ivf_bq_build(
            x, ivf_bq.IndexParams(n_lists=8, kmeans_n_iters=4), mesh)
        assert int(np.asarray(jax.device_get(idx.list_sizes)).sum()) == n
        q = x[:64]
        g = _gather_index(idx)
        d_, i_ = ivf_bq.search(g, q, 10,
                               ivf_bq.SearchParams(n_probes=8,
                                                   rescore_factor=8))
        ibn = np.asarray(i_)
        self_hit = np.mean([int(r in ibn[j]) for j, r in
                            enumerate(range(len(q)))])
        assert self_hit >= 0.7, self_hit
        # rescored distances are exact for the returned ids
        want = np.sum((np.asarray(x)[ibn] - np.asarray(q)[:, None]) ** 2,
                      axis=2)
        np.testing.assert_allclose(np.asarray(d_), want, rtol=1e-4,
                                   atol=1e-4)


class TestBuildStreaming:
    def _chunks(self, x, size):
        return [np.asarray(x[s:s + size]) for s in range(0, len(x), size)]

    def test_exact_parity_full_trainset(self):
        from raft_tpu.neighbors import host_memory, ivf_flat
        x = _clustered(48, 32, 128, scale=4.0, noise=0.5, seed=6)
        n = x.shape[0]
        params = ivf_flat.IndexParams(n_lists=32, kmeans_n_iters=8,
                                      kmeans_trainset_fraction=1.0)
        h = host_memory.build_streaming(iter(self._chunks(x, 1024)),
                                        params, train_rows=n)
        idx = ivf_flat.build(x, params)
        # identical trainset → identical centers → identical membership
        sizes_mem = np.asarray(jax.device_get(idx.list_sizes))
        sizes_str = (h.lists_indices >= 0).sum(axis=1)
        np.testing.assert_array_equal(sizes_mem, sizes_str)
        ids_mem = np.asarray(jax.device_get(idx.lists_indices))
        for l in range(params.n_lists):
            assert (set(h.lists_indices[l][h.lists_indices[l] >= 0])
                    == set(ids_mem[l][ids_mem[l] >= 0]))

    def test_o_chunk_device_allocation_and_recall(self):
        from raft_tpu.neighbors import host_memory, ivf_flat
        from raft_tpu.neighbors.brute_force import brute_force_knn
        x = _clustered(48, 32, 128, scale=4.0, noise=0.5, seed=8)
        n, k = x.shape[0], 10
        chunk, train = 1024, 2048
        seen = []
        orig = host_memory._fetch

        def spy(a):
            seen.append(int(np.shape(a)[0]) if np.ndim(a) else 0)
            return orig(a)

        host_memory._fetch = spy
        try:
            h = host_memory.build_streaming(
                iter(self._chunks(x, chunk)),
                ivf_flat.IndexParams(n_lists=32, kmeans_n_iters=8),
                train_rows=train)
        finally:
            host_memory._fetch = orig
        # the transfer-guard assertion: every host→device move during
        # the build is bounded by the chunk/trainset size — device
        # allocation is O(chunk), never O(n)
        assert seen and max(seen) <= max(chunk, train) < n
        q = x[:128]
        _, ie = brute_force_knn(x, q, k, mode="exact")
        r_stream = _recall(host_memory.search(
            h, q, k, ivf_flat.SearchParams(n_probes=8))[1], ie, k)
        idx = ivf_flat.build(x, ivf_flat.IndexParams(n_lists=32,
                                                     kmeans_n_iters=8))
        r_mem = _recall(ivf_flat.search(
            idx, q, k, ivf_flat.SearchParams(n_probes=8))[1], ie, k)
        assert abs(r_stream - r_mem) <= 0.02, (r_stream, r_mem)


class TestPqReseedThreshold:
    def test_default_unchanged(self):
        from raft_tpu.neighbors import ivf_pq
        x = _clustered(8, 16, 64, seed=9)
        base = ivf_pq.IndexParams(n_lists=4, kmeans_n_iters=4, pq_bits=4,
                                  pq_dim=4)
        explicit = dataclasses.replace(base, reseed_threshold=0.25)
        i1 = ivf_pq.build(x, base, seed=0)
        i2 = ivf_pq.build(x, explicit, seed=0)
        # surfacing the knob must not move the default trainer
        np.testing.assert_array_equal(np.asarray(i1.pq_centers),
                                      np.asarray(i2.pq_centers))
        np.testing.assert_array_equal(np.asarray(i1.codes),
                                      np.asarray(i2.codes))

    def test_zero_disables_reseeding(self):
        from raft_tpu.neighbors import ivf_pq
        x = _clustered(8, 16, 64, seed=10)
        n = x.shape[0]
        params = ivf_pq.IndexParams(n_lists=4, kmeans_n_iters=4,
                                    pq_bits=4, pq_dim=4,
                                    reseed_threshold=0.0)
        idx = ivf_pq.build(x, params, seed=0)
        q = x[:32]
        _, iq = ivf_pq.search(idx, q, 5, ivf_pq.SearchParams(n_probes=4))
        iqn = np.asarray(iq)
        assert ((iqn >= -1) & (iqn < n)).all()
        self_hit = np.mean([int(r in iqn[j]) for j, r in
                            enumerate(range(len(q)))])
        assert self_hit >= 0.6, self_hit
