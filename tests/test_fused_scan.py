"""Fused IVF scan + select-k Pallas kernel (ISSUE 7).

The fused tier keeps the per-query top-k state resident in VMEM across
the list grid (``pallas_ivf_scan._merge_state`` — the ``_select_kernel``
output-block-revisiting trick), so the fine phase is ONE pallas_call
where the unfused path dispatches scan → gather → select_k. These run
under the Pallas interpreter on the CPU test mesh — the kernel-logic
contract is what's validated here, like tests/test_ops_pallas.py
(tests/test_tpu_compile.py compiles the kernels for the chip).

Coverage per the issue checklist: interpret-mode parity vs the exact
XLA ``inverted_scan`` tier (``bins == max_list`` ⇒ bit-exact ids)
across l2/ip metrics, f32/bf16/int8 storage tiers, ragged list sizes
(the blob fixture's lists are naturally uneven) and the cap-overflow
mask path; a dispatch-count test asserting the fused route compiles to
one ``pallas_call``; plan/ladder routing with zero steady-state
compiles; the coarse-selection fallback counter.
"""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.neighbors import _ivf_scan, ivf_bq, ivf_flat, ivf_pq, plan
from raft_tpu.random import make_blobs


def _cdiff(before, after, name):
    return (after["counters"].get(name, 0.0)
            - before["counters"].get(name, 0.0))


def _unfused(index, q, k, sp):
    """The eager search along its own route with the scan called
    ``fused=False`` — the unfused Pallas reference."""
    r = plan.route(index, q.shape[0], k, sp)
    assert r.fused and r.pallas
    return plan.run(index, q, dataclasses.replace(r, fused=False), sp)


def _recall(got, want, k):
    return np.mean([
        len(set(np.asarray(got[r])) & set(np.asarray(want[r]))) / k
        for r in range(got.shape[0])])


def _count_pallas_calls(closed):
    """Count pallas_call primitives recursively through a jaxpr
    (pjit/scan/cond sub-jaxprs included) — the dispatch-count oracle."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from subjaxprs(item)

    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
                continue  # the kernel body holds no nested pallas_call
            for p in eqn.params.values():
                for sub in subjaxprs(p):
                    n += walk(sub)
        return n

    return walk(closed.jaxpr if isinstance(closed, ClosedJaxpr)
                else closed)


@pytest.fixture(scope="module")
def flat_data():
    x, _ = make_blobs(n_samples=6000, n_features=24, centers=40,
                      cluster_std=3.0, seed=0)
    q, _ = make_blobs(n_samples=80, n_features=24, centers=40,
                      cluster_std=3.0, seed=1)
    return jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(q))


@pytest.fixture(scope="module")
def flat_index(flat_data):
    x, _ = flat_data
    return ivf_flat.build(x, ivf_flat.IndexParams(n_lists=32,
                                                  kmeans_n_iters=4))


class TestFusedFlat:
    """IVF-Flat: the fused kernel vs the exact XLA tier and the unfused
    Pallas tier. The blob fixture's list sizes are RAGGED (cluster_std
    3.0 over 40 centers into 32 lists), so the id −1 pad-row masking is
    always exercised."""

    def test_exact_bins_ids_bit_identical_to_xla_tier(self, flat_index,
                                                      flat_data,
                                                      monkeypatch):
        """bins == max_list ⇒ both tiers select the exact global top-k
        of the same f32 scores: ids must be BIT-IDENTICAL (the issue
        acceptance contract)."""
        _, q = flat_data
        k, ml = 8, int(flat_index.lists_indices.shape[1])
        sp = ivf_flat.SearchParams(n_probes=16, scan_order="list",
                                   scan_bins=ml)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_f, i_f = ivf_flat.search(flat_index, q, k, sp)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "never")  # → xla_inverted
        d_x, i_x = ivf_flat.search(flat_index, q, k, sp)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_x))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_x),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8"])
    def test_exact_bins_matches_unfused_pallas_storage_tiers(
            self, flat_data, storage, monkeypatch):
        """Across the narrow-storage tiers the fused kernel shares the
        unfused kernel's scoring body verbatim — exact bins ⇒ identical
        candidates ⇒ identical ids."""
        x, q = flat_data
        idx = ivf_flat.build(x, ivf_flat.IndexParams(
            n_lists=32, kmeans_n_iters=4, storage_dtype=storage))
        k, ml = 8, int(idx.lists_indices.shape[1])
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list",
                                   scan_bins=ml)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_f, i_f = ivf_flat.search(idx, q, k, sp)
        d_u, i_u = _unfused(idx, q, k, sp)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_u))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_u),
                                   rtol=1e-5, atol=1e-5)

    def test_ip_metric_matches_probe_major_exact(self, flat_data,
                                                 monkeypatch):
        """ip core: the exact reference is the probe-major scan (the
        XLA list tier is l2-only); with exact bins the fused kernel's
        negated-similarity ranking must reproduce it."""
        from raft_tpu.distance.distance_types import DistanceType
        x, q = flat_data
        idx = ivf_flat.build(x, ivf_flat.IndexParams(
            n_lists=32, kmeans_n_iters=4,
            metric=DistanceType.InnerProduct))
        k, ml = 8, int(idx.lists_indices.shape[1])
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_f, i_f = ivf_flat.search(idx, q, k, ivf_flat.SearchParams(
            n_probes=8, scan_order="list", scan_bins=ml))
        d_p, i_p = ivf_flat.search(idx, q, k, ivf_flat.SearchParams(
            n_probes=8, scan_order="probe"))
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_p))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_p),
                                   rtol=1e-4, atol=1e-4)

    def test_cap_overflow_mask_path(self, flat_index, flat_data,
                                    monkeypatch):
        """A pinned cap smaller than the drop-free width sheds the
        highest-rank probes; the fused kernel's qmap simply never holds
        the shed pairs — same drops, same ids as the unfused merge's
        inv_pos ≥ cap mask."""
        _, q = flat_data
        k, ml = 8, int(flat_index.lists_indices.shape[1])
        sp = ivf_flat.SearchParams(n_probes=16, scan_order="list",
                                   scan_bins=ml, probe_cap=8)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_f, i_f = ivf_flat.search(flat_index, q, k, sp)
        d_u, i_u = _unfused(flat_index, q, k, sp)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_u))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_u),
                                   rtol=1e-5, atol=1e-5)

    def test_default_bins_recall_within_0005_of_unfused(self, flat_index,
                                                        flat_data,
                                                        monkeypatch):
        """At the default (binned) operating point the fused and
        unfused tiers share the identical binned candidate sets — the
        acceptance bound is recall within 0.005 of the unfused tier."""
        x, q = flat_data
        k = 8
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        _, i_f = ivf_flat.search(flat_index, q, k, sp)
        _, i_u = _unfused(flat_index, q, k, sp)
        xn, qn = np.asarray(x), np.asarray(q)
        d2 = ((xn ** 2).sum(1)[None, :] + (qn ** 2).sum(1)[:, None]
              - 2 * qn @ xn.T)
        exact = np.argsort(d2, axis=1)[:, :k]
        rec_f = _recall(np.asarray(i_f), exact, k)
        rec_u = _recall(np.asarray(i_u), exact, k)
        assert rec_f >= rec_u - 0.005, (rec_f, rec_u)


class TestDispatchCount:
    """The headline structural claim: ONE compiled fine-phase dispatch
    where there were three (scan pallas_call → XLA gather → select_k
    pallas_call)."""

    def _probes_cap(self, flat_index, q, n_probes):
        probes = _ivf_scan.coarse_probes(q, flat_index.centers, n_probes)
        cap = _ivf_scan.probe_cap(probes, flat_index.n_lists)
        return probes, cap

    def test_fused_fine_phase_is_one_pallas_call(self, flat_index,
                                                 flat_data):
        from raft_tpu.ops.pallas_ivf_scan import ivf_list_scan_pallas
        _, q = flat_data
        k = 8
        probes, cap = self._probes_cap(flat_index, q, 8)

        def fine(fused):
            return jax.make_jaxpr(functools.partial(
                ivf_list_scan_pallas, k=k, cap=cap, fused=fused))(
                    q, flat_index.lists_data, flat_index.lists_norms,
                    flat_index.lists_indices, probes)

        assert _count_pallas_calls(fine(True)) == 1
        # the unfused fine phase: scan kernel + select_k kernel
        assert _count_pallas_calls(fine(False)) == 2

    def test_full_search_collapses_three_to_one(self, flat_index,
                                                flat_data):
        """End-to-end fused_list_search: coarse select_k + fine phase.
        Unfused = 3 pallas_calls (coarse, scan, merge select_k); fused
        = 2 (coarse, fused scan+select) — the fine phase collapsed."""
        _, q = flat_data
        k = 8
        _, cap = self._probes_cap(flat_index, q, 8)

        def full(fused):
            fn = functools.partial(
                _ivf_scan.fused_list_search, k=k, n_probes=8, cap=cap,
                bins=0, sqrt=False, kind="l2", use_pallas=True,
                gather="rows", fused=fused)
            return jax.make_jaxpr(fn)(
                q, flat_index.centers, flat_index.lists_data,
                flat_index.lists_norms, flat_index.lists_indices,
                jnp.float32(1.0))

        assert _count_pallas_calls(full(False)) == 3
        assert _count_pallas_calls(full(True)) == 2


def _synthetic_lists(n_lists, max_list, dim, storage, seed=0):
    """Bucketed lists as an index stores them: ragged fills (id −1 pad
    rows at each list's end), norms of the stored values."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_lists, max_list, dim)).astype(np.float32)
    scale = 1.0
    if storage == "int8":
        scale = 1.0 / 32
        x = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    data = jnp.asarray(x).astype(storage)
    fill = rng.integers(max_list // 2, max_list + 1, size=n_lists)
    fill[0] = max_list                       # one list runs to the end
    rows = np.arange(max_list)[None, :]
    ids = np.where(rows < fill[:, None],
                   np.arange(n_lists * max_list).reshape(n_lists, -1), -1)
    ids = jnp.asarray(ids.astype(np.int32))
    vals = data.astype(jnp.float32) * scale
    norms = jnp.where(ids >= 0, jnp.sum(vals * vals, axis=2), 0.0)
    return data, norms, ids, scale


class TestUnpaddedLists:
    """The flat list scan reads the lists as the index stores them and
    completes a partial last bins window in VMEM: every tier must return
    exactly what it returns for the same lists padded in HBM to a
    multiple of bins (the layout it used to build per batch)."""

    # (max_list, k, bins, pinned cap): 200 at bins 128, 72 at bins 64
    # (auto, k 16), the cap-overflow mask path, and a max_list that the
    # bins divide (the kernel unchanged)
    CASES = {"ml200_b128": (200, 32, 128, 0),
             "ml72_b64": (72, 16, 0, 0),
             "cap_overflow": (200, 32, 128, 8),
             "aligned": (256, 32, 128, 0)}

    @pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("metric", ["l2", "ip"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bit_identical_to_padded_lists(self, case, metric, storage):
        from raft_tpu.ops.pallas_ivf_scan import (_Layout,
                                                  ivf_list_scan_pallas,
                                                  ragged_tail)
        max_list, k, bins, pin = self.CASES[case]
        n_lists, dim, nq, n_probes = 8, 16, 24, 3
        data, norms, ids, scale = _synthetic_lists(n_lists, max_list,
                                                   dim, storage)
        q = jnp.asarray(np.random.default_rng(1).normal(
            size=(nq, dim)).astype(np.float32))
        centers = data[:, 0].astype(jnp.float32) * scale
        probes = _ivf_scan.coarse_probes(q, centers, n_probes, kind=metric)
        cap = pin or _ivf_scan.probe_cap(probes, n_lists)
        if pin:
            assert cap < _ivf_scan.probe_cap(probes, n_lists)
        assert ragged_tail(max_list, bins, k) == (case != "aligned")
        pad = _Layout.resolve_bins(bins, k, max_list)
        pad = -max_list % pad
        padded = (jnp.pad(data, ((0, 0), (0, pad), (0, 0))),
                  jnp.pad(norms, ((0, 0), (0, pad))),
                  jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1))
        for fused in (True, False):
            scan = functools.partial(
                ivf_list_scan_pallas, k=k, cap=cap, scale=scale,
                bins=bins, metric=metric, fused=fused)
            d_u, i_u = scan(q, data, norms, ids, probes)
            d_p, i_p = scan(q, *padded, probes)
            np.testing.assert_array_equal(np.asarray(i_u), np.asarray(i_p))
            np.testing.assert_array_equal(np.asarray(d_u), np.asarray(d_p))
            assert (np.asarray(i_u) >= 0).any()


class TestFlatPlanReadsStoredLists:
    """Structure of the flat serving program (``plan._program``):
    no ``pad`` copies the lists per batch, the scan kernel takes the
    stored (n_lists, max_list, dim) array, and a plan build that takes
    the in-VMEM tail says so once on ``raft.ivf_scan.ragged_tail``."""

    @staticmethod
    def _eqns(jaxpr):
        from jax.extend.core import ClosedJaxpr, Jaxpr

        def subs(v):
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v
            elif isinstance(v, (tuple, list)):
                for item in v:
                    yield from subs(item)

        for eqn in jaxpr.eqns:
            yield eqn
            for p in eqn.params.values():
                for sub in subs(p):
                    yield from TestFlatPlanReadsStoredLists._eqns(sub)

    @pytest.mark.parametrize("scan_bins,ragged", [(0, True), (-1, False),
                                                  (48, False)])
    def test_no_list_pad_and_tail_counted_once(self, flat_index, flat_data,
                                               scan_bins, ragged,
                                               monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        _, q = flat_data
        k = 8
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        n_lists, max_list, dim = flat_index.lists_data.shape
        # 432 rows: auto bins (64 at k 8) leave a 48-row tail; exact
        # bins and 48 divide it
        assert (max_list % 64 != 0) and (max_list % 48 == 0)
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list",
                                   scan_bins=scan_bins)
        r = dataclasses.replace(plan.route(flat_index, q.shape[0], k, sp),
                                cap=16)
        closed = jax.make_jaxpr(
            lambda q, *ops: plan._program(q, *ops, r=r))(
                q, *plan._operands(r, flat_index))
        eqns = list(self._eqns(closed.jaxpr))
        pads = [e.invars[0].aval.shape for e in eqns
                if e.primitive.name == "pad"]
        assert not [s for s in pads if s[:2] == (n_lists, max_list)], pads
        kernel_in = [v.aval.shape for e in eqns
                     if e.primitive.name == "pallas_call"
                     for v in e.invars]
        assert (n_lists, max_list, dim) in kernel_in

        name = "raft.ivf_scan.ragged_tail.total{family=ivf_flat}"
        before = obs.snapshot()
        p = plan.build_plan(flat_index, q, k, sp, warm=False)
        mid = obs.snapshot()
        assert _cdiff(before, mid, name) == (1 if ragged else 0)
        # values: the plan answers what the cold path answers
        d0, i0 = ivf_flat.search(flat_index, q, k, sp)
        d1, i1 = p.search(q, block=True)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


class TestFusedBq:
    @pytest.fixture(scope="class")
    def bq_data(self):
        x, _ = make_blobs(n_samples=6000, n_features=64, centers=40,
                          cluster_std=3.0, seed=0)
        q, _ = make_blobs(n_samples=80, n_features=64, centers=40,
                          cluster_std=3.0, seed=1)
        return jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(q))

    @pytest.mark.parametrize("metric", ["l2", "ip"])
    def test_exact_bins_matches_unfused(self, bq_data, metric,
                                        monkeypatch):
        """Exact bins ⇒ identical estimator candidates (shared scoring
        body; the ip center term moves in-kernel but commutes with the
        binned min) ⇒ identical rescored output."""
        from raft_tpu.distance.distance_types import DistanceType
        x, q = bq_data
        m = (DistanceType.InnerProduct if metric == "ip"
             else DistanceType.L2Expanded)
        idx = ivf_bq.build(x, ivf_bq.IndexParams(n_lists=32,
                                                 kmeans_n_iters=4,
                                                 metric=m))
        ml = int(idx.lists_indices.shape[1])
        sp = ivf_bq.SearchParams(n_probes=16, scan_bins=ml)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_f, i_f = ivf_bq.search(idx, q, 8, sp)
        d_u, i_u = _unfused(idx, q, 8, sp)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_u))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_u),
                                   rtol=1e-5, atol=1e-5)


class TestFusedPq:
    @pytest.fixture(scope="class")
    def pq_setup(self):
        x, _ = make_blobs(n_samples=6000, n_features=32, centers=40,
                          cluster_std=3.0, seed=0)
        q, _ = make_blobs(n_samples=80, n_features=32, centers=40,
                          cluster_std=3.0, seed=1)
        x = jnp.asarray(np.asarray(x))
        q = jnp.asarray(np.asarray(q))
        idx = ivf_pq.build(x, ivf_pq.IndexParams(n_lists=32,
                                                 kmeans_n_iters=4,
                                                 pq_dim=8))
        return idx, x, q

    @pytest.mark.parametrize("metric", ["l2", "ip"])
    def test_wrapper_exact_bins_ids_match_unfused(self, pq_setup,
                                                  metric):
        """Direct wrapper parity (replacing merge_cap_major's tail):
        exact bins, same candidates, same ids."""
        from raft_tpu.ops.pallas_ivf_scan import ivf_pq_code_scan_pallas
        idx, x, q = pq_setup
        k, ml = 8, int(idx.codes.shape[1])
        probes = _ivf_scan.coarse_probes(q, idx.centers, 8, kind=metric)
        cap = _ivf_scan.probe_cap(probes, idx.n_lists)
        q_rot = q @ idx.rotation_matrix.T
        norms = ivf_pq._code_norms(idx.codes, idx.pq_centers,
                                   idx.lists_indices)
        kw = dict(bins=ml, metric=metric)
        d_u, i_u = ivf_pq_code_scan_pallas(
            q_rot, idx.centers_rot, idx.pq_centers, idx.codes, norms,
            idx.lists_indices, probes, k, cap, **kw)
        d_f, i_f = ivf_pq_code_scan_pallas(
            q_rot, idx.centers_rot, idx.pq_centers, idx.codes, norms,
            idx.lists_indices, probes, k, cap, fused=True, **kw)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_u))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_u),
                                   rtol=1e-4, atol=1e-4)

    def test_vmem_split_path_agrees(self, pq_setup, monkeypatch):
        """A tiny VMEM budget forces split > 1 (sub-cells sharing their
        list's qmap/query blocks via g // split): the resident-state
        merge must land the same neighbors."""
        from raft_tpu.ops import pallas_ivf_scan as pis
        idx, x, q = pq_setup
        k = 8
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="codes")
        d0, i0 = ivf_pq.search(idx, q, k, sp)
        monkeypatch.setattr(pis, "_VMEM_LIMIT", 1 << 18)  # force split
        d1, i1 = ivf_pq.search(idx, q, k, sp)
        assert _recall(np.asarray(i1), np.asarray(i0), k) >= 0.95
        np.testing.assert_allclose(np.asarray(d1)[:, :k // 2],
                                   np.asarray(d0)[:, :k // 2],
                                   rtol=0.05, atol=0.5)

    def test_codes_search_recall_vs_unfused(self, pq_setup, monkeypatch):
        """Public route at default bins: same binned candidate sets —
        recall within 0.005 of the unfused code scan."""
        idx, x, q = pq_setup
        k = 8
        sp = ivf_pq.SearchParams(n_probes=8, scan_mode="codes")
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        _, i_f = ivf_pq.search(idx, q, k, sp)
        _, i_u = _unfused(idx, q, k, sp)
        xn, qn = np.asarray(x), np.asarray(q)
        d2 = ((xn ** 2).sum(1)[None, :] + (qn ** 2).sum(1)[:, None]
              - 2 * qn @ xn.T)
        exact = np.argsort(d2, axis=1)[:, :k]
        rec_f = _recall(np.asarray(i_f), exact, k)
        rec_u = _recall(np.asarray(i_u), exact, k)
        assert rec_f >= rec_u - 0.005, (rec_f, rec_u)


def _pq_layout(per_cluster: bool):
    """A hand-built IVF-PQ layout over 8 lists of max_list 2048 (pq_dim
    8, 32 codes, rot_dim 32) whose extents cover the code scan's cases:
    an empty list, an extent off the chunk grid, a full list (extent ==
    the padded length), a −1 hole before the last real row, a 3-row
    list, one that ends inside a second sub-list, and two lists that
    carry rows but no query probes."""
    rng = np.random.default_rng(7)
    n_lists, max_list, pq_dim, n_codes, pq_len = 8, 2048, 8, 32, 4
    rot_dim = pq_dim * pq_len
    sizes = [0, 300, 2048, 200, 3, 1300, 400, 400]
    ids = np.full((n_lists, max_list), -1, np.int64)
    for lst, n in enumerate(sizes):
        ids[lst, :n] = lst * max_list + np.arange(n)
    ids[3, 50:60] = -1                                 # the hole
    codes = rng.integers(0, n_codes, (n_lists, max_list, pq_dim),
                         dtype=np.uint8)
    norms = rng.uniform(1.0, 4.0, (n_lists, max_list)).astype(np.float32)
    books = rng.normal(size=(n_lists if per_cluster else pq_dim, n_codes,
                             pq_len)).astype(np.float32)
    q_rot = rng.normal(size=(6, rot_dim)).astype(np.float32)
    centers_rot = rng.normal(size=(n_lists, rot_dim)).astype(np.float32)
    # query 0 probes the empty and the 3-row list only (k 8 leaves it
    # five −1 sentinels); lists 6 and 7 are probed by no query
    probes = np.array([[0, 4], [1, 2], [2, 3], [5, 1], [3, 5], [2, 4]],
                      np.int32)
    return dict(q_rot=jnp.asarray(q_rot), centers_rot=jnp.asarray(
        centers_rot), pq_centers=jnp.asarray(books),
        codes=jnp.asarray(codes), code_norms=jnp.asarray(norms),
        lists_indices=jnp.asarray(ids.astype(np.int32)),
        probes=jnp.asarray(probes)), sizes


class TestPqExtents:
    """The code scan decodes each cell only up to its list's extent, in
    chunks, and skips cells past it and lists no query probes. Its
    answers must be bit-identical to the same kernels run over every
    row (extents forced past the padded length)."""

    K = 8

    @staticmethod
    def _force_split(monkeypatch, max_list, cap, want):
        """Shrink the VMEM budget until the code scan splits each list
        into ``want`` sub-lists (``test_vmem_split_path_agrees``'s
        lever); returns ``(split, sub_ml, chunk)``."""
        from raft_tpu.ops import pallas_ivf_scan as pis
        lim = pis._VMEM_LIMIT
        while True:
            geo = pis._pq_cells(max_list, 128, cap, 8, 32, 32,
                                jnp.bfloat16)
            if geo[0] >= want:
                return geo
            lim //= 2
            monkeypatch.setattr(pis, "_VMEM_LIMIT", lim)

    @pytest.mark.parametrize("fused", [True, False])
    @pytest.mark.parametrize("split", [1, 2])
    @pytest.mark.parametrize("per_cluster", [False, True])
    @pytest.mark.parametrize("metric", ["l2", "ip"])
    def test_bit_identical_to_every_row(self, metric, per_cluster, split,
                                        fused, monkeypatch):
        from raft_tpu.ops import pallas_ivf_scan as pis
        args, sizes = _pq_layout(per_cluster)
        cap = _ivf_scan.probe_cap(args["probes"], len(sizes))
        geo = self._force_split(monkeypatch, 2048, cap, split)
        assert geo[0] == split and geo[2] < geo[1]   # chunks per cell
        # the extents hold every case: hole scanned, unprobed lists 0
        lay = pis._Layout(args["probes"], len(sizes), 2048, cap, 128,
                          self.K)
        ext = np.asarray(pis._scan_extents(args["lists_indices"],
                                           lay.qmap))
        np.testing.assert_array_equal(ext, [0, 300, 2048, 200, 3, 1300,
                                            0, 0])
        assert 300 % geo[2] and 1300 % geo[2]
        scan = functools.partial(pis.ivf_pq_code_scan_pallas, k=self.K,
                                 cap=cap, metric=metric,
                                 per_cluster=per_cluster, fused=fused)
        d_e, i_e = scan(**args)
        monkeypatch.setattr(
            pis, "_scan_extents",
            lambda ids, qmap: jnp.full((ids.shape[0],), 1 << 30, jnp.int32))
        d_a, i_a = scan(**args)
        i_e, i_a = np.asarray(i_e), np.asarray(i_a)
        np.testing.assert_array_equal(i_e, i_a)
        np.testing.assert_array_equal(np.asarray(d_e), np.asarray(d_a))
        # the sentinel: query 0 has three real rows for k 8
        assert (i_e[0] >= 0).sum() == 3 and (i_e[0, 3:] == -1).all()
        assert (i_e[1:] >= 0).all()
        assert not np.isin(i_e, np.arange(3 * 2048 + 50,
                                          3 * 2048 + 60)).any()


class TestPqDecodedShare:
    """``raft.ivf_scan.pq.decoded_share``: the share of the stored code
    rows the scan decodes at full batches — each list's extent rounded
    up to the chunk, over ``n_cells · sub_ml``."""

    def test_hand_built_layout(self):
        from raft_tpu.ops import pallas_ivf_scan as pis
        args, _ = _pq_layout(False)
        split, sub_ml, chunk = pis._pq_cells(2048, 128, 3, 8, 32, 32,
                                             jnp.bfloat16)
        assert (split, sub_ml, chunk) == (1, 2048, 256)
        got = pis.pq_decoded_share(args["lists_indices"], 8, 3, 0, 8, 32,
                                   32, jnp.bfloat16)
        # extents 0, 300, 2048, 200 (hole inside), 3, 1300, 400, 400
        # → decoded 0 + 512 + 2048 + 256 + 256 + 1536 + 512 + 512
        assert got == 5632 / (8 * 2048)

    def test_plan_build_sets_gauge(self, monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        from raft_tpu.ops import pallas_ivf_scan as pis
        x, _ = make_blobs(n_samples=3000, n_features=32, centers=20,
                          cluster_std=3.0, seed=3)
        idx = ivf_pq.build(jnp.asarray(np.asarray(x)), ivf_pq.IndexParams(
            n_lists=16, kmeans_n_iters=4, pq_dim=8))
        q = jnp.asarray(np.asarray(x)[:16])
        sp = ivf_pq.SearchParams(n_probes=4, scan_mode="codes")
        p = plan.build_plan(idx, q, 8, sp, warm=False)
        got = obs.snapshot()["gauges"]["raft.ivf_scan.pq.decoded_share"]
        ids = np.asarray(idx.lists_indices)
        ml = ids.shape[1]
        ext = np.where(ids >= 0, np.arange(1, ml + 1), 0).max(axis=1)
        split, sub_ml, chunk = pis._pq_cells(
            ml, pis._pq_bins(0, 8, ml), p.cap, 8, idx.pq_centers.shape[1],
            idx.rot_dim, sp.lut_dtype)
        want = (-(-ext // chunk) * chunk).sum() / (ids.shape[0] * split
                                                    * sub_ml)
        assert 0.0 < want <= 1.0
        assert got == want


class TestPlanRoutesFused:
    """Acceptance: SearchPlan / PlanLadder route through the fused
    kernel with zero steady-state compiles — asserted from the
    raft.plan.cache counters, as in test_serve."""

    def test_plan_key_carries_fused_and_zero_steady_state(
            self, flat_index, flat_data, monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        _, q = flat_data
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        before = obs.snapshot()
        p = plan.warmup(flat_index, q, 8, sp)
        mid = obs.snapshot()
        # the plan build recorded its fused routing decision
        assert _cdiff(before, mid,
                      "raft.ivf_scan.fused.total{family=ivf_flat}") >= 1
        for _ in range(3):
            p.search(q, block=True)
        after = obs.snapshot()
        assert _cdiff(mid, after, "raft.plan.cache.misses") == 0
        assert _cdiff(mid, after, "raft.plan.build.total") == 0
        assert _cdiff(mid, after,
                      "raft.ivf_scan.resolve_cap.syncs") == 0
        # value parity with the cold fused route
        d0, i0 = ivf_flat.search(flat_index, q, 8, sp)
        d1, i1 = p.search(q, block=True)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    def test_plan_ladder_zero_steady_state(self, flat_index, flat_data,
                                           monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        from raft_tpu.serve.ladder import PlanLadder
        _, q = flat_data
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        ladder = PlanLadder.build(flat_index, q, 8, sp, shapes=(16, 80))
        before = obs.snapshot()
        for rows in (5, 16, 80):
            _, pl_ = ladder.plan_for(rows, 0)
            pl_.search(q[:pl_.nq], block=True)
        after = obs.snapshot()
        assert _cdiff(before, after, "raft.plan.cache.misses") == 0
        assert _cdiff(before, after, "raft.plan.build.total") == 0
        assert _cdiff(before, after,
                      "raft.ivf_scan.resolve_cap.syncs") == 0


class TestCoarseFallbackCounter:
    def test_counts_only_past_the_selectk_bound(self):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        before = obs.snapshot()
        _ivf_scan.count_coarse_fallback(300, use_pallas=True)
        _ivf_scan.count_coarse_fallback(300, use_pallas=False)
        _ivf_scan.count_coarse_fallback(64, use_pallas=True)
        after = obs.snapshot()
        assert _cdiff(before, after,
                      "raft.ivf_scan.coarse.fallback") == 1


class TestLcVariants:
    """The list-scan formulations agree: Pallas at auto/pinned lists per
    grid cell and the XLA inverted scan (kernel tiers run under the
    Pallas interpreter on the test mesh)."""

    def _index(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal((3000, 32), np.float32)
        return ivf_flat.build(
            x, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)), x

    def test_lc_variants_and_xla_agree(self, monkeypatch):
        idx, x = self._index()
        q = jnp.asarray(x[:64])
        cap = _ivf_scan.resolve_cap(idx.cap_cache, q, idx.centers,
                                    ivf_flat.SearchParams(), 8,
                                    idx.n_lists, use_pallas=True)

        def run(use_pallas, lc):
            return _ivf_scan.fused_list_search(
                q, idx.centers, idx.lists_data, idx.lists_norms,
                idx.lists_indices, jnp.float32(1.0), k=10, n_probes=8,
                cap=cap, bins=-1, sqrt=False, kind="l2",
                use_pallas=use_pallas, lc=lc)

        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        d_auto, i_auto = run(True, 0)
        d_lc1, i_lc1 = run(True, 1)
        d_lc4, i_lc4 = run(True, 4)
        d_xla, i_xla = run(False, 0)
        # exact bins (-1): all four formulations are exact → identical
        np.testing.assert_array_equal(np.asarray(i_auto),
                                      np.asarray(i_lc1))
        np.testing.assert_array_equal(np.asarray(i_auto),
                                      np.asarray(i_lc4))
        np.testing.assert_array_equal(np.asarray(i_auto),
                                      np.asarray(i_xla))
        np.testing.assert_allclose(np.asarray(d_auto),
                                   np.asarray(d_lc1), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d_auto),
                                   np.asarray(d_xla), rtol=1e-4,
                                   atol=1e-4)

    def test_lc_env_threads_through_search(self, monkeypatch):
        """RAFT_TPU_IVF_LC is resolved per call: results stay correct
        whichever value the env pins."""
        from raft_tpu.ops.pallas_ivf_scan import lc_mode

        idx, x = self._index()
        q = x[:32]
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        monkeypatch.setenv("RAFT_TPU_IVF_LC", "2")
        assert lc_mode() == 2
        d2, i2 = ivf_flat.search(idx, q, 10, sp)
        monkeypatch.setenv("RAFT_TPU_IVF_LC", "1")
        assert lc_mode() == 1  # env flip takes effect (static arg)
        d1, i1 = ivf_flat.search(idx, q, 10, sp)
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))


def _shaped_index(family, metric):
    """An index of bare shapes (64 lists of 1000 rows, dim 16): enough
    for ``plan.route``, which reads shapes only — any device work on it
    would fail."""
    from raft_tpu.distance.distance_types import DistanceType
    S = jax.ShapeDtypeStruct
    m = (DistanceType.InnerProduct if metric == "ip"
         else DistanceType.L2Expanded)
    n_lists, ml, dim = 64, 1000, 16
    ids = S((n_lists, ml), jnp.int32)
    sizes = S((n_lists,), jnp.int32)
    center = S((n_lists, dim), jnp.float32)
    if family == "ivf_flat":
        return ivf_flat.Index(
            centers=center, lists_data=S((n_lists, ml, dim), jnp.float32),
            lists_indices=ids, lists_norms=S((n_lists, ml), jnp.float32),
            list_sizes=sizes, metric=m, size=50000)
    rot = S((dim, dim), jnp.float32)
    if family == "ivf_pq":
        return ivf_pq.Index(
            centers=center, centers_rot=center, rotation_matrix=rot,
            pq_centers=S((4, 256, 4), jnp.float32),
            codes=S((n_lists, ml, 4), jnp.uint8), lists_indices=ids,
            list_sizes=sizes, metric=m, pq_bits=8, size=50000)
    return ivf_bq.Index(
        centers=center, centers_rot=center, rotation_matrix=rot,
        bits=S((n_lists, ml, 1), jnp.uint32),
        norms2=S((n_lists, ml), jnp.float32),
        scales=S((n_lists, ml), jnp.float32), lists_indices=ids,
        list_sizes=sizes, metric=m, size=50000)


class TestRouteTable:
    """The one kernel choice, per family × Pallas on/off × kk ≤/> 256 ×
    l2/ip × small/large batch, read off ``plan.route`` over an index of
    bare shapes (nothing is traced or compiled)."""

    @pytest.mark.parametrize("nq", [8, 512])
    @pytest.mark.parametrize("metric", ["l2", "ip"])
    @pytest.mark.parametrize("big_kk", [False, True])
    @pytest.mark.parametrize("pallas", [True, False])
    @pytest.mark.parametrize("family", ["ivf_flat", "ivf_pq", "ivf_bq"])
    def test_route(self, family, pallas, big_kk, metric, nq, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_PALLAS",
                           "always" if pallas else "never")
        index = _shaped_index(family, metric)
        if family == "ivf_flat":
            k, kk = (300, 300) if big_kk else (32, 32)
            params = ivf_flat.SearchParams(n_probes=16)
        else:
            k, kk = 32, (512 if big_kk else 256)
            mod = ivf_pq if family == "ivf_pq" else ivf_bq
            params = mod.SearchParams(n_probes=16,
                                      rescore_factor=kk // k)
        r = plan.route(index, nq, k, params)
        # list-major: BQ always, the PQ code scan always; otherwise a
        # batch that reuses lists (512 × 16 probes over 64 lists), on a
        # scan that has the metric's core (XLA: l2 only)
        listed = (family == "ivf_bq" or (family == "ivf_pq" and pallas)
                  or (nq == 512 and (metric == "l2"
                                     or (pallas and family == "ivf_flat"))))
        assert r.order == ("list" if listed else "probe")
        assert r.pallas == (pallas and listed)
        assert r.fused == (r.pallas and not big_kk)
        assert r.scan_mode == {"ivf_pq": "codes" if pallas
                               else "reconstruct"}.get(family, "")
        assert (r.k, r.kk, r.n_probes, r.kind) == (k, kk, 16, metric)
        # PQ/BQ spread a 32·kk candidate pool over the probes (floor
        # 128 a list); the flat scan resolves its own 4·k bins
        pool = min(max(128, 32 * kk // 16), 1000)
        assert r.bins == (pool if listed and family != "ivf_flat" else 0)
        assert r.gather == "rows" and r.cap == 0
        assert not r.rescoring and not r.host_tail


class TestPlanRejects:
    """A plan refuses exactly what the eager IVF-PQ search refuses."""

    @pytest.fixture(scope="class")
    def pq_index(self):
        x, _ = make_blobs(n_samples=2000, n_features=16, centers=10,
                          seed=5)
        return ivf_pq.build(jnp.asarray(np.asarray(x)), ivf_pq.IndexParams(
            n_lists=8, kmeans_n_iters=2, pq_dim=4)), np.asarray(x)[:16]

    @pytest.mark.parametrize("bad", [
        dict(lut_dtype=jnp.float8_e4m3fn, scan_mode="reconstruct"),
        dict(scan_mode="nope"),
        dict(rescore_factor=2, rescore_on_device="bogus")])
    def test_build_plan_rejects(self, pq_index, bad):
        from raft_tpu.core.error import LogicError
        idx, q = pq_index
        sp = ivf_pq.SearchParams(n_probes=4, **bad)
        with pytest.raises(LogicError):
            ivf_pq.search(idx, q, 4, sp)
        with pytest.raises(LogicError):
            plan.build_plan(idx, q, 4, sp, warm=False)


class TestEagerRouteCounters:
    """The eager searches note their route like a plan build does."""

    def test_flat_ragged_tail(self, flat_index, flat_data, monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        _, q = flat_data
        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        # 432-row lists at auto bins (64 at k 8): a 48-row tail
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        name = "raft.ivf_scan.ragged_tail.total{family=ivf_flat}"
        before = obs.snapshot()
        ivf_flat.search(flat_index, q, 8, sp)
        assert _cdiff(before, obs.snapshot(), name) == 1

    def test_pq_decoded_share(self, monkeypatch):
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        from raft_tpu.ops import pallas_ivf_scan as pis
        x, _ = make_blobs(n_samples=3000, n_features=32, centers=20,
                          cluster_std=3.0, seed=4)
        idx = ivf_pq.build(jnp.asarray(np.asarray(x)), ivf_pq.IndexParams(
            n_lists=16, kmeans_n_iters=4, pq_dim=8))
        q = jnp.asarray(np.asarray(x)[:16])
        sp = ivf_pq.SearchParams(n_probes=4, scan_mode="codes")
        gauge = "raft.ivf_scan.pq.decoded_share"
        obs.gauge(gauge).set(-1.0)
        ivf_pq.search(idx, q, 8, sp)
        r = plan.resolve_cap(plan.route(idx, 16, 8, sp), idx, q, sp)
        want = pis.pq_decoded_share(idx.lists_indices, 8, r.cap, r.bins,
                                    8, idx.pq_centers.shape[1],
                                    idx.rot_dim, sp.lut_dtype)
        assert 0.0 < want <= 1.0
        assert obs.snapshot()["gauges"][gauge] == want


class TestEagerSharesThePlanRoute:
    @pytest.mark.parametrize("metric", ["l2", "ip"])
    def test_lut_plan_matches_eager(self, metric):
        """``scan_mode="lut"`` has a plan, and it answers what the eager
        LUT-gather search answers."""
        from raft_tpu.distance.distance_types import DistanceType
        x, _ = make_blobs(n_samples=3000, n_features=16, centers=20,
                          seed=6)
        x = np.asarray(x)
        m = (DistanceType.InnerProduct if metric == "ip"
             else DistanceType.L2Expanded)
        idx = ivf_pq.build(jnp.asarray(x), ivf_pq.IndexParams(
            n_lists=16, kmeans_n_iters=3, pq_dim=4, metric=m))
        q = x[:24]
        sp = ivf_pq.SearchParams(n_probes=4, scan_mode="lut")
        d0, i0 = ivf_pq.search(idx, q, 6, sp)
        p = plan.build_plan(idx, q, 6, sp)
        d1, i1 = p.search(q, block=True)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))

    @pytest.mark.parametrize("family", ["ivf_flat", "ivf_pq", "ivf_bq"])
    def test_eager_adds_no_plan_build(self, family, flat_data):
        """An eager search at a shape a plan was built for measures
        nothing, builds nothing, and answers what the plan answers."""
        if not obs.enabled():
            pytest.skip("metrics disabled (RAFT_TPU_METRICS=0)")
        x, q = flat_data
        mod = {"ivf_flat": ivf_flat, "ivf_pq": ivf_pq,
               "ivf_bq": ivf_bq}[family]
        kw = dict(pq_dim=8) if family == "ivf_pq" else {}
        idx = mod.build(x, mod.IndexParams(n_lists=16, kmeans_n_iters=3,
                                           **kw))
        sp = mod.SearchParams(n_probes=4)
        p = plan.build_plan(idx, q, 8, sp)
        before = obs.snapshot()
        d0, i0 = mod.search(idx, q, 8, sp)
        after = obs.snapshot()
        for name in ("raft.plan.build.total", "raft.plan.cache.misses",
                     "raft.ivf_scan.resolve_cap.syncs"):
            assert _cdiff(before, after, name) == 0, name
        d1, i1 = p.search(q, block=True)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
