"""Compile-budget ladder (ops/compile_budget.py).

Fused searches run as a ladder of tiers: an error from a tier is
raised, and only a tier that exceeds the opt-in compile budget is
parked (never killed) while the next tier serves. Tier thunks here are plain
Python (sleep/raise) — the ladder is orthogonal to jax — plus an
end-to-end check that the IVF searches produce identical results
through every tier of their ladders.
"""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from raft_tpu.ops import compile_budget as cb


@pytest.fixture(autouse=True)
def _clean_registry():
    cb.reset()
    yield
    cb.reset()


class TestRunTiers:
    def test_first_tier_serves(self):
        out = cb.run_tiers("lad", [("a", lambda: 1), ("b", lambda: 2)],
                           budget=5.0)
        assert out == 1
        assert cb.tier_state("lad", "a") == "ok"
        assert cb.tier_state("lad", "b") == "untried"

    def test_timeout_falls_back_and_parks(self):
        release = threading.Event()
        finished = threading.Event()

        def slow():
            release.wait(10.0)
            finished.set()
            return "slow"

        out = cb.run_tiers("lad", [("slow", slow), ("fast", lambda: 7)],
                           budget=0.2)
        assert out == 7
        assert cb.tier_state("lad", "slow") == "poisoned"
        assert cb.tier_state("lad", "fast") == "ok"
        # the parked thunk was NOT killed: releasing it lets it finish,
        # and late completion un-poisons the tier
        release.set()
        assert finished.wait(5.0)
        deadline = time.time() + 5.0
        while (cb.tier_state("lad", "slow") != "ok"
               and time.time() < deadline):
            time.sleep(0.01)
        assert cb.tier_state("lad", "slow") == "ok"

    def test_park_poisons_same_family_siblings(self):
        """A parked pallas_* tier also poisons its pallas_* siblings
        (one budget burned, not one per rung); the cross-family tail
        still serves, and the LAST tier is never sibling-poisoned."""
        release = threading.Event()

        def slow():
            release.wait(10.0)
            return "slow"

        sib_ran = []
        out = cb.run_tiers(
            "fam", [("pallas_lcauto", slow),
                    ("pallas_lc1", lambda: sib_ran.append(1) or "sib"),
                    ("xla_decode", lambda: 42)],
            budget=0.2)
        assert out == 42
        assert sib_ran == []
        # assert BEFORE release: late completion un-poisons the parked
        # tier (by design), which would race these checks
        assert cb.tier_state("fam", "pallas_lcauto") == "poisoned"
        assert cb.tier_state("fam", "pallas_lc1") == "poisoned"
        assert cb.tier_state("fam", "xla_decode") == "ok"
        release.set()

    def test_park_skips_only_same_family(self):
        release = threading.Event()
        out = cb.run_tiers(
            "fam2", [("pallas_lcauto", lambda: release.wait(10.0)),
                     ("xla_inverted", lambda: "x"),
                     ("probe_major", lambda: "last")],
            budget=0.2)
        assert out == "x"
        assert cb.tier_state("fam2", "xla_inverted") == "ok"
        assert cb.tier_state("fam2", "probe_major") == "untried"
        release.set()

    def test_poisoned_tier_skipped_next_call(self):
        calls = []

        def slow():
            calls.append("slow")
            time.sleep(10.0)

        out = cb.run_tiers("lad", [("slow", slow), ("fast", lambda: 7)],
                           budget=0.2)
        assert out == 7 and calls == ["slow"]
        out = cb.run_tiers("lad", [("slow", slow), ("fast", lambda: 8)],
                           budget=0.2)
        assert out == 8
        assert calls == ["slow"]  # not re-submitted while poisoned

    @pytest.mark.parametrize("budget", [0.0, 5.0])
    def test_error_raises_without_fallthrough(self, budget):
        """A failing first tier (e.g. a kernel Mosaic refuses) is
        raised, inline and on the budgeted thread alike — the next tier
        never hides it."""
        served = []

        def bad():
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            cb.run_tiers("lad", [("bad", bad),
                                 ("ok", lambda: served.append(1))],
                         budget=budget)
        assert served == []
        assert cb.tier_state("lad", "ok") == "untried"

    def test_last_tier_error_raises(self):
        def bad():
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            cb.run_tiers("lad", [("a", bad), ("b", bad)], budget=5.0)

    def test_budget_zero_runs_inline(self):
        # b == 0 (the CPU default): no threads, straight call
        out = cb.run_tiers("lad", [("a", lambda: 42)], budget=0.0)
        assert out == 42
        assert cb.tier_state("lad", "a") == "ok"

    def test_ok_tier_runs_inline_later(self):
        slow_calls = []

        def was_slow():
            # fast on the second call (jit cache analogue)
            if not slow_calls:
                slow_calls.append(1)
                time.sleep(0.4)
            return "served"

        out = cb.run_tiers("lad", [("t", was_slow), ("u", lambda: 0)],
                           budget=5.0)
        assert out == "served"
        t0 = time.time()
        out = cb.run_tiers("lad", [("t", lambda: "cached"),
                                   ("u", lambda: 0)], budget=5.0)
        assert out == "cached" and time.time() - t0 < 0.2

    def test_snapshot(self):
        cb.run_tiers("lad", [("slow", lambda: time.sleep(10)),
                             ("fast", lambda: 1)], budget=0.1)
        snap = cb.snapshot()
        assert snap["lad"]["slow"] == "poisoned"
        assert snap["lad"]["fast"] == "ok"

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_default_budget_disabled_on_every_backend(self, backend,
                                                      monkeypatch):
        monkeypatch.delenv("RAFT_TPU_COMPILE_BUDGET_S", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert cb.budget_s() == 0.0
        monkeypatch.setenv("RAFT_TPU_COMPILE_BUDGET_S", "2.5")
        assert cb.budget_s() == 2.5


class TestLadderEquivalence:
    """Every tier of the IVF-Flat ladder returns the same neighbors
    (kernel tiers run under the Pallas interpreter on the test mesh)."""

    def _index(self):
        from raft_tpu.neighbors import ivf_flat
        rng = np.random.default_rng(11)
        x = rng.standard_normal((3000, 32), np.float32)
        return ivf_flat.build(
            x, ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)), x

    def test_lc_variants_and_xla_agree(self, monkeypatch):
        from raft_tpu.neighbors import _ivf_scan, ivf_flat
        from raft_tpu.ops.pallas_ivf_scan import lc_mode

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
        """RAFT_TPU_IVF_LC is resolved per call (ADVICE r3 #1): results
        stay correct whichever value the env pins."""
        from raft_tpu.neighbors import ivf_flat
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

    def test_poisoned_pallas_tier_serves_from_xla(self, monkeypatch):
        """Simulated hang: the pallas tier thunk blocks; the ladder
        must serve the same neighbors from the XLA tier."""
        from raft_tpu.neighbors import ivf_flat

        idx, x = self._index()
        q = x[:32]
        sp = ivf_flat.SearchParams(n_probes=8, scan_order="list")
        monkeypatch.setenv("RAFT_TPU_PALLAS", "never")
        d_ref, i_ref = ivf_flat.search(idx, q, 10, sp)

        monkeypatch.setenv("RAFT_TPU_PALLAS", "always")
        monkeypatch.setenv("RAFT_TPU_COMPILE_BUDGET_S", "0.3")
        import raft_tpu.neighbors._ivf_scan as S
        real = S.fused_list_search

        def hang_if_pallas(*a, **kw):
            if kw.get("use_pallas"):
                time.sleep(30.0)
            return real(*a, **kw)

        monkeypatch.setattr(S, "fused_list_search", hang_if_pallas)
        d, i = ivf_flat.search(idx, q, 10, sp)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
        snap = cb.snapshot()
        # the shape key carries the fused-routing flag (fz=...), so the
        # PALLAS=never reference run above owns a sibling entry — scan
        # every ladder entry of the family for the parked tier
        lad = [k for k in snap if k.startswith("ivf_flat[")]
        assert lad and any(v == "poisoned"
                           for key in lad for v in snap[key].values())
