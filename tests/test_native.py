"""Native C++ host runtime (cpp/raft_tpu_host.cpp) vs Python fallbacks.

The reference tests its host-side C++ directly (gtest); here the native
path is asserted to agree exactly with the pure-Python formulation —
the naive-reference-vs-primitive pattern of SURVEY.md §4.
"""

import numpy as np
import pytest

from raft_tpu.core import native


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native lib unavailable")


def _force_python(monkeypatch):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", True)


def _random_tree(n, rng):
    src = np.arange(1, n, dtype=np.int64)
    dst = np.array([rng.integers(0, i) for i in range(1, n)], np.int64)
    w = rng.random(n - 1)
    return src, dst, w


class TestDendrogramNative:
    def test_parity_with_python(self, monkeypatch):
        from raft_tpu.cluster.single_linkage import build_dendrogram_host
        rng = np.random.default_rng(1)
        src, dst, w = _random_tree(500, rng)
        cn, hn, sn = build_dendrogram_host(src, dst, w)
        _force_python(monkeypatch)
        cp, hp, sp = build_dendrogram_host(src, dst, w)
        np.testing.assert_array_equal(cn, cp)
        np.testing.assert_allclose(hn, hp)
        np.testing.assert_array_equal(sn, sp)

    def test_extract_parity(self, monkeypatch):
        from raft_tpu.cluster.single_linkage import (
            _extract_flattened, build_dendrogram_host)
        rng = np.random.default_rng(2)
        n = 300
        src, dst, w = _random_tree(n, rng)
        children, _, _ = build_dendrogram_host(src, dst, w)
        for n_clusters in (1, 2, 7, n):
            ln = _extract_flattened(children, n, n_clusters)
            assert len(np.unique(ln)) == n_clusters
            _force_python(monkeypatch)
            lp = _extract_flattened(children, n, n_clusters)
            monkeypatch.undo()
            np.testing.assert_array_equal(ln, lp)

    def test_cycle_rejected(self):
        # edges with a cycle are not an MST: native path must raise
        src = np.array([0, 1, 0], np.int64)
        dst = np.array([1, 2, 2], np.int64)
        w = np.array([0.1, 0.2, 0.3])
        with pytest.raises(ValueError):
            native.build_dendrogram(src, dst, w)

    def test_out_of_range_rejected(self):
        src = np.array([0, 5], np.int64)  # 5 out of range for n=3
        dst = np.array([1, 2], np.int64)
        w = np.array([0.1, 0.2])
        with pytest.raises(ValueError):
            native.build_dendrogram(src, dst, w)


class TestNativeLogging:
    def test_callback_sink_and_level_gate(self):
        seen = []
        assert native.log_set_callback(lambda lvl, msg: seen.append((lvl, msg)))
        try:
            assert native.log_set_level(4)  # info
            native.log(4, "hello")
            native.log(5, "gated-out debug")
            assert seen == [(4, "hello")]
            assert native.log_set_level(5)
            native.log(5, "debug now visible")
            assert seen[-1] == (5, "debug now visible")
        finally:
            native.log_set_callback(None)
            native.log_set_level(4)


class TestSingleLinkageEndToEnd:
    def test_native_path_used_in_single_linkage(self):
        # three well-separated blobs → 3 clusters, via the native path
        from raft_tpu.cluster.single_linkage import single_linkage
        rng = np.random.default_rng(3)
        pts = np.concatenate([
            rng.normal(0, 0.1, (40, 2)),
            rng.normal(5, 0.1, (40, 2)),
            rng.normal((0, 8), 0.1, (40, 2)),
        ]).astype(np.float32)
        labels, children = single_linkage(pts, n_clusters=3)
        labels = np.asarray(labels)
        assert len(np.unique(labels)) == 3
        # each blob uniform
        for s in (slice(0, 40), slice(40, 80), slice(80, 120)):
            assert len(np.unique(labels[s])) == 1


class TestBoruvkaNative:
    def test_mst_parity_with_numpy(self, monkeypatch):
        from raft_tpu.sparse.solver.mst import boruvka_mst_edges
        rng = np.random.default_rng(7)
        n, m = 200, 1500
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        w = rng.random(len(src))
        s_n, d_n, w_n, c_n = boruvka_mst_edges(n, src, dst, w)
        _force_python(monkeypatch)
        s_p, d_p, w_p, c_p = boruvka_mst_edges(n, src, dst, w)
        # identical unique MSF: same total weight, same edge count, same
        # component partition
        assert len(s_n) == len(s_p)
        np.testing.assert_allclose(np.sort(w_n), np.sort(w_p), rtol=1e-12)
        edges_n = {frozenset((a, b)) for a, b in zip(s_n, d_n)}
        edges_p = {frozenset((a, b)) for a, b in zip(s_p, d_p)}
        assert edges_n == edges_p
        # same partition (labels up to renaming)
        remap = {}
        for a, b in zip(c_n, c_p):
            assert remap.setdefault(a, b) == b

    def test_disconnected_forest(self, monkeypatch):
        from raft_tpu.sparse.solver.mst import boruvka_mst_edges
        # two components: 0-1-2 and 3-4
        src = np.array([0, 1, 3])
        dst = np.array([1, 2, 4])
        w = np.array([1.0, 2.0, 3.0])
        s, d, wts, comp = boruvka_mst_edges(5, src, dst, w)
        assert len(s) == 3
        assert len(np.unique(comp)) == 2


class TestNativeKVBroker:
    """C++ TCP tagged-KV broker (the ucp_helper/UCX role,
    _cpp/raft_tpu_host.cpp rth_kv_*)."""

    @pytest.fixture()
    def broker(self):
        from raft_tpu.comms.native_p2p import NativeKVServer
        with NativeKVServer() as s:
            yield s

    def test_put_get_timeout_overwrite(self, broker):
        p = broker.port
        assert native.kv_put("127.0.0.1", p, "a", b"v1")
        assert native.kv_get("127.0.0.1", p, "a", 500) == b"v1"
        # consumed: second read times out
        assert native.kv_get("127.0.0.1", p, "a", 50) is None
        # overwrite + non-consuming peek
        native.kv_put("127.0.0.1", p, "hb", b"1")
        native.kv_put("127.0.0.1", p, "hb", b"2")
        assert native.kv_get("127.0.0.1", p, "hb", 50, consume=False) == b"2"
        assert native.kv_get("127.0.0.1", p, "hb", 50, consume=False) == b"2"

    def test_blocking_get_sees_later_put(self, broker):
        import threading
        p = broker.port
        out = {}

        def getter():
            out["v"] = native.kv_get("127.0.0.1", p, "late", 3000)

        t = threading.Thread(target=getter)
        t.start()
        time_mod = __import__("time"); time_mod.sleep(0.15)
        native.kv_put("127.0.0.1", p, "late", b"arrived")
        t.join(5)
        assert out["v"] == b"arrived"

    def test_host_p2p_over_native_transport(self, broker):
        from raft_tpu.comms import HostP2P, NativeKVClient, Status
        cl = NativeKVClient("127.0.0.1", broker.port)
        a = HostP2P(0, 2, session="native-t", client=cl)
        b = HostP2P(1, 2, session="native-t", client=cl)
        a.isend(b"payload-x", dest=1, tag=3)
        req = b.irecv(source=0, tag=3)
        assert req.wait(5.0) == Status.SUCCESS
        assert req.payload == b"payload-x"
        # ordering by seq for same (src, dst, tag)
        a.isend(b"m0", dest=1, tag=0)
        a.isend(b"m1", dest=1, tag=0)
        r0, r1 = b.irecv(0, 0), b.irecv(0, 0)
        assert b.waitall([r0, r1], timeout_s=5.0) == Status.SUCCESS
        assert (r0.payload, r1.payload) == (b"m0", b"m1")
        # missing message -> ABORT, not hang
        dead = b.irecv(source=0, tag=9)
        assert dead.wait(0.1) == Status.ABORT

    def test_health_monitor_over_native_transport(self, broker):
        import time as _t
        from raft_tpu.comms import HealthMonitor, NativeKVClient
        cl = NativeKVClient("127.0.0.1", broker.port)
        m0 = HealthMonitor(0, 2, session="native-h", interval_s=0.05,
                           stale_after_s=0.3, client=cl).start()
        m1 = HealthMonitor(1, 2, session="native-h", interval_s=0.05,
                           stale_after_s=0.3, client=cl).start()
        try:
            _t.sleep(0.15)
            assert m0.suspect_ranks() == []
            m1.stop()
            _t.sleep(0.5)
            assert m0.suspect_ranks() == [1]
        finally:
            m0.stop(); m1.stop()


class TestNativeInterruptible:
    """C++ token registry behind core.interruptible (rth_interrupt_*)."""

    def test_cross_thread_cancel_via_native(self):
        import importlib
        import threading
        intr = importlib.import_module("raft_tpu.core.interruptible")

        state = {}

        def worker():
            state["tid"] = threading.get_ident()
            state["ready"].set()
            try:
                while True:
                    intr.yield_()
                    import time
                    time.sleep(0.005)
            except intr.InterruptedException:
                state["cancelled"] = True

        state["ready"] = threading.Event()
        t = threading.Thread(target=worker)
        t.start()
        state["ready"].wait(2)
        intr.cancel(state["tid"])
        t.join(5)
        assert state.get("cancelled") is True

    def test_flag_cleared_after_consume(self):
        import importlib
        import threading
        intr = importlib.import_module("raft_tpu.core.interruptible")
        tid = threading.get_ident()
        intr.cancel(tid)
        assert intr.yield_no_throw() is True
        assert intr.yield_no_throw() is False  # consumed, not sticky


class TestNativeStamp:
    """The .so is untracked: the loader rebuilds when the stamp written
    at build time does not match the hash of the committed sources."""

    def test_stale_stamp_rebuilds(self, monkeypatch, tmp_path):
        stamp = tmp_path / "lib.stamp"
        stamp.write_text("stale\n")
        monkeypatch.setattr(native, "_stamp_path", lambda: str(stamp))
        builds = []

        def fake_build():
            builds.append(1)
            stamp.write_text(native._source_hash() + "\n")
            return True

        monkeypatch.setattr(native, "_try_build", fake_build)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", False)
        assert not native._is_current()
        assert native.load() is not None
        assert builds == [1]
        # a matching stamp loads without building again
        monkeypatch.setattr(native, "_lib", None)
        assert native._is_current()
        assert native.load() is not None
        assert builds == [1]

    def test_source_edit_changes_hash(self, monkeypatch, tmp_path):
        import shutil
        src = tmp_path / "_cpp"
        shutil.copytree(native._cpp_dir(), src)
        monkeypatch.setattr(native, "_cpp_dir", lambda: str(src))
        before = native._source_hash()
        with open(src / "raft_tpu_host.cpp", "a") as f:
            f.write("\n// edited\n")
        assert native._source_hash() != before
