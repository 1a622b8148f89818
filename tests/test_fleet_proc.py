"""Multi-process fleet tests (ISSUE 20).

The acceptance, layer by layer:

* the wire format IS the log — ``read_raw``/``decode_stream`` return
  the on-disk bytes verbatim (CRCs travel untouched), positioned and
  bounded exactly like ``WalReader.tail``, with the same typed
  :class:`WalGapError` when the position was folded into a checkpoint;
* WAL over HTTP — ``GET /rpc/wal/tail`` streams those bytes, the gap
  maps to 410 and back to ``WalGapError`` client-side,
  ``GET /rpc/checkpoint`` serves the compactor snapshot bit-identical;
* remote bootstrap parity — a follower built over the wire
  (:func:`bootstrap_from_url`) answers bit-identically to one built by
  the local :func:`bootstrap_replica` AND to the live primary, through
  a checkpointed compaction; a mid-tail gap re-bootstraps cleanly;
* the search RPC — same answers as the in-process server, typed
  errors mapped 429/504/410/* → the same exception classes the router
  already handles, a SIGKILLed process indistinguishable from a
  crashed dispatch;
* :class:`RemoteReplica` behind the stock ``FleetRouter`` — retry +
  suspect routing around a dead transport with zero router changes;
* the 3-process daemon smoke — real ``tools/fleetd.py`` processes:
  SIGKILL the primary under load (availability ≥ 0.999), promote a
  follower (it opens its OWN WAL at the inherited seq), accept writes,
  SIGKILL the new primary and restart it over its own log (the writes
  survive), with zero steady-state compiles asserted per-process from
  each daemon's own ``/metrics``.
"""

import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from raft_tpu import mutate, obs
from raft_tpu.fleet import (FleetConfig, FleetRouter, ProcessFleet,
                            RemoteReplica, RemoteSearchClient,
                            RemoteWalReader, TransportClient,
                            bootstrap_from_url, bootstrap_replica,
                            serve_replica)
from raft_tpu.mutate.wal import (MutationWAL, WalGapError, WalReader,
                                 decode_stream, read_raw)
from raft_tpu.neighbors import ivf_flat
from raft_tpu.random import make_blobs
from raft_tpu.serve import (DeadlineExceeded, DispatchError,
                            RejectedError, SearchServer, ServeConfig)


@pytest.fixture(scope="module")
def small_flat():
    x, _ = make_blobs(n_samples=1500, n_features=16, centers=8,
                      cluster_std=2.0, seed=0)
    x = np.asarray(x)
    return x, ivf_flat.build(x, ivf_flat.IndexParams(n_lists=8,
                                                     kmeans_n_iters=3))


def _primary(x, idx, tmp_path):
    wal_p = str(tmp_path / "m.wal")
    ckpt_p = str(tmp_path / "m.ckpt")
    m = mutate.MutableIndex(idx, k=4)
    m.attach_wal(MutationWAL(wal_p, sync=False), checkpoint_path=ckpt_p)
    return m, wal_p, ckpt_p


def _rec_tuples(recs):
    out = []
    for r in recs:
        ids = None if r.ids is None else np.asarray(r.ids).tolist()
        rows = None if r.rows is None else \
            np.asarray(r.rows, np.float32).tobytes()
        out.append((r.seq, r.op, r.ts, ids, rows, r.meta))
    return out


# ---------------------------------------------------------------------------
# the log IS the wire format
# ---------------------------------------------------------------------------


class TestWalWireFormat:
    def test_read_raw_is_the_file_verbatim(self, tmp_path):
        p = str(tmp_path / "m.wal")
        w = MutationWAL(p, sync=False)
        w.append_upsert([1, 2],
                        np.arange(8, dtype=np.float32).reshape(2, 4))
        w.append_delete([1])
        w.append_meta({"epoch": 1, "id_base": 0, "next_id": 3})
        buf, n, last = read_raw(p)
        with open(p, "rb") as f:
            assert buf == f.read()      # bit-identical, CRCs included
        assert (n, last) == (3, 3)
        assert _rec_tuples(decode_stream(buf)) == \
            _rec_tuples(WalReader(p).tail())

    def test_read_raw_positioned_and_bounded(self, tmp_path):
        p = str(tmp_path / "m.wal")
        w = MutationWAL(p, sync=False)
        for i in range(5):
            w.append_delete([i])
        buf, n, last = read_raw(p, from_seq=2)
        assert [r.seq for r in decode_stream(buf)] == [3, 4, 5]
        assert (n, last) == (3, 5)
        # a positioned slice is a verbatim substring of the full log
        full, _, _ = read_raw(p)
        assert buf[len(b"RTPUWAL2"):] in full
        buf2, n2, last2 = read_raw(p, from_seq=2, max_records=2)
        assert [r.seq for r in decode_stream(buf2)] == [3, 4]
        assert (n2, last2) == (2, 4)

    def test_read_raw_gap_and_missing_file(self, tmp_path):
        p = str(tmp_path / "m.wal")
        w = MutationWAL(p, sync=False)
        for i in range(4):
            w.append_delete([i])
        w.rewrite(meta={"epoch": 1, "id_base": 4, "next_id": 4})
        with pytest.raises(WalGapError) as ei:
            read_raw(p, from_seq=2)     # seqs 3,4 folded away
        assert ei.value.last_seq == 2 and ei.value.first_seq == 5
        # a fresh position replays the rewritten log without a gap
        buf, n, _ = read_raw(p, from_seq=0)
        assert n == 1 and decode_stream(buf)[0].op == 3
        # no log yet = empty tail, not an error
        buf, n, last = read_raw(str(tmp_path / "absent.wal"))
        assert (n, last) == (0, 0) and decode_stream(buf) == []


# ---------------------------------------------------------------------------
# WAL + checkpoint over HTTP
# ---------------------------------------------------------------------------


class TestWalOverHttp:
    def test_tail_verbatim_and_remote_reader(self, tmp_path):
        p = str(tmp_path / "m.wal")
        w = MutationWAL(p, sync=False)
        w.append_upsert([7, 8], np.ones((2, 4), np.float32))
        for i in range(3):
            w.append_delete([i])
        tr = serve_replica(wal_path=p)
        try:
            cli = TransportClient(tr.url)
            assert _rec_tuples(cli.wal_tail(0)) == \
                _rec_tuples(WalReader(p).tail())
            # positioned + bounded, like the local reader
            assert [r.seq for r in cli.wal_tail(2, max_records=1)] \
                == [3]
            # RemoteWalReader keeps position like WalReader
            rr = RemoteWalReader(cli, batch_records=2)
            seqs = []
            while True:
                recs = rr.tail()
                if not recs:
                    break
                assert len(recs) <= 2
                seqs += [r.seq for r in recs]
            assert seqs == [1, 2, 3, 4]
            assert rr.position == 4
            assert rr.probe_caught_up(4)
            w.append_delete([9])
            assert not rr.probe_caught_up(4)    # seq 5 now exists
            assert [r.seq for r in rr.tail()] == [5]
            assert rr.probe_caught_up(5)
        finally:
            tr.close()

    def test_gap_is_410_checkpoint_is_bit_identical(self, tmp_path):
        p = str(tmp_path / "m.wal")
        ckpt = str(tmp_path / "ckpt.npz")
        w = MutationWAL(p, sync=False)
        for i in range(4):
            w.append_delete([i])
        w.rewrite(meta={"epoch": 1, "id_base": 4, "next_id": 4})
        with open(ckpt, "wb") as f:
            f.write(os.urandom(4096))   # payload opacity: any bytes
        tr = serve_replica(wal_path=p, checkpoint_path=ckpt)
        try:
            cli = TransportClient(tr.url)
            with pytest.raises(WalGapError) as ei:
                cli.wal_tail(2)         # HTTP 410 → typed gap
            assert ei.value.last_seq == 2 and ei.value.first_seq == 5
            dest = str(tmp_path / "fetched.npz")
            assert cli.fetch_checkpoint(dest)
            with open(ckpt, "rb") as a, open(dest, "rb") as b:
                assert a.read() == b.read()
        finally:
            tr.close()

    def test_no_wal_no_checkpoint_surfaces(self, tmp_path):
        tr = serve_replica()            # bare transport: no log
        try:
            cli = TransportClient(tr.url)
            with pytest.raises(OSError):
                cli.wal_tail(0)         # 404 → transient to replicator
            assert not cli.fetch_checkpoint(
                str(tmp_path / "none.npz"))
            # control verbs without a daemon behind them: typed refusal
            with pytest.raises(DispatchError):
                cli.promote()
        finally:
            tr.close()


# ---------------------------------------------------------------------------
# remote bootstrap parity (the log is the wire format, end to end)
# ---------------------------------------------------------------------------


class TestRemoteBootstrap:
    def test_parity_through_checkpointed_compaction(self, small_flat,
                                                    tmp_path):
        """A follower bootstrapped over HTTP (/rpc/checkpoint + tail)
        is bit-identical to one bootstrapped from the local files —
        and to the live primary — through a compaction."""
        x, idx = small_flat
        prim, wal_p, ckpt_p = _primary(x, idx, tmp_path)
        ids = prim.upsert(x[:12] + 0.01)
        prim.delete(ids[:3])
        assert prim.compact()           # checkpoint + rewritten log
        prim.upsert(x[20:26] + 0.04)    # traffic after the fold
        tr = serve_replica(wal_path=wal_p, checkpoint_path=ckpt_p)
        try:
            local_f, _, _ = bootstrap_replica(
                wal_p, k=4, checkpoint_path=ckpt_p, name="lf")
            remote_f, reader, applier = bootstrap_from_url(
                tr.url, k=4, cache_dir=str(tmp_path / "cache"),
                name="rf")
            s_p, s_l, s_r = (prim.stats(), local_f.stats(),
                             remote_f.stats())
            for key in ("delta_used", "delta_live", "tombstones",
                        "next_id", "id_base"):
                assert s_p[key] == s_l[key] == s_r[key], key
            assert prim.epoch == local_f.epoch == remote_f.epoch == 1
            q = x[:32]
            d_p, i_p = prim.search(q, block=True)
            d_l, i_l = local_f.search(q, block=True)
            d_r, i_r = remote_f.search(q, block=True)
            np.testing.assert_array_equal(np.asarray(i_p),
                                          np.asarray(i_r))
            np.testing.assert_array_equal(np.asarray(i_l),
                                          np.asarray(i_r))
            np.testing.assert_allclose(np.asarray(d_p),
                                       np.asarray(d_r), rtol=1e-5)
            # the wire reader is positioned at the tip: new primary
            # traffic flows through apply to the same answers
            prim.upsert(x[40:44] + 0.06)
            for rec in reader.tail():
                applier.apply(rec)
            _, i_p2 = prim.search(q, block=True)
            _, i_r2 = remote_f.search(q, block=True)
            np.testing.assert_array_equal(np.asarray(i_p2),
                                          np.asarray(i_r2))
        finally:
            tr.close()

    def test_mid_tail_gap_rebootstraps(self, small_flat, tmp_path):
        """A wire follower stranded behind a compaction gets the typed
        gap (410 → WalGapError) and a fresh bootstrap_from_url — now
        checkpoint-sourced — restores parity."""
        x, idx = small_flat
        prim, wal_p, ckpt_p = _primary(x, idx, tmp_path)
        prim.upsert(x[:8] + 0.01)
        tr = serve_replica(wal_path=wal_p, checkpoint_path=ckpt_p)
        try:
            # bootstrapped pre-checkpoint: base_index-sourced
            m1, reader, applier = bootstrap_from_url(
                tr.url, k=4, cache_dir=str(tmp_path / "c1"),
                base_index=idx, name="rf1")
            assert reader.position == 1
            # the primary moves on and folds the reader's future away
            ids = prim.upsert(x[8:16] + 0.02)
            prim.delete(ids[:2])
            assert prim.compact()
            with pytest.raises(WalGapError):
                reader.tail()
            # re-bootstrap: the checkpoint now exists over the wire
            m2, reader2, _ = bootstrap_from_url(
                tr.url, k=4, cache_dir=str(tmp_path / "c2"),
                name="rf2")
            q = x[:32]
            _, i_p = prim.search(q, block=True)
            _, i_2 = m2.search(q, block=True)
            np.testing.assert_array_equal(np.asarray(i_p),
                                          np.asarray(i_2))
            assert m2.epoch == prim.epoch
        finally:
            tr.close()


# ---------------------------------------------------------------------------
# the search RPC + RemoteReplica behind the stock router
# ---------------------------------------------------------------------------


class TestSearchRpc:
    @pytest.fixture(scope="class")
    def rpc_stack(self, small_flat):
        x, idx = small_flat
        sp = ivf_flat.SearchParams(n_probes=8)   # exhaustive: 8 lists
        cfg = ServeConfig(batch_sizes=(1, 8), max_queue=256,
                          max_wait_ms=1.0, default_deadline_ms=5000.0)
        srv = SearchServer.from_index(idx, x[:8], 4, params=sp,
                                      config=cfg)
        tr = serve_replica(searcher=srv)
        yield x, srv, tr
        tr.close()
        srv.close()

    def test_rpc_matches_in_process_answers(self, rpc_stack):
        x, srv, tr = rpc_stack
        q = x[:4]
        d_loc, i_loc = srv.search(q)
        rsc = RemoteSearchClient(tr.url, name="p0")
        try:
            d_rpc, i_rpc = rsc.search(q)
            np.testing.assert_array_equal(np.asarray(i_loc),
                                          np.asarray(i_rpc))
            np.testing.assert_allclose(np.asarray(d_loc),
                                       np.asarray(d_rpc), rtol=1e-5)
            # submit() is future-shaped like SearchServer.submit
            d2, i2 = rsc.submit(q).result(timeout=60)
            np.testing.assert_array_equal(np.asarray(i_rpc),
                                          np.asarray(i2))
            # the load snapshot piggybacked on the response
            load = rsc.load()
            assert load["remote"] is True
            assert "queued_rows" in load and load["load_age_s"] >= 0
        finally:
            rsc.close()

    def test_typed_error_mapping(self, rpc_stack):
        _, _, tr = rpc_stack
        cli = TransportClient(tr.url)
        assert isinstance(cli._typed(429, {}, "search"), RejectedError)
        assert isinstance(cli._typed(504, {}, "search"),
                          DeadlineExceeded)
        gap = cli._typed(410, {"last_seq": 3, "first_seq": 9}, "tail")
        assert isinstance(gap, WalGapError)
        assert gap.last_seq == 3 and gap.first_seq == 9
        assert isinstance(cli._typed(503, {}, "search"), DispatchError)

    def test_dead_process_is_a_dispatch_error(self):
        # a port nothing listens on = a SIGKILLed daemon
        dead = TransportClient("http://127.0.0.1:1")
        with pytest.raises(DispatchError):
            dead.search_raw(np.zeros((1, 16), np.float32), k=4)
        with pytest.raises(DispatchError):
            dead.state(timeout=1.0)
        with pytest.raises(OSError):    # replication plane: transient
            dead.wal_tail(0, timeout=1.0)

    def test_router_routes_around_dead_transport(self, small_flat,
                                                 rpc_stack):
        """Two RemoteReplicas behind the stock FleetRouter; one
        transport dies; retry + suspect keep every request answered —
        zero router changes for remote processes."""
        x, idx = small_flat
        _, srv, tr = rpc_stack
        sp = ivf_flat.SearchParams(n_probes=8)
        cfg = ServeConfig(batch_sizes=(1, 8), max_queue=256,
                          max_wait_ms=1.0, default_deadline_ms=5000.0)
        srv2 = SearchServer.from_index(idx, x[:8], 4, params=sp,
                                       config=cfg)
        tr2 = serve_replica(searcher=srv2)
        reps = [RemoteReplica("p0", tr.url),
                RemoteReplica("p1", tr2.url)]
        router = FleetRouter(reps, FleetConfig(max_retries=1,
                                               suspect_ms=400.0,
                                               seed=0))
        try:
            q = x[:1]
            _, i0 = router.search(q, timeout=60)
            tr2.close()                 # p1's process "dies"
            srv2.close()
            before = obs.snapshot()
            for _ in range(6):
                _, i1 = router.search(q, timeout=60)
                np.testing.assert_array_equal(np.asarray(i0),
                                              np.asarray(i1))
            after = obs.snapshot()
            routed_p0 = (after["counters"].get(
                "raft.fleet.route.total{replica=p0}", 0.0)
                - before["counters"].get(
                    "raft.fleet.route.total{replica=p0}", 0.0))
            assert routed_p0 == 6       # all traffic re-routed to p0
        finally:
            router.close()


# ---------------------------------------------------------------------------
# the 3-process daemon smoke (the ISSUE 20 acceptance row on CPU)
# ---------------------------------------------------------------------------


def _scrape_plan_compiles(url):
    """This daemon's OWN plan counters from its /metrics — the
    federated zero-compile assertion, one process at a time."""
    with urllib.request.urlopen(url + "/metrics", timeout=10.0) as r:
        text = r.read().decode("utf-8", "replace")
    total = 0.0
    for line in text.splitlines():
        if line.startswith("raft_plan_cache_misses_total") or \
                line.startswith("raft_plan_build_total_total"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return total


class TestProcessFleetSmoke:
    def test_three_process_sigkill_failover(self, tmp_path):
        """Real fleetd daemons: kill -9 the primary under load →
        availability ≥ 0.999 (router suspects + re-routes), promote a
        follower (it opens its OWN WAL at the inherited seq), writes
        land on the new primary, kill -9 it too and restart it over
        its own log — the post-promotion writes survive. Steady-state
        compiles are asserted at 0 per process from each daemon's own
        /metrics."""
        n, dim = 800, 8
        x, _ = make_blobs(n_samples=n, n_features=dim, centers=4,
                          cluster_std=2.0, seed=0)
        q = np.asarray(x[:64], np.float32)
        pf = ProcessFleet(str(tmp_path), n_procs=3, n=n, dim=dim,
                          seed=0, n_lists=4, k=4, n_probes=4,
                          deadline_ms=10_000.0,
                          startup_timeout_s=300.0)
        router = FleetRouter(pf.replicas(),
                             FleetConfig(max_retries=2,
                                         suspect_ms=400.0, seed=0))
        try:
            for i in range(6):          # warm every route
                router.search(q[i:i + 1], timeout=60)

            # -- steady state: zero compiles per process -----------------
            before = {name: _scrape_plan_compiles(url)
                      for name, url in pf.urls().items()}
            for i in range(30):
                router.search(q[i % 64:i % 64 + 1], timeout=60)
            for name, url in pf.urls().items():
                assert _scrape_plan_compiles(url) == before[name], name

            # -- SIGKILL the primary under load --------------------------
            stop = threading.Event()
            failures, done = [], [0]
            lock = threading.Lock()

            def traffic(tid):
                i = tid
                while not stop.is_set():
                    try:
                        router.search(q[i % 64:i % 64 + 1], timeout=60)
                        with lock:
                            done[0] += 1
                    except Exception as e:
                        with lock:
                            failures.append(repr(e))
                    i += 3
            threads = [threading.Thread(target=traffic, args=(t,))
                       for t in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            pf.kill("r0")               # real SIGKILL, router not told
            time.sleep(0.8)             # retries + suspect ride it out
            stop.set()
            for t in threads:
                t.join(timeout=60)
            total = done[0] + len(failures)
            assert total > 20
            availability = done[0] / total
            assert availability >= 0.999, (availability, failures[:3])

            # -- promote: the follower opens its OWN WAL -----------------
            out = pf.promote("r1")
            assert out["primary"] == "r1"
            next_seq = int(out["next_seq"])
            assert next_seq >= 2        # inherited, not restarted at 1
            # writes land on the new primary and continue the id space
            rows = np.asarray(x[:3], np.float32) + 0.5
            new_ids = pf.process("r1").client.upsert(rows)
            assert len(new_ids) == 3 and min(new_ids) >= n
            status, body = pf.process("r1").client.search_raw(
                rows[:1], k=4, deadline_ms=10_000.0)
            assert status == 200
            assert new_ids[0] in [int(v) for v in body["ids"][0]]

            # -- kill -9 the NEW primary; it restarts over its own WAL ---
            pf.kill("r1")
            fp = pf.respawn("r1", role="primary")
            state = fp.client.state()
            assert state["role"] == "primary"
            assert int(state["wal_next_seq"]) > next_seq
            status, body = fp.client.search_raw(
                rows[:1], k=4, deadline_ms=10_000.0)
            assert status == 200        # the promoted writes survived
            assert new_ids[0] in [int(v) for v in body["ids"][0]]
        finally:
            router.close()
            pf.close()


# ---------------------------------------------------------------------------
# loadgen grammar for the new flag
# ---------------------------------------------------------------------------


def test_loadgen_fleet_procs_chaos_grammar():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "raft_loadgen_proc_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    events = loadgen.parse_chaos_spec("kill_replica:2@t+2s+3s")
    assert events == [(2.0, "kill_replica", "2", 3.0)]
    # the flag validations are argparse errors — no fleet is spawned
    for argv in (["--fleet-procs", "1"],              # needs >= 2
                 ["--fleet-procs", "3", "--fleet", "2"],
                 ["--fleet-procs", "3", "--mutate-frac", "0.1"],
                 ["--fleet-procs", "3",
                  "--chaos", "stall_shard:0@t+1s"]):  # kill only
        with pytest.raises(SystemExit):
            loadgen.main(argv)


class TestOneProcessPerChip:
    """A chip belongs to one process: a TPU fleet is refused before any
    daemon spawns when the parent holds the TPU or the host has fewer
    chips than daemons."""

    @pytest.mark.parametrize("holds,chips,match", [
        (True, 4, "already initialised the TPU"),
        (False, 1, "the host has 1"),
    ])
    def test_tpu_fleet_refused(self, tmp_path, monkeypatch, holds, chips,
                               match):
        from raft_tpu.core.error import LogicError
        from raft_tpu.fleet import proc
        monkeypatch.setattr(proc, "parent_holds_tpu", lambda: holds)
        monkeypatch.setattr(proc, "host_tpu_chips", lambda: chips)
        with pytest.raises(LogicError, match=match):
            ProcessFleet(str(tmp_path), n_procs=2, platform="tpu")
        assert not os.listdir(tmp_path)  # nothing spawned

    def test_cpu_parent_is_not_a_tpu_holder(self):
        from raft_tpu.fleet import proc
        assert not proc.parent_holds_tpu()
