#!/usr/bin/env python
"""fleetd: one replica of a multi-process fleet, as a daemon.

Runs one SearchServer (over a MutableIndex built from a deterministic
dataset — every process derives the SAME base index from
``--n/--dim/--seed/--n-lists``, so follower bootstrap can fall back to
it before the primary's first compaction) behind a
:class:`~raft_tpu.fleet.transport.ReplicaTransport`: ONE port serving
the fleet RPC plane (``/rpc/*``) and the whole obs debug plane
(``/metrics``, ``/healthz``, ``/debug/*``) — a metrics federator and
``tools/doctor.py --url`` point at the same address the router does.

Roles:

* ``--role primary`` — owns the mutation WAL (``--wal``): recovers
  over it when it exists (restart-over-own-log, the
  post-promotion-survival contract) else starts it fresh; serves
  ``/rpc/wal/tail`` + ``/rpc/checkpoint`` and accepts
  ``/rpc/upsert``/``/rpc/delete``.
* ``--role follower --primary-url URL`` — bootstraps over the wire
  (checkpoint + tail; ``raft_tpu.fleet.remote.bootstrap_from_url``)
  and keeps a :class:`~raft_tpu.fleet.replication.Replicator` tailing
  the primary. Rejects writes with HTTP 409.

``POST /rpc/promote`` completes a failover IN PLACE: the follower
closes its replicator, opens its OWN WAL at the inherited
``next_seq`` (``MutationWAL(start_seq=...)``) and compacts once —
compaction's atomic checkpoint+rewrite writes a meta head carrying the
inherited epoch/id-space into the fresh log, so (a) a caught-up peer
re-targeted here resumes tailing contiguously across the ownership
transfer, (b) a behind peer gets the same typed 410-gap it would get
from any checkpoint rewrite, and (c) a restart of THIS process over
its own log (``--role primary``) reproduces the state, writes
included. One mechanism — rewrite-resume — covers promotion, restart
and re-bootstrap.

The spawner handshake: bind (ephemeral ``--port 0`` by default), write
the bound port to ``--port-file``, serve until SIGTERM/SIGINT (or
``POST /rpc/stop``), then drain and exit 0.
"""

import argparse
import logging
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_args(argv=None):
    ap = argparse.ArgumentParser(
        description="raft-tpu fleet replica daemon")
    ap.add_argument("--name", default="r0")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (written to --port-file)")
    ap.add_argument("--port-file", default=None)
    ap.add_argument("--role", choices=("primary", "follower"),
                    default="primary")
    ap.add_argument("--primary-url", default=None,
                    help="bootstrap/replication target (follower)")
    ap.add_argument("--wal", default="mutations.wal",
                    help="this replica's OWN log (primary now, or "
                         "after promotion)")
    ap.add_argument("--checkpoint", default="checkpoint.npz")
    ap.add_argument("--cache-dir", default=".",
                    help="bootstrap checkpoint download cache")
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-lists", type=int, default=8)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--n-probes", type=int, default=8)
    ap.add_argument("--batch-sizes", default="1,8")
    ap.add_argument("--deadline-ms", type=float, default=5000.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=1.0)
    ap.add_argument("--sync-wal", action="store_true",
                    help="fsync every WAL append (durability over "
                         "smoke-test speed)")
    ap.add_argument("--blackbox", default=None,
                    help="crash-durable flight-recorder directory")
    ap.add_argument("--log-level", default="INFO")
    return ap.parse_args(argv)


class Daemon:
    """The transport's ``control`` object + the process lifecycle.

    Control verbs run on transport handler threads; ``ValueError``
    raised here maps to HTTP 409 (refused transition), anything else
    to 503. The promotion/retarget swaps are serialized by ``_lock``
    (GL003 contract below).
    """

    # static race contract (tools/graftlint GL003): handler threads
    # (promote/retarget/stop/writes) and the main thread meet here
    GUARDED_BY = ("_role", "_replicator", "_promoting")

    def __init__(self, args, mindex, server, replicator, blackbox):
        self.args = args
        self.name = args.name
        self.m = mindex
        self.server = server
        self._lock = threading.Lock()
        self._role = args.role
        self._replicator = replicator
        self._promoting = False
        self._blackbox = blackbox
        self.transport = None          # installed by main()
        self.stop_event = threading.Event()

    # -- introspection -----------------------------------------------------
    def state(self):
        from raft_tpu.mutate.wal import MutationWAL  # noqa: F401
        with self._lock:
            role = self._role
            repl = self._replicator
        body = {"name": self.name, "role": role, "pid": os.getpid(),
                "state": "serving" if not self.stop_event.is_set()
                else "down",
                "epoch": self.m.epoch}
        wal = getattr(self.m, "_wal", None)
        if wal is not None:
            body["wal_next_seq"] = wal.next_seq
        if repl is not None:
            body["applied_seq"] = repl.applier.applied_seq
            body["replication_gap"] = repl.gap
        return body

    # -- lifecycle ---------------------------------------------------------
    def drain(self, timeout_s=30.0):
        return {"drained": self.server.drain(float(timeout_s))}

    def stop(self):
        # respond first, die after: the handler thread must get its
        # 200 out before the main thread tears the transport down
        threading.Timer(0.2, self.stop_event.set).start()
        return {"stopping": True}

    # -- failover ----------------------------------------------------------
    def promote(self):
        """Follower → primary, in place. The inherited seq floor
        becomes this process's OWN log's start_seq; one compaction
        writes the meta head + checkpoint atomically."""
        from raft_tpu.mutate.wal import MutationWAL
        with self._lock:
            if self._role == "primary":
                raise ValueError(f"{self.name} is already primary")
            if self._promoting:
                raise ValueError(f"{self.name}: promotion already "
                                 f"in flight")
            self._promoting = True
            repl = self._replicator
            self._replicator = None
        try:
            if repl is not None:
                applier = repl.applier
                repl.close()
            else:
                raise ValueError(f"{self.name}: no replication state "
                                 f"to promote from")
            next_seq = max(applier.applied_seq,
                           applier._skip_upto) + 1
            wal = MutationWAL(self.args.wal, sync=self.args.sync_wal,
                              start_seq=next_seq)
            self.m.attach_wal(wal,
                              checkpoint_path=self.args.checkpoint)
            # the ownership stamp: checkpoint + meta-headed log, in
            # one atomic swap — peers resume or 410 off this log
            self.m.compact()
            if self.transport is not None:
                self.transport.wal_path = self.args.wal
            with self._lock:
                self._role = "primary"
        finally:
            with self._lock:
                self._promoting = False
        from raft_tpu import obs
        obs.counter("raft.fleet.proc.promotions.total").inc()
        if self._blackbox is not None:
            self._blackbox.flush("promote")
        return {"primary": self.name, "next_seq": wal.next_seq,
                "epoch": self.m.epoch}

    def retarget(self, primary_url):
        """Point this follower's replication at a NEW primary (after a
        promotion elsewhere). Resumes from the applied floor; if the
        new primary's log no longer holds it, the replicator parks on
        the usual typed gap and this replica must be respawned."""
        from raft_tpu.fleet.replication import Replicator
        from raft_tpu.fleet.transport import (RemoteWalReader,
                                              TransportClient)
        with self._lock:
            if self._role == "primary":
                raise ValueError(f"{self.name} is primary — it has "
                                 f"no replication to retarget")
            repl = self._replicator
            self._replicator = None
        applier = repl.applier if repl is not None else None
        if repl is not None:
            repl.close()
        if applier is None:
            raise ValueError(f"{self.name}: no replication state to "
                             f"retarget")
        floor = max(applier.applied_seq, applier._skip_upto)
        reader = RemoteWalReader(TransportClient(str(primary_url)),
                                 from_seq=floor)
        new_repl = Replicator(self.m, wal_path=str(primary_url),
                              name=self.name, reader=reader,
                              applier=applier)
        with self._lock:
            self._replicator = new_repl
        return {"retargeted": True, "from_seq": floor,
                "primary_url": str(primary_url)}

    # -- writes (primary only) ---------------------------------------------
    def _require_primary(self, verb):
        with self._lock:
            if self._role != "primary":
                raise ValueError(
                    f"{self.name} is a follower — {verb} goes to "
                    f"the primary")

    def upsert(self, rows, ids=None):
        import numpy as np
        self._require_primary("upsert")
        out = self.m.upsert(np.asarray(rows, np.float32),
                            ids=None if ids is None
                            else np.asarray(ids, np.int64))
        return {"ids": np.asarray(out).tolist()}

    def delete(self, ids):
        import numpy as np
        self._require_primary("delete")
        n = self.m.delete(np.asarray(ids, np.int64))
        return {"deleted": int(n)}

    def close_replication(self):
        with self._lock:
            repl = self._replicator
            self._replicator = None
        if repl is not None:
            repl.close()


def build_index(args):
    """The deterministic shared base: every process derives the same
    index from the same (n, dim, seed, n_lists)."""
    import numpy as np

    from raft_tpu.mutate import MutableIndex
    from raft_tpu.mutate.wal import MutationWAL
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.random import make_blobs

    x, _ = make_blobs(n_samples=args.n, n_features=args.dim,
                      centers=max(2, args.n_lists), cluster_std=2.0,
                      seed=args.seed)
    x = np.asarray(x)
    base = ivf_flat.build(x, ivf_flat.IndexParams(
        n_lists=args.n_lists, kmeans_n_iters=3))
    params = ivf_flat.SearchParams(n_probes=args.n_probes)
    rep_queries = x[:64]

    replicator = None
    if args.role == "primary":
        if os.path.exists(args.wal):
            # restart over our own log — the promotion-survival path
            m = MutableIndex.recover(
                args.wal, args.k, base_index=base,
                checkpoint_path=args.checkpoint, params=params,
                sync=args.sync_wal)
        else:
            m = MutableIndex(base, k=args.k, params=params)
            m.attach_wal(MutationWAL(args.wal, sync=args.sync_wal),
                         checkpoint_path=args.checkpoint)
    else:
        from raft_tpu.fleet.remote import bootstrap_from_url
        from raft_tpu.fleet.replication import Replicator
        m, reader, applier = bootstrap_from_url(
            args.primary_url, args.k, args.cache_dir,
            base_index=base, params=params, name=args.name)
        replicator = Replicator(m, wal_path=args.primary_url,
                                name=args.name, reader=reader,
                                applier=applier)
    return m, rep_queries, replicator


def main(argv=None):
    args = build_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format=f"%(asctime)s fleetd[{args.name}] %(levelname)s "
               f"%(name)s: %(message)s")
    log = logging.getLogger("fleetd")
    if args.role == "follower" and not args.primary_url:
        log.error("--role follower requires --primary-url")
        return 2

    # before anything touches JAX: each daemon reuses the compiles its
    # peers (and earlier runs) already cached
    from raft_tpu.core.compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    from raft_tpu import obs
    from raft_tpu.fleet.transport import serve_replica
    from raft_tpu.serve import SearchServer, ServeConfig

    blackbox = None
    if args.blackbox:
        from raft_tpu.obs.blackbox import BlackBox
        blackbox = BlackBox(args.blackbox, box=args.name).start()

    log.info("building index (role=%s)", args.role)
    m, rep_queries, replicator = build_index(args)

    cfg = ServeConfig(
        batch_sizes=tuple(int(b) for b
                          in args.batch_sizes.split(",")),
        max_queue=args.max_queue, max_wait_ms=args.max_wait_ms,
        default_deadline_ms=args.deadline_ms)
    server = SearchServer.from_index(m, rep_queries, args.k,
                                     config=cfg)

    daemon = Daemon(args, m, server, replicator, blackbox)
    transport = serve_replica(
        host=args.host, port=args.port, searcher=server,
        wal_path=(args.wal if args.role == "primary" else None),
        checkpoint_path=args.checkpoint, control=daemon)
    daemon.transport = transport
    obs.gauge("raft.fleet.replica.state", replica=args.name).set(1)

    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{transport.port}\n")
        os.replace(tmp, args.port_file)
    log.info("serving on %s (pid %d)", transport.url, os.getpid())

    def _on_signal(signum, frame):
        log.info("signal %d — shutting down", signum)
        daemon.stop_event.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    daemon.stop_event.wait()
    obs.gauge("raft.fleet.replica.state", replica=args.name).set(3)
    log.info("draining")
    try:
        server.drain(10.0)
    finally:
        daemon.close_replication()
        server.close()
        transport.close()
        if blackbox is not None:
            blackbox.close()
    log.info("exited clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
