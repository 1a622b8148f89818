"""ProcessFleet: N replica daemons as real OS processes.

The fleet tier (PR 13) was deliberately process-agnostic; this module
is where processes actually happen. :class:`ProcessFleet` spawns N
``tools/fleetd.py`` daemons (one primary owning the mutation WAL, N-1
followers bootstrapping over the wire), health-checks them up through
``/rpc/state``, and hands back :class:`~raft_tpu.fleet.remote.
RemoteReplica` objects a stock
:class:`~raft_tpu.fleet.router.FleetRouter` routes over — the GIL and
the single device set stop bounding capacity, which is what arms the
linear-scaling gate (``bench_suite.bench_fleet``).

Spawn contract:

* **per-process device env** — :func:`device_env` gives each process
  its platform (and, on real accelerators, its own chip slice via the
  visible-devices variables) so N processes mean N device owners, not
  N queues on one. On CPU everything shares cores — the scaling gate
  stays informational there.
* **port-file handshake** — each daemon binds an ephemeral port and
  writes ``<port>\\n`` to its port file; the spawner polls the file,
  then polls ``/rpc/state`` until the daemon reports ``serving``
  (:func:`~raft_tpu.fleet.transport.wait_healthy`). No fixed ports, no
  races.
* **death is physical** — :meth:`kill` sends real ``SIGKILL`` to the
  PID and touches no replica state: the router must DISCOVER the death
  through dispatch errors (suspect → re-route), exactly like
  production. :meth:`promote` completes the failover: the chosen
  follower opens its OWN WAL at the inherited ``next_seq`` (see
  ``tools/fleetd.py``) and starts serving the tail; surviving peers
  are retargeted at it.

Everything here is loopback-process orchestration for one host; the
same transport fronts other hosts when a real supervisor replaces
``subprocess``.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from raft_tpu import obs
from raft_tpu.core.error import expects
from raft_tpu.core.logger import get_logger
from raft_tpu.fleet.remote import RemoteReplica, RemoteSearchClient
from raft_tpu.fleet.transport import TransportClient, wait_healthy

__all__ = ["ProcessFleet", "FleetProcess", "device_env"]

_FLEETD = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools", "fleetd.py")


def device_env(index: int, platform: str = "cpu",
               devices_per_proc: int = 1) -> Dict[str, str]:
    """Per-process device ownership env for daemon ``index``. On CPU
    there is nothing to partition (JAX_PLATFORMS pins the backend); on
    TPU each process gets its own chip slice via the visible-chips
    variables so processes scale devices, not queue on one."""
    env = {"JAX_PLATFORMS": platform}
    if platform == "tpu":
        first = index * devices_per_proc
        chips = ",".join(str(first + j)
                         for j in range(devices_per_proc))
        env["TPU_VISIBLE_CHIPS"] = chips
        # one controller per process — without these, process 1's
        # runtime tries to grab the whole pod slice process 0 holds
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"1,{devices_per_proc},1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def host_tpu_chips() -> int:
    """TPU chips on this host, counted from their device files without
    touching JAX (which would claim them for this process)."""
    import glob
    n = len(glob.glob("/dev/accel[0-9]*"))
    if n == 0:
        n = len([e for e in glob.glob("/dev/vfio/*")
                 if os.path.basename(e).isdigit()])
    return n


def parent_holds_tpu() -> bool:
    """Has this process already initialised a TPU backend? Then it holds
    the host's chips and no child can open them."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return "tpu" in getattr(xla_bridge, "_backends", {})


def check_one_process_per_chip(n_procs: int, devices_per_proc: int,
                               platform: str) -> None:
    """A chip belongs to one process at a time: refuse a TPU fleet the
    host cannot give each daemon its own chips, or whose parent already
    holds them."""
    if platform != "tpu":
        return
    expects(not parent_holds_tpu(),
            "ProcessFleet: this process has already initialised the TPU "
            "backend and holds the host's chips; fleet daemons could not "
            "open them. Start the fleet from a process that has not "
            "touched the TPU.")
    chips = host_tpu_chips()
    expects(n_procs * devices_per_proc <= chips,
            "ProcessFleet: %d daemons x %d chips each need %d TPU chips, "
            "the host has %d (one process per chip)",
            n_procs, devices_per_proc, n_procs * devices_per_proc, chips)


class FleetProcess:
    """One spawned daemon: the Popen handle + its addresses + role."""

    def __init__(self, name: str, popen: subprocess.Popen, url: str,
                 workdir: str, role: str):
        self.name = name
        self.popen = popen
        self.url = url
        self.workdir = workdir
        self.role = role                      # "primary" | "follower"
        self.client = TransportClient(url)

    @property
    def pid(self) -> int:
        return self.popen.pid

    def alive(self) -> bool:
        return self.popen.poll() is None

    def describe(self) -> dict:
        return {"name": self.name, "pid": self.pid, "url": self.url,
                "role": self.role, "alive": self.alive(),
                "workdir": self.workdir}


class ProcessFleet:
    """Spawn, health-check, route over, kill and fail over N replica
    daemons. Use as a context manager — :meth:`close` drains and
    terminates every child it still owns."""

    # static race contract (tools/graftlint GL003): the operator
    # thread, chaos threads (kill/respawn) and close() meet on the
    # process table
    GUARDED_BY = ("_procs", "_closed")

    def __init__(self, workdir: str, n_procs: int = 2,
                 n: int = 2000, dim: int = 16, seed: int = 0,
                 n_lists: int = 8, k: int = 4, n_probes: int = 8,
                 deadline_ms: float = 5000.0,
                 batch_sizes: str = "1,8",
                 platform: str = "cpu", devices_per_proc: int = 1,
                 startup_timeout_s: float = 180.0,
                 sync_wal: bool = False, blackbox: bool = False,
                 python: Optional[str] = None,
                 extra_args: Optional[List[str]] = None,
                 spawn: bool = True):
        expects(n_procs >= 1,
                "ProcessFleet: n_procs must be >= 1, got %d", n_procs)
        check_one_process_per_chip(int(n_procs), int(devices_per_proc),
                                   str(platform))
        self.workdir = os.path.abspath(workdir)
        self.n_procs = int(n_procs)
        self._dataset = dict(n=int(n), dim=int(dim), seed=int(seed),
                             n_lists=int(n_lists))
        self.k = int(k)
        self.n_probes = int(n_probes)
        self.deadline_ms = float(deadline_ms)
        self.batch_sizes = str(batch_sizes)
        self.platform = str(platform)
        self.devices_per_proc = int(devices_per_proc)
        self.startup_timeout_s = float(startup_timeout_s)
        self.sync_wal = bool(sync_wal)
        self.blackbox = bool(blackbox)
        self.python = python or sys.executable
        self.extra_args = list(extra_args or [])
        self._lock = threading.Lock()
        self._procs: Dict[str, FleetProcess] = {}
        self._closed = False
        os.makedirs(self.workdir, exist_ok=True)
        if spawn:
            self.spawn_all()

    # -- spawn -------------------------------------------------------------
    def _proc_paths(self, name: str) -> dict:
        d = os.path.join(self.workdir, name)
        os.makedirs(d, exist_ok=True)
        return {"dir": d,
                "wal": os.path.join(d, "mutations.wal"),
                "ckpt": os.path.join(d, "checkpoint.npz"),
                "port_file": os.path.join(d, "port"),
                "log": os.path.join(d, "daemon.log"),
                "blackbox": os.path.join(d, "blackbox")}

    def _spawn_one(self, index: int, name: str, role: str,
                   primary_url: Optional[str]) -> FleetProcess:
        p = self._proc_paths(name)
        try:
            os.remove(p["port_file"])
        except OSError:
            pass
        cmd = [self.python, _FLEETD,
               "--name", name, "--role", role,
               "--port-file", p["port_file"],
               "--wal", p["wal"], "--checkpoint", p["ckpt"],
               "--cache-dir", p["dir"],
               "--n", str(self._dataset["n"]),
               "--dim", str(self._dataset["dim"]),
               "--seed", str(self._dataset["seed"]),
               "--n-lists", str(self._dataset["n_lists"]),
               "--k", str(self.k), "--n-probes", str(self.n_probes),
               "--batch-sizes", self.batch_sizes,
               "--deadline-ms", str(self.deadline_ms)]
        if role == "follower":
            expects(primary_url is not None,
                    "ProcessFleet: follower %s needs a primary url",
                    name)
            cmd += ["--primary-url", primary_url]
        if self.sync_wal:
            cmd += ["--sync-wal"]
        if self.blackbox:
            cmd += ["--blackbox", p["blackbox"]]
        cmd += self.extra_args
        env = dict(os.environ)
        env.update(device_env(index, self.platform,
                              self.devices_per_proc))
        with open(p["log"], "ab") as logf:
            popen = subprocess.Popen(cmd, stdout=logf, stderr=logf,
                                     cwd=p["dir"], env=env)
        obs.counter("raft.fleet.proc.spawned.total").inc()
        url = self._handshake(name, popen, p["port_file"])
        return FleetProcess(name, popen, url, p["dir"], role)

    def _handshake(self, name: str, popen: subprocess.Popen,
                   port_file: str) -> str:
        """Port-file poll → base url → /rpc/state poll to serving."""
        deadline = time.monotonic() + self.startup_timeout_s
        port = None
        while time.monotonic() < deadline:
            if popen.poll() is not None:
                raise RuntimeError(
                    f"fleetd {name}: exited rc={popen.returncode} "
                    f"during startup (see its daemon.log)")
            try:
                with open(port_file) as f:
                    txt = f.read().strip()
                if txt:
                    port = int(txt)
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.1)
        if port is None:
            popen.kill()
            raise TimeoutError(
                f"fleetd {name}: no port file after "
                f"{self.startup_timeout_s:.0f}s")
        url = f"http://127.0.0.1:{port}"
        wait_healthy(TransportClient(url),
                     timeout_s=max(5.0,
                                   deadline - time.monotonic()))
        return url

    def spawn_all(self) -> "ProcessFleet":
        """Bring up the whole fleet: the primary first (it owns the
        WAL and serves bootstrap), then every follower against it."""
        with self._lock:
            expects(not self._closed, "ProcessFleet: closed")
            expects(not self._procs, "ProcessFleet: already spawned")
        primary = self._spawn_one(0, "r0", "primary", None)
        with self._lock:
            self._procs[primary.name] = primary
        for i in range(1, self.n_procs):
            fp = self._spawn_one(i, f"r{i}", "follower", primary.url)
            with self._lock:
                self._procs[fp.name] = fp
        self._export_alive()
        return self

    def _export_alive(self) -> None:
        with self._lock:
            alive = sum(1 for fp in self._procs.values()
                        if fp.alive())
        obs.gauge("raft.fleet.proc.alive").set(alive)

    # -- introspection -----------------------------------------------------
    def processes(self) -> List[FleetProcess]:
        with self._lock:
            return list(self._procs.values())

    def process(self, name: str) -> FleetProcess:
        with self._lock:
            fp = self._procs.get(name)
        expects(fp is not None, "ProcessFleet: no process %r", name)
        return fp

    def primary(self) -> FleetProcess:
        with self._lock:
            for fp in self._procs.values():
                if fp.role == "primary":
                    return fp
        raise RuntimeError("ProcessFleet: no primary (all killed?)")

    def urls(self) -> Dict[str, str]:
        """``{name: url}`` — exactly the federator's ``instances``
        argument; each daemon's one port serves /metrics too."""
        with self._lock:
            return {n: fp.url for n, fp in self._procs.items()}

    def replicas(self, **client_kw) -> List[RemoteReplica]:
        """Fresh :class:`RemoteReplica` fronts for every process —
        feed them to a :class:`~raft_tpu.fleet.router.FleetRouter`."""
        with self._lock:
            items = list(self._procs.items())
        return [RemoteReplica(name, fp.url, **client_kw)
                for name, fp in items]

    def describe(self) -> dict:
        return {"workdir": self.workdir, "platform": self.platform,
                "processes": [fp.describe()
                              for fp in self.processes()]}

    # -- chaos / failover --------------------------------------------------
    def kill(self, name: str) -> int:
        """Real ``SIGKILL`` — no drain, no state bookkeeping; the
        router finds out the hard way. Returns the dead pid."""
        fp = self.process(name)
        pid = fp.pid
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        fp.popen.wait(timeout=30.0)
        obs.counter("raft.fleet.proc.killed.total").inc()
        get_logger("fleet").warning(
            "proc fleet: SIGKILL %s (pid %d)", name, pid)
        self._export_alive()
        return pid

    def promote(self, name: str, retarget_peers: bool = True) -> dict:
        """Complete a failover: promote follower ``name`` (its daemon
        opens its OWN WAL at the inherited next_seq — the RPC returns
        ``{primary, next_seq, epoch}``) and point every other live
        follower's replication at it."""
        fp = self.process(name)
        out = fp.client.promote(timeout=120.0)
        with self._lock:
            fp.role = "primary"
            peers = [o for o in self._procs.values()
                     if o.name != name and o.role == "follower"]
        obs.counter("raft.fleet.proc.promotions.total").inc()
        if retarget_peers:
            for peer in peers:
                if not peer.alive():
                    continue
                try:
                    peer.client.retarget(fp.url, timeout=30.0)
                except Exception:
                    get_logger("fleet").warning(
                        "proc fleet: retarget of %s at new primary "
                        "%s failed — it keeps its old target",
                        peer.name, name)
        return out

    def respawn(self, name: str, role: str = "follower") -> FleetProcess:
        """Bring a dead slot back (fresh process, same workdir —
        a promoted-primary slot restarts over its own WAL). The
        returned process replaces the old entry."""
        old = self.process(name)
        expects(not old.alive(),
                "ProcessFleet: %s is still alive — kill it first",
                name)
        index = int(name.lstrip("r")) if name.lstrip("r").isdigit() \
            else 0
        primary_url = None
        if role == "follower":
            primary_url = self.primary().url
        fp = self._spawn_one(index, name, role, primary_url)
        with self._lock:
            self._procs[name] = fp
        self._export_alive()
        return fp

    # -- shutdown ----------------------------------------------------------
    def close(self, drain_timeout_s: float = 10.0) -> None:
        """Graceful fleet shutdown: RPC stop (drain inside the daemon)
        → SIGTERM → wait → SIGKILL stragglers. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            procs = list(self._procs.values())
        for fp in procs:
            if not fp.alive():
                continue
            try:
                fp.client.stop(timeout=drain_timeout_s)
            except Exception:   # graftlint: disable=GL006
                # a dead/hung daemon gets the signal path below
                # (justified swallow: close must reach SIGTERM)
                pass
        deadline = time.monotonic() + drain_timeout_s
        for fp in procs:
            if fp.alive():
                fp.popen.terminate()
        for fp in procs:
            left = max(0.5, deadline - time.monotonic())
            try:
                fp.popen.wait(timeout=left)
            except subprocess.TimeoutExpired:
                fp.popen.kill()
                fp.popen.wait(timeout=10.0)
        self._export_alive()

    def __enter__(self) -> "ProcessFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
