"""Multi-process read fleet: replica DBs as real subprocesses (ISSUE 16).

The in-process :class:`~nornicdb_tpu.replication.read_fleet.ReadFleet`
proved replica correctness (parity, drains, failover) but every replica
shares one interpreter and one GIL — measured fleet read scaling was
~0.5x, i.e. a replica made reads *slower*. This module takes the same
topology across real process boundaries:

- each replica runs ``python -m nornicdb_tpu.replication.fleet_proc
  --replica <json-spec>`` — the api/wire_plane.py spawn discipline: a
  clean interpreter via module entry (never multiprocessing spawn, which
  re-imports the parent's ``__main__``), PYTHONPATH pinned to the
  package parent, stderr to a file (a pipe nobody drains would block the
  child mid-write), an atomically-written ready file the parent polls,
  and a stop-file + parent-pid watch in the child's serve loop so an
  orphaned replica exits instead of eating the test timeout;
- the child is a full :class:`ReadReplica` (WAL streaming over the
  two-plane socket transport, epoch persisted in its data dir) fronted
  by the standard :class:`~nornicdb_tpu.api.http_server.HttpServer` —
  ``/readyz`` carries the replica watermark doc, ``/nornicdb/search``
  serves reads, ``/admin/fleet/state`` feeds the fleet aggregator;
- the parent-side :class:`ReplicaProcess` handle wraps spawn/stop/kill,
  and :class:`ProcessReadFleet` assembles 1 in-parent primary + N
  replica subprocesses behind a :class:`~nornicdb_tpu.api.fleet_router.
  FleetRouter` of :class:`RemoteReplica` node handles, with every
  replica registered as a fleet telemetry source so ``/admin/fleet``
  merges the whole topology.

A killed replica resumes from its persisted epoch + seq-aligned local
WAL: the restart pulls only the tail (``resume_seq`` in the ready file
is the watermark recovered from disk BEFORE any catch-up), never a full
re-bootstrap.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple


# -- child side --------------------------------------------------------------


def _replica_main(spec: Dict[str, Any]) -> None:
    """Subprocess entry: build the replica, attach, serve until the
    parent signals stop or disappears."""
    work_dir = spec["work_dir"]
    name = spec["name"]
    stop_paths = (os.path.join(work_dir, "stop"),
                  os.path.join(work_dir, f"stop-{name}"))
    try:
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.replication.read_fleet import ReadReplica

        replica = ReadReplica(
            name, spec["data_dir"], database=spec.get("database", "neo4j"),
            heartbeat_interval=float(spec.get("heartbeat_interval", 0.25)),
            failover_timeout=float(spec.get("failover_timeout", 30.0)),
        )
        # the watermark/epoch recovered from LOCAL state, before any
        # catch-up traffic: the parent's restart test reads this to
        # prove the rejoin was a tail-pull, not a re-bootstrap
        resume_seq = int(replica.standby.applied_seq)
        resume_epoch = int(replica.standby.epoch)
        replica.attach(tuple(spec["primary_addr"]),
                       [tuple(a) for a in spec.get("peer_addrs", ())])
        http = HttpServer(replica.db, host=spec.get("host", "127.0.0.1"),
                          port=0).start()
        ready_doc = {
            "pid": os.getpid(),
            "transport_addr": list(replica.addr),
            "http_port": http.port,
            "resume_seq": resume_seq,
            "resume_epoch": resume_epoch,
        }
        ready_path = os.path.join(work_dir, f"ready-{name}")
        with open(ready_path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(ready_doc, f)
        os.replace(ready_path + ".tmp", ready_path)
    except Exception:  # noqa: BLE001 — parent's ready-poll times out
        import traceback

        traceback.print_exc()
        os._exit(1)

    ppid = os.getppid()
    while True:
        time.sleep(0.25)
        if any(os.path.exists(p) for p in stop_paths):
            break
        if os.getppid() != ppid:
            break  # orphaned: the parent died without cleanup
    try:
        http.stop()
        replica.close()
    except Exception:  # noqa: BLE001
        pass
    os._exit(0)


# -- parent side -------------------------------------------------------------


class ReplicaProcess:
    """Parent-side handle over one replica subprocess."""

    def __init__(self, name: str, data_dir: str, work_dir: str,
                 primary_addr: Tuple[str, int],
                 peer_addrs: Sequence[Tuple[str, int]] = (),
                 database: str = "neo4j",
                 heartbeat_interval: float = 0.25,
                 failover_timeout: float = 30.0,
                 host: str = "127.0.0.1"):
        self.name = str(name)
        self.data_dir = data_dir
        self.work_dir = work_dir
        self.host = host
        self._spec = {
            "name": self.name,
            "data_dir": data_dir,
            "work_dir": work_dir,
            "primary_addr": list(primary_addr),
            "peer_addrs": [list(a) for a in peer_addrs],
            "database": database,
            "heartbeat_interval": heartbeat_interval,
            "failover_timeout": failover_timeout,
            "host": host,
        }
        self._proc: Optional[Any] = None
        self.ready_doc: Dict[str, Any] = {}

    # -- lifecycle -------------------------------------------------------

    def start(self, ready_timeout_s: float = 90.0) -> "ReplicaProcess":
        import subprocess
        import sys

        import nornicdb_tpu as _pkg
        from nornicdb_tpu.jaxenv import cpu_child_env

        os.makedirs(self.work_dir, exist_ok=True)
        for stale in (f"ready-{self.name}", f"stop-{self.name}"):
            try:
                os.unlink(os.path.join(self.work_dir, stale))
            except OSError:
                pass
        # the child interpreter must resolve this package regardless of
        # the parent's cwd: prepend the package parent (wire_plane
        # discipline)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(_pkg.__file__)))
        # a replica never owns the chip: the primary holds it
        env = cpu_child_env()
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
            else "")
        err_path = os.path.join(self.work_dir, f"{self.name}.err")
        with open(err_path, "wb") as err_f:
            self._proc = subprocess.Popen(
                [sys.executable, "-m",
                 "nornicdb_tpu.replication.fleet_proc", "--replica",
                 json.dumps(self._spec)],
                stdout=subprocess.DEVNULL, stderr=err_f, env=env)
        self._err_path = err_path
        ready_path = os.path.join(self.work_dir, f"ready-{self.name}")
        deadline = time.time() + ready_timeout_s
        while time.time() < deadline:
            if os.path.exists(ready_path):
                with open(ready_path, "r", encoding="utf-8") as f:
                    self.ready_doc = json.load(f)
                return self
            if self._proc.poll() is not None:
                raise RuntimeError(
                    f"replica {self.name} died during startup: "
                    f"{self.err_tail()}")
            time.sleep(0.05)
        self.stop()
        raise RuntimeError(
            f"replica {self.name} not ready within {ready_timeout_s:.0f}s")

    def err_tail(self, n: int = 800) -> str:
        try:
            with open(self._err_path, "rb") as f:
                return f.read().decode(errors="replace")[-n:]
        except OSError:
            return ""

    @property
    def addr(self) -> Tuple[str, int]:
        return tuple(self.ready_doc["transport_addr"])

    @property
    def base_url(self) -> str:
        return f"http://{self.host}:{self.ready_doc['http_port']}"

    @property
    def pid(self) -> Optional[int]:
        return None if self._proc is None else self._proc.pid

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def remote(self, timeout_s: float = 2.0):
        """The router-facing node handle for this process."""
        from nornicdb_tpu.api.fleet_router import RemoteReplica

        return RemoteReplica(self.name, self.base_url,
                             timeout_s=timeout_s)

    def kill(self) -> None:
        """Hard SIGKILL — failure injection for the drain tests."""
        if self._proc is not None:
            try:
                self._proc.kill()
                self._proc.wait(timeout=5)
            except Exception:  # noqa: BLE001
                pass

    def stop(self, timeout_s: float = 5.0) -> None:
        """Graceful stop via the per-replica stop file, escalating to
        terminate/kill — teardown is guaranteed (no orphan may outlive
        the test and eat the tier-1 timeout)."""
        if self._proc is None:
            return
        try:
            with open(os.path.join(self.work_dir, f"stop-{self.name}"),
                      "w") as f:
                f.write("1")
        except OSError:
            pass
        try:
            self._proc.wait(timeout=timeout_s)
        except Exception:  # noqa: BLE001
            self._proc.terminate()
            try:
                self._proc.wait(timeout=3)
            except Exception:  # noqa: BLE001
                self._proc.kill()
                try:
                    self._proc.wait(timeout=3)
                except Exception:  # noqa: BLE001
                    pass
        self._proc = None


class ProcessReadFleet:
    """1 in-parent primary + N replica subprocesses behind the router.

    Construction order (inverse of the in-process ReadFleet, because a
    child cannot exist before it can be told the primary's address):
    primary DB first with an empty peer set, then the replica processes
    — each attaches to the primary over the two-plane transport and
    pulls history — then the collected child transport addresses become
    the primary's streaming peer set, and each child's RemoteReplica
    handle joins the router. Every replica also registers as a fleet
    telemetry source (obs/fleet.py http_state_source) so
    ``/admin/fleet`` merges the whole topology."""

    def __init__(
        self,
        base_dir: str,
        n_replicas: int = 2,
        database: str = "neo4j",
        sync: str = "async",
        heartbeat_interval: float = 0.1,
        failover_timeout: float = 30.0,
        auto_embed: bool = False,
        ready_timeout_s: float = 90.0,
        http_timeout_s: float = 5.0,
    ):
        from nornicdb_tpu import obs
        from nornicdb_tpu.api.fleet_router import FleetRouter
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.db import DB
        from nornicdb_tpu.replication.replicator import ReplicationConfig

        self.base_dir = base_dir
        self.work_dir = os.path.join(base_dir, "fleet-proc")
        self._http_timeout_s = http_timeout_s
        self.procs: List[ReplicaProcess] = []
        self.remotes: List[Any] = []
        self.primary_db = None
        self.primary_http = None
        self._fleet_sources: List[str] = []
        try:
            cfg = ReplicationConfig(
                mode="ha_standby", ha_role="primary", node_id="primary",
                sync=sync, peers=[],
                heartbeat_interval=heartbeat_interval,
                failover_timeout=failover_timeout,
                data_listen=("127.0.0.1", 0),
            )
            self.primary_db = DB(
                os.path.join(base_dir, "primary"), engine="python",
                auto_embed=auto_embed, database=database,
                replication=cfg)
            primary_addr = self.primary_db._cluster_transport.addr
            # the primary's own HTTP surface: the single-process bench
            # baseline, and the fallback read target
            self.primary_http = HttpServer(self.primary_db, port=0).start()
            for i in range(n_replicas):
                proc = ReplicaProcess(
                    f"replica-{i}",
                    os.path.join(base_dir, f"replica-{i}"),
                    self.work_dir, primary_addr,
                    database=database,
                    heartbeat_interval=heartbeat_interval,
                    failover_timeout=failover_timeout,
                )
                self.procs.append(proc)
                proc.start(ready_timeout_s=ready_timeout_s)
            # children are attached and caught up: their transport
            # addresses become the primary's streaming peer set (list
            # swap is atomic; the stream/heartbeat loops read it fresh
            # each round)
            self.primary_db.replicator.config.peers = [
                tuple(p.addr) for p in self.procs]
            self.router = FleetRouter(self.primary_db)
            for proc in self.procs:
                remote = proc.remote(timeout_s=http_timeout_s)
                self.remotes.append(remote)
                self.router.add_replica(remote)
                obs.register_fleet_source(
                    proc.name, obs.http_state_source(proc.base_url))
                self._fleet_sources.append(proc.name)
            # cross-NODE admission posture (ISSUE 16): the replicas'
            # posture gauges ride the telemetry feeds just registered;
            # the aggregator sweep becomes a posture source for the
            # primary's controller
            from nornicdb_tpu import admission as _adm
            from nornicdb_tpu.obs import fleet as _obs_fleet

            self._posture_source = _obs_fleet.posture_source()
            _adm.CONTROLLER.add_posture_source(self._posture_source)
        except BaseException:
            self.close()
            raise

    @property
    def primary_url(self) -> str:
        return f"http://127.0.0.1:{self.primary_http.port}"

    def restart(self, index: int,
                ready_timeout_s: float = 90.0) -> ReplicaProcess:
        """Restart replica ``index`` in place. The child resumes from
        its persisted standby epoch + local WAL watermark (no full
        re-bootstrap — the ready doc's ``resume_seq``/``resume_epoch``
        prove it), comes back on fresh ephemeral ports, and the
        primary's streaming peer set plus the router's node handle are
        re-pointed at them. The replica rejoins UNADMITTED — callers
        re-admit once it converges, mirroring first boot."""
        from nornicdb_tpu import obs

        proc = self.procs[index]
        proc.stop()  # no-op when the child is already dead (kill())
        proc.start(ready_timeout_s=ready_timeout_s)
        self.primary_db.replicator.config.peers = [
            tuple(p.addr) for p in self.procs]
        remote = proc.remote(timeout_s=self._http_timeout_s)
        self.router.remove_replica(proc.name)
        self.router.add_replica(remote)
        self.remotes[index] = remote
        try:
            obs.unregister_fleet_source(proc.name)
        except Exception:  # noqa: BLE001
            pass
        obs.register_fleet_source(
            proc.name, obs.http_state_source(proc.base_url))
        if proc.name not in self._fleet_sources:
            self._fleet_sources.append(proc.name)
        return proc

    def admit_all_unchecked(self) -> None:
        """Admit every replica without the in-process parity probe —
        remote handles are parity-verified out of band against their
        own HTTP surface (bench/tests), per the RemoteReplica
        contract."""
        for proc in self.procs:
            self.router.admit_unchecked(proc.name)

    def wait_converged(self, timeout_s: float = 30.0) -> bool:
        """Block until every live replica's applied watermark reaches
        the primary's current last_seq (observed over each replica's
        /readyz watermark doc)."""
        self.primary_db._base.wal.flush()
        target = self.primary_db._base.wal.last_seq
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            done = True
            for remote in self.remotes:
                remote.ready_reasons()  # refreshes the watermark doc
                seq = remote.applied_seq()
                if seq is None or seq < target:
                    done = False
            if done:
                return True
            time.sleep(0.1)
        return False

    def close(self) -> None:
        from nornicdb_tpu import obs

        if getattr(self, "_posture_source", None) is not None:
            from nornicdb_tpu import admission as _adm

            _adm.CONTROLLER.remove_posture_source(self._posture_source)
            self._posture_source = None
        for name in self._fleet_sources:
            try:
                obs.unregister_fleet_source(name)
            except Exception:  # noqa: BLE001
                pass
        self._fleet_sources = []
        # broadcast stop to all children first so they exit in parallel
        try:
            os.makedirs(self.work_dir, exist_ok=True)
            with open(os.path.join(self.work_dir, "stop"), "w") as f:
                f.write("1")
        except OSError:
            pass
        for proc in self.procs:
            try:
                proc.stop()
            except Exception:  # noqa: BLE001
                pass
        if self.primary_http is not None:
            try:
                self.primary_http.stop()
            except Exception:  # noqa: BLE001
                pass
        if self.primary_db is not None:
            try:
                self.primary_db.close()
            except Exception:  # noqa: BLE001
                pass


if __name__ == "__main__":  # replica process entry
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--replica", required=True,
                    help="JSON replica spec from ReplicaProcess")
    _args = ap.parse_args()
    _replica_main(json.loads(_args.replica))
