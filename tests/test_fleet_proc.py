"""Multi-process read fleet tests (ISSUE 16): replica DBs as REAL
subprocesses streaming WAL over the two-plane socket transport, routed
HTTP reads through RemoteReplica handles, leader leases for
read-your-writes, kill/restart resume from the persisted standby
epoch + local WAL watermark, and fleet-wide admission posture over the
broker-ring control word and the telemetry aggregator.

Budget discipline (ISSUE 14): every test here spawns or talks to real
child processes, so the module arms an explicit faulthandler budget
even when the env watchdog is off, and the module fixture asserts no
child outlives teardown.
"""

import faulthandler
import json
import os
import struct
import time
import urllib.request

import pytest

from nornicdb_tpu import admission as adm
from nornicdb_tpu import obs
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import fleet as obs_fleet
from nornicdb_tpu.replication.fleet_proc import ProcessReadFleet

# explicit per-test budget: a hung subprocess fleet dumps every thread
# stack instead of silently eating the tier-1 timeout
FLEET_TEST_BUDGET_S = 240.0


@pytest.fixture(autouse=True)
def _fleet_watchdog():
    armed = not os.environ.get("NORNICDB_TEST_WATCHDOG_S")
    if armed:
        faulthandler.dump_traceback_later(FLEET_TEST_BUDGET_S,
                                          exit=False)
    try:
        yield
    finally:
        if armed:
            faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="module")
def pfleet(tmp_path_factory):
    """ONE subprocess fleet for the whole module (child spawn pays a
    full interpreter + JAX import; the tests share the topology the
    way the in-process suites share a DB)."""
    base = str(tmp_path_factory.mktemp("pfleet"))
    # the children are spawned beside a primary that owns the chip: what
    # they inherit says "tpu", and they must come up on the CPU anyway
    import jax  # noqa: F401 — this process reads the variable at import

    inherited = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "tpu"
    try:
        fleet = ProcessReadFleet(base, n_replicas=2,
                                 heartbeat_interval=0.1, auto_embed=True)
    finally:
        if inherited is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = inherited
    try:
        db = fleet.primary_db
        for i in range(30):
            db.store(f"fleet doc {i} about topic {i % 5}",
                     node_id=f"d{i}")
        assert fleet.wait_converged(30.0)
        fleet.admit_all_unchecked()
        yield fleet
    finally:
        fleet.close()
        # guaranteed teardown: no child outlives the module
        for proc in fleet.procs:
            assert not proc.alive()


def _drain_events(node):
    return [e for e in obs.event_snapshot(500, kind="drain")
            if e.get("node") == node]


def _fleet_ledger(name, reason=None):
    return [r for r in _audit.degrade_snapshot(800)
            if r.get("surface") == "fleet" and r.get("index") == name
            and (reason is None or r.get("reason") == reason)]


class TestTopology:
    def test_replicas_are_real_subprocesses(self, pfleet):
        pids = {proc.pid for proc in pfleet.procs}
        assert len(pids) == 2 and os.getpid() not in pids
        for proc in pfleet.procs:
            assert proc.alive()
            # the child streamed to the primary's watermark over the
            # real socket transport and said so in its ready file
            assert proc.ready_doc["transport_addr"][1] > 0
            assert proc.ready_doc["http_port"] > 0
            # pinned to the CPU by the spawner, whatever was inherited
            with open(f"/proc/{proc.pid}/environ", "rb") as f:
                assert b"JAX_PLATFORMS=cpu" in f.read().split(b"\0")

    def test_two_plane_stream_converges(self, pfleet):
        target = pfleet.primary_db._base.wal.last_seq
        for remote in pfleet.remotes:
            remote.ready_reasons()
            assert remote.applied_seq() == target
            assert remote.lag_ops() == 0

    def test_standby_epoch_persisted_on_disk(self, pfleet):
        for proc in pfleet.procs:
            path = os.path.join(pfleet.base_dir, proc.name,
                                "standby.epoch")
            assert os.path.exists(path)
            with open(path) as f:
                assert int(f.read().strip()) >= 1

    def test_child_state_feeds_fleet_aggregator(self, pfleet):
        summary = obs.fleet_summary()
        for proc in pfleet.procs:
            assert summary["sources"].get(proc.name) == "ok"
            assert proc.name in summary["replicas"]


class TestRoutedReads:
    def test_http_search_routes_to_replica(self, pfleet):
        doc = pfleet.router.http_search(
            {"query": "fleet doc 3", "limit": 5})
        assert doc and doc["results"]
        drains = pfleet.router.drain_state()
        assert all(st["admitted"] and st["drain"] is None
                   for st in drains.values())

    def test_remote_replica_graduated_handle(self, pfleet):
        remote = pfleet.remotes[0]
        assert remote.db is None and remote.supports_vec is False
        out = remote.search({"query": "fleet doc 1", "limit": 3})
        assert out["results"]
        state = remote.state()
        assert "state" in state
        assert remote.epoch() >= 1

    def test_trace_header_crosses_the_http_hop(self, pfleet):
        """Cross-process trace propagation over the routed read: the
        parent's trace id must appear as a ROOT span in the serving
        child's own trace ring (the child adopted the propagated id
        instead of minting a fresh one)."""
        with obs.trace("fleet-routed-read") as span:
            doc = pfleet.router.http_search(
                {"query": "fleet doc 7", "limit": 2})
            assert doc
            tid = span.trace_id
        assert tid
        found = False
        for proc in pfleet.procs:
            with urllib.request.urlopen(
                    proc.base_url + "/admin/traces", timeout=5) as resp:
                body = json.loads(resp.read())
            if any(t.get("trace_id") == tid
                   for t in body.get("traces", [])):
                found = True
        assert found


class TestLeases:
    def test_lease_grant_and_read_your_writes(self, pfleet):
        assert pfleet.wait_converged(30.0)
        pfleet.router.refresh_leases()
        leases = pfleet.router.lease_state()
        assert set(leases) == {"replica-0", "replica-1"}
        wm = pfleet.router._primary_watermark()
        for doc in leases.values():
            assert doc["watermark"] >= wm
        fresh = pfleet.router.pick_fresh()
        assert fresh is not None
        doc = pfleet.router.http_search(
            {"query": "fleet doc 5", "limit": 3},
            read_your_writes=True)
        assert doc and doc["results"]
        grants = [e for e in obs.event_snapshot(500, kind="lease_grant")]
        assert {e["node"] for e in grants} >= {"replica-0", "replica-1"}

    def test_write_invalidates_lease_until_caught_up(self, pfleet):
        pfleet.router.refresh_leases()
        # a write moves the primary watermark past every held lease
        pfleet.primary_db.store("lease invalidation probe",
                                node_id="lease-probe")
        wm = pfleet.router._primary_watermark()
        stale = [doc for doc in pfleet.router.lease_state().values()
                 if doc["watermark"] < wm]
        assert stale  # at least one lease is now behind the watermark
        assert pfleet.wait_converged(30.0)
        pfleet.router.refresh_leases()
        assert all(doc["watermark"] >= wm
                   for doc in pfleet.router.lease_state().values())


class TestPosturePropagation:
    def test_ring_control_word_pins_every_worker(self, tmp_path):
        """Test-pinned ring propagation: one endpoint publishes shed
        into the control block; the local controller's next refresh
        tightens to it; the TTL clears a stale signal."""
        from nornicdb_tpu.search import broker as brk

        b = brk.DispatchBroker(lambda *a: [], targets={}, n_workers=1)
        try:
            b.bind_admission()
            client = brk.BrokerClient(
                b.client_spec(0, cross_process=False))
            try:
                assert client.publish_posture(2)  # a peer went "shed"
                assert adm.CONTROLLER.refresh(force=True) == "shed"
                assert adm.CONTROLLER.posture_local == "admit"
                assert adm.CONTROLLER.posture_source == "fleet"
                # age the word past the TTL: the fleet signal clears
                struct.pack_into(
                    "<d", b._buf, brk._OFF_POSTURE_TS,
                    time.time() - 10 * adm.cfg()["fleet_posture_ttl_s"])
                assert adm.CONTROLLER.refresh(force=True) == "admit"
                # write-if-more-severe: a healthy publish cannot clear
                # a FRESH severe word early
                assert client.publish_posture(3)
                assert not client.publish_posture(0)
                assert client.ring_posture()[0] == 3
            finally:
                client.close()
        finally:
            b.stop()
            adm.reload()

    def test_aggregator_sweep_pins_cross_node(self, pfleet):
        """Test-pinned cross-node propagation: a peer node's state dump
        carries its posture gauge; the aggregator sweep becomes the
        primary controller's posture source."""

        def overloaded_peer():
            return [{"name": "nornicdb_admission_posture",
                     "kind": "gauge", "help": "", "labels": (),
                     "children": {(): 2.0}}]

        obs_fleet.register_source("overloaded-peer", overloaded_peer)
        try:
            level, _age = obs_fleet.refresh_remote_posture()
            assert level == 2
            # ProcessReadFleet registered the aggregator sweep as a
            # posture source at construction
            assert adm.CONTROLLER.refresh(force=True) == "shed"
            assert adm.CONTROLLER.posture_source == "fleet"
        finally:
            obs_fleet.unregister_source("overloaded-peer")
            obs_fleet.refresh_remote_posture()
            adm.reload()

    def test_live_children_export_posture_gauge(self, pfleet):
        """The REAL cross-process feed: each child's /admin/fleet/state
        carries nornicdb_admission_posture (healthy: level 0), so the
        sweep sees live peers, not just fakes."""
        seen = 0
        for name, fn in [(p.name,
                          obs_fleet.http_state_source(p.base_url))
                         for p in pfleet.procs]:
            state = fn()
            fams = {fam["name"] for fam in state}
            assert "nornicdb_admission_posture" in fams, name
            seen += 1
        assert seen == 2
        level, _age = obs_fleet.refresh_remote_posture()
        assert level == 0  # a healthy fleet pins nothing


class TestKillRestart:
    def test_kill_drains_once_survivors_serve_restart_resumes(
            self, pfleet):
        """The ISSUE 16 failure drill: SIGKILL one replica subprocess
        mid-load — the router drains it EXACTLY once (ledger reason
        replica_drain), survivors keep serving, and the restarted
        child resumes from its persisted epoch + local WAL watermark
        without a full re-bootstrap."""
        victim = pfleet.procs[0]
        n_ledger = len(_fleet_ledger(victim.name, "replica_drain"))
        n_events = len(_drain_events(victim.name))
        epoch_before = victim.remote().epoch()
        victim.kill()
        assert not victim.alive()

        served = 0
        for _ in range(10):
            if pfleet.router.http_search(
                    {"query": "fleet doc", "limit": 2}):
                served += 1
        assert served >= 8  # the survivor keeps the fleet serving
        st = pfleet.router.drain_state()
        assert st[victim.name]["drain"] is not None
        assert st["replica-1"]["drain"] is None
        # exactly once: one new ledger record, one new drain event
        assert len(_fleet_ledger(victim.name, "replica_drain")) \
            == n_ledger + 1
        assert len(_drain_events(victim.name)) == n_events + 1

        # restart: the ready file proves tail-resume (a fresh bootstrap
        # would report resume_seq 0)
        pfleet.restart(0)
        rd = pfleet.procs[0].ready_doc
        assert rd["resume_seq"] > 0
        assert rd["resume_epoch"] >= epoch_before
        # new writes stream to the restarted child on its NEW ports
        for i in range(5):
            pfleet.primary_db.store(f"post-restart doc {i}",
                                    node_id=f"pr{i}")
        assert pfleet.wait_converged(30.0)
        pfleet.admit_all_unchecked()
        doc = pfleet.router.http_search(
            {"query": "post-restart doc", "limit": 3})
        assert doc and doc["results"]
        pfleet.router.refresh_leases()
        assert set(pfleet.router.lease_state()) \
            == {"replica-0", "replica-1"}
