"""Admission control (ISSUE 15): deadline budgets, priority lanes,
SLO-driven shedding.

The acceptance contracts pinned here:

- a rider already past its deadline budget FAILS FAST (one
  degrade-ledger record, one ``shed`` journal event, both trace-linked
  — exactly once each) instead of occupying a device slot;
- a rider whose remaining budget would expire inside the gather window
  triggers an immediate smaller dispatch;
- multi-lane backlogs seal in priority order (interactive > replay >
  background) with an aging promotion;
- honest backpressure: REST 429 carries ``Retry-After`` derived from
  the lane drain rate, gRPC maps to ``RESOURCE_EXHAUSTED`` with
  ``grpc-retry-pushback-ms`` trailing metadata, probe routes are never
  shed;
- the broker rider timeout consults the REQUEST deadline (a generous
  client deadline is not truncated to ``NORNICDB_WIRE_TIMEOUT_S``, a
  tight one is not held open);
- deadline propagation is visible end-to-end in one trace — budget at
  ingress, at the ring crossing, at the dispatch decision — including
  across a 2-worker WirePlane;
- a background rebuild kicked mid-load does not move interactive p99
  past the PR 3 overhead budget;
- ``/admin/scheduler`` serves the actuator state, mirrored in
  ``/admin/telemetry`` and SLO flight dumps.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu import admission as adm
from nornicdb_tpu import obs
from nornicdb_tpu.obs import audit
from nornicdb_tpu.obs import events as obs_events
from nornicdb_tpu.search.microbatch import BatchCoalescer, MicroBatcher


@pytest.fixture(autouse=True)
def _fresh_controller():
    adm.CONTROLLER.reset()
    yield
    adm.CONTROLLER.reset()


def _shed_ledger_records():
    return [r for r in audit.LEDGER.snapshot(limit=500)
            if r.get("to_tier") == "shed"]


def _shed_events():
    return obs_events.event_snapshot(limit=500, kind="shed")


# ---------------------------------------------------------------------------
# deadline context
# ---------------------------------------------------------------------------


class TestDeadlineContext:
    def test_mint_prefers_explicit_budget(self):
        now = 1000.0
        dl, explicit = adm.mint_deadline("grpc", 0.25, now=now)
        assert dl == 1000.25 and explicit is True

    def test_default_derives_from_slo_objective(self):
        # grpc objective threshold 100ms x factor 120 = 12s default
        now = 1000.0
        dl, explicit = adm.mint_deadline("grpc", None, now=now)
        assert explicit is False
        assert 1000.0 < dl <= now + adm.cfg()["deadline_defaults_s"]["*"]
        assert dl == now + adm.cfg()["deadline_defaults_s"]["grpc"]

    def test_header_parse_garbage_degrades_to_default(self):
        d_bad, exp_bad = adm.parse_deadline_header("not-a-number",
                                                   "http")
        d_none, exp_none = adm.parse_deadline_header(None, "http")
        assert abs(d_bad - d_none) < 1.0  # both the default budget
        assert exp_bad is False and exp_none is False
        d, explicit = adm.parse_deadline_header("250", "http")
        assert explicit is True
        assert 0.0 < d - time.time() <= 0.3

    def test_scope_binds_and_restores(self):
        assert adm.deadline() is None
        dl = time.time() + 1.0
        with adm.request_scope("http", dl):
            assert adm.deadline() == dl
            assert adm.remaining() <= 1.0
        assert adm.deadline() is None

    def test_lane_scope_nests(self):
        assert adm.lane() == adm.LANE_INTERACTIVE
        with adm.lane_scope(adm.LANE_BACKGROUND):
            assert adm.lane() == adm.LANE_BACKGROUND
            with adm.lane_scope(adm.LANE_REPLAY):
                assert adm.lane() == adm.LANE_REPLAY
            assert adm.lane() == adm.LANE_BACKGROUND
        assert adm.lane() == adm.LANE_INTERACTIVE

    def test_lane_rank_aging_promotion(self):
        assert adm.lane_rank(adm.LANE_INTERACTIVE) == 0
        assert adm.lane_rank(adm.LANE_REPLAY) == 1
        assert adm.lane_rank(adm.LANE_BACKGROUND) == 2
        # an aged background rider seals like interactive
        aged = adm.cfg()["lane_max_wait_s"] + 0.1
        assert adm.lane_rank(adm.LANE_BACKGROUND, waited_s=aged) == 0

    def test_select_batch_weighted_minimum_share(self):
        """Lanes competing for one batch: interactive dominates by
        priority, but background is GUARANTEED its weighted minimum
        share (NORNICDB_LANE_WEIGHTS) — weighted queuing, not pure
        starvation-prone priority."""
        class It:
            def __init__(self, i, lane):
                self.i, self.lane, self.t_enq = i, lane, time.time()

        now = time.time()
        pending = [It(i, adm.LANE_INTERACTIVE) for i in range(100)] \
            + [It(100 + i, adm.LANE_BACKGROUND) for i in range(20)]
        batch, rest = adm.select_batch(pending, 16, now)
        assert len(batch) == 16
        lanes = [it.lane for it in batch]
        # weights 16:1 over a 16-slot batch: background still lands
        # its floor-1 guaranteed slot; the rest is interactive
        assert lanes.count(adm.LANE_BACKGROUND) >= 1
        assert lanes.count(adm.LANE_INTERACTIVE) >= 14
        assert len(rest) == len(pending) - 16
        # FIFO within each lane
        it_ids = [it.i for it in batch
                  if it.lane == adm.LANE_INTERACTIVE]
        assert it_ids == sorted(it_ids)

    def test_request_scope_binds_resolved_lane(self):
        """The ingress scope counts the request on the lane the shed
        verdict used — a write flood registers as background
        pressure, not interactive."""
        dl = time.time() + 1.0
        with adm.request_scope("grpc", dl,
                               lane_name=adm.LANE_BACKGROUND,
                               explicit=True):
            assert adm.lane() == adm.LANE_BACKGROUND
            assert adm.deadline_explicit() is True
            assert adm.CONTROLLER.inflight(adm.LANE_BACKGROUND) == 1
            assert adm.CONTROLLER.inflight(adm.LANE_INTERACTIVE) == 0
        assert adm.CONTROLLER.inflight(adm.LANE_BACKGROUND) == 0


# ---------------------------------------------------------------------------
# deadline-aware MicroBatcher dispatch
# ---------------------------------------------------------------------------


def _echo_batcher(**kw):
    calls = []

    def search_batch(queries, k):
        calls.append(len(queries))
        return [[("id", 1.0)]] * len(queries)

    mb = MicroBatcher(search_batch, surface="t-adm", **kw)
    return mb, calls


class TestMicroBatcherDeadline:
    def test_expired_rider_fails_fast_exactly_once(self):
        mb, calls = _echo_batcher()
        led0 = len(_shed_ledger_records())
        ev0 = len(_shed_events())
        with obs.trace("wire", method="t-adm-dead") as root:
            with adm.deadline_scope(time.time() - 0.01):
                with pytest.raises(adm.DeadlineExceeded):
                    mb.search([0.1, 0.2], 3)
        # never dispatched, never queued a device slot
        assert calls == []
        assert mb.queue_depth() == 0
        led = _shed_ledger_records()[: len(_shed_ledger_records()) - led0]
        led = _shed_ledger_records()
        assert len(led) - led0 == 1
        rec = led[0]
        assert rec["reason"] == "deadline"
        assert rec["trace_id"] == root.trace_id
        evs = _shed_events()
        assert len(evs) - ev0 == 1
        assert evs[-1]["trace_id"] == root.trace_id
        assert evs[-1]["reason"] == "deadline"

    def test_expired_in_queue_fails_fast_without_dispatch(self):
        mb, calls = _echo_batcher()
        release = threading.Event()

        def slow_batch(queries, k):
            release.wait(timeout=5.0)
            calls.append(len(queries))
            return [[("id", 1.0)]] * len(queries)

        mb._search_batch = slow_batch
        errs = []

        def leader():
            try:
                mb.search([1.0, 0.0], 1)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        t_lead = threading.Thread(target=leader)
        t_lead.start()
        for _ in range(100):
            if mb._busy:
                break
            time.sleep(0.005)
        assert mb._busy

        def rider():
            with adm.deadline_scope(time.time() + 0.05):
                try:
                    mb.search([0.0, 1.0], 1)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

        t_ride = threading.Thread(target=rider)
        t_ride.start()
        t_ride.join(timeout=3.0)
        assert not t_ride.is_alive(), "rider stuck past its deadline"
        release.set()
        t_lead.join(timeout=5.0)
        # the rider failed fast with DeadlineExceeded; the leader served
        assert any(isinstance(e, adm.DeadlineExceeded) for e in errs)
        assert calls == [1]  # only the leader's row dispatched

    def test_tight_budget_skips_gather_window(self):
        """A rider whose remaining budget would expire inside the
        gather window dispatches immediately (smaller batch NOW)."""
        mb, calls = _echo_batcher(gather_window_s=0.25)
        mb._last_batch = 4  # pretend the last batch was concurrent
        with adm.deadline_scope(time.time() + 0.1):
            t0 = time.time()
            mb.search([0.5, 0.5], 1)
            elapsed = time.time() - t0
        # without the deadline the leader would wait the full 250ms
        # window; with it the dispatch is immediate
        assert elapsed < 0.2, elapsed
        assert calls == [1]
        fam = obs.REGISTRY.get("nornicdb_deadline_early_dispatch_total")
        child = fam.children().get(("t-adm",))
        assert child is not None and child.value >= 1

    def test_lane_priority_orders_multi_lane_backlog(self):
        order = []
        release = threading.Event()
        first = threading.Event()

        def batch(queries, k):
            if not first.is_set():
                first.set()
                release.wait(timeout=5.0)
            else:
                order.append(int(queries[0][0]))
            return [[("id", 1.0)]] * len(queries)

        mb = MicroBatcher(batch, max_batch=1, surface="t-adm-lane")
        done = []

        def go(row, lane):
            def run():
                with adm.lane_scope(lane):
                    mb.search([float(row), 0.0], 1)
                done.append(row)

            t = threading.Thread(target=run)
            t.start()
            return t

        threads = [go(0, adm.LANE_INTERACTIVE)]  # becomes the leader
        first.wait(timeout=5.0)
        # backlog while the leader is busy: background first in ARRIVAL
        # order, interactive second — priority must invert arrival
        threads.append(go(1, adm.LANE_BACKGROUND))
        time.sleep(0.05)
        threads.append(go(2, adm.LANE_INTERACTIVE))
        time.sleep(0.05)
        release.set()
        for t in threads:
            t.join(timeout=5.0)
        assert order[0] == 2, order  # interactive sealed first
        assert 1 in order

    def test_coalescer_expired_item_fails_fast(self):
        co = BatchCoalescer(lambda items: items, surface="t-adm-co")
        with adm.deadline_scope(time.time() - 0.01):
            with pytest.raises(adm.DeadlineExceeded):
                co.submit("x")
        assert co.queue_depth() == 0
        assert co.batches == 0


# ---------------------------------------------------------------------------
# honest-backpressure conformance: REST 429 + gRPC RESOURCE_EXHAUSTED
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shed_serving():
    import grpc

    import nornicdb_tpu
    from nornicdb_tpu.api.grpc_server import GrpcServer
    from nornicdb_tpu.api.http_server import HttpServer
    from nornicdb_tpu.api.proto import qdrant_pb2 as q

    db = nornicdb_tpu.open(auto_embed=False)
    emb = db._embedder
    for i in range(8):
        db.store(f"shed doc {i}", node_id=f"sh{i}",
                 embedding=emb.embed(f"shed doc {i}"))
    grpc_srv = GrpcServer(db, port=0).start()
    http = HttpServer(db, port=0).start()
    ch = grpc.insecure_channel(grpc_srv.address)

    def call(method, request, resp_cls, **kw):
        return ch.unary_unary(
            method,
            request_serializer=lambda r: r.SerializeToString(),
            response_deserializer=resp_cls.FromString)(request, **kw)

    req = q.CreateCollection(collection_name="shed")
    req.vectors_config.params.size = 8
    req.vectors_config.params.distance = q.Cosine
    call("/qdrant.Collections/Create", req, q.CollectionOperationResponse)
    up = q.UpsertPoints(collection_name="shed")
    for i in range(8):
        p = up.points.add()
        p.id.num = i
        p.vectors.vector.data.extend([float((i >> j) & 1)
                                      for j in range(8)])
    call("/qdrant.Points/Upsert", up, q.PointsOperationResponse)
    yield {"db": db, "http": http, "call": call, "q": q,
           "grpc": grpc_srv}
    ch.close()
    grpc_srv.stop()
    http.stop()
    db.close()


def _force_posture(monkeypatch, posture):
    monkeypatch.setattr(adm.CONTROLLER, "refresh",
                        lambda now=None, force=False: posture)
    monkeypatch.setattr(adm.CONTROLLER, "posture", posture)


class TestHonestBackpressure:
    def test_rest_429_carries_retry_after(self, shed_serving,
                                          monkeypatch):
        _force_posture(monkeypatch, "shed_hard")
        led0 = len(_shed_ledger_records())
        ev0 = len(_shed_events())
        body = json.dumps({"query": "shed doc", "limit": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{shed_serving['http'].port}"
            f"/nornicdb/search", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        e = ei.value
        assert e.code == 429
        ra = e.headers.get("Retry-After")
        assert ra is not None and int(ra) >= 1
        payload = json.loads(e.read())
        assert "ResourceExhausted" in payload["errors"][0]["code"]
        # exactly ONE ledger record and ONE journal event, trace-linked
        led = _shed_ledger_records()
        assert len(led) - led0 == 1
        assert led[0]["reason"] == "shed"
        assert led[0].get("trace_id")
        evs = _shed_events()
        assert len(evs) - ev0 == 1
        assert evs[-1]["trace_id"] == led[0]["trace_id"]

    def test_http_lane_classification(self):
        from nornicdb_tpu.api.http_server import _shed_lane_for

        # qdrant point READS stay interactive (gRPC parity)
        assert _shed_lane_for(
            "POST", "/collections/c/points/search") \
            == adm.LANE_INTERACTIVE
        assert _shed_lane_for(
            "POST", "/collections/c/points/scroll") \
            == adm.LANE_INTERACTIVE
        assert _shed_lane_for(
            "POST", "/collections/c/points/count") \
            == adm.LANE_INTERACTIVE
        # point WRITES ride background
        assert _shed_lane_for("PUT", "/collections/c/points") \
            == adm.LANE_BACKGROUND
        assert _shed_lane_for(
            "POST", "/collections/c/points/delete") \
            == adm.LANE_BACKGROUND
        # probes exempt
        assert _shed_lane_for("GET", "/readyz") is None
        assert _shed_lane_for("GET", "/admin/scheduler") is None

    def test_cached_hit_served_under_shed(self, shed_serving,
                                          monkeypatch):
        """A byte-fresh wire-cache hit is pure goodput: it is served
        even under shed_hard — only MISSES pass the controller."""
        body = json.dumps({"query": "shed doc cached-hit",
                           "limit": 2}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{shed_serving['http'].port}"
            f"/nornicdb/search", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert resp.status == 200  # populate the wire cache
        _force_posture(monkeypatch, "shed_hard")
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert resp.status == 200  # hit: never shed
        # a fresh body (miss) under the same posture sheds
        miss = urllib.request.Request(
            f"http://127.0.0.1:{shed_serving['http'].port}"
            f"/nornicdb/search",
            data=json.dumps({"query": "shed doc miss-path",
                             "limit": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(miss, timeout=5)
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After")

    def test_probe_routes_never_shed(self, shed_serving, monkeypatch):
        _force_posture(monkeypatch, "shed_hard")
        port = shed_serving["http"].port
        for path in ("/health", "/readyz", "/metrics"):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}",
                        timeout=5) as resp:
                    assert resp.status in (200, 503)
            except urllib.error.HTTPError as e:
                assert e.code == 503  # readyz degraded is fine; not 429

    def test_grpc_resource_exhausted_with_pushback(self, shed_serving,
                                                   monkeypatch):
        import grpc

        _force_posture(monkeypatch, "shed_hard")
        q = shed_serving["q"]
        sr = q.SearchPoints(collection_name="shed",
                            vector=[0.9] * 8, limit=3)
        with pytest.raises(grpc.RpcError) as ei:
            shed_serving["call"]("/qdrant.Points/Search", sr,
                                 q.SearchResponse)
        e = ei.value
        assert e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        md = dict(e.trailing_metadata() or ())
        assert int(md["grpc-retry-pushback-ms"]) >= 1000

    def test_degrade_posture_sheds_background_not_interactive(
            self, shed_serving, monkeypatch):
        import grpc

        _force_posture(monkeypatch, "degrade")
        q = shed_serving["q"]
        # interactive read passes
        sr = q.SearchPoints(collection_name="shed",
                            vector=[0.7] * 8, limit=3)
        resp = shed_serving["call"]("/qdrant.Points/Search", sr,
                                    q.SearchResponse)
        assert len(resp.result) >= 1
        # background write (upsert convoy lane) sheds
        up = q.UpsertPoints(collection_name="shed")
        p = up.points.add()
        p.id.num = 99
        p.vectors.vector.data.extend([0.5] * 8)
        with pytest.raises(grpc.RpcError) as ei:
            shed_serving["call"]("/qdrant.Points/Upsert", up,
                                 q.PointsOperationResponse)
        assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED

    def test_admission_tier_gate_forces_brute(self, monkeypatch):
        _force_posture(monkeypatch, "degrade")
        assert not audit.admission_allows("vector_walk_f32")
        assert not audit.admission_allows("vector_pq")
        assert not audit.admission_allows("graph_chain_device")
        assert audit.admission_allows("vector_brute_f32")
        assert audit.admission_allows("hybrid_brute_f32")
        assert audit.admission_allows("host")
        assert audit.admission_allows("cached")

    def test_cagra_degrades_to_brute_under_admission_hold(
            self, monkeypatch):
        from nornicdb_tpu.search.cagra import CagraIndex

        rng = np.random.default_rng(4)
        vecs = rng.standard_normal((600, 16)).astype(np.float32)
        idx = CagraIndex(min_n=256)
        idx.add_batch([(f"v{i}", vecs[i]) for i in range(len(vecs))])
        assert idx.build()
        _force_posture(monkeypatch, "degrade")
        led0 = audit.LEDGER.recorded
        res = idx.search_batch(vecs[:2], 5)
        assert len(res) == 2 and res[0][0][0] == "v0"
        recs = [r for r in audit.LEDGER.snapshot(limit=20)
                if r["reason"] == "admission"]
        assert audit.LEDGER.recorded > led0
        assert recs and recs[0]["from_tier"].startswith("vector_walk")


# ---------------------------------------------------------------------------
# broker: the rider timeout consults the request deadline
# ---------------------------------------------------------------------------


class TestBrokerDeadline:
    def _broker(self, dispatch, **kw):
        from nornicdb_tpu.search.broker import (
            BrokerClient,
            DispatchBroker,
        )

        broker = DispatchBroker(dispatch, targets=kw.pop("targets", {}),
                                n_workers=1, slots=8,
                                gather_window_s=0.0).start()
        spec = broker.client_spec(0, cross_process=False)
        spec.update(kw)
        return broker, BrokerClient(spec)

    def test_tight_deadline_not_held_open(self):
        from nornicdb_tpu.search.broker import BrokerTimeout

        def slow(key, queries, k):
            time.sleep(1.0)
            return [[("id", 1.0)]] * len(queries)

        broker, client = self._broker(slow)
        try:
            t0 = time.time()
            with adm.deadline_scope(time.time() + 0.3):
                with pytest.raises(BrokerTimeout):
                    client.vec_search("k", np.ones(4, np.float32), 1)
            elapsed = time.time() - t0
            # the flat NORNICDB_WIRE_TIMEOUT_S default is 15s; the
            # rider honored its 300ms budget instead
            assert elapsed < 1.0, elapsed
        finally:
            time.sleep(1.1)  # let the dispatch finish before teardown
            client.close()
            broker.stop()

    def test_generous_deadline_not_truncated(self):
        def slow(key, queries, k):
            time.sleep(0.5)
            return [[("id", 1.0)]] * len(queries)

        broker, client = self._broker(slow, timeout_s=0.2)
        try:
            # flat rider timeout 200ms would fail this op; the 5s
            # request budget overrides it
            with adm.deadline_scope(time.time() + 5.0):
                doc = client.vec_search("k", np.ones(4, np.float32), 1)
            assert doc["hits"]
        finally:
            client.close()
            broker.stop()

    def test_default_budget_clamps_to_flat_timeout(self):
        """A server-minted DEFAULT budget (30s http) must not extend
        the flat rider timeout — dead-plane detection stays at
        NORNICDB_WIRE_TIMEOUT_S; only explicit client budgets may
        extend it."""
        from nornicdb_tpu.search.broker import BrokerTimeout

        def slow(key, queries, k):
            time.sleep(0.8)
            return [[("id", 1.0)]] * len(queries)

        broker, client = self._broker(slow, timeout_s=0.2)
        try:
            with adm.request_scope("http", time.time() + 30.0,
                                   explicit=False):
                t0 = time.time()
                with pytest.raises(BrokerTimeout):
                    client.vec_search("k", np.ones(4, np.float32), 1)
                assert time.time() - t0 < 0.6  # flat 0.2s, not 30s
        finally:
            time.sleep(0.9)  # let the dispatch finish before teardown
            client.close()
            broker.stop()

    def test_expired_budget_never_posts(self):
        calls = []

        def dispatch(key, queries, k):
            calls.append(1)
            return [[("id", 1.0)]] * len(queries)

        broker, client = self._broker(dispatch)
        try:
            with adm.deadline_scope(time.time() - 0.01):
                with pytest.raises(adm.DeadlineExceeded):
                    client.vec_search("k", np.ones(4, np.float32), 1)
            assert calls == []
        finally:
            client.close()
            broker.stop()

    def test_plane_sheds_expired_rider_at_claim(self):
        """A rider that expires between post and claim is answered
        with an explicit DeadlineExceeded by the plane — the worker
        maps it; it never occupies a device dispatch."""
        from nornicdb_tpu.search.broker import BrokerRemoteError

        calls = []
        gate = threading.Event()

        def dispatch(key, queries, k):
            calls.append(len(queries))
            gate.wait(timeout=5.0)
            return [[("id", 1.0)]] * len(queries)

        broker, client = self._broker(dispatch)
        try:
            # rider A occupies the key's busy gate
            errs = []

            def first():
                try:
                    client.vec_search("k", np.ones(4, np.float32), 1)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            t = threading.Thread(target=first)
            t.start()
            for _ in range(200):
                if calls:
                    break
                time.sleep(0.005)
            assert calls
            # rider B posts with a 150ms budget; the busy gate holds it
            # POSTED past expiry, then a timer releases the gate so the
            # next round claims B — the plane must shed it at claim
            # with an explicit DeadlineExceeded, never dispatch it
            releaser = threading.Timer(0.4, gate.set)
            releaser.start()
            with adm.deadline_scope(time.time() + 0.15):
                with pytest.raises(BrokerRemoteError) as ei:
                    client.vec_search("k", np.ones(4, np.float32), 1,
                                      timeout_s=3.0)
            assert ei.value.type_name == "DeadlineExceeded"
            t.join(timeout=5.0)
            assert not errs, errs
            # the expired rider never widened a device dispatch
            assert all(c == 1 for c in calls), calls
        finally:
            gate.set()
            client.close()
            broker.stop()


# ---------------------------------------------------------------------------
# acceptance: deadline visible at ingress, ring crossing, dispatch
# ---------------------------------------------------------------------------


def _span_index(doc, out=None):
    out = {} if out is None else out
    out.setdefault(doc["name"], []).append(doc.get("attrs", {}))
    for c in doc.get("children", ()):
        _span_index(c, out)
    return out


class TestDeadlinePropagation:
    def test_single_process_trace_carries_budget(self, shed_serving):
        q = shed_serving["q"]
        sr = q.SearchPoints(collection_name="shed",
                            vector=[0.3, 0.8] + [0.1] * 6, limit=3)
        shed_serving["call"]("/qdrant.Points/Search", sr,
                             q.SearchResponse, timeout=2.0)
        roots = [t for t in obs.TRACES.snapshot(limit=50)
                 if t["attrs"].get("method") == "/qdrant.Points/Search"
                 and "deadline_ms" in t["attrs"]]
        assert roots, "no traced Search carried a deadline"
        # the client sent a 2s gRPC deadline: the minted budget honors
        # it (not the 12s surface default). Neighbor tests leave
        # default-budget Search roots in the shared ring, so assert on
        # ANY root carrying the client's 2s budget.
        assert any(0 < t["attrs"]["deadline_ms"] <= 2100
                   for t in roots), [
            t["attrs"]["deadline_ms"] for t in roots]

    def test_two_worker_wire_plane_end_to_end(self, tmp_path):
        import grpc

        import nornicdb_tpu
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.wire_plane import WirePlane

        db = nornicdb_tpu.open(auto_embed=False)
        plane = None
        try:
            rng = np.random.default_rng(7)
            pvecs = rng.normal(size=(16, 8)).astype(np.float32)
            db.qdrant_compat.create_collection(
                "dl", {"size": 8, "distance": "Cosine"})
            db.qdrant_compat.upsert_points("dl", [
                {"id": i, "vector": [float(x) for x in pvecs[i]],
                 "payload": {"i": i}} for i in range(16)])
            plane = WirePlane(db, workers=2, mode="thread").start()
            ch = grpc.insecure_channel(plane.grpc_address)
            stub = ch.unary_unary(
                "/qdrant.Points/Search",
                request_serializer=lambda r: r.SerializeToString(),
                response_deserializer=q.SearchResponse.FromString)
            resp = stub(q.SearchPoints(
                collection_name="dl",
                vector=[float(x) for x in pvecs[5]], limit=3),
                timeout=3.0)
            assert int(resp.result[0].id.num) == 5
            ch.close()
            roots = [t for t in obs.TRACES.snapshot(limit=50)
                     if t["attrs"].get("method")
                     == "/qdrant.Points/Search"
                     and "deadline_ms" in t["attrs"]]
            assert roots, "no ingress root carried the budget"
            chained = None
            for t in roots:
                idx = _span_index(t)
                if "ring.claim" in idx and "device.dispatch" in idx:
                    chained = idx
                    break
            assert chained is not None, [
                list(_span_index(t)) for t in roots]
            # budget at the ring crossing and at the dispatch decision
            claim = chained["ring.claim"][0]
            disp = chained["device.dispatch"][0]
            assert claim.get("deadline_ms", 0) > 0
            assert disp.get("deadline_ms", 0) > 0
            assert disp["deadline_ms"] <= claim["deadline_ms"] + 1.0
            assert claim.get("lane") == "interactive"
        finally:
            if plane is not None:
                plane.stop()
            db.close()


# ---------------------------------------------------------------------------
# background rebuild cannot convoy interactive traffic
# ---------------------------------------------------------------------------


class TestBackgroundLanes:
    def test_rebuild_mid_load_keeps_interactive_p99(self):
        """Satellite pin: a CAGRA background rebuild kicked mid-load
        does not move interactive p99 past the PR 3 overhead budget
        (2x + 1ms, with the base floored at 2ms — sub-ms baselines on
        a contended CI box are dominated by scheduler jitter, not by
        the convoy this test guards against). A convoy is there at
        every rebuild and a hiccup of the box is not: up to three
        rebuilds are measured, each a real one on a fresh index, and
        the best is held to the budget.

        What ``during`` holds is less than its name says (ROADMAP D10):
        with ``build_inline`` at its default the first search finds no
        graph and builds inline, that is, waits on the build lock until
        the background build is over (one sample of seconds, which a
        p99 of 200 does not see), and the other 199 are served by the
        new graph."""
        from nornicdb_tpu.search.cagra import CagraIndex

        rng = np.random.default_rng(11)
        vecs = rng.standard_normal((4000, 32)).astype(np.float32)
        qs = vecs[rng.integers(0, len(vecs), 64)]

        def measure():
            idx = CagraIndex(min_n=100_000)  # brute serves; rebuild manual
            idx.add_batch([(f"v{i}", vecs[i]) for i in range(len(vecs))])
            mb = MicroBatcher(idx.search_batch, surface="t-adm-bg")

            def p99(n=200):
                lat = []
                for i in range(n):
                    t0 = time.perf_counter()
                    mb.search(qs[i % len(qs)], 5)
                    lat.append(time.perf_counter() - t0)
                return float(np.percentile(np.asarray(lat), 99))

            mb.search(qs[0], 5)  # warm the compile cache
            base = p99()
            # kick a REAL background build (the background-lane thread)
            idx.min_n = 256
            idx._kick_background_rebuild()
            during = p99()
            with idx._rebuild_flag_lock:
                rebuilding = idx._rebuilding
            budget = 2.0 * max(base, 0.002) + 0.001
            return during <= budget, (base, during, budget, rebuilding)

        seen = []
        for _ in range(3):
            ok, reading = measure()
            seen.append(reading)
            if ok:
                break
        assert ok, seen

    def test_background_writers_ride_the_background_lane(self):
        """The rebuild threads' coalescer rides carry the background
        lane: observed directly via the lane contextvar inside the
        rebuild thread."""
        from nornicdb_tpu.search.cagra import CagraIndex

        seen = {}
        rng = np.random.default_rng(3)
        vecs = rng.standard_normal((400, 8)).astype(np.float32)
        idx = CagraIndex(min_n=256)
        idx.add_batch([(f"v{i}", vecs[i]) for i in range(len(vecs))])
        orig_build = idx.build

        def spy_build():
            seen["lane"] = adm.lane()
            return orig_build()

        idx.build = spy_build
        idx._kick_background_rebuild()
        for _ in range(400):
            with idx._rebuild_flag_lock:
                if not idx._rebuilding:
                    break
            time.sleep(0.01)
        assert seen.get("lane") == adm.LANE_BACKGROUND

    def test_upsert_convoy_rides_background_lane(self):
        import nornicdb_tpu

        db = nornicdb_tpu.open(auto_embed=False)
        try:
            compat = db.qdrant_compat
            compat.create_collection("lanes", {"size": 4,
                                               "distance": "Cosine"})
            seen = {}
            orig = compat._upsert_coalescer.submit

            def spy(value):
                seen["lane"] = adm.lane()
                return orig(value)

            compat._upsert_coalescer.submit = spy
            compat.upsert_points_coalesced(
                "lanes", [{"id": 1, "vector": [0.1] * 4}])
            assert seen["lane"] == adm.LANE_BACKGROUND
        finally:
            db.close()


# ---------------------------------------------------------------------------
# /admin/scheduler + telemetry + flight dump
# ---------------------------------------------------------------------------


class TestSchedulerSurface:
    def test_summary_schema(self):
        with adm.request_scope("http", time.time() + 1.0):
            doc = adm.scheduler_summary()
        assert doc["posture"] in ("admit", "degrade", "shed",
                                  "shed_hard")
        assert set(doc["lanes"]) == {"interactive", "replay",
                                     "background"}
        for lane_doc in doc["lanes"].values():
            assert {"inflight", "drain_qps", "wait_ms",
                    "weight"} <= set(lane_doc)
        assert "defaults_ms" in doc["deadline"]
        assert "misses" in doc["deadline"]
        assert "total" in doc["shed"] and "by" in doc["shed"]
        assert doc["limits"]["max_wait_ms"] > 0

    def test_admin_endpoints_serve_scheduler(self, shed_serving):
        port = shed_serving["http"].port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/admin/scheduler",
                timeout=5) as resp:
            doc = json.loads(resp.read())
        assert doc["posture"] in ("admit", "degrade", "shed",
                                  "shed_hard")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/admin/telemetry",
                timeout=5) as resp:
            tel = json.loads(resp.read())
        assert tel["scheduler"]["posture"] == doc["posture"]
        assert set(tel["scheduler"]["lanes"]) == set(doc["lanes"])

    def test_flight_dump_carries_scheduler_block(self, tmp_path):
        from nornicdb_tpu.obs.slo import SloEngine

        eng = SloEngine(dump_dir=str(tmp_path / "fl"),
                        dump_interval_s=300.0)
        path = eng.dump(reason="manual")
        lines = [json.loads(ln) for ln in open(path, encoding="utf-8")]
        sched = [ln for ln in lines if ln["kind"] == "scheduler"]
        assert len(sched) == 1
        assert sched[0]["summary"]["posture"] in (
            "admit", "degrade", "shed", "shed_hard")

    def test_shedding_observed_wait_control_loop(self):
        """Unit: sustained measured waits past the bound flip the
        posture to shed and interactive arrivals get ShedError; the
        wait decays and the posture heals."""
        adm.CONTROLLER.reset()
        now = time.time()
        for _ in range(50):
            adm.CONTROLLER.note_wait(adm.LANE_INTERACTIVE, 0.5, now=now)
        posture = adm.CONTROLLER.refresh(now=now, force=True)
        assert posture in ("shed", "shed_hard")
        with pytest.raises(adm.ShedError) as ei:
            adm.CONTROLLER.check("t-surface", adm.LANE_INTERACTIVE,
                                 now=now)
        assert ei.value.retry_after_s >= 1.0
        # posture transition journaled
        evs = obs_events.event_snapshot(limit=50, kind="posture")
        assert evs and evs[-1]["reason"] in ("shed", "shed_hard")
        # ...and heals once the wait has decayed (halves per second)
        later = now + 30.0
        healed = adm.CONTROLLER.refresh(now=later, force=True)
        assert healed == "admit"
        adm.CONTROLLER.check("t-surface", adm.LANE_INTERACTIVE,
                             now=later)
