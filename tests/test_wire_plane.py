"""Multi-worker wire plane (ISSUE 11): broker correctness under
concurrency, cross-worker coalescing, tier/degrade truth across the
process boundary, zero-copy response assembly, serialization offload,
and the two-worker scrape contract."""

from __future__ import annotations

import asyncio
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs.metrics import REGISTRY, Registry, render_merged
from nornicdb_tpu.search.broker import (
    BrokerClient,
    BrokerRemoteError,
    BrokerTimeout,
    DispatchBroker,
)


def _mk_db(n=40):
    import os

    os.environ.setdefault("NORNICDB_TPU_EMBEDDER", "hash")
    db = nornicdb_tpu.open(auto_embed=False)
    emb = db._embedder
    for i in range(n):
        db.store(f"person{i} topic{i % 7}", node_id=f"p{i}",
                 labels=["Person"],
                 properties={"name": f"person{i}", "idx": i},
                 embedding=emb.embed(f"person{i} topic{i % 7}"))
    db.flush()
    return db


def _grpc_call(address, method, request, response_cls):
    import grpc

    ch = grpc.insecure_channel(address)
    try:
        return ch.unary_unary(
            method,
            request_serializer=lambda r: r.SerializeToString(),
            response_deserializer=response_cls.FromString)(request)
    finally:
        ch.close()


def _setup_collection(db, address, name="wires", n=40, step=2):
    from nornicdb_tpu.api.proto import qdrant_pb2 as q

    emb = db._embedder
    req = q.CreateCollection(collection_name=name)
    req.vectors_config.params.size = emb.dims
    req.vectors_config.params.distance = q.Cosine
    _grpc_call(address, "/qdrant.Collections/Create", req,
               q.CollectionOperationResponse)
    up = q.UpsertPoints(collection_name=name)
    for i in range(0, n, step):
        node = db.storage.get_node(f"p{i}")
        p = up.points.add()
        p.id.num = i
        p.vectors.vector.data.extend(node.embedding)
    _grpc_call(address, "/qdrant.Points/Upsert", up,
               q.PointsOperationResponse)


# ---------------------------------------------------------------------------
# zero-copy codec
# ---------------------------------------------------------------------------


class TestWireCodec:
    """The hand-encoded SearchResponse must parse identically to the
    protobuf-built message for every payload shape the compat layer
    produces."""

    def _reference(self, pts, time_s):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.qdrant_official_grpc import (
            py_to_point_id,
            py_to_value,
        )

        ref = q.SearchResponse(time=time_s)
        for d in pts:
            sp = q.ScoredPoint(id=py_to_point_id(d["id"]),
                               score=float(d.get("score", 0.0)),
                               version=0)
            for k, v in (d.get("payload") or {}).items():
                sp.payload[k].CopyFrom(py_to_value(v))
            if d.get("vector") is not None:
                sp.vectors.vector.data.extend(
                    float(x) for x in d["vector"])
            ref.result.append(sp)
        return ref

    def test_parity_across_payload_shapes(self):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.wire_codec import encode_search_response

        pts = [
            {"id": 4, "score": 0.5,
             "payload": {"name": "x", "idx": 3, "f": 1.5, "b": True,
                         "none": None, "neg": -7,
                         "lst": [1, "a", {"z": -2.5}],
                         "nested": {"a": {"b": [False, 0]}}},
             "vector": [0.1, -0.25, 3.5]},
            {"id": "uuid-ish", "score": 0.0, "payload": {},
             "vector": None},
            {"id": "12abc", "score": -1.25,
             "payload": {"empty_list": [], "empty_map": {}},
             "vector": []},
        ]
        raw = encode_search_response(pts, 0.0123)
        assert q.SearchResponse.FromString(raw) == \
            self._reference(pts, 0.0123)

    def test_time_splice_is_last_wins(self):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.wire_codec import (
            append_time,
            encode_search_response,
        )

        prefix = encode_search_response(
            [{"id": 1, "score": 1.0, "payload": {}}], 99.0)
        # appending a fresh time overrides the frozen one (scalar
        # fields are last-wins on the wire — the wire-cache trick)
        msg = q.SearchResponse.FromString(append_time(prefix, 0.5))
        assert msg.time == 0.5

    def test_empty_response(self):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.wire_codec import encode_search_response

        msg = q.SearchResponse.FromString(encode_search_response([], 0.0))
        assert list(msg.result) == [] and msg.time == 0.0


# ---------------------------------------------------------------------------
# broker protocol
# ---------------------------------------------------------------------------


class _Ranker:
    """Deterministic stand-in for a batched device dispatch."""

    def __init__(self):
        self.calls = []
        self.batch_sizes = []

    def __call__(self, key, queries, k):
        self.calls.append((key, queries.shape, k))
        self.batch_sizes.append(queries.shape[0])
        out = []
        for row in queries:
            order = np.argsort(-row)[:k]
            out.append([(f"d{j}", float(row[j])) for j in order])
        return out


class _CallTarget:
    def __init__(self):
        self.seen = []
        self.inner = self

    def echo(self, *args, **kwargs):
        self.seen.append((args, kwargs))
        return {"args": list(args), "kwargs": kwargs}

    def boom(self):
        from nornicdb_tpu.api.qdrant import QdrantError

        raise QdrantError("no such thing", status=404)

    def big(self, n):
        return "x" * n

    def degrading(self):
        _audit.record_degrade("vector", "vector_int8",
                              "vector_brute_f32", "rerank_race",
                              index="test:idx")
        return "ok"


@pytest.fixture()
def ring():
    ranker = _Ranker()
    target = _CallTarget()
    broker = DispatchBroker(
        ranker, {"t": target}, n_workers=4, slots=8,
        slot_bytes=16 * 1024).start()
    clients = [BrokerClient({**broker.client_spec(w, cross_process=False),
                             "timeout_s": 10.0}) for w in range(4)]
    yield broker, clients, ranker, target
    for c in clients:
        c.close()
    broker.stop()


class TestBroker:
    def test_vec_search_rank_identical_to_direct(self, ring):
        broker, clients, ranker, _ = ring
        vec = np.arange(16, dtype=np.float32)
        doc = clients[0].vec_search("k1", vec, 5)
        direct = _Ranker()("k1", vec[None, :], 8)[0][:5]
        assert doc["hits"] == direct
        assert doc["batch"] >= 1 and doc["t1"] >= doc["t0"] > 0

    def test_concurrent_riders_coalesce_and_stay_rank_identical(
            self, ring):
        """2-4 workers racing coalesced dispatches: every rider's
        answer must equal single-worker serving, and at least one
        dispatch must have carried multiple riders."""
        broker, clients, ranker, _ = ring
        rng = np.random.default_rng(7)
        vecs = rng.standard_normal((24, 16)).astype(np.float32)
        results = [None] * len(vecs)
        errors = []

        def one(i):
            try:
                results[i] = clients[i % 4].vec_search(
                    "g", vecs[i], 6)["hits"]
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(vecs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        ref = _Ranker()
        for i, vec in enumerate(vecs):
            assert results[i] == ref("g", vec[None, :], 8)[0][:6], i
        assert max(ranker.batch_sizes) >= 2, \
            "no cross-worker coalescing observed"

    def test_generic_call_roundtrip_and_dotted_resolution(self, ring):
        _, clients, _, target = ring
        doc = clients[1].call("t", "echo", 1, "two", flag=True)
        assert doc["result"] == {"args": [1, "two"],
                                 "kwargs": {"flag": True}}
        # dotted method paths resolve through attributes
        doc = clients[1].call("t", "inner.echo", 3)
        assert doc["result"]["args"] == [3]

    def test_remote_exception_maps_type_and_status(self, ring):
        _, clients, _, _ = ring
        with pytest.raises(BrokerRemoteError) as ei:
            clients[2].call("t", "boom")
        assert ei.value.type_name == "QdrantError"
        assert ei.value.status == 404
        from nornicdb_tpu.api.qdrant import QdrantError
        from nornicdb_tpu.api.wire_plane import _map_remote

        mapped = _map_remote(ei.value)
        assert isinstance(mapped, QdrantError) and mapped.status == 404

    def test_oversized_response_spills_and_roundtrips(self, ring):
        _, clients, _, _ = ring
        big = clients[3].call("t", "big", 64 * 1024)["result"]
        assert big == "x" * (64 * 1024)

    def test_degrade_records_ride_the_response(self, ring):
        _, clients, _, _ = ring
        doc = clients[0].call("t", "degrading")
        degs = doc["meta"]["degrades"]
        assert len(degs) == 1
        assert degs[0]["reason"] == "rerank_race"
        assert degs[0]["from_tier"] == "vector_int8"

    def test_poisoned_rider_fails_alone(self, ring):
        """One malformed vector (wrong dims) must not fail its
        batch-mates — the broker replays riders singly (MicroBatcher
        poison discipline)."""
        broker, clients, ranker, _ = ring
        good_res = {}
        bad_err = []
        barrier = threading.Barrier(3)

        def good(i):
            barrier.wait()
            good_res[i] = clients[i].vec_search(
                "p", np.arange(16, dtype=np.float32), 4)["hits"]

        def bad():
            barrier.wait()
            try:
                clients[2].vec_search(
                    "p", np.arange(8, dtype=np.float32), 4)
            except Exception as exc:  # noqa: BLE001
                bad_err.append(exc)

        ts = [threading.Thread(target=good, args=(i,)) for i in (0, 1)]
        ts.append(threading.Thread(target=bad))
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        ref = _Ranker()("p", np.arange(16, dtype=np.float32)[None, :],
                        4)[0][:4]
        # good riders answered correctly whether or not they shared a
        # round with the poisoned one (dims mismatch only breaks a
        # MIXED stack; a solo round serves the 8-dim query fine)
        assert good_res[0] == ref and good_res[1] == ref

    def test_rider_timeout_never_hangs(self):
        """Broker crash mid-flight: the rider times out promptly with
        BrokerTimeout — never a hang — and the client survives."""
        ranker = _Ranker()
        broker = DispatchBroker(ranker, {}, n_workers=1, slots=4,
                                slot_bytes=8 * 1024)
        client = BrokerClient({**broker.client_spec(
            0, cross_process=False), "timeout_s": 0.6})
        # broker never started: the slot stays POSTED forever
        t0 = time.time()
        with pytest.raises(BrokerTimeout):
            client.vec_search("x", np.arange(4, dtype=np.float32), 2)
        assert time.time() - t0 < 5.0
        # the timed-out slot is tombstoned, but the worker still has
        # free slots and stays operational
        assert len(client._tombstoned) == 1
        with pytest.raises(BrokerTimeout):
            client.call("t", "echo")
        client.close()
        broker.stop()

    def test_queue_depth_counts_posted(self, ring):
        broker, clients, _, _ = ring
        assert broker.queue_depth() == 0

    def test_burst_beyond_max_batch_all_served_no_slot_leak(self):
        """Review regression: riders past max_batch in one scan must
        stay POSTED for the next round — claiming-then-truncating
        orphaned their slots (rider timeout + permanent tombstone)."""
        ranker = _Ranker()
        broker = DispatchBroker(ranker, {}, n_workers=2, slots=16,
                                slot_bytes=16 * 1024,
                                max_batch=4).start()
        clients = [BrokerClient({**broker.client_spec(
            w, cross_process=False), "timeout_s": 15.0})
            for w in range(2)]
        try:
            results = {}
            errors = []
            barrier = threading.Barrier(20)

            def one(i):
                try:
                    barrier.wait()
                    results[i] = clients[i % 2].vec_search(
                        "burst", np.arange(16, dtype=np.float32) + i,
                        3)["hits"]
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(20)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors
            assert len(results) == 20
            ref = _Ranker()
            for i in range(20):
                vec = np.arange(16, dtype=np.float32) + i
                assert results[i] == ref("b", vec[None, :], 4)[0][:3]
            # no group ever exceeded the cap, and no slot leaked
            assert max(ranker.batch_sizes) <= 4
            for c in clients:
                assert not c._tombstoned
        finally:
            for c in clients:
                c.close()
            broker.stop()


# ---------------------------------------------------------------------------
# cross-process metrics merge + resource dedupe
# ---------------------------------------------------------------------------


class TestMetricsMerge:
    def test_counters_and_histograms_sum_gauges_remote_wins(self):
        from nornicdb_tpu.obs.metrics import dump_state

        local = Registry()
        local.counter("nornicdb_x_total", "x", labels=("a",)) \
            .labels("one").inc(2)
        local.gauge("nornicdb_g", "g").set(5.0)
        local.histogram("nornicdb_h_seconds", "h",
                        buckets=(1, 2)).observe(0.5)

        remote = Registry()
        remote.counter("nornicdb_x_total", "x", labels=("a",)) \
            .labels("one").inc(3)
        remote.counter("nornicdb_x_total", "x", labels=("a",)) \
            .labels("two").inc(7)
        remote.gauge("nornicdb_g", "g").set(11.0)
        remote.histogram("nornicdb_h_seconds", "h",
                         buckets=(1, 2)).observe(0.5)
        remote.gauge("nornicdb_remote_only", "r").set(1.0)

        text = render_merged([dump_state(remote)], registry=local)
        assert 'nornicdb_x_total{a="one"} 5' in text
        assert 'nornicdb_x_total{a="two"} 7' in text
        assert "nornicdb_g 11" in text          # shared plane wins
        assert "nornicdb_remote_only 1" in text
        assert "nornicdb_h_seconds_count 2" in text
        # exactly once: one TYPE line per family
        assert text.count("# TYPE nornicdb_x_total") == 1
        assert text.count("# TYPE nornicdb_h_seconds") == 1

    def test_register_same_object_is_noop_replacement_still_works(self):
        from nornicdb_tpu.obs import resources

        class Q:
            def queue_depth(self):
                return 3

        q1 = Q()
        resources.register("queue", "dedupe-test", q1)
        ref1 = resources._objects[("queue", "dedupe-test")]
        resources.register("queue", "dedupe-test", q1)  # same obj: noop
        assert resources._objects[("queue", "dedupe-test")] is ref1
        q2 = Q()
        resources.register("queue", "dedupe-test", q2)  # replace
        assert resources._objects[("queue", "dedupe-test")]() is q2
        resources.unregister("queue", "dedupe-test")


# ---------------------------------------------------------------------------
# serialization offload (satellite)
# ---------------------------------------------------------------------------


class TestSerializeOffload:
    def test_large_response_serializes_off_the_loop(self, monkeypatch):
        """The regression the satellite pins: while a ~10MB response
        serializes, the grpc.aio event loop must keep turning — the
        flatten runs on the serializer pool even when no compute
        executor was configured."""
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api import qdrant_official_grpc as og

        big = q.ScrollResponse()
        for i in range(3000):
            rp = big.result.add()
            rp.id.num = i
            rp.vectors.vector.data.extend([0.5] * 256)
            rp.payload["text"].string_value = "y" * 700
        assert big.ByteSize() > 5 * 1024 * 1024
        t0 = time.perf_counter()
        big.SerializeToString()
        inline_s = time.perf_counter() - t0

        monkeypatch.setenv("NORNICDB_WIRE_SERIALIZE_OFFLOAD_BYTES",
                           "1024")
        handler = og.aio_unary_raw(lambda data: big,
                                   method="/test/Big", executor=None)

        async def run():
            gaps = []
            stop = [False]

            async def heartbeat():
                loop = asyncio.get_running_loop()
                prev = loop.time()
                while not stop[0]:
                    await asyncio.sleep(0.0005)
                    now = loop.time()
                    gaps.append(now - prev)
                    prev = now

            hb = asyncio.ensure_future(heartbeat())
            out = await handler.unary_unary(b"req", None)
            stop[0] = True
            await hb
            return out, max(gaps)

        out, max_gap = asyncio.new_event_loop().run_until_complete(run())
        assert out == big.SerializeToString()
        # the loop must never have been blocked for anything close to
        # the serialize cost; the satellite's contract is ~1ms, with
        # slack for a loaded CI box
        assert max_gap < max(0.020, inline_s * 0.5), \
            (max_gap, inline_s)

    def test_small_responses_keep_inline_path(self):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api import qdrant_official_grpc as og

        small = q.CountResponse(result=q.CountResult(count=3), time=0.1)
        handler = og.aio_unary_raw(lambda data: small,
                                   method="/test/Small", executor=None)

        async def run():
            return await handler.unary_unary(b"req", None)

        out = asyncio.new_event_loop().run_until_complete(run())
        assert q.CountResponse.FromString(out).result.count == 3


# ---------------------------------------------------------------------------
# wire plane e2e (thread mode: fast, in-process)
# ---------------------------------------------------------------------------


@pytest.fixture()
def thread_plane():
    from nornicdb_tpu.api.wire_plane import WirePlane

    db = _mk_db()
    plane = WirePlane(db, workers=2, mode="thread").start()
    _setup_collection(db, plane.grpc_address)
    yield db, plane
    plane.stop()
    db.close()


class TestWirePlaneThread:
    def test_qdrant_hot_shape_rides_op_vec(self, thread_plane):
        """ISSUE 12 satellite: the qdrant Search hot shape (cosine, no
        filter, no vector echo) posts its raw embedding onto the ring
        (OP_VEC) instead of a pickled OP_CALL — and a filtered search
        still rides the full-fidelity OP_CALL path."""
        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.obs.metrics import REGISTRY

        db, plane = thread_plane

        def vec_rides():
            fam = REGISTRY.get("nornicdb_broker_requests_total")
            kids = {k: c.value for k, c in fam._children.items()} \
                if fam else {}
            return kids.get(("vec",), 0)

        target = db.storage.get_node("p6")
        before = vec_rides()
        sr = q.SearchPoints(collection_name="wires",
                            vector=list(target.embedding), limit=5)
        resp = _grpc_call(plane.grpc_address, "/qdrant.Points/Search",
                          sr, q.SearchResponse)
        assert vec_rides() == before + 1  # hot shape rode the ring
        # answer parity vs the full-fidelity path (tie-aware exact)
        direct = db.qdrant_compat.search_points(
            "wires", list(target.embedding), limit=5)
        assert _audit.ShadowAuditor.parity_of(
            [(int(p.id.num), float(p.score)) for p in resp.result],
            [(int(d["id"]), float(d["score"])) for d in direct],
            k=5, exact=True) == 1.0
        # a filtered search is NOT the hot shape: OP_CALL serves it
        before = vec_rides()
        fr = q.SearchPoints(collection_name="wires",
                            vector=list(target.embedding), limit=5)
        cond = fr.filter.must.add()
        cond.has_id.has_id.add().num = 6
        resp2 = _grpc_call(plane.grpc_address, "/qdrant.Points/Search",
                           fr, q.SearchResponse)
        assert vec_rides() == before
        assert [int(p.id.num) for p in resp2.result] == [6]

    def test_search_rank_identical_to_direct_compat(self, thread_plane):
        from nornicdb_tpu.api.proto import qdrant_pb2 as q

        db, plane = thread_plane
        target = db.storage.get_node("p4")
        sr = q.SearchPoints(collection_name="wires",
                            vector=list(target.embedding), limit=5)
        resp = _grpc_call(plane.grpc_address, "/qdrant.Points/Search",
                          sr, q.SearchResponse)
        got = [(int(p.id.num), round(p.score, 5)) for p in resp.result]
        direct = db.qdrant_compat.search_points(
            "wires", list(target.embedding), limit=5)
        want = [(int(d["id"]), round(d["score"], 5)) for d in direct]
        assert got == want

    def test_racing_searches_rank_identical(self, thread_plane):
        """Concurrent Search RPCs across both workers: every answer
        equals the single-process reference."""
        import grpc

        from nornicdb_tpu.api.proto import qdrant_pb2 as q

        db, plane = thread_plane
        queries = [db.storage.get_node(f"p{i}").embedding
                   for i in range(0, 24, 2)]
        want = [
            [(int(d["id"]), float(d["score"]))
             for d in db.qdrant_compat.search_points(
                 "wires", list(v), limit=4)]
            for v in queries
        ]
        results = [None] * len(queries)
        errors = []

        def one(i):
            ch = grpc.insecure_channel(plane.grpc_address)
            try:
                stub = ch.unary_unary(
                    "/qdrant.Points/Search",
                    request_serializer=lambda r: r.SerializeToString(),
                    response_deserializer=q.SearchResponse.FromString)
                resp = stub(q.SearchPoints(
                    collection_name="wires", vector=list(queries[i]),
                    limit=4))
                results[i] = [(int(p.id.num), float(p.score))
                              for p in resp.result]
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
            finally:
                ch.close()

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        # tie-aware exact parity (the ISSUE 11 contract): a coalesced
        # padded-batch dispatch (and the ISSUE 12 OP_VEC fast path)
        # may permute ids WITHIN an exact score tie vs the b=1
        # search_points reference — same scores, same membership at
        # every score level is the exact-tier contract
        for got, ref in zip(results, want):
            assert got is not None
            assert _audit.ShadowAuditor.parity_of(
                got, ref, k=4, exact=True) == 1.0, (got, ref)

    def test_served_tier_attribution_crosses_the_boundary(
            self, thread_plane):
        from nornicdb_tpu.api.proto import nornic_pb2 as pb

        db, plane = thread_plane
        before = _audit.tier_counts()
        target = db.storage.get_node("p4")
        resp = _grpc_call(plane.grpc_address,
                          "/nornic.v1.SearchService/Search",
                          pb.SearchRequest(vector=list(target.embedding),
                                           limit=3),
                          pb.SearchResponse)
        assert resp.hits and resp.hits[0].node_id == "p4"
        after = _audit.tier_counts()
        gained = {k: after[k] - before.get(k, 0)
                  for k in after if after[k] > before.get(k, 0)}
        assert any(k.startswith("vector:") for k in gained), gained

    def test_wire_gen_mirror_invalidates_worker_caches(
            self, thread_plane):
        db, plane = thread_plane
        client = plane._thread_workers[0].client
        g0 = client.qdrant_gen()
        db.qdrant_compat.upsert_points(
            "wires", [{"id": 999, "vector": list(
                db.storage.get_node("p1").embedding), "payload": {}}])
        assert client.qdrant_gen() > g0

    def test_rest_hot_path_and_scrape_exactly_once(self, thread_plane):
        db, plane = thread_plane
        body = json.dumps({"query": "topic1 person",
                           "limit": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{plane.http_port}/nornicdb/search",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc.get("results")
        # /metrics: the shared-plane series appear EXACTLY ONCE even
        # with two workers booted over the same plane (satellite 2)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{plane.http_port}/metrics",
                timeout=15) as r:
            text = r.read().decode()
        for fam in ("nornicdb_microbatch_batch_size",
                    "nornicdb_index_rows",
                    "nornicdb_compile_cache_entries",
                    "nornicdb_broker_requests_total"):
            assert text.count(f"# TYPE {fam}") == 1, fam
        # and they did not vanish: the plane's index gauges carry rows
        assert "nornicdb_index_rows{" in text
        # readiness merges the plane verdict
        with urllib.request.urlopen(
                f"http://127.0.0.1:{plane.http_port}/readyz",
                timeout=15) as r:
            assert r.status == 200
            ready = json.loads(r.read())
        assert ready["status"] == "ready" and "worker" in ready

    def test_forwarded_route_serves_admin_surface(self, thread_plane):
        db, plane = thread_plane
        with urllib.request.urlopen(
                f"http://127.0.0.1:{plane.http_port}/admin/degrades",
                timeout=15) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert "records" in doc or "recorded" in json.dumps(doc)


class TestTieAwareExactParity:
    """ISSUE 11 hardening surfaced by the wire-plane load run: a
    padded-batch device dispatch may permute rows WITHIN an exact
    score tie relative to the b=1 exact replay. With (id, score)
    pairs the exact contract becomes 'same scores, same membership
    per score level'; ids-only samples keep strict positional
    parity."""

    def test_tie_permutation_is_parity(self):
        p = _audit.AUDITOR.parity_of
        dev = [("a", 1.0), ("c", 0.5), ("b", 0.5), ("d", 0.2)]
        host = [("a", 1.0), ("b", 0.5), ("c", 0.5), ("d", 0.2)]
        assert p(dev, host, 4, exact=True) == 1.0

    def test_tie_group_straddling_k_is_parity(self):
        p = _audit.AUDITOR.parity_of
        # host's 0.5 tie group extends past the cutoff: a device pick
        # from the same group beyond k still counts as parity
        dev = [("a", 1.0), ("x", 0.5)]
        host = [("a", 1.0), ("b", 0.5), ("x", 0.5), ("y", 0.5)]
        assert p(dev, host, 2, exact=True) == 1.0

    def test_tie_group_truncated_by_host_list_is_parity(self):
        p = _audit.AUDITOR.parity_of
        # the host replay's OWN list ends inside the tie group:
        # membership beyond the cutoff is unobservable, score equality
        # carries the contract (the r11 load-run repro shape)
        dev = [("a", 1.0), ("zz", 0.5)]
        host = [("a", 1.0), ("b", 0.5), ("c", 0.5)]
        assert p(dev, host, 2, exact=True) == 1.0
        # but when the host list ends BELOW the tie score, membership
        # was fully observable and a foreign id is a mismatch
        host2 = [("a", 1.0), ("b", 0.5), ("c", 0.2)]
        assert p(dev, host2, 2, exact=True) == 0.5

    def test_wrong_score_or_foreign_id_still_mismatches(self):
        p = _audit.AUDITOR.parity_of
        # host list ends BELOW the tie score, so group membership was
        # fully observable — a foreign id is a real mismatch
        dev = [("a", 1.0), ("z", 0.5)]          # z not in the host set
        host = [("a", 1.0), ("b", 0.5), ("c", 0.5), ("d", 0.2)]
        assert p(dev, host, 2, exact=True) == 0.5
        dev = [("a", 1.0), ("b", 0.4)]          # right id, wrong score
        assert p(dev, host, 2, exact=True) == 1.0  # id match wins
        dev = [("a", 1.0), ("c", 0.4)]          # wrong score, no tie
        assert p(dev, host, 2, exact=True) == 0.5

    def test_ids_only_samples_keep_strict_positional_contract(self):
        p = _audit.AUDITOR.parity_of
        assert p(["a", "b"], ["a", "c"], 2, exact=True) == 0.5
        assert p(["a", "b"], ["a", "b"], 2, exact=True) == 1.0

    def test_statistical_recall_unchanged_with_pairs(self):
        p = _audit.AUDITOR.parity_of
        dev = [("a", 0.9), ("b", 0.8)]
        host = [("b", 1.0), ("c", 0.7)]
        assert p(dev, host, 2, exact=False) == 0.5


class TestDegradeLedgerBoundary:
    def test_degrades_relay_into_worker_ledger(self):
        """A degrade produced on the device plane while serving a
        worker's op must land in the worker's ledger ring (marked
        via broker) — satellite 3's ledger-crossing contract. Uses a
        cross_process-flagged client so the relay path runs."""
        target = _CallTarget()
        broker = DispatchBroker(_Ranker(), {"compat": target},
                                n_workers=1, slots=4,
                                slot_bytes=8 * 1024).start()
        # cross_process flag drives the relay; untrack_shm=False keeps
        # the in-process resource tracker coherent for this simulation
        client = BrokerClient({**broker.client_spec(
            0, cross_process=True), "untrack_shm": False,
            "timeout_s": 10.0})
        try:
            from nornicdb_tpu.api.wire_plane import BrokerCompat

            compat = BrokerCompat(client)
            _audit.LEDGER.clear()
            compat.degrading()
            recs = [r for r in _audit.degrade_snapshot(50)
                    if r.get("via") == "broker"]
            assert recs and recs[0]["reason"] == "rerank_race"
        finally:
            client.close()
            broker.stop()


# ---------------------------------------------------------------------------
# streaming search RPC
# ---------------------------------------------------------------------------


class TestSearchStream:
    def test_stream_matches_unary_in_order(self):
        import grpc

        from nornicdb_tpu.api.grpc_server import GrpcServer
        from nornicdb_tpu.api.proto import nornic_pb2 as pb

        db = _mk_db(n=20)
        srv = GrpcServer(db, port=0).start()
        try:
            vecs = [db.storage.get_node(f"p{i}").embedding
                    for i in range(6)]
            ch = grpc.insecure_channel(srv.address)
            unary = ch.unary_unary(
                "/nornic.v1.SearchService/Search",
                request_serializer=lambda r: r.SerializeToString(),
                response_deserializer=pb.SearchResponse.FromString)
            want = [[h.node_id for h in unary(
                pb.SearchRequest(vector=list(v), limit=3)).hits]
                for v in vecs]
            stream = ch.stream_stream(
                "/nornic.v1.SearchService/SearchStream",
                request_serializer=lambda r: r.SerializeToString(),
                response_deserializer=pb.SearchResponse.FromString)
            got = [[h.node_id for h in resp.hits] for resp in stream(
                iter([pb.SearchRequest(vector=list(v), limit=3)
                      for v in vecs]))]
            assert got == want
            ch.close()
        finally:
            srv.stop()
            db.close()


# ---------------------------------------------------------------------------
# wire plane e2e (process mode: real frontends, shared port)
# ---------------------------------------------------------------------------


class TestWirePlaneProcess:
    def test_process_workers_serve_rank_identical_and_survive_crash(
            self, monkeypatch):
        """2 real worker processes on one SO_REUSEPORT port: racing
        searches stay rank-identical to the direct path; killing one
        worker mid-serving leaves the survivor taking traffic (the
        crash satellite's no-hang contract). The workers start beside a
        device plane that owns the chip: they inherit JAX_PLATFORMS=tpu
        and must come up pinned to the CPU."""
        import grpc

        from nornicdb_tpu.api.proto import qdrant_pb2 as q
        from nornicdb_tpu.api.wire_plane import WirePlane

        import jax  # noqa: F401 — this process reads the variable at import

        db = _mk_db()
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        plane = WirePlane(db, workers=2, mode="process").start()
        try:
            for p in plane._procs:
                with open(f"/proc/{p.pid}/environ", "rb") as f:
                    assert b"JAX_PLATFORMS=cpu" in f.read().split(b"\0")
            _setup_collection(db, plane.grpc_address)
            target = db.storage.get_node("p4")
            want = [int(d["id"]) for d in db.qdrant_compat.search_points(
                "wires", list(target.embedding), limit=5)]

            def search_once(timeout=10):
                ch = grpc.insecure_channel(plane.grpc_address)
                try:
                    stub = ch.unary_unary(
                        "/qdrant.Points/Search",
                        request_serializer=lambda r:
                            r.SerializeToString(),
                        response_deserializer=q.SearchResponse.FromString)
                    resp = stub(q.SearchPoints(
                        collection_name="wires",
                        vector=list(target.embedding), limit=5),
                        timeout=timeout)
                    return [int(p.id.num) for p in resp.result]
                finally:
                    ch.close()

            for _ in range(4):
                assert search_once() == want

            # the merged scrape through the shared HTTP port carries
            # the plane's tier mix exactly once
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{plane.http_port}/metrics",
                    timeout=20) as r:
                text = r.read().decode()
            assert text.count("# TYPE nornicdb_served_tier_total") == 1
            assert 'nornicdb_served_tier_total{surface="vector"' in text

            # crash one worker: the kernel drops its listener from the
            # reuseport group; the survivor keeps serving. Retry a few
            # times to ride out connections caught mid-teardown.
            plane._procs[0].kill()
            plane._procs[0].wait(timeout=10)
            deadline = time.time() + 20
            ok = False
            while time.time() < deadline:
                try:
                    assert search_once(timeout=5) == want
                    ok = True
                    break
                except Exception:  # noqa: BLE001
                    time.sleep(0.3)
            assert ok, "no worker served after a peer crash"
        finally:
            plane.stop()
            db.close()
