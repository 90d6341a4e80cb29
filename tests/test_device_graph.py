"""Device graph plane (ISSUE 9): parity corpus + freshness ladder.

Contract under test: every LDBC fast-path shape served through
query/device_graph.py is ROW-IDENTICAL to the host executor, and every
freshness/degrade rung (mutation mid-batch, catalog invalidation,
env-gate-off, guard trips) lands on the host path — never a wrong
answer. Plus: the device-built strip/gram views are bit-identical to
the host builds, the fused traverse-rank program matches its host
reference, coalesced chain reads share one dispatch, and the shared
PageRank snapshot is bit-identical and actually cached.
"""

import os
import random
import threading

import numpy as np
import pytest

from nornicdb_tpu.query.executor import CypherExecutor
from nornicdb_tpu.storage import MemoryEngine, NamespacedEngine
from nornicdb_tpu.storage.types import Edge, Node


def _sorted_rows(result):
    return sorted([repr(r) for r in result.rows])


@pytest.fixture()
def mode():
    """Restore the device-gate env after each test."""
    prev = {k: os.environ.get(k) for k in (
        "NORNICDB_GRAPH_DEVICE", "NORNICDB_GRAPH_DEVICE_MIN_N",
        "NORNICDB_GRAPH_DEVICE_MIN_B")}

    def set_mode(value, **extra):
        os.environ["NORNICDB_GRAPH_DEVICE"] = value
        for k, v in extra.items():
            os.environ[f"NORNICDB_GRAPH_DEVICE_{k}"] = str(v)

    yield set_mode
    for k, v in prev.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _build_graph(n_people=50, n_msgs=110, knows=4, seed=7):
    eng = NamespacedEngine(MemoryEngine(), "t")
    rng = random.Random(seed)
    cities = ["Oslo", "Bergen", "Pune", "Kyoto"]
    tags = ["ai", "tpu", "graphs", "jax"]
    for c in cities:
        eng.create_node(Node(id=f"c_{c}", labels=["City"],
                             properties={"name": c}))
    for t in tags:
        eng.create_node(Node(id=f"t_{t}", labels=["Tag"],
                             properties={"name": t}))
    for i in range(n_people):
        eng.create_node(Node(
            id=f"p{i}", labels=["Person"],
            properties={"id": i, "name": f"p{i}", "age": 18 + (i * 7) % 50}))
    eid = iter(range(10 ** 9))
    for i in range(n_people):
        eng.create_edge(Edge(id=f"e{next(eid)}", type="IS_LOCATED_IN",
                             start_node=f"p{i}",
                             end_node=f"c_{cities[i % len(cities)]}",
                             properties={}))
        for j in rng.sample(range(n_people), knows):
            if j != i:
                eng.create_edge(Edge(id=f"e{next(eid)}", type="KNOWS",
                                     start_node=f"p{i}", end_node=f"p{j}",
                                     properties={}))
    for m in range(n_msgs):
        props = {"id": 1000 + m, "content": f"message {m}"}
        if m < n_msgs - 3:  # three undated: null-first DESC order rung
            # deliberate key ties (ts repeats every 10 messages): the
            # device merge must reproduce the host's stable tie order
            props["creationDate"] = 1700000000 + (m % 10) * 37
        eng.create_node(Node(id=f"m{m}", labels=["Message"],
                             properties=props))
        eng.create_edge(Edge(id=f"e{next(eid)}", type="HAS_CREATOR",
                             start_node=f"m{m}",
                             end_node=f"p{rng.randrange(n_people)}",
                             properties={}))
        for t in rng.sample(tags, rng.randrange(1, 3)):
            eng.create_edge(Edge(id=f"e{next(eid)}", type="HAS_TAG",
                                 start_node=f"m{m}", end_node=f"t_{t}",
                                 properties={}))
    return eng


def _ex(eng):
    ex = CypherExecutor(eng)
    ex.enable_query_cache = False
    return ex


Q_CHAIN = ("MATCH (p:Person {id: $pid})-[:KNOWS]->(f:Person)"
           "<-[:HAS_CREATOR]-(m:Message) "
           "RETURN f.name, m.content, m.creationDate "
           "ORDER BY m.creationDate DESC ")
Q_STRIP = ("MATCH (c:City)<-[:IS_LOCATED_IN]-(p:Person)-[:KNOWS]->"
           "(f:Person) RETURN c.name, "
           "count(f) / count(DISTINCT p) AS avgFriends")
Q_COOC = ("MATCH (t1:Tag)<-[:HAS_TAG]-(m:Message)-[:HAS_TAG]->(t2:Tag) "
          "WHERE t1 <> t2 RETURN t1.name, t2.name, count(m) AS freq")


class TestChainTopkParity:
    """Row/rank-identical device vs host across the chain family."""

    def test_param_and_limit_sweep(self, mode):
        eng = _build_graph()
        mode("off")
        ex_h = _ex(eng)
        mode("on")
        ex_d = _ex(eng)
        cases = []
        for pid in (0, 3, 17, 29, 49):
            for tail in ("LIMIT 10", "LIMIT 1", "LIMIT 3",
                         "SKIP 2 LIMIT 5", "LIMIT 1000"):
                cases.append((Q_CHAIN + tail, {"pid": pid}))
        cases.append((Q_CHAIN + "LIMIT 10", {"pid": 10 ** 9}))  # no anchor
        for q, params in cases:
            mode("off")
            want = ex_h.execute(q, params)
            mode("on")
            got = ex_d.execute(q, params)
            assert got.columns == want.columns, (q, params)
            assert got.rows == want.rows, (q, params)
        assert ex_d.device_graph.dispatches > 0  # parity isn't vacuous

    def test_empty_frontier_and_dangling_label(self, mode):
        eng = _build_graph()
        # a person with no KNOWS edges at all
        eng.create_node(Node(id="p_lonely", labels=["Person"],
                             properties={"id": 7777, "name": "lonely"}))
        mode("on")
        ex_d = _ex(eng)
        assert ex_d.execute(Q_CHAIN + "LIMIT 5", {"pid": 7777}).rows == []
        # dangling mid label: no Ghost nodes exist anywhere
        q = ("MATCH (p:Person {id: $pid})-[:KNOWS]->(f:Ghost)"
             "<-[:HAS_CREATOR]-(m:Message) RETURN m.content "
             "ORDER BY m.creationDate DESC LIMIT 5")
        mode("off")
        want = _ex(eng).execute(q, {"pid": 0})
        mode("on")
        assert ex_d.execute(q, {"pid": 0}).rows == want.rows == []

    def test_multi_hit_anchor_stays_host(self, mode):
        eng = _build_graph()
        # duplicate anchor key: two persons share id 0
        eng.create_node(Node(id="p_dup", labels=["Person"],
                             properties={"id": 0, "name": "dup"}))
        mode("off")
        want = _ex(eng).execute(Q_CHAIN + "LIMIT 10", {"pid": 0})
        mode("on")
        ex_d = _ex(eng)
        got = ex_d.execute(Q_CHAIN + "LIMIT 10", {"pid": 0})
        assert got.rows == want.rows
        assert ex_d.device_graph.dispatches == 0  # multi-anchor: host

    def test_coalesced_concurrent_reads_share_dispatches(self, mode):
        eng = _build_graph()
        mode("off")
        ex_h = _ex(eng)
        expected = {pid: ex_h.execute(Q_CHAIN + "LIMIT 10",
                                      {"pid": pid}).rows
                    for pid in range(20)}
        mode("on")
        ex_d = _ex(eng)
        ex_d.execute(Q_CHAIN + "LIMIT 10", {"pid": 0})  # warm snapshot
        results = {}
        errors = []

        def worker(pid):
            try:
                results[pid] = ex_d.execute(Q_CHAIN + "LIMIT 10",
                                            {"pid": pid}).rows
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(pid,))
                   for pid in range(20)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for pid in range(20):
            assert results[pid] == expected[pid], pid
        batcher = next(
            (b for k, b in ex_d.device_graph._batchers.items()
             if k[0] == "chainb"), None)
        assert batcher is not None
        assert batcher.batched_items >= 20


class TestChainFreshnessLadder:
    """Every rung serves correct answers; degrades land on host."""

    def test_write_visible_immediately(self, mode):
        eng = _build_graph()
        mode("on")
        ex_d = _ex(eng)
        before = ex_d.execute(Q_CHAIN + "LIMIT 5", {"pid": 0}).rows
        assert before
        # a brand-new newest message from one of p0's friends
        friend = None
        for row in ex_d.execute(
                "MATCH (p:Person {id: 0})-[:KNOWS]->(f:Person) "
                "RETURN f.name", {}).rows:
            friend = row[0]
            break
        assert friend is not None
        ex_d.execute(
            "MATCH (f:Person {name: $n}) "
            "CREATE (m:Message {id: 999999, content: 'fresh', "
            "creationDate: 1900000000})-[:HAS_CREATOR]->(f)",
            {"n": friend})
        after = ex_d.execute(Q_CHAIN + "LIMIT 5", {"pid": 0}).rows
        # nulls order first under DESC; "fresh" carries the highest
        # real date, so it must appear in the head
        assert any(row[1] == "fresh" for row in after)
        mode("off")
        assert _ex(eng).execute(Q_CHAIN + "LIMIT 5",
                                {"pid": 0}).rows == after

    def test_invalidation_and_delete(self, mode):
        eng = _build_graph()
        mode("on")
        ex_d = _ex(eng)
        ex_d.execute(Q_CHAIN + "LIMIT 10", {"pid": 1})
        # update a message property -> wholesale invalidation
        ex_d.execute("MATCH (m:Message {id: 1000}) "
                     "SET m.creationDate = 1950000000", {})
        got = ex_d.execute(Q_CHAIN + "LIMIT 10", {"pid": 1})
        mode("off")
        want = _ex(eng).execute(Q_CHAIN + "LIMIT 10", {"pid": 1})
        assert got.rows == want.rows
        mode("on")
        ex_d.execute("MATCH (m:Message {id: 1000}) DETACH DELETE m", {})
        got2 = ex_d.execute(Q_CHAIN + "LIMIT 10", {"pid": 1})
        mode("off")
        want2 = _ex(eng).execute(Q_CHAIN + "LIMIT 10", {"pid": 1})
        assert got2.rows == want2.rows

    def test_mutation_mid_batch_degrades_to_host(self, mode, monkeypatch):
        """A write landing INSIDE the dispatch window: the post-dispatch
        version check must throw the device result away and serve host."""
        import nornicdb_tpu.query.device_graph as dg

        eng = _build_graph()
        mode("on")
        ex_d = _ex(eng)
        ex_d.execute(Q_CHAIN + "LIMIT 5", {"pid": 2})  # warm snapshot
        real_fn = dg._chain_topk_fn
        fired = {}

        def racing_fn(f, kp):
            impl = real_fn(f, kp)

            def wrapper(*args):
                if "done" not in fired:
                    fired["done"] = True
                    # the race: a create lands while the program runs
                    eng.create_node(Node(id="race_node",
                                         labels=["Person"],
                                         properties={"id": 55555}))
                    ex_d.columnar.apply_node_created(
                        eng.get_node("race_node"))
                return impl(*args)

            return wrapper

        monkeypatch.setattr(dg, "_chain_topk_fn", racing_fn)
        got = ex_d.execute(Q_CHAIN + "LIMIT 5", {"pid": 2})
        monkeypatch.setattr(dg, "_chain_topk_fn", real_fn)
        mode("off")
        want = _ex(eng).execute(Q_CHAIN + "LIMIT 5", {"pid": 2})
        assert got.rows == want.rows
        assert fired.get("done")

    def test_env_gate_off_never_dispatches(self, mode):
        eng = _build_graph()
        mode("off")
        ex = _ex(eng)
        for pid in range(5):
            ex.execute(Q_CHAIN + "LIMIT 10", {"pid": pid})
        assert ex.device_graph.dispatches == 0

    def test_auto_single_stream_stays_host(self, mode):
        """auto mode: a lone reader never pays a b=1 dispatch, even on
        an eligible catalog (the demand gate)."""
        eng = _build_graph()
        mode("auto", MIN_N="1", MIN_B="2")
        ex = _ex(eng)
        for pid in range(5):
            ex.execute(Q_CHAIN + "LIMIT 10", {"pid": pid})
        assert ex.device_graph.dispatches == 0


class TestStripAndGramBuilds:
    """Device-built views bit-identical to the host builds."""

    def _strip_args(self):
        return ("IS_LOCATED_IN", "dst", "Person", "KNOWS", "out",
                "Person")

    def test_strip_arrays_bit_identical(self, mode):
        eng = _build_graph()
        # parallel edges: duplicate (g, p) membership, the DISTINCT rung
        eng.create_edge(Edge(id="dup1", type="IS_LOCATED_IN",
                             start_node="p0", end_node="c_Oslo",
                             properties={}))
        mode("off")
        ex_h = _ex(eng)
        host_sv = ex_h.columnar.strip_view(*self._strip_args())
        mode("on")
        ex_d = _ex(eng)
        dev_sv = ex_d.device_graph.build_strip_view(*self._strip_args())
        assert dev_sv is not None
        assert np.array_equal(host_sv.deg, dev_sv.deg)
        assert np.array_equal(host_sv.sum_deg, dev_sv.sum_deg)
        assert np.array_equal(host_sv.nnz, dev_sv.nnz)
        assert dev_sv.deg.dtype == host_sv.deg.dtype == np.int64

    def test_strip_label_none_variants(self, mode):
        eng = _build_graph()
        for args in (("IS_LOCATED_IN", "dst", None, "KNOWS", "out", None),
                     ("HAS_CREATOR", "dst", "Person", "IS_LOCATED_IN",
                      "out", "City")):
            mode("off")
            host_sv = _ex(eng).columnar.strip_view(*args)
            mode("on")
            ex_d = _ex(eng)
            dev_sv = ex_d.device_graph.build_strip_view(*args)
            assert dev_sv is not None, args
            assert np.array_equal(host_sv.sum_deg, dev_sv.sum_deg), args
            assert np.array_equal(host_sv.nnz, dev_sv.nnz), args

    def test_strip_query_parity_and_maintenance(self, mode):
        eng = _build_graph()
        mode("off")
        want = _ex(eng).execute(Q_STRIP)
        mode("on")
        ex_d = _ex(eng)
        got = ex_d.execute(Q_STRIP)
        assert _sorted_rows(got) == _sorted_rows(want)
        # the installed view must ride the catalog's incremental
        # maintenance exactly like a host-built one
        ex_d.execute(
            "MATCH (a:Person {id: 0}), (b:Person {id: 49}) "
            "CREATE (a)-[:KNOWS]->(b)", {})
        mode("off")
        want2 = _ex(eng).execute(Q_STRIP)
        mode("on")
        got2 = ex_d.execute(Q_STRIP)
        assert _sorted_rows(got2) == _sorted_rows(want2)

    def test_gram_bit_identical_and_query_parity(self, mode):
        eng = _build_graph()
        key = ("HAS_TAG", "mid_src", "Message", "Tag", "Tag")
        mode("off")
        ex_h = _ex(eng)
        host_gram = ex_h.columnar.cooc_gram(*key)
        mode("on")
        ex_d = _ex(eng)
        dev_gram = ex_d.columnar.cooc_gram(
            *key, device_plane=ex_d.device_graph)
        assert host_gram is not None and dev_gram is not None
        assert np.array_equal(host_gram.C, dev_gram.C)
        got = ex_d.execute(Q_COOC)
        mode("off")
        want = _ex(eng).execute(Q_COOC)
        assert _sorted_rows(got) == _sorted_rows(want)

    def test_exactness_guard_degrades(self, mode):
        """Structures past the f32-exactness bound refuse the device
        build (host serves) instead of risking inexact counts."""
        import nornicdb_tpu.query.device_graph as dg

        eng = _build_graph()
        mode("on")
        ex = _ex(eng)
        plane = ex.device_graph
        orig = dg._EXACT_F32
        try:
            dg._EXACT_F32 = 1.0  # force the guard
            assert plane.build_strip_view(*self._strip_args()) is None
        finally:
            dg._EXACT_F32 = orig
        # query still answers correctly through the host build
        mode("off")
        want = _ex(eng).execute(Q_STRIP)
        mode("on")
        assert _sorted_rows(ex.execute(Q_STRIP)) == _sorted_rows(want)


class TestTraverseRank:
    def _setup(self, mode_fn, with_vectors=True):
        from nornicdb_tpu.search.vector_index import BruteForceIndex

        eng = _build_graph(n_people=30, n_msgs=60)
        mode_fn("on")
        ex = _ex(eng)
        cat = ex.columnar
        rng = np.random.default_rng(5)
        index = BruteForceIndex(use_device=True)
        if with_vectors:
            rows = cat.label_rows("Message")
            nodes = cat.nodes()
            ids = [nodes[int(r)].id for r in rows]
            vecs = rng.normal(size=(len(ids), 24)).astype(np.float32)
            index.add_batch(list(zip(ids, vecs)))
        return eng, ex, index, rng

    def test_device_matches_host(self, mode):
        eng, ex, index, rng = self._setup(mode)
        plane = ex.device_graph
        cat = ex.columnar
        hops = [("KNOWS", "out"), ("HAS_CREATOR", "in")]
        anchors = [int(cat.node_row(f"p{i}")) for i in (0, 3, 9, 21)]
        q = rng.normal(size=(len(anchors), 24)).astype(np.float32)
        dev = plane.traverse_rank(anchors, hops, q, 7, index)
        host = plane.traverse_rank_host(anchors, hops, q, 7, index)
        assert dev is not None
        for d, h in zip(dev, host):
            assert [r for r, _s in d] == [r for r, _s in h]
            assert np.allclose([s for _r, s in d], [s for _r, s in h],
                               atol=1e-5)

    def test_one_hop_and_empty_frontier(self, mode):
        eng, ex, index, rng = self._setup(mode)
        plane = ex.device_graph
        cat = ex.columnar
        q = rng.normal(size=(1, 24)).astype(np.float32)
        # 1-hop from a message to its creator: Person has no vector ->
        # frontier exists but nothing rankable
        m_row = int(cat.node_row("m0"))
        dev = plane.traverse_rank([m_row], [("HAS_CREATOR", "out")], q, 5,
                                  index)
        assert dev is not None and dev[0] == []
        # empty frontier: a node with no outgoing KNOWS
        eng.create_node(Node(id="iso", labels=["Person"],
                             properties={"id": 424242}))
        ex.invalidate_caches()
        iso_row = int(ex.columnar.node_row("iso"))
        dev2 = plane.traverse_rank(
            [iso_row], [("KNOWS", "out"), ("HAS_CREATOR", "in")], q, 5,
            index)
        assert dev2 is not None and dev2[0] == []

    def test_index_mutation_resnapshots(self, mode):
        eng, ex, index, rng = self._setup(mode)
        plane = ex.device_graph
        cat = ex.columnar
        hops = [("KNOWS", "out"), ("HAS_CREATOR", "in")]
        a = [int(cat.node_row("p0"))]
        q = rng.normal(size=(1, 24)).astype(np.float32)
        first = plane.traverse_rank(a, hops, q, 5, index)
        assert first is not None
        # overwrite one frontier vector with the query itself: it must
        # win the rank on the NEXT call (mutation-keyed snapshot)
        target_row = None
        host = plane.traverse_rank_host(a, hops, q, 50, index)
        assert host[0]
        target_row = host[0][-1][0]
        target_id = cat.nodes()[target_row].id
        index.add(target_id, q[0])
        dev = plane.traverse_rank(a, hops, q, 5, index)
        host2 = plane.traverse_rank_host(a, hops, q, 5, index)
        assert dev is not None
        assert [r for r, _s in dev[0]] == [r for r, _s in host2[0]]
        assert dev[0][0][0] == target_row

    def test_gate_off_returns_none(self, mode):
        eng, ex, index, rng = self._setup(mode)
        mode("off")
        q = rng.normal(size=(1, 24)).astype(np.float32)
        a = [int(ex.columnar.node_row("p0"))]
        assert ex.device_graph.traverse_rank(
            a, [("KNOWS", "out")], q, 5, index) is None

    def test_db_service_surface(self, mode):
        from nornicdb_tpu.db import DB

        mode("on")
        db = DB()
        rng = np.random.default_rng(1)
        for i in range(8):
            db.store(f"n{i}", labels=["Person"], properties={"pid": i},
                     node_id=f"p{i}",
                     embedding=rng.normal(size=12).tolist())
        for i in range(8):
            db.link(f"p{i}", f"p{(i + 1) % 8}", "KNOWS")
        q = rng.normal(size=12).tolist()
        hits = db.graph_vector_search("p0", ["KNOWS"], q, k=3)
        assert hits and hits[0][0] == "p1"
        mode("off")
        assert db.graph_vector_search("p0", ["KNOWS"], q, k=3) == hits
        mode("on")
        # explicit-embedding store AFTER the search service exists must
        # still be rankable (the embed queue skips embedded nodes; the
        # store path indexes them directly)
        db.store("late", labels=["Person"], properties={"pid": 99},
                 node_id="p_late", embedding=q)
        db.link("p0", "p_late", "KNOWS")
        hits2 = db.graph_vector_search("p0", ["KNOWS"], q, k=3)
        assert hits2[0][0] == "p_late"
        with pytest.raises(ValueError):
            db.graph_vector_search("p0", [], q)
        assert db.graph_vector_search("missing", ["KNOWS"], q) == []


class TestPageRankSnapshot:
    def test_bit_identical_and_cached(self, mode):
        from nornicdb_tpu.ops.graph import pagerank_engine

        eng = _build_graph(n_people=25, n_msgs=30)
        mode("on")
        ex = _ex(eng)
        plane = ex.device_graph
        base = pagerank_engine(eng)
        via_plane = pagerank_engine(eng, plane=plane)
        assert base == via_plane  # bit-identical, same snapshot recipe
        snap1 = plane.pagerank_snapshot()
        snap2 = plane.pagerank_snapshot()
        assert snap1 is snap2  # cached: no per-call rebuild/re-ship
        # a write moves the catalog version -> fresh snapshot
        ex.execute("CREATE (:Person {id: 909090})")
        snap3 = plane.pagerank_snapshot()
        assert snap3 is not snap1
        assert len(snap3["ids"]) == len(snap1["ids"]) + 1

    def test_degree_counts_matches_ops(self, mode):
        from nornicdb_tpu.ops.graph import degree_counts, graph_snapshot

        eng = _build_graph(n_people=20, n_msgs=20)
        mode("on")
        plane = _ex(eng).device_graph
        out_d, in_d = plane.degree_counts()
        src, dst, ids = graph_snapshot(eng)
        ref_o, ref_i = degree_counts(src, dst, len(ids))
        assert np.array_equal(out_d, np.asarray(ref_o))
        assert np.array_equal(in_d, np.asarray(ref_i))


class TestObsWiring:
    def test_cost_and_dispatch_accounting(self, mode):
        from nornicdb_tpu import obs
        from nornicdb_tpu.obs.cost import cost_summary

        eng = _build_graph()
        mode("on")
        ex = _ex(eng)
        for pid in range(3):
            ex.execute(Q_CHAIN + "LIMIT 10", {"pid": pid})
        ex.execute(Q_STRIP)
        ex.execute(Q_COOC)
        kinds = {e["kind"] for e in obs.compile_universe()}
        assert {"graph_chain_topk", "graph_strip_agg",
                "graph_cooc_gram"} <= kinds
        rows = {(r["kind"], r["index"]): r for r in cost_summary()}
        chain = next((r for (k, _i), r in rows.items()
                      if k == "graph_chain_topk"), None)
        assert chain is not None
        assert chain["queries"] >= 3  # REAL query counts, not batches
        assert chain["flops_per_query"] > 0

    def test_resource_stats_and_gap(self, mode):
        eng = _build_graph()
        mode("on")
        ex = _ex(eng)
        ex.execute(Q_CHAIN + "LIMIT 10", {"pid": 0})
        stats = ex.device_graph.resource_stats()
        assert stats["device_bytes"] > 0
        assert stats["rows"] > 0
        assert stats["mutation_gap"] == 0
        ex.execute("CREATE (:Person {id: 777777})")
        assert ex.device_graph.resource_stats()["mutation_gap"] >= 1

    def test_gauges_exported(self, mode):
        from nornicdb_tpu import obs
        from nornicdb_tpu.obs.metrics import REGISTRY
        from nornicdb_tpu.obs.resources import update_gauges

        eng = _build_graph()
        mode("on")
        ex = _ex(eng)
        ex.execute(Q_CHAIN + "LIMIT 10", {"pid": 0})
        update_gauges()
        fam = REGISTRY.get("nornicdb_index_device_bytes")
        assert fam is not None
        keys = [k for k in fam.children() if k[0] == "device_graph"]
        assert keys, "device_graph family missing from resource gauges"

    def test_declared_kinds_present_before_traffic(self):
        from nornicdb_tpu.obs.dispatch import bucket_counts

        counts = bucket_counts()
        for kind in ("graph_chain_topk", "graph_strip_agg",
                     "graph_cooc_gram", "graph_traverse_rank"):
            assert kind in counts
