"""Device-fused hybrid search (ISSUE 4): BM25 CSR scoring on device,
single-program BM25+vector+RRF fusion, shard_map parity, freshness
ladder (alive refresh + delta side-scan + background rebuild), service
wiring through the hybrid MicroBatcher, and the incremental-df /
weighted-RRF satellites.

The acceptance gate is the hybrid parity corpus: the fused device
pipeline must be RANK-IDENTICAL to the host reference
(BM25Index.search_batch -> BruteForceIndex.search_batch -> rrf_fuse)
on a single device and on 2/4-shard CPU meshes, across multi-term
queries, tombstones, empty lexical/vector sides and k > corpus.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from nornicdb_tpu.search.bm25 import BM25Index, tokenize
from nornicdb_tpu.search.device_bm25 import DeviceBM25
from nornicdb_tpu.search.hybrid_fused import FusedHybrid
from nornicdb_tpu.search.microbatch import pow2_bucket
from nornicdb_tpu.search.rrf import rrf_fuse
from nornicdb_tpu.search.vector_index import BruteForceIndex

VOCAB = [f"term{i}" for i in range(64)]
D = 32


def _corpus(n=400, seed=7, text_only=12, vec_only=12):
    rng = np.random.default_rng(seed)
    bm25 = BM25Index()
    brute = BruteForceIndex()
    for i in range(n):
        words = rng.choice(VOCAB, size=int(rng.integers(3, 12)))
        bm25.index(f"d{i}", " ".join(words))
        brute.add(f"d{i}", rng.standard_normal(D).astype(np.float32))
    for i in range(text_only):
        bm25.index(f"t{i}", f"term1 term2 textonly{i % 3}")
    for i in range(vec_only):
        brute.add(f"v{i}", rng.standard_normal(D).astype(np.float32))
    return bm25, brute, rng


# the >= 20-case parity corpus: multi-term, repeated-term, single-term,
# rare/common mixes, no-match (empty lexical) and stopword-only queries
PARITY_QUERIES = [
    "term1 term2 term3",
    "term4 term9 term11 term12",
    "term7 term8",
    "term0 term63",
    "term5 term5 term5 term6",      # repeated terms
    "term13 term14 term15 term16 term17",
    "term20",
    "term21 term22",
    "term23 term24 term25",
    "term30 term31 term32 term33",
    "term40 term41",
    "term42 term43 term44",
    "term50 term51 term52",
    "term60 term61 term62",
    "term2 textonly0",
    "term1 textonly1 term3",
    "zzz qqq nothing",              # empty lexical side
    "the and of is",                # stopword-only -> no tokens
    "term6 missingword",
    "term18 term19 term26 term27 term28 term29",
    "term34 term35",
    "term36 term37 term38 term39",
]


def _host_reference(bm25, brute, queries, embs, overfetch, weights=()):
    lex = bm25.search_batch(queries, overfetch)
    vec = brute.search_batch(embs, overfetch)
    out = []
    for li, vi in zip(lex, vec):
        if li and vi:
            fused = rrf_fuse([li, vi], weights=weights, limit=overfetch)
        elif li:
            fused = li[:overfetch]
        else:
            fused = vi[:overfetch]
        out.append((li, vi, fused))
    return out


def _fused_rows(fh, queries, embs, overfetch, weights=(1.0, 1.0)):
    kq = pow2_bucket(overfetch)
    extras = [{"tokens": tokenize(q), "n_cand": overfetch,
               "w": tuple(weights)} for q in queries]
    return fh.search_batch(np.asarray(embs, np.float32), kq, extras)


def _assert_parity(fh, bm25, brute, queries, embs, overfetch,
                   weights=(1.0, 1.0)):
    rows = _fused_rows(fh, queries, embs, overfetch, weights)
    ref = _host_reference(bm25, brute, queries, embs, overfetch,
                          weights=list(weights))
    for qi, (row, (li, vi, fused)) in enumerate(zip(rows, ref)):
        assert row is not None, f"query {qi} fell back unexpectedly"
        assert [x[0] for x in row["lex"]] == [x[0] for x in li], qi
        assert [x[0] for x in row["vec"]] == [x[0] for x in vi], qi
        if li and vi:
            assert [x[0] for x in row["fused"]] == \
                [x[0] for x in fused], qi
            # fused scores are float32-bitwise identical to host rrf
            assert [x[1] for x in row["fused"]] == \
                [x[1] for x in fused], qi


# ---------------------------------------------------------------------------
# satellite: incremental live df + search_batch on the host index
# ---------------------------------------------------------------------------


class TestBM25Incremental:
    def _df_recount(self, idx, term):
        p = idx._postings.get(term)
        if p is None:
            return 0
        return sum(1 for i in p.doc_ids if idx._alive[i])

    def test_df_tracks_add_remove_update(self):
        idx = BM25Index()
        idx.index("a", "apple banana")
        idx.index("b", "apple cherry")
        assert idx._df["apple"] == 2
        idx.remove("a")
        assert idx._df["apple"] == 1
        assert "banana" not in idx._df
        idx.index("b", "banana only now")  # update drops apple
        assert "apple" not in idx._df
        assert idx._df["banana"] == 1
        for t in ("banana", "only", "now"):
            assert idx._df.get(t, 0) == self._df_recount(idx, t)

    def test_df_survives_compaction(self):
        idx = BM25Index()
        for i in range(1200):
            idx.index(f"d{i}", f"common word{i % 7}")
        for i in range(0, 1200, 2):
            idx.remove(f"d{i}")
        # force the compaction path (hot re-index triggers it)
        idx.index("fresh", "common freshterm")
        for t in list(idx._df):
            assert idx._df[t] == self._df_recount(idx, t), t

    def test_df_rebuilt_from_dict(self):
        idx = BM25Index()
        idx.index("a", "apple banana")
        idx.index("b", "apple")
        idx.remove("a")
        restored = BM25Index.from_dict(idx.to_dict())
        assert restored._df.get("apple", 0) == 1
        assert "banana" not in restored._df
        # tombstone removal still maintains counters post-restore
        restored.remove("b")
        assert "apple" not in restored._df

    def test_search_batch_matches_search(self):
        bm25, _, _ = _corpus(150)
        queries = PARITY_QUERIES[:8]
        batch = bm25.search_batch(queries, 12)
        single = [bm25.search(q, 12) for q in queries]
        assert batch == single

    def test_seed_doc_ids_uses_live_df(self):
        idx = BM25Index()
        for i in range(40):
            idx.index(f"d{i}", f"shared word{i % 5} filler{i}")
        seeds = idx.seed_doc_ids(max_seeds=16)
        assert seeds and all(s in idx for s in seeds)
        # removing every doc holding a term drops it from seed ranking
        for i in range(40):
            idx.remove(f"d{i}")
        assert idx.seed_doc_ids() == []

    def test_changed_since_and_compaction_floor(self):
        idx = BM25Index()
        idx.index("a", "one")
        gen = idx.mut_gen
        idx.index("b", "two")
        idx.index("a", "one updated")
        assert set(idx.changed_since(gen)) == {"a", "b"}
        assert idx.changed_since(idx.mut_gen) == []
        # compaction invalidates every older marker
        for i in range(1200):
            idx.index(f"d{i}", "bulk")
        for i in range(1100):
            idx.remove(f"d{i}")
        idx.index("trigger", "compact me")
        assert idx.changed_since(gen) is None

    def test_score_docs_matches_search_scores(self):
        bm25, _, _ = _corpus(120)
        q = "term1 term2 term3"
        full = dict(bm25.search(q, 120))
        some = list(full)[:10]
        scored = bm25.score_docs(tokenize(q), some)
        for eid in some:
            assert scored[eid] == pytest.approx(full[eid], rel=1e-6)


# ---------------------------------------------------------------------------
# satellite: weighted + deterministic RRF
# ---------------------------------------------------------------------------


class TestRRFDeterminism:
    def test_weights_shift_ranking(self):
        a = [("x", 1.0), ("y", 0.9)]
        b = [("y", 1.0), ("x", 0.9)]
        lex_heavy = rrf_fuse([a, b], weights=[10.0, 1.0], limit=2)
        vec_heavy = rrf_fuse([a, b], weights=[1.0, 10.0], limit=2)
        assert lex_heavy[0][0] == "x"
        assert vec_heavy[0][0] == "y"

    def test_tie_break_source_rank_then_id(self):
        # A only in source 0 at rank 1; B only in source 1 at rank 1:
        # equal fused scores — source order wins
        s0 = [("top0", 1.0), ("A", 0.5)]
        s1 = [("top1", 1.0), ("B", 0.5)]
        fused = rrf_fuse([s0, s1], limit=4)
        names = [x[0] for x in fused]
        assert names.index("A") < names.index("B")
        # equal score, same source impossible; same (source, rank)
        # impossible -> ordering is total and repeatable
        assert fused == rrf_fuse([s0, s1], limit=4)

    def test_absent_entries_contribute_nothing(self):
        fused = rrf_fuse([[("a", 1.0)], []], limit=3)
        assert [x[0] for x in fused] == ["a"]


# ---------------------------------------------------------------------------
# device BM25: host parity + freshness
# ---------------------------------------------------------------------------


class TestDeviceBM25:
    def test_parity_with_host(self):
        bm25, _, _ = _corpus(300)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        host = bm25.search_batch(PARITY_QUERIES, 15)
        devr = dev.search_batch(PARITY_QUERIES, 15)
        for h, d in zip(host, devr):
            assert [x[0] for x in h] == [x[0] for x in d]

    def test_tombstones_live_filtered_with_df_corrected(self):
        bm25, _, _ = _corpus(300)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        for i in range(0, 120, 2):
            bm25.remove(f"d{i}")
        host = bm25.search_batch(PARITY_QUERIES[:8], 15)
        devr = dev.search_batch(PARITY_QUERIES[:8], 15)
        for h, d in zip(host, devr):
            assert [x[0] for x in h] == [x[0] for x in d]
            # df correction: scores match too (idf from live counters)
            for (he, hs), (de, ds) in zip(h, d):
                assert hs == pytest.approx(ds, rel=1e-5)

    def test_read_your_writes_delta(self):
        bm25, _, _ = _corpus(300)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        bm25.index("fresh", "term1 term2 uniquefresh")
        bm25.index("d0", "term1 updated content")  # update = new slot
        host = bm25.search_batch(["term1 uniquefresh", "term1 term2"], 20)
        devr = dev.search_batch(["term1 uniquefresh", "term1 term2"], 20)
        for h, d in zip(host, devr):
            assert [x[0] for x in h] == [x[0] for x in d]
        assert any(e == "fresh" for e, _ in devr[0])

    def test_below_min_n_serves_host(self):
        bm25 = BM25Index()
        for i in range(10):
            bm25.index(f"d{i}", "tiny corpus term1")
        dev = DeviceBM25(bm25, min_n=64)
        assert not dev.build()
        assert dev.search_batch(["term1"], 5) == \
            bm25.search_batch(["term1"], 5)

    def test_k_larger_than_corpus(self):
        bm25, _, _ = _corpus(60, text_only=0, vec_only=0)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        host = bm25.search_batch(["term1 term2"], 500)
        devr = dev.search_batch(["term1 term2"], 500)
        assert [x[0] for x in host[0]] == [x[0] for x in devr[0]]


# ---------------------------------------------------------------------------
# the fused pipeline: parity corpus (acceptance)
# ---------------------------------------------------------------------------


class TestHybridParityCorpus:
    def test_single_device_parity(self):
        bm25, brute, rng = _corpus()
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        embs = rng.standard_normal(
            (len(PARITY_QUERIES), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, PARITY_QUERIES, embs, 30)

    def test_parity_with_weights(self):
        bm25, brute, rng = _corpus(seed=11)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        qs = PARITY_QUERIES[:10]
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 30, weights=(2.0, 0.5))
        _assert_parity(fh, bm25, brute, qs, embs, 30, weights=(0.3, 3.0))

    def test_parity_after_tombstones(self):
        bm25, brute, rng = _corpus(seed=13)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        for i in range(0, 150, 3):
            bm25.remove(f"d{i}")
            brute.remove(f"d{i}")
        embs = rng.standard_normal(
            (len(PARITY_QUERIES), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, PARITY_QUERIES, embs, 30)

    def test_parity_k_exceeds_corpus(self):
        bm25, brute, rng = _corpus(80, seed=17, text_only=4, vec_only=4)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        qs = PARITY_QUERIES[:6]
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 500)

    def test_parity_small_k(self):
        bm25, brute, rng = _corpus(seed=19)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        qs = PARITY_QUERIES[:8]
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 4)

    def test_empty_vector_index_falls_back(self):
        bm25, _, rng = _corpus(100, text_only=0, vec_only=0)
        empty = BruteForceIndex()
        fh = FusedHybrid(bm25, empty, min_n=1)
        assert fh.build()
        rows = _fused_rows(fh, ["term1 term2"],
                           rng.standard_normal((1, D)), 10)
        assert rows == [None]  # host path must serve


class TestShardedParity:
    """Acceptance: the mesh shard_map pipeline is bit-identical to the
    single-device reference merge and rank-identical to the host
    reference, on the virtual 2/4-shard CPU meshes."""

    def setup_method(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device CPU mesh")

    def _run(self, shards):
        bm25, brute, rng = _corpus(600, seed=23)
        fh = FusedHybrid(bm25, brute, n_shards=shards, min_n=1)
        assert fh.build()
        assert "mesh" in fh.lex._snap  # placed on the mesh at build
        qs = PARITY_QUERIES
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 30)

    def test_two_shards(self):
        self._run(2)

    def test_four_shards(self):
        self._run(4)

    def test_mesh_bit_identical_to_reference(self):
        import jax.numpy as jnp

        from nornicdb_tpu.ops.similarity import l2_normalize
        from nornicdb_tpu.search.hybrid_fused import (
            _fused_sharded_impl,
            _holder,
        )

        bm25, brute, rng = _corpus(600, seed=29)
        fh = FusedHybrid(bm25, brute, n_shards=2, min_n=1)
        assert fh.build()
        snap = fh.lex._snap
        qs = PARITY_QUERIES[:4]
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        # no writer in this test: the arrays may outlive the lease
        with brute.device_lease() as lease:
            view = lease.view
        m, valid = view[0], view[1]
        l2v = fh._ensure_map(snap, view[3])
        fh.lex.refresh_alive(snap)
        toks = [tokenize(q) for q in qs]
        b = len(qs)
        kq = 32
        ptr, urow, sel, avgdl = fh.lex.plan(snap, toks, b)
        args = (jnp.asarray(ptr), jnp.asarray(urow), jnp.asarray(sel),
                snap["post_doc"], snap["post_tf"], snap["doc_len"],
                snap["alive"], l2v, jnp.float32(avgdl),
                l2_normalize(jnp.asarray(embs)))
        tail = (jnp.asarray(np.full(b, 30, np.int32)),
                jnp.asarray(np.ones(b, np.float32)),
                jnp.asarray(np.ones(b, np.float32)))
        mp, vp = fh._vec_arrays(m, valid, snap)
        mesh_out = _fused_sharded_impl(
            *args, mp, vp, *tail, kq=kq, rrf_k=60,
            mesh_holder=_holder(snap["mesh"]))
        loop_out = fh._shard_loop(snap, args, m, valid, tail, kq)
        for a_arr, b_arr in zip(mesh_out, loop_out):
            a_np, b_np = np.asarray(a_arr), np.asarray(b_arr)
            if a_np.dtype.kind == "f":
                np.testing.assert_array_equal(
                    a_np.view(np.int32), b_np.view(np.int32))
            else:
                np.testing.assert_array_equal(a_np, b_np)


# ---------------------------------------------------------------------------
# freshness: read-your-writes + rebuild ladder
# ---------------------------------------------------------------------------


class TestHybridFreshness:
    def test_read_your_writes_upsert_visible(self):
        bm25, brute, rng = _corpus(seed=31)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        builds_before = fh.lex.builds
        bm25.index("fresh", "term1 term2 veryfreshterm")
        brute.add("fresh", rng.standard_normal(D).astype(np.float32))
        qs = ["term1 veryfreshterm"]
        embs = rng.standard_normal((1, D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 20)
        rows = _fused_rows(fh, qs, embs, 20)
        assert any(e == "fresh" for e, _ in rows[0]["lex"])
        assert fh.lex.builds == builds_before  # no rebuild needed

    def test_update_replaces_old_slot(self):
        bm25, brute, rng = _corpus(seed=37)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        bm25.index("d1", "term50 term51 replacedcontent")
        qs = ["term50 replacedcontent", "term1 term2 term3"]
        embs = rng.standard_normal((2, D)).astype(np.float32)
        rows = _fused_rows(fh, qs, embs, 25)
        for row in rows:
            ids = [e for e, _ in row["lex"]]
            assert len(ids) == len(set(ids)), "duplicate id served"
        _assert_parity(fh, bm25, brute, qs, embs, 25)

    def test_churn_kicks_background_rebuild(self):
        bm25, brute, rng = _corpus(200, seed=41, text_only=0, vec_only=0)
        fh = FusedHybrid(bm25, brute, min_n=1, rebuild_stale_frac=0.05)
        assert fh.build()
        for i in range(60):
            bm25.index(f"churn{i}", f"term1 churnword{i % 5}")
        embs = rng.standard_normal((1, D)).astype(np.float32)
        _fused_rows(fh, ["term1"], embs, 10)
        # the rebuild runs on a daemon thread; wait for it to land
        import time as _t

        deadline = _t.time() + 10
        while fh.lex.builds < 2 and _t.time() < deadline:
            _t.sleep(0.02)
        assert fh.lex.builds >= 2
        _assert_parity(fh, bm25, brute, ["term1 churnword0"], embs, 10)

    def test_midrequest_bm25_compaction_detected_by_slot_guard(self):
        """A compaction that lands AFTER a request's changelog check
        must not let snapshot-era slot ids read the remapped alive
        array (resurrected tombstones): alive_slots pins the read to
        the snapshot's compaction generation under one lock hold."""
        from nornicdb_tpu.search.device_bm25 import SnapshotStale

        bm25, _, _ = _corpus(200, seed=71, text_only=0, vec_only=0)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        snap = dev._snap
        # simulate the mid-request compaction: the snapshot's slot
        # space is stale the instant the counter moves
        bm25.remove("d0")  # force a refresh (gen moved)
        with bm25._lock:
            bm25.compactions += 1
        with pytest.raises(SnapshotStale):
            dev.refresh_alive(snap)
        # the public path degrades to host-exact, never wrong
        host = bm25.search_batch(["term1 term2"], 10)
        assert dev.search_batch(["term1 term2"], 10) == host

    def test_slots_of_pins_brute_generation(self):
        brute = BruteForceIndex()
        brute.add("a", np.ones(4, np.float32))
        gen = brute.mutations
        assert brute.slots_of(["a"], expect_mutations=gen) == [0]
        brute.add("b", np.ones(4, np.float32))
        # stale expectation -> None, the fused path's mis-join guard
        assert brute.slots_of(["a"], expect_mutations=gen) is None

    def test_plan_overflow_falls_back_to_host(self):
        from nornicdb_tpu.search.device_bm25 import PlanOverflow

        bm25, _, _ = _corpus(120, seed=73, text_only=0, vec_only=0)
        dev = DeviceBM25(bm25, min_n=1)
        assert dev.build()
        snap = dev._snap
        orig_c = snap["c_local"]
        # a c_local so large that any planned batch would wrap int32
        snap["c_local"] = 2**31 - 1
        try:
            with pytest.raises(PlanOverflow):
                dev.plan(snap, [("term1",)], 1)
            host = bm25.search_batch(["term1 term2"], 10)
            assert dev.search_batch(["term1 term2"], 10) == host
        finally:
            snap["c_local"] = orig_c

    def test_brute_compaction_never_misjoins(self):
        bm25, brute, rng = _corpus(seed=43, text_only=0, vec_only=0)
        fh = FusedHybrid(bm25, brute, min_n=1)
        assert fh.build()
        # force a brute compaction (slot remap) without touching bm25
        for i in range(150, 400):
            brute.remove(f"d{i}")
        brute.compact()
        qs = PARITY_QUERIES[:6]
        embs = rng.standard_normal((len(qs), D)).astype(np.float32)
        _assert_parity(fh, bm25, brute, qs, embs, 20)


# ---------------------------------------------------------------------------
# service wiring + observability
# ---------------------------------------------------------------------------


def _make_service(store, rng, n=180):
    from nornicdb_tpu.search.service import SearchService
    from nornicdb_tpu.storage.types import Node

    svc = SearchService(storage=store)
    for i in range(n):
        text = " ".join(rng.choice(VOCAB, size=int(rng.integers(3, 10))))
        node = Node(id=f"n{i}", labels=["Doc"],
                    properties={"content": text},
                    embedding=list(
                        rng.standard_normal(D).astype(np.float32)))
        store.create_node(node)
        svc.index_node(node)
    return svc


class TestServiceWiring:
    def test_fused_path_matches_host_path(self, monkeypatch):
        from nornicdb_tpu.storage import MemoryEngine

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        rng = np.random.default_rng(47)
        store = MemoryEngine()
        svc = _make_service(store, rng)
        qv = rng.standard_normal(D).astype(np.float32)
        fused_res = svc.search("term1 term2 term3", limit=10,
                               query_embedding=qv)
        assert svc._fused is not None and svc._fused.ready
        monkeypatch.setenv("NORNICDB_HYBRID_FUSED", "0")
        svc2 = _make_service(store, np.random.default_rng(47),
                             n=0)
        for node in store.all_nodes():
            svc2.index_node(node)
        host_res = svc2.search("term1 term2 term3", limit=10,
                               query_embedding=qv)
        assert [r["id"] for r in fused_res] == \
            [r["id"] for r in host_res]
        assert [r["score"] for r in fused_res] == \
            [r["score"] for r in host_res]

    def test_weights_parity_and_cache_key(self, monkeypatch):
        from nornicdb_tpu.storage import MemoryEngine

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        rng = np.random.default_rng(53)
        store = MemoryEngine()
        svc = _make_service(store, rng)
        qv = rng.standard_normal(D).astype(np.float32)
        r1 = svc.search("term1 term2", limit=8, query_embedding=qv,
                        weights=(4.0, 0.25))
        r2 = svc.search("term1 term2", limit=8, query_embedding=qv)
        assert [x["id"] for x in r1] != [x["id"] for x in r2] or \
            [x["score"] for x in r1] != [x["score"] for x in r2]

    def test_strategy_counter_and_small_corpus_stays_host(
            self, monkeypatch):
        from nornicdb_tpu.obs import REGISTRY
        from nornicdb_tpu.storage import MemoryEngine

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        rng = np.random.default_rng(59)
        store = MemoryEngine()
        svc = _make_service(store, rng, n=20)  # below the floor
        qv = rng.standard_normal(D).astype(np.float32)
        svc.search("term1", limit=5, query_embedding=qv)
        assert svc._fused is None  # corpus too small
        svc2 = _make_service(MemoryEngine(), rng, n=120)
        before = _counter_value(
            REGISTRY, "nornicdb_search_strategy_total",
            {"strategy": "hybrid_fused"})
        svc2.search("term1 term2", limit=5, query_embedding=qv)
        after = _counter_value(
            REGISTRY, "nornicdb_search_strategy_total",
            {"strategy": "hybrid_fused"})
        assert after == before + 1

    def test_sharded_service_parity(self, monkeypatch):
        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device CPU mesh")
        from nornicdb_tpu.storage import MemoryEngine

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        monkeypatch.setenv("NORNICDB_HYBRID_SHARDS", "2")
        rng = np.random.default_rng(61)
        store = MemoryEngine()
        svc = _make_service(store, rng, n=300)
        qv = rng.standard_normal(D).astype(np.float32)
        res = svc.search("term1 term2 term3", limit=10,
                         query_embedding=qv)
        assert svc._fused is not None
        assert svc._fused.lex._snap["shards"] == 2
        monkeypatch.setenv("NORNICDB_HYBRID_FUSED", "0")
        svc2 = _make_service(store, rng, n=0)
        for node in store.all_nodes():
            svc2.index_node(node)
        host = svc2.search("term1 term2 term3", limit=10,
                           query_embedding=qv)
        assert [r["id"] for r in res] == [r["id"] for r in host]

    def test_hybrid_spans_recorded(self, monkeypatch):
        from nornicdb_tpu.obs import tracing
        from nornicdb_tpu.storage import MemoryEngine

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        rng = np.random.default_rng(67)
        svc = _make_service(MemoryEngine(), rng)
        qv = rng.standard_normal(D).astype(np.float32)
        with tracing.trace("hybrid.test") as root:
            svc.search("term1 term2 term3", limit=5,
                       query_embedding=qv)
        names = root.span_names()
        assert "lexical.score" in names
        assert "fuse" in names
        assert "rerank" in names


def _counter_value(registry, name, labels):
    text = registry.render()
    label_str = ",".join(f'{k}="{v}"' for k, v in labels.items())
    needle = f"{name}{{{label_str}}} "
    for line in text.splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return 0.0


class TestGrpcHybridObservability:
    """Satellite: one gRPC Hybrid call shows the lexical.score -> fuse
    -> rerank ladder in /admin/traces and bumps the hybrid_fused
    strategy counter in /metrics."""

    def test_grpc_hybrid_trace_and_metrics(self, monkeypatch):
        import json as _json
        import urllib.request

        import grpc

        import nornicdb_tpu
        from nornicdb_tpu.api.grpc_server import GrpcServer
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.api.proto import nornic_pb2 as pb
        from nornicdb_tpu.storage.types import Node

        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        rng = np.random.default_rng(71)
        db = nornicdb_tpu.open(auto_embed=False)
        try:
            svc = db.search
            for i in range(120):
                text = " ".join(
                    rng.choice(VOCAB, size=int(rng.integers(3, 10))))
                node = Node(id=f"g{i}", labels=["Doc"],
                            properties={"content": text},
                            embedding=list(rng.standard_normal(D)
                                           .astype(np.float32)))
                db.storage.create_node(node)
                svc.index_node(node)
            grpc_srv = GrpcServer(db, port=0).start()
            http = HttpServer(db, port=0).start()
            try:
                ch = grpc.insecure_channel(grpc_srv.address)
                req = pb.HybridRequest(
                    query="term1 term2 term3",
                    vector=[float(x) for x in
                            rng.standard_normal(D)],
                    limit=5)
                resp = ch.unary_unary(
                    "/nornic.v1.SearchService/Hybrid",
                    request_serializer=lambda r: r.SerializeToString(),
                    response_deserializer=pb.SearchResponse.FromString,
                )(req)
                assert len(resp.hits) == 5
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{http.port}/admin/traces",
                        timeout=5) as r:
                    doc = _json.loads(r.read())
                hybrid = [
                    t for t in doc["traces"]
                    if t["attrs"].get("method")
                    == "/nornic.v1.SearchService/Hybrid"]
                assert hybrid, "Hybrid RPC produced no trace"

                def names(t):
                    out = [t["name"]]
                    for c in t["children"]:
                        out.extend(names(c))
                    return out

                flat = names(hybrid[0])
                assert "lexical.score" in flat
                assert "fuse" in flat
                assert "rerank" in flat
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{http.port}/metrics",
                        timeout=5) as r:
                    metrics_text = r.read().decode()
                assert ('nornicdb_search_strategy_total'
                        '{strategy="hybrid_fused"}') in metrics_text
                ch.close()
            finally:
                grpc_srv.stop()
                http.stop()
        finally:
            db.close()
