"""Walk-fused hybrid tier (ISSUE 6): the CAGRA greedy walk as the
vector half of the fused BM25+RRF pipeline.

The contract under test is **walk-parity**: the walk tier is
approximate by construction, so instead of the brute tier's
rank-identity gate its fused top-k must stay within recall@10
tolerance of the host hybrid reference (the sentinel's absolute floor
is 0.95), every freshness gap must degrade DOWN the ladder —
walk-fused -> brute-fused -> host — never to a wrong answer, and the
sharded walk-fused merge must be bit-identical to the single-device
reference loop on the virtual CPU meshes.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax

from nornicdb_tpu.search.bm25 import BM25Index, tokenize
from nornicdb_tpu.search.hybrid_fused import FusedHybrid
from nornicdb_tpu.search.microbatch import pow2_bucket
from nornicdb_tpu.search.rrf import rrf_fuse
from nornicdb_tpu.search.vector_index import BruteForceIndex

VOCAB = [f"term{i}" for i in range(64)]
D = 32
RECALL_FLOOR = 0.95  # the absolute walk-parity floor

QUERIES = [
    "term1 term2 term3",
    "term4 term9 term11 term12",
    "term7 term8",
    "term0 term63",
    "term5 term5 term5 term6",
    "term13 term14 term15 term16 term17",
    "term20",
    "term21 term22",
    "term23 term24 term25",
    "term30 term31 term32 term33",
    "term2 textonly0",
    "zzz qqq nothing",           # empty lexical side
    "term6 missingword",
    "term34 term35",
]


def _corpus(n=500, seed=7, centers=8, text_only=8):
    """Clustered corpus — the regime the graph walk serves (a k-NN
    graph over isotropic noise has no structure to navigate)."""
    rng = np.random.default_rng(seed)
    cent = (rng.standard_normal((centers, D)) * 2.0).astype(np.float32)
    bm25 = BM25Index()
    brute = BruteForceIndex()
    for i in range(n):
        words = rng.choice(VOCAB, size=int(rng.integers(3, 12)))
        bm25.index(f"d{i}", " ".join(words))
        brute.add(f"d{i}", cent[i % centers]
                  + 0.4 * rng.standard_normal(D).astype(np.float32))
    for i in range(text_only):
        bm25.index(f"t{i}", f"term1 term2 textonly{i % 3}")
    return bm25, brute, cent, rng


def _walk_pipeline(bm25, brute, n_shards=1, **kw):
    fh = FusedHybrid(bm25, brute, n_shards=n_shards, min_n=1,
                     walk_min_n=1, **kw)
    assert fh.build()
    fh.cagra.min_n = 1
    assert fh.cagra.build()
    return fh


def _fused_rows(fh, queries, embs, overfetch, weights=(1.0, 1.0)):
    kq = pow2_bucket(overfetch)
    extras = [{"tokens": tokenize(q), "n_cand": overfetch,
               "w": tuple(weights)} for q in queries]
    return fh.search_batch(np.asarray(embs, np.float32), kq, extras)


def _host_top(bm25, brute, query, emb, overfetch, weights=()):
    lex = bm25.search(query, overfetch)
    vec = brute.search_batch(
        np.asarray([emb], np.float32), overfetch)[0]
    if lex and vec:
        return rrf_fuse([lex, vec], weights=list(weights),
                        limit=overfetch)
    return lex or vec


def _recall10(fh, bm25, brute, queries, embs, overfetch,
              weights=(1.0, 1.0), expect_tier="walk"):
    rows = _fused_rows(fh, queries, embs, overfetch, weights)
    total = 0.0
    for qi, row in enumerate(rows):
        assert row is not None, f"query {qi} fell back to host"
        if expect_tier is not None:
            assert row["tier"] == expect_tier, (qi, row["tier"])
        host = _host_top(bm25, brute, queries[qi], embs[qi], overfetch,
                         weights)[:10]
        host_ids = {e for e, _ in host}
        got = {e for e, _ in row["fused"][:10]}
        total += len(host_ids & got) / max(len(host_ids), 1)
    return total / len(queries)


def _embs(cent, rng, nq):
    idx = rng.integers(0, len(cent), nq)
    return (cent[idx]
            + 0.4 * rng.standard_normal((nq, D))).astype(np.float32)


# ---------------------------------------------------------------------------
# walk-parity corpus
# ---------------------------------------------------------------------------


class TestWalkParityCorpus:
    def test_recall_tolerance_single_device(self):
        bm25, brute, cent, rng = _corpus()
        fh = _walk_pipeline(bm25, brute)
        embs = _embs(cent, rng, len(QUERIES))
        assert _recall10(fh, bm25, brute, QUERIES, embs, 30) \
            >= RECALL_FLOOR

    def test_recall_with_weights(self):
        bm25, brute, cent, rng = _corpus(seed=11)
        fh = _walk_pipeline(bm25, brute)
        qs = QUERIES[:8]
        embs = _embs(cent, rng, len(qs))
        for w in ((2.0, 0.5), (0.3, 3.0)):
            assert _recall10(fh, bm25, brute, qs, embs, 30,
                             weights=w) >= RECALL_FLOOR

    def test_tombstones_filtered_and_recall_kept(self):
        bm25, brute, cent, rng = _corpus(seed=13)
        fh = _walk_pipeline(bm25, brute)
        dead = {f"d{i}" for i in range(0, 120, 4)}
        for eid in dead:
            bm25.remove(eid)
            brute.remove(eid)
        qs = QUERIES[:8]
        embs = _embs(cent, rng, len(qs))
        rows = _fused_rows(fh, qs, embs, 30)
        for row in rows:
            assert row is not None
            served = {e for e, _ in row["vec"]} \
                | {e for e, _ in row["fused"]}
            assert not (dead & served), "tombstoned id served"
        assert _recall10(fh, bm25, brute, qs, embs, 30,
                         expect_tier=None) >= RECALL_FLOOR

    def test_k_exceeds_walk_pool_degrades_to_brute(self):
        """overfetch deeper than itopk can't come from the walk pool:
        the batch serves the exact tier, rank-identical to host."""
        bm25, brute, cent, rng = _corpus(120, seed=17, text_only=0)
        fh = _walk_pipeline(bm25, brute)
        qs = QUERIES[:4]
        embs = _embs(cent, rng, len(qs))
        rows = _fused_rows(fh, qs, embs, 500)
        for qi, row in enumerate(rows):
            assert row is not None and row["tier"] == "brute"
            host = _host_top(bm25, brute, qs[qi], embs[qi], 500)
            assert [e for e, _ in row["fused"]] == \
                [e for e, _ in host], qi

    def test_text_only_docs_still_fuse(self):
        """Docs with no vector join as lexical-only candidates (the
        l2g = -1 branch) and can still win the fused ranking."""
        bm25, brute, cent, rng = _corpus(seed=19)
        fh = _walk_pipeline(bm25, brute)
        rows = _fused_rows(fh, ["term1 term2 textonly0"],
                           _embs(cent, rng, 1), 30)
        assert rows[0] is not None and rows[0]["tier"] == "walk"
        lex_ids = {e for e, _ in rows[0]["lex"]}
        assert any(e.startswith("t") for e in lex_ids)
        fused_ids = {e for e, _ in rows[0]["fused"]}
        assert any(e.startswith("t") for e in fused_ids)


# ---------------------------------------------------------------------------
# sharded: mesh bit-identity vs the single-device reference
# ---------------------------------------------------------------------------


class TestWalkShardedParity:
    def setup_method(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device CPU mesh")

    def _run(self, shards):
        bm25, brute, cent, rng = _corpus(600, seed=23)
        fh = _walk_pipeline(bm25, brute, n_shards=shards)
        assert fh.cagra._graph["shards"] == shards
        assert "mesh" in fh.lex._snap and "mesh" in fh.cagra._graph
        qs = QUERIES
        embs = _embs(cent, rng, len(qs))
        assert _recall10(fh, bm25, brute, qs, embs, 30) >= RECALL_FLOOR

    def test_two_shards(self):
        self._run(2)

    def test_four_shards(self):
        self._run(4)

    def test_mesh_bit_identical_to_reference(self):
        import jax.numpy as jnp

        from nornicdb_tpu.ops.similarity import l2_normalize
        from nornicdb_tpu.search.hybrid_fused import (
            _holder,
            _walk_fused_sharded_impl,
        )

        bm25, brute, cent, rng = _corpus(600, seed=29)
        fh = _walk_pipeline(bm25, brute, n_shards=2)
        snap = fh.lex._snap
        g = fh.cagra._graph
        qs = QUERIES[:4]
        embs = _embs(cent, rng, len(qs))
        fh.lex.refresh_alive(snap)
        toks = [tokenize(q) for q in qs]
        b = len(qs)
        ptr, urow, sel, avgdl = fh.lex.plan(snap, toks, b)
        l2g = fh._ensure_walk_map(snap, g)
        lex_base = (jnp.asarray(ptr), jnp.asarray(urow),
                    jnp.asarray(sel), snap["post_doc"],
                    snap["post_tf"], snap["doc_len"], snap["alive"])
        qn = l2_normalize(jnp.asarray(embs))
        tail = (jnp.asarray(np.full(b, 30, np.int32)),
                jnp.asarray(np.ones(b, np.float32)),
                jnp.asarray(np.ones(b, np.float32)))
        wctx = {"g": g, "l2g": l2g, "iters": g["iters"],
                "width": fh.cagra.search_width,
                "itopk": fh.cagra.itopk,
                "hash_bits": fh.cagra.hash_bits,
                "n_seeds": fh.cagra.n_seeds}
        kp = fh.cagra.itopk
        mesh_out = _walk_fused_sharded_impl(
            *lex_base, l2g, jnp.float32(avgdl), qn, g["matrix"],
            g["adj"], g["validf"], *tail, kq=kp, rrf_k=60,
            iters=wctx["iters"], width=wctx["width"],
            itopk=wctx["itopk"], hash_bits=wctx["hash_bits"],
            n_seeds=wctx["n_seeds"], mesh_holder=_holder(snap["mesh"]))
        loop_out = fh._walk_shard_loop(snap, g, lex_base, l2g, avgdl,
                                       qn, tail, kp, wctx)
        for a_arr, b_arr in zip(mesh_out, loop_out):
            a_np, b_np = np.asarray(a_arr), np.asarray(b_arr)
            if a_np.dtype.kind == "f":
                np.testing.assert_array_equal(
                    a_np.view(np.int32), b_np.view(np.int32))
            else:
                np.testing.assert_array_equal(a_np, b_np)


# ---------------------------------------------------------------------------
# freshness ladder: walk -> brute-fused -> host, read-your-writes
# ---------------------------------------------------------------------------


class TestWalkFreshnessLadder:
    def test_read_your_writes_upsert_visible(self):
        bm25, brute, cent, rng = _corpus(seed=31)
        fh = _walk_pipeline(bm25, brute)
        bm25.index("fresh", "term1 term2 veryfreshterm")
        brute.add("fresh", cent[1])
        rows = _fused_rows(fh, ["term1 veryfreshterm"],
                           np.asarray([cent[1]]), 30)
        assert rows[0] is not None and rows[0]["tier"] == "walk"
        assert any(e == "fresh" for e, _ in rows[0]["lex"])
        assert any(e == "fresh" for e, _ in rows[0]["vec"])
        assert any(e == "fresh" for e, _ in rows[0]["fused"])

    def test_updated_vector_rescored_exactly(self):
        """The walk scored the pre-update vector; the delta side-scan
        must replace it with the exact post-update cosine."""
        bm25, brute, cent, rng = _corpus(seed=37)
        fh = _walk_pipeline(bm25, brute)
        brute.add("d1", cent[2])  # update: move d1 onto center 2
        q = cent[2] / np.linalg.norm(cent[2])
        rows = _fused_rows(fh, ["term1 term2"], np.asarray([cent[2]]),
                           30)
        assert rows[0] is not None and rows[0]["tier"] == "walk"
        vec = dict(rows[0]["vec"])
        assert "d1" in vec
        stored = brute.get("d1")
        exact = float(q @ (stored / np.linalg.norm(stored)))
        assert vec["d1"] == pytest.approx(exact, rel=1e-5)

    def test_delete_landing_mid_batch_still_filtered(self):
        """A remove() racing the batch's host-side planning window must
        still be live-filtered from the walk output: ``stale`` reads
        the LIVE mutation counter after ``delta_block`` drains the
        changelog, so a tombstone landing after an earlier counter
        capture can't compare clean and ride the walk to the caller."""
        bm25, brute, cent, rng = _corpus(seed=47)
        fh = _walk_pipeline(bm25, brute)
        emb = brute.get("d5").copy()  # walk top-1 by construction
        orig_plan = fh.lex.plan
        fired = []

        def plan_hook(snap, token_rows, b):
            if not fired:  # delete mid-batch, before the walk gate
                fired.append(True)
                bm25.remove("d5")
                brute.remove("d5")
            return orig_plan(snap, token_rows, b)

        fh.lex.plan = plan_hook
        try:
            rows = _fused_rows(fh, ["term1 term2"],
                               np.asarray([emb]), 30)
        finally:
            del fh.lex.plan
        assert fired and rows[0] is not None
        assert "d5" not in {e for e, _ in rows[0]["vec"]}, \
            rows[0]["tier"]

    def test_changelog_overrun_degrades_to_brute_then_host(self):
        """Vector changelog overrun -> brute-fused (rank-identical);
        lexical changelog overrun on top -> host path (rows None)."""
        bm25, brute, cent, rng = _corpus(seed=41)
        # pin rebuild cadence so the ladder (not a rebuild) serves
        fh = _walk_pipeline(bm25, brute, rebuild_stale_frac=1e9)
        fh.cagra.rebuild_stale_frac = 1e9
        cap = brute.changelog_cap()
        churn = (cent[rng.integers(0, len(cent), cap + 10)]
                 + 0.4 * rng.standard_normal((cap + 10, D))
                 ).astype(np.float32)
        for i in range(cap + 10):
            brute.add(f"x{i}", churn[i])
        q = "term1 term2"
        emb = cent[1]
        rows = _fused_rows(fh, [q], np.asarray([emb]), 30)
        assert rows[0] is not None and rows[0]["tier"] == "brute"
        host = _host_top(bm25, brute, q, emb, 30)
        assert [e for e, _ in rows[0]["fused"]] == \
            [e for e, _ in host]
        # now overrun the lexical changelog too -> host serves
        for i in range(bm25.changelog_cap() + 10):
            bm25.index(f"y{i}", "term5 bulkchurn")
        rows = _fused_rows(fh, [q], np.asarray([emb]), 30)
        assert rows[0] is None

    def test_pending_graph_build_serves_brute(self):
        bm25, brute, cent, rng = _corpus(seed=43)
        fh = FusedHybrid(bm25, brute, min_n=1, walk_min_n=1,
                         build_inline=False)
        assert fh.cagra is not None and not fh.cagra.graph_built
        fh.lex.build()  # lexical snapshot ready; graph still missing
        rows = _fused_rows(fh, ["term1 term2"],
                           np.asarray([cent[1]]), 30)
        # first batch kicked the background build; it must have served
        # the exact tier (or host) — never a walk over a missing graph
        assert rows[0] is None or rows[0]["tier"] == "brute"
        deadline = time.time() + 10
        while not fh.cagra.graph_built and time.time() < deadline:
            time.sleep(0.02)
        assert fh.cagra.graph_built
        rows = _fused_rows(fh, ["term1 term2"],
                           np.asarray([cent[1]]), 30)
        assert rows[0] is not None and rows[0]["tier"] == "walk"

    def test_underfill_redispatches_exact(self):
        """Mass deletes cluster the walk output on tombstones; the
        under-fill veto re-dispatches through the exact tier instead of
        serving short lists."""
        from nornicdb_tpu.obs import REGISTRY

        bm25, brute, cent, rng = _corpus(400, seed=47, text_only=0)
        fh = _walk_pipeline(bm25, brute, rebuild_stale_frac=1e9)
        fh.cagra.rebuild_stale_frac = 1e9
        for i in range(360):
            brute.remove(f"d{i}")  # bm25 keeps them: lex side intact
        q = "term1 term2 term3"
        emb = cent[1]
        before = _counter(REGISTRY, "nornicdb_hybrid_fused_events_total",
                          "walk_underfill_brute")
        rows = _fused_rows(fh, [q], np.asarray([emb]), 30)
        after = _counter(REGISTRY, "nornicdb_hybrid_fused_events_total",
                         "walk_underfill_brute")
        assert rows[0] is not None and rows[0]["tier"] == "brute"
        assert after == before + 1
        host = _host_top(bm25, brute, q, emb, 30)
        assert [e for e, _ in rows[0]["fused"]] == \
            [e for e, _ in host]

    def test_foreign_brute_graph_never_binds(self):
        """A graph wrapping a DIFFERENT brute index (a background
        build that raced an index reload) must be refused at wrap and
        at rebind — its row ids belong to a discarded corpus."""
        from nornicdb_tpu.search.cagra import CagraIndex

        bm25, brute, cent, rng = _corpus(seed=79)
        other = BruteForceIndex()
        other.add("z", np.ones(D, np.float32))
        foreign = CagraIndex(brute=other, min_n=1)
        fh = FusedHybrid(bm25, brute, min_n=1, walk_min_n=1,
                         cagra=foreign)
        assert fh.cagra is not foreign
        assert fh.cagra._brute is brute
        assert fh.rebind_cagra(foreign) is False
        assert fh.cagra is not foreign

    def test_graph_rebuild_rebinds_join_map(self):
        """A background graph rebuild produces a new row space; the
        l2g map (keyed on build_seq) must rebind on the next batch —
        the stale-wrapper lifecycle the PR 2 ANN wrapper already has."""
        bm25, brute, cent, rng = _corpus(seed=53)
        fh = _walk_pipeline(bm25, brute)
        _fused_rows(fh, ["term1 term2"], np.asarray([cent[1]]), 30)
        snap = fh.lex._snap
        tok0, _ = snap["row_maps"]["l2g"]
        assert tok0 == fh.cagra._graph["build_seq"]
        brute.add("newdoc", cent[3])
        bm25.index("newdoc", "term1 newdocterm")
        assert fh.cagra.build()  # the "background rebuild completed"
        rows = _fused_rows(fh, ["term1 newdocterm"],
                           np.asarray([cent[3]]), 30)
        assert rows[0] is not None and rows[0]["tier"] == "walk"
        tok1, _ = snap["row_maps"]["l2g"]
        assert tok1 == fh.cagra._graph["build_seq"] != tok0
        assert any(e == "newdoc" for e, _ in rows[0]["vec"])


def _counter(registry, name, event):
    text = registry.render()
    needle = f'{name}{{event="{event}"}} '
    for line in text.splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return 0.0


def _strategy_count(registry, strategy):
    text = registry.render()
    needle = f'nornicdb_search_strategy_total{{strategy="{strategy}"}} '
    for line in text.splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return 0.0


# ---------------------------------------------------------------------------
# service wiring: the third hybrid tier + lifecycle
# ---------------------------------------------------------------------------


def _make_service(store, rng, cent, n=200):
    from nornicdb_tpu.search.service import SearchService
    from nornicdb_tpu.storage.types import Node

    svc = SearchService(storage=store)
    for i in range(n):
        text = " ".join(rng.choice(VOCAB, size=int(rng.integers(3, 10))))
        node = Node(id=f"n{i}", labels=["Doc"],
                    properties={"content": text},
                    embedding=list(
                        (cent[i % len(cent)] + 0.4
                         * rng.standard_normal(D)).astype(np.float32)))
        store.create_node(node)
        svc.index_node(node)
    return svc


class TestServiceWalkTier:
    def _env(self, monkeypatch, walk_min_n="100"):
        monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
        monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
        monkeypatch.setenv("NORNICDB_HYBRID_WALK_MIN_N", walk_min_n)

    def test_walk_strategy_counter_and_recall(self, monkeypatch):
        from nornicdb_tpu.obs import REGISTRY
        from nornicdb_tpu.storage import MemoryEngine

        self._env(monkeypatch)
        rng = np.random.default_rng(59)
        cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
        store = MemoryEngine()
        svc = _make_service(store, rng, cent)
        qv = (cent[1] + 0.4 * rng.standard_normal(D)).astype(np.float32)
        before = _strategy_count(REGISTRY, "hybrid_walk_fused")
        res = svc.search("term1 term2 term3", limit=10,
                         query_embedding=qv)
        after = _strategy_count(REGISTRY, "hybrid_walk_fused")
        assert after == before + 1
        assert svc._fused is not None and svc._fused.cagra is not None
        monkeypatch.setenv("NORNICDB_HYBRID_FUSED", "0")
        svc2 = _make_service(store, rng, cent, n=0)
        for node in store.all_nodes():
            svc2.index_node(node)
        host = svc2.search("term1 term2 term3", limit=10,
                           query_embedding=qv)
        got = {r["id"] for r in res}
        want = {r["id"] for r in host}
        assert len(got & want) / max(len(want), 1) >= RECALL_FLOOR

    def test_walk_span_with_iters_attrs(self, monkeypatch):
        from nornicdb_tpu.obs import tracing
        from nornicdb_tpu.storage import MemoryEngine

        self._env(monkeypatch)
        rng = np.random.default_rng(61)
        cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
        svc = _make_service(MemoryEngine(), rng, cent)
        qv = (cent[2] + 0.4 * rng.standard_normal(D)).astype(np.float32)
        with tracing.trace("walk.test") as root:
            svc.search("term1 term2 term3", limit=5,
                       query_embedding=qv)
        names = root.span_names()
        assert "vector.walk" in names
        assert "lexical.score" in names and "fuse" in names

        def find(span, name):
            if span.name == name:
                return span
            for c in span.children:
                hit = find(c, name)
                if hit is not None:
                    return hit
            return None

        walk_span = find(root, "vector.walk")
        assert walk_span.attrs.get("iters") >= 1
        assert walk_span.attrs.get("itopk") >= 16

    def test_brute_tier_below_walk_floor(self, monkeypatch):
        """Corpus under NORNICDB_HYBRID_WALK_MIN_N keeps the exact
        matmul tier (rank-identical fused path, PR 4 contract)."""
        from nornicdb_tpu.obs import REGISTRY
        from nornicdb_tpu.storage import MemoryEngine

        self._env(monkeypatch, walk_min_n="1000000")
        rng = np.random.default_rng(67)
        cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
        svc = _make_service(MemoryEngine(), rng, cent)
        qv = (cent[1] + 0.4 * rng.standard_normal(D)).astype(np.float32)
        before = _strategy_count(REGISTRY, "hybrid_fused")
        svc.search("term1 term2", limit=5, query_embedding=qv)
        after = _strategy_count(REGISTRY, "hybrid_fused")
        assert after == before + 1

    def test_rebuild_cagra_rebinds_shared_graph(self, monkeypatch):
        """The strategy machine building its CAGRA tier rebinds the
        fused wrapper onto the new graph IN PLACE — one graph in HBM,
        one rebuild cadence, and the lexical snapshot keeps serving
        (the _ensure_fused lifecycle satellite)."""
        from nornicdb_tpu.storage import MemoryEngine

        self._env(monkeypatch)
        monkeypatch.setenv("NORNICDB_VECTOR_ANN_QUALITY", "cagra")
        rng = np.random.default_rng(71)
        cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
        store = MemoryEngine()
        svc = _make_service(store, rng, cent)
        qv = (cent[1] + 0.4 * rng.standard_normal(D)).astype(np.float32)
        svc.search("term1 term2", limit=5, query_embedding=qv)
        f0 = svc._fused
        assert f0 is not None
        own_graph = f0.cagra
        # strategy switch builds the service graph
        svc.hnsw_threshold = 10
        svc._maybe_switch_strategy()
        assert svc.cagra is not None and svc.cagra is not own_graph
        svc.search("term1 term2 term3", limit=5, query_embedding=qv)
        assert svc._fused is f0, "lexical snapshot was torn down"
        assert f0.cagra is svc.cagra, "graph not shared"
        # the rebound walk tier serves from the SERVICE graph
        snap = f0.lex._snap
        tok, _ = snap["row_maps"]["l2g"]
        assert tok == svc.cagra._graph["build_seq"]

    def test_reload_rebinds_fused_wrapper(self, monkeypatch, tmp_path):
        """load_indexes swaps the index objects; the next search must
        re-wrap onto them — the old pipeline (old row->slot maps) can
        never serve the discarded corpus."""
        from nornicdb_tpu.search.service import SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        self._env(monkeypatch)
        rng = np.random.default_rng(73)
        cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
        store = MemoryEngine()
        svc = SearchService(storage=store,
                            persist_dir=str(tmp_path / "idx"))
        for i in range(120):
            text = " ".join(rng.choice(VOCAB,
                                       size=int(rng.integers(3, 10))))
            node = Node(id=f"n{i}", labels=["Doc"],
                        properties={"content": text},
                        embedding=list(
                            (cent[i % 8] + 0.4
                             * rng.standard_normal(D))
                            .astype(np.float32)))
            store.create_node(node)
            svc.index_node(node)
        qv = (cent[1] + 0.4 * rng.standard_normal(D)).astype(np.float32)
        svc.search("term1 term2", limit=5, query_embedding=qv)
        f0 = svc._fused
        assert f0 is not None
        assert svc.save_indexes()
        assert svc.load_indexes()
        assert svc._fused is None, "wrapper survived reload"
        res = svc.search("term1 term2", limit=5, query_embedding=qv)
        assert res
        f1 = svc._fused
        assert f1 is not None and f1 is not f0
        assert f1.brute is svc.vectors and f1.bm25 is svc.bm25
