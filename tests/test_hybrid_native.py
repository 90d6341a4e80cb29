"""The native hybrid read path at a size worth a deployment (ISSUE 28):
the bulk entry leaves the indexes as a node-by-node load would, the
strategy machine keeps the exact device tier on an accelerator and
behaves as before on the CPU, the warm call leaves nothing to compile,
and ``POST /nornicdb/search`` agrees with the benchmark's plain
reference while the fused device tier serves.
"""

from __future__ import annotations

import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import nornicdb_tpu  # noqa: E402
from nornicdb_tpu.search import service as service_mod  # noqa: E402
from nornicdb_tpu.search.bm25 import BM25Index, tokenize  # noqa: E402
from nornicdb_tpu.search.service import SearchService  # noqa: E402
from nornicdb_tpu.search.vector_index import BruteForceIndex  # noqa: E402
from nornicdb_tpu.storage import MemoryEngine, Node  # noqa: E402
from nornicdb_tpu.errors import AlreadyExistsError  # noqa: E402
from nornicdb_tpu.storage.types import (  # noqa: E402
    ListenableEngine,
    MutationListener,
)

D = 32
WORDS = [f"w{i:x}" for i in range(65, 65 + 3000)]


def _corpus(n, seed=0, dims=D):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(65, 65 + len(WORDS))
    p /= p.sum()
    texts = [" ".join(rng.choice(WORDS, int(rng.integers(5, 60)), p=p))
             for _ in range(n)]
    # what real text brings: stop words, one-letter and over-long runs,
    # punctuation, capitals, text that is not ASCII, an empty document
    texts[3] = "The quick brown fox; a FOX! " + "z" * 41 + " x yy"
    texts[4] = "Ünïcode café 42 and naïve " + texts[4]
    texts[5] = ""
    vectors = rng.standard_normal((n, dims)).astype(np.float32)
    vectors[7] = 0.0
    return [f"p{i}" for i in range(n)], texts, vectors


def _bm25_state(idx: BM25Index):
    return (idx.to_dict(), list(idx._postings), idx._df, list(idx._df),
            idx._doc_terms, idx._int_of, idx._total_len, idx._n_alive,
            idx._n_postings, idx._mut_gen, idx._changelog,
            idx._changelog_floor, idx.compactions)


def _brute_state(idx: BruteForceIndex):
    return (idx._capacity, idx._count, idx._n_alive, idx._ext_ids,
            idx._slot_of, idx._free, idx.mutations, idx.compactions,
            idx._matrix.view(np.int32).tolist(), idx._valid.tolist())


def _changelog_reaches_as_far(bulk: BruteForceIndex, loop: BruteForceIndex):
    """``add_matrix`` trims the changelog once, at the final capacity:
    it ends with the loop's entries and may hold older ones before."""
    n = len(loop._changelog)
    return (bulk._changelog[len(bulk._changelog) - n:] == loop._changelog
            and bulk._changelog_floor <= loop._changelog_floor
            and len(bulk._changelog) <= bulk.changelog_cap()
            and bulk.changed_since(loop._changelog_floor)
            == loop.changed_since(loop._changelog_floor))


# -- the bulk entries equal the loops they stand for -------------------------


@pytest.mark.parametrize("before,n", [(0, 6000), (700, 5000), (5000, 70),
                                      (0, 40)])
def test_bm25_index_batch_equals_index_once_a_doc(before, n):
    ids, texts, _ = _corpus(before + n, seed=before + n)
    bulk, loop = BM25Index(), BM25Index()
    for i in range(before):
        bulk.index(ids[i], texts[i])
        loop.index(ids[i], texts[i])
    bulk.index_batch(list(zip(ids[before:], texts[before:])))
    for i in range(before, before + n):
        loop.index(ids[i], texts[i])
    assert _bm25_state(bulk) == _bm25_state(loop)
    some = list(bulk._postings)[:: max(len(bulk._postings) // 40, 1)]
    for term in some:
        for a, b in zip(bulk._postings[term].arrays(),
                        loop._postings[term].arrays()):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    for query in ("fox cafe", " ".join(WORDS[:3]), texts[10]):
        assert bulk.search(query, 20) == loop.search(query, 20)


@pytest.mark.parametrize("how", ["id_indexed_already", "id_twice",
                                 "tombstone"])
def test_bm25_index_batch_takes_the_loop_when_it_must(how):
    ids, texts, _ = _corpus(300, seed=5)
    docs = list(zip(ids, texts))
    bulk, loop = BM25Index(), BM25Index()
    for idx in (bulk, loop):
        idx.index("p0", "an older text for p0")
        if how == "tombstone":
            idx.index("gone", "soon removed")
            idx.remove("gone")
    if how == "id_twice":
        docs = docs + [docs[17]]
    if how == "tombstone":
        docs = docs[1:]
    bulk.index_batch(docs)
    for doc_id, text in docs:
        loop.index(doc_id, text)
    assert _bm25_state(bulk) == _bm25_state(loop)


@pytest.mark.parametrize("before,n", [(0, 20000), (300, 17000), (5000, 70),
                                      (0, 40)])
def test_add_matrix_equals_add_once_a_row(before, n):
    ids, _, vectors = _corpus(before + n, seed=n, dims=16)
    bulk, loop = BruteForceIndex(), BruteForceIndex()
    for i in range(before):
        bulk.add(ids[i], vectors[i])
        loop.add(ids[i], vectors[i])
    bulk.add_matrix(ids[before:], vectors[before:])
    for i in range(before, before + n):
        loop.add(ids[i], vectors[i])
    assert _brute_state(bulk) == _brute_state(loop)
    assert _changelog_reaches_as_far(bulk, loop)


def test_add_matrix_takes_the_loop_over_free_slots_and_known_ids():
    ids, _, vectors = _corpus(200, seed=9, dims=16)
    bulk, loop = BruteForceIndex(), BruteForceIndex()
    for idx in (bulk, loop):
        idx.add("old", vectors[0])
        idx.add("p3", vectors[1])        # p3 comes again in the batch
        idx.remove("old")                # a free slot
    bulk.add_matrix(ids, vectors)
    for i, v in zip(ids, vectors):
        loop.add(i, v)
    assert _brute_state(bulk) == _brute_state(loop)
    assert (bulk._changelog, bulk._changelog_floor) \
        == (loop._changelog, loop._changelog_floor)
    with pytest.raises(ValueError):
        bulk.add_matrix(["a", "b"], vectors[:3])


def test_create_nodes_is_one_batch_all_or_nothing_and_tells_no_node():
    class Seen(MutationListener):
        def __init__(self):
            self.upserts, self.bulk = 0, 0

        def on_node_upsert(self, node):
            self.upserts += 1

        def on_bulk_change(self):
            self.bulk += 1

    eng = ListenableEngine(MemoryEngine())
    seen = Seen()
    eng.add_listener(seen)
    eng.create_nodes([Node(id=f"n{i}", labels=["L"],
                           properties={"content": str(i)})
                      for i in range(50)])
    assert eng.count_nodes() == 50 and (seen.upserts, seen.bulk) == (0, 1)
    assert eng.get_node("n7").properties == {"content": "7"}
    assert eng.get_node("n7").created_at > 0
    assert len(eng.node_ids_by_label("L")) == 50
    with pytest.raises(AlreadyExistsError):
        eng.create_nodes([Node(id="fresh"), Node(id="n3")])
    assert not eng.has_node("fresh")
    with pytest.raises(AlreadyExistsError):
        eng.create_nodes([Node(id="twice"), Node(id="twice")])
    # a decorator without a batch path of its own loops over ITS
    # create_node, so what it adds to a write is never skipped
    from nornicdb_tpu.storage.types import EngineDecorator

    class Counting(EngineDecorator):
        calls = 0

        def create_node(self, node):
            Counting.calls += 1
            super().create_node(node)

    Counting(MemoryEngine()).create_nodes([Node(id="a"), Node(id="b")])
    assert Counting.calls == 2


@pytest.fixture
def two_stores(monkeypatch):
    """One database filled by ``store_batch``, one by ``store`` with an
    explicit embedding, a node at a time; hash embedder, no HNSW."""
    monkeypatch.setenv("NORNICDB_TPU_EMBEDDER", "hash")
    n = 4500
    ids, texts, vectors = _corpus(n, seed=21)
    texts = [t or "w41" for t in texts]      # store() keeps empty content
    bulk, loop = nornicdb_tpu.open(), nornicdb_tpu.open()
    loop.search
    for i in range(n):
        loop.store(texts[i], labels=["Passage"], node_id=ids[i],
                   embedding=vectors[i].tolist())
    bulk.store_batch(texts, vectors, node_ids=ids, labels=["Passage"])
    yield bulk, loop, ids, texts, vectors
    bulk.close()
    loop.close()


def test_store_batch_equals_store_once_a_node(two_stores):
    bulk, loop, ids, texts, vectors = two_stores
    a, b = bulk.search, loop.search
    assert _bm25_state(a.bm25) == _bm25_state(b.bm25)
    assert a.bm25._doc_len == b.bm25._doc_len
    assert _brute_state(a.vectors) == _brute_state(b.vectors)
    assert _changelog_reaches_as_far(a.vectors, b.vectors)
    assert a.stats.indexed_docs == b.stats.indexed_docs == len(ids)
    assert a.stats.strategy == b.stats.strategy == "brute"
    assert a.hnsw is None and b.hnsw is None
    assert bulk.storage.count_nodes() == loop.storage.count_nodes()
    node = bulk.storage.get_node(ids[11])
    assert node.labels == ["Passage"] and node.embedding is None
    assert node.properties == {"content": texts[11]}
    # nothing was queued for a second embedding, and the rescan's test
    # knows these nodes have their vector
    bulk.flush()
    assert bulk._embed_queue.has_vector(ids[11])
    assert a.vectors.get(ids[11]) is not None
    # the fused tier builds in the background and the host serves until
    # it is there; the two tiers' scores differ in their last bits, so
    # both stores answer from the same one: wait for both builds
    deadline = time.time() + 60
    while (a._ensure_fused() is None or b._ensure_fused() is None) \
            and time.time() < deadline:
        time.sleep(0.01)
    rng = np.random.default_rng(22)
    for _ in range(100):
        row = int(rng.integers(0, len(ids)))
        words = tokenize(texts[row])
        query = " ".join(rng.choice(words, min(len(words), 4),
                                    replace=False))
        qv = rng.standard_normal(D).astype(np.float32)
        got = a.search(query, limit=10, query_embedding=qv, enrich=False)
        want = b.search(query, limit=10, query_embedding=qv, enrich=False)
        assert got == want and len(got) == 10


def test_index_batch_runs_the_bookkeeping_once_a_call(monkeypatch):
    svc = SearchService()
    calls = {"strategy": 0, "cache": 0, "save": 0}
    for name, key in (("_maybe_switch_strategy", "strategy"),
                      ("_clear_result_cache", "cache"),
                      ("_schedule_save", "save")):
        inner = getattr(svc, name)

        def counted(inner=inner, key=key):
            calls[key] += 1
            return inner()

        monkeypatch.setattr(svc, name, counted)
    ids, texts, vectors = _corpus(300, seed=3)
    svc.index_batch(ids, texts, vectors)
    assert calls == {"strategy": 1, "cache": 1, "save": 1}
    assert len(svc.bm25) == 299 and len(svc.vectors) == 300  # one empty text
    with pytest.raises(ValueError):
        svc.index_batch(ids[:2], texts[:3], vectors[:2])


# -- the strategy machine ----------------------------------------------------


@pytest.mark.parametrize("device_bytes,bulk,want", [
    (None, False, "hnsw"),            # the CPU backend: today's ladder
    (None, True, "hnsw"),
    (16 * 2 ** 30, False, "brute"),   # an accelerator the matrix fits
    (16 * 2 ** 30, True, "brute"),
    (100_000, False, "hnsw"),         # an accelerator it does not fit
])
def test_strategy_keeps_the_device_tier_where_there_is_a_device(
        monkeypatch, device_bytes, bulk, want):
    monkeypatch.setattr(service_mod, "_accelerator_memory_limit",
                        lambda: device_bytes)
    svc = SearchService(hnsw_threshold=300)
    ids, texts, vectors = _corpus(400, seed=1)
    if bulk:
        svc.index_batch(ids, texts, vectors)
    else:
        for i, t, v in zip(ids, texts, vectors):
            svc.index_node(Node(id=i, properties={"content": t},
                                embedding=v.tolist()))
    assert svc.stats.strategy == want
    assert (svc.hnsw is not None) == (want == "hnsw")
    assert svc.stats.hnsw_builds == (1 if want == "hnsw" else 0)
    hits = svc.vector_search_candidates(vectors[9], k=5)
    assert hits[0][0] == "p9"


def test_the_backend_look_says_none_on_the_cpu():
    service_mod._accelerator_memory_limit.cache_clear()
    assert service_mod._accelerator_memory_limit() is None
    assert SearchService()._device_keeps_exact_tier() is False


# -- warm, then nothing compiles ---------------------------------------------


class _SeededEmbedder:
    dims = D

    def embed(self, text):
        seed = int.from_bytes(text.encode()[:8].ljust(8, b"\0"), "little")
        return np.random.default_rng(seed).standard_normal(D).tolist()

    def embed_batch(self, texts):
        return [self.embed(t) for t in texts]


def test_after_the_warm_call_a_thousand_queries_compile_nothing(
        monkeypatch):
    from jax._src import monitoring

    monkeypatch.setenv("NORNICDB_HYBRID_WALK", "0")
    db = nornicdb_tpu.open(embedder=_SeededEmbedder())
    try:
        ids, texts, vectors = _corpus(4500, seed=31)
        db.store_batch([t or "w41" for t in texts], vectors, node_ids=ids,
                       labels=["Passage"])
        assert db.search.warm_hybrid(limit=10, max_batch=4) == [1, 2, 4]
        compiled = []
        monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: compiled.append(event)
            if "backend_compile" in event else None)
        served0 = _served("hybrid_brute_f32")
        rng = np.random.default_rng(32)
        for _ in range(1000):
            row = int(rng.integers(0, len(ids)))
            words = sorted(set(tokenize(texts[row])))
            n = min(len(words), 2 + int(rng.binomial(10, 0.4)))
            if n == 0:
                continue
            query = " ".join(rng.choice(words, n, replace=False))
            assert len(db.search.search(query, limit=10)) == 10
        assert compiled == []
        assert _served("hybrid_brute_f32") - served0 >= 990
    finally:
        db.close()


def _served(tier):
    from nornicdb_tpu.obs import REGISTRY

    key = f'nornicdb_served_tier_total{{surface="hybrid",tier="{tier}"}} '
    for line in REGISTRY.render().splitlines():
        if line.startswith(key):
            return float(line[len(key):])
    return 0.0


def test_warm_hybrid_warms_nothing_below_the_fused_tiers_floor():
    svc = SearchService()
    ids, texts, vectors = _corpus(200, seed=2)
    svc.index_batch(ids, texts, vectors)
    assert svc.warm_hybrid() == []


# -- the served path against the plain reference -----------------------------


def test_nornicdb_search_agrees_with_the_plain_reference(
        benchmark_state_put_back):
    """A whole rehearsed run of the benchmark's cell at its CPU sizes
    (6,000 passages, over ``HYBRID_MIN_N``, a 2-layer random encoder):
    text in over HTTP, every score of a sample of the served hits against
    the reference's, per source, and membership by ``fused_gap``."""
    from benchmark import run as bench_run

    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", "hybrid1m-c32", "--seed",
                             "2147483661", "--seconds", "2", "--trace",
                             "0", "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 50
    checks = result["checks"]
    assert checks["fused_gap_max"]["value"] == 0.0
    assert checks["window_compiles"]["value"] == 0.0
    assert 0.0 < checks["lex_score_err_max"]["value"] < 1e-4
    assert 0.0 < checks["vec_score_err_max"]["value"] < 2e-6
    read = result["counts"]["readers_that_read"]
    assert {"hybrid_device_share_pct", "query_embed_ms", "hybrid_plan_ms",
            "hybrid_dispatch_ms", "hybrid_hydrate_ms", "hybrid_decode_ms",
            "coalesce_batch_mean"} <= set(read)


# -- the roofline's bytes, against a hand count ------------------------------


def test_hybrid_cost_against_a_hand_count():
    from benchmark.lib.hybrid_costs import hybrid_cost, padded

    assert padded(1_048_576) == 1_048_576 and padded(6000) == 8192
    assert padded(1_048_577) == 2_097_152 and padded(10) == 256
    # a batch of 8 over the deployment's sizes, 128 term rows, 640,000
    # postings: by hand,
    #   matrix   1,048,576 x 1,024 x 4        = 4,294,967,296 B
    #   postings 640,000 x (4 + 2 + 2)        =     5,120,000 B
    #   tf-norms 2 x 128 x 1,048,576 x 4      = 1,073,741,824 B
    #   FLOPs    2 x 8 x 1,048,576 x 1,024    = 17,179,869,184
    #          + 2 x 8 x 128 x 1,048,576      =  2,147,483,648
    flops, byts = hybrid_cost(8, 128, 640_000, 1_048_576, 1024, 1_048_576)
    assert byts == 4_294_967_296 + 5_120_000 + 1_073_741_824
    assert flops == 17_179_869_184 + 2_147_483_648
    # at the chip's peaks the bytes bind: 6.56 ms against 0.098 ms
    assert byts / 819e9 > 50 * flops / 197e12
