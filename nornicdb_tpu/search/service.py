"""Hybrid search service: BM25 + vector + RRF, with strategy state machine.

Reference: pkg/search/search.go ``Service`` (:417-524), ``Search`` (:2841),
``BuildIndexes`` (:2246), ``IndexNode`` (:1785), strategy state machine
bruteCPU <-> bruteGPU <-> HNSW (:528-535). TPU design: the "GPU" strategy
is simply the device-backed BruteForceIndex (ops dispatch to whatever
backend JAX has). What happens above ``hnsw_threshold`` vectors depends on
the backend (``_maybe_switch_strategy``): on the CPU backend a host HNSW
is built, BM25-seeded, and fed on the write path from then on; on an
accelerator the exact device tier stays for as long as the float32 matrix
takes no more than half the chip's memory, and no host graph is built
(a scan of a million rows is milliseconds there, and a Python HNSW on the
write path never finishes loading them). The ``cagra`` profile builds
its device graph above the threshold on either.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from nornicdb_tpu.embed.http_providers import EmbedHTTPError
from nornicdb_tpu.obs import REGISTRY, attach_span
from nornicdb_tpu.obs.tracing import span as _span
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu.search.bm25 import BM25Index, tokenize
from nornicdb_tpu.search.hnsw import HNSWIndex
from nornicdb_tpu.search.rrf import rrf_fuse
from nornicdb_tpu.search.vector_index import BruteForceIndex
from nornicdb_tpu.storage.types import Engine, Node

TEXT_PROPERTIES = ("content", "title", "name", "description", "text", "summary")

# which index the strategy machine actually routed each vector search
# to — the brute/cagra/hnsw split the ROADMAP tuning loop reads
_STRATEGY_C = REGISTRY.counter(
    "nornicdb_search_strategy_total",
    "Vector search dispatches by chosen strategy", labels=("strategy",))

# tier-mix truth for result-cache hits (ISSUE 10): cached child — the
# hit path must not pay a labels() probe per request
_HYBRID_CACHED_SERVED = _audit.served_counter("hybrid", "cached")


# the share of one chip's memory the exact tier's float32 matrix may take
# before the strategy machine looks for an approximate index: the rest is
# for the encoder's parameters, the lexical snapshot and the programs'
# temporaries (a fused batch of 32 keeps ~2.5 GB beside a 4.3 GB matrix)
DEVICE_EXACT_SHARE = 0.5


@functools.lru_cache(maxsize=1)
def _accelerator_memory_limit() -> Optional[int]:
    """Bytes of device memory one chip offers, as the backend reports
    them; None on the CPU backend, which has no device tier to keep.
    Asked once: ``index_node`` runs the strategy machine once a node."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0)) or None


@contextlib.contextmanager
def gc_paused(freeze: bool = False):
    """The cyclic collector off for the length of a bulk load: millions
    of new containers trigger full collections that find nothing (a
    20,000-document ``BM25Index.index_batch`` takes 0.64 s without them
    and 1.14 s with, CPU).

    ``freeze`` is for the caller that OWNS a load of lasting objects
    (``DB.store_batch``): garbage is collected first, and on the way out
    ``gc.freeze()`` moves everything then alive into the collector's
    permanent generation, so that later full collections do not walk a
    million nodes and their postings again (0.05-0.07 s a collection
    with them frozen, v5e host, PR 28). That is a lasting effect on the
    whole process, not on the load alone: reference counting frees a
    frozen object as before, but a reference CYCLE alive at that moment,
    anywhere in the process, is never collected once it is dropped
    (``gc.unfreeze()`` undoes it)."""
    was_on = gc.isenabled()
    if was_on and freeze:
        gc.collect()
    gc.disable()
    try:
        yield
    finally:
        if was_on:
            if freeze:
                gc.freeze()
            gc.enable()


def _copy_tree(v):
    """Manual deep copy of plain JSON-shaped data. copy.deepcopy's
    protocol machinery (memo dict, reduce dispatch) costs ~8x more per
    hit and sat at the top of the REST-search request profile."""
    if isinstance(v, dict):
        return {k: _copy_tree(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_copy_tree(x) for x in v]
    return v


def _copy_hit(r: Dict[str, Any]) -> Dict[str, Any]:
    """Cache-safe copy of one search hit: the nested properties/labels
    come from the node BY REFERENCE (to_dict), so a shallow dict() would
    let a caller's mutation poison the cached entry for the whole TTL."""
    c = dict(r)
    if "properties" in c:
        c["properties"] = _copy_tree(c["properties"])
    if "labels" in c:
        c["labels"] = list(c["labels"])
    return c


def extract_text(node: Node) -> str:
    """Searchable text from a node (reference: pkg/indexing
    ExtractSearchableText — title/content-ish properties + labels)."""
    parts: List[str] = []
    for key in TEXT_PROPERTIES:
        v = node.properties.get(key)
        if isinstance(v, str) and v:
            parts.append(v)
    parts.extend(node.labels)
    return " ".join(parts)


@dataclass
class SearchResult:
    node_id: str
    score: float
    node: Optional[Node] = None
    bm25_score: Optional[float] = None
    vector_score: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"id": self.node_id, "score": self.score}
        if self.bm25_score is not None:
            d["bm25_score"] = self.bm25_score
        if self.vector_score is not None:
            d["vector_score"] = self.vector_score
        if self.node is not None:
            d["labels"] = self.node.labels
            d["properties"] = self.node.properties
        return d


@dataclass
class SearchStats:
    indexed_docs: int = 0
    indexed_vectors: int = 0
    strategy: str = "brute"
    searches: int = 0
    cache_hits: int = 0
    hnsw_builds: int = 0
    cagra_builds: int = 0
    # per-stage timings of the most recent search, populated when
    # NORNICDB_TPU_SEARCH_DIAG is set (reference:
    # NORNICDB_SEARCH_DIAG_TIMINGS)
    last_timings: Dict[str, float] = field(default_factory=dict)


class SearchService:
    """One search service per logical database
    (reference: per-DB instances, pkg/nornicdb/search_services.go:68).

    ``hnsw_threshold`` is where the vector strategy leaves the plain
    brute tier: for a host HNSW on the CPU backend, for nothing while
    the matrix fits the chip on an accelerator (the module's header
    says why), for the device graph under the ``cagra`` profile."""

    def __init__(
        self,
        storage: Optional[Engine] = None,
        embedder: Optional[Any] = None,
        hnsw_threshold: int = 10_000,
        hnsw_m: int = 16,
        hnsw_ef_search: int = 64,
        reranker: Optional[Any] = None,
        database: str = "neo4j",
        vector_registry: Optional[Any] = None,
        persist_dir: Optional[str] = None,
        save_debounce_s: float = 5.0,
        resource_name: Optional[str] = None,
    ):
        self.storage = storage
        self.embedder = embedder
        self.reranker = reranker  # stage-2 rerank (rerank.py), optional
        self.hnsw_threshold = hnsw_threshold
        self._lock = threading.RLock()
        self.bm25 = BM25Index()
        # the document vector index lives in a registered vector space
        # (reference: pkg/vectorspace/registry.go keyed spaces; the
        # service's default doc space is (db, "node", "embedding"))
        from nornicdb_tpu.vectorspace import VectorSpaceRegistry

        self.database = database
        # per-service registry unless the caller shares one (multidb
        # passes a shared registry so spaces are keyed per database)
        self.vector_registry = vector_registry or VectorSpaceRegistry()
        self._doc_space = self.vector_registry.get_or_create(
            database=database, entity_type="node", backend="brute"
        )
        self.vectors = self._doc_space.ensure_index()
        self.hnsw: Optional[HNSWIndex] = None
        # device-resident graph ANN (profile cagra): wraps self.vectors
        # as its vector store, so index mutations propagate and the
        # graph rebuilds itself from the shared brute snapshot
        self.cagra = None
        self._hnsw_m = hnsw_m
        self._hnsw_ef = hnsw_ef_search
        self.stats = SearchStats()
        # Search() result cache, query+options keyed — same semantics
        # as the Cypher query cache and the reference's
        # searchResultCache; generation-guarded puts + copy-on-return
        # (cache.py ResultCache)
        from nornicdb_tpu.cache import ResultCache

        self._result_cache: ResultCache = ResultCache(_copy_hit)
        # index persistence: debounced saves + load-on-open so a restart
        # skips the rebuild (reference: search.go:496-507, versioned
        # persisted indexes + resumeVectorBuild search.go:432)
        self.persist_dir = persist_dir
        self._save_debounce_s = save_debounce_s
        self._save_timer: Optional[threading.Timer] = None
        self._save_lock = threading.Lock()  # serializes snapshot writers
        self._saved_at_ms = 0
        self._closed = False

        # concurrent b=1 vector queries coalesce into one batched device
        # call (SURVEY §7: "batched query aggregation, or the TPU path
        # only wins at batch/scale")
        from nornicdb_tpu.search.microbatch import MicroBatcher

        # dispatch resolves the ACTIVE ANN index per batch (cagra once
        # built, else brute), so the coalescing window feeds whichever
        # device index the strategy machine currently owns;
        # tier_surface="vector" makes every rider record the serving
        # tier the dispatch path noted (walk/quant/brute — ISSUE 10)
        self._microbatch = MicroBatcher(self._ann_search_batch,
                                        surface="service:vector",
                                        tier_surface="vector")
        # fused hybrid pipeline (hybrid_fused.py): concurrent hybrid
        # searches coalesce here into ONE device dispatch that scores
        # BM25 + cosine + RRF end-to-end, instead of convoying on the
        # BM25 lock. Tokens/fusion options ride as extras; rows come
        # back pre-shaped, so the batcher neither stacks nor truncates
        # them (pass_extras/truncate flags).
        self._fused = None
        self._hybrid_batch = MicroBatcher(
            self._fused_hybrid_dispatch, pass_extras=True, truncate=False,
            surface="service:hybrid")
        # resource & freshness accounting (obs/resources.py): register
        # the index structures and coalescing queues so /metrics carries
        # their device-memory/staleness gauges and /readyz can gate on
        # rebuild/backlog/queue state. Weak registration — a dropped
        # service's series disappear with it.
        from nornicdb_tpu.obs import register_resource

        # resource identity: "service:<db>" unless the caller tags this
        # service (read replicas pass "service:<db>@<node>" so an
        # in-process fleet's per-replica gauges never collide)
        self.resource_name = resource_name or f"service:{database}"
        register_resource("bm25", self.resource_name, self.bm25)
        register_resource("brute", self.resource_name, self.vectors)
        register_resource("queue", f"{self.resource_name}:vector",
                          self._microbatch)
        register_resource("queue", f"{self.resource_name}:hybrid",
                          self._hybrid_batch)

    def _ann_search_batch(self, queries, k):
        """Batched device dispatch for the micro-batcher: the CAGRA
        graph walk when built, else the brute matmul+top-k."""
        cagra = self.cagra
        if cagra is not None:
            return cagra.search_batch(queries, k)
        return self.vectors.search_batch(queries, k)

    def _fused_hybrid_dispatch(self, queries, k_max, extras):
        """Batched device dispatch of the hybrid batcher: one compiled
        BM25+vector+RRF program per pow2 (B, k) bucket. None rows tell
        riders to fall back to the host hybrid path."""
        fused = self._fused
        if fused is None:
            return [None] * len(queries)
        return fused.search_batch(queries, k_max, extras)

    def _ensure_fused(self):
        """Resolve (building if needed) the fused hybrid pipeline, or
        None while the host path must serve. Env-gated like the ANN
        profiles: NORNICDB_HYBRID_FUSED (default on),
        NORNICDB_HYBRID_MIN_N corpus floor, NORNICDB_HYBRID_SHARDS mesh
        row-sharding, NORNICDB_HYBRID_INLINE_BUILD for deterministic
        (blocking) first builds in tests/benches. The walk tier
        (NORNICDB_HYBRID_WALK, default on) replaces the pipeline's
        exact vector matmul with the CAGRA greedy walk above
        NORNICDB_HYBRID_WALK_MIN_N live vectors (default 100k — below
        it the O(N) matmul is cheap enough that exact rank parity
        wins), sharing the strategy machine's graph when one exists.

        Lifecycle: the wrapper is evicted and re-wrapped when the
        underlying index OBJECTS move — an index reload
        (:meth:`load_indexes` clears ``_fused``) — and rebound IN PLACE
        (:meth:`FusedHybrid.rebind_cagra`, below) when the strategy
        machine builds a new CAGRA graph over the same brute index, so
        a stale pipeline can never keep serving a discarded corpus or
        keep walking a replaced graph while its row->slot maps silently
        mis-age. Anything snapshot-coupled to the graph must live on
        the per-graph snapshot (keyed by ``build_seq``), not on the
        wrapper: a graph swap does NOT rebuild the wrapper."""
        from nornicdb_tpu.config import env_bool, env_int

        # the whole resolve runs under the service RLock: the eviction
        # checks and the re-wrap race load_indexes (which swaps the
        # index objects and clears _fused under the same lock) — an
        # unguarded re-wrap here could briefly resurrect a wrapper over
        # a discarded corpus and double-build under concurrent searches
        with self._lock:
            f = self._ensure_fused_locked(env_bool, env_int)
        if f is None or not f.ensure():
            return None  # first build runs in background; host serves
        return f

    def _ensure_fused_locked(self, env_bool, env_int):
        if not env_bool("HYBRID_FUSED", True):
            self._fused = None
            return None
        min_n = env_int("HYBRID_MIN_N", 4096)
        if len(self.bm25) < min_n or len(self.vectors) == 0:
            self._fused = None
            return None
        f = self._fused
        if f is not None and f.bm25 is self.bm25 \
                and f.brute is self.vectors \
                and self.cagra is not None \
                and f.cagra is not self.cagra:
            # the strategy machine built its own graph over the same
            # brute index: rebind it in place — one graph, one rebuild
            # cadence, and the lexical snapshot keeps serving (a full
            # re-wrap would drop hybrid to the host path until the CSR
            # snapshot rebuilt)
            if not f.rebind_cagra(self.cagra):
                # the candidate graph wraps a brute other than the live
                # one (a racy background build finished after an index
                # reload): the wrapper itself is sound, so keep serving
                # it — rewrapping here would rebuild the pipeline on
                # EVERY search while the stale graph lingered — and
                # drop the graph, which would serve the discarded
                # corpus from any path that walked it
                self.cagra = None
        if f is None or f.bm25 is not self.bm25 \
                or f.brute is not self.vectors:
            # index reload swapped the underlying objects: re-wrap so
            # the pipeline can never serve a discarded corpus
            from nornicdb_tpu.search.hybrid_fused import FusedHybrid

            walk_min_n = None
            if env_bool("HYBRID_WALK", True):
                walk_min_n = env_int("HYBRID_WALK_MIN_N", 100_000)
            cagra = self.cagra
            if cagra is not None and cagra._brute is not self.vectors:
                # a racy background build captured a pre-reload brute:
                # its graph indexes a discarded corpus (FusedHybrid
                # re-checks this too; None = wrap a fresh one)
                cagra = None
            f = FusedHybrid(
                self.bm25, self.vectors,
                n_shards=max(1, env_int("HYBRID_SHARDS", 1)),
                min_n=min_n,
                build_inline=env_bool("HYBRID_INLINE_BUILD", False),
                walk_min_n=walk_min_n,
                cagra=cagra)
            self._fused = f
            from nornicdb_tpu.obs import register_resource

            register_resource("device_bm25",
                              self.resource_name, f.lex)
            if f.cagra is not None and f.cagra is not self.cagra:
                # pipeline-owned graph (walk tier without the cagra
                # strategy profile): account for its device arrays too
                register_resource(
                    "cagra", f"{self.resource_name}:hybrid_walk",
                    f.cagra)
        return f

    def warm_hybrid(self, limit: int = 10,
                    max_batch: Optional[int] = None) -> List[int]:
        """Compile, before traffic needs them, every program a hybrid
        search of ``limit`` hits can dispatch: the embedder's
        single-query shape, and for each power-of-two bucket up to
        ``max_batch`` riders (default: the most the hybrid batcher
        seals) BOTH of the bucket's fused programs (``device_bm25.
        lex_rows``): the ``half`` one with every rider the text "warm
        up", the ``full`` one with riders that ask, between them, for
        more distinct terms than ``half`` holds, taken from the
        snapshot's rarest so that the batch walks next to no postings.
        Then the index's update programs
        (``BruteForceIndex.warm_updates``), so a store after the warm-up
        compiles nothing either. The lexical snapshot is built first,
        inline, and the first batch ships the vector matrix to the
        device. Returns
        the buckets warmed: none while the fused tier is not eligible
        (``_ensure_fused``). A server calls this once after a bulk load;
        nothing else changes (no result is cached, no counter of served
        searches moves)."""
        from nornicdb_tpu.config import env_bool, env_int
        from nornicdb_tpu.search.device_bm25 import row_buckets
        from nornicdb_tpu.search.microbatch import pow2_bucket

        with self._lock:
            fused = self._ensure_fused_locked(env_bool, env_int)
        if fused is None or not fused.build():
            return []
        dims = int(self.vectors.dims or 0)
        qv = None
        if self.embedder is not None:
            qv = self._query_embedding("warm up")
        if qv is None:
            qv = np.ones((dims,), np.float32)
        overfetch = max(limit * 3, 30)
        kq = pow2_bucket(overfetch)

        def rider(tokens):
            return {"tokens": tuple(tokens), "n_cand": overfetch,
                    "w": (1.0, 1.0)}

        few = rider(tokenize("warm up"))
        top = self._hybrid_batch.max_batch if max_batch is None \
            else max_batch
        buckets: List[int] = []
        b = 1
        while b <= pow2_bucket(max(top, 1)):
            buckets.append(b)
            b *= 2
        # the `full` program of a bucket is asked for by one term more
        # than its `half` holds, dealt round the riders; a snapshot with
        # no more terms than that has, until it is rebuilt, no batch
        # that could ask for it
        rare = fused.lex.rare_terms(
            fused.lex.ensure_snapshot(), row_buckets(buckets[-1])[0] + 1)
        for b in buckets:
            qs = np.tile(qv, (b, 1))
            fused.search_batch(qs, kq, [few] * b)
            half, full = row_buckets(b)
            many = rare[: half + 1]
            if half < len(many) <= full:
                fused.search_batch(
                    qs, kq, [rider(many[i::b]) for i in range(b)])
        # and the programs that write later stores into the device copy
        self.vectors.warm_updates()
        return buckets

    def _fused_hybrid_trio(self, query, qv, overfetch, weights):
        """One coalesced fused-hybrid ride: (lex, vec, fused) candidate
        lists for this query, or None when the host path must serve.
        Fail-open — any device-path error degrades to host, never to a
        failed search."""
        f = self._ensure_fused()
        if f is None:
            return None
        w = tuple(weights) if weights else (1.0, 1.0)
        if len(w) != 2:
            return None  # host rrf_fuse handles exotic weight shapes
        t_ride = time.time()
        try:
            trio = self._hybrid_batch.search(
                qv, overfetch,
                extra={"tokens": tuple(tokenize(query)),
                       "n_cand": overfetch, "w": w})
        except Exception:
            return None
        if trio is None:
            return None
        tier = trio.get("tier", "brute")
        _STRATEGY_C.labels("hybrid_walk_fused" if tier == "walk"
                           else "hybrid_fused").inc()
        # rider-accurate tier attribution: this ROW's served_by (a
        # live-filter correction makes one rider "host" while its
        # batch-mates keep the device tier), counted + latency-observed
        # + stamped on the trace span (ISSUE 10)
        served = trio.get("served_by", "hybrid_brute_f32")
        _audit.record_served("hybrid", served,
                             seconds=time.time() - t_ride)
        if served != "host" and _audit.sampling_active():
            self._maybe_shadow_hybrid(served, trio, query, qv,
                                      overfetch, w)
        t = trio.get("times")
        if t:
            # the whole lexical+vector scoring ran inside one device
            # dispatch; split the trace at the decode boundary so
            # /admin/traces shows the hybrid ladder per request
            attach_span("lexical.score", t["device_t0"] - t["plan_s"],
                        t["device_t1"])
            if tier == "walk":
                # the vector half was the graph walk: surface its
                # fixed-iteration/pool config on the request's trace
                attach_span("vector.walk", t["device_t0"],
                            t["device_t1"], iters=t.get("walk_iters"),
                            itopk=t.get("walk_itopk"))
            attach_span("fuse", t["device_t1"],
                        t["device_t1"] + t["decode_s"])
        return trio

    def _maybe_shadow_hybrid(self, tier, trio, query, qv, overfetch, w):
        """Offer one device-served hybrid answer to the shadow-parity
        auditor. The reference closure re-runs the HOST hybrid path —
        live BM25 scoring, exact brute vector scan, bit-compatible
        rrf_fuse — on the audit worker thread, never on the hot path.
        Best-effort: sampling must never fail a search."""
        try:
            device_ids = [i for i, _ in trio["fused"]]
            bm25, vectors = self.bm25, self.vectors
            weights = list(w)

            def ref():
                bm_hits = bm25.search(query, overfetch)
                vec_hits = vectors.search_batch(
                    qv[None, :], overfetch, exact=True)[0]
                fused = rrf_fuse([bm_hits, vec_hits], weights=weights,
                                 limit=overfetch)
                return [i for i, _ in fused]

            # the result-cache generation bumps on EVERY index mutation
            # (text or vector), so it is the one version the replay-time
            # staleness check needs: a write between sampling and the
            # host reference run drops the sample instead of scoring a
            # correct device answer as a mismatch
            def versions_now():
                return {"generation": self._result_cache.generation}

            _audit.maybe_sample(
                "hybrid", tier, device_ids, k=min(10, overfetch),
                ref=ref, versions=versions_now(),
                versions_now=versions_now,
                query={"query": query, "overfetch": overfetch,
                       "weights": weights})
        except Exception:  # noqa: BLE001
            pass

    def _clear_result_cache(self) -> None:
        self._result_cache.bump_generation()

    @property
    def generation(self) -> int:
        """Write generation of the result cache — bumped on every index
        mutation. The gRPC wire cache (api/grpc_server.py) validates its
        cached response BYTES against this, so native-search responses
        served from raw bytes stay exactly as fresh as the result cache
        itself."""
        return self._result_cache.generation

    def microbatch_stats(self) -> Dict[str, float]:
        """Coalescing effectiveness of the vector micro-batcher (how
        many concurrent b=1 queries rode one device dispatch)."""
        mb = self._microbatch
        return {
            "batches": mb.batches,
            "batched_queries": mb.batched_queries,
            "mean_batch": mb.batched_queries / max(mb.batches, 1),
        }

    # -- indexing ---------------------------------------------------------

    def index_node(self, node: Node) -> None:
        """Index one node's text + embedding
        (reference: Service.IndexNode search.go:1785)."""
        if any(lbl.startswith("_") for lbl in node.labels):
            # system-owned nodes (Qdrant collections/points, meta) stay
            # out of the native hybrid index — they have their own
            # per-collection indexes (api/qdrant.py)
            return
        text = extract_text(node)
        with self._lock:
            if text:
                self.bm25.index(node.id, text)
            else:
                self.bm25.remove(node.id)  # update cleared the text
            vec = node.embedding
            if vec is None and node.chunk_embeddings:
                # whole-doc vector = mean of chunks (best-of-chunks is used
                # at query time by inference; mean anchors doc search)
                vec = list(np.mean(np.asarray(node.chunk_embeddings), axis=0))
            if vec is not None:
                self.vectors.add(node.id, vec)
                if self.hnsw is not None:
                    self.hnsw.add(node.id, vec)
            else:
                # update removed the embedding: drop stale vectors
                self.vectors.remove(node.id)
                if self.hnsw is not None:
                    self.hnsw.remove(node.id)
            self.stats.indexed_docs = len(self.bm25)
            self.stats.indexed_vectors = len(self.vectors)
            self._maybe_switch_strategy()
        self._clear_result_cache()
        self._schedule_save()

    def index_batch(self, ids: Sequence[str], texts: Sequence[str],
                    vectors: np.ndarray) -> None:
        """Index many nodes in one call: ``ids[i]`` with the searchable
        text ``texts[i]`` (what ``extract_text`` gives for the node) and
        the embedding ``vectors[i]`` (a float32 ``[n, dims]`` matrix).
        The text and vector indexes end in exactly the state
        ``index_node`` called once a node, in order, would leave them
        in, and fresh ids (a bulk load) get there without a Python call
        a row (``BM25Index.index_batch``, ``BruteForceIndex.add_matrix``).
        The strategy machine, the result cache's generation and the
        debounced save run once for the call, not once a node. The
        caller keeps system-owned nodes (a label starting with ``_``)
        out, as ``index_node`` does."""
        ids, texts = list(ids), list(texts)
        vectors = np.asarray(vectors, dtype=np.float32)
        if not (len(ids) == len(texts) == len(vectors)) \
                or vectors.ndim != 2:
            raise ValueError(
                f"index_batch: {len(ids)} ids, {len(texts)} texts, "
                f"vectors {vectors.shape}")
        with gc_paused(), self._lock:
            for node_id, text in zip(ids, texts):
                if not text:
                    self.bm25.remove(node_id)
            self.bm25.index_batch(
                [(i, t) for i, t in zip(ids, texts) if t])
            self.vectors.add_matrix(ids, vectors)
            if self.hnsw is not None:
                for node_id, vec in zip(ids, vectors):
                    self.hnsw.add(node_id, vec)
            self.stats.indexed_docs = len(self.bm25)
            self.stats.indexed_vectors = len(self.vectors)
            self._maybe_switch_strategy()
        self._clear_result_cache()
        self._schedule_save()

    def remove_node(self, node_id: str) -> None:
        with self._lock:
            self.bm25.remove(node_id)
            self.vectors.remove(node_id)
            if self.hnsw is not None:
                self.hnsw.remove(node_id)
                if self.hnsw.should_rebuild():
                    self._rebuild_hnsw_locked()
            self.stats.indexed_docs = len(self.bm25)
            self.stats.indexed_vectors = len(self.vectors)
        self._clear_result_cache()
        self._schedule_save()

    def prune_missing(self) -> int:
        """Drop every indexed id whose storage node no longer exists.
        Bulk deletions that bypass per-node mutation events — a
        ``delete_by_prefix`` WAL record replayed on a read replica, a
        database drop under a shared store — leave the indexes holding
        tombstone-less ghosts; this reconciles them through the same
        ``remove_node`` path a live delete takes (changelogs, rebuild
        triggers and freshness ladders all see ordinary removals).
        Returns the number of ids pruned."""
        if self.storage is None:
            return 0
        with self._lock:
            indexed = set(self.bm25.ids()) | set(self.vectors.ids())
        pruned = 0
        for nid in indexed:
            try:
                missing = not self.storage.has_node(nid)
            except Exception:  # noqa: BLE001 — storage races resolve next sweep
                continue
            if missing:
                self.remove_node(nid)
                pruned += 1
        return pruned

    def build_indexes(self) -> int:
        """Index every node in storage (reference: BuildIndexes :2246).
        Returns count indexed. With a persist_dir, a valid on-disk
        snapshot is loaded first and only nodes created/updated since the
        snapshot are (re)indexed — the resume-aware build of
        search.go:432 resumeVectorBuild."""
        if self.storage is None:
            return 0
        resumed = self.load_indexes()
        n = 0
        for node in self.storage.all_nodes():
            if resumed and not self._needs_reindex(node):
                continue
            self.index_node(node)
            n += 1
        if resumed:
            # drop index entries whose node vanished while we were down —
            # both vector AND bm25 entries (a text-only node never enters
            # the vector index)
            live = {nd.id for nd in self.storage.all_nodes()}
            stale = set(self.vectors.ids()) | set(self.bm25.ids())
            for ext_id in stale - live:
                self.remove_node(ext_id)
        return n

    def _needs_reindex(self, node: Node) -> bool:
        if any(lbl.startswith("_") for lbl in node.labels):
            return False  # system nodes never enter this index (index_node)
        if (node.updated_at or 0) > self._saved_at_ms:
            return True
        has_vec = node.embedding is not None or node.chunk_embeddings
        if has_vec and node.id not in self.vectors:
            return True
        return node.id not in self.bm25 and bool(extract_text(node))

    # -- persistence ------------------------------------------------------

    _FORMAT_VERSION = 1

    def save_indexes(self) -> bool:
        """Write BM25 + vector (+ HNSW) snapshots atomically. Serialized:
        a timer-thread save racing a close() save over the same .tmp
        paths would publish a torn or mixed-generation snapshot."""
        if not self.persist_dir:
            return False
        with self._save_lock:
            return self._save_indexes_locked()

    def _save_indexes_locked(self) -> bool:
        import json
        import os

        os.makedirs(self.persist_dir, exist_ok=True)
        # capture under the service lock, but do the (slow) compression
        # and disk writes OUTSIDE it — the index objects snapshot under
        # their own locks, so searches/indexing keep flowing during the
        # multi-second write of a large matrix
        with self._lock:
            saved_at = int(time.time() * 1000)
            bm25_doc = self.bm25.to_dict()
            vectors = self.vectors
            hnsw = self.hnsw
        vectors.save(os.path.join(self.persist_dir, "vectors.npz.tmp"))
        if hnsw is not None:
            # HNSWIndex.save appends .npz itself
            hnsw.save(os.path.join(self.persist_dir, "hnsw.tmp"))
        with open(os.path.join(self.persist_dir, "bm25.json.tmp"), "w") as f:
            json.dump(bm25_doc, f)
        meta = {
            "format": self._FORMAT_VERSION,
            "saved_at_ms": saved_at,
            "has_hnsw": hnsw is not None,
            "strategy": self.stats.strategy,
        }
        with open(os.path.join(self.persist_dir, "meta.json.tmp"), "w") as f:
            json.dump(meta, f)
        # publish: meta last, so a torn save is simply ignored on load
        renames = [("vectors.npz.tmp", "vectors.npz"),
                   ("hnsw.tmp.npz", "hnsw.npz"),
                   ("bm25.json.tmp", "bm25.json"),
                   ("meta.json.tmp", "meta.json")]
        for tmp_name, name in renames:
            tmp = os.path.join(self.persist_dir, tmp_name)
            if os.path.exists(tmp):
                os.replace(tmp, os.path.join(self.persist_dir, name))
        self._saved_at_ms = saved_at
        return True

    def load_indexes(self) -> bool:
        """Load a persisted snapshot; False if absent/invalid/other
        format version (caller falls back to full rebuild)."""
        if not self.persist_dir:
            return False
        import json
        import os

        meta_path = os.path.join(self.persist_dir, "meta.json")
        try:
            with open(meta_path) as f:
                meta = json.load(f)
            if meta.get("format") != self._FORMAT_VERSION:
                return False
            with open(os.path.join(self.persist_dir, "bm25.json")) as f:
                bm25 = BM25Index.from_dict(json.load(f))
            vectors = BruteForceIndex.load(
                os.path.join(self.persist_dir, "vectors.npz"))
            hnsw = None
            if meta.get("has_hnsw"):
                hnsw = HNSWIndex.load(
                    os.path.join(self.persist_dir, "hnsw.npz"))
        except (OSError, ValueError, KeyError):
            return False
        with self._lock:
            self.bm25 = bm25
            # swap contents into the registered vector space so the
            # space's index IS still the live service index
            self._doc_space.index = vectors
            self.vectors = vectors
            # re-point the resource gauges at the restored structures
            from nornicdb_tpu.obs import register_resource

            register_resource("bm25", self.resource_name, bm25)
            register_resource("brute", self.resource_name,
                              vectors)
            self.hnsw = hnsw
            # any prior graph wraps the REPLACED brute index — drop it
            # or searches would keep serving the discarded corpus
            self.cagra = None
            self._fused = None  # same: the fused pipeline re-wraps lazily
            self._saved_at_ms = int(meta.get("saved_at_ms", 0))
            self.stats.indexed_docs = len(self.bm25)
            self.stats.indexed_vectors = len(self.vectors)
            self.stats.strategy = "brute"
            if hnsw is not None:
                self.stats.strategy = "hnsw"
            elif meta.get("strategy") == "cagra":
                # the graph is derived state (not persisted): rebuild it
                # from the restored vectors now so a read-only workload
                # after restart doesn't silently serve brute-force
                self._maybe_switch_strategy()
        return True

    def _schedule_save(self) -> None:
        """Throttled persistence: at most one pending timer — a steady
        write stream persists every debounce interval instead of pushing
        the save out forever (and no Timer churn per indexed node)."""
        if not self.persist_dir or self._closed:
            return
        with self._save_lock:
            if self._save_timer is not None:
                return
            t = threading.Timer(self._save_debounce_s, self._save_quietly)
            t.daemon = True
            self._save_timer = t
            t.start()

    def _save_quietly(self) -> None:
        with self._save_lock:
            self._save_timer = None
        try:
            self.save_indexes()
        except Exception:
            pass  # a failed background save must not take down the app

    def close(self) -> None:
        """Final save; cancels any pending save timer."""
        self._closed = True
        with self._save_lock:
            if self._save_timer is not None:
                self._save_timer.cancel()
                self._save_timer = None
        if self.persist_dir:
            try:
                self.save_indexes()
            except Exception:
                pass

    # -- strategy state machine -------------------------------------------

    def _device_keeps_exact_tier(self) -> bool:
        """On an accelerator, while the index's float32 matrix (at the
        capacity it is padded to) takes no more than
        ``DEVICE_EXACT_SHARE`` of one chip's memory. Never on the CPU
        backend."""
        limit = _accelerator_memory_limit()
        if limit is None:
            return False
        from nornicdb_tpu.ops.similarity import pad_dim

        need = pad_dim(len(self.vectors)) * int(self.vectors.dims or 0) * 4
        return need <= DEVICE_EXACT_SHARE * limit

    def _maybe_switch_strategy(self) -> None:
        if len(self.vectors) < self.hnsw_threshold:
            return
        from nornicdb_tpu.search.ann_quality import current_profile

        if current_profile().index_kind == "cagra":
            # device-graph tier: the CagraIndex manages its own rebuild
            # cadence after the first build (mutation-churn threshold)
            if self.cagra is None:
                self._rebuild_cagra_locked()
            return
        if self.hnsw is None and not self._device_keeps_exact_tier():
            self._rebuild_hnsw_locked()

    def _rebuild_cagra_locked(self) -> None:
        """Build the device-resident graph over the live brute index.
        Config-gated (NORNICDB_VECTOR_ANN_QUALITY=cagra); the service
        threshold is the build gate, so min_n only keeps the index
        honest if the corpus later shrinks."""
        from nornicdb_tpu.search.ann_quality import (
            cagra_shards_from_env,
            current_profile,
        )
        from nornicdb_tpu.search.cagra import CagraIndex

        p = current_profile()
        # build_inline=False: the first build happens right here (the
        # explicit build() below, on the write path); any LATER
        # graph-from-scratch transition (corpus shrank below min_n and
        # regrew) must not stall a search convoy — brute serves while
        # the background build runs
        idx = CagraIndex(
            brute=self.vectors,
            degree=p.cagra_degree, itopk=p.cagra_itopk,
            search_width=p.cagra_width,
            min_n=min(p.cagra_min_n, self.hnsw_threshold),
            n_shards=cagra_shards_from_env(p.cagra_shards),
            build_inline=False,
        )
        if not idx.build():
            return
        if idx._brute is not self.vectors:
            return  # an index reload swapped the corpus mid-build
        self.cagra = idx
        # any fused wrapper built before this graph existed rebinds to
        # it on the next search (_ensure_fused's in-place rebind) —
        # one graph, one rebuild cadence, no second copy in HBM
        from nornicdb_tpu.obs import register_resource

        register_resource("cagra", self.resource_name, idx)
        # surface the graph index as its own vector space, mirroring the
        # hnsw tier (reference: backend kinds, registry.go:1-60)
        cagra_space = self.vector_registry.get_or_create(
            database=self.database, entity_type="node",
            vector_name="embedding_cagra", backend="cagra",
        )
        cagra_space.index = idx
        self.stats.cagra_builds += 1
        self.stats.strategy = "cagra"

    def _rebuild_hnsw_locked(self) -> None:
        """(Re)build HNSW from the brute index, BM25 seeds first."""
        items = []
        matrix, valid, ext_ids = self.vectors.snapshot()
        for slot, eid in enumerate(ext_ids):
            if eid is not None and valid[slot]:
                items.append((eid, matrix[slot]))
        seeds = self.bm25.seed_doc_ids()
        idx = HNSWIndex(m=self._hnsw_m, ef_search=self._hnsw_ef)
        idx.build(items, seed_ids=seeds)
        self.hnsw = idx
        # surface the graph index as its own vector space (reference:
        # backend kinds auto/brute-force/hnsw, registry.go:1-60)
        hnsw_space = self.vector_registry.get_or_create(
            database=self.database, entity_type="node",
            vector_name="embedding_hnsw", backend="hnsw",
        )
        hnsw_space.index = idx
        self.stats.hnsw_builds += 1
        self.stats.strategy = "hnsw"

    # -- search -----------------------------------------------------------

    def _query_embedding(self, query: str) -> Optional[np.ndarray]:
        if self.embedder is None:
            return None
        try:
            return np.asarray(self.embedder.embed(query), dtype=np.float32)
        except EmbedHTTPError:
            # fail-open for a REMOTE provider's transport error only:
            # hybrid degrades to text-only, counted. A local (JAX)
            # embedder that raises is the caller's to see.
            _audit.record_degrade("hybrid", "hybrid_brute_f32", "host",
                                  "error", index=self.resource_name)
            return None

    def similar(self, node_id: str, limit: int = 10) -> List[Dict[str, Any]]:
        """Nodes nearest to a stored node's embedding (reference: the REST
        /similar endpoint, server_nornicdb.go). Empty when the node has no
        vector yet."""
        try:
            node = self.storage.get_node(node_id)
        except KeyError:
            return []
        emb = node.embedding or (
            node.chunk_embeddings[0] if node.chunk_embeddings else None)
        if emb is None:
            return []
        hits = self.vector_search_candidates(emb, limit + 1)
        out: List[Dict[str, Any]] = []
        for nid, score in hits:
            if nid == node_id:
                continue
            res = SearchResult(node_id=nid, score=score, vector_score=score)
            try:
                res.node = self.storage.get_node(nid)
            except KeyError:
                continue
            out.append(res.to_dict())
            if len(out) >= limit:
                break
        return out

    def vector_search_candidates(
        self, query_vec: Sequence[float], k: int = 10, exact: bool = False,
        lexical_doc_ids: Optional[Sequence[str]] = None,
    ) -> List[Tuple[str, float]]:
        """Raw vector candidates (reference: VectorSearchCandidates
        search.go:3045). Strategy: HNSW if built (unless exact), else the
        doc space's index. Cluster-routed indexes (IVF-HNSW) additionally
        take the BM25 top hits for hybrid probe selection
        (reference: hybrid_cluster_routing.go:248-256)."""
        with self._lock:
            hnsw = self.hnsw
            cagra = self.cagra
        if not exact:
            if lexical_doc_ids \
                    and hasattr(self.vectors, "_tiered_search_batch"):
                # beyond-HBM tier (ISSUE 17): hybrid lexical+semantic
                # cluster routing — the BM25 top docs bias the probe
                # set toward partitions the lexical half already ranked.
                # Direct (un-coalesced) call: probe hints are per-query
                # and cannot ride a shared micro-batch. None = plane
                # off/cold/degraded; fall through to the ladder below.
                out = self.vectors._tiered_search_batch(
                    np.asarray([query_vec], dtype=np.float32), k,
                    lex_hints=[list(lexical_doc_ids)])
                if out is not None:
                    _STRATEGY_C.labels("tiered_route").inc()
                    tier = _audit.consume_batch_tier()
                    _audit.record_served("vector",
                                         tier or "vector_tiered")
                    return out[0]
            if cagra is not None:
                # device graph walk, micro-batched: concurrent b=1
                # queries coalesce into one pow2-bucketed walk dispatch
                _STRATEGY_C.labels("cagra").inc()
                return self._vector_ride(query_vec, k)
            if hnsw is not None:
                _STRATEGY_C.labels("hnsw").inc()
                # host-resident graph index: the host tier by taxonomy
                _audit.record_served("vector", "host")
                return hnsw.search(query_vec, k)
        if lexical_doc_ids and hasattr(self.vectors, "route"):
            _STRATEGY_C.labels("ivf_route").inc()
            _audit.record_served("vector", "host")
            return self.vectors.search(query_vec, k,
                                       lexical_doc_ids=lexical_doc_ids)
        if hasattr(self.vectors, "search_batch"):
            if exact:
                # exact requests never ride the micro-batcher: its
                # dispatch re-reads self.cagra, so a concurrent graph
                # build could answer an exact request approximately.
                # Direct brute call (rare path: eval + exact=True).
                _STRATEGY_C.labels("exact").inc()
                _audit.record_served("vector", "vector_brute_f32")
                return self.vectors.search_batch(
                    np.asarray([query_vec], dtype=np.float32), k,
                    exact=True)[0]
            # micro-batched: concurrent singles ride one device call
            _STRATEGY_C.labels("brute").inc()
            return self._vector_ride(query_vec, k)
        _STRATEGY_C.labels("backend").inc()
        _audit.record_served("vector", "host")
        return self.vectors.search(query_vec, k)  # IVF backends

    def _vector_ride(self, query_vec, k: int):
        """One coalesced vector ride. The MicroBatcher stamps the
        serving tier (leader-consumed from the dispatch path) onto this
        rider's count/span; on the way out the answer is offered to the
        shadow-parity auditor with an exact-brute reference closure."""
        hits = self._microbatch.search(query_vec, k)
        if _audit.sampling_active():
            tier = _audit.last_served()
            if tier is not None and tier != "host":
                try:
                    qv = np.asarray(query_vec, dtype=np.float32)
                    vectors = self.vectors

                    def versions_now():
                        return {"brute_mutations":
                                getattr(vectors, "mutations", 0)}

                    # (id, score) pairs: exact tiers score TIE-AWARE
                    # rank parity (a padded-batch dispatch may permute
                    # rows within an exact score tie vs the b=1 replay)
                    _audit.maybe_sample(
                        "vector", tier,
                        [(i, float(s)) for i, s in hits],
                        k=min(10, k),
                        ref=lambda: [
                            (i, float(s)) for i, s in
                            vectors.search_batch(
                                qv[None, :], k, exact=True)[0]],
                        versions=versions_now(),
                        versions_now=versions_now,
                        query={"k": k})
                except Exception:  # noqa: BLE001
                    pass
        return hits

    def search(
        self,
        query: str = "",
        limit: int = 10,
        query_embedding: Optional[Sequence[float]] = None,
        mode: str = "hybrid",
        min_score: float = 0.0,
        enrich: bool = True,
        labels: Optional[Sequence[str]] = None,
        weights: Optional[Sequence[float]] = None,
    ) -> List[Dict[str, Any]]:
        """Hybrid search (reference: Service.Search search.go:2841):
        BM25 + vector candidate lists fused with (optionally weighted)
        RRF, enriched from storage. On large corpora the whole hybrid
        candidate stage — lexical scoring, vector scoring and the RRF
        fuse — runs as ONE compiled device program per coalesced batch
        (hybrid_fused.py); the host path below is the exact fallback
        and the small-corpus fast path. Results are cached by
        query+options (reference: search.go:2853-2856 cacheKey Get/Put)
        and invalidated on any index mutation. ``weights`` is the
        per-source (lexical, vector) RRF weighting of the reference's
        weighted fusion; None means (1.0, 1.0)."""
        self.stats.searches += 1
        # opt-in per-stage timing diagnostics (reference:
        # NORNICDB_SEARCH_DIAG_TIMINGS, server_nornicdb.go:282-286);
        # recorded on stats.last_timings for /status and log inspection.
        # Stale-timing clearing runs BEFORE the cache probe so a cache
        # hit can't serve timings from a prior diag run forever.
        from nornicdb_tpu.config import env_bool

        # deliberate per-query env read: the toggle must take effect on
        # the NEXT search (pinned by test_aux_cmds diag tests), and the
        # ~1 us read is noise against the ms-scale hybrid search it
        # gates — unlike the 50 us chain path the hot-path rule guards
        diag = env_bool("TPU_SEARCH_DIAG")  # lint: env-ok
        if not diag and self.stats.last_timings:
            self.stats.last_timings = {}  # never serve stale timings
        # explicit query embeddings are unhashable request-local state;
        # those requests bypass the cache (the reference keys only on
        # query text + options too)
        cache_key = None
        if query_embedding is None and self.reranker is None:
            cache_key = (query, limit, mode, min_score, enrich,
                         tuple(labels) if labels else None,
                         tuple(weights) if weights else None)
            cached = self._result_cache.get_hits(cache_key)
            if cached is not None:
                self.stats.cache_hits += 1
                _HYBRID_CACHED_SERVED.inc()
                # pre-bound child skips record_served; the per-tenant
                # request still counts the hit (ISSUE 18)
                _tenant.record_served("hybrid", "cached")
                return cached
            gen_at_miss = self._result_cache.generation
        timings: Dict[str, float] = {}
        t0 = time.perf_counter() if diag else 0.0
        overfetch = max(limit * 3, 30)
        bm25_hits: List[Tuple[str, float]] = []
        vec_hits: List[Tuple[str, float]] = []
        qv = None
        if mode in ("hybrid", "vector"):
            if query_embedding is not None:
                qv = np.asarray(query_embedding, dtype=np.float32)
            elif query.strip():
                with _span("hybrid.embed_query"):
                    qv = self._query_embedding(query)
            if diag:
                timings["embed_ms"] = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
        trio = None
        trio_eligible = (mode == "hybrid" and bool(query)
                         and qv is not None and len(self.vectors) > 0)
        if trio_eligible:
            # fused device path: concurrent hybrid searches coalesce
            # into one compiled BM25+vector+RRF dispatch. None = the
            # pipeline isn't (yet/any longer) eligible — host serves.
            trio = self._fused_hybrid_trio(query, qv, overfetch, weights)
            if trio is None:
                # a fused-eligible query served by the host hybrid
                # path: count the host tier so the mix stays truthful
                _audit.record_served("hybrid", "host")
        if trio is not None:
            bm25_hits, vec_hits = trio["lex"], trio["vec"]
            if diag:
                timings["fused_ms"] = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
        else:
            if mode in ("hybrid", "text") and query:
                t_lex = time.time()
                bm25_hits = self.bm25.search(query, overfetch)
                attach_span("lexical.score", t_lex, time.time())
            if diag:
                timings["bm25_ms"] = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
            if qv is not None and len(self.vectors) > 0:
                if trio_eligible:
                    # this query is already counted (hybrid host tier):
                    # the nested vector ride is a sub-dispatch, not a
                    # second served query — one query, one increment
                    with _audit.suppress_attribution():
                        vec_hits = self.vector_search_candidates(
                            qv, overfetch,
                            lexical_doc_ids=[d for d, _ in
                                             bm25_hits[:32]])
                else:
                    vec_hits = self.vector_search_candidates(
                        qv, overfetch,
                        lexical_doc_ids=[d for d, _ in bm25_hits[:32]],
                    )
            if diag:
                timings["vector_ms"] = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()

        if bm25_hits and vec_hits:
            # the fused trio already carries the device-fused ranking;
            # the host fuse is bit-compatible with it (rrf.py)
            if trio is not None:
                fused = trio["fused"]
            else:
                t_fuse = time.time()
                fused = rrf_fuse([bm25_hits, vec_hits],
                                 weights=list(weights) if weights else (),
                                 limit=overfetch)
                attach_span("fuse", t_fuse, time.time())
        elif bm25_hits:
            fused = bm25_hits[:overfetch]
        else:
            fused = vec_hits[:overfetch]
        if diag:
            timings["fuse_ms"] = (time.perf_counter() - t0) * 1e3
            t0 = time.perf_counter()

        t_rerank = time.time()
        bm = dict(bm25_hits)
        vs = dict(vec_hits)
        out: List[Dict[str, Any]] = []
        with _span("hybrid.hydrate"):
            for node_id, score in fused:
                # min_score filters on the raw similarity scores (cosine and/or
                # BM25), NOT the fused RRF value — fused magnitudes depend on
                # which lists fired and are not comparable across modes. A hit
                # survives if ANY of its raw scores clears the threshold (a
                # strong text match must not be vetoed by a negative cosine).
                v_sc, b_sc = vs.get(node_id), bm.get(node_id)
                gates = [g for g in (v_sc, b_sc) if g is not None]
                if gates and max(gates) < min_score:
                    continue
                res = SearchResult(
                    node_id=node_id,
                    score=score,
                    bm25_score=b_sc,
                    vector_score=v_sc,
                )
                if (enrich or labels) and self.storage is not None:
                    try:
                        node = self.storage.get_node(node_id)
                    except KeyError:
                        continue  # deleted since indexing; drop stale hit
                    if labels and not set(labels) & set(node.labels):
                        continue
                    if enrich:
                        res.node = node
                out.append(res.to_dict())
                if len(out) >= limit and self.reranker is None:
                    break
        if self.reranker is not None and out:
            # stage-2 rerank over the full fused overfetch, then cut
            # (reference: rerank.go after RRF). Pass the query embedding
            # already computed — the reranker must not re-embed.
            try:
                out = self.reranker.rerank(query, out, limit=limit,
                                           query_embedding=qv)
            except Exception:
                out = out[:limit]  # fail-open (reference: llm_rerank.go)
        attach_span("rerank", t_rerank, time.time(),
                    reranker=self.reranker is not None)
        if diag:
            timings["enrich_rerank_ms"] = (time.perf_counter() - t0) * 1e3
            self.stats.last_timings = timings
        out = out[:limit]
        if cache_key is not None:
            return self._result_cache.put_guarded(cache_key, out,
                                                  gen_at_miss)
        return out
