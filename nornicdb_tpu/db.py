"""DB facade: Open/Store/Recall/Cypher over the composed engine chain.

Reference: pkg/nornicdb/db.go:742 ``Open`` and the public API surface
(Store :1951, Recall :2107, Remember :2026, Link :2251, Neighbors :2299,
Forget :2378, Cypher :2222). Round-1 facade — search/cypher services are
wired in as those layers land.
"""

from __future__ import annotations

import os
import threading
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

from nornicdb_tpu.storage import (
    AsyncEngine,
    DurableEngine,
    Direction,
    Edge,
    Engine,
    ListenableEngine,
    MemoryEngine,
    MutationListener,
    NamespacedEngine,
    Node,
)


class _QdrantInvalidationListener(MutationListener):
    """Routes node mutations from ANY surface into the qdrant layer's
    cache invalidation (qdrant.py _on_external_mutation — the layer's
    own writes are filtered out there by a thread-local guard)."""

    def __init__(self, compat):
        self._compat = compat

    def on_node_upsert(self, node: Node) -> None:
        self._compat._on_external_mutation(node.id)

    def on_node_delete(self, node_id: str) -> None:
        self._compat._on_external_mutation(node_id)


class DB:
    """One logical NornicDB-style database instance."""

    def __init__(
        self,
        data_dir: Optional[str] = None,
        database: str = "neo4j",
        async_writes: bool = False,
        sync_every_write: bool = False,
        embedder: Optional[Any] = None,
        auto_embed: bool = True,
        engine: str = "auto",  # auto | native | python | memory
        replication: Optional[Any] = None,  # ReplicationConfig
        passphrase: Optional[str] = None,  # at-rest AES-256-GCM encryption
    ):
        # engine chain: Disk/Durable/Memory -> [Async] -> Namespaced ->
        # Listenable (reference chain order: db.go:742-947; the listener
        # layer sits on top so mutation callbacks carry LOGICAL node ids)
        if engine not in ("auto", "native", "python", "memory"):
            raise ValueError(f"unknown engine {engine!r}")
        if engine in ("native", "python") and not data_dir:
            raise ValueError(f"engine={engine!r} requires data_dir")
        self._data_dir = data_dir if engine != "memory" else None
        if data_dir and engine != "memory":
            # at-rest encryption: PBKDF2-derived key + salt file in the
            # data dir (reference: db.go:776-805 DeriveKey + salt)
            from nornicdb_tpu.encryption import make_encryptor

            encryptor = make_encryptor(passphrase, data_dir)
            if engine == "python":
                base: Engine = DurableEngine(
                    data_dir, sync_every_write=sync_every_write,
                    encryptor=encryptor,
                )
            elif engine == "native":
                from nornicdb_tpu.storage.disk import DiskEngine

                base = DiskEngine(data_dir, sync_every_write=sync_every_write,
                                  encryptor=encryptor)
            else:
                from nornicdb_tpu.storage import make_persistent_engine

                base = make_persistent_engine(
                    data_dir, sync_every_write=sync_every_write,
                    encryptor=encryptor,
                )
        else:
            base = MemoryEngine()
        self._base = base
        chain: Engine = base
        if async_writes:
            chain = AsyncEngine(chain)
        self.replicator = None
        self._cluster_transport = None
        if replication is not None and replication.mode != "standalone":
            try:
                chain = self._enable_replication(chain, replication)
            except Exception:
                # don't leak the already-open engine chain (file locks,
                # async flush thread) when replication wiring fails
                chain.close()
                raise
        self._chain = chain  # pre-namespace engine: multidb roots here
        self._listenable = ListenableEngine(NamespacedEngine(chain, database))
        self.storage = self._listenable
        self.database = database
        self._lock = threading.Lock()
        self._closed = False
        self._db_manager = None

        # lazily-built services (per logical DB)
        self._executor = None
        self._search = None
        if embedder is None:
            try:
                embedder = self._default_embedder()
            except Exception:
                # don't leak the already-open engine chain (file locks,
                # async flush thread) when e.g. the embedder sidecar is
                # corrupt — same discipline as the replication path above
                self._listenable.close()
                raise
        self._embedder = embedder
        self._embed_queue = None
        self._decay = None
        self._temporal = None
        self._inference = None
        if auto_embed:
            self._start_embed_queue()
        rep = getattr(self, "_deferred_rep_start", None)
        if rep is not None:
            self._deferred_rep_start = None
            rep.start()

    def _default_embedder(self):
        """Default local embedder (reference default: local embedding
        always on, embed.go — a real bge-m3 via llama.cpp). Here: the
        committed contrastively-trained mini encoder (models/pretrain.py)
        behind an LRU; HashEmbedder when the checkpoint is absent or
        forced (NORNICDB_TPU_EMBEDDER=hash).

        The chosen embedder identity (kind + dims) is PERSISTED with
        disk-backed stores (``embedder.json`` sidecar) and honored on
        reopen, so an existing database keeps its embedding space even
        when the default changes across versions — mixing spaces would
        silently break recall (advisor r3: db.py:117)."""
        import io as _io  # builtins.open is shadowed by module-level open()
        import json as _json
        import logging

        from nornicdb_tpu.embed.embedder import CachedEmbedder, HashEmbedder

        log = logging.getLogger("nornicdb_tpu.db")
        sidecar = (
            os.path.join(self._data_dir, "embedder.json")
            if self._data_dir else None
        )
        recorded = None
        sidecar_unreadable = False
        if sidecar and os.path.exists(sidecar):
            try:
                with _io.open(sidecar, encoding="utf-8") as f:
                    recorded = _json.load(f)
            except Exception as exc:
                # a corrupt sidecar must NOT be treated as "no recorded
                # identity": the default embedder could then write new
                # vectors into a different space before anyone notices.
                # Fail loudly (the data damage would be done by the time
                # a log line is read); NORNICDB_TPU_EMBEDDER=hash stays
                # available as the explicit escape hatch.
                sidecar_unreadable = True
                if os.environ.get("NORNICDB_TPU_EMBEDDER", "") != "hash":
                    raise ValueError(
                        f"embedder sidecar {sidecar} is unreadable "
                        f"({exc}); fix or remove the file to re-bind the "
                        "store's embedding space, or force "
                        "NORNICDB_TPU_EMBEDDER=hash to open anyway"
                    ) from exc
                log.error(
                    "embedder sidecar %s is unreadable (%s); forced hash "
                    "embedder is active — the recorded identity is NOT "
                    "re-written", sidecar, exc,
                )

        from nornicdb_tpu.models.hf_import import default_model_dir

        def build(kind):
            if kind == "hf":
                from nornicdb_tpu.models.hf_import import HFEncoderEmbedder

                d = default_model_dir()
                if d is None:
                    raise FileNotFoundError(
                        "NORNICDB_TPU_MODEL_DIR not set or not a model "
                        "dir, but the store was created with an "
                        "imported-weights embedder")
                return HFEncoderEmbedder(d)
            if kind == "encoder-mini":
                from nornicdb_tpu.models.pretrain import load_default_embedder

                inner = load_default_embedder()
                if inner is None:
                    raise FileNotFoundError("encoder checkpoint missing")
                return inner
            return HashEmbedder(
                # recorded dims only apply if the store really was hash:
                # another kind's dims would silently change hash's space
                dims=int(recorded.get("dims", 256))
                if recorded and recorded.get("kind") == "hash" else 256
            )

        env_force = os.environ.get("NORNICDB_TPU_EMBEDDER", "")
        if env_force == "hash":
            # the explicit choice ALWAYS wins: no recorded preference
            # may route around it
            want = "hash"
        elif default_model_dir() is not None:
            want = "hf"  # real imported weights beat the mini encoder
        else:
            want = "encoder-mini"
        kind = want
        if recorded and env_force != "hash":
            kind = recorded.get("kind", want)
        try:
            inner = build(kind)
        except FileNotFoundError:
            # no checkpoint / model directory: the one case the hash
            # embedder stands in for. Anything else — a JAX backend that
            # will not initialise included — is the caller's to see.
            log.warning(
                "default embedder %r unavailable; falling back to "
                "hash embedder — embeddings written now will be in a "
                "different space", kind,
            )
            kind = "hash"
            inner = build("hash")
        if recorded and recorded.get("kind") != kind:
            log.warning(
                "store was created with embedder %r but %r is active; "
                "existing embeddings are in the recorded space — reindex "
                "to migrate", recorded.get("kind"), kind,
            )
        if sidecar and recorded is None and not sidecar_unreadable:
            try:
                with _io.open(sidecar, "w", encoding="utf-8") as f:
                    _json.dump({"kind": kind, "dims": inner.dims}, f)
            except OSError:
                pass
        return CachedEmbedder(inner)

    def _enable_replication(self, chain: Engine, cfg: Any) -> Engine:
        """Insert the ReplicatedEngine into the chain (reference:
        maybeEnableReplication, db.go:931,1261 — chain position
        …→[Async]→[Replicated]→Namespaced). HA modes stream the base
        WALEngine's log; Raft applies committed entries to the chain."""
        from nornicdb_tpu.replication import (
            ClusterTransport,
            HAPrimary,
            HAStandby,
            RaftNode,
            ReplicatedEngine,
        )
        from nornicdb_tpu.replication.replicator import decode_op_args
        from nornicdb_tpu.storage.wal_engine import WALEngine

        if getattr(cfg, "data_listen", None) is not None:
            # two-plane endpoint (ISSUE 16): heartbeats/fences on the
            # control channel, WAL batches and snapshot ships on a
            # separate bulk socket so replication volume never delays
            # failure detection
            from nornicdb_tpu.replication.transport import DualPlaneTransport

            transport = DualPlaneTransport(
                cfg.node_id, cfg.listen, cfg.data_listen)
        else:
            transport = ClusterTransport(cfg.node_id, cfg.listen)
        transport.start()
        self._cluster_transport = transport
        if cfg.mode == "multi_region":
            from nornicdb_tpu.replication import MultiRegionNode

            def mr_apply_fn(op, data, _chain=chain):
                getattr(_chain, op)(*decode_op_args(op, data))

            rep = MultiRegionNode(transport, cfg, mr_apply_fn)
            rep.start()
            self.replicator = rep
            return ReplicatedEngine(chain, rep)
        if cfg.mode == "ha_standby":
            if not isinstance(self._base, WALEngine):
                transport.close()
                raise ValueError(
                    f"replication mode {cfg.mode!r} requires a WAL-backed "
                    "engine (open with data_dir and engine='python')"
                )
            if not isinstance(chain, WALEngine):
                # HA replicators write to the base WALEngine directly;
                # an AsyncEngine overlay would be silently bypassed
                transport.close()
                raise ValueError(
                    "async_writes cannot be combined with HA replication "
                    "(writes route through the WAL primary directly)"
                )
            primary_cls = getattr(cfg, "primary_cls", None) or HAPrimary
            standby_cls = getattr(cfg, "standby_cls", None) or HAStandby
            if cfg.ha_role == "primary":
                rep = primary_cls(self._base, transport, cfg)
                rep.start()
            else:
                rep = standby_cls(
                    self._base, transport, cfg,
                    primary_addr=cfg.primary_addr,
                    on_promote=getattr(cfg, "on_promote", None),
                )
                # monitor start is DEFERRED to the end of __init__: the
                # failover clock must not tick while this facade is
                # still loading its embedder/services — a standby that
                # auto-promotes because its own open was slow fences
                # the healthy primary (split-brain at boot)
                self._deferred_rep_start = rep
        elif cfg.mode == "raft":
            def apply_fn(op, data, _chain=chain):
                getattr(_chain, op)(*decode_op_args(op, data))

            rep = RaftNode(transport, cfg, apply_fn)
            rep.start()
        else:
            transport.close()
            raise ValueError(f"unknown replication mode {cfg.mode!r}")
        self.replicator = rep
        return ReplicatedEngine(chain, rep)

    # -- service accessors ----------------------------------------------

    @property
    def executor(self):
        if self._executor is None:
            from nornicdb_tpu.query.executor import CypherExecutor

            self._executor = CypherExecutor(self.storage)
            if self._search is not None:
                self._executor.set_search_service(self._search)
            # Writes arriving outside Cypher (Store/Link, embed queue,
            # replication apply) must invalidate the executor's read
            # cache + columnar snapshot (reference: cache_policy.go).
            ex = self._executor

            class _CacheInvalidator(MutationListener):
                def on_node_upsert(self, node):
                    ex.on_external_node_upsert(node)

                def on_node_delete(self, node_id):
                    ex.on_external_mutation()

                def on_edge_upsert(self, edge):
                    ex.on_external_mutation()

                def on_edge_delete(self, edge_id):
                    ex.on_external_mutation()

                def on_bulk_change(self):
                    ex.on_external_mutation()

            self._listenable.add_listener(_CacheInvalidator())
        return self._executor

    @property
    def search(self):
        if self._search is None:
            from nornicdb_tpu.search.service import SearchService

            import os as _os

            svc = SearchService(
                self.storage, embedder=self._embedder,
                persist_dir=(_os.path.join(self._data_dir, "search")
                             if self._data_dir else None),
                # read replicas tag their service (read_fleet.py sets
                # _search_resource_name before first access) so an
                # in-process fleet's per-node gauges never collide
                resource_name=getattr(self, "_search_resource_name",
                                      None),
            )
            # publish BEFORE backfill so a concurrently-finishing embed
            # lands via _on_embedded instead of being dropped (index_node
            # is idempotent, double-index is harmless)
            self._search = svc
            try:
                svc.build_indexes()  # nodes stored before first search
            except BaseException:
                # un-publish: a half-built index must not be served for
                # the life of the process; next access retries backfill
                self._search = None
                raise
            if self._executor is not None:
                self._executor.set_search_service(self._search)
        return self._search

    @property
    def qdrant_compat(self):
        """Single shared Qdrant translation layer per DB — the REST and
        gRPC surfaces must share one per-collection index cache or
        cross-surface writes go stale."""
        if getattr(self, "_qdrant_compat", None) is None:
            from nornicdb_tpu.api.qdrant import QdrantCompat

            compat = QdrantCompat(self.storage)
            # qdrant points are ordinary storage nodes: a Cypher
            # SET/DELETE (or GDPR delete) over any surface must
            # invalidate the per-collection index + search caches, not
            # just qdrant's own ops
            listener = _QdrantInvalidationListener(compat)
            if hasattr(self.storage, "add_listener"):
                self.storage.add_listener(listener)
            self._qdrant_compat = compat
        return self._qdrant_compat

    @property
    def decay(self):
        if self._decay is None:
            from nornicdb_tpu.decay import DecayManager

            self._decay = DecayManager(self.storage)
        return self._decay

    @property
    def temporal(self):
        if self._temporal is None:
            from nornicdb_tpu.temporal import TemporalTracker

            self._temporal = TemporalTracker()
        return self._temporal

    @property
    def inference(self):
        if self._inference is None:
            from nornicdb_tpu.inference import EvidenceBuffer, InferenceEngine

            # co-access edges materialize only after accumulated evidence
            # (reference wiring: evidence buffer ahead of Auto-TLP edges)
            self._inference = InferenceEngine(
                self.storage, self.search, evidence=EvidenceBuffer())
        return self._inference

    def _start_embed_queue(self):
        from nornicdb_tpu.embed.queue import EmbedQueue

        self._embed_queue = EmbedQueue(
            self.storage, self._embedder, on_embedded=self._on_embedded,
            has_vector=lambda nid: (self._search is not None
                                    and nid in self._search.vectors),
        )
        self._listenable.add_listener(self._embed_queue)
        self._embed_queue.start()

    def _on_embedded(self, node: Node) -> None:
        if self._search is not None:
            self._search.index_node(node)

    # -- public API ------------------------------------------------------

    def store(
        self,
        content: str,
        labels: Optional[Sequence[str]] = None,
        properties: Optional[Dict[str, Any]] = None,
        node_id: Optional[str] = None,
        embedding: Optional[List[float]] = None,
        auto_link: bool = False,
    ) -> Node:
        """Store a memory node (reference: db.go:1951 Store)."""
        nid = node_id or str(uuid.uuid4())
        props = dict(properties or {})
        props.setdefault("content", content)
        node = Node(
            id=nid,
            labels=list(labels or ["Memory"]),
            properties=props,
            embedding=embedding,
        )
        self.storage.create_node(node)
        if embedding is not None and self._search is not None:
            # explicit-embedding stores bypass the embed queue (its
            # listener only enqueues un-embedded nodes), so an already
            # built search service must index them here — otherwise a
            # node stored after the first recall() is invisible to
            # every vector surface (recall/similar/graph_vector_search).
            # Best-effort: the node is durably stored either way, and a
            # dims-mismatched explicit embedding was never indexable
            # (it stays recallable by text, exactly as before).
            try:
                self._search.index_node(self.storage.get_node(nid))
            except Exception:  # noqa: BLE001
                pass
        if auto_link and embedding is not None:
            self.inference.on_store(node)
        return self.storage.get_node(nid)

    def store_batch(
        self,
        contents: Sequence[str],
        embeddings: Any,
        node_ids: Optional[Sequence[str]] = None,
        labels: Optional[Sequence[str]] = None,
    ) -> List[str]:
        """Bulk ``store`` with the caller's embeddings: one node per
        content (``properties={"content": ...}``, the same ``labels``
        for all), created under one hold of the engine's lock
        (``Engine.create_nodes``) and indexed in one call
        (``SearchService.index_batch``), with no per-node listener
        event, so nothing is embedded or indexed a second time.
        ``embeddings`` is a float32 ``[n, dims]`` matrix; the vectors go
        to the index and its snapshot, and the nodes carry no
        ``embedding`` list (33 KB a node as Python floats at 1,024
        dims). Returns the ids.

        Part of the contract: the load ends with ``gc.freeze()``
        (``search.service.gc_paused``), which takes what it built, and
        whatever else is alive in the process at that moment, out of the
        cyclic collector's later passes for good. A server calls this
        to fill a store that stays; a process that loads and drops
        stores over and over should ``gc.unfreeze()`` between them."""
        import numpy as np

        from nornicdb_tpu.search.service import extract_text, gc_paused

        labels = list(labels or ["Memory"])
        if any(lbl.startswith("_") for lbl in labels):
            raise ValueError("store_batch is not for system-owned labels")
        contents = list(contents)
        ids = ([str(uuid.uuid4()) for _ in contents]
               if node_ids is None else list(node_ids))
        embeddings = np.asarray(embeddings, dtype=np.float32)
        if not (len(ids) == len(contents) == len(embeddings)):
            raise ValueError(
                f"store_batch: {len(ids)} ids, {len(contents)} contents, "
                f"embeddings {embeddings.shape}")
        search = self.search     # built, and back-filled, before the load
        with gc_paused(freeze=True):
            nodes = [Node(id=nid, labels=list(labels),
                          properties={"content": content})
                     for nid, content in zip(ids, contents)]
            self.storage.create_nodes(nodes)
            search.index_batch(ids, [extract_text(n) for n in nodes],
                               embeddings)
        return ids

    def recall(self, query: str, limit: int = 10, **kw) -> List[Dict[str, Any]]:
        """Hybrid search over stored memories (reference: db.go:2107 Recall)."""
        return self.search.search(query, limit=limit, **kw)

    def remember(self, node_id: str) -> Node:
        """Fetch a node and record the access for decay/temporal tracking;
        repeated co-access accumulates evidence toward inferred edges
        (reference: db.go:2026 Remember + inference.OnAccess :778)."""
        node = self.storage.get_node(node_id)
        self.decay.record_access(node_id)
        self.temporal.record_access(node_id)
        # evidence-gated co-access inference. Only once the inference
        # engine exists (store/auto-link path created it) — building the
        # whole search stack as a side effect of a read would surprise
        # pure-KV users on large stores.
        if self._inference is not None:
            try:
                self._inference.on_access(self._temporal, node_id)
            except Exception:
                pass  # inference must never fail a read
        return node

    def link(
        self,
        from_id: str,
        to_id: str,
        rel_type: str = "RELATES_TO",
        properties: Optional[Dict[str, Any]] = None,
        edge_id: Optional[str] = None,
    ) -> Edge:
        eid = edge_id or str(uuid.uuid4())
        edge = Edge(
            id=eid,
            type=rel_type,
            start_node=from_id,
            end_node=to_id,
            properties=dict(properties or {}),
        )
        self.storage.create_edge(edge)
        return self.storage.get_edge(eid)

    def neighbors(self, node_id: str, direction: str = Direction.BOTH) -> List[Node]:
        ids = self.storage.neighbors(node_id, direction)
        return [n for n in self.storage.batch_get_nodes(ids) if n is not None]

    def forget(self, node_id: str) -> None:
        self.storage.delete_node(node_id)
        if self._search is not None:
            self._search.remove_node(node_id)

    def cypher(
        self, query: str, params: Optional[Dict[str, Any]] = None
    ) -> "Any":
        """Execute a Cypher query (reference: db.go:2222 Cypher)."""
        return self.executor.execute(query, params or {})

    def graph_vector_search(
        self,
        anchor_id: str,
        hops: Sequence[Any],
        query_vector: Sequence[float],
        k: int = 10,
    ) -> List[Tuple[str, float]]:
        """Fused graph+vector query (the scenario-frontier workload of
        ROADMAP item 5): expand ``hops`` — an (etype, direction)
        sequence, 1 or 2 stages; a bare string means outgoing — from
        the anchor node, then rank the DISTINCT frontier nodes by
        cosine similarity to ``query_vector`` over the search service's
        vector index. Top-k ``(node_id, score)``, score descending.

        With the device graph plane gated on (``NORNICDB_GRAPH_DEVICE``)
        the traversal, frontier dedup, vector gather, scoring and top-k
        run as ONE compiled dispatch; any freshness gap or gate-off
        serves the identical-contract host fallback instead."""
        import numpy as np

        ex = self.executor
        cat = ex.columnar
        hops_n: List[Tuple[str, str]] = []
        for h in hops:
            if isinstance(h, str):
                hops_n.append((h, "out"))
            elif isinstance(h, (list, tuple)) and len(h) == 2:
                etype, direction = h
                if direction not in ("out", "in"):
                    raise ValueError(f"bad hop direction {direction!r}")
                hops_n.append((str(etype), direction))
            else:
                raise ValueError(
                    "each hop must be a relationship type or a "
                    "[type, 'in'|'out'] pair")
        if not hops_n or len(hops_n) > 2:
            raise ValueError("graph_vector_search supports 1 or 2 hops")
        row = cat.node_row(anchor_id)
        if row is None:
            return []
        q = np.asarray(query_vector, dtype=np.float32)
        if q.ndim != 1 or q.size == 0:
            raise ValueError("query_vector must be a flat float vector")
        index = self.search.vectors
        dims = getattr(index, "dims", None)
        if dims and q.shape[0] != dims:
            raise ValueError(
                f"query_vector has {q.shape[0]} dims, index has {dims}")
        q = q[None, :]
        plane = ex.device_graph
        from nornicdb_tpu.obs import audit as _audit
        import time as _time

        t0 = _time.time()
        hits = plane.traverse_rank([row], hops_n, q, k, index)
        if hits is None:
            hits = plane.traverse_rank_host([row], hops_n, q, k, index)
            _audit.record_served("graph", "host",
                                 seconds=_time.time() - t0)
        else:
            _audit.record_served("graph", "graph_traverse_rank_device",
                                 seconds=_time.time() - t0)
            if _audit.sampling_active():
                # shadow-parity: replay the identical-contract host
                # fallback on the audit worker and compare row ids

                def versions_now():
                    return {"catalog_version": cat.version,
                            "index_mutations":
                            getattr(index, "mutations", 0)}

                _audit.maybe_sample(
                    "graph", "graph_traverse_rank_device",
                    [r for r, _ in hits[0]], k=min(10, k),
                    ref=lambda: [r for r, _ in plane.traverse_rank_host(
                        [row], hops_n, q, k, index)[0]],
                    versions=versions_now(), versions_now=versions_now,
                    query={"anchor": anchor_id, "hops": hops_n, "k": k})
        nodes = cat.nodes()
        return [(nodes[r].id, s) for r, s in hits[0]]

    def multidb_manager(self, max_databases: int = 64):
        """Lazily-built multi-database manager rooted on the same engine
        chain this facade namespaces — CREATE/DROP DATABASE and per-DB
        storage views share durability with the default database
        (reference: cmd wires pkg/multidb into every server surface)."""
        with self._lock:
            if self._db_manager is None:
                from nornicdb_tpu.multidb import DatabaseManager

                self._db_manager = DatabaseManager(
                    self._chain, default_database=self.database,
                    max_databases=max_databases)
            return self._db_manager

    def flush(self) -> None:
        if self._embed_queue is not None:
            self._embed_queue.drain()
        self.storage.flush()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        if self._embed_queue is not None:
            self._embed_queue.stop()
        if self._search is not None:
            self._search.close()  # final index snapshot (search.go:496)
        if self._decay is not None:
            self._decay.stop()
        if self.replicator is not None:
            self.replicator.close()
        if self._cluster_transport is not None:
            self._cluster_transport.close()
        self.storage.close()

    def __enter__(self) -> "DB":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def open(data_dir: Optional[str] = None, **kw) -> DB:  # noqa: A001
    """Open a database (reference: pkg/nornicdb/db.go:742 Open)."""
    from nornicdb_tpu.jaxenv import ensure_compile_cache

    ensure_compile_cache()
    return DB(data_dir=data_dir, **kw)
