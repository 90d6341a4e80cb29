"""Background embed queue: embeds un-embedded nodes and triggers indexing.

Reference: pkg/nornicdb/embed_queue.go — ``EmbedWorker`` (:21), batch
processing with retry (:498), debounced k-means/clustering trigger (:330),
periodic rescan (15 min), text assembly (:886 buildEmbeddingText).
Implements the MutationListener hook so the ListenableEngine feeds it
(reference wiring: db.go:1076-1080).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, List, Optional

from nornicdb_tpu.obs import REGISTRY
from nornicdb_tpu.obs.tracing import Span, span as _span, trace as _trace
from nornicdb_tpu.storage.types import Engine, MutationListener, Node

logger = logging.getLogger(__name__)

# where the worker's wall time goes: the phases of a batch (each the
# summed duration of that batch's `embed.<phase>` spans, `other` what
# the batch's root holds beside them) and `starved`, blocked on an
# empty queue. All phases together are the worker's wall time.
_WORKER_S = REGISTRY.counter(
    "nornicdb_embed_worker_seconds_total",
    "Embed worker wall time by phase (load, encode, chunks, store, "
    "publish, other, starved)", labels=("phase",))
_BATCHES_C = REGISTRY.counter(
    "nornicdb_embed_batches_total", "Batches the embed worker processed")


def _account_batch(root) -> None:
    """Feed the phase counter from a finished ``embed.batch`` root: each
    direct child ``embed.<phase>`` adds its own duration, so the span and
    the counter share one timing. Nothing while telemetry is off."""
    if not isinstance(root, Span) or root.t1 is None:
        return
    covered = 0.0
    for child in root.children:
        seconds = child.t1 - child.t0
        covered += seconds
        _WORKER_S.labels(child.name.partition(".")[2]).inc(seconds)
    _WORKER_S.labels("other").inc(max(root.t1 - root.t0 - covered, 0.0))
    _BATCHES_C.inc()

CHUNK_THRESHOLD_CHARS = 2000  # texts longer than this get chunk embeddings


def build_embedding_text(node: Node) -> str:
    """Reference: buildEmbeddingText (embed_queue.go:886)."""
    from nornicdb_tpu.search.service import extract_text

    return extract_text(node)


def embed_exempt(node: Node) -> bool:
    """System-owned nodes the queue must never embed: any label starting
    with ``_`` (Qdrant collections/points, internal meta). The Qdrant
    surface's vectors are client-authoritative (embedding-ownership
    rule, reference pkg/qdrantgrpc COMPAT.md:12-14)."""
    return any(lbl.startswith("_") for lbl in node.labels)


class EmbedQueue(MutationListener):
    def __init__(
        self,
        storage: Engine,
        embedder,
        on_embedded: Optional[Callable[[Node], None]] = None,
        batch_size: int = 16,
        max_retries: int = 3,
        rescan_interval_s: float = 900.0,
        cluster_debounce_s: float = 30.0,
        on_cluster: Optional[Callable[[], None]] = None,
        has_vector: Optional[Callable[[str], bool]] = None,
    ):
        self.storage = storage
        self.embedder = embedder
        self.on_embedded = on_embedded
        # a node whose vector lives in the search index alone (a bulk
        # load with the caller's embeddings, ``DB.store_batch``) has no
        # ``embedding`` list: the rescan must not take it for a miss
        self.has_vector = has_vector
        self.batch_size = batch_size
        self.max_retries = max_retries
        self.rescan_interval_s = rescan_interval_s
        self.cluster_debounce_s = cluster_debounce_s
        self.on_cluster = on_cluster
        self._q: "queue.Queue[Optional[str]]" = queue.Queue()
        self._pending = set()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self._rescanner: Optional[threading.Thread] = None
        self._cluster_timer: Optional[threading.Timer] = None
        self.embedded_count = 0
        self.failed_count = 0
        # seconds the worker was blocked on the queue since its last batch
        self._starved_s = 0.0

    # -- MutationListener ------------------------------------------------

    def on_node_upsert(self, node: Node) -> None:
        if (
            node.embedding is None
            and not embed_exempt(node)
            and build_embedding_text(node)
        ):
            self.enqueue(node.id)

    def on_node_delete(self, node_id: str) -> None:
        with self._lock:
            self._pending.discard(node_id)

    # -- queue -----------------------------------------------------------

    def enqueue(self, node_id: str) -> None:
        with self._lock:
            if node_id in self._pending:
                return
            self._pending.add(node_id)
        self._q.put(node_id)

    def start(self) -> None:
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._run, name="embed-queue", daemon=True
            )
            self._worker.start()
        if self._rescanner is None and self.rescan_interval_s > 0:
            self._rescanner = threading.Thread(
                target=self._rescan_loop, name="embed-rescan", daemon=True
            )
            self._rescanner.start()

    def stop(self) -> None:
        self._stop.set()
        self._q.put(None)
        if self._worker is not None:
            self._worker.join(timeout=10)
        if self._cluster_timer is not None:
            self._cluster_timer.cancel()

    def drain(self, timeout_s: float = 60.0) -> None:
        """Block until all currently-pending nodes are embedded."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._lock:
                if not self._pending:
                    return
            time.sleep(0.02)

    # -- worker ----------------------------------------------------------

    def _run(self) -> None:
        # background maintenance lane (ISSUE 15): embedding catch-up
        # work seals behind interactive traffic in shared coalescers
        from nornicdb_tpu import admission as _adm

        _adm.lane_scope(_adm.LANE_BACKGROUND).__enter__()
        while not self._stop.is_set():
            batch: List[str] = []
            t_wait = time.perf_counter()
            try:
                item = self._q.get(timeout=0.25)
            except queue.Empty:
                continue
            finally:
                waited = time.perf_counter() - t_wait
                self._starved_s += waited
                _WORKER_S.labels("starved").inc(waited)
            if item is None:
                break
            batch.append(item)
            while len(batch) < self.batch_size:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    self._stop.set()
                    break
                batch.append(nxt)
            try:
                self._process_batch(batch)
            except Exception:
                logger.exception("embed batch failed")

    def _process_batch(self, node_ids: List[str]) -> None:
        """One batch is one root span ``embed.batch`` (``/admin/traces``,
        and ``nornic:embed.batch`` in a profiler trace) whose children
        are its phases; the phase counter is fed from the same spans."""
        starved, self._starved_s = self._starved_s, 0.0
        root = None
        try:
            with _trace("embed.batch", rows=len(node_ids),
                        starved_ms=round(starved * 1e3, 3)) as root:
                self._embed_and_store(node_ids)
        finally:
            _account_batch(root)

    def _embed_and_store(self, node_ids: List[str]) -> None:
        nodes = []
        with _span("embed.load"):
            for nid in node_ids:
                try:
                    node = self.storage.get_node(nid)
                except KeyError:
                    with self._lock:
                        self._pending.discard(nid)
                    continue
                if node.embedding is not None:
                    with self._lock:
                        self._pending.discard(nid)
                    continue
                nodes.append(node)
            texts = [build_embedding_text(n) for n in nodes]
        if not nodes:
            return
        with _span("embed.encode"):
            vectors = self._embed_with_retry(texts)
        if vectors is None:
            self.failed_count += len(nodes)
            for n in nodes:
                with self._lock:
                    self._pending.discard(n.id)
            return
        for node, text, vec in zip(nodes, texts, vectors):
            # per-node isolation: one failing write must not wedge the rest
            # of the batch in _pending (they'd never re-enqueue)
            try:
                chunk_vectors = None
                if len(text) > CHUNK_THRESHOLD_CHARS and hasattr(
                    self.embedder, "embed_chunks"
                ):
                    with _span("embed.chunks"):
                        try:
                            chunk_vectors = self.embedder.embed_chunks(text)
                        except Exception:
                            logger.exception(
                                "chunk embed failed for %s", node.id)
                with _span("embed.store"):
                    try:
                        fresh = self.storage.get_node(node.id)
                    except KeyError:
                        continue
                    fresh.embedding = list(vec)
                    if chunk_vectors is not None:
                        fresh.chunk_embeddings = chunk_vectors
                    try:
                        self.storage.update_node(fresh)
                    except KeyError:
                        continue  # deleted concurrently
                self.embedded_count += 1
                if self.on_embedded is not None:
                    with _span("embed.publish"):
                        try:
                            self.on_embedded(fresh)
                        except Exception:
                            logger.exception("on_embedded callback failed")
            except Exception:
                logger.exception("embed write failed for %s", node.id)
                self.failed_count += 1
            finally:
                with self._lock:
                    self._pending.discard(node.id)
        self._schedule_clustering()

    def _embed_with_retry(self, texts: List[str]):
        """Reference: embedBatchWithRetry + llama crash recovery
        (local_gguf.go:202-254) — retries with backoff, fail-open."""
        delay = 0.1
        for attempt in range(self.max_retries):
            try:
                return self.embedder.embed_batch(texts)
            except Exception:
                logger.exception("embed attempt %d failed", attempt + 1)
                if attempt + 1 < self.max_retries:  # no sleep after the last try
                    time.sleep(delay)
                    delay *= 4
        return None

    # -- clustering debounce + rescan -------------------------------------

    def _schedule_clustering(self) -> None:
        """Debounced clustering trigger (reference:
        scheduleClusteringDebounced, embed_queue.go:330)."""
        if self.on_cluster is None:
            return
        with self._lock:
            if self._cluster_timer is not None:
                self._cluster_timer.cancel()
            self._cluster_timer = threading.Timer(
                self.cluster_debounce_s, self._fire_cluster
            )
            self._cluster_timer.daemon = True
            self._cluster_timer.start()

    def _fire_cluster(self) -> None:
        try:
            self.on_cluster()
        except Exception:
            logger.exception("clustering trigger failed")

    def _rescan_loop(self) -> None:
        """Periodic sweep for nodes that missed the event path
        (reference: 15-min rescan, embed_queue.go)."""
        while not self._stop.wait(self.rescan_interval_s):
            try:
                for node in self.storage.all_nodes():
                    if (
                        node.embedding is None
                        and not embed_exempt(node)
                        and build_embedding_text(node)
                        and not (self.has_vector is not None
                                 and self.has_vector(node.id))
                    ):
                        self.enqueue(node.id)
            except Exception:
                logger.exception("rescan failed")
