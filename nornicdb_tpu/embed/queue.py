"""Background embed queue: embeds un-embedded nodes and triggers indexing.

Reference: pkg/nornicdb/embed_queue.go — ``EmbedWorker`` (:21), batch
processing with retry (:498), debounced k-means/clustering trigger (:330),
periodic rescan (15 min), text assembly (:886 buildEmbeddingText).
Implements the MutationListener hook so the ListenableEngine feeds it
(reference wiring: db.go:1076-1080).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from itertools import islice
from typing import Callable, Dict, List, Optional, Tuple

from nornicdb_tpu.embed.embedder import FULL_BATCH_MIN_WIDTH, width_bucket
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
# how often sealing by length engages: `arrival` rows left in arrival
# order (nothing older stayed behind), `by_length` rows were picked ahead
# of an older document for their length
_SEALED_C = REGISTRY.counter(
    "nornicdb_embed_sealed_rows_total",
    "Rows the embed worker sealed into batches: in arrival order, or "
    "picked by length ahead of older documents", labels=("order",))
_WAIT_H = REGISTRY.histogram(
    "nornicdb_embed_queue_wait_seconds",
    "Enqueue of a node to its on_embedded hook returned",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 20.0, 30.0, 60.0, 120.0))

# a seal looks no further than the oldest LOOK_AHEAD_BATCHES x batch_size
# pending documents, so a rescan's million ids never make it O(backlog)
LOOK_AHEAD_BATCHES = 16


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


def text_length(text: str) -> int:
    """The length a document is sealed by: CLS and one token a
    whitespace word. Exact for the hash tokenizer on plain words and
    monotone for any other, and the queue needs no embedder for it."""
    return len(text.split()) + 1


def seal_width(length: int) -> int:
    """The embedder's power-of-two width bucket for ``length`` tokens.
    Nothing under ``FULL_BATCH_MIN_WIDTH`` is told apart: a full batch
    is never narrower than that."""
    return max(FULL_BATCH_MIN_WIDTH, width_bucket(length))


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
        # ids not yet sealed into a batch, in arrival order, each with
        # the length it is sealed by; and every id enqueued and not yet
        # done with (waiting or in the worker's batch) with its arrival
        # time. One lock guards both; the worker sleeps on its condition.
        self._waiting: "OrderedDict[str, int]" = OrderedDict()
        self._pending: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._arrived = threading.Condition(self._lock)
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
        if node.embedding is None and not embed_exempt(node):
            text = build_embedding_text(node)
            if text:
                self.enqueue(node.id, text_length(text))

    def on_node_delete(self, node_id: str) -> None:
        with self._lock:
            self._pending.pop(node_id, None)
            self._waiting.pop(node_id, None)

    # -- queue -----------------------------------------------------------

    def enqueue(self, node_id: str, length: int = 0) -> None:
        """``length`` is ``text_length`` of the node's embedding text;
        a caller that does not know it is sealed with the short ones."""
        with self._arrived:
            if node_id in self._pending:
                return
            self._pending[node_id] = time.perf_counter()
            self._waiting[node_id] = length
            self._arrived.notify()

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
        with self._arrived:
            self._arrived.notify_all()
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
            t_wait = time.perf_counter()
            with self._arrived:
                if not self._waiting and not self._stop.is_set():
                    self._arrived.wait(timeout=0.25)
                batch, picked, oldest = ([], 0, 0.0) \
                    if self._stop.is_set() else self._seal()
            waited = time.perf_counter() - t_wait
            self._starved_s += waited
            _WORKER_S.labels("starved").inc(waited)
            if not batch:
                continue
            try:
                self._process_batch(
                    batch, picked=picked,
                    oldest_wait_s=time.perf_counter() - oldest)
            except Exception:
                logger.exception("embed batch failed")

    def _seal(self) -> Tuple[List[str], int, float]:
        """Take the next batch off the waiting set (lock held): its ids
        in arrival order, how many of them were picked ahead of an older
        document, and the oldest one's arrival time.

        With ``batch_size`` or fewer waiting the batch is all of them, in
        arrival order: a write that arrives alone is embedded alone and
        at once. With more, the oldest is the anchor and decides the
        width (``seal_width`` of its length); its companions come from
        the look-ahead: the longest that fit that width, and where those
        are too few the nearest above it. A batch is as wide as its
        longest row, so rows of a length share the padding they cause.
        The oldest document always leaves with the next batch, so none
        waits for more batches than documents were ahead of it when it
        arrived, however short the ones behind it are."""
        n = self.batch_size
        ahead = list(islice(self._waiting.items(), LOOK_AHEAD_BATCHES * n))
        if len(ahead) <= n:
            rows = list(range(len(ahead)))
        else:
            width = seal_width(ahead[0][1])
            rest = range(1, len(ahead))
            # sorted() is stable: equal lengths stay in arrival order
            fit = sorted((i for i in rest if ahead[i][1] <= width),
                         key=lambda i: -ahead[i][1])
            over = sorted((i for i in rest if ahead[i][1] > width),
                          key=lambda i: ahead[i][1])
            rows = sorted([0] + (fit + over)[:n - 1])
        if not rows:
            return [], 0, 0.0
        batch = [ahead[i][0] for i in rows]
        for nid in batch:
            del self._waiting[nid]
        # a row is in arrival order while nothing older stays behind
        in_order = sum(1 for at, i in enumerate(rows) if at == i)
        _SEALED_C.labels("arrival").inc(in_order)
        _SEALED_C.labels("by_length").inc(len(rows) - in_order)
        return batch, len(rows) - in_order, self._pending.get(
            batch[0], time.perf_counter())

    def _process_batch(self, node_ids: List[str], picked: int = 0,
                       oldest_wait_s: float = 0.0) -> None:
        """One batch is one root span ``embed.batch`` (``/admin/traces``,
        and ``nornic:embed.batch`` in a profiler trace) whose children
        are its phases; the phase counter is fed from the same spans.
        ``picked`` rows were sealed ahead of older documents; the oldest
        row had waited ``oldest_wait_s`` when the batch was sealed."""
        starved, self._starved_s = self._starved_s, 0.0
        root = None
        try:
            with _trace("embed.batch", rows=len(node_ids),
                        starved_ms=round(starved * 1e3, 3), picked=picked,
                        oldest_wait_ms=round(oldest_wait_s * 1e3, 3)
                        ) as root:
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
                    self._done(nid)
                    continue
                if node.embedding is not None:
                    self._done(nid)
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
                self._done(n.id)
            return
        for node, text, vec in zip(nodes, texts, vectors):
            # per-node isolation: one failing write must not wedge the rest
            # of the batch in _pending (they'd never re-enqueue)
            embedded = False
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
                embedded = True
            except Exception:
                logger.exception("embed write failed for %s", node.id)
                self.failed_count += 1
            finally:
                self._done(node.id, embedded)
        self._schedule_clustering()

    def _done(self, node_id: str, embedded: bool = False) -> None:
        """The worker is done with ``node_id``, embedded or dropped."""
        with self._lock:
            arrived = self._pending.pop(node_id, None)
        if embedded and arrived is not None:
            _WAIT_H.observe(time.perf_counter() - arrived)

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
                    if not (self.has_vector is not None
                            and self.has_vector(node.id)):
                        self.on_node_upsert(node)
            except Exception:
                logger.exception("rescan failed")
