"""The embed queue seals its batches by length (ISSUE 31): which pending
documents a batch holds, the bound on waiting, what the encoder is then
handed, and that a lone write, delete, stop and drain behave as they did."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.embed.embedder import (
    FULL_BATCH_MIN_WIDTH,
    JaxEncoderEmbedder,
    width_bucket,
)
from nornicdb_tpu.embed.queue import (
    LOOK_AHEAD_BATCHES,
    EmbedQueue,
    seal_width,
    text_length,
)
from nornicdb_tpu.embed.tokenizer import CHUNK_OVERLAP, CHUNK_SIZE
from nornicdb_tpu.ops.similarity import pow2_bucket
from nornicdb_tpu.storage.memory import MemoryEngine
from nornicdb_tpu.storage.types import Node


class _Stub:
    dims = 4

    def embed_batch(self, texts):
        return [[0.5] * self.dims for _ in texts]


def _queue(**kw):
    return EmbedQueue(MemoryEngine(), _Stub(), rescan_interval_s=0, **kw)


def _fill(q, lengths):
    for i, n in enumerate(lengths):
        q.enqueue(f"n{i}", n)


def _seal(q):
    with q._lock:
        return q._seal()


def _sealed_rows():
    fam = obs.REGISTRY.get("nornicdb_embed_sealed_rows_total")
    return {o: fam.labels(o).value for o in ("arrival", "by_length")}


# -- which documents a batch holds -------------------------------------------


class TestSeal:
    @pytest.mark.parametrize("length,width", [
        (0, 256), (10, 256), (256, 256), (257, 512), (512, 512),
        (513, 1024), (2048, 2048), (2049, 4096), (4096, 4096)])
    def test_seal_width_is_the_embedders_bucket_floored_at_256(
            self, length, width):
        assert seal_width(length) == width
        assert width == max(FULL_BATCH_MIN_WIDTH, width_bucket(length))

    @pytest.mark.parametrize("text,length", [
        ("w1", 2), ("w1 w2 w3 Doc", 5), ("  spaced\tout\nwords ", 4)])
    def test_text_length_is_cls_and_one_a_word(self, text, length):
        assert text_length(text) == length

    def test_text_length_is_the_hash_tokenizers_count_on_plain_words(self):
        from nornicdb_tpu.embed.tokenizer import HashTokenizer

        text = " ".join(f"w{i}" for i in range(700)) + " Doc"
        assert text_length(text) == len(
            HashTokenizer(1024).encode(text, max_len=8192))

    @pytest.mark.parametrize("pending", [1, 5, 16])
    def test_batch_size_or_fewer_pending_seal_in_arrival_order(
            self, pending):
        q = _queue()
        lengths = [3000, 12, 700, 40, 2100][:pending] + [20] * max(
            pending - 5, 0)
        _fill(q, lengths)
        before = _sealed_rows()
        batch, picked, oldest = _seal(q)
        assert batch == [f"n{i}" for i in range(pending)]
        assert picked == 0 and oldest == q._pending["n0"]
        assert not q._waiting
        after = _sealed_rows()
        assert after["arrival"] - before["arrival"] == pending
        assert after["by_length"] == before["by_length"]
        assert _seal(q) == ([], 0, 0.0)

    def test_oldest_is_the_anchor_and_the_longest_that_fit_join_it(self):
        q = _queue(batch_size=4)
        # anchor 300 -> width 512: of those that fit, the longest three
        _fill(q, [300, 20, 900, 480, 30, 511, 260, 3000, 513])
        before = _sealed_rows()
        batch, picked, _ = _seal(q)
        assert batch == ["n0", "n3", "n5", "n6"]
        assert picked == 3
        after = _sealed_rows()
        assert after["arrival"] - before["arrival"] == 1
        assert after["by_length"] - before["by_length"] == 3
        # what stays keeps its arrival order; the next anchor is n1
        assert list(q._waiting) == ["n1", "n2", "n4", "n7", "n8"]
        batch, picked, _ = _seal(q)
        # anchor 20 -> width 256: one more fits, so the nearest above
        # (513) and the next (900) join, in arrival order
        assert batch == ["n1", "n2", "n4", "n8"]
        assert picked == 1            # n8 was picked ahead of n7
        assert _seal(q) == (["n7"], 0, q._pending["n7"])

    def test_too_few_that_fit_take_the_nearest_above(self):
        q = _queue(batch_size=4)
        _fill(q, [100, 4000, 600, 90, 1500, 2500])
        batch, _, _ = _seal(q)
        assert batch == ["n0", "n2", "n3", "n4"]

    def test_under_256_nothing_is_told_apart_but_the_longest_lead(self):
        q = _queue(batch_size=4)
        _fill(q, [10, 250, 30, 200, 256, 257])
        batch, _, _ = _seal(q)
        assert batch == ["n0", "n1", "n3", "n4"]

    def test_equal_lengths_leave_in_arrival_order(self):
        q = _queue(batch_size=4)
        _fill(q, [50] * 9)
        assert _seal(q)[0] == ["n0", "n1", "n2", "n3"]
        assert _seal(q)[:2] == (["n4", "n5", "n6", "n7"], 0)

    def test_look_ahead_is_bounded(self):
        q = _queue(batch_size=4)
        reach = LOOK_AHEAD_BATCHES * 4
        # the only long companions lie just inside and just outside it
        lengths = [3000] + [20] * (reach - 2) + [2900, 2950]
        _fill(q, lengths)
        batch, _, _ = _seal(q)
        assert f"n{reach - 1}" in batch and f"n{reach}" not in batch

    def test_enqueue_is_idempotent_while_pending(self):
        q = _queue()
        q.enqueue("a", 10)
        q.enqueue("a", 900)
        assert list(q._waiting.items()) == [("a", 10)]

    def test_upsert_records_the_texts_length(self):
        q = _queue()
        q.on_node_upsert(Node(id="a", labels=["Doc"],
                              properties={"content": "w1 w2 w3"}))
        q.on_node_upsert(Node(id="b", labels=["_sys"],
                              properties={"content": "w1"}))
        q.on_node_upsert(Node(id="c", labels=[], properties={}))
        assert dict(q._waiting) == {"a": 5}


# -- the bound on waiting ----------------------------------------------------


class TestWaitingBound:
    def _drain(self, q, order):
        """Seal until nothing waits: {id: batch number}, each id once."""
        at = {}
        while q._waiting:
            batch, _, _ = _seal(q)
            assert len(batch) == min(q.batch_size,
                                     len(batch) + len(q._waiting))
            for nid in batch:
                assert nid not in at
                at[nid] = len(order)
            order.append(batch)
        return at

    @pytest.mark.parametrize("long_at", [0, 250, 500])
    def test_every_id_once_and_none_later_than_its_place(self, long_at):
        q = _queue()
        rng = np.random.default_rng(long_at)
        lengths = [int(x) for x in rng.integers(10, 200, 501)]
        lengths[long_at] = 4000
        _fill(q, lengths)
        order = []
        at = self._drain(q, order)
        assert sorted(at) == sorted(f"n{i}" for i in range(501))
        assert all(at[f"n{i}"] <= i for i in range(501))
        assert all(len(b) == 16 for b in order[:-1])

    def test_a_long_document_is_not_starved_by_short_arrivals(self):
        """Sixteen short documents arrive for every batch sealed: the
        long one leaves when it is the oldest, at the latest."""
        q = _queue()
        _fill(q, [20] * 40 + [4000])
        nxt, batches = 41, 0
        while "n40" in q._waiting:
            for _ in range(16):
                q.enqueue(f"n{nxt}", 20)
                nxt += 1
            _seal(q)
            batches += 1
            assert batches <= 41
        assert batches <= 41

    def test_mixed_stream_none_waits_more_batches_than_were_ahead(self):
        """The full mix's law at a backlog of 128-256: a document that
        arrived with ``k`` ahead of it has at most ``k`` batches sealed
        before its own."""
        q = _queue()
        rng = np.random.default_rng(7)
        lengths = np.clip(np.rint(200 * np.exp(
            1.2 * rng.standard_normal(3000))), 10, 4096).astype(int)
        arrived, waited, nxt, batches = {}, [], 0, 0
        while nxt < len(lengths) or q._waiting:
            if len(q._waiting) <= 128:
                while len(q._waiting) < 256 and nxt < len(lengths):
                    arrived[f"n{nxt}"] = (batches, len(q._waiting))
                    q.enqueue(f"n{nxt}", int(lengths[nxt]))
                    nxt += 1
            for nid in _seal(q)[0]:
                since, ahead = arrived.pop(nid)
                assert batches - since <= ahead
                waited.append(batches - since)
            batches += 1
        assert not arrived and len(waited) == len(lengths)
        # and the typical document leaves sooner than arrival order's
        # 8-16 batches would let it
        assert sorted(waited)[len(waited) // 2] <= 8


# -- delete, stop and drain, as before ---------------------------------------


class _Gate(_Stub):
    """Holds the worker inside its first ``embed_batch`` until opened."""

    def __init__(self):
        self.entered = threading.Event()
        self.open = threading.Event()
        self.seen = []

    def embed_batch(self, texts):
        self.entered.set()
        assert self.open.wait(10)
        self.seen.append(list(texts))
        return super().embed_batch(texts)


def _store_nodes(storage, n, words=3):
    for i in range(n):
        storage.create_node(Node(
            id=f"n{i}", labels=["Doc"],
            properties={"content": " ".join(["w"] * (words + i % 5))}))


class TestLifecycle:
    def test_a_lone_write_is_embedded_alone_and_at_once(self):
        storage, gate = MemoryEngine(), _Gate()
        gate.open.set()
        published = []
        q = EmbedQueue(storage, gate, on_embedded=published.append,
                       rescan_interval_s=0)
        q.start()
        try:
            node = Node(id="one", labels=["Doc"],
                        properties={"content": "hello there"})
            storage.create_node(node)
            t0 = time.perf_counter()
            q.on_node_upsert(node)
            q.drain(5)
            assert time.perf_counter() - t0 < 2.0
            assert gate.seen == [["hello there Doc"]]
            assert [n.id for n in published] == ["one"]
            assert storage.get_node("one").embedding == [0.5] * 4
        finally:
            q.stop()

    def test_delete_while_pending_is_dropped_and_the_rest_embedded(self):
        storage, gate = MemoryEngine(), _Gate()
        _store_nodes(storage, 60)
        q = EmbedQueue(storage, gate, rescan_interval_s=0)
        q.start()
        try:
            q.enqueue("n0", 4)
            assert gate.entered.wait(5)        # n0 is in the worker's hands
            for i in range(1, 60):
                q.enqueue(f"n{i}", 4 + i % 5)
            doomed = {f"n{i}" for i in range(1, 60, 4)}
            for nid in doomed:
                storage.delete_node(nid)
                q.on_node_delete(nid)
            assert not doomed & set(q._waiting)
            assert not doomed & set(q._pending)
            gate.open.set()
            q.drain(10)
            assert not q._pending and not q._waiting
            for i in range(60):
                if f"n{i}" in doomed:
                    assert not storage.has_node(f"n{i}")
                else:
                    assert storage.get_node(f"n{i}").embedding is not None
            assert q.embedded_count == 60 - len(doomed)
            assert q.failed_count == 0
        finally:
            gate.open.set()
            q.stop()

    def test_a_deleted_id_still_waiting_in_storage_terms_is_dropped(self):
        """Deleted behind the queue's back (no listener call): dropped
        when met, as ``_embed_and_store`` always did."""
        storage = MemoryEngine()
        _store_nodes(storage, 20)
        q = EmbedQueue(storage, _Stub(), rescan_interval_s=0)
        for i in range(20):
            q.enqueue(f"n{i}", 4)
        storage.delete_node("n3")
        q.start()
        try:
            q.drain(10)
            assert not q._pending
            assert q.embedded_count == 19
        finally:
            q.stop()

    def test_stop_returns_with_documents_waiting_and_embeds_no_more(self):
        storage, gate = MemoryEngine(), _Gate()
        _store_nodes(storage, 50)
        q = EmbedQueue(storage, gate, rescan_interval_s=0)
        q.start()
        q.enqueue("n0", 4)
        assert gate.entered.wait(5)
        for i in range(1, 50):
            q.enqueue(f"n{i}", 4)
        stopper = threading.Thread(target=q.stop)
        stopper.start()
        time.sleep(0.05)
        gate.open.set()
        stopper.join(timeout=10)
        assert not stopper.is_alive() and not q._worker.is_alive()
        assert q.embedded_count == 1           # the batch in hand, no other
        assert len(q._waiting) == 49

    def test_stop_wakes_an_idle_worker(self):
        q = _queue()
        q.start()
        time.sleep(0.05)
        t0 = time.perf_counter()
        q.stop()
        assert not q._worker.is_alive()
        assert time.perf_counter() - t0 < 1.0

    def test_drain_waits_for_everything_pending(self):
        storage = MemoryEngine()
        _store_nodes(storage, 100)

        class Slow(_Stub):
            def embed_batch(self, texts):
                time.sleep(0.01)
                return super().embed_batch(texts)

        q = EmbedQueue(storage, Slow(), rescan_interval_s=0)
        q.start()
        try:
            for i in range(100):
                q.enqueue(f"n{i}", 4 + i % 5)
            q.drain(20)
            assert q.embedded_count == 100 and not q._pending
        finally:
            q.stop()

    def test_rescan_enqueues_what_the_event_path_missed_with_its_length(
            self):
        storage = MemoryEngine()
        _store_nodes(storage, 6, words=2)
        storage.create_node(Node(id="sys", labels=["_meta"],
                                 properties={"content": "w"}))
        storage.create_node(Node(id="held", labels=["Doc"],
                                 properties={"content": "w"}))
        gate = _Gate()
        q = EmbedQueue(storage, gate, rescan_interval_s=0.02,
                       has_vector=lambda nid: nid == "n5")
        q.enqueue("held", 3)            # keeps the worker in the gate
        q.start()
        try:
            assert gate.entered.wait(10)
            deadline = time.time() + 20
            while len(q._waiting) < 5 and time.time() < deadline:
                time.sleep(0.01)
            # content of 2 + i % 5 words, the label, CLS
            assert dict(q._waiting) == {f"n{i}": 4 + i for i in range(5)}
        finally:
            gate.open.set()
            q.stop()

    def test_concurrent_writers_each_id_embedded_exactly_once(self):
        """Eight writers enqueue (and re-enqueue) while the worker seals:
        every id is handed to the embedder once."""
        import sys

        storage = MemoryEngine()
        _store_nodes(storage, 400, words=1)
        seen = []

        class Rec(_Stub):
            def embed_batch(self, texts):
                seen.append(len(texts))
                return super().embed_batch(texts)

        published = []
        q = EmbedQueue(storage, Rec(), on_embedded=published.append,
                       rescan_interval_s=0)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        q.start()
        try:
            def writer(k):
                for i in range(k, 400, 8):
                    q.enqueue(f"n{i}", 10 + 37 * i % 3000)
                    q.enqueue(f"n{i}", 5)

            threads = [threading.Thread(target=writer, args=(k,))
                       for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
            assert not any(t.is_alive() for t in threads)
            q.drain(20)
        finally:
            sys.setswitchinterval(interval)
            q.stop()
        ids = [n.id for n in published]
        assert sorted(ids) == sorted(f"n{i}" for i in range(400))
        assert sum(seen) == 400 and not q._pending and not q._waiting


# -- the counters and the span -----------------------------------------------


class TestCounted:
    def test_wait_histogram_and_span_attributes(self):
        storage = MemoryEngine()
        _store_nodes(storage, 40)
        q = EmbedQueue(storage, _Stub(), on_embedded=lambda n: None,
                       rescan_interval_s=0)
        wait = obs.REGISTRY.get("nornicdb_embed_queue_wait_seconds")
        count0 = wait.child()._count
        lengths = [3000, 2800] + [20] * 36 + [2900, 2950]
        for i, n in enumerate(lengths):
            q.enqueue(f"n{i}", n)
        obs.TRACES.clear()
        time.sleep(0.01)
        batch, picked, oldest = _seal(q)
        assert batch[:2] == ["n0", "n1"] and batch[-2:] == ["n38", "n39"]
        q._process_batch(batch, picked=picked,
                         oldest_wait_s=time.perf_counter() - oldest)
        (root,) = [s for s in obs.TRACES._ring if s.name == "embed.batch"]
        assert root.attrs["rows"] == 16
        assert root.attrs["picked"] == picked == 14 - 12
        assert root.attrs["oldest_wait_ms"] >= 10.0
        assert wait.child()._count - count0 == 16
        assert not set(batch) & set(q._pending)
        text = obs.REGISTRY.render()
        assert 'nornicdb_embed_sealed_rows_total{order="by_length"}' in text
        assert "nornicdb_embed_queue_wait_seconds_bucket" in text

    def test_a_dropped_document_is_not_a_wait(self):
        storage = MemoryEngine()
        _store_nodes(storage, 2)
        q = EmbedQueue(storage, _Stub(), rescan_interval_s=0)
        wait = obs.REGISTRY.get("nornicdb_embed_queue_wait_seconds")
        count0 = wait.child()._count
        q.enqueue("n0", 4)
        q.enqueue("gone", 4)
        q._process_batch(_seal(q)[0])
        assert wait.child()._count - count0 == 1
        assert not q._pending


# -- what the encoder is handed ----------------------------------------------


@pytest.fixture(scope="module")
def tapped():
    """A tiny encoder 4,096 positions long whose jitted forward is
    replaced, as the benchmark taps it, by a recorder of shapes that runs
    nothing."""
    from nornicdb_tpu.models.encoder import EncoderConfig

    cfg = EncoderConfig(vocab_size=1024, hidden_size=32, num_layers=1,
                        num_heads=2, mlp_dim=64, max_len=4096)
    emb = JaxEncoderEmbedder(cfg=cfg)
    shapes = []

    def tap(params, ids):
        rows, width = (int(d) for d in ids.shape)
        shapes.append((rows, width))
        return np.zeros((rows, emb.dims), np.float32)

    emb._jit = tap
    return emb, shapes


def _words(n_tokens):
    return " ".join(["w7"] * (n_tokens - 1))       # CLS and one a word


def _parent_shape(lengths, max_len=4096):
    """What the parent's ``_run`` gave: rows and the longest row's width
    each on the power-of-two ladder."""
    return (pow2_bucket(len(lengths)),
            min(max(16, pow2_bucket(max(lengths))), max_len))


class TestShapes:
    @pytest.mark.parametrize("lengths", [
        [3], [12], [16], [17], [300], [4096], [3, 9], [5, 40, 7],
        [20] * 8, [2000] * 7, [100] * 5])
    def test_fewer_than_16_rows_come_out_as_the_parent_gave_them(
            self, tapped, lengths):
        emb, shapes = tapped
        del shapes[:]
        emb.embed_batch([_words(n) for n in lengths])
        assert shapes == [_parent_shape(lengths)]

    def test_a_single_short_text_is_1_by_16(self, tapped):
        emb, shapes = tapped
        del shapes[:]
        emb.embed("w1 Doc")
        assert shapes == [(1, 16)]

    @pytest.mark.parametrize("n_tokens", [300, 512, 513, 1000, 2049, 4096,
                                          7000, 7500])
    def test_chunks_come_out_as_the_parent_gave_them(self, tapped,
                                                     n_tokens):
        emb, shapes = tapped
        del shapes[:]
        emb.embed_chunks(_words(n_tokens))
        step = CHUNK_SIZE - CHUNK_OVERLAP
        chunks = 1 if n_tokens <= CHUNK_SIZE else \
            -(-(n_tokens - CHUNK_SIZE) // step) + 1
        assert shapes == [(pow2_bucket(chunks), 512)]

    @pytest.mark.parametrize("longest,width", [
        (3, 256), (64, 256), (256, 256), (257, 512), (1024, 1024),
        (1025, 2048), (4096, 4096), (5000, 4096)])
    def test_a_full_batch_is_never_narrower_than_256(self, tapped,
                                                     longest, width):
        emb, shapes = tapped
        del shapes[:]
        emb.embed_batch([_words(longest)] + ["w1"] * 15)
        # the rule is on the row bucket: nine texts are dispatched as 16
        emb.embed_batch([_words(longest)] + ["w1"] * 8)
        assert shapes == [(16, width)] * 2

    def test_the_floor_is_the_configurations_length_where_that_is_less(
            self):
        from nornicdb_tpu.models.encoder import EncoderConfig

        emb = JaxEncoderEmbedder(cfg=EncoderConfig.tiny())
        shapes = []
        emb._jit = lambda p, ids: (
            shapes.append(tuple(ids.shape)),
            np.zeros((ids.shape[0], emb.dims), np.float32))[1]
        emb.embed_batch(["w1 w2"] * 16)
        emb.embed_batch(["w1 w2"] * 3)
        assert shapes == [(16, 128), (4, 16)]

    def test_pad_tokens_counted_at_the_widened_width(self, tapped):
        emb, _ = tapped
        tokens = obs.REGISTRY.get("nornicdb_embed_tokens_total")
        real, padded = (tokens.labels(k).value for k in ("real", "padded"))
        emb.embed_batch([_words(5)] * 16)
        assert tokens.labels("real").value - real == 80
        assert tokens.labels("padded").value - padded == 16 * 256


class TestFill:
    """The full mix's law through the real queue and embedder, the
    forward tapped dry: sealed by length against the same stream taken
    sixteen at a time in arrival order, which is what the parent did."""

    DOCS = 1536

    @pytest.fixture(scope="class")
    def stream(self):
        rng = np.random.default_rng(20260930)
        lengths = np.clip(np.rint(200 * np.exp(
            1.2 * rng.standard_normal(self.DOCS))), 10, 4096).astype(int)
        storage = MemoryEngine()
        for i, n in enumerate(lengths):
            # the label is the text's last word
            storage.create_node(Node(
                id=f"n{i}", labels=["Doc"],
                properties={"content": _words(int(n) - 1)}))
        return storage, [int(n) for n in lengths]

    @staticmethod
    def _fill_pct(shapes, real):
        return 100.0 * real / sum(r * w for r, w in shapes)

    @pytest.fixture(scope="class")
    def runs(self, tapped, stream):
        emb, shapes = tapped
        storage, lengths = stream
        del shapes[:]
        for at in range(0, self.DOCS, 16):
            emb.embed_batch([_words(n) for n in lengths[at:at + 16]])
        fifo = list(shapes)
        del shapes[:]
        q = EmbedQueue(storage, emb, rescan_interval_s=0)
        nxt, batches = 0, []
        while nxt < self.DOCS or q._waiting:
            if len(q._waiting) <= 128 and nxt < self.DOCS:
                # the mix's hysteresis: posting resumes at 128, to 256
                while len(q._waiting) < 256 and nxt < self.DOCS:
                    q.on_node_upsert(storage.get_node(f"n{nxt}"))
                    nxt += 1
            batch, picked, _ = _seal(q)
            before = len(shapes)
            q._process_batch(batch, picked=picked)
            batches.append((len(batch), shapes[before]))
        return {"fifo": fifo, "sealed": list(shapes), "batches": batches,
                "queue": q, "real": sum(lengths)}

    def test_every_document_embedded_once(self, runs, stream):
        storage, lengths = stream
        q = runs["queue"]
        assert q.embedded_count == self.DOCS and q.failed_count == 0
        assert not q._pending and not q._waiting
        assert all(storage.get_node(f"n{i}").embedding is not None
                   for i in range(self.DOCS))

    def test_fill_is_at_least_three_times_fifos(self, runs):
        whole = [s for _, s in runs["batches"]]
        fifo = self._fill_pct(runs["fifo"], runs["real"])
        sealed = self._fill_pct(whole, runs["real"])
        assert 12.0 < fifo < 20.0
        assert sealed >= 3.0 * fifo and sealed >= 50.0

    def test_full_batches_take_only_the_five_widths(self, runs):
        full = {s for n, s in runs["batches"] if n == 16}
        assert full <= {(16, w) for w in (256, 512, 1024, 2048, 4096)}
        assert (16, 256) in full and (16, 4096) in full
        fifo_full = {s for s in runs["fifo"]}
        assert fifo_full <= {(16, w) for w in (256, 512, 1024, 2048, 4096)}

    def test_every_other_call_is_a_documents_chunks(self, runs):
        whole = [s for _, s in runs["batches"]]
        rest = list(runs["sealed"])
        for s in whole:
            rest.remove(s)
        assert rest and all(w == 512 and r <= 16 for r, w in rest)

    def test_the_wide_batches_are_few(self, runs):
        """Three documents in 128 are over 2,048 tokens: arrival order
        pays a (16,4096) pass for nearly each, sealing one for sixteen."""
        def wide(shapes):
            return sum(1 for s in shapes if s == (16, 4096))

        whole = [s for _, s in runs["batches"]]
        assert wide(runs["fifo"]) >= 3 * wide(whole)
        partial = [n for n, _ in runs["batches"] if n < 16]
        assert len(partial) <= 1
