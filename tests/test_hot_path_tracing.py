"""Spans and counters where the time is (ISSUE 26).

What is pinned here, on the CPU:

- a search that widens (since ISSUE 29 a selective filter or a ``limit``
  over the bound: the coalesced round asks for the request's ``limit``, so
  ``limit`` 100 alone is one shared scan and no ``qdrant.widen``) yields
  ``qdrant.widen`` > ``index.snapshot`` (with ``ids``: the id snapshot
  ``reused`` or ``copied``, ISSUE 27), ``index.scan`` (with its ``path``),
  ``index.collect``, inside the interval ``qdrant.rank`` covers, and
  ``qdrant.rank`` carries the hydration's running sum;
- the widening search and the encoder forward are dispatch kinds of the
  compile universe, with their shapes;
- the embed worker opens one ``embed.batch`` root a batch whose phase
  spans feed ``nornicdb_embed_worker_seconds_total`` (one timing for
  both), and the encoder counts real and padded tokens;
- ``MemoryEngine.get_node`` counts its lock's acquires and wait;
- live spans reach a profiler trace as ``nornic:<name>``, and ``obs``
  still imports without JAX;
- the readers that were there read the same on a span tree with and
  without the new children, and each new reader reads a hand-made
  ``Observed`` and returns ``None`` on an empty one.
"""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmark.lib import loader
from benchmark.lib.observed import Observed
from nornicdb_tpu import obs
from nornicdb_tpu.api import qdrant
from nornicdb_tpu.api.qdrant import QdrantCompat
from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
from nornicdb_tpu.embed.queue import CHUNK_THRESHOLD_CHARS, EmbedQueue
from nornicdb_tpu.obs import tracing
from nornicdb_tpu.storage.memory import MemoryEngine
from nornicdb_tpu.storage.types import Node

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEARCH = "POST /collections/c/points/search"


def _walk(span):
    yield span
    for child in span.children:
        yield from _walk(child)


def _named(root, name):
    return [s for s in _walk(root) if s.name == name]


def _collection(rows, dims):
    compat = QdrantCompat(MemoryEngine())
    compat.create_collection("c", {"size": dims, "distance": "Cosine"})
    rng = np.random.default_rng(7)
    vectors = rng.standard_normal((rows, dims)).astype(np.float32)
    compat.upsert_points("c", [
        {"id": i, "vector": vectors[i].tolist(), "payload": {"n": i}}
        for i in range(rows)])
    return compat, vectors


@pytest.fixture(scope="module")
def device_collection():
    # 4,200 x 64 cells is past BruteForceIndex._SMALL_HOST: the jitted scan
    return _collection(4200, 64)


# half the fixture's points: a limit-100 search's first round (k=100)
# yields ~50 that pass, so it widens once, to k=400
HALF = {"must": [{"key": "n", "range": {"lt": 2100}}]}


def _search(compat, vector, limit=100, query_filter=None):
    with obs.trace("wire", method=SEARCH, transport="http") as root:
        hits = compat.search_points("c", vector.tolist(), limit=limit,
                                    query_filter=query_filter)
    return root, hits


def _node_ids(hits):
    return [qdrant._point_node_id("c", h["id"]) for h in hits]


def _widen_dispatches():
    return {(e["b"], e["k"]): e["dispatches"]
            for e in obs.compile_universe() if e["kind"] == "vector_widen"}


class TestVectorReadPath:
    def test_widening_search_tree(self, device_collection):
        compat, vectors = device_collection
        root, hits = _search(compat, vectors[3], query_filter=HALF)
        assert len(hits) == 100
        assert all(h["payload"]["n"] < 2100 for h in hits)
        (widen,) = _named(root, "qdrant.widen")
        assert widen in root.children
        assert widen.attrs["k"] == 400 and widen.attrs["round"] == 1
        names = [c.name for c in widen.children]
        assert names == ["index.snapshot", "index.scan", "index.collect"]
        snapshot, scan, _ = widen.children
        assert snapshot.attrs["lock_wait_ms"] >= 0.0
        assert scan.attrs["path"] == "xla"
        assert scan.attrs["b"] == 1 and scan.attrs["k"] == 400
        # the coalesced round's scan hangs from the batch leader's root
        assert len(_named(root, "index.scan")) == 2

    def test_limit_100_without_a_filter_is_one_shared_scan(
            self, device_collection):
        compat, vectors = device_collection
        before = _widen_dispatches()
        root, hits = _search(compat, vectors[11])
        assert not _named(root, "qdrant.widen")
        (scan,) = _named(root, "index.scan")
        assert scan.attrs["b"] == 1 and scan.attrs["k"] == 128
        assert _widen_dispatches() == before
        # the same hundred, in the same order, as the k=160 scan the
        # request used to make for itself
        old = compat._index("c").search(vectors[11], k=160)[:100]
        assert _node_ids(hits) == [nid for nid, _ in old]
        assert [h["score"] for h in hits] == pytest.approx(
            [score for _, score in old], abs=1e-6)

    @pytest.mark.parametrize("limit, first_k", [(10, 64), (40, 64),
                                                (41, 64), (65, 128),
                                                (256, 256)])
    def test_first_round_asks_for_the_limit(self, device_collection,
                                            limit, first_k):
        # the batcher pads k to its pow2 bucket: limit <= 40 keeps the
        # k=40 -> 64 programs it always had
        compat, vectors = device_collection
        root, hits = _search(compat, vectors[12], limit=limit)
        assert len(hits) == limit
        (scan,) = _named(root, "index.scan")
        assert scan.attrs["k"] == first_k
        assert not _named(root, "qdrant.widen")

    def test_limit_over_the_bound_starts_at_the_bound_then_widens(
            self, device_collection):
        compat, vectors = device_collection
        limit = qdrant._FIRST_K_MAX + 44
        root, hits = _search(compat, vectors[13], limit=limit)
        assert len(hits) == limit
        assert len({h["id"] for h in hits}) == limit
        scores = [h["score"] for h in hits]
        assert scores == sorted(scores, reverse=True)
        first, second = _named(root, "index.scan")
        assert first.attrs["k"] == qdrant._FIRST_K_MAX
        (widen,) = _named(root, "qdrant.widen")
        assert widen.attrs["k"] == 4 * qdrant._FIRST_K_MAX
        assert second in widen.children

    def test_mixed_limits_sealed_together_get_their_own_counts(
            self, device_collection):
        compat, vectors = device_collection
        batcher = compat._collection_microbatch("c")
        shipped = batcher._gather_window_s
        # hold the gather window open for a burst of two, as the
        # benchmark's warm-up does
        batcher._gather_window_s, batcher._last_batch = 5.0, 2
        gate = threading.Barrier(2)
        got = {}

        def one(limit, row):
            gate.wait(timeout=30)
            got[limit] = _search(compat, vectors[row], limit=limit)

        threads = [threading.Thread(target=one, args=a)
                   for a in ((10, 14), (100, 15))]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            batcher._gather_window_s = shipped
        assert not any(t.is_alive() for t in threads)
        assert {k: len(hits) for k, (_, hits) in got.items()} \
            == {10: 10, 100: 100}
        # one batch, run at the larger rider's k, under the leader's root
        scans = [s for root, _ in got.values()
                 for s in _named(root, "index.scan")]
        assert [(s.attrs["b"], s.attrs["k"]) for s in scans] == [(2, 128)]
        for root, _ in got.values():
            (dispatch,) = _named(root, "device.dispatch")
            assert dispatch.attrs["batch"] == 2
            assert not _named(root, "qdrant.widen")
        for limit, row in ((10, 14), (100, 15)):
            alone = compat._index("c").search(vectors[row], k=limit)
            assert _node_ids(got[limit][1]) == [nid for nid, _ in alone]

    def test_rank_span_carries_hydration(self, device_collection):
        compat, vectors = device_collection
        root, _ = _search(compat, vectors[4])
        (rank,) = _named(root, "qdrant.rank")
        assert rank.attrs["hydrated"] == 100
        assert 0.0 < rank.attrs["hydrate_ms"] <= rank.duration_ms

    def test_widen_lies_inside_the_rank_interval(self, device_collection):
        # wire_self_ms.search is the root less its children's union: a new
        # direct child outside qdrant.rank's interval would shift it
        compat, vectors = device_collection
        root, _ = _search(compat, vectors[5], query_filter=HALF)
        (rank,) = _named(root, "qdrant.rank")
        assert _named(root, "qdrant.widen")
        for child in root.children:
            if child.name in ("qdrant.widen", "index.snapshot",
                              "index.scan", "index.collect"):
                assert rank.t0 <= child.t0 and child.t1 <= rank.t1

    def test_small_collection_scans_on_the_host(self):
        compat, vectors = _collection(300, 16)
        root, hits = _search(compat, vectors[0])
        assert len(hits) == 100
        scans = _named(root, "index.scan")
        assert scans and {s.attrs["path"] for s in scans} == {"host"}
        # the host scan runs under the index lock: nested in the snapshot
        for snapshot in _named(root, "index.snapshot"):
            assert [c.name for c in snapshot.children] == ["index.scan"]

    def test_widen_is_a_dispatch_kind_with_its_shape(self, device_collection):
        compat, vectors = device_collection
        before = _widen_dispatches()
        _search(compat, vectors[6], query_filter=HALF)
        after = _widen_dispatches()
        assert after[(1, 512)] == before.get((1, 512), 0) + 1
        assert "vector_widen" in obs.dispatch.bucket_counts()

    def test_snapshot_span_says_whether_the_ids_were_copied(
            self, device_collection):
        def counts():
            fam = obs.REGISTRY.get("nornicdb_index_ids_snapshot_total")
            return {r: fam.labels(r).value
                    for r in ("reused", "extended", "copied")}

        compat, vectors = device_collection
        # the same vector over the same point: a write that moves no id
        compat.upsert_points("c", [
            {"id": 0, "vector": vectors[0].tolist(), "payload": {"n": 0}}])
        before = counts()
        root, _ = _search(compat, vectors[9], query_filter=HALF)
        snapshots = _named(root, "index.snapshot")
        # one count and one ``ids`` a search_batch call: an overwrite
        # leaves the table shared (ISSUE 32), and the first round's
        # snapshot holds the refresh that wrote the row to the device
        assert [s.attrs["ids"] for s in snapshots] == ["reused", "reused"]
        assert [[(c.name, c.attrs["kind"], c.attrs["rows"],
                  c.attrs["bucket"]) for c in s.children]
                for s in snapshots] == [[("index.refresh", "rows", 1, 16)],
                                        []]
        assert counts() == dict(before, reused=before["reused"] + 2)
        # a new point moves one id: its chunk is rebuilt, once
        compat.upsert_points("c", [
            {"id": 990_000, "vector": vectors[1].tolist(),
             "payload": {"n": 0}}])
        root, _ = _search(compat, vectors[10], query_filter=HALF)
        assert [s.attrs["ids"] for s in _named(root, "index.snapshot")] \
            == ["extended", "reused"]
        assert counts() == {"copied": before["copied"],
                            "extended": before["extended"] + 1,
                            "reused": before["reused"] + 3}
        compat.delete_points("c", [990_000])
        text = obs.REGISTRY.render()
        assert 'nornicdb_index_ids_snapshot_total{result="reused"}' in text

    def test_disabled_telemetry_leaves_no_span(self, device_collection):
        compat, vectors = device_collection
        obs.set_enabled(False)
        try:
            recorded = obs.TRACES.recorded
            root, hits = _search(compat, vectors[8])
            assert len(hits) == 100
            assert not isinstance(root, obs.Span)
            assert obs.TRACES.recorded == recorded
        finally:
            obs.set_enabled(True)


def test_unpriced_rider_count_does_not_leak_past_its_dispatch():
    # found by this file's disabled-telemetry search: the batcher notes
    # its rider count, nothing prices it, and the note then corrected the
    # next unrelated cost on the thread (tests/test_device_truth.py)
    from nornicdb_tpu.obs import device as dev

    with dev.dispatch_scope("probe_kind"):
        dev.note_real_rows(1.0)
    dev.note_cost("probe_kind_after", 6, 1e6, 2e5)
    doc = dev.calibration_summary()["kinds"]["probe_kind_after"]
    assert doc["flops"] == 1e6
    with dev._lock:
        assert dev._kinds["probe_kind_after"]["real_rows"] == 6


class TestStorageLockCounters:
    def test_get_node_counts_acquires_and_wait(self):
        def series(name):
            obs.REGISTRY.run_collectors()
            return obs.REGISTRY.get(name).labels("get_node").value

        engine = MemoryEngine()
        engine.create_node(Node(id="a", labels=["X"], properties={}))
        acquires = series("nornicdb_storage_lock_acquires_total")
        wait = series("nornicdb_storage_lock_wait_seconds_total")
        for _ in range(37):
            engine.get_node("a")
        with pytest.raises(KeyError):
            engine.get_node("missing")
        assert series(
            "nornicdb_storage_lock_acquires_total") == acquires + 38
        grown = series("nornicdb_storage_lock_wait_seconds_total") - wait
        assert 0.0 < grown < 0.01
        text = obs.REGISTRY.render()
        assert 'nornicdb_storage_lock_wait_seconds_total{op="get_node"}' \
            in text


class _StubEmbedder:
    dims = 4

    def embed_batch(self, texts):
        time.sleep(0.002)
        return [[0.5] * self.dims for _ in texts]

    def embed_chunks(self, text):
        time.sleep(0.001)
        return [[0.25] * self.dims]


def _phase_seconds():
    fam = obs.REGISTRY.get("nornicdb_embed_worker_seconds_total")
    return {key[0]: child.value for key, child in fam.children().items()}


@pytest.fixture()
def embedded_batch():
    """One batch of four nodes (one long enough for chunk vectors) run
    through ``_process_batch``: its root, and the counters' growth."""
    storage = MemoryEngine()
    long_text = "word " * (CHUNK_THRESHOLD_CHARS // 5 + 10)
    texts = ["alpha beta", "gamma delta", long_text, "epsilon"]
    for i, text in enumerate(texts):
        storage.create_node(Node(id=f"n{i}", labels=["Doc"],
                                 properties={"content": text}))
    published = []
    queue = EmbedQueue(storage, _StubEmbedder(),
                       on_embedded=published.append)
    obs.TRACES.clear()
    before = _phase_seconds()
    batches = obs.REGISTRY.get("nornicdb_embed_batches_total").value
    queue._process_batch([f"n{i}" for i in range(4)])
    after = _phase_seconds()
    roots = [s for s in obs.TRACES._ring if s.name == "embed.batch"]
    return {
        "roots": roots, "published": published, "storage": storage,
        "grown": {p: after[p] - before.get(p, 0.0) for p in after},
        "batches": obs.REGISTRY.get(
            "nornicdb_embed_batches_total").value - batches}


class TestEmbedWorker:
    def test_one_root_a_batch_with_every_phase(self, embedded_batch):
        (root,) = embedded_batch["roots"]
        assert root.attrs["rows"] == 4 and "starved_ms" in root.attrs
        names = [c.name for c in root.children]
        assert names[:2] == ["embed.load", "embed.encode"]
        assert names.count("embed.store") == 4
        assert names.count("embed.publish") == 4
        assert names.count("embed.chunks") == 1
        assert embedded_batch["batches"] == 1
        assert len(embedded_batch["published"]) == 4
        assert embedded_batch["storage"].get_node(
            "n2").chunk_embeddings == [[0.25] * 4]

    def test_phase_counter_grows_by_the_spans_durations(self,
                                                        embedded_batch):
        (root,) = embedded_batch["roots"]
        grown = embedded_batch["grown"]
        by_phase = {}
        for child in root.children:
            phase = child.name.partition(".")[2]
            by_phase[phase] = by_phase.get(phase, 0.0) \
                + (child.t1 - child.t0)
        for phase in ("load", "encode", "chunks", "store", "publish"):
            assert grown[phase] == pytest.approx(by_phase[phase], abs=1e-9)
        assert grown["encode"] >= 0.002 and grown["chunks"] >= 0.001
        # every phase together is the root's wall time
        assert sum(by_phase.values()) + grown["other"] == pytest.approx(
            root.t1 - root.t0, abs=1e-9)

    def test_worker_counts_the_time_it_is_starved(self):
        queue = EmbedQueue(MemoryEngine(), _StubEmbedder(),
                           rescan_interval_s=0)
        before = _phase_seconds().get("starved", 0.0)
        queue.start()
        time.sleep(0.3)
        queue.stop()
        assert not queue._worker.is_alive()
        assert _phase_seconds()["starved"] - before >= 0.2


class TestEncoderDispatchRecord:
    @pytest.fixture(scope="class")
    def embedder(self):
        from nornicdb_tpu.models.encoder import EncoderConfig

        return JaxEncoderEmbedder(cfg=EncoderConfig.tiny())

    def test_tokens_equal_what_the_jitted_forward_was_handed(self, embedder):
        handed = []

        def stub(params, ids):
            handed.append(tuple(int(d) for d in ids.shape))
            return np.zeros((ids.shape[0], embedder.dims), np.float32)

        def tokens(kind):
            return obs.REGISTRY.get(
                "nornicdb_embed_tokens_total").labels(kind).value

        forward, embedder._jit = embedder._jit, stub
        try:
            real, padded = tokens("real"), tokens("padded")
            id_lists = [list(range(1, 6)), list(range(1, 40)),
                        list(range(1, 21))]
            with obs.trace("test.root") as root:
                embedder._run(id_lists)
        finally:
            embedder._jit = forward
        assert handed == [(4, 64)]
        assert tokens("padded") - padded == 4 * 64
        assert tokens("real") - real == 5 + 39 + 20
        (span,) = _named(root, "encoder.forward")
        assert span.attrs == {"rows": 4, "width": 64}

    def test_encoder_is_a_dispatch_kind_with_its_shapes(self, embedder):
        def seen():
            return {(e["b"], e["k"]): e["dispatches"]
                    for e in obs.compile_universe()
                    if e["kind"] == "encoder"}

        before = seen()
        sum_before = obs.REGISTRY.get(
            "nornicdb_device_dispatch_seconds").labels(
                "encoder").snapshot()["sum"]
        vectors = embedder.embed_batch(["a b c", "d e f g h"])
        assert len(vectors) == 2 and len(vectors[0]) == embedder.dims
        after = seen()
        assert after[(2, 16)] == before.get((2, 16), 0) + 1
        assert obs.REGISTRY.get(
            "nornicdb_device_dispatch_seconds").labels(
                "encoder").snapshot()["sum"] > sum_before
        # the benchmark's tap and its program name depend on both
        assert callable(embedder._jit)
        assert "lambda" in getattr(embedder._jit, "__name__", "")


class TestProfilerClock:
    def test_live_spans_reach_a_profiler_trace(self, tmp_path):
        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.trace("probe.root"):
                with obs.span("probe.child"):
                    time.sleep(0.002)
                obs.attach_span("probe.grafted", time.time() - 0.001,
                                time.time())
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = {ev.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith(tracing.PROFILER_PREFIX)}
        # live spans, roots included; a grafted interval cannot be there
        assert names == {"nornic:probe.root", "nornic:probe.child"}

    def test_obs_imports_and_traces_without_jax(self):
        code = (
            "import sys\n"
            "from nornicdb_tpu import obs\n"
            "with obs.trace('r') as root:\n"
            "    with obs.span('c'):\n"
            "        pass\n"
            "assert root.span_names() == ['r', 'c']\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n")
        done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr


# -- the benchmark's readers ---------------------------------------------


def _span(name, start_ms, duration_ms, children=(), **attrs):
    return {"name": name, "start_ms": start_ms, "duration_ms": duration_ms,
            "attrs": attrs, "children": list(children)}


def _search_root(start_ms, new_children):
    """A search's span tree as the program records it: 100 ms of wire,
    the coalesced round, then a 40 ms widening search and hydration
    inside ``qdrant.rank``."""
    widen = _span("qdrant.widen", start_ms + 30, 40, [
        _span("index.snapshot", start_ms + 30, 12, lock_wait_ms=2.0),
        _span("index.scan", start_ms + 42, 25, path="xla", b=1, k=160),
        _span("index.collect", start_ms + 67, 3)], k=160, round=1)
    rank_attrs = {"collection": "c", "distance": "Cosine"}
    if new_children:
        rank_attrs.update(hydrate_ms=9.0, hydrated=100)
    kids = [
        _span("coalesce.wait", start_ms + 5, 1),
        _span("device.dispatch", start_ms + 6, 20, [
            _span("index.scan", start_ms + 7, 18, path="xla", b=2, k=64)]
            if new_children else ()),
        _span("merge", start_ms + 26, 1),
        _span("qdrant.rank", start_ms + 4, 80, **rank_attrs)]
    if new_children:
        kids.insert(3, widen)
    return _span("wire", start_ms, 100, kids, method=SEARCH,
                 transport="http")


def _observed(new_children=True):
    observed = Observed()
    observed.spans = [_search_root(1000.0, new_children),
                      _search_root(2000.0, new_children)]
    if new_children:
        observed.prom_before = {
            'nornicdb_http_request_seconds_count{route="collections"}': 7.0,
            'nornicdb_http_request_seconds_count{route="metrics"}': 1.0,
            'nornicdb_storage_lock_wait_seconds_total{op="get_node"}': 1.0,
            'nornicdb_embed_worker_seconds_total{phase="encode"}': 10.0,
            'nornicdb_embed_worker_seconds_total{phase="store"}': 1.0,
            'nornicdb_device_dispatch_seconds_sum{kind="encoder"}': 8.0,
            'nornicdb_device_dispatch_seconds_sum{kind="microbatch"}': 3.0,
            'nornicdb_embed_tokens_total{kind="real"}': 100.0,
            'nornicdb_embed_tokens_total{kind="padded"}': 1000.0}
        observed.prom_after = {
            'nornicdb_http_request_seconds_count{route="collections"}': 11.0,
            'nornicdb_http_request_seconds_count{route="metrics"}': 2.0,
            'nornicdb_storage_lock_wait_seconds_total{op="get_node"}': 1.5,
            'nornicdb_embed_worker_seconds_total{phase="encode"}': 19.0,
            'nornicdb_embed_worker_seconds_total{phase="store"}': 2.0,
            'nornicdb_device_dispatch_seconds_sum{kind="encoder"}': 16.5,
            'nornicdb_device_dispatch_seconds_sum{kind="microbatch"}': 9.0,
            'nornicdb_embed_tokens_total{kind="real"}': 350.0,
            'nornicdb_embed_tokens_total{kind="padded"}': 2000.0}
    return observed


NEW_READERS = {
    "widen_ms": 40.0,
    "hydrate_ms": 9.0,
    "index_snapshot_ms": 12.0,
    "scan_turnaround_ms": (25.0 + 18.0) / 2,
    "storage_lock_wait_ms": 0.5 * 1e3 / 4,
    "embed_host_share_pct": 100.0 * (10.0 - 8.5) / 10.0,
    "embed_rows_fill_pct": 25.0,
}


class TestReaders:
    @pytest.mark.parametrize("name", ["rank_uncoalesced_ms",
                                      "wire_self_ms.search"])
    def test_old_readers_read_the_same_with_the_new_children(self, name):
        reader = loader.load_metric_reader(name, ROOT)
        with_new = reader.read(_observed(True))
        assert with_new == pytest.approx(reader.read(_observed(False)))
        assert with_new == pytest.approx(
            {"rank_uncoalesced_ms": 80.0 - 21.0,
             "wire_self_ms.search": 20.0}[name])

    @pytest.mark.parametrize("name", sorted(NEW_READERS))
    def test_new_reader_on_a_hand_made_window(self, name):
        reader = loader.load_metric_reader(name, ROOT)
        assert reader.read(_observed()) == pytest.approx(NEW_READERS[name])

    @pytest.mark.parametrize("name", sorted(NEW_READERS))
    def test_new_reader_finds_nothing_on_the_parents_window(self, name):
        # the parent commit records none of these: a reader returns None
        # on an empty window and on one that holds only the old spans
        reader = loader.load_metric_reader(name, ROOT)
        assert reader.read(Observed()) is None
        assert reader.read(_observed(False)) is None

    def test_new_readers_are_listed_in_the_benchmark(self):
        bench = loader.load_benchmark(ROOT)
        listed = {m["name"]: m for m in bench["per_layer"]}
        for name in NEW_READERS:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "layer_metrics", name + ".py"))
            cells = listed[name]["workloads"]
            first = ["ingest-bulk-4k"] if name.startswith("embed_") \
                else ["vec2m-c32", "vec2m-c1"]
            # later cells are appended (ISSUE 32: the live vector cell,
            # where the reader counts searches only; ISSUE 34: the
            # tenants cell)
            assert cells[:len(first)] == first
            assert set(cells[len(first):]) <= {"vec2m-rw-c32",
                                               "vec2m-tenant-c32"}


def test_a_host_scan_is_left_out_of_scan_turnaround():
    observed = _observed()
    for scan in observed.span_walk("index.scan"):
        scan["attrs"]["path"] = "host"
    reader = loader.load_metric_reader("scan_turnaround_ms", ROOT)
    assert reader.read(observed) is None
