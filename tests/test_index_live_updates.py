"""A brute index that is written while it is read (ISSUE 32): the device
copy is updated in place by the reader that finds writes pending, never
re-shipped for a write at fixed capacity, and every answer is of one
generation (matrix, validity and ids together).

On the jitted scan's path (4,200 x 64 is past ``_SMALL_HOST``), against a
NumPy reference recomputed from scratch. JAX's CPU backend donates too: an
array kept across an update is deleted here as on the chip.
"""

import json
import threading
import time

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu import obs
from nornicdb_tpu.search import vector_index
from nornicdb_tpu.search.vector_index import BruteForceIndex

ROWS, DIMS = 4200, 64


def _unit(m):
    return (m / np.linalg.norm(m, axis=-1, keepdims=True)
            ).astype(np.float32)


def _vectors(seed, rows=ROWS):
    return _unit(np.random.default_rng(seed).standard_normal((rows, DIMS)))


def _counter(name, *labels):
    fam = obs.REGISTRY.get(name)
    return {k: fam.labels(k).value for k in labels}


def _refreshes():
    return _counter("nornicdb_index_refresh_total", "rows", "full")


def _reference(held, queries, k):
    """Exact top-k of ``held`` (id -> row) from scratch, float32."""
    ids = sorted(held)
    m = np.stack([held[i] for i in ids])
    scores = queries @ m.T
    out = []
    for row in scores:
        top = np.argsort(-row, kind="stable")[:k]
        out.append([(ids[j], float(row[j])) for j in top])
    return out


def _same(served, want):
    assert [len(h) for h in served] == [len(h) for h in want]
    for got, ref in zip(served, want):
        # ids by score: equal cosines may swap
        assert {e for e, _ in got} == {e for e, _ in ref} or np.allclose(
            [s for _, s in got], [s for _, s in ref], atol=2e-6)
        np.testing.assert_allclose([s for _, s in got],
                                   [s for _, s in ref], atol=2e-6)


@pytest.mark.parametrize("pending", [1, 16, 100, 257, "capacity"])
def test_interleaved_writes_and_searches_match_numpy(pending):
    """A seeded interleaving of new adds, overwrites, removes and
    ``search_batch``: ``pending`` writes between two searches, each search
    compared with the reference. Only the case that grows the capacity may
    ship the matrix whole."""
    rng = np.random.default_rng([32, 0 if pending == "capacity" else pending])
    grow = pending == "capacity"
    start = 8100 if grow else ROWS          # 8,192 is the capacity's edge
    per_step = 150 if grow else pending
    base = _vectors(10, start)
    held = {f"n{i}": base[i] for i in range(start)}
    idx = BruteForceIndex()
    idx.add_batch(list(held.items()))
    queries = _unit(rng.standard_normal((3, DIMS)))
    _same(idx.search_batch(queries, 10), _reference(held, queries, 10))
    capacity = idx._capacity
    before = _refreshes()
    fresh = 0
    for step in range(4):
        probes = []
        for _ in range(per_step):
            kind = "new" if grow else rng.choice(["new", "over", "remove"])
            if kind == "new":
                eid, fresh = f"w{fresh}", fresh + 1
            else:
                eid = sorted(held)[int(rng.integers(len(held)))]
            if kind == "remove":
                assert idx.remove(eid)
                del held[eid]
                continue
            row = _unit(rng.standard_normal(DIMS))
            idx.add(eid, row)
            held[eid] = row
            probes.append(row)
        near = _unit(np.stack(probes[-2:] + [queries[step % 3]])
                     + 0.05 * rng.standard_normal((len(probes[-2:]) + 1,
                                                   DIMS)))
        _same(idx.search_batch(near, 10), _reference(held, near, 10))
    grown = {k: v - before[k] for k, v in _refreshes().items()}
    if grow:
        assert idx._capacity > capacity and grown["full"] == 1
    else:
        assert idx._capacity == capacity
        assert grown == {"rows": 4, "full": 0}
    assert len(idx) == len(held)


def test_writes_at_fixed_capacity_never_ship_the_matrix():
    vectors = _vectors(11)
    idx = BruteForceIndex()
    idx.add_batch([(f"n{i}", v) for i, v in enumerate(vectors)])
    idx.search(vectors[0], 3)
    idx.warm_updates()
    before = _refreshes()
    shipped = _counter("nornicdb_index_device_ship_bytes_total",
                       "rows", "full")
    kept = idx._dev_matrix
    for i in range(40):
        idx.add(f"n{i}", vectors[i + 100])
        idx.add(f"extra{i}", vectors[i + 200])
        idx.remove(f"n{i + 50}")
        assert idx.search(vectors[i + 100], 1)[0][0] in (f"n{i}",
                                                         f"n{i + 100}")
    after = _refreshes()
    assert after["full"] == before["full"]
    assert after["rows"] == before["rows"] + 40
    now = _counter("nornicdb_index_device_ship_bytes_total", "rows", "full")
    assert now["full"] == shipped["full"]
    # three rows a refresh, padded to the bucket of 16
    assert now["rows"] - shipped["rows"] == 40 * 16 * (DIMS * 4 + 4 + 1)
    # the arrays a refresh was given are gone: nobody may keep them
    assert kept.is_deleted()
    kinds = {(e["kind"], e["b"]) for e in obs.compile_universe()}
    assert {("index_update", b) for b in vector_index.UPDATE_BUCKETS} <= kinds


def test_every_answer_is_of_one_generation_beside_writers():
    """Two writers (overwrites, removes, adds into freed slots) and six
    readers for two seconds: every answer equals the reference at some
    generation between the answer's start and its end, and nothing raises
    (a reader left holding a donated array would)."""
    first, second = _vectors(12), _vectors(13)
    held_rows = np.stack([first, second])        # [2, ROWS, DIMS]
    churn = 96
    idx = BruteForceIndex()
    idx.add_batch([(f"n{i}", v) for i, v in enumerate(first)])
    # the churn rows' history: (generation, row, which vector or -1)
    state = np.zeros(churn, np.int8)
    history = [state.copy()]
    started = [0]                       # writes begun
    done = [0]                          # writes finished
    gen_lock = threading.Lock()
    stop = threading.Event()
    errors, answers = [], []

    def writer(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                row = int(rng.integers(churn))
                with gen_lock:          # one write a generation
                    started[0] += 1
                    if state[row] < 0 or rng.random() < 0.7:
                        state[row] = int(rng.integers(2))
                        idx.add(f"n{row}", held_rows[state[row], row])
                    else:
                        state[row] = -1
                        idx.remove(f"n{row}")
                    history.append(state.copy())
                    done[0] += 1
                time.sleep(0.0005)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                row = int(rng.integers(churn))
                q = _unit(held_rows[int(rng.integers(2)), row]
                          + 0.05 * rng.standard_normal(DIMS))
                g0 = done[0]
                hits = idx.search_batch(q[None], 5)[0]
                answers.append((g0, started[0], q, hits))
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(s,)) for s in (1, 2)] \
        + [threading.Thread(target=reader, args=(s,)) for s in range(3, 9)]
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert done[0] >= 200 and len(answers) >= 200
    rest = first[churn:] @ np.stack([a[2] for a in answers]).T
    for n, (g0, g1, q, hits) in enumerate(answers):
        col_rest = rest[:, n]
        top_rest = np.argsort(-col_rest, kind="stable")[:5]
        matched = False
        for g in range(g0, g1 + 1):
            st = history[g]
            live = np.flatnonzero(st >= 0)
            col = held_rows[st[live], live] @ q
            ids = [f"n{i}" for i in live] + [f"n{i + churn}"
                                             for i in top_rest]
            sc = np.concatenate([col, col_rest[top_rest]])
            top = np.argsort(-sc, kind="stable")[:5]
            if [ids[j] for j in top] == [e for e, _ in hits] and np.allclose(
                    sc[top], [s for _, s in hits], atol=2e-6):
                matched = True
                break
        assert matched, (g0, g1, hits)


@pytest.fixture
def served():
    """A database behind its HTTP server, and a client."""
    from benchmark.lib.client import Client
    from nornicdb_tpu.api.http_server import HttpServer

    db = nornicdb_tpu.open(auto_embed=False)
    http = HttpServer(db, port=0).start()
    try:
        yield db, Client(http.port)
    finally:
        http.stop()
        db.close()


def _ok(reply):
    status, raw = reply
    assert status == 200, raw[:300]
    return json.loads(raw)


def test_upsert_then_search_over_http_reads_its_writes(served):
    db, client = served
    dims = 512                          # 600 x 512 is past _SMALL_HOST
    vectors = _unit(np.random.default_rng(14).standard_normal((700, dims)))
    _ok(client.request("PUT", "/collections/live", json.dumps(
        {"vectors": {"size": dims, "distance": "Cosine"}}).encode()))

    def upsert(ids):
        return _ok(client.request("PUT", "/collections/live/points",
                                  json.dumps({"points": [
                                      {"id": int(i), "payload": {"v": int(v)},
                                       "vector": vectors[v].tolist()}
                                      for i, v in ids]}).encode()))

    def search(v, limit=3):
        return _ok(client.post("/collections/live/points/search", json.dumps(
            {"vector": vectors[v].tolist(), "limit": limit,
             "with_payload": True}).encode()))["result"]

    upsert([(i, i) for i in range(600)])
    assert search(5)[0]["id"] == 5
    before = _refreshes()
    # a new point is found first by the search that follows its 200
    upsert([(600, 600), (601, 601)])
    hit = search(600)[0]
    assert (hit["id"], hit["payload"]) == (600, {"v": 600})
    assert hit["score"] == pytest.approx(1.0, abs=1e-5)
    # an overwrite hides the old vector: point 5 now holds row 650
    upsert([(5, 650)])
    hits = search(5, limit=10)
    assert 5 not in [h["id"] for h in hits] and hits[0]["score"] < 0.9
    hit = search(650)[0]
    assert (hit["id"], hit["payload"]) == (5, {"v": 650})
    grown = {k: v - before[k] for k, v in _refreshes().items()}
    assert grown == {"rows": 2, "full": 0}
    index = db.qdrant_compat._index("live")
    assert index._capacity * dims > BruteForceIndex._SMALL_HOST


class _SeededEmbedder:
    """Query text -> a vector of the index's width, from the text."""

    dims = DIMS

    def embed(self, text):
        seed = int.from_bytes(text.encode()[:8].ljust(8, b"\0"), "little")
        return np.random.default_rng(seed).standard_normal(DIMS).tolist()

    def embed_batch(self, texts):
        return [self.embed(t) for t in texts]


def _fused_dispatches():
    return sum(e["dispatches"] for e in obs.compile_universe()
               if e["kind"] == "hybrid_fused")


def test_store_after_warm_hybrid_is_found_by_a_hybrid_search(monkeypatch):
    """The native path's share of the same repair: ``device_lease`` hands
    the fused program the arrays the update donates."""
    monkeypatch.setenv("NORNICDB_HYBRID_WALK", "0")
    db = nornicdb_tpu.open(embedder=_SeededEmbedder())
    try:
        rows = _vectors(15, 4300)
        db.store_batch([f"passage w{i % 97} about t{i % 13}"
                        for i in range(4200)], rows[:4200],
                       node_ids=[f"p{i}" for i in range(4200)],
                       labels=["Passage"])
        assert db.search.warm_hybrid(limit=5, max_batch=2) == [1, 2]
        before = _refreshes()
        fused = _fused_dispatches()
        fresh = rows[4250]
        db.store("a zebrafinch passage unlike the others", node_id="late",
                 embedding=fresh.tolist())
        hits = db.search.search("zebrafinch passage", mode="hybrid",
                                query_embedding=fresh.tolist(), limit=5)
        assert hits and hits[0]["id"] == "late"
        again = db.search.search("passage w3", mode="hybrid",
                                 query_embedding=rows[3].tolist(), limit=5)
        assert again[0]["id"] == "p3"
        grown = {k: v - before[k] for k, v in _refreshes().items()}
        assert grown["full"] == 0 and grown["rows"] >= 1
        # both searches went through the fused program on the device
        # arrays (a post-snapshot document's row is then re-fused on the
        # host, which is why the tier counter says ``host`` for it)
        assert _fused_dispatches() == fused + 2
    finally:
        db.close()
