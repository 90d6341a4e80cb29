"""A Qdrant collection with a payload index (ISSUE 34): a filter that is a
``must`` list of ``match.value`` / ``range`` conditions on indexed fields
is evaluated by the scan itself, on int32 columns beside the vectors, and
the answer is exact INSIDE the filter; every other filter answers as it
always has, on the host; the columns follow every kind of write.

On the jitted scan's path (4,500 x 64 pads to 8,192 x 64, past
``_SMALL_HOST``), against a NumPy reference that evaluates each filter
with a predicate of its own, and against a twin collection without a
payload index (today's host path).
"""

import json
import threading

import numpy as np
import pytest

import nornicdb_tpu
from nornicdb_tpu import obs
from nornicdb_tpu.api.qdrant import QdrantCompat, QdrantError
from nornicdb_tpu.ops import similarity
from nornicdb_tpu.search.vector_index import (
    BruteForceIndex,
    StaleFilterPlan,
)
from nornicdb_tpu.storage import MemoryEngine

ROWS, DIMS, LIMIT = 4500, 64, 20
LANGS = ("en", "de", "fr", "es", "ja")


def _unit(m):
    return (m / np.linalg.norm(m, axis=-1, keepdims=True)
            ).astype(np.float32)


def _payload(i):
    return {"t": i % 16, "lang": LANGS[i % 5], "n": i,
            "meta": {"tier": i % 3}, "title": f"doc-{i}"}


def _points(vectors, ids):
    return [{"id": int(i), "vector": vectors[i].tolist(),
             "payload": _payload(int(i))} for i in ids]


def _tier_counts():
    fam = obs.REGISTRY.get("nornicdb_qdrant_filtered_search_total")
    return {t: fam.labels(t).value for t in ("device", "host", "empty")}


def _dispatches(kind):
    return sum(e["dispatches"] for e in obs.compile_universe()
               if e["kind"] == kind)


@pytest.fixture(scope="module")
def world():
    """(compat, vectors): collection ``c`` with payload indexes on three
    fields (one nested), collection ``h`` with the same points and none."""
    vectors = _unit(np.random.default_rng(34).standard_normal((ROWS, DIMS)))
    q = QdrantCompat(MemoryEngine())
    for name in ("c", "h"):
        q.create_collection(name, {"size": DIMS, "distance": "Cosine"})
        q.upsert_points(name, _points(vectors, range(ROWS)))
    q.create_payload_index("c", "t", "integer")
    q.create_payload_index("c", "lang", "keyword")
    q.create_payload_index("c", "n", {"type": "integer"})
    q.create_payload_index("c", "meta.tier", "integer")
    idx = q._index("c")
    assert idx._capacity * DIMS > BruteForceIndex._SMALL_HOST
    assert idx.columns() == {"t": "integer", "lang": "keyword",
                             "n": "integer", "meta.tier": "integer"}
    return q, vectors


def _reference(vectors, payloads, query, passes, limit=LIMIT):
    """Exact top ``limit`` among the rows whose payload ``passes``."""
    scores = vectors @ _unit(query)
    keep = np.asarray([passes(p) for p in payloads])
    order = np.argsort(-np.where(keep, scores, -np.inf), kind="stable")
    top = [int(i) for i in order[:limit] if keep[i]]
    return top, scores[top]


def _query(vectors, seed):
    rng = np.random.default_rng([34, seed])
    return vectors[int(rng.integers(ROWS))] + np.float32(0.05) \
        * rng.standard_normal(DIMS).astype(np.float32)


def _must(*conds):
    return {"must": list(conds)}


def _eq(key, value):
    return {"key": key, "match": {"value": value}}


def _rng(key, **bounds):
    return {"key": key, "range": bounds}


# (name, filter, predicate of the plain reference, tier it must count as)
GRID = [
    ("integer_value", _must(_eq("t", 3)), lambda p: p["t"] == 3, "device"),
    ("keyword_value", _must(_eq("lang", "de")),
     lambda p: p["lang"] == "de", "device"),
    ("nested_key", _must(_eq("meta.tier", 2)),
     lambda p: p["meta"]["tier"] == 2, "device"),
    ("range_gt", _must(_rng("t", gt=12)), lambda p: p["t"] > 12, "device"),
    ("range_gte", _must(_rng("t", gte=12)), lambda p: p["t"] >= 12,
     "device"),
    ("range_lt", _must(_rng("t", lt=2)), lambda p: p["t"] < 2, "device"),
    ("range_lte", _must(_rng("t", lte=2)), lambda p: p["t"] <= 2, "device"),
    ("range_float_bounds", _must(_rng("n", gt=99.5, lte=300.5)),
     lambda p: 99.5 < p["n"] <= 300.5, "device"),
    ("range_both_sides", _must(_rng("n", gte=1000, lt=1400)),
     lambda p: 1000 <= p["n"] < 1400, "device"),
    ("two_fields", _must(_eq("lang", "fr"), _rng("t", gte=4, lte=9)),
     lambda p: p["lang"] == "fr" and 4 <= p["t"] <= 9, "device"),
    ("two_conditions_one_field", _must(_rng("n", gte=50), _rng("n", lt=90)),
     lambda p: 50 <= p["n"] < 90, "device"),
    ("unknown_keyword", _must(_eq("lang", "xx")), lambda p: False, "empty"),
    ("bounds_exclude_each_other", _must(_rng("t", gt=9), _rng("t", lt=3)),
     lambda p: False, "empty"),
    ("no_point_passes", _must(_eq("t", 99)), lambda p: False, "device"),
    ("fewer_than_limit_pass", _must(_rng("n", lte=4)),
     lambda p: p["n"] <= 4, "device"),
]


@pytest.mark.parametrize("name,flt,passes,tier",
                         GRID, ids=[g[0] for g in GRID])
def test_device_path_host_path_and_reference_agree(world, name, flt,
                                                   passes, tier):
    q, vectors = world
    payloads = [_payload(i) for i in range(ROWS)]
    query = _query(vectors, GRID.index((name, flt, passes, tier)))
    want, want_scores = _reference(vectors, payloads, query, passes)
    before = _tier_counts()
    widened = _dispatches("vector_widen")
    served = q.search_points("c", query.tolist(), limit=LIMIT,
                             query_filter=flt)
    after = _tier_counts()
    assert {t: after[t] - before[t] for t in after} == {
        t: float(t == tier) for t in after}
    # answered by the one coalesced scan: no widening round of its own
    assert _dispatches("vector_widen") == widened
    assert [h["id"] for h in served] == want
    np.testing.assert_allclose([h["score"] for h in served], want_scores,
                               atol=2e-6)
    assert all(passes(h["payload"]) for h in served)
    if name == "fewer_than_limit_pass":
        assert len(served) == 5
    # today's host path, on the twin without a payload index
    host = q.search_points("h", query.tolist(), limit=LIMIT,
                           query_filter=flt)
    assert [h["id"] for h in host] == want
    np.testing.assert_allclose([h["score"] for h in host], want_scores,
                               atol=2e-6)


FALLBACK = [
    ("should", {"should": [_eq("t", 3), _eq("t", 4)]}),
    ("must_and_should", {"must": [_eq("lang", "de")],
                         "should": [_eq("t", 3)]}),
    ("must_not", {"must": [_eq("t", 3)], "must_not": [_eq("lang", "de")]}),
    ("match_any", _must({"key": "t", "match": {"any": [1, 2]}})),
    ("match_text", _must({"key": "title", "match": {"text": "doc-12"}})),
    ("has_id", _must({"has_id": [5, 6, 7, 4000]})),
    ("nested", _must({"filter": _must(_eq("t", 3))})),
    ("unindexed_key", _must(_eq("title", "doc-77"))),
    ("is_empty", _must({"is_empty": {"key": "nothing"}}, _eq("t", 3))),
    ("value_of_another_type", _must(_eq("t", 3.0))),
    ("range_on_a_keyword", _must(_rng("lang", gte=1))),
]


@pytest.mark.parametrize("name,flt", FALLBACK, ids=[f[0] for f in FALLBACK])
def test_every_other_filter_answers_as_before_on_the_host(world, name, flt):
    q, vectors = world
    query = _query(vectors, 100 + [f[0] for f in FALLBACK].index(name))
    before = _tier_counts()
    filtered = _dispatches("vector_filtered")
    served = q.search_points("c", query.tolist(), limit=LIMIT,
                             query_filter=flt)
    after = _tier_counts()
    assert after["host"] - before["host"] == 1
    assert after["device"] == before["device"]
    assert _dispatches("vector_filtered") == filtered
    twin = q.search_points("h", query.tolist(), limit=LIMIT,
                           query_filter=flt)
    assert [(h["id"], h["score"]) for h in served] \
        == [(h["id"], h["score"]) for h in twin]
    assert served or name == "range_on_a_keyword"


def test_an_unfiltered_batch_dispatches_the_unchanged_program(world,
                                                              monkeypatch):
    """A search without a filter on a collection WITH a payload index runs
    the plain scan with the plain arguments, under ``microbatch``."""
    q, vectors = world
    calls = []
    inner = BruteForceIndex.search_batch

    def spy(self, queries, k=10, exact=False, **kw):
        calls.append(kw)
        return inner(self, queries, k, exact, **kw)

    def never(*a, **kw):
        raise AssertionError("the filtered program ran")

    monkeypatch.setattr(BruteForceIndex, "search_batch", spy)
    monkeypatch.setattr(similarity, "_cosine_topk_filtered_impl", never)
    plain, filtered = _dispatches("microbatch"), _dispatches(
        "vector_filtered")
    query = _query(vectors, 200)
    served = q.search_points("c", query.tolist(), limit=LIMIT)
    want, _ = _reference(vectors, [{}] * ROWS, query, lambda p: True)
    assert [h["id"] for h in served] == want
    assert calls == [{}]
    assert _dispatches("microbatch") == plain + 1
    assert _dispatches("vector_filtered") == filtered


def _burst(q, requests):
    """Send ``requests`` (vector, filter) through the collection's
    coalescer so that they seal into ONE batch; their answers in order."""
    batcher = q._collection_microbatch("c")
    shipped = batcher._gather_window_s
    batcher._gather_window_s, batcher._last_batch = 1.0, len(requests)
    gate = threading.Barrier(len(requests))
    out, errors = [None] * len(requests), []

    def one(j):
        try:
            gate.wait(timeout=30)
            vec, flt = requests[j]
            out[j] = q.search_points("c", vec.tolist(), limit=LIMIT,
                                     query_filter=flt)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=one, args=(j,))
               for j in range(len(requests))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        batcher._gather_window_s = shipped
    assert errors == []
    return out


@pytest.mark.parametrize("mix", ["filtered_and_unfiltered",
                                 "different_tenants"])
def test_riders_with_different_filters_share_a_batch(world, mix):
    q, vectors = world
    payloads = [_payload(i) for i in range(ROWS)]
    if mix == "filtered_and_unfiltered":
        plans = [(_must(_eq("t", 3)), lambda p: p["t"] == 3),
                 (None, lambda p: True),
                 (_must(_eq("lang", "ja")), lambda p: p["lang"] == "ja"),
                 (None, lambda p: True)]
    else:
        plans = [(_must(_eq("t", t)), lambda p, t=t: p["t"] == t)
                 for t in (0, 5, 5, 11)]
    requests = [(_query(vectors, 300 + j), flt)
                for j, (flt, _) in enumerate(plans)]
    obs.tracing.TRACES.clear()
    before = {(e["b"], e["k"]): e["dispatches"]
              for e in obs.compile_universe()
              if e["kind"] == "vector_filtered"}
    plain = _dispatches("microbatch")
    answers = _burst(q, requests)
    for (vec, _), (_, passes), served in zip(requests, plans, answers):
        want, scores = _reference(vectors, payloads, vec, passes)
        assert [h["id"] for h in served] == want
        np.testing.assert_allclose([h["score"] for h in served], scores,
                                   atol=2e-6)
    grown = {(e["b"], e["k"]): e["dispatches"] - before.get(
        (e["b"], e["k"]), 0) for e in obs.compile_universe()
        if e["kind"] == "vector_filtered"}
    # one batch of four, the filtered program; nothing under `microbatch`
    assert {k: v for k, v in grown.items() if v} == {(4, 64): 1}
    assert _dispatches("microbatch") == plain


def test_spans_say_where_the_filter_was_evaluated(world):
    q, vectors = world
    with obs.tracing.trace("probe") as root:
        q.search_points("c", _query(vectors, 400).tolist(), limit=LIMIT,
                        query_filter=_must(_eq("t", 3), _eq("lang", "en")))
    tree = root.to_dict()

    def walk(node):
        yield node
        for child in node.get("children", ()):
            yield from walk(child)

    spans = {s["name"]: s for s in walk(tree)}
    assert spans["qdrant.filter_plan"]["attrs"] == {
        "tier": "device", "conds": 2, "fields": 2}
    assert spans["index.scan"]["attrs"]["filtered"] == 1
    assert spans["index.scan"]["attrs"]["fields"] == 4
    assert spans["device.dispatch"]["attrs"]["filtered"] == 1
    assert "qdrant.widen" not in spans


# -- the column follows the row ------------------------------------------


def _fresh(rows=ROWS):
    vectors = _unit(np.random.default_rng(35).standard_normal((rows, DIMS)))
    q = QdrantCompat(MemoryEngine())
    q.create_collection("c", {"size": DIMS, "distance": "Cosine"})
    return q, vectors


def _tenant_hits(q, vectors, row, tenant, limit=5):
    return [h["id"] for h in q.search_points(
        "c", vectors[row].tolist(), limit=limit,
        query_filter=_must(_eq("t", tenant)))]


def _codes_match_storage(q):
    """Every live slot's code of ``t`` is its stored payload's value."""
    idx = q._index("c")
    f = idx._col_fields.index("t")
    for ext_id, slot in idx._slot_of.items():
        stored = q.storage.get_node(ext_id).properties["payload"].get("t")
        assert int(idx._columns[f, slot]) == (
            similarity.COL_MISSING if stored is None else stored), ext_id
    dead = np.setdiff1d(np.arange(idx._capacity),
                        np.fromiter(idx._slot_of.values(), np.int64))
    assert np.all(idx._columns[f, dead] == similarity.COL_MISSING)


WRITES = ["new_point", "overwrite_changes_the_value", "delete",
          "compaction", "capacity_growth", "declared_on_filled",
          "payload_without_the_field", "payload_alone_changes"]


@pytest.mark.parametrize("write", WRITES)
def test_the_column_follows_the_row(write):
    q, vectors = _fresh()
    base = range(4096) if write == "capacity_growth" else range(4200)
    if write == "declared_on_filled":
        q.upsert_points("c", _points(vectors, base))
        assert q.search_points("c", vectors[3].tolist(), limit=3)
        q.create_payload_index("c", "t", "integer")
    else:
        q.create_payload_index("c", "t", "integer")
        q.upsert_points("c", _points(vectors, base))
    idx = q._index("c")
    assert _tenant_hits(q, vectors, 19, 3)[0] == 19      # 19 % 16 == 3
    if write == "new_point":
        q.upsert_points("c", _points(vectors, [4300]))   # 4300 % 16 == 12
        assert _tenant_hits(q, vectors, 4300, 12)[0] == 4300
        assert 4300 not in _tenant_hits(q, vectors, 4300, 3)
    elif write == "overwrite_changes_the_value":
        q.upsert_points("c", [{"id": 19, "vector": vectors[19].tolist(),
                               "payload": {"t": 7}}])
        assert _tenant_hits(q, vectors, 19, 7)[0] == 19
        assert 19 not in _tenant_hits(q, vectors, 19, 3)
    elif write == "delete":
        q.delete_points("c", [19])
        assert 19 not in _tenant_hits(q, vectors, 19, 3)
        # the freed slot taken by a point of another tenant
        q.upsert_points("c", _points(vectors, [4300]))
        assert 4300 not in _tenant_hits(q, vectors, 19, 3)
    elif write == "compaction":
        q.delete_points("c", [i for i in base if i % 2])
        assert idx.compact() or idx.compactions
        assert idx._capacity < 8192
        assert _tenant_hits(q, vectors, 20, 4)[0] == 20
        assert set(_tenant_hits(q, vectors, 20, 4, limit=50)) \
            <= {i for i in base if i % 16 == 4}
    elif write == "capacity_growth":
        assert idx._capacity == 4096
        q.upsert_points("c", _points(vectors, range(4096, 4200)))
        assert idx._capacity == 8192
        assert _tenant_hits(q, vectors, 4100, 4)[0] == 4100
        assert _tenant_hits(q, vectors, 19, 3)[0] == 19
    elif write == "payload_alone_changes":     # an upsert with no vector
        q.upsert_points("c", [{"id": 19, "payload": {"t": 7}}])
        assert _tenant_hits(q, vectors, 19, 7)[0] == 19
        assert 19 not in _tenant_hits(q, vectors, 19, 3)
    elif write == "payload_without_the_field":
        q.upsert_points("c", [{"id": 19, "vector": vectors[19].tolist(),
                               "payload": {"other": 1}}])
        assert 19 not in _tenant_hits(q, vectors, 19, 3)
    _codes_match_storage(q)
    got = set(_tenant_hits(q, vectors, 36, 4, limit=4500))
    want = {n.properties["_point_id"] for n in q.storage.get_nodes_by_label(
        q._label("c")) if n.properties["payload"].get("t") == 4}
    assert got == want and got


def test_columns_are_saved_and_loaded_with_the_index(tmp_path):
    q, vectors = _fresh(600)
    q.create_payload_index("c", "t", "integer")
    q.create_payload_index("c", "lang", "keyword")
    q.upsert_points("c", _points(vectors, range(600)))
    q.delete_points("c", [5, 6])
    idx = q._index("c")
    path = str(tmp_path / "index.npz")
    idx.save(path)
    back = BruteForceIndex.load(path)
    assert back.columns() == idx.columns()
    for conds in ([("t", "eq", 3)], [("lang", "eq", "fr"), ("t", "lt", 9)]):
        _, bounds = idx.filter_bounds(conds)
        gen, again = back.filter_bounds(conds)
        assert np.array_equal(bounds, again)
        query = vectors[19][None]
        assert back.search_batch(query, 10, bounds=again[None],
                                 bounds_gen=gen) == idx.search_batch(
            query, 10, bounds=bounds[None], bounds_gen=idx._col_gen)
    assert back.filter_bounds([("lang", "eq", "xx")]) == "empty"


def test_a_search_after_a_payload_changing_upsert_sees_the_new_value():
    """One point moved between two tenants, beside readers: the search
    sent after an upsert's return finds it under its new tenant and not
    under its old one."""
    q, vectors = _fresh()
    q.create_payload_index("c", "t", "integer")
    q.upsert_points("c", _points(vectors, range(4200)))
    stop = threading.Event()
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                tenant = int(rng.integers(16))
                for h in q.search_points(
                        "c", vectors[int(rng.integers(4200))].tolist(),
                        limit=10, query_filter=_must(_eq("t", tenant))):
                    assert h["payload"]["t"] == tenant
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    readers = [threading.Thread(target=reader, args=(s,)) for s in (1, 2, 3)]
    for t in readers:
        t.start()
    try:
        for round_ in range(40):
            new, old = (100, 101) if round_ % 2 else (101, 100)
            q.upsert_points("c", [{"id": 19, "vector": vectors[19].tolist(),
                                   "payload": {"t": new}}])
            assert _tenant_hits(q, vectors, 19, new) == [19]
            assert _tenant_hits(q, vectors, 19, old) == []
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=60)
    assert errors == []
    assert _dispatches("vector_filtered") > 0


def test_a_value_the_column_cannot_hold_sends_the_field_to_the_host():
    """A float in an ``integer`` field equals an integer on the host
    (3.0 == 3): the column cannot say so, so while such a row lives the
    field's filters are evaluated on the host, and on the device again
    once it is gone."""
    q, vectors = _fresh()
    q.create_payload_index("c", "t", "integer")
    q.upsert_points("c", _points(vectors, range(4200)))
    q.upsert_points("c", [{"id": 9000, "vector": vectors[19].tolist(),
                           "payload": {"t": 3.0}}])
    before = _tier_counts()
    assert _tenant_hits(q, vectors, 19, 3)[:2] == [19, 9000] \
        or _tenant_hits(q, vectors, 19, 3)[:2] == [9000, 19]
    assert _tier_counts()["host"] - before["host"] >= 1
    q.delete_points("c", [9000])
    before = _tier_counts()
    assert _tenant_hits(q, vectors, 19, 3)[0] == 19
    assert _tier_counts()["device"] - before["device"] == 1


def test_a_plan_made_before_the_columns_changed_is_refused_not_misread():
    q, vectors = _fresh()
    q.create_payload_index("c", "t", "integer")
    q.upsert_points("c", _points(vectors, range(4200)))
    idx = q._index("c")
    gen, bounds = idx.filter_bounds([("t", "eq", 3)])
    q.create_payload_index("c", "lang", "keyword")
    with pytest.raises(StaleFilterPlan):
        idx.search_batch(vectors[19][None], 5, bounds=bounds[None],
                         bounds_gen=gen)
    # through the surface such a rider is answered on the host
    inner = idx.filter_bounds
    idx.filter_bounds = lambda conds: (gen, bounds)
    try:
        assert _tenant_hits(q, vectors, 19, 3)[0] == 19
    finally:
        idx.filter_bounds = inner
    q.delete_payload_index("c", "lang")
    assert idx.columns() == {"t": "integer"}
    assert _tenant_hits(q, vectors, 19, 3)[0] == 19


def test_at_most_four_indexed_fields_and_only_two_schemas():
    q, vectors = _fresh(100)
    for field in ("a", "b", "c", "d"):
        q.create_payload_index("c", field, "integer")
    with pytest.raises(QdrantError):
        q.create_payload_index("c", "e", "integer")
    with pytest.raises(QdrantError):
        q.create_payload_index("c", "a", "float")
    with pytest.raises(QdrantError):
        q.create_payload_index("c", "a", "keyword")   # indexed as integer
    assert q.create_payload_index("c", "a", "integer")     # idempotent
    assert q._index("c")._columns.shape[0] == 4
    q.delete_payload_index("c", "b")
    q.delete_payload_index("c", "c")
    assert q._index("c")._columns.shape[0] == 2
    assert set(q.get_collection("c")["payload_schema"]) == {"a", "d"}


# -- the REST surface, and a restart -------------------------------------


def _ok(reply):
    status, raw = reply
    assert status == 200, raw[:300]
    return json.loads(raw)["result"]


def test_index_routes_and_a_restart_of_a_disk_backed_database(tmp_path):
    from benchmark.lib.client import Client
    from nornicdb_tpu.api.http_server import HttpServer

    dims = 512                          # 600 x 512 is past _SMALL_HOST
    vectors = _unit(np.random.default_rng(36).standard_normal((600, dims)))

    def search(client, row, tenant):
        return _ok(client.post("/collections/t/points/search", json.dumps(
            {"vector": vectors[row].tolist(), "limit": 3,
             "with_payload": True,
             "filter": _must(_eq("group_id", tenant))}).encode()))

    db = nornicdb_tpu.open(str(tmp_path / "data"), auto_embed=False)
    http = HttpServer(db, port=0).start()
    try:
        client = Client(http.port)
        _ok(client.request("PUT", "/collections/t", json.dumps(
            {"vectors": {"size": dims, "distance": "Cosine"}}).encode()))
        _ok(client.request("PUT", "/collections/t/points", json.dumps(
            {"points": [{"id": i, "vector": vectors[i].tolist(),
                         "payload": {"group_id": f"user-{i % 7}"}}
                        for i in range(600)]}).encode()))
        _ok(client.request("PUT", "/collections/t/index", json.dumps(
            {"field_name": "group_id", "field_schema": {
                "type": "keyword", "is_tenant": True}}).encode()))
        status, _ = client.request("PUT", "/collections/t/index", json.dumps(
            {"field_name": "x", "field_schema": "geo"}).encode())
        assert status == 400
        info = _ok(client.get("/collections/t"))
        assert info["payload_schema"]["group_id"]["data_type"] == "keyword"
        before = _tier_counts()
        hits = search(client, 10, "user-3")
        assert hits[0]["id"] == 10 and all(
            h["payload"]["group_id"] == "user-3" for h in hits)
        assert _tier_counts()["device"] - before["device"] == 1
    finally:
        http.stop()
        db.close()
    db = nornicdb_tpu.open(str(tmp_path / "data"), auto_embed=False)
    http = HttpServer(db, port=0).start()
    try:
        client = Client(http.port)
        info = _ok(client.get("/collections/t"))
        assert set(info["payload_schema"]) == {"group_id"}
        before = _tier_counts()
        assert search(client, 10, "user-3")[0]["id"] == 10
        assert search(client, 10, "user-4")[0]["id"] != 10
        assert _tier_counts()["device"] - before["device"] == 2
        _ok(client.request("DELETE", "/collections/t/index/group_id"))
        assert _ok(client.get("/collections/t"))["payload_schema"] == {}
        before = _tier_counts()
        assert search(client, 10, "user-3")[0]["id"] == 10
        assert _tier_counts()["host"] - before["host"] == 1
    finally:
        http.stop()
        db.close()
