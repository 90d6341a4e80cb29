"""The rows of the lexical matrix follow the batch's terms (ISSUE 33):
``DeviceBM25.plan`` takes the smaller of two row buckets a batch bucket
(``half`` = 8 x B, no less than 16; ``full`` = 16 x B) that holds the
batch's distinct scoring terms, both give the same bits, and the warm
call compiles both, so which one a batch takes costs no compile.
"""

from __future__ import annotations

import numpy as np
import pytest

from nornicdb_tpu.obs import REGISTRY
from nornicdb_tpu.search import device_bm25
from nornicdb_tpu.search.bm25 import BM25Index
from nornicdb_tpu.search.device_bm25 import DeviceBM25, lex_rows
from nornicdb_tpu.search.hybrid_fused import FusedHybrid
from nornicdb_tpu.search.microbatch import pow2_bucket
from nornicdb_tpu.search.service import SearchService

from test_hybrid_fused import (D, PARITY_QUERIES, _corpus, _counter_value,
                               _fused_rows)
from test_hybrid_native import _corpus as _native_corpus

BUCKETS = ("half", "full", "over")


def _plan_rows():
    return {bucket: _counter_value(
        REGISTRY, "nornicdb_device_bm25_plan_rows_total",
        {"bucket": bucket}) for bucket in BUCKETS}


def _growth(before):
    after = _plan_rows()
    return {b: after[b] - before[b] for b in BUCKETS}


# -- (i) which bucket a batch takes -------------------------------------------


@pytest.fixture(scope="module")
def wide_lex():
    """600 documents, each with a word of its own: a vocabulary that can
    fill the `over` bucket of B = 32 (513 terms)."""
    bm25 = BM25Index()
    for i in range(600):
        bm25.index(f"d{i}", f"own{i:03d} shared{i % 7}")
    dev = DeviceBM25(bm25, min_n=1)
    assert dev.build()
    return dev


def _edges():
    for b in (1, 2, 4, 8, 16, 32):
        half, full = max(16, 8 * b), 16 * b
        want = {half - 1: (half, "half"), half: (half, "half"),
                full - 1: (full, "full"), full: (full, "full"),
                full + 1: (pow2_bucket(full + 1), "over")}
        # at B = 1 the two are one program, and it reads `half`
        want[half + 1] = (full, "full") if half < full \
            else (pow2_bucket(half + 1), "over")
        if half == full:
            want[full - 1] = want[full] = (half, "half")
        for n, (u, bucket) in sorted(want.items()):
            yield pytest.param(b, n, u, bucket, id=f"b{b}-terms{n}-{bucket}")


@pytest.mark.parametrize("b,n_terms,u,bucket", list(_edges()))
def test_plan_takes_the_smaller_bucket_that_holds_the_terms(
        wide_lex, b, n_terms, u, bucket):
    assert lex_rows(n_terms, b) == (u, bucket)
    snap = wide_lex._snap
    terms = [f"own{i:03d}" for i in range(n_terms)]
    # dealt round the riders, and a word no document has: it scores
    # nothing and takes no row
    rows = [terms[i::b] + ["absent"] for i in range(b)]
    before = _plan_rows()
    tstart, tlen, sel, _ = wide_lex.plan(snap, rows, b)
    assert sel.shape == (b, u)
    assert tstart.shape == tlen.shape == (u,)
    assert wide_lex._plan_cost.shape == (n_terms, n_terms, u)
    assert int((tlen > 0).sum()) == n_terms
    assert int((sel > 0).sum()) == n_terms
    grew = _growth(before)
    assert grew == {**dict.fromkeys(BUCKETS, 0.0), bucket: 1.0}


def test_the_two_widths_have_names_and_no_switch():
    assert device_bm25.LEX_TERMS_PER_QUERY == 16
    assert device_bm25.LEX_TERMS_HALF == 8
    assert device_bm25.LEX_ROWS_MIN == 16
    assert [device_bm25.row_buckets(b) for b in (1, 2, 4, 16, 32)] == [
        (16, 16), (16, 32), (32, 64), (128, 256), (256, 512)]
    # an unbucketed caller (b = 0) plans as B = 1
    assert lex_rows(3, 0) == (16, "half")


# -- (ii) the same bits from either bucket ------------------------------------


def _forced(bucket):
    def rows(n_terms, b_bucket):
        _, full = device_bm25.row_buckets(b_bucket)
        return (full, "full") if bucket == "full" else (2 * full, "over")
    return rows


@pytest.mark.parametrize("layout", ["single", "shard_loop"])
@pytest.mark.parametrize("forced", ["full", "over"])
def test_half_and_forced_wider_rows_give_the_same_bits(
        monkeypatch, layout, forced):
    bm25, brute, rng = _corpus(600, seed=23)
    fh = FusedHybrid(bm25, brute, min_n=1,
                     n_shards=1 if layout == "single" else 2)
    assert fh.build()
    # the sharded layout on one device: the reference merge the mesh
    # program is held to (test_hybrid_fused.TestShardedParity)
    fh.lex._snap.pop("mesh", None)
    qs = PARITY_QUERIES[:8]
    embs = rng.standard_normal((len(qs), D)).astype(np.float32)
    before = _plan_rows()
    narrow = _fused_rows(fh, qs, embs, 30)
    assert fh.lex._plan_cost.shape[2] == 64
    assert _growth(before) == {"half": 1.0, "full": 0.0, "over": 0.0}
    monkeypatch.setattr(device_bm25, "lex_rows", _forced(forced))
    wide = _fused_rows(fh, qs, embs, 30)
    assert fh.lex._plan_cost.shape[2] == (128 if forced == "full" else 256)
    assert all(r is not None for r in narrow)
    assert any(r["lex"] for r in narrow) and any(r["fused"] for r in narrow)
    for a, b in zip(narrow, wide):
        # (id, score) lists: a float equal to a float is the same bits
        for part in ("lex", "vec", "fused"):
            assert a[part] == b[part]
    # and the lexical-only device search, which plans the same way
    lex_wide = fh.lex.search_batch(qs, 30)
    monkeypatch.undo()
    assert fh.lex.search_batch(qs, 30) == lex_wide


# -- (iii), (iv) both buckets are warm ----------------------------------------


@pytest.fixture(scope="module")
def native_service():
    svc = SearchService()
    ids, texts, vectors = _native_corpus(4500, seed=33)
    svc.index_batch(ids, texts, vectors)
    return svc, vectors


def test_warm_hybrid_still_returns_the_batch_buckets(native_service):
    svc, _ = native_service
    before = _plan_rows()
    assert svc.warm_hybrid(limit=10, max_batch=4) == [1, 2, 4]
    # B = 1 has one program, B = 2 and 4 two each; nothing beyond `full`
    assert _growth(before) == {"half": 3.0, "full": 2.0, "over": 0.0}


def test_after_the_warm_call_neither_bucket_compiles(native_service):
    from jax._src import monitoring

    svc, vectors = native_service
    assert svc.warm_hybrid(limit=10, max_batch=8) == [1, 2, 4, 8]
    fused = svc._fused
    snap = fused.lex._snap
    # common words, not the rare ones the warm call planned
    order = np.argsort(-np.diff(snap["off_sh"][0]))
    words = [snap["terms"][int(i)] for i in order[:100]]
    compiled = []

    def listener(event, secs, **kw):
        if "backend_compile" in event:
            compiled.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        before = _plan_rows()
        for n_terms, u in ((40, 64), (100, 128)):
            extras = [{"tokens": tuple(words[i:n_terms:8]), "n_cand": 30,
                       "w": (1.0, 1.0)} for i in range(8)]
            rows = fused.search_batch(vectors[:8], 32, extras)
            assert fused.lex._plan_cost.shape[1:] == (n_terms, u)
            assert all(r is not None and r["lex"] and r["fused"]
                       for r in rows)
        assert compiled == []
        assert _growth(before) == {"half": 1.0, "full": 1.0, "over": 0.0}
    finally:
        monitoring.unregister_event_duration_listener(listener)


def test_rare_terms_are_live_and_short(native_service):
    svc, _ = native_service
    svc.warm_hybrid(limit=10, max_batch=1)
    lex = svc._fused.lex
    snap = lex._snap
    rare = lex.rare_terms(snap, 65)
    assert len(rare) == len(set(rare)) == 65
    plen = np.diff(snap["off_sh"][0])
    longest = max(int(plen[snap["vocab"][t]]) for t in rare)
    assert 1 <= longest <= int(np.sort(plen)[2 * 65])
