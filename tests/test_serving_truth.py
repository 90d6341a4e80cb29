"""Serving-tier truth (ISSUE 10): per-query tier attribution, the
unified degrade ledger, and the online shadow-parity auditor.

The contracts under test:

- every device-served search/graph query is counted in
  ``nornicdb_served_tier_total{surface,tier}`` and stamps ``served_by``
  on its trace — **rider-accurate**: one rider of a coalesced hybrid
  batch whose live-filter forced a host re-fuse counts ``host`` while
  its batch-mates keep the device tier;
- ladder step-downs land structured records (normalized reason
  vocabulary) in the ledger ring served at ``/admin/degrades``;
- the shadow auditor re-executes sampled device answers on the host
  reference off the hot path: an injected device/host mismatch drops
  the parity gauge, writes a flight-recorder repro dump and surfaces in
  ``/readyz``; with quarantine enabled the tier steps down its existing
  ladder and recovers once the breach clears;
- with auditing enabled at the default sample rate the instrumented
  serving path stays within the established ≤ 2x + 1 ms/op budget and
  the auditor never blocks a dispatch.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.obs import audit
from nornicdb_tpu.obs.metrics import REGISTRY
from nornicdb_tpu.search.bm25 import BM25Index
from nornicdb_tpu.search.microbatch import MicroBatcher
from nornicdb_tpu.search.vector_index import BruteForceIndex

VOCAB = [f"term{i}" for i in range(64)]
D = 32


def _served(surface, tier):
    fam = REGISTRY.get("nornicdb_served_tier_total")
    child = fam.children().get((surface, tier))
    return child.value if child is not None else 0.0


def _counter_value(name, key):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    child = fam.children().get(tuple(str(v) for v in key))
    return child.value if child is not None else 0.0


@pytest.fixture(autouse=True)
def _reset_auditor():
    audit.AUDITOR.set_sample_rate(None)
    audit.AUDITOR.set_quarantine(None)
    audit.AUDITOR.reset()
    yield
    audit.AUDITOR.set_sample_rate(None)
    audit.AUDITOR.set_quarantine(None)
    audit.AUDITOR.reset()


# ---------------------------------------------------------------------------
# taxonomy + vocabulary
# ---------------------------------------------------------------------------


class TestTaxonomy:
    def test_tiers_partition_into_contract_classes(self):
        for surface, tiers in audit.TIERS.items():
            assert tiers[-1] in (audit.TIER_HOST, audit.TIER_CACHED)
            for t in tiers:
                assert t in audit.ALL_TIERS
        for t in audit.ALL_TIERS:
            if t in (audit.TIER_HOST, audit.TIER_CACHED,
                     audit.TIER_SHED):
                # host is the reference, cached is generation-fresh,
                # shed never served an answer (ISSUE 15) — none carry
                # a parity contract
                continue
            exact = t in audit.EXACT_TIERS
            stat = t in audit.STATISTICAL_FLOORS
            assert exact != stat, t  # exactly one contract class
        # the ISSUE's named examples exist under their surfaces
        assert "hybrid_walk_quant" in audit.TIERS["hybrid"]
        assert "hybrid_brute_f32" in audit.TIERS["hybrid"]
        assert "vector_pq" in audit.TIERS["vector"]
        assert "graph_chain_device" in audit.TIERS["graph"]

    def test_floors(self):
        assert audit.tier_floor("graph_chain_device") == 1.0
        assert audit.tier_floor("hybrid_brute_f32") == 1.0
        assert audit.tier_floor("hybrid_walk_f32") == 0.95
        assert audit.tier_floor("vector_pq") == 0.95

    def test_legacy_events_normalize_onto_the_vocabulary(self):
        for event, reason in audit._LEGACY_REASONS.items():
            assert reason in audit.REASONS, (event, reason)
        assert audit.normalize_reason("exact_fallback_itopk") \
            == "itopk_exceeded"
        assert audit.normalize_reason("quant_fallback_changelog") \
            == "changelog_overrun"
        # vocabulary values pass through; unknowns map to error
        for r in audit.REASONS:
            assert audit.normalize_reason(r) == r
        assert audit.normalize_reason("brand_new_event") == "error"

    def test_parity_of(self):
        p = audit.ShadowAuditor.parity_of
        assert p(["a", "b", "c"], ["a", "b", "c"], 3, exact=True) == 1.0
        assert p(["a", "c", "b"], ["a", "b", "c"], 3, exact=True) \
            == pytest.approx(1 / 3)
        # recall ignores order
        assert p(["a", "c", "b"], ["a", "b", "c"], 3, exact=False) == 1.0
        assert p(["x", "y"], ["a", "b"], 2, exact=False) == 0.0
        # host found nothing: agreeing is parity 1, extras are not
        assert p([], [], 5, exact=True) == 1.0
        assert p(["a"], [], 5, exact=True) == 0.0


# ---------------------------------------------------------------------------
# auditor unit behavior
# ---------------------------------------------------------------------------


class TestAuditorUnit:
    def test_rate_parsing(self):
        assert audit._parse_rate("0") == 0.0
        assert audit._parse_rate("off") == 0.0
        assert audit._parse_rate("") == 0.0
        assert audit._parse_rate("1/256") == pytest.approx(1 / 256)
        assert audit._parse_rate("0.5") == 0.5
        assert audit._parse_rate("on") == pytest.approx(1 / 256)
        assert audit._parse_rate("garbage") == 0.0

    def test_sampling_interval_and_budget(self):
        a = audit.ShadowAuditor(rate=0.5, max_qps=1000.0)
        enq = [a.maybe_sample("vector", "vector_brute_f32", ["a"], 1,
                              lambda: ["a"]) for _ in range(10)]
        assert sum(enq) == 5  # every 2nd query at rate 1/2
        a.flush()
        # budget: 1 token/s cap — the second sample inside the same
        # second must be dropped, counted, and never block
        b = audit.ShadowAuditor(rate=1.0, max_qps=1.0)
        assert b.maybe_sample("vector", "vector_brute_f32", ["a"], 1,
                              lambda: ["a"])
        dropped0 = _counter_value("nornicdb_audit_dropped_total",
                                  ("budget",))
        assert not b.maybe_sample("vector", "vector_brute_f32", ["a"],
                                  1, lambda: ["a"])
        assert _counter_value("nornicdb_audit_dropped_total",
                              ("budget",)) == dropped0 + 1

    def test_queue_full_drops_without_blocking(self):
        gate = threading.Event()
        a = audit.ShadowAuditor(rate=1.0, max_qps=1e9, queue_cap=2)

        def slow_ref():
            gate.wait(5)
            return ["a"]

        dropped0 = _counter_value("nornicdb_audit_dropped_total",
                                  ("queue_full",))
        t0 = time.perf_counter()
        results = [a.maybe_sample("vector", "vector_brute_f32", ["a"],
                                  1, slow_ref) for _ in range(8)]
        elapsed = time.perf_counter() - t0
        gate.set()
        a.flush()
        # the worker may have drained at most a couple while enqueuing;
        # the rest must drop — and the WHOLE loop never blocks on the
        # slow reference execution
        assert elapsed < 1.0
        assert results.count(False) >= 4
        assert _counter_value("nornicdb_audit_dropped_total",
                              ("queue_full",)) > dropped0

    def test_host_and_cached_tiers_never_sampled(self):
        a = audit.ShadowAuditor(rate=1.0, max_qps=1e9)
        assert not a.maybe_sample("hybrid", "host", ["a"], 1,
                                  lambda: ["a"])
        assert not a.maybe_sample("hybrid", "cached", ["a"], 1,
                                  lambda: ["a"])

    def test_concurrent_write_drops_sample_instead_of_mismatch(self):
        """A write landing between sampling and the reference replay
        (or during it) makes the comparison meaningless: the sample is
        dropped as ``stale`` — never scored as a device mismatch."""
        a = audit.ShadowAuditor(rate=1.0, max_qps=1e9)
        gen = {"v": 1}
        dropped0 = _counter_value("nornicdb_audit_dropped_total",
                                  ("stale",))
        assert a.maybe_sample(
            "vector", "vector_brute_f32", ["a"], 1,
            ref=lambda: ["TOTALLY-DIFFERENT"],
            versions=dict(gen), versions_now=lambda: {"v": gen["v"]})
        gen["v"] = 2  # the "write" lands before the worker replays
        a.flush()
        time.sleep(0.1)
        assert a.mismatches == 0 and a.sampled == 0
        assert _counter_value("nornicdb_audit_dropped_total",
                              ("stale",)) == dropped0 + 1
        # unchanged versions still score normally
        assert a.maybe_sample(
            "vector", "vector_brute_f32", ["a"], 1, ref=lambda: ["a"],
            versions=dict(gen), versions_now=lambda: dict(gen))
        a.flush()
        time.sleep(0.1)
        assert a.sampled == 1 and a.mismatches == 0

    def test_ref_error_is_a_drop_not_a_mismatch(self):
        a = audit.ShadowAuditor(rate=1.0, max_qps=1e9)

        def boom():
            raise RuntimeError("ref failed")

        assert a.maybe_sample("vector", "vector_brute_f32", ["a"], 1,
                              boom)
        a.flush()
        time.sleep(0.1)
        assert a.mismatches == 0
        assert a.sampled == 0


# ---------------------------------------------------------------------------
# tier attribution through the serving paths
# ---------------------------------------------------------------------------


def _vector_service(n=24, seed=3):
    from nornicdb_tpu.search.service import SearchService

    rng = np.random.default_rng(seed)
    svc = SearchService()
    for i in range(n):
        svc.vectors.add(f"v{i}", rng.standard_normal(D)
                        .astype(np.float32))
    return svc, rng


class TestVectorTierAttribution:
    def test_microbatched_ride_counts_and_stamps_brute_tier(self):
        svc, rng = _vector_service()
        q = rng.standard_normal(D).astype(np.float32)
        before = _served("vector", "vector_brute_f32")
        with obs.trace("wire", method="/test") as root:
            hits = svc.vector_search_candidates(q, 5)
        assert hits
        assert _served("vector", "vector_brute_f32") == before + 1
        assert root.attrs.get("served_by") == "vector_brute_f32"
        # per-tier latency histogram observed this rider
        fam = REGISTRY.get("nornicdb_served_tier_seconds")
        child = fam.children().get(("vector", "vector_brute_f32"))
        assert child is not None and child.snapshot()["count"] >= 1

    def test_exact_path_counts_brute_tier(self):
        svc, rng = _vector_service()
        q = rng.standard_normal(D).astype(np.float32)
        before = _served("vector", "vector_brute_f32")
        svc.vector_search_candidates(q, 5, exact=True)
        assert _served("vector", "vector_brute_f32") == before + 1

    def test_hnsw_counts_host_tier(self):
        svc, rng = _vector_service(n=32)
        from nornicdb_tpu.search.hnsw import HNSWIndex

        items = [(f"v{i}", svc.vectors.get(f"v{i}")) for i in range(32)]
        idx = HNSWIndex(m=4, ef_search=16)
        idx.build(items)
        svc.hnsw = idx
        before = _served("vector", "host")
        svc.vector_search_candidates(
            rng.standard_normal(D).astype(np.float32), 5)
        assert _served("vector", "host") == before + 1

    def test_tier_stage_split_recorded(self):
        svc, rng = _vector_service()
        svc.vector_search_candidates(
            rng.standard_normal(D).astype(np.float32), 5)
        fam = REGISTRY.get("nornicdb_tier_stage_seconds")
        kids = fam.children()
        assert ("vector_brute_f32", "device_dispatch") in kids
        assert ("vector_brute_f32", "coalesce_wait") in kids


def _hybrid_walk_service(monkeypatch, n=320, seed=59):
    """Service whose fused hybrid serves the WALK tier: clustered
    corpus, walk_min_n below the corpus size, inline builds."""
    from nornicdb_tpu.search.service import SearchService
    from nornicdb_tpu.storage import MemoryEngine
    from nornicdb_tpu.storage.types import Node

    monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "50")
    monkeypatch.setenv("NORNICDB_HYBRID_INLINE_BUILD", "1")
    monkeypatch.setenv("NORNICDB_HYBRID_WALK_MIN_N", "100")
    rng = np.random.default_rng(seed)
    cent = (rng.standard_normal((8, D)) * 2.0).astype(np.float32)
    store = MemoryEngine()
    svc = SearchService(storage=store)
    for i in range(n):
        text = " ".join(rng.choice(VOCAB, size=int(rng.integers(3, 10))))
        node = Node(id=f"n{i}", labels=["Doc"],
                    properties={"content": text},
                    embedding=list(
                        (cent[i % 8] + 0.4 * rng.standard_normal(D))
                        .astype(np.float32)))
        store.create_node(node)
        svc.index_node(node)
    return svc, cent, rng


class TestRiderAccurateMidBatchDegrade:
    """ISSUE 10 satellite: a coalesced hybrid batch where ONE rider's
    live-filter forces the host re-fuse must count one host-tier and
    N-1 device-tier queries, with matching ``served_by`` spans."""

    def test_one_rider_degrades_neighbors_keep_walk_tier(
            self, monkeypatch):
        svc, cent, rng = _hybrid_walk_service(monkeypatch)
        # first search builds the fused pipeline + walk graph
        warm = svc.search("term1 term2", limit=5,
                          query_embedding=cent[1])
        assert warm is not None
        fh = svc._fused
        assert fh is not None and fh.cagra is not None \
            and fh.cagra.graph_built
        # freeze rebuild cadence: the tombstone below must be served
        # through the stale graph's live-filter, not a rebuild
        fh.cagra.rebuild_stale_frac = 1e9
        # victim: a doc rider 0 will rank top-1 (it IS the query)
        victim_emb = np.asarray(svc.vectors.get("n0"), np.float32)
        svc.remove_node("n0")

        n_riders = 4
        barrier = threading.Barrier(n_riders)
        spans = [None] * n_riders
        results = [None] * n_riders

        def rider(i):
            emb = victim_emb if i == 0 else cent[(i % 7) + 1]
            with obs.trace("wire", method=f"/rider{i}") as root:
                barrier.wait(5)
                results[i] = svc.search(
                    f"term{i} term{i + 1}", limit=5,
                    query_embedding=np.asarray(emb, np.float32))
            spans[i] = root

        host0 = _served("hybrid", "host")
        walk0 = _served("hybrid", "hybrid_walk_f32")
        threads = [threading.Thread(target=rider, args=(i,))
                   for i in range(n_riders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        assert all(r is not None for r in results)
        # rider 0's live-filter correction made ITS row host; the
        # other riders kept the walk tier — rider-accurate counts
        assert _served("hybrid", "host") == host0 + 1
        assert _served("hybrid", "hybrid_walk_f32") == walk0 + 3
        assert spans[0].attrs.get("served_by") == "host"
        for i in range(1, n_riders):
            assert spans[i].attrs.get("served_by") == "hybrid_walk_f32", i
        # the batch's live-filter step-down landed in the ledger
        recent = audit.degrade_snapshot(limit=20)
        assert any(r["reason"] == "live_filter"
                   and r["from_tier"] == "hybrid_walk_f32"
                   and r["to_tier"] == "host" for r in recent)

    def test_host_served_query_counts_once_not_twice(self, monkeypatch):
        """A fused-eligible query that fell to the host hybrid path
        counts ONE hybrid:host serve — the nested vector ride inside
        it is a sub-dispatch, not a second served query."""
        from nornicdb_tpu.search.service import SearchService
        from nornicdb_tpu.storage import MemoryEngine
        from nornicdb_tpu.storage.types import Node

        monkeypatch.setenv("NORNICDB_HYBRID_FUSED", "0")  # host serves
        rng = np.random.default_rng(23)
        store = MemoryEngine()
        svc = SearchService(storage=store)
        for i in range(30):
            node = Node(id=f"h{i}", labels=["Doc"],
                        properties={"content": f"term{i % 5} body"},
                        embedding=list(rng.standard_normal(D)
                                       .astype(np.float32)))
            store.create_node(node)
            svc.index_node(node)
        host0 = _served("hybrid", "host")
        vec0 = sum(c.value for (s, _t), c in
                   REGISTRY.get("nornicdb_served_tier_total")
                   .children().items() if s == "vector")
        svc.search("term1 term2", limit=5,
                   query_embedding=rng.standard_normal(D)
                   .astype(np.float32))
        assert _served("hybrid", "host") == host0 + 1
        vec1 = sum(c.value for (s, _t), c in
                   REGISTRY.get("nornicdb_served_tier_total")
                   .children().items() if s == "vector")
        assert vec1 == vec0  # no second increment for the same query

    def test_brute_tier_counts_when_walk_disabled(self, monkeypatch):
        svc, cent, rng = _hybrid_walk_service(monkeypatch, n=160)
        monkeypatch.setenv("NORNICDB_HYBRID_WALK_MIN_N", "100000")
        svc._fused = None  # re-wrap under the new walk floor
        before = _served("hybrid", "hybrid_brute_f32")
        with obs.trace("wire", method="/t") as root:
            svc.search("term3 term4", limit=5, query_embedding=cent[2])
        assert _served("hybrid", "hybrid_brute_f32") == before + 1
        assert root.attrs.get("served_by") == "hybrid_brute_f32"


# ---------------------------------------------------------------------------
# degrade ledger
# ---------------------------------------------------------------------------


class TestDegradeLedger:
    def test_cagra_itopk_fallback_lands_structured_record(self):
        from nornicdb_tpu.search.cagra import CagraIndex

        rng = np.random.default_rng(11)
        idx = CagraIndex(min_n=32, itopk=16, n_seeds=32, hash_bits=10)
        idx.add_batch([(f"v{i}", rng.standard_normal(16)
                        .astype(np.float32)) for i in range(64)])
        assert idx.build()
        before = _counter_value(
            "nornicdb_degrade_total",
            ("vector", "vector_walk_f32", "vector_brute_f32",
             "itopk_exceeded"))
        with obs.trace("wire", method="/t") as root:
            idx.search_batch(rng.standard_normal((1, 16))
                             .astype(np.float32), k=32)
        assert _counter_value(
            "nornicdb_degrade_total",
            ("vector", "vector_walk_f32", "vector_brute_f32",
             "itopk_exceeded")) == before + 1
        rec = next(r for r in audit.degrade_snapshot(20)
                   if r["reason"] == "itopk_exceeded")
        # schema: every ledger record carries the full edge + versions
        assert rec["surface"] == "vector"
        assert rec["from_tier"] == "vector_walk_f32"
        assert rec["to_tier"] == "vector_brute_f32"
        assert "ts" in rec and "index" in rec
        assert "build_seq" in rec["versions"]
        assert rec["trace_id"]  # grafted into the owning trace
        assert "degrade" in root.span_names()

    def test_ring_is_bounded(self):
        ledger = audit.DegradeLedger(capacity=16)
        for i in range(40):
            ledger.record({"reason": f"r{i % 3}"})
        assert ledger.recorded == 40
        snap = ledger.snapshot(limit=100)
        assert len(snap) == 16
        assert snap[0]["reason"] == "r0"  # newest (i=39) first


# ---------------------------------------------------------------------------
# HTTP admin + readyz surfaces
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving():
    import nornicdb_tpu
    from nornicdb_tpu.api.http_server import HttpServer

    db = nornicdb_tpu.open(auto_embed=False)
    rng = np.random.default_rng(21)
    for i in range(24):
        db.store(f"doc {i} term{i % 7}", node_id=f"st-{i}",
                 embedding=list(rng.standard_normal(D)
                                .astype(np.float32)))
    db.search.search("term1", mode="text")  # stand up the indexes
    http = HttpServer(db, port=0).start()
    yield {"db": db, "http": http}
    http.stop()
    db.close()


def _http_get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        return json.loads(resp.read())


def _readyz(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestAdminSurfaces:
    def test_admin_degrades_schema(self, serving):
        audit.record_degrade("hybrid", "hybrid_walk_f32",
                             "hybrid_brute_f32", "underfill",
                             index="test:deg", versions={"g": 1})
        doc = _http_get(serving["http"].port, "/admin/degrades")
        assert set(doc) >= {"recorded", "capacity", "by_reason",
                            "degrades"}
        assert doc["recorded"] >= 1
        assert doc["degrades"][0]["ts"] >= doc["degrades"][-1]["ts"]
        rec = next(r for r in doc["degrades"]
                   if r.get("index") == "test:deg")
        assert set(rec) >= {"ts", "surface", "from_tier", "to_tier",
                            "reason"}
        assert rec["reason"] in audit.REASONS
        assert doc["by_reason"].get("underfill", 0) >= 1
        # /admin/degrades/<limit> truncates
        doc2 = _http_get(serving["http"].port, "/admin/degrades/1")
        assert len(doc2["degrades"]) <= 1

    def test_telemetry_carries_tier_mix_and_parity(self, serving):
        db = serving["db"]
        db.search.vector_search_candidates(
            np.zeros(D, np.float32) + 0.1, 3)
        doc = _http_get(serving["http"].port, "/admin/telemetry")
        assert "tiers" in doc and "parity" in doc
        assert doc["tiers"].get("vector", {}).get(
            "vector_brute_f32", 0) >= 1
        assert set(doc["parity"]) >= {"enabled", "sample_rate",
                                      "sampled", "mismatches", "tiers",
                                      "quarantine"}


class TestInjectedMismatch:
    """Acceptance: a monkeypatched device answer produces a
    parity-gauge drop, a flight-recorder repro dump, and a /readyz
    reason."""

    def test_mismatch_gauge_dump_and_readyz(self, serving, monkeypatch,
                                            tmp_path):
        from nornicdb_tpu.obs import slo

        db = serving["db"]
        svc = db.search
        monkeypatch.setenv("NORNICDB_OBS_DUMP_DIR", str(tmp_path))
        monkeypatch.setenv("NORNICDB_AUDIT_WINDOW", "8")
        monkeypatch.setenv("NORNICDB_AUDIT_MIN_SAMPLES", "2")
        monkeypatch.setenv("NORNICDB_AUDIT_DUMP_INTERVAL_S", "0")
        monkeypatch.setattr(slo, "_engine", None)  # pick up dump dir
        audit.AUDITOR.set_sample_rate(1.0)

        orig = svc.vectors.search_batch

        def mangled(queries, k=10, exact=False):
            out = orig(queries, k, exact=exact)
            if exact:
                return out  # the host reference stays honest
            return [list(reversed(row)) for row in out]

        monkeypatch.setattr(svc.vectors, "search_batch", mangled)
        rng = np.random.default_rng(77)
        for _ in range(4):
            svc.vector_search_candidates(
                rng.standard_normal(D).astype(np.float32), 5)
        audit.AUDITOR.flush()
        time.sleep(0.2)

        fam = REGISTRY.get("nornicdb_parity_ratio")
        child = fam.children().get(("vector", "vector_brute_f32"))
        assert child is not None and child.value < 1.0
        assert _counter_value("nornicdb_audit_mismatch_total",
                              ("vector", "vector_brute_f32")) >= 1
        # self-contained repro dump through the PR 5 flight recorder
        dumps = sorted(glob.glob(str(tmp_path / "flightrec-*.jsonl")))
        assert dumps, os.listdir(tmp_path)
        lines = [json.loads(ln) for ln in
                 open(dumps[-1], encoding="utf-8")]
        meta = lines[0]
        assert meta["reason"].startswith(
            "parity_mismatch:vector_brute_f32")
        repro = next(ln for ln in lines if ln["kind"] == "parity_repro")
        rec = repro["record"]
        assert rec["tier"] == "vector_brute_f32"
        assert rec["device_ids"] and rec["host_ids"]
        assert rec["device_ids"] != rec["host_ids"]
        assert "versions" in rec and rec["parity"] < 1.0
        # the dump also carries the tier mix / degrade / parity state
        kinds = {ln["kind"] for ln in lines}
        assert {"tiers", "degrades", "parity"} <= kinds
        # sustained breach surfaces in /readyz
        status, doc = _readyz(serving["http"].port)
        assert status == 503
        assert any(r.startswith("parity_breach:vector:vector_brute_f32")
                   for r in doc["reasons"])
        assert doc["checks"]["parity_breaches"] >= 1
        # clears once the device answers heal and the window refills
        monkeypatch.setattr(svc.vectors, "search_batch", orig)
        for _ in range(16):
            svc.vector_search_candidates(
                rng.standard_normal(D).astype(np.float32), 5)
        audit.AUDITOR.flush()
        time.sleep(0.2)
        status, doc = _readyz(serving["http"].port)
        assert status == 200, doc


class TestQuarantine:
    """With quarantine enabled a breached tier steps down its existing
    ladder (the real serving gate, not a mock) and recovers after the
    breach clears."""

    def test_walk_tier_steps_down_and_recovers(self, monkeypatch):
        from nornicdb_tpu.search.hybrid_fused import FusedHybrid
        from nornicdb_tpu.search.microbatch import pow2_bucket
        from nornicdb_tpu.search.bm25 import tokenize

        monkeypatch.setenv("NORNICDB_AUDIT_WINDOW", "4")
        monkeypatch.setenv("NORNICDB_AUDIT_MIN_SAMPLES", "2")
        monkeypatch.setenv("NORNICDB_AUDIT_QUARANTINE_S", "1.0")
        audit.AUDITOR.set_sample_rate(1.0)
        audit.AUDITOR.set_quarantine(True)

        rng = np.random.default_rng(13)
        cent = (rng.standard_normal((4, D)) * 2.0).astype(np.float32)
        bm25 = BM25Index()
        brute = BruteForceIndex()
        for i in range(200):
            words = rng.choice(VOCAB, size=6)
            bm25.index(f"d{i}", " ".join(words))
            brute.add(f"d{i}", cent[i % 4]
                      + 0.4 * rng.standard_normal(D).astype(np.float32))
        fh = FusedHybrid(bm25, brute, min_n=1, walk_min_n=1)
        assert fh.build()
        fh.cagra.min_n = 1
        assert fh.cagra.build()

        def rows(n=1):
            kq = pow2_bucket(16)
            extras = [{"tokens": tokenize("term1 term2"), "n_cand": 16,
                       "w": (1.0, 1.0)} for _ in range(n)]
            embs = np.asarray([cent[0]] * n, np.float32)
            return fh.search_batch(embs, kq, extras)

        assert rows()[0]["served_by"] == "hybrid_walk_f32"
        # breach the walk tier: injected bad parity samples
        quarantined_at = time.time()
        for _ in range(3):
            audit.AUDITOR.maybe_sample(
                "hybrid", "hybrid_walk_f32", ["x", "y", "z"], 3,
                lambda: ["a", "b", "c"])
        audit.AUDITOR.flush()
        deadline = time.time() + 5
        while not audit.parity_breaches() and time.time() < deadline:
            time.sleep(0.01)
        assert audit.parity_breaches()
        assert not audit.tier_allowed("hybrid_walk_f32")
        # the tier steps DOWN its ladder: brute-fused serves, ledger
        # records the quarantine step
        row = rows()[0]
        assert row["served_by"] == "hybrid_brute_f32"
        assert any(r["reason"] == "quarantine"
                   and r["from_tier"] == "hybrid_walk_f32"
                   for r in audit.degrade_snapshot(10))
        # after the quarantine window the tier re-probes; good samples
        # heal the window and the breach clears
        time.sleep(max(0.0, quarantined_at + 1.1 - time.time()))
        assert audit.tier_allowed("hybrid_walk_f32")
        assert rows()[0]["served_by"] == "hybrid_walk_f32"
        for _ in range(8):
            audit.AUDITOR.maybe_sample(
                "hybrid", "hybrid_walk_f32", ["a", "b", "c"], 3,
                lambda: ["a", "b", "c"])
        audit.AUDITOR.flush()
        time.sleep(0.2)
        assert not audit.parity_breaches()
        assert rows()[0]["served_by"] == "hybrid_walk_f32"


# ---------------------------------------------------------------------------
# overhead guard (acceptance): auditing on, hot path within budget
# ---------------------------------------------------------------------------


class TestOverheadGuard:
    def test_audited_search_path_within_budget(self):
        """The tier-attributed + audit-sampled serving path (counter,
        tier histogram, stage split, sampling decision at the default
        1/256 rate) vs the same path with telemetry disabled. Budget:
        ≤ 2x + 1 ms/op — the same guard the obs layers are held to."""
        idx = BruteForceIndex()
        rng = np.random.default_rng(11)
        vecs = rng.standard_normal((512, D)).astype(np.float32)
        idx.add_batch([(f"v{i}", vecs[i]) for i in range(512)])
        mb = MicroBatcher(idx.search_batch, surface="t-audit",
                          tier_surface="vector")
        n = 300

        def one(i):
            with obs.trace("wire", method="/audited"):
                hits = mb.search(vecs[i % 512], 10)
                if audit.sampling_active():
                    tier = audit.last_served()
                    if tier:
                        audit.maybe_sample(
                            "vector", tier, [h for h, _ in hits], 10,
                            lambda: [h for h, _ in hits])

        def measure():
            for i in range(30):
                one(i)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(n):
                    one(i)
                best = min(best, time.perf_counter() - t0)
            return best

        audit.AUDITOR.set_sample_rate(1.0 / 256.0)
        t_on = measure()
        audit.AUDITOR.flush()
        obs.set_enabled(False)
        try:
            t_off = measure()
        finally:
            obs.set_enabled(True)
            audit.AUDITOR.set_sample_rate(None)
        per_op_on = t_on / n
        per_op_off = t_off / n
        assert per_op_on <= 2.0 * per_op_off + 1e-3, (
            f"audited {per_op_on * 1e6:.1f}us/op vs "
            f"bare {per_op_off * 1e6:.1f}us/op")


# ---------------------------------------------------------------------------
# catalog lint extensions
# ---------------------------------------------------------------------------


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCatalogLintExtensions:
    def _lint(self):
        import sys

        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import check_metrics_catalog as lint
        finally:
            sys.path.pop(0)
        return lint

    def test_tier_and_reason_vocabulary_documented(self):
        lint = self._lint()
        with open(os.path.join(REPO, "docs", "observability.md"),
                  encoding="utf-8") as f:
            doc = f.read()
        tiers, reasons = lint.tier_vocabulary()
        assert not lint.missing_terms(doc, tiers)
        assert not lint.missing_terms(doc, reasons)

    def test_declared_kinds_documented_fresh_process(self):
        """Dispatch kinds must come from a FRESH interpreter: the suite
        process has recorded runtime shapes (test kinds, microbatch)
        that are not part of the import-time declared vocabulary."""
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_metrics_catalog.py")],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
        assert verdict["missing_kinds"] == [], verdict
        assert verdict["missing_tiers"] == [], verdict
        assert verdict["missing_reasons"] == [], verdict
        assert proc.returncode == 0, verdict

    def test_lint_flags_undocumented_vocabulary(self):
        lint = self._lint()
        doc = "served_tier_total only mentions hybrid_brute_f32 here"
        missing = lint.missing_terms(doc, ["hybrid_brute_f32",
                                           "vector_pq"])
        assert missing == ["vector_pq"]
        # substring of a documented name must not pass
        assert lint.missing_terms("hybrid_brute_f32_extra",
                                  ["hybrid_brute_f32"]) \
            == ["hybrid_brute_f32"]

    def test_parity_gauge_and_degrade_families_registered(self):
        for name in ("nornicdb_parity_ratio",
                     "nornicdb_audit_sampled_total",
                     "nornicdb_audit_mismatch_total",
                     "nornicdb_served_tier_total",
                     "nornicdb_degrade_total"):
            assert REGISTRY.get(name) is not None, name
