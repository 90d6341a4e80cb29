"""Operability layer (ISSUE 5): resource & freshness accounting, SLO
burn-rate health with /readyz, label-cardinality caps and percentile null
safety.

The acceptance contract pinned here: /metrics exposes device-memory and
freshness-lag gauges for all three device-resident index families;
/readyz flips to degraded during cagra/device-bm25 background rebuilds
and under injected MicroBatcher queue saturation, then recovers; the
SLO engine computes multi-window burn rates from the existing latency
histograms and writes a flight-recorder dump on breach.
"""

import gc
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.obs.metrics import Registry
from nornicdb_tpu.obs.slo import Objective, SloEngine
from nornicdb_tpu.search.bm25 import BM25Index
from nornicdb_tpu.search.cagra import CagraIndex
from nornicdb_tpu.search.device_bm25 import DeviceBM25
from nornicdb_tpu.search.microbatch import MicroBatcher
from nornicdb_tpu.search.vector_index import BruteForceIndex


# ---------------------------------------------------------------------------
# label-cardinality cap (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


class TestCardinalityCap:
    def test_overflow_folds_into_other(self):
        r = Registry(max_label_children=3)
        c = r.counter("nornicdb_t_total", "t", labels=("collection",))
        for i in range(8):
            c.labels(f"col{i}").inc()
        text = r.render()
        # first 3 collections materialized; the 5 overflow increments
        # all landed on one __other__ series
        assert 'nornicdb_t_total{collection="col0"} 1' in text
        assert 'nornicdb_t_total{collection="col2"} 1' in text
        assert 'nornicdb_t_total{collection="col5"}' not in text
        assert 'nornicdb_t_total{collection="__other__"} 5' in text
        dropped = r.counter("nornicdb_metric_labels_dropped_total",
                            labels=("metric",))
        assert dropped.labels("nornicdb_t_total").value == 5

    def test_existing_children_unaffected_and_histograms_fold(self):
        r = Registry(max_label_children=2)
        h = r.histogram("nornicdb_t_seconds", "t", labels=("m",),
                        buckets=(0.1, 1.0))
        h.labels("a").observe(0.05)
        h.labels("b").observe(0.05)
        h.labels("c").observe(0.5)  # folds
        h.labels("a").observe(0.05)  # existing child keeps working
        text = r.render()
        assert 'nornicdb_t_seconds_count{m="a"} 2' in text
        assert 'nornicdb_t_seconds_count{m="__other__"} 1' in text
        assert '{m="c"}' not in text

    def test_default_cap_from_env(self, monkeypatch):
        monkeypatch.setenv("NORNICDB_OBS_MAX_LABELS", "4")
        assert Registry().max_label_children == 4
        monkeypatch.setenv("NORNICDB_OBS_MAX_LABELS", "junk")
        assert Registry().max_label_children > 0


# ---------------------------------------------------------------------------
# percentile math on empty/new histograms (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


class TestPercentileNullSafety:
    def test_labeled_family_without_children_returns_none(self):
        r = Registry()
        h = r.histogram("nornicdb_fresh_seconds", "t", labels=("m",))
        # no child materialized yet: quantile/snapshot must not raise
        assert h.quantile(0.95) is None
        assert h.snapshot()["count"] == 0

    def test_latency_summary_include_empty_reports_nulls(self):
        r = Registry()
        r.histogram("nornicdb_idle_seconds", "t", labels=("m",))
        empty_child = r.histogram("nornicdb_new_seconds", "t",
                                  labels=("m",))
        empty_child.labels("x")  # materialized, zero observations
        assert obs.latency_summary(r) == {}  # default: skip empty
        full = obs.latency_summary(r, include_empty=True)
        assert full["nornicdb_idle_seconds"]["p95_ms"] is None
        entry = full['nornicdb_new_seconds{m="x"}']
        assert entry["count"] == 0
        assert entry["p50_ms"] is None and entry["p99_ms"] is None

    def test_admin_telemetry_serves_nulls_not_500(self, serving):
        # a brand-new labeled series in the process registry: the admin
        # endpoint must report it with null percentiles, never raise
        fam = obs.REGISTRY.histogram(
            f"nornicdb_opstest_{time.time_ns()}_seconds", "t",
            labels=("m",))
        fam.labels("fresh")
        doc = _http_get(serving["http"].port, "/admin/telemetry")
        series = [k for k in doc["latency"] if "opstest" in k]
        assert series, "empty series missing from include_empty summary"
        entry = doc["latency"][series[0]]
        assert entry["count"] == 0
        assert entry["p50_ms"] is None


# ---------------------------------------------------------------------------
# resource & freshness accounting (ISSUE 5 tentpole pillar 1)
# ---------------------------------------------------------------------------


class TestResourceAccounting:
    def test_brute_stats_memory_and_changelog(self):
        idx = BruteForceIndex()
        rng = np.random.default_rng(1)
        for i in range(40):
            idx.add(f"v{i}", rng.standard_normal(16).astype(np.float32))
        for i in range(10):
            idx.remove(f"v{i}")
        s = idx.resource_stats()
        assert s["rows"] == 30
        assert s["capacity"] >= 40
        assert s["host_bytes"] > 0
        assert 0 < s["dead_fraction"] < 1
        assert s["changelog_depth"] == 40  # removes aren't logged
        assert s["changelog_cap"] >= 4096
        # device arrays not materialized yet (small host-path corpus)
        assert s["device_bytes"] == 0
        with idx.device_lease():
            pass
        assert idx.resource_stats()["device_bytes"] > 0

    def test_bm25_stats_postings_and_tombstones(self):
        bm = BM25Index()
        for i in range(30):
            bm.index(f"d{i}", f"alpha beta w{i % 7} gamma")
        bm.index("d0", "alpha replaced")  # tombstones the old slot
        s = bm.resource_stats()
        assert s["rows"] == 30
        assert s["capacity"] == 31  # one tombstone
        assert s["dead_fraction"] > 0
        assert s["postings"] > 0 and s["host_bytes"] > 0
        assert s["changelog_depth"] == 31
        assert s["changelog_cap"] >= 4096

    def test_cagra_stats_graph_bytes_and_mutation_gap(self):
        rng = np.random.default_rng(2)
        idx = CagraIndex(min_n=64, n_seeds=64, hash_bits=10)
        idx.add_batch([(f"v{i}", rng.standard_normal(8).astype(np.float32))
                       for i in range(128)])
        assert idx.build()
        s = idx.resource_stats()
        assert s["rows"] == 128
        assert s["device_bytes"] > 0
        assert s["mutation_gap"] == 0
        assert s["rebuild_in_flight"] == 0.0
        idx.add("fresh", rng.standard_normal(8).astype(np.float32))
        assert idx.resource_stats()["mutation_gap"] == 1

    def test_device_bm25_stats_csr_bytes_and_gap(self):
        bm = BM25Index()
        for i in range(64):
            bm.index(f"d{i}", f"term{i % 9} shared body w{i}")
        dev = DeviceBM25(bm, min_n=16)
        assert dev.build()
        s = dev.resource_stats()
        assert s["rows"] == 64
        assert s["device_bytes"] > 0
        assert s["mutation_gap"] == 0
        bm.index("dnew", "fresh doc")
        assert dev.resource_stats()["mutation_gap"] == 1

    def test_gauges_reach_metrics_exposition(self):
        rng = np.random.default_rng(3)
        idx = BruteForceIndex()
        for i in range(32):
            idx.add(f"v{i}", rng.standard_normal(8).astype(np.float32))
        mb = MicroBatcher(idx.search_batch)
        obs.register_resource("brute", "opstest:gauges", idx)
        obs.register_resource("queue", "opstest:gauges", mb)
        try:
            text = obs.REGISTRY.render()
            assert ('nornicdb_index_rows{family="brute",'
                    'index="opstest:gauges"} 32') in text
            assert ('nornicdb_index_changelog_cap{family="brute",'
                    'index="opstest:gauges"}') in text
            assert 'nornicdb_queue_depth{queue="opstest:gauges"} 0' in text
        finally:
            obs.resources.unregister("brute", "opstest:gauges")
            obs.resources.unregister("queue", "opstest:gauges")

    def test_dead_index_series_retire(self):
        idx = BruteForceIndex()
        idx.add("v", [1.0, 0.0])
        obs.register_resource("brute", "opstest:dying", idx)
        text = obs.REGISTRY.render()
        assert 'index="opstest:dying"' in text
        del idx
        gc.collect()
        text = obs.REGISTRY.render()
        assert 'index="opstest:dying"' not in text

    def test_all_three_families_exposed_from_serving(self, serving):
        """Acceptance: /metrics carries device-memory and freshness
        gauges for brute + cagra + device-bm25 structures at once."""
        rng = np.random.default_rng(4)
        brute = BruteForceIndex()
        for i in range(96):
            brute.add(f"v{i}",
                      rng.standard_normal(8).astype(np.float32))
        cagra = CagraIndex(brute=brute, min_n=64, n_seeds=64,
                           hash_bits=10)
        assert cagra.build()
        cagra.search_batch(rng.standard_normal((2, 8)).astype(
            np.float32), k=5)  # records a cagra_walk compile bucket
        bm = BM25Index()
        for i in range(64):
            bm.index(f"d{i}", f"token{i % 11} corpus body w{i}")
        dev = DeviceBM25(bm, min_n=16)
        assert dev.build()
        obs.register_resource("brute", "opstest:acc", brute)
        obs.register_resource("cagra", "opstest:acc", cagra)
        obs.register_resource("device_bm25", "opstest:acc", dev)
        try:
            text = _http_get(serving["http"].port, "/metrics")
            for family in ("brute", "cagra", "device_bm25"):
                assert (f'nornicdb_index_device_bytes{{family='
                        f'"{family}",index="opstest:acc"}}') in text, family
            assert ('nornicdb_index_mutation_gap{family="cagra",'
                    'index="opstest:acc"} 0') in text
            assert "# TYPE nornicdb_index_device_bytes gauge" in text
            assert "nornicdb_compile_cache_entries" in text
        finally:
            for fam in ("brute", "cagra", "device_bm25"):
                obs.resources.unregister(fam, "opstest:acc")


# ---------------------------------------------------------------------------
# /readyz gating (ISSUE 5 tentpole pillar 2 + satellite tests)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving():
    import nornicdb_tpu
    from nornicdb_tpu.api.http_server import HttpServer

    db = nornicdb_tpu.open(auto_embed=False)
    db.store("operability probe doc", node_id="ops-1",
             embedding=[0.5] * 8)
    db.search.search("probe", mode="text")  # stand up the indexes
    http = HttpServer(db, port=0).start()
    yield {"db": db, "http": http}
    http.stop()
    db.close()


def _http_get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        raw = resp.read()
        if "json" in resp.headers.get("Content-Type", ""):
            return json.loads(raw)
        return raw.decode()


def _readyz(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestReadyz:
    def test_ready_when_idle(self, serving):
        status, doc = _readyz(serving["http"].port)
        assert status == 200
        assert doc["status"] == "ready"
        assert doc["checks"]["indexes"] >= 2  # service bm25 + brute

    def test_degrades_during_cagra_background_rebuild(self, serving):
        rng = np.random.default_rng(5)
        idx = CagraIndex(min_n=64, n_seeds=64, hash_bits=10)
        idx.add_batch([(f"v{i}",
                        rng.standard_normal(8).astype(np.float32))
                       for i in range(96)])
        assert idx.build()
        gate = threading.Event()
        real_build = idx.build
        idx.build = lambda: (gate.wait(10), real_build())[1]
        obs.register_resource("cagra", "opstest:rebuild", idx)
        try:
            idx._kick_background_rebuild()
            status, doc = _readyz(serving["http"].port)
            assert status == 503
            assert doc["status"] == "degraded"
            assert any(r.startswith("index_rebuild:cagra/opstest:rebuild")
                       for r in doc["reasons"])
            assert doc["checks"]["rebuilds_pending"] >= 1
            gate.set()
            deadline = time.time() + 10
            while idx._rebuilding and time.time() < deadline:
                time.sleep(0.02)
            status, doc = _readyz(serving["http"].port)
            assert status == 200 and doc["status"] == "ready"
        finally:
            gate.set()
            obs.resources.unregister("cagra", "opstest:rebuild")

    def test_degrades_during_device_bm25_rebuild(self, serving):
        bm = BM25Index()
        for i in range(64):
            bm.index(f"d{i}", f"lex{i % 7} body w{i}")
        dev = DeviceBM25(bm, min_n=16)
        assert dev.build()
        gate = threading.Event()
        real_build = dev.build
        dev.build = lambda: (gate.wait(10), real_build())[1]
        obs.register_resource("device_bm25", "opstest:lexreb", dev)
        try:
            dev._kick_background_rebuild()
            status, doc = _readyz(serving["http"].port)
            assert status == 503
            assert any("device_bm25/opstest:lexreb" in r
                       for r in doc["reasons"])
            gate.set()
            deadline = time.time() + 10
            while dev._rebuilding and time.time() < deadline:
                time.sleep(0.02)
            status, _doc = _readyz(serving["http"].port)
            assert status == 200
        finally:
            gate.set()
            obs.resources.unregister("device_bm25", "opstest:lexreb")

    def test_degrades_under_queue_saturation(self, serving):
        idx = BruteForceIndex()
        idx.add("v", [1.0, 0.0])
        mb = MicroBatcher(idx.search_batch, max_batch=8)
        obs.register_resource("queue", "opstest:sat", mb)
        try:
            with mb._cond:
                mb._pending.extend(object() for _ in range(8))
            status, doc = _readyz(serving["http"].port)
            assert status == 503
            assert any(r.startswith("queue_saturated:opstest:sat")
                       for r in doc["reasons"])
            assert doc["checks"]["queues_saturated"] >= 1
            with mb._cond:
                mb._pending.clear()
            status, doc = _readyz(serving["http"].port)
            assert status == 200 and doc["status"] == "ready"
        finally:
            with mb._cond:
                mb._pending.clear()
            obs.resources.unregister("queue", "opstest:sat")

    def test_degrades_near_changelog_overrun(self, serving):
        idx = BruteForceIndex()
        idx.add("v", [1.0, 0.0])
        # fake a changelog sitting at 95% of its cap
        idx._changelog = [(i, "v") for i in range(3900)]
        idx.changelog_cap = lambda: 4096
        obs.register_resource("brute", "opstest:overrun", idx)
        try:
            status, doc = _readyz(serving["http"].port)
            assert status == 503
            assert any("changelog_near_overrun:brute/opstest:overrun"
                       in r for r in doc["reasons"])
        finally:
            obs.resources.unregister("brute", "opstest:overrun")


# ---------------------------------------------------------------------------
# SLO engine (ISSUE 5 tentpole pillar 2)
# ---------------------------------------------------------------------------


class TestSloEngine:
    def _engine(self, tmp_path, target=0.99):
        r = Registry()
        h = r.histogram("nornicdb_slotest_seconds", "t", labels=("m",))
        eng = SloEngine(
            registry=r,
            objectives=[Objective("test", "nornicdb_slotest_seconds",
                                  0.1, target)],
            windows=(10.0, 60.0),
            min_requests=10,
            dump_dir=str(tmp_path / "flight"),
            dump_interval_s=300.0,
            sample_min_interval_s=0.0,
        )
        return r, h, eng

    def test_good_traffic_burns_nothing(self, tmp_path):
        _r, h, eng = self._engine(tmp_path)
        for _ in range(100):
            h.labels("a").observe(0.001)
        eng.tick(now=1000.0)
        for _ in range(50):
            h.labels("a").observe(0.001)
        eng.tick(now=1005.0)
        st = eng.status(now=1005.0)
        obj = st["objectives"]["test"]
        assert obj["total"] == 150 and obj["bad_total"] == 0
        fast = obj["windows"][0]
        assert fast["burn_rate"] == 0.0 and fast["bad"] == 0
        assert st["breached"] == []
        assert eng.dumps == []

    def test_breach_computes_burn_and_dumps_flight_record(self, tmp_path):
        _r, h, eng = self._engine(tmp_path)
        for _ in range(100):
            h.labels("a").observe(0.001)
        eng.tick(now=1000.0)
        for _ in range(50):
            h.labels("a").observe(2.0)  # way over the 100ms threshold
        eng.tick(now=1004.0)
        st = eng.status(now=1004.0)
        obj = st["objectives"]["test"]
        fast = obj["windows"][0]
        assert fast["total"] == 50 and fast["bad"] == 50
        # bad_fraction 1.0 over a 1% budget = burn rate 100
        assert fast["burn_rate"] == pytest.approx(100.0)
        assert st["breached"] == ["test"]
        # the tick wrote exactly one flight record (rate-limited)
        assert len(eng.dumps) == 1
        eng.tick(now=1005.0)
        assert len(eng.dumps) == 1
        lines = [json.loads(ln) for ln in
                 open(eng.dumps[0], encoding="utf-8")]
        kinds = [ln["kind"] for ln in lines]
        assert kinds[0] == "meta"
        assert lines[0]["reason"].startswith("slo_breach:test")
        for kind in ("slo", "latency", "resources", "compile_universe"):
            assert kind in kinds, kind

    def test_breach_needs_min_requests(self, tmp_path):
        _r, h, eng = self._engine(tmp_path)
        eng.tick(now=1000.0)
        for _ in range(5):  # high burn but below min_requests
            h.labels("a").observe(2.0)
        eng.tick(now=1001.0)
        assert eng.status(now=1001.0)["breached"] == []

    def test_objectives_from_env(self, monkeypatch):
        from nornicdb_tpu.obs.slo import _objectives_from_env

        monkeypatch.setenv("NORNICDB_SLO_HTTP", "100:0.999")
        monkeypatch.setenv("NORNICDB_SLO_BOLT", "off")
        objs = {o.name: o for o in _objectives_from_env()}
        assert "bolt" not in objs
        assert objs["http"].threshold_s == pytest.approx(0.1)
        assert objs["http"].target == 0.999
        assert objs["grpc"].target == 0.99  # default untouched
        # a half-malformed spec keeps the WHOLE default objective — a
        # valid threshold must not apply when the target is junk
        monkeypatch.setenv("NORNICDB_SLO_HTTP", "100:99%")
        objs = {o.name: o for o in _objectives_from_env()}
        assert objs["http"].threshold_s == pytest.approx(0.25)
        assert objs["http"].target == 0.99

    def test_admin_slo_endpoint(self, serving):
        doc = _http_get(serving["http"].port, "/admin/slo")
        assert set(doc["objectives"]) >= {"http", "grpc", "bolt"}
        http_obj = doc["objectives"]["http"]
        assert http_obj["threshold_ms"] > 0
        assert 0 < http_obj["target"] < 1
        assert len(http_obj["windows"]) >= 2
        assert "dump_dir" in doc
