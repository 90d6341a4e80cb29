"""Load-truth observability (ISSUE 7): queue-delay stage attribution,
per-query device cost accounting, histogram exemplars with OpenMetrics
content negotiation, and the metric-catalog drift lint.

The acceptance contract pinned here: every MicroBatcher/BatchCoalescer
rider records its coalesce-wait/dispatch/merge (or apply) split into
``nornicdb_request_stage_seconds{surface,stage}`` and the derived
queueing fraction answers "queued or compute?"; device dispatches are
priced in FLOPs/bytes per (kind, index) and aggregate per real query;
``/metrics`` serves OpenMetrics exemplars under content negotiation
while the classic exposition stays byte-identical with tagging on or
off; SLO flight-recorder dumps carry the stage summary; and an
import-time metric family missing from docs/observability.md fails the
catalog lint.
"""

import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.obs import cost as obs_cost
from nornicdb_tpu.obs import stages as obs_stages
from nornicdb_tpu.obs.metrics import LATENCY_BUCKETS, Registry
from nornicdb_tpu.search.microbatch import BatchCoalescer, MicroBatcher
from nornicdb_tpu.search.vector_index import BruteForceIndex

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


def _stage_child(surface, stage):
    fam = obs.REGISTRY.get("nornicdb_request_stage_seconds")
    assert fam is not None
    return fam.children().get((surface, stage))


def _stage_count(surface, stage):
    child = _stage_child(surface, stage)
    return child.snapshot()["count"] if child is not None else 0


# ---------------------------------------------------------------------------
# stage attribution
# ---------------------------------------------------------------------------


class TestStageAttribution:
    def test_record_stage_clamps_negative_intervals(self):
        before = _stage_count("t-clamp", "coalesce_wait")
        obs.record_stage("t-clamp", "coalesce_wait", -0.5)
        child = _stage_child("t-clamp", "coalesce_wait")
        snap = child.snapshot()
        assert snap["count"] == before + 1
        assert snap["sum"] == 0.0  # clamped, not recorded negative

    def test_stage_summary_math_and_queueing_fraction(self):
        r = Registry()
        h = r.histogram("nornicdb_request_stage_seconds", "t",
                        labels=("surface", "stage"),
                        buckets=LATENCY_BUCKETS)
        # 3 requests: 10ms wait + 30ms dispatch each on one surface
        for _ in range(3):
            h.labels("svc", "coalesce_wait").observe(0.010)
            h.labels("svc", "device_dispatch").observe(0.030)
        h.labels("other", "parse").observe(0.002)
        summary = obs.stage_summary(r)
        svc = summary["svc"]
        assert svc["stages"]["coalesce_wait"]["count"] == 3
        assert svc["stages"]["coalesce_wait"]["total_ms"] == \
            pytest.approx(30.0, abs=0.01)
        assert svc["stages"]["device_dispatch"]["mean_ms"] == \
            pytest.approx(30.0, abs=0.01)
        # queueing fraction: 30ms waited / 120ms attributed = 0.25
        assert svc["queueing_fraction"] == pytest.approx(0.25, abs=0.001)
        # a surface with no queue-delay stage reports 0.0, not None
        assert summary["other"]["queueing_fraction"] == 0.0

    def test_microbatcher_records_stage_split(self):
        idx = BruteForceIndex()
        rng = np.random.default_rng(3)
        vecs = rng.standard_normal((32, 8)).astype(np.float32)
        idx.add_batch([(f"v{i}", vecs[i]) for i in range(32)])
        mb = MicroBatcher(idx.search_batch, surface="t-stage-mb")
        before = {s: _stage_count("t-stage-mb", s)
                  for s in ("coalesce_wait", "device_dispatch", "merge")}
        n = 5
        for i in range(n):
            mb.search(vecs[i], 3)
        for s in ("coalesce_wait", "device_dispatch", "merge"):
            assert _stage_count("t-stage-mb", s) == before[s] + n, s

    def test_convoy_records_wait_and_apply_stages(self):
        applied = []
        co = BatchCoalescer(lambda batch: [applied.append(v) or v
                                           for v in batch],
                            surface="t-stage-convoy")
        before_wait = _stage_count("t-stage-convoy", "coalesce_wait")
        before_apply = _stage_count("t-stage-convoy", "apply")
        n_threads = 6
        barrier = threading.Barrier(n_threads)

        def write(i):
            barrier.wait()
            assert co.submit(i) == i

        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(applied) == list(range(n_threads))
        assert _stage_count("t-stage-convoy", "coalesce_wait") == \
            before_wait + n_threads
        assert _stage_count("t-stage-convoy", "apply") == \
            before_apply + n_threads

    def test_convoy_stage_spans_ride_the_trace(self):
        co = BatchCoalescer(lambda batch: list(batch),
                            surface="t-span-convoy")
        with obs.trace("wire", method="/t/convoy") as root:
            co.submit("x")
        names = root.span_names()
        assert "coalesce.wait" in names and "apply" in names

    def test_convoy_queue_depth_contract_and_gauge(self):
        """Satellite: write convoys expose the same queue_depth contract
        MicroBatchers got in PR 5, and registering one with
        obs/resources surfaces nornicdb_queue_depth{queue=...}."""
        import re

        from nornicdb_tpu.obs import register_resource, resource_snapshot

        release = threading.Event()
        entered = threading.Event()

        def slow_apply(batch):
            entered.set()
            release.wait(timeout=5)
            return list(batch)

        co = BatchCoalescer(slow_apply, surface="t-depth-convoy")
        assert co.queue_depth() == 0
        register_resource("queue", "t-depth-convoy", co)
        leader = threading.Thread(target=co.submit, args=("lead",))
        leader.start()
        assert entered.wait(timeout=5)
        # while the leader holds the apply, new submissions queue
        followers = [threading.Thread(target=co.submit, args=(i,))
                     for i in range(3)]
        for t in followers:
            t.start()
        deadline = 50
        while co.queue_depth() < 3 and deadline:
            deadline -= 1
            import time as _t
            _t.sleep(0.01)
        assert co.queue_depth() == 3
        entries = [e for e in resource_snapshot()
                   if e["family"] == "queue"
                   and e["index"] == "t-depth-convoy"]
        assert entries and entries[0]["queue_depth"] == 3
        text = obs.REGISTRY.render()
        m = re.search(
            r'nornicdb_queue_depth\{queue="t-depth-convoy"\} (\d+)',
            text)
        assert m and int(m.group(1)) == 3
        release.set()
        leader.join()
        for t in followers:
            t.join()
        assert co.queue_depth() == 0

    def test_qdrant_upsert_convoy_registered(self):
        """The qdrant compat layer registers its upsert coalescer so
        write convoys are /readyz- and gauge-visible."""
        import nornicdb_tpu
        from nornicdb_tpu.api.qdrant import QdrantCompat
        from nornicdb_tpu.obs import resource_snapshot

        db = nornicdb_tpu.open(auto_embed=False)
        try:
            compat = QdrantCompat(db)
            # registration name is per-instance (bare for the first
            # compat in the process, ":n"-suffixed after) so concurrent
            # instances never shadow each other's gauge
            name = compat._convoy_resource_name
            assert name.startswith("qdrant:upsert_convoy")
            entries = [e for e in resource_snapshot()
                       if e["family"] == "queue"
                       and e["index"] == name]
            assert entries and "queue_depth" in entries[0]
            assert compat._upsert_coalescer.queue_depth() == 0
        finally:
            db.close()

    def test_stage_summary_served_in_admin_telemetry(self):
        import nornicdb_tpu
        from nornicdb_tpu.api.http_server import HttpServer

        db = nornicdb_tpu.open(auto_embed=False)
        db.store("stage doc", node_id="st-1", embedding=[0.5] * 8)
        http = HttpServer(db, port=0).start()
        try:
            db.search.search("", mode="vector",
                             query_embedding=[0.5] * 8)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http.port}/admin/telemetry",
                    timeout=5) as resp:
                doc = json.loads(resp.read())
            assert "stages" in doc and "cost" in doc
            vec = doc["stages"].get("service:vector")
            assert vec is not None
            assert "coalesce_wait" in vec["stages"]
            assert vec["queueing_fraction"] is not None
        finally:
            http.stop()
            db.close()


# ---------------------------------------------------------------------------
# per-query cost accounting
# ---------------------------------------------------------------------------


class TestQueryCost:
    def test_pricing_functions_scale_with_shape(self):
        f1, b1 = obs_cost.price_brute(1, 1000, 64)
        f8, b8 = obs_cost.price_brute(8, 1000, 64)
        assert f1 == 2.0 * 1000 * 64 and f8 == 8 * f1
        assert b8 > b1 > 0
        fw, bw = obs_cost.price_walk(4, 64, iters=12, width=4,
                                     degree=16, itopk=64)
        assert fw > 0 and bw > 0
        # more iterations = strictly more work
        fw2, _ = obs_cost.price_walk(4, 64, iters=24, width=4,
                                     degree=16, itopk=64)
        assert fw2 > fw
        fb, bb = obs_cost.price_bm25(4, nnz=5000, unique_terms=30,
                                     rows=2000)
        assert fb >= 8.0 * 5000 and bb > 0

    def test_record_and_summary_per_kind_index(self):
        obs_cost.record_query_cost("t_kind", "t_idx", 4, 1000.0, 400.0)
        obs_cost.record_query_cost("t_kind", "t_idx", 4, 1000.0, 400.0)
        rows = [r for r in obs.cost_summary()
                if r["kind"] == "t_kind" and r["index"] == "t_idx"]
        assert len(rows) == 1
        row = rows[0]
        assert row["queries"] == 8
        assert row["flops_total"] == 2000.0
        assert row["flops_per_query"] == 250.0
        assert row["bytes_per_query"] == 100.0

    def test_brute_search_is_priced_under_resource_identity(self):
        from nornicdb_tpu.obs import register_resource

        idx = BruteForceIndex()
        register_resource("brute", "t-cost-brute", idx)
        rng = np.random.default_rng(5)
        vecs = rng.standard_normal((16, 8)).astype(np.float32)
        idx.add_batch([(f"v{i}", vecs[i]) for i in range(16)])
        idx.search_batch([vecs[0], vecs[1]], 3)
        rows = [r for r in obs.cost_summary()
                if r["kind"] == "brute" and r["index"] == "t-cost-brute"]
        assert len(rows) == 1
        row = rows[0]
        assert row["queries"] >= 2
        # priced at the capacity-padded matrix, so per-query flops >=
        # the live-rows price (padding waste is the point)
        assert row["flops_per_query"] >= 2.0 * 16 * 8

    def test_unregistered_structure_prices_as_unregistered(self):
        idx = BruteForceIndex()
        assert obs_cost.cost_name(idx) == "unregistered"

    def test_device_bm25_and_hybrid_dispatches_priced(self):
        """End-to-end: a hybrid search through the service prices its
        device dispatches (kind depends on corpus-size routing, but the
        cost table must gain rows under the service's identity)."""
        import nornicdb_tpu

        db = nornicdb_tpu.open(auto_embed=False)
        try:
            for i in range(8):
                db.store(f"doc about topic{i % 3} number {i}",
                         node_id=f"c{i}", embedding=[float(i % 3)] * 8)
            db.search.search("topic1", mode="text")
            rows = obs.cost_summary()
            assert any(r["index"].startswith("service:") or
                       r["index"] == "unregistered" for r in rows)
        finally:
            db.close()


# ---------------------------------------------------------------------------
# exemplars + OpenMetrics exposition
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_traced_observe_tags_bucket(self):
        r = Registry()
        h = r.histogram("nornicdb_ex_seconds", "t")
        with obs.trace("wire", method="/t/ex") as root:
            h.observe(0.001)
        assert root.trace_id is not None
        # unlabeled histogram family: the default child carries the tag
        exemplars = [e for e in h.labels().exemplars() if e is not None]
        assert len(exemplars) == 1
        tid, value, ts = exemplars[0]
        assert tid == root.trace_id
        assert value == pytest.approx(0.001)
        assert ts > 0

    def test_untraced_observe_stays_untagged(self):
        r = Registry()
        h = r.histogram("nornicdb_ex2_seconds", "t")
        h.labels().observe(0.001)
        assert all(e is None for e in h.labels().exemplars())

    def test_toggle_disables_tagging(self):
        r = Registry()
        h = r.histogram("nornicdb_ex3_seconds", "t")
        obs.set_exemplars_enabled(False)
        try:
            with obs.trace("wire", method="/t/ex3"):
                h.labels().observe(0.001)
            assert all(e is None for e in h.labels().exemplars())
        finally:
            obs.set_exemplars_enabled(True)
        assert obs.exemplars_enabled()

    def test_openmetrics_exposition_carries_exemplar_and_eof(self):
        r = Registry()
        h = r.histogram("nornicdb_ex4_seconds", "t", labels=("m",))
        with obs.trace("wire", method="/t/ex4") as root:
            h.labels("a").observe(0.001)
        om = r.render_openmetrics()
        assert om.endswith("# EOF\n")
        assert f'# {{trace_id="{root.trace_id}"}}' in om
        # spec: counter TYPE line drops _total, sample keeps it
        c = r.counter("nornicdb_ex4_total", "t")
        c.inc()
        om = r.render_openmetrics()
        assert "# TYPE nornicdb_ex4 counter" in om
        assert "nornicdb_ex4_total 1" in om

    def test_classic_exposition_byte_identical_with_tagging(self):
        def build(tag: bool):
            r = Registry()
            h = r.histogram("nornicdb_ex5_seconds", "t", labels=("m",))
            obs.set_exemplars_enabled(tag)
            try:
                with obs.trace("wire", method="/t/ex5"):
                    for v in (0.001, 0.004, 0.2):
                        h.labels("a").observe(v)
            finally:
                obs.set_exemplars_enabled(True)
            return r.render()

        tagged, untagged = build(True), build(False)
        assert tagged == untagged
        assert "trace_id" not in tagged

    def test_metrics_endpoint_content_negotiation(self):
        import nornicdb_tpu
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.obs.metrics import REGISTRY as GLOBAL_REG

        db = nornicdb_tpu.open(auto_embed=False)
        http = HttpServer(db, port=0).start()
        base = f"http://127.0.0.1:{http.port}/metrics"
        try:
            with urllib.request.urlopen(base, timeout=5) as resp:
                classic_type = resp.headers.get("Content-Type", "")
                classic = resp.read().decode()
            req = urllib.request.Request(base, headers={
                "Accept": "application/openmetrics-text; version=1.0.0"})
            with urllib.request.urlopen(req, timeout=5) as resp:
                om_type = resp.headers.get("Content-Type", "")
                om = resp.read().decode()
            assert "openmetrics" not in classic_type
            assert "# EOF" not in classic
            assert om_type.startswith("application/openmetrics-text")
            assert om.rstrip().endswith("# EOF")
            assert GLOBAL_REG.OPENMETRICS_CONTENT_TYPE.startswith(
                "application/openmetrics-text")
        finally:
            http.stop()
            db.close()

    def test_trace_ids_unique_and_visible_in_traces(self):
        ids = set()
        for _ in range(50):
            with obs.trace("wire", method="/t/uniq") as root:
                pass
            ids.add(root.trace_id)
        assert len(ids) == 50
        doc = root.to_dict()
        assert doc["trace_id"] == root.trace_id


# ---------------------------------------------------------------------------
# SLO flight recorder carries the stage summary
# ---------------------------------------------------------------------------


class TestFlightRecorderStages:
    def test_dump_includes_stage_decomposition(self, tmp_path):
        from nornicdb_tpu.obs.slo import Objective, SloEngine

        r = Registry()
        h = r.histogram("nornicdb_slotest_seconds", "t", labels=("m",))
        # the dump summarizes ITS registry's stage family (in
        # production that is the process-wide one)
        sh = r.histogram("nornicdb_request_stage_seconds", "t",
                         labels=("surface", "stage"),
                         buckets=LATENCY_BUCKETS)
        sh.labels("t-slo-dump", "coalesce_wait").observe(0.005)
        sh.labels("t-slo-dump", "device_dispatch").observe(0.015)
        eng = SloEngine(
            registry=r,
            objectives=[Objective("test", "nornicdb_slotest_seconds",
                                  0.1, 0.99)],
            windows=(10.0, 60.0), min_requests=10,
            dump_dir=str(tmp_path / "flight"),
            dump_interval_s=300.0, sample_min_interval_s=0.0)
        for _ in range(100):
            h.labels("a").observe(0.001)
        eng.tick(now=1000.0)
        for _ in range(50):
            h.labels("a").observe(2.0)
        eng.tick(now=1004.0)
        assert len(eng.dumps) == 1
        lines = [json.loads(ln) for ln in
                 open(eng.dumps[0], encoding="utf-8")]
        stages = [ln for ln in lines if ln["kind"] == "stages"]
        assert len(stages) == 1
        summary = stages[0]["summary"]
        assert "t-slo-dump" in summary
        assert summary["t-slo-dump"]["queueing_fraction"] == \
            pytest.approx(0.25, abs=0.001)


# ---------------------------------------------------------------------------
# metric-catalog drift lint
# ---------------------------------------------------------------------------


class TestMetricsCatalogLint:
    def test_catalog_is_current(self):
        """The repo's own doc covers every import-time family — the
        CI wiring of scripts/check_metrics_catalog.py. Families come
        from a FRESH subprocess (--list), not this test process's
        registry, which earlier tests may have polluted with
        lazily-created families outside the import-time contract."""
        import subprocess

        import check_metrics_catalog as lint

        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_metrics_catalog.py"),
             "--list"],
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stdout + out.stderr
        families = json.loads(out.stdout)
        assert "nornicdb_request_stage_seconds" in families
        assert "nornicdb_query_cost_flops_total" in families
        doc_path = os.path.join(REPO, "docs", "observability.md")
        with open(doc_path, encoding="utf-8") as f:
            doc_text = f.read()
        missing = lint.missing_from_catalog(doc_text, families)
        assert missing == [], (
            f"undocumented metric families {missing}: add them to "
            f"docs/observability.md (the catalog lint gates this)")

    def test_lint_catches_removed_family(self):
        import check_metrics_catalog as lint

        families = ["nornicdb_request_stage_seconds",
                    "nornicdb_invented_total"]
        missing = lint.missing_from_catalog(
            "the doc mentions request_stage_seconds only", families)
        assert missing == ["nornicdb_invented_total"]

    def test_lint_rejects_substring_of_documented_name(self):
        """Matching is word-bounded: a new family whose name happens to
        be a substring of a documented one must still be flagged."""
        import check_metrics_catalog as lint

        doc = "catalog: nornicdb_request_stage_seconds"
        missing = lint.missing_from_catalog(
            doc, ["nornicdb_stage_seconds",
                  "nornicdb_request_stage_seconds"])
        assert missing == ["nornicdb_stage_seconds"]

    def test_brace_shorthand_expands(self):
        import check_metrics_catalog as lint

        doc = "wire_cache_{hits,misses,invalidations}_total"
        missing = lint.missing_from_catalog(
            doc, ["nornicdb_wire_cache_hits_total",
                  "nornicdb_wire_cache_misses_total",
                  "nornicdb_wire_cache_invalidations_total"])
        assert missing == []

    def test_cli_exit_codes(self):
        import subprocess

        ok = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_metrics_catalog.py")],
            capture_output=True, text=True, cwd=REPO)
        assert ok.returncode == 0, ok.stdout + ok.stderr
        verdict = json.loads(ok.stdout)
        assert verdict["verdict"] == "pass"
