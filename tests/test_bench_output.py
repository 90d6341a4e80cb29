"""Bench artifact-chain tests (VERDICT r4 #2 and #6).

Round 4's headline numbers were lost because the driver records only
the LAST 2000 chars of bench output and bench.py printed the headline
first. These tests pin (a) the compact last-line summary: parseable,
complete headline set, comfortably under the tail window; and (b) the
one-shot TPU proof harness end-to-end on CPU with interpret-mode
Pallas, so the first real TPU session can't be burned on a harness bug.
"""

import json
import os
import subprocess
import sys

import pytest

import bench

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SENTINEL = os.path.join(_REPO, "scripts", "bench_sentinel.py")


@pytest.fixture(scope="module")
def dry_run_lines():
    """One shared ``bench.py --dry-run`` subprocess for every test that
    needs a real artifact (the schema contract AND the sentinel gate) —
    the dry run is the expensive part, so it runs once per module."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("NORNICDB_TPU_EMBEDDER", "hash")
    out = subprocess.run(
        [sys.executable, bench.__file__, "--dry-run"],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln]
    assert len(lines) >= 2
    return lines


def _fake_result():
    """A representative full bench result (shape mirrors a real run)."""
    shape = {"value": 1.0, "unit": "queries/s", "vs_baseline": 2.5}
    return {
        "metric": "ldbc_snb_cypher_geomean",
        "value": 9300.0,
        "unit": "queries/s",
        "vs_baseline": 3.03,
        "cypher": {
            **{name: dict(shape) for name in bench._LDBC_BASELINES},
            "device_graph": {
                "recent_messages_friends": {
                    "host_qps": 17000.0, "device_qps_b1": 1200.0,
                    "parity": True, "concurrent_threads": 16,
                    "concurrent_host_qps": 2700.0,
                    "concurrent_auto_qps": 2800.0,
                    "concurrent_device_qps": 3100.0},
                "avg_friends_per_city": {
                    "host_build_ms": 12.0, "device_build_ms": 9.0,
                    "parity": True},
                "tag_cooccurrence": {
                    "host_build_ms": 2.0, "device_build_ms": 4.0,
                    "parity": True},
                "traverse_rank": {
                    "host_qps_b1": 9000.0, "device_qps_b1": 1100.0,
                    "device_qps_b16": 13000.0, "parity": True},
                "parity": 1.0,
                "compile_buckets": 7,
                "min_n_default": 200000,
            },
        },
        "knn": {"value": 110.0, "vs_baseline": 0.011,
                "b1_concurrent_qps": 900.0, "b64_qps": 5000.0,
                "backend": "tpu"},
        "northstar": {
            "hnsw_build_100k": {"inserts_per_s": 1700.0,
                                "vs_baseline": 1.02,
                                "seeded_speedup": 1.6,
                                "seeded_recall10": 0.93},
            "ann_qps_recall95": {"qps_at_recall95": {
                "brute_force": 100.0, "hnsw": 800.0,
                "ivf_hnsw": 500.0, "ivfpq": 317.0}},
            "pagerank_device": {"speedup_vs_numpy": 1.2},
        },
        "ann": {"cagra": {"qps_at_recall95": 4100.0,
                          "recall_at_10": 0.99,
                          "speedup_vs_brute": 2.0,
                          "brute_qps": 2050.0,
                          "backend": "cpu"}},
        "hybrid": {"rank_parity": 1.0, "host_qps": 350.0,
                   "fused_qps": {"1": 280.0, "16": 1250.0,
                                 "64": 1380.0},
                   "speedup_vs_host_b16": 3.5,
                   "speedup_vs_host_b64": 3.9,
                   "compile_buckets": 4,
                   "walk": {"sweep": [
                       {"n": 20_000, "walk_qps_b16": 1010.0,
                        "brute_qps_b16": 1340.0,
                        "walk_recall10": 0.97},
                       {"n": 100_000, "walk_qps_b16": 250.0,
                        "brute_qps_b16": 215.0,
                        "walk_recall10": 0.96}],
                       "crossover_n": 100_000,
                       "walk_qps_b16": 250.0,
                       "walk_recall10": 0.96}},
        "quant": {"n": 100_000, "dims": 64, "backend": "cpu",
                  "modes": {
                      "off": {"qps_b16": 220.0, "recall10": 1.0},
                      "int8": {"qps_b16": 260.0, "recall10": 1.0,
                               "compression_ratio": 3.7},
                      "pq": {"qps_b16": 300.0, "recall10": 0.97,
                             "compression_ratio": 14.2}},
                  "quant_qps_b16": 260.0,
                  "quant_recall10": 0.97,
                  "compression_ratio": 14.2,
                  "speedup_int8_vs_f32": 1.18},
        "tiered": {"n": 50_000, "dims": 64, "parts": 32, "k": 10,
                   "batch": 16, "backend": "cpu", "build_s": 2.1,
                   "tiered_recall10": 0.97,
                   "tiered_qps_b16": 180.0,
                   "tiered_capacity_ratio": 8.2,
                   "tiered_device_bytes": 800_000,
                   "disk_bytes": 12_000_000,
                   "latency_ms": {"resident_p50": 4.0,
                                  "resident_p99": 9.0,
                                  "cold_p50": 40.0, "cold_p99": 80.0},
                   "cold": {"parity": 1.0, "ledger_records": 4,
                            "batches": 4},
                   "paging": {"pages_per_s": 40.0, "promotions": 64,
                              "evictions": 62}},
        "fleet": {"replicas": 2, "n": 4000, "dims": 64,
                  "converged": True, "replica_parity": 1.0,
                  "admitted": 2, "single_read_qps": 5300.0,
                  "fleet_read_qps": 2600.0, "read_scaling": 0.49,
                  "replay_lag": {"burst_ops": 1500,
                                 "peak_lag_ops": 447,
                                 "drain_s": 1.09},
                  "apply_delay": {"replica-0": {"count": 900,
                                                "p50_ms": 7.2,
                                                "p99_ms": 38.0}},
                  "apply_delay_p99_ms": 38.0,
                  "trace_completeness": 1.0,
                  "drain": {"breached_drained": True,
                            "ledger_reason": True, "recovered": True,
                            "events_ordered": True}},
        "fleet_proc": {"replicas": 2, "n": 2000, "cores": 8,
                       "converged": True, "out_of_process": True,
                       "replica_parity": 1.0,
                       "single_read_qps": 210.0,
                       "fleet_read_qps": 390.0,
                       "read_scaling": 1.857,
                       "sheds": {"single": 0, "fleet": 3},
                       "errors": {"single": 0, "fleet": 0},
                       "replay_lag": {"burst_ops": 800,
                                      "peak_lag_ops": 310,
                                      "drain_s": 2.4},
                       "trace_completeness": 1.0},
        "tenants": {"tenants_total": 10, "knee_upserts_per_s": 80.0,
                    "flood": {"collection": "bulk_flood",
                              "target_multiple": 2.0,
                              "upserts_per_s": 40.0, "shed": 60,
                              "offered_vs_knee": 2.1},
                    "interactive": {"readers": 9,
                                    "reads_per_s": 3000.0,
                                    "errors": 0},
                    "tenant_attribution": 1.0,
                    "flood_cost_share": 0.61,
                    "noisy_neighbor_events": 1,
                    "noisy_neighbor_advisory": {
                        "tenant": "bulk_flood", "cost_share": 0.6,
                        "posture_level": 1},
                    "requests_by_tenant": {"bulk_flood": 84.0},
                    "admin_tenants": {"known": 10, "top": []}},
        "background": {"n": 2000, "edges": 6000, "seeds": 64,
                       "decay": {"host_s": 0.04, "device_s": 0.008,
                                 "speedup": 5.1, "parity": 1.0,
                                 "device_dispatches": 2},
                       "linkpredict": {"device_s": 0.007,
                                       "host_uncached_est_s": 1.0,
                                       "speedup_vs_replaced_loop": 147.0,
                                       "device_qps": 9300.0,
                                       "parity": 1.0},
                       "fastrp": {"dim": 32, "cos_min": 0.9997},
                       "cost": {"priced": True},
                       "convoy": {"solo_p99_ms": 0.2,
                                  "during_p99_ms": 0.17,
                                  "budget_ms": 1.4,
                                  "within_budget": True,
                                  "sweeps_during": 5},
                       "background_parity": 1.0,
                       "background_sweep_speedup": 5.1,
                       "background_convoy_ok": 1.0},
        "device_truth": {"backend": {"platform": "cpu",
                                     "device_kind": "cpu",
                                     "device_count": 1,
                                     "host_cores": 8,
                                     "hbm_bytes": None},
                         "calibration_coverage": 1.0,
                         "served_kinds": ["cagra_walk", "microbatch"],
                         "calibrated_kinds": ["cagra_walk",
                                              "microbatch"],
                         "unexpected_recompiles": 0,
                         "kinds": {},
                         "pred_ratio": {"microbatch": 0.9,
                                        "cagra_walk": 1.1},
                         "pred_ratio_p50": 1.0,
                         "pred_ratio_ok": 1.0,
                         "memory": {"ledger_bytes": 0,
                                    "backend_bytes": 130_000,
                                    "drift_bytes": 130_000,
                                    "bound_bytes": 67_108_864,
                                    "window_s": 60.0,
                                    "sustained_s": 0.0,
                                    "leak_suspected": False},
                         "mem_drift_ok": 1.0,
                         "cost_gate": {"pred_ms": 1.4, "attempts": 3,
                                       "sheds": 3,
                                       "ledger_records": 3,
                                       "journal_events": 3,
                                       "exactly_once": 1.0}},
        "surfaces": {name: {"ops_per_s": 2000.0, "vs_baseline": 0.5}
                     for name in bench._SURFACE_BASELINES},
        "telemetry": {
            "latency": {
                series: {"count": 100, "p50_ms": 0.4, "p95_ms": 1.1,
                         "p99_ms": 2.2}
                for series in bench._TELEMETRY_HEADLINES.values()
            },
            "compile_universe": [
                {"kind": "microbatch", "b": 1, "k": 16, "dispatches": 9,
                 "first_call_ms": 11.0, "mean_ms": 1.5}],
        },
        "tpu_proof": {"skipped": "backend is 'cpu'"},
    }


class TestCompactSummary:
    def test_headline_set_complete_and_small(self):
        # measure the line exactly as bench emits it (compact
        # separators — _dump_summary)
        line = bench._dump_summary(bench._compact_summary(_fake_result()))
        # the driver keeps the LAST 2000 chars; the summary is the last
        # line, so < 1900 leaves margin for real-run value widths (the
        # r15 overload pack rides as a 6-element array for exactly
        # this reason — named keys would blow the window)
        assert len(line) < 1900, f"summary too long for tail window: {len(line)}"
        s = json.loads(line)
        assert s["summary"] is True
        assert s["metric"] == "ldbc_snb_cypher_geomean"
        assert s["vs_baseline"] == 3.03
        assert set(s["shapes_vs_baseline"]) == set(bench._LDBC_BASELINES)
        assert set(s["surfaces"]) == set(bench._SURFACE_BASELINES)
        assert s["surfaces"]["bolt"] == [2000.0, 0.5]
        assert s["knn"]["b1_qps"] == 110.0
        assert s["knn"]["b1_concurrent_qps"] == 900.0
        assert s["hnsw_build"]["seeded_speedup"] == 1.6
        assert s["hnsw_build"]["vs_baseline"] == 1.02
        assert s["qps_at_recall95"]["ivfpq"] == 317.0
        assert s["cagra"] == {"qps_at_recall95": 4100.0,
                              "recall_at_10": 0.99,
                              "speedup_vs_brute": 2.0,
                              "backend": "cpu"}
        # fused hybrid (ISSUE 4 trio + ISSUE 6 walk tier): qps at
        # serving batch, honest speedup, the rank-identity fraction
        # behind it, and the walk tier's headline pair + crossover
        assert s["hybrid"] == {"fused_qps_b16": 1250.0,
                               "speedup_vs_host": 3.5,
                               "rank_parity": 1.0,
                               "walk_qps_b16": 250.0,
                               "walk_recall10": 0.96,
                               "crossover_n": 100_000}
        # quantization ladder (ISSUE 8 trio), packed [qps_b16,
        # recall10, compression_ratio, speedup_int8_vs_f32]: int8-rung
        # qps, worst-rung recall (the sentinel's 0.95 absolute floor),
        # PQ compression
        assert s["quant"] == [260.0, 0.97, 14.2, 1.18]
        # tiered vector storage (ISSUE 17), packed [recall10, qps_b16,
        # capacity_ratio, cold_parity, cold_records, pages_per_s]:
        # recall through the paged plane (sentinel absolute 0.95),
        # serving rate, the beyond-HBM capacity multiple, the
        # forced-cold parity verdict (absolute 1.0) with its honest
        # ledger-record count, and paging throughput
        assert s["tiered"] == [0.97, 180.0, 8.2, 1.0, 4, 40.0]
        # device graph plane (ISSUE 9): parity flag the sentinel holds
        # to 1.0, the coalesced-chain comparison, traverse-rank rate,
        # and the graph compile-bucket count behind the growth cap
        assert s["graph"] == {"device_parity": 1.0,
                              "chain_conc_device_qps": 3100.0,
                              "traverse_rank_qps_b16": 13000.0,
                              "compile_buckets": 7}
        # read fleet (ISSUE 12/13), packed [qps, scaling, parity,
        # drain, trace_completeness]: router read rate, scaling vs
        # single node, the parity-gated-admission verdict (sentinel
        # absolute floor 1.0), drain flag, and the cross-process
        # trace-completeness fraction (sentinel absolute floor 1.0;
        # apply-delay p50/p99 rides the full artifact)
        assert s["fleet"] == [2600.0, 0.49, 1.0, True, 1.0]
        # multi-process fleet (ISSUE 16), packed [qps, scaling,
        # parity, trace_completeness, cores]: out-of-GIL goodput
        # through the router vs the primary's own HTTP surface, the
        # HTTP-ranked parity verdict (sentinel absolute floor 1.0),
        # the cross-process trace fraction (absolute 1.0), and the
        # core count the sentinel's scaling floor keys on
        assert s["fleet_proc"] == [390.0, 1.857, 1.0, 1.0, 8]
        # tenant truth (ISSUE 18), packed [attribution_completeness,
        # flood_cost_share, noisy_neighbor_events, flood_vs_knee]:
        # the sentinel gates attribution ABSOLUTELY at 1.0 and the
        # flooder's cost share at the 0.5 floor
        assert s["tenants"] == [1.0, 0.61, 1, 2.1]
        # background plane (ISSUE 19), packed [sweep_speedup, parity,
        # convoy_ok]: the sentinel gates the speedup at the 0.5 qps
        # floor and parity/convoy ABSOLUTELY at 1.0
        assert s["background"] == [5.1, 1.0, 1.0]
        # device truth (ISSUE 20), packed [calibration_coverage,
        # pred_ratio_p50, pred_ratio_ok, mem_drift_ok, exactly_once,
        # drift_bytes]: the sentinel gates coverage, the ratio band,
        # the memory verdict and the shed evidence ABSOLUTELY at 1.0
        # and the p50 ratio at the 3x bound
        assert s["device_truth"] == [1.0, 1.0, 1.0, 1.0, 1.0, 130_000]
        assert s["pagerank_speedup_vs_numpy"] == 1.2
        assert s["tpu_proof"] == "skipped"
        # latency percentiles ride the summary per headline surface
        assert set(s["latency_ms"]) == set(bench._TELEMETRY_HEADLINES)
        assert s["latency_ms"]["qdrant_grpc_search"] == [0.4, 1.1, 2.2]

    def test_missing_subresults_never_raise(self):
        s = bench._compact_summary({"metric": "x"})
        assert s["summary"] is True
        assert s["shapes_vs_baseline"] == {}
        assert s["surfaces"] == {}
        assert s["hnsw_build"]["inserts_per_s"] is None
        assert s["knn"]["b1_qps"] is None
        assert s["cagra"]["qps_at_recall95"] is None
        assert s["hybrid"]["fused_qps_b16"] is None
        assert s["quant"] == [None] * 4
        assert s["tiered"] == [None] * 6
        assert s["graph"]["device_parity"] is None
        assert s["latency_ms"] == {}
        assert s["tpu_proof"] is None

    def test_error_result_still_summarizes(self):
        err = {"metric": "ldbc_snb_cypher_geomean", "value": 0.0,
               "unit": "queries/s", "vs_baseline": 0.0,
               "error": "RuntimeError: boom"}
        line = json.dumps(bench._compact_summary(err))
        assert json.loads(line)["vs_baseline"] == 0.0

    _STUBBED_MAIN = (
        "import sys; sys.path.insert(0, %r)\n"
        "import bench\n"
        # every stage runs in a child; stub the stage runner itself
        "def stage(name, timeout):\n"
        "    if name == 'cypher':\n"
        "        return {'ldbc_geomean_ops': 1.0,\n"
        "                'ldbc_geomean_vs_baseline': 2.0}\n"
        "    return %s\n"
        "bench._stage_subprocess = stage\n"
        "rc = bench.main()\n"
        # the orchestrating process must stay off JAX: a parent that
        # has touched it holds the chip its children need
        "assert 'jax' not in sys.modules, 'bench parent imported jax'\n"
        "sys.exit(rc)\n"
    )

    def _run_stubbed_main(self, stage_doc: str):
        code = self._STUBBED_MAIN % (
            str(bench.__file__).rsplit('/', 1)[0], stage_doc)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        return subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env=env,
        )

    def test_summary_is_last_line_of_main(self):
        """Drive the real ordering contract: whatever main() prints, the
        LAST stdout line must parse as the compact summary. Uses a tiny
        subprocess that stubs the stage children so it runs in seconds —
        and asserts the orchestrating parent never imported jax."""
        out = self._run_stubbed_main("{'value': 3.0}")
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [ln for ln in out.stdout.strip().splitlines() if ln]
        assert len(lines) == 2
        full = json.loads(lines[0])
        summary = json.loads(lines[-1])
        assert "cypher" in full and "summary" not in full
        assert set(bench._STAGES) - {"cypher", "ann_cagra"} <= set(full)
        assert summary["summary"] is True
        assert summary["vs_baseline"] == 2.0
        # the tail the driver keeps (last 2000 chars) contains the
        # complete summary line
        tail = out.stdout[-2000:]
        assert lines[-1] in tail

    def test_failed_stage_fails_the_bench(self):
        """A device stage that failed (raised, timed out, found no chip)
        makes bench.py exit non-zero; the artifact still prints and names
        the stage."""
        out = self._run_stubbed_main(
            "({'error': 'knn: rc=1'} if name == 'knn' else {'value': 3.0})")
        assert out.returncode == 1, out.stderr[-2000:]
        full = json.loads(out.stdout.strip().splitlines()[0])
        assert full["failed_stages"] == ["knn"]
        assert "stages failed: knn" in out.stderr


class TestStageChild:
    """``python bench.py --stage X`` is the measurement path: it refuses
    to run without an accelerator and never exits 0 on an exception."""

    def test_stage_without_a_chip_fails_and_the_runner_says_so(self):
        """This box has no chip: the real child exits non-zero with no
        CPU numbers, and the stage runner hands main() an error doc."""
        doc = bench._stage_subprocess("knn", 300.0)
        assert set(doc) == {"error"}
        assert "knn: rc=1" in doc["error"]
        assert "needs an accelerator" in doc["error"]

    def test_stage_that_raises_exits_nonzero(self):
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import bench, jax\n"
            "class _Dev: platform = 'tpu'\n"
            "jax.devices = lambda *a: [_Dev()]\n"
            "def boom(): raise RuntimeError('stage blew up')\n"
            "bench._STAGES['knn'] = (boom, 1.0)\n"
            "sys.exit(bench.run_stage('knn'))\n"
        ) % (str(bench.__file__).rsplit('/', 1)[0],)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode != 0
        assert "stage blew up" in out.stderr
        assert out.stdout.strip() == ""

    def test_unknown_device_kind_has_no_peak(self):
        assert bench._peak_flops("TPU v5 lite") == 197e12
        with pytest.raises(KeyError):
            bench._peak_flops("cpu")


class TestBenchDryRunArtifactSchema:
    """A fast ``bench.py --dry-run`` runs every in-process stage on toy
    sizes and must emit a schema-complete artifact — including the
    framework_floor calibration and the concurrent-kNN field — so a
    malformed bench artifact can never land silently (it would fail the
    default suite here first)."""

    REQUIRED_TOP = ("metric", "value", "unit", "vs_baseline", "cypher",
                    "knn", "northstar", "ann", "hybrid", "quant",
                    "tiered", "surfaces", "telemetry", "load", "fleet",
                    "tenants", "background", "tpu_proof")

    def test_dry_run_artifact_schema(self, dry_run_lines):
        lines = dry_run_lines
        full = json.loads(lines[0])
        summary = json.loads(lines[-1])

        for key in self.REQUIRED_TOP:
            assert key in full, f"artifact missing {key!r}"
        assert full["dry_run"] is True
        assert full["metric"] == "ldbc_snb_cypher_geomean"
        assert full["value"] > 0
        for shape in bench._LDBC_BASELINES:
            assert full["cypher"][shape]["value"] > 0, shape

        # the device graph plane (ISSUE 9): every shape measured on
        # both paths at toy sizes with row parity intact, the
        # coalesced-chain trio present, and the fused traverse-rank
        # dispatch served
        dg = full["cypher"]["device_graph"]
        assert dg["parity"] == 1.0
        chain = dg["recent_messages_friends"]
        assert chain["host_qps"] > 0 and chain["device_qps_b1"] > 0
        assert chain["parity"] is True
        for key in ("concurrent_host_qps", "concurrent_auto_qps",
                    "concurrent_device_qps"):
            assert chain[key] > 0, key
        for name in ("avg_friends_per_city", "tag_cooccurrence"):
            assert dg[name]["parity"] is True, name
            assert dg[name]["host_build_ms"] > 0
            assert dg[name]["device_build_ms"] > 0
        tr = dg["traverse_rank"]
        assert tr["parity"] is True
        assert tr["device_qps_b1"] > 0 and tr["device_qps_b16"] > 0
        assert dg["compile_buckets"] >= 3

        # the concurrent-kNN serving figure must always be present
        knn = full["knn"]
        assert knn["b1_concurrent_qps"] > 0
        assert knn["value"] > 0  # headline b=1 qps

        # the device graph-ANN stage: schema-complete even at toy sizes
        # (graph built, recall measured, both qps sides present)
        cagra = full["ann"]["cagra"]
        assert cagra["graph_built"] is True
        assert cagra["recall_at_10"] > 0
        assert cagra["qps"] > 0 and cagra["brute_qps"] > 0
        assert len(cagra["sweep"]) == 3
        assert "qps_at_recall95" in cagra and "speedup_vs_brute" in cagra
        assert full["ann"]["cagra"]["backend"] == "cpu"

        # the fused hybrid stage: schema-complete at toy sizes, with
        # the quality gate (rank parity vs the host reference) and all
        # three serving batch shapes measured
        hyb = full["hybrid"]
        assert hyb["built"] is True
        assert hyb["rank_parity"] == 1.0
        assert hyb["host_qps"] > 0
        for b in ("1", "16", "64"):
            assert hyb["fused_qps"][b] > 0, b
        assert "speedup_vs_host_b16" in hyb
        assert hyb["compile_buckets"] >= 1
        assert hyb["backend"] == "cpu"
        # the walk tier's corpus-size sweep (ISSUE 6): both tiers
        # measured at every point, walk-parity recall present, and the
        # crossover key emitted (null at toy sizes — the walk only
        # wins at scale)
        walk = hyb["walk"]
        assert len(walk["sweep"]) == 2
        for point in walk["sweep"]:
            assert point["walk_qps_b16"] > 0
            assert point["brute_qps_b16"] > 0
            assert point["tier"] == "walk"
            assert point["walk_recall10"] >= 0.95
        assert "crossover_n" in walk
        assert walk["walk_qps_b16"] > 0
        assert walk["walk_recall10"] >= 0.95

        # the quantization ladder (ISSUE 8): every rung measured on the
        # same corpus — int8 must be rank-exact behind the rerank even
        # at toy sizes, PQ holds the recall floor, and the compressed
        # rungs report their device bytes + ratio
        qu = full["quant"]
        assert set(qu["modes"]) == {"off", "int8", "pq"}
        for mode, point in qu["modes"].items():
            assert point["qps_b16"] > 0, mode
            assert point["recall10"] > 0, mode
        assert qu["modes"]["off"]["recall10"] == 1.0
        assert qu["modes"]["int8"]["recall10"] == 1.0
        assert qu["modes"]["pq"]["recall10"] >= 0.95
        for mode in ("int8", "pq"):
            assert qu["modes"][mode]["quant_device_bytes"] > 0
            assert qu["modes"][mode]["compression_ratio"] > 1.0
        assert qu["quant_qps_b16"] > 0
        assert qu["quant_recall10"] >= 0.95
        assert qu["compression_ratio"] >= 4.0
        assert qu["backend"] == "cpu"

        # the tiered storage plane (ISSUE 17): recall through the
        # cluster-routed paged plane holds the floor even at toy
        # sizes, forced-cold serving stays rank-identical to the
        # resident answer (with the honest ledger records behind it),
        # and the capacity multiple + paging throughput are measured
        ti = full["tiered"]
        assert ti["tiered_recall10"] >= 0.95
        assert ti["tiered_qps_b16"] > 0
        assert ti["tiered_capacity_ratio"] > 1.0
        assert ti["tiered_device_bytes"] > 0
        assert ti["disk_bytes"] > 0
        assert ti["cold"]["parity"] == 1.0
        assert ti["cold"]["ledger_records"] >= 1
        assert ti["paging"]["pages_per_s"] > 0
        assert ti["latency_ms"]["resident_p50"] > 0
        assert ti["latency_ms"]["cold_p50"] > 0
        assert ti["backend"] == "cpu"

        # every surface measured, and the new framework-floor fields
        surf = full["surfaces"]
        for name in bench._SURFACE_BASELINES:
            assert surf[name]["ops_per_s"] > 0, name
        qg = surf["qdrant_grpc"]
        assert qg["framework_floor"] > 0
        assert qg["vs_floor"] > 0

        # the telemetry stage: every headline series the surfaces run
        # drives must carry count + p50/p95/p99 (ISSUE 3 satellite)
        lat = full["telemetry"]["latency"]
        for short, series in bench._TELEMETRY_HEADLINES.items():
            assert series in lat, f"telemetry missing {short} ({series})"
            entry = lat[series]
            assert entry["count"] > 0, series
            for q in ("p50_ms", "p95_ms", "p99_ms"):
                assert entry[q] is not None and entry[q] >= 0, (series, q)
            assert entry["p50_ms"] <= entry["p95_ms"] <= entry["p99_ms"], (
                series)
        # the pow2 compile-bucket discipline is observable: every shape
        # the run compiled is in the universe, with b and k powers of 2
        universe = full["telemetry"]["compile_universe"]
        assert universe, "no device dispatches recorded"
        for entry in universe:
            assert entry["b"] & (entry["b"] - 1) == 0, entry
            assert entry["dispatches"] >= 1

        # the resource-accounting snapshot rides the artifact (ISSUE 5):
        # the surfaces run stood up real indexes, so at least the
        # service structures must report their footprint
        res = full["telemetry"]["resources"]
        assert isinstance(res, list) and res
        families = {e["family"] for e in res}
        assert "brute" in families and "bm25" in families
        for e in res:
            assert "error" not in e, e

        # the open-loop load stage (ISSUE 7): Poisson arrivals against
        # the real wire surfaces — tiny 2-point sweep in dry-run, but
        # the schema (offered vs achieved, p99-at-load, knee estimate,
        # collapse verdict) must be complete per surface
        load = full["load"]
        assert load["open_loop"] is True
        assert load["arrival"] == "poisson"
        for name in ("qdrant_grpc_search", "rest_search"):
            sweep = load["surfaces"][name]
            assert "error" not in sweep, sweep
            assert sweep["closed_loop_qps"] > 0, name
            assert len(sweep["points"]) == 2, name
            for pt in sweep["points"]:
                assert pt["offered"] > 0 and pt["offered_qps"] > 0
                assert pt["achieved_qps"] >= 0
                assert "collapsed" in pt
                if pt["completed"]:
                    assert pt["p99_ms"] is not None
                    assert pt["p50_ms"] <= pt["p99_ms"]
            assert sweep["knee_qps"] is not None and sweep["knee_qps"] > 0
            assert sweep["p99_at_load_ms"] is not None
            assert isinstance(sweep["queue_collapse_detected"], bool)
            # serving-tier truth (ISSUE 10): every swept point carries
            # its tier mix — fractions over the taxonomy's
            # surface:tier keys, summing to ~1 when non-empty
            for pt in sweep["points"]:
                mix = pt["served_tiers"]
                assert isinstance(mix, dict)
                if mix:
                    assert abs(sum(mix.values()) - 1.0) < 0.01
                    for key in mix:
                        assert ":" in key, key

        # admission-control overload sweep (ISSUE 15): 1.2x/1.5x the
        # measured knee against the gRPC surface — p99-of-served,
        # goodput, shed fraction (server counter bracket) and the
        # honest-backpressure invariant must all be present. The
        # ABSOLUTE acceptance ratios are None in tiny mode (0.25s
        # windows are noise); the sentinel skips None.
        ov = load["overload"]
        assert ov["knee_qps"] == load["surfaces"][
            "qdrant_grpc_search"]["knee_qps"]
        assert set(ov["points"]) == {"1.2", "1.5"}
        for pt in ov["points"].values():
            assert pt["offered"] > 0
            assert pt["goodput_qps"] == pt["achieved_qps"]
            assert pt["shed"] >= 0 and 0 <= pt["shed_fraction"] <= 1
            assert pt["unacked"] >= 0
        assert "p99_at_1p2x_ms" in ov
        assert "goodput_at_1p2x" in ov
        assert ov["unacked_with_shed_1p2x"] == 0
        assert ov["p99_bound_ratio_1p2x"] is None  # tiny: no ratios
        assert ov["goodput_ratio_1p2x"] is None
        # the scheduler verdict block rides the artifact
        sched = full["load"]["scheduler"]
        assert sched["posture"] in ("admit", "degrade", "shed",
                                    "shed_hard")
        assert set(sched["lanes"]) == {"interactive", "replay",
                                       "background"}

        # multi-worker wire-plane sweep (ISSUE 11): tiny mode sweeps
        # worker counts {1, 2} (thread mode); each count carries both
        # surfaces' knee brief plus the batch-size distribution
        wire = load["wire_workers"]
        assert wire["mode"] in ("thread", "process")
        assert wire["counts"] == [1, 2]
        for count in ("1", "2"):
            per = wire["per_count"][count]
            assert "error" not in per, per
            for surf in ("grpc", "rest"):
                assert per[surf]["knee_qps"] is not None, (count, surf)
                assert per[surf]["closed_loop_qps"] > 0
            dist = per["batch_size_dist"]
            assert dist is not None and dist["n"] >= 0
            assert len(dist["counts"]) == len(dist["buckets"]) + 1
        # worker count 1 IS the single-process sweep just measured
        assert (wire["per_count"]["1"]["grpc"]["knee_qps"]
                == load["surfaces"]["qdrant_grpc_search"]["knee_qps"])

        # run-level tier mix + the shadow-parity verdict the sentinel
        # gates: the tiny load run samples at 1/16, so the exact class
        # must have been audited and must replay the host at 1.0
        assert isinstance(load["served_tiers"], dict) and load["served_tiers"]
        sp = load["shadow_parity"]
        assert "error" not in sp, sp
        assert set(sp) >= {"exact", "statistical", "sampled", "mismatches"}
        assert sp["sampled"] >= 1
        assert sp["mismatches"] == 0
        assert sp["exact"] == 1.0

        # compact summary carries the floor too (driver tail window)
        assert summary["summary"] is True
        assert summary["dry_run"] is True
        assert summary["qdrant_floor"][0] > 0
        assert summary["knn"]["b1_concurrent_qps"] > 0
        # and the latency trio for the hottest surface
        p = summary["latency_ms"]["qdrant_grpc_search"]
        assert len(p) == 3 and all(x is not None for x in p)
        # and the open-loop load trio the sentinel gates
        assert summary["load"]["knee_qps"] > 0
        assert summary["load"]["p99_at_load_ms"] is not None
        assert isinstance(summary["load"]["collapse"], bool)
        # serving-tier truth (ISSUE 10): the summary carries the tier
        # mix and the shadow-parity verdicts the sentinel gates
        assert isinstance(summary["load"]["served_tiers"], dict)
        assert summary["load"]["shadow_parity_exact"] == 1.0
        assert "shadow_parity_statistical" in summary["load"]
        # wire-plane trio (ISSUE 11): REST knee + knee/batch per count
        assert summary["load"]["knee_qps_rest"] > 0
        assert set(summary["load"]["wire_knee_qps"]) == {"1", "2"}
        assert summary["load"]["wire_knee_qps"]["2"] is not None
        assert "wire_batch_mean" in summary["load"]
        # admission overload contract (ISSUE 15): the summary packs
        # [p99_at_1p2x, goodput_at_1p2x, shed_fraction, unacked,
        # p99_bound_ratio, goodput_ratio] (ratios None in tiny mode)
        ovp = summary["load"]["overload"]
        assert len(ovp) == 6
        assert ovp[0] is not None  # p99 at 1.2x measured
        assert ovp[1] is not None  # goodput at 1.2x measured
        assert ovp[3] == 0         # unacked_with_shed
        assert ovp[4] is None and ovp[5] is None  # tiny: no ratios
        assert len(lines[-1]) < 2600

    def test_fleet_stage_schema(self, dry_run_lines):
        """Read-fleet stage (ISSUE 12): the tiny 1-primary/2-replica
        topology must converge, pass parity-gated admission at the
        exact-contract floor, measure both read rates, and prove the
        drain-on-breach round trip — in every dry run."""
        full = json.loads(dry_run_lines[0])
        summary = json.loads(dry_run_lines[-1])
        fl = full["fleet"]
        assert "error" not in fl, fl
        assert fl["replicas"] == 2
        assert fl["converged"] is True
        assert fl["admitted"] == 2
        assert fl["replica_parity"] == 1.0  # exact-contract floor
        assert fl["fleet_read_qps"] > 0
        assert fl["single_read_qps"] > 0
        assert fl["read_scaling"] > 0
        lag = fl["replay_lag"]
        assert lag["burst_ops"] > 0
        assert lag["peak_lag_ops"] >= 0
        assert lag["drain_s"] is not None and lag["drain_s"] >= 0
        drain = fl["drain"]
        assert drain["breached_drained"] is True
        assert drain["ledger_reason"] is True
        assert drain["recovered"] is True
        # fleet truth (ISSUE 13): the drain->recover round trip must
        # land in the incident timeline as ordered records
        assert drain["events_ordered"] is True
        # per-record replication latency in SECONDS: the write burst
        # streamed through the WAL plane, so both replicas carry
        # non-empty apply-delay histograms
        assert len(fl["apply_delay"]) == 2, fl["apply_delay"]
        for node_delay in fl["apply_delay"].values():
            assert node_delay["count"] > 0
            assert node_delay["p99_ms"] >= node_delay["p50_ms"] >= 0
        assert fl["apply_delay_p99_ms"] is not None
        # cross-process trace propagation: every traced ring-routed
        # read carried the full plane-side chain (absolute 1.0 —
        # a broken seam is wrong, not slow)
        assert fl["trace_completeness"] == 1.0
        # the summary packs [qps, scaling, parity, drain,
        # trace_completeness] for the sentinel (tail-window economy)
        assert summary["fleet"][0] == fl["fleet_read_qps"]
        assert summary["fleet"][2] == 1.0
        assert summary["fleet"][3] is True
        assert summary["fleet"][4] == 1.0

    def test_fleet_proc_stage_schema(self, dry_run_lines):
        """Multi-process fleet stage (ISSUE 16): the tiny topology must
        spawn REAL replica subprocesses, converge over the two-plane
        stream, serve rank-identical answers over HTTP, measure both
        goodput rates with sheds accounted, drain the write burst, and
        carry every propagated trace id into a child's ring — in
        every dry run."""
        full = json.loads(dry_run_lines[0])
        summary = json.loads(dry_run_lines[-1])
        fp = full["fleet_proc"]
        assert "error" not in fp, fp
        assert fp["replicas"] == 2
        assert fp["cores"] >= 1
        assert fp["converged"] is True
        assert fp["out_of_process"] is True  # real pids, not threads
        assert fp["replica_parity"] == 1.0  # exact-contract floor
        assert fp["single_read_qps"] > 0
        assert fp["fleet_read_qps"] > 0
        assert fp["read_scaling"] > 0
        assert fp["errors"] == {"single": 0, "fleet": 0}
        lag = fp["replay_lag"]
        assert lag["burst_ops"] > 0
        assert lag["peak_lag_ops"] >= 0
        assert lag["drain_s"] is not None and lag["drain_s"] >= 0
        assert fp["trace_completeness"] == 1.0
        # the summary packs [qps, scaling, parity, trace, cores]
        assert summary["fleet_proc"][0] == fp["fleet_read_qps"]
        assert summary["fleet_proc"][1] == fp["read_scaling"]
        assert summary["fleet_proc"][2] == 1.0
        assert summary["fleet_proc"][3] == 1.0
        assert summary["fleet_proc"][4] == fp["cores"]

    def test_tenants_stage_schema(self, dry_run_lines):
        """Multi-tenant overload stage (ISSUE 18): one tenant floods
        bulk upserts through the collection->tenant mapping while nine
        interactive tenants read under explicit headers. Attribution
        completeness must hit the ABSOLUTE 1.0 contract, the flooder
        must own >= 0.5 of the measured dispatch cost, the rollup must
        surface it at /admin/tenants, and the noisy-neighbor advisory
        must land in the journal — in every dry run."""
        full = json.loads(dry_run_lines[0])
        summary = json.loads(dry_run_lines[-1])
        tn = full["tenants"]
        assert "error" not in tn, tn
        assert tn["tenants_total"] == 10
        assert tn["knee_upserts_per_s"] > 0
        assert tn["flood"]["collection"] == "bulk_flood"
        assert tn["flood"]["offered_vs_knee"] > 1.0
        assert tn["interactive"]["readers"] == 9
        assert tn["interactive"]["reads_per_s"] > 0
        assert tn["tenant_attribution"] == 1.0  # absolute contract
        assert tn["flood_cost_share"] >= 0.5
        assert tn["noisy_neighbor_events"] >= 1
        adv = tn["noisy_neighbor_advisory"]
        assert adv["tenant"] == "bulk_flood"
        assert adv["posture_level"] >= 1
        assert adv["cost_share"] >= 0.5
        assert "bulk_flood" in tn["requests_by_tenant"]
        # the rollup ranks by cumulative flops across the whole bench
        # process, so earlier direct-library stages (no tenant scope)
        # may outrank the stage's tenants — the contract is that the
        # flooder is VISIBLE at /admin/tenants with a cost row, not
        # that it tops a process-lifetime leaderboard
        top = tn["admin_tenants"]["top"]
        flood_rows = [t for t in top if t["tenant"] == "bulk_flood"]
        assert flood_rows and flood_rows[0]["requests"] > 0
        assert flood_rows[0]["cost_share"] is not None
        # the summary packs [attribution, cost_share, events, vs_knee]
        assert summary["tenants"][0] == 1.0
        assert summary["tenants"][1] == tn["flood_cost_share"]
        assert summary["tenants"][2] >= 1
        assert summary["tenants"][3] == tn["flood"]["offered_vs_knee"]

    def test_background_stage_schema(self, dry_run_lines):
        """Background plane stage (ISSUE 19): device decay sweep and
        link-prediction batch vs the replaced per-node host loops,
        verdict parity at the ABSOLUTE 1.0 contract, per-job pricing
        evidence in the cost counters, and the no-convoy guard (the
        forked replica probe's p99 inside 2x solo + 1ms while sweeps
        run) — in every dry run."""
        full = json.loads(dry_run_lines[0])
        summary = json.loads(dry_run_lines[-1])
        bg = full["background"]
        assert "error" not in bg, bg
        assert bg["n"] == 2000
        assert bg["decay"]["parity"] == 1.0  # absolute contract
        assert bg["decay"]["device_dispatches"] >= 2
        assert bg["decay"]["host_s"] > 0 and bg["decay"]["device_s"] > 0
        lpb = bg["linkpredict"]
        assert lpb["parity"] == 1.0  # absolute contract
        assert lpb["speedup_vs_replaced_loop"] > 1.0
        assert lpb["device_qps"] > 0
        assert bg["fastrp"]["cos_min"] > 0.999
        assert bg["cost"]["priced"] is True
        for kind in ("bg_decay_sweep", "bg_linkpredict", "bg_fastrp"):
            assert bg["cost"]["flops_by_kind"][kind] > 0, kind
        cv = bg["convoy"]
        assert cv["mode"] == "forked_replica_probe"
        assert cv["sweeps_during"] >= 1
        assert cv["during_p99_ms"] <= cv["budget_ms"]
        assert cv["within_budget"] is True
        assert bg["background_parity"] == 1.0
        assert bg["background_convoy_ok"] == 1.0
        # the summary packs [sweep_speedup, parity, convoy_ok] for the
        # sentinel (tail-window economy; named detail rides the full
        # artifact)
        assert summary["background"] == [
            bg["background_sweep_speedup"], 1.0, 1.0]

    def test_device_truth_stage_schema(self, dry_run_lines):
        """Device-truth stage (ISSUE 20): the timing bracket samples
        every dispatch over a two-kind serve (coalesced microbatch +
        self-aligned cagra_walk), the calibration join must cover both
        at the ABSOLUTE 1.0 contract, the predicted-vs-measured ratio
        must land inside the 3x band, the memory ledger must reconcile
        inside the drift bound, and the cost gate must shed with the
        exactly-once ledger+journal evidence — in every dry run."""
        full = json.loads(dry_run_lines[0])
        summary = json.loads(dry_run_lines[-1])
        dt = full["device_truth"]
        assert "error" not in dt, dt
        # self-describing artifact: the box's device identity
        be = dt["backend"]
        assert be["platform"]
        assert "device_kind" in be
        assert be["device_count"] >= 1
        assert be["host_cores"] >= 1
        assert "hbm_bytes" in be  # None on backends with no budget
        # calibration: both served kinds joined against analytic cost
        assert dt["calibration_coverage"] == 1.0  # absolute contract
        assert set(dt["served_kinds"]) == {"cagra_walk", "microbatch"}
        assert dt["calibrated_kinds"] == dt["served_kinds"]
        assert dt["unexpected_recompiles"] == 0
        for kind in ("cagra_walk", "microbatch"):
            kd = dt["kinds"][kind]
            assert kd["dispatches"] > 0
            assert kd["eff_flops_per_s"] > 0
            assert kd["eff_bytes_per_s"] > 0
            assert 0 < kd["padding_efficiency"] <= 1.0
            assert kd["compile_s_est"] >= 0
            assert kd["execute_s"] > 0
        # prediction honesty: measured wall time within 3x of the
        # model both ways (a model that can't place a dispatch within
        # 3x has no business gating admission)
        assert set(dt["pred_ratio"]) == {"cagra_walk", "microbatch"}
        assert dt["pred_ratio_p50"] is not None
        assert dt["pred_ratio_ok"] == 1.0
        # memory ledger reconciles inside the drift bound
        mem = dt["memory"]
        assert mem["bound_bytes"] > 0
        assert mem["leak_suspected"] is False
        assert dt["mem_drift_ok"] == 1.0
        # cost gate: every shed left exactly one ledger record and
        # one journal event with reason admission_cost
        cg = dt["cost_gate"]
        assert cg["pred_ms"] is not None and cg["pred_ms"] > 0
        assert cg["sheds"] >= 1
        assert cg["ledger_records"] == cg["sheds"]
        assert cg["journal_events"] == cg["sheds"]
        assert cg["exactly_once"] == 1.0
        # the summary packs [coverage, ratio_p50, ratio_ok,
        # mem_drift_ok, exactly_once, drift_bytes] for the sentinel
        pack = summary["device_truth"]
        assert pack[0] == 1.0
        assert pack[1] == dt["pred_ratio_p50"]
        assert pack[2] == 1.0
        assert pack[3] == 1.0
        assert pack[4] == 1.0
        assert pack[5] == mem["drift_bytes"]


class TestTpuProofDryRun:
    """VERDICT r4 #6: _bench_tpu_proof had never executed anywhere.
    Run the whole proof path on CPU (interpret-mode Pallas, tiny
    shapes) and pin the artifact schema, MFU field included."""

    def test_full_artifact_schema_on_cpu(self):
        out = bench._bench_tpu_proof(interpret=True, tiny=True)
        assert out["platform"] == "cpu"
        assert "device_kind" in out

        topk = out["pallas_topk_compiled"]
        assert topk["matches_xla"] is True
        assert topk["pallas_qps"] > 0 and topk["xla_qps"] > 0

        att = out["pallas_attention_compiled"]
        assert att["matches_reference"] is True
        assert att["tflops_per_s"] > 0

        knn = out["knn_batched_64"]
        assert knn["qps"] > 0 and "vs_baseline" in knn

        mfu = out["encoder_forward_mfu"]
        assert mfu["tokens_per_s"] > 0
        assert mfu["achieved_tflops_per_s"] > 0
        assert "mfu" in mfu and "peak_tflops_per_s" in mfu
        assert mfu["params_m"] > 0

    def test_summary_extracts_proof_fields(self):
        res = _fake_result()
        res["tpu_proof"] = {
            "platform": "tpu",
            "pallas_topk_compiled": {"matches_xla": True},
            "encoder_forward_mfu": {"mfu": 0.41},
        }
        s = bench._compact_summary(res)
        assert s["tpu_proof"] == {"platform": "tpu",
                                  "topk_matches_xla": True, "mfu": 0.41}


class TestBenchSentinelGate:
    """ISSUE 5 CI satellite: the default suite pipes a real
    ``bench.py --dry-run`` artifact through ``scripts/
    bench_sentinel.py`` — one self-consistent case that must pass, one
    injected 2x regression that must be flagged. A silent sentinel
    schema drift fails here before it can miss a real regression."""

    def _run_sentinel(self, artifact_text, args):
        out = subprocess.run(
            [sys.executable, _SENTINEL, *args],
            input=artifact_text, capture_output=True, text=True,
            timeout=60,
        )
        lines = [ln for ln in out.stdout.strip().splitlines() if ln]
        return out.returncode, [json.loads(ln) for ln in lines]

    def test_dry_run_passes_against_own_baseline(self, dry_run_lines,
                                                 tmp_path):
        artifact = "\n".join(dry_run_lines)
        base = tmp_path / "baseline.json"
        rc, docs = self._run_sentinel(
            artifact, ["--save-baseline", str(base)])
        assert rc == 0 and docs[-1]["saved"] == str(base)
        saved = json.loads(base.read_text())
        assert saved["sentinel_baseline"] is True
        # the dry run carries the full qps + quality metric set
        for metric in ("cypher_geomean", "knn_b1_qps", "cagra_qps95",
                       "cagra_recall10", "hybrid_fused_qps_b16",
                       "hybrid_rank_parity", "hybrid_compile_buckets",
                       "hybrid_walk_qps_b16", "hybrid_walk_recall10",
                       "quant_qps_b16", "quant_recall10",
                       "tiered_qps_b16", "tiered_recall10",
                       "tiered_cold_parity",
                       "surface_qdrant_grpc_qps", "load_knee_qps",
                       "load_knee_qps_rest", "load_p99_at_load_ms"):
            assert metric in saved["metrics"], metric
        rc, docs = self._run_sentinel(
            artifact, ["--baseline", str(base), "--emit-summary"])
        assert rc == 0
        verdict = docs[0]
        assert verdict["sentinel"] is True
        assert verdict["verdict"] == "pass"
        assert verdict["checked"] >= 8
        assert verdict["flagged"] == []
        # the verdict block rides the compact summary as the last line
        summary = docs[-1]
        assert summary["summary"] is True
        assert summary["sentinel"]["verdict"] == "pass"

    def test_injected_2x_regression_is_flagged(self, dry_run_lines,
                                               tmp_path):
        artifact = "\n".join(dry_run_lines)
        base = tmp_path / "baseline.json"
        rc, _docs = self._run_sentinel(
            artifact, ["--save-baseline", str(base)])
        assert rc == 0
        saved = json.loads(base.read_text())
        # inject: the baseline claims 2x the throughput the fresh run
        # achieved — exactly the regression shape the gate must catch
        inflated = {
            k: (v * 2 if (k.endswith("_qps")
                          or k == "cypher_geomean") else v)
            for k, v in saved["metrics"].items()
        }
        base.write_text(json.dumps(
            {"sentinel_baseline": True, "metrics": inflated}))
        rc, docs = self._run_sentinel(
            artifact, ["--baseline", str(base), "--emit-summary"])
        assert rc == 1
        verdict = docs[0]
        assert verdict["verdict"] == "regression"
        flagged = {f["metric"] for f in verdict["flagged"]}
        assert "cypher_geomean" in flagged or "knn_b1_qps" in flagged
        # quality metrics were NOT inflated, so they still pass —
        # per-stage tolerances, not one global knob
        assert "hybrid_rank_parity" not in flagged
        assert "cagra_recall10" not in flagged
        summary = docs[-1]
        assert summary["sentinel"]["verdict"] == "regression"
        assert summary["sentinel"]["flagged"]

    def test_p99_at_load_ceiling_flags_tail_balloon(self,
                                                    dry_run_lines,
                                                    tmp_path):
        """ISSUE 7: the open-loop p99-at-load gate is a CEILING (lower
        is better) — a fresh run whose tail latency under load balloons
        past tolerance x baseline is a regression even when every
        throughput floor passes."""
        artifact = "\n".join(dry_run_lines)
        base = tmp_path / "baseline.json"
        rc, _docs = self._run_sentinel(
            artifact, ["--save-baseline", str(base)])
        assert rc == 0
        saved = json.loads(base.read_text())
        assert saved["metrics"]["load_p99_at_load_ms"] > 0
        # baseline claims a 20x lower p99-at-load than the fresh run:
        # past the 5x ceiling -> flagged; throughput floors untouched
        deflated = dict(saved["metrics"])
        deflated["load_p99_at_load_ms"] /= 20.0
        base.write_text(json.dumps(
            {"sentinel_baseline": True, "metrics": deflated}))
        rc, docs = self._run_sentinel(
            artifact, ["--baseline", str(base)])
        assert rc == 1
        flags = {f["metric"]: f for f in docs[0]["flagged"]}
        assert set(flags) == {"load_p99_at_load_ms"}
        assert flags["load_p99_at_load_ms"]["kind"] == "latency_ceiling"
        # within the ceiling (same artifact vs its own baseline) passes
        base.write_text(json.dumps(
            {"sentinel_baseline": True, "metrics": saved["metrics"]}))
        rc, docs = self._run_sentinel(
            artifact, ["--baseline", str(base)])
        assert rc == 0
        assert "load_p99_at_load_ms" in docs[0]["passed"]

    def test_knee_vs_closed_loop_ratio_warns_never_fails(
            self, tmp_path):
        """ISSUE 11: an open-loop knee under half the same run's
        closed-loop rate is ADVISORY — it lands in the verdict's
        warnings, the exit code stays 0."""
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps({
            "sentinel_baseline": True,
            "metrics": {"load_knee_qps": 400.0,
                        "load_knee_qps_rest": 3000.0}}))
        fresh = json.dumps({"load": {"surfaces": {
            "qdrant_grpc_search": {"knee_qps": 400.0,
                                   "closed_loop_qps": 1200.0,
                                   "p99_at_load_ms": 5.0},
            "rest_search": {"knee_qps": 3000.0,
                            "closed_loop_qps": 3100.0}}}})
        rc, docs = self._run_sentinel(fresh, ["--baseline", str(base)])
        assert rc == 0
        warns = docs[0]["warnings"]
        assert [w["surface"] for w in warns] == ["qdrant_grpc"]
        assert warns[0]["kind"] == "knee_vs_closed_loop"
        assert warns[0]["ratio"] == pytest.approx(0.333, abs=0.001)
        # above the 0.5 ratio on both surfaces: no warnings at all
        fresh_ok = json.dumps({"load": {"surfaces": {
            "qdrant_grpc_search": {"knee_qps": 900.0,
                                   "closed_loop_qps": 1200.0},
            "rest_search": {"knee_qps": 3000.0,
                            "closed_loop_qps": 3100.0}}}})
        rc, docs = self._run_sentinel(fresh_ok,
                                      ["--baseline", str(base)])
        assert rc == 0
        assert docs[0]["warnings"] == []

    def test_fleet_scaling_floor_is_core_aware(self, tmp_path):
        """ISSUE 16: the out-of-GIL read-scaling floor (1.5 absolute)
        binds wherever the box has >= 2 cores to express process
        parallelism; a 1-core box time-shares one core across the
        replica subprocesses, so only the collapse guard (0.6) gates
        there. The core count rides the SAME artifact, so the verdict
        is reproducible from the file alone."""
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps(
            {"sentinel_baseline": True,
             "metrics": {"fleet_proc_read_qps": 300.0}}))

        def fp(scaling, cores):
            return json.dumps({"fleet_proc": {
                "fleet_read_qps": 300.0, "read_scaling": scaling,
                "replica_parity": 1.0, "trace_completeness": 1.0,
                "cores": cores}})

        # multi-core box below the 1.5 contract -> flagged
        rc, docs = self._run_sentinel(fp(1.1, 8),
                                      ["--baseline", str(base)])
        assert rc == 1
        flags = {f["metric"]: f for f in docs[0]["flagged"]}
        assert flags["fleet_read_scaling"]["kind"] == "scaling_floor"
        assert flags["fleet_read_scaling"]["floor"] == 1.5
        assert flags["fleet_read_scaling"]["cores"] == 8
        # the same scaling on a 1-core box passes (no parallelism to
        # demand) — the collapse guard is the only floor there
        rc, docs = self._run_sentinel(fp(1.1, 1),
                                      ["--baseline", str(base)])
        assert rc == 0
        assert "fleet_read_scaling" in docs[0]["passed"]
        # routing collapse is flagged on ANY box
        rc, docs = self._run_sentinel(fp(0.3, 1),
                                      ["--baseline", str(base)])
        assert rc == 1
        flags = {f["metric"]: f for f in docs[0]["flagged"]}
        assert flags["fleet_read_scaling"]["floor"] == 0.6
        # contract met on a multi-core box passes
        rc, docs = self._run_sentinel(fp(1.9, 8),
                                      ["--baseline", str(base)])
        assert rc == 0
        assert "fleet_read_scaling" in docs[0]["passed"]
        # the parity/trace contracts gate absolutely alongside
        assert "fleet_proc_parity" in docs[0]["passed"]
        assert "fleet_proc_trace_completeness" in docs[0]["passed"]

    def test_walk_recall_gates_absolutely_without_baseline(
            self, tmp_path):
        """The walk tier lands in round r06: its recall floor is
        ABSOLUTE, so it must gate even against a trajectory that
        predates the metric (qps floors stay relative and skip)."""
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps({
            "sentinel_baseline": True,
            "metrics": {"cypher_geomean": 100.0}}))
        fresh = json.dumps({
            "summary": True, "value": 100.0,
            "hybrid": {"walk_qps_b16": 500.0, "walk_recall10": 0.90}})
        rc, docs = self._run_sentinel(
            fresh, ["--baseline", str(base)])
        assert rc == 1
        flagged = {f["metric"] for f in docs[0]["flagged"]}
        assert "hybrid_walk_recall10" in flagged
        assert "hybrid_walk_qps_b16" in docs[0]["skipped"]
        # at/above the absolute floor the same shape passes
        fresh_ok = json.dumps({
            "summary": True, "value": 100.0,
            "hybrid": {"walk_qps_b16": 500.0, "walk_recall10": 0.96}})
        rc, docs = self._run_sentinel(
            fresh_ok, ["--baseline", str(base)])
        assert rc == 0
        assert "hybrid_walk_recall10" in docs[0]["passed"]

    def test_quant_recall_gates_absolutely_without_baseline(
            self, tmp_path):
        """ISSUE 8: the quantization ladder lands in round r08 — its
        recall floor is ABSOLUTE (0.95) and must gate even against a
        trajectory that predates the metric, while the quant qps floor
        stays relative and skips without a baseline."""
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps({
            "sentinel_baseline": True,
            "metrics": {"cypher_geomean": 100.0}}))
        fresh = json.dumps({
            "summary": True, "value": 100.0,
            "quant": {"quant_qps_b16": 400.0, "quant_recall10": 0.91}})
        rc, docs = self._run_sentinel(
            fresh, ["--baseline", str(base)])
        assert rc == 1
        flagged = {f["metric"] for f in docs[0]["flagged"]}
        assert "quant_recall10" in flagged
        assert "quant_qps_b16" in docs[0]["skipped"]
        fresh_ok = json.dumps({
            "summary": True, "value": 100.0,
            "quant": {"quant_qps_b16": 400.0, "quant_recall10": 0.97}})
        rc, docs = self._run_sentinel(
            fresh_ok, ["--baseline", str(base)])
        assert rc == 0
        assert "quant_recall10" in docs[0]["passed"]

    def test_tiered_floors_gate_absolutely_without_baseline(
            self, tmp_path):
        """ISSUE 17: the tiered plane lands in round r17 — its recall
        floor (0.95) and forced-cold parity floor (1.0) are ABSOLUTE
        and must gate even against a trajectory that predates the
        metrics, while the tiered qps floor stays relative and skips
        without a baseline."""
        base = tmp_path / "baseline.json"
        base.write_text(json.dumps({
            "sentinel_baseline": True,
            "metrics": {"cypher_geomean": 100.0}}))
        fresh = json.dumps({
            "summary": True, "value": 100.0,
            "tiered": [0.91, 150.0, 8.0, 0.5, 4, 40.0]})
        rc, docs = self._run_sentinel(
            fresh, ["--baseline", str(base)])
        assert rc == 1
        flagged = {f["metric"] for f in docs[0]["flagged"]}
        assert "tiered_recall10" in flagged
        assert "tiered_cold_parity" in flagged
        assert "tiered_qps_b16" in docs[0]["skipped"]
        # the full-artifact shape (named keys, parity under "cold")
        # extracts identically and passes at/above the floors
        fresh_ok = json.dumps({
            "summary": True, "value": 100.0,
            "tiered": {"tiered_qps_b16": 150.0,
                       "tiered_recall10": 0.97,
                       "cold": {"parity": 1.0}}})
        rc, docs = self._run_sentinel(
            fresh_ok, ["--baseline", str(base)])
        assert rc == 0
        assert "tiered_recall10" in docs[0]["passed"]
        assert "tiered_cold_parity" in docs[0]["passed"]

    def test_sentinel_passes_real_trajectory_files(self):
        """The checked-in BENCH_r0*.json trajectory gates cleanly: the
        newest driver artifact vs the earlier rounds."""
        import glob

        paths = sorted(glob.glob(os.path.join(_REPO, "BENCH_r0?.json")))
        assert len(paths) >= 2
        out = subprocess.run(
            [sys.executable, _SENTINEL,
             "--artifact", paths[-1],
             "--trajectory", os.path.join(_REPO, "BENCH_r0?.json")],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        verdict = json.loads(out.stdout.strip().splitlines()[-1])
        assert verdict["verdict"] == "pass"
        assert verdict["checked"] >= 1
        assert verdict["baseline_runs"] >= 1
