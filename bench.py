"""Benchmark vs the reference's published numbers (BASELINE.md).

Headline: geometric mean over the LDBC-SNB/Northwind Cypher family —
the reference's own headline benchmarks (BASELINE.md rows 1-7) — as
sustained single-stream ops/s with the query-result cache disabled and
lookup params rotating. Sub-metric "knn": brute-force cosine kNN
throughput over 10k x 1024 embeddings (BASELINE.json config[0]),
compared against the reference's highest-throughput search surface,
REST search at 10,296 ops/s (testing/e2e/README.md). Each kNN query is
a distinct device-resident [1, D] tensor; no batching.

Where it runs. The measurement path needs an accelerator and refuses to
run anywhere else: every stage runs in its own child process, one child
at a time, because a chip belongs to one process — the parent only
orchestrates and never initialises a JAX backend. A stage that raises,
times out or finds no chip fails the bench (non-zero exit); nothing is
re-run on the CPU. Each stage's document carries the device it ran on.
``--dry-run`` is the in-process CPU schema check: toy sizes, every stage,
marked ``"dry_run": true``.

Output is truncation-proof (the driver records only the LAST 2000 chars
of output): the full result JSON line prints FIRST, and
a compact single-line summary carrying the complete headline set
(geomean, per-shape vs_baseline, knn, hnsw build, qps@recall95,
surfaces, pagerank, backend) prints LAST.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np


BASELINE_REST_SEARCH_OPS = 10_296.0


def _stage_subprocess(stage: str, timeout_s: float):
    """Run one bench stage in a child process with a hard deadline and
    return its document. The child owns the chip for as long as it lives;
    a blocked device call cannot be interrupted in-thread, so the process
    boundary is also the watchdog. A child that fails yields an
    ``{"error": ...}`` document: main() reports it and exits non-zero."""
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage]
    try:
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            start_new_session=True,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"{stage}: timed out after {timeout_s:.0f}s"}
    if out.returncode != 0:
        return {"error": f"{stage}: rc={out.returncode}: "
                         f"{(out.stderr or '')[-300:]}"}
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return {"error": f"{stage}: no JSON in stage output"}


# every stage that can touch JAX — the Cypher stage included: it forces
# the device graph plane on. (fn, child deadline in seconds)
_STAGES = {
    "cypher": (lambda: _bench_cypher(), 1800.0),
    "knn": (lambda: _bench_knn(), 900.0),
    "northstar": (lambda: _bench_northstar(), 1800.0),
    "ann_cagra": (lambda: {"cagra": _bench_ann_cagra()}, 900.0),
    "hybrid": (lambda: _bench_hybrid(), 900.0),
    "quant": (lambda: _bench_quant(), 900.0),
    "tiered": (lambda: _bench_tiered(), 900.0),
    # the telemetry read is the registry the surfaces run just filled:
    # same process, and BEFORE the load stage's deliberate overload
    "surfaces": (lambda: {"surfaces": _bench_surfaces(),
                          "telemetry": _bench_telemetry()}, 1800.0),
    "load": (lambda: _bench_load(), 1800.0),
    "fleet": (lambda: _bench_fleet(), 900.0),
    "fleet_proc": (lambda: _bench_fleet_proc(), 900.0),
    "tenants": (lambda: _bench_tenants(), 900.0),
    "device_truth": (lambda: _bench_device_truth(), 900.0),
    "background": (lambda: _bench_background(), 900.0),
    "tpu_proof": (lambda: _bench_tpu_proof(), 900.0),
}


def run_stage(stage: str) -> int:
    """``python bench.py --stage X``: one stage on the accelerator, one
    JSON line stamped with the device. No accelerator, or a stage that
    raises, is a non-zero exit — never CPU numbers under device keys."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from nornicdb_tpu.jaxenv import ensure_compile_cache

    ensure_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        sys.stderr.write(
            f"bench: stage {stage!r} needs an accelerator and JAX found "
            "only the CPU; use --dry-run for the CPU schema check\n")
        return 1
    fn, _timeout = _STAGES[stage]
    doc = fn()
    doc["device"] = _device_block()
    print(json.dumps(doc))
    return 0


def _dry_run():
    """Schema-faithful fast pass (same stages, toy sizes, in-process on
    the CPU): validates the whole artifact chain — including the
    framework_floor calibration — in well under a minute, so a malformed
    artifact can never land silently (the default test suite runs this;
    tests/test_bench_output.py). A stage that raises is recorded under
    its key; the schema tests then fail on it."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    os.environ.setdefault("NORNICDB_E2E_CONCURRENCY", "4")
    cypher = _bench_cypher(n_people=2_000, n_msgs=4_000, knows_per=8,
                           measure_s=0.25)
    result = _headline(cypher)
    result["dry_run"] = True

    def stage(fn):
        try:
            return fn()
        except Exception as exc:  # noqa: BLE001 — recorded, schema-checked
            return {"error": f"{type(exc).__name__}: {exc}"[:400]}

    result["knn"] = stage(lambda: _bench_knn(tiny=True))
    result["northstar"] = {"skipped": "dry-run"}
    result["ann"] = {"cagra": stage(lambda: _bench_ann_cagra(tiny=True))}
    result["hybrid"] = stage(lambda: _bench_hybrid(tiny=True))
    result["quant"] = stage(lambda: _bench_quant(tiny=True))
    result["tiered"] = stage(lambda: _bench_tiered(tiny=True))
    result["surfaces"] = stage(lambda: _bench_surfaces(
        n_people=80, secs=0.3, warmup_s=0.1))
    result["telemetry"] = _bench_telemetry()
    # open-loop arrival harness AFTER the telemetry read, so the
    # artifact's closed-loop surface percentiles stay unpolluted by
    # deliberate overload traffic
    result["load"] = stage(lambda: _bench_load(tiny=True))
    # read fleet (ISSUE 12): tiny 1-primary/2-replica topology — the
    # schema (scaling/lag/drain/parity) is what's validated
    result["fleet"] = stage(lambda: _bench_fleet(tiny=True))
    # multi-process fleet (ISSUE 16): tiny 1-primary/2-subprocess
    # topology — schema validation for scaling/parity/lag/trace
    result["fleet_proc"] = stage(lambda: _bench_fleet_proc(tiny=True))
    # tenant truth (ISSUE 18): tiny multi-tenant overload — one flooding
    # tenant vs nine interactive ones
    result["tenants"] = stage(lambda: _bench_tenants(tiny=True))
    # device truth (ISSUE 20): tiny calibration pass. BEFORE the
    # background stage: the convoy guard demotes this process to the
    # idle class, which would distort the predicted-vs-measured timing
    result["device_truth"] = stage(lambda: _bench_device_truth(tiny=True))
    # background plane (ISSUE 19) — LAST among dry-run stages, because
    # the convoy guard demotes this process to the idle scheduling class
    # and the restore is best-effort
    result["background"] = stage(lambda: _bench_background(tiny=True))
    result["tpu_proof"] = {"skipped": "dry-run"}
    return result


def _headline(cypher):
    # The reference's headline benchmarks are the LDBC-SNB/Northwind
    # Cypher rates (BASELINE.md rows 1-7); the geomean across that
    # family is the apples-to-apples figure.
    return {
        "metric": "ldbc_snb_cypher_geomean",
        "value": cypher.pop("ldbc_geomean_ops"),
        "unit": "queries/s",
        "vs_baseline": cypher["ldbc_geomean_vs_baseline"],
        "cypher": cypher,
    }


def main(dry_run: bool = False) -> int:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if dry_run:
        result = _dry_run()
        failed = []
    else:
        # one child at a time, in artifact order; the parent stays off JAX
        docs = {name: _stage_subprocess(name, _STAGES[name][1])
                for name in _STAGES}
        failed = [name for name, doc in docs.items() if "error" in doc]
        cypher = docs.pop("cypher")
        if "error" in cypher:
            cypher = {"ldbc_geomean_ops": 0.0,
                      "ldbc_geomean_vs_baseline": 0.0, **cypher}
        result = _headline(cypher)
        result["ann"] = docs.pop("ann_cagra")
        surf = docs.pop("surfaces")
        result["surfaces"] = surf.get("surfaces", surf)
        result["telemetry"] = surf.get("telemetry", {})
        result.update(docs)
        if failed:
            result["failed_stages"] = failed
    # full result first, compact summary LAST: the driver keeps only the
    # last 2000 chars, and round 4's headline numbers were lost to
    # truncation because the headline printed first
    print(json.dumps(result))
    sys.stdout.flush()
    print(_dump_summary(_compact_summary(result)))
    if failed:
        sys.stderr.write(f"bench: stages failed: {', '.join(failed)}\n")
        return 1
    return 0


# the telemetry series whose p50/p95/p99 ride the compact summary (one
# per serving surface family); keys are registry series names
_TELEMETRY_HEADLINES = {
    "qdrant_grpc_search":
        'nornicdb_grpc_request_seconds{method="/qdrant.Points/Search"}',
    "rest_search": 'nornicdb_http_request_seconds{route="nornicdb"}',
    "neo4j_http": 'nornicdb_http_request_seconds{route="tx"}',
    "bolt_run": 'nornicdb_bolt_request_seconds{msg="run"}',
    "device_dispatch":
        'nornicdb_device_dispatch_seconds{kind="microbatch"}',
}


def _bench_telemetry():
    """Read the in-process telemetry registry populated by the surfaces
    stage: per-series latency percentiles, the device compile universe
    actually paid for during the run, and the resource-accounting
    snapshot (per-index device memory + freshness lag — the artifact
    records what the run's structures cost in HBM, not just how fast
    they were). Defensive — a failed surfaces stage just yields empty
    summaries, never an exception."""
    try:
        from nornicdb_tpu import obs

        return {
            "latency": obs.latency_summary(),
            "compile_universe": obs.compile_universe(),
            "resources": obs.resource_snapshot(),
        }
    except Exception as exc:  # noqa: BLE001 — artifact must always emit
        return {"error": f"{type(exc).__name__}: {exc}"[:400]}


def _device_block():
    """Self-describing artifact (ISSUE 20): the box's device identity
    beside PR 16's ``cores`` — platform, device kind, device count,
    host cores, and the HBM budget when the backend reports one (the
    CPU backend reports none; ``hbm_bytes`` is then null, honestly)."""
    try:
        import jax

        d = jax.devices()[0]
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — CPU backends have no stats
            stats = None
        hbm = None
        if stats:
            hbm = stats.get("bytes_limit") \
                or stats.get("bytes_reservable_limit")
        return {
            "platform": d.platform,
            "device_kind": getattr(d, "device_kind", "") or "",
            "device_count": jax.device_count(),
            "host_cores": os.cpu_count() or 1,
            "hbm_bytes": int(hbm) if hbm else None,
        }
    except Exception as exc:  # noqa: BLE001 — artifact must always emit
        return {"error": f"{type(exc).__name__}: {exc}"[:200]}


def _bench_device_truth(tiny: bool = False):
    """Device-truth calibration stage (ISSUE 20): serve real dispatch
    kinds with the timing bracket at full sampling, then report

    - the roofline join: effective FLOPs/s, bytes/s and padding
      efficiency for EVERY kind the stage served (the sentinel holds
      ``calibration_coverage`` at the absolute 1.0 floor);
    - model accuracy: the calibrated ``predict_ms`` vs a freshly
      measured pass per kind (gated within a 3x band — a model 3x off
      would shed the wrong queries);
    - the device-memory reconciliation verdict (ledger vs backend,
      drift within the bound);
    - the cost-aware admission shed demonstrated END-TO-END: posture
      forced to degrade + a deadline below the calibrated prediction
      must shed with reason ``admission_cost``, exactly once in the
      ledger AND the journal per refusal.
    """
    from nornicdb_tpu import admission as adm
    from nornicdb_tpu.obs import audit as aud
    from nornicdb_tpu.obs import device as dev
    from nornicdb_tpu.obs import dispatch as dsp
    from nornicdb_tpu.obs import events as ev
    from nornicdb_tpu.search.cagra import CagraIndex
    from nornicdb_tpu.search.microbatch import MicroBatcher, pow2_bucket
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    n, d = (512, 32) if tiny else (8192, 128)
    steady_ops = 24 if tiny else 96
    measure_ops = 16 if tiny else 64

    # full-rate sampling for the calibration pass: every steady
    # dispatch feeds the EWMA so the models go confident in one run
    # (production defaults to 1/16; the tests pin the overhead guard
    # with sampling ON)
    prev_sample = os.environ.get("NORNICDB_DEVICE_TIMING_SAMPLE")
    os.environ["NORNICDB_DEVICE_TIMING_SAMPLE"] = "1"
    dev.reload()
    # dry-run pollution guard: earlier in-process stages served their
    # own kinds; coverage must judge exactly what THIS stage serves,
    # and the recompile verdict must be the STAGE's delta (bucket
    # churn in earlier stages is their story, not this one's — the
    # registry counter is process-cumulative and survives reset)
    dsp.reset()
    dev.reset()
    recompiles0 = dev.calibration_summary()["unexpected_recompiles"]
    try:
        rng = np.random.default_rng(20)
        vecs = rng.standard_normal((n, d)).astype(np.float32)

        # kind 1: microbatch — the coalescer over the brute plane; the
        # inner brute pricing credits the serving kind via the
        # dispatch scope
        idx = BruteForceIndex()
        idx.add_batch([(f"dv{i}", vecs[i]) for i in range(n)])
        mb = MicroBatcher(idx.search_batch, surface="bench-device")
        for i in range(steady_ops):
            mb.search(vecs[i % n], 10)

        # kind 2: cagra_walk — a self-aligned device kind (prices and
        # dispatches under the same name, pads internally)
        cag = CagraIndex(min_n=min(1024, n))
        cag.add_batch([(f"cv{i}", vecs[i]) for i in range(n)])
        cag_built = cag.build()
        qs16 = vecs[:16] + 0.1 * rng.standard_normal(
            (16, d)).astype(np.float32)
        if cag_built:
            for _ in range(max(10, steady_ops // 2)):
                cag.search_batch(qs16, 10)

        # predicted vs measured: a fresh timed pass per kind against
        # the model the warmup just calibrated
        def _measured_ms(fn, ops):
            t0 = time.perf_counter()
            for _ in range(ops):
                fn()
            return (time.perf_counter() - t0) / ops * 1e3

        ratios = {}
        mb_ms = _measured_ms(lambda: mb.search(vecs[0], 10),
                             measure_ops)
        pred_mb = dev.predict_ms("microbatch", 1)
        if pred_mb is not None and mb_ms > 0:
            ratios["microbatch"] = pred_mb / mb_ms
        if cag_built:
            cag_ms = _measured_ms(lambda: cag.search_batch(qs16, 10),
                                  max(4, measure_ops // 4))
            pred_cag = dev.predict_ms("cagra_walk", pow2_bucket(16))
            if pred_cag is not None and cag_ms > 0:
                ratios["cagra_walk"] = pred_cag / cag_ms
        ratio_vals = sorted(ratios.values())
        ratio_p50 = (ratio_vals[len(ratio_vals) // 2]
                     if ratio_vals else None)
        ratio_ok = 1.0 if ratio_vals and all(
            1 / 3 <= r <= 3.0 for r in ratio_vals) else 0.0

        cal = dev.calibration_summary()
        kinds_brief = {
            k: {
                "dispatches": kd["dispatches"],
                "eff_flops_per_s": kd["eff_flops_per_s"],
                "eff_bytes_per_s": kd["eff_bytes_per_s"],
                "padding_efficiency": kd["padding_efficiency"],
                "compile_s_est": kd["compile_s_est"],
                "execute_s": kd["execute_s"],
            }
            for k, kd in cal["kinds"].items()
        }

        # memory reconciliation: ledger vs the live backend
        mem = dev.reconcile()
        drift = mem["drift_bytes"]
        mem_ok = 1.0 if (drift is None
                         or abs(drift) <= mem["bound_bytes"]) else 0.0

        # cost-aware admission, end-to-end: posture forced to degrade
        # (the PR 15 test seam), deadline budget pinned BELOW the
        # calibrated prediction -> every attempt must shed up front
        # with reason admission_cost, exactly once in ledger + journal
        def _count_ledger():
            return sum(1 for r in aud.degrade_snapshot(limit=2048)
                       if r.get("reason") == "admission_cost")

        def _count_journal():
            return sum(1 for r in ev.event_snapshot(limit=2048,
                                                    kind="shed")
                       if r.get("reason") == "admission_cost")

        attempts, sheds = 3, 0
        pred_gate = dev.predict_ms("microbatch", 1)
        led0, jrn0 = _count_ledger(), _count_journal()
        orig_refresh = adm.CONTROLLER.refresh
        adm.CONTROLLER.refresh = \
            lambda now=None, force=False: "degrade"
        try:
            for _ in range(attempts):
                budget_s = (pred_gate or 1.0) / 1e3 / 2.0
                with adm.deadline_scope(time.time() + budget_s):
                    try:
                        mb.search(vecs[0], 10)
                    except adm.ShedError as exc:
                        if exc.reason == "admission_cost":
                            sheds += 1
                    except adm.DeadlineExceeded:
                        pass  # budget burned before the gate: no shed
        finally:
            adm.CONTROLLER.refresh = orig_refresh
        led, jrn = _count_ledger() - led0, _count_journal() - jrn0
        exactly_once = 1.0 if (sheds > 0 and led == sheds
                               and jrn == sheds) else 0.0

        return {
            "backend": _device_block(),
            "calibration_coverage": cal["calibration_coverage"],
            "served_kinds": cal["served_kinds"],
            "calibrated_kinds": cal["calibrated_kinds"],
            "unexpected_recompiles": (cal["unexpected_recompiles"]
                                      - recompiles0),
            "kinds": kinds_brief,
            "pred_ratio": {k: round(v, 4) for k, v in ratios.items()},
            "pred_ratio_p50": (round(ratio_p50, 4)
                               if ratio_p50 is not None else None),
            "pred_ratio_ok": ratio_ok,
            "memory": mem,
            "mem_drift_ok": mem_ok,
            "cost_gate": {
                "pred_ms": pred_gate,
                "attempts": attempts,
                "sheds": sheds,
                "ledger_records": led,
                "journal_events": jrn,
                "exactly_once": exactly_once,
            },
        }
    finally:
        if prev_sample is None:
            os.environ.pop("NORNICDB_DEVICE_TIMING_SAMPLE", None)
        else:
            os.environ["NORNICDB_DEVICE_TIMING_SAMPLE"] = prev_sample
        dev.reload()


def _dump_summary(doc):
    # the driver keeps only the LAST 2000 chars of output; compact
    # separators buy ~150 chars of headroom over json.dumps defaults
    return json.dumps(doc, separators=(",", ":"))


def _compact_summary(result):
    """One short JSON object with every headline number; must stay well
    under the driver's 2000-char tail window. Extraction is defensive —
    a missing sub-result yields null, never an exception."""

    def g(d, *path):
        for p in path:
            if not isinstance(d, dict) or p not in d:
                return None
            d = d[p]
        return d

    cy = result.get("cypher", {})
    shapes = {
        name: g(cy, name, "vs_baseline")
        for name in _LDBC_BASELINES
        if isinstance(cy.get(name), dict)
    }
    surfaces = {
        name: [g(result, "surfaces", name, "ops_per_s"),
               g(result, "surfaces", name, "vs_baseline")]
        for name in _SURFACE_BASELINES
        if isinstance(g(result, "surfaces", name), dict)
    }
    qfloor = g(result, "surfaces", "qdrant_grpc", "framework_floor")
    tpu = result.get("tpu_proof")
    if isinstance(tpu, dict):
        tpu_brief = (tpu.get("skipped") and "skipped") or (
            tpu.get("error") and "error") or {
            "platform": tpu.get("platform"),
            "topk_matches_xla": g(tpu, "pallas_topk_compiled",
                                  "matches_xla"),
            "mfu": g(tpu, "encoder_forward_mfu", "mfu"),
        }
    else:
        tpu_brief = None
    return {
        "summary": True,
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "shapes_vs_baseline": shapes,
        "knn": {
            "b1_qps": g(result, "knn", "value"),
            "vs_baseline": g(result, "knn", "vs_baseline"),
            "b1_concurrent_qps": g(result, "knn", "b1_concurrent_qps"),
            "b64_qps": g(result, "knn", "b64_qps"),
            "backend": g(result, "knn", "backend"),
        },
        "hnsw_build": {
            "inserts_per_s": g(result, "northstar", "hnsw_build_100k",
                               "inserts_per_s"),
            "vs_baseline": g(result, "northstar", "hnsw_build_100k",
                             "vs_baseline"),
            "seeded_speedup": g(result, "northstar", "hnsw_build_100k",
                                "seeded_speedup"),
            "seeded_recall10": g(result, "northstar", "hnsw_build_100k",
                                 "seeded_recall10"),
        },
        "qps_at_recall95": g(result, "northstar", "ann_qps_recall95",
                             "qps_at_recall95"),
        # device graph ANN (cagra stage): the headline trio only — the
        # full sweep lives in the main artifact
        "cagra": {
            "qps_at_recall95": g(result, "ann", "cagra", "qps_at_recall95"),
            "recall_at_10": g(result, "ann", "cagra", "recall_at_10"),
            "speedup_vs_brute": g(result, "ann", "cagra",
                                  "speedup_vs_brute"),
            "backend": g(result, "ann", "cagra", "backend"),
        },
        # fused hybrid (hybrid stage): the headline trio — device-fused
        # qps at the serving batch, speedup over the host hybrid path,
        # and the rank-identity fraction that makes the speedup honest
        "hybrid": {
            "fused_qps_b16": g(result, "hybrid", "fused_qps", "16"),
            "speedup_vs_host": g(result, "hybrid",
                                 "speedup_vs_host_b16"),
            "rank_parity": g(result, "hybrid", "rank_parity"),
            # walk tier (ISSUE 6): sub-linear vector half at the
            # largest swept N, the recall that keeps it honest, and
            # the measured brute<->walk crossover corpus size
            "walk_qps_b16": g(result, "hybrid", "walk", "walk_qps_b16"),
            "walk_recall10": g(result, "hybrid", "walk",
                               "walk_recall10"),
            "crossover_n": g(result, "hybrid", "walk", "crossover_n"),
        },
        # quantization ladder (quant stage), packed [qps_b16,
        # recall10, compression_ratio, speedup_int8_vs_f32]
        # (fleet-pack precedent, repacked in r17 to keep the summary
        # inside the tail window): int8-rung qps at the serving batch,
        # the WORST rung's recall@10 (the sentinel's absolute floor),
        # and PQ's measured compression ratio
        "quant": [
            g(result, "quant", "quant_qps_b16"),
            g(result, "quant", "quant_recall10"),
            g(result, "quant", "compression_ratio"),
            g(result, "quant", "speedup_int8_vs_f32"),
        ],
        # tiered vector storage (ISSUE 17), packed [recall10, qps_b16,
        # capacity_ratio, cold_parity, cold_records, pages_per_s]
        # (fleet-pack precedent — named keys would blow the tail
        # window): serving recall at the default residency (sentinel
        # absolute floor 0.95), qps at the serving batch, the
        # beyond-HBM capacity multiple, the forced-cold exact-parity
        # contract (absolute 1.0) with its ledger evidence, and
        # host->device paging throughput
        "tiered": [
            g(result, "tiered", "tiered_recall10"),
            g(result, "tiered", "tiered_qps_b16"),
            g(result, "tiered", "tiered_capacity_ratio"),
            g(result, "tiered", "cold", "parity"),
            g(result, "tiered", "cold", "ledger_records"),
            g(result, "tiered", "paging", "pages_per_s"),
        ],
        # device graph plane (ISSUE 9): row parity across the device
        # LDBC fast paths (sentinel absolute floor 1.0), the coalesced
        # concurrent chain comparison, the fused traverse-rank rate,
        # and the graph compile-bucket count the growth cap gates
        "graph": {
            "device_parity": g(result, "cypher", "device_graph",
                               "parity"),
            "chain_conc_device_qps": g(
                result, "cypher", "device_graph",
                "recent_messages_friends", "concurrent_device_qps"),
            "traverse_rank_qps_b16": g(result, "cypher", "device_graph",
                                       "traverse_rank",
                                       "device_qps_b16"),
            "compile_buckets": g(result, "cypher", "device_graph",
                                 "compile_buckets"),
        },
        "pagerank_speedup_vs_numpy": g(result, "northstar",
                                       "pagerank_device",
                                       "speedup_vs_numpy"),
        # open-loop load harness (ISSUE 7): the saturation knee of the
        # hottest surface under Poisson arrivals, the tail latency AT
        # that load (the sentinel-gated metric), and whether any swept
        # rate showed queueing collapse
        "load": {
            "knee_qps": g(result, "load", "surfaces",
                          "qdrant_grpc_search", "knee_qps"),
            "p99_at_load_ms": g(result, "load", "surfaces",
                                "qdrant_grpc_search", "p99_at_load_ms"),
            "collapse": g(result, "load", "surfaces",
                          "qdrant_grpc_search",
                          "queue_collapse_detected"),
            # REST-surface knee (ISSUE 11): gated alongside the gRPC
            # knee so a wire-plane win on one surface can't hide a
            # collapse on the other
            "knee_qps_rest": g(result, "load", "surfaces",
                               "rest_search", "knee_qps"),
            # multi-worker wire plane: gRPC knee and mean coalesced
            # batch size per frontend-worker count (the "more
            # frontends -> wider batches -> higher knee" claim)
            "wire_mode": g(result, "load", "wire_workers", "mode"),
            "wire_knee_qps": {
                c: g(result, "load", "wire_workers", "per_count", c,
                     "grpc", "knee_qps")
                for c in ((g(result, "load", "wire_workers",
                             "per_count") or {}).keys())},
            # mean coalesced batch per count: the "more frontends ->
            # wider batches" evidence, one number per count
            "wire_batch_mean": {
                c: g(result, "load", "wire_workers", "per_count", c,
                     "batch_size_dist", "mean")
                for c in ((g(result, "load", "wire_workers",
                             "per_count") or {}).keys())},
            # serving-tier truth (ISSUE 10): what actually answered
            # under load, and the worst shadow parity per contract
            # class (the sentinel's absolute floors)
            "served_tiers": g(result, "load", "served_tiers"),
            "shadow_parity_exact": g(result, "load", "shadow_parity",
                                     "exact"),
            "shadow_parity_statistical": g(result, "load",
                                           "shadow_parity",
                                           "statistical"),
            # admission-control overload contract (ISSUE 15), packed
            # [p99_at_1p2x_ms, goodput_at_1p2x, shed_fraction_1p2x,
            # unacked_with_shed_1p2x, p99_bound_ratio_1p2x,
            # goodput_ratio_1p2x] — the fleet-pack precedent: the
            # driver tail window is 2000 chars, so the summary carries
            # the sentinel-gated set in array form
            "overload": [
                g(result, "load", "overload", "p99_at_1p2x_ms"),
                g(result, "load", "overload", "goodput_at_1p2x"),
                g(result, "load", "overload", "shed_fraction_1p2x"),
                g(result, "load", "overload",
                  "unacked_with_shed_1p2x"),
                g(result, "load", "overload", "p99_bound_ratio_1p2x"),
                g(result, "load", "overload", "goodput_ratio_1p2x"),
            ],
        },
        # read fleet (ISSUE 12/13), packed [fleet_read_qps,
        # read_scaling, replica_parity, drain_on_breach,
        # trace_completeness] — the driver tail window is 2000 chars,
        # so the summary carries the sentinel-gated headline set in
        # the array form the surfaces/qdrant_floor entries use
        # (apply-delay p50/p99 per node rides the full artifact's
        # fleet.apply_delay block)
        "fleet": [
            g(result, "fleet", "fleet_read_qps"),
            g(result, "fleet", "read_scaling"),
            g(result, "fleet", "replica_parity"),
            g(result, "fleet", "drain", "breached_drained"),
            g(result, "fleet", "trace_completeness"),
        ],
        # multi-process fleet (ISSUE 16), packed [fleet_read_qps,
        # read_scaling, replica_parity, trace_completeness, cores] —
        # cores rides along because the sentinel's scaling floor is
        # core-aware (out-of-GIL parallelism needs real cores; a
        # 1-core box gates collapse, not the 1.5x contract)
        "fleet_proc": [
            g(result, "fleet_proc", "fleet_read_qps"),
            g(result, "fleet_proc", "read_scaling"),
            g(result, "fleet_proc", "replica_parity"),
            g(result, "fleet_proc", "trace_completeness"),
            g(result, "fleet_proc", "cores"),
        ],
        # tenant truth (ISSUE 18), packed [attribution_completeness,
        # flood_cost_share, noisy_neighbor_events, flood_vs_knee] —
        # the sentinel gates the first ABSOLUTELY at 1.0 and the
        # second at the 0.5 floor
        "tenants": [
            g(result, "tenants", "tenant_attribution"),
            g(result, "tenants", "flood_cost_share"),
            g(result, "tenants", "noisy_neighbor_events"),
            g(result, "tenants", "flood", "offered_vs_knee"),
        ],
        # background plane (ISSUE 19), packed [sweep_speedup, parity,
        # convoy_ok] — the sentinel gates the first at the 0.5 qps
        # floor and parity/convoy ABSOLUTELY at 1.0
        "background": [
            g(result, "background", "background_sweep_speedup"),
            g(result, "background", "background_parity"),
            g(result, "background", "background_convoy_ok"),
        ],
        # device truth (ISSUE 20), packed [calibration_coverage,
        # pred_ratio_p50, pred_ratio_ok, mem_drift_ok,
        # cost_shed_exactly_once, mem_drift_bytes] — the sentinel
        # gates coverage/pred_ok/mem_ok/exactly_once ABSOLUTELY at
        # 1.0 and bounds the raw drift at the 64 MiB detector bound
        "device_truth": [
            g(result, "device_truth", "calibration_coverage"),
            g(result, "device_truth", "pred_ratio_p50"),
            g(result, "device_truth", "pred_ratio_ok"),
            g(result, "device_truth", "mem_drift_ok"),
            g(result, "device_truth", "cost_gate", "exactly_once"),
            g(result, "device_truth", "memory", "drift_bytes"),
        ],
        "surfaces": surfaces,
        # what grpc-python can physically do on this box with this
        # harness, and how close the real surface got (the perf gate)
        "qdrant_floor": [qfloor,
                         g(result, "surfaces", "qdrant_grpc", "vs_floor")],
        # serving-latency headline: [p50, p95, p99] ms per surface from
        # the telemetry registry (null until that surface has traffic)
        "latency_ms": {
            short: [g(result, "telemetry", "latency", series, q)
                    for q in ("p50_ms", "p95_ms", "p99_ms")]
            for short, series in _TELEMETRY_HEADLINES.items()
            if isinstance(g(result, "telemetry", "latency", series), dict)
        },
        "tpu_proof": tpu_brief,
        **({"dry_run": True} if result.get("dry_run") else {}),
    }


# bf16 peak FLOP/s per chip by device_kind substring (public specs);
# None -> report raw flops/s with mfu=null rather than guessing
_TPU_PEAK_FLOPS = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _peak_flops(device_kind: str):
    """Published bf16 peak of the chip. A device that is not in the table
    is an error, not a default: a utilisation over a guessed peak is not
    a measurement."""
    kind = device_kind.lower()
    for sub, peak in _TPU_PEAK_FLOPS:
        if sub in kind:
            return peak
    raise KeyError(f"no published peak FLOP/s for device kind "
                   f"{device_kind!r}; add it to _TPU_PEAK_FLOPS")


def _bench_tpu_proof(interpret: bool = False, tiny: bool = False):
    """Runs ONLY on a live accelerator (production path). Captures, in
    one shot:

    - compiled (interpret=False) Pallas fused cosine top-k, validated
      against the XLA path and timed;
    - compiled Pallas flash attention, validated against the naive
      einsum reference and timed;
    - batched device kNN (batch 64) alongside the headline batch-1;
    - encoder forward MFU at the bge-m3-like shape: measured tokens/s
      x analytic FLOPs/token over the chip's public bf16 peak.

    ``interpret=True, tiny=True`` is the CPU dry-run mode: same code path,
    same artifact schema, interpret-mode Pallas on toy shapes — so a
    harness bug can't burn the first real TPU session.
    """
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    out = {"platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", "unknown")}
    rng = np.random.default_rng(7)

    from nornicdb_tpu.ops import cosine_topk, l2_normalize, pad_dim
    from nornicdb_tpu.ops.pallas_topk import fused_cosine_topk

    # -- compiled pallas top-k vs XLA path --------------------------------
    n, d, k = (4096, 128, 10) if tiny else (100_000, 1024, 10)
    cap = pad_dim(n)
    m = np.zeros((cap, d), np.float32)
    m[:n] = rng.standard_normal((n, d), dtype=np.float32)
    valid = np.zeros(cap, bool)
    valid[:n] = True
    mj = l2_normalize(jnp.asarray(m))
    vj = jnp.asarray(valid)
    q = l2_normalize(jnp.asarray(
        rng.standard_normal((64, d), dtype=np.float32)))
    s_ref, i_ref = cosine_topk(q, mj, vj, k)
    s_ref.block_until_ready()
    s_pal, i_pal = fused_cosine_topk(q, mj, vj, k, interpret=interpret)
    s_pal.block_until_ready()
    exact = bool(jnp.all(i_ref == i_pal)) and bool(
        jnp.allclose(s_ref, s_pal, atol=1e-3))
    iters = 3 if tiny else 50
    t0 = time.perf_counter()
    for _ in range(iters):
        s_pal, _ = fused_cosine_topk(q, mj, vj, k, interpret=interpret)
    s_pal.block_until_ready()
    dt_pal = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        s_ref, _ = cosine_topk(q, mj, vj, k)
    s_ref.block_until_ready()
    dt_xla = time.perf_counter() - t0
    out["pallas_topk_compiled"] = {
        "n": n, "dims": d, "batch": 64, "matches_xla": exact,
        "pallas_qps": round(64 * iters / dt_pal, 1),
        "xla_qps": round(64 * iters / dt_xla, 1),
    }

    # -- compiled pallas flash attention vs naive reference ---------------
    from nornicdb_tpu.ops.pallas_attention import (
        flash_attention, reference_attention)

    B, S, H, Dh = (1, 128, 2, 32) if tiny else (4, 1024, 8, 64)
    qa = jnp.asarray(rng.standard_normal((B, S, H, Dh), dtype=np.float32))
    ka = jnp.asarray(rng.standard_normal((B, S, H, Dh), dtype=np.float32))
    va = jnp.asarray(rng.standard_normal((B, S, H, Dh), dtype=np.float32))
    mask = jnp.ones((B, S), bool)
    o_ref = reference_attention(qa, ka, va, mask)
    o_pal = flash_attention(qa, ka, va, mask, interpret=interpret)
    o_pal.block_until_ready()
    att_exact = bool(jnp.allclose(o_ref, o_pal, atol=2e-3))
    iters = 3 if tiny else 30
    t0 = time.perf_counter()
    for _ in range(iters):
        o_pal = flash_attention(qa, ka, va, mask, interpret=interpret)
    o_pal.block_until_ready()
    dt = time.perf_counter() - t0
    att_flops = 4.0 * B * H * S * S * Dh  # QK^T + AV matmuls
    out["pallas_attention_compiled"] = {
        "shape": [B, S, H, Dh], "matches_reference": att_exact,
        # 3 significant digits, not fixed decimals: interpret-mode CPU
        # dry-runs produce tiny values that round(x, 2) floors to 0.0
        "tflops_per_s": float(f"{att_flops * iters / dt / 1e12:.3g}"),
    }

    # -- batched device kNN (the headline is batch-1) ---------------------
    iters = 10 if tiny else 200
    t0 = time.perf_counter()
    for _ in range(iters):
        s, _ = cosine_topk(q, mj, vj, k)
    s.block_until_ready()
    dt = time.perf_counter() - t0
    out["knn_batched_64"] = {
        "n": n, "dims": d,
        "qps": round(64 * iters / dt, 1),
        "vs_baseline": round(
            (64 * iters / dt) / BASELINE_REST_SEARCH_OPS, 3),
    }

    # -- encoder forward MFU at the bge-m3-like shape ---------------------
    from nornicdb_tpu.models.encoder import Encoder, EncoderConfig

    cfg = (EncoderConfig.tiny() if tiny
           else EncoderConfig.bge_m3_like())
    model = Encoder(cfg)
    Bt, St = (2, 64) if tiny else (8, 512)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (Bt, St)), jnp.int32)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), ids)["params"])()
    fwd = jax.jit(lambda p, x: model.apply({"params": p}, x))
    fwd(params, ids).block_until_ready()  # compile
    iters = 3 if tiny else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fwd(params, ids)
    y.block_until_ready()
    dt = time.perf_counter() - t0
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree_util.tree_leaves(params))
    # matmul-dominated forward: 2 FLOPs/param/token + attention
    # 4*L*S*Dmodel per token (QK^T + AV)
    flops_per_token = (2.0 * n_params
                       + 4.0 * cfg.num_layers * St * cfg.hidden_size)
    tokens_per_s = Bt * St * iters / dt
    achieved = tokens_per_s * flops_per_token
    # the interpret-mode rehearsal runs on a CPU: no peak, no utilisation
    peak = None if interpret else _peak_flops(out["device_kind"])
    out["encoder_forward_mfu"] = {
        "config": "bge_m3_like", "batch": Bt, "seq": St,
        "params_m": round(n_params / 1e6, 1),
        "tokens_per_s": round(tokens_per_s, 1),
        "achieved_tflops_per_s": float(f"{achieved / 1e12:.3g}"),
        "peak_tflops_per_s": None if peak is None else round(peak / 1e12),
        "mfu": None if peak is None else round(achieved / peak, 4),
    }
    return out


_SURFACE_BASELINES = {
    "bolt": 2489.0,
    "neo4j_http": 4082.0,
    "graphql": 3200.0,
    "rest_search": 10296.0,
    "qdrant_grpc": 29331.0,
}


def _echo_floor_server(payload: bytes):
    """Same-box grpc-python calibration server: a grpc.aio server whose
    single raw-bytes handler returns ``payload`` unconditionally — the
    physical ceiling of what ANY python gRPC server can serve with this
    harness on this box. Returns (port, stop_fn)."""
    import asyncio
    import threading

    import grpc

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True,
                     name="bench-echo-floor").start()

    async def build():
        server = grpc.aio.server()

        async def echo(data, context):
            return payload

        server.add_generic_rpc_handlers((
            grpc.method_handlers_generic_handler(
                "bench.Floor",
                {"Echo": grpc.unary_unary_rpc_method_handler(echo)}),
        ))
        port = server.add_insecure_port("127.0.0.1:0")
        await server.start()
        return server, port

    server, port = asyncio.run_coroutine_threadsafe(build(), loop).result(30)

    def stop():
        asyncio.run_coroutine_threadsafe(server.stop(0.1), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)

    return port, stop


class _LeanHttpClient:
    """Persistent keep-alive HTTP/1.1 client over a raw socket with
    prebuilt request bytes. The reference bench's clients are compiled
    Go — a urllib/http.client loop spends more CPU in the client than
    the server does serving it, and on a small box that client cost is
    what gets measured. This measures the server."""

    def __init__(self, port: int):
        import socket

        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._buf = b""

    @staticmethod
    def build(path: str, body: dict, method: str = "POST",
              headers: "dict | None" = None) -> bytes:
        data = json.dumps(body).encode()
        extra = "".join(f"{k}: {v}\r\n"
                        for k, v in (headers or {}).items())
        return (
            f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n{extra}"
            f"Content-Length: {len(data)}\r\n\r\n"
        ).encode() + data

    def roundtrip(self, request: bytes) -> bytes:
        import re as _re

        self.sock.sendall(request)
        while b"\r\n\r\n" not in self._buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed connection")
            self._buf += chunk
        head, _, rest = self._buf.partition(b"\r\n\r\n")
        m = _re.search(rb"content-length:\s*(\d+)", head, _re.I)
        clen = int(m.group(1)) if m else 0
        while len(rest) < clen:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed connection")
            rest += chunk
        body, self._buf = rest[:clen], rest[clen:]
        if not head.startswith(b"HTTP/1.1 2"):
            raise RuntimeError(f"bad status: {head[:40]!r} {body[:200]!r}")
        return body

    def close(self) -> None:
        self.sock.close()


def _bench_surfaces(n_people: int = 1000, secs: float = 2.0,
                    warmup_s: float = 0.5):
    """Sustained ops/s on every protocol surface over one 1k-node
    dataset, with the reference's e2e methodology
    (testing/e2e/endpoints_bench_test.go): persistent per-worker
    connections, fixed request per surface (its bolt/graphql shapes are
    fixed count queries and its REST/qdrant searches repeat one query —
    riding the server's result caches is part of the measured contract,
    search.go:88-92), concurrency = NORNICDB_E2E_CONCURRENCY or cpu
    count (the reference uses GOMAXPROCS; its baselines rode a 16-core
    M3 Max, so absolute ops/s on a small box understate per-core
    standing — `cpus` is reported alongside)."""
    import threading

    import grpc

    import nornicdb_tpu
    from nornicdb_tpu.api.bolt import BoltServer
    from nornicdb_tpu.api.grpc_server import GrpcServer
    from nornicdb_tpu.api.http_server import HttpServer
    from nornicdb_tpu.api.proto import qdrant_pb2 as q
    from tests.test_e2e_surfaces import _Bolt

    cpus = os.cpu_count() or 1
    conc = int(os.environ.get("NORNICDB_E2E_CONCURRENCY", 0)) or min(cpus, 16)

    os.environ.setdefault("NORNICDB_TPU_EMBEDDER", "hash")
    db = nornicdb_tpu.open(auto_embed=False)
    embedder = db._embedder
    for i in range(n_people):
        db.store(f"person{i} writes about topic{i % 7}",
                 node_id=f"p{i}", labels=["Person"],
                 properties={"name": f"person{i}", "idx": i},
                 embedding=embedder.embed(f"person{i} topic{i % 7}"))
    db.flush()
    db.recall("warm")  # build search indexes
    http = HttpServer(db, port=0).start()
    bolt = BoltServer(db, port=0).start()
    grpc_srv = GrpcServer(db, port=0).start()
    ch = grpc.insecure_channel(grpc_srv.address)

    def grpc_call(method, request, response_cls):
        return ch.unary_unary(
            method,
            request_serializer=lambda r: r.SerializeToString(),
            response_deserializer=response_cls.FromString,
        )(request)

    req = q.CreateCollection(collection_name="bench")
    req.vectors_config.params.size = embedder.dims
    req.vectors_config.params.distance = q.Cosine
    grpc_call("/qdrant.Collections/Create", req,
              q.CollectionOperationResponse)
    up = q.UpsertPoints(collection_name="bench")
    for i in range(0, n_people, 4):
        node = db.storage.get_node(f"p{i}")
        p = up.points.add()
        p.id.num = i
        p.vectors.vector.data.extend(node.embedding)
    grpc_call("/qdrant.Points/Upsert", up, q.PointsOperationResponse)

    def sustain(make_worker):
        """Reference runBench shape: N workers, each with its own
        connection; warmup, then a timed window. A worker that dies
        before its barrier aborts the barrier (instead of hanging the
        whole bench forever — the artifact must always be produced)."""
        stop = threading.Event()
        counts = [0] * conc
        barrier = threading.Barrier(conc + 1)

        def run(idx):
            try:
                fn, cleanup = make_worker()
            except Exception:
                barrier.abort()
                raise
            try:
                fn()  # connection + compile warmup
                barrier.wait(timeout=120)
                # warmup window (results discarded)
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < warmup_s:
                    fn()
                barrier.wait(timeout=120)
                n = 0
                while not stop.is_set():
                    fn()
                    n += 1
                counts[idx] = n
            except threading.BrokenBarrierError:
                pass
            except Exception:
                barrier.abort()
                raise
            finally:
                cleanup()

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(conc)]
        for t in threads:
            t.start()
        try:
            barrier.wait(timeout=120)  # all connected
            barrier.wait(timeout=120)  # warmup done
        except threading.BrokenBarrierError:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            raise RuntimeError("bench worker failed during setup/warmup")
        t0 = time.perf_counter()
        time.sleep(secs)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        return round(sum(counts) / (time.perf_counter() - t0), 1)

    def http_worker(path, body):
        request = _LeanHttpClient.build(path, body)

        def make():
            client = _LeanHttpClient(http.port)
            return (lambda: client.roundtrip(request)), client.close

        return make

    out = {}
    try:
        def bolt_worker():
            b = _Bolt(bolt.port)
            return (lambda: b.query_value(
                "MATCH (p:Person {idx: 3}) RETURN p.name")), b.close

        out["bolt"] = sustain(bolt_worker)
        out["neo4j_http"] = sustain(http_worker(
            "/db/neo4j/tx/commit",
            {"statements": [{"statement":
                             "MATCH (p:Person {idx: 3}) "
                             "RETURN p.name"}]}))
        out["graphql"] = sustain(http_worker(
            "/graphql",
            {"query": "{ nodes(label: \"Person\", limit: 5) "
                      "{ id } }"}))
        out["rest_search"] = sustain(http_worker(
            "/nornicdb/search", {"query": "topic1 person", "limit": 5}))
        target = db.storage.get_node("p4")
        sr = q.SearchPoints(collection_name="bench",
                            vector=list(target.embedding), limit=5)

        sr_bytes = sr.SerializeToString()
        # canned response for the echo-floor calibration: the REAL
        # serialized Search response, so the floor moves the same bytes
        resp_payload = grpc_call("/qdrant.Points/Search", sr,
                                 q.SearchResponse).SerializeToString()

        def grpc_worker():
            # per-worker channel: one shared channel would multiplex all
            # workers over a single TCP connection, unlike every other
            # surface (and unlike the reference's per-worker clients).
            # The identical request is serialized ONCE per worker and
            # responses stay raw bytes — the artifact measures the
            # server, not the python client's per-call protobuf
            # encode/decode (r4 #1(d) persistent-client methodology;
            # the reference's Go clients pay negligible codec cost,
            # python protobuf costs ~100us/response on one core).
            # Response correctness is covered by the parsing client in
            # tests/test_e2e_surfaces.py.
            wch = grpc.insecure_channel(grpc_srv.address)
            stub = wch.unary_unary(
                "/qdrant.Points/Search",
                request_serializer=lambda b: b,
                response_deserializer=lambda b: b)
            return (lambda: stub(sr_bytes)), wch.close

        out["qdrant_grpc"] = sustain(grpc_worker)

        # -- framework-floor calibration (same harness, same box) -----
        # An echo handler serving the identical response bytes bounds
        # what grpc-python can physically do here; the artifact carries
        # it so "within 0.95x of the framework" is a driver-verifiable
        # claim instead of PERF.md prose. Measured AFTER the real
        # surface with identical concurrency/windows, so box load
        # cancels out of the ratio as much as one run allows.
        floor_port, stop_floor = _echo_floor_server(resp_payload)
        try:
            def floor_worker():
                wch = grpc.insecure_channel(f"127.0.0.1:{floor_port}")
                stub = wch.unary_unary(
                    "/bench.Floor/Echo",
                    request_serializer=lambda b: b,
                    response_deserializer=lambda b: b)
                return (lambda: stub(sr_bytes)), wch.close

            out["qdrant_grpc_floor"] = sustain(floor_worker)
        finally:
            stop_floor()
    finally:
        ch.close()
        grpc_srv.stop()
        bolt.stop()
        http.stop()
        db.close()
    floor = out.pop("qdrant_grpc_floor", None)
    result = {
        name: {
            "ops_per_s": ops,
            "vs_baseline": round(ops / _SURFACE_BASELINES[name], 3),
        }
        for name, ops in out.items()
    }
    if floor and "qdrant_grpc" in result:
        result["qdrant_grpc"]["framework_floor"] = floor
        result["qdrant_grpc"]["vs_floor"] = round(
            result["qdrant_grpc"]["ops_per_s"] / floor, 3)
    result["config"] = {
        "cpus": cpus, "concurrency": conc,
        "baseline_note": "reference numbers from a 16-core M3 Max "
                         "(testing/e2e/README.md); vs_baseline is the "
                         "absolute ratio, not per-core",
    }
    return result


# ---------------------------------------------------------------------------
# open-loop load harness (ISSUE 7)
# ---------------------------------------------------------------------------
#
# Every stage above is CLOSED-LOOP: each worker waits for its response
# before sending the next request, so offered load automatically tracks
# capacity and queueing collapse is structurally invisible (the GPU
# graph-search survey, arXiv:2602.16719, shows the batch/latency knee is
# exactly what closed-loop harnesses flatten). This harness generates
# POISSON arrivals at configured rates — arrivals never wait for
# completions — sweeps the rate to locate the saturation knee, and
# records p50/p95/p99-at-load, achieved-vs-offered QPS and
# queue-collapse detection into the artifact. scripts/bench_sentinel.py
# gates `p99_at_load` so future batching/admission PRs are held to a
# tail-latency-under-load floor, not just closed-loop QPS.


class _AsyncHttpPool:
    """Keep-alive asyncio HTTP client pool with prebuilt request bytes
    (the async analog of _LeanHttpClient). A fixed pool bounds client
    fds; a request arriving while every connection is busy waits for a
    free one — that wait stays in its measured latency, which is what a
    real client behind a connection pool experiences under overload."""

    def __init__(self, port: int, request: bytes, size: int = 32):
        self.port = port
        self.request = request
        self.size = size
        self._q = None

    async def init(self):
        import asyncio

        self._q = asyncio.Queue()
        for _ in range(self.size):
            conn = await asyncio.open_connection("127.0.0.1", self.port)
            self._q.put_nowait(conn)
        return self

    async def send(self) -> None:
        import asyncio
        import re as _re

        conn = await self._q.get()
        try:
            if conn is None:
                # slot poisoned by an earlier failure: reconnect lazily
                conn = await asyncio.open_connection(
                    "127.0.0.1", self.port)
            reader, writer = conn
            writer.write(self.request)
            await writer.drain()
            head = await reader.readuntil(b"\r\n\r\n")
            m = _re.search(rb"content-length:\s*(\d+)", head, _re.I)
            body = await reader.readexactly(int(m.group(1)) if m else 0)
            if not head.startswith(b"HTTP/1.1 2"):
                raise RuntimeError(f"bad status: {head[:40]!r} "
                                   f"{body[:120]!r}")
        except BaseException:
            # Poisoned connection: return the slot as a None token (the
            # next send on it reconnects) so the pool never shrinks. The
            # put must not await — a reconnect here could itself fail or
            # be cancelled by the drain timeout, losing the slot and
            # eventually deadlocking every later send on _q.get().
            if conn is not None:
                conn[1].close()
            self._q.put_nowait(None)
            raise
        self._q.put_nowait((reader, writer))

    async def aclose(self) -> None:
        while not self._q.empty():
            conn = self._q.get_nowait()
            if conn is not None:
                conn[1].close()


async def _open_loop_point(send, rate_qps: float, duration_s: float,
                           seed: int, max_arrivals: int = 30_000,
                           drain_timeout_s: float = 15.0):
    """One open-loop measurement point: schedule Poisson arrivals at
    ``rate_qps`` for ``duration_s``; every arrival spawns a task
    immediately (no waiting on in-flight completions). Returns offered
    vs achieved QPS and the latency distribution AT that load."""
    import asyncio

    lat = []
    errors = [0]

    async def one():
        t0 = time.perf_counter()
        try:
            await send()
        except Exception:
            errors[0] += 1
            return
        lat.append(time.perf_counter() - t0)

    loop = asyncio.get_running_loop()
    rng = np.random.default_rng(seed)
    t_start = loop.time()
    t_end = t_start + duration_s
    t_next = t_start
    tasks = []
    while t_next < t_end and len(tasks) < max_arrivals:
        delay = t_next - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(loop.create_task(one()))
        t_next += rng.exponential(1.0 / rate_qps)
    arrival_window = loop.time() - t_start
    timed_out = 0
    if tasks:
        _done, pending = await asyncio.wait(tasks,
                                            timeout=drain_timeout_s)
        timed_out = len(pending)
        for p in pending:
            p.cancel()
    wall = loop.time() - t_start
    offered = len(tasks)
    completed = len(lat)
    point = {
        "offered_qps": round(offered / max(arrival_window, 1e-9), 1),
        "achieved_qps": round(completed / max(wall, 1e-9), 1),
        "offered": offered,
        "completed": completed,
        "errors": errors[0],
        "timed_out": timed_out,
    }
    if lat:
        arr = np.asarray(lat) * 1e3
        for q, name in ((50, "p50_ms"), (95, "p95_ms"), (99, "p99_ms")):
            point[name] = round(float(np.percentile(arr, q)), 3)
        point["mean_ms"] = round(float(arr.mean()), 3)
    else:
        point.update({"p50_ms": None, "p95_ms": None, "p99_ms": None,
                      "mean_ms": None})
    return point


def _estimate_knee(points):
    """Saturation-knee estimate over a rate sweep (points in offered-
    rate order). A point has COLLAPSED when the service stopped keeping
    up with offered load (achieved < 85% of offered), requests timed
    out, or the p99 latency slope blew up (>3x the previous point at a
    <=2.5x rate step, or >5x the lowest-rate p99) — the queueing-
    collapse signature a closed-loop bench can never show. The knee is
    the best achieved rate among stable points; ``p99_at_load_ms`` is
    the tail latency AT that knee (falling back to the first point so
    the gate metric exists even on a fully-collapsed sweep)."""
    base_p99 = next((p["p99_ms"] for p in points
                     if p.get("p99_ms") is not None), None)
    prev = None
    for pt in points:
        collapsed = False
        if pt["offered"] > 0 and pt["completed"] < 0.85 * pt["offered"]:
            collapsed = True
        if pt["timed_out"] > 0 or (pt["errors"] > 0.05 * max(pt["offered"], 1)):
            collapsed = True
        p99 = pt.get("p99_ms")
        if p99 is None:
            collapsed = True
        else:
            if base_p99 is not None and p99 > max(5.0 * base_p99,
                                                  base_p99 + 50.0):
                collapsed = True
            if (prev is not None and prev.get("p99_ms")
                    and prev["offered_qps"] > 0
                    and pt["offered_qps"] / prev["offered_qps"] <= 2.5
                    and p99 > 3.0 * prev["p99_ms"]
                    and p99 > (base_p99 or 0.0) + 20.0):
                collapsed = True
        pt["collapsed"] = collapsed
        prev = pt
    stable = [p for p in points if not p["collapsed"]]
    knee = (max(stable, key=lambda p: p["achieved_qps"]) if stable
            else (points[0] if points else None))
    return {
        "knee_qps": knee["achieved_qps"] if knee else None,
        "p99_at_load_ms": knee.get("p99_ms") if knee else None,
        "knee_offered_qps": knee["offered_qps"] if knee else None,
        "queue_collapse_detected": any(p["collapsed"] for p in points),
    }


def _shed_counts():
    """Flat snapshot of the admission counters the overload sweep
    brackets: total sheds + deadline misses (ISSUE 15)."""
    from nornicdb_tpu.obs import REGISTRY

    out = {"shed": 0.0, "deadline_miss": 0.0}
    fam = REGISTRY.get("nornicdb_shed_total")
    if fam is not None:
        out["shed"] = sum(c.value for c in fam.children().values())
    fam = REGISTRY.get("nornicdb_deadline_miss_total")
    if fam is not None:
        out["deadline_miss"] = sum(c.value
                                   for c in fam.children().values())
    return out


def _overload_sweep(factory, knee_qps, knee_offered_qps, knee_p99_ms,
                    duration_s: float, max_arrivals: int,
                    multipliers=(1.2, 1.5), ratios: bool = True):
    """The admission-control acceptance measurement (ISSUE 15): drive
    the surface PAST its measured knee (1.2x / 1.5x the knee's offered
    rate) and record what the scheduler does about it — p99-at-load of
    the SERVED stream, goodput (successful completions/s), the shed
    fraction (server-side counter bracket), and unacknowledged drops
    (arrivals that got neither an answer nor an honest error). The
    ROADMAP acceptance story: p99 stays bounded (vs 74x blow-up
    unmanaged), goodput holds ~knee, and every unserved query got an
    explicit 429/RESOURCE_EXHAUSTED."""
    import asyncio

    from nornicdb_tpu.api.grpc_server import GrpcServer

    base = knee_offered_qps or knee_qps
    doc = {"knee_qps": knee_qps, "knee_offered_qps": knee_offered_qps,
           "p99_at_knee_ms": knee_p99_ms, "points": {}}

    async def run():
        loop = asyncio.get_running_loop()
        loop.set_exception_handler(GrpcServer._quiet_poller_eagain)
        send, aclose = await factory()
        try:
            for _ in range(3):
                try:
                    await send()
                except Exception:  # noqa: BLE001 — warmup only
                    pass
            for j, mult in enumerate(multipliers):
                before = _shed_counts()
                pt = await _open_loop_point(
                    send, max(base * mult, 5.0), duration_s,
                    seed=91 + j, max_arrivals=max_arrivals)
                after = _shed_counts()
                shed = after["shed"] - before["shed"]
                offered = max(pt["offered"], 1)
                pt["multiplier"] = mult
                pt["shed"] = shed
                pt["shed_fraction"] = round(shed / offered, 4)
                pt["deadline_misses"] = (after["deadline_miss"]
                                         - before["deadline_miss"])
                # goodput IS achieved_qps: completions exclude errors
                pt["goodput_qps"] = pt["achieved_qps"]
                pt["unacked"] = pt["timed_out"]
                doc["points"][f"{mult:g}"] = pt
        finally:
            await aclose()

    asyncio.run(run())
    p12 = doc["points"].get("1.2") or {}
    doc["p99_at_1p2x_ms"] = p12.get("p99_ms")
    doc["goodput_at_1p2x"] = p12.get("goodput_qps")
    doc["shed_fraction_1p2x"] = p12.get("shed_fraction")
    # the honest-backpressure invariant: shed > 0 must imply ZERO
    # unacknowledged drops (every unserved query got an explicit
    # 429/RESOURCE_EXHAUSTED — timeouts are silent drops)
    doc["unacked_with_shed_1p2x"] = (
        p12.get("unacked", 0) if (p12.get("shed") or 0) > 0 else 0)
    # the ABSOLUTE acceptance ratios (sentinel bounds: p99 at 1.2x
    # within 5x the at-knee p99, goodput >= 0.9x knee) only carry
    # meaning at full scale: tiny dry-run windows (0.25s) are pure
    # measurement noise, so they emit None there and the sentinel
    # skips (the relative p99/goodput gates still ride the dry run)
    if ratios and p12.get("p99_ms") and knee_p99_ms:
        doc["p99_bound_ratio_1p2x"] = round(
            p12["p99_ms"] / knee_p99_ms, 3)
    else:
        doc["p99_bound_ratio_1p2x"] = None
    if ratios and p12.get("goodput_qps") and knee_qps:
        doc["goodput_ratio_1p2x"] = round(
            p12["goodput_qps"] / knee_qps, 4)
    else:
        doc["goodput_ratio_1p2x"] = None
    return doc


def _tier_fractions(before, after):
    """Served-tier mix of one window: fraction of the window's served
    queries per ``surface:tier`` key (obs.audit.tier_counts deltas)."""
    deltas = {}
    for key, v in after.items():
        d = v - before.get(key, 0.0)
        if d > 0:
            deltas[key] = d
    total = sum(deltas.values())
    if total <= 0:
        return {}
    return {k: round(v / total, 4) for k, v in sorted(deltas.items())}


def _open_loop_sweep(factory, multipliers, duration_s: float,
                     calib_s: float, calib_conc: int,
                     max_arrivals: int, explicit_rates=None,
                     point_probe=None):
    """Calibrate a closed-loop baseline, then sweep open-loop arrival
    rates at ``multipliers`` x that baseline (or ``explicit_rates``
    QPS). One event loop per sweep; the async client (channel/pool) is
    shared across every point, like a real caller fleet.
    ``point_probe`` (returns a flat counter snapshot) brackets every
    swept point so each carries its own served-tier mix — what actually
    answered at each offered rate, not just how fast (ISSUE 10)."""
    import asyncio

    from nornicdb_tpu.api.grpc_server import GrpcServer

    async def run():
        loop = asyncio.get_running_loop()
        # the harness loop sees the same cross-loop grpc-aio poller
        # EAGAIN noise the server loop does — share its squelch
        loop.set_exception_handler(GrpcServer._quiet_poller_eagain)
        send, aclose = await factory()
        try:
            for _ in range(3):
                await send()  # connection + compile warmup
            # closed-loop calibration: small worker fleet, short window
            stop_at = loop.time() + calib_s
            counts = [0] * calib_conc

            async def worker(i):
                while loop.time() < stop_at:
                    try:
                        await send()
                    except Exception:
                        continue
                    counts[i] += 1

            t0 = loop.time()
            await asyncio.gather(*(worker(i) for i in range(calib_conc)))
            base_qps = sum(counts) / max(loop.time() - t0, 1e-9)
            rates = (list(explicit_rates) if explicit_rates
                     else [max(base_qps * m, 5.0) for m in multipliers])
            points = []
            for j, rate in enumerate(rates):
                tiers0 = point_probe() if point_probe else None
                pt = await _open_loop_point(
                    send, rate, duration_s, seed=17 + j,
                    max_arrivals=max_arrivals)
                if tiers0 is not None:
                    pt["served_tiers"] = _tier_fractions(
                        tiers0, point_probe())
                points.append(pt)
            doc = {
                "closed_loop_qps": round(base_qps, 1),
                "points": points,
            }
            doc.update(_estimate_knee(points))
            return doc
        finally:
            await aclose()

    return asyncio.run(run())


def _hist_state(name: str):
    """Label-less histogram family snapshot (None when unregistered)."""
    from nornicdb_tpu.obs import REGISTRY

    fam = REGISTRY.get(name)
    return fam.snapshot() if fam is not None else None


def _batch_size_dist(name: str, before):
    """Per-bucket delta of a batch-size histogram across one sweep —
    the coalescing-quality evidence of the wire-worker sweep: batch
    sizes should WIDEN as frontend count grows (ISSUE 11)."""
    after = _hist_state(name)
    if not after or before is None:
        return None
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    n = after["count"] - before["count"]
    total = after["sum"] - before["sum"]
    return {"buckets": [int(b) for b in after["buckets"]],
            "counts": counts, "n": n,
            "mean": round(total / n, 2) if n else None}


def _sweep_brief(doc):
    """The per-worker-count subset of a sweep doc the artifact keeps."""
    if not isinstance(doc, dict):
        return {"error": "sweep missing"}
    return {k: doc.get(k) for k in
            ("closed_loop_qps", "knee_qps", "p99_at_load_ms",
             "knee_offered_qps", "queue_collapse_detected")}


def _fleet_trace_completeness(fleet, qpool, k: int,
                              probes: int = 32) -> float:
    """Fraction of traced ring-routed reads whose span tree carries
    the full plane-side chain (ring.claim -> plane.coalesce ->
    device.dispatch) grafted back across the broker seam (ISSUE 13).
    Runs the REAL BrokerClient/DispatchBroker OP_VEC path (thread
    mode) over the fleet router — the same seam the wire plane's
    frontend workers serve through."""
    from nornicdb_tpu import obs as _obs
    from nornicdb_tpu.api.wire_plane import (
        BrokerSearch,
        resolve_vec_dispatch,
    )
    from nornicdb_tpu.search.broker import BrokerClient, DispatchBroker

    def local_fn(key, queries, kk):
        return resolve_vec_dispatch(fleet.router.primary_db, key,
                                    queries, kk)

    def vec_dispatch(key, queries, kk):
        return fleet.router.vec_dispatch(key, queries, kk, local_fn)

    broker = DispatchBroker(vec_dispatch, targets={},
                            n_workers=1, slots=8).start()
    client = None
    try:
        client = BrokerClient(
            broker.client_spec(0, cross_process=False))
        search = BrokerSearch(client)
        need = ("ring.claim", "plane.coalesce", "device.dispatch")
        complete = 0
        for i in range(probes):
            with _obs.trace("wire", method="bench.fleet_trace",
                            transport="bench") as root:
                search.vector_search_candidates(
                    qpool[i % len(qpool)], k=k)
            names = root.span_names()
            if all(n in names for n in need):
                complete += 1
        return round(complete / max(probes, 1), 4)
    finally:
        if client is not None:
            client.close()
        broker.stop()


def _bench_fleet(tiny: bool = False):
    """Read-fleet stage (ISSUE 12): an in-process 1-primary/2-replica
    topology over real loopback WAL streaming. Measures (1) READ
    SCALING — closed-loop vector-read throughput through the
    replica-aware router vs the primary alone; (2) REPLAY LAG — peak
    replica lag (WAL ops) under a write burst and the time the fleet
    takes to drain it; (3) DRAIN-ON-BREACH — a replica pushed past the
    lag threshold leaves the read rotation (degrade-ledger
    ``replica_lag`` record) and rejoins once healed. ``replica_parity``
    is the parity-gated-admission verdict: probe answers from each
    replica's device path vs the primary's exact host reference (the
    sentinel gates it absolutely at the exact-contract floor 1.0)."""
    import shutil
    import tempfile
    import threading as _threading

    from nornicdb_tpu.obs import audit as _fleet_audit
    from nornicdb_tpu.replication.read_fleet import ReadFleet

    n = 300 if tiny else 4000
    d = 16 if tiny else 64
    secs = 0.25 if tiny else 2.0
    burst = 120 if tiny else 1500
    n_threads = 4 if tiny else 8
    k = 10
    tmp = tempfile.mkdtemp(prefix="nornic-fleet-")
    out = {"replicas": 2, "n": n, "dims": d}
    fleet = None
    try:
        fleet = ReadFleet(tmp, n_replicas=2, heartbeat_interval=0.05)
        db = fleet.primary_db
        rng = np.random.default_rng(12)
        vecs = rng.normal(size=(n + burst, d)).astype(np.float32)
        for i in range(n):
            db.store(f"fleet doc {i}", node_id=f"f{i}",
                     embedding=[float(x) for x in vecs[i]])
        out["converged"] = bool(fleet.wait_converged(60.0))

        # parity-gated admission (PR 10 floors: exact 1.0)
        probe_ids = rng.integers(0, n, size=8)
        ratios = fleet.admit_all([vecs[i] for i in probe_ids], k=k)
        out["replica_parity"] = min(ratios.values())
        out["admitted"] = sum(
            1 for s in fleet.router.drain_state().values()
            if s["admitted"])

        # read scaling: the same closed-loop drivers against the
        # router (reads fan across both replicas) and the primary alone
        local = fleet.router.primary_db.search
        qpool = vecs[rng.integers(0, n, size=256)]

        def measure(read_one):
            counts = [0] * n_threads
            stop_at = time.time() + secs

            def worker(t):
                r = np.random.default_rng(t)
                while time.time() < stop_at:
                    q = qpool[int(r.integers(0, len(qpool)))]
                    read_one(q)
                    counts[t] += 1

            threads = [_threading.Thread(target=worker, args=(t,))
                       for t in range(n_threads)]
            t0 = time.time()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            return sum(counts) / max(time.time() - t0, 1e-9)

        def via_router(q):
            fleet.router.vec_dispatch(
                "__service__", q[None, :], k,
                lambda key, qs, kk: local._ann_search_batch(qs, kk))

        def via_primary(q):
            local._ann_search_batch(q[None, :], k)

        out["single_read_qps"] = round(measure(via_primary), 1)
        out["fleet_read_qps"] = round(measure(via_router), 1)
        out["read_scaling"] = round(
            out["fleet_read_qps"] / max(out["single_read_qps"], 1e-9), 3)

        # replay lag under a write burst: peak replica lag + drain time
        t_burst = time.time()
        for i in range(burst):
            db.store(f"burst doc {i}", node_id=f"b{i}",
                     embedding=[float(x) for x in vecs[n + i]])
        peak_lag = max(r.standby.lag_ops() for r in fleet.replicas)
        drained_at = None
        deadline = time.time() + 60.0
        while time.time() < deadline:
            lags = [r.standby.lag_ops() for r in fleet.replicas]
            peak_lag = max(peak_lag, max(lags))
            if max(lags) == 0 and all(
                    r.standby.applied_seq >= db._base.wal.last_seq
                    for r in fleet.replicas):
                drained_at = time.time()
                break
            time.sleep(0.01)
        out["replay_lag"] = {
            "burst_ops": burst,
            "peak_lag_ops": int(peak_lag),
            "drain_s": (round(drained_at - t_burst, 3)
                        if drained_at else None),
        }

        # per-record replication latency (ISSUE 13): the burst above
        # streamed through the WAL plane, so both replicas observed
        # nornicdb_replication_apply_delay_seconds — report p50/p99 in
        # ms per node ("lag 400 ops" -> "p99 replay delay N ms")
        from nornicdb_tpu.obs.metrics import REGISTRY as _REG
        delay_fam = _REG.get("nornicdb_replication_apply_delay_seconds")
        apply_delay = {}
        for key, child in (delay_fam.children().items()
                           if delay_fam else ()):
            snap = child.snapshot()
            if not snap["count"]:
                continue
            apply_delay[key[0]] = {
                "count": snap["count"],
                "p50_ms": round((child.quantile(0.5) or 0.0) * 1e3, 3),
                "p99_ms": round((child.quantile(0.99) or 0.0) * 1e3, 3),
            }
        out["apply_delay"] = apply_delay
        out["apply_delay_p99_ms"] = (
            max(d["p99_ms"] for d in apply_delay.values())
            if apply_delay else None)

        # cross-process trace completeness (ISSUE 13): traced reads
        # through the broker ring (thread-mode DispatchBroker over the
        # fleet router — the same OP_VEC seam the wire plane serves
        # through) must come back with the FULL plane-side span chain
        # grafted into the live root. Fraction of requests whose trace
        # carries ring.claim + plane.coalesce + device.dispatch; the
        # sentinel gates this ABSOLUTELY at 1.0 — a broken propagation
        # seam is wrong, not slow.
        out["trace_completeness"] = _fleet_trace_completeness(
            fleet, qpool, k, probes=16 if tiny else 32)

        # drain-on-breach: push replica-0 past the lag threshold via an
        # inflated primary watermark; the router must stop routing to
        # it (ledger reason replica_lag) and re-admit once healed
        r0 = fleet.replicas[0]

        def pick_names(tries=8):
            # None = primary fallback (e.g. the sibling replica is
            # momentarily catching up) — a routing verdict, not a crash
            out = set()
            for _ in range(tries):
                r = fleet.router.pick_read()
                out.add(r.name if r is not None else "primary")
            return out

        with r0.standby._lock:
            r0.standby.primary_last_seq += 1_000_000
        time.sleep(fleet.router._check_interval_s * 2)
        picked = pick_names()
        out_drain = {"breached_drained": r0.name not in picked}
        ledger = [rec for rec in _fleet_audit.degrade_snapshot(200)
                  if rec.get("surface") == "fleet"
                  and rec.get("index") == r0.name
                  and rec.get("reason") == "replica_lag"]
        out_drain["ledger_reason"] = bool(ledger)
        with r0.standby._lock:
            r0.standby.primary_last_seq = r0.standby.applied_seq
        time.sleep(fleet.router._check_interval_s * 2)
        out_drain["recovered"] = r0.name in pick_names()
        # the incident timeline must replay this drain->recover as
        # ORDERED records (ISSUE 13): one drain, then one admit for
        # the same node, ascending seq
        from nornicdb_tpu.obs import events as _fleet_events
        evs = [e for e in _fleet_events.event_snapshot(limit=200)
               if e.get("node") == r0.name
               and e["kind"] in ("drain", "admit")]
        drain_seqs = [e["seq"] for e in evs if e["kind"] == "drain"]
        admit_seqs = [e["seq"] for e in evs if e["kind"] == "admit"]
        out_drain["events_ordered"] = bool(
            drain_seqs and admit_seqs
            and min(drain_seqs) < max(admit_seqs))
        out["drain"] = out_drain
        return out
    except Exception as exc:  # noqa: BLE001 — stage isolation
        out["error"] = f"{type(exc).__name__}: {exc}"[:400]
        return out
    finally:
        if fleet is not None:
            fleet.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_fleet_proc(tiny: bool = False):
    """Multi-process read-fleet stage (ISSUE 16): 1 in-parent primary
    + 2 REAL replica subprocesses (WAL streamed over the two-plane
    socket transport) behind the router's RemoteReplica handles.
    Measures (1) READ SCALING — closed-loop ``/nornicdb/search``
    goodput through the fleet router (reads fan out-of-GIL across the
    replica processes) vs the primary's own HTTP surface alone, with
    admission sheds (429/503) counted separately, never as served;
    (2) HTTP PARITY — ranked result ids from each replica's surface vs
    the primary's surface for the same queries (absolute 1.0: a
    replica serving different answers is a correctness bug); (3)
    REPLAY LAG — peak replica lag under a primary write burst and the
    drain time, observed over the remote /readyz watermark docs; (4)
    TRACE COMPLETENESS — the fraction of traced routed reads whose
    trace id shows up as a root span in the serving CHILD's own trace
    ring (the propagated X-Nornic-Trace context crossed the process
    boundary). ``cores`` rides the artifact: out-of-GIL scaling needs
    real cores, so the sentinel's scaling floor is core-aware (a
    1-core box gates collapse, not parallelism)."""
    import shutil
    import tempfile
    import threading as _threading
    import urllib.request as _urlreq

    from nornicdb_tpu import obs as _obs
    from nornicdb_tpu.api.fleet_router import RemoteReplica, ReplicaBusy
    from nornicdb_tpu.replication.fleet_proc import ProcessReadFleet

    n = 150 if tiny else 2000
    secs = 0.2 if tiny else 3.0
    burst = 60 if tiny else 800
    n_threads = 4 if tiny else 8
    n_probes = 6 if tiny else 16
    limit = 10
    words = ["alpha", "bravo", "charlie", "delta",
             "echo", "foxtrot", "golf", "hotel"]
    tmp = tempfile.mkdtemp(prefix="nornic-fleetproc-")
    out = {"replicas": 2, "n": n, "cores": os.cpu_count() or 1}
    fleet = None
    try:
        fleet = ProcessReadFleet(tmp, n_replicas=2,
                                 heartbeat_interval=0.05,
                                 auto_embed=True,
                                 http_timeout_s=30.0)
        db = fleet.primary_db
        for i in range(n):
            db.store(f"fleet doc {i} about {words[i % 8]} "
                     f"topic {i % 31}", node_id=f"f{i}")
        out["converged"] = bool(fleet.wait_converged(120.0))
        fleet.admit_all_unchecked()
        pids = sorted(p.pid for p in fleet.procs)
        out["out_of_process"] = bool(
            len(set(pids)) == 2 and os.getpid() not in pids)

        # the primary's own HTTP surface through the same keep-alive
        # client the router uses: the single-process baseline
        primary = RemoteReplica("primary", fleet.primary_url,
                                timeout_s=30.0)

        # warm every surface past first-search compile/index-sync
        # (the first query on a cold node ranks through the fallback
        # tier — warmup is not optional for the parity gate)
        for w in range(6):
            q = {"query": f"warm {w} {words[w]}", "limit": limit}
            primary.search(q)
            for rem in fleet.remotes:
                rem.search(q)

        # HTTP parity: ranked ids, replica surface vs primary surface
        agree, total = 0, 0
        for i in range(n_probes):
            q = {"query": f"{words[i % 8]} topic {i % 31}",
                 "limit": limit}
            want = [r["id"] for r in primary.search(q)["results"]]
            for rem in fleet.remotes:
                got = [r["id"] for r in rem.search(q)["results"]]
                agree += int(got == want)
                total += 1
        out["replica_parity"] = round(agree / max(total, 1), 4)

        # closed-loop goodput: sheds (429/503 admission verdicts and
        # all-busy routing) are counted, never served
        def measure(read_one):
            ok = [0] * n_threads
            shed = [0] * n_threads
            err = [0] * n_threads
            stop_at = time.time() + secs

            def worker(t):
                i = 0
                while time.time() < stop_at:
                    i += 1
                    try:
                        if read_one(t, i) is None:
                            shed[t] += 1
                        else:
                            ok[t] += 1
                    except ReplicaBusy:
                        shed[t] += 1
                    except Exception:  # noqa: BLE001
                        err[t] += 1

            threads = [_threading.Thread(target=worker, args=(t,))
                       for t in range(n_threads)]
            t0 = time.time()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            rate = sum(ok) / max(time.time() - t0, 1e-9)
            return rate, sum(shed), sum(err)

        single_qps, single_shed, single_err = measure(
            lambda t, i: primary.search(
                {"query": f"s{t}x{i} fleet doc", "limit": limit}))
        fleet_qps, fleet_shed, fleet_err = measure(
            lambda t, i: fleet.router.http_search(
                {"query": f"r{t}x{i} fleet doc", "limit": limit}))
        out["single_read_qps"] = round(single_qps, 1)
        out["fleet_read_qps"] = round(fleet_qps, 1)
        out["read_scaling"] = round(
            fleet_qps / max(single_qps, 1e-9), 3)
        out["sheds"] = {"single": single_shed, "fleet": fleet_shed}
        out["errors"] = {"single": single_err, "fleet": fleet_err}

        # replay lag under a primary write burst, observed the way a
        # real operator would: over the remote /readyz watermark docs
        t_burst = time.time()
        for i in range(burst):
            db.store(f"burst doc {i} {words[i % 8]}",
                     node_id=f"bp{i}")
        db._base.wal.flush()
        target = db._base.wal.last_seq
        peak_lag, drained_at = 0, None
        deadline = time.time() + 120.0
        while time.time() < deadline:
            seqs = []
            for rem in fleet.remotes:
                rem.ready_reasons()
                seqs.append(rem.applied_seq() or 0)
            peak_lag = max(peak_lag, target - min(seqs))
            if min(seqs) >= target:
                drained_at = time.time()
                break
            time.sleep(0.02)
        out["replay_lag"] = {
            "burst_ops": burst,
            "peak_lag_ops": int(peak_lag),
            "drain_s": (round(drained_at - t_burst, 3)
                        if drained_at else None),
        }

        # cross-process trace completeness: every traced routed read's
        # trace id must be adopted as a ROOT span by the serving child
        # (checked in that child's own /admin/traces ring, right after
        # the read so ring churn can't evict it)
        found, probed = 0, 0
        for i in range(n_probes):
            with _obs.trace("fleet-proc-read") as span:
                doc = fleet.router.http_search(
                    {"query": f"t{i} {words[i % 8]} doc",
                     "limit": limit})
                tid = span.trace_id
            if doc is None:
                continue  # shed: nothing was served, nothing to trace
            probed += 1
            for proc in fleet.procs:
                with _urlreq.urlopen(proc.base_url + "/admin/traces",
                                     timeout=10) as resp:
                    body = json.loads(resp.read())
                if any(t.get("trace_id") == tid
                       for t in body.get("traces", [])):
                    found += 1
                    break
        out["trace_completeness"] = (
            round(found / probed, 4) if probed else None)
        return out
    except Exception as exc:  # noqa: BLE001 — stage isolation
        out["error"] = f"{type(exc).__name__}: {exc}"[:400]
        return out
    finally:
        if fleet is not None:
            fleet.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_load(tiny: bool = False, n_people: "int | None" = None,
                duration_s: "float | None" = None, explicit_rates=None,
                multipliers=None, worker_counts=None, wire_mode=None):
    """Open-loop load stage: Poisson arrivals against the REAL serving
    surfaces (qdrant gRPC Search and REST /nornicdb/search) through
    async clients. Emits offered-vs-achieved QPS, p50/p95/p99-at-load
    per swept rate, the saturation-knee estimate and queue-collapse
    verdict. ``tiny`` shrinks corpus/windows for the --dry-run schema
    pass (tests/test_bench_output.py) but only fills in parameters the
    caller left unset, so ``load_harness.py --tiny --n-people 2000``
    honors the explicit flag."""
    import grpc

    import nornicdb_tpu
    from nornicdb_tpu.api.grpc_server import GrpcServer
    from nornicdb_tpu.api.http_server import HttpServer
    from nornicdb_tpu.api.proto import qdrant_pb2 as q

    if n_people is None:
        n_people = 60 if tiny else 400
    if duration_s is None:
        duration_s = 0.25 if tiny else 1.5
    if multipliers is None:
        multipliers = (0.5, 1.5) if tiny else (0.3, 0.6, 0.9, 1.2)
    if tiny:
        calib_s, calib_conc, max_arrivals = 0.15, 4, 400
    else:
        calib_s, calib_conc, max_arrivals = 0.5, 8, 30_000

    os.environ.setdefault("NORNICDB_TPU_EMBEDDER", "hash")
    from nornicdb_tpu.obs import audit as _audit

    db = nornicdb_tpu.open(auto_embed=False)
    out = {"open_loop": True, "arrival": "poisson",
           "duration_s_per_point": duration_s, "surfaces": {}}
    http = grpc_srv = ch = None
    # shadow-parity auditing rides the load run (ISSUE 10): sample a
    # fraction of the device-served queries and compare against the
    # host reference, so the artifact carries parity-under-load, not
    # just parity-in-tests. Rate restored after the stage.
    _audit.AUDITOR.set_sample_rate(1.0 / 16.0 if tiny else 1.0 / 64.0)
    tiers_run0 = _audit.tier_counts()
    try:
        embedder = db._embedder
        for i in range(n_people):
            db.store(f"person{i} writes about topic{i % 7}",
                     node_id=f"p{i}", labels=["Person"],
                     properties={"name": f"person{i}", "idx": i},
                     embedding=embedder.embed(f"person{i} topic{i % 7}"))
        db.flush()
        db.recall("warm")
        http = HttpServer(db, port=0).start()
        grpc_srv = GrpcServer(db, port=0).start()
        # one-time qdrant collection setup over a sync channel
        ch = grpc.insecure_channel(grpc_srv.address)

        def call(method, request, response_cls):
            return ch.unary_unary(
                method,
                request_serializer=lambda r: r.SerializeToString(),
                response_deserializer=response_cls.FromString,
            )(request)

        req = q.CreateCollection(collection_name="load")
        req.vectors_config.params.size = embedder.dims
        req.vectors_config.params.distance = q.Cosine
        call("/qdrant.Collections/Create", req,
             q.CollectionOperationResponse)
        up = q.UpsertPoints(collection_name="load")
        for i in range(0, n_people, 2):
            node = db.storage.get_node(f"p{i}")
            p = up.points.add()
            p.id.num = i
            p.vectors.vector.data.extend(node.embedding)
        call("/qdrant.Points/Upsert", up, q.PointsOperationResponse)
        target = db.storage.get_node("p4")
        sr_bytes = q.SearchPoints(
            collection_name="load", vector=list(target.embedding),
            limit=5).SerializeToString()
        ch.close()
        ch = None

        def grpc_factory():
            async def make():
                ach = grpc.aio.insecure_channel(grpc_srv.address)
                stub = ach.unary_unary(
                    "/qdrant.Points/Search",
                    request_serializer=lambda b: b,
                    response_deserializer=lambda b: b)

                async def send():
                    await stub(sr_bytes)

                async def aclose():
                    await ach.close()

                return send, aclose

            return make()

        def grpc_factory_for(address):
            def factory():
                async def make():
                    ach = grpc.aio.insecure_channel(address)
                    stub = ach.unary_unary(
                        "/qdrant.Points/Search",
                        request_serializer=lambda b: b,
                        response_deserializer=lambda b: b)

                    async def send():
                        await stub(sr_bytes)

                    async def aclose():
                        await ach.close()

                    return send, aclose

                return make()

            return factory

        http_req = _LeanHttpClient.build(
            "/nornicdb/search", {"query": "topic1 person", "limit": 5})

        def http_factory_for(port):
            def factory():
                async def make():
                    pool = await _AsyncHttpPool(
                        port, http_req,
                        size=8 if tiny else 32).init()
                    return pool.send, pool.aclose

                return make()

            return factory

        mb0 = _hist_state("nornicdb_microbatch_batch_size")
        out["surfaces"]["qdrant_grpc_search"] = _open_loop_sweep(
            grpc_factory_for(grpc_srv.address), multipliers, duration_s,
            calib_s, calib_conc, max_arrivals, explicit_rates,
            point_probe=_audit.tier_counts)

        out["surfaces"]["rest_search"] = _open_loop_sweep(
            http_factory_for(http.port), multipliers, duration_s,
            calib_s, calib_conc, max_arrivals, explicit_rates,
            point_probe=_audit.tier_counts)

        # overload acceptance sweep (ISSUE 15): drive the gRPC surface
        # at 1.2x and 1.5x its measured knee and record p99-at-load,
        # shed fraction, goodput and unacknowledged drops — the
        # admission actuator's sentinel-gated contract
        g_sweep = out["surfaces"].get("qdrant_grpc_search") or {}
        if g_sweep.get("knee_qps"):
            out["overload"] = _overload_sweep(
                grpc_factory_for(grpc_srv.address),
                g_sweep.get("knee_qps"),
                g_sweep.get("knee_offered_qps"),
                g_sweep.get("p99_at_load_ms"),
                duration_s, max_arrivals, ratios=not tiny)
            from nornicdb_tpu import admission as _admission

            out["scheduler"] = _admission.scheduler_summary()

        # multi-worker wire-plane sweep (ISSUE 11): the SAME open-loop
        # harness against NORNICDB_WIRE_WORKERS ∈ {1, 2, 4} frontends.
        # Worker count 1 IS the single-process serving just measured —
        # its numbers are reused, so the sweep adds only the plane
        # runs. Each count records knee_qps per surface plus the batch
        # size distribution its coalescer saw (microbatch for 1,
        # broker for >= 2: coalescing must widen with more frontends).
        counts = tuple(worker_counts) if worker_counts else (
            (1, 2) if tiny else (1, 2, 4))
        mode = wire_mode or os.environ.get(
            "NORNICDB_WIRE_SWEEP_MODE") or (
                "thread" if tiny else "process")
        wire = {"mode": mode, "counts": [int(c) for c in counts],
                "per_count": {}}
        out["wire_workers"] = wire
        for w in counts:
            if w <= 1:
                wire["per_count"]["1"] = {
                    "grpc": _sweep_brief(
                        out["surfaces"].get("qdrant_grpc_search")),
                    "rest": _sweep_brief(
                        out["surfaces"].get("rest_search")),
                    "batch_size_dist": _batch_size_dist(
                        "nornicdb_microbatch_batch_size", mb0),
                }
                continue
            from nornicdb_tpu.api.wire_plane import WirePlane

            plane = None
            try:
                plane = WirePlane(db, workers=int(w), mode=mode).start()
                mbw = _hist_state("nornicdb_microbatch_batch_size")
                br0 = _hist_state("nornicdb_broker_batch_size")
                g_sweep = _open_loop_sweep(
                    grpc_factory_for(plane.grpc_address), multipliers,
                    duration_s, calib_s, calib_conc, max_arrivals,
                    explicit_rates, point_probe=_audit.tier_counts)
                r_sweep = _open_loop_sweep(
                    http_factory_for(plane.http_port), multipliers,
                    duration_s, calib_s, calib_conc, max_arrivals,
                    explicit_rates, point_probe=_audit.tier_counts)
                wire["per_count"][str(int(w))] = {
                    "grpc": _sweep_brief(g_sweep),
                    "rest": _sweep_brief(r_sweep),
                    # device-facing coalescing quality: the shared
                    # plane's MicroBatcher batch sizes during this
                    # sweep (wider with more frontends is the claim)
                    "batch_size_dist": _batch_size_dist(
                        "nornicdb_microbatch_batch_size", mbw),
                    # raw-embedding ring groups (OP_VEC), when the
                    # nornic vector surface took part
                    "ring_batch_dist": _batch_size_dist(
                        "nornicdb_broker_batch_size", br0),
                }
            except Exception as exc:  # noqa: BLE001 — sweep must emit
                wire["per_count"][str(int(w))] = {
                    "error": f"{type(exc).__name__}: {exc}"[:300]}
            finally:
                if plane is not None:
                    plane.stop()
    except Exception as exc:  # noqa: BLE001 — stage must always emit
        out["error"] = f"{type(exc).__name__}: {exc}"[:400]
    finally:
        # stop traffic first, then DRAIN the audit queue while the
        # indexes are still alive (a reference replay against a closed
        # db would read as a drop — or worse, a false mismatch — in
        # the sentinel-gated verdict), and only then tear the db down
        if ch is not None:
            ch.close()
        if grpc_srv is not None:
            grpc_srv.stop()
        if http is not None:
            http.stop()
        # whole-run tier mix + the shadow-parity verdict the sentinel
        # gates: exact tiers must replay the host reference at 1.0,
        # statistical tiers at their documented floors. Null when no
        # tier of that class was sampled (the check then skips).
        try:
            _audit.AUDITOR.flush(timeout_s=5.0)
            out["served_tiers"] = _tier_fractions(
                tiers_run0, _audit.tier_counts())
            out["shadow_parity"] = _shadow_parity_verdict(_audit)
        except Exception as exc:  # noqa: BLE001
            out["shadow_parity"] = {
                "error": f"{type(exc).__name__}: {exc}"[:200]}
        _audit.AUDITOR.set_sample_rate(None)
        db.close()
    return out


def _shadow_parity_verdict(_audit):
    """Worst rolling parity per contract class from the auditor's
    windows: {"exact": min over exact tiers, "statistical": min over
    statistical tiers, "sampled": N} — nulls when unsampled."""
    summary = _audit.audit_summary()
    exact = statistical = None
    for key, doc in summary["tiers"].items():
        tier = key.split(":", 1)[1]
        p = doc.get("parity")
        if p is None or not doc.get("samples"):
            continue
        if tier in _audit.EXACT_TIERS:
            exact = p if exact is None else min(exact, p)
        elif tier in _audit.STATISTICAL_FLOORS:
            statistical = (p if statistical is None
                           else min(statistical, p))
    return {"exact": exact, "statistical": statistical,
            "sampled": summary["sampled"],
            "mismatches": summary["mismatches"]}


def _bench_tenants(tiny: bool = False):
    """Multi-tenant overload (ISSUE 18): one tenant floods qdrant REST
    bulk upserts at ~2x the single-connection knee while nine tenants
    serve interactive REST reads, every request carrying a tenant
    identity (readers: X-Nornic-Tenant header; flooder: the
    collection->tenant mapping — no header at all). The artifact
    proves (a) attribution completeness 1.0 over the stage window,
    (b) the flooding tenant owns >= 0.5 of the measured dispatch cost
    via the write-path pricing + batch-mix split, (c) the rollup
    surfaces it at /admin/tenants, and (d) the noisy-neighbor detector
    files its advisory journal event while admission posture >=
    degrade (held there through the fleet-tighten source — the same
    mechanism a peer posture feed uses)."""
    import threading as _thr
    import urllib.request as _url

    import nornicdb_tpu
    from nornicdb_tpu import admission as _admission
    from nornicdb_tpu import obs as _obs
    from nornicdb_tpu.api.http_server import HttpServer
    from nornicdb_tpu.obs import tenant as _ten
    from nornicdb_tpu.obs.metrics import REGISTRY as _REG

    n_people = 60 if tiny else 400
    calib_s = 0.15 if tiny else 0.5
    flood_s = 0.6 if tiny else 3.0
    n_readers = 9
    points_per = 256
    os.environ.setdefault("NORNICDB_TPU_EMBEDDER", "hash")
    # deterministic detector window for the stage: tiny floods move
    # few FLOPs, so the advisory floor scales down with the run
    min_flops_prev = os.environ.get("NORNICDB_TENANT_NOISY_MIN_FLOPS")
    if tiny:
        os.environ["NORNICDB_TENANT_NOISY_MIN_FLOPS"] = "1000"
    _ten.reload()
    # the 30s rolling window must hold ONLY this scenario's costs:
    # an earlier stage's priced dispatches landing in-window would
    # dilute the flooder's share below the advisory threshold on a
    # fast run (clears window + cooldowns; `emitted` is cumulative)
    _ten.DETECTOR.reset()
    emitted0 = _ten.DETECTOR.emitted

    def _by_tenant(name):
        fam = _REG.get(name)
        snap = {}
        for key, child in (fam.children() if fam else {}).items():
            snap[key[0]] = snap.get(key[0], 0.0) + child.value
        return snap

    def _delta(cur, before):
        return {t: v - before.get(t, 0.0) for t, v in cur.items()
                if v - before.get(t, 0.0) > 1e-9}

    db = nornicdb_tpu.open(auto_embed=False)
    out = {"tenants_total": 1 + n_readers, "flood_s": flood_s,
           "points_per_upsert": points_per}
    http = None

    def _posture_degrade():
        # fresh peer-published degrade: tightens, never loosens
        return (1, 0.0)

    try:
        embedder = db._embedder
        d = embedder.dims
        for i in range(n_people):
            db.store(f"person{i} writes about topic{i % 7}",
                     node_id=f"p{i}", labels=["Person"],
                     properties={"name": f"person{i}", "idx": i},
                     embedding=embedder.embed(f"person{i} topic{i % 7}"))
        db.flush()
        db.recall("warm")
        # attribution window opens AFTER warmup: the in-process warm
        # query above is direct library use (no ingress, no tenant)
        # and must not read as an attribution seam
        req0 = _by_tenant("nornicdb_tenant_requests_total")
        flops0 = _by_tenant("nornicdb_tenant_cost_flops_total")
        http = HttpServer(db, port=0).start()
        setup = _LeanHttpClient(http.port)
        setup.roundtrip(_LeanHttpClient.build(
            "/collections/bulk_flood",
            {"vectors": {"size": d, "distance": "Cosine"}},
            method="PUT"))
        setup.close()
        vec = [((31 * j) % 97) / 97.0 for j in range(d)]
        flood_req = _LeanHttpClient.build(
            "/collections/bulk_flood/points",
            {"points": [{"id": j, "vector": vec}
                        for j in range(points_per)]},
            method="PUT")
        # single-connection closed-loop knee for the bulk-upsert shape
        calib = _LeanHttpClient(http.port)
        done = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < calib_s:
            calib.roundtrip(flood_req)
            done += 1
        calib.close()
        knee = done / (time.perf_counter() - t0)
        out["knee_upserts_per_s"] = round(knee, 1)

        counts = {"flood": 0, "flood_shed": 0, "reads": 0,
                  "read_errors": 0}
        lock = _thr.Lock()
        stop_at = time.perf_counter() + flood_s

        def _loop(cli, req, ok_key, err_key):
            """Closed-loop client that keeps offering load through
            shed verdicts (a flooder does not politely stop at 429)."""
            n = err = 0
            while time.perf_counter() < stop_at:
                try:
                    cli.roundtrip(req)
                    n += 1
                except RuntimeError:
                    err += 1  # shed (429) — still offered load
                except ConnectionError:
                    break
            cli.close()
            with lock:
                counts[ok_key] += n
                counts[err_key] += err

        def flooder():
            _loop(_LeanHttpClient(http.port), flood_req,
                  "flood", "flood_shed")

        def reader(i):
            req = _LeanHttpClient.build(
                "/nornicdb/search",
                {"query": f"topic{i % 7} person", "limit": 5},
                headers={"X-Nornic-Tenant": f"interactive-{i}"})
            _loop(_LeanHttpClient(http.port), req,
                  "reads", "read_errors")

        # two saturated flood connections ~= 2x the 1-conn knee
        threads = [_thr.Thread(target=flooder) for _ in range(2)]
        threads += [_thr.Thread(target=reader, args=(i,))
                    for i in range(n_readers)]
        for t in threads:
            t.start()
        # first half: the flood accrues attributed cost under admit;
        # second half: posture held at degrade (the fleet-tighten
        # source) — the background-lane flood sheds, interactive
        # reads keep serving, and the detector's advisory window has
        # both the posture gate and the flooder's dominant cost share
        time.sleep(flood_s * 0.5)
        _admission.CONTROLLER.add_posture_source(_posture_degrade)
        _admission.CONTROLLER.refresh(force=True)
        for t in threads:
            t.join()
        offered = (counts["flood"] + counts["flood_shed"]) / flood_s
        out["flood"] = {
            "collection": "bulk_flood", "target_multiple": 2.0,
            "upserts_per_s": round(counts["flood"] / flood_s, 1),
            "shed": counts["flood_shed"],
            "offered_vs_knee": (round(offered / knee, 2)
                                if knee else None)}
        out["interactive"] = {
            "readers": n_readers,
            "reads_per_s": round(counts["reads"] / flood_s, 1),
            "errors": counts["read_errors"]}

        req_d = _delta(_by_tenant("nornicdb_tenant_requests_total"),
                       req0)
        flops_d = _delta(_by_tenant("nornicdb_tenant_cost_flops_total"),
                         flops0)
        total_req = sum(req_d.values())
        unatt = req_d.get(_ten.UNATTRIBUTED, 0.0)
        out["tenant_attribution"] = (
            round(1.0 - unatt / total_req, 4) if total_req else None)
        total_flops = sum(flops_d.values())
        out["flood_cost_share"] = (
            round(flops_d.get("bulk_flood", 0.0) / total_flops, 4)
            if total_flops else None)
        out["requests_by_tenant"] = {
            t: round(v, 1) for t, v in sorted(
                req_d.items(), key=lambda kv: -kv[1])[:12]}
        out["noisy_neighbor_events"] = _ten.DETECTOR.emitted - emitted0
        advisories = [e for e in _obs.event_snapshot(limit=200)
                      if e.get("kind") == "noisy_neighbor"]
        out["noisy_neighbor_advisory"] = (
            advisories[-1].get("detail") if advisories else None)
        # top-12: the rollup ranks by cumulative flops, so earlier
        # direct-library stages (outside any tenant scope) can outrank
        # the stage's tenants — fetch deep enough that every stage
        # tenant's row is visible
        with _url.urlopen(f"http://127.0.0.1:{http.port}"
                          "/admin/tenants/12", timeout=10) as r:
            admin = json.loads(r.read())
        out["admin_tenants"] = {
            "known": admin.get("known"),
            "top": [{"tenant": t.get("tenant"),
                     "requests": t.get("requests"),
                     "cost_share": t.get("cost_share"),
                     "p99_ms": t.get("p99_ms")}
                    for t in admin.get("tenants", [])]}
    except Exception as exc:  # noqa: BLE001 — stage must always emit
        out["error"] = f"{type(exc).__name__}: {exc}"[:400]
    finally:
        _admission.CONTROLLER.remove_posture_source(_posture_degrade)
        _admission.CONTROLLER.refresh(force=True)
        if min_flops_prev is None:
            os.environ.pop("NORNICDB_TENANT_NOISY_MIN_FLOPS", None)
        else:
            os.environ["NORNICDB_TENANT_NOISY_MIN_FLOPS"] = \
                min_flops_prev
        _ten.reload()
        if http is not None:
            http.stop()
        db.close()
    return out


def _bench_background(tiny: bool = False):
    """Device-resident background plane (ISSUE 19): the decay sweep and
    link-prediction loops that used to walk the graph one node at a
    time in Python, re-run as vmapped device programs over the
    per-etype delta snapshots — host-vs-device wall clock at N>=100k,
    exact-parity verdicts, per-job cost-counter evidence, and the
    no-convoy guard (interactive p99 from a forked replica probe must
    stay inside 2x solo p99 + 1ms while a sweep runs)."""
    import multiprocessing as _mp
    import random as _random
    import threading as _threading

    import numpy as np

    from nornicdb_tpu import linkpredict as _lp
    from nornicdb_tpu.background.device_plane import (
        BackgroundDevicePlane, demote_to_background_priority)
    from nornicdb_tpu.decay import DecayManager
    from nornicdb_tpu.obs.metrics import REGISTRY as _REG
    from nornicdb_tpu.query.columnar import ColumnarCatalog
    from nornicdb_tpu.storage import Edge, MemoryEngine, Node, now_ms

    n = 2_000 if tiny else 100_000
    n_edges = 3 * n
    n_seeds = 64 if tiny else 256
    day = 86_400_000
    now = now_ms()
    out = {"n": n, "edges": n_edges, "seeds": n_seeds}

    def build_engine():
        eng = MemoryEngine()
        r = _random.Random(19)
        for i in range(n):
            eng.create_node(Node(
                id=f"n{i}", labels=["T"],
                properties={"importance": r.random()},
                created_at=now - r.randrange(0, 80 * day)))
        for j in range(n_edges):
            eng.create_edge(Edge(
                id=f"e{j}", type=("KNOWS", "LIKES")[j % 2],
                start_node=f"n{r.randrange(n)}",
                end_node=f"n{r.randrange(n)}"))
        return eng

    def mk_decay(eng):
        dm = DecayManager(eng, archive_threshold=0.45)
        r = _random.Random(7)
        for i in range(0, n, 3):
            dm.record_access(f"n{i}", at_ms=now - r.randrange(0, 40 * day))
        return dm

    def _kind_delta(name, before):
        fam = _REG.get(name)
        cur = {}
        for key, child in (fam.children() if fam else {}).items():
            cur[key[0]] = cur.get(key[0], 0.0) + child.value
        return cur, {k: v - before.get(k, 0.0) for k, v in cur.items()}

    prev_sched = None
    try:
        # two bit-identical graphs: the host engine runs the replaced
        # per-node Python loops, the device engine runs the plane
        eng_dev = build_engine()
        eng_host = build_engine()
        dm_dev = mk_decay(eng_dev)
        dm_host = mk_decay(eng_host)
        cat_dev = ColumnarCatalog(eng_dev)
        plane = BackgroundDevicePlane(eng_dev, cat_dev, decay=dm_dev)

        flops0, _ = _kind_delta("nornicdb_query_cost_flops_total", {})
        queries0, _ = _kind_delta("nornicdb_query_cost_queries_total", {})

        # -- decay: verdict parity on sweep 1 (cold), timing on sweep 2
        # (warm compile, kalman initialized on both sides) -------------
        res_dev = dm_dev.sweep(now)
        res_host = dm_host.sweep(now)

        def archived_parity():
            flags_host = {nd.id: bool(nd.properties.get("_archived"))
                          for nd in eng_host.all_nodes()}
            same = sum(1 for nd in eng_dev.all_nodes()
                       if flags_host.get(nd.id)
                       == bool(nd.properties.get("_archived")))
            return same / max(1, n)

        parity1 = archived_parity() * (1.0 if res_dev == res_host else 0.0)
        t0 = time.perf_counter()
        res_dev2 = dm_dev.sweep(now + day)
        t_decay_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        res_host2 = dm_host.sweep(now + day)
        t_decay_host = time.perf_counter() - t0
        parity2 = archived_parity() * (
            1.0 if res_dev2 == res_host2 else 0.0)
        decay_parity = min(parity1, parity2)
        decay_speedup = t_decay_host / max(1e-9, t_decay_dev)
        out["decay"] = {
            "host_s": round(t_decay_host, 4),
            "device_s": round(t_decay_dev, 4),
            "speedup": round(decay_speedup, 2),
            "parity": decay_parity,
            "scored_archived_sweep1": list(res_dev),
            "scored_archived_sweep2": list(res_dev2),
            "device_dispatches": plane.dispatches,
        }

        # -- link prediction: device batch vs the cached-snapshot host
        # loop (parity oracle + secondary baseline) and the replaced
        # per-seed rebuild loop (the seed code's cost model) ----------
        seeds = [f"n{i}" for i in range(n_seeds)]
        plane.linkpredict_topk(seeds, method="adamic_adar", limit=10)
        t0 = time.perf_counter()
        got = plane.linkpredict_topk(seeds, method="adamic_adar", limit=10)
        t_lp_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = {s: _lp.predict_links(eng_dev, s, method="adamic_adar",
                                     limit=10, catalog=cat_dev)
                for s in seeds}
        t_lp_cached = time.perf_counter() - t0
        lp_parity = (sum(1 for s in seeds if got[s] == want[s])
                     / max(1, len(seeds)))
        # the replaced loop rebuilt the adjacency snapshot per seed;
        # sample it (full at tiny sizes) and extrapolate
        sample = seeds if tiny else seeds[:4]
        t0 = time.perf_counter()
        for s in sample:
            _lp.predict_links(eng_dev, s, method="adamic_adar", limit=10)
        t_lp_uncached = ((time.perf_counter() - t0) / len(sample)
                         * len(seeds))
        lp_speedup = t_lp_uncached / max(1e-9, t_lp_dev)
        out["linkpredict"] = {
            "method": "adamic_adar",
            "device_s": round(t_lp_dev, 4),
            "host_cached_s": round(t_lp_cached, 4),
            "host_uncached_est_s": round(t_lp_uncached, 3),
            "uncached_sampled_seeds": len(sample),
            "speedup_vs_replaced_loop": round(lp_speedup, 1),
            "speedup_vs_cached_host": round(
                t_lp_cached / max(1e-9, t_lp_dev), 2),
            "device_qps": round(len(seeds) / max(1e-9, t_lp_dev), 1),
            "parity": lp_parity,
        }

        # -- fastrp: on-device matmul chain over the same CSR ---------
        from nornicdb_tpu.ops.fastrp import fastrp_embeddings
        dim = 32 if tiny else 64
        plane.fastrp(dim=dim)
        t0 = time.perf_counter()
        ids, emb = plane.fastrp(dim=dim)
        t_rp_dev = time.perf_counter() - t0
        snap = plane._union_snapshot()
        pairs_src = np.repeat(
            np.arange(snap["n"], dtype=np.int32),
            snap["indptr"][1:] - snap["indptr"][:-1])
        pairs_dst = snap["nbr"]
        half = pairs_src < pairs_dst
        loops = pairs_src == pairs_dst
        t0 = time.perf_counter()
        emb_host = fastrp_embeddings(
            snap["n"],
            np.concatenate([pairs_src[half], pairs_src[loops]]),
            np.concatenate([pairs_dst[half], pairs_dst[loops]]),
            dim=dim)
        t_rp_host = time.perf_counter() - t0
        # isolated nodes embed to the zero vector on both sides; cosine
        # parity is only defined over the connected rows
        live = (np.linalg.norm(emb, axis=1) > 1e-9) & (
            np.linalg.norm(emb_host, axis=1) > 1e-9)
        cos = np.sum(emb[live] * emb_host[live], axis=1)
        out["fastrp"] = {
            "dim": dim,
            "device_s": round(t_rp_dev, 4),
            "host_s": round(t_rp_host, 4),
            "speedup": round(t_rp_host / max(1e-9, t_rp_dev), 2),
            "cos_min": round(float(cos.min()), 6) if cos.size else None,
            "isolated": int((~live).sum()),
        }

        # -- per-job pricing evidence: the background kinds must have
        # moved the cost counters -------------------------------------
        _, flops_d = _kind_delta("nornicdb_query_cost_flops_total",
                                 flops0)
        _, queries_d = _kind_delta("nornicdb_query_cost_queries_total",
                                   queries0)
        out["cost"] = {
            "flops_by_kind": {
                k: round(v, 1) for k, v in flops_d.items()
                if k.startswith("bg_")},
            "queries_by_kind": {
                k: round(v, 1) for k, v in queries_d.items()
                if k.startswith("bg_")},
            "priced": all(
                flops_d.get(k, 0.0) > 0 and queries_d.get(k, 0.0) > 0
                for k in ("bg_decay_sweep", "bg_linkpredict",
                          "bg_fastrp")),
        }

        # -- no-convoy guard: interactive probe in a forked replica
        # process (the multi-process fleet's serving shape) while the
        # primary, self-demoted to the idle scheduling class, runs
        # back-to-back sweeps. Gate: during-p99 <= 2x solo-p99 + 1ms.
        ctx = _mp.get_context("fork")
        start_evt = ctx.Event()
        parent_c, child_c = ctx.Pipe()
        iters = 120 if tiny else 400
        k_warm = iters // 4
        probe_ids = max(1, n // 20)

        def _probe(conn, start):
            def run(k):
                lats = []
                for i in range(k):
                    t0 = time.perf_counter()
                    _lp.predict_links(eng_dev, f"n{(i * 37) % probe_ids}",
                                      limit=10, catalog=cat_dev)
                    lats.append(time.perf_counter() - t0)
                return [float(x) for x in np.percentile(
                    np.array(lats) * 1e3, [50, 99])]
            run(max(20, k_warm))
            conn.send(run(iters))
            start.wait()
            time.sleep(0.1)
            conn.send(run(iters))
            conn.close()

        # warm the host adjacency snapshot pre-fork so the child never
        # pays the build, and never touches jax at all
        _lp.predict_links(eng_dev, "n0", limit=10, catalog=cat_dev)
        proc = ctx.Process(target=_probe, args=(child_c, start_evt))
        proc.start()
        solo = parent_c.recv()
        prev_sched = demote_to_background_priority()
        start_evt.set()
        got_during = []
        waiter = _threading.Thread(
            target=lambda: got_during.append(parent_c.recv()))
        waiter.start()
        sweeps = 0
        deadline = time.monotonic() + 120.0
        while waiter.is_alive() and time.monotonic() < deadline:
            plane.decay_sweep(now + 2 * day)
            plane.linkpredict_topk(seeds, method="adamic_adar", limit=10)
            sweeps += 1
            waiter.join(timeout=0.001)
        proc.join(timeout=30)
        if proc.is_alive():
            proc.terminate()
        if not got_during:
            raise RuntimeError("convoy probe child never reported")
        during = got_during[0]
        budget_ms = 2 * solo[1] + 1.0
        within = bool(during[1] <= budget_ms)
        out["convoy"] = {
            "mode": "forked_replica_probe",
            "bg_sched": ("SCHED_IDLE" if prev_sched is not None
                         else "nice19_or_unshaped"),
            "probe": "predict_links cached-snapshot limit=10",
            "solo_p50_ms": round(solo[0], 3),
            "solo_p99_ms": round(solo[1], 3),
            "during_p50_ms": round(during[0], 3),
            "during_p99_ms": round(during[1], 3),
            "budget_ms": round(budget_ms, 3),
            "within_budget": within,
            "sweeps_during": sweeps,
        }
        out["background_parity"] = min(decay_parity, lp_parity)
        out["background_sweep_speedup"] = round(
            min(decay_speedup, lp_speedup), 2)
        out["background_convoy_ok"] = 1.0 if within else 0.0
    except Exception as exc:  # noqa: BLE001 — stage must always emit
        out["error"] = f"{type(exc).__name__}: {exc}"[:400]
    finally:
        if prev_sched is not None:
            try:
                os.sched_setscheduler(0, prev_sched[0], os.sched_param(0))
            except OSError:
                pass
    return out


def _bench_northstar():
    """BASELINE.json north-star configs the headline doesn't cover:

    - ``hnsw_build_100k``: wall-clock to build a 100k-embedding HNSW,
      unseeded vs BM25-seeded insertion order (the reference's marquee
      2.7x result, docs/release-notes-since-v1.0.11.md:75-151). The
      seeds come from the real BM25 seed provider over a synthetic
      clustered corpus (cluster tokens = the high-IDF terms).
    - ``ann_qps_recall95``: recall@10 vs QPS sweep for HNSW / IVF-HNSW /
      IVF-PQ against brute force (BASELINE.json's own kNN metric).
    - ``pagerank_device``: on-device PageRank at LDBC scale (100k nodes,
      2M edges) vs a pure-NumPy reference loop.
    """
    from nornicdb_tpu.search.bm25 import BM25Index
    from nornicdb_tpu.search.hnsw import HNSWIndex
    from nornicdb_tpu.search.ivf_hnsw import IVFHNSWIndex
    from nornicdb_tpu.search.ivfpq import IVFPQIndex

    out = {}
    rng = np.random.default_rng(5)
    # 256-d topic-model corpus (>=256d with a real lexical backbone):
    # vectors cluster by topic with Zipf-ish topic sizes, and each doc's
    # TEXT draws from its topic's term pool, so BM25's high-IDF seeds
    # genuinely cover the vector space the way bge-m3 embeddings of real
    # docs do.
    n, d, centers = 100_000, 256, 256
    cent = (rng.standard_normal((centers, d)) * 2.0).astype(np.float32)
    topic_p = rng.dirichlet(np.full(centers, 0.3))
    assign = rng.choice(centers, n, p=topic_p)
    vecs = (cent[assign]
            + rng.standard_normal((n, d)).astype(np.float32))
    ids = [f"v{i}" for i in range(n)]
    vn = vecs / np.maximum(
        np.linalg.norm(vecs, axis=1, keepdims=True), 1e-12)

    nq = 200
    qrows = rng.choice(n, nq, replace=False)
    qs = vecs[qrows] + 0.3 * rng.standard_normal((nq, d)).astype(np.float32)
    qn = qs / np.maximum(np.linalg.norm(qs, axis=1, keepdims=True), 1e-12)
    gt = np.argsort(-(qn @ vn.T), axis=1)[:, :10]
    gt_sets = [set(f"v{j}" for j in row) for row in gt]

    def recall_of(index, ef=None, nprobe=None):
        hit = 0
        for qi in range(nq):
            kwargs = {}
            if ef is not None:
                kwargs["ef"] = ef
            if nprobe is not None:
                kwargs["nprobe"] = nprobe
            res = {h[0] for h in index.search(qs[qi], k=10, **kwargs)}
            hit += len(res & gt_sets[qi])
        return hit / (nq * 10)

    def qps_of(index, ef=None, nprobe=None):
        t0 = time.perf_counter()
        m = 0
        while True:
            for qi in range(nq):
                kwargs = {}
                if ef is not None:
                    kwargs["ef"] = ef
                if nprobe is not None:
                    kwargs["nprobe"] = nprobe
                index.search(qs[qi], k=10, **kwargs)
            m += nq
            if time.perf_counter() - t0 > 1.5:
                break
        return m / (time.perf_counter() - t0)

    # (1) HNSW build wall-clock, unseeded vs BM25-seeded
    # doc text = 5 draws from the topic's 12-term pool + shared terms
    term_rng = np.random.default_rng(6)
    topic_terms = [[f"t{c}w{j}" for j in range(12)] for c in range(centers)]
    texts = [
        " ".join(term_rng.choice(topic_terms[assign[i]], 5, replace=True))
        + f" common f{i % 7}"
        for i in range(n)
    ]
    bm25 = BM25Index()
    bm25.index_batch(list(zip(ids, texts)))
    seeds = bm25.seed_doc_ids(max_seeds=2048)
    items = list(zip(ids, vecs))
    sys.stderr.write("bench: northstar hnsw unseeded build...\n")
    h1 = HNSWIndex(ef_construction=128)
    t0 = time.perf_counter()
    h1.build(items)
    dt_unseeded = time.perf_counter() - t0
    r_unseeded = recall_of(h1)
    sys.stderr.write("bench: northstar hnsw seeded build...\n")
    h2 = HNSWIndex(ef_construction=128)
    t0 = time.perf_counter()
    # bulk beam 48 over the seeded backbone: the best measured
    # speed/recall tradeoff at this config (recall cost is visible
    # right next to the speedup: seeded_recall10 vs unseeded_recall10)
    h2.build(items, seed_ids=seeds, bulk_ef_scale=0.375)
    dt_seeded = time.perf_counter() - t0
    r_seeded = recall_of(h2)
    out["hnsw_build_100k"] = {
        "n": n, "dims": d, "ef_construction": 128,
        "unseeded_wall_s": round(dt_unseeded, 1),
        "unseeded_recall10": round(r_unseeded, 3),
        "seeded_wall_s": round(dt_seeded, 1),
        "seeded_recall10": round(r_seeded, 3),
        # Seed-first + adaptive bulk beam (hnsw.build bulk_ef_scale):
        # the BM25-seeded backbone is topically representative, so the
        # bulk phase builds with a halved construction beam at matched
        # recall — the same less-work-over-a-good-backbone effect the
        # reference reports as its 2.7x (release-notes-since-v1.0.11).
        "seeded_speedup": round(dt_unseeded / dt_seeded, 3),
        "bm25_seeds": len(seeds),
        "inserts_per_s": round(n / dt_seeded, 1),
        # reference marquee: 1M x 1024d in ~10 min on a 16-core M3 Max
        # = ~1,666 inserts/s (docs/release-notes-since-v1.0.11.md:75).
        # This config is 100k x 256d on fewer cores — stated so the
        # ratio is read with its caveats.
        "vs_baseline": round((n / dt_seeded) / 1666.7, 3),
        "baseline_note": "ref 1M x 1024d @ ~1666 inserts/s on M3 Max; "
                         "this config 100k x 256d",
    }

    # (2) ANN QPS@recall95 curves vs brute force (reuse the seeded HNSW)
    sys.stderr.write("bench: northstar ann sweeps...\n")
    t0 = time.perf_counter()
    for qi in range(nq):
        x = qn[qi] @ vn.T
        np.argpartition(-x, 9)[:10]
    brute_qps = nq / (time.perf_counter() - t0)

    curves = {"brute_force": {"recall": 1.0, "qps": round(brute_qps, 1)}}
    sweep = []
    for ef in (16, 32, 64, 128):
        sweep.append({"ef": ef, "recall": round(recall_of(h2, ef=ef), 3),
                      "qps": round(qps_of(h2, ef=ef), 1)})
    curves["hnsw"] = sweep

    sub = 50_000
    sub_items = items[:sub]
    ivf = IVFHNSWIndex(n_clusters=32, ef_construction=128)
    ivf.build(sub_items, seed_ids=seeds)
    gt_sub = np.argsort(-(qn @ vn[:sub].T), axis=1)[:, :10]
    gt_sets_sub = [set(f"v{j}" for j in row) for row in gt_sub]

    def recall_sub(index, **kw):
        hit = 0
        for qi in range(nq):
            res = {h[0] for h in index.search(qs[qi], k=10, **kw)}
            hit += len(res & gt_sets_sub[qi])
        return hit / (nq * 10)

    sweep = []
    for nprobe in (1, 2, 4, 8):
        t0 = time.perf_counter()
        for qi in range(nq):
            ivf.search(qs[qi], k=10, nprobe=nprobe)
        sweep.append({
            "nprobe": nprobe,
            "recall": round(recall_sub(ivf, nprobe=nprobe), 3),
            "qps": round(nq / (time.perf_counter() - t0), 1),
        })
    curves["ivf_hnsw"] = sweep

    pq = IVFPQIndex(n_clusters=64, n_subspaces=32, keep_vectors=True,
                    min_refine_pool=512)
    pq.train(vecs[:20_000])
    pq.add_batch(sub_items)
    gt_ids_sub = [[f"v{j}" for j in row] for row in gt_sub]
    sweep = []
    for nprobe in (1, 2, 4, 8):
        t0 = time.perf_counter()
        for qi in range(nq):
            pq.search(qs[qi], k=10, nprobe=nprobe)
        sweep.append({
            "nprobe": nprobe,
            "recall": round(recall_sub(pq, nprobe=nprobe), 3),
            "qps": round(nq / (time.perf_counter() - t0), 1),
            "coarse_hit_rate": round(
                pq.coarse_hit_rate(qn, gt_ids_sub, nprobe=nprobe), 3),
        })
    curves["ivfpq"] = sweep
    curves["ivfpq_config"] = {
        "subspaces": 32, "refine": True, "min_refine_pool": 512,
        "code_bytes_per_vec": 32, "refine_bytes_per_vec": 2 * d,
    }

    def qps_at_recall95(entries):
        ok = [e for e in entries if e["recall"] >= 0.95]
        return max((e["qps"] for e in ok), default=None)

    out["ann_qps_recall95"] = {
        "n": n, "n_ivf": sub, "dims": d, "curves": curves,
        "qps_at_recall95": {
            "brute_force": round(brute_qps, 1),
            "hnsw": qps_at_recall95(curves["hnsw"]),
            "ivf_hnsw": qps_at_recall95(curves["ivf_hnsw"]),
            "ivfpq": qps_at_recall95(curves["ivfpq"]),
        },
    }

    # (3) device PageRank at LDBC scale
    sys.stderr.write("bench: northstar pagerank...\n")
    import jax

    from nornicdb_tpu.ops.graph import pagerank_arrays

    pn, pe = 100_000, 2_000_000
    src = rng.integers(0, pn, pe).astype(np.int32)
    dst = rng.integers(0, pn, pe).astype(np.int32)
    iters = 20
    # warm up the EXACT program: iters is a static argname, so a
    # different iteration count compiles a different executable (r5: the
    # old iters=2 warm-up left the timed call paying a full compile)
    pagerank_arrays(src, dst, pn, iters=iters)
    t0 = time.perf_counter()
    pr = pagerank_arrays(src, dst, pn, iters=iters)
    dt_dev = time.perf_counter() - t0

    def pagerank_numpy(src, dst, n, iters, damping=0.85):
        deg = np.bincount(src, minlength=n).astype(np.float32)
        p = np.full(n, 1.0 / n, np.float32)
        for _ in range(iters):
            contrib = np.where(deg > 0, p / np.maximum(deg, 1), 0.0)
            nxt = np.zeros(n, np.float32)
            np.add.at(nxt, dst, contrib[src])
            dangling = p[deg == 0].sum() / n
            p = (1 - damping) / n + damping * (nxt + dangling)
        return p

    t0 = time.perf_counter()
    pr_np = pagerank_numpy(src, dst, pn, iters)
    dt_np = time.perf_counter() - t0
    agree = bool(
        np.allclose(np.asarray(pr), pr_np, rtol=5e-3, atol=1e-7)
    )
    out["pagerank_device"] = {
        "nodes": pn, "edges": pe, "iters": iters,
        "backend": jax.devices()[0].platform,
        "wall_s": round(dt_dev, 3),
        "edge_iters_per_s": round(pe * iters / dt_dev, 1),
        "speedup_vs_numpy": round(dt_np / dt_dev, 2),
        "matches_numpy_reference": agree,
    }
    return out


def _bench_ann_cagra(tiny: bool = False):
    """Device graph-ANN stage (ISSUE 2): recall@10 and qps@recall95 for
    the CAGRA-style index vs the brute-force device kernel at the same
    (N, D). Both sides are measured at the serving batch shape (B=64,
    what the MicroBatcher dispatches under concurrent load), through the
    same public search_batch surface — honest end-to-end numbers
    including host id-resolution."""
    import jax

    from nornicdb_tpu.search.cagra import CagraIndex

    n, d, centers = (2_000, 64, 16) if tiny else (50_000, 256, 128)
    nq = 64 if tiny else 256
    secs = 0.3 if tiny else 1.5
    rng = np.random.default_rng(7)
    cent = (rng.standard_normal((centers, d)) * 2.0).astype(np.float32)
    assign = rng.integers(0, centers, n)
    vecs = cent[assign] + rng.standard_normal((n, d)).astype(np.float32)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)

    idx = CagraIndex(min_n=min(1024, n))
    idx.add_batch([(f"v{i}", vecs[i]) for i in range(n)])
    t0 = time.perf_counter()
    built = idx.build()
    build_s = time.perf_counter() - t0

    qs = vecs[rng.choice(n, nq, replace=False)] \
        + 0.3 * rng.standard_normal((nq, d)).astype(np.float32)
    qn = qs / np.linalg.norm(qs, axis=1, keepdims=True)
    gt = np.argsort(-(qn @ vn.T), axis=1)[:, :10]
    gt_sets = [set(f"v{j}" for j in row) for row in gt]

    batch = 64

    def measure(search_fn):
        res = search_fn(qs, 10)  # recall pass (B=nq compile)
        hit = sum(len({h for h, _ in res[qi]} & gt_sets[qi])
                  for qi in range(nq))
        search_fn(qs[:batch], 10)  # warm the TIMED (B=batch) compile
        t0 = time.perf_counter()
        m = 0
        while True:
            for s0 in range(0, nq, batch):
                search_fn(qs[s0:s0 + batch], 10)
            m += nq
            if time.perf_counter() - t0 > secs:
                break
        return hit / (nq * 10), m / (time.perf_counter() - t0)

    brute_recall, brute_qps = measure(idx._brute.search_batch)

    # recall/qps sweep over search-time statics — ONE graph serves every
    # setting (iters/width are walk parameters, not build parameters)
    auto_it = idx._graph["iters"] if built else 0
    sweep = []
    recall10 = None
    qps_auto = None
    if built:
        for label, kw in (("fast", {"iters": max(4, auto_it // 2)}),
                          ("auto", {}),
                          ("wide", {"iters": auto_it + 4, "width": 2})):
            r, q = measure(
                lambda qrows, k, kw=kw: idx.search_batch(qrows, k, **kw))
            sweep.append({"setting": label, "recall": round(r, 3),
                          "qps": round(q, 1), **kw})
            if label == "auto":
                recall10, qps_auto = round(r, 3), round(q, 1)
    ok = [e for e in sweep if e["recall"] >= 0.95]
    qps95 = max((e["qps"] for e in ok), default=None)
    return {
        "n": n, "dims": d, "k": 10, "batch": batch,
        "backend": jax.devices()[0].platform,
        "graph_built": built,
        "build_s": round(build_s, 2),
        "degree": idx.degree, "itopk": idx.itopk,
        "n_seeds": idx.n_seeds, "iters_auto": auto_it,
        "recall_at_10": recall10,
        "qps": qps_auto,
        "brute_recall": round(brute_recall, 3),
        "brute_qps": round(brute_qps, 1),
        "sweep": sweep,
        "qps_at_recall95": qps95,
        "speedup_vs_brute": (round(qps95 / brute_qps, 2)
                             if qps95 and brute_qps else None),
    }


def _bench_hybrid(tiny: bool = False):
    """Fused hybrid stage (ISSUE 4): the one-program BM25+vector+RRF
    pipeline vs the host hybrid path (BM25Index.search -> brute
    search_batch -> rrf_fuse) at the same corpus and ranking quality.
    Quality gate first: the fused top-10 must be rank-identical to the
    host reference on every probe query; then qps at serving batch
    shapes 1/16/64 through the same public search_batch surface."""
    import jax

    from nornicdb_tpu.search.bm25 import BM25Index, tokenize
    from nornicdb_tpu.search.hybrid_fused import FusedHybrid
    from nornicdb_tpu.search.microbatch import pow2_bucket
    from nornicdb_tpu.search.rrf import rrf_fuse
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    n, d, n_vocab = (1_000, 32, 200) if tiny else (20_000, 128, 2_000)
    nq = 32 if tiny else 128
    secs = 0.2 if tiny else 1.2
    limit, overfetch = 10, 30
    rng = np.random.default_rng(7)
    vocab = np.asarray([f"w{i}" for i in range(n_vocab)])
    # zipf-ish term popularity: realistic posting-length skew
    weights = 1.0 / np.arange(1, n_vocab + 1) ** 0.9
    weights /= weights.sum()

    bm25 = BM25Index()
    brute = BruteForceIndex()
    for i in range(n):
        terms = rng.choice(vocab, size=int(rng.integers(8, 24)),
                           p=weights)
        bm25.index(f"d{i}", " ".join(terms))
        brute.add(f"d{i}", rng.standard_normal(d).astype(np.float32))

    fh = FusedHybrid(bm25, brute, min_n=1)
    t0 = time.perf_counter()
    built = fh.build()
    build_s = time.perf_counter() - t0

    q_texts = [" ".join(rng.choice(vocab, size=int(rng.integers(2, 5)),
                                   p=weights)) for _ in range(nq)]
    q_embs = rng.standard_normal((nq, d)).astype(np.float32)
    kq = pow2_bucket(overfetch)
    extras = [{"tokens": tokenize(q), "n_cand": overfetch,
               "w": (1.0, 1.0)} for q in q_texts]

    def host_one(qi):
        lex = bm25.search(q_texts[qi], overfetch)
        vec = brute.search_batch(q_embs[qi:qi + 1], overfetch)[0]
        if lex and vec:
            return rrf_fuse([lex, vec], limit=overfetch)[:limit]
        return (lex or vec)[:limit]

    # quality gate: rank-identical top-10 on every probe query
    rows = fh.search_batch(q_embs, kq, extras)
    same = 0
    for qi in range(nq):
        host_ids = [e for e, _ in host_one(qi)]
        if rows[qi] is None:
            continue
        lex, vec = rows[qi]["lex"], rows[qi]["vec"]
        fused = (rows[qi]["fused"] if lex and vec
                 else (lex or vec))[:limit]
        if [e for e, _ in fused] == host_ids:
            same += 1
    rank_parity = same / nq

    # host-path qps (single stream — the pre-fused serving shape: every
    # query serializes through the BM25 lock)
    for qi in range(min(4, nq)):
        host_one(qi)
    t0 = time.perf_counter()
    m = 0
    while True:
        host_one(m % nq)
        m += 1
        if time.perf_counter() - t0 > secs:
            break
    host_qps = m / (time.perf_counter() - t0)

    fused_qps = {}
    for batch in (1, 16, 64):
        bq = min(batch, nq)
        ex = extras[:bq]
        emb = q_embs[:bq]
        fh.search_batch(emb, kq, ex)  # warm the (B, k) compile
        t0 = time.perf_counter()
        m = 0
        while True:
            fh.search_batch(emb, kq, ex)
            m += bq
            if time.perf_counter() - t0 > secs:
                break
        fused_qps[str(batch)] = round(m / (time.perf_counter() - t0), 1)

    from nornicdb_tpu.obs.dispatch import compile_universe

    hybrid_shapes = [e for e in compile_universe()
                     if e["kind"] == "hybrid_fused"]
    sp16 = (round(fused_qps["16"] / host_qps, 2)
            if host_qps and fused_qps.get("16") else None)
    try:
        walk = _bench_hybrid_walk_sweep(tiny=tiny)
    except Exception as exc:  # noqa: BLE001 — stage must always emit
        walk = {"error": f"{type(exc).__name__}: {exc}"[:400]}
    return {
        "n": n, "dims": d, "vocab": n_vocab, "k": limit,
        "overfetch": overfetch,
        "backend": jax.devices()[0].platform,
        "built": built,
        "build_s": round(build_s, 2),
        "rank_parity": round(rank_parity, 4),
        "host_qps": round(host_qps, 1),
        "fused_qps": fused_qps,
        "speedup_vs_host_b16": sp16,
        "speedup_vs_host_b64": (
            round(fused_qps["64"] / host_qps, 2)
            if host_qps and fused_qps.get("64") else None),
        # bounded compile universe: distinct (B, k) buckets the fused
        # pipeline compiled during this stage
        "compile_buckets": len(hybrid_shapes),
        # walk tier (ISSUE 6): the corpus-size sweep that locates the
        # brute-fused <-> walk-fused crossover
        "walk": walk,
    }


def _bench_hybrid_walk_sweep(tiny: bool = False):
    """Walk-tier corpus-size sweep (ISSUE 6): at each N, the SAME
    fused pipeline (one lexical snapshot, one graph) measured twice —
    walk tier forced on, then off (exact matmul) — plus walk-parity
    recall@10 of the walk-fused ranking vs the host hybrid reference.
    The headline pair is at the largest N: walk qps over brute qps
    (the sub-linear win) and the recall that keeps it honest; the
    crossover N is the smallest swept corpus where the walk tier
    outruns the matmul tier."""
    import jax

    from nornicdb_tpu.search.bm25 import BM25Index, tokenize
    from nornicdb_tpu.search.hybrid_fused import FusedHybrid
    from nornicdb_tpu.search.microbatch import pow2_bucket
    from nornicdb_tpu.search.rrf import rrf_fuse
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    # clustered corpora (the regime graph ANN serves — same generator
    # shape as the cagra stage); d below the brute-stage 128 keeps the
    # 100k graph build inside the stage deadline on CPU
    sizes = [400, 1_000] if tiny else [20_000, 100_000]
    d = 32 if tiny else 64
    n_vocab = 300 if tiny else 4_000
    nq = 32 if tiny else 64
    secs = 0.15 if tiny else 1.2
    limit, overfetch, batch = 10, 30, 16
    sweep = []
    for n in sizes:
        rng = np.random.default_rng(11)
        vocab = np.asarray([f"w{i}" for i in range(n_vocab)])
        weights = 1.0 / np.arange(1, n_vocab + 1) ** 0.9
        weights /= weights.sum()
        centers = max(8, n // 400)
        cent = (rng.standard_normal((centers, d)) * 2.0).astype(
            np.float32)
        vecs = (cent[rng.integers(0, centers, n)]
                + rng.standard_normal((n, d)).astype(np.float32))
        lens = rng.integers(8, 24, n)
        terms = rng.choice(vocab, size=(n, 24), p=weights)
        bm25 = BM25Index()
        brute = BruteForceIndex()
        for i in range(n):
            bm25.index(f"d{i}", " ".join(terms[i, :lens[i]]))
        brute.add_batch([(f"d{i}", vecs[i]) for i in range(n)])

        fh = FusedHybrid(bm25, brute, min_n=1, walk_min_n=1)
        fh.build()
        fh.cagra.min_n = 1
        t0 = time.perf_counter()
        fh.cagra.build()
        graph_build_s = time.perf_counter() - t0

        q_texts = [" ".join(rng.choice(vocab,
                                       size=int(rng.integers(2, 5)),
                                       p=weights)) for _ in range(nq)]
        q_embs = (cent[rng.integers(0, centers, nq)]
                  + rng.standard_normal((nq, d)).astype(np.float32))
        kq = pow2_bucket(overfetch)
        extras = [{"tokens": tokenize(q), "n_cand": overfetch,
                   "w": (1.0, 1.0)} for q in q_texts]

        # walk-parity recall@10: fused walk ranking vs host hybrid.
        # The gate is only honest if the WALK tier actually served —
        # a silent veto (underfill, pending build) would measure the
        # brute tier's trivial parity, so a non-walk tier zeroes the
        # recall and the sentinel's 0.95 absolute floor flags it.
        rows = fh.search_batch(q_embs, kq, extras)
        tier = next((r["tier"] for r in rows if r is not None), None)
        lex_ref = bm25.search_batch(q_texts, overfetch)
        vec_ref = brute.search_batch(q_embs, overfetch)
        hit = 0
        for qi in range(nq):
            if lex_ref[qi] and vec_ref[qi]:
                host = rrf_fuse([lex_ref[qi], vec_ref[qi]],
                                limit=overfetch)
            else:
                host = lex_ref[qi] or vec_ref[qi]
            host_ids = {e for e, _ in host[:limit]}
            row = rows[qi]
            got = ({e for e, _ in row["fused"][:limit]}
                   if row is not None else set())
            hit += len(host_ids & got) / max(len(host_ids), 1)
        recall10 = (hit / nq) if tier == "walk" else 0.0

        def qps(tier_fh):
            ex = extras[:batch]
            emb = q_embs[:batch]
            tier_fh.search_batch(emb, kq, ex)  # warm the compile
            t0 = time.perf_counter()
            m = 0
            while True:
                tier_fh.search_batch(emb, kq, ex)
                m += batch
                if time.perf_counter() - t0 > secs:
                    break
            return m / (time.perf_counter() - t0)

        walk_qps = qps(fh)
        fh.walk_min_n = None  # SAME pipeline, exact matmul tier
        brute_qps = qps(fh)
        fh.walk_min_n = 1
        sweep.append({
            "n": n, "walk_qps_b16": round(walk_qps, 1),
            "brute_qps_b16": round(brute_qps, 1),
            "speedup_walk_vs_brute": (round(walk_qps / brute_qps, 2)
                                      if brute_qps else None),
            "walk_recall10": round(recall10, 4),
            "graph_build_s": round(graph_build_s, 2),
            "tier": tier,
        })
    crossover = next((p["n"] for p in sweep
                      if p["walk_qps_b16"] > p["brute_qps_b16"]), None)
    last = sweep[-1]
    return {
        "dims": d, "k": limit, "overfetch": overfetch, "batch": batch,
        "backend": jax.devices()[0].platform,
        "sweep": sweep,
        "crossover_n": crossover,
        "walk_qps_b16": last["walk_qps_b16"],
        "walk_recall10": last["walk_recall10"],
    }


def _bench_quant(tiny: bool = False):
    """Quantization-ladder sweep (ISSUE 8): the SAME corpus served
    through NORNICDB_VECTOR_QUANT={off,int8,pq} — recall@10 vs the
    exact float32 reference, qps at the serving batch, and the
    device-bytes/compression each rung buys. The headline trio:
    ``quant_qps_b16`` (int8, the serving-default rung), ``quant_
    recall10`` (the WORST rung's recall — the floor the sentinel
    gates at 0.95 absolute), and ``compression_ratio`` (PQ, the
    capacity claim: >= 4x is what moves per-chip corpus ceilings)."""
    import jax

    from nornicdb_tpu.search.vector_index import BruteForceIndex

    n, d = (1_200, 32) if tiny else (100_000, 64)
    nq = 32 if tiny else 64
    secs = 0.15 if tiny else 1.2
    k, batch = 10, 16
    env = {"NORNICDB_VECTOR_QUANT": "off",
           "NORNICDB_QUANT_MIN_N": "64",
           "NORNICDB_QUANT_INLINE_BUILD": "1"}
    saved = {key: os.environ.get(key) for key in env}
    os.environ.update(env)
    try:
        rng = np.random.default_rng(17)
        centers = max(8, n // 400)
        cent = (rng.standard_normal((centers, d)) * 2.0).astype(
            np.float32)
        vecs = (cent[rng.integers(0, centers, n)]
                + rng.standard_normal((n, d)).astype(np.float32))
        idx = BruteForceIndex()
        idx.add_batch([(f"d{i}", vecs[i]) for i in range(n)])
        q = (cent[rng.integers(0, centers, nq)]
             + rng.standard_normal((nq, d))).astype(np.float32)
        exact = idx.search_batch(q, k, exact=True)
        exact_ids = [{e for e, _ in hits} for hits in exact]

        def run_mode(mode):
            os.environ["NORNICDB_VECTOR_QUANT"] = mode
            t0 = time.perf_counter()
            if mode != "off":
                plane = idx.quant_plane()
                if tiny and mode == "pq":
                    plane.pq_m, plane.pq_codes = 8, 64
                plane.build()
            build_s = time.perf_counter() - t0
            got = idx.search_batch(q, k)  # warms the serving compile
            hit = sum(
                len({e for e, _ in hits} & want) / max(len(want), 1)
                for hits, want in zip(got, exact_ids))
            recall10 = hit / nq
            qb = q[:batch]
            idx.search_batch(qb, k)
            t0 = time.perf_counter()
            m = 0
            while True:
                idx.search_batch(qb, k)
                m += batch
                if time.perf_counter() - t0 > secs:
                    break
            qps = m / (time.perf_counter() - t0)
            stats = idx.resource_stats()
            return {
                "qps_b16": round(qps, 1),
                "recall10": round(recall10, 4),
                "build_s": round(build_s, 2),
                "device_bytes": stats.get("device_bytes"),
                "quant_device_bytes": stats.get("quant_device_bytes",
                                                0),
                "compression_ratio": stats.get("compression_ratio"),
            }

        modes = {mode: run_mode(mode) for mode in ("off", "int8",
                                                   "pq")}
        f32_qps = modes["off"]["qps_b16"]
        return {
            "n": n, "dims": d, "k": k, "batch": batch,
            "backend": jax.devices()[0].platform,
            "modes": modes,
            "quant_qps_b16": modes["int8"]["qps_b16"],
            "quant_recall10": min(modes["int8"]["recall10"],
                                  modes["pq"]["recall10"]),
            "compression_ratio": modes["pq"]["compression_ratio"],
            "speedup_int8_vs_f32": (
                round(modes["int8"]["qps_b16"] / f32_qps, 2)
                if f32_qps else None),
        }
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def _bench_tiered(tiny: bool = False):
    """Tiered vector storage (ISSUE 17): cluster-routed PQ slabs with
    demand paging — the beyond-HBM capacity rung. Four claims ride the
    artifact: ``tiered_recall10`` (cluster-probe serving quality, the
    sentinel's absolute 0.95 floor), ``tiered_qps_b16`` (serving rate
    at the batch-16 shape), ``tiered_capacity_ratio`` (device bytes vs
    an all-device float32 plane — the >= 4x capacity claim), and the
    forced-cold contract: with one resident slab, every query is still
    RANK-IDENTICAL to exact (cold partitions host-scan exactly) with
    exactly one ``tiered_cold`` ledger record per batch."""
    import jax

    from nornicdb_tpu.obs import audit as _audit
    from nornicdb_tpu.search.tiered_store import TieredStore
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    n, d, parts = (1_200, 32, 4) if tiny else (50_000, 64, 32)
    nq = 16 if tiny else 64
    secs = 0.15 if tiny else 1.2
    k, batch = 10, 16
    env = {"NORNICDB_VECTOR_TIERED": "1",
           "NORNICDB_TIERED_MIN_N": "64",
           "NORNICDB_TIERED_INLINE_BUILD": "1",
           "NORNICDB_TIERED_PARTS": str(parts),
           "NORNICDB_TIERED_NPROBE": str(max(4, parts // 2)),
           "NORNICDB_VECTOR_QUANT": "off"}
    saved = {key: os.environ.get(key) for key in env}
    os.environ.update(env)
    try:
        rng = np.random.default_rng(17)
        centers = max(8, n // 400)
        cent = (rng.standard_normal((centers, d)) * 2.0).astype(
            np.float32)
        vecs = (cent[rng.integers(0, centers, n)]
                + rng.standard_normal((n, d)).astype(np.float32))
        idx = BruteForceIndex()
        idx.add_batch([(f"d{i}", vecs[i]) for i in range(n)])
        q = (cent[rng.integers(0, centers, nq)]
             + rng.standard_normal((nq, d))).astype(np.float32)
        exact = idx.search_batch(q, k, exact=True)
        exact_ids = [[e for e, _ in hits] for hits in exact]

        # -- all-resident serving through the index ladder ------------
        t0 = time.perf_counter()
        got = idx.search_batch(q, k)  # builds the plane inline + warms
        build_s = time.perf_counter() - t0
        recall10 = sum(
            len({e for e, _ in hits} & set(want)) / max(len(want), 1)
            for hits, want in zip(got, exact_ids)) / nq
        qb = q[:batch]
        idx.search_batch(qb, k)
        times = []
        t0 = time.perf_counter()
        m = 0
        while True:
            t1 = time.perf_counter()
            idx.search_batch(qb, k)
            times.append(time.perf_counter() - t1)
            m += batch
            if time.perf_counter() - t0 > secs:
                break
        qps = m / (time.perf_counter() - t0)
        stats = idx.resource_stats()
        res_ms = np.asarray(times) * 1e3

        # -- LRU paging round-trip throughput -------------------------
        # one resident slab: every promotion is a full evict+promote
        # round trip through the disk spill store
        cold_store = TieredStore(
            idx, nprobe=parts, parts=parts, resident_max=1,
            min_pool=1 << 20, min_n=64, build_inline=True,
            rebuild_stale_frac=1e9)
        cold_store.build()
        pids = list(range(parts)) * (2 if tiny else 1)
        t0 = time.perf_counter()
        for pid in pids:
            cold_store.promote_inline([pid])
        page_s = time.perf_counter() - t0
        pages_per_s = len(pids) / max(page_s, 1e-9)

        # -- forced-cold contract: exact parity + one record/batch ----
        before = _audit.LEDGER.by_reason().get("tiered_cold", 0)
        cold_batches = 2 if tiny else 4
        good = total = 0
        cold_times = []
        for i in range(cold_batches):
            # the previous batch queued cold partitions for background
            # promotion; wait the pager out so a mid-batch residency
            # swap can't race this batch's dispatch
            deadline = time.time() + 30.0
            while cold_store._paging and time.time() < deadline:
                time.sleep(0.01)
            lo = (i * batch) % max(nq - batch, 1)
            qc = q[lo: lo + batch]
            t1 = time.perf_counter()
            got_c = cold_store.search_batch(qc, k)
            cold_times.append(time.perf_counter() - t1)
            if got_c is None:
                total += len(qc)  # a degrade scores as zero parity
                continue
            for hits, want in zip(got_c, exact_ids[lo: lo + batch]):
                total += 1
                if [e for e, _ in hits] == want:
                    good += 1
        records = _audit.LEDGER.by_reason().get("tiered_cold", 0) \
            - before
        cold_ms = np.asarray(cold_times) * 1e3
        cold_store.store.close()

        return {
            "n": n, "dims": d, "parts": parts, "k": k, "batch": batch,
            "backend": jax.devices()[0].platform,
            "build_s": round(build_s, 2),
            "tiered_recall10": round(recall10, 4),
            "tiered_qps_b16": round(qps, 1),
            "tiered_capacity_ratio": stats.get("tiered_capacity_ratio"),
            "tiered_device_bytes": stats.get("tiered_device_bytes"),
            "disk_bytes": stats.get("disk_bytes"),
            "latency_ms": {
                "resident_p50": round(float(np.percentile(res_ms, 50)),
                                      3),
                "resident_p99": round(float(np.percentile(res_ms, 99)),
                                      3),
                "cold_p50": round(float(np.percentile(cold_ms, 50)), 3),
                "cold_p99": round(float(np.percentile(cold_ms, 99)), 3),
            },
            "cold": {
                "parity": round(good / max(total, 1), 4),
                "ledger_records": records,
                "batches": cold_batches,
            },
            "paging": {
                "pages_per_s": round(pages_per_s, 1),
                "promotions": cold_store.promotions,
                "evictions": cold_store.evictions,
            },
        }
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def _bench_knn(tiny: bool = False):
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ops import cosine_topk, l2_normalize, pad_dim

    n, d, k = (2_000, 64, 10) if tiny else (10_000, 1024, 10)
    rng = np.random.default_rng(0)
    cap = pad_dim(n)
    m = np.zeros((cap, d), np.float32)
    m[:n] = rng.standard_normal((n, d), dtype=np.float32)
    valid = np.zeros(cap, bool)
    valid[:n] = True

    mj = l2_normalize(jnp.asarray(m))
    vj = jnp.asarray(valid)
    queries = l2_normalize(
        jnp.asarray(rng.standard_normal((64, d), dtype=np.float32))
    )

    # pre-stage 64 distinct single-query device arrays (a server keeps the
    # incoming query on device; re-slicing per request would measure host
    # transfer, not search)
    qs = [queries[j : j + 1] for j in range(64)]
    for q in qs:
        q.block_until_ready()

    # warmup / compile
    s, i = cosine_topk(qs[0], mj, vj, k)
    s.block_until_ready()

    iters = 300 if tiny else 2000
    t0 = time.perf_counter()
    for it in range(iters):
        s, i = cosine_topk(qs[it % 64], mj, vj, k)
    s.block_until_ready()
    dt = time.perf_counter() - t0
    qps = iters / dt

    # batched throughput at b=64 (the shape the MXU actually wants)
    b_iters = 20 if tiny else 100
    s, _ = cosine_topk(queries, mj, vj, k)
    s.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(b_iters):
        s, _ = cosine_topk(queries, mj, vj, k)
    s.block_until_ready()
    b64_qps = 64 * b_iters / (time.perf_counter() - t0)

    # concurrent b=1 through the micro-batching window:
    # N client threads each issue single-vector queries; the MicroBatcher
    # coalesces whatever is pending into one batched device call
    import threading

    from nornicdb_tpu.search.microbatch import MicroBatcher

    def search_batch(batch_q, kk):
        bs, bi = cosine_topk(jnp.asarray(batch_q), mj, vj, kk)
        bs.block_until_ready()
        return list(zip(np.asarray(bs), np.asarray(bi)))

    mb = MicroBatcher(search_batch, max_batch=64)
    host_qs = [np.asarray(q[0]) for q in qs]
    # enough offered load to fill 64-wide batches (32 clients cap the
    # mean coalesced batch at ~22, leaving device throughput unreached)
    n_threads = 16 if tiny else 64
    stop = threading.Event()
    counts = [0] * n_threads

    def worker(t):
        j = t
        while not stop.is_set():
            mb.search(host_qs[j % 64], k)
            counts[t] += 1
            j += 1

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    # warm EVERY power-of-two bucket shape the coalescer can produce:
    # on an accelerator each distinct (B, k) is its own compile, and a
    # compile landing inside the 2s window would be measured as
    # throughput collapse
    k_bucket = 1
    while k_bucket < k:
        k_bucket <<= 1
    b = 1
    while b <= 64:
        mb._search_batch(np.stack([host_qs[0]] * b), k_bucket)
        b <<= 1
    mb.search(host_qs[0], k)  # warm the coalescer path itself
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(0.5 if tiny else 2.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    conc_qps = sum(counts) / (time.perf_counter() - t0)

    return {
        "metric": "knn_throughput_b1_10k_x_1024",
        "value": round(qps, 1),
        "unit": "queries/s",
        "vs_baseline": round(qps / BASELINE_REST_SEARCH_OPS, 3),
        "b64_qps": round(b64_qps, 1),
        "b1_concurrent_qps": round(conc_qps, 1),
        "b1_concurrent_clients": n_threads,
        "b1_concurrent_vs_serial_b1": round(conc_qps / qps, 2),
        "microbatch_mean_batch": round(
            mb.batched_queries / max(mb.batches, 1), 1),
        "backend": jax.devices()[0].platform,
    }


# LDBC-SNB published reference numbers (BASELINE.md rows 1-4, M3 Max)
# plus the Northwind write bench (create/delete rel, 4,920 ops/s).
_LDBC_BASELINES = {
    "msg_content_lookup": 6389.0,
    "recent_messages_friends": 2769.0,
    "avg_friends_per_city": 4713.0,
    "tag_cooccurrence": 2076.0,
    "northwind_writes": 4920.0,
}


def _bench_cypher(n_people: int = 50_000, n_msgs: int = 100_000,
                  knows_per: int = 20, measure_s: float = 2.0):
    """Sustained single-stream ops/s for the four LDBC-shaped queries in
    BASELINE.md, on a 50k-person / ~1.35M-edge social graph (a 10-100x
    scale-up of the first rounds' graph: 50k persons x 20 KNOWS = 1M
    KNOWS edges, 100k messages). The query-result cache is disabled so
    this measures real execution — the columnar fast paths over
    incrementally-maintained materialized aggregate views — not cache
    hits; lookup params rotate across iterations. Dry-run shrinks the
    graph and the windows (same code path, same artifact schema)."""
    import random

    from nornicdb_tpu.query.executor import CypherExecutor
    from nornicdb_tpu.storage import MemoryEngine, NamespacedEngine
    from nornicdb_tpu.storage.types import Edge, Node

    eng = NamespacedEngine(MemoryEngine(), "bench")
    rng = random.Random(11)
    cities = [f"city{c}" for c in range(50)]
    tags = [f"tag{t}" for t in range(40)]
    seq = iter(range(10**9))

    def add_node(labels, props):
        n = Node(id=f"n{next(seq)}", labels=labels, properties=props)
        eng.create_node(n)
        return n.id

    def add_edge(etype, a, b, props=None):
        eng.create_edge(Edge(id=f"e{next(seq)}", type=etype, start_node=a,
                             end_node=b, properties=props or {}))

    city_ids = [add_node(["City"], {"name": c}) for c in cities]
    tag_ids = [add_node(["Tag"], {"name": t}) for t in tags]
    people = [
        add_node(["Person"], {"id": i, "name": f"p{i}", "age": 18 + (i * 7) % 50})
        for i in range(n_people)
    ]
    n_knows = 0
    for i, pid in enumerate(people):
        add_edge("IS_LOCATED_IN", pid, city_ids[i % len(cities)])
        for j in rng.sample(range(n_people), knows_per):
            if j != i:
                add_edge("KNOWS", pid, people[j])
                n_knows += 1
    for m in range(n_msgs):
        mid = add_node(
            ["Message"],
            {"id": 100000 + m, "content": f"msg {m}",
             "creationDate": 1700000000 + m * 37},
        )
        add_edge("HAS_CREATOR", mid, people[rng.randrange(n_people)])
        for t in rng.sample(range(len(tags)), rng.randrange(1, 4)):
            add_edge("HAS_TAG", mid, tag_ids[t])

    ex = CypherExecutor(eng)
    ex.enable_query_cache = False

    queries = {
        "msg_content_lookup": (
            "MATCH (m:Message {id: $mid}) RETURN m.content",
            lambda it: {"mid": 100000 + (it * 7) % n_msgs},
        ),
        "recent_messages_friends": (
            "MATCH (p:Person {id: $pid})-[:KNOWS]->(f:Person)"
            "<-[:HAS_CREATOR]-(m:Message) "
            "RETURN f.name, m.content, m.creationDate "
            "ORDER BY m.creationDate DESC LIMIT 10",
            lambda it: {"pid": (it * 13) % n_people},
        ),
        "avg_friends_per_city": (
            "MATCH (c:City)<-[:IS_LOCATED_IN]-(p:Person)-[:KNOWS]->(f:Person) "
            "RETURN c.name, count(f) / count(DISTINCT p) AS avgFriends",
            lambda it: {},
        ),
        "tag_cooccurrence": (
            "MATCH (t1:Tag)<-[:HAS_TAG]-(m:Message)-[:HAS_TAG]->(t2:Tag) "
            "WHERE t1 <> t2 RETURN t1.name, t2.name, count(m) AS freq",
            lambda it: {},
        ),
    }

    def measure(q, mk_params):
        ex.execute(q, mk_params(0))  # warm (builds columnar tables)
        iters = 50
        t0 = time.perf_counter()
        n_done = 0
        while True:
            for it in range(iters):
                # touch the row count: results are consumed column-major
                # (servers serialize straight from columns; see
                # CypherResult lazy rows)
                _ = ex.execute(q, mk_params(n_done + it)).n_rows
            n_done += iters
            dt = time.perf_counter() - t0
            if dt > measure_s or n_done >= 20000:
                break
        return n_done / dt

    # Northwind write shape: MATCH two indexed nodes, CREATE a rel
    # (BASELINE "Northwind write ops (create/delete rel)": 4,920 ops/s)
    queries["northwind_writes"] = (
        "MATCH (a:Person {id: $a}), (b:Person {id: $b}) "
        "CREATE (a)-[:BOUGHT_WITH]->(b)",
        lambda it: {"a": (it * 7) % n_people, "b": (it * 13 + 1) % n_people},
    )

    out = {
        "graph": {
            "persons": n_people, "knows_edges": n_knows,
            "messages": n_msgs, "cities": len(cities), "tags": len(tags),
        },
    }
    ratios = []
    rates = []
    for name, (q, mk_params) in queries.items():
        qps = measure(q, mk_params)
        base = _LDBC_BASELINES[name]
        out[name] = {
            "value": round(qps, 1), "unit": "queries/s",
            "vs_baseline": round(qps / base, 3),
        }
        ratios.append(qps / base)
        rates.append(qps)
        # Repeated identical reads are the reference's bench pattern and
        # hit its LRU result cache (read-cache probe, executor.go:634);
        # report our cached number too for the static-param queries.
        if not mk_params(0):
            ex.enable_query_cache = True
            cached_qps = measure(q, mk_params)
            ex.enable_query_cache = False
            ex.query_cache.clear()
            out[name]["cached_value"] = round(cached_qps, 1)
            out[name]["cached_vs_baseline"] = round(cached_qps / base, 3)
    geomean = float(np.exp(np.mean(np.log(ratios)))) if ratios else 0.0
    out["ldbc_geomean_vs_baseline"] = round(geomean, 3)
    out["ldbc_geomean_ops"] = (
        round(float(np.exp(np.mean(np.log(rates)))), 1) if rates else 0.0
    )
    # device graph plane (ISSUE 9): the same LDBC shapes routed through
    # query/device_graph.py — device-vs-host qps per shape, a row-parity
    # flag, the coalesced concurrent chain comparison, and cold
    # view-build latency. Runs AFTER the headline measurements so the
    # geomean above is untouched by forced-device traffic.
    try:
        out["device_graph"] = _bench_cypher_device(
            eng, queries, n_people, min(measure_s, 1.0))
    except Exception as exc:  # noqa: BLE001 — never cost the headline
        out["device_graph"] = {
            "error": f"{type(exc).__name__}: {exc}"[:400]}
    return out


def _bench_cypher_device(eng, queries, n_people, measure_s):
    """Device-vs-host for the graph plane on the SAME bench graph.

    - ``recent_messages_friends``: steady-state qps with the plane
      forced on (every lookup is one b=1 dispatch) vs off, row parity,
      and a 16-thread concurrent run in all three modes — ``auto`` is
      the shipped behavior (host until coalescible demand), ``on``
      shows what a coalesced batch dispatch costs/buys on this backend.
    - ``avg_friends_per_city`` / ``tag_cooccurrence``: the maintained
      views make steady-state identical by construction, so the device
      question is the COLD build — view-build latency host vs device,
      plus row parity through the full query path.
    - ``traverse_rank``: the fused graph+vector dispatch (chain
      expansion -> cosine top-k in one program) at b=1 and b=16 vs the
      host fallback, id-parity checked.
    """
    import concurrent.futures
    import os

    from nornicdb_tpu import obs
    from nornicdb_tpu.query.executor import CypherExecutor

    prev = os.environ.get("NORNICDB_GRAPH_DEVICE")

    def set_mode(m):
        os.environ["NORNICDB_GRAPH_DEVICE"] = m
        # the plane caches the forced-mode flag (hot-path pre-gate);
        # measurements toggling modes mid-process must not serve a few
        # hundred queries under the previous mode's cached verdict
        ex.device_graph._forced = None

    def timed_qps(fn, warm=2):
        for _ in range(warm):
            fn(0)
        n_done = 0
        t0 = time.perf_counter()
        while True:
            for i in range(20):
                fn(n_done + i)
            n_done += 20
            dt = time.perf_counter() - t0
            if dt > measure_s or n_done >= 20000:
                return round(n_done / dt, 1)

    out = {}
    parity = True
    try:
        ex = CypherExecutor(eng)
        ex.enable_query_cache = False
        q_chain, mk_chain = queries["recent_messages_friends"]

        def run_chain(i):
            return ex.execute(q_chain, mk_chain(i)).rows

        set_mode("off")
        host_rows = [run_chain(i) for i in range(4)]
        host_qps = timed_qps(run_chain)
        set_mode("on")
        dev_rows = [run_chain(i) for i in range(4)]
        dev_qps = timed_qps(run_chain)
        chain_parity = dev_rows == host_rows
        parity &= chain_parity
        # coalesced concurrency: 16 threads, per-mode qps. GIL-bound
        # host loops vs ONE shared dispatch per convoy of riders.
        n_threads = 16

        def concurrent_qps():
            stop = time.perf_counter() + measure_s
            counts = [0] * n_threads

            def worker(t):
                i = t * 1000
                while time.perf_counter() < stop:
                    run_chain(i)
                    i += 1
                    counts[t] += 1

            with concurrent.futures.ThreadPoolExecutor(n_threads) as p:
                list(p.map(worker, range(n_threads)))
            return round(sum(counts) / measure_s, 1)

        # pre-pay the per-(B, k)-bucket compiles the convoy sizes can
        # touch (coalesced batch sizes float with thread scheduling, so
        # without this the measure window is mostly XLA compiles)
        set_mode("on")
        spec = ("KNOWS", "out", "Person", "HAS_CREATOR", "dst",
                "creationDate", "Message")
        a0 = int(ex.columnar.label_rows("Person")[0])
        for bsz in (1, 2, 4, 8, 16, 32, 64):
            ex.device_graph._chain_batch(spec, [(a0, 10)] * bsz)
        conc = {}
        for mode in ("off", "auto", "on"):
            set_mode(mode)
            run_chain(0)  # warm snapshot for this mode
            conc[mode] = concurrent_qps()
        out["recent_messages_friends"] = {
            "host_qps": host_qps, "device_qps_b1": dev_qps,
            "parity": chain_parity,
            "concurrent_threads": n_threads,
            "concurrent_host_qps": conc["off"],
            "concurrent_auto_qps": conc["auto"],
            "concurrent_device_qps": conc["on"],
        }

        # cold view builds: host numpy vs device segment-sum/matmul
        def cold_build(name, pop_fn, host_fn, dev_fn, q, mk):
            set_mode("off")
            rows_h = ex.execute(q, mk(0)).rows
            host_ms = []
            dev_ms = []
            for _ in range(3):
                pop_fn()
                t0 = time.perf_counter()
                host_fn()
                host_ms.append((time.perf_counter() - t0) * 1e3)
            set_mode("on")
            for _ in range(3):
                pop_fn()
                t0 = time.perf_counter()
                built = dev_fn()
                dev_ms.append((time.perf_counter() - t0) * 1e3)
            pop_fn()
            rows_d = ex.execute(q, mk(0)).rows
            ok = rows_d == rows_h and built is not None
            return {
                "host_build_ms": round(min(host_ms), 2),
                "device_build_ms": round(min(dev_ms), 2),
                "parity": ok,
            }

        cat = ex.columnar
        plane = ex.device_graph
        strip_key = ("IS_LOCATED_IN", "dst", "Person", "KNOWS", "out",
                     "Person")
        q_s, mk_s = queries["avg_friends_per_city"]
        out["avg_friends_per_city"] = cold_build(
            "strip",
            lambda: cat._strip_views.clear(),
            lambda: cat.strip_view(*strip_key),
            lambda: plane.build_strip_view(*strip_key),
            q_s, mk_s)
        parity &= out["avg_friends_per_city"]["parity"]

        gram_key = ("HAS_TAG", "mid_src", "Message", "Tag", "Tag")

        def pop_gram():
            cat._gram_views.clear()
            cat._injective.clear()

        q_c, mk_c = queries["tag_cooccurrence"]
        out["tag_cooccurrence"] = cold_build(
            "gram",
            pop_gram,
            lambda: cat.cooc_gram(*gram_key),
            lambda: cat.cooc_gram(*gram_key, device_plane=plane),
            q_c, mk_c)
        parity &= out["tag_cooccurrence"]["parity"]

        # fused traverse-then-rank: message embeddings over the bench
        # graph, ranked from each person's 2-hop message frontier
        from nornicdb_tpu.search.vector_index import BruteForceIndex

        d = 64
        rng = np.random.default_rng(17)
        index = BruteForceIndex(use_device=True)
        msg_rows = cat.label_rows("Message")
        nodes = cat.nodes()
        ids = [nodes[int(r)].id for r in msg_rows]
        vecs = rng.normal(size=(len(ids), d)).astype(np.float32)
        index.add_batch(list(zip(ids, vecs)))
        hops = [("KNOWS", "out"), ("HAS_CREATOR", "in")]
        person_rows = cat.label_rows("Person")
        qv = rng.normal(size=(16, d)).astype(np.float32)

        def anchor(i):
            return int(person_rows[(i * 13) % len(person_rows)])

        host1 = plane.traverse_rank_host(
            [anchor(0)], hops, qv[:1], 10, index)
        set_mode("on")
        dev1 = plane.traverse_rank([anchor(0)], hops, qv[:1], 10, index)
        tr_parity = (dev1 is not None and
                     [r for r, _s in dev1[0]] == [r for r, _s in host1[0]])
        parity &= tr_parity
        tr_host_qps = timed_qps(lambda i: plane.traverse_rank_host(
            [anchor(i)], hops, qv[:1], 10, index))
        tr_dev_qps = timed_qps(lambda i: plane.traverse_rank(
            [anchor(i)], hops, qv[:1], 10, index))
        plane.traverse_rank(
            [anchor(j) for j in range(16)], hops, qv, 10, index)  # warm
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < measure_s:
            plane.traverse_rank(
                [anchor(reps * 16 + j) for j in range(16)], hops, qv, 10,
                index)
            reps += 1
        tr_b16 = round(reps * 16 / (time.perf_counter() - t0), 1)
        out["traverse_rank"] = {
            "host_qps_b1": tr_host_qps, "device_qps_b1": tr_dev_qps,
            "device_qps_b16": tr_b16, "parity": tr_parity,
        }

        out["parity"] = 1.0 if parity else 0.0
        out["compile_buckets"] = sum(
            1 for e in obs.compile_universe()
            if str(e.get("kind", "")).startswith("graph_"))
        out["min_n_default"] = int(os.environ.get(
            "NORNICDB_GRAPH_DEVICE_MIN_N", "200000") or 200000)
    finally:
        if prev is None:
            os.environ.pop("NORNICDB_GRAPH_DEVICE", None)
        else:
            os.environ["NORNICDB_GRAPH_DEVICE"] = prev
    return out


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        sys.exit(run_stage(sys.argv[2]))
    sys.exit(main(dry_run="--dry-run" in sys.argv[1:]))
