"""Device-truth calibration plane (ISSUE 20): measured dispatch
timing, cost-model calibration, device-memory reconciliation, and
cost-aware admission.

The acceptance contracts pinned here:

- the EWMA service-time models calibrate from steady-state samples
  only, abstain below the confidence floor, and split compile out of
  first-call wall time (the PR 3 conflation, fixed);
- a compile observed after a kind is warm increments the
  unexpected-recompile counter and lands ONE ``recompile`` journal
  event;
- the memory ledger reconciles shape-derived gauges against the
  backend probe; sustained drift past the bound flips the leak verdict
  (counter + ``/readyz`` reason), transient drift does not;
- the admission cost gate sheds a predicted-over-budget query with
  reason ``admission_cost`` (exactly-once ledger + journal) at posture
  >= degrade, admits under budget, and abstains when the model is
  unconfident or the posture is ``admit`` — the full matrix;
- measured device wall seconds split across batch riders by tenant
  (the ISSUE 18 rider-mix rule, now in time);
- the 2x + 1ms/op overhead guard HOLDS with the timing bracket
  sampling every dispatch.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nornicdb_tpu import admission as adm
from nornicdb_tpu import obs
from nornicdb_tpu.obs import audit
from nornicdb_tpu.obs import device as dev
from nornicdb_tpu.obs import dispatch as dsp
from nornicdb_tpu.obs import events as obs_events
from nornicdb_tpu.obs import tenant
from nornicdb_tpu.search.microbatch import MicroBatcher
from nornicdb_tpu.search.vector_index import BruteForceIndex


@pytest.fixture(autouse=True)
def _fresh_device_state(monkeypatch):
    # every steady dispatch samples (deterministic math) unless a test
    # overrides; the models/joins start empty and the admission
    # controller's counters reset around each test
    monkeypatch.setenv("NORNICDB_DEVICE_TIMING_SAMPLE", "1")
    dev.reload()
    dev.reset()
    dev.set_backend_probe(None)
    adm.CONTROLLER.reset()
    yield
    dev.set_backend_probe(None)
    dev.reset()
    dev.reload()
    adm.CONTROLLER.reset()


def _force_posture(monkeypatch, posture):
    monkeypatch.setattr(adm.CONTROLLER, "refresh",
                        lambda now=None, force=False: posture)
    monkeypatch.setattr(adm.CONTROLLER, "posture", posture)


def _feed(kind, b, k, first_s, steady_s, n_steady):
    """Drive the observer directly with a fake timer feed: one first
    call, then n steady calls at a flat execute time."""
    dev.observe_dispatch(kind, b, k, first_s, True)
    for _ in range(n_steady):
        dev.observe_dispatch(kind, b, k, steady_s, False)


def _cost_sheds():
    return [r for r in audit.LEDGER.snapshot(limit=500)
            if r.get("reason") == "admission_cost"]


def _cost_shed_events():
    return [r for r in obs_events.event_snapshot(limit=500, kind="shed")
            if r.get("reason") == "admission_cost"]


# ---------------------------------------------------------------------------
# calibration math (fake timer feeds — no device, no clock)
# ---------------------------------------------------------------------------


class TestCalibrationMath:
    def test_predict_abstains_below_min_samples(self):
        min_n = dev.cfg()["min_samples"]
        _feed("fake_kind", 8, 16, 0.100, 0.010, min_n - 1)
        assert dev.predict_ms("fake_kind", 8) is None
        dev.observe_dispatch("fake_kind", 8, 16, 0.010, False)
        assert dev.predict_ms("fake_kind", 8) == pytest.approx(
            10.0, rel=0.01)

    def test_predict_unknown_kind_or_bucket_is_none(self):
        assert dev.predict_ms("never_served", 8) is None
        _feed("fake_kind", 8, 16, 0.1, 0.01, 20)
        assert dev.predict_ms("fake_kind", 64) is None

    def test_ewma_tracks_flat_feed_exactly(self):
        _feed("fake_kind", 8, 16, 0.100, 0.010, 20)
        # a flat feed converges to the flat value whatever alpha is
        assert dev.predict_ms("fake_kind", 8) == pytest.approx(
            10.0, rel=1e-6)

    def test_compile_split_subtracts_steady_estimate(self):
        _feed("fake_kind", 8, 16, 0.120, 0.010, 20)
        doc = dev.calibration_summary()["kinds"]["fake_kind"]
        # first call 120ms, steady 10ms -> compile est 110ms; execute
        # is measured total minus the compile share
        assert doc["compile_s_est"] == pytest.approx(0.110, rel=0.01)
        assert doc["execute_s"] == pytest.approx(
            0.120 + 20 * 0.010 - 0.110, rel=0.01)
        assert doc["compile_shapes_split"] == 1

    def test_compile_series_supersedes_first_call_gauge(self):
        dsp.reset()
        dsp.record_dispatch("legacy_kind", 4, 8, 0.2)
        for _ in range(10):
            dsp.record_dispatch("legacy_kind", 4, 8, 0.01)
        # PR 3's conflated first-call gauge is gone (nothing read it);
        # the calibrated split is the series, and the conflated wall
        # time stays in the admin view of the compile universe
        obs.REGISTRY.run_collectors()
        assert obs.REGISTRY.get(
            "nornicdb_device_first_call_seconds") is None
        fam = obs.REGISTRY.get("nornicdb_device_compile_seconds")
        assert fam.labels("legacy_kind", 4, 8).value == pytest.approx(
            0.19, rel=0.01)
        shape = next(e for e in dsp.compile_universe()
                     if e["kind"] == "legacy_kind")
        assert shape["first_call_ms"] == pytest.approx(200.0)

    def test_roofline_join_and_padding_efficiency(self):
        _feed("fake_kind", 8, 16, 0.020, 0.010, 20)
        # cost priced pre-padding: 6 real rows per 8-row dispatch
        for _ in range(21):
            dev.note_cost("fake_kind", 6, 1e6, 2e5)
        doc = dev.calibration_summary()["kinds"]["fake_kind"]
        assert doc["padding_efficiency"] == pytest.approx(6 / 8,
                                                          rel=0.01)
        assert doc["eff_flops_per_s"] == pytest.approx(
            21e6 / doc["execute_s"], rel=0.01)
        assert doc["eff_bytes_per_s"] == pytest.approx(
            21 * 2e5 / doc["execute_s"], rel=0.01)

    def test_dispatch_scope_credits_serving_kind(self):
        with dev.dispatch_scope("serving_kind"):
            # the inner plane prices under its own cost kind and
            # records its own nested dispatch
            dev.note_cost("inner_kind", 4, 5e5, 1e5)
            dev.observe_dispatch("inner_kind", 4, 8, 0.001, True)
            dev.observe_dispatch("serving_kind", 8, 16, 0.010, True)
        cal = dev.calibration_summary()
        assert cal["kinds"]["serving_kind"]["flops"] == 5e5
        assert "inner_kind" not in cal["served_kinds"]  # nested only
        assert cal["kinds"]["inner_kind"]["top_dispatches"] == 0

    def test_note_real_rows_overrides_padded_pricing(self):
        # a coalescer pads 3 riders to an 8-row program and the inner
        # plane prices the padded array; the note pins the real count
        with dev.dispatch_scope("serving_kind"):
            dev.note_real_rows(3.0)
            dev.note_cost("inner_kind", 8, 1e6, 1e5)
            dev.observe_dispatch("serving_kind", 8, 16, 0.010, True)
        for _ in range(12):
            with dev.dispatch_scope("serving_kind"):
                dev.note_real_rows(3.0)
                dev.note_cost("inner_kind", 8, 1e6, 1e5)
                dev.observe_dispatch("serving_kind", 8, 16, 0.010,
                                     False)
        doc = dev.calibration_summary()["kinds"]["serving_kind"]
        assert doc["padding_efficiency"] == pytest.approx(3 / 8,
                                                          rel=0.01)

    def test_coverage_counts_top_level_served_kinds_only(self):
        # a fully calibrated kind...
        _feed("covered", 8, 16, 0.02, 0.01, 20)
        for _ in range(21):
            dev.note_cost("covered", 8, 1e6, 1e5)
        cal = dev.calibration_summary()
        assert cal["served_kinds"] == ["covered"]
        assert cal["calibration_coverage"] == 1.0
        # ...then a served kind with no cost join drops coverage
        _feed("uncosted", 4, 8, 0.02, 0.01, 20)
        cal = dev.calibration_summary()
        assert set(cal["served_kinds"]) == {"covered", "uncosted"}
        assert cal["calibration_coverage"] == 0.5
        assert "uncosted" not in cal["calibrated_kinds"]


# ---------------------------------------------------------------------------
# unexpected-recompile detector
# ---------------------------------------------------------------------------


class TestRecompileDetector:
    def test_cold_compiles_are_expected(self):
        before = dev.calibration_summary()["unexpected_recompiles"]
        _feed("cold_kind", 8, 16, 0.1, 0.01, 5)
        assert dev.calibration_summary()["unexpected_recompiles"] \
            == before

    def test_warm_compile_counts_and_journals(self, monkeypatch):
        monkeypatch.setenv("NORNICDB_DEVICE_RECOMPILE_WARMUP", "10")
        dev.reload()
        ev0 = len(obs_events.event_snapshot(limit=500,
                                            kind="recompile"))
        before = dev.calibration_summary()["unexpected_recompiles"]
        _feed("warm_kind", 8, 16, 0.1, 0.01, 12)  # warm: 13 >= 10
        dev.observe_dispatch("warm_kind", 32, 16, 0.250, True)
        assert dev.calibration_summary()["unexpected_recompiles"] \
            == before + 1
        evs = obs_events.event_snapshot(limit=500, kind="recompile")
        assert len(evs) == ev0 + 1
        rec = evs[-1]
        assert rec["surface"] == "warm_kind"
        assert rec["reason"] == "bucket_churn"
        assert rec["detail"]["b"] == 32
        assert rec["detail"]["first_call_ms"] == pytest.approx(250.0)


# ---------------------------------------------------------------------------
# device-memory ledger reconciliation
# ---------------------------------------------------------------------------


class TestMemoryLedger:
    def test_backend_probe_injection(self):
        dev.set_backend_probe(lambda: 12345.0)
        assert dev.backend_bytes() == 12345.0

    def test_transient_drift_is_not_a_leak(self, monkeypatch):
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_BYTES", "1000")
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_S", "60")
        dev.reload()
        ledger = dev.ledger_bytes()
        dev.set_backend_probe(lambda: ledger + 1e9)
        t0 = time.time()
        doc = dev.reconcile(now=t0)
        assert doc["drift_bytes"] == pytest.approx(1e9)
        assert not doc["leak_suspected"]
        # drift clears before the window elapses: episode resets
        dev.set_backend_probe(lambda: ledger)
        doc = dev.reconcile(now=t0 + 30)
        assert not doc["leak_suspected"]
        dev.set_backend_probe(lambda: ledger + 1e9)
        doc = dev.reconcile(now=t0 + 31)
        assert doc["sustained_s"] == 0.0 and not doc["leak_suspected"]

    def test_sustained_drift_flags_leak_once_per_episode(self,
                                                         monkeypatch):
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_BYTES", "1000")
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_S", "60")
        dev.reload()
        ledger = dev.ledger_bytes()
        dev.set_backend_probe(lambda: ledger + 1e9)
        leak = obs.REGISTRY.get("nornicdb_device_mem_leak_total")
        c0 = leak.value
        t0 = time.time()
        assert not dev.reconcile(now=t0)["leak_suspected"]
        doc = dev.reconcile(now=t0 + 61)
        assert doc["leak_suspected"] and doc["sustained_s"] >= 60
        assert leak.value == c0 + 1
        # still drifting: the episode counts ONCE
        doc = dev.reconcile(now=t0 + 120)
        assert doc["leak_suspected"]
        assert leak.value == c0 + 1
        # recovery closes the episode; a fresh one counts again
        dev.set_backend_probe(lambda: ledger)
        assert not dev.reconcile(now=t0 + 121)["leak_suspected"]
        dev.set_backend_probe(lambda: ledger + 1e9)
        dev.reconcile(now=t0 + 122)
        dev.reconcile(now=t0 + 200)
        assert leak.value == c0 + 2

    def test_no_probe_means_abstain_not_zero_drift(self):
        dev.set_backend_probe(lambda: None)
        doc = dev.reconcile()
        assert doc["backend_bytes"] is None
        assert doc["drift_bytes"] is None
        assert not doc["leak_suspected"]


# ---------------------------------------------------------------------------
# cost-aware admission: the gate matrix
# ---------------------------------------------------------------------------


def _confident_model(kind="microbatch", bucket=1, ms=50.0):
    dev.observe_dispatch(kind, bucket, 16, 1.0, True)
    for _ in range(dev.cfg()["min_samples"] + 2):
        dev.observe_dispatch(kind, bucket, 16, ms / 1e3, False)


class TestAdmissionCostGate:
    def test_confident_over_budget_sheds_exactly_once(self,
                                                      monkeypatch):
        _confident_model(ms=50.0)
        _force_posture(monkeypatch, "degrade")
        led0, ev0 = len(_cost_sheds()), len(_cost_shed_events())
        with adm.deadline_scope(time.time() + 0.010):  # 10ms < 50ms
            with pytest.raises(adm.ShedError) as ei:
                adm.CONTROLLER.cost_check("t-cost", "microbatch", 1,
                                          "interactive")
        assert ei.value.reason == "admission_cost"
        assert ei.value.status == 429
        assert len(_cost_sheds()) == led0 + 1
        assert len(_cost_shed_events()) == ev0 + 1

    def test_confident_under_budget_admits_with_prediction(
            self, monkeypatch):
        _confident_model(ms=5.0)
        _force_posture(monkeypatch, "degrade")
        with adm.deadline_scope(time.time() + 1.0):
            pred = adm.CONTROLLER.cost_check("t-cost", "microbatch",
                                             1, "interactive")
        assert pred == pytest.approx(5.0, rel=0.01)

    def test_unconfident_model_abstains_at_degrade(self, monkeypatch):
        # below the sample floor there is NO prediction: the gate
        # does nothing even over budget (queue-wait-only, no guess)
        dev.observe_dispatch("microbatch", 1, 16, 0.050, True)
        dev.observe_dispatch("microbatch", 1, 16, 0.050, False)
        _force_posture(monkeypatch, "degrade")
        led0 = len(_cost_sheds())
        with adm.deadline_scope(time.time() + 0.001):
            assert adm.CONTROLLER.cost_check(
                "t-cost", "microbatch", 1, "interactive") is None
        assert len(_cost_sheds()) == led0

    def test_admit_posture_skips_gate_even_over_budget(self,
                                                       monkeypatch):
        _confident_model(ms=500.0)
        _force_posture(monkeypatch, "admit")
        with adm.deadline_scope(time.time() + 0.001):
            assert adm.CONTROLLER.cost_check(
                "t-cost", "microbatch", 1, "interactive") is None

    def test_shed_posture_gates_too(self, monkeypatch):
        _confident_model(ms=50.0)
        _force_posture(monkeypatch, "shed")
        with adm.deadline_scope(time.time() + 0.010):
            with pytest.raises(adm.ShedError):
                adm.CONTROLLER.cost_check("t-cost", "microbatch", 1,
                                          "interactive")

    def test_no_deadline_means_no_gate(self, monkeypatch):
        _confident_model(ms=500.0)
        _force_posture(monkeypatch, "degrade")
        assert adm.CONTROLLER.cost_check(
            "t-cost", "microbatch", 1, "interactive") is None

    def test_gate_disable_knob(self, monkeypatch):
        _confident_model(ms=500.0)
        _force_posture(monkeypatch, "degrade")
        monkeypatch.setenv("NORNICDB_ADMISSION_COST_GATE", "0")
        adm.reload()
        try:
            with adm.deadline_scope(time.time() + 0.001):
                assert adm.CONTROLLER.cost_check(
                    "t-cost", "microbatch", 1, "interactive") is None
        finally:
            monkeypatch.delenv("NORNICDB_ADMISSION_COST_GATE")
            adm.reload()

    def test_end_to_end_microbatch_ingress_shed(self, monkeypatch):
        # the real seam: a MicroBatcher rider with a confident model,
        # degrade posture and a too-tight budget sheds AT INGRESS —
        # before taking a queue slot — with the exactly-once records
        idx = BruteForceIndex()
        rng = np.random.default_rng(21)
        vecs = rng.standard_normal((64, 16)).astype(np.float32)
        idx.add_batch([(f"c{i}", vecs[i]) for i in range(64)])
        mb = MicroBatcher(idx.search_batch, surface="t-cost-e2e")
        for i in range(dev.cfg()["min_samples"] + 4):
            mb.search(vecs[i % 64], 5)
        pred = dev.predict_ms("microbatch", 1)
        assert pred is not None
        _force_posture(monkeypatch, "degrade")
        led0, ev0 = len(_cost_sheds()), len(_cost_shed_events())
        with adm.deadline_scope(time.time() + pred / 1e3 / 2.0):
            with pytest.raises(adm.ShedError) as ei:
                mb.search(vecs[0], 5)
        assert ei.value.reason == "admission_cost"
        assert len(_cost_sheds()) == led0 + 1
        assert len(_cost_shed_events()) == ev0 + 1


# ---------------------------------------------------------------------------
# per-tenant device seconds (the rider-mix rule, in time)
# ---------------------------------------------------------------------------


class TestTenantDeviceSeconds:
    def test_measured_seconds_split_across_batch_mix(self):
        fam = obs.REGISTRY.get("nornicdb_tenant_device_seconds_total")

        def val(label):
            ch = fam.children().get((label,))
            return ch.value if ch is not None else 0.0

        a0, b0 = val("dt-a"), val("dt-b")
        with tenant.batch_scope(["dt-a", "dt-a", "dt-a", "dt-b"]):
            dev.observe_dispatch("mix_kind", 4, 8, 0.008, True)
        assert val("dt-a") - a0 == pytest.approx(0.006, rel=0.01)
        assert val("dt-b") - b0 == pytest.approx(0.002, rel=0.01)

    def test_device_seconds_ride_tenants_summary(self):
        with tenant.tenant_scope("dt-solo", explicit=True):
            dev.observe_dispatch("mix_kind", 2, 8, 0.004, True)
        doc = tenant.tenants_summary()
        mine = [t for t in doc["tenants"]
                if t["tenant"] == "dt-solo"]
        assert mine and mine[0]["cost"]["device_seconds"] \
            == pytest.approx(0.004, rel=0.01)


# ---------------------------------------------------------------------------
# /readyz + /admin surfaces
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving():
    import nornicdb_tpu
    from nornicdb_tpu.api.http_server import HttpServer

    db = nornicdb_tpu.open(auto_embed=False)
    db.store("device truth probe", node_id="dt-1",
             embedding=[0.25] * 8)
    db.search.search("probe", mode="text")
    http = HttpServer(db, port=0).start()
    yield {"db": db, "http": http}
    http.stop()
    db.close()


def _http_get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestAdminSurfaces:
    def test_admin_device_serves_calibration_and_memory(self, serving):
        _feed("fake_kind", 8, 16, 0.02, 0.01, 20)
        for _ in range(21):
            dev.note_cost("fake_kind", 8, 1e6, 1e5)
        status, doc = _http_get(serving["http"].port, "/admin/device")
        assert status == 200
        assert "fake_kind" in doc["kinds"]
        assert doc["kinds"]["fake_kind"]["eff_flops_per_s"] > 0
        assert "calibration_coverage" in doc
        assert "memory" in doc and "bound_bytes" in doc["memory"]

    def test_telemetry_carries_device_block(self, serving):
        status, doc = _http_get(serving["http"].port,
                                "/admin/telemetry")
        assert status == 200
        assert "device" in doc
        assert "calibration_coverage" in doc["device"]

    def test_readyz_carries_leak_reason(self, serving, monkeypatch):
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_BYTES", "1000")
        monkeypatch.setenv("NORNICDB_DEVICE_MEM_DRIFT_S", "0")
        dev.reload()
        ledger = dev.ledger_bytes()
        dev.set_backend_probe(lambda: ledger + 1e9)
        try:
            status, doc = _http_get(serving["http"].port, "/readyz")
            assert status == 503
            assert doc["checks"]["device_mem_leak"] == 1
            assert any(r.startswith("device_mem_drift:")
                       for r in doc["reasons"])
            # recovery: drift back to zero (the probe now agrees with
            # the ledger — the REAL backend in a shared test process
            # carries other tests' arrays, so pin the probe instead
            # of dropping it) -> the drift reason clears
            monkeypatch.delenv("NORNICDB_DEVICE_MEM_DRIFT_BYTES")
            monkeypatch.delenv("NORNICDB_DEVICE_MEM_DRIFT_S")
            dev.reload()
            dev.set_backend_probe(lambda: dev.ledger_bytes())
            status, doc = _http_get(serving["http"].port, "/readyz")
            assert not any(r.startswith("device_mem_drift:")
                           for r in doc.get("reasons", []))
        finally:
            dev.set_backend_probe(None)
            dev.reload()


# ---------------------------------------------------------------------------
# overhead guard with the timing bracket ON
# ---------------------------------------------------------------------------


class TestOverheadWithSampling:
    def test_full_sampling_holds_the_overhead_budget(self):
        # the PR 3 guard, re-pinned with the ISSUE 20 bracket sampling
        # EVERY dispatch (worse than the 1/16 default): instrumented
        # stays within 2x + 1ms/op of the telemetry-off path
        assert dev.cfg()["sample_every"] == 1  # fixture pinned
        idx = BruteForceIndex()
        rng = np.random.default_rng(17)
        vecs = rng.standard_normal((512, 32)).astype(np.float32)
        idx.add_batch([(f"o{i}", vecs[i]) for i in range(512)])
        mb = MicroBatcher(idx.search_batch, surface="t-dev-overhead")
        n = 300

        def measure():
            for i in range(30):
                mb.search(vecs[i], 10)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(n):
                    with obs.trace("wire", method="/dev-overhead"):
                        mb.search(vecs[i % 512], 10)
                best = min(best, time.perf_counter() - t0)
            return best

        t_on = measure()
        # the bracket really ran: the bucket-1 model is confident
        assert dev.predict_ms("microbatch", 1) is not None
        obs.set_enabled(False)
        try:
            t_off = measure()
        finally:
            obs.set_enabled(True)
        assert t_on <= t_off * 2.0 + n * 1e-3, (
            f"sampled bracket {t_on:.4f}s vs bare {t_off:.4f}s")
