"""CPU rehearsal of chip_smoke.py (ISSUE 21).

The smoke's own phase functions run end to end at a tiny encoder and
corpus: the platform the phase-0 assertion asks for is the only thing
substituted, and the Pallas kernels run in interpret mode. The real
entry point must fail at phase 0 on a box without a chip, and a phase
whose check fails must fail the run.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny() -> chip_smoke.SmokeConfig:
    from nornicdb_tpu.models.encoder import EncoderConfig

    return chip_smoke.SmokeConfig(
        platform="cpu",
        encoder=lambda: EncoderConfig(
            vocab_size=2048, hidden_size=128, num_layers=2, num_heads=4,
            mlp_dim=256, max_len=512),
        bursts=((24, 10, 30), (16, 300, 500)),
        # 2112 rows pad to a 4096 x 128 matrix: above the brute index's
        # host-numpy floor, so the device arm serves as it does at 8192
        index_rows=2112,
        n_queries=96,
        burst_clients=8,
        graph_edges=128,
        query_df=8,
        topk_shape=(1024, 128),
        topk_batches=(8,),
        flash_shape=(2, 128, 4, 32),
        pallas_interpret=True,
    )


def test_rehearsal_drives_every_phase(monkeypatch, capsys):
    # the fused hybrid tier's corpus floor, scaled with the corpus
    monkeypatch.setenv("NORNICDB_HYBRID_MIN_N", "1024")
    run = chip_smoke.run_smoke(_tiny())
    assert [d["phase"] for d in run.report] == [
        name for name, _ in chip_smoke.PHASES]
    by = {d["phase"]: d for d in run.report}
    assert by["native"]["hnsw"] == "native"
    assert by["ingest"]["engine"] == "DiskEngine"
    assert by["ingest"]["embedded"] == 40 and by["ingest"]["failed"] == 0
    assert by["fill"]["rows"] == 2112
    served = by["serve"]["served"]
    assert served == {"vector:vector_brute_f32": 8,
                      "hybrid:hybrid_brute_f32": 8}
    assert by["serve"]["driven_programs_compiled"] == 0
    assert by["serve"]["driven"]["recall_at_10"] >= chip_smoke.RECALL_FLOOR
    assert by["kernels"]["interpret"] is True
    lines = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["ok"] is True and summary["claim"] is None
    assert run.db is None and run.http is None  # everything stopped


def test_failed_check_fails_the_run(monkeypatch, capsys):
    def phase_broken(run):
        chip_smoke.check(False, "planted failure")

    monkeypatch.setattr(chip_smoke.SmokeConfig, "full",
                        staticmethod(_tiny))
    monkeypatch.setattr(chip_smoke, "PHASES", (
        ("device", chip_smoke.phase_device),
        ("broken", phase_broken),
        ("kernels", chip_smoke.phase_kernels),
    ))
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out
    assert "phase broken FAILED: SmokeFailure: planted failure" in out
    assert '"phase": "kernels"' not in out  # nothing ran after the failure
    assert '"ok": true, "device"' not in out  # and no result line


class _ScriptedClient:
    """Answers 200 with a full hit list, except where told to refuse."""

    def __init__(self, refuse):
        self.refuse = refuse
        self.sent = 0

    def post(self, path, body):
        self.sent += 1
        if self.refuse(self.sent):
            return 429, {"error": "shed"}
        return 200, {"results": [{"id": "x", "score": 1.0}]
                     * chip_smoke.TOP_K}


def test_refused_driven_request_ends_the_run():
    run = chip_smoke.Run(_tiny())
    run.queries = ["q"] * 8
    run.client = _ScriptedClient(lambda n: n == 3)
    with pytest.raises(chip_smoke.SmokeFailure, match="answered 429"):
        chip_smoke._drive_window(run, list(range(8)))
    assert run.client.sent == 3  # no retry, and nothing after the refusal


def test_warm_up_refusals_are_bounded(monkeypatch):
    monkeypatch.setattr(chip_smoke.time, "sleep", lambda s: None)
    run = chip_smoke.Run(_tiny())
    run.queries = ["q"]
    run.client = _ScriptedClient(lambda n: n <= 2)
    assert chip_smoke._search(run, 0, "vector", patient=True)["hits"]
    assert run.warm_sheds == 2
    run.client = _ScriptedClient(lambda n: True)
    with pytest.raises(chip_smoke.SmokeFailure, match="more than"):
        chip_smoke._search(run, 0, "vector", patient=True)
    assert run.warm_sheds == chip_smoke.WARM_SHED_MAX + 1


def test_failed_embed_batch_fails_ingest(monkeypatch):
    from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder

    real = JaxEncoderEmbedder._run

    def run_or_oom(self, id_lists):
        if max(len(x) for x in id_lists) > 64:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real(self, id_lists)

    monkeypatch.setattr(JaxEncoderEmbedder, "_run", run_or_oom)
    monkeypatch.setattr(chip_smoke, "PHASES", chip_smoke.PHASES[:3])
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="embed queue failed 16 documents"):
        chip_smoke.run_smoke(_tiny())


def test_entry_point_fails_at_phase_0_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "phase device FAILED" in proc.stdout
    assert '"ok": true' not in proc.stdout
