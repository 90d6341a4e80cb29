"""The yardstick that judges every PR (``benchmark/`` + ``BENCHMARK.json``),
live, on a CPU: its own hand-worked checks, one rehearsed run of every
cell, and the contract between program and yardstick: every per-layer
metric a cell lists is read from that run, or is named below as one that
needs the chip's device trace.
"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import selfcheck
from benchmark.lib import loader

pytestmark = pytest.mark.usefixtures("benchmark_state_put_back")

BENCH = loader.load_benchmark(loader.ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
PAIRS = [(cell, m["name"]) for cell in CELLS
         for m in loader.metrics_of_cell(BENCH, "per_layer", cell)]

# Read from the profiler's device trace, which a rehearsal does not record.
NEEDS_CHIP = {
    "scan_roofline": "the scan programs' device seconds against the peaks",
    "scans_per_query": "counts executions of the scan programs on the device",
    "device_idle_pct.search": "device busy time over the traced window",
    "device_idle_pct.ingest": "device busy time over the traced window",
    "encoder_roofline": "the encoder programs' device seconds against the "
                        "peaks",
    "encoder_mfu_pct": "the encoder programs' device seconds against peak "
                       "FLOP/s",
    "hybrid_fused_roofline": "the fused program's device seconds against "
                             "the peaks",
    "query_encoder_busy_pct": "the query encoder's device seconds over the "
                              "window",
    "index_update_roofline": "the update program's device seconds against "
                             "the peak bytes/s",
    "filtered_scan_roofline": "the filtered scan's device seconds against "
                              "the peak bytes/s",
}
# Listed by a cell and read by nothing: strict, so that the PR that mends
# the metric takes the mark out.
BROKEN = {
    "widen_ms": "no `qdrant.widen` span at `limit` <= 256 since PR 29; a "
                "`benchmark` PR retires or re-points the metric (ledger "
                "notes, PR 29)",
}


@pytest.mark.parametrize("name", ["check_trace", "check_costs",
                                  "check_peaks", "check_loader",
                                  "check_lengths"])
def test_selfcheck(name):
    getattr(selfcheck, name)()


@pytest.fixture(scope="module")
def rehearsed():
    """``rehearsed(cell)``: the result line of one rehearsed run of the
    cell, made once a module."""
    from benchmark import run as bench_run

    runs = {}

    def result(cell):
        if cell not in runs:
            out = io.StringIO()
            with redirect_stdout(out):
                rc = bench_run.main(["--workload", cell, "--seed",
                                     str(2147483800 + CELLS.index(cell)),
                                     "--seconds", "2", "--trace", "0",
                                     "--rehearse"])
            assert rc == 0
            runs[cell] = json.loads(out.getvalue().strip().splitlines()[-1])
        return runs[cell]

    return result


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsed_cell_is_correct(rehearsed, cell):
    result = rehearsed(cell)
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["checks"]["window_compiles"]["value"] == 0, \
        result["notes"]["compile"]


def _pair(cell, metric):
    marks = []
    if metric in NEEDS_CHIP:
        marks.append(pytest.mark.skip(reason="needs the chip: "
                                      + NEEDS_CHIP[metric]))
    elif metric in BROKEN:
        marks.append(pytest.mark.xfail(strict=True, reason=BROKEN[metric]))
    return pytest.param(cell, metric, id=f"{cell}-{metric}", marks=marks)


@pytest.mark.parametrize("cell,metric", [_pair(*p) for p in PAIRS])
def test_metric_is_read_or_accounted_for(rehearsed, cell, metric):
    """A metric the cell lists has a reader that reads on a CPU. One that
    cannot says so in ``NEEDS_CHIP``, with its reason."""
    read = rehearsed(cell)["counts"]["readers_that_read"]
    assert metric in read, (
        f"{cell}: no value for {metric} from a rehearsed run (read: {read}). "
        f"Either the span or counter it reads is gone, or it needs the "
        f"device trace and belongs in NEEDS_CHIP")


def test_tables_name_only_listed_metrics():
    listed = {m for _, m in PAIRS}
    assert set(NEEDS_CHIP) | set(BROKEN) <= listed
    assert not set(NEEDS_CHIP) & set(BROKEN)
