#!/usr/bin/env python3
"""Checks of the yardstick itself. Run by hand (and in the CPU rehearsal):

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

1. the trace reduction, on the small trace recorded on a v5e and kept in
   ``benchmark/data/selfcheck.xplane.pb``, gives the idle share and kernel
   time worked out by hand from that trace;
2. the byte and FLOP functions give hand-worked values for a scan of
   (B=32, 2,097,152 x 1024) and an encoder batch of (16, 4096);
3. the loader finds a cell, a configuration, a traffic mix and a per-layer
   metric dropped in as new files plus one ``BENCHMARK.json`` entry each,
   with no edit to a file that was there;
4. the peaks table refuses a device kind it does not know;
5. the length law of the ingest mix: hand-worked quantiles of a log-normal
   and of a histogram, every block of the schedule the same lengths, and
   another seed another order inside a group and nowhere else.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import costs, loader, peaks, xplane  # noqa: E402

# worked out by hand from the events of benchmark/data/selfcheck.xplane.pb
# (PERF.md section 3 says how it was recorded)
TRACE = os.path.join(ROOT, "benchmark", "data", "selfcheck.xplane.pb")
# The trace holds eight executions of jit_selfcheck_matmul, ~90.9 us each,
# 21-22 ms apart. The device's clock reads 1.1 ms behind the host's there,
# so the first execution (45.486 ms) starts before bench:window opens
# (46.557 ms, 172.955 ms long) and belongs to no window: seven count.
# Their module events last 90852 + 90872 + 90878 + 90877 + 90872 + 90881
# + 91136 = 636368 ns; their ops (copy-start 13 ns, copy-done 2-3 ns, one
# fusion, disjoint) 90846 + 90863 + 90871 + 90869 + 90864 + 90874 + 91131
# = 636318 ns, so idle is 1 - 636318 / 172954741 = 0.996321.
TRACE_EXPECT = {"executions": 7, "program": "jit_selfcheck_matmul",
                "program_s": 636368e-9, "busy_s": 636318e-9,
                "window_s": 172954741e-9, "idle_share": 0.996321}


def near(a: float, b: float, rel: float = 1e-6) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def check_trace() -> None:
    ts = xplane.summarize(TRACE)
    count, seconds = ts.module_seconds(TRACE_EXPECT["program"])
    assert ts.n_devices == 1, ts.n_devices
    assert count == TRACE_EXPECT["executions"], count
    assert near(seconds, TRACE_EXPECT["program_s"], 1e-3), seconds
    assert near(ts.busy_s, TRACE_EXPECT["busy_s"], 1e-3), ts.busy_s
    assert near(ts.window_s, TRACE_EXPECT["window_s"], 1e-3), ts.window_s
    idle = 1.0 - ts.busy_s / ts.window_s
    assert near(idle, TRACE_EXPECT["idle_share"], 1e-3), idle
    assert 0.0 < ts.busy_s < ts.window_s
    gaps = dict((k, v) for k, v in ts.top_gaps())
    assert max(gaps, key=gaps.get) == "bench:sleep", gaps
    assert ts.top_ops(3), "no device operations found"


def check_costs() -> None:
    v5e = peaks.peaks("TPU v5 lite")
    flops, byts = costs.scan_cost(32, 2_097_152, 1024)
    assert flops == 2 * 32 * 2_097_152 * 1024 == 137_438_953_472
    assert byts == 2_097_152 * 1024 * 4 == 8_589_934_592
    least, bound = costs.least_seconds(flops, byts, v5e)
    # 8.59e9 B / 819e9 B/s = 10.49 ms; 1.37e11 / 197e12 = 0.70 ms
    assert bound == "bytes" and near(least, 8_589_934_592 / 819e9)
    assert near(least, 0.0104883, 1e-4)
    # (16, 4096): 65,536 tokens x 24 layers x 2 x (4 x 1024^2 +
    # 2 x 1024 x 4096) = 3.9582e13, and 16 rows x 24 layers x 4 x 4096^2
    # x 1024 = 2.6388e13
    got = costs.encoder_flops(1024, 24, 4096, [4096] * 16)
    assert near(got, 39_582_418_599_936 + 26_388_279_066_624), got
    assert near(got, 6.597e13, 1e-3)


def check_peaks() -> None:
    try:
        peaks.peaks("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("an unknown device kind was given peaks")


def check_loader() -> None:
    tmp = tempfile.mkdtemp(prefix="selfcheck_")
    try:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__", "data"))
        before = {}
        for dirpath, _, files in os.walk(tmp):
            for f in files:
                p = os.path.join(dirpath, f)
                with open(p, "rb") as fh:
                    before[p] = fh.read()
        b = os.path.join(tmp, "benchmark")
        with open(os.path.join(b, "configs", "new-config.json"), "w") as f:
            json.dump({"system": "new_system", "rows": 7,
                       "reference": "benchmark/configs/new.reference.py"}, f)
        with open(os.path.join(b, "configs", "new.reference.py"), "w") as f:
            f.write("NAME = 'new reference'\n")
        with open(os.path.join(b, "systems", "new_system.py"), "w") as f:
            f.write("class System:\n    pass\n")
        with open(os.path.join(b, "traffic", "new-mix.json"), "w") as f:
            json.dump({"loop": "closed", "clients": 3}, f)
        with open(os.path.join(b, "layer_metrics", "new_metric.x.py"),
                  "w") as f:
            f.write("def read(observed):\n    return 42.0\n")
        bench = loader.load_benchmark(tmp)
        bench["configs"].append({"name": "new-config", "source": "none",
                                 "file": "benchmark/configs/new-config.json",
                                 "reduced": [], "why": "selfcheck"})
        bench["workloads"].append({"name": "new-cell",
                                   "config": "new-config",
                                   "traffic": "new-mix", "chips": 1,
                                   "why": "selfcheck"})
        bench["per_layer"].append({"name": "new_metric.x", "unit": "x",
                                   "better": "higher",
                                   "source": "program_counter",
                                   "layer": "wire", "moves": "setup_s",
                                   "workloads": ["new-cell"]})
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        bench = loader.load_benchmark(tmp)
        cell = loader.find_cell(bench, "new-cell")
        cfg = loader.load_config(bench, cell, tmp)
        assert cfg["rows"] == 7 and cfg["name"] == "new-config"
        assert loader.load_traffic(cell, tmp)["clients"] == 3
        assert hasattr(loader.load_system(cfg, tmp), "System")
        assert loader.load_reference(cfg, tmp).NAME == "new reference"
        names = [m["name"] for m in loader.metrics_of_cell(
            bench, "per_layer", "new-cell")]
        assert names == ["new_metric.x"], names
        assert loader.load_metric_reader("new_metric.x", tmp).read(None) \
            == 42.0
        e2e = [m["name"] for m in loader.metrics_of_cell(
            bench, "end_to_end", "new-cell")]
        assert e2e == ["setup_s"], e2e
        for p, data in before.items():
            if p.endswith("BENCHMARK.json"):
                continue
            with open(p, "rb") as fh:
                assert fh.read() == data, f"{p} was edited"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def check_lengths() -> None:
    system = loader.load_module(os.path.join(
        ROOT, "benchmark", "systems", "ingest_encoder.py"))
    law = {"distribution": "lognormal", "median": 200, "sigma": 1.2,
           "min": 10, "max": 4096}
    # exp(1.2 x 1.6449) = 7.198: the 95th percentile is 1,440 tokens
    assert system.law_quantile(law, 0.5) == 200
    assert system.law_quantile(law, 0.95) == 1440
    assert system.law_quantile(law, 0.9999) == 4096
    hist = {"distribution": "histogram", "edges": [10, 100, 1000, 4096],
            "shares": [0.5, 0.4, 0.1], "min": 10, "max": 4096}
    # half the mass under 100; u = 0.7 lies half way through the second bin
    assert system.law_quantile(hist, 0.25) == 55
    assert system.law_quantile(hist, 0.7) == 550
    assert system.law_quantile(hist, 0.95) == 2548
    mix = {"lengths": dict(law, block_docs=64, blocks=3, group=16,
                           schedule_seed=5)}
    one = system.length_schedule(mix, 1)
    two = system.length_schedule(mix, 2 ** 31 + 7)
    assert len(one) == 192 and one != two
    assert sorted(one[:64]) == sorted(one[64:128]) == sorted(two[128:])
    for g in range(0, 192, 16):
        assert sorted(one[g:g + 16]) == sorted(two[g:g + 16]), g


def main() -> int:
    for fn in (check_costs, check_peaks, check_loader, check_lengths,
               check_trace):
        fn()
        print("ok", fn.__name__)
    return 0


if __name__ == "__main__":
    sys.exit(main())
