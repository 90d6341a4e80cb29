#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip. It finds the cell in ``BENCHMARK.json``, its
configuration, traffic mix and system by name (``benchmark/lib/loader.py``),
builds the system under test, warms every shape the cell's traffic uses
(set-up), drives the measured window over the loopback socket, frees the
system, compares what the window served with the configuration's plain
reference, and prints one JSON object as the last line of standard output.
With no TPU it exits 2 before any work and prints no result.

``--rehearse`` (not on the driver's command line) runs the configuration's
``rehearse`` sizes on whatever backend JAX has, to find wrong paths and
control flow here on the CPU. Its line carries ``"rehearsal": true`` and no
metric at all: a number from a CPU is never written under a device name.
"""

from __future__ import annotations

import time

_T_START = time.time()  # process start, as near as Python can tell

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import loader  # noqa: E402
from benchmark.lib.check import Check  # noqa: E402


class Run:
    """What one run knows; handed to the system and to the readers."""

    def __init__(self, args: argparse.Namespace) -> None:
        self.root = ROOT
        self.bench = loader.load_benchmark(ROOT)
        self.cell = loader.find_cell(self.bench, args.workload)
        self.config = loader.load_config(self.bench, self.cell, ROOT)
        self.traffic = loader.load_traffic(self.cell, ROOT)
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse)
        if self.rehearse:   # the files' ``rehearse`` sizes take over, once
            self.config.update(self.config.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.control = args.control
        self.t_start = _T_START
        self.meter = None            # CompileMeter, once JAX is in
        self.device: Dict[str, Any] = {}
        self.peak: Optional[Dict[str, Any]] = None

    def size(self, key: str) -> Any:
        """A size of the configuration (the rehearsal's where this is one)."""
        return self.config[key]

    def mix(self, key: str, default: Any = None) -> Any:
        """A parameter of the traffic mix, likewise."""
        return self.traffic.get(key, default)


def _require_chip(run: Run) -> None:
    """Exit 2, with nothing on standard output, unless JAX has a TPU and
    as many chips as the cell asks for."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as exc:
        sys.stderr.write(f"benchmark: JAX found no device: {exc}\n")
        raise SystemExit(2)
    dev = devs[0]
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs)}
    if run.rehearse:
        return
    if dev.platform != "tpu" or len(devs) < int(run.cell["chips"]):
        sys.stderr.write(
            f"benchmark: cell {run.cell['name']} needs "
            f"{run.cell['chips']} TPU chip(s); JAX has {len(devs)} x "
            f"{dev.platform} ({dev.device_kind}). Nothing falls back to "
            f"the CPU.\n")
        raise SystemExit(2)
    from benchmark.lib.peaks import peaks

    run.peak = peaks(dev.device_kind)  # an unknown kind is an error


def prepare(run: Run) -> Any:
    """What every entry does before set-up: the deployment's environment,
    the look for the chip, the compile cache, the compile meter. Returns
    the cell's system, not yet set up, and the cache directory."""
    system_mod = loader.load_system(run.config, ROOT)
    for key, value in run.config.get("program_env", {}).items():
        os.environ[key] = str(value)   # before the program reads them
    _require_chip(run)
    from benchmark.lib.compile_meter import CompileMeter
    from nornicdb_tpu.jaxenv import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    run.meter = CompileMeter()
    return system_mod.System(run), cache_dir


def _memory_peak() -> Dict[str, int]:
    """``memory_stats()`` of the fullest chip (by ``peak_bytes_in_use``)."""
    import jax

    fullest: Dict[str, int] = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if int(stats.get("peak_bytes_in_use", 0)) >= int(
                fullest.get("peak_bytes_in_use", 0)):
            fullest = {k: int(v) for k, v in stats.items()}
    return fullest


def _layer_metrics(run: Run, observed: Any) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for m in loader.metrics_of_cell(run.bench, "per_layer",
                                    run.cell["name"]):
        reader = loader.load_metric_reader(m["name"], ROOT)
        value = reader.read(observed)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no metric")
    ap.add_argument("--control", default=None,
                    help="by hand only: put the configuration's control "
                         "in the program's place (see README)")
    args = ap.parse_args(argv)
    run = Run(args)
    system, cache_dir = prepare(run)
    from benchmark.lib.tracer import Tracer

    tracer = Tracer(run.trace, float(run.mix("trace_seconds", 4.0)))
    try:
        system.setup()
        window = system.window(tracer)
        memory = _memory_peak()
    finally:
        system.free()
    checks: List[Check] = system.verify()
    summary = tracer.summary() if run.trace else None
    observed = window["observed"]
    observed.trace = summary
    observed.peak = run.peak

    correct = all(c.ok for c in checks)
    device = dict(run.device,
                  memory_peak_bytes=memory.get("peak_bytes_in_use", 0))
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": window["attempted"],
                              "failed": window["failed"]}
    if run.rehearse:
        result["rehearsal"] = True
        result["metrics"] = {}
        result["counts"] = dict(
            window.get("counts", {}),
            readers_that_read=sorted(_layer_metrics(run, observed)))
    elif run.trace:
        result["metrics"] = _layer_metrics(run, observed)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
    else:
        e2e = dict(window["end_to_end"], setup_s=window["setup_s"])
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in loader.metrics_of_cell(run.bench, "end_to_end",
                                            run.cell["name"])}
    result["device"] = device
    if summary is not None and not run.rehearse:
        window.setdefault("notes", {})["programs_on_device"] = sorted(
            ([n, c, t] for n, (c, t) in summary.modules.items()),
            key=lambda row: -row[2])[:12]
    result["notes"] = dict(window.get("notes", {}), cache_dir=cache_dir,
                           memory_stats=memory,
                           compile=run.meter.snapshot(),
                           total_s=time.time() - _T_START)
    result["checks"] = {c.name: c.to_json() for c in checks}
    for c in checks:
        sys.stderr.write(f"check {c.name}: {c.value!r} limit {c.limit!r} "
                         f"{'ok' if c.ok else 'FAILED'}\n")
    sys.stderr.flush()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
