#!/usr/bin/env python3
"""Readings for the limits of ``correct``, several seeds to one set-up.

Set-up of a cell is long, so the program's readings over a dozen traffic
seeds, and the control's over a few, are taken in one process: one set-up
(data or weights from ``--seed``), then ``--windows`` short windows whose
requests are drawn from ``--seed + 1, + 2, ...``. After each of the first
``--control-windows`` windows the configuration's control is put in the
program's place over the same sample. One JSON line per window on standard
output. By hand, on the chip:

    python3 benchmark/tools/read_limits.py --workload vec2m-c32 --seed 7 \\
        --seconds 8 --windows 12 --control-windows 3
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--windows", type=int, default=12)
    ap.add_argument("--control-windows", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace, args.control = 0, None
    run = bench_run.Run(args)
    system, _ = bench_run.prepare(run)
    from benchmark.lib.tracer import Tracer

    try:
        system.setup()
        for i in range(args.windows):
            system.traffic_seed = args.seed + 1 + i
            window = system.window(Tracer(False, 0.0))
            line = {"traffic_seed": system.traffic_seed,
                    "attempted": window["attempted"],
                    "failed": window["failed"],
                    "end_to_end": None if run.rehearse
                    else window["end_to_end"],
                    "program": {c.name: c.value for c in system.verify()}}
            if i < args.control_windows:
                run.control = run.config["control"]
                line["control"] = {c.name: c.value
                                   for c in system.verify()}
                run.control = None
            print(json.dumps(line), flush=True)
    finally:
        system.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
