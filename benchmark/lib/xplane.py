"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. What is taken:

- device planes (``/device:TPU:<n>``): the ``XLA Ops`` line gives the
  intervals in which an operation ran (busy time is their union; an op's
  own time is its duration less the ops nested in it), the ``XLA Modules``
  line gives one event per execution of a compiled program;
- the host plane: events whose name starts with ``bench:`` are the spans
  the benchmark's client and wrappers wrote with
  ``jax.profiler.TraceAnnotation``; the longest ``bench:window`` bounds the
  traced window. Idle gaps on the device are attributed to the ``bench:``
  span that covers most of each gap (the shortest such span on a tie, so
  the innermost wins).

The planes of one trace share a clock to within a millisecond or two (in
the recorded self-check trace the device's first execution reads 1.1 ms
before the host span that launched it), which is nothing against windows
of seconds and gaps of milliseconds. Times are seconds.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib.stats import merge_intervals, union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench:"
WINDOW_ANNOTATION = "bench:window"

Interval = Tuple[float, float]


def _events(line) -> List[Tuple[str, float, float]]:
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        out.append((ev.name, start, start + ev.duration_ns * 1e-9))
    return out


def module_name(event_name: str) -> str:
    """``jit_foo(123456)`` -> ``jit_foo``: the fingerprint changes with
    every recompilation, the program's name does not."""
    return event_name.split("(", 1)[0]


def self_seconds(events: Sequence[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Own time by op name: each event's duration less the events nested
    inside it on the same line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -(events[i][2] - events[i][1])))
    own = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i in order:
        _, start, end = events[i]
        while stack and events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= end - start
        stack.append(i)
    out: Dict[str, float] = {}
    for (name, _, _), t in zip(events, own):
        out[name] = out.get(name, 0.0) + max(t, 0.0)
    return out


class TraceSummary:
    """What the readers and the result line take from one trace."""

    def __init__(self) -> None:
        self.n_devices = 0
        self.window: Optional[Interval] = None
        self.window_s = 0.0
        self.busy_s = 0.0            # mean over the device planes
        # program name -> (executions, device seconds), summed over devices
        self.modules: Dict[str, Tuple[int, float]] = {}
        self.op_self_s: Dict[str, float] = {}
        # label -> idle seconds on the (first) device attributed to it
        self.idle_by_label: Dict[str, float] = {}
        self.annotations: Dict[str, List[Interval]] = {}

    def idle_pct(self) -> Optional[float]:
        """Share of the traced window in which no operation ran on the
        device; nothing where the trace holds no window or no operation."""
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def top_ops(self, n: int = 10) -> List[List[object]]:
        rows = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in rows]

    def top_gaps(self, n: int = 10) -> List[List[object]]:
        rows = sorted(self.idle_by_label.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in rows]

    def module_seconds(self, name_part: str) -> Tuple[int, float]:
        """(executions, device seconds) of every program whose name
        contains ``name_part``."""
        count, total = 0, 0.0
        for name, (c, s) in self.modules.items():
            if name_part in name:
                count += c
                total += s
        return count, total


def _clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    a, b = window
    return [(max(s, a), min(e, b)) for s, e in intervals if e > a and s < b]


def _attribute_gaps(gaps: Sequence[Interval],
                    annotations: Dict[str, List[Interval]]
                    ) -> Dict[str, float]:
    merged = {label: merge_intervals(iv) for label, iv in annotations.items()
              if label != WINDOW_ANNOTATION}
    starts = {label: [s for s, _ in iv] for label, iv in merged.items()}
    # innermost first on a tie: the label whose spans are shortest
    depth = sorted(merged, key=lambda lb: (
        sum(e - s for s, e in annotations[lb]) / max(len(annotations[lb]), 1)))
    out: Dict[str, float] = {}
    for a, b in gaps:
        best, best_cov = "no_bench_span", 0.0
        for label in depth:
            iv = merged[label]
            i = max(bisect.bisect_right(starts[label], a) - 1, 0)
            cov = 0.0
            while i < len(iv) and iv[i][0] < b:
                cov += max(0.0, min(iv[i][1], b) - max(iv[i][0], a))
                i += 1
            if cov > best_cov * 1.000001:
                best, best_cov = label, cov
        out[best] = out.get(best, 0.0) + (b - a)
    return out


def summarize(path: str, window_s: Optional[float] = None) -> TraceSummary:
    """Reduce one ``.xplane.pb``. ``window_s`` is the host's own length of
    the traced window, used when the trace holds no ``bench:window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ts = TraceSummary()
    device_ops: List[List[Tuple[str, float, float]]] = []
    device_mods: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops: List[Tuple[str, float, float]] = []
            mods: List[Tuple[str, float, float]] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    mods = _events(line)
            device_ops.append(ops or mods)
            device_mods.append(mods)
            for name, t in self_seconds(ops).items():
                ts.op_self_s[name] = ts.op_self_s.get(name, 0.0) + t
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        s = ev.start_ns * 1e-9
                        ts.annotations.setdefault(ev.name, []).append(
                            (s, s + ev.duration_ns * 1e-9))
    ts.n_devices = len(device_ops)
    windows = ts.annotations.get(WINDOW_ANNOTATION)
    if windows:
        ts.window = max(windows, key=lambda iv: iv[1] - iv[0])
    elif any(device_ops):
        lo = min(s for ops in device_ops for _, s, _ in ops)
        hi = max(e for ops in device_ops for _, _, e in ops)
        ts.window = (lo, max(hi, lo + (window_s or 0.0)))
    if ts.window is None:
        ts.window_s = window_s or 0.0
        return ts
    ts.window_s = ts.window[1] - ts.window[0]
    # an execution belongs to the window it starts in, whole
    for mods in device_mods:
        for name, s, e in mods:
            if ts.window[0] <= s < ts.window[1]:
                c, t = ts.modules.get(module_name(name), (0, 0.0))
                ts.modules[module_name(name)] = (c + 1, t + (e - s))
    busy = []
    for ops in device_ops:
        busy.append(union_seconds(
            _clip([(s, e) for _, s, e in ops], ts.window)))
    ts.busy_s = sum(busy) / len(busy) if busy else 0.0
    if device_ops:
        merged = merge_intervals(
            _clip([(s, e) for _, s, e in device_ops[0]], ts.window))
        gaps, cursor = [], ts.window[0]
        for s, e in merged:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < ts.window[1]:
            gaps.append((cursor, ts.window[1]))
        ts.idle_by_label = _attribute_gaps(gaps, ts.annotations)
    return ts
