"""What a window left behind for the per-layer readers.

A reader (``benchmark/layer_metrics/<name>.py``) is ``read(observed)`` and
returns one number, or ``None`` where it finds nothing to read (the harness
then leaves the metric out of the line; a share of a roofline or of a peak
is never reported as 0).

spans
    the program's own root spans (``nornicdb_tpu.obs.tracing``) that ended
    inside the window, as dicts: name, start_ms, duration_ms, attrs,
    children.
prom_before, prom_after
    the program's ``/metrics`` text read over the wire as the window opened
    and closed, as ``{"name{labels}": value}``.
trace
    ``benchmark.lib.xplane.TraceSummary`` of the traced part of the window
    (``None`` without ``--trace 1``); ``traced`` counts what the clients saw
    complete inside that part.
counters
    what the benchmark's own wrappers counted over the window.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


class Observed:
    def __init__(self) -> None:
        self.config: Dict[str, Any] = {}
        self.traffic: Dict[str, Any] = {}
        self.sizes: Dict[str, Any] = {}
        self.window_s = 0.0
        self.spans: List[Dict[str, Any]] = []
        self.prom_before: Dict[str, float] = {}
        self.prom_after: Dict[str, float] = {}
        self.counters: Dict[str, Any] = {}
        self.traced: Dict[str, float] = {}
        self.trace: Any = None
        self.peak: Optional[Dict[str, Any]] = None

    def prom_delta(self, name: str) -> float:
        """Growth over the window of every series of one metric name
        (all label sets summed)."""
        def total(snap: Dict[str, float]) -> float:
            return sum(v for k, v in snap.items()
                       if k == name or k.startswith(name + "{"))
        return total(self.prom_after) - total(self.prom_before)

    def span_walk(self, name: str) -> List[Dict[str, Any]]:
        """Every span of that name at any depth of the window's traces."""
        out: List[Dict[str, Any]] = []
        stack = list(self.spans)
        while stack:
            s = stack.pop()
            if s["name"] == name:
                out.append(s)
            stack.extend(s.get("children", ()))
        return out


def parse_prometheus(text: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, rest = line.rpartition(" ")
        if not key:
            continue
        try:
            out[key] = float(rest)
        except ValueError:
            # "name{..} value timestamp" or an exemplar suffix: not ours
            continue
    return out
