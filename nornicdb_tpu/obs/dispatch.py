"""Device-dispatch instrumentation: the XLA compile universe, observed.

PR 1/2 bounded the compile universe by padding every device call to
power-of-two (B, k) buckets (microbatch.pow2_bucket) — but nothing
showed whether the bound held in production. This module records every
batched device dispatch by (kind, B, k): the FIRST call at a shape is
its compile (JAX compiles on first trace; its wall time includes the
compile), later calls are steady-state dispatches. ``/metrics`` then
exposes the real compile universe as labeled series, and bucket churn
(new shapes appearing at serve time) is visible as compile-counter
growth instead of mystery latency spikes.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from nornicdb_tpu.obs import metrics as _m
from nornicdb_tpu.obs.metrics import REGISTRY

_lock = threading.Lock()
# the device-truth calibration plane (obs/device.py, ISSUE 20)
# registers itself here; every recorded dispatch is forwarded. Held as
# a module global (not an import) so this module stays importable
# first in the obs package.
_observer: Optional[Callable[[str, int, int, float, bool], None]] = None
# (kind, b, k) -> {"dispatches": int, "first_call_s": float,
#                  "total_s": float}
_shapes: Dict[Tuple[str, int, int], Dict[str, Any]] = {}
# kinds announced by their owning module at import — the compile-cache
# accounting carries these series from process start (a dashboard can
# tell "tier exists, zero traffic" from "tier doesn't exist")
_declared: set = set()

_DISPATCH_C = REGISTRY.counter(
    "nornicdb_device_dispatch_total",
    "Batched device dispatches by compile bucket",
    labels=("kind", "b", "k"))
_COMPILE_C = REGISTRY.counter(
    "nornicdb_device_compile_total",
    "First-touch compiles by dispatch kind", labels=("kind",))
_LATENCY_H = REGISTRY.histogram(
    "nornicdb_device_dispatch_seconds",
    "Device dispatch wall time (first call includes compile)",
    labels=("kind",))


def set_observer(
        fn: Optional[Callable[[str, int, int, float, bool], None]]) -> None:
    """Register the per-dispatch observer (obs/device.py): called as
    ``fn(kind, b, k, seconds, first)`` after this module's own
    recording, outside its lock."""
    global _observer
    _observer = fn


def declare_kind(kind: str) -> None:
    """Pre-register a dispatch kind in the compile universe. The shape
    table still fills lazily on first dispatch; declaring only seeds
    ``bucket_counts`` (-> ``nornicdb_compile_cache_entries{kind=...}``)
    with a zero entry so the series exists before first traffic."""
    with _lock:
        _declared.add(kind)


def record_dispatch(kind: str, b: int, k: int, seconds: float) -> None:
    """Record one batched device call at pow2-bucketed shape (b, k)."""
    if not _m.enabled():
        return
    key = (kind, int(b), int(k))
    first = False
    with _lock:
        entry = _shapes.get(key)
        if entry is None:
            first = True
            entry = {"dispatches": 0, "first_call_s": seconds,
                     "total_s": 0.0}
            _shapes[key] = entry
        entry["dispatches"] += 1
        entry["total_s"] += seconds
    _DISPATCH_C.labels(kind, b, k).inc()
    _LATENCY_H.labels(kind).observe(seconds)
    if first:
        _COMPILE_C.labels(kind).inc()
    obs_fn = _observer
    if obs_fn is not None:
        obs_fn(kind, int(b), int(k), seconds, first)


def compile_universe() -> List[Dict[str, Any]]:
    """Every (kind, B, k) shape seen since process start — the admin
    view of how many distinct XLA programs serving has paid for."""
    with _lock:
        items = sorted(_shapes.items())
    return [
        {"kind": kind, "b": b, "k": k,
         "dispatches": e["dispatches"],
         "first_call_ms": round(e["first_call_s"] * 1e3, 3),
         "mean_ms": round(e["total_s"] / max(e["dispatches"], 1) * 1e3, 4)}
        for (kind, b, k), e in items
    ]


def bucket_counts() -> Dict[str, int]:
    """Distinct compiled (B, k) buckets per dispatch kind — the size of
    each compile cache. The resource accounting layer exposes this as
    ``nornicdb_compile_cache_entries{kind=...}``; growth at serve time
    is the bucket-churn signal."""
    with _lock:
        out: Dict[str, int] = {kind: 0 for kind in sorted(_declared)}
        for (kind, _b, _k) in _shapes:
            out[kind] = out.get(kind, 0) + 1
    return out


def reset() -> None:
    """Test helper: forget the shape universe (registry counters keep
    their monotone totals)."""
    with _lock:
        _shapes.clear()
