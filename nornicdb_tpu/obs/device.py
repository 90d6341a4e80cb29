"""Device-truth calibration plane (ISSUE 20): measured dispatch
timing, cost-model calibration, and device-memory reconciliation.

Every observability layer before this one was host-side or analytic:
PR 7 prices dispatches from padded shapes (obs/cost.py), PR 3's
compile-universe instrument folds compile into first-call wall time
(obs/dispatch.py), and the ``nornicdb_index_device_bytes`` gauges are
shape-derived assertions, not measurements. This module closes the
loop three ways:

1. **Measured service-time models.** Every ``record_dispatch`` feeds a
   per-(kind, pow2-batch-bucket) EWMA of steady-state execute seconds.
   Steady updates are sampled (``NORNICDB_DEVICE_TIMING_SAMPLE``) so
   the 2x+1ms overhead guard holds; first calls always record. The
   steady-state estimate subtracts out of first-call wall time, fixing
   the PR 3 conflation — ``nornicdb_device_compile_seconds`` is the
   calibrated compile split, and a compile appearing after a kind is
   warm is an *unexpected recompile* (counter + ``recompile`` journal
   event): bucket churn caught as an incident, not a latency mystery.

2. **Calibration.** Measurements join PR 7's analytic FLOPs/bytes into
   effective FLOPs/s, bytes/s and padding efficiency (real rows /
   padded rows) per kind — the roofline view (arxiv 2602.16719 splits
   these kernels into compute- vs bandwidth-bound regimes; effective
   rates tell them apart on this box) served at ``GET /admin/device``.
   Cost recorded while a :func:`dispatch_scope` is active credits the
   *serving* dispatch kind (a brute plane priced under a MicroBatcher
   credits ``microbatch``), so the join divides like with like.

3. **Device-memory ledger.** The shape-derived gauges are reconciled
   against the JAX backend's own live-buffer accounting
   (``memory_stats()['bytes_in_use']`` on an accelerator,
   ``jax.live_arrays()`` on the CPU backend). Sustained drift past
   ``NORNICDB_DEVICE_MEM_DRIFT_BYTES`` is a leak verdict with its own
   metric family and a /readyz reason.

The payoff actuates PR 15's named headroom: :func:`predict_ms` gives
admission a calibrated per-query cost estimate — confidence-gated
(below ``NORNICDB_DEVICE_MIN_SAMPLES`` it returns None and admission
falls back to queue-wait-only, never a guess) so at posture >= degrade
a predicted-over-budget query sheds up front (``admission_cost``)
instead of occupying a device slot.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from nornicdb_tpu.config import env_float, env_int
from nornicdb_tpu.obs import metrics as _m
from nornicdb_tpu.obs.metrics import REGISTRY

_lock = threading.Lock()
_tls = threading.local()

# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------

_COMPILE_S_G = REGISTRY.gauge(
    "nornicdb_device_compile_seconds",
    "Calibrated compile time per bucket: first-call wall time minus "
    "the steady-state execute estimate (set once the bucket's EWMA is "
    "confident)", labels=("kind", "b", "k"))
_RECOMPILE_C = REGISTRY.counter(
    "nornicdb_device_unexpected_recompile_total",
    "Compiles observed after the kind was warm (bucket churn at serve "
    "time)", labels=("kind",))
_EFF_FLOPS_G = REGISTRY.gauge(
    "nornicdb_device_eff_flops_per_s",
    "Effective FLOPs/s per dispatch kind: analytic padded-shape FLOPs "
    "over measured execute seconds", labels=("kind",))
_EFF_BYTES_G = REGISTRY.gauge(
    "nornicdb_device_eff_bytes_per_s",
    "Effective bytes/s per dispatch kind: analytic padded-shape bytes "
    "over measured execute seconds", labels=("kind",))
_PAD_EFF_G = REGISTRY.gauge(
    "nornicdb_device_padding_efficiency",
    "Real rows / padded rows per dispatch kind (1.0 = no pow2-pad "
    "waste)", labels=("kind",))
_MEM_LEDGER_G = REGISTRY.gauge(
    "nornicdb_device_mem_ledger_bytes",
    "Shape-derived device bytes: what the resource accounting claims "
    "is resident")
_MEM_BACKEND_G = REGISTRY.gauge(
    "nornicdb_device_mem_backend_bytes",
    "Backend-reported device bytes (memory_stats bytes_in_use, or the "
    "live-array sum on the CPU backend)")
_MEM_DRIFT_G = REGISTRY.gauge(
    "nornicdb_device_mem_drift_bytes",
    "backend - ledger: positive means bytes the accounting cannot "
    "name (the leak direction)")
_MEM_LEAK_C = REGISTRY.counter(
    "nornicdb_device_mem_leak_total",
    "Sustained-drift episodes: |drift| stayed past the bound for the "
    "full detection window")

# ---------------------------------------------------------------------------
# cached configuration (env read once; per-request paths read the dict)
# ---------------------------------------------------------------------------

_cfg_lock = threading.Lock()
_cfg: Optional[Dict[str, Any]] = None


def _load_cfg() -> Dict[str, Any]:
    sample = env_float("DEVICE_TIMING_SAMPLE", 1.0)
    sample = min(max(sample, 0.0), 1.0)
    return {
        # fraction of steady-state dispatches that update the EWMA (and
        # pay the explicit block_until_ready at seams that use
        # maybe_sync); internally a 1-in-N tick so the decision is a
        # modulo, not an RNG draw
        "sample_every": 0 if sample <= 0.0 else max(1, round(1.0 / sample)),
        "ewma_alpha": env_float("DEVICE_EWMA_ALPHA", 0.2),
        # predict_ms confidence gate: below this many steady samples
        # the model abstains (admission falls back to queue-wait-only)
        "min_samples": env_int("DEVICE_MIN_SAMPLES", 8),
        # dispatches per kind after which a new (b, k) shape counts as
        # an unexpected recompile
        "recompile_warmup": env_int("DEVICE_RECOMPILE_WARMUP", 32),
        "mem_drift_bytes": env_int("DEVICE_MEM_DRIFT_BYTES", 64 << 20),
        "mem_drift_s": env_float("DEVICE_MEM_DRIFT_S", 60.0),
    }


def cfg() -> Dict[str, Any]:
    global _cfg
    c = _cfg
    if c is None:
        with _cfg_lock:
            if _cfg is None:
                _cfg = _load_cfg()
            c = _cfg
    return c


def reload() -> None:
    """Drop the cached env-derived config (tests; admin flags)."""
    global _cfg
    with _cfg_lock:
        _cfg = None


# ---------------------------------------------------------------------------
# per-kind / per-bucket state
# ---------------------------------------------------------------------------

# kind -> {"dispatches", "top_dispatches", "measured_s", "padded_rows",
#          "real_rows", "flops", "bytes"}
_kinds: Dict[str, Dict[str, float]] = {}
# (kind, b) -> {"n": steady samples ingested, "ewma_s": execute est}
_models: Dict[Tuple[str, int], Dict[str, float]] = {}
# (kind, b, k) -> first-call wall seconds (the conflated compile+execute)
_first: Dict[Tuple[str, int, int], float] = {}
_tick = 0

# memory-ledger episode state
_drift_since: Optional[float] = None
_leak_flagged = False
_backend_probe: Optional[Callable[[], Optional[float]]] = None


def _kind_entry(kind: str) -> Dict[str, float]:
    e = _kinds.get(kind)
    if e is None:
        e = {"dispatches": 0, "top_dispatches": 0, "measured_s": 0.0,
             "padded_rows": 0, "real_rows": 0.0, "flops": 0.0,
             "bytes": 0.0}
        _kinds[kind] = e
    return e


# ---------------------------------------------------------------------------
# the record_dispatch seam
# ---------------------------------------------------------------------------


class _DispatchScope:
    __slots__ = ("_kind", "_prev")

    def __init__(self, kind: str) -> None:
        self._kind = kind

    def __enter__(self) -> "_DispatchScope":
        self._prev = getattr(_tls, "scope", None)
        _tls.scope = self._kind
        return self

    def __exit__(self, *exc) -> None:
        _tls.scope = self._prev
        if self._prev is None:
            # a rider count nobody priced inside the scope (telemetry
            # off, an empty index) must not pad-correct the next,
            # unrelated cost on this thread
            _tls.real_rows = None


def dispatch_scope(kind: str) -> _DispatchScope:
    """Bind the *serving* dispatch kind around a batched dispatch:
    cost priced inside the scope credits ``kind`` (a brute plane under
    a MicroBatcher prices as ``microbatch``), and inner
    ``record_dispatch`` calls are tagged nested so coverage counts
    top-level serving kinds only. Outermost scope wins."""
    return _DispatchScope(kind)


def maybe_sync(result: Any = None) -> bool:
    """The sampled timing bracket: decide whether THIS dispatch is a
    calibration sample and, when it is, block on the result so the
    caller's ``t1`` measures device completion, not enqueue. Callers
    that materialize results to host anyway pay nothing extra; the
    decision is stashed thread-locally for the ``record_dispatch``
    observer to consume."""
    global _tick
    if not _m.enabled():
        return False
    every = cfg()["sample_every"]
    if every <= 0:
        _tls.sampled = False
        return False
    with _lock:
        _tick += 1
        sampled = (_tick % every) == 0
    _tls.sampled = sampled
    if sampled and result is not None:
        try:
            import jax

            jax.block_until_ready(result)
        except Exception:  # noqa: BLE001 — host-only results are fine
            pass
    return sampled


def _consume_sample_decision() -> Optional[bool]:
    s = getattr(_tls, "sampled", None)
    if s is not None:
        _tls.sampled = None
    return s


def observe_dispatch(kind: str, b: int, k: int, seconds: float,
                     first: bool) -> None:
    """Observer registered with obs.dispatch: every recorded dispatch
    lands here (telemetry already gated by the caller)."""
    global _tick
    c = cfg()
    scope = getattr(_tls, "scope", None)
    nested = scope is not None and scope != kind
    recompile = False
    with _lock:
        e = _kind_entry(kind)
        warm = e["dispatches"] >= c["recompile_warmup"]
        e["dispatches"] += 1
        e["measured_s"] += seconds
        e["padded_rows"] += int(b)
        if not nested:
            e["top_dispatches"] += 1
        key = (kind, int(b))
        mdl = _models.get(key)
        if mdl is None:
            mdl = {"n": 0, "ewma_s": 0.0}
            _models[key] = mdl
        if first:
            _first[(kind, int(b), int(k))] = seconds
            recompile = warm
        else:
            sampled = _consume_sample_decision()
            if sampled is None:
                every = c["sample_every"]
                if every > 0:
                    _tick += 1
                    sampled = (_tick % every) == 0
                else:
                    sampled = False
            if sampled:
                if mdl["n"] == 0:
                    mdl["ewma_s"] = seconds
                else:
                    a = c["ewma_alpha"]
                    mdl["ewma_s"] += a * (seconds - mdl["ewma_s"])
                mdl["n"] += 1
    if recompile:
        _RECOMPILE_C.labels(kind).inc()
        from nornicdb_tpu.obs import events as _events

        _events.record_event(
            "recompile", surface=kind, reason="bucket_churn",
            detail={"b": int(b), "k": int(k),
                    "first_call_ms": round(seconds * 1e3, 3)})
    # per-tenant device-seconds (ISSUE 20 satellite): the measured wall
    # time splits across the batch riders by tenant, the same rider-mix
    # channel the FLOPs meter uses
    from nornicdb_tpu.obs import tenant as _tenant

    _tenant.record_device_seconds(seconds)


def note_real_rows(rows: float) -> None:
    """Pin the REAL (pre-padding) rider count for the cost about to be
    priced under the active :func:`dispatch_scope`. The self-aligned
    device modules price ``queries`` pre-padding already; a coalescer
    hands its inner plane the PADDED array, so without this note the
    padding-efficiency join would read the pad rows as real work."""
    _tls.real_rows = rows


def note_cost(kind: str, queries: float, flops: float,
              bytes_: float) -> None:
    """Observer registered with obs.cost: analytic cost credited to the
    active dispatch scope (the serving kind) or, absent one, to the
    cost kind itself (the self-aligned device modules)."""
    credit = getattr(_tls, "scope", None) or kind
    rr = getattr(_tls, "real_rows", None)
    if rr is not None:
        _tls.real_rows = None
    with _lock:
        e = _kind_entry(credit)
        e["flops"] += flops
        e["bytes"] += bytes_
        e["real_rows"] += queries if rr is None else rr


# ---------------------------------------------------------------------------
# prediction (the admission consumer)
# ---------------------------------------------------------------------------


def predict_ms(kind: str, b: int) -> Optional[float]:
    """Calibrated steady-state service-time estimate for one dispatch
    of ``kind`` at batch bucket ``b`` — or None below the confidence
    floor (the caller must fall back, never guess). Per-request hot
    path: one dict read under the lock, no env access."""
    min_n = cfg()["min_samples"]
    with _lock:
        mdl = _models.get((kind, int(b)))
        if mdl is None or mdl["n"] < min_n:
            return None
        return mdl["ewma_s"] * 1e3


# ---------------------------------------------------------------------------
# calibration summaries
# ---------------------------------------------------------------------------


def _kind_doc_locked(kind: str, min_n: int) -> Dict[str, Any]:
    e = _kinds[kind]
    compile_s = 0.0
    compile_shapes = 0
    for (fk, fb, fkk), first_s in _first.items():
        if fk != kind:
            continue
        mdl = _models.get((fk, fb))
        if mdl is not None and mdl["n"] >= min_n:
            compile_s += max(first_s - mdl["ewma_s"], 0.0)
            compile_shapes += 1
    execute_s = max(e["measured_s"] - compile_s, 0.0)
    flops, byts = e["flops"], e["bytes"]
    eff_flops = flops / execute_s if flops > 0 and execute_s > 0 else None
    eff_bytes = byts / execute_s if byts > 0 and execute_s > 0 else None
    pad_eff = (min(e["real_rows"] / e["padded_rows"], 1.0)
               if e["padded_rows"] and e["real_rows"] else None)
    buckets = {}
    for (mk, mb), mdl in _models.items():
        if mk != kind:
            continue
        buckets[str(mb)] = {
            "samples": mdl["n"],
            "execute_ms": (round(mdl["ewma_s"] * 1e3, 4)
                           if mdl["n"] else None),
            "confident": mdl["n"] >= min_n,
        }
    return {
        "dispatches": int(e["dispatches"]),
        "top_dispatches": int(e["top_dispatches"]),
        "measured_s": round(e["measured_s"], 6),
        "compile_s_est": round(compile_s, 6),
        "compile_shapes_split": compile_shapes,
        "execute_s": round(execute_s, 6),
        "flops": flops,
        "bytes": byts,
        "eff_flops_per_s": eff_flops,
        "eff_bytes_per_s": eff_bytes,
        "padding_efficiency": (round(pad_eff, 4)
                               if pad_eff is not None else None),
        "buckets": buckets,
    }


def _calibrated(doc: Dict[str, Any]) -> bool:
    return (doc["eff_flops_per_s"] is not None
            and doc["padding_efficiency"] is not None
            and any(bk["confident"] for bk in doc["buckets"].values()))


def calibration_summary() -> Dict[str, Any]:
    """Per-kind roofline view + the coverage verdict: every top-level
    served dispatch kind must carry effective FLOPs/s and padding
    efficiency."""
    min_n = cfg()["min_samples"]
    with _lock:
        kinds = {k: _kind_doc_locked(k, min_n) for k in sorted(_kinds)}
    served = [k for k, d in kinds.items() if d["top_dispatches"] > 0]
    calibrated = [k for k in served if _calibrated(kinds[k])]
    coverage = (len(calibrated) / len(served)) if served else 1.0
    return {
        "kinds": kinds,
        "served_kinds": served,
        "calibrated_kinds": calibrated,
        "calibration_coverage": round(coverage, 4),
        "unexpected_recompiles": int(sum(
            ch.value for ch in _RECOMPILE_C.children().values())),
        "min_samples": min_n,
        "sample_every": cfg()["sample_every"],
    }


# ---------------------------------------------------------------------------
# device-memory ledger
# ---------------------------------------------------------------------------


def set_backend_probe(
        fn: Optional[Callable[[], Optional[float]]]) -> None:
    """Override the backend live-bytes probe (tests inject drift; a
    remote-backend deployment can plug its own accounting)."""
    global _backend_probe
    _backend_probe = fn


def ledger_bytes() -> float:
    """Shape-derived device bytes: every ``*device_bytes`` stat the
    resource accounting carries (brute/quant/tiered slabs, graph
    snapshots, background plane)."""
    from nornicdb_tpu.obs import resources as _resources

    total = 0.0
    for entry in _resources.snapshot():
        for key, val in entry.items():
            if not isinstance(val, (int, float)):
                continue
            if key == "device_bytes" or key.endswith("_device_bytes"):
                total += float(val)
    return total


def backend_bytes() -> Optional[float]:
    """The backend's own accounting: ``memory_stats()`` bytes-in-use on
    a real accelerator; the live-array sum on the CPU backend (which
    has no HBM ledger). None when no probe works — reconciliation
    abstains rather than reporting a fake zero drift."""
    probe = _backend_probe
    if probe is not None:
        return probe()
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
        if stats and stats.get("bytes_in_use"):
            return float(stats["bytes_in_use"])
        live = getattr(jax, "live_arrays", None)
        if live is None:
            return None
        return float(sum(int(x.nbytes) for x in live()))
    except Exception:  # noqa: BLE001 — no backend, no verdict
        return None


def reconcile(now: Optional[float] = None) -> Dict[str, Any]:
    """One ledger pass: publish the three gauges and run the sustained
    -drift leak detector. |drift| must sit past the bound for the full
    window before the episode counts — a transient allocation burst
    (mid-rebuild double residency) is not a leak."""
    global _drift_since, _leak_flagged
    c = cfg()
    now = time.time() if now is None else now
    ledger = ledger_bytes()
    backend = backend_bytes()
    drift = (backend - ledger) if backend is not None else None
    _MEM_LEDGER_G.set(ledger)
    if backend is not None:
        _MEM_BACKEND_G.set(backend)
        _MEM_DRIFT_G.set(drift)
    sustained_s = 0.0
    if drift is not None and abs(drift) > c["mem_drift_bytes"]:
        if _drift_since is None:
            _drift_since = now
        sustained_s = now - _drift_since
        if sustained_s >= c["mem_drift_s"] and not _leak_flagged:
            _leak_flagged = True
            _MEM_LEAK_C.inc()
    else:
        _drift_since = None
        _leak_flagged = False
    return {
        "ledger_bytes": int(ledger),
        "backend_bytes": None if backend is None else int(backend),
        "drift_bytes": None if drift is None else int(drift),
        "bound_bytes": int(c["mem_drift_bytes"]),
        "window_s": c["mem_drift_s"],
        "sustained_s": round(sustained_s, 3),
        "leak_suspected": bool(_leak_flagged),
    }


# ---------------------------------------------------------------------------
# the admin payload + scrape-time collector
# ---------------------------------------------------------------------------


def device_summary() -> Dict[str, Any]:
    """The ``GET /admin/device`` payload: calibration roofline, compile
    split, and the memory ledger in one document."""
    cal = calibration_summary()
    cal["memory"] = reconcile()
    return cal


def _collect() -> None:
    """Scrape-time publication: calibrated gauges + the memory ledger.
    Runs on every /metrics render (the resources.update_gauges
    precedent) — never on the request path."""
    if not _m.enabled():
        return
    min_n = cfg()["min_samples"]
    with _lock:
        kinds = {k: _kind_doc_locked(k, min_n) for k in _kinds}
        firsts = dict(_first)
        models = {k: dict(v) for k, v in _models.items()}
    for kind, doc in kinds.items():
        if doc["eff_flops_per_s"] is not None:
            _EFF_FLOPS_G.labels(kind).set(doc["eff_flops_per_s"])
        if doc["eff_bytes_per_s"] is not None:
            _EFF_BYTES_G.labels(kind).set(doc["eff_bytes_per_s"])
        if doc["padding_efficiency"] is not None:
            _PAD_EFF_G.labels(kind).set(doc["padding_efficiency"])
    # the calibrated compile split (the PR 3 conflation, fixed): only
    # shapes whose bucket has a confident steady-state estimate
    for (kind, b, k), first_s in firsts.items():
        mdl = models.get((kind, b))
        if mdl is not None and mdl["n"] >= min_n:
            _COMPILE_S_G.labels(kind, b, k).set(
                max(first_s - mdl["ewma_s"], 0.0))
    try:
        reconcile()
    except Exception:  # noqa: BLE001 — a probe failure must not fail scrape
        pass


REGISTRY.add_collector(_collect)


def reset() -> None:
    """Test/bench helper: forget models, joins and ledger episode state
    (registry counters keep their monotone totals)."""
    global _tick, _drift_since, _leak_flagged
    with _lock:
        _kinds.clear()
        _models.clear()
        _first.clear()
        _tick = 0
    _drift_since = None
    _leak_flagged = False


# hook registration: dispatch/cost call these per record; device.py
# imports them (not vice versa) so obs/__init__'s import order stays
# dispatch -> cost -> tenant -> device with no cycle
from nornicdb_tpu.obs import cost as _cost  # noqa: E402
from nornicdb_tpu.obs import dispatch as _dispatch  # noqa: E402

_dispatch.set_observer(observe_dispatch)
_cost.set_observer(note_cost)
