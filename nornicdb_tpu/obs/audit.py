"""Serving-tier truth: per-query tier attribution, the unified degrade
ledger, and the online shadow-parity auditor (ISSUE 10).

The device serving stack degrades along multi-rung ladders — quantized
-> float32 -> host for vectors (ISSUE 8), walk -> brute-fused -> host
for hybrid (ISSUE 6), device-graph -> host (ISSUE 9) — but until now a
live node never recorded *which rung actually answered a query*, *why*
degradations happened, or *whether device answers still matched the
host reference* under real traffic. This module is that trust layer;
the replica fleet (ROADMAP item 3) and the admission controller (item
4) both consume it.

Three parts:

1. **Per-query tier attribution.** A canonical tier taxonomy (`TIERS`)
   shared by every serving path. Each served query increments
   ``nornicdb_served_tier_total{surface,tier}``, observes its wall time
   into ``nornicdb_served_tier_seconds{surface,tier}`` and annotates
   its trace span with ``served_by``. Batched paths propagate the tier
   leader -> riders through a thread-local channel
   (:func:`note_batch_tier` set inside the dispatch,
   :func:`consume_batch_tier` read by the MicroBatcher leader, stamped
   onto every rider) so attribution is **rider-accurate**: the fused
   hybrid decode stamps per-ROW tiers, so one rider whose live-filter
   forced a host re-fuse counts ``host`` while its batch-mates keep
   their device tier.

2. **Unified degrade ledger.** :func:`record_degrade` replaces the
   scattered free-form ``*_events_total{event=degrade_*}`` semantics
   with one structured record — (surface, from_tier, to_tier,
   normalized reason, index identity, snapshot/generation versions) —
   kept in a bounded ring served at ``/admin/degrades``, grafted into
   the owning trace as a zero-width ``degrade`` span, counted in
   ``nornicdb_degrade_total`` and included in every SLO flight-recorder
   dump. The legacy per-module event counters keep their old label
   values as aliases; ``REASONS`` is the one documented vocabulary and
   ``normalize_reason`` maps every legacy event value onto it.

3. **Online shadow-parity auditor.** An env-gated background sampler
   (``NORNICDB_AUDIT_SAMPLE=1/256``-style rate plus the absolute QPS
   budget ``NORNICDB_AUDIT_MAX_QPS``) captures a copy of device-served
   queries and re-executes them on the host reference path on a worker
   thread — never on the hot path; a full queue drops the sample,
   never blocks a dispatch. Parity per tier (rank-parity for exact
   tiers, recall@k for statistical ones) feeds
   ``nornicdb_parity_ratio{surface,tier}`` and
   ``nornicdb_audit_{sampled,mismatch,dropped}_total``; a per-sample
   floor miss dumps a self-contained repro record (query, both answer
   sets, all snapshot versions) through the PR 5 flight recorder; a
   sustained parity-floor breach surfaces in ``/readyz`` reasons and —
   with ``NORNICDB_AUDIT_QUARANTINE=1`` (default off) — quarantines the
   offending tier down its existing ladder (:func:`tier_allowed`),
   re-probing after ``NORNICDB_AUDIT_QUARANTINE_S`` so the tier
   recovers once the breach clears.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from nornicdb_tpu.obs import events as _events
from nornicdb_tpu.obs import metrics as _m
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu.obs.metrics import LATENCY_BUCKETS, REGISTRY
from nornicdb_tpu.obs.tracing import annotate, attach_span, current_trace_id

# ---------------------------------------------------------------------------
# canonical tier taxonomy
# ---------------------------------------------------------------------------

# host-resident serving (the exhaustive reference path, HNSW/IVF host
# indexes, the host Cypher executor): shared across surfaces
TIER_HOST = "host"
# answers served straight from a response/result cache — no index of
# any rung executed. Counted so the under-load tier mix stays truthful
# (a steady-state wire workload is mostly this); never shadow-audited
# (the cache generation machinery already guarantees freshness).
TIER_CACHED = "cached"
# queries admission control REJECTED (429 / RESOURCE_EXHAUSTED) or
# failed fast past their deadline budget (ISSUE 15): counted in the
# tier mix so the under-load serve accounting sums to offered work —
# a shed query is an answered query (honest backpressure), just not a
# ranked one. Never shadow-audited; never a ladder rung.
TIER_SHED = "shed"

# per-surface device ladders, best rung first. These are the ONLY legal
# `tier` label values — the catalog lint checks each against
# docs/observability.md.
TIERS: Dict[str, Tuple[str, ...]] = {
    "vector": ("vector_walk_quant", "vector_walk_f32", "vector_tiered",
               "vector_int8", "vector_pq", "vector_brute_f32",
               TIER_HOST, TIER_CACHED),
    "hybrid": ("hybrid_walk_quant", "hybrid_walk_f32",
               "hybrid_brute_int8", "hybrid_brute_pq",
               "hybrid_brute_f32", TIER_HOST, TIER_CACHED),
    "graph": ("graph_chain_device", "graph_traverse_rank_device",
              TIER_HOST),
    # ISSUE 19: background device plane (decay / link prediction /
    # FastRP) — no statistical floor, so the exact contract (1.0)
    # applies: every guard trip degrades to host, never a wrong answer
    "background": ("background_device", TIER_HOST),
}

ALL_TIERS: Tuple[str, ...] = tuple(sorted(
    {t for tiers in TIERS.values() for t in tiers} | {TIER_SHED}))

# parity contracts per tier (host is the reference; never audited).
# Exact tiers must reproduce the host ranking bit-for-bit (rank-parity
# floor 1.0); statistical tiers carry the documented recall floors the
# tests already hold them to (walk parity / quant recall >= 0.95).
STATISTICAL_FLOORS: Dict[str, float] = {
    "vector_walk_quant": 0.95,
    "vector_walk_f32": 0.95,
    "vector_tiered": 0.95,
    "vector_int8": 0.95,
    "vector_pq": 0.95,
    "hybrid_walk_quant": 0.95,
    "hybrid_walk_f32": 0.95,
    "hybrid_brute_int8": 0.95,
    "hybrid_brute_pq": 0.95,
}

EXACT_TIERS: Tuple[str, ...] = tuple(sorted(
    t for t in ALL_TIERS
    if t not in (TIER_HOST, TIER_CACHED, TIER_SHED)
    and t not in STATISTICAL_FLOORS))


def tier_floor(tier: str) -> float:
    """Parity floor for a tier: documented statistical floor, else the
    exact contract (1.0)."""
    return STATISTICAL_FLOORS.get(tier, 1.0)


# ---------------------------------------------------------------------------
# normalized degrade-reason vocabulary
# ---------------------------------------------------------------------------

# the one documented reason vocabulary (catalog lint checks each value
# against docs/observability.md). Legacy per-module event label values
# stay as aliases on their original counters; the ledger and
# nornicdb_degrade_total speak only these.
REASONS: Tuple[str, ...] = (
    "changelog_overrun",   # read-your-writes changelog trimmed past marker
    "compaction",          # slot space remapped under the snapshot
    "overflow",            # lexical plan exceeded the CSR plan bounds
    "pending_build",       # first/background build not yet landed
    "underfill",           # live-filtering left a row short of candidates
    "itopk_exceeded",      # requested depth exceeds the walk pool
    "shard_mismatch",      # snapshot/graph disagree on mesh layout
    "unshardable",         # capacity not divisible across the mesh
    "vec_race",            # join map lost a race with a concurrent write
    "rerank_race",         # compaction landed mid exact-rerank gather
    "exactness",           # f32/int32 integer-exactness bound exceeded
    "rank_overflow",       # composite merge key would overflow int32
    "stale_snapshot",      # versioned snapshot invalidated by a write
    "min_batch",           # auto mode: batch below coalescible demand
    "live_filter",         # tombstone correction forced a host re-fuse
    "error",               # caught exception on the device path
    "quarantine",          # shadow-parity auditor stepped the tier down
    "broker_timeout",      # shared device plane missed the rider deadline
    "replica_lag",         # read replica behind the lag threshold drained
    "replica_drain",       # replica drained: parity/rebuild/unreachable
    "deadline",            # request budget expired before/while queued
    "shed",                # admission control rejected the request
    "admission",           # admission posture forced the tier down
    "admission_cost",      # calibrated predicted cost exceeded the
                           # remaining deadline budget (ISSUE 20)
    "tiered_cold",         # probe hit a non-resident partition: host scan
    "paging_race",         # residency churned while a dispatch was in flight
)

# legacy event label value -> normalized reason. One table so the old
# names remain greppable aliases of exactly one documented reason.
_LEGACY_REASONS: Dict[str, str] = {
    # hybrid_fused_events_total
    "host_fallback_changelog": "changelog_overrun",
    "host_fallback_compaction": "compaction",
    "host_fallback_overflow": "overflow",
    "host_fallback_vec_race": "vec_race",
    "host_fallback_unshardable": "unshardable",
    "walk_pending_build": "pending_build",
    "walk_fallback_itopk": "itopk_exceeded",
    "walk_fallback_shards": "shard_mismatch",
    "walk_fallback_changelog": "changelog_overrun",
    "walk_underfill_brute": "underfill",
    "walk_quarantined": "quarantine",
    "quant_pending_build": "pending_build",
    "quant_fallback_compaction": "compaction",
    "quant_fallback_changelog": "changelog_overrun",
    "quant_fallback_vec_race": "vec_race",
    "quant_underfill_f32": "underfill",
    "quant_quarantined": "quarantine",
    # quant_events_total
    "degrade_compaction": "compaction",
    "degrade_changelog": "changelog_overrun",
    "degrade_rerank_race": "rerank_race",
    "degrade_underfill": "underfill",
    "degrade_error": "error",
    "degrade_quarantine": "quarantine",
    # cagra_events_total
    "exact_fallback_itopk": "itopk_exceeded",
    "exact_fallback_changelog": "changelog_overrun",
    "exact_fallback_underfill": "underfill",
    "exact_fallback_quarantine": "quarantine",
    # device_bm25_events_total
    "host_fallback_pending": "pending_build",
    # tiered_events_total
    "degrade_paging_race": "paging_race",
    "cold_scan": "tiered_cold",
    # device_graph_events_total
    "degrade_stale": "stale_snapshot",
    "degrade_exactness": "exactness",
    "degrade_rank_overflow": "rank_overflow",
    "batch_below_min_b": "min_batch",
}


def normalize_reason(event: str) -> str:
    """Normalized reason for a legacy event label value; values already
    in the vocabulary pass through, unknowns map to ``error``."""
    if event in REASONS:
        return event
    return _LEGACY_REASONS.get(event, "error")


# ---------------------------------------------------------------------------
# tier attribution metrics
# ---------------------------------------------------------------------------

_SERVED_C = REGISTRY.counter(
    "nornicdb_served_tier_total",
    "Queries answered, by serving surface and ladder tier",
    labels=("surface", "tier"))
_SERVED_H = REGISTRY.histogram(
    "nornicdb_served_tier_seconds",
    "Per-query wall time by serving surface and ladder tier",
    labels=("surface", "tier"), buckets=LATENCY_BUCKETS)
# the PR 7 stage attribution split by tier: the coalesce/dispatch/merge
# intervals of tier-attributed requests, keyed by the tier that served
# (bounded label set — the taxonomy above)
_TIER_STAGE_H = REGISTRY.histogram(
    "nornicdb_tier_stage_seconds",
    "Per-request stage attribution split by serving tier",
    labels=("tier", "stage"), buckets=LATENCY_BUCKETS)
_DEGRADE_C = REGISTRY.counter(
    "nornicdb_degrade_total",
    "Tier degradations by surface, ladder edge and normalized reason",
    labels=("surface", "from_tier", "to_tier", "reason"))
_PARITY_G = REGISTRY.gauge(
    "nornicdb_parity_ratio",
    "Shadow-audit device/host parity ratio per tier (rolling window)",
    labels=("surface", "tier"))
_SAMPLED_C = REGISTRY.counter(
    "nornicdb_audit_sampled_total",
    "Shadow-parity samples completed per tier",
    labels=("surface", "tier"))
_MISMATCH_C = REGISTRY.counter(
    "nornicdb_audit_mismatch_total",
    "Shadow-parity samples below the tier's floor",
    labels=("surface", "tier"))
_DROPPED_C = REGISTRY.counter(
    "nornicdb_audit_dropped_total",
    "Shadow-parity samples dropped (queue full / budget exhausted)",
    labels=("reason",))


def served_counter(surface: str, tier: str):
    """The materialized child counter for one (surface, tier) — hot
    paths that cannot afford a labels() probe per query (the ~50us host
    chain fast path) cache this at import and call ``.inc()``."""
    return _SERVED_C.labels(surface, tier)


def record_served(surface: str, tier: str, seconds: Optional[float] = None,
                  n: int = 1) -> None:
    """Count one (or ``n``) served queries on a tier, observe the wall
    time when known, and stamp ``served_by`` on the active trace span.
    No-op under :func:`suppress_attribution` (a nested sub-dispatch of
    an already-counted query)."""
    if not _m.enabled() or getattr(_tls, "suppress", False):
        return
    _SERVED_C.labels(surface, tier).inc(n)
    if seconds is not None:
        _SERVED_H.labels(surface, tier).observe(seconds)
    # the per-tenant side rides the same chokepoint (ISSUE 18): under
    # an active batch mix the n serves distribute across the riders'
    # tenants, else the current context's tenant takes them
    _tenant.record_served(surface, tier, seconds=seconds, n=n)
    annotate(served_by=tier)


def record_tier_stages(tier: str, wait_s: float, dispatch_s: float,
                       merge_s: float) -> None:
    """The PR 7 stage split attributed to the tier that served."""
    if not _m.enabled():
        return
    _TIER_STAGE_H.labels(tier, "coalesce_wait").observe(max(wait_s, 0.0))
    _TIER_STAGE_H.labels(tier, "device_dispatch").observe(
        max(dispatch_s, 0.0))
    _TIER_STAGE_H.labels(tier, "merge").observe(max(merge_s, 0.0))


def tier_mix() -> Dict[str, Dict[str, float]]:
    """Served-tier counts per surface — the tier mix /admin/telemetry
    and the bench load stage report."""
    out: Dict[str, Dict[str, float]] = {}
    for (surface, tier), child in _SERVED_C.children().items():
        v = child.value
        if v:
            out.setdefault(surface, {})[tier] = v
    return out


def tier_counts() -> Dict[str, float]:
    """Flat ``surface:tier -> count`` snapshot (delta-friendly shape
    for the bench sweep's per-point tier-mix probe)."""
    return {f"{surface}:{tier}": child.value
            for (surface, tier), child in _SERVED_C.children().items()
            if child.value}


# -- the leader->rider tier channel ------------------------------------------
#
# Batched dispatch functions (the device index code) know which ladder
# rung actually served a batch; the MicroBatcher leader thread runs
# them and the riders need the verdict. The dispatch notes the tier in
# a thread-local; the leader consumes it after the call and stamps it
# onto every rider's request object; each rider then records itself
# (counter + histogram + span) in its own thread — rider-accurate
# counting with zero cross-thread coordination beyond the stamp.

_tls = threading.local()


def note_batch_tier(tier: str) -> None:
    """Called by a batched dispatch path: this batch was served by
    ``tier``. Last note wins (a fallback overwrites the tier it fell
    back from)."""
    _tls.batch_tier = tier


def consume_batch_tier() -> Optional[str]:
    """Read-and-clear the current thread's batch tier note."""
    tier = getattr(_tls, "batch_tier", None)
    _tls.batch_tier = None
    return tier


def set_last_served(tier: Optional[str]) -> None:
    """Rider-side: the tier that served this thread's latest batched
    query (stamped by the MicroBatcher) — read by sampling call sites
    that sit above the batcher."""
    _tls.last_served = tier


class _SuppressAttribution:
    """Context manager: sub-dispatches inside an already-attributed
    query (the host hybrid path's nested vector ride) must not count a
    second serve — one user query, one tier-mix increment."""

    __slots__ = ("_prev",)

    def __enter__(self):
        self._prev = getattr(_tls, "suppress", False)
        _tls.suppress = True
        return self

    def __exit__(self, *exc):
        _tls.suppress = self._prev


def suppress_attribution() -> _SuppressAttribution:
    return _SuppressAttribution()


def last_served() -> Optional[str]:
    return getattr(_tls, "last_served", None)


# -- the fleet-node channel (ISSUE 13) ---------------------------------------
#
# Same discipline as the batch-tier channel: the FleetRouter knows which
# replica served a coalesced dispatch, the broker (running the dispatch
# on its pool thread) needs the verdict to stamp the riders' span
# records and response docs — a note in a thread-local, read-and-clear
# by the dispatcher after the call.


def note_fleet_node(node: str) -> None:
    """Called by the fleet router when a replica served this thread's
    dispatch (``primary`` on local fallback)."""
    _tls.fleet_node = node


def consume_fleet_node() -> Optional[str]:
    node = getattr(_tls, "fleet_node", None)
    _tls.fleet_node = None
    return node


# ---------------------------------------------------------------------------
# unified degrade ledger
# ---------------------------------------------------------------------------


def _ring_capacity() -> int:
    try:
        return max(16, int(os.environ.get("NORNICDB_DEGRADE_RING", "512")))
    except ValueError:
        return 512


class DegradeLedger:
    """Bounded ring of structured degrade records, newest last."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        self.capacity = capacity or _ring_capacity()
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self.recorded = 0

    def record(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._ring.append(rec)
            self.recorded += 1

    def snapshot(self, limit: int = 100) -> List[Dict[str, Any]]:
        """Most recent first."""
        with self._lock:
            items = list(self._ring)
        return list(reversed(items))[:max(0, limit)]

    def by_reason(self) -> Dict[str, int]:
        with self._lock:
            items = list(self._ring)
        out: Dict[str, int] = {}
        for rec in items:
            out[rec["reason"]] = out.get(rec["reason"], 0) + 1
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


LEDGER = DegradeLedger()


def record_degrade(surface: str, from_tier: str, to_tier: str,
                   reason: str, index: str = "",
                   versions: Optional[Dict[str, Any]] = None) -> None:
    """One structured degrade record: counted, ring-buffered, and
    grafted into the owning trace as a zero-width ``degrade`` span.
    ``reason`` may be a legacy event label value — it is normalized
    onto the documented vocabulary. Never raises; never blocks."""
    if not _m.enabled():
        return
    r = normalize_reason(reason)
    _DEGRADE_C.labels(surface, from_tier, to_tier, r).inc()
    now = time.time()
    rec: Dict[str, Any] = {
        "ts": round(now, 6),
        "surface": surface,
        "from_tier": from_tier,
        "to_tier": to_tier,
        "reason": r,
        "index": index,
    }
    if versions:
        rec["versions"] = dict(versions)
    tid = current_trace_id()
    if tid is not None:
        rec["trace_id"] = tid
    tenant = _tenant.current_tenant()
    if tenant:
        rec["tenant"] = tenant
    _tenant.record_degrade(surface, r)
    LEDGER.record(rec)
    # a broker op capture in flight on this thread (ISSUE 11): the
    # record also ships back to the frontend worker that owns the
    # query, so its /admin/degrades stays truthful across the
    # process boundary
    collector = getattr(_tls, "degrade_collector", None)
    if collector is not None:
        collector.append(dict(rec))
    # graft into the owning trace: a degraded request's span tree
    # answers "why was this served from a lower rung" on its own
    attach_span("degrade", now, now, surface=surface,
                from_tier=from_tier, to_tier=to_tier, reason=r)
    # and into the unified incident timeline (ISSUE 13) — trace-linked
    # through the same (possibly propagated) trace id
    _events.record_event("degrade", node=index, surface=surface,
                         reason=r, trace_id=tid,
                         detail={"from_tier": from_tier,
                                 "to_tier": to_tier})


class _DegradeCollector:
    """Thread-local capture of degrade records produced while a broker
    op executes on a device-plane pool thread — the records ride the
    op's response back to the frontend worker (ISSUE 11)."""

    __slots__ = ("_prev", "records")

    def __init__(self) -> None:
        self.records: List[Dict[str, Any]] = []

    def __enter__(self) -> List[Dict[str, Any]]:
        self._prev = getattr(_tls, "degrade_collector", None)
        _tls.degrade_collector = self.records
        return self.records

    def __exit__(self, *exc) -> None:
        _tls.degrade_collector = self._prev


def collect_degrades() -> _DegradeCollector:
    return _DegradeCollector()


def replay_degrade(rec: Dict[str, Any]) -> None:
    """Frontend-worker side of the boundary crossing: append a degrade
    record relayed from the device plane to THIS process's ledger ring
    (marked ``via: broker``). The counter is NOT re-incremented — the
    worker's /metrics aggregation already carries the shared plane's
    ``nornicdb_degrade_total`` exactly once. The record's ``trace_id``
    — stamped plane-side under the PROPAGATED context (ISSUE 13) — is
    kept, so a broker-crossing degrade joins its trace in this
    worker's ledger exactly like a local one. The incident-timeline
    event is NOT re-recorded either: the plane's ``record_degrade``
    already journaled it, and the worker's merged ``/admin/events``
    view carries the plane journal — a second record here would
    double-count the one incident (same exactly-once discipline as
    the counter)."""
    if not _m.enabled():
        return
    LEDGER.record({**rec, "via": "broker"})


def degrade_snapshot(limit: int = 100) -> List[Dict[str, Any]]:
    return LEDGER.snapshot(limit)


def degrade_summary() -> Dict[str, Any]:
    return {
        "recorded": LEDGER.recorded,
        "capacity": LEDGER.capacity,
        "by_reason": LEDGER.by_reason(),
    }


# ---------------------------------------------------------------------------
# online shadow-parity auditor
# ---------------------------------------------------------------------------


def _parse_rate(spec: str) -> float:
    """``1/256`` | float | ``0``/``off`` (disabled) | ``on``/``default``
    (the documented default 1/256)."""
    s = (spec or "").strip().lower()
    if s in ("", "0", "off", "false", "none"):
        return 0.0
    if s in ("on", "default", "true"):
        return 1.0 / 256.0
    try:
        if "/" in s:
            num, _, den = s.partition("/")
            return max(0.0, min(1.0, float(num) / max(float(den), 1e-9)))
        return max(0.0, min(1.0, float(s)))
    except ValueError:
        return 0.0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


class ShadowAuditor:
    """Background device/host parity sampler.

    ``maybe_sample`` is the only hot-path entry: a modulo check on a
    per-tier counter, a token-bucket budget probe, and a non-blocking
    queue append — a full queue or an exhausted budget drops the
    sample (counted), never blocks the serving dispatch. The worker
    thread re-executes the captured query on the caller-provided host
    reference closure, scores parity, updates the gauges/windows, and
    on a per-sample floor miss writes a self-contained repro record
    through the SLO flight recorder."""

    def __init__(
        self,
        rate: Optional[float] = None,
        max_qps: Optional[float] = None,
        window: Optional[int] = None,
        min_samples: Optional[int] = None,
        queue_cap: int = 256,
        dump_interval_s: Optional[float] = None,
        quarantine_s: Optional[float] = None,
    ) -> None:
        self._rate_override = rate
        self._max_qps = max_qps
        self._window_n = window
        self._min_samples = min_samples
        self._queue_cap = queue_cap
        self._dump_interval_s = dump_interval_s
        self._quarantine_s = quarantine_s
        self._lock = threading.Lock()
        self._queue: deque = deque()
        self._have_work = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self._seq: Dict[Tuple[str, str], int] = {}
        # token bucket for the absolute QPS budget (starts full)
        self._tokens: Optional[float] = None
        self._tokens_t = time.time()
        # per (surface, tier): rolling parity window
        self._windows: Dict[Tuple[str, str], deque] = {}
        self._blocked_until: Dict[str, float] = {}
        self._last_dump_t = 0.0
        self._quarantine_override: Optional[bool] = None
        self.sampled = 0
        self.mismatches = 0
        self.dumps: List[str] = []

    # -- config (env read per call so tests/bench can flip at runtime) ----

    def sample_rate(self) -> float:
        if self._rate_override is not None:
            return self._rate_override
        return _parse_rate(os.environ.get("NORNICDB_AUDIT_SAMPLE", "0"))

    def set_sample_rate(self, rate: Optional[float]) -> None:
        """Runtime override (None = back to the env)."""
        self._rate_override = rate

    def max_qps(self) -> float:
        if self._max_qps is not None:
            return self._max_qps
        return max(0.1, _env_float("NORNICDB_AUDIT_MAX_QPS", 50.0))

    def window_n(self) -> int:
        if self._window_n is not None:
            return self._window_n
        try:
            return max(4, int(os.environ.get("NORNICDB_AUDIT_WINDOW", "64")))
        except ValueError:
            return 64

    def min_samples(self) -> int:
        if self._min_samples is not None:
            return self._min_samples
        try:
            return max(1, int(os.environ.get(
                "NORNICDB_AUDIT_MIN_SAMPLES", "8")))
        except ValueError:
            return 8

    def quarantine_enabled(self) -> bool:
        if self._quarantine_override is not None:
            return self._quarantine_override
        return os.environ.get("NORNICDB_AUDIT_QUARANTINE", "0").lower() \
            in ("1", "true", "on", "yes")

    def set_quarantine(self, enabled: Optional[bool]) -> None:
        self._quarantine_override = enabled

    def quarantine_s(self) -> float:
        if self._quarantine_s is not None:
            return self._quarantine_s
        return _env_float("NORNICDB_AUDIT_QUARANTINE_S", 30.0)

    def dump_interval_s(self) -> float:
        if self._dump_interval_s is not None:
            return self._dump_interval_s
        return _env_float("NORNICDB_AUDIT_DUMP_INTERVAL_S", 60.0)

    # -- hot path ---------------------------------------------------------

    def maybe_sample(
        self,
        surface: str,
        tier: str,
        device_ids: Sequence[Any],
        k: int,
        ref: Callable[[], Sequence[Any]],
        versions: Optional[Dict[str, Any]] = None,
        query: Optional[Dict[str, Any]] = None,
        versions_now: Optional[Callable[[], Dict[str, Any]]] = None,
    ) -> bool:
        """Capture one device-served query for shadow re-execution.
        ``ref`` is a zero-arg closure computing the host reference
        answer (ranked ids) off the hot path. ``versions_now`` re-reads
        the same version dict at replay time: if a write moved the
        indexes between sampling and the reference run (before OR
        during it), the sample is dropped as ``stale`` instead of being
        scored — a concurrent upsert must never read as a device
        mismatch. Returns True when the sample was enqueued. Never
        blocks, never raises."""
        if not _m.enabled() or tier in (TIER_HOST, TIER_CACHED):
            return False
        if getattr(_tls, "in_audit", False):
            return False  # the reference path must never re-sample
        rate = self.sample_rate()
        if rate <= 0.0:
            return False
        key = (surface, tier)
        with self._lock:
            n = self._seq.get(key, 0)
            self._seq[key] = n + 1
            interval = max(1, int(round(1.0 / rate)))
            if n % interval != 0:
                return False
            # absolute QPS budget: token bucket refilled on the fly
            now = time.time()
            cap = self.max_qps()
            tokens = cap if self._tokens is None else self._tokens
            self._tokens = min(cap, tokens
                               + (now - self._tokens_t) * cap)
            self._tokens_t = now
            if self._tokens < 1.0:
                _DROPPED_C.labels("budget").inc()
                return False
            self._tokens -= 1.0
            if len(self._queue) >= self._queue_cap:
                _DROPPED_C.labels("queue_full").inc()
                return False
            self._queue.append({
                "surface": surface,
                "tier": tier,
                "k": int(k),
                "device_ids": list(device_ids),
                "ref": ref,
                "versions": dict(versions or {}),
                "versions_now": versions_now,
                "query": query,
                "trace_id": current_trace_id(),
                "ts": now,
            })
        self._ensure_worker()
        self._have_work.set()
        return True

    # -- worker -----------------------------------------------------------

    def _ensure_worker(self) -> None:
        w = self._worker
        if w is not None and w.is_alive():
            return
        with self._lock:
            if self._worker is not None and self._worker.is_alive():
                return
            t = threading.Thread(target=self._run, name="shadow-audit",
                                 daemon=True)
            self._worker = t
            t.start()

    def _run(self) -> None:
        # lazy: admission imports this module; at worker start the
        # cycle is long resolved. Shadow replays ride the REPLAY lane
        # (ISSUE 15) so reference re-executions seal behind interactive
        # traffic in any coalescer they touch.
        from nornicdb_tpu import admission as _adm_lane

        _tls.in_audit = True
        _adm_lane.lane_scope(_adm_lane.LANE_REPLAY).__enter__()
        while True:
            self._have_work.wait(timeout=1.0)
            item = None
            with self._lock:
                if self._queue:
                    item = self._queue.popleft()
                else:
                    self._have_work.clear()
            if item is None:
                continue
            try:
                self._process(item)
            except Exception:  # noqa: BLE001 — the auditor never crashes
                pass

    def flush(self, timeout_s: float = 5.0) -> None:
        """Drain the queue (tests / bench summaries)."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._lock:
                if not self._queue:
                    return
            self._ensure_worker()
            self._have_work.set()
            time.sleep(0.005)

    @staticmethod
    def parity_of(device_ids: Sequence[Any], host_ids: Sequence[Any],
                  k: int, exact: bool) -> float:
        """Rank-parity (exact tiers) or recall@k (statistical tiers) of
        a device answer vs the host reference, both ranked id lists.

        Entries may be ``(id, score)`` pairs. For EXACT tiers that
        enables tie-aware rank parity: a position matches when the ids
        agree OR the scores are identical and the device id belongs to
        the host's same-score tie group — the device contract is "same
        scores, same membership at every score level", and a padded-
        batch dispatch may legitimately permute rows WITHIN an exact
        tie relative to the b=1 replay (ISSUE 11: surfaced by the
        wire-plane load run; ids-only exact samples keep the strict
        positional contract). Statistical tiers always compare ids."""

        def _pair(x):
            if isinstance(x, (tuple, list)) and len(x) == 2:
                return x[0], float(x[1])
            return x, None

        kk = min(k, len(host_ids)) if host_ids else 0
        if kk == 0:
            # host found nothing: the device agreeing (also nothing)
            # is parity 1, anything extra is a mismatch
            return 1.0 if not list(device_ids)[:k] else 0.0
        d = [_pair(x) for x in list(device_ids)[:kk]]
        h = [_pair(x) for x in list(host_ids)[:kk]]
        if exact:
            host_full = [_pair(x) for x in host_ids]
            tie_groups: Dict[float, set] = {}
            for hid, hs in host_full:
                if hs is not None:
                    tie_groups.setdefault(hs, set()).add(hid)
            # a tie group the host list was truncated INSIDE (its last
            # entry carries the group's score) has unobservable
            # membership beyond the cutoff: score equality is all the
            # sample can check there
            tail_score = host_full[-1][1] if host_full else None
            same = 0
            for (di, ds), (hi, hs) in zip(d, h):
                if di == hi:
                    same += 1
                elif ds is not None and hs is not None and ds == hs \
                        and (di in tie_groups.get(ds, ())
                             or ds == tail_score):
                    same += 1
            return same / kk
        return len({i for i, _ in d} & {i for i, _ in h}) / kk

    def _process(self, item: Dict[str, Any]) -> None:
        surface, tier = item["surface"], item["tier"]
        vnow = item.get("versions_now")

        def _stale() -> bool:
            if vnow is None:
                return False
            try:
                return dict(vnow()) != item["versions"]
            except Exception:  # noqa: BLE001 — treat as moved on
                return True

        # a write that landed between sampling and replay makes the
        # live reference incomparable to the captured device answer:
        # drop (counted), never score a correct answer as a mismatch
        if _stale():
            _DROPPED_C.labels("stale").inc()
            return
        try:
            host_ids = list(item["ref"]() or [])
        except Exception as exc:  # noqa: BLE001
            # a failed reference execution is not a device mismatch —
            # count the sample dropped and move on
            _DROPPED_C.labels("ref_error").inc()
            del exc
            return
        if _stale():  # a write landed DURING the reference run
            _DROPPED_C.labels("stale").inc()
            return
        exact = tier in EXACT_TIERS
        parity = self.parity_of(item["device_ids"], host_ids,
                                item["k"], exact)
        floor = tier_floor(tier)
        key = (surface, tier)
        with self._lock:
            win = self._windows.get(key)
            if win is None or win.maxlen != self.window_n():
                win = deque(win or (), maxlen=self.window_n())
                self._windows[key] = win
            win.append(parity)
            ratio = sum(win) / len(win)
            self.sampled += 1
        _SAMPLED_C.labels(surface, tier).inc()
        _PARITY_G.labels(surface, tier).set(ratio)
        if parity < floor - 1e-9:
            with self._lock:
                self.mismatches += 1
            _MISMATCH_C.labels(surface, tier).inc()
            self._dump_mismatch(item, host_ids, parity, floor)
        if self.quarantine_enabled():
            if len(win) >= self.min_samples() and ratio < floor - 1e-9:
                with self._lock:
                    # timeline records the step-down TRANSITION only,
                    # not every sample that extends an open quarantine
                    fresh_block = self._blocked_until.get(tier, 0.0) \
                        <= time.time()
                    self._blocked_until[tier] = (
                        time.time() + self.quarantine_s())
                if fresh_block:
                    _events.record_event(
                        "quarantine", surface=surface, node=tier,
                        reason="parity_breach",
                        trace_id=item.get("trace_id"),
                        detail={"ratio": round(ratio, 4),
                                "floor": floor})
            elif ratio >= floor - 1e-9:
                # the rolling window recovered: the breach has cleared,
                # so the quarantine lifts immediately (probation-window
                # samples wrote the recovery; don't serve degraded for
                # the rest of the block)
                with self._lock:
                    lifted = self._blocked_until.pop(tier, None)
                if lifted is not None:
                    _events.record_event(
                        "quarantine_lift", surface=surface, node=tier,
                        reason="parity_recovered",
                        detail={"ratio": round(ratio, 4)})

    def _dump_mismatch(self, item: Dict[str, Any],
                       host_ids: List[Any], parity: float,
                       floor: float) -> None:
        """Self-contained repro record through the PR 5 flight
        recorder: query, both answer sets, every snapshot version —
        enough to re-run the comparison without the live node.
        Rate-limited; best-effort (a failed dump never fails the
        audit)."""
        now = time.time()
        with self._lock:
            if now - self._last_dump_t < self.dump_interval_s():
                return
            self._last_dump_t = now
        record = {
            "surface": item["surface"],
            "tier": item["tier"],
            "k": item["k"],
            "parity": round(parity, 6),
            "floor": floor,
            "device_ids": _jsonable_ids(item["device_ids"]),
            "host_ids": _jsonable_ids(host_ids),
            "versions": item["versions"],
            "query": item.get("query"),
            "trace_id": item.get("trace_id"),
            "sampled_ts": item["ts"],
        }
        try:
            from nornicdb_tpu.obs import slo as _slo

            path = _slo.get_engine().dump(
                reason=f"parity_mismatch:{item['tier']}",
                extra=[{"kind": "parity_repro", "record": record}])
            with self._lock:
                self.dumps.append(path)
        except Exception:  # noqa: BLE001
            pass

    # -- status / gating --------------------------------------------------

    def parity_breaches(self) -> List[Dict[str, Any]]:
        """Tiers whose rolling parity sits below their floor with
        enough samples — the /readyz reasons feed."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            items = list(self._windows.items())
            min_n = self.min_samples()
        for (surface, tier), win in items:
            if len(win) < min_n:
                continue
            ratio = sum(win) / len(win)
            floor = tier_floor(tier)
            if ratio < floor - 1e-9:
                out.append({"surface": surface, "tier": tier,
                            "ratio": round(ratio, 4), "floor": floor})
        return out

    def tier_allowed(self, tier: str) -> bool:
        """False while quarantine is enabled and the tier sits inside
        its quarantine window — callers step the query down the tier's
        existing ladder. After the window the tier re-probes: fresh
        samples either re-trip the quarantine or heal the parity
        window, so recovery is automatic once the breach clears."""
        if not self.quarantine_enabled():
            return True
        until = self._blocked_until.get(tier)
        if until is None:
            return True
        if time.time() >= until:
            return True  # probation: serve again, let samples decide
        return False

    def summary(self) -> Dict[str, Any]:
        """The /admin/telemetry ``parity`` block."""
        tiers: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            items = list(self._windows.items())
            blocked = dict(self._blocked_until)
            queue_depth = len(self._queue)
        now = time.time()
        for (surface, tier), win in items:
            ratio = (sum(win) / len(win)) if win else None
            floor = tier_floor(tier)
            tiers[f"{surface}:{tier}"] = {
                "parity": None if ratio is None else round(ratio, 4),
                "floor": floor,
                "samples": len(win),
                "breached": (ratio is not None
                             and len(win) >= self.min_samples()
                             and ratio < floor - 1e-9),
                "quarantined": (self.quarantine_enabled()
                                and blocked.get(tier, 0.0) > now),
            }
        return {
            "enabled": self.sample_rate() > 0.0,
            "sample_rate": self.sample_rate(),
            "max_qps": self.max_qps(),
            "quarantine": self.quarantine_enabled(),
            "sampled": self.sampled,
            "mismatches": self.mismatches,
            "queue_depth": queue_depth,
            "tiers": tiers,
        }

    def reset(self) -> None:
        """Test helper: forget windows, quarantine state and queue."""
        with self._lock:
            self._queue.clear()
            self._windows.clear()
            self._blocked_until.clear()
            self._seq.clear()
            self.sampled = 0
            self.mismatches = 0
            self.dumps = []
            self._last_dump_t = 0.0
            self._tokens = None
            self._tokens_t = time.time()


def _jsonable_ids(ids: Sequence[Any]) -> List[Any]:
    out = []
    for i in ids:
        if isinstance(i, (tuple, list)) and len(i) == 2:
            # (id, score) pair from a tie-aware exact sample
            i = [i[0] if isinstance(i[0], (str, int)) else str(i[0]),
                 float(i[1])]
        try:
            json.dumps(i)
            out.append(i)
        except (TypeError, ValueError):
            out.append(str(i))
    return out


AUDITOR = ShadowAuditor()


def maybe_sample(surface: str, tier: str, device_ids: Sequence[Any],
                 k: int, ref: Callable[[], Sequence[Any]],
                 versions: Optional[Dict[str, Any]] = None,
                 query: Optional[Dict[str, Any]] = None,
                 versions_now: Optional[Callable[[], Dict[str, Any]]]
                 = None) -> bool:
    return AUDITOR.maybe_sample(surface, tier, device_ids, k, ref,
                                versions=versions, query=query,
                                versions_now=versions_now)


def sampling_active() -> bool:
    """Cheap pre-gate for hot call sites: skip building the sample's
    id lists/closures entirely while auditing is off."""
    return _m.enabled() and AUDITOR.sample_rate() > 0.0


def tier_allowed(tier: str) -> bool:
    return AUDITOR.tier_allowed(tier)


# -- admission-posture tier forcing (ISSUE 15) --------------------------------
#
# The admission controller (nornicdb_tpu/admission.py) degrades along
# the existing serving ladders BEFORE it rejects work: under a degrade-
# or-worse posture the expensive device rungs (walk/quant/graph) step
# down to brute/host exactly like a parity quarantine would, through
# the same per-ladder gate sites — one registered hook, so audit stays
# import-light and admission stays optional.

_ADMISSION_GATE: Callable[[str], bool] = lambda tier: True


def set_admission_gate(fn: Callable[[str], bool]) -> None:
    global _ADMISSION_GATE
    _ADMISSION_GATE = fn


def admission_allows(tier: str) -> bool:
    """True unless the admission posture is holding this tier down its
    ladder (ledger reason ``admission`` at the gate sites — distinct
    from the auditor's ``quarantine``)."""
    try:
        return _ADMISSION_GATE(tier)
    except Exception:  # noqa: BLE001 — a broken gate must not fail serving
        return True


def parity_breaches() -> List[Dict[str, Any]]:
    return AUDITOR.parity_breaches()


def audit_summary() -> Dict[str, Any]:
    return AUDITOR.summary()
