"""Admission control: deadline budgets, priority lanes, SLO-driven
load shedding (ISSUE 15 / ROADMAP item 2).

The observability stack can *see* overload perfectly — burn rates
(obs/slo.py), per-request queue-delay stage attribution (obs/stages.py),
per-query tier attribution (obs/audit.py) — but until now nothing
*acted* on it: a pre-chip CPU run showed the gRPC surface past its open-loop
knee collapsing from p99 7.6 ms to 565 ms while achieved QPS fell below
offered, because every arrival was admitted into an unbounded queue.
This module is the actuator, in three parts:

1. **Per-request deadline budgets.** Every ingress mints an absolute
   deadline — from gRPC deadline metadata (``context.time_remaining``),
   the ``X-Nornic-Deadline-Ms`` HTTP header, or a default derived from
   the surface's SLO objective (threshold x
   ``NORNICDB_DEADLINE_SLO_FACTOR``, overridable with
   ``NORNICDB_DEADLINE_DEFAULT_MS``) — carried in a contextvar so it
   crosses the executor hop exactly like the trace context, and carried
   across the broker ring in the OP_VEC/OP_CALL slot header
   (search/broker.py). The MicroBatcher/BatchCoalescer consult it: a
   rider already past budget fails fast with a degrade-ledger record
   instead of occupying a device slot, and a rider whose remaining
   budget would expire inside the gather window triggers an immediate
   smaller dispatch (pow2 buckets absorb the size change — no new
   compile universe).

2. **Priority lanes.** Three bounded lanes — ``interactive`` (client
   reads) > ``replay`` (replica WAL replay, shadow-audit replays) >
   ``background`` (index rebuilds, decay/inference sweeps, bulk upsert
   convoys) — carried in a contextvar set by :func:`lane_scope` at the
   top of every background worker thread. Batch leaders seal batches in
   lane-priority order (with an aging promotion so background work can
   never starve outright), so a rebuild kicked mid-load cannot convoy
   interactive traffic through the shared dispatch machinery.

3. **SLO-driven shedding.** The controller tracks per-lane in-flight
   counts and a completion-rate EWMA per surface; when the estimated
   queue wait crosses ``NORNICDB_ADMIT_MAX_WAIT_MS`` (or the burn-rate
   engine breaches), admission first *degrades along the existing
   serving ladders* — the :func:`tier_gate` hook registered with
   obs/audit.py forces walk/quant/graph device tiers down to brute/host
   to shrink device pressure — then sheds lowest-priority work first
   with honest backpressure: HTTP 429 + ``Retry-After`` derived from
   the lane drain rate, gRPC ``RESOURCE_EXHAUSTED`` with
   ``grpc-retry-pushback-ms`` trailing metadata. Every shed is counted
   (``nornicdb_shed_total``), ledgered (one degrade-ledger record) and
   journaled (one ``shed`` event), trace-linked to the originating
   request.

Configuration is read ONCE at first use and cached (:func:`reload` for
tests) — the per-request functions here are registered hot paths
(lint/config.py HOT_PATHS) and must never read the environment.
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import events as _events
from nornicdb_tpu.obs import metrics as _m
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu.obs.metrics import REGISTRY
from nornicdb_tpu.obs.tracing import annotate, current_trace_id

# ---------------------------------------------------------------------------
# lanes
# ---------------------------------------------------------------------------

LANE_INTERACTIVE = "interactive"
LANE_REPLAY = "replay"
LANE_BACKGROUND = "background"
# priority order, best first — index IS the lane rank
LANES = (LANE_INTERACTIVE, LANE_REPLAY, LANE_BACKGROUND)
_LANE_RANK = {lane: i for i, lane in enumerate(LANES)}

# ring wire codes (search/broker.py slot header carries one byte)
LANE_CODES = {LANE_INTERACTIVE: 0, LANE_REPLAY: 1, LANE_BACKGROUND: 2}
LANE_FROM_CODE = {v: k for k, v in LANE_CODES.items()}

# the HTTP header carrying a client's deadline budget in milliseconds
DEADLINE_HEADER = "X-Nornic-Deadline-Ms"

_ctx_deadline: contextvars.ContextVar[Optional[float]] = \
    contextvars.ContextVar("nornic_deadline", default=None)
# True when the active deadline came from the CLIENT (gRPC deadline
# metadata, X-Nornic-Deadline-Ms, or a programmatic deadline_scope) as
# opposed to the server-minted surface default: only explicit budgets
# may EXTEND infrastructure timeouts (the broker's flat rider timeout)
# — a 30s server default must not double the dead-plane detection time
_ctx_deadline_explicit: contextvars.ContextVar[bool] = \
    contextvars.ContextVar("nornic_deadline_explicit", default=False)
_ctx_lane: contextvars.ContextVar[str] = \
    contextvars.ContextVar("nornic_lane", default=LANE_INTERACTIVE)
# set by record_shed inside an ingress scope: the scope's exit must
# not count a shed as served capacity in the drain-rate EWMA (a shed
# completes "instantly"; counting it would inflate the drain estimate
# and oscillate the shedding verdict)
_ctx_was_shed: contextvars.ContextVar[bool] = \
    contextvars.ContextVar("nornic_was_shed", default=False)

# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

_SHED_C = REGISTRY.counter(
    "nornicdb_shed_total",
    "Queries rejected by admission control, by surface/lane/reason",
    labels=("surface", "lane", "reason"))
_MISS_C = REGISTRY.counter(
    "nornicdb_deadline_miss_total",
    "Requests failed fast past their deadline budget, by surface and "
    "the stage that caught the expiry",
    labels=("surface", "stage"))
_LANE_IN_G = REGISTRY.gauge(
    "nornicdb_lane_inflight",
    "Admitted requests currently in flight per priority lane",
    labels=("lane",))
_POSTURE_G = REGISTRY.gauge(
    "nornicdb_admission_posture",
    "Current admission posture (0 admit, 1 degrade, 2 shed, "
    "3 shed_hard)")

POSTURES = ("admit", "degrade", "shed", "shed_hard")


class ShedError(Exception):
    """Admission refused this request. Maps to HTTP 429 +
    ``Retry-After`` / gRPC ``RESOURCE_EXHAUSTED`` with
    ``grpc-retry-pushback-ms`` metadata — honest backpressure, never a
    silent queue."""

    status = 429

    def __init__(self, surface: str, lane: str, retry_after_s: float,
                 reason: str = "shed"):
        super().__init__(
            f"admission shed ({reason}): lane {lane} over capacity on "
            f"{surface}; retry after {retry_after_s:.1f}s")
        self.surface = surface
        self.lane = lane
        self.reason = reason
        self.retry_after_s = retry_after_s


class DeadlineExceeded(RuntimeError):
    """The request's deadline budget expired before (or while) it
    queued for dispatch — failed fast instead of occupying a device
    slot it can no longer use."""

    status = 504


# ---------------------------------------------------------------------------
# cached configuration (env read once; per-request paths read the dict)
# ---------------------------------------------------------------------------

_cfg_lock = threading.Lock()
_cfg: Optional[Dict[str, Any]] = None


def _load_cfg() -> Dict[str, Any]:
    from nornicdb_tpu.config import (env_bool, env_float, env_int,
                                     env_str)

    factor = env_float("DEADLINE_SLO_FACTOR", 120.0)
    default_ms = env_float("DEADLINE_DEFAULT_MS", 0.0)
    # per-surface default budgets derive from the SLO objectives: a
    # surface whose objective says "99% under 100ms" gets factor x
    # 100ms of budget before the scheduler treats the rider as
    # abandoned. NORNICDB_DEADLINE_DEFAULT_MS overrides every surface.
    defaults: Dict[str, float] = {}
    try:
        from nornicdb_tpu.obs.slo import _objectives_from_env

        for obj in _objectives_from_env():
            defaults[obj.name] = obj.threshold_s * factor
    except Exception:  # noqa: BLE001 — deadline defaults must not fail boot
        pass
    defaults.setdefault("http", 0.25 * factor)
    defaults.setdefault("grpc", 0.1 * factor)
    if default_ms > 0:
        defaults = {k: default_ms / 1e3 for k in defaults}
        defaults["*"] = default_ms / 1e3
    else:
        defaults["*"] = max(defaults.values())
    weights_spec = env_str("LANE_WEIGHTS", "")
    weights = {LANE_INTERACTIVE: 16.0, LANE_REPLAY: 4.0,
               LANE_BACKGROUND: 1.0}
    if weights_spec:
        try:
            parts = [float(x) for x in weights_spec.split(",")]
            for lane, w in zip(LANES, parts):
                weights[lane] = max(w, 0.1)
        except ValueError:
            pass
    return {
        "deadline_defaults_s": defaults,
        "lane_weights": weights,
        # aging promotion: a background/replay rider older than this
        # seals like interactive (no outright starvation)
        "lane_max_wait_s": env_float("LANE_MAX_WAIT_S", 2.0),
        "shed_enabled": env_str("ADMIT_SHED", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        # estimated-wait bound for the interactive lane: the queueing
        # delay the scheduler refuses to let build up (the p99-at-load
        # bound under overload ≈ this + one dispatch)
        "max_wait_s": env_float("ADMIT_MAX_WAIT_MS", 50.0) / 1e3,
        # absolute in-flight cap per lane when no drain estimate exists
        "max_queue": env_int("ADMIT_MAX_QUEUE", 512),
        # burn-rate posture thresholds (fast window, obs/slo.py)
        "burn_degrade": env_float("ADMIT_BURN_DEGRADE", 6.0),
        "burn_shed": env_float("ADMIT_BURN_SHED", 14.4),
        # posture recompute cadence (the per-request check reads cache)
        "interval_s": env_float("ADMIT_INTERVAL_MS", 100.0) / 1e3,
        # fleet posture sharing (ISSUE 16): a peer-published posture
        # older than this is ignored (and may be overwritten in the
        # ring control block) — bounds how long a dead node's overload
        # signal can pin the fleet
        "fleet_posture_ttl_s": env_float("FLEET_POSTURE_TTL_S", 5.0),
        # cost-aware admission (ISSUE 20): at posture >= degrade a
        # query whose CALIBRATED predicted dispatch cost exceeds its
        # remaining deadline budget sheds up front (reason
        # ``admission_cost``) instead of occupying a device slot. The
        # gate only actuates on confident models (obs/device.py
        # abstains below its min-sample floor) — below confidence the
        # posture controller stays queue-wait-only, never a guess.
        "cost_gate_enabled": env_bool("ADMISSION_COST_GATE", True),
        # predicted_ms must exceed slack x remaining_ms to shed: > 1.0
        # sheds only clearly-doomed queries, < 1.0 sheds speculatively
        "cost_gate_slack": env_float("ADMISSION_COST_SLACK", 1.0),
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
    CONTROLLER.reset()


# ---------------------------------------------------------------------------
# deadline + lane context
# ---------------------------------------------------------------------------


def deadline() -> Optional[float]:
    """Absolute epoch deadline of the current request, or None."""
    return _ctx_deadline.get()


def deadline_explicit() -> bool:
    """True when the active deadline was supplied by the client (or a
    programmatic scope), not minted as the surface default."""
    return _ctx_deadline_explicit.get()


def remaining(now: Optional[float] = None) -> Optional[float]:
    dl = _ctx_deadline.get()
    if dl is None:
        return None
    return dl - (time.time() if now is None else now)


def lane() -> str:
    return _ctx_lane.get()


def lane_rank(lane_name: str, waited_s: float = 0.0) -> int:
    """Seal-order rank of a lane (lower seals first); a rider that has
    already waited past the aging bound promotes to interactive rank so
    low lanes cannot starve outright."""
    if waited_s >= cfg()["lane_max_wait_s"]:
        return 0
    return _LANE_RANK.get(lane_name, 0)


def default_deadline(surface: str, now: Optional[float] = None
                     ) -> float:
    d = cfg()["deadline_defaults_s"]
    budget = d.get(surface) or d["*"]
    return (time.time() if now is None else now) + budget


def mint_deadline(surface: str, budget_s: Optional[float] = None,
                  now: Optional[float] = None) -> Tuple[float, bool]:
    """(absolute deadline, explicit) for a fresh ingress request: the
    client's explicit budget when one came with the request (gRPC
    deadline, ``X-Nornic-Deadline-Ms``), else the surface default
    (``explicit`` False — a server-minted default must never EXTEND
    infrastructure timeouts downstream)."""
    now = time.time() if now is None else now
    if budget_s is not None and budget_s > 0:
        return now + budget_s, True
    return default_deadline(surface, now=now), False


def parse_deadline_header(value: Optional[str],
                          surface: str = "http") -> Tuple[float, bool]:
    """``X-Nornic-Deadline-Ms`` → (absolute deadline, explicit),
    falling back to the surface default on absent/garbage input — a
    malformed header degrades to the default budget, never to an
    error."""
    budget = None
    if value:
        try:
            ms = float(value)
            if 0 < ms <= 3.6e6:  # cap at one hour; junk stays default
                budget = ms / 1e3
        except ValueError:
            pass
    return mint_deadline(surface, budget)


class _Scope:
    __slots__ = ("_dl_tok", "_exp_tok", "_lane_tok", "_shed_tok",
                 "_surface", "_lane", "_t0")

    def __init__(self, surface: str, dl: Optional[float],
                 lane_name: Optional[str], explicit: bool):
        self._surface = surface
        self._lane = lane_name
        self._dl_tok = _ctx_deadline.set(dl)
        self._exp_tok = _ctx_deadline_explicit.set(
            explicit and dl is not None)
        self._lane_tok = (_ctx_lane.set(lane_name)
                          if lane_name is not None else None)
        self._shed_tok = _ctx_was_shed.set(False)
        self._t0 = time.time()
        CONTROLLER.note_enter(lane_name or _ctx_lane.get())

    def __enter__(self) -> "_Scope":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        served = exc_type is None and not _ctx_was_shed.get()
        CONTROLLER.note_exit(self._lane or _ctx_lane.get(),
                             self._surface, time.time() - self._t0,
                             served=served)
        _ctx_deadline.reset(self._dl_tok)
        _ctx_deadline_explicit.reset(self._exp_tok)
        _ctx_was_shed.reset(self._shed_tok)
        if self._lane_tok is not None:
            _ctx_lane.reset(self._lane_tok)


def request_scope(surface: str, dl: Optional[float],
                  lane_name: Optional[str] = None,
                  explicit: bool = False) -> _Scope:
    """Ingress scope: binds the deadline (and optionally the LANE —
    ingresses that resolved a lane for the shed verdict pass it here
    too, so the per-lane in-flight/drain accounting sees the same lane
    the verdict used) into the context, counts the request in the
    lane's in-flight gauge and feeds the completion-rate EWMA the
    shedding verdict divides by. ``explicit`` marks a CLIENT-supplied
    budget (may extend infrastructure timeouts downstream; a
    server-minted default may not). The constructor performs the enter
    so ``with request_scope(...)`` brackets exactly the handling
    interval."""
    return _Scope(surface, dl, lane_name, explicit)


class _LaneScope:
    __slots__ = ("_lane", "_tok")

    def __init__(self, lane_name: str):
        self._lane = lane_name
        self._tok = None

    def __enter__(self) -> "_LaneScope":
        self._tok = _ctx_lane.set(self._lane)
        return self

    def __exit__(self, *exc) -> None:
        if self._tok is not None:
            _ctx_lane.reset(self._tok)
            self._tok = None


class _DeadlineScope:
    __slots__ = ("_dl", "_tok", "_exp_tok")

    def __init__(self, dl: Optional[float]):
        self._dl = dl
        self._tok = None
        self._exp_tok = None

    def __enter__(self) -> "_DeadlineScope":
        self._tok = _ctx_deadline.set(self._dl)
        # a programmatic scope IS an explicit budget
        self._exp_tok = _ctx_deadline_explicit.set(self._dl is not None)
        return self

    def __exit__(self, *exc) -> None:
        if self._tok is not None:
            _ctx_deadline.reset(self._tok)
            self._tok = None
        if self._exp_tok is not None:
            _ctx_deadline_explicit.reset(self._exp_tok)
            self._exp_tok = None


def deadline_scope(dl: Optional[float]) -> _DeadlineScope:
    """Bind an absolute deadline into the context without the ingress
    accounting — the broker binds a ring-carried deadline around a
    plane-side dispatch with this (the worker's ingress scope already
    counted the request). A programmatic scope counts as an EXPLICIT
    budget (it may extend infrastructure timeouts)."""
    return _DeadlineScope(dl)


def select_batch(pending: Sequence[Any], max_batch: int,
                 now: float) -> Tuple[List[Any], List[Any]]:
    """Choose up to ``max_batch`` items from ``pending`` (objects with
    ``.lane`` and ``.t_enq``) — the ONE seal policy shared by the
    MicroBatcher and BatchCoalescer (ISSUE 15):

    - FIFO within a lane; a single-lane backlog is a plain slice;
    - lanes seal in priority order (interactive > replay >
      background), with items older than the aging bound promoted to
      interactive rank so low lanes cannot starve outright;
    - when lanes compete for one batch, each present lane is
      guaranteed its WEIGHTED minimum share of the batch
      (``NORNICDB_LANE_WEIGHTS``, floor 1 slot) before the remainder
      fills in priority order — the weighted-queue contract, not just
      strict priority.

    Returns ``(batch, rest)``; ``rest`` preserves arrival order."""
    if len(pending) <= max_batch:
        return list(pending), []
    c = cfg()
    ranked: Dict[int, List[Any]] = {}
    for it in pending:
        ranked.setdefault(lane_rank(it.lane, now - it.t_enq),
                          []).append(it)
    if len(ranked) == 1:
        only = next(iter(ranked.values()))
        taken = set(map(id, only[:max_batch]))
        return (only[:max_batch],
                [it for it in pending if id(it) not in taken])
    weights = c["lane_weights"]
    present = sorted(ranked)
    total_w = sum(weights.get(LANES[min(r, len(LANES) - 1)], 1.0)
                  for r in present)
    batch: List[Any] = []
    # weighted minimum share first: every present lane lands at least
    # floor(max_batch * w / total_w) (>= 1) of its items
    for r in present:
        w = weights.get(LANES[min(r, len(LANES) - 1)], 1.0)
        share = max(1, int(max_batch * w / total_w))
        take = ranked[r][:share]
        del ranked[r][: len(take)]
        batch.extend(take)
    # remainder by priority order
    for r in present:
        if len(batch) >= max_batch:
            break
        take = ranked[r][: max_batch - len(batch)]
        batch.extend(take)
    batch = batch[:max_batch]
    taken = set(map(id, batch))
    return batch, [it for it in pending if id(it) not in taken]


def lane_scope(lane_name: str) -> _LaneScope:
    """Tag everything inside (one thread's work) with a priority lane —
    wrapped around every background maintenance worker body (index
    rebuilds, decay/inference sweeps, replica replay, shadow-audit
    replays) so any coalescer ride from that thread seals BEHIND
    interactive traffic."""
    return _LaneScope(lane_name)


# ---------------------------------------------------------------------------
# shed / deadline-miss recording (exactly-once ledger + journal)
# ---------------------------------------------------------------------------


def record_shed(surface: str, lane_name: str, reason: str,
                retry_after_s: float = 0.0) -> None:
    """One shed, recorded exactly once everywhere it must appear:
    ``nornicdb_shed_total``, one ``shed`` serve in the tier mix, ONE
    degrade-ledger record and ONE ``shed`` event-journal record — both
    trace-linked. Deliberately NOT via :func:`obs.audit.record_degrade`
    (which would journal a second, ``degrade``-kind event for the same
    query)."""
    if not _m.enabled():
        return
    try:
        _ctx_was_shed.set(True)
    except Exception:  # noqa: BLE001 — accounting only
        pass
    _SHED_C.labels(surface, lane_name, reason).inc()
    _audit.record_served(surface, _audit.TIER_SHED)
    tid = current_trace_id()
    rec: Dict[str, Any] = {
        "ts": round(time.time(), 6),
        "surface": surface,
        "from_tier": lane_name,
        "to_tier": _audit.TIER_SHED,
        "reason": reason,
        "index": "",
    }
    if tid:
        rec["trace_id"] = tid
    if retry_after_s:
        rec["retry_after_s"] = round(retry_after_s, 3)
    tenant = _tenant.current_tenant()
    if tenant:
        rec["tenant"] = tenant
    _tenant.record_shed(surface, reason)
    _audit.LEDGER.record(rec)
    _events.record_event("shed", surface=surface, reason=reason,
                         trace_id=tid,
                         detail={"lane": lane_name,
                                 "retry_after_s": round(retry_after_s,
                                                        3)})
    annotate(shed=reason)


def record_deadline_miss(surface: str, stage: str,
                         lane_name: Optional[str] = None) -> None:
    """A request failed fast past its budget: counted per stage that
    caught it (``ingress`` / ``queued`` / ``ring``) and recorded as a
    shed with reason ``deadline``."""
    if not _m.enabled():
        return
    _MISS_C.labels(surface, stage).inc()
    record_shed(surface, lane_name or _ctx_lane.get(), "deadline")


# ---------------------------------------------------------------------------
# the controller
# ---------------------------------------------------------------------------


class AdmissionController:
    """Per-lane in-flight accounting, completion-rate EWMAs, and the
    cached admission posture the per-request :meth:`check` reads.

    Everything on the request path is a couple of lock-striped integer
    updates plus one float compare against the cached posture; the
    posture itself recomputes at most once per ``interval_s`` (burn
    rates + thresholds), triggered lazily from whichever request
    crosses the cadence."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._inflight: Dict[str, int] = {ln: 0 for ln in LANES}
        # completion EWMA: done/sec per lane (the drain rate Retry-After
        # derives from)
        self._done: Dict[str, int] = {ln: 0 for ln in LANES}
        self._drain: Dict[str, float] = {ln: 0.0 for ln in LANES}
        # per-lane OBSERVED queue-wait EWMA (seconds), time-decayed.
        # This is measured wait — batcher coalesce_wait, the executor
        # hop, the broker ring post->dispatch interval — not a
        # Little's-law estimate: residence-time estimates conflate
        # service time with queueing (a closed-loop fleet of slow
        # requests would read as overload) and rate estimates over
        # bursty low traffic divide by idle time. Measured wait is
        # ~zero in both healthy shapes and explodes within tens of ms
        # at the open-loop knee.
        self._wait: Dict[str, float] = {ln: 0.0 for ln in LANES}
        self._wait_t: Dict[str, float] = {ln: 0.0 for ln in LANES}
        self._drain_t = time.time()
        self.posture = "admit"
        self.posture_since = time.time()
        self._next_eval = 0.0
        self.sheds = 0
        self._burn_fast = 0.0
        self._eff_max_wait = 0.05
        # fleet posture sharing (ISSUE 16): the publisher pushes the
        # LOCAL posture out (ring control block, metrics gauge); each
        # source returns a peer-observed (level, age_s). The effective
        # posture is max(local, freshest-remote) — hooks survive
        # reset() because they encode topology, not load state.
        self.posture_local = "admit"
        self.posture_source = "local"
        self._posture_publisher: Optional[Any] = None
        self._posture_sources: List[Any] = []

    def reset(self) -> None:
        with self._lock:
            self._inflight = {ln: 0 for ln in LANES}
            self._done = {ln: 0 for ln in LANES}
            self._drain = {ln: 0.0 for ln in LANES}
            self._wait = {ln: 0.0 for ln in LANES}
            self._wait_t = {ln: 0.0 for ln in LANES}
            self._drain_t = time.time()
            self.posture = "admit"
            self.posture_since = time.time()
            self._next_eval = 0.0
            self.sheds = 0
            self._burn_fast = 0.0
            self._eff_max_wait = cfg()["max_wait_s"]
            self.posture_local = "admit"
            self.posture_source = "local"
            # publisher/sources deliberately survive: topology wiring

    # -- fleet posture sharing (ISSUE 16) ------------------------------

    def set_posture_publisher(self, fn: Optional[Any]) -> None:
        """``fn(level:int)`` is called with the LOCAL posture level on
        every posture evaluation (never the fleet-merged one — a node
        must not echo a peer's overload back at the fleet)."""
        with self._lock:
            self._posture_publisher = fn

    def add_posture_source(self, fn: Any) -> None:
        """Register ``fn() -> (level:int, age_s:float) | None`` —
        a peer-observed posture (the broker-ring control word, the
        fleet aggregator's remote gauge sweep). Idempotent per
        callable identity."""
        with self._lock:
            if fn not in self._posture_sources:
                self._posture_sources.append(fn)

    def remove_posture_source(self, fn: Any) -> None:
        with self._lock:
            try:
                self._posture_sources.remove(fn)
            except ValueError:
                pass

    def clear_posture_publisher(self, fn: Any = None) -> None:
        """Drop the publisher — only if it is ``fn`` when one is given
        (a stopping ring endpoint must not unhook a replacement)."""
        with self._lock:
            if fn is None or self._posture_publisher == fn:
                self._posture_publisher = None

    def _merge_fleet_posture(self, local_level: int,
                             ttl_s: float) -> Tuple[int, str]:
        """(effective level, source tag): the max of the local verdict
        and every FRESH peer-published level. Failing sources
        contribute nothing — posture must never fail a request."""
        with self._lock:
            pub = self._posture_publisher
            sources = list(self._posture_sources)
        if pub is not None:
            try:
                pub(local_level)
            except Exception:  # noqa: BLE001 — publish is best-effort
                pass
        eff, src = local_level, "local"
        for fn in sources:
            try:
                res = fn()
            except Exception:  # noqa: BLE001 — a dead peer feed is not overload
                continue
            if not res:
                continue
            level, age = res
            if age <= ttl_s and int(level) > eff:
                eff, src = int(level), "fleet"
        return eff, src

    # -- accounting ----------------------------------------------------

    def note_enter(self, lane_name: str) -> None:
        with self._lock:
            self._inflight[lane_name] = \
                self._inflight.get(lane_name, 0) + 1

    def note_exit(self, lane_name: str, surface: str,
                  seconds: float, served: bool = True) -> None:
        with self._lock:
            n = self._inflight.get(lane_name, 0)
            self._inflight[lane_name] = n - 1 if n > 0 else 0
            if served:
                self._done[lane_name] = self._done.get(lane_name, 0) + 1

    def note_wait(self, lane_name: str, seconds: float,
                  now: Optional[float] = None) -> None:
        """One measured queue-wait observation (a batcher rider's
        coalesce wait, the gRPC executor hop, the broker ring
        post->dispatch interval). Folds into the lane's time-decayed
        EWMA — the signal the shedding verdict gates on."""
        if seconds <= 0.0:
            return
        now = time.time() if now is None else now
        with self._lock:
            v = self._decayed_wait_locked(lane_name, now)
            self._wait[lane_name] = (seconds if v <= 0.0
                                     else v * 0.8 + seconds * 0.2)
            self._wait_t[lane_name] = now

    def _decayed_wait_locked(self, lane_name: str, now: float) -> float:
        v = self._wait.get(lane_name, 0.0)
        if v <= 0.0:
            return 0.0
        dt = now - self._wait_t.get(lane_name, now)
        if dt <= 0.0:
            return v
        # halve per second of silence: a past burst cannot poison
        # admission once the queue has actually drained
        return v * (0.5 ** dt)

    def observed_wait(self, lane_name: str,
                      now: Optional[float] = None) -> float:
        now = time.time() if now is None else now
        with self._lock:
            return self._decayed_wait_locked(lane_name, now)

    def inflight(self, lane_name: str) -> int:
        with self._lock:
            return self._inflight.get(lane_name, 0)

    def drain_rate(self, lane_name: str) -> float:
        """Completions/sec EWMA for one lane (0.0 until warm)."""
        with self._lock:
            return self._drain.get(lane_name, 0.0)


    # -- posture -------------------------------------------------------

    def _roll_drain_locked(self, now: float) -> None:
        # caller holds the lock (refresh): fold the completion
        # counters into the EWMAs over the elapsed window. The window
        # clamps to 5s so an idle gap attributes its completions to
        # recent time instead of diluting them to ~zero.
        dt = now - self._drain_t
        if dt <= 0:
            return
        dt_eff = min(dt, 5.0)
        alpha = min(1.0, dt_eff / 2.0)  # ~2s time constant
        for ln in LANES:
            inst = self._done.get(ln, 0) / dt_eff
            prev = self._drain.get(ln, 0.0)
            self._drain[ln] = (prev * (1.0 - alpha) + inst * alpha
                               if prev > 0.0 else inst)
            self._done[ln] = 0
        self._drain_t = now

    def _burn_rate(self) -> float:
        """Worst fast-window burn rate across SLO objectives (0.0 when
        the engine has no complete data)."""
        try:
            from nornicdb_tpu.obs.slo import get_engine

            status = get_engine().status()
        except Exception:  # noqa: BLE001 — posture must not fail
            return 0.0
        worst = 0.0
        for doc in status.get("objectives", {}).values():
            wins = doc.get("windows") or []
            if not wins:
                continue
            fast = wins[0]
            br = fast.get("burn_rate")
            if br is not None and fast.get("total", 0) >= 30:
                worst = max(worst, float(br))
        return worst

    def refresh(self, now: Optional[float] = None,
                force: bool = False) -> str:
        """Recompute the posture if the evaluation cadence elapsed."""
        now = time.time() if now is None else now
        c = cfg()
        with self._lock:
            if not force and now < self._next_eval:
                return self.posture
            self._next_eval = now + c["interval_s"]
            self._roll_drain_locked(now)
            inflight = dict(self._inflight)
            drain = dict(self._drain)
        burn = self._burn_rate()
        it_in = inflight.get(LANE_INTERACTIVE, 0)
        est_wait = self.observed_wait(LANE_INTERACTIVE, now=now)
        # MEASURED QUEUE PRESSURE is the posture trigger (it reacts in
        # ms and is zero on an idle or merely-slow node); an SLO
        # burn-rate breach TIGHTENS the wait bound — a node already
        # torching its error budget gets less slack before it
        # degrades/sheds — but never flips the posture on its own (a
        # breach with no queue means the latency is in compute, and
        # shedding would not help it). The absolute in-flight cap is
        # the backstop for pathologies no wait observation survives.
        max_wait = c["max_wait_s"]
        if burn >= c["burn_shed"]:
            max_wait *= 0.5
        elif burn >= c["burn_degrade"]:
            max_wait *= 0.75
        posture = "admit"
        if est_wait > max_wait * 0.5 or it_in > c["max_queue"] // 2:
            posture = "degrade"
        if est_wait > max_wait or it_in > c["max_queue"]:
            posture = "shed"
        if est_wait > max_wait * 4 or it_in > c["max_queue"] * 2:
            posture = "shed_hard"
        # fleet merge (ISSUE 16): publish the local verdict, then let a
        # FRESH peer-published posture tighten (never loosen) it — the
        # whole fleet sheds together instead of funneling the load one
        # worker at a time into the overloaded one
        local_posture = posture
        eff_level, src = self._merge_fleet_posture(
            POSTURES.index(posture), c["fleet_posture_ttl_s"])
        posture = POSTURES[min(eff_level, len(POSTURES) - 1)]
        with self._lock:
            self._eff_max_wait = max_wait
            self._burn_fast = burn
            self.posture_local = local_posture
            self.posture_source = src
            if posture != self.posture:
                prev, self.posture = self.posture, posture
                self.posture_since = time.time()
            else:
                prev = None
        if prev is not None:
            _POSTURE_G.set(float(POSTURES.index(local_posture)))
            _events.record_event(
                "posture", reason=posture,
                detail={"from": prev, "source": src,
                        "burn_fast": round(burn, 2),
                        "interactive_inflight": it_in,
                        "est_wait_ms": (round(est_wait * 1e3, 1)
                                        if est_wait != float("inf")
                                        else None)})
        return posture

    def retry_after_s(self, lane_name: str) -> float:
        """Honest pushback interval from the lane's drain rate: the
        time the current backlog takes to drain, clamped to [1, 30]s."""
        with self._lock:
            inflight = self._inflight.get(lane_name, 0)
            drain = self._drain.get(lane_name, 0.0)
        if drain <= 0.0:
            return 2.0
        return min(30.0, max(1.0, inflight / drain))

    # -- the per-request verdict ---------------------------------------

    def check(self, surface: str, lane_name: Optional[str] = None,
              now: Optional[float] = None) -> None:
        """Admit or raise :class:`ShedError`. Cheap: reads the cached
        posture (recomputing at most once per interval across all
        callers) and compares the lane against it."""
        c = cfg()
        if not c["shed_enabled"]:
            return
        ln = lane_name if lane_name is not None else _ctx_lane.get()
        posture = self.refresh(now=now)
        if posture == "admit":
            return
        rank = _LANE_RANK.get(ln, 0)
        if posture == "degrade":
            shed = rank >= 2                            # background only
        elif posture == "shed":
            # replay+background shed outright; interactive sheds the
            # EXCESS — only while the live observed queue wait still
            # sits past the bound, so the admitted stream stays at
            # capacity (goodput ~= knee) with bounded p99
            shed = rank >= 1 or \
                self.observed_wait(LANE_INTERACTIVE) > self._eff_max_wait
        else:                                           # shed_hard
            shed = True
        if not shed:
            return
        with self._lock:
            self.sheds += 1
        ra = self.retry_after_s(ln)
        record_shed(surface, ln, "shed", retry_after_s=ra)
        raise ShedError(surface, ln, ra)

    def cost_check(self, surface: str, kind: str, bucket: int = 1,
                   lane_name: Optional[str] = None,
                   now: Optional[float] = None) -> Optional[float]:
        """Cost-aware admission (ISSUE 20): while posture >= degrade,
        shed a query whose CALIBRATED predicted dispatch milliseconds
        exceed its remaining deadline budget — up front, before it
        occupies a queue or device slot (reason ``admission_cost``,
        exactly-once ledger+journal via record_shed). Confidence-gated:
        obs/device.py abstains below its min-sample floor, and this
        gate then does nothing (queue-wait-only, never a guess).
        Returns the predicted ms when a confident model admitted the
        query, else None. Per-request hot path: cached config + one
        model-dict read, no env access."""
        c = cfg()
        if not c["shed_enabled"] or not c["cost_gate_enabled"]:
            return None
        t = time.time() if now is None else now
        rem = remaining(now=t)
        if rem is None:
            return None
        if self.refresh(now=t) == "admit":
            return None
        from nornicdb_tpu.obs import device as _device

        pred_ms = _device.predict_ms(kind, bucket)
        if pred_ms is None:
            return None
        if pred_ms <= max(rem, 0.0) * 1e3 * c["cost_gate_slack"]:
            return pred_ms
        ln = lane_name if lane_name is not None else _ctx_lane.get()
        with self._lock:
            self.sheds += 1
        ra = self.retry_after_s(ln)
        record_shed(surface, ln, "admission_cost", retry_after_s=ra)
        raise ShedError(surface, ln, ra, reason="admission_cost")

    # -- tier forcing (degrade-first actuation) ------------------------

    def tier_gate(self, tier: str) -> bool:
        """False while the posture is ``degrade`` or worse and ``tier``
        is an expensive device rung — registered with obs/audit.py so
        every existing ladder gate steps walk/quant/graph tiers down to
        brute/host (reason ``admission``), shrinking device pressure
        before any query is rejected."""
        if self.posture == "admit":
            return True
        if tier in (_audit.TIER_HOST, _audit.TIER_CACHED,
                    _audit.TIER_SHED):
            return True
        return tier.endswith("brute_f32")

    # -- the /admin/scheduler payload ----------------------------------

    def summary(self) -> Dict[str, Any]:
        c = cfg()
        now = time.time()
        with self._lock:
            inflight = dict(self._inflight)
            drain = dict(self._drain)
            waits = {ln: self._decayed_wait_locked(ln, now)
                     for ln in LANES}
        lanes: Dict[str, Any] = {}
        for ln in LANES:
            lanes[ln] = {
                "inflight": inflight.get(ln, 0),
                "drain_qps": round(drain.get(ln, 0.0), 1),
                "wait_ms": round(waits.get(ln, 0.0) * 1e3, 2),
                "weight": c["lane_weights"][ln],
            }
        misses = {}
        for (surface, stage), child in _MISS_C.children().items():
            if child.value:
                misses[f"{surface}:{stage}"] = child.value
        sheds = {}
        for key, child in _SHED_C.children().items():
            if child.value:
                sheds[":".join(key)] = child.value
        return {
            "posture": self.posture,
            "posture_local": self.posture_local,
            "posture_source": self.posture_source,
            "posture_since": round(self.posture_since, 3),
            "burn_fast": round(self._burn_fast, 3),
            "shed_enabled": c["shed_enabled"],
            "fleet": {
                "publisher": self._posture_publisher is not None,
                "sources": len(self._posture_sources),
                "ttl_s": c["fleet_posture_ttl_s"],
            },
            "lanes": lanes,
            "deadline": {
                "defaults_ms": {k: round(v * 1e3, 1)
                                for k, v in
                                c["deadline_defaults_s"].items()},
                "misses": misses,
            },
            "shed": {"total": sum(sheds.values()), "by": sheds},
            "limits": {
                "max_wait_ms": round(c["max_wait_s"] * 1e3, 1),
                "max_queue": c["max_queue"],
                "burn_degrade": c["burn_degrade"],
                "burn_shed": c["burn_shed"],
            },
        }


CONTROLLER = AdmissionController()


def check(surface: str, lane_name: Optional[str] = None) -> None:
    CONTROLLER.check(surface, lane_name)


def scheduler_summary() -> Dict[str, Any]:
    return CONTROLLER.summary()


def retry_after_s(lane_name: str = LANE_INTERACTIVE) -> float:
    return CONTROLLER.retry_after_s(lane_name)


def _collect() -> None:
    # scrape-time lane gauges (PR 5 collector discipline). The posture
    # gauge carries the LOCAL posture — it is the cross-node
    # propagation carrier (obs/fleet.py sweeps it off peer state
    # dumps), so publishing the fleet-merged value would echo a peer's
    # overload back at the fleet forever.
    with CONTROLLER._lock:
        for ln in LANES:
            _LANE_IN_G.labels(ln).set(
                float(CONTROLLER._inflight.get(ln, 0)))
        _POSTURE_G.set(float(POSTURES.index(CONTROLLER.posture_local)))


REGISTRY.add_collector(_collect)

# degrade-first actuation: the ladder gates in cagra/device_quant/
# hybrid_fused/device_graph consult obs.audit.tier_allowed +
# admission_allows; registering here makes the admission posture a
# first-class rung-forcing input beside the parity quarantine
_audit.set_admission_gate(CONTROLLER.tier_gate)

# the noisy-neighbor detector (obs/tenant.py) arms only while the
# posture is >= degrade — it reads the level through this provider so
# the tenant layer never imports the actuator
_tenant.set_posture_provider(
    lambda: POSTURES.index(CONTROLLER.posture))
