"""Micro-batching aggregation for concurrent single-query kNN.

SURVEY §7 names this the hard part of the TPU design: a single b=1
query cannot feed the MXU, so the device path only wins at batch — and
a serving workload is exactly many concurrent b=1 queries. This
coalescer turns them into device-sized batches (reference analog: the
strategy machine's batch thresholds, search.go:528-535; the reference
never needed the window because its per-query CPU/GPU dispatch is
cheap, while a device dispatch here costs ~100us+).

Design: adaptive leader election instead of a timed window. The first
idle request becomes the leader of the next batch and runs immediately
(ZERO added latency when the service is idle); requests arriving while
a batch is in flight queue up and are drained as ONE batched call by
the next leader. Under load the batch size self-tunes to the arrival
rate; there is no artificial sleep to tune.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, List, Sequence, Tuple

import numpy as np

from nornicdb_tpu.obs import (
    REGISTRY,
    SIZE_BUCKETS,
    attach_span,
    record_dispatch,
    record_stage,
)
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import device as _device
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu import admission as _adm
from nornicdb_tpu.ops.similarity import pow2_bucket

# one metric family set shared by every batcher instance (per-collection
# MicroBatchers, the search service's, the upsert coalescer): the
# registry is process-global and get-or-create is idempotent
_BATCH_H = REGISTRY.histogram(
    "nornicdb_microbatch_batch_size",
    "Coalesced queries per device dispatch", buckets=SIZE_BUCKETS)
_QUEUE_H = REGISTRY.histogram(
    "nornicdb_microbatch_queue_depth",
    "Requests still pending when a batch sealed", buckets=SIZE_BUCKETS)
_CONVOY_H = REGISTRY.histogram(
    "nornicdb_convoy_batch_size",
    "Coalesced items per merged apply (write convoys)",
    buckets=SIZE_BUCKETS)
# deadline-aware dispatch (ISSUE 15): batches sealed EARLY — the gather
# window skipped because a rider's remaining budget would expire inside
# it — dispatch smaller now instead of convoying toward a miss (pow2
# buckets absorb the size change: no new compile universe)
_EARLY_C = REGISTRY.counter(
    "nornicdb_deadline_early_dispatch_total",
    "Batches sealed early because a rider's deadline budget was tight",
    labels=("surface",))


def _expire_in_queue(owner, item, msg: str) -> bool:
    """Caller holds ``owner._cond``: fail one budget-expired item fast
    if it is still pending (not yet claimed by a leader). Shared by the
    MicroBatcher/BatchCoalescer wait loops (ISSUE 15)."""
    try:
        owner._pending.remove(item)
    except ValueError:
        return False  # claimed: it rides out the in-flight batch
    item.error = _adm.DeadlineExceeded(msg)
    item.done = True
    return True


def _seal_pending(owner, now: float, msg: str):
    """Caller holds ``owner._cond``: drop budget-expired items (failed
    fast, never dispatched) then select the next batch via the shared
    lane-priority/weighted-share policy (admission.select_batch). The
    ONE seal implementation both coalescers share (ISSUE 15)."""
    pending = owner._pending
    expired = [r for r in pending
               if r.deadline is not None and now >= r.deadline]
    if expired:
        dead = set(map(id, expired))
        pending = [r for r in pending if id(r) not in dead]
        owner._pending = pending
        for r in expired:
            r.error = _adm.DeadlineExceeded(msg)
            r.done = True
        owner._cond.notify_all()
    batch, rest = _adm.select_batch(pending, owner._max_batch, now)
    owner._pending = rest
    return batch


class BatchCoalescer:
    """Leader-elected coalescer for arbitrary batchable operations.

    The generalization of MicroBatcher's search-specific protocol to any
    op where N concurrent requests are cheaper served as one merged
    apply (gRPC point upserts: one merged ``upsert_points`` per
    collection means one lock acquisition, one index touch and ONE cache
    generation bump for the whole convoy instead of one per RPC).

    ``apply_batch(items) -> results`` must return one result per item;
    raising fails every waiter in the batch unless ``apply_single`` is
    given, in which case the coalescer falls back to per-item
    application so one poisoned item cannot fail its convoy-mates.
    """

    def __init__(self, apply_batch, apply_single=None, max_batch: int = 64,
                 surface: str = "convoy"):
        self._apply_batch = apply_batch
        self._apply_single = apply_single
        self._max_batch = max_batch
        # bounded stage-attribution label for
        # nornicdb_request_stage_seconds{surface,...} — code-chosen, one
        # value per coalescer role (never client-derived)
        self._surface = surface
        self._cond = threading.Condition()
        self._pending: List["_Item"] = []
        self._busy = False
        self.batches = 0
        self.batched_items = 0

    def queue_depth(self) -> int:
        """Live pending items (not yet claimed by a convoy leader) —
        same contract as MicroBatcher.queue_depth, so write convoys get
        the nornicdb_queue_depth gauge and the /readyz saturation check
        when registered with obs/resources."""
        with self._cond:
            return len(self._pending)

    def submit(self, value: Any) -> Any:
        t_enq = time.time()
        # admission context (ISSUE 15): convoy items carry the caller's
        # lane + deadline budget like MicroBatcher riders — an expired
        # item fails fast instead of riding a merged apply
        dl = _adm.deadline()
        lane = _adm.lane()
        if dl is not None and t_enq >= dl:
            _adm.record_deadline_miss(self._surface, "ingress", lane)
            raise _adm.DeadlineExceeded(
                f"deadline budget expired before enqueue "
                f"({self._surface})")
        item = _Item(value)
        item.deadline, item.lane, item.t_enq = dl, lane, t_enq
        item.tenant = _tenant.current_tenant()
        with self._cond:
            self._pending.append(item)
        while True:
            batch: List[_Item] = []
            with self._cond:
                while not item.done and self._busy:
                    timeout = 30.0
                    if item.deadline is not None:
                        timeout = min(
                            timeout,
                            max(item.deadline - time.time(), 0.0) + 1e-3)
                    self._cond.wait(timeout=timeout)
                    if (not item.done and item.deadline is not None
                            and time.time() >= item.deadline):
                        if _expire_in_queue(
                                self, item,
                                f"deadline budget expired in convoy "
                                f"queue ({self._surface})"):
                            break
                        continue  # claimed: ride out the convoy
                if item.done:
                    break
                batch = _seal_pending(
                    self, time.time(),
                    f"deadline budget expired in convoy queue "
                    f"({self._surface})")
                if not batch:
                    continue  # taken by another leader but not done yet
                self._busy = True
            try:
                self._run(batch)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()
            if item.done:
                break
        if item.apply_t1:
            # queue-delay attribution + trace spans: the wait from
            # enqueue to the leader sealing our convoy, and the shared
            # merged apply every convoy-mate experienced
            record_stage(self._surface, "coalesce_wait",
                         item.apply_t0 - t_enq)
            record_stage(self._surface, "apply",
                         item.apply_t1 - item.apply_t0)
            record_stage("lane:" + item.lane, "coalesce_wait",
                         item.apply_t0 - t_enq)
            _adm.CONTROLLER.note_wait(item.lane, item.apply_t0 - t_enq)
            attach_span("coalesce.wait", t_enq, item.apply_t0,
                        surface=self._surface, batch=item.batch_size,
                        lane=item.lane)
            attach_span("apply", item.apply_t0, item.apply_t1,
                        surface=self._surface, batch=item.batch_size)
        if isinstance(item.error, _adm.DeadlineExceeded) \
                and not item.apply_t1:
            _adm.record_deadline_miss(self._surface, "queued",
                                      item.lane)
            raise item.error
        if item.error is not None:
            raise item.error
        return item.result

    def _run(self, batch: List["_Item"]) -> None:
        self.batches += 1
        self.batched_items += len(batch)
        _CONVOY_H.observe(len(batch))
        t0 = time.time()
        for item in batch:
            item.apply_t0 = t0
            item.batch_size = len(batch)
        try:
            # the riders' tenant mix binds around the merged apply so
            # any cost/serve recorded inside splits per tenant (18)
            with _tenant.batch_scope([i.tenant for i in batch]):
                results = self._apply_batch([i.value for i in batch])
            for item, res in zip(batch, results):
                item.result = res
        except Exception as exc:  # noqa: BLE001 — delivered per-request
            if self._apply_single is None or len(batch) == 1:
                for item in batch:
                    item.error = exc
            else:
                # isolate the poison: apply per item so only the bad
                # request(s) observe the error
                for item in batch:
                    try:
                        with _tenant.batch_scope([item.tenant]):
                            item.result = self._apply_single(item.value)
                    except Exception as single_exc:  # noqa: BLE001
                        item.error = single_exc
        t1 = time.time()
        for item in batch:
            item.apply_t1 = t1
            item.done = True


class _Item:
    __slots__ = ("value", "done", "result", "error", "apply_t0",
                 "apply_t1", "batch_size", "lane", "deadline", "t_enq",
                 "tenant")

    def __init__(self, value: Any):
        self.value = value
        self.done = False
        self.result: Any = None
        self.error: Any = None
        # stamped by the convoy leader: the shared merged-apply interval
        self.apply_t0 = 0.0
        self.apply_t1 = 0.0
        self.batch_size = 0
        # admission context captured at enqueue (ISSUE 15)
        self.lane = _adm.LANE_INTERACTIVE
        self.deadline: "float | None" = None
        self.t_enq = 0.0
        # tenant captured at enqueue (ISSUE 18): the convoy leader
        # binds the batch's tenant mix so merged-apply cost splits
        self.tenant: "str | None" = None


class _Req:
    __slots__ = ("vec", "k", "extra", "done", "result", "error",
                 "dispatch_t0", "dispatch_t1", "batch_size", "tier",
                 "lane", "deadline", "t_enq", "early", "tenant",
                 "batch_attrs")

    def __init__(self, vec: np.ndarray, k: int, extra: Any = None):
        self.vec = vec
        self.k = k
        self.extra = extra
        self.done = False
        self.result: Any = None
        self.error: Any = None
        # stamped by the batch LEADER so every rider can graft the one
        # shared device-dispatch interval into its own trace
        self.dispatch_t0 = 0.0
        self.dispatch_t1 = 0.0
        self.batch_size = 0
        # serving-tier verdict of the batch that answered this request
        # (leader consumes the dispatch path's audit.note_batch_tier)
        self.tier: Any = None
        # admission context captured at enqueue (ISSUE 15): priority
        # lane + absolute deadline budget — leaders seal batches in
        # lane order and fail budget-expired riders fast
        self.lane = _adm.LANE_INTERACTIVE
        self.deadline: "float | None" = None
        self.t_enq = 0.0
        # the leader skipped the gather window because this rider's (or
        # a batch-mate's) budget was tight — annotated on the trace
        self.early = False
        # tenant captured at enqueue (ISSUE 18): the batch leader binds
        # the riders' mix so the padded-dispatch cost splits per tenant
        self.tenant: "str | None" = None
        # what the leader's ``batch_kind`` said of the batch's extras,
        # for this rider's ``device.dispatch`` span
        self.batch_attrs: dict = {}


def _plain_kind(extras: Sequence[Any]) -> Tuple[str, dict]:
    return "microbatch", {}


class MicroBatcher:
    """Coalesces concurrent ``search(vec, k)`` calls into
    ``search_batch(queries[B,D], k_max)`` calls.

    ``search_batch`` must return one result list per query row. Results
    for a request asking k smaller than the batch max are truncated."""

    def __init__(
        self,
        search_batch: Callable[[np.ndarray, int], List[List[Tuple[str, float]]]],
        max_batch: int = 64,
        gather_window_s: float = 0.0005,
        pass_extras: bool = False,
        truncate: bool = True,
        surface: str = "search",
        tier_surface: "str | None" = None,
        batch_kind: "Callable[[Sequence[Any]], Tuple[str, dict]] | None"
        = None,
    ):
        self._search_batch = search_batch
        # batch_kind(extras of the riders) -> (dispatch kind, span attrs):
        # a batcher whose riders' extras decide which program a batch
        # runs (the Qdrant surface: riders with and without filter bounds
        # seal together, and a batch with bounds runs the filtered scan)
        # records each batch under the kind of the program it ran, so
        # nornicdb_device_dispatch_* and admission's predict_ms keep the
        # programs apart. None: every batch is ``microbatch``.
        self._batch_kind = batch_kind or _plain_kind
        self._max_batch = max_batch
        # bounded stage-attribution label (code-chosen per batcher role:
        # "service:vector", "service:hybrid", "qdrant", ...) for the
        # nornicdb_request_stage_seconds{surface,stage} histograms
        self._surface = surface
        # tier-attribution surface ("vector", ...): when set, each rider
        # records nornicdb_served_tier_total/_seconds for the tier the
        # dispatch path noted (audit.note_batch_tier) — rider-accurate
        # counting without the batcher knowing the ladder. None = the
        # caller above this batcher does its own (per-row) attribution.
        self._tier_surface = tier_surface
        # pass_extras: dispatch as search_batch(queries, k, extras) with
        # one opaque per-request item (the hybrid path rides tokenized
        # query terms and per-request fusion options alongside the
        # stackable embedding rows). truncate=False leaves per-request
        # result shaping to the dispatch fn (hybrid rows are structured
        # triples, not plain hit lists).
        self._pass_extras = pass_extras
        self._truncate = truncate
        # when the PREVIOUS batch was concurrent, the next leader waits
        # up to this long for stragglers that are mid-return from that
        # batch — without it, mean batch size collapses to ~half the
        # client count. An idle service (last batch = 1) never waits.
        self._gather_window_s = gather_window_s
        self._last_batch = 1
        self._cond = threading.Condition()
        self._pending: List[_Req] = []
        self._busy = False
        # observability: how well the window is aggregating
        self.batches = 0
        self.batched_queries = 0

    @property
    def max_batch(self) -> int:
        """The most riders one sealed batch takes."""
        return self._max_batch

    def queue_depth(self) -> int:
        """Live pending requests (not yet claimed by a batch leader) —
        the saturation signal /readyz and the resource gauges read
        (the threshold itself lives with its env knob in
        http_server._readyz: depth >= READY_QUEUE_FACTOR x max_batch)."""
        with self._cond:
            return len(self._pending)

    def search(self, vec: Sequence[float], k: int,
               extra: Any = None) -> List[Tuple[str, float]]:
        t_enq = time.time()
        # admission context (ISSUE 15): the deadline budget minted at
        # ingress and the caller's priority lane ride the request —
        # a rider ALREADY past budget fails fast before it can occupy
        # a queue slot, let alone a device one
        dl = _adm.deadline()
        lane = _adm.lane()
        if dl is not None and t_enq >= dl:
            _adm.record_deadline_miss(self._surface, "ingress", lane)
            raise _adm.DeadlineExceeded(
                f"deadline budget expired before enqueue "
                f"({self._surface})")
        # cost-aware admission (ISSUE 20): at posture >= degrade, a
        # rider whose CALIBRATED predicted dispatch cost exceeds its
        # remaining budget sheds here (reason ``admission_cost``) —
        # before taking a queue slot it cannot convert into an answer.
        # Predicts at the bucket the next batch will likely compile to;
        # an unconfident model abstains and admission stays
        # queue-wait-only.
        if dl is not None:
            _adm.CONTROLLER.cost_check(
                self._surface, self._batch_kind([extra])[0],
                pow2_bucket(max(min(self._last_batch, self._max_batch),
                                1)),
                lane, now=t_enq)
        req = _Req(np.asarray(vec, np.float32), k, extra)
        req.deadline, req.lane, req.t_enq = dl, lane, t_enq
        req.tenant = _tenant.current_tenant()
        with self._cond:
            self._pending.append(req)
        while True:
            batch: List[_Req] = []
            with self._cond:
                while not req.done and self._busy:
                    timeout = 30.0
                    if req.deadline is not None:
                        timeout = min(
                            timeout,
                            max(req.deadline - time.time(), 0.0) + 1e-3)
                    self._cond.wait(timeout=timeout)
                    if (not req.done and req.deadline is not None
                            and time.time() >= req.deadline):
                        # budget expired while queued: leave the queue
                        # instead of riding (and padding) a dispatch
                        # whose answer nobody will read. A rider a
                        # leader already claimed is no longer in
                        # _pending — it rides out the in-flight batch.
                        if _expire_in_queue(
                                self, req,
                                f"deadline budget expired in queue "
                                f"({self._surface})"):
                            break
                        continue
                if req.done:
                    break
                if req.deadline is not None \
                        and time.time() >= req.deadline:
                    # would-be leader past budget: same fail-fast
                    if _expire_in_queue(
                            self, req,
                            f"deadline budget expired in queue "
                            f"({self._surface})"):
                        break
                    continue
                # leader candidate: if the service just served a
                # concurrent batch, give its returning clients one short
                # window to re-enqueue before sealing this batch —
                # UNLESS a pending rider's remaining budget would expire
                # inside the window: dispatch smaller NOW (the pow2
                # buckets absorb the size change)
                early = False
                if (self._gather_window_s > 0.0
                        and self._last_batch >= 2
                        and len(self._pending)
                        < min(self._last_batch, self._max_batch)):
                    if self._deadline_tight_locked():
                        early = True
                    else:
                        self._cond.wait(timeout=self._gather_window_s)
                        if req.done:
                            break
                        if self._busy:
                            continue  # another thread led while we waited
                # idle and our request unserved: lead the next batch
                batch = _seal_pending(
                    self, time.time(),
                    f"deadline budget expired in queue "
                    f"({self._surface})")
                if not batch:
                    # taken by another leader but not done yet — loop
                    continue
                if early:
                    _EARLY_C.labels(self._surface).inc()
                    for r in batch:
                        r.early = True
                _QUEUE_H.observe(len(self._pending))
                self._busy = True
            try:
                self._run(batch)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()
            if req.done:
                break
            # our request was queued behind this batch — go again
        if isinstance(req.error, _adm.DeadlineExceeded) \
                and not req.dispatch_t1:
            # failed fast without a dispatch: count the miss + one
            # ledger/journal shed record in THIS rider's own trace
            _adm.record_deadline_miss(self._surface, "queued", req.lane)
            raise req.error
        if req.error is not None:
            self._trace_req(req, t_enq)
            raise req.error
        self._trace_req(req, t_enq)
        return req.result

    def _deadline_tight_locked(self) -> bool:
        """Any pending rider whose remaining budget would not survive
        the gather window (with dispatch margin)? Caller holds _cond."""
        horizon = time.time() + 4.0 * self._gather_window_s
        return any(r.deadline is not None and r.deadline <= horizon
                   for r in self._pending)

    def _trace_req(self, req: "_Req", t_enq: float) -> None:
        """Graft this request's coalescing story into the active trace
        AND the per-stage latency histograms: the wait from enqueue to
        the (leader-stamped) device dispatch, the shared dispatch
        interval, and the post-dispatch merge. The histogram half runs
        even without an active trace — fleet-wide queue-delay
        attribution must not depend on tracing. No-op when the request
        errored before dispatch."""
        if not req.dispatch_t1:
            return
        t_done = time.time()
        record_stage(self._surface, "coalesce_wait",
                     req.dispatch_t0 - t_enq)
        record_stage(self._surface, "device_dispatch",
                     req.dispatch_t1 - req.dispatch_t0)
        record_stage(self._surface, "merge", t_done - req.dispatch_t1)
        # lane-keyed queue-wait mirror (ISSUE 15): the same coalesce
        # wait re-recorded under surface "lane:<lane>" so per-lane
        # queueing is one /admin/telemetry query (bounded: 3 lanes),
        # and fed to the admission controller as a MEASURED wait
        # observation — the signal the shedding verdict gates on
        record_stage("lane:" + req.lane, "coalesce_wait",
                     req.dispatch_t0 - t_enq)
        _adm.CONTROLLER.note_wait(req.lane, req.dispatch_t0 - t_enq)
        wait_attrs: dict = {"surface": self._surface,
                            "batch": req.batch_size, "lane": req.lane}
        disp_attrs: dict = {"surface": self._surface,
                            "batch": req.batch_size, "k": req.k,
                            **req.batch_attrs}
        if req.deadline is not None:
            # the budget at the dispatch decision (ISSUE 15 acceptance:
            # a trace shows the deadline at ingress, ring crossing and
            # dispatch) — remaining ms when the leader sealed us in
            disp_attrs["deadline_ms"] = round(
                (req.deadline - req.dispatch_t0) * 1e3, 1)
        if req.early:
            disp_attrs["early_dispatch"] = True
        attach_span("coalesce.wait", t_enq, req.dispatch_t0,
                    **wait_attrs)
        attach_span("device.dispatch", req.dispatch_t0, req.dispatch_t1,
                    **disp_attrs)
        attach_span("merge", req.dispatch_t1, t_done)
        # rider-accurate serving-tier attribution (ISSUE 10): the tier
        # the leader consumed from the dispatch path stamps THIS
        # rider's count/latency/span, and the stage split re-records
        # keyed by tier — which rung was slow, not just which surface
        if self._tier_surface is not None and req.tier is not None:
            _audit.record_served(self._tier_surface, req.tier,
                                 seconds=t_done - t_enq)
            _audit.record_tier_stages(
                req.tier, req.dispatch_t0 - t_enq,
                req.dispatch_t1 - req.dispatch_t0,
                t_done - req.dispatch_t1)
        # sampling call sites above the batcher read the verdict here
        _audit.set_last_served(req.tier)

    def _run(self, batch: List[_Req]) -> None:
        try:
            self.batches += 1
            self.batched_queries += len(batch)
            self._last_batch = len(batch)
            _BATCH_H.observe(len(batch))
            # k is usually a static jit arg too: bucket it alongside B
            k_max = pow2_bucket(max(r.k for r in batch))
            queries = np.stack([r.vec for r in batch])
            # pad the batch dim to a power-of-two bucket: every distinct
            # B is a fresh XLA compile on an accelerator backend, and
            # arrival-rate batches take nearly every size — observed on
            # an older chip run as 24 q/s.
            # Buckets cap the compile universe at log2(max_batch)
            # shapes; the pad rows repeat row 0 (no NaN paths) and their
            # results are dropped.
            b = len(batch)
            bucket = pow2_bucket(b)
            if bucket != b:
                pad = np.broadcast_to(
                    queries[0], (bucket - b,) + queries.shape[1:])
                queries = np.concatenate([queries, pad], axis=0)
            kind, attrs = self._batch_kind([r.extra for r in batch])
            t0 = time.time()
            _audit.consume_batch_tier()  # clear any stale leader note
            # bind the riders' tenant mix around the dispatch (18): the
            # padded program's cost splits across riders by tenant, and
            # (ISSUE 20) the dispatch scope credits inner-plane pricing
            # to this serving kind while the sampled bracket pins t1 to
            # device completion — the measured wall seconds then split
            # across the same rider mix
            with _tenant.batch_scope([r.tenant for r in batch]):
                with _device.dispatch_scope(kind):
                    # the inner plane prices the PADDED array; the
                    # padding-efficiency join needs the rider count
                    _device.note_real_rows(float(b))
                    if self._pass_extras:
                        # pad extras like the query rows: repeat
                        # request 0's
                        extras = [r.extra for r in batch]
                        extras += [batch[0].extra] * (bucket - b)
                        results = self._search_batch(queries, k_max,
                                                     extras)
                    else:
                        results = self._search_batch(queries, k_max)
                    _device.maybe_sync(results)
                    t1 = time.time()
                tier = _audit.consume_batch_tier()
                record_dispatch(kind, bucket, k_max, t1 - t0)
            for r, res in zip(batch, results):
                r.dispatch_t0, r.dispatch_t1 = t0, t1
                r.batch_size = b
                r.tier = tier
                r.batch_attrs = attrs
                if self._truncate:
                    r.result = res[: r.k] if r.k < k_max else res
                else:
                    r.result = res
        except Exception:  # noqa: BLE001
            # isolate the poison: one malformed request (wrong dims in
            # np.stack, bad k) must not fail its convoy-mates — replay
            # each request as its own single-row batch and deliver
            # errors only to the requests that actually own them
            for r in batch:
                if r.deadline is not None and time.time() >= r.deadline:
                    # the failed batch consumed this rider's budget:
                    # don't burn a b=1 device dispatch on an answer
                    # nobody will read
                    r.error = _adm.DeadlineExceeded(
                        f"deadline budget expired during replay "
                        f"({self._surface})")
                    continue
                try:
                    kb = pow2_bucket(max(r.k, 1))
                    kind, r.batch_attrs = self._batch_kind([r.extra])
                    r.dispatch_t0 = time.time()
                    q1 = np.asarray(r.vec, np.float32)[None, :]
                    _audit.consume_batch_tier()
                    with _tenant.batch_scope([r.tenant]):
                        with _device.dispatch_scope(kind):
                            if self._pass_extras:
                                res = self._search_batch(q1, kb,
                                                         [r.extra])[0]
                            else:
                                res = self._search_batch(q1, kb)[0]
                            _device.maybe_sync(res)
                            r.dispatch_t1 = time.time()
                        r.tier = _audit.consume_batch_tier()
                        r.batch_size = 1
                        record_dispatch(kind, 1, kb,
                                        r.dispatch_t1 - r.dispatch_t0)
                    if self._truncate:
                        r.result = res[: r.k] if r.k < kb else res
                    else:
                        r.result = res
                except Exception as exc:  # noqa: BLE001 — per-request
                    r.error = exc
        for r in batch:
            r.done = True
