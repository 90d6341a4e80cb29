"""Cross-worker dispatch broker: the MicroBatcher generalized over a
shared-memory ring (ISSUE 11).

One Python event loop cannot parse and serialize wire traffic fast
enough to feed the device plane (a pre-chip CPU run: the qdrant gRPC
surface kneed at 724 qps open-loop while the Go reference does ~29k ops/s on
the same contract, and PR 1's framework-floor calibration says we sit
at the ceiling of one loop). The architectural fix is N frontend
workers — separate processes parsing/serializing in parallel — funneled
into ONE shared device plane, because device throughput is won by
wider batches and more frontends posting concurrently produce exactly
that.

This module is the funnel. Layout:

- a ``multiprocessing.shared_memory`` segment holding a control block
  (shared write-generation mirrors for the wire caches) plus a ring of
  fixed-size request slots, partitioned per worker so every slot has
  ONE writer per protocol state: the owning worker writes
  ``FREE -> POSTED`` and ``DONE -> FREE``, the broker writes
  ``POSTED -> CLAIMED -> DONE`` — single-producer/single-consumer
  transitions, no cross-process lock anywhere on the request path;
- two op kinds: ``OP_VEC`` carries a RAW float32 embedding (no pickle
  on the hot payload) and is coalesced across workers into one batched
  ``search_batch`` device dispatch per group — the MicroBatcher's
  leader/rider protocol with the broker as the standing leader, so
  coalescing gets *better* with more frontends; ``OP_CALL`` carries a
  pickled generic operation executed on a parent-side target object
  (full-fidelity qdrant ``search_points``, upsert convoys, scroll
  pages, admin reads) on a pool whose concurrent execution coalesces
  in the existing MicroBatcher/BatchCoalescer machinery;
- doorbells are unix datagram sockets (worker -> broker on post,
  broker -> worker on completion) so neither side spins; both sides
  also poll slot state on a short timeout, so a lost datagram degrades
  to a few hundred microseconds of latency, never to a hang;
- per-rider serving-tier attribution and stage timing cross the
  process boundary in the response header/meta (the dispatch path's
  ``audit.note_batch_tier`` / ``audit.last_served`` verdicts and the
  leader-stamped t_claim/t0/t1), and OP_CALL responses carry the
  degrade-ledger records the op produced so the worker's
  ``/admin/degrades`` stays truthful;
- a rider whose broker died mid-dispatch times out
  (``NORNICDB_WIRE_TIMEOUT_S``) and surfaces an error — never a hang:
  the abandoned slot is tombstoned until the broker's DONE (if any)
  is observed, then reclaimed.

Responses larger than a slot's payload spill to a temp file next to
the doorbell sockets (marker in the header; reader unlinks) so a 10k-
point scroll page cannot wedge the ring.
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from nornicdb_tpu.obs import (
    REGISTRY,
    SIZE_BUCKETS,
    declare_kind,
    record_dispatch,
)
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import device as _device
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu.obs import tracing as _tracing
from nornicdb_tpu import admission as _adm
from nornicdb_tpu.search.microbatch import pow2_bucket

# pre-register the ring's dispatch kind so the compile-universe
# accounting reports 0 before first traffic (PR 6 discipline)
declare_kind("broker_vec")

# -- slot protocol ----------------------------------------------------------

ST_FREE, ST_POSTED, ST_CLAIMED, ST_DONE = 0, 1, 2, 3
OP_VEC, OP_CALL = 1, 2
# response delivery: inline payload bytes, or spilled to a file whose
# utf-8 path is the payload (responses bigger than one slot)
RESP_INLINE, RESP_SPILL = 0, 1

# slot header: state, op, ok, resp_kind, seq, req_len, resp_len, k,
# t_post, t_claim, t0, t1, batch, deadline  (packed little-endian).
# On a POSTED slot the resp_kind byte carries the rider's priority-LANE
# code (admission.LANE_CODES) — the response pack overwrites it — and
# ``deadline`` is the rider's absolute budget (0.0 = none), so both
# survive the worker -> plane hop without touching the payload
# (ISSUE 15).
_HDR = struct.Struct("<BBBBIIIIddddId")
_HDR_SIZE = 64  # header struct is exactly 64 bytes; slots align to 64
assert _HDR.size <= _HDR_SIZE

# control block: magic, n_workers, slots_per_worker, slot_bytes (u32 x4)
# then qdrant_gen (u64 @16), search_gen (u64 @24), broker_alive (u8 @32),
# admission posture level (u8 @40) + its write timestamp (f64 @48) —
# the fleet-wide posture word (ISSUE 16)
_CTRL = struct.Struct("<IIII")
_CTRL_SIZE = 64
_MAGIC = 0x4E57_4252  # "NWBR"
_OFF_QDRANT_GEN = 16
_OFF_SEARCH_GEN = 24
_OFF_ALIVE = 32
_OFF_POSTURE = 40
_OFF_POSTURE_TS = 48


def _read_posture_word(buf) -> Tuple[int, float]:
    """(posture level, write timestamp) from a ring control block. A
    torn read across the two fields is harmless — the posture word is
    advisory and self-heals within one publish cadence."""
    (ts,) = struct.unpack_from("<d", buf, _OFF_POSTURE_TS)
    return int(buf[_OFF_POSTURE]), float(ts)


def _write_posture_word(buf, level: int, ttl_s: float) -> bool:
    """Publish one process's LOCAL admission posture into the shared
    control block: write-if-more-severe-or-stale. A severe posture any
    ring member published sticks until it ages past ``ttl_s`` — a
    healthy worker cannot clear a peer's overload signal early, and a
    dead worker's stale signal cannot pin the fleet shed forever."""
    now = time.time()
    cur, ts = _read_posture_word(buf)
    if level >= cur or (now - ts) > ttl_s:
        struct.pack_into("<d", buf, _OFF_POSTURE_TS, now)
        buf[_OFF_POSTURE] = max(0, min(255, int(level)))
        return True
    return False

_BATCH_H = REGISTRY.histogram(
    "nornicdb_broker_batch_size",
    "Cross-worker riders coalesced per broker dispatch group",
    buckets=SIZE_BUCKETS)
_REQS_C = REGISTRY.counter(
    "nornicdb_broker_requests_total",
    "Requests brokered from wire workers to the shared device plane",
    labels=("op",))
_ERRS_C = REGISTRY.counter(
    "nornicdb_broker_errors_total",
    "Broker-path failures by kind (dispatch errors, spills, timeouts)",
    labels=("kind",))
_WORKERS_G = REGISTRY.gauge(
    "nornicdb_wire_workers",
    "Frontend workers configured on this node's wire plane")


def default_timeout_s() -> float:
    try:
        return float(os.environ.get("NORNICDB_WIRE_TIMEOUT_S", "15"))
    except ValueError:
        return 15.0


class BrokerTimeout(RuntimeError):
    """The shared device plane did not answer within the rider timeout
    (broker crashed, wedged, or saturated past the deadline). The wire
    layer maps this to an error response — never a hang."""


class BrokerRemoteError(RuntimeError):
    """A generic op raised in the device-plane process; carries the
    remote type name for error mapping at the wire layer."""

    def __init__(self, type_name: str, message: str, status: int = 400):
        super().__init__(message)
        self.type_name = type_name
        self.status = status


class _Layout:
    """Offset math shared by both sides of the ring."""

    def __init__(self, n_workers: int, slots: int, slot_bytes: int):
        self.n_workers = n_workers
        self.slots = slots
        self.slot_bytes = slot_bytes
        self.payload_bytes = slot_bytes - _HDR_SIZE
        self.total = _CTRL_SIZE + n_workers * slots * slot_bytes

    def slot_off(self, worker: int, slot: int) -> int:
        return _CTRL_SIZE + (worker * self.slots + slot) * self.slot_bytes


def _read_hdr(buf, off: int):
    return _HDR.unpack_from(buf, off)


def _mk_socket(path: str) -> socket.socket:
    s = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
    s.bind(path)
    return s


def _ring_doorbell(sock: socket.socket, path: str) -> None:
    try:
        sock.sendto(b"!", path)
    except OSError:
        # receiver gone or its buffer full — the poll timeout covers it
        pass


def _untrack_shm(shm) -> None:
    """Drop a SharedMemory segment from this process's resource
    tracker: the BROKER owns unlinking (its stop()), while attaching
    clients must never let their tracker reap the live ring when they
    exit (CPython registers attachments too — bpo-39959)."""
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:  # noqa: BLE001 — best-effort hygiene
        pass


# -- client (frontend worker side) ------------------------------------------


class BrokerClient:
    """Worker-side endpoint of the ring. Thread-safe within the worker:
    slot allocation is an in-process lock; the cross-process protocol
    itself is lock-free (single-writer state transitions)."""

    def __init__(self, spec: Dict[str, Any]):
        from multiprocessing import shared_memory

        self.worker_id = int(spec["worker_id"])
        self._shm = shared_memory.SharedMemory(name=spec["shm_name"])
        if spec.get("untrack_shm", spec.get("cross_process", True)):
            # attaching registers with THIS process's resource tracker
            # (CPython registers attachments too); a worker exiting
            # must not reap the live ring out from under its peers.
            # Thread mode keeps the single registration the creating
            # broker owns.
            _untrack_shm(self._shm)
        self._buf = self._shm.buf
        magic, n_workers, slots, slot_bytes = _CTRL.unpack_from(self._buf, 0)
        if magic != _MAGIC:
            raise RuntimeError("broker shm magic mismatch")
        self._layout = _Layout(n_workers, slots, slot_bytes)
        self.sock_dir = spec["sock_dir"]
        self._broker_path = os.path.join(self.sock_dir, "broker.sock")
        self._sock_path = os.path.join(
            self.sock_dir, f"worker{self.worker_id}.sock")
        if os.path.exists(self._sock_path):
            os.unlink(self._sock_path)
        self._sock = _mk_socket(self._sock_path)
        self._sock.settimeout(0.02)
        # whether the device plane lives in ANOTHER process: governs
        # degrade-record relay (in thread mode the ledger is already
        # shared, replaying would double-record)
        self.cross_process = bool(spec.get("cross_process", True))
        self.timeout_s = float(spec.get("timeout_s") or default_timeout_s())
        self._lock = threading.Lock()
        self._free = list(range(self._layout.slots))
        self._cond = threading.Condition(self._lock)
        # slots abandoned by a timed-out rider: unusable until the
        # broker's DONE is observed (it may still write into them)
        self._tombstoned: set = set()
        self._seq = 0

    # -- shared generation mirrors (wire-cache validation) ------------

    def qdrant_gen(self) -> int:
        return int.from_bytes(
            bytes(self._buf[_OFF_QDRANT_GEN:_OFF_QDRANT_GEN + 8]), "little")

    def search_gen(self) -> int:
        return int.from_bytes(
            bytes(self._buf[_OFF_SEARCH_GEN:_OFF_SEARCH_GEN + 8]), "little")

    def broker_alive(self) -> bool:
        return self._buf[_OFF_ALIVE] == 1

    # -- fleet posture word (ISSUE 16) ---------------------------------

    def ring_posture(self) -> Tuple[int, float]:
        """(posture level, age in seconds) of the shared posture word —
        the AdmissionController posture-source shape."""
        level, ts = _read_posture_word(self._buf)
        return level, max(0.0, time.time() - ts)

    def publish_posture(self, level: int,
                        ttl_s: Optional[float] = None) -> bool:
        if ttl_s is None:
            ttl_s = _adm.cfg()["fleet_posture_ttl_s"]
        return _write_posture_word(self._buf, level, ttl_s)

    def bind_admission(self) -> None:
        """Wire this process's AdmissionController to the ring posture
        word: every local posture evaluation publishes into the control
        block (write-if-more-severe-or-stale), and every refresh reads
        the word back as a fleet posture source — one overloaded wire
        worker tightens EVERY worker's admission verdict within a
        publish cadence."""
        _adm.CONTROLLER.set_posture_publisher(self.publish_posture)
        _adm.CONTROLLER.add_posture_source(self.ring_posture)

    def unbind_admission(self) -> None:
        _adm.CONTROLLER.clear_posture_publisher(self.publish_posture)
        _adm.CONTROLLER.remove_posture_source(self.ring_posture)

    # -- slot lifecycle ------------------------------------------------

    def _acquire_slot(self, deadline: float) -> int:
        with self._cond:
            while True:
                # lazily reclaim tombstones whose DONE has landed
                if self._tombstoned:
                    reclaimed = []
                    for s in self._tombstoned:
                        off = self._layout.slot_off(self.worker_id, s)
                        if self._buf[off] == ST_DONE:
                            self._buf[off] = ST_FREE
                            reclaimed.append(s)
                    for s in reclaimed:
                        self._tombstoned.discard(s)
                        self._free.append(s)
                if self._free:
                    return self._free.pop()
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise BrokerTimeout(
                        "no free broker slots within timeout "
                        f"(worker {self.worker_id})")
                self._cond.wait(timeout=min(remaining, 0.05))

    def _release_slot(self, slot: int) -> None:
        with self._cond:
            self._free.append(slot)
            self._cond.notify()

    def _post(self, slot: int, op: int, payload: bytes, k: int = 0,
              deadline: float = 0.0, lane_code: int = 0) -> int:
        lay = self._layout
        if len(payload) > lay.payload_bytes:
            raise ValueError(
                f"request payload {len(payload)}B exceeds slot capacity "
                f"{lay.payload_bytes}B (raise NORNICDB_WIRE_SLOT_BYTES)")
        off = lay.slot_off(self.worker_id, slot)
        self._seq += 1
        seq = self._seq & 0xFFFFFFFF
        self._buf[off + _HDR_SIZE:off + _HDR_SIZE + len(payload)] = payload
        # resp_kind byte carries the LANE code on a posted slot; the
        # trailing double carries the rider's absolute deadline budget
        # (0.0 = none) — the plane sheds expired riders at claim and
        # binds the budget around the dispatch (ISSUE 15)
        _HDR.pack_into(self._buf, off, ST_FREE, op, 0, lane_code, seq,
                       len(payload), 0, k, time.time(), 0.0, 0.0, 0.0,
                       0, deadline)
        # publish LAST: the state byte flips ownership to the broker
        self._buf[off] = ST_POSTED
        _ring_doorbell(self._sock, self._broker_path)
        return seq

    def _await(self, slot: int, seq: int, deadline: float) -> Tuple:
        off = self._layout.slot_off(self.worker_id, slot)
        while True:
            if self._buf[off] == ST_DONE:
                hdr = _read_hdr(self._buf, off)
                if hdr[4] == seq:
                    return hdr
                # stale DONE from an abandoned predecessor: reclaim the
                # race by treating it as still-pending
            if time.time() >= deadline:
                with self._cond:
                    self._tombstoned.add(slot)
                _ERRS_C.labels("rider_timeout").inc()
                raise BrokerTimeout(
                    "device plane did not answer within the rider "
                    "deadline (op abandoned, slot tombstoned)")
            try:
                self._sock.recv(64)
            except socket.timeout:
                pass
            except OSError:
                time.sleep(0.001)

    def _response(self, slot: int, hdr) -> Any:
        lay = self._layout
        off = lay.slot_off(self.worker_id, slot)
        _state, _op, ok, resp_kind, _seq, _rl, resp_len, _k = hdr[:8]
        raw = bytes(self._buf[off + _HDR_SIZE:off + _HDR_SIZE + resp_len])
        if resp_kind == RESP_SPILL:
            path = raw.decode("utf-8")
            try:
                with open(path, "rb") as f:
                    raw = f.read()
            finally:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        doc = pickle.loads(raw)
        self._buf[off] = ST_FREE
        if not ok:
            type_name, msg, status = doc
            raise BrokerRemoteError(type_name, msg, status)
        return doc

    # -- public ops ----------------------------------------------------

    def vec_search(self, key: str, vec: np.ndarray, k: int,
                   timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Raw-embedding coalesced search: one rider of a cross-worker
        batched device dispatch. Returns ``{"hits", "tier", "t_claim",
        "t0", "t1", "batch", "t_post"}`` plus plane-side ``spans`` when
        the rider posted under an active trace (ISSUE 13): the slot
        carries a compact trace context behind the key, the plane's
        child spans ride the response back, and the worker grafts them
        so the ingress trace shows the full chain."""
        vec = np.ascontiguousarray(vec, dtype=np.float32)
        kb = key.encode("utf-8")
        tb = _tracing.pack_context(_tracing.trace_context()) \
            .encode("utf-8")
        payload = (struct.pack("<HHI", len(kb), len(tb), vec.shape[0])
                   + kb + tb + vec.tobytes())
        return self._roundtrip(OP_VEC, payload, k, timeout_s)

    def _await_deadline(self, timeout_s: Optional[float],
                        now: float) -> Tuple[float, Optional[float]]:
        """(rider await deadline, request deadline or None). The rider
        timeout consults the REQUEST deadline when one is in context —
        a generous CLIENT budget is not truncated to the flat
        ``NORNICDB_WIRE_TIMEOUT_S`` and a tight one is not held open
        past its own expiry (ISSUE 15; closes the PR 11 headroom
        note). Only an EXPLICIT budget (gRPC deadline, the deadline
        header, a programmatic scope) may extend the flat timeout: a
        server-minted surface default (30s http) must not double the
        dead-plane detection latency, so defaults clamp to the flat
        knob while still failing the rider fast if they are tighter.
        An explicit ``timeout_s`` argument still wins (internal
        callers: readiness probes, admin ops)."""
        req_dl = _adm.deadline()
        if timeout_s is not None:
            return now + timeout_s, req_dl
        if req_dl is not None:
            if _adm.deadline_explicit():
                return req_dl, req_dl
            return min(req_dl, now + self.timeout_s), req_dl
        return now + self.timeout_s, req_dl

    def call(self, target: str, method: str, *args,
             timeout_s: Optional[float] = None, **kwargs) -> Dict[str, Any]:
        """Generic op on a device-plane target. Returns ``{"result",
        "meta", timing...}``; remote exceptions re-raise as
        :class:`BrokerRemoteError`. The active trace context rides the
        pickled tuple, so the plane executes the op under a PROPAGATED
        trace — degrade records minted over there carry this rider's
        trace id, and the plane-side span tree comes back in
        ``meta["spans"]``."""
        ctx = _tracing.trace_context()
        if ctx is None:
            # no active trace (worker HTTP frontends don't root one)
            # — the tenant identity still crosses the ring so the
            # plane-side serve attributes to the rider, not
            # __unattributed__ (ISSUE 18)
            t = _tenant.current_tenant()
            if t:
                ctx = {"tenant": t}
        payload = pickle.dumps(
            (target, method, args, kwargs, ctx),
            protocol=5)
        return self._roundtrip(OP_CALL, payload, 0, timeout_s)

    def _roundtrip(self, op: int, payload: bytes, k: int,
                   timeout_s: Optional[float]) -> Dict[str, Any]:
        now = time.time()
        deadline, req_dl = self._await_deadline(timeout_s, now)
        if req_dl is not None and now >= req_dl:
            # budget already spent: never post a slot the plane would
            # claim, dispatch and answer into the void
            lane_name = _adm.lane()
            _adm.record_deadline_miss("broker", "ring", lane_name)
            raise _adm.DeadlineExceeded(
                "deadline budget expired before ring post")
        slot = self._acquire_slot(deadline)
        try:
            seq = self._post(slot, op, payload, k=k,
                             deadline=req_dl or 0.0,
                             lane_code=_adm.LANE_CODES.get(
                                 _adm.lane(), 0))
            hdr = self._await(slot, seq, deadline)
            doc = self._response(slot, hdr)
        except BrokerTimeout:
            raise  # slot tombstoned by _await; never reused raw
        except BaseException:
            # remote error or local parse failure AFTER the broker
            # finished with the slot: safe to recycle
            self._release_slot(slot)
            raise
        self._release_slot(slot)
        doc.update({"t_post": hdr[8], "t_claim": hdr[9],
                    "t0": hdr[10], "t1": hdr[11], "batch": hdr[12]})
        return doc

    def close(self) -> None:
        self.unbind_admission()
        try:
            self._sock.close()
        finally:
            try:
                os.unlink(self._sock_path)
            except OSError:
                pass
            try:
                self._shm.close()
            except Exception:  # noqa: BLE001
                pass


# -- broker (device-plane side) ---------------------------------------------


class DispatchBroker:
    """Parent-side scan/claim/dispatch engine over the ring.

    ``vec_dispatch(key, queries[B, D], k) -> per-row hit lists`` is the
    batched device entry (the same contract as MicroBatcher's
    ``search_batch``); ``targets`` maps OP_CALL target names to live
    objects whose (dotted) methods generic ops invoke. Dispatches run
    on a thread pool, so concurrent OP_CALLs coalesce in the existing
    MicroBatcher/BatchCoalescer machinery below, while OP_VEC groups
    are batched HERE — one ``search_batch`` per group per round, with
    a per-key busy gate so riders arriving mid-dispatch queue for the
    next round exactly like MicroBatcher riders."""

    def __init__(self, vec_dispatch: Callable[[str, np.ndarray, int], List],
                 targets: Dict[str, Any], n_workers: int,
                 slots: Optional[int] = None,
                 slot_bytes: Optional[int] = None,
                 pool_workers: int = 8, max_batch: int = 64,
                 gather_window_s: float = 0.0005):
        from concurrent import futures
        from multiprocessing import shared_memory

        def _env_int(name: str, default: int) -> int:
            try:
                return int(os.environ.get(name, str(default)))
            except ValueError:
                return default

        slots = slots or _env_int("NORNICDB_WIRE_SLOTS", 64)
        slot_bytes = slot_bytes or _env_int("NORNICDB_WIRE_SLOT_BYTES",
                                            256 * 1024)
        self._vec_dispatch = vec_dispatch
        self._targets = dict(targets)
        self._layout = _Layout(n_workers, slots, slot_bytes)
        self._max_batch = max_batch
        self._gather_window_s = gather_window_s
        self._shm = shared_memory.SharedMemory(
            create=True, size=self._layout.total,
            name=f"nornic_wire_{uuid.uuid4().hex[:12]}")
        self._buf = self._shm.buf
        self._buf[:self._layout.total] = b"\x00" * self._layout.total
        _CTRL.pack_into(self._buf, 0, _MAGIC, n_workers, slots, slot_bytes)
        self.sock_dir = tempfile.mkdtemp(prefix="nornic-wire-")
        self._sock_path = os.path.join(self.sock_dir, "broker.sock")
        self._sock = _mk_socket(self._sock_path)
        self._sock.settimeout(0.002)
        self._wake = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
        self._pool = futures.ThreadPoolExecutor(
            max_workers=pool_workers, thread_name_prefix="broker-dispatch")
        self._run = False
        self._thread: Optional[threading.Thread] = None
        self._vec_busy: Dict[str, bool] = {}
        self._busy_lock = threading.Lock()
        self._last_round = 1
        _WORKERS_G.set(float(n_workers))

    # -- shared generation mirrors -------------------------------------

    def set_qdrant_gen(self, gen: int) -> None:
        self._buf[_OFF_QDRANT_GEN:_OFF_QDRANT_GEN + 8] = \
            int(gen).to_bytes(8, "little")

    def set_search_gen(self, gen: int) -> None:
        self._buf[_OFF_SEARCH_GEN:_OFF_SEARCH_GEN + 8] = \
            int(gen).to_bytes(8, "little")

    # -- fleet posture word (ISSUE 16) ---------------------------------

    def ring_posture(self) -> Tuple[int, float]:
        """(posture level, age seconds) — see BrokerClient.ring_posture."""
        level, ts = _read_posture_word(self._buf)
        return level, max(0.0, time.time() - ts)

    def publish_posture(self, level: int,
                        ttl_s: Optional[float] = None) -> bool:
        if ttl_s is None:
            ttl_s = _adm.cfg()["fleet_posture_ttl_s"]
        return _write_posture_word(self._buf, level, ttl_s)

    def bind_admission(self) -> None:
        """Parent-side mirror of BrokerClient.bind_admission: the device
        plane's controller publishes/consumes the same posture word as
        the wire workers."""
        _adm.CONTROLLER.set_posture_publisher(self.publish_posture)
        _adm.CONTROLLER.add_posture_source(self.ring_posture)

    def unbind_admission(self) -> None:
        _adm.CONTROLLER.clear_posture_publisher(self.publish_posture)
        _adm.CONTROLLER.remove_posture_source(self.ring_posture)

    # -- lifecycle -----------------------------------------------------

    def client_spec(self, worker_id: int,
                    cross_process: bool = True) -> Dict[str, Any]:
        """Picklable attach spec handed to one frontend worker."""
        return {"shm_name": self._shm.name, "sock_dir": self.sock_dir,
                "worker_id": int(worker_id),
                "cross_process": bool(cross_process)}

    def start(self) -> "DispatchBroker":
        self._run = True
        self._buf[_OFF_ALIVE] = 1
        self._thread = threading.Thread(
            target=self._loop, name="wire-broker", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._run = False
        self.unbind_admission()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        try:
            self._buf[_OFF_ALIVE] = 0
        except (ValueError, TypeError):
            pass  # shm already unlinked under us
        self._pool.shutdown(wait=False)
        try:
            self._sock.close()
            os.unlink(self._sock_path)
        except OSError:
            pass
        try:
            self._wake.close()
        except OSError:
            pass
        try:
            self._shm.close()
            self._shm.unlink()  # unlink also unregisters from the tracker
        except Exception:  # noqa: BLE001
            pass

    def queue_depth(self) -> int:
        """POSTED-but-unclaimed riders across every worker — registered
        with obs/resources as queue "broker" so the shared
        nornicdb_queue_depth gauge and the /readyz saturation check
        cover the cross-worker ring like any MicroBatcher."""
        lay = self._layout
        n = 0
        for w in range(lay.n_workers):
            for s in range(lay.slots):
                if self._buf[lay.slot_off(w, s)] == ST_POSTED:
                    n += 1
        return n

    # -- scan/claim/dispatch loop --------------------------------------

    def _scan_posted(self) -> List[Tuple[int, int]]:
        lay = self._layout
        out = []
        for w in range(lay.n_workers):
            for s in range(lay.slots):
                if self._buf[lay.slot_off(w, s)] == ST_POSTED:
                    out.append((w, s))
        return out

    def _loop(self) -> None:
        while self._run:
            try:
                self._sock.recv(64)
            except socket.timeout:
                pass
            except OSError:
                if not self._run:
                    return
            try:
                self._round()
            except Exception:  # noqa: BLE001 — the loop must survive
                _ERRS_C.labels("round_error").inc()

    def _round(self) -> None:
        posted = self._scan_posted()
        if not posted:
            return
        # MicroBatcher-style gather window: after a concurrent round,
        # give stragglers (clients mid-return from the last batch) one
        # short window to re-post before sealing this round's groups
        if (self._gather_window_s > 0.0 and self._last_round >= 2
                and len(posted) < min(self._last_round, self._max_batch)):
            time.sleep(self._gather_window_s)
            posted = self._scan_posted()
        lay = self._layout
        vec_groups: Dict[str, List[Tuple[int, int, dict]]] = {}
        calls: List[Tuple[int, int, dict]] = []
        now = time.time()
        claimed = 0
        for w, s in posted:
            off = lay.slot_off(w, s)
            hdr = _read_hdr(self._buf, off)
            op, req_len, k = hdr[1], hdr[5], hdr[7]
            if op == OP_VEC:
                head = struct.unpack_from("<HHI", self._buf,
                                          off + _HDR_SIZE)
                key_len, ctx_len, dims = head
                base = off + _HDR_SIZE + 8
                key = bytes(self._buf[base:base + key_len]
                            ).decode("utf-8")
                ctx = None
                if ctx_len:
                    ctx = _tracing.unpack_context(bytes(
                        self._buf[base + key_len:
                                  base + key_len + ctx_len]
                    ).decode("utf-8", errors="replace"))
                with self._busy_lock:
                    if self._vec_busy.get(key):
                        # leader/rider: a dispatch for this key is in
                        # flight — the rider stays POSTED and joins the
                        # NEXT batch, which drains every waiter at once
                        continue
                group = vec_groups.setdefault(key, [])
                if len(group) >= self._max_batch:
                    # group sealed at max_batch: the overflow rider
                    # stays POSTED (unclaimed) and rides the next
                    # round — claiming it here would orphan the slot
                    continue
                item = {"off": off, "k": k, "dims": dims,
                        "vec_off": base + key_len + ctx_len,
                        "t_post": hdr[8], "worker": w, "ctx": ctx,
                        # ring-carried admission context (ISSUE 15):
                        # the rider's absolute budget and lane survive
                        # the worker -> plane hop in the slot header
                        "deadline": hdr[13] or None,
                        "lane": _adm.LANE_FROM_CODE.get(
                            hdr[3], _adm.LANE_INTERACTIVE)}
                group.append((w, s, item))
            else:
                req = bytes(self._buf[off + _HDR_SIZE:
                                      off + _HDR_SIZE + req_len])
                calls.append((w, s, {"off": off, "req": req,
                                     "t_post": hdr[8], "worker": w,
                                     "deadline": hdr[13] or None,
                                     "lane": _adm.LANE_FROM_CODE.get(
                                         hdr[3],
                                         _adm.LANE_INTERACTIVE)}))
            self._buf[off] = ST_CLAIMED
            claimed += 1
        self._last_round = max(claimed, 1)
        for key, group in vec_groups.items():
            with self._busy_lock:
                self._vec_busy[key] = True
            _REQS_C.labels("vec").inc(len(group))
            self._pool.submit(self._run_vec_group, key, group, now)
        for w, s, item in calls:
            _REQS_C.labels("call").inc()
            self._pool.submit(self._run_call, w, s, item, now)

    # -- dispatch bodies -----------------------------------------------

    def _respond(self, off: int, hdr, ok: int, doc: Any,
                 t_claim: float, t0: float, t1: float, batch: int,
                 worker: int) -> None:
        lay = self._layout
        raw = pickle.dumps(doc, protocol=5)
        resp_kind = RESP_INLINE
        if len(raw) > lay.payload_bytes:
            # spill: the ring carries a path, the file carries the data
            path = os.path.join(
                self.sock_dir, f"spill-{uuid.uuid4().hex[:16]}.bin")
            with open(path, "wb") as f:
                f.write(raw)
            raw = path.encode("utf-8")
            resp_kind = RESP_SPILL
            _ERRS_C.labels("spill").inc()
        self._buf[off + _HDR_SIZE:off + _HDR_SIZE + len(raw)] = raw
        _HDR.pack_into(self._buf, off, ST_CLAIMED, hdr[1], ok, resp_kind,
                       hdr[4], hdr[5], len(raw), hdr[7],
                       hdr[8], t_claim, t0, t1, batch, 0)
        self._buf[off] = ST_DONE
        _ring_doorbell(
            self._wake, os.path.join(self.sock_dir, f"worker{worker}.sock"))

    def _shed_expired(self, item: dict, t_claim: float) -> None:
        """Respond to a rider whose budget expired before the plane
        could dispatch it: an explicit DeadlineExceeded (the worker
        maps it onto its surface's honest error), recorded under the
        rider's PROPAGATED trace so the ledger/journal shed record
        carries the originating trace id (ISSUE 15)."""
        hdr = _read_hdr(self._buf, item["off"])

        def _record():
            # the rider's propagated tenant binds the shed verdict
            # (ISSUE 18): the per-tenant shed/served counters on the
            # shared plane attribute to the flooder, not __other__
            with _tenant.scope_from_context(item.get("ctx")):
                _adm.record_deadline_miss("broker", "ring", item["lane"])

        if item.get("ctx"):
            with _tracing.propagated_trace("broker.shed", item["ctx"],
                                           surface="broker"):
                _record()
        else:
            _record()
        now = time.time()
        self._respond(item["off"], hdr, 0,
                      ("DeadlineExceeded",
                       "deadline budget expired on the ring", 504),
                      t_claim, now, now, 1, item["worker"])

    def _run_vec_group(self, key: str,
                       group: List[Tuple[int, int, dict]],
                       t_claim: float) -> None:
        try:
            now = time.time()
            live = []
            for w, s, item in group:
                if item.get("deadline") and now >= item["deadline"]:
                    self._shed_expired(item, t_claim)
                else:
                    live.append((w, s, item))
            group = live
            if not group:
                return
            b = len(group)
            _BATCH_H.observe(b)
            # zero-copy gather off the ring: each rider's embedding is
            # viewed in place; a dims mismatch fails the stack and
            # drops to the per-rider poison-isolation replay below
            rows = [np.frombuffer(self._buf, dtype=np.float32,
                                  count=item["dims"],
                                  offset=item["vec_off"])
                    for _w, _s, item in group]
            queries = np.stack(rows)
            k_max = pow2_bucket(max(max(item["k"] for _w, _s, item
                                        in group), 1))
            bucket = pow2_bucket(b)
            if bucket != b:
                pad = np.broadcast_to(queries[0],
                                      (bucket - b,) + queries.shape[1:])
                queries = np.concatenate([queries, pad], axis=0)
            t0 = time.time()
            _audit.consume_batch_tier()
            _audit.consume_fleet_node()
            # the LEADER's trace context (first rider that carried one)
            # binds the plane-side dispatch: degrade records and spans
            # minted inside join the leader's trace — the MicroBatcher
            # precedent (the leader's dispatch story is the batch's)
            lead_ctx = next((item["ctx"] for _w, _s, item in group
                             if item.get("ctx")), None)
            # ring-carried admission context binds the dispatch: the
            # group's tightest budget and best lane govern any nested
            # coalescing below the plane entry (ISSUE 15)
            dls = [item["deadline"] for _w, _s, item in group
                   if item.get("deadline")]
            group_dl = min(dls) if dls else None
            group_lane = min(
                (item["lane"] for _w, _s, item in group),
                key=lambda ln: _adm.lane_rank(ln))
            # the riders' tenant mix (propagated in each slot's packed
            # trace ctx) binds the dispatch AND the serve recording:
            # padded-dispatch cost splits across riders by tenant and
            # the n=b serve distributes the same way (ISSUE 18)
            rider_tenants = [(item.get("ctx") or {}).get("tenant")
                             for _w, _s, item in group]
            with _tenant.batch_scope(rider_tenants):
                # ISSUE 20: cost priced below this seam credits the
                # broker_vec serving kind, and the sampled bracket pins
                # t1 to device completion before record_dispatch
                with _adm.deadline_scope(group_dl), \
                        _adm.lane_scope(group_lane), \
                        _device.dispatch_scope("broker_vec"):
                    # the plane prices the PADDED batch; the padding-
                    # efficiency join needs the real rider count
                    _device.note_real_rows(float(b))
                    if lead_ctx is not None:
                        attrs = {"key": key, "batch": b,
                                 "surface": "broker", "lane": group_lane}
                        if group_dl is not None:
                            attrs["deadline_ms"] = round(
                                (group_dl - t0) * 1e3, 1)
                        with _tracing.propagated_trace(
                                "broker.vec", lead_ctx, **attrs):
                            results = self._vec_dispatch(key, queries,
                                                         k_max)
                    else:
                        results = self._vec_dispatch(key, queries, k_max)
                    _device.maybe_sync(results)
                t1 = time.time()
                tier = _audit.consume_batch_tier()
                # fleet-routed reads stamp the chosen node (ISSUE 13):
                # the FleetRouter notes which replica served this
                # thread's dispatch; the stamp rides every response
                node = _audit.consume_fleet_node()
                record_dispatch("broker_vec", bucket, k_max, t1 - t0)
                # rider-accurate tier attribution (ISSUE 10) for the
                # ring path: the direct batched dispatch bypasses a
                # MicroBatcher so the broker, as the standing leader,
                # records one serve per rider on the shared plane —
                # each worker's merged scrape then carries the tier
                # mix exactly once
                _audit.record_served("vector", tier or "host", n=b)
            for idx, (_w, _s, item) in enumerate(group):
                hdr = _read_hdr(self._buf, item["off"])
                hits = results[idx]
                k = item["k"]
                doc = {"hits": list(hits[:k] if k < k_max else hits),
                       "tier": tier}
                if node:
                    doc["node"] = node
                if item.get("ctx"):
                    doc["spans"] = _vec_span_docs(
                        item["t_post"], t_claim, t0, t1, b, tier, node,
                        deadline=item.get("deadline"),
                        lane=item.get("lane"))
                self._respond(item["off"], hdr, 1, doc, t_claim, t0, t1,
                              b, item["worker"])
        except Exception as exc:  # noqa: BLE001 — poison isolation
            _ERRS_C.labels("vec_dispatch").inc()
            # replay each rider alone so only the poisoned request
            # observes its error (MicroBatcher discipline)
            for _w, _s, item in group:
                if item.get("deadline") \
                        and time.time() >= item["deadline"]:
                    # the failed batch consumed this rider's budget
                    self._shed_expired(item, t_claim)
                    continue
                hdr = _read_hdr(self._buf, item["off"])
                try:
                    q1 = np.frombuffer(
                        self._buf, dtype=np.float32, count=item["dims"],
                        offset=item["vec_off"]).reshape(1, -1)
                    kb = pow2_bucket(max(item["k"], 1))
                    t0 = time.time()
                    _audit.consume_batch_tier()
                    _audit.consume_fleet_node()
                    with _tenant.batch_scope(
                            [(item.get("ctx") or {}).get("tenant")]):
                        if item.get("ctx") is not None:
                            with _tracing.propagated_trace(
                                    "broker.vec", item["ctx"], key=key,
                                    batch=1, surface="broker"):
                                res = self._vec_dispatch(
                                    key, np.array(q1), kb)[0]
                        else:
                            res = self._vec_dispatch(key, np.array(q1),
                                                     kb)[0]
                        t1 = time.time()
                        tier = _audit.consume_batch_tier()
                        node = _audit.consume_fleet_node()
                        _audit.record_served("vector", tier or "host")
                    doc = {"hits": list(res[:item["k"]]), "tier": tier}
                    if node:
                        doc["node"] = node
                    if item.get("ctx"):
                        doc["spans"] = _vec_span_docs(
                            item["t_post"], t_claim, t0, t1, 1, tier,
                            node, deadline=item.get("deadline"),
                            lane=item.get("lane"))
                    self._respond(item["off"], hdr, 1, doc, t_claim,
                                  t0, t1, 1, item["worker"])
                except Exception as single:  # noqa: BLE001
                    self._respond(
                        item["off"], hdr, 0,
                        _remote_error_doc(single), t_claim,
                        time.time(), time.time(), 1, item["worker"])
            del exc
        finally:
            with self._busy_lock:
                self._vec_busy[key] = False

    def _run_call(self, w: int, s: int, item: dict,
                  t_claim: float) -> None:
        off = item["off"]
        hdr = _read_hdr(self._buf, off)
        try:
            req = pickle.loads(item["req"])
            target_name, method, args, kwargs = req[:4]
            ctx = req[4] if len(req) > 4 else None
            if item.get("deadline") and time.time() >= item["deadline"]:
                # rider budget spent before the op could run (ISSUE 15)
                item.setdefault("ctx", ctx)
                self._shed_expired(item, t_claim)
                return
            obj = self._targets[target_name]
            fn = obj
            for part in method.split("."):
                fn = getattr(fn, part)
            t0 = time.time()
            _audit.set_last_served(None)
            pspan = None
            with _audit.collect_degrades() as degrades, \
                    _adm.deadline_scope(item.get("deadline")), \
                    _adm.lane_scope(item.get("lane")
                                    or _adm.LANE_INTERACTIVE), \
                    _tenant.scope_from_context(ctx):
                # the ring-carried admission context binds the op: a
                # nested MicroBatcher/convoy ride below inherits the
                # rider's budget and lane (ISSUE 15)
                if ctx is not None and ctx.get("trace_id"):
                    # PROPAGATED trace (ISSUE 13): the op executes
                    # under the rider's trace id, so degrade records
                    # minted here carry it across the boundary, and
                    # plane-side child spans export back in meta. A
                    # tenant-only ctx (untraced rider) binds the scope
                    # above but must NOT mint spans — untraced in,
                    # untraced out
                    attrs = {"target": target_name, "op": method,
                             "surface": "broker"}
                    if item.get("deadline"):
                        attrs["deadline_ms"] = round(
                            (item["deadline"] - t0) * 1e3, 1)
                    with _tracing.propagated_trace(
                            "plane.call", ctx, **attrs) as pspan:
                        result = fn(*args, **kwargs)
                else:
                    result = fn(*args, **kwargs)
            t1 = time.time()
            meta = {"tier": _audit.last_served(),
                    "degrades": list(degrades)}
            if isinstance(pspan, _tracing.Span):
                # telemetry disabled plane-side returns a _NullSpan —
                # serve untraced rather than fail the op on export
                meta["spans"] = [_tracing.export_span(pspan)]
            self._respond(off, hdr, 1, {"result": result, "meta": meta},
                          t_claim, t0, t1, 1, item["worker"])
        except Exception as exc:  # noqa: BLE001 — delivered per-request
            _ERRS_C.labels("call_error").inc()
            self._respond(off, hdr, 0, _remote_error_doc(exc), t_claim,
                          time.time(), time.time(), 1, item["worker"])


def _vec_span_docs(t_post: float, t_claim: float, t0: float, t1: float,
                   batch: int, tier: Optional[str],
                   node: Optional[str],
                   deadline: Optional[float] = None,
                   lane: Optional[str] = None) -> List[Dict[str, Any]]:
    """Plane-side span records for ONE OP_VEC rider — the exported
    tree the worker grafts into its live trace so `/admin/traces` on
    the ingress worker shows the full wire -> ring -> coalesce ->
    device.dispatch chain with original timing. The ring.claim span
    carries the rider's remaining budget AT the ring crossing and the
    dispatch span its remaining budget AT the dispatch decision
    (ISSUE 15 acceptance: the deadline is visible at every hop)."""
    claim_attrs: Dict[str, Any] = {"surface": "broker"}
    dispatch_attrs: Dict[str, Any] = {"surface": "broker",
                                      "batch": batch,
                                      "kind": "broker_vec"}
    if lane:
        claim_attrs["lane"] = lane
    if deadline:
        claim_attrs["deadline_ms"] = round((deadline - t_post) * 1e3, 1)
        dispatch_attrs["deadline_ms"] = round((deadline - t0) * 1e3, 1)
    if tier:
        dispatch_attrs["tier"] = tier
    if node:
        dispatch_attrs["fleet_node"] = node
    return [
        {"name": "ring.claim", "t0": t_post, "t1": t_claim,
         "attrs": claim_attrs, "children": []},
        {"name": "plane.coalesce", "t0": t_claim, "t1": t0,
         "attrs": {"surface": "broker"}, "children": []},
        {"name": "device.dispatch", "t0": t0, "t1": t1,
         "attrs": dispatch_attrs, "children": []},
    ]


def _remote_error_doc(exc: Exception) -> Tuple[str, str, int]:
    return (type(exc).__name__, str(exc),
            int(getattr(exc, "status", 400) or 400))
