"""Brute-force exact kNN index with a device-resident matrix.

The TPU analog of the reference's GPUEmbeddingIndex
(pkg/gpu/accelerator.go:290-843 Add/Sync/Search): a host NumPy mirror is
the source of truth, written only under the index lock; a capacity-padded
[C,D] normalized matrix lives in device HBM and is queried with one MXU
matmul + top-k (nornicdb_tpu.ops.similarity). Growth re-pads to the next
power-of-two capacity so jit never sees a new shape per insert.

The device copy is UPDATED, not replaced: a write notes the slots it
touched, and the next reader applies them to the resident arrays with one
jitted, donated scatter (``index_update``) before it scans. Only a change
of capacity (growth, compaction, a load) ships the whole matrix. Because
an update donates the arrays, nobody may hold them across a release of
the index lock: a reader captures them, DISPATCHES the program that reads
them and only then lets the lock go (``_Lease``); the device runs
programs in the order they were dispatched, so a scan dispatched before
an update reads the old contents.
"""

from __future__ import annotations

import functools
import json
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.obs import cost as _cost
from nornicdb_tpu.obs.dispatch import declare_kind, record_dispatch
from nornicdb_tpu.obs.metrics import REGISTRY
from nornicdb_tpu.obs.tracing import span as _span
from nornicdb_tpu.ops.similarity import (
    CHUNKED_THRESHOLD,
    COL_HI,
    COL_LO,
    COL_MISSING,
    COL_UNCODED,
    cosine_topk,
    cosine_topk_auto,
    cosine_topk_chunked,
    cosine_topk_filtered,
    l2_normalize,
    pad_dim,
    pow2_bucket,
)


# how a reader of the slot-to-id table came by it: the table every reader
# since the last write that moved an id shares (reused), that table with
# only the touched chunks rebuilt (extended), or a whole new one (copied)
_IDS_SNAPSHOT_C = REGISTRY.counter(
    "nornicdb_index_ids_snapshot_total",
    "Slot-to-id tables handed to index readers, by whether the last "
    "one was shared, had its touched chunks rebuilt, or was rebuilt whole",
    labels=("result",))
_REFRESH_C = REGISTRY.counter(
    "nornicdb_index_refresh_total",
    "Refreshes of the index's device copy, by whether pending rows were "
    "written into the resident arrays or the whole matrix was shipped",
    labels=("kind",))
_SHIP_BYTES_C = REGISTRY.counter(
    "nornicdb_index_device_ship_bytes_total",
    "Bytes sent from the host to the index's device copy",
    labels=("kind",))

# the update program under its own kind in nornicdb_device_dispatch_*:
# b = the bucket its rows were padded to, k = 1; the seconds are the
# host's (call to return: the program runs behind the scans dispatched
# before it, and nobody waits for it)
KIND_UPDATE = "index_update"
declare_kind(KIND_UPDATE)

# the filtered scan (a batch with at least one rider that carries bounds)
# under its own kind: admission's predict_ms does not mix it with
# ``microbatch``, whose batches run the plain program
KIND_FILTERED = "vector_filtered"
declare_kind(KIND_FILTERED)

# a collection indexes at most this many payload fields: the column stack
# is [F, capacity] with F the power-of-two bucket of their number (1, 2, 4)
MAX_COLUMNS = 4
COLUMN_SCHEMAS = ("integer", "keyword")

# pending rows are applied in rounds padded to the smallest bucket that
# holds them, so a (capacity, dims) has three update programs whatever
# the writers do; more than the largest takes several rounds
UPDATE_BUCKETS = (16, 128, 1024)

# slots of the id table are grouped so that a write which moves an id
# rebuilds one group and not the table
IDS_CHUNK = 4096


def _update_bucket(n: int) -> int:
    return next(b for b in UPDATE_BUCKETS if b >= n)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def index_update(matrix, valid, slots, rows, vals):
    """``matrix[slots] = rows`` and ``valid[slots] = vals`` in the arrays
    they are given (both donated: the caller's references are dead when
    this returns). ``slots`` may repeat a slot, with the same row."""
    return matrix.at[slots].set(rows), valid.at[slots].set(vals)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def index_update_columns(matrix, valid, columns, slots, rows, vals, codes):
    """``index_update`` for an index with payload columns: the rows'
    codes ``[F, n]`` go into ``columns[:, slots]`` in the same program, so
    a reader never sees a row of one write beside the codes of another."""
    return (matrix.at[slots].set(rows), valid.at[slots].set(vals),
            columns.at[:, slots].set(codes))


class StaleFilterPlan(RuntimeError):
    """Bounds were planned against a set of payload columns the index no
    longer has (an index on a field was created or dropped in between)."""


def open_bounds(*shape: int) -> np.ndarray:
    """Bounds ``[*shape, 2]`` that let every code through."""
    bounds = np.empty(shape + (2,), np.int32)
    bounds[..., 0], bounds[..., 1] = COL_MISSING, COL_HI
    return bounds


def payload_value(payload, key: str):
    """``(found, value)`` of a dotted ``key`` in a payload, walked as the
    host filter walks it (``api/qdrant._match_condition``)."""
    value = payload
    for part in str(key).split("."):
        if isinstance(value, dict) and part in value:
            value = value[part]
        else:
            return False, None
    return True, value


def _ship(host: np.ndarray):
    """A device copy of ``host`` that no later write to ``host`` can
    reach, there before this returns. On the CPU backend ``device_put``
    aliases a 64-byte-aligned buffer instead of copying it (ROADMAP
    D10(d)); there the copy is made first."""
    if jax.default_backend() == "cpu":
        host = host.copy()
    return jax.block_until_ready(jax.device_put(host))


class IdTable:
    """Slot -> external id for one generation of an index, read-only: a
    tuple of tuples of ``IDS_CHUNK`` ids. A reader that captured one
    keeps resolving slots to the ids they held then, whatever is freed
    and reused afterwards; successive tables share every chunk no write
    touched."""

    __slots__ = ("chunks",)

    def __init__(self, chunks: Tuple[Tuple[Optional[str], ...], ...]):
        self.chunks = chunks

    def __getitem__(self, slot: int) -> Optional[str]:
        return self.chunks[slot // IDS_CHUNK][slot % IDS_CHUNK]


class _Lease:
    """The index lock, held from the capture of the device arrays to the
    dispatch of the program that reads them and released by hand there
    (``release`` is idempotent; leaving the ``with`` releases too)."""

    __slots__ = ("_lock", "_held", "view")

    def __init__(self, lock) -> None:
        self._lock = lock
        self._held = False
        self.view = None

    def acquire(self) -> None:
        self._lock.acquire()
        self._held = True

    def release(self) -> None:
        if self._held:
            self._held = False
            self._lock.release()

    def __enter__(self) -> "_Lease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


# the fewest rows ``add_matrix`` takes as one block: 2.7 us a row against
# ``add``'s 6.1 at 64 rows of 1,024 floats, 6.7 against 5.8 at 8 (the
# block's fixed cost; CPU host, PR 28)
BULK_MIN_ROWS = 64


def _use_pallas() -> bool:
    """Opt-in fused Pallas top-k (NORNICDB_PALLAS_TOPK=1). The kernel
    compiles on a v5e and matches cosine_topk (chip_smoke.py phase 5).
    At 8,192 x 1,024 it measured slower than the XLA matmul+top_k,
    1.12-1.47 ms against 0.91-1.21 ms for B = 8..256
    (scripts/bringup_probe.py, PR 21), so it stays off by default."""
    import os

    return os.environ.get("NORNICDB_PALLAS_TOPK", "0") == "1"


class BruteForceIndex:
    """Exact cosine kNN over (id -> vector). Thread-safe."""

    def __init__(
        self,
        dims: Optional[int] = None,
        use_device: bool = True,
        compact_min_dead: int = 1024,
        compact_dead_frac: float = 0.5,
    ):
        self.dims = dims
        self.use_device = use_device
        # compaction policy: once dead (tombstoned) slots exceed BOTH
        # the absolute floor and the fraction of used slots, live rows
        # are re-packed and capacity re-padded — long-lived collections
        # with churn stop scanning (and shipping to HBM) garbage rows
        self.compact_min_dead = compact_min_dead
        self.compact_dead_frac = compact_dead_frac
        self._lock = threading.RLock()
        self._capacity = 0
        self._count = 0  # high-water mark of used slots
        self._matrix: Optional[np.ndarray] = None  # [cap, D] normalized f32
        self._valid: Optional[np.ndarray] = None  # [cap] bool
        self._ext_ids: List[Optional[str]] = []
        self._slot_of: Dict[str, int] = {}
        self._free: List[int] = []  # recycled slots (deletes)
        self._n_alive = 0
        # write-generation counter: bumped on every add/remove/compact.
        # Derived indexes (search/cagra.py graphs) key their staleness
        # off it instead of subscribing to individual mutations.
        self.mutations = 0
        self.compactions = 0
        # changelog of (mutation seq, ext_id) for adds/updates — derived
        # indexes exact-score these between rebuilds (read-your-writes).
        # Length-capped; _changelog_floor marks how far back it reaches.
        self._changelog: List[Tuple[int, str]] = []
        self._changelog_floor = 0
        # the device copy, and what the host mirror has that it lacks:
        # slots written since the last refresh (noted only while a copy
        # exists; without one the first reader ships everything)
        self._dev_matrix = None
        self._dev_valid = None
        self._pending: set = set()
        # payload columns (``declare_column``): for each indexed field one
        # int32 code a slot, slot-aligned with the matrix, written under
        # the index lock with the row and carried to the device by the
        # same refresh. Row f of the stack is ``_col_fields[f]``; the stack
        # has pow2_bucket(len(fields)) rows, the spare ones all MISSING.
        # A field serves filters once it is ready (filled) and while no
        # live row holds a value its column cannot code (``_col_uncoded``).
        self._col_fields: List[str] = []
        self._col_schema: Dict[str, str] = {}
        self._col_dict: Dict[str, Dict[str, int]] = {}
        self._col_uncoded: Dict[str, int] = {}
        self._col_ready: set = set()
        self._col_gen = 0  # bumped when the set of fields changes
        self._columns: Optional[np.ndarray] = None  # [F, cap] int32
        self._dev_columns = None
        # the slot-to-id table readers share (_ids_snapshot_locked) and
        # the chunks of it in which a write has moved an id since
        self._ids_table: Optional[IdTable] = None
        self._ids_moved: set = set()
        # quantized serving plane (search/device_quant.py), created
        # lazily when NORNICDB_VECTOR_QUANT != off and the corpus
        # clears the quant floor — HBM then holds int8/PQ codes while
        # this host matrix stays the float32 source of truth
        self._quant = None
        # tiered serving plane (search/tiered_store.py), created lazily
        # when NORNICDB_VECTOR_TIERED is on and the corpus clears the
        # tiered floor — HBM then holds PQ slabs for the RESIDENT
        # partitions only; cold partitions spill to disk and this host
        # matrix serves exact reranks + cold side-scans
        self._tiered = None

    def __len__(self) -> int:
        return self._n_alive

    def __contains__(self, ext_id: str) -> bool:
        with self._lock:
            return ext_id in self._slot_of

    def contains_many(self, ext_ids) -> set:
        """Live members of ``ext_ids`` under ONE lock hold — bulk
        membership for decode-path filters (per-id ``in`` would take
        the lock once per candidate and convoy with writers)."""
        with self._lock:
            return {e for e in ext_ids if e in self._slot_of}

    def ids(self) -> List[str]:
        """Live external ids under one lock hold — the maintenance
        sweep (SearchService.prune_missing, replica bulk-delete replay)
        reconciles these against storage."""
        with self._lock:
            return list(self._slot_of.keys())

    @staticmethod
    def _normalize(v: np.ndarray) -> np.ndarray:
        n = np.linalg.norm(v)
        return v / n if n > 1e-12 else v

    def _ensure_capacity_locked(self, needed: int, dims: int) -> None:
        if self.dims is None:
            self.dims = dims
        if dims != self.dims:
            raise ValueError(f"dims mismatch: index={self.dims}, vector={dims}")
        if needed <= self._capacity:
            return
        new_cap = pad_dim(needed)
        new_m = np.zeros((new_cap, self.dims), dtype=np.float32)
        new_v = np.zeros((new_cap,), dtype=bool)
        if self._matrix is not None:
            new_m[: self._capacity] = self._matrix
            new_v[: self._capacity] = self._valid
        if self._columns is not None:
            new_c = np.full((self._columns.shape[0], new_cap), COL_MISSING,
                            np.int32)
            new_c[:, : self._capacity] = self._columns
            self._columns = new_c
        self._matrix = new_m
        self._valid = new_v
        self._ext_ids.extend([None] * (new_cap - len(self._ext_ids)))
        self._capacity = new_cap
        self._drop_device_locked()

    # -- mutation ---------------------------------------------------------

    def add(self, ext_id: str, vector: Sequence[float],
            payload: Optional[Dict] = None) -> None:
        """Insert or overwrite one row. ``payload`` is what the indexed
        fields' codes are taken from (an index with payload columns; a
        row written without one has none of the fields)."""
        v = np.asarray(vector, dtype=np.float32)
        with self._lock:
            if ext_id in self._slot_of:
                slot = self._slot_of[ext_id]
                self._matrix[slot] = self._normalize(v)
                self._code_slot_locked(slot, payload)
                self._wrote_locked(slot)
                self.mutations += 1
                self._log_change_locked(ext_id)
                return
            self._ensure_capacity_locked(self._count + (0 if self._free else 1), v.shape[0])
            if self._free:
                slot = self._free.pop()
            else:
                slot = self._count
                self._count += 1
            self._matrix[slot] = self._normalize(v)
            self._valid[slot] = True
            self._ext_ids[slot] = ext_id
            self._slot_of[ext_id] = slot
            self._n_alive += 1
            self._code_slot_locked(slot, payload)
            self._wrote_locked(slot, id_moved=True)
            self.mutations += 1
            self._log_change_locked(ext_id)

    def _wrote_locked(self, slot: int, id_moved: bool = False) -> None:
        """A write changed ``slot`` of the host mirror (and, with
        ``id_moved``, whose slot it is)."""
        if self._dev_matrix is not None:
            self._pending.add(slot)
        if id_moved:
            self._ids_moved.add(slot // IDS_CHUNK)

    def _drop_device_locked(self) -> None:
        """The slot space changed (growth, compaction): the device copy
        and the id table are of another shape. The next reader ships the
        matrix whole."""
        self._dev_matrix = None
        self._dev_valid = None
        self._dev_columns = None
        self._pending = set()
        self._ids_table = None
        self._ids_moved = set()

    def _log_change_locked(self, ext_id: str) -> None:
        self._changelog.append((self.mutations, ext_id))
        # cap well above any derived index's rebuild threshold (10% of
        # corpus churn) so changed_since() can always reach a live
        # build marker; beyond the cap the floor advances and consumers
        # fall back to a full rebuild/exact path
        self._trim_changelog_locked()

    def _trim_changelog_locked(self) -> None:
        cut = len(self._changelog) - self.changelog_cap()
        if cut > 0:
            self._changelog_floor = self._changelog[cut - 1][0]
            del self._changelog[:cut]

    def changelog_cap(self) -> int:
        """Current changelog length cap (what _trim_changelog_locked
        cuts to) — the accounting layer reports depth vs cap
        so near-overrun is visible before the device paths degrade."""
        return max(4096, self._capacity // 4)

    def resource_stats(self) -> Dict[str, float]:
        """Memory + freshness accounting for obs/resources.py: the
        device/host footprint of the matrix and its mirrors, tombstone
        pressure, and changelog depth vs cap. One short lock hold."""
        with self._lock:
            dims = self.dims or 0
            matrix_b = self._capacity * dims * 4  # float32
            valid_b = self._capacity  # bool
            dev = self._dev_matrix
            dev_b = 0
            if dev is not None:
                dev_b = int(getattr(dev, "nbytes", 0)) + int(
                    getattr(self._dev_valid, "nbytes", 0) or 0)
            # the payload columns' device copy, counted in device_bytes
            # too so that the memory ledger reconciles
            col_b = int(getattr(self._dev_columns, "nbytes", 0) or 0)
            dev_b += col_b
            used = max(self._count, 1)
            quant = self._quant
            tiered = self._tiered
            stats = {
                "rows": self._n_alive,
                "capacity": self._capacity,
                "device_bytes": dev_b,
                "payload_index_bytes": col_b,
                # host mirror + the ext-id slot table (pointer-sized
                # slots; string payloads are shared with callers)
                "host_bytes": matrix_b + valid_b + 8 * len(self._ext_ids)
                + (self._columns.nbytes if self._columns is not None else 0),
                "dead_fraction": round(
                    (self._count - self._n_alive) / used, 6),
                "changelog_depth": len(self._changelog),
                "changelog_cap": self.changelog_cap(),
                "mutations": self.mutations,
            }
        if quant is not None:
            # outside the index lock: the plane takes no brute locks in
            # resource_stats_extra, but keep lock ordering trivial
            stats.update(quant.resource_stats_extra())
        if tiered is not None:
            stats.update(tiered.resource_stats_extra())
        return stats

    def changed_since(self, seq: int) -> Optional[List[str]]:
        """ext_ids added or UPDATED after mutation ``seq`` (latest first,
        deduped). Deletes are not reported — consumers live-filter those.
        Returns None when the changelog has been trimmed past ``seq``
        (consumer should rebuild or take an exact path instead)."""
        with self._lock:
            if seq < self._changelog_floor:
                return None
            out: List[str] = []
            for s, eid in reversed(self._changelog):
                if s <= seq:
                    break
                out.append(eid)
        return list(dict.fromkeys(out))

    def add_batch(self, items: Sequence[Tuple[str, Sequence[float]]]) -> None:
        with self._lock:
            for ext_id, vec in items:
                self.add(ext_id, vec)

    def add_matrix(self, ext_ids: Sequence[str], matrix: np.ndarray,
                   payloads: Optional[Sequence[Optional[Dict]]] = None
                   ) -> None:
        """``add`` for row i of a float32 ``[n, dims]`` matrix under
        ``ext_ids[i]`` (with ``payloads[i]``), for every i in order: the
        same rows, slots, ids, codes and mutation count afterwards. ``BULK_MIN_ROWS`` or more fresh
        ids into an index without free slots (a bulk load) are
        normalised and copied block by block, with no call a row;
        anything else takes the loop. The changelog is trimmed once, at
        the capacity the load ends with, so it may reach further back
        than a row-by-row load's (whose cap grew with the capacity,
        step by step), never less far."""
        matrix = np.asarray(matrix, dtype=np.float32)
        ext_ids = list(ext_ids)
        n = len(ext_ids)
        if matrix.ndim != 2 or matrix.shape[0] != n:
            raise ValueError(f"matrix {matrix.shape} for {n} ids")
        with self._lock:
            if (n < BULK_MIN_ROWS or self._free or len(set(ext_ids)) != n
                    or not self._slot_of.keys().isdisjoint(ext_ids)):
                for i, (ext_id, vec) in enumerate(zip(ext_ids, matrix)):
                    self.add(ext_id, vec,
                             None if payloads is None else payloads[i])
                return
            start = self._count
            self._ensure_capacity_locked(start + n, matrix.shape[1])
            for lo in range(0, n, 65536):
                block = matrix[lo:lo + 65536]
                # the norm as ``_normalize`` takes it, row by row
                norms = np.fromiter((np.sqrt(r.dot(r)) for r in block),
                                    np.float32, len(block))
                norms[norms <= 1e-12] = 1.0
                np.divide(block, norms[:, None],
                          out=self._matrix[start + lo:start + lo + len(block)])
            self._valid[start:start + n] = True
            self._ext_ids[start:start + n] = ext_ids
            if self._col_fields and payloads is not None:
                for i in range(n):
                    self._code_slot_locked(start + i, payloads[i])
            self._slot_of.update(zip(ext_ids, range(start, start + n)))
            self._count += n
            self._n_alive += n
            if self._dev_matrix is not None:
                self._pending.update(range(start, start + n))
            self._ids_moved.update(range(start // IDS_CHUNK,
                                         (start + n - 1) // IDS_CHUNK + 1))
            seq0 = self.mutations
            self.mutations += n
            self._changelog.extend(zip(range(seq0 + 1, seq0 + n + 1),
                                       ext_ids))
            self._trim_changelog_locked()

    def remove(self, ext_id: str) -> bool:
        with self._lock:
            slot = self._slot_of.pop(ext_id, None)
            if slot is None:
                return False
            self._valid[slot] = False
            self._ext_ids[slot] = None
            self._free.append(slot)
            self._n_alive -= 1
            self._code_slot_locked(slot, None)
            self._wrote_locked(slot, id_moved=True)
            self.mutations += 1
            self._maybe_compact_locked()
            return True

    def _maybe_compact_locked(self) -> None:
        dead = self._count - self._n_alive
        if (dead < self.compact_min_dead
                or dead < self.compact_dead_frac * max(self._count, 1)):
            return
        self._compact_locked()

    def compact(self) -> bool:
        """Re-pack live rows and re-pad capacity. Normally triggered by
        the remove-path policy; public for tests and admin tooling."""
        with self._lock:
            if self._count == self._n_alive:
                return False
            self._compact_locked()
            return True

    def _compact_locked(self) -> None:
        """Drop tombstoned rows: live rows move to the front (insertion
        order preserved) and capacity shrinks to pad_dim(n_alive), so
        search matmuls — and the HBM mirror — stop paying for deletes.
        Slot ids are remapped; _slot_of is the only consumer."""
        if self._n_alive == 0:
            self._capacity = 0
            self._count = 0
            self._matrix = None
            self._valid = None
            self._ext_ids = []
            self._slot_of = {}
            self._free = []
            if self._columns is not None:
                self._columns = np.full((self._columns.shape[0], 0),
                                        COL_MISSING, np.int32)
        else:
            rows = [i for i, e in enumerate(self._ext_ids)
                    if e is not None and self._valid[i]]
            new_cap = pad_dim(len(rows))
            new_m = np.zeros((new_cap, self.dims), dtype=np.float32)
            new_m[: len(rows)] = self._matrix[rows]
            new_v = np.zeros((new_cap,), dtype=bool)
            new_v[: len(rows)] = True
            if self._columns is not None:
                new_c = np.full((self._columns.shape[0], new_cap),
                                COL_MISSING, np.int32)
                new_c[:, : len(rows)] = self._columns[:, rows]
                self._columns = new_c
            self._ext_ids = ([self._ext_ids[i] for i in rows]
                             + [None] * (new_cap - len(rows)))
            self._slot_of = {e: s for s, e in enumerate(self._ext_ids)
                             if e is not None}
            self._matrix = new_m
            self._valid = new_v
            self._capacity = new_cap
            self._count = len(rows)
            self._free = []
        self._drop_device_locked()
        self.mutations += 1
        self.compactions += 1

    def get(self, ext_id: str) -> Optional[np.ndarray]:
        with self._lock:
            slot = self._slot_of.get(ext_id)
            if slot is None:
                return None
            return self._matrix[slot].copy()

    def delta_vectors(self, ext_ids):
        """(ids, rows f32 [n, D] or None) for changelog delta ids under
        ONE lock hold, skipping ids removed since logging — the
        exact-float32 side-scan gather every quantized serving path
        shares (rows are CURRENT matrix values: read-your-writes)."""
        with self._lock:
            ids: List[str] = []
            rows = []
            for eid in ext_ids:
                slot = self._slot_of.get(eid)
                if slot is None:
                    continue
                ids.append(eid)
                rows.append(self._matrix[slot].copy())
        return ids, (np.stack(rows) if ids else None)

    def slots_of(
        self, ext_ids: Sequence[str],
        expect_mutations: Optional[int] = None,
    ) -> Optional[List[int]]:
        """Current matrix slot per ext id (-1 when absent). Slot ids
        only mean anything relative to a specific matrix state, so the
        read and the staleness check share one lock hold: when
        ``expect_mutations`` no longer matches (a write or compaction
        landed since the caller captured its device view), returns None
        — joining fresh slots against an older matrix would mis-join."""
        with self._lock:
            if expect_mutations is not None \
                    and self.mutations != expect_mutations:
                return None
            return [self._slot_of.get(e, -1) for e in ext_ids]

    def rows_for_slots(
        self, slots, expect_compactions: Optional[int] = None,
    ):
        """(rows f32 [n, D] copy, alive [n] bool, ext_ids [n]) for the
        given slot ids under ONE lock hold — the exact-rerank gather of
        the quantized plane. Rows are the CURRENT matrix values, so an
        in-place update reranks fresh automatically. Returns None when
        ``expect_compactions`` no longer matches (a compaction remapped
        the slot space since the caller's plane was built — slot-keyed
        reads can no longer be trusted) or a slot is out of range."""
        with self._lock:
            if expect_compactions is not None \
                    and self.compactions != expect_compactions:
                return None
            if self._matrix is None:
                return None
            sl = np.asarray(slots, dtype=np.int64)
            if sl.size and (sl.min() < 0 or sl.max() >= self._capacity):
                return None
            return (self._matrix[sl].copy(), self._valid[sl].copy(),
                    [self._ext_ids[int(i)] for i in sl])

    # -- search -----------------------------------------------------------

    def _device_arrays_locked(self):
        """The device arrays, current with the host mirror; call with the
        index lock held and keep it until the program that reads them is
        dispatched (``_Lease``): the next refresh donates them."""
        if self._dev_matrix is None:
            with _span("index.refresh", kind="full", rows=self._capacity,
                       bucket=0):
                self._dev_matrix = _ship(self._matrix)
                self._dev_valid = _ship(self._valid)
            self._pending = set()
            _REFRESH_C.labels("full").inc()
            _SHIP_BYTES_C.labels("full").inc(
                self._matrix.nbytes + self._valid.nbytes)
        elif self._pending:
            slots = np.sort(np.fromiter(self._pending, np.int32,
                                        len(self._pending)))
            self._pending = set()
            with _span("index.refresh", kind="rows", rows=len(slots)) as sp:
                sp.annotate(bucket=self._apply_rows_locked(slots))
            _REFRESH_C.labels("rows").inc()
        if self._columns is not None and self._dev_columns is None:
            # a thousandth of the matrix: shipped whole beside a full
            # ship, after a fill and when the set of fields changed
            self._dev_columns = _ship(self._columns)
            _SHIP_BYTES_C.labels("columns").inc(self._columns.nbytes)
        return self._dev_matrix, self._dev_valid

    def _apply_rows_locked(self, slots: np.ndarray) -> int:
        """Write rows ``slots`` of the host mirror into the resident
        device arrays, in rounds of the smallest bucket that holds what
        is left (padded by repeating the round's last row); returns the
        last round's bucket."""
        top = UPDATE_BUCKETS[-1]
        for lo in range(0, len(slots), top):
            part = slots[lo:lo + top]
            n = len(part)
            bucket = _update_bucket(n)
            padded = np.full(bucket, part[-1], np.int32)
            padded[:n] = part
            # fancy indexing copies: what is handed over is no view of
            # the mirror
            rows, vals = self._matrix[padded], self._valid[padded]
            t0 = time.perf_counter()
            if self._dev_columns is None:
                # no columns, or a stack the next lines of the caller
                # ship whole: the plain program, as an index without a
                # payload index runs it
                self._dev_matrix, self._dev_valid = index_update(
                    self._dev_matrix, self._dev_valid, padded, rows, vals)
                shipped = 0
            else:
                codes = self._columns[:, padded]
                self._dev_matrix, self._dev_valid, self._dev_columns = \
                    index_update_columns(
                        self._dev_matrix, self._dev_valid,
                        self._dev_columns, padded, rows, vals, codes)
                shipped = codes.nbytes
            record_dispatch(KIND_UPDATE, bucket, 1,
                            time.perf_counter() - t0)
            _SHIP_BYTES_C.labels("rows").inc(
                padded.nbytes + rows.nbytes + vals.nbytes + shipped)
        return bucket

    def warm_updates(self) -> None:
        """Compile (or load from the cache) every program a write to this
        index can need at its present capacity, by rewriting slot 0 with
        its own row once a bucket. A server calls this before it takes
        traffic, so that the first search after a write is not the one
        that compiles. Nothing to do while the index is empty or below
        the host tier's size (no device copy)."""
        with self._lock:
            if (self._n_alive == 0 or self._capacity * (self.dims or 1)
                    <= self._SMALL_HOST):
                return
            # with payload columns the program that also writes the codes
            self._device_arrays_locked()
            for bucket in UPDATE_BUCKETS:
                self._apply_rows_locked(np.zeros(bucket, np.int32))
            jax.block_until_ready(self._dev_matrix)

    # -- payload columns --------------------------------------------------

    def _code_locked(self, field: str, payload: Optional[Dict]) -> int:
        """The int32 code of ``field`` in ``payload``. ``integer``: the
        value itself when it is an ``int`` the column holds; ``keyword``:
        the string's number in the field's dictionary (grown here). A
        point without the field, or with a value no ``match.value`` or
        ``range`` of the host filter can match (a list, a dict, ``None``;
        for a keyword anything but a string), is MISSING. A scalar the
        host filter could match and the column cannot hold (a float, a
        bool, a numeric string, an integer past int32) is UNCODED, and
        the field answers no filter while a live row holds one."""
        found, value = payload_value(payload, field) \
            if payload else (False, None)
        if not found or value is None or isinstance(value, (list, dict)):
            return COL_MISSING
        if self._col_schema[field] == "keyword":
            if type(value) is not str:
                return COL_MISSING
            codes = self._col_dict[field]
            code = codes.get(value)
            if code is None:
                code = codes[value] = len(codes)
            return code
        if type(value) is int and COL_LO <= value <= COL_HI:
            return value
        return COL_UNCODED

    def _set_code_locked(self, f: int, slot: int,
                         payload: Optional[Dict]) -> None:
        """Write field ``f``'s code of ``slot`` from ``payload``, keeping
        the field's count of uncoded live rows."""
        field = self._col_fields[f]
        new = self._code_locked(field, payload)
        old = int(self._columns[f, slot])
        if old == new:
            return
        if old == COL_UNCODED:
            self._col_uncoded[field] -= 1
        if new == COL_UNCODED:
            self._col_uncoded[field] += 1
        self._columns[f, slot] = new

    def _code_slot_locked(self, slot: int, payload: Optional[Dict]) -> None:
        """Write ``slot``'s codes from ``payload`` (``None``: the row has
        none of the fields)."""
        for f in range(len(self._col_fields)):
            self._set_code_locked(f, slot, payload)

    def columns(self) -> Dict[str, str]:
        """``{field: schema}`` of the payload columns that serve filters."""
        with self._lock:
            return {f: self._col_schema[f] for f in self._col_fields
                    if f in self._col_ready}

    def declare_column(self, field: str, schema: str,
                       ready: bool = False) -> bool:
        """A payload column for ``field`` (``integer`` | ``keyword``), every
        slot MISSING. It takes the codes of rows written from now on and
        serves filters once ``publish_column`` says it is filled
        (``ready=True``: there is nothing to fill). False when the index
        already has the column under that schema."""
        if schema not in COLUMN_SCHEMAS:
            raise ValueError(f"field_schema {schema!r}: the payload index "
                             f"takes {COLUMN_SCHEMAS}")
        with self._lock:
            if field in self._col_schema:
                if self._col_schema[field] != schema:
                    raise ValueError(
                        f"field {field!r} is indexed as "
                        f"{self._col_schema[field]!r}; drop it first")
                return False
            if len(self._col_fields) >= MAX_COLUMNS:
                raise ValueError(f"at most {MAX_COLUMNS} indexed payload "
                                 f"fields a collection")
            self._col_fields.append(field)
            self._col_schema[field] = schema
            self._col_dict[field] = {}
            self._col_uncoded[field] = 0
            if ready:
                self._col_ready.add(field)
            self._restack_locked({f: i for i, f in
                                  enumerate(self._col_fields[:-1])})
            return True

    def drop_column(self, field: str) -> bool:
        with self._lock:
            if field not in self._col_schema:
                return False
            was = {f: i for i, f in enumerate(self._col_fields)}
            self._col_fields.remove(field)
            for table in (self._col_schema, self._col_dict,
                          self._col_uncoded):
                del table[field]
            self._col_ready.discard(field)
            self._restack_locked(was)
            return True

    def _restack_locked(self, was: Dict[str, int]) -> None:
        """The column stack for the present fields, each field's row taken
        from row ``was[field]`` of the old stack (a new field: MISSING).
        Plans made against the old stack are stale from here."""
        old = self._columns
        if not self._col_fields:
            self._columns = None
        else:
            self._columns = np.full(
                (pow2_bucket(len(self._col_fields)), self._capacity),
                COL_MISSING, np.int32)
            for f, field in enumerate(self._col_fields):
                if field in was:
                    self._columns[f] = old[was[field]]
        self._dev_columns = None
        self._col_gen += 1

    def fill_column(self, field: str, ext_ids: Sequence[str],
                    read_payloads) -> None:
        """Set ``field``'s code for rows that were there before the column
        was. ``read_payloads(ext_ids)`` reads their stored payloads; it
        is called outside the index lock and its reading thrown away when
        a write landed meanwhile (the payloads may then be older than the
        rows), and after three such readings with the writers held."""
        for _ in range(3):
            seen = self.mutations
            payloads = read_payloads(ext_ids)
            with self._lock:
                if self.mutations == seen:
                    self._fill_locked(field, ext_ids, payloads)
                    return
        with self._lock:
            self._fill_locked(field, ext_ids, read_payloads(ext_ids))

    def _fill_locked(self, field, ext_ids, payloads) -> None:
        f = self._col_fields.index(field)
        slot_of, code = self._slot_of.get, self._code_locked
        pairs = [(slot_of(e), code(field, p))
                 for e, p in zip(ext_ids, payloads)]
        slots = np.fromiter((s for s, _ in pairs if s is not None),
                            np.int64)
        codes = np.fromiter((c for s, c in pairs if s is not None),
                            np.int32, len(slots))
        self._col_uncoded[field] += int(
            np.count_nonzero(codes == COL_UNCODED)) - int(
            np.count_nonzero(self._columns[f, slots] == COL_UNCODED))
        self._columns[f, slots] = codes
        # shipped whole by the next reader (a thousandth of the matrix)
        self._dev_columns = None

    def set_payload(self, ext_id: str, payload: Optional[Dict]) -> None:
        """The codes of a row whose payload changed and whose vector did
        not."""
        with self._lock:
            slot = self._slot_of.get(ext_id)
            if slot is not None and self._col_fields:
                self._code_slot_locked(slot, payload)
                self._wrote_locked(slot)
                self.mutations += 1

    @property
    def has_columns(self) -> bool:
        return bool(self._col_fields)

    def publish_column(self, field: str) -> None:
        with self._lock:
            if field in self._col_schema:
                self._col_ready.add(field)

    def filter_bounds(self, conds: Sequence[Tuple[str, str, object]]):
        """Per-field inclusive bounds for a conjunction of conditions
        ``(field, op, value)``, ``op`` one of ``eq``, ``gt``, ``gte``,
        ``lt``, ``lte``: ``(generation, bounds [F, 2] int32)`` for
        :meth:`search_batch`; ``"empty"`` when no row can pass (a keyword
        the dictionary has never seen, bounds that exclude each other);
        ``None`` when this index cannot answer exactly and the caller
        filters on the host (a field without a ready column, a row whose
        value the column cannot hold, a value of another type than the
        schema's, the quantised or tiered rung serving)."""
        if self.tiered_plane() is not None or self.quant_plane() is not None:
            return None
        with self._lock:
            if self._columns is None:
                return None
            bounds = open_bounds(self._columns.shape[0])
            lo = {}
            hi = {}
            for field, op, value in conds:
                if field not in self._col_ready \
                        or self._col_uncoded[field]:
                    return None
                keyword = self._col_schema[field] == "keyword"
                a, b = COL_LO, COL_HI
                if op == "eq":
                    if keyword:
                        if type(value) is not str:
                            return None
                        code = self._col_dict[field].get(value)
                        if code is None:
                            return "empty"
                        a = b = code
                    elif type(value) is int:
                        a = b = value
                    else:
                        return None
                elif keyword or type(value) not in (int, float) \
                        or not math.isfinite(value):
                    return None
                elif op == "gt":
                    a = math.floor(value) + 1
                elif op == "gte":
                    a = math.ceil(value)
                elif op == "lt":
                    b = math.ceil(value) - 1
                elif op == "lte":
                    b = math.floor(value)
                else:
                    return None
                lo[field] = max(lo.get(field, COL_LO), a)
                hi[field] = min(hi.get(field, COL_HI), b)
            for field in lo:
                if lo[field] > hi[field]:
                    return "empty"
                bounds[self._col_fields.index(field)] = (lo[field],
                                                         hi[field])
            return self._col_gen, bounds

    def warm_filtered(self, max_batch: int = 32,
                      ks: Sequence[int] = (64, 128, 256)) -> None:
        """Compile (or load from the cache) the filtered scan for every
        batch bucket up to ``max_batch`` at the power-of-two bucket of
        each k in ``ks`` (what a coalesced batch asks for), with open
        bounds, so that the first filtered search is not the one that
        compiles. The defaults are what a Qdrant search's first round can
        ask for. A server calls this before it takes traffic. Nothing to
        do for an index without payload columns or below the host tier's
        size."""
        with self._lock:
            if (self._columns is None or self._n_alive == 0
                    or self._capacity * (self.dims or 1)
                    <= self._SMALL_HOST):
                return
            gen, rows = self._col_gen, self._columns.shape[0]
        b = 1
        while b <= pow2_bucket(max_batch):
            bounds = open_bounds(b, rows)
            for k in ks:
                self.search_batch(np.ones((b, self.dims), np.float32),
                                  pow2_bucket(k), bounds=bounds,
                                  bounds_gen=gen)
            b *= 2

    def _ids_snapshot_locked(self):
        """(slot-to-id table, ``"reused"`` | ``"extended"`` |
        ``"copied"``); call with the index lock held. Every writer of
        ``_ext_ids`` notes the chunk it touched under the lock; an
        overwrite of a row moves no id and leaves the table shared, a
        new or removed id rebuilds its chunk of ``IDS_CHUNK`` slots, and
        only a change of the slot space (growth, compaction, a load)
        rebuilds the table. Nobody may write a table handed out."""
        table = self._ids_table
        n_chunks = -(-self._capacity // IDS_CHUNK)
        if table is None:
            table = IdTable(tuple(
                tuple(self._ext_ids[c * IDS_CHUNK:(c + 1) * IDS_CHUNK])
                for c in range(n_chunks)))
            result = "copied"
        elif self._ids_moved:
            chunks = list(table.chunks)
            for c in self._ids_moved:
                chunks[c] = tuple(
                    self._ext_ids[c * IDS_CHUNK:(c + 1) * IDS_CHUNK])
            table = IdTable(tuple(chunks))
            result = "extended"
        else:
            result = "reused"
        self._ids_table = table
        self._ids_moved = set()
        _IDS_SNAPSHOT_C.labels(result).inc()
        return table, result

    def view_meta(self):
        """(mutations, compactions) — or None while the index is empty
        — WITHOUT forcing the device arrays current. The walk tier
        only needs the mutation counter for its freshness gate, and
        :meth:`device_lease` would apply the pending writes to the
        device copy, which the walk dispatch never reads."""
        with self._lock:
            if self._n_alive == 0 or self._matrix is None:
                return None
            return self.mutations, self.compactions

    def ids_meta(self):
        """(ext_ids, mutations, compactions) — or None while
        empty — WITHOUT forcing the device arrays current. The
        quantized fused tier joins/decodes against slot ids and never
        reads the float32 device copy. ``ext_ids`` is the shared
        read-only id table (:class:`IdTable`), the one
        :meth:`device_lease` and :meth:`search_batch` resolve through."""
        with self._lock:
            if self._n_alive == 0 or self._matrix is None:
                return None
            ext_ids, _ = self._ids_snapshot_locked()
            return ext_ids, self.mutations, self.compactions

    def device_lease(self) -> _Lease:
        """Consistent device-side view for external batched kernels (the
        fused hybrid pipeline, the graph plane's rank): a lease whose
        ``view`` is (matrix[C,D], valid[C], ext_ids, mutations,
        compactions) captured atomically, or None while the index is
        empty. The lease HOLDS THE INDEX LOCK: dispatch the program that
        reads the arrays, then ``release()`` (or leave the ``with``)
        before waiting for its result. The arrays are the ones
        ``search_batch`` scans and the next write's refresh donates them,
        so they must not be kept past the release. ``ext_ids`` is the
        shared read-only id table (:meth:`ids_meta`) and may be kept."""
        lease = _Lease(self._lock)
        lease.acquire()
        try:
            if self._n_alive and self._matrix is not None:
                m, valid = self._device_arrays_locked()
                ext_ids, _ = self._ids_snapshot_locked()
                lease.view = (m, valid, ext_ids, self.mutations,
                              self.compactions)
        except BaseException:
            lease.release()
            raise
        return lease

    def search(
        self, query: Sequence[float], k: int = 10
    ) -> List[Tuple[str, float]]:
        return self.search_batch(np.asarray([query], dtype=np.float32), k)[0]

    @staticmethod
    def _search_host(queries, m, valid, ext_ids, k_eff, columns=None,
                     bounds=None):
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-12)
        scores = qn @ m.T
        scores[:, ~valid] = -np.inf
        if bounds is not None:
            # the device scan's per-rider mask (ops/similarity._bounds_mask)
            seen = np.all((columns[None] >= bounds[:, :, 0, None])
                          & (columns[None] <= bounds[:, :, 1, None]), axis=1)
            scores[~seen] = -np.inf
        out: List[List[Tuple[str, float]]] = []
        for row in range(scores.shape[0]):
            top = np.argpartition(-scores[row], k_eff - 1)[:k_eff]
            # exact-tie order is lower-slot-first, matching lax.top_k on
            # the device path (hybrid parity relies on it); lexsort's
            # primary key is the last one
            top = top[np.lexsort((top, -scores[row][top]))]
            hits = []
            for idx in top:
                if not np.isfinite(scores[row, idx]):
                    break
                eid = ext_ids[int(idx)]
                if eid is not None:
                    hits.append((eid, float(scores[row, idx])))
            out.append(hits)
        return out

    # at or below this many matrix cells the search runs in host numpy
    # instead of paying a device dispatch — small qdrant collections and
    # early index life live here. The constant was set on a CPU backend
    # and has NOT been measured on the chip (ROADMAP S6 re-derives it).
    _SMALL_HOST = 1 << 18

    def quant_plane(self):
        """The lazily-created quantized serving plane when
        NORNICDB_VECTOR_QUANT is configured and the corpus clears the
        quant floor, else None. ONE plane per index — direct kNN
        serving and the fused hybrid tier share it (one compressed copy
        in HBM, one rebuild cadence)."""
        from nornicdb_tpu.search.device_quant import (
            quant_min_n,
            quant_mode,
        )

        if quant_mode() == "off" or self._n_alive < quant_min_n():
            return None
        plane = self._quant
        if plane is None:
            from nornicdb_tpu.config import env_bool, env_int
            from nornicdb_tpu.search.device_quant import (
                QuantizedBrutePlane,
            )

            with self._lock:
                plane = self._quant
                if plane is None:
                    plane = QuantizedBrutePlane(
                        self,
                        n_shards=max(1, env_int("QUANT_SHARDS", 1)),
                        build_inline=env_bool("QUANT_INLINE_BUILD",
                                              False),
                        overfetch=max(1, env_int("QUANT_OVERFETCH", 8)),
                        min_pool=max(1, env_int("QUANT_MIN_POOL", 128)))
                    self._quant = plane
        return plane

    def tiered_plane(self):
        """The lazily-created tiered serving plane when
        NORNICDB_VECTOR_TIERED is on and the corpus clears the tiered
        floor, else None. ONE plane per index: one partition layout,
        one residency LRU, one disk spill store. All NORNICDB_TIERED_*
        knobs are read HERE, once, at plane creation — the per-request
        path (route/search_batch) is environment-free by the PR 14
        hot-path contract."""
        from nornicdb_tpu.search.tiered_store import (
            tiered_enabled,
            tiered_min_n,
        )

        if not tiered_enabled() or self._n_alive < tiered_min_n():
            return None
        plane = self._tiered
        if plane is None:
            from nornicdb_tpu.config import (
                env_bool,
                env_float,
                env_int,
                env_str,
            )
            from nornicdb_tpu.search.tiered_store import TieredStore

            with self._lock:
                plane = self._tiered
                if plane is None:
                    plane = TieredStore(
                        self,
                        nprobe=max(1, env_int("TIERED_NPROBE", 8)),
                        parts=max(0, env_int("TIERED_PARTS", 0)),
                        resident_max=max(
                            0, env_int("TIERED_RESIDENT", 0)),
                        part_rows=max(
                            256, env_int("TIERED_PART_ROWS", 4096)),
                        lex_bonus=env_float("TIERED_LEX_BONUS", 0.15),
                        build_inline=env_bool("TIERED_INLINE_BUILD",
                                              False),
                        overfetch=max(
                            1, env_int("TIERED_OVERFETCH", 8)),
                        min_pool=max(
                            1, env_int("TIERED_MIN_POOL", 128)),
                        root_dir=env_str("TIERED_DIR", "") or None)
                    self._tiered = plane
        return plane

    def _tiered_search_batch(self, queries, k, lex_hints=None):
        """Tiered cluster-routed serving (tiered_store.py) when
        NORNICDB_VECTOR_TIERED is on and the corpus clears the tiered
        floor. None = the quant/float32 rungs serve this batch — the
        ladder is tiered -> quant -> f32 -> host, never a wrong
        answer. Fail-open like the quant plane."""
        plane = self.tiered_plane()
        if plane is None:
            return None
        try:
            return plane.search_batch(
                np.asarray(queries, dtype=np.float32), k,
                lex_hints=lex_hints)
        except Exception:  # noqa: BLE001 — degrade, never fail
            from nornicdb_tpu.obs import audit as _audit
            from nornicdb_tpu.search.tiered_store import _TIERED_C

            _TIERED_C.labels("degrade_error").inc()
            _audit.record_degrade(
                "vector", "vector_tiered", "vector_brute_f32",
                "error", index=_cost.cost_name(self))
            return None

    def _quant_search_batch(self, queries, k):
        """Quantized coarse-then-exact serving (device_quant.py) when
        NORNICDB_VECTOR_QUANT is set and the corpus clears the quant
        floor. None = the float32 tier serves this batch — the degrade
        ladder is quantized -> float32 -> host, never a wrong answer.
        Fail-open: any plane error degrades, never fails a search."""
        plane = self.quant_plane()
        if plane is None:
            return None
        try:
            return plane.search_batch(
                np.asarray(queries, dtype=np.float32), k)
        except Exception:  # noqa: BLE001 — degrade, never fail
            # counted: a persistent plane bug silently eating the
            # compression win must show up in quant_events_total
            from nornicdb_tpu.obs import audit as _audit
            from nornicdb_tpu.search.device_quant import (
                _QUANT_C,
                quant_mode,
            )

            _QUANT_C.labels("degrade_error").inc()
            _audit.record_degrade(
                "vector", f"vector_{quant_mode()}", "vector_brute_f32",
                "error", index=_cost.cost_name(self))
            return None

    def search_batch(
        self, queries: np.ndarray, k: int = 10, exact: bool = False,
        bounds: Optional[np.ndarray] = None,
        bounds_gen: Optional[int] = None,
    ) -> List[List[Tuple[str, float]]]:
        """Batched exact search; returns per-query [(ext_id, cosine)].
        ``bounds [B, F, 2] int32`` (:meth:`filter_bounds`, stacked a
        rider; ``bounds_gen`` the generation they were planned against)
        restricts rider b to the rows whose payload codes lie inside
        ``bounds[b]``: the filtered program scores every row and ranks
        exactly among those a rider may see; matrix, validity, ids AND
        columns are of one generation. A call without bounds runs the
        plain program with the plain arguments.
        With ``NORNICDB_VECTOR_QUANT`` set, large corpora serve through
        the quantized coarse+exact-rerank plane instead (answers remain
        exact-rescored float32; ``exact=True`` bypasses the plane for
        callers whose contract is exhaustive recall). Every write
        acknowledged before the call is in the answer: the device copy
        is refreshed, the id table captured and the scan dispatched
        under one hold of the index lock, so matrix, validity and ids
        are of one generation (:meth:`_ids_snapshot_locked`)."""
        from nornicdb_tpu.obs import audit as _audit

        if bounds is not None:
            exact = True    # the other rungs take no bounds
            bounds = np.asarray(bounds, np.int32)
            filtered = int(np.count_nonzero(np.any(
                bounds[:, :, 0] > COL_MISSING, axis=1)
                | np.any(bounds[:, :, 1] < COL_HI, axis=1)))
            scan_attrs = {"filtered": filtered, "fields": bounds.shape[1]}
        else:
            scan_attrs = {}
        if not exact:
            # capacity rung first (beyond-HBM corpora), then the
            # device-resident quant rung
            out = self._tiered_search_batch(queries, k)
            if out is not None:
                return out
            out = self._quant_search_batch(queries, k)
            if out is not None:
                return out
        # serving-tier note for the batch leader (ISSUE 10): every
        # return below — small-host numpy, XLA matmul, empty answer —
        # is the exact float32 brute tier (the quant plane notes its
        # own tier before returning above)
        _audit.note_batch_tier("vector_brute_f32")
        # three child spans of whoever searches (the batch leader's
        # root, or ``qdrant.widen``): the lock and what is captured under
        # it, the scan until its result is on the host, the result loop.
        # ``path`` on index.scan is the tier label that tells host NumPy
        # from the chip, which ``vector_brute_f32`` does not.
        with _Lease(self._lock) as lease:
            with _span("index.snapshot") as snap:
                t_ask = time.perf_counter()
                lease.acquire()
                snap.annotate(lock_wait_ms=round(
                    (time.perf_counter() - t_ask) * 1e3, 3))
                if self._n_alive == 0:
                    return [[] for _ in range(len(queries))]
                if bounds is not None and (
                        self._columns is None
                        or bounds_gen != self._col_gen
                        or bounds.shape[1] != self._columns.shape[0]):
                    raise StaleFilterPlan(
                        "the payload columns changed after the filter "
                        "was planned")
                k_eff = min(k, self._n_alive)
                # per-query cost accounting: the brute scan's price is
                # its known shapes — B queries against the
                # capacity-padded [C, D] matrix (host or device, the
                # arithmetic is the same)
                if _cost.pricing_enabled():
                    flops, byts = _cost.price_brute(
                        len(queries), self._capacity, self.dims or 1)
                    _cost.record_query_cost(
                        "brute", _cost.cost_name(self), len(queries),
                        flops, byts)
                if self._capacity * (self.dims or 1) <= self._SMALL_HOST:
                    # no defensive copies: the whole host search runs
                    # under the lock and only reads the matrix/valid/
                    # ext_ids, so its scan nests inside the snapshot
                    with _span("index.scan", path="host",
                               b=len(queries), k=k_eff, **scan_attrs):
                        return self._search_host(
                            np.asarray(queries, np.float32), self._matrix,
                            self._valid, self._ext_ids, k_eff,
                            self._columns, bounds)
                # one lock hold: (m, valid, ext_ids) are one generation,
                # with every write acknowledged before it applied
                m, valid = self._device_arrays_locked()
                cols = self._dev_columns if bounds is not None else None
                ext_ids, ids = self._ids_snapshot_locked()
                snap.annotate(ids=ids)
            pallas = _use_pallas() and bounds is None
            # from the call into the jitted scan to its result on the
            # host: the wait behind other callers' scans, the execution,
            # D2H. The lock goes once the scan is DISPATCHED, not before:
            # the next refresh donates m and valid, and the device runs
            # what it is given in order
            with _span("index.scan", path="pallas" if pallas else "xla",
                       b=len(queries), k=k_eff, **scan_attrs):
                q = l2_normalize(jnp.asarray(queries, dtype=jnp.float32))
                if bounds is not None:
                    s, i = cosine_topk_filtered(q, m, valid, cols, bounds,
                                                k_eff)
                    del cols
                elif pallas:
                    from nornicdb_tpu.ops.pallas_topk import (
                        fused_cosine_topk,
                    )

                    s, i = fused_cosine_topk(q, m, valid, k_eff)
                else:
                    s, i = cosine_topk_auto(q, m, valid, k_eff)
                # not pinned while this thread waits for the result
                del m, valid
                lease.release()
                s = np.asarray(s)
                i = np.asarray(i)
        out: List[List[Tuple[str, float]]] = []
        chunks = ext_ids.chunks
        with _span("index.collect"):
            for row in range(s.shape[0]):
                hits = []
                for col in range(s.shape[1]):
                    if s[row, col] < -1e29:
                        break
                    slot = int(i[row, col])
                    eid = chunks[slot // IDS_CHUNK][slot % IDS_CHUNK]
                    if eid is not None:
                        hits.append((eid, float(s[row, col])))
                out.append(hits)
        return out

    # -- bulk access (for HNSW/kmeans builds) ------------------------------

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray, List[Optional[str]]]:
        """(matrix[cap,D], valid[cap], ext_ids) — normalized, host-side.
        An empty index (never populated, or compacted to empty) yields
        zero-row arrays rather than crashing its graph/HNSW builders."""
        with self._lock:
            if self._matrix is None:
                return (np.zeros((0, self.dims or 0), np.float32),
                        np.zeros((0,), bool), [])
            return self._matrix.copy(), self._valid.copy(), list(self._ext_ids)

    def ids(self) -> List[str]:
        with self._lock:
            return [e for e in self._ext_ids if e is not None]

    # -- persistence (reference: vector store save/load, search.go:496) --

    def save(self, path: str) -> None:
        """Snapshot live rows to an .npz (compacted: dead slots dropped)."""
        with self._lock:
            extra = {}
            if self._matrix is None or self._n_alive == 0:
                ids = np.asarray([], dtype="U1")
                matrix = np.zeros((0, 0), np.float32)
            else:
                rows = [i for i, e in enumerate(self._ext_ids)
                        if e is not None and self._valid[i]]
                ids = np.asarray([self._ext_ids[i] for i in rows])
                matrix = self._matrix[rows]
                if self._col_fields:
                    # the payload columns of the live rows, the fields
                    # that serve filters and the keyword dictionaries
                    fields = [f for f in self._col_fields
                              if f in self._col_ready]
                    extra["columns"] = self._columns[
                        [self._col_fields.index(f) for f in fields]][:, rows]
                    extra["columns_meta"] = np.asarray(json.dumps({
                        "fields": fields,
                        "schema": {f: self._col_schema[f] for f in fields},
                        "dict": {f: self._col_dict[f] for f in fields}}))
        # write through a file object — np.savez would append ".npz" to a
        # bare path, breaking the caller's atomic tmp-then-rename publish
        with open(path, "wb") as f:
            np.savez_compressed(f, ids=ids, matrix=matrix, **extra)

    @classmethod
    def load(cls, path: str, use_device: bool = True) -> "BruteForceIndex":
        """Exact restore: rows go back verbatim (no re-normalization — a
        second normalize of float32 rows drifts ~1e-7 and reorders
        equal-score ties vs the saved index)."""
        data = np.load(path, allow_pickle=False)
        idx = cls(use_device=use_device)
        ids = data["ids"]
        matrix = np.asarray(data["matrix"], np.float32)
        n = len(ids)
        if n == 0:
            return idx
        idx._ensure_capacity_locked(n, matrix.shape[1])
        idx._matrix[:n] = matrix
        idx._valid[:n] = True
        for i in range(n):
            eid = str(ids[i])
            idx._ext_ids[i] = eid
            idx._slot_of[eid] = i
        idx._count = n
        idx._n_alive = n
        if "columns_meta" in data.files:
            meta = json.loads(str(data["columns_meta"]))
            for field in meta["fields"]:
                idx.declare_column(field, meta["schema"][field], ready=True)
                idx._col_dict[field] = dict(meta["dict"][field])
            if meta["fields"]:
                codes = np.asarray(data["columns"], np.int32)
                idx._columns[: len(meta["fields"]), :n] = codes
                for f, field in enumerate(meta["fields"]):
                    idx._col_uncoded[field] = int(
                        np.count_nonzero(codes[f] == COL_UNCODED))
        return idx
