"""Brute-force exact kNN index with a device-resident matrix.

The TPU analog of the reference's GPUEmbeddingIndex
(pkg/gpu/accelerator.go:290-843 Add/Sync/Search): a host NumPy mirror is
the source of truth; a capacity-padded [C,D] normalized matrix is synced
to device HBM lazily (dirty-flag) and queried with one MXU matmul + top-k
(nornicdb_tpu.ops.similarity). Growth re-pads to the next power-of-two
capacity so jit never sees a new shape per insert.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.obs import cost as _cost
from nornicdb_tpu.obs.metrics import REGISTRY
from nornicdb_tpu.obs.tracing import span as _span
from nornicdb_tpu.ops.similarity import (
    CHUNKED_THRESHOLD,
    cosine_topk,
    cosine_topk_auto,
    cosine_topk_chunked,
    l2_normalize,
    pad_dim,
)


# how often a reader of the slot-to-id table was handed the generation's
# shared snapshot (reused) against a fresh copy after a write (copied)
_IDS_SNAPSHOT_C = REGISTRY.counter(
    "nornicdb_index_ids_snapshot_total",
    "Slot-to-id snapshots handed to index readers, by whether the "
    "mutation generation's snapshot was shared or rebuilt",
    labels=("result",))

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
        # device cache
        self._dev_matrix = None
        self._dev_valid = None
        self._dirty = True
        # (mutations, tuple(_ext_ids)): the slot-to-id snapshot every
        # reader of that generation shares (_ids_snapshot_locked)
        self._view_ids_cache = None
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
        self._matrix = new_m
        self._valid = new_v
        self._ext_ids.extend([None] * (new_cap - len(self._ext_ids)))
        self._capacity = new_cap
        self._dirty = True

    # -- mutation ---------------------------------------------------------

    def add(self, ext_id: str, vector: Sequence[float]) -> None:
        v = np.asarray(vector, dtype=np.float32)
        with self._lock:
            if ext_id in self._slot_of:
                slot = self._slot_of[ext_id]
                self._matrix[slot] = self._normalize(v)
                self._dirty = True
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
            self._dirty = True
            self.mutations += 1
            self._log_change_locked(ext_id)

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
            used = max(self._count, 1)
            quant = self._quant
            tiered = self._tiered
            stats = {
                "rows": self._n_alive,
                "capacity": self._capacity,
                "device_bytes": dev_b,
                # host mirror + the ext-id slot table (pointer-sized
                # slots; string payloads are shared with callers)
                "host_bytes": matrix_b + valid_b + 8 * len(self._ext_ids),
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

    def add_matrix(self, ext_ids: Sequence[str], matrix: np.ndarray) -> None:
        """``add`` for row i of a float32 ``[n, dims]`` matrix under
        ``ext_ids[i]``, for every i in order: the same rows, slots, ids
        and mutation count afterwards. ``BULK_MIN_ROWS`` or more fresh
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
                for ext_id, vec in zip(ext_ids, matrix):
                    self.add(ext_id, vec)
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
            self._slot_of.update(zip(ext_ids, range(start, start + n)))
            self._count += n
            self._n_alive += n
            self._dirty = True
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
            self._dirty = True
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
        else:
            rows = [i for i, e in enumerate(self._ext_ids)
                    if e is not None and self._valid[i]]
            new_cap = pad_dim(len(rows))
            new_m = np.zeros((new_cap, self.dims), dtype=np.float32)
            new_m[: len(rows)] = self._matrix[rows]
            new_v = np.zeros((new_cap,), dtype=bool)
            new_v[: len(rows)] = True
            self._ext_ids = ([self._ext_ids[i] for i in rows]
                             + [None] * (new_cap - len(rows)))
            self._slot_of = {e: s for s, e in enumerate(self._ext_ids)
                             if e is not None}
            self._matrix = new_m
            self._valid = new_v
            self._capacity = new_cap
            self._count = len(rows)
            self._free = []
        self._dirty = True
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
        if self._dirty or self._dev_matrix is None:
            self._dev_matrix = jnp.asarray(self._matrix)
            self._dev_valid = jnp.asarray(self._valid)
            self._dirty = False
        return self._dev_matrix, self._dev_valid

    def _ids_snapshot_locked(self):
        """(slot-to-id snapshot, ``"reused"`` | ``"copied"``) for the
        current ``mutations`` generation; call with the index lock held.
        The snapshot is rebuilt only when a write has moved the
        generation (every writer of ``_ext_ids`` bumps ``mutations``
        under the lock), is shared by every reader of that generation
        and is a tuple because nobody may write it: a reader that
        captured it keeps resolving slots to the ids they held then,
        whatever is freed and reused afterwards."""
        cached = self._view_ids_cache
        if cached is not None and cached[0] == self.mutations:
            result = "reused"
        else:
            cached = (self.mutations, tuple(self._ext_ids))
            self._view_ids_cache = cached
            result = "copied"
        _IDS_SNAPSHOT_C.labels(result).inc()
        return cached[1], result

    def view_meta(self):
        """(mutations, compactions) — or None while the index is empty
        — WITHOUT forcing the device arrays current. The walk tier
        only needs the mutation counter for its freshness gate; after
        a write burst, :meth:`device_view` would re-ship the whole
        matrix to device and re-copy the capacity-sized ext-id list,
        a per-write tax the walk dispatch never uses."""
        with self._lock:
            if self._n_alive == 0 or self._matrix is None:
                return None
            return self.mutations, self.compactions

    def ids_meta(self):
        """(ext_ids, mutations, compactions) — or None while
        empty — WITHOUT forcing the device arrays current. The
        quantized fused tier joins/decodes against slot ids and must
        not pay the float32 matrix re-ship that :meth:`device_view`
        implies after a write burst. ``ext_ids`` is the generation's
        id snapshot: one per mutation generation, shared with
        :meth:`device_view` and :meth:`search_batch`, read-only."""
        with self._lock:
            if self._n_alive == 0 or self._matrix is None:
                return None
            ext_ids, _ = self._ids_snapshot_locked()
            return ext_ids, self.mutations, self.compactions

    def device_view(self):
        """Consistent device-side view for external batched kernels (the
        fused hybrid pipeline): (matrix[C,D], valid[C], ext_ids,
        mutations, compactions) captured atomically, or None while the
        index is empty. The matrix/valid arrays are the same lazily
        synced device cache ``search_batch`` dispatches against;
        ``ext_ids`` is the generation's id snapshot: one per mutation
        generation, shared with :meth:`ids_meta` and
        :meth:`search_batch`, read-only, so a steady read stream
        doesn't re-copy a capacity-sized list per batch."""
        with self._lock:
            if self._n_alive == 0 or self._matrix is None:
                return None
            m, valid = self._device_arrays_locked()
            ext_ids, _ = self._ids_snapshot_locked()
            return m, valid, ext_ids, self.mutations, self.compactions

    def search(
        self, query: Sequence[float], k: int = 10
    ) -> List[Tuple[str, float]]:
        return self.search_batch(np.asarray([query], dtype=np.float32), k)[0]

    @staticmethod
    def _search_host(queries, m, valid, ext_ids, k_eff):
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-12)
        scores = qn @ m.T
        scores[:, ~valid] = -np.inf
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
        self, queries: np.ndarray, k: int = 10, exact: bool = False
    ) -> List[List[Tuple[str, float]]]:
        """Batched exact search; returns per-query [(ext_id, cosine)].
        With ``NORNICDB_VECTOR_QUANT`` set, large corpora serve through
        the quantized coarse+exact-rerank plane instead (answers remain
        exact-rescored float32; ``exact=True`` bypasses the plane for
        callers whose contract is exhaustive recall). Slots resolve
        through the id snapshot of the mutation generation the scan
        ran against: one per generation, shared with
        :meth:`device_view` and :meth:`ids_meta`, read-only, rebuilt
        by the first read after a write under the lock this call
        already takes."""
        from nornicdb_tpu.obs import audit as _audit

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
        with _span("index.snapshot") as snap:
            t_ask = time.perf_counter()
            with self._lock:
                snap.annotate(lock_wait_ms=round(
                    (time.perf_counter() - t_ask) * 1e3, 3))
                if self._n_alive == 0:
                    return [[] for _ in range(len(queries))]
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
                               b=len(queries), k=k_eff):
                        return self._search_host(
                            np.asarray(queries, np.float32), self._matrix,
                            self._valid, self._ext_ids, k_eff)
                # one lock hold: (m, valid, ext_ids) are one generation
                m, valid = self._device_arrays_locked()
                ext_ids, ids = self._ids_snapshot_locked()
                snap.annotate(ids=ids)
        pallas = _use_pallas()
        # from the call into the jitted scan to its result on the host:
        # the wait behind other callers' scans, the execution, D2H
        with _span("index.scan", path="pallas" if pallas else "xla",
                   b=len(queries), k=k_eff):
            q = l2_normalize(jnp.asarray(queries, dtype=jnp.float32))
            if pallas:
                from nornicdb_tpu.ops.pallas_topk import fused_cosine_topk

                s, i = fused_cosine_topk(q, m, valid, k_eff)
            else:
                s, i = cosine_topk_auto(q, m, valid, k_eff)
            s = np.asarray(s)
            i = np.asarray(i)
        out: List[List[Tuple[str, float]]] = []
        with _span("index.collect"):
            for row in range(s.shape[0]):
                hits = []
                for col in range(s.shape[1]):
                    if s[row, col] < -1e29:
                        break
                    eid = ext_ids[int(i[row, col])]
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
            if self._matrix is None or self._n_alive == 0:
                ids = np.asarray([], dtype="U1")
                matrix = np.zeros((0, 0), np.float32)
            else:
                rows = [i for i, e in enumerate(self._ext_ids)
                        if e is not None and self._valid[i]]
                ids = np.asarray([self._ext_ids[i] for i in rows])
                matrix = self._matrix[rows]
        # write through a file object — np.savez would append ".npz" to a
        # bare path, breaking the caller's atomic tmp-then-rename publish
        with open(path, "wb") as f:
            np.savez_compressed(f, ids=ids, matrix=matrix)

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
        idx._dirty = True
        return idx
