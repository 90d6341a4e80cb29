"""Device-resident columnar graph plane: the LDBC Cypher family compiled
the way search was compiled.

PRs 2/4/6/8 made vector and hybrid search fully device-resident; the
Cypher fast paths that produce the headline ``ldbc_snb_cypher_geomean``
(query/fastpaths.py over query/columnar.py) still ran on host numpy.
This module snapshots the ``ColumnarCatalog``'s hot structures — CSR
adjacency, segment-sorted strips, label masks, incidence matrices —
into device arrays and compiles the LDBC fast-path shapes onto them as
batched gather/segment-sum programs (the CAGRA-style fixed-shape
traversal pattern; ``ops/graph.py`` PageRank already proves the
segment-sum half at ~1 ms / 20 iterations):

- **chain top-k** (``recent_messages_friends``): anchors -> CSR friend
  gather -> per-friend strip heads -> one ``lax.top_k`` merge, B
  anchors per dispatch. Concurrent point lookups coalesce through a
  ``BatchCoalescer`` so they ride ONE dispatch; key order is encoded as
  a dense tie-sharing rank so the device merge is *row-identical* to
  the host's stable ``argsort`` (no float-precision drift: the f64 sort
  keys never leave the host).
- **strip aggregation** (``avg_friends_per_city``): the materialized
  two-hop grouped-degree view (deg/sum_deg/nnz) built as device
  segment-sums + a lexicographic distinct-pair pass, installed back
  into the catalog so every downstream read and the incremental
  maintenance machinery are unchanged — the arrays are verified-exact
  integers, so parity is inherited, not re-proven per query.
- **co-occurrence Gram** (``tag_cooccurrence``): the incidence
  contraction ``Ma^T @ Mb`` as a device matmul under the same 2^24
  exactness bound the host path uses (0/1-integer f32 products are
  exact below it, so host and device produce equal integers).
- **fused traverse-then-rank**: chain expansion feeding the brute
  cosine top-k over the vector index's device matrix in ONE program —
  the service-level graph+vector query (SURVEY §6: no single baseline
  serves it).

Freshness discipline (PR 2/4/6/8): every snapshot is keyed on the
catalog's mutation-generation ``version()``; any write bumps it and the
next read degrades to the host path while the snapshot lazily rebuilds
— never a wrong answer. Guards (int32 rank overflow, 2^24 count
exactness, torn concurrent builds) likewise degrade to host.

Routing: ``NORNICDB_GRAPH_DEVICE`` = ``off`` | ``auto`` (default) |
``on``. ``auto`` keeps small catalogs on the host path
(``NORNICDB_GRAPH_DEVICE_MIN_N`` structure entries) and only dispatches
chain lookups on-device when concurrent demand actually coalesces a
batch (``NORNICDB_GRAPH_DEVICE_MIN_B`` riders) — a single-stream read
of a device-eligible catalog stays on the ~50 us host path instead of
paying a ~100 us+ b=1 dispatch. ``on`` forces the device route (tests,
benches, real accelerators at batch).
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from nornicdb_tpu.obs import declare_kind, record_dispatch
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import cost as _cost
from nornicdb_tpu.obs.metrics import REGISTRY
from nornicdb_tpu.search.microbatch import BatchCoalescer, pow2_bucket

_EVENTS_C = REGISTRY.counter(
    "nornicdb_device_graph_events_total",
    "Device graph plane lifecycle/degrade events", labels=("event",))

# dispatch kinds pre-registered so the compile-cache accounting carries
# their series from start
KIND_CHAIN = "graph_chain_topk"
KIND_AGG = "graph_strip_agg"
KIND_GRAM = "graph_cooc_gram"
KIND_RANK = "graph_traverse_rank"
for _k in (KIND_CHAIN, KIND_AGG, KIND_GRAM, KIND_RANK):
    declare_kind(_k)

# canonical serving-tier names (obs/audit taxonomy) for the plane's
# query-shaped rungs (strip/gram are builds, not per-query serving)
TIER_CHAIN = "graph_chain_device"
TIER_RANK = "graph_traverse_rank_device"


def _ledger(from_tier: str, reason: str,
            versions: "Dict[str, Any] | None" = None) -> None:
    """Structured degrade record for a device-graph -> host step (the
    legacy device_graph_events_total labels stay as aliases)."""
    _audit.record_degrade("graph", from_tier, "host", reason,
                          index="device_graph", versions=versions)

_I32_MAX = 2 ** 31 - 1
_EXACT_F32 = float(2 ** 24)  # integer-exactness bound for f32 sums


def graph_device_mode() -> str:
    mode = os.environ.get("NORNICDB_GRAPH_DEVICE", "auto").lower()
    return mode if mode in ("off", "auto", "on") else "auto"


def graph_device_min_n() -> int:
    try:
        return int(os.environ.get("NORNICDB_GRAPH_DEVICE_MIN_N", "200000"))
    except ValueError:
        return 200_000


def graph_device_min_b() -> int:
    try:
        return int(os.environ.get("NORNICDB_GRAPH_DEVICE_MIN_B", "4"))
    except ValueError:
        return 4


def _event(name: str) -> None:
    _EVENTS_C.labels(name).inc()


@functools.lru_cache(maxsize=1)
def _cpu_backend() -> bool:
    """True on the CPU PJRT fallback. ``auto`` mode only engages the
    device plane on a real accelerator — measured on CPU the host numpy
    paths win every rung (strip build 1.7 ms host vs 78 ms XLA-CPU at
    LDBC scale; coalesced chain dispatch roughly GIL-parity) — the same
    host-path policy as ops/graph.py PageRank and vector_index. ``on``
    forces the device route regardless (tests, benches). A backend that
    fails to initialise raises: it is not a reason to serve from host."""
    return _jx().default_backend() == "cpu"


# -- jitted programs ---------------------------------------------------------
#
# All programs take pow2-padded shapes (static) with dynamic validity
# masks, so the compile universe stays log-sized per kind. int32
# everywhere (x64 is off); every count that could exceed the f32/int32
# exactness bounds is guarded at the call site and degrades to host.


def _jx():
    import jax  # deferred: query/ imports stay light for host-only use

    return jax


@functools.lru_cache(maxsize=None)
def _chain_topk_fn(f: int, kp: int):
    jax = _jx()
    jnp = jax.numpy

    @functools.partial(jax.jit)
    def impl(anchors, kh, indptr1, far1, s_indptr, s_nbr, s_rank, mid_ok):
        b = anchors.shape[0]
        e1 = far1.shape[0]
        s = s_nbr.shape[0]
        a = jnp.maximum(anchors, 0)
        a_valid = anchors >= 0
        start = indptr1[a]
        cnt = indptr1[a + 1] - start
        fi = jnp.arange(f, dtype=jnp.int32)
        fpos = start[:, None] + fi[None, :]
        fvalid = (fi[None, :] < cnt[:, None]) & a_valid[:, None]
        friends = far1[jnp.clip(fpos, 0, max(e1 - 1, 0))]
        fvalid = fvalid & mid_ok[friends]
        sstart = s_indptr[friends]
        scnt = jnp.minimum(s_indptr[friends + 1] - sstart, kh)
        ci = jnp.arange(kp, dtype=jnp.int32)
        cpos = sstart[..., None] + ci[None, None, :]
        cvalid = (ci[None, None, :] < scnt[..., None]) & fvalid[..., None]
        cpos_c = jnp.clip(cpos, 0, max(s - 1, 0))
        width = f * kp
        rank = s_rank[cpos_c].reshape(b, width)
        order_idx = jnp.arange(width, dtype=jnp.int32)
        # composite merge key: dense tie-sharing key rank (primary,
        # ascending == key DESC) then candidate order (friend-major,
        # head-position minor) — exactly the host's stable tie order
        combined = jnp.where(
            cvalid.reshape(b, width),
            rank * width + order_idx[None, :],
            _I32_MAX,
        )
        neg_vals, sel = jax.lax.top_k(-combined, kp)
        sel_valid = (-neg_vals) < _I32_MAX
        sel_f = jnp.take_along_axis(friends, sel // kp, axis=1)
        sel_t = jnp.take_along_axis(
            s_nbr[cpos_c].reshape(b, width), sel, axis=1)
        return sel_f, sel_t, sel_valid

    return impl


@functools.lru_cache(maxsize=None)
def _strip_agg_fn(e1p: int, e2p: int, npad: int):
    jax = _jx()
    jnp = jax.numpy

    @functools.partial(jax.jit)
    def impl(g_e, p_e, pmask_e, keys2, fmask2):
        # terminal-hop filtered degree: one segment-sum over etype2
        deg = jax.ops.segment_sum(
            fmask2.astype(jnp.int32), keys2, num_segments=npad)
        # weighted group sums: f32 (exact while < 2^24; caller-verified)
        w = jnp.where(pmask_e, deg[p_e].astype(jnp.float32), 0.0)
        sum_deg = jax.ops.segment_sum(w, g_e, num_segments=npad)
        # DISTINCT (g, p) pairs with deg[p] > 0: lexicographic sort then
        # first-occurrence flags — no g*n+p composite (overflows int32)
        valid = pmask_e & (deg[p_e] > 0)
        g_s = jnp.where(valid, g_e, npad - 1)
        p_s = jnp.where(valid, p_e, npad - 1)
        g_sorted, p_sorted = jax.lax.sort((g_s, p_s), num_keys=2)
        prev_g = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                  g_sorted[:-1]])
        prev_p = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                  p_sorted[:-1]])
        first = (g_sorted != prev_g) | (p_sorted != prev_p)
        live = g_sorted < (npad - 1)
        nnz = jax.ops.segment_sum(
            (first & live).astype(jnp.int32), g_sorted, num_segments=npad)
        return deg, sum_deg, nnz, jnp.max(deg), jnp.max(sum_deg)

    return impl


@functools.lru_cache(maxsize=None)
def _gram_fn(mp: int):
    jax = _jx()
    jnp = jax.numpy

    @functools.partial(jax.jit)
    def impl(ma, mb):
        # 0/1-integer f32 contraction: exact below 2^24 (caller-guarded)
        return ma.T @ mb

    return impl


@functools.lru_cache(maxsize=None)
def _traverse_rank_fn(f1: int, f2: int, kp: int):
    jax = _jx()
    jnp = jax.numpy

    @functools.partial(jax.jit)
    def impl(anchors, q, indptr1, far1, indptr2, far2, slot_of_row,
             matrix, valid, n_nodes):
        b = anchors.shape[0]
        e1 = far1.shape[0]
        a = jnp.maximum(anchors, 0)
        a_valid = anchors >= 0
        start = indptr1[a]
        cnt = indptr1[a + 1] - start
        fi = jnp.arange(f1, dtype=jnp.int32)
        fpos = start[:, None] + fi[None, :]
        fvalid = (fi[None, :] < cnt[:, None]) & a_valid[:, None]
        rows = far1[jnp.clip(fpos, 0, max(e1 - 1, 0))]
        if f2 > 0:
            e2 = far2.shape[0]
            s2 = indptr2[rows]
            c2 = indptr2[rows + 1] - s2
            gi = jnp.arange(f2, dtype=jnp.int32)
            gpos = s2[..., None] + gi[None, None, :]
            gvalid = (gi[None, None, :] < c2[..., None]) & fvalid[..., None]
            rows = far2[jnp.clip(gpos, 0, max(e2 - 1, 0))].reshape(b, f1 * f2)
            rvalid = gvalid.reshape(b, f1 * f2)
        else:
            rvalid = fvalid
        # dedup: ascending sort with the invalid sentinel past every row
        rows_s = jnp.sort(jnp.where(rvalid, rows, n_nodes), axis=1)
        prev = jnp.concatenate(
            [jnp.full((b, 1), -1, jnp.int32), rows_s[:, :-1]], axis=1)
        keep = (rows_s != prev) & (rows_s < n_nodes)
        slots = slot_of_row[jnp.clip(rows_s, 0, slot_of_row.shape[0] - 1)]
        ok = keep & (slots >= 0)
        slots_c = jnp.maximum(slots, 0)
        ok = ok & valid[slots_c]
        vecs = matrix[slots_c]  # [b, F, D] frontier gather
        qn = q / jnp.maximum(
            jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        scores = jnp.einsum("bd,bfd->bf", qn, vecs)
        scores = jnp.where(ok, scores, -jnp.inf)
        vals, idx = jax.lax.top_k(scores, kp)
        sel_rows = jnp.take_along_axis(rows_s, idx, axis=1)
        return vals, sel_rows

    return impl


# -- the plane ---------------------------------------------------------------


class DeviceGraphPlane:
    """Versioned device snapshots of one ``ColumnarCatalog`` plus the
    compiled LDBC programs over them. One instance per executor; all
    public entry points return ``None`` to mean "serve on the host
    path" — the caller never distinguishes *why* (gated off, too small,
    stale snapshot, guard tripped): every miss is a correct host
    answer."""

    # refuse device arrays past this many entries per structure (int32
    # indices everywhere)
    MAX_ENTRIES = _I32_MAX - 2

    def __init__(self, catalog):
        self.catalog = catalog
        self._lock = threading.Lock()
        self._snaps: Dict[Any, Dict[str, Any]] = {}
        self._batchers: Dict[Any, BatchCoalescer] = {}
        # demand heuristic for auto mode: live chain reads in flight.
        # Guarded by its own tiny lock — a bare `+=` from concurrent
        # query threads loses updates, and a lost decrement would pin
        # the gate permanently (stuck demand or stuck silence)
        self.inflight = 0
        self._inflight_lock = threading.Lock()
        self.dispatches = 0
        # cached forced-mode flag for the per-query pre-gate (env reads
        # cost ~1 us — 2-8% of a whole host chain query); refreshed
        # every 256 single-stream calls. Staleness is only a routing
        # hint: the batch leader re-reads the env authoritatively, so a
        # stale True costs one wasted coalescer submit, never a wrong
        # answer or a gated-off dispatch.
        self._forced: Optional[bool] = None
        self._gate_tick = 0

    # -- snapshot bookkeeping ---------------------------------------------

    def _get_snap(self, key) -> Optional[Dict[str, Any]]:
        """Fetch a live snapshot. Snapshots carrying per-etype delta
        keys (``etv``/``etypes``, ISSUE 19) stay live across writes to
        UNRELATED edge types; legacy whole-catalog snapshots compare
        the global version as before."""
        with self._lock:
            snap = self._snaps.get(key)
        if snap is None:
            return None
        etypes = snap.get("etypes")
        if etypes is not None:
            if snap.get("etv") == self.catalog.etype_versions(etypes):
                return snap
            return None
        if snap.get("version") == self.catalog.version:
            return snap
        return None

    def _put_snap(self, key, snap: Dict[str, Any]) -> bool:
        """Install ``snap`` iff the catalog hasn't moved past its
        version (a build that raced a write must not resurrect a stale
        snapshot — same rule as the catalog's own caches). Per-etype
        snapshots compare their delta key, so an unrelated-etype write
        landing mid-build does not waste the build."""
        etypes = snap.get("etypes")
        if etypes is not None:
            fresh = self.catalog.etype_versions(etypes) == snap.get("etv")
        else:
            fresh = self.catalog.version == snap.get("version")
        if not fresh:
            _event("snapshot_raced")
            return False
        with self._lock:
            self._snaps[key] = snap
        _event("snapshot_built")
        return True

    def drop_snapshots(self) -> None:
        with self._lock:
            self._snaps.clear()

    # -- resource accounting ----------------------------------------------

    def resource_stats(self) -> Dict[str, float]:
        """Device/host footprint + generation gap for the resource
        gauges (nornicdb_index_device_bytes{family="device_graph",...},
        _rows, _mutation_gap)."""
        v = self.catalog.version
        dev = host = rows = 0
        newest = None
        with self._lock:
            snaps = list(self._snaps.values())
        for snap in snaps:
            dev += int(snap.get("device_bytes", 0))
            host += int(snap.get("host_bytes", 0))
            rows += int(snap.get("rows", 0))
            sv = snap.get("version")
            if sv is not None and (newest is None or sv > newest):
                newest = sv
        return {
            "device_bytes": dev,
            "host_bytes": host,
            "rows": rows,
            "mutation_gap": 0 if newest is None else max(0, v - newest),
        }

    # -- chain top-k (recent_messages_friends family) ---------------------

    def _chain_snapshot(self, spec: Tuple) -> Optional[Dict[str, Any]]:
        key = ("chain",) + spec
        snap = self._get_snap(key)
        if snap is not None:
            return snap if snap.get("ok") else None
        (etype1, dir1, mid_label, etype2, mid_side, order_prop,
         term_label) = spec
        cat = self.catalog
        v0 = cat.version
        # per-etype delta key (ISSUE 19): the program reads only these
        # two etypes' CSRs plus node-axis structures, and every
        # node-axis change moves the structural generation inside the
        # tuple — so writes to OTHER etypes leave this snapshot live
        etypes = (etype1, etype2)
        etv0 = cat.etype_versions(etypes)
        jax = _jx()
        jnp = jax.numpy
        try:
            sa = cat.sorted_adjacency(etype2, mid_side, order_prop,
                                      term_label)
            n = cat.n_nodes()
            tbl1 = cat.edge_table(etype1)
            indptr1, order1 = tbl1.csr(dir1, n)
            far_raw = tbl1.dst if dir1 == "out" else tbl1.src
            if sa is None or len(order1) != len(far_raw):
                # non-numeric order prop / torn build: record the
                # verdict so repeat reads don't re-probe until a write
                self._put_snap(key, {"version": v0, "etypes": etypes,
                                     "etv": etv0, "ok": False})
                return None
            if (len(sa.nbr) > self.MAX_ENTRIES
                    or len(far_raw) > self.MAX_ENTRIES
                    or len(sa.nbr) == 0 or len(far_raw) == 0
                    or np.isnan(sa.keys).any()):
                # empty structures answer trivially on the host path
                self._put_snap(key, {"version": v0, "etypes": etypes,
                                     "etv": etv0, "ok": False})
                return None
            far1 = far_raw[order1]
            # dense DESC rank with ties SHARING a rank: the device merge
            # key must order exactly like -keys under stable argsort
            uniq = np.unique(sa.keys)
            rank = (len(uniq) - 1) - np.searchsorted(uniq, sa.keys)
            if mid_label is not None:
                mid_ok = cat.label_mask(mid_label)
            else:
                mid_ok = np.ones(n, dtype=bool)
            if len(mid_ok) < n or len(indptr1) != n + 1 \
                    or len(sa.indptr) != n + 1:
                return None  # raced a node create; next read rebuilds
            snap = {
                "version": v0,
                "etypes": etypes,
                "etv": etv0,
                "ok": True,
                "n": n,
                "s": len(sa.nbr),
                "max_deg": int((indptr1[1:] - indptr1[:-1]).max())
                if n else 0,
                "indptr1": jnp.asarray(indptr1, jnp.int32),
                "far1": jnp.asarray(far1, jnp.int32),
                "s_indptr": jnp.asarray(sa.indptr, jnp.int32),
                "s_nbr": jnp.asarray(sa.nbr, jnp.int32),
                "s_rank": jnp.asarray(rank, jnp.int32),
                "mid_ok": jnp.asarray(mid_ok),
                "device_bytes": 4 * (2 * (n + 1) + 2 * len(far1)
                                     + 2 * len(sa.nbr)) + n,
                "host_bytes": rank.nbytes,
                "rows": len(sa.nbr) + len(far1),
            }
        except (IndexError, ValueError):
            return None  # torn under a concurrent write: host path
        if not self._put_snap(key, snap):
            return None
        return snap

    def chain_enter(self) -> None:
        with self._inflight_lock:
            self.inflight += 1

    def chain_exit(self) -> None:
        with self._inflight_lock:
            self.inflight -= 1

    def maybe_device(self) -> bool:
        """Allocation-free pre-gate for the per-query hot path: False
        when the device route cannot possibly engage — not forced on,
        and no coalescible demand (another chain read in flight). The
        host chain path runs ~50 us per query, so this avoids even the
        env read in the single-stream steady state (see ``_forced``)."""
        if self.inflight > 1:
            return True  # demand exists; the batcher decides the rest
        tick = self._gate_tick = (self._gate_tick + 1) & 0xFF
        if tick == 0 or self._forced is None:
            # THE amortized read the env-knob lint's hot-path rule
            # points at: refreshed every 256 calls, staleness is a
            # routing hint only (see _forced above)
            self._forced = os.environ.get(  # lint: env-ok
                "NORNICDB_GRAPH_DEVICE", "auto") == "on"
        return self._forced

    def chain_topk(
        self,
        spec: Tuple,
        anchor: int,
        k_head: int,
        size_hint: int,
    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Device merge for ONE anchor of the per-friend top-k family:
        returns (friend_rows, term_rows) — globally ordered, already
        trimmed to ≤ k_head — or None for the host path. Concurrent
        calls sharing ``spec`` coalesce into one batched dispatch."""
        mode = graph_device_mode()
        if mode == "off" or k_head <= 0:
            return None
        if mode == "auto":
            if _cpu_backend() or size_hint < graph_device_min_n():
                return None
            # demand gate: a single-stream read never pays the b=1
            # dispatch; only coalescible concurrency routes on-device
            if self.inflight <= 1:
                return None
        hold = None
        if not _audit.tier_allowed(TIER_CHAIN):
            # shadow-parity quarantine: the chain rung steps down to
            # the host executor until the breach clears
            hold = "quarantine"
        elif not _audit.admission_allows(TIER_CHAIN):
            # admission posture (ISSUE 15): overload forces the chain
            # rung to the host executor to shrink device pressure
            hold = "admission"
        if hold is not None:
            _event("degrade_quarantine")
            _ledger(TIER_CHAIN, hold,
                    {"catalog_version": self.catalog.version})
            return None
        batcher = self._chain_batcher(spec)
        import time as _time

        t0 = _time.time()
        out = batcher.submit((int(anchor), int(k_head)))
        if out is not None:
            # rider-accurate attribution: this rider was answered by
            # the device chain rung (a None falls to the host path,
            # counted at the fast-path call site)
            _audit.record_served("graph", TIER_CHAIN,
                                 seconds=_time.time() - t0)
        return out

    def _chain_batcher(self, spec: Tuple) -> BatchCoalescer:
        key = ("chainb",) + spec
        with self._lock:
            b = self._batchers.get(key)
            if b is None:
                b = BatchCoalescer(
                    functools.partial(self._chain_batch, spec),
                    max_batch=64, surface="service:graph")
                self._batchers[key] = b
            return b

    def _chain_batch(self, spec: Tuple, items: List[Tuple[int, int]]):
        mode = graph_device_mode()
        none_all = [None] * len(items)
        if mode == "off":
            return none_all
        if mode == "auto" and len(items) < graph_device_min_b():
            _event("batch_below_min_b")
            _ledger(TIER_CHAIN, "min_batch")
            return none_all
        snap = self._chain_snapshot(spec)
        if snap is None:
            _event("degrade_stale")
            _ledger(TIER_CHAIN, "stale_snapshot",
                    {"catalog_version": self.catalog.version})
            return none_all
        import time as _time

        kh = max(k for _a, k in items)
        kp = pow2_bucket(kh)
        # frontier bucket: the snapshot-wide max degree, pow2-padded —
        # stable per snapshot, so batch composition can't churn compiles
        f = pow2_bucket(max(1, snap["max_deg"]))
        width = f * kp
        if snap["s"] * width >= _I32_MAX or width > 1 << 20:
            _event("degrade_rank_overflow")
            _ledger(TIER_CHAIN, "rank_overflow",
                    {"snapshot_version": snap["version"]})
            return none_all
        bsz = pow2_bucket(len(items))
        anchors = np.full(bsz, -1, dtype=np.int32)
        for i, (a, _k) in enumerate(items):
            anchors[i] = a
        jax = _jx()
        jnp = jax.numpy
        t0 = _time.perf_counter()
        try:
            fn = _chain_topk_fn(f, kp)
            sel_f, sel_t, sel_valid = fn(
                jnp.asarray(anchors), jnp.int32(kh),
                snap["indptr1"], snap["far1"], snap["s_indptr"],
                snap["s_nbr"], snap["s_rank"], snap["mid_ok"])
            sel_f = np.asarray(sel_f)
            sel_t = np.asarray(sel_t)
            sel_valid = np.asarray(sel_valid)
        except Exception:  # noqa: BLE001 — degrade, never fail the read
            _event("degrade_error")
            _ledger(TIER_CHAIN, "error",
                    {"snapshot_version": snap["version"]})
            return none_all
        dt = _time.perf_counter() - t0
        record_dispatch(KIND_CHAIN, bsz, f * 100_000 + kp, dt)
        if _cost.pricing_enabled():
            flops, byts = _cost.price_chain_topk(bsz, f, kp)
            _cost.record_query_cost(
                KIND_CHAIN, _cost.cost_name(self), len(items), flops, byts)
        self.dispatches += 1
        # freshness: a write that landed during the dispatch window
        # invalidated the snapshot under us — the host path must serve.
        # Per-etype delta key: only writes touching THIS program's
        # etypes (or the node axis) stale it; unrelated edge appends
        # during the dispatch window are fine (ISSUE 19).
        if self.catalog.etype_versions(snap["etypes"]) != snap["etv"]:
            _event("degrade_stale")
            _ledger(TIER_CHAIN, "stale_snapshot",
                    {"snapshot_etv": snap["etv"],
                     "catalog_version": self.catalog.version})
            return none_all
        out = []
        for i, (_a, k) in enumerate(items):
            nv = int(sel_valid[i].sum())
            take = min(k, nv)
            out.append((sel_f[i, :take].copy(), sel_t[i, :take].copy()))
        return out

    # -- strip aggregation (avg_friends_per_city family) ------------------

    def build_strip_view(
        self,
        etype1: str,
        g_side: str,
        p_label: Optional[str],
        etype2: str,
        dir2: str,
        f_label: Optional[str],
    ):
        """Device-built materialized strip view, installed into the
        catalog (which then serves reads and incremental maintenance
        exactly as if the host had built it). Returns the view or None
        (host builds instead). Exactness: all three arrays are integer
        counts computed as int32/f32 segment-sums with the 2^24 bound
        verified post-dispatch — equal to the host build bit-for-bit."""
        mode = graph_device_mode()
        if mode == "off" or etype1 == etype2:
            return None
        if mode == "auto" and _cpu_backend():
            return None  # host numpy wins the build on CPU (measured)
        cat = self.catalog
        key = (etype1, g_side, p_label, etype2, dir2, f_label)
        sv = cat.peek_strip_view(key)
        if sv is not None:
            return sv
        v0 = cat.version
        try:
            tbl1 = cat.edge_table(etype1)
            tbl2 = cat.edge_table(etype2)
            n = cat.n_nodes()
            e1, e2 = len(tbl1.src), len(tbl2.src)
            if mode == "auto" and (e1 + e2) < graph_device_min_n():
                return None
            if max(e1, e2, n) > self.MAX_ENTRIES or min(e1, e2) == 0:
                return None
            if e1 >= _EXACT_F32 or e2 >= _EXACT_F32:
                _event("degrade_exactness")
                return None
            g_e = tbl1.src if g_side == "src" else tbl1.dst
            p_e = tbl1.dst if g_side == "src" else tbl1.src
            keys2 = tbl2.src if dir2 == "out" else tbl2.dst
            far2 = tbl2.dst if dir2 == "out" else tbl2.src
            pmask_e = (cat.label_mask(p_label)[p_e] if p_label is not None
                       else np.ones(e1, dtype=bool))
            fmask2 = (cat.label_mask(f_label)[far2] if f_label is not None
                      else np.ones(e2, dtype=bool))
        except (IndexError, ValueError):
            return None
        import time as _time

        jax = _jx()
        jnp = jax.numpy
        e1p, e2p, npad = pow2_bucket(e1), pow2_bucket(e2), pow2_bucket(n + 2)
        # pad: sentinel rows land on npad-1 (sliced away on decode)
        g_pad = np.full(e1p, npad - 1, np.int32)
        g_pad[:e1] = g_e
        p_pad = np.full(e1p, npad - 1, np.int32)
        p_pad[:e1] = p_e
        pm_pad = np.zeros(e1p, bool)
        pm_pad[:e1] = pmask_e
        k2_pad = np.full(e2p, npad - 1, np.int32)
        k2_pad[:e2] = keys2
        fm_pad = np.zeros(e2p, bool)
        fm_pad[:e2] = fmask2
        t0 = _time.perf_counter()
        try:
            fn = _strip_agg_fn(e1p, e2p, npad)
            deg_d, sum_d, nnz_d, deg_max, sum_max = fn(
                jnp.asarray(g_pad), jnp.asarray(p_pad), jnp.asarray(pm_pad),
                jnp.asarray(k2_pad), jnp.asarray(fm_pad))
            deg_max = float(deg_max)
            sum_max = float(sum_max)
            deg = np.asarray(deg_d)[:n].astype(np.int64)
            sum_deg = np.asarray(sum_d)[:n]
            nnz = np.asarray(nnz_d)[:n].astype(np.int64)
        except Exception:  # noqa: BLE001
            _event("degrade_error")
            return None
        dt = _time.perf_counter() - t0
        record_dispatch(KIND_AGG, max(e1p, e2p), npad, dt)
        if _cost.pricing_enabled():
            flops, byts = _cost.price_graph_agg(e1p, e2p, npad)
            _cost.record_query_cost(
                KIND_AGG, _cost.cost_name(self), 1, flops, byts)
        if deg_max >= _EXACT_F32 or sum_max >= _EXACT_F32:
            _event("degrade_exactness")
            return None
        from nornicdb_tpu.query.columnar import _StripView

        sv = _StripView(deg, np.rint(sum_deg).astype(np.int64), nnz)
        if not cat.install_strip_view(key, sv, v0):
            _event("degrade_stale")
            return None
        _event("strip_view_device_built")
        return sv

    # -- co-occurrence Gram (tag_cooccurrence family) ---------------------

    def gram_matmul(
        self, ma: np.ndarray, mb: np.ndarray
    ) -> Optional[np.ndarray]:
        """Device contraction ``Ma^T @ Mb`` for the co-occurrence
        family. Caller (columnar.cooc_gram) already holds the 2^24
        exactness bound, under which f32 0/1-integer matmuls are exact
        on host AND device — equal integers, no parity caveat. Returns
        the f32 product or None (host matmul instead)."""
        mode = graph_device_mode()
        if mode == "off":
            return None
        nmid = ma.shape[0]
        if mode == "auto" and (_cpu_backend()
                               or nmid < graph_device_min_n()):
            return None
        if ma.size == 0 or mb.size == 0:
            return None
        import time as _time

        jax = _jx()
        jnp = jax.numpy
        # pad BOTH axes to pow2 (zero rows/columns cannot change the
        # live region of Ma^T @ Mb) so a growing label axis re-uses the
        # bucketed program instead of retracing per distinct width
        mp = pow2_bucket(nmid)
        ac, bc = pow2_bucket(ma.shape[1]), pow2_bucket(mb.shape[1])
        ma_p = np.zeros((mp, ac), np.float32)
        ma_p[:nmid, :ma.shape[1]] = ma
        if mb is ma and bc == ac:
            mb_p = ma_p
        else:
            mb_p = np.zeros((mp, bc), np.float32)
            mb_p[:nmid, :mb.shape[1]] = mb
        t0 = _time.perf_counter()
        try:
            c = np.asarray(_gram_fn(mp)(jnp.asarray(ma_p),
                                        jnp.asarray(mb_p)))
            c = c[:ma.shape[1], :mb.shape[1]]
        except Exception:  # noqa: BLE001
            _event("degrade_error")
            return None
        dt = _time.perf_counter() - t0
        record_dispatch(KIND_GRAM, mp,
                        pow2_bucket(max(ma.shape[1], mb.shape[1], 1)), dt)
        if _cost.pricing_enabled():
            flops, byts = _cost.price_cooc_gram(
                mp, ma.shape[1], mb.shape[1])
            _cost.record_query_cost(
                KIND_GRAM, _cost.cost_name(self), 1, flops, byts)
        return c

    # -- fused traverse-then-rank (graph+vector) --------------------------

    def _rank_snapshot(self, hops: Tuple[Tuple[str, str], ...],
                       index) -> Optional[Dict[str, Any]]:
        meta = index.view_meta()
        if meta is None:
            return None
        mutations, _compactions = meta
        key = ("rank", hops, id(index))
        snap = self._get_snap(key)
        if snap is not None:
            if snap.get("mutations") == mutations:
                return snap
            snap = None  # index moved: rebuild the row->slot join
        cat = self.catalog
        v0 = cat.version
        # per-etype delta key (ISSUE 19): the fused program touches
        # only the hop etypes' CSRs and the node axis
        etypes = tuple(et for et, _d in hops)
        etv0 = cat.etype_versions(etypes)
        jax = _jx()
        jnp = jax.numpy
        try:
            n = cat.n_nodes()
            nodes = cat.nodes()
            per_hop = []
            for etype, direction in hops:
                tbl = cat.edge_table(etype)
                indptr, order = tbl.csr(direction, n)
                far = (tbl.dst if direction == "out" else tbl.src)[order]
                if len(far) > self.MAX_ENTRIES or len(indptr) != n + 1:
                    return None
                per_hop.append((indptr, far))
            slots = index.slots_of([nd.id for nd in nodes],
                                   expect_mutations=mutations)
            if slots is None:
                return None
        except (IndexError, ValueError):
            return None
        snap = {
            "version": v0,
            "etypes": etypes,
            "etv": etv0,
            "mutations": mutations,
            "n": n,
            "hops": [
                (jnp.asarray(ip, jnp.int32), jnp.asarray(fr, jnp.int32),
                 int((ip[1:] - ip[:-1]).max()) if n else 0)
                for ip, fr in per_hop
            ],
            "slot_of_row": jnp.asarray(
                np.asarray(slots, dtype=np.int32)),
            "device_bytes": 4 * sum(len(ip) + len(fr)
                                    for ip, fr in per_hop) + 4 * n,
            "host_bytes": 0,
            "rows": sum(len(fr) for _ip, fr in per_hop),
        }
        if not self._put_snap(key, snap):
            return None
        return snap

    def traverse_rank(
        self,
        anchors: Sequence[int],
        hops: Sequence[Tuple[str, str]],
        queries: np.ndarray,
        k: int,
        index,
    ) -> Optional[List[List[Tuple[int, float]]]]:
        """ONE fused program: chain expansion from ``anchors`` along
        ``hops`` (1 or 2 (etype, direction) stages), frontier dedup,
        cosine scoring against the vector index's device matrix, top-k.
        Returns per-anchor [(catalog_node_row, score)] or None (host
        fallback). The workload no single baseline serves: graph
        traversal and vector ranking in one dispatch."""
        mode = graph_device_mode()
        if mode == "off" or not hops or len(hops) > 2 or k <= 0:
            return None
        if mode == "auto" and _cpu_backend() \
                and len(anchors) < graph_device_min_b():
            # measured on CPU: the fused dispatch beats the host
            # fallback ~2x at b=16 but loses ~4x at b=1
            return None
        hold = None
        if not _audit.tier_allowed(TIER_RANK):
            hold = "quarantine"
        elif not _audit.admission_allows(TIER_RANK):
            hold = "admission"
        if hold is not None:
            _event("degrade_quarantine")
            _ledger(TIER_RANK, hold,
                    {"catalog_version": self.catalog.version})
            return None
        hops_t = tuple((str(e), str(d)) for e, d in hops)
        snap = self._rank_snapshot(hops_t, index)
        if snap is None:
            _event("degrade_stale")
            _ledger(TIER_RANK, "stale_snapshot",
                    {"catalog_version": self.catalog.version})
            return None
        # the lease holds the index lock until the rank is DISPATCHED:
        # the next write's refresh donates the device arrays
        with index.device_lease() as lease:
            if lease.view is None:
                return None
            matrix, valid, _ext_ids, mutations, _comp = lease.view
            if mutations != snap["mutations"]:
                _event("degrade_stale")
                _ledger(TIER_RANK, "stale_snapshot",
                        {"snapshot_mutations": snap["mutations"],
                         "index_mutations": mutations})
                return None
            import time as _time

            jax = _jx()
            jnp = jax.numpy
            f1 = pow2_bucket(max(1, snap["hops"][0][2]))
            f2 = pow2_bucket(max(1, snap["hops"][1][2])) \
                if len(hops_t) == 2 else 0
            frontier = f1 * max(f2, 1)
            if frontier > 1 << 18:
                _event("degrade_rank_overflow")
                _ledger(TIER_RANK, "rank_overflow",
                        {"snapshot_version": snap["version"]})
                return None
            kp = pow2_bucket(min(k, max(frontier, 1)))
            bsz = pow2_bucket(len(anchors))
            a = np.full(bsz, -1, dtype=np.int32)
            a[:len(anchors)] = np.asarray(anchors, dtype=np.int32)
            q = np.zeros((bsz, queries.shape[1]), np.float32)
            q[:len(anchors)] = queries
            ip1, fr1, _d1 = snap["hops"][0]
            if f2:
                ip2, fr2, _d2 = snap["hops"][1]
            else:
                ip2, fr2 = ip1, fr1  # unused when f2 == 0
            t0 = _time.perf_counter()
            try:
                vals, sel_rows = _traverse_rank_fn(f1, f2, kp)(
                    jnp.asarray(a), jnp.asarray(q), ip1, fr1, ip2, fr2,
                    snap["slot_of_row"], matrix, valid,
                    jnp.int32(snap["n"]))
                dims = int(matrix.shape[1])
                del matrix, valid
                lease.release()
                vals = np.asarray(vals)
                sel_rows = np.asarray(sel_rows)
            except Exception:  # noqa: BLE001
                _event("degrade_error")
                _ledger(TIER_RANK, "error",
                        {"snapshot_version": snap["version"]})
                return None
        dt = _time.perf_counter() - t0
        record_dispatch(KIND_RANK, bsz, f1 * 100_000 + kp, dt)
        if _cost.pricing_enabled():
            flops, byts = _cost.price_traverse_rank(
                bsz, frontier, dims, kp)
            _cost.record_query_cost(
                KIND_RANK, _cost.cost_name(self), len(anchors), flops,
                byts)
        self.dispatches += 1
        # per-etype recheck (ISSUE 19): only hop-etype writes or
        # node-axis changes during the dispatch window stale this
        if self.catalog.etype_versions(snap["etypes"]) != snap["etv"] \
                or index.view_meta() != (snap["mutations"], _comp):
            _event("degrade_stale")
            _ledger(TIER_RANK, "stale_snapshot",
                    {"snapshot_etv": snap["etv"],
                     "catalog_version": self.catalog.version})
            return None
        out: List[List[Tuple[int, float]]] = []
        for i in range(len(anchors)):
            hits = [(int(r), float(v))
                    for v, r in zip(vals[i], sel_rows[i])
                    if np.isfinite(v)][:k]
            out.append(hits)
        return out

    # -- shared whole-graph CSR snapshot (PageRank / degree counts) -------

    def pagerank_snapshot(self) -> Optional[Dict[str, Any]]:
        """The whole-graph columnar edge snapshot — built EXACTLY like
        ``ops.graph.graph_snapshot`` (same storage iteration order, so
        PageRank stays bit-identical to the uncached implementation) —
        cached per catalog version together with its one-time device
        transfer. Repeat ``apoc.algo.pagerank`` calls stop re-listing
        the store and re-shipping edge arrays per call."""
        key = ("pagerank",)
        snap = self._get_snap(key)
        if snap is not None:
            return snap
        from nornicdb_tpu.ops.graph import graph_snapshot

        cat = self.catalog
        v0 = cat.version
        try:
            src, dst, ids = graph_snapshot(cat.storage)
        except Exception:  # noqa: BLE001 — engines without iteration
            return None
        if len(src) > self.MAX_ENTRIES:
            return None
        jnp = _jx().numpy
        snap = {
            "version": v0,
            "src": src,
            "dst": dst,
            "ids": ids,
            "dev_src": jnp.asarray(src, jnp.int32),
            "dev_dst": jnp.asarray(dst, jnp.int32),
            "device_bytes": 8 * len(src),
            "host_bytes": src.nbytes + dst.nbytes,
            "rows": len(src),
        }
        if not self._put_snap(key, snap):
            return None
        return snap

    def degree_counts(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(out_degree, in_degree) over the shared snapshot — one fused
        device pass, edge arrays shipped once per catalog version."""
        snap = self.pagerank_snapshot()
        if snap is None:
            return None
        from nornicdb_tpu.ops.graph import degree_counts

        out_d, in_d = degree_counts(
            snap["dev_src"], snap["dev_dst"], len(snap["ids"]))
        return np.asarray(out_d), np.asarray(in_d)

    def traverse_rank_host(
        self,
        anchors: Sequence[int],
        hops: Sequence[Tuple[str, str]],
        queries: np.ndarray,
        k: int,
        index,
    ) -> List[List[Tuple[int, float]]]:
        """Host reference/fallback with the same contract: expand,
        dedup (ascending row order), score exactly, stable top-k."""
        from nornicdb_tpu.query.columnar import expand_hop

        cat = self.catalog
        n = cat.n_nodes()
        nodes = cat.nodes()
        out: List[List[Tuple[int, float]]] = []
        for i, anchor in enumerate(anchors):
            frontier = np.asarray([anchor], dtype=np.int32)
            for etype, direction in hops:
                tbl = cat.edge_table(etype)
                _rep, _erows, frontier = expand_hop(
                    tbl, frontier, direction, n)
            rows = np.unique(frontier)
            if len(rows) == 0:
                out.append([])
                continue
            ids = [nodes[int(r)].id for r in rows]
            vecs = []
            keep_rows = []
            for r, eid in zip(rows.tolist(), ids):
                v = index.get(eid)
                if v is not None:
                    vecs.append(v)
                    keep_rows.append(r)
            if not vecs:
                out.append([])
                continue
            m = np.stack(vecs).astype(np.float32)
            qv = queries[i].astype(np.float32)
            qn = qv / max(float(np.linalg.norm(qv)), 1e-12)
            scores = m @ qn
            order = np.argsort(-scores, kind="stable")[:k]
            out.append([(int(keep_rows[j]), float(scores[j]))
                        for j in order])
        return out
