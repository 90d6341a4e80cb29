"""Device-resident BM25: batched Okapi scoring over CSR postings in HBM.

``BM25Index.search`` is a single-query NumPy loop under the index lock —
every hybrid query serializes behind it and none of the lexical math
ever touches the accelerator. This module closes that host/device
boundary (the dominant hybrid-search bottleneck per the GPU
vector-search taxonomy, arXiv:2602.16719) the same way ``cagra.py``
closed it for graph ANN:

- **Layout**: the live postings flatten into device-resident CSR
  columns — per-term offset ranges over ``(doc_row, tf)`` pairs — plus
  ``doc_len`` and ``alive`` vectors over a dense, capacity-padded row
  space. Terms are sorted so host and device accumulate per-doc scores
  in the same order.
- **Scoring** (one jitted program per pow2 bucket): the host plans a
  query batch as each unique term's posting RANGE and an idf-weighted
  ``[B, U]`` selection matrix (idf comes from the index's *incremental
  live-df counters*, so deletes correct df without touching the
  snapshot); the device walks the ranges' postings in fixed chunks,
  applies the vectorized Okapi tf normalization, scatters them into a
  ``[U, C]`` matrix, multiplies into a dense ``[B, C]`` score matrix
  and takes one top-k. Batch and k pad to power-of-two buckets
  (``microbatch.pow2_bucket``); U follows the batch's distinct scoring
  terms in two steps a batch bucket (``lex_rows``: ``half`` = 8 x B,
  no less than 16, or ``full`` = 16 x B, whichever is the smaller that
  holds them) and the number of postings is no shape at all, so the XLA
  compile universe is (B, k, rows in {half, full}), all of it compiled
  before traffic by ``SearchService.warm_hybrid``. A batch with more
  terms than ``full`` still takes the next power of two, which compiles
  on the request that needs it (ROADMAP S8).
- **Sharding** (``shard_map``): postings, doc vectors and the planned
  entry columns row-shard over the ``data`` mesh axis; each shard
  scores its local rows, then one all-gather + top-k merges shard-local
  winners — bit-identical to the single-device reference merge
  (``ops.similarity.concat_topk``), same collective pattern as
  ``cagra`` and ``parallel.mesh.sharded_cosine_topk``.
- **Freshness** (PR 2 discipline): the snapshot records the index's
  mutation generation; churn beyond ``rebuild_stale_frac`` kicks a
  background rebuild while the stale snapshot keeps serving. Tombstones
  are live-filtered through a per-slot alive refresh (df corrected via
  the live counters), and adds/updates ride the index's capped
  changelog into an exact host delta side-scan — read-your-writes
  without a rebuild. A trimmed changelog or a slot-remapping compaction
  falls back to the host index until the fresh snapshot lands.
"""

from __future__ import annotations

import functools
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.obs import REGISTRY, declare_kind, record_dispatch
from nornicdb_tpu.ops.similarity import EXACT, NEG_INF, concat_topk, pad_dim
from nornicdb_tpu.search.bm25 import B, K1, BM25Index, tokenize
from nornicdb_tpu.search.microbatch import pow2_bucket

# lifecycle + freshness decisions of the device lexical snapshot — the
# same observability contract the cagra tier established
_LEX_C = REGISTRY.counter(
    "nornicdb_device_bm25_events_total",
    "Device BM25 snapshot lifecycle and per-search freshness decisions",
    labels=("event",))

# which row bucket each planned batch took (lex_rows): `over` is a
# batch whose program nothing warmed
_PLAN_ROWS_C = REGISTRY.counter(
    "nornicdb_device_bm25_plan_rows_total",
    "Planned lexical batches by the row bucket of their [B, U] program",
    labels=("bucket",))
for _bucket in ("half", "full", "over"):
    _PLAN_ROWS_C.labels(_bucket)  # every series reads from 0

declare_kind("bm25_score")


class PlanOverflow(Exception):
    """The (U+1)*C cell space of a planned batch would exceed int32
    (jax's default index width). Callers serve the batch host-exact
    instead."""


class SnapshotStale(Exception):
    """A compaction remapped the host slot space after this snapshot's
    freshness checks began — slot-keyed reads can no longer be trusted
    and the caller must serve host-exact (a rebuild is already due)."""


# ---------------------------------------------------------------------------
# pure scoring kernels (shared with the fused hybrid pipeline)
# ---------------------------------------------------------------------------


# postings scored per step of the device loop (below): the entries of a
# batch are walked in chunks of this many, so what a dispatch costs
# follows the postings its terms have, and no program's shape does
LEX_CHUNK = 65536

# unique-term rows of a program, by its batch bucket B: the [B, U]
# selection matrix and the flat [U x C] scatter target are compiled at
# one of TWO widths, and plan() takes the smaller that holds the batch's
# distinct scoring terms (lex_rows, below):
#   half  LEX_TERMS_HALF x B, no less than LEX_ROWS_MIN
#   full  LEX_TERMS_PER_QUERY x B
# What a dispatch costs grows with U whatever the postings are (the
# scatter's target, its layout conversion for the product, the product
# and the zero-fill), so the rows follow the terms: at a million passages
# a batch of 12.8 six-term riders (~77 terms) runs in 105 ms at U = 128
# where U = 256 takes 141 (v5e, PR 28). `half` holds a batch of queries
# that average eight terms, `full` every batch of queries of up to
# sixteen; both are warmed (SearchService.warm_hybrid), so which one a
# batch takes costs no compile. Past `full` U is the next power of two
# that holds the terms, a program nothing warmed (ROADMAP S8)
LEX_TERMS_PER_QUERY = 16
LEX_TERMS_HALF = 8
LEX_ROWS_MIN = 16


def row_buckets(b_bucket: int) -> Tuple[int, int]:
    """(half, full): the two widths U the batch bucket has programs
    for. At B = 1 they are one."""
    b = max(b_bucket, 1)
    return max(LEX_ROWS_MIN, LEX_TERMS_HALF * b), LEX_TERMS_PER_QUERY * b


def lex_rows(n_terms: int, b_bucket: int) -> Tuple[int, str]:
    """(U, bucket) for a batch of ``n_terms`` distinct scoring terms in
    the batch bucket ``b_bucket``: the smaller of ``half`` and ``full``
    that holds them, else (``over``) the next power of two."""
    half, full = row_buckets(b_bucket)
    if n_terms <= half:
        return half, "half"
    if n_terms <= full:
        return full, "full"
    return pow2_bucket(n_terms), "over"


def bm25_dense_scores(
    tstart: jnp.ndarray,  # [U] int32 first posting of each unique term
    tlen: jnp.ndarray,  # [U] int32 postings of each unique term (0 = pad)
    sel: jnp.ndarray,  # [B, U] f32 idf-weighted term-selection matrix
    post_doc: jnp.ndarray,  # [Pcap] int32 doc row per posting
    post_tf: jnp.ndarray,  # [Pcap] f32 OR uint16 term freq per posting
    doc_len: jnp.ndarray,  # [C] f32 OR uint16
    alive_f: jnp.ndarray,  # [C] f32 {0,1}
    avgdl: jnp.ndarray,  # scalar f32
) -> jnp.ndarray:
    """Dense BM25 scores [B, C]; rows with no matching live term (and
    padding terms, whose ranges are empty and whose sel columns are
    all-zero) come out NEG_INF.

    The aggregation is term-deduplicated across the batch: postings
    scatter ONCE per unique query term into a [U, C] tf-norm matrix
    (unique indices — each posting owns its (term, doc) cell), and the
    per-query accumulation is one idf-weighted [B,U]x[U,C] matmul. A
    coalesced batch whose queries share terms — the common case under
    zipfian traffic — thus pays the scatter once per term, not once per
    (query, term): the device dispatch gets CHEAPER per query as the
    MicroBatcher coalesces harder. Okapi contributions are strictly
    positive, so `score > 0` IS the touched-by-a-query-term mask.

    The host hands over each unique term's posting RANGE, not its
    postings: the flat entry space (term 0's postings, then term 1's,
    ...) is walked here in chunks of ``LEX_CHUNK`` by a loop whose trip
    count is the batch's own, so neither the host nor the program's
    shape sees the number of entries (at a million documents a batch of
    32 queries has millions, and a power-of-two bucket of them was one
    more compiled program per bucket)."""
    u = sel.shape[1]
    c = doc_len.shape[0]
    ch = min(LEX_CHUNK, c)
    ends = jnp.cumsum(tlen)
    total = ends[-1]
    # entry j of term t is posting tstart[t] + (j - begin[t]): the shift
    # tstart - begin steps at every term's end, and so does t, so both
    # come from ONE [chunk, U] comparison of j with the ends, summed
    # along U. No table is gathered from: a 65,536-wide gather out of a
    # 256-entry table cost the chip as much as the scatter itself
    # (0.77 s of a 3.0 s fused second, v5e, PR 28)
    shift = tstart - (ends - tlen)
    shift_step = jnp.concatenate([shift[:1], shift[1:] - shift[:-1]])
    lane = jnp.arange(ch, dtype=jnp.int32)

    def chunk(i, m):
        j = i * ch + lane
        live = j < total
        past = j[:, None] >= ends[None, :]            # [chunk, U]
        t = jnp.sum(past, axis=1, dtype=jnp.int32)    # ends at or below j
        p = j + shift_step[0] + jnp.sum(
            jnp.where(past[:, :-1], shift_step[None, 1:], 0), axis=1,
            dtype=jnp.int32)
        p = jnp.where(live, p, 0)
        # cast AFTER the gather: tf and doc-len are integer counts, so
        # the quantized (uint16) CSR columns are exactly lossless below
        # 65536 — HBM holds 2-byte columns, the Okapi arithmetic stays
        # float32 bit-identical (PR 8 headroom; f32 columns pass
        # through unchanged)
        d = post_doc[p]
        tf = post_tf[p].astype(jnp.float32)
        dl = doc_len[d].astype(jnp.float32)
        tf_norm = tf * (K1 + 1.0) / (tf + K1 * (1.0 - B + B * dl / avgdl))
        # the tf-norm matrix is kept FLAT, cell (t, d) at t * C + d: the
        # chip scatters into one dimension, and a two-dimensional target
        # was converted there and back row by row on every step (0.35 s a
        # batch of 16 against 0.13 s, v5e, PR 28). Entries past the end
        # point past the last cell, each at an index of its own, and are
        # dropped: every index of a chunk is distinct and ascends, and
        # the scatter is told both
        return m.at[jnp.where(live, t * c + d, u * c + lane)].add(
            tf_norm, mode="drop",
            indices_are_sorted=True, unique_indices=True)

    m = jax.lax.fori_loop(0, (total + ch - 1) // ch, chunk,
                          jnp.zeros((u * c,), jnp.float32))
    # idf weights and tf-norms are float32 scores the host path ranks
    # on: the exact-tier matmul precision (ops/similarity.EXACT)
    dense = jnp.matmul(sel, m.reshape(u, c), precision=EXACT)
    return jnp.where((alive_f[None, :] > 0.0) & (dense > 0.0),
                     dense, NEG_INF)


@functools.partial(jax.jit, static_argnames=("k",))
def _bm25_topk(tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f,
               avgdl, k):
    dense = bm25_dense_scores(tstart, tlen, sel, post_doc, post_tf,
                              doc_len, alive_f, avgdl)
    return jax.lax.top_k(dense, k)


@functools.partial(jax.jit, static_argnames=("k_local",))
def _bm25_local_topk(tstart, tlen, sel, post_doc, post_tf, doc_len,
                     alive_f, avgdl, row_offset, k_local):
    """One shard's local top-k with globalized row ids — the building
    block of the single-device reference merge."""
    dense = bm25_dense_scores(tstart, tlen, sel, post_doc, post_tf,
                              doc_len, alive_f, avgdl)
    s, i = jax.lax.top_k(dense, k_local)
    return s, i + row_offset


@functools.partial(
    jax.jit, static_argnames=("k", "mesh_holder"))
def _sharded_bm25_impl(tstart, tlen, sel, post_doc, post_tf, doc_len,
                       alive_f, avgdl, k, mesh_holder):
    from jax.sharding import PartitionSpec as P

    from nornicdb_tpu.parallel.mesh import shard_map_unchecked

    mesh = mesh_holder.mesh
    n_shards = mesh.shape["data"]
    c_local = doc_len.shape[0] // n_shards
    k_local = min(k, c_local)

    def local_fn(tstart_s, tlen_s, sel_r, pd_s, pt_s, dl_s, al_s, avg_r):
        dense = bm25_dense_scores(tstart_s, tlen_s, sel_r, pd_s, pt_s,
                                  dl_s, al_s, avg_r)
        s, i = jax.lax.top_k(dense, k_local)
        shard = jax.lax.axis_index("data")
        gi = i + shard * c_local
        all_s = jax.lax.all_gather(s, "data", axis=1, tiled=True)
        all_i = jax.lax.all_gather(gi, "data", axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(all_s, k)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    return shard_map_unchecked(
        local_fn,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P("data"), P("data"),
                  P("data"), P("data"), P()),
        out_specs=(P(), P()),
    )(tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f, avgdl)


# ---------------------------------------------------------------------------
# the device snapshot index
# ---------------------------------------------------------------------------


class DeviceBM25:
    """Batched device BM25 over a wrapped (host) :class:`BM25Index`.

    The host index stays the mutable source of truth; the device
    snapshot is an immutable CSR build over it, kept fresh by alive
    refreshes + exact delta side-scans and rebuilt in the background
    once churn crosses ``rebuild_stale_frac``. Below ``min_n`` live
    docs search serves from the host index (one lock-held NumPy pass
    beats any device dispatch at tiny N)."""

    def __init__(
        self,
        bm25: BM25Index,
        n_shards: int = 1,
        min_n: int = 256,
        rebuild_stale_frac: float = 0.1,
        build_inline: bool = True,
        quant_cols: Optional[bool] = None,
    ):
        self.bm25 = bm25
        self.n_shards = max(1, n_shards)
        self.min_n = min_n
        if quant_cols is None:
            # captured once at construction (init-time env read, PR 14
            # hot-path contract): store the tf/doc-len CSR columns as
            # uint16 — exactly lossless for integer counts below 65536;
            # a corpus exceeding that falls back to f32 per column
            from nornicdb_tpu.config import env_bool

            quant_cols = env_bool("BM25_QUANT", True)
        self.quant_cols = bool(quant_cols)
        self.rebuild_stale_frac = rebuild_stale_frac
        # build_inline=False defers even the first build to a background
        # thread (read-path wiring: the host index serves until the
        # snapshot is ready); True blocks once — the right call in
        # tests/benches needing determinism.
        self.build_inline = build_inline
        self._snap: Optional[Dict[str, Any]] = None
        self._build_lock = threading.Lock()
        self._rebuilding = False
        self._rebuild_started = 0.0  # backlog age for /readyz + gauges
        self._rebuild_flag_lock = threading.Lock()
        self._alive_lock = threading.Lock()
        self._map_lock = threading.Lock()
        self._delta_cache: Optional[Tuple] = None
        # per-thread (nnz, unique_terms) from the latest plan() on this
        # thread — cost pricing reads it instead of re-deriving the
        # unique-term set and df stats on the hot path
        self._plan_cost = threading.local()
        self.builds = 0

    # -- build ------------------------------------------------------------

    def build(self) -> bool:
        """(Re)build the device snapshot. False when below ``min_n``
        (search stays on the host index)."""
        with self._build_lock:
            return self._build_locked()

    def _build_locked(self) -> bool:
        gen = self.bm25.mut_gen
        snap = self._snap
        if snap is not None and snap["built_gen"] == gen:
            return True  # raced another builder; already fresh
        base = self.bm25.csr_snapshot()
        n = len(base["row_ids"])
        if n < self.min_n:
            self._snap = None
            return False
        s_n = self.n_shards
        base_rows = -(-n // s_n)  # ceil
        c_local = pad_dim(base_rows)
        offsets = base["offsets"]
        post_doc = base["post_doc"]
        post_tf = base["post_tf"]
        n_terms = len(base["terms"])

        if s_n == 1:
            off_sh = offsets[None, :]
            doc_parts = [post_doc]
            tf_parts = [post_tf]
        else:
            # split every term's (ascending-row) posting range at the
            # shard boundaries; rows become shard-local
            off_sh = np.zeros((s_n, n_terms + 1), dtype=np.int64)
            doc_lists: List[List[np.ndarray]] = [[] for _ in range(s_n)]
            tf_lists: List[List[np.ndarray]] = [[] for _ in range(s_n)]
            edges = np.asarray(
                [sh * base_rows for sh in range(s_n + 1)], dtype=np.int64)
            for ti in range(n_terms):
                lo, hi = offsets[ti], offsets[ti + 1]
                docs = post_doc[lo:hi]
                tfs = post_tf[lo:hi]
                bounds = np.searchsorted(docs, edges)
                for sh in range(s_n):
                    a, bnd = bounds[sh], bounds[sh + 1]
                    doc_lists[sh].append(docs[a:bnd] - sh * base_rows)
                    tf_lists[sh].append(tfs[a:bnd])
                    off_sh[sh, ti + 1] = off_sh[sh, ti] + (bnd - a)
            doc_parts = [
                np.concatenate(dl) if dl else np.zeros(0, np.int32)
                for dl in doc_lists]
            tf_parts = [
                np.concatenate(tl) if tl else np.zeros(0, np.float32)
                for tl in tf_lists]

        p_cap = pad_dim(max(max(len(d) for d in doc_parts), 1))
        pd_all = np.zeros((s_n, p_cap), dtype=np.int32)
        pt_all = np.zeros((s_n, p_cap), dtype=np.float32)
        for sh in range(s_n):
            pd_all[sh, : len(doc_parts[sh])] = doc_parts[sh]
            pt_all[sh, : len(tf_parts[sh])] = tf_parts[sh]

        doc_len_all = np.zeros(s_n * c_local, dtype=np.float32)
        alive_all = np.zeros(s_n * c_local, dtype=np.float32)
        row_ids_all: List[Optional[str]] = [None] * (s_n * c_local)
        slot_all = np.full(s_n * c_local, -1, dtype=np.int64)
        for sh in range(s_n):
            lo, hi = sh * base_rows, min((sh + 1) * base_rows, n)
            if lo >= hi:
                continue
            cnt = hi - lo
            doc_len_all[sh * c_local: sh * c_local + cnt] = \
                base["doc_len"][lo:hi]
            alive_all[sh * c_local: sh * c_local + cnt] = 1.0
            row_ids_all[sh * c_local: sh * c_local + cnt] = \
                base["row_ids"][lo:hi]
            slot_all[sh * c_local: sh * c_local + cnt] = \
                base["slots"][lo:hi]

        # quantized CSR columns (PR 8 headroom): tf and doc-len are
        # integer counts, so uint16 storage is EXACTLY lossless below
        # 65536 (the kernel casts to f32 after the gather; idf stays
        # exact from the host plan's live-df counters). A column whose
        # max clears the range keeps f32 — degrade is per column and
        # the score arithmetic is bit-identical either way.
        tf_dtype = np.float32
        dl_dtype = np.float32
        if self.quant_cols:
            if not pt_all.size or float(pt_all.max()) < 65536.0:
                tf_dtype = np.uint16
            if not doc_len_all.size or float(doc_len_all.max()) < 65536.0:
                dl_dtype = np.uint16
            if tf_dtype is np.uint16 or dl_dtype is np.uint16:
                _LEX_C.labels("quant_cols").inc()
        snap = {
            "n": n,
            "shards": s_n,
            "c_local": c_local,
            "built_compactions": base["compactions"],
            "vocab": base["vocab"],
            "terms": base["terms"],
            "off_sh": off_sh,
            "post_doc": jnp.asarray(pd_all.reshape(-1)),
            "post_tf": jnp.asarray(pt_all.reshape(-1).astype(tf_dtype)),
            "doc_len": jnp.asarray(doc_len_all.astype(dl_dtype)),
            "alive_np": alive_all,
            "alive": jnp.asarray(alive_all),
            "alive_gen": gen,
            "row_ids": row_ids_all,
            "slots": slot_all,
            "built_gen": gen,
            "cols_quant": 1.0 if (tf_dtype is np.uint16
                                  or dl_dtype is np.uint16) else 0.0,
        }
        if s_n > 1 and len(jax.devices()) >= s_n:
            # place the snapshot on the mesh ONCE (cagra discipline): a
            # persistent serving index never re-ships postings per batch
            from jax.sharding import NamedSharding, PartitionSpec

            from nornicdb_tpu.parallel.mesh import data_mesh

            mesh = data_mesh(s_n)
            snap["mesh"] = mesh
            sh1 = NamedSharding(mesh, PartitionSpec("data"))
            for key in ("post_doc", "post_tf", "doc_len", "alive"):
                snap[key] = jax.device_put(snap[key], sh1)
        self._snap = snap
        self.builds += 1
        _LEX_C.labels("build").inc()
        return True

    def _kick_background_rebuild(self) -> None:
        with self._rebuild_flag_lock:
            if self._rebuilding:
                return
            self._rebuilding = True
            self._rebuild_started = time.time()
        _LEX_C.labels("background_rebuild").inc()

        def run():
            from nornicdb_tpu import admission as _adm

            try:
                # background maintenance lane (ISSUE 15): any coalescer
                # ride from this thread seals behind interactive work
                with _adm.lane_scope(_adm.LANE_BACKGROUND):
                    self.build()
            finally:
                # same lock as the set above: an unguarded clear can
                # interleave with a concurrent kick's read-then-set
                with self._rebuild_flag_lock:
                    self._rebuilding = False
                    self._rebuild_started = 0.0

        t = threading.Thread(target=run, name="device-bm25-rebuild",
                             daemon=True)
        t.start()

    def ensure_snapshot(self) -> Optional[Dict[str, Any]]:
        """Current snapshot (possibly stale-but-correct), or None while
        the host index must serve. Mirrors cagra._ensure_graph."""
        snap = self._snap
        gen = self.bm25.mut_gen
        if snap is not None:
            churn = gen - snap["built_gen"]
            if churn > self.rebuild_stale_frac * max(snap["n"], 1):
                self._kick_background_rebuild()
            return snap
        if len(self.bm25) < self.min_n:
            return None
        if not self.build_inline:
            self._kick_background_rebuild()
            return self._snap
        self.build()
        return self._snap

    @property
    def snapshot_built(self) -> bool:
        return self._snap is not None

    def stats(self) -> Dict[str, Any]:
        snap = self._snap
        return {
            "n_alive": len(self.bm25),
            "snapshot_built": snap is not None,
            "snapshot_n": snap["n"] if snap else 0,
            "shards": snap["shards"] if snap else 0,
            "builds": self.builds,
            "cols_quant": snap.get("cols_quant", 0.0) if snap else 0.0,
        }

    def resource_stats(self) -> Dict[str, Any]:
        """Memory + freshness accounting for obs/resources.py: device
        bytes of the CSR columns (postings doc/tf + doc-len/alive
        vectors), the mutation-generation gap between the live host
        index and the snapshot, and the rebuild backlog state."""
        snap = self._snap
        dev_b = 0
        rows = 0
        capacity = 0
        if snap is not None:
            for key in ("post_doc", "post_tf", "doc_len", "alive"):
                dev_b += int(getattr(snap[key], "nbytes", 0) or 0)
            rows = snap["n"]
            capacity = snap["shards"] * snap["c_local"]
        gen = self.bm25.mut_gen
        gap = (gen - snap["built_gen"]) if snap is not None else 0
        started = self._rebuild_started
        return {
            "rows": rows,
            "capacity": capacity,
            "device_bytes": dev_b,
            # host-side offset table + row-id/slot columns
            "host_bytes": (
                (snap["off_sh"].nbytes + snap["slots"].nbytes
                 + 8 * len(snap["row_ids"])) if snap is not None else 0),
            "mutation_gap": gap,
            "rebuild_in_flight": 1.0 if self._rebuilding else 0.0,
            "rebuild_backlog_s": (
                round(time.time() - started, 3)
                if self._rebuilding and started else 0.0),
            "builds": self.builds,
        }

    # -- shared snapshot plumbing -----------------------------------------

    def row_map(self, snap: Dict[str, Any], name: str, token: Any,
                derive) -> Optional[jnp.ndarray]:
        """Memoized ``snapshot lex row -> foreign row`` device map.

        The fused hybrid tiers join lexical candidates to another
        index's row space — the brute slot space (``l2v``, matmul tier)
        or the CAGRA graph row space (``l2g``, walk tier). Both maps
        live ON the snapshot dict under one lock, keyed by ``token``
        (the foreign index's generation: brute mutation counter, graph
        build sequence — MONOTONE integers, which is what lets the
        publish step below refuse cross-generation overwrites), so a
        snapshot rebuild drops every map with it and a foreign rebuild
        rebinds on the next batch instead of surviving stale.
        ``derive()`` returns the int32 host column or None when the
        foreign index moved mid-derivation (the caller retries next
        batch — a stale map can never mis-join silently).
        """
        with self._map_lock:
            maps = snap.setdefault("row_maps", {})
            cur = maps.get(name)
            if cur is not None and cur[0] == token:
                return cur[1]
        # derive OUTSIDE the lock: the l2g derivation is O(corpus)
        # host work + a device transfer, and holding the lock for it
        # would convoy every concurrent batch that only needs to READ
        # an already-cached map. Racing derivers duplicate rare work;
        # the double-check below keeps one winner.
        raw = derive()
        if raw is None:
            return None
        dev = jnp.asarray(np.asarray(raw, dtype=np.int32))
        if "mesh" in snap:
            from jax.sharding import NamedSharding, PartitionSpec

            dev = jax.device_put(
                dev, NamedSharding(snap["mesh"],
                                   PartitionSpec("data")))
        with self._map_lock:
            maps = snap.setdefault("row_maps", {})
            cur = maps.get(name)
            if cur is not None and cur[0] == token:
                return cur[1]  # raced another deriver; theirs serves
            if cur is not None and cur[0] > token:
                # a newer-generation map was published while we
                # derived: OUR batch still needs the map matching its
                # captured view, but storing it would evict the newer
                # one and force the next batch to re-derive
                return dev
            maps[name] = (token, dev)
            return dev

    # -- freshness --------------------------------------------------------

    def refresh_alive(self, snap: Dict[str, Any]) -> None:
        """Re-derive the device alive vector from per-SLOT liveness when
        the host index mutated. Slot-level (not ext-id) membership is
        load-bearing: a re-indexed doc tombstones its old slot while the
        ext id stays live — the old row must die here and the new one
        arrives via the delta side-scan, or results would carry both.
        Raises :class:`SnapshotStale` when a compaction remapped the
        slot space mid-request (the liveness read and the compaction
        check share one lock hold, so a resurrected slot id can never
        slip through)."""
        gen = self.bm25.mut_gen
        if snap["alive_gen"] == gen:
            return
        with self._alive_lock:
            if snap["alive_gen"] == gen:
                return
            alive = snap["alive_np"].copy()
            rows = np.nonzero(alive)[0]
            if rows.size:
                live = self.bm25.alive_slots(
                    snap["slots"][rows],
                    expect_compactions=snap["built_compactions"])
                if live is None:
                    raise SnapshotStale
                alive[rows] = live.astype(np.float32)
            dev = jnp.asarray(alive)
            if "mesh" in snap:
                from jax.sharding import NamedSharding, PartitionSpec

                dev = jax.device_put(
                    dev, NamedSharding(snap["mesh"],
                                       PartitionSpec("data")))
            snap["alive"] = dev
            snap["alive_gen"] = gen

    def delta_block(self, snap: Dict[str, Any]) -> Optional[List[str]]:
        """ext ids added/updated since the snapshot build (host
        side-scan scores them exactly). None = changelog trimmed or
        slots remapped — caller must serve host-exact and a rebuild is
        kicked. Memoized on the mutation counter."""
        m = self.bm25.mut_gen
        cached = self._delta_cache
        if cached is not None and cached[0] == m \
                and cached[1] == snap["built_gen"]:
            return cached[2]
        ids = self.bm25.changed_since(snap["built_gen"])
        self._delta_cache = (m, snap["built_gen"], ids)
        return ids

    # -- planning (host side of a batch) ----------------------------------

    def plan(
        self,
        snap: Dict[str, Any],
        token_rows: Sequence[Sequence[str]],
        b_bucket: int,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.float32]:
        """Plan a tokenized query batch: for each unique scoring term its
        posting range in every shard of the snapshot (first posting,
        count; both [shards * U], sharded like the snapshot), plus the
        [B, U] idf-weighted selection matrix and current avgdl.

        Terms are DEDUPED across the whole batch (each unique term's
        postings are walked once, however many coalesced queries share
        it) and idf comes from the incremental live-df counters, so
        deletes correct df without a rebuild. U follows the terms
        (``lex_rows``: the ``half`` or the ``full`` bucket of
        ``b_bucket``, or the next power of two that holds them), and
        nothing the size of the postings is built here: the device
        expands the ranges."""
        vocab = snap["vocab"]
        off_sh = snap["off_sh"]
        s_n = snap["shards"]
        uniq_all = sorted({t for row in token_rows for t in row})
        dfs, n_alive, avgdl = self.bm25.term_stats(uniq_all)
        self._plan_cost.stats = (float(sum(dfs.values())),
                                 len(uniq_all))
        n = max(n_alive, 1)
        # unique scoring terms, in sorted order (the host accumulation
        # order); their idf rides the selection matrix
        terms: List[str] = []
        idfs: List[np.float32] = []
        u_of: Dict[str, int] = {}
        for t in uniq_all:
            df = dfs.get(t, 0)
            if df > 0 and t in vocab:
                u_of[t] = len(terms)
                terms.append(t)
                idfs.append(np.float32(
                    math.log(1.0 + (n - df + 0.5) / (df + 0.5))))
        u_b, rows = lex_rows(len(terms), b_bucket)
        # the device's [U+1, C] tf-norm matrix is addressed in int32
        # (jax's default index width) — refuse to plan a batch whose
        # cell space would wrap
        if (u_b + 1) * snap["c_local"] > 2**31 - 1:
            raise PlanOverflow
        sel = np.zeros((b_bucket, u_b), dtype=np.float32)
        for qi, row in enumerate(token_rows):
            for t in set(row):
                ui = u_of.get(t)
                if ui is not None:
                    sel[qi, ui] = idfs[ui]
        tstart = np.zeros((s_n, u_b), dtype=np.int32)
        tlen = np.zeros((s_n, u_b), dtype=np.int32)   # pad terms: empty
        if terms:
            ti = np.fromiter((vocab[t] for t in terms), np.int64,
                             len(terms))
            tstart[:, : len(terms)] = off_sh[:, ti]
            tlen[:, : len(terms)] = off_sh[:, ti + 1] - off_sh[:, ti]
        self._plan_cost.shape = (int(tlen.sum()), len(terms), u_b)
        _PLAN_ROWS_C.labels(rows).inc()
        return (tstart.reshape(-1), tlen.reshape(-1), sel,
                np.float32(avgdl))

    def rare_terms(self, snap: Dict[str, Any], n: int) -> List[str]:
        """Up to ``n`` scoring terms of the snapshot with the fewest
        postings: what a warm-up plans when it needs a batch of many
        distinct terms and none of their work."""
        plen = np.diff(snap["off_sh"], axis=1).sum(axis=0)
        # twice as many as asked for: a term whose documents were all
        # deleted since the build scores nothing and is planned away
        order = np.argsort(plen, kind="stable")[: 2 * n]
        cand = [snap["terms"][int(i)] for i in order]
        dfs, _, _ = self.bm25.term_stats(cand)
        return [t for t in cand if dfs.get(t, 0) > 0][:n]

    # -- dispatch ---------------------------------------------------------

    def topk_device(
        self,
        snap: Dict[str, Any],
        token_rows: Sequence[Sequence[str]],
        k: int,
        b_bucket: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Scores + global row ids [b_bucket, k] for a tokenized batch
        (rows beyond len(token_rows) are planning no-ops)."""
        tstart, tlen, sel, avgdl = self.plan(snap, token_rows, b_bucket)
        args = (jnp.asarray(tstart), jnp.asarray(tlen), jnp.asarray(sel),
                snap["post_doc"], snap["post_tf"], snap["doc_len"],
                snap["alive"], jnp.float32(avgdl))
        s_n = snap["shards"]
        if s_n == 1:
            s, i = _bm25_topk(*args, k=k)
        elif "mesh" in snap and len(jax.devices()) >= s_n:
            from nornicdb_tpu.parallel.mesh import _MeshHolder

            s, i = _sharded_bm25_impl(
                *args, k=k, mesh_holder=_MeshHolder(snap["mesh"]))
        else:
            s, i = self._topk_shards_single_device(snap, args, k)
        return np.asarray(s), np.asarray(i)

    def _topk_shards_single_device(self, snap, args, k):
        """Reference merge for the sharded layout on one device: score
        each shard's local rows, concatenate shard-local winners in
        shard order (exactly the all-gather layout) and take one global
        top-k. The mesh path must be bit-identical to this."""
        tstart, tlen, sel, pd, pt, dl, al, avgdl = args
        s_n = snap["shards"]
        c_local = snap["c_local"]
        p_b = tstart.shape[0] // s_n
        p_cap = pd.shape[0] // s_n
        k_local = min(k, c_local)
        parts_s, parts_i = [], []
        for sh in range(s_n):
            s, i = _bm25_local_topk(
                tstart[sh * p_b:(sh + 1) * p_b],
                tlen[sh * p_b:(sh + 1) * p_b],
                sel,
                pd[sh * p_cap:(sh + 1) * p_cap],
                pt[sh * p_cap:(sh + 1) * p_cap],
                dl[sh * c_local:(sh + 1) * c_local],
                al[sh * c_local:(sh + 1) * c_local],
                avgdl, jnp.int32(sh * c_local),
                k_local=k_local)
            parts_s.append(s)
            parts_i.append(i)
        return concat_topk(parts_s, parts_i, k)

    # -- search -----------------------------------------------------------

    def search(self, query: str, k: int = 10) -> List[Tuple[str, float]]:
        return self.search_batch([query], k)[0]

    def search_batch(
        self, queries: Sequence[str], k: int = 10
    ) -> List[List[Tuple[str, float]]]:
        """Batched BM25 top-k; same contract as
        :meth:`BM25Index.search_batch`, so callers swap host and device
        paths freely. Serves host-exact whenever the snapshot is
        missing or its changelog was overrun."""
        queries = list(queries)
        if not queries:
            return []
        snap = self.ensure_snapshot()
        if snap is None:
            return self.bm25.search_batch(queries, k)
        delta = self.delta_block(snap)
        if delta is None:
            _LEX_C.labels("host_fallback_changelog").inc()
            self._kick_background_rebuild()
            return self.bm25.search_batch(queries, k)
        token_rows = [tokenize(q) for q in queries]
        b = len(queries)
        bb = pow2_bucket(b)
        c_total = snap["shards"] * snap["c_local"]
        kb = min(pow2_bucket(max(min(k, snap["n"]), 1)), c_total)
        t0 = time.time()
        try:
            self.refresh_alive(snap)
            s, i = self.topk_device(snap, token_rows, kb, bb)
        except SnapshotStale:
            _LEX_C.labels("host_fallback_compaction").inc()
            self._kick_background_rebuild()
            return self.bm25.search_batch(queries, k)
        except PlanOverflow:
            _LEX_C.labels("host_fallback_overflow").inc()
            return self.bm25.search_batch(queries, k)
        record_dispatch("bm25_score", bb, kb, time.time() - t0)
        # per-query cost: the CSR nnz actually gathered is the batch's
        # unique-term posting mass (the scatter runs once per unique
        # term), plus the [B, U] x [U, C] idf-weighted score matmul.
        # Best-effort and gated — pricing must never fail or slow a
        # search with telemetry off
        from nornicdb_tpu.obs import cost as _cost

        if _cost.pricing_enabled():
            try:
                nnz, u = self._plan_cost.stats  # stashed by plan()
                flops, byts = _cost.price_bm25(bb, nnz, u, c_total)
                _cost.record_query_cost(
                    "bm25_score", _cost.cost_name(self), b, flops, byts)
            except Exception:  # noqa: BLE001
                pass
        out = self._resolve(snap, s[:b], i[:b], min(k, kb))
        if delta:
            _LEX_C.labels("delta_merge").inc()
            out = self._merge_delta(out, delta, token_rows, k)
        return out

    def _resolve(self, snap, s, i, k_eff):
        row_ids = snap["row_ids"]
        out: List[List[Tuple[str, float]]] = []
        for r in range(s.shape[0]):
            hits: List[Tuple[str, float]] = []
            for c in range(s.shape[1]):
                if s[r, c] < 0.5 * NEG_INF:
                    break
                eid = row_ids[int(i[r, c])]
                if eid is None:
                    continue
                hits.append((eid, float(s[r, c])))
                if len(hits) >= k_eff:
                    break
            out.append(hits)
        return out

    def _merge_delta(self, rows, delta_ids, token_rows, k):
        """Exact-score docs indexed since the snapshot and merge them in
        (read-your-writes). An updated doc's old row died in the alive
        refresh, so drop any same-id device entry defensively and let
        the fresh host score stand. Stable sort keeps device-rank order
        on exact ties, matching the host reference's slot order."""
        dset = set(delta_ids)
        out: List[List[Tuple[str, float]]] = []
        for qi, hits in enumerate(rows):
            fresh = self.bm25.score_docs(token_rows[qi], delta_ids)
            merged = [(eid, sc) for eid, sc in hits if eid not in dset]
            merged.extend(sorted(fresh.items()))
            merged.sort(key=lambda kv: -kv[1])
            out.append(merged[:k])
        return out
