"""Device-resident graph ANN: CAGRA-style fixed-out-degree index.

The sub-linear indexes so far (HNSW, IVF-HNSW, IVF-PQ) are
pointer-chasing CPU walks; only brute force ran on the accelerator.
CAGRA (arxiv 2308.15136) shows the accelerator-native shape of graph
ANN: a *fixed* out-degree adjacency searched with wide, batched frontier
expansion — every step is a padded gather + one batched dot + one
top-k, which is exactly what the MXU + XLA pipeline wants and what
pointer-chasing is not.

Design:

- **Build** (host + device): a k-NN graph from the device brute-force
  kernel (chunked matmul top-k; the Pallas fused kernel when
  ``NORNICDB_PALLAS_TOPK=1``), then CAGRA-style rank-based reordering:
  keep the top ``degree/2`` forward edges by rank and fill the rest with
  rank-ordered *reverse* edges, which restores reachability that pure
  k-NN graphs lack on clustered data.
- **Search** (device, jitted): a batched greedy walk with a candidate
  pool of ``itopk`` entries per query. Each iteration expands the best
  ``search_width`` unexplored candidates, gathers their ``degree``
  neighbors (``[B, W*deg]``), hash-bitmask-checks the visited set,
  scores the fresh ones with one batched dot against the queries, and
  merges into the pool with one top-k. The iteration count is FIXED so
  one XLA compile serves every query at a given (batch, k) pow2 bucket
  (microbatch.pow2_bucket discipline — same as the brute path).
- **Sharding** (``shard_map``): base vectors and adjacency are
  row-sharded over the ``data`` mesh axis. Each shard runs the walk over
  its *local* subgraph, then one all-gather + top-k merges shard-local
  winners into the exact global pool union — the same collective
  pattern as ``parallel.mesh.sharded_cosine_topk``. A single-device
  reference path (per-shard walk + identical merge) exists for parity
  testing and for meshes smaller than the shard count.
- **Freshness**: the index wraps a ``BruteForceIndex`` (source of truth
  for vectors/ids). Deletes after a build are filtered out of results
  via live-membership checks; once the mutation churn since the build
  exceeds ``rebuild_stale_frac`` of the corpus the graph is rebuilt
  in-line. Below ``min_n`` rows the graph is never built and search
  delegates to the (already device-resident) brute kernel.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.obs import REGISTRY, declare_kind, record_dispatch
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.ops.similarity import (
    NEG_INF,
    concat_topk,
    cosine_topk_auto,
    l2_normalize,
    pad_dim,
)
from nornicdb_tpu.search.microbatch import pow2_bucket
from nornicdb_tpu.search.vector_index import BruteForceIndex, _use_pallas

_HASH_MULT = np.uint32(2654435761)  # Knuth multiplicative hash
# globally unique graph build sequence (GIL-atomic): consumers cache
# derived state per graph keyed on this, and a PER-INDEX counter would
# collide across indexes (two first builds both numbered 1) when a
# consumer rebinds from one index to another over the same corpus
_BUILD_SEQ = itertools.count(1)

# freshness machinery events: graph (re)builds, delta side-scans merged
# into walk results, and the exact-fallback reasons — the counters that
# make strategy-machine decisions observable (ISSUE 3)
_CAGRA_C = REGISTRY.counter(
    "nornicdb_cagra_events_total",
    "CAGRA index lifecycle and per-search freshness decisions",
    labels=("event",))

declare_kind("cagra_walk")


# ---------------------------------------------------------------------------
# the batched greedy walk (pure function; jitted below and traced inside
# shard_map for the sharded path)
# ---------------------------------------------------------------------------


def _walk_body(
    queries: jnp.ndarray,  # [B, D] L2-normalized
    matrix: jnp.ndarray,  # [C, D] L2-normalized, zero pad rows
    adj: jnp.ndarray,  # [C, deg] int32 row indices (pad rows -> 0)
    validf: jnp.ndarray,  # [C] float32 {0,1}
    k: int,
    iters: int,
    width: int,
    itopk: int,
    hash_bits: int,
    n_seeds: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-iteration batched greedy graph walk.

    Returns (scores [B,k], row ids [B,k]) best-first; slots that never
    filled carry scores <= NEG_INF (callers filter, same contract as
    ops.similarity).
    """
    b = queries.shape[0]
    c, deg = adj.shape
    p = itopk
    m = width * deg
    tbl = 1 << hash_bits

    def hbucket(ids):
        h = ids.astype(jnp.uint32) * _HASH_MULT
        return (h >> np.uint32(32 - hash_bits)).astype(jnp.int32)

    # -- seed round: score `n_seeds` strided rows with one small matmul
    # and keep the best `itopk` as the initial pool. A k-NN graph on
    # clustered data has almost no cross-cluster edges, so the walk can
    # only find what some seed's cluster reaches — the wide seed round
    # is what guarantees every sizable cluster gets an entry point.
    # Exactness of marking ALL scored seeds visited: the pool only ever
    # improves, so a row that lost the seed round (ranked > itopk among
    # seeds) can never belong to the final top-k for k <= itopk.
    # stride = c // s0 guarantees no wraparound dups when c >= s0; when
    # c < s0 the tail repeats and is masked to NEG_INF so a duplicate id
    # can never surface with a finite score.
    s0 = max(n_seeds, p)
    stride = max(1, c // s0)
    seed_ids = (jnp.arange(s0, dtype=jnp.int32) * stride) % c
    seed_unique = jnp.arange(s0) < c
    seed_s = queries @ matrix[seed_ids].T  # [B, S0]
    seed_ok = seed_unique[None, :] & (validf[seed_ids][None, :] > 0.0)
    seed_s = jnp.where(seed_ok, seed_s, NEG_INF)
    pool_s, pos0 = jax.lax.top_k(seed_s, p)
    pool_i = jnp.take_along_axis(
        jnp.broadcast_to(seed_ids[None, :], (b, s0)), pos0, axis=1)
    explored = jnp.zeros((b, p), dtype=bool)

    # visited hash-bitmask: [B, 2^hash_bits] bool. Collisions only ever
    # SKIP a node (slight recall loss), never duplicate one — insertion
    # sets the exact bucket of the inserted id.
    visited0 = jnp.zeros((tbl,), dtype=bool).at[hbucket(seed_ids)].set(True)
    visited = jnp.broadcast_to(visited0[None, :], (b, tbl))

    rows_b = jnp.arange(b, dtype=jnp.int32)[:, None]
    slot = jnp.arange(p, dtype=jnp.int32)
    mcol = jnp.arange(m, dtype=jnp.int32)
    # dup[i] = an equal id appears earlier in the same expansion batch
    earlier = (mcol[None, :] < mcol[:, None])[None, :, :]

    def body(_, carry):
        pool_s, pool_i, explored, visited = carry
        # frontier: best `width` unexplored pool entries
        f_s, f_pos = jax.lax.top_k(
            jnp.where(explored, NEG_INF, pool_s), width
        )  # [B, W]
        f_ids = jnp.take_along_axis(pool_i, f_pos, axis=1)
        explored = explored | jnp.any(
            slot[None, None, :] == f_pos[:, :, None], axis=1
        )
        f_ok = f_s > 0.5 * NEG_INF  # exhausted-pool slots expand nothing

        nbrs = adj[f_ids].reshape(b, m)  # [B, W*deg]
        nb_ok = jnp.repeat(f_ok, deg, axis=1)
        h = hbucket(nbrs)
        seen = jnp.take_along_axis(visited, h, axis=1)
        dup = jnp.any((nbrs[:, :, None] == nbrs[:, None, :]) & earlier, axis=2)
        fresh = nb_ok & ~seen & ~dup & (validf[nbrs] > 0.0)

        scores = jnp.einsum("bmd,bd->bm", matrix[nbrs], queries)
        scores = jnp.where(fresh, scores, NEG_INF)
        # max == OR for bool and is well-defined under duplicate buckets
        # (two neighbors of one query hashing to the same word) — a
        # plain .set would leave the winner undefined and could let a
        # pool member be re-inserted as a finite-score duplicate
        visited = visited.at[rows_b, h].max(fresh)

        all_s = jnp.concatenate([pool_s, scores], axis=1)
        all_i = jnp.concatenate([pool_i, nbrs], axis=1)
        all_e = jnp.concatenate(
            [explored, jnp.zeros((b, m), dtype=bool)], axis=1
        )
        pool_s, pos = jax.lax.top_k(all_s, p)
        pool_i = jnp.take_along_axis(all_i, pos, axis=1)
        explored = jnp.take_along_axis(all_e, pos, axis=1)
        return pool_s, pool_i, explored, visited

    pool_s, pool_i, _, _ = jax.lax.fori_loop(
        0, iters, body, (pool_s, pool_i, explored, visited)
    )
    top_s, pos = jax.lax.top_k(pool_s, k)
    top_i = jnp.take_along_axis(pool_i, pos, axis=1)
    return top_s, top_i


_cagra_walk = functools.partial(
    jax.jit,
    static_argnames=("k", "iters", "width", "itopk", "hash_bits",
                     "n_seeds"),
)(_walk_body)


# ---------------------------------------------------------------------------
# sharded walk: per-shard local walk + one all-gather top-k merge, the
# same collective pattern (and the same _MeshHolder static-arg trick) as
# parallel.mesh.sharded_cosine_topk
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "iters", "width", "itopk", "hash_bits",
                     "n_seeds", "mesh_holder"),
)
def _sharded_walk_impl(
    queries, matrix, adj, validf, k, iters, width, itopk, hash_bits,
    n_seeds, mesh_holder,
):
    from jax.sharding import PartitionSpec as P

    from nornicdb_tpu.parallel.mesh import shard_map_unchecked

    mesh = mesh_holder.mesh
    n_shards = mesh.shape["data"]
    shard_rows = matrix.shape[0] // n_shards

    def local_walk(q, m, a, v):
        # q replicated; m/a/v are this shard's local rows + LOCAL adjacency
        s, i = _walk_body(q, m, a, v, k, iters, width, itopk, hash_bits,
                          n_seeds)
        shard = jax.lax.axis_index("data")
        gi = i + shard * shard_rows
        all_s = jax.lax.all_gather(s, "data", axis=1, tiled=True)
        all_i = jax.lax.all_gather(gi, "data", axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(all_s, k)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    return shard_map_unchecked(
        local_walk,
        mesh=mesh,
        in_specs=(P(), P("data", None), P("data", None), P("data")),
        out_specs=(P(), P()),
    )(queries, matrix, adj, validf)


def sharded_cagra_walk(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    adj: jnp.ndarray,
    validf: jnp.ndarray,
    k: int,
    iters: int,
    width: int,
    itopk: int,
    hash_bits: int,
    n_seeds: int = 1024,
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-device CAGRA search: row-shard vectors + local adjacency
    over the mesh's ``data`` axis, walk per shard, one all-gather merge.
    ``adj`` must hold SHARD-LOCAL indices and ``matrix.shape[0]`` must
    divide evenly by the shard count."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nornicdb_tpu.parallel.mesh import _MeshHolder, data_mesh

    mesh = mesh or data_mesh()
    n = mesh.shape["data"]
    if matrix.shape[0] % n != 0:
        raise ValueError(
            f"capacity {matrix.shape[0]} not divisible by {n} shards")
    matrix = jax.device_put(matrix, NamedSharding(mesh, P("data", None)))
    adj = jax.device_put(adj, NamedSharding(mesh, P("data", None)))
    validf = jax.device_put(validf, NamedSharding(mesh, P("data")))
    queries = jax.device_put(queries, NamedSharding(mesh, P()))
    return _sharded_walk_impl(
        queries, matrix, adj, validf, k, iters, width, itopk, hash_bits,
        n_seeds, _MeshHolder(mesh),
    )


def merge_delta_hits(
    hits: Sequence[Tuple[str, float]],
    delta_ids: Sequence[str],
    delta_scores,
    k: int,
) -> List[Tuple[str, float]]:
    """One ranked hit list with exact delta scores merged in: an
    updated id's stale entry is REPLACED (its graph/snapshot score came
    from the pre-update vector), the list re-sorts score-desc and
    truncates to ``k``. The single read-your-writes merge semantic
    shared by the walk index and the walk-fused hybrid tier."""
    merged = dict(hits)
    for j, eid in enumerate(delta_ids):
        merged[eid] = float(delta_scores[j])
    return sorted(merged.items(), key=lambda kv: -kv[1])[:k]


# ---------------------------------------------------------------------------
# graph construction: device k-NN + rank-based reorder/reverse fill
# ---------------------------------------------------------------------------


def _knn_forward(matrix_n: np.ndarray, degree: int,
                 chunk: int = 1024) -> np.ndarray:
    """Forward k-NN edges [n, deg] by rank (self excluded), computed with
    the device brute-force kernel in query chunks (the Pallas fused
    kernel when enabled — same routing as BruteForceIndex.search_batch).
    """
    n = matrix_n.shape[0]
    deg = min(degree, max(n - 1, 1))
    k_knn = min(deg + 1, n)
    mj = jnp.asarray(matrix_n)
    vj = jnp.ones((n,), dtype=bool)
    if _use_pallas():
        from nornicdb_tpu.ops.pallas_topk import fused_cosine_topk

        topk = lambda q: fused_cosine_topk(q, mj, vj, k_knn)  # noqa: E731
    else:
        topk = lambda q: cosine_topk_auto(q, mj, vj, k_knn)  # noqa: E731
    fwd = np.empty((n, deg), dtype=np.int32)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        _, idx = topk(mj[start:stop])
        idx = np.asarray(idx)
        # drop self wherever it ranked (duplicate vectors can push the
        # self-match out of the top-k entirely); stable-sort keeps rank
        # order among the survivors
        not_self = idx != np.arange(start, stop, dtype=np.int32)[:, None]
        order = np.argsort(~not_self, axis=1, kind="stable")
        fwd[start:stop] = np.take_along_axis(idx, order, axis=1)[:, :deg]
    return fwd


def _rank_reorder(fwd: np.ndarray, degree: int,
                  chunk: int = 8192) -> np.ndarray:
    """CAGRA-style rank-based reordering: keep the top ``degree//2``
    forward edges, fill the rest with rank-ordered reverse edges (dedup
    against the kept set), then backfill with the remaining forward
    edges. Reverse edges are what make a pure k-NN graph navigable —
    hub nodes gain in-links from every cluster that ranks them."""
    n, deg = fwd.shape
    if n <= 1:
        return np.zeros((n, degree), dtype=np.int32)
    keep_f = min(max(degree // 2, 1), deg)

    # reverse lists grouped by destination, ordered (rank, src)
    dst = fwd.ravel()
    src = np.repeat(np.arange(n, dtype=np.int32), deg)
    rank = np.tile(np.arange(deg, dtype=np.int32), n)
    order = np.lexsort((src, rank, dst))
    dsts, srcs = dst[order], src[order]
    counts = np.bincount(dsts, minlength=n)
    offsets = np.zeros(n, dtype=np.int64)
    np.cumsum(counts[:-1], out=offsets[1:])
    pos = np.arange(len(dsts), dtype=np.int64) - offsets[dsts]
    rev = np.full((n, degree), -1, dtype=np.int32)
    take = pos < degree
    rev[dsts[take], pos[take]] = srcs[take]

    adj = np.full((n, degree), -1, dtype=np.int32)
    adj[:, :keep_f] = fwd[:, :keep_f]
    fill_w = degree - keep_f
    if fill_w == 0:
        return adj
    cand = np.concatenate([rev, fwd[:, keep_f:]], axis=1)
    mc = cand.shape[1]
    earlier = np.arange(mc)[None, :] < np.arange(mc)[:, None]
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        c = cand[start:stop]
        bad = (c < 0) | (c == np.arange(start, stop,
                                        dtype=np.int32)[:, None])
        bad |= (c[:, :, None] == adj[start:stop, None, :keep_f]).any(2)
        bad |= ((c[:, :, None] == c[:, None, :]) & earlier[None]).any(2)
        good_first = np.argsort(bad, axis=1, kind="stable")
        picked = np.take_along_axis(c, good_first[:, :fill_w], axis=1)
        n_good = (~bad).sum(axis=1)
        usable = np.arange(fill_w)[None, :] < n_good[:, None]
        # short rows duplicate their best forward edge: a duplicate slot
        # is a no-op at search time (visited mask), never a wrong edge
        adj[start:stop, keep_f:] = np.where(usable, picked,
                                            fwd[start:stop, :1])
    return adj


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------


class CagraIndex:
    """Fixed-out-degree graph ANN over a wrapped ``BruteForceIndex``.

    The brute index remains the mutable source of truth (adds/removes
    delegate to it); the graph is an immutable device-side build over a
    snapshot, rebuilt when churn exceeds ``rebuild_stale_frac``. Below
    ``min_n`` live rows search delegates to the brute kernel — at small
    N one MXU matmul beats any walk's dispatch chain.
    """

    def __init__(
        self,
        dims: Optional[int] = None,
        degree: int = 32,
        itopk: int = 64,
        search_width: int = 1,
        iters: Optional[int] = None,
        hash_bits: int = 16,
        n_seeds: int = 1024,
        min_n: int = 4096,
        n_shards: int = 1,
        rebuild_stale_frac: float = 0.1,
        build_inline: bool = True,
        brute: Optional[BruteForceIndex] = None,
    ):
        if itopk <= 0 or itopk & (itopk - 1):
            raise ValueError(
                f"itopk must be a positive power of two, got {itopk}")
        self.degree = degree
        self.itopk = itopk
        self.search_width = search_width
        self.iters = iters
        self.hash_bits = hash_bits
        self.n_seeds = n_seeds
        self.min_n = min_n
        self.n_shards = max(1, n_shards)
        self.rebuild_stale_frac = rebuild_stale_frac
        # build_inline=False defers even the FIRST build to a background
        # thread (read-path wiring like qdrant: searches serve the exact
        # brute kernel until the graph is ready); True blocks once, the
        # right call when the build runs on a write path (service
        # strategy switch) or in tests/benches that need determinism.
        self.build_inline = build_inline
        self._brute = brute if brute is not None else BruteForceIndex(dims)
        self._graph: Optional[Dict[str, Any]] = None
        self._build_lock = threading.Lock()
        self._rebuilding = False
        self._rebuild_started = 0.0  # backlog age for /readyz + gauges
        self._rebuild_flag_lock = threading.Lock()
        # (brute.mutations, built_mutations, ids, vectors) — the delta
        # block is identical between searches until a mutation lands, so
        # the steady state pays one integer compare instead of O(churn)
        # locked get() calls per request
        self._delta_cache: Optional[Tuple] = None
        self.builds = 0

    # -- delegation: the brute index owns the vectors. Mutations may go
    # through this wrapper OR directly to the shared brute (the service
    # and qdrant layers do the latter) — freshness therefore keys off
    # the brute's own mutation counter + changelog, never wrapper state.

    def __len__(self) -> int:
        return len(self._brute)

    def __contains__(self, ext_id: str) -> bool:
        return ext_id in self._brute

    def add(self, ext_id: str, vector: Sequence[float]) -> None:
        self._brute.add(ext_id, vector)

    def add_batch(self, items) -> None:
        self._brute.add_batch(items)

    def remove(self, ext_id: str) -> bool:
        return self._brute.remove(ext_id)

    def get(self, ext_id: str):
        return self._brute.get(ext_id)

    def ids(self) -> List[str]:
        return self._brute.ids()

    def snapshot(self):
        return self._brute.snapshot()

    def save(self, path: str) -> None:
        """Vectors only — the graph is derived state, rebuilt on demand
        after a load (a 50k x 256d build is seconds on any backend)."""
        self._brute.save(path)

    @classmethod
    def load(cls, path: str, **kwargs) -> "CagraIndex":
        brute = BruteForceIndex.load(path)
        return cls(brute=brute, **kwargs)

    # -- build ------------------------------------------------------------

    def _auto_iters(self, n: int) -> int:
        # the wide seed round lands every query in its basin, so the
        # walk only refines locally: ~0.75 * log2(n) hops, floor 8.
        # Measured at 50k x 256d (clustered): recall@10 plateaus ~2
        # iterations below this; the margin absorbs harder corpora.
        return max(8, int(np.ceil(0.75 * np.log2(max(n, 4)))))

    def build(self) -> bool:
        """(Re)build the graph from the brute snapshot. Returns False
        when below ``min_n`` (search stays on the brute path)."""
        with self._build_lock:
            return self._build_locked()

    def _build_locked(self) -> bool:
        mutations = getattr(self._brute, "mutations", 0)
        g = self._graph
        if g is not None and g["built_mutations"] == mutations:
            # another thread rebuilt while we waited on the lock (or an
            # explicit build() raced the auto-rebuild): the graph is
            # already current — a second multi-second kNN pass over the
            # same snapshot would only stall serving
            return True
        matrix, valid, ext_ids = self._brute.snapshot()
        live = [i for i, e in enumerate(ext_ids)
                if e is not None and valid[i]]
        n = len(live)
        if n < self.min_n:
            self._graph = None
            return False
        rows = np.asarray(matrix[live], dtype=np.float32)
        row_ids = [ext_ids[i] for i in live]

        s = self.n_shards
        base = -(-n // s)  # ceil
        r = pad_dim(base)
        d = rows.shape[1]
        mat = np.zeros((s * r, d), dtype=np.float32)
        adj = np.zeros((s * r, self.degree), dtype=np.int32)
        validf = np.zeros((s * r,), dtype=np.float32)
        all_ids: List[Optional[str]] = [None] * (s * r)
        for sh in range(s):
            lo, hi = sh * base, min((sh + 1) * base, n)
            if lo >= hi:
                continue
            local = rows[lo:hi]
            fwd = _knn_forward(local, self.degree)
            ladj = _rank_reorder(fwd, self.degree)
            mat[sh * r: sh * r + (hi - lo)] = local
            adj[sh * r: sh * r + (hi - lo)] = ladj
            validf[sh * r: sh * r + (hi - lo)] = 1.0
            all_ids[sh * r: sh * r + (hi - lo)] = row_ids[lo:hi]

        # quantized base (NORNICDB_VECTOR_QUANT != off, single-shard):
        # HBM holds int8 PCA-projected codes + the head prefilter
        # column; float32 rows stay HOST-side for the exact pool
        # rerank, so the device footprint drops ~4x. Sharded graphs
        # keep float32 (the mesh walk program is float32-only) — a
        # degrade, never a wrong answer.
        quant = None
        from nornicdb_tpu.search.device_quant import quant_mode

        if s == 1 and quant_mode() != "off" and n >= self.min_n:
            from nornicdb_tpu.config import env_int
            from nornicdb_tpu.search.device_quant import (
                quantize_graph_base,
            )

            # None = a PQ-mode gap (indivisible dims, too few rows to
            # train honest codebooks): the f32 graph serves instead
            quant = quantize_graph_base(mat)
            if quant is not None and quant["mode"] == "int8":
                quant["rot_dev"] = jnp.asarray(quant["rot"])
                # keep 3/4 of each expansion past the head prefilter:
                # measured (8k x 64d clustered, CPU) recall@10 0.93 at
                # 1/2, 0.98 at 3/4, 1.00 unpruned — 3/4 clears the
                # 0.95 recall floor with margin while still dropping
                # a quarter of the full-row gathers
                quant["keep"] = max(8, env_int(
                    "QUANT_WALK_KEEP",
                    (3 * self.search_width * self.degree) // 4))
        graph: Dict[str, Any] = {
            "n": n,
            "shards": s,
            "rows_per_shard": r,
            # host float32 under quant (rerank gather source); device
            # array otherwise — every consumer but the walk reads only
            # shapes/rows from it
            "matrix": mat if quant is not None else jnp.asarray(mat),
            "quant": quant,
            "adj": jnp.asarray(adj),
            "validf": jnp.asarray(validf),
            "row_ids": all_ids,
            "iters": (self.iters if self.iters is not None
                      else self._auto_iters(n)),
            "built_mutations": mutations,
            # globally unique build sequence: consumers that cache
            # derived state per graph (the walk-fused join map) key on
            # this instead of object identity, which can alias across
            # a gc'd dict or collide across index instances
            "build_seq": next(_BUILD_SEQ),
        }
        if s > 1:
            # pre-slice once for the single-device reference merge (a
            # per-search slice would re-copy every call) ...
            graph["shard_slices"] = [
                (graph["matrix"][sh * r:(sh + 1) * r],
                 graph["adj"][sh * r:(sh + 1) * r],
                 graph["validf"][sh * r:(sh + 1) * r])
                for sh in range(s)]
            if len(jax.devices()) >= s:
                # ... and place the arrays on the mesh ONCE: device_put
                # with an identical sharding is a no-op at search time,
                # so a persistent serving index never re-ships the
                # corpus across devices per batch
                from jax.sharding import NamedSharding, PartitionSpec
                from nornicdb_tpu.parallel.mesh import data_mesh

                mesh = data_mesh(s)
                graph["mesh"] = mesh
                rows_sh = NamedSharding(mesh, PartitionSpec("data", None))
                graph["matrix"] = jax.device_put(graph["matrix"], rows_sh)
                graph["adj"] = jax.device_put(graph["adj"], rows_sh)
                graph["validf"] = jax.device_put(
                    graph["validf"], NamedSharding(mesh,
                                                   PartitionSpec("data")))
        self._graph = graph
        self.builds += 1
        _CAGRA_C.labels("build").inc()
        return True

    def _ensure_graph(self) -> Optional[Dict[str, Any]]:
        g = self._graph
        mutations = getattr(self._brute, "mutations", 0)
        n_alive = len(self._brute)
        if g is not None:
            churn = mutations - g["built_mutations"]
            if churn > self.rebuild_stale_frac * max(g["n"], 1):
                # serve the CURRENT graph while a fresh one builds off
                # the search path: stale results stay correct (deletes
                # live-filtered, adds/updates delta-merged), and the
                # MicroBatcher leader never stalls a convoy for the
                # multi-second device kNN rebuild
                self._kick_background_rebuild()
            return g
        if n_alive < self.min_n:
            self._graph = None
            return None
        if not self.build_inline:
            # read-path wiring: never stall a search convoy on the first
            # build either — brute serves exactly until the graph lands
            self._kick_background_rebuild()
            return self._graph
        # inline initial build: there is no older graph to serve, and it
        # mirrors the blocking first HNSW build of that tier
        self.build()
        return self._graph

    def _kick_background_rebuild(self) -> None:
        with self._rebuild_flag_lock:
            if self._rebuilding:
                return
            self._rebuilding = True
            self._rebuild_started = time.time()
        _CAGRA_C.labels("background_rebuild").inc()

        def run():
            from nornicdb_tpu import admission as _adm

            try:
                # background maintenance lane (ISSUE 15): any coalescer
                # ride from this thread seals behind interactive work
                with _adm.lane_scope(_adm.LANE_BACKGROUND):
                    self.build()  # _build_locked no-ops if already fresh
            finally:
                # same lock as the set in _kick_background_rebuild: an
                # unguarded clear can interleave with a concurrent
                # kick's read-then-set and double-start a rebuild
                with self._rebuild_flag_lock:
                    self._rebuilding = False
                    self._rebuild_started = 0.0

        t = threading.Thread(target=run, name="cagra-rebuild", daemon=True)
        t.start()

    @property
    def graph_built(self) -> bool:
        return self._graph is not None

    # -- external consumers (the walk-fused hybrid tier) ------------------

    def ensure_graph(self) -> Optional[Dict[str, Any]]:
        """Current graph dict under the index's own rebuild policy
        (churn kicks a background rebuild; the stale graph keeps
        serving), or None while callers must use an exact tier."""
        return self._ensure_graph()

    def delta_block(self, g) -> Tuple[Optional[List[str]],
                                      Optional[np.ndarray]]:
        """Public delta accessor for fused pipelines composing their
        own freshness ladder on this graph: (ids, vectors) added or
        updated since ``g`` was built, or (None, None) on changelog
        overrun (callers degrade to an exact tier)."""
        return self._delta_block(g)

    def stats(self) -> Dict[str, Any]:
        g = self._graph
        return {
            "n_alive": len(self._brute),
            "graph_built": g is not None,
            "graph_n": g["n"] if g else 0,
            "shards": g["shards"] if g else 0,
            "degree": self.degree,
            "itopk": self.itopk,
            "iters": g["iters"] if g else None,
            "builds": self.builds,
        }

    def resource_stats(self) -> Dict[str, Any]:
        """Memory + freshness accounting for obs/resources.py: device
        bytes of the graph arrays (base matrix + fixed-degree adjacency
        + validity — the reorder maps live in ``adj``), the mutation
        gap between the live brute index and the built graph, and the
        background-rebuild backlog state."""
        g = self._graph
        dev_b = 0
        graph_rows = 0
        host_extra = 0
        quant_b = 0
        f32_base = 0
        if g is not None:
            quant = g.get("quant")
            for key in ("adj", "validf"):
                dev_b += int(getattr(g[key], "nbytes", 0) or 0)
            f32_base = int(getattr(g["matrix"], "nbytes", 0) or 0)
            if quant is None:
                dev_b += f32_base
            else:
                # quantized base: float32 rows live HOST-side (rerank
                # gather source); HBM holds codes+head+scale+rotation
                host_extra += f32_base
                keys = (("codes", "codebooks")
                        if quant["mode"] == "pq"
                        else ("codes", "codes_head", "scale", "rot_dev"))
                for key in keys:
                    quant_b += int(
                        getattr(quant[key], "nbytes", 0) or 0)
                dev_b += quant_b
            graph_rows = g["n"]
        mutations = getattr(self._brute, "mutations", 0)
        gap = (mutations - g["built_mutations"]) if g is not None else 0
        started = self._rebuild_started
        stats_extra = {}
        if quant_b:
            stats_extra = {
                "quant_device_bytes": quant_b,
                "compression_ratio": round(f32_base / max(quant_b, 1),
                                           3),
            }
        return {
            **stats_extra,
            "rows": graph_rows,
            "capacity": (g["shards"] * g["rows_per_shard"]) if g else 0,
            "device_bytes": dev_b,
            # row_ids table (pointer-sized slots) + the host-resident
            # float32 base under quantization
            "host_bytes": (8 * len(g["row_ids"]) + host_extra)
            if g else 0,
            "mutation_gap": gap,
            "rebuild_in_flight": 1.0 if self._rebuilding else 0.0,
            "rebuild_backlog_s": (
                round(time.time() - started, 3)
                if self._rebuilding and started else 0.0),
            "builds": self.builds,
        }

    # -- search -----------------------------------------------------------

    def search(self, query: Sequence[float], k: int = 10,
               **kw) -> List[Tuple[str, float]]:
        return self.search_batch(
            np.asarray([query], dtype=np.float32), k, **kw)[0]

    def search_batch(
        self,
        queries: np.ndarray,
        k: int = 10,
        itopk: Optional[int] = None,
        iters: Optional[int] = None,
        width: Optional[int] = None,
    ) -> List[List[Tuple[str, float]]]:
        """Batched ANN search; per-query [(ext_id, cosine)] best-first.

        Batch and k are padded to pow2 buckets so every arrival-rate
        batch from the MicroBatcher reuses one of log2(max_batch)
        compiled programs. ``itopk``/``iters``/``width`` overrides exist
        for recall/qps sweeps; production callers leave them to the
        index config."""
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim != 2:
            raise ValueError(f"queries must be [B, D], got {queries.shape}")
        if len(queries) == 0:
            return []
        g = self._ensure_graph()
        if g is None:
            return self._brute.search_batch(queries, k)
        tier = ("vector_walk_quant" if g.get("quant") is not None
                else "vector_walk_f32")
        hold = None
        if not _audit.tier_allowed(tier):
            # shadow-parity quarantine: the walk steps down its ladder
            # to the exact tier until the breach clears
            hold = "quarantine"
        elif not _audit.admission_allows(tier):
            # admission posture (ISSUE 15): overload forces the walk
            # down to the exact tier to shrink device pressure
            hold = "admission"
        if hold is not None:
            _CAGRA_C.labels("exact_fallback_quarantine").inc()
            self._degrade(tier, hold, g)
            return self._brute.search_batch(queries, k)
        p = itopk or self.itopk
        quant0 = g.get("quant")
        if quant0 is not None and quant0["mode"] == "pq" and itopk is None:
            # PQ ADC carries reconstruction noise the int8 rung doesn't:
            # widen the beam 4x (still pow2) so the exact host rerank of
            # the pool recovers the true top-k despite noisy navigation
            p = min(4 * p, 1024)
        if min(k, g["n"]) > p:
            # the pool can only ever hold itopk candidates — a deeper
            # request silently truncated would differ from the brute and
            # hnsw strategies, so serve it exactly instead
            _CAGRA_C.labels("exact_fallback_itopk").inc()
            self._degrade(tier, "itopk_exceeded", g)
            return self._brute.search_batch(queries, k)
        delta_ids, delta_vecs = self._delta_block(g)
        if delta_ids is None:
            # churn outran the brute changelog (only possible while a
            # background rebuild is in flight): serve exactly until the
            # fresh graph swaps in
            _CAGRA_C.labels("exact_fallback_changelog").inc()
            self._degrade(tier, "changelog_overrun", g)
            return self._brute.search_batch(queries, k)
        n_iters = iters if iters is not None else g["iters"]
        w = width or self.search_width
        k_eff = min(k, g["n"], p)
        if k_eff < 1:
            return [[] for _ in range(len(queries))]
        b = len(queries)
        bb = pow2_bucket(max(b, 1))
        kb = min(pow2_bucket(k_eff), p)
        if bb != b:
            queries = np.concatenate(
                [queries,
                 np.broadcast_to(queries[:1], (bb - b,) + queries.shape[1:])],
                axis=0)
        qn = l2_normalize(jnp.asarray(queries))
        t0 = time.time()
        s, i = self._walk(g, qn, kb, n_iters, w, p)
        # force to host INSIDE the timed window: jax dispatch is async,
        # so timing the call alone would record enqueue, not the walk
        s_host, i_host = np.asarray(s), np.asarray(i)
        record_dispatch("cagra_walk", bb, kb, time.time() - t0)
        # per-query cost: seed round + iters x width x degree distance
        # evals at the padded batch; real (pre-pad) queries counted
        from nornicdb_tpu.obs import cost as _cost

        if _cost.pricing_enabled():
            quant = g.get("quant")
            if quant is not None:
                if quant["mode"] == "pq":
                    flops, byts = _cost.price_walk_pq(
                        bb, int(queries.shape[1]), n_iters, w,
                        self.degree, p, quant["pq_m"],
                        quant["pq_codes"], n_seeds=self.n_seeds)
                else:
                    flops, byts = _cost.price_walk_quant(
                        bb, int(queries.shape[1]), n_iters, w,
                        self.degree, p, quant["head_dims"],
                        quant["keep"], n_seeds=self.n_seeds)
                rf, rb = _cost.price_rerank(bb, p,
                                            int(queries.shape[1]))
                flops, byts = flops + rf, byts + rb
            else:
                flops, byts = _cost.price_walk(
                    bb, int(queries.shape[1]), n_iters, w, self.degree,
                    p, n_seeds=self.n_seeds)
            _cost.record_query_cost("cagra_walk", _cost.cost_name(self),
                                    b, flops, byts)
        out = self._resolve(g, s_host[:b], i_host[:b], k_eff)
        if delta_ids:
            _CAGRA_C.labels("delta_merge").inc()
            out = self._merge_delta(out, delta_ids, delta_vecs,
                                    np.asarray(qn)[:b], k_eff)
        # a stale graph's live-filter can under-fill a row even though
        # plenty of live rows remain (deletes clustered in the query's
        # neighborhood). Serve those batches exactly — rare by
        # construction (churn is capped by the rebuild threshold), and
        # callers like hybrid RRF assume k hits when the corpus has them
        want = min(k_eff, len(self._brute))
        if any(len(hits) < want for hits in out):
            _CAGRA_C.labels("exact_fallback_underfill").inc()
            self._degrade(tier, "underfill", g)
            return self._brute.search_batch(queries[:b], k)
        _audit.note_batch_tier(tier)
        return out

    def _degrade(self, tier: str, reason: str, g) -> None:
        """Structured ledger record for a walk -> exact-tier step (the
        legacy cagra_events_total label stays as the alias)."""
        from nornicdb_tpu.obs import cost as _cost

        _audit.record_degrade(
            "vector", tier, "vector_brute_f32", reason,
            index=_cost.cost_name(self._brute),
            versions={"build_seq": g.get("build_seq"),
                      "built_mutations": g.get("built_mutations"),
                      "mutations": getattr(self._brute, "mutations", 0)})

    def _delta_block(self, g):
        """(ids, vectors[m,D]) of rows added/updated since the graph
        build, straight from the brute changelog — mutations that
        bypassed this wrapper (service index_node, qdrant upserts write
        straight to the shared brute) are covered too. (None, None) =
        changelog trimmed past the marker. Memoized on the mutation
        counter: until a write lands, repeat searches pay one integer
        compare instead of O(churn) locked row fetches."""
        m = getattr(self._brute, "mutations", 0)
        cached = self._delta_cache
        if cached is not None and cached[0] == m \
                and cached[1] == g["built_mutations"]:
            return cached[2], cached[3]
        fn = getattr(self._brute, "changed_since", None)
        ids = fn(g["built_mutations"]) if fn is not None else []
        if ids is None:
            block = (None, None)
        else:
            pairs = []
            for eid in ids:
                v = self._brute.get(eid)  # None if removed since logging
                if v is not None:
                    pairs.append((eid, v))
            block = ([eid for eid, _ in pairs],
                     np.stack([v for _, v in pairs]) if pairs else None)
        self._delta_cache = (m, g["built_mutations"], block[0], block[1])
        return block

    def _merge_delta(self, hits_rows, ids, dvecs, qn, k_eff):
        """Exact-score rows added/updated since the build and merge them
        into the walk results (read-your-writes without a rebuild). The
        walk's entry for an updated id is replaced — its graph score was
        computed from the pre-update vector."""
        ds = qn @ dvecs.T  # rows are stored normalized; exact cosine
        return [merge_delta_hits(hits, ids, ds[r], k_eff)
                for r, hits in enumerate(hits_rows)]

    def _walk(self, g, qn, kb, n_iters, w, p):
        if g.get("quant") is not None:
            return self._walk_quant(g, qn, kb, n_iters, w, p)
        if g["shards"] == 1:
            return _cagra_walk(
                qn, g["matrix"], g["adj"], g["validf"],
                k=kb, iters=n_iters, width=w, itopk=p,
                hash_bits=self.hash_bits, n_seeds=self.n_seeds)
        if "mesh" in g and len(jax.devices()) >= g["shards"]:
            return sharded_cagra_walk(
                qn, g["matrix"], g["adj"], g["validf"],
                kb, n_iters, w, p, self.hash_bits, self.n_seeds,
                mesh=g["mesh"])
        return self._walk_shards_single_device(g, qn, kb, n_iters, w, p)

    def _walk_quant(self, g, qn, kb, n_iters, w, p):
        """Quantized walk (device_quant): the greedy walk runs over the
        int8 PCA-projected base with the two-stage frontier scorer
        (head prefilter -> full int8 dot), then the ENTIRE itopk pool
        is exactly re-scored against the host float32 rows before the
        final top-k — approximate scores rank the pool, never an
        answer. Shapes match the float32 walk's (scores, row ids)."""
        from nornicdb_tpu.search.device_quant import (
            _pq_walk,
            _quant_walk,
        )

        q = g["quant"]
        if q["mode"] == "pq":
            # PQ rung (ISSUE 17 satellite): codes-only ADC walk in the
            # original basis — M bytes per row in HBM, exact rerank of
            # the whole pool below is identical to the int8 path
            s, i = _pq_walk(
                qn, q["codes"], q["codebooks"], g["adj"], g["validf"],
                k=p, iters=n_iters, width=w, itopk=p,
                hash_bits=self.hash_bits, n_seeds=self.n_seeds)
        else:
            qp = qn @ q["rot_dev"]  # orthogonal: norms/dots preserved
            s, i = _quant_walk(
                qp, q["codes"], q["codes_head"], q["scale"], g["adj"],
                g["validf"], k=p, iters=n_iters, width=w, itopk=p,
                hash_bits=self.hash_bits, n_seeds=self.n_seeds,
                keep=q["keep"])
        s_h, i_h = np.asarray(s), np.asarray(i)
        qh = np.asarray(qn)
        gathered = g["matrix"][i_h]  # host f32 [B, itopk, D]
        exact = np.einsum("bpd,bd->bp", gathered, qh)
        exact = np.where(s_h > 0.5 * NEG_INF, exact,
                         np.float32(NEG_INF))
        order = np.argsort(-exact, axis=1, kind="stable")[:, :kb]
        return (np.take_along_axis(exact, order, axis=1),
                np.take_along_axis(i_h, order, axis=1))

    def _walk_shards_single_device(self, g, qn, kb, n_iters, w, p):
        """Reference merge for the sharded layout on one device: walk
        each shard's local subgraph, concatenate shard-local winners in
        shard order (exactly the all-gather layout) and take one global
        top-k. The sharded path must be bit-identical to this."""
        r = g["rows_per_shard"]
        parts_s, parts_i = [], []
        for sh, (m_sh, a_sh, v_sh) in enumerate(g["shard_slices"]):
            s, i = _cagra_walk(
                qn, m_sh, a_sh, v_sh,
                k=kb, iters=n_iters, width=w, itopk=p,
                hash_bits=self.hash_bits, n_seeds=self.n_seeds)
            parts_s.append(s)
            parts_i.append(i + sh * r)
        return concat_topk(parts_s, parts_i, kb)

    def _resolve(self, g, s, i, k_eff):
        """Map walk row ids to ext ids, dropping never-filled slots and
        rows deleted since the build (live-membership filter keeps stale
        graphs honest between rebuilds)."""
        row_ids = g["row_ids"]
        stale = getattr(self._brute, "mutations", 0) != g["built_mutations"]
        out: List[List[Tuple[str, float]]] = []
        for row in range(s.shape[0]):
            hits: List[Tuple[str, float]] = []
            for col in range(s.shape[1]):
                if s[row, col] < 0.5 * NEG_INF:
                    break
                eid = row_ids[int(i[row, col])]
                if eid is None:
                    continue
                if stale and eid not in self._brute:
                    continue
                hits.append((eid, float(s[row, col])))
                if len(hits) >= k_eff:
                    break
            out.append(hits)
        return out
