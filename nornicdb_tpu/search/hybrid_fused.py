"""Fused hybrid search: BM25 + vector + RRF in one compiled pipeline.

Reference: pkg/search Service.Search (search.go:2841) fuses BM25 and
vector candidate lists with (weighted) RRF — in this repo that fusion,
and the whole lexical half, ran as host Python under the BM25 lock.
This module executes the complete hybrid read path on device: one
jitted program takes a query batch's embeddings and planned lexical
entries and emits the RRF-fused top-k, with the per-source candidate
lists along for the ride (the service's min_score gates and result
payloads need the raw scores).

Pipeline (two compiles per pow2 ``(B, k)`` bucket: the lexical half's
unique-term rows follow the batch's distinct terms in two steps,
``device_bm25.lex_rows``: ``half`` = 8 x B, no less than 16, and
``full`` = 16 x B; the number of postings a batch walks is no shape of
the program. ``SearchService.warm_hybrid`` compiles both of every
bucket before traffic; only a batch with more terms than ``full`` takes
a program nothing warmed, the next power of two: ROADMAP S8):

1. **lexical** — ``device_bm25.bm25_dense_scores`` over the CSR
   snapshot -> top-k rows;
2. **vector** — one of two tiers. The **brute tier**: one MXU matmul
   over the brute index's device matrix (the same lazily-synced arrays
   ``BruteForceIndex.search_batch`` dispatches against, so the vector
   side is always write-fresh) -> top-k slots. The **walk tier**
   (above ``walk_min_n`` live vectors): the jitted CAGRA greedy walk
   (``cagra._walk_body`` — fixed iterations, fixed ``itopk`` pool)
   over the device graph — sub-linear per query, which is what moves
   the corpus ceiling at which fusion wins (arXiv:2308.15136; the
   fused lexical+graph-ANN+fusion pipeline is the open frontier named
   by arXiv:2602.16719 §research-directions);
3. **fuse** — the two candidate lists join on a device-resident
   ``lexical row -> vector row`` map (brute slots for the matmul tier,
   graph rows for the walk tier; docs in both sources must merge into
   ONE fused candidate), reciprocal-rank weights accumulate in float32
   in source-major order — bit-identical to the host ``rrf.rrf_fuse``
   — and one final top-k emits the fused ranking. Ties resolve by
   concatenated position = (source, rank), exactly the host fuse's
   deterministic ordering.

Parity contract per tier: the brute tier is **rank-identical** to the
host hybrid path (the PR 4 parity corpus). The walk tier is
approximate by construction, so its contract is **walk-parity**: the
fused top-k must stay within recall@k tolerance of the host hybrid
ranking (tests/test_hybrid_walk.py: recall@10 >= 0.95 absolute), and every
freshness gap degrades DOWN the ladder — walk-fused -> brute-fused ->
host — never to a wrong answer.

Sharding row-shards BOTH corpora over the ``data`` mesh axis: each
shard scores its lexical rows and vector slots locally, one all-gather
+ top-k per source merges shard winners, and the fuse then runs
replicated — bit-identical to the single-device shard-loop reference
(same collective pattern as cagra and ``mesh.sharded_cosine_topk``).

Freshness composes the PR 2 ladder: the lexical snapshot rebuilds in
the background on churn with tombstones alive-filtered (df corrected)
and adds/updates exact-scored by the host delta side-scan; the vector
side needs no snapshot (the brute matrix is the live index); the
row->slot join map re-derives whenever the brute index mutates, so
compactions can never mis-join. Any freshness gap degrades to the
host path — never to a wrong answer.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nornicdb_tpu.obs import REGISTRY, declare_kind, record_dispatch
from nornicdb_tpu.obs.tracing import span as _span
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import cost as _cost
from nornicdb_tpu.ops.similarity import EXACT, NEG_INF, l2_normalize
from nornicdb_tpu.search.bm25 import BM25Index
from nornicdb_tpu.search.cagra import (
    CagraIndex,
    _cagra_walk,
    _walk_body,
    merge_delta_hits,
)
from nornicdb_tpu.search.device_bm25 import (
    DeviceBM25,
    PlanOverflow,
    SnapshotStale,
    bm25_dense_scores,
)
from nornicdb_tpu.search.microbatch import pow2_bucket
from nornicdb_tpu.search.rrf import DEFAULT_RRF_K, rrf_fuse
from nornicdb_tpu.search.vector_index import BruteForceIndex

_HYB_C = REGISTRY.counter(
    "nornicdb_hybrid_fused_events_total",
    "Fused hybrid pipeline dispatches and freshness decisions",
    labels=("event",))

declare_kind("hybrid_fused")
declare_kind("hybrid_walk_fused")
declare_kind("hybrid_fused_quant")
declare_kind("hybrid_walk_fused_quant")

# canonical serving-tier names (obs/audit taxonomy) for the pipeline's
# rungs; every decoded row carries `served_by` — per ROW, because one
# rider's freshness correction (host re-fuse) must not relabel its
# batch-mates (ISSUE 10 rider accuracy)
TIER_BRUTE_F32 = "hybrid_brute_f32"
TIER_WALK_F32 = "hybrid_walk_f32"
TIER_WALK_QUANT = "hybrid_walk_quant"


def quant_tier(mode: str) -> str:
    return f"hybrid_brute_{mode}"


# ---------------------------------------------------------------------------
# pure device fusion
# ---------------------------------------------------------------------------


def _pad_cols(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    if x.shape[1] >= k:
        return x
    pad = jnp.full((x.shape[0], k - x.shape[1]), fill, dtype=x.dtype)
    return jnp.concatenate([x, pad], axis=1)


def rrf_fuse_device(
    ls: jnp.ndarray,  # [B, kq] lexical scores, NEG_INF padded
    lid: jnp.ndarray,  # [B, kq] vector slot per lexical hit (-1 = none)
    lgrow: jnp.ndarray,  # [B, kq] global lexical row ids
    vs: jnp.ndarray,  # [B, kq] vector scores
    vi: jnp.ndarray,  # [B, kq] vector slots
    n_cand: jnp.ndarray,  # [B] per-request candidate depth (overfetch)
    w_lex: jnp.ndarray,  # [B]
    w_vec: jnp.ndarray,  # [B]
    rrf_k: int,
    c_vec: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted RRF over the concatenated candidate lists. Docs present
    in both sources join via ``lid`` and keep their FIRST (lexical)
    position; per-candidate sums accumulate float32 source-major, so the
    result is bit-identical to host ``rrf_fuse`` on the same lists.
    Returns (fused scores [B, 2kq], concat positions [B, 2kq])."""
    b, kq = ls.shape
    r = jnp.arange(kq)
    in_cand = r[None, :] < n_cand[:, None]
    lval = (ls > 0.5 * NEG_INF) & in_cand
    vval = (vs > 0.5 * NEG_INF) & in_cand
    # shared candidate id space: vector slot when the lexical doc has a
    # vector, else a unique id past the vector capacity
    cid = jnp.concatenate(
        [jnp.where(lid >= 0, lid, c_vec + lgrow), vi], axis=1)
    val = jnp.concatenate([lval, vval], axis=1)
    inv = (rrf_k + 1.0 + r).astype(jnp.float32)
    w = jnp.concatenate(
        [w_lex[:, None] / inv[None, :], w_vec[:, None] / inv[None, :]],
        axis=1)
    w = jnp.where(val, w, 0.0)
    match = (cid[:, :, None] == cid[:, None, :]) \
        & val[:, :, None] & val[:, None, :]
    # each row of `match` has at most two hits (one per source), so the
    # einsum sum is a plain two-term float32 add — no reassociation
    fused = jnp.einsum("bij,bj->bi", match.astype(jnp.float32), w)
    m2 = jnp.arange(2 * kq)
    dup = jnp.any(match & (m2[None, None, :] < m2[None, :, None]), axis=2)
    fused = jnp.where(val & ~dup, fused, NEG_INF)
    return jax.lax.top_k(fused, 2 * kq)


@functools.partial(jax.jit, static_argnames=("kq", "rrf_k"))
def _fused_single(tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f,
                  l2v, avgdl, qn, vmatrix, vvalid, n_cand, w_lex, w_vec,
                  kq, rrf_k):
    c_vec = vmatrix.shape[0]
    ls, lid, lgrow, vs, vi = _local_parts_impl(
        tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f, l2v,
        avgdl, qn, vmatrix, vvalid, jnp.int32(0), jnp.int32(0), kq=kq)
    ls = _pad_cols(ls, kq, NEG_INF)
    lid = _pad_cols(lid, kq, 0)
    lgrow = _pad_cols(lgrow, kq, 0)
    vs = _pad_cols(vs, kq, NEG_INF)
    vi = _pad_cols(vi, kq, 0)
    fs, fpos = rrf_fuse_device(ls, lid, lgrow, vs, vi, n_cand,
                               w_lex, w_vec, rrf_k, c_vec)
    return ls, lgrow, vs, vi, fs, fpos


def _lex_parts_impl(tstart, tlen, sel, post_doc, post_tf, doc_len,
                    alive_f, l2map, avgdl, lex_off, kq):
    """One shard's lexical top-k with globalized row ids plus the
    joined foreign-row column (brute slot for the matmul tier, graph
    row for the walk tier) — the lexical half of every shard path."""
    c_lex = doc_len.shape[0]
    dense = bm25_dense_scores(tstart, tlen, sel, post_doc, post_tf,
                              doc_len, alive_f, avgdl)
    ls, li = jax.lax.top_k(dense, min(kq, c_lex))
    return ls, l2map[li], li + lex_off


_lex_parts = functools.partial(
    jax.jit, static_argnames=("kq",))(_lex_parts_impl)


def _local_parts_impl(tstart, tlen, sel, post_doc, post_tf, doc_len,
                      alive_f, l2v, avgdl, qn, vmatrix, vvalid, lex_off,
                      vec_off, kq):
    """One shard's per-source top-k with globalized ids — the building
    block of both the single-device reference loop and the mesh path."""
    c_vec = vmatrix.shape[0]
    ls, lid, lgrow = _lex_parts_impl(tstart, tlen, sel, post_doc, post_tf,
                                     doc_len, alive_f, l2v, avgdl,
                                     lex_off, kq)
    vsc = jnp.matmul(qn, vmatrix.T, precision=EXACT)
    vsc = jnp.where(vvalid[None, :], vsc, NEG_INF)
    vs, vi = jax.lax.top_k(vsc, min(kq, c_vec))
    return ls, lid, lgrow, vs, vi + vec_off


_local_parts = functools.partial(
    jax.jit, static_argnames=("kq",))(_local_parts_impl)


def _merge_parts(parts, kq):
    """Concat per-shard (scores, aux...) blocks in shard order and take
    one top-k, gathering every aux column by the winning positions —
    the all-gather-equivalent merge layout."""
    all_s = jnp.concatenate([p[0] for p in parts], axis=1)
    auxes = [jnp.concatenate([p[j] for p in parts], axis=1)
             for j in range(1, len(parts[0]))]
    k = min(kq, all_s.shape[1])
    top_s, pos = jax.lax.top_k(all_s, k)
    out = [_pad_cols(top_s, kq, NEG_INF)]
    for a in auxes:
        out.append(_pad_cols(jnp.take_along_axis(a, pos, axis=1), kq, 0))
    return out


@functools.partial(
    jax.jit, static_argnames=("kq", "rrf_k", "c_vec_total"))
def _fuse_merged(ls, lid, lgrow, vs, vi, n_cand, w_lex, w_vec, kq,
                 rrf_k, c_vec_total):
    return rrf_fuse_device(ls, lid, lgrow, vs, vi, n_cand, w_lex, w_vec,
                           rrf_k, c_vec_total)


@functools.partial(
    jax.jit, static_argnames=("kq", "rrf_k", "mesh_holder"))
def _fused_sharded_impl(tstart, tlen, sel, post_doc, post_tf, doc_len,
                        alive_f, l2v, avgdl, qn, vmatrix, vvalid,
                        n_cand, w_lex, w_vec, kq, rrf_k, mesh_holder):
    from jax.sharding import PartitionSpec as P

    from nornicdb_tpu.parallel.mesh import shard_map_unchecked

    mesh = mesh_holder.mesh
    s_n = mesh.shape["data"]
    c_lex_local = doc_len.shape[0] // s_n
    c_vec_local = vmatrix.shape[0] // s_n
    c_vec_total = vmatrix.shape[0]

    def local_fn(tstart_s, tlen_s, sel_r, pd_s, pt_s, dl_s, al_s, l2v_s,
                 avg_r, qn_r, vm_s, vv_s, nc_r, wl_r, wv_r):
        sh = jax.lax.axis_index("data")
        ls, lid, lgrow, vs, gvi = _local_parts_impl(
            tstart_s, tlen_s, sel_r, pd_s, pt_s, dl_s, al_s, l2v_s, avg_r,
            qn_r, vm_s, vv_s, sh * c_lex_local, sh * c_vec_local,
            kq=kq)

        def gat(x):
            return jax.lax.all_gather(x, "data", axis=1, tiled=True)

        ls2, lid2, lgrow2 = _merge_parts(
            [(gat(ls), gat(lid), gat(lgrow))], kq)
        vs2, vi2 = _merge_parts([(gat(vs), gat(gvi))], kq)
        fs, fpos = rrf_fuse_device(ls2, lid2, lgrow2, vs2, vi2, nc_r,
                                   wl_r, wv_r, rrf_k, c_vec_total)
        return ls2, lgrow2, vs2, vi2, fs, fpos

    return shard_map_unchecked(
        local_fn,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P("data"), P("data"),
                  P("data"), P("data"), P("data"), P(), P(),
                  P("data", None), P("data"), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
    )(tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f, l2v,
      avgdl, qn, vmatrix, vvalid, n_cand, w_lex, w_vec)


# ---------------------------------------------------------------------------
# quantized vector halves (device_quant): int8/PQ coarse scoring inside
# the same compiled program; the decode exact-reranks the vector
# candidates on host float32 rows and re-fuses through the
# bit-compatible host rrf_fuse — compressed scores rank the POOL, never
# an answer
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kq", "pool", "mode"))
def _fused_single_quant(tstart, tlen, sel, post_doc, post_tf, doc_len,
                        alive_f, l2v, avgdl, qn, codes_t, aux,
                        vvalid, kq, pool, mode):
    """Lexical CSR scoring + quantized coarse vector top-``pool`` in
    one compiled program; ``mode`` picks the coarse scorer (``aux`` is
    the int8 per-row scales or the PQ codebooks). No device fuse: the
    quant decode exact-reranks the pool and re-fuses through the
    bit-compatible host rrf_fuse, so a device fuse over COARSE scores
    would be discarded anyway — and skipping it frees the vector half
    to overfetch ``pool`` > kq candidates (the rerank's recall slack,
    same policy as the standalone plane)."""
    from nornicdb_tpu.search.device_quant import (
        _int8_scores,
        _pq_adc_scores,
    )

    c_vec = codes_t.shape[1]
    ls, _lid, lgrow = _lex_parts_impl(tstart, tlen, sel, post_doc,
                                      post_tf, doc_len, alive_f, l2v,
                                      avgdl, jnp.int32(0), kq=kq)
    if mode == "int8":
        vsc = _int8_scores(qn, codes_t, aux)
    else:
        vsc = _pq_adc_scores(qn, codes_t, aux)
    vsc = jnp.where(vvalid[None, :], vsc, NEG_INF)
    vs, vi = jax.lax.top_k(vsc, min(pool, c_vec))
    ls = _pad_cols(ls, kq, NEG_INF)
    lgrow = _pad_cols(lgrow, kq, 0)
    vs = _pad_cols(vs, pool, NEG_INF)
    vi = _pad_cols(vi, pool, 0)
    return ls, lgrow, vs, vi


@functools.partial(jax.jit, static_argnames=(
    "kq", "iters", "width", "itopk", "hash_bits", "n_seeds", "keep"))
def _walk_fused_single_q(tstart, tlen, sel, post_doc, post_tf, doc_len,
                         alive_f, l2g, avgdl, qp, codes, codes_head,
                         scale, gadj, gvalidf, kq, iters, width, itopk,
                         hash_bits, n_seeds, keep):
    """Walk tier over a QUANTIZED graph base: the two-stage
    (head-prefilter -> full int8 dot) greedy walk replaces the float32
    walk inside the same compiled program. ``qp`` is the PCA-projected
    query batch (rotation is orthogonal, so dots are preserved). The
    walk's whole itopk pool rides out for the exact rerank; the host
    re-fuse replaces the device fuse (see _fused_single_quant)."""
    from nornicdb_tpu.search.device_quant import _walk_body_quant

    ls, _lid, lgrow = _lex_parts_impl(tstart, tlen, sel, post_doc,
                                      post_tf, doc_len, alive_f, l2g,
                                      avgdl, jnp.int32(0), kq=kq)
    vs, vi = _walk_body_quant(qp, codes, codes_head, scale, gadj,
                              gvalidf, itopk, iters, width,
                              itopk, hash_bits, n_seeds, keep)
    ls = _pad_cols(ls, kq, NEG_INF)
    lgrow = _pad_cols(lgrow, kq, 0)
    return ls, lgrow, vs, vi


@functools.partial(jax.jit, static_argnames=(
    "kq", "iters", "width", "itopk", "hash_bits", "n_seeds"))
def _walk_fused_single_pq(tstart, tlen, sel, post_doc, post_tf, doc_len,
                          alive_f, l2g, avgdl, qn, codes, codebooks,
                          gadj, gvalidf, kq, iters, width, itopk,
                          hash_bits, n_seeds):
    """Walk tier over a PQ graph base (ISSUE 17 satellite): the
    codes-only ADC walk replaces the int8 two-stage walk inside the
    same compiled program — HBM holds M bytes per graph row. The pool
    rides out for the exact host rerank and the host re-fuse replaces
    the device fuse, exactly as in :func:`_walk_fused_single_q`."""
    from nornicdb_tpu.search.device_quant import _walk_body_pq

    ls, _lid, lgrow = _lex_parts_impl(tstart, tlen, sel, post_doc,
                                      post_tf, doc_len, alive_f, l2g,
                                      avgdl, jnp.int32(0), kq=kq)
    vs, vi = _walk_body_pq(qn, codes, codebooks, gadj, gvalidf,
                           itopk, iters, width, itopk, hash_bits,
                           n_seeds)
    ls = _pad_cols(ls, kq, NEG_INF)
    lgrow = _pad_cols(lgrow, kq, 0)
    return ls, lgrow, vs, vi


# ---------------------------------------------------------------------------
# the walk tier: CAGRA greedy walk instead of the brute matmul
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "kq", "rrf_k", "iters", "width", "itopk", "hash_bits", "n_seeds"))
def _walk_fused_single(tstart, tlen, sel, post_doc, post_tf, doc_len,
                       alive_f, l2g, avgdl, qn, gmatrix, gadj, gvalidf,
                       n_cand, w_lex, w_vec, kq, rrf_k, iters, width,
                       itopk, hash_bits, n_seeds):
    """One compiled program for the walk tier: CSR lexical scoring,
    the fixed-iteration CAGRA greedy walk over the device graph, and
    device RRF joining on the ``lexical row -> graph row`` map. Same
    pow2 ``(B, kq)`` compile-bucket discipline as the brute tier —
    the walk's own statics (iters/width/itopk) are per-graph-build
    constants, not per-request knobs."""
    c_g = gmatrix.shape[0]
    ls, lid, lgrow = _lex_parts_impl(tstart, tlen, sel, post_doc, post_tf,
                                     doc_len, alive_f, l2g, avgdl,
                                     jnp.int32(0), kq=kq)
    vs, vi = _walk_body(qn, gmatrix, gadj, gvalidf, min(kq, itopk),
                        iters, width, itopk, hash_bits, n_seeds)
    ls = _pad_cols(ls, kq, NEG_INF)
    lid = _pad_cols(lid, kq, 0)
    lgrow = _pad_cols(lgrow, kq, 0)
    vs = _pad_cols(vs, kq, NEG_INF)
    vi = _pad_cols(vi, kq, 0)
    fs, fpos = rrf_fuse_device(ls, lid, lgrow, vs, vi, n_cand,
                               w_lex, w_vec, rrf_k, c_g)
    return ls, lgrow, vs, vi, fs, fpos


@functools.partial(jax.jit, static_argnames=(
    "kq", "rrf_k", "iters", "width", "itopk", "hash_bits", "n_seeds",
    "mesh_holder"))
def _walk_fused_sharded_impl(tstart, tlen, sel, post_doc, post_tf,
                             doc_len, alive_f, l2g, avgdl, qn, gmatrix,
                             gadj, gvalidf, n_cand, w_lex, w_vec, kq,
                             rrf_k, iters, width, itopk, hash_bits,
                             n_seeds, mesh_holder):
    """Mesh walk tier: both corpora row-sharded over ``data``; each
    shard scores its lexical rows and walks its local subgraph, one
    all-gather + top-k per source merges shard winners, and the fuse
    runs replicated — the same collective pattern as the brute-fused
    mesh path and ``cagra.sharded_cagra_walk``."""
    from jax.sharding import PartitionSpec as P

    from nornicdb_tpu.parallel.mesh import shard_map_unchecked

    mesh = mesh_holder.mesh
    s_n = mesh.shape["data"]
    c_lex_local = doc_len.shape[0] // s_n
    g_local = gmatrix.shape[0] // s_n
    c_g_total = gmatrix.shape[0]
    kw = min(kq, itopk)

    def local_fn(tstart_s, tlen_s, sel_r, pd_s, pt_s, dl_s, al_s, l2g_s,
                 avg_r, qn_r, gm_s, ga_s, gv_s, nc_r, wl_r, wv_r):
        sh = jax.lax.axis_index("data")
        ls, lid, lgrow = _lex_parts_impl(
            tstart_s, tlen_s, sel_r, pd_s, pt_s, dl_s, al_s, l2g_s,
            avg_r, sh * c_lex_local, kq=kq)
        ws, wi = _walk_body(qn_r, gm_s, ga_s, gv_s, kw, iters, width,
                            itopk, hash_bits, n_seeds)
        gwi = wi + sh * g_local

        def gat(x):
            return jax.lax.all_gather(x, "data", axis=1, tiled=True)

        ls2, lid2, lgrow2 = _merge_parts(
            [(gat(ls), gat(lid), gat(lgrow))], kq)
        vs2, vi2 = _merge_parts([(gat(ws), gat(gwi))], kq)
        fs, fpos = rrf_fuse_device(ls2, lid2, lgrow2, vs2, vi2, nc_r,
                                   wl_r, wv_r, rrf_k, c_g_total)
        return ls2, lgrow2, vs2, vi2, fs, fpos

    return shard_map_unchecked(
        local_fn,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P("data"), P("data"),
                  P("data"), P("data"), P("data"), P(), P(),
                  P("data", None), P("data", None), P("data"), P(),
                  P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
    )(tstart, tlen, sel, post_doc, post_tf, doc_len, alive_f, l2g,
      avgdl, qn, gmatrix, gadj, gvalidf, n_cand, w_lex, w_vec)


# ---------------------------------------------------------------------------
# the pipeline object
# ---------------------------------------------------------------------------


class FusedHybrid:
    """Device-fused hybrid search over a (BM25Index, BruteForceIndex)
    pair. Stateless beyond the lexical snapshot (owned by
    :class:`DeviceBM25`) and the lexical-row -> vector-slot join map;
    both re-derive from the live host indexes, which remain the
    mutable sources of truth."""

    def __init__(
        self,
        bm25: BM25Index,
        brute: BruteForceIndex,
        n_shards: int = 1,
        min_n: int = 256,
        rebuild_stale_frac: float = 0.1,
        build_inline: bool = True,
        rrf_k: int = DEFAULT_RRF_K,
        walk_min_n: Optional[int] = None,
        cagra: Optional[CagraIndex] = None,
    ):
        self.bm25 = bm25
        self.brute = brute
        self.rrf_k = rrf_k
        self.n_shards = max(1, n_shards)
        self.lex = DeviceBM25(
            bm25, n_shards=self.n_shards, min_n=min_n,
            rebuild_stale_frac=rebuild_stale_frac,
            build_inline=build_inline)
        # walk tier: above `walk_min_n` live vectors the vector half
        # runs the CAGRA greedy walk instead of the exact matmul
        # (None = tier disabled, matmul always). A caller that already
        # owns a graph over the SAME brute index (the service's cagra
        # strategy tier) shares it here — one graph, one rebuild
        # cadence; otherwise the pipeline wraps its own.
        self.walk_min_n = walk_min_n
        if cagra is not None and cagra._brute is not brute:
            # a graph over some OTHER brute index (e.g. captured by a
            # background build that raced an index reload) must never
            # serve: its row ids and freshness counters belong to a
            # discarded corpus
            cagra = None
        if cagra is None and walk_min_n is not None:
            from nornicdb_tpu.search.ann_quality import current_profile

            p = current_profile()
            cagra = CagraIndex(
                brute=brute, degree=p.cagra_degree,
                itopk=p.cagra_itopk, search_width=p.cagra_width,
                min_n=walk_min_n, n_shards=self.n_shards,
                build_inline=build_inline)
        self.cagra = cagra
        self._grow_cache: Optional[Tuple] = None
        # sharded placement cache for the brute device arrays, keyed on
        # the array object identity (BruteForceIndex recreates it on
        # mutation) — a persistent serving index never re-ships the
        # corpus across devices per batch
        self._vec_placed: Optional[Tuple] = None

    def build(self) -> bool:
        return self.lex.build()

    @property
    def ready(self) -> bool:
        return self.lex.snapshot_built

    def ensure(self) -> bool:
        """Have (or start building) a lexical snapshot; False while the
        host path must serve."""
        return self.lex.ensure_snapshot() is not None

    # -- freshness helpers ------------------------------------------------

    def _ensure_map(self, snap: Dict[str, Any], mutations: int):
        """Device lex-row -> vector-slot map consistent with the brute
        matrix at generation ``mutations``, or None when a concurrent
        write/compaction moved the matrix on from the captured view —
        slots_of pins the read to the expected generation under the
        brute lock, so a remap can never mis-join silently."""

        def derive():
            ids = ["" if e is None else e for e in snap["row_ids"]]
            raw = self.brute.slots_of(ids, expect_mutations=mutations)
            return None if raw is None else np.asarray(raw, np.int32)

        return self.lex.row_map(snap, "l2v", mutations, derive)

    def _ensure_walk_map(self, snap: Dict[str, Any], g: Dict[str, Any]):
        """Device lex-row -> graph-row map for the walk tier, keyed on
        the graph's build sequence so a background rebuild (new row
        space) rebinds the join on the very next batch instead of
        serving a stale map."""

        def derive():
            grow = self._graph_rows(g)
            return np.asarray(
                [-1 if e is None else grow.get(e, -1)
                 for e in snap["row_ids"]], dtype=np.int32)

        return self.lex.row_map(snap, "l2g", g["build_seq"], derive)

    def _graph_rows(self, g: Dict[str, Any]) -> Dict[str, int]:
        # keyed on build_seq, NOT the dict: holding g here would pin a
        # replaced graph's device arrays until the next walk dispatch
        cached = self._grow_cache
        if cached is not None and cached[0] == g["build_seq"]:
            return cached[1]
        grow = {e: i for i, e in enumerate(g["row_ids"])
                if e is not None}
        self._grow_cache = (g["build_seq"], grow)
        return grow

    def rebind_cagra(self, cagra: CagraIndex) -> bool:
        """Swap the walk tier's graph index in place (the strategy
        machine built its own over the same brute index). Keeps the
        lexical snapshot serving — the graph-derived state (l2g map,
        row cache) rebinds lazily via the new graph's build_seq.
        False when the graph wraps a DIFFERENT brute index (caller
        must re-wrap the whole pipeline instead)."""
        if cagra is not None and cagra._brute is not self.brute:
            return False
        self.cagra = cagra
        self._grow_cache = None
        return True

    def _vec_arrays(self, m, valid, snap):
        if snap["shards"] == 1 or "mesh" not in snap:
            return m, valid
        if m.shape[0] % snap["shards"] != 0:
            return None, None  # capacity not shardable; caller falls back
        cached = self._vec_placed
        if cached is not None and cached[0] is m:
            return cached[1], cached[2]
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = snap["mesh"]
        mp = jax.device_put(m, NamedSharding(mesh, P("data", None)))
        vp = jax.device_put(valid, NamedSharding(mesh, P("data")))
        self._vec_placed = (m, mp, vp)
        return mp, vp

    def _record_cost(self, kind: str, b: int, snap: Dict[str, Any],
                     vec_flops_bytes: Tuple[float, float]) -> None:
        """Per-query cost accounting for one fused dispatch: the vector
        tier's price (matmul or walk, passed in) plus the lexical CSR
        price from the (nnz, unique-terms) the lexical plan() just
        stashed on this thread. The lexical matmul is priced at the
        snapshot's PADDED doc width (shards * c_local — the shape the
        program executes, same as DeviceBM25's standalone pricing), not
        the live row count. Best-effort — pricing must never fail a
        search, and with telemetry off the arithmetic is skipped
        entirely."""
        if not _cost.pricing_enabled():
            return
        try:
            nnz, u = self.lex._plan_cost.stats
            lex_f, lex_b = _cost.price_bm25(
                pow2_bucket(max(b, 1)), nnz, u,
                int(snap["shards"]) * int(snap["c_local"]))
            vec_f, vec_b = vec_flops_bytes
            _cost.record_query_cost(
                kind, _cost.cost_name(self.lex), b,
                lex_f + vec_f, lex_b + vec_b)
        except Exception:  # noqa: BLE001
            pass

    # -- search -----------------------------------------------------------

    def search_batch(
        self,
        queries_emb: np.ndarray,
        kq: int,
        extras: Sequence[Dict[str, Any]],
    ) -> List[Optional[Dict[str, Any]]]:
        """One fused dispatch for a coalesced hybrid batch.

        ``extras[i]`` carries the non-stackable half of request i:
        ``tokens`` (tokenized query), ``n_cand`` (its overfetch depth)
        and ``w`` ((w_lex, w_vec) fusion weights). Returns one row per
        query: dict with ``lex``/``vec``/``fused`` ranked lists and the
        shared stage ``times``, or None when the device path must not
        serve this batch (caller falls back to the host path)."""
        b = len(queries_emb)
        none_rows: List[Optional[Dict[str, Any]]] = [None] * b
        snap = self.lex.ensure_snapshot()
        if snap is None:
            return none_rows
        delta = self.lex.delta_block(snap)
        if delta is None:
            _HYB_C.labels("host_fallback_changelog").inc()
            self._ledger(TIER_BRUTE_F32, "host", "changelog_overrun",
                         snap)
            self.lex._kick_background_rebuild()
            return none_rows
        if self.brute.view_meta() is None:
            return none_rows  # vector index empty
        t_plan0 = time.time()
        token_rows = [e["tokens"] for e in extras]
        try:
            with _span("hybrid.plan", b=b) as sp:
                self.lex.refresh_alive(snap)
                tstart, tlen, sel, avgdl = self.lex.plan(
                    snap, token_rows, b)
                entries, n_terms, u_b = self.lex._plan_cost.shape
                sp.annotate(entries=entries, terms=n_terms, u=u_b)
        except SnapshotStale:
            _HYB_C.labels("host_fallback_compaction").inc()
            self._ledger(TIER_BRUTE_F32, "host", "compaction", snap)
            self.lex._kick_background_rebuild()
            return none_rows
        except PlanOverflow:
            _HYB_C.labels("host_fallback_overflow").inc()
            self._ledger(TIER_BRUTE_F32, "host", "overflow", snap)
            return none_rows
        n_cand = np.asarray(
            [int(e["n_cand"]) for e in extras], dtype=np.int32)
        w_lex = np.asarray([e["w"][0] for e in extras], dtype=np.float32)
        w_vec = np.asarray([e["w"][1] for e in extras], dtype=np.float32)
        qn = l2_normalize(jnp.asarray(queries_emb, dtype=jnp.float32))
        lex_base = (jnp.asarray(tstart), jnp.asarray(tlen),
                    jnp.asarray(sel), snap["post_doc"],
                    snap["post_tf"], snap["doc_len"], snap["alive"])
        tail = (jnp.asarray(n_cand), jnp.asarray(w_lex),
                jnp.asarray(w_vec))
        # tier selection: walk above walk_min_n (sub-linear vector
        # half), else the exact matmul; a vetoed walk batch falls
        # through to the matmul tier, never to the host
        wctx = self._walk_context(snap, kq)
        walk_discarded_s = 0.0
        if wctx is not None:
            t_w0 = time.time()
            out = self._dispatch_walk(snap, wctx, lex_base, avgdl, qn,
                                      tail, kq, b, delta, token_rows,
                                      extras, t_plan0)
            if out is not None:
                return out
            # vetoed: account the discarded walk explicitly and reset
            # the plan clock, or the brute tier's plan_s (and the
            # lexical.score trace span) would silently absorb the
            # whole walk dispatch + decode
            walk_discarded_s = time.time() - t_w0
            t_plan0 = time.time()
        # quantized brute tier (device_quant): int8/PQ coarse scoring
        # replaces the float32 matmul inside the same compiled program;
        # the decode exact-reranks and host-refuses. A veto (freshness
        # gap, under-fill) falls through to the float32 exact tier —
        # the ladder is quantized -> float32 -> host
        qctx = self._quant_context(snap)
        if qctx is not None:
            t_q0 = time.time()
            out = self._dispatch_quant(snap, qctx, lex_base, avgdl, qn,
                                       tail, kq, b, delta, token_rows,
                                       extras, t_plan0)
            if out is not None:
                return out
            walk_discarded_s += time.time() - t_q0
            t_plan0 = time.time()
        # the exact tier's view capture happens only here — the walk
        # dispatch above never touches the brute matrix, so a served
        # walk batch leaves the pending writes to whoever scans next.
        # The lease holds the index lock until the fused program is
        # DISPATCHED: the next write's refresh donates m and valid
        with self.brute.device_lease() as lease:
            if lease.view is None:
                return none_rows
            m, valid, vec_ext, mutations, _compactions = lease.view
            l2v = self._ensure_map(snap, mutations)
            if l2v is None:
                # a write/compaction moved the brute matrix between the
                # view capture and the map read — retry next batch
                _HYB_C.labels("host_fallback_vec_race").inc()
                self._ledger(TIER_BRUTE_F32, "host", "vec_race", snap)
                return none_rows
            args = (*lex_base, l2v, jnp.float32(avgdl), qn)
            t0 = time.time()
            # from the call into the fused program to its arrays on the
            # host
            with _span("hybrid.dispatch", b=b, k=kq, entries=entries,
                       terms=n_terms, u=u_b, tier=TIER_BRUTE_F32):
                if snap["shards"] == 1:
                    ls, li, vs, vi, fs, fpos = _fused_single(
                        *args, m, valid, *tail, kq=kq, rrf_k=self.rrf_k)
                    lgrow = li
                elif "mesh" in snap \
                        and len(jax.devices()) >= snap["shards"]:
                    mp, vp = self._vec_arrays(m, valid, snap)
                    if mp is None:
                        _HYB_C.labels("host_fallback_unshardable").inc()
                        self._ledger(TIER_BRUTE_F32, "host",
                                     "unshardable", snap)
                        return none_rows
                    ls, lgrow, vs, vi, fs, fpos = _fused_sharded_impl(
                        *args, mp, vp, *tail, kq=kq, rrf_k=self.rrf_k,
                        mesh_holder=_holder(snap["mesh"]))
                else:
                    ls, lgrow, vs, vi, fs, fpos = self._shard_loop(
                        snap, args, m, valid, tail, kq)
                vec_price = _cost.price_brute(
                    pow2_bucket(b), int(m.shape[0]), int(m.shape[1]))
                # not pinned while this thread waits for the result
                del m, valid
                lease.release()
                # force to host inside the timed window (async dispatch)
                ls, lgrow = np.asarray(ls), np.asarray(lgrow)
                vs, vi = np.asarray(vs), np.asarray(vi)
                fs, fpos = np.asarray(fs), np.asarray(fpos)
        t1 = time.time()
        record_dispatch("hybrid_fused", pow2_bucket(b), kq, t1 - t0)
        _HYB_C.labels("dispatch").inc()
        self._record_cost("hybrid_fused", b, snap,
                          vec_flops_bytes=vec_price)
        with _span("hybrid.decode", b=b):
            out = self._decode(snap, vec_ext, delta, token_rows, extras,
                               ls, lgrow, vs, vi, fs, fpos, kq,
                               tier=TIER_BRUTE_F32)
        if delta:
            _HYB_C.labels("delta_merge").inc(len(extras))
        times = {"plan_s": t0 - t_plan0, "device_t0": t0,
                 "device_t1": t1, "decode_s": time.time() - t1,
                 "tier": "brute"}
        if walk_discarded_s:
            times["walk_discarded_s"] = round(walk_discarded_s, 6)
        for row in out:
            if row is not None:
                row["times"] = times
                row["tier"] = "brute"
        return out

    def _ledger(self, from_tier: str, to_tier: str, reason: str,
                snap=None, g=None) -> None:
        """Structured degrade record for this pipeline (the legacy
        hybrid_fused_events_total labels stay as aliases)."""
        versions = {}
        if snap is not None:
            versions["lex_built_mutations"] = snap.get("built_mutations")
        if g is not None:
            versions["graph_build_seq"] = g.get("build_seq")
            versions["graph_built_mutations"] = g.get("built_mutations")
        versions["brute_mutations"] = getattr(self.brute, "mutations", 0)
        _audit.record_degrade(
            "hybrid", from_tier, to_tier, reason,
            index=_cost.cost_name(self.lex), versions=versions)

    # -- quantized brute tier ---------------------------------------------

    def _quant_context(self, snap) -> Optional[Dict[str, Any]]:
        """Eligibility + freshness gate for the quantized vector half
        of the brute tier. None means the float32 exact tier serves —
        every gap degrades DOWN (quantized -> float32 -> host), never
        into a wrong answer."""
        from nornicdb_tpu.search.device_quant import quant_mode

        if quant_mode() == "off" or snap["shards"] != 1:
            # the quant programs are single-shard; sharded snapshots
            # keep the float32 mesh path
            return None
        hold = None
        if not _audit.tier_allowed(quant_tier(quant_mode())):
            # shadow-parity quarantine: the quantized rung steps down
            # to the float32 tier of the same ladder
            hold = "quarantine"
        elif not _audit.admission_allows(quant_tier(quant_mode())):
            # admission posture (ISSUE 15): overload forces the quant
            # rung down to float32 to shrink device pressure
            hold = "admission"
        if hold is not None:
            _HYB_C.labels("quant_quarantined").inc()
            self._ledger(quant_tier(quant_mode()), TIER_BRUTE_F32,
                         hold, snap)
            return None
        brute = self.brute
        plane = getattr(brute, "quant_plane", lambda: None)()
        if plane is None:
            return None
        qsnap = plane.ensure()
        if qsnap is None:
            _HYB_C.labels("quant_pending_build").inc()
            return None
        if qsnap["shards"] != 1:
            return None
        if qsnap["built_compactions"] != getattr(brute, "compactions",
                                                 0):
            _HYB_C.labels("quant_fallback_compaction").inc()
            self._ledger(quant_tier(qsnap["mode"]), TIER_BRUTE_F32,
                         "compaction", snap)
            plane._kick_background_rebuild()
            return None
        vdelta = brute.changed_since(qsnap["built_mutations"])
        if vdelta is None:
            _HYB_C.labels("quant_fallback_changelog").inc()
            self._ledger(quant_tier(qsnap["mode"]), TIER_BRUTE_F32,
                         "changelog_overrun", snap)
            plane._kick_background_rebuild()
            return None
        ids_view = brute.ids_meta()
        if ids_view is None:
            return None
        ids, mutations, compactions = ids_view
        if compactions != qsnap["built_compactions"]:
            return None
        return {"plane": plane, "qsnap": qsnap, "vdelta": vdelta,
                "ids": ids, "mutations": mutations}

    def _dispatch_quant(self, snap, qctx, lex_base, avgdl, qn, tail,
                        kq, b, delta, token_rows, extras, t_plan0):
        """One quantized brute-tier dispatch. Returns decoded rows, or
        None when the float32 exact tier must re-serve the batch
        (join-map race, rerank race, under-fill)."""
        qsnap = qctx["qsnap"]
        tier = quant_tier(qsnap["mode"])
        brute = self.brute
        l2v = self._ensure_map(snap, qctx["mutations"])
        if l2v is None:
            _HYB_C.labels("quant_fallback_vec_race").inc()
            self._ledger(tier, TIER_BRUTE_F32, "vec_race", snap)
            return None
        args = (*lex_base, l2v, jnp.float32(avgdl), qn)
        # the vector half overfetches past kq: coarse ordering is
        # noisiest exactly where the rerank matters, so the pool takes
        # the standalone plane's policy (overfetch * kq, floored; PQ
        # adds the capacity-scaled floor)
        plane = qctx["plane"]
        pool = plane.pool_for(kq, qsnap)
        t0 = time.time()
        if qsnap["mode"] == "int8":
            aux = qsnap["scale"]
            vec_price = _cost.price_int8_coarse(
                pow2_bucket(b), qsnap["capacity"], qsnap["dims"])
        else:
            aux = qsnap["codebooks"]
            vec_price = _cost.price_pq_adc(
                pow2_bucket(b), qsnap["capacity"], qsnap["pq_m"],
                qsnap["pq_codes"], qsnap["dims"] // qsnap["pq_m"])
        ls, li, vs, vi = _fused_single_quant(
            *args, qsnap["codes_t"], aux, qsnap["valid"], kq=kq,
            pool=pool, mode=qsnap["mode"])
        lgrow = li
        ls, lgrow = np.asarray(ls), np.asarray(lgrow)
        vs, vi = np.asarray(vs), np.asarray(vi)
        # decode never reads the device fuse on quant tiers (it always
        # re-fuses on host over the exact-reranked lists) — vs/vi stand
        # in for the unused (fs, fpos) slots
        fs = fpos = None
        t1 = time.time()
        record_dispatch("hybrid_fused_quant", pow2_bucket(b), kq,
                        t1 - t0)
        rf, rb = _cost.price_rerank(pow2_bucket(b), vs.shape[1],
                                    qsnap["dims"])
        self._record_cost("hybrid_fused_quant", b, snap,
                          vec_flops_bytes=(vec_price[0] + rf,
                                           vec_price[1] + rb))
        # exact rerank: gather the vector candidates' CURRENT float32
        # rows from the host source of truth (one lock hold) and
        # re-score — compressed scores rank the pool, never an answer
        qh = np.asarray(qn)
        uniq = np.unique(vi)
        got = brute.rows_for_slots(
            uniq, expect_compactions=qsnap["built_compactions"])
        if got is None:
            _HYB_C.labels("quant_fallback_vec_race").inc()
            self._ledger(tier, TIER_BRUTE_F32, "rerank_race", snap)
            return None
        rows_u, alive_u, _ids_u = got
        exact_u = qh @ rows_u.T  # [B, U]
        inv = np.searchsorted(uniq, vi)
        vs_e = np.take_along_axis(exact_u, inv, axis=1)
        ok = (vs > 0.5 * NEG_INF) & alive_u[inv]
        vs_e = np.where(ok, vs_e, np.float32(NEG_INF)).astype(
            np.float32)
        order = np.argsort(-vs_e, axis=1, kind="stable")
        vs_e = np.take_along_axis(vs_e, order, axis=1)
        vi = np.take_along_axis(vi, order, axis=1)
        # vector delta block: exact-float32 side-scan of post-build
        # adds/updates (the changelog discipline — stale plane codes
        # for an updated doc never reach an answer; ids removed since
        # logging are skipped by the shared one-lock gather)
        d_ids, d_mat = brute.delta_vectors(qctx["vdelta"])
        vec_delta = (d_ids, d_mat)
        out = self._decode(snap, qctx["ids"], delta, token_rows,
                           extras, ls, lgrow, vs_e, vi, fs, fpos, kq,
                           vec_delta=vec_delta, qn=qh,
                           force_refuse=True, tier=tier)
        # under-fill veto: live-filtering can leave a row short of
        # candidates the corpus does have — the float32 tier re-serves
        alive_n = len(brute)
        for row, e in zip(out, extras):
            if row is None:
                continue
            if len(row["vec"]) < min(int(e["n_cand"]), kq, alive_n):
                _HYB_C.labels("quant_underfill_f32").inc()
                self._ledger(tier, TIER_BRUTE_F32, "underfill", snap)
                return None
        _HYB_C.labels("quant_dispatch").inc()
        if d_ids:
            _HYB_C.labels("quant_delta_merge").inc()
        if delta:
            _HYB_C.labels("delta_merge").inc(len(extras))
        times = {"plan_s": t0 - t_plan0, "device_t0": t0,
                 "device_t1": t1, "decode_s": time.time() - t1,
                 "tier": "brute", "quant": qsnap["mode"]}
        for row in out:
            if row is not None:
                row["times"] = times
                row["tier"] = "brute"
        return out

    # -- walk tier --------------------------------------------------------

    def _walk_context(self, snap, kq: int) -> Optional[Dict[str, Any]]:
        """Eligibility + freshness gate for the walk tier. None means
        the brute-fused tier serves this batch — every ineligibility
        degrades DOWN the ladder (walk -> brute-fused -> host), never
        sideways into a wrong answer."""
        cagra = self.cagra
        if cagra is None or self.walk_min_n is None:
            return None
        if len(self.brute) < self.walk_min_n:
            return None
        g = cagra.ensure_graph()
        if g is None:
            # first build (or a rebuild after shrinking below min_n)
            # still running in the background: exact tier serves
            _HYB_C.labels("walk_pending_build").inc()
            return None
        tier = (TIER_WALK_QUANT
                if snap["shards"] == 1 and g.get("quant") is not None
                else TIER_WALK_F32)
        hold = None
        if not _audit.tier_allowed(tier):
            # shadow-parity quarantine: walk steps down its ladder to
            # the brute-fused tier until the breach clears
            hold = "quarantine"
        elif not _audit.admission_allows(tier):
            # admission posture (ISSUE 15): overload forces the walk
            # down to the brute-fused tier to shrink device pressure
            hold = "admission"
        if hold is not None:
            _HYB_C.labels("walk_quarantined").inc()
            self._ledger(tier, TIER_BRUTE_F32, hold, snap, g)
            return None
        if kq > cagra.itopk:
            # the walk pool only ever holds itopk candidates; a deeper
            # overfetch must come from the exact matmul tier
            _HYB_C.labels("walk_fallback_itopk").inc()
            self._ledger(tier, TIER_BRUTE_F32, "itopk_exceeded", snap, g)
            return None
        if g["shards"] != snap["shards"]:
            # lexical snapshot and graph must agree on the mesh layout
            # to run inside one shard_map program
            _HYB_C.labels("walk_fallback_shards").inc()
            self._ledger(tier, TIER_BRUTE_F32, "shard_mismatch", snap, g)
            return None
        delta_ids, delta_vecs = cagra.delta_block(g)
        if delta_ids is None:
            # churn outran the brute changelog (rebuild in flight):
            # brute-fused serves exactly until the fresh graph lands
            _HYB_C.labels("walk_fallback_changelog").inc()
            self._ledger(tier, TIER_BRUTE_F32, "changelog_overrun",
                         snap, g)
            return None
        # staleness from the LIVE counter, read only after delta_block
        # drained the changelog (the same order as CagraIndex._resolve):
        # a delete landing after an earlier capture would bump the
        # counter delta_block sees while the old value still compared
        # clean — skipping the live-filter and serving a tombstone
        return {"g": g, "l2g": self._ensure_walk_map(snap, g),
                "delta_ids": delta_ids, "delta_vecs": delta_vecs,
                "stale": self.brute.mutations != g["built_mutations"],
                "iters": g["iters"], "width": cagra.search_width,
                "itopk": cagra.itopk, "hash_bits": cagra.hash_bits,
                "n_seeds": cagra.n_seeds, "tier": tier}

    def _dispatch_walk(self, snap, wctx, lex_base, avgdl, qn, tail,
                       kq, b, delta, token_rows, extras, t_plan0):
        """One walk-tier dispatch. Returns the decoded rows, or None
        when the walk output under-filled a row's candidate list (the
        caller re-dispatches the batch through the exact tier)."""
        g = wctx["g"]
        # the program runs at per-source width itopk, not kq: the fuse
        # masks candidate depth by the traced n_cand anyway, and the
        # extra columns are what give the live-filter slack — a few
        # tombstones in the walk's top-n_cand must not force the exact
        # tier. One compiled width per graph config, so the (B, k)
        # compile universe stays one bucket per batch size.
        kp = wctx["itopk"]
        statics = dict(kq=kp, rrf_k=self.rrf_k, iters=wctx["iters"],
                       width=wctx["width"], itopk=wctx["itopk"],
                       hash_bits=wctx["hash_bits"],
                       n_seeds=wctx["n_seeds"])
        quant = g.get("quant") if snap["shards"] == 1 else None
        t0 = time.time()
        if quant is not None and quant["mode"] == "pq":
            # PQ graph base (ISSUE 17): the codes-only ADC walk runs
            # inside the same compiled program; exact pool rerank and
            # host re-fuse below are shared with the int8 path
            q_statics = dict(statics)
            del q_statics["rrf_k"]
            # 4x pool (matches cagra's PQ widening): ADC reconstruction
            # noise needs a wider pool for the exact rerank to recover
            q_statics["itopk"] = min(4 * q_statics["itopk"], 1024)
            kp = q_statics["itopk"]
            ls, li, vs, vi = _walk_fused_single_pq(
                *lex_base, wctx["l2g"], jnp.float32(avgdl), qn,
                quant["codes"], quant["codebooks"],
                g["adj"], g["validf"], **q_statics)
            lgrow = li
            fs = fpos = None
        elif quant is not None:
            # quantized graph base: the two-stage int8 walk runs inside
            # the same compiled program; the pool is exact-reranked
            # below from the HOST-resident float32 rows, and the host
            # re-fuse replaces the device fuse (fs/fpos never read)
            qp = qn @ quant["rot_dev"]
            q_statics = dict(statics)
            del q_statics["rrf_k"]
            ls, li, vs, vi = _walk_fused_single_q(
                *lex_base, wctx["l2g"], jnp.float32(avgdl), qp,
                quant["codes"], quant["codes_head"], quant["scale"],
                g["adj"], g["validf"], **q_statics,
                keep=quant["keep"])
            lgrow = li
            fs = fpos = None
        elif snap["shards"] == 1:
            ls, li, vs, vi, fs, fpos = _walk_fused_single(
                *lex_base, wctx["l2g"], jnp.float32(avgdl), qn,
                g["matrix"], g["adj"], g["validf"], *tail, **statics)
            lgrow = li
        elif "mesh" in snap and "mesh" in g \
                and len(jax.devices()) >= snap["shards"]:
            args = (*lex_base, wctx["l2g"], jnp.float32(avgdl), qn,
                    g["matrix"], g["adj"], g["validf"], *tail)
            ls, lgrow, vs, vi, fs, fpos = _walk_fused_sharded_impl(
                *args, **statics, mesh_holder=_holder(snap["mesh"]))
        else:
            ls, lgrow, vs, vi, fs, fpos = self._walk_shard_loop(
                snap, g, lex_base, wctx["l2g"], avgdl, qn, tail, kp,
                wctx)
        # force to host inside the timed window (async dispatch)
        ls, lgrow = np.asarray(ls), np.asarray(lgrow)
        vs, vi = np.asarray(vs), np.asarray(vi)
        if fs is not None:
            fs, fpos = np.asarray(fs), np.asarray(fpos)
        if quant is not None:
            # exact rerank of the walk pool against the host float32
            # rows (non-delta rows are immutable between builds, so
            # these ARE current values; delta ids re-score in _decode)
            gathered = g["matrix"][vi]  # host f32 [B, kp, D]
            vs_e = np.einsum("bpd,bd->bp", gathered, np.asarray(qn))
            vs_e = np.where(vs > 0.5 * NEG_INF, vs_e,
                            np.float32(NEG_INF)).astype(np.float32)
            order = np.argsort(-vs_e, axis=1, kind="stable")
            vs = np.take_along_axis(vs_e, order, axis=1)
            vi = np.take_along_axis(vi, order, axis=1)
        t1 = time.time()
        kind = ("hybrid_walk_fused_quant" if quant is not None
                else "hybrid_walk_fused")
        record_dispatch(kind, pow2_bucket(b), kp, t1 - t0)
        _HYB_C.labels("walk_dispatch").inc()
        if quant is not None:
            d_model = int(qn.shape[1])
            if quant["mode"] == "pq":
                vf, vb = _cost.price_walk_pq(
                    pow2_bucket(b), d_model, wctx["iters"],
                    wctx["width"], int(g["adj"].shape[1]),
                    kp, quant["pq_m"], quant["pq_codes"],
                    n_seeds=wctx["n_seeds"])
            else:
                vf, vb = _cost.price_walk_quant(
                    pow2_bucket(b), d_model, wctx["iters"],
                    wctx["width"], int(g["adj"].shape[1]),
                    wctx["itopk"], quant["head_dims"], quant["keep"],
                    n_seeds=wctx["n_seeds"])
            rf, rb = _cost.price_rerank(pow2_bucket(b), kp, d_model)
            self._record_cost(kind, b, snap,
                              vec_flops_bytes=(vf + rf, vb + rb))
        else:
            self._record_cost(kind, b, snap,
                              vec_flops_bytes=_cost.price_walk(
                                  pow2_bucket(b),
                                  int(g["matrix"].shape[1]),
                                  wctx["iters"], wctx["width"],
                                  int(g["adj"].shape[1]), wctx["itopk"],
                                  n_seeds=wctx["n_seeds"]))
        out = self._decode(
            snap, g["row_ids"], delta, token_rows, extras,
            ls, lgrow, vs, vi, fs, fpos, kp,
            vec_delta=(wctx["delta_ids"], wctx["delta_vecs"]),
            vec_stale=wctx["stale"], qn=np.asarray(qn),
            force_refuse=quant is not None, tier=wctx["tier"])
        # under-fill veto: a stale graph's live-filter (or a walk miss)
        # can leave a row short of candidates the corpus does have —
        # those batches re-dispatch through the exact tier, the same
        # never-under-serve contract as CagraIndex.search_batch
        alive_n = len(self.brute)
        for row, e in zip(out, extras):
            if row is None:
                continue
            if len(row["vec"]) < min(int(e["n_cand"]), kp, alive_n):
                _HYB_C.labels("walk_underfill_brute").inc()
                self._ledger(wctx["tier"], TIER_BRUTE_F32, "underfill",
                             snap, g)
                return None
        # freshness/merge accounting only once the batch actually
        # serves from the walk tier — a vetoed batch re-dispatches
        # through the exact tier and must not count twice
        if wctx["delta_ids"]:
            _HYB_C.labels("walk_delta_merge").inc()
        elif wctx["stale"]:
            _HYB_C.labels("walk_live_filter").inc()
        if delta:
            _HYB_C.labels("delta_merge").inc(len(extras))
        times = {"plan_s": t0 - t_plan0, "device_t0": t0,
                 "device_t1": t1, "decode_s": time.time() - t1,
                 "tier": "walk", "walk_iters": wctx["iters"],
                 "walk_itopk": wctx["itopk"],
                 **({"quant": "int8"} if quant is not None else {})}
        for row in out:
            if row is not None:
                row["times"] = times
                row["tier"] = "walk"
        return out

    def _walk_shard_loop(self, snap, g, lex_base, l2g, avgdl, qn,
                         tail, kq, wctx):
        """Single-device reference for the sharded walk tier: each
        shard's lexical parts + local-subgraph walk, merged in shard
        order (the all-gather layout), fused once. The mesh path must
        match this bit-for-bit."""
        tstart, tlen, sel, pd, pt, dl, al = lex_base
        n_cand, w_lex, w_vec = tail
        s_n = snap["shards"]
        c_local = snap["c_local"]
        p_b = tstart.shape[0] // s_n
        p_cap = pd.shape[0] // s_n
        r = g["rows_per_shard"]
        kw = min(kq, wctx["itopk"])
        avgdl_j = jnp.float32(avgdl)
        lex_parts, vec_parts = [], []
        for sh in range(s_n):
            ls, lid, lgrow = _lex_parts(
                tstart[sh * p_b:(sh + 1) * p_b],
                tlen[sh * p_b:(sh + 1) * p_b],
                sel,
                pd[sh * p_cap:(sh + 1) * p_cap],
                pt[sh * p_cap:(sh + 1) * p_cap],
                dl[sh * c_local:(sh + 1) * c_local],
                al[sh * c_local:(sh + 1) * c_local],
                l2g[sh * c_local:(sh + 1) * c_local],
                avgdl_j, jnp.int32(sh * c_local), kq=kq)
            lex_parts.append((ls, lid, lgrow))
        for sh, (m_sh, a_sh, v_sh) in enumerate(g["shard_slices"]):
            ws, wi = _cagra_walk(
                qn, m_sh, a_sh, v_sh, k=kw, iters=wctx["iters"],
                width=wctx["width"], itopk=wctx["itopk"],
                hash_bits=wctx["hash_bits"], n_seeds=wctx["n_seeds"])
            vec_parts.append((ws, wi + sh * r))
        ls2, lid2, lgrow2 = _merge_parts(lex_parts, kq)
        vs2, vi2 = _merge_parts(vec_parts, kq)
        fs, fpos = _fuse_merged(ls2, lid2, lgrow2, vs2, vi2, n_cand,
                                w_lex, w_vec, kq=kq, rrf_k=self.rrf_k,
                                c_vec_total=int(g["shards"] * r))
        return ls2, lgrow2, vs2, vi2, fs, fpos

    def _shard_loop(self, snap, args, m, valid, tail, kq):
        """Single-device reference for the sharded layout: run every
        shard's local parts, merge in shard order (the all-gather
        layout), fuse once. The mesh path must match this bit-for-bit."""
        tstart, tlen, sel, pd, pt, dl, al, l2v, avgdl, qn = args
        n_cand, w_lex, w_vec = tail
        s_n = snap["shards"]
        c_local = snap["c_local"]
        p_b = tstart.shape[0] // s_n
        p_cap = pd.shape[0] // s_n
        mj, vj = jnp.asarray(m), jnp.asarray(valid)
        c_vec_local = mj.shape[0] // s_n
        lex_parts, vec_parts = [], []
        for sh in range(s_n):
            ls, lid, lgrow, vvs, gvi = _local_parts(
                tstart[sh * p_b:(sh + 1) * p_b],
                tlen[sh * p_b:(sh + 1) * p_b],
                sel,
                pd[sh * p_cap:(sh + 1) * p_cap],
                pt[sh * p_cap:(sh + 1) * p_cap],
                dl[sh * c_local:(sh + 1) * c_local],
                al[sh * c_local:(sh + 1) * c_local],
                l2v[sh * c_local:(sh + 1) * c_local],
                avgdl, qn,
                mj[sh * c_vec_local:(sh + 1) * c_vec_local],
                vj[sh * c_vec_local:(sh + 1) * c_vec_local],
                jnp.int32(sh * c_local), jnp.int32(sh * c_vec_local),
                kq=kq)
            lex_parts.append((ls, lid, lgrow))
            vec_parts.append((vvs, gvi))
        ls2, lid2, lgrow2 = _merge_parts(lex_parts, kq)
        vs2, vi2 = _merge_parts(vec_parts, kq)
        fs, fpos = _fuse_merged(ls2, lid2, lgrow2, vs2, vi2, n_cand,
                                w_lex, w_vec, kq=kq, rrf_k=self.rrf_k,
                                c_vec_total=int(mj.shape[0]))
        return ls2, lgrow2, vs2, vi2, fs, fpos

    def _decode(self, snap, vec_ids, delta, token_rows, extras,
                ls, lgrow, vs, vi, fs, fpos, kq,
                vec_delta=None, vec_stale=False, qn=None,
                force_refuse=False, tier=TIER_BRUTE_F32):
        """Decode one dispatch's device candidates into per-request
        ranked lists. ``vec_ids`` maps vector candidate ids to ext ids
        (the brute ext-id table for the matmul tier, graph ``row_ids``
        for the walk tier). The walk tier's vector-side freshness rides
        ``vec_delta``/``vec_stale``: tombstoned docs are live-filtered
        out of the walk output, post-build adds/updates are
        exact-scored (``qn @ delta_vecs``) and merged in, and any
        vector-side correction reroutes fusion through the
        bit-compatible host ``rrf_fuse`` — read-your-writes without a
        graph rebuild.

        Every returned row carries ``served_by`` (obs/audit taxonomy):
        ``tier`` when the device fuse answered, ``host`` for rows whose
        freshness correction (live-filter drop, delta merge) forced the
        host re-fuse — PER ROW, so one corrected rider in a coalesced
        batch never relabels its batch-mates. The quant tiers' by-design
        host re-fuse (``force_refuse``) keeps the quant tier label: the
        exact rerank is the tier's contract, not a degrade."""
        row_ids = snap["row_ids"]
        d_ids, d_vecs = vec_delta if vec_delta is not None else ([], None)
        d_set = set(d_ids)
        d_scores = qn @ d_vecs.T if d_ids else None  # exact cosines
        live: Optional[set] = None
        if vec_stale:
            # ONE locked membership pass over every distinct walk
            # candidate — a per-id `in brute` inside the loop would
            # take the index lock up to B*itopk times per batch
            cand = {vec_ids[i] for i in np.unique(vi)}
            cand.discard(None)
            live = self.brute.contains_many(cand)
        out: List[Optional[Dict[str, Any]]] = []
        live_filtered_rows = 0
        for r in range(len(extras)):
            n_cand = int(extras[r]["n_cand"])
            lex_hits: List[Tuple[str, float]] = []
            lex_by_pos: Dict[int, str] = {}
            for c in range(min(kq, ls.shape[1])):
                if ls[r, c] < 0.5 * NEG_INF or len(lex_hits) >= n_cand:
                    break
                eid = row_ids[int(lgrow[r, c])]
                if eid is None:
                    continue
                lex_by_pos[c] = eid
                lex_hits.append((eid, float(ls[r, c])))
            vec_hits: List[Tuple[str, float]] = []
            vec_by_pos: Dict[int, str] = {}
            vec_fixed = force_refuse  # this row's list diverged from
            #   the device-fused one: re-fuse on host. A merely-stale
            #   graph whose top-itopk held no tombstone keeps the
            #   device fuse. Quantized tiers ALWAYS re-fuse: their
            #   device fuse ranked coarse scores, the decode reranked
            #   them exactly.
            # the quant tiers overfetch vs/vi wider than kq (rerank
            # pool); the break on n_cand keeps served depth identical
            for c in range(vs.shape[1]):
                if vs[r, c] < 0.5 * NEG_INF or len(vec_hits) >= n_cand:
                    break
                eid = vec_ids[int(vi[r, c])]
                if eid is None:
                    continue
                if eid in d_set:
                    continue  # walk scored the pre-update vector
                if live is not None and eid not in live:
                    vec_fixed = True
                    continue  # tombstoned since the graph build
                vec_by_pos[c] = eid
                vec_hits.append((eid, float(vs[r, c])))
            if d_ids:
                vec_hits = merge_delta_hits(vec_hits, d_ids,
                                            d_scores[r], n_cand)
                vec_fixed = True
            served_by = tier
            if delta:
                # read-your-writes: exact host scores for post-snapshot
                # docs, then the (bit-compatible) host fuse over the
                # merged lists (the caller counts delta_merge once the
                # batch actually serves — a vetoed walk decode must not
                # double-count against the brute re-dispatch)
                dset = set(delta)
                fresh = self.bm25.score_docs(token_rows[r], delta)
                merged = [(e, s) for e, s in lex_hits if e not in dset]
                merged.extend(sorted(fresh.items()))
                merged.sort(key=lambda kv: -kv[1])
                lex_hits = merged[:n_cand]
                fused = rrf_fuse([lex_hits, vec_hits],
                                 weights=list(extras[r]["w"]),
                                 k=self.rrf_k, limit=n_cand)
                if not force_refuse:
                    served_by = "host"
            elif vec_fixed:
                # the device fuse saw the pre-correction vector list;
                # re-fuse on host (bit-compatible) over the fixed lists
                fused = rrf_fuse([lex_hits, vec_hits],
                                 weights=list(extras[r]["w"]),
                                 k=self.rrf_k, limit=n_cand)
                if not force_refuse:
                    # this rider's live-filter/delta correction routed
                    # its fusion to the host — ITS tier is host, its
                    # batch-mates keep the device tier
                    served_by = "host"
                    live_filtered_rows += 1
            else:
                fused = []
                for c in range(fs.shape[1]):
                    if fs[r, c] < 0.5 * NEG_INF or len(fused) >= n_cand:
                        break
                    pos = int(fpos[r, c])
                    eid = (lex_by_pos.get(pos) if pos < kq
                           else vec_by_pos.get(pos - kq))
                    if eid is None:
                        continue
                    fused.append((eid, float(fs[r, c])))
            out.append({"lex": lex_hits, "vec": vec_hits,
                        "fused": fused, "served_by": served_by})
        if live_filtered_rows:
            # one ledger record per batch for the rider-level host
            # re-fuse (delta merges are routine read-your-writes and
            # ride the delta_merge counter instead)
            self._ledger(tier, "host", "live_filter", snap)
        return out


def _holder(mesh):
    from nornicdb_tpu.parallel.mesh import _MeshHolder

    return _MeshHolder(mesh)
