"""Cosine similarity + top-k kernels — the hot path of vector search.

Replaces the reference's per-backend kernels (CUDA cuda_kernels.cu:263-420
cosine/topk, Metal shaders_darwin.metal:43-360, Vulkan shaders/*.comp,
pkg/simd BatchCosineSimilarity simd.go:149) with jitted XLA:

- one [B,D] x [D,C] matmul lands on the MXU;
- capacity-padded buffers + validity masks keep shapes static so XLA
  never recompiles as the index grows (SURVEY.md §7 "dynamic shapes");
- a chunked lax.scan variant bounds HBM for very large C by never
  materializing the full [B,C] score matrix.

All functions are pure and jit-cached per (shape, k) signature.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# What "exact" means for the float32 tiers. A TPU's default float32 matmul
# rounds its operands to bfloat16. The exactness contracts — rank parity
# with the host NumPy path, shadow parity 1.0, lower-slot-first tie order —
# were written against float32 arithmetic, so every exact-tier matmul asks
# for it. On a v5e against float64 (scripts/bringup_probe.py, PR 21;
# PERF.md finding 1): DEFAULT is off by 2.6e-4 and gets 1 top-10 id in 640
# wrong; HIGH (three passes) is off by 2.0e-6, inside a tie band of 1e-5,
# and still got 1 id in 640 wrong; HIGHEST (six passes) stays at float32
# rounding, 2.1e-8, recall 1.0, for 8-25% more time than DEFAULT once the
# matrix is large enough to see it. HIGH is the candidate if a contract
# with a stated tie band ever replaces rank parity. On CPU all three are
# the same program.
EXACT = jax.lax.Precision.HIGHEST


def pad_dim(n: int, minimum: int = 256) -> int:
    """Round capacity up to the next power-of-two multiple of `minimum`
    (a lane-friendly size) so jit caches stay small as the index grows."""
    if n <= minimum:
        return minimum
    capacity = minimum
    while capacity < n:
        capacity *= 2
    return capacity


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n. Shape bucketing for device dispatch:
    every distinct (B, k) is its own XLA compile, so batch and k are
    padded to buckets to cap the compile universe at log2 shapes."""
    b = 1
    while b < n:
        b <<= 1
    return b


@jax.jit
def l2_normalize(x: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    """Row-normalize so cosine similarity reduces to a dot product
    (reference: normalize kernels, cuda_kernels.cu:206)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, eps)


@functools.partial(jax.jit, static_argnames=("k",))
def _cosine_topk_impl(
    queries: jnp.ndarray,  # [B, D] (normalized)
    matrix: jnp.ndarray,  # [C, D] (normalized, capacity-padded)
    valid: jnp.ndarray,  # [C] bool
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scores = jnp.matmul(queries, matrix.T, precision=EXACT)  # [B, C]
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def cosine_topk(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact cosine top-k. Inputs must be L2-normalized. Returns
    (scores [B,k], indices [B,k]); masked-out rows score NEG_INF."""
    k = min(k, matrix.shape[0])
    return _cosine_topk_impl(queries, matrix, valid, k)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _cosine_topk_chunked_impl(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b = queries.shape[0]
    c = matrix.shape[0]
    n_chunks = c // chunk  # capacity is a multiple of chunk by construction

    def step(carry, i):
        best_s, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(matrix, i * chunk, chunk, axis=0)
        vmask = jax.lax.dynamic_slice_in_dim(valid, i * chunk, chunk, axis=0)
        s = jnp.matmul(queries, rows.T, precision=EXACT)  # [B, chunk]
        s = jnp.where(vmask[None, :], s, NEG_INF)
        idx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(idx, (b, chunk))], axis=1)
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return (top_s, top_i), None

    init = (
        jnp.full((b, k), NEG_INF, dtype=queries.dtype),
        jnp.zeros((b, k), dtype=jnp.int32),
    )
    (best_s, best_i), _ = jax.lax.scan(
        step, init, jnp.arange(n_chunks, dtype=jnp.int32)
    )
    return best_s, best_i


# codes of a payload column (``BruteForceIndex``): a bounded condition never
# matches the first two, open bounds match every code
COL_MISSING = -(1 << 31)       # the point has no such field
COL_UNCODED = COL_MISSING + 1  # it has a value the column cannot hold
COL_LO = COL_MISSING + 2
COL_HI = (1 << 31) - 1


def _bounds_mask(cols: jnp.ndarray, bounds: jnp.ndarray) -> jnp.ndarray:
    """``[B, n]`` bool: row of ``cols [F, n]`` by rider of ``bounds
    [B, F, 2]``, true where every field's code lies in the rider's
    inclusive ``[lo, hi]``."""
    lo = bounds[:, :, 0, None]
    hi = bounds[:, :, 1, None]
    return jnp.all((cols[None] >= lo) & (cols[None] <= hi), axis=1)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _cosine_topk_filtered_impl(
    queries: jnp.ndarray,  # [B, D] (normalized)
    matrix: jnp.ndarray,  # [C, D]
    valid: jnp.ndarray,  # [C] bool
    columns: jnp.ndarray,  # [F, C] int32 payload codes, slot-aligned
    bounds: jnp.ndarray,  # [B, F, 2] int32, inclusive, per rider
    k: int,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``_cosine_topk_chunked_impl`` with a mask a rider: rider ``b`` sees
    row ``c`` when ``valid[c]`` and ``bounds[b, f, 0] <= columns[f, c] <=
    bounds[b, f, 1]`` for every field ``f``. Every row is scored; a row a
    rider may not see scores ``NEG_INF`` for that rider. ``chunk`` equal to
    the capacity is the dense scan."""
    b = queries.shape[0]
    c = matrix.shape[0]
    f = columns.shape[0]
    if chunk >= c:
        s = jnp.matmul(queries, matrix.T, precision=EXACT)
        s = jnp.where(valid[None, :] & _bounds_mask(columns, bounds), s,
                      NEG_INF)
        return jax.lax.top_k(s, k)

    def step(carry, i):
        best_s, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(matrix, i * chunk, chunk, axis=0)
        vmask = jax.lax.dynamic_slice_in_dim(valid, i * chunk, chunk, axis=0)
        cols = jax.lax.dynamic_slice(columns, (0, i * chunk), (f, chunk))
        s = jnp.matmul(queries, rows.T, precision=EXACT)  # [B, chunk]
        s = jnp.where(vmask[None, :] & _bounds_mask(cols, bounds), s,
                      NEG_INF)
        idx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(idx, (b, chunk))], axis=1)
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return (top_s, top_i), None

    init = (
        jnp.full((b, k), NEG_INF, dtype=queries.dtype),
        jnp.zeros((b, k), dtype=jnp.int32),
    )
    (best_s, best_i), _ = jax.lax.scan(
        step, init, jnp.arange(c // chunk, dtype=jnp.int32)
    )
    return best_s, best_i


def cosine_topk_filtered(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    columns: jnp.ndarray,
    bounds: jnp.ndarray,
    k: int,
    chunk: int = 16384,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact cosine top-k among the rows each rider's bounds let through,
    routed as ``cosine_topk_auto`` routes the plain scan: dense up to
    ``CHUNKED_THRESHOLD`` rows (and for a capacity no power-of-two chunk
    divides), chunked above."""
    c = matrix.shape[0]
    k = min(k, c)
    if c > CHUNKED_THRESHOLD:
        while c % chunk != 0 and chunk >= 512:
            chunk //= 2
    if c <= CHUNKED_THRESHOLD or c % chunk != 0:
        chunk = c
    return _cosine_topk_filtered_impl(queries, matrix, valid, columns,
                                      bounds, k, chunk)


# above this row count, route to the chunked kernel to bound HBM
CHUNKED_THRESHOLD = 262_144


def cosine_topk_auto(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense below CHUNKED_THRESHOLD rows, chunked above — the single
    routing point so every caller (and every fallback) bounds HBM the
    same way."""
    if matrix.shape[0] > CHUNKED_THRESHOLD:
        return cosine_topk_chunked(queries, matrix, valid, k)
    return cosine_topk(queries, matrix, valid, k)


def cosine_topk_chunked(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    chunk: int = 16384,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact cosine top-k without materializing the [B,C] score matrix:
    scans C in chunks, keeping a running [B,k] best set. Use when
    B*C*4 bytes would pressure HBM (e.g. C ~ 1M)."""
    c = matrix.shape[0]
    k = min(k, c)
    if c <= chunk:
        return _cosine_topk_impl(queries, matrix, valid, k)
    chunk = min(chunk, c)
    # pad_dim capacities are power-of-two multiples of 256, so a power-of-two
    # chunk divides them; for other capacities fall back to dense rather
    # than degrading to a tiny-chunk scan
    while c % chunk != 0 and chunk >= 512:
        chunk //= 2
    if c % chunk != 0:
        return _cosine_topk_impl(queries, matrix, valid, k)
    return _cosine_topk_chunked_impl(queries, matrix, valid, k, chunk)


def concat_topk(
    scores_parts: Sequence[jnp.ndarray],
    ids_parts: Sequence[jnp.ndarray],
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-shard top-k blocks: concatenate [B, k_i] score/id parts
    in shard order and take one global top-k. This is the single-device
    reference of the ``all_gather + top_k`` collective merge — the
    shard-major concat layout is identical to a tiled all-gather, so the
    merged ranking (including tie order, which lax.top_k resolves by
    lower concatenated position) is bit-identical to the sharded path.
    Shared by the CAGRA walk, the device BM25 scorer and the fused
    hybrid pipeline."""
    all_s = jnp.concatenate(list(scores_parts), axis=1)
    all_i = jnp.concatenate(list(ids_parts), axis=1)
    top_s, pos = jax.lax.top_k(all_s, k)
    return top_s, jnp.take_along_axis(all_i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
def euclidean_topk(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k by (negated) squared euclidean distance
    (reference: euclidean_distance kernel, shaders_darwin.metal)."""
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)  # [B,1]
    m2 = jnp.sum(matrix * matrix, axis=1)  # [C]
    d2 = q2 + m2[None, :] - 2.0 * (queries @ matrix.T)
    d2 = jnp.where(valid[None, :], -d2, NEG_INF)
    neg_d, idx = jax.lax.top_k(d2, k)
    return -neg_d, idx


@jax.jit
def batch_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-wise dot products (reference: batch_dot kernel)."""
    return jnp.sum(a * b, axis=-1)


@functools.partial(jax.jit, static_argnames=("threshold_is_min",))
def filter_by_similarity(
    query: jnp.ndarray,  # [D]
    matrix: jnp.ndarray,  # [C, D]
    valid: jnp.ndarray,  # [C]
    threshold: float,
    threshold_is_min: bool = True,
) -> jnp.ndarray:
    """Boolean mask of rows whose cosine similarity clears the threshold
    (reference: filter_by_similarity kernel, shaders_darwin.metal)."""
    scores = matrix @ query
    ok = scores >= threshold if threshold_is_min else scores <= threshold
    return ok & valid
