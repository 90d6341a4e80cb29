"""Plain reference for an exact cosine top-k collection, and its control.

Imports nothing of the program and takes nothing the program made: only
the vectors the benchmark generated from the seed, the query vectors it
sent and the (id, score) lists the timed requests returned.

Reference: cosine of every row with every checked query. A float32 matrix
product over the whole collection (in blocks of rows, on the host) picks,
for each query, every row within ``MARGIN`` of its ``limit``-th best;
those rows and every row the program served are then scored again in
float64. The float32 pass errs by ~1e-7, a thousandth of the margin, so
the float64 top-``limit`` is exact.

Three numbers come out, over all hits of all checked requests:

``score_err``  the largest |served score - float64 cosine|.
``score_err_rms`` the root mean square of the same differences: it barely
               moves from seed to seed, where the largest of 6,400 swings.
``rank_gap``   the float64 cosine of the true ``limit``-th best row less
               the lowest float64 cosine among the rows served, not below
               0: how far below the cut the program reached for a row. A
               swap of two rows closer than float32 can tell apart reads
               about 1e-7; a row that does not belong reads 1e-3 or more.

Control (the nearest precision below the float32 ``highest`` the
configuration states): the same top-``limit`` with every product at matmul
precision ``high``, three bfloat16 passes. On a TPU that is the chip's own
``jax.lax.Precision.HIGH``; elsewhere the flag does nothing, so the three
passes (hi*hi + hi*lo + lo*hi) are written out in NumPy. With the high
piece taken by truncation the errors of aligned vectors add up one way, as
the chip's do: 4.1e-6 rms here against 4.2e-6 on the v5e; with both pieces
rounded it reads 1.9e-7 (PERF.md section 6).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

BLOCK = 65536
MARGIN = 1e-4


def _row_norms(block: np.ndarray) -> np.ndarray:
    return np.sqrt(np.einsum("ij,ij->i", block, block, dtype=np.float32))


def cosines32(vectors: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """[N, S] float32 cosines, block by block."""
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    out = np.empty((vectors.shape[0], queries.shape[0]), np.float32)
    for start in range(0, vectors.shape[0], BLOCK):
        block = vectors[start:start + BLOCK]
        out[start:start + BLOCK] = (block @ qn.T.astype(np.float32)) \
            / _row_norms(block)[:, None]
    return out


def cosines64(vectors: np.ndarray, query: np.ndarray,
              rows: np.ndarray) -> np.ndarray:
    v = vectors[rows].astype(np.float64)
    q = query.astype(np.float64)
    return (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))


def judge(vectors: np.ndarray, queries: np.ndarray,
          served_ids: Sequence[np.ndarray],
          served_scores: Sequence[np.ndarray],
          limit: int) -> Dict[str, float]:
    """Worst ``score_err`` and ``rank_gap`` over the checked requests.
    ``served_ids[i]`` are row numbers, best first, for ``queries[i]``."""
    coarse = cosines32(vectors, queries)
    score_err = 0.0
    rank_gap = 0.0
    squares, count = 0.0, 0
    for i in range(queries.shape[0]):
        col = coarse[:, i]
        kth = np.partition(col, -limit)[-limit]
        cand = np.flatnonzero(col >= kth - MARGIN)
        ids = np.asarray(served_ids[i], np.int64)
        rows = np.union1d(cand, ids)
        exact = cosines64(vectors, queries[i], rows)
        true_kth = np.partition(exact, -limit)[-limit]
        of_served = exact[np.searchsorted(rows, ids)]
        served = np.asarray(served_scores[i], np.float64)
        diff = served - of_served
        score_err = max(score_err, float(np.max(np.abs(diff))))
        squares += float(np.sum(diff * diff))
        count += len(diff)
        rank_gap = max(rank_gap, float(true_kth - of_served.min()), 0.0)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "score_err_rms": (squares / max(count, 1)) ** 0.5}


def _split_bf16(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """float32 as two bfloat16 pieces the way the chip's ``HIGH`` reads on
    this data: the high piece by truncation, the low by rounding."""
    import ml_dtypes

    hi = (np.ascontiguousarray(x).view(np.uint32)
          & np.uint32(0xFFFF0000)).view(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _high_on_tpu():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scores(block, qn):
        bn = block / jnp.sqrt(jnp.sum(block * block, axis=1, keepdims=True))
        return jnp.matmul(bn, qn.T, precision=jax.lax.Precision.HIGH)

    return scores


def control_answers(vectors: np.ndarray, queries: np.ndarray, limit: int
                    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """What a three-pass (``high``) scan would serve: rows and query
    normalised in float32 as the index does, then the products in three
    bfloat16 passes accumulated in float32."""
    import jax

    qn = (queries / np.linalg.norm(queries, axis=1, keepdims=True)
          ).astype(np.float32)
    on_tpu = jax.default_backend() == "tpu"
    device_scores = _high_on_tpu() if on_tpu else None
    q_hi, q_lo = _split_bf16(qn)
    scores = np.empty((vectors.shape[0], queries.shape[0]), np.float32)
    for start in range(0, vectors.shape[0], BLOCK):
        block = vectors[start:start + BLOCK]
        if on_tpu:
            scores[start:start + BLOCK] = np.asarray(
                device_scores(block, qn))
            continue
        bn = block / _row_norms(block)[:, None]
        b_hi, b_lo = _split_bf16(bn)
        scores[start:start + BLOCK] = (b_hi @ q_hi.T + b_hi @ q_lo.T
                                       + b_lo @ q_hi.T)
    ids: List[np.ndarray] = []
    vals: List[np.ndarray] = []
    for i in range(queries.shape[0]):
        col = scores[:, i]
        top = np.argpartition(-col, limit - 1)[:limit]
        top = top[np.lexsort((top, -col[top]))]
        ids.append(top.astype(np.int64))
        vals.append(col[top].astype(np.float64))
    return ids, vals
