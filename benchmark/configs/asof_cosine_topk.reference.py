"""Plain reference for an exact cosine top-k collection that is written
while it is read, and its control.

Imports nothing of the program and takes nothing the program made: only
the base vectors the benchmark generated from the seed, the log of the
writes it sent (send time, acknowledgement time, ids, rows), the query
vectors it sent with their send and reply times, and the (id, score) lists
the timed requests returned. The arithmetic is that of
``exact_cosine_topk.reference.py``, which is loaded from beside this file:
a float32 pass over everything picks the rows near the cut, float64 decides.

What an answer is held to, AS OF ITS REQUEST ``[t_send, t_done]``. A point
has versions: the base row, then one for every acknowledged write to its
id. A write may take effect at any moment between its send and its
acknowledgement, so a version

  is *admissible* for the request if it was current at some moment of it:
     its write was sent before ``t_done`` and the next write to the id was
     not acknowledged before ``t_send``;
  *held through* the request if its write was acknowledged before
     ``t_send`` and the next write to the id was not sent before ``t_done``.

``score_err`` / ``score_err_rms``
    |served score - float64 cosine| of the admissible version of the hit's
    id that lies nearest the served score.
``rank_gap``
    the float64 cosine of the ``limit``-th best among the versions HELD
    THROUGH the request, less the lowest cosine served, not below 0. Points
    in flight can only push the true cut up, so a right answer reads 0 (to
    float32 ties); a point acknowledged before the request and not found
    reads its distance above the cut.
``stale_after_ack``
    hits whose served score is the cosine of a version that is NOT
    admissible (an overwritten point scored by the vector it had before an
    acknowledged write) and of no admissible one.
``unknown_ids``
    hits whose id has no admissible version at all (never written, or
    written only after the reply).
``fresh_not_first``
    of the queries made from a just-written point (its stored row plus the
    readers' noise) whose version held through the request: those whose
    first hit is another id.

Control, as the exact reference's: every product at matmul precision
``high`` (three bfloat16 passes), over the collection as of each request's
send time (every write acknowledged before it applied, none in flight).
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "benchmark_exact_cosine_topk_reference",
    os.path.join(_HERE, "exact_cosine_topk.reference.py"))
exact = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(exact)

# a served score within this of a version's cosine IS that version's: the
# versions of one id are separate draws, tenths apart
SAME = 1e-4
INF = float("inf")


class Search(NamedTuple):
    t_send: float
    t_done: float
    query: np.ndarray


class WriteLog:
    """Every acknowledged write, flat, in send order: entry ``e`` wrote
    ``rows[e]`` under ``ids[e]``, sent at ``t_send[e]`` and acknowledged
    at ``t_ack[e]``; ``next_send[e]`` / ``next_ack[e]`` are those of the
    next write to the same id (infinite where there is none), and
    ``first_send`` / ``first_ack`` the same for a base row."""

    def __init__(self, base_rows: int,
                 writes: Sequence[Tuple[int, float, float, np.ndarray,
                                        np.ndarray]]) -> None:
        self.base_rows = int(base_rows)
        writes = sorted(writes, key=lambda w: w[1])
        counts = [len(w[3]) for w in writes]
        self.number = np.repeat([w[0] for w in writes], counts
                                ).astype(np.int64)
        self.t_send = np.repeat([w[1] for w in writes], counts
                                ).astype(np.float64)
        self.t_ack = np.repeat([w[2] for w in writes], counts
                               ).astype(np.float64)
        self.ids = np.concatenate([w[3] for w in writes]).astype(np.int64) \
            if writes else np.zeros(0, np.int64)
        self.rows = np.concatenate([w[4] for w in writes]) if writes \
            else np.zeros((0, 0), np.float32)
        m = len(self.ids)
        self.next_send = np.full(m, INF)
        self.next_ack = np.full(m, INF)
        self.entries: Dict[int, List[int]] = {}
        for e in range(m):
            seen = self.entries.setdefault(int(self.ids[e]), [])
            if seen:
                self.next_send[seen[-1]] = self.t_send[e]
                self.next_ack[seen[-1]] = self.t_ack[e]
            seen.append(e)
        # base rows that were ever overwritten, with their first write
        over = sorted(i for i in self.entries if i < self.base_rows)
        self.over_rows = np.asarray(over, np.int64)
        self.first_send = np.asarray(
            [self.t_send[self.entries[i][0]] for i in over], np.float64)
        self.first_ack = np.asarray(
            [self.t_ack[self.entries[i][0]] for i in over], np.float64)
        self._first = {i: k for k, i in enumerate(over)}

    def admissible(self, e: np.ndarray, s: Search) -> np.ndarray:
        return (self.t_send[e] <= s.t_done) & (self.next_ack[e] >= s.t_send)

    def held(self, s: Search) -> np.ndarray:
        """Mask over the entries: versions held through the request."""
        return (self.t_ack <= s.t_send) & (self.next_send >= s.t_done)

    def base_admissible(self, row: int, s: Search) -> bool:
        k = self._first.get(row)
        return 0 <= row < self.base_rows \
            and (k is None or self.first_ack[k] >= s.t_send)

    def base_not_held(self, s: Search) -> np.ndarray:
        """Base rows whose base version did not hold through the request."""
        return self.over_rows[self.first_send < s.t_done]

    def as_of(self, t: float) -> Tuple[np.ndarray, np.ndarray]:
        """(entries current, base rows superseded) with every write
        acknowledged before ``t`` applied and no other."""
        current = (self.t_ack <= t) & (self.next_ack > t)
        return current, self.over_rows[self.first_ack <= t]


def _cos64(rows: np.ndarray, query: np.ndarray) -> np.ndarray:
    v = rows.astype(np.float64)
    q = query.astype(np.float64)
    return (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))


def judge(base: np.ndarray, log: WriteLog, searches: Sequence[Search],
          served_ids: Sequence[np.ndarray],
          served_scores: Sequence[np.ndarray], limit: int
          ) -> Dict[str, float]:
    queries = np.stack([s.query for s in searches])
    coarse = exact.cosines32(base, queries)
    coarse_w = exact.cosines32(log.rows, queries) if len(log.ids) \
        else np.zeros((0, len(searches)), np.float32)
    n_base = base.shape[0]
    score_err = rank_gap = squares = 0.0
    count = stale = unknown = 0
    for i, s in enumerate(searches):
        # the cut: the limit-th best among the versions held through
        col = np.concatenate([coarse[:, i], coarse_w[:, i]])
        col[log.base_not_held(s)] = -np.inf
        col[n_base:][~log.held(s)] = -np.inf
        kth = np.partition(col, -limit)[-limit]
        cand = np.flatnonzero(col >= kth - exact.MARGIN)
        of_cand = np.concatenate([
            _cos64(base[cand[cand < n_base]], s.query),
            _cos64(log.rows[cand[cand >= n_base] - n_base], s.query)])
        true_kth = np.partition(of_cand, -limit)[-limit]
        # each hit against the versions of its id
        served = np.asarray(served_scores[i], np.float64)
        lowest = np.inf
        for hit, got in zip(np.asarray(served_ids[i], np.int64), served):
            hit = int(hit)
            ents = np.asarray(log.entries.get(hit, ()), np.int64)
            cosines = _cos64(log.rows[ents], s.query) if len(ents) \
                else np.zeros(0)
            ok = log.admissible(ents, s) if len(ents) \
                else np.zeros(0, bool)
            if 0 <= hit < n_base:
                cosines = np.append(cosines, _cos64(base[hit:hit + 1],
                                                    s.query))
                ok = np.append(ok, log.base_admissible(hit, s))
            if not ok.any():
                unknown += 1
                continue
            err = np.abs(cosines - got)
            best = int(np.argmin(np.where(ok, err, np.inf)))
            if err[best] > SAME and (err[~ok] <= SAME).any():
                stale += 1
            score_err = max(score_err, float(err[best]))
            squares += float(err[best] ** 2)
            count += 1
            lowest = min(lowest, float(cosines[best]))
        rank_gap = max(rank_gap, float(true_kth - lowest), 0.0)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "score_err_rms": (squares / max(count, 1)) ** 0.5,
            "stale_after_ack": float(stale), "unknown_ids": float(unknown)}


def fresh_not_first(log: WriteLog,
                    fresh: Sequence[Tuple[float, float, int, int,
                                          np.ndarray]]) -> int:
    """``fresh``: (t_send, t_done, the point's id, the number of the write
    request its row was taken from, the ids served). Counts the queries
    whose point's version held through the request and was not the first
    hit."""
    bad = 0
    for t_send, t_done, point, number, ids in fresh:
        for e in log.entries.get(int(point), ()):
            if log.number[e] == number and log.t_ack[e] <= t_send \
                    and log.next_send[e] >= t_done:
                if len(ids) == 0 or int(ids[0]) != int(point):
                    bad += 1
    return bad


def _high_scores(vectors: np.ndarray, qn: np.ndarray) -> np.ndarray:
    """[N, S] cosines with every product in three bfloat16 passes, as
    ``exact.control_answers`` makes them."""
    import jax

    scores = np.empty((vectors.shape[0], qn.shape[0]), np.float32)
    on_tpu = jax.default_backend() == "tpu"
    device_scores = exact._high_on_tpu() if on_tpu else None
    q_hi, q_lo = exact._split_bf16(qn)
    for start in range(0, vectors.shape[0], exact.BLOCK):
        block = vectors[start:start + exact.BLOCK]
        if on_tpu:
            scores[start:start + exact.BLOCK] = np.asarray(
                device_scores(block, qn))
            continue
        bn = block / exact._row_norms(block)[:, None]
        b_hi, b_lo = exact._split_bf16(bn)
        scores[start:start + exact.BLOCK] = (b_hi @ q_hi.T + b_hi @ q_lo.T
                                             + b_lo @ q_hi.T)
    return scores


def control_answers(base: np.ndarray, log: WriteLog,
                    searches: Sequence[Search], limit: int
                    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    queries = np.stack([s.query for s in searches])
    qn = (queries / np.linalg.norm(queries, axis=1, keepdims=True)
          ).astype(np.float32)
    scores = _high_scores(base, qn)
    scores_w = _high_scores(log.rows, qn) if len(log.ids) \
        else np.zeros((0, len(searches)), np.float32)
    point = np.concatenate([np.arange(base.shape[0]), log.ids])
    ids: List[np.ndarray] = []
    vals: List[np.ndarray] = []
    for i, s in enumerate(searches):
        current, superseded = log.as_of(s.t_send)
        col = np.concatenate([scores[:, i], scores_w[:, i]])
        col[superseded] = -np.inf
        col[base.shape[0]:][~current] = -np.inf
        top = np.argpartition(-col, limit - 1)[:limit]
        top = top[np.lexsort((top, -col[top]))]
        ids.append(point[top].astype(np.int64))
        vals.append(col[top].astype(np.float64))
    return ids, vals
