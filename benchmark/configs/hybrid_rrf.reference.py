"""Plain reference for native hybrid search (text in; Okapi BM25 + exact
cosine + reciprocal rank fusion out), and its controls.

Imports nothing of the program and takes nothing the program made but what
the timed requests returned and, for the checked queries, the vector the
program's embedder gave for the query text: only the passages and vectors
the benchmark generated from the seed, the query texts it sent, and the
encoder's parameters it made from the seed. The encoder's forward pass is
the accepted plain reference beside this file
(``xlmr_encoder.reference.py``), loaded by path.

The published rules, as the configuration's file states them:

tokens    lower-cased maximal runs of ``[a-z0-9]``, 2 to 40 characters,
          less the stop list below (upstream's ``TokenizeForBM25``).
lexical   Okapi BM25, k1 1.2, b 0.75, idf ``ln(1 + (N - df + 0.5) /
          (df + 0.5))``, over every passage; a query's distinct tokens
          each count once. Here in float64.
vector    cosine of every stored row with the query's vector: a float32
          product over the whole collection picks every row within
          ``MARGIN`` of the ``depth``-th best, and those and every served
          row are scored again in float64 (as
          ``exact_cosine_topk.reference.py`` does).
fusion    the best ``depth`` of each side, ``weight / (rrf_k + rank)``
          summed over the sides a passage appears on, the best ``limit``
          returned best first.

A served answer is never asked to repeat the reference's order among
scores closer than arithmetic can tell apart. Each side's scores are held
to the reference's within a limit, and with ``tol`` = that limit a
passage's rank on a side may lie anywhere between ``1 + #(others above it
by more than tol)`` and ``1 + #(others not below it by more than tol)``.
From the two ranks' bounds come the highest and the lowest fused score a
passage can have. Two numbers then judge the fuse:

``fused_gap``       the largest, over the checked requests, of (the
                    highest LOWEST-possible fused score among passages
                    that were NOT served) less (the lowest
                    HIGHEST-possible fused score among those that were),
                    not below 0. Above 0, a passage that had to be served
                    was left out: a skipped block of rows or postings, a
                    lexical row joined to the wrong vector slot, wrong
                    weights.
``fused_score_err`` how far a served fused score lies outside the
                    interval its passage's bounds allow, at worst.

Controls (the nearest precision below the one the configuration states):
BM25 with every intermediate rounded to bfloat16; the cosine products at matmul precision
``high`` (three bfloat16 passes, ``exact_cosine_topk.reference.py``'s
emulation off the TPU); the query's vector from the encoder with float8
operands (``xlmr_encoder.reference.py``'s).
"""

from __future__ import annotations

import importlib.util
import itertools
import os
import re
import sys
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 65536
MARGIN = 1e-4

STOP = frozenset(
    """a an and are as at be by for from has he in is it its of on that the
    to was were will with this these those i you your not or but if then
    than so we they them there here what which who whom when where how"""
    .split())
MIN_LEN, MAX_LEN = 2, 40
_RUN = re.compile(r"[a-z0-9]+")
_TO_SPACE = str.maketrans(
    {chr(c): " " for c in range(128) if not chr(c).isalnum()})


def _sibling(filename: str):
    name = "benchmark_reference_" + re.sub(r"\W", "_", filename)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, filename))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def encoder():
    """The plain encoder reference (parameters from the seed, float32
    ``highest`` forward pass, float8 control)."""
    return _sibling("xlmr_encoder.reference.py")


# -- tokens -------------------------------------------------------------


def runs(text: str) -> List[str]:
    """Lower-cased maximal runs of [a-z0-9], before the length and stop
    rules. ASCII text is split at everything else; other text goes
    through the regular expression."""
    low = text.lower()
    if low.isascii():
        return low.translate(_TO_SPACE).split()
    return _RUN.findall(low)


def keeps(token: str) -> bool:
    return MIN_LEN <= len(token) <= MAX_LEN and token not in STOP


def tokens(text: str) -> List[str]:
    return [t for t in runs(text) if keeps(t)]


# -- lexical side ---------------------------------------------------------


class Lexical:
    """What Okapi BM25 needs of the collection for a set of wanted terms:
    every passage's length in kept tokens, and each wanted term's
    (passage, tf) pairs. One pass over the texts."""

    def __init__(self, texts: Sequence[str], wanted: Sequence[str]) -> None:
        n = len(texts)
        wanted = sorted(set(wanted))
        raw = [runs(t) for t in texts]
        lens = np.fromiter(map(len, raw), np.int64, n)
        flat = list(itertools.chain.from_iterable(raw))
        del raw
        # a token's class, settled once a distinct token: 0 dropped by
        # the rules, 1 kept, 2 + j the wanted term j
        cls_of: Dict[str, int] = {t: 1 if keeps(t) else 0
                                  for t in set(flat)}
        cls_of.update((t, 2 + j) for j, t in enumerate(wanted)
                      if cls_of.get(t, 1))
        cls = np.fromiter(map(cls_of.__getitem__, flat), np.int32,
                          len(flat))
        del flat
        doc = np.repeat(np.arange(n, dtype=np.int64), lens)
        self.n = n
        self.doc_len = np.bincount(doc[cls > 0], minlength=n).astype(
            np.float64)
        self.avgdl = max(float(self.doc_len.sum()) / max(n, 1), 1.0)
        hit = cls >= 2
        key, tf = np.unique((cls[hit] - 2).astype(np.int64) * n + doc[hit],
                            return_counts=True)
        term, docs = key // n, key % n
        cut = np.searchsorted(term, np.arange(len(wanted) + 1))
        self.postings: Dict[str, Tuple[np.ndarray, np.ndarray]] = {
            t: (docs[cut[j]:cut[j + 1]], tf[cut[j]:cut[j + 1]])
            for j, t in enumerate(wanted) if cut[j + 1] > cut[j]}

    def scores(self, query: str, k1: float, b: float,
               low_precision: bool = False) -> np.ndarray:
        """Okapi BM25 of every passage for ``query`` ([n] float64, 0
        where no term matches). With ``low_precision`` (the control)
        every intermediate is rounded to bfloat16, the nearest format
        below the float32 the configuration states that still holds a
        collection's size."""
        r = _to_bf16 if low_precision else (lambda x: x)
        out = np.zeros(self.n, dtype=np.float64)
        n = float(self.n)
        for t in sorted(set(tokens(query))):
            got = self.postings.get(t)
            if got is None:
                continue
            docs, tf = got
            df = float(len(docs))
            idf = r(np.log(r(1.0 + r((n - df + 0.5) / (df + 0.5)))))
            tf = tf.astype(np.float64)
            length = r(1.0 - b + r(b * r(self.doc_len[docs] / self.avgdl)))
            norm = r(r(tf * (k1 + 1.0)) / r(tf + r(k1 * length)))
            out[docs] = r(out[docs] + r(idf * norm))
        return out


def _to_bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


# -- vector side ----------------------------------------------------------


def _row_norms(block: np.ndarray) -> np.ndarray:
    return np.sqrt(np.einsum("ij,ij->i", block, block, dtype=np.float32))


def cosines32(vectors: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """[N, S] float32 cosines, block by block."""
    qn = (queries / np.linalg.norm(queries, axis=1, keepdims=True)
          ).astype(np.float32)
    out = np.empty((vectors.shape[0], queries.shape[0]), np.float32)
    for start in range(0, vectors.shape[0], BLOCK):
        block = vectors[start:start + BLOCK]
        out[start:start + BLOCK] = (block @ qn.T) \
            / _row_norms(block)[:, None]
    return out


def cosines64(vectors: np.ndarray, query: np.ndarray,
              rows: np.ndarray) -> np.ndarray:
    v = vectors[rows].astype(np.float64)
    q = query.astype(np.float64)
    return (v @ q) / (np.linalg.norm(v, axis=1) * np.linalg.norm(q))


def cosines_high(vectors: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """The control's [N, S] cosines: products at precision ``high``."""
    ctl = _sibling("exact_cosine_topk.reference.py")
    import jax

    qn = (queries / np.linalg.norm(queries, axis=1, keepdims=True)
          ).astype(np.float32)
    on_tpu = jax.default_backend() == "tpu"
    device_scores = ctl._high_on_tpu() if on_tpu else None
    q_hi, q_lo = ctl._split_bf16(qn)
    out = np.empty((vectors.shape[0], queries.shape[0]), np.float32)
    for start in range(0, vectors.shape[0], BLOCK):
        block = vectors[start:start + BLOCK]
        if on_tpu:
            out[start:start + BLOCK] = np.asarray(device_scores(block, qn))
            continue
        bn = block / _row_norms(block)[:, None]
        b_hi, b_lo = ctl._split_bf16(bn)
        out[start:start + BLOCK] = (b_hi @ q_hi.T + b_hi @ q_lo.T
                                    + b_lo @ q_hi.T)
    return out


# -- ranks, bounds, fusion --------------------------------------------------


def rank_bounds(scores: np.ndarray, tol: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """For each of ``scores`` (one side's candidates, any order): the
    best and the worst 1-based rank it can have when scores closer than
    ``tol`` may fall either way."""
    order = np.sort(scores)
    n = len(scores)
    best = 1 + (n - np.searchsorted(order, scores + tol, side="right"))
    worst = n - np.searchsorted(order, scores - tol, side="left")
    return best, np.maximum(worst, 1)


def _side_terms(rank_lo: np.ndarray, rank_hi: np.ndarray, weight: float,
                rrf_k: float, depth: int) -> Tuple[np.ndarray, np.ndarray]:
    """(highest, lowest) fused contribution of one side from a passage's
    best (``rank_lo``) and worst (``rank_hi``) possible rank there."""
    high = np.where(rank_lo <= depth, weight / (rrf_k + rank_lo), 0.0)
    low = np.where(rank_hi <= depth, weight / (rrf_k + rank_hi), 0.0)
    return high, low


def fused_bounds(lex: Dict[int, float], vec: Dict[int, float],
                 tol_lex: float, tol_vec: float, rrf_k: float, depth: int,
                 weights: Tuple[float, float] = (1.0, 1.0)
                 ) -> Dict[int, Tuple[float, float]]:
    """``{row: (lowest, highest)}`` possible fused score for every row
    that can reach the best ``depth`` of a side. ``lex`` and ``vec`` hold
    each side's candidates with their reference scores (every passage
    with a positive BM25 score; every row within MARGIN of the cut)."""
    out: Dict[int, List[float]] = {}
    for side, tol, w in ((lex, tol_lex, weights[0]),
                         (vec, tol_vec, weights[1])):
        if not side:
            continue
        rows = np.fromiter(side.keys(), np.int64, len(side))
        sc = np.fromiter(side.values(), np.float64, len(side))
        best, worst = rank_bounds(sc, tol)
        high, low = _side_terms(best, worst, w, rrf_k, depth)
        for i in np.flatnonzero(best <= depth):
            acc = out.setdefault(int(rows[i]), [0.0, 0.0])
            acc[0] += float(low[i])
            acc[1] += float(high[i])
    return {r: (lo, hi) for r, (lo, hi) in out.items()}


def judge(lexical: Lexical, vectors: np.ndarray, queries: Sequence[str],
          query_vectors: np.ndarray, served: Sequence[Dict[str, Any]],
          k1: float, b: float, rrf_k: float, depth: int, limit: int,
          tol_lex: float, tol_vec: float,
          weights: Tuple[float, float] = (1.0, 1.0)) -> Dict[str, float]:
    """Every number of the comparison over the checked requests.
    ``served[i]``: ``rows`` (int64, best first), ``score`` (fused),
    ``bm25`` and ``vector`` (NaN where the hit carried none).
    ``query_vectors[i]`` is the vector the PROGRAM's embedder gave
    ``queries[i]``: the vector side is judged on what the program
    searched with, the embedder apart (``vector_dist``)."""
    coarse = cosines32(vectors, query_vectors)
    sq = {"lex": 0.0, "vec": 0.0}
    cnt = {"lex": 0, "vec": 0}
    worst = {"lex": 0.0, "vec": 0.0}
    fused_gap = 0.0
    fused_score_err = 0.0
    for i, query in enumerate(queries):
        got = served[i]
        rows = np.asarray(got["rows"], np.int64)
        # lexical: every passage a query term touches
        bm = lexical.scores(query, k1, b)
        lex_rows = np.flatnonzero(bm > 0.0)
        lex = dict(zip(lex_rows.tolist(), bm[lex_rows].tolist()))
        # vector: every row near the cut, and the served ones, in float64
        col = coarse[:, i]
        kth = np.partition(col, -depth)[-depth] if len(col) >= depth \
            else col.min()
        cand = np.union1d(np.flatnonzero(col >= kth - MARGIN), rows)
        exact = cosines64(vectors, query_vectors[i], cand)
        vec_all = dict(zip(cand.tolist(), exact.tolist()))
        near = {r: s for r, s in vec_all.items() if col[r] >= kth - MARGIN}
        for name, field, ref in (("lex", "bm25", lex),
                                 ("vec", "vector", vec_all)):
            have = np.asarray(got[field], np.float64)
            for r, s in zip(rows.tolist(), have.tolist()):
                if np.isnan(s):
                    continue
                d = s - ref.get(r, 0.0)
                sq[name] += d * d
                cnt[name] += 1
                worst[name] = max(worst[name], abs(d))
        bounds = fused_bounds(lex, near, tol_lex, tol_vec, rrf_k, depth,
                              weights)
        served_set = set(rows.tolist())
        out_low = max((lo for r, (lo, _) in bounds.items()
                       if r not in served_set), default=0.0)
        in_high = min((bounds.get(r, (0.0, 0.0))[1]
                       for r in served_set), default=0.0)
        if len(served_set) >= min(limit, len(bounds)):
            fused_gap = max(fused_gap, out_low - in_high)
        else:
            fused_gap = max(fused_gap, out_low)   # a hit short: any miss
        for r, s in zip(rows.tolist(),
                        np.asarray(got["score"], np.float64).tolist()):
            lo, hi = bounds.get(r, (0.0, 0.0))
            fused_score_err = max(fused_score_err, lo - s, s - hi)
    return {
        "lex_err_rms": (sq["lex"] / max(cnt["lex"], 1)) ** 0.5,
        "lex_err_max": worst["lex"],
        "vec_err_rms": (sq["vec"] / max(cnt["vec"], 1)) ** 0.5,
        "vec_err_max": worst["vec"],
        "fused_gap": max(fused_gap, 0.0),
        "fused_score_err": max(fused_score_err, 0.0),
        "lex_scored": float(cnt["lex"]),
        "vec_scored": float(cnt["vec"]),
    }


def answers(lexical: Lexical, vectors: np.ndarray, queries: Sequence[str],
            query_vectors: np.ndarray, k1: float, b: float, rrf_k: float,
            depth: int, limit: int, low_precision: bool = False,
            weights: Tuple[float, float] = (1.0, 1.0)
            ) -> List[Dict[str, Any]]:
    """What a plain implementation serves, in ``judge``'s ``served``
    form. With ``low_precision`` it is the control: BM25 rounded to
    bfloat16, the cosines at precision ``high``."""
    cos = cosines_high(vectors, query_vectors) if low_precision \
        else cosines32(vectors, query_vectors)
    out: List[Dict[str, Any]] = []
    for i, query in enumerate(queries):
        bm = lexical.scores(query, k1, b, low_precision)
        lex_rows = np.flatnonzero(bm > 0)
        lex_top = lex_rows[np.argsort(-bm[lex_rows], kind="stable")][:depth]
        col = cos[:, i]
        vec_top = np.argpartition(-col, min(depth, len(col)) - 1)[:depth]
        vec_top = vec_top[np.lexsort((vec_top, -col[vec_top]))]
        fused: Dict[int, float] = {}
        for side, w in ((lex_top, weights[0]), (vec_top, weights[1])):
            for rank, r in enumerate(side.tolist(), start=1):
                fused[r] = fused.get(r, 0.0) + w / (rrf_k + rank)
        top = sorted(fused, key=lambda r: (-fused[r], r))[:limit]
        in_lex = {int(r): float(bm[r]) for r in lex_top}
        in_vec = {int(r): float(col[r]) for r in vec_top}
        out.append({
            "rows": np.asarray(top, np.int64),
            "score": np.asarray([fused[r] for r in top], np.float64),
            "bm25": np.asarray([in_lex.get(r, np.nan) for r in top]),
            "vector": np.asarray([in_vec.get(r, np.nan) for r in top]),
        })
    return out
