"""Plain reference for exact cosine top-k INSIDE a payload filter, and its
control.

Imports nothing of the program and takes nothing the program made: only
the vectors the benchmark generated from the seed, for every row the value
of the filtered field as the benchmark's own ``payload_of`` gives it from
the row's id (never the program's column or nodes), the query vectors and
tenants the benchmark sent, and the (id, score) lists the timed requests
returned.

Reference: for each checked search, the rows whose field equals the
request's tenant; the cosine of every one of them with the query in
float32 picks every such row within ``MARGIN`` of the ``limit``-th best
(or every one, where fewer than ``limit`` pass); those and every row the
program served are then scored again in float64. So the top ``limit`` is
the top of the TENANT'S rows: the best of the whole collection, cut down
to those that pass, is not it.

What comes out, over all hits of all checked requests, is what
``exact_cosine_topk.reference.py`` reads (``score_err``,
``score_err_rms``, ``rank_gap``: see there), with ``rank_gap`` taken
against the tenant's own ``limit``-th best (where fewer than ``limit``
pass: against the worst passing row, so a passing row left out reads as a
gap), and two counts:

``filter_violations``  served rows whose field is not the request's tenant.
``short``              answers with fewer hits than pass the filter and
                       ``limit`` allow.

Control (the nearest precision below the float32 ``highest`` the
configuration states): the same filtered top with every product at matmul
precision ``high``, as the unfiltered reference's control, whose pieces
(``cosines64``, the bfloat16 split, the chip's own ``HIGH`` product) are
taken from ``exact_cosine_topk.reference.py``, loaded from beside this
file.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_exact_cosine_topk_reference",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "exact_cosine_topk.reference.py"))
exact = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(exact)

MARGIN = exact.MARGIN
cosines64 = exact.cosines64


def _unit_rows(block: np.ndarray) -> np.ndarray:
    return block / exact._row_norms(block)[:, None]


def _unit(query: np.ndarray) -> np.ndarray:
    return (query / np.linalg.norm(query)).astype(np.float32)


def _by_tenant(tenants: Sequence[int]) -> Dict[int, List[int]]:
    groups: Dict[int, List[int]] = {}
    for i, t in enumerate(tenants):
        groups.setdefault(int(t), []).append(i)
    return groups


def judge(vectors: np.ndarray, field: np.ndarray, queries: np.ndarray,
          tenants: Sequence[int], served_ids: Sequence[np.ndarray],
          served_scores: Sequence[np.ndarray], limit: int
          ) -> Dict[str, float]:
    """``field[r]`` is row r's value of the filtered field; search i asked
    for the rows with ``field == tenants[i]``; ``served_ids[i]`` are row
    numbers, best first."""
    score_err = rank_gap = squares = 0.0
    count = violations = short = 0
    for tenant, members in _by_tenant(tenants).items():
        own = np.flatnonzero(field == tenant)
        block = _unit_rows(vectors[own]) if len(own) else None
        for i in members:
            ids = np.asarray(served_ids[i], np.int64)
            served = np.asarray(served_scores[i], np.float64)
            violations += int(np.count_nonzero(field[ids] != tenant))
            want = min(limit, len(own))
            if len(ids) < want:
                short += 1
            if want == 0:
                continue
            col = block @ _unit(queries[i])
            kth = np.partition(col, -want)[-want]
            rows = np.union1d(own[col >= kth - MARGIN], ids)
            true = cosines64(vectors, queries[i], rows)
            passing = true[field[rows] == tenant]
            true_kth = np.partition(passing, -want)[-want]
            if len(ids) == 0:
                rank_gap = max(rank_gap, float(true_kth + 1.0))
                continue
            of_served = true[np.searchsorted(rows, ids)]
            diff = served - of_served
            score_err = max(score_err, float(np.max(np.abs(diff))))
            squares += float(np.sum(diff * diff))
            count += len(diff)
            # an answer that is short has left out passing rows at least
            # as good as the tenant's want-th: it reads by how far
            low = float(of_served.min()) if len(ids) >= want else -1.0
            rank_gap = max(rank_gap, float(true_kth) - low, 0.0)
    return {"score_err": score_err, "rank_gap": rank_gap,
            "score_err_rms": (squares / max(count, 1)) ** 0.5,
            "filter_violations": float(violations), "short": float(short)}


def control_answers(vectors: np.ndarray, field: np.ndarray,
                    queries: np.ndarray, tenants: Sequence[int], limit: int
                    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """What a three-pass (``high``) scan would serve inside each filter:
    rows and query normalised in float32 as the index does, the products in
    three bfloat16 passes accumulated in float32 (on a TPU the chip's own
    ``Precision.HIGH``), the top ``limit`` of the tenant's rows."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    device_scores = exact._high_on_tpu() if on_tpu else None
    ids: List[np.ndarray] = [np.zeros(0, np.int64)] * len(tenants)
    vals: List[np.ndarray] = [np.zeros(0, np.float64)] * len(tenants)
    for tenant, members in _by_tenant(tenants).items():
        own = np.flatnonzero(field == tenant)
        if not len(own):
            continue
        qn = np.stack([_unit(queries[i]) for i in members])
        block = vectors[own]
        if on_tpu:
            scores = np.asarray(device_scores(block, qn))
        else:
            b_hi, b_lo = exact._split_bf16(_unit_rows(block))
            q_hi, q_lo = exact._split_bf16(qn)
            scores = b_hi @ q_hi.T + b_hi @ q_lo.T + b_lo @ q_hi.T
        for j, i in enumerate(members):
            col = scores[:, j]
            n = min(limit, len(own))
            top = np.argpartition(-col, n - 1)[:n]
            top = top[np.lexsort((own[top], -col[top]))]
            ids[i] = own[top].astype(np.int64)
            vals[i] = col[top].astype(np.float64)
    return ids, vals
