"""The tenants cell (``vec2m-tenant-c32``): its reference judges the top of
the TENANT'S rows and finds plain float32 arithmetic correct, its control
reads above the limit, and answers that are not exact inside the filter
read not correct: the mask dropped (the unfiltered top served), the
parent's shape (the hits of the unfiltered top 128 that pass, short or
padded out), and a whole rehearsed run with the scan's mask dropped
underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmark.lib import loader

ROOT = loader.ROOT
CELL = "vec2m-tenant-c32"
LIMIT = 100
ROWS, TENANTS = 16384, 64


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qdrant-bge-m3-2m-tenants.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _world(seed):
    """(vectors, field, queries, tenants, float32 cosines [rows, searches])
    at a size a test holds: 16 searches, each from a row of its tenant."""
    from benchmark.systems.qdrant_collection import make_vectors, payload_of

    cfg = _config()
    vectors = make_vectors(seed, ROWS, 1024, 16, 1.0)
    field = np.asarray([payload_of(i)[cfg["tenant_field"]]
                        for i in range(ROWS)])
    rng = np.random.default_rng(seed + 1)
    tenants = [int(t) for t in rng.integers(0, TENANTS, 16)]
    rows = [int(rng.choice(np.flatnonzero(field == t))) for t in tenants]
    queries = (vectors[rows] + np.float32(0.25 / 32.0)
               * rng.standard_normal((16, 1024), dtype=np.float32))
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    return vectors, field, queries, tenants, vectors @ qn.T


def _top(col, rows, n):
    """The best ``n`` of ``rows`` by ``col``, as (ids, scores)."""
    best = rows[np.argsort(-col[rows], kind="stable")[:n]]
    return best, col[best].astype(np.float64)


def _limits_broken(cfg, read):
    lim = cfg["limits"]
    return {name for name, value, limit in (
        ("score_err_rms", read["score_err_rms"], lim["score_err_rms"]),
        ("score_err_max", read["score_err"], lim["score_err_max"]),
        ("rank_gap_max", read["rank_gap"], lim["rank_gap_max"]),
        ("filter_violations", read["filter_violations"], 0),
        ("short", read["short"], 0)) if not value <= limit}


def test_the_filtered_reference_judges_its_own_answers_correct():
    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    vectors, field, queries, tenants, scores = _world(31)
    answers = [_top(scores[:, i], np.flatnonzero(field == t), LIMIT)
               for i, t in enumerate(tenants)]
    read = ref.judge(vectors, field, queries, tenants,
                     [a[0] for a in answers], [a[1] for a in answers], LIMIT)
    assert _limits_broken(cfg, read) == set(), read
    # a tenant's own row is its search's first hit
    assert all(field[a[0][0]] == t for a, t in zip(answers, tenants))


def test_the_filtered_control_reads_above_the_limit():
    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    vectors, field, queries, tenants, _ = _world(32)
    ids, vals = ref.control_answers(vectors, field, queries, tenants, LIMIT)
    assert all(len(a) == LIMIT and np.all(field[a] == t)
               for a, t in zip(ids, tenants))
    control = ref.judge(vectors, field, queries, tenants, ids, vals, LIMIT)
    assert control["filter_violations"] == control["short"] == 0
    assert control["score_err"] > cfg["limits"]["score_err_max"]
    assert control["score_err_rms"] > cfg["limits"]["score_err_rms"]


@pytest.mark.parametrize("fault", ["mask_dropped", "parent_short",
                                   "parent_padded"])
def test_answers_not_exact_inside_the_filter_are_not_correct(fault):
    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    vectors, field, queries, tenants, scores = _world(33)
    everyone = np.arange(ROWS)
    ids, vals = [], []
    for i, t in enumerate(tenants):
        col = scores[:, i]
        if fault == "mask_dropped":      # the unfiltered top 100
            a, s = _top(col, everyone, LIMIT)
        else:                            # what passes of the unfiltered 128
            a, _ = _top(col, everyone, 128)
            a = a[field[a] == t]
            if fault == "parent_padded":  # filled up with passing rows
                rest = np.setdiff1d(np.flatnonzero(field == t), a)
                a = np.concatenate([a, rest[:LIMIT - len(a)]])
                a = a[np.argsort(-col[a], kind="stable")]
            s = col[a].astype(np.float64)
        ids.append(a)
        vals.append(s)
    broken = _limits_broken(cfg, ref.judge(vectors, field, queries, tenants,
                                           ids, vals, LIMIT))
    want = {"mask_dropped": {"filter_violations"},
            "parent_short": {"short", "rank_gap_max"},
            "parent_padded": {"rank_gap_max"}}[fault]
    assert broken >= want, broken
    assert not broken & {"score_err_max", "score_err_rms"}, broken


def _run(seed):
    from benchmark import run as bench_run

    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                               "--seconds", "2", "--trace", "0",
                               "--rehearse"]) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["metrics"] == {} and result["rehearsal"] is True
    return result


def test_unbroken_tenants_cell_is_correct():
    result = _run(2147483721)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "filter_device_share_pct" in result["counts"]["readers_that_read"]


def test_a_scan_that_drops_the_mask_is_not_correct(monkeypatch):
    """The filtered scan serving the unfiltered top, and the guard in the
    hydration loop gone with it: every reply has its ``limit`` hits, and
    the run is not correct by the hits of other tenants."""
    from nornicdb_tpu.api import qdrant
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    inner = BruteForceIndex.search_batch

    def unmasked(self, queries, k=10, exact=False, bounds=None,
                 bounds_gen=None):
        return inner(self, queries, k, exact)

    monkeypatch.setattr(BruteForceIndex, "search_batch", unmasked)
    monkeypatch.setattr(qdrant, "_match_filter", lambda *a, **kw: True)
    result = _run(2147483722)
    assert result["correct"] is False
    failed = {k for k, c in result["checks"].items()
              if not c["value"] <= c["limit"]}
    assert "filter_violations" in failed, result["checks"]
