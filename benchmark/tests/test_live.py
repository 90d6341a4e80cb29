"""The live cell (``vec2m-rw-c32``): its reference judges answers AS OF
their request (either version while a write is in flight, the old one
refused once the write was acknowledged), its control reads above the
limit, and a whole rehearsed run with the write path broken underneath
comes out not correct: an acknowledged write that never reaches the device
copy, an acknowledgement sent before the write is applied.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import io
import json
import os
import threading
from contextlib import redirect_stdout

import numpy as np

from benchmark.lib import loader

ROOT = loader.ROOT
CELL = "vec2m-rw-c32"
LIMIT = 10


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qdrant-bge-m3-2m-live.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _run(seed, control=None):
    from benchmark import run as bench_run

    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(argv) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["metrics"] == {} and result["rehearsal"] is True
    return result


def _failed(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def _unit(m):
    return (m / np.linalg.norm(m, axis=-1, keepdims=True)
            ).astype(np.float32)


def _answer(rows_of, query):
    """The exact top ``LIMIT`` over ``rows_of`` (id -> row), in float32 as
    the index scores: the program's place taken by plain arithmetic."""
    ids = np.asarray(sorted(rows_of), np.int64)
    m = np.stack([rows_of[i] for i in ids])
    s = m @ (query / np.linalg.norm(query)).astype(np.float32)
    top = np.argsort(-s, kind="stable")[:LIMIT]
    return ids[top], s[top].astype(np.float64)


def test_the_reference_judges_answers_as_of_their_request():
    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    rng = np.random.default_rng(5)
    base = _unit(rng.standard_normal((512, 64)))
    new_row, moved = _unit(rng.standard_normal((2, 64)))
    # one write: point 512 is new, base row 7 is overwritten; sent at
    # t=10, acknowledged at t=11
    log = ref.WriteLog(512, [(0, 10.0, 11.0, np.asarray([512, 7]),
                              np.stack([new_row, moved]))])
    before = {i: base[i] for i in range(512)}
    after = {**before, 512: new_row, 7: moved}
    near_new = _unit(new_row + 0.02 * rng.standard_normal(64))
    near_old = _unit(base[7] + 0.02 * rng.standard_normal(64))

    def read(t_send, t_done, query, state):
        ids, scores = _answer(state, query)
        out = ref.judge(base, log, [ref.Search(t_send, t_done, query)],
                        [ids], [scores], LIMIT)
        out["first"] = int(ids[0])
        return out

    def right(out):
        return (out["score_err"] <= cfg["limits"]["score_err_max"]
                and out["rank_gap"] <= cfg["limits"]["rank_gap_max"]
                and out["stale_after_ack"] == 0 and out["unknown_ids"] == 0)

    # while the write is in flight either version is an answer
    assert right(read(10.2, 10.8, near_new, before))
    assert right(read(10.2, 10.8, near_new, after))
    assert right(read(10.2, 10.8, near_old, before))
    assert right(read(10.2, 10.8, near_old, after))
    # before it was sent only the old one; the new point is unknown
    assert right(read(9.0, 9.5, near_old, before))
    assert read(9.0, 9.5, near_new, after)["unknown_ids"] == 1
    # after the acknowledgement only the new one: the new point not found
    # reads as a gap, the overwritten point at its old vector as stale
    assert right(read(11.5, 12.0, near_new, after))
    assert right(read(11.5, 12.0, near_old, after))
    late = read(11.5, 12.0, near_new, before)
    # (by the distance between the cut and the row served in its place)
    assert late["rank_gap"] > 100 * cfg["limits"]["rank_gap_max"]
    stale = read(11.5, 12.0, near_old, before)
    assert stale["first"] == 7 and stale["stale_after_ack"] == 1
    # the fresh query's first hit, judged where its version held through
    ids_new, _ = _answer(after, near_new)
    ids_old, _ = _answer(before, near_new)
    assert ref.fresh_not_first(log, [(11.5, 12.0, 512, 0, ids_new)]) == 0
    assert ref.fresh_not_first(log, [(11.5, 12.0, 512, 0, ids_old)]) == 1
    assert ref.fresh_not_first(log, [(10.5, 12.0, 512, 0, ids_old)]) == 0


def test_the_live_control_reads_above_the_limit():
    from benchmark.lib.live_writes import Writes
    from benchmark.systems.qdrant_collection import make_vectors

    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    base = make_vectors(21, 16384, 1024, 16, 1.0)
    writes = Writes(21, 16384, 1024, 16, 1.0, new=8, over=4)
    log = ref.WriteLog(16384, [(n, float(n), n + 0.5, *writes.points(n))
                               for n in range(4)])
    rng = np.random.default_rng(22)
    targets = np.concatenate([log.rows[[0, 9, 30]],
                              base[rng.integers(0, 16384, 13)]])
    queries = targets + np.float32(0.25 / 32.0) * rng.standard_normal(
        targets.shape, dtype=np.float32)
    searches = [ref.Search(10.0, 10.5, q) for q in queries]
    ids, vals = ref.control_answers(base, log, searches, 100)
    assert [int(a[0]) for a in ids[:3]] \
        == [int(log.ids[e]) for e in (0, 9, 30)]
    control = ref.judge(base, log, searches, ids, vals, 100)
    assert control["score_err"] > cfg["limits"]["score_err_max"]
    assert control["score_err_rms"] > cfg["limits"]["score_err_rms"]
    assert control["stale_after_ack"] == control["unknown_ids"] == 0


def test_unbroken_live_cell_is_correct():
    result = _run(2147483711)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["counts"]["writes_sent"] > 0


def test_a_write_that_never_reaches_the_device_is_not_correct(monkeypatch):
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    inner = BruteForceIndex._wrote_locked

    def forgets(self, slot, id_moved=False):
        inner(self, slot, id_moved)
        self._pending.clear()           # the device copy is never told

    monkeypatch.setattr(BruteForceIndex, "_wrote_locked", forgets)
    result = _run(2147483712)
    assert result["correct"] is False
    assert _failed(result) >= {"fresh_not_first", "rank_gap_max"}, \
        result["checks"]


def test_an_acknowledgement_before_the_apply_is_not_correct(monkeypatch):
    from nornicdb_tpu.api.qdrant import QdrantCompat

    inner = QdrantCompat.upsert_points
    timers = []

    def acknowledges_first(self, name, points):
        timer = threading.Timer(0.35, inner, (self, name, list(points)))
        timers.append(timer)
        timer.start()
        return len(points)

    monkeypatch.setattr(QdrantCompat, "upsert_points", acknowledges_first)
    try:
        result = _run(2147483713)
    finally:
        for timer in timers:
            timer.join()
    assert result["correct"] is False
    assert "fresh_not_first" in _failed(result), result["checks"]
