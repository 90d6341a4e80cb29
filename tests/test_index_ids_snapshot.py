"""The index's slot-to-id table: shared until an id moves (ISSUES 27, 32).

``BruteForceIndex.search_batch``, ``device_lease`` and ``ids_meta`` resolve
slots through one shared, read-only ``IdTable`` of ``_ext_ids``. Pinned
here, on the jitted scan's path (4,200 x 64 is past ``_SMALL_HOST``):

- reads with no write between them share one object and count ``reused``;
- every kind of write makes the next search serve the written state; an
  overwrite of a row moves no id and the table stays shared (``reused``),
  a new or removed id rebuilds its chunk only (``extended``), a change of
  the slot space rebuilds the table (``copied``);
- a snapshot captured before a slot is freed and reused keeps the old id,
  also for a search whose scan is in flight while the write lands;
- readers beside a writer that churns ids through recycled slots only
  ever see an id with the cosine of a vector that id held.
"""

import sys
import threading
import time

import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.search import vector_index
from nornicdb_tpu.search.vector_index import BruteForceIndex, IdTable

ROWS, DIMS = 4200, 64


def _unit(m):
    return m / np.linalg.norm(m, axis=-1, keepdims=True)


def _vectors(seed, rows=ROWS):
    rng = np.random.default_rng(seed)
    return _unit(rng.standard_normal((rows, DIMS))).astype(np.float32)


def _index(vectors):
    idx = BruteForceIndex()
    idx.add_batch([(f"n{i}", v) for i, v in enumerate(vectors)])
    assert idx._capacity * DIMS > BruteForceIndex._SMALL_HOST
    return idx


def _counts():
    fam = obs.REGISTRY.get("nornicdb_index_ids_snapshot_total")
    return {r: fam.labels(r).value
            for r in ("reused", "extended", "copied")}


def _grown(before):
    after = _counts()
    return {r: after[r] - before[r] for r in after}


@pytest.fixture
def handed(monkeypatch):
    """Every snapshot ``_ids_snapshot_locked`` hands out, in order."""
    seen = []
    inner = BruteForceIndex._ids_snapshot_locked

    def spy(self):
        out = inner(self)
        seen.append(out)
        return out

    monkeypatch.setattr(BruteForceIndex, "_ids_snapshot_locked", spy)
    return seen


def _top(idx, vector, k=5):
    return idx.search_batch(np.asarray([vector], np.float32), k)[0]


def _add_new(idx, vectors, tmp_path):
    fresh = _vectors(91, 1)[0]
    idx.add("fresh", fresh)
    return idx, fresh, "fresh"


def _add_over_existing(idx, vectors, tmp_path):
    moved = _vectors(92, 1)[0]
    idx.add("n7", moved)
    return idx, moved, "n7"


def _remove(idx, vectors, tmp_path):
    # n8 gone: its own vector now finds someone else
    assert idx.remove("n8")
    return idx, vectors[8], None


def _compact(idx, vectors, tmp_path):
    for i in range(40):
        assert idx.remove(f"n{i}")
    _top(idx, vectors[50])      # a reader holds the pre-compaction memo
    assert idx.compact()
    return idx, vectors[50], "n50"


def _load_then_add(idx, vectors, tmp_path):
    path = str(tmp_path / "index.npz")
    idx.save(path)
    loaded = BruteForceIndex.load(path)
    assert _top(loaded, vectors[9])[0][0] == "n9"
    fresh = _vectors(93, 1)[0]
    loaded.add("after-load", fresh)
    return loaded, fresh, "after-load"


# the write, and how the first search after it comes by its id table
WRITES = {
    "add_new_id": (_add_new, "extended"),
    "add_over_existing_id": (_add_over_existing, "reused"),
    "remove": (_remove, "extended"),
    "compact": (_compact, "copied"),
    "load_then_add": (_load_then_add, "extended"),
}


def _case_no_write_reuses(handed, tmp_path, monkeypatch):
    vectors = _vectors(1)
    idx = _index(vectors)
    assert _top(idx, vectors[3])[0][0] == "n3"
    before = _counts()
    for row in (4, 5):
        assert _top(idx, vectors[row])[0][0] == f"n{row}"
    assert _grown(before) == {"reused": 2, "extended": 0, "copied": 0}
    assert handed[-1][0] is handed[-2][0] is handed[-3][0]
    assert [r for _, r in handed[-3:]] == ["copied", "reused", "reused"]


def _case_write(write):
    def case(handed, tmp_path, monkeypatch):
        vectors = _vectors(2)
        idx = _index(vectors)
        _top(idx, vectors[0])
        do, how = WRITES[write]
        idx, query, expect = do(idx, vectors, tmp_path)
        before = _counts()
        hits = _top(idx, query)
        want = dict.fromkeys(("reused", "extended", "copied"), 0)
        want[how] = 1
        assert _grown(before) == want
        if expect is None:
            assert "n8" not in {h[0] for h in hits}
            assert hits[0][1] < 0.9
        else:
            assert hits[0][0] == expect
            assert hits[0][1] == pytest.approx(1.0, abs=1e-5)
        # read-your-writes cost at most one chunk; the table is shared again
        _top(idx, query)
        want["reused"] += 1
        assert _grown(before) == want
        assert handed[-1][0] is handed[-2][0]
    return case


def _case_captured_snapshot_keeps_freed_slot(handed, tmp_path, monkeypatch):
    vectors = _vectors(3)
    idx = _index(vectors)
    slot = idx._slot_of["n11"]
    with idx.device_lease() as lease:
        captured = lease.view[2]
    assert isinstance(captured, IdTable)
    assert all(isinstance(c, tuple) for c in captured.chunks)  # read-only
    assert idx.remove("n11")
    idx.add("newcomer", _vectors(94, 1)[0])
    assert idx._slot_of["newcomer"] == slot     # the freed slot, reused
    assert captured[slot] == "n11"
    assert idx.ids_meta()[0][slot] == "newcomer"


def _case_in_flight_search_keeps_its_generation(handed, tmp_path,
                                                monkeypatch):
    vectors = _vectors(4)
    idx = _index(vectors)
    newcomer = _vectors(95, 1)[0]
    scan = vector_index.cosine_topk_auto

    def write_lands_mid_scan(q, m, valid, k):
        # a writer frees n12's slot and reuses it: the write is pending,
        # the arrays this scan was handed are not touched
        assert idx.remove("n12")
        idx.add("newcomer", newcomer)
        return scan(q, m, valid, k)

    monkeypatch.setattr(vector_index, "cosine_topk_auto",
                        write_lands_mid_scan)
    hits = _top(idx, vectors[12])
    monkeypatch.setattr(vector_index, "cosine_topk_auto", scan)
    # the scan ran against the old matrix: its best row is n12's, and the
    # slot resolves to the id it held then, never to the new tenant
    assert hits[0] == ("n12", pytest.approx(1.0, abs=1e-5))
    assert "newcomer" not in {h[0] for h in hits}
    assert _top(idx, newcomer)[0][0] == "newcomer"
    assert "n12" not in {h[0] for h in _top(idx, vectors[12])}


def _case_three_readers_one_object(handed, tmp_path, monkeypatch):
    vectors = _vectors(5)
    idx = _index(vectors)
    before = _counts()
    with idx.device_lease() as lease:
        view = lease.view
    meta = idx.ids_meta()
    _top(idx, vectors[1])
    assert view[2] is meta[0] is handed[-1][0] is idx._ids_table
    assert (view[3], view[4]) == (meta[1], meta[2]) == (idx.mutations, 0)
    assert _grown(before) == {"reused": 2, "extended": 0, "copied": 1}
    idx.add("n1", vectors[2])           # a row overwritten: no id moved
    assert idx.ids_meta()[0] is meta[0]
    idx.add("one-more", vectors[3])     # one id moved: one chunk rebuilt
    after = idx.ids_meta()[0]
    slot = idx._slot_of["one-more"]
    assert after is not meta[0] and after[slot] == "one-more"
    assert [a is b for a, b in zip(after.chunks, meta[0].chunks)] \
        == [c != slot // vector_index.IDS_CHUNK
            for c in range(len(after.chunks))]
    with idx.device_lease() as lease:
        assert lease.view[2] is after


CASES = {
    "no_write_reuses": _case_no_write_reuses,
    **{f"write_{name}": _case_write(name) for name in WRITES},
    "captured_snapshot_keeps_freed_slot":
        _case_captured_snapshot_keeps_freed_slot,
    "in_flight_search_keeps_its_generation":
        _case_in_flight_search_keeps_its_generation,
    "three_readers_one_object": _case_three_readers_one_object,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ids_snapshot(case, handed, tmp_path, monkeypatch):
    CASES[case](handed, tmp_path, monkeypatch)


def test_small_host_path_takes_no_snapshot():
    idx = BruteForceIndex()
    vectors = _vectors(6, 300)
    idx.add_batch([(f"n{i}", v) for i, v in enumerate(vectors)])
    before = _counts()
    assert _top(idx, vectors[2])[0][0] == "n2"
    assert _grown(before) == {"reused": 0, "extended": 0, "copied": 0}
    assert idx._ids_table is None


def test_readers_beside_a_writer_recycling_slots():
    """Eight readers beside one writer that removes churn ids and adds
    OTHER churn ids into the freed slots, each with one of two vectors:
    every served (id, score) is the cosine of a vector that id held. An
    id table of another generation than the matrix that was scanned
    would serve a newcomer with its predecessor's score."""
    first, second = _vectors(7), _vectors(8)
    held = np.stack([first, second])            # [2, ROWS, DIMS]
    churn = list(range(0, 128))
    idx = _index(first)
    absent = churn[::2]
    for i in absent:
        idx.remove(f"n{i}")
    absent = list(absent)
    present = churn[1::2]
    stop = threading.Event()
    deadline = time.monotonic() + 20.0
    wrong, served, writes = [], [0], [0]

    def writer():
        rng = np.random.default_rng(11)
        while not stop.is_set() and time.monotonic() < deadline:
            out = present.pop(int(rng.integers(len(present))))
            idx.remove(f"n{out}")
            come = absent.pop(int(rng.integers(len(absent))))
            idx.add(f"n{come}", held[int(rng.integers(2)), come])
            absent.append(out)
            present.append(come)
            writes[0] += 1
            time.sleep(0.001)   # let scans start between writes

    def reader(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set() and time.monotonic() < deadline:
            near = held[int(rng.integers(2)), int(rng.choice(churn))]
            q = _unit(near + 0.05 * rng.standard_normal(DIMS))
            q = q.astype(np.float32)
            for eid, score in _top(idx, q, k=10):
                row = int(eid[1:])
                if np.abs(held[:, row] @ q - score).min() > 1e-4:
                    wrong.append((eid, score))
                served[0] += 1

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(s,)) for s in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        while ((writes[0] < 300 or served[0] < 2000)
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert writes[0] >= 300 and served[0] >= 2000
    assert wrong == []
