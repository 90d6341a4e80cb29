"""Adversarial durability / concurrency / chaos corpus (VERDICT r1 item 8).

Reference test strategy (SURVEY §4): corruption-injection durability
tests (wal_corruption_test.go — garbage bytes mid-segment, not just the
torn-tail happy path), race regressions (concurrent_count_test.go,
async_engine_count_flush_race_test.go, index_lock_contention_test.go),
and chaos/injection corpora (chaos_injection_test.go — unicode,
injection strings, empty values).
"""

import os
import struct
import threading
import zlib

import pytest

import nornicdb_tpu
from nornicdb_tpu.query.executor import CypherExecutor
from nornicdb_tpu.storage import MemoryEngine, NamespacedEngine
from nornicdb_tpu.storage.types import Edge, Node
from nornicdb_tpu.storage.wal import WAL, _HEADER
from nornicdb_tpu.storage.wal_engine import DurableEngine


# ---------------------------------------------------------- WAL corruption


def _segments(d):
    return sorted(
        os.path.join(d, f) for f in os.listdir(d)
        if f.startswith("wal-") and f.endswith(".log")
    )


class TestWALCorruptionInjection:
    def _write_records(self, d, n=50):
        wal = WAL(d, max_segment_bytes=512)  # force several segments
        for i in range(n):
            wal.append("put", {"k": f"key{i}", "v": "x" * 40})
        wal.close()
        return wal

    def test_garbage_mid_segment_flags_degraded(self, tmp_path):
        """Corrupting a NON-tail segment must surface degraded mode, not
        silently truncate history (reference: wal_degraded.go)."""
        d = str(tmp_path)
        self._write_records(d)
        segs = _segments(d)
        assert len(segs) >= 3
        victim = segs[0]
        size = os.path.getsize(victim)
        with open(victim, "r+b") as f:
            f.seek(size // 2)
            f.write(b"\xDE\xAD\xBE\xEF" * 4)
        wal = WAL(d)
        applied = []
        res = wal.replay(lambda op, data: applied.append(data))
        assert res.degraded
        assert victim in res.corrupt_segments
        assert applied  # later segments still replay

    def test_flipped_crc_byte(self, tmp_path):
        """A single flipped payload byte must be caught by the CRC."""
        d = str(tmp_path)
        wal = WAL(d)
        wal.append("put", {"k": "a", "v": "sensitive"})
        wal.append("put", {"k": "b", "v": "later"})
        wal.close()
        path = _segments(d)[0]
        data = bytearray(open(path, "rb").read())
        data[_HEADER.size + 3] ^= 0x01  # flip a bit inside record 1 payload
        open(path, "wb").write(bytes(data))
        wal = WAL(d)
        applied = []
        res = wal.replay(lambda op, rec: applied.append(rec))
        # record 1 rejected; everything after is unreachable in that
        # segment (stream framing), tail segment handling applies
        assert applied == [] or applied[0].get("k") != "a"

    def test_truncated_header_mid_file(self, tmp_path):
        d = str(tmp_path)
        wal = WAL(d)
        for i in range(5):
            wal.append("put", {"k": f"k{i}"})
        wal.close()
        path = _segments(d)[0]
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 3)  # cut into the last record
        eng = DurableEngine(d)
        assert eng.replay_result.torn_tail_repaired
        eng.close()

    def test_insane_length_header(self, tmp_path):
        """A corrupted length field (huge) must not trigger a giant
        allocation or hang — treated as torn frame."""
        d = str(tmp_path)
        wal = WAL(d)
        wal.append("put", {"k": "a"})
        wal.close()
        path = _segments(d)[0]
        with open(path, "ab") as f:
            f.write(_HEADER.pack(0x7FFFFFFF, 0))
        wal = WAL(d)
        applied = []
        res = wal.replay(lambda op, rec: applied.append(rec))
        assert len(applied) == 1
        assert res.torn_tail_repaired

    def test_zero_filled_tail(self, tmp_path):
        d = str(tmp_path)
        wal = WAL(d)
        wal.append("put", {"k": "a"})
        wal.close()
        path = _segments(d)[0]
        with open(path, "ab") as f:
            f.write(b"\x00" * 64)
        eng = DurableEngine(d)
        assert eng.replay_result.torn_tail_repaired
        eng.close()
        # after repair, a reopen must be clean (no repeated repair)
        eng2 = DurableEngine(d)
        assert not eng2.replay_result.torn_tail_repaired
        eng2.close()

    def test_all_snapshots_corrupt_refuses_silent_data_loss(self, tmp_path):
        """When every snapshot is unreadable, recovery must REFUSE rather
        than silently open a near-empty store (pre-snapshot segments were
        pruned) — the explicit-failure analog of wal_degraded.go."""
        from nornicdb_tpu.errors import WALCorruptionError

        d = str(tmp_path)
        eng = DurableEngine(d)
        eng.create_node(Node(id="n1", labels=["A"], properties={"v": 1}))
        eng.snapshot()
        eng.create_node(Node(id="n2", labels=["A"], properties={"v": 2}))
        eng.close()  # prunes to the newest snapshot
        snaps = sorted(
            os.path.join(d, f) for f in os.listdir(d)
            if f.startswith("snapshot-")
        )
        for snap in snaps:
            with open(snap, "r+b") as f:
                f.seek(_HEADER.size + 2)
                f.write(b"\xFF\xFF\xFF\xFF")
        with pytest.raises(WALCorruptionError):
            DurableEngine(d)

    def test_encrypted_wal_corruption_still_repairs(self, tmp_path):
        from nornicdb_tpu.encryption import Encryptor

        d = str(tmp_path)
        enc = Encryptor(b"k" * 32)
        wal = WAL(d, encryptor=enc)
        for i in range(3):
            wal.append("put", {"k": f"k{i}"})
        wal.close()
        path = _segments(d)[0]
        with open(path, "ab") as f:
            f.write(b"garbage-tail-bytes")
        wal2 = WAL(d, encryptor=enc)
        applied = []
        res = wal2.replay(lambda op, rec: applied.append(rec))
        assert len(applied) == 3
        assert res.torn_tail_repaired


# ------------------------------------------------------- native KV chaos


class TestNativeKVCorruption:
    @pytest.fixture(autouse=True)
    def _native(self):
        from nornicdb_tpu.storage.disk import native_available

        if not native_available():
            pytest.skip("native kv unavailable")

    def test_garbage_appended_to_segment(self, tmp_path):
        from nornicdb_tpu.storage.disk import DiskEngine

        d = str(tmp_path / "db")
        eng = DiskEngine(d)
        eng.create_node(Node(id="a", labels=["X"], properties={"v": 1}))
        eng.close()
        kv_dir = os.path.join(d, "kv")
        seg = sorted(
            os.path.join(kv_dir, f) for f in os.listdir(kv_dir)
            if not f.endswith(".tmp")
        )[0]
        with open(seg, "ab") as f:
            f.write(b"\xBA\xAD\xF0\x0D" * 8)
        eng2 = DiskEngine(d)
        assert eng2.get_node("a").properties["v"] == 1
        assert eng2.kv.repaired >= 0  # repair counter exposed
        eng2.close()


# --------------------------------------------------------- race regressions


class TestConcurrencyRaces:
    def test_concurrent_creates_unique_counts(self):
        """reference: concurrent_count_test.go — counts must equal the
        number of successful creates under contention."""
        eng = NamespacedEngine(MemoryEngine(), "test")
        n_threads, per = 8, 50
        errors = []

        def worker(t):
            for i in range(per):
                try:
                    eng.create_node(Node(id=f"t{t}-{i}", labels=["C"],
                                         properties={}))
                except Exception as e:  # pragma: no cover
                    errors.append(e)

        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(n_threads)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errors
        assert eng.count_nodes() == n_threads * per
        assert len(eng.get_nodes_by_label("C")) == n_threads * per

    def test_concurrent_update_delete_no_ghosts(self):
        eng = NamespacedEngine(MemoryEngine(), "test")
        for i in range(100):
            eng.create_node(Node(id=f"n{i}", labels=["G"], properties={"v": 0}))
        stop = threading.Event()
        errors = []

        def updater():
            i = 0
            while not stop.is_set():
                try:
                    n = eng.get_node(f"n{i % 100}")
                    n.properties["v"] += 1
                    eng.update_node(n)
                except KeyError:
                    pass
                except Exception as e:
                    errors.append(e)
                i += 1

        def deleter():
            for i in range(0, 100, 2):
                try:
                    eng.delete_node(f"n{i}")
                except Exception:
                    pass
            stop.set()

        t1 = threading.Thread(target=updater)
        t2 = threading.Thread(target=deleter)
        t1.start(); t2.start()
        t2.join(); stop.set(); t1.join()
        assert not errors
        assert eng.count_nodes() == 50
        # label index consistent with primary records
        assert len(eng.get_nodes_by_label("G")) == 50

    def test_concurrent_cypher_reads_during_writes(self):
        """Executor read path (fast paths + columnar cache) must never
        crash or return phantom errors while another thread mutates."""
        eng = NamespacedEngine(MemoryEngine(), "test")
        ex = CypherExecutor(eng)
        for i in range(50):
            ex.execute("CREATE (:R {i: $i})", {"i": i})
        errors = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                try:
                    r = ex.execute("MATCH (n:R) RETURN count(n)")
                    assert isinstance(r.rows[0][0], int)
                    ex.execute("MATCH (n:R) WHERE n.i > 10 RETURN n.i")
                except Exception as e:
                    errors.append(e)

        def writer():
            for i in range(50, 150):
                try:
                    ex.execute("CREATE (:R {i: $i})", {"i": i})
                except Exception as e:
                    errors.append(e)
            stop.set()

        ts = [threading.Thread(target=reader) for _ in range(3)]
        tw = threading.Thread(target=writer)
        [t.start() for t in ts]
        tw.start()
        tw.join()
        [t.join() for t in ts]
        assert not errors, [f"{type(e).__name__}: {e}" for e in errors[:3]]
        # short here and no exception above: ROADMAP D10, a reader's
        # result put into the query cache after the last write cleared it
        assert ex.execute("MATCH (n:R) RETURN count(n)").rows == [[150]]

    def test_concurrent_search_index_and_query(self):
        from nornicdb_tpu.search.service import SearchService

        eng = NamespacedEngine(MemoryEngine(), "test")
        svc = SearchService(eng)
        import numpy as np

        rng = np.random.default_rng(0)
        errors = []

        def indexer(base):
            for i in range(60):
                node = Node(id=f"d{base}-{i}", labels=["Doc"],
                            properties={"content": f"text {base} {i}"},
                            embedding=list(rng.standard_normal(8)))
                try:
                    eng.create_node(node)
                    svc.index_node(node)
                except Exception as e:
                    errors.append(e)

        def searcher():
            for _ in range(40):
                try:
                    svc.search("text", limit=5)
                except Exception as e:
                    errors.append(e)

        ts = [threading.Thread(target=indexer, args=(b,)) for b in range(3)]
        ts += [threading.Thread(target=searcher) for _ in range(2)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errors
        assert len(svc.vectors) == 180


# ------------------------------------------------------------ cypher chaos


CHAOS_INPUTS = [
    "Robert'); DROP TABLE students;--",
    "''; MATCH (n) DETACH DELETE n; //",
    "日本語のテキスト",
    "emoji 🧨🦉🌋 payload",
    "line\nbreaks\r\nand\ttabs",
    "quotes \" and ' mixed ` backtick",
    "a" * 10_000,
    "\\u0000 escaped null",
    "${injection} {curly} [bracket]",
    "unicode ‮ RLO override",
    "",
]


class TestCypherChaos:
    @pytest.fixture()
    def ex(self):
        return CypherExecutor(NamespacedEngine(MemoryEngine(), "test"))

    @pytest.mark.parametrize("payload", CHAOS_INPUTS,
                             ids=[repr(c)[:25] for c in CHAOS_INPUTS])
    def test_parameter_values_are_inert(self, ex, payload):
        """Parameterized values must round-trip exactly and never execute
        (reference: chaos_injection_test.go)."""
        ex.execute("CREATE (:Chaos {v: $v})", {"v": payload})
        r = ex.execute("MATCH (c:Chaos) WHERE c.v = $v RETURN c.v", {"v": payload})
        assert r.rows == [[payload]]
        assert ex.execute("MATCH (n) RETURN count(n)").rows[0][0] == 1

    @pytest.mark.parametrize("bad", [
        "MATCH (n RETURN n",
        "CREATE (n:Label {unclosed: 'str)",
        "RETURN",
        "MATCH (a)-[]->() WHERE RETURN a",
        "CALL unknown.proc.name()",
        "RETURN 1 +",
        "MATCH (a))--((b) RETURN a",
        ")(",
    ])
    def test_malformed_queries_raise_cypher_errors(self, ex, bad):
        from nornicdb_tpu.errors import CypherRuntimeError, CypherSyntaxError

        with pytest.raises((CypherSyntaxError, CypherRuntimeError)):
            ex.execute(bad)

    def test_deeply_nested_expression(self, ex):
        expr = "1" + " + 1" * 200
        assert ex.execute(f"RETURN {expr}").rows == [[201]]

    def test_deeply_nested_lists(self, ex):
        lit = "[" * 50 + "1" + "]" * 50
        r = ex.execute(f"RETURN {lit}")
        v = r.rows[0][0]
        for _ in range(50):
            v = v[0]
        assert v == 1

    def test_huge_parameter_list(self, ex):
        big = list(range(50_000))
        r = ex.execute("RETURN size($l)", {"l": big})
        assert r.rows == [[50_000]]

    def test_null_bytes_in_strings(self, ex):
        s = "before\x00after"
        r = ex.execute("RETURN $s AS v", {"s": s})
        assert r.rows == [[s]]

    def test_label_with_unicode(self, ex):
        ex.execute("CREATE (:Størrelse {ok: true})")
        r = ex.execute("MATCH (n:Størrelse) RETURN n.ok")
        assert r.rows == [[True]]


# -------------------------------------------------- async engine races


class TestAsyncEngineRaces:
    def test_flush_vs_write_no_lost_updates(self):
        from nornicdb_tpu.storage import AsyncEngine

        inner = MemoryEngine()
        eng = AsyncEngine(inner, flush_interval_s=0.01)
        try:
            errors = []

            def writer(base):
                for i in range(100):
                    try:
                        eng.create_node(Node(id=f"a{base}-{i}", labels=["W"],
                                             properties={}))
                    except Exception as e:
                        errors.append(e)

            ts = [threading.Thread(target=writer, args=(b,)) for b in range(4)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            eng.flush()
            assert not errors
            assert inner.count_nodes() == 400
        finally:
            eng.close()

    def test_count_during_flush_window(self):
        """reference: async_engine_count_flush_race_test.go — counts seen
        through the async layer must include unflushed writes."""
        from nornicdb_tpu.storage import AsyncEngine

        inner = MemoryEngine()
        eng = AsyncEngine(inner, flush_interval_s=60.0)  # no auto flush
        try:
            for i in range(25):
                eng.create_node(Node(id=f"c{i}", labels=["F"], properties={}))
            assert eng.count_nodes() == 25
            assert len(eng.get_nodes_by_label("F")) == 25
            eng.flush()
            assert eng.count_nodes() == 25
        finally:
            eng.close()
