"""System under test: one Qdrant-compatible collection behind the HTTP
server, as ``cli serve`` builds it (``nornicdb_tpu.open()`` +
``HttpServer``), searched through ``POST /collections/<c>/points/search``.

Set-up fills the collection in bulk (``PERF.md`` says why and what each
part costs), ships the matrix to the device with the first search and
warms every batch bucket the cell's clients can seal. The window is driven
by ``benchmark.lib.traffic``. ``verify`` compares a seeded sample of what
the timed requests returned with the configuration's plain reference.
"""

from __future__ import annotations

import gc
import io
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib import loader
from benchmark.lib.check import Check
from benchmark.lib.client import Client
from benchmark.lib.observed import Observed, parse_prometheus
from benchmark.lib.stats import percentile
from benchmark.lib.traffic import Reply, drive

GEN_BLOCK = 32768          # rows made per generator task
GEN_THREADS = 8
LANGS = ("en", "de", "fr", "es", "ja", "zh", "pt", "ko")


def payload_of(point_id: int) -> Dict[str, Any]:
    """The three short fields of a point, a function of its id alone."""
    return {"shard": point_id & 63, "lang": LANGS[point_id % len(LANGS)],
            "title": f"doc-{point_id}"}


def make_vectors(seed: int, rows: int, dims: int, centers: int,
                 spread: float) -> np.ndarray:
    """``rows`` unit float32 vectors from a mixture of ``centers``
    Gaussians: row i is its centre plus ``spread`` x unit noise, scaled to
    length 1 as an embedding model's output is. Made block by block from
    (seed, block), so the result does not depend on which thread made
    which block."""
    cent = np.random.default_rng([seed, 1]).standard_normal(
        (centers, dims), dtype=np.float32)
    out = np.empty((rows, dims), np.float32)
    blocks = list(range(0, rows, GEN_BLOCK))
    nxt = iter(blocks)
    lock = threading.Lock()

    def work() -> None:
        while True:
            with lock:
                start = next(nxt, None)
            if start is None:
                return
            rng = np.random.default_rng([seed, 2, start])
            part = out[start:start + GEN_BLOCK]
            rng.standard_normal(out=part, dtype=np.float32)
            if spread != 1.0:
                part *= np.float32(spread)
            part += cent[rng.integers(0, centers, part.shape[0])]
            part /= np.sqrt(np.einsum("ij,ij->i", part, part,
                                      dtype=np.float32))[:, None]

    threads = [threading.Thread(target=work) for _ in range(GEN_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def _stored_matrix(npz: io.BytesIO, shape: Tuple[int, int]) -> np.ndarray:
    """The float32 member ``matrix.npy`` of an uncompressed ``.npz`` held in
    memory, as a view of the buffer (which it keeps alive)."""
    import struct
    import zipfile

    with zipfile.ZipFile(npz) as z:
        info = z.getinfo("matrix.npy")
        if info.compress_type != zipfile.ZIP_STORED:
            raise ValueError("the .npz member is compressed")
        with z.open(info) as member:
            fmt = np.lib.format
            if fmt.read_magic(member) == (1, 0):
                fmt.read_array_header_1_0(member)
            else:
                fmt.read_array_header_2_0(member)
            header_len = member.tell()
    view = npz.getbuffer()
    name_len, extra_len = struct.unpack_from("<HH", view,
                                             info.header_offset + 26)
    start = info.header_offset + 30 + name_len + extra_len + header_len
    return np.frombuffer(view, np.float32, shape[0] * shape[1],
                         start).reshape(shape)


class System:
    def __init__(self, run: Any) -> None:
        self.run = run
        self.rows = int(run.size("rows"))
        self.dims = int(run.size("dims"))
        self.collection = str(run.config["collection"])
        self.limit = int(run.mix("limit"))
        self.clients = int(run.mix("clients"))
        self.noise = float(run.mix("query_noise"))
        self.db = None
        self.http = None
        self.vectors: Optional[np.ndarray] = None
        self.parts: Dict[str, float] = {}
        self.replies: List[Reply] = []
        self.t_open = self.t_close = 0.0
        self.compiles_in_window = 0
        # what the requests are drawn from; tools/read_limits.py drives
        # several windows over one set-up by changing it
        self.traffic_seed = run.seed
        self._path = f"/collections/{self.collection}/points/search"

    # -- set-up ----------------------------------------------------------

    def _timed(self, name: str, t0: float) -> float:
        now = time.time()
        self.parts[name] = now - t0
        return now

    def setup(self) -> None:
        run = self.run
        t = time.time()
        self.parts["imports_and_device_s"] = t - run.t_start
        import nornicdb_tpu
        from nornicdb_tpu.api.http_server import HttpServer

        self.db = nornicdb_tpu.open()
        self.http = HttpServer(self.db, port=0).start()
        self.client = Client(self.http.port,
                             headers=run.config.get("request_headers"))
        status, raw = self.client.request(
            "PUT", f"/collections/{self.collection}",
            json.dumps({"vectors": {"size": self.dims,
                                    "distance": "Cosine"}}).encode())
        if status != 200:
            raise RuntimeError(f"create collection answered {status}: "
                               f"{raw[:300]!r}")
        t = self._timed("open_and_server_s", t)
        self.vectors = make_vectors(
            run.seed, self.rows, self.dims,
            int(run.size("mixture_centers")),
            float(run.config["mixture_spread"]))
        t = self._timed("make_vectors_s", t)
        # index first: its fill holds the most host memory at once (three
        # copies of the matrix; the nodes beside a fourth ran the 40 GiB
        # host out of memory), and the nodes are written inside the
        # layer's own-write scope, so the external-mutation listener
        # leaves the filled index alone
        self._fill_index()
        t = self._timed("fill_index_s", t)
        self._create_nodes()
        t = self._timed("create_nodes_s", t)
        self._search_once(-1, 0)            # ships the matrix
        t = self._timed("first_search_ship_s", t)
        if run.trace:
            self._wrap_for_trace()
        self._warm()
        self._timed("warm_s", t)

    def _create_nodes(self) -> None:
        """The points' storage nodes: ``_point_id`` and payload, no
        ``_vector`` (a second copy of every vector as a Python list would
        not fit the host). Written inside the layer's own-write scope so
        the external-mutation listener leaves the index alone."""
        from nornicdb_tpu.api.qdrant import _point_node_id
        from nornicdb_tpu.storage import Node

        compat = self.db.qdrant_compat
        label = [compat._label(self.collection)]
        create = self.db.storage.create_node
        with compat._own_write():
            for i in range(self.rows):
                create(Node(id=_point_node_id(self.collection, i),
                            labels=label,
                            properties={"_point_id": i,
                                        "payload": payload_of(i)}))

    def _fill_index(self) -> None:
        """``BruteForceIndex.load`` restores rows verbatim from an ``.npz``.
        ``np.load`` takes a file object, so the "file" stays in memory and
        nothing is written to disk. The fastest bulk entry point the
        program has: ``add`` costs 21 us a row, 45 s for the collection
        against 38 s this way (PERF.md section 4)."""
        from nornicdb_tpu.api.qdrant import _point_node_id
        from nornicdb_tpu.search.vector_index import BruteForceIndex

        compat = self.db.qdrant_compat
        ids = np.asarray([_point_node_id(self.collection, i)
                          for i in range(self.rows)])
        buf = io.BytesIO()
        np.savez(buf, matrix=self.vectors, ids=ids)
        # the .npz stores the matrix verbatim: from here on the benchmark's
        # own copy is a view of those bytes, 8.6 GB less on the host
        self.vectors = _stored_matrix(buf, self.vectors.shape)
        buf.seek(0)
        index = BruteForceIndex.load(buf)
        with compat._lock:
            compat._space(self.collection).index = index
        if len(compat._index(self.collection)) != self.rows:
            raise RuntimeError("the index does not hold every row")

    def _query(self, k: int, seq: int) -> Tuple[int, np.ndarray]:
        """The vector of client ``k``'s request number ``seq``: a seeded
        row plus fresh noise of length ``query_noise`` (the rows have length
        1), so no two requests carry the same vector."""
        rng = np.random.default_rng([self.traffic_seed, 3, k + 1, seq])
        row = int(rng.integers(0, self.rows))
        q = self.vectors[row] + np.float32(
            self.noise / np.sqrt(self.dims)) * rng.standard_normal(
                self.dims, dtype=np.float32)
        return row, q

    def _body(self, q: np.ndarray) -> bytes:
        return json.dumps({"vector": q.tolist(), "limit": self.limit,
                           "with_payload": True}).encode()

    def _search_once(self, k: int, seq: int) -> None:
        _, q = self._query(k, seq)
        status, raw = self.client.post(self._path, self._body(q))
        ok, _ = self._judge(status, raw, q)
        if not ok:
            raise RuntimeError(f"warm-up search answered {status}: "
                               f"{raw[:300]!r}")

    def _warm(self) -> None:
        """One sealed batch for every power-of-two bucket that
        ``clients`` callers can seal, so every program the window can need
        is compiled before it. The batcher seals whatever has arrived, so
        the warm-up holds its gather window open for a burst of n (as
        ``chip_smoke._warm_ladder`` does), in warm-up only."""
        compat = self.db.qdrant_compat
        batcher = compat._collection_microbatch(self.collection)
        shipped = batcher._gather_window_s
        batcher._gather_window_s = 0.5
        seq = 1
        try:
            n = 1
            while True:
                n = min(n, self.clients)
                self._wait_admit()
                batcher._last_batch = n
                gate = threading.Barrier(n)
                errors: List[BaseException] = []

                def one(j: int, s: int = seq) -> None:
                    try:
                        gate.wait(timeout=60)
                        self._search_once(-1, s + j)
                    except BaseException as exc:  # noqa: BLE001
                        errors.append(exc)

                ts = [threading.Thread(target=one, args=(j,))
                      for j in range(n)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errors:
                    raise errors[0]
                seq += n
                if n >= self.clients:
                    break
                n *= 2
        finally:
            batcher._gather_window_s = shipped
        self._wait_admit()

    @staticmethod
    def _wait_admit(timeout_s: float = 120.0) -> None:
        from nornicdb_tpu import admission

        deadline = time.time() + timeout_s
        while admission.CONTROLLER.refresh(force=True) != "admit":
            if time.time() > deadline:
                raise RuntimeError("admission did not return to 'admit'")
            time.sleep(0.25)

    def _wrap_for_trace(self) -> None:
        """Host spans on the profiler's clock, written from outside the
        program: round the index's batched search and round the whole
        ``search_points`` call."""
        import jax

        compat = self.db.qdrant_compat
        index = compat._index(self.collection)
        inner_batch = index.search_batch
        inner_points = compat.search_points

        def search_batch(*a, **kw):
            with jax.profiler.TraceAnnotation("bench:index.search_batch"):
                return inner_batch(*a, **kw)

        def search_points(*a, **kw):
            with jax.profiler.TraceAnnotation("bench:qdrant.search_points"):
                return inner_points(*a, **kw)

        index.search_batch = search_batch
        compat.search_points = search_points

    # -- the window ------------------------------------------------------

    def _make(self, k: int, seq: int) -> Tuple[str, bytes, Any]:
        _, q = self._query(k, seq)
        return self._path, self._body(q), q

    def _judge(self, status: int, raw: bytes, q: Any) -> Tuple[bool, Any]:
        """A request is answered when it is a 200 with ``limit`` hits;
        what is kept is enough to check it afterwards."""
        if status != 200:
            return False, None
        hits = json.loads(raw)["result"]
        ids = np.fromiter((h["id"] for h in hits), np.int64, len(hits))
        scores = np.fromiter((h["score"] for h in hits), np.float64,
                             len(hits))
        well = len(hits) == self.limit \
            and all(h.get("payload") == payload_of(int(h["id"]))
                    for h in hits)
        return len(hits) == self.limit, (q, ids, scores, well)

    def window(self, tracer: Any) -> Dict[str, Any]:
        from nornicdb_tpu import admission
        from nornicdb_tpu.obs import tracing

        run = self.run
        obs = Observed()
        obs.config, obs.traffic = run.config, run.traffic
        index = self.db.qdrant_compat._index(self.collection)
        obs.sizes = {"capacity": int(index._capacity), "dims": self.dims,
                     "rows": self.rows, "limit": self.limit}
        tracing.TRACES.capacity = 1 << 20
        marks: Dict[str, Any] = {}

        def on_open() -> None:
            tracing.TRACES.clear()
            marks["prom0"] = self.client.get("/metrics")[1].decode()
            marks["programs0"] = run.meter.snapshot()["programs"]
            if tracer.enabled:
                tracer.start()
            marks["wall_open"] = time.time()

        def on_close() -> None:
            marks["wall_close"] = time.time()
            if tracer.enabled:
                tracer.stop()
            marks["programs1"] = run.meter.snapshot()["programs"]
            marks["prom1"] = self.client.get("/metrics")[1].decode()

        gen2: List[float] = []       # seconds of each full collection

        def on_gc(phase: str, info: Dict[str, Any]) -> None:
            if info["generation"] == 2:
                if phase == "start":
                    gen2.append(-time.perf_counter())
                elif gen2 and gen2[-1] < 0:
                    gen2[-1] += time.perf_counter()

        gc.callbacks.append(on_gc)
        setup_s = time.time() - run.t_start   # up to the ramp's first request
        t_open, t_close, replies = drive(
            self.http.port, run.traffic, run.seconds, self._make, self._judge,
            headers=run.config.get("request_headers"),
            on_open=on_open, on_close=on_close,
            on_tick=tracer.tick if tracer.enabled else None,
            annotate=tracer.annotate if tracer.enabled else None)
        gc.callbacks.remove(on_gc)
        self.replies, self.t_open, self.t_close = replies, t_open, t_close
        self.compiles_in_window = marks["programs1"] - marks["programs0"]
        sent = [r for r in replies if t_open <= r.t_send < t_close]
        answered = [r for r in sent if r.ok]
        in_time = [r for r in replies
                   if r.ok and t_open <= r.t_done <= t_close]
        if not answered or not in_time:
            raise RuntimeError(
                f"no request of the window was answered "
                f"({len(sent)} sent, statuses "
                f"{sorted({r.status for r in sent})})")
        obs.window_s = t_close - t_open
        obs.spans = [s for s in tracing.TRACES.snapshot(limit=1 << 20)
                     if s["start_ms"] >= marks["wall_open"] * 1e3
                     and s["start_ms"] + s["duration_ms"]
                     <= marks["wall_close"] * 1e3]
        obs.prom_before = parse_prometheus(marks["prom0"])
        obs.prom_after = parse_prometheus(marks["prom1"])
        if tracer.enabled:
            obs.traced = {"requests": float(sum(
                1 for r in replies
                if r.ok and tracer.t0 <= r.t_done <= tracer.t1))}
        adm = admission.CONTROLLER.summary()
        batches = obs.prom_delta("nornicdb_microbatch_batch_size_count")
        by_5s = [0] * (int(run.seconds // 5) + 1)
        for r in in_time:
            by_5s[min(int((r.t_done - t_open) // 5), len(by_5s) - 1)] += 1
        return {
            "setup_s": setup_s,
            "end_to_end": {
                "search_qps": len(in_time) / (t_close - t_open),
                "search_p95_ms": percentile(
                    [(r.t_done - r.t_send) * 1e3 for r in answered], 95.0),
            },
            "attempted": len(sent),
            "failed": len(sent) - len(answered),
            "observed": obs,
            "counts": {"sent": len(sent), "answered": len(answered)},
            "notes": {"setup_parts_s": self.parts,
                      "window_programs_compiled": self.compiles_in_window,
                      "admission": {
                          "posture": adm["posture"],
                          "interactive_wait_ms":
                              adm["lanes"]["interactive"]["wait_ms"],
                          "max_wait_ms": adm["limits"]["max_wait_ms"],
                          "shed_total": adm["shed"]["total"]},
                      "p50_ms": percentile(
                          [(r.t_done - r.t_send) * 1e3 for r in answered],
                          50.0),
                      # how steady the window was, for the reader of a
                      # run that reads far off
                      "answered_by_5s": by_5s,
                      "ramp_s_taken": t_open - min(r.t_send
                                                   for r in replies),
                      "ramp_requests_answered": sum(
                          1 for r in replies if r.t_done < t_open),
                      "riders_per_batch": obs.prom_delta(
                          "nornicdb_microbatch_batch_size_sum")
                      / batches if batches else None,
                      "gc_gen2_s_during_drive": gen2},
        }

    # -- after the window ------------------------------------------------

    def free(self) -> None:
        """Stop the server and drop the program's state (device matrix
        and host mirror) before the reference runs."""
        if self.http is not None:
            self.http.stop()
            self.http = None
        if self.db is not None:
            self.db.close()
            self.db = None
        gc.collect()

    def verify(self) -> List[Any]:
        run = self.run
        limits = run.size("limits")
        reference = loader.load_reference(run.config, run.root)
        pool = [r for r in self.replies
                if r.ok and self.t_open <= r.t_send < self.t_close]
        n = min(int(run.mix("checked")), len(pool))
        rng = np.random.default_rng([self.traffic_seed, 4])
        sample = [pool[i] for i in rng.choice(len(pool), n, replace=False)]
        queries = np.stack([r.kept[0] for r in sample])
        if run.control == "reference_high":
            ids, scores = reference.control_answers(
                self.vectors, queries, self.limit)
        elif run.control is None:
            ids = [r.kept[1] for r in sample]
            scores = [r.kept[2] for r in sample]
        else:
            raise ValueError(f"control {run.control!r}")
        malformed = 0
        for i, r in enumerate(sample):
            a, s = ids[i], scores[i]
            if (len(a) != self.limit or len(set(a.tolist())) != len(a)
                    or a.min() < 0 or a.max() >= self.rows
                    or np.any(np.diff(s) > 0) or not r.kept[3]):
                malformed += 1
                ids[i] = np.clip(a, 0, self.rows - 1)
        t = time.time()
        read = reference.judge(self.vectors, queries, ids, scores,
                               self.limit)
        self.parts["reference_s"] = time.time() - t
        unwell = sum(1 for r in pool if not r.kept[3])
        return [
            Check("score_err_rms", read["score_err_rms"],
                  limits["score_err_rms"]),
            Check("score_err_max", read["score_err"],
                  limits["score_err_max"]),
            Check("rank_gap_max", read["rank_gap"], limits["rank_gap_max"]),
            Check("answers_malformed", malformed + unwell, 0),
            Check("window_compiles", self.compiles_in_window, 0),
        ]
