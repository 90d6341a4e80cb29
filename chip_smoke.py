#!/usr/bin/env python3
"""chip_smoke.py — quickest proof that the system still starts on the chip.

One process owns the chip. It builds the objects ``cli serve`` builds — a
database opened over a data directory with the full-width bge-m3-shaped
encoder as its embedder, and the HTTP server in front of it — and drives
them over the loopback socket with a client that never touches JAX:

  0. device     platform, device kind, JAX/libtpu versions, compile cache,
                both native libraries built from source
  1. ingest     POST /nornicdb/store bursts -> embed queue -> encoder -> index
  2. fill       seeded vectors with text until the index sits in the device
                window (above the host-numpy floor, below the HNSW threshold)
  3. serve      /nornicdb/search, vector and hybrid: a warm-up that compiles
                every batch bucket, then one driven window (b=1, then every
                client at once) in which nothing may compile, be refused or
                come from the host; every answer checked against a host
                NumPy top-k over the same vectors
  4. surface    one Cypher statement, PageRank through its procedure, one
                /v1/chat/completions
  5. kernels    both Pallas kernels compiled for real against their XLA
                references

Every check is a hard failure. The exit code is 0 only if every check held,
and only then is the last line of standard output the result object. The
script never sets ``JAX_PLATFORMS``: with no accelerator it fails at phase 0.

    python3 chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# What "exact" means on the chip. The brute tier's matmul runs at
# Precision.HIGHEST (ops/similarity.EXACT): float32 in, float32 arithmetic.
# Against a float64 host reference a returned cosine may then differ by
# float32 rounding over a 1024-term dot product, and two candidates closer
# than that may swap. Both bounds are stated here, not tuned per run.
SCORE_TOL = 1e-5
RECALL_FLOOR = 0.99
TOP_K = 10
DEADLINE_MS = "600000"  # cold compiles sit inside the first requests
QUERY_WORDS = 8
# Riders queued behind a bucket's first compile read as overload to the
# admission controller, which then answers 429 until the wait it measured
# decays (ROADMAP S8). The warm-up waits for ``admit`` before each burst,
# and both clean chip runs of PR 21 saw no refusal at all; without that
# wait one warm-up took 499. More than one burst's worth fails the run.
WARM_SHED_MAX = 32
# how long the warm-up lets a batch leader wait for the rest of its burst
GATHER_S = 2.0


class SmokeFailure(Exception):
    """A phase check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass
class SmokeConfig:
    """Sizes of one run. ``full()`` is what ``python chip_smoke.py`` drives;
    the CPU rehearsal in tests/test_chip_smoke.py shrinks it."""

    platform: str
    encoder: Callable[[], Any]
    # (documents, fewest tokens, most tokens) per ingest burst
    bursts: Sequence[Tuple[int, int, int]]
    index_rows: int
    n_queries: int
    burst_clients: int
    graph_edges: int
    # every query word is indexed in exactly this many documents (a power
    # of two), so a sealed batch's lexical plan widths follow from its
    # pow2 batch bucket alone and the warm-up can compile all of them
    query_df: int
    topk_shape: Tuple[int, int]          # (rows, dims) of the fused top-k
    topk_batches: Sequence[int]
    flash_shape: Tuple[int, int, int, int]
    pallas_interpret: bool
    seed: int = 0

    @staticmethod
    def full() -> "SmokeConfig":
        from nornicdb_tpu.models.encoder import EncoderConfig

        return SmokeConfig(
            platform="tpu",
            encoder=EncoderConfig.bge_m3_like,
            bursts=((96, 10, 30), (96, 40, 120), (96, 300, 500)),
            index_rows=8192,
            n_queries=256,
            burst_clients=32,
            graph_edges=1024,
            query_df=32,
            topk_shape=(8192, 1024),
            topk_batches=(8, 64, 256),
            flash_shape=(8, 512, 16, 64),
            pallas_interpret=False,
        )


# -- the client: sockets and JSON only ---------------------------------------


class Client:
    """Loopback HTTP client, one keep-alive connection per thread."""

    def __init__(self, port: int):
        self._port = port
        self._tls = threading.local()

    def _conn(self) -> http.client.HTTPConnection:
        c = getattr(self._tls, "conn", None)
        if c is None:
            c = http.client.HTTPConnection("127.0.0.1", self._port,
                                           timeout=900)
            self._tls.conn = c
        return c

    def request(self, method: str, path: str,
                body: Optional[Dict[str, Any]] = None) -> Tuple[int, Any]:
        data = json.dumps(body).encode() if body is not None else None
        headers = {"Content-Type": "application/json",
                   "X-Nornic-Deadline-Ms": DEADLINE_MS}
        conn = self._conn()
        try:
            conn.request(method, path, body=data, headers=headers)
            resp = conn.getresponse()
            raw = resp.read()
        except (OSError, http.client.HTTPException):
            conn.close()
            self._tls.conn = None
            raise
        return resp.status, (json.loads(raw) if raw else None)

    def post(self, path: str, body: Dict[str, Any]) -> Tuple[int, Any]:
        return self.request("POST", path, body)


def _parallel(fn: Callable[[Any], Any], items: Sequence[Any],
              workers: int) -> List[Any]:
    """Map over client threads; every future's result is read, so one
    failed request fails the phase."""
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return [f.result() for f in [pool.submit(fn, it) for it in items]]


# -- compile accounting ------------------------------------------------------


class CompileMeter:
    """What JAX reports about compilation in this process: seconds spent
    tracing, lowering and in the backend compiler (a persistent-cache hit
    is charged its retrieval time), summed over threads; the wall-clock
    intervals those seconds covered; and cache hits and misses."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._intervals: List[Tuple[float, float]] = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in self._DURATIONS:
            now = time.time()  # the event is reported as its section ends
            with self._lock:
                self.seconds += duration
                self._intervals.append((now - duration, now))
                if event == self._DURATIONS[-1]:
                    self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def wall_between(self, t0: float, t1: float) -> float:
        """Wall-clock seconds of [t0, t1] in which some thread was
        compiling: the union of the reported intervals, so concurrent and
        nested sections are not counted twice."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1))
                           for a, b in self._intervals if b > t0 and a < t1)
        total, end = 0.0, t0
        for a, b in spans:
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compile_s": round(self.seconds, 2),
                    "programs": self.programs,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


# -- run state ---------------------------------------------------------------


class Run:
    """Everything the phases share. ``close()`` stops what was started."""

    def __init__(self, cfg: SmokeConfig):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self.meter: Optional[CompileMeter] = None
        self.device: Dict[str, Any] = {}
        self.cache_dir = ""
        self.data_dir = ""
        self.db = None
        self.http = None
        self.client: Optional[Client] = None
        self.embedder = None
        self.dims = 0
        self.doc_ids: List[str] = []
        # reference copy of what the index must hold: id -> float32 vector
        self.vectors: Dict[str, np.ndarray] = {}
        self.queries: List[str] = []
        self.query_vecs: Optional[np.ndarray] = None
        self.plants: Dict[int, str] = {}
        self._next_query = 0
        self.warm_sheds = 0
        self.report: List[Dict[str, Any]] = []

    def fresh_queries(self, n: int) -> List[int]:
        """Indices of ``n`` queries no request has used yet (a repeated
        query would be answered from the result cache, not the index)."""
        check(self._next_query + n <= len(self.queries),
              "query pool exhausted")
        out = list(range(self._next_query, self._next_query + n))
        self._next_query += n
        return out

    def close(self) -> None:
        if self.http is not None:
            self.http.stop()
            self.http = None
        if self.db is not None:
            self.db.close()
            self.db = None
        if self.data_dir:
            shutil.rmtree(self.data_dir, ignore_errors=True)
            self.data_dir = ""


_WORDS = [f"w{i}" for i in range(4000)]


def _text(rng: np.random.Generator, lo: int, hi: int) -> str:
    n = int(rng.integers(lo, hi + 1))
    return " ".join(_WORDS[int(i)] for i in rng.integers(0, len(_WORDS), n))


# -- phase 0: device, cache, native libraries --------------------------------


def phase_device(run: Run) -> Dict[str, Any]:
    from nornicdb_tpu.jaxenv import ensure_compile_cache

    run.cache_dir = ensure_compile_cache()
    import jax
    import jaxlib

    dev = jax.devices()[0]
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    check(dev.platform == run.cfg.platform,
          f"JAX found platform {dev.platform!r}, need {run.cfg.platform!r}")
    run.meter = CompileMeter()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    return {"device": run.device, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
            "compile_cache_dir": run.cache_dir,
            "compile_cache_from_env":
                bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}


def phase_native(run: Run) -> Dict[str, Any]:
    """Both native libraries build from the committed sources on first
    use. A library that is missing, stale or loaded from an older build
    fails here instead of quietly dropping callers to the Python paths."""
    from nornicdb_tpu._native import load_build_module
    from nornicdb_tpu.search import hnsw_native
    from nornicdb_tpu.storage import disk

    out: Dict[str, Any] = {}
    for script in ("build.py", "build_hnsw.py"):
        mod = load_build_module(script)
        so = mod.build()
        with open(mod.STAMP, encoding="utf-8") as f:
            stamp = f.read().split()
        check(os.path.exists(so) and stamp[:1] == [mod._src_hash()],
              f"{os.path.basename(so)} was not built from {mod.SRC}")
        out[os.path.basename(so)] = "built from source"
    check(disk.native_available(), "native kv library does not load")
    check(hnsw_native.get_lib() is not None,
          "native HNSW library does not load")
    out["hnsw"] = "native"
    return out


# -- phase 1: ingest through the encoder -------------------------------------


def phase_ingest(run: Run) -> Dict[str, Any]:
    import jax

    import nornicdb_tpu
    from nornicdb_tpu.api.http_server import HttpServer
    from nornicdb_tpu.embed.embedder import CachedEmbedder, \
        JaxEncoderEmbedder

    cfg = run.cfg
    t0 = time.time()
    inner = JaxEncoderEmbedder(cfg=cfg.encoder(), seed=cfg.seed)
    jax.block_until_ready(inner.params)
    init_s = time.time() - t0
    run.embedder = inner
    run.dims = inner.dims
    # the same objects cli.cmd_serve builds: open(data_dir) + HttpServer,
    # the embedder behind the LRU db._default_embedder puts in front of it
    run.data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    run.db = nornicdb_tpu.open(run.data_dir,
                               embedder=CachedEmbedder(inner))
    engine = type(run.db._base).__name__
    check(engine == "DiskEngine",
          f"open(data_dir) chose {engine}, not the native DiskEngine")
    run.http = HttpServer(run.db, port=0).start()
    run.client = Client(run.http.port)
    status, body = run.client.request("GET", "/health")
    check(status == 200, f"/health answered {status}: {body}")

    dev = jax.devices()[0]
    leaves = jax.tree_util.tree_leaves(inner.params)
    check(all(isinstance(x, jax.Array) and x.devices() == {dev}
              for x in leaves),
          "encoder parameters are not jax.Arrays on the device")

    queue = run.db._embed_queue
    compile_before = run.meter.snapshot()["compile_s"]
    t_ingest = time.time()
    for b, (count, lo, hi) in enumerate(cfg.bursts):
        docs = []
        for _ in range(count):
            i = len(run.doc_ids)
            run.doc_ids.append(f"doc-{i}")
            docs.append({"id": f"doc-{i}", "content": _text(run.rng, lo, hi),
                         "labels": ["Doc"], "properties": {"idx": i}})

        def store(doc):
            status, body = run.client.post("/nornicdb/store", doc)
            check(status == 201, f"store answered {status}: {body}")

        _parallel(store, docs, workers=8)
        queue.drain(timeout_s=600.0)
        check(queue.failed_count == 0,
              f"embed queue failed {queue.failed_count} documents in "
              f"burst {b}")
        check(queue.embedded_count == len(run.doc_ids),
              f"burst {b}: {queue.embedded_count} embedded of "
              f"{len(run.doc_ids)} sent")
    t_done = time.time()
    wall = t_done - t_ingest
    compile_s = run.meter.snapshot()["compile_s"] - compile_before
    compile_wall_s = run.meter.wall_between(t_ingest, t_done)

    for nid in run.doc_ids:
        vec = np.asarray(run.db.storage.get_node(nid).embedding, np.float32)
        check(vec.shape == (run.dims,), f"{nid}: embedding shape {vec.shape}")
        check(bool(np.isfinite(vec).all()), f"{nid}: non-finite embedding")
        check(abs(float(np.linalg.norm(vec)) - 1.0) < 1e-3,
              f"{nid}: embedding norm {np.linalg.norm(vec)}")
        run.vectors[nid] = vec

    out = inner._jit(inner.params, np.ones((1, 16), np.int32))
    check(isinstance(out, jax.Array) and out.devices() == {dev},
          "encoder output is not a jax.Array on the device")

    # the bucketing's promise: batch and width both on the pow2 ladder,
    # batch no larger than the queue hands over, width no wider than the
    # longest document class
    max_b = queue.batch_size
    widths = {inner._bucket_width(hi + 2) for _, _, hi in cfg.bursts}
    ladder = {(b, w) for b in _pow2_between(1, max_b)
              for w in _pow2_between(16, max(widths))}
    shapes = sorted(inner.shapes)
    check(set(shapes) <= ladder,
          f"compiled shapes off the pow2 ladder: "
          f"{sorted(set(shapes) - ladder)}")
    return {"engine": engine, "documents": len(run.doc_ids),
            "embedded": queue.embedded_count, "failed": queue.failed_count,
            "dims": run.dims, "params_init_s": round(init_s, 2),
            "wall_s": round(wall, 2), "compile_s": round(compile_s, 2),
            # compile_s adds threads up; the wall clock splits into the
            # time some thread was compiling and the rest
            "compile_wall_s": round(compile_wall_s, 2),
            "steady_s": round(wall - compile_wall_s, 2),
            "compiled_shapes": [list(s) for s in shapes],
            "ladder_size": len(ladder)}


def _pow2_between(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


# -- phase 2: fill the index into the device window --------------------------


def phase_fill(run: Run) -> Dict[str, Any]:
    cfg = run.cfg
    t0 = time.time()
    # every query is QUERY_WORDS words of its own that no other text uses
    # unless this phase puts them there, cfg.query_df documents each. The
    # fused hybrid program compiles per pow2 (batch, unique terms,
    # postings); with a fixed count of words and of postings per word the
    # last two follow from the first, whichever riders a batch seals.
    run.queries = [" ".join(f"q{j}x{i}" for i in range(QUERY_WORDS))
                   for j in range(cfg.n_queries)]
    # query vectors through the encoder, over the wire: the client needs
    # them for the reference, and the LRU then holds them, so a burst of
    # searches reaches the batcher together instead of queueing one by
    # one behind the encoder
    vecs: List[List[float]] = []
    for start in range(0, cfg.n_queries, 64):
        status, body = run.client.post(
            "/nornicdb/embed", {"texts": run.queries[start:start + 64]})
        check(status == 200, f"/nornicdb/embed answered {status}: {body}")
        vecs.extend(body["embeddings"])
    run.query_vecs = np.asarray(vecs, np.float32)
    check(run.query_vecs.shape == (cfg.n_queries, run.dims)
          and bool(np.isfinite(run.query_vecs).all()),
          f"query embeddings {run.query_vecs.shape}")

    # the first burst_clients queries are kept for the driven window, each
    # with its nearest neighbour planted: its own vector, its own words
    n_plants = cfg.burst_clients
    run._next_query = n_plants
    n_seeded = cfg.index_rows - len(run.doc_ids) - n_plants
    check(n_seeded >= cfg.query_df,
          "index_rows leaves no room for the seeded vectors")
    texts = [_text(run.rng, 8, 24) for _ in range(n_seeded)]
    for j, query in enumerate(run.queries):
        copies = cfg.query_df - (1 if j < n_plants else 0)
        for word in query.split():
            for i in run.rng.choice(n_seeded, copies, replace=False):
                texts[i] += " " + word
    rows = []
    for j in range(n_plants):
        nid = f"plant-{j}"
        run.plants[j] = nid
        rows.append((nid, run.query_vecs[j], run.queries[j]))
    for i in range(n_seeded):
        # short decimal components: a 1024-wide vector stays a few KB of
        # JSON, and the server's float32 parse is exact
        vec = (np.round(run.rng.standard_normal(run.dims) * 32.0) / 32.0
               ).astype(np.float32)
        rows.append((f"vec-{i}", vec, texts[i]))

    def store(row):
        nid, vec, text = row
        status, body = run.client.post("/nornicdb/store", {
            "id": nid, "content": text, "labels": ["Vec"],
            "embedding": [float(x) for x in vec]})
        check(status == 201, f"store answered {status}: {body}")

    _parallel(store, rows, workers=8)
    run.vectors.update((nid, vec) for nid, vec, _ in rows)
    search = run.db.search
    check(len(search.vectors) == cfg.index_rows,
          f"vector index holds {len(search.vectors)} rows, "
          f"need {cfg.index_rows}")
    check(len(search.bm25) == cfg.index_rows,
          f"lexical index holds {len(search.bm25)} docs, "
          f"need {cfg.index_rows}")
    words = [w for q in run.queries for w in q.split()]
    dfs = search.bm25.term_stats(words)[0]
    off = {w: df for w, df in dfs.items() if df != cfg.query_df}
    check(not off, f"query words not in exactly {cfg.query_df} documents: "
          f"{dict(list(off.items())[:5])}")
    return {"rows": len(search.vectors), "lexical_docs": len(search.bm25),
            "seeded": n_seeded, "planted": n_plants,
            "queries_embedded": cfg.n_queries, "query_df": cfg.query_df,
            "wall_s": round(time.time() - t0, 2)}


# -- phase 3: serve ----------------------------------------------------------


class Reference:
    """Host NumPy top-k over the vectors the index was given."""

    def __init__(self, vectors: Dict[str, np.ndarray]):
        self.ids = list(vectors)
        m = np.stack([vectors[i] for i in self.ids]).astype(np.float64)
        self.matrix = m / np.linalg.norm(m, axis=1, keepdims=True)
        self.row = {nid: r for r, nid in enumerate(self.ids)}

    def scores(self, q: np.ndarray) -> np.ndarray:
        q = q.astype(np.float64)
        return self.matrix @ (q / np.linalg.norm(q))


def _search(run: Run, qi: int, mode: str,
            patient: bool = False) -> Dict[str, Any]:
    """One /nornicdb/search. ``patient`` is the warm-up's client: refused
    with 429 it comes back after the second it was told to wait, and the
    run fails once WARM_SHED_MAX refusals have been counted."""
    request = {"query": run.queries[qi], "mode": mode, "limit": TOP_K}
    status, body = run.client.post("/nornicdb/search", request)
    while patient and status == 429:
        run.warm_sheds += 1
        check(run.warm_sheds <= WARM_SHED_MAX,
              f"warm-up was refused (429) more than {WARM_SHED_MAX} times")
        time.sleep(1.0)
        status, body = run.client.post("/nornicdb/search", request)
    check(status == 200, f"search({mode}) answered {status}: {body}")
    hits = body["results"]
    check(len(hits) == TOP_K,
          f"search({mode}) returned {len(hits)} hits, need {TOP_K}")
    return {"qi": qi, "mode": mode, "hits": hits}


def _judge(run: Run, ref: Reference, answers: List[Dict[str, Any]]
           ) -> Dict[str, float]:
    """Agreement with the host reference. Vector answers: every returned
    cosine within SCORE_TOL of the reference's for that id, tie-aware
    recall@10 (an id counts when its true score is within SCORE_TOL of
    the true 10th), planted neighbour first. Hybrid answers carry the
    fused ranking, so only the cosines and the plant are judged."""
    recalls = []
    worst = 0.0
    for a in answers:
        truth = ref.scores(run.query_vecs[a["qi"]])
        ids = [h["id"] for h in a["hits"]]
        for h in a["hits"]:
            got = h.get("vector_score") if a["mode"] == "hybrid" \
                else h["score"]
            if got is None:
                continue  # a hybrid hit only the lexical list returned
            err = abs(float(got) - float(truth[ref.row[h["id"]]]))
            worst = max(worst, err)
            check(err <= SCORE_TOL,
                  f"query {a['qi']} ({a['mode']}): {h['id']} scored "
                  f"{got}, reference {truth[ref.row[h['id']]]}")
        plant = run.plants.get(a["qi"])
        if plant is not None:
            check(ids[0] == plant,
                  f"query {a['qi']} ({a['mode']}): planted neighbour "
                  f"{plant} not first: {ids[:3]}")
        if a["mode"] == "vector":
            kth = np.partition(truth, -TOP_K)[-TOP_K]
            good = sum(1 for i in ids
                       if truth[ref.row[i]] >= kth - SCORE_TOL)
            recalls.append(good / TOP_K)
    recall = float(np.mean(recalls)) if recalls else 1.0
    check(recall >= RECALL_FLOOR,
          f"recall@{TOP_K} {recall:.4f} below {RECALL_FLOOR}")
    return {"recall_at_10": round(recall, 4), "max_score_err": worst}


def _burst(run: Run, qis: Sequence[int], mode: str,
           patient: bool = False) -> List[Dict[str, Any]]:
    """All of ``qis`` at once, one client thread each."""
    gate = threading.Barrier(len(qis))

    def one(qi):
        gate.wait(timeout=60)
        return _search(run, qi, mode, patient=patient)

    return _parallel(one, qis, workers=len(qis))


def _served_delta(before: Dict[str, float], after: Dict[str, float]
                  ) -> Dict[str, int]:
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v - before.get(k, 0) > 0}


def _wait_admit(timeout_s: float = 120.0) -> None:
    """Wait until the admission controller admits again. Riders queued
    behind a compile read as overload to it; the wait it measured halves
    per quiet second."""
    from nornicdb_tpu import admission

    deadline = time.time() + timeout_s
    while admission.CONTROLLER.refresh(force=True) != "admit":
        check(time.time() < deadline,
              f"admission posture did not return to 'admit' in "
              f"{timeout_s:.0f} s")
        time.sleep(0.25)


def _drive_window(run: Run, qis: Sequence[int]) -> List[Dict[str, Any]]:
    """The traffic of the driven window over the planted queries: two
    requests one at a time, then every remaining client at once, in each
    mode. No request is retried; the first that fails ends the run."""
    answers: List[Dict[str, Any]] = []
    for mode in ("vector", "hybrid"):
        for qi in qis[:2]:
            answers.append(_search(run, qi, mode))
    for mode in ("vector", "hybrid"):
        answers.extend(_burst(run, qis[2:], mode))
    return answers


def _dispatched(kind: str, bucket: int) -> bool:
    """Has the server dispatched ``kind`` at this batch bucket yet?"""
    from nornicdb_tpu import obs

    return any(e["kind"] == kind and e["b"] == bucket
               for e in obs.compile_universe())


def _warm_ladder(run: Run, batcher: Any, mode: str,
                 kind: str) -> List[Dict[str, Any]]:
    """One sealed batch in ``mode`` for every pow2 bucket a burst of
    burst_clients can seal, so each is compiled before the driven window.

    A batcher seals whatever has arrived when its leader looks, so which
    buckets a server has compiled is a matter of timing, and the server
    does not compile its ladder ahead of traffic (ROADMAP S8). Until it
    does, the warm-up steers the seal: it holds the batcher's gather
    window open and tells it the last batch had ``bucket`` riders, and
    the leader then waits for that many. The requests still come over
    the socket; the driven window runs with the window as shipped."""
    answers: List[Dict[str, Any]] = []
    shipped = batcher._gather_window_s
    batcher._gather_window_s = GATHER_S
    try:
        for bucket in _pow2_between(1, run.cfg.burst_clients):
            _wait_admit()
            batcher._last_batch = bucket
            answers.extend(_burst(run, run.fresh_queries(bucket), mode,
                                  patient=True))
            check(_dispatched(kind, bucket),
                  f"a {mode} burst of {bucket} did not seal as one batch")
    finally:
        batcher._gather_window_s = shipped
    return answers


def phase_serve(run: Run) -> Dict[str, Any]:
    import jax

    from nornicdb_tpu import obs
    from nornicdb_tpu.ops.similarity import pad_dim

    cfg = run.cfg
    search = run.db.search
    ref = Reference(run.vectors)
    t0 = time.time()
    compile_before = run.meter.snapshot()["compile_s"]

    # warm-up. The first requests pay the index ship and the compiles; the
    # first hybrid request starts the lexical snapshot's background build
    # and is served by the host path while it runs. Then every batch
    # bucket, in each mode: the vector and hybrid batchers record the
    # same "microbatch" kind, so the vector ladder goes first, while that
    # kind's buckets above 1 are its alone. With phase_fill's query words
    # the plan widths follow from the bucket, so this is every program
    # the driven window can need.
    answers = [_search(run, qi, "vector", patient=True)
               for qi in run.fresh_queries(4)]
    answers.append(_search(run, run.fresh_queries(1)[0], "hybrid",
                           patient=True))
    deadline = time.time() + 300
    while not (search._fused is not None and search._fused.ready):
        check(time.time() < deadline,
              "fused hybrid snapshot not built within 300 s")
        time.sleep(0.05)
    answers.extend(_warm_ladder(run, search._microbatch, "vector",
                                "microbatch"))
    answers.extend(_warm_ladder(run, search._hybrid_batch, "hybrid",
                                "hybrid_fused"))
    warm = _judge(run, ref, answers)
    warm_s = time.time() - t0
    warm_compile_s = run.meter.snapshot()["compile_s"] - compile_before

    # the driven window, once: nothing in it may compile, be refused,
    # degrade on an error, or be answered from the host
    _wait_admit()
    tiers0 = obs.audit.tier_counts()
    errors0 = obs.audit.degrade_summary()["by_reason"].get("error", 0)
    recompiles0 = obs.calibration_summary()["unexpected_recompiles"]
    meter0 = run.meter.snapshot()
    t1 = time.time()
    driven = _drive_window(run, list(range(cfg.burst_clients)))
    driven_s = time.time() - t1
    meter1 = run.meter.snapshot()
    compiled = meter1["programs"] - meter0["programs"]
    check(compiled == 0,
          f"the driven window compiled {compiled} programs "
          f"({meter1['compile_s'] - meter0['compile_s']:.1f} s)")
    verdict = _judge(run, ref, driven)
    served = _served_delta(tiers0, obs.audit.tier_counts())
    errors = obs.audit.degrade_summary()["by_reason"].get("error", 0) \
        - errors0
    recompiles = obs.calibration_summary()["unexpected_recompiles"] \
        - recompiles0

    dev = jax.devices()[0]
    matrix = search.vectors._dev_matrix
    check(isinstance(matrix, jax.Array) and matrix.devices() == {dev}
          and matrix.shape == (pad_dim(cfg.index_rows), run.dims),
          f"brute index device matrix: {type(matrix).__name__} "
          f"{getattr(matrix, 'shape', None)}")
    host = {k: v for k, v in served.items() if k.endswith(":host")}
    check(not host, f"driven requests served by a host tier: {host}")
    check(served.get("vector:vector_brute_f32", 0) == cfg.burst_clients,
          f"vector requests not all counted on the brute device tier: "
          f"{served}")
    fused = {k: v for k, v in served.items()
             if k.startswith("hybrid:hybrid_")}
    check(sum(fused.values()) == cfg.burst_clients,
          f"hybrid requests not all counted on a fused device tier: "
          f"{served}")
    check(errors == 0, f"{errors} 'error' degrades in the driven window: "
          f"{obs.audit.degrade_snapshot(5)}")
    check(recompiles == 0,
          f"{recompiles} unexpected recompiles in the driven window")
    return {"warm": warm, "driven": verdict, "served": served,
            "requests": {"warm": len(answers), "driven": len(driven),
                         "warm_shed_429": run.warm_sheds},
            "warm_s": round(warm_s, 2),
            "warm_compile_s": round(warm_compile_s, 2),
            "driven_s": round(driven_s, 2),
            "driven_programs_compiled": compiled,
            "microbatch": search.microbatch_stats(),
            "compile_universe": obs.compile_universe()}


# -- phase 4: the rest of one request's surface ------------------------------


def phase_surface(run: Run) -> Dict[str, Any]:
    import jax

    from nornicdb_tpu.ops import graph as graph_ops

    cfg = run.cfg
    n_docs = len(run.doc_ids)
    _wait_admit()

    def cypher(statement: str, params: Optional[Dict[str, Any]] = None):
        status, body = run.client.post("/db/neo4j/tx/commit", {
            "statements": [{"statement": statement,
                            "parameters": params or {}}]})
        check(status == 200 and not body["errors"],
              f"cypher answered {status}: {body}")
        return [r["row"] for r in body["results"][0]["data"]]

    rows = cypher("MATCH (n:Doc) RETURN count(n) AS c")
    check(rows == [[n_docs]], f"MATCH (n:Doc) counted {rows}, not {n_docs}")

    pairs = {(int(a), int(b)) for a, b in
             run.rng.integers(0, n_docs, (cfg.graph_edges, 2)) if a != b}
    rows = cypher(
        "UNWIND $pairs AS p MATCH (a:Doc {idx: p[0]}), (b:Doc {idx: p[1]}) "
        "CREATE (a)-[:CITES]->(b) RETURN count(*) AS c",
        {"pairs": [list(p) for p in sorted(pairs)]})
    check(rows == [[len(pairs)]], f"created {rows} of {len(pairs)} edges")
    top = min(20, n_docs // 2)
    programs_before = graph_ops._pagerank_impl._cache_size()
    ranked = cypher(
        "CALL apoc.algo.pageRank() YIELD node, score "
        f"RETURN node.idx AS idx, score ORDER BY score DESC LIMIT {top}")
    src, dst, ids = graph_ops.graph_snapshot(run.db.storage)
    host = dict(zip(ids, graph_ops._pagerank_host(
        src, dst, len(ids), 20, 0.85)))
    check(len(ranked) == top, f"PageRank returned {len(ranked)} rows")
    for idx, score in ranked:
        check(idx is not None, "PageRank ranked an unlinked node first")
        want = float(host[f"doc-{idx}"])
        check(abs(score - want) <= 1e-4 * want,
              f"PageRank node {idx}: {score} vs host {want}")
    # the device program runs wherever the backend is an accelerator, and
    # only there (counted as this call's growth: under the tests another
    # file of the same process may have compiled the program before)
    device_arm = graph_ops._pagerank_impl._cache_size() > programs_before
    check(device_arm == (jax.default_backend() != "cpu"),
          f"PageRank device arm ran={device_arm} on "
          f"{jax.default_backend()}")

    t0 = time.time()
    status, body = run.client.post("/v1/chat/completions", {
        "messages": [{"role": "user", "content": "status?"}],
        "max_tokens": 8})
    check(status == 200, f"chat answered {status}: {body}")
    text = body["choices"][0]["message"]["content"]
    check(isinstance(text, str) and len(text) > 0, f"chat text {text!r}")
    status, body = run.client.request("GET", "/heimdall/models")
    check(status == 200 and any(m["loaded"] for m in body["models"]),
          f"no Heimdall model loaded: {body}")
    return {"cypher_count": n_docs, "pagerank_edges": len(pairs),
            "pagerank_device_arm": device_arm,
            "pagerank_top": ranked[0],
            "chat_model": body["models"][0]["name"],
            "chat_s": round(time.time() - t0, 2)}


# -- phase 5: kernels --------------------------------------------------------


def phase_kernels(run: Run) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ops.pallas_attention import (
        flash_attention,
        reference_attention,
    )
    from nornicdb_tpu.ops.pallas_topk import fused_cosine_topk
    from nornicdb_tpu.ops.similarity import cosine_topk, l2_normalize

    cfg = run.cfg
    interpret = cfg.pallas_interpret
    rows, dims = cfg.topk_shape
    matrix = l2_normalize(jnp.asarray(
        run.rng.standard_normal((rows, dims)), jnp.float32))
    valid = jnp.ones((rows,), bool).at[rows // 3].set(False)
    out: Dict[str, Any] = {"interpret": interpret, "topk": {}}
    for b in cfg.topk_batches:
        q = l2_normalize(jnp.asarray(
            run.rng.standard_normal((b, dims)), jnp.float32))
        s, i = fused_cosine_topk(q, matrix, valid, TOP_K,
                                 interpret=interpret)
        rs, ri = cosine_topk(q, matrix, valid, TOP_K)
        s, i, rs, ri = (np.asarray(x) for x in (s, i, rs, ri))
        err = float(np.max(np.abs(s - rs)))
        # same ids wherever the scores are not a float32-rounding tie
        differ = (i != ri) & (np.abs(s - rs) > SCORE_TOL)
        check(err <= SCORE_TOL and not differ.any(),
              f"fused top-k B={b}: max score diff {err}, "
              f"{int(differ.sum())} ids differ from cosine_topk")
        out["topk"][str(b)] = {"max_score_diff": err}

    shape = cfg.flash_shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(cfg.seed), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    lens = np.maximum(1, (np.arange(shape[0]) + 1) * shape[1] // shape[0])
    mask = jnp.asarray(np.arange(shape[1])[None, :] < lens[:, None])
    got = flash_attention(q, k, v, mask, interpret=interpret)
    want = reference_attention(q, k, v, mask)
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    # bfloat16 outputs: two units in the last place, at any magnitude
    excess = np.abs(got - want) - 2.0 ** -6 * (1.0 + np.abs(want))
    check(bool(np.isfinite(got).all()) and float(excess.max()) <= 0.0,
          f"flash attention {shape}: max diff "
          f"{float(np.abs(got - want).max())} from the reference")
    out["flash"] = {"shape": list(shape),
                    "max_diff": float(np.abs(got - want).max())}
    return out


# -- driver ------------------------------------------------------------------

PHASES: Sequence[Tuple[str, Callable[[Run], Dict[str, Any]]]] = (
    ("device", phase_device),
    ("native", phase_native),
    ("ingest", phase_ingest),
    ("fill", phase_fill),
    ("serve", phase_serve),
    ("surface", phase_surface),
    ("kernels", phase_kernels),
)


def run_smoke(cfg: SmokeConfig, out=None) -> Run:
    """Run every phase in order; the first failed check ends the run.
    Returns the run (closed) for its report; raises on failure."""
    out = out or sys.stdout
    run = Run(cfg)
    t_start = time.time()
    try:
        for name, phase in PHASES:
            t0 = time.time()
            before = run.meter.snapshot() if run.meter else None
            try:
                doc = phase(run)
            except Exception as exc:
                print(f"chip_smoke: phase {name} FAILED: "
                      f"{type(exc).__name__}: {exc}", file=out, flush=True)
                raise
            doc = {"phase": name, "ok": True,
                   "phase_wall_s": round(time.time() - t0, 2), **doc}
            if before is not None:
                doc["phase_compile_s"] = round(
                    run.meter.snapshot()["compile_s"]
                    - before["compile_s"], 2)
            run.report.append(doc)
            print(json.dumps(doc), file=out, flush=True)
        summary = {"summary": "chip_smoke", "ok": True,
                   "wall_s": round(time.time() - t_start, 2),
                   **run.meter.snapshot(),
                   "compile_cache_dir": run.cache_dir,
                   "claim": None}
        print(json.dumps(summary), file=out, flush=True)
    finally:
        run.close()
    return run


def main() -> int:
    try:
        run = run_smoke(SmokeConfig.full())
    except Exception:  # noqa: BLE001 — the process edge: report and fail
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": run.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
