"""System under test: the database with the full-width encoder as its
embedder (``nornicdb_tpu.open(data_dir, embedder=CachedEmbedder(
JaxEncoderEmbedder(cfg, params)))`` + ``HttpServer``, what ``cli serve``
builds), fed through ``POST /nornicdb/store``.

The benchmark makes the parameters from the seed (the reference file's
``make_params``) and hands them to the program's embedder. A delegating
embedder wrapped round the program's counts the tokens of the texts it is
handed, and a tap on the embedder's jitted forward records the shape of
every array the device is given: padding is read from what ran, not from a
model of the program's buckets. At the start of the ramp, before the
window, the wrapper holds the queue's worker once until the backlog is
full, so that the start's partial batches (shapes no steady import shows)
need no program of their own. A document counts as searchable when the
queue's ``on_embedded`` hook has returned for it and its vector is in
``db.search.vectors``.
"""

from __future__ import annotations

import gc
import json
import shutil
import tempfile
import threading
import time
from statistics import NormalDist
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib import loader
from benchmark.lib.check import Check
from benchmark.lib.client import Client
from benchmark.lib.costs import encoder_flops
from benchmark.lib.observed import Observed, parse_prometheus
from benchmark.lib.traffic import drive

LABEL = "Doc"
CHUNK_THRESHOLD_CHARS = 2000   # the queue's: longer texts get chunk vectors
TOPIC_WORDS = 64
UNIVERSE = 50000


def law_quantile(spec: Dict[str, Any], u: float) -> int:
    """The mix's length law at quantile ``u``, clipped to its range.
    ``lognormal`` (median, sigma) or ``histogram`` (``edges`` of n+1 lengths
    and the n ``shares`` between them, straight inside a bin), so that a
    later mix can bring a measured histogram as data alone."""
    kind = spec["distribution"]
    if kind == "lognormal":
        x = float(spec["median"]) * np.exp(
            float(spec["sigma"]) * NormalDist().inv_cdf(u))
    elif kind == "histogram":
        edges = [float(e) for e in spec["edges"]]
        cum = np.cumsum([float(w) for w in spec["shares"]])
        cum = cum / cum[-1]
        i = min(int(np.searchsorted(cum, u, side="left")), len(cum) - 1)
        lo = cum[i - 1] if i else 0.0
        x = edges[i] + (edges[i + 1] - edges[i]) * (u - lo) / (cum[i] - lo)
    else:
        raise ValueError(f"length distribution {kind!r}")
    return int(np.clip(np.rint(x), int(spec["min"]), int(spec["max"])))


def length_schedule(mix: Dict[str, Any], seed: int) -> List[int]:
    """The token lengths of the stream, in order. The stream is one block
    of ``block_docs`` documents over and over: the law's quantiles at
    (i + 1/2) / block_docs, in one order drawn from the mix's
    ``schedule_seed``, so every block is the same work and has the law's
    share of long documents. The run's seed then reorders each ``group``
    of consecutive documents (a group is one batch of the queue) in every
    block anew. A FIFO batch is as wide as its longest document, so the
    order IS the work: every seed gets the same sizes in another order as
    far as that leaves the work alone, and no further (PERF.md section 4
    has the spread an order drawn freely from the seed would give)."""
    spec = mix["lengths"]
    n, group = int(spec["block_docs"]), int(spec["group"])
    base = np.array([law_quantile(spec, (i + 0.5) / n) for i in range(n)])
    out: List[int] = []
    block = base[np.random.default_rng(
        int(spec["schedule_seed"])).permutation(n)]
    for b in range(int(spec["blocks"])):
        for g in range(0, n, group):
            part = block[g:g + group]
            out.extend(int(x) for x in part[np.random.default_rng(
                [seed, 7, b, g]).permutation(len(part))])
    return out


class CountingEmbedder:
    """Delegates to the program's embedder. Counts the tokens of the texts
    each call is handed (``texts``), and taps the embedder's jitted forward
    for the shape of every array the device is given (``shapes``). With
    ``dry`` set the tap answers zeros without running anything, which lets
    set-up ask the program itself which shape a text would take.
    ``start_hold`` holds ``embed_batch`` while it is clear (ramp only)."""

    def __init__(self, inner: Any, tokens_of: Any, max_len: int) -> None:
        if not callable(getattr(inner, "_jit", None)):
            raise RuntimeError(
                "the program's embedder has no jitted forward at `_jit`: "
                "the benchmark reads the shapes the device is given there "
                "(PERF.md section 7 lists the hooks it depends on)")
        self.inner = inner
        self.dims = inner.dims
        self._tokens_of = tokens_of
        self._max_len = max_len
        self._forward = inner._jit
        inner._jit = self._tap
        self.dry = False
        self.start_hold = threading.Event()
        self.start_hold.set()
        self.lock = threading.Lock()
        self.shapes: List[Tuple[float, int, int]] = []   # t, rows, width
        self.texts: List[Tuple[float, int]] = []         # t, tokens
        if hasattr(inner, "embed_chunks"):
            self.embed_chunks = self._embed_chunks

    def _tap(self, params: Any, ids: Any) -> Any:
        rows, width = (int(d) for d in ids.shape)
        with self.lock:
            self.shapes.append((time.perf_counter(), rows, width))
        if self.dry:
            return np.zeros((rows, self.dims), np.float32)
        return self._forward(params, ids)

    def _note(self, texts: Any) -> None:
        tokens = sum(min(self._tokens_of(t), self._max_len) for t in texts)
        with self.lock:
            self.texts.append((time.perf_counter(), tokens))

    def embed(self, text: str) -> List[float]:
        return self.embed_batch([text])[0]

    def embed_batch(self, texts):
        self.start_hold.wait()
        self._note(texts)
        return self.inner.embed_batch(texts)

    def _embed_chunks(self, text: str):
        self._note([text])
        return self.inner.embed_chunks(text)


class System:
    def __init__(self, run: Any) -> None:
        self.run = run
        self.model = {k: run.size(k) for k in (
            "vocab_size", "hidden_size", "num_layers", "num_heads",
            "mlp_dim", "max_len")}
        self.reference = loader.load_reference(run.config, run.root)
        self.schedule = length_schedule(run.traffic, run.seed)
        self.db = None
        self.http = None
        self.data_dir: Optional[str] = None
        self.params = None
        self.parts: Dict[str, float] = {}
        self.docs: Dict[int, Tuple[str, int]] = {}     # seq -> (text, tokens)
        self.searchable_at: Dict[str, float] = {}
        # when the n-th document of the stream became searchable, for
        # every n that ends a block: the window's edges lie on these
        self.block_docs = int(run.traffic["lengths"]["block_docs"])
        self.boundaries: List[float] = []
        self._stream_done = 0
        self._ramp_mark: Optional[float] = None
        self.served: Dict[int, np.ndarray] = {}
        self.compiles_in_window = 0
        self.t_open = self.t_close = 0.0
        # what the words are drawn from, and where the next window's
        # documents start; tools/read_limits.py drives several windows
        # over one set-up
        self.traffic_seed = run.seed
        self.next_index = 0
        self.index_lock = threading.Lock()
        self.windows = 0

    # -- documents -------------------------------------------------------

    def tokens_of(self, text: str) -> int:
        """Tokens the encoder sees: CLS and one a word (hash tokenizer)."""
        return 1 + text.count(" ") + 1

    def document(self, seq: int) -> Tuple[str, int]:
        """Document ``seq`` of the stream: its length from the schedule,
        its words from the run's seed, drawn from a small vocabulary of its
        own so that documents differ however long they are. The stored
        text is the content plus the label the program appends."""
        n_tokens = self.schedule[seq % len(self.schedule)]
        n_words = max(n_tokens - 2, 1)         # less CLS and the label
        rng = np.random.default_rng([self.traffic_seed, 5, seq])
        topic = rng.integers(0, UNIVERSE, TOPIC_WORDS)
        content = " ".join(f"w{w}" for w in topic[
            rng.integers(0, TOPIC_WORDS, n_words)])
        return content, n_words + 2

    # -- set-up ----------------------------------------------------------

    def _timed(self, name: str, t0: float) -> float:
        now = time.time()
        self.parts[name] = now - t0
        return now

    def setup(self) -> None:
        import jax

        run = self.run
        t = time.time()
        self.parts["imports_and_device_s"] = t - run.t_start
        import nornicdb_tpu
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.embed.embedder import CachedEmbedder, \
            JaxEncoderEmbedder
        from nornicdb_tpu.models.encoder import EncoderConfig

        self.params = self.reference.make_params(self.model, run.seed)
        jax.block_until_ready(self.params)
        t = self._timed("make_params_s", t)
        full = EncoderConfig.bge_m3_like()
        cfg = EncoderConfig(
            vocab_size=self.model["vocab_size"],
            hidden_size=self.model["hidden_size"],
            num_layers=self.model["num_layers"],
            num_heads=self.model["num_heads"],
            mlp_dim=self.model["mlp_dim"], max_len=self.model["max_len"])
        if not run.rehearse and cfg != full:
            raise RuntimeError("the configuration's sizes are not the "
                               "program's bge_m3_like()")
        inner = JaxEncoderEmbedder(cfg=cfg, params=self.params,
                                   seed=run.seed % (2 ** 31))
        want = jax.tree_util.tree_structure(jax.eval_shape(
            inner.model.init, jax.random.PRNGKey(0),
            np.ones((1, 8), np.int32))["params"])
        if jax.tree_util.tree_structure(self.params) != want:
            raise RuntimeError("the reference's parameter tree is not the "
                               "program's")
        self.counting = CountingEmbedder(inner, self.tokens_of,
                                         cfg.max_len)
        self.data_dir = tempfile.mkdtemp(prefix="bench_ingest_")
        self.db = nornicdb_tpu.open(
            self.data_dir, embedder=CachedEmbedder(self.counting))
        self.db.search                       # so that on_embedded indexes
        queue = self.db._embed_queue
        hook = queue.on_embedded

        def on_embedded(node):
            hook(node)
            now = time.perf_counter()
            self.searchable_at[node.id] = now
            if node.id.startswith("doc-"):
                self._stream_done += 1
                if self._stream_done % self.block_docs == 0:
                    self.boundaries.append(now)

        queue.on_embedded = on_embedded
        self.http = HttpServer(self.db, port=0).start()
        self.client = Client(self.http.port,
                             headers=run.config.get("request_headers"))
        t = self._timed("open_and_server_s", t)
        self._warm()
        self._timed("warm_s", t)

    def _warm(self) -> None:
        """Every shape the stream can give the device, each run once. The
        program itself says which shape a text takes: with the tap dry,
        set-up hands the embedder a batch as long as each stretch of
        ``batch`` consecutive documents of the schedule is at its longest
        (at every alignment, and with the ``reorder_slack`` longest of a
        stretch that much longer left out, since 8 connections reorder
        arrivals by a few places), and every document long enough for
        chunk vectors; the shapes seen are then compiled through the same
        jitted program the queue calls."""
        batch = int(self.db._embed_queue.batch_size)
        slack = int(self.run.mix("reorder_slack", 0))
        sched = self.schedule + self.schedule[:batch + slack]
        longest = set()
        for i in range(len(self.schedule)):
            longest.add(max(sched[i:i + batch]))
            if slack:
                longest.add(sorted(sched[i:i + batch + slack])[-1 - slack])
        counting, inner = self.counting, self.counting.inner
        examples: Dict[Tuple[int, int], Any] = {}
        counting.dry = True
        try:
            for n in sorted(longest):
                texts = [self._filler(n)] + ["w1"] * (batch - 1)
                inner.embed_batch(texts)
                examples.setdefault(counting.shapes[-1][1:], texts)
            for n in sorted(set(self.schedule)):
                text = self._filler(n)
                if len(text) > CHUNK_THRESHOLD_CHARS:
                    inner.embed_chunks(text)
                    examples.setdefault(counting.shapes[-1][1:], text)
            inner.embed_batch(["w1 " + LABEL])           # the plug
            examples.setdefault(counting.shapes[-1][1:], ["w1 " + LABEL])
        finally:
            counting.dry = False
            counting.shapes.clear()
        for shape in sorted(examples):
            what = examples[shape]
            if isinstance(what, str):
                inner.embed_chunks(what)
            else:
                inner.embed_batch(what)
        self.warmed = sorted(examples)

    @staticmethod
    def _filler(n_tokens: int) -> str:
        """A text of ``n_tokens`` tokens (CLS and one a word), as long in
        characters as a document of the stream that long."""
        return " ".join(["w10000"] * (n_tokens - 1))

    # -- the window ------------------------------------------------------

    def _make(self, k: int, seq: int) -> Tuple[str, bytes, Any]:
        # the connections take the stream's next document, whichever of
        # them is free (an importer's workers reading one corpus), so the
        # stream arrives in its own order to within the requests in flight
        with self.index_lock:
            index = self.next_index
            self.next_index += 1
        content, tokens = self.document(index)
        self.docs[index] = (content, tokens)
        body = json.dumps({"id": f"doc-{index}", "content": content,
                           "labels": [LABEL],
                           "properties": {"idx": index}}).encode()
        return "/nornicdb/store", body, index

    @staticmethod
    def _judge(status: int, raw: bytes, index: Any) -> Tuple[bool, Any]:
        return status == 201, index

    def _in_flight(self) -> int:
        return len(self.docs) - len(self.searchable_at)

    def _open_at(self) -> Optional[float]:
        """The first block boundary after the ramp's seconds are over."""
        if self._ramp_mark is None:
            self._ramp_mark = time.perf_counter()
        return next((t for t in self.boundaries if t >= self._ramp_mark),
                    None)

    def _close_at(self, t_open: float, seconds: float) -> Optional[float]:
        return next((t for t in self.boundaries if t >= t_open + seconds),
                    None)

    def window(self, tracer: Any) -> Dict[str, Any]:
        run = self.run
        obs = Observed()
        obs.config, obs.traffic = run.config, run.traffic
        obs.sizes = dict(self.model)
        queue = self.db._embed_queue
        marks: Dict[str, Any] = {}
        mix = run.traffic

        # the start hold (ramp only): one short document, the plug, is
        # sealed alone and held in the wrapper until the backlog is full,
        # so the import starts on full batches; nothing is held once the
        # window is open
        queue.drain(timeout_s=120.0)   # a no-op but between the windows of
        self.counting.start_hold.clear()   # tools/read_limits.py
        status, raw = self.client.post("/nornicdb/store", json.dumps(
            {"id": f"plug-{self.windows}", "content": "w1",
             "labels": [LABEL]}).encode())
        if status != 201:
            raise RuntimeError(f"store answered {status}: {raw[:300]!r}")
        opened = threading.Event()

        def release_when_full() -> None:
            while not opened.is_set() and \
                    self._in_flight() < int(mix["in_flight"]["min"]):
                time.sleep(0.005)
            self.counting.start_hold.set()

        releaser = threading.Thread(target=release_when_full, daemon=True)
        releaser.start()

        def on_open() -> None:
            opened.set()
            marks["prom0"] = self.client.get("/metrics")[1].decode()
            marks["programs0"] = run.meter.snapshot()["programs"]
            marks["failed0"] = queue.failed_count
            if tracer.enabled:
                tracer.start()

        def on_close() -> None:
            if tracer.enabled:
                tracer.stop()
            marks["programs1"] = run.meter.snapshot()["programs"]
            marks["failed1"] = queue.failed_count
            marks["prom1"] = self.client.get("/metrics")[1].decode()

        setup_s = time.time() - run.t_start
        t_open, t_close, replies = drive(
            self.http.port, mix, run.seconds, self._make, self._judge,
            headers=run.config.get("request_headers"),
            gauge=self._in_flight, on_open=on_open, on_close=on_close,
            open_at=self._open_at, close_at=self._close_at,
            on_tick=tracer.tick if tracer.enabled else None,
            annotate=tracer.annotate if tracer.enabled else None)
        releaser.join(timeout=5)
        self.windows += 1
        self._ramp_mark = None
        self.t_open, self.t_close = t_open, t_close
        self.compiles_in_window = marks["programs1"] - marks["programs0"]
        sent = [r for r in replies if t_open <= r.t_send < t_close]
        stored = [r for r in sent if r.ok]
        done = {int(nid[4:]): t for nid, t in self.searchable_at.items()
                if nid.startswith("doc-") and t_open < t <= t_close}
        vectors = self.db.search.vectors
        self.served = {}
        missing = 0
        for index in done:
            vec = vectors.get(f"doc-{index}")
            if vec is None:
                missing += 1
            else:
                self.served[index] = np.asarray(vec, np.float32)
        if not done:
            raise RuntimeError("no document became searchable in the "
                               "window")
        tokens = sum(self.docs[i][1] for i in done)
        embed_failed = marks["failed1"] - marks["failed0"]
        with self.counting.lock:
            calls = [c for c in self.counting.shapes
                     if t_open <= c[0] <= t_close]
            real = sum(n for t, n in self.counting.texts
                       if t_open <= t <= t_close)
        padded = sum(rows * width for _, rows, width in calls)
        m = self.model
        obs.window_s = t_close - t_open
        obs.prom_before = parse_prometheus(marks["prom0"])
        obs.prom_after = parse_prometheus(marks["prom1"])
        obs.counters = {
            "encoder_calls": len(calls),
            "real_tokens_in_calls": real,
            "padded_tokens_in_calls": padded,
            "tokens_searchable": tokens,
            "flops_real": encoder_flops(
                m["hidden_size"], m["num_layers"], m["mlp_dim"],
                [self.docs[i][1] for i in done]),
        }
        if tracer.enabled:
            obs.traced = {
                "t0": tracer.t0, "t1": tracer.t1,
                "flops_padded": sum(
                    encoder_flops(m["hidden_size"], m["num_layers"],
                                  m["mlp_dim"], [width] * rows)
                    for t, rows, width in calls
                    if tracer.t0 <= t <= tracer.t1)}
        return {
            "setup_s": setup_s,
            "end_to_end": {
                "ingest_tokens_per_s": tokens / (t_close - t_open)},
            "attempted": len(sent),
            "failed": (len(sent) - len(stored)) + embed_failed + missing,
            "observed": obs,
            "counts": {"sent": len(sent), "searchable": len(done),
                       "encoder_calls": len(calls),
                       "shapes": sorted({c[1:] for c in calls})},
            "notes": {"setup_parts_s": self.parts,
                      "window_programs_compiled": self.compiles_in_window,
                      "documents_searchable": len(done),
                      "documents_per_s": len(done) / (t_close - t_open),
                      "encoder_calls": len(calls),
                      "shapes": sorted({c[1:] for c in calls}),
                      "shapes_warmed": self.warmed,
                      "window_s": t_close - t_open,
                      "window_on_block_boundaries":
                          t_close in self.boundaries,
                      "batches": [[round(t - t_open, 3), width]
                                  for t, rows, width in calls
                                  if rows == queue.batch_size
                                  and width != 512],
                      "real_tokens_in_calls": real,
                      "padded_tokens_in_calls": padded,
                      "indexed_vectors": len(vectors)},
        }

    # -- after the window ------------------------------------------------

    def free(self) -> None:
        if self.db is not None and self.db._embed_queue is not None:
            self.db._embed_queue.stop()   # the backlog past the window
        if self.http is not None:
            self.http.stop()
            self.http = None
        if self.db is not None:
            self.db.close()
            self.db = None
        if self.data_dir:
            shutil.rmtree(self.data_dir, ignore_errors=True)
            self.data_dir = None
        self.counting = None
        gc.collect()

    def verify(self) -> List[Any]:
        run = self.run
        limits = run.size("limits")
        pool = sorted(self.served)
        n = min(int(run.mix("checked")), len(pool))
        rng = np.random.default_rng([self.traffic_seed, 6])
        longest = max(pool, key=lambda i: self.docs[i][1])
        sample = [longest] + [
            pool[i] for i in rng.choice(len(pool), n, replace=False)
            if pool[i] != longest][: n - 1]
        id_lists = [self.reference.tokenize(
            self.docs[i][0] + " " + LABEL, self.model["vocab_size"],
            self.model["max_len"]) for i in sample]
        t = time.time()
        ref = self.reference.embed(self.model, self.params, id_lists)
        if run.control == "reference_fp8":
            served = self.reference.embed(self.model, self.params,
                                          id_lists, fp8=True)
        elif run.control is None:
            served = np.stack([self.served[i] for i in sample])
        else:
            raise ValueError(f"control {run.control!r}")
        self.parts["reference_s"] = time.time() - t
        self.parts["reference_tokens"] = float(sum(map(len, id_lists)))
        return [
            Check("vector_dist_max",
                  self.reference.worst_distance(served, ref),
                  limits["vector_dist_max"]),
            Check("window_compiles", self.compiles_in_window, 0),
        ]
