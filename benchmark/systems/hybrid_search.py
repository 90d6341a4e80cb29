"""System under test: the database's own hybrid read path, as ``cli
serve`` builds it (``nornicdb_tpu.open(embedder=CachedEmbedder(
JaxEncoderEmbedder(cfg, params)))`` + ``HttpServer``), searched through
``POST /nornicdb/search`` with a TEXT query: the program embeds the
query with the full-width encoder, scores BM25 and cosine over every
passage on the device, fuses with RRF and returns the best ``limit``
with their stored content.

Set-up makes the encoder's parameters, the passages and their vectors
from the seed, loads them through the program's bulk entry
(``DB.store_batch``) and warms every program a search can need through
the program's warm call (``SearchService.warm_hybrid``). A program that
lacks either is refused at once, before any data is made. The window is
driven by ``benchmark.lib.traffic``. After it, and before the program is
freed, the program's own vector for each checked query text is fetched
through ``POST /nornicdb/embed``; ``verify`` then holds that vector to
the plain encoder, and what the timed requests returned to the
configuration's plain reference.
"""

from __future__ import annotations

import gc
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib import loader
from benchmark.lib.check import Check
from benchmark.lib.client import Client
from benchmark.lib.costs import least_seconds
from benchmark.lib.hybrid_costs import hybrid_cost, padded
from benchmark.lib.observed import Observed, parse_prometheus
from benchmark.lib.stats import percentile
from benchmark.lib.traffic import Reply, drive

LABEL = "Passage"
TEXT_BLOCK = 65536          # passages made per block of the generator


def word_table(vocabulary: int, skipped: int) -> np.ndarray:
    """The vocabulary's words by Zipf rank, the ``skipped`` most frequent
    ranks (the band a stop list removes from English) left out: word i has
    rank ``skipped + 1 + i``. Each is a token of its own under the
    published rule (letters and digits, 2-40 characters, no stop word)."""
    return np.asarray([f"w{i + skipped + 1:x}" for i in range(vocabulary)],
                      dtype=object)


def passage_words(seed: int, rows: int, spec: Dict[str, Any]
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(word index of every token, [rows + 1] offsets): lengths
    log-normal about ``mean_words`` clipped to ``min_words``..
    ``max_words``; words by Zipf's law with exponent 1 over the ranks
    left after the skipped band, drawn as ``floor(lo * (hi / lo) ** u)``
    (a rank r then has probability ln(1 + 1/r) / ln(hi / lo), 1 / r to
    within 1 / (2 r^2)). Block by block from (seed, block), so the
    result does not depend on how it is consumed."""
    sigma = float(spec["length_sigma"])
    mu = np.log(float(spec["mean_words"])) - 0.5 * sigma * sigma
    lo = float(spec["skipped_ranks"]) + 1.0
    hi = lo + float(spec["vocabulary"])
    lens = np.empty(rows, np.int64)
    parts: List[np.ndarray] = []
    for start in range(0, rows, TEXT_BLOCK):
        rng = np.random.default_rng([seed, 11, start])
        n = min(TEXT_BLOCK, rows - start)
        ln = np.clip(np.rint(rng.lognormal(mu, sigma, n)),
                     int(spec["min_words"]), int(spec["max_words"]))
        lens[start:start + n] = ln
        u = rng.random(int(ln.sum()))
        rank = np.floor(lo * np.exp(u * np.log(hi / lo)))
        parts.append((np.minimum(rank, hi - 1.0) - lo).astype(np.int32))
    offsets = np.zeros(rows + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    return np.concatenate(parts), offsets


def passage_texts(words: np.ndarray, codes: np.ndarray,
                  offsets: np.ndarray) -> List[str]:
    out: List[str] = []
    for start in range(0, len(offsets) - 1, TEXT_BLOCK):
        stop = min(start + TEXT_BLOCK, len(offsets) - 1)
        toks = words[codes[offsets[start]:offsets[stop]]]
        base = offsets[start]
        out.extend(" ".join(toks[offsets[i] - base:offsets[i + 1] - base])
                   for i in range(start, stop))
    return out


class System:
    def __init__(self, run: Any) -> None:
        self.run = run
        self.rows = int(run.size("rows"))
        self.dims = int(run.size("dims"))
        self.limit = int(run.mix("limit"))
        self.clients = int(run.mix("clients"))
        self.model = {k: run.size(k) for k in (
            "vocab_size", "hidden_size", "num_layers", "num_heads",
            "mlp_dim", "max_len")}
        self.text_spec = dict(run.config["passages"])
        self.reference = loader.load_reference(run.config, run.root)
        self.encoder_ref = self.reference.encoder()
        self.db = None
        self.http = None
        self.params = None
        self.vectors: Optional[np.ndarray] = None
        self.codes: Optional[np.ndarray] = None
        self.offsets: Optional[np.ndarray] = None
        self.texts: List[str] = []
        self.parts: Dict[str, float] = {}
        self.replies: List[Reply] = []
        self.t_open = self.t_close = 0.0
        self.compiles_in_window = 0
        self.sample: List[Reply] = []
        self.program_vectors: Optional[np.ndarray] = None
        self._lexical: Any = None
        self._lexical_terms: set = set()
        # what the requests are drawn from; tools/read_limits.py drives
        # several windows over one set-up by changing it
        self.traffic_seed = run.seed

    # -- set-up ----------------------------------------------------------

    def _timed(self, name: str, t0: float) -> float:
        now = time.time()
        self.parts[name] = now - t0
        return now

    @staticmethod
    def node_id(row: int) -> str:
        return f"p{row}"

    def setup(self) -> None:
        import jax

        run = self.run
        t = time.time()
        self.parts["imports_and_device_s"] = t - run.t_start
        import nornicdb_tpu
        from nornicdb_tpu.db import DB
        from nornicdb_tpu.search.service import SearchService

        if not callable(getattr(DB, "store_batch", None)) \
                or not callable(getattr(SearchService, "warm_hybrid", None)):
            raise RuntimeError(
                "this program has no bulk entry (DB.store_batch) or no "
                "warm call (SearchService.warm_hybrid): the deployment's "
                f"{self.rows} passages cannot be loaded in a run's time, "
                "and one by one they would feed a host HNSW on the write "
                "path")
        from nornicdb_tpu.api.http_server import HttpServer
        from nornicdb_tpu.embed.embedder import CachedEmbedder, \
            JaxEncoderEmbedder
        from nornicdb_tpu.models.encoder import EncoderConfig

        self.params = self.encoder_ref.make_params(self.model, run.seed)
        jax.block_until_ready(self.params)
        t = self._timed("make_params_s", t)
        cfg = EncoderConfig(
            vocab_size=self.model["vocab_size"],
            hidden_size=self.model["hidden_size"],
            num_layers=self.model["num_layers"],
            num_heads=self.model["num_heads"],
            mlp_dim=self.model["mlp_dim"], max_len=self.model["max_len"])
        if not run.rehearse and cfg != EncoderConfig.bge_m3_like():
            raise RuntimeError("the configuration's sizes are not the "
                               "program's bge_m3_like()")
        self.db = nornicdb_tpu.open(embedder=CachedEmbedder(
            JaxEncoderEmbedder(cfg=cfg, params=self.params,
                               seed=run.seed % (2 ** 31))))
        self.http = HttpServer(self.db, port=0).start()
        self.client = Client(self.http.port,
                             headers=run.config.get("request_headers"))
        t = self._timed("open_and_server_s", t)
        make_vectors = loader.load_module(os.path.join(
            run.root, "benchmark", "systems",
            "qdrant_collection.py")).make_vectors
        self.vectors = make_vectors(
            run.seed, self.rows, self.dims,
            int(run.size("mixture_centers")),
            float(run.config["mixture_spread"]))
        t = self._timed("make_vectors_s", t)
        words = word_table(int(self.text_spec["vocabulary"]),
                           int(self.text_spec["skipped_ranks"]))
        self.codes, self.offsets = passage_words(run.seed, self.rows,
                                                 self.text_spec)
        self.texts = passage_texts(words, self.codes, self.offsets)
        self.words = words
        t = self._timed("make_texts_s", t)
        ids = [self.node_id(i) for i in range(self.rows)]
        self.db.store_batch(self.texts, self.vectors, node_ids=ids,
                            labels=[LABEL])
        if self.db.storage.count_nodes() != self.rows \
                or len(self.db.search.vectors) != self.rows \
                or len(self.db.search.bm25) != self.rows:
            raise RuntimeError("the load did not store and index every "
                               "passage")
        t = self._timed("store_batch_s", t)
        self.warmed = self.db.search.warm_hybrid(limit=self.limit,
                                                 max_batch=self.clients)
        if not self.warmed or self.warmed[-1] < self.clients:
            raise RuntimeError(f"warm_hybrid warmed {self.warmed}: the "
                               f"fused tier is not serving")
        t = self._timed("warm_hybrid_s", t)
        for j in range(4):               # the wire, once, and its caches
            self._search_once(-1, j)
        self._timed("first_requests_s", t)

    # -- requests ----------------------------------------------------------

    def _query(self, k: int, seq: int) -> str:
        """The text of client ``k``'s request number ``seq``: 2 + a
        Binomial(10, 0.4) number of words (2 to 12, mean 6), drawn
        without replacement from the distinct words of one seeded
        passage, a passage of its own for every (client, number)."""
        rng = np.random.default_rng([self.traffic_seed, 3, k + 1, seq])
        row = int(rng.integers(0, self.rows))
        own = np.unique(self.codes[self.offsets[row]:self.offsets[row + 1]])
        n = min(2 + int(rng.binomial(10, 0.4)), len(own))
        return " ".join(self.words[rng.choice(own, n, replace=False)])

    def _body(self, query: str) -> bytes:
        return json.dumps({"query": query, "limit": self.limit}).encode()

    def _search_once(self, k: int, seq: int) -> None:
        query = self._query(k, seq)
        status, raw = self.client.post("/nornicdb/search",
                                       self._body(query))
        ok, _ = self._judge(status, raw, query)
        if not ok:
            raise RuntimeError(f"warm-up search answered {status}: "
                               f"{raw[:300]!r}")

    def _make(self, k: int, seq: int) -> Tuple[str, bytes, Any]:
        query = self._query(k, seq)
        return "/nornicdb/search", self._body(query), query

    def _judge(self, status: int, raw: bytes,
               query: Any) -> Tuple[bool, Any]:
        """A request is answered when it is a 200 with ``limit`` hits;
        what is kept is enough to check it afterwards."""
        if status != 200:
            return False, None
        hits = json.loads(raw)["results"]
        n = len(hits)
        rows = np.full(n, -1, np.int64)
        well = n == self.limit
        for j, h in enumerate(hits):
            hid = str(h.get("id", ""))
            if hid[:1] == "p" and hid[1:].isdigit() \
                    and int(hid[1:]) < self.rows:
                rows[j] = int(hid[1:])
                well = well and h.get("properties", {}).get(
                    "content") == self.texts[rows[j]]
            else:
                well = False
        nan = float("nan")
        kept = {
            "query": query, "rows": rows, "well": well,
            "score": np.fromiter((h.get("score", nan) for h in hits),
                                 np.float64, n),
            "bm25": np.fromiter((h.get("bm25_score", nan) for h in hits),
                                np.float64, n),
            "vector": np.fromiter((h.get("vector_score", nan)
                                   for h in hits), np.float64, n)}
        return n == self.limit, kept

    # -- the window ------------------------------------------------------

    def window(self, tracer: Any) -> Dict[str, Any]:
        from nornicdb_tpu import admission
        from nornicdb_tpu.obs import tracing

        run = self.run
        obs = Observed()
        obs.config, obs.traffic = run.config, run.traffic
        tracing.TRACES.capacity = 1 << 20
        marks: Dict[str, Any] = {}

        def on_open() -> None:
            tracing.TRACES.clear()
            marks["prom0"] = self.client.get("/metrics")[1].decode()
            marks["programs0"] = run.meter.snapshot()["programs"]
            if tracer.enabled:
                tracer.start()
                marks["trace_wall0"] = time.time()
            marks["wall_open"] = time.time()

        def on_close() -> None:
            marks["wall_close"] = time.time()
            if tracer.enabled:
                tracer.stop()
            marks["programs1"] = run.meter.snapshot()["programs"]
            marks["prom1"] = self.client.get("/metrics")[1].decode()

        gen2: List[float] = []

        def on_gc(phase: str, info: Dict[str, Any]) -> None:
            if info["generation"] == 2:
                if phase == "start":
                    gen2.append(-time.perf_counter())
                elif gen2 and gen2[-1] < 0:
                    gen2[-1] += time.perf_counter()

        gc.callbacks.append(on_gc)
        setup_s = time.time() - run.t_start   # up to the ramp's first request
        t_open, t_close, replies = drive(
            self.http.port, run.traffic, run.seconds, self._make,
            self._judge, headers=run.config.get("request_headers"),
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
        self._take_sample(answered)
        capacity = int(self.db.search.vectors.resource_stats()["capacity"])
        if capacity != padded(self.rows):
            raise RuntimeError(f"the index is padded to {capacity} rows, "
                               f"not {padded(self.rows)}")
        obs.sizes = {"rows": self.rows, "dims": self.dims,
                     "limit": self.limit, "capacity": capacity,
                     "lex_capacity": padded(self.rows)}
        obs.window_s = t_close - t_open
        obs.spans = [s for s in tracing.TRACES.snapshot(limit=1 << 20)
                     if s["start_ms"] >= marks["wall_open"] * 1e3
                     and s["start_ms"] + s["duration_ms"]
                     <= marks["wall_close"] * 1e3]
        obs.prom_before = parse_prometheus(marks["prom0"])
        obs.prom_after = parse_prometheus(marks["prom1"])
        if tracer.enabled:
            # the fused dispatches of the traced part, priced by the
            # benchmark's own count of the bytes each must move: the
            # riders and the live terms of the batch, not the rows the
            # program pads them to. The traced part is the tracer's own
            # t0..t1 (``stop()`` stamps t1 and only then writes the
            # trace out, for seconds, while the clients keep sending:
            # dispatches of that time are in no device plane)
            a = marks["trace_wall0"] * 1e3
            b = a + (tracer.t1 - tracer.t0) * 1e3
            cost = [hybrid_cost(int(s["attrs"]["b"]),
                                int(s["attrs"]["terms"]),
                                int(s["attrs"]["entries"]), capacity,
                                self.dims, obs.sizes["lex_capacity"])
                    for s in obs.span_walk("hybrid.dispatch")
                    if a <= s["start_ms"] < b and "terms" in s["attrs"]]
            obs.traced = {
                "requests": float(sum(
                    1 for r in replies
                    if r.ok and tracer.t0 <= r.t_done <= tracer.t1)),
                "fused_dispatches": float(len(cost)),
                "fused_bytes": float(sum(c[1] for c in cost))}
            if run.peak is not None:
                obs.traced["fused_least_s"] = float(sum(
                    least_seconds(f, by, run.peak)[0] for f, by in cost))
        adm = admission.CONTROLLER.summary()
        batches = obs.prom_delta("nornicdb_microbatch_batch_size_count")
        by_5s = [0] * (int(run.seconds // 5) + 1)
        for r in in_time:
            by_5s[min(int((r.t_done - t_open) // 5), len(by_5s) - 1)] += 1
        latencies = [(r.t_done - r.t_send) * 1e3 for r in answered]
        return {
            "setup_s": setup_s,
            "end_to_end": {
                "search_qps": len(in_time) / (t_close - t_open),
                "search_p95_ms": percentile(latencies, 95.0),
            },
            "attempted": len(sent),
            "failed": len(sent) - len(answered),
            "observed": obs,
            "counts": {"sent": len(sent), "answered": len(answered)},
            "notes": {"setup_parts_s": self.parts,
                      "window_programs_compiled": self.compiles_in_window,
                      "buckets_warmed": self.warmed,
                      "admission": {
                          "posture": adm["posture"],
                          "interactive_wait_ms":
                              adm["lanes"]["interactive"]["wait_ms"],
                          "max_wait_ms": adm["limits"]["max_wait_ms"],
                          "shed_total": adm["shed"]["total"]},
                      "p50_ms": percentile(latencies, 50.0),
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

    def _take_sample(self, answered: List[Reply]) -> None:
        """The seeded sample of the window's answered requests, and the
        program's own vector for each of their query texts, one text a
        call (the shape a search embeds at)."""
        n = min(int(self.run.mix("checked")), len(answered))
        rng = np.random.default_rng([self.traffic_seed, 4])
        self.sample = [answered[i]
                       for i in rng.choice(len(answered), n, replace=False)]
        t = time.time()
        vectors = []
        for r in self.sample:
            status, raw = self.client.post(
                "/nornicdb/embed",
                json.dumps({"texts": [r.kept["query"]]}).encode())
            if status != 200:
                raise RuntimeError(f"embed answered {status}: "
                                   f"{raw[:300]!r}")
            vectors.append(json.loads(raw)["embeddings"][0])
        self.program_vectors = np.asarray(vectors, np.float32)
        self.parts["fetch_query_vectors_s"] = time.time() - t

    # -- after the window ------------------------------------------------

    def free(self) -> None:
        """Stop the server and drop the program's state (device matrix,
        postings, nodes) before the reference runs."""
        if self.http is not None:
            self.http.stop()
            self.http = None
        if self.db is not None:
            self.db.close()
            self.db = None
        gc.collect()

    def _served(self) -> List[Dict[str, Any]]:
        return [{"rows": np.clip(r.kept["rows"], 0, self.rows - 1),
                 "score": r.kept["score"], "bm25": r.kept["bm25"],
                 "vector": r.kept["vector"]} for r in self.sample]

    def verify(self) -> List[Any]:
        run = self.run
        ref = self.reference
        limits = run.size("limits")
        k1, b = float(run.config["bm25_k1"]), float(run.config["bm25_b"])
        rrf_k = float(run.config["rrf_k"])
        depth = max(3 * self.limit, 30)
        queries = [r.kept["query"] for r in self.sample]
        t = time.time()
        # the passages as the program's text index sees them: content
        # and the node's label; rebuilt only for terms not yet wanted
        # (tools/read_limits.py judges one window twice)
        wanted = {w for q in queries for w in ref.tokens(q)}
        if not wanted <= self._lexical_terms:
            self._lexical = ref.Lexical(
                [text + " " + LABEL for text in self.texts], sorted(wanted))
            self._lexical_terms = wanted
        self.parts["reference_lexical_s"] = time.time() - t
        t = time.time()
        id_lists = [self.encoder_ref.tokenize(
            q, self.model["vocab_size"], self.model["max_len"])
            for q in queries]
        plain_vectors = self.encoder_ref.embed(self.model, self.params,
                                               id_lists)
        if run.control == "reference_low":
            program_vectors = self.encoder_ref.embed(
                self.model, self.params, id_lists, fp8=True)
            served = ref.answers(self._lexical, self.vectors, queries,
                                 program_vectors, k1, b, rrf_k, depth,
                                 self.limit, low_precision=True)
        elif run.control is None:
            program_vectors = self.program_vectors
            served = self._served()
        else:
            raise ValueError(f"control {run.control!r}")
        self.parts["reference_encoder_s"] = time.time() - t
        t = time.time()
        read = ref.judge(self._lexical, self.vectors, queries,
                         program_vectors, served, k1, b, rrf_k, depth,
                         self.limit, float(limits["lex_score_err_max"]),
                         float(limits["vec_score_err_max"]))
        self.parts["reference_judge_s"] = time.time() - t
        malformed = 0
        for r in self.sample:
            rows, score = r.kept["rows"], r.kept["score"]
            if (len(rows) != self.limit or rows.min() < 0
                    or len(set(rows.tolist())) != len(rows)
                    or np.any(np.isnan(score))
                    or np.any(np.diff(score) > 0) or not r.kept["well"]):
                malformed += 1
        pool = [r for r in self.replies
                if r.ok and self.t_open <= r.t_send < self.t_close]
        unwell = sum(1 for r in pool if not r.kept["well"])
        return [
            Check("vector_dist_max", self.encoder_ref.worst_distance(
                program_vectors, plain_vectors), limits["vector_dist_max"]),
            Check("vec_score_err_rms", read["vec_err_rms"],
                  limits["vec_score_err_rms"]),
            Check("vec_score_err_max", read["vec_err_max"],
                  limits["vec_score_err_max"]),
            Check("lex_score_err_rms", read["lex_err_rms"],
                  limits["lex_score_err_rms"]),
            Check("lex_score_err_max", read["lex_err_max"],
                  limits["lex_score_err_max"]),
            Check("fused_score_err_max", read["fused_score_err"],
                  limits["fused_score_err_max"]),
            Check("fused_gap_max", read["fused_gap"], 0.0),
            Check("answers_malformed", malformed + unwell, 0),
            Check("window_compiles", self.compiles_in_window, 0),
        ]
