"""System under test: the collection of ``systems/qdrant_collection.py``,
written while it is read. Everything that file does stays as it is (it is
imported, not edited): the bulk fill of the base, the warm-up of the batch
buckets, the readers' closed loops, the window's accounting of searches.
Added here: the writer beside ``drive``, the log of its writes (send time,
acknowledgement time, ids, rows: the benchmark's own copy of everything
the collection holds past the base), the readers' fresh queries, and a
``verify`` that judges what the window served AS OF each request
(``configs/asof_cosine_topk.reference.py``).

Every write goes through the public surface, ``PUT
/collections/<c>/points``, and is acknowledged by its 200. The writer keeps
a fixed schedule from the ramp's first request, at most one write in
flight; a write that is late is sent at once and the schedule moves with
it (lost time is not caught up in a burst). Its bodies come from a child
process (``lib/live_writes.py`` says why).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib import loader
from benchmark.lib.check import Check
from benchmark.lib.live_costs import update_least_seconds
from benchmark.lib.live_writes import Producer
from benchmark.systems import qdrant_collection as base

FULL_SHIPS = 'nornicdb_index_refresh_total{kind="full"}'


class Write:
    """One write request as the writer saw it."""

    __slots__ = ("n", "t_send", "t_ack", "status", "ids", "rows")

    def __init__(self, n: int, t_send: float, t_ack: float, status: int,
                 ids: np.ndarray, rows: np.ndarray) -> None:
        self.n = n
        self.t_send = t_send
        self.t_ack = t_ack
        self.status = status
        self.ids = ids
        self.rows = rows


class System(base.System):
    def __init__(self, run: Any) -> None:
        super().__init__(run)
        spec = dict(run.mix("writer"))
        self.period = 1.0 / float(spec["requests_per_s"])
        self.new = int(spec["new_per_request"])
        self.over = int(spec["overwrites_per_request"])
        self.write_deadline = float(spec["deadline_s"])
        self.fresh_one_in = int(run.mix("fresh_one_in"))
        self.recent = int(run.mix("fresh_recent_writes"))
        self.writes: List[Write] = []        # in send order, acknowledged
        self.writer_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._writer: Optional[threading.Thread] = None
        self._producer: Optional[Producer] = None
        self._started = threading.Lock()
        self.count_after: Optional[int] = None
        self.full_ships = float("nan")
        self._points_path = f"/collections/{self.collection}/points"

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        super().setup()
        index = self.db.qdrant_compat._index(self.collection)
        if int(index._capacity) != int(self.run.size("capacity")):
            raise RuntimeError("the index's capacity is not the "
                               "configuration's")
        t = time.time()
        # the update programs, where the program has a warm call for them
        # (else they compile in the ramp)
        warm = getattr(index, "warm_updates", None)
        if warm is not None:
            warm()
        self._producer = Producer(
            seed=self.run.seed, rows=self.rows, dims=self.dims,
            centers=int(self.run.size("mixture_centers")),
            spread=float(self.run.config["mixture_spread"]),
            new=self.new, over=self.over)
        self._timed("warm_updates_s", t)

    # -- the writer ------------------------------------------------------

    def _write_loop(self) -> None:
        try:
            due = time.perf_counter()
            n = 0
            while not self._stop.is_set():
                ids, rows, body = self._producer.next()
                wait = due - time.perf_counter()
                if wait > 0 and self._stop.wait(wait):
                    break
                t_send = time.perf_counter()
                status, _ = self.client.request("PUT", self._points_path,
                                                body)
                t_ack = time.perf_counter()
                self.writes.append(Write(n, t_send, t_ack, status, ids,
                                         rows))
                n += 1
                # the next is due a period after this one was; one that is
                # late already goes at once and the schedule moves with it
                due = max(due + self.period, t_ack)
        except BaseException as exc:  # noqa: BLE001 - reported by window()
            self.writer_error = exc

    def _start_writer(self) -> None:
        if self._writer is not None:
            return
        with self._started:
            if self._writer is None:
                self._writer = threading.Thread(
                    target=self._write_loop, daemon=True, name="bench-writer")
                self._writer.start()

    # -- the readers -----------------------------------------------------

    def _make(self, k: int, seq: int) -> Tuple[str, bytes, Any]:
        """A reader's request: one in ``fresh_one_in`` (drawn from the
        seed) starts from the stored row of a point of one of the
        ``fresh_recent_writes`` most recently acknowledged write requests,
        the rest from a base row as ``closed-c32``'s do; the same noise."""
        self._start_writer()            # the ramp's first request
        rng = np.random.default_rng([self.traffic_seed, 7, k + 1, seq])
        fresh = (-1, -1)                # the point, its write's number
        writes = [w for w in self.writes[-self.recent:] if w.status == 200]
        if writes and rng.integers(0, self.fresh_one_in) == 0:
            w = writes[int(rng.integers(0, len(writes)))]
            j = int(rng.integers(0, len(w.ids)))
            fresh = (int(w.ids[j]), w.n)
            q = w.rows[j] + np.float32(
                self.noise / np.sqrt(self.dims)) * rng.standard_normal(
                    self.dims, dtype=np.float32)
        else:
            _, q = self._query(k, seq)
        return self._path, self._body(q), (q, fresh)

    def _judge(self, status: int, raw: bytes, meta: Any) -> Tuple[bool, Any]:
        """As the base's, with the fresh point kept beside the answer. A
        server error ends the run: a program that cannot serve this
        traffic fails, soon, and does not hang."""
        q, fresh = meta if isinstance(meta, tuple) else (meta, (-1, -1))
        if status >= 500:
            raise RuntimeError(f"a search answered {status}: {raw[:300]!r}")
        ok, kept = super()._judge(status, raw, q)
        return ok, None if kept is None else kept + fresh

    # -- the window ------------------------------------------------------

    def window(self, tracer: Any) -> Dict[str, Any]:
        offset = time.time() - time.perf_counter()   # perf_counter -> wall
        try:
            out = super().window(tracer)
        finally:
            self._stop.set()
            if self._writer is not None:
                self._writer.join(timeout=self.write_deadline + 30.0)
            self._producer.close()
        if self.writer_error is not None:
            raise self.writer_error
        if self._writer is None or self._writer.is_alive():
            raise RuntimeError("the writer did not start, or did not end")
        status, raw = self.client.post(self._points_path + "/count", b"{}")
        if status == 200:
            self.count_after = int(json.loads(raw)["result"]["count"])
        obs = out["observed"]
        sent = [w for w in self.writes
                if self.t_open <= w.t_send < self.t_close]
        bad = [w for w in sent if self._failed(w)]
        acked = [w for w in self.writes if w.status == 200
                 and self.t_open <= w.t_ack <= self.t_close]
        out["attempted"] += len(sent)
        out["failed"] += len(bad)
        obs.counters["points_acked"] = float(sum(len(w.ids) for w in acked))
        if any(k.startswith("nornicdb_index_refresh_total")
               for k in obs.prom_after):
            self.full_ships = obs.prom_after.get(FULL_SHIPS, 0.0) \
                - obs.prom_before.get(FULL_SHIPS, 0.0)
        if tracer.enabled and tracer.t0 is not None:
            # the refreshes of the traced part (the tracer's own t0..t1),
            # priced by the rows they wrote, not the bucket they padded to
            a = (tracer.t0 + offset) * 1e3
            b = (tracer.t1 + offset) * 1e3
            rows = sum(int(s["attrs"].get("rows", 0))
                       for s in obs.span_walk("index.refresh")
                       if a <= s["start_ms"] < b
                       and s["attrs"].get("kind") == "rows")
            obs.traced["update_rows"] = float(rows)
            if self.run.peak is not None and rows:
                obs.traced["update_least_s"] = update_least_seconds(
                    rows, self.dims, self.run.peak)
        late = [w.t_ack - w.t_send for w in self.writes]
        # where the window's answers stopped: the p95 of a run is moved
        # by a few stops of every request at once (PERF.md section 6), so
        # a run that reads far off says here when, and for how long
        done = sorted(r.t_done for r in self.replies
                      if r.ok and self.t_open <= r.t_done <= self.t_close)
        out["notes"]["no_answer_over_100ms"] = [
            [round(a - self.t_open, 2), round((b - a) * 1e3, 1)]
            for a, b in zip(done, done[1:]) if b - a > 0.1]

        def mean_ms(name: str) -> Optional[float]:
            spans = obs.span_walk(name)
            return sum(x["duration_ms"] for x in spans) / len(spans) \
                if spans else None

        out["counts"].update(writes_sent=len(sent), writes_failed=len(bad))
        out["notes"]["writer"] = {
            "requests": len(self.writes), "in_window": len(sent),
            "points_acked_in_window": obs.counters["points_acked"],
            # against the schedule's requests_per_s x points a request: a
            # writer that is always late runs as a closed loop of one
            "points_acked_per_s": obs.counters["points_acked"]
            / (self.t_close - self.t_open),
            "points_offered_per_s": (self.new + self.over) / self.period,
            "new_ids_acked": self._new_acked(),
            "write_ms_p50": float(np.median(late) * 1e3) if late else None,
            "write_ms_max": float(max(late) * 1e3) if late else None,
            # where a write's time goes inside the server, for the reader
            # of a run (the metric is the whole of qdrant.upsert)
            "upsert_storage_ms": mean_ms("upsert.storage"),
            "upsert_index_ms": mean_ms("upsert.index"),
            "count_after": self.count_after,
            "full_ships_in_window": self.full_ships}
        return out

    def _failed(self, w: Write) -> bool:
        """Not a 200 inside its deadline."""
        return w.status != 200 or w.t_ack - w.t_send > self.write_deadline

    def _new_acked(self) -> int:
        return sum(int((w.ids >= self.rows).sum()) for w in self.writes
                   if w.status == 200)

    # -- after the window ------------------------------------------------

    def verify(self) -> List[Any]:
        run = self.run
        limits = run.size("limits")
        reference = loader.load_reference(run.config, run.root)
        log = reference.WriteLog(self.rows, [
            (w.n, w.t_send, w.t_ack, w.ids, w.rows) for w in self.writes
            if w.status == 200])
        pool = [r for r in self.replies
                if r.ok and self.t_open <= r.t_send < self.t_close]
        fresh = [r for r in pool if r.kept[4] >= 0]
        # a seeded sample, with the fresh queries it must hold drawn first
        rng = np.random.default_rng([self.traffic_seed, 4])
        n = min(int(run.mix("checked")), len(pool))
        n_fresh = min(int(run.mix("checked_fresh_min")), len(fresh), n)
        sample = [fresh[i] for i in
                  rng.choice(len(fresh), n_fresh, replace=False)]
        rest = [r for r in pool if all(r is not s for s in sample)]
        sample += [rest[i] for i in
                   rng.choice(len(rest), n - n_fresh, replace=False)]
        searches = [reference.Search(r.t_send, r.t_done, r.kept[0])
                    for r in sample]
        if run.control == "reference_high":
            ids, scores = reference.control_answers(
                self.vectors, log, searches, self.limit)
        elif run.control is None:
            ids = [r.kept[1] for r in sample]
            scores = [r.kept[2] for r in sample]
        else:
            raise ValueError(f"control {run.control!r}")
        malformed = 0
        for i, r in enumerate(sample):
            a, s = ids[i], scores[i]
            if (len(a) != self.limit or len(set(a.tolist())) != len(a)
                    or a.min() < 0 or np.any(np.diff(s) > 0)
                    or not r.kept[3]):
                malformed += 1
        t = time.time()
        read = reference.judge(self.vectors, log, searches, ids, scores,
                               self.limit)
        # read-your-writes where users look first: EVERY fresh query of
        # the window, not the sample alone
        not_first = reference.fresh_not_first(
            log, [(r.t_send, r.t_done, r.kept[4], r.kept[5], r.kept[1])
                  for r in fresh])
        self.parts["reference_s"] = time.time() - t
        unwell = sum(1 for r in pool
                     if not r.kept[3]
                     or len(set(r.kept[1].tolist())) != len(r.kept[1]))
        want = self.rows + self._new_acked()
        count_off = float("nan") if self.count_after is None \
            else abs(self.count_after - want)
        return [
            Check("score_err_rms", read["score_err_rms"],
                  limits["score_err_rms"]),
            Check("score_err_max", read["score_err"],
                  limits["score_err_max"]),
            Check("rank_gap_max", read["rank_gap"], limits["rank_gap_max"]),
            Check("stale_after_ack", read["stale_after_ack"], 0),
            Check("fresh_not_first", not_first, 0),
            Check("fresh_checked_short", max(
                0, int(run.mix("checked_fresh_min")) - n_fresh), 0),
            Check("answers_malformed",
                  malformed + unwell + read["unknown_ids"], 0),
            Check("full_ships_in_window", self.full_ships, 0),
            Check("count_off", count_off, 0),
            Check("window_compiles", self.compiles_in_window, 0),
        ]

    def free(self) -> None:
        self._stop.set()
        if self._producer is not None:
            self._producer.close()
        super().free()
