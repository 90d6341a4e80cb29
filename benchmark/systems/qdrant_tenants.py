"""System under test: the collection of ``systems/qdrant_collection.py``
shared by many tenants. Everything that file does stays as it is (it is
imported, not edited): the bulk fill of the base, the ladder that warms
the batch buckets, the closed loops, the window's accounting. Added here:
the payload index, declared through the public route once the base is
loaded (``PUT /collections/<c>/index``, as a client would); the tenant of
every request, drawn from the mix's law; its filter in every body; its
query, drawn from the tenant's own rows; and a ``verify`` that judges what
the window served against the top of the TENANT'S rows
(``configs/filtered_cosine_topk.reference.py``).

Which rows are a tenant's is computed here from the ids alone, by
``payload_of``: never read back from the program.
"""

from __future__ import annotations

import gc
import json
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.lib import loader
from benchmark.lib.check import Check
from benchmark.systems import qdrant_collection as base
from benchmark.systems.qdrant_collection import payload_of


def tenant_law(law: Dict[str, Any], tenants: int,
               schedule_seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(cumulative probability by rank, tenant of each rank): a Zipf law
    over the ranks, and which tenant holds which rank by a permutation
    from the mix's own seed, so the same tenants are hot in every run."""
    if law.get("law") != "zipf":
        raise ValueError(f"tenant_law {law!r}: this system draws zipf")
    weights = 1.0 / np.arange(1, tenants + 1) ** float(law["exponent"])
    order = np.random.default_rng([schedule_seed, 11]).permutation(tenants)
    return np.cumsum(weights / weights.sum()), order


class System(base.System):
    def __init__(self, run: Any) -> None:
        super().__init__(run)
        self.field = str(run.config["tenant_field"])
        self.tenants = int(run.size("tenants"))
        self.cdf, self.rank_to_tenant = tenant_law(
            dict(run.mix("tenant_law")), self.tenants,
            int(run.mix("schedule_seed")))
        # row -> the field's value, and each tenant's rows (set-up)
        self.field_of: np.ndarray = np.zeros(0, np.int64)
        self.rows_of: List[np.ndarray] = []

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        super().setup()
        # the index's time is its own part, not the nodes'
        self.parts["create_nodes_s"] -= self.parts["payload_index_s"] \
            + self.parts["tenant_rows_s"]
        t = time.time()
        index = self.db.qdrant_compat._index(self.collection)
        # every batch bucket of the filtered program at the cell's k, where
        # the program has a warm call for them (the ladder above has sent
        # a burst a bucket; which riders seal together is timing)
        warm = getattr(index, "warm_filtered", None)
        if warm is not None:
            warm(max_batch=self.clients, ks=(self.limit,))
        self._wait_admit()
        self._timed("warm_filtered_s", t)

    def _create_nodes(self) -> None:
        """The base's nodes, then what this deployment adds before the
        first search: the payload index, declared over the wire once the
        base is loaded (the program builds its column from the stored
        payloads), and the benchmark's own table of which rows are whose."""
        super()._create_nodes()
        t = time.time()
        status, raw = self.client.request(
            "PUT", f"/collections/{self.collection}/index",
            json.dumps({"field_name": self.field,
                        "field_schema": str(self.run.config[
                            "tenant_field_schema"])}).encode())
        if status != 200:
            raise RuntimeError(f"create payload index answered {status}: "
                               f"{raw[:300]!r}")
        status, raw = self.client.get(f"/collections/{self.collection}")
        schema = json.loads(raw)["result"].get("payload_schema", {}) \
            if status == 200 else {}
        if self.field not in schema:
            raise RuntimeError(f"the collection reports no payload index "
                               f"on {self.field!r}: {schema!r}")
        t = self._timed("payload_index_s", t)
        self.field_of = np.fromiter(
            (payload_of(i)[self.field] for i in range(self.rows)),
            np.int64, self.rows)
        self.rows_of = [np.flatnonzero(self.field_of == tenant)
                        for tenant in range(self.tenants)]
        if min(map(len, self.rows_of)) < self.limit:
            raise RuntimeError("a tenant owns fewer rows than `limit`")
        self._timed("tenant_rows_s", t)

    # -- requests --------------------------------------------------------

    def _query(self, k: int, seq: int) -> Tuple[int, np.ndarray]:
        """The base's query, drawn from the rows of a tenant that the law
        picks: ``(tenant, vector)``."""
        rng = np.random.default_rng([self.traffic_seed, 3, k + 1, seq])
        tenant = int(self.rank_to_tenant[min(
            int(np.searchsorted(self.cdf, rng.random())),
            self.tenants - 1)])
        own = self.rows_of[tenant]
        row = int(own[rng.integers(0, len(own))])
        q = self.vectors[row] + np.float32(
            self.noise / np.sqrt(self.dims)) * rng.standard_normal(
                self.dims, dtype=np.float32)
        return tenant, q

    def _filter(self, tenant: int) -> Dict[str, Any]:
        return {"must": [{"key": self.field, "match": {"value": tenant}}]}

    def _make(self, k: int, seq: int) -> Tuple[str, bytes, Any]:
        tenant, q = self._query(k, seq)
        body = json.dumps({"vector": q.tolist(), "limit": self.limit,
                           "with_payload": True,
                           "filter": self._filter(tenant)}).encode()
        return self._path, body, (q, tenant)

    def _judge(self, status: int, raw: bytes, meta: Any) -> Tuple[bool, Any]:
        """As the base's (a 200 with ``limit`` hits), the tenant kept
        beside the answer. A server error ends the run: a program that
        cannot serve this traffic fails, soon, and does not hang."""
        q, tenant = meta
        if status >= 500:
            raise RuntimeError(f"a search answered {status}: {raw[:300]!r}")
        ok, kept = super()._judge(status, raw, q)
        return ok, None if kept is None else kept + (tenant,)

    def _search_once(self, k: int, seq: int) -> None:
        path, body, meta = self._make(k, seq)
        status, raw = self.client.post(path, body)
        ok, _ = self._judge(status, raw, meta)
        if not ok:
            raise RuntimeError(f"warm-up search answered {status}: "
                               f"{raw[:300]!r}")

    # -- the window ------------------------------------------------------

    def window(self, tracer: Any) -> Dict[str, Any]:
        # collections of the interpreter's YOUNG generations that take
        # over 50 ms (the base notes the full ones): every thread stands
        # while one runs
        young: List[List[float]] = []
        begun: Dict[int, float] = {}

        def on_gc(phase: str, info: Dict[str, Any]) -> None:
            g = info["generation"]
            if g < 2:
                now = time.perf_counter()
                if phase == "start":
                    begun[g] = now
                elif now - begun.get(g, now) > 0.05:
                    young.append([begun[g], g, now - begun[g]])

        gc.callbacks.append(on_gc)
        try:
            out = super().window(tracer)
        finally:
            gc.callbacks.remove(on_gc)
        out["notes"]["gc_young_over_50ms"] = [
            [round(t - self.t_open, 2), g, round(dt * 1e3, 1)]
            for t, g, dt in young if self.t_open <= t <= self.t_close]
        sent = np.bincount(
            [r.kept[4] for r in self.replies
             if r.ok and self.t_open <= r.t_send < self.t_close],
            minlength=self.tenants)
        # where the window's answers stopped: the p95 of a run is moved by
        # a few stops of every request at once (PERF.md section 6), so a
        # run that reads far off says here when, and for how long
        done = sorted(r.t_done for r in self.replies
                      if r.ok and self.t_open <= r.t_done <= self.t_close)
        out["notes"]["no_answer_over_100ms"] = [
            [round(a - self.t_open, 2), round((b - a) * 1e3, 1)]
            for a, b in zip(done, done[1:]) if b - a > 0.1]
        out["notes"]["tenants"] = {
            "answered_for_hottest": int(sent.max()),
            "answered_for_coldest": int(sent.min()),
            "tenants_answered": int(np.count_nonzero(sent))}
        return out

    # -- after the window ------------------------------------------------

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
        tenants = [r.kept[4] for r in sample]
        if run.control == "reference_high":
            ids, scores = reference.control_answers(
                self.vectors, self.field_of, queries, tenants, self.limit)
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
        read = reference.judge(self.vectors, self.field_of, queries, tenants,
                               ids, scores, self.limit)
        self.parts["reference_s"] = time.time() - t
        # the filter held in EVERY answer of the window, not the sample's
        # alone: ids that payload_of() gives to another tenant
        strangers = sum(
            int(np.count_nonzero(self.field_of[np.clip(
                r.kept[1], 0, self.rows - 1)] != r.kept[4]))
            for r in pool)
        unwell = sum(1 for r in pool if not r.kept[3])
        return [
            Check("score_err_rms", read["score_err_rms"],
                  limits["score_err_rms"]),
            Check("score_err_max", read["score_err"],
                  limits["score_err_max"]),
            Check("rank_gap_max", read["rank_gap"], limits["rank_gap_max"]),
            Check("filter_violations",
                  max(strangers, read["filter_violations"]), 0),
            Check("answers_malformed", malformed + unwell + read["short"],
                  0),
            Check("window_compiles", self.compiles_in_window, 0),
        ]
