"""Multi-worker wire plane: parallel frontends over ONE device plane.

ISSUE 11 / ROADMAP item 3. A pre-chip CPU run showed the serving stack
collapsing at the wire, not the device: the qdrant gRPC surface kneed
at 724 qps open-loop while the Go reference does ~29k ops/s on the
same contract, and PR 1's framework-floor calibration (vs_floor 1.31)
says one Python event loop is the ceiling. This module is the
architectural fix:

- ``NORNICDB_WIRE_WORKERS`` frontend workers — separate PROCESSES by
  default (``NORNICDB_WIRE_WORKER_MODE=thread`` keeps them in-process
  for tests/tiny benches) — each running its own grpc.aio server and a
  lean HTTP frontend bound to ONE shared port pair via SO_REUSEPORT,
  so the kernel load-balances connections and protobuf/JSON
  parse+serialize runs on N cores instead of one;
- every worker funnels into the single shared device plane through the
  lock-free :class:`~nornicdb_tpu.search.broker.DispatchBroker` ring:
  raw-embedding ops coalesce across workers into one batched device
  dispatch (the MicroBatcher's leader/rider protocol with the broker
  as standing leader — coalescing gets *better* with more frontends),
  and generic ops (full-fidelity ``search_points``, upsert convoys,
  scroll pages, any REST route) execute concurrently on the plane's
  pool where they coalesce in the existing MicroBatcher/BatchCoalescer
  machinery;
- responses assemble zero-copy in the worker: the qdrant Search reply
  is hand-encoded straight from the plane's point dicts
  (api/wire_codec.py — no protobuf object graph), validated response
  bytes ride each worker's own generation-checked WireCache against
  write generations MIRRORED into shared memory (cache.py
  ``set_generation_mirror``), so a cache hit never crosses the ring;
- per-rider tier attribution stays rider-accurate across the process
  boundary (the plane records serves; broker responses carry the tier
  and the leader-stamped stage intervals which the worker re-records
  under surface ``broker``), degrade-ledger records produced by a
  worker's query ride its response back into the worker's own ledger,
  and each worker's ``/metrics`` scrape merges the shared plane's
  series exactly once (obs/metrics.py ``render_merged``); ``/readyz``
  forwards the plane verdict and adds ``broker_unreachable``;
- a worker whose broker died times out (``NORNICDB_WIRE_TIMEOUT_S``)
  and errors — never hangs; a crashed worker's listening socket leaves
  the SO_REUSEPORT group, so surviving workers keep taking traffic.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from nornicdb_tpu import admission as _adm
from nornicdb_tpu import obs
from nornicdb_tpu.obs import audit as _audit
from nornicdb_tpu.obs import tenant as _tenant
from nornicdb_tpu.search.broker import (
    BrokerClient,
    BrokerRemoteError,
    BrokerTimeout,
    DispatchBroker,
)


def resolve_vec_dispatch(db, key: str, queries, k: int):
    """The OP_VEC dispatch-key vocabulary resolved against one DB's
    device indexes — the ONE copy shared by the plane's local dispatch
    and each read replica's dispatch (replication/read_fleet.py), so a
    new key can never silently exist on one side only."""
    if key == "__service__":
        return db.search._ann_search_batch(queries, k)
    if key.startswith("qdrant:"):
        return db.qdrant_compat._ann_search_index(
            key[len("qdrant:"):]).search_batch(queries, k)
    raise KeyError(f"unknown vec-dispatch key {key!r}")


def wire_workers_from_env(default: int = 1) -> int:
    try:
        return int(os.environ.get("NORNICDB_WIRE_WORKERS", str(default)))
    except ValueError:
        return default


def wire_worker_mode() -> str:
    mode = os.environ.get("NORNICDB_WIRE_WORKER_MODE", "process").lower()
    return mode if mode in ("process", "thread") else "process"


# -- worker-side proxies ----------------------------------------------------


def _map_remote(exc: BrokerRemoteError):
    from nornicdb_tpu.api.qdrant import QdrantError

    if exc.type_name == "QdrantError":
        return QdrantError(str(exc), status=exc.status)
    if exc.type_name == "DeadlineExceeded":
        # the plane shed a budget-expired rider (ISSUE 15): surface it
        # as the same fail-fast the local batcher would have raised
        return _adm.DeadlineExceeded(str(exc))
    return exc


def _graft_vec_spans(doc: Dict[str, Any], k: int) -> None:
    """Graft an OP_VEC response's plane-side span records into the
    live trace (ring.claim -> plane.coalesce -> device.dispatch with
    original timing), falling back to the single leader-stamped
    interval when the rider posted without a trace context. Also
    stamps the fleet node the router chose (ISSUE 13)."""
    spans = doc.get("spans")
    if spans:
        for sd in spans:
            obs.attach_span_tree(sd)
    else:
        obs.attach_span("broker.dispatch", doc["t0"], doc["t1"],
                        surface="broker", batch=doc["batch"], k=k)
    if doc.get("node"):
        obs.annotate(fleet_node=doc["node"])


class BrokerCompat:
    """Worker-side stand-in for QdrantCompat: every method forwards as
    a generic broker op to the real compat on the device plane, where
    concurrent ops from all workers coalesce through the existing
    MicroBatcher (searches) and BatchCoalescer (upsert convoys).
    Degrade records produced by an op ride back into THIS process's
    ledger; stage intervals re-record under surface ``broker``."""

    def __init__(self, client: BrokerClient):
        self._client = client

    @property
    def cache_gen(self) -> int:
        # shared-memory mirror of the plane's search-cache generation:
        # worker wire caches validate without a ring round trip
        return self._client.qdrant_gen()

    def _call(self, method: str, *args, **kwargs):
        try:
            doc = self._client.call("compat", method, *args, **kwargs)
        except BrokerTimeout:
            from nornicdb_tpu.api.qdrant import QdrantError

            _audit.record_degrade("wire", "broker", "error",
                                  "broker_timeout", index=method)
            raise QdrantError(
                "device plane unavailable (broker timeout)", status=503)
        except BrokerRemoteError as exc:
            raise _map_remote(exc) from None
        meta = doc.get("meta") or {}
        if self._client.cross_process:
            for rec in meta.get("degrades", ()):
                _audit.replay_degrade(rec)
        # plane-side span tree (ISSUE 13): graft it so this worker's
        # /admin/traces shows the op's full plane story under the
        # ingress root — same trace id on both sides of the ring
        for sd in meta.get("spans", ()):
            obs.attach_span_tree(sd)
        obs.record_stage("broker", "coalesce_wait",
                         doc["t0"] - doc["t_post"])
        obs.record_stage("broker", "apply", doc["t1"] - doc["t0"])
        # ring post->dispatch interval = this worker's measured queue
        # wait (ISSUE 15): the shedding verdict's signal
        _adm.CONTROLLER.note_wait(_adm.lane(), doc["t0"] - doc["t_post"])
        return doc["result"]

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        import functools

        return functools.partial(self._call, name)


class BrokerSearch:
    """Worker-side stand-in for the SearchService surface the gRPC
    servicers use. Raw vector search posts the embedding RAW onto the
    ring (OP_VEC) and rides a cross-worker batched device dispatch;
    hybrid/exact paths forward generically."""

    def __init__(self, client: BrokerClient):
        self._client = client

    @property
    def generation(self) -> int:
        return self._client.search_gen()

    def vector_search_candidates(self, query_vec, k: int = 10,
                                 exact: bool = False,
                                 lexical_doc_ids=None):
        if exact or lexical_doc_ids:
            doc = self._search_call("vector_search_candidates",
                                    np.asarray(query_vec, np.float32),
                                    k=k, exact=exact,
                                    lexical_doc_ids=lexical_doc_ids)
            return doc
        try:
            doc = self._client.vec_search(
                "__service__", np.asarray(query_vec, np.float32), k)
        except BrokerTimeout:
            _audit.record_degrade("vector", "broker", "error",
                                  "broker_timeout")
            raise RuntimeError(
                "device plane unavailable (broker timeout)")
        except BrokerRemoteError as exc:
            raise _map_remote(exc) from None
        now = time.time()
        obs.record_stage("broker", "coalesce_wait",
                         doc["t0"] - doc["t_post"])
        obs.record_stage("broker", "device_dispatch",
                         doc["t1"] - doc["t0"])
        obs.record_stage("broker", "merge", now - doc["t1"])
        _adm.CONTROLLER.note_wait(_adm.lane(), doc["t0"] - doc["t_post"])
        _graft_vec_spans(doc, k)
        _audit.set_last_served(doc.get("tier"))
        return doc["hits"]

    def _search_call(self, method: str, *args, **kwargs):
        try:
            doc = self._client.call("search", method, *args, **kwargs)
        except BrokerTimeout:
            _audit.record_degrade("vector", "broker", "error",
                                  "broker_timeout", index=method)
            raise RuntimeError(
                "device plane unavailable (broker timeout)")
        except BrokerRemoteError as exc:
            raise _map_remote(exc) from None
        meta = doc.get("meta") or {}
        if self._client.cross_process:
            for rec in meta.get("degrades", ()):
                _audit.replay_degrade(rec)
        for sd in meta.get("spans", ()):
            obs.attach_span_tree(sd)
        return doc["result"]

    def search(self, **kwargs):
        return self._search_call("search", **kwargs)


class _BrokerStorage:
    """Minimal storage facade for servicer fallbacks (point payload
    lookups); hot paths use the batched plane op instead."""

    def __init__(self, client: BrokerClient):
        self._client = client

    def get_node(self, node_id: str):
        try:
            return self._client.call("db", "storage.get_node",
                                     node_id)["result"]
        except BrokerRemoteError as exc:
            raise _map_remote(exc) from None


class _WorkerDB:
    """The db-shaped object a worker's GrpcServer is built over."""

    def __init__(self, client: BrokerClient):
        self._client = client
        self.qdrant_compat = BrokerCompat(client)
        self.search = BrokerSearch(client)
        self.storage = _BrokerStorage(client)
        self._data_dir = None

    def plane_call(self, method: str, *args, **kwargs):
        doc = self._client.call("plane", method, *args, **kwargs)
        return doc["result"]


# -- worker servicer overrides ----------------------------------------------


def _worker_servicers():
    """Built lazily so importing wire_plane never drags grpc in."""
    from nornicdb_tpu.api import wire_codec
    from nornicdb_tpu.api.grpc_server import SearchServicer
    from nornicdb_tpu.api.qdrant_official_grpc import (
        OfficialPointsServicer,
        _with_payload,
        _with_vectors,
        filter_to_dict,
    )
    from nornicdb_tpu.api.proto import nornic_pb2 as pb

    class WorkerSearchServicer(SearchServicer):
        """nornic.v1.SearchService in a frontend worker: raw vector
        rides the ring's coalesced OP_VEC; payloads come back in ONE
        batched plane op instead of a storage read per hit."""

        def Search(self, request):
            t0 = time.time()
            k = int(request.limit) or 10
            hits = self.db.search.vector_search_candidates(
                np.asarray(list(request.vector), dtype=np.float32), k=k)
            payloads = self.db.plane_call(
                "payload_json_many", [nid for nid, _ in hits])
            return pb.SearchResponse(
                hits=[pb.Hit(node_id=str(nid), score=float(score),
                             payload_json=payloads.get(nid, "{}"))
                      for nid, score in hits],
                took_ms=(time.time() - t0) * 1e3,
            )

    class WorkerPointsServicer(OfficialPointsServicer):
        """qdrant.Points in a frontend worker. Search assembles the
        reply ZERO-COPY: ranked point dicts from the plane splice
        straight into wire bytes (api/wire_codec.py) — no protobuf
        object graph in the worker, the only per-reply work after the
        encode is the 9-byte time splice.

        The HOT SHAPE — cosine collection, no filter, no vector echo —
        rides the ring's coalesced OP_VEC instead of a pickled
        full-fidelity OP_CALL (the PR 11 named headroom): the raw
        embedding posts straight onto the ring, coalesces across every
        worker into one batched device dispatch per collection, and one
        batched plane op hydrates payloads. Anything the fast path
        cannot prove sound — non-cosine distance, filters, a hydration
        under-fill from a racing delete — falls back to the
        full-fidelity ``search_points`` OP_CALL, never to a wrong or
        short answer."""

        def __init__(self, compat):
            super().__init__(compat)
            # collection eligibility briefs, validated against the
            # shared qdrant write generation (any write invalidates)
            self._fast_briefs: Dict[str, Tuple[int, Dict[str, Any]]] = {}

        def _fast_brief(self, name: str) -> Optional[Dict[str, Any]]:
            gen = self.compat._client.qdrant_gen()
            cached = self._fast_briefs.get(name)
            if cached is not None and cached[0] == gen:
                return cached[1]
            try:
                brief = self.compat._client.call(
                    "plane", "qdrant_fast_brief", name)["result"]
            except Exception:  # noqa: BLE001 — slow path decides
                return None
            if len(self._fast_briefs) > 256:
                self._fast_briefs.clear()
            self._fast_briefs[name] = (gen, brief)
            return brief

        def _fast_search(self, brief, request, limit: int, offset: int,
                         with_payload: bool, threshold, t0: float):
            """OP_VEC fast path; None = let the OP_CALL path serve."""
            vec = np.asarray(list(request.vector), dtype=np.float32)
            want = int(brief.get("size") or 0)
            if want and vec.shape[0] != want:
                from nornicdb_tpu.api.qdrant import QdrantError

                raise QdrantError(
                    f"search vector size {vec.shape[0]} != collection "
                    f"size {want}")
            try:
                doc = self.compat._client.vec_search(
                    "qdrant:" + brief["collection"], vec, limit + offset)
            except BrokerTimeout:
                from nornicdb_tpu.api.qdrant import QdrantError

                _audit.record_degrade("wire", "broker", "error",
                                      "broker_timeout",
                                      index=brief["collection"])
                raise QdrantError(
                    "device plane unavailable (broker timeout)",
                    status=503)
            except BrokerRemoteError as exc:
                raise _map_remote(exc) from None
            hits = doc.get("hits") or []
            obs.record_stage("broker", "coalesce_wait",
                             doc["t0"] - doc["t_post"])
            obs.record_stage("broker", "device_dispatch",
                             doc["t1"] - doc["t0"])
            _adm.CONTROLLER.note_wait(_adm.lane(),
                                      doc["t0"] - doc["t_post"])
            _graft_vec_spans(doc, limit + offset)
            _audit.set_last_served(doc.get("tier"))
            got = self.compat._client.call(
                "plane", "qdrant_points_brief", brief["collection"],
                [nid for nid, _ in hits],
                with_payload)["result"]
            by_id = got.get("points") or {}
            missing = sum(1 for nid, _ in hits if nid not in by_id)
            points = []
            for nid, score in hits:
                d = by_id.get(nid)
                if d is None:
                    continue  # deleted between dispatch and hydrate
                if threshold is not None and float(score) < threshold:
                    continue
                d = dict(d)
                d["score"] = float(score)
                points.append(d)
            if missing and len(points) < limit + offset \
                    and len(points) < int(got.get("total") or 0):
                # racing deletes displaced candidates the widening
                # rounds of the full path would have refilled
                return None
            return wire_codec.append_time(
                wire_codec.encode_search_response(points[offset:]),
                time.time() - t0)

        def Search(self, request):
            t0 = time.time()
            offset = (int(request.offset)
                      if request.HasField("offset") else 0)
            limit = int(request.limit) or 10
            query_filter = filter_to_dict(request.filter)
            with_payload = _with_payload(request.with_payload)
            with_vector = _with_vectors(request)
            threshold = (request.score_threshold
                         if request.HasField("score_threshold") else None)
            if query_filter is None and not with_vector:
                brief = self._fast_brief(request.collection_name)
                if brief and brief.get("ok"):
                    resp = self._fast_search(brief, request, limit,
                                             offset, with_payload,
                                             threshold, t0)
                    if resp is not None:
                        return resp
            hits = self.compat.search_points(
                request.collection_name,
                list(request.vector),
                limit=limit + offset,
                with_payload=with_payload,
                with_vector=with_vector,
                score_threshold=threshold,
                query_filter=query_filter,
            )
            return wire_codec.append_time(
                wire_codec.encode_search_response(hits[offset:]),
                time.time() - t0)

    return WorkerSearchServicer, WorkerPointsServicer


# -- worker HTTP frontend ---------------------------------------------------


class _WorkerHttpServer:
    """Lean HTTP frontend of one wire worker: the hot search route
    parses/serializes locally (device work via the broker), /metrics
    merges the shared plane's series exactly once, /readyz merges the
    plane verdict with broker reachability, and every other route
    forwards to the device plane's full router (rendered there)."""

    def __init__(self, worker_db: _WorkerDB, host: str, port: int,
                 worker_id: int):
        from nornicdb_tpu.cache import LRUCache

        self.db = worker_db
        self.host = host
        self.port = port
        self.worker_id = worker_id
        self._client = worker_db._client
        self._search_wire: LRUCache = LRUCache(max_size=512,
                                               ttl_seconds=300.0)
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        if self._client.cross_process:
            # the device plane as a fleet-telemetry source (ISSUE 13):
            # this worker's /admin/fleet merges its own registry with
            # the plane's. Thread mode shares ONE registry — a source
            # there would double-count every shared counter.
            obs.register_fleet_source(
                "plane", lambda: self.db.plane_call("metrics_state"))

    # -- route bodies --------------------------------------------------

    def _nornicdb_search(self, body: bytes, headers) -> Tuple[int, bytes]:
        from nornicdb_tpu.api.http_server import _json_default

        gen = self._client.search_gen()
        key = (headers.get("Authorization", ""), body)
        hit = self._search_wire.get(key)
        if hit is not None and hit[0] == gen:
            _audit.record_served("hybrid", "cached")
            return 200, hit[1]
        # miss-only admission verdict (ISSUE 15): a byte-fresh hit is
        # never shed; only a miss pays the broker round trip
        _adm.check("http", _adm.lane())
        status, payload = self.db.plane_call(
            "search_payload", body,
            headers.get("Authorization", ""))
        t_ser = time.perf_counter()
        data = json.dumps(payload, default=_json_default).encode()
        obs.record_stage("http", "serialize",
                         time.perf_counter() - t_ser)
        if status == 200:
            self._search_wire.put(key, (gen, data))
        return status, data

    def _metrics(self, accept: str = "") -> Tuple[str, str]:
        """(content_type, body). Content-negotiated like the main
        server's /metrics: an OpenMetrics Accept gets the exemplar-
        carrying exposition — including the PLANE's bucket exemplars,
        which ride the merged dump_state (ISSUE 13 satellite: they
        were silently dropped from worker scrapes before)."""
        from nornicdb_tpu.api.http_server import _accepts_openmetrics
        from nornicdb_tpu.obs.metrics import REGISTRY, render_merged

        om = _accepts_openmetrics(accept)
        ctype = (REGISTRY.OPENMETRICS_CONTENT_TYPE if om
                 else "text/plain; version=0.0.4")
        if not self._client.cross_process:
            # thread-mode workers share the plane's process registry:
            # the shared series are already here exactly once
            return ctype, (REGISTRY.render_openmetrics() if om
                           else REGISTRY.render())
        try:
            remote = self.db.plane_call("metrics_state")
        except Exception:  # noqa: BLE001 — scrape must not fail
            remote = []
        return ctype, render_merged([remote] if remote else [],
                                    openmetrics=om)

    def _admin_check(self, headers) -> None:
        """Admin routes served WORKER-locally still authorize on the
        plane (the authenticator lives there); raises the plane's
        HTTPError-equivalent through the broker on denial."""
        self.db.plane_call("admin_check",
                           headers.get("Authorization", ""))

    def _admin_traces(self, path: str) -> Dict[str, Any]:
        """This worker's own trace ring — the ingress roots with the
        plane-side spans grafted (a forwarded /admin/traces would show
        the PLANE's ring, not this worker's wire->ring chains)."""
        if path.endswith("/slowest"):
            return {"slow_ms": obs.TRACES.slow_ms,
                    "recorded": obs.TRACES.recorded,
                    "worker": self.worker_id,
                    "traces": obs.TRACES.slowest(limit=10)}
        return {"slow_ms": obs.TRACES.slow_ms,
                "recorded": obs.TRACES.recorded,
                "worker": self.worker_id,
                "traces": obs.TRACES.snapshot(limit=50)}

    def _admin_events(self, path: str) -> Dict[str, Any]:
        """Unified incident timeline, merged across the process seam:
        this worker's journal (broker-replayed degrades) plus the
        plane's (drains, failovers, quarantines), ordered causally —
        by timestamp, seq tie-break — with per-record origin."""
        limit = 100
        tail = path.rsplit("/", 1)[-1]
        if tail.isdigit():
            limit = int(tail)
        local = [{**rec, "origin": f"worker-{self.worker_id}"}
                 for rec in obs.event_snapshot(limit=limit)]
        doc = dict(obs.event_summary())
        if self._client.cross_process:
            try:
                remote = self.db.plane_call("events_state", limit)
                local += [{**rec, "origin": "plane"}
                          for rec in remote.get("events", ())]
                doc["plane"] = {k: remote.get(k)
                                for k in ("recorded", "by_kind")}
            except Exception:  # noqa: BLE001 — local timeline still serves
                doc["plane"] = "unreachable"
        local.sort(key=lambda r: (r.get("ts", 0.0), r.get("seq", 0)))
        doc["worker"] = self.worker_id
        doc["events"] = local[-limit:]
        return doc

    def _admin_tenants(self, path: str) -> Dict[str, Any]:
        """Per-tenant rollup over the MERGED registry view (ISSUE 18):
        this worker's own series plus the shared device plane's,
        exactly once — the same merge discipline as /metrics. Thread
        mode shares one registry, so the local dump already holds the
        whole truth."""
        from nornicdb_tpu.obs.metrics import dump_state, merge_states

        top = None
        tail = path.rsplit("/", 1)[-1]
        if tail.isdigit():
            top = int(tail)
        remotes: List[Any] = []
        if self._client.cross_process:
            try:
                remotes = [self.db.plane_call("metrics_state")]
            except Exception:  # noqa: BLE001 — local view still serves
                remotes = []
        merged = merge_states(dump_state(), remotes)
        doc = _tenant.tenants_summary(state=merged, top=top)
        doc["worker"] = self.worker_id
        return doc

    def _readyz(self) -> Tuple[int, Dict[str, Any]]:
        try:
            status, payload = self.db.plane_call("readyz")
        except Exception:  # noqa: BLE001
            return 503, {"status": "degraded",
                         "reasons": ["broker_unreachable"],
                         "worker": self.worker_id}
        payload = dict(payload)
        payload["worker"] = self.worker_id
        return status, payload

    def _forward(self, method: str, path: str, body: bytes,
                 headers) -> Tuple[int, str, bytes]:
        return tuple(self.db.plane_call(
            "route_rendered", method, path, body,
            {"Authorization": headers.get("Authorization", ""),
             "Accept": headers.get("Accept", "")}))

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "_WorkerHttpServer":
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True
            wbufsize = 64 * 1024

            def log_message(self, *args):
                pass

            def _reply_bytes(self, status: int, ctype: str,
                             data: bytes) -> None:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _handle(self, method: str) -> None:
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                path = self.path.split("?")[0]
                # ingress deadline + admission verdict (ISSUE 15): the
                # worker mints the budget like the main server; it
                # rides the broker ring to the plane in the slot
                # header. Shedding is worker-local (each frontend sees
                # its own in-flight pressure).
                dl, explicit = _adm.parse_deadline_header(
                    self.headers.get(_adm.DEADLINE_HEADER), "http")
                from nornicdb_tpu.api.http_server import _shed_lane_for

                lane = _shed_lane_for(method, path)
                # the wire-cached search route checks admission AFTER
                # its cache probe (a byte-fresh hit is never shed) —
                # inside _nornicdb_search; every other work route
                # checks here, before the broker round trip
                cached_route = (method == "POST"
                                and path == "/nornicdb/search")
                # tenant identity resolved at THIS ingress (ISSUE 18):
                # header first, multidb path namespace as fallback —
                # shed verdicts and cached serves attribute here, and
                # the identity rides the broker ring in the slot
                # header's packed trace context for plane-side work
                segs = [s for s in path.split("/") if s]
                namespace = (segs[1]
                             if len(segs) > 1 and segs[0] == "db"
                             else None)
                ten, ten_explicit = _tenant.resolve(
                    self.headers.get(_tenant.TENANT_HEADER), None,
                    namespace)
                with _tenant.tenant_scope(ten, explicit=ten_explicit), \
                        _adm.request_scope("http", dl, lane_name=lane,
                                           explicit=explicit):
                    if lane is not None and not cached_route:
                        try:
                            _adm.check("http", lane)
                        except _adm.ShedError as e:
                            self._reply_shed(e)
                            return
                    self._handle_admitted(method, path, body)

            def _reply_shed(self, e) -> None:
                data = json.dumps({"errors": [{
                    "code": "Neo.TransientError.Request."
                            "ResourceExhausted",
                    "message": str(e)}]}).encode()
                self.send_response(429)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.send_header("Retry-After", str(
                    max(1, int(round(e.retry_after_s)))))
                self.end_headers()
                self.wfile.write(data)

            def _handle_admitted(self, method: str, path: str,
                                 body: bytes) -> None:
                try:
                    if method == "POST" and path == "/nornicdb/search":
                        status, data = outer._nornicdb_search(
                            body, self.headers)
                        self._reply_bytes(status, "application/json",
                                          data)
                        return
                    if method == "GET" and path == "/metrics":
                        ctype, body = outer._metrics(
                            self.headers.get("Accept", ""))
                        self._reply_bytes(200, ctype, body.encode())
                        return
                    if method == "GET" and (
                            path == "/admin/traces"
                            or path == "/admin/traces/slowest"):
                        # worker-LOCAL: the ingress traces live here
                        outer._admin_check(self.headers)
                        self._reply_bytes(
                            200, "application/json",
                            json.dumps(outer._admin_traces(path),
                                       default=str).encode())
                        return
                    if method == "GET" and (
                            path == "/admin/events"
                            or path.startswith("/admin/events/")):
                        outer._admin_check(self.headers)
                        self._reply_bytes(
                            200, "application/json",
                            json.dumps(outer._admin_events(path),
                                       default=str).encode())
                        return
                    if method == "GET" and path == "/admin/fleet":
                        # merged local+plane view via the aggregator
                        # (the plane source registered at worker boot)
                        outer._admin_check(self.headers)
                        self._reply_bytes(
                            200, "application/json",
                            json.dumps(obs.fleet_summary(),
                                       default=str).encode())
                        return
                    if method == "GET" and (
                            path == "/admin/tenants"
                            or path.startswith("/admin/tenants/")):
                        # merged local+plane per-tenant rollup
                        outer._admin_check(self.headers)
                        self._reply_bytes(
                            200, "application/json",
                            json.dumps(outer._admin_tenants(path),
                                       default=str).encode())
                        return
                    if method == "GET" and path == "/readyz":
                        status, payload = outer._readyz()
                        self._reply_bytes(status, "application/json",
                                          json.dumps(payload).encode())
                        return
                    if method == "GET" and path == "/health":
                        self._reply_bytes(200, "application/json",
                                          b'{"status": "ok"}')
                        return
                    status, ctype, data = outer._forward(
                        method, self.path, body, self.headers)
                    self._reply_bytes(status, ctype, data)
                except _adm.ShedError as e:
                    # miss-path shed from the cached search route:
                    # honest 429 with the Retry-After header
                    self._reply_shed(e)
                    return
                except Exception as e:  # noqa: BLE001 — boundary
                    # a plane-side auth denial keeps its 401/403
                    # through the ring (BrokerRemoteError carries the
                    # remote HTTPError status), a shed keeps its 429
                    # and a deadline fail-fast its 504 (ISSUE 15);
                    # everything else stays the transient 503 it
                    # always was
                    status = getattr(e, "status", None)
                    if status not in (401, 403, 429, 504):
                        status = 503
                    self._reply_bytes(
                        status, "application/json",
                        json.dumps({"errors": [{
                            "code": "Neo.TransientError.General."
                                    "WirePlane",
                            "message": str(e)[:300]}]}).encode())

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_PUT(self):
                self._handle("PUT")

            def do_DELETE(self):
                self._handle("DELETE")

        from nornicdb_tpu.api.http_server import (
            ReuseportThreadingHTTPServer,
        )

        self._server = ReuseportThreadingHTTPServer(
            (self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"wire-http-{self.worker_id}", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._client.cross_process:
            obs.unregister_fleet_source("plane")
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


# -- one worker (grpc + http frontends over one BrokerClient) ---------------


class WireWorker:
    """One frontend worker: its own grpc.aio server + lean HTTP server,
    both SO_REUSEPORT-bound to the plane's shared ports, all device
    work funneled through its BrokerClient."""

    def __init__(self, spec: Dict[str, Any]):
        self.spec = spec
        self.worker_id = int(spec["worker_id"])
        self.client = BrokerClient(spec["broker"])
        # fleet admission posture (ISSUE 16): this worker's controller
        # publishes into / reads back the ring posture word — one
        # overloaded worker tightens every frontend's verdict
        self.client.bind_admission()
        self.worker_db = _WorkerDB(self.client)
        self.grpc = None
        self.http = None

    def start(self) -> "WireWorker":
        from nornicdb_tpu.api.grpc_server import GrpcServer

        search_cls, points_cls = _worker_servicers()
        want_port = int(self.spec["grpc_port"])
        self.grpc = GrpcServer(
            self.worker_db, host=self.spec["host"], port=want_port,
            search_servicer_cls=search_cls,
            points_servicer_cls=points_cls)
        if want_port and self.grpc.port != want_port:
            raise RuntimeError(
                f"worker {self.worker_id} failed SO_REUSEPORT bind on "
                f"{want_port} (got {self.grpc.port})")
        self.grpc.start()
        self.http = _WorkerHttpServer(
            self.worker_db, self.spec["host"],
            int(self.spec["http_port"]), self.worker_id).start()
        # readiness flag the plane polls: servers are bound and serving
        with open(self._ready_path(), "w") as f:
            f.write(str(os.getpid()))
        return self

    def _ready_path(self) -> str:
        return os.path.join(self.spec["broker"]["sock_dir"],
                            f"ready-{self.worker_id}")

    def _stop_path(self) -> str:
        return os.path.join(self.spec["broker"]["sock_dir"], "stop")

    def serve_forever(self) -> None:
        """Process-mode main loop: exit when the plane signals stop,
        the parent process died, or the broker went away for good."""
        ppid = os.getppid()
        stale_since = None
        while True:
            time.sleep(0.25)
            if os.path.exists(self._stop_path()):
                break
            if os.getppid() != ppid:
                break
            try:
                alive = self.client.broker_alive()
            except Exception:  # noqa: BLE001 — shm unlinked
                break
            if not alive:
                stale_since = stale_since or time.time()
                if time.time() - stale_since > 10.0:
                    break
            else:
                stale_since = None
        self.stop()

    def stop(self) -> None:
        try:
            if self.grpc is not None:
                self.grpc.stop()
        finally:
            if self.http is not None:
                self.http.stop()
            self.client.close()


def _worker_main(spec: Dict[str, Any]) -> None:
    """Process-mode entry (``python -m nornicdb_tpu.api.wire_plane
    --worker <json>``): build the worker, serve until the plane
    stops."""
    worker = WireWorker(spec)
    try:
        worker.start()
    except Exception:  # noqa: BLE001 — plane's ready-poll times out
        import traceback

        traceback.print_exc()
        try:
            worker.stop()
        finally:
            os._exit(1)
    worker.serve_forever()
    os._exit(0)


# -- plane-side ops exposed to workers --------------------------------------


class _PlaneOps:
    """The generic-op surface workers call on the device plane (target
    ``plane``): batched payload fetches, rendered route forwarding,
    readiness, and the metrics snapshot the worker scrape merges."""

    def __init__(self, plane: "WirePlane"):
        self._plane = plane

    def payload_json_many(self, ids: List[str]) -> Dict[str, str]:
        out: Dict[str, str] = {}
        storage = self._plane.db.storage
        for nid in ids:
            try:
                node = storage.get_node(nid)
                out[nid] = json.dumps(node.properties, default=str)
            except Exception:  # noqa: BLE001
                out[nid] = "{}"
        return out

    def search_payload(self, body: bytes, auth: str = ""):
        from nornicdb_tpu.api.http_server import HTTPError

        try:
            return self._plane.parent_http.route(
                "POST", "/nornicdb/search", body,
                {"Authorization": auth} if auth else {})
        except HTTPError as e:
            # client errors keep their status through the ring instead
            # of surfacing as a broker-side 503
            return (e.status, {"errors": [{"code": e.code,
                                           "message": e.message}]})

    def route_rendered(self, method: str, path: str, body: bytes,
                       headers: Dict[str, str]):
        from nornicdb_tpu.api.http_server import (
            HTTPError,
            _json_default,
            _NegotiatedText,
        )

        try:
            status, payload = self._plane.parent_http.route(
                method, path, body, headers or {})
        except HTTPError as e:
            return (e.status, "application/json", json.dumps(
                {"errors": [{"code": e.code,
                             "message": e.message}]}).encode())
        if isinstance(payload, _NegotiatedText):
            return (status, payload.content_type, payload.encode())
        if isinstance(payload, str):
            ctype = ("text/html; charset=utf-8"
                     if payload.lstrip().startswith("<")
                     else "text/plain; version=0.0.4")
            return (status, ctype, payload.encode())
        return (status, "application/json",
                json.dumps(payload, default=_json_default).encode())

    def readyz(self):
        return self._plane.parent_http._readyz()

    def metrics_state(self):
        from nornicdb_tpu.obs.metrics import dump_state

        return dump_state()

    def events_state(self, limit: int = 100):
        """The plane's incident-timeline slice for a worker's merged
        ``/admin/events`` view (ISSUE 13)."""
        doc = dict(obs.event_summary())
        doc["events"] = obs.event_snapshot(limit=int(limit))
        return doc

    def admin_check(self, auth: str = "") -> bool:
        """Authorize a worker-local admin route on the plane (the
        authenticator lives here); raises the HTTPError — carrying its
        401/403 status — back through the ring on denial."""
        http = self._plane.parent_http
        username = http.authenticate(
            {"Authorization": auth} if auth else {})
        from nornicdb_tpu.auth import ADMIN

        http.authorize(username, "system", ADMIN)
        return True

    # -- qdrant OP_VEC fast path (ISSUE 12 satellite) ------------------

    def qdrant_fast_brief(self, name: str) -> Dict[str, Any]:
        """Eligibility brief for the worker's OP_VEC qdrant Search fast
        path: alias-resolved collection name, distance and vector size.
        Only Cosine collections are eligible (the coalesced device
        index serves cosine; Dot/Euclid ride the raw-matrix path)."""
        compat = self._plane.db.qdrant_compat
        try:
            resolved = compat.resolve(name)
            meta = compat._meta(resolved)
        except Exception:  # noqa: BLE001 — missing collections 404 on
            # the slow path with the full error mapping
            return {"ok": False}
        cfg = meta.properties.get("config", {}) or {}
        return {
            "ok": cfg.get("distance", "Cosine") == "Cosine",
            "collection": resolved,
            "size": int(cfg.get("size", 0) or 0),
            "distance": cfg.get("distance", "Cosine"),
        }

    def qdrant_points_brief(self, name: str, ids: List[str],
                            with_payload: bool = True) -> Dict[str, Any]:
        """Batched hydration for OP_VEC-ranked collection hits: point
        dicts (scoreless — the worker splices its own scores) keyed by
        node id, plus the live point count so the worker can detect a
        racing-delete under-fill and fall back."""
        compat = self._plane.db.qdrant_compat
        storage = self._plane.db.storage
        points: Dict[str, Any] = {}
        for nid in ids:
            try:
                node = storage.get_node(nid)
            except Exception:  # noqa: BLE001 — deleted mid-flight
                continue
            points[nid] = compat._point_dict(node, with_payload, False)
        try:
            total = len(compat._index(compat.resolve(name)))
        except Exception:  # noqa: BLE001
            total = len(points)
        return {"points": points, "total": total}


# -- the plane --------------------------------------------------------------


def _reserve_port(host: str, port: int) -> Tuple[socket.socket, int]:
    """Bind (not listen) a placeholder SO_REUSEPORT socket so the port
    number is fixed before any worker boots; workers join the reuseport
    group, the placeholder never accepts."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    s.bind((host, port))
    return s, s.getsockname()[1]


class WirePlane:
    """N frontend workers + one broker over one device plane (one DB).

    ``workers <= 1`` is not served here — callers keep today's
    single-process GrpcServer/HttpServer path; the plane exists to add
    frontends, so it requires ``workers >= 2``."""

    def __init__(self, db, workers: Optional[int] = None,
                 host: str = "127.0.0.1", grpc_port: int = 0,
                 http_port: int = 0, mode: Optional[str] = None,
                 slot_bytes: Optional[int] = None,
                 timeout_s: Optional[float] = None,
                 authenticator=None, fleet=None):
        from nornicdb_tpu.api.http_server import HttpServer

        self.db = db
        # replica-aware read routing (ISSUE 12): with a FleetRouter the
        # plane's coalesced vector dispatches and the workers' generic
        # search/qdrant READ ops fan across admitted+ready replicas
        # (writes keep funneling to this db, the primary); None keeps
        # the single-node plane exactly as before
        self.fleet = fleet
        self.workers = workers if workers is not None \
            else wire_workers_from_env(2)
        if self.workers < 2:
            raise ValueError(
                "WirePlane needs >= 2 workers; use GrpcServer/"
                "HttpServer directly for single-process serving")
        self.mode = (mode or wire_worker_mode())
        self.host = host
        # full router instance for forwarded REST routes + /readyz —
        # never started: route() is a plain method over the db
        self.parent_http = HttpServer(db, port=0,
                                      authenticator=authenticator)
        self._plane_ops = _PlaneOps(self)
        compat = db.qdrant_compat
        target_compat = fleet.routed_compat() if fleet is not None \
            else compat
        target_search = fleet.routed_search() if fleet is not None \
            else db.search
        self.broker = DispatchBroker(
            self._vec_dispatch,
            targets={"compat": target_compat, "search": target_search,
                     "db": db, "plane": self._plane_ops},
            n_workers=self.workers, slot_bytes=slot_bytes)
        self._timeout_s = timeout_s
        # the device plane's controller shares the same posture word
        # as the wire workers (ISSUE 16)
        self.broker.bind_admission()
        obs.register_resource("queue", "broker", self.broker)
        # write-generation mirrors: worker wire caches validate against
        # shared memory instead of a broker round trip
        compat._search_cache.set_generation_mirror(
            self.broker.set_qdrant_gen)
        db.search._result_cache.set_generation_mirror(
            self.broker.set_search_gen)
        self._grpc_sock, self.grpc_port = _reserve_port(host, grpc_port)
        self._http_sock, self.http_port = _reserve_port(host, http_port)
        self._procs: List[Any] = []
        self._thread_workers: List[WireWorker] = []
        self._started = False

    # -- device-plane dispatch targets ---------------------------------

    def _local_vec_dispatch(self, key: str, queries: np.ndarray, k: int):
        return resolve_vec_dispatch(self.db, key, queries, k)

    def _vec_dispatch(self, key: str, queries: np.ndarray, k: int):
        if self.fleet is not None:
            return self.fleet.vec_dispatch(key, queries, k,
                                           self._local_vec_dispatch)
        return self._local_vec_dispatch(key, queries, k)

    # -- lifecycle -----------------------------------------------------

    def _spec(self, wid: int) -> Dict[str, Any]:
        spec = {
            "worker_id": wid,
            "host": self.host,
            "grpc_port": self.grpc_port,
            "http_port": self.http_port,
            "broker": self.broker.client_spec(
                wid, cross_process=(self.mode == "process")),
        }
        if self._timeout_s is not None:
            spec["broker"]["timeout_s"] = self._timeout_s
        return spec

    def start(self, ready_timeout_s: Optional[float] = None
              ) -> "WirePlane":
        self.broker.start()
        if self.mode == "thread":
            for wid in range(self.workers):
                self._thread_workers.append(
                    WireWorker(self._spec(wid)).start())
        else:
            # subprocess + module entry, not multiprocessing spawn:
            # spawn re-imports the parent's __main__ (breaks under
            # embedded/driver mains), while `-m ...wire_plane --worker`
            # gives each frontend a clean interpreter whose only job
            # is this JSON spec
            import subprocess
            import sys

            import nornicdb_tpu as _pkg
            from nornicdb_tpu.jaxenv import cpu_child_env

            # the worker interpreter must resolve this package no
            # matter the caller's cwd: prepend the package parent
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.abspath(_pkg.__file__)))
            # frontends never own the chip: the device plane holds it
            env = cpu_child_env()
            env["PYTHONPATH"] = pkg_root + (
                os.pathsep + env["PYTHONPATH"]
                if env.get("PYTHONPATH") else "")
            for wid in range(self.workers):
                # stderr to a file, not a pipe: nobody drains a pipe
                # during serving, and a full pipe buffer would block
                # the worker mid-write
                err_path = os.path.join(self.broker.sock_dir,
                                        f"worker{wid}.err")
                with open(err_path, "wb") as err_f:
                    p = subprocess.Popen(
                        [sys.executable, "-m",
                         "nornicdb_tpu.api.wire_plane", "--worker",
                         json.dumps(self._spec(wid))],
                        stdout=subprocess.DEVNULL,
                        stderr=err_f, env=env)
                p._nornic_err_path = err_path
                self._procs.append(p)
            timeout = ready_timeout_s or 90.0
            deadline = time.time() + timeout
            missing = set(range(self.workers))
            while missing and time.time() < deadline:
                for wid in list(missing):
                    if os.path.exists(os.path.join(
                            self.broker.sock_dir, f"ready-{wid}")):
                        missing.discard(wid)
                dead = [p for p in self._procs if p.poll() is not None]
                if dead:
                    err = ""
                    try:
                        with open(dead[0]._nornic_err_path, "rb") as f:
                            err = f.read().decode(
                                errors="replace")[-800:]
                    except OSError:
                        pass
                    self.stop()
                    raise RuntimeError(
                        f"wire worker died during startup: {err}")
                if missing:
                    time.sleep(0.05)
            if missing:
                self.stop()
                raise RuntimeError(
                    f"wire workers {sorted(missing)} not ready within "
                    f"{timeout:.0f}s")
        self._started = True
        return self

    def stop(self) -> None:
        try:
            with open(os.path.join(self.broker.sock_dir, "stop"),
                      "w") as f:
                f.write("1")
        except OSError:
            pass
        for w in self._thread_workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001
                pass
        self._thread_workers = []
        for p in self._procs:
            try:
                p.wait(timeout=3)
            except Exception:  # noqa: BLE001
                p.terminate()
                try:
                    p.wait(timeout=3)
                except Exception:  # noqa: BLE001
                    p.kill()
        self._procs = []
        try:
            self.db.qdrant_compat._search_cache.set_generation_mirror(
                None)
            self.db.search._result_cache.set_generation_mirror(None)
        except Exception:  # noqa: BLE001
            pass
        obs.resources.unregister("queue", "broker")
        sock_dir = self.broker.sock_dir
        self.broker.stop()
        import shutil

        shutil.rmtree(sock_dir, ignore_errors=True)
        for s in (self._grpc_sock, self._http_sock):
            try:
                s.close()
            except OSError:
                pass

    @property
    def grpc_address(self) -> str:
        return f"{self.host}:{self.grpc_port}"


if __name__ == "__main__":  # worker process entry
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", required=True,
                    help="JSON worker spec from WirePlane._spec")
    _args = ap.parse_args()
    _worker_main(json.loads(_args.worker))
