"""The one traffic generator. A traffic mix is a JSON file of parameters
(``benchmark/traffic/<name>.json``); this module turns it into load.

``loop``
    ``closed``: ``clients`` callers, each sending its next request when the
    last one is answered (callers that wait for their reply).
``ramp_s``, ``ramp_requests``
    the callers start before the measured window opens, so the window is
    cut out of a stream that is already steady: the window opens once
    ``ramp_s`` seconds have passed and ``ramp_requests`` requests (default
    0) have been answered, whichever comes later, so a ramp counted in
    requests stays past the same point of the program's life however fast
    the program is. The callers stop sending when the window closes, and
    requests in flight then are waited for.
``in_flight``
    optional ``{"min": a, "max": b}``: a caller holds its next request
    while the system's own count of work accepted and not yet finished is
    at ``b`` or more, until it has fallen to ``a`` (a bulk importer that
    keeps a bounded backlog).

``open_at``, ``close_at`` (from the system, not the mix)
    where the system's work comes in periods (an import whose stream
    repeats a block of documents), it may place the window's edges on
    them: ``open_at()`` gives the time of the first period boundary the
    ramp has passed, or ``None`` while there is none, and
    ``close_at(t_open, seconds)`` the time of the first boundary at least
    ``seconds`` after the opening. Every window then holds whole periods,
    the same work, and is ``seconds`` long or up to one period longer
    (``close_max_s`` at the most: then it closes where it is).

What a request is (its path, its bytes, what its reply must hold) belongs
to the system under test: the system hands ``drive`` a ``make`` and a
``judge``. Everything is made from the seed by (client, sequence number),
so the same seed gives every client the same requests whatever the timing.
Clients never touch JAX.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmark.lib.client import Client


class Reply:
    """One request as the client saw it."""

    __slots__ = ("client", "seq", "t_send", "t_done", "status", "ok",
                 "kept")

    def __init__(self, client: int, seq: int, t_send: float, t_done: float,
                 status: int, ok: bool, kept: Any) -> None:
        self.client = client
        self.seq = seq
        self.t_send = t_send
        self.t_done = t_done
        self.status = status
        self.ok = ok
        self.kept = kept


class _Gate:
    """Hysteresis on the system's own in-flight count."""

    def __init__(self, spec: Optional[Dict[str, int]],
                 gauge: Optional[Callable[[], int]]) -> None:
        self._spec = spec if gauge is not None else None
        self._gauge = gauge
        self._held = False
        self._lock = threading.Lock()

    def wait(self, stop: threading.Event) -> None:
        if self._spec is None:
            return
        while not stop.is_set():
            n = self._gauge()
            with self._lock:
                if self._held and n <= self._spec["min"]:
                    self._held = False
                elif not self._held and n >= self._spec["max"]:
                    self._held = True
                held = self._held
            if not held:
                return
            time.sleep(0.002)


def drive(port: int, traffic: Dict[str, Any], seconds: float,
          make: Callable[[int, int], Tuple[str, bytes, Any]],
          judge: Callable[[int, bytes, Any], Tuple[bool, Any]],
          headers: Optional[Dict[str, str]] = None,
          gauge: Optional[Callable[[], int]] = None,
          on_open: Optional[Callable[[], None]] = None,
          on_close: Optional[Callable[[], None]] = None,
          on_tick: Optional[Callable[[float], None]] = None,
          annotate: Optional[Callable[[str], Any]] = None,
          open_at: Optional[Callable[[], Optional[float]]] = None,
          close_at: Optional[Callable[[float, float],
                                      Optional[float]]] = None,
          ) -> Tuple[float, float, List[Reply]]:
    """Run the mix against ``127.0.0.1:port``. Returns the window
    ``(t_open, t_close)`` on ``time.perf_counter``'s clock and every reply,
    ramp included. ``on_open``/``on_close`` run on the calling thread as the
    window opens and closes, ``on_tick(elapsed)`` every few milliseconds
    in between (the traced run starts and stops the profiler from these);
    ``annotate(name)`` gives a context manager put round each
    request in a traced run."""
    if traffic.get("loop") != "closed":
        raise ValueError(f"traffic loop {traffic.get('loop')!r}: this "
                         f"generator drives closed loops")
    n_clients = int(traffic["clients"])
    ramp_s = float(traffic.get("ramp_s", 0.0))
    ramp_requests = int(traffic.get("ramp_requests", 0))
    ramp_max_s = float(traffic.get("ramp_max_s", 180.0))
    close_max_s = float(traffic.get("close_max_s", 30.0))
    client = Client(port, headers=headers)
    stop = threading.Event()
    gate = _Gate(traffic.get("in_flight"), gauge)
    replies: List[List[Reply]] = [[] for _ in range(n_clients)]
    errors: List[BaseException] = []

    def run(k: int) -> None:
        seq = 0
        try:
            while not stop.is_set():
                gate.wait(stop)
                if stop.is_set():
                    break
                path, body, meta = make(k, seq)
                t_send = time.perf_counter()
                if annotate is not None:
                    with annotate("bench:client.request"):
                        status, raw = client.post(path, body)
                else:
                    status, raw = client.post(path, body)
                t_done = time.perf_counter()
                ok, kept = judge(status, raw, meta)
                replies[k].append(Reply(k, seq, t_send, t_done, status,
                                        ok, kept))
                seq += 1
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)
            stop.set()

    threads = [threading.Thread(target=run, args=(k,), daemon=True,
                                name=f"bench-client-{k}")
               for k in range(n_clients)]
    for t in threads:
        t.start()
    t_ramp = time.perf_counter()
    while not stop.is_set():
        ramped = time.perf_counter() - t_ramp
        if ramped >= ramp_s and sum(map(len, replies)) >= ramp_requests:
            t_open = time.perf_counter() if open_at is None else open_at()
            if t_open is not None:
                break
        if ramped > ramp_max_s:
            errors.append(RuntimeError(
                f"the ramp answered {sum(map(len, replies))} of "
                f"{ramp_requests} requests in {ramp_max_s:.0f} s"))
            stop.set()
        time.sleep(0.01)
    if stop.is_set():           # a client failed, or the ramp never ended
        for t in threads:
            t.join(timeout=120.0)
        raise errors[0]
    if on_open is not None:
        on_open()
    if open_at is None:
        t_open = time.perf_counter()
    t_close = None
    while not stop.is_set():
        elapsed = time.perf_counter() - t_open
        if elapsed >= seconds:
            if close_at is None or elapsed >= seconds + close_max_s:
                t_close = time.perf_counter()
            else:
                t_close = close_at(t_open, seconds)
            if t_close is not None:
                break
        if on_tick is not None:
            on_tick(elapsed)
        time.sleep(0.005)
    if t_close is None:
        t_close = time.perf_counter()
    stop.set()
    if on_close is not None:
        on_close()
    for t in threads:
        t.join(timeout=120.0)
        if t.is_alive():
            raise RuntimeError("a client did not finish within 120 s of "
                               "the window's close")
    if errors:
        raise errors[0]
    return t_open, t_close, [r for rs in replies for r in rs]
