"""The writer's requests for a collection that is written while it is read.

Write request ``n`` of a run is a function of the seed alone: ``new`` points
under ids no point has yet (``first_new_id + n x new`` onwards) and ``over``
overwrites of base rows drawn uniformly, every vector a fresh draw from the
mixture the collection was made from (``systems/qdrant_collection.
make_vectors``: a centre plus ``spread`` x unit noise, scaled to length 1),
every payload ``payload_of(id)``.

A request's JSON body is 1.4 MB of text and takes ~30 ms of an interpreter
to write. The benchmark's clients share a process, and so an interpreter,
with the server they measure, so the bodies are written by a child process
(this module run with ``-m`` from the checkout's root: NumPy and the standard
library, no JAX) that
stays one request ahead and hands each over a pipe with the float32 rows it
holds: what the timed process spends on a write is the socket. Floats are
written with nine significant digits, which a float32 survives: the server
stores exactly the rows the log keeps.
"""

from __future__ import annotations

import json
import struct
import subprocess
import sys
from typing import Any, BinaryIO, Dict, Optional, Tuple

import numpy as np

from benchmark.lib.loader import ROOT
from benchmark.systems.qdrant_collection import payload_of

HEADER = struct.Struct("<II")          # body bytes, points


class Writes:
    """``request(n)`` -> (ids int64 [p], rows float32 [p, dims], body)."""

    def __init__(self, seed: int, rows: int, dims: int, centers: int,
                 spread: float, new: int, over: int) -> None:
        self.seed, self.rows, self.dims = int(seed), int(rows), int(dims)
        self.spread = np.float32(spread)
        self.new, self.over = int(new), int(over)
        self.centres = np.random.default_rng([self.seed, 1]).standard_normal(
            (int(centers), self.dims), dtype=np.float32)
        self._row_fmt = "[" + ",".join(["%.9g"] * self.dims) + "]"

    def points(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng([self.seed, 5, n])
        over = np.unique(rng.integers(0, self.rows, self.over))
        first = self.rows + n * self.new
        ids = np.concatenate([np.arange(first, first + self.new), over]
                             ).astype(np.int64)
        vec = rng.standard_normal((len(ids), self.dims), dtype=np.float32)
        vec *= self.spread
        vec += self.centres[rng.integers(0, len(self.centres), len(ids))]
        vec /= np.sqrt(np.einsum("ij,ij->i", vec, vec,
                                 dtype=np.float32))[:, None]
        return ids, vec

    def body(self, ids: np.ndarray, vec: np.ndarray) -> bytes:
        parts = ['{"id":%d,"vector":%s,"payload":%s}'
                 % (int(i), self._row_fmt % tuple(v.tolist()),
                    json.dumps(payload_of(int(i))))
                 for i, v in zip(ids, vec)]
        return ('{"points":[' + ",".join(parts) + "]}").encode()

    def request(self, n: int) -> Tuple[np.ndarray, np.ndarray, bytes]:
        ids, vec = self.points(n)
        return ids, vec, self.body(ids, vec)


class Producer:
    """The child process that writes the bodies, and the pipe's other end."""

    def __init__(self, **spec: Any) -> None:
        self._dims = int(spec["dims"])
        self._proc: Optional[subprocess.Popen] = subprocess.Popen(
            [sys.executable, "-m", "benchmark.lib.live_writes",
             json.dumps(spec)],
            cwd=ROOT, stdout=subprocess.PIPE, stdin=subprocess.DEVNULL)

    def _read(self, n: int) -> bytes:
        data = self._proc.stdout.read(n)
        if len(data) != n:
            raise RuntimeError(
                f"the writer's producer ended (exit code "
                f"{self._proc.poll()}) with {len(data)} of {n} bytes read")
        return data

    def next(self) -> Tuple[np.ndarray, np.ndarray, bytes]:
        body_len, points = HEADER.unpack(self._read(HEADER.size))
        body = self._read(body_len)
        ids = np.frombuffer(self._read(8 * points), np.int64)
        vec = np.frombuffer(self._read(4 * points * self._dims),
                            np.float32).reshape(points, self._dims)
        return ids, vec, body

    def close(self) -> None:
        proc, self._proc = self._proc, None
        if proc is not None:
            proc.kill()
            proc.stdout.close()
            proc.wait(timeout=30)


def _produce(spec: Dict[str, Any], out: BinaryIO) -> None:
    writes = Writes(**spec)
    n = 0
    while True:                 # until the pipe's reader goes away
        ids, vec, body = writes.request(n)
        out.write(HEADER.pack(len(body), len(ids)))
        out.write(body)
        out.write(ids.tobytes())
        out.write(vec.tobytes())
        out.flush()
        n += 1


if __name__ == "__main__":
    try:
        _produce(json.loads(sys.argv[1]), sys.stdout.buffer)
    except BrokenPipeError:
        pass
