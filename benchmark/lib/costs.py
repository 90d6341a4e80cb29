"""Operations and bytes the algorithms need, from shapes alone.

These are what a roofline or utilisation share divides by, so they live
with the benchmark: a PR that claims a gain cannot change them. Each counts
what the mathematics requires, not what an implementation happens to do
(six bf16 passes for a float32 product, recomputation, padding the caller
did not ask for)."""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def scan_cost(batch: int, capacity: int, dims: int,
              itemsize: int = 4) -> Tuple[float, float]:
    """(FLOPs, bytes) of one exact cosine scan: ``batch`` normalised
    queries against a ``capacity x dims`` matrix read once from HBM.
    The query block, the validity mask and the running top-k are under a
    thousandth of the matrix and are left out."""
    flops = 2.0 * batch * capacity * dims
    byts = float(capacity) * dims * itemsize
    return flops, byts


def least_seconds(flops: float, byts: float,
                  peak: Dict[str, object]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    by_flops = flops / float(peak["flops_per_s"])
    by_bytes = byts / float(peak["bytes_per_s"])
    return (by_flops, "flops") if by_flops >= by_bytes \
        else (by_bytes, "bytes")


def encoder_flops(hidden: int, layers: int, mlp: int,
                  rows: Iterable[int]) -> float:
    """FLOPs of the encoder's forward pass over rows of the given token
    lengths: per token and layer the four attention projections
    (4 x hidden^2 multiply-adds) and the two MLP products
    (2 x hidden x mlp), and per row and layer the two attention products
    over its own length (2 x S^2 x hidden multiply-adds). The embedding
    tables are lookups and the norms, softmax and pooling are not matrix
    work: they count nothing."""
    per_token = 2.0 * (4 * hidden * hidden + 2 * hidden * mlp)
    total = 0.0
    for s in rows:
        total += layers * (s * per_token + 4.0 * s * s * hidden)
    return total
