"""Operations and bytes one fused hybrid dispatch needs, from shapes alone
(as ``costs.py`` counts the scan and the encoder: what the mathematics
requires, not what an implementation happens to do).

One dispatch scores a batch of ``batch`` queries against every passage on
both sides:

vector side   the ``capacity x dims`` float32 matrix read once, and
              ``2 x batch x capacity x dims`` FLOPs;
lexical side  ``entries`` postings gathered (a document row, a term
              frequency and that document's length: ``posting_bytes``
              each), written into a ``terms_rows x lex_capacity`` float32
              matrix of term-frequency norms that is filled once (zeros
              where no posting lands) and read once by the
              ``[batch, terms_rows] x [terms_rows, lex_capacity]`` product
              that weights it by idf: ``2 x terms_rows x lex_capacity x 4``
              bytes and ``2 x batch x terms_rows x lex_capacity`` FLOPs.
              ``batch`` is the riders of the batch and ``terms_rows``
              its distinct scoring terms, NOT the power-of-two rows a
              program pads either to: padding is no work.

The two ``[batch, capacity]`` score matrices, the top-k and the fuse are
under a fiftieth of that at every batch the coalescer seals and are left
out, so the share reads a little high, never low."""

from __future__ import annotations

from typing import Tuple


def padded(n: int, minimum: int = 256) -> int:
    """The capacity a collection of ``n`` rows is padded to: the next
    power-of-two multiple of ``minimum`` (hand-checked in the tests:
    1,048,576 rows -> 1,048,576; 6,000 -> 8,192)."""
    capacity = minimum
    while capacity < n:
        capacity *= 2
    return capacity


def hybrid_cost(batch: int, terms_rows: int, entries: int, capacity: int,
                dims: int, lex_capacity: int,
                posting_bytes: int = 8) -> Tuple[float, float]:
    """(FLOPs, bytes) of one fused dispatch."""
    flops = 2.0 * batch * capacity * dims \
        + 2.0 * batch * terms_rows * lex_capacity
    byts = float(capacity) * dims * 4 \
        + float(entries) * posting_bytes \
        + 2.0 * terms_rows * lex_capacity * 4
    return flops, byts
