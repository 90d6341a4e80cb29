"""Bytes a filtered exact scan needs, from shapes alone (as ``costs.py``
counts the plain scan: what the mathematics requires, not what an
implementation happens to do).

To rank exactly inside a filter every row that passes must be read once:
``pass_rows`` rows of ``dims`` float32. Rows that do not pass need not be
read at all (a layout that keeps a tenant's rows together reads none of
them), so they count nothing: the program of PR 34 masks a scan of the
whole matrix and reads 64 times this at 64 equal tenants, which is what
its share of this roofline says. A batch needs at least ONE tenant's rows
whatever tenants its riders bring, so this is a floor of every execution's
least time and the share cannot pass 100%, whether the program masks a
full scan or one day reads a tenant's rows only. ``costs.scan_cost``'s
whole matrix would read over 100% on that day. The payload codes of the
rows read (4 B beside 4,096 B), the query block and the running top-k are
under a thousandth and are left out; the arithmetic (2 x B x pass_rows x
dims FLOPs) is far under the bytes bound at every batch the coalescer
seals, which the reader checks."""

from __future__ import annotations

from typing import Tuple


def filtered_scan_cost(pass_rows: int, dims: int, batch: int = 1,
                       itemsize: int = 4) -> Tuple[float, float]:
    """(FLOPs, bytes) of one exact cosine scan of the ``pass_rows`` rows a
    filter lets through, ``batch`` riders sharing it."""
    flops = 2.0 * batch * pass_rows * dims
    byts = float(pass_rows) * dims * itemsize
    return flops, byts
