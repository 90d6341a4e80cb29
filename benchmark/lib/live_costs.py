"""Bytes the index's in-place update needs, from shapes alone (as
``costs.py`` counts the scan: what the mathematics requires, not what an
implementation happens to do).

Writing ``rows`` rows of ``dims`` float32 into a resident matrix touches
each row's bytes twice: the new row read from where the host put it, and
written into the matrix. Slots and validity are a thousandth of that and
are left out; the rows a program pads its round to are no work. There is
no arithmetic: the bytes bound is the only one."""

from __future__ import annotations

from typing import Dict


def update_bytes(rows: int, dims: int, itemsize: int = 4) -> float:
    return 2.0 * rows * dims * itemsize


def update_least_seconds(rows: int, dims: int,
                         peak: Dict[str, object]) -> float:
    return update_bytes(rows, dims) / float(peak["bytes_per_s"])
