"""Share of the window's filtered searches whose filter the scan evaluated
itself: growth of ``nornicdb_qdrant_filtered_search_total{tier="device"}``
over its growth for every tier (``host``: ranked without the filter and
filtered point by point afterwards; ``empty``: no point could pass), read
from ``/metrics`` as the window opened and closed. Under 99 the cell is
measuring the host post-filter, not the deployment. ``None`` where the
program has no such counter."""

import re

SERIES = re.compile(
    r'^nornicdb_qdrant_filtered_search_total\{tier="([^"]+)"\}$')


def read(observed):
    device = total = 0.0
    for key, after in observed.prom_after.items():
        m = SERIES.match(key)
        if m is None:
            continue
        grew = after - observed.prom_before.get(key, 0.0)
        total += grew
        if m.group(1) == "device":
            device += grew
    return 100.0 * device / total if total > 0 else None
