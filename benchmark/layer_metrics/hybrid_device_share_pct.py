"""Share of the window's hybrid searches that the fused device tier
served: growth of ``nornicdb_served_tier_total{surface="hybrid"}`` for the
tiers that start with ``hybrid_`` (the device rungs of the ladder) over its
growth for every tier (``host``, the fallback; ``cached``, the result
cache), read from ``/metrics`` as the window opened and closed. Under 99
the cell is measuring the host path, and its line says so."""

import re

SERIES = re.compile(
    r'^nornicdb_served_tier_total\{surface="hybrid",tier="([^"]+)"\}$')


def read(observed):
    device = total = 0.0
    for key, after in observed.prom_after.items():
        m = SERIES.match(key)
        if m is None:
            continue
        grew = after - observed.prom_before.get(key, 0.0)
        total += grew
        if m.group(1).startswith("hybrid_"):
            device += grew
    return 100.0 * device / total if total > 0 else None
