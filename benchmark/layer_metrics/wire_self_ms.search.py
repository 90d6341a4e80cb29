"""Mean self time of the HTTP request span of a search: the root ``wire``
span's duration less the part its child spans cover (parse, admission,
routing, JSON encode and the socket write are what is left)."""

from benchmark.lib.stats import covered_seconds, merge_intervals


def read(observed):
    total, n = 0.0, 0
    for root in observed.spans:
        if root["name"] != "wire" or not str(
                root["attrs"].get("method", "")).endswith("/points/search"):
            continue
        a = root["start_ms"]
        b = a + root["duration_ms"]
        kids = merge_intervals(
            (c["start_ms"], c["start_ms"] + c["duration_ms"])
            for c in root.get("children", ()))
        total += (b - a) - covered_seconds(a, b, kids)
        n += 1
    return total / n if n else None
