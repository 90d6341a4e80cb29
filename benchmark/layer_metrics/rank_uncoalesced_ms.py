"""Mean ``qdrant.rank`` span less the parts of it that the coalescer's
``coalesce.wait`` and ``device.dispatch`` spans cover: what a request
spends ranking outside the coalesced round. That is NOT host time alone:
it holds the request's own un-coalesced widening search whole (the wait
for the index lock, the b=1 device scan, the copy back) and the hydration
of the hits. The program records no span for the widening search yet
(PERF.md lists it for the tracing PR), so the two cannot be told apart
here; the trace's idle gaps under ``bench:index.search_batch`` can."""

from benchmark.lib.stats import covered_seconds, merge_intervals

COVERED = ("coalesce.wait", "device.dispatch")


def read(observed):
    total, n = 0.0, 0
    for root in observed.spans:
        kids = root.get("children", ())
        ranks = [c for c in kids if c["name"] == "qdrant.rank"]
        if not ranks:
            continue
        cover = merge_intervals(
            (c["start_ms"], c["start_ms"] + c["duration_ms"])
            for c in kids if c["name"] in COVERED)
        for r in ranks:
            a = r["start_ms"]
            b = a + r["duration_ms"]
            total += (b - a) - covered_seconds(a, b, cover)
            n += 1
    return total / n if n else None
