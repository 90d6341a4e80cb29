"""Mean ``index.snapshot`` span, over every ``search_batch`` call of the
window (coalesced rounds and widening rounds alike): from asking for the
index lock to its release, which holds the wait for the lock, the look at
the device arrays and the copy of the id list (the free of that copy costs
as much again and lies in ``index.collect``)."""


def read(observed):
    spans = observed.span_walk("index.snapshot")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
