"""Mean ``index.refresh`` span: what a reader that finds writes pending
spends, under the index lock, bringing the device copy up to the host
mirror before it scans (``kind`` ``rows``: the slots gathered, handed over
and the update program dispatched; ``full``: the whole matrix shipped)."""


def read(observed):
    spans = observed.span_walk("index.refresh")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
