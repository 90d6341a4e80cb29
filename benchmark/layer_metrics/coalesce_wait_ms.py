"""Mean ``coalesce.wait`` span: from a rider's enqueue to the dispatch of
the batch that carried it."""


def read(observed):
    spans = observed.span_walk("coalesce.wait")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
