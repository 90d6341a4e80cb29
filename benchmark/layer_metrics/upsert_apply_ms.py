"""Mean ``qdrant.upsert`` span: a write request's points into storage and
into the index, after its JSON is parsed and before its 200: the
interpreter a write takes from the readers."""


def read(observed):
    spans = observed.span_walk("qdrant.upsert")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
