"""Mean ``hybrid.embed_query`` span over the window: the program's embedder answering one query text (the cache, the lock of the encoder, its forward pass, the vector back on the host)."""


def read(observed):
    spans = observed.span_walk("hybrid.embed_query")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
