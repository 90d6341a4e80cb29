"""Mean ``hybrid.hydrate`` span over the window: one search's fused hits made into results: the raw-score gate, ``storage.get_node`` for each hit kept (``MemoryEngine``'s one lock, the node's copy) and the result's dict."""


def read(observed):
    spans = observed.span_walk("hybrid.hydrate")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
