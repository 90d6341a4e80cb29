"""Mean ``hybrid.decode`` span over the window: one fused batch's arrays on the host turned into each rider's (lexical, vector, fused) id lists."""


def read(observed):
    spans = observed.span_walk("hybrid.decode")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
