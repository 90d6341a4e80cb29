"""Mean ``hybrid.dispatch`` span over the window: one fused batch from the call into the compiled program to its arrays on the host."""


def read(observed):
    spans = observed.span_walk("hybrid.dispatch")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
