"""Mean ``hybrid.plan`` span over the window: the host side of one fused batch before the device is called (liveness refresh, the terms' posting ranges, the selection matrix)."""


def read(observed):
    spans = observed.span_walk("hybrid.plan")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
