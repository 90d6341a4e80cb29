"""Mean ``qdrant.filter_plan`` span over the window: what a filtered search pays the planner on the host (the filter's grammar walked, the indexed fields' bounds taken under the index lock) before it joins the coalescer."""


def read(observed):
    spans = observed.span_walk("qdrant.filter_plan")
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
