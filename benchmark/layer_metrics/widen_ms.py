"""What an answered search spends in its own widening rounds: per ``wire``
root of a search, the summed duration of its ``qdrant.widen`` spans (the
request's own b=1 dispatch outside the coalescer: ``index.snapshot``,
``index.scan`` and ``index.collect`` nest under it); mean over the
window's searches, one that did not widen counting 0. Nothing where the
program opens no such span."""


def read(observed):
    if not observed.span_walk("qdrant.widen"):
        return None
    total, n = 0.0, 0
    for root in observed.spans:
        if root["name"] != "wire" or not str(
                root["attrs"].get("method", "")).endswith("/points/search"):
            continue
        stack = list(root.get("children", ()))
        while stack:
            s = stack.pop()
            if s["name"] == "qdrant.widen":
                total += s["duration_ms"]
            else:
                stack.extend(s.get("children", ()))
        n += 1
    return total / n if n else None
