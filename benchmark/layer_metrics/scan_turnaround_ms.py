"""Mean ``index.scan`` span whose ``path`` is not ``host``: what a scan's
caller waits from the call into the jitted program to the result on the
host (the queue behind other callers' scans, the execution, the copy
back), to be read against the scan's device time in the trace."""


def read(observed):
    spans = [s for s in observed.span_walk("index.scan")
             if s["attrs"].get("path") != "host"]
    if not spans:
        return None
    return sum(s["duration_ms"] for s in spans) / len(spans)
