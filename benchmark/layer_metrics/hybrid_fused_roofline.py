"""The fused hybrid program's share of its roofline: the least time the
chip could take for the dispatches of the traced part of the window, each
priced from its own shapes by ``benchmark.lib.hybrid_costs.hybrid_cost``
(the vector matrix read once, the postings its terms gathered, the
term-by-passage matrix filled and read once; the larger of bytes over peak
bytes/s and FLOPs over peak FLOP/s, which is the bytes at every batch the
coalescer seals), over the device time of the program's executions
there."""


def read(observed):
    trace = observed.trace
    least = observed.traced.get("fused_least_s", 0.0)
    name = observed.config.get("programs", {}).get("fused")
    if trace is None or not name or not least:
        return None
    count, seconds = trace.module_seconds(name)
    if not count or seconds <= 0:
        return None
    return 100.0 * least / seconds
