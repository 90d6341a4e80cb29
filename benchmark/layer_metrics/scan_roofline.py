"""The scan kernel's share of its roofline: the least time the chip could
take for the executions seen in the trace, over their device time.

One execution reads the whole capacity x dims float32 matrix once and does
2 x B x capacity x dims FLOPs. At every batch the coalescer can seal
(B <= 64) the bytes bound is the larger, so the least time of an execution
does not depend on its B; the reader checks that and refuses to guess
otherwise."""

from benchmark.lib.costs import least_seconds, scan_cost


def read(observed):
    trace = observed.trace
    if trace is None or observed.peak is None:
        return None
    count, seconds = trace.module_seconds(
        observed.config["programs"]["scan"])
    if not count or seconds <= 0:
        return None
    cap, dims = observed.sizes["capacity"], observed.sizes["dims"]
    least_1, bound_1 = least_seconds(*scan_cost(1, cap, dims), observed.peak)
    least_64, bound_64 = least_seconds(*scan_cost(64, cap, dims),
                                       observed.peak)
    if bound_1 != "bytes" or bound_64 != "bytes":
        raise ValueError("the scan is not bytes-bound at every batch; "
                         "this reader needs each execution's batch")
    return 100.0 * count * least_1 / seconds
