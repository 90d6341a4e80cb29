"""The filtered scan's share of its roofline: the least time the chip could
take for the executions seen in the trace, over their device time.

The least time of one execution is that of reading ONE tenant's rows once
(``lib/filter_costs.py``: rows / tenants x dims x 4 B at the peak bytes/s),
whatever tenants the batch's riders brought: a floor of every execution,
so the share cannot pass 100%. A program that masks a scan of the whole
matrix reads about 100 / tenants; a layout that reads only the rows a
batch's riders may see would read up to 100 for a batch of one tenant."""

from benchmark.lib.costs import least_seconds
from benchmark.lib.filter_costs import filtered_scan_cost


def read(observed):
    trace = observed.trace
    tenants = observed.config.get("tenants")
    if trace is None or observed.peak is None or not tenants:
        return None
    count, seconds = trace.module_seconds(
        observed.config["programs"]["scan"])
    if not count or seconds <= 0:
        return None
    rows = observed.sizes["rows"] // int(tenants)
    dims = observed.sizes["dims"]
    least_1, bound_1 = least_seconds(*filtered_scan_cost(rows, dims, 1),
                                     observed.peak)
    least_64, bound_64 = least_seconds(*filtered_scan_cost(rows, dims, 64),
                                       observed.peak)
    if bound_1 != "bytes" or bound_64 != "bytes":
        raise ValueError("the filtered scan is not bytes-bound at every "
                         "batch; this reader needs each execution's batch")
    return 100.0 * count * least_1 / seconds
