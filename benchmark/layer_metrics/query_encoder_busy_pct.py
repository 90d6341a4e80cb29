"""The encoder program's share of the device's busy time in the traced
part of the window: device seconds of its executions (one a query, at the
``(1, 16)`` shape) over the union of the intervals in which any operation
ran."""


def read(observed):
    trace = observed.trace
    name = observed.config.get("programs", {}).get("encoder")
    if trace is None or not name or trace.busy_s <= 0:
        return None
    count, seconds = trace.module_seconds(name)
    if not count:
        return None
    return 100.0 * seconds / (trace.busy_s * max(trace.n_devices, 1))
