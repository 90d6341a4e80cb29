"""The update program's share of its roofline: the least time the chip
could take to write the rows the traced part's refreshes wrote
(``benchmark.lib.live_costs``: each row's bytes read once and written once,
at the peak bytes/s) over the device time of the program's executions
there. Small by nature (a launch is longer than 64 KB takes); it tells an
update in place from one that copies the matrix (8.59 GB: 21 ms). ``None``
when no update ran in the traced part."""


def read(observed):
    trace = observed.trace
    least = observed.traced.get("update_least_s", 0.0)
    name = observed.config.get("programs", {}).get("update")
    if trace is None or not name or not least:
        return None
    count, seconds = trace.module_seconds(name)
    if not count or seconds <= 0:
        return None
    return 100.0 * least / seconds
