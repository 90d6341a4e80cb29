"""Executions of the scan program on the device, from the trace, over the
requests the clients saw complete in the traced part of the window. One
coalesced scan shared by a batch reads under 1; a request that also widens
on its own adds 1."""


def read(observed):
    trace = observed.trace
    requests = observed.traced.get("requests", 0.0)
    if trace is None or requests <= 0:
        return None
    count, _ = trace.module_seconds(observed.config["programs"]["scan"])
    return count / requests if count else None
