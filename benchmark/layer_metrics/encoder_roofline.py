"""What the encoder program reaches on the rows it is given, padding
included: FLOPs of the padded shapes of the encoder calls made in the
traced part of the window (the shapes as a tap on the embedder's
jitted forward saw them), over the device time of the encoder program's
executions there x peak FLOP/s."""


def read(observed):
    trace = observed.trace
    flops = observed.traced.get("flops_padded", 0.0)
    if trace is None or observed.peak is None or not flops:
        return None
    count, seconds = trace.module_seconds(
        observed.config["programs"]["encoder"])
    if not count or seconds <= 0:
        return None
    return 100.0 * flops / (seconds * float(observed.peak["flops_per_s"]))
