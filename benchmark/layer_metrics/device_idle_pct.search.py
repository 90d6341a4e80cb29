"""Share of the traced window in which no operation ran on the device."""


def read(observed):
    return observed.trace.idle_pct() if observed.trace is not None else None
