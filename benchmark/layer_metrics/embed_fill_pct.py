"""What share of the rows x width the device is given is text: tokens of
the texts handed to the embedder in the window (whole documents and the
documents chunked again, each counted once a call) over rows x width of
every array the embedder's jitted forward was given, as a tap on that
forward saw them. Chunk overlap and pad rows count as padding."""


def read(observed):
    padded = observed.counters.get("padded_tokens_in_calls", 0)
    if not padded:
        return None
    return 100.0 * observed.counters["real_tokens_in_calls"] / padded
