"""The whole ingest step's share of the chip's peak: FLOPs the encoder's
mathematics needs for the documents that became searchable in the window,
each at its own length (24 layers' projections, MLP and attention; the
embedding tables are lookups and count nothing; padding, chunk embeddings
and recomputation count nothing), over window seconds x peak FLOP/s."""


def read(observed):
    flops = observed.counters.get("flops_real", 0.0)
    if not flops or observed.peak is None or observed.window_s <= 0:
        return None
    return 100.0 * flops / (observed.window_s
                            * float(observed.peak["flops_per_s"]))
