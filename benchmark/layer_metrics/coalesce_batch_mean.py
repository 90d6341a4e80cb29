"""Riders per sealed batch over the window: growth of the
``nornicdb_microbatch_batch_size`` histogram's sum over that of its count,
read from ``/metrics`` as the window opened and closed."""


def read(observed):
    count = observed.prom_delta("nornicdb_microbatch_batch_size_count")
    if count <= 0:
        return None
    return observed.prom_delta("nornicdb_microbatch_batch_size_sum") / count
