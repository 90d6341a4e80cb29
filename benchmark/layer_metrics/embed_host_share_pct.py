"""The share of the embed worker's time in which it is not waiting for the
encoder: growth of ``nornicdb_embed_worker_seconds_total`` over every phase
(the worker's wall time: batches and the wait on an empty queue) less
growth of ``nornicdb_device_dispatch_seconds_sum{kind="encoder"}`` (each
forward from its call to its vectors on the host), over the former. The
program's own twin of ``device_idle_pct.ingest``, on the host's clock and
over the whole window where that one is the device's clock over the traced
seconds. It reads under it by whatever the worker waits INSIDE a forward
while the device is already idle: the dispatch, the copy back, and above
all the wait to get the interpreter lock back from the handlers of a burst
of posts (0.15-0.5 s of 6 traced seconds, PERF.md section 5)."""

WORKER = "nornicdb_embed_worker_seconds_total"
ENCODER = 'nornicdb_device_dispatch_seconds_sum{kind="encoder"}'


def read(observed):
    if ENCODER not in observed.prom_after:
        return None
    worker = observed.prom_delta(WORKER)
    if worker <= 0:
        return None
    return 100.0 * (worker - observed.prom_delta(ENCODER)) / worker
