"""What an answered search waits for the storage engine's one lock: growth
over the window of ``nornicdb_storage_lock_wait_seconds_total`` (summed by
``MemoryEngine.get_node`` round each acquire, so an uncontended acquire
adds its own few hundred nanoseconds), in ms, over the requests the server
finished on the ``collections`` route between the same two scrapes (in the
vector cells every one is a search)."""

WAIT = "nornicdb_storage_lock_wait_seconds_total"
SEARCHES = 'nornicdb_http_request_seconds_count{route="collections"}'


def read(observed):
    if not any(k.startswith(WAIT) for k in observed.prom_after):
        return None
    searches = observed.prom_delta(SEARCHES)
    if searches <= 0:
        return None
    return observed.prom_delta(WAIT) * 1e3 / searches
