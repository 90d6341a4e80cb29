"""Bytes sent from the host to the index's device copy over the window
(growth of ``nornicdb_index_device_ship_bytes_total``, every kind) over the
points whose write was acknowledged in it, by the writer's own log.
``dims x 4`` (4,096 at 1,024 dims) is the floor; a program that re-ships
the matrix for a write reads its size, 8.59e9."""

SHIPPED = "nornicdb_index_device_ship_bytes_total"


def read(observed):
    points = observed.counters.get("points_acked", 0.0)
    if not points or not any(k.startswith(SHIPPED)
                             for k in observed.prom_after):
        return None
    return observed.prom_delta(SHIPPED) / points
