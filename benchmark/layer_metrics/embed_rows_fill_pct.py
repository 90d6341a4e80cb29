"""What share of the rows x width the device is given is text, by the
program's own count: growth of ``nornicdb_embed_tokens_total{kind="real"}``
(the lengths of the id lists each forward is handed) over
``{kind="padded"}`` (rows x width of its array). The inside twin of
``embed_fill_pct``: it counts the overlap of chunks as text, so it reads at
or a little above it."""

REAL = 'nornicdb_embed_tokens_total{kind="real"}'
PADDED = 'nornicdb_embed_tokens_total{kind="padded"}'


def read(observed):
    if PADDED not in observed.prom_after:
        return None
    padded = observed.prom_delta(PADDED)
    if padded <= 0:
        return None
    return 100.0 * observed.prom_delta(REAL) / padded
