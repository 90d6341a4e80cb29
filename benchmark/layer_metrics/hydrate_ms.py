"""Mean ``hydrate_ms`` attribute of the ``qdrant.rank`` spans: what a search
spends fetching its hits' nodes from storage and shaping them
(``storage.get_node``, the payload filter, ``_point_dict``), summed over
the hits by the program itself."""


def read(observed):
    values = [s["attrs"]["hydrate_ms"]
              for s in observed.span_walk("qdrant.rank")
              if "hydrate_ms" in s["attrs"]]
    return sum(values) / len(values) if values else None
