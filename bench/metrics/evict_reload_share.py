"""Eviction to the cold store and reload from it (``cold.put`` and its
bookkeeping, ``cold.take``), as a share of the layers' time (%): sum of
``evict_seconds`` over sum of ``seconds`` in the traced pass; 0 where
nothing is evicted.  None where the program does not count it."""

from bench.metrics._shares import share_of_layer_time

FIELD = "evict_seconds"


def read(record):
    if not all(FIELD in m for m in record["layers"]):
        return None
    return share_of_layer_time(record, FIELD)
