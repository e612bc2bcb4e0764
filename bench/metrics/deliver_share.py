"""The main thread's per-chunk delivery into the hot store (shield,
activate, accumulate, orchestrator, graduation hand-off, eviction and
reload), as a share of the layers' time (%): sum of ``deliver_seconds``
over sum of ``seconds`` in the traced pass.  None where the program does
not count it."""

from bench.metrics._shares import share_of_layer_time

FIELD = "deliver_seconds"


def read(record):
    if not all(FIELD in m for m in record["layers"]):
        return None
    return share_of_layer_time(record, FIELD)
