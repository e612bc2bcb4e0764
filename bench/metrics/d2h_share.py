"""The device-to-host copy of each chunk's padded kernel output, inside
the aggregate call, as a share of the layers' time (%): sum of
``d2h_seconds`` over sum of ``seconds`` in the traced pass.  None where
the program does not count it."""

from bench.metrics._shares import share_of_layer_time

FIELD = "d2h_seconds"


def read(record):
    if not all(FIELD in m for m in record["layers"]):
        return None
    return share_of_layer_time(record, FIELD)
