"""The host's destination dictionary and operand padding (``np.unique``,
segment ids, the edge and feature scratch fills) inside the aggregate
call, as a share of the layers' time (%): sum of ``dedup_seconds`` over
sum of ``seconds`` in the traced pass.  None where the program does not
count it."""

from bench.metrics._shares import share_of_layer_time

FIELD = "dedup_seconds"


def read(record):
    if not all(FIELD in m for m in record["layers"]):
        return None
    return share_of_layer_time(record, FIELD)
