"""The max merge of the pooling aggregator's partials into the hot store
(``MemoryManager.accumulate`` with ``combine="max"``), as a share of the
layers' time (%): sum of ``max_merge_seconds`` over sum of ``seconds``
in the traced pass.  None where the program does not count it."""

from bench.metrics._shares import share_of_layer_time

FIELD = "max_merge_seconds"


def read(record):
    if not all(FIELD in m for m in record["layers"]):
        return None
    return share_of_layer_time(record, FIELD)
