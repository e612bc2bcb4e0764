"""Time inside the aggregate call, host side: np.unique, padding, h2d, kernel and the blocking read-back, as a share of the layers' time (%):
sum of ``aggregate_seconds`` over sum of ``seconds`` in the traced pass."""

from bench.metrics._shares import share_of_layer_time


def read(record):
    return share_of_layer_time(record, "aggregate_seconds")
