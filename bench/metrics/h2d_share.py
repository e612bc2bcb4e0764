"""Host-to-device staging time (core/broadcast.py, core/staging.py), as a share of the layers' time (%):
sum of ``h2d_seconds`` over sum of ``seconds`` in the traced pass."""

from bench.metrics._shares import share_of_layer_time


def read(record):
    return share_of_layer_time(record, "h2d_seconds")
