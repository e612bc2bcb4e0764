"""Graduation's dense layer update (core/graduation.py, models/gnn.py::layer_update), as a share of the layers' time (%):
sum of ``transform_seconds`` over sum of ``seconds`` in the traced pass."""

from bench.metrics._shares import share_of_layer_time


def read(record):
    return share_of_layer_time(record, "transform_seconds")
