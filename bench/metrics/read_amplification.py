"""Bytes the reader read over the bytes of the layers' input rows (x):
sum of ``bytes_read`` over sum of ``V * d_in * 4``."""


def read(record):
    layers = record["layers"]
    if not layers:
        return None
    dims = record["config"]["dims"]
    need = sum(record["num_vertices"] * d * 4 for d in dims[:len(layers)])
    return sum(m["bytes_read"] for m in layers) / need
