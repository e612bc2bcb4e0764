"""Bytes the reader read over the bytes of the layers' input rows (x):
sum of ``bytes_read`` (over every shard, where the run is sharded) over
sum of ``V * d_in * 4``."""


def read(record):
    layers = record["layers"]
    if not layers:
        return None
    dims = record["config"]["dims"]
    num_layers = len({m["layer"] for m in layers})
    need = sum(record["num_vertices"] * d * 4 for d in dims[:num_layers])
    return sum(m["bytes_read"] for m in layers) / need
