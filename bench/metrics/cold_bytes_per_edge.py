"""Bytes moved to and from the cold store per message (B/edge): the
pass's ``cold_bytes_read + cold_bytes_written``, over every layer (and
every shard), over its messages (``bench/work.py``: E, plus one self
message per vertex for SAGE, per layer)."""

from bench import work


def read(record):
    layers = record["layers"]
    if not layers:
        return None
    cfg = record["config"]
    num_layers = len({m["layer"] for m in layers})
    msgs = num_layers * work.messages(cfg["kind"], record["num_vertices"],
                                      record["num_edges"])
    moved = sum(m["cold_bytes_read"] + m["cold_bytes_written"]
                for m in layers)
    return moved / msgs
