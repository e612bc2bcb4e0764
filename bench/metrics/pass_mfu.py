"""Model FLOP utilisation of a whole pass (%): the model operations of one
pass (aggregation and update of every layer, ``bench/work.py``) over the
traced pass's length times the peak of all the cell's chips."""

from bench import work


def read(record):
    w0, w1 = record["trace"]["window_ns"]
    if w1 <= w0:
        return None
    cfg = record["config"]
    flops = work.pass_model_flops(cfg["kind"], record["num_vertices"],
                                  record["num_edges"], cfg["dims"])
    peak = record["chips"] * record["peaks"]["flops_per_s"]
    return 100.0 * flops / ((w1 - w0) / 1e9 * peak)
