"""Share of the traced pass in which no operation ran on the device (%):
100 * (1 - union of the device-op intervals / the pass), from the trace."""


def read(record):
    red = record["trace"]
    w0, w1 = red["window_ns"]
    if not red["devices"] or w1 <= w0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / (w1 - w0))
