"""Shared arithmetic of the readers that divide one ``LayerMetrics``
counter, summed over the pass's layers, by the layers' ``seconds``."""


def share_of_layer_time(record, field: str):
    layers = record["layers"]
    total = sum(m["seconds"] for m in layers)
    if not layers or total <= 0:
        return None
    return 100.0 * sum(m[field] for m in layers) / total
