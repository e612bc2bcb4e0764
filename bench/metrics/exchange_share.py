"""The sharded engine's exchange between chips, as a share of the
shards' layer time (%): the ``exchange_post`` and ``exchange_collect``
spans of every shard (``dist/worker.py``: handing over the combined
remote buckets, then the barrier and the ``all_to_all`` that routes
them), over the sum of the shard reports' ``seconds``.  None where the
pass has no such span: a run on one chip exchanges nothing."""

NAMES = ("exchange_post", "exchange_collect")


def read(record):
    spans = [sp for sp in record["spans"] if sp["name"] in NAMES]
    total = sum(m["seconds"] for m in record["layers"])
    if not spans or total <= 0:
        return None
    return 100.0 * sum(sp["end_ns"] - sp["start_ns"] for sp in spans) \
        / 1e9 / total
