"""Median wait of a step in the batcher, enqueue to batch flush
(``ServiceMetrics.queue_wait_us``)."""


def read(run):
    h = run.stats["queue_wait_us"]
    return h["p50"] / 1e6 if h["count"] else None
