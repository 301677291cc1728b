"""Mean seconds of the witness stage per query in the window: the
``zkg.witness`` span around plan execution and witness building, through
``ServiceMetrics.witness_us``.  ``None`` where the program has no such
histogram."""


def read(run):
    h = run.stats.get("witness_us")
    return h["mean"] / 1e6 if h and h["count"] else None
