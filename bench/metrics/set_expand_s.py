"""Mean seconds of one lane-batched prove of an ``expand_set`` step in
the window (``ServiceMetrics.prove_us_by_circuit``; host clock).  In a
one-client cell every batch holds one step.  ``None`` where the program
keeps no prove times by circuit or proved no such step."""


def read(run):
    h = run.stats.get("prove_us_by_circuit", {}).get("expand_set")
    return h["mean"] / 1e6 if h and h["count"] else None
