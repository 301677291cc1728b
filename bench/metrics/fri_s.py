"""Mean seconds of the prover's ``fri`` phase per prove call in the
window (``Proof.timings`` through ``ServiceMetrics.phase_us``; host
clock)."""


def read(run):
    h = run.stats["phase_us"]["fri"]
    return h["mean"] / 1e6 if h["count"] else None
