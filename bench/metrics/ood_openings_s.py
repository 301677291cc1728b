"""Mean seconds of the prover's ``ood_openings`` phase per prove call in the
window (``Proof.timings`` through ``ServiceMetrics.phase_us``; host
clock)."""


def read(run):
    h = run.stats["phase_us"]["ood_openings"]
    return h["mean"] / 1e6 if h["count"] else None
