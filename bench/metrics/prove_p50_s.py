"""Median latency of the window's queries, ``submit`` to bundle.  Host
clock."""
from harness.driver import median


def read(run):
    return median(run.latencies())
