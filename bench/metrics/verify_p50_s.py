"""Median ``verify_bytes`` time over the window's bundles, by a verifier
built only from the published manifest.  Host clock."""
from harness.driver import median


def read(run):
    return median(run.verify_s)
