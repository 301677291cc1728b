"""The poseidon kernel's share of the HBM roofline: its least bytes at the
published HBM rate over its device time in the trace (``harness.kernels``).
A lower bound of its roofline share; see ``harness/kernels.py``."""
from harness import kernels


def read(run):
    return kernels.hbm_share(run.trace, run.device_kind, "poseidon")
