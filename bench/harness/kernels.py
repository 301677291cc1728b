"""Operations and bytes of the program's Pallas kernels, and their share
of the chip's HBM roofline, from the device trace.

A kernel is found by the names its ``pallas_call``s carry in the trace
(``KERNELS``).  Its least traffic is one read of its input and one write
of its result, which have the same shape for both kernels here (an NTT
stage or local pass maps ``(rows, w)`` to ``(rows, w)``; the permutation
maps ``(16, n)`` states to ``(16, n)``); twiddles and round constants are
a few KiB and left out.  The share is that traffic at the published HBM
rate over the kernel's device time.  No integer peak of the vector unit
is published for the chips in ``peaks.py``, so this is the memory bound
alone and a lower bound of the roofline share."""
from __future__ import annotations

from . import peaks
from .trace import KERNEL_MARK, Op, result_bytes

KERNELS = {
    "ntt": ("ntt",),
    "poseidon": ("permute",),
}


def is_kernel(op: Op, kernel: str) -> bool:
    """A Pallas call (``tpu_custom_call``) inside a program whose name
    names the kernel."""
    return KERNEL_MARK in op.text and any(k in op.module
                                          for k in KERNELS[kernel])


def min_bytes(out_bytes: int) -> int:
    """One read of an input shaped like the result, one write of it."""
    return 2 * out_bytes


def hbm_share(trace_window, device_kind: str, kernel: str):
    """Percent of the HBM bound over all of the kernel's calls in the
    window, or ``None`` where the trace holds none or does not give every
    call's result shape."""
    if trace_window is None:
        return None
    calls = [o for o in trace_window.ops if is_kernel(o, kernel)]
    if not calls:
        return None
    nbytes = [result_bytes(o) for o in calls]
    if any(b is None for b in nbytes):
        return None
    secs = sum(o.dur_ns for o in calls) / 1e9
    least = sum(min_bytes(b) for b in nbytes) / peaks.peak(
        device_kind, "hbm_bytes_per_s")
    return 100.0 * least / secs
