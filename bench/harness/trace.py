"""Reduction of a profiler trace of the measured window to device numbers.

Works on anything shaped like ``jax.profiler.ProfileData``: planes with a
``name`` and ``lines``, lines with a ``name`` and ``events``, events with
``name``, ``start_ns`` and ``duration_ns``.  Device and host events share
one clock.

* The traced window is the host span named ``TRACED_SPAN``, which the
  harness opens for the first seconds of the measured window.
* A device plane is one whose name starts with ``/device:TPU:``.  Its
  operations are the events of its ``XLA Ops`` line, whose name is the
  operation's HLO text; the program each ran in is the event of the
  ``XLA Modules`` line around it.
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices that ran any; the idle share is one
  minus busy over the window.
* An idle gap is a stretch of the window with no operation on a device.
  The ``GAPS_LABELLED`` longest are each put down to the host event that
  overlaps them most (or to ``untraced host work``); the rest are summed
  as ``SHORT_GAPS``.
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field

import numpy as np

TRACED_SPAN = "bench.traced"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNTRACED = "untraced host work"
SHORT_GAPS = "shorter idle gaps"
GAPS_LABELLED = 1000
LONG_HOST_NS = 1e9      # host events longer than this are checked one by one
KERNEL_MARK = "tpu_custom_call"


@dataclass
class Op:
    """One device operation, kept in full only for kernels."""
    device: str
    text: str           # the HLO text the trace names it by
    module: str         # the program it ran in, hash stripped
    start_ns: float
    dur_ns: float

    @property
    def name(self) -> str:
        return op_name(self.text)


@dataclass
class Device:
    name: str
    starts: np.ndarray          # operation starts, clipped to the window
    ends: np.ndarray
    op_time: dict = field(default_factory=dict)   # "module/op" -> ns
    kernels: list = field(default_factory=list)   # [Op] custom calls

    def busy(self) -> tuple:
        """The union of the operation intervals: (starts, ends)."""
        if not len(self.starts):
            return self.starts, self.ends
        order = np.argsort(self.starts, kind="stable")
        s, e = self.starts[order], self.ends[order]
        reach = np.maximum.accumulate(e)
        new = np.r_[True, s[1:] > reach[:-1]]
        last = np.r_[new[1:], True]
        return s[new], reach[last]


@dataclass
class Window:
    start_ns: float
    end_ns: float
    devices: list               # [Device] that ran an operation
    host: tuple = (np.zeros(0), np.zeros(0), [])   # starts, ends, names

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def ops(self) -> list:
        return [k for d in self.devices for k in d.kernels]

    def busy_s(self) -> float:
        """Union of operation intervals, mean over the devices that ran."""
        if not self.devices:
            return 0.0
        total = 0.0
        for d in self.devices:
            s, e = d.busy()
            total += float((e - s).sum())
        return total / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.seconds

    def gaps(self, device: Device) -> tuple:
        s, e = device.busy()
        gs = np.r_[self.start_ns, e]
        ge = np.r_[s, self.end_ns]
        keep = ge > gs
        return gs[keep], ge[keep]


def op_name(text: str) -> str:
    """``fusion.25`` of ``%fusion.25 = u32[4]{0} fusion(...)``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def module_name(text: str) -> str:
    """``jit_add`` of ``jit_add(9880566550298853791)``."""
    return text.split("(", 1)[0]


def _span(profile) -> tuple:
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in profile.planes
             if not plane.name.startswith(DEVICE_PREFIX)
             for line in plane.lines for ev in line.events
             if ev.name == TRACED_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {TRACED_SPAN!r} span, found "
                         f"{len(spans)}")
    return spans[0]


def _device(plane, w0: float, w1: float) -> Device:
    mods = {line.name: line for line in plane.lines}
    m_start, m_end, m_name = [], [], []
    if MODULES_LINE in mods:
        for ev in mods[MODULES_LINE].events:
            if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1:
                m_start.append(ev.start_ns)
                m_end.append(ev.start_ns + ev.duration_ns)
                m_name.append(sys.intern(module_name(ev.name)))
    m_start, m_end = np.asarray(m_start, float), np.asarray(m_end, float)
    order = np.argsort(m_start, kind="stable")
    m_start, m_end = m_start[order], m_end[order]
    m_name = [m_name[i] for i in order]

    def module_at(t: float) -> str:
        i = int(np.searchsorted(m_start, t, side="right")) - 1
        return m_name[i] if i >= 0 and m_end[i] >= t else ""

    starts, ends, op_time, kernels = [], [], {}, []
    line = mods.get(OPS_LINE)
    for ev in (line.events if line is not None else ()):
        s, d = ev.start_ns, ev.duration_ns
        if s + d <= w0 or s >= w1:
            continue
        starts.append(max(s, w0))
        ends.append(min(s + d, w1))
        text = ev.name
        mod = module_at(s)
        key = f"{mod}/{op_name(text)}" if mod else op_name(text)
        op_time[key] = op_time.get(key, 0.0) + d
        if KERNEL_MARK in text:
            kernels.append(Op(plane.name, text, mod, s, d))
    return Device(plane.name, np.asarray(starts, float),
                  np.asarray(ends, float), op_time, kernels)


def window(profile) -> Window:
    """The traced window, its devices' operations and the host events in
    it."""
    w0, w1 = _span(profile)
    devices, hs, he, hn = [], [], [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = _device(plane, w0, w1)
            if len(dev.starts):
                devices.append(dev)
            continue
        for line in plane.lines:
            for ev in line.events:
                s, d = ev.start_ns, ev.duration_ns
                if d <= 0 or s + d <= w0 or s >= w1 or ev.name == TRACED_SPAN:
                    continue
                hs.append(s)
                he.append(s + d)
                hn.append(sys.intern(ev.name))
    order = np.argsort(np.asarray(hs, float), kind="stable")
    host = (np.asarray(hs, float)[order], np.asarray(he, float)[order],
            [hn[i] for i in order])
    return Window(w0, w1, sorted(devices, key=lambda d: d.name), host)


def top_ops(w: Window, n: int = 10) -> list:
    """``[[program/op, seconds], ...]``: device time by operation, summed
    over the devices."""
    tot = {}
    for d in w.devices:
        for k, v in d.op_time.items():
            tot[k] = tot.get(k, 0.0) + v / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _label(host: tuple, long_idx: np.ndarray, g0: float, g1: float) -> str:
    hs, he, hn = host
    short_lo = int(np.searchsorted(hs, g0 - LONG_HOST_NS))
    short_hi = int(np.searchsorted(hs, g1))
    idx = np.r_[np.arange(short_lo, short_hi), long_idx]
    if not len(idx):
        return UNTRACED
    cover = np.minimum(he[idx], g1) - np.maximum(hs[idx], g0)
    by_name = {}
    for i, c in zip(idx.tolist(), cover.tolist()):
        if c > 0:
            by_name[hn[i]] = by_name.get(hn[i], 0.0) + c
    return max(by_name, key=by_name.get) if by_name else UNTRACED


def idle_gaps(w: Window, n: int = 10) -> list:
    """``[[host activity, seconds], ...]``: the idle time of the first
    device by what the host was doing."""
    if not w.devices:
        return [[UNTRACED, w.seconds]]
    gs, ge = w.gaps(w.devices[0])
    length = ge - gs
    order = np.argsort(-length, kind="stable")
    hs, he, _ = w.host
    long_idx = np.flatnonzero(he - hs > LONG_HOST_NS)
    tot = {}
    for i in order[:GAPS_LABELLED].tolist():
        name = _label(w.host, long_idx, gs[i], ge[i])
        tot[name] = tot.get(name, 0.0) + length[i] / 1e9
    rest = float(length[order[GAPS_LABELLED:]].sum()) / 1e9
    if rest > 0:
        tot[SHORT_GAPS] = rest
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


_SHAPE = re.compile(r"\b(u32|s32|f32|u64|s64|bf16|f16|u8|s8|pred)"
                    r"\[([0-9,]*)\]")
_BYTES = dict(u32=4, s32=4, f32=4, u64=8, s64=8, bf16=2, f16=2, u8=1, s8=1,
              pred=1)


def result_bytes(op: Op) -> int | None:
    """Bytes of an operation's result, from its HLO text
    (``%name = u32[16,4096]{...} custom-call(...)``; a tuple result sums
    its parts); ``None`` where the text does not say."""
    parts = op.text.split(" = ", 1)
    if len(parts) != 2:
        return None
    rhs = parts[1]
    cut = min((i for i in (rhs.find(" custom-call("), rhs.find(" fusion("))
               if i >= 0), default=-1)
    if cut < 0:
        return None
    total = 0
    for dtype, dims in _SHAPE.findall(rhs[:cut]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total or None

