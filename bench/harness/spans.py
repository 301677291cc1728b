"""The program's own spans (``repro.obs``) in a run, and the device's idle
time put down to them.

Two views of the same spans:

* the records of ``obs.recording()`` (``repro.obs.Record``), on the host's
  ``perf_counter`` clock, which :func:`select` cuts into set-up, the
  measured window and the verification pass after it;
* the ``zkg.*`` host events of a profiler trace, which a span leaves there
  while the program records and a profiler session is active.  They share
  the device's clock.  :func:`split` sets them aside from the trace
  reduction, so that ``trace.idle_gaps``, ``trace.top_ops`` and the idle
  share read what they read without them.

:func:`prove_idle_share` and :func:`idle_by_span` read the first device,
as ``trace.idle_gaps`` does.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import trace

PREFIX = "zkg."
PROVE_PREFIX = "zkg.prove."
OUTSIDE = "outside program spans"
PARTS = ("setup", "window", "verify")


@dataclass
class _Line:
    name: str
    events: list


@dataclass
class _Plane:
    name: str
    lines: list


@dataclass
class _Profile:
    planes: list


def split(profile) -> tuple:
    """``(window, spans)``: the trace reduction of ``profile`` without the
    program's spans, and those spans as ``(name, start_ns, end_ns)``
    clipped to the window, in order of start."""
    planes, found = [], []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            planes.append(plane)
            continue
        lines = []
        for line in plane.lines:
            kept = []
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    found.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
                else:
                    kept.append(ev)
            lines.append(_Line(line.name, kept))
        planes.append(_Plane(plane.name, lines))
    w = trace.window(_Profile(planes))
    spans = [(n, max(s, w.start_ns), min(e, w.end_ns))
             for n, s, e in found if e > w.start_ns and s < w.end_ns]
    return w, sorted(spans, key=lambda sp: sp[1])


def select(records: list, name: str, run, part: str) -> list:
    """The records named ``name`` that start in ``part`` of ``run``:
    ``setup`` (before the window), ``window`` (from its start to the last
    completion) or ``verify`` (after it)."""
    if part not in PARTS:
        raise ValueError(f"part is one of {PARTS}, not {part!r}")
    out = []
    for r in records:
        if r.name != name:
            continue
        t = r.start_ns / 1e9
        if part == "setup":
            ok = t < run.window_start
        elif part == "window":
            ok = run.window_start <= t <= run.window_end
        else:
            ok = t > run.window_end
        if ok:
            out.append(r)
    return out


def _gaps(w) -> tuple:
    if not w.devices:
        return np.array([w.start_ns], float), np.array([w.end_ns], float)
    return w.gaps(w.devices[0])


def _idle_before(gs: np.ndarray, ge: np.ndarray, t) -> np.ndarray:
    """Idle ns of the sorted, disjoint gaps ``[gs, ge)`` before each ``t``."""
    t = np.asarray(t, float)
    cum = np.r_[0.0, np.cumsum(ge - gs)]
    i = np.searchsorted(ge, t, side="right")    # gaps over by t
    nxt = np.r_[gs, np.inf][i]                  # the gap t may fall in
    return cum[i] + np.clip(t - nxt, 0, None)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def prove_idle_share(w, spans: list):
    """Percent of the union of the ``zkg.prove.*`` spans in which the
    device ran no operation; ``None`` where the window holds none."""
    union = _union([(s, e) for n, s, e in spans
                    if n.startswith(PROVE_PREFIX) and e > s])
    if not union:
        return None
    gs, ge = _gaps(w)
    a = np.array([u[0] for u in union], float)
    b = np.array([u[1] for u in union], float)
    idle = _idle_before(gs, ge, b) - _idle_before(gs, ge, a)
    return 100.0 * float(idle.sum()) / float((b - a).sum())


def idle_by_span(w, spans: list) -> list:
    """``[[span name, seconds], ...]``: the device's idle time in the
    window, each stretch put down to the innermost span open over it (the
    one that started last) or to ``OUTSIDE``.  Sums to the idle time."""
    cuts = sorted({w.start_ns, w.end_ns}
                  | {t for _, s, e in spans for t in (s, e)
                     if w.start_ns < t < w.end_ns})
    gs, ge = _gaps(w)
    idle = np.diff(_idle_before(gs, ge, cuts))
    tot = {}
    for a, b, secs in zip(cuts[:-1], cuts[1:], idle.tolist()):
        open_ = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        label = max(open_)[2] if open_ else OUTSIDE
        tot[label] = tot.get(label, 0.0) + secs / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            if v > 0]
