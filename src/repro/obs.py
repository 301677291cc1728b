"""Program spans: one timing facility for the service, witness, prover,
commit and verify layers.

A span times one layer boundary::

    with obs.span("zkg.prove.deep", lanes=4) as sp:
        deep = ...
        sp.sync(deep)           # the phase owns its device work
    sp.seconds

With recording off (the default) a span takes two ``perf_counter_ns``
readings and nothing more: no record, no profiler annotation, no device
sync.  Inside ``with obs.recording() as rec:`` every span, on any thread,
also

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so that it
  sits in a JAX profiler trace on the device trace's clock whenever a
  profiler session is active;
* blocks on the outputs handed to :meth:`Span.sync` before it closes;
* appends a :class:`Record` to the recorder's bounded buffer, which drops
  its oldest records when full and counts the drops.

A span opened inside another on the same thread is its child.  Work that
moves between threads carries ids explicitly: ``parent=`` and
``request=``, and :func:`record` for an interval opened on one thread and
closed on another (a queue wait, a whole request).  Records carry
``perf_counter_ns`` times, the clock ``time.perf_counter`` reads.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from dataclasses import asdict, dataclass, field

import jax

DEFAULT_CAPACITY = 1 << 16

_ids = itertools.count(1)
_local = threading.local()
_recorder = None                # the active Recorder, or None

now = time.perf_counter_ns      # the clock of every span, in ns


@dataclass(frozen=True)
class Record:
    """One closed span."""
    name: str
    id: int
    parent: int | None
    request: int | None
    start_ns: int
    end_ns: int
    attrs: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Recorder:
    """A bounded, thread-safe buffer of records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError("a recorder holds at least one record")
        self._buf = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    def add(self, rec: Record):
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(rec)

    def spans(self) -> list:
        with self._lock:
            return list(self._buf)

    def dump(self, path) -> int:
        """Write the records as JSON lines, oldest first; returns how many."""
        recs = self.spans()
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(asdict(r)) + "\n")
        return len(recs)


def load(path) -> list:
    """The records of a :meth:`Recorder.dump` file."""
    with open(path) as f:
        return [Record(**json.loads(line)) for line in f if line.strip()]


class recording:
    """``with recording(capacity=...) as rec``: record every span of the
    process until the block ends.  One recording at a time."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.recorder = Recorder(capacity)

    def __enter__(self) -> Recorder:
        global _recorder
        if _recorder is not None:
            raise RuntimeError("spans are already being recorded")
        _recorder = self.recorder
        return self.recorder

    def __exit__(self, *exc):
        global _recorder
        _recorder = None
        return False


def new_id() -> int:
    """A fresh span id (also used as a request id)."""
    return next(_ids)


def _annotation(name: str):
    return jax.profiler.TraceAnnotation(name)


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """The context manager :func:`span` returns."""
    __slots__ = ("name", "id", "parent", "request", "attrs", "start_ns",
                 "end_ns", "_rec", "_ann")

    def __init__(self, name: str, parent, request, attrs: dict):
        self.name, self.parent, self.request = name, parent, request
        self.attrs = attrs
        self.id = None
        self.start_ns = self.end_ns = None
        self._rec = self._ann = None

    def __enter__(self) -> "Span":
        rec = _recorder
        if rec is not None:
            self._rec = rec
            self.id = next(_ids)
            st = _stack()
            if st and self.parent is None:
                self.parent = st[-1].id
                if self.request is None:
                    self.request = st[-1].request
            st.append(self)
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        if self._rec is not None:
            self._ann.__exit__(*exc)
            st = _stack()
            if st and st[-1] is self:
                st.pop()
            self._rec.add(Record(self.name, self.id, self.parent,
                                 self.request, self.start_ns, self.end_ns,
                                 dict(self.attrs)))
        return False

    def set(self, **attrs):
        """Counts known only inside the span (rows, steps, ...)."""
        self.attrs.update(attrs)

    def sync(self, *outputs):
        """Block on ``outputs`` while recording, so that the span's end
        covers the device work it queued; nothing otherwise."""
        if self._rec is not None:
            jax.block_until_ready(outputs)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def span(name: str, *, parent: int = None, request: int = None,
         **attrs) -> Span:
    """Time ``name``; see the module docstring."""
    return Span(name, parent, request, attrs)


def record(name: str, start_ns: int, end_ns: int, *, parent: int = None,
           request: int = None, span_id: int = None, **attrs):
    """Record an interval whose ends were read on different threads
    (``time.perf_counter_ns``); nothing while not recording.  It has no
    profiler annotation: an annotation opens and closes on one thread."""
    rec = _recorder
    if rec is not None:
        rec.add(Record(name, span_id if span_id is not None else next(_ids),
                       parent, request, start_ns, end_ns, attrs))


class Phases:
    """Consecutive spans named ``<prefix>.<phase>`` with shared attributes,
    and their seconds by phase."""

    def __init__(self, prefix: str, **attrs):
        self.prefix, self.attrs = prefix, attrs
        self._spans = []

    def __call__(self, phase: str) -> Span:
        sp = span(f"{self.prefix}.{phase}", **self.attrs)
        self._spans.append((phase, sp))
        return sp

    def timings(self) -> dict:
        """``{phase: seconds}``, plus ``total`` from the first span's start
        to the last one's end."""
        out = {phase: sp.seconds for phase, sp in self._spans}
        if self._spans:
            out["total"] = (self._spans[-1][1].end_ns
                            - self._spans[0][1].start_ns) / 1e9
        return out
