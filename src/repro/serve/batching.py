"""Shape-keyed batch queues: route same-shaped steps into shared lanes.

The routing key is :meth:`ZKGraphSession.step_shape_key` — the keygen-cache
key — so two steps land in the same queue exactly when they share circuit
structure, prover config, and compute backend, i.e. exactly when their
witnesses can ride one :func:`repro.core.prover_batch.prove_batch` pass.

A queue flushes on **size or deadline**: the moment it holds ``max_batch``
slots it emits a full batch; otherwise the scheduler flushes any queue whose
oldest slot has waited ``flush_interval`` seconds.  Deadline flushing bounds
the latency a lone query pays for batching; size flushing bounds memory.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field as dc_field

from .. import obs


@dataclass
class StepSlot:
    """One plan step of one in-flight query, waiting for lane-mates."""
    ticket: object          # serve.service._Ticket owning this step
    pos: int                # index into the query's plan-step order
    step: object            # ir.Step (witness already built)
    enqueued: float = dc_field(default_factory=time.monotonic)
    enqueued_ns: int = dc_field(default_factory=obs.now)   # zkg.queue start


@dataclass
class BatchReady:
    """A flushed batch: same-shaped slots ready for one lane-batched prove."""
    key: tuple              # the shared step_shape_key
    slots: list             # [StepSlot], 1 <= len <= max_batch


class ShapeBatcher:
    """The shared batch queues; thread-safe, no threads of its own."""

    def __init__(self, max_batch: int = 8, flush_interval: float = 0.025):
        assert max_batch >= 1
        self.max_batch = max_batch
        self.flush_interval = flush_interval
        self._lock = threading.Lock()
        # key -> [StepSlot]; OrderedDict so expiry scans oldest-first
        self._queues: "OrderedDict[tuple, list]" = OrderedDict()

    def add(self, key: tuple, slot: StepSlot):
        """Queue one slot; returns a BatchReady when this fill hits
        ``max_batch``, else None (the scheduler's deadline will flush it)."""
        with self._lock:
            q = self._queues.setdefault(key, [])
            q.append(slot)
            if len(q) >= self.max_batch:
                del self._queues[key]
                return BatchReady(key, q)
        return None

    def take_expired(self, now: float = None):
        """Flush every queue whose oldest slot exceeded the deadline."""
        if now is None:
            now = time.monotonic()
        ready = []
        with self._lock:
            for key in list(self._queues):
                q = self._queues[key]
                if q and now - q[0].enqueued >= self.flush_interval:
                    del self._queues[key]
                    ready.append(BatchReady(key, q))
        return ready

    def drain(self):
        """Flush everything (service shutdown)."""
        with self._lock:
            ready = [BatchReady(k, q) for k, q in self._queues.items() if q]
            self._queues.clear()
        return ready

    def depth(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    def next_deadline(self, now: float = None) -> float:
        """Seconds until the oldest queued slot expires (scheduler sleep
        bound); ``flush_interval`` when nothing is queued."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            oldest = min((q[0].enqueued for q in self._queues.values() if q),
                         default=None)
        if oldest is None:
            return self.flush_interval
        return max(0.0, oldest + self.flush_interval - now)
