"""Observability surface of the proving service (`docs/serving.md` schema).

Everything here is plain host-side bookkeeping — thread-safe, allocation-
bounded, and cheap enough to leave on in production.  The service exposes one
:meth:`ServiceMetrics.snapshot` dict; ``benchmarks/paper_tables.py:serving``
and the regression gate consume the same schema.
"""
from __future__ import annotations

import threading


class Histogram:
    """Bounded-reservoir latency/occupancy histogram.

    Keeps the most recent ``max_samples`` observations (a ring buffer — a
    long-lived service must not grow without limit) plus exact running
    count/sum, and reports order statistics over the reservoir.
    """

    def __init__(self, max_samples: int = 4096):
        self._max = max_samples
        self._ring = [0.0] * max_samples
        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0

    def observe(self, value: float):
        with self._lock:
            self._ring[self.count % self._max] = float(value)
            self.count += 1
            self.total += float(value)

    def _samples(self):
        n = min(self.count, self._max)
        return sorted(self._ring[:n])

    def percentile(self, p: float) -> float:
        """p in [0, 100]; nearest-rank over the reservoir (0.0 when empty)."""
        with self._lock:
            s = self._samples()
        if not s:
            return 0.0
        rank = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
        return s[rank]

    def snapshot(self) -> dict:
        with self._lock:
            s = self._samples()
            count, total = self.count, self.total
        if not s:
            return dict(count=0, mean=0.0, p50=0.0, p95=0.0, max=0.0)

        def pct(p):
            return s[min(len(s) - 1,
                         max(0, int(round(p / 100.0 * (len(s) - 1)))))]

        return dict(count=count, mean=total / count, p50=pct(50),
                    p95=pct(95), max=s[-1])


# the prover's per-phase timing keys, in pipeline order (prover.py timings)
PHASES = ("commit_advice", "phase2_ext", "quotient", "ood_openings", "deep",
          "fri", "total")


class ServiceMetrics:
    """All service counters + histograms; one :meth:`snapshot` dict.

    Schema (documented in docs/serving.md and consumed by the serving
    benchmark)::

        counters:        submitted / completed / failed / batches /
                         lanes / pad_lanes
        phase_us:        per prover phase -> {count, mean, p50, p95, max}
        witness_us:      per-query witness stage time   (same stats dict)
        queue_wait_us:   submit -> batch-flush wait     (same stats dict)
        prove_us:        per-batch prove wall time      (same stats dict)
        prove_us_by_circuit: circuit name -> per-batch prove wall time of
                         that circuit's batches (same stats dict; an
                         entry appears with the circuit's first batch)
        batch_occupancy: real lanes per flushed batch   (same stats dict)
        keygen_cache:    {hits, misses, waits, entries} (KeygenCache.stats)
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters = dict(submitted=0, completed=0, failed=0, batches=0,
                              lanes=0, pad_lanes=0)
        self.phase_us = {p: Histogram() for p in PHASES}
        self.witness_us = Histogram()
        self.queue_wait_us = Histogram()
        self.prove_us = Histogram()
        self.prove_us_by_circuit = {}
        self.batch_occupancy = Histogram()

    def inc(self, name: str, by: int = 1):
        with self._lock:
            self._counters[name] += by

    def observe_phases(self, timings: dict):
        """Record one prove's per-phase seconds (stored as microseconds)."""
        for phase in PHASES:
            if phase in timings:
                self.phase_us[phase].observe(timings[phase] * 1e6)

    def observe_prove(self, circuit: str, us: float):
        """Record one batch's prove wall time, pooled and by circuit."""
        self.prove_us.observe(us)
        with self._lock:
            h = self.prove_us_by_circuit.get(circuit)
            if h is None:
                h = self.prove_us_by_circuit[circuit] = Histogram()
        h.observe(us)

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def snapshot(self, cache_stats: dict = None) -> dict:
        with self._lock:
            by_circuit = sorted(self.prove_us_by_circuit.items())
        out = dict(
            counters=self.counters(),
            phase_us={p: h.snapshot() for p, h in self.phase_us.items()},
            witness_us=self.witness_us.snapshot(),
            queue_wait_us=self.queue_wait_us.snapshot(),
            prove_us=self.prove_us.snapshot(),
            prove_us_by_circuit={c: h.snapshot() for c, h in by_circuit},
            batch_occupancy=self.batch_occupancy.snapshot(),
        )
        if cache_stats is not None:
            out["keygen_cache"] = dict(cache_stats)
        return out
