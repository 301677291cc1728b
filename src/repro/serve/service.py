"""ProofService: batched, pipelined, concurrent verifiable query serving.

``submit(qname, params)`` returns a ``concurrent.futures.Future`` resolving
to the same :class:`~repro.core.session.ProofBundle` a direct
``session.prove`` call would produce — wire-byte-identical, which is what
lets one service answer many mutually-distrustful clients: batching is
invisible in the artifact.

Dataflow (docs/serving.md has the picture)::

    submit -> [witness stage] -> ShapeBatcher -> [prove stage] -> Future
                  run_query       size/deadline     prove_steps
                                  flush (scheduler)  (lane-batched)

* The witness stage executes the query plan (host-heavy) and drops each
  step into the shape-keyed batcher; same-shaped steps from different
  queries share a queue.
* The scheduler thread flushes queues on deadline; full queues flush
  inline on size.
* The prove stage pads each batch to a power-of-two lane count (bounding
  the set of jitted shapes), runs ONE lane-batched prove, and fulfills the
  per-query slots; a query's future resolves when its last step lands.

Backpressure is the bounded stage inboxes: a slow prover backs up the
batch queue, then the witness inbox, then ``submit`` itself blocks.
Failures are per-query: a poisoned query fails its own future; the service
keeps serving.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future
from dataclasses import dataclass, field as dc_field

from .. import obs
from ..core import backend as be
from ..core.session import ProofBundle, ZKGraphSession
from .batching import BatchReady, ShapeBatcher, StepSlot
from .metrics import ServiceMetrics
from .pipeline import Stage


class ServiceClosed(RuntimeError):
    """submit() after close()."""


@dataclass
class _Ticket:
    """One in-flight query submission."""
    qname: str
    params: dict
    future: Future
    request: int = dc_field(default_factory=obs.new_id)  # zkg.request id
    submitted_ns: int = dc_field(default_factory=obs.now)
    witness_span: int = None    # zkg.witness id, parent of the queue spans
    run: object = None          # ir.QueryRun once the witness stage ran
    results: list = None        # per-step StepProof slots (plan order)
    remaining: int = 0
    failed: bool = False


class ProofService:
    """Batched concurrent proving on top of one owner session.

    ``max_batch``: lane cap per shape queue (flush-on-size threshold).
    ``flush_interval``: seconds a lone step may wait for lane-mates.
    ``max_pending``: admission bound — submissions beyond it block.
    ``placement``: optional :class:`repro.serve.placement.Placement`
    sharding the lane axis across a device mesh.
    ``pad_pow2``: pad batches to power-of-two lane counts so the jit cache
    sees O(log max_batch) shapes per circuit, not O(max_batch).
    """

    def __init__(self, session: ZKGraphSession, *, max_batch: int = 8,
                 flush_interval: float = 0.025, max_pending: int = 64,
                 placement=None, pad_pow2: bool = True):
        assert session.db is not None, \
            "ProofService serves an owner session (needs the database)"
        self.session = session
        self.placement = placement
        self.pad_pow2 = pad_pow2
        # pin the compute backend NOW, in the caller's thread: worker threads
        # do not inherit be.use() scopes (thread-local), so the service must
        # carry the resolved name across and re-enter it per worker task
        self._backend = be.resolve_name(session.cfg.backend)
        with be.use(self._backend):
            # prime the manifest once so worker threads never race the lazy
            # publish; its digest is stamped into every bundle
            self._manifest_digest = session.commitments.digest()
        self.metrics = ServiceMetrics()
        self.batcher = ShapeBatcher(max_batch, flush_interval)
        self._lock = threading.Lock()
        self._closed = False
        self._prove = Stage("prove", self._handle_batch, maxsize=4,
                            on_error=self._batch_error).start()
        self._witness = Stage("witness", self._handle_ticket,
                              maxsize=max_pending,
                              on_error=self._ticket_error).start()
        self._stop_evt = threading.Event()
        self._scheduler = threading.Thread(target=self._run_scheduler,
                                           name="zkserve-scheduler",
                                           daemon=True)
        self._scheduler.start()

    # -- client surface ------------------------------------------------------
    def submit(self, qname: str, params: dict,
               timeout: float = None) -> Future:
        """Queue one query; blocks when ``max_pending`` submissions are in
        flight (backpressure).  The future resolves to the ProofBundle, or
        raises the query's failure."""
        with self._lock:
            if self._closed:
                raise ServiceClosed("ProofService is closed")
        ticket = _Ticket(qname, dict(params), Future())
        self.metrics.inc("submitted")
        self._witness.put(ticket, timeout=timeout)
        return ticket.future

    def stats(self) -> dict:
        """The full metrics snapshot (docs/serving.md schema) plus live
        queue depths."""
        out = self.metrics.snapshot(cache_stats=self.session.cache.stats())
        out["depths"] = dict(witness=self._witness.depth(),
                             batcher=self.batcher.depth(),
                             prove=self._prove.depth())
        return out

    def close(self):
        """Drain everything in flight, then stop the workers.  Every
        already-submitted future resolves before close returns."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._witness.stop(wait=True)           # all tickets reach batcher
        self._stop_evt.set()
        self._scheduler.join()
        for ready in self.batcher.drain():      # flush partial batches
            self._prove.put(ready)
        self._prove.stop(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- witness stage -------------------------------------------------------
    def _handle_ticket(self, ticket: _Ticket):
        with be.use(self._backend):
            with obs.span("zkg.witness", parent=ticket.request,
                          request=ticket.request) as sp:
                run = self.session.run_query(ticket.qname, ticket.params)
                sp.set(steps=len(run.steps))
            self.metrics.witness_us.observe(sp.seconds * 1e6)
            ticket.witness_span = sp.id
            ticket.run = run
            ticket.results = [None] * len(run.steps)
            ticket.remaining = len(run.steps)
            if not run.steps:
                self._complete(ticket)
                return
            for pos, st in enumerate(run.steps):
                key = self.session.step_shape_key(st)
                ready = self.batcher.add(key, StepSlot(ticket, pos, st))
                if ready is not None:
                    self._prove.put(ready)      # blocks = backpressure

    def _ticket_error(self, ticket: _Ticket, exc: BaseException):
        self._fail(ticket, exc)

    # -- scheduler (deadline flush) ------------------------------------------
    def _run_scheduler(self):
        while not self._stop_evt.wait(
                timeout=max(0.001, self.batcher.next_deadline())):
            for ready in self.batcher.take_expired():
                self._prove.put(ready)

    # -- prove stage ---------------------------------------------------------
    def _lane_count(self, n: int) -> int:
        if not self.pad_pow2:
            return n
        lanes = 1
        while lanes < n:
            lanes *= 2
        return lanes

    def _handle_batch(self, ready: BatchReady):
        live = [s for s in ready.slots if not s.ticket.failed]
        if not live:
            return
        now = obs.now()
        for s in live:
            obs.record("zkg.queue", s.enqueued_ns, now,
                       parent=s.ticket.witness_span, request=s.ticket.request)
            self.metrics.queue_wait_us.observe((now - s.enqueued_ns) / 1e3)
        steps = [s.step for s in live]
        pad = self._lane_count(len(steps)) - len(steps)
        circuit = steps[0].op.circuit
        with obs.span("zkg.prove_batch", lanes=len(steps), pad_lanes=pad,
                      circuit=circuit.name, rows=circuit.n_rows,
                      requests=[s.ticket.request for s in live]) as sp, \
                be.use(self._backend):
            # pad lanes replicate the last witness; their proofs are
            # discarded (bit-identity makes them redundant, not wrong)
            step_proofs = self.session.prove_steps(steps + [steps[-1]] * pad)
        self.metrics.observe_prove(circuit.name, sp.seconds * 1e6)
        self.metrics.inc("batches")
        self.metrics.inc("lanes", len(steps))
        self.metrics.inc("pad_lanes", pad)
        self.metrics.batch_occupancy.observe(len(steps))
        self.metrics.observe_phases(step_proofs[0].proof.timings)
        for slot, sp in zip(live, step_proofs):
            self._fulfill(slot, sp)

    def _batch_error(self, ready: BatchReady, exc: BaseException):
        for slot in ready.slots:
            self._fail(slot.ticket, exc)

    # -- completion bookkeeping ----------------------------------------------
    def _fulfill(self, slot: StepSlot, step_proof):
        ticket = slot.ticket
        with self._lock:
            if ticket.failed:
                return
            ticket.results[slot.pos] = step_proof
            ticket.remaining -= 1
            done = ticket.remaining == 0
        if done:
            self._complete(ticket)

    def _complete(self, ticket: _Ticket):
        bundle = ProofBundle(ticket.qname, dict(ticket.params),
                             list(ticket.results or []), ticket.run.result,
                             self.session.cfg, self._manifest_digest)
        self.metrics.inc("completed")
        obs.record("zkg.request", ticket.submitted_ns, obs.now(),
                   span_id=ticket.request, request=ticket.request,
                   query=ticket.qname)
        ticket.future.set_result(bundle)

    def _fail(self, ticket: _Ticket, exc: BaseException):
        with self._lock:
            if ticket.failed:
                return
            ticket.failed = True
        self.metrics.inc("failed")
        obs.record("zkg.request", ticket.submitted_ns, obs.now(),
                   span_id=ticket.request, request=ticket.request,
                   query=ticket.qname, failed=1)
        ticket.future.set_exception(exc)
