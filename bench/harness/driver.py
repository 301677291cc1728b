"""One run of one cell: set-up, the measured window, the checks.

Set-up, in order: the tables from ``--seed``; the owner's commitments to
the base tables the mix reads; the fixed request list from ``--seed``;
then every step shape of that list proved once at every lane count the
batcher can form for it, and one bundle of each query verified, so that no
keygen and no XLA program falls inside the window or the verify timings.

The window drives ``ProofService.submit`` on the owner session with
closed-loop clients.  Submissions stop at ``seconds``; what is in flight
then is drained and counted.  Afterwards every bundle of the window is
verified from its wire bytes by a session built only from the published
manifest, and its claimed result is compared with the plain reference.
"""
from __future__ import annotations

import statistics
import threading
import time
from dataclasses import dataclass, field

from . import spec as spec_mod
from . import trace as trace_mod
from . import traffic as traffic_mod

LATE_S = 120.0      # how long past the close an answer is waited for
TRACE_SECONDS = 8.0  # traced part of the window, from its start: the trace
                     # of an eager prover grows by ~10^5 device ops a second


@dataclass
class Served:
    client: int
    query: str
    params: dict
    submitted: float
    done: float = None
    bundle: object = None
    error: str = None


@dataclass
class Run:
    """What one run saw; metric readers read this."""
    cell: object
    device_kind: str
    setup_s: float
    served: list
    window_start: float
    window_end: float
    stats: dict
    counts: dict
    verify_s: list = field(default_factory=list)
    wire_bytes: list = field(default_factory=list)
    checks: dict = field(default_factory=dict)
    trace: object = None            # trace.Window of the traced run
    memory_peak: int = None         # peak device bytes, read after the window

    @property
    def completed(self) -> list:
        return [s for s in self.served if s.bundle is not None]

    @property
    def failed(self) -> int:
        return len(self.served) - len(self.completed)

    @property
    def correct(self) -> bool:
        return bool(self.completed) and all(
            v["value"] <= v["limit"] for v in self.checks.values())

    def latencies(self) -> list:
        return [s.done - s.submitted for s in self.completed]


def median(values: list):
    return statistics.median(values) if values else None


def base_tables(queries) -> list:
    """The registered base tables the queries' plans read."""
    from repro.core import ir
    from repro.core.operators import registry
    descs = set()
    for q in queries:
        for node in ir.build_plan(q).nodes:
            d = registry.adapter_for(node).data_desc(node)
            if d != "chained":
                descs.add(d)
    return sorted(descs)


def lane_counts(max_lanes: int, pad_pow2: bool) -> list:
    if not pad_pow2:
        return list(range(1, max_lanes + 1))
    out, n = [], 1
    while n < max_lanes:
        out.append(n)
        n *= 2
    return out + [n]


def warm(owner, verifier, requests: list, service: dict, log) -> dict:
    """Prove every step shape of the request list at every lane count the
    batcher can form, and verify one bundle of each query."""
    from repro.core.session import ProofBundle
    clients = len(requests)
    seen, verified, n_proves = {}, set(), 0
    per_query = {}
    for q, params in dict.fromkeys((q, tuple(sorted(p.items())))
                                   for row in requests for q, p in row):
        run = owner.run_query(q, dict(params))
        keys = [owner.step_shape_key(st) for st in run.steps]
        for k in set(keys):
            per_query[k] = max(per_query.get(k, 0), keys.count(k))
        if q not in verified:
            steps = [owner.prove_steps([st])[0] for st in run.steps]
            n_proves += len(steps)
            bundle = ProofBundle(q, dict(params), steps, run.result,
                                 owner.cfg, owner.commitments.digest())
            if not verifier.verify_bytes(bundle.to_bytes()):
                log(f"warm-up: the verifier rejects {q} {dict(params)}")
            verified.add(q)
        else:
            for st, k in zip(run.steps, keys):
                if k not in seen:
                    owner.prove_steps([st])
                    n_proves += 1
        for st, k in zip(run.steps, keys):
            seen.setdefault(k, st)
    for k, st in seen.items():
        most = min(service["max_batch"], clients * per_query[k])
        for lanes in lane_counts(most, service["pad_pow2"])[1:]:
            owner.prove_steps([st] * lanes)
            n_proves += 1
    log(f"warm-up: {len(seen)} step shapes, {n_proves} prove calls, "
        f"{len(verified)} bundles verified")
    return dict(shapes=len(seen), proves=n_proves)


def drive(svc, requests: list, seconds: float, log, during=None) -> tuple:
    """Closed-loop clients over the service until ``seconds`` pass;
    ``during(start)`` runs on this thread once the clients are going."""
    served, lock = [], threading.Lock()
    start = time.perf_counter()
    close = start + seconds

    def client(c: int, row: list):
        i = 0
        while time.perf_counter() < close:
            q, params = row[i % len(row)]
            i += 1
            s = Served(c, q, params, time.perf_counter())
            with lock:
                served.append(s)
            try:
                fut = svc.submit(q, params)
                s.bundle = fut.result(
                    timeout=max(1.0, close + LATE_S - time.perf_counter()))
            except Exception as e:      # a failed or late answer counts
                s.error = f"{type(e).__name__}: {e}"
                s.bundle = None
                log(f"request failed: client {c} {q} {params}: {s.error}")
            s.done = time.perf_counter()

    threads = [threading.Thread(target=client, args=(c, row),
                                name=f"bench-client-{c}")
               for c, row in enumerate(requests)]
    for t in threads:
        t.start()
    try:
        if during is not None:
            during(start)
    finally:
        for t in threads:
            t.join()
    return served, start


def run_cell(cell, seed: int, seconds: float, *, backend: str,
             t_start: float, meter, trace_dir=None, owner_prover=None,
             device_kind: str = "", memory_peak=lambda: None,
             log=print) -> Run:
    """Set up, measure and check one run of ``cell``.

    ``owner_prover`` replaces the configuration's prover parameters on the
    owner only (the control); the verifier always holds the
    configuration's."""
    import jax
    from repro.core import commit
    from repro.core import prover as pv
    from repro.core.commit import CommitmentManifest
    from repro.core.session import TrustAnchor, ZKGraphSession
    from repro.serve import ProofService

    config, service = cell.config, dict(cell.config["service"])
    ds, ref = spec_mod.dataset(cell), spec_mod.reference(cell)
    tables = ds.make(config, seed)
    db = ds.to_graphdb(tables)
    requests = traffic_mod.requests(cell.traffic, tables, seed)
    queries = sorted({q for row in requests for q, _ in row})

    cfg = pv.ProverConfig(**config["prover"], backend=backend)
    owner_cfg = pv.ProverConfig(**(owner_prover or config["prover"]),
                                backend=backend)
    mark = meter.mark()
    t = time.perf_counter()
    descs = base_tables(queries)
    manifest = commit.publish_commitments(db, owner_cfg, only=descs)
    log(f"commit: {len(manifest.roots)} roots of {descs} "
        f"({time.perf_counter() - t:.3f} s)")
    owner = ZKGraphSession(db, owner_cfg, commitments=manifest)
    published = CommitmentManifest.from_bytes(manifest.to_bytes())
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=published), cfg=cfg)
    t = time.perf_counter()
    warm(owner, verifier, requests, service, log)
    log(f"warm-up: {time.perf_counter() - t:.3f} s")
    setup = meter.since(mark)
    setup_s = time.perf_counter() - t_start

    svc = ProofService(owner, **service)
    mark = meter.mark()
    misses = owner.cache.stats()["misses"]
    tracing = []

    def trace_first_seconds(start):
        with jax.profiler.TraceAnnotation(trace_mod.TRACED_SPAN):
            time.sleep(max(0.0, start + min(TRACE_SECONDS, seconds)
                           - time.perf_counter()))
        jax.profiler.stop_trace()
        tracing.clear()

    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=_trace_options())
        tracing.append(True)
    try:
        served, start = drive(svc, requests, seconds, log,
                              during=trace_first_seconds if tracing
                              else None)
        end = max([s.done for s in served] + [start])
    finally:
        if tracing:
            jax.profiler.stop_trace()
        svc.close()
    window = meter.since(mark)
    counts = dict(
        window_programs=window["programs"],
        window_keygen_misses=owner.cache.stats()["misses"] - misses,
        setup_programs=setup["programs"],
        setup_compile_s=setup["secs"],
        setup_cache_hits=setup["cache_hits"])
    run = Run(cell, device_kind, setup_s, served, start, end, svc.stats(),
              counts)
    run.memory_peak = memory_peak()
    del svc, owner

    mark = meter.mark()
    mismatches = rejects = 0
    for s in run.completed:
        raw = s.bundle.to_bytes()
        run.wire_bytes.append(len(raw))
        t = time.perf_counter()
        ok = verifier.verify_bytes(raw)
        run.verify_s.append(time.perf_counter() - t)
        if not ok:
            rejects += 1
            log(f"verifier rejects {s.query} {s.params}")
        want = ref.answer(tables, s.query, s.params)
        got = ref.canonical(s.query, s.bundle.result)
        if got != want:
            mismatches += 1
            log(f"result differs from the reference: {s.query} "
                f"{s.params}: {got} != {want}")
    run.counts["verify_programs"] = meter.since(mark)["programs"]
    run.checks = dict(
        failed=dict(value=run.failed, limit=0),
        result_mismatch=dict(value=mismatches, limit=0),
        verify_reject=dict(value=rejects, limit=0))
    return run


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts
