"""Serving-layer tests: the lane-batched prover's bit-identity contract,
ProofService end-to-end equivalence with the sequential session, the
thread-safe single-flight keygen cache, and the pipeline mechanics.

The load-bearing property: a proof produced inside a batch is WIRE-BYTE-
IDENTICAL to the same witness proved solo (timings excluded — they are
host telemetry).  Everything the service does — shape routing, lane
padding, deadline flushing — must be invisible in the artifact.
"""
import dataclasses
import textwrap
import threading
import time
from collections import Counter

import numpy as np
import pytest

from repro.core import backend as be
from repro.core import prover as pv
from repro.core.session import KeygenCache, ZKGraphSession
from repro.core.transcript import BatchedTranscript, Transcript
from repro.serve import (Histogram, ProofService, ServiceClosed, ShapeBatcher,
                         Stage, StepSlot)

PARITY = ["ref", "pallas-interpret"]


def _canonical_proof(proof) -> bytes:
    proof.timings = {}
    return proof.to_bytes()


def _canonical_bundle(bundle) -> bytes:
    for sp in bundle.steps:
        sp.proof.timings = {}
    return bundle.to_bytes()


# ---------------------------------------------------------------------------
# batched transcript: lockstep lanes == solo transcripts
# ---------------------------------------------------------------------------
def test_batched_transcript_matches_solo_lanes():
    rng = np.random.default_rng(5)
    lane_vals = [rng.integers(0, 2**31, size=13) for _ in range(3)]
    shared = rng.integers(0, 2**31, size=9)

    solos = []
    for vals in lane_vals:
        tx = Transcript("lanes-test")
        tx.absorb(shared)
        tx.absorb(vals)
        solos.append(tx)
    btx = BatchedTranscript("lanes-test", lanes=3)
    btx.absorb_shared(shared)
    btx.absorb(np.stack(lane_vals))

    ch = btx.challenge_ext()
    for l, tx in enumerate(solos):
        np.testing.assert_array_equal(ch[l], tx.challenge_ext())
    idx = btx.challenge_indices(7, 64)
    for l, tx in enumerate(solos):
        np.testing.assert_array_equal(idx[l], tx.challenge_indices(7, 64))


# ---------------------------------------------------------------------------
# lane-batched prover: bit-identity with the solo prover
# ---------------------------------------------------------------------------
def test_prove_batch_bytes_match_solo(owner):
    """Two IS5 queries: batch their (same-shaped) steps in one prove_batch
    pass and require byte equality with solo proves, lane by lane."""
    runs = [owner.run_query("IS5", dict(message=(1 << 20) + m))
            for m in (3, 9)]
    steps = [st for run in runs for st in run.steps]
    key0 = owner.step_shape_key(steps[0])
    assert all(owner.step_shape_key(st) == key0 for st in steps[1:])

    solo = [_canonical_proof(owner.prove_step(st).proof) for st in steps]
    batched = owner.prove_steps(steps)
    assert len(batched) == len(steps)
    for sp_solo, sp_batch in zip(solo, batched):
        assert _canonical_proof(sp_batch.proof) == sp_solo


def test_prove_steps_rejects_mixed_shapes(owner):
    st_is5 = owner.run_query("IS5", dict(message=(1 << 20) + 3)).steps[0]
    st_is4 = owner.run_query("IS4", dict(message=(1 << 20) + 3)).steps[0]
    if owner.step_shape_key(st_is4) == owner.step_shape_key(st_is5):
        pytest.skip("IS4/IS5 share a circuit shape at this size")
    with pytest.raises(AssertionError):
        owner.prove_steps([st_is5, st_is4])


def test_prove_steps_single_lane_degrades_to_solo(owner):
    st = owner.run_query("IS5", dict(message=(1 << 20) + 5)).steps[0]
    sp = owner.prove_steps([st])[0]
    assert _canonical_proof(sp.proof) == \
        _canonical_proof(owner.prove_step(st).proof)


def test_batched_proofs_verify(owner):
    """Step proofs from a batch pass the solo verifier (full-bundle
    verification through the service is covered below)."""
    runs = [owner.run_query("IS5", dict(message=(1 << 20) + m))
            for m in (11, 15)]
    steps = [st for run in runs for st in run.steps]
    sps = owner.prove_steps(steps)
    for st, sp in zip(steps, sps):
        assert st.op.verify(sp.instance, sp.proof)


# ---------------------------------------------------------------------------
# ProofService: concurrent serving == sequential session, byte for byte
# ---------------------------------------------------------------------------
def _query_mix(seed: int, n: int):
    """A deterministic 'random' mix of single-step LDBC short reads."""
    rng = np.random.default_rng(seed)
    mix = []
    for _ in range(n):
        kind = ["IS5", "IS4"][int(rng.integers(0, 2))]
        mix.append((kind, dict(message=(1 << 20) + int(rng.integers(0, 32)))))
    return mix


def _serve_and_compare(db, owner, cfg, queries, **svc_kw):
    seq = ZKGraphSession(db, cfg, commitments=owner.commitments)
    expected = [_canonical_bundle(seq.prove(q, p)) for q, p in queries]

    svc_session = ZKGraphSession(db, cfg, commitments=owner.commitments)
    with ProofService(svc_session, **svc_kw) as svc:
        futs = [svc.submit(q, p) for q, p in queries]
        got = [f.result(timeout=600) for f in futs]
        stats = svc.stats()
    for bundle, raw in zip(got, expected):
        assert _canonical_bundle(bundle) == raw
    return got, stats


def test_service_bundles_wire_identical_ref(db, owner, tiny_cfg, verifier):
    queries = _query_mix(seed=7, n=5)
    bundles, stats = _serve_and_compare(
        db, owner, tiny_cfg, queries, max_batch=4, flush_interval=0.1)
    assert stats["counters"]["completed"] == len(queries)
    assert stats["counters"]["failed"] == 0
    # batching actually happened: fewer prove batches than queries
    assert stats["counters"]["batches"] < len(queries)
    assert stats["batch_occupancy"]["max"] >= 2
    for bundle in bundles:
        assert verifier.verify(bundle)


@pytest.mark.slow
def test_service_bundles_wire_identical_both_backends(db, owner, tiny_cfg):
    """The cross-backend property: for a random query mix, served bundles
    are wire-byte-identical to sequential proves under BOTH the ref and the
    pallas-interpret backend (and therefore to each other)."""
    queries = _query_mix(seed=13, n=3)
    per_backend = {}
    for name in PARITY:
        cfg = dataclasses.replace(tiny_cfg, backend=name)
        bundles, stats = _serve_and_compare(
            db, owner, cfg, queries, max_batch=4, flush_interval=0.1)
        assert stats["counters"]["failed"] == 0
        per_backend[name] = [_canonical_bundle(b) for b in bundles]
    # cfg.backend is compare=False metadata, so the encodings must agree
    assert per_backend["ref"] == per_backend["pallas-interpret"]


def test_service_error_isolated_to_one_query(db, owner, tiny_cfg):
    session = ZKGraphSession(db, tiny_cfg, commitments=owner.commitments)
    with ProofService(session, max_batch=2, flush_interval=0.05) as svc:
        bad = svc.submit("NO_SUCH_QUERY", {})
        good = svc.submit("IS5", dict(message=(1 << 20) + 7))
        with pytest.raises(KeyError):
            bad.result(timeout=600)
        assert good.result(timeout=600).query == "IS5"
    stats = svc.stats()
    assert stats["counters"]["failed"] == 1
    assert stats["counters"]["completed"] == 1


def test_service_rejects_after_close(db, owner, tiny_cfg):
    session = ZKGraphSession(db, tiny_cfg, commitments=owner.commitments)
    svc = ProofService(session)
    svc.close()
    with pytest.raises(ServiceClosed):
        svc.submit("IS5", dict(message=3))
    svc.close()     # idempotent


def test_service_metrics_schema(db, owner, tiny_cfg):
    session = ZKGraphSession(db, tiny_cfg, commitments=owner.commitments)
    with ProofService(session, max_batch=2, flush_interval=0.05) as svc:
        svc.submit("IS5", dict(message=(1 << 20) + 2)).result(timeout=600)
        stats = svc.stats()
    # the documented schema (docs/serving.md) — exact top-level keys
    assert set(stats) == {"counters", "phase_us", "witness_us",
                          "queue_wait_us", "prove_us", "prove_us_by_circuit",
                          "batch_occupancy", "keygen_cache", "depths"}
    assert set(stats["counters"]) == {"submitted", "completed", "failed",
                                      "batches", "lanes", "pad_lanes"}
    assert {"fri", "total"} <= set(stats["phase_us"])
    assert set(stats["prove_us_by_circuit"]) == {"expand_el"}
    for stat in (stats["phase_us"]["total"], stats["witness_us"],
                 stats["queue_wait_us"], stats["batch_occupancy"],
                 stats["prove_us_by_circuit"]["expand_el"]):
        assert set(stat) == {"count", "mean", "p50", "p95", "max"}
    assert set(stats["keygen_cache"]) == {"hits", "misses", "waits",
                                          "entries"}


def test_service_prove_times_by_circuit_ic9(db, owner, tiny_cfg):
    """One IC9 through the service: its set expansions, BiRC hops and
    OrderBy are timed apart (``prove_us_by_circuit``), each batch span
    names its circuit and rows, and with recording off nothing is
    recorded."""
    from repro import obs
    from repro.core import ir
    k = db.tables["person_knows_person"]
    ids, deg = np.unique(np.concatenate([k.src, k.dst]), return_counts=True)
    person = int(ids[np.argmax(deg)])
    steps = ir.execute(db, ir.build_plan("IC9"), dict(person=person)).steps
    want = Counter(st.op.circuit.name for st in steps)
    assert want == {"expand_set_birc": 2, "expand_set": 2, "orderby": 1}
    session = ZKGraphSession(db, tiny_cfg, commitments=owner.commitments)
    with obs.recording() as rec:
        with ProofService(session, max_batch=2, flush_interval=0.05) as svc:
            svc.submit("IC9", dict(person=person)).result(timeout=600)
    by = svc.stats()["prove_us_by_circuit"]
    assert {c: h["count"] for c, h in by.items()} == want
    assert all(h["mean"] > 0 for h in by.values())
    batches = [r for r in rec.spans() if r.name == "zkg.prove_batch"]
    assert sorted((b.attrs["circuit"], b.attrs["rows"]) for b in batches) \
        == sorted((st.op.circuit.name, st.op.circuit.n_rows) for st in steps)
    for b in batches:
        phases = [r for r in rec.spans() if r.parent == b.id
                  and r.name.startswith("zkg.prove.")]
        assert phases and all(p.attrs["circuit"] == b.attrs["circuit"]
                              for p in phases)
    recorded = len(rec.spans())
    with ProofService(session, max_batch=2, flush_interval=0.05) as svc:
        svc.submit("IC9", dict(person=person)).result(timeout=600)
    assert len(rec.spans()) == recorded
    assert sum(h["count"] for h in
               svc.stats()["prove_us_by_circuit"].values()) == len(steps)


# ---------------------------------------------------------------------------
# shape batcher + pipeline mechanics (no proving)
# ---------------------------------------------------------------------------
def _slot(key="k"):
    return StepSlot(ticket=None, pos=0, step=key)


def test_batcher_flushes_on_size():
    b = ShapeBatcher(max_batch=3, flush_interval=999)
    assert b.add("a", _slot()) is None
    assert b.add("b", _slot()) is None      # different shape: own queue
    assert b.add("a", _slot()) is None
    ready = b.add("a", _slot())
    assert ready is not None and ready.key == "a" and len(ready.slots) == 3
    assert b.depth() == 1                   # "b" still waiting


def test_batcher_flushes_on_deadline():
    b = ShapeBatcher(max_batch=8, flush_interval=0.01)
    b.add("a", _slot())
    assert b.take_expired(now=time.monotonic()) == [] or True  # not yet due
    time.sleep(0.02)
    ready = b.take_expired()
    assert len(ready) == 1 and len(ready[0].slots) == 1
    assert b.depth() == 0


def test_batcher_drain():
    b = ShapeBatcher(max_batch=8, flush_interval=999)
    b.add("a", _slot())
    b.add("b", _slot())
    assert sorted(r.key for r in b.drain()) == ["a", "b"]
    assert b.depth() == 0


def test_stage_backpressure_and_error_isolation():
    done, errs = [], []
    gate = threading.Event()

    def handler(item):
        gate.wait(5)
        done.append(item)
        if item == "bad":
            raise ValueError(item)

    stage = Stage("t", handler, maxsize=1,
                  on_error=lambda item, e: errs.append(item))
    stage.start()
    stage.put("bad")            # worker picks it up, blocks on gate
    time.sleep(0.05)
    stage.put("ok")             # fills the 1-slot inbox
    with pytest.raises(Exception):
        stage.inbox.put("overflow", timeout=0.05)   # backpressure: full
    gate.set()
    stage.stop(wait=True)
    assert done == ["bad", "ok"] and errs == ["bad"]


def test_histogram_percentiles():
    h = Histogram(max_samples=100)
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["max"] == 100.0
    assert 45 <= snap["p50"] <= 55 and 90 <= snap["p95"] <= 100


def test_prove_times_by_circuit_lose_no_observation_across_threads():
    """Threads racing to make the first entry of a circuit all land in
    one histogram per circuit, and the pooled one sees every batch."""
    import sys
    from repro.serve.metrics import ServiceMetrics
    m, names, per = ServiceMetrics(), ("a", "b", "c"), 200
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda i=i: [m.observe_prove(names[(i + j) % 3], 1.0)
                                for j in range(per)]) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    snap = m.snapshot()
    assert sum(h["count"] for h in snap["prove_us_by_circuit"].values()) \
        == snap["prove_us"]["count"] == 16 * per
    assert set(snap["prove_us_by_circuit"]) == set(names)


# ---------------------------------------------------------------------------
# thread-safe keygen cache: single-flight misses, thread-local backend scopes
# ---------------------------------------------------------------------------
def _tiny_op():
    from repro.core.operators import registry
    return registry.build_operator("expand", dict(
        n_rows=32, m_edges=20, with_prop=False, reverse=False))


def test_keygen_cache_single_flight(tiny_cfg, monkeypatch):
    """N threads demand the same missing key at once: keygen runs once,
    everyone else blocks on the leader and shares its Keys."""
    calls = []
    real_keygen = pv.keygen

    def slow_keygen(circuit, cfg):
        calls.append(threading.get_ident())
        time.sleep(0.1)                     # widen the race window
        return real_keygen(circuit, cfg)

    monkeypatch.setattr(pv, "keygen", slow_keygen)
    cache = KeygenCache()
    results, failures = [], []

    def worker():
        try:
            results.append(cache.ensure(_tiny_op(), tiny_cfg).keys)
        except BaseException as exc:        # pragma: no cover
            failures.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures
    assert len(calls) == 1, "keygen must be single-flight per key"
    assert all(keys is results[0] for keys in results)
    stats = cache.stats()
    assert stats["misses"] == 1 and stats["entries"] == 1
    assert stats["waits"] >= 1              # someone actually blocked


def test_keygen_cache_leader_failure_reelects(tiny_cfg, monkeypatch):
    """A failing leader must not strand its waiters: they re-elect and one
    of them completes the keygen."""
    real_keygen = pv.keygen
    state = dict(first=True)
    barrier = threading.Barrier(2)

    def flaky_keygen(circuit, cfg):
        if state.pop("first", False):
            barrier.wait(5)                 # ensure a waiter is parked
            time.sleep(0.05)
            raise RuntimeError("injected keygen failure")
        return real_keygen(circuit, cfg)

    monkeypatch.setattr(pv, "keygen", flaky_keygen)
    cache = KeygenCache()
    outcomes = []

    def worker(first):
        try:
            if not first:
                barrier.wait(5)
            outcomes.append(cache.ensure(_tiny_op(), tiny_cfg).keys)
        except RuntimeError as exc:
            outcomes.append(exc)

    t1 = threading.Thread(target=worker, args=(True,))
    t2 = threading.Thread(target=worker, args=(False,))
    t1.start()
    time.sleep(0.02)
    t2.start()
    t1.join()
    t2.join()
    kinds = sorted(type(o).__name__ for o in outcomes)
    assert kinds == ["Keys", "RuntimeError"]
    assert cache.stats()["entries"] == 1


def test_backend_scopes_are_thread_local():
    """A be.use() scope on one thread must not leak into another — worker
    threads pin their own backend explicitly (ProofService does)."""
    seen = {}

    def probe():
        seen["worker"] = be.active_name()

    with be.use("pallas-interpret"):
        t = threading.Thread(target=probe)
        t.start()
        t.join()
        assert be.active_name() == "pallas-interpret"
    assert seen["worker"] != "pallas-interpret"


def test_lde_cache_concurrent_access(db, tiny_cfg):
    """Concurrent ensure() against one shared session cache (the service's
    real access pattern) keeps the fixed-LDE caches consistent: every
    thread ends up with the same Keys object per shape."""
    session = ZKGraphSession(db, tiny_cfg)
    st = session.run_query("IS5", dict(message=(1 << 20) + 3)).steps[0]
    solo_keys = pv.keygen(st.op.circuit, tiny_cfg)
    got = []

    def worker():
        got.append(session.cache.ensure(st.op, session.cfg).keys)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(k is got[0] for k in got)
    np.testing.assert_array_equal(np.asarray(got[0].fixed_lde),
                                  np.asarray(solo_keys.fixed_lde))


# ---------------------------------------------------------------------------
# lanes spread over a four-device mesh (subprocess: the virtual-device XLA
# flag must be set before JAX starts, and must not leak into other tests)
# ---------------------------------------------------------------------------
_FOUR_DEVICE_SERVICE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.core import prover as pv
    from repro.core.session import ZKGraphSession
    from repro.graphdb import ldbc
    from repro.serve import ProofService
    from repro.serve.placement import Placement, serving_mesh

    assert len(jax.devices()) == 4, jax.devices()
    db = ldbc.generate(n_knows=96, n_persons=24, n_comments=64, seed=11)
    cfg = pv.ProverConfig(blowup=4, n_queries=4, fri_final_size=16,
                          backend="pallas-interpret")
    owner = ZKGraphSession(db, cfg)
    params = [dict(message=(1 << 20) + m) for m in range(8)]

    def canonical(bundle):
        for sp in bundle.steps:
            sp.proof.timings = {}
        return bundle.to_bytes()

    solo = [canonical(owner.prove("IS5", p)) for p in params]
    placement = Placement(serving_mesh())
    assert placement.lane_parallelism == 4
    with ProofService(owner, max_batch=8, flush_interval=0.5,
                      placement=placement) as svc:
        futures = [svc.submit("IS5", p) for p in params]
        served = [canonical(f.result(timeout=400)) for f in futures]
        batches = svc.stats()["counters"]["batches"]
    assert batches == 1, batches
    assert served == solo
    print("OK")
""")


def test_service_lanes_over_four_devices_match_solo():
    """``Placement(serving_mesh())`` on four (virtual CPU) devices: eight
    IS5 lanes proved in one batch, spread over the devices with the Pallas
    kernels (interpret mode) run per device, are wire-byte-identical to
    solo one-device proves."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_SERVICE],
                         capture_output=True, text=True, timeout=500, env=env)
    assert out.returncode == 0 and "OK" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-4000:]
