"""Program spans (``repro.obs``): nesting and ids, the bounded buffer, what
recording off costs, the dump format, and the span tree a ``ProofService``
leaves per request, with proof bytes unchanged by recording."""
import dataclasses
import sys
import threading

import jax
import pytest

from repro import obs
from repro.core.session import ZKGraphSession
from repro.serve import ProofService

PHASES = ("commit_advice", "phase2_ext", "quotient", "ood_openings", "deep",
          "fri", "query_openings")


def _by_name(records, name):
    return [r for r in records if r.name == name]


# ---------------------------------------------------------------------------
# the facility
# ---------------------------------------------------------------------------
def test_spans_nest_and_take_parent_ids():
    with obs.recording() as rec:
        with obs.span("outer", request=7, rows=3) as outer:
            with obs.span("inner") as inner:
                pass
            with obs.span("sibling", lanes=2):
                pass
        with obs.span("root"):
            pass
    got = {r.name: r for r in rec.spans()}
    assert [r.name for r in rec.spans()] == ["inner", "sibling", "outer",
                                             "root"]
    assert got["outer"].parent is None and got["outer"].request == 7
    assert got["outer"].attrs == {"rows": 3}
    assert got["inner"].parent == outer.id == got["outer"].id
    assert got["inner"].id == inner.id
    assert got["sibling"].parent == outer.id
    assert got["inner"].request == got["sibling"].request == 7
    assert got["sibling"].attrs == {"lanes": 2}
    assert got["root"].parent is None and got["root"].request is None
    assert len({r.id for r in rec.spans()}) == 4
    o = got["outer"]
    for child in (got["inner"], got["sibling"]):
        assert o.start_ns <= child.start_ns <= child.end_ns <= o.end_ns


def test_request_id_crosses_threads():
    """A span on another thread names its parent and request explicitly;
    its own children there inherit the request."""
    rid = obs.new_id()
    with obs.recording() as rec:
        with obs.span("witness", parent=rid, request=rid) as w:
            pass

        def prove_thread():
            with obs.span("batch", parent=w.id, request=rid):
                with obs.span("phase"):
                    pass
        t = threading.Thread(target=prove_thread)
        t.start()
        t.join()
        obs.record("request", w.start_ns, obs.now(), span_id=rid,
                   request=rid, query="IS5")
    got = {r.name: r for r in rec.spans()}
    assert got["witness"].parent == rid and got["witness"].request == rid
    assert got["batch"].parent == got["witness"].id
    assert got["batch"].request == rid
    assert got["phase"].parent == got["batch"].id
    assert got["phase"].request == rid
    assert got["request"].id == rid and got["request"].attrs == {
        "query": "IS5"}


def test_buffer_drops_oldest_and_counts():
    with obs.recording(capacity=3) as rec:
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
    assert [r.name for r in rec.spans()] == ["s2", "s3", "s4"]
    assert rec.dropped == 2
    with pytest.raises(ValueError):
        obs.recording(capacity=0)


def test_recorder_counts_every_span_from_many_threads():
    """Spans from more threads than cores, with the interpreter switching
    threads often: every span is kept or counted as dropped, and each
    thread's spans nest under that thread's own parent."""
    n_threads, per_thread = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.recording(capacity=1000) as rec:
            def work(i):
                with obs.span("outer", request=i):
                    for _ in range(per_thread - 1):
                        with obs.span("inner"):
                            pass
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    kept = rec.spans()
    assert len(kept) == 1000
    assert len(kept) + rec.dropped == n_threads * per_thread
    outer = {r.id: r.request for r in kept if r.name == "outer"}
    for r in kept:
        if r.name == "inner" and r.parent in outer:
            assert r.request == outer[r.parent]


def test_recording_off_leaves_no_record_and_opens_no_annotation(
        monkeypatch):
    opened, blocked = [], []

    class Fake:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs, "_annotation", Fake)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    assert obs._recorder is None
    with obs.span("off", rows=1) as sp:
        sp.sync(jax.numpy.zeros(2))
    obs.record("off.cross", 1, 2)
    assert sp.id is None and sp.parent is None and sp.seconds >= 0
    assert opened == [] and blocked == []

    with obs.recording() as rec:
        with obs.span("on") as sp:
            sp.sync(jax.numpy.zeros(2))
        obs.record("on.cross", 1, 2)
    assert opened == ["on"] and len(blocked) == 1
    assert [r.name for r in rec.spans()] == ["on", "on.cross"]
    assert obs._recorder is None


def test_one_recording_at_a_time():
    with obs.recording():
        with pytest.raises(RuntimeError):
            with obs.recording():
                pass
    with obs.recording() as rec:         # the first one ended cleanly
        with obs.span("again"):
            pass
    assert len(rec.spans()) == 1


def test_dump_round_trips(tmp_path):
    with obs.recording() as rec:
        with obs.span("a", lanes=2, requests=[3, 4]):
            with obs.span("b", query="IS5"):
                pass
        obs.record("c", 10, 20, parent=1, request=2)
    path = tmp_path / "spans.jsonl"
    assert rec.dump(path) == 3
    assert len(path.read_text().splitlines()) == 3
    assert obs.load(path) == rec.spans()


def test_phases_time_consecutive_spans():
    phase = obs.Phases("zkg.test", lanes=4)
    with obs.recording() as rec:
        with phase("one"):
            pass
        with phase("two"):
            pass
    t = phase.timings()
    assert set(t) == {"one", "two", "total"}
    assert t["total"] >= t["one"] + t["two"]
    assert [r.name for r in rec.spans()] == ["zkg.test.one", "zkg.test.two"]
    assert all(r.attrs == {"lanes": 4} for r in rec.spans())


def test_prover_phases_sync_only_while_recording(owner, monkeypatch):
    """While recording, each prover phase blocks on its device outputs;
    off, the prover never waits.  The proof bytes are the same."""
    st = owner.run_query("IS5", dict(message=(1 << 20) + 5)).steps[0]
    owner.cache.ensure(st.op, owner.cfg)
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    off = st.op.prove(st.advice, st.instance, st.data)
    assert calls == []
    with obs.recording() as rec:
        on = st.op.prove(st.advice, st.instance, st.data)
    assert len(calls) == 4      # commit_advice, phase2_ext, quotient, deep
    assert on.to_bytes() == off.to_bytes()
    assert [r.name for r in rec.spans()] == [f"zkg.prove.{p}"
                                            for p in PHASES]
    assert set(off.timings) == set(PHASES) | {"total"}


# ---------------------------------------------------------------------------
# the service's span tree
# ---------------------------------------------------------------------------
def _check_tree(records, n_requests: int):
    """Each request: ``zkg.request`` -> ``zkg.witness`` -> ``zkg.queue`` per
    step -> the ``zkg.prove_batch`` that lists it -> seven phases inside
    that batch."""
    requests = _by_name(records, "zkg.request")
    assert len(requests) == n_requests
    batches = _by_name(records, "zkg.prove_batch")
    for req in requests:
        rid = req.id
        assert req.request == rid and "failed" not in req.attrs
        (wit,) = [r for r in _by_name(records, "zkg.witness")
                  if r.request == rid]
        assert wit.parent == rid and wit.attrs["steps"] >= 1
        queues = [r for r in _by_name(records, "zkg.queue")
                  if r.request == rid]
        assert len(queues) == wit.attrs["steps"]
        assert all(q.parent == wit.id for q in queues)
        mine = [b for b in batches if rid in b.attrs["requests"]]
        assert len(mine) == wit.attrs["steps"]
        for b in mine:
            assert b.attrs["lanes"] == len(b.attrs["requests"])
            phases = [r for r in records if r.parent == b.id
                      and r.name.startswith("zkg.prove.")]
            assert [p.name for p in phases] == [f"zkg.prove.{p}"
                                                for p in PHASES]
            for p in phases:
                assert b.start_ns <= p.start_ns <= p.end_ns <= b.end_ns
                assert p.attrs["lanes"] == b.attrs["lanes"] + \
                    b.attrs["pad_lanes"]
            assert min(q.end_ns for q in queues) <= b.start_ns
        assert req.start_ns <= wit.start_ns and wit.end_ns <= req.end_ns


def test_service_span_tree_per_request_ref(db, owner, tiny_cfg):
    queries = [("IS5", dict(message=(1 << 20) + m)) for m in (7, 9, 12)]
    solo = [owner.prove(q, p).to_bytes() for q, p in queries]
    session = ZKGraphSession(db, tiny_cfg, commitments=owner.commitments)
    with obs.recording() as rec:
        with ProofService(session, max_batch=2, flush_interval=0.05) as svc:
            got = [f.result(timeout=600).to_bytes()
                   for f in [svc.submit(q, p) for q, p in queries]]
    assert got == solo                  # recording on == recording off
    _check_tree(rec.spans(), len(queries))
    assert rec.dropped == 0
    stats = svc.stats()
    assert stats["witness_us"]["count"] == len(queries)
    assert stats["phase_us"]["fri"]["count"] == stats["counters"]["batches"]


def test_service_span_tree_pallas_interpret(db, owner, tiny_cfg, bundle):
    """The same tree under the Pallas kernels, and the same bytes as the
    recording-off ``ref`` prove of the same query."""
    cfg = dataclasses.replace(tiny_cfg, backend="pallas-interpret")
    session = ZKGraphSession(db, cfg, commitments=owner.commitments)
    with obs.recording() as rec:
        with ProofService(session) as svc:
            got = svc.submit(bundle.query, bundle.params).result(timeout=600)
    assert got.to_bytes() == bundle.to_bytes()
    _check_tree(rec.spans(), 1)
