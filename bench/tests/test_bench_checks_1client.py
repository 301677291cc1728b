"""Whole runs of the one-client cell on the CPU at a tiny size: a sound run
is correct, and each fault this cell can have, planted in the timed path
underneath, makes ``correct`` come out false, as does the control."""
from __future__ import annotations

import threading

import pytest

import tinycell
from harness.meter import CompileMeter

SECONDS = 3.0


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


@pytest.fixture(scope="module")
def cell():
    return tinycell.cell("snb_60k", "is_1client", requests_per_client=3)


def in_service():
    """Faults strike the service's worker threads, not the warm-up."""
    return threading.current_thread() is not threading.main_thread() \
        and not threading.current_thread().name.startswith("bench-client")


def test_sound_run_is_correct(cell, meter):
    run = tinycell.run(cell, 2**31 + 11, SECONDS, meter)
    assert run.correct, run.checks
    assert {s.query for s in run.completed} == {"IS3", "IS4", "IS5"}
    assert run.counts["window_programs"] == 0
    assert run.counts["window_keygen_misses"] == 0
    assert len(run.verify_s) == len(run.wire_bytes) == len(run.completed)


def test_an_altered_answer_is_not_correct(cell, meter, monkeypatch):
    from repro.core.session import ZKGraphSession
    orig = ZKGraphSession.run_query

    def altered(self, qname, params):
        run = orig(self, qname, params)
        if in_service():
            key = sorted(run.result)[0]
            run.result[key] = run.result[key] + 1
        return run
    monkeypatch.setattr(ZKGraphSession, "run_query", altered)
    run = tinycell.run(cell, 5, SECONDS, meter)
    assert not run.correct
    assert run.checks["result_mismatch"]["value"] > 0
    assert run.checks["verify_reject"]["value"] > 0


def test_a_stale_proof_is_not_correct(cell, meter, monkeypatch):
    """The prover hands back the proof it made before, state unchanged."""
    from repro.core.session import ZKGraphSession
    orig, last = ZKGraphSession.prove_step, {}

    def stale(self, st):
        key = self.step_shape_key(st)
        fresh = orig(self, st)
        if in_service() and key in last:
            return last[key]
        last[key] = fresh
        return fresh
    monkeypatch.setattr(ZKGraphSession, "prove_step", stale)
    run = tinycell.run(cell, 6, SECONDS, meter)
    assert not run.correct
    assert run.checks["verify_reject"]["value"] > 0


def test_control_is_not_correct(cell, meter):
    """Fewer FRI queries than the configuration's guarantee: the verifier
    holding the configured parameters refuses every bundle."""
    run = tinycell.run(cell, 7, SECONDS, meter,
                       owner_prover=cell.config["control"]["prover"])
    assert not run.correct
    assert run.checks["verify_reject"]["value"] == len(run.completed) > 0
