"""Whole runs of the IC9 cell on the CPU at a tiny size: a sound run is
correct and reads its per-circuit prove times; a claimed answer altered
after proving is a result mismatch."""
from __future__ import annotations

import threading

import pytest

import tinycell
from harness import spec
from harness.meter import CompileMeter

SECONDS = 2.0
CELL = "snb_60k_ic.ic9_1client"


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


@pytest.fixture(scope="module")
def cell():
    return tinycell.cell("snb_60k_ic", "ic9_1client", requests_per_client=1)


def read(run, name):
    return spec.reader(spec.load_cell(CELL), name)(run)


def test_sound_run_is_correct(cell, meter):
    run = tinycell.run(cell, 2**33 + 3, SECONDS, meter)
    assert run.correct, run.checks
    assert {s.query for s in run.completed} == {"IC9"}
    assert run.counts["window_programs"] == 0
    assert run.counts["window_keygen_misses"] == 0
    by = run.stats["prove_us_by_circuit"]
    assert by["expand_set"]["count"] == by["expand_set_birc"]["count"] == \
        2 * len(run.completed)
    assert read(run, "set_expand_s") == pytest.approx(
        by["expand_set"]["mean"] / 1e6)
    assert read(run, "set_expand_birc_s") == pytest.approx(
        by["expand_set_birc"]["mean"] / 1e6)


def test_altered_messages_are_a_result_mismatch(cell, meter, monkeypatch):
    """The service's bundle claims other messages than it proved (the
    warm-up's, built on the main thread, are left alone): the reference
    sees it."""
    from repro.core.session import ProofBundle
    orig = ProofBundle.__init__

    def altered(self, query, params, steps, result, *rest, **kw):
        if threading.current_thread() is not threading.main_thread():
            result = dict(result, messages=[m + 1 for m in
                                            result["messages"]])
        orig(self, query, params, steps, result, *rest, **kw)
    monkeypatch.setattr(ProofBundle, "__init__", altered)
    run = tinycell.run(cell, 2**33 + 4, SECONDS, meter)
    assert not run.correct
    assert run.checks["result_mismatch"]["value"] == 1


def test_control_is_not_correct(cell, meter):
    """The owner proves with half the configuration's FRI queries: the
    verifier holding the configured parameters refuses the IC9 bundle."""
    run = tinycell.run(cell, 2**33 + 5, SECONDS, meter,
                       owner_prover=cell.config["control"]["prover"])
    assert not run.correct
    assert run.checks["verify_reject"]["value"] == len(run.completed) >= 1
