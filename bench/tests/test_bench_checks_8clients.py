"""Whole runs of the eight-client cell on the CPU at a tiny size: a sound
run fills lane-batched proves and is correct; a prover that proves half of
a batch and hands the rest copies is not."""
from __future__ import annotations

import threading

import pytest

import tinycell
from harness.meter import CompileMeter

SECONDS = 4.0


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


@pytest.fixture(scope="module")
def cell():
    return tinycell.cell("snb_60k", "is5_8clients", requests_per_client=2)


def test_sound_run_is_correct(cell, meter):
    run = tinycell.run(cell, 2**33 + 1, SECONDS, meter)
    assert run.correct, run.checks
    assert run.stats["counters"]["lanes"] > run.stats["counters"]["batches"]
    assert run.counts["window_programs"] == 0
    assert run.counts["window_keygen_misses"] == 0


def test_half_a_batch_left_out_is_not_correct(cell, meter, monkeypatch):
    from repro.core.session import ZKGraphSession
    orig = ZKGraphSession.prove_steps

    def half(self, steps):
        if threading.current_thread() is threading.main_thread() \
                or len(steps) < 2:
            return orig(self, steps)
        kept = orig(self, steps[:len(steps) // 2])
        return kept + [kept[-1]] * (len(steps) - len(kept))
    monkeypatch.setattr(ZKGraphSession, "prove_steps", half)
    run = tinycell.run(cell, 9, SECONDS, meter)
    assert not run.correct
    assert run.checks["verify_reject"]["value"] > 0
