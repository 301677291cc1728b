"""The program's spans in a run: set aside from the trace reduction, the
device's idle time inside the prover's phases, idle time by span, the
records cut by part of the run, and the ``witness_s`` reader."""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts the program on the path)
from harness import spans, trace
from repro.obs import Record
from test_bench_reduce import Ev, Line, fake_run, profile, read


def with_spans():
    """``profile()`` (window 100..1100; TPU 0 idle 300-500 and 600-1050)
    with the program's spans on two host threads: a batch 150-1000 holding
    ``quotient`` 200-400 and ``ood_openings`` 400-900, and a witness span
    700-800 on another thread."""
    p = profile()
    p.planes[0].lines[0].events += [Ev("zkg.prove_batch", 150, 850),
                                    Ev("zkg.prove.quotient", 200, 200),
                                    Ev("zkg.prove.ood_openings", 400, 500)]
    p.planes[0].lines.append(Line("witness", [Ev("zkg.witness", 700, 100),
                                              Ev("zkg.commit", 0, 50)]))
    return p


def test_spans_leave_the_trace_reduction_as_it_was():
    plain = trace.window(profile())
    w, found = spans.split(with_spans())
    assert w.busy_s() == plain.busy_s()
    assert w.idle_share() == plain.idle_share()
    assert trace.idle_gaps(w) == trace.idle_gaps(plain)
    assert trace.top_ops(w) == trace.top_ops(plain)
    for a, b in zip(w.host, plain.host):
        assert list(a) == list(b)
    assert found == [("zkg.prove_batch", 150, 1000),
                     ("zkg.prove.quotient", 200, 400),
                     ("zkg.prove.ood_openings", 400, 900),
                     ("zkg.witness", 700, 800)]     # zkg.commit: before it
    # left in, the spans would take over the labels of the idle gaps
    mixed = dict(trace.idle_gaps(trace.window(with_spans())))
    assert any(k.startswith(spans.PREFIX) for k in mixed)


def test_prove_idle_share():
    w, found = spans.split(with_spans())
    # the phases cover 200-900; the device is idle 300-500 and 600-900
    assert spans.prove_idle_share(w, found) == pytest.approx(100 * 500 / 700)
    assert spans.prove_idle_share(w, [("zkg.witness", 700, 800)]) is None
    busy = [("zkg.prove.fri", 100, 300), ("zkg.prove.deep", 500, 600)]
    assert spans.prove_idle_share(w, busy) == 0.0


def test_idle_by_span_picks_the_innermost_and_sums_to_the_idle_time():
    w, found = spans.split(with_spans())
    got = dict(spans.idle_by_span(w, found))
    assert got == pytest.approx({
        "zkg.prove.quotient": 100e-9,      # 300-400
        "zkg.prove.ood_openings": 300e-9,  # 400-500, 600-700, 800-900
        "zkg.witness": 100e-9,             # 700-800, started last
        "zkg.prove_batch": 100e-9,         # 900-1000
        spans.OUTSIDE: 50e-9})             # 1000-1050
    gs, ge = w.gaps(w.devices[0])
    assert sum(got.values()) == pytest.approx((ge - gs).sum() / 1e9)
    assert spans.idle_by_span(w, []) == [[spans.OUTSIDE,
                                          pytest.approx(650e-9)]]


def test_idle_before_counts_partial_gaps():
    gs, ge = np.array([10.0, 50.0]), np.array([20.0, 60.0])
    got = spans._idle_before(gs, ge, [0, 10, 15, 20, 30, 55, 60, 99])
    assert list(got) == [0, 0, 5, 10, 10, 15, 20, 20]


def test_select_cuts_records_by_part_of_the_run():
    def rec(name, start_s):
        return Record(name, 1, None, None, int(start_s * 1e9),
                      int(start_s * 1e9) + 5)

    run = SimpleNamespace(window_start=100.0, window_end=130.0)
    recs = [rec("zkg.commit", 10.0), rec("zkg.witness", 101.0),
            rec("zkg.witness", 129.5), rec("zkg.verify.openings", 131.0),
            rec("zkg.verify.openings", 50.0)]
    assert [r.start_ns for r in spans.select(recs, "zkg.commit", run,
                                              "setup")] == [10 * 10**9]
    assert len(spans.select(recs, "zkg.witness", run, "window")) == 2
    assert len(spans.select(recs, "zkg.witness", run, "setup")) == 0
    assert [r.start_ns for r in spans.select(
        recs, "zkg.verify.openings", run, "verify")] == [131 * 10**9]
    with pytest.raises(ValueError):
        spans.select(recs, "zkg.commit", run, "warm-up")


def test_witness_s_reads_the_service_histogram():
    run = fake_run()
    assert read(run, "witness_s") is None      # a program without it
    run.stats["witness_us"] = dict(count=0, mean=0.0)
    assert read(run, "witness_s") is None
    run.stats["witness_us"] = dict(count=3, mean=250000.0)
    assert read(run, "witness_s") == pytest.approx(0.25)
