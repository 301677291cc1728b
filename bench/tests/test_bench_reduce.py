"""The reduction from a trace and a run's records to metrics, on hand-built
traces and runs."""
from __future__ import annotations

from dataclasses import dataclass, field

import pytest

import tinycell
from harness import driver, kernels, spec, trace


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = field(default_factory=list)


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


@dataclass
class Profile:
    planes: list


KERNEL_TEXT = ('%custom-call.3 = u32[16,4096]{1,0:T(8,128)} custom-call('
               'u32[16,4096]{1,0:T(8,128)} %p0), custom_call_target='
               '"tpu_custom_call"')


def profile():
    """Window 100..1100 ns.  TPU 0 runs ops at 0-200, 150-300, 500-600 and
    1050-1200: busy 100-300, 500-600, 1050-1100 inside the window, 350 ns;
    the op at 500 is a Pallas call in a permutation program.  TPU 1 runs
    100-1100 throughout.  The host is in ``numpy glue`` from 600 to 1000."""
    host = Plane("/host:CPU", [
        Line("python3", [Ev(trace.TRACED_SPAN, 100, 1000),
                         Ev("numpy glue", 600, 400),
                         Ev("before", 0, 50)])])
    tpu0 = Plane("/device:TPU:0", [
        Line(trace.MODULES_LINE, [Ev("jit_a(11)", 0, 320),
                                  Ev("jit__permute_bucket(7)", 490, 120),
                                  Ev("jit_b(12)", 1040, 200)]),
        Line(trace.OPS_LINE, [
            Ev("%fusion.1 = u32[4]{0} fusion(u32[4]{0} %p)", 0, 200),
            Ev("%fusion.2 = u32[4]{0} fusion(u32[4]{0} %p)", 150, 150),
            Ev(KERNEL_TEXT, 500, 100),
            Ev("%fusion.1 = u32[8]{0} fusion(u32[8]{0} %q)", 1050, 150)])])
    tpu1 = Plane("/device:TPU:1", [
        Line(trace.OPS_LINE, [Ev("%fusion.9 = u32[2]{0} fusion()", 100,
                                 1000)])])
    idle = Plane("/device:TPU:2", [Line(trace.OPS_LINE, [])])
    return Profile([host, tpu0, tpu1, idle])


def test_window_busy_and_idle():
    w = trace.window(profile())
    assert w.seconds == pytest.approx(1000e-9)
    assert [d.name for d in w.devices] == ["/device:TPU:0", "/device:TPU:1"]
    s, e = w.devices[0].busy()
    assert list(zip(s, e)) == [(100, 300), (500, 600), (1050, 1100)]
    # mean over the two devices that ran, of 350 ns and 1000 ns
    assert w.busy_s() == pytest.approx(675e-9)
    assert w.idle_share() == pytest.approx(1 - 0.675)


def test_idle_gaps_go_to_what_the_host_did():
    w = trace.window(profile())
    gs, ge = w.gaps(w.devices[0])
    assert list(zip(gs, ge)) == [(300, 500), (600, 1050)]
    gaps = dict(trace.idle_gaps(w))
    assert gaps["numpy glue"] == pytest.approx(450e-9)
    assert gaps[trace.UNTRACED] == pytest.approx(200e-9)


def test_idle_gaps_beyond_the_labelled_ones_are_summed(monkeypatch):
    monkeypatch.setattr(trace, "GAPS_LABELLED", 1)
    gaps = dict(trace.idle_gaps(trace.window(profile())))
    assert gaps["numpy glue"] == pytest.approx(450e-9)
    assert gaps[trace.SHORT_GAPS] == pytest.approx(200e-9)


def test_top_ops_sum_by_program_and_op():
    top = dict(trace.top_ops(trace.window(profile())))
    assert top["jit_a/fusion.1"] == pytest.approx(200e-9)
    assert top["jit_b/fusion.1"] == pytest.approx(150e-9)
    assert top["jit__permute_bucket/custom-call.3"] == pytest.approx(100e-9)
    assert top["fusion.9"] == pytest.approx(1000e-9)


def test_a_trace_needs_one_window_span():
    p = profile()
    p.planes[0].lines[0].events.append(Ev(trace.TRACED_SPAN, 2000, 10))
    with pytest.raises(ValueError):
        trace.window(p)


def test_result_bytes_from_the_hlo_text():
    op = trace.Op("/device:TPU:0", KERNEL_TEXT, "m", 0, 1)
    assert op.name == "custom-call.3"
    assert trace.result_bytes(op) == 16 * 4096 * 4
    tup = trace.Op("d", "%x = (u32[8,128]{1,0}, u32[2]{0}) custom-call(...)",
                   "m", 0, 1)
    assert trace.result_bytes(tup) == (8 * 128 + 2) * 4
    assert trace.result_bytes(trace.Op("d", "x", "m", 0, 1)) is None


def test_kernel_least_bytes_and_share():
    # a (16, 4096) uint32 permutation reads and writes 512 KiB
    assert kernels.min_bytes(16 * 4096 * 4) == 2 * 16 * 4096 * 4
    w = trace.window(profile())
    assert [o.module for o in w.ops] == ["jit__permute_bucket"]
    share = kernels.hbm_share(w, "TPU v5 lite", "poseidon")
    least = 2 * 16 * 4096 * 4 / 819e9
    assert share == pytest.approx(100 * least / 100e-9)
    assert kernels.hbm_share(w, "TPU v5 lite", "ntt") is None
    assert kernels.hbm_share(None, "TPU v5 lite", "ntt") is None
    with pytest.raises(KeyError):
        kernels.hbm_share(w, "no such chip", "poseidon")


def fake_run():
    cell = spec.load_cell("snb_60k.is_1client")
    served = []
    for i, (sub, done) in enumerate([(0.0, 2.0), (1.0, 4.0), (2.5, 3.0),
                                     (3.0, None)]):
        s = driver.Served(0, "IS5", {}, sub, done if done else 5.0,
                          bundle=object() if done else None)
        served.append(s)
    stats = dict(counters=dict(lanes=6, pad_lanes=2),
                 queue_wait_us=dict(count=3, p50=25000.0),
                 phase_us=dict(ood_openings=dict(count=2, mean=1.5e6),
                               fri=dict(count=0, mean=0.0)))
    run = driver.Run(cell, "TPU v5 lite", 42.0, served, 0.0, 4.0, stats, {})
    run.verify_s = [3.0, 1.0, 2.0]
    run.wire_bytes = [100, 200, 600]
    return run


def read(run, name):
    return spec.reader(run.cell, name)(run)


def test_end_to_end_arithmetic():
    run = fake_run()
    assert run.failed == 1
    assert read(run, "proved_qps") == pytest.approx(3 / 4.0)
    assert read(run, "prove_p50_s") == pytest.approx(2.0)   # of 2, 3, 0.5
    assert read(run, "verify_p50_s") == pytest.approx(2.0)
    assert read(run, "bundle_bytes") == pytest.approx(300.0)
    assert read(run, "setup_s") == 42.0


def test_per_layer_arithmetic():
    run = fake_run()
    assert read(run, "lane_occupancy") == pytest.approx(75.0)
    assert read(run, "queue_wait_s") == pytest.approx(0.025)
    assert read(run, "ood_openings_s") == pytest.approx(1.5)
    assert read(run, "fri_s") is None          # nothing to read
    for name in ("device_idle_share", "ntt_roofline", "poseidon_roofline"):
        assert read(run, name) is None         # no trace in this run


def test_median_and_lane_counts():
    assert driver.median([3.0, 1.0, 2.0, 10.0]) == 2.5
    assert driver.median([]) is None
    assert driver.lane_counts(8, True) == [1, 2, 4, 8]
    assert driver.lane_counts(5, True) == [1, 2, 4, 8]
    assert driver.lane_counts(1, True) == [1]
    assert driver.lane_counts(3, False) == [1, 2, 3]
