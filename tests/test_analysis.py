"""Circuit soundness analyzer (repro.analysis): structural checks on
hand-built circuits, the witness perturbation probe, the registry vetting
contract, corpus detection, and the CLI surface.

The expensive all-registry sweep and full seeded-bug corpus are marked
``slow`` (nightly full-suite); the blocking CI `analysis` job runs both on
every PR via ``python -m repro.analysis --all-adapters --purity --selftest``.
"""
import ast
import json
import re
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (analyze_case, apply_baseline, load_baseline,
                            registry_cases, write_baseline)
from repro.analysis.findings import ALL_CHECKS, ERROR, Finding, WARNING
from repro.analysis.structural import analyze_circuit
from repro.analysis.witness import witness_analysis
from repro.core.plonkish import ADVICE, Circuit, Col, Const

ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# structural checks on hand-built circuits (fast, no witness)
# ---------------------------------------------------------------------------
def _checks(findings, check):
    return [f for f in findings if f.check == check]


def test_degree_overflow_detected():
    c = Circuit(8, "t")
    a = c.add_advice("a")
    c.gates.append(("deg5", a * a * a * a * a))     # bypass add_gate's assert
    hits = _checks(analyze_circuit(c, "t", blowup=4), "gate-degree-overflow")
    assert len(hits) == 1 and hits[0].severity == ERROR
    assert "deg5" in hits[0].key


def test_rotation_out_of_range_detected():
    c = Circuit(8, "t")
    a = c.add_advice("a")
    c.gates.append(("wide", Col(ADVICE, a.index, 8) - a))
    assert _checks(analyze_circuit(c, "t"), "rotation-out-of-range")


def test_unguarded_wrap_flagged_and_guard_accepted():
    n = 8
    bad = Circuit(n, "bad")
    a = bad.add_advice("a")
    bad.add_gate("step", Col(ADVICE, a.index, 1) - a)
    assert _checks(analyze_circuit(bad, "bad"), "unguarded-wrap")

    good = Circuit(n, "good")
    a = good.add_advice("a")
    sel = good.add_fixed("sel", [1] * (n - 1) + [0])   # vanishes on wrap row
    good.add_gate("step", sel * (Col(ADVICE, a.index, 1) - a))
    assert not _checks(analyze_circuit(good, "good"), "unguarded-wrap")


def test_vacuous_gate_detected():
    c = Circuit(8, "t")
    a = c.add_advice("a")
    sel = c.add_fixed("sel", [0] * 8)                  # all-zero selector
    c.add_gate("dead", sel * a * (a - Const(1)))
    hits = _checks(analyze_circuit(c, "t"), "vacuous-gate")
    assert hits and hits[0].severity == ERROR


def test_orphan_and_unused_columns_detected():
    c = Circuit(8, "t")
    a = c.add_advice("a")
    c.add_gate("bool", a * (a - Const(1)))
    c.add_advice("ghost")                              # never referenced
    c.add_instance("pub")                              # public, unchecked!
    c.add_fixed("dead_sel", [1] * 8)                   # never referenced
    fs = analyze_circuit(c, "t")
    assert any(f.key == "ghost" for f in _checks(fs, "orphan-advice-column"))
    assert any(f.key == "pub" for f in _checks(fs, "orphan-instance-column"))
    assert any(f.key == "dead_sel" and f.severity == WARNING
               for f in _checks(fs, "unused-fixed-column"))


def test_floating_advice_component_detected():
    c = Circuit(8, "t")
    a, b = c.add_advice("a"), c.add_advice("b")
    c.add_gate("tie", a - b)          # a,b only ever constrained to each other
    assert _checks(analyze_circuit(c, "t"), "floating-advice-component")


def test_honest_minimal_circuit_is_clean():
    c = Circuit(8, "t")
    a = c.add_advice("a")
    sel = c.add_fixed("sel", [1] * 8)
    c.add_gate("bool", sel * a * (a - Const(1)))
    assert [f for f in analyze_circuit(c, "t") if f.fails_gate()] == []


# ---------------------------------------------------------------------------
# witness perturbation probe (fast, hand-built)
# ---------------------------------------------------------------------------
def _wit(c, n_adv, n_inst, n):
    return (np.zeros((n_adv, n), np.uint32),
            np.zeros((n_inst, n), np.uint32),
            np.zeros((0, n), np.uint32))


def test_probe_bound_column_has_no_free_cells():
    n = 8
    c = Circuit(n, "t")
    a = c.add_advice("a")
    c.add_gate("bool", a * (a - Const(1)))
    adv, inst, data = _wit(c, 1, 0, n)
    fs, cov = witness_analysis(c, adv, inst, data, "t")
    assert [f for f in fs if f.fails_gate()] == []
    assert cov[0]["column"] == "a" and cov[0]["free_cells"] == 0


def test_probe_flags_referenced_but_unconstrained_column():
    n = 8
    c = Circuit(n, "t")
    a = c.add_advice("a")
    b = c.add_advice("b")
    c.add_gate("bool", a * (a - Const(1)))
    zero = c.add_fixed("zsel", [0] * n)
    c.add_gate("dead", zero * b)      # b referenced, never actually bound
    adv, inst, data = _wit(c, 2, 0, n)
    fs, _ = witness_analysis(c, adv, inst, data, "t")
    assert any(f.check == "unconstrained-advice-column" and f.key == "b"
               for f in fs)


def test_probe_reports_honest_witness_violation_first():
    n = 8
    c = Circuit(n, "t")
    a = c.add_advice("a")
    c.add_gate("bool", a * (a - Const(1)))
    adv = np.full((1, n), 2, np.uint32)               # 2*(2-1) != 0
    fs, _ = witness_analysis(c, adv, *_wit(c, 0, 0, n)[1:], "t")
    hits = [f for f in fs if f.check == "witness-violation"]
    assert hits and hits[0].severity == ERROR and "bool" in hits[0].key


def test_probe_classifies_forgeable_public_output():
    n = 8
    c = Circuit(n, "t")
    a = c.add_advice("a")
    c.add_gate("bool", a * (a - Const(1)))
    c.add_instance("out")                              # public, unbound
    adv, inst, data = _wit(c, 1, 1, n)

    def extract(instance):
        return dict(out=np.asarray(instance[0], np.int64))

    fs, _ = witness_analysis(c, adv, inst, data, "t", extract=extract)
    hits = [f for f in fs if f.check == "forgeable-output"]
    assert hits and hits[0].severity == ERROR and hits[0].key == "out"


# ---------------------------------------------------------------------------
# registry vetting contract + one end-to-end case
# ---------------------------------------------------------------------------
def test_every_adapter_declares_two_representative_shapes(db):
    cases = registry_cases(db)
    per = {}
    for case in cases:
        per.setdefault(case.adapter, []).append(case.label)
    from repro.core.operators import registry
    assert set(per) == set(registry.adapters()), \
        "some registered adapter produced no analysis cases"
    for name, labels in per.items():
        assert len(labels) >= 2, \
            f"adapter {name!r} declares fewer than 2 analysis shapes"
    # labels are unique per adapter (they key findings and reports)
    for name, labels in per.items():
        assert len(set(labels)) == len(labels)


def test_orderby_case_end_to_end_clean(db):
    case = next(c for c in registry_cases(db)
                if (c.adapter, c.label) == ("orderby", "top3_desc"))
    findings, stats = analyze_case(case)
    assert [f for f in findings if f.fails_gate()] == []
    assert stats["gates"], "gate_info() should describe the circuit"
    # selector-bound columns are fully covered on the honest witness
    cov = {c["column"]: c["free_cells"] for c in stats["coverage"]}
    assert cov["IS_k"] == 0 and cov["out_sel"] == 0


@pytest.mark.slow
def test_full_registry_is_clean(db):
    from repro.analysis import analyze_all
    report = analyze_all(db)
    assert report.gating() == [], \
        f"registry circuits have findings: " \
        f"{[(f.check, f.where, f.key) for f in report.gating()]}"


@pytest.mark.slow
def test_seeded_bug_corpus_fully_detected(db):
    from repro.analysis.corpus import run_selftest
    assert run_selftest(db=db, verbose=False)


def test_corpus_variant_detected_fast(db):
    """One corpus variant in tier-1 so detection regressions surface on
    every push, not only nightly: the zeroed selector must be caught."""
    from repro.analysis.corpus import v_dropped_selector
    name, case, expected = v_dropped_selector(db)
    findings, _ = analyze_case(case)
    got = {f.check for f in findings if f.fails_gate()}
    assert expected <= got, f"{name}: expected {expected}, got {got}"


# ---------------------------------------------------------------------------
# findings / baseline mechanics
# ---------------------------------------------------------------------------
def test_check_ids_stay_in_catalogue():
    """Every kebab-case string literal in the emitting modules is a
    registered check id — no module invents ids the docs don't list."""
    kebab = re.compile(r"^[a-z]+(-[a-z]+)+$")
    for mod in ("structural", "witness", "purity"):
        src = (ROOT / "src" / "repro" / "analysis" / f"{mod}.py").read_text()
        ids = {node.value for node in ast.walk(ast.parse(src))
               if isinstance(node, ast.Constant)
               and isinstance(node.value, str) and kebab.fullmatch(node.value)}
        unknown = ids - ALL_CHECKS
        assert not unknown, f"{mod}.py emits unregistered check ids {unknown}"


def test_baseline_roundtrip_and_staleness(tmp_path):
    f1 = Finding("vacuous-gate", ERROR, "x:y/z", "g1", "d")
    f2 = Finding("banned-import", ERROR, "core/a.py", "import time", "d", 3)
    path = tmp_path / "b.json"
    assert write_baseline([f1, f2], path) == 2
    base = load_baseline(path)
    kept, suppressed, stale = apply_baseline([f1], base)
    assert kept == [] and suppressed == [f1]
    assert stale == [f2.ident()], "unmatched entries must be reported stale"


def test_committed_baseline_is_minimal_and_current():
    """The committed baseline is empty: the prover's timings come from
    ``repro.obs`` spans, so no proof-path module imports ``time`` — nothing
    may creep in without showing up in this diff."""
    base = load_baseline(ROOT / "analysis_baseline.json")
    assert base == set()


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------
def test_cli_purity_json_and_gate(tmp_path):
    from repro.analysis.__main__ import main
    out = tmp_path / "report.json"
    rc = main(["--purity", "--json", str(out), "--fail-on-findings"])
    assert rc == 0, "purity lint over the real tree must pass the gate"
    doc = json.loads(out.read_text())
    assert doc["purity"]["files_scanned"] > 30
    assert doc["gating_after_baseline"] == 0
    assert doc["suppressed"] == 0 and doc["stale_baseline"] == []


def test_cli_write_baseline_then_clean(tmp_path):
    from repro.analysis.__main__ import main
    bl = tmp_path / "bl.json"
    assert main(["--purity", "--no-baseline", "--write-baseline",
                 "--baseline", str(bl)]) == 0
    assert load_baseline(bl) == load_baseline(ROOT / "analysis_baseline.json")
    assert main(["--purity", "--baseline", str(bl),
                 "--fail-on-findings"]) == 0
