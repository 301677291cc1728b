"""The plain reference and the traffic generator, at 2k rows on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, traffic

ROWS = 2048
DATASET = spec.load_module(tinycell.BENCH / "datasets" / "ldbc_snb.py",
                           "bench_dataset_test")
REF = spec.load_module(tinycell.BENCH / "references" /
                       "snb_short_reads.py", "bench_reference_test")


@pytest.fixture(scope="module")
def data():
    tables = DATASET.generate(ROWS, ROWS // 16, ROWS, seed=2**31 + 5)
    return tables, DATASET.to_graphdb(tables)


def engine_answer(db, qname, params):
    """The same answers from the program's plain engine."""
    from repro.graphdb import engine
    knows = db.tables["person_knows_person"]
    if qname == "IS3":
        friends, fwd, bwd = engine.expand_undirected(knows, params["person"])
        date = knows.props["creationDate"]
        dates = np.concatenate([date[fwd], date[bwd]])
        return dict(pairs=sorted(zip(friends.tolist(), dates.tolist())),
                    newest_first=True)
    mid = params["message"] - DATASET.COMMENT_BASE
    if qname == "IS4":
        cp = db.node_props["comment"]
        return dict(content=[int(cp["content"][mid])],
                    date=[int(cp["creationDate"][mid])])
    creator, _ = engine.expand(db.tables["comment_hasCreator_person"],
                               params["message"])
    return dict(creator=sorted(creator.tolist()))


def drawn(tables, seed=7):
    t = dict(clients=1, requests_per_client=24,
             mix=[["IS3", 1], ["IS4", 1], ["IS5", 1]],
             params=dict(IS3=dict(person="person_by_knows_degree"),
                         IS4=dict(message="comment_uniform"),
                         IS5=dict(message="comment_uniform")))
    return traffic.requests(t, tables, seed)[0]


def test_reference_matches_engine(data):
    tables, db = data
    for q, params in drawn(tables):
        assert REF.answer(tables, q, params) == engine_answer(db, q, params)


def test_reference_matches_executed_plans(data):
    """The executor's claimed results, put in canonical form, equal the
    reference: the two sides of the harness's comparison agree."""
    from repro.core import ir
    tables, db = data
    for q, params in drawn(tables)[:9]:
        run = ir.execute(db, ir.build_plan(q), params)
        assert REF.canonical(q, run.result) == REF.answer(tables, q, params)


def test_reference_sees_an_altered_answer(data):
    tables, _ = data
    q, params = next((q, p) for q, p in drawn(tables) if q == "IS5")
    want = REF.answer(tables, q, params)
    bad = dict(creator=[want["creator"][0] + 1])
    assert REF.canonical(q, bad) != want
    q, params = next((q, p) for q, p in drawn(tables) if q == "IS3")
    want = REF.answer(tables, q, params)
    friends = [f for f, _ in want["pairs"]]
    dates = sorted((d for _, d in want["pairs"]), reverse=True)
    assert len(friends) > 1
    swapped = dict(friends=friends, dates=dates[::-1])
    assert REF.canonical(q, swapped) != want


def test_requests_repeat_for_a_seed_and_keep_the_mix(data):
    tables, _ = data
    a, b, c = drawn(tables, 11), drawn(tables, 11), drawn(tables, 12)
    assert a == b
    assert [q for q, _ in a] == [q for q, _ in c]
    assert [q for q, _ in a][:3] == ["IS3", "IS4", "IS5"]
    assert a != c


def test_clients_start_at_different_places_in_the_rotation(data):
    tables, _ = data
    t = dict(clients=3, requests_per_client=2, mix=[["IS4", 1], ["IS5", 2]],
             params=dict(IS4=dict(message="comment_uniform"),
                         IS5=dict(message="comment_uniform")))
    rows = traffic.requests(t, tables, 3)
    assert [[q for q, _ in r] for r in rows] == [
        ["IS4", "IS5"], ["IS5", "IS5"], ["IS5", "IS4"]]


def test_persons_follow_the_knows_degree(data):
    tables, _ = data
    k = tables["knows"]
    deg = np.bincount(np.concatenate([k["src"], k["dst"]]),
                      minlength=len(tables["person"]["id"]) + 1)
    rng = np.random.default_rng(0)
    picks = traffic.person_by_knows_degree(tables, rng, 4000)
    # a draw weighted by degree has mean degree sum(d^2) / sum(d)
    d = deg[tables["person"]["id"]].astype(float)
    assert deg[picks].mean() == pytest.approx((d * d).sum() / d.sum(),
                                              rel=0.05)
    assert all(deg[p] > 0 for p in picks)


def test_large_seeds_draw(data):
    tables, _ = data
    assert drawn(tables, 2**40 + 3) == drawn(tables, 2**40 + 3)
