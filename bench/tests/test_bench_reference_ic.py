"""The plain reference for the complex reads IC2, IC8 and IC9 against the
program's executor, at 2k rows on the CPU, and the altered answers it has
to see."""
from __future__ import annotations

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec, traffic

ROWS = 2048
PERSONS = 8
DATASET = spec.load_module(tinycell.BENCH / "datasets" / "ldbc_snb.py",
                           "bench_dataset_test_ic")
REF = spec.load_module(tinycell.BENCH / "references" /
                       "snb_complex_reads.py", "bench_reference_test_ic")


@pytest.fixture(scope="module")
def data():
    tables = DATASET.generate(ROWS, ROWS // 16, ROWS, seed=2**31 + 5)
    return tables, DATASET.to_graphdb(tables)


def lonely(tables) -> int:
    """A person with no ``knows`` edge: the plans' start sets come out
    empty and the set expansions run on the empty-set sentinel."""
    k = tables["knows"]
    ids = tables["person"]["id"]
    alone = ids[~np.isin(ids, np.concatenate([k["src"], k["dst"]]))]
    assert len(alone), "the generator left every person with a friend"
    return int(alone[0])


def persons(tables, seed=13) -> list:
    return traffic.person_by_knows_degree(
        tables, np.random.default_rng(seed), PERSONS)


def executed(db, qname, person):
    from repro.core import ir
    return ir.execute(db, ir.build_plan(qname), dict(person=person)).result


@pytest.mark.parametrize("qname", REF.QUERIES)
def test_reference_matches_executed_plans(data, qname):
    """Over persons drawn as the traffic draws them, and one with no
    friends, the executor's claimed result equals the reference."""
    tables, db = data
    people = persons(tables) + [lonely(tables)]
    for p in people:
        got = REF.canonical(qname, executed(db, qname, p))
        want = REF.answer(tables, qname, dict(person=p))
        assert got == want, (qname, p)
        assert not got != want
    empty = REF.answer(tables, qname, dict(person=lonely(tables)))
    assert empty["dates"] == []


def ic9_answer(data):
    """A drawn person's IC9 result with 20 messages and its reference."""
    tables, db = data
    for p in persons(tables):
        res = executed(db, "IC9", p)
        if len(res["messages"]) == 20:
            return tables, p, res
    raise AssertionError("no drawn person has 20 candidate messages")


def altered(res, **cols):
    out = {k: np.asarray(v).copy() for k, v in res.items()}
    out.update(cols)
    return out


def test_reference_sees_altered_ic9_answers(data):
    tables, p, res = ic9_answer(data)
    want = REF.answer(tables, "IC9", dict(person=p))
    assert REF.canonical("IC9", res) == want
    msgs, dates = np.asarray(res["messages"]), np.asarray(res["dates"])

    # a message replaced by one of a non-friend's, its date kept
    cand = set(REF.candidates(tables, "IC9", p).tolist())
    stranger = next(int(c) for c in tables["comment"]["id"]
                    if int(c) not in cand)
    bad = altered(res, messages=np.r_[stranger, msgs[1:]])
    assert REF.canonical("IC9", bad) != want

    # a date altered, order kept
    bad = altered(res, dates=np.r_[dates[:-1], dates[-1] - 1])
    assert REF.canonical("IC9", bad) != want

    # two dates (with their messages) swapped out of order
    i = next(i for i in range(len(dates) - 1) if dates[i] > dates[i + 1])
    order = np.arange(len(dates))
    order[[i, i + 1]] = order[[i + 1, i]]
    bad = altered(res, messages=msgs[order], dates=dates[order])
    assert REF.canonical("IC9", bad) != want

    # one short
    bad = altered(res, messages=msgs[:-1], dates=dates[:-1])
    assert REF.canonical("IC9", bad) != want


def test_ties_at_the_kth_date_may_go_either_way(data):
    """A candidate planted at the 20th date may stand in for the message
    there, but no message may stand twice."""
    tables, p, res = ic9_answer(data)
    msgs, dates = np.asarray(res["messages"]), np.asarray(res["dates"])
    ids = REF.candidates(tables, "IC9", p)
    spare = int(next(m for m in ids if m not in set(msgs.tolist())))
    tied = {k: dict(v) for k, v in tables.items()}
    c = tied["comment"]
    c["creationDate"] = c["creationDate"].copy()
    c["creationDate"][np.searchsorted(c["id"], spare)] = dates[-1]
    want = REF.answer(tied, "IC9", dict(person=p))
    assert want["dates"] == dates.tolist()
    assert REF.canonical("IC9", res) == want
    other = altered(res, messages=np.r_[msgs[:-1], spare])
    assert REF.canonical("IC9", other) == want

    # the last message moved to the date before it: a tie in which the
    # message before may not be claimed twice
    c["creationDate"][np.searchsorted(c["id"], msgs[-1])] = dates[-2]
    want = REF.answer(tied, "IC9", dict(person=p))
    moved = altered(res, dates=np.r_[dates[:-1], dates[-2]])
    assert REF.canonical("IC9", moved) == want
    twice = altered(moved, messages=np.r_[msgs[:-1], msgs[-2]])
    assert REF.canonical("IC9", twice) != want


def test_an_unknown_query_is_refused(data):
    tables, _ = data
    with pytest.raises(KeyError):
        REF.answer(tables, "IC13", dict(person=1))
    with pytest.raises(KeyError):
        REF.canonical("IS5", dict(creator=[1]))
