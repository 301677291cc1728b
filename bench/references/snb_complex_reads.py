"""Plain numpy answers to the LDBC SNB complex reads IC2, IC8 and IC9.

Reads only the generated tables (``bench/datasets``); imports nothing of
the program.  ``answer`` gives the expected answer in a canonical form and
``canonical`` puts a proved bundle's claimed ``result`` into that form, so
the two compare with ``==``.

Each of the three reads is the newest ``k`` (default 20) messages of a
candidate set, newest first:

* IC2, messages by the person's friends;
* IC9, messages by friends and friends of friends, the person left out;
* IC8, replies to the person's messages.

``knows`` is stored once per edge, so a friend is a ``dst`` where the
person is the ``src`` or a ``src`` where the person is the ``dst``.
Messages are comments (the schema has no posts); a comment's creator and
parent come from ``hasCreator`` and ``replyOf``, its date from
``comment.creationDate``.

Dates tie, and which message wins a tie at the ``k``-th date is the
prover's choice, not a fact of the data.  So the comparison does not
depend on tie order: the claimed dates, sorted, must equal the reference's
top-``k`` dates; every claimed (message, date) pair must be a candidate
with its true date, no message twice (``Drawn``); and the claimed dates
must never increase.  A read with no candidates has the empty answer; the
program's OrderBy pads an empty input with one (0, 0) row, and id 0 is no
message, so ``canonical`` reads that lone row as empty.
"""
from __future__ import annotations

from itertools import zip_longest

import numpy as np

QUERIES = ("IC2", "IC8", "IC9")
RESULT_KEY = {"IC2": "messages", "IC8": "replies", "IC9": "messages"}
DEFAULT_K = 20


class UnknownQuery(KeyError):
    pass


class Drawn:
    """Equal to any list of (message, date) pairs drawn from ``pool`` with
    no message twice: the claimed pairs of a top-``k`` whose ties the
    prover may break either way."""

    __hash__ = None

    def __init__(self, pool):
        self.pool = frozenset(pool)

    def __eq__(self, other):
        if not isinstance(other, list):
            return NotImplemented
        msgs = [m for m, _ in other]
        return len(set(msgs)) == len(msgs) and all(
            tuple(p) in self.pool for p in other)

    def __repr__(self):
        return f"<distinct pairs of {len(self.pool)} candidates>"


def _friends(knows: dict, persons) -> np.ndarray:
    """Every person with a ``knows`` edge to one of ``persons``."""
    fwd = np.isin(knows["src"], persons)
    bwd = np.isin(knows["dst"], persons)
    return np.unique(np.concatenate([knows["dst"][fwd], knows["src"][bwd]]))


def candidates(tables: dict, qname: str, person: int) -> np.ndarray:
    """The message ids the read chooses its top ``k`` from."""
    if qname in ("IC2", "IC9"):
        friends = _friends(tables["knows"], [person])
        if qname == "IC9":
            friends = np.union1d(friends, _friends(tables["knows"], friends))
        friends = friends[friends != person]
        h = tables["hasCreator"]
        return h["src"][np.isin(h["dst"], friends)]
    if qname == "IC8":
        h, r = tables["hasCreator"], tables["replyOf"]
        mine = h["src"][h["dst"] == person]
        return r["src"][np.isin(r["dst"], mine)]
    raise UnknownQuery(qname)


def answer(tables: dict, qname: str, params: dict) -> dict:
    ids = candidates(tables, qname, int(params["person"]))
    c = tables["comment"]
    dates = c["creationDate"][np.searchsorted(c["id"], ids)]
    k = min(int(params.get("k", DEFAULT_K)), len(ids))
    top = sorted(dates.tolist(), reverse=True)[:k]
    return dict(dates=top, pairs=Drawn(zip(ids.tolist(), dates.tolist())),
                newest_first=True)


def canonical(qname: str, result: dict) -> dict:
    if qname not in RESULT_KEY:
        raise UnknownQuery(qname)
    msgs = np.asarray(result[RESULT_KEY[qname]], np.int64).tolist()
    dates = np.asarray(result["dates"], np.int64).tolist()
    pairs = list(zip_longest(msgs, dates))   # (id, None): never drawn
    if pairs == [(0, 0)]:
        pairs, dates = [], []
    return dict(dates=sorted(dates, reverse=True), pairs=pairs,
                newest_first=all(a >= b for a, b in zip(dates, dates[1:])))
