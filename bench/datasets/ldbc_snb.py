"""Seeded LDBC-SNB-shaped social network: persons, comments, ``knows``,
``hasCreator`` and ``replyOf``.

A copy of the program's generator (``repro.graphdb.ldbc.generate``), kept
here so that a change to the program's generator cannot move the data the
benchmark serves.  ``generate`` returns plain numpy arrays; ``to_graphdb``
hands them to the system under test in its own storage types.

* ``knows``: ``n_knows`` undirected edges stored once, drawn in 16 blocks
  with probability proportional to each person's degree so far
  (preferential attachment), self-loops redrawn, multiplicity kept, with a
  ``creationDate``.
* ``hasCreator``: one creator per comment, drawn by the final ``knows``
  degree weights; comment ids start at ``COMMENT_BASE``.
* ``replyOf``: each comment after the first replies to an earlier one with
  probability 0.6.
"""
from __future__ import annotations

import numpy as np

PERSON_BASE = 1            # person ids: 1..n_persons
COMMENT_BASE = 1 << 20     # comment ids start here (disjoint from persons)


def generate(n_knows: int, n_persons: int, n_comments: int,
             seed: int) -> dict:
    rng = np.random.default_rng(seed)
    person_ids = np.arange(PERSON_BASE, PERSON_BASE + n_persons,
                           dtype=np.int64)

    deg_w = np.ones(n_persons)
    srcs = np.empty(n_knows, np.int64)
    dsts = np.empty(n_knows, np.int64)
    block = max(1, n_knows // 16)
    filled = 0
    while filled < n_knows:
        k = min(block, n_knows - filled)
        p = deg_w / deg_w.sum()
        a = rng.choice(n_persons, size=k, p=p)
        b = rng.choice(n_persons, size=k, p=p)
        mask = a != b
        a, b = a[mask], b[mask]
        srcs[filled:filled + len(a)] = person_ids[a]
        dsts[filled:filled + len(a)] = person_ids[b]
        np.add.at(deg_w, a, 1.0)
        np.add.at(deg_w, b, 1.0)
        filled += len(a)
    knows_date = rng.integers(20200101, 20250101,
                              size=n_knows).astype(np.int64)

    comment_ids = np.arange(COMMENT_BASE, COMMENT_BASE + n_comments,
                            dtype=np.int64)
    creators = person_ids[rng.choice(n_persons, size=n_comments,
                                     p=deg_w / deg_w.sum())]
    cdates = rng.integers(20200101, 20250101,
                          size=n_comments).astype(np.int64)
    reply_src, reply_dst = [], []
    for i in range(1, n_comments):
        if rng.random() < 0.6:
            reply_src.append(int(comment_ids[i]))
            reply_dst.append(int(comment_ids[rng.integers(0, i)]))

    person = {
        "id": person_ids,
        "firstName": rng.integers(1, 2000, size=n_persons).astype(np.int64),
        "lastName": rng.integers(1, 2000, size=n_persons).astype(np.int64),
        "birthday": rng.integers(19500101, 20051231,
                                 size=n_persons).astype(np.int64),
    }
    comment = {
        "id": comment_ids,
        "content": rng.integers(1, 1 << 27, size=n_comments).astype(np.int64),
        "creationDate": cdates,
        "length": rng.integers(1, 2000, size=n_comments).astype(np.int64),
    }
    return {
        "person": person,
        "comment": comment,
        "knows": {"src": srcs, "dst": dsts, "creationDate": knows_date},
        "hasCreator": {"src": comment_ids.copy(), "dst": creators,
                       "creationDate": cdates},
        "replyOf": {"src": np.asarray(reply_src, np.int64),
                    "dst": np.asarray(reply_dst, np.int64)},
    }


def make(config: dict, seed: int) -> dict:
    """The configuration's tables, from its sizes and the run's seed."""
    return generate(config["n_knows"], config["n_persons"],
                    config["n_comments"], seed)


def to_graphdb(tables: dict):
    """The same tables in the program's storage types."""
    from repro.graphdb.storage import EdgeTable, GraphDB
    k, h, r = tables["knows"], tables["hasCreator"], tables["replyOf"]
    person, comment = tables["person"], tables["comment"]
    return GraphDB(
        n_nodes=len(person["id"]),
        node_ids=person["id"],
        tables={
            "person_knows_person": EdgeTable(
                k["src"], k["dst"], {"creationDate": k["creationDate"]}),
            "comment_hasCreator_person": EdgeTable(
                h["src"], h["dst"], {"creationDate": h["creationDate"]}),
            "comment_replyOf_comment": EdgeTable(r["src"], r["dst"]),
        },
        node_props={
            "person": {c: person[c] for c in
                       ("firstName", "lastName", "birthday")},
            "comment": {c: comment[c] for c in
                        ("content", "creationDate", "length")},
        },
    )
