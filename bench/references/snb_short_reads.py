"""Plain numpy answers to the LDBC SNB short reads IS3, IS4 and IS5.

Reads only the generated tables (``bench/datasets``); imports nothing of
the program.  ``answer`` gives the expected answer in a canonical form and
``canonical`` puts a proved bundle's claimed ``result`` into that form, so
the two compare with ``==``.

* IS3, friends of a person with the date of each friendship, newest first:
  ``knows`` is stored once per edge, so a friend is a ``dst`` where the
  person is the ``src`` or a ``src`` where the person is the ``dst``;
  repeated edges are repeated friends.  Compared as the sorted list of
  (friend, date) pairs, plus the claimed order: dates never increase.
* IS4, content and creation date of a message.
* IS5, creator of a message.
"""
from __future__ import annotations

import numpy as np

QUERIES = ("IS3", "IS4", "IS5")


class UnknownQuery(KeyError):
    pass


def answer(tables: dict, qname: str, params: dict) -> dict:
    if qname == "IS3":
        k, p = tables["knows"], params["person"]
        fwd, bwd = k["src"] == p, k["dst"] == p
        friends = np.concatenate([k["dst"][fwd], k["src"][bwd]])
        dates = np.concatenate([k["creationDate"][fwd],
                                k["creationDate"][bwd]])
        return dict(pairs=sorted(zip(friends.tolist(), dates.tolist())),
                    newest_first=True)
    if qname in ("IS4", "IS5"):
        c = tables["comment"]
        row = np.flatnonzero(c["id"] == params["message"])
        if qname == "IS4":
            return dict(content=c["content"][row].tolist(),
                        date=c["creationDate"][row].tolist())
        h = tables["hasCreator"]
        return dict(creator=sorted(h["dst"][h["src"] ==
                                            params["message"]].tolist()))
    raise UnknownQuery(qname)


def canonical(qname: str, result: dict) -> dict:
    if qname == "IS3":
        friends = np.asarray(result["friends"]).tolist()
        dates = np.asarray(result["dates"])
        return dict(pairs=sorted(zip(friends, dates.tolist())),
                    newest_first=bool((np.diff(dates) <= 0).all()))
    if qname == "IS4":
        return dict(content=np.asarray(result["content"]).tolist(),
                    date=np.asarray(result["date"]).tolist())
    if qname == "IS5":
        return dict(creator=sorted(np.asarray(result["creator"]).tolist()))
    raise UnknownQuery(qname)
