"""The one traffic generator: a traffic file's parameters -> the run's
fixed request list, drawn from ``--seed``.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``clients``: closed-loop clients; each submits its next request only when
  its previous bundle has returned;
* ``mix``: ``[[query, weight], ...]`` with whole weights.  Each client walks
  the rotation these weights spell out (``[["IS3", 1], ["IS4", 2]]`` ->
  IS3, IS4, IS4, IS3, ...), client ``c`` starting ``c`` places in, so every
  seed serves the same queries in the same order and only the parameters
  differ;
* ``requests_per_client``: the length of each client's list.  A client
  that reaches its end starts it again, so a faster program never runs
  out of requests, and set-up warms only the shapes of this list;
* ``params``: ``{query: {parameter: draw}}``, each draw named from
  ``DRAWS``.
"""
from __future__ import annotations

import numpy as np

TRAFFIC_SALT = 0x7A6B   # keeps the request stream apart from the data's


def person_by_knows_degree(tables: dict, rng, n: int) -> list:
    """Persons with probability proportional to their ``knows`` degree
    (both directions counted), the generator's own skew, drawn as a
    systematic sample: the ``i``-th of ``n`` draws is the person at
    ``(i + 1/2) / n`` of the degree-weighted distribution, persons of equal
    degree in an order drawn from the seed.  So every seed asks for the
    same degrees in the same order, hence the same circuit sizes, and only
    the persons differ."""
    k, ids = tables["knows"], tables["person"]["id"]
    deg = np.zeros(len(ids))
    np.add.at(deg, np.searchsorted(ids, k["src"]), 1.0)
    np.add.at(deg, np.searchsorted(ids, k["dst"]), 1.0)
    order = np.lexsort((rng.permutation(len(ids)), deg))
    cdf = np.cumsum(deg[order]) / deg.sum()
    at = np.searchsorted(cdf, (np.arange(n) + 0.5) / n)
    return ids[order[at]].tolist()


def comment_uniform(tables: dict, rng, n: int) -> list:
    """Messages uniformly over all comments."""
    ids = tables["comment"]["id"]
    return ids[rng.integers(0, len(ids), size=n)].tolist()


DRAWS = {"person_by_knows_degree": person_by_knows_degree,
         "comment_uniform": comment_uniform}


def rotation(mix: list) -> list:
    return [q for q, w in mix for _ in range(int(w))]


def requests(traffic: dict, tables: dict, seed: int) -> list:
    """``[client] -> [(query, params), ...]``, the same for the same seed."""
    rng = np.random.default_rng([seed, TRAFFIC_SALT])
    clients, per = int(traffic["clients"]), int(traffic["requests_per_client"])
    rot = rotation(traffic["mix"])
    queries = [[rot[(c + i) % len(rot)] for i in range(per)]
               for c in range(clients)]
    drawn = {}
    for q, draws in sorted(traffic["params"].items()):
        n = sum(row.count(q) for row in queries)
        drawn[q] = {p: iter(DRAWS[d](tables, rng, n))
                    for p, d in sorted(draws.items())}
    return [[(q, {p: int(next(it)) for p, it in drawn[q].items()})
             for q in row] for row in queries]
