"""The parsed query front door, end to end: a GQL-subset text is compiled
to the plan IR, proved, serialized, and verified by a session that holds
only the commitments — the verifier re-compiles the query text itself to
rebuild the expected plan, so prover and verifier agree on nothing beyond
the text and the published commitments.

    PYTHONPATH=src python examples/query_text.py
"""
import sys
sys.path.insert(0, "src")

import numpy as np

from repro.core import prover as pv
from repro.core.session import TrustAnchor, ZKGraphSession
from repro.graphdb import ldbc
from repro.query import QUERY_TEXTS, compile_query, render_plan

CFG = pv.ProverConfig(blowup=4, n_queries=16, fri_final_size=16)


def main(n_knows=150, n_persons=32, cfg=CFG, seed=13):
    db = ldbc.generate(n_knows=n_knows, n_persons=n_persons, seed=seed)
    owner = ZKGraphSession(db, cfg)
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=owner.commitments), cfg=cfg)
    names = db.node_props["person"]["firstName"]
    thr = int(np.median(names))

    # -- a query no hand-written plan covers: order predicate + aggregate --
    text = ("MATCH (p:Person {id: $person})-[:KNOWS]-(f:Person) "
            "WHERE f.firstName >= $thr RETURN f.id AS ids")
    plan = compile_query(text)
    print("compiled plan for the filter query:")
    print(render_plan(plan))
    bundle = owner.prove_plan(plan, dict(person=2, thr=thr))
    assert verifier.verify_bytes(bundle.to_bytes())
    print(f"friends of person 2 with firstName >= {thr}: "
          f"{sorted(np.asarray(bundle.result['ids']).tolist())}")

    agg_text = ("MATCH (p:Person {id: $person})-[:KNOWS]-(f:Person) "
                "RETURN min(f.firstName) AS youngest")
    bundle = owner.prove_plan(compile_query(agg_text), dict(person=2))
    assert verifier.verify_bytes(bundle.to_bytes())
    print(f"min firstName among person 2's friends: "
          f"{int(bundle.result['youngest'])} "
          f"(proved by the Aggregate circuit, not asserted by the owner)")

    # -- an LDBC text compiles to the hand-written plan's exact wire bytes --
    qname = "IS5"
    params = dict(message=int(db.tables["comment_hasCreator_person"].src[0]))
    hand = owner.prove(qname, dict(params))
    compiled = owner.prove_plan(compile_query(QUERY_TEXTS[qname],
                                              name=qname), dict(params))
    assert hand.to_bytes() == compiled.to_bytes()
    print(f"{qname}: compiled text proves to the hand plan's exact "
          f"{len(compiled.to_bytes())} wire bytes")


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    main()
