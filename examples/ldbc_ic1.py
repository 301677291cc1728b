"""IC1-style chained query proof: 3-hop friend expansion + name filter +
order-by — the expansion-centric decomposition end to end (paper §III-D),
driven through the declarative plan IR and the session API.

    PYTHONPATH=src python examples/ldbc_ic1.py
"""
import sys
sys.path.insert(0, "src")

from repro.core import ir
from repro.core import prover as pv
from repro.core.session import TrustAnchor, ZKGraphSession
from repro.graphdb import ldbc

CFG = pv.ProverConfig(blowup=4, n_queries=16, fri_final_size=16)


def main(n_knows=150, n_persons=32, cfg=CFG, seed=13):
    db = ldbc.generate(n_knows=n_knows, n_persons=n_persons, seed=seed)
    owner = ZKGraphSession(db, cfg)
    name = int(db.node_props["person"]["firstName"][0])
    params = dict(person=2, firstName=name)

    plan = ir.build_plan("IC1")
    print(f"IC1 plan: {len(plan.nodes)} nodes:")
    for i, node in enumerate(plan.nodes):
        print(f"  [{i}] {type(node).__name__}")

    bundle = owner.prove("IC1", params)
    print(f"executed -> {len(bundle.steps)} chained operator proofs:")
    for rec in bundle.steps:
        shape = {k: v for k, v in rec.shape.items() if k != "n_rows"}
        print(f"  {rec.kind:12s} rows={rec.shape['n_rows']:5d} "
              f"data={rec.data_desc:20s} {shape}")
    print(f"proved in {bundle.prove_seconds():.1f}s, chain proof = "
          f"{bundle.size_fields()} field elements "
          f"({bundle.size_fields() * 4 / 1024:.1f} KB)")

    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=owner.commitments), cfg=cfg)
    ok = verifier.verify(bundle)
    print(f"chain verifies: {ok}")
    assert ok
    print(f"result (persons named {name}, 3 hops of person 2): "
          f"{sorted(set(bundle.result['persons'].tolist()))}")

    # the session keygen cache: proving the same query again reuses every key
    before = dict(owner.cache.stats())
    owner.prove("IC1", params)
    after = owner.cache.stats()
    print(f"keygen cache: {before} -> {after} "
          f"(second prove added {after['misses'] - before['misses']} keygens)")


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    main()
