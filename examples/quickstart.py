"""Quickstart: prove one LDBC query over a private graph via the session API.

    PYTHONPATH=src python examples/quickstart.py

Walks the paper's full workflow (§III-C) through the three layers
(ir -> operator registry -> session, see docs/architecture.md): the owner
commits the dataset, proves a query as a chained bundle of operator proofs,
the verifier — holding only the published commitments — checks it; then a
tampered result is shown to be rejected.
"""
import sys
sys.path.insert(0, "src")

import numpy as np

from repro.core import prover as pv
from repro.core.operators import registry
from repro.core.session import ProofBundle, TrustAnchor, ZKGraphSession
from repro.graphdb import engine, ldbc

CFG = pv.ProverConfig(blowup=4, n_queries=16, fri_final_size=16)


def main(n_knows=200, n_persons=32, cfg=CFG, seed=7):
    # ---- data owner: private social graph + published commitments ---------
    db = ldbc.generate(n_knows=n_knows, n_persons=n_persons, seed=seed)
    t = db.tables["person_knows_person"]
    print(f"private graph: {db.n_nodes} persons, {len(t)} friendships")

    owner = ZKGraphSession(db, cfg)
    commitments = owner.commitments
    print(f"published {len(commitments)} dataset commitments")

    # ---- verifier asks: who are the friends of this person? ---------------
    src_id = int(t.src[0])   # guaranteed to have edges
    bundle = owner.prove("IS3", dict(person=src_id))
    friends = bundle.result["friends"]
    print(f"claimed friends of {src_id} (newest first): {friends.tolist()}")
    print(f"chain: {len(bundle.steps)} operator proofs, "
          f"{bundle.size_fields()} field elements "
          f"({bundle.size_fields() * 4 / 1024:.1f} KB), "
          f"prover {bundle.prove_seconds():.1f}s")

    # ---- verifier: only the commitments + the (serialized) bundle ---------
    # bytes cross the trust boundary through the canonical wire codec
    # (repro.core.wire): versioned, deterministic, bounded — never pickle
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=commitments), cfg=cfg)
    raw = bundle.to_bytes()
    received = ProofBundle.from_bytes(raw)
    assert received.to_bytes() == raw      # one canonical encoding
    ok = verifier.verify(received)
    print(f"verifier accepts: {ok}")
    assert ok
    # hostile bytes fail closed: no crash, no code execution, just False
    assert not verifier.verify_bytes(raw[: len(raw) // 2])
    assert not verifier.verify_bytes(b"\x80\x04pickle?")
    print("malformed / legacy-pickle bytes rejected: True")
    want, *_ = engine.expand_undirected(t, src_id)
    assert sorted(friends.tolist()) == sorted(want.tolist())

    # ---- a cheating prover: claim one extra 'friend' ----------------------
    bad = ProofBundle.from_bytes(bundle.to_bytes())
    rec = bad.steps[0]
    op = registry.build_operator(rec.kind, rec.shape)
    sel = np.nonzero(rec.instance[op.handles["out_sel"].index] == 1)[0]
    row = int(sel[0]) if len(sel) else 0
    rec.instance[op.handles["C_t"].index, row] = 999
    rejected = not verifier.verify(bad)
    print(f"tampered chain rejected: {rejected}")
    assert rejected
    print("quickstart OK")


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    main()
