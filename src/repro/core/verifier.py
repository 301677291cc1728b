"""Verifier for the DEEP-ALI + FRI PLONKish proofs.

Replays the Fiat-Shamir transcript, checks the constraint identity at the OOD
point, recomputes the DEEP composition at each FRI query from the Merkle
openings, and checks FRI folds + degree bound.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .. import obs
from . import field as F
from . import fri as fri_mod
from . import merkle
from . import poly
from .plonkish import (ADVICE, DATA, FIXED, INSTANCE, Circuit, ExtOps,
                       eval_expr)
from .prover import Keys, Proof, combine_constraints, opening_schedule
from .transcript import Transcript

_U32 = jnp.uint32

BASIS = [np.eye(4, dtype=np.uint32)[c] for c in range(4)]


def verify(keys: Keys, instance_np: np.ndarray, proof: Proof,
           expected_data_root: np.ndarray = None,
           label: str = "zkgraph") -> bool:
    circuit, cfg = keys.circuit, keys.cfg
    n, B = circuit.n_rows, cfg.blowup
    nl = n * B

    # the paper's "declared dataset" check: the proof must be rooted in the
    # published dataset commitment
    if expected_data_root is not None and \
            not np.array_equal(proof.data_root, np.asarray(expected_data_root)):
        return False

    phase = obs.Phases("zkg.verify")
    with phase("openings"):
        inst = jnp.asarray(instance_np.astype(np.uint32)) if circuit.n_instance \
            else jnp.zeros((0, n), _U32)
        tx = Transcript(label)
        tx.absorb(circuit.digest_seed())
        if circuit.n_instance:
            tx.absorb_digest(np.asarray(merkle.commit(inst.T).root))
        tx.absorb_digest(proof.data_root)
        tx.absorb_digest(proof.advice_root)
        alpha = jnp.asarray(tx.challenge_ext())
        beta = jnp.asarray(tx.challenge_ext())
        tx.absorb_digest(proof.ext_root)
        alpha_c = jnp.asarray(tx.challenge_ext())
        tx.absorb_digest(proof.quotient_root)
        z = jnp.asarray(tx.challenge_ext())

        # -- recompute public-poly openings, assemble the full opening table -----
        sched = opening_schedule(circuit, B)
        inst_coeffs = poly.intt(inst) if circuit.n_instance else inst
        w_n = F.root_of_unity(n)
        openings = dict(proof.openings)
        rots = sorted({r for (k, _, r) in sched if k in (FIXED, INSTANCE)})
        for rot in rots:
            zr = F.emul_fp(z, _U32(pow(w_n, rot, F.P)))
            for kind, coeffs in ((FIXED, keys.fixed_coeffs), (INSTANCE, inst_coeffs)):
                idxs = [i for (k, i, rr) in sched if k == kind and rr == rot]
                if not idxs:
                    continue
                # repeat a row up to a multiple of 8: one gather/eval shape
                rows = idxs + idxs[:1] * ((-len(idxs)) % 8)
                vals = poly.eval_at_ext(coeffs[jnp.asarray(rows)], zr)
                for i, v in zip(idxs, np.asarray(vals)):
                    openings[(kind, i, rot)] = v
        # transcript absorbs ALL openings in schedule order (must match prover)
        for key in sched:
            if key not in openings:
                return False
            tx.absorb(openings[key])

    # -- constraint identity at z ---------------------------------------------
    with phase("constraints"):
        def base_getter(kind, idx, rot):
            return jnp.asarray(openings[(kind, idx, rot)])

        def ext_getter(col, rot):
            acc = jnp.zeros(4, _U32)
            for c in range(4):
                v = jnp.asarray(openings[("ext", col * 4 + c, rot)])
                acc = F.eadd(acc, F.emul(jnp.asarray(BASIS[c]), v))
            return acc

        like = jnp.zeros(4, _U32)  # scalar ext template

        class ScalarExtOps:
            """base columns evaluated at z are Fp4 scalars: use ext arithmetic."""
            add = staticmethod(F.eadd)
            sub = staticmethod(F.esub)
            mul = staticmethod(F.emul)

            @staticmethod
            def const(v, like_):
                out = jnp.zeros(4, _U32)
                return out.at[0].set(v % F.P)

        row0_val = (base_getter(FIXED, circuit.fixed_names.index("__row0"), 0)
                    if circuit.gps else jnp.zeros(4, _U32))
        c_at_z = combine_constraints(
            circuit, base_getter, ext_getter, alpha, beta, alpha_c,
            like, ScalarExtOps, lambda v: v, row0_val)

        q_at_z = jnp.zeros(4, _U32)
        z_pow_n = F.epow(z, n)
        zk = jnp.asarray(F.EXT_ONE)
        for k in range(B):
            seg = jnp.zeros(4, _U32)
            for c in range(4):
                seg = F.eadd(seg, F.emul(jnp.asarray(BASIS[c]),
                                         jnp.asarray(openings[("quotient", k * 4 + c, 0)])))
            q_at_z = F.eadd(q_at_z, F.emul(zk, seg))
            zk = F.emul(zk, z_pow_n)
        zh_at_z = F.esub(z_pow_n, jnp.asarray(F.EXT_ONE))
        if not np.array_equal(np.asarray(c_at_z),
                              np.asarray(F.emul(q_at_z, zh_at_z))):
            return False

    # -- DEEP + FRI -------------------------------------------------------------
    with phase("fri"):
        gamma = jnp.asarray(tx.challenge_ext())
        ok, q_idx, layer0, _ = fri_mod.fri_verify(proof.fri_proof, tx, cfg.fri(), nl)
    if not ok:
        return False
    lo, hi, pair_idx = layer0
    idx_all = np.concatenate([pair_idx, pair_idx + nl // 2])

    # Merkle openings of committed trees at the queried rows
    with phase("merkle"):
        col_counts = {"data": circuit.n_data, "advice": circuit.n_advice,
                      "ext": circuit.n_ext * 4, "quotient": B * 4}
        roots = {"data": proof.data_root, "advice": proof.advice_root,
                 "ext": proof.ext_root, "quotient": proof.quotient_root}
        rowvals = {}
        for name in ("data", "advice", "ext", "quotient"):
            rows, paths = proof.tree_openings[name]
            if col_counts[name] == 0:
                continue
            if rows.shape[0] != len(idx_all) or rows.shape[1] != col_counts[name]:
                return False
            if not bool(merkle.verify_open(jnp.asarray(roots[name]),
                                           jnp.asarray(idx_all),
                                           jnp.asarray(rows), jnp.asarray(paths))):
                return False
            rowvals[name] = rows

    # recompute DEEP composition at each queried point
    with phase("deep"):
        committed = [(k, i, r) for (k, i, r) in sched
                     if k in (DATA, ADVICE, "ext", "quotient")]
        groups = {}
        for (k, i, r) in committed:
            groups.setdefault(r, []).append((k, i))
        pts = np.asarray(F.fmul(poly.domain_points(nl), _U32(cfg.shift)))[idx_all]
        pts = jnp.asarray(pts)
        nq = len(idx_all)
        deep = jnp.zeros((nq, 4), _U32)
        g_pow = gamma
        name_of = {DATA: "data", ADVICE: "advice", "ext": "ext",
                   "quotient": "quotient"}
        for r in sorted(groups):
            zr = F.emul_fp(z, _U32(pow(w_n, r, F.P)))
            denom = F.esub(F.ext(pts), jnp.broadcast_to(zr, (nq, 4)))
            inv_d = F.ebatch_inv(denom)
            num = jnp.zeros((nq, 4), _U32)
            for (k, i) in groups[r]:
                vals = jnp.asarray(rowvals[name_of[k]][:, i].astype(np.uint32))
                diff = F.esub(F.ext(vals), jnp.broadcast_to(
                    jnp.asarray(openings[(k, i, r)]), (nq, 4)))
                num = F.eadd(num, F.emul(jnp.broadcast_to(g_pow, (nq, 4)), diff))
                g_pow = F.emul(g_pow, gamma)
            deep = F.eadd(deep, F.emul(num, inv_d))
        expect = np.concatenate([lo, hi], axis=0)
        return bool(np.array_equal(np.asarray(deep), expect))
