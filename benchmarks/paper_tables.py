"""One benchmark function per paper table/figure. Each yields CSV rows
(name, us_per_call, derived)."""
from __future__ import annotations

import numpy as np

from repro.core import prover as pv
from repro.core.session import TrustAnchor, ZKGraphSession
from repro.core.operators import birc, expansion, set_expansion, sssp
from repro.graphdb import engine
from repro.graphdb.storage import expand_bidirectional, pad_pow2

from . import common
from .common import BENCH_CFG, db_with_rows, est_prover_mem_bytes, timed


def timed_prove(op, a, i, d):
    """Prove twice, time the second run (jit caches warm — the steady-state
    cost a proving service pays; see EXPERIMENTS.md for methodology)."""
    op.prove(a.copy(), i, d)
    return timed(op.prove, a, i, d)


# ---------------------------------------------------------------------------
# Table I: edge-list vs CSR single-source expansion
# ---------------------------------------------------------------------------
def table1(rows: int = 2048):
    db = db_with_rows(rows)
    t = db.tables["person_knows_person"]
    src_id = int(t.src[0])
    n_rows = pad_pow2(len(t))
    # edge-list
    op_el = expansion.build_edge_list(n_rows, len(t))
    _, keygen_el = timed(op_el.keygen, BENCH_CFG)
    a, i, d = expansion.witness_edge_list(op_el, t.src, t.dst, src_id)
    op_el.prove(a.copy(), i, d)                  # warm jit caches
    proof_el, prove_el = timed(op_el.prove, a, i, d)
    op_el.verify(i, proof_el)
    ok, verify_el = timed(op_el.verify, i, proof_el)
    assert ok
    yield ("table1/edge_list/keygen", keygen_el, "")
    yield ("table1/edge_list/prove", prove_el, f"cols={op_el.circuit.n_advice}")
    yield ("table1/edge_list/verify", verify_el,
           f"proof_fields={proof_el.size_fields()}")
    # CSR
    col, row_ptr, lut = t.to_csr(db.node_ids)
    n_rows_c = pad_pow2(max(len(col), len(lut) + 1))
    op_csr = expansion.build_csr(n_rows_c, len(col), len(lut),
                                 id_bits=max(db.id_bits,
                                             n_rows_c.bit_length()))
    _, keygen_c = timed(op_csr.keygen, BENCH_CFG)
    a, i, d = expansion.witness_csr(op_csr, col, row_ptr, lut, src_id)
    op_csr.prove(a.copy(), i, d)                 # warm jit caches
    proof_c, prove_c = timed(op_csr.prove, a, i, d)
    op_csr.verify(i, proof_c)
    ok, verify_c = timed(op_csr.verify, i, proof_c)
    assert ok
    yield ("table1/csr/keygen", keygen_c, "")
    yield ("table1/csr/prove", prove_c, f"cols={op_csr.circuit.n_advice}")
    yield ("table1/csr/verify", verify_c,
           f"proof_fields={proof_c.size_fields()}")
    yield ("table1/ratio/prove_csr_over_el", prove_c / prove_el,
           "paper: 40.36/11.42=3.5x")


# ---------------------------------------------------------------------------
# Table II: public-parameter setup vs max rows
# ---------------------------------------------------------------------------
def table2():
    """Setup = twiddle/LDE/tree precompute capacity; measure keygen of a
    fixed-shape circuit at growing row counts (the paper's SRS-size axis)."""
    import repro.core.poly as poly
    for log_n in (10, 11, 12, 13, 14):
        n = 1 << log_n
        c = _fixed_circuit(n)
        pv.keygen(c, BENCH_CFG)          # warm the per-size NTT jit cache
        (keys), t_us = timed(pv.keygen, c, BENCH_CFG)
        yield (f"table2/setup_rows_2^{log_n}", t_us,
               f"lde_bytes={keys.fixed_lde.size * 4}")


def _fixed_circuit(n):
    from repro.core import plonkish as pk
    c = pk.Circuit(n, name=f"setup{n}")
    for j in range(8):
        c.add_fixed(f"f{j}", np.arange(n) * (j + 1))
    a = c.add_advice("a")
    c.add_gate("g", a * (a - pk.Const(1)))
    return c


# ---------------------------------------------------------------------------
# Table III: PK/VK generation per LDBC query
# ---------------------------------------------------------------------------
def table3(rows: int = 1024):
    db = db_with_rows(rows)
    session = ZKGraphSession(db, BENCH_CFG)
    params = {"IS3": dict(person=3), "IS4": dict(message=(1 << 20) + 5),
              "IS5": dict(message=(1 << 20) + 7),
              "IC1": dict(person=2, firstName=int(
                  db.node_props["person"]["firstName"][0])),
              "IC2": dict(person=4, k=10), "IC8": dict(person=5, k=10),
              "IC13": dict(person1=1, person2=9)}
    for q, p in params.items():
        run = session.run_query(q, p)

        def keygen_all():
            for st in run.steps:
                st.op.keygen(BENCH_CFG)     # raw keygen, no session cache
        _, t_us = timed(keygen_all)
        yield (f"table3/keygen/{q}", t_us, f"steps={len(run.steps)}")


# ---------------------------------------------------------------------------
# keygen cache: cold vs warm session (the ZKGraphSession hot-path win)
# ---------------------------------------------------------------------------
def cachewin(rows: int = 1024):
    """Before/after for the session keygen cache on repeated queries: a warm
    session skips every per-step keygen (fixed-column intt + LDE + device
    transfer), which the seed paid on each prove_query call."""
    from repro.core.operators import registry
    from repro.core.session import circuit_shape_digest
    db = db_with_rows(rows)
    p = dict(person=3)
    ZKGraphSession(db, BENCH_CFG).prove("IS3", p)       # warm jit caches
    session = ZKGraphSession(db, BENCH_CFG)
    _, cold_us = timed(session.prove, "IS3", p)         # cold keygen cache
    after_cold = session.cache.stats()
    _, warm_us = timed(session.prove, "IS3", p)         # warm keygen cache
    after_warm = session.cache.stats()
    yield ("cachewin/IS3/cold_session", cold_us,
           f"keygens={after_cold['misses']}")
    yield ("cachewin/IS3/warm_session", warm_us,
           f"keygen_hits={after_warm['hits']};"
           f"speedup={cold_us / warm_us:.2f}x")
    # the shape digest is memoized on the circuit: a cache *hit* no longer
    # pays the SHA-256 over every fixed-column's bytes on each ensure()
    t = db.tables["person_knows_person"]
    op = registry.build_operator("expand", dict(
        n_rows=pad_pow2(len(t)), m_edges=len(t), with_prop=False,
        reverse=False))
    session.cache.ensure(op, BENCH_CFG)                 # digest + keygen once
    _, hit_us = timed(session.cache.ensure, op, BENCH_CFG)  # memoized digest
    op.circuit._shape_digest = None                     # force a recompute
    _, digest_us = timed(circuit_shape_digest, op.circuit)
    yield ("cachewin/ensure_hit_memoized", hit_us,
           f"rows={op.circuit.n_rows}")
    yield ("cachewin/ensure_hit_digest_recompute", hit_us + digest_us,
           f"digest_us={digest_us:.1f};"
           f"speedup={(hit_us + digest_us) / max(hit_us, 1e-9):.2f}x")


# ---------------------------------------------------------------------------
# Fig 6a: SSSP operator vs in-circuit BFS, varying hops
# ---------------------------------------------------------------------------
def fig6a(rows: int = 512):
    db = db_with_rows(rows)
    t = db.tables["person_knows_person"]
    src_id = int(db.node_ids[0])
    n_rows = pad_pow2(max(len(t), db.n_nodes))
    # our SSSP: hop-independent
    dist, pred, pd = engine.bfs_sssp(t, db.node_ids, src_id, True)
    op = sssp.build(n_rows, len(t), db.n_nodes, undirected=True)
    op.keygen(BENCH_CFG)
    a, i, d = sssp.witness(op, t.src, t.dst, db.node_ids, src_id, dist,
                           pred, pd)
    proof, t_sssp = timed_prove(op, a, i, d)
    mem = est_prover_mem_bytes(op.circuit, BENCH_CFG)
    yield ("fig6a/sssp/anyhops", t_sssp, f"mem_bytes={mem}")
    for hops in (2, 4, 6):
        bop = common.build_bfs_circuit(n_rows, len(t), db.n_nodes, hops)
        bop.keygen(BENCH_CFG)
        a, i, d = common.bfs_witness(bop, t.src, t.dst, db.node_ids, src_id)
        proof, t_bfs = timed_prove(bop, a, i, d)
        mem_b = est_prover_mem_bytes(bop.circuit, BENCH_CFG)
        yield (f"fig6a/bfs/hops{hops}", t_bfs,
               f"mem_bytes={mem_b};ratio={t_bfs/t_sssp:.2f}")


# ---------------------------------------------------------------------------
# Fig 6b: set-based expansion vs repeated single-source
# ---------------------------------------------------------------------------
def fig6b(rows: int = 2048):
    db = db_with_rows(rows)
    t = db.tables["person_knows_person"]
    n_rows = pad_pow2(len(t))
    for n_start in (4, 16, 64):
        ids = np.unique(t.src)[:n_start]
        op = set_expansion.build(pad_pow2(max(len(t), len(ids) + 2)), len(t),
                                 len(ids))
        op.keygen(BENCH_CFG)
        a, i, d = set_expansion.witness(op, t.src, t.dst, ids)
        _, t_set = timed_prove(op, a, i, d)
        mem = est_prover_mem_bytes(op.circuit, BENCH_CFG)
        yield (f"fig6b/set_based/n{n_start}", t_set, f"mem_bytes={mem}")
        # repeated single-source: cost = n_start * (one expansion proof)
        op1 = expansion.build_edge_list(n_rows, len(t))
        op1.keygen(BENCH_CFG)
        a, i, d = expansion.witness_edge_list(op1, t.src, t.dst, int(ids[0]))
        _, t_one = timed_prove(op1, a, i, d)
        yield (f"fig6b/repeated_single/n{n_start}", t_one * n_start,
               f"mem_bytes={est_prover_mem_bytes(op1.circuit, BENCH_CFG) * n_start}"
               f";extrapolated_from_one")


# ---------------------------------------------------------------------------
# Table IV: BiRC integrated vs preprocessing (duplicate edges)
# ---------------------------------------------------------------------------
def table4(rows: int = 1024):
    db = db_with_rows(rows)
    t = db.tables["person_knows_person"]
    ids = np.unique(t.src)[:8]
    # set-based expansion: integrated BiRC on canonical storage
    op = set_expansion.build(pad_pow2(len(t)), len(t), len(ids),
                             bidirectional=True)
    op.keygen(BENCH_CFG)
    a, i, d = set_expansion.witness(op, t.src, t.dst, ids)
    _, t_birc = timed_prove(op, a, i, d)
    yield ("table4/set_exp/birc", t_birc,
           f"mem_bytes={est_prover_mem_bytes(op.circuit, BENCH_CFG)}")
    # preprocessing: duplicated edge table (2m rows), plain operator
    t2 = expand_bidirectional(t)
    op2 = set_expansion.build(pad_pow2(len(t2)), len(t2), len(ids))
    op2.keygen(BENCH_CFG)
    a, i, d = set_expansion.witness(op2, t2.src, t2.dst, ids)
    _, t_pre = timed_prove(op2, a, i, d)
    yield ("table4/set_exp/preprocess", t_pre,
           f"mem_bytes={est_prover_mem_bytes(op2.circuit, BENCH_CFG)}"
           f";ratio={t_pre/t_birc:.2f} (paper 21.67/8.22=2.6x)")
    # SSSP variant
    src_id = int(db.node_ids[0])
    dist, pred, pd = engine.bfs_sssp(t, db.node_ids, src_id, True)
    n_rows = pad_pow2(max(len(t), db.n_nodes))
    op3 = sssp.build(n_rows, len(t), db.n_nodes, undirected=True)
    op3.keygen(BENCH_CFG)
    a, i, d = sssp.witness(op3, t.src, t.dst, db.node_ids, src_id, dist,
                           pred, pd)
    _, t_birc_s = timed_prove(op3, a, i, d)
    yield ("table4/sssp/birc", t_birc_s,
           f"mem_bytes={est_prover_mem_bytes(op3.circuit, BENCH_CFG)}")
    n_rows2 = pad_pow2(max(len(t2), db.n_nodes))
    op4 = sssp.build(n_rows2, len(t2), db.n_nodes, undirected=False)
    op4.keygen(BENCH_CFG)
    dist2, pred2, pd2 = engine.bfs_sssp(t2, db.node_ids, src_id, False)
    a, i, d = sssp.witness(op4, t2.src, t2.dst, db.node_ids, src_id, dist2,
                           pred2, pd2)
    _, t_pre_s = timed_prove(op4, a, i, d)
    yield ("table4/sssp/preprocess", t_pre_s,
           f"mem_bytes={est_prover_mem_bytes(op4.circuit, BENCH_CFG)}"
           f";ratio={t_pre_s/t_birc_s:.2f} (paper 31.31/26.96=1.16x)")


# ---------------------------------------------------------------------------
# Fig 7: proof-generation breakdown for IC1 and IC9
# ---------------------------------------------------------------------------
def fig7(rows: int = 1024):
    db = db_with_rows(rows)
    session = ZKGraphSession(db, BENCH_CFG)
    for q, p in (("IC1", dict(person=2, firstName=int(
            db.node_props["person"]["firstName"][0]))),
            ("IC9", dict(person=6, k=10))):
        bundle = session.prove(q, p)
        total = 0.0
        for rec in bundle.steps:
            t_us = rec.proof.timings["total"] * 1e6
            total += t_us
            yield (f"fig7/{q}/{rec.kind}", t_us,
                   ";".join(f"{k}={v:.2f}s"
                            for k, v in rec.proof.timings.items()
                            if k != "total"))
        yield (f"fig7/{q}/TOTAL", total, f"steps={len(bundle.steps)}")


# ---------------------------------------------------------------------------
# wire codec: canonical ProofBundle bytes vs the seed's pickle placeholder
# ---------------------------------------------------------------------------
def wire_codec(rows: int = 1024):
    """Encode/decode time + serialized size for the canonical wire format
    (repro.core.wire) against the legacy pickle it replaced (pickle is
    measured here as the baseline only — it no longer ships).  Also emits
    ``BENCH_wire.json`` so the serialization perf trajectory is recorded."""
    import json
    import pickle

    from repro.core.session import ProofBundle

    db = db_with_rows(rows)
    session = ZKGraphSession(db, BENCH_CFG)
    records = {}
    for q, p in (("IS5", dict(message=(1 << 20) + 7)),
                 ("IS3", dict(person=3)),
                 ("IC13", dict(person1=1, person2=9))):
        bundle = session.prove(q, p)
        raw, enc_us = timed(bundle.to_bytes)
        rt, dec_us = timed(ProofBundle.from_bytes, raw)
        assert rt.to_bytes() == raw                 # canonical round trip
        pkl, penc_us = timed(pickle.dumps, bundle, pickle.HIGHEST_PROTOCOL)
        _, pdec_us = timed(pickle.loads, pkl)
        records[q] = dict(
            steps=len(bundle.steps), wire_bytes=len(raw),
            pickle_bytes=len(pkl), encode_us=round(enc_us, 1),
            decode_us=round(dec_us, 1), pickle_encode_us=round(penc_us, 1),
            pickle_decode_us=round(pdec_us, 1),
            size_ratio=round(len(raw) / len(pkl), 3))
        yield (f"wire/{q}/encode", enc_us,
               f"bytes={len(raw)};pickle_bytes={len(pkl)};"
               f"size_ratio={len(raw) / len(pkl):.2f}")
        yield (f"wire/{q}/decode", dec_us,
               f"pickle_decode_us={pdec_us:.1f}")
    with open("BENCH_wire.json", "w") as f:
        json.dump(dict(rows=rows, cfg=dict(
            blowup=BENCH_CFG.blowup, n_queries=BENCH_CFG.n_queries,
            fri_final_size=BENCH_CFG.fri_final_size), queries=records),
            f, indent=2, sort_keys=True)
    yield ("wire/BENCH_wire.json", 0.0, f"queries={len(records)}")


# ---------------------------------------------------------------------------
# transparency: manifest codec + digest + log append / proof timings
# ---------------------------------------------------------------------------
def transparency_bench(rows: int = 1024):
    """Perf trajectory of the publication path (repro.core.transparency):
    canonical manifest encode/decode/digest, transparency-log appends at
    growing log sizes, and inclusion/consistency proof generate+verify.
    Emits ``BENCH_transparency.json``."""
    import json

    from repro.core.commit import CommitmentManifest
    from repro.core import transparency as tl

    db = db_with_rows(rows)
    session = ZKGraphSession(db, BENCH_CFG)
    manifest = session.commitments
    raw, enc_us = timed(manifest.to_bytes)
    m2, dec_us = timed(CommitmentManifest.from_bytes, raw)
    assert m2.to_bytes() == raw                     # canonical round trip
    tl.manifest_digest(raw)                         # warm the sponge jit
    digest, dig_us = timed(tl.manifest_digest, raw)
    records = dict(manifest_bytes=len(raw), encode_us=round(enc_us, 1),
                   decode_us=round(dec_us, 1), digest_us=round(dig_us, 1))
    yield ("transparency/manifest/encode", enc_us, f"bytes={len(raw)}")
    yield ("transparency/manifest/decode", dec_us, "")
    yield ("transparency/manifest/digest", dig_us,
           f"roots={len(manifest.roots)}")

    # append cost vs log size: O(log n) compressions thanks to subtree memo
    log = tl.TransparencyLog("bench-log")
    appends = {}
    next_mark = 1
    for i in range(64):
        entry = raw + i.to_bytes(8, "little")       # 64 manifest revisions
        if i + 1 == next_mark:
            cp, t_us = timed(log.append, entry)
            appends[i + 1] = round(t_us, 1)
            yield (f"transparency/log/append_at_{i + 1}", t_us,
                   f"tree_size={cp.tree_size}")
            next_mark *= 2
        else:
            log.append(entry)
    records["append_us_by_size"] = appends

    cp = log.checkpoint()
    pf, inc_us = timed(log.inclusion_proof, 17)
    leaf = tl.manifest_digest(log.entry(17))
    ok, incv_us = timed(tl.verify_inclusion, cp, pf, leaf)
    assert ok
    yield ("transparency/inclusion/prove", inc_us,
           f"path_nodes={pf.path.shape[0]}")
    yield ("transparency/inclusion/verify", incv_us, "")
    old_cp = log.checkpoint(21)
    cpf, con_us = timed(log.consistency_proof, 21)
    ok, conv_us = timed(tl.verify_consistency, old_cp, cp, cpf)
    assert ok
    yield ("transparency/consistency/prove", con_us,
           f"path_nodes={cpf.path.shape[0]}")
    yield ("transparency/consistency/verify", conv_us, "")
    records.update(
        inclusion_prove_us=round(inc_us, 1),
        inclusion_verify_us=round(incv_us, 1),
        consistency_prove_us=round(con_us, 1),
        consistency_verify_us=round(conv_us, 1), log_size=log.size)

    # the durable store: fsync'd append, full replay-and-cross-check reopen
    import tempfile
    from pathlib import Path

    from repro.core import gossip as gp
    from repro.core.transparency import TransparencyLog

    with tempfile.TemporaryDirectory() as tmp:
        path = Path(tmp) / "bench.log"
        dlog = TransparencyLog.open(path, "bench-log")
        for i in range(32):
            dlog.append(raw + i.to_bytes(8, "little"))
        _, dapp_us = timed(dlog.append, raw + b"durable-append-timing")
        _, sync_us = timed(dlog.sync)
        dlog.close()
        dlog2, open_us = timed(TransparencyLog.open, path)
        store_bytes = path.stat().st_size
        n_leaves = dlog2.size
        dlog2.close()
    yield ("transparency/logstore/append", dapp_us,
           f"fsync;store_bytes={store_bytes}")
    yield ("transparency/logstore/sync", sync_us, "replay+cross-check")
    yield ("transparency/logstore/open_replay", open_us,
           f"leaves={n_leaves}")
    records.update(durable_append_us=round(dapp_us, 1),
                   durable_sync_us=round(sync_us, 1),
                   durable_open_replay_us=round(open_us, 1),
                   store_bytes=store_bytes)

    # gossip: Ed25519 sign/verify, emit, and the peer's verify-and-advance
    # hot path (all pure Python — the ed25519_* rows are the floor every
    # networked gossip round pays per head)
    from repro.core import ed25519 as ed
    key = ed.SigningKey.from_secret(b"bench-gossip-key")
    head = log.checkpoint()
    sig, sign_us = timed(gp.sign_checkpoint, key, head)
    ok, sigv_us = timed(gp.verify_signature, key.pub, head, sig)
    assert ok
    yield ("transparency/ed25519/sign", sign_us,
           f"msg_bytes={len(head.to_bytes()) + 1}")
    yield ("transparency/ed25519/verify", sigv_us, "")
    msg, emit_us = timed(gp.emit, log, key, 21)
    wire_bytes = msg.to_bytes()
    cp21 = log.checkpoint(21)
    pinned_root = np.asarray(cp21.root, np.uint32)

    def offer_advance():
        # exactly the verifier's hot path: decode hostile bytes, check the
        # signature, verify the consistency proof, advance the pin.  The
        # peer's pre-pinned state is set directly so bootstrap cost (an
        # extra signature check + offer) stays out of the gated metric.
        p = gp.GossipPeer(log.origin, key.pub)
        p.head, p.seen = cp21, {21: pinned_root}
        return p.offer(gp.GossipMessage.from_bytes(wire_bytes))

    assert offer_advance() is True
    _, offer_us = timed(offer_advance)
    yield ("transparency/gossip/emit", emit_us,
           f"bytes={len(wire_bytes)}")
    yield ("transparency/gossip/decode_verify_advance", offer_us,
           f"span=21->{log.size}")
    records.update(gossip_emit_us=round(emit_us, 1),
                   gossip_offer_us=round(offer_us, 1),
                   gossip_bytes=len(wire_bytes),
                   ed25519_sign_us=round(sign_us, 1),
                   ed25519_verify_us=round(sigv_us, 1))

    # framed round trip: one gossip head served over the real socket
    # transport (loopback), REQ_HEAD -> signed envelope -> verify+advance
    from repro.net import framing, server as net_server
    from repro.net.peer import PeerClient

    srv = net_server.NetServer()
    srv.register(framing.REQ_HEAD,
                 lambda payload: (framing.RESP_HEAD, wire_bytes))
    with srv.serving() as addr:
        client = PeerClient(addr, timeout=5.0)

        def framed_round_trip():
            kind, payload = client.request(framing.REQ_HEAD, b"")
            assert kind == framing.RESP_HEAD
            p = gp.GossipPeer(log.origin, key.pub)
            p.head, p.seen = cp21, {21: pinned_root}
            return p.offer(gp.GossipMessage.from_bytes(payload))

        assert framed_round_trip() is True
        _, rt_us = timed(framed_round_trip)
        client.close()
    yield ("transparency/net/framed_head_round_trip", rt_us,
           f"loopback;bytes={len(wire_bytes)}")
    records.update(framed_head_round_trip_us=round(rt_us, 1))

    with open("BENCH_transparency.json", "w") as f:
        json.dump(dict(rows=rows, results=records), f, indent=2,
                  sort_keys=True)
    yield ("transparency/BENCH_transparency.json", 0.0, f"log_size={log.size}")


# ---------------------------------------------------------------------------
# compute backends: ref vs pallas-interpret vs pallas, per primitive + e2e
# ---------------------------------------------------------------------------
def kernels(rows: int = 256):
    """Per-primitive and end-to-end backend comparison; emits
    ``BENCH_kernels.json``.

    On a CPU container the compiled ``pallas`` backend is unavailable
    (recorded as such) and ``pallas-interpret`` is *slower* than ``ref`` —
    the interpreter exists for parity/CI, not speed.  On an accelerator a
    failed ``pallas`` probe raises.  All timings are second-call (warm jit
    caches) host-clock times."""
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp

    from repro.core import backend, field as F, hashing, merkle, poly

    usable, status = [], {}
    for name in backend.names():
        ok, reason = backend.probe(name)
        status[name] = "ok" if ok else reason
        if ok:
            usable.append(name)
        elif name == "pallas" and jax.default_backend() != "cpu":
            # on an accelerator the compiled kernels are the product: a
            # failed probe is a fault, never a dropped column
            raise backend.BackendUnavailableError(
                f"pallas unusable on {jax.default_backend()}: {reason}")
        yield (f"kernels/backend/{name}", 0.0, status[name][:60])

    rng = np.random.default_rng(0)
    states = jnp.asarray(rng.integers(0, F.P, size=(4096, 16))
                         .astype(np.uint32))
    hrows = jnp.asarray(rng.integers(0, F.P, size=(1024, 8))
                        .astype(np.uint32))
    gp = jnp.asarray(rng.integers(0, F.P, size=(4096, 4)).astype(np.uint32))
    prims = {
        "poseidon_permute_4096": lambda: hashing.permute(states),
        "hash_rows_1024x8": lambda: hashing.hash_rows(hrows),
        "merkle_commit_1024x8": lambda: merkle.commit(hrows).root,
        "grand_product_ext_4096": lambda: backend.active()
                                          .grand_product_ext(gp),
    }
    for log_n in (10, 12, 14):
        x = jnp.asarray(rng.integers(0, F.P, size=(4, 1 << log_n))
                        .astype(np.uint32))
        prims[f"ntt_b4_2^{log_n}"] = (lambda x=x: poly.ntt(x))

    def run_blocked(fn):
        return jax.block_until_ready(fn())

    primitives = {}
    for pname, fn in prims.items():
        primitives[pname] = {}
        for bname in usable:
            with backend.use(bname):
                run_blocked(fn)                          # warm trace + jit
                _, t_us = timed(run_blocked, fn)
            primitives[pname][f"{bname}_us"] = round(t_us, 1)
        ref_us = primitives[pname]["ref_us"]
        derived = ";".join(f"{b}={primitives[pname][f'{b}_us']:.0f}us"
                           for b in usable)
        yield (f"kernels/{pname}", ref_us, derived)

    # end-to-end prove latency per LDBC query, per backend
    db = db_with_rows(rows)
    manifest = ZKGraphSession(db, BENCH_CFG).commitments   # shared: parity
    end_to_end = {}
    for q, p in (("IS3", dict(person=3)),
                 ("IS5", dict(message=(1 << 20) + 7))):
        end_to_end[q] = {}
        for bname in usable:
            cfg = dataclasses.replace(BENCH_CFG, backend=bname)
            session = ZKGraphSession(db, cfg, commitments=manifest)
            session.prove(q, p)                          # warm
            bundle, t_us = timed(session.prove, q, p)
            end_to_end[q][f"{bname}_us"] = round(t_us, 1)
        yield (f"kernels/e2e/{q}", end_to_end[q]["ref_us"],
               ";".join(f"{b}={end_to_end[q][f'{b}_us']:.0f}us"
                        for b in usable))

    with open("BENCH_kernels.json", "w") as f:
        json.dump(dict(rows=rows, backends=status, primitives=primitives,
                       end_to_end=end_to_end), f, indent=2, sort_keys=True)
    yield ("kernels/BENCH_kernels.json", 0.0,
           f"backends={'+'.join(usable)}")


# ---------------------------------------------------------------------------
# serving: ProofService throughput vs a sequential prove loop, same run
# ---------------------------------------------------------------------------
def serving(rows: int = 128):
    """Concurrent serving throughput (repro.serve.ProofService) against a
    sequential ``session.prove`` loop over the SAME query mix, measured in
    the same run with warm jit caches.  Lane-batched proving amortizes the
    per-dispatch overhead every solo prove pays, so queries/sec should grow
    with concurrency while each bundle stays wire-byte-identical to its
    solo prove (asserted below).  Emits
    ``BENCH_serving.json``; latency leaves are gated by
    ``benchmarks/check_regression.py`` against baselines/serving.json."""
    import json
    import time

    from repro.serve import ProofService

    db = db_with_rows(rows)
    session = ZKGraphSession(db, BENCH_CFG)
    queries = [("IS5", dict(message=(1 << 20) + 7 + i)) for i in range(16)]

    def serve(n):
        """Submit queries[:n] concurrently; max_batch=n + a long deadline
        means exactly one size-triggered flush per full batch, so the jit
        cache sees one lane count per concurrency level."""
        latencies = []
        t0 = time.perf_counter()
        with ProofService(session, max_batch=n, flush_interval=5.0) as svc:
            futs = []
            for q, p in queries[:n]:
                ts = time.perf_counter()
                fut = svc.submit(q, p)
                fut.add_done_callback(
                    lambda _f, ts=ts: latencies.append(
                        (time.perf_counter() - ts) * 1e6))
                futs.append(fut)
            bundles = [f.result() for f in futs]
            stats = svc.stats()
        total_us = (time.perf_counter() - t0) * 1e6
        return bundles, latencies, stats, total_us

    # warm every shape the measured runs will hit: the solo prover (c=1
    # degrades to it; also the sequential baseline) and each padded lane
    # count the service flushes at
    session.prove(*queries[0])
    for n in (4, 16):
        serve(n)

    results = {}
    for conc in (1, 4, 16):
        seq_bundles, seq_us = timed(
            lambda n=conc: [session.prove(q, p) for q, p in queries[:n]])
        bundles, lat, stats, svc_us = serve(conc)
        for got, want in zip(bundles, seq_bundles):
            assert got.to_bytes() == want.to_bytes(), \
                "serviced bundle bytes diverged from the sequential prover"
        qps = conc / (svc_us / 1e6)
        seq_qps = conc / (seq_us / 1e6)
        speedup = seq_us / svc_us
        occ = stats["batch_occupancy"]
        results[f"concurrency_{conc}"] = dict(
            queries=conc,
            service_total_us=round(svc_us, 1),
            sequential_total_us=round(seq_us, 1),
            qps=round(qps, 3), sequential_qps=round(seq_qps, 3),
            speedup=round(speedup, 3),
            latency_p50_us=round(float(np.percentile(lat, 50)), 1),
            latency_p95_us=round(float(np.percentile(lat, 95)), 1),
            occupancy_mean=round(occ["mean"], 2),
            batches=stats["counters"]["batches"],
            pad_lanes=stats["counters"]["pad_lanes"])
        yield (f"serving/c{conc}/service_total", svc_us,
               f"qps={qps:.2f};speedup={speedup:.2f}x;"
               f"occupancy={occ['mean']:.1f}")
        yield (f"serving/c{conc}/sequential_total", seq_us,
               f"qps={seq_qps:.2f}")
        yield (f"serving/c{conc}/latency_p95", float(np.percentile(lat, 95)),
               f"p50={np.percentile(lat, 50):.0f}us")

    with open("BENCH_serving.json", "w") as f:
        json.dump(dict(rows=rows, query="IS5", cfg=dict(
            blowup=BENCH_CFG.blowup, n_queries=BENCH_CFG.n_queries,
            fri_final_size=BENCH_CFG.fri_final_size), results=results),
            f, indent=2, sort_keys=True)
    yield ("serving/BENCH_serving.json", 0.0,
           f"speedup_c16={results['concurrency_16']['speedup']:.2f}x")


# ---------------------------------------------------------------------------
# Fig 8: scalability with database size
# ---------------------------------------------------------------------------
def fig8():
    for rows in (1024, 2048, 4096):
        db = db_with_rows(rows)
        session = ZKGraphSession(db, BENCH_CFG)
        verifier = ZKGraphSession.verifier(
            anchor=TrustAnchor(manifest=session.commitments), cfg=BENCH_CFG)
        for q, p in (("IS3", dict(person=3)),
                     ("IS5", dict(message=(1 << 20) + 7)),
                     ("IC13", dict(person1=1, person2=9))):
            bundle = session.prove(q, p)
            prove_us = bundle.prove_seconds() * 1e6
            ok, verify_us = timed(verifier.verify, bundle)
            assert ok
            yield (f"fig8/{q}/rows{rows}/prove", prove_us,
                   f"proof_fields={bundle.size_fields()}")
            yield (f"fig8/{q}/rows{rows}/verify", verify_us, "")


# ---------------------------------------------------------------------------
# Federation: sub-plan routing overhead + chain-joint verification
# ---------------------------------------------------------------------------
def federation(rows: int = 128):
    """Two-owner federated proving vs a single-owner prove of the SAME
    query, measured warm in one run: the coordinator's sub-plan routing
    overhead (cut computation, seed plumbing, per-owner request/response
    assembly — local clients, so the wire/socket cost is excluded and the
    overhead is the protocol's own), the composed ``verify_federated``
    cost, and the chain-joint leg alone (hand-off byte-compare + Ed25519
    signature check).  Emits ``BENCH_federation.json``; ``*_us`` leaves
    are gated by ``benchmarks/check_regression.py`` against
    ``baselines/federation.json``."""
    import json

    from repro.core import ed25519
    from repro.federation import (FederationCoordinator, GraphPartition,
                                  LocalOwnerClient, OwnerNode, OwnerSpec,
                                  verify_federated, verify_handoff)

    key_a = ed25519.SigningKey.from_secret(b"bench owner-a")
    key_b = ed25519.SigningKey.from_secret(b"bench owner-b")
    partition = GraphPartition((
        OwnerSpec("owner-a", ("knows", "knows_date", "knows_nodes",
                              "person_firstName"), key_a.pub),
        OwnerSpec("owner-b", ("hasCreator", "hasCreator_date",
                              "hasCreator_rev", "replyOf", "replyOf_rev",
                              "comment_date", "comment_content_date"),
                  key_b.pub)))
    db = db_with_rows(rows)
    qname, params = "IC2", dict(person=4, k=10)

    single = ZKGraphSession(db, BENCH_CFG)
    oa = OwnerNode.from_partition(partition, "owner-a", db, key_a,
                                  BENCH_CFG)
    ob = OwnerNode.from_partition(partition, "owner-b", db, key_b,
                                  BENCH_CFG)
    anchors = {"owner-a": TrustAnchor(manifest=oa.session.commitments),
               "owner-b": TrustAnchor(manifest=ob.session.commitments)}
    clients = {"owner-a": LocalOwnerClient(oa),
               "owner-b": LocalOwnerClient(ob)}
    co = FederationCoordinator(partition, clients, BENCH_CFG)

    single.prove(qname, params)                   # warm jit + keygen caches
    co.prove(qname, params)
    _, solo_us = timed(single.prove, qname, params)
    fed, fed_us = timed(co.prove, qname, params)
    routing_us = max(0.0, fed_us - solo_us)

    vsession = ZKGraphSession(db=None, cfg=BENCH_CFG)
    ok = verify_federated(fed, partition, anchors, session=vsession)
    assert ok
    _, verify_us = timed(verify_federated, fed, partition, anchors,
                         session=vsession)
    # the chain-joint leg alone: hand-off array compare + the
    # predecessor's Ed25519 signature over the signing bytes
    part = fed.parts[1]
    pred_key = partition.owner(fed.parts[0].owner).verify_key

    def joint():
        assert verify_handoff(pred_key, fed.query, fed.params, part.lo,
                              part.handoff, part.handoff_sig)
        for _, _, arr in part.handoff:
            np.asarray(arr, np.int64).tobytes()

    joint()
    _, joint_us = timed(joint)

    results = dict(
        prove_single_us=round(solo_us, 1),
        prove_federated_us=round(fed_us, 1),
        routing_overhead_us=round(routing_us, 1),
        verify_federated_us=round(verify_us, 1),
        chain_joint_verify_us=round(joint_us, 1),
        bundle_bytes=len(fed.to_bytes()),
        parts=len(fed.parts),
        handoff_entries=len(part.handoff))
    with open("BENCH_federation.json", "w") as f:
        json.dump(dict(rows=rows, query=qname, cfg=dict(
            blowup=BENCH_CFG.blowup, n_queries=BENCH_CFG.n_queries,
            fri_final_size=BENCH_CFG.fri_final_size), results=results),
            f, indent=2, sort_keys=True)
    yield ("federation/prove_single", solo_us, f"query={qname}")
    yield ("federation/prove_federated", fed_us,
           f"parts={len(fed.parts)};routing_overhead_us={routing_us:.0f}")
    yield ("federation/verify_federated", verify_us,
           f"chain_joint_us={joint_us:.0f}")
    yield ("federation/chain_joint", joint_us,
           f"entries={len(part.handoff)}")
    yield ("federation/BENCH_federation.json", 0.0,
           f"bundle_bytes={len(fed.to_bytes())}")


ALL = {"table1": table1, "table2": table2, "table3": table3, "fig6a": fig6a,
       "fig6b": fig6b, "table4": table4, "fig7": fig7, "fig8": fig8,
       "cachewin": cachewin, "wire": wire_codec,
       "transparency": transparency_bench, "kernels": kernels,
       "serving": serving, "federation": federation}
