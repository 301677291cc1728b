"""Lane-batched DEEP-ALI + FRI prover: L same-shaped witnesses, one pass.

The serving observation (ROADMAP "millions of users" axis): the paper's
expansion-centric decomposition makes every query a chain of SMALL
shape-regular circuits, and at those sizes the prover's wall-clock is
dominated by per-dispatch overhead, not arithmetic.  Same-shaped steps from
*different* queries follow the identical Fiat–Shamir schedule — only the
absorbed values differ — so stacking their witnesses behind a leading lane
axis ``L`` lets every phase (NTT/LDE, Merkle levels, sponge blocks,
constraint evaluation, FRI folds) run as ONE batched dispatch that amortizes
across queries.  ``repro.serve`` routes concurrent queries into these lanes.

Bit-identity contract (enforced by ``tests/test_serve.py`` across compute
backends): lane ``l`` of :func:`prove_batch` produces a :class:`Proof` whose
wire bytes equal the solo ``prove(keys, *witnesses[l])`` bytes.  It holds
because every primitive here is the solo primitive with a leading batch dim
— all field ops are elementwise integers mod P (no reassociation), hashing
and the NTT are row-independent under every backend, and per-lane challenge
streams never mix (:class:`~repro.core.transcript.BatchedTranscript`).
Nothing is approximated: this is the same proof, computed L at a time.

Layout conventions (solo shape -> lane shape):
  witness columns   (c, n)     -> (L, c, n)
  LDE matrices      (c, nl)    -> (L, c, nl)
  ext/Fp4 values    (n, 4)     -> (L, n, 4)
  challenges        (4,)       -> (L, 4)
  Merkle digests    (8,)       -> (L, 8)
Challenge broadcasts use ``[:, None, :]`` where the solo code used
``jnp.broadcast_to(ch, val.shape)`` — same elementwise products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import backend as be
from . import field as F
from . import fri as fri_mod
from . import merkle
from . import poly
from . import prover as pv
from .plonkish import ADVICE, DATA, FIXED, INSTANCE, BaseOps, eval_expr
from .transcript import BatchedTranscript

_U32 = jnp.uint32

__all__ = ["prove_batch"]


# ---------------------------------------------------------------------------
# lane-shaped helpers (solo siblings live in repro.core.prover)
# ---------------------------------------------------------------------------
def _lde_lanes(cols: jnp.ndarray, blowup: int, shift: int) -> jnp.ndarray:
    """(L, c, n) evaluations -> (L, c, n*blowup) coset LDE (c may be 0)."""
    if cols.shape[1] == 0:
        return jnp.zeros((cols.shape[0], 0, cols.shape[2] * blowup), _U32)
    return poly.coset_lde(cols, blowup, shift)


def _compress_tuple_lanes(vals, alpha):
    """Paper Eq. (1) with (L, 4) lane challenges over (L, n) columns."""
    acc = F.ext(vals[0])
    apow = alpha
    for v in vals[1:]:
        acc = F.eadd(acc, F.emul(apow[:, None, :], F.ext(v)))
        apow = F.emul(apow, alpha)
    return acc


def _build_ext_columns_lanes(circuit, getter_n, like_n, alpha, beta):
    """(L, n_ext, n, 4) phase-2 columns; mirrors pv.build_ext_columns."""
    lanes, n = like_n.shape
    cols = []
    for bus in circuit.buses:
        f_vals = [eval_expr(e, getter_n, BaseOps, like_n) for e in bus.f_tuple]
        t_vals = [eval_expr(e, getter_n, BaseOps, like_n) for e in bus.t_tuple]
        m_f = eval_expr(bus.m_f, getter_n, BaseOps, like_n)
        m_t = eval_expr(bus.m_t * bus.t_sel, getter_n, BaseOps, like_n)
        d_f = F.eadd(beta[:, None, :], _compress_tuple_lanes(f_vals, alpha))
        d_t = F.eadd(beta[:, None, :], _compress_tuple_lanes(t_vals, alpha))
        num = F.esub(F.fmul(d_t, m_f[:, :, None]), F.fmul(d_f, m_t[:, :, None]))
        inc = F.emul(num, F.ebatch_inv(F.emul(d_f, d_t)))
        h = pv._cumsum_mod(inc, axis=1)
        h = jnp.concatenate([jnp.zeros((lanes, 1, 4), _U32), h[:, :-1]], axis=1)
        cols.append(h)
    for gp in circuit.gps:
        c1 = [eval_expr(e, getter_n, BaseOps, like_n) for e in gp.c1_tuple]
        c2 = [eval_expr(e, getter_n, BaseOps, like_n) for e in gp.c2_tuple]
        s1 = eval_expr(gp.sel1, getter_n, BaseOps, like_n)
        s2 = eval_expr(gp.sel2, getter_n, BaseOps, like_n)
        one = jnp.zeros((lanes, n, 4), _U32).at[..., 0].set(1)
        d1 = F.eadd(beta[:, None, :], _compress_tuple_lanes(c1, alpha))
        d2 = F.eadd(beta[:, None, :], _compress_tuple_lanes(c2, alpha))
        not_s1 = F.fsub(jnp.full_like(s1, 1), s1)
        not_s2 = F.fsub(jnp.full_like(s2, 1), s2)
        f1 = F.eadd(F.fmul(d1, s1[:, :, None]), F.fmul(one, not_s1[:, :, None]))
        f2 = F.eadd(F.fmul(d2, s2[:, :, None]), F.fmul(one, not_s2[:, :, None]))
        ratio = F.emul(f1, F.ebatch_inv(f2))
        # the dispatched accumulator is (n, 4)-shaped; lanes run it in turn
        # (bit-identical to solo by construction — same call per lane)
        z = jnp.stack([be.active().grand_product_ext(ratio[l])
                       for l in range(lanes)])
        cols.append(z)
    if not cols:
        return jnp.zeros((lanes, 0, n, 4), _U32)
    return jnp.stack(cols, axis=1)


def _combine_constraints_lanes(circuit, base_getter, alpha, beta, alpha_c,
                               like_base, ext_getter, row0_val):
    """sum_i alpha_c^i * constraint_i on the LDE domain, lane-batched.

    Base values are (L, nl); the accumulator is (L, nl, 4); challenges are
    (L, 4).  Mirrors pv.combine_constraints with BaseOps (the prover path).
    """
    acc = None
    a_pow = None

    def ext_of_base(v):
        z = jnp.zeros(v.shape + (4,), _U32)
        return z.at[..., 0].set(v)

    def add_term(val_ext):
        nonlocal acc, a_pow
        if acc is None:
            acc = val_ext
            a_pow = alpha_c
        else:
            acc = F.eadd(acc, F.emul(a_pow[:, None, :], val_ext))
            a_pow = F.emul(a_pow, alpha_c)

    for _, gate in circuit.gates:
        v = eval_expr(gate, base_getter, BaseOps, like_base)
        add_term(ext_of_base(v))

    def compress(exprs):
        vals = [eval_expr(e, base_getter, BaseOps, like_base) for e in exprs]
        out = ext_of_base(vals[0])
        apow = alpha
        for v in vals[1:]:
            out = F.eadd(out, F.emul(apow[:, None, :], ext_of_base(v)))
            apow = F.emul(apow, alpha)
        return out

    def mul_base(val_ext, base_v):
        return F.emul(val_ext, ext_of_base(base_v))

    for bus in circuit.buses:
        d_f = F.eadd(beta[:, None, :], compress(bus.f_tuple))
        d_t = F.eadd(beta[:, None, :], compress(bus.t_tuple))
        h = ext_getter(bus.ext_col, 0)
        h1 = ext_getter(bus.ext_col, 1)
        m_f = eval_expr(bus.m_f, base_getter, BaseOps, like_base)
        m_t = eval_expr(bus.m_t * bus.t_sel, base_getter, BaseOps, like_base)
        term = F.emul(F.esub(h1, h), F.emul(d_f, d_t))
        term = F.esub(term, mul_base(d_t, m_f))
        term = F.eadd(term, mul_base(d_f, m_t))
        add_term(term)
    for gp in circuit.gps:
        d1 = F.eadd(beta[:, None, :], compress(gp.c1_tuple))
        d2 = F.eadd(beta[:, None, :], compress(gp.c2_tuple))
        s1 = eval_expr(gp.sel1, base_getter, BaseOps, like_base)
        s2 = eval_expr(gp.sel2, base_getter, BaseOps, like_base)
        one_b = BaseOps.const(1, like_base)
        f1 = F.eadd(mul_base(d1, s1), ext_of_base(BaseOps.sub(one_b, s1)))
        f2 = F.eadd(mul_base(d2, s2), ext_of_base(BaseOps.sub(one_b, s2)))
        z = ext_getter(gp.ext_col, 0)
        z1 = ext_getter(gp.ext_col, 1)
        add_term(F.esub(F.emul(z1, f2), F.emul(z, f1)))
        one_e = jnp.zeros(z.shape, _U32).at[..., 0].set(1)
        add_term(F.emul(ext_of_base(row0_val), F.esub(z, one_e)))
    if acc is None:
        acc = jnp.zeros(like_base.shape + (4,), _U32)
    return acc


@jax.jit
def _eval_at_ext_lanes(coeffs: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Evaluate (L, m, n) Fp coefficients at per-lane Fp4 ``z``
    (L, 4) -> (L, m, 4): poly.eval_at_ext per lane, over one (L, n, 4)
    table from poly.ext_powers (jit like it — the table's scans must not
    re-trace on each of the rot x kind calls)."""
    zpows = poly.ext_powers(z, coeffs.shape[-1])           # (L, n, 4)
    prod = F.fmul(coeffs[..., None].astype(_U32), zpows[:, None, :, :])
    s = F.mod_p(jnp.sum(prod.astype(jnp.uint64), axis=-2))
    return s.astype(_U32)


# ---------------------------------------------------------------------------
# the batched prove
# ---------------------------------------------------------------------------
def prove_batch(keys: pv.Keys, witnesses: list, label: str = "zkgraph",
                placement=None) -> list:
    """Prove L same-shaped witnesses as one lane-batched pass.

    ``witnesses``: list of ``(advice_np, instance_np, data_np)`` triples,
    all for ``keys.circuit``.  Returns one :class:`~repro.core.prover.Proof`
    per lane, wire-byte-identical to the solo
    ``prove(keys, ...)`` of that lane.  ``placement`` (optional,
    :class:`repro.serve.placement.Placement`) shards the lane axis across a
    device mesh; ``None`` keeps everything on the default device.

    Runs under ``keys.backend`` like solo prove — lanes never mix backends.
    """
    with be.use(keys.backend):
        return _prove_batch_impl(keys, witnesses, label, placement)


def _prove_batch_impl(keys: pv.Keys, witnesses: list, label: str,
                      placement=None) -> list:
    circuit, cfg = keys.circuit, keys.cfg
    n, B = circuit.n_rows, cfg.blowup
    nl = n * B
    lanes = len(witnesses)
    assert lanes >= 1, "prove_batch needs at least one lane"
    phase = obs.Phases("zkg.prove", lanes=lanes, circuit=circuit.name)

    with phase("commit_advice") as sp:
        adv_list, inst_list, data_list = [], [], []
        for advice_np, instance_np, data_np in witnesses:
            if data_np is None:
                data_np = np.zeros((0, n), np.uint32)
            pv.auto_multiplicities(circuit, data_np, advice_np, instance_np)
            adv_list.append(advice_np.astype(np.uint32))
            inst_list.append(instance_np.astype(np.uint32))
            data_list.append(data_np.astype(np.uint32))
        advice = jnp.asarray(np.stack(adv_list))               # (L, n_adv, n)
        data = jnp.asarray(np.stack(data_list)) if circuit.n_data \
            else jnp.zeros((lanes, 0, n), _U32)
        inst = jnp.asarray(np.stack(inst_list)) if circuit.n_instance \
            else jnp.zeros((lanes, 0, n), _U32)
        if placement is not None:
            advice, data, inst = placement.shard_lanes(advice, data, inst)

        btx = BatchedTranscript(label, lanes)
        btx.absorb_shared(circuit.digest_seed())
        if circuit.n_instance:
            inst_tree = merkle.commit_lanes(inst.transpose(0, 2, 1))
            btx.absorb_digest(np.asarray(inst_tree.roots))

        # --- phase 0: commit the dataset (the declared-DB binding) --------------
        data_coeffs = poly.intt(data) if circuit.n_data else data
        data_lde = _lde_lanes(data, B, cfg.shift)
        data_tree = merkle.commit_lanes(data_lde.transpose(0, 2, 1)) \
            if circuit.n_data else None
        data_roots = np.asarray(data_tree.roots) if data_tree \
            else np.zeros((lanes, 8), np.uint32)
        btx.absorb_digest(data_roots)

        # --- phase 1: commit advice -------------------------------------------
        adv_coeffs = poly.intt(advice) if circuit.n_advice else advice
        adv_lde = _lde_lanes(advice, B, cfg.shift)
        adv_tree = merkle.commit_lanes(adv_lde.transpose(0, 2, 1)) \
            if circuit.n_advice else None
        adv_roots = np.asarray(adv_tree.roots) if adv_tree \
            else np.zeros((lanes, 8), np.uint32)
        btx.absorb_digest(adv_roots)
        sp.sync(data_coeffs, adv_coeffs, data_lde, adv_lde)

    alpha = jnp.asarray(btx.challenge_ext())               # (L, 4)
    beta = jnp.asarray(btx.challenge_ext())

    # --- phase 2: ext columns ----------------------------------------------
    with phase("phase2_ext") as sp:
        fixed_n = jnp.asarray(np.stack(circuit.fixed_cols)
                              if circuit.fixed_cols
                              else np.zeros((0, n), np.uint32))
        fixed_n_lanes = jnp.broadcast_to(fixed_n, (lanes,) + fixed_n.shape)

        def getter_n(kind, idx, rot):
            src = {FIXED: fixed_n_lanes, ADVICE: advice, INSTANCE: inst,
                   DATA: data}[kind]
            return jnp.roll(src[:, idx], -rot, axis=-1)

        like_n = jnp.zeros((lanes, n), _U32)
        ext_cols = _build_ext_columns_lanes(circuit, getter_n, like_n, alpha, beta)
        n_ext = circuit.n_ext
        ext_base = ext_cols.transpose(0, 1, 3, 2).reshape(lanes, n_ext * 4, n) \
            if n_ext else jnp.zeros((lanes, 0, n), _U32)
        ext_coeffs = poly.intt(ext_base) if n_ext else ext_base
        ext_lde = _lde_lanes(ext_base, B, cfg.shift)
        ext_tree = merkle.commit_lanes(ext_lde.transpose(0, 2, 1)) \
            if n_ext else None
        ext_roots = np.asarray(ext_tree.roots) if ext_tree \
            else np.zeros((lanes, 8), np.uint32)
        btx.absorb_digest(ext_roots)
        sp.sync(ext_coeffs, ext_lde)

    alpha_c = jnp.asarray(btx.challenge_ext())

    # --- quotient -----------------------------------------------------------
    with phase("quotient") as sp:
        fixed_lde = jnp.broadcast_to(keys.fixed_lde,
                                     (lanes,) + keys.fixed_lde.shape)
        inst_lde = _lde_lanes(inst, B, cfg.shift)

        def getter_lde(kind, idx, rot):
            src = {FIXED: fixed_lde, ADVICE: adv_lde, INSTANCE: inst_lde,
                   DATA: data_lde}[kind]
            return jnp.roll(src[:, idx], -B * rot, axis=-1)

        def ext_getter_lde(col, rot):
            comps = [jnp.roll(ext_lde[:, col * 4 + c], -B * rot, axis=-1)
                     for c in range(4)]
            return jnp.stack(comps, axis=-1)

        like_lde = jnp.zeros((lanes, nl), _U32)
        row0_lde = (getter_lde(FIXED, circuit.fixed_names.index("__row0"), 0)
                    if circuit.gps else like_lde)
        c_lde = _combine_constraints_lanes(circuit, getter_lde, alpha, beta,
                                           alpha_c, like_lde, ext_getter_lde,
                                           row0_lde)
        # Z_H(x_i): same period-B host sequence as solo (lane-independent)
        wn = F.root_of_unity(nl)
        ratio = pow(wn, n, F.P)
        vals = np.empty(B, np.uint64)
        acc = pow(cfg.shift, n, F.P)
        for i in range(B):
            vals[i] = (acc - 1) % F.P
            acc = acc * ratio % F.P
        zh = np.asarray([vals[i % B] for i in range(nl)], np.uint32)
        zh_inv = F.fbatch_inv(jnp.asarray(zh))
        q_evals = F.fmul(c_lde, zh_inv[None, :, None])
        q_coeffs = poly.coset_coeffs(q_evals.transpose(0, 2, 1), cfg.shift)
        q_segments = q_coeffs.reshape(lanes, 4, B, n) \
            .transpose(0, 2, 1, 3).reshape(lanes, B * 4, n)
        q_lde = pv._lde_from_coeffs(q_segments, B, cfg.shift)
        q_tree = merkle.commit_lanes(q_lde.transpose(0, 2, 1))
        q_roots = np.asarray(q_tree.roots)
        btx.absorb_digest(q_roots)
        sp.sync(q_segments, q_lde)

    # --- OOD openings --------------------------------------------------------
    with phase("ood_openings"):
        z = jnp.asarray(btx.challenge_ext())                   # (L, 4)
        sched = pv.opening_schedule(circuit, B)
        fixed_coeffs = jnp.broadcast_to(keys.fixed_coeffs,
                                        (lanes,) + keys.fixed_coeffs.shape)
        coeff_src = {FIXED: fixed_coeffs,
                     INSTANCE: poly.intt(inst) if circuit.n_instance else inst,
                     DATA: data_coeffs, ADVICE: adv_coeffs, "ext": ext_coeffs,
                     "quotient": q_segments}
        w_n = F.root_of_unity(n)
        openings = {}              # (kind, i, rot) -> (L, 4) np
        rots = sorted({r for (_, _, r) in sched})
        for rot in rots:
            zr = F.emul_fp(z, _U32(pow(w_n, rot, F.P)))
            for kind in (FIXED, INSTANCE, DATA, ADVICE, "ext", "quotient"):
                idxs = [i for (k, i, rr) in sched if k == kind and rr == rot]
                if not idxs:
                    continue
                # repeat a row up to a multiple of 8: one gather/eval shape
                rows = idxs + idxs[:1] * ((-len(idxs)) % 8)
                coeffs = coeff_src[kind][:, jnp.asarray(rows)]
                vals = np.asarray(_eval_at_ext_lanes(coeffs, zr))  # (L, m, 4)
                for j, i in enumerate(idxs):
                    openings[(kind, i, rot)] = vals[:, j]
        for key in sched:
            btx.absorb(openings[key])

    # --- DEEP composition -----------------------------------------------------
    with phase("deep") as sp:
        gamma = jnp.asarray(btx.challenge_ext())
        pts_ext = F.ext(F.fmul(poly.domain_points(nl), _U32(cfg.shift)))  # (nl,4)
        committed = [(k, i, r) for (k, i, r) in sched
                     if k in (DATA, ADVICE, "ext", "quotient")]
        lde_src = {DATA: data_lde, ADVICE: adv_lde, "ext": ext_lde,
                   "quotient": q_lde}
        deep = jnp.zeros((lanes, nl, 4), _U32)
        g_pow = gamma
        groups = {}
        for (k, i, r) in committed:
            groups.setdefault(r, []).append((k, i))
        for r in sorted(groups):
            zr = F.emul_fp(z, _U32(pow(w_n, r, F.P)))
            denom = F.esub(pts_ext[None], zr[:, None, :])
            inv_d = F.ebatch_inv(denom)
            num = jnp.zeros((lanes, nl, 4), _U32)
            for (k, i) in groups[r]:
                p_lde = lde_src[k][:, i]                       # (L, nl)
                diff = F.esub(F.ext(p_lde),
                              jnp.asarray(openings[(k, i, r)])[:, None, :])
                num = F.eadd(num, F.emul(g_pow[:, None, :], diff))
                g_pow = F.emul(g_pow, gamma)
            deep = F.eadd(deep, F.emul(num, inv_d))
        sp.sync(deep)

    # --- FRI -------------------------------------------------------------------
    with phase("fri"):
        fproofs = fri_mod.fri_prove_lanes(deep, btx, cfg.fri())

    # --- query openings ---------------------------------------------------------
    with phase("query_openings"):
        q_idx = jnp.asarray(np.stack([fp.query_indices for fp in fproofs]))
        idx_all = jnp.concatenate([q_idx, q_idx + nl // 2], axis=1)
        tree_rows = {}             # name -> (rows (L,k,w), paths (L,k,d,8)) np
        n_open = idx_all.shape[1]
        for name, tree in (("data", data_tree), ("advice", adv_tree),
                           ("ext", ext_tree), ("quotient", q_tree)):
            if tree is None:
                tree_rows[name] = (
                    np.zeros((lanes, n_open, 0), np.uint32),
                    np.zeros((lanes, n_open, 0, 8), np.uint32))
            else:
                rows, paths = merkle.open_lanes(tree, idx_all)
                tree_rows[name] = (np.asarray(rows), np.asarray(paths))
    timings = phase.timings()

    # --- per-lane Proof assembly (same key orders as solo) ---------------------
    proofs = []
    for l in range(lanes):
        sent = {k: v[l] for k, v in openings.items()
                if k[0] in (DATA, ADVICE, "ext", "quotient")}
        tree_openings = {name: (rows[l], paths[l])
                         for name, (rows, paths) in tree_rows.items()}
        proofs.append(pv.Proof(data_roots[l], adv_roots[l], ext_roots[l],
                               q_roots[l], sent, fproofs[l], tree_openings,
                               dict(timings)))
    return proofs
