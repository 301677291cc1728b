"""DEEP-ALI + FRI prover for PLONKish circuits (replaces Halo2/KZG backend).

Pipeline (paper §III-B, adapted per DESIGN.md §2):
  witness finalize -> commit phase-1 advice -> draw α,β (Eq. (1) tuple
  compression + bus denominators) -> build phase-2 ext columns (logUp running
  sums / Eq. (2) running products) -> commit -> combine constraints -> quotient
  -> OOD openings at z -> DEEP composition -> FRI -> query openings.
"""
from __future__ import annotations

from dataclasses import dataclass, field as dc_field

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import backend as be
from . import field as F
from . import fri as fri_mod
from . import merkle
from . import poly
from .plonkish import (ADVICE, DATA, FIXED, INSTANCE, BaseOps, Circuit, Const,
                       ExtOps, eval_expr)
from .transcript import Transcript

_U32 = jnp.uint32
_U64 = jnp.uint64


@dataclass(frozen=True)
class ProverConfig:
    blowup: int = 4
    n_queries: int = 32
    fri_final_size: int = 32
    shift: int = poly.COSET_SHIFT
    # compute backend for keygen/prove (repro.core.backend); None = ambient
    # selection (ZKGRAPH_BACKEND env var, default "ref").  compare=False:
    # backends are bit-identical, so which one ran is an execution detail —
    # never serialized, never part of cfg equality or proof acceptance.
    backend: str = dc_field(default=None, compare=False)

    def fri(self) -> fri_mod.FriConfig:
        return fri_mod.FriConfig(self.blowup, self.n_queries,
                                 self.fri_final_size, self.shift)


@dataclass
class Keys:
    """PK/VK: fixed-column coefficient/LDE caches (paper Table III keygen)."""
    circuit: Circuit
    cfg: ProverConfig
    fixed_coeffs: jnp.ndarray     # (n_fixed, N)
    fixed_lde: jnp.ndarray        # (n_fixed, N*blowup)
    backend: str = "ref"          # resolved compute backend keygen ran under


@dataclass
class Proof:
    data_root: np.ndarray
    advice_root: np.ndarray
    ext_root: np.ndarray
    quotient_root: np.ndarray
    openings: dict                 # (kind, idx, rot) -> np (4,) for committed kinds
    fri_proof: fri_mod.FriProof
    tree_openings: dict            # tree name -> (rows, paths) at [q, q+half]
    # seconds by phase, from the prover's spans (repro.obs); in memory
    # only, never serialized
    timings: dict = dc_field(default_factory=dict)

    def size_fields(self) -> int:
        total = 24 + self.fri_proof.size_fields()
        total += 4 * len(self.openings)
        for rows, paths in self.tree_openings.values():
            total += int(np.prod(rows.shape)) + int(np.prod(paths.shape))
        return total

    # -- canonical serialization (repro.core.wire; never pickle) -------------
    def to_bytes(self) -> bytes:
        from . import wire
        return wire.encode_proof(self)

    @staticmethod
    def from_bytes(raw: bytes) -> "Proof":
        """Decode canonical proof bytes; raises ``wire.WireFormatError`` on
        any malformed input."""
        from . import wire
        return wire.decode_proof(raw)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _ext_scale(base_vec: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """(N,) Fp x (4,) Fp4 -> (N, 4)."""
    return F.fmul(e[None, :], base_vec[:, None])


def _lde(cols: jnp.ndarray, blowup: int, shift: int) -> jnp.ndarray:
    if cols.shape[0] == 0:
        return jnp.zeros((0, cols.shape[1] * blowup), _U32)
    return poly.coset_lde(cols, blowup, shift)


def _lde_from_coeffs(coeffs: jnp.ndarray, blowup: int, shift: int) -> jnp.ndarray:
    n = coeffs.shape[-1]
    powers = np.ones(n, np.uint64)
    for i in range(1, n):
        powers[i] = powers[i - 1] * shift % F.P
    scaled = F.fmul(coeffs, jnp.asarray(powers.astype(np.uint32)))
    pad = [(0, 0)] * (coeffs.ndim - 1) + [(0, n * (blowup - 1))]
    return poly.ntt(jnp.pad(scaled, pad))


def _cumsum_mod(x: jnp.ndarray, axis=0) -> jnp.ndarray:
    return F.mod_p(jnp.cumsum(x.astype(_U64), axis=axis)).astype(_U32)


def opening_schedule(circuit: Circuit, blowup: int):
    """Deterministic list of (kind, index, rot) openings at z*w^rot.

    kinds: fixed/instance (verifier-computed), advice, ext (components),
    quotient (components). Every committed polynomial appears at least at
    rot 0 so the DEEP argument binds it.
    """
    rotset = circuit.rotation_set()
    sched = []
    for kind, count in ((FIXED, circuit.n_fixed), (INSTANCE, circuit.n_instance),
                        (DATA, circuit.n_data), (ADVICE, circuit.n_advice)):
        for i in range(count):
            rots = {r for (k, j, r) in rotset if k == kind and j == i} | {0}
            for r in sorted(rots):
                sched.append((kind, i, r))
    for c in range(circuit.n_ext * 4):
        for r in (0, 1):
            sched.append(("ext", c, r))
    for c in range(blowup * 4):
        sched.append(("quotient", c, 0))
    return sched


def auto_multiplicities(circuit: Circuit, data_np: np.ndarray,
                        advice_np: np.ndarray, instance_np: np.ndarray):
    """Fill auto-multiplicity advice columns for lookup buses (host-side).

    t-side counts land only on rows where the bus t_sel is active, and on the
    first selected occurrence of each distinct tuple.
    """
    n = circuit.n_rows

    def getter(kind, idx, rot):
        src = {FIXED: None, ADVICE: advice_np, INSTANCE: instance_np,
               DATA: data_np}[kind]
        col = circuit.fixed_cols[idx] if kind == FIXED else src[idx]
        return jnp.asarray(np.roll(col, -rot).astype(np.uint32))

    like = jnp.zeros(n, _U32)
    for bus in circuit.buses:
        if bus.auto_mult_col < 0:
            continue
        f_vals = np.stack([np.asarray(eval_expr(e, getter, BaseOps, like))
                           for e in bus.f_tuple], axis=1)
        t_vals = np.stack([np.asarray(eval_expr(e, getter, BaseOps, like))
                           for e in bus.t_tuple], axis=1)
        m_f = np.asarray(eval_expr(bus.m_f, getter, BaseOps, like), np.int64)
        t_sel = np.asarray(eval_expr(bus.t_sel, getter, BaseOps, like), np.int64)
        both = np.concatenate([t_vals, f_vals], axis=0)
        _, inv = np.unique(both, axis=0, return_inverse=True)
        code_t, code_f = inv[:n], inv[n:]
        # exact int64 accumulation: float-weighted bincount would round
        # above 2^53 and is banned from field code by the purity lint
        counts = np.zeros(int(inv.max()) + 1, np.int64)
        np.add.at(counts, code_f, m_f)
        sel_rows = np.nonzero(t_sel != 0)[0]
        u_t, first_sel = np.unique(code_t[sel_rows], return_index=True)
        m_t = np.zeros(n, np.int64)
        m_t[sel_rows[first_sel]] = counts[u_t]
        advice_np[bus.auto_mult_col] = (m_t % F.P).astype(np.uint32)


# ---------------------------------------------------------------------------
# keygen
# ---------------------------------------------------------------------------
def keygen(circuit: Circuit, cfg: ProverConfig = ProverConfig()) -> Keys:
    with be.use(cfg.backend) as backend:
        circuit.assign_ext_cols()
        if circuit.gps and not any(n == "__row0" for n in circuit.fixed_names):
            onehot = np.zeros(circuit.n_rows, np.uint32)
            onehot[0] = 1
            circuit.add_fixed("__row0", onehot)
        fixed = jnp.asarray(np.stack(circuit.fixed_cols)
                            if circuit.fixed_cols else np.zeros((0, circuit.n_rows), np.uint32))
        coeffs = poly.intt(fixed) if circuit.n_fixed else fixed
        lde = _lde(fixed, cfg.blowup, cfg.shift)
        return Keys(circuit, cfg, coeffs, lde, backend.name)


def _row0_col(circuit: Circuit):
    from .plonkish import Col
    return Col(FIXED, circuit.fixed_names.index("__row0"))


# ---------------------------------------------------------------------------
# phase-2 ext column construction
# ---------------------------------------------------------------------------
def build_ext_columns(circuit: Circuit, getter_n, like_n, alpha, beta):
    """Returns (n_ext, N, 4) ext columns: bus running sums then GP products."""
    from .plonkish import compress_tuple
    n = circuit.n_rows
    cols = []
    for bus in circuit.buses:
        f_vals = [eval_expr(e, getter_n, BaseOps, like_n) for e in bus.f_tuple]
        t_vals = [eval_expr(e, getter_n, BaseOps, like_n) for e in bus.t_tuple]
        m_f = eval_expr(bus.m_f, getter_n, BaseOps, like_n)
        m_t = eval_expr(bus.m_t * bus.t_sel, getter_n, BaseOps, like_n)
        d_f = F.eadd(jnp.broadcast_to(beta, (n, 4)), compress_tuple(f_vals, alpha))
        d_t = F.eadd(jnp.broadcast_to(beta, (n, 4)), compress_tuple(t_vals, alpha))
        # m_f/d_f - m_t/d_t = (m_f*d_t - m_t*d_f) / (d_f*d_t): one batched
        # inversion instead of two (EXPERIMENTS.md §Perf iteration 4)
        num = F.esub(F.fmul(d_t, m_f[:, None]), F.fmul(d_f, m_t[:, None]))
        inc = F.emul(num, F.ebatch_inv(F.emul(d_f, d_t)))
        h = _cumsum_mod(inc, axis=0)
        h = jnp.concatenate([jnp.zeros((1, 4), _U32), h[:-1]], axis=0)
        cols.append(h)
    for gp in circuit.gps:
        c1 = [eval_expr(e, getter_n, BaseOps, like_n) for e in gp.c1_tuple]
        c2 = [eval_expr(e, getter_n, BaseOps, like_n) for e in gp.c2_tuple]
        s1 = eval_expr(gp.sel1, getter_n, BaseOps, like_n)
        s2 = eval_expr(gp.sel2, getter_n, BaseOps, like_n)
        one = jnp.zeros((n, 4), _U32).at[:, 0].set(1)
        d1 = F.eadd(jnp.broadcast_to(beta, (n, 4)), compress_tuple(c1, alpha))
        d2 = F.eadd(jnp.broadcast_to(beta, (n, 4)), compress_tuple(c2, alpha))
        not_s1 = F.fsub(jnp.full_like(s1, 1), s1)
        not_s2 = F.fsub(jnp.full_like(s2, 1), s2)
        f1 = F.eadd(F.fmul(d1, s1[:, None]), F.fmul(one, not_s1[:, None]))
        f2 = F.eadd(F.fmul(d2, s2[:, None]), F.fmul(one, not_s2[:, None]))
        ratio = F.emul(f1, F.ebatch_inv(f2))
        # Eq. (2) exclusive running product: Z[0]=1, Z[i]=prod_{j<i} —
        # dispatched (ref: associative scan; pallas: blocked-scan kernel)
        z = be.active().grand_product_ext(ratio)
        cols.append(z)
    if not cols:
        return jnp.zeros((0, n, 4), _U32)
    return jnp.stack(cols)


# ---------------------------------------------------------------------------
# constraint evaluation (shared shape between LDE-domain and OOD-point)
# ---------------------------------------------------------------------------
def combine_constraints(circuit: Circuit, base_getter, ext_getter, alpha, beta,
                        alpha_c, like_base, ops, ext_of_base, row0_val):
    """Evaluate sum_i alpha_c^i * constraint_i.

    ``base_getter``: base-column access returning ops-domain values.
    ``ext_getter(col, rot)``: ext helper column value (always Fp4-shaped).
    ``ext_of_base(v)``: lift a base-domain value into the ext accumulator space.
    ``row0_val``: evaluation of the __row0 one-hot fixed column (or None).
    Returns the combined accumulator (ext space).
    """
    acc = None
    a_pow = None

    def add_term(val_ext):
        nonlocal acc, a_pow
        if acc is None:
            acc = val_ext
            a_pow = alpha_c
        else:
            acc = F.eadd(acc, F.emul(jnp.broadcast_to(a_pow, val_ext.shape), val_ext))
            a_pow = F.emul(a_pow, alpha_c)

    for _, gate in circuit.gates:
        v = eval_expr(gate, base_getter, ops, like_base)
        add_term(ext_of_base(v))

    def compress(exprs):
        vals = [eval_expr(e, base_getter, ops, like_base) for e in exprs]
        out = ext_of_base(vals[0])
        apow = alpha
        for v in vals[1:]:
            out = F.eadd(out, F.emul(jnp.broadcast_to(apow, out.shape), ext_of_base(v)))
            apow = F.emul(apow, alpha)
        return out

    def mul_base(val_ext, base_v):
        return F.emul(val_ext, ext_of_base(base_v))

    for bus in circuit.buses:
        d_f = F.eadd(jnp.broadcast_to(beta, compress(bus.f_tuple).shape),
                     compress(bus.f_tuple))
        d_t = F.eadd(jnp.broadcast_to(beta, d_f.shape), compress(bus.t_tuple))
        h = ext_getter(bus.ext_col, 0)
        h1 = ext_getter(bus.ext_col, 1)
        m_f = eval_expr(bus.m_f, base_getter, ops, like_base)
        m_t = eval_expr(bus.m_t * bus.t_sel, base_getter, ops, like_base)
        term = F.emul(F.esub(h1, h), F.emul(d_f, d_t))
        term = F.esub(term, mul_base(d_t, m_f))
        term = F.eadd(term, mul_base(d_f, m_t))
        add_term(term)
    for gp in circuit.gps:
        d1 = F.eadd(jnp.broadcast_to(beta, compress(gp.c1_tuple).shape),
                    compress(gp.c1_tuple))
        d2 = F.eadd(jnp.broadcast_to(beta, d1.shape), compress(gp.c2_tuple))
        s1 = eval_expr(gp.sel1, base_getter, ops, like_base)
        s2 = eval_expr(gp.sel2, base_getter, ops, like_base)
        one_b = ops.const(1, like_base)
        f1 = F.eadd(mul_base(d1, s1), ext_of_base(ops.sub(one_b, s1)))
        f2 = F.eadd(mul_base(d2, s2), ext_of_base(ops.sub(one_b, s2)))
        z = ext_getter(gp.ext_col, 0)
        z1 = ext_getter(gp.ext_col, 1)
        add_term(F.esub(F.emul(z1, f2), F.emul(z, f1)))
        # boundary Z[row0] = 1
        one_e = jnp.zeros(z.shape, _U32).at[..., 0].set(1)
        add_term(F.emul(ext_of_base(row0_val), F.esub(z, one_e)))
    if acc is None:
        like = ext_of_base(ops.const(0, like_base))
        acc = jnp.zeros(like.shape, _U32)
    return acc


# ---------------------------------------------------------------------------
# prove
# ---------------------------------------------------------------------------
def prove(keys: Keys, advice_np: np.ndarray, instance_np: np.ndarray,
          data_np: np.ndarray = None, label: str = "zkgraph") -> Proof:
    """Prove under the backend that produced these Keys (``keys.backend``,
    resolved at keygen time) — PK/LDE buffers and the proving run never
    cross backends.  Proof bytes are bit-identical across backends —
    Fiat–Shamir soundness depends on it, and the suite asserts it — so the
    backend choice is pure execution policy."""
    with be.use(keys.backend):
        return _prove_impl(keys, advice_np, instance_np, data_np, label)


def _prove_impl(keys: Keys, advice_np: np.ndarray, instance_np: np.ndarray,
                data_np: np.ndarray = None, label: str = "zkgraph") -> Proof:
    circuit, cfg = keys.circuit, keys.cfg
    n, B = circuit.n_rows, cfg.blowup
    nl = n * B
    phase = obs.Phases("zkg.prove", lanes=1, circuit=circuit.name)

    with phase("commit_advice") as sp:
        if data_np is None:
            data_np = np.zeros((0, n), np.uint32)
        auto_multiplicities(circuit, data_np, advice_np, instance_np)
        advice = jnp.asarray(advice_np.astype(np.uint32))
        data = jnp.asarray(data_np.astype(np.uint32)) if circuit.n_data \
            else jnp.zeros((0, n), _U32)
        inst = jnp.asarray(instance_np.astype(np.uint32)) if circuit.n_instance \
            else jnp.zeros((0, n), _U32)

        tx = Transcript(label)
        tx.absorb(circuit.digest_seed())
        if circuit.n_instance:
            # bind public I/O by a Merkle root (one digest, not O(N) sponge blocks)
            tx.absorb_digest(np.asarray(merkle.commit(inst.T).root))

        # --- phase 0: commit the dataset (the declared-DB binding) --------------
        data_coeffs = poly.intt(data) if circuit.n_data else data
        data_lde = _lde(data, B, cfg.shift)
        data_tree = merkle.commit(data_lde.T) if circuit.n_data else None
        data_root = np.asarray(data_tree.root) if data_tree else np.zeros(8, np.uint32)
        tx.absorb_digest(data_root)

        # --- phase 1: commit advice -------------------------------------------
        adv_coeffs = poly.intt(advice) if circuit.n_advice else advice
        adv_lde = _lde(advice, B, cfg.shift)
        adv_tree = merkle.commit(adv_lde.T) if circuit.n_advice else None
        adv_root = np.asarray(adv_tree.root) if adv_tree else np.zeros(8, np.uint32)
        tx.absorb_digest(adv_root)
        sp.sync(data_coeffs, adv_coeffs, data_lde, adv_lde)

    alpha = jnp.asarray(tx.challenge_ext())
    beta = jnp.asarray(tx.challenge_ext())

    # --- phase 2: ext columns ----------------------------------------------
    with phase("phase2_ext") as sp:
        fixed_n = jnp.asarray(np.stack(circuit.fixed_cols)
                              if circuit.fixed_cols else np.zeros((0, n), np.uint32))

        def getter_n(kind, idx, rot):
            src = {FIXED: fixed_n, ADVICE: advice, INSTANCE: inst, DATA: data}[kind]
            return jnp.roll(src[idx], -rot)

        like_n = jnp.zeros(n, _U32)
        ext_cols = build_ext_columns(circuit, getter_n, like_n, alpha, beta)
        n_ext = circuit.n_ext
        ext_base = ext_cols.transpose(0, 2, 1).reshape(n_ext * 4, n) if n_ext \
            else jnp.zeros((0, n), _U32)
        ext_coeffs = poly.intt(ext_base) if n_ext else ext_base
        ext_lde = _lde(ext_base, B, cfg.shift)
        ext_tree = merkle.commit(ext_lde.T) if n_ext else None
        ext_root = np.asarray(ext_tree.root) if ext_tree else np.zeros(8, np.uint32)
        tx.absorb_digest(ext_root)
        sp.sync(ext_coeffs, ext_lde)

    alpha_c = jnp.asarray(tx.challenge_ext())

    # --- quotient -----------------------------------------------------------
    with phase("quotient") as sp:
        fixed_lde, inst_lde = keys.fixed_lde, _lde(inst, B, cfg.shift)

        def getter_lde(kind, idx, rot):
            src = {FIXED: fixed_lde, ADVICE: adv_lde, INSTANCE: inst_lde,
                   DATA: data_lde}[kind]
            return jnp.roll(src[idx], -B * rot)

        def ext_getter_lde(col, rot):
            comps = [jnp.roll(ext_lde[col * 4 + c], -B * rot) for c in range(4)]
            return jnp.stack(comps, axis=-1)

        like_lde = jnp.zeros(nl, _U32)
        row0_lde = (getter_lde(FIXED, circuit.fixed_names.index("__row0"), 0)
                    if circuit.gps else like_lde)

        def ext_of_base_lde(v):
            z = jnp.zeros(v.shape + (4,), _U32)
            return z.at[..., 0].set(v)

        c_lde = combine_constraints(circuit, getter_lde, ext_getter_lde, alpha, beta,
                                    alpha_c, like_lde, BaseOps, ext_of_base_lde,
                                    row0_lde)
        # Z_H(x_i) = x_i^N - 1 = shift^N * (w_nl^N)^i - 1: period-B sequence in i
        wn = F.root_of_unity(nl)
        ratio = pow(wn, n, F.P)
        vals = np.empty(B, np.uint64)
        acc = pow(cfg.shift, n, F.P)
        for i in range(B):
            vals[i] = (acc - 1) % F.P
            acc = acc * ratio % F.P
        zh = np.asarray([vals[i % B] for i in range(nl)], np.uint32)
        zh_inv = F.fbatch_inv(jnp.asarray(zh))
        q_evals = F.fmul(c_lde, zh_inv[:, None])
        q_coeffs = poly.coset_coeffs(q_evals.T, cfg.shift)    # (4, NL)
        q_segments = q_coeffs.reshape(4, B, n).transpose(1, 0, 2).reshape(B * 4, n)
        q_lde = _lde_from_coeffs(q_segments, B, cfg.shift)
        q_tree = merkle.commit(q_lde.T)
        q_root = np.asarray(q_tree.root)
        tx.absorb_digest(q_root)
        sp.sync(q_segments, q_lde)

    # --- OOD openings --------------------------------------------------------
    with phase("ood_openings"):
        z = jnp.asarray(tx.challenge_ext())
        sched = opening_schedule(circuit, B)
        coeff_src = {FIXED: keys.fixed_coeffs, INSTANCE: poly.intt(inst) if
                     circuit.n_instance else inst, DATA: data_coeffs,
                     ADVICE: adv_coeffs, "ext": ext_coeffs, "quotient": q_segments}
        w_n = F.root_of_unity(n)
        openings = {}
        rots = sorted({r for (_, _, r) in sched})
        for rot in rots:
            zr = F.emul_fp(z, _U32(pow(w_n, rot, F.P)))
            for kind in (FIXED, INSTANCE, DATA, ADVICE, "ext", "quotient"):
                idxs = [i for (k, i, rr) in sched if k == kind and rr == rot]
                if not idxs:
                    continue
                # repeat a row up to a multiple of 8: one gather/eval shape
                rows = idxs + idxs[:1] * ((-len(idxs)) % 8)
                vals = poly.eval_at_ext(coeff_src[kind][jnp.asarray(rows)], zr)
                for i, v in zip(idxs, np.asarray(vals)):
                    openings[(kind, i, rot)] = v
        for key in sched:
            tx.absorb(openings[key])

    # --- DEEP composition -----------------------------------------------------
    with phase("deep") as sp:
        gamma = jnp.asarray(tx.challenge_ext())
        pts = F.fmul(poly.domain_points(nl), _U32(cfg.shift))   # (NL,)
        committed = [(k, i, r) for (k, i, r) in sched
                     if k in (DATA, ADVICE, "ext", "quotient")]
        lde_src = {DATA: data_lde, ADVICE: adv_lde, "ext": ext_lde,
                   "quotient": q_lde}
        deep = jnp.zeros((nl, 4), _U32)
        g_pow = gamma
        groups = {}
        for (k, i, r) in committed:
            groups.setdefault(r, []).append((k, i))
        for r in sorted(groups):
            zr = F.emul_fp(z, _U32(pow(w_n, r, F.P)))
            denom = F.esub(F.ext(pts), jnp.broadcast_to(zr, (nl, 4)))
            inv_d = F.ebatch_inv(denom)
            num = jnp.zeros((nl, 4), _U32)
            for (k, i) in groups[r]:
                p_lde = lde_src[k][i]
                diff = F.esub(F.ext(p_lde), jnp.broadcast_to(
                    jnp.asarray(openings[(k, i, r)]), (nl, 4)))
                num = F.eadd(num, F.emul(jnp.broadcast_to(g_pow, (nl, 4)), diff))
                g_pow = F.emul(g_pow, gamma)
            deep = F.eadd(deep, F.emul(num, inv_d))
        sp.sync(deep)

    # --- FRI -------------------------------------------------------------------
    with phase("fri"):
        fproof = fri_mod.fri_prove(deep, tx, cfg.fri())

    # --- query openings ---------------------------------------------------------
    with phase("query_openings"):
        q_idx = jnp.asarray(fproof.query_indices)
        idx_all = jnp.concatenate([q_idx, q_idx + nl // 2])
        tree_openings = {}
        for name, tree in (("data", data_tree), ("advice", adv_tree),
                           ("ext", ext_tree), ("quotient", q_tree)):
            if tree is None:
                tree_openings[name] = (np.zeros((len(idx_all), 0), np.uint32),
                                       np.zeros((len(idx_all), 0, 8), np.uint32))
            else:
                rows, paths = merkle.open_at(tree, idx_all)
                tree_openings[name] = (np.asarray(rows), np.asarray(paths))

    # strip fixed/instance openings from the transmitted proof (verifier
    # recomputes them); keep data/advice/ext/quotient
    sent = {k: v for k, v in openings.items()
            if k[0] in (DATA, ADVICE, "ext", "quotient")}
    return Proof(data_root, adv_root, ext_root, q_root, sent, fproof,
                 tree_openings, phase.timings())
