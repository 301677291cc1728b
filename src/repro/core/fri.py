"""FRI low-degree argument over Fp4 codewords (replaces the paper's KZG —
DESIGN.md §2).

Codewords live on a multiplicative coset ``shift * H_N`` in *natural* order,
so the fold pairs are (i, i + N/2):  -x_i = x_{i+N/2}.

    fold(f)[i] = (f(x) + f(-x))/2 + beta * (f(x) - f(-x)) / (2 x)

Each committed layer stores leaf i = concat(f[i], f[i + N/2]) (8 lanes), so a
single opening feeds one fold step. The final (small) codeword is sent in
full; the verifier interpolates it and checks the degree bound.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from . import field as F
from . import merkle
from . import poly
from .transcript import Transcript

_U32 = jnp.uint32


@dataclass(frozen=True)
class FriConfig:
    blowup: int = 4          # LDE rate 1/blowup
    n_queries: int = 32
    final_size: int = 32     # stop folding at this codeword length
    shift: int = poly.COSET_SHIFT


@dataclass
class FriProof:
    layer_roots: list          # np (8,) per committed layer
    final_codeword: np.ndarray  # (final_size, 4)
    query_indices: np.ndarray   # (q,) indices into [0, N/2)
    layer_openings: list       # per layer: (rows (q,8), paths (q,depth,8))

    def size_fields(self) -> int:
        """Proof size in field elements (for the paper's proof-size metric)."""
        total = len(self.layer_roots) * 8 + self.final_codeword.size
        for rows, paths in self.layer_openings:
            total += int(np.prod(rows.shape)) + int(np.prod(paths.shape))
        return total

    # -- canonical serialization (repro.core.wire; never pickle) -------------
    def to_bytes(self) -> bytes:
        from . import wire
        return wire.encode_fri_proof(self)

    @staticmethod
    def from_bytes(raw: bytes) -> "FriProof":
        """Decode canonical FRI-proof bytes; raises ``wire.WireFormatError``
        on any malformed input."""
        from . import wire
        return wire.decode_fri_proof(raw)


def _fold(codeword: jnp.ndarray, beta: jnp.ndarray, shift: int) -> jnp.ndarray:
    """One FRI fold of an Fp4 codeword (N,4) on coset shift*H_N -> (N/2,4)."""
    n = codeword.shape[0]
    half = n // 2
    lo, hi = codeword[:half], codeword[half:]
    inv2 = pow(2, F.P - 2, F.P)
    # x_i^{-1} for i < half on the coset
    inv_pts = poly.domain_points(n, 1)
    inv_pts = F.finv(F.fmul(inv_pts[:half], _U32(shift)))
    even = F.emul_fp(F.eadd(lo, hi), jnp.full((half,), inv2, _U32))
    odd = F.emul_fp(F.esub(lo, hi), F.fmul(inv_pts, _U32(inv2)))
    return F.eadd(even, F.emul(jnp.broadcast_to(beta, odd.shape), odd))


def _layer_leaves(codeword: jnp.ndarray) -> jnp.ndarray:
    n = codeword.shape[0]
    return jnp.concatenate([codeword[: n // 2], codeword[n // 2:]], axis=-1)  # (N/2, 8)


def fri_prove(codeword: jnp.ndarray, tx: Transcript, cfg: FriConfig) -> FriProof:
    """codeword: (N, 4) Fp4 evals on cfg.shift * H_N."""
    n = codeword.shape[0]
    trees = []
    roots = []
    words = []
    shift = cfg.shift
    cur = codeword
    while cur.shape[0] > cfg.final_size:
        tree = merkle.commit(_layer_leaves(cur))
        trees.append(tree)
        words.append(cur)
        root = np.asarray(tree.root)
        roots.append(root)
        tx.absorb_digest(root)
        beta = jnp.asarray(tx.challenge_ext())
        cur = _fold(cur, beta, shift)
        shift = shift * shift % F.P
    final_codeword = np.asarray(cur)
    tx.absorb(final_codeword.reshape(-1))

    q_idx = tx.challenge_indices(cfg.n_queries, n // 2)
    openings = []
    idx = jnp.asarray(q_idx)
    for tree, word in zip(trees, words):
        half = word.shape[0] // 2
        idx = idx % half
        rows, paths = merkle.open_at(tree, idx)
        openings.append((np.asarray(rows), np.asarray(paths)))
    return FriProof(roots, final_codeword, q_idx, openings)


# ---------------------------------------------------------------------------
# lane-batched proving (repro.core.prover_batch): L same-length codewords
# fold/commit/open in lockstep with per-lane challenges.  Lane l's FriProof
# is bit-identical to ``fri_prove(codewords[l], solo_tx, cfg)`` when the
# transcripts agree — every op below is the solo op with a leading lane dim.
# ---------------------------------------------------------------------------
def _fold_lanes(codewords: jnp.ndarray, beta: jnp.ndarray,
                shift: int) -> jnp.ndarray:
    """One fold of (L, N, 4) codewords with per-lane betas (L, 4)."""
    n = codewords.shape[1]
    half = n // 2
    lo, hi = codewords[:, :half], codewords[:, half:]
    inv2 = pow(2, F.P - 2, F.P)
    inv_pts = poly.domain_points(n, 1)
    inv_pts = F.finv(F.fmul(inv_pts[:half], _U32(shift)))
    even = F.emul_fp(F.eadd(lo, hi), jnp.full((half,), inv2, _U32))
    odd = F.emul_fp(F.esub(lo, hi), F.fmul(inv_pts, _U32(inv2)))
    return F.eadd(even, F.emul(beta[:, None, :], odd))


def fri_prove_lanes(codewords: jnp.ndarray, btx, cfg: FriConfig) -> list:
    """codewords: (L, N, 4) on cfg.shift * H_N; ``btx`` a
    :class:`~repro.core.transcript.BatchedTranscript` with L lanes.
    Returns one :class:`FriProof` per lane."""
    lanes, n = codewords.shape[0], codewords.shape[1]
    trees = []
    roots = []                 # per committed layer: (L, 8) np
    words = []
    shift = cfg.shift
    cur = codewords
    while cur.shape[1] > cfg.final_size:
        half = cur.shape[1] // 2
        leaves = jnp.concatenate([cur[:, :half], cur[:, half:]], axis=-1)
        tree = merkle.commit_lanes(leaves)
        trees.append(tree)
        words.append(cur)
        layer_roots = np.asarray(tree.roots)
        roots.append(layer_roots)
        btx.absorb_digest(layer_roots)
        beta = jnp.asarray(btx.challenge_ext())         # (L, 4)
        cur = _fold_lanes(cur, beta, shift)
        shift = shift * shift % F.P
    final_codewords = np.asarray(cur)                   # (L, final, 4)
    btx.absorb(final_codewords.reshape(lanes, -1))

    q_idx = btx.challenge_indices(cfg.n_queries, n // 2)   # (L, q)
    openings = []              # per layer: (rows (L,q,8), paths (L,q,d,8))
    idx = jnp.asarray(q_idx)
    for tree, word in zip(trees, words):
        half = word.shape[1] // 2
        idx = idx % half
        rows, paths = merkle.open_lanes(tree, idx)
        openings.append((np.asarray(rows), np.asarray(paths)))
    return [
        FriProof([r[l] for r in roots], final_codewords[l], q_idx[l],
                 [(rows[l], paths[l]) for rows, paths in openings])
        for l in range(lanes)]


def fri_verify(proof: FriProof, tx: Transcript, cfg: FriConfig, n: int):
    """Replay the transcript and check folds/paths/degree.

    Returns (ok, query_indices (q,), layer0_lo (q,4), layer0_hi (q,4)) where
    layer0 values are the opened evaluations of the first codeword at global
    indices ``q_idx`` and ``q_idx + n/2`` — the caller must check them against
    the DEEP composition recomputed from the trace openings.
    """
    betas = []
    for root in proof.layer_roots:
        tx.absorb_digest(root)
        betas.append(jnp.asarray(tx.challenge_ext()))
    tx.absorb(proof.final_codeword.reshape(-1))
    q_idx = tx.challenge_indices(cfg.n_queries, n // 2)
    if not np.array_equal(q_idx, proof.query_indices):
        return False, q_idx, None, None

    ok = True
    shift = cfg.shift
    size = n
    idx = jnp.asarray(q_idx)
    prev_fold = None          # expected folded value at current layer index
    layer0 = None
    inv2 = pow(2, F.P - 2, F.P)
    for li, (root, (rows, paths)) in enumerate(zip(proof.layer_roots, proof.layer_openings)):
        half = size // 2
        idx = idx % half
        rows = jnp.asarray(rows)
        ok &= bool(merkle.verify_open(jnp.asarray(root), idx, rows, jnp.asarray(paths)))
        lo, hi = rows[:, :4], rows[:, 4:]
        if li == 0:
            layer0 = (np.asarray(lo), np.asarray(hi), np.asarray(idx))
        if prev_fold is not None:
            # the folded value from the previous layer must appear at slot
            # lo/hi depending on whether prev index < half
            pick_hi = (prev_idx >= half)[:, None]
            expect = jnp.where(pick_hi, hi, lo)
            ok &= bool(jnp.all(expect == prev_fold))
        # fold to next layer
        pts = poly.domain_points(size, 1)
        x_inv = F.finv(F.fmul(pts[idx], _U32(shift)))
        even = F.emul_fp(F.eadd(lo, hi), jnp.full((len(q_idx),), inv2, _U32))
        odd = F.emul_fp(F.esub(lo, hi), F.fmul(x_inv, _U32(inv2)))
        prev_fold = F.eadd(even, F.emul(jnp.broadcast_to(betas[li], odd.shape), odd))
        prev_idx = idx
        shift = shift * shift % F.P
        size = half
    # final layer: folded values must match the plain codeword
    final = jnp.asarray(proof.final_codeword)
    if prev_fold is not None:
        ok &= bool(jnp.all(final[prev_idx % size] == prev_fold))
    # degree check on the final codeword: interpolate on coset shift*H_size
    deg_bound = max(size // cfg.blowup, 1)
    w = F.root_of_unity(size)
    w_inv = pow(w, F.P - 2, F.P)
    s_inv = pow(shift, F.P - 2, F.P)
    n_inv = pow(size, F.P - 2, F.P)
    ij = np.outer(np.arange(size), np.arange(size))
    Wm = jnp.asarray(
        np.vectorize(lambda e: pow(w_inv, int(e), F.P))(ij).astype(np.uint32))
    # c_j = n^{-1} s^{-j} sum_i v_i w^{-ij}
    prod = F.fmul(final[:, None, :], Wm[:, :, None])     # (i, j, 4)
    sums = F.mod_p(jnp.sum(prod.astype(jnp.uint64), axis=0))
    sj = np.array([pow(s_inv, j, F.P) * n_inv % F.P for j in range(size)], np.uint32)
    coeffs = F.fmul(sums.astype(_U32), jnp.asarray(sj)[:, None])
    ok &= bool(jnp.all(coeffs[deg_bound:] == 0))
    return ok, np.asarray(q_idx), layer0, None
