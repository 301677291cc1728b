"""Poseidon2-shaped permutation over BabyBear, batched as matmuls.

The TPU adaptation (DESIGN.md §2): the per-round linear layer of a width-16
permutation is a 16x16 matrix, so hashing a batch of states is one
(batch,16)x(16,16) modular matmul per round — an MXU-friendly schedule (the
Pallas kernel in ``repro.kernels.poseidon`` tiles exactly this). NOT a
security-audited parameter set (see DESIGN.md §8).

:func:`permute` dispatches through the active compute backend
(:mod:`repro.core.backend`): ``ref`` runs :func:`permute_ref` (the jnp path
below), the ``pallas*`` backends run the kernel.  All backends produce
bit-identical states, so everything above this primitive — the sponge, the
Merkle trees, the Fiat–Shamir transcript — is backend-independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import backend
from . import field as F

WIDTH = 16          # state lanes
RATE = 8            # sponge rate (lanes absorbed/squeezed per block)
DIGEST = 8          # digest lanes
FULL_ROUNDS = 8     # 4 at start + 4 at end
PARTIAL_ROUNDS = 14
SBOX_DEG = 7        # gcd(7, p-1) = 1 -> permutation

_U32 = jnp.uint32
_U64 = jnp.uint64


@functools.lru_cache(maxsize=None)
def _params():
    """(mds (16,16), round_constants (n_rounds,16)) as numpy uint32."""
    # DFT-style matrix: M[i][j] = w^(i*j) with w a 16th root of unity.
    # Vandermonde-of-roots => invertible; dense mixing; literally an NTT step.
    w = F.root_of_unity(WIDTH)
    mds = np.zeros((WIDTH, WIDTH), np.uint32)
    for i in range(WIDTH):
        for j in range(WIDTH):
            mds[i, j] = pow(w, i * j, F.P)
    rng = np.random.default_rng(20250713)
    n_rounds = FULL_ROUNDS + PARTIAL_ROUNDS
    rc = (rng.integers(0, F.P, size=(n_rounds, WIDTH), dtype=np.int64)).astype(np.uint32)
    return mds, rc


def _sbox(x):
    x2 = F.fmul(x, x)
    x4 = F.fmul(x2, x2)
    x6 = F.fmul(x4, x2)
    return F.fmul(x6, x)


def _matmul_mod(state, mat):
    """(batch..., 16) x (16, 16) modular matmul.  Sum of 16 products of
    values < 2^31: fits in uint64 (16 * 2^62 overflows — reduce per-term)."""
    prod = state[..., :, None].astype(_U64) * mat[None, :, :].astype(_U64)
    prod = F.mod_p(prod)                         # (batch..., 16, 16) < 2^31
    s = F.mod_p(jnp.sum(prod, axis=-2))          # 16 * 2^31 < 2^36: safe
    return s.astype(_U32)


def permute(state: jnp.ndarray) -> jnp.ndarray:
    """Apply the permutation to (..., 16) BabyBear states.

    Dispatches to the active compute backend; the backends are
    bit-identical, so callers never observe which one ran."""
    return backend.active().permute(state)


@jax.jit
def permute_ref(state: jnp.ndarray) -> jnp.ndarray:
    """The pure-jnp reference permutation (the ``ref`` backend, and the
    oracle the Pallas kernel is validated against).  The rounds run as
    ``fori_loop``s over the round-constant table, so each round kind is
    traced and compiled once."""
    mds, rc = _params()
    mds = jnp.asarray(mds)
    rc = jnp.asarray(rc)

    def full_round(r, state):
        return _matmul_mod(_sbox(F.fadd(state, rc[r])), mds)

    def partial_round(r, state):
        state = F.fadd(state, rc[r])
        state = state.at[..., 0].set(_sbox(state[..., 0]))
        return _matmul_mod(state, mds)

    half = FULL_ROUNDS // 2
    mid = half + PARTIAL_ROUNDS
    state = jax.lax.fori_loop(0, half, full_round, state.astype(_U32))
    state = jax.lax.fori_loop(half, mid, partial_round, state)
    return jax.lax.fori_loop(mid, mid + half, full_round, state)


def compress(left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    """2-to-1 compression for Merkle: (..., 8),(..., 8) -> (..., 8)."""
    state = jnp.concatenate([left, right], axis=-1)
    return permute(state)[..., :DIGEST]


def hash_bytes(data: bytes) -> np.ndarray:
    """Sponge-hash a byte string -> (8,) uint32 BabyBear digest.

    The canonical byte-to-field packing (docs/protocol.md §6): 3 bytes per
    lane little-endian (values < 2^24 < P), zero-padded to a multiple of 3,
    with two leading lanes carrying the byte length — so inputs that differ
    only in trailing zero bytes cannot collide.  This is the digest primitive
    under ``transparency.manifest_digest`` and the transparency-log leaves.
    """
    data = bytes(data)
    n = len(data)
    pad = (-n) % 3
    chunks = np.frombuffer(data + b"\x00" * pad, np.uint8)
    chunks = chunks.reshape(-1, 3).astype(np.uint32)
    lanes = chunks[:, 0] | (chunks[:, 1] << 8) | (chunks[:, 2] << 16)
    head = np.array([n & 0xFFFFFF, n >> 24], np.uint32)
    row = jnp.asarray(np.concatenate([head, lanes])[None, :])
    return np.asarray(hash_rows(row)[0])


def hash_rows(rows: jnp.ndarray) -> jnp.ndarray:
    """Sponge-hash each row of (..., n, k) field elements -> (..., n, 8).

    k is padded to a multiple of RATE; absorb RATE lanes per permutation.
    """
    *batch, n, k = rows.shape
    pad = (-k) % RATE
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
        k += pad
    state = jnp.zeros(tuple(batch) + (n, WIDTH), _U32)
    # domain-separate by absorbed length
    state = state.at[..., WIDTH - 1].set(_U32(k % F.P))
    for blk in range(k // RATE):
        chunk = rows[..., blk * RATE:(blk + 1) * RATE]
        state = state.at[..., :RATE].set(F.fadd(state[..., :RATE], chunk))
        state = permute(state)
    return state[..., :DIGEST]
