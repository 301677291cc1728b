"""Pallas kernel for the Poseidon2-like permutation over BabyBear.

TPU mapping: states are laid out transposed, ``(16, n)`` — lane ``j`` of
every state sits on sublane row ``j`` and the batch runs along the 128-wide
lane axis, so each ``(16, block)`` tile fills whole vregs.  A round adds its
constants, applies the S-box (all rows, or row 0 in a partial round) and
multiplies by the 16x16 MDS matrix as 16 row-broadcast multiply-adds with
the 16-bit-limb modular multiply.  The 22 rounds run as three
``fori_loop``s over the round-constant table, so the round body is traced
and compiled once per round kind instead of 22 times.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import pallas_call
from ...core import hashing as H
from ..fieldops.fieldops import addmod, mulmod_limb

_U32 = jnp.uint32


def _sbox(x):
    x2 = mulmod_limb(x, x)
    x4 = mulmod_limb(x2, x2)
    return mulmod_limb(mulmod_limb(x4, x2), x)


def _matmul_mod(x, mds_ref):
    """out[j] = sum_i x[i] * mds[i][j]; x (16, bt), mds_ref[i] (16, 1)."""
    acc = None
    for i in range(H.WIDTH):
        term = mulmod_limb(jnp.broadcast_to(x[i:i + 1], x.shape),
                           jnp.broadcast_to(mds_ref[i], x.shape))
        acc = term if acc is None else addmod(acc, term)
    return acc


def _permute_kernel(x_ref, rc_ref, mds_ref, o_ref):
    row0 = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0) == 0

    def full_round(r, x):
        x = addmod(x, jnp.broadcast_to(rc_ref[r], x.shape))
        return _matmul_mod(_sbox(x), mds_ref)

    def partial_round(r, x):
        x = addmod(x, jnp.broadcast_to(rc_ref[r], x.shape))
        x = jnp.where(row0, jnp.broadcast_to(_sbox(x[0:1]), x.shape), x)
        return _matmul_mod(x, mds_ref)

    half = H.FULL_ROUNDS // 2
    mid = half + H.PARTIAL_ROUNDS
    x = jax.lax.fori_loop(0, half, full_round, x_ref[...])
    x = jax.lax.fori_loop(half, mid, partial_round, x)
    o_ref[...] = jax.lax.fori_loop(mid, mid + half, full_round, x)


def permute(states_t: jnp.ndarray, block: int,
            interpret: bool = True) -> jnp.ndarray:
    """states_t: (16, n) transposed states, n % block == 0 -> (16, n)."""
    n = states_t.shape[1]
    assert n % block == 0
    mds, rc = H._params()
    rc3 = jnp.asarray(rc[:, :, None])          # (rounds, 16, 1) columns
    mds3 = jnp.asarray(mds[:, :, None])        # row i of mds as a column
    return pallas_call(
        _permute_kernel,
        name="poseidon_permute",
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((H.WIDTH, block), lambda i: (0, i)),
            pl.BlockSpec(rc3.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(mds3.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((H.WIDTH, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((H.WIDTH, n), _U32),
        interpret=interpret,
    )(states_t.astype(_U32), rc3, mds3)
