"""Shape adapter for the Poseidon-like permutation kernel.

The raw kernel (``poseidon.permute``) wants transposed ``(16, n)`` states
with ``n`` a multiple of its block.  Callers (Merkle level builds, sponge
absorbs, lane stacks) arrive with any leading batch shape and row count,
so :func:`permute` flattens and zero-pads the batch up to a size bucket —
padding rows are independent states, so they cannot perturb real lanes —
and slices the padding back off.  Buckets are powers of two of at least
one tile, so a Merkle build, which halves its batch at every level, meets
one compiled kernel per level and every batch below a tile shares one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import poseidon as K

_U32 = jnp.uint32
TILE = 256         # states per grid step (the lane axis of a kernel block)


def bucket(n: int) -> int:
    """Padded batch size the kernel is compiled for: a power of two >= TILE."""
    return max(TILE, 1 << (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _permute_bucket(flat, interpret: bool):
    return K.permute(flat.T, block=TILE, interpret=interpret).T


def permute(states, interpret: bool = True):
    """Backend entry point: (..., 16) states, any batch shape/count."""
    states = jnp.asarray(states).astype(_U32)
    shape = states.shape
    flat = states.reshape(-1, 16)
    n = flat.shape[0]
    if n == 0:
        return states
    size = bucket(n)
    if size != n:
        flat = jnp.pad(flat, ((0, size - n), (0, 0)))
    return _permute_bucket(flat, interpret)[:n].reshape(shape)
