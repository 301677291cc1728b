"""jit'd wrappers + shape adapters for the fieldops Pallas kernels.

Inputs of any shape are flattened and zero-padded up to a block multiple
(elementwise kernels: padding lanes are dead work, never observed), so a
prime-sized circuit row count no longer degenerates to a block-1 grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import pallas_call
from . import fieldops as K

_U32 = jnp.uint32


def _pick_block(n: int) -> int:
    for b in (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def _pad_flat(flat: jnp.ndarray) -> jnp.ndarray:
    """Pad a flat vector to a 256 multiple so _pick_block always finds a
    real block (one 256-lane block beats a grid of degenerate 1-blocks
    even for tiny inputs)."""
    pad = (-flat.shape[0]) % 256
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), _U32)])
    return flat


@functools.partial(jax.jit, static_argnames=("interpret",))
def mulmod(a: jnp.ndarray, b: jnp.ndarray, interpret: bool = True):
    """Elementwise modular multiply via the 16-bit-limb Pallas kernel.

    a, b: uint32 arrays of any (same) shape."""
    shape = a.shape
    n = a.size
    flat_a = _pad_flat(a.reshape(-1).astype(_U32))
    flat_b = _pad_flat(b.reshape(-1).astype(_U32))
    block = _pick_block(flat_a.shape[0])
    out = pallas_call(
        K._mulmod_kernel,
        name="fieldops_mulmod",
        grid=(flat_a.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))] * 2,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(flat_a.shape, _U32),
        interpret=interpret,
    )(flat_a, flat_b)
    return out[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_mul_add(a, b, c, interpret: bool = True):
    """(a*b + c) mod P — one kernel, one VMEM round-trip."""
    shape = a.shape
    n = a.size
    flat_a, flat_b, flat_c = (_pad_flat(x.reshape(-1).astype(_U32))
                              for x in (a, b, c))
    block = _pick_block(flat_a.shape[0])
    out = pallas_call(
        K._fma_kernel,
        name="fieldops_fma",
        grid=(flat_a.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))] * 3,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(flat_a.shape, _U32),
        interpret=interpret,
    )(flat_a, flat_b, flat_c)
    return out[:n].reshape(shape)
