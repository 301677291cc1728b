"""jit'd wrappers + shape adapters for the grand-product kernel.

The kernel wants coefficient planes of whole ``(block_rows, 128)`` blocks,
so both wrappers pad the sequence with the multiplicative identity — extra
trailing ones leave every real prefix product untouched — lay it out as
planes, and slice the padding back off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import grand_product as K

_U32 = jnp.uint32
BLOCK_ROWS = 64    # 128-lane rows per grid step (8192 elements)


def _layout(n: int) -> tuple:
    """(rows, block_rows) for a length-n sequence."""
    rows = -(-n // K.LANES)
    block = min(BLOCK_ROWS, max(8, 1 << (rows - 1).bit_length()))
    return -(-rows // block) * block, block


def _scan(x, ext: bool, interpret: bool):
    """x: (n, k) -> exclusive running products (n, k)."""
    n, k = x.shape
    rows, block = _layout(n)
    pad = rows * K.LANES - n
    if pad:
        one = jnp.zeros((pad, k), _U32).at[:, 0].set(1)
        x = jnp.concatenate([x, one], axis=0)
    planes = x.T.reshape(k, rows, K.LANES)
    out = K.exclusive_scan(planes, block, ext, interpret=interpret)
    return out.reshape(k, rows * K.LANES).T[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def grand_product(x, interpret: bool = True):
    """Exclusive running product of (n,) Fp scalars, any n >= 1."""
    return _scan(x.astype(_U32)[:, None], False, interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def grand_product_ext(x, interpret: bool = True):
    """Exclusive running product of (n, 4) Fp4 elements, any n >= 1."""
    return _scan(x.astype(_U32), True, interpret)
