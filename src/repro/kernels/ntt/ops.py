"""jit'd NTT built from the Pallas kernels, plus the shape adapter.

:func:`ntt` flattens leading dims and zero-pads the batch — transform
rows are independent, so padding rows cannot perturb real ones — puts
each row in bit-reversed order, runs every stage that fits in a
``LOCAL``-lane row in one :func:`ntt.local_stages` call and each longer
stage as one :func:`ntt.stage` call, then slices the padding back off.
Rows shorter than 128 lanes are packed several to a kernel row (a stage
never pairs positions from different transforms).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import field as F
from ...core import poly
from ..fieldops.fieldops import mulmod_limb
from . import ntt as K

_U32 = jnp.uint32
BATCH_TILE = 8     # kernel rows per block (sublanes)
LOCAL = 2048       # lanes per row of the fused early-stage kernel
ROW_TILE = 64      # 128-lane rows per late-stage slab (8192 positions)


@functools.lru_cache(maxsize=None)
def _lane_twiddles(n: int, w: int, inverse: bool) -> np.ndarray:
    """(stages, 1, w): entry [k, 0, i] = stage-2^k twiddle of lane i."""
    tables = poly._stage_twiddles(n, inverse)
    lane = np.arange(w)
    stages = min(n, w).bit_length() - 1
    out = np.zeros((stages, 1, w), np.uint32)
    for k in range(stages):
        out[k, 0] = tables[k][lane % (1 << k)]
    return out


def _bitrev(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-reverse the last axis of (b, n) as two short gathers and one
    transpose: with n = a*c, position h*c + l goes to rev(l)*a + rev(h).
    (A single length-n lane gather takes ~16x the array in TPU scratch.)"""
    b, n = x.shape
    a = 1 << ((n.bit_length() - 1) // 2)
    c = n // a
    y = x.reshape(b, a, c)
    y = jnp.take(y, jnp.asarray(poly._bitrev_perm(a), jnp.int32), axis=1)
    y = jnp.take(y, jnp.asarray(poly._bitrev_perm(c), jnp.int32), axis=2)
    return y.transpose(0, 2, 1).reshape(b, n)


def ntt(x: jnp.ndarray, inverse: bool = False, interpret: bool = True):
    """Backend entry point: (..., n) NTT via the Pallas kernels.

    The batch is padded here, outside the jit, so every batch count that
    pads to the same size shares one compiled transform."""
    x = jnp.asarray(x)
    shape = x.shape
    n = shape[-1]
    x = x.reshape(-1, n).astype(_U32)
    b = x.shape[0]
    if b == 0 or n == 1:
        return x.reshape(shape)
    pad = (-b) % (BATCH_TILE * max(1, _width(n) // n))
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return _ntt_padded(x, inverse, interpret)[:b].reshape(shape)


def _width(n: int) -> int:
    """Lanes per row of the fused early-stage kernel."""
    return max(K.LANES, min(n, LOCAL))


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def _ntt_padded(x: jnp.ndarray, inverse: bool, interpret: bool):
    """(b, n) NTT, b a multiple of the kernels' row tiles."""
    b, n = x.shape
    w = _width(n)
    x = _bitrev(x)
    tw_lanes = jnp.asarray(_lane_twiddles(n, w, inverse))
    x = K.local_stages(x.reshape(b * n // w, w), tw_lanes, BATCH_TILE,
                       interpret=interpret).reshape(b, n)
    tables = poly._stage_twiddles(n, inverse)
    for k in range(tw_lanes.shape[0], len(tables)):
        x = K.stage(x, jnp.asarray(tables[k]), 1 << k, BATCH_TILE, ROW_TILE,
                    interpret=interpret)
    if inverse:
        x = mulmod_limb(x, jnp.full_like(x, pow(n, F.P - 2, F.P)))
    return x
