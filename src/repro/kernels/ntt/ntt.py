"""Pallas NTT kernels: radix-2 DIT butterflies in two tilings.

TPU mapping.  The input is already in bit-reversed order, so stage ``m``
pairs positions ``i`` and ``i + m`` inside each ``2m``-long group.

* :func:`local_stages` runs every stage with ``2m <= w`` in one kernel.
  The codeword matrix is viewed as rows of ``w`` lanes (``w`` a multiple
  of 128), a ``(row_tile, w)`` block sits in VMEM, and each stage is a
  ``fori_loop`` step: multiply by the stage's lane twiddles, fetch the
  partner with a lane roll by ``m`` and pick the sum or the difference by
  bit ``m`` of the lane index.
* :func:`stage` runs one later stage (``m >= w``).  The ``(b, n)`` matrix is
  viewed as ``(b, n / 2m, 2, m / 128, 128)``; the even and odd halves are
  read as two ``(bt, tr, 128)`` slabs of the same array and the twiddles as
  the matching ``(tr, 128)`` slab, so a block is bounded whatever ``m`` is
  and the butterfly pair never sits on the sublane axis.  The last grid
  axis picks the half written (sum or difference).

Modular arithmetic is the 16-bit-limb ``uint32`` path of ``fieldops``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_call
from ..fieldops.fieldops import addmod, mulmod_limb, submod

_U32 = jnp.uint32
LANES = 128


def _local_kernel(x_ref, tw_ref, o_ref):
    """x_ref: (row_tile, w); tw_ref: (stages, 1, w) lane twiddles."""
    w = x_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)

    def butterfly(k, x):
        m = jnp.left_shift(1, k)
        t = mulmod_limb(x, jnp.broadcast_to(tw_ref[k], x.shape))
        t_up = pltpu.roll(t, w - m, 1)        # t[i + m]
        x_down = pltpu.roll(x, m, 1)          # x[i - m]
        return jnp.where((lane & m) != 0, submod(x_down, t), addmod(x, t_up))

    o_ref[...] = jax.lax.fori_loop(0, tw_ref.shape[0], butterfly, x_ref[...])


def local_stages(x: jnp.ndarray, tw_lanes: jnp.ndarray, row_tile: int,
                 interpret: bool = True) -> jnp.ndarray:
    """x: (rows, w), rows % row_tile == 0; tw_lanes: (stages, 1, w) with
    ``tw_lanes[k, 0, i]`` the stage-``2^k`` twiddle of lane ``i``."""
    rows, w = x.shape
    return pallas_call(
        _local_kernel,
        name="ntt_local",
        grid=(rows // row_tile,),
        in_specs=[pl.BlockSpec((row_tile, w), lambda i: (i, 0)),
                  pl.BlockSpec(tw_lanes.shape, lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((row_tile, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, _U32),
        interpret=interpret,
    )(x, tw_lanes)


def _stage_kernel(even_ref, odd_ref, tw_ref, o_ref):
    """even/odd: (bt, tr, 128) slabs; tw: (tr, 128)."""
    t = mulmod_limb(odd_ref[...], jnp.broadcast_to(tw_ref[...],
                                                   odd_ref.shape))
    e = even_ref[...]
    o_ref[...] = jnp.where(pl.program_id(3) == 0, addmod(e, t),
                           submod(e, t))


def stage(x: jnp.ndarray, twiddles: jnp.ndarray, m: int, batch_tile: int,
          row_tile: int, interpret: bool = True) -> jnp.ndarray:
    """One radix-2 DIT stage of half-length m (a multiple of 128).
    x: (b, n), b % batch_tile == 0; twiddles: (m,) stage table."""
    b, n = x.shape
    g, r = n // (2 * m), m // LANES
    tr = min(r, row_tile)
    x5 = x.reshape(b, g, 2, r, LANES)
    sq = pl.Squeezed()
    half = (batch_tile, sq, sq, tr, LANES)
    out = pallas_call(
        _stage_kernel,
        name="ntt_stage",
        grid=(b // batch_tile, g, r // tr, 2),
        in_specs=[pl.BlockSpec(half, lambda i, j, k, p: (i, j, 0, k, 0)),
                  pl.BlockSpec(half, lambda i, j, k, p: (i, j, 1, k, 0)),
                  pl.BlockSpec((tr, LANES), lambda i, j, k, p: (k, 0))],
        out_specs=pl.BlockSpec(half, lambda i, j, k, p: (i, j, p, k, 0)),
        out_shape=jax.ShapeDtypeStruct(x5.shape, _U32),
        interpret=interpret,
    )(x5, x5, twiddles.reshape(r, LANES))
    return out.reshape(b, n)
