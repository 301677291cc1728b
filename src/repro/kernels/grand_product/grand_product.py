"""Pallas kernel for the paper's Eq. (2) running-product accumulator.

One sequential pass over the grid.  Elements are laid out as ``k`` planes
of ``(rows, 128)`` — ``k = 1`` for base-field scalars, ``k = 4`` for the
coefficients of Fp4 elements — with position ``p`` at row ``p // 128``,
lane ``p % 128``.  Each grid step loads a ``(k, block_rows, 128)`` block
and computes, in VMEM:

1. the inclusive product along each row (log-step doubling with lane
   rolls), with the carry — the product of all earlier blocks, kept in a
   VMEM scratch across steps — folded into the first element,
2. the inclusive product of the row totals down the block (sublane rolls),
3. each row scaled by the rows before it, then moved one place on to
   make the product exclusive.

The modular multiply is the shared 16-bit-limb primitive (fieldops); the
Fp4 multiply (:func:`_emul_limb`) is the same schoolbook ``x^4 = W_EXT``
reduction as ``field.emul``.  Modular arithmetic is exact, so both give
the same canonical representatives bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_call
from ...core.field import W_EXT
from ..fieldops.fieldops import addmod, mulmod_limb

_U32 = jnp.uint32
LANES = 128


def _emul_limb(a, b):
    """Schoolbook Fp4 multiply (reduction x^4 = W_EXT) on 4-tuples of
    coefficient planes — mirrors ``field.emul`` term for term."""
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    m = mulmod_limb

    def mw(x):
        return mulmod_limb(jnp.full_like(x, W_EXT), x)

    c0 = addmod(m(a0, b0), mw(addmod(addmod(m(a1, b3), m(a2, b2)),
                                     m(a3, b1))))
    c1 = addmod(addmod(m(a0, b1), m(a1, b0)), mw(addmod(m(a2, b3),
                                                        m(a3, b2))))
    c2 = addmod(addmod(m(a0, b2), m(a1, b1)), addmod(m(a2, b0),
                                                     mw(m(a3, b3))))
    c3 = addmod(addmod(m(a0, b3), m(a1, b2)), addmod(m(a2, b1), m(a3, b0)))
    return (c0, c1, c2, c3)


def _mul_limb(a, b):
    return (mulmod_limb(a[0], b[0]),)


def _scan_kernel(x_ref, o_ref, carry_ref, *, mul):
    """x_ref/o_ref: (k, block_rows, 128); carry_ref: (k, 1, 128).

    One ``fori_loop`` holds the only multiply, so the (large) Fp4 multiply
    is traced and compiled once.  Its steps: fold the carry into the first
    element; 7 lane-doubling steps (inclusive product along each row, whose
    last lane is broadcast as the row total); log2(block_rows) sublane-
    doubling steps over the row totals; one step scaling each row by the
    product of the rows before it."""
    k, rows, _ = x_ref.shape
    one = (1,) + (0,) * (k - 1)          # the multiplicative identity
    lane_steps = LANES.bit_length() - 1
    row_steps = rows.bit_length() - 1

    @pl.when(pl.program_id(0) == 0)
    def _():
        for c in range(k):
            carry_ref[c] = jnp.full(carry_ref.shape[1:], one[c], _U32)

    shape = (rows, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    carry = tuple(jnp.broadcast_to(carry_ref[c], shape) for c in range(k))

    def ones():
        return tuple(jnp.full(shape, o, _U32) for o in one)

    def shifted(v, by, axis, idx):
        """v moved `by` places up `axis`, the vacated places set to one."""
        return tuple(jnp.where(idx < by, o, pltpu.roll(a, by, axis))
                     for a, o in zip(v, ones()))

    def pick(flag, a, b):
        return tuple(jnp.where(flag, u, v) for u, v in zip(a, b))

    def row_totals(v):
        return tuple(jnp.broadcast_to(a[:, LANES - 1:], shape) for a in v)

    def step(s, state):
        acc, tot = state
        on_lanes = s <= lane_steps
        on_rows = (s > lane_steps) & (s <= lane_steps + row_steps)
        by_lane = jnp.left_shift(1, jnp.clip(s - 1, 0, lane_steps - 1))
        by_row = jnp.left_shift(1, jnp.clip(s - 1 - lane_steps, 0,
                                            max(row_steps - 1, 0)))
        origin = (lane == 0) & (row == 0)
        b = pick(s == 0, pick(origin, carry, ones()),
                 pick(on_lanes, shifted(acc, by_lane, 1, lane),
                      pick(on_rows, shifted(tot, by_row, 0, row),
                           shifted(tot, 1, 0, row))))
        r = mul(pick(on_rows, tot, acc), b)
        new_acc = pick(on_rows, acc, r)
        new_tot = pick(on_rows, r, pick(on_lanes, row_totals(r), tot))
        return new_acc, new_tot

    x = tuple(x_ref[c] for c in range(k))
    incl, tot = jax.lax.fori_loop(0, lane_steps + row_steps + 2, step,
                                  (x, row_totals(x)))
    # exclusive = inclusive moved one place on; position (0, 0) is the carry
    first = pick(row == 0, carry, shifted(tot, 1, 0, row))
    for c in range(k):
        o_ref[c] = jnp.where(lane == 0, first[c], pltpu.roll(incl[c], 1, 1))
        carry_ref[c] = tot[c][rows - 1:]


def exclusive_scan(planes: jnp.ndarray, block_rows: int, ext: bool,
                   interpret: bool = True) -> jnp.ndarray:
    """Exclusive running product over planes (k, rows, 128) in row-major
    position order; rows % block_rows == 0, block_rows a power of two
    >= 8.  ``ext`` selects Fp4 (k = 4) over base-field (k = 1) products."""
    k, rows, _ = planes.shape
    block = (k, block_rows, LANES)
    return pallas_call(
        functools.partial(_scan_kernel, mul=_emul_limb if ext else _mul_limb),
        name="grand_product_ext" if ext else "grand_product",
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec(block, lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec(block, lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(planes.shape, _U32),
        scratch_shapes=[pltpu.VMEM((k, 1, LANES), _U32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(planes.astype(_U32))
