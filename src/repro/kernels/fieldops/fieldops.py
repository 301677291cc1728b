"""Pallas TPU kernels for BabyBear modular arithmetic.

TPU adaptation core (DESIGN.md §2): TPUs have no 64-bit integer multiply, so
the 31-bit x 31-bit -> 62-bit product is assembled from 16-bit limbs on the
int32 VPU lanes, then reduced mod P with shift/add arithmetic exploiting
P = 2^31 - 2^27 + 1  =>  2^31 ≡ 2^27 - 1 (mod P).

The same ``mulmod_limb`` primitive is reused by the NTT and Poseidon kernels.
All kernels are validated in interpret mode against the uint64 oracle
(ref.py); the limb path itself uses only uint32 ops so it lowers to real TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.field import P

_U32 = jnp.uint32
MASK16 = 0xFFFF


def mulmod_limb(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a * b) mod P using only 32-bit integer ops (TPU-native path).

    Product decomposition with 16-bit limbs:
        a*b = p0 + (p1 << 16) + (p2 << 32)
      with p0 = al*bl, p1 = al*bh + ah*bl (may carry), p2 = ah*bh.
    Reduction uses 2^31 ≡ 2^27 - 1 and 2^32 ≡ 2^28 - 2 (mod P), folding the
    high parts down until the value fits below 2*P, then a final conditional
    subtract.
    """
    a = a.astype(_U32)
    b = b.astype(_U32)
    al, ah = a & MASK16, a >> 16
    bl, bh = b & MASK16, b >> 16
    p0 = al * bl                       # < 2^32
    mid1 = al * bh                     # < 2^31
    mid2 = ah * bl                     # < 2^31
    p2 = ah * bh                       # < 2^30 (a,b < 2^31 so ah < 2^15)

    # full 64-bit value = p0 + (mid1 + mid2) << 16 + p2 << 32, tracked as
    # lo (bits 0..31) and hi (bits 32..63) with manual carries.
    mid = mid1 + mid2                  # < 2^32, may wrap: detect carry
    mid_carry = (mid < mid1).astype(_U32)          # 1 if wrapped
    lo = p0 + (mid << 16)
    carry0 = (lo < p0).astype(_U32)
    hi = p2 + (mid >> 16) + (mid_carry << _U32(16)) + carry0

    # reduce: x = hi * 2^32 + lo;  2^32 ≡ 2^28 - 2 (mod P)
    # hi < 2^31 so hi * (2^28 - 2) needs another limb round: do it via
    # recursive single step using the same decomposition (hi < 2^31):
    def fold32(hi_part, lo_part):
        """(hi*2^32 + lo) mod-ish -> value < 2^33ish then final reduce."""
        # hi * 2^32 mod P = hi * (2^28 - 2) mod P; hi < 2^31 =>
        # hi*2^28 = (hi << 28) needs 59 bits: split hi into 16/15 limbs.
        hl, hh = hi_part & MASK16, hi_part >> 16
        # hi*(2^28-2) = hl*2^28 + hh*2^44 - 2*hi
        # 2^44 mod P: fold 2^44 = 2^32 * 2^12 ≡ (2^28-2)*2^12 = 2^40 - 2^13
        #   2^40 ≡ 2^8 * 2^32 ≡ 2^8 (2^28 - 2) = 2^36 - 2^9
        #   2^36 ≡ 2^4 (2^28 - 2) = 2^32 - 2^5 ≡ 2^28 - 2 - 2^5
        # => 2^44 ≡ 2^28 - 2^13 - 2^9 - 2^5 - 2 (mod P)   [all < 2^31]
        c44 = (1 << 28) - (1 << 13) - (1 << 9) - (1 << 5) - 2
        t1 = mulmod_small(hl, (1 << 28) % P)
        t2 = mulmod_small(hh, c44 % P)
        # -2*hi mod P
        two_hi = addmod(hi_part, hi_part)
        acc = addmod(t1, t2)
        acc = submod(acc, modred(two_hi))
        return addmod(acc, modred(lo_part))

    return fold32(hi, lo)


def mulmod_small(a: jnp.ndarray, c: int) -> jnp.ndarray:
    """a (< 2^16) times python-int constant c (< P) mod P — product < 2^47:
    one limb round suffices."""
    cl, ch = c & MASK16, c >> 16
    lo = a * cl                        # < 2^32
    hi = a * ch                        # < 2^31 (represents << 16)
    # value = lo + hi * 2^16; hi*2^16 < 2^47: fold via 2^32 ≡ 2^28-2
    hi_lo = (hi << 16)
    hi_hi = hi >> 16                   # bits 32+
    part = mulmod_small16(hi_hi, ((1 << 28) - 2) % P)
    return addmod(addmod(modred(lo), modred(hi_lo)), part)


def mulmod_small16(a, c):
    """a < 2^16, c < 2^31, product < 2^47: split c."""
    cl, ch = c & MASK16, c >> 16
    lo = a * cl
    hi = a * ch                        # << 16, < 2^31
    return addmod(modred(lo), modred2(hi))


def modred(x: jnp.ndarray) -> jnp.ndarray:
    """Reduce x < 2^32 to [0, P): 2^31 ≡ 2^27 - 1."""
    lo = x & 0x7FFFFFFF
    hi = x >> 31                       # 0 or 1
    v = lo + hi * ((1 << 27) - 1)
    return jnp.where(v >= P, v - P, v)


def modred2(x: jnp.ndarray) -> jnp.ndarray:
    """Reduce (x << 16) where x < 2^31: x*2^16 mod P via limb split."""
    xl, xh = x & MASK16, x >> 16
    # x*2^16 = xl*2^16 + xh*2^32 ≡ xl*2^16 + xh*(2^28-2)
    t0 = modred(xl << 16)
    t1 = mulmod_small16_basic(xh, ((1 << 28) - 2) % P)
    return addmod(t0, t1)


def mulmod_small16_basic(a, c):
    """a < 2^15, c < 2^29ish: product < 2^44: two rounds of modred."""
    cl, ch = c & MASK16, c >> 16
    lo = a * cl                        # < 2^31
    hi = a * ch                        # << 16, < 2^28
    t = modred(hi << 16)
    hi2 = hi >> 16                     # ~0 for our ranges but keep exact
    t2 = modred(hi2 * (((1 << 28) - 2) % P))
    return addmod(addmod(modred(lo), t), t2)


def addmod(a, b):
    s = a + b
    return jnp.where(s >= P, s - P, s)


def submod(a, b):
    return jnp.where(a >= b, a - b, a + (P - 0) - b)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
def _mulmod_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = mulmod_limb(a_ref[...], b_ref[...])


def _fma_kernel(a_ref, b_ref, c_ref, o_ref):
    o_ref[...] = addmod(mulmod_limb(a_ref[...], b_ref[...]), c_ref[...])

