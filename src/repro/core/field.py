"""BabyBear prime field Fp (p = 2^31 - 2^27 + 1) and its quartic extension Fp4.

TPU adaptation of the paper's BN254 scalar field (see DESIGN.md §2): all
arithmetic stays inside 32-bit lanes with 64-bit intermediates on CPU; the
Pallas kernels carry a pure-uint32 16-bit-limb multiply path for real TPUs.

Conventions
-----------
* Fp elements: ``jnp.uint32`` arrays, canonical representatives in [0, p).
* Fp4 elements: uint32 arrays whose **last axis has size 4** (coefficients of
  1, x, x^2, x^3 in Fp[x]/(x^4 - W)).
* All ops are vectorized and jit-safe.  The elementwise ones are jitted
  themselves: the prover calls them eagerly, and one compiled program per
  shape costs far less to compile and dispatch — on the TPU above all —
  than the handful of separate ops each would otherwise be.
"""
from __future__ import annotations

import functools

import jax

jax.config.update("jax_enable_x64", True)  # uint64 intermediates for mulmod

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Base field constants
# ---------------------------------------------------------------------------
P = 2013265921                     # 15 * 2^27 + 1  (BabyBear)
TWO_ADICITY = 27
GENERATOR = 31                     # multiplicative generator of Fp*
W_EXT = 11                         # Fp4 = Fp[x]/(x^4 - 11)  (Plonky3 constant)

_U32 = jnp.uint32
_U64 = jnp.uint64


def _pow_py(base: int, exp: int, mod: int = P) -> int:
    return pow(base, exp, mod)


# two-adic roots of unity: ROOTS[k] has order 2^k
ROOTS: list[int] = [1] * (TWO_ADICITY + 1)
ROOTS[TWO_ADICITY] = _pow_py(GENERATOR, (P - 1) >> TWO_ADICITY)
for _k in range(TWO_ADICITY - 1, -1, -1):
    ROOTS[_k] = ROOTS[_k + 1] * ROOTS[_k + 1] % P
assert ROOTS[1] == P - 1 and ROOTS[0] == 1


# ---------------------------------------------------------------------------
# Fp ops
# ---------------------------------------------------------------------------
def fp(x) -> jnp.ndarray:
    """Coerce ints / arrays into canonical Fp uint32 form."""
    arr = jnp.asarray(x)
    if arr.dtype in (jnp.int64, jnp.uint64, jnp.int32):
        arr = jnp.remainder(arr.astype(jnp.int64), P).astype(_U32)
    else:
        arr = arr.astype(_U32)
        arr = jnp.where(arr >= P, arr - P, arr)
    return arr


@jax.jit
def fadd(a, b):
    s = a.astype(_U32) + b.astype(_U32)          # < 2^32, no overflow (a,b < 2^31)
    return jnp.where(s >= P, s - P, s)


@jax.jit
def fsub(a, b):
    a = a.astype(_U32)
    b = b.astype(_U32)
    return jnp.where(a >= b, a - b, a + (_U32(P) - b))


@jax.jit
def fneg(a):
    a = a.astype(_U32)
    return jnp.where(a == 0, a, _U32(P) - a)


_M32 = 0xFFFFFFFF
_BARRETT = (1 << 93) // P          # < 2^63


@jax.jit
def mod_p(x):
    """``x mod P`` for uint64 ``x``, as uint64, without a 64-bit division.

    XLA emulates 64-bit integers on the TPU, and its 64-bit remainder
    compiles to a graph so large that a function of a few dozen field
    products takes minutes to compile.  Barrett reduction needs only 64-bit
    multiplies, shifts and adds: with ``m = floor(2^93 / P)``,
    ``floor(x * m / 2^93)`` is ``floor(x / P)`` or one less for every
    ``x < 2^64``, so one conditional subtraction gives the remainder."""
    x = jnp.asarray(x).astype(_U64)
    x0, x1 = x & _U64(_M32), x >> _U64(32)
    m0, m1 = _U64(_BARRETT & _M32), _U64(_BARRETT >> 32)
    lo, mid0, mid1 = x0 * m0, x1 * m0, x0 * m1
    carry = ((lo >> _U64(32)) + (mid0 & _U64(_M32)) +
             (mid1 & _U64(_M32))) >> _U64(32)
    hi = x1 * m1 + (mid0 >> _U64(32)) + (mid1 >> _U64(32)) + carry
    r = x - (hi >> _U64(29)) * _U64(P)          # hi = floor(x * m / 2^64)
    return jnp.where(r >= _U64(P), r - _U64(P), r)


@jax.jit
def fmul(a, b):
    prod = a.astype(_U64) * b.astype(_U64)
    return mod_p(prod).astype(_U32)


def _square_and_multiply(mul, one, base, e: int):
    """base ** e for a static python-int exponent, as a ``fori_loop`` over
    its bits: one squaring and one multiply are traced, not ~60 (compile
    time on the TPU grows with the number of traced products)."""
    if e == 0:
        return one
    bits = jnp.asarray([(e >> i) & 1 for i in range(e.bit_length())],
                       jnp.uint32)

    def step(i, acc):
        result, base = acc
        result = jnp.where(bits[i] == 1, mul(result, base), result)
        return result, mul(base, base)

    result, _ = jax.lax.fori_loop(0, e.bit_length(), step, (one, base))
    return result


@functools.partial(jax.jit, static_argnums=1)
def fpow(a, e: int):
    """a ** e with a *static* python-int exponent (square and multiply)."""
    base = jnp.asarray(a, _U32)
    return _square_and_multiply(fmul, jnp.ones_like(base), base, e)


def finv(a):
    return fpow(a, P - 2)


_INV_BLOCK = 64


def _montgomery_rows(mul, inv, one, x):
    """Inverse of every element of ``x`` (shape ``(rows, ...)``, no zeros):
    Montgomery's trick run down the rows, vectorised across the rest.

    One forward scan of prefix products, one inversion of the row totals,
    one backward scan: three products per element plus ``inv`` on one row.
    Each scan body is traced once, so the compiled program does not grow
    with the length (an ``associative_scan`` adds a level of products per
    doubling, and XLA compiles every level)."""
    total, excl = jax.lax.scan(lambda acc, xi: (mul(acc, xi), acc), one, x)

    def back(acc, xs):          # acc: inverse of the product up to row i
        xi, pre = xs
        return mul(acc, xi), mul(acc, pre)

    _, out = jax.lax.scan(back, inv(total), (x, excl), reverse=True)
    return out


def _blocked_inv(mul, inv, one, flat):
    """Inverses of the rows of ``flat`` (``(n, ...)``, no zeros), folded
    into at most ``_INV_BLOCK`` scan rows."""
    n = flat.shape[0]
    rows = min(_INV_BLOCK, n)
    cols = -(-n // rows)
    pad = jnp.broadcast_to(one, (rows * cols - n,) + flat.shape[1:])
    x = jnp.concatenate([flat, pad]).reshape((rows, cols) + flat.shape[1:])
    one_row = jnp.broadcast_to(one, (cols,) + flat.shape[1:])
    out = _montgomery_rows(mul, inv, one_row, x)
    return out.reshape((rows * cols,) + flat.shape[1:])[:n]


@jax.jit
def fbatch_inv(a):
    """Inverse of every element (Montgomery batch inversion, blocked);
    zero entries map to zero (callers guard their own semantics)."""
    a = jnp.asarray(a, _U32)
    if a.size == 0:
        return a
    safe = jnp.where(a == 0, _U32(1), a).reshape(-1)
    inv = _blocked_inv(fmul, finv, _U32(1), safe).reshape(a.shape)
    return jnp.where(a == 0, _U32(0), inv)


# ---------------------------------------------------------------------------
# Fp4 ops — last axis of size 4
# ---------------------------------------------------------------------------
def ext(x) -> jnp.ndarray:
    """Embed Fp scalar/array into Fp4 (append 3 zero coefficients)."""
    x = fp(x)
    z = jnp.zeros(x.shape + (3,), _U32)
    return jnp.concatenate([x[..., None], z], axis=-1)


def ext_from_coeffs(c0, c1, c2, c3):
    return jnp.stack([fp(c0), fp(c1), fp(c2), fp(c3)], axis=-1)


EXT_ZERO = np.array([0, 0, 0, 0], np.uint32)
EXT_ONE = np.array([1, 0, 0, 0], np.uint32)


def eadd(a, b):
    return fadd(a, b)


def esub(a, b):
    return fsub(a, b)


def eneg(a):
    return fneg(a)


@jax.jit
def emul(a, b):
    """Schoolbook Fp4 multiply with reduction x^4 = W_EXT."""
    a0, a1, a2, a3 = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    b0, b1, b2, b3 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    w = _U32(W_EXT)

    def m(x, y):
        return fmul(x, y)

    c0 = fadd(m(a0, b0), fmul(w, fadd(fadd(m(a1, b3), m(a2, b2)), m(a3, b1))))
    c1 = fadd(fadd(m(a0, b1), m(a1, b0)), fmul(w, fadd(m(a2, b3), m(a3, b2))))
    c2 = fadd(fadd(m(a0, b2), m(a1, b1)), fadd(m(a2, b0), fmul(w, m(a3, b3))))
    c3 = fadd(fadd(m(a0, b3), m(a1, b2)), fadd(m(a2, b1), m(a3, b0)))
    return jnp.stack([c0, c1, c2, c3], axis=-1)


@jax.jit
def emul_fp(a_ext, b_fp):
    """Fp4 * Fp (scalar multiply each coefficient)."""
    return fmul(a_ext, b_fp[..., None].astype(_U32))


@functools.partial(jax.jit, static_argnums=1)
def epow(a, e: int):
    base = jnp.asarray(a, _U32)
    one = jnp.broadcast_to(jnp.asarray(EXT_ONE), base.shape).astype(_U32)
    return _square_and_multiply(emul, one, base, e)


def _norm_parts(a):
    """``(c, n)`` with ``a * c = n`` in Fp: ``c`` is the product of the
    three Frobenius conjugates of ``a``, ``n = N(a)`` its norm.

    For q = p, Frobenius phi(a)(x) = a(x^p). Since x^4 = W, x^p = x * W^((p-1)/4)
    with (p-1) divisible by 4. N(a) = a * phi(a) * phi^2(a) * phi^3(a) in Fp.
    """
    s = _pow_py(W_EXT, (P - 1) // 4)  # x^p = s * x, s^4 = W^(p-1) = 1
    # phi^k multiplies coefficient i by s^(i*k)
    def frob(v, k):
        mults = np.array([_pow_py(s, i * k) for i in range(4)], np.uint32)
        return fmul(v, jnp.asarray(mults))

    prod = emul(emul(frob(a, 1), frob(a, 2)), frob(a, 3))
    norm = emul(a, prod)  # lies in Fp: coefficients 1..3 are ~0
    return prod, norm[..., 0]


def einv(a):
    """Inverse in Fp4 via the norm map: inv(a) = phi(a)phi^2(a)phi^3(a) / N(a)."""
    prod, norm = _norm_parts(a)
    return emul_fp(prod, finv(norm))


def ebatch_inv(a):
    """Inverse of every Fp4 element via the norm map, with the norms
    inverted together by :func:`fbatch_inv`; zero elements map to zero."""
    a = jnp.asarray(a, _U32)
    is_zero = jnp.all(a == 0, axis=-1, keepdims=True)
    one = jnp.broadcast_to(jnp.asarray(EXT_ONE), a.shape).astype(_U32)
    prod, norm = _norm_parts(jnp.where(is_zero, one, a))
    inv = emul_fp(prod, fbatch_inv(norm))
    return jnp.where(is_zero, jnp.zeros_like(inv), inv)


# ---------------------------------------------------------------------------
# misc helpers
# ---------------------------------------------------------------------------
def rand_fp(key, shape):
    """Uniform Fp sample (rejection-free: 2^31 mod p bias is ~2^-4 of range;
    use 64-bit sample mod p for negligible bias)."""
    bits = jax.random.bits(key, shape, dtype=jnp.uint32).astype(_U64)
    bits2 = jax.random.bits(jax.random.fold_in(key, 1), shape, dtype=jnp.uint32)
    wide = (bits << _U64(32)) | bits2.astype(_U64)
    return mod_p(wide).astype(_U32)


def rand_ext(key, shape=()):
    return rand_fp(key, tuple(shape) + (4,))


@functools.lru_cache(maxsize=None)
def root_of_unity(order: int) -> int:
    """Primitive root of unity of the given power-of-two order (python int)."""
    k = order.bit_length() - 1
    assert order == 1 << k and k <= TWO_ADICITY, f"bad NTT order {order}"
    return ROOTS[k]
