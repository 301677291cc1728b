"""Polynomial arithmetic over BabyBear: radix-2 NTT, coset LDE, evaluation.

The NTT is the prover's compute hot-spot (together with Merkle hashing); the
Pallas kernel in ``repro.kernels.ntt`` implements the same butterfly schedule
with explicit VMEM BlockSpecs.  :func:`ntt` dispatches through the active
compute backend (:mod:`repro.core.backend`); :func:`ntt_ref` is the pure-jnp
oracle and the ``ref`` (CPU default) path.  Backends are bit-identical, so
``coset_lde``/``intt`` and everything built on them (commitments, quotient,
FRI folds) are backend-independent.

Domain conventions
------------------
* ``H_n``     : multiplicative subgroup of size n (powers of w_n, natural order)
* coset LDE   : evaluations on ``shift * H_{n*blowup}``
* evaluation order is *natural* (index i ↦ shift * w^i), not bit-reversed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import backend
from . import field as F

_U32 = jnp.uint32

# default coset shift for LDEs: the field generator (not in any small H)
COSET_SHIFT = F.GENERATOR


@functools.lru_cache(maxsize=None)
def _bitrev_perm(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n, dtype=np.int64)
    rev = np.zeros_like(idx)
    for b in range(bits):
        rev |= ((idx >> b) & 1) << (bits - 1 - b)
    return rev


@functools.lru_cache(maxsize=None)
def _stage_twiddles(n: int, inverse: bool) -> tuple[np.ndarray, ...]:
    """Per-stage twiddle tables for DIT butterflies, stage m = 1,2,4,...,n/2."""
    root = F.root_of_unity(n)
    if inverse:
        root = pow(root, F.P - 2, F.P)
    tables = []
    m = 1
    while m < n:
        w_m = pow(root, n // (2 * m), F.P)     # order 2m
        tw = np.ones(m, np.uint64)
        for j in range(1, m):
            tw[j] = tw[j - 1] * w_m % F.P
        tables.append(tw.astype(np.uint32))
        m *= 2
    return tuple(tables)


def ntt(a: jnp.ndarray, inverse: bool = False) -> jnp.ndarray:
    """Radix-2 DIT NTT along the last axis (length must be a power of two).

    Natural-order input -> natural-order output. ``inverse=True`` gives the
    inverse transform including the 1/n scaling.  Dispatches to the active
    compute backend (bit-identical across backends)."""
    return backend.active().ntt(a, inverse=inverse)


@functools.partial(jax.jit, static_argnames=("inverse",))
def ntt_ref(a: jnp.ndarray, inverse: bool = False) -> jnp.ndarray:
    """The pure-jnp reference NTT (the ``ref`` backend, and the oracle the
    Pallas stage kernel is validated against)."""
    n = a.shape[-1]
    if n == 1:
        return a
    a = a[..., jnp.asarray(_bitrev_perm(n))]
    tables = _stage_twiddles(n, inverse)
    batch = a.shape[:-1]
    m = 1
    for tw in tables:
        a = a.reshape(batch + (n // (2 * m), 2, m))
        even = a[..., 0, :]
        odd = F.fmul(a[..., 1, :], jnp.asarray(tw))
        a = jnp.stack([F.fadd(even, odd), F.fsub(even, odd)], axis=-2)
        m *= 2
    a = a.reshape(batch + (n,))
    if inverse:
        n_inv = pow(n, F.P - 2, F.P)
        a = F.fmul(a, _U32(n_inv))
    return a


def intt(a: jnp.ndarray) -> jnp.ndarray:
    return ntt(a, inverse=True)


def coset_lde(evals: jnp.ndarray, blowup: int, shift: int = COSET_SHIFT) -> jnp.ndarray:
    """Given evaluations on H_n (natural order), return evaluations on
    ``shift * H_{n*blowup}`` (natural order). Last-axis transform."""
    n = evals.shape[-1]
    coeffs = intt(evals)
    # scale c_i by shift^i, zero-pad to N = n * blowup
    powers = np.ones(n, np.uint64)
    for i in range(1, n):
        powers[i] = powers[i - 1] * shift % F.P
    coeffs = F.fmul(coeffs, jnp.asarray(powers.astype(np.uint32)))
    pad = [(0, 0)] * (coeffs.ndim - 1) + [(0, n * (blowup - 1))]
    coeffs = jnp.pad(coeffs, pad)
    return ntt(coeffs)


def coeffs_from_evals(evals: jnp.ndarray) -> jnp.ndarray:
    return intt(evals)


def coset_coeffs(evals: jnp.ndarray, shift: int) -> jnp.ndarray:
    """Interpolate coefficients from evaluations on ``shift * H_n``."""
    n = evals.shape[-1]
    coeffs = intt(evals)
    s_inv = pow(shift, F.P - 2, F.P)
    powers = np.ones(n, np.uint64)
    for i in range(1, n):
        powers[i] = powers[i - 1] * s_inv % F.P
    return F.fmul(coeffs, jnp.asarray(powers.astype(np.uint32)))


@functools.partial(jax.jit, static_argnums=1)
def ext_powers(z: jnp.ndarray, n: int) -> jnp.ndarray:
    """The powers z^0 .. z^(n-1) of Fp4 points: (..., 4) -> (..., n, 4).

    ``n`` is a power of two.  Baby steps and giant steps: with
    ``s = 2^ceil(log2(n)/2)`` and ``g = n/s``, one scan gives ``z^i`` for
    i < s (its last carry is ``z^s``), a second gives ``z^(s*j)`` for j < g,
    and one broadcast product of the two fills row ``j*s + i`` with
    ``z^(j*s+i)``.  On the TPU every scan step is one serial ``while``
    iteration, so an n-step scan of single products runs n of them; this
    runs s + g (512 at n = 2^16).  A doubling table would need fewer still,
    but traces log2(n) products, and TPU compile time grows with traced
    products (``field._square_and_multiply``)."""
    s = 1 << (((n - 1).bit_length() + 1) // 2)
    g = n // s

    def step(mult):
        return lambda carry, _: (F.emul(carry, mult), carry)

    one = jnp.broadcast_to(jnp.asarray(F.EXT_ONE), z.shape).astype(_U32)
    z_s, small = jax.lax.scan(step(z), one, None, length=s)       # (s, ..., 4)
    _, giant = jax.lax.scan(step(z_s), one, None, length=g)       # (g, ..., 4)
    small = jnp.moveaxis(small, 0, -2)                            # (..., s, 4)
    giant = jnp.moveaxis(giant, 0, -2)                            # (..., g, 4)
    table = F.emul(giant[..., :, None, :], small[..., None, :, :])
    return table.reshape(z.shape[:-1] + (n, 4))


@jax.jit
def eval_at_ext(coeffs: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Evaluate an Fp-coefficient polynomial at an Fp4 point ``z``.

    coeffs: (..., n) Fp; z: (4,) Fp4. Returns (..., 4): sum_i c_i * z^i,
    as one product with the power table of :func:`ext_powers` and a
    modular sum, vectorized over the rows.  The table comes from two scans
    of about sqrt(n) steps (baby steps z^i, giant steps z^(s*j)) and one
    broadcast product: each scan step is a serial ``while`` iteration on
    the TPU, so a single scan over all n powers would run n of them.
    """
    zpows = ext_powers(z, coeffs.shape[-1])                      # (n, 4)
    # sum_i c_i * zpows[i]: (..., n, 1) * (n, 4) -> mod-P dot
    prod = F.fmul(coeffs[..., None].astype(_U32), zpows)      # (..., n, 4)
    # modular sum along axis -2 (values < P; sum in uint64 then reduce)
    s = F.mod_p(jnp.sum(prod.astype(jnp.uint64), axis=-2))
    return s.astype(_U32)


def domain_points(n: int, shift: int = 1) -> jnp.ndarray:
    """Natural-order points of shift * H_n as Fp array."""
    w = F.root_of_unity(n)
    pts = np.ones(n, np.uint64)
    for i in range(1, n):
        pts[i] = pts[i - 1] * w % F.P
    pts = pts * shift % F.P
    return jnp.asarray(pts.astype(np.uint32))


def naive_dft(a: np.ndarray, inverse: bool = False) -> np.ndarray:
    """O(n^2) reference DFT (numpy, python ints) for testing."""
    n = len(a)
    root = F.root_of_unity(n)
    if inverse:
        root = pow(root, F.P - 2, F.P)
    out = np.zeros(n, np.uint32)
    for k in range(n):
        acc = 0
        wk = pow(root, k, F.P)
        x = 1
        for i in range(n):
            acc = (acc + int(a[i]) * x) % F.P
            x = x * wk % F.P
        if inverse:
            acc = acc * pow(n, F.P - 2, F.P) % F.P
        out[k] = acc
    return out
