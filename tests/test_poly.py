"""NTT vs naive DFT, LDE consistency, extension-point evaluation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field as F
from repro.core import poly
from repro.core import prover_batch


@pytest.mark.parametrize("n", [1, 2, 8, 64, 256])
def test_ntt_matches_naive_dft(n):
    rng = np.random.default_rng(n)
    a = rng.integers(0, F.P, size=n).astype(np.uint32)
    got = np.asarray(poly.ntt(jnp.asarray(a)))
    want = poly.naive_dft(a)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [2, 32, 128])
def test_intt_roundtrip(n):
    rng = np.random.default_rng(n + 1)
    a = jnp.asarray(rng.integers(0, F.P, size=(3, n)).astype(np.uint32))
    back = poly.intt(poly.ntt(a))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(a))


@pytest.mark.parametrize("blowup", [2, 4])
def test_coset_lde_agrees_pointwise(blowup):
    """LDE evaluations must equal Horner evaluation of the coefficients at
    every coset point."""
    n = 16
    rng = np.random.default_rng(7)
    evals = jnp.asarray(rng.integers(0, F.P, size=n).astype(np.uint32))
    lde = np.asarray(poly.coset_lde(evals, blowup))
    coeffs = np.asarray(poly.intt(evals))
    pts = np.asarray(poly.domain_points(n * blowup, poly.COSET_SHIFT))
    for i in range(0, n * blowup, 5):
        x = int(pts[i])
        want = 0
        for j in range(n - 1, -1, -1):
            want = (want * x + int(coeffs[j])) % F.P
        assert int(lde[i]) == want


def test_lde_restricts_to_original_on_subgroup():
    """f on H_n must reappear inside the LDE when the shift is 1 and indices
    are strided by blowup."""
    n, blowup = 32, 4
    rng = np.random.default_rng(9)
    evals = jnp.asarray(rng.integers(0, F.P, size=n).astype(np.uint32))
    lde = np.asarray(poly.coset_lde(evals, blowup, shift=1))
    np.testing.assert_array_equal(lde[::blowup], np.asarray(evals))


def test_eval_at_ext_matches_base_eval():
    n = 32
    rng = np.random.default_rng(11)
    coeffs = jnp.asarray(rng.integers(0, F.P, size=n).astype(np.uint32))
    # pick a base-field point embedded in Fp4 — must agree with Horner in Fp
    x = 12345
    z = jnp.asarray(np.array([x, 0, 0, 0], np.uint32))
    got = np.asarray(poly.eval_at_ext(coeffs, z))
    want = 0
    cs = np.asarray(coeffs)
    for j in range(n - 1, -1, -1):
        want = (want * x + int(cs[j])) % F.P
    assert got[0] == want and np.all(got[1:] == 0)


# a genuine Fp4 point: every coefficient non-zero
_Z_EXT = np.array([1234567, 7654321, 1111111, 2013265920], np.uint32)


def _scan_eval_at_ext(coeffs, z):
    """The n-step scan power table (one Fp4 product per step), as the
    oracle the baby-step/giant-step table must reproduce bit for bit."""
    n = coeffs.shape[-1]

    def step(carry, _):
        return F.emul(carry, z), carry

    _, zpows = jax.lax.scan(step, jnp.asarray(F.EXT_ONE), None, length=n)
    prod = F.fmul(coeffs[..., None].astype(jnp.uint32), zpows)
    return F.mod_p(jnp.sum(prod.astype(jnp.uint64), axis=-2)).astype(jnp.uint32)


def _ext_mul_py(a, b):
    """Schoolbook Fp4 product in python ints, reduced by x^4 = W."""
    full = [0] * 7
    for i in range(4):
        for j in range(4):
            full[i + j] = (full[i + j] + a[i] * b[j]) % F.P
    for k in range(6, 3, -1):
        full[k - 4] = (full[k - 4] + full[k] * F.W_EXT) % F.P
    return full[:4]


def _horner_ext_py(row, z):
    acc = [0, 0, 0, 0]
    for c in reversed(row):
        acc = _ext_mul_py(acc, z)
        acc[0] = (acc[0] + int(c)) % F.P
    return acc


@pytest.mark.parametrize("n", [1, 2, 8, 32, 512, 2048])
def test_eval_at_ext_matches_scan_and_horner(n):
    """Odd and even log2(n), and the one-step edges (s = 1, g = 1)."""
    rng = np.random.default_rng(100 + n)
    coeffs = jnp.asarray(rng.integers(0, F.P, size=(8, n)).astype(np.uint32))
    z = jnp.asarray(_Z_EXT)
    got = np.asarray(poly.eval_at_ext(coeffs, z))
    assert got.shape == (8, 4)
    np.testing.assert_array_equal(got, np.asarray(_scan_eval_at_ext(coeffs, z)))
    zi = [int(v) for v in _Z_EXT]
    want = [_horner_ext_py(row, zi) for row in np.asarray(coeffs)]
    np.testing.assert_array_equal(got, np.asarray(want, np.uint32))


@pytest.mark.parametrize("n", [1, 8, 128])
def test_ext_powers_lanes_match_single_lane(n):
    rng = np.random.default_rng(200 + n)
    zs = rng.integers(1, F.P, size=(3, 4)).astype(np.uint32)
    got = np.asarray(poly.ext_powers(jnp.asarray(zs), n))
    assert got.shape == (3, n, 4)
    for lane, z in enumerate(zs):
        np.testing.assert_array_equal(
            got[lane], np.asarray(poly.ext_powers(jnp.asarray(z), n)))


def test_eval_at_ext_lanes_match_per_lane_eval():
    n, m, lanes = 64, 8, 3
    rng = np.random.default_rng(300)
    coeffs = rng.integers(0, F.P, size=(lanes, m, n)).astype(np.uint32)
    zs = rng.integers(1, F.P, size=(lanes, 4)).astype(np.uint32)
    got = np.asarray(prover_batch._eval_at_ext_lanes(jnp.asarray(coeffs),
                                                     jnp.asarray(zs)))
    assert got.shape == (lanes, m, 4)
    for lane in range(lanes):
        want = poly.eval_at_ext(jnp.asarray(coeffs[lane]), jnp.asarray(zs[lane]))
        np.testing.assert_array_equal(got[lane], np.asarray(want))


def test_batched_ntt_shapes():
    a = jnp.zeros((5, 3, 16), jnp.uint32)
    out = poly.ntt(a)
    assert out.shape == (5, 3, 16)
