"""Field axioms + inversion for BabyBear Fp and Fp4 (hypothesis property tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.core import field as F

fp_elem = st.integers(min_value=0, max_value=F.P - 1)


@given(fp_elem, fp_elem, fp_elem)
@settings(max_examples=50, deadline=None)
def test_fp_ring_axioms(a, b, c):
    A, B, C = (jnp.uint32(x) for x in (a, b, c))
    assert int(F.fadd(A, B)) == (a + b) % F.P
    assert int(F.fsub(A, B)) == (a - b) % F.P
    assert int(F.fmul(A, B)) == (a * b) % F.P
    # distributivity
    lhs = F.fmul(A, F.fadd(B, C))
    rhs = F.fadd(F.fmul(A, B), F.fmul(A, C))
    assert int(lhs) == int(rhs)


@given(fp_elem)
@settings(max_examples=30, deadline=None)
def test_fp_inverse(a):
    if a == 0:
        return
    inv = F.finv(jnp.uint32(a))
    assert int(F.fmul(jnp.uint32(a), inv)) == 1


def test_batch_inverse():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, F.P, size=257).astype(np.uint32))
    a = a.at[13].set(0)
    inv = F.fbatch_inv(a)
    prod = F.fmul(a, inv)
    expect = np.ones(257, np.uint32)
    expect[13] = 0
    np.testing.assert_array_equal(np.asarray(prod), expect)


@pytest.mark.parametrize("shape", [(1,), (63,), (64,), (65,), (3, 1000),
                                   (2, 5, 129)])
def test_batch_inverse_matches_elementwise_inverse(shape):
    """The blocked batch inverse equals a^(P-2) elementwise at lengths
    around and across its block, with leading axes and zeros."""
    rng = np.random.default_rng(len(shape) * 1000 + shape[-1])
    a = rng.integers(0, F.P, size=shape).astype(np.uint32)
    a.reshape(-1)[::7] = 0
    got = np.asarray(F.fbatch_inv(jnp.asarray(a)))
    want = np.asarray(F.finv(jnp.asarray(np.where(a == 0, 1, a))))
    np.testing.assert_array_equal(got, np.where(a == 0, 0, want))
    ext = rng.integers(0, F.P, size=shape + (4,)).astype(np.uint32)
    ext.reshape(-1, 4)[::5] = 0
    got = np.asarray(F.ebatch_inv(jnp.asarray(ext)))
    zero = (ext == 0).all(-1, keepdims=True)
    want = np.asarray(F.einv(jnp.asarray(np.where(zero, F.EXT_ONE, ext))))
    np.testing.assert_array_equal(got, np.where(zero, 0, want))


@given(st.integers(0, 2**32), st.integers(0, 2**32))
@settings(max_examples=30, deadline=None)
def test_ext_mul_matches_poly_mul(seed_a, seed_b):
    rng = np.random.default_rng(seed_a * 2**33 + seed_b)
    a = rng.integers(0, F.P, size=4)
    b = rng.integers(0, F.P, size=4)
    got = np.asarray(F.emul(jnp.asarray(a, jnp.uint32), jnp.asarray(b, jnp.uint32)))
    # schoolbook in python ints, reduce x^4 = W
    full = [0] * 7
    for i in range(4):
        for j in range(4):
            full[i + j] = (full[i + j] + int(a[i]) * int(b[j])) % F.P
    for k in range(6, 3, -1):
        full[k - 4] = (full[k - 4] + full[k] * F.W_EXT) % F.P
    np.testing.assert_array_equal(got, np.asarray(full[:4], np.uint32))


@given(st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_ext_inverse(seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, F.P, size=4), jnp.uint32)
    if int(jnp.sum(a)) == 0:
        return
    inv = F.einv(a)
    one = F.emul(a, inv)
    np.testing.assert_array_equal(np.asarray(one), F.EXT_ONE)


def test_ext_batch_inverse():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, F.P, size=(33, 4)), jnp.uint32)
    a = a.at[7].set(0)
    inv = F.ebatch_inv(a)
    prod = F.emul(a, inv)
    expect = np.tile(F.EXT_ONE, (33, 1))
    expect[7] = 0
    np.testing.assert_array_equal(np.asarray(prod), expect)


def test_roots_of_unity():
    for k in [1, 2, 8, 16]:
        w = F.root_of_unity(k)
        assert pow(w, k, F.P) == 1
        if k > 1:
            assert pow(w, k // 2, F.P) != 1


def test_epow_matches_repeated_mul():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.integers(0, F.P, size=4), jnp.uint32)
    acc = jnp.asarray(F.EXT_ONE)
    for e in range(8):
        np.testing.assert_array_equal(np.asarray(F.epow(a, e)), np.asarray(acc))
        acc = F.emul(acc, a)


def test_mod_p_matches_remainder_on_uint64():
    """Barrett reduction == the 64-bit remainder, edges and random values."""
    rng = np.random.default_rng(3)
    p = F.P
    edges = np.array([0, 1, p - 1, p, p + 1, 2 * p - 1, 2 * p, (p - 1) ** 2,
                      2 ** 31, 2 ** 32 - 1, 2 ** 62, 2 ** 63 - 1, 2 ** 63,
                      2 ** 64 - 2, 2 ** 64 - 1, (2 ** 64 - 1) // p * p,
                      (2 ** 64 - 1) // p * p - 1], np.uint64)
    wide = (rng.integers(0, 2 ** 32, 4096, dtype=np.uint64) << np.uint64(32)) \
        | rng.integers(0, 2 ** 32, 4096, dtype=np.uint64)
    prods = rng.integers(0, p, 4096).astype(np.uint64) * \
        rng.integers(0, p, 4096).astype(np.uint64)
    x = np.concatenate([edges, wide, prods])
    got = np.asarray(F.mod_p(jnp.asarray(x)))
    np.testing.assert_array_equal(got, x % np.uint64(p))
