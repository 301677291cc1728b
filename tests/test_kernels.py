"""Pallas kernels vs pure-jnp oracles (interpret mode): shape sweeps, edge
values, and the uint32 16-bit-limb mulmod path vs the uint64 oracle.

hypothesis is optional: only the property-based test skips without it —
the rest of the kernel suite must run everywhere (CI runs this module
under ``ZKGRAPH_BACKEND=pallas-interpret`` to catch kernel drift)."""
import ast
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import field as F
from repro.core import hashing, poly
from repro.kernels.fieldops import ops as fops
from repro.kernels.fieldops import ref as fref
from repro.kernels.fieldops.fieldops import mulmod_limb
from repro.kernels.ntt import ops as ntt_ops
from repro.kernels.ntt import ref as ntt_ref
from repro.kernels.poseidon import ops as pos_ops
from repro.kernels.poseidon import ref as pos_ref


# ---------------------------------------------------------------------------
# fieldops: limb mulmod
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [8, 256, 4096])
def test_mulmod_kernel_matches_oracle(n):
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.integers(0, F.P, size=n).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, F.P, size=n).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(fops.mulmod(a, b)),
                                  np.asarray(fref.mulmod_ref(a, b)))


def test_mulmod_edge_values():
    edge = np.asarray([0, 1, 2, 3, F.P - 1, F.P - 2, (1 << 16) - 1, 1 << 16,
                       (1 << 16) + 1, (1 << 27), (1 << 27) - 1, F.P // 2,
                       (1 << 30), 1234567, F.P - (1 << 16)], np.uint64)
    a, b = np.meshgrid(edge, edge)
    a, b = a.ravel(), b.ravel()
    # pad to kernel block multiple
    pad = (-len(a)) % 8
    a = np.concatenate([a, np.zeros(pad, np.uint64)])
    b = np.concatenate([b, np.zeros(pad, np.uint64)])
    got = np.asarray(fops.mulmod(jnp.asarray(a.astype(np.uint32)),
                                 jnp.asarray(b.astype(np.uint32))))
    want = ((a * b) % F.P).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, F.P - 1), st.integers(0, F.P - 1))
    @settings(max_examples=50, deadline=None)
    def test_mulmod_limb_property(a, b):
        got = int(mulmod_limb(jnp.full((8,), a, jnp.uint32),
                              jnp.full((8,), b, jnp.uint32))[0])
        assert got == (a * b) % F.P


@pytest.mark.parametrize("shape", [(64,), (8, 32), (4, 4, 16)])
def test_fused_mul_add(shape):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, F.P, size=shape).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, F.P, size=shape).astype(np.uint32))
    c = jnp.asarray(rng.integers(0, F.P, size=shape).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(fops.fused_mul_add(a, b, c)),
                                  np.asarray(fref.fused_mul_add_ref(a, b, c)))


# ---------------------------------------------------------------------------
# NTT kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 16, 64, 512])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_kernel_matches_oracle(n, batch, inverse):
    rng = np.random.default_rng(n + batch)
    x = jnp.asarray(rng.integers(0, F.P, size=(batch, n)).astype(np.uint32))
    got = np.asarray(ntt_ops.ntt(x, inverse=inverse))
    want = np.asarray(ntt_ref.ntt_ref(x, inverse=inverse))
    np.testing.assert_array_equal(got, want)


def test_ntt_kernel_roundtrip():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, F.P, size=(2, 128)).astype(np.uint32))
    back = ntt_ops.ntt(ntt_ops.ntt(x), inverse=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


# ---------------------------------------------------------------------------
# Poseidon kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 8, 64, 128])
def test_poseidon_kernel_matches_oracle(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(0, F.P, size=(n, 16)).astype(np.uint32))
    got = np.asarray(pos_ops.permute(x))
    want = np.asarray(pos_ref.permute_ref(x))
    np.testing.assert_array_equal(got, want)


def test_grand_product_kernel_matches_oracle():
    from repro.kernels.grand_product import ops as gp_ops
    from repro.kernels.grand_product import ref as gp_ref
    rng = np.random.default_rng(0)
    for n in (8, 256, 1024):
        x = jnp.asarray(rng.integers(1, F.P, size=n).astype(np.uint32))
        got = np.asarray(gp_ops.grand_product(x))
        want = np.asarray(gp_ref.grand_product_ref(x))
        np.testing.assert_array_equal(got, want)
    # paper Eq. (2): a true permutation ratio telescopes back to 1
    vals = rng.integers(1, F.P, size=255).astype(np.uint64)
    one = np.ones(1, np.uint64)
    num = np.concatenate([vals, one])
    den = np.concatenate([one, vals])
    inv_den = np.asarray([pow(int(d), F.P - 2, F.P) for d in den], np.uint64)
    ratios = (num * inv_den % F.P).astype(np.uint32)
    z = np.asarray(gp_ops.grand_product(jnp.asarray(ratios)))
    total = int(z[-1]) * int(ratios[-1]) % F.P
    assert total == 1


def test_grand_product_ext_kernel_matches_oracle():
    from repro.kernels.grand_product import ops as gp_ops
    from repro.kernels.grand_product import ref as gp_ref
    rng = np.random.default_rng(7)
    for n in (8, 256, 512):
        x = jnp.asarray(rng.integers(0, F.P, size=(n, 4)).astype(np.uint32))
        got = np.asarray(gp_ops.grand_product_ext(x))
        want = np.asarray(gp_ref.grand_product_ext_ref(x))
        np.testing.assert_array_equal(got, want)
    # telescoping sanity: ratios of a cyclic shift multiply back to one
    vals = jnp.asarray(rng.integers(1, F.P, size=(64, 4)).astype(np.uint32))
    num = jnp.concatenate([vals[1:], vals[:1]], axis=0)
    inv = F.ebatch_inv(vals)
    ratios = F.emul(num, inv)
    z = np.asarray(gp_ops.grand_product_ext(ratios))
    total = F.emul(jnp.asarray(z[-1]), ratios[-1])
    assert np.asarray(total).tolist() == [1, 0, 0, 0]


def test_poseidon_kernel_zero_state():
    x = jnp.zeros((8, 16), jnp.uint32)
    got = np.asarray(pos_ops.permute(x))
    want = np.asarray(pos_ref.permute_ref(x))
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got[0], np.zeros(16))  # permutation moves zero


# ---------------------------------------------------------------------------
# kernel names: every Pallas call is named, so a device trace names it
# ---------------------------------------------------------------------------
KERNEL_NAMES = {"ntt_local", "ntt_stage", "poseidon_permute",
                "grand_product", "grand_product_ext", "fieldops_mulmod",
                "fieldops_fma"}


def _literal_names(node) -> list:
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, ast.IfExp):
        return _literal_names(node.body) + _literal_names(node.orelse)
    raise AssertionError(f"kernel name is not a literal: {ast.dump(node)}")


def test_every_pallas_call_site_passes_a_name():
    """Each kernel goes through ``repro.kernels.pallas_call`` with a
    literal ``name=``; ``pl.pallas_call`` appears only inside that
    wrapper."""
    import repro.kernels
    root = Path(repro.kernels.__file__).parent
    names = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root)
        for call in ast.walk(ast.parse(path.read_text())):
            if not isinstance(call, ast.Call):
                continue
            direct = isinstance(call.func, ast.Attribute) and \
                call.func.attr == "pallas_call"
            wrapped = isinstance(call.func, ast.Name) and \
                call.func.id == "pallas_call"
            if direct:
                assert str(rel) == "__init__.py", \
                    f"{rel}:{call.lineno}: pl.pallas_call outside the wrapper"
            if wrapped:
                kw = {k.arg: k.value for k in call.keywords}
                assert "name" in kw, \
                    f"{rel}:{call.lineno}: pallas_call without a name"
                names.extend(_literal_names(kw["name"]))
    assert set(names) == KERNEL_NAMES


def test_pallas_call_requires_a_name():
    from repro import kernels
    with pytest.raises(TypeError):
        kernels.pallas_call(lambda x_ref, o_ref: None)
    with pytest.raises(ValueError):
        kernels.pallas_call(lambda x_ref, o_ref: None, name="")
