"""Compute-backend subsystem (repro.core.backend): registry/selection
semantics, padding-edge parity for every dispatched primitive (row counts
1, tile-1, tile+1 — the adapters in each kernel's ops.py), keygen-cache
isolation, and the Fiat–Shamir-critical guarantee: a full
ZKGraphSession.prove round trip emits bit-identical proof bytes on every
backend (timings — a wall-clock diagnostic the wire format carries — are
normalized before comparison; all semantic fields must match exactly).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import backend
from repro.core import commit, field as F, hashing, merkle, poly
from repro.core import prover as pv
from repro.core.operators import registry
from repro.core.session import KeygenCache, TrustAnchor, ZKGraphSession

PARITY = ("ref", "pallas-interpret")


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, F.P, size=shape).astype(np.uint32))


# ---------------------------------------------------------------------------
# registry + selection
# ---------------------------------------------------------------------------
def test_registry_has_all_backends():
    assert set(PARITY) | {"pallas"} <= set(backend.names())
    for name in backend.names():
        be = backend.get(name)
        assert be.name == name and callable(be.permute)


def test_unknown_backend_fails_loudly():
    with pytest.raises(backend.UnknownBackendError, match="available"):
        backend.get("cuda")
    with pytest.raises(backend.UnknownBackendError):
        with backend.use("not-a-backend"):
            pass


def test_env_var_selection(monkeypatch):
    monkeypatch.setenv(backend.ENV_VAR, "pallas-interpret")
    assert backend.active_name() == "pallas-interpret"
    monkeypatch.setenv(backend.ENV_VAR, "bogus")
    with pytest.raises(backend.UnknownBackendError):
        backend.active_name()
    monkeypatch.delenv(backend.ENV_VAR)
    assert backend.active_name() == backend.DEFAULT


def test_use_nests_and_restores(monkeypatch):
    monkeypatch.delenv(backend.ENV_VAR, raising=False)
    with backend.use("pallas-interpret") as outer:
        assert outer.name == backend.active_name() == "pallas-interpret"
        with backend.use("ref"):
            assert backend.active_name() == "ref"
            # use(None) pins whatever is active at entry
            with backend.use(None):
                assert backend.active_name() == "ref"
        assert backend.active_name() == "pallas-interpret"
    assert backend.active_name() == backend.DEFAULT


def test_probe_reports_cleanly():
    ok, reason = backend.probe("pallas-interpret")
    assert ok, reason
    # the compiled backend needs a TPU: on CPU hosts the probe must answer
    # False with a reason, never raise; on a TPU it must work
    import jax
    ok, reason = backend.probe("pallas")
    if jax.default_backend() == "cpu":
        assert not ok and reason
    if jax.default_backend() == "tpu":
        assert ok, reason


def test_require_fails_loudly_where_the_backend_cannot_run():
    import jax
    assert backend.require("pallas-interpret").name == "pallas-interpret"
    if jax.default_backend() == "cpu":
        with pytest.raises(backend.BackendUnavailableError, match="pallas"):
            backend.require("pallas")


# ---------------------------------------------------------------------------
# per-primitive parity at padding edges (tile-1 / tile / tile+1 / 1)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 63, 64, 65, 130])
def test_permute_parity(n):
    x = _rand((n, 16), seed=n)
    want = np.asarray(hashing.permute_ref(x))
    with backend.use("pallas-interpret"):
        got = np.asarray(hashing.permute(x))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", [(1, 64), (7, 32), (9, 128), (2, 3, 16)])
@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_parity(shape, inverse):
    x = _rand(shape, seed=sum(shape))
    want = np.asarray(poly.ntt_ref(x, inverse=inverse))
    with backend.use("pallas-interpret"):
        got = np.asarray(poly.ntt(x, inverse=inverse))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [1, 255, 256, 257])
def test_grand_product_ext_parity(n):
    from repro.kernels.grand_product.ref import grand_product_ext_ref
    x = _rand((n, 4), seed=n)
    want = np.asarray(grand_product_ext_ref(x))
    with backend.use("pallas-interpret"):
        got = np.asarray(backend.active().grand_product_ext(x))
    np.testing.assert_array_equal(got, want)
    assert got[0].tolist() == [1, 0, 0, 0]          # exclusive: Z[0] = 1


def test_hash_bytes_and_merkle_parity():
    data = b"zkgraph backend parity \x00\x01\x02"
    rows = _rand((32, 5), seed=3)
    want_digest = hashing.hash_bytes(data)
    want_root = np.asarray(merkle.commit(rows).root)
    with backend.use("pallas-interpret"):
        got_digest = hashing.hash_bytes(data)
        got_root = np.asarray(merkle.commit(rows).root)
    np.testing.assert_array_equal(got_digest, want_digest)
    np.testing.assert_array_equal(got_root, want_root)


def test_data_root_parity(tiny_cfg):
    import dataclasses
    cols = np.asarray(np.arange(3 * 20).reshape(3, 20), np.uint32)
    cfg_r = dataclasses.replace(tiny_cfg, backend="ref")
    cfg_k = dataclasses.replace(tiny_cfg, backend="pallas-interpret")
    want = commit.data_root(cols, 32, cfg_r, desc="parity")
    got = commit.data_root(cols, 32, cfg_k, desc="parity")
    np.testing.assert_array_equal(got, want)
    # cfg equality ignores the backend field: it is execution policy, not a
    # proof parameter (the verifier would otherwise reject the bundle)
    assert cfg_k == cfg_r == tiny_cfg


# ---------------------------------------------------------------------------
# keygen cache isolation + cfg routing
# ---------------------------------------------------------------------------
def _tiny_op():
    return registry.build_operator("expand", dict(
        n_rows=32, m_edges=20, with_prop=False, reverse=False))


def test_keygen_cache_never_crosses_backends(tiny_cfg):
    # explicit backends on both sides: the test must hold under ANY ambient
    # selection (CI runs the whole suite with ZKGRAPH_BACKEND set)
    import dataclasses
    cfg_ref = dataclasses.replace(tiny_cfg, backend="ref")
    cfg_pal = dataclasses.replace(tiny_cfg, backend="pallas-interpret")
    cache = KeygenCache()
    cache.ensure(_tiny_op(), cfg_ref)
    cache.ensure(_tiny_op(), cfg_pal)
    assert cache.stats() == dict(hits=0, misses=2, waits=0, entries=2)
    # same backend again: a hit, not a third keygen
    cache.ensure(_tiny_op(), cfg_ref)
    assert cache.stats()["hits"] == 1


def test_keygen_records_resolved_backend(tiny_cfg):
    import dataclasses
    keys = pv.keygen(_tiny_op().circuit, tiny_cfg)
    assert keys.backend == backend.active_name()    # None = ambient
    cfg_k = dataclasses.replace(tiny_cfg, backend="pallas-interpret")
    keys = pv.keygen(_tiny_op().circuit, cfg_k)
    assert keys.backend == "pallas-interpret"
    cfg_r = dataclasses.replace(tiny_cfg, backend="ref")
    np.testing.assert_array_equal(
        np.asarray(keys.fixed_lde),
        np.asarray(pv.keygen(_tiny_op().circuit, cfg_r).fixed_lde))


# ---------------------------------------------------------------------------
# the parity guarantee: full prove/verify round trip, byte-identical
# ---------------------------------------------------------------------------
def _canonical_bytes(bundle):
    """Wire bytes with the wall-clock timings diagnostic normalized out —
    every *semantic* field (roots, openings, FRI layers, tree openings,
    result, manifest digest) must already be bit-identical."""
    for step in bundle.steps:
        step.proof.timings = {}
    return bundle.to_bytes()


def test_proof_bytes_identical_across_backends(db, owner, tiny_cfg):
    raws = {}
    for name in PARITY:
        with backend.use(name):
            session = ZKGraphSession(db, tiny_cfg,
                                     commitments=owner.commitments)
            bundle = session.prove("IS5", dict(message=(1 << 20) + 7))
        raws[name] = _canonical_bytes(bundle)
    assert raws["ref"] == raws["pallas-interpret"], \
        "backends diverged: Fiat–Shamir transcripts are not bit-identical"
    # cross-verification: a bundle proven on one backend verifies on the
    # other (the verifier re-derives chained roots with ITS backend)
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=owner.commitments), cfg=tiny_cfg)
    for prover_name, raw in raws.items():
        other = [n for n in PARITY if n != prover_name][0]
        with backend.use(other):
            assert verifier.verify_bytes(raw), \
                f"bundle proven under {prover_name} rejected under {other}"
