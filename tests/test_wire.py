"""Adversarial wire-format tests: the canonical ProofBundle codec must treat
every byte as hostile — truncations, flipped tags, oversized length prefixes,
wrong dtypes, legacy pickle, version skew — and the verifier must reject a
re-encoded bundle whose base-table geometry disagrees with the published
manifest (the soundness gap this codec + manifest close)."""
import pickle
import struct

import numpy as np
import pytest

from repro.core import wire
from repro.core.session import ProofBundle, WireFormatError

HEADER = len(wire.MAGIC) + 2 + 1     # magic + u16 version + u8 payload kind


@pytest.fixture(scope="module")
def raw(bundle):
    return bundle.to_bytes()


# ---------------------------------------------------------------------------
# canonical round trip
# ---------------------------------------------------------------------------
def test_roundtrip_byte_identical(raw):
    """One canonical encoding per bundle: decode+re-encode is the identity."""
    rt = ProofBundle.from_bytes(raw)
    assert rt.to_bytes() == raw


def test_roundtrip_preserves_every_field(bundle, raw):
    rt = ProofBundle.from_bytes(raw)
    assert rt.query == bundle.query
    assert rt.params == bundle.params
    assert rt.cfg == bundle.cfg
    assert len(rt.steps) == len(bundle.steps)
    for a, b in zip(rt.steps, bundle.steps):
        assert a.kind == b.kind and a.shape == b.shape
        assert a.data_desc == b.data_desc
        assert np.array_equal(a.instance, b.instance)
        assert a.instance.dtype == np.uint32
        assert sorted(a.proof.openings) == sorted(b.proof.openings)
        assert a.proof.size_fields() == b.proof.size_fields()
    assert set(rt.result) == set(bundle.result)


def test_proof_and_fri_standalone_roundtrip(bundle):
    proof = bundle.steps[0].proof
    from repro.core.prover import Proof
    from repro.core.fri import FriProof
    p2 = Proof.from_bytes(proof.to_bytes())
    assert p2.to_bytes() == proof.to_bytes()
    assert np.array_equal(p2.data_root, proof.data_root)
    f2 = FriProof.from_bytes(proof.fri_proof.to_bytes())
    assert f2.to_bytes() == proof.fri_proof.to_bytes()
    assert np.array_equal(f2.query_indices, proof.fri_proof.query_indices)


def test_decoded_arrays_are_writable(raw):
    rt = ProofBundle.from_bytes(raw)
    rt.steps[0].instance[0, 0] = 7      # tamper tests rely on this


# ---------------------------------------------------------------------------
# malformed input: every deviation is a typed error, never a crash/exec
# ---------------------------------------------------------------------------
def test_truncation_rejected(raw):
    for cut in (0, 1, HEADER - 1, HEADER, HEADER + 3, len(raw) // 2,
                len(raw) - 1):
        with pytest.raises(WireFormatError):
            ProofBundle.from_bytes(raw[:cut])


def test_trailing_bytes_rejected(raw):
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(raw + b"\x00")


def test_legacy_pickle_rejected(bundle):
    blob = pickle.dumps(bundle, protocol=pickle.HIGHEST_PROTOCOL)
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(blob)


def test_bad_magic_rejected(raw):
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(b"NOPE" + raw[4:])


def test_version_mismatch_rejected_and_verify_bytes_false(raw, verifier):
    future = raw[:4] + struct.pack("<H", wire.WIRE_VERSION + 1) + raw[6:]
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(future)
    # the serving path fails closed, it does not crash
    assert verifier.verify_bytes(future) is False
    assert verifier.verify_bytes(b"junk") is False
    assert verifier.verify_bytes(raw) is True


def test_v3_bytes_fail_closed(raw, verifier):
    """Wire v4 dropped the proof's timings field; a v3 message is refused
    on its version, before any field is read."""
    v3 = raw[:4] + struct.pack("<H", 3) + raw[6:]
    with pytest.raises(WireFormatError, match="unsupported wire version 3"):
        ProofBundle.from_bytes(v3)
    assert verifier.verify_bytes(v3) is False


def test_proofs_carry_no_timings(bundle):
    """Phase timings are in-memory telemetry: they do not reach the wire,
    and a decoded proof has none."""
    import dataclasses
    from repro.core.prover import Proof
    proof = bundle.steps[0].proof
    timed = dataclasses.replace(proof, timings={"fri": 1.5, "total": 9.0})
    bare = dataclasses.replace(proof, timings={})
    assert timed.to_bytes() == bare.to_bytes() == proof.to_bytes()
    assert Proof.from_bytes(timed.to_bytes()).timings == {}
    assert all(s.proof.timings == {}
               for s in ProofBundle.from_bytes(bundle.to_bytes()).steps)


def test_payload_kind_confusion_rejected(bundle, raw):
    proof_bytes = bundle.steps[0].proof.to_bytes()
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(proof_bytes)       # a Proof is not a bundle
    from repro.core.prover import Proof
    with pytest.raises(WireFormatError):
        Proof.from_bytes(raw)                     # and vice versa


def test_flipped_field_tag_rejected(raw):
    flipped = bytearray(raw)
    flipped[HEADER] ^= 0xFF                       # first field tag (query)
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(bytes(flipped))


def test_oversized_length_prefix_rejected(raw):
    # the query-string length prefix sits right after its field tag
    huge = raw[: HEADER + 1] + struct.pack("<I", 0xFFFFFFFF) + \
        raw[HEADER + 5:]
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(huge)
    # a plausible-but-too-long length must hit the bound, not allocate
    biggish = raw[: HEADER + 1] + struct.pack("<I", wire.MAX_STR + 1) + \
        raw[HEADER + 5:]
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(biggish)


def test_wrong_dtype_array_rejected(bundle):
    fri_bytes = bytearray(bundle.steps[0].proof.fri_proof.to_bytes())
    # layout: header, tag(_F_FRI_ROOTS), u32 count, then dtype code byte
    dtype_off = HEADER + 1 + 4
    fri_bytes[dtype_off] = 1                      # int64 where u32 expected
    from repro.core.fri import FriProof
    with pytest.raises(WireFormatError):
        FriProof.from_bytes(bytes(fri_bytes))
    fri_bytes[dtype_off] = 99                     # unknown dtype code
    with pytest.raises(WireFormatError):
        FriProof.from_bytes(bytes(fri_bytes))


def test_unknown_step_kind_rejected(bundle):
    clone = ProofBundle.from_bytes(bundle.to_bytes())
    clone.steps[0].kind = "evil_operator"
    with pytest.raises(WireFormatError):
        clone.to_bytes()                          # encode validates too
    raw = bundle.to_bytes()
    patched = raw.replace(b"expand", b"expanq")
    with pytest.raises(WireFormatError):
        ProofBundle.from_bytes(patched)


def test_shape_schema_checked(bundle):
    with pytest.raises(WireFormatError):
        wire.check_shape_schema("expand", dict(n_rows=64))     # missing keys
    with pytest.raises(WireFormatError):
        wire.check_shape_schema("expand", dict(
            n_rows=64, m_edges=48, with_prop=False, reverse=False, evil=1))
    with pytest.raises(WireFormatError):
        wire.check_shape_schema("expand", dict(                # bool != int
            n_rows=True, m_edges=48, with_prop=False, reverse=False))
    with pytest.raises(WireFormatError):
        wire.check_shape_schema("expand", dict(                # int != bool
            n_rows=64, m_edges=48, with_prop=0, reverse=False))
    with pytest.raises(WireFormatError):
        wire.check_shape_schema("no_such_kind", dict(n_rows=64))


def test_unknown_query_name_fails_closed(raw, verifier):
    b = ProofBundle.from_bytes(raw)
    b.query = "IC999"
    assert verifier.verify(b) is False


def test_deep_nesting_rejected_not_recursion_error(bundle, verifier):
    """A ~2.5KB payload of nested single-element lists must hit the depth
    cap as WireFormatError — a RecursionError would crash verify_bytes
    instead of failing closed."""
    deep = bytearray()
    for _ in range(500):
        deep.append(wire._T_LIST)
        deep += struct.pack("<I", 1)
    deep.append(wire._T_INT)
    deep += struct.pack("<q", 0)
    with pytest.raises(WireFormatError, match="nesting"):
        wire._Dec(bytes(deep)).value()
    # the encoder refuses to produce such bytes in the first place
    nested = 0
    for _ in range(500):
        nested = [nested]
    clone = ProofBundle.from_bytes(bundle.to_bytes())
    clone.params = dict(evil=nested)
    with pytest.raises(WireFormatError, match="nesting"):
        clone.to_bytes()


def test_non_canonical_dict_rejected():
    e = wire._Enc()
    e.u8(wire._T_DICT)
    e.u32(2)
    for key in ("b", "a"):                        # out of sorted order
        e.u8(wire._T_STR)
        e.string(key)
        e.u8(wire._T_INT)
        e.i64(1)
    with pytest.raises(WireFormatError):
        wire._Dec(bytes(e.buf)).value()


def test_byte_flips_never_crash(raw, verifier):
    """Flipping any byte either raises WireFormatError or yields a bundle
    the verifier handles without crashing — malformed bundles are *invalid
    proofs*, not exceptions. A few surviving decodes are pushed through
    verify to prove the no-crash property end to end."""
    rng = np.random.default_rng(7)
    checked = 0
    for pos in rng.integers(0, len(raw), size=24):
        flipped = bytearray(raw)
        flipped[pos] ^= 0x40
        try:
            b = ProofBundle.from_bytes(bytes(flipped))
        except WireFormatError:
            continue
        if checked < 3:
            # a flip that survives decode landed in payload data (arrays,
            # floats): verify must return a clean bool, never raise
            assert verifier.verify(b) in (True, False)
            checked += 1


# ---------------------------------------------------------------------------
# the closed geometry gap, end to end through the wire
# ---------------------------------------------------------------------------
def test_reencoded_tampered_n_rows_fails_via_manifest(bundle, owner,
                                                      verifier):
    """Acceptance: a bundle re-encoded with a tampered base-table n_rows —
    at a size the owner even published a root for — must now fail via the
    manifest geometry pin (the shape is schema-valid, so only the published
    geometry can catch it)."""
    clone = ProofBundle.from_bytes(bundle.to_bytes())
    rec = clone.steps[0]
    assert rec.data_desc == "hasCreator"
    bigger = rec.shape["n_rows"] * 2
    assert ("hasCreator", bigger) in owner.commitments
    rec.shape = dict(rec.shape, n_rows=bigger)
    rewired = ProofBundle.from_bytes(clone.to_bytes())   # survives the codec
    assert rewired.steps[0].shape["n_rows"] == bigger
    assert verifier.verify(rewired) is False             # dies at the pin


def test_reencoded_tampered_m_edges_fails_via_manifest(bundle, verifier):
    clone = ProofBundle.from_bytes(bundle.to_bytes())
    rec = clone.steps[0]
    rec.shape = dict(rec.shape, m_edges=rec.shape["m_edges"] - 1)
    rewired = ProofBundle.from_bytes(clone.to_bytes())
    assert verifier.verify(rewired) is False


def test_no_pickle_in_session_module():
    """The trust boundary ships no pickle: neither the session module nor
    the codec imports it."""
    import repro.core.session as session_mod
    import repro.core.wire as wire_mod
    import inspect
    for mod in (session_mod, wire_mod):
        assert not hasattr(mod, "pickle")
        assert "import pickle" not in inspect.getsource(mod)
