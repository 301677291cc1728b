"""Golden wire-format vectors: the byte-level spec in docs/protocol.md is
checked against bytes committed under tests/vectors/, so neither the codec
nor the doc can silently drift.  Each vector is rebuilt programmatically and
must equal the committed hex byte-for-byte; the committed hex must decode
and re-encode to itself; digests and log proofs must verify.

Regenerate after an INTENTIONAL format change (and update docs/protocol.md):

    PYTHONPATH=src python tests/test_vectors.py --write
"""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import gossip as gp
from repro.core import transparency as tl
from repro.core import wire
from repro.core.commit import (CommitmentManifest, MANIFEST_VERSION,
                               TableGeometry)
from repro.core.ed25519 import SigningKey

VECTOR_DIR = Path(__file__).resolve().parent / "vectors"


# ---------------------------------------------------------------------------
# deterministic builders (no database, no randomness, no timestamps)
# ---------------------------------------------------------------------------
def build_manifest() -> CommitmentManifest:
    """A tiny two-table manifest with fixed roots — the spec's worked
    example (docs/protocol.md §7)."""
    roots = {
        ("knows", 8): np.arange(8, dtype=np.uint32),
        ("knows", 16): np.arange(8, 16, dtype=np.uint32),
        ("person_name", 8): np.full(8, 7, dtype=np.uint32),
    }
    tables = {
        "knows": TableGeometry("knows", 2, 5, (8, 16), ("src", "dst")),
        "person_name": TableGeometry("person_name", 2, 4, (8,),
                                     ("id", "name")),
    }
    return CommitmentManifest(MANIFEST_VERSION, 6,
                              {"person_knows_person": 5}, tables, roots)


def build_log() -> tl.TransparencyLog:
    """A 5-leaf log: leaf 0 is the manifest vector, later leaves are
    distinct revisions of it."""
    log = tl.TransparencyLog("zkgraph-vector-log")
    raw = build_manifest().to_bytes()
    log.append(raw)
    for i in range(4):
        log.append(raw + bytes([i]))
    return log


def build_value() -> bytes:
    """A kitchen-sink `value` exercising every tag of the value grammar
    (docs/protocol.md §2)."""
    e = wire._Enc()
    e.value({
        "arr": np.array([[1, 2], [3, 4]], np.uint32),
        "bool": True,
        "float": 2.5,
        "int": -7,
        "list": [1, "two"],
        "str": "zkgraph",
        "tuple": (np.array([5], np.int64), False),
    })
    return bytes(e.buf)


def _u32s_to_bytes(digest: np.ndarray) -> bytes:
    return np.asarray(digest, np.uint32).astype("<u4").tobytes()


VECTOR_GOSSIP_KEY = SigningKey.from_secret(b"zkgraph-vector-gossip-key")


def build_gossip() -> gp.GossipMessage:
    """The vector log's size-5 head as an Ed25519-signed gossip message
    carrying the 3 -> 5 consistency proof (docs/protocol.md §10)."""
    return gp.emit(build_log(), VECTOR_GOSSIP_KEY, since=3)


def vectors() -> dict:
    manifest_raw = build_manifest().to_bytes()
    log = build_log()
    return {
        "manifest.hex": manifest_raw,
        "manifest_digest.hex": _u32s_to_bytes(tl.manifest_digest(
            manifest_raw)),
        "checkpoint_size5.hex": log.checkpoint().to_bytes(),
        "checkpoint_size3.hex": log.checkpoint(3).to_bytes(),
        "inclusion_leaf0_size5.hex": log.inclusion_proof(0).to_bytes(),
        "consistency_3_to_5.hex": log.consistency_proof(3).to_bytes(),
        "value_kitchen_sink.hex": build_value(),
        "gossip_head_3_to_5.hex": build_gossip().to_bytes(),
        "logstore_5_leaves.hex": build_store_bytes(),
    }


def build_store_bytes() -> bytes:
    """The exact on-disk bytes of a durable store holding the vector log
    (docs/protocol.md §9): magic, origin record, and per append an entry
    record followed by its checkpoint record — all CRC-framed and
    position-bound (each record's CRC covers its file offset)."""
    from repro.core import logstore as ls
    log = build_log()
    out = bytearray(ls.STORE_MAGIC)
    out += ls.frame_record(ls.REC_ORIGIN, log.origin.encode("utf-8"),
                           len(out))
    for i in range(log.size):
        out += ls.frame_record(ls.REC_ENTRY, log.entry(i), len(out))
        out += ls.frame_record(ls.REC_CHECKPOINT,
                               log.checkpoint(i + 1).to_bytes(), len(out))
    return bytes(out)


def _read(name: str) -> bytes:
    path = VECTOR_DIR / name
    assert path.exists(), \
        f"missing golden vector {name}; regenerate with " \
        f"`PYTHONPATH=src python tests/test_vectors.py --write`"
    return bytes.fromhex(path.read_text().strip())


# ---------------------------------------------------------------------------
# the vectors hold
# ---------------------------------------------------------------------------
def test_builders_reproduce_committed_bytes():
    for name, built in vectors().items():
        assert built == _read(name), f"vector {name} drifted from the codec"


def test_manifest_vector_decodes_and_reencodes():
    raw = _read("manifest.hex")
    m = CommitmentManifest.from_bytes(raw)
    assert m.to_bytes() == raw
    assert m.n_nodes == 6
    assert m.geometry("knows").columns == ("src", "dst")
    assert np.array_equal(m.root("knows", 16),
                          np.arange(8, 16, dtype=np.uint32))


def test_manifest_digest_vector():
    digest = np.frombuffer(_read("manifest_digest.hex"), "<u4")
    assert np.array_equal(tl.manifest_digest(_read("manifest.hex")), digest)


def test_checkpoint_and_proof_vectors_verify():
    cp5 = tl.Checkpoint.from_bytes(_read("checkpoint_size5.hex"))
    cp3 = tl.Checkpoint.from_bytes(_read("checkpoint_size3.hex"))
    incl = tl.InclusionProof.from_bytes(_read("inclusion_leaf0_size5.hex"))
    cons = tl.ConsistencyProof.from_bytes(_read("consistency_3_to_5.hex"))
    assert cp5.to_bytes() == _read("checkpoint_size5.hex")
    assert (cp5.origin, cp5.tree_size) == ("zkgraph-vector-log", 5)
    digest = np.frombuffer(_read("manifest_digest.hex"), "<u4")
    assert tl.verify_inclusion(cp5, incl, digest)
    assert tl.verify_consistency(cp3, cp5, cons)
    # and the binding is real: the digest of different bytes is NOT included
    other = tl.manifest_digest(_read("manifest.hex") + b"\x00")
    assert not tl.verify_inclusion(cp5, incl, other)


def test_value_vector_decodes_to_expected_object():
    raw = _read("value_kitchen_sink.hex")
    got = wire._Dec(raw).value()
    assert got["int"] == -7 and got["bool"] is True and got["float"] == 2.5
    assert got["str"] == "zkgraph" and got["list"] == [1, "two"]
    assert np.array_equal(got["arr"], [[1, 2], [3, 4]])
    assert np.array_equal(got["tuple"][0], [5]) and got["tuple"][1] is False
    # canonical: re-encoding the decoded object reproduces the bytes
    e = wire._Enc()
    e.value(got)
    assert bytes(e.buf) == raw


def test_gossip_vector_verifies_end_to_end():
    raw = _read("gossip_head_3_to_5.hex")
    msg = gp.GossipMessage.from_bytes(raw)
    assert msg.to_bytes() == raw
    assert msg.signer == VECTOR_GOSSIP_KEY.pub
    assert gp.verify_signature(msg.signer, msg.checkpoint, msg.signature)
    cp3 = tl.Checkpoint.from_bytes(_read("checkpoint_size3.hex"))
    assert tl.verify_consistency(cp3, msg.checkpoint, msg.consistency)
    # a peer pinned at the size-3 vector checkpoint advances on exactly it
    peer = gp.GossipPeer("zkgraph-vector-log", VECTOR_GOSSIP_KEY.pub)
    peer.offer(gp.GossipMessage(
        cp3, None, VECTOR_GOSSIP_KEY.pub,
        gp.sign_checkpoint(VECTOR_GOSSIP_KEY, cp3)))
    assert peer.offer(msg) is True
    assert peer.pinned.tree_size == 5


def test_logstore_vector_replays_to_the_vector_log():
    from repro.core import logstore as ls
    raw = _read("logstore_5_leaves.hex")
    origin, entries, checkpoints, intact = ls.replay(raw)
    assert intact == len(raw)
    assert origin == "zkgraph-vector-log"
    log = build_log()
    assert entries == [log.entry(i) for i in range(log.size)]
    assert [cp.tree_size for _, cp in checkpoints] == [1, 2, 3, 4, 5]
    assert np.array_equal(checkpoints[-1][1].root, log.root())
    # and a torn tail inside the final (checkpoint) record truncates back
    # to exactly the end of the last intact record
    last_cp = log.checkpoint(5).to_bytes()
    last_start = len(raw) - (5 + len(last_cp) + 4)   # hdr + payload + crc
    assert ls.frame_record(ls.REC_CHECKPOINT, last_cp, last_start) \
        == raw[last_start:]
    _, entries, _, intact2 = ls.replay(raw[:-5])
    assert len(entries) == 5 and intact2 == last_start


def test_wire_constants_pinned():
    """The spec constants in docs/protocol.md §1 are written against these
    values; bump the doc and regenerate vectors when changing them."""
    assert wire.MAGIC == b"ZKGB"
    assert wire.WIRE_VERSION == 4
    assert (wire.KIND_BUNDLE, wire.KIND_PROOF, wire.KIND_FRI,
            wire.KIND_MANIFEST, wire.KIND_CHECKPOINT, wire.KIND_INCLUSION,
            wire.KIND_CONSISTENCY, wire.KIND_GOSSIP) == (1, 2, 3, 4, 5, 6,
                                                         7, 9)
    assert wire._KIND_GOSSIP_MAC_RETIRED == 8   # never reused
    assert (wire.SIGNER_LEN, wire.SIG_LEN) == (32, 64)


if __name__ == "__main__":
    if "--write" not in sys.argv:
        sys.exit("usage: PYTHONPATH=src python tests/test_vectors.py --write")
    VECTOR_DIR.mkdir(exist_ok=True)
    for name, built in vectors().items():
        (VECTOR_DIR / name).write_text(built.hex() + "\n")
        print(f"wrote {name}: {len(built)} bytes")
