"""Canonical proof-bundle wire format: versioned, deterministic, bounded.

This replaces the seed's pickle serialization of :class:`ProofBundle` — the
one place where attacker-controlled bytes crossed the verifier's trust
boundary (paper §III-C assumes the verifier trusts only the owner's published
commitments).  Design rules:

* **No code execution on decode.**  The format is a fixed grammar of tagged
  fields over five primitive kinds (ints, floats, strings, numpy arrays,
  containers); decoding allocates nothing before validating dtype, shape and
  remaining-byte bounds.
* **Versioned.**  Every message starts with ``MAGIC + version + payload
  kind``; a version or kind mismatch raises :class:`WireFormatError` (so a
  verifier fed a legacy / future bundle fails closed instead of
  mis-interpreting bytes).
* **Deterministic.**  Dict entries are sorted by their encoded key bytes and
  the decoder *rejects* out-of-order entries, so every bundle has exactly one
  canonical encoding and ``encode(decode(b)) == b`` byte-for-byte.
* **Bounded.**  Strings, containers, array dims and element counts all have
  hard caps; a length prefix larger than the remaining buffer is an error,
  never an allocation.
* **Schema-checked.**  A step's ``kind`` must name a registered operator
  adapter and its ``shape`` dict must match that adapter's declared
  ``shape_schema`` exactly (key set *and* types, ``bool`` distinct from
  ``int``) — malformed circuit geometry is rejected before the verifier
  does any work.

Grammar (all integers little-endian; the full byte-level spec with golden
test vectors is ``docs/protocol.md``)::

    message   := MAGIC(4) version:u16 kind:u8 body
    bundle    := Q query:str P params:value C cfg(4 x u32) G digest:arr(8,)
                 S nsteps:u32 step* R result:value
    step      := K kind:str H shape:value D desc:str I instance:arr F proof
    proof     := 4 roots:arr(8,) OPEN openings TREE tree_openings
                 FRI friproof
    friproof  := roots:[arr(8,)] final:arr(n,4) qidx:arr(i64)
                 openings:[(rows:arr, paths:arr)]
    manifest  := V mver:u32 N n_nodes:i64 E edge_counts T tables R roots
    checkpt   := O origin:str S tree_size:i64 R root:arr(8,)
    incl      := I leaf_index:i64 S tree_size:i64 P path:arr(d,8)
    consist   := O old_size:i64 N new_size:i64 P path:arr(d,8)
    value     := tagged int | bool | float | str | arr | tuple | list | dict
    arr       := dtype:u8 ndim:u8 dims:u32* raw-bytes

Any deviation — truncation, a flipped tag, an oversized length, a wrong
dtype, trailing bytes — raises :class:`WireFormatError`.
"""
from __future__ import annotations

import struct

import numpy as np

MAGIC = b"ZKGB"
WIRE_VERSION = 4     # v4: a proof ends at its FRI proof (the v3 timings
                     # field 0x24 is gone); v3: gossip envelopes carry
                     # Ed25519 detached signatures (kind 9), and the v2
                     # MAC-era envelope (kind 8) is rejected by name

# payload kinds (a message's top-level type)
KIND_BUNDLE = 1
KIND_PROOF = 2
KIND_FRI = 3
KIND_MANIFEST = 4
KIND_CHECKPOINT = 5
KIND_INCLUSION = 6
KIND_CONSISTENCY = 7
_KIND_GOSSIP_MAC_RETIRED = 8    # v2 MAC-era envelope; never decoded again
KIND_GOSSIP = 9      # v3 signed envelope (Ed25519 over checkpoint bytes)
KIND_FEDERATED = 10  # multi-owner federated bundle (repro.federation)
KIND_FEDREQ = 11     # coordinator -> owner sub-plan prove request
KIND_FEDRESP = 12    # owner -> coordinator sub-plan prove response

# the federated envelope carries its own version so it can evolve without
# touching WIRE_VERSION (the kinds above were added additively under v3:
# older decoders reject unknown kinds, they never mis-parse them)
FED_VERSION = 1

# hard caps: a malformed length prefix can never trigger a large allocation
MAX_STR = 4096
MAX_ITEMS = 1 << 16          # container entries (dict / list / tuple)
MAX_STEPS = 64
MAX_ARR_DIMS = 4
MAX_ARR_ELEMS = 1 << 24      # per-array element cap (64 MiB of int64)
MAX_FRI_LAYERS = 64
MAX_DEPTH = 16               # value-nesting cap (no RecursionError from bytes)
MAX_TABLES = 256             # manifest: registered base-table descriptors
MAX_SIZES = 64               # manifest: published circuit sizes per table
MAX_COLUMNS = 64             # manifest: named columns per table
MAX_LOG_DEPTH = 64           # transparency log: audit/consistency path nodes
MAX_EMBED = 1 << 20          # gossip: embedded checkpoint/proof message bytes
MAX_PARTS = 16               # federated bundle: per-owner sub-plan parts
MAX_HANDOFF = 256            # federated bundle: hand-off entries per joint

# value tags
_T_INT, _T_BOOL, _T_FLOAT, _T_STR, _T_ARR, _T_TUPLE, _T_LIST, _T_DICT = \
    range(1, 9)

# struct field tags (explicit, one per field, checked in order)
_F_QUERY, _F_PARAMS, _F_CFG, _F_STEPS, _F_RESULT, _F_DIGEST = \
    0x01, 0x02, 0x03, 0x04, 0x05, 0x06
_F_KIND, _F_SHAPE, _F_DESC, _F_INSTANCE, _F_PROOF = \
    0x10, 0x11, 0x12, 0x13, 0x14
_F_ROOTS, _F_OPENINGS, _F_TREES, _F_FRI = 0x20, 0x21, 0x22, 0x23
# 0x24 was the v3 proof timings (host telemetry); retired, never reused
_F_FRI_ROOTS, _F_FRI_FINAL, _F_FRI_QIDX, _F_FRI_OPENS = \
    0x30, 0x31, 0x32, 0x33
_F_M_VERSION, _F_M_NNODES, _F_M_EDGES, _F_M_TABLES, _F_M_ROOTS = \
    0x40, 0x41, 0x42, 0x43, 0x44
_F_C_ORIGIN, _F_C_SIZE, _F_C_ROOT = 0x50, 0x51, 0x52
_F_I_INDEX, _F_I_SIZE, _F_I_PATH = 0x60, 0x61, 0x62
_F_Y_OLD, _F_Y_NEW, _F_Y_PATH = 0x70, 0x71, 0x72
_F_G_CHECKPOINT, _F_G_CONSIST = 0x80, 0x81
# 0x82 was the v2 MAC authenticator; retired with kind 8, never reused
_F_G_SIGNER, _F_G_SIG = 0x83, 0x84
_F_FB_VERSION, _F_FB_QUERY, _F_FB_PARAMS, _F_FB_CFG, _F_FB_PARTS, \
    _F_FB_RESULT = 0x90, 0x91, 0x92, 0x93, 0x94, 0x95
_F_FP_OWNER, _F_FP_LO, _F_FP_HI, _F_FP_DIGEST, _F_FP_STEPS, _F_FP_HANDOFF, \
    _F_FP_SIG = 0xA0, 0xA1, 0xA2, 0xA3, 0xA4, 0xA5, 0xA6
_F_FR_QUERY, _F_FR_PARAMS, _F_FR_CFG, _F_FR_LO, _F_FR_HI, _F_FR_SEEDS, \
    _F_FR_SEEDS_SIG, _F_FR_EXPORTS = 0xB0, 0xB1, 0xB2, 0xB3, 0xB4, 0xB5, \
    0xB6, 0xB7
_F_FS_PART, _F_FS_EXPORTS, _F_FS_SIG, _F_FS_RESULT = 0xC0, 0xC1, 0xC2, 0xC3

# Ed25519 material carried by the signed gossip envelope (raw, fixed-width)
SIGNER_LEN = 32      # compressed Edwards verify key (repro.core.ed25519)
SIG_LEN = 64         # detached signature R || S

_DTYPES = {0: np.dtype("<u4"), 1: np.dtype("<i8")}
_DTYPE_CODE = {np.dtype(np.uint32): 0, np.dtype(np.int64): 1}

_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1


class WireFormatError(ValueError):
    """Malformed wire bytes: truncated, mistagged, oversized, mistyped, or
    schema-violating input.  Decoding raises this instead of executing or
    trusting anything; ``ZKGraphSession.verify_bytes`` maps it to ``False``."""


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------
class _Enc:
    def __init__(self):
        self.buf = bytearray()

    def u8(self, v: int):
        self.buf += struct.pack("<B", v)

    def u16(self, v: int):
        self.buf += struct.pack("<H", v)

    def u32(self, v: int):
        if not 0 <= int(v) < (1 << 32):
            raise WireFormatError(f"u32 out of range: {v}")
        self.buf += struct.pack("<I", int(v))

    def i64(self, v: int):
        v = int(v)
        if not _I64_MIN <= v <= _I64_MAX:
            raise WireFormatError(f"integer does not fit in i64: {v}")
        self.buf += struct.pack("<q", v)

    def f64(self, v: float):
        self.buf += struct.pack("<d", float(v))

    def string(self, s: str):
        if not isinstance(s, str):
            raise WireFormatError(f"expected str, got {type(s).__name__}")
        raw = s.encode("utf-8")
        if len(raw) > MAX_STR:
            raise WireFormatError(f"string too long: {len(raw)} > {MAX_STR}")
        self.u32(len(raw))
        self.buf += raw

    def array(self, a, dtype=None, ndim=None):
        a = np.ascontiguousarray(a)
        if dtype is not None:
            a = np.ascontiguousarray(a, np.dtype(dtype))
        code = _DTYPE_CODE.get(a.dtype.newbyteorder("<"))
        if code is None:
            code = _DTYPE_CODE.get(a.dtype)
        if code is None:
            raise WireFormatError(f"unsupported array dtype {a.dtype}")
        if ndim is not None and a.ndim != ndim:
            raise WireFormatError(f"expected {ndim}-d array, got {a.ndim}-d")
        if a.ndim > MAX_ARR_DIMS or a.size > MAX_ARR_ELEMS:
            raise WireFormatError(f"array too large: shape {a.shape}")
        self.u8(code)
        self.u8(a.ndim)
        for d in a.shape:
            self.u32(d)
        self.buf += a.astype(_DTYPES[code], copy=False).tobytes()

    def value(self, v, depth: int = 0):
        if depth > MAX_DEPTH:
            raise WireFormatError(f"value nesting deeper than {MAX_DEPTH}")
        if isinstance(v, bool) or isinstance(v, np.bool_):
            self.u8(_T_BOOL)
            self.u8(1 if v else 0)
        elif isinstance(v, (int, np.integer)):
            self.u8(_T_INT)
            self.i64(v)
        elif isinstance(v, (float, np.floating)):
            self.u8(_T_FLOAT)
            self.f64(v)
        elif isinstance(v, str):
            self.u8(_T_STR)
            self.string(v)
        elif isinstance(v, np.ndarray):
            self.u8(_T_ARR)
            self.array(v)
        elif isinstance(v, tuple):
            self.u8(_T_TUPLE)
            self._seq(v, depth)
        elif isinstance(v, list):
            self.u8(_T_LIST)
            self._seq(v, depth)
        elif isinstance(v, dict):
            self.u8(_T_DICT)
            self._dict(v, depth)
        else:
            raise WireFormatError(
                f"value of type {type(v).__name__} is not wire-encodable")

    def _seq(self, items, depth: int):
        if len(items) > MAX_ITEMS:
            raise WireFormatError(f"container too large: {len(items)}")
        self.u32(len(items))
        for it in items:
            self.value(it, depth + 1)

    def _dict(self, d: dict, depth: int):
        if len(d) > MAX_ITEMS:
            raise WireFormatError(f"dict too large: {len(d)}")
        encoded = []
        for k, v in d.items():
            ek = _Enc()
            ek.value(k, depth + 1)
            encoded.append((bytes(ek.buf), v))
        encoded.sort(key=lambda kv: kv[0])
        for i in range(1, len(encoded)):
            if encoded[i][0] == encoded[i - 1][0]:
                raise WireFormatError("duplicate dict key")
        self.u32(len(encoded))
        for kb, v in encoded:
            self.buf += kb
            self.value(v, depth + 1)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------
class _Dec:
    def __init__(self, raw: bytes):
        if not isinstance(raw, (bytes, bytearray, memoryview)):
            raise WireFormatError(
                f"expected bytes, got {type(raw).__name__}")
        self.raw = bytes(raw)
        self.pos = 0

    def take(self, n: int) -> bytes:
        if n < 0 or self.pos + n > len(self.raw):
            raise WireFormatError(
                f"truncated input: need {n} bytes at offset {self.pos}, "
                f"have {len(self.raw) - self.pos}")
        out = self.raw[self.pos: self.pos + n]
        self.pos += n
        return out

    def done(self):
        if self.pos != len(self.raw):
            raise WireFormatError(
                f"{len(self.raw) - self.pos} trailing bytes after message")

    def u8(self) -> int:
        return self.take(1)[0]

    def u16(self) -> int:
        return struct.unpack("<H", self.take(2))[0]

    def u32(self) -> int:
        return struct.unpack("<I", self.take(4))[0]

    def i64(self) -> int:
        return struct.unpack("<q", self.take(8))[0]

    def f64(self) -> float:
        return struct.unpack("<d", self.take(8))[0]

    def tag(self, expected: int, what: str):
        got = self.u8()
        if got != expected:
            raise WireFormatError(
                f"bad field tag for {what}: expected {expected:#x}, "
                f"got {got:#x}")

    def string(self) -> str:
        n = self.u32()
        if n > MAX_STR:
            raise WireFormatError(f"string length {n} > {MAX_STR}")
        try:
            return self.take(n).decode("utf-8")
        except UnicodeDecodeError as e:
            raise WireFormatError(f"invalid utf-8 string: {e}") from None

    def array(self, dtype=None, ndim=None, shape=None) -> np.ndarray:
        code = self.u8()
        dt = _DTYPES.get(code)
        if dt is None:
            raise WireFormatError(f"unknown array dtype code {code}")
        if dtype is not None and dt != np.dtype(dtype):
            raise WireFormatError(
                f"expected {np.dtype(dtype)} array, got {dt}")
        nd = self.u8()
        if nd > MAX_ARR_DIMS:
            raise WireFormatError(f"array rank {nd} > {MAX_ARR_DIMS}")
        if ndim is not None and nd != ndim:
            raise WireFormatError(f"expected {ndim}-d array, got {nd}-d")
        dims = []
        elems = 1
        for _ in range(nd):
            d = self.u32()
            dims.append(d)
            elems *= max(d, 1)
            if elems > MAX_ARR_ELEMS:
                raise WireFormatError(f"array too large: dims {dims}")
        if shape is not None and tuple(dims) != tuple(shape):
            raise WireFormatError(
                f"expected array shape {tuple(shape)}, got {tuple(dims)}")
        nbytes = int(np.prod(dims, dtype=np.int64)) * dt.itemsize
        raw = self.take(nbytes)
        # .copy(): callers mutate instances/results; frombuffer is read-only
        return np.frombuffer(raw, dtype=dt).reshape(dims).copy()

    def value(self, depth: int = 0):
        if depth > MAX_DEPTH:
            raise WireFormatError(f"value nesting deeper than {MAX_DEPTH}")
        t = self.u8()
        if t == _T_BOOL:
            b = self.u8()
            if b not in (0, 1):
                raise WireFormatError(f"non-canonical bool byte {b}")
            return bool(b)
        if t == _T_INT:
            return self.i64()
        if t == _T_FLOAT:
            return self.f64()
        if t == _T_STR:
            return self.string()
        if t == _T_ARR:
            return self.array()
        if t in (_T_TUPLE, _T_LIST):
            n = self.u32()
            if n > MAX_ITEMS:
                raise WireFormatError(f"container length {n} > {MAX_ITEMS}")
            items = [self.value(depth + 1) for _ in range(n)]
            return tuple(items) if t == _T_TUPLE else items
        if t == _T_DICT:
            n = self.u32()
            if n > MAX_ITEMS:
                raise WireFormatError(f"dict length {n} > {MAX_ITEMS}")
            out = {}
            prev = None
            for _ in range(n):
                start = self.pos
                k = self.value(depth + 1)
                kb = self.raw[start: self.pos]
                if prev is not None and kb <= prev:
                    raise WireFormatError(
                        "non-canonical dict: keys not strictly sorted")
                prev = kb
                try:
                    out[k] = None
                except TypeError:
                    raise WireFormatError(
                        f"unhashable dict key {k!r}") from None
                out[k] = self.value(depth + 1)
            return out
        raise WireFormatError(f"unknown value tag {t:#x}")


# ---------------------------------------------------------------------------
# schema validation for step shapes
# ---------------------------------------------------------------------------
def check_shape_schema(kind: str, shape) -> dict:
    """Validate a step's declared circuit geometry against the registered
    adapter's ``shape_schema``: exact key set, exact value types (``bool`` is
    *not* accepted where ``int`` is declared, and vice versa)."""
    from .operators import registry
    if not isinstance(shape, dict):
        raise WireFormatError(
            f"step shape must be a dict, got {type(shape).__name__}")
    try:
        schema = registry.adapter_named(kind).shape_schema
    except KeyError:
        raise WireFormatError(f"unknown step kind {kind!r}") from None
    if set(shape) != set(schema):
        raise WireFormatError(
            f"step {kind!r} shape keys {sorted(shape)} do not match "
            f"schema {sorted(schema)}")
    for key, typ in schema.items():
        if type(shape[key]) is not typ:
            raise WireFormatError(
                f"step {kind!r} shape field {key!r} must be "
                f"{typ.__name__}, got {type(shape[key]).__name__}")
    return shape


# ---------------------------------------------------------------------------
# FriProof
# ---------------------------------------------------------------------------
def _fri_to_wire(e: _Enc, fp):
    if len(fp.layer_roots) > MAX_FRI_LAYERS:
        raise WireFormatError(f"too many FRI layers: {len(fp.layer_roots)}")
    if len(fp.layer_openings) != len(fp.layer_roots):
        raise WireFormatError("FRI layer roots/openings count mismatch")
    e.u8(_F_FRI_ROOTS)
    e.u32(len(fp.layer_roots))
    for r in fp.layer_roots:
        e.array(r, dtype=np.uint32, ndim=1)
    e.u8(_F_FRI_FINAL)
    e.array(fp.final_codeword, dtype=np.uint32, ndim=2)
    e.u8(_F_FRI_QIDX)
    e.array(fp.query_indices, dtype=np.int64, ndim=1)
    e.u8(_F_FRI_OPENS)
    e.u32(len(fp.layer_openings))
    for rows, paths in fp.layer_openings:
        e.array(rows, dtype=np.uint32, ndim=2)
        e.array(paths, dtype=np.uint32, ndim=3)


def _fri_from_wire(d: _Dec):
    from .fri import FriProof
    d.tag(_F_FRI_ROOTS, "fri.layer_roots")
    n_layers = d.u32()
    if n_layers > MAX_FRI_LAYERS:
        raise WireFormatError(f"FRI layer count {n_layers} > {MAX_FRI_LAYERS}")
    roots = [d.array(dtype=np.uint32, ndim=1, shape=(8,))
             for _ in range(n_layers)]
    d.tag(_F_FRI_FINAL, "fri.final_codeword")
    final = d.array(dtype=np.uint32, ndim=2)
    if final.shape[1] != 4:
        raise WireFormatError(
            f"final codeword must be (n, 4), got {final.shape}")
    d.tag(_F_FRI_QIDX, "fri.query_indices")
    qidx = d.array(dtype=np.int64, ndim=1)
    d.tag(_F_FRI_OPENS, "fri.layer_openings")
    n_open = d.u32()
    if n_open != n_layers:
        raise WireFormatError(
            f"FRI openings count {n_open} != layer count {n_layers}")
    openings = []
    for _ in range(n_open):
        rows = d.array(dtype=np.uint32, ndim=2)
        paths = d.array(dtype=np.uint32, ndim=3)
        if paths.shape[0] != rows.shape[0]:
            raise WireFormatError("FRI opening rows/paths leaf-count mismatch")
        openings.append((rows, paths))
    return FriProof(roots, final, qidx, openings)


# ---------------------------------------------------------------------------
# Proof
# ---------------------------------------------------------------------------
def _proof_to_wire(e: _Enc, p):
    e.u8(_F_ROOTS)
    for root in (p.data_root, p.advice_root, p.ext_root, p.quotient_root):
        e.array(root, dtype=np.uint32, ndim=1)
    e.u8(_F_OPENINGS)
    keys = sorted(p.openings)
    if len(keys) > MAX_ITEMS:
        raise WireFormatError(f"too many openings: {len(keys)}")
    e.u32(len(keys))
    for (kind, idx, rot) in keys:
        e.string(kind)
        e.u32(idx)
        e.u32(rot)
        e.array(p.openings[(kind, idx, rot)], dtype=np.uint32, ndim=1)
    e.u8(_F_TREES)
    names = sorted(p.tree_openings)
    e.u32(len(names))
    for name in names:
        rows, paths = p.tree_openings[name]
        e.string(name)
        e.array(rows, dtype=np.uint32, ndim=2)
        e.array(paths, dtype=np.uint32, ndim=3)
    e.u8(_F_FRI)
    _fri_to_wire(e, p.fri_proof)


def _proof_from_wire(d: _Dec):
    from .prover import Proof
    d.tag(_F_ROOTS, "proof.roots")
    roots = [d.array(dtype=np.uint32, ndim=1, shape=(8,)) for _ in range(4)]
    d.tag(_F_OPENINGS, "proof.openings")
    n = d.u32()
    if n > MAX_ITEMS:
        raise WireFormatError(f"openings count {n} > {MAX_ITEMS}")
    openings = {}
    prev = None
    for _ in range(n):
        kind = d.string()
        idx = d.u32()
        rot = d.u32()
        key = (kind, idx, rot)
        if prev is not None and key <= prev:
            raise WireFormatError("non-canonical openings order")
        prev = key
        openings[key] = d.array(dtype=np.uint32, ndim=1, shape=(4,))
    d.tag(_F_TREES, "proof.tree_openings")
    n = d.u32()
    if n > MAX_ITEMS:
        raise WireFormatError(f"tree openings count {n} > {MAX_ITEMS}")
    trees = {}
    prev = None
    for _ in range(n):
        name = d.string()
        if prev is not None and name <= prev:
            raise WireFormatError("non-canonical tree-openings order")
        prev = name
        rows = d.array(dtype=np.uint32, ndim=2)
        paths = d.array(dtype=np.uint32, ndim=3)
        if paths.shape[0] != rows.shape[0]:
            raise WireFormatError("tree opening rows/paths count mismatch")
        trees[name] = (rows, paths)
    d.tag(_F_FRI, "proof.fri_proof")
    fri_proof = _fri_from_wire(d)
    return Proof(roots[0], roots[1], roots[2], roots[3], openings, fri_proof,
                 trees)


# ---------------------------------------------------------------------------
# StepProof / ProofBundle
# ---------------------------------------------------------------------------
def _step_to_wire(e: _Enc, step):
    check_shape_schema(step.kind, step.shape)
    e.u8(_F_KIND)
    e.string(step.kind)
    e.u8(_F_SHAPE)
    e.value(step.shape)
    e.u8(_F_DESC)
    e.string(step.data_desc)
    e.u8(_F_INSTANCE)
    e.array(step.instance, dtype=np.uint32, ndim=2)
    e.u8(_F_PROOF)
    _proof_to_wire(e, step.proof)


def _step_from_wire(d: _Dec):
    from .session import StepProof
    d.tag(_F_KIND, "step.kind")
    kind = d.string()
    d.tag(_F_SHAPE, "step.shape")
    shape = check_shape_schema(kind, d.value())
    d.tag(_F_DESC, "step.data_desc")
    desc = d.string()
    d.tag(_F_INSTANCE, "step.instance")
    instance = d.array(dtype=np.uint32, ndim=2)
    d.tag(_F_PROOF, "step.proof")
    proof = _proof_from_wire(d)
    return StepProof(kind, shape, desc, instance, proof)


def _header(e: _Enc, kind: int):
    e.buf += MAGIC
    e.u16(WIRE_VERSION)
    e.u8(kind)


def _check_header(d: _Dec, kind: int):
    magic = d.take(4)
    if magic != MAGIC:
        raise WireFormatError(
            f"bad magic {magic!r}: not a canonical proof message "
            f"(legacy pickle bundles are not accepted)")
    version = d.u16()
    if version != WIRE_VERSION:
        raise WireFormatError(
            f"unsupported wire version {version} (this verifier speaks "
            f"{WIRE_VERSION})")
    got = d.u8()
    if got == _KIND_GOSSIP_MAC_RETIRED:
        raise WireFormatError(
            "payload kind 8 is the retired MAC-era gossip envelope; "
            "checkpoints are Ed25519-signed since wire v3 (kind 9)")
    if got != kind:
        raise WireFormatError(f"payload kind {got} != expected {kind}")


def encode_bundle(bundle) -> bytes:
    """Canonical bytes for a :class:`repro.core.session.ProofBundle`."""
    e = _Enc()
    _header(e, KIND_BUNDLE)
    e.u8(_F_QUERY)
    e.string(bundle.query)
    e.u8(_F_PARAMS)
    e.value(dict(bundle.params))
    e.u8(_F_CFG)
    for v in (bundle.cfg.blowup, bundle.cfg.n_queries,
              bundle.cfg.fri_final_size, bundle.cfg.shift):
        e.u32(v)
    e.u8(_F_DIGEST)
    digest = bundle.manifest_digest
    if digest is None:
        raise WireFormatError(
            "bundle has no manifest_digest: prove against a published "
            "CommitmentManifest (ZKGraphSession.prove sets it)")
    digest = np.asarray(digest)
    if digest.shape != (8,):
        raise WireFormatError(
            f"manifest digest must have shape (8,), got {digest.shape}")
    e.array(digest, dtype=np.uint32, ndim=1)
    if len(bundle.steps) > MAX_STEPS:
        raise WireFormatError(f"too many steps: {len(bundle.steps)}")
    e.u8(_F_STEPS)
    e.u32(len(bundle.steps))
    for step in bundle.steps:
        _step_to_wire(e, step)
    e.u8(_F_RESULT)
    e.value(dict(bundle.result))
    return bytes(e.buf)


def decode_bundle(raw: bytes):
    """Decode + validate canonical bundle bytes; raises
    :class:`WireFormatError` on any malformed input."""
    from .prover import ProverConfig
    from .session import ProofBundle
    d = _Dec(raw)
    _check_header(d, KIND_BUNDLE)
    d.tag(_F_QUERY, "bundle.query")
    query = d.string()
    d.tag(_F_PARAMS, "bundle.params")
    params = d.value()
    if not isinstance(params, dict) or not all(
            isinstance(k, str) for k in params):
        raise WireFormatError("bundle params must be a str-keyed dict")
    d.tag(_F_CFG, "bundle.cfg")
    cfg = ProverConfig(blowup=d.u32(), n_queries=d.u32(),
                       fri_final_size=d.u32(), shift=d.u32())
    d.tag(_F_DIGEST, "bundle.manifest_digest")
    digest = d.array(dtype=np.uint32, ndim=1, shape=(8,))
    d.tag(_F_STEPS, "bundle.steps")
    n_steps = d.u32()
    if n_steps > MAX_STEPS:
        raise WireFormatError(f"step count {n_steps} > {MAX_STEPS}")
    steps = [_step_from_wire(d) for _ in range(n_steps)]
    d.tag(_F_RESULT, "bundle.result")
    result = d.value()
    if not isinstance(result, dict) or not all(
            isinstance(k, str) for k in result):
        raise WireFormatError("bundle result must be a str-keyed dict")
    d.done()
    return ProofBundle(query, params, steps, result, cfg, digest)


def encode_proof(proof) -> bytes:
    """Standalone canonical bytes for one step's :class:`Proof`."""
    e = _Enc()
    _header(e, KIND_PROOF)
    _proof_to_wire(e, proof)
    return bytes(e.buf)


def decode_proof(raw: bytes):
    d = _Dec(raw)
    _check_header(d, KIND_PROOF)
    p = _proof_from_wire(d)
    d.done()
    return p


def encode_fri_proof(fp) -> bytes:
    """Standalone canonical bytes for a :class:`FriProof`."""
    e = _Enc()
    _header(e, KIND_FRI)
    _fri_to_wire(e, fp)
    return bytes(e.buf)


def decode_fri_proof(raw: bytes):
    d = _Dec(raw)
    _check_header(d, KIND_FRI)
    fp = _fri_from_wire(d)
    d.done()
    return fp


# ---------------------------------------------------------------------------
# CommitmentManifest: the owner's published trust root, canonically encoded
# ---------------------------------------------------------------------------
def _nonneg(v: int, what: str) -> int:
    v = int(v)
    if v < 0:
        raise WireFormatError(f"{what} must be non-negative, got {v}")
    return v


def _root8(root, what: str) -> np.ndarray:
    root = np.asarray(root)
    if root.shape != (8,):
        raise WireFormatError(
            f"{what} must be an (8,) digest, got shape {root.shape}")
    return root


def encode_manifest(manifest) -> bytes:
    """Canonical bytes for a :class:`repro.core.commit.CommitmentManifest`.

    Deterministic (``encode(decode(b)) == b``): edge counts sort by table
    name, geometries by descriptor, roots by ``(descriptor, size)``; the
    decoder rejects out-of-order entries.  Every root entry must name a
    descriptor with published geometry and a size that geometry lists — the
    encoder enforces the same invariants, so the encodable set and the
    decodable set are the same language.  ``transparency.manifest_digest``
    over these bytes is the digest bundles and log leaves bind to.
    """
    from .commit import MANIFEST_VERSION
    e = _Enc()
    _header(e, KIND_MANIFEST)
    e.u8(_F_M_VERSION)
    if manifest.version != MANIFEST_VERSION:
        raise WireFormatError(
            f"manifest version {manifest.version} != {MANIFEST_VERSION}")
    e.u32(manifest.version)
    e.u8(_F_M_NNODES)
    e.i64(_nonneg(manifest.n_nodes, "manifest n_nodes"))
    e.u8(_F_M_EDGES)
    if len(manifest.edge_counts) > MAX_TABLES:
        raise WireFormatError(
            f"too many edge tables: {len(manifest.edge_counts)}")
    e.u32(len(manifest.edge_counts))
    for name in sorted(manifest.edge_counts):
        e.string(name)
        e.i64(_nonneg(manifest.edge_counts[name], f"edge count {name!r}"))
    e.u8(_F_M_TABLES)
    if len(manifest.tables) > MAX_TABLES:
        raise WireFormatError(f"too many tables: {len(manifest.tables)}")
    e.u32(len(manifest.tables))
    for desc in sorted(manifest.tables):
        geo = manifest.tables[desc]
        if geo.desc != desc:
            raise WireFormatError(
                f"geometry desc {geo.desc!r} != manifest key {desc!r}")
        e.string(desc)
        e.u32(_nonneg(geo.n_cols, f"{desc!r} n_cols"))
        e.u32(_nonneg(geo.n_table_rows, f"{desc!r} n_table_rows"))
        if len(geo.sizes) > MAX_SIZES:
            raise WireFormatError(
                f"table {desc!r} has too many sizes: {len(geo.sizes)}")
        e.u32(len(geo.sizes))
        prev = -1
        for n in geo.sizes:
            if int(n) <= prev:
                raise WireFormatError(
                    f"table {desc!r} sizes must be strictly increasing")
            prev = int(n)
            e.u32(n)
        if len(geo.columns) > MAX_COLUMNS:
            raise WireFormatError(
                f"table {desc!r} has too many columns: {len(geo.columns)}")
        e.u32(len(geo.columns))
        for col in geo.columns:
            e.string(col)
    e.u8(_F_M_ROOTS)
    if len(manifest.roots) > MAX_TABLES * MAX_SIZES:
        raise WireFormatError(f"too many roots: {len(manifest.roots)}")
    e.u32(len(manifest.roots))
    for desc, size in sorted(manifest.roots):
        geo = manifest.tables.get(desc)
        if geo is None or int(size) not in {int(s) for s in geo.sizes}:
            raise WireFormatError(
                f"root for {(desc, size)} has no matching published geometry")
        e.string(desc)
        e.u32(size)
        e.array(_root8(manifest.roots[(desc, size)], f"root {(desc, size)}"),
                dtype=np.uint32, ndim=1)
    return bytes(e.buf)


def decode_manifest(raw: bytes):
    """Decode + validate canonical manifest bytes (fails closed on any
    malformed, non-canonical, or version-skewed input)."""
    from .commit import MANIFEST_VERSION, CommitmentManifest, TableGeometry
    d = _Dec(raw)
    _check_header(d, KIND_MANIFEST)
    d.tag(_F_M_VERSION, "manifest.version")
    mver = d.u32()
    if mver != MANIFEST_VERSION:
        raise WireFormatError(
            f"unsupported manifest version {mver} (this verifier speaks "
            f"{MANIFEST_VERSION})")
    d.tag(_F_M_NNODES, "manifest.n_nodes")
    n_nodes = d.i64()
    if n_nodes < 0:
        raise WireFormatError(f"negative n_nodes {n_nodes}")
    d.tag(_F_M_EDGES, "manifest.edge_counts")
    n = d.u32()
    if n > MAX_TABLES:
        raise WireFormatError(f"edge table count {n} > {MAX_TABLES}")
    edge_counts = {}
    prev = None
    for _ in range(n):
        name = d.string()
        if prev is not None and name <= prev:
            raise WireFormatError("non-canonical edge-count order")
        prev = name
        count = d.i64()
        if count < 0:
            raise WireFormatError(f"negative edge count for {name!r}")
        edge_counts[name] = count
    d.tag(_F_M_TABLES, "manifest.tables")
    n = d.u32()
    if n > MAX_TABLES:
        raise WireFormatError(f"table count {n} > {MAX_TABLES}")
    tables = {}
    prev = None
    for _ in range(n):
        desc = d.string()
        if prev is not None and desc <= prev:
            raise WireFormatError("non-canonical table-geometry order")
        prev = desc
        n_cols = d.u32()
        n_table_rows = d.u32()
        n_sizes = d.u32()
        if n_sizes > MAX_SIZES:
            raise WireFormatError(f"size count {n_sizes} > {MAX_SIZES}")
        sizes = []
        last = -1
        for _ in range(n_sizes):
            s = d.u32()
            if s <= last:
                raise WireFormatError(
                    f"table {desc!r} sizes not strictly increasing")
            last = s
            sizes.append(s)
        n_columns = d.u32()
        if n_columns > MAX_COLUMNS:
            raise WireFormatError(f"column count {n_columns} > {MAX_COLUMNS}")
        columns = tuple(d.string() for _ in range(n_columns))
        tables[desc] = TableGeometry(desc, n_cols, n_table_rows,
                                     tuple(sizes), columns)
    d.tag(_F_M_ROOTS, "manifest.roots")
    n = d.u32()
    if n > MAX_TABLES * MAX_SIZES:
        raise WireFormatError(f"root count {n} > {MAX_TABLES * MAX_SIZES}")
    roots = {}
    prev = None
    for _ in range(n):
        desc = d.string()
        size = d.u32()
        if prev is not None and (desc, size) <= prev:
            raise WireFormatError("non-canonical root order")
        prev = (desc, size)
        geo = tables.get(desc)
        if geo is None or size not in geo.sizes:
            raise WireFormatError(
                f"root for {(desc, size)} has no matching published geometry")
        roots[(desc, size)] = d.array(dtype=np.uint32, ndim=1, shape=(8,))
    d.done()
    return CommitmentManifest(mver, n_nodes, edge_counts, tables, roots)


# ---------------------------------------------------------------------------
# transparency-log structures (Checkpoint / InclusionProof / ConsistencyProof)
# ---------------------------------------------------------------------------
def _log_path(d: _Dec, what: str) -> np.ndarray:
    path = d.array(dtype=np.uint32, ndim=2)
    if path.shape[0] > MAX_LOG_DEPTH or path.shape[1] != 8:
        raise WireFormatError(
            f"{what} path must be (d<={MAX_LOG_DEPTH}, 8), got {path.shape}")
    return path


def encode_checkpoint(cp) -> bytes:
    """Canonical bytes for a :class:`repro.core.transparency.Checkpoint`."""
    e = _Enc()
    _header(e, KIND_CHECKPOINT)
    e.u8(_F_C_ORIGIN)
    e.string(cp.origin)
    e.u8(_F_C_SIZE)
    e.i64(_nonneg(cp.tree_size, "checkpoint tree_size"))
    e.u8(_F_C_ROOT)
    e.array(_root8(cp.root, "checkpoint root"), dtype=np.uint32, ndim=1)
    return bytes(e.buf)


def decode_checkpoint(raw: bytes):
    from .transparency import Checkpoint
    d = _Dec(raw)
    _check_header(d, KIND_CHECKPOINT)
    d.tag(_F_C_ORIGIN, "checkpoint.origin")
    origin = d.string()
    d.tag(_F_C_SIZE, "checkpoint.tree_size")
    tree_size = d.i64()
    if tree_size < 0:
        raise WireFormatError(f"negative tree size {tree_size}")
    d.tag(_F_C_ROOT, "checkpoint.root")
    root = d.array(dtype=np.uint32, ndim=1, shape=(8,))
    d.done()
    return Checkpoint(origin, tree_size, root)


def encode_inclusion_proof(pf) -> bytes:
    e = _Enc()
    _header(e, KIND_INCLUSION)
    e.u8(_F_I_INDEX)
    e.i64(_nonneg(pf.leaf_index, "inclusion leaf_index"))
    e.u8(_F_I_SIZE)
    e.i64(_nonneg(pf.tree_size, "inclusion tree_size"))
    if pf.leaf_index >= pf.tree_size:
        raise WireFormatError(
            f"leaf index {pf.leaf_index} outside tree of {pf.tree_size}")
    e.u8(_F_I_PATH)
    path = np.asarray(pf.path, np.uint32).reshape(-1, 8)
    if path.shape[0] > MAX_LOG_DEPTH:
        raise WireFormatError(f"inclusion path too deep: {path.shape[0]}")
    e.array(path, dtype=np.uint32, ndim=2)
    return bytes(e.buf)


def decode_inclusion_proof(raw: bytes):
    from .transparency import InclusionProof
    d = _Dec(raw)
    _check_header(d, KIND_INCLUSION)
    d.tag(_F_I_INDEX, "inclusion.leaf_index")
    leaf_index = d.i64()
    d.tag(_F_I_SIZE, "inclusion.tree_size")
    tree_size = d.i64()
    if not 0 <= leaf_index < tree_size:
        raise WireFormatError(
            f"leaf index {leaf_index} outside tree of {tree_size}")
    d.tag(_F_I_PATH, "inclusion.path")
    path = _log_path(d, "inclusion")
    d.done()
    return InclusionProof(leaf_index, tree_size, path)


def encode_consistency_proof(pf) -> bytes:
    e = _Enc()
    _header(e, KIND_CONSISTENCY)
    e.u8(_F_Y_OLD)
    e.i64(_nonneg(pf.old_size, "consistency old_size"))
    e.u8(_F_Y_NEW)
    e.i64(_nonneg(pf.new_size, "consistency new_size"))
    if not 1 <= pf.old_size <= pf.new_size:
        raise WireFormatError(
            f"consistency sizes must satisfy 1 <= old <= new, got "
            f"{pf.old_size}, {pf.new_size}")
    e.u8(_F_Y_PATH)
    path = np.asarray(pf.path, np.uint32).reshape(-1, 8)
    if path.shape[0] > MAX_LOG_DEPTH:
        raise WireFormatError(f"consistency path too deep: {path.shape[0]}")
    e.array(path, dtype=np.uint32, ndim=2)
    return bytes(e.buf)


def decode_consistency_proof(raw: bytes):
    from .transparency import ConsistencyProof
    d = _Dec(raw)
    _check_header(d, KIND_CONSISTENCY)
    d.tag(_F_Y_OLD, "consistency.old_size")
    old_size = d.i64()
    d.tag(_F_Y_NEW, "consistency.new_size")
    new_size = d.i64()
    if not 1 <= old_size <= new_size:
        raise WireFormatError(
            f"consistency sizes must satisfy 1 <= old <= new, got "
            f"{old_size}, {new_size}")
    d.tag(_F_Y_PATH, "consistency.path")
    path = _log_path(d, "consistency")
    d.done()
    return ConsistencyProof(old_size, new_size, path)


# ---------------------------------------------------------------------------
# gossip envelope (kind 9): Ed25519-signed checkpoint + optional consistency
# ---------------------------------------------------------------------------
def _embed(e: _Enc, raw: bytes, what: str):
    """A complete inner wire message, length-prefixed.  Nesting whole
    messages (their own header included) keeps one canonical encoding per
    payload and reuses each inner codec's validation wholesale."""
    if len(raw) > MAX_EMBED:
        raise WireFormatError(
            f"embedded {what} message too large: {len(raw)} > {MAX_EMBED}")
    e.u32(len(raw))
    e.buf += raw


def _unembed(d: _Dec, what: str) -> bytes:
    n = d.u32()
    if n > MAX_EMBED:
        raise WireFormatError(
            f"embedded {what} length {n} > {MAX_EMBED}")
    return d.take(n)


def encode_gossip_message(msg) -> bytes:
    """Canonical bytes for a :class:`repro.core.gossip.GossipMessage`."""
    e = _Enc()
    _header(e, KIND_GOSSIP)
    e.u8(_F_G_CHECKPOINT)
    _embed(e, encode_checkpoint(msg.checkpoint), "checkpoint")
    e.u8(_F_G_CONSIST)
    if msg.consistency is None:
        e.u8(0)
    else:
        e.u8(1)
        _embed(e, encode_consistency_proof(msg.consistency), "consistency")
    e.u8(_F_G_SIGNER)
    signer = bytes(msg.signer)
    if len(signer) != SIGNER_LEN:
        raise WireFormatError(
            f"gossip signer must be {SIGNER_LEN} bytes, got {len(signer)}")
    e.buf += signer
    e.u8(_F_G_SIG)
    signature = bytes(msg.signature)
    if len(signature) != SIG_LEN:
        raise WireFormatError(
            f"gossip signature must be {SIG_LEN} bytes, got {len(signature)}")
    e.buf += signature
    return bytes(e.buf)


def decode_gossip_message(raw: bytes):
    """Decode + validate canonical gossip bytes; the embedded checkpoint
    and consistency proof pass through their own full decoders, so every
    inner invariant (kinds, bounds, size relations) holds before a
    :class:`~repro.core.gossip.GossipPeer` sees the message."""
    from .gossip import GossipMessage
    d = _Dec(raw)
    _check_header(d, KIND_GOSSIP)
    d.tag(_F_G_CHECKPOINT, "gossip.checkpoint")
    checkpoint = decode_checkpoint(_unembed(d, "checkpoint"))
    d.tag(_F_G_CONSIST, "gossip.consistency")
    flag = d.u8()
    if flag not in (0, 1):
        raise WireFormatError(f"non-canonical consistency flag {flag}")
    consistency = None
    if flag:
        consistency = decode_consistency_proof(_unembed(d, "consistency"))
    d.tag(_F_G_SIGNER, "gossip.signer")
    signer = d.take(SIGNER_LEN)
    d.tag(_F_G_SIG, "gossip.signature")
    signature = d.take(SIG_LEN)
    d.done()
    return GossipMessage(checkpoint, consistency, signer, signature)


# ---------------------------------------------------------------------------
# federated envelope (kinds 10-12): multi-owner sub-plan proving
# ---------------------------------------------------------------------------
def _handoff_to_wire(e: _Enc, entries, what: str):
    """Hand-off columns: a canonically ordered ``(step, key, int64 array)``
    list — the inter-owner transcript a predecessor owner signs and the
    verifier byte-compares against its own re-derived outputs."""
    entries = list(entries)
    if len(entries) > MAX_HANDOFF:
        raise WireFormatError(
            f"{what} has too many entries: {len(entries)} > {MAX_HANDOFF}")
    e.u32(len(entries))
    prev = None
    for step, key, arr in entries:
        step = int(step)
        if not 0 <= step < MAX_STEPS:
            raise WireFormatError(f"{what} step {step} out of range")
        if prev is not None and (step, key) <= prev:
            raise WireFormatError(f"non-canonical {what} order")
        prev = (step, key)
        e.u32(step)
        e.string(key)
        a = np.asarray(arr, np.int64)
        if a.ndim > 2:
            raise WireFormatError(
                f"{what} value for ({step}, {key!r}) must be at most 2-d")
        e.array(a, dtype=np.int64)


def _handoff_from_wire(d: _Dec, what: str) -> list:
    n = d.u32()
    if n > MAX_HANDOFF:
        raise WireFormatError(f"{what} entry count {n} > {MAX_HANDOFF}")
    out = []
    prev = None
    for _ in range(n):
        step = d.u32()
        if step >= MAX_STEPS:
            raise WireFormatError(f"{what} step {step} out of range")
        key = d.string()
        if prev is not None and (step, key) <= prev:
            raise WireFormatError(f"non-canonical {what} order")
        prev = (step, key)
        arr = d.array(dtype=np.int64)
        if arr.ndim > 2:
            raise WireFormatError(
                f"{what} value for ({step}, {key!r}) must be at most 2-d")
        out.append((step, key, arr))
    return out


def _export_keys_to_wire(e: _Enc, exports, what: str):
    exports = list(exports)
    if len(exports) > MAX_HANDOFF:
        raise WireFormatError(
            f"{what} has too many entries: {len(exports)} > {MAX_HANDOFF}")
    e.u32(len(exports))
    prev = None
    for step, key in exports:
        step = int(step)
        if not 0 <= step < MAX_STEPS:
            raise WireFormatError(f"{what} step {step} out of range")
        if prev is not None and (step, key) <= prev:
            raise WireFormatError(f"non-canonical {what} order")
        prev = (step, key)
        e.u32(step)
        e.string(key)


def _export_keys_from_wire(d: _Dec, what: str) -> list:
    n = d.u32()
    if n > MAX_HANDOFF:
        raise WireFormatError(f"{what} entry count {n} > {MAX_HANDOFF}")
    out = []
    prev = None
    for _ in range(n):
        step = d.u32()
        if step >= MAX_STEPS:
            raise WireFormatError(f"{what} step {step} out of range")
        key = d.string()
        if prev is not None and (step, key) <= prev:
            raise WireFormatError(f"non-canonical {what} order")
        prev = (step, key)
        out.append((step, key))
    return out


def _opt_sig_to_wire(e: _Enc, sig, what: str):
    if sig is None:
        e.u8(0)
        return
    sig = bytes(sig)
    if len(sig) != SIG_LEN:
        raise WireFormatError(
            f"{what} must be {SIG_LEN} bytes, got {len(sig)}")
    e.u8(1)
    e.buf += sig


def _opt_sig_from_wire(d: _Dec, what: str):
    flag = d.u8()
    if flag not in (0, 1):
        raise WireFormatError(f"non-canonical {what} flag {flag}")
    return d.take(SIG_LEN) if flag else None


def _cfg_to_wire(e: _Enc, cfg):
    for v in (cfg.blowup, cfg.n_queries, cfg.fri_final_size, cfg.shift):
        e.u32(v)


def _cfg_from_wire(d: _Dec):
    from .prover import ProverConfig
    return ProverConfig(blowup=d.u32(), n_queries=d.u32(),
                        fri_final_size=d.u32(), shift=d.u32())


def _str_dict(v, what: str) -> dict:
    if not isinstance(v, dict) or not all(isinstance(k, str) for k in v):
        raise WireFormatError(f"{what} must be a str-keyed dict")
    return v


def _fedpart_to_wire(e: _Enc, part):
    e.u8(_F_FP_OWNER)
    e.string(part.owner)
    if not 0 <= part.lo < part.hi <= MAX_STEPS:
        raise WireFormatError(
            f"federated part range [{part.lo}, {part.hi}) is invalid")
    e.u8(_F_FP_LO)
    e.u32(part.lo)
    e.u8(_F_FP_HI)
    e.u32(part.hi)
    e.u8(_F_FP_DIGEST)
    e.array(_root8(part.manifest_digest, "part manifest digest"),
            dtype=np.uint32, ndim=1)
    if len(part.steps) != part.hi - part.lo:
        raise WireFormatError(
            f"federated part carries {len(part.steps)} steps for a "
            f"{part.hi - part.lo}-node slice")
    e.u8(_F_FP_STEPS)
    e.u32(len(part.steps))
    for step in part.steps:
        _step_to_wire(e, step)
    e.u8(_F_FP_HANDOFF)
    _handoff_to_wire(e, part.handoff, "part handoff")
    e.u8(_F_FP_SIG)
    _opt_sig_to_wire(e, part.handoff_sig, "part handoff signature")


def _fedpart_from_wire(d: _Dec):
    from ..federation.envelope import FederatedPart
    d.tag(_F_FP_OWNER, "part.owner")
    owner = d.string()
    d.tag(_F_FP_LO, "part.lo")
    lo = d.u32()
    d.tag(_F_FP_HI, "part.hi")
    hi = d.u32()
    if not lo < hi <= MAX_STEPS:
        raise WireFormatError(f"federated part range [{lo}, {hi}) is invalid")
    d.tag(_F_FP_DIGEST, "part.manifest_digest")
    digest = d.array(dtype=np.uint32, ndim=1, shape=(8,))
    d.tag(_F_FP_STEPS, "part.steps")
    n = d.u32()
    if n != hi - lo:
        raise WireFormatError(
            f"federated part carries {n} steps for a {hi - lo}-node slice")
    steps = [_step_from_wire(d) for _ in range(n)]
    d.tag(_F_FP_HANDOFF, "part.handoff")
    handoff = _handoff_from_wire(d, "part handoff")
    d.tag(_F_FP_SIG, "part.handoff_sig")
    sig = _opt_sig_from_wire(d, "part handoff signature")
    return FederatedPart(owner, lo, hi, digest, steps, handoff, sig)


def encode_federated(fed) -> bytes:
    """Canonical bytes for a
    :class:`repro.federation.envelope.FederatedBundle` (payload kind 10,
    spec in ``docs/protocol.md`` §11)."""
    e = _Enc()
    _header(e, KIND_FEDERATED)
    e.u8(_F_FB_VERSION)
    e.u8(FED_VERSION)
    e.u8(_F_FB_QUERY)
    e.string(fed.query)
    e.u8(_F_FB_PARAMS)
    e.value(_str_dict(dict(fed.params), "federated params"))
    e.u8(_F_FB_CFG)
    _cfg_to_wire(e, fed.cfg)
    if not 0 < len(fed.parts) <= MAX_PARTS:
        raise WireFormatError(
            f"federated bundle needs 1..{MAX_PARTS} parts, "
            f"got {len(fed.parts)}")
    e.u8(_F_FB_PARTS)
    e.u32(len(fed.parts))
    for part in fed.parts:
        _fedpart_to_wire(e, part)
    e.u8(_F_FB_RESULT)
    e.value(_str_dict(dict(fed.result), "federated result"))
    return bytes(e.buf)


def decode_federated(raw: bytes):
    """Decode + validate canonical federated-bundle bytes; any malformed
    input raises :class:`WireFormatError` (the verifier fails closed)."""
    from ..federation.envelope import FederatedBundle
    d = _Dec(raw)
    _check_header(d, KIND_FEDERATED)
    d.tag(_F_FB_VERSION, "federated.version")
    fver = d.u8()
    if fver != FED_VERSION:
        raise WireFormatError(
            f"unsupported federated envelope version {fver} (this verifier "
            f"speaks {FED_VERSION})")
    d.tag(_F_FB_QUERY, "federated.query")
    query = d.string()
    d.tag(_F_FB_PARAMS, "federated.params")
    params = _str_dict(d.value(), "federated params")
    d.tag(_F_FB_CFG, "federated.cfg")
    cfg = _cfg_from_wire(d)
    d.tag(_F_FB_PARTS, "federated.parts")
    n = d.u32()
    if not 0 < n <= MAX_PARTS:
        raise WireFormatError(f"federated part count {n} not in 1..{MAX_PARTS}")
    parts = [_fedpart_from_wire(d) for _ in range(n)]
    d.tag(_F_FB_RESULT, "federated.result")
    result = _str_dict(d.value(), "federated result")
    d.done()
    return FederatedBundle(query, params, cfg, parts, result)


def encode_fed_request(req) -> bytes:
    """Canonical bytes for a coordinator -> owner sub-plan prove request
    (payload kind 11; rides a ``REQ_FEDPROVE`` frame)."""
    e = _Enc()
    _header(e, KIND_FEDREQ)
    e.u8(_F_FR_QUERY)
    e.string(req.query)
    e.u8(_F_FR_PARAMS)
    e.value(_str_dict(dict(req.params), "fed request params"))
    e.u8(_F_FR_CFG)
    _cfg_to_wire(e, req.cfg)
    if not 0 <= req.lo < req.hi <= MAX_STEPS:
        raise WireFormatError(
            f"fed request range [{req.lo}, {req.hi}) is invalid")
    e.u8(_F_FR_LO)
    e.u32(req.lo)
    e.u8(_F_FR_HI)
    e.u32(req.hi)
    e.u8(_F_FR_SEEDS)
    _handoff_to_wire(e, req.seeds, "request seeds")
    e.u8(_F_FR_SEEDS_SIG)
    _opt_sig_to_wire(e, req.seeds_sig, "request seeds signature")
    e.u8(_F_FR_EXPORTS)
    _export_keys_to_wire(e, req.exports, "request exports")
    return bytes(e.buf)


def decode_fed_request(raw: bytes):
    from ..federation.envelope import FedRequest
    d = _Dec(raw)
    _check_header(d, KIND_FEDREQ)
    d.tag(_F_FR_QUERY, "fedreq.query")
    query = d.string()
    d.tag(_F_FR_PARAMS, "fedreq.params")
    params = _str_dict(d.value(), "fed request params")
    d.tag(_F_FR_CFG, "fedreq.cfg")
    cfg = _cfg_from_wire(d)
    d.tag(_F_FR_LO, "fedreq.lo")
    lo = d.u32()
    d.tag(_F_FR_HI, "fedreq.hi")
    hi = d.u32()
    if not lo < hi <= MAX_STEPS:
        raise WireFormatError(f"fed request range [{lo}, {hi}) is invalid")
    d.tag(_F_FR_SEEDS, "fedreq.seeds")
    seeds = _handoff_from_wire(d, "request seeds")
    d.tag(_F_FR_SEEDS_SIG, "fedreq.seeds_sig")
    seeds_sig = _opt_sig_from_wire(d, "request seeds signature")
    d.tag(_F_FR_EXPORTS, "fedreq.exports")
    exports = _export_keys_from_wire(d, "request exports")
    d.done()
    return FedRequest(query, params, cfg, lo, hi, seeds, seeds_sig, exports)


def encode_fed_response(resp) -> bytes:
    """Canonical bytes for an owner -> coordinator sub-plan prove response
    (payload kind 12; rides a ``RESP_FEDPROVE`` frame)."""
    e = _Enc()
    _header(e, KIND_FEDRESP)
    e.u8(_F_FS_PART)
    _fedpart_to_wire(e, resp.part)
    e.u8(_F_FS_EXPORTS)
    _handoff_to_wire(e, resp.exports, "response exports")
    e.u8(_F_FS_SIG)
    _opt_sig_to_wire(e, resp.exports_sig, "response exports signature")
    e.u8(_F_FS_RESULT)
    e.value(_str_dict(dict(resp.result), "response result"))
    return bytes(e.buf)


def decode_fed_response(raw: bytes):
    from ..federation.envelope import FedResponse
    d = _Dec(raw)
    _check_header(d, KIND_FEDRESP)
    d.tag(_F_FS_PART, "fedresp.part")
    part = _fedpart_from_wire(d)
    d.tag(_F_FS_EXPORTS, "fedresp.exports")
    exports = _handoff_from_wire(d, "response exports")
    d.tag(_F_FS_SIG, "fedresp.exports_sig")
    exports_sig = _opt_sig_from_wire(d, "response exports signature")
    d.tag(_F_FS_RESULT, "fedresp.result")
    result = _str_dict(d.value(), "response result")
    d.done()
    return FedResponse(part, exports, exports_sig, result)


def handoff_signing_bytes(query: str, params: dict, boundary: int,
                          entries) -> bytes:
    """The deterministic byte string an owner signs over the hand-off it
    exports at segment boundary ``boundary`` (the consuming part's ``lo``).

    Binding the query name, the canonical params encoding, and the boundary
    index means a signature cannot be replayed across queries, parameter
    sets, or chain positions.  The federation layer prepends its own domain
    prefix before signing (``repro.federation`` uses ``0x04``; ``0x00`` is
    the log leaf domain, ``0x03`` the gossip signature domain)."""
    e = _Enc()
    e.string(query)
    e.value(_str_dict(dict(params), "signed handoff params"))
    e.u32(int(boundary))
    _handoff_to_wire(e, entries, "signed handoff")
    return bytes(e.buf)
