"""ZKGraph session API: the query-serving entry point.

A :class:`ZKGraphSession` owns the published dataset commitments and a keygen
cache keyed by ``(circuit shape, fixed-columns digest)`` so repeated queries
— and repeated steps within one query — reuse the fixed-column LDE / coeff
caches instead of re-running keygen per step (the hot path a proving service
pays; see ``benchmarks/paper_tables.py:cachewin``).

Owner side::

    owner = ZKGraphSession(db)
    bundle = owner.prove("IC1", dict(person=2, firstName=name))

Verifier side (no database access) — the trust root is one typed
:class:`TrustAnchor` value::

    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=owner.commitments))
    assert verifier.verify(bundle)

or, bootstrapping the whole trust root from a transparency log
(:mod:`repro.core.transparency`) instead of an in-process object::

    checkpoint, inclusion, manifest_bytes = owner.publish_to(log)
    verifier = ZKGraphSession.verifier(anchor=TrustAnchor(
        checkpoint=checkpoint, inclusion=inclusion,
        manifest_bytes=manifest_bytes))

(The pre-anchor kwargs ``commitments=`` / ``checkpoint=`` / ``inclusion=``
/ ``manifest_bytes=`` / ``gossip=`` survive one release as deprecation
shims that assemble the same anchor internally.)

Every bundle carries the digest of the canonical manifest encoding it was
proven against; ``verify`` rejects any bundle whose digest differs from the
verifier's (checkpoint-authenticated) manifest.

The bundle is self-contained and serializable: per step it carries the
registry adapter name + circuit shape (so the verifier rebuilds the circuit
itself), the public instance, the data descriptor, and the proof.  The wire
format is the canonical codec of :mod:`repro.core.wire` — versioned,
deterministic, bounded, never pickle — so ``from_bytes`` can face hostile
input (malformed bytes raise :class:`~repro.core.wire.WireFormatError`;
:meth:`ZKGraphSession.verify_bytes` maps that to ``False``).

The verifier trusts ONLY the owner's published
:class:`~repro.core.commit.CommitmentManifest`: every base-table step is
bound to a published root (a missing commitment raises
:class:`MissingCommitmentError`, it is never recomputed from prover-supplied
data) and its declared circuit geometry — row counts, ``m_edges`` selector
regions, SSSP's ``n_nodes`` — is pinned against the manifest's published
geometry; chained intermediate roots and shapes are re-derived from the
previous steps' (already verified) public outputs.
"""
from __future__ import annotations

import hashlib
import threading
import warnings
from dataclasses import dataclass, field as dc_field

import numpy as np

from .. import obs
from . import backend as be
from . import commit, ir, wire
from . import prover as pv
from .commit import CommitmentManifest, MissingCommitmentError
from .operators import registry
from .plonkish import Circuit
from .wire import WireFormatError

__all__ = ["KeygenCache", "MissingCommitmentError", "ProofBundle",
           "StepProof", "TrustAnchor", "WireFormatError", "ZKGraphSession",
           "circuit_shape_digest"]


# ---------------------------------------------------------------------------
# keygen cache
# ---------------------------------------------------------------------------
def circuit_shape_digest(circuit: Circuit) -> str:
    """Digest of everything the constraint system depends on: fixed-column
    values, the column layout, and the full gate/bus/gp *expressions* (two
    circuits that differ only in a constraint polynomial — e.g. ascending vs
    descending order-by — must not share keys).

    Memoized on the circuit (``Circuit._shape_digest``, invalidated by every
    structural mutation): the SHA-256 over all fixed-column bytes is paid
    once per circuit object, not on every cache lookup."""
    if circuit._shape_digest is not None:
        return circuit._shape_digest
    h = hashlib.sha256()
    h.update(repr(circuit.digest_seed()).encode())
    for name, col in zip(circuit.fixed_names, circuit.fixed_cols):
        h.update(name.encode())
        h.update(np.ascontiguousarray(col).tobytes())
    for names in (circuit.advice_names, circuit.instance_names,
                  circuit.data_names):
        h.update("\0".join(names).encode() + b"\1")
    for name, expr in circuit.gates:
        h.update(f"{name}={expr!r}".encode() + b"\1")
    for b in circuit.buses:
        h.update(repr((b.name, b.f_tuple, b.t_tuple, b.m_f, b.m_t,
                       b.t_sel)).encode() + b"\1")
    for g in circuit.gps:
        h.update(repr((g.name, g.c1_tuple, g.c2_tuple, g.sel1,
                       g.sel2)).encode() + b"\1")
    circuit._shape_digest = h.hexdigest()
    return circuit._shape_digest


@dataclass
class KeygenCache:
    """(circuit shape digest, prover config, compute backend) -> Keys.
    Shared by prover and verifier sessions; ``ensure`` attaches cached keys
    to an operator.  The resolved backend name is part of the key (cached
    ``Keys`` hold backend-produced buffers; PK/LDE caches never cross
    backends — this also covers the fixed-column LDE cache the Keys carry).
    Bounded: oldest entries are evicted past ``max_entries`` so a
    long-lived verifier fed ever-fresh shapes cannot grow it without limit.

    Thread-safe with single-flight misses: concurrent ``ensure`` calls for
    the same key (the proving-service hot path — many queries hit the same
    circuit shapes) run keygen exactly once; the other callers block on the
    leader's in-flight event and reuse its Keys (``waits`` counts them).
    Distinct keys keygen concurrently — only bookkeeping is locked, never
    the keygen compute itself."""
    entries: dict = dc_field(default_factory=dict)
    hits: int = 0
    misses: int = 0
    waits: int = 0          # ensure() calls that blocked on another's keygen
    max_entries: int = 128
    _lock: threading.Lock = dc_field(default_factory=threading.Lock,
                                     repr=False, compare=False)
    _inflight: dict = dc_field(default_factory=dict, repr=False,
                               compare=False)   # key -> threading.Event

    @staticmethod
    def _key(op, cfg: pv.ProverConfig):
        # the resolved compute backend is part of the key: PK/LDE caches
        # must never cross backends (entries hold backend-produced device
        # buffers, and a keygen re-run is the only safe way to switch)
        return (op.name, op.circuit.n_rows,
                (cfg.blowup, cfg.n_queries, cfg.fri_final_size, cfg.shift,
                 be.resolve_name(cfg.backend)),
                circuit_shape_digest(op.circuit))

    def ensure(self, op, cfg: pv.ProverConfig):
        """Attach (possibly cached) keys to ``op``; keygen on first sight."""
        key = self._key(op, cfg)
        while True:
            wait_on = None
            with self._lock:
                keys = self.entries.get(key)
                if keys is not None:
                    self.hits += 1
                    self.entries[key] = self.entries.pop(key)  # LRU refresh
                    op.keys = keys
                    return op
                flight = self._inflight.get(key)
                if flight is None:
                    # this caller is the flight leader: keygen outside the
                    # lock (other keys must not serialize behind it)
                    flight = self._inflight[key] = threading.Event()
                    break
                self.waits += 1
                wait_on = flight
            wait_on.wait()
            # leader finished (or failed): re-check the cache / re-elect
        try:
            with obs.span("zkg.keygen", rows=op.circuit.n_rows):
                keys = pv.keygen(op.circuit, cfg)
        except BaseException:
            with self._lock:
                self._inflight.pop(key, None)
            flight.set()        # waiters wake, re-check, one re-leads
            raise
        with self._lock:
            self.misses += 1
            self.entries[key] = keys
            while len(self.entries) > self.max_entries:
                self.entries.pop(next(iter(self.entries)))
            self._inflight.pop(key, None)
        flight.set()
        op.keys = keys
        return op

    def stats(self) -> dict:
        with self._lock:
            return dict(hits=self.hits, misses=self.misses, waits=self.waits,
                        entries=len(self.entries))


# ---------------------------------------------------------------------------
# proof bundle
# ---------------------------------------------------------------------------
@dataclass
class StepProof:
    """One chained step: enough for a verifier to rebuild the circuit,
    re-derive the expected data root, and check the proof."""
    kind: str           # registry adapter name
    shape: dict         # serializable build kwargs
    data_desc: str      # base-table descriptor or "chained"
    instance: np.ndarray
    proof: pv.Proof


@dataclass
class ProofBundle:
    query: str
    params: dict
    steps: list         # [StepProof]
    result: dict        # claimed query result (re-derived by the verifier)
    cfg: pv.ProverConfig
    # digest of the canonical CommitmentManifest this bundle was proven
    # against (transparency-log leaf hash, (8,) uint32); the verifier fails
    # closed if it does not match the manifest it bootstrapped trust from
    manifest_digest: np.ndarray = None

    def size_fields(self) -> int:
        return sum(s.proof.size_fields() for s in self.steps)

    def prove_seconds(self) -> float:
        return sum(s.proof.timings.get("total", 0.0) for s in self.steps)

    def to_bytes(self) -> bytes:
        """Canonical wire bytes (versioned + deterministic; never pickle)."""
        return wire.encode_bundle(self)

    @staticmethod
    def from_bytes(raw: bytes) -> "ProofBundle":
        """Decode canonical wire bytes.  Any malformed input — truncation,
        bad tags, oversized lengths, wrong dtypes, legacy pickle bytes, a
        mismatched wire version — raises :class:`WireFormatError`; nothing
        attacker-controlled is ever executed."""
        return wire.decode_bundle(raw)


def _values_equal(a, b) -> bool:
    return np.array_equal(np.asarray(a), np.asarray(b))


def _results_equal(a: dict, b: dict) -> bool:
    return set(a) == set(b) and all(_values_equal(a[k], b[k]) for k in a)


# ---------------------------------------------------------------------------
# trust anchor
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TrustAnchor:
    """One owner's verification trust root, as a single typed value.

    Exactly one bootstrap mode must be populated:

    * ``TrustAnchor(manifest=m)`` — an in-process
      :class:`~repro.core.commit.CommitmentManifest` obtained out of band
      (tests, co-located deployments).
    * ``TrustAnchor(checkpoint=cp, inclusion=pf, manifest_bytes=raw)`` —
      the transparency-log path: the manifest bytes are authenticated
      against the log checkpoint via the inclusion proof
      (:func:`repro.core.transparency.bootstrap_manifest`) before anything
      trusts them; a failed inclusion raises
      :class:`~repro.core.transparency.TransparencyError`.
    * ``TrustAnchor(gossip=peer, inclusion=pf, manifest_bytes=raw)`` — the
      deployment path: the checkpoint is the
      :class:`~repro.core.gossip.GossipPeer`'s pinned head — the freshest
      head that peer has verified consistent with every other head it
      gossiped (``peer.pinned`` raises
      :class:`~repro.core.gossip.GossipError` if nothing is pinned yet), so
      the trust root is backed by the gossip network, not a single served
      checkpoint.

    A federated verifier holds one anchor per owner organization
    (:func:`repro.federation.verify_federated`); the single-owner
    :meth:`ZKGraphSession.verifier` takes exactly one.
    """
    manifest: CommitmentManifest | None = None
    checkpoint: object = None
    inclusion: object = None
    manifest_bytes: bytes | None = None
    gossip: object = None

    def resolve(self) -> CommitmentManifest:
        """The authenticated manifest this anchor pins.

        Raises ``TypeError`` on a conflicting or empty anchor,
        ``TransparencyError`` on a failed inclusion check, and
        ``GossipError`` if the gossip peer has no pinned head yet."""
        checkpoint = self.checkpoint
        if self.gossip is not None:
            if checkpoint is not None:
                raise TypeError(
                    "pass either checkpoint= or gossip= (whose pinned head "
                    "becomes the checkpoint), not both")
            checkpoint = self.gossip.pinned
        if checkpoint is not None or self.inclusion is not None \
                or self.manifest_bytes is not None:
            if self.manifest is not None:
                raise TypeError(
                    "pass either a manifest or a checkpoint bootstrap "
                    "(checkpoint + inclusion + manifest_bytes), not both")
            from . import transparency
            return transparency.bootstrap_manifest(
                checkpoint, self.inclusion, self.manifest_bytes)
        if self.manifest is None:
            raise TypeError(
                "TrustAnchor needs a CommitmentManifest, or a transparency "
                "checkpoint + inclusion proof + manifest bytes")
        return self.manifest


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------
class ZKGraphSession:
    """Owns commitments + keygen cache; proves and verifies query bundles."""

    def __init__(self, db=None, cfg: pv.ProverConfig = None,
                 commitments: CommitmentManifest = None):
        self.db = db
        self.cfg = cfg or pv.ProverConfig()
        self._commitments = commitments
        self.cache = KeygenCache()

    @classmethod
    def verifier(cls, anchor: TrustAnchor = None,
                 cfg: pv.ProverConfig = None, *, commitments=None,
                 checkpoint=None, inclusion=None, manifest_bytes=None,
                 gossip=None):
        """A verifier-side session: no database, trust root only.

        Primary signature: ``verifier(anchor=TrustAnchor(...), cfg)`` —
        the three bootstrap modes (in-process manifest, transparency-log
        checkpoint, gossip-pinned head) live on :class:`TrustAnchor`, one
        typed value per owner.  The session pins the resolved manifest's
        digest, and :meth:`verify` rejects any bundle whose
        ``manifest_digest`` differs.

        Deprecated shims (one release): a positional
        :class:`~repro.core.commit.CommitmentManifest`, and the accreted
        ``commitments=`` / ``checkpoint=`` / ``inclusion=`` /
        ``manifest_bytes=`` / ``gossip=`` kwargs.  Both emit
        ``DeprecationWarning`` and assemble the same :class:`TrustAnchor`
        internally, so verification decisions are byte-identical to the
        anchor path (covered by ``tests/test_trust_anchor.py``).
        """
        legacy = dict(manifest=commitments, checkpoint=checkpoint,
                      inclusion=inclusion, manifest_bytes=manifest_bytes,
                      gossip=gossip)
        if anchor is not None and not isinstance(anchor, TrustAnchor):
            # legacy positional call: verifier(manifest, cfg)
            warnings.warn(
                "ZKGraphSession.verifier(manifest, ...) is deprecated; "
                "pass verifier(anchor=TrustAnchor(manifest=...))",
                DeprecationWarning, stacklevel=2)
            if legacy["manifest"] is not None:
                raise TypeError("manifest passed both positionally and as "
                                "commitments=")
            legacy["manifest"] = anchor
            anchor = None
        if anchor is not None:
            if any(v is not None for v in legacy.values()):
                raise TypeError(
                    "pass either anchor= or the deprecated kwargs "
                    "(commitments=/checkpoint=/inclusion=/manifest_bytes=/"
                    "gossip=), not both")
        elif any(v is not None for v in legacy.values()):
            warnings.warn(
                "the commitments=/checkpoint=/inclusion=/manifest_bytes=/"
                "gossip= kwargs of ZKGraphSession.verifier are deprecated; "
                "pass one typed anchor=TrustAnchor(...) instead",
                DeprecationWarning, stacklevel=2)
            anchor = TrustAnchor(**legacy)
        else:
            raise TypeError(
                "verifier needs a TrustAnchor: an in-process "
                "CommitmentManifest, or a transparency checkpoint + "
                "inclusion proof + manifest bytes")
        return cls(db=None, cfg=cfg, commitments=anchor.resolve())

    # -- owner side ---------------------------------------------------------
    @property
    def commitments(self) -> CommitmentManifest:
        if self._commitments is None:
            self._commitments = self.publish()
        return self._commitments

    def publish(self) -> CommitmentManifest:
        """(Re)compute the owner's commitment manifest (roots + geometry)."""
        assert self.db is not None, "publishing requires the database"
        self._commitments = commit.publish_commitments(self.db, self.cfg)
        return self._commitments

    def publish_to(self, log) -> tuple:
        """Publish the manifest on a transparency log.

        Appends the canonical manifest bytes as a new leaf and returns
        ``(checkpoint, inclusion_proof, manifest_bytes)`` — exactly the
        bootstrap inputs of :meth:`verifier`, so the owner's publication and
        the verifier's trust root are the same auditable artifact.  ``log``
        may be an in-process :class:`~repro.core.transparency.
        TransparencyLog` or a durable one (``TransparencyLog.open(path)``)
        — with a durable log the append is fsync'd before the checkpoint is
        returned, so a served checkpoint always survives an owner crash."""
        raw = self.commitments.to_bytes()
        cp = log.append(raw)
        pf = log.inclusion_proof(cp.tree_size - 1, cp.tree_size)
        return cp, pf, raw

    def run_query(self, qname: str, params: dict) -> ir.QueryRun:
        """Execute a query plan (engine + witnesses), no proving."""
        return self.run_plan(ir.build_plan(qname), params)

    def run_plan(self, plan: ir.Plan, params: dict) -> ir.QueryRun:
        """Execute an explicit :class:`~repro.core.ir.Plan` object."""
        assert self.db is not None, "query execution requires the database"
        return ir.execute(self.db, plan, params)

    def prove(self, qname: str, params: dict) -> ProofBundle:
        """Prove a registered (or parseable) query by name.

        Resolves ``qname`` through :func:`~repro.core.ir.build_plan` and
        delegates to :meth:`prove_plan`; returns one serializable
        :class:`ProofBundle` (the same type every prove entry point
        returns)."""
        return self.prove_plan(ir.build_plan(qname), params, name=qname)

    def prove_plan(self, plan: ir.Plan, params: dict,
                   name: str = None) -> ProofBundle:
        """Prove an explicit plan object (e.g. a compiled query).

        The bundle's ``query`` field is ``name`` (default ``plan.name``);
        the verifier re-resolves that name through
        :func:`~repro.core.ir.build_plan` — which consults registered plan
        resolvers, so a bundle may be named by a registered query or by a
        parseable query text — and checks the proof against *its own*
        resolution, never the prover's plan object.

        Returns one serializable :class:`ProofBundle`; the step-level entry
        points below (:meth:`prove_step` / :meth:`prove_steps`) return the
        :class:`StepProof` records a bundle is assembled from."""
        run = self.run_plan(plan, params)
        steps = [self.prove_step(st) for st in run.steps]
        return ProofBundle(name if name is not None else plan.name,
                           dict(params), steps, run.result, self.cfg,
                           self.commitments.digest())

    # -- step-level prove entry points (the batcher's call surface) ----------
    def step_shape_key(self, st: ir.Step):
        """The batching key for one executed plan step: two steps with equal
        keys share circuit structure, prover config, and compute backend, so
        their witnesses can ride one lane-batched prove
        (:func:`repro.core.prover_batch.prove_batch`).  This is exactly the
        keygen-cache key — same Keys, same transcript schedule."""
        return self.cache._key(st.op, self.cfg)

    def prove_step(self, st: ir.Step) -> StepProof:
        """Prove one executed plan step solo (keygen-cached)."""
        self.cache.ensure(st.op, self.cfg)
        proof = st.op.prove(st.advice, st.instance, st.data)
        return StepProof(st.kind, st.shape, st.data_desc, st.instance, proof)

    def prove_steps(self, steps: list) -> list[StepProof]:
        """Prove same-shaped steps as ONE lane-batched pass.

        Every step must carry the same :meth:`step_shape_key` (asserted) —
        the lanes share Keys and per-phase dispatch, and each lane's proof
        bytes are identical to what :meth:`prove_step` would have produced
        for it alone.  One step degrades to the solo path.  Returns one
        :class:`StepProof` per input step, in order."""
        if len(steps) == 1:
            return [self.prove_step(steps[0])]
        from . import prover_batch as pvb
        key0 = self.step_shape_key(steps[0])
        for st in steps[1:]:
            assert self.step_shape_key(st) == key0, \
                "prove_steps lanes must share one circuit shape"
        for st in steps:
            self.cache.ensure(st.op, self.cfg)
        keys = steps[0].op.keys
        proofs = pvb.prove_batch(
            keys, [(st.advice, st.instance, st.data) for st in steps],
            label=steps[0].op.name)
        return [StepProof(st.kind, st.shape, st.data_desc, st.instance, pf)
                for st, pf in zip(steps, proofs)]

    # -- verifier side ------------------------------------------------------
    def verify_bytes(self, raw: bytes,
                     commitments: CommitmentManifest = None) -> bool:
        """Decode + verify a serialized bundle; malformed bytes (including
        legacy pickle and version-mismatched encodings) are simply invalid —
        ``False``, never a crash, never code execution."""
        with obs.span("zkg.verify"):
            try:
                bundle = ProofBundle.from_bytes(raw)
            except WireFormatError:
                return False
            return self.verify(bundle, commitments)

    def verify(self, bundle: ProofBundle,
               commitments: CommitmentManifest = None) -> bool:
        """Check every step proof, its dataset-root binding, the published
        circuit geometry, the chained intermediate tables, and the claimed
        result.

        Base tables MUST match a published commitment (missing => raise) and
        their declared circuit geometry MUST match the published manifest
        (``manifest_pins`` + published-size membership) — neither is ever
        taken from prover-supplied data.  Only ``data_desc == "chained"``
        roots are recomputed, and then from the *verifier's own*
        re-derivation of the previous steps' outputs.
        """
        comms = commitments if commitments is not None else self.commitments
        if not isinstance(comms, CommitmentManifest):
            raise TypeError(
                "verification requires the owner's CommitmentManifest "
                "(publish_commitments); a bare root dict has no published "
                "geometry to pin circuit shapes against")
        if bundle.cfg != self.cfg:
            return False    # proof parameters below the session's policy
        # the bundle must have been proven against the SAME published
        # manifest this verifier bootstrapped trust from (for a transparency
        # bootstrap that digest is the log-included leaf): a missing or
        # mismatched digest fails closed before any proof work
        if bundle.manifest_digest is None or not np.array_equal(
                np.asarray(bundle.manifest_digest), comms.digest()):
            return False
        try:
            plan = ir.build_plan(bundle.query)
        except KeyError:
            return False    # unknown query name = invalid bundle
        if len(plan.nodes) != len(bundle.steps):
            return False
        env = ir.Env(dict(bundle.params))
        try:
            for node, rec in zip(plan.nodes, bundle.steps):
                if not self._verify_step(comms, node, rec, env):
                    return False
            result = {k: ir.resolve(b, env) for k, b in plan.result.items()}
            return _results_equal(result, bundle.result)
        except MissingCommitmentError:
            raise                   # an owner/deployment problem, not a proof
        except (TypeError, KeyError, ValueError, AssertionError, IndexError):
            return False            # malformed bundle = invalid proof

    def _verify_step(self, comms: CommitmentManifest, node, rec,
                     env: ir.Env) -> bool:
        """Verify ONE plan step against ONE owner's manifest, appending the
        verifier's own re-derived outputs to ``env`` on success.

        This is the sole per-step decision procedure: :meth:`verify` runs it
        over a single-owner bundle, and
        :func:`repro.federation.verify_federated` runs the SAME code over
        each owner's sub-bundle (with that owner's manifest), so federated
        and single-owner verification decisions are byte-identical."""
        ad = registry.adapter_for(node)
        if ad.name != rec.kind:
            return False
        # all structural checks happen BEFORE any keygen work, so a
        # malformed bundle cannot make the verifier burn keygen cycles
        desc = ad.data_desc(node)           # the PLAN's binding, never
        if rec.data_desc != desc:           # the bundle's claim
            return False
        try:                                # one schema check, shared
            wire.check_shape_schema(rec.kind, rec.shape)
        except WireFormatError:             # with the wire decoder:
            return False                    # exact keys, bool is not int
        for k, v in ad.shape_flags(node).items():
            if rec.shape.get(k) != v:       # semantic circuit flags are
                return False                # pinned by the plan node
        n_rows = rec.shape.get("n_rows")
        if not isinstance(n_rows, int) or n_rows <= 0:
            return False
        if desc == "chained":
            # the chain glue: step k's table is re-derived from
            # earlier verified outputs, and the declared shape must
            # match that re-derivation exactly
            if ad.shape(None, node, env) != rec.shape:
                return False
            cols = ad.chained_cols(node, env)
            expected = commit.data_root(cols, n_rows, self.cfg,
                                        desc="chained")
        else:
            # base tables: full circuit geometry is pinned against
            # the PUBLISHED manifest (missing tables raise; tampered
            # geometry over a published table is just invalid)
            geo = comms.geometry(desc)
            if n_rows not in geo.sizes:
                return False
            pins = ad.manifest_pins(node, env, comms, geo)
            if any(rec.shape.get(k) != v for k, v in pins.items()):
                return False
            expected = comms.root(desc, n_rows)
        op = self.cache.ensure(
            registry.build_operator(rec.kind, rec.shape), self.cfg)
        # the instance's public inputs must be the CLAIMED query's
        # (params + chained outputs), not whatever was proven
        if not ad.check_instance(op, rec.instance, node, env):
            return False
        if not op.verify(rec.instance, rec.proof,
                         expected_data_root=expected):
            return False
        env.outputs.append(ad.extract_outputs(op, rec.instance))
        return True
