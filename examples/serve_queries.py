"""Networked transparency deployment demo: one owner, two verifiers, TCP.

The full deployment story of the transparency fabric, end to end::

    PYTHONPATH=src python examples/serve_queries.py [--queries 4] [--dir D]

The driver (this process) orchestrates three child processes that talk to
each other **over real sockets** (`repro.net`, protocol.md §10) — no
in-process object crosses a trust boundary, only signed bytes on the wire:

* an **owner** that opens a *durable* transparency log
  (``TransparencyLog.open``), publishes the commitment manifest as leaf 0,
  proves a queue of LDBC queries through a ``ProofService``, and runs a
  ``NetServer`` serving its Ed25519-signed gossip head, the manifest,
  inclusion/consistency proofs, and finished ``ProofBundle``\\ s;
* **two verifiers** that each run their own ``NetServer`` (for
  verifier-to-verifier gossip) and a ``PeerClient`` toward the owner —
  through a deterministic in-process ``FaultProxy`` that drops and
  truncates frames to prove the retry/backoff path — bootstrap their
  entire trust root from fetched bytes, verify every bundle, advance
  their pinned head across a manifest revision only on a valid
  consistency proof, and cross-gossip their heads over TCP.

Mid-stream the driver **kills the owner with SIGKILL**, appends a torn
half-record to the log file (what a crash during an unsynced write leaves
behind), and restarts the owner on a fresh port: the reopened log
truncates the torn tail, the owner resumes at the first unproven query,
and the verifiers — whose circuit breakers opened while the owner was
dead — keep serving from their last pinned head, re-resolve the port, and
reconnect.  Finally the driver plays a malicious owner: it forks the log
history, signs the forked head with the REAL origin key, and pushes it to
both verifiers over their gossip sockets — both must answer with an
``RESP_EQUIVOCATION`` frame carrying the ``EquivocationError`` evidence.

Then the **multi-owner act**: the LDBC tables are partitioned between two
owner organizations (`repro.federation`), each publishing its own
manifest on its own transparency log and pinning its own Ed25519 key.
owner-b serves its prover behind a real ``NetServer``; a
``FederationCoordinator`` cuts IC2 at the ownership boundary, routes the
sub-plans (owner-a in-process, owner-b over TCP), and
``verify_federated`` accepts the stitched bundle against one
``TrustAnchor`` per owner.  Finally owner-b's server is torn down and
the dead owner **degrades the query, not the service**: the federated
IC2 fails typed (``OwnerUnavailableError``) while IC13 — cut entirely to
owner-a — still proves and verifies.

A chip belongs to one process, so only the owner processes may use an
accelerator; the driver and the verifiers pin JAX to the CPU (verifying
is host-side work).

The driver checks all of it, phase by phase: recovery happened, every
bundle verified in both verifier processes, heads advanced exactly once,
equivocation was detected by both peers, and the federated act held.  A
failed check exits **nonzero naming the failing phase** — the contract
the CI `federation` job gates on.
"""
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"
sys.path.insert(0, str(SRC))

import argparse
import contextlib
import json
import os
import signal
import subprocess
import tempfile
import threading
import time

from repro.core import gossip
from repro.core import prover as pv
from repro.core.ed25519 import SigningKey
from repro.core.session import TrustAnchor, ZKGraphSession
from repro.core.transparency import InclusionProof, TransparencyLog
from repro.federation import (FederationCoordinator, GraphPartition,
                              LocalOwnerClient, OwnerNode, OwnerSpec,
                              OwnerUnavailableError, RemoteOwnerClient,
                              verify_federated)
from repro.graphdb import ldbc
from repro.net import framing
from repro.net.faults import FaultProxy
from repro.net.peer import PeerClient, PeerUnavailable
from repro.net.server import NetServer
from repro.serve import ProofService

CFG = pv.ProverConfig(blowup=4, n_queries=16, fri_final_size=16)
ORIGIN = "zkgraph-serve-log"
# the log operator's Ed25519 identity.  The demo driver knowingly holds the
# signing half so it can play a MALICIOUS owner in the final act — which is
# exactly the threat gossip exists to catch: a correctly-signed but
# equivocating head.  Verifiers pin only KEY.pub.
KEY = SigningKey.from_secret(b"zkgraph-demo-origin-key")
TIMEOUT = float(os.environ.get("ZKGRAPH_DEMO_TIMEOUT", "900"))

# the multi-owner act: two owner organizations, each with its own tables
# and its own Ed25519 identity (verifiers pin only the .pub halves)
FED_KEY_A = SigningKey.from_secret(b"owner-a demo secret")
FED_KEY_B = SigningKey.from_secret(b"owner-b demo secret")
A_TABLES = ("knows", "knows_date", "knows_nodes", "person_firstName")
B_TABLES = ("hasCreator", "hasCreator_date", "hasCreator_rev", "replyOf",
            "replyOf_rev", "comment_date", "comment_content_date")


class DemoFailure(Exception):
    """A demo invariant did not hold (asserts would vanish under -O)."""


def check(cond, what: str) -> None:
    if not cond:
        raise DemoFailure(what)


@contextlib.contextmanager
def demo_phase(name: str):
    """Every driver act runs under a named phase; any failure exits
    NONZERO naming the phase — the machine-checkable contract the CI
    federation job (and anyone scripting this demo) keys on."""
    print(f"[driver] -- phase: {name}", flush=True)
    try:
        yield
    except SystemExit:
        raise
    except BaseException as e:
        print(f"[driver] FAILED in phase {name!r}: "
              f"{type(e).__name__}: {e}", flush=True)
        raise SystemExit(f"demo failed in phase {name!r}") from e

# deterministic fault scripts, one per verifier: the first frames of each
# verifier's owner-link get dropped/truncated/stalled, so bootstrap itself
# exercises retry-with-backoff and typed frame errors on every demo run
FAULT_SCRIPTS = {
    "v1": ["drop", "pass", "truncate", "pass", "drop"],
    "v2": ["pass", "drop", "pass", "truncate"],
}


def query_queue(db, n):
    import numpy as np
    rng = np.random.default_rng(41)
    qs = []
    for i in range(n):
        kind = ["IS3", "IS5", "IC13"][i % 3]
        if kind == "IS3":
            qs.append((kind, dict(person=int(rng.integers(1, db.n_nodes)))))
        elif kind == "IS5":
            qs.append((kind, dict(message=(1 << 20) + int(
                rng.integers(0, 32)))))
        else:
            qs.append((kind, dict(person1=int(rng.integers(1, 8)),
                                  person2=int(rng.integers(9, 24)))))
    return qs


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def atomic_write(path: Path, data: bytes) -> None:
    tmp = path.with_suffix(path.suffix + ".tmp")
    tmp.write_bytes(data)
    os.replace(tmp, path)       # readers only ever see complete files


def wait_for(path: Path, deadline: float) -> bytes:
    while time.time() < deadline:
        if path.exists():
            return path.read_bytes()
        time.sleep(0.1)
    raise TimeoutError(f"timed out waiting for {path}")


def read_port(d: Path, name: str, deadline: float) -> int:
    return int(wait_for(d / f"{name}.port", deadline).decode())


def _cfg_args(cfg: pv.ProverConfig, n_knows: int, n_persons: int) -> list:
    return ["--blowup", str(cfg.blowup), "--n-queries", str(cfg.n_queries),
            "--fri-final-size", str(cfg.fri_final_size),
            "--n-knows", str(n_knows), "--n-persons", str(n_persons)]


def _build(args):
    cfg = pv.ProverConfig(blowup=args.blowup, n_queries=args.n_queries,
                          fri_final_size=args.fri_final_size)
    db = ldbc.generate(n_knows=args.n_knows, n_persons=args.n_persons,
                       seed=3)
    return db, cfg


# ---------------------------------------------------------------------------
# the owner process: a durable log + a ProofService behind a NetServer
# ---------------------------------------------------------------------------
def run_owner(args) -> None:
    d = Path(args.dir)
    db, cfg = _build(args)
    session = ZKGraphSession(db, cfg)
    log = TransparencyLog.open(d / "transparency.log", ORIGIN)
    if log.recovered_bytes:
        print(f"[owner] crash recovery: truncated {log.recovered_bytes} "
              f"torn-tail bytes, {log.size} intact leaves", flush=True)
    raw = session.commitments.to_bytes()
    if log.size == 0:
        _, _, raw = session.publish_to(log)
        print(f"[owner] manifest published: {len(raw)} bytes -> "
              f"log {ORIGIN!r} size {log.size}", flush=True)
    else:
        assert log.entry(0) == raw, "restart re-derived a different manifest"
        print(f"[owner] resumed with {log.size} published leaves", flush=True)
    log.sync()                  # audit disk against memory before serving

    spool = d / "bundles"
    spool.mkdir(exist_ok=True)
    log_lock = threading.Lock()     # server threads vs the revision append

    def on_head(payload):
        with log_lock:
            return (framing.RESP_HEAD, gossip.emit(log, KEY).to_bytes())

    def on_manifest(payload):
        return (framing.RESP_MANIFEST, raw)

    def on_inclusion(payload):
        # payload: the verifier's pinned tree size, so the proof targets
        # exactly the checkpoint that verifier has verified
        size = int.from_bytes(payload, "little") if payload else 1
        with log_lock:
            return (framing.RESP_INCLUSION,
                    log.inclusion_proof(0, size).to_bytes())

    def on_consistency(payload):
        since = int.from_bytes(payload, "little")
        with log_lock:
            return (framing.RESP_CONSISTENCY,
                    gossip.emit(log, KEY, since=since).to_bytes())

    def on_bundle(payload):
        cursor = int.from_bytes(payload, "little")
        path = spool / f"q{cursor}.bin"
        if cursor >= args.queries:
            raise ValueError(f"no query at cursor {cursor}")
        if not path.exists():
            return (framing.RESP_PENDING, b"")
        return (framing.RESP_BUNDLE, path.read_bytes())

    srv = NetServer()
    srv.register(framing.REQ_PING, lambda p: (framing.RESP_PONG, p))
    srv.register(framing.REQ_HEAD, on_head)
    srv.register(framing.REQ_MANIFEST, on_manifest)
    srv.register(framing.REQ_INCLUSION, on_inclusion)
    srv.register(framing.REQ_CONSISTENCY, on_consistency)
    srv.register(framing.REQ_BUNDLE, on_bundle)
    _, port = srv.start()
    atomic_write(d / "owner.port", str(port).encode())
    print(f"[owner] serving on 127.0.0.1:{port}", flush=True)

    pending = [(i, kind, params)
               for i, (kind, params) in enumerate(query_queue(db,
                                                              args.queries))
               if not (spool / f"q{i}.bin").exists()]
    # all unproven queries ride ONE ProofService: same-shaped steps from
    # different queries share lane-batched proves, and each returned bundle
    # is wire-byte-identical to a solo session.prove (spot-checked below)
    if pending:
        with ProofService(session, max_batch=4, flush_interval=0.25) as svc:
            t0 = time.time()
            futs = [(i, kind, svc.submit(kind, params))
                    for i, kind, params in pending]
            for i, kind, fut in futs:
                bundle = fut.result()
                atomic_write(spool / f"q{i}.bin", bundle.to_bytes())
                print(f"[owner] q{i} {kind:5s} spooled at "
                      f"{time.time() - t0:.1f}s ({len(bundle.steps)} ops)",
                      flush=True)
            occupancy = svc.stats()["batch_occupancy"]
        print(f"[owner] served {len(pending)} queries, mean batch "
              f"occupancy {occupancy['mean']:.2f}", flush=True)
        # byte-for-byte spot check: re-prove one serviced query solo and
        # compare wire bytes
        i0, kind0, params0 = pending[0]
        serviced = (spool / f"q{i0}.bin").read_bytes()
        solo = session.prove(kind0, params0)
        assert serviced == solo.to_bytes(), \
            "serviced bundle bytes diverged from the solo prover"
        print(f"[owner] q{i0} re-proven solo: bytes identical", flush=True)

    with log_lock:
        if log.size < 2:        # manifest revision: the log must only GROW
            session.publish_to(log)
        head = log.sync()
    stats = session.cache.stats()
    atomic_write(d / "owner.done", json.dumps(dict(
        queries=args.queries, tree_size=head.tree_size,
        keygen_misses=stats["misses"], keygen_hits=stats["hits"]),
        sort_keys=True).encode())
    print(f"[owner] done: log size {head.tree_size}, keygen cache "
          f"{stats['misses']} misses / {stats['hits']} hits; still serving",
          flush=True)
    # stay up serving heads/proofs/bundles until the driver reaps us
    while True:
        time.sleep(0.5)


# ---------------------------------------------------------------------------
# a verifier process: its own gossip server + a fault-proxied owner link
# ---------------------------------------------------------------------------
class OwnerLink:
    """The verifier's resilient path to the owner: resolves the owner's
    current port from the work dir, optionally routes through a
    deterministic FaultProxy, and retries through PeerUnavailable — which
    is exactly what an owner SIGKILL and restart on a new port looks like
    from this side.  Every wait is bounded by the shared deadline."""

    def __init__(self, d: Path, name: str, deadline: float, faults):
        self.d = d
        self.name = name
        self.deadline = deadline
        self.faults = list(faults)
        self.port = None
        self.proxy = None
        self.client = None

    def _connect(self) -> None:
        port = read_port(self.d, "owner", self.deadline)
        if port == self.port and self.client is not None:
            return
        self.close()
        self.port = port
        target = ("127.0.0.1", port)
        if self.faults:
            # the scripted faults hit this first incarnation of the link;
            # a reconnect after owner restart goes direct
            self.proxy = FaultProxy(target, script=self.faults,
                                    stall_seconds=1.0)
            target = self.proxy.start()
            self.faults = []
        self.client = PeerClient(target, timeout=2.0, retries=3,
                                 backoff=0.05, cooldown=0.3)

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
            self.client = None
        if self.proxy is not None:
            self.proxy.stop()
            self.proxy = None

    def rpc(self, kind: int, payload: bytes = b"",
            fallback=None) -> tuple[int, bytes]:
        """One request, surviving owner death: on PeerUnavailable the link
        re-resolves the port (the restarted owner binds a new one) and
        tries again until the deadline.  ``fallback`` is called once per
        outage — the hook verifiers use to report they keep serving from
        the pinned head instead of wedging."""
        reported = False
        while time.time() < self.deadline:
            self._connect()
            try:
                return self.client.request(kind, payload)
            except PeerUnavailable:
                if fallback is not None and not reported:
                    fallback()
                    reported = True
                # stale port file?  the restarted owner rewrites it
                self.port = None
                time.sleep(0.2)
        raise TimeoutError(f"[{self.name}] owner unreachable past deadline")


def run_verifier(args) -> None:
    d = Path(args.dir)
    name = args.name
    deadline = time.time() + TIMEOUT
    # proof policy only — a verifier holds NO database, just the trust root
    cfg = pv.ProverConfig(blowup=args.blowup, n_queries=args.n_queries,
                          fri_final_size=args.fri_final_size)

    peer = gossip.GossipPeer(ORIGIN, KEY.pub)
    peer_lock = threading.Lock()
    equivocation = {"detected": False, "evidence": ""}
    alarm = threading.Event()

    def on_gossip(payload):
        """Another peer (or the adversary) pushes a head at this verifier:
        verify-and-advance under the lock; an equivocating head answers
        with the alarm frame carrying the evidence."""
        msg = gossip.GossipMessage.from_bytes(payload)
        try:
            with peer_lock:
                advanced = peer.offer(msg)
        except gossip.EquivocationError as e:
            equivocation.update(detected=True, evidence=str(e))
            alarm.set()
            print(f"[{name}] ALARM: {e}", flush=True)
            return (framing.RESP_EQUIVOCATION, str(e).encode("utf-8"))
        return (framing.RESP_ACK, b"advanced" if advanced else b"agreed")

    def on_head(payload):
        with peer_lock:
            return (framing.RESP_HEAD, peer.head_message().to_bytes())

    srv = NetServer()
    srv.register(framing.REQ_PING, lambda p: (framing.RESP_PONG, p))
    srv.register(framing.REQ_GOSSIP, on_gossip)
    srv.register(framing.REQ_HEAD, on_head)
    _, port = srv.start()
    atomic_write(d / f"{name}.port", str(port).encode())

    link = OwnerLink(d, name, deadline,
                     FAULT_SCRIPTS.get(name, []) if args.faults else [])

    def fallback():
        with peer_lock:
            pinned = peer.head.tree_size if peer.head is not None else None
        state = f"serving from pinned head @{pinned}" if pinned is not None \
            else "no head pinned yet"
        print(f"[{name}] owner unreachable; {state}, retrying", flush=True)

    # ---- bootstrap: the whole trust root arrives as frames ---------------
    kind, head_raw = link.rpc(framing.REQ_HEAD, fallback=fallback)
    assert kind == framing.RESP_HEAD, f"expected RESP_HEAD, got {kind:#x}"
    with peer_lock:
        peer.offer(gossip.GossipMessage.from_bytes(head_raw))
        boot_size = peer.pinned.tree_size
    kind, manifest_raw = link.rpc(framing.REQ_MANIFEST, fallback=fallback)
    assert kind == framing.RESP_MANIFEST
    kind, incl_raw = link.rpc(framing.REQ_INCLUSION,
                              int(boot_size).to_bytes(8, "little"),
                              fallback=fallback)
    assert kind == framing.RESP_INCLUSION
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(gossip=peer,
                           inclusion=InclusionProof.from_bytes(incl_raw),
                           manifest_bytes=manifest_raw), cfg=cfg)
    print(f"[{name}] trust root bootstrapped over TCP from gossip-pinned "
          f"head @{boot_size}", flush=True)

    # ---- stream the bundles (the owner dies and resumes mid-stream) ------
    results = {}
    for i in range(args.queries):
        while True:
            kind, data = link.rpc(framing.REQ_BUNDLE,
                                  i.to_bytes(8, "little"), fallback=fallback)
            if kind == framing.RESP_BUNDLE:
                break
            assert kind == framing.RESP_PENDING, f"unexpected {kind:#x}"
            if time.time() > deadline:
                raise TimeoutError(f"[{name}] q{i} never arrived")
            time.sleep(0.2)
        results[f"q{i}"] = bool(verifier.verify_bytes(data))
        print(f"[{name}] q{i} verified from {len(data)} bytes: "
              f"{results[f'q{i}']}", flush=True)

    # ---- the owner revised the manifest: advance ONLY on a proof ---------
    advanced = False
    while time.time() < deadline and not advanced:
        kind, head_raw = link.rpc(framing.REQ_HEAD, fallback=fallback)
        assert kind == framing.RESP_HEAD
        msg = gossip.GossipMessage.from_bytes(head_raw)
        with peer_lock:
            if msg.checkpoint.tree_size == peer.pinned.tree_size:
                pass                            # not revised yet
            else:
                try:
                    advanced = peer.offer(msg)
                except gossip.ConsistencyRequired:
                    pass                        # fetch the linking proof
        if advanced:
            break
        if msg.checkpoint.tree_size > boot_size:
            kind, linked = link.rpc(
                framing.REQ_CONSISTENCY,
                int(peer.pinned.tree_size).to_bytes(8, "little"),
                fallback=fallback)
            assert kind == framing.RESP_CONSISTENCY
            with peer_lock:
                advanced = peer.offer(gossip.GossipMessage.from_bytes(linked))
        else:
            time.sleep(0.2)
    print(f"[{name}] head advanced to @{peer.pinned.tree_size} "
          f"(append-only growth proven)", flush=True)
    atomic_write(d / f"{name}.advanced", b"1")

    # ---- verifier <-> verifier gossip over TCP ---------------------------
    other = "v2" if name == "v1" else "v1"
    wait_for(d / f"{other}.advanced", deadline)
    other_client = PeerClient(("127.0.0.1", read_port(d, other, deadline)),
                              timeout=2.0, retries=5, backoff=0.1)
    with peer_lock:
        my_head = peer.head_message().to_bytes()
    kind, verdict = other_client.request(framing.REQ_GOSSIP, my_head)
    other_client.close()
    assert kind == framing.RESP_ACK, \
        f"cross-gossip with {other} raised: {verdict!r}"
    cross = verdict == b"advanced"
    print(f"[{name}] cross-gossip with {other}: heads agree "
          f"({verdict.decode()})", flush=True)

    # ---- the forged fork arrives on OUR server; wait for the alarm -------
    if not alarm.wait(timeout=max(0.0, deadline - time.time())):
        print(f"[{name}] no equivocation push arrived before the deadline",
              flush=True)
    atomic_write(d / f"{name}.done", json.dumps(dict(
        results=results, advanced=bool(advanced), cross_advance=bool(cross),
        equivocation_detected=bool(equivocation["detected"]),
        head=peer.pinned.tree_size), sort_keys=True).encode())
    # stay up until the driver reaps us: the other verifier or the driver
    # may still be talking to our gossip server
    while True:
        time.sleep(0.5)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------
def _spawn(role: str, d: str, args, extra=()) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    if role != "owner":
        env["JAX_PLATFORMS"] = "cpu"     # the chip is the owner's
    cmd = [sys.executable, str(Path(__file__).resolve()), "--role", role,
           "--dir", d, "--queries", str(args.queries),
           *(() if args.faults else ("--no-faults",)),
           *_cfg_args(pv.ProverConfig(args.blowup, args.n_queries,
                                      args.fri_final_size), args.n_knows,
                      args.n_persons), *extra]
    return subprocess.Popen(cmd, env=env)


def _wait_done(path: Path, procs, deadline: float) -> dict:
    while time.time() < deadline:
        if path.exists():
            return json.loads(path.read_bytes())
        for p in procs:
            if p.poll() not in (None, 0):
                raise RuntimeError(
                    f"child {p.args[-1]} exited with {p.returncode} "
                    f"before producing {path.name}")
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {path}")


def run_federation_act(args) -> None:
    """Two owner organizations, one verifier verdict.

    owner-a holds the person/knows tables, owner-b the comment tables;
    each publishes its own manifest on its OWN transparency log.  IC2
    cuts across the boundary, so the coordinator routes sub-plan 0 to
    owner-a (in-process) and sub-plan 1 to owner-b over a real TCP
    socket, and the verifier composes the verdict from both owners'
    checkpoint-pinned anchors.  Then owner-b dies: the cross-owner query
    degrades typed while an owner-a-only query keeps proving."""
    db, cfg = _build(args)
    partition = GraphPartition((
        OwnerSpec("owner-a", A_TABLES, FED_KEY_A.pub),
        OwnerSpec("owner-b", B_TABLES, FED_KEY_B.pub)))
    oa = OwnerNode.from_partition(partition, "owner-a", db, FED_KEY_A, cfg)
    ob = OwnerNode.from_partition(partition, "owner-b", db, FED_KEY_B, cfg)
    anchors = {}
    for name, node in (("owner-a", oa), ("owner-b", ob)):
        cp, incl, man_raw = node.publish_to(
            TransparencyLog(f"zkgraph-{name}-log"))
        anchors[name] = TrustAnchor(checkpoint=cp, inclusion=incl,
                                    manifest_bytes=man_raw)
        print(f"[driver] {name}: manifest published on its own log, "
              f"anchor pinned @{cp.tree_size}", flush=True)

    ic2 = dict(person=4, k=10)
    server = NetServer(conn_timeout=TIMEOUT)
    ob.serve_on(server)
    with server.serving() as (host, port):
        print(f"[driver] owner-b proving on {host}:{port} (TCP)",
              flush=True)
        clients = {
            "owner-a": LocalOwnerClient(oa),
            "owner-b": RemoteOwnerClient(
                PeerClient((host, port), timeout=TIMEOUT)),
        }
        with FederationCoordinator(partition, clients, cfg) as co:
            t0 = time.time()
            fed = co.prove("IC2", ic2)
        dead_port = port
    parts = [(p.owner, p.lo, p.hi) for p in fed.parts]
    print(f"[driver] federated IC2 proven in {time.time() - t0:.1f}s: "
          f"{parts}", flush=True)
    check(parts == [("owner-a", 0, 1), ("owner-b", 1, 4)],
          f"unexpected plan cuts: {parts}")
    check(verify_federated(fed.__class__.from_bytes(fed.to_bytes()),
                           partition, anchors, cfg),
          "verify_federated rejected an honestly-proven bundle")
    print("[driver] verify_federated accepted both owners' sub-proofs, "
          "chain joint + hand-off signature checked", flush=True)

    # owner-b is now dead (its server closed with the `with` block): the
    # cross-owner query degrades TYPED, the single-owner query still runs
    dead = {
        "owner-a": LocalOwnerClient(oa),
        "owner-b": RemoteOwnerClient(PeerClient(
            ("127.0.0.1", dead_port), timeout=0.3, retries=1,
            backoff=0.01)),
    }
    with FederationCoordinator(partition, dead, cfg) as co:
        try:
            co.prove("IC2", ic2)
            check(False, "dead owner-b did not fail the cross-owner query")
        except OwnerUnavailableError as e:
            print(f"[driver] owner-b dead: IC2 degraded typed "
                  f"({type(e).__name__})", flush=True)
        fed13 = co.prove("IC13", dict(person1=1, person2=9))
    check([(p.owner) for p in fed13.parts] == ["owner-a"],
          "IC13 should cut entirely to owner-a")
    check(verify_federated(fed13, partition, anchors, cfg),
          "owner-a-only query must keep serving while owner-b is dead")
    print("[driver] degradation contract held: dead owner kills the "
          "query, not the service", flush=True)


def run_driver(args) -> dict:
    d = Path(args.dir or tempfile.mkdtemp(prefix="zkgraph_demo_"))
    d.mkdir(parents=True, exist_ok=True)
    stale = [p.name for p in (d / "owner.done", d / "v1.done",
                              d / "v2.done", d / "transparency.log")
             if p.exists()]
    if stale:
        raise SystemExit(
            f"[driver] {d} holds artifacts from a previous run ({stale}); "
            f"the demo's waits would satisfy themselves from them without "
            f"exercising anything — use a fresh --dir")
    (d / "bundles").mkdir(exist_ok=True)
    print(f"[driver] work dir: {d}", flush=True)
    deadline = time.time() + TIMEOUT
    children = []
    try:
        with demo_phase("spawn-fleet"):
            for name in ("v1", "v2"):
                children.append(_spawn("verifier", str(d), args,
                                       ("--name", name)))
            owner = _spawn("owner", str(d), args)
            children.append(owner)

        with demo_phase("crash-recovery"):
            # let the owner prove `kill_after` queries, then pull the plug
            kill_mark = d / "bundles" / f"q{args.kill_after - 1}.bin"
            wait_for(kill_mark, deadline)
            try:
                owner.send_signal(signal.SIGKILL)
            except ProcessLookupError:
                pass            # already exited: restart is a plain resume
            owner.wait()
            (d / "owner.port").unlink(missing_ok=True)  # port died with it
            print(f"[driver] owner SIGKILLed after {args.kill_after} "
                  f"queries", flush=True)
            # what a crash mid-write leaves: a torn half-record on the tail
            with open(d / "transparency.log", "ab") as fh:
                fh.write(b"\x01\x40\x00\x00\x00partial")
            print("[driver] torn half-record appended to the log tail",
                  flush=True)
            owner = _spawn("owner", str(d), args)
            children.append(owner)
            owner_summary = _wait_done(d / "owner.done", [owner], deadline)
            check(owner_summary["tree_size"] == 2,
                  f"restarted owner's log should hold exactly the manifest "
                  f"+ one revision, got size {owner_summary['tree_size']}")

        with demo_phase("equivocation-alarm"):
            # the malicious-owner act: fork the history (different leaf 0),
            # sign the forked head with the REAL origin key, and PUSH it to
            # both verifiers' gossip servers — only after both advanced, so
            # the fork collides with verified history, not a knowledge gap
            for name in ("v1", "v2"):
                wait_for(d / f"{name}.advanced", deadline)
            client = PeerClient(
                ("127.0.0.1", read_port(d, "owner", deadline)),
                timeout=2.0, retries=5, backoff=0.1)
            kind, manifest_raw = client.request(framing.REQ_MANIFEST, b"")
            client.close()
            check(kind == framing.RESP_MANIFEST,
                  f"owner answered {kind:#x} to REQ_MANIFEST")
            fork = TransparencyLog(ORIGIN)
            fork.append(manifest_raw + b"\xff")
            fork.append(manifest_raw)
            forged = gossip.emit(fork, KEY)
            alarms = {}
            for name in ("v1", "v2"):
                client = PeerClient(
                    ("127.0.0.1", read_port(d, name, deadline)),
                    timeout=2.0, retries=5, backoff=0.1)
                kind, evidence = client.request(framing.REQ_GOSSIP,
                                                forged.to_bytes())
                client.close()
                alarms[name] = (kind, evidence)
                print(f"[driver] forged (signed!) fork head pushed to "
                      f"{name}: frame {kind:#x}", flush=True)
            for name, (kind, evidence) in alarms.items():
                check(kind == framing.RESP_EQUIVOCATION,
                      f"{name} answered {kind:#x} instead of the alarm "
                      f"frame")
                check(b"equivocation detected" in evidence,
                      f"{name} alarm carries no evidence: {evidence!r}")

        with demo_phase("verifier-verdicts"):
            summaries = {
                name: _wait_done(d / f"{name}.done", children[:2], deadline)
                for name in ("v1", "v2")}
            for name, s in summaries.items():
                check(all(s["results"].values()),
                      f"{name} rejected a bundle: {s}")
                check(s["advanced"] and not s["cross_advance"], str(s))
                check(s["equivocation_detected"] is True,
                      f"{name} missed the equivocation")
    finally:
        for p in children:
            if p.poll() is None:
                p.kill()

    with demo_phase("federation"):
        run_federation_act(args)

    n_ok = sum(len(s["results"]) for s in summaries.values())
    print(f"[driver] OK: crash-recovered owner served {args.queries} "
          f"queries over TCP; {n_ok} bundle verifications across 2 "
          f"verifier processes; revision advanced by consistency proof; "
          f"forged fork alarmed by both peers; 2-owner federated IC2 "
          f"proven, verified, and dead-owner degraded", flush=True)
    return dict(owner=owner_summary, **summaries)


def main(argv=None, n_knows=128, n_persons=24, cfg=CFG):
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=["driver", "owner", "verifier"],
                    default="driver")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--name", default="v1")
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--kill-after", type=int, default=2,
                    help="SIGKILL the owner after this many proven queries")
    ap.add_argument("--no-faults", dest="faults", action="store_false",
                    help="disable the deterministic frame-fault injection "
                         "on the verifiers' owner links")
    ap.add_argument("--blowup", type=int, default=cfg.blowup)
    ap.add_argument("--n-queries", type=int, default=cfg.n_queries)
    ap.add_argument("--fri-final-size", type=int, default=cfg.fri_final_size)
    ap.add_argument("--n-knows", type=int, default=n_knows)
    ap.add_argument("--n-persons", type=int, default=n_persons)
    args = ap.parse_args(argv)
    # the kill mark must be a bundle the owner actually produces, or the
    # driver would wait out the whole demo timeout on a short queue
    args.kill_after = max(1, min(args.kill_after, args.queries))
    if args.role == "owner":
        return run_owner(args)
    if args.role == "verifier":
        return run_verifier(args)
    # a chip belongs to one process: the owner proves on it; the driver's
    # own JAX work (commit, forged head, federation act) runs on the host
    import jax
    jax.config.update("jax_platforms", "cpu")
    return run_driver(args)


if __name__ == "__main__":
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    main()
