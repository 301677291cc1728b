#!/usr/bin/env python3
"""On-chip smoke run of ZKGraph's main path: commit -> prove -> verify.

    python chip_smoke.py [--seed S] [--n-knows N]
    python chip_smoke.py --four-chips

Runs in one process on one TPU, through the entry points a user calls
(``ZKGraphSession``, ``ProofService``), with the compiled ``pallas``
backend, on LDBC-shaped tables of the smallest size the paper proved
(60k-row fact tables, 2^16-row circuits) with
``benchmarks/common.py:BENCH_CFG``:

* commit: publish the base-table commitments once;
* prove IS3, IS4, IS5 and IC1 (six chained steps) with parameters drawn
  from ``--seed``; each result must equal a plain evaluator built from
  ``repro.graphdb.engine`` over the uncommitted tables, an independent
  verifier must accept the bundle's wire bytes and reject them with one
  byte flipped;
* prove IS5 again under the ``ref`` backend on the chip: its wire bytes
  must equal the ``pallas`` bytes;
* serve eight IS5 submissions through ``ProofService``: their bytes must
  equal solo proves.

``--four-chips`` runs only the serving placement across four devices: a
``ProofService`` with ``Placement(serving_mesh())`` proves a batch of eight
IS5 lanes split over the four chips, compared byte for byte with solo
one-device proves in the same process.  What it checks (lane placement,
per-device kernels, byte identity) does not depend on table scale, and a
cold run is compile-bound, so it defaults to small tables
(``FOUR_CHIP_ROWS``).

Earlier lines report the devices, each phase's wall time (a proof is
complete once its bytes are on the host), bundle sizes and a ``reduced:``
line for every cut of scale.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU, when the compiled
backend fails its probe, or when any check fails, the script exits
nonzero with the reason and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_ROWS = 60_000      # smallest fact-table size the paper proved
DEFAULT_ROWS = PAPER_ROWS
FOUR_CHIP_ROWS = 512
SERVE_LANES = 8
FOUR = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(cond, what: str) -> None:
    if not cond:
        fail(what)


class CompileMeter:
    """XLA compiles seen through ``jax.monitoring``, so each phase can say
    how much of its wall time went to compiling."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.secs += secs

    def mark(self):
        return self.count, self.secs

    def since(self, mark) -> str:
        return (f"{self.count - mark[0]} XLA compiles, "
                f"{self.secs - mark[1]:.3f} s")


METER = None


def timed(fn, *args, **kwargs):
    """(result, wall seconds, compile note) of one call."""
    mark = METER.mark()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0, METER.since(mark)


# ---------------------------------------------------------------------------
# set-up: the program, the chip, the compiled backend
# ---------------------------------------------------------------------------
def import_program():
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not next to this script ({ROOT}/src)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import backend
    cache = backend.enable_compile_cache()
    log(f"compile cache: {cache}")
    return backend


def require_tpu(n_devices: int):
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        fail(f"JAX finds no TPU (default backend: {platform})")
    devices = jax.devices()
    log(f"devices: {devices}")
    check(len(devices) >= n_devices,
          f"{n_devices} TPU devices needed, JAX sees {len(devices)}")
    return devices


def select_pallas(backend):
    """Pin the compiled backend for the whole process, or fail."""
    import os
    try:
        backend.require("pallas")
    except backend.BackendUnavailableError as e:
        fail(str(e))
    os.environ[backend.ENV_VAR] = "pallas"
    check(backend.active_name() == "pallas", "pallas backend not active")
    log("backend: pallas (compiled Pallas kernels; probe matches ref)")


# ---------------------------------------------------------------------------
# data, parameters and the plain evaluator
# ---------------------------------------------------------------------------
def make_db(n_knows: int, seed: int, why_reduced: str):
    from repro.graphdb import ldbc
    db, secs, _ = timed(ldbc.generate, n_knows=n_knows, n_comments=n_knows,
                        seed=seed)
    log(f"data: n_knows={n_knows} n_comments={n_knows} "
        f"n_persons={db.n_nodes} seed={seed} ({secs:.3f} s)")
    if n_knows < PAPER_ROWS:
        log(f"reduced: n_knows {PAPER_ROWS} -> {n_knows} (fact tables below "
            f"the paper's smallest proved size; {why_reduced})")
    return db


def draw_params(db, seed: int) -> dict:
    import numpy as np
    from repro.graphdb.tables import COMMENT_ID_BASE
    rng = np.random.default_rng(seed)
    knows = db.tables["person_knows_person"]
    n_comments = len(db.tables["comment_hasCreator_person"])
    first = db.node_props["person"]["firstName"]
    messages = rng.choice(n_comments, SERVE_LANES + 2, replace=False)
    return {
        "IS3": dict(person=int(rng.choice(knows.src))),
        "IS4": dict(message=COMMENT_ID_BASE + int(messages[0])),
        "IS5": dict(message=COMMENT_ID_BASE + int(messages[1])),
        "IC1": dict(person=int(rng.choice(knows.src)),
                    firstName=int(first[rng.integers(len(first))])),
        "serve": [dict(message=COMMENT_ID_BASE + int(m))
                  for m in messages[2:]],
    }


def plain_result(db, qname: str, params: dict) -> dict:
    """The query's answer from ``repro.graphdb.engine`` over the
    uncommitted tables — no plan IR, no circuits, no witnesses."""
    import numpy as np
    from repro.graphdb import engine
    from repro.graphdb.tables import COMMENT_ID_BASE
    knows = db.tables["person_knows_person"]
    if qname == "IS3":
        friends, fwd, bwd = engine.expand_undirected(knows, params["person"])
        date = knows.props["creationDate"]
        dates = np.concatenate([date[fwd], date[bwd]])
        return dict(pairs=sorted(zip(friends.tolist(), dates.tolist())))
    if qname == "IS4":
        mid = params["message"] - COMMENT_ID_BASE
        cp = db.node_props["comment"]
        return dict(content=[int(cp["content"][mid])],
                    date=[int(cp["creationDate"][mid])])
    if qname == "IS5":
        creator, _ = engine.expand(db.tables["comment_hasCreator_person"],
                                   params["message"])
        return dict(creator=sorted(creator.tolist()))
    if qname == "IC1":
        p = params["person"]
        dist, _, _ = engine.bfs_sssp(knows, db.node_ids, p, True)
        near = (dist >= 1) & (dist <= 3)
        if near.any():          # p is its neighbours' neighbour
            near |= db.node_ids == p
        first = db.node_props["person"]["firstName"]
        match = db.node_ids[near & (first == params["firstName"])]
        return dict(persons=sorted(match.tolist(), reverse=True)[:20])
    raise KeyError(qname)


def proved_result(qname: str, result: dict) -> dict:
    """The bundle's claimed answer, in the plain evaluator's form."""
    import numpy as np
    if qname == "IS3":
        dates = np.asarray(result["dates"])
        check((np.diff(dates) <= 0).all(), "IS3 dates not newest first")
        return dict(pairs=sorted(zip(np.asarray(result["friends"]).tolist(),
                                     dates.tolist())))
    if qname == "IS4":
        return dict(content=np.asarray(result["content"]).tolist(),
                    date=np.asarray(result["date"]).tolist())
    if qname == "IS5":
        return dict(creator=sorted(np.asarray(result["creator"]).tolist()))
    if qname == "IC1":
        return dict(persons=sorted(np.asarray(result["persons"]).tolist(),
                                   reverse=True))
    raise KeyError(qname)


def flip_one_byte(raw: bytes) -> bytes:
    i = len(raw) // 2
    return raw[:i] + bytes([raw[i] ^ 0x01]) + raw[i + 1:]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def owner_session(db):
    sys.path.insert(0, str(ROOT))
    from benchmarks.common import BENCH_CFG
    from repro.core.session import ZKGraphSession
    owner = ZKGraphSession(db, BENCH_CFG)
    manifest, secs, note = timed(lambda: owner.commitments)
    log(f"commit: {len(manifest)} base-table commitments published "
        f"({secs:.3f} s, LDE + Merkle; {note})")
    return owner


def prove_and_check(owner, verifier, db, qname: str, params: dict):
    bundle, secs, note = timed(owner.prove, qname, params)
    raw = bundle.to_bytes()
    want = plain_result(db, qname, params)
    got = proved_result(qname, bundle.result)
    check(got == want, f"{qname} result {got} != plain evaluator {want}")
    ok, vsecs, vnote = timed(verifier.verify_bytes, raw)
    check(ok, f"verifier rejected the honest {qname} bundle")
    check(not verifier.verify_bytes(flip_one_byte(raw)),
          f"verifier accepted a {qname} bundle with one byte flipped")
    log(f"prove {qname}: {secs:.3f} s first prove ({note}), "
        f"{len(bundle.steps)} steps, {len(raw)} bundle bytes; result == "
        f"plain evaluator; verify {vsecs:.3f} s accept ({vnote}); "
        f"one-byte flip rejected")
    return bundle


def run_one_chip(args) -> None:
    import dataclasses
    from repro.core.session import TrustAnchor, ZKGraphSession
    db = make_db(args.n_knows, args.seed, "set by --n-knows")
    log("reduced: queries IS3/IS4/IS5/IC1 of the eight plans")
    params = draw_params(db, args.seed)
    owner = owner_session(db)
    verifier = ZKGraphSession.verifier(
        anchor=TrustAnchor(manifest=owner.commitments), cfg=owner.cfg)

    bundles = {q: prove_and_check(owner, verifier, db, q, params[q])
               for q in ("IS3", "IS4", "IS5", "IC1")}

    warm, secs, note = timed(owner.prove, "IS5", params["IS5"])
    phases = ", ".join(f"{k} {v:.3f}" for k, v in
                       warm.steps[0].proof.timings.items())
    log(f"warm prove IS5: {secs:.3f} s ({note}; prover phases, s: "
        f"{phases})")
    pallas_raw = warm.to_bytes()
    check(pallas_raw == bundles["IS5"].to_bytes(),
          "two pallas proves of one IS5 query differ")

    ref_cfg = dataclasses.replace(owner.cfg, backend="ref")
    ref_owner = ZKGraphSession(db, ref_cfg, commitments=owner.commitments)
    ref_bundle, secs, note = timed(ref_owner.prove, "IS5", params["IS5"])
    check(ref_bundle.to_bytes() == pallas_raw,
          "ref and pallas IS5 wire bytes differ on the chip")
    log(f"ref == pallas: IS5 wire bytes identical on the chip "
        f"({len(pallas_raw)} bytes; ref prove {secs:.3f} s, {note})")

    served, stats, secs, note = serve(owner, params["serve"])
    solo, solo_secs, _ = timed(lambda: [owner.prove("IS5", p)
                                        for p in params["serve"]])
    for p, a, b in zip(params["serve"], served, solo):
        check(a.to_bytes() == b.to_bytes(),
              f"ProofService bytes != solo prove for IS5 {p}")
        check(verifier.verify(a), f"served IS5 {p} rejected")
    log(f"serve: {SERVE_LANES} IS5 submissions through ProofService "
        f"({stats['counters']['batches']} batches, {secs:.3f} s, {note}) "
        f"byte-identical to solo proves ({solo_secs:.3f} s)")


def serve(owner, params, placement=None):
    """Submit IS5 for every params entry to one ProofService; returns
    (bundles, stats, wall seconds, compile note)."""
    from repro.serve import ProofService

    def run():
        with ProofService(owner, max_batch=SERVE_LANES, flush_interval=0.5,
                          placement=placement) as svc:
            futures = [svc.submit("IS5", p) for p in params]
            return [f.result() for f in futures], svc.stats()
    (served, stats), secs, note = timed(run)
    return served, stats, secs, note


def run_four_chips(args) -> None:
    from repro.serve.placement import Placement, serving_mesh
    db = make_db(args.n_knows, args.seed,
                 "lane placement and byte identity do not depend on scale")
    log("reduced: queries IS5 only")
    params = draw_params(db, args.seed)["serve"]
    owner = owner_session(db)
    placement = Placement(serving_mesh())
    check(placement.lane_parallelism == FOUR,
          f"serving mesh spans {placement.lane_parallelism} devices")
    solo, solo_secs, _ = timed(lambda: [owner.prove("IS5", p)
                                        for p in params])
    served, stats, secs, note = serve(owner, params, placement)
    check(stats["counters"]["batches"] == 1,
          f"expected one batch of {SERVE_LANES} lanes, got "
          f"{stats['counters']['batches']}")
    for p, a, b in zip(params, served, solo):
        check(a.to_bytes() == b.to_bytes(),
              f"four-chip ProofService bytes != solo prove for IS5 {p}")
    log(f"four chips: {SERVE_LANES} IS5 lanes in one batch over {FOUR} "
        f"devices ({secs:.3f} s, {note}) byte-identical to solo one-device "
        f"proves ({solo_secs:.3f} s)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-knows", type=int, default=None,
                    help=f"fact-table rows (default {DEFAULT_ROWS}; "
                         f"{FOUR_CHIP_ROWS} with --four-chips)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device serving placement")
    args = ap.parse_args(argv)
    if args.n_knows is None:
        args.n_knows = FOUR_CHIP_ROWS if args.four_chips else DEFAULT_ROWS
    global METER
    backend = import_program()
    METER = CompileMeter()
    devices = require_tpu(FOUR if args.four_chips else 1)
    select_pallas(backend)
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(args)
    else:
        run_one_chip(args)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
