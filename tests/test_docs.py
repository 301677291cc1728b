"""Docs stay true: the protocol spec's pinned constants and worked-example
digest are checked against the live codec, and the README quickstart block
must exist and reference the real API (CI additionally *executes* it via
docs/run_quickstart.py)."""
import re
from pathlib import Path

from repro.core import wire

ROOT = Path(__file__).resolve().parent.parent


def _section(text, start, end=None):
    """The slice of a doc between two headings (to its end if end=None)."""
    i = text.index(start)
    return text[i:text.index(end)] if end else text[i:]


_PIN_ROW = r"\|\s*`([A-Z_]+)`\s*\|\s*`([0-9a-fx]+)`\s*\|"


def test_protocol_constants_match_wire_module():
    text = (ROOT / "docs" / "protocol.md").read_text()
    rows = re.findall(_PIN_ROW, _section(text, "## 8.", "## 9."))
    pinned = dict(rows)
    assert "MAGIC" in pinned and "WIRE_VERSION" in pinned, \
        "protocol.md §8 constants table is missing or unparseable"
    assert bytes.fromhex(pinned.pop("MAGIC")) == wire.MAGIC
    for name, value in pinned.items():
        assert int(value, 0) == getattr(wire, name), \
            f"docs/protocol.md pins {name}={value} but wire.{name} is " \
            f"{getattr(wire, name)}"
    # every cap and kind the module exports is pinned in the doc
    exported = {n for n in dir(wire)
                if n.startswith(("KIND_", "MAX_", "SIG"))
                or n == "WIRE_VERSION"}
    missing = exported - set(pinned) - {"MAGIC"}
    assert not missing, f"protocol.md §8 is missing constants: {missing}"


def test_protocol_net_constants_match_framing_module():
    """§10's transport constants AND the frame-kind table are pinned
    against repro.net.framing — the wire format of the socket fabric is a
    spec, not an implementation detail."""
    from repro.net import framing
    text = (ROOT / "docs" / "protocol.md").read_text()
    sec = _section(text, "## 10.")
    pinned = dict(re.findall(_PIN_ROW, sec))
    assert "NET_MAGIC" in pinned and "MAX_FRAME" in pinned, \
        "protocol.md §10 constants tables are missing or unparseable"
    assert bytes.fromhex(pinned.pop("NET_MAGIC")) == framing.NET_MAGIC
    for name, value in pinned.items():
        assert int(value, 0) == getattr(framing, name), \
            f"docs/protocol.md pins {name}={value} but framing.{name} is " \
            f"{getattr(framing, name)}"
    # every frame kind and transport cap the module exports is pinned
    exported = {n for n in dir(framing)
                if n.startswith(("REQ_", "RESP_"))
                or n in ("NET_VERSION", "MAX_FRAME")}
    missing = exported - set(pinned) - {"NET_MAGIC"}
    assert not missing, f"protocol.md §10 is missing constants: {missing}"
    # the retirement story stays told: kind 8 and tag 0x82 are documented
    # as retired, never reused
    assert "retired" in _section(text, "## 1.", "## 2.").lower()
    assert "0x82" in sec and "never reused" in sec


def test_protocol_worked_example_digest_matches_vector():
    text = (ROOT / "docs" / "protocol.md").read_text()
    vector = (ROOT / "tests" / "vectors" / "manifest_digest.hex") \
        .read_text().strip()
    assert vector in text, \
        "protocol.md §7's worked-example digest drifted from " \
        "tests/vectors/manifest_digest.hex"


def test_readme_quickstart_block_present_and_current():
    readme = (ROOT / "README.md").read_text()
    m = re.search(r"```python\n(.*?)```", readme, re.S)
    assert m, "README.md lost its quickstart code block"
    code = m.group(1)
    # the snippet must exercise the documented trust path end to end:
    # durable log, gossip-pinned head bound into one typed TrustAnchor,
    # byte-level verification
    for needle in ("ZKGraphSession", "TransparencyLog.open", "publish_to",
                   "verify_bytes", "GossipPeer", "gossip=", "TrustAnchor",
                   "anchor="):
        assert needle in code, f"README quickstart no longer uses {needle}"
    compile(code, "README.md#quickstart", "exec")    # at least parses


def test_readme_networked_snippet_present_and_current():
    """The README's networked-quickstart block must exercise the real
    socket fabric: a NetServer serving the signed head, a PeerClient
    fetching it, and the gossip peer verifying the Ed25519 envelope."""
    readme = (ROOT / "README.md").read_text()
    blocks = re.findall(r"```python\n(.*?)```", readme, re.S)
    net = [b for b in blocks if "NetServer" in b]
    assert net, "README.md lost its networked-quickstart code block"
    code = net[0]
    for needle in ("from repro.net import", "PeerClient", "REQ_HEAD",
                   "GossipMessage.from_bytes", "peer.offer"):
        assert needle in code, \
            f"README networked snippet no longer uses {needle}"
    compile(code, "README.md#networked", "exec")     # at least parses
    # and the full multi-process demo is pointed at
    assert "examples/serve_queries.py" in readme


def test_readme_serving_snippet_present_and_current():
    readme = (ROOT / "README.md").read_text()
    blocks = re.findall(r"```python\n(.*?)```", readme, re.S)
    serving = [b for b in blocks if "ProofService" in b]
    assert serving, "README.md lost its serving code block"
    code = serving[0]
    for needle in ("from repro.serve import ProofService", "svc.submit",
                   "f.result()", "svc.stats()"):
        assert needle in code, f"README serving snippet no longer uses {needle}"
    compile(code, "README.md#serving", "exec")       # at least parses


def test_serving_doc_matches_live_surfaces():
    """docs/serving.md must keep naming the real API and the real metrics
    schema (the schema itself is asserted against the live service in
    tests/test_serve.py::test_service_metrics_schema)."""
    text = (ROOT / "docs" / "serving.md").read_text()
    for needle in ("ProofService", "step_shape_key", "prove_batch",
                   "BatchedTranscript", "commit_lanes", "fri_prove_lanes",
                   "max_batch", "flush_interval", "max_pending",
                   "wire-byte-identical", "BENCH_serving.json"):
        assert needle in text, f"docs/serving.md no longer mentions {needle}"
    # every documented metrics key exists in the live schema constant
    from repro.serve.metrics import PHASES
    for phase in PHASES:
        assert f"`{phase}`" in text, \
            f"docs/serving.md metrics table is missing phase {phase}"
    for key in ("counters", "phase_us", "queue_wait_us", "prove_us",
                "prove_us_by_circuit", "batch_occupancy", "keygen_cache",
                "depths"):
        assert f"`{key}`" in text, \
            f"docs/serving.md metrics table is missing key {key}"
    # architecture.md links the serving section
    arch = (ROOT / "docs" / "architecture.md").read_text()
    assert "repro.serve" in arch and "serving.md" in arch


def test_query_language_doc_matches_live_surfaces():
    """docs/query_language.md pins the real grammar surface: every catalog
    label/edge/property, every comparison and aggregation, and the parser's
    hard caps must match the live modules."""
    from repro.query import ast, catalog, parser
    text = (ROOT / "docs" / "query_language.md").read_text()
    for label in catalog.LABELS:
        assert f":{label}" in text, \
            f"docs/query_language.md is missing label {label}"
    for etype in catalog.EDGES:
        assert f":{etype}" in text, \
            f"docs/query_language.md is missing edge type {etype}"
    for fn in ast.AGG_FNS:
        assert f"`{fn}`" in text or f"{fn} \"(\"" in text, \
            f"docs/query_language.md is missing aggregation {fn}"
    for cmp in ast.CMP_TOKENS:
        assert f'"{cmp}"' in text, \
            f"docs/query_language.md is missing comparison {cmp}"
    # the documented caps are the enforced caps
    for name in ("MAX_TEXT", "MAX_ITEMS", "MAX_HOPS"):
        cap = getattr(parser, name)
        assert f"`{name}` {cap}" in text, \
            f"docs/query_language.md pins a stale value for {name}"
    from repro.core.operators import filter as filter_op
    assert f"`VAL_BITS` = {filter_op.VAL_BITS}" in text
    for needle in ("compile_query", "prove_plan", "QuerySyntaxError",
                   "QueryCompileError", "tests/test_query_conformance.py",
                   "wire-byte-identical", "tests/test_query_vectors.py",
                   "shortestPath", "repro.query.ldbc_texts"):
        assert needle in text, \
            f"docs/query_language.md no longer mentions {needle}"
    # architecture.md links the section; README points at the doc
    arch = (ROOT / "docs" / "architecture.md").read_text()
    assert "repro.query" in arch and "query_language.md" in arch
    assert "query_language.md" in (ROOT / "README.md").read_text()


def test_federation_docs_match_live_surfaces():
    """The federated layer is documented where users will look: the
    envelope + chain-joint rule in protocol.md §11, the design in
    architecture.md, and a runnable multi-owner quickstart in the README."""
    proto = (ROOT / "docs" / "protocol.md").read_text()
    sec11 = _section(proto, "## 11.")
    for needle in ("FederatedBundle", "FedRequest", "FedResponse",
                   "Chain-joint rule", "0x04", "hand-off", "KIND_FEDERATED",
                   "manifest_digest", "strictly", "OwnerUnavailableError"):
        assert needle in sec11, f"protocol.md §11 no longer covers {needle}"
    # the federated tag bytes are spelled out in the layout blocks
    for tag in ("0x90", "0xA0", "0xB0", "0xC0"):
        assert tag in sec11, f"protocol.md §11 lost tag byte {tag}"

    arch = (ROOT / "docs" / "architecture.md").read_text()
    for needle in ("repro.federation", "GraphPartition", "FederatedPlanner",
                   "expansion boundaries", "verify_federated", "TrustAnchor",
                   "ChainJointError", "OwnerUnavailableError",
                   "BENCH_federation.json", "tests/test_federation.py"):
        assert needle in arch, \
            f"docs/architecture.md no longer documents {needle}"

    readme = (ROOT / "README.md").read_text()
    blocks = re.findall(r"```python\n(.*?)```", readme, re.S)
    fed = [b for b in blocks if "FederationCoordinator" in b]
    assert fed, "README.md lost its multi-owner federation code block"
    code = fed[0]
    for needle in ("from repro.federation import", "GraphPartition",
                   "OwnerSpec", "OwnerNode.from_partition", "co.prove",
                   "verify_federated", "TrustAnchor"):
        assert needle in code, \
            f"README federation snippet no longer uses {needle}"
    compile(code, "README.md#federation", "exec")    # at least parses
    assert "OwnerUnavailableError" in readme


def test_analysis_doc_matches_live_catalogue():
    """docs/analysis.md documents every check id the analyzer can emit,
    the adapter vetting contract, and the baseline workflow."""
    from repro.analysis.findings import ALL_CHECKS
    text = (ROOT / "docs" / "analysis.md").read_text()
    for check in sorted(ALL_CHECKS):
        assert f"`{check}`" in text, \
            f"docs/analysis.md is missing check id {check}"
    for needle in ("analysis_cases", "analysis_baseline.json",
                   "python -m repro.analysis", "--fail-on-findings",
                   "--selftest", "auto_multiplicities", "rotation diameter"):
        assert needle in text, f"docs/analysis.md no longer mentions {needle}"
    # architecture.md links the analysis section; README points at the doc
    arch = (ROOT / "docs" / "architecture.md").read_text()
    assert "repro.analysis" in arch and "analysis.md" in arch
    assert "analysis.md" in (ROOT / "README.md").read_text()
