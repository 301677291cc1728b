"""A benchmark cell cut to a size the CPU runs in seconds, for the tests.

Only the sizes change: the cell's traffic, service settings and checks are
the committed ones."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import driver, spec  # noqa: E402

TINY = dict(n_knows=256, n_comments=256, n_persons=64)
TINY_PROVER = dict(blowup=4, n_queries=4, fri_final_size=16)


def cell(config: str, traffic: str, requests_per_client: int = 2):
    """The committed configuration and traffic files, at the tiny size."""
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    c.update(TINY, prover=TINY_PROVER,
             control=dict(prover=dict(TINY_PROVER, n_queries=2)))
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    t["requests_per_client"] = requests_per_client
    return spec.Cell(f"{config}.{traffic}", 1, c, t, (), BENCH)


def run(c, seed: int, seconds: float, meter, **kw):
    """A whole run on the CPU: the harness minus its look for a chip."""
    return driver.run_cell(c, seed, seconds, backend="ref",
                           t_start=time.perf_counter(), meter=meter,
                           log=lambda msg: None, **kw)
