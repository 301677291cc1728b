#!/usr/bin/env python3
"""Run a cell's control on the chip: the cell as ``bench/run.py`` runs it,
but with the owner proving under the configuration's ``control`` prover
parameters (fewer FRI queries than the configuration's guarantee), while
the verifier keeps the configured ones.  Every run has to come out not
correct.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] --seconds <s>

One process runs every seed.  Each run's checks go to standard output; the
last line is one JSON object, ``{"workload": ..., "runs": [{"seed", "correct",
"checks"}, ...]}``.  Exits 0 when every run came out not correct, 1 when
one came out correct, nonzero without a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402  (bench/ is this script's directory)
from harness import driver, spec  # noqa: E402
from harness.meter import CompileMeter  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    backend = bench_run.import_program()
    meter = CompileMeter()
    try:
        devices = bench_run.require_chips(cell.chips)
        name = bench_run.select_pallas(backend)
    except (bench_run.NoChip, backend.BackendUnavailableError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    runs = []
    for seed in args.seeds:
        run = driver.run_cell(cell, seed, args.seconds, backend=name,
                              t_start=time.perf_counter(), meter=meter,
                              owner_prover=cell.config["control"]["prover"],
                              device_kind=devices[0].device_kind,
                              log=bench_run.log)
        runs.append(dict(seed=seed, correct=run.correct,
                         completed=len(run.completed), checks=run.checks))
        bench_run.log(f"control seed {seed}: correct {run.correct}, "
                      f"{len(run.completed)} bundles, {run.checks}")
    print(json.dumps(dict(workload=cell.name, runs=runs)), flush=True)
    return 1 if any(r["correct"] for r in runs) else 0


if __name__ == "__main__":
    sys.exit(main())
