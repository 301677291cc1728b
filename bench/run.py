#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its metrics.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/README.md``).  The run drives
``ProofService.submit`` on an owner ``ZKGraphSession`` with the compiled
``pallas`` backend, in this one process, on the devices JAX finds.

Earlier lines report set-up, the window's counts (XLA programs built and
keygen misses inside the window, which must be 0, and the set-up's
compile seconds) and every request that failed.  The last lines on
standard error give each number the check compares beside its limit.  The
last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.  Without a TPU, with fewer chips than
the cell asks for, or without the program next to ``bench/``, it exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import driver, spec, trace  # noqa: E402
from harness.meter import CompileMeter  # noqa: E402


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def import_program():
    """The system under test, with JAX's persistent compilation cache on
    and every program written to it, however quickly it compiled."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: the program is not next to bench/ "
                         f"({ROOT / 'src'})")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.core import backend
    cache = backend.enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    return backend


def require_chips(n: int) -> list:
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise NoChip(f"JAX finds no TPU (default backend: {platform})")
    devices = jax.devices()
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def select_pallas(backend) -> str:
    backend.require("pallas")
    os.environ[backend.ENV_VAR] = "pallas"
    return "pallas"


def memory_peak(devices):
    def read():
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None
    return read


def read_trace(trace_dir: str):
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return trace.window(jax.profiler.ProfileData.from_file(files[0]))


def metrics(run, traced: bool) -> dict:
    kind = "per_layer" if traced else "end_to_end"
    out = {}
    for m in run.cell.metrics_of(kind):
        value = spec.reader(run.cell, m.name)(run)
        if value is not None:
            out[m.name] = dict(value=value, unit=m.unit)
    return out


def result_line(run, devices, traced: bool) -> dict:
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=run.memory_peak)
    line = dict(correct=run.correct, attempted=len(run.served),
                failed=run.failed, metrics=metrics(run, traced),
                device=device)
    if traced:
        device.update(busy_s=run.trace.busy_s(), window_s=run.trace.seconds)
        line["breakdown"] = dict(device_ops=trace.top_ops(run.trace),
                                 idle_gaps=trace.idle_gaps(run.trace))
    line["checks"] = run.checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    backend = import_program()
    meter = CompileMeter()
    try:
        devices = require_chips(cell.chips)
        name = select_pallas(backend)
    except (NoChip, backend.BackendUnavailableError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log(f"cell {cell.name}: seed {args.seed}, {args.seconds} s window, "
        f"trace {args.trace}, {len(devices)} x {devices[0].device_kind}, "
        f"backend {name}")
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        run = driver.run_cell(
            cell, args.seed, args.seconds, backend=name, t_start=T_START,
            meter=meter, trace_dir=tdir if args.trace else None,
            device_kind=devices[0].device_kind,
            memory_peak=memory_peak(devices), log=log)
        if args.trace:
            run.trace = read_trace(tdir)
    c = run.counts
    log(f"set-up: {run.setup_s:.3f} s, {c['setup_programs']} XLA programs "
        f"({c['setup_cache_hits']} from the persistent cache), "
        f"{c['setup_compile_s']:.3f} s compiling or loading them")
    log(f"window: {c['window_programs']} XLA programs built, "
        f"{c['window_keygen_misses']} keygen misses; "
        f"{len(run.completed)} of {len(run.served)} queries proved in "
        f"{run.window_end - run.window_start:.3f} s; verify built "
        f"{c['verify_programs']} XLA programs")
    for s in run.served:
        phases = " ".join(f"{k} {v:.3f}" for k, v in
                          (s.bundle.steps[0].proof.timings.items()
                           if s.bundle is not None else ()))
        log(f"query {s.query} {s.params}: submitted at "
            f"{s.submitted - run.window_start:.3f} s, took "
            f"{s.done - s.submitted:.3f} s; first step's phases: {phases}")
    for k, v in run.checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result_line(run, devices, bool(args.trace))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
