"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration: the JSON file its entry names (``configs[].file``);
* a traffic mix: ``<bench>/traffic/<traffic>.json``;
* a metric: the reader ``<bench>/metrics/<metric name>.py``, whose
  ``read(run)`` returns the value or ``None`` when the run holds nothing to
  read;
* a configuration's data set and plain reference: the modules
  ``<bench>/datasets/<dataset>.py`` and ``<bench>/references/<reference>.py``
  that its file names.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name that ``BENCHMARK.json`` or the files under ``bench/`` lack."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                   # "end_to_end" or "per_layer"


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: tuple              # end-to-end metrics, then per-layer ones
    bench_dir: Path

    def metrics_of(self, kind: str) -> list:
        return [m for m in self.metrics if m.kind == kind]


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json names no {what} {name!r}; known: "
                    f"{[e['name'] for e in entries]}")


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = _read_json(root / "BENCHMARK.json")
    cell = _by_name(spec["workloads"], name, "workload")
    conf = _by_name(spec["configs"], cell["config"], "configuration")
    config = _read_json(root / conf["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if name in m.get("workloads", [name]):
                metrics.append(Metric(m["name"], m["unit"], kind))
    return Cell(name, int(cell["chips"]), config, traffic, tuple(metrics),
                bench_dir)


def reader(cell: Cell, metric: str):
    """The ``read(run)`` function of one metric's reader file."""
    mod = load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                      f"bench_metric_{metric.replace('.', '_')}")
    return mod.read


def dataset(cell: Cell):
    return load_module(cell.bench_dir / "datasets" /
                       f"{cell.config['dataset']}.py",
                       f"bench_dataset_{cell.config['dataset']}")


def reference(cell: Cell):
    return load_module(cell.bench_dir / "references" /
                       f"{cell.config['reference']}.py",
                       f"bench_reference_{cell.config['reference']}")
