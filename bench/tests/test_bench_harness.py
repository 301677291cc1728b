"""The harness's entry, its files found by name, and BENCHMARK.json."""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tinycell
from harness import driver, spec

ROOT, BENCH = tinycell.ROOT, tinycell.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench_cmd(root, *extra):
    spec_ = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [sys.executable, *spec_["command"][1:], "--workload",
            "snb_60k.is_1client", "--seed", "3", "--seconds", "1",
            "--trace", "0", *extra]


def run_bench(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(bench_cmd(root), cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    out = run_bench(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "correct" not in out.stdout


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_bench(tmp_path)
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_benchmark_json_names_files_that_exist():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        config = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in config, (c["name"], key)
        for mod in ("datasets", "references"):
            key = "dataset" if mod == "datasets" else "reference"
            assert (BENCH / mod / f"{config[key]}.py").is_file()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = spec.load_cell(w["name"])
        kinds = {m.kind for m in cell.metrics}
        assert kinds == {"end_to_end", "per_layer"}, w["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    assert {m["name"] for m in b["end_to_end"]} >= {"setup_s"}


def _tree_digest(path):
    h = hashlib.sha256()
    for p in sorted(path.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(path)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def test_new_files_are_found_by_name_without_editing_any(tmp_path):
    """A configuration, a traffic mix and a metric are added as new files
    and new entries; no file the benchmark already has changes."""
    root = tmp_path / "repo"
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = _tree_digest(bench)

    config = json.loads((bench / "configs" / "snb_60k.json").read_text())
    config.update(name="snb_tiny", n_knows=512, n_comments=512,
                  n_persons=64)
    (bench / "configs" / "snb_tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "is4_2clients.json").write_text(json.dumps(dict(
        clients=2, requests_per_client=3, mix=[["IS4", 1]],
        params=dict(IS4=dict(message="comment_uniform")))))
    (bench / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.served))\n")

    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append(dict(b["configs"][0], name="snb_tiny",
                             file="bench/configs/snb_tiny.json"))
    b["workloads"].append(dict(name="snb_tiny.is4_2clients",
                               config="snb_tiny", traffic="is4_2clients",
                               chips=1, why="a test cell"))
    b["per_layer"].append(dict(name="requests_seen", unit="count",
                               better="higher", source="program_counter",
                               layer="service", moves="proved_qps",
                               workloads=["snb_tiny.is4_2clients"]))
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("snb_tiny.is4_2clients", root=root,
                          bench_dir=bench)
    assert cell.config["n_knows"] == 512
    assert cell.traffic["clients"] == 2
    assert "requests_seen" in [m.name for m in cell.metrics_of("per_layer")]
    fake = driver.Run(cell, "", 1.0, [object()] * 5, 0.0, 1.0, {}, {})
    assert spec.reader(cell, "requests_seen")(fake) == 5.0
    tables = spec.dataset(cell).make(cell.config, 1)
    assert len(tables["knows"]["src"]) == 512
    # the committed cells still load from the copy, unchanged
    spec.load_cell("snb_60k.is_1client", root=root, bench_dir=bench)
    for f in ("snb_tiny.json", "is4_2clients.json", "requests_seen.py"):
        next(bench.rglob(f)).unlink()
    assert _tree_digest(bench) == before


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such.cell")
    cell = spec.load_cell("snb_60k.is_1client")
    with pytest.raises(spec.SpecError):
        spec.reader(cell, "no_such_metric")
