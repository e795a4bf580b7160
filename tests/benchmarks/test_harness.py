"""The harness is driven by data: every name in BENCHMARK.json resolves to
files, a fourth cell is added by adding files, and run.py has no CPU mode.

Everything here runs on the CPU at the configurations' ``tiny`` sizes: it
checks resolution, control flow and the shape of the result line, never a
device metric.
"""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import cells, program
from benchmarks.lib.compile_meter import CompileMeter
from benchmarks.lib.spans import Spans

ROOT = os.path.dirname(cells.BENCH_DIR)
BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _perf_layers() -> set[str]:
    """The words of PERF.md's section on layers: a metric's ``layer`` is a
    layer's name there."""
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        text = f.read()
    section = re.search(r"^## 3\..*?(?=^## 4\.|\Z)", text, re.S | re.M)
    return set(re.findall(r"[A-Za-z0-9_][A-Za-z0-9_.-]*",
                          section.group(0) if section else text))


PERF_LAYERS = _perf_layers()

# the jobs at a size the CPU runs in a second
TINY_TRAFFIC = {"batch": 4, "seq": 32, "prompt_len": 16, "max_new": 8}
# made up, so that the readers of utilizations run; nothing here is a
# utilization of anything
MADE_UP_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


def tiny(cell: cells.Cell) -> cells.Cell:
    cell.config = program.tiny(cell.config)
    cell.traffic = {k: TINY_TRAFFIC.get(k, v) for k, v in cell.traffic.items()}
    return cell


def measure(cell, meter, trace):
    return bench_run.measure(cell, jax.devices()[:cell.chips], meter, Spans(),
                             seed=3, seconds=0.3, trace=trace,
                             peaks=MADE_UP_PEAKS, t0=time.perf_counter())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_by_name(workload):
    cell = cells.resolve(workload)
    row = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.chips == row["chips"] == cell.config["chips"]
    assert cell.config["name"] == row["config"]
    assert callable(cell.runner.build)
    assert {r["name"] for r in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for _row, reader in cell.per_layer:
        assert callable(reader.read)
    config_row = next(c for c in BENCH["configs"] if c["name"] == row["config"])
    assert config_row["reduced"] == cell.config["reduced"]
    assert config_row["source"] == cell.config["source"]
    for key in ("assumed", "departures", "mesh", "entry", "reference"):
        assert key in cell.config


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    assert LAYER.match(metric["layer"]), metric["layer"]
    assert metric["layer"] in PERF_LAYERS, metric["layer"]
    assert metric["unit"] and metric["better"] in ("higher", "lower")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    for workload in metric.get("workloads", WORKLOADS):
        assert workload in WORKLOADS
        assert workload in moved.get("workloads", WORKLOADS)


def test_names_and_shape_of_the_benchmark_file():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert len(x["why"]) <= 200, x["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert 1 <= four <= max(1, len(WORKLOADS) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_harness_names_no_cell_configuration_runner_or_metric():
    """Whatever belongs to one cell sits in its own file: run.py and lib/
    find it by the name BENCHMARK.json gives and know none themselves
    (``setup_s`` is the contract's own name)."""
    names = {x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]}
    names |= {w["traffic"] for w in BENCH["workloads"]}
    names.discard("setup_s")
    runners = {cells.resolve(w).traffic["runner"] for w in WORKLOADS}
    quoted = {q + r + q for r in runners for q in "'\""}
    sources = [os.path.join(cells.BENCH_DIR, "run.py")]
    lib = os.path.join(cells.BENCH_DIR, "lib")
    sources += [os.path.join(lib, f) for f in os.listdir(lib)
                if f.endswith(".py")]
    for path in sources:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        found = [n for n in names | quoted if n in text]
        assert not found, f"{path} names {found}"


def test_run_py_has_no_cpu_mode():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", WORKLOADS[0], "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no CPU mode" in proc.stderr
    assert "metrics" not in proc.stdout and "{" not in proc.stdout


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_tiny_on_the_cpu(workload, meter):
    """Both kinds of run print the contract's keys and the cell's metrics,
    but for those only a device gives: the CPU backend has no device planes
    in its trace and reports no ``memory_stats``."""
    cell = tiny(cells.resolve(workload))
    plain = measure(cell, meter, trace=False)
    traced = measure(cell, meter, trace=True)
    for line in (plain, traced):
        assert {"correct", "attempted", "failed", "metrics",
                "device"} <= set(line)
        assert line["correct"] is True, line["checks"]
        assert line["attempted"] > 0 and line["failed"] == 0
        assert set(line["device"]) >= {"platform", "kind", "count",
                                       "memory_peak_bytes"}
        json.dumps(line)
    assert set(plain["metrics"]) == {r["name"] for r in cell.end_to_end}
    assert all(m["value"] > 0 for m in plain["metrics"].values())
    assert "breakdown" not in plain
    host_side = {r["name"] for r, _ in cell.per_layer
                 if r["source"] != "device_trace" and r["unit"] != "GiB"}
    assert host_side and host_side == set(traced["metrics"])
    assert not set(traced["metrics"]) & set(plain["metrics"])


def test_a_fourth_cell_is_added_without_editing_a_file(tmp_path, meter):
    """A copied configuration with another depth, a copied traffic file, one
    new metric file and one more row each: the new cell resolves and runs,
    and every file the benchmark had is byte for byte what it was."""
    def digest(top):
        out = {}
        for folder, _dirs, files in os.walk(top):
            for f in files:
                if f.endswith(".pyc"):
                    continue
                path = os.path.join(folder, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, top)] = hashlib.sha256(
                        fh.read()).hexdigest()
        return out

    bench_dir = str(tmp_path / "benchmarks")
    shutil.copytree(cells.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(bench_dir)

    base = next(w for w in BENCH["workloads"] if w["chips"] == 1)
    config = cells.resolve(base["name"]).config
    config = {**config, "name": "added-config",
              "num_hidden_layers": config["num_hidden_layers"] + 1}
    with open(os.path.join(bench_dir, "configs", "added-config.json"),
              "w") as f:
        json.dump(config, f)
    shutil.copy(os.path.join(bench_dir, "traffic", base["traffic"] + ".json"),
                os.path.join(bench_dir, "traffic", "added-mix.json"))
    with open(os.path.join(bench_dir, "metrics", "added_metric.py"),
              "w") as f:
        f.write("def read(run):\n    return run.median('readback')\n")
    moved = next(m["name"] for m in BENCH["end_to_end"]
                 if base["name"] in m.get("workloads", []))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**BENCH["configs"][0], "name": "added-config",
                             "file": "benchmarks/configs/added-config.json"})
    bench["workloads"].append({"name": "added-cell", "config": "added-config",
                               "traffic": "added-mix", "chips": 1,
                               "why": "dry addition"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append("added-cell")
    bench["per_layer"].append({"name": "added_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": moved,
                               "workloads": ["added-cell"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    cell = cells.resolve("added-cell", bench_dir)
    assert cell.config["num_hidden_layers"] == config["num_hidden_layers"]
    assert "added_metric" in {r["name"] for r, _ in cell.per_layer}
    traced = measure(tiny(cell), meter, trace=True)
    assert traced["correct"] and traced["metrics"]["added_metric"]["value"] > 0
    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"configs/added-config.json",
                                        "traffic/added-mix.json",
                                        "metrics/added_metric.py"}
