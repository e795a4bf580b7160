"""The harness is driven by data: every name in BENCHMARK.json resolves to
files, a fourth cell and a model of another family are added by adding
files, and run.py has no CPU mode.

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
from benchmarks.lib import cells, program, scopes, xplane
from benchmarks.lib.compile_meter import CompileMeter
from benchmarks.lib.rundata import RunData
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


def measure(cell, meter, trace, dump=None):
    return bench_run.measure(cell, jax.devices()[:cell.chips], meter, Spans(),
                             seed=3, seconds=0.3, trace=trace,
                             peaks=MADE_UP_PEAKS, t0=time.perf_counter(),
                             dump=dump)


def digest(top) -> dict[str, str]:
    """relative path -> sha256 of every file under ``top``."""
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


def copied_benchmark(tmp_path) -> tuple[str, dict]:
    """The benchmark's directory copied under ``tmp_path``, and its digest."""
    bench_dir = str(tmp_path / "benchmarks")
    shutil.copytree(cells.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return bench_dir, digest(bench_dir)


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
    (``setup_s`` is the contract's own name).  A runner, a shared reader or
    a reference has a common word for a name, so it is looked for as code
    would spell it: quoted, or as a module of its package."""
    names = {x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]}
    names |= {w["traffic"] for w in BENCH["workloads"]}
    names.discard("setup_s")
    resolved = [cells.resolve(w) for w in WORKLOADS]
    files = {"runners": {c.traffic["runner"] for c in resolved},
             "reference": {c.config["reference"] for c in resolved},
             "readers": {reader.spec["reader"] for c in resolved
                         for _row, reader in c.per_layer
                         if hasattr(reader, "spec")}}
    assert all(files.values()), files
    quoted = {spelt for package, stems in files.items() for stem in stems
              for spelt in (f"'{stem}'", f'"{stem}"', f"{package}.{stem}",
                            f"{package}/{stem}", f"import {stem}")}
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


def test_set_up_imports_none_of_the_traced_runs_reduction():
    """``setup_s`` counts from process start, and an untraced run reads no
    trace: resolving every cell, its runner, reference and readers, leaves
    the modules of the scope reduction unimported."""
    code = ("import sys; import benchmarks.run; "
            "from benchmarks.lib import cells, program; "
            f"found = [cells.resolve(w) for w in {WORKLOADS!r}]; "
            "[program.reference(c.config) for c in found]; "
            "late = {'scopes', 'clock', 'hlo_names'}; "
            "print(sorted(m for m in sys.modules "
            "if m.rpartition('.')[2] in late and m.startswith('benchmarks')))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"


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
    bench_dir, before = copied_benchmark(tmp_path)

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


OTHER_COUNTS = {"active_params": 1000 + 35, "projection_params": 35,
                "kv_elements": 6}


def test_a_model_of_another_family_is_added_without_editing_a_file(
        tmp_path, meter):
    """Another reference (the equations of the first configuration's under
    another name, with counts of its own: a routed model with an untied
    head and grouped K/V heads would have such), a configuration that names
    it, a cell on the decode mix, and a share that a shared reader reads
    from keys of its own, as data alone: the cell resolves and runs tiny and
    traced, its facts follow the new reference's counts, the share reads a
    recorded trace, and every file the benchmark had is byte for byte what
    it was."""
    bench_dir, before = copied_benchmark(tmp_path)

    base = next(w for w in BENCH["workloads"] if w["chips"] == 1
                and "prompt_len" in cells.resolve(w["name"]).traffic)
    config = cells.resolve(base["name"]).config
    with open(os.path.join(bench_dir, "reference",
                           config["reference"] + ".py")) as f:
        equations = f.read()
    with open(os.path.join(bench_dir, "reference", "other.py"), "w") as f:
        f.write(equations + f"\n\ndef counts(shape):\n"
                            f"    return {OTHER_COUNTS!r}\n")
    config = {**config, "name": "other-family", "reference": "other"}
    with open(os.path.join(bench_dir, "configs", "other-family.json"),
              "w") as f:
        json.dump(config, f)
    keys = ["scope/unembed@decode.step", "scope/ffn@decode.step"]
    with open(os.path.join(bench_dir, "metrics", "other_weights_share.json"),
              "w") as f:
        json.dump({"reader": "scope_share", "keys": keys}, f)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**BENCH["configs"][0], "name": "other-family",
                             "file": "benchmarks/configs/other-family.json"})
    bench["workloads"].append({"name": "other-cell", "config": "other-family",
                               "traffic": base["traffic"], "chips": 1,
                               "why": "dry addition"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append("other-cell")
    moved = next(m["name"] for m in BENCH["end_to_end"]
                 if base["name"] in m.get("workloads", []))
    bench["per_layer"].append({"name": "other_weights_share", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "decoder", "moves": moved,
                               "workloads": ["other-cell"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    cell = tiny(cells.resolve("other-cell", bench_dir))
    assert cell.config["reference"] == "other"
    # the runner is the copy's and finds the reference beside it: the
    # benchmark this test was started from has no such file
    assert cell.runner.__file__.startswith(bench_dir)
    assert not os.path.exists(os.path.join(cells.BENCH_DIR, "reference",
                                           "other.py"))
    dump = str(tmp_path / "dump")
    traced = measure(cell, meter, trace=True, dump=dump)
    assert traced["correct"] is True, traced["checks"]
    assert "other_weights_share" not in traced["metrics"]  # no device plane here
    assert traced["metrics"]["decode_step_ms"]["value"] > 0
    with open(os.path.join(dump, "other-cell.seed3.trace1.json")) as f:
        facts = json.load(f)["facts"]
    t, small = cell.traffic, cell.config
    L, D = small["num_hidden_layers"], small["hidden_size"]
    assert facts["prefill_flops"] == (
        t["batch"] * t["prompt_len"] * (2 * 1000 + 4 * L * D * t["prompt_len"])
        + t["batch"] * 2 * 35)
    param_bytes = facts["n_params"] * 4
    assert facts["decode_step_bytes"] == param_bytes + (
        L * t["batch"] * (t["prompt_len"] + t["max_new"] / 2) * 6 * 2)

    # the share, on the events the chip recorded of the base cell
    reader = dict((r["name"], rd) for r, rd in cell.per_layer)
    events = xplane.load_events(os.path.join(
        os.path.dirname(xplane.__file__), "testdata", "scoped",
        base["name"] + ".events.json.gz"))
    table = scopes.reduce_scopes(events)
    run = RunData(durations={}, facts={}, peaks=None,
                  trace=xplane.reduce_events(events), compiles_in_window=0,
                  peak_bytes=None, scopes=table, events=events)
    share = reader["other_weights_share"].read(run)
    assert share == pytest.approx(
        100 * (table[keys[0]] + table[keys[1]]) / run.trace.window_s)
    assert 0 < share < reader["decode_attention_share"].read(run) < 100

    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"reference/other.py",
                                        "configs/other-family.json",
                                        "metrics/other_weights_share.json"}
