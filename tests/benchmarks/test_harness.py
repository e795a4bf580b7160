"""The harness is driven by data: every name in BENCHMARK.json resolves to
files, a fourth cell and a model of another family are added by adding
files, and run.py has no CPU mode.

Everything here runs on the CPU at the configurations' ``tiny`` sizes: it
checks resolution, control flow and the shape of the result line, never a
device metric.
"""

import importlib
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
from tests.benchmarks import controls_cases, decode_cells, fits_case
from tests.benchmarks.decode_cells import (LOGIT_CHECK, copied_benchmark,
                                           digest)

ROOT = os.path.dirname(cells.BENCH_DIR)
HERE = os.path.dirname(os.path.abspath(__file__))
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


# the two longest sets of cases are a file a cell: (stem, cells that have one)
SPLIT = ((fits_case.STEM, WORKLOADS),
         (controls_cases.STEM, controls_cases.DECODE))


def _file_of(stem: str, workload: str) -> str:
    return os.path.join(HERE, f"{stem}{fits_case.slug(workload)}.py")


@pytest.mark.parametrize("stem,workload", [
    pytest.param(stem, w, id=f"{stem}{w}")
    for stem, workloads in SPLIT for w in workloads])
def test_a_cell_has_a_file_of_its_own_of_each_test_that_is_split_a_cell(
        stem, workload):
    """A cell's compile at real sizes is a file of its own so that one
    command runs one cell's, and its controls so that the driver's ``--dist
    loadfile`` hands them to whichever worker is free: a PR that adds a cell
    adds its two files (its one, for a train cell), each naming the cell, or
    fails here.  The compile is on demand: every test of its file carries
    the ``slow`` mark, which the driver's ``-m 'not slow'`` leaves out."""
    path = _file_of(stem, workload)
    assert os.path.isfile(path), path
    with open(path, encoding="utf-8") as f:
        assert f'\nCELL = "{workload}"\n' in f.read(), path
    if stem == fits_case.STEM:
        module = importlib.import_module(
            f"tests.benchmarks.{stem}{fits_case.slug(workload)}")
        tests = [v for k, v in vars(module).items() if k.startswith("test")]
        assert tests, path
        for test in tests:
            assert "slow" in {m.name for m in test.pytestmark}, path


def test_the_files_that_are_split_a_cell_are_the_benchmarks_cells():
    """And no file of either stem is left behind by a cell that went."""
    for stem, workloads in SPLIT:
        found = {os.path.join(HERE, f) for f in os.listdir(HERE)
                 if f.startswith(stem) and f.endswith(".py")}
        assert found == {_file_of(stem, w) for w in workloads}


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


def test_a_row_appended_behind_the_last_row_is_taken_without_an_edit():
    """The rehearsal that a later ``tracing``, ``model_config`` or
    ``perf_opt`` PR appends a per-layer row and edits no test: in a copy of
    ``BENCHMARK.json`` with ``added_metric`` behind the file's real last
    row, PR 40's six rows are still the issue's
    (``test_startup_metrics.py``) and every row that a ``test_*_rows.py``
    brought is still the benchmark's row of its name: each is held by name
    and none by its place."""
    from tests.benchmarks import per_layer_rows, test_startup_metrics

    bench = json.loads(json.dumps(BENCH))
    base = next(w["name"] for w in BENCH["workloads"] if w["chips"] == 1)
    moved = next(m["name"] for m in BENCH["end_to_end"]
                 if base in m.get("workloads", []))
    bench["per_layer"].append({"name": "added_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": moved,
                               "workloads": [base]})
    assert bench["per_layer"][:-1] == BENCH["per_layer"]
    test_startup_metrics.rows_are_the_issues(bench)
    brought = per_layer_rows.modules()
    assert len(brought) >= 11
    names = {m["name"] for m in bench["per_layer"]}
    held = 0
    for module in brought:
        for row in module.ROWS:
            per_layer_rows.held(row, bench, getattr(module, "STAYS_OUT", {}))
            held += row["name"] in names
    assert held >= 39


def add_family(tmp_path, bench_dir: str, stem: str, counts: dict, share: str,
               keys: list[str], also: tuple[str, ...] = ()):
    """To the copied benchmark, by files and rows alone: a reference
    ``<stem>.py`` (the equations of the first decode configuration's, with
    ``counts`` of its own), a configuration ``<stem>-family`` that names it,
    the cell ``<stem>-cell`` on that configuration's decode mix, which
    reports what the base cell reports and the metrics ``also`` names, and
    the share ``share`` of the scope table's ``keys``, as data.  Returns the
    base cell's row and the new cell at tiny sizes."""
    base = next(w for w in BENCH["workloads"] if w["chips"] == 1
                and "prompt_len" in cells.resolve(w["name"]).traffic)
    config = cells.resolve(base["name"]).config
    with open(os.path.join(bench_dir, "reference",
                           config["reference"] + ".py")) as f:
        equations = f.read()
    with open(os.path.join(bench_dir, "reference", stem + ".py"), "w") as f:
        f.write(equations + f"\n\ndef counts(shape):\n"
                            f"    return {counts!r}\n")
    family, cell = stem + "-family", stem + "-cell"
    config = {**config, "name": family, "reference": stem}
    with open(os.path.join(bench_dir, "configs", family + ".json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "metrics", share + ".json"), "w") as f:
        json.dump({"reader": "scope_share", "keys": keys}, f)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**BENCH["configs"][0], "name": family,
                             "file": f"benchmarks/configs/{family}.json"})
    bench["workloads"].append({"name": cell, "config": family,
                               "traffic": base["traffic"], "chips": 1,
                               "why": "dry addition"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if base["name"] in m.get("workloads", []) or m["name"] in also:
            m["workloads"].append(cell)
    moved = next(m["name"] for m in BENCH["end_to_end"]
                 if base["name"] in m.get("workloads", []))
    bench["per_layer"].append({"name": share, "unit": "%", "better": "lower",
                               "source": "device_trace", "layer": "decoder",
                               "moves": moved, "workloads": [cell]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return base, tiny(cells.resolve(cell, bench_dir))


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
    keys = ["scope/unembed@decode.step", "scope/ffn@decode.step"]
    base, cell = add_family(tmp_path, bench_dir, "other", OTHER_COUNTS,
                            "other_weights_share", keys)
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
    # stored in float32 at tiny sizes and read as the step computes, in
    # bfloat16; this reference names no lookup table
    assert cell.config["param_dtype"] == "float32"
    assert cell.config["entry"]["options"]["compute_dtype"] == "bfloat16"
    param_bytes = facts["n_params"] * 2
    assert decode_cells.routed(cell, bench_dir) is None
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


# 1 of the layers attends, 6 query heads of 8 over a K/V of 3 + 3 elements a
# position; a sequence holds 21 elements of state beside the cache; the one
# routed layer's experts are 24 wide, which no key of the configuration says
HYBRID_COUNTS = {"active_params": 1000 + 35, "projection_params": 35,
                 "kv_elements": 6, "attention_layers": 1,
                 "attention_width": 48, "state_elements": 21,
                 "routed": {"layers": 1, "experts": 4, "top_k": 2,
                            "d_model": 64, "d_expert": 24}}
NEW_SCOPE = "conv.state"


def test_a_model_whose_layers_differ_is_added_without_editing_a_file(
        tmp_path, meter, monkeypatch):
    """A reference whose ``counts`` say that fewer layers attend than there
    are, over a narrower K/V, beside a fixed-size state, with routed layers
    of a shape of their own (the first decode configuration's equations
    under another name: the harness reads the counts and never the
    equations), a configuration that names it, a cell on the decode mix,
    and a share, data alone, of a scope that the program's vocabulary gains
    here.  The cell resolves and runs tiny and traced; its facts are the
    hand counts from the new keys; the new name is a scope to the reader
    and the share reads it; ``grouped_matmul_roofline`` reads the routed
    layers' own shape; and every file the benchmark had is byte for byte
    what it was."""
    from ompi_tpu.core import scopes as program_scopes

    bench_dir, before = copied_benchmark(tmp_path)
    routed_kernel = "grouped_matmul_roofline"
    _base, cell = add_family(
        tmp_path, bench_dir, "hybrid", HYBRID_COUNTS, "hybrid_state_share",
        [f"scope/{NEW_SCOPE}@decode.step"], also=(routed_kernel,))
    assert cell.runner.__file__.startswith(bench_dir)
    dump = str(tmp_path / "dump")
    traced = measure(cell, meter, trace=True, dump=dump)
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"]["decode_step_ms"]["value"] > 0
    # no device plane on the CPU: nothing to read, and nothing raised
    assert not {"hybrid_state_share", routed_kernel} & set(traced["metrics"])
    with open(os.path.join(dump, "hybrid-cell.seed3.trace1.json")) as f:
        facts = json.load(f)["facts"]
    assert facts["counts"] == {**HYBRID_COUNTS, "lookup_params": 0}
    t = cell.traffic
    B, T = t["batch"], t["prompt_len"]
    assert cell.config["num_hidden_layers"] > 1     # not every layer attends
    assert facts["prefill_flops"] == (
        B * T * (2 * 1000 + 4 * 1 * 48 * T) + B * 2 * 35)
    assert facts["decode_step_bytes"] == (
        facts["n_params"] * 2 + 1 * B * (T + t["max_new"] / 2) * 6 * 2
        + B * 21 * 2)
    # sorted with the routed cells by what its reference counts: its routed
    # layers sit under keys of its own, and no key of its file says so
    assert decode_cells.routed(cell, bench_dir) == HYBRID_COUNTS["routed"]
    assert not [key for key in cell.config if "expert" in key]

    # the program's vocabulary gains a name: the reader's table gains its
    # keys, and the share, which is data, reads them
    reader = dict((r["name"], rd) for r, rd in cell.per_layer)
    under = "jit(decode)/shard_map/decode.step/layers/while/body/"
    events = [xplane.Event("/device:TPU:0", xplane.OPS_LINE, name, lo,
                           hi - lo, under + scope)
              for name, lo, hi, scope in [
                  ("fusion.1", 0, 30, NEW_SCOPE + "/mul"),
                  ("fusion.2", 30, 100, "attention/exp")]]

    def run_of(events):
        return RunData(durations={}, facts=facts, peaks=MADE_UP_PEAKS,
                       trace=xplane.reduce_events(events),
                       compiles_in_window=0, peak_bytes=None,
                       scopes=scopes.reduce_scopes(events), events=events,
                       config=cell.config, traffic=cell.traffic)

    scopes.classify.cache_clear()
    assert scopes.classify(events[0].scope).scope == "layers"
    assert reader["hybrid_state_share"].read(run_of(events)) is None
    monkeypatch.setattr(program_scopes, "SCOPES",
                        program_scopes.SCOPES + (NEW_SCOPE,))
    scopes.classify.cache_clear()
    try:
        assert scopes.classify(events[0].scope).chain == (
            "decode.step", "layers", NEW_SCOPE)
        run = run_of(events)
        assert run.scopes["self/" + NEW_SCOPE] == pytest.approx(30e-9)
        assert reader["hybrid_state_share"].read(run) == pytest.approx(30.0)
        assert reader["decode_attention_share"].read(run) == pytest.approx(
            70.0)
    finally:
        scopes.classify.cache_clear()

    # the routed layers' kernel, read by the shape the reference counts:
    # one routed layer, so three calls a pass; every call 1 ms
    routed = HYBRID_COUNTS["routed"]
    calls = 3 * routed["layers"] * (2 + t["max_new"] - 1)
    kernel = ("%grouped_matmul.3 = bf16[64,24]{1,0:T(8,128)(2,1)} "
              "custom-call(s32[8]{0} %fusion.5)")
    kernels = [xplane.Event("/device:TPU:0", xplane.OPS_LINE, kernel,
                            2e6 * i, 1e6) for i in range(calls)]
    metric = reader[routed_kernel]

    def layer(tokens):      # gate and up, then down, of bfloat16
        rows = tokens * routed["top_k"]
        return (2 * metric.least_seconds(rows, 64, 24, 4, 2, MADE_UP_PEAKS)
                + metric.least_seconds(rows, 24, 64, 4, 2, MADE_UP_PEAKS))

    least = routed["layers"] * (2 * layer(t["batch"] * t["prompt_len"])
                                + (t["max_new"] - 1) * layer(t["batch"]))
    assert metric.read(run_of(kernels)) == pytest.approx(
        100 * least / (calls * 1e-3))

    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"reference/hybrid.py",
                                        "configs/hybrid-family.json",
                                        "metrics/hybrid_state_share.json"}


# ---- a decoder that hands its logits back ----------------------------------

def add_logits_cell(tmp_path, bench_dir: str, decoder: str, check) -> cells.Cell:
    """To the copied benchmark, by files and rows alone
    (``decode_cells.add_logits_cell``): the first decode configuration at tiny
    sizes and float32 behind the factory ``decoder``, held to ``check`` (left
    out where None), and a cell on a mix of 40 checked positions."""
    base = next(w["name"] for w in BENCH["workloads"] if w["chips"] == 1
                and "prompt_len" in cells.resolve(w["name"]).traffic)
    cell = decode_cells.add_logits_cell(
        tmp_path, bench_dir, base, decoder, check, tiny=True,
        traffic={"batch": 4, "prompt_len": 12, "max_new": 20,
                 "reference_sequences": 2})
    return cells.resolve(cell, bench_dir)


def over(limit: float) -> dict:
    return {**LOGIT_CHECK, "positions_over": {"limit": limit, "why": "test"}}


@pytest.mark.parametrize("decoder,check,correct", [
    pytest.param("logits_decoder", LOGIT_CHECK, True, id="sound"),
    pytest.param("logits_decoder_shifted", LOGIT_CHECK, False,
                 id="shifted-at-every-position"),
    pytest.param("logits_decoder_spiky", LOGIT_CHECK, True,
                 id="one-position-in-twenty-allowed"),
    pytest.param("logits_decoder_spiky", over(0.04), False,
                 id="one-position-in-twenty-refused"),
    pytest.param("logits_decoder_wrong_token", LOGIT_CHECK, False,
                 id="token-not-its-logits-argmax"),
])
def test_a_decoder_that_hands_its_logits_back_is_compared_on_logits(
        tmp_path, meter, decoder, check, correct):
    """A configuration whose ``entry`` names ``decoder_logits``, with limits
    of its own under ``check``, added by files and rows alone: it resolves,
    runs tiny on the CPU, and ``correct`` asks the logits: the median of the
    per-position error, the share of positions over the position limit, and
    that every checked token is the argmax of the logits handed back.  Every
    file the benchmark had is byte for byte what it was."""
    bench_dir, before = copied_benchmark(tmp_path)
    cell = add_logits_cell(tmp_path, bench_dir, decoder, check)
    assert cell.runner.__file__.startswith(bench_dir)
    line = measure(cell, meter, trace=False)
    c = line["checks"]
    assert line["correct"] is correct, c
    assert c["tokens_checked"] == 40 and line["attempted"] > 0
    assert set(line["metrics"]) == {r["name"] for r in cell.end_to_end}
    assert c["logit_err_median_limit"] == 0.01
    assert c["logit_err_position_limit"] == 0.05
    if decoder == "logits_decoder":
        assert c["logit_err_max"] < 1e-4 and c["positions_over"] == 0
        assert c["tokens_are_argmax"] and c["deficit_max"] < 1e-3
    elif decoder == "logits_decoder_shifted":
        assert c["logit_err_median"] > 0.05 and c["positions_over"] == 1
        assert c["tokens_are_argmax"]
    elif decoder == "logits_decoder_spiky":
        assert c["logit_err_median"] < 1e-3 and c["tokens_are_argmax"]
        assert c["positions_over"] == 2 / 40 and c["logit_err_max"] > 1
    else:
        assert not c["tokens_are_argmax"] and c["logit_err_median"] < 1e-3
    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"configs/logits-family.json",
                                        "traffic/logits-mix.json"}


@pytest.mark.parametrize("check", [
    None, {}, {k: v for k, v in LOGIT_CHECK.items() if k != "positions_over"},
    {**LOGIT_CHECK, "logit_err_median": {"limit": 0.01}},
    {**LOGIT_CHECK, "logit_err_median": {"limit": "0.01", "why": "a string"}},
], ids=["no-check", "empty", "one-missing", "no-reason", "limit-not-a-number"])
def test_a_decoder_that_hands_logits_back_without_its_limits_is_refused(
        tmp_path, check):
    bench_dir, _before = copied_benchmark(tmp_path)
    cell = add_logits_cell(tmp_path, bench_dir, "logits_decoder", check)
    with pytest.raises(ValueError, match="check"):
        cell.runner.build(cell.config, cell.traffic, jax.devices()[:1])


def built_as_its_kind(cell: cells.Cell) -> None:
    """A decode cell's job, built at tiny sizes, asks its decoder for what
    the configuration says it hands back and is held to that kind's limits
    alone; a train cell builds no decoder, whatever its configuration (which
    a decode cell may share) says of one."""
    if "prompt_len" not in cell.traffic:
        assert not hasattr(cell.runner.build(
            program.tiny(cell.config), cell.traffic,
            jax.devices()[:cell.chips]), "kept")
        return
    job = cell.runner.build(program.tiny(cell.config), cell.traffic,
                            jax.devices()[:cell.chips])
    if decode_cells.hands_back(cell) == "tokens":
        # built with ``max_new`` alone, returns tokens
        assert job.kept == {}
    else:
        assert job.kept == {cell.config["entry"]["decoder_logits"]:
                            cell.traffic["reference_sequences"]}
    assert set(job.held_to()) == set(decode_cells.limit_keys(cell))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_configuration_is_built_as_what_its_decoder_hands_back(workload):
    built_as_its_kind(cells.resolve(workload))


def test_a_configuration_that_names_decoder_logits_is_built_with_it(tmp_path):
    """The same assertions on a cell whose configuration names the keyword,
    gives the three limits for logits and no limit for tokens."""
    bench_dir, before = copied_benchmark(tmp_path)
    base = next(w for w in WORKLOADS
                if "prompt_len" in cells.resolve(w).traffic)
    cell = cells.resolve(
        decode_cells.add_logits_cell(tmp_path, bench_dir, base), bench_dir)
    assert decode_cells.hands_back(cell) == "logits"
    assert not set(cell.config["check"]) & set(decode_cells.TOKEN_KEYS)
    built_as_its_kind(cell)
    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before
