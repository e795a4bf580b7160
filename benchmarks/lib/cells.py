"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's row names its file, which names its plain
reference, ``reference/<name>.py`` (``lib/program.reference``); the traffic
mix is ``traffic/<mix>.json`` and names its runner, ``runners/<runner>.py``;
each per-layer metric the cell reports is ``metrics/<metric>.py``, a reader
of its own, or ``metrics/<metric>.json``, which names a reader many metrics
share, ``readers/<reader>.py``, and gives it its keys.  (The keys sit in a
file beside ``BENCHMARK.json`` and not in the metric's row because the
contract of that file refuses a row with any key it does not list.)
Nothing here knows the name of any of them, so a cell, a configuration, a
reference, a mix, a runner or a metric is added by adding files and rows,
and a metric that a shared reader can read by adding data alone.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import types
import zlib

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration's file
    traffic: dict               # the traffic mix's file
    runner: types.ModuleType
    end_to_end: list[dict]      # rows of BENCHMARK.json this cell reports
    per_layer: list[tuple[dict, types.ModuleType]]    # row, its reader


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str) -> types.ModuleType:
    """A runner, a reference or a reader, imported from its file: it is
    found by name under whatever directory the benchmark was given, not on
    ``sys.path``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: named in BENCHMARK.json, a "
                                f"configuration, a metric or a traffic "
                                f"file, and not there")
    # one module a file: a name of its own in ``sys.modules``, where
    # ``dataclasses`` looks a class's module up while the file still runs
    path = os.path.abspath(path)
    stem = os.path.splitext(os.path.basename(path))[0]
    name = f"benchmarks_file_{stem}_{zlib.crc32(path.encode()):08x}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def load_benchmark(bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(os.path.dirname(bench_dir),
                                  "BENCHMARK.json"))


def load_reader(bench_dir: str, metric: str):
    """Something with ``read(run)`` for one per-layer metric: the module
    ``metrics/<metric>.py``, or the shared reader that
    ``metrics/<metric>.json`` names, bound to that file's content as
    ``read(run, spec)``."""
    stem = os.path.join(bench_dir, "metrics", metric)
    if not os.path.isfile(stem + ".json"):
        return load_module(stem + ".py")
    spec = {**load_json(stem + ".json"), "name": metric}
    shared = load_module(os.path.join(bench_dir, "readers",
                                      spec["reader"] + ".py"))
    return types.SimpleNamespace(
        spec=spec, read=functools.partial(shared.read, spec=spec))


def _reported(rows: list[dict], workload: str) -> list[dict]:
    """A metric with no ``workloads`` key exists in every cell."""
    return [r for r in rows if workload in r.get("workloads", [workload])]


def resolve(workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_benchmark(bench_dir)
    root = os.path.dirname(bench_dir)
    rows = {w["name"]: w for w in bench["workloads"]}
    if workload not in rows:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {sorted(rows)})")
    row = rows[workload]
    config_row = next(c for c in bench["configs"]
                      if c["name"] == row["config"])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     row["traffic"] + ".json"))
    return Cell(
        name=workload, chips=row["chips"],
        config=load_json(os.path.join(root, config_row["file"])),
        traffic=traffic,
        runner=load_module(os.path.join(bench_dir, "runners",
                                        traffic["runner"] + ".py")),
        end_to_end=_reported(bench["end_to_end"], workload),
        per_layer=[(r, load_reader(bench_dir, r["name"]))
                   for r in _reported(bench["per_layer"], workload)])
