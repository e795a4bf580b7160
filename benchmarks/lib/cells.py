"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's row names its file; the traffic mix is
``traffic/<mix>.json`` and names its runner, ``runners/<runner>.py``; each
per-layer metric the cell reports is ``metrics/<metric>.py``.  Nothing here
knows the name of any of them, so a cell, a configuration, a mix, a runner
or a metric is added by adding files and rows.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

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
    """A runner or a metric, imported from its file: it is found by name
    under whatever directory the benchmark was given, not on ``sys.path``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: named in BENCHMARK.json or a "
                                f"traffic file, and not there")
    name = "benchmarks_file_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(os.path.dirname(bench_dir),
                                  "BENCHMARK.json"))


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
        per_layer=[(r, load_module(os.path.join(bench_dir, "metrics",
                                                r["name"] + ".py")))
                   for r in _reported(bench["per_layer"], workload)])
