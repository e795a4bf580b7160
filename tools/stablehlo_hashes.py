#!/usr/bin/env python3
"""What every cell's programs lower to, and optionally compile to, for a
described v5e: the check of a PR that says it changes no program.

    python3 tools/stablehlo_hashes.py [--root DIR] [--cells a,b] [--tiny] \\
        [--compiled] > change.jsonl
    python3 tools/stablehlo_hashes.py --diff parent.jsonl change.jsonl

Here on the CPU box, with ``JAX_PLATFORMS=cpu``: the v5e's own compiler is
installed as libtpu and compiles for a 2x2 topology that is described and not
attached (a cell takes as many of its devices as it has chips).  Nothing runs
and nothing printed is a time.  One JSON line a ``job.programs()`` entry of
every cell of ``BENCHMARK.json`` (26 of fourteen cells at PR 75), at the
cell's real sizes unless ``--tiny``:

- ``stablehlo_sha256``: of ``fn.lower(*args).as_text()`` with every pallas
  call's ``backend_config`` blanked (a serialized Mosaic module, which holds
  the kernel's source lines: they move with every edit above a kernel);
- with ``--compiled`` (minutes a cell: 1650 s for the fourteen at PR 73),
  ``memory``: the compiled module's ``memory_analysis()`` byte counts, and
  ``opcodes``: its optimised HLO's count of instructions by opcode.

``--root DIR`` takes ``ompi_tpu`` and ``benchmarks`` from another checkout
(the parent's: ``git archive`` or ``git clone`` it under ``/root/scratch``),
so one copy of this script reads both sides; ``--diff`` prints every field
of every program that differs between two such outputs and exits 1 if any
does.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the jobs at a size that lowers in a second (tests/benchmarks/test_harness.py)
TINY_TRAFFIC = {"batch": 4, "seq": 32, "prompt_len": 16, "max_new": 8}
_BACKEND_CONFIG = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')
# an instruction of optimised HLO: "%name = <type> opcode(", the type one
# array's or a tuple's in parentheses
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.-]+ = (?:\(.*?\)|\S+) ([a-z][\w-]*)\(", re.M)
MEMORY = ("argument_size_in_bytes", "output_size_in_bytes",
          "alias_size_in_bytes", "temp_size_in_bytes")


def blanked(text: str) -> str:
    """StableHLO text with every pallas call's serialized kernel blanked."""
    return "\n".join(
        _BACKEND_CONFIG.sub('backend_config = ""', line)
        if "tpu_custom_call" in line else line
        for line in text.split("\n"))


def opcodes(hlo: str) -> dict:
    return dict(sorted(collections.Counter(
        _INSTRUCTION.findall(hlo)).items()))


def described_chips():
    from jax.experimental import topologies

    return list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)


def rows(workloads, chips, tiny: bool = False, compiled: bool = False):
    """One row a program of every cell of ``workloads``, built on as many of
    ``chips`` as the cell has.  A compile for a described chip is written to
    JAX's persistent cache and cannot be read back, so a caller that compiles
    keeps that cache off (``main`` does)."""
    from benchmarks.lib import cells, program

    for workload in workloads:
        cell = cells.resolve(workload)
        config, traffic = cell.config, cell.traffic
        if tiny:
            config = program.tiny(config)
            traffic = {k: TINY_TRAFFIC.get(k, v) for k, v in traffic.items()}
        job = cell.runner.build(config, traffic, chips[:cell.chips])
        for name, (fn, args) in job.programs().items():
            lowered = fn.lower(*args)
            row = {"cell": workload, "program": name,
                   "stablehlo_sha256": hashlib.sha256(
                       blanked(lowered.as_text()).encode()).hexdigest()}
            if compiled:
                exe = lowered.compile()
                memory = exe.memory_analysis()
                row["memory"] = {k: getattr(memory, k) for k in MEMORY}
                row["opcodes"] = opcodes(exe.as_text())
            yield row


def diff(before: str, after: str) -> list:
    """Every (cell, program, field) at which two outputs differ; a program
    that only one has differs in ``present``."""
    def read(path):
        with open(path) as f:
            return {(r["cell"], r["program"]): r
                    for r in map(json.loads, filter(str.strip, f))}

    a, b = read(before), read(after)
    out = []
    for key in sorted(set(a) | set(b)):
        if key not in a or key not in b:
            out.append((*key, "present", key in a, key in b))
            continue
        for field in sorted(set(a[key]) | set(b[key])):
            x, y = a[key].get(field), b[key].get(field)
            if isinstance(x, dict) and isinstance(y, dict):
                out += [(*key, f"{field}.{k}", x.get(k), y.get(k))
                        for k in sorted(set(x) | set(y))
                        if x.get(k) != y.get(k)]
            elif x != y:
                out.append((*key, field, x, y))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=HERE, metavar="DIR")
    ap.add_argument("--cells", metavar="A,B", help="default: every cell")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--compiled", action="store_true")
    ap.add_argument("--diff", nargs=2, metavar=("BEFORE", "AFTER"))
    args = ap.parse_args(argv)

    if args.diff:
        differing = diff(*args.diff)
        for cell, name, field, x, y in differing:
            print(f"{cell} {name} {field}: {x} -> {y}")
        print(f"{len(differing)} differences")
        return 1 if differing else 0

    sys.path.insert(0, os.path.abspath(args.root))
    # a trainer learns its optimizer state's tree on as many CPU devices as
    # the cell has chips (``runners/train.py``)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import jax

    from benchmarks.lib import cells

    jax.config.update("jax_enable_compilation_cache", False)
    names = (args.cells.split(",") if args.cells else
             [w["name"] for w in cells.load_benchmark()["workloads"]])
    for row in rows(names, described_chips(), args.tiny, args.compiled):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
