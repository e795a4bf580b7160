#!/usr/bin/env python3
"""Run one cell of the benchmark as the driver does, then print what the
program recorded of its host while the jobs ran.

    python3 tools/run_record.py [--record FILE] --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on the TPU: every argument but ``--record`` is
``benchmarks/run.py``'s, whose own ``main`` runs here in this process and in
its flow (the flow a call is made in moves seconds, PR 54), so stdout is
that run's and its last line the result.  After it, on stderr:

- ``run {...}``: ``ompi_tpu.core.scopes.run()``: the host's seconds inside a
  call by callable, each program object's first dispatch and its rest, a
  later call that compiled, the collector's passes, and ``jobs``;
- ``off {...}``: ``run()["jobs"]`` (every ``run.call`` to the next in time:
  a job of the benchmark, the caller's wait for the device and its read-back
  included) against each callable's median ``wall_s``: ``callables`` has the
  medians, ``off`` the jobs more than ``OFF`` (0.5%) from theirs, each with
  what the record holds of it: the host's ``call_s`` inside the call, and
  over the job the process's ``cpu_s``, involuntary ``switches``, major
  ``faults`` and the collector's ``gc_s``.

``--record FILE`` appends one JSON line with the arguments, the result line,
both lines and the held spans themselves, ``scopes.Span`` field for field
(``chiprun_out/`` brings it back from the chip).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


OFF = 0.005     # a job this far from its callable's median is listed


def off(jobs: list) -> dict:
    """``jobs`` (``scopes.run()["jobs"]``) against their callable's median
    ``wall_s``: the medians, and the jobs more than ``OFF`` from theirs."""
    walls: dict = {}
    for row in jobs:
        walls.setdefault(row["made"], []).append(row["wall_s"])
    medians = {made: statistics.median(w) for made, w in walls.items()}
    rows = [{**row, "off": row["wall_s"] / medians[row["made"]] - 1}
            for row in jobs]
    return {"callables": {str(made): {"jobs": len(walls[made]),
                                      "median_wall_s": medians[made]}
                          for made in walls},
            "off": [row for row in rows if abs(row["off"]) > OFF]}


class _Tee(io.TextIOBase):
    def __init__(self, *streams) -> None:
        self.streams = streams

    def write(self, text: str) -> int:
        for stream in self.streams:
            stream.write(text)
        return len(text)

    def flush(self) -> None:
        for stream in self.streams:
            stream.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--record", metavar="FILE")
    args, rest = ap.parse_known_args(argv)

    from benchmarks import run as bench_run     # its T0: this process's start

    said = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, said)):
        code = bench_run.main(rest)

    from ompi_tpu.core import scopes

    ran = scopes.run()
    record = {"run": ran, "off": off(ran["jobs"])}
    for name, value in record.items():
        print(f"{name} {json.dumps(value)}", file=sys.stderr)
    if args.record:
        lines = said.getvalue().strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        folder = os.path.dirname(os.path.abspath(args.record))
        os.makedirs(folder, exist_ok=True)
        with open(args.record, "a", encoding="utf-8") as f:
            f.write(json.dumps({"argv": rest, "code": code, "result": result,
                                **record, "spans": [list(span) for span in
                                                    scopes.run_records()]})
                    + "\n")
    return code


if __name__ == "__main__":
    sys.exit(main())
