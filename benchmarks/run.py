#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, in this process, on the TPU.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Set-up (imports, parameters on the device from
``--seed``, compile or cache read, warm-up, the correctness check against
the plain reference) runs from process start to the first timed sample and
is reported as ``setup_s``, less two things that are not the program's.  The
start-up of the TPU runtime inside ``jax.devices()``: that took 7 to 12 s of
a one-chip run's 17 to 21 s and drifted by 2 s between sets of runs of the
same code (PERF.md), which no change to this repo moves and which would bury
one that adds a second to the rest.  And the benchmark's own check against
the plain reference (the runners' spans ``setup.reference``): 6.2 s of the
four-chip cell's 15.0, the checker's cost and not the program's, which
refused a change for how its leaves reach the checker.  Both are reported
beside it, under ``setup`` (``backend_s``, ``reference_s``).  Then samples
of the cell's job are taken for ``--seconds`` seconds.  With ``--trace 1`` a
few more samples run under ``jax.profiler`` and the line carries the cell's
per-layer metrics instead of its end-to-end ones; ``breakdown`` then has the
longest device operations and idle gaps, the scope table of ``lib/scopes.py``
(``device_scopes``: device seconds by pass, ``jax.named_scope`` and collective
site), the device clock's lead with the gaps named after it
(``lib/clock.py``), and ``tracing``: how much of the device's program runs
its operations cover, and how often the window was traced again because the
profiler had lost events.  ``--dump DIR`` keeps the traced events, scopes and
all; ``python3 -m benchmarks.lib.scopes <file>`` reads them again.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced); the
further keys ``setup``, ``spans`` and, last, ``checks`` are for people:
``checks`` has every number ``correct`` compared beside its limit, and is
also the last lines of stderr.  There is
no CPU mode: without a TPU, with fewer chips than the cell asks for, or on a
device without published peaks, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()    # process start, as near as python can read it

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import cells, xplane  # noqa: E402
from benchmarks.lib.rundata import RunData  # noqa: E402
from benchmarks.lib.spans import Spans  # noqa: E402


BACKEND_SPAN = "setup.backend"     # around jax.devices(), left out of setup_s
REFERENCE_SPAN = "setup.reference"  # the runners' check, left out as well


def trace_samples(job, n: int) -> list[xplane.Event]:
    """``n`` more samples of the job under the profiler; every event of that
    trace.  The trace goes to a temporary directory (under ``TMPDIR``) that
    does not outlive it."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # host spans only, no python frames
    out = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with jax.profiler.trace(out, profiler_options=options):
            for _ in range(n):
                job.sample()
        files = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {out}")
        return xplane.read_events(files[0])
    finally:
        shutil.rmtree(out, ignore_errors=True)


def whole_trace(job, n: int, attempts: int = 3) -> tuple[list, dict]:
    """``trace_samples`` again, up to ``attempts`` times in all, while the
    device's operations leave a hole in its program runs
    (``xplane.program_coverage``): the profiler lost events, and every share
    of such a trace would read wrong.  The events of the last trace taken,
    and what was seen: ``{"covered": [a share a trace], "retraced": n}``."""
    covered = []
    for left in reversed(range(attempts)):
        traced = trace_samples(job, n)
        covered.append(xplane.program_coverage(traced))
        if covered[-1] is None or covered[-1] >= xplane.WHOLE:
            break
        print(f"the trace covers {covered[-1]:.3f} of the device's program "
              f"runs: events were lost"
              + ("; tracing again" if left else ""), file=sys.stderr)
    return traced, {"covered": covered, "retraced": len(covered) - 1}


def peak_bytes(device) -> int | None:
    """The most bytes the device has held, or None where the backend keeps
    no count (the CPU).  On the v5e's runtime a program's temporaries are
    reserved, not "in use": with the one-chip train cell resident,
    ``peak_bytes_in_use`` read 4.94 GB (parameters and optimizer state) and
    ``peak_bytes_reserved`` 11.04 GB, which is the compiler's 15.90 GB for
    the step less its 4.86 GB of arguments.  The two are held together
    while a step runs, so the peak is their sum."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def measure(cell: cells.Cell, devices, meter, spans: Spans, seed: int,
            seconds: float, trace: bool, peaks: dict | None, t0: float,
            dump: str | None = None) -> dict:
    """One run of ``cell`` on ``devices``: the result line as a dict.
    ``t0`` is the process's start on ``time.perf_counter`` and ``spans``
    holds what was recorded since."""
    import jax

    job = cell.runner.build(cell.config, cell.traffic, devices)
    try:
        job.setup(seed, spans)
        setup = {"compile_s": meter.seconds, "programs": meter.programs,
                 "cache_hits": meter.hits, "cache_misses": meter.misses}
        attempted = failed = 0
        start = time.perf_counter()
        setup["phases"] = {name: sum(v) for name, v in
                           spans.durations(t0, start).items()}
        setup["backend_s"] = setup["phases"].get(BACKEND_SPAN, 0.0)
        setup["reference_s"] = setup["phases"].get(REFERENCE_SPAN, 0.0)
        setup["seconds"] = (start - t0 - setup["backend_s"]
                            - setup["reference_s"])
        while time.perf_counter() - start < seconds:
            before = meter.programs
            attempted += 1
            try:
                job.sample()
            except Exception:   # the job's state is gone with a donated step
                traceback.print_exc()
                failed += 1
                break
            if meter.programs > before:
                failed += 1
        end = time.perf_counter()
        compiles = meter.programs - setup["programs"]
        traced, tracing = (whole_trace(job, cell.traffic["trace_samples"])
                           if trace else ([], None))
        outcome = job.finish()
        facts = job.facts()
    finally:
        job.close()

    durations = spans.durations(start, end)
    fullest = max((peak_bytes(d) or 0 for d in devices)) or None
    events, summary, table, by_clock = [], None, None, {}
    if trace:
        # the reduction's modules are a traced run's: set-up, which counts
        # from process start, imports none of them
        from benchmarks.lib import clock, scopes

        events = (xplane.device_and_span_events(traced)
                  + clock.program_annotations(traced))
        summary = xplane.reduce_events(events)
        table = scopes.reduce_scopes(events)
        by_clock = clock.breakdown(events)
    run = RunData(durations=durations, facts=facts, peaks=peaks,
                  trace=summary, compiles_in_window=compiles,
                  peak_bytes=fullest, scopes=table, events=events,
                  config=cell.config, traffic=cell.traffic)

    if trace:
        rows = [(row, reader.read(run)) for row, reader in cell.per_layer]
    else:
        values = {"setup_s": setup["seconds"], **job.end_to_end(durations)}
        rows = [(row, values.get(row["name"])) for row in cell.end_to_end]
    d0 = devices[0]
    result = {
        "correct": bool(outcome["correct"]),
        "attempted": attempted,
        "failed": min(attempted, failed + outcome["failed"]),
        "metrics": {row["name"]: {"value": value, "unit": row["unit"]}
                    for row, value in rows if value is not None},
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": fullest},
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps,
                               "device_scopes": table, **by_clock,
                               "tracing": tracing}
    # ``checks`` comes last: every number ``correct`` compared, beside its
    # limit, where the end of the line is what a record keeps
    result.update(
        setup=setup,
        spans={name: {"n": len(v), "median_s": statistics.median(v)}
               for name, v in sorted(durations.items())},
        checks=outcome["checks"])
    if dump:
        os.makedirs(dump, exist_ok=True)
        stem = os.path.join(dump, f"{cell.name}.seed{seed}.trace{int(trace)}")
        with open(stem + ".json", "w", encoding="utf-8") as f:
            json.dump({"result": result, "durations": durations,
                       "facts": facts,
                       "memory_stats": [d.memory_stats() for d in devices],
                       "trace_lines": xplane.describe(traced)}, f)
        if events:
            xplane.save_events(events, stem + ".events.json.gz")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", metavar="DIR", help="also write the raw span "
                    "durations, and the traced events, under DIR")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)

    import jax

    from benchmarks.lib.compile_meter import CompileMeter
    from benchmarks.lib.peaks import device_peaks
    from ompi_tpu.core import enable_compile_cache

    spans = Spans()
    with spans.span(BACKEND_SPAN):
        devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py measures a TPU and JAX found "
              f"{devices[0].platform!r} ({devices[0].device_kind}); there is "
              f"no CPU mode", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips and JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = device_peaks(devices[0].device_kind)
    # <checkout>/.jax_cache, or where JAX_COMPILATION_CACHE_DIR says: a
    # fixed path, so every run of a cell after its first compiles nothing
    enable_compile_cache()
    result = measure(cell, devices[:cell.chips], CompileMeter(), spans,
                     args.seed, args.seconds, bool(args.trace), peaks, T0,
                     args.dump)
    for name, value in result["checks"].items():
        print(f"check {name} = {value}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
