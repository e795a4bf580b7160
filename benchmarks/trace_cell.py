#!/usr/bin/env python3
"""Where a cell's device time goes, by the names inside its programs.

    python3 benchmarks/trace_cell.py --workload <name> --seed <n> [--dump DIR]

from the root of a checkout, on the TPU.  Builds the cell's job as
``run.py`` does (same configuration, traffic, runner, parameters from
``--seed``, warm-up), takes the traffic file's ``trace_samples`` samples of
it under ``jax.profiler`` and prints, as the last line of stdout, one JSON
object: the scope table of ``lib/scopes.py`` (``device_scopes``), the shares
derived from it (``shares``: forward, backward, recomputation, optimizer,
attention, FFN, loss, each collective site, the decode cache's movement,
...), the device clock's lead and the idle gaps named after it
(``lib/clock.py``), and the input stream's own counters.

**Temporary.**  This is beside ``run.py`` and not in it because a PR that is
not a benchmark PR may add benchmark files and edit none; ``run.py --trace
1`` keeps what it printed, reads no scope, and its ``--dump`` keeps events
without them.  Until a benchmark PR makes the four edits ``PERF.md`` lists
(open questions), this file repeats ``run.trace_samples`` (it needs the
``.xplane.pb`` itself, for the programs embedded in it) and reads the train
job's ``stream`` from outside (``facts()`` is the runner's).  That PR puts
the shares into ``run.py``'s traced line as metrics and deletes this file,
``SHARES``, ``hlo_names.ScopedEvent`` and ``hlo_names.load_events`` with it.
``--dump`` here keeps the events with their scopes; ``python3 -m
benchmarks.lib.scopes <file>`` reads them again, or any ``.xplane.pb``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import cells, clock, hlo_names, scopes, xplane  # noqa: E402
from benchmarks.lib.spans import Spans  # noqa: E402


# What the records want to steer by: kind of job (the traffic file's
# ``runner``) -> share -> the keys of the scope table it sums (``fnmatch``
# patterns), in percent of the traced window.  They are not metrics of
# ``BENCHMARK.json`` yet; the names are the ones they should take.
SHARES = {
    "train": {
        "train_fwd_share": ("phase/fwd",),
        "train_bwd_share": ("phase/bwd", "phase/recompute"),
        "train_recompute_share": ("phase/recompute",),
        "train_optimizer_share": ("scope/optimizer",),
        "train_attention_share": ("scope/attention@layers",),
        "train_ffn_share": ("scope/ffn@layers",),
        "train_loss_share": ("scope/loss",),
        "train_unscoped_share": ("unscoped",),
        "coll_tp_share": ("coll/*.tp",),
        "coll_grad_sync_share": ("coll/grad_sync",),
    },
    "decode": {
        # writing the new position, and what lies under the generation loop
        # or its layer scan and under no child scope: the copies of the
        # loop's carry and the per-layer slicing and stacking of the
        # stacked cache
        "decode_cache_move_share": ("scope/kv_cache@decode.step",
                                    "self/decode.step",
                                    "self/layers@decode.step"),
        "decode_attention_share": ("scope/attention@decode.step",),
        "decode_unscoped_share": ("unscoped",),
    },
}
# Milliseconds of ``scope/prefill`` a run, of the program ``ttft_ms`` times:
# the decode job's ``first`` span encloses that program and no other (the
# generation program has a prefill of its own, for a longer cache).
PREFILL_MS = {"decode": ("prefill_device_ms", "first", ("scope/prefill",))}

STALE = ("{name}: no device time under {keys}. First suspect: a stale "
         "executable from the compilation cache, whose key leaves scope "
         "names out (try an empty JAX_COMPILATION_CACHE_DIR); then a scope "
         "that moved.")


def shares(table: dict[str, float], window_s: float, kind: str,
           file=sys.stderr) -> dict[str, float]:
    """The shares of this kind of job.  One whose keys the table lacks is
    left out and named on ``file``, but a collective's where the trace has
    no collective at all (one chip)."""
    out = {}
    collectives = any(key.startswith("coll/") for key in table)
    for name, keys in SHARES.get(kind, {}).items():
        took = scopes.seconds(table, keys)
        if took is not None:
            out[name] = 100.0 * took / window_s
        elif collectives or not keys[0].startswith("coll/"):
            print(STALE.format(name=name, keys=", ".join(keys)), file=file)
    return out


def prefill_ms(events, kind: str, file=sys.stderr) -> dict[str, float]:
    """``PREFILL_MS`` of this kind of job, from the program runs under its
    host span alone."""
    if kind not in PREFILL_MS:
        return {}
    name, span, keys = PREFILL_MS[kind]
    table = scopes.reduce_scopes(events, span=span)
    took = scopes.seconds(table, keys)
    if took is None or not table["executions"]:
        print(STALE.format(name=name, keys=f"{', '.join(keys)} in the runs "
                           f"under the host span {span!r}"), file=file)
        return {}
    return {name: 1e3 * took / table["executions"]}


def traced_events(job, n: int) -> list[hlo_names.ScopedEvent]:
    """``n`` samples of the job under the profiler: every event of that
    trace, the device's operations with their op_names."""
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
        return hlo_names.read_events(files[0])
    finally:
        shutil.rmtree(out, ignore_errors=True)


def trace_cell(cell: cells.Cell, devices, seed: int,
               dump: str | None = None) -> dict:
    """One traced run of ``cell`` on ``devices``: the result line as a dict.
    On a backend whose trace has no device plane (the CPU) the tables are
    ``None`` and the counters are still there."""
    job = cell.runner.build(cell.config, cell.traffic, devices)
    try:
        job.setup(seed, Spans())
        stream = getattr(getattr(job, "stream", None), "stats", None)
        before = stream() if stream else {}
        traced = traced_events(job, cell.traffic["trace_samples"])
        counted = ({key: value - before[key] for key, value in stream().items()}
                   if stream else None)
        outcome = job.finish()
    finally:
        job.close()

    events = (xplane.device_and_span_events(traced)
              + clock.program_annotations(traced))
    summary = xplane.reduce_events(events)
    table = scopes.reduce_scopes(events)
    d0 = devices[0]
    result = {
        "workload": cell.name, "correct": bool(outcome["correct"]),
        "samples": cell.traffic["trace_samples"],
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)},
        "shares": None, "device_scopes": table, "stream": counted,
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        kind = cell.traffic["runner"]
        result["shares"] = {**shares(table, summary.window_s, kind),
                            **prefill_ms(events, kind)}
        result.update(idle_gaps=summary.idle_gaps, **clock.breakdown(events))
    if counted and counted.get("batches"):
        # takes that found the prefetch queue empty: the host set the pace
        result["data_starved_share"] = (100.0 * counted["starved"]
                                        / counted["batches"])
    if dump and events:
        os.makedirs(dump, exist_ok=True)
        xplane.save_events(events, os.path.join(
            dump, f"{cell.name}.seed{seed}.scoped.events.json.gz"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dump", metavar="DIR",
                    help="also write the traced events, with scopes, under DIR")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)

    import jax

    from ompi_tpu.core import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{cell.name} is traced on {cell.chips} TPU chip(s) and JAX "
              f"found {len(devices)} x {devices[0].platform!r}; there is no "
              f"CPU mode", file=sys.stderr)
        return 2
    enable_compile_cache()
    print(json.dumps(trace_cell(cell, devices[:cell.chips], args.seed,
                                args.dump)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
