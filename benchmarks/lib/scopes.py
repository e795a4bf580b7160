"""From the ``op_name`` of a device operation to the layer it belongs to.

The program wraps every boundary the records talk about in a
``jax.named_scope`` from one vocabulary, ``ompi_tpu/core/scopes.py``, and
JAX's own name stack tells the passes apart.  This module keeps no copy of
that vocabulary: ``classify`` looks a name up in the program's own tuple
when it is called, so a scope added there is a scope here, a key of the
table and a line of ``breakdown.device_scopes``, with no edit under
``benchmarks/``.  An ``op_name`` is a path,

    jit(train_step)/jvp()/shard_map/layers/while/body/closed_call/attention/dot_general
    jit(train_step)/transpose(jvp())/shard_map/layers/while/body/closed_call/
        checkpoint/rematted_computation/attn_proj/coll.allreduce.tp/psum
    jit(train_step)/transpose(jvp(loss))/while/body/...

in which a scope is an element of its own, or sits inside the transform
that was entered just before it (``jvp(loss)``, ``transpose(jvp(loss))``).
``classify`` reads one path; ``reduce_scopes`` adds up a trace's device time
by what ``classify`` says.  Time is the union of intervals on each device,
clipped to the window ``xplane.reduce_events`` uses (``xplane.split`` and
``xplane.window_of`` are shared), and the mean over the devices: the
arithmetic of ``collective_s``.

The keys of the table (seconds, but for ``executions``):

``phase/fwd``, ``phase/bwd``, ``phase/recompute``
    operations under ``jvp``; under ``transpose``; under
    ``rematted_computation`` (which lies under ``transpose`` and is counted
    here only).
``scope/<name>``
    operations with ``<name>`` anywhere in their chain of scopes.
``self/<name>``
    operations whose innermost scope is ``<name>``: the scope's time that
    no child scope covers.
``scope/<name>@<root>``, ``self/<name>@<root>``
    the same, of the operations whose outermost scope is ``<root>``
    (``layers`` lies under ``prefill`` and under ``decode.step``).
``coll/<method>.<axes>``
    collective operations (an asynchronous pair counts once) by the
    outermost ``coll.*`` scope around them: the call that asked for them.
``coll/grad_sync``
    collectives in the backward pass under no scope at all: the
    all-reduces ``shard_map``'s transpose emits for replicated parameters.
``coll/other``
    any other collective, such as one the SPMD partitioner inserted.
``unscoped``
    operations in no phase, no scope and no ``coll.*`` site: what the
    compiler made and left no metadata on, or a program without scopes.
``executions``
    a count, not seconds: runs of a program that lie mostly in the window
    (events of the ``XLA Modules`` line), mean over the devices.

A trace may hold several programs whose scopes have the same names (the
decode job's two, each with a ``prefill``).  ``reduce_scopes(events,
span=<host span of the benchmark>)`` counts the program runs that lie mostly
under a host span of that name, and their operations, alone.

    python3 -m benchmarks.lib.scopes <trace.xplane.pb | events.json.gz>

prints the table of any trace of any program built on ``ompi_tpu``.
"""

from __future__ import annotations

import fnmatch
import functools
import re
import sys
from typing import Iterable, NamedTuple

from benchmarks.lib import hlo_names, xplane
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event, Interval

# the one vocabulary, ``SCOPES`` and ``COLL``: the program's own, read
# when a name is classified (``classify`` remembers what it has read)
from ompi_tpu.core import scopes as vocabulary

_TRANSFORM = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
# a function's name, not a scope, is what these wrap
_CALLS = ("jit", "pjit", "xla_call", "custom_jvp_call", "custom_vjp_call")


class Where(NamedTuple):
    phase: str | None           # "fwd", "bwd", "recompute"
    chain: tuple[str, ...]      # vocabulary scopes, outermost first
    coll: str | None            # "<method>.<axes>" of the outermost coll.*

    @property
    def scope(self) -> str | None:
        return self.chain[-1] if self.chain else None


@functools.lru_cache(maxsize=None)
def classify(op_name: str) -> Where:
    transforms: set[str] = set()
    chain: list[str] = []
    coll = None
    recompute = False
    coll_prefix = vocabulary.COLL + "."
    for element in op_name.split("/"):
        call = False
        while (m := _TRANSFORM.match(element)):
            transforms.add(m.group(1))
            call = call or m.group(1) in _CALLS
            element = m.group(2)
        if call:
            continue
        if element == "rematted_computation":
            recompute = True
        elif element in vocabulary.SCOPES:
            chain.append(element)
        elif element.startswith(coll_prefix) and coll is None:
            coll = element[len(coll_prefix):]
    phase = ("recompute" if recompute else "bwd" if "transpose" in transforms
             else "fwd" if "jvp" in transforms else None)
    return Where(phase, tuple(chain), coll)


@functools.lru_cache(maxsize=None)
def keys_of(where: Where) -> tuple[str, ...]:
    """The time keys an operation counts under, a collective too."""
    out = [f"phase/{where.phase}"] if where.phase else []
    for name in where.chain:
        out.append(f"scope/{name}")
        if name != where.chain[0]:
            out.append(f"scope/{name}@{where.chain[0]}")
    if where.chain:
        out.append(f"self/{where.chain[-1]}")
        if len(where.chain) > 1:
            out.append(f"self/{where.chain[-1]}@{where.chain[0]}")
    if not out and where.coll is None:
        out.append("unscoped")
    return tuple(out)


def coll_site(where: Where) -> str:
    if where.coll:
        return where.coll
    if where.phase == "bwd" and not where.chain:
        return "grad_sync"
    return "other"


def _mostly_in(run: Event, intervals: list[Interval]) -> bool:
    """More than half of the run lies in the (disjoint) intervals: the
    clocks differ by a millisecond or two (clock.py), so a run may begin
    before the host span that started it."""
    extent = (run.start_ns, run.start_ns + run.duration_ns)
    return 2 * sum(xplane.length(xplane.clip([extent], i))
                   for i in intervals) > run.duration_ns


def reduce_scopes(events: Iterable[Event], window: Interval | None = None,
                  span: str | None = None) -> dict[str, float] | None:
    """``None`` where no operation ran on a device plane.  With ``span``,
    of the program runs mostly under a host span of that name alone."""
    per_device, host, runs = xplane.split(events)
    if not per_device:
        return None
    if window is None:
        window = xplane.window_of(per_device, host)
    if span is not None:
        under = [(h.start_ns, h.start_ns + h.duration_ns) for h in host
                 if h.name == TRACE_PREFIX + span]
        runs = {plane: [r for r in plane_runs if _mostly_in(r, under)]
                for plane, plane_runs in runs.items()}
        kept = {plane: [(r.start_ns, r.start_ns + r.duration_ns, r.name)
                        for r in plane_runs]
                for plane, plane_runs in runs.items()}
        per_device = {plane: [e for e in ops if hlo_names.run_at(
                          kept.get(plane, []), e.start_ns)]
                      for plane, ops in per_device.items()}
    # always there, so that "no time" and "no such scope" read differently
    total: dict[str, float] = {"unscoped": 0.0, "executions": 0.0}
    for plane, ops in per_device.items():
        spans: dict[str, list[Interval]] = {}
        by_site: dict[str, list[Event]] = {}
        for e in ops:
            where = classify(e.scope)
            if xplane.collective_kind(e.name) is not None:
                by_site.setdefault(coll_site(where), []).append(e)
            for key in keys_of(where):
                spans.setdefault(key, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
        for site, collectives in by_site.items():
            spans["coll/" + site] = xplane.collective_intervals(collectives)
        for key, intervals in spans.items():
            seconds = xplane.length(xplane.union(
                xplane.clip(intervals, window))) / 1e9
            total[key] = total.get(key, 0.0) + seconds
        total["executions"] += sum(_mostly_in(r, [window])
                                   for r in runs.get(plane, []))
    return {key: value / len(per_device)
            for key, value in sorted(total.items())}


def seconds(table: dict[str, float] | None,
            keys: Iterable[str]) -> float | None:
    """The sum of the table's entries that match ``keys`` (``fnmatch``
    patterns); ``None`` where none does, as in a program without scopes."""
    if table is None:
        return None
    found = [table[k] for pattern in keys
             for k in fnmatch.filter(table, pattern)]
    return sum(found) if found else None


def warn_missing(name: str, keys: Iterable[str], where: str = "") -> None:
    """Says on stderr that the metric ``name`` found no time under ``keys``,
    and what to suspect."""
    print(f"{name}: no device time under {', '.join(keys)}{where}. First "
          f"suspect: a stale executable from the compilation cache, whose "
          f"key leaves scope names out (try an empty "
          f"JAX_COMPILATION_CACHE_DIR); then a scope that moved.",
          file=sys.stderr)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: python3 -m benchmarks.lib.scopes "
              "<trace.xplane.pb | events.json.gz>", file=sys.stderr)
        return 2
    path = argv[0]
    events = (xplane.load_events(path) if path.endswith(".json.gz")
              else xplane.read_events(path))
    summary = xplane.reduce_events(events)
    table = reduce_scopes(events)
    if table is None:
        print(f"{path}: no operation on a device plane", file=sys.stderr)
        return 1
    print(f"{summary.devices} device(s), window {summary.window_s:.6f} s, "
          f"busy {summary.busy_s:.6f} s, "
          f"collectives {summary.collective_s:.6f} s")
    for key, value in table.items():
        if key == "executions":
            print(f"{key:44s} {value:12.2f}")
        else:
            print(f"{key:44s} {value:12.6f} s "
                  f"{100 * value / summary.window_s:6.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
