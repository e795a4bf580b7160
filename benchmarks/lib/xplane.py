"""From a ``jax.profiler`` trace to device busy, idle and collective time.

Two stages, so that each can be checked apart:

1. ``read_events``: ``.xplane.pb`` -> ``Event`` tuples (plane, line, name,
   start, duration; nanoseconds on the profiler's clock; and, of a device
   operation, the HLO ``op_name`` that ``hlo_names`` finds for it in the
   programs the profile embeds).  ``save_events`` / ``load_events`` keep
   such a list as gzip JSON.
2. ``reduce_events``: events -> ``TraceSummary``.

What a TPU trace looks like, as read by hand from traces recorded on a v5e
and a v5litepod-4 in the PR that added this file (``PERF.md``, Findings):
each chip is a plane ``/device:TPU:<n>`` with the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` and ``Async XLA Ops``.  ``XLA Ops`` holds one event
per executed HLO instruction, named by the instruction's whole text
(``%fusion.12 = f32[8,16]{1,0:T(8,128)} fusion(...), kind=kLoop, ...``).  A
``while``, ``conditional`` or ``call`` event is an envelope around the
events of its body, which follow on the same line; envelopes are dropped,
or a loop would count as one long operation and hide every gap and every
exposed collective inside it.  An asynchronous operation is two events,
``%<op>-start.<n>`` (about a microsecond) and ``%<op>-done.<n>`` (the wait),
with the same number.  The benchmark's host spans (``spans.TRACE_PREFIX``)
are events of a ``/host:CPU`` line; the device's clock ran about a
millisecond ahead of the host's in those traces, so an idle gap shorter
than that can be named after the neighbouring span.

Time is counted as the union of intervals, never as a sum of durations: busy
is the union of a device's operations, collective time the union of its
collectives (an asynchronous pair counts once, from the start event's begin
to the done event's end), and exposed collective time the part of that
union in which no other operation runs on the device.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Iterable, NamedTuple

from benchmarks.lib.spans import TRACE_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"    # one event per run of a program
HOST_PLANE = "/host:CPU"
ENVELOPES = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = tuple[float, float]


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    duration_ns: float
    scope: str = ""     # a device operation's op_name, where one was found


# --------------------------------------------------------------------------
# stage 1: the file
# --------------------------------------------------------------------------

def read_events(pb_path: str) -> list[Event]:
    """Every event of every line of every plane, with nothing left out or
    renamed; ``device_and_span_events`` picks what stage 2 reads."""
    import jax.profiler

    from benchmarks.lib import hlo_names    # imports this module

    data = jax.profiler.ProfileData.from_file(pb_path)
    events = [Event(plane.name, line.name, ev.name or "", float(ev.start_ns),
                    float(ev.duration_ns or 0.0))
              for plane in data.planes for line in plane.lines
              for ev in line.events]
    return hlo_names.with_op_names(events,
                                   hlo_names.program_op_names(pb_path))


def device_and_span_events(events: Iterable[Event]) -> list[Event]:
    """The device planes whole and the benchmark's own host spans: what
    ``reduce_events`` reads, and what is worth keeping of a trace."""
    return [e for e in events
            if DEVICE_PLANE.match(e.plane)
            or (e.plane == HOST_PLANE and e.name.startswith(TRACE_PREFIX))]


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump([list(e) for e in events], f, separators=(",", ":"))


def load_events(path: str) -> list[Event]:
    """What ``save_events`` wrote: six columns, or the five of a recording
    from before events had a scope."""
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return [Event(*row) for row in json.load(f)]


def describe(events: Iterable[Event]) -> dict[str, dict[str, int]]:
    """plane -> line -> number of events: what to look at first in a trace
    from a device not seen before."""
    out: dict[str, dict[str, int]] = {}
    for e in events:
        lines = out.setdefault(e.plane, {})
        lines[e.line] = lines.get(e.line, 0) + 1
    return out


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    merged: list[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The part of ``a`` that ``b`` does not cover; both are unions."""
    out: list[Interval] = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> list[Interval]:
    return [(max(lo, window[0]), min(hi, window[1])) for lo, hi in intervals
            if hi > window[0] and lo < window[1]]


def length(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


# --------------------------------------------------------------------------
# stage 2: the reduction
# --------------------------------------------------------------------------

_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def instruction(name: str) -> str:
    """``%all-reduce-start.12 = f32[...] all-reduce-start(...)`` ->
    ``all-reduce-start.12``; a bare instruction name stays what it is."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _base(name: str) -> str:
    """``all-reduce-start.12.remat`` -> ``all-reduce-start``."""
    return instruction(name).split(".")[0]


def opcode(name: str) -> str:
    """The HLO opcode where the event carries the instruction's text, and
    the instruction's base name where it carries the name alone."""
    _, eq, rest = name.partition(" = ")
    found = _OPCODE.search(rest) if eq else None
    return found.group(1) if found else _base(name)


def label(name: str, width: int = 120) -> str:
    """An event's name short enough to print: layouts and sigils out, cut
    to ``width``.  The shapes stay, they say what a ``fusion.461`` is."""
    return _LAYOUT.sub("", name).replace("%", "")[:width]


def collective_kind(name: str) -> str | None:
    """The collective an operation belongs to, or None.  The opcode decides
    where the event carries one (``psum.158 = f32[...] all-reduce(...)``),
    and the instruction's name otherwise, or where the opcode is a wrapper
    (``reduce-scatter-start.3 = ... async-start(...)``, a fusion named after
    the collective inside it)."""
    for text in (opcode(name), _base(name)):
        for kind in COLLECTIVES:
            if text.startswith(kind):
                return kind
    return None


def _half(name: str) -> str | None:
    """``"start"`` or ``"done"`` for one half of an asynchronous pair."""
    for text in (opcode(name), _base(name)):
        for half in ("start", "done"):
            if text.endswith("-" + half):
                return half
    return None


def collective_intervals(ops: list[Event]) -> list[Interval]:
    """One interval per collective of one device's operations in time
    order.  A start event opens one and the next done event of the same
    instruction number (of the same kind, where the numbers differ) closes
    it; any other collective operation, such as the synchronous
    ``all-reduce`` a ``lax.psum`` became in the v5litepod-4 trace, is its own
    interval."""
    pending: dict[str, list[Event]] = {}
    out: list[Interval] = []
    for e in ops:
        kind = collective_kind(e.name)
        if kind is None:
            continue
        half = _half(e.name)
        end = e.start_ns + e.duration_ns
        if half == "start":
            pending.setdefault(kind, []).append(e)
        elif half == "done" and pending.get(kind):
            number = instruction(e.name).partition(".")[2]
            starts = pending[kind]
            match = next((s for s in starts if number ==
                          instruction(s.name).partition(".")[2]), starts[0])
            starts.remove(match)
            out.append((match.start_ns, end))
        else:
            out.append((e.start_ns, end))
    for starts in pending.values():     # cut off by the end of the trace
        out += [(s.start_ns, s.start_ns + s.duration_ns) for s in starts]
    return out


@dataclasses.dataclass
class TraceSummary:
    """Seconds, each the mean over the devices that ran an operation."""
    devices: int
    window_s: float
    busy_s: float
    collective_s: float
    exposed_collective_s: float
    device_ops: list[list]      # [name, seconds], most time first
    idle_gaps: list[list]       # [host span, idle seconds under it]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def split(events: Iterable[Event]):
    """(device plane -> its operations in time order, the benchmark's host
    spans, device plane -> its program runs): what every reduction reads.
    Envelopes and events without a duration are left out."""
    per_device: dict[str, list[Event]] = {}
    runs: dict[str, list[Event]] = {}
    host: list[Event] = []
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            if e.line == OPS_LINE:
                if opcode(e.name) not in ENVELOPES and e.duration_ns > 0:
                    per_device.setdefault(e.plane, []).append(e)
            elif e.line == MODULES_LINE:
                runs.setdefault(e.plane, []).append(e)
        elif e.plane == HOST_PLANE and e.name.startswith(TRACE_PREFIX):
            host.append(e)
    for ops in list(per_device.values()) + list(runs.values()):
        ops.sort(key=lambda e: e.start_ns)
    return per_device, host, runs


def window_of(per_device: dict[str, list[Event]],
              host: list[Event]) -> Interval:
    """The extent of the benchmark's host spans, which enclose whole samples
    of the job; without any, the extent of the device's operations."""
    bounds = host or [e for ops in per_device.values() for e in ops]
    return (min(e.start_ns for e in bounds),
            max(e.start_ns + e.duration_ns for e in bounds))


def _busy(ops: list[Event], window: Interval | None = None
          ) -> tuple[list[Interval], list[Interval], list[Interval]]:
    """(busy, collectives, other operations) of one device, as intervals.
    An asynchronous collective is in flight between its two events, so the
    device is busy there even when nothing else runs."""
    others = ((e.start_ns, e.start_ns + e.duration_ns) for e in ops
              if collective_kind(e.name) is None)
    colls = collective_intervals(ops)
    if window is not None:
        others, colls = clip(others, window), clip(colls, window)
    others, colls = union(others), union(colls)
    return union(others + colls), colls, others


# A trace is whole where the operations of a device cover its program runs:
# inside a run of a program the device goes from one operation to the next
# (0.9998 and more of every run on the traces kept under ``testdata``).  The
# profiler can lose a stretch of a device's events: one traced run of
# sixteen on the chip (PR 37) kept 1.5 s of the operations of a 2.4 s run of
# a decoder's program, and every share of that trace read wrong.
WHOLE = 0.9


def program_coverage(events: Iterable[Event]) -> float | None:
    """The share of its program runs (the ``XLA Modules`` line) that a
    device's operations cover, on the device where that is least; ``None``
    where no device plane has both."""
    per_device, _host, runs = split(events)
    shares = []
    for plane, ops in per_device.items():
        programs = union((e.start_ns, e.start_ns + e.duration_ns)
                         for e in runs.get(plane, []) if e.duration_ns > 0)
        if programs:
            holes = subtract(programs, _busy(ops)[0])
            shares.append(1.0 - length(holes) / length(programs))
    return min(shares, default=None)


def reduce_events(events: Iterable[Event], top: int = 10) -> TraceSummary | None:
    """``None`` where no operation ran on a device plane.

    The window is ``window_of``.  An idle gap goes to the innermost host
    span that is open at its middle, and to ``outside`` where none is.
    """
    per_device, host, _runs = split(events)
    if not per_device:
        return None
    window = window_of(per_device, host)

    busy = coll = exposed = 0.0
    op_ns: dict[str, float] = {}
    gap_ns: dict[str, float] = {}
    for ops in per_device.values():
        busy_at, colls, others = _busy(ops, window)
        busy += length(busy_at)
        coll += length(colls)
        exposed += length(subtract(colls, others))
        for e in ops:
            op_ns[e.name] = op_ns.get(e.name, 0.0) + e.duration_ns
        for lo, hi in subtract([window], busy_at):
            mid = (lo + hi) / 2
            open_spans = [h for h in host
                          if h.start_ns <= mid < h.start_ns + h.duration_ns]
            name = (min(open_spans, key=lambda h: h.duration_ns)
                    .name[len(TRACE_PREFIX):] if open_spans else "outside")
            gap_ns[name] = gap_ns.get(name, 0.0) + (hi - lo)

    n = len(per_device)

    def ranked(table: dict[str, float]) -> list[list]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[label(name), ns / n / 1e9] for name, ns in rows]

    return TraceSummary(
        devices=n, window_s=(window[1] - window[0]) / 1e9,
        busy_s=busy / n / 1e9, collective_s=coll / n / 1e9,
        exposed_collective_s=exposed / n / 1e9,
        device_ops=ranked(op_ns), idle_gaps=ranked(gap_ns))
