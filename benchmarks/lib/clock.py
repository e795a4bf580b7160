"""How far the device's clock is from the host's in one trace, and the idle
gaps named again once that is taken out.

Both clocks are in the profile, and nothing says how they were aligned.  On
the v5e the device's stamps ran 0.4 to 1.9 ms *behind* the host's (a
negative lead; ``PERF.md`` section 3), which is enough to put the 1.8 ms gap
between two steps under the wrong host span: ``xplane.reduce_events`` names
gaps without any correction, and stays as it is.  Causality bounds the
lead.  In each traced job the host's ``dispatch`` span starts at ``d0`` and
its ``readback`` ends at ``r1``; the program that job ran starts on the
device at ``s`` and ends at ``e``, both on the device's clock, which reads
host time plus ``lead``.  The device cannot start before it is asked and
the host cannot have the result before the device is done:

    s - lead >= d0   and   e - lead <= r1,   so   max(e - r1) <= lead <= min(s - d0)

over every job of every device.  The midpoint is the estimate and the width
of the band is what it cannot tell.

What ``aligned_gaps`` can and cannot attribute.  A gap much longer than the
band (a stalled input stream, a compile, a host that was descheduled) is
named after the host span, or the program's own ``ompi_tpu:`` annotation,
that was open at its middle, which the uncorrected ``idle_gaps`` gets wrong
by the lead.  A gap shorter than the band could lie under either of two
neighbouring spans and goes to ``below_band`` unnamed.  A gap about as long
as the band, which is where the 1.8 ms turn-around between two train steps
sits against a band of 1.2 to 2.1 ms, falls on either side from one trace
to the next; read its seconds, not its name.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple

from benchmarks.lib import xplane
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event

PROGRAM_PREFIX = "ompi_tpu:"    # annotations made inside the program
DISPATCH, READBACK = TRACE_PREFIX + "dispatch", TRACE_PREFIX + "readback"
BELOW_BAND = "below_band"


class Lead(NamedTuple):
    offset_ns: float        # device clock minus host clock, the midpoint
    band_ns: float          # upper bound minus lower bound


def program_annotations(events: Iterable[Event]) -> list[Event]:
    """The host spans the program itself makes (``ompi_tpu:data.produce``),
    which ``xplane.device_and_span_events`` leaves out."""
    return [e for e in events if e.plane == xplane.HOST_PLANE
            and e.name.startswith(PROGRAM_PREFIX)]


def _jobs(host: list[Event]) -> list[tuple[float, float]]:
    """(d0, r1) of each job: a ``dispatch`` span and the first ``readback``
    that starts at or after its end."""
    dispatches = sorted((e for e in host if e.name == DISPATCH),
                        key=lambda e: e.start_ns)
    readbacks = sorted((e for e in host if e.name == READBACK),
                       key=lambda e: e.start_ns)
    jobs, j = [], 0
    for d in dispatches:
        while (j < len(readbacks)
               and readbacks[j].start_ns < d.start_ns + d.duration_ns):
            j += 1
        if j == len(readbacks):
            break
        jobs.append((d.start_ns,
                     readbacks[j].start_ns + readbacks[j].duration_ns))
    return jobs


def estimate(events: Iterable[Event]) -> Lead | None:
    """``None`` where the trace has no job with a program run to bound the
    lead with, or where the two bounds cross (the spans are not around the
    device work they were taken for)."""
    _ops, host, runs = xplane.split(events)
    jobs = _jobs(host)
    lower, upper = float("-inf"), float("inf")
    for plane_runs in runs.values():
        for run in plane_runs:
            s, e = run.start_ns, run.start_ns + run.duration_ns

            def overlap(job):
                return min(e, job[1]) - max(s, job[0])

            job = max(jobs, key=overlap, default=None)
            # the lead is small beside a job, so a run lies mostly inside
            # the job that started it; anything else is not a job's program
            if job is None or overlap(job) < 0.5 * (e - s):
                continue
            lower, upper = max(lower, e - job[1]), min(upper, s - job[0])
    if lower == float("-inf") or lower > upper:
        return None
    return Lead((lower + upper) / 2, upper - lower)


def aligned_gaps(events: Iterable[Event], lead: Lead,
                 top: int = 10) -> list[list]:
    """``TraceSummary.idle_gaps`` again with the device's events moved back
    by the lead: [host span, idle seconds under it], most first, mean over
    the devices.  A gap goes to the innermost span open at its middle, the
    program's own annotations included, to ``outside`` where none is, and
    to ``below_band`` where it is shorter than the band."""
    events = list(events)
    per_device, host, _runs = xplane.split(events)
    window = xplane.window_of(per_device, host)
    named = host + program_annotations(events)
    gap_ns: dict[str, float] = {}
    for ops in per_device.values():
        busy = xplane.union(xplane.clip(
            [(e.start_ns - lead.offset_ns,
              e.start_ns - lead.offset_ns + e.duration_ns) for e in ops]
            + [(lo - lead.offset_ns, hi - lead.offset_ns)
               for lo, hi in xplane.collective_intervals(ops)], window))
        for lo, hi in xplane.subtract([window], busy):
            mid = (lo + hi) / 2
            open_spans = [h for h in named
                          if h.start_ns <= mid < h.start_ns + h.duration_ns]
            if hi - lo < lead.band_ns:
                name = BELOW_BAND
            elif open_spans:
                name = min(open_spans, key=lambda h: h.duration_ns).name
                name = name.removeprefix(TRACE_PREFIX)
            else:
                name = "outside"
            gap_ns[name] = gap_ns.get(name, 0.0) + (hi - lo)
    rows = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / len(per_device) / 1e9] for name, ns in rows]


def breakdown(events: Iterable[Event]) -> dict:
    """``clock`` and ``idle_gaps_aligned`` of a result line's breakdown;
    neither where the lead cannot be bounded."""
    events = list(events)
    lead = estimate(events)
    if lead is None:
        return {}
    return {"clock": {"offset_us": lead.offset_ns / 1e3,
                      "band_us": lead.band_ns / 1e3},
            "idle_gaps_aligned": aligned_gaps(events, lead)}
