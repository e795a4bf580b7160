"""The trace reduction, stage by stage: events -> metrics on intervals
counted by hand and on events recorded on the chip; .xplane.pb -> events on
a trace this test records on the CPU."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import xplane
from benchmarks.lib.spans import TRACE_PREFIX, Spans
from benchmarks.lib.xplane import Event

TESTDATA = os.path.join(os.path.dirname(xplane.__file__), "testdata")


def ops(device, rows):
    return [Event(f"/device:TPU:{device}", xplane.OPS_LINE, name, lo, hi - lo)
            for name, lo, hi in rows]


def host(rows):
    return [Event(xplane.HOST_PLANE, "python3", TRACE_PREFIX + name, lo,
                  hi - lo) for name, lo, hi in rows]


# Two devices under one host sample of 100 ns.
#
# device 0: busy [10,50] and [60,90] = 70.  Collectives: the asynchronous
# pair [25,50] and the synchronous [60,70] = 35; of those, fusion.1 and
# fusion.2 cover [25,40], so [40,50] and [60,70] = 20 are exposed.  Idle:
# [0,10] under data.next, [50,60] and [90,100] under readback.
# device 1: busy [0,80] = 80; its collective-permute pair [50,60] = 10 runs
# beside nothing = 10 exposed.  Idle: [80,100] under readback.
SYNTHETIC = (
    host([("sample", 0, 100), ("data.next", 0, 10), ("dispatch", 10, 20),
          ("readback", 20, 100)])
    + ops(0, [("while.1", 0, 100),                  # an envelope: dropped
              ("fusion.1", 10, 30),
              ("all-reduce-start.1", 25, 27),
              ("fusion.2", 30, 40),
              ("all-reduce-done.1", 45, 50),
              ("all-reduce.2", 60, 70),
              ("fusion.3", 70, 90)])
    + ops(1, [("fusion.1", 0, 50),
              ("collective-permute-start.4", 50, 52),
              ("collective-permute-done.4", 58, 60),
              ("fusion.2", 60, 80)])
    # not operations of a device: another line, another plane
    + [Event("/device:TPU:0", "XLA Modules", "jit_step", 0, 100),
       Event("/device:TPU:0", "Steps", "0", 0, 100),
       Event(xplane.HOST_PLANE, "python3", "PjitFunction(step)", 0, 100)]
)


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert xplane.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) \
        == [(0, 2), (4, 8), (22, 29)]
    assert xplane.clip([(0, 5), (8, 12), (20, 30)], (4, 10)) == [
        (4, 5), (8, 10)]
    assert xplane.length([(0, 3), (5, 8)]) == 6


def test_reduction_on_hand_counted_intervals():
    s = xplane.reduce_events(xplane.device_and_span_events(SYNTHETIC))
    assert s.devices == 2
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((70 + 80) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(0.25)                # device_idle
    assert s.collective_s / s.window_s == pytest.approx(0.225)      # coll_time
    assert s.exposed_collective_s / s.window_s == pytest.approx(0.15)
    gaps = {name: ns for name, ns in s.idle_gaps}
    assert gaps == {"readback": pytest.approx(20e-9),
                    "data.next": pytest.approx(5e-9)}
    top = {name: ns for name, ns in s.device_ops}
    assert "while.1" not in top and "jit_step" not in top
    assert top["fusion.1"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert s.device_ops[0][0] == "fusion.1"


def test_pairing_and_windows_without_host_spans():
    # two pairs in flight at once close by their instruction numbers; a
    # start the trace cut off counts for its own length; with no host span
    # the window is the extent of the operations
    events = ops(0, [("all-gather-start.1", 0, 1),
                     ("all-gather-start.2", 2, 3),
                     ("all-gather-done.2", 4, 5),
                     ("all-gather-done.1", 8, 10),
                     ("reduce-scatter-start.7", 12, 13)])
    per = sorted(xplane.collective_intervals(events))
    assert per == [(0, 10), (2, 5), (12, 13)]
    s = xplane.reduce_events(events)
    assert s.window_s == pytest.approx(13e-9)
    assert s.collective_s == pytest.approx(11e-9)
    assert s.exposed_collective_s == pytest.approx(11e-9)
    assert s.idle_gaps == [["outside", pytest.approx(2e-9)]]
    assert xplane.reduce_events(host([("sample", 0, 5)])) is None


def test_events_survive_the_gzip_json_round_trip(tmp_path):
    path = str(tmp_path / "events.json.gz")
    xplane.save_events(SYNTHETIC, path)
    assert xplane.load_events(path) == SYNTHETIC


@pytest.mark.parametrize("path", sorted(glob.glob(TESTDATA + "/*.json.gz")),
                         ids=os.path.basename)
def test_reduction_on_events_recorded_on_the_chip(path):
    """A few steps of the four-chip train cell, recorded on a v5litepod-4 in
    the PR that added the benchmark and exported by ``save_events``."""
    events = xplane.load_events(path)
    planes = {e.plane for e in events if xplane.DEVICE_PLANE.match(e.plane)}
    assert len(planes) == 4
    assert any(xplane.collective_kind(e.name) == "all-reduce"
               for e in events if e.plane in planes)
    assert any(e.name.startswith(TRACE_PREFIX) for e in events)
    s = xplane.reduce_events(events)
    assert s.devices == 4
    assert 0.0 < s.busy_s / s.window_s < 1.0
    assert 0.0 < s.exposed_collective_s <= s.collective_s < s.busy_s
    assert 1 <= len(s.device_ops) <= 10 and 1 <= len(s.idle_gaps) <= 10
    assert all(seconds > 0 for _name, seconds in s.device_ops)


def test_chip_testdata_is_there_and_small():
    files = glob.glob(TESTDATA + "/*.json.gz")
    assert files
    assert sum(os.path.getsize(f) for f in files) < 1 << 20


def test_first_stage_reads_what_jax_profiler_writes():
    """On the CPU there is no device plane, so the reduction has nothing to
    reduce; the reader must still find the planes, the lines and the
    benchmark's own spans in a trace that this JAX wrote."""
    class Job:
        def __init__(self):
            self.spans = Spans()
            self.fn = jax.jit(lambda x: (x @ x).sum())
            self.x = jnp.ones((64, 64))
            self.fn(self.x).block_until_ready()

        def sample(self):
            with self.spans.span("sample"):
                with self.spans.span("dispatch"):
                    y = self.fn(self.x)
                with self.spans.span("readback"):
                    y.block_until_ready()

    events = bench_run.trace_samples(Job(), 3)
    lines = xplane.describe(events)
    assert xplane.HOST_PLANE in lines
    ours = [e for e in events if e.name.startswith(TRACE_PREFIX)]
    assert sorted(e.name[len(TRACE_PREFIX):] for e in ours) == sorted(
        ["sample", "dispatch", "readback"] * 3)
    assert all(e.plane == xplane.HOST_PLANE and e.duration_ns > 0
               for e in ours)
    samples = sorted((e for e in ours if e.name.endswith("sample")),
                     key=lambda e: e.start_ns)
    inner = [e for e in ours if not e.name.endswith("sample")]
    assert all(any(s.start_ns <= e.start_ns and e.start_ns + e.duration_ns
                   <= s.start_ns + s.duration_ns for s in samples)
               for e in inner)
    kept = xplane.device_and_span_events(events)
    assert kept == ours or sorted(kept) == sorted(ours)
    assert xplane.reduce_events(kept) is None


# ---- a trace that lost events -----------------------------------------------

def program_runs(device, rows):
    return [Event(f"/device:TPU:{device}", xplane.MODULES_LINE, name, lo,
                  hi - lo) for name, lo, hi in rows]


def test_program_coverage_is_the_least_covered_devices_share():
    """Device 0 runs a program over [0, 100] and its operations cover 70 of
    it (the asynchronous collective counts while it is in flight); device 1
    runs one over [0, 80], covered whole; a device without a program run,
    or a trace without one, has nothing to cover."""
    assert xplane.program_coverage(SYNTHETIC) == pytest.approx(0.70)
    both = SYNTHETIC + program_runs(1, [("jit_step", 0, 80)])
    assert xplane.program_coverage(both) == pytest.approx(0.70)
    assert xplane.program_coverage(
        ops(1, [("fusion.1", 0, 50), ("fusion.2", 50, 80)])
        + program_runs(1, [("jit_step", 0, 80)])) == 1.0
    assert xplane.program_coverage(ops(1, [("fusion.1", 0, 50)])) is None
    assert xplane.program_coverage([]) is None


@pytest.mark.parametrize("file", [
    "pythia-6.9b-widths.train-2k-dp2tp2.events.json.gz",
    "scoped/pythia-6.9b-widths.train-2k-dp2tp2.events.json.gz"])
def test_a_trace_recorded_on_the_chip_is_whole(file):
    events = xplane.load_events(os.path.join(TESTDATA, file))
    assert 0.999 < xplane.program_coverage(events) <= 1.0
    assert xplane.WHOLE < 0.999


@pytest.mark.parametrize("covered,taken", [
    ([1.0], 1), ([0.6, 0.995], 2), ([0.5, 0.6, 0.7], 3), ([None], 1)])
def test_a_trace_that_lost_events_is_taken_again(monkeypatch, capsys,
                                                 covered, taken):
    """``whole_trace`` traces again while the operations leave a hole in the
    device's program runs, three times at most, keeps the last trace, and
    says what it saw."""
    traces = []

    def trace_samples(job, n):
        share = covered[len(traces)]
        traces.append(
            [] if share is None else
            ops(0, [("fusion.1", 0, round(1000 * share))])
            + program_runs(0, [("jit_step", 0, 1000)]))
        return traces[-1]

    monkeypatch.setattr(bench_run, "trace_samples", trace_samples)
    traced, tracing = bench_run.whole_trace(object(), 1)
    assert len(traces) == taken and traced is traces[-1]
    assert tracing == {"covered": covered[:taken], "retraced": taken - 1}
    said = capsys.readouterr().err
    assert said.count("events were lost") == sum(
        share is not None and share < xplane.WHOLE
        for share in covered[:taken])
    assert said.count("tracing again") == taken - 1
