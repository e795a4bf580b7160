"""``metrics/retention_update_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events under the scope the
program gives the cached step's retention update.  CPU only: arithmetic, no
device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

CELL = "brumby-14b-base.decode-2k-128-b48"
PEAKS = device_peaks("TPU v5 lite")
STATE = 8 * 8320 * (128 + 1)    # a sequence's state and normaliser, a layer
SHAPE = (48, 4, 8, 40, 8320, 128, 4)


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "retention_update_roofline.py")


def test_costs_are_the_hand_counts(metric):
    config = cells.resolve(CELL).config
    assert (config["num_key_value_heads"] * config["retention_state_dim"]
            * (config["head_dim"] + 1)) == STATE == 8_586_240
    ops, nbytes = metric.costs(*SHAPE)
    # every element read once and written once in float32; three operations
    # for the decay and the write, two for each of five query heads
    assert nbytes == 2 * 4 * 48 * 4 * STATE == 13_188_464_640
    assert ops == 13 * 48 * 4 * STATE
    assert metric.costs(*SHAPE[:-1], 2)[1] == nbytes // 2
    assert metric.costs(12, *SHAPE[1:])[1] == nbytes // 4
    # the least exact layout would be 8256 wide: the stated one is what the
    # configuration carries, 0.8% more
    assert metric.costs(48, 4, 8, 40, 8256, 128, 4)[1] == pytest.approx(
        nbytes / 1.00775, rel=1e-4)


def test_the_update_is_bound_by_bytes(metric):
    least = metric.least_seconds(PEAKS, *SHAPE)
    assert least == 13_188_464_640 / PEAKS["hbm_bytes_per_s"]
    assert 16.0e-3 < least < 16.2e-3    # of a step's least 17.4 GB / 819e9


def _run(update_ms: float, jobs: int,
         scope: str = "attention/retention.update") -> RunData:
    """A traced window of ``jobs`` ``full`` jobs (and as many ``first``),
    each one run of ``decode`` whose updates take ``update_ms`` in all."""
    cell = cells.resolve(CELL)
    under = ("jit(decode)/shard_map/decode.step/while/body/closed_call/"
             f"layers/while/body/closed_call/{scope}/")
    events, at = [], 0
    for _ in range(jobs):
        for span, steps in (("first", 0), ("full", 1)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 10e6))
            events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                                "jit_decode(1)", at + 1e6, 8e6))
            if steps:
                events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                    "fusion.481", at + 2e6, 1e6 * update_ms,
                                    under + "dynamic_update_slice"))
            events.append(Event(
                "/device:TPU:0", xplane.OPS_LINE, "fusion.7", at + 1e6, 1e6,
                "jit(decode)/shard_map/prefill/attention/retention.scan/mul"))
            at += 10e6
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_reading_is_least_time_over_the_time_under_the_scope(metric):
    traffic = cells.resolve(CELL).traffic
    steps = traffic["max_new"] - 1
    least = steps * metric.least_seconds(PEAKS, *SHAPE)
    # two traced jobs, the updates of each 5 ms in all (made-up events:
    # the arithmetic is what is held, not a share under 100)
    assert metric.read(_run(5.0, jobs=2)) == pytest.approx(
        100 * 2 * least / (2 * 5e-3))
    assert metric.read(_run(2.5, jobs=1)) == pytest.approx(
        100 * least / 2.5e-3)


def test_a_run_with_nothing_under_the_scope_reads_as_nothing(metric, capsys):
    run = _run(5.0, jobs=1, scope="attention")
    assert metric.read(run) is None
    assert "retention_update_roofline" in capsys.readouterr().err
    # no trace, no peaks, or a configuration with no such state: nothing,
    # and nothing raised
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run(5.0, jobs=1)
    run.peaks = None
    assert metric.read(run) is None
    for other in ("kimi-linear-48b-a3b.decode-512-128-b384",
                  "falcon-h1-34b.decode-128-64-b192"):
        run = _run(5.0, jobs=1)
        run.config = cells.resolve(other).config
        assert metric.read(run) is None
