"""``metrics/lightning_update_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events under the scope the
program gives the cached step's lightning update.  CPU only: arithmetic, no
device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

CELL = "minicpm-sala.decode-16k-512-b24"
PEAKS = device_peaks("TPU v5 lite")
STATE = 32 * 128 * 128          # a sequence's state in one layer
SHAPE = (24, 3, 32, 128, 4)


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "lightning_update_roofline.py")


def test_costs_are_the_hand_counts(metric):
    config = cells.resolve(CELL).config
    assert metric.lightning_layers(config) == 3
    assert (config["lightning_nh"] * config["lightning_head_dim"] ** 2
            == STATE == 524_288)
    ops, nbytes = metric.costs(*SHAPE)
    # every element read once and written once in float32, five operations
    assert nbytes == 2 * 4 * 24 * 3 * STATE == 301_989_888
    assert ops == 5 * 24 * 3 * STATE
    assert metric.costs(*SHAPE[:-1], 2)[1] == nbytes // 2
    assert metric.costs(12, *SHAPE[1:])[1] == nbytes // 2
    # the whole model's 24 lightning layers
    assert metric.lightning_layers({**config, "num_hidden_layers": 32}) == 24


def test_the_update_is_bound_by_bytes(metric):
    least = metric.least_seconds(PEAKS, *SHAPE)
    assert least == 301_989_888 / PEAKS["hbm_bytes_per_s"]
    assert 0.36e-3 < least < 0.38e-3


def _run(update_ms: float, jobs: int, scope: str = "lightning.update"
         ) -> RunData:
    """A traced window of ``jobs`` ``full`` jobs (and as many ``first``),
    each one run of ``decode`` whose updates take ``update_ms`` in all."""
    cell = cells.resolve(CELL)
    under = ("jit(decode)/shard_map/decode.step/while/body/closed_call/"
             f"layers/jit(run)/{scope}/")
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
                                    under + "reduce_sum"))
            events.append(Event(
                "/device:TPU:0", xplane.OPS_LINE, "fusion.7", at + 1e6, 1e6,
                "jit(decode)/shard_map/prefill/layers/jit(run)/"
                "lightning.scan/mul"))
            at += 10e6
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_reading_is_least_time_over_the_time_under_the_scope(metric):
    steps = cells.resolve(CELL).traffic["max_new"] - 1
    least = steps * metric.least_seconds(PEAKS, *SHAPE)
    # made-up events: the arithmetic is what is held, not a share under 100
    assert metric.read(_run(5.0, jobs=2)) == pytest.approx(
        100 * 2 * least / (2 * 5e-3))
    assert metric.read(_run(2.5, jobs=1)) == pytest.approx(
        100 * least / 2.5e-3)


def test_a_run_with_nothing_under_the_scope_reads_as_nothing(metric, capsys):
    run = _run(5.0, jobs=1, scope="lightning_proj")
    assert metric.read(run) is None
    assert "lightning_update_roofline" in capsys.readouterr().err
    # no trace, no peaks, or a configuration with no such state: nothing,
    # and nothing raised
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run(5.0, jobs=1)
    run.peaks = None
    assert metric.read(run) is None
    for other in ("kimi-linear-48b-a3b.decode-512-128-b384",
                  "brumby-14b-base.decode-2k-128-b48"):
        run = _run(5.0, jobs=1)
        run.config = cells.resolve(other).config
        assert metric.read(run) is None
