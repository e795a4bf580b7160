"""``metrics/kda_update_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events under the scope the
program gives the cached step's delta-rule update.  CPU only: arithmetic, no
device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
PEAKS = device_peaks("TPU v5 lite")
STATE = 32 * 128 * 128          # a sequence's matrix state in one layer


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "kda_update_roofline.py")


def test_costs_are_the_hand_counts(metric):
    config = cells.resolve(CELL).config
    group = config["linear_attn_config"]
    assert group["num_heads"] * group["head_dim"] ** 2 == STATE == 524_288
    # layers 1, 2, 3 and 5 of the five that are run; 4 is the latent one
    assert metric.kda_layers(config) == 4
    assert metric.kda_layers({**config, "num_hidden_layers": 27}) == 20
    ops, nbytes = metric.costs(384, 4, 32, 128, 4)
    # every element read once and written once in float32, seven operations
    assert nbytes == 2 * 4 * 384 * 4 * 524_288 == 6_442_450_944
    assert ops == 7 * 384 * 4 * 524_288
    assert metric.costs(384, 4, 32, 128, 2)[1] == nbytes // 2
    assert metric.costs(96, 4, 32, 128, 4)[1] == nbytes // 4


def test_the_update_is_bound_by_bytes(metric):
    least = metric.least_seconds(384, 4, 32, 128, 4, PEAKS)
    assert least == 6_442_450_944 / PEAKS["hbm_bytes_per_s"]
    assert 7.8e-3 < least < 7.9e-3      # of a step's least 15.4 GB / 819e9


def _run(update_ms: float, jobs: int, scope: str = "kda.update") -> RunData:
    """A traced window of ``jobs`` ``full`` jobs (and as many ``first``),
    each one run of ``decode`` whose updates take ``update_ms`` in all."""
    cell = cells.resolve(CELL)
    under = ("jit(decode)/shard_map/decode.step/while/body/closed_call/"
             f"layers/{scope}/")
    events, at = [], 0
    for _ in range(jobs):
        for span, programs in (("first", 0), ("full", 1)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 10e6))
            events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                                "jit_decode(1)", at + 1e6, 8e6))
            if programs:
                events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                    "fusion.481", at + 2e6, 1e6 * update_ms,
                                    under + "dynamic_update_slice"))
            events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                "fusion.7", at + 1e6, 1e6,
                                "jit(decode)/shard_map/prefill/kda.scan/mul"))
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
    least = steps * metric.least_seconds(traffic["batch"], 4, 32, 128, 4,
                                         PEAKS)
    # two traced jobs, the updates of each 5 ms in all (made-up events:
    # the arithmetic is what is held, not a share under 100)
    assert metric.read(_run(5.0, jobs=2)) == pytest.approx(
        100 * 2 * least / (2 * 5e-3))
    assert metric.read(_run(2.5, jobs=1)) == pytest.approx(
        100 * least / 2.5e-3)


def test_a_run_with_nothing_under_the_scope_reads_as_nothing(metric, capsys):
    run = _run(5.0, jobs=1, scope="attention")
    assert metric.read(run) is None
    assert "kda_update_roofline" in capsys.readouterr().err
    # no trace, no peaks, or a configuration with no such state: nothing,
    # and nothing raised
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run(5.0, jobs=1)
    run.peaks = None
    assert metric.read(run) is None
    for other in ("olmoe-1b-7b.decode-1k-128",
                  "falcon-h1-34b.decode-128-64-b192"):
        run = _run(5.0, jobs=1)
        run.config = cells.resolve(other).config
        assert metric.read(run) is None
