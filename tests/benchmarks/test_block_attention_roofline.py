"""``metrics/block_attention_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events under the scopes the
program gives the cached step's block selection and attention.  CPU only:
arithmetic, no device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

CELL = "minicpm-sala.decode-16k-512-b24"
PEAKS = device_peaks("TPU v5 lite")
LIVE = 15_872 + 256             # positions live on the mean over the steps
SHAPE = (24, 1, 32, 2, 128, LIVE, 16, 64, 64, 2)


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "block_attention_roofline.py")


def test_costs_are_the_hand_counts(metric):
    config = cells.resolve(CELL).config
    assert metric.selected_layers(config) == 1
    assert metric.selected_layers({**config, "num_hidden_layers": 32}) == 8
    ops, nbytes = metric.costs(*SHAPE)
    # 1008 pooled keys and 2 x 4096 rows of 128 a K/V head, bfloat16
    assert nbytes == 24 * 2 * 128 * 2 * (1008 + 2 * 4096) == 113_049_600
    assert ops == 24 * 32 * 128 * 2 * (1008 + 2 * 4096)
    # a cache within the selection is read whole, and no more
    short = (*SHAPE[:5], 1024, *SHAPE[6:])
    assert metric.costs(*short)[1] == 24 * 2 * 128 * 2 * (64 + 2 * 1024)
    # a dense read of the live rows would be 3.6 times the selection's
    dense = 24 * 2 * 128 * 2 * 2 * LIVE
    assert dense / nbytes == pytest.approx(3.5, abs=0.1)


def test_the_layer_is_bound_by_bytes(metric):
    least = metric.least_seconds(PEAKS, *SHAPE)
    assert least == 113_049_600 / PEAKS["hbm_bytes_per_s"]
    assert 0.13e-3 < least < 0.14e-3


def _run(ms: dict, jobs: int) -> RunData:
    """A traced window of ``jobs`` ``full`` jobs (and as many ``first``),
    each one run of ``decode`` with ``ms[scope]`` in all under each scope."""
    cell = cells.resolve(CELL)
    under = ("jit(decode)/shard_map/decode.step/while/body/closed_call/"
             "layers/jit(run)/")
    events, at = [], 0
    for _ in range(jobs):
        for span, steps in (("first", 0), ("full", 1)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 10e6))
            events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                                "jit_decode(1)", at + 1e6, 8e6))
            start = at + 2e6
            for scope, took in (ms.items() if steps else ()):
                events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                    "fusion.9", start, 1e6 * took,
                                    under + scope + "/mul"))
                start += 1e6 * took
            events.append(Event(
                "/device:TPU:0", xplane.OPS_LINE, "fusion.7", at + 1e6, 1e6,
                "jit(decode)/shard_map/prefill/layers/jit(run)/attention/"
                "mul"))
            at += 10e6
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_reading_is_least_time_over_the_time_under_the_scopes(metric):
    steps = cells.resolve(CELL).traffic["max_new"] - 1
    least = steps * metric.least_seconds(PEAKS, *SHAPE)
    ms = {"blocks.score": 1.0, "blocks.select": 0.5, "attention": 3.5}
    assert metric.read(_run(ms, jobs=2)) == pytest.approx(
        100 * 2 * least / (2 * 5e-3))
    # the pooled key a step completes and the cache's write are not under it
    ms = {"blocks.pool": 0.4, "kv_cache": 0.1, "attention": 2.5}
    assert metric.read(_run(ms, jobs=1)) == pytest.approx(
        100 * least / 2.5e-3)
    # a step that gathers its rows has that time under it too
    ms = {"attention.gather": 1.5, "attention": 1.0}
    assert metric.read(_run(ms, jobs=1)) == pytest.approx(
        100 * least / 2.5e-3)


def test_a_run_with_nothing_under_the_scopes_reads_as_nothing(metric, capsys):
    run = _run({"blocks.pool": 5.0}, jobs=1)
    assert metric.read(run) is None
    assert "block_attention_roofline" in capsys.readouterr().err
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run({"attention": 5.0}, jobs=1)
    run.peaks = None
    assert metric.read(run) is None
    for other in ("keye-vl-2.0-30b-a3b.decode-8k-128-b64",
                  "kimi-linear-48b-a3b.decode-512-128-b384"):
        run = _run({"attention": 5.0}, jobs=1)
        run.config = cells.resolve(other).config
        assert metric.read(run) is None
