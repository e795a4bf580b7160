"""``metrics/grouped_matmul_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events that carry the
kernel's name as the chip's trace spells it.  CPU only: arithmetic, no
device metric."""

import pytest

from benchmarks.lib import cells, program
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.xplane import Event

CELL = "olmoe-1b-7b.decode-1k-128"
PEAKS = device_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "grouped_matmul_roofline.py")


def test_costs_are_the_hand_counts(metric):
    # the prefill's gate projection: 48 x 1024 tokens x 8 experts
    ops, nbytes = metric.costs(393_216, 2048, 1024, 64, 2)
    assert ops == 2 * 393_216 * 2048 * 1024
    assert nbytes == 2 * (393_216 * (2048 + 1024) + 64 * 2048 * 1024)
    # a cached step's: 384 rows reach all 64 experts; 10 rows reach 10
    assert metric.costs(384, 2048, 1024, 64, 2)[1] == 2 * (
        384 * 3072 + 64 * 2048 * 1024)
    assert metric.costs(10, 2048, 1024, 64, 2)[1] == 2 * (
        10 * 3072 + 10 * 2048 * 1024)


def test_the_prefill_is_bound_by_operations_and_the_step_by_bytes(metric):
    prefill = metric.least_seconds(393_216, 2048, 1024, 64, 2, PEAKS)
    assert prefill == 2 * 393_216 * 2048 * 1024 / PEAKS["bf16_flops"]
    step = metric.least_seconds(384, 2048, 1024, 64, 2, PEAKS)
    assert step == metric.costs(384, 2048, 1024, 64, 2)[1] / PEAKS[
        "hbm_bytes_per_s"]
    assert 0.3e-3 < step < 0.4e-3 and 8e-3 < prefill < 9e-3


def _run(n_events: int, seconds_each: float) -> RunData:
    """What ``run.measure`` hands the reader of a traced run of the cell:
    the cell's two files, and the facts its reference counts."""
    cell = cells.resolve(CELL)
    name = ("%grouped_matmul.57 = bf16[1408,1024]{1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[88]{0} %broadcast_minimum_fusion.5)")
    events = [Event("/device:TPU:0", "XLA Ops", name, 1e6 * i,
                    1e9 * seconds_each) for i in range(n_events)]
    events.append(Event("/device:TPU:0", "XLA Ops",
                        "%fusion.1 = bf16[8] fusion(%grouped_matmul.57)",
                        0.0, 5e9))      # names the kernel, is not it
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    ref = program.reference(cell.config)
    facts["counts"] = program.counts(ref, ref.Shape.from_config(cell.config))
    return RunData(durations={}, facts=facts, peaks=PEAKS, trace=object(),
                   compiles_in_window=0, peak_bytes=None, scopes={},
                   events=events, config=cell.config, traffic=cell.traffic)


def test_reading_is_least_time_over_the_kernels_time(metric):
    config = cells.resolve(CELL).config
    routed = {"layers": config["num_hidden_layers"], "experts": 64,
              "top_k": 8, "d_model": 2048, "d_expert": 1024}
    assert _run(0, 2e-3).facts["counts"]["routed"] == routed
    layers, new = routed["layers"], 128
    calls = 3 * layers * (2 + new - 1)
    prefill = metric.layer_seconds(routed, 2, 48 * 1024, PEAKS)
    step = metric.layer_seconds(routed, 2, 48, PEAKS)
    least = layers * (2 * prefill + (new - 1) * step)
    assert 1.0 < least < 1.5    # seconds of a pair's kernels at their bounds
    # two traced samples, every call 2 ms
    got = metric.read(_run(2 * calls, 2e-3))
    assert got == pytest.approx(100 * 2 * least / (2 * calls * 2e-3))
    # a cut sample, no kernel event: left out
    assert metric.read(_run(calls - 1, 2e-3)) is None
    assert metric.read(_run(0, 2e-3)) is None
    # a run whose reference names no routed layers
    dense = _run(2 * calls, 2e-3)
    del dense.facts["counts"]["routed"]
    assert metric.read(dense) is None
