"""``metrics/masked_attention_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events that carry the
kernel's name.  CPU only: arithmetic, no device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib import xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
PEAKS = device_peaks("TPU v5 lite")
# batch, layers, prompt, topk, heads, K/V heads, head width, bytes an element
SIZES = (64, 4, 8064, 2048, 32, 4, 128, 2)
SLICES = 16         # 8064 queries in slices of 512, the last of 384


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "masked_attention_roofline.py")


def test_pairs_are_the_selections(metric):
    # every earlier position and itself while a query has no more than topk
    assert metric.pairs(5, 8) == 1 + 2 + 3 + 4 + 5
    assert metric.pairs(8, 8) == 36
    # topk of them past that
    assert metric.pairs(11, 8) == 36 + 3 * 8
    assert metric.pairs(8064, 2048) == sum(min(t + 1, 2048)
                                           for t in range(8064))
    assert metric.pairs(8064, 2048) == 14_418_944


def test_costs_are_the_hand_counts(metric):
    ops, nbytes = metric.costs(*SIZES)
    # a kept pair is two products of 128 elements, two operations each, for
    # each of 32 query heads: 236 GFLOP a sequence and layer
    assert ops == 64 * 4 * 14_418_944 * 32 * 128 * 4
    assert ops // (64 * 4) == 236_239_978_496
    # q and o of 32 heads, k and v of 4, 8064 x 128 bfloat16 elements each
    assert nbytes == 64 * 4 * 8064 * 128 * 2 * (32 + 32 + 4 + 4)
    # a dense causal prefill keeps every pair: more operations, equal bytes
    dense = metric.costs(*SIZES[:3], 1 << 30, *SIZES[4:])
    assert dense == (64 * 4 * (8064 * 8065 // 2) * 32 * 128 * 4, nbytes)
    assert metric.costs(*SIZES[:-1], 4) == (ops, 2 * nbytes)


def test_the_prefill_is_bound_by_operations(metric):
    ops, nbytes = metric.costs(*SIZES)
    least = metric.least_seconds(PEAKS, *SIZES)
    assert least == ops / PEAKS["bf16_flops"]
    assert nbytes / PEAKS["hbm_bytes_per_s"] < least / 5
    assert 0.30 < least < 0.31          # 60.5 TFLOP a prefill at 197e12


def _run(calls: int, ms: float, samples: int = 1) -> RunData:
    """A traced window of ``samples`` pairs of jobs, each one run of
    ``decode`` with ``calls`` events of the kernel of ``ms`` each."""
    cell = cells.resolve(CELL)
    events, at = [], 0
    for _ in range(samples):
        for span in ("first", "full"):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 10e9))
            events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                                "jit_decode(1)", at + 1e6, 9e9))
            for n in range(calls):
                events.append(Event(
                    "/device:TPU:0", xplane.OPS_LINE,
                    f"%masked_attention.{n % 16} = bf16[2,512,4096] "
                    f"custom-call(...)", at + 2e6 + n * 1e6 * ms, 1e6 * ms,
                    "jit(decode)/prefill/layers/attention/jit(_call)/"
                    "masked_attention/pallas_call"))
            # its operands' slices carry its name further in: not the kernel
            events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                "%slice-start.3 = bf16[2,512,512] "
                                "slice-start(%masked_attention.1)",
                                at + 8e9, 1e6))
            at += 10e9
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


@pytest.mark.parametrize("groups", [32, 64, 1])
def test_reading_is_least_time_over_the_kernels_events(metric, groups):
    cell = cells.resolve(CELL)
    assert (cell.traffic["batch"], cell.config["num_hidden_layers"],
            cell.traffic["prompt_len"], cell.config["sa_config"]["topk"]
            ) == SIZES[:4]
    least = metric.least_seconds(PEAKS, *SIZES)
    calls = 4 * SLICES * groups
    # two prefills a sample, however many sequences a pass holds
    assert metric.read(_run(calls, 0.5)) == pytest.approx(
        100 * 2 * least / (2 * calls * 0.5e-3))
    assert metric.read(_run(calls, 0.25, samples=2)) == pytest.approx(
        100 * 4 * least / (4 * calls * 0.25e-3))


def test_a_run_without_the_kernel_reads_as_nothing(metric):
    assert metric.read(_run(0, 1.0)) is None
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run(4 * SLICES, 1.0)
    run.peaks = None
    assert metric.read(run) is None
    run = _run(4 * SLICES, 1.0)
    run.config = cells.resolve("olmoe-1b-7b.decode-1k-128").config
    assert metric.read(run) is None


@pytest.mark.parametrize("calls", [4 * SLICES * 32 - 1, 4 * 15 * 32,
                                   4 * SLICES * 3])
def test_a_kernel_that_engaged_in_part_raises(metric, calls):
    """One event short; a slice a layer on another path; groups that do not
    divide the batch."""
    with pytest.raises(ValueError, match="not whole prefills"):
        metric.read(_run(calls, 1.0))


def test_the_events_name_is_the_kernels(metric):
    """The name the kernel gives its ``pallas_call`` is the events' name
    (the metric's row is in ``test_keye_vl2_rows.py`` until the benchmark
    can take it)."""
    import inspect

    from ompi_tpu.ops import masked_attention

    assert f'name="{metric.KERNEL}"' in inspect.getsource(masked_attention)
