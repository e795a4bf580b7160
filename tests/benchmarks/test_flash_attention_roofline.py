"""``metrics/flash_attention_roofline.py``: its operations against a direct
count of the pairs a causal mask leaves, and its reading of made-up events
that carry the kernels' names as the chip's trace spells them.  CPU only:
arithmetic, no device metric."""

import numpy as np
import pytest

from benchmarks.lib import cells, program
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.xplane import Event

ONE_CHIP = "pythia-1.4b-widths.train-2k"
FOUR_CHIPS = "pythia-6.9b-widths.train-2k-dp2tp2"
PEAKS = device_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "flash_attention_roofline.py")


def test_costs_are_a_direct_count_at_a_tiny_shape(metric):
    batch, heads, seq, head_dim, itemsize = 2, 3, 5, 4, 2
    visible = np.tril(np.ones((seq, seq), bool))     # query >= key
    pairs = batch * heads * int(visible.sum())
    # scores and context forward; scores, dO·Vᵀ, dV, dQ, dK backward: one
    # multiply and one add an element of the head, each
    forward = sum(2 * head_dim for _product in range(2)) * pairs
    backward = sum(2 * head_dim for _product in range(5)) * pairs
    # q, k, v, o forward; q, k, v, o, dO, dq, dk, dv backward: once each
    operand = itemsize * batch * seq * heads * head_dim
    assert metric.costs(batch, heads, seq, head_dim, itemsize) == (
        (forward, 4 * operand), (backward, 8 * operand))


@pytest.mark.parametrize("batch,gflop,mbytes", [(8, (137.5, 343.8), 67.1),
                                                (4, (68.8, 171.9), 33.6)],
                         ids=["cell1", "cell3"])
def test_costs_against_hand_counts_at_the_cells_shapes(metric, batch, gflop,
                                                       mbytes):
    """One layer on one device: 16 heads of 128 over 2048 positions,
    bfloat16; 8 sequences in cell 1, 4 a device in cell 3."""
    (f_ops, f_bytes), (b_ops, b_bytes) = metric.costs(batch, 16, 2048, 128, 2)
    assert f_ops == 4 * 128 * batch * 16 * 2048 * 2049 // 2   # the triangle
    assert f_ops < 4 * 128 * batch * 16 * 2048 * 2048         # not the square
    assert b_ops * 2 == f_ops * 5
    assert (round(f_ops / 1e9, 1), round(b_ops / 1e9, 1)) == gflop
    assert f_bytes == 4 * 2 * batch * 2048 * 16 * 128
    assert b_bytes == 2 * f_bytes
    assert round(f_bytes / 4e6, 1) == mbytes
    # at this head width the operations bind, forward and backward
    forward, backward = metric.least_seconds(batch, 16, 2048, 128, 2, PEAKS)
    assert forward == f_ops / PEAKS["bf16_flops"]
    assert backward == b_ops / PEAKS["bf16_flops"]
    assert forward > f_bytes / PEAKS["hbm_bytes_per_s"]


def test_bytes_bind_where_the_sequence_is_short(metric):
    forward, _ = metric.least_seconds(64, 16, 64, 128, 2, PEAKS)
    assert forward == 4 * 2 * 64 * 64 * 16 * 128 / PEAKS["hbm_bytes_per_s"]


def test_a_device_of_the_mesh_has_its_share_of_batch_and_heads(metric):
    one = cells.resolve(ONE_CHIP).config
    four = cells.resolve(FOUR_CHIPS).config
    assert metric.device_shape(one, 8, 2048) == (8, 16, 2048, 128, 2)
    assert metric.device_shape(four, 8, 2048) == (4, 16, 2048, 128, 2)


def _run(workload: str, counts: dict, seconds_each: float) -> RunData:
    """What ``run.measure`` hands the reader of a traced run of the cell:
    the cell's two files, and the facts its reference counts."""
    cell = cells.resolve(workload)
    events = []
    for kernel, n in counts.items():
        name = (f"%{kernel}.16 = (bf16[8,2048,2048]{{2,1,0:T(8,128)(2,1)}}, "
                "f32[8,16,1,2048]{3,2,1,0:T(1,128)}) custom-call("
                "s32[1]{0:T(128)} %get-tuple-element.1772)")
        events += [Event(f"/device:TPU:{i % cell.chips}", "XLA Ops", name,
                         1e6 * i, 1e9 * seconds_each) for i in range(n)]
    events.append(Event("/device:TPU:0", "XLA Ops",
                        "%fusion.1 = bf16[8] fusion(%flash_fwd.16)",
                        0.0, 5e9))      # names a kernel, is not one
    ref = program.reference(cell.config)
    facts = {"chips": cell.chips,
             "counts": program.counts(ref,
                                      ref.Shape.from_config(cell.config))}
    return RunData(durations={}, facts=facts, peaks=PEAKS, trace=object(),
                   compiles_in_window=0, peak_bytes=None, scopes={},
                   events=events, config=cell.config, traffic=cell.traffic)


@pytest.mark.parametrize("workload,batch", [(ONE_CHIP, 8), (FOUR_CHIPS, 4)])
def test_reading_is_least_time_over_the_kernels_time(metric, workload, batch):
    cell = cells.resolve(workload)
    calls = cell.config["num_hidden_layers"] * cell.chips
    least = sum(metric.least_seconds(batch, 16, 2048, 128, 2, PEAKS))
    assert (2.4e-3 < least < 2.5e-3) == (batch == 8)
    # three traced steps, every call 2 ms, the forward kept by the policy
    every = dict.fromkeys(metric.KERNELS, 3 * calls)
    got = metric.read(_run(workload, every, 2e-3))
    assert got == pytest.approx(100 * least / (3 * 2e-3))
    assert got < 100
    # a forward that runs again in the backward pass is the kernels' own
    again = metric.read(_run(workload, {**every, "flash_fwd": 6 * calls},
                             2e-3))
    assert again == pytest.approx(100 * least / (4 * 2e-3))


@pytest.mark.parametrize("workload,batch", [(ONE_CHIP, 8), (FOUR_CHIPS, 4)])
def test_the_share_is_100_when_every_call_takes_its_least_time(
        metric, workload, batch):
    """Kernels that did only what the algorithm needs, at the chip's peak:
    the forward its least time, the two backward kernels theirs between
    them.  Nothing faster exists, so nothing reads over 100%."""
    cell = cells.resolve(workload)
    calls = 2 * cell.config["num_hidden_layers"] * cell.chips
    forward, backward = metric.least_seconds(batch, 16, 2048, 128, 2, PEAKS)
    run = _run(workload, {"flash_fwd": calls}, forward)
    for kernel, part in (("flash_bwd_dq", 3 / 7), ("flash_bwd_dkv", 4 / 7)):
        run.events += _run(workload, {kernel: calls},
                           backward * part).events[:calls]
    assert metric.read(run) == pytest.approx(100.0)
    slower = _run(workload, dict.fromkeys(metric.KERNELS, calls),
                  max(forward, backward))
    assert metric.read(slower) < 100.0


def test_no_kernel_event_reads_as_nothing(metric):
    """The parent's program, or an untraced run: the line leaves the metric
    out and nothing raises."""
    calls = cells.resolve(ONE_CHIP).config["num_hidden_layers"]
    every = dict.fromkeys(metric.KERNELS, 3 * calls)
    assert metric.read(_run(ONE_CHIP, every, 2e-3)) is not None
    assert metric.read(_run(ONE_CHIP, {}, 2e-3)) is None
    untraced = _run(ONE_CHIP, every, 2e-3)
    untraced.trace = None
    assert metric.read(untraced) is None


@pytest.mark.parametrize("change", [
    {"flash_fwd": -1, "flash_bwd_dq": -1, "flash_bwd_dkv": -1},  # a cut sample
    {"flash_bwd_dkv": None},                            # a kernel missing
    {"flash_fwd": +1},                                  # one forward more
], ids=["cut", "dkv_missing", "forward_extra"])
def test_kernels_that_engaged_in_part_raise(metric, change):
    """Events that carry the kernels' names and do not add up to the cell's
    steps must not read like the parent's absent kernels."""
    calls = cells.resolve(ONE_CHIP).config["num_hidden_layers"]
    counts = dict.fromkeys(metric.KERNELS, 3 * calls)
    for kernel, by in change.items():
        counts[kernel] = 0 if by is None else counts[kernel] + by
    with pytest.raises(ValueError, match="whole steps"):
        metric.read(_run(ONE_CHIP, counts, 2e-3))


def test_kernels_run_in_the_layers_that_attend(metric):
    """A model whose reference counts fewer layers that attend than it has
    layers calls the kernels that many times a step."""
    calls = cells.resolve(ONE_CHIP).config["num_hidden_layers"]
    every = dict.fromkeys(metric.KERNELS, 3 * calls)
    whole = metric.read(_run(ONE_CHIP, every, 2e-3))
    fewer = _run(ONE_CHIP, dict.fromkeys(metric.KERNELS, 3 * 2), 2e-3)
    fewer.facts["counts"]["attention_layers"] = 2
    assert metric.read(fewer) == pytest.approx(whole)
    with pytest.raises(ValueError, match="whole steps"):
        metric.read(_run(ONE_CHIP, dict.fromkeys(metric.KERNELS, 3 * 2 + 1),
                         2e-3))
