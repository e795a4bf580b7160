"""``metrics/sparse_attention_roofline.py``: its operations and bytes against
counts made by hand, and its reading of made-up events under the scopes the
program gives a cached step's index, selection, gather and attention; and
the three data files beside it, whose keys the cell's own programs have.
CPU only: arithmetic, no device metric."""

import pytest

from benchmarks.lib import cells, scopes
from benchmarks.lib import xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
PEAKS = device_peaks("TPU v5 lite")
SIZES = (64, 4, 8128.0, 16, 64, 2048, 32, 4, 128, 2)
DATA = ("sparse_index_share", "sparse_gather_share", "prefill_index_ms")


@pytest.fixture(scope="module")
def metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "sparse_attention_roofline.py")


def test_costs_are_the_hand_counts(metric):
    ops, nbytes = metric.costs(*SIZES)
    # 64 sequences x 4 layers: the index key of 8128 live positions (64
    # bfloat16 elements each) and 2048 K and 2048 V rows of 4 x 128
    keys = 64 * 4 * 8128 * 64 * 2
    rows = 64 * 4 * 2048 * 2 * 4 * 128 * 2
    assert nbytes == keys + rows
    assert (keys, rows) == (266_338_304, 1_073_741_824)
    # two operations a pair of index head element and position; four a pair
    # of query head element and selected position
    assert ops == 64 * 4 * (2 * 16 * 64 * 8128 + 4 * 32 * 128 * 2048)
    # under topk positions every one is selected
    short = metric.costs(64, 4, 100.0, 16, 64, 2048, 32, 4, 128, 2)
    assert short[1] == 64 * 4 * 100 * (64 + 2 * 4 * 128) * 2
    # the cache's type scales the bytes alone
    assert metric.costs(*SIZES[:-1], 4) == (ops, 2 * nbytes)


def test_the_step_is_bound_by_bytes(metric):
    ops, nbytes = metric.costs(*SIZES)
    least = metric.least_seconds(PEAKS, *SIZES)
    assert least == nbytes / PEAKS["hbm_bytes_per_s"]
    assert ops / PEAKS["bf16_flops"] < least / 10
    assert 1.6e-3 < least < 1.7e-3      # 1.34 GB a step


def _run(ms: dict, jobs: int) -> RunData:
    """A traced window of ``jobs`` ``full`` jobs (and as many ``first``),
    each one run of ``decode`` whose cached steps spend ``ms[scope]`` under
    each scope in all."""
    cell = cells.resolve(CELL)
    under = ("jit(decode)/shard_map/decode.step/while/body/closed_call/"
             "layers/while/body/closed_call/")
    events, at = [], 0
    for _ in range(jobs):
        for span, programs in (("first", 0), ("full", 1)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 10e6))
            events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                                "jit_decode(1)", at + 1e6, 8e6))
            then = at + 2e6
            for scope, took in ms.items() if programs else ():
                events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                    "fusion.1", then, 1e6 * took,
                                    under + scope + "/mul"))
                then += 1e6 * took
            events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                "fusion.7", at + 1e6, 1e6,
                                "jit(decode)/shard_map/prefill/layers/"
                                "index.score/mul"))
            at += 10e6
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


FOUR = {"index.score": 0.5, "index.select": 0.25, "attention.gather": 1.0,
        "attention": 0.75}


def test_reading_is_least_time_over_the_time_under_the_four_scopes(metric):
    traffic = cells.resolve(CELL).traffic
    assert (traffic["batch"], traffic["prompt_len"] + traffic["max_new"] / 2
            ) == SIZES[:3:2]
    least = (traffic["max_new"] - 1) * metric.least_seconds(PEAKS, *SIZES)
    # the prefill's index.score is not the steps'
    assert metric.read(_run(FOUR, jobs=2)) == pytest.approx(
        100 * 2 * least / (2 * 2.5e-3))
    assert metric.read(_run({"attention": 5.0}, jobs=1)) == pytest.approx(
        100 * least / 5e-3)


def test_a_run_with_nothing_under_the_scopes_reads_as_nothing(metric, capsys):
    assert metric.read(_run({"ffn": 5.0}, jobs=1)) is None
    assert "sparse_attention_roofline" in capsys.readouterr().err
    assert metric.read(RunData(durations={}, facts={}, peaks=PEAKS,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    run = _run(FOUR, jobs=1)
    run.peaks = None
    assert metric.read(run) is None
    run = _run(FOUR, jobs=1)
    run.config = cells.resolve("olmoe-1b-7b.decode-1k-128").config
    assert metric.read(run) is None


@pytest.mark.parametrize("name", DATA)
def test_a_data_files_keys_are_scopes_of_the_vocabulary(name):
    """The three metrics that are data for a shared reader: each key names a
    scope the program has, under the root the metric says."""
    from ompi_tpu.core.scopes import SCOPES

    spec = cells.load_json(f"{cells.BENCH_DIR}/metrics/{name}.json")
    assert spec["reader"] in ("scope_share", "scope_ms_per_run")
    root = "prefill" if spec["reader"] == "scope_ms_per_run" else "decode.step"
    assert (spec.get("span") == "first") == (root == "prefill")
    for key in spec["keys"]:
        kind, _, rest = key.partition("/")
        scope, _, under = rest.partition("@")
        assert kind == "scope" and scope in SCOPES and under == root
    made = _run(FOUR, jobs=1)
    reader = cells.load_module(
        f"{cells.BENCH_DIR}/readers/{spec['reader']}.py")
    value = reader.read(made, {**spec, "name": name})
    if name == "sparse_gather_share":
        assert value == pytest.approx(100 * 1e-3 / made.trace.window_s)
    elif name == "sparse_index_share":
        assert value == pytest.approx(100 * 0.75e-3 / made.trace.window_s)
    else:
        assert value == pytest.approx(1.0)     # the first job's 1 ms
