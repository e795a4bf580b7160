"""Two more per-layer rows that ``BENCHMARK.json`` cannot take yet (PERF.md
section 7 item 1): the windows of held picks a routed call runs, in the
prefill and in a cached step (``benchmarks/readers/windows_a_call.py``, from
the ``grouped_matmul`` events of a traced run and the router's ``top_k``).
``ROWS`` is what a ``benchmark`` PR appends beside the thirty that
``test_longcat_flash_rows.py`` counts.  Until then what the tests of a row
ask is asked here, on made-up events under the names a traced run of cell 11
carries (``chiprun_out/pr59a/dump_change``).  CPU only: nothing here is a
time."""

import pytest

from benchmarks.lib import cells, xplane
from benchmarks.lib.rundata import RunData
from benchmarks.lib.xplane import Event
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "longcat-flash-chat.decode-896-128-b160"
BENCH = cells.load_benchmark()
ROWS = [
    {"name": "prefill_moe_windows_a_call", "unit": "windows/call",
     "better": "lower", "source": "device_trace", "layer": "decoder",
     "moves": "ttft_ms", "workloads": [CELL]},
    {"name": "moe_step_windows_a_call", "unit": "windows/call",
     "better": "lower", "source": "device_trace", "layer": "decoder",
     "moves": "decode_tokens_per_s", "workloads": [CELL]},
]
ROOT = {"prefill_moe_windows_a_call": "prefill",
        "moe_step_windows_a_call": "decode.step"}
PATH = "jit(decode)/{root}/while/body/closed_call/layers/jit(run)/ffn/"


@pytest.mark.parametrize("row", ROWS, ids=lambda r: r["name"])
def test_a_row_moves_a_metric_the_cell_reports(row):
    assert NAME.match(row["name"]) and LAYER.match(row["layer"])
    assert row["layer"] in PERF_LAYERS
    taken = {m["name"] for key in ("end_to_end", "per_layer")
             for m in BENCH[key]}
    assert row["name"] not in taken
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == row["moves"])
    assert CELL in moved["workloads"]
    reader = cells.load_reader(cells.BENCH_DIR, row["name"])
    assert reader.spec["root"] == ROOT[row["name"]]


def _run(root: str, windows: list[int], laid_out: int) -> RunData:
    """A traced run with one routed call a number of ``windows``: the
    router's sort, then three kernel events a window the call ran, under
    ``root``; and one more call under another root, which no reading of
    ``root`` may count."""
    events, at = [], 0.0

    def op(name, scope):
        nonlocal at
        events.append(Event("/device:TPU:0", xplane.OPS_LINE, name, at, 1e3,
                            scope))
        at += 2e3

    def call(under, ran):
        path = PATH.format(root=under)
        op("%sort.7 = (f32[160,768]{1,0}, s32[160,768]{1,0}) sort(...)",
           path + "moe.route/top_k")
        op("%sort.9 = (s32[1920]{0}, s32[1920]{0}) sort(...)",
           path + "moe.dispatch/sort")
        for _ in range(ran):
            for i, wide in enumerate((2048, 2048, 6144)):
                op(f"%grouped_matmul.{i} = bf16[{laid_out},{wide}]"
                   "{1,0:T(8,128)(2,1)} custom-call(s32[21]{0} %x)",
                   path + "while/body/closed_call/cond/branch_1_fun/"
                   "moe.experts/grouped_matmul/pallas_call")

    for ran in windows:
        call(root, ran)
    call("decode.step" if root == "prefill" else "prefill", 5)
    return RunData(durations={}, facts={}, peaks=None,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, events=events)


@pytest.mark.parametrize("windows,want", [
    pytest.param([1, 1, 1, 1], 1.0, id="every-call-fits-one"),
    pytest.param([1, 2, 1, 4], 2.0, id="two-calls-overflow"),
    pytest.param([24], 24.0, id="every-pick-held-here")])
@pytest.mark.parametrize("name", sorted(ROOT))
def test_a_reader_counts_the_windows_a_call(name, windows, want):
    reader = cells.load_reader(cells.BENCH_DIR, name)
    run = _run(ROOT[name], windows, 336)
    assert reader.read(run) == want
    shared = cells.load_module(cells.BENCH_DIR + "/readers/windows_a_call.py")
    assert shared.layouts(run.events, ROOT[name]) == {336: 3 * sum(windows)}


@pytest.mark.parametrize("name", sorted(ROOT))
def test_a_run_without_a_trace_or_a_routed_layer_reads_as_nothing(name):
    reader = cells.load_reader(cells.BENCH_DIR, name)
    run = _run(ROOT[name], [1], 336)
    assert reader.read(RunData(durations={}, facts={}, peaks=None,
                               trace=None, compiles_in_window=0,
                               peak_bytes=None)) is None
    dense = [e for e in run.events if "grouped_matmul" not in e.name]
    assert reader.read(RunData(
        durations={}, facts={}, peaks=None, trace=run.trace,
        compiles_in_window=0, peak_bytes=None, events=dense)) is None
