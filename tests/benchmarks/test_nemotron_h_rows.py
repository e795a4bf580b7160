"""The three per-layer rows that ``BENCHMARK.json`` cannot take yet.

``benchmarks/metrics/`` holds a reader or a data file for each of them, and no
row names them: a program PR may add rows at the end of ``per_layer`` alone,
and ``test_startup_metrics.py`` holds six other rows to that end.  ``ROWS`` is
what a ``benchmark`` PR appends once that line goes, beside the thirty-two
that the other ``test_*_rows.py`` count: thirty-five in all.  Until then the
tests that take a metric from its row cannot see these, so what they ask is
asked here: the form of a row, a reader under its name, every scope key a
reader reads among the names of the cell's own programs at tiny sizes, and a
number from each on a traced run of made-up events under those names.  CPU
only: nothing here is a time.

``ssm_state_update_roofline`` is ``ssm_update_roofline``'s metric for a model
only some of whose layers hold a state: that reader multiplies
``num_hidden_layers x mamba_d_ssm x mamba_d_state`` of the configuration's
file, which this family's file does not have and which would count 14 layers
where 6 hold a state.
"""

import pytest

from benchmarks.lib import cells, scopes, xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
BENCH = cells.load_benchmark()
PEAKS = device_peaks("TPU v5 lite")


def _row(name, layer, better):
    return {"name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "decode_tokens_per_s", "workloads": [CELL]}


ROWS = [
    _row("ssm_state_update_roofline", "kernels", "higher"),
    _row("attention_layer_step_share", "decoder", "lower"),
    _row("moe_shared_step_share", "decoder", "lower"),
]
UPDATE = "scope/ssm.update@decode.step"
KEYS = [(ROWS[0]["name"], UPDATE)] + [
    (row["name"], key) for row in ROWS[1:]
    for key in cells.load_reader(cells.BENCH_DIR, row["name"]).spec["keys"]]


@pytest.mark.parametrize("row", ROWS, ids=lambda r: r["name"])
def test_a_row_moves_a_metric_the_cell_reports(row):
    assert NAME.match(row["name"]) and LAYER.match(row["layer"])
    assert row["layer"] in PERF_LAYERS
    taken = {m["name"] for key in ("end_to_end", "per_layer")
             for m in BENCH[key]}
    assert row["name"] not in taken
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == row["moves"])
    assert CELL in moved["workloads"]
    assert callable(cells.load_reader(cells.BENCH_DIR, row["name"]).read)


def test_the_cell_is_not_listed_where_a_reader_would_count_it_wrong():
    """``ssm_update_roofline`` counts every layer of the file as one that
    holds a state, and ``grouped_matmul_roofline`` three kernel calls a
    routed layer and one prefill pass a job."""
    for name in ("ssm_update_roofline", "grouped_matmul_roofline"):
        row = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in row["workloads"]
    config = cells.resolve(CELL).config
    assert "mamba_d_ssm" not in config and "mamba_d_state" not in config
    for name in ("ssm_step_share", "prefill_ssm_ms", "moe_experts_share",
                 "moe_routing_share", "prefill_moe_ms",
                 "decode_attention_share"):
        row = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in row["workloads"]


@pytest.mark.parametrize("name,key", KEYS, ids=lambda x: x)
def test_every_key_a_reader_reads_is_in_the_cells_programs(name, key):
    table = {k: 1.0 for k in test_scopes.cell_table(CELL)}
    assert scopes.seconds(table, [key]), sorted(table)


def test_the_cells_programs_keep_the_names_the_other_cells_have():
    """One name means one thing in every cell: the mixers under cell 5's
    scopes, the routed layers under the routed cells', attention under the
    dense cells'; nothing of the hybrid block's second norm."""
    table = test_scopes.cell_table(CELL)
    for name in ("ssm_proj", "ssm.conv", "moe.route", "moe.dispatch",
                 "moe.experts", "moe.combine", "moe.shared", "attention",
                 "attn_proj", "ffn"):
        for at in ("prefill", "decode.step"):
            assert f"scope/{name}@{at}" in table, (name, at)
    assert "scope/ssm.scan@prefill" in table
    assert "scope/ssm.update@decode.step" in table
    assert "scope/kv_cache@decode.step" in table
    for at in ("prefill", "decode.step"):
        routed = set().union(*(table[f"scope/moe.{part}@{at}"] for part in (
            "route", "dispatch", "experts", "combine", "shared")))
        assert routed <= table[f"scope/ffn@{at}"]
        mixers = set().union(*(table[f"scope/{name}@{at}"] for name in (
            "ssm_proj", "ssm.conv", "attention", "attn_proj")))
        assert not mixers & table[f"scope/ffn@{at}"]
    assert not [key for key in table if ".second" in key]


def _run() -> RunData:
    """A traced window of one sample: a ``first`` job (one run of the
    prefill's program) and a ``full`` job (that run again and one of the
    generating program), each program's operations one a name of the cell's
    own tiny programs under the keys the readers read, a millisecond each."""
    cell = cells.resolve(CELL)
    table = test_scopes.cell_table(CELL)
    events, at = [], 0.0

    def program_run(root):
        nonlocal at
        names = sorted({min(table[key]) for _name, key in KEYS
                        if key.endswith("@" + root)})
        events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                            "jit_decode(1)", at, 1e6 * (len(names) + 2)))
        for i, name in enumerate(names):
            events.append(Event("/device:TPU:0", xplane.OPS_LINE,
                                f"fusion.{i}", at + 1e6 * (i + 1), 1e6, name))
        at += 1e6 * (len(names) + 3)

    for span, roots in (("first", ["prefill"]),
                        ("full", ["prefill", "decode.step"])):
        start = at
        for root in roots:
            program_run(root)
        events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                            start, at - start))
    facts = {key: cell.traffic[key] for key in ("batch", "prompt_len",
                                                "max_new")}
    return RunData(durations={}, facts=facts, peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_the_readers_give_a_number_on_a_traced_run():
    run = _run()
    window_ms = 1e3 * run.trace.window_s
    got = {row["name"]: cells.load_reader(cells.BENCH_DIR,
                                          row["name"]).read(run)
           for row in ROWS}
    # one operation of a millisecond a key
    assert got["attention_layer_step_share"] == pytest.approx(
        300 / window_ms)
    assert got["moe_shared_step_share"] == pytest.approx(100 / window_ms)
    # 127 steps' updates of 256 sequences' six float32 states of 64 x 64 x
    # 128, read once and written once, over the millisecond under the scope
    elements = 256 * 6 * 64 * 64 * 128
    least = 127 * 2 * 4 * elements / PEAKS["hbm_bytes_per_s"]
    assert 5 * elements / PEAKS["bf16_flops"] < 8 * elements / PEAKS[
        "hbm_bytes_per_s"]
    assert got["ssm_state_update_roofline"] == pytest.approx(
        100 * least / 1e-3)


def test_the_roofline_counts_the_layers_that_hold_a_state():
    """Six of fourteen, from the reference: at 7.87 ms a step's updates (the
    least a v5e could take) the reading is 100%, where a count over every
    layer of the file would read 233%."""
    metric = cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                               "ssm_update_roofline.py")
    least = metric.least_seconds(256, 6, 4096, 128, 4, PEAKS)
    assert least == 2 * 4 * 256 * 6 * 4096 * 128 / PEAKS["hbm_bytes_per_s"]
    assert 7.8e-3 < least < 7.9e-3
    assert metric.least_seconds(256, 14, 4096, 128, 4, PEAKS) / least == (
        pytest.approx(14 / 6))


def test_a_run_with_nothing_to_read_reads_as_nothing(capsys):
    run = _run()
    run.scopes = {k: v for k, v in run.scopes.items()
                  if "ssm.update" not in k and "moe.shared" not in k
                  and "att" not in k and "kv_cache" not in k}
    run.events = [e for e in run.events if "ssm.update" not in (e.scope or "")]
    for row in ROWS:
        assert cells.load_reader(cells.BENCH_DIR,
                                 row["name"]).read(run) is None
    said = capsys.readouterr().err
    assert all(row["name"] in said for row in ROWS)
    # a configuration whose reference names no such state: nothing, and
    # nothing raised (the parent's checkout has no ``nemotron_h.py`` at all,
    # and no cell of it resolves there)
    run = _run()
    run.config = cells.resolve("olmoe-1b-7b.decode-1k-128").config
    reader = cells.load_reader(cells.BENCH_DIR, "ssm_state_update_roofline")
    assert reader.read(run) is None
    run.peaks = None
    assert reader.read(run) is None
