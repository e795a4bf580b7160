"""The seven per-layer rows that PR 77's cell brings, rows of
``BENCHMARK.json`` as ``ROWS`` has them: three shares of a cached step, two
times of the prefill, and two rooflines whose readers are this PR's
(``metrics/shared_kv_read_roofline.py``, ``selective_scan_roofline.py``).
Their keys are in the cell's programs, no other decode cell of
``BENCHMARK.json`` has time under the three new names, and each reader reads
a number from the cell's made-up trace below.  CPU only: nothing here is a
time.
"""

import pytest

from benchmarks.lib import cells, scopes, xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event
from tests.benchmarks import per_layer_rows, test_scopes

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
BENCH = cells.load_benchmark()
PEAKS = device_peaks("TPU v5 lite")


def _row(name, unit, layer, moves):
    return {"name": name, "unit": unit,
            "better": "higher" if name.endswith("roofline") else "lower",
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("shared_kv_step_share", "%", "decoder", "decode_tokens_per_s"),
    _row("window_step_share", "%", "decoder", "decode_tokens_per_s"),
    _row("gmu_step_share", "%", "decoder", "decode_tokens_per_s"),
    _row("prefill_window_ms", "ms", "decoder", "ttft_ms"),
    _row("prefill_upper_rows_ms", "ms", "decoder", "ttft_ms"),
    _row("shared_kv_read_roofline", "%", "kernels", "decode_tokens_per_s"),
    _row("selective_scan_roofline", "%", "kernels", "ttft_ms"),
]
DATA = [row["name"] for row in ROWS[:5]]
SHARED = "scope/attention.shared@decode.step"
SCAN = "scope/ssm.scan@prefill"
KEYS = [(name, key) for name in DATA
        for key in cells.load_reader(cells.BENCH_DIR, name).spec["keys"]] + [
    ("shared_kv_read_roofline", SHARED), ("selective_scan_roofline", SCAN)]
# the names this PR added to the vocabulary: no other cell's programs have them
OWN = [key for _name, key in KEYS
       if any(word in key for word in ("attention.shared",
                                       "attention.window", "gmu"))]
# rows of other cells' that list this cell too
STANDS_ON = ("ssm_step_share", "prefill_ssm_ms")
# its keys have time in this cell too (the 17 attending rows run under them),
# and the row stays its two cells': ``test_nemotron_h_rows.py::ROWS`` holds
# its ``workloads`` at those two, and that file is the benchmark's
STAYS_TWO_CELLS = "attention_layer_step_share"


@pytest.mark.parametrize("row", ROWS, ids=lambda r: r["name"])
def test_a_row_moves_a_metric_the_cell_reports(row):
    per_layer_rows.held(row, BENCH)


def test_the_cell_is_listed_where_its_scopes_are_read_and_not_where_counts_are_another_mixers():
    on = {m["name"] for m in BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert set(STANDS_ON) <= on and {r["name"] for r in ROWS} <= on
    assert STAYS_TWO_CELLS not in on
    # their counts are Mamba-2's: heads, head widths and groups
    for name in ("ssm_update_roofline", "ssm_state_update_roofline",
                 "ssm_scan_roofline"):
        assert name not in on
    two = "pythia-1.4b-widths.decode-1k-128"
    every = {m["name"] for m in BENCH["per_layer"]
             if set(m.get("workloads", ())) >= {
                 two, "deepseek-v3.2-exp.decode-16k-512-b8"}}
    assert every <= on and len(every) >= 22


@pytest.mark.parametrize("name,key", KEYS, ids=lambda x: x)
def test_every_key_a_reader_reads_is_in_the_cells_programs(name, key):
    table = {k: 1.0 for k in test_scopes.cell_table(CELL)}
    assert scopes.seconds(table, [key]), sorted(table)


def test_no_other_decode_cell_has_anything_under_the_cells_own_keys():
    assert len(KEYS) == 8 and len(OWN) == 7
    per_layer_rows.no_other_cell_reads(
        [(name, key) for name, key in KEYS if key in OWN], ROWS,
        test_scopes.DECODE, test_scopes.cell_table)


def test_the_cells_programs_keep_the_names_the_other_cells_have():
    """One name means one thing in every cell: the Mamba-1 mixer under the
    state-space cells' scopes, the attending layers under ``attn_proj``,
    ``attention`` and ``kv_cache``, the window's and the shared read's own
    names inside ``attention``."""
    from ompi_tpu.core.scopes import SCOPES

    table = test_scopes.cell_table(CELL)
    for name in ("ssm_proj", "ssm.conv", "attention", "attn_proj", "ffn",
                 "attention.window", "attention.shared", "gmu"):
        for at in ("prefill", "decode.step"):
            assert f"scope/{name}@{at}" in table, (name, at)
    assert SCAN in table and "scope/ssm.update@decode.step" in table
    assert "scope/ssm.scan@decode.step" not in table
    assert "scope/kv_cache@decode.step" in table
    named = {key.partition("/")[2].partition("@")[0] for key in table
             if key.startswith("scope/")}
    assert named <= set(SCOPES), named - set(SCOPES)
    for at in ("prefill", "decode.step"):
        inside = (table[f"scope/attention.window@{at}"]
                  | table[f"scope/attention.shared@{at}"])
        assert inside <= table[f"scope/attention@{at}"]
        assert not table[f"scope/gmu@{at}"] & table[f"scope/ffn@{at}"]
        assert not inside & table[f"scope/ssm_proj@{at}"]


def _run(workload=CELL) -> RunData:
    """A traced window of one sample: a ``first`` job (one run of the
    prefill's program) and a ``full`` job (that run again and one of the
    generating program), each program's operations one a name of the cell's
    own tiny programs under the keys the readers read, a millisecond each."""
    cell = cells.resolve(workload)
    table = test_scopes.cell_table(workload)
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


def test_the_data_files_readers_give_a_number():
    run = _run()
    window_ms = 1e3 * run.trace.window_s
    got = {name: cells.load_reader(cells.BENCH_DIR, name).read(run)
           for name in DATA}
    # one operation of a millisecond a key
    for name in DATA[:3]:
        assert got[name] == pytest.approx(100 / window_ms), name
    assert got["prefill_window_ms"] == pytest.approx(1.0)
    assert got["prefill_upper_rows_ms"] == pytest.approx(2.0)


def test_the_two_rooflines_readers_give_a_number():
    """Made-up events of a millisecond: over 100% here, which no chip's trace
    may read."""
    run = _run()
    shared = cells.load_reader(cells.BENCH_DIR, "shared_kv_read_roofline")
    least = 255 * 7 * 16 * (16_128 + 128) * 2560 * 2 / PEAKS[
        "hbm_bytes_per_s"]
    assert shared.read(run) == pytest.approx(100 * least / 1e-3)
    scan = cells.load_reader(cells.BENCH_DIR, "selective_scan_roofline")
    least = 16 * 16_128 * 9 * (3 * 5120 + 32) * 4 / PEAKS["hbm_bytes_per_s"]
    assert scan.read(run) == pytest.approx(100 * least / 1e-3)


@pytest.mark.parametrize("name,word", [
    ("shared_kv_read_roofline", "attention.shared"),
    ("selective_scan_roofline", "ssm.scan")])
def test_a_run_with_nothing_to_read_reads_as_nothing(name, word, capsys):
    """As on the parent commit, whose programs have no such scope, and in a
    cell whose reference names no such state: nothing, and nothing raised."""
    reader = cells.load_reader(cells.BENCH_DIR, name)
    run = _run()
    run.scopes = {k: v for k, v in run.scopes.items() if word not in k}
    run.events = [e for e in run.events if word not in (e.scope or "")]
    assert reader.read(run) is None
    assert name in capsys.readouterr().err
    run = _run()
    run.config = cells.resolve("granite-4.0-h-small.decode-512-128-b160"
                               ).config
    assert reader.read(run) is None
    run = _run()
    run.peaks = None
    assert reader.read(run) is None
    run.scopes = None
    assert reader.read(run) is None
