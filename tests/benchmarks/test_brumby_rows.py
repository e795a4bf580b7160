"""The three per-layer rows that ``BENCHMARK.json`` cannot take yet.

``benchmarks/metrics/`` holds a reader for each of them, and no row names
them: a program PR may add rows at the end of ``per_layer`` alone, and
``test_startup_metrics.py`` holds six other rows to that end.  ``ROWS`` is
what a ``benchmark`` PR appends once that line goes, beside the five of
``test_keye_vl2_rows.py`` and the four of ``test_kimi_linear_rows.py``.
Until then the tests that take a metric from its row cannot see these, so
what they ask is asked here: the form of a row, a reader under its name, and
every scope key a reader reads among the names of the cell's own programs at
tiny sizes.  CPU only: nothing here is a time.
"""

import pytest

from benchmarks.lib import cells, scopes
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "brumby-14b-base.decode-2k-128-b48"
BENCH = cells.load_benchmark()


def _row(name, unit, better, layer, moves):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("retention_step_share", "%", "lower", "decoder",
         "decode_tokens_per_s"),
    _row("prefill_retention_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("retention_update_roofline", "%", "higher", "kernels",
         "decode_tokens_per_s"),
]
KEYS = [(row["name"], key) for row in ROWS
        for key in (getattr(cells.load_reader(cells.BENCH_DIR, row["name"]),
                            "spec", {}).get("keys")
                    or cells.load_reader(cells.BENCH_DIR, row["name"]).KEYS)]


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


@pytest.mark.parametrize("name,key", KEYS, ids=lambda x: x)
def test_every_key_a_reader_reads_is_in_the_cells_programs(name, key):
    table = {k: 1.0 for k in test_scopes.cell_table(CELL)}
    assert scopes.seconds(table, [key]), sorted(table)


def test_the_cells_programs_carry_both_new_scopes_each_in_its_pass():
    table = test_scopes.cell_table(CELL)
    assert "scope/retention.scan@prefill" in table
    assert "scope/retention.update@decode.step" in table
    assert "scope/retention.scan@decode.step" not in table
    assert "scope/retention.update@prefill" not in table
    # both lie inside the core, under the name every decode cell's has
    for key in ("scope/retention.scan", "scope/retention.update"):
        assert all("attention" in scopes.classify(n).chain
                   for n in table[key])
    # the gate's projection is with the other projections
    assert any(n.endswith("log_sigmoid") or "logsigmoid" in n or "log" in n
               for n in table["scope/attn_proj@decode.step"])


def test_no_other_decode_cell_has_anything_under_the_cells_own_keys():
    assert len(KEYS) == 3
    own = [key for _name, key in KEYS]
    for workload in test_scopes.DECODE:
        if workload != CELL:
            table = {k: 1.0 for k in test_scopes.cell_table(workload)}
            assert not scopes.seconds(table, own), workload
