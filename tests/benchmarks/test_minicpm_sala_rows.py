"""The six per-layer rows that ``BENCHMARK.json`` cannot take yet.

``benchmarks/metrics/`` holds a reader for each of them, and no row names
them: a program PR may add rows at the end of ``per_layer`` alone, and
``test_startup_metrics.py`` holds six other rows to that end.  ``ROWS`` is
what a ``benchmark`` PR appends once that line goes, beside the five of
``test_keye_vl2_rows.py``, the four of ``test_kimi_linear_rows.py`` and the
three of ``test_brumby_rows.py``: eighteen in all.  Until then the tests that
take a metric from its row cannot see these, so what they ask is asked here:
the form of a row, a reader under its name, and every scope key a reader
reads among the names of the cell's own programs at tiny sizes (at prompts
long enough for the selection to bind, which ``test_scopes.py``'s 16
positions are not).  CPU only: nothing here is a time.
"""

import jax
import pytest

from benchmarks.lib import cells, program, scopes
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "minicpm-sala.decode-16k-512-b24"
BENCH = cells.load_benchmark()
TRAFFIC = {"batch": 2, "prompt_len": 64, "max_new": 8}


def _row(name, unit, better, layer, moves):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("lightning_step_share", "%", "lower", "decoder",
         "decode_tokens_per_s"),
    _row("prefill_lightning_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("block_select_share", "%", "lower", "decoder",
         "decode_tokens_per_s"),
    _row("prefill_block_select_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("lightning_update_roofline", "%", "higher", "kernels",
         "decode_tokens_per_s"),
    _row("block_attention_roofline", "%", "higher", "kernels",
         "decode_tokens_per_s"),
]
KEYS = [(row["name"], key) for row in ROWS
        for key in (getattr(cells.load_reader(cells.BENCH_DIR, row["name"]),
                            "spec", {}).get("keys")
                    or cells.load_reader(cells.BENCH_DIR, row["name"]).KEYS)]
# a step of this program streams its rows under the mask and gathers none
# (``models/block_select.py``): the key is a reader's for the day one does
UNUSED = "scope/attention.gather@decode.step"
_table: dict = {}


def cell_table() -> dict:
    """``test_scopes.cell_table`` of the cell at prompts of 64: the prefill's
    last slice ends past ``topk`` blocks and the steps' cache past
    ``dense_len``."""
    if not _table:
        cell = cells.resolve(CELL)
        job = cell.runner.build(program.tiny(cell.config),
                                {**cell.traffic, **TRAFFIC},
                                jax.devices()[:1])
        every, collective = set(), set()
        for fn, args in job.programs().values():
            one, two = test_scopes.names_of(fn.lower(*args).compile())
            every |= one
            collective |= two
        _table.update(test_scopes.table_of(every, collective))
    return _table


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
    table = {k: 1.0 for k in cell_table()}
    if key == UNUSED:
        assert key not in table
    else:
        assert scopes.seconds(table, [key]), sorted(table)


def test_the_cells_programs_carry_the_new_scopes_each_in_its_pass():
    table = cell_table()
    for name in ("lightning_proj", "blocks.pool", "blocks.score",
                 "blocks.select", "attn_proj", "attention", "kv_cache"):
        assert f"scope/{name}@prefill" in table or name == "kv_cache", name
        assert f"scope/{name}@decode.step" in table, name
    assert "scope/lightning.scan@prefill" in table
    assert "scope/lightning.update@decode.step" in table
    assert "scope/lightning.scan@decode.step" not in table
    assert "scope/lightning.update@prefill" not in table
    # the state's write is inside the update; the rows' and the pooled
    # key's are directly under kv_cache (one name: a table is of names)
    assert any(n.endswith("kv_cache/dynamic_update_slice")
               for n in table["scope/kv_cache@decode.step"])
    assert any(n.endswith("dot_general")
               for n in table["scope/lightning_proj@decode.step"])


def test_no_other_decode_cell_has_anything_under_the_cells_own_keys():
    own = sorted({key for _name, key in KEYS
                  if "lightning" in key or "blocks." in key})
    assert len(KEYS) == 16 and len(own) == 10
    for workload in test_scopes.DECODE:
        if workload != CELL:
            table = {k: 1.0 for k in test_scopes.cell_table(workload)}
            assert not scopes.seconds(table, own), workload
