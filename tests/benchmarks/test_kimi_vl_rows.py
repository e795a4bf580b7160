"""The four per-layer rows that ``BENCHMARK.json`` cannot take yet.

``benchmarks/metrics/`` holds a reader for each of them, and no row names
them: a program PR may add rows at the end of ``per_layer`` alone, and
``test_startup_metrics.py`` holds six other rows to that end.  ``ROWS`` is
what a ``benchmark`` PR appends once that line goes, beside the five of
``test_keye_vl2_rows.py``, the four of ``test_kimi_linear_rows.py``, the three
of ``test_brumby_rows.py``, the six of ``test_minicpm_sala_rows.py`` and the
five of ``test_startup_split_rows.py``: twenty-seven in all.  Until then the
tests that take a metric from its row cannot see these, so what they ask is
asked here: the form of a row, a reader under its name, and every scope key a
reader reads among the names of the cell's own programs at tiny sizes.

ISSUE 56 asked that the accepted ``latent_step_share`` (a waiting row of
``test_kimi_linear_rows.py``) read this cell as it is.  It cannot, whole:
that file holds that no other decode cell has time under ``mla_proj``, and a
program PR may not edit it.  So the rotary form's projections have a scope of
their own, ``mla_proj.rope``, ``latent_step_share`` reads this cell's scores
and cache write without its projections (a hundredth of them on the chip),
and ``latent_rope_step_share`` is the same sum with this form's name in it.
CPU only: nothing here is a time.
"""

import pytest

from benchmarks.lib import cells, scopes
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "kimi-vl-a3b.decode-16k-256-b32"
BENCH = cells.load_benchmark()


def _row(name, unit, better, layer, moves):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("prefill_latent_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("latent_rope_step_share", "%", "lower", "decoder",
         "decode_tokens_per_s"),
    _row("latent_read_roofline", "%", "higher", "kernels",
         "decode_tokens_per_s"),
    _row("latent_prefill_roofline", "%", "higher", "kernels", "ttft_ms"),
]
KEYS = [(row["name"], key) for row in ROWS
        for key in (getattr(cells.load_reader(cells.BENCH_DIR, row["name"]),
                            "spec", {}).get("keys")
                    or cells.load_reader(cells.BENCH_DIR, row["name"]).KEYS)]
# the scores and the cache's write go under the names every decode cell's
# attention has: what is this cell's own is the rest
OWN = sorted({key for _name, key in KEYS
              if not key.startswith(("scope/attention@", "scope/kv_cache@"))})


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


def test_the_cells_programs_carry_the_new_scopes_in_both_passes():
    table = test_scopes.cell_table(CELL)
    for name in ("attn_proj", "mla_proj.rope", "mla.rotate", "attention",
                 "moe.route", "moe.experts", "moe.shared", "ffn"):
        for at in ("prefill", "decode.step"):
            assert f"scope/{name}@{at}" in table, (name, at, sorted(table))
    assert "scope/kv_cache@decode.step" in table
    # the rotation lies inside the projections' scope, that inside attn_proj
    assert (set(table["scope/mla.rotate@decode.step"])
            <= set(table["scope/mla_proj.rope@decode.step"])
            <= set(table["scope/attn_proj@decode.step"]))
    assert not any(key.startswith("scope/mla_proj@") for key in table)


def test_the_accepted_latent_share_reads_the_cell_without_its_projections():
    accepted = cells.load_reader(cells.BENCH_DIR, "latent_step_share")
    own = cells.load_reader(cells.BENCH_DIR, "latent_rope_step_share")
    assert accepted.spec["reader"] == own.spec["reader"] == "scope_share"
    assert [k.replace("mla_proj@", "mla_proj.rope@")
            for k in accepted.spec["keys"]] == own.spec["keys"]
    table = {k: 1.0 for k in test_scopes.cell_table(CELL)}
    assert scopes.seconds(table, accepted.spec["keys"]) == 2.0
    assert scopes.seconds(table, own.spec["keys"]) == 3.0


def test_no_other_decode_cell_has_anything_under_the_cells_own_keys():
    assert len(KEYS) == 7 and OWN == ["scope/mla_proj.rope@decode.step",
                                      "scope/mla_proj.rope@prefill"]
    for workload in test_scopes.DECODE:
        if workload != CELL:
            table = {k: 1.0 for k in test_scopes.cell_table(workload)}
            assert not scopes.seconds(table, OWN), workload
