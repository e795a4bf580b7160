"""The three per-layer rows that ``BENCHMARK.json`` cannot take yet.

``benchmarks/metrics/`` holds a data file for each of them, read by the
shared readers, and no row names them: a program PR may add rows at the end
of ``per_layer`` alone, and ``test_startup_metrics.py`` holds six other rows
to that end.  ``ROWS`` is what a ``benchmark`` PR appends once that line goes,
beside the twenty-seven that ``test_kimi_vl_rows.py`` counts: thirty in all.
Until then the tests that take a metric from its row cannot see these, so
what they ask is asked here: the form of a row, a reader under its name,
every scope key a reader reads among the names of the cell's own programs at
tiny sizes, and a number from each on a traced run of made-up events under
those names.  CPU only: nothing here is a time.

On the chip ``moe_zero_step_share`` read nothing (my chip runs, PR 58): XLA
fuses the identity picks' add into ``moe.combine``'s sum, so no event of a
cached step carries ``moe.zero``; a ``benchmark`` PR appends it only with a
reader of the fused event, or leaves it out (``PERF.md`` section 7).
"""

import pytest

from benchmarks.lib import cells, scopes, xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "longcat-flash-chat.decode-896-128-b160"
BENCH = cells.load_benchmark()


def _row(name, unit, moves):
    return {"name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "decoder", "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("moe_zero_step_share", "%", "decode_tokens_per_s"),
    _row("prefill_moe_zero_ms", "ms", "ttft_ms"),
    _row("ffn_dense_step_share", "%", "decode_tokens_per_s"),
]
KEYS = [(row["name"], key) for row in ROWS
        for key in cells.load_reader(cells.BENCH_DIR,
                                     row["name"]).spec["keys"]]
SECOND = ("attn_proj.second", "attention.second", "ffn.second")


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


def test_the_cells_programs_tell_a_layers_second_half_from_its_first():
    table = test_scopes.cell_table(CELL)
    for name in ("attn_proj", "mla_proj.query_latent", "mla.rotate",
                 "attention", "ffn", "moe.route", "moe.dispatch",
                 "moe.experts", "moe.combine", "moe.zero", *SECOND):
        for at in ("prefill", "decode.step"):
            assert f"scope/{name}@{at}" in table, (name, at, sorted(table))
    for at in ("prefill", "decode.step"):
        # a second half's scope lies inside the name without the suffix,
        # which holds both halves; the branch is the first half's
        for name in SECOND:
            whole = table[f"scope/{name.rpartition('.')[0]}@{at}"]
            assert table[f"scope/{name}@{at}"] < whole
            assert all(scopes.classify(n).chain[2:4]
                       == (name.rpartition(".")[0], name)
                       for n in table[f"scope/{name}@{at}"])
        routed = set().union(*(table[f"scope/moe.{part}@{at}"] for part in (
            "route", "dispatch", "experts", "combine", "zero")))
        assert routed <= table[f"scope/ffn@{at}"]
        assert not routed & table[f"scope/ffn.second@{at}"]
        # the dense MLPs' own: under ffn, in no moe.* scope
        assert not routed & (table[f"self/ffn@{at}"]
                             | table[f"self/ffn.second@{at}"])
    # the cache's write has no second name (test_scopes.py holds its chain)
    assert not any(key.startswith("scope/kv_cache.") for key in table)


def test_the_cell_has_nothing_under_the_other_latent_forms_names():
    """``test_kimi_vl_rows.py`` holds ``mla_proj.rope`` to its cell alone, and
    ``test_kimi_linear_rows.py`` ``mla_proj`` to its own."""
    assert not [key for key in test_scopes.cell_table(CELL)
                if "mla_proj.rope" in key
                or key.startswith("scope/mla_proj@")]


def _run() -> RunData:
    """A traced window of one sample: a ``first`` job (one run of the
    prefill's program) and a ``full`` job (that run again and one of the
    generating program), each program's operations one a name of the cell's
    own tiny programs under the keys the three readers read, a millisecond
    each."""
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
    return RunData(durations={}, facts={}, peaks=device_peaks("TPU v5 lite"),
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_the_three_readers_give_a_number_on_a_traced_run():
    run = _run()
    window_ms = 1e3 * run.trace.window_s
    got = {row["name"]: cells.load_reader(cells.BENCH_DIR,
                                          row["name"]).read(run)
           for row in ROWS}
    # one operation of a millisecond a key
    assert got["moe_zero_step_share"] == pytest.approx(100 / window_ms)
    assert got["ffn_dense_step_share"] == pytest.approx(200 / window_ms)
    assert got["prefill_moe_zero_ms"] == pytest.approx(1.0)


def test_a_run_with_nothing_to_read_reads_as_nothing(capsys):
    run = _run()
    run.scopes = {k: v for k, v in run.scopes.items()
                  if "moe.zero" not in k and "ffn" not in k}
    run.events = [e for e in run.events if "moe.zero" not in (e.scope or "")]
    for row in ROWS[::2]:
        assert cells.load_reader(cells.BENCH_DIR,
                                 row["name"]).read(run) is None
    assert "moe_zero_step_share" in capsys.readouterr().err
