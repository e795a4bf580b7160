"""The five per-layer rows this cell brings that ``BENCHMARK.json`` cannot
take yet.

``benchmarks/metrics/`` holds a reader for each of them, and no row names
them: a program PR may add rows at the end of ``per_layer`` alone, and
thirty-six rows wait to that end in the other ``test_*_rows.py`` and in
``test_startup_metrics.py``.  ``ROWS`` is what a ``benchmark`` PR appends once
that line goes: forty-one in all.  Until then the tests that take a metric
from its row cannot see these, so what they ask is asked here: the form of a
row, a reader under its name, every scope key a reader reads among the names
of the cell's own programs at tiny sizes, the two rooflines' operations and
bytes against hand counts, and their reading of made-up events.

ISSUE 67 asked that the index run under the scope names cell 6's has
(``index_proj``, ``index.score``, ``index.select``) so that the waiting
``sparse_index_share`` and ``prefill_index_ms`` read this cell as it is.  It
cannot: ``test_keye_vl2_rows.py`` holds that no other decode cell has time
under those keys, and a program PR may not edit it.  So the index of a latent
layer has names of its own (``latent_index_proj``, ``latent_index.score``,
``latent_index.select``), and ``prefill_latent_index_ms`` and
``latent_select_step_share`` are the same sums under them.
CPU only: nothing here is a time.
"""

import jax
import pytest

from benchmarks.lib import cells, scopes, xplane
from benchmarks.lib.peaks import device_peaks
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import TRACE_PREFIX
from benchmarks.lib.xplane import Event
from tests.benchmarks import test_scopes
from tests.benchmarks.test_harness import LAYER, NAME, PERF_LAYERS

CELL = "deepseek-v3.2-exp.decode-16k-512-b8"
BENCH = cells.load_benchmark()
PEAKS = device_peaks("TPU v5 lite")
B, TP, NEW, L, H = 8, 15_872, 512, 5, 128


def _row(name, unit, better, layer, moves):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}


ROWS = [
    _row("latent_select_step_share", "%", "lower", "decoder",
         "decode_tokens_per_s"),
    _row("prefill_latent_index_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("prefill_selected_latent_ms", "ms", "lower", "decoder", "ttft_ms"),
    _row("selected_latent_read_roofline", "%", "higher", "kernels",
         "decode_tokens_per_s"),
    _row("masked_latent_attention_roofline", "%", "higher", "kernels",
         "ttft_ms"),
]
KEYS = [(row["name"], key) for row in ROWS
        for key in (getattr(cells.load_reader(cells.BENCH_DIR, row["name"]),
                            "spec", {}).get("keys")
                    or getattr(cells.load_reader(cells.BENCH_DIR,
                                                 row["name"]), "KEYS", ()))]
OWN = sorted({key for _name, key in KEYS})


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
    if row["name"].endswith("_roofline"):
        assert row["unit"] == "%" and row["layer"] == "kernels"


_table: dict = {}


def cell_table() -> dict:
    """``test_scopes.cell_table`` of the cell with prompts of 24 positions:
    the harness's tiny prompts (16) are within the tiny index's ``topk``
    (16), so their prefill selects nothing and has no index scope in it."""
    if not _table:
        from benchmarks.lib import program

        cell = cells.resolve(CELL)
        traffic = {**cell.traffic, **test_scopes.TINY_TRAFFIC,
                   "prompt_len": 24}
        job = cell.runner.build(program.tiny(cell.config), traffic,
                                jax.devices()[:cell.chips])
        every, collective = set(), set()
        for fn, args in job.programs().values():
            one, two = test_scopes.names_of(fn.lower(*args).compile())
            every |= one
            collective |= two
        _table.update(test_scopes.table_of(every, collective))
    return _table


@pytest.mark.parametrize("name,key", KEYS, ids=lambda x: x)
def test_every_key_a_reader_reads_is_in_the_cells_programs(name, key):
    table = {k: 1.0 for k in cell_table()}
    assert scopes.seconds(table, [key]), sorted(table)


def test_the_cells_programs_carry_the_new_scopes_where_they_belong():
    table = cell_table()
    for name in ("attn_proj", "mla_proj.query_latent", "mla.rotate",
                 "latent_index_proj", "latent_index.score",
                 "latent_index.select", "attention", "attention.selected",
                 "moe.route", "moe.groups", "moe.experts", "moe.shared",
                 "ffn"):
        for at in ("prefill", "decode.step"):
            assert f"scope/{name}@{at}" in table, (name, at, sorted(table))
    assert "scope/kv_cache@decode.step" in table
    for at in ("prefill", "decode.step"):
        # the selected read lies inside attention, the group selection
        # inside the routing, the index's rotation inside its projections;
        # the index's own work is outside attention and the projections
        assert (set(table[f"scope/attention.selected@{at}"])
                <= set(table[f"scope/attention@{at}"]))
        assert (set(table[f"scope/moe.groups@{at}"])
                <= set(table[f"scope/moe.route@{at}"]))
        for name in ("latent_index.score", "latent_index.select"):
            assert not (set(table[f"scope/{name}@{at}"])
                        & (set(table[f"scope/attention@{at}"])
                           | set(table[f"scope/attn_proj@{at}"])))
    assert not any(key.startswith(("scope/index", "scope/mla_proj@",
                                   "scope/mla_proj.rope@"))
                   for key in table)


def test_no_other_decode_cell_has_anything_under_the_cells_own_keys():
    assert len(KEYS) == 11 and len(OWN) == 8
    for workload in test_scopes.DECODE:
        if workload != CELL:
            table = {k: 1.0 for k in test_scopes.cell_table(workload)}
            assert not scopes.seconds(table, OWN), workload


def test_the_accepted_shares_read_the_cell_as_it_is():
    """The cell is on the lists of the rows whose keys its programs have."""
    table = {k: 1.0 for k in cell_table()}
    for name in ("decode_attention_share", "moe_experts_share",
                 "moe_routing_share", "prefill_moe_ms",
                 "decode_cache_move_share"):
        row = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in row["workloads"]
        reader = cells.load_reader(cells.BENCH_DIR, name)
        assert scopes.seconds(table, reader.spec["keys"]), name
    for name in ("ssm_step_share", "prefill_ssm_ms",
                 "grouped_matmul_roofline"):
        row = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in row["workloads"]


# ---- the two rooflines -----------------------------------------------------

@pytest.fixture(scope="module")
def read_metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "selected_latent_read_roofline.py")


@pytest.fixture(scope="module")
def prefill_metric():
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/"
                             "masked_latent_attention_roofline.py")


def test_the_selected_read_is_every_index_key_and_the_selected_rows(
        read_metric):
    live = TP + NEW / 2
    ops, nbytes = read_metric.costs(B, L, H, 512, 64, 64, 128, 2048, live, 2)
    assert nbytes == B * L * 2 * (live * 128 + 2048 * 576) == 259_522_560
    assert ops == B * L * (live * 64 * 2 * 128
                           + 2048 * H * 2 * (512 + 64 + 512))
    least = read_metric.least_seconds(PEAKS, B, L, H, 512, 64, 64, 128, 2048,
                                      live, 2)
    assert least == nbytes / PEAKS["hbm_bytes_per_s"]
    assert 1.5 * ops / PEAKS["bf16_flops"] < least
    assert 3.1e-4 < least < 3.2e-4
    # a cache within its selection: every live row
    _ops, short = read_metric.costs(B, L, H, 512, 64, 64, 128, 2048, 1000, 2)
    assert short == B * L * 2 * 1000 * (128 + 576)


def test_the_prefills_attention_is_the_selections_pairs(prefill_metric):
    pairs = prefill_metric.pairs(TP, 2048)
    assert pairs == 2048 * 2049 // 2 + (TP - 2048) * 2048 == 30_409_728
    assert prefill_metric.pairs(100, 2048) == 5050
    ops, nbytes = prefill_metric.costs(B, L, TP, 2048, H, 128, 64, 128, 2)
    assert ops == B * L * H * 2 * 320 * pairs
    assert nbytes == B * L * 2 * TP * (H * (2 * 256 + 64 + 128) + 64)
    least = prefill_metric.least_seconds(PEAKS, B, L, TP, 2048, H, 128, 64,
                                         128, 2)
    assert least == ops / PEAKS["bf16_flops"]
    assert 0.50 < least < 0.51 and nbytes / PEAKS["hbm_bytes_per_s"] < 0.15


def _run(step_ms: float, kernel_ms: float, jobs: int,
         kernel: str = "masked_latent_attention",
         under: str = "attention/attention.selected") -> RunData:
    """A traced window of ``jobs`` samples: a ``first`` job, one run of the
    prefill's program, and a ``full`` job, that run again and one of the
    generating program.  A prefill's kernel events are a call a layer, slice
    and sequence, ``kernel_ms`` each; a job's cached steps take ``step_ms``
    under the selected read's scope."""
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    made.n_params = 1
    events, at = [], 0

    def program_run(ops):
        nonlocal at
        events.append(Event("/device:TPU:0", xplane.MODULES_LINE,
                            "jit_decode(1)", at + 1e6, 3e6))
        start = at + 1e6
        for name, path, took_ms in ops:     # one after another
            events.append(Event("/device:TPU:0", xplane.OPS_LINE, name,
                                start, 1e6 * took_ms,
                                f"jit(decode)/shard_map/{path}/dot"))
            start += 1e6 * took_ms
        at += 4e6

    calls = L * B * 31
    for _ in range(jobs):
        for span, programs in (("first", 1), ("full", 2)):
            events.append(Event("/host:CPU", "python", TRACE_PREFIX + span,
                                at, 4e6 * programs))
            program_run([(f"%{kernel}.{i} = bf16[1,512,16384]",
                          f"prefill/layers/jit(run)/{under}", kernel_ms)
                         for i in range(calls)])
            if programs == 2:
                program_run([("fusion.9", "decode.step/while/body/closed_call"
                              f"/layers/jit(run)/{under}", step_ms)])
    return RunData(durations={}, facts=made.facts(), peaks=PEAKS,
                   trace=xplane.reduce_events(events), compiles_in_window=0,
                   peak_bytes=None, scopes=scopes.reduce_scopes(events),
                   events=events, config=cell.config, traffic=cell.traffic)


def test_readings_are_least_time_over_the_time_measured(read_metric,
                                                        prefill_metric):
    step = read_metric.least_seconds(PEAKS, B, L, H, 512, 64, 64, 128, 2048,
                                     TP + NEW / 2, 2)
    prompt = prefill_metric.least_seconds(PEAKS, B, L, TP, 2048, H, 128, 64,
                                          128, 2)
    # made-up events: the arithmetic is what is held, not a share under 100
    for jobs in (1, 2):
        run = _run(2.0, 1e-3, jobs)
        assert read_metric.read(run) == pytest.approx(
            100 * (NEW - 1) * step / 2.0e-3)
        assert prefill_metric.read(run) == pytest.approx(
            100 * prompt / (L * B * 31 * 1e-6))
    shared = cells.load_reader(cells.BENCH_DIR, "prefill_selected_latent_ms")
    assert shared.read(_run(2.0, 1e-3, 1)) == pytest.approx(
        L * B * 31 * 1e-3, rel=1e-3)


def test_a_run_with_nothing_to_read_reads_as_nothing(read_metric,
                                                     prefill_metric, capsys):
    run = _run(2.0, 1e-3, 1, kernel="fusion", under="attn_proj")
    assert read_metric.read(run) is None and prefill_metric.read(run) is None
    assert "selected_latent_read_roofline" in capsys.readouterr().err
    empty = RunData(durations={}, facts={}, peaks=PEAKS, trace=None,
                    compiles_in_window=0, peak_bytes=None)
    run = _run(2.0, 1e-3, 1)
    run.peaks = None
    other = _run(2.0, 1e-3, 1)
    other.config = cells.resolve("kimi-vl-a3b.decode-16k-256-b32").config
    for metric in (read_metric, prefill_metric):
        assert metric.read(empty) is None
        assert metric.read(run) is None
        assert metric.read(other) is None
    # kernel events that are not whole prefills are refused, not read
    ragged = _run(2.0, 1e-3, 1)
    ragged.events = [e for i, e in enumerate(ragged.events) if i % 7]
    with pytest.raises(ValueError, match="not whole prefills"):
        prefill_metric.read(ragged)
