"""The three readers of the run half of the program's own host record (PR
70), rows of ``BENCHMARK.json`` as ``ROWS`` has them, behind PR 69's last.
``metrics/startup_first_call_rest_s.py``, ``decode_call_host_ms.py`` and
``train_call_host_ms.py`` read ``ompi_tpu/core/scopes.run()`` from the
process's memory, as the ``startup_*`` readers read ``startup()``.  Beside
what the harness's tests ask of every row, asked here: the benchmark's row of
that name equal to this one and the three the file's last, in this order;
every reader's arithmetic on a made-up ``run()``; nothing from a program
without the run half (the parent's, which the driver lays these files over).
CPU only: nothing here is a time.
"""

import pytest

from benchmarks.lib import cells
from benchmarks.lib.rundata import RunData
from ompi_tpu.core import scopes
from tests.benchmarks import per_layer_rows

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
TRAIN = [w for w in WORKLOADS if ".train-" in w]
DECODE = [w for w in WORKLOADS if ".decode-" in w]


def _row(name, unit, source, layer, moves, workloads):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves, "workloads": workloads}


ROWS = [
    _row("startup_first_call_rest_s", "s", "program_span", "startup",
         "setup_s", WORKLOADS),
    _row("decode_call_host_ms", "ms", "program_span", "decoder", "ttft_ms",
         DECODE),
    _row("train_call_host_ms", "ms", "program_span", "trainer",
         "train_tokens_per_s", TRAIN),
]
NAMES = [row["name"] for row in ROWS]


def _read(metric: str):
    run = RunData(durations={}, facts={}, peaks=None, trace=None,
                  compiles_in_window=0, peak_bytes=None)
    return cells.load_reader(cells.BENCH_DIR, metric).read(run)


def test_the_cells_are_two_train_cells_and_twelve_decode_cells():
    assert (len(TRAIN), len(DECODE), len(WORKLOADS)) == (2, 12, 14)


@pytest.mark.parametrize("row", ROWS, ids=lambda r: r["name"])
def test_a_row_is_the_benchmarks_row_of_its_name(row):
    per_layer_rows.held(row, BENCH)
    assert row["source"] in ("program_span", "program_counter")


def test_the_three_rows_are_appended_behind_pr_69s_last_in_order():
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == NAMES
    assert BENCH["per_layer"][-4]["name"] == "masked_latent_attention_roofline"


def _callable(program, made, median_s):
    return {"program": program, "made": made, "calls": 5, "host_s": 9.0,
            "compiled": 1, "quiet": 3, "median_s": median_s}


def _object(program, part, built, rest):
    return {"program": program, "part": part, "built": built,
            "dispatches": 9, "compiles": 1, "recompiled": 0, "first_s": 4.0,
            "first_rest_s": rest, "recompiles": []}


MADE_UP = {
    # the decoder made first is the one read: the second's calls may hold
    # the host for a whole prefill
    "callables": [_callable("decode", 0, 0.0025),
                  _callable("decode", 1, 6.5),
                  _callable("train_step", 2, 0.00125)],
    "programs": [_object("decode", "prefill", 0, 0.25),
                 _object("decode", "generate", 1, 0.125),
                 _object("train_step", None, 2, 0.5),
                 # an object that was built and never called
                 _object("decode", "whole", 3, None)],
    "gc": {"gen0": {"passes": 7, "seconds": 0.001},
           "gen1": {"passes": 1, "seconds": 0.002},
           "gen2": {"passes": 1, "seconds": 0.0625},
           "longest_s": 0.0625, "recorded": 2, "in_calls_s": 0.0625},
    "jobs": [], "records": 40, "wrapped": 0,
}
WANT = {"startup_first_call_rest_s": 0.875, "decode_call_host_ms": 2.5,
        "train_call_host_ms": 1.25}


@pytest.mark.parametrize("metric", NAMES)
def test_a_readers_arithmetic_on_a_made_up_run(metric, monkeypatch):
    monkeypatch.setattr(scopes, "run", lambda: MADE_UP)
    assert _read(metric) == WANT[metric]


@pytest.mark.parametrize("metric", NAMES)
def test_a_program_without_the_run_half_reads_nothing(metric, monkeypatch):
    """Laid over the parent's checkout: ``scopes`` has no ``run``."""
    monkeypatch.delattr(scopes, "run")
    assert _read(metric) is None


def test_a_process_that_ran_no_job_reads_nothing(monkeypatch):
    empty = {**MADE_UP, "callables": [], "programs": []}
    monkeypatch.setattr(scopes, "run", lambda: empty)
    assert [_read(name) for name in NAMES] == [None] * 3


def test_a_callable_whose_calls_all_compiled_is_passed_over(monkeypatch):
    """A process may have built decoders before the job's (a test's worker
    has): the first with a call in which nothing compiled is read."""
    only = {**MADE_UP, "callables": [_callable("decode", 0, None),
                                     _callable("decode", 1, 0.5),
                                     _callable("decode", 2, 6.5)]}
    monkeypatch.setattr(scopes, "run", lambda: only)
    assert _read("decode_call_host_ms") == 500.0
    only["callables"] = [_callable("decode", 0, None)]
    assert _read("decode_call_host_ms") is None


def test_the_readers_read_the_record_a_real_call_leaves():
    """One program of the package's form, called twice: the whole way from
    the factory's handle through ``run()`` to the rows."""
    import jax
    import jax.numpy as jnp

    scopes.reset()
    try:
        record = scopes.program("train_step")

        @jax.jit
        def train_step(x):
            record.traced()
            return jnp.tanh(x) @ x

        step = scopes.ran(train_step, record)
        x = jnp.ones((8, 8), jnp.float32)
        for _ in range(3):
            jax.block_until_ready(step(x))
        assert _read("train_call_host_ms") > 0
        assert _read("startup_first_call_rest_s") >= 0
        assert _read("decode_call_host_ms") is None     # no decoder ran
    finally:
        scopes.reset()
