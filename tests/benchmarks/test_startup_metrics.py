"""The six readers of the program's own host record (``metrics/startup_*.py``
over ``ompi_tpu/core/scopes.startup()``, ``metrics/data_take_wait_ms.py``
over the stream's ``stats()``): a number on a tiny CPU run of each of their
cells, the arithmetic on a made-up record, and nothing, without raising,
where the program has no such record (the parent of the PR that added them)
or the job no stream.  CPU only: no reading here is a device metric.
"""

import time

import jax
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import cells, program
from benchmarks.lib.compile_meter import CompileMeter
from benchmarks.lib.rundata import RunData
from benchmarks.lib.spans import Spans
from ompi_tpu.core import scopes
from ompi_tpu.core.scopes import Span

BENCH = cells.load_benchmark()
STARTUP = ["startup_build_s", "startup_trace_lower_s", "startup_backend_s",
           "startup_other_programs_s", "startup_retraces"]
TAKE = "data_take_wait_ms"
ROWS = {m["name"]: m for m in BENCH["per_layer"]
        if m["name"] in STARTUP + [TAKE]}
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
TINY_TRAFFIC = {"batch": 4, "seq": 32, "prompt_len": 16, "max_new": 8}


def _reader(metric: str):
    return cells.load_module(f"{cells.BENCH_DIR}/metrics/{metric}.py")


def _run(facts=None) -> RunData:
    return RunData(durations={}, facts=facts or {}, peaks=None, trace=None,
                   compiles_in_window=0, peak_bytes=None)


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


def test_the_rows_are_the_issues():
    assert set(ROWS) == set(STARTUP) | {TAKE}
    for name in STARTUP:
        row = ROWS[name]
        assert (row["layer"], row["moves"]) == ("startup", "setup_s")
        assert row["workloads"] == WORKLOADS and row["better"] == "lower"
    assert [ROWS[n]["source"] for n in STARTUP] == [
        "program_span"] * 4 + ["program_counter"]
    take = ROWS[TAKE]
    assert (take["layer"], take["moves"]) == ("input", "train_tokens_per_s")
    assert take["workloads"] == [w for w in WORKLOADS if "train" in w]
    # appended: the last rows of the file, in the issue's order
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == STARTUP + [TAKE]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_reader_gives_a_number_on_a_tiny_run_of_its_cell(workload,
                                                               meter):
    scopes.reset()
    cell = cells.resolve(workload)
    cell.config = program.tiny(cell.config)
    cell.traffic = {k: TINY_TRAFFIC.get(k, v)
                    for k, v in cell.traffic.items()}
    line = bench_run.measure(cell, jax.devices()[:cell.chips], meter,
                             Spans(), seed=5, seconds=0.2, trace=True,
                             peaks={"bf16_flops": 1e12,
                                    "hbm_bytes_per_s": 1e11},
                             t0=time.perf_counter())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine = [name for name, row in ROWS.items()
            if workload in row["workloads"]]
    assert set(mine) <= set(got)
    assert (TAKE in got) == ("train" in workload)
    # the cell's program was built, traced, lowered and compiled here, and
    # the draws' and the checker's programs beside it
    assert got["startup_trace_lower_s"] > 0
    assert got["startup_backend_s"] > 0
    assert got["startup_other_programs_s"] > 0
    assert got["startup_build_s"] >= 0
    assert got["startup_retraces"] == 0     # a warm-up traces a program once
    own = scopes.startup()["programs"]
    assert set(own) == ({"train_step"} if "train" in workload
                        else {"decode"})
    if TAKE in got:
        # the same wait, timed inside ``__next__`` and around it
        assert 0 <= got[TAKE] <= 10 * got["data_wait_ms"] + 1.0
    scopes.reset()


def test_the_readers_arithmetic_on_a_made_up_record(monkeypatch):
    made_up = [
        Span("build.decoder", "decode", 0.0, 1.0, None, 1),
        Span("compile.trace", "zeros", 0.25, 0.5, 1, 2),
        Span("compile.trace", "decode", 2.0, 6.0, None, 3),
        Span("import.pallas", None, 3.0, 4.0, 3, 4),
        Span("compile.trace", "matmul", 4.0, 4.5, 3, 5),
        Span("compile.lower", "decode", 6.0, 6.5, None, 6),
        Span("compile.backend", "decode", 6.5, 6.75, None, 7, "hit"),
        Span("compile.backend", "zeros", 7.0, 9.0, None, 8, "miss"),
        Span("data.produce", None, 9.0, 9.5, None, 9),
    ]
    scopes.reset()
    handle = scopes.program("decode")
    for _ in range(3):
        handle.traced()
    whole = scopes.startup
    monkeypatch.setattr(scopes, "startup", lambda: whole(made_up))
    want = {"startup_build_s": 0.75 + 1.0,          # less ``zeros``' trace
            "startup_trace_lower_s": 3.0 + 0.5,     # less the import
            "startup_backend_s": 0.25,
            "startup_other_programs_s": 0.25 + 2.0,
            "startup_retraces": 2}
    for name, value in want.items():
        assert _reader(name).read(_run()) == value, name
    scopes.reset()


@pytest.mark.parametrize("metric", STARTUP)
def test_a_program_without_the_record_reads_nothing(metric, monkeypatch):
    """Laid over the parent's checkout, whose ``core/scopes.py`` has the
    device half alone, a reader returns None and the line leaves its metric
    out."""
    monkeypatch.delattr(scopes, "startup")
    assert _reader(metric).read(_run()) is None


@pytest.mark.parametrize("facts", [
    {}, {"stream": None}, {"stream": {}},
    {"stream": {"batches": 0, "starved": 0, "wait_s": 0.0}},
    {"stream": {"batches": 12, "starved": 0}},      # the parent's stats()
], ids=["no-stream", "none", "empty", "no-batch", "no-wait_s"])
def test_the_take_wait_reads_nothing_where_there_is_nothing(facts):
    assert _reader(TAKE).read(_run(facts)) is None


def test_the_take_wait_is_the_mean_wait_of_a_take_in_ms():
    facts = {"stream": {"batches": 40, "starved": 1, "wait_s": 0.002}}
    assert _reader(TAKE).read(_run(facts)) == pytest.approx(0.05)
