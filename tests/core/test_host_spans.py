"""The host half of ``ompi_tpu/core/scopes.py``: ``host()``, the record, the
compile stages by program name from ``jax.monitoring``, and ``startup()``.
CPU only: what is recorded and how it adds up, never how long a chip took.
"""

import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from ompi_tpu.core import scopes
from ompi_tpu.core.scopes import Span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STAGES = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture(autouse=True)
def fresh_record():
    scopes.reset()
    yield
    scopes.reset()


def _of(program: str) -> list:
    return [s for s in scopes.records() if s.program == program]


@pytest.mark.parametrize("name", ["setup", "build", "attention", "",
                                  "ompi_tpu:data.produce"])
def test_host_refuses_a_name_outside_the_vocabulary(name):
    with pytest.raises(ValueError, match="host span vocabulary"):
        scopes.host(name)
    assert scopes.records() == []


@pytest.mark.parametrize("name", scopes.HOST_SPANS)
def test_every_name_of_the_vocabulary_records_one_span(name):
    assert re.fullmatch(r"[a-z]+\.[a-z_]+", name)
    with scopes.host(name, program="p"):
        pass
    (span,) = scopes.records()
    assert (span.name, span.program, span.parent) == (name, "p", None)
    assert span.end >= span.start


def test_records_nest_with_the_right_parent():
    with scopes.host("build.decoder", program="decode"):
        with scopes.host("import.pallas"):
            pass
        with scopes.host("build.stream"):
            with scopes.host("data.produce"):
                pass
    with scopes.host("build.train_step"):
        pass
    by_name = {s.name: s for s in scopes.records()}
    # appended as they end: the innermost first
    assert [s.name for s in scopes.records()] == [
        "import.pallas", "data.produce", "build.stream", "build.decoder",
        "build.train_step"]
    outer = by_name["build.decoder"]
    assert outer.parent is None and outer.program == "decode"
    assert by_name["import.pallas"].parent == outer.id
    assert by_name["build.stream"].parent == outer.id
    assert by_name["data.produce"].parent == by_name["build.stream"].id
    assert by_name["build.train_step"].parent is None
    assert len({s.id for s in scopes.records()}) == 5


def test_a_span_of_another_thread_is_no_child():
    def worker():
        with scopes.host("data.produce"):
            pass

    with scopes.host("build.stream"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10.0)
        assert not t.is_alive()
    by_name = {s.name: s for s in scopes.records()}
    assert by_name["data.produce"].parent is None


def test_a_span_that_raises_is_recorded_and_closed():
    with pytest.raises(KeyError):
        with scopes.host("build.decoder"):
            raise KeyError("x")
    with scopes.host("build.stream"):
        pass
    first, second = scopes.records()
    assert first.name == "build.decoder" and second.parent is None


def test_self_time_by_containment_on_made_up_records():
    """A trace of ``decode`` of 10 s holds an import of 3 s and an inner
    trace of 2 s which holds one of 0.5 s; beside it a build of 4 s holds
    another program's three stages."""
    made_up = [
        Span("compile.trace", "decode", 0.0, 10.0, None, 1),
        Span("import.pallas", None, 1.0, 4.0, 1, 2),
        Span("compile.trace", "matmul", 5.0, 7.0, 1, 3),
        Span("compile.trace", "_where", 5.5, 6.0, 3, 4),
        Span("compile.lower", "decode", 10.0, 11.0, None, 5),
        Span("compile.backend", "decode", 11.0, 11.25, None, 6, "hit"),
        Span("build.train_step", "train_step", 20.0, 24.0, None, 7),
        Span("compile.trace", "zeros", 20.0, 20.5, 7, 8),
        Span("compile.lower", "zeros", 20.5, 20.75, 7, 9),
        Span("compile.backend", "zeros", 21.0, 22.0, 7, 10, "miss"),
        Span("compile.backend", "zeros", 22.0, 22.25, 7, 11, "hit"),
    ]
    scopes.program("decode")
    out = scopes.startup(made_up)
    assert out["spans"] == {
        "compile.trace": 10.0 - 3.0 + 0.5,      # the import is not a trace
        "import.pallas": 3.0, "compile.lower": 1.25,
        "compile.backend": 1.5, "build.train_step": 4.0 - 2.0}
    assert sum(out["spans"].values()) == pytest.approx(
        10.0 + 1.0 + 0.25 + 4.0)                # every second once
    # the helpers traced inside ``decode`` are its own seconds
    assert out["programs"] == {"decode": {
        "trace_s": 7.0, "lower_s": 1.0, "backend_s": 0.25, "cache": "hit",
        "traces": 0, "compiles": 1}}
    assert out["others"] == {"zeros": {
        "trace_s": 0.5, "lower_s": 0.25, "backend_s": 1.25, "cache": "miss",
        "traces": 1, "compiles": 2}}
    assert out["records"] == len(made_up) and out["retraces"] == 0


def test_an_own_program_inside_another_keeps_its_seconds():
    """``jax.jit(lambda: decode(...))``: the stages of ``decode`` are
    the package's own though another program's trace is around them."""
    made_up = [Span("compile.trace", "<lambda>", 0.0, 5.0, None, 1),
               Span("compile.trace", "decode", 1.0, 4.0, 1, 2),
               Span("compile.trace", "matmul", 2.0, 3.0, 2, 3)]
    scopes.program("decode")
    out = scopes.startup(made_up)
    assert out["programs"]["decode"]["trace_s"] == 3.0
    assert out["others"]["<lambda>"]["trace_s"] == 2.0
    assert set(out["others"]) == {"<lambda>"}


def _build_train_step():
    """What a factory does, with a program small enough for any test."""
    with scopes.host("build.train_step", program="train_step"):
        record = scopes.program("train_step")

        @jax.jit
        def train_step(x):
            record.traced()
            return jnp.tanh(x) @ x + jnp.sum(x)

    return train_step


def test_a_program_is_recorded_stage_by_stage_under_its_name():
    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)       # made before: not the step's
    before = scopes.startup()["totals"]["programs"]
    jax.block_until_ready(step(x))
    mine = _of("train_step")
    assert [s.name for s in mine] == ["build.train_step", *STAGES]
    trace = mine[1]
    # ``tanh`` or ``matmul``, traced inside it, are its children
    inner = [s for s in scopes.records() if s.parent == trace.id]
    assert inner and {s.name for s in inner} == {"compile.trace"}
    assert all(trace.start <= s.start and s.end <= trace.end + 1e-3
               for s in inner)
    # on the record's clock: after the build span, in order
    assert mine[0].end <= mine[1].start <= mine[2].start <= mine[3].start
    out = scopes.startup()
    row = out["programs"]["train_step"]
    assert row["traces"] == 1 and row["compiles"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["trace_s"] == pytest.approx(
        trace.end - trace.start, rel=1e-6)     # its helpers' seconds are its
    assert "train_step" not in out["others"]
    assert out["totals"]["programs"] == before + 1
    assert out["totals"]["backend_s"] >= row["backend_s"]
    assert out["retraces"] == 0

    n = len(scopes.records())
    jax.block_until_ready(step(x))          # a second call: nothing new
    assert len(scopes.records()) == n
    assert scopes.startup()["totals"]["programs"] == before + 1


def test_a_second_distinct_trace_of_one_object_is_a_retrace():
    step = _build_train_step()
    step(jnp.ones((8, 8), jnp.float32))
    assert scopes.startup()["retraces"] == 0
    other = _build_train_step()             # another object: its first
    other(jnp.ones((8, 8), jnp.float32))
    assert scopes.startup()["retraces"] == 0
    step(jnp.ones((4, 4), jnp.float32))     # the first object, a new shape
    out = scopes.startup()
    assert out["retraces"] == 1
    assert out["programs"]["train_step"]["traces"] == 3
    assert out["programs"]["train_step"]["compiles"] == 3


def test_the_listeners_are_registered_once():
    from jax._src import monitoring

    with scopes.host("build.stream"):
        pass
    with scopes.host("build.stream"):
        pass
    mine = [f for f in monitoring.get_event_time_span_listeners()
            if f is scopes._on_span]
    assert len(mine) == 1


def test_the_record_keeps_the_first_limit_spans_and_counts_the_rest(
        monkeypatch):
    monkeypatch.setattr(scopes, "LIMIT", 3)
    for _ in range(5):
        with scopes.host("data.produce"):
            pass
    out = scopes.startup()
    assert out["records"] == 3 and out["dropped"] == 2
    scopes.reset()
    assert scopes.startup()["dropped"] == 0 and scopes.records() == []


def test_a_span_is_an_annotation_under_the_prefix_in_a_profile(tmp_path):
    """The same span lies on the profiler's clock as ``ompi_tpu:<name>``,
    where ``benchmarks/lib/clock.py`` looks for it."""
    import glob

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        with scopes.host("data.produce"):
            jnp.ones(4).block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert scopes.PREFIX + "data.produce" in names


def test_one_record_and_one_vocabulary():
    """``TraceAnnotation`` is spelt in the helper alone, and
    ``chip_smoke.py`` keeps no compile meter of its own."""
    found = []
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "ompi_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path, encoding="utf-8") as f:
                    if "TraceAnnotation(" in f.read():
                        found.append(os.path.relpath(path, ROOT))
    assert found == [os.path.join("ompi_tpu", "core", "scopes.py")]
    with open(os.path.join(ROOT, "chip_smoke.py"), encoding="utf-8") as f:
        assert "CompileMeter" not in f.read()
