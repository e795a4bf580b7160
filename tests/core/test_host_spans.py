"""The host half of ``ompi_tpu/core/scopes.py``: ``host()``, the record, the
compile stages by program name from ``jax.monitoring`` (helpers folded into
the stage around them), and ``startup()`` by name, by program object and by
``trace.*`` span; then the run half: ``run.call``, ``run.dispatch`` and
``run.gc`` in a ring of their own, and ``run()``.  CPU only: what is
recorded and how it adds up, never how long a chip took.
"""

import gc
import os
import re
import threading
import types

import jax
import jax.numpy as jnp
import pytest

from ompi_tpu.core import scopes
from ompi_tpu.core.scopes import Span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STAGES = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture(autouse=True)
def fresh_record(monkeypatch):
    """An empty record, and no pass of the collector in it but where a test
    asks for them (a pass of a millisecond can land in any test)."""
    monkeypatch.setattr(scopes, "GC_RECORDED_FROM", 1e9)
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
    # a name of the run half is the ring's, any other the start-up record's
    mine, other = ((scopes.run_records, scopes.records)
                   if name.startswith("run.")
                   else (scopes.records, scopes.run_records))
    (span,) = [s for s in mine() if s.name == name]
    assert (span.name, span.program, span.parent) == (name, "p", None)
    assert span.end >= span.start
    assert not [s for s in other() if s.name == name]


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
        "traces": 0, "compiles": 1, "helpers": 0}}
    # no object marked these stages: they are the name's one object's
    assert out["calls"] == [{
        "program": "decode", "part": None, "built": 0, "trace_s": 7.0,
        "lower_s": 1.0, "backend_s": 0.25, "cache": "hit", "traces": 0,
        "helpers": 0}]
    assert out["others"] == {"zeros": {
        "trace_s": 0.5, "lower_s": 0.25, "backend_s": 1.25, "cache": "miss",
        "traces": 1, "compiles": 2, "helpers": 0}}
    assert out["trace"] == {}
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
    """What a factory does, with a program small enough for any test: the
    start-up half's tests below all run with the run half recording."""
    with scopes.host("build.train_step", program="train_step"):
        record = scopes.program("train_step")

        @jax.jit
        def train_step(x):
            record.traced()
            return jnp.tanh(x) @ x + jnp.sum(x)

    return scopes.ran(train_step, record)


def test_a_program_is_recorded_stage_by_stage_under_its_name():
    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)       # made before: not the step's
    before = scopes.startup()["totals"]["programs"]
    jax.block_until_ready(step(x))
    mine = _of("train_step")
    assert [s.name for s in mine] == ["build.train_step", *STAGES]
    trace = mine[1]
    # ``tanh`` or ``matmul``, traced inside it, are folded into it: counted,
    # and no records of their own
    assert trace.helpers > 0 and trace.built == 0
    assert not [s for s in scopes.records() if s.parent == trace.id]
    # on the record's clock: after the build span, in order
    assert mine[0].end <= mine[1].start <= mine[2].start <= mine[3].start
    out = scopes.startup()
    row = out["programs"]["train_step"]
    assert row["traces"] == 1 and row["compiles"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["trace_s"] == pytest.approx(
        trace.end - trace.start, rel=1e-6)     # its helpers' seconds are its
    assert row["helpers"] == sum(s.helpers for s in mine) >= trace.helpers
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


# ---------------------------------------------------------------------------
# a made-up stream of listener calls: (name, program, start, end, children)
# ---------------------------------------------------------------------------

EVENTS = {stage: event for event, stage in scopes._STAGES.items()}
# helpers nest in a stage of their own kind, so folding moves no second from
# one of a row's keys to another
STREAM = [
    ("build.decoder", "decode", 0.0, 1.0, [
        ("compile.trace", "zeros", 0.25, 0.5, [
            ("compile.trace", "_where", 0.25, 0.375, [])])]),
    ("compile.trace", "decode", 2.0, 12.0, [
        ("import.pallas", None, 3.0, 4.0, []),
        ("compile.trace", "multiply", 4.0, 4.5, [
            ("compile.trace", "_where", 4.0, 4.25, [])]),
        ("trace.layer", "kda", 5.0, 9.0, [
            ("compile.trace", "run", 5.0, 8.5, [
                ("trace.kernel", "kda_update", 6.0, 7.0, [
                    ("compile.trace", "exp", 6.25, 6.5, [])]),
                ("compile.trace", "add", 7.0, 7.5, [])])]),
        ("trace.layer", "mla", 9.0, 10.0, [])]),
    ("compile.lower", "decode", 12.0, 13.0, [
        ("compile.lower", "_where", 12.25, 12.5, [])]),
    ("compile.backend", "decode", 13.0, 13.25, []),
    ("compile.trace", "forward", 20.0, 24.0, [
        ("compile.trace", "matmul", 21.0, 22.0, [
            ("compile.trace", "_where", 21.0, 21.5, [])])]),
    ("compile.backend", "forward", 24.0, 26.0, []),
]


def _replay(stream, clock) -> None:
    """The listener calls and ``host()`` spans JAX and the package would
    make of ``stream``, on the made-up clock."""
    for name, prog, start, end, children in stream:
        if name in EVENTS:
            scopes._on_begin(EVENTS[name], start, fun_name=prog)
            _replay(children, clock)
            scopes._on_span(EVENTS[name], start, end, fun_name=prog)
        else:
            clock.now = start
            with scopes.host(name, program=prog):
                _replay(children, clock)
                clock.now = end


def _unfolded(stream, parent=None, ids=None) -> list:
    """``stream`` with a record for every stage, as the listeners kept them
    before they folded: in the order the spans end."""
    ids = ids if ids is not None else iter(range(1, 1000))
    out = []
    for name, prog, start, end, children in stream:
        mine = next(ids)
        out += _unfolded(children, mine, ids)
        out.append(Span(name, prog, start, end, parent, mine))
    return out


@pytest.fixture
def clock(monkeypatch):
    """``host()`` reads this clock, and a stage's times are the record's."""
    with scopes.host("build.stream"):       # the listeners, on the real one
        pass
    scopes.reset()
    made_up = types.SimpleNamespace(now=0.0)
    monkeypatch.setattr(scopes, "time", types.SimpleNamespace(
        perf_counter=lambda: made_up.now))
    monkeypatch.setattr(scopes, "_offset", 0.0)
    return made_up


def _without(rows: dict, key: str) -> dict:
    return {name: {k: v for k, v in row.items() if k != key}
            for name, row in rows.items()}


def test_a_stream_folded_and_unfolded_adds_up_to_the_same(clock):
    scopes.program("decode", "prefill")
    _replay(STREAM, clock)
    folded, unfolded = scopes.startup(), scopes.startup(_unfolded(STREAM))
    for key in ("programs", "others"):
        assert _without(folded[key], "helpers") == _without(
            unfolded[key], "helpers"), key
    for name in ("build.decoder", "import.pallas"):
        assert folded["spans"][name] == unfolded["spans"][name], name
    # every second once, either way; a helper inside a layer is the layer's
    # seconds where it is folded and ``compile.trace``'s where it is not
    assert sum(folded["spans"].values()) == sum(unfolded["spans"].values())
    # what the record holds: every span of ``host()``, every stage of an
    # own program, the top-level stages of the others
    assert folded["records"] == 11 and unfolded["records"] == 20
    assert [(s.name, s.program) for s in scopes.records()
            if s.name in STAGES] == [
        ("compile.trace", "zeros"), ("compile.trace", "decode"),
        ("compile.lower", "decode"), ("compile.backend", "decode"),
        ("compile.trace", "forward"), ("compile.backend", "forward")]
    assert folded["programs"]["decode"] == {
        "trace_s": 10.0 - 1.0, "lower_s": 1.0, "backend_s": 0.25,
        "cache": None, "traces": 0, "compiles": 1, "helpers": 6}
    assert folded["others"]["zeros"]["helpers"] == 1
    assert folded["others"]["forward"]["helpers"] == 2
    assert unfolded["programs"]["decode"]["helpers"] == 0
    # by layer kind and by kernel, self time: the kernel's is not the layer's
    assert folded["trace"] == {
        "trace.layer": {"kda": {"seconds": 3.0, "own_s": 3.0, "spans": 1},
                        "mla": {"seconds": 1.0, "own_s": 1.0, "spans": 1}},
        "trace.kernel": {"kda_update": {"seconds": 1.0, "own_s": 1.0,
                                        "spans": 1}}}
    # the trace's seconds are whole: its own, its layers', its kernel's
    assert folded["spans"]["compile.trace"] + 3.0 + 1.0 + 1.0 == (
        9.0 + 0.25 + 4.0)
    assert folded["dropped"] == 0


def test_a_folded_stage_is_no_record_and_an_early_one_is(clock):
    """``_on_span`` tells a helper it folded from a stage that began before
    the listeners were registered, which has nothing on the stack either."""
    trace = EVENTS["compile.trace"]
    scopes._on_span(trace, 1.0, 2.0, fun_name="early")
    scopes._on_begin(trace, 3.0, fun_name="outer")
    scopes._on_begin(trace, 4.0, fun_name="helper")
    scopes._on_begin(trace, 4.25, fun_name="helper")    # itself, inside
    scopes._on_span(trace, 4.25, 4.5, fun_name="helper")
    scopes._on_span(trace, 4.0, 5.0, fun_name="helper")
    scopes._on_span(trace, 3.5, 3.75, fun_name="late")  # never began
    with scopes.host("trace.layer", program="block"):
        scopes._on_begin(trace, 5.0, fun_name="helper")
        scopes._on_span(trace, 5.0, 5.5, fun_name="helper")
    scopes._on_span(trace, 3.0, 6.0, fun_name="outer")
    got = {s.program: s for s in scopes.records()}
    assert set(got) == {"early", "late", "block", "outer"}
    assert got["early"].parent is None
    assert got["late"].parent == got["outer"].id == got["block"].parent
    assert got["outer"].helpers == 3 and got["early"].helpers == 0
    assert scopes._stack() == []
    # a helper at top level is a record, and its own helpers fold into it
    scopes._on_begin(trace, 7.0, fun_name="helper")
    scopes._on_begin(trace, 7.0, fun_name="_where")
    scopes._on_span(trace, 7.0, 7.5, fun_name="_where")
    scopes._on_span(trace, 7.0, 8.0, fun_name="helper")
    last = scopes.records()[-1]
    assert (last.program, last.helpers, last.parent) == ("helper", 1, None)
    assert scopes.startup()["others"]["helper"]["trace_s"] == 1.0


def test_a_folded_backend_stage_still_counts_in_the_totals(clock):
    """An operation run eagerly while a program is traced: its compile is
    the trace's seconds and the process's count."""
    scopes.program("decode")
    scopes._on_begin(EVENTS["compile.trace"], 0.0, fun_name="decode")
    scopes._on_begin(EVENTS["compile.backend"], 1.0, fun_name="jit(iota)")
    scopes._on_event("/jax/compilation_cache/cache_hits")
    scopes._on_span(EVENTS["compile.backend"], 1.0, 1.5,
                    fun_name="jit(iota)")
    scopes._on_span(EVENTS["compile.trace"], 0.0, 2.0, fun_name="decode")
    out = scopes.startup()
    assert out["programs"]["decode"] == {
        "trace_s": 2.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None,
        "traces": 0, "compiles": 0, "helpers": 1}
    assert out["totals"]["programs"] == 1
    assert out["totals"]["backend_s"] == 0.5
    assert out["totals"]["cache_hits"] == 1 and out["records"] == 1


def test_two_objects_of_one_name_are_two_calls_that_add_up(clock):
    """A decoder's prefill and its generating program, both ``decode``:
    ``traced()`` marks the open trace, and the lowering and the backend's
    stage that follow on the thread are the same object's."""
    first = scopes.program("decode", "prefill")
    second = scopes.program("decode", "generate")
    scopes.program("train_step")
    assert (first.built, second.built) == (0, 1)
    at = 0.0
    for handle, seconds in ((first, 4.0), (second, 1.0), (first, 0.5)):
        for stage, share in zip(STAGES, (1.0, 0.5, 0.25)):
            prog = "decode" if stage == "compile.trace" else "jit(decode)"
            scopes._on_begin(EVENTS[stage], at, fun_name=prog)
            if stage == "compile.trace":
                scopes._on_begin(EVENTS[stage], at, fun_name="multiply")
                scopes._on_span(EVENTS[stage], at, at, fun_name="multiply")
                handle.traced()
            scopes._on_span(EVENTS[stage], at, at + seconds * share,
                            fun_name=prog)
            at += seconds * share
    assert [s.built for s in scopes.records()] == [0] * 3 + [1] * 3 + [0] * 3
    out = scopes.startup()
    assert out["calls"] == [
        {"program": "decode", "part": "prefill", "built": 0,
         "trace_s": 4.5, "lower_s": 2.25, "backend_s": 1.125, "cache": None,
         "traces": 2, "helpers": 2},
        {"program": "decode", "part": "generate", "built": 1,
         "trace_s": 1.0, "lower_s": 0.5, "backend_s": 0.25, "cache": None,
         "traces": 1, "helpers": 1},
        {"program": "train_step", "part": None, "built": 2,
         "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None,
         "traces": 0, "helpers": 0}]
    assert out["programs"] == {"decode": {
        "trace_s": 5.5, "lower_s": 2.75, "backend_s": 1.375, "cache": None,
        "traces": 3, "compiles": 3, "helpers": 3}}
    assert out["retraces"] == 1
    # an object that reset() forgot marks nothing, and a stage without a
    # mark is the name's last object's
    scopes.reset()
    assert first.built is None
    kept = scopes.program("decode", "whole")
    scopes._on_begin(EVENTS["compile.trace"], 9.0, fun_name="decode")
    first.traced()
    scopes._on_span(EVENTS["compile.trace"], 9.0, 9.5, fun_name="decode")
    (row,) = scopes.startup()["calls"]
    assert (row["part"], row["trace_s"], row["traces"]) == ("whole", 0.5, 0)
    assert kept.traces == 0
    # ... and where no factory has registered its name again, its trace
    # inside another program's is a helper's
    scopes.reset()
    scopes._on_begin(EVENTS["compile.trace"], 10.0, fun_name="<lambda>")
    scopes._on_begin(EVENTS["compile.trace"], 10.0, fun_name="decode")
    first.traced()
    scopes._on_span(EVENTS["compile.trace"], 10.0, 10.5, fun_name="decode")
    scopes._on_span(EVENTS["compile.trace"], 10.0, 11.0, fun_name="<lambda>")
    (span,) = scopes.records()
    assert (span.program, span.helpers, span.built) == ("<lambda>", 1, None)


def test_two_program_objects_of_a_process_add_up_to_their_names_row():
    one, other = _build_train_step(), _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)
    jax.block_until_ready((one(x), other(x), one(jnp.ones((4, 4)))))
    out = scopes.startup()
    rows, total = out["calls"], out["programs"]["train_step"]
    assert [(r["program"], r["part"], r["built"], r["traces"])
            for r in rows] == [("train_step", None, 0, 2),
                               ("train_step", None, 1, 1)]
    for key in ("trace_s", "lower_s", "backend_s"):
        assert all(r[key] > 0 for r in rows), key
        assert sum(r[key] for r in rows) == pytest.approx(total[key],
                                                          abs=1e-6)
    assert out["retraces"] == 1
    assert sum(r["helpers"] for r in rows) == total["helpers"] > 0
    assert total["traces"] == 3 and total["compiles"] == 3


def test_a_layer_traced_inside_an_own_program_is_that_programs_trace():
    """``trace.layer`` (and a ``trace.kernel`` inside it) takes its seconds
    out of ``compile.trace`` by name, and not out of the program's row."""
    record = scopes.program("decode", "whole")

    @jax.jit
    def decode(x):
        record.traced()
        with scopes.host("trace.layer", program="kda"):
            x = jnp.tanh(x) @ x
            with scopes.host("trace.kernel", program="kda_update"):
                x = jnp.exp(x)
        return x + 1

    jax.block_until_ready(decode(jnp.ones((8, 8), jnp.float32)))
    by_name = {s.name: s for s in scopes.records()}
    trace, layer, kernel = (by_name[n] for n in (
        "compile.trace", "trace.layer", "trace.kernel"))
    assert layer.parent == trace.id and kernel.parent == layer.id
    out = scopes.startup()
    assert out["programs"]["decode"]["trace_s"] == pytest.approx(
        trace.end - trace.start, rel=1e-6)
    assert out["calls"][0]["trace_s"] == out["programs"]["decode"]["trace_s"]
    assert trace.helpers >= 3           # tanh, matmul, exp: none a record
    seconds = {name: by["seconds"] for name, by in (
        ("trace.layer", out["trace"]["trace.layer"]["kda"]),
        ("trace.kernel", out["trace"]["trace.kernel"]["kda_update"]))}
    assert seconds["trace.kernel"] == pytest.approx(kernel.end - kernel.start)
    assert seconds["trace.layer"] == pytest.approx(
        layer.end - layer.start - seconds["trace.kernel"])
    assert out["spans"]["trace.layer"] == seconds["trace.layer"]
    for by in out["trace"].values():
        (row,) = by.values()
        assert row["own_s"] == row["seconds"] > 0 and row["spans"] == 1
    assert out["spans"]["compile.trace"] + sum(seconds.values()) == (
        pytest.approx(out["programs"]["decode"]["trace_s"], rel=1e-6))


def test_a_kernel_called_outside_any_program_is_in_trace_alone():
    with scopes.host("trace.kernel", program="rope"):
        pass
    out = scopes.startup()
    assert out["trace"]["trace.kernel"]["rope"]["own_s"] == 0.0
    assert out["trace"]["trace.kernel"]["rope"]["spans"] == 1
    assert out["programs"] == {} and out["others"] == {}


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


def test_chip_smoke_prints_the_record_by_object_and_by_kind():
    """``startup_summary`` is the operator's reader: the whole of
    ``startup()``, the other programs summed, as one line of JSON."""
    import json

    import chip_smoke

    step = _build_train_step()
    jax.block_until_ready(step(jnp.ones((8, 8), jnp.float32)))
    with scopes.host("trace.kernel", program="rope"):
        pass
    line = json.loads(json.dumps(chip_smoke.startup_summary(slowest=1)))
    assert set(line) == {"spans", "programs", "calls", "others", "trace",
                         "retraces", "totals", "records", "dropped"}
    assert line["dropped"] == 0 and line["trace"]["trace.kernel"]["rope"]
    (row,) = line["calls"]
    assert (row["program"], row["built"]) == ("train_step", 0)
    assert set(line["others"]) == {"programs", "seconds", "slowest"}
    assert {"cache_retrieval_s", "cache_hits"} <= set(line["totals"])
    assert "cache" in row           # what ``Span.cache`` is kept for


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


# ---------------------------------------------------------------------------
# the run half: ``run.call``, ``run.dispatch``, ``run.gc`` and ``run()``
# ---------------------------------------------------------------------------

def _ran(name: str) -> list:
    return [s for s in scopes.run_records() if s.name == name]


def test_the_request_id_runs_from_the_call_through_the_dispatch_to_a_stage():
    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)
    jax.block_until_ready(step(x))
    jax.block_until_ready(step(x))
    first, second = _ran("run.call")
    one, two = _ran("run.dispatch")
    assert (first.n, second.n, one.n, two.n) == (1, 2, 1, 2)
    assert first.parent is None and one.parent == first.id
    assert two.parent == second.id and len({first.id, second.id}) == 2
    assert (first.program, one.program, one.built) == (
        "train_step", "train_step", 0)
    assert first.cpu_s > 0 and first.switches >= 0 and first.faults >= 0
    assert second.cpu_s >= first.cpu_s and second.switches >= first.switches
    # the stages began inside the first dispatch: they name it, in the
    # start-up record as in the ring
    stages = [s for s in _of("train_step") if s.name in STAGES]
    assert [s.parent for s in stages] == [one.id] * 3
    assert [s.id for s in scopes.run_records() if s.name in STAGES] == [
        s.id for s in stages]
    assert first.start <= one.start <= stages[0].start
    assert stages[-1].end <= one.end <= first.end
    out = scopes.run()
    (callable_,) = out["callables"]
    assert (callable_["calls"], callable_["compiled"],
            callable_["quiet"]) == (2, 1, 1)
    assert callable_["median_s"] == callable_["longest_s"] == (
        second.end - second.start)
    assert callable_["host_s"] == pytest.approx(
        first.end - first.start + second.end - second.start)
    (row,) = out["programs"]
    assert (row["program"], row["part"], row["built"], row["dispatches"],
            row["compiles"], row["recompiled"], row["recompiles"]) == (
        "train_step", None, 0, 2, 1, 0, [])
    assert row["first_s"] == one.end - one.start
    assert 0 <= row["first_rest_s"] == pytest.approx(
        row["first_s"] - sum(s.end - s.start for s in stages), abs=1e-9)
    (job,) = out["jobs"]
    assert job["n"] == 1 and job["wall_s"] == second.start - first.start
    assert job["call_s"] == first.end - first.start
    assert job["cpu_s"] == second.cpu_s - first.cpu_s
    assert out["records"] == 4 and out["wrapped"] == 0


def test_a_later_call_that_compiles_says_which_call_and_which_object():
    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)
    for _ in range(3):
        step(x)
    step(jnp.ones((4, 4), jnp.float32))         # the fourth call: a new shape
    step(x)
    out = scopes.run()
    (row,) = out["programs"]
    assert (row["dispatches"], row["compiles"], row["recompiled"]) == (5, 2, 1)
    (again,) = row["recompiles"]
    assert again["n"] == 4 and again["backend_s"] > 0
    (callable_,) = out["callables"]
    assert (callable_["calls"], callable_["compiled"],
            callable_["quiet"]) == (5, 2, 3)
    fourth = _ran("run.dispatch")[3]
    assert [s.parent for s in _of("train_step")
            if s.name in STAGES][3:] == [fourth.id] * 3


def test_a_call_traced_into_another_program_is_no_run():
    """``Job.programs()`` does ``jax.jit(self.first)`` over a decoder's
    callable: its call runs under that program's ``compile.trace``."""
    step = _build_train_step()
    run = scopes.caller("decode")

    def handed_out(x):
        with run.call():
            return step(x)

    x = jnp.ones((8, 8), jnp.float32)
    jax.jit(handed_out).lower(x)
    jax.block_until_ready(jax.jit(handed_out)(x))
    assert [s for s in scopes.run_records() if s.name.startswith("run.")] == []
    out = scopes.run()
    assert [c["calls"] for c in out["callables"]] == [0, 0]
    assert out["programs"][0]["dispatches"] == 0
    assert out["programs"][0]["first_s"] is None
    # the start-up half saw the program traced, as it did
    assert scopes.startup()["programs"]["train_step"]["traces"] == 1
    # and the same callable, called, is a run: two calls, one dispatch
    jax.block_until_ready(handed_out(x))
    assert [(s.name, s.program) for s in scopes.run_records()
            if s.name.startswith("run.")] == [
        ("run.call", "decode"), ("run.call", "train_step"),
        ("run.dispatch", "train_step")]
    outer, inner, dispatch = _ran("run.call") + _ran("run.dispatch")
    assert inner.parent == outer.id and dispatch.parent == inner.id


def _call(n, start, end, ident, cpu_s, switches, faults, made=0):
    return Span("run.call", "decode", start, end, None, ident, None, 0, made,
                n, cpu_s, switches, faults)


def _dispatch(n, start, end, parent, ident, built):
    return Span("run.dispatch", "decode", start, end, parent, ident, None, 0,
                built, n)


MADE_UP_RUN = [
    # the first call: both objects trace, lower and compile inside it
    _call(1, 0.0, 10.0, 1, 0.5, 3, 0),
    _dispatch(1, 0.0, 6.0, 1, 2, 0),
    Span("compile.trace", "decode", 0.5, 2.5, 2, 3, None, 5, 0),
    Span("trace.layer", "kda", 1.0, 2.0, 3, 4),     # the trace's own child
    Span("compile.lower", "decode", 2.5, 3.0, 2, 5, None, 0, 0),
    Span("compile.backend", "decode", 3.0, 5.0, 2, 6, "miss", 0, 0),
    _dispatch(1, 6.0, 10.0, 1, 7, 1),
    Span("import.pallas", None, 6.0, 6.5, 7, 8),
    Span("compile.backend", "decode", 6.5, 9.0, 7, 9, "hit", 0, 1),
    # a quiet call, with a pass of the collector inside it
    _call(2, 20.0, 20.5, 10, 0.75, 3, 0),
    _dispatch(2, 20.0, 20.25, 10, 11, 0),
    _dispatch(2, 20.25, 20.5, 10, 12, 1),
    Span("run.gc", "gen2", 20.125, 20.375, None, 13),
    Span("run.gc", "gen1", 25.0, 26.0, None, 14),   # between two calls
    # the third call recompiles its second program
    _call(3, 30.0, 33.0, 15, 1.0, 7, 1),
    _dispatch(3, 30.0, 30.25, 15, 16, 0),
    _dispatch(3, 30.25, 33.0, 15, 17, 1),
    Span("compile.backend", "decode", 30.5, 32.5, 17, 18, "miss", 0, 1),
    Span("run.gc", "gen2", 31.0, 31.5, None, 19),   # in a call that compiled
    # another callable of the same name: one quiet call
    _call(1, 40.0, 41.5, 20, 1.5, 7, 1, made=1),
    _dispatch(1, 40.0, 41.0, 20, 21, 0),
]


def test_first_rest_recompiles_gc_and_jobs_on_made_up_records():
    scopes.program("decode", "prefill")
    scopes.program("decode", "generate")
    scopes.caller("decode")
    scopes.caller("decode")
    out = scopes.run(MADE_UP_RUN)
    prefill, generate = out["programs"]
    assert (prefill["part"], prefill["first_s"], prefill["first_rest_s"],
            prefill["recompiles"]) == ("prefill", 6.0, 6.0 - 4.5, [])
    assert (generate["part"], generate["first_s"],
            generate["first_rest_s"]) == ("generate", 4.0, 4.0 - 3.0)
    assert generate["recompiles"] == [{"n": 3, "backend_s": 2.0}]
    one, other = out["callables"]
    assert (one["quiet"], one["median_s"], one["total_s"],
            one["longest_s"]) == (1, 0.5, 0.5, 0.5)
    assert (other["quiet"], other["median_s"]) == (1, 1.5)
    # the pass inside the quiet call is that call's; the one inside the
    # call that compiled is no quiet call's
    assert out["gc"]["in_calls_s"] == 0.25 and out["gc"]["recorded"] == 3
    # a job is a call to the next in time, whichever callable's
    assert out["jobs"] == [
        {"program": "decode", "made": 0, "n": 1, "wall_s": 20.0,
         "call_s": 10.0, "cpu_s": 0.25, "switches": 0, "faults": 0,
         "gc_s": 0.0},
        {"program": "decode", "made": 0, "n": 2, "wall_s": 10.0,
         "call_s": 0.5, "cpu_s": 0.25, "switches": 4, "faults": 1,
         "gc_s": 0.25 + 1.0},
        {"program": "decode", "made": 0, "n": 3, "wall_s": 10.0,
         "call_s": 3.0, "cpu_s": 0.5, "switches": 0, "faults": 0,
         "gc_s": 0.5}]
    assert out["records"] == 4 + 7 + 3
    # the counters are the handles', which made-up spans do not move
    assert [c["calls"] for c in out["callables"]] == [0, 0]
    assert [p["dispatches"] for p in out["programs"]] == [0, 0]


def test_a_pass_on_another_thread_counts_for_the_call_it_overlaps(
        monkeypatch):
    """The input worker's allocations can trip a pass that stops the main
    thread's dispatch: the pass is on no thread's stack, nobody's child and
    nobody's parent, and the call's by its time."""
    monkeypatch.setattr(scopes, "GC_RECORDED_FROM", 0.0)
    run = scopes.caller("decode")
    before = scopes.run()["gc"]

    def worker():
        with scopes.host("data.produce"):
            gc.collect()

    with run.call():
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30.0)
        assert not t.is_alive()
    (call,) = _ran("run.call")
    passes = [s for s in _ran("run.gc") if s.program == "gen2"]
    assert passes and all(s.parent is None for s in passes)
    inside = [s for s in passes if call.start <= s.start and s.end <= call.end]
    assert inside
    (produced,) = [s for s in scopes.records() if s.name == "data.produce"]
    assert produced.parent is None              # and the pass is not its child
    assert not [s for s in scopes.records() if s.name == "run.gc"]
    out = scopes.run()
    assert out["gc"]["in_calls_s"] >= sum(s.end - s.start for s in inside) > 0
    assert out["gc"]["gen2"]["passes"] > before["gen2"]["passes"]
    assert out["gc"]["gen2"]["seconds"] > before["gen2"]["seconds"]
    assert out["gc"]["longest_s"] >= max(s.end - s.start for s in inside)


def test_a_short_pass_is_counted_and_not_recorded():
    with scopes.host("data.produce"):       # the listeners, the callback
        pass
    before = scopes.run()["gc"]["gen0"]["passes"]
    gc.collect(0)
    out = scopes.run()["gc"]
    assert out["gen0"]["passes"] == before + 1 and out["recorded"] == 0


def test_the_collectors_callback_never_raises(monkeypatch):
    with scopes.host("data.produce"):
        pass
    monkeypatch.setattr(scopes, "_passes", None)    # whatever goes wrong
    scopes._on_gc("start", {"generation": 2})
    scopes._on_gc("stop", {"generation": 2})
    scopes._on_gc("stop", {})                       # a stop with no start
    assert scopes._on_gc in gc.callbacks
    assert gc.callbacks.count(scopes._on_gc) == 1


def test_the_ring_wraps_and_the_counters_do_not():
    assert scopes.RING == 4096
    record, run = scopes.program("decode", "whole"), scopes.caller("decode")
    calls = scopes.RING // 2 + 52
    for _ in range(calls):
        with run.call(), record.dispatch():
            pass
    held = scopes.run_records()
    # the ring's newest 4096, and the object's first dispatch beside them
    assert len(held) == scopes.RING + 1
    assert held[0].name == "run.dispatch" and held[0].n == 1
    assert held[1].n == 53 and held[-1].n == calls
    out = scopes.run()
    assert out["wrapped"] == 2 * calls - scopes.RING == 104
    (callable_,) = out["callables"]
    assert callable_["calls"] == calls and callable_["quiet"] == calls - 52
    (row,) = out["programs"]
    assert row["dispatches"] == calls and row["first_s"] is not None
    assert len(out["jobs"]) == calls - 52 - 1
    # the start-up record holds none of it
    assert scopes.records() == [] and scopes.startup()["records"] == 0
    scopes.reset()
    assert scopes.run_records() == [] and scopes.run()["wrapped"] == 0
    assert run.made is None and scopes.run()["callables"] == []


def test_the_start_up_record_is_what_it_was_with_the_run_half_recording():
    keys = {"spans", "programs", "calls", "others", "trace", "retraces",
            "totals", "records", "dropped"}
    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)
    jax.block_until_ready(step(x))
    n = len(scopes.records())
    for _ in range(3):
        jax.block_until_ready(step(x))
    gc.collect()
    out = scopes.startup()
    assert set(out) == keys
    assert out["records"] == len(scopes.records()) == n
    assert out["dropped"] == 0
    assert not [s for s in scopes.records() if s.name.startswith("run.")]
    assert not [name for name in out["spans"] if name.startswith("run.")]
    assert [s.name for s in _of("train_step")] == ["build.train_step", *STAGES]
    # a stage inside a dispatch is a top-level stage to the start-up half
    assert out["programs"]["train_step"]["compiles"] == 1
    assert len(_ran("run.call")) == 4


def test_what_the_factories_hand_out_keeps_lower_and_the_fast_path():
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models.decode import make_decoder
    from ompi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1])
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                                d_ff=64, seq=16, attention="xla",
                                compute_dtype="float32", remat=False)
    params = tfm.init_params(cfg)
    tokens = jnp.zeros((2, cfg.seq), jnp.int32)
    for factory, name in ((tfm.make_train_step, "train_step"),
                          (tfm.make_train_loop, "train_loop")):
        step, init = factory(cfg, mesh)
        assert step.__name__ == name and callable(step.lower)
        text = step.lower(params, init(params), tokens).as_text()
        assert f"@jit_{name}" in text
    assert not _ran("run.call")                 # a lowering is no run
    decode = make_decoder(cfg, mesh, max_new=2)
    prompt = jnp.zeros((2, 4), jnp.int32)
    assert "@jit__lambda" in jax.jit(decode).lower(params, prompt).as_text()
    assert not _ran("run.call")
    # a call is the jitted function's own: nothing between the handle and
    # the function but the two spans
    step, init = tfm.make_train_step(cfg, mesh)
    placed = tfm.shard_params(cfg, mesh, params)
    state = init(placed)
    placed, state, loss = step(placed, state, tokens)
    placed, state, loss = step(placed, state, tokens)
    jax.block_until_ready(loss)
    (row,) = [r for r in scopes.run()["programs"] if r["dispatches"]]
    assert (row["program"], row["dispatches"], row["compiles"],
            row["recompiled"]) == ("train_step", 2, 1, 0)
    assert [s.n for s in _ran("run.call")] == [1, 2]
    # parameters that no one placed come back placed: the second call
    # compiles the step again (``transformer._init_on_mesh``), and the
    # record says it was the second
    scopes.reset()
    step, init = tfm.make_train_step(cfg, mesh)
    state = init(params)
    for _ in range(3):
        params, state, loss = step(params, state, tokens)
    (row,) = [r for r in scopes.run()["programs"] if r["dispatches"]]
    assert (row["compiles"], row["recompiled"]) == (2, 1)
    assert [r["n"] for r in row["recompiles"]] == [2]


def test_a_forced_pass_inside_a_job_is_the_jobs_and_an_annotation(
        tmp_path, monkeypatch):
    """``gc.in_calls_s`` of the job it stopped, and ``ompi_tpu:run.gc`` on
    the profile's clock, where ``benchmarks/lib/clock.py`` names idle gaps
    after the innermost span."""
    import glob

    monkeypatch.setattr(scopes, "GC_RECORDED_FROM", 0.0)
    step = _build_train_step()
    run = scopes.caller("decode")

    def job(x, collect):
        with run.call():
            y = step(x)
            if collect:
                gc.collect()
            return y

    x = jnp.ones((8, 8), jnp.float32)
    jax.block_until_ready(job(x, False))        # compiles: no quiet call
    assert scopes.run()["gc"]["in_calls_s"] == 0
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        jax.block_until_ready(job(x, True))
    out = scopes.run()
    forced = [s for s in _ran("run.gc") if s.program == "gen2"][-1]
    second = [s for s in _ran("run.call") if s.program == "decode"][-1]
    assert second.start <= forced.start and forced.end <= second.end
    assert out["gc"]["in_calls_s"] >= forced.end - forced.start > 0
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert {scopes.PREFIX + "run.gc", scopes.PREFIX + "run.call",
            scopes.PREFIX + "run.dispatch"} <= names


def test_chip_smoke_prints_the_run_half_beside_the_start_up_half():
    import json

    import chip_smoke

    step = _build_train_step()
    x = jnp.ones((8, 8), jnp.float32)
    for _ in range(4):
        jax.block_until_ready(step(x))
    line = json.loads(json.dumps(chip_smoke.run_summary(slowest=2)))
    assert set(line) == {"callables", "programs", "gc", "jobs", "records",
                         "wrapped"}
    assert len(line["jobs"]) == 2               # the longest, not all three
    assert line["callables"][0]["calls"] == 4
    assert line["programs"][0]["first_rest_s"] >= 0


def _cells():
    from benchmarks.lib import cells

    return [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", _cells())
def test_a_tiny_job_of_a_cell_is_one_call_and_its_dispatches(workload):
    """Through the factories' callables as the runners hold them: a train
    step is one dispatch, a decoder of one program one, a ``full`` job of a
    plan or retention cell its prefill and then its generating program, and
    its ``first`` the prefill alone."""
    from benchmarks.lib import cells, program
    from benchmarks.lib.spans import Spans
    from ompi_tpu.models import decode
    from tests.benchmarks.test_harness import TINY_TRAFFIC

    decode._prefill_program.cache_clear()
    cell = cells.resolve(workload)
    cell.config = program.tiny(cell.config)
    cell.traffic = {k: TINY_TRAFFIC.get(k, v)
                    for k, v in cell.traffic.items()}
    job = cell.runner.build(cell.config, cell.traffic,
                            jax.devices()[:cell.chips])
    try:
        if hasattr(job, "step"):
            job.setup(5, Spans())       # two warm-up steps of one callable
            jobs = [("train_step", 1, [None]), ("train_step", 2, [None])]
        else:
            params, prompts = job.draw(5)
            jax.block_until_ready(job.first(params, prompts))
            jax.block_until_ready(job.full(params, prompts))
            parts = [p.part for p in scopes._programs]
            assert parts in (["whole", "whole"], ["prefill", "generate"])
            jobs = ([("decode", 1, ["whole"]), ("decode", 1, ["whole"])]
                    if parts[0] == "whole" else
                    [("decode", 1, ["prefill"]),
                     ("decode", 1, ["prefill", "generate"])])
    finally:
        job.close()
    calls = _ran("run.call")
    assert [(c.program, c.n, c.parent) for c in calls] == [
        (name, n, None) for name, n, _parts in jobs]
    by_built = {p.built: p.part for p in scopes._programs}
    for call, (name, _n, parts) in zip(calls, jobs):
        inside = [s for s in _ran("run.dispatch") if s.parent == call.id]
        assert [(s.program, by_built[s.built], s.n) for s in inside] == [
            (name, part, call.n) for part in parts]
        assert call.start <= inside[0].start and inside[-1].end <= call.end
    out = scopes.run()
    assert sum(c["calls"] for c in out["callables"]) == len(jobs)
    assert all(p["first_rest_s"] is not None and p["recompiled"] == 0
               for p in out["programs"])
    # a prefill shared by both decoders compiled once, in the first job
    if jobs[-1][2] == ["prefill", "generate"]:
        prefill, generate = out["programs"]
        assert (prefill["dispatches"], prefill["compiles"]) == (2, 1)
        assert (generate["dispatches"], generate["compiles"]) == (1, 1)
    assert scopes.startup()["records"] < 300


def test_the_tool_lists_the_jobs_off_their_callables_median():
    """``tools/run_record.py``: ``run()["jobs"]`` against the median of each
    callable's, and the ones more than ``OFF`` from it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "run_record", os.path.join(ROOT, "tools", "run_record.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    scopes.caller("decode")
    out = tool.off(scopes.run(MADE_UP_RUN)["jobs"])
    assert tool.OFF == 0.005
    assert out["callables"] == {"0": {"jobs": 3, "median_wall_s": 10.0}}
    (late,) = out["off"]
    assert (late["n"], late["wall_s"], late["call_s"], late["off"]) == (
        1, 20.0, 10.0, 1.0)
