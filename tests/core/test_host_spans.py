"""The host half of ``ompi_tpu/core/scopes.py``: ``host()``, the record, the
compile stages by program name from ``jax.monitoring`` (helpers folded into
the stage around them), and ``startup()`` by name, by program object and by
``trace.*`` span.  CPU only: what is recorded and how it adds up, never how
long a chip took.
"""

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
