"""The names of the device path: inside the programs, and on the host
around them.

**The device half.**  Every boundary the performance records talk about is a
``jax.named_scope`` from the one vocabulary ``SCOPES``.  The tuple is the
only copy of it: the reader of a profile, ``benchmarks/lib/scopes.py``,
keeps no list of its own and reads this tuple when it classifies an
operation, so a name added here is a scope there, a key of its table and a
line of a traced run's ``breakdown.device_scopes``, with no edit under
``benchmarks/``; a metric that reads it is a data file that names its keys
(``PERF.md`` section 3 says which metric reads which name).  A profile of
any program built on this package is thus read by layer instead of by
``fusion.461``.  A scope is HLO metadata (``op_name``): it changes no
computation and costs nothing when the program runs.  JAX's own name stack
already tells forward (``jvp``), backward (``transpose``) and recomputation
(``rematted_computation``) apart, so none of them is a scope.

One caution: JAX's persistent compilation cache leaves metadata out of its
key.  A program that differs from a cached one only in where its scopes sit
is served the cached executable with the old names; use a fresh
``JAX_COMPILATION_CACHE_DIR`` after moving a scope.

**The host half.**  What the host does before a program's first step
(importing, building, tracing, lowering, compiling or reading the
compilation cache) and to feed it after is a span from the second tuple,
``HOST_SPANS``, opened by ``host()`` where the work happens: the factories
(``make_train_step``, ``make_train_loop``, ``make_decoder``,
``train_stream``), the package's import of pallas, one layer's python and one
kernel's body while a program is traced, the input stream's worker.  A span
is a ``jax.profiler.TraceAnnotation`` named ``ompi_tpu:<name>``, so a
profile shows it on the device trace's clock
(``benchmarks/lib/clock.py`` names idle gaps after it), and one record in
memory on ``time.perf_counter``, so that a process can say where its
set-up went with no profiler: ``startup()``.  The three ``compile.*`` names
are not opened here: they are JAX's own timing of every program's trace,
lowering and backend compile (``jax.monitoring``), recorded under the
program's name by listeners that the first ``host()`` registers, once, for
the life of the process.  A stage of one of the package's own programs (one
a factory registered, ``program()``) and a top-level stage of any other are
records; a stage of any other program that begins inside a stage, a helper
(``multiply``, ``_where``, a plan's layer), is **folded**: no record, its
seconds stay the enclosing stage's, which counts it (``helpers``).  Always
on, like the stream's ``stats()``: no file, no exporter, no option.  The
flight recorder of ``mpi/trace.py`` belongs to the host MPI plane and
carries none of this (``OBSERVABILITY.md``, "The device path").

**The run half.**  What the host does once a job runs is three names more of
``HOST_SPANS``.  ``run.call`` is one call of a callable that a factory hands
out (``train_step``, ``train_loop``, a decoder's), from entry to the return
of its last dispatch; its ``id`` is the request's identifier, which
everything that begins inside it names through ``parent``; it carries ``n``,
the callable's calls so far, and at its start the process's CPU seconds,
involuntary context switches and major page faults.  ``run.dispatch`` is
one invocation of one jitted program object inside it (a plan's decoder:
``prefill``, then ``generate``): a ``compile.*`` stage that begins inside
is its child, so a call that compiles says which object, which call and
which stage.  ``run.gc`` is a pass of CPython's collector of a millisecond
or more (``gc.callbacks``; every pass is counted, by generation): it stops
every python thread whichever thread trips it, so it goes on no thread's
stack and belongs to the call it overlaps in time.  A call made while a
program is traced (``jax.jit`` over a decoder) is no run and leaves
nothing.  These records live in a store of their own, a ring of the newest
``RING`` beside counters that never wrap, because a job's operator wants
the newest where set-up wants the first: ``records()`` and ``startup()`` are
the start-up half's as they were, and ``run()`` reads this one.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import itertools
import resource
import statistics
import threading
import time
from typing import NamedTuple, Optional

__all__ = ["SCOPES", "COLL", "scope", "second", "coll",
           "HOST_SPANS", "Span", "host", "program", "records", "startup",
           "caller", "ran", "run", "run_records", "reset"]

# plain lower-case words; a dot says which scope a name belongs under
SCOPES = (
    "embed",            # token lookup
    "layers",           # the loop over layers, its slicing and stacking
    "attn_proj",        # ln1, q/k/v projections, rope, the wo projection
    "attention",        # scores, mask, softmax, context
    "attention.ring",   # ... K/V blocks around the sp ring
    "attention.ulysses",    # ... resharded seq -> heads by all_to_all
    "attention.flash",  # ... the pallas kernels
    "attention.gather",     # a cached step's selected K/V rows gathered out
                            # of the carry (nothing where it streams them)
    "attention.selected",   # a latent layer's read of the rows its index
                            # selected: the cached step's pass over the
                            # cache under the mask, the prefill's kernel
    "attention.window",     # a windowed layer's scores to context: a band
                            # of keys a block of queries, a ring when cached
    "attention.shared",     # a layer's read of another layer's K and V: its
                            # scores to context against a cache it does not own
    "ffn",              # ln2, the MLP, the residual
    "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
    "moe.groups",       # inside ``moe.route``: the groups of experts a token
                        # may pick in (group-limited top-k)
    "moe.shared",       # the shared expert every token passes
    "moe.zero",         # the identity experts' part: a token itself times
                        # the summed weights of its picks among them
    "ssm_proj",         # the mixer's scalings, in/out projections, gated norm
    "ssm.conv",         # ... its causal convolution (from a state, when cached)
    "ssm.scan",         # ... the chunked scan over a whole sequence
    "ssm.update",       # ... one cached step's recurrence: from reading the
                        # layer's state out of the carry to writing it back
    "gmu",              # a gated memory unit: ln1, its two products and the
                        # gate on another layer's scan output
    "kda_proj",         # the delta-rule mixer's ln1, six projections, L2 norms,
                        # gated norm and output projection
    "kda.conv",         # ... its three causal convolutions (from a state, cached)
    "kda.scan",         # ... the chunked recurrence over a whole sequence
    "kda.update",       # ... one cached step's recurrence: from reading the
                        # layer's matrix state out of the carry to writing it
    "retention.scan",   # power retention over a whole sequence: the chunked
                        # form's scan, or inside it the two that follow
    "retention.direct",     # ... a prefill's quadratic form summed over the
                            # whole prompt (the kernel) and the quotient
    "retention.end_state",  # ... the state after the last position, formed
                            # once
    "retention.update",     # ... one cached step's recurrence: from reading
                            # the layer's state out of the carry to writing
                            # it back, the expansion, the read for the query
                            # heads and the quotient inside it
    "lightning_proj",   # the lightning mixer's ln1, four projections, head
                        # norms, rotary embedding, gated norm and output
                        # projection
    "lightning.scan",   # ... the chunked recurrence over a whole sequence
    "lightning.update",     # ... one cached step's recurrence: from reading
                            # the layer's matrix state out of the carry to
                            # writing it
    "blocks.pool",      # a block selection's pooled keys: all of them in a
                        # whole-sequence pass, the one a cached step completes
    "blocks.score",     # ... a query's scores against them, the softmax, the
                        # group's sum, the pooling to blocks
    "blocks.select",    # ... the forced blocks and the topk: a threshold
    "mla_proj",         # latent attention's ln1, projections, latent norm and
                        # the cached step's absorbed products
    "mla_proj.rope",    # the same of the form that rotates (``MLA.theta``):
                        # a name of its own, the NoPE form's metrics' keys
                        # find nothing under it
    "mla_proj.query_latent",    # the same of the form with a query latent
                        # (``MLA.q_rank``: its down-projection, norm and
                        # up-projection too), rotating or not: a third name,
                        # the other two forms' metrics' keys find nothing
                        # under it
    "mla.rotate",       # ... the rotary embedding of the queries' rope part
                        # and of the shared key, inside either
    "index_proj",       # the index's three projections, key norm, rotary
    "index.score",      # ... its scores of a query against the index keys
    "index.select",     # ... the topk positions: a threshold, or a top-k
    # the same three of an index inside a latent layer (``MLA.index``):
    # names of their own, the K/V index's metrics' keys find nothing there
    "latent_index_proj", "latent_index.score", "latent_index.select",
    "loss",             # the unembed matmul and the cross entropy
    "optimizer",        # the optimizer's update and its application
    "prefill",          # backbone over the prompt, cache padding, first logits
    "decode.step",      # one cached token for the whole batch
    "kv_cache",         # ... writing the new K/V (and index key) into the cache
    "unembed",          # ... the vocabulary matmul
    "sample",           # ... picking the next token
    # a layer of two mixers and two MLPs (``models/plan.py``'s ``branches``:
    # a shortcut-connected layer): its second half's scopes, each opened
    # inside the name without the suffix, which so keeps its meaning and
    # holds both halves (``second()``; the cache's write has none)
    "attn_proj.second", "attention.second", "ffn.second",
)

# ``coll.<method>.<axes>``: one collective call site, named by the
# DeviceCommunicator method (or its lax equivalent's method) and the mesh
# axes it runs over, joined by "-"; ``allreduce_rows`` is the all-reduce
# that completes a table split by rows (``models/transformer._ROWS_SITE``)
COLL = "coll"


_SECOND = ".second"
_half = threading.local()       # ``second()``'s flag, of the tracing thread


def scope(name: str):
    """``with scope("attention"): ...`` around traced code.  Under
    :func:`second` a name that has a ``<name>.second`` in the vocabulary
    opens that inside itself."""
    import jax

    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in the scope vocabulary {SCOPES}")
    if getattr(_half, "second", False) and name + _SECOND in SCOPES:
        return jax.named_scope(f"{name}/{name}{_SECOND}")
    return jax.named_scope(name)


@contextlib.contextmanager
def second():
    """While the second mixer and MLP of a layer that has two are traced:
    the scopes they open (the same functions as the first's) also open their
    ``.second`` where the vocabulary has one, so a profile tells the halves
    apart and every reader of ``attention``, ``attn_proj`` or ``ffn`` still
    finds both."""
    was = getattr(_half, "second", False)
    _half.second = True
    try:
        yield
    finally:
        _half.second = was


def coll(method: str, axes):
    """``with coll("allreduce", "tp"): lax.psum(x, "tp")``."""
    import jax

    names = (axes,) if isinstance(axes, str) else tuple(axes)
    return jax.named_scope(f"{COLL}.{method}.{'-'.join(names)}")


# ---------------------------------------------------------------------------
# the host half
# ---------------------------------------------------------------------------

# ``<what>.<which>``; ``PERF.md`` section 3 says which metric reads which
HOST_SPANS = (
    "import.pallas",    # jax.experimental.pallas[.tpu] (``ops/_pallas.py``)
    "import.optax",     # the train step's optimizer library
    "build.train_step", "build.train_loop", "build.decoder", "build.stream",
                        # the bodies of the four factories
    "compile.trace",    # JAX's own clock, by program: python to a jaxpr
    "compile.lower",    # ... the jaxpr to an MLIR module
    "compile.backend",  # ... the backend: a compile, or a cache read
    "trace.layer",      # while a program is traced, one layer's python, by
                        # kind: a plan's mixer (``models/plan.py``), "block"
    "trace.kernel",     # ... one ``pallas_call``'s, the kernel's body traced
                        # with it, by the kernel's name (``ops/_pallas.py``)
    "data.produce",     # the input stream's worker: one host batch made
                        # and put on the devices
    # the run half: the ring's, read by ``run()``
    "run.call",         # one call of a callable a factory hands out
    "run.dispatch",     # ... one invocation of a jitted program object in it
    "run.gc",           # a pass of the collector, by generation ("gen2")
)
PREFIX = "ompi_tpu:"    # of the annotation's name in a profile
# The record keeps a process's first LIMIT spans and counts the rest
# (``startup()["dropped"]``): what it is read for is the start, and a long
# job's stream makes a span a batch.  Helpers being folded, a decoder's
# set-up is a few hundred.
LIMIT = 16384
# The run half keeps the newest RING of its spans (and each program object's
# first dispatch beside them): a train step is two spans, so two thousand
# steps back; the counters beside the ring never wrap.
RING = 4096
GC_RECORDED_FROM = 1e-3     # seconds: a shorter pass is counted, not recorded
_RUN = ("run.call", "run.dispatch", "run.gc")

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
# a stage's key in a program's row of startup()
_STAGE_KEYS = {"compile.trace": "trace_s", "compile.lower": "lower_s",
               "compile.backend": "backend_s"}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class Span(NamedTuple):
    """One record.  ``start`` and ``end`` are on ``time.perf_counter``;
    ``parent`` is the ``id`` of the innermost span that was open on the same
    thread when this one began, or None."""
    name: str                   # of HOST_SPANS
    program: Optional[str]      # the program built, traced, lowered, compiled;
                                # a ``trace.*`` span's layer kind, kernel name
    start: float
    end: float
    parent: Optional[int]
    id: int
    cache: Optional[str] = None     # compile.backend: "hit" | "miss"
    helpers: int = 0            # compile.*: the stages folded into this one
    built: Optional[int] = None     # compile.* of an own program, and
                                    # run.dispatch: which of the name's
                                    # objects (``Program.built``); run.call:
                                    # which callable (``Caller.made``)
    n: Optional[int] = None     # run.call: the callable's calls so far, this
                                # one included; run.dispatch: its call's
    # run.call, at its start: the process's CPU seconds (every thread's user
    # and system, ``time.process_time``), involuntary context switches and
    # major page faults (``getrusage(RUSAGE_SELF)``)
    cpu_s: Optional[float] = None
    switches: Optional[int] = None
    faults: Optional[int] = None


class _Open:
    """A span that has begun on a thread and not ended: what a later span
    of that thread names as its parent."""
    __slots__ = ("id", "name", "program", "cache", "helpers", "built", "n",
                 "went")
    folded = False

    def __init__(self, name: str, program: Optional[str]) -> None:
        self.id, self.name, self.program = next(_ids), name, program
        self.cache = None       # compile.backend: what the cache answered
        self.helpers, self.built = 0, None
        self.n = None           # run.*: the call's count
        self.went = 0           # run.*: backend stages that ended inside


class _Folded:
    """A helper's stage, which leaves no record: it stands on the thread's
    stack until it ends, under the ``id`` of the span around it (the parent
    of whatever begins inside it), so that ``_on_span`` can tell its end
    from that of a stage which began before the listeners were registered."""
    __slots__ = ("id", "name", "program", "cache", "into")
    folded = True

    def __init__(self, name: str, program: str, around: int,
                 into: _Open) -> None:
        self.id, self.name, self.program, self.cache = (around, name,
                                                        program, None)
        self.into = into        # the stage that keeps its seconds
        into.helpers += 1


class Program:
    """One program object a factory builds: ``name``, that of its jitted
    function; ``part``, which of a decoder's programs it is ("prefill",
    "generate", "whole") or None; ``built``, its place among the factories'
    calls (None once ``reset()`` has forgotten it).  The factory calls
    ``traced()`` as the first statement of the jitted function: python that
    runs when JAX traces the function and never when the program runs.
    Around each invocation of the jitted function the factory's callable
    opens ``dispatch()``; the counters are the run half's: ``dispatches``,
    ``compiles`` (backend stages that ended inside them) and ``recompiled``
    (dispatches after the first inside which a program went to the
    backend)."""
    __slots__ = ("name", "part", "built", "traces", "dispatches", "compiles",
                 "recompiled")

    def __init__(self, name: str, part: Optional[str], built: int) -> None:
        self.name, self.part, self.built, self.traces = name, part, built, 0
        self.dispatches = self.compiles = self.recompiled = 0

    def dispatch(self):
        """``with record.dispatch(): out = jitted(*args)``: a
        ``run.dispatch`` span, or nothing while a program is traced."""
        stack = _stack()
        if stack and _tracing(stack):
            return _NOTHING
        return _Dispatch(self, stack)

    def traced(self) -> None:
        """Inside the open ``compile.trace`` stage of the object's own jit:
        that stage is this object's, and so are the lowering and the backend
        stage that follow on the thread under its name."""
        self.traces += 1
        _traced_last()[self.name] = self.built
        for entry in reversed(_stack()):
            # folded: the name is no longer a registered one (``reset()``)
            if (entry.name == "compile.trace" and entry.program == self.name
                    and not entry.folded):
                entry.built = self.built
                break


class Caller:
    """One callable a factory hands out: ``program``, the name of what it
    runs; ``made``, its place among the factories' callables (None once
    ``reset()`` has forgotten it); the counters ``calls``, ``seconds`` (the
    host's, inside ``run.call``) and ``compiled`` (calls inside which a
    program went to the backend)."""
    __slots__ = ("program", "made", "calls", "seconds", "compiled")

    def __init__(self, program: str, made: int) -> None:
        self.program, self.made = program, made
        self.calls, self.seconds, self.compiled = 0, 0.0, 0

    def call(self):
        """``with run.call(): ...`` around one call's dispatches: a
        ``run.call`` span, or nothing while a program is traced."""
        stack = _stack()
        if stack and _tracing(stack):
            return _NOTHING
        return _Call(self)


def _tracing(stack: list) -> bool:
    """A ``compile.trace`` stage is open on the thread: what is called now
    is traced into that program and is no run."""
    return any(entry.name == "compile.trace" for entry in stack)


_NOTHING = contextlib.nullcontext()


def _new_totals() -> dict:
    return {"programs": 0, "backend_s": 0.0, "cache_hits": 0,
            "cache_misses": 0, "cache_retrieval_s": 0.0}


_lock = threading.Lock()        # the record, the totals, the registration
_records: list = []
_programs: list = []
_own: set = set()               # the names of _programs
_totals = _new_totals()
_dropped = 0
_ids = itertools.count()
_open = threading.local()       # .stack: the spans open on this thread;
                                # .last: name -> the object last traced on it
_listening = False
_offset = 0.0                   # perf_counter less time.time, at registration
# the run half's store.  A deque's append is atomic, so the collector's
# callback, which may fire while this thread holds ``_lock``, takes no lock.
_ring: collections.deque = collections.deque(maxlen=RING)
_firsts: dict = {}              # Program.built -> its first run.dispatch
_callers: list = []
_ran = 0                        # spans the ring was handed, kept or not
_annotation = None              # jax.profiler.TraceAnnotation, once listening


def _new_passes() -> dict:
    return {"passes": [0, 0, 0], "seconds": [0.0, 0.0, 0.0], "longest_s": 0.0}


_passes = _new_passes()         # of the collector, by generation
_pass = [0.0, None]             # the open pass: its start, its annotation


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _traced_last() -> dict:
    try:
        return _open.last
    except AttributeError:
        _open.last = {}
        return _open.last


def _append(span: Span, stack: list = ()) -> None:
    """``span`` has ended, and ``stack`` is what is still open on its
    thread.  A start-up span goes to the record; one that ended inside a run
    span (a ``compile.*`` stage inside a dispatch) to the ring as well,
    which still has it when the record is full."""
    global _dropped
    with _lock:
        if len(_records) < LIMIT:
            _records.append(span)
        else:
            _dropped += 1
    if stack and any(entry.name in _RUN for entry in stack):
        _keep(span)


def _keep(span: Span) -> None:
    global _ran
    _ring.append(span)
    _ran += 1


def _close(stack: list, entry) -> Optional[int]:
    """Take ``entry`` (and anything left open above it) off ``stack``; the
    id of its parent."""
    for i in range(len(stack) - 1, -1, -1):
        if stack[i] is entry:
            del stack[i:]
            break
    return stack[-1].id if stack else None


def _jax():
    """``jax``, with the listeners registered."""
    import jax

    if not _listening:
        _listen(jax)
    return jax


def _listen(jax) -> None:
    global _listening, _offset, _annotation
    with _lock:
        if _listening:
            return
        _listening = True
        # JAX stamps its stages on time.time(); the record is on perf_counter
        _offset = time.perf_counter() - time.time()
    _annotation = jax.profiler.TraceAnnotation
    gc.callbacks.append(_on_gc)
    monitoring = jax.monitoring
    monitoring.register_scalar_listener(_on_begin)
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def _program_of(fun_name: str) -> str:
    """``jit(train_step)`` (a lowering, a compile) and ``train_step`` (a
    trace) are one program."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_begin(event: str, _value, fun_name: str = "", **_kw) -> None:
    """A stage begins (JAX records its start as a scalar): it is open on
    this thread until ``_on_span`` closes it, so what happens inside it, an
    inner trace or a lazy import, is its child.  A stage of a program that
    no factory registered, inside another stage, is folded into that one."""
    name = _STAGES.get(event)
    if name is None:
        return
    prog, stack = _program_of(fun_name), _stack()
    if prog in _own:
        entry = _Open(name, prog)
        entry.built = _traced_last().get(prog)  # a trace: ``traced()`` says
        stack.append(entry)
        return
    for around in reversed(stack):
        if around.name in _STAGE_KEYS:
            stack.append(_Folded(name, prog, stack[-1].id,
                                 around.into if around.folded else around))
            return
    stack.append(_Open(name, prog))


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    name = _STAGES.get(event)
    if name is None:
        return
    prog, stack = _program_of(fun_name), _stack()
    if name == "compile.backend":
        with _lock:
            _totals["programs"] += 1
            _totals["backend_s"] += end - start
        for around in stack:    # the call and the dispatch it ended inside
            if around.name in _RUN:
                around.went += 1
    entry = next((e for e in reversed(stack)
                  if e.name == name and e.program == prog), None)
    if entry is None:       # it began before the listeners were registered
        entry = _Open(name, prog)
    parent = _close(stack, entry)
    if not entry.folded:
        _append(Span(name, prog, start + _offset, end + _offset, parent,
                     entry.id, entry.cache, entry.helpers, entry.built),
                stack)


def _on_event(event: str, **_kw) -> None:
    found = _CACHE.get(event)
    if found is None:
        return
    with _lock:
        _totals["cache_hits" if found == "hit" else "cache_misses"] += 1
    stack = _stack()    # the cache is asked inside the backend's stage
    if stack and stack[-1].name == "compile.backend":
        stack[-1].cache = found


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _RETRIEVAL:
        with _lock:
            _totals["cache_retrieval_s"] += seconds


class host:
    """``with host("build.decoder", program="decode"): ...`` around host
    work: an ``ompi_tpu:<name>`` annotation in a profile, and one record (a
    ``run.*`` name's in the ring, any other's in the start-up record)."""
    __slots__ = ("name", "program", "entry", "annotation", "start")

    def __init__(self, name: str, program: Optional[str] = None) -> None:
        if name not in HOST_SPANS:
            raise ValueError(f"{name!r} is not in the host span vocabulary "
                             f"{HOST_SPANS}")
        self.name, self.program = name, program

    def __enter__(self) -> "host":
        jax = _jax()
        self.entry = _Open(self.name, self.program)
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + self.name)
        _stack().append(self.entry)
        self.annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self.annotation.__exit__(*exc)
        stack = _stack()
        span = self._span(end, _close(stack, self.entry))
        if self.name in _RUN:
            _keep(span)
        else:
            _append(span, stack)

    def _span(self, end: float, parent: Optional[int]) -> Span:
        return Span(self.name, self.program, self.start, end, parent,
                    self.entry.id)


class _Call(host):
    """``Caller.call()``: the ``run.call`` span of one call."""
    __slots__ = ("caller", "at_start")

    def __init__(self, caller: Caller) -> None:
        self.name, self.program, self.caller = ("run.call", caller.program,
                                                caller)

    def __enter__(self) -> "_Call":
        caller = self.caller
        caller.calls += 1
        usage = resource.getrusage(resource.RUSAGE_SELF)
        self.at_start = (caller.calls, time.process_time(), usage.ru_nivcsw,
                         usage.ru_majflt)
        host.__enter__(self)
        self.entry.n = caller.calls
        return self

    def _span(self, end: float, parent: Optional[int]) -> Span:
        caller, entry = self.caller, self.entry
        caller.seconds += end - self.start
        caller.compiled += entry.went > 0
        return Span("run.call", self.program, self.start, end, parent,
                    entry.id, None, 0, caller.made, *self.at_start)


class _Dispatch(host):
    """``Program.dispatch()``: the ``run.dispatch`` span of one invocation
    of the object's jitted function, inside ``stack``'s call if any."""
    __slots__ = ("record", "n")

    def __init__(self, record: Program, stack: list) -> None:
        self.name, self.program, self.record = ("run.dispatch", record.name,
                                                record)
        # the call's, where one is open around it
        self.n = getattr(stack[-1], "n", None) if stack else None

    def _span(self, end: float, parent: Optional[int]) -> Span:
        record, went = self.record, self.entry.went
        first = not record.dispatches
        record.dispatches += 1
        record.compiles += went
        record.recompiled += bool(went) and not first
        span = Span("run.dispatch", self.program, self.start, end, parent,
                    self.entry.id, None, 0, record.built, self.n)
        if first and record.built is not None:
            _firsts.setdefault(record.built, span)
        return span


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: every pass counted by generation, a pass of
    ``GC_RECORDED_FROM`` seconds or more a ``run.gc`` span in the ring.  A
    pass can begin between any two bytecodes of any thread, so it goes on no
    thread's stack; it takes no lock and never raises.  Two clock reads a
    pass, and an annotation only while a profile is taken."""
    try:
        if phase == "start":
            _pass[1] = (_annotation(PREFIX + "run.gc")
                        if _annotation.is_enabled() else None)
            _pass[0] = time.perf_counter()
            return
        end = time.perf_counter()
        start, annotation = _pass
        _pass[:] = 0.0, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if not start:           # it began before the callback was registered
            return
        gen, seconds, passes = min(info["generation"], 2), end - start, _passes
        passes["passes"][gen] += 1
        passes["seconds"][gen] += seconds
        passes["longest_s"] = max(passes["longest_s"], seconds)
        if seconds >= GC_RECORDED_FROM:
            _keep(Span("run.gc", f"gen{gen}", start, end, None, next(_ids)))
    except Exception:   # noqa: BLE001 - a record is never worth a job
        pass


class _Ran:
    """A jitted function that a factory hands out itself (a train step): a
    call is a ``run.call`` span and inside it a ``run.dispatch`` around the
    jitted function's own call (its fast path); ``lower`` and every other
    attribute are the jitted function's.  One python frame under the first
    call, which a decoder's callables do without (``decode._greedy``):
    set-up's seconds on the chip's host follow how deep that call is made."""
    __slots__ = ("_jitted", "_record", "_caller")

    def __init__(self, jitted, record: Program) -> None:
        self._jitted, self._record = jitted, record
        self._caller = caller(record.name)

    def __call__(self, *args, **kwargs):
        with self._caller.call(), self._record.dispatch():
            return self._jitted(*args, **kwargs)

    def __getattr__(self, name: str):
        return getattr(object.__getattribute__(self, "_jitted"), name)


def ran(jitted, record: Program) -> _Ran:
    """``jitted``, the function of the program object ``record``, as a
    callable of its own with the run half's spans around each call."""
    return _Ran(jitted, record)


def program(name: str, part: Optional[str] = None) -> Program:
    """Called by the factory that builds the program ``name`` (the name of
    its jitted function), once for each program object; ``part`` says which
    of a factory's objects this one is.  The record then knows ``name`` for
    one of the package's own programs, and the handle counts that object's
    traces and marks its stages."""
    with _lock:
        handle = Program(name, part, len(_programs))
        _programs.append(handle)
        _own.add(name)
    return handle


def caller(name: str) -> Caller:
    """Called by the factory for each callable it hands out that runs the
    program ``name``: the handle counts the callable's calls and opens their
    ``run.call`` spans (``Caller.call``)."""
    with _lock:
        handle = Caller(name, len(_callers))
        _callers.append(handle)
    return handle


def records() -> list:
    """The start-up half's spans recorded so far, in the order they ended
    (the run half's are ``run()``'s)."""
    with _lock:
        return list(_records)


def run_records() -> list:
    """The run half's spans held now, by start: the ring's newest ``RING``
    (``run.*``, and the stages that ended inside one) and each program
    object's first dispatch."""
    return _held(startup=False)


def reset() -> None:
    """Forget both records, the registered programs and callables and the
    totals (for tests; the listeners stay)."""
    global _dropped, _totals, _passes, _ran
    with _lock:
        del _records[:]
        for handle in _programs:
            handle.built = None
        del _programs[:]
        _own.clear()
        _totals = _new_totals()
        _dropped = 0
        for handle in _callers:
            handle.made = None
        del _callers[:]
        _ring.clear()
        _firsts.clear()
        _passes, _ran = _new_passes(), 0


def _row() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": None,
            "traces": 0, "compiles": 0, "helpers": 0}


def startup(spans: Optional[list] = None) -> dict:
    """Where the host's time went, from the record (or from ``spans``).

    Seconds are **self time by containment**: a span's duration less its
    children's, because spans nest (a ``trace.layer`` lies inside
    ``decode``'s trace, a ``trace.kernel`` inside that, a lazy
    ``import.pallas`` inside that) and a plain sum would count those seconds
    twice.  A folded helper's seconds are its enclosing stage's own.

    - ``spans``: seconds by span name.
    - ``programs``: the package's own programs (those a factory registered),
      a row each name: ``trace_s``, ``lower_s``, ``backend_s``; ``cache``,
      "miss" where a compile of it missed the persistent cache, else "hit"
      where one was read from it, else None; ``traces``, how often the
      python bodies of its objects ran (``Program.traced``); ``compiles``,
      how often it went to the backend; ``helpers``, the helper stages
      folded into its own.  A stage inside another stage counts for the
      outermost program of the package's own around it, else for the
      outermost.  A ``trace.*`` span inside a stage counts for that stage's
      program under that stage's key: ``trace_s`` is the whole trace, its
      layers and kernels included (an ``import.pallas`` there is not).
    - ``calls``: the same seconds by program object, a row each in the order
      the factories were called: ``program``, ``part``, ``built``,
      ``trace_s``, ``lower_s``, ``backend_s``, ``cache``, ``traces``,
      ``helpers``.  A name's row of ``programs`` is the sum of its objects'
      rows; a stage that no object marked (a made-up record) is the name's
      last object's.
    - ``others``: the rows of ``programs`` for every other program of the
      process that has a record (a top-level one: the draws, the
      reference's); their ``traces`` are JAX's trace events, and JAX also
      records one, of microseconds, for a call that misses the jitted
      function's fast path and finds its jaxpr cached.
    - ``trace``: the ``trace.*`` spans, by span name and then by layer kind
      or kernel name: ``seconds``, ``own_s`` (those inside the package's own
      programs) and ``spans``, how many.
    - ``retraces``: traces of an own program beyond the first of each
      distinct program object.
    - ``totals``: the process's counts since the listeners were registered:
      ``programs`` sent to the backend, ``backend_s`` there, the persistent
      cache's ``cache_hits``, ``cache_misses`` and ``cache_retrieval_s``.
    - ``records``, ``dropped``: spans kept, and spans beyond ``LIMIT``.
    """
    with _lock:
        calls = [{"program": p.name, "part": p.part, "built": p.built,
                  "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                  "cache": None, "traces": p.traces, "helpers": 0}
                 for p in _programs]
        totals, dropped = dict(_totals), _dropped
    last = {call["program"]: call for call in calls}    # a name's last object
    if spans is None:
        spans = records()
    by_id = {s.id: s for s in spans}
    inside = collections.defaultdict(float)     # id -> its children's seconds
    for s in spans:
        if s.parent in by_id:
            inside[s.parent] += s.end - s.start

    def stages(span: Span):
        """The stages around ``span``, itself included: the innermost, the
        outermost and the outermost of an own program."""
        innermost = outermost = outermost_own = None
        while span is not None:
            if span.name in _STAGE_KEYS:
                innermost, outermost = innermost or span, span
                if span.program in last:
                    outermost_own = span
            span = by_id.get(span.parent)
        return innermost, outermost, outermost_own

    by_name = collections.defaultdict(float)
    programs = collections.defaultdict(_row)
    others = collections.defaultdict(_row)
    traced = collections.defaultdict(
        lambda: collections.defaultdict(
            lambda: {"seconds": 0.0, "own_s": 0.0, "spans": 0}))
    for s in spans:
        self_s = max(0.0, s.end - s.start - inside[s.id])
        by_name[s.name] += self_s
        is_stage = s.name in _STAGE_KEYS
        if not (is_stage or s.name.startswith("trace.")):
            continue
        innermost, outermost, own = stages(s)
        if not is_stage:
            entry = traced[s.name][s.program]
            entry["seconds"] += self_s
            entry["own_s"] += self_s if own is not None else 0.0
            entry["spans"] += 1
            if innermost is None:   # a kernel called outside any program
                continue
        if own is None:
            row = others[outermost.program]
            into = (row,)
        else:
            row, call = programs[own.program], last[own.program]
            if (own.built is not None and own.built < len(calls)
                    and calls[own.built]["program"] == own.program):
                call = calls[own.built]
            into = (row, call)
        for sums in into:
            sums[_STAGE_KEYS[innermost.name]] += self_s
            sums["helpers"] += s.helpers
            if s.name == "compile.backend" and (s.cache == "miss"
                                                or sums["cache"] is None):
                sums["cache"] = s.cache
        row["compiles"] += s.name == "compile.backend"
        row["traces"] += (s.name == "compile.trace"     # an own row's: below
                          and s.program == outermost.program)
    for name, row in programs.items():
        # counted where the python body ran
        row["traces"] = sum(c["traces"] for c in calls if c["program"] == name)
    return {"spans": dict(by_name), "programs": dict(programs),
            "calls": calls, "others": dict(others),
            "trace": {name: dict(by) for name, by in traced.items()},
            "retraces": sum(max(0, c["traces"] - 1) for c in calls),
            "totals": totals, "records": len(spans), "dropped": dropped}


# ---------------------------------------------------------------------------
# the run half's reduction
# ---------------------------------------------------------------------------

_INSIDE_FIRST = ("compile.", "trace.", "import.")   # not a first call's rest


def _held(startup: bool = True) -> list:
    """Every span ``run()`` reads: each object's first dispatch, the ring,
    and with ``startup`` the start-up record (the stages inside a
    dispatch), each once, by start."""
    while True:
        try:
            ring = list(_ring)
            break
        except RuntimeError:    # the collector's callback appended meanwhile
            continue
    with _lock:
        spans = {s.id: s for s in _records} if startup else {}
        spans.update((s.id, s) for s in _firsts.values())
    spans.update((s.id, s) for s in ring)
    return sorted(spans.values(), key=lambda s: s.start)


def _quiet_row(seconds: list) -> dict:
    return {"quiet": len(seconds),
            "median_s": statistics.median(seconds) if seconds else None,
            "total_s": sum(seconds),
            "longest_s": max(seconds, default=None)}


class _Passes:
    """The recorded passes of the collector, by start: the seconds of them
    that lie inside an interval."""

    def __init__(self, spans: list) -> None:
        self.spans = sorted((s for s in spans if s.name == "run.gc"),
                            key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]
        # a pass stops every thread: no two overlap, so ends ascend too
        self.ends = [s.end for s in self.spans]

    def inside(self, lo: float, hi: float) -> float:
        first = bisect.bisect_right(self.ends, lo)
        last = bisect.bisect_left(self.starts, hi)
        return sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                   for s in self.spans[first:last])


def run(spans: Optional[list] = None) -> dict:
    """What the host did while the job ran, from the run half's record (or
    from ``spans``; the counters are the handles' either way).

    - ``callables``: a row for each callable a factory handed out:
      ``program``, ``made``; the counters ``calls``, ``host_s`` (seconds
      inside ``run.call``) and ``compiled`` (calls inside which a program
      went to the backend); and of the held calls in which none did,
      ``quiet``, how many, their ``median_s``, ``total_s`` and
      ``longest_s``.
    - ``programs``: a row for each program object: ``program``, ``part``,
      ``built``; the counters ``dispatches``, ``compiles`` (backend stages
      inside them) and ``recompiled`` (dispatches after the first inside
      which a program went to the backend); ``first_s``, its first
      dispatch's seconds, and ``first_rest_s``, that less the ``compile.*``,
      ``trace.*`` and ``import.*`` spans inside it: what lies between the
      backend's return and the dispatch's; ``recompiles``, of the held
      dispatches after the first, ``{"n", "backend_s"}`` for each backend
      stage inside one: which call recompiled.
    - ``gc``: ``gen0`` to ``gen2``, ``{"passes", "seconds"}`` each, since
      the listeners were registered; ``longest_s``; ``recorded``, the held
      passes (of ``GC_RECORDED_FROM`` seconds or more); ``in_calls_s``,
      their seconds that overlap a quiet call, on whatever thread.
    - ``jobs``: every held call to the next in time, whichever callable's
      (a caller's job starts with a call and ends where its next call
      starts: its wait for the device and its read-back lie in between):
      ``program``, ``made``, ``n`` (the earlier call's), ``wall_s``,
      ``call_s`` (the host's seconds inside the call), and over the job
      ``cpu_s``, ``switches``, ``faults`` and ``gc_s`` (recorded passes).
      A stall with collector seconds is the collector's; one with a jump
      in ``switches`` a host that was descheduled; ``cpu_s`` ticks in the
      host's steps (10 ms on the chip's), so one job's cannot tell a wait
      from busy threads below python.
    - ``records``, ``wrapped``: run spans held, and spans that the ring has
      let go.
    """
    with _lock:
        callables = [{"program": c.program, "made": c.made, "calls": c.calls,
                      "host_s": c.seconds, "compiled": c.compiled}
                     for c in _callers]
        programs = [{"program": p.name, "part": p.part, "built": p.built,
                     "dispatches": p.dispatches, "compiles": p.compiles,
                     "recompiled": p.recompiled, "first_s": None,
                     "first_rest_s": None, "recompiles": []}
                    for p in _programs]
        passes = {"passes": list(_passes["passes"]),
                  "seconds": list(_passes["seconds"]),
                  "longest_s": _passes["longest_s"]}
        wrapped = _ran - len(_ring)
    if spans is None:
        spans = _held()
    by_id = {s.id: s for s in spans}
    children = collections.defaultdict(list)
    for s in spans:
        if s.parent in by_id:
            children[s.parent].append(s)

    def backend_stages(span: Span) -> list:
        found = []
        for child in children[span.id]:
            if child.name == "compile.backend":
                found.append(child)
            found += backend_stages(child)
        return found

    collector = _Passes(spans)
    quiet = collections.defaultdict(list)       # made -> quiet calls' seconds
    calls = []                                  # every call, by start
    in_calls = 0.0
    for s in spans:
        if s.name != "run.call":
            continue
        calls.append(s)
        if not backend_stages(s):
            quiet[s.built].append(s.end - s.start)
            in_calls += collector.inside(s.start, s.end)
    for row in callables:
        row.update(_quiet_row(quiet[row["made"]]))

    firsts: dict = {}                           # built -> its first dispatch
    for s in spans:
        if s.name != "run.dispatch" or s.built is None:
            continue
        row = programs[s.built] if s.built < len(programs) else None
        if row is None or row["program"] != s.program:
            continue
        first = firsts.setdefault(s.built, s)
        if s is first:
            row["first_s"] = s.end - s.start
            row["first_rest_s"] = max(0.0, row["first_s"] - sum(
                c.end - c.start for c in children[s.id]
                if c.name.startswith(_INSIDE_FIRST)))
        else:
            row["recompiles"] += [{"n": s.n, "backend_s": b.end - b.start}
                                  for b in backend_stages(s)]

    calls.sort(key=lambda s: s.start)
    jobs = [{"program": a.program, "made": a.built, "n": a.n,
             "wall_s": b.start - a.start, "call_s": a.end - a.start,
             "cpu_s": b.cpu_s - a.cpu_s, "switches": b.switches - a.switches,
             "faults": b.faults - a.faults,
             "gc_s": collector.inside(a.start, b.start)}
            for a, b in zip(calls, calls[1:]) if a.cpu_s is not None
            and b.cpu_s is not None]
    return {
        "callables": callables,
        "programs": programs,
        "gc": {**{f"gen{g}": {"passes": passes["passes"][g],
                              "seconds": passes["seconds"][g]}
                  for g in range(3)},
               "longest_s": passes["longest_s"],
               "recorded": len(collector.spans), "in_calls_s": in_calls},
        "jobs": jobs,
        "records": sum(s.name in _RUN for s in spans), "wrapped": wrapped}
