"""The generating programs of the three cells with a layer plan, compiled at
the cells' real sizes for a v5e that is described and not attached
(``tests/benchmarks/test_fits.py``'s idiom).  Nothing runs and nothing here
is a time: what is read is the compiled programs' text and memory.  No cached
step writes a weight: a layer's leaves are static slices of their kind's
stacks, which the compiler reads where they lie, inside the product's own
fusion.  Cell 9's parent did not: the lightning mixer's q, k and v products
had the head reshape and the head norm's sums folded into them, wanted their
weights with ``D`` minor, and each step copied every layer's matrix out of
the re-laid stack first (three ``slice`` fusions of ``bf16[3,4096,4096]``,
603 MB read and written a step, 10% of it: PR 53).  Cell 10's cached step
reads each latent layer's cache by one call of ``latent_decode`` on the carry
as it lies (the parent read it twice, in two fusions: PR 57).  Cell 7's
passes a delta-rule layer's state through ``kda_update`` and through nothing
else as large (a copy of a layer's state is 805 MB a step and 0.75 GiB of
the chip), in the buffer it lies in.
"""

import math
import re

from tests.parallel.compiled import (INSTRUCTION, MOVES, _cell, _peak,
                                     _program)

CELL_9 = "minicpm-sala.decode-16k-512-b24"
CELL_7 = "kimi-linear-48b-a3b.decode-512-128-b384"
CELL_10 = "kimi-vl-a3b.decode-16k-256-b32"
# cell 9's generating program at the parent (CPU box, PR 53): the re-laid
# stacks, 288 MiB standing and as much again of layers' copies, were in both
PARENT_TEMP_BYTES = 501_877_248
PARENT_PEAK_BYTES = 5_197_119_488
# cell 7's (``traffic/decode-512-128-b384.json``'s ``batch_why``: arguments +
# results + temporaries - written in place)
CELL_7_PARENT_PEAK_GIB = 13.15
# an instruction with its result's types whole, an array or a tuple of them
# (``INSTRUCTION`` reads single arrays: what is inside a fusion)
RESULTS = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$", re.M)
# what names or views a buffer inside a fusion that does nothing but move
VIEWS = ("parameter", "bitcast", "reshape", "tuple", "get-tuple-element")
_COMPILED = {}


def _generating(name, chip):
    """(the program's configuration, the cell's job, its generating program
    compiled for ``chip``), compiled once a cell; call under
    ``for_the_chip``."""
    if name not in _COMPILED:
        cfg, job = _cell(name, chip)
        fn, args = _program(job, chip, 1)
        _COMPILED[name] = (cfg, job, fn.lower(*args).compile())
    return _COMPILED[name]


def _sizes(dims) -> tuple:
    """An array's sizes above one, sorted: what a move in any order of the
    axes, with or without an axis of one, leaves alike."""
    return tuple(sorted(int(d) for d in dims if int(d) > 1))


def _weights(cfg) -> set:
    """The sizes, sorted, of one layer or of several of every matrix, of
    ``d_model`` squared elements or more, that is stacked over a kind's
    layers: what a copy, a slice or a relayout of a weight results in,
    whatever the order it leaves the axes in."""
    from ompi_tpu.models import plan

    sizes = set()
    for n, leaves in plan._kinds(cfg).values():
        for dims, _deviation in leaves.values():
            if math.prod(dims) >= cfg.d_model ** 2:
                sizes |= {_sizes((layers, *dims))
                          for layers in range(1, n + 1)}
    return sizes


def _step_bodies(text) -> tuple:
    """(every computation of the program by name, the names of the scan over
    steps' body and of every loop and call inside it): what the compiler
    hoists out of the loop keeps its name and is not a step's."""
    computations = dict(re.findall(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    [steps] = [line for line in text.splitlines() if " while(" in line
               and 'decode.step/while"' in line]
    bodies, called = [], [re.search(r"body=%([\w.-]+)", steps).group(1)]
    while called:
        bodies.append(called.pop())
        called += [name for name in re.findall(
            r"(?:body|condition|to_apply)=%([\w.-]+)",
            computations[bodies[-1]]) if name not in bodies]
    return computations, bodies


def _moves(text, moved) -> list:
    """The instructions of the step's body, outside any fusion, that move an
    array ``moved`` says yes to (asked with the sizes, sorted, of each of an
    instruction's results): a move by name or a fusion of nothing but moves.
    A product that reads a slice inside its own fusion is none, nor is the
    compiler's asynchronous prefetch (``slice-start``), which writes no
    HBM."""
    computations, bodies = _step_bodies(text)

    def only_moves(rest):
        body = computations[re.search(r"calls=%([\w.-]+)", rest).group(1)]
        return all(op in MOVES or op in VIEWS
                   for _n, _d, op, _r in INSTRUCTION.findall(body))

    found = []
    for body in bodies:
        for instruction, types, op, rest in RESULTS.findall(
                computations[body]):
            results = [_sizes(dims.split(","))
                       for dims in re.findall(r"\w+\[([\d,]+)\]", types)]
            if any(map(moved, results)) and (
                    op in MOVES or op == "fusion" and only_moves(rest)):
                found.append((instruction, types.split("{")[0], op))
    return found


def _weight_moves(cfg, text) -> list:
    """``_moves`` of a weight: a layer's matrix or several layers'."""
    return _moves(text, _weights(cfg).__contains__)


def test_cell_9_steps_move_no_weight(chip, for_the_chip):
    cfg, _job, compiled = _generating(CELL_9, chip)
    lt = cfg.plan.lightning
    assert (cfg.d_model, lt.width) == (4096, 4096)
    assert (4096, 4096) in _weights(cfg) and (3, 4096, 4096) in _weights(cfg)
    # the parent: fusion.587, fusion.584, fusion.563, each ``lt_k``, ``lt_q``
    # or ``lt_v`` out of its stack as three ``bf16[1,4096,4096]``
    moved = _weight_moves(cfg, compiled.as_text())
    assert not moved, moved


def test_cell_9s_generating_program_holds_no_relaid_stack(chip, for_the_chip):
    cfg, _job, compiled = _generating(CELL_9, chip)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= PARENT_TEMP_BYTES
    assert _peak(memory) <= PARENT_PEAK_BYTES, _peak(memory) / 2 ** 30
    # nothing before the loop re-lays a stack either (the parent: copy.235
    # to .237): every product reads the layout the leaves are stored in
    weights = _weights(cfg)
    relaid = [(name, dims) for name, dims, op, _rest
              in INSTRUCTION.findall(compiled.as_text())
              if op in ("copy", "transpose") and dims
              and _sizes(dims.split(",")) in weights]
    assert not relaid, relaid


def test_cell_7_steps_move_no_weight(chip, for_the_chip):
    cfg, _job, compiled = _generating(CELL_7, chip)
    assert (128, 1024, 2304) in _weights(cfg)       # a layer's held experts
    moved = _weight_moves(cfg, compiled.as_text())
    assert not moved, moved


def test_cell_7_steps_pass_each_state_through_the_kernel_alone(
        chip, for_the_chip):
    cfg, job, compiled = _generating(CELL_7, chip)
    text = compiled.as_text()

    kd = cfg.plan.kda
    state = (job.batch, kd.n_heads, kd.head_dim, kd.head_dim)
    shapes = {",".join(map(str, dims)) for dims in (state, (1, *state))}
    # a name for a buffer, not a pass over it
    names = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
    passes = [(name, dims, op)
              for name, dims, op, _rest in INSTRUCTION.findall(text)
              if dims in shapes and op not in names]
    assert not passes, passes
    # the kernel's result is a tuple (o, state), which the pattern above,
    # of single arrays, does not read: the calls are counted by name
    kernels = re.findall(
        rf"= \(f32\[[\d,]+\]\S* f32\[{','.join(map(str, state))}\]\S*\) "
        rf"custom-call\([^\n]*kda_update", text)
    assert len(kernels) == cfg.plan.count("kda") == 4

    memory = compiled.memory_analysis()
    # every state is written where it lies: 4 x 805 MB and the convolutions'
    assert memory.alias_size_in_bytes > 4 * 4 * math.prod(state)
    peak, most = _peak(memory), CELL_7_PARENT_PEAK_GIB * 2 ** 30 + (64 << 20)
    assert peak < most, peak / 2 ** 30


def test_cell_10_steps_read_each_latent_cache_by_one_kernel_call(
        chip, for_the_chip):
    cfg, _job, compiled = _generating(CELL_10, chip)
    text = compiled.as_text()
    computations, bodies = _step_bodies(text)
    ml = cfg.plan.mla
    cache = (32, 16_384, ml.cached)             # 604 MB a layer
    assert (ml.kv_rank, ml.rope, len(cfg.plan.layers)) == (512, 64, 5)
    # a layer, a call: its operands the position, the absorbed queries and
    # the cache that the row's write results in, in the carry's own layout
    calls = [(types, rest) for body in bodies for _name, types, op, rest
             in RESULTS.findall(computations[body])
             if op == "custom-call" and "latent_decode" in rest]
    assert len(calls) == 5
    written = set()
    for types, rest in calls:
        assert types.startswith("f32[32,16,512]")
        operands = re.match(r"([^)]*)\)", rest).group(1).split(", ")
        assert len(operands) == 3 and "dynamic_update_slice" in operands[2]
        written.add(operands[2])
        assert "bf16[32,16384,576]{2,1,0}" in rest      # no other layout
    assert len(written) == 5
    # the cache goes through a step as the loop's own buffer: written in
    # place, and nothing as large is copied, re-laid, padded, cut or converted
    large = math.prod(cache)
    moved = _moves(text, lambda sizes: math.prod(sizes) >= large)
    assert not moved, moved
    passes = {op for body in bodies for _name, types, op, _rest
              in RESULTS.findall(computations[body])
              if _sizes(cache) in {_sizes(dims.split(",")) for dims
                                   in re.findall(r"\w+\[([\d,]+)\]", types)}}
    assert passes <= {*VIEWS, "dynamic-update-slice", "while", "call"}, passes
