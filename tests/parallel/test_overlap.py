"""The train step's all-reduces run under its compute, where there is a
mesh to sum over and a TPU to compile for, and nowhere else.

The four-chip train step is compiled for a v5e:2x2 that is described and
not attached (the chip's own compiler, installed here; nothing runs and
nothing here is a measurement): with the step's own compile options
(``transformer._OVERLAP_OPTIONS``) the backward loop's body holds
asynchronous pairs, ``async-collective-start`` ... ``async-collective-done``,
around fusions that hold a part of an all-reduce and a matmul: a layer's six
gradient sums over dp and one half of each block's psum over tp, which
``layers.row_parallel`` makes and transposes by halves of the sequences
(the forward loop's body holds the forward's two).  The
one-chip step is handed no option and lowers to the text of the step as it
was before the gradient moved inside the shard_map (a local copy below); a
mesh of CPU devices is handed none either, or its backend would refuse the
compile ("No such compile option").
"""

import re

import jax
import numpy as np
import pytest

from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel.mesh import make_mesh
from tests.parallel.compiled import _cell

FOUR_CHIPS = "pythia-6.9b-widths.train-2k-dp2tp2"
ONE_CHIP = "pythia-1.4b-widths.train-2k"
# every test of the file: the cache and the suite's interpret mode off
pytestmark = pytest.mark.usefixtures("for_the_chip")
_FOUR_CHIP_STEP = {}


@pytest.fixture
def four_chip_step(chip, for_the_chip):
    """name -> lines of every computation of the four-chip cell's real
    ``train_step`` as the v5e's compiler scheduled it: one compile for the
    tests that read it."""
    if not _FOUR_CHIP_STEP:
        fn, args = _cell(FOUR_CHIPS, chip)[1].programs()["train_step"]
        _FOUR_CHIP_STEP.update(
            _computations(fn.lower(*args).compile().as_text()))
    return _FOUR_CHIP_STEP


def _computations(text):
    """name -> lines of every computation of a compiled program's text."""
    out, name = {}, None
    for line in text.splitlines():
        opened = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if opened and not line.startswith(" "):
            name = opened.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _backward_body(computations):
    """The backward loop's body: where a layer's gradient sums over dp end."""
    bodies = [lines for lines in computations.values()
              if any(re.match(r"\s*%async-collective-done[\w.]* = .*transpose"
                              r"\(jvp\(layers\)\)/while/body/.*coll\.allreduce"
                              r"\.dp/", line) for line in lines)]
    assert len(bodies) == 1, [len(b) for b in bodies]
    return bodies[0]


def _async_starts(lines):
    return [line for line in lines
            if re.match(r"\s*%async-collective-start(\.\d+)? = ", line)]


def _forward_body(computations):
    """The forward loop's body: where a block's psum over tp is made, and no
    fusion's computation."""
    fused = {m.group(1) for lines in computations.values() for line in lines
             if (m := re.search(r"calls=%([\w.\-]+)", line))}
    bodies = [lines for name, lines in computations.items()
              if name not in fused
              and any((m := _TP_PSUM.match(line))
                      and m.groups()[2:] == ("all-reduce", "jvp(layers)")
                      for line in lines)]
    assert len(bodies) == 1, [len(b) for b in bodies]
    return bodies[0]


# an instruction of a loop's body under a block's ``coll.allreduce.tp``:
# (name, type, opcode, pass)
_TP_PSUM = re.compile(
    r"\s*%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(.*op_name=\"[^\"]*?"
    r"((?:transpose\()?jvp\(layers\)\)?)/while/body/[^\"]*coll\.allreduce\.tp/")


def _opcodes(lines):
    return {m.group(1) for line in lines
            if (m := re.search(r" ([a-z][\w-]*)\(", line.split(" = ", 1)[-1]))}


def test_four_chip_step_runs_all_reduces_under_matmuls(four_chip_step):
    computations = four_chip_step
    body = _backward_body(computations)
    starts = _async_starts(body)
    assert len(starts) >= 2, len(starts)
    # between a start and its done: fusions that continue the all-reduce
    # beside a matmul
    under = [re.search(r"calls=%(async_collective_fusion[\w.]*)", line)
             for line in body]
    under = [m.group(1) for m in under if m]
    assert len(under) >= len(starts)
    for name in under:
        opcodes = _opcodes(computations[name])
        assert {"all-reduce", "convolution"} <= opcodes, (name, opcodes)


def test_four_chip_step_sums_half_of_the_table_over_the_dp_pair(
        four_chip_step):
    """The table is stored by rows over ``tp``: the scheduled step holds no
    all-reduce of the whole table's gradient, f32[50432, 4096], and one of a
    rank's rows, f32[25216, 4096], between the two chips of a dp pair; and
    a layer's backward holds its eight asynchronous pairs: six gradient
    sums over dp and a half of each block's psum over tp."""
    reduces = [line for lines in four_chip_step.values() for line in lines
               if re.search(r" all-reduce(-start)?\(", line)]
    assert not [line for line in reduces if "f32[50432,4096]" in line]
    table = [line for line in reduces if "f32[25216,4096]" in line]
    assert len(table) == 1, table
    assert "replica_groups={{0,2},{1,3}}" in table[0], table[0]
    assert len(_async_starts(_backward_body(four_chip_step))) == 8


def _halves_under_products(computations, body, where):
    """Of a loop's ``body``, the pairs and the synchronous all-reduces under
    a block's ``coll.allreduce.tp`` in the pass ``where``, each as its
    type, after the check that every pair is around a fusion that holds an
    all-reduce and a convolution."""
    found = [m.groups() for line in body if (m := _TP_PSUM.match(line))
             and m.group(4) == where]
    done = [(name, kind) for name, kind, opcode, _ in found
            if name.startswith("async-collective-done")]
    for name, _ in done:
        start = name.replace("done", "start")
        at = [i for i, line in enumerate(body)
              if re.match(rf"\s*%({re.escape(start)}|{re.escape(name)}) = ",
                          line)]
        assert len(at) == 2, (name, at)
        between = [m.group(1) for line in body[at[0] + 1:at[1]]
                   if (m := re.search(
                       r"calls=%(async_collective_fusion[\w.]*)", line))]
        assert between, name
        for fusion in between:
            assert {"all-reduce", "convolution"} <= _opcodes(
                computations[fusion]), (name, fusion)
    return ([kind for _, kind in done],
            [kind for _, kind, opcode, _ in found if opcode == "all-reduce"])


def test_four_chip_step_sums_each_tp_psum_by_halves(four_chip_step):
    """Of a block's psum over tp, bf16[4,2048,4096], each pass sums the two
    halves of the sequences apart: one half a synchronous all-reduce, the
    other an asynchronous pair named ``coll.allreduce.tp`` around a fusion
    that holds the all-reduce and the other half's product.  No whole one is
    left in either loop's body, and the backward's makes none again (the
    forward's sums are kept by name).  What S5.4c starts from: the forward's
    two synchronous halves and what its two pairs still wait."""
    half = "bf16[2,2048,4096]"
    body = _backward_body(four_chip_step)
    assert not [line for line in body if "rematted_computation" in line
                and "coll.allreduce.tp/psum" in line]
    assert not [line for line in body
                if (m := _TP_PSUM.match(line)) and m.group(4) == "jvp(layers)"]
    for lines, where in ((body, "transpose(jvp(layers))"),
                         (_forward_body(four_chip_step), "jvp(layers)")):
        pairs, synchronous = _halves_under_products(
            four_chip_step, lines, where)
        assert pairs == synchronous == [half, half], (where, pairs,
                                                      synchronous)
    assert len(_async_starts(_forward_body(four_chip_step))) == 2


def _step_before(cfg, mesh, lr):
    """The train step as it was while shard_map's transpose summed the
    gradients: ``jax.value_and_grad`` outside ``make_loss_fn``."""
    import functools

    import optax

    from ompi_tpu.core.scopes import scope

    loss_fn = tfm.make_loss_fn(cfg, mesh)
    opt = optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.01,
                      mu_dtype=cfg.adam_mu_dtype)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        with scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def test_one_chip_step_is_handed_no_option_and_lowers_as_before(chip):
    _cfg, job = _cell(ONE_CHIP, chip)
    assert tfm._compiler_options(job.mesh) is None
    fn, args = job.programs()["train_step"]
    before = _step_before(job.cfg, job.mesh, job.traffic["lr"])


    def text(program):
        # a pallas call's backend_config embeds the source lines of its
        # call stack, which a step made in this file does not share
        return re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', "",
                      program.lower(*args).as_text())

    assert "tpu_custom_call" in text(fn)
    assert text(fn) == text(before)


def test_options_follow_the_mesh(chip):
    four = _cell(FOUR_CHIPS, chip)[1].mesh
    assert tfm._compiler_options(four) == tfm._OVERLAP_OPTIONS
    cpus = make_mesh({"dp": 2, "sp": 1, "tp": 2}, devices=jax.devices()[:4])
    assert tfm._compiler_options(cpus) is None


def test_step_on_cpu_devices_compiles():
    """No compile option reaches the backend of CPU devices, which knows
    neither name."""
    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128, seq=32,
        attention="xla", compute_dtype="float32")
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 2}, devices=jax.devices()[:4])
    params = tfm.init_params(cfg)
    for make in (tfm.make_train_step,
                 lambda c, m: tfm.make_train_loop(c, m, steps=2)):
        step, init = make(cfg, mesh)
        tokens = np.zeros((4, cfg.seq), np.int32)
        step.lower(tfm.shard_params(cfg, mesh, params), init(params),
                   tokens).compile()
