"""``kda_update`` and cell 7's two programs, compiled at the cell's real
sizes for a v5e that is described and not attached
(``tests/benchmarks/test_fits.py``'s idiom).  Nothing runs and nothing here
is a time: what is read is that the kernel compiles at the cell's block with
no ``vmem_limit_bytes`` named, and the compiled programs' text and memory.
The generating program: every cached step passes a layer's state through
the kernel and through nothing else as large (a copy of a layer's state is
805 MB a step and 0.75 GiB of the chip), in the buffer it lies in.  The
prefill program: a delta-rule layer is one loop over the pass's 8 blocks of
64 whose body is the products with the state and no row-at-a-time solve,
and what is made before the loop never writes the diagonal sub-blocks'
per-channel differences out whole (1.07 GB a layer).
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from ompi_tpu.ops import kda_update as kernel_module  # noqa: E402
# the described chip, and the compile cache and interpret mode off around it
from tests.parallel.test_kda_update import _pallas_calls  # noqa: E402
from tests.parallel.test_selected_attention_compiled import (  # noqa: E402,F401
    INSTRUCTION, chip, for_the_chip)

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
# the parent's generating program (``traffic/decode-512-128-b384.json``'s
# ``batch_why``: arguments + results + temporaries - written in place)
PARENT_PEAK_GIB = 13.15
# what the prefill program read here with blocks of 16 solved inside the scan
PARENT_PREFILL_PEAK_GIB = 13.05


def _on(chip, dims, dtype=jnp.float32):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(dims, dtype,
                                sharding=SingleDeviceSharding(chip[0]))


def test_the_kernel_compiles_at_cell_7s_block_with_no_limit_named(
        chip, for_the_chip):
    B, H, K = 384, 32, 128
    assert kernel_module.block(True, jnp.float32, H, K) == (1, H, K, K)
    vector = _on(chip, (B, H, K))
    args = (_on(chip, (B, H, K, K)), vector, vector, vector, vector,
            _on(chip, (B, H)))
    [call] = _pallas_calls(jax.make_jaxpr(kernel_module._call)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    compiled = jax.jit(kernel_module.kda_update, donate_argnums=0).lower(
        *args).compile()
    text = compiled.as_text()
    assert "kda_update" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    # the state comes back in the argument's buffer; nothing else is held
    assert memory.alias_size_in_bytes == 4 * B * H * K * K
    assert memory.temp_size_in_bytes < 32 << 20


@pytest.fixture(scope="module")
def cell_7(chip):
    """(the program's configuration, the cell's job) from the configuration
    and traffic files; building traces nothing."""
    from benchmarks.lib import cells, program

    cell = cells.resolve(CELL)
    return (program.program_config(cell.config),
            cell.runner.build(cell.config, cell.traffic, chip))


def _program(job, chip, which):
    """One of the two programs of a decoder that has two, as it runs
    (``decode._two_programs``: the prefill's, then the generating one, which
    takes the carry donated), cut out of the job's ``full`` decoder, whose
    two programs ``job.programs()`` compiles as one."""
    from jax.extend.core import jaxpr_as_fun

    _fn, args = job.programs()["decode_full"]
    programs = [eqn for eqn in jax.make_jaxpr(job.full)(*args).eqns
                if eqn.params.get("name") == "decode"]
    assert len(programs) == 2
    closed = programs[which].params["jaxpr"]
    donated = [i for i, given in enumerate(
        programs[which].params["donated_invars"]) if given]
    # a prefill is given nothing; a generating program all of whose buffers
    # grow (cell 10: latent caches alone) is not either
    assert not donated or which
    return (jax.jit(jaxpr_as_fun(closed), donate_argnums=donated),
            [_on(chip, v.aval.shape, v.aval.dtype)
             for v in closed.jaxpr.invars])


def _peak(memory) -> int:
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)


def test_cell_7_steps_pass_each_state_through_the_kernel_alone(
        chip, cell_7, for_the_chip):
    cfg, job = cell_7
    fn, args = _program(job, chip, 1)
    compiled = fn.lower(*args).compile()
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
    peak = _peak(memory)
    assert peak < (PARENT_PEAK_GIB * 2 ** 30) + (64 << 20), peak / 2 ** 30


def test_cell_7s_prefill_scans_eight_blocks_a_layer_and_solves_before_them(
        chip, cell_7, for_the_chip):
    from ompi_tpu.models import decode

    cfg, job = cell_7
    fn, args = _program(job, chip, 0)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    computations = dict(re.findall(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S))

    kd = cfg.plan.kda
    loops = [line for line in text.splitlines()
             if " while(" in line and "kda.scan" in line]
    assert len(loops) == cfg.plan.count("kda") == 4
    steps = job.prompt_len // kd.chunk
    assert steps == 8
    for loop in loops:
        condition, body = (computations[name] for name in re.search(
            r"condition=%([\w.-]+), body=%([\w.-]+)", loop).groups())
        assert f"constant({steps})" in condition
        # the five products with the state, what they are made from, the
        # slices of this block: the parent's body, a block of 16 solved a
        # row at a time, was 174 instructions, 15 of them row updates
        assert "dynamic-update-slice(" not in body
        assert len(body.splitlines()) < 48, body

    # the (B, H, blocks, sub-blocks, 16, 16, K) differences of a layer, or a
    # quarter of them, in no instruction but one inside a fusion
    sub = min(16, kd.chunk)
    group = decode._prefill_group(job.batch, job.prompt_len,
                                  cfg.prefill_tokens)
    assert group == 8
    whole = group * kd.n_heads * job.prompt_len * sub * kd.head_dim
    written = [
        (name, dims) for body_name, body in computations.items()
        if "fused_computation" not in body_name
        for name, dims, _op, _rest in INSTRUCTION.findall(body)
        if dims.endswith(f",{sub},{sub},{kd.head_dim}")
        and math.prod(map(int, dims.split(","))) >= whole // 4]
    assert not written, written

    peak = _peak(compiled.memory_analysis())
    assert peak < (PARENT_PREFILL_PEAK_GIB + 0.3) * 2 ** 30, peak / 2 ** 30
