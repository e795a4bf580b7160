"""``retention_update`` and cell 8's generating program, compiled at the
cell's real sizes for a v5e that is described and not attached
(``tests/benchmarks/test_fits.py``'s idiom).  Nothing runs and nothing here
is a time: what is read is that the kernel compiles at the cell's block with
no ``vmem_limit_bytes`` named, and the compiled program's text and memory:
every cached step passes a layer's state through the kernel and through
nothing else as large (a copy of a layer is 1.64 GB a step, a second stack
6.1 GiB and would not fit), in the stack's own buffer, under the loop over
layers, once.
"""

import math
import re

import jax
import jax.numpy as jnp

from ompi_tpu.ops import retention_update as kernel_module
from tests.parallel.compiled import (INSTRUCTION, _cell, _on, _pallas_calls,
                                     _program)

CELL = "brumby-14b-base.decode-2k-128-b48"
# the parent's generating program (``PERF.md`` section 4: arguments + results
# + temporaries - written in place, read by ``memory_stats`` on the chip)
PARENT_PEAK_GIB = 12.772
L, B, G, R, D, d = 4, 48, 8, 5, 8320, 128


def test_the_kernel_compiles_at_cell_8s_block_with_no_limit_named(
        chip, for_the_chip):
    assert kernel_module.block(True, jnp.float32, D, d) == (1664, d)
    args = (_on(chip, (L, B, G, D, d)), _on(chip, (), jnp.int32),
            _on(chip, (B, G, R, D)), _on(chip, (B, G)), _on(chip, (B, G, D)),
            _on(chip, (B, G, d)))
    [call] = _pallas_calls(jax.make_jaxpr(
        kernel_module.retention_update)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    compiled = jax.jit(kernel_module.retention_update,
                       donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "retention_update" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    # the stack comes back in the argument's buffer; beside it the columns
    # (102 MB) and nothing as large as a layer
    assert memory.alias_size_in_bytes == 4 * L * B * G * D * d
    assert memory.temp_size_in_bytes < 256 << 20


def test_cell_8_steps_pass_a_layers_state_through_the_kernel_alone(
        chip, for_the_chip):
    cfg, job = _cell(CELL, chip)
    fn, args = _program(job, chip, 1)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()

    layer = (job.batch, cfg.kv_heads, D, cfg.head_dim)
    stack = (cfg.n_layers, *layer)
    assert stack == (L, B, G, D, d)
    shapes = {",".join(map(str, dims)) for dims in (layer, (1, *layer), stack)}
    # a name for a buffer, not a pass over it
    names = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
    passes = [(name, dims, op)
              for name, dims, op, _rest in INSTRUCTION.findall(text)
              if dims in shapes and op not in names]
    assert not passes, passes       # no copy, slice, fusion or remat of one
    # the kernel's result is a tuple (sums, stack), which the pattern above,
    # of single arrays, does not read: the calls are counted by name.  One,
    # in the body of the loop over layers, which the steps' scan runs
    kernels = re.findall(
        rf"= \(f32\[[\d,]+\]\S* f32\[{','.join(map(str, stack))}\]\S*\) "
        rf"custom-call\([^\n]*retention_update", text)
    assert len(kernels) == 1
    assert "remat" not in text

    memory = compiled.memory_analysis()
    # the stack is written where it lies
    assert memory.alias_size_in_bytes > 4 * math.prod(stack)
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert peak < (PARENT_PEAK_GIB * 2 ** 30) + (64 << 20), peak / 2 ** 30
