"""``kda_update`` and cell 7's prefill program, compiled at the cell's real
sizes for a v5e that is described and not attached
(``tests/benchmarks/test_fits.py``'s idiom).  Nothing runs and nothing here
is a time: what is read is that the kernel compiles at the cell's block with
no ``vmem_limit_bytes`` named, and the compiled program's text and memory.
The prefill program: a delta-rule layer is one loop over the pass's 8 blocks
of 64 whose body is the products with the state and no row-at-a-time solve,
and what is made before the loop never writes the diagonal sub-blocks'
per-channel differences out whole (1.07 GB a layer).  (The generating
program is compiled once, in ``test_plan_step_compiled.py``, for every test
that reads it.)
"""

import math
import re

import jax
import jax.numpy as jnp

from ompi_tpu.ops import kda_update as kernel_module
from tests.parallel.compiled import (INSTRUCTION, _cell, _on, _pallas_calls,
                                     _peak, _program)

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
# what the prefill program read here with blocks of 16 solved inside the scan
PARENT_PREFILL_PEAK_GIB = 13.05


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


def test_cell_7s_prefill_scans_eight_blocks_a_layer_and_solves_before_them(
        chip, for_the_chip):
    from ompi_tpu.models import decode

    cfg, job = _cell(CELL, chip)
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
