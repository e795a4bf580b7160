"""Cell 8's prefill program, compiled at the cell's real sizes for a v5e
that is described and not attached (``test_retention_update_compiled.py``'s
idiom).  Nothing runs and nothing here is a time: what is read is that the
kernel compiles at the cell's tile with no ``vmem_limit_bytes`` named, and
the compiled program's text and memory: a layer's prompt is read directly,
by the kernel, once, under the loop over layers; no query head's ``phi(q)``
(8320 wide: ``bf16[2,256,8,5,65,128]`` was the parent's longest operation)
and no (2048, 2048) array of weights is written out; the state the prompt
leaves is formed by the second kernel (``ops/retention_end_state.py``, PR
73), once, in the same loop, and no chunk of ``phi(k)`` (``f32[2,256,8,65,
128]``, which PR 72's program wrote out and read back eight times a layer)
is an array of the program; the program's peak is not above the parent's.
And the trainer's program of the same configuration, traced for the same
chip, calls no such kernel.
"""

import re

import jax
import jax.numpy as jnp

from ompi_tpu.models import retention
from ompi_tpu.ops import retention_end_state as end_module
from ompi_tpu.ops import retention_prefill as kernel_module
from tests.parallel.compiled import (INSTRUCTION, _cell, _on, _pallas_calls,
                                     _peak, _program)

CELL = "brumby-14b-base.decode-2k-128-b48"
# the parent's prefill program, the larger of the cell's two, as this file's
# compile read it at PR 72 on PR 73's box (12.68 at PR 63 by ``PERF.md``;
# the change reads 12.51)
PARENT_PEAK_GIB = 12.624
B, T, H, G, d, D = 2, 2048, 40, 8, 128, 8320


def _named(jaxpr, name):
    return [c for c in _pallas_calls(jaxpr) if c.params["name"] == name]


def _calls_of(text, kernel):
    """The ``op_name`` of every custom call of ``kernel`` in a compiled
    program's text."""
    return re.findall(r"custom-call\([^\n]*tpu_custom_call[^\n]*"
                      rf'op_name="([^"\n]*{kernel}[^"\n]*)"', text)


def test_the_kernel_compiles_at_cell_8s_tile_with_no_limit_named(
        chip, for_the_chip):
    assert kernel_module.tiles(T, d) and retention.direct(True, True, T, d)
    bf16 = jnp.bfloat16
    args = (_on(chip, (B, T, H, d), bf16), _on(chip, (B, T, G, d), bf16),
            _on(chip, (B, T, G, d), bf16), _on(chip, (B, T, G)))

    def sums(*a):
        return kernel_module.retention_prefill(*a, retention._power)

    [call] = _pallas_calls(jax.make_jaxpr(sums)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    assert call.params["grid_mapping"].grid == (B, G, T // kernel_module.ROWS)
    compiled = jax.jit(sums).lower(*args).compile()
    text = compiled.as_text()
    assert "retention_prefill" in text and "tpu_custom_call" in text
    # beside the sums that come back, the decays' sums one lane wide
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_end_states_kernel_compiles_at_cell_8s_block_with_no_limit_named(
        chip, for_the_chip):
    """A head's ``S`` twice (the output's buffers) and its transpose are 12.8
    MB: inside what Mosaic gives unasked, or this compile fails."""
    assert end_module.tiles(T, d) and end_module.ROWS == 256
    bf16 = jnp.bfloat16
    args = (_on(chip, (B, T, G, d), bf16), _on(chip, (B, T, G, d), bf16),
            _on(chip, (B, T, G)), _on(chip, (D,)))
    [call] = _pallas_calls(
        jax.make_jaxpr(end_module.retention_end_state)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    assert params.dimension_semantics[-1] == "arbitrary"
    assert call.params["grid_mapping"].grid == (B, G, T // end_module.ROWS)
    compiled = jax.jit(end_module.retention_end_state).lower(*args).compile()
    text = compiled.as_text()
    assert "retention_end_state" in text and "tpu_custom_call" in text
    # beside S and z that come back, the decays a column a head (one lane
    # wide, padded to a tile's 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def test_cell_8s_prefill_reads_a_prompt_directly_and_expands_no_query(
        chip, for_the_chip):
    cfg, job = _cell(CELL, chip)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (H, G, d)
    fn, args = _program(job, chip, 0)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()

    # one call, in the body of the loop over layers, under the scopes a
    # trace is read by
    kernels = _calls_of(text, "retention_prefill")
    assert len(kernels) == 1, kernels
    assert ("/prefill/" in kernels[0] and "/layers/while/body/" in kernels[0]
            and "/attention/retention.scan/retention.direct/" in kernels[0])
    # and one call of the kernel that forms the state the prompt leaves,
    # in the same loop, under the scope a trace reads the end state by
    ends = _calls_of(text, "retention_end_state")
    assert len(ends) == 1, ends
    assert ("/prefill/" in ends[0] and "/layers/while/body/" in ends[0]
            and "/attention/retention.scan/retention.end_state/" in ends[0])
    assert "retention.scan/retention.end_state/" in text
    written = {dims for _name, dims, _op, _rest in INSTRUCTION.findall(text)}
    written = [tuple(int(n) for n in dims.split(",")) for dims in written
               if dims]
    R = H // G
    # a query head's expansion, whole or a shift a row, in any order of axes
    assert not [s for s in written if R in s and G in s
                and (D in s or (D // d in s and d in s))]
    assert not [s for s in written if s.count(T) > 1]
    # no chunk of ``phi(k)``, whole or a shift a row: it is built and used
    # in VMEM (the parent wrote ``f32[2,256,8,65,128]`` and read it back)
    chunk = retention.Retention().chunk
    assert not [s for s in written if chunk in s
                and (D in s or (D // d in s and d in s))]
    # what is as wide as the state is the states themselves
    assert [s for s in written if D in s]
    peak = _peak(compiled.memory_analysis())
    assert peak < (PARENT_PEAK_GIB * 2 ** 30) + (64 << 20), peak / 2 ** 30


def test_the_trainers_program_of_cell_8_keeps_the_chunked_form(
        chip, for_the_chip):
    """``make_loss_fn`` of the cell's configuration, traced at the cell's
    sizes under the same described chip (so ``_chip._traced_for_tpus`` says
    TPUs): a gradient may be asked, and the scan it traces is ``chunked``'s,
    with ``phi(q)`` in it and no kernel."""
    from benchmarks.lib import cells, program
    from ompi_tpu.models import transformer as tfm

    cell = cells.resolve(CELL)
    cfg = program.program_config(cell.config)
    mesh = program.mesh(cell.config, chip[:1])
    params = program.abstract_params(
        program.reference(cell.config), cell.config,
        program.param_shardings(cell.config, cfg, mesh))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    jaxpr = jax.make_jaxpr(tfm.make_loss_fn(cfg, mesh))(params, tokens)
    assert not _named(jaxpr.jaxpr, "retention_prefill")
    assert f"{B},256,{G},{H // G},{D}" in str(jaxpr)    # phi(q) of a chunk
