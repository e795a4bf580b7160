"""``ops/masked_latent_attention.py`` compiled at a slice of cell 14's
prefill for a v5e that is described and not attached.  Nothing runs and
nothing here is a time: what is read is the traced call (its grid, its
scratch, the VMEM limit it does not name) and the compiled module's text."""

import math

import jax
import jax.numpy as jnp

from ompi_tpu.ops import _chip
from ompi_tpu.ops import masked_latent_attention as kernel
from tests.parallel.compiled import _on, _pallas_calls

CELL = "deepseek-v3.2-exp.decode-16k-512-b8"


def test_a_slice_of_cell_14_compiles_with_no_limit_named(chip, for_the_chip):
    """A sequence a pass and a slice of 512 queries a call: a grid of (1, 32
    groups of 4 heads, 31 key blocks), the running max and sum 128 lanes
    wide beside the accumulator (1 MB each, what ``(4, 512, 1)`` pads to),
    and blocks that fit what Mosaic gives a kernel unasked."""
    from benchmarks.lib import cells, program

    cell = cells.resolve(CELL)
    ml = program.program_config(cell.config).plan.mla
    t_q, t_k = ml.index.q_slice, cell.traffic["prompt_len"]
    assert (t_q, t_k, ml.n_heads, ml.nope, ml.rope, ml.v_dim) == (
        512, 15_872, 128, 128, 64, 128)
    assert kernel.tiles(t_q, ml.n_heads, ml.nope, ml.rope, ml.v_dim)
    bf16 = jnp.bfloat16
    args = (_on(chip, (1, t_q, ml.n_heads, ml.nope + ml.rope), bf16),
            _on(chip, (1, t_k, ml.n_heads, ml.nope + ml.v_dim), bf16),
            _on(chip, (1, t_k, ml.rope), bf16),
            _on(chip, (1, t_q, t_k), jnp.bool_))
    k_len = _on(chip, (), jnp.int32)

    def slice_(q, kv, k_r, mask, k_len):
        return kernel.masked_latent_attention(q, kv, k_r, mask, ml.scale,
                                              k_len=k_len)

    [call] = _pallas_calls(jax.make_jaxpr(slice_)(*args, k_len).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    grid = call.params["grid_mapping"]
    assert grid.grid == (1, ml.n_heads // kernel._GROUP, t_k // kernel._BLOCK)
    scratch = [(s.shape, s.dtype) for s in grid.scratch_avals]
    assert scratch == 3 * [((kernel._GROUP, t_q, 128), jnp.float32)]
    # two buffers a block of an operand or of the result, one of scratch
    held = (sum(2 * b.block_aval.size * b.block_aval.dtype.itemsize
                for b in grid.block_mappings)
            + sum(math.prod(shape) * 4 for shape, _ in scratch))
    assert held == 8_650_752 < _chip._VMEM_BUDGET_BYTES

    compiled = jax.jit(slice_).lower(*args, k_len).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "masked_latent_attention" in text
    out = jax.eval_shape(slice_, *args, k_len)
    assert (out.shape, out.dtype) == ((1, t_q, ml.n_heads, ml.v_dim), bf16)
