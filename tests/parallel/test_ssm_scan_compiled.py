"""``ssm_scan`` compiled at cell 12's real shapes for a v5e that is described
and not attached (``test_retention_prefill_compiled.py``'s idiom).  Nothing
runs and nothing here is a time: what is read is that the kernel compiles for
a pass of 8 prompts of 1024 positions, in bfloat16 and in float32, with no
``vmem_limit_bytes`` named; that its operands are the convolution's one
output, thrice, and the decays re-laid (2 MB); and that the compiled call
holds nothing beside its results; and the same for the other grouping the
benchmark has, one group of 128 heads (``granite-4.0-h-small``'s pass of 8
prompts of 512), which the kernel takes 16 heads a grid cell.  (Which of the
two cells' programs call it is read in ``test_ssm.py``.)
"""

import jax
import jax.numpy as jnp
import pytest

from ompi_tpu.models import ssm
from ompi_tpu.ops import ssm_scan as kernel_module
from tests.parallel.compiled import _on, _pallas_calls

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
B, T = 8, 1024          # a prefill pass: ``prefill_tokens`` 8192


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_compiles_at_cell_12s_shapes_with_no_limit_named(
        chip, for_the_chip, dtype):
    from benchmarks.lib import cells, program

    sz = program.program_config(cells.resolve(CELL).config).plan.ssm
    H, P, G, N = sz.n_heads, sz.head_dim, sz.n_groups, sz.d_state
    assert (H, P, G, N, sz.chunk) == (64, 64, 8, 128, kernel_module.CHUNK)
    assert ssm.fused(sz, True, True, T, jnp.dtype(dtype))
    xbc = _on(chip, (B, T, sz.conv_dim), jnp.dtype(dtype))
    args = (xbc, _on(chip, (B, T, H)), _on(chip, (H,)), _on(chip, (H,)))

    def scan(*a):
        return kernel_module.ssm_scan(*a, G, N)

    [call] = _pallas_calls(jax.make_jaxpr(scan)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    # a group's 8 heads are one block: the grid, the blocks and the body are
    # what they were before a group could be cut (PR 65)
    assert kernel_module.heads_a_block(T, P, N, H // G, dtype) == H // G
    assert call.params["grid_mapping"].grid == (B, G, 1)
    # x, B and C are one array under three index maps, read as it lies
    assert call.invars[0] is call.invars[1] is call.invars[2]
    assert call.invars[0].aval.shape == xbc.shape
    assert [v.aval.shape for v in call.invars[3:]] == [
        (B, G, H // G, T), (G, H // G, 1), (G, 1, H // G)]
    compiled = jax.jit(scan).lower(*args).compile()
    text = compiled.as_text()
    assert "ssm_scan" in text and "tpu_custom_call" in text
    y, end = jax.eval_shape(scan, *args)
    assert (y.shape, end.shape) == ((B, T, H * P), (B, H, P, N))
    # beside y and the end state, the decays heads-major
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_one_group_of_128_heads_compiles_in_blocks_with_no_limit_named(
        chip, for_the_chip):
    """The cell's pass: 8 prompts of 512 positions, 128 heads of 64 sharing
    one ``B`` and one ``C``.  As one block a grid cell (what ``tiles`` said
    yes to before it counted VMEM) the call ran out of VMEM on the
    compiler's stack; in blocks of 16 heads it compiles under the default
    limit, every block reading the group's ``B`` and ``C``."""
    H, P, G, N = 128, 64, 1, 128
    b, t, heads = 8, 512, 16
    sz = ssm.Mamba2(d_ssm=H * P, d_state=N, n_groups=G, n_heads=H, d_conv=4,
                    chunk=kernel_module.CHUNK)
    assert ssm.fused(sz, True, True, t, jnp.bfloat16)
    assert kernel_module.heads_a_block(t, P, N, H // G) == heads
    xbc = _on(chip, (b, t, sz.conv_dim), jnp.bfloat16)
    args = (xbc, _on(chip, (b, t, H)), _on(chip, (H,)), _on(chip, (H,)))

    def scan(*a):
        return kernel_module.ssm_scan(*a, G, N)

    [call] = _pallas_calls(jax.make_jaxpr(scan)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    assert call.params["grid_mapping"].grid == (b, H // heads, 1)
    assert call.invars[0] is call.invars[1] is call.invars[2]
    assert [v.aval.shape for v in call.invars[3:]] == [
        (b, H // heads, heads, t), (H // heads, heads, 1),
        (H // heads, 1, heads)]
    compiled = jax.jit(scan).lower(*args).compile()
    assert "ssm_scan" in compiled.as_text()
    y, end = jax.eval_shape(scan, *args)
    assert (y.shape, end.shape) == ((b, t, H * P), (b, H, P, N))
    # twice as many heads a cell are past the scoped default
    with pytest.raises(Exception, match="vmem"):
        kernel_module._call.lower(*args, G, N, 32).compile()
