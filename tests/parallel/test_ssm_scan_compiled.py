"""``ssm_scan`` compiled at cell 12's real shapes for a v5e that is described
and not attached (``test_retention_prefill_compiled.py``'s idiom).  Nothing
runs and nothing here is a time: what is read is that the kernel compiles for
a pass of 8 prompts of 1024 positions, in bfloat16 and in float32, with no
``vmem_limit_bytes`` named; that its operands are the convolution's one
output, thrice, and the decays re-laid (2 MB); and that the compiled call
holds nothing beside its results.  (Which of the two cells' programs call it
is read in ``test_ssm.py``.)
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from ompi_tpu.models import ssm  # noqa: E402
from ompi_tpu.ops import ssm_scan as kernel_module  # noqa: E402
# the described chip, and the compile cache and interpret mode off around it
from tests.parallel.test_kda_update import _pallas_calls  # noqa: E402
from tests.parallel.test_kda_update_compiled import _on  # noqa: E402
from tests.parallel.test_selected_attention_compiled import (  # noqa: E402,F401
    chip, for_the_chip)

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
