"""``grouped_matmul`` compiled for a v5e that is described and not attached
(``tests/benchmarks/test_fits.py``'s idiom), wherever its rule takes a block
near the top of the kernel's VMEM budget (a whole matrix, or past the ridge
a whole ``K`` beside 512 rows): the call names no ``vmem_limit_bytes``, so
what ``weight_block`` admits has to fit what the compiler gives a kernel by
default.  Nothing runs and nothing here is a time.
"""

import jax
import jax.numpy as jnp
import pytest

from ompi_tpu.ops.grouped_matmul import (_VMEM_BUDGET_BYTES,
                                         _working_set_bytes, grouped_matmul,
                                         weight_block)
from tests.parallel.compiled import _on


def _compiles(tm, K, N, chip):
    """The call at the block the rule gives it, counted under the budget,
    compiles for the described chip with no limit named."""
    tk, tn = weight_block(tm, K, N, 2)
    assert _working_set_bytes(tm, tk, tn, 2) <= _VMEM_BUDGET_BYTES
    n_tiles, G = 64, 48
    text = jax.jit(grouped_matmul).lower(
        _on(chip, (n_tiles * tm, K), jnp.bfloat16),
        _on(chip, (G, K, N), jnp.bfloat16), _on(chip, (n_tiles,), jnp.int32),
        _on(chip, (1,), jnp.int32)).compile().as_text()
    assert "grouped_matmul" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("tm,K,N", [
    pytest.param(16, 2304, 1024, id="kimi-step-w1"),
    pytest.param(128, 2304, 1024, id="kimi-prefill-w1"),
    pytest.param(128, 1024, 2304, id="kimi-prefill-w2"),
    pytest.param(16, 2048, 1920, id="15.5-mib-of-16-rows"),
    pytest.param(64, 2048, 1792, id="15.8-mib-of-64-rows"),
    pytest.param(128, 1536, 2048, id="15.75-mib-of-128-rows"),
    pytest.param(16, 4096, 768, id="granite-step-w1"),
    pytest.param(16, 768, 4096, id="granite-step-w2"),
])
def test_a_whole_matrix_block_compiles_under_the_default_limit(
        tm, K, N, chip, for_the_chip):
    assert weight_block(tm, K, N, 2) == (K, N)
    _compiles(tm, K, N, chip)


# The prefill calls of OLMoE (cell 4) and Keye-VL (cell 6), 512 rows a tile,
# at the blocks the rule admits since PR 55; and a matrix whose whole-matrix
# working set is 1.6% over the budget, which the rule and not Mosaic refuses
# (OLMoE's w1 whole, 18.9 MB as counted, is what Mosaic does refuse).
@pytest.mark.parametrize("K,N,block", [
    pytest.param(2048, 768, (2048, 768), id="keye-prefill-w1-whole-15.2-mb"),
    pytest.param(768, 2048, (768, 1024), id="keye-prefill-w2"),
    pytest.param(2048, 1024, (2048, 512), id="olmoe-prefill-w1"),
    pytest.param(1024, 2048, (1024, 1024), id="olmoe-prefill-w2"),
    pytest.param(2048, 896, (2048, 128), id="just-over-the-budget"),
    # 512 rows of 4096 beside (4096, 256) count 13.5 MiB and Mosaic asks
    # 16.7: beside rows of 4 MiB the rule takes one lane tile of a part of N
    pytest.param(4096, 768, (4096, 128), id="granite-prefill-w1"),
    pytest.param(768, 4096, (768, 1024), id="granite-prefill-w2"),
    # cell 11's rows are long too (6 MiB): the budget alone gives one lane
    # tile, as before the rule knew of long rows
    pytest.param(6144, 2048, (6144, 128), id="longcat-prefill-w1"),
    pytest.param(2048, 6144, (2048, 768), id="longcat-prefill-w2"),
])
def test_what_the_rule_admits_beside_512_rows_compiles(K, N, block, chip,
                                                       for_the_chip):
    assert weight_block(512, K, N, 2) == block
    if block != (K, N):     # refused by the count, before Mosaic is asked
        assert _working_set_bytes(512, K, N, 2) > _VMEM_BUDGET_BYTES
    _compiles(512, K, N, chip)
