"""``ops/selective_scan.py`` in interpret mode against ``selective.scan`` (the
``lax.scan`` a position) and against a recurrence written out in numpy, and
compiled at the real shapes of its cell for a v5e that is described and not
attached.  Nothing here is a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.models import selective
from ompi_tpu.ops import selective_scan as kernel_module
from tests.parallel.compiled import _on, _pallas_calls

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"


def operands(B, T, Di, N, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, T, Di)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(B, T, Di)))).astype(np.float32)
    a = -np.exp(rng.normal(0, 2, size=(N, Di))).astype(np.float32)
    b, c = (rng.normal(size=(B, T, N)).astype(np.float32) for _ in "bc")
    return x, dt, a, b, c


def by_hand(x, dt, a, b, c):
    B, T, Di = x.shape
    S, ys = np.zeros((B, a.shape[0], Di), np.float64), []
    for t in range(T):
        S = (np.exp(dt[:, t, None, :] * a) * S
             + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None])
        ys.append((S * c[:, t, :, None]).sum(1))
    return np.stack(ys, 1), S


@pytest.mark.parametrize("B,T,Di,N", [(2, 256, 2048, 16), (1, 128, 1024, 4)])
def test_the_kernel_is_the_recurrence(B, T, Di, N):
    args = operands(B, T, Di, N)
    y, end = jax.jit(kernel_module.selective_scan)(*args)
    want_y, want_end = by_hand(*args)
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(y) - want_y).max() < 1e-5 * scale
    assert np.abs(np.asarray(end) - want_end).max() < 1e-5 * np.abs(
        want_end).max()
    scan_y, scan_end = jax.jit(selective.scan)(*args)
    assert np.abs(np.asarray(scan_y) - want_y).max() < 1e-5 * scale
    assert scan_end.shape == end.shape == (B, N, Di)


def test_what_does_not_tile_is_refused():
    assert kernel_module.tiles(16_128, 5120, 16)
    for t, di, n in ((16_100, 5120, 16), (128, 5000, 16), (128, 1024, 64),
                     (0, 1024, 16)):
        assert not kernel_module.tiles(t, di, n)
    with pytest.raises(ValueError, match="do not tile"):
        kernel_module.selective_scan(*operands(1, 100, 1024, 4))


def test_the_kernel_compiles_at_cell_15s_shapes_with_no_limit_named(
        chip, for_the_chip):
    """One sequence a prefill pass (``prefill_tokens`` 16,128) of 16,128
    positions of 5120 channels over 16 state elements: a grid of (1, 5, 126),
    the operands as the mixer leaves them, and nothing held beside the
    results but their float32 copies."""
    from benchmarks.lib import cells, program

    cell = cells.resolve(CELL)
    sz = program.program_config(cell.config).plan.selective
    B, T, Di, N = 1, cell.traffic["prompt_len"], sz.d_inner, sz.d_state
    assert (T, Di, N) == (16_128, 5120, 16)
    assert cell.config["entry"]["options"]["prefill_tokens"] == T
    args = (_on(chip, (B, T, Di)), _on(chip, (B, T, Di)), _on(chip, (N, Di)),
            _on(chip, (B, T, N)), _on(chip, (B, T, N)))
    [call] = _pallas_calls(jax.make_jaxpr(
        kernel_module.selective_scan)(*args).jaxpr)
    [params] = call.params["compiler_params"].values()
    assert params.vmem_limit_bytes is None
    assert call.params["grid_mapping"].grid == (
        B, Di // kernel_module.CHANNELS, T // kernel_module.CHUNK)
    compiled = jax.jit(kernel_module.selective_scan).lower(*args).compile()
    text = compiled.as_text()
    assert "selective_scan" in text and "tpu_custom_call" in text
    y, end = jax.eval_shape(kernel_module.selective_scan, *args)
    assert (y.shape, end.shape) == ((B, T, Di), (B, N, Di))
    assert y.dtype == end.dtype == jnp.float32
