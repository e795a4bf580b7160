"""The pallas rotary-embedding kernel against the model's jnp form: the same
sums term for term, forward and backward, and the rule for the shapes it
takes."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu.models.transformer import _rope  # noqa: E402
from ompi_tpu.ops import rope as rope_kernel  # noqa: E402


def _x(shape, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


@pytest.mark.parametrize("shape,dtype", [
    ((2, 64, 2, 128), jnp.bfloat16), ((1, 48, 3, 128), jnp.float32),
    ((1, 1024, 1, 256), jnp.bfloat16)])
def test_rope_kernel_is_the_jnp_form(shape, dtype):
    x = _x(shape, dtype)
    positions = 1000 + jnp.arange(shape[1])
    got = _rope(x, positions, "flash")
    ref = _rope(x, positions)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    # one rounding of the result's type: the CPU contracts a multiply and
    # an add into one operation where it pleases
    tol = 2 ** -7 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rope_kernel_gradient_is_the_jnp_forms(dtype):
    x = _x((2, 32, 2, 128), dtype, seed=1)
    w = _x(x.shape, jnp.float32, seed=2)
    positions = 7 + jnp.arange(32)

    def loss(impl):
        return lambda x: (_rope(x, positions, impl).astype(jnp.float32)
                          * w).sum()

    got, ref = jax.grad(loss("flash"))(x), jax.grad(loss("jnp"))(x)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_rope_kernel_takes_whole_lane_tiles_and_rows_that_tile():
    tiles = rope_kernel.rope_tiles
    assert tiles(2048, 16, 128, jnp.bfloat16)
    assert tiles(1024, 16, 128, jnp.bfloat16) and tiles(48, 2, 256, "float32")
    assert not tiles(1024, 16, 64, jnp.bfloat16)      # half a lane tile
    assert not tiles(1, 16, 128, jnp.bfloat16)        # the cached step's
    assert not tiles(1000, 16, 128, jnp.bfloat16)     # no 16·2^n divides it
    # a block stays under 2 MiB: 512 rows of 2048 bfloat16, 64 of 16384
    assert rope_kernel._rows(2048, 2048, 2) == 512
    assert rope_kernel._rows(2048, 16384, 2) == 64
    # where it does not tile, the model's form is the jnp one
    x = _x((1, 24, 2, 64), jnp.float32)
    np.testing.assert_array_equal(np.asarray(_rope(x, jnp.arange(24), "flash")),
                                  np.asarray(_rope(x, jnp.arange(24))))
    with pytest.raises(ValueError, match="use the jnp form"):
        rope_kernel.rope(x, jnp.ones((24, 32)), jnp.zeros((24, 32)))
