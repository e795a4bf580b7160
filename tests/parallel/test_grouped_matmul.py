"""The pallas grouped matmul under dropless expert routing
(``ops/grouped_matmul.py``), in TPU interpret mode: each row tile against
the matrix its entry of ``tile_group`` names, the tiles past ``tiles_used``
zero, and the custom backward against ``lax.ragged_dot``'s own."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.ops.grouped_matmul import grouped_matmul, tile_rows


@pytest.mark.parametrize("rows_a_group,want", [
    (0.5, 16), (6, 16), (16, 16), (31, 16), (32, 32), (100, 64),
    (6144, 512), (1e6, 512)])
def test_tile_rows_follow_the_groups_size(rows_a_group, want):
    assert tile_rows(rows_a_group) == want


def _case(tm, K, N, G, tile_group, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    tile_group = np.asarray(tile_group, np.int32)
    rows = rng.normal(size=(len(tile_group) * tm, K)).astype(dtype)
    w = rng.normal(size=(G, K, N)).astype(dtype)
    return jnp.asarray(rows), jnp.asarray(w), jnp.asarray(tile_group)


@pytest.mark.parametrize("tm,K,N", [
    pytest.param(16, 64, 32, id="whole-matrix-blocks"),
    pytest.param(128, 2048, 1024, id="k-and-n-in-blocks"),
])
def test_each_tile_multiplies_its_groups_matrix(tm, K, N):
    tile_group = [0, 0, 2, 3, 3, 3, 5, 5]    # groups 1 and 4 have no tile
    rows, w, tg = _case(tm, K, N, 6, tile_group)
    used = jnp.asarray([6], jnp.int32)
    got = np.asarray(jax.jit(grouped_matmul)(rows, w, tg, used))
    for t, g in enumerate(tile_group):
        tile = slice(t * tm, (t + 1) * tm)
        if t < 6:
            want = np.asarray(rows[tile], np.float64) @ np.asarray(
                w[g], np.float64)
            np.testing.assert_allclose(got[tile], want, rtol=1e-4,
                                       atol=1e-4 * np.abs(want).max())
        else:
            assert not got[tile].any()


def test_backward_is_ragged_dots_own():
    tm = 16
    tile_group = [0, 1, 1, 3, 3]
    rows, w, tg = _case(tm, 32, 48, 4, tile_group, seed=1)
    used = jnp.asarray([4], jnp.int32)      # the last tile holds nothing
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=(len(tile_group) * tm, 48)).astype(np.float32))
    # what the caller reads back: nothing of an unused tile
    weight = weight.at[4 * tm:].set(0)
    sizes = jnp.asarray([16, 32, 0, 16], jnp.int32)

    got = jax.grad(lambda r, m: (grouped_matmul(r, m, tg, used)
                                 * weight).sum(), argnums=(0, 1))(rows, w)
    want = jax.grad(lambda r, m: (lax.ragged_dot(r, m, sizes)
                                  * weight).sum(), argnums=(0, 1))(rows, w)
    for g, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-5)
    assert not np.asarray(got[1][2]).any()      # group 2 got no row


def test_shapes_that_do_not_tile_are_refused():
    rows, w, tg = _case(16, 32, 48, 4, [0, 1, 2])
    used = jnp.asarray([3], jnp.int32)
    with pytest.raises(ValueError, match="grouped_matmul"):
        grouped_matmul(rows[:-8], w, tg, used)          # 40 rows, 3 tiles
    with pytest.raises(ValueError, match="grouped_matmul"):
        grouped_matmul(rows, w[:, :16], tg, used)       # K differs
