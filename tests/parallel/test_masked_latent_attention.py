"""``ops/masked_latent_attention.py`` in interpret mode against its
``jnp_form``, at what the tile's body could get wrong and
``tests/benchmarks/test_v32.py``'s three cases do not hold: a row's running
max starts at a finite floor and one select is all that keeps a masked score
out, ``scale`` is applied inside the exponent, and a row's max and sum are
kept 128 lanes wide.  Nothing here is a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import masked_latent_attention as kernel

BLOCK = kernel._BLOCK
HEADS, NOPE, V_DIM = 4, 128, 128


def _random(key, shape, times=1.0):
    return times * jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _operands(t_q, t_k, rope, times=1.0):
    return (_random(1, (1, t_q, HEADS, NOPE + rope), times),
            _random(2, (1, t_k, HEADS, NOPE + V_DIM)),
            _random(3, (1, t_k, rope)),
            jax.random.bernoulli(jax.random.key(4), 0.3, (1, t_q, t_k)))


def _late_row(mask):
    """Row 5 sees its first key in the third key block, row 3 none."""
    return mask.at[0, 5, :2 * BLOCK + 7].set(False).at[0, 3].set(False)


CASES = {
    # name: (queries, keys, k_len, rope, the queries' factor, the mask's edit)
    "first_key_in_the_third_block": (32, 4 * BLOCK, 4 * BLOCK, 64, 1.0,
                                     _late_row),
    "a_row_of_no_key": (32, 2 * BLOCK, 2 * BLOCK, 64, 1.0,
                        lambda mask: mask.at[0, 3].set(False)),
    "k_len_on_a_blocks_edge": (32, 3 * BLOCK, 2 * BLOCK, 64, 1.0, _late_row),
    "k_len_inside_a_block": (32, 3 * BLOCK, 2 * BLOCK + 77, 64, 1.0,
                             _late_row),
    # scores of some hundreds: exp of one without the running max is inf
    "scores_that_overflow_exp": (32, 2 * BLOCK, 2 * BLOCK, 64, 40.0,
                                 lambda mask: mask.at[0, 3].set(False)),
    "rope_128": (32, 3 * BLOCK, 2 * BLOCK + 77, 128, 1.0, _late_row),
    "rows_512": (512, 3 * BLOCK, 3 * BLOCK, 64, 1.0, _late_row),
    "rows_512_rope_128": (512, 2 * BLOCK, BLOCK + 5, 128, 1.0,
                          lambda mask: mask.at[0, 3].set(False)),
}


@pytest.mark.parametrize("case", CASES)
def test_the_tiles_body_is_the_jnp_form(case):
    t_q, t_k, k_len, rope, times, edit = CASES[case]
    scale = 0.1352
    assert kernel.tiles(t_q, HEADS, NOPE, rope, V_DIM)
    q, kv, k_r, mask = _operands(t_q, t_k, rope, times)
    mask = edit(mask)
    # keys past k_len hold large values: a kernel that read one would differ
    kv = kv.at[:, k_len:].set(1e4)
    seen = mask & (jnp.arange(t_k) < k_len)
    got = np.asarray(jax.jit(kernel.masked_latent_attention,
                             static_argnums=4)(q, kv, k_r, mask, scale,
                                               k_len))
    want = np.asarray(kernel.jnp_form(q, kv.at[:, k_len:].set(0), k_r, seen,
                                      scale))
    assert got.shape == (1, t_q, HEADS, V_DIM)
    assert np.isfinite(got).all()
    if times > 1:
        biggest = float(jnp.abs(jnp.einsum(
            "qhd,khd->hqk", q[0, ..., :NOPE], kv[0, :k_len, :, :NOPE])).max())
        assert biggest * scale > 89         # float32's exp overflows from 88.7
    # a row without a key reads zeros, one whose first key comes late its
    # context: zeros until then is what the floor of the running max gives
    none = ~np.asarray(seen[0]).any(-1)
    assert none[3]
    assert (got[0, none] == 0).all()
    assert np.abs(got[0, ~none]).max() > 0.1
    # a sum's order inside a row differs from the jnp form's (a lane's part
    # of a row's sum is kept apart until the last tile): 2e-5 as test_v32's,
    # of contexts about 1 wide; peaked rows (the overflow case) lose the
    # scores' own rounding, |s| 200 at 2^-17
    assert np.abs(got - want).max() < (2e-5 if times == 1 else 2e-4)
