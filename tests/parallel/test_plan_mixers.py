"""The mixers of a layer plan on their own (``models/kda.py``,
``models/mla.py``) at cell 7's tiny sizes, float32, seeded, on the CPU: the
chunked delta rule against the recurrent one and the reference at lengths
that are no multiple of the chunk and with fast and slow channels, its
gradient, a step against the carried state, the convolution's zeros;
absorbed against materialised latent attention, and that no position
reaches it.  ``test_plan.py`` has the configuration (``tiny``) and the whole
model, ``test_plan_routed.py`` the routed layer, ``test_plan_train.py`` the
trainer: four files, because in tier-1 a file is one worker's
(``tools/tier1_time.py``).  Agreement only: nothing here is a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.models import kda, mla, plan
from tests.parallel.test_plan import PARITY, error, tiny


def delta_inputs(seed, B=2, T=37, H=2, K=8, fast=True):
    """q, k, v, g, beta of a delta rule whose channels decay at rates from
    1e-3 to 30 a position: a fast channel's exp(-cumsum) overflows float32
    inside a chunk of 16 (e^480), and a slow one must not be lost."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, T, H, K)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    rate = np.exp(rng.uniform(np.log(1e-3), np.log(30.0 if fast else 0.5),
                              size=(1, 1, H, K)))
    g = -(rate * rng.uniform(0.5, 1.5, size=(B, T, H, K))).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, size=(B, T, H)).astype(np.float32)
    return q, k, v, g, beta


# ---- the delta rule --------------------------------------------------------

@pytest.mark.parametrize("T,chunk", [(37, 16), (16, 16), (5, 16), (33, 4),
                                     (64, 64), (130, 64), (100, 32)])
def test_the_chunked_rule_is_the_recurrence(T, chunk):
    """Lengths short of a block, of one block, and that cross sub-blocks
    (16 positions) and blocks at the default of 64 and at 32."""
    ref, *_ = tiny()
    args = delta_inputs(T, T=T)
    want_o, want_s = ref.delta_rule(*map(jnp.asarray, args))
    got_o, got_s = jax.jit(kda.chunked, static_argnums=5)(*args, chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    assert error(got_o, want_o) < PARITY
    assert error(got_s, want_s) < PARITY


@pytest.mark.parametrize("chunk,T,held", [(16, 48, 30), (64, 80, 60)])
def test_a_fast_channel_neither_overflows_nor_hides_a_slow_one(chunk, T,
                                                               held):
    """With the decays' exponentials formed from ``exp(-cumsum)`` a channel
    at 30 a position reads inf or nan inside a block, and inside a sub-block
    of a block of 64, and across the boundary of two; here every exponent
    is a difference that is at most zero.  Nothing is written in the last
    ``held`` positions, so what a slow channel beside it still holds was
    written that long ago."""
    ref, *_ = tiny()
    q, k, v, g, beta = delta_inputs(3, T=T)
    beta[:, T - held:] = 0.0
    fallen = np.cumsum(-g, axis=1)
    assert (fallen[:, 15] > 100).any()                    # e^100 > float32
    assert (fallen[:, 20] - fallen[:, 10] > 100).any()    # across position 16
    got_o, got_s = kda.chunked(q, k, v, g, beta, chunk)
    want_o, want_s = ref.delta_rule(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    assert error(got_o, want_o) < PARITY and error(got_s, want_s) < PARITY
    slow = g.max(axis=(0, 1)) > -2e-3 * 1.5
    assert slow.any() and np.abs(np.asarray(got_s))[:, slow].max() > 0.1


def test_the_chunked_rules_gradient_is_the_recurrences():
    """``jax.grad`` of one number made of every output and of the last
    state, through blocks of 64 (two blocks, four sub-blocks each, fast
    channels among them) and through the reference's recurrence: the
    trainer's path, which no cell runs."""
    ref, *_ = tiny()
    args = tuple(map(jnp.asarray, delta_inputs(5, T=100)))
    B, _T, H, K = args[0].shape
    rng = np.random.default_rng(6)
    wo, ws = (jnp.asarray(rng.normal(size=dims), jnp.float32)
              for dims in (args[0].shape, (B, H, K, K)))

    def gradient(rule):
        def number(*inputs):
            o, S = rule(*inputs)
            return jnp.sum(o * wo) + jnp.sum(S * ws)
        return jax.jit(jax.grad(number, argnums=range(5)))(*args)

    want = gradient(ref.delta_rule)
    got = gradient(lambda *inputs: kda.chunked(*inputs, 64))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert error(a, b) < PARITY, name


def test_a_block_that_is_no_multiple_of_its_sub_blocks_is_refused():
    args = delta_inputs(1, T=48)
    with pytest.raises(ValueError, match="no multiple"):
        kda.chunked(*args, 24)


def test_a_step_against_the_carried_state_is_the_next_position():
    """``mixer`` with a carry, position by position from a zero state,
    against ``mixer`` over the whole sequence: outputs and final states."""
    _ref, _shape, cfg, _mesh, params = tiny()
    lp = plan._mixer_leaves(cfg, params, 1, "kda")
    B, T = 2, 21
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(B, T, cfg.d_model)), jnp.float32)
    whole, conv, state = kda.mixer(cfg, lp, h)
    conv_shape, state_shape = kda.state_shapes(cfg.plan.kda, B)
    conv_c = jnp.zeros(conv_shape, jnp.float32)
    kda_c = jnp.zeros(state_shape, jnp.float32)
    outs = []
    for t in range(T):
        o, conv_c, kda_c = kda.mixer(cfg, lp, h[:, t:t + 1],
                                     carry=(conv_c, kda_c))
        outs.append(o)
    assert error(jnp.concatenate(outs, axis=1), whole) < PARITY
    assert error(conv_c, conv) < PARITY and error(kda_c, state) < PARITY


def test_a_prompt_shorter_than_the_convolution_keeps_zeros_before_it():
    _ref, _shape, cfg, _mesh, params = tiny()
    lp = plan._mixer_leaves(cfg, params, 0, "kda")
    h = jnp.ones((1, 2, cfg.d_model), jnp.float32)
    _out, conv, _state = kda.mixer(cfg, lp, h)
    assert conv.shape == (1, 3, 3 * cfg.plan.kda.width)
    assert not np.asarray(conv[:, 0]).any() and np.asarray(conv[:, 1:]).all()


# ---- latent attention ------------------------------------------------------

def test_absorbed_attention_is_materialised_attention():
    """The cached step (the query through W^K, the context through W^V,
    against the latent alone) position by position against the
    whole-sequence form, which multiplies keys and values out."""
    _ref, _shape, cfg, _mesh, params = tiny()
    lp = plan._mixer_leaves(cfg, params, 3, "mla")
    B, T = 2, 13
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(B, T, cfg.d_model)), jnp.float32)
    whole, lat = mla.mixer(cfg, lp, h)
    assert lat.shape == (B, T, cfg.plan.mla.cached)
    lat_c = jnp.zeros((B, T + 3, cfg.plan.mla.cached), jnp.float32)
    outs = []
    for t in range(T):
        o, lat_c = mla.mixer(cfg, lp, h[:, t:t + 1],
                             carry=(lat_c, jnp.int32(t)))
        outs.append(o)
    assert error(jnp.concatenate(outs, axis=1), whole) < PARITY
    assert error(lat_c[:, :T], lat) < PARITY
    assert not np.asarray(lat_c[:, T:]).any()


def test_no_position_reaches_the_latent_layer():
    """NoPE: nothing in the layer knows where a position sits, so the last
    query's output is the same whatever order the earlier positions come
    in (under a rotary embedding it is not)."""
    _ref, _shape, cfg, _mesh, params = tiny()
    lp = plan._mixer_leaves(cfg, params, 3, "mla")
    h = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 9, cfg.d_model)), jnp.float32)
    order = np.array([4, 0, 7, 2, 6, 1, 5, 3, 8])
    straight, _ = mla.mixer(cfg, lp, h)
    shuffled, _ = mla.mixer(cfg, lp, h[:, order])
    assert error(shuffled[:, -1], straight[:, -1]) < PARITY
    assert error(shuffled[:, 4], straight[:, 4]) > 0.01
