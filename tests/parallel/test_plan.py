"""Layers of different kinds in one model (``models/plan.py``: Kimi Delta
Attention, NoPE latent attention, a leading dense MLP, a sigmoid router with
a selection bias and a shared expert over the experts one device holds)
against the plain reference, ``benchmarks/reference/kimi_linear.py``, at the
configuration's tiny sizes, float32, seeded, on the CPU: the chunked delta
rule against the recurrent one and the reference at lengths that are no
multiple of the chunk and with fast and slow channels, prefill then cached
steps against the full forward, absorbed against materialised latent
attention, the router against numpy, the shares of two devices and the
shared expert against the uncut layer, loss and gradient, what the decoder
carries, and the plan of one kind.  Agreement only: nothing here is a time.

Program and reference are both float32 and differ in the order of their
sums alone, so logits agree to ``PARITY`` of a deviation of the logits as
long as both pick the same experts, which they do in these seeds.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import kda, mla, plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel import moe
from ompi_tpu.parallel.mesh import make_mesh
from ompi_tpu.parallel.moe import routed_moe

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
PARITY = 1e-4

_built: dict = {}


def tiny():
    """(reference, its shape, the program's config in float32, a one-device
    mesh, parameters from the benchmark's initializer with every leaf that
    starts at one drawn away from it), made once."""
    if not _built:
        config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
        config["entry"]["options"]["compute_dtype"] = "float32"
        ref = program.reference(config)
        cfg = program.program_config(config)
        mesh = program.mesh(config, jax.devices()[:1])
        params = program.init_params(
            ref, config, program.param_shardings(config, cfg, mesh), seed=11)
        rng = np.random.default_rng(12)
        ones = [k for k, (_dims, std) in
                program.param_table(ref, config).items() if std is None]
        params = {k: (jnp.asarray(rng.uniform(0.5, 1.5, size=v.shape),
                                  v.dtype) if k in ones else v)
                  for k, v in params.items()}
        _built.update(ref=ref, shape=ref.Shape.from_config(config), cfg=cfg,
                      mesh=mesh, params=params, config=config)
    return (_built[k] for k in ("ref", "shape", "cfg", "mesh", "params"))


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


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


# ---- the router ------------------------------------------------------------

def numpy_router(x, wg, wgb, k, scale):
    score = 1 / (1 + np.exp(-(x @ wg)))
    at = np.argsort(-(score + wgb), axis=-1, kind="stable")[..., :k]
    w = np.take_along_axis(score, at, axis=-1)
    return at, w / w.sum(axis=-1, keepdims=True) * scale


def test_the_router_picks_by_the_biased_scores_and_weighs_by_the_scores():
    ref, shape, cfg, _mesh, params = tiny()
    rng = np.random.default_rng(6)
    x = rng.normal(size=(3, 7, cfg.d_model)).astype(np.float32)
    wg = np.asarray(params["wg"][0], np.float32)
    wgb = rng.normal(scale=0.3, size=cfg.moe_experts).astype(np.float32)
    at, w = numpy_router(x.astype(np.float64), wg, wgb, cfg.moe_top_k,
                         cfg.moe_scale)
    dense = np.zeros((3, 7, cfg.moe_experts))
    np.put_along_axis(dense, at, w, axis=-1)
    got = ref.route(shape, {"wg": wg, "wgb": wgb}, jnp.asarray(x))
    assert np.abs(np.asarray(got) - dense).max() < 1e-5
    # the bias changes picks: without it another set is chosen somewhere
    plain, _ = numpy_router(x.astype(np.float64), wg, 0 * wgb, cfg.moe_top_k,
                            cfg.moe_scale)
    assert (np.sort(plain, -1) != np.sort(at, -1)).any()
    assert np.allclose(w.sum(-1), cfg.moe_scale)


def moe_layer(cfg, params, x, weights=None, **over):
    """``routed_moe`` on layer 0 of the routed stacks, every argument the
    configuration's but those in ``over``."""
    args = dict(gated=True, layer=0, renorm=cfg.moe_norm_topk,
                score=cfg.moe_score, scale=cfg.moe_scale, held=cfg.moe_held)
    weights = weights or {"wg": params["wg"][0], "wgb": params["wgb"][0],
                          **{k: params[k] for k in ("w1", "w3", "w2")}}
    return routed_moe(x, weights, cfg.moe_top_k, **{**args, **over})


def test_the_shares_of_two_devices_and_the_shared_expert_are_the_uncut_layer():
    """Rank 0 holds experts 0-3 and rank 1 experts 4-7 of the same router:
    what each adds, and the shared expert once, is the reference's layer
    with all eight held."""
    ref, shape, cfg, _mesh, params = tiny()
    rng = np.random.default_rng(7)
    E, held = cfg.moe_experts, cfg.moe_held[1]
    both = {k: jnp.asarray(rng.normal(
        scale=0.2, size=(1, E, *params[k].shape[2:])), jnp.float32)
        for k in ("w1", "w3", "w2")}
    x = jnp.asarray(rng.normal(size=(2, 9, cfg.d_model)), jnp.float32)
    router = {"wg": params["wg"][0], "wgb": params["wgb"][0]}
    shares = [moe_layer(cfg, params, x, held=(first, held), weights={
        **router, **{k: v[:, first:first + held] for k, v in both.items()}})
        for first in (0, held)]
    whole = moe_layer(cfg, params, x, held=None,
                      weights={**router, **both})
    assert error(shares[0] + shares[1], whole) < PARITY
    assert float(jnp.abs(shares[0]).max()) > 0.01 < float(
        jnp.abs(shares[1]).max())
    # the reference: the uncut layer, shared expert once; and rank 0's share
    stacks = {**{k: params[k] for k in ("wg", "wgb", "sw1", "sw3", "sw2",
                                         "ln2")}, **both}
    stacks["ln2"] = jnp.ones_like(stacks["ln2"])
    uncut = ref._moe_layer(shape, stacks, 0, 0, x * 1.0, (0, E), True)
    normed = ref._rmsnorm(x, 1.0, shape.eps)
    got = sum(moe_layer(cfg, params, normed, held=(first, held), weights={
        **router, **{k: v[:, first:first + held] for k, v in both.items()}})
        for first in (0, held)) + tfm._shared_expert(
            normed, {k: params[k][0] for k in ("sw1", "sw3", "sw2")})
    assert error(x + got, uncut) < PARITY


@pytest.mark.parametrize("experts,identity,tokens,windows", [
    pytest.param(8, 4, (2, 9), False, id="4-shares-of-12-outputs"),
    pytest.param(8, 16, (4, 32), True, id="4-shares-of-24-outputs")])
def test_the_shares_of_two_and_the_identity_part_once_are_the_uncut_layer(
        experts, identity, tokens, windows):
    """LongCat-Flash's router at a small size: 8 experts and 4 identity
    experts, 3 a token, shared by 4 devices of 2 experts each.  The four
    shares' routed parts, and what every device adds alike for its own
    tokens (the identity picks' part) once, are the uncut ``MoE(x)``; and a
    shortcut-connected layer built from them is the uncut layer.  And at a
    router 24 wide, 16 of its outputs identity experts, where every share
    works through windows of its held picks (``moe._window_rows``)."""
    cell = "longcat-flash-chat.decode-896-128-b160"
    config = copy.deepcopy(program.tiny(cells.resolve(cell).config))
    config["entry"]["options"]["compute_dtype"] = "float32"
    config.update(num_layers=1, router_experts=experts,
                  zero_expert_num=identity, n_routed_experts=experts,
                  experts_held={"first": 0, "count": experts})
    ref = program.reference(config)
    shape, cfg = ref.Shape.from_config(config), program.program_config(config)
    assert (cfg.moe_experts, cfg.moe_zero, cfg.moe_top_k) == (
        experts + identity, identity, 3)
    picks = tokens[0] * tokens[1] * 3
    assert (moe._window_rows(picks, 16, 2, cfg.moe_experts) < picks) == windows
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=13)
    x = jnp.asarray(np.random.default_rng(14).normal(
        size=(*tokens, cfg.d_model)), jnp.float32)
    whole, weight = ref.moe(shape, params, 0, x)
    assert (np.asarray(weight[..., experts:]) > 0).any()    # identity picks
    router = {"wg": params["wg"][0], "wgb": params["wgb"][0]}
    firsts = range(0, experts, 2)

    def share(first, zero):
        """Device ``first // 2``'s routed part (``zero`` 0: the router's
        last outputs are experts held elsewhere), or with the identity
        part."""
        held = {k: params[k][:, first:first + 2] for k in ("w1", "w3", "w2")}
        return routed_moe(x, {**router, **held}, 3, gated=True, layer=0,
                          score="softmax", scale=cfg.moe_scale,
                          held=(first, 2), zero=zero)

    routed = [share(first, 0) for first in firsts]
    identity_part = share(0, identity) - routed[0]
    assert error(sum(routed) + identity_part, whole) < PARITY
    assert all(float(jnp.abs(part).max()) > 0.01
               for part in (*routed, identity_part))
    # the reference's own shares say the same
    parts = [ref.moe(shape, {**params, **{
        k: params[k][:, first:first + 2] for k in ("w1", "w3", "w2")}}, 0, x,
        (first, 2), False)[0] for first in firsts]
    for got, want in zip(routed, parts):
        assert error(got, want) < PARITY
    only = ref.moe(shape, params, 0, x, (0, 0), True)[0]
    assert error(sum(parts) + only, whole) < PARITY
    if windows:     # the layer below is built from the same function
        return
    # the layer: a branch lands by an add, so a layer built from the shares
    # is the uncut layer where the shares add up to ``MoE(x)`` and the uncut
    # program is the uncut reference (a chip's own share through the whole
    # model is ``tests/parallel/test_shortcut_plan.py``'s)
    ids = np.random.default_rng(15).integers(
        0, cfg.vocab, size=(2, 9)).astype(np.int32)
    uncut = jax.jit(lambda p: plan.backbone(
        cfg, tfm._mesh_comm(mesh), p, ids)[0])(params)
    assert error(uncut, ref.forward(shape, params, ids)) < PARITY


def test_picks_held_elsewhere_add_nothing_and_are_not_renormalised_away():
    """Rank 0's share is the uncut layer's terms of experts 0-3 with the
    weights made over all of a token's picks; renormalised over the held
    picks it would be larger."""
    ref, shape, cfg, _mesh, params = tiny()
    x = jnp.asarray(np.random.default_rng(8).normal(
        size=(2, 9, cfg.d_model)), jnp.float32)
    stacks = {k: params[k] for k in ("wg", "wgb", "w1", "w3", "w2", "sw1",
                                     "sw3", "sw2")}
    stacks["ln2"] = jnp.ones((1, cfg.d_model), jnp.float32)
    normed_in = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + shape.eps)
    want = ref._moe_layer(shape, stacks, 0, 0, normed_in, None, False)
    # the reference norms its input again: hand the program the same
    again = ref._rmsnorm(normed_in, 1.0, shape.eps)
    got = moe_layer(cfg, params, again)
    assert error(normed_in + got, want) < PARITY
    weight = np.asarray(ref.route(shape, {"wg": stacks["wg"][0],
                                          "wgb": stacks["wgb"][0]}, again))
    here = weight[..., :cfg.moe_held[1]].sum(-1)
    assert (here < 0.999 * cfg.moe_scale).any()     # some picks are absent
    assert np.allclose(weight.sum(-1), cfg.moe_scale, rtol=1e-5)


def test_the_defaults_trace_to_the_program_the_other_cells_have():
    """``routed_moe`` with the new arguments at their defaults is the
    function it was: the same jaxpr as with none of them given."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    w = {"wg": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
         "w1": jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32),
         "w3": jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32),
         "w2": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}
    old = jax.make_jaxpr(lambda x, w: routed_moe(
        x, w, 2, gated=True, renorm=True))(x, w)
    new = jax.make_jaxpr(lambda x, w: routed_moe(
        x, w, 2, gated=True, renorm=True, score="softmax", scale=1.0,
        held=None))(x, w)
    assert str(old) == str(new)


# ---- the whole model -------------------------------------------------------

@pytest.mark.parametrize("prompt_len,max_new", [(12, 8), (17, 5), (3, 4)])
def test_prefill_then_cached_steps_are_the_full_forward(prompt_len, max_new):
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 2, prompt_len, seed=prompt_len)
    answer, z = make_decoder(cfg, mesh, max_new=max_new, keep_logits=2)(
        params, prompts)
    answer = np.asarray(answer)
    want = ref.logits(shape, params, answer)[:, prompt_len - 1:-1]
    assert error(z, want) < PARITY
    assert np.array_equal(np.asarray(z).argmax(-1), answer[:, prompt_len:])


def test_the_prefill_in_groups_fills_the_same_carry():
    ref, shape, cfg, mesh, params = tiny()
    prompts = prompts_of(cfg, 4, 9, seed=3)
    whole = make_decoder(cfg, mesh, max_new=6, keep_logits=4)(params, prompts)
    grouped = make_decoder(dataclasses.replace(cfg, prefill_tokens=18), mesh,
                           max_new=6, keep_logits=4)(params, prompts)
    assert np.array_equal(whole[0], grouped[0])
    assert error(grouped[1], whole[1]) < PARITY


@pytest.mark.parametrize("dp", [1, 2])
def test_every_decoder_of_a_plan_starts_from_one_prefill_program(dp):
    """The ``max_new=1`` decoder and a longer one call one jitted prefill,
    so their first tokens (and the logits those were picked from) are the
    same bits; the second program takes every state that does not grow in
    the buffer it came in, and no donated buffer goes unused."""
    import warnings

    from ompi_tpu.core import scopes
    from ompi_tpu.models import decode

    _ref, _shape, cfg, mesh, params = tiny()
    scopes.reset()
    if dp == 2:
        mesh = make_mesh({"dp": 2, "sp": 1, "tp": 1},
                         devices=jax.devices()[:2])
        params = tfm.shard_params(cfg, mesh, params)
    keep = 2 if dp == 1 else 0
    prompts = prompts_of(cfg, 4, 11, seed=7)
    decode._prefill_program.cache_clear()
    one = make_decoder(cfg, mesh, max_new=1, keep_logits=keep)
    many = make_decoder(cfg, mesh, max_new=6, keep_logits=keep)
    info = decode._prefill_program.cache_info()
    assert (info.misses, info.hits) == (1, 1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        first, rest = one(params, prompts), many(params, prompts)
    if keep:
        (first, z1), (rest, zn) = first, rest
        assert z1.shape == (2, 1, cfg.vocab) and zn.shape == (2, 6, cfg.vocab)
        assert np.array_equal(z1[:, 0], zn[:, 0])
    assert first.shape == (4, 12) and rest.shape == (4, 17)
    assert np.array_equal(first, np.asarray(rest)[:, :12])
    # one trace each of the two programs, however often they are called
    many(params, prompts)
    assert scopes.startup()["retraces"] == 0


def test_loss_and_gradient_are_the_references():
    ref, shape, cfg, mesh, params = tiny()
    tokens = prompts_of(cfg, 2, cfg.seq, seed=5)
    loss_fn = tfm.make_loss_fn(cfg, mesh)
    ours, grads = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)

    def ref_loss(p):
        return ref.nll_sum(shape, p, jnp.asarray(tokens)) / (
            tokens.shape[0] * (tokens.shape[1] - 1))

    theirs, want = jax.value_and_grad(ref_loss)(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5)
    for leaf in ("kda_q", "kda_a", "kda_dt", "kda_b", "kda_f2", "kda_cv",
                 "mla_kvb", "mla_q", "wg", "w2", "sw1", "dw2", "emb", "ln1"):
        scale = float(jnp.abs(want[leaf]).max())
        assert scale > 0, leaf
        assert float(jnp.abs(grads[leaf] - want[leaf]).max()) < 2e-3 * scale, leaf
    # the selection bias picks and does not weigh: no gradient reaches it
    assert not np.asarray(grads["wgb"]).any()


def test_a_train_step_runs_on_two_devices():
    """dp = 2: the layers' gradients are summed where the loop starts."""
    _ref, _shape, cfg, _mesh, params = tiny()
    mesh = make_mesh({"dp": 2, "sp": 1, "tp": 1}, devices=jax.devices()[:2])
    tokens = prompts_of(cfg, 4, cfg.seq, seed=6)
    one = tfm.make_loss_fn(cfg, make_mesh(
        {"dp": 1, "sp": 1, "tp": 1}, devices=jax.devices()[:1]))
    want = jax.jit(jax.grad(one))(params, tokens)
    loss_and_grads = jax.jit(tfm._make_loss_and_grads(cfg, mesh))
    _loss, got = loss_and_grads(tfm.shard_params(cfg, mesh, params), tokens)
    for leaf in ("kda_q", "mla_q", "w2", "dw1", "emb", "head", "ln2"):
        a, b = np.asarray(got[leaf]), np.asarray(want[leaf])
        assert np.abs(a - b).max() < 1e-3 * np.abs(b).max(), leaf


def test_the_carry_is_a_layers_own_buffers_and_the_state_is_float32():
    _ref, _shape, cfg, mesh, _params = tiny()
    cfg = dataclasses.replace(cfg, compute_dtype="bfloat16")
    buffers = plan.carry(cfg, mesh, 3, 20)
    kd, ml = cfg.plan.kda, cfg.plan.mla
    conv = (1, 3, kd.conv - 1, 3 * kd.width)
    state = (1, 3, kd.n_heads, kd.head_dim, kd.head_dim)
    lat = (1, 3, 20, ml.kv_rank + ml.rope)
    # KDA, KDA, KDA, latent, KDA: two buffers a KDA layer, one a latent one
    assert [b.shape for b in buffers] == [conv, state] * 3 + [lat] + [
        conv, state]
    assert [b.dtype for b in buffers] == (
        [jnp.bfloat16, jnp.float32] * 3 + [jnp.bfloat16]
        + [jnp.bfloat16, jnp.float32])


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_a_split_of_the_mixers_is_refused(axis):
    _ref, _shape, cfg, _mesh, _params = tiny()
    shape = {"dp": 1, "sp": 1, "tp": 1, axis: 2}
    mesh = make_mesh(shape, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"{axis} == 1 only"):
        plan.check_mesh(cfg, mesh)
    if axis == "tp":
        with pytest.raises(ValueError, match="tp == 1 only"):
            make_decoder(cfg, mesh, max_new=2)


def test_the_plan_is_read_off_the_published_lists():
    _ref, shape, cfg, _mesh, _params = tiny()
    assert cfg.plan.layers == shape.kinds == (
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"))
    assert [cfg.plan.index(layer, "kda") for layer in (0, 1, 2, 4)] == [
        0, 1, 2, 3]
    assert cfg.plan.index(3, "mla") == 0 and cfg.plan.index(4, "moe") == 3
    real = program.program_config(cells.resolve(CELL).config)
    assert real.plan.layers == cfg.plan.layers
    assert real.moe_held == (0, 128) and real.moe_experts == 256
    assert real.plan.kda.state_dtype == "float32"


BENCH = cells.load_benchmark()
PLANNED = ("kimi-linear-48b-a3b", "minicpm-sala", "kimi-vl-a3b",
           "longcat-flash-chat")
ONE_KIND = [c["name"] for c in BENCH["configs"] if c["name"] not in PLANNED]


@pytest.mark.parametrize("name", ONE_KIND)
def test_a_configuration_without_a_plan_has_nothing_of_a_plans(name):
    """The five configurations the benchmark had: no plan, and the tree, the layer leaves and the specs
    what the reference of each lays out (``test_reference.py`` holds the
    shapes): nothing of a plan's is in them."""
    from jax.sharding import PartitionSpec as P

    config = program.tiny(cells.load_json(
        f"{cells.BENCH_DIR}/configs/{name}.json"))
    cfg = program.program_config(config)
    assert cfg.plan is None and cfg.moe_held is None
    assert cfg.moe_score == "softmax" and not cfg.moe_shared
    tree = tfm.init_params(cfg)
    table = program.param_table(program.reference(config), config)
    assert set(tree) == set(table) == set(tfm.param_specs(P, cfg))
    assert set(tfm.layer_leaves(cfg)) == {
        k for k, v in tree.items() if v.ndim and v.shape[0] == cfg.n_layers
        and k not in ("emb", "head", "lnf")}
    assert not {k for k in tree if k.startswith(("kda_", "mla_", "sw",
                                                 "lt_"))}


# ---- a kind is a module and a line ------------------------------------------

def test_the_old_two_kinds_give_the_tree_the_carry_and_the_names_they_gave():
    """Cell 7's plan through the table of kinds: the leaves in the order the
    program draws them, the dense MLP's under the names it has beside
    experts, the carry's buffers and which of them grow."""
    _ref, _shape, cfg, mesh, params = tiny()
    assert plan.leaf_names(cfg) == (
        "ln1", "ln2", "kda_q", "kda_k", "kda_v", "kda_cq", "kda_ck",
        "kda_cv", "kda_f1", "kda_f2", "kda_a", "kda_dt", "kda_b", "kda_g1",
        "kda_g2", "kda_n", "kda_o", "mla_q", "mla_kva", "mla_n", "mla_kvb",
        "wo", "dw1", "dw3", "dw2", "wg", "w1", "w3", "w2", "wgb", "sw1",
        "sw3", "sw2")
    assert set(params) == {"emb", "head", "lnf", *plan.leaf_names(cfg)}
    assert plan.grows(cfg) == (False, False) * 3 + (True,) + (False, False)
    assert (cfg.plan.scale_emb, cfg.plan.branch_scale,
            cfg.plan.head_divisor) == (1, 1, 1)
    assert not kda.POSITIONED and mla.POSITIONED
    short = plan.carry(cfg, mesh, 2, 9)
    longer = plan.carried(cfg, mesh, iter(short), 13)
    assert [b.shape for b in longer] == [
        b.shape for b in plan.carry(cfg, mesh, 2, 13)]
    assert longer[6].shape[2] == 13 and longer[0] is short[0]


def four_kinds(cfg):
    """Cell 7's tiny configuration with a lightning and a block-selected
    layer in the place of its latent one and of a KDA one."""
    from ompi_tpu.models.block_select import BlockSelect
    from ompi_tpu.models.lightning import Lightning

    layers = (("kda", "dense"), ("lightning", "moe"), ("kda", "moe"),
              ("block_select", "moe"), ("lightning", "dense"))
    return dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, layers=layers,
        lightning=Lightning(n_heads=2, head_dim=16, depth=5, chunk=8),
        block_select=BlockSelect(kernel=4, stride=2, block=8, topk=3,
                                 init_blocks=1, window=8, dense_len=16,
                                 q_slice=16),
        scale_emb=2.0, branch_scale=0.5, head_divisor=4.0))


def test_a_plan_of_the_new_kinds_beside_the_old_decodes_as_it_forwards():
    _ref, _shape, cfg, mesh, _params = tiny()
    cfg = four_kinds(cfg)
    names = plan.leaf_names(cfg)
    assert {"kda_q", "lt_q", "wq", "wz", "wo", "dw1", "w1"} <= set(names)
    assert "mla_q" not in names
    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg, seed=3))
    assert params["lt_q"].shape[0] == params["dw1"].shape[0] == 2
    assert params["wq"].shape[0] == 1 and params["kda_q"].shape[0] == 2
    assert plan.grows(cfg) == (False, False, False, False, False, True, True,
                               False)
    prompts = prompts_of(cfg, 3, 27)
    tokens, kept = make_decoder(cfg, mesh, max_new=10, keep_logits=3)(
        params, prompts)
    full = jax.jit(tfm.make_forward(cfg, mesh))(params, tokens)[:, 26:-1]
    assert error(kept, full) < PARITY
    again = make_decoder(dataclasses.replace(cfg, prefill_tokens=27), mesh,
                         max_new=10)(params, prompts)
    assert np.array_equal(again, tokens)
    # the three constants are in the result
    for change in ({"scale_emb": 1.0}, {"branch_scale": 1.0},
                   {"head_divisor": 1.0}):
        other = dataclasses.replace(cfg, plan=dataclasses.replace(
            cfg.plan, **change))
        moved = jax.jit(tfm.make_forward(other, mesh))(params,
                                                       tokens)[:, 26:-1]
        assert error(moved, full) > 0.05, change


def test_the_barrier_behind_a_steps_lightning_products_moves_no_arithmetic(
        monkeypatch):
    """A cached step's q, k and v products of a lightning layer end behind
    an ``optimization_barrier`` (``lightning.mixer`` says why: the weights
    are then read where they lie in their stacks); the whole-sequence pass
    has none.  The decoder's tokens and kept logits are bit for bit what the
    same plan gives with the barrier taken out."""
    _ref, _shape, cfg, mesh, _params = tiny()
    cfg = four_kinds(cfg)
    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg, seed=3))
    prompts = prompts_of(cfg, 3, 27)

    def decoded():
        decoder = make_decoder(cfg, mesh, max_new=10, keep_logits=3)
        return (str(jax.make_jaxpr(decoder)(params, prompts)),
                *decoder(params, prompts))

    text, tokens, kept = decoded()
    assert (text.count("optimization_barrier")
            == 3 * cfg.plan.count("lightning") == 6)
    forward = str(jax.make_jaxpr(tfm.make_forward(cfg, mesh))(params, tokens))
    assert "optimization_barrier" not in forward
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    plain, plain_tokens, plain_kept = decoded()
    assert "optimization_barrier" not in plain
    assert np.array_equal(plain_tokens, tokens)
    assert np.array_equal(plain_kept, kept)


def test_two_kinds_that_name_a_leaf_alike_are_refused():
    _ref, _shape, cfg, _mesh, _params = tiny()
    cfg = four_kinds(cfg)
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, layers=(*cfg.plan.layers[:4], ("mla", "moe"))))
    with pytest.raises(ValueError, match="name a leaf alike.*wo"):
        plan.leaf_names(cfg)


def test_a_kind_the_table_lacks_is_refused():
    _ref, _shape, cfg, mesh, _params = tiny()
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, layers=(("mamba", "dense"), ("kda", "glu"))))
    with pytest.raises(ValueError, match=r"\['glu', 'mamba'\]: not built"):
        plan.check_mesh(cfg, mesh)
