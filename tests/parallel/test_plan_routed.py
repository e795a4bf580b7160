"""The routed layer of a layer plan (``parallel/moe.routed_moe`` as
``models/plan.py`` calls it: a sigmoid router with a selection bias and a
shared expert over the experts one device holds) at tiny sizes, float32,
seeded, on the CPU: the router against numpy, the shares of two devices and
the shared expert against the uncut layer, four devices' shares and the
identity experts' part against LongCat-Flash's, picks held elsewhere, and
the defaults' jaxpr.  ``test_plan.py`` has the configuration (``tiny``).
Agreement only: nothing here is a time.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.parallel import moe
from ompi_tpu.parallel.moe import routed_moe
from tests.parallel.test_plan import PARITY, error, tiny


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
        cfg, tfm._mesh_comm(mesh), p, ids))(params)
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
