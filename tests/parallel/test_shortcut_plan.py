"""A layer of two mixers with a branch across them (``models/plan.py``'s
``branches``: LongCat-Flash's shortcut-connected layer), latent attention with
a query latent and the two scale corrections (``models/mla.py``), and a
softmax router with a selection bias and identity experts
(``parallel/moe.routed_moe``'s ``zero``), against the plain reference,
``benchmarks/reference/longcat_flash.py``, at the configuration's tiny sizes,
float32, seeded, on the CPU: prefill then cached steps against the full
forward on logits, loss and gradient, the router against numpy, what a
decoder carries, what the plan says, what stays unbuilt.  Agreement only:
nothing here is a time.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.moe import routed_moe

CELL = "longcat-flash-chat.decode-896-128-b160"
PARITY = 1e-4


@pytest.fixture(scope="module")
def tiny():
    """(reference, its shape, the program's config in float32, a one-device
    mesh, parameters from the benchmark's initializer with every leaf that
    starts at one drawn away from it, the tiny configuration at two layers)."""
    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    config["entry"]["options"]["compute_dtype"] = "float32"
    config["num_layers"] = 2    # the second layer's place in every stack
    ref = program.reference(config)
    cfg = program.program_config(config)
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=11)
    rng = np.random.default_rng(12)
    ones = [k for k, (_dims, std) in
            program.param_table(ref, config).items() if std is None]
    params = {k: (jnp.asarray(rng.uniform(0.5, 1.5, size=v.shape), v.dtype)
                  if k in ones else v) for k, v in params.items()}
    return ref, ref.Shape.from_config(config), cfg, mesh, params, config


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


@pytest.mark.parametrize("prompt_len,max_new", [(17, 5), (3, 4)])
def test_prefill_then_cached_steps_are_the_full_forward(tiny, prompt_len,
                                                        max_new):
    ref, shape, cfg, mesh, params, _config = tiny
    prompts = prompts_of(cfg, 2, prompt_len, seed=prompt_len)
    answer, z = make_decoder(cfg, mesh, max_new=max_new, keep_logits=2)(
        params, prompts)
    want = ref.logits(shape, params, np.asarray(answer))[:, prompt_len - 1:-1]
    assert error(z, want) < PARITY
    assert np.array_equal(np.asarray(z).argmax(-1),
                          np.asarray(answer)[:, prompt_len:])


def test_the_prefill_in_groups_fills_the_same_carry(tiny):
    _ref, _shape, cfg, mesh, params, _config = tiny
    prompts = prompts_of(cfg, 4, 9)
    whole = make_decoder(cfg, mesh, max_new=6, keep_logits=4)(params, prompts)
    grouped = make_decoder(dataclasses.replace(cfg, prefill_tokens=18), mesh,
                           max_new=6, keep_logits=4)(params, prompts)
    assert np.array_equal(whole[0], grouped[0])
    assert error(grouped[1], whole[1]) < PARITY


def test_loss_and_gradient_are_the_references(tiny):
    ref, shape, cfg, mesh, params, _config = tiny
    cfg = dataclasses.replace(cfg, remat=None)
    tokens = jnp.asarray(prompts_of(cfg, 2, cfg.seq, seed=3))
    loss_fn = tfm.make_loss_fn(cfg, mesh)
    got, got_grad = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)

    def theirs(p):
        return ref.nll_sum(shape, p, tokens) / (tokens.shape[0]
                                                * (tokens.shape[1] - 1))

    want, want_grad = jax.value_and_grad(theirs)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    # the selection bias picks and does not weigh: no gradient reaches it
    assert not np.asarray(got_grad["wgb"]).any()
    assert not np.asarray(want_grad["wgb"]).any()
    for name in params:
        if name != "wgb":
            assert np.asarray(got_grad[name]).any(), name
            assert error(got_grad[name], want_grad[name]) < 1e-3, name


def numpy_router(x, wg, wgb, k, scale):
    logit = x @ wg
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    at = np.argsort(-(p + wgb), axis=-1, kind="stable")[..., :k]
    return at, np.take_along_axis(p, at, axis=-1) * scale


def test_the_router_is_a_softmax_that_picks_by_bias_and_is_not_renormalised(
        tiny):
    ref, shape, cfg, _mesh, params, _config = tiny
    rng = np.random.default_rng(6)
    x = rng.normal(size=(3, 7, cfg.d_model)).astype(np.float32)
    wg = np.asarray(params["wg"][0], np.float32)
    wgb = rng.normal(scale=0.05, size=cfg.moe_experts).astype(np.float32)
    at, w = numpy_router(x.astype(np.float64), wg, wgb, cfg.moe_top_k,
                         cfg.moe_scale)
    dense = np.zeros((3, 7, cfg.moe_experts))
    np.put_along_axis(dense, at, w, axis=-1)
    got = ref.route(shape, {"wg": wg, "wgb": wgb}, jnp.asarray(x))
    assert np.abs(np.asarray(got) - dense).max() < 1e-5
    plain, _ = numpy_router(x.astype(np.float64), wg, 0 * wgb, cfg.moe_top_k,
                            cfg.moe_scale)
    assert (np.sort(plain, -1) != np.sort(at, -1)).any()
    assert not np.allclose(w.sum(-1), cfg.moe_scale)    # as they are
    assert (w.sum(-1) < cfg.moe_scale).all()
    # the program's layer on the same router: the reference's, bias and all
    stacks = {"wg": wg[None], "wgb": wgb[None],
              **{k: params[k] for k in ("w1", "w3", "w2")}}
    want, _weight = ref.moe(shape, stacks, 0, jnp.asarray(x))
    got = routed_moe(jnp.asarray(x), {**stacks, "wg": wg, "wgb": wgb},
                     cfg.moe_top_k, gated=True, layer=0, score="softmax",
                     scale=cfg.moe_scale, held=cfg.moe_held,
                     zero=cfg.moe_zero)
    assert error(got, want) < PARITY


def test_the_defaults_trace_to_the_program_the_other_cells_have():
    """``routed_moe`` with ``zero`` at its default is the function it was,
    under either score: the same jaxpr as without it."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    w = {"wg": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
         "wgb": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
         "w1": jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32),
         "w3": jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32),
         "w2": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}
    for score, leaves in (("sigmoid", w), ("softmax", {
            k: v for k, v in w.items() if k != "wgb"})):
        old = jax.make_jaxpr(lambda x, w: routed_moe(
            x, w, 2, gated=True, score=score, held=(0, 2)))(x, leaves)
        new = jax.make_jaxpr(lambda x, w: routed_moe(
            x, w, 2, gated=True, score=score, held=(0, 2), zero=0))(
                x, leaves)
        assert str(old) == str(new)
        assert "moe.zero" not in old.pretty_print(name_stack=True)


def test_a_layer_owns_two_caches_and_a_branch_crosses_its_second_row(tiny):
    _ref, _shape, cfg, mesh, _params, _config = tiny
    pl, ml = cfg.plan, cfg.plan.mla
    assert pl.layers == (("mla", "dense"),) * 4 and cfg.n_layers == 4
    assert pl.branches == (("moe", 0, 1), ("moe", 2, 3))
    assert [pl.second(row) for row in range(4)] == [False, True, False, True]
    assert pl.count("mla") == pl.count("dense") == 4 and pl.count("moe") == 2
    assert [pl.index(row, "moe", branch=True) for row in (0, 2)] == [0, 1]
    assert [pl.index(row, "dense") for row in range(4)] == [0, 1, 2, 3]
    buffers = plan.carry(cfg, mesh, 3, 20)
    assert [b.shape for b in buffers] == [(1, 3, 20, ml.kv_rank + ml.rope)] * 4
    assert plan.grows(cfg) == (True,) * 4
    assert plan.leaf_names(cfg) == (
        "ln1", "ln2", "mla_qa", "mla_qn", "mla_qb", "mla_kva", "mla_n",
        "mla_kvb", "wo", "dw1", "dw3", "dw2", "wg", "w1", "w3", "w2", "wgb")
    assert (ml.q_rank, ml.q_scale, ml.kv_scale) == (48, (128 / 48) ** 0.5, 2)


def test_what_stays_unbuilt_is_refused(tiny):
    *_rest, config = tiny
    for key, value in (("attention_method", "MHA"), ("attention_bias", True),
                       ("zero_expert_type", "constant")):
        with pytest.raises(ValueError, match=f"not built for {key}"):
            program.program_config({**config, key: value})
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})
    cfg = program.program_config(config)
    for branches in ((("dense", 0, 1),), (("moe", 1, 0),), (("moe", 0, 4),)):
        astray = dataclasses.replace(cfg, plan=dataclasses.replace(
            cfg.plan, branches=branches))
        with pytest.raises(ValueError, match="not built|reads a row"):
            plan.check_mesh(astray, program.mesh(config, jax.devices()[:1]))
