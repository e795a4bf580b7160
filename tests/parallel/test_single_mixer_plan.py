"""A plan whose rows are one half (``models/plan.py``: a mixer alone or an MLP
alone, one norm a row), with the state-space mixer (``models/ssm.py``) and
today's attention without a rotary embedding (``models/block.py``) as kinds
of it, and ungated relu2 experts beside a shared one of their form
(``parallel/moe.routed_moe``'s ``act``), against the plain reference,
``benchmarks/reference/nemotron_h.py``, at the configuration's tiny sizes
(four rows, ``ME*E``: every kind, and two routed rows so that a kind's
stacks are indexed), float32, seeded, on the CPU: prefill then cached steps
against the full forward on logits, the decoder against ``plan.backbone``,
loss and gradient, the mixer alone at 8 groups on lengths that are no
multiple of the chunk, the experts in both forms, the two shares of a pair of
chips against the uncut layer, what a decoder carries and which of it grows,
what the real plan says, what stays unbuilt.  Agreement only: nothing here is
a time.
"""

import copy
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, program
from ompi_tpu.models import block, plan, ssm
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.moe import routed_moe

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
PARITY = 1e-4


@pytest.fixture(scope="module")
def tiny():
    """(reference, its shape, the program's config in float32, a one-device
    mesh, parameters from the benchmark's initializer with every leaf that
    starts at one drawn away from it, the tiny configuration)."""
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
    params = {k: (jnp.asarray(rng.uniform(0.5, 1.5, size=v.shape), v.dtype)
                  if k in ones else v) for k, v in params.items()}
    return ref, ref.Shape.from_config(config), cfg, mesh, params, config


def error(got, want) -> float:
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.asarray(want).std())


def prompts_of(cfg, batch, length, seed=1):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(batch, length)).astype(np.int32)


@pytest.mark.parametrize("prompt_len,max_new", [(13, 6), (3, 4)])
def test_prefill_then_cached_steps_are_the_full_forward(tiny, prompt_len,
                                                        max_new):
    """Prompts of 13 and of 3: no multiple of the chunk (4), and under the
    convolution's taps."""
    ref, shape, cfg, mesh, params, _config = tiny
    prompts = prompts_of(cfg, 4, prompt_len, seed=prompt_len)
    # two sequences a prefill pass, each writing its states into the carry
    cfg = dataclasses.replace(cfg, prefill_tokens=2 * prompt_len)
    answer, z = make_decoder(cfg, mesh, max_new=max_new, keep_logits=4)(
        params, prompts)
    want = ref.logits(shape, params, np.asarray(answer))[:, prompt_len - 1:-1]
    assert error(z, want) < PARITY
    assert np.array_equal(np.asarray(z).argmax(-1),
                          np.asarray(answer)[:, prompt_len:])
    # the decoder against the plan's own whole-sequence pass
    whole = jax.jit(tfm.make_forward(cfg, mesh))(params, answer)
    assert error(z, whole[:, prompt_len - 1:-1]) < PARITY


def test_loss_and_gradient_are_the_references(tiny):
    ref, shape, cfg, mesh, params, _config = tiny
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
    for name in params:
        if name != "wgb":
            assert np.asarray(got_grad[name]).any(), name
            assert error(got_grad[name], want_grad[name]) < 1e-3, name


@pytest.mark.parametrize("length", [13, 3])
def test_the_state_space_kind_is_the_references_mixer_at_8_groups(tiny,
                                                                  length):
    """The kind's mixer alone: whole sequences of a length that is no
    multiple of the chunk against the reference's recurrence a position at a
    time, and then one cached step from the states it handed over against the
    whole sequence one longer."""
    ref, shape, cfg, _mesh, params, _config = tiny
    sz = cfg.plan.ssm
    assert (sz.n_groups, sz.chunk, shape.n_groups) == (8, 4, 8)
    rng = np.random.default_rng(length)
    h = jnp.asarray(rng.normal(size=(2, length + 1, cfg.d_model)),
                    jnp.float32)
    lp = {"ln1": params["ln1"][0],
          **{k: params[k][0] for k in ref.SSM_LEAVES}}
    mixer = jax.jit(ssm.PLAN_KIND.mixer, static_argnums=0)
    want = h + ref.mamba(shape, lp, ref._rmsnorm(h, lp["ln1"], shape.eps))
    got, conv, state = mixer(cfg, lp, h[:, :length])
    assert error(got, want[:, :length]) < PARITY
    assert conv.shape == (2, sz.d_conv - 1, sz.conv_dim)
    assert state.shape == (2, sz.n_heads, sz.head_dim, sz.d_state)
    assert state.dtype == jnp.float32
    step, conv1, state1 = mixer(cfg, lp, h[:, length:], carry=(conv, state))
    assert error(step, want[:, length:]) < PARITY
    _, conv2, state2 = mixer(cfg, lp, h)
    assert error(conv1, conv2) < PARITY and error(state1, state2) < PARITY


def moe_layer(cfg, x, weights, **over):
    args = dict(gated=cfg.moe_gated, act=cfg.moe_act, layer=0,
                renorm=cfg.moe_norm_topk, score=cfg.moe_score,
                scale=cfg.moe_scale, held=cfg.moe_held)
    return routed_moe(x, weights, cfg.moe_top_k, **{**args, **over})


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "kernel"])
def test_the_ungated_relu2_experts_and_shared_expert_are_the_references(
        tiny, kernel):
    ref, shape, cfg, _mesh, params, _config = tiny
    assert (cfg.moe_gated, cfg.moe_act) == (False, "relu2")
    assert "w3" not in params and "sw3" not in params
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 9, cfg.d_model)), jnp.float32)
    stacks = {k: params[k] for k in ("wg", "wgb", "w1", "w2", "sw1", "sw2")}
    want, weight = ref.moe(shape, stacks, 1, x)
    # the six weights of a token: renormalised, times the factor
    assert np.allclose(np.asarray(weight).sum(-1), cfg.moe_scale, atol=1e-5)

    @jax.jit
    def ours(x, params):
        weights = {"wg": params["wg"][1], "wgb": params["wgb"][1],
                   "w1": params["w1"], "w2": params["w2"]}
        return (moe_layer(cfg, x, weights, layer=1, kernel=kernel)
                + tfm._shared_expert(x, {k: params[k][1]
                                         for k in ("sw1", "sw2")},
                                     cfg.moe_act))

    assert error(ours(x, params), want) < PARITY
    routed, _ = ref.moe(shape, stacks, 1, x, None, False)
    assert float(jnp.abs(want - routed).max()) > 0.01   # the shared expert


def test_the_shares_of_two_chips_and_the_shared_expert_once_are_the_uncut_layer(
        tiny):
    """Rank 0 holds experts 0 to 7 and rank 1 experts 8 to 15 of the same
    router of 16: what each adds, and the shared expert once, is the
    reference's layer with all sixteen held."""
    ref, shape, cfg, _mesh, params, _config = tiny
    rng = np.random.default_rng(7)
    E, held = cfg.moe_experts, cfg.moe_held[1]
    assert (E, held) == (16, 8)
    both = {k: jnp.asarray(rng.normal(
        scale=0.2, size=(1, E, *params[k].shape[2:])), jnp.float32)
        for k in ("w1", "w2")}
    x = jnp.asarray(rng.normal(size=(2, 9, cfg.d_model)), jnp.float32)
    router = {"wg": params["wg"][0], "wgb": params["wgb"][0]}
    shares = [moe_layer(cfg, x, {**router, **{
        k: v[:, first:first + held] for k, v in both.items()}},
        held=(first, held)) for first in (0, held)]
    assert min(float(jnp.abs(s).max()) for s in shares) > 0.01
    shared = tfm._shared_expert(x, {k: params[k][0] for k in ("sw1", "sw2")},
                                cfg.moe_act)
    stacks = {**{k: params[k] for k in ("wg", "wgb", "sw1", "sw2")}, **both}
    uncut, _ = ref.moe(shape, stacks, 0, x, (0, E), True)
    assert error(shares[0] + shares[1] + shared, uncut) < PARITY
    # and the reference's own two shares, the shared expert in neither
    theirs = [ref.moe(shape, {**stacks, **{
        k: v[:, first:first + held] for k, v in both.items()}}, 0, x,
        (first, held), False)[0] for first in (0, held)]
    for got, want in zip(shares, theirs):
        assert error(got, want) < PARITY


def test_no_rotary_embedding_is_applied_and_one_can_be(tiny):
    """The attention kind as the model is assumed (NoPE) and with the rotary
    embedding a control plants: each is the reference's of its own form, and
    the two lie apart."""
    ref, shape, cfg, mesh, params, _config = tiny
    assert cfg.plan.attention == block.Attention(rope=False)
    tokens = prompts_of(cfg, 2, 14, seed=8)
    rotated = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, attention=block.Attention(rope=True)))
    got = jax.jit(tfm.make_forward(rotated, mesh))(params, tokens)
    want = ref.logits(dataclasses.replace(shape, rope=True), params, tokens)
    assert error(got, want[:, :]) < PARITY
    assert error(got, ref.logits(shape, params, tokens)[:, :]) > 0.01


def test_the_three_kinds_buffers_and_which_of_them_grow(tiny):
    _ref, _shape, cfg, mesh, _params, _config = tiny
    pl, sz = cfg.plan, cfg.plan.ssm
    assert pl.layers == (("ssm", None), (None, "moe"), ("attention", None),
                         (None, "moe")) and cfg.n_layers == 4
    assert [pl.norm(row, 0) for row in range(4)] == [0, 1, 1, 2]
    assert [pl.norm(row, 1) for row in range(4)] == [0, 0, 1, 1]
    assert [pl.index(row, "moe") for row in (1, 3)] == [0, 1]
    buffers = plan.carry(cfg, mesh, 3, 20)
    # a routed row carries nothing
    assert [(b.shape, str(b.dtype)) for b in buffers] == [
        ((1, 3, sz.d_conv - 1, sz.conv_dim), "float32"),
        ((1, 3, sz.n_heads, sz.head_dim, sz.d_state), "float32"),
        ((1, 3, 20, cfg.kv_heads, cfg.head_dim), "float32"),
        ((1, 3, 20, cfg.kv_heads, cfg.head_dim), "float32")]
    assert plan.grows(cfg) == (False, False, True, True)
    assert plan.leaf_names(cfg) == (
        "ln1", "ln2", "ssm_in", "ssm_out", "conv_w", "conv_b", "a_log",
        "dt_bias", "ssm_d", "ssm_norm", "wq", "wk", "wv", "wo", "wg", "w1",
        "w2", "wgb", "sw1", "sw2")
    # the state's type is the configuration's, the other buffers the stream's
    half = dataclasses.replace(
        cfg, compute_dtype="bfloat16", plan=dataclasses.replace(
            pl, ssm=dataclasses.replace(sz, state_dtype="bfloat16")))
    assert [str(b.dtype) for b in plan.carry(half, mesh, 1, 4)] == [
        "bfloat16"] * 4
    kept = dataclasses.replace(cfg, compute_dtype="bfloat16")
    assert [str(b.dtype) for b in plan.carry(kept, mesh, 1, 4)] == [
        "bfloat16", "float32", "bfloat16", "bfloat16"]


def test_the_first_fourteen_layers_are_six_mixers_six_routed_and_two_attending():
    """The cell's plan at its real sizes, read and not run."""
    config = cells.resolve(CELL).config
    cfg = program.program_config(config)
    pl = cfg.plan
    pattern = config["hybrid_override_pattern"][:14]
    assert pattern == "MEMEM*EMEMEM*E" and len(pl.layers) == 14
    rows = {"M": ("ssm", None), "E": (None, "moe"), "*": ("attention", None)}
    assert pl.layers == tuple(rows[c] for c in pattern)
    assert (pl.count("ssm"), pl.count("moe"), pl.count("attention")) == (
        6, 6, 2)
    assert pl.norm(14, 0) == 8 and pl.norm(14, 1) == 6
    sz = pl.ssm
    assert (sz.d_ssm, sz.n_heads, sz.head_dim, sz.d_state, sz.n_groups,
            sz.d_conv, sz.chunk, sz.state_dtype) == (
        4096, 64, 64, 128, 8, 4, 128, "float32")
    assert (sz.conv_dim, sz.in_dim) == (6144, 10304)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert not pl.attention.rope
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.moe_scale,
            cfg.moe_norm_topk, cfg.moe_score, cfg.moe_select_bias,
            cfg.moe_gated, cfg.moe_act, pl.d_expert, cfg.moe_shared) == (
        128, (0, 64), 6, 2.5, True, "sigmoid", True, False, "relu2", 1856,
        3712)
    shapes = {name: (n, *dims) for n, leaves in plan._kinds(cfg).values()
              for name, (dims, _std) in leaves.items()}
    assert shapes["w1"] == (6, 64, 2688, 1856)
    assert shapes["sw2"] == (6, 3712, 2688)
    assert shapes["ssm_in"] == (6, 2688, 10304)
    assert shapes["wk"] == (2, 2688, 256)


def test_what_stays_unbuilt_is_refused(tiny):
    *_rest, cfg, mesh, _params, config = tiny
    for key, value, said in (
            ("hybrid_override_pattern", "ME-E", "layers of kinds"),
            ("n_group", 8, "n_group"), ("topk_group", 4, "topk_group"),
            ("mlp_hidden_act", "silu", "mlp_hidden_act"),
            ("use_conv_bias", False, "use_conv_bias"),
            ("residual_in_fp32", True, "residual_in_fp32"),
            ("mamba_proj_bias", True, "a projection's bias"),
            ("num_hidden_layers", 5, "layers 4")):
        with pytest.raises(ValueError, match=f"not built for .*{said}"):
            program.program_config({**config, key: value})
        with pytest.raises(ValueError, match="written for"):
            program.reference(config).Shape.from_config(
                {**config, key: value})
    # one function says it of the hybrid block and of a plan's kinds
    for axis in ("tp", "sp"):
        split = types.SimpleNamespace(shape={"dp": 1, "sp": 1, "tp": 1,
                                             axis: 2})
        with pytest.raises(ValueError, match="a layer plan .* runs with "
                           f"{axis} == 1 only") as of_plan:
            plan.check_mesh(cfg, split)
        with pytest.raises(ValueError, match="the hybrid block .* runs with "
                           f"{axis} == 1 only") as of_block:
            ssm.check_mesh(cfg, split)
        assert (str(of_plan.value).partition(" runs with ")[2]
                == str(of_block.value).partition(" runs with ")[2])
    for layers, said in ((((None, None),), "a mixer, an MLP or both"),
                         ((("conv", None),), "not built")):
        with pytest.raises(ValueError, match=said):
            plan.check_mesh(dataclasses.replace(
                cfg, plan=dataclasses.replace(cfg.plan, layers=layers)), mesh)
    unsized = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, ssm=None))
    with pytest.raises(ValueError, match="holds no sizes"):
        plan.check_mesh(unsized, mesh)
