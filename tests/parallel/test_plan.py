"""Layers of different kinds in one model (``models/plan.py``: Kimi Delta
Attention, NoPE latent attention, a leading dense MLP, a sigmoid router with
a selection bias and a shared expert over the experts one device holds)
against the plain reference, ``benchmarks/reference/kimi_linear.py``, at the
configuration's tiny sizes, float32, seeded, on the CPU: prefill then cached
steps against the full forward, the prefill in groups, one prefill program
for every decoder, what the decoder carries, the plan of one kind, and the
table of kinds.  The mixers' own cases are ``test_plan_mixers.py``'s, the
routed layer's ``test_plan_routed.py``'s, loss and gradient
``test_plan_train.py``'s; they take ``tiny`` and the helpers from here.
Agreement only: nothing here is a time.

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
from ompi_tpu.models import decode, kda, mla, plan
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.parallel.mesh import make_mesh

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
           "longcat-flash-chat", "nemotron-3-nano-30b-a3b",
           "granite-4.0-h-small", "deepseek-v3.2-exp",
           "phi-4-mini-flash-reasoning")
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
    has none.  (A routed layer's sum ends behind one of its own in every
    pass, ``moe._sum_of_picks``.)  The decoder's tokens and kept logits are
    bit for bit what the same plan gives with the barriers taken out."""
    _ref, _shape, cfg, mesh, _params = tiny()
    cfg = four_kinds(cfg)
    params = tfm.shard_params(cfg, mesh, tfm.init_params(cfg, seed=3))
    prompts = prompts_of(cfg, 3, 27)

    def decoded():
        decoder = make_decoder(cfg, mesh, max_new=10, keep_logits=3)
        return (str(jax.make_jaxpr(decoder)(params, prompts)),
                *decoder(params, prompts))

    text, tokens, kept = decoded()
    routed = cfg.plan.count("moe")      # in the prefill and in the step
    assert (text.count("optimization_barrier") - 2 * routed
            == 3 * cfg.plan.count("lightning") == 6)
    forward = str(jax.make_jaxpr(tfm.make_forward(cfg, mesh))(params, tokens))
    assert forward.count("optimization_barrier") == routed == 3
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    decode._prefill_program.cache_clear()   # its routed layers' are traced
    plain, plain_tokens, plain_kept = decoded()
    decode._prefill_program.cache_clear()
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
