"""``deepseek-v3.2-exp``: the program at the configuration's tiny sizes
against its plain reference (a prefill and then cached steps on logits, the
chips' shares against the uncut layer, the group-limited picks against a
numpy sort, the scaled rotation against its formula, the index's queries
out of the query latent), the two kernels in TPU interpret mode against
their ``jax.numpy`` forms, the yardstick's arithmetic at the real sizes
against counts made by hand, and what ``mla_moe_config`` builds and still
refuses.  CPU only: agreement and counts, no device metric."""

import copy
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, costs, program
from ompi_tpu.models import mla, plan, sparse_index
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models.decode import make_decoder
from ompi_tpu.ops import latent_decode as decode_kernel
from ompi_tpu.ops import masked_latent_attention as prefill_kernel
from ompi_tpu.parallel import moe
from tests.benchmarks import decode_cells, test_reference

NAME = "deepseek-v3.2-exp"
CELL = "deepseek-v3.2-exp.decode-16k-512-b8"
CELL_10 = "kimi-vl-a3b.decode-16k-256-b32"
D, V, H = 7168, 16_160, 128
MLA = (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512 + 512 * H * 256
       + H * 128 * D)
INDEX = 1536 * 64 * 128 + D * 128 + 2 * 128 + D * 64
EXPERT = 3 * D * 2048
DENSE_LAYER = MLA + INDEX + 3 * D * 18432 + 2 * D
ROUTED_LAYER = MLA + INDEX + D * 256 + 256 + 8 * EXPERT + EXPERT + 2 * D
N_PARAMS = 2 * V * D + D + DENSE_LAYER + 4 * ROUTED_LAYER
B, TP, NEW = 8, 15_872, 512
IDLE = round((31 / 32) ** 8 * 8 * 4 * EXPERT)


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


# ---- the yardstick at the real sizes ---------------------------------------

def test_the_parameters_by_kind_are_the_hand_counts():
    _cell, made = job()
    assert MLA == 187_107_328 and INDEX == 13_959_424      # ISSUE 67
    assert EXPERT == 44_040_192
    assert DENSE_LAYER == 597_442_816 and ROUTED_LAYER == 599_278_080
    assert V * D == 115_834_880
    assert made.n_params == N_PARAMS == 3_226_232_064       # 6.45 GB


def test_facts_are_the_hand_counts():
    _cell, made = job()
    facts = made.facts()
    counts = facts["counts"]
    # what one token multiplies here: no norm's scale and no bias; a quarter
    # of an expert (8 picks x 8 held / 256) and the shared one
    active = (5 * (MLA - 1536 - 512 + INDEX - 256) + 3 * D * 18432
              + 4 * (D * 256 + EXPERT + EXPERT // 4) + V * D)
    assert counts["active_params"] == active
    assert counts["attention_width"] == 2 * 64 * 128 // 4 == 4096
    assert counts["kv_elements"] == 128
    assert counts["state_elements"] == 5 * 2048 * 576
    assert counts["lookup_params"] == V * D + IDLE
    assert IDLE == 1_093_183_092                            # ISSUE 67
    assert facts["prefill_flops"] == B * TP * (
        2 * (active - V * D) + 4 * 5 * 4096 * TP) + B * 2 * V * D
    # every parameter but the table and the experts no token of a step is
    # sent to; the index key of every live row; the selected latent rows
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D - IDLE) + 5 * B * (TP + NEW // 2) * 128 * 2
        + B * 5 * 2048 * 576 * 2) == costs.decode_step_bytes(
            2 * (N_PARAMS - V * D - IDLE), 5, B, TP, NEW, 128, 2,
            5 * 2048 * 576)
    assert 4.29e9 < facts["decode_step_bytes"] < 4.30e9
    assert counts["routed"] == {"layers": 4, "experts": 8, "top_k": 1,
                                "d_model": D, "d_expert": 2048}


def test_the_cut_keeps_every_width_and_lists_what_it_reduced():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"] if c["name"] == NAME)
    assert row["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129_280,
        "torch_dtype": config["published"]["torch_dtype"]}
    kept = {"hidden_size": 7168, "num_attention_heads": 128,
            "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
            "index_topk": 2048, "intermediate_size": 18432,
            "moe_intermediate_size": 2048, "router_experts": 256,
            "n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "routed_scaling_factor": 2.5,
            "rope_theta": 10000, "num_nextn_predict_layers": 1,
            "max_position_embeddings": 163_840, "rms_norm_eps": 1e-6}
    assert {k: config[k] for k in kept} == kept
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert config["experts_held"] == {"first": 0, "count": 8}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (5, 1, 8, V)
    assert (cell.traffic["batch"], cell.traffic["prompt_len"],
            cell.traffic["max_new"], cell.chips) == (B, TP, NEW, 1)
    assert (TP + NEW) % 1024 == 0 and TP % config["index_q_slice"] == 0
    assert config["counters"]["sequences_a_step"] == B
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why", "assumed"):
        assert config[key], key
    said = " ".join(config["departures"])
    for word in ("multi-token-prediction", "8-bit", "FP8"):
        assert word in said, word


# ---- the scaled rotation ---------------------------------------------------

def test_the_blended_frequencies_are_the_formulas():
    cfg = program.program_config(cells.resolve(CELL).config)
    ml = cfg.plan.mla
    yarn = ml.yarn
    assert yarn.limits(64, 1e4) == (10, 23)
    # my arithmetic of ISSUE 67: 10.47 and 22.51 before floor and ceil
    pair = [64 * math.log(4096 / (turns * 2 * math.pi))
            / (2 * math.log(1e4)) for turns in (32, 1)]
    assert pair == pytest.approx([10.47, 22.51], abs=5e-3)
    freqs = ml.frequencies()
    assert freqs.shape == (32,) and freqs.dtype == np.float32
    i = np.arange(32)
    plain = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    assert freqs == pytest.approx((1 - ramp) * plain + ramp * plain / 40,
                                  rel=1e-6)
    assert (freqs[:11] == plain[:11].astype(np.float32)).all()
    assert freqs[23:] == pytest.approx(plain[23:] / 40, rel=1e-6)
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert yarn.softmax_factor == pytest.approx(1.8739, abs=1e-4) == m * m
    assert yarn.rotation_factor == 1
    assert ml.scale == pytest.approx(192 ** -0.5 * m * m)
    # the reference's own arithmetic gives the same
    ref = program.reference(cells.resolve(CELL).config)
    shape = ref.Shape.from_config(cells.resolve(CELL).config)
    low, high, theirs = ref.yarn_pairs(shape)
    assert (low, high) == (10, 23)
    assert freqs == pytest.approx(theirs, rel=1e-6)
    assert ref.softmax_scale(shape) == pytest.approx(ml.scale)


def test_rotate_turns_pairs_by_the_frequencies_it_is_handed():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 8)), jnp.float32)
    at = jnp.asarray([0, 1, 2, 7, 30])
    freqs = np.asarray([1.0, 0.5, 0.05, 0.001], np.float32)
    got = np.asarray(mla.rotate(x, at, 1e4, freqs))
    ang = np.asarray(at)[:, None] * freqs                   # (T, 4)
    a, b = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.stack([a * cos - b * sin, b * cos + a * sin],
                    axis=-1).reshape(x.shape)
    assert got == pytest.approx(want, abs=1e-5)
    # no frequencies: the published theta's, as every other cell's
    plain = 1e4 ** (-2 * np.arange(4) / 8)
    assert np.asarray(mla.rotate(x, at, 1e4)) == pytest.approx(
        np.asarray(mla.rotate(x, at, 1e4, plain.astype(np.float32))),
        abs=1e-6)


# ---- the group-limited router ----------------------------------------------

def numpy_picks(choice, n_group, keep, k):
    """The picks (n, k), sorted, of ``choice`` (n, E) by numpy sorts."""
    n, E = choice.shape
    by_group = choice.reshape(n, n_group, E // n_group)
    score = np.sort(by_group, -1)[..., -2:].sum(-1)
    best = np.argsort(-score, -1, kind="stable")[:, :keep]
    allowed = np.zeros((n, n_group), bool)
    np.put_along_axis(allowed, best, True, -1)
    masked = np.where(allowed[:, :, None], by_group, -np.inf).reshape(n, E)
    return np.sort(np.argsort(-masked, -1, kind="stable")[:, :k], -1)


def test_group_limited_picks_are_a_numpy_sorts():
    rng = np.random.default_rng(3)
    choice = rng.uniform(size=(64, 256)).astype(np.float32)
    want = numpy_picks(choice.astype(np.float64), 8, 4, 8)
    limited = moe._within_groups(jnp.asarray(choice), (8, 4))
    got = np.sort(np.asarray(jax.lax.top_k(limited, 8)[1]), -1)
    assert (got == want).all()
    plain = np.sort(np.asarray(jax.lax.top_k(jnp.asarray(choice), 8)[1]), -1)
    assert (plain != want).any(axis=-1).mean() > 0.5
    # every pick lies in one of four groups of 32 neighbours
    assert all(len(set(row // 32)) <= 4 for row in got)


def test_a_token_whose_plain_top_8_is_refused_by_its_groups():
    """Eight experts of one a group lead; the groups' scores (their two
    best) keep four groups, so four of the eight leaders are not picked."""
    choice = np.full((1, 256), 0.1, np.float32)
    leaders = 32 * np.arange(8)
    choice[0, leaders] = 0.9 - 0.01 * np.arange(8)
    # groups 4 to 7 have a strong second expert: they are the four best
    choice[0, 32 * np.arange(4, 8) + 1] = 0.8
    limited = moe._within_groups(jnp.asarray(choice), (8, 4))
    got = set(np.asarray(jax.lax.top_k(limited, 8)[1])[0].tolist())
    assert got == set((32 * np.arange(4, 8)).tolist()) | set(
        (32 * np.arange(4, 8) + 1).tolist())
    assert set(np.asarray(jax.lax.top_k(jnp.asarray(choice), 8)[1])[0]
               .tolist()) != got
    assert (numpy_picks(choice.astype(np.float64), 8, 4, 8)[0]
            == sorted(got)).all()


def test_the_router_is_the_references_and_refuses_what_is_not_built():
    ref, config, cfg, _mesh, params = test_reference.tiny(NAME)
    shape = ref.Shape.from_config(config)
    assert cfg.moe_groups == (4, 2) and cfg.moe_held == (0, 8)
    rng = np.random.default_rng(6)
    x = rng.normal(size=(3, 7, cfg.d_model)).astype(np.float32)
    wg = np.asarray(params["wg"][0], np.float32)
    wgb = rng.normal(scale=0.3, size=cfg.moe_experts).astype(np.float32)
    score = 1 / (1 + np.exp(-(x.astype(np.float64) @ wg)))
    at = numpy_picks((score + wgb).reshape(21, 16), 4, 2, 4)
    w = np.take_along_axis(score.reshape(21, 16), at, -1)
    dense = np.zeros((21, 16))
    np.put_along_axis(dense, at, w / w.sum(-1, keepdims=True) * 2.5, -1)
    got = ref.route(shape, {"wg": wg, "wgb": wgb}, jnp.asarray(x))
    assert np.abs(np.asarray(got).reshape(21, 16) - dense).max() < 1e-5
    weights = {"wg": wg, "wgb": wgb, "w1": params["w1"][0],
               "w3": params["w3"][0], "w2": params["w2"][0]}
    for bad in (dict(score="softmax"), dict(groups=(3, 2)),
                dict(groups=(4, 1), top_k=8)):
        with pytest.raises(ValueError, match="group-limited"):
            moe.routed_moe(jnp.asarray(x), weights, bad.pop("top_k", 4),
                           gated=True, **{"score": "sigmoid",
                                          "groups": (4, 2), **bad})


def test_the_shares_of_four_chips_and_the_shared_expert_are_the_uncut_layer():
    """The router 16 wide in 4 groups, 4 experts a chip: each chip's routed
    part (its own experts' stacks cut out of the uncut ones) adds up, with
    the shared expert once, to the layer one chip would compute with every
    expert, and to the reference's with every expert held."""
    ref, config, cfg, mesh, _params = test_reference.tiny(NAME)
    whole = copy.deepcopy(config)
    whole["n_routed_experts"] = 16
    whole["experts_held"] = {"first": 0, "count": 16}
    ref_shape = ref.Shape.from_config(whole)
    full = program.program_config(whole)
    assert full.moe_held == (0, 16)
    params = program.init_params(
        ref, whole, program.param_shardings(whole, full, mesh), seed=11)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 9, cfg.d_model)), jnp.float32)
    comm = tfm._mesh_comm(mesh)

    def routed(c, first, count, shared):
        lp = {"ln2": params["ln2"][1], "wg": params["wg"][0],
              "wgb": params["wgb"][0],
              **{k: params[k][:, first:first + count]
                 for k in ("w1", "w3", "w2")},
              **({k: params[k][0] for k in ("sw1", "sw3", "sw2")}
                 if shared else {})}
        c = dataclasses.replace(c, moe_held=(first, count),
                                moe_shared=c.moe_shared if shared else 0)
        with mesh:
            # the layer's output: alone, or the first of what it returns
            return jax.tree.leaves(tfm._moe_ffn_tail(
                c, h, lp, comm, layer=0, residual=False))[0]

    uncut = routed(full, 0, 16, True)
    parts = sum(routed(cfg, 4 * rank, 4, rank == 0) for rank in range(4))
    assert np.abs(np.asarray(parts - uncut)).max() < 1e-5
    theirs = ref._moe_layer(
        ref_shape, {k: params[k] for k in (*ref.ROUTER_LEAVES,
                                           *ref.EXPERT_LEAVES, "ln2")},
        0, 1, h)[0] - h
    assert np.abs(np.asarray(theirs - uncut)).max() < 1e-4
    # a share is not the whole: the picks held elsewhere add nothing here
    assert np.abs(np.asarray(routed(cfg, 0, 4, True) - uncut)).max() > 0.05


# ---- the index inside the latent layer -------------------------------------

def layer_leaves(ref, params, layer=0):
    return {**{k: jnp.asarray(params[k][layer]) for k in ref.MLA_LEAVES},
            "ln1": jnp.asarray(params["ln1"][layer])}


def test_the_index_reads_the_query_latent_and_the_layers_input():
    """Its queries move with the query latent's down-projection and not
    with anything else of ``x``; its key and head weights with ``x``."""
    ref, config, cfg, _mesh, params = test_reference.tiny(NAME)
    ml = cfg.plan.mla
    lp = layer_leaves(ref, params)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 6, cfg.d_model)), jnp.float32)
    cq = jnp.asarray(rng.normal(size=(2, 6, ml.q_rank)), jnp.float32)
    at = jnp.arange(6)

    def project(x, cq):
        return sparse_index.project(cfg, lp, x, at, queries_from=cq,
                                    ix=ml.index,
                                    rotate=mla._index_rotation(ml))

    qi, ki, wi = project(x, cq)
    assert qi.shape == (2, 6, 4, 16) and ki.shape == (2, 6, 16)
    assert wi.shape == (2, 6, 4) and wi.dtype == jnp.float32
    qi2, ki2, wi2 = project(x + 1, cq)
    assert (qi2 == qi).all() and (ki2 != ki).any() and (wi2 != wi).any()
    qi3, ki3, wi3 = project(x, cq + 1)
    assert (qi3 != qi).any() and (ki3 == ki).all() and (wi3 == wi).all()
    # only the first ``rope`` elements of a head and of the key turn
    plain = sparse_index.project(cfg, lp, x, at, queries_from=cq, ix=ml.index,
                                 rotate=lambda y, _at: y)
    assert (plain[0][..., ml.rope:] == qi[..., ml.rope:]).all()
    assert (plain[1][..., ml.rope:] == ki[..., ml.rope:]).all()
    assert (plain[0][:, 1:, :, :ml.rope] != qi[:, 1:, :, :ml.rope]).any()
    assert (plain[0][:, 0] == qi[:, 0]).all()      # position 0: no turn
    # the reference's selection of the same leaves is the program's
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(ref.selection(
            ref.Shape.from_config(config), cq[0], x[0],
            {k: v for k, v in lp.items() if k != "ln1"}))
    found = sparse_index.scores(qi, wi, ki.swapaxes(1, 2))
    live = (jnp.arange(6) <= jnp.arange(6)[:, None])[None]
    ours = np.asarray(sparse_index.select(found, live, 3))[0]
    tight = dataclasses.replace(ref.Shape.from_config(config), index_topk=3)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(ref.selection(
            tight, cq[0], x[0], {k: v for k, v in lp.items() if k != "ln1"}))
    assert (ours == theirs).all() and ours.sum(-1).max() == 3


@pytest.mark.parametrize("prompt_len", [12, 19, 23, 26])
def test_prefill_then_cached_steps_are_the_references_forward(prompt_len):
    """Logits of the timed path (a prefill in slices under the selection,
    then steps against the carried latent rows and index keys) against the
    reference's full forward over what was generated; the selection binds
    (16 of up to 32 positions: in the steps alone after 12 positions, in a
    tail after 19, in a whole slice and a tail after 23 and 26)."""
    ref, config, cfg, mesh, params = test_reference.tiny(NAME)
    shape = ref.Shape.from_config(config)
    assert cfg.plan.mla.index.topk == 16 and cfg.plan.mla.index.q_slice == 4
    new = 6
    prompts = np.random.default_rng(prompt_len).integers(
        0, shape.vocab, size=(3, prompt_len)).astype(np.int32)
    tokens, kept = make_decoder(cfg, mesh, max_new=new, keep_logits=3)(
        params, prompts)
    theirs = np.asarray(ref.logits(shape, params, np.asarray(tokens))[
        :, prompt_len - 1:-1])
    err = np.abs(np.asarray(kept) - theirs).max() / theirs.std()
    assert err < 2e-4, err
    first = make_decoder(cfg, mesh, max_new=1)(params, prompts)
    assert (np.asarray(first)[:, -1] == np.asarray(tokens)[:, prompt_len]).all()
    # the selection matters at these sizes: a program that reads every row
    # is not the reference
    dropped = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, mla=dataclasses.replace(
            cfg.plan.mla, index=dataclasses.replace(
                cfg.plan.mla.index, topk=1 << 20))))
    z = jax.jit(tfm.make_forward(dropped, mesh))(params, tokens)[
        :, prompt_len - 1:-1]
    assert np.abs(np.asarray(z) - theirs).max() / theirs.std() > 0.05


def test_the_carry_is_latent_rows_and_index_keys_that_grow():
    _ref, _config, cfg, mesh, _params = test_reference.tiny(NAME)
    ml = cfg.plan.mla
    assert mla.buffers(cfg, ml, 3, 20) == (
        ((3, 20, 40), cfg.compute_dtype, 2),
        ((3, 16, 20), cfg.compute_dtype, 3))
    assert plan.grows(cfg) == (True,) * 10
    shapes = [b.shape for b in plan.carry(cfg, mesh, 3, 20)]
    assert shapes == [(1, 3, 20, 40), (1, 3, 16, 20)] * 5
    plain = program.program_config(cells.resolve(CELL_10).config)
    assert len(mla.buffers(plain, plain.plan.mla, 3, 20)) == 1


# ---- the kernels, in interpret mode ----------------------------------------

def _random(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


@pytest.mark.parametrize("t_q,t_k,k_len", [(64, 256, 256), (64, 640, 300),
                                           (32, 200, 200)])
def test_the_masked_prefill_kernel_is_the_jnp_form(t_q, t_k, k_len):
    heads, nope, rope, v_dim = 8, 128, 64, 128
    assert prefill_kernel.tiles(t_q, heads, nope, rope, v_dim)
    assert prefill_kernel.tiles(512, 128, 128, 64, 128)
    assert not prefill_kernel.tiles(t_q, 6, nope, rope, v_dim)
    assert not prefill_kernel.tiles(1024, heads, nope, rope, v_dim)
    q = _random(1, (2, t_q, heads, nope + rope))
    kv = _random(2, (2, t_k, heads, nope + v_dim))
    k_r = _random(3, (2, t_k, rope))
    mask = jax.random.bernoulli(jax.random.key(4), 0.3, (2, t_q, t_k))
    mask = mask.at[:, :, 0].set(True).at[0, 3].set(False)   # a row of none
    # keys past k_len hold large values: a kernel that read one would differ
    kv = kv.at[:, k_len:].set(1e4)
    seen = mask & (jnp.arange(t_k) < k_len)
    got = jax.jit(prefill_kernel.masked_latent_attention, static_argnums=4)(
        q, kv, k_r, mask, 0.11, k_len)
    want = prefill_kernel.jnp_form(q, kv.at[:, k_len:].set(0), k_r, seen,
                                   0.11)
    assert got.shape == (2, t_q, heads, v_dim)
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert (np.asarray(got)[0, 3] == 0).all()


@pytest.mark.parametrize("pos", [5, 1023, 1024, 2047])
def test_the_step_kernel_reads_the_chosen_rows_alone(pos):
    heads, rank, rope, block = 16, 128, 64, decode_kernel._BLOCK
    q = _random(pos, (2, heads, rank + rope))
    cache = _random(pos + 1, (2, 2 * block, rank + rope))
    chosen = jax.random.bernoulli(jax.random.key(7), 0.2, (2, 2 * block))
    chosen = chosen.at[:, pos].set(True).at[1, :block].set(False)
    f32 = jnp.float32
    s = jnp.einsum("bhc,bkc->bhk", q, cache) * 0.2
    allowed = (jnp.arange(2 * block) <= pos) & chosen
    s = jnp.where(allowed[:, None], s, -1e30)
    want = jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(s, -1),
                      cache[..., :rank], preferred_element_type=f32)
    # a sequence whose selection allows nothing yet reads as zeros
    want = jnp.where(allowed.any(-1)[:, None, None], want, 0.0)
    assert bool(allowed.any(-1).all()) == (pos >= block)
    got = jax.jit(decode_kernel.latent_decode, static_argnums=(3, 4))(
        q, cache.at[:, pos + 1:].set(1e4), pos, 0.2, rank, chosen)
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    # without a selection: every live row, as it always was
    plain = jax.jit(decode_kernel.latent_decode, static_argnums=(3, 4))(
        q, cache, pos, 0.2, rank)
    every = jnp.ones_like(chosen)
    assert np.abs(np.asarray(plain - jax.jit(
        decode_kernel.latent_decode, static_argnums=(3, 4))(
            q, cache, pos, 0.2, rank, every))).max() < 2e-6


def test_the_mixer_takes_the_kernels_where_it_is_traced_for_tpus(monkeypatch):
    """Told that it is traced for TPUs, an indexed layer of the kernels'
    widths scans its prefill's slices through one traced shape a scan and
    reads a cached step through the step kernel under the mask; both agree
    with the ``jax.numpy`` forms."""
    config = copy.deepcopy(program.tiny(cells.resolve(CELL).config))
    config["entry"]["options"].update(compute_dtype="float32", remat=None)
    config.update(num_attention_heads=4, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=128,
                  index_head_dim=128, index_topk=64, index_q_slice=32,
                  num_hidden_layers=2)
    cfg = program.program_config(config)
    ref = program.reference(config)
    mesh = program.mesh(config, jax.devices()[:1])
    params = program.init_params(
        ref, config, program.param_shardings(config, cfg, mesh), seed=3)
    prompts = np.random.default_rng(0).integers(
        0, 128, size=(2, 1024 - 4)).astype(np.int32)
    plain_tokens, plain = make_decoder(cfg, mesh, max_new=4, keep_logits=2)(
        params, prompts)
    calls = []
    for module, name in ((prefill_kernel, "masked_latent_attention"),
                         (decode_kernel, "latent_decode")):
        sound = getattr(module, name)

        def counted(*args, _sound=sound, _name=name, **kwargs):
            calls.append((_name, len(args) + len(kwargs)))
            return _sound(*args, **kwargs)

        monkeypatch.setattr(module, name, counted)
    decode_cells.tell_it_is_traced_for_tpus(monkeypatch)
    from ompi_tpu.models import decode

    decode._prefill_program.cache_clear()
    tokens, kept = make_decoder(cfg, mesh, max_new=4, keep_logits=2)(
        params, prompts)
    decode._prefill_program.cache_clear()
    assert (np.asarray(tokens) == np.asarray(plain_tokens)).all()
    assert np.abs(np.asarray(kept - plain)).max() / np.asarray(
        plain).std() < 2e-4
    # a layer's prefill: one trace of the dense slices' scan, one of the
    # selected slices', one of the tail (1020 = 31 x 32 + 28: not a whole
    # 32, so the tail takes the jnp form); a step: one call a layer
    prefill = [c for c in calls if c[0] == "masked_latent_attention"]
    steps = [c for c in calls if c[0] == "latent_decode"]
    assert len(prefill) == 2 * 2 and len(steps) == 2
    assert all(n == 6 for _name, n in steps)        # the mask handed over


# ---- what the family's entry builds ----------------------------------------

def test_mla_moe_config_still_gives_cell_10_its_plan():
    config = cells.resolve(CELL_10).config
    cfg = program.program_config(config)
    ml = cfg.plan.mla
    assert ml == mla.MLA(n_heads=16, nope=128, rope=64, v_dim=128,
                         kv_rank=512, theta=800_000.0)
    assert ml.index is None and ml.yarn is None and ml.frequencies() is None
    assert ml.scale == 192 ** -0.5
    assert cfg.moe_groups is None and cfg.moe_held is None
    assert cfg.plan.layers == (("mla", "dense"),) + (("mla", "moe"),) * 4
    assert "wiq" not in plan.leaf_names(cfg)


def test_mla_moe_config_builds_the_cell_and_refuses_what_is_not_built():
    config = cells.resolve(CELL).config
    cfg = program.program_config(config)
    ml = cfg.plan.mla
    assert (ml.n_heads, ml.q_rank, ml.kv_rank, ml.cached) == (128, 1536, 512,
                                                              576)
    assert ml.index == sparse_index.SparseIndex(n_heads=64, head_dim=128,
                                                topk=2048, q_slice=512)
    assert ml.yarn == mla.Yarn(factor=40.0, original=4096, beta_fast=32.0,
                               beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert cfg.moe_groups == (8, 4) and cfg.moe_held == (0, 8)
    assert cfg.moe_experts == 256 and cfg.moe_top_k == 8
    assert cfg.plan.layers == (("mla", "dense"),) + (("mla", "moe"),) * 4
    sizes = {arg: config[key] for arg, key in config["entry"]["sizes"].items()}
    options = config["entry"]["options"]
    for change, said in (
            (dict(moe_layer_freq=2), "moe_layer_freq 2"),
            (dict(scoring_func="softmax"), "scoring_func 'softmax'"),
            (dict(topk_method="greedy"), "topk_method 'greedy'"),
            (dict(rope_scaling={"type": "linear", "factor": 4}),
             "rope_scaling"),
            (dict(rope_scaling={**config["rope_scaling"], "mscale": 0.5}),
             "mscale"),
            (dict(q_lora_rank=None), "an index without q_lora_rank"),
            (dict(run_nextn_predict=True), "num_nextn_predict_layers")):
        with pytest.raises(ValueError, match=said):
            plan.mla_moe_config(**{**sizes, **change}, **options)
    # the module is there to be refused only where it is asked to run
    assert plan.mla_moe_config(**{**sizes, "num_nextn_predict_layers": 0,
                                  "run_nextn_predict": True}, **options)
