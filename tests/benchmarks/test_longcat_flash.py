"""``longcat-flash-chat``: the yardstick's arithmetic at the real sizes against
counts made by hand, the cut as the configuration's file states it, what the
declared draw of the router and of its selection bias does to a token's
picks, and the reference's counters.  Shapes and numpy only: no device
metric."""

import math

import jax
import numpy as np

from benchmarks.lib import cells, program

CELL = "longcat-flash-chat.decode-896-128-b160"
D, V, H = 6144, 16_384, 64
MLA = (D * 1536 + 1536 + 1536 * H * 192 + D * 576 + 512 + 512 * H * 256
       + H * 128 * D)
DENSE = 3 * D * 12288
EXPERT = 3 * D * 2048
ROUTER = D * 768 + 768
N_PARAMS = (8 * (MLA + DENSE) + 4 * (ROUTER + 16 * EXPERT) + 2 * 8 * D + D
            + 2 * V * D)
B, TP, NEW = 160, 896, 128


def job():
    cell = cells.resolve(CELL)
    made = cell.runner.build(cell.config, cell.traffic,
                             jax.devices()[:cell.chips])
    table = program.param_table(made.reference, cell.config)
    made.n_params = sum(math.prod(dims) for dims, _std in table.values())
    return cell, made


def test_the_parameters_by_kind_are_the_hand_counts():
    _cell, made = job()
    assert MLA == 90_572_800                        # ISSUE 58: 90.57M
    assert DENSE == 226_492_416                     # 226.49M
    assert ROUTER == 4_719_360                      # 4.72M, the bias in it
    assert 16 * EXPERT == 603_979_776               # 16 x 37.75M
    assert 2 * (MLA + DENSE) + ROUTER == 638_849_792    # 638.9M a layer
    assert made.n_params == N_PARAMS == 5_172_749_312   # 10.35 GB in bfloat16


def test_facts_are_the_hand_counts():
    _cell, made = job()
    facts = made.facts()
    # what one token multiplies here: no norm's scale and no selection bias;
    # of its 12 picks 8 are experts on the mean and 16 of 512 of those here
    here = 12 * 16 / 768
    assert here == 0.25
    active = (8 * (MLA - 1536 - 512 + DENSE)
              + 4 * (D * 768 + round(here * EXPERT)) + V * D)
    assert facts["counts"]["active_params"] == active == 2_693_791_744
    assert facts["counts"]["attention_width"] == H * (192 + 128) // 2
    assert facts["counts"]["kv_elements"] == 576
    assert facts["counts"]["attention_layers"] == 8
    assert facts["counts"]["lookup_params"] == V * D == 100_663_296
    assert facts["prefill_flops"] == B * TP * (
        2 * (active - V * D) + 4 * 8 * 10_240 * TP) + B * 2 * V * D
    # all but the lookup table, and the live latent rows at 960 positions in
    # both of a layer's caches
    assert facts["decode_step_bytes"] == (
        2 * (N_PARAMS - V * D) + 8 * B * (TP + NEW // 2) * 576 * 2
    ) == 11_559_749_632                             # ISSUE 58: about 11.8 GB
    assert facts["counts"]["routed"] == {
        "layers": 4, "experts": 16, "top_k": 1, "d_model": D,
        "d_expert": 2048}


def test_the_cut_is_depth_experts_held_and_vocabulary():
    cell, _made = job()
    config, row = cell.config, next(
        c for c in cells.load_benchmark()["configs"]
        if c["name"] == "longcat-flash-chat")
    assert row["reduced"] == config["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131_072,
        "torch_dtype": "bfloat16"}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16_384)
    assert config["experts_held"] == {"first": 0, "count": 16}
    # every width as published, and the router as wide as published
    assert (config["hidden_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"],
            config["q_lora_rank"], config["ffn_hidden_size"],
            config["expert_ffn_hidden_size"], config["router_experts"],
            config["zero_expert_num"], config["moe_topk"],
            config["routed_scaling_factor"], config["rope_theta"],
            config["mla_scale_q_lora"], config["mla_scale_kv_lora"]) == (
        6144, 64, 128, 64, 128, 512, 1536, 12_288, 2048, 512, 256, 12, 6,
        10_000_000, True, True)
    cfg = program.program_config(config)
    assert cfg.moe_experts == 768 and cfg.moe_zero == 256
    assert cfg.moe_held == (0, 16) and cfg.moe_top_k == 12
    assert cfg.moe_score == "softmax" and not cfg.moe_norm_topk
    assert cfg.n_layers == 8 and len(cfg.plan.branches) == 4
    assert cfg.plan.mla.q_scale == 2.0
    assert cfg.plan.mla.kv_scale == 12 ** 0.5
    assert (cell.traffic["batch"], cell.traffic["prompt_len"],
            cell.traffic["max_new"], cell.chips) == (B, TP, NEW, 1)
    assert (TP + NEW) % 1024 == 0       # one block of ops/latent_decode.py
    assert config["counters"]["sequences_a_step"] == B
    for key in ("published", "reduced_why", "deployment", "departures",
                "check_why", "tiny_why", "assumed"):
        assert config[key], key
    said = " ".join(config["assumed"])
    for word in ("shortcut", "identity experts", "selection bias",
                 "norm_topk_prob", "Seeded weights"):
        assert word in said, word


def test_the_declared_router_weighs_a_token_about_two_and_a_half():
    """Twelve of 768 softmax probabilities at logits of deviation
    ROUTER_SPREAD, times 6: what a token's picks weigh together, a third of
    them identity experts, one in 48 held here; and the declared bias moves
    about one pick of the twelve."""
    ref = program.reference(cells.resolve(CELL).config)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1024, D))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    wg = rng.normal(scale=ref.ROUTER_SPREAD * D ** -0.5, size=(D, 768))
    logit = x @ wg
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    bias = rng.normal(scale=ref.BIAS, size=768)
    plain = np.argsort(-p, -1)[:, :12]
    biased = np.argsort(-(p + bias), -1)[:, :12]
    together = 6 * np.take_along_axis(p, biased, -1).sum(-1).mean()
    assert 2.0 < together < 3.0, together
    moved = np.mean([len(set(a) - set(b)) for a, b in zip(plain, biased)])
    assert 0.6 < moved < 1.6, moved
    assert 0.30 < (biased >= 512).mean() < 0.37
    assert 0.015 < (biased < 16).mean() < 0.027


def test_the_counters_are_shares_of_the_picks():
    ref = program.reference(cells.resolve(CELL).config)
    shape = ref.Shape.from_config(cells.resolve(CELL).config)
    weight = np.zeros((1, 4, 768), np.float32)
    # 4 tokens x 12 picks: 16 identity, 2 on the held experts, 30 elsewhere
    for t in range(4):
        weight[0, t, 512:516] = 0.1
        weight[0, t, 100 + t:108 + t] = 0.1
    weight[0, 0, 100:102] = 0
    weight[0, 0, 3:5] = 0.1
    got = ref.counters(shape, [weight])
    assert got["moe_identity_pick_share"] == 16 / 48
    assert got["moe_held_pick_share"] == 2 / 48
    assert got["moe_empty_group_share"] == (1 - 2 / 48 / 16) ** (160 * 12)
    after = ref.counters(shape, [weight], first=1)
    assert after["moe_held_pick_share"] == 0
    assert after["moe_empty_group_share"] == 1.0
    # the deployment's expectation: 1 / 48 of the picks, 8% of the pairs
    assert 0.08 < (1 - 1 / 768) ** (160 * 12) < 0.083
